"""collectives + schedule: milliseconds a step has a collective in
flight (union over the step, worst device, median over traced steps)."""


def read(run):
    return run.reduced_trace.get("collective_ms")
