"""collectives + schedule: the part of ``collective_ms`` during which
no other instruction runs on that device: what the step pays."""


def read(run):
    return run.reduced_trace.get("exposed_collective_ms")
