"""model: milliseconds a step keeps the device busy (union of all
instructions, worst device, median over traced steps)."""


def read(run):
    return run.reduced_trace.get("device_busy_ms")
