"""device: share of the traced steps' span (first step's beginning to
last step's end) in which no instruction ran, worst device."""


def read(run):
    return run.reduced_trace.get("device_idle_pct")
