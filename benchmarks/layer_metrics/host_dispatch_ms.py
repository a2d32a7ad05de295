"""trainer loop: median milliseconds the host spends in one call of the
compiled step until it returns (enqueue, not execution), over the
untraced window's calls."""

import statistics


def read(run):
    calls = run.spans_in_window("step_call")
    return 1e3 * statistics.median(calls) if calls else None
