"""device: ``memory_stats()["peak_bytes_in_use"]``, largest over
devices: live buffers only; on this runtime it leaves out a running
program's temporaries, which ``step_hbm_gib`` contains."""

from benchmarks import harness


def read(run):
    if run.rehearse or not run.memory_stats_peak:
        return None
    return run.memory_stats_peak / harness.GIB
