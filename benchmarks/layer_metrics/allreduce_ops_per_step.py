"""optimizer wrap + fusion: all-reduce instructions in the compiled
train step (the scalar loss ``psum`` included)."""

from benchmarks import hlo

PLATFORM_FREE = True  # a count from the HLO text


def read(run):
    return float(len(hlo.allreduces(run.hlo_text))) if run.hlo_text \
        else None
