"""optimizer wrap + fusion: MiB that the compiled train step's
all-reduce instructions reduce, per chip and step."""

from benchmarks import harness, hlo

PLATFORM_FREE = True  # a count from the HLO text


def read(run):
    if not run.hlo_text:
        return None
    return sum(hlo.allreduces(run.hlo_text)) / harness.MIB
