"""model: milliseconds a step spends in what a state-space mixer does
beside its projections and its recurrence, both directions:
instructions named by the scopes ``mamba_conv`` (the causal depthwise
convolution, silu, the splits, dt's softplus) and ``mamba_gate`` (the
gate and the norm over the inner width)
(``horovod_tpu/utils/scopes.MAMBA_CONV``, ``MAMBA_GATE``). Nothing on a
program that has no such scopes, or a model with no such layer."""

from benchmarks import scopes


def read(run):
    names = {getattr(scopes.program, "MAMBA_CONV", None),
             getattr(scopes.program, "MAMBA_GATE", None)} - {None}
    return (names and scopes.read(
        run, lambda phase, layer, kernel: layer in names)) or None
