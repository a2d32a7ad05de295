"""model: milliseconds a step spends in the normalisation layers:
instructions Flax names ``ln_attn``, ``ln_mlp`` or ``ln_final``, both
directions. A norm fused into a neighbour's fusion counts with the
neighbour (``benchmarks/scopes.py``: an instruction's own ``op_name``)."""

from benchmarks import scopes


def read(run):
    return scopes.read(run, lambda phase, layer, kernel:
                       layer == "norm")
