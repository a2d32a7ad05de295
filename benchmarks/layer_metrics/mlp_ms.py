"""model: milliseconds a step spends in the dense MLP, both directions:
instructions Flax names ``mlp`` (``models/transformer.Mlp``: ``fc1``,
the activation, ``fc2``; ``gate``, ``up``, ``down`` under SwiGLU) and
no scope of the program's names more closely. A fusion counts under
its own ``op_name`` (``benchmarks/scopes.py``): at one chip AdamW rides
in the weight-gradient products' fusions and counts here with them, and
the next norm's statistics in ``fc2``'s epilogue. Listed for the dense
cells: in a routed model layer ``mlp`` is only what ``RoutedMlp`` does
outside its two scopes."""

from benchmarks import scopes

LAYER = "mlp"


def read(run):
    return scopes.read(run, lambda phase, layer, kernel: layer == LAYER)
