"""model: milliseconds a step spends from the final hidden state to
the loss and back, fused or dense: instructions named by the scope
``loss_head`` (``ops/fused_cross_entropy.py``; the logits branch of
``Transformer.__call__``, ``causal_lm_loss`` and ``mlm_loss`` in
``models/transformer.py``), both directions."""

from benchmarks import scopes


def read(run):
    return scopes.read(run, lambda phase, layer, kernel:
                       layer == scopes.program.LOSS_HEAD)
