"""model: milliseconds a step spends in the state-space mixers'
recurrence, both directions: instructions named by the scope
``mamba_scan`` (``horovod_tpu/utils/scopes.MAMBA_SCAN``): everything
from x, dt, B, C to y (a * dt and its cumulative sums, the decay
tiles, the products inside a chunk, the states between chunks, D x).
Under ``remat`` a rematerialised block's second run counts as
backward. Nothing on a program that has no such scope, or a model with
no such layer."""

from benchmarks import scopes


def read(run):
    scope = getattr(scopes.program, "MAMBA_SCAN", None)
    return (scope and scopes.read(
        run, lambda phase, layer, kernel: layer == scope)) or None
