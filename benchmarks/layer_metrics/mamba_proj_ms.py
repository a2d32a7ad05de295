"""model: milliseconds a step spends in the state-space mixers' two
projections (``in_proj``, ``out_proj`` of ``models/mamba.Mamba2Mixer``),
both directions: instructions named by the scope ``mamba_proj``
(``horovod_tpu/utils/scopes.MAMBA_PROJ``). A fusion counts under its
own ``op_name`` (``benchmarks/scopes.py``): at one chip AdamW rides in
the weight-gradient products' fusions and counts here with them.
Nothing on a program that has no such scope, or a model with no such
layer."""

from benchmarks import scopes


def read(run):
    scope = getattr(scopes.program, "MAMBA_PROJ", None)
    return (scope and scopes.read(
        run, lambda phase, layer, kernel: layer == scope)) or None
