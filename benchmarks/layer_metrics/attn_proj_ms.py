"""model: milliseconds a step spends in attention's four projections
(``query``, ``key``, ``value``, ``out`` of ``models/transformer.Attention``),
both directions: instructions named by the scope ``attn_proj``
(``horovod_tpu/utils/scopes.ATTN_PROJ``). A fusion counts under its
own ``op_name`` (``benchmarks/scopes.py``): at one chip AdamW rides in
the weight-gradient products' fusions and counts here with them.
Nothing on a program that has no such scope."""

from benchmarks import scopes


def read(run):
    scope = getattr(scopes.program, "ATTN_PROJ", None)
    return scope and scopes.read(
        run, lambda phase, layer, kernel: layer == scope)
