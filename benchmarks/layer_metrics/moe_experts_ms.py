"""model: milliseconds a step spends in the routed MLP's three expert
products, both directions (``horovod_tpu/models/moe.py``): the
instructions named by the scope ``moe_experts`` (the products'
epilogues and casts), and the products themselves. The TPU compiler
replaces each ``lax.ragged_dot`` by a Mosaic call of its own, named
``ragged-dot-none.<n>`` with ``op_name="ragged-dot-none"``: the
program's scope is lost on it (``benchmarks/scopes.py`` counts it under
phase ``optimizer``, layer ``other``), so those calls are found by that
stem among the step's Mosaic calls (``trace.reduce``). Nothing on a
program that has no such scope."""

from benchmarks import scopes

# the stem the TPU compiler gives the calls it makes of a ragged dot
RAGGED_DOT_STEM = "ragged-dot-none"


def read(run):
    scope = getattr(scopes.program, "MOE_EXPERTS", None)
    named = scope and scopes.read(
        run, lambda phase, layer, kernel: layer == scope)
    if named is None:
        return None
    by_stem = (run.reduced_trace or {}).get("kernel_ms_by_stem", {})
    return named + by_stem.get(RAGGED_DOT_STEM, 0.0)
