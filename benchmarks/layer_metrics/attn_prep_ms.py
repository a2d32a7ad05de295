"""model: milliseconds a step spends on what attention does that is
neither a projection nor a kernel, both directions: the q/k norms,
rope, and around the two flash calls the transposes into and out of
the kernels' layout, pads and slices, delta, casts and the sum of
partial dk/dv over a group's query heads; instructions named by the
scope ``attn_prep`` (``horovod_tpu/utils/scopes.ATTN_PREP``; set in
``models/transformer.Attention`` and ``ops/pallas_attention.py``).
Under ``remat`` one pass stays outside it, in layer ``attn``:
``jax.checkpoint``'s copy of the last block's kept flash output, named
for the call that made it (``.../attn/flash_fwd/reduce_precision``,
0.41 ms a step in ``sdar_bd_s4096``); it is no unscoped layout work.
Nothing on a program that has no such scope."""

from benchmarks import scopes


def read(run):
    scope = getattr(scopes.program, "ATTN_PREP", None)
    return scope and scopes.read(
        run, lambda phase, layer, kernel: layer == scope)
