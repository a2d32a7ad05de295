"""kernels: milliseconds a step spends in the flash-attention forward
and backward kernels, summed, worst device, median over traced steps.

The rule: a device event counts here if its instruction carries
``tpu_custom_call`` in the compiled step's HLO (a Mosaic call) AND its
stem is one of STEMS. ``ops/pallas_attention.py`` gives its three
``pallas_call``s no ``name=``, so all three are ``attn.<n>`` after the
function that holds them. Another Mosaic kernel in the step (a Pallas
norm, the fused cross entropy) has another stem and is not counted
here: it shows in ``mosaic_kernel_ms``, which counts them all."""

STEMS = ("attn",)


def read(run):
    by_stem = run.reduced_trace.get("kernel_ms_by_stem", {})
    found = [by_stem[s] for s in STEMS if s in by_stem]
    return sum(found) if found else None
