"""kernels: milliseconds a step spends in the flash-attention forward
and backward kernels, summed, worst device, median over traced steps.

The rule: a device event counts here if its instruction carries
``tpu_custom_call`` in the compiled step's HLO (a Mosaic call) AND the
layer of its ``op_name`` is ``attn``: it was traced under the Flax
module ``attn`` (``benchmarks/scopes.kernel_layers``), whatever its
instruction is called, so a ``name=`` on a ``pallas_call`` does not
hide it. Another Mosaic kernel in the step (a Pallas norm, the fused
cross entropy) is of another layer and is not counted here: it shows in
``mosaic_kernel_ms``, which counts them all."""

LAYER = "attn"


def read(run):
    return run.reduced_trace.get("kernel_ms_by_layer", {}).get(LAYER)
