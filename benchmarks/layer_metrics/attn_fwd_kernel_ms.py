"""kernels: milliseconds a step spends in the flash-attention forward
kernel: the Mosaic calls of ``ops/pallas_attention._flash_core``, found
as the calls under the Flax module ``attn`` in the forward phase
(``benchmarks/scopes.kernel_kind``; the kernels carry no names of
their own, and why). With the dq and dkv kernels' it adds up to
``attn_kernel_ms``."""

from benchmarks import scopes


def read(run):
    return scopes.read(run, lambda phase, layer, kernel:
                       kernel == scopes.KERNEL_FWD)
