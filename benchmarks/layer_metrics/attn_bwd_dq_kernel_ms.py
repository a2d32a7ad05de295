"""kernels: milliseconds a step spends in the flash-attention backward
kernel for dq (``_flash_bwd_dq_kernel``): the Mosaic calls under
``attn`` in the backward phase that return one array
(``benchmarks/scopes.kernel_kind``)."""

from benchmarks import scopes


def read(run):
    return scopes.read(run, lambda phase, layer, kernel:
                       kernel == scopes.KERNEL_DQ)
