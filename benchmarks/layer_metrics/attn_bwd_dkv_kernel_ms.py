"""kernels: milliseconds a step spends in the flash-attention backward
kernel for dk and dv (``_flash_bwd_dkv_kernel``): the Mosaic calls
under ``attn`` in the backward phase that return a pair
(``benchmarks/scopes.kernel_kind``)."""

from benchmarks import scopes


def read(run):
    return scopes.read(run, lambda phase, layer, kernel:
                       kernel == scopes.KERNEL_DKV)
