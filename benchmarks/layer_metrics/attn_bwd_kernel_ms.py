"""kernels: milliseconds a step spends in the flash-attention backward
kernel (``ops/pallas_attention._flash_bwd_kernel``: dq, dk and dv from
one call): the Mosaic calls the program names ``flash_bwd``
(``horovod_tpu/utils/scopes.FLASH_BWD``, ``benchmarks/kernel_names.py``),
and no forward kernel a rematerialised block runs again, which
``attn_bwd_dkv_kernel_ms`` takes in. Nothing on a program whose
kernels have no names."""

from benchmarks import kernel_names, scopes


def read(run):
    name = getattr(scopes.program, "FLASH_BWD", None)
    return name and kernel_names.read(
        run, lambda phase, layer, kernel: kernel == name)
