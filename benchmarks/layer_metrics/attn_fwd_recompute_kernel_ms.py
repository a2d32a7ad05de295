"""kernels: milliseconds a step spends running the flash-attention
forward kernel a second time: the Mosaic calls the program names
``flash_fwd`` (``horovod_tpu/utils/scopes.FLASH_FWD``,
``benchmarks/kernel_names.py``) whose phase is backward, which is where
a block under ``remat`` runs its forward again. Exactly 0 where nothing
is rematerialised. With ``attn_bwd_kernel_ms`` it adds up to what
``attn_bwd_dkv_kernel_ms`` reads. Nothing on a program whose kernels
have no names."""

from benchmarks import kernel_names, scopes


def read(run):
    name = getattr(scopes.program, "FLASH_FWD", None)
    return name and kernel_names.read(
        run, lambda phase, layer, kernel:
        kernel == name and phase == "backward")
