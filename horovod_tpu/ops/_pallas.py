"""The one rule for how this repo's Pallas kernels run."""

import jax


def interpret() -> bool:
    """``interpret=`` for every ``pl.pallas_call`` here: compiled by
    Mosaic on TPU, interpreted only on the CPU test mesh (the same
    kernel bodies, bitwise-testable). Any other backend is an error —
    an accelerator must never run these kernels interpreted unnoticed."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise NotImplementedError(
            f"horovod_tpu's Pallas kernels are TPU kernels; backend "
            f"{backend!r} can neither compile nor test them")
    return backend == "cpu"
