"""Model-FLOPs-utilization accounting.

The reference reports raw images/sec (docs/benchmarks.rst:40); on TPU the
meaningful denominator is the chip's peak matmul throughput, so benchmarks
here also report MFU = achieved model FLOP/s / peak bf16 FLOP/s. Peak
numbers are the published per-chip bf16 figures for each TPU generation.
"""

from __future__ import annotations

from typing import Optional

# published peak bf16 TFLOP/s per chip
_PEAK_TFLOPS = {
    "v3": 123.0,
    "v4": 275.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6e": 918.0,
}


def _generation(device_kind: str) -> str:
    """TPU generation key for a PJRT ``device_kind`` string ("TPU v3",
    "TPU v4", "TPU v5 lite", "TPU v5p", "TPU v6 lite" — the lite suffix
    marks the e variants); "" for a device the table does not know."""
    kind = device_kind.lower()
    if "tpu" not in kind:
        return ""
    for version in ("v6", "v5", "v4", "v3"):
        if version in kind:
            if version in ("v5", "v6"):
                return version + ("e" if "lit" in kind else "p")
            return version
    return ""


def peak_flops_per_chip(device_kind: Optional[str] = None) -> float:
    """Peak bf16 FLOP/s of one chip of ``device_kind`` (default: the
    kind of ``jax.devices()[0]``). A device the table does not know —
    every CPU world included — raises ValueError: an MFU against an
    assumed peak is not a measurement."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    gen = _generation(device_kind)
    if gen not in _PEAK_TFLOPS:
        raise ValueError(
            f"no published peak FLOP/s for device kind {device_kind!r}; "
            f"MFU is only defined on {sorted(_PEAK_TFLOPS)} TPUs")
    return _PEAK_TFLOPS[gen] * 1e12


def tpu_peak_or_none():
    """Peak bf16 FLOP/s of the chip this process runs on, None on any
    platform but a TPU — the one place that decides where an MFU may be
    reported. An unknown TPU generation still raises."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    return peak_flops_per_chip(dev.device_kind)


def mfu_or_none(flops_per_sec_per_chip: float):
    """``flops / peak`` on a TPU, None ("not measured") elsewhere."""
    peak = tpu_peak_or_none()
    return None if peak is None else flops_per_sec_per_chip / peak


def format_mfu(mfu) -> str:
    return "MFU not measured" if mfu is None else f"MFU {mfu:.1%}"


def transformer_train_flops(n_params: int, tokens: int) -> float:
    """Training FLOPs for a dense transformer: the standard 6·N·D
    estimate (fwd 2ND + bwd 4ND), N = non-embedding ≈ total params for
    the sizes benchmarked here."""
    return 6.0 * float(n_params) * float(tokens)


# per-image forward multiply-accumulates at each model's native
# resolution (published GMAC counts: torchvision/ptflops tables); one
# MAC = 2 FLOPs on the MXU, matching the transformer 6·N·D convention
_CNN_FWD_MACS = {
    "resnet50": (4.1e9, 224),
    "resnet101": (7.8e9, 224),
    "resnet152": (11.5e9, 224),
    "inception3": (5.7e9, 299),
    "vgg16": (15.5e9, 224),
}


def cnn_train_flops(model: str, images: int, image_size: int) -> float:
    """Training FLOPs (fwd MACs ×2 FLOPs/MAC ×3 for fwd+bwd) for the
    synthetic-benchmark CNN family, scaled from each model's native
    resolution."""
    macs, native = _CNN_FWD_MACS[model]
    return 3.0 * 2.0 * macs * (image_size / native) ** 2 * float(images)


def resnet50_train_flops(images: int, image_size: int = 224) -> float:
    """Deprecated alias for ``cnn_train_flops("resnet50", ...)``; kept
    for callers of the pre-r3 helper. Note the accounting change: since
    r3 a MAC counts 2 FLOPs (earlier rounds counted 1), so values are 2x
    the pre-r3 helper's."""
    return cnn_train_flops("resnet50", images, image_size)


def count_params(tree) -> int:
    import jax

    return sum(x.size for x in jax.tree_util.tree_leaves(tree))
