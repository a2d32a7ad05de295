"""kernels: least time the chip could take for the attention of the
step's ``window_attention`` layers over the measured
``attn_window_kernel_ms``.

``benchmarks/layer_kinds/window_attention.kernel_work`` (the six
products over the pairs the window shows, the twelve arrays each moved
once in bf16: the same work whatever implements it) times the window
layers, the larger of operations over peak FLOP/s and bytes over peak
HBM bytes/s, over the measured time of those layers' Mosaic calls. The
work is the window's pairs, so kernels that run the tiles of the causal
range read low and none can read over 100%; under ``remat`` the forward
kernel of a rematerialised block runs again. Nothing for a model with
no such layer."""

from benchmarks import flops
from benchmarks.layer_metrics import (
    attn_proj_roofline, attn_window_kernel_ms)


def read(run):
    measured_ms = attn_window_kernel_ms.read(run)
    layers = len(attn_window_kernel_ms.window_layers(run.model_sizes))
    if not measured_ms or not layers:
        return None
    one = flops.load_kind(attn_window_kernel_ms.KIND).kernel_work(
        run.model_sizes, run.traffic)
    work = {key: float(layers * one[key]) for key in ("flops", "bytes")}
    return attn_proj_roofline.share(
        run, "window attention kernels'", work, measured_ms)
