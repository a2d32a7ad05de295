"""kernels: least time the chip could take for the step's attention
(``benchmarks/flops.py``: required operations over peak FLOP/s or least
bytes over peak HBM bytes/s, whichever is larger) over the measured
``attn_kernel_ms``, the Mosaic calls of layer ``attn`` alone. The
harness logs which peak bounds it."""

from benchmarks import flops, harness
from benchmarks.layer_metrics import attn_kernel_ms


def read(run):
    measured_ms = attn_kernel_ms.read(run)
    if not measured_ms:
        return None
    work = flops.attention_kernel_work(run.model_sizes, run.traffic)
    least_s, bound = flops.roofline_seconds(
        work, harness.peak_of(run.device_kind))
    run.log(f"attention roofline: {work['flops']:.4g} operations, "
            f"{work['bytes']:.4g} bytes a step and chip, least "
            f"{1e3 * least_s:.3f} ms ({bound}-bound) against "
            f"{measured_ms:.3f} ms measured")
    return 100.0 * 1e3 * least_s / measured_ms
