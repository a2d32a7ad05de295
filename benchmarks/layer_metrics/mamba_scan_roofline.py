"""kernels: least time the chip could take for the step's state-space
recurrences over the measured ``mamba_scan_ms``.

``benchmarks/layer_kinds/mamba2.kernel_work`` (the recurrence's update
and readout in three passes; x, dt, B, C, y and their gradients each
moved once in bf16: the same work whatever implements it) times the
``mamba2`` layers, the larger of operations over peak FLOP/s and bytes
over peak HBM bytes/s, over the measured time. The scan is plain XLA
today, so this reads the whole scope ``mamba_scan`` and not a kernel's
calls; under ``remat`` the forward scan of a rematerialised block runs
again. Nothing for a model with no ``mamba2`` layer."""

from benchmarks import flops
from benchmarks.layer_metrics import attn_proj_roofline, mamba_scan_ms

KIND = "mamba2"


def read(run):
    measured_ms = mamba_scan_ms.read(run)
    layers = flops.layers_by_kind(run.model_sizes).get(KIND)
    if not measured_ms or not layers:
        return None
    one = flops.load_kind(KIND).kernel_work(run.model_sizes, run.traffic)
    work = {key: float(layers * one[key]) for key in ("flops", "bytes")}
    return attn_proj_roofline.share(
        run, "state-space recurrences'", work, measured_ms)
