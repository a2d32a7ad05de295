"""model: least time the chip could take for the step's state-space
projections over the measured ``mamba_proj_ms``.

Operations and least bytes as ``attn_proj_roofline`` counts attention's:
the two products of ``benchmarks/layer_kinds/mamba2.products`` (the
input projection to gate, stream, B, C and dt; the output projection),
two operations a multiply-add a position, over the step's positions and
the ``mamba2`` layers, in three passes, each product's two operands and
result once in bf16 a pass. Under ``remat`` the forward products of a
rematerialised block run twice: the reading cannot pass about three
quarters there. Nothing for a model with no ``mamba2`` layer."""

from benchmarks import flops
from benchmarks.layer_metrics import attn_proj_roofline, mamba_proj_ms

KIND = "mamba2"


def work(model: dict, traffic: dict):
    layers = flops.layers_by_kind(model).get(KIND)
    if not layers:
        return None
    weights = flops.load_kind(KIND).products(model)
    return attn_proj_roofline.dense_work(
        sum(k * n for k, n in weights), weights,
        attn_proj_roofline.positions_per_step(traffic), layers)


def read(run):
    measured_ms = mamba_proj_ms.read(run)
    found = work(run.model_sizes, run.traffic) if measured_ms else None
    if not found:
        return None
    return attn_proj_roofline.share(
        run, "state-space projections'", found, measured_ms)
