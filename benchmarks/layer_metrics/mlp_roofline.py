"""model: least time the chip could take for the step's dense MLPs
over the measured ``mlp_ms``.

Operations: ``benchmarks/flops.mlp_macs(model)[0]`` (a plain MLP, what
``mfu_pct`` counts as its part of ``blocks``) a position and layer with
a plain MLP, counted and bounded as ``attn_proj_roofline`` counts the
projections: three passes, each product's two operands and result once
in bf16. At one chip the measured time holds AdamW, which rides in the
weight-gradient products' fusions, so the products alone run nearer
their peak than this reads. Nothing for a model none of whose layers
has a plain MLP."""

from benchmarks import flops
from benchmarks.layer_metrics import attn_proj_roofline, mlp_ms


def products(model: dict) -> list:
    """``(rows in, columns out)`` of a plain MLP's matrices."""
    h = model["hidden_size"]
    width = int(h * model["mlp_ratio"])
    up = 2 if model.get("activation") == "swiglu" else 1
    return [(h, width)] * up + [(width, h)]


def read(run):
    measured_ms = mlp_ms.read(run)
    model = run.model_sizes
    layers = model.get("dense_layers", 0) \
        if model.get("num_experts", 0) else model["num_layers"]
    if not measured_ms or not layers:
        return None
    work = attn_proj_roofline.dense_work(
        flops.mlp_macs(model)[0], products(model),
        attn_proj_roofline.positions_per_step(run.traffic), layers)
    return attn_proj_roofline.share(run, "dense MLPs'", work, measured_ms)
