"""kernels: least time the chip could take for the step's expert
products over the measured ``moe_experts_ms``.

The count is of the **expected** load, as ``benchmarks/flops.mlp_macs``
counts a routed layer: under even routing a position sends
``experts_per_token * experts_held / num_experts`` rows to the experts
held here, whatever the routing of a run was and whatever rows the
program's products ran over (a program whose first product is sized
for the expected rows never runs fewer, so the reading cannot pass
100%; a run that routing sent more rows reads lower). Operations: the three
products of a SwiGLU expert (gate and up at hidden x width, down at
width x hidden), forward and the two backward products of each,
2·rows·hidden·width apiece. Least bytes: each product's two operands
and its result moved once in bf16, in each of its three passes. The
larger of operations over peak FLOP/s and bytes over peak HBM bytes/s,
over the measured time."""

from benchmarks import flops, harness
from benchmarks.layer_metrics import moe_experts_ms


def expert_work(model: dict, traffic: dict) -> dict:
    """Required operations and least bytes of one training step's
    expert products on one chip, at the expected load."""
    experts = model.get("num_experts", 0)
    held = model.get("experts_held", experts)
    positions = (traffic["batch_per_chip"] * traffic["seq_len"]
                 * flops.positions_per_token(traffic))
    rows = positions * model["experts_per_token"] * held / experts
    h = model["hidden_size"]
    width = model.get("expert_mlp_dim") or int(h * model["mlp_ratio"])
    matrices = 3 if model.get("activation") == "swiglu" else 2
    layers = model["num_layers"] - model.get("dense_layers", 0)
    passes = 3  # forward, and backward into the rows and the weights
    per_product = rows * (h + width) + held * h * width
    return {
        "flops": float(layers * passes * matrices * 2 * rows * h * width),
        "bytes": float(layers * passes * matrices * per_product
                       * flops.BF16_BYTES)}


def read(run):
    measured_ms = moe_experts_ms.read(run)
    if not measured_ms or not run.model_sizes.get("num_experts"):
        return None
    work = expert_work(run.model_sizes, run.traffic)
    least_s, bound = flops.roofline_seconds(
        work, harness.peak_of(run.device_kind))
    run.log(f"expert products' roofline at the expected load: "
            f"{work['flops']:.4g} operations, {work['bytes']:.4g} bytes a "
            f"step and chip, least {1e3 * least_s:.3f} ms ({bound}-bound) "
            f"against {measured_ms:.3f} ms measured")
    return 100.0 * 1e3 * least_s / measured_ms
