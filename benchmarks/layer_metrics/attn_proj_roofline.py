"""model: least time the chip could take for the step's attention
projections over the measured ``attn_proj_ms``.

Operations: ``benchmarks/flops.projection_macs`` (what ``mfu_pct``
counts as the projections' part of ``blocks``) a position and layer,
two operations a multiply-add, in three passes: forward, and backward
into the activations and into the weights. Least bytes: each product's
two operands and its result moved once in bf16, in each of its three
passes. The larger of operations over peak FLOP/s and bytes over peak
HBM bytes/s, over the measured time. Under ``remat`` the forward
products of a rematerialised block run twice, four passes' time for
three passes' work: the reading cannot pass about three quarters
there."""

from benchmarks import flops, harness
from benchmarks.layer_metrics import attn_proj_ms


def products(model: dict):
    """``(rows in, columns out)`` of each projection's weight, as
    ``flops.projection_macs`` counts them; None for latent attention
    (``kv_lora_rank``), which no cell runs: the configuration that
    brings it brings its products."""
    if "kv_lora_rank" in model:
        return None
    h, heads = model["hidden_size"], model["num_heads"]
    kv, qk, v = (flops.kv_heads(model), flops.qk_head_dim(model),
                 flops.v_head_dim(model))
    return [(h, heads * qk), (h, kv * qk), (h, kv * v), (heads * v, h)]


def positions_per_step(traffic: dict) -> int:
    return (traffic["batch_per_chip"] * traffic["seq_len"]
            * flops.positions_per_token(traffic))


def dense_work(macs: int, weights: list, positions: int,
               layers: int) -> dict:
    """Required operations and least bytes of ``layers`` layers' dense
    products over ``positions`` rows a training step: ``macs``
    multiply-adds a row and layer (``flops.py``'s own count) and
    ``weights`` their ``(rows in, columns out)``."""
    passes = 3
    elements = sum(positions * (k + n) + k * n for k, n in weights)
    return {"flops": float(layers * passes * 2 * positions * macs),
            "bytes": float(layers * passes * elements * flops.BF16_BYTES)}


def share(run, what: str, work: dict, measured_ms: float) -> float:
    least_s, bound = flops.roofline_seconds(
        work, harness.peak_of(run.device_kind))
    run.log(f"{what} roofline: {work['flops']:.4g} operations, "
            f"{work['bytes']:.4g} bytes a step and chip, least "
            f"{1e3 * least_s:.3f} ms ({bound}-bound) against "
            f"{measured_ms:.3f} ms measured")
    return 100.0 * 1e3 * least_s / measured_ms


def read(run):
    measured_ms = attn_proj_ms.read(run)
    model = run.model_sizes
    weights = products(model)
    if not measured_ms or not weights:
        return None
    work = dense_work(flops.projection_macs(model), weights,
                      positions_per_step(run.traffic), model["num_layers"])
    return share(run, "attention projections'", work, measured_ms)
