"""model: least time the chip could take for the step's attention
projections over the measured ``attn_proj_ms``.

Operations: the ``mixer_macs`` of each layer whose kind runs the
attention kernels (``benchmarks/layer_kinds``; what ``mfu_pct`` counts
as those layers' part of ``blocks``; ``flops.projection_macs`` where
every layer is ``attention``) a position, two operations a
multiply-add, in three passes: forward, and backward into the
activations and into the weights. A layer of another kind (a
state-space mixer) has projections of its own, which are not under
the ``attn_proj`` scope and are not counted here. Least bytes: each product's
two operands and its result moved once in bf16, in each of its three
passes. The larger of operations over peak FLOP/s and bytes over peak
HBM bytes/s, over the measured time. Under ``remat`` the forward
products of a rematerialised block run twice, four passes' time for
three passes' work: the reading cannot pass about three quarters
there."""

from benchmarks import flops, harness
from benchmarks.layer_metrics import attn_proj_ms


def products(model: dict):
    """``(rows in, columns out)`` of each projection's weight of an
    ``attention`` layer; None for latent attention."""
    return flops.load_kind(flops.DEFAULT_KIND).products(model)


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


def work(model: dict, traffic: dict):
    """Required operations and least bytes of the step's attention
    projections, over the layers whose kind runs the attention kernels
    (``flops.layers_of``); None where there is no such layer or one's
    kind states no products."""
    total = None
    for kind, layers in flops.layers_of(model).items():
        weights = kind.products(model)
        if not weights:
            return None
        one = dense_work(kind.mixer_macs(model), weights,
                         positions_per_step(traffic), layers)
        total = one if total is None else {
            key: total[key] + one[key] for key in one}
    return total


def read(run):
    measured_ms = attn_proj_ms.read(run)
    found = work(run.model_sizes, run.traffic) if measured_ms else None
    if not found:
        return None
    return share(run, "attention projections'", found, measured_ms)
