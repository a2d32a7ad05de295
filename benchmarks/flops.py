"""Operations and bytes a cell's work requires, from its shapes alone.

The benchmark's own arithmetic (the program's ``utils/mfu.py`` counts
6·N·D with every parameter and no attention term). "Required" means
what the forward and backward passes need if nothing is recomputed: a
matrix multiplication costs 2·m·n·k forward and twice that backward, so
training is three times the forward count.
"""

from __future__ import annotations

BF16_BYTES = 2


def head_positions_per_token(traffic: dict) -> float:
    """Share of positions at which the vocabulary head is required."""
    if traffic["objective"] == "causal_lm":
        t = traffic["seq_len"]
        return (t - 1) / t  # the last position predicts nothing
    if traffic["objective"] == "masked_lm":
        return float(traffic["mask_fraction"])
    raise ValueError(f"unknown objective {traffic['objective']!r}")


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["hidden_size"] // model[
        "num_heads"]


def kv_heads(model: dict) -> int:
    return model.get("num_kv_heads") or model["num_heads"]


def forward_flops_per_token(model: dict, traffic: dict) -> dict:
    """Forward operations per token, by part: the blocks' matrix
    multiplications, attention (scores and weighted values) at the
    cell's sequence length with a causal mask counted as half, and the
    vocabulary head at the positions that have a target.

    Read from the configuration's ``model`` group: ``hidden_size``,
    ``num_heads``, ``num_layers``, ``mlp_ratio``, ``vocab_size``,
    ``causal``; and, where the group has them, ``num_kv_heads`` (else
    as many as heads), ``head_dim`` (else ``hidden_size / num_heads``),
    ``activation`` (``swiglu`` has three MLP matrices, anything else
    two), ``num_experts`` and ``experts_per_token`` (0 or absent: a
    dense MLP). Required operations of an expert layer are the
    router's and those of the experts a token is sent to, not of the
    experts the layer holds."""
    h = model["hidden_size"]
    m = int(h * model["mlp_ratio"])
    layers = model["num_layers"]
    heads, d = model["num_heads"], head_dim(model)
    t = traffic["seq_len"]
    # q and out at h x heads·d, k and v at h x kv_heads·d
    projections = 2 * h * heads * d + 2 * h * kv_heads(model) * d
    mlp = (3 if model.get("activation") == "swiglu" else 2) * h * m
    if model.get("num_experts", 0):
        mlp = model["experts_per_token"] * mlp + h * model["num_experts"]
    blocks = layers * 2 * (projections + mlp)
    # QK^T and PV: 2·t·heads·d each per token per layer
    attention = (layers * 4 * t * heads * d
                 * (0.5 if model["causal"] else 1.0))
    head = 2 * h * model["vocab_size"] * head_positions_per_token(traffic)
    return {"blocks": blocks, "attention": attention, "head": head}


def train_flops_per_token(model: dict, traffic: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(model, traffic).values())


def attention_kernel_work(model: dict, traffic: dict) -> dict:
    """Required operations and least HBM bytes of one training step's
    attention on one chip: (batch, heads, T, head_dim) per layer.

    Operations: forward QK^T and PV, backward dV, dP, dQ, dK — six
    T×T×D products per head (the flash backward's recomputed scores are
    not required work). Bytes: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv — twelve bf16
    arrays, each moved once: six of (B, heads, T, D) and six, k, v and
    their gradients, of (B, kv_heads, T, D)."""
    b = traffic["batch_per_chip"]
    t = traffic["seq_len"]
    heads, d = model["num_heads"], head_dim(model)
    layers = model["num_layers"]
    flops = layers * 6 * 2 * b * heads * t * t * d
    if model["causal"]:
        flops *= 0.5
    nbytes = (layers * 6 * (heads + kv_heads(model)) * b * t * d
              * BF16_BYTES)
    return {"flops": float(flops), "bytes": float(nbytes)}


def roofline_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """Least time the chip could take for ``work`` and which peak
    bounds it."""
    by_flops = work["flops"] / peak["bf16_flops_per_s"]
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    if by_flops >= by_bytes:
        return by_flops, "compute"
    return by_bytes, "hbm"
