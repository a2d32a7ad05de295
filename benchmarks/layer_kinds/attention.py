"""The ``attention`` kind of layer: projections to heads, scores and
weighted values under the model's one mask (full, causal or block
diffusion), an output projection. Plain, grouped-query or latent
(``kv_lora_rank``). What every layer is where the ``model`` group
states no ``layer_types``. The contract of a kind is at the top of
``benchmarks/flops.py``; the counts are those ``flops.py`` had for
every layer before a layer had a kind (PR 22, 27, 32), for one layer."""

from benchmarks import flops

KEYS = {
    "num_heads": "query heads; required",
    "causal": "the mask where there is no diffusion_block; required",
    "diffusion_block": "the block length b of a block-diffusion mask, "
                       "which then stands in place of causal; absent, "
                       "no such mask",
    "num_kv_heads": "key and value heads; absent, as many as heads",
    "head_dim": "a head's width; absent, hidden_size / num_heads, which "
                "has to be whole",
    "kv_lora_rank": "keys and values come from a latent of this width "
                    "and a rope key shared by the heads; absent, plain "
                    "projections",
    "q_lora_rank": "queries come through a latent of this width; "
                   "absent, null or 0, a full-rank query",
    "qk_nope_head_dim": "with qk_rope_head_dim the width of a head's "
                        "query and key; absent, head_dim",
    "qk_rope_head_dim": "see qk_nope_head_dim",
    "v_head_dim": "the width of a head's value and output; absent, "
                  "head_dim",
}
BOOKED_UNDER = "attention"
SOURCE_NAMES = ("attention", "full_attention")
# heads, key-value heads, head widths and latents are sizes of the
# ``model`` group that ``published.ROWS`` holds for every file, as it
# did before a layer had a kind: no row is this kind's alone
ROWS = ()


def mixer_macs(model: dict) -> int:
    """Multiply-adds a token of one layer's attention projections."""
    h, heads = model["hidden_size"], model["num_heads"]
    qk, v = flops.qk_head_dim(model), flops.v_head_dim(model)
    out = heads * v * h
    if "kv_lora_rank" not in model:
        # q at h x heads·qk, k at h x kv_heads·qk, v at h x kv_heads·v
        return (h * heads * qk + flops.kv_heads(model) * h * (qk + v)
                + out)
    # latent attention: the query through its latent (or full rank); one
    # latent and one rope key for all heads; keys' no-rope part and
    # values expanded from the latent for every head
    q_rank, kv_rank = model.get("q_lora_rank"), model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    query = (h * q_rank + q_rank * heads * qk) if q_rank \
        else h * heads * qk
    return (query + h * (kv_rank + rope) + kv_rank * heads * (nope + v)
            + out)


def products(model: dict):
    """``(rows in, columns out)`` of each projection's weight, as
    ``mixer_macs`` counts them; None for latent attention
    (``kv_lora_rank``), which no cell runs: the configuration that
    brings it brings its products."""
    if "kv_lora_rank" in model:
        return None
    h, heads = model["hidden_size"], model["num_heads"]
    kv, qk, v = (flops.kv_heads(model), flops.qk_head_dim(model),
                 flops.v_head_dim(model))
    return [(h, heads * qk), (h, kv * qk), (h, kv * v), (heads * v, h)]


def mixing_flops(model: dict, traffic: dict) -> float:
    """Scores at the query-and-key width and weighted values at the
    value width, a data token: 2·heads·width each for every pair the
    mask shows (``flops.visible_pairs``) of a sequence, over its T data
    tokens: 2·T·heads·widths halved under a causal mask,
    2·(T + b)·heads·widths under a block-diffusion one."""
    return mixing_over(model, traffic, flops.visible_pairs(model, traffic))


def mixing_over(model: dict, traffic: dict, pairs: float) -> float:
    """``mixing_flops`` over ``pairs`` visible (query, key) pairs a
    sequence: what a kind with another mask counts by."""
    return (2 * (pairs / traffic["seq_len"]) * model["num_heads"]
            * (flops.qk_head_dim(model) + flops.v_head_dim(model)))


def kernel_work(model: dict, traffic: dict) -> dict:
    """Required operations and least HBM bytes of one layer's attention
    in one training step on one chip.

    Operations: forward QK^T and PV, backward dV, dP, dQ, dK: six
    products per head over the pairs the mask shows
    (``flops.visible_pairs``), QK^T, dQ and dK over the query-and-key
    width, PV, dV and dP over the value width (the flash backward's
    recomputed scores are not required work). Bytes: forward reads q,
    k, v and writes o; backward reads q, k, v, o, do and writes dq, dk,
    dv: twelve bf16 arrays, each moved once, of P positions a sequence
    (T, or 2T where a data token runs two). q and dq are (B, heads, P,
    query-and-key width), o and do (B, heads, P, value width), k and dk
    (B, kv_heads, P, query-and-key width), v and dv (B, kv_heads, P,
    value width)."""
    return work_over(model, traffic, flops.visible_pairs(model, traffic))


def work_over(model: dict, traffic: dict, pairs: float) -> dict:
    """``kernel_work`` over ``pairs`` visible (query, key) pairs a
    sequence."""
    b = traffic["batch_per_chip"]
    positions = flops.positions_per_token(traffic) * traffic["seq_len"]
    heads = model["num_heads"]
    widths = flops.qk_head_dim(model) + flops.v_head_dim(model)
    return {"flops": 3 * 2 * b * heads * pairs * widths,
            "bytes": (3 * (heads + flops.kv_heads(model)) * b * positions
                      * widths * flops.BF16_BYTES)}
