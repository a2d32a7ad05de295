"""Operations and bytes a cell's work requires, from its shapes alone.

The benchmark's own arithmetic (the program's ``utils/mfu.py`` counts
6·N·D with every parameter and no attention term). "Required" means
what the forward and backward passes need if nothing is recomputed: a
matrix multiplication costs 2·m·n·k forward and twice that backward, so
training is three times the forward count.

Of the traffic it reads ``objective``, ``seq_len`` (the **data tokens**
of a sequence, which ``tokens_per_s_per_chip`` counts: what a user pays
for), ``batch_per_chip`` and what the objective needs
(``mask_fraction``; ``t_min``). Under ``block_diffusion`` a sequence of
T data tokens is noised block by block and run beside its clean copy:
every layer runs 2T positions, the mask shows T^2 + T*b of their pairs,
and the head is required at the masked positions of the noisy half.

A step is counted from the configuration's ``model`` group as it is
run, which may be one chip's share of a deployment, by kind of layer.
The keys this file reads, and what a key's absence means (a test holds
this list to the keys the functions touch):

* ``hidden_size``, ``num_heads``, ``num_layers``, ``mlp_ratio``,
  ``vocab_size``, ``causal``: required. ``num_layers`` counts the
  leading dense layers and the layers after them, not the MTP layers;
  ``vocab_size`` is the rows of the head that are held here.
* ``diffusion_block``: the block length b of a block-diffusion mask,
  which then stands in place of ``causal``; absent, no such mask.
* ``num_kv_heads``: absent, as many as heads.
* ``head_dim``: absent, ``hidden_size / num_heads``, which has to be
  whole.
* ``activation``: ``swiglu`` has three matrices a MLP or an expert,
  anything else, or absent, two.
* ``num_experts``: the router's width, as published; 0 or absent, every
  layer has a plain MLP of ``hidden_size * mlp_ratio``.
* ``experts_per_token``: the experts the router sends a token to, read
  where there are experts.
* ``experts_held``: the routed experts of a layer that this chip
  holds; absent, all ``num_experts``.
* ``expert_mlp_dim``: one expert's width, routed or shared; absent,
  ``hidden_size * mlp_ratio``.
* ``shared_experts``: experts every token goes through; absent, 0.
* ``dense_layers``: leading layers with a plain MLP of
  ``hidden_size * mlp_ratio`` in a model that has experts; absent, 0.
* ``kv_lora_rank``: keys and values come from a latent of this width
  and a rope key shared by the heads; absent, plain projections.
* ``q_lora_rank``: queries come through a latent of this width;
  absent, null or 0, a full-rank query.
* ``qk_nope_head_dim`` and ``qk_rope_head_dim``: their sum is the width
  of a head's query and key; absent, ``head_dim``.
* ``v_head_dim``: the width of a head's value and output; absent,
  ``head_dim``.
* ``mtp_layers``: further prediction heads (multi-token prediction);
  absent, 0.
"""

from __future__ import annotations

BF16_BYTES = 2


def head_positions_per_token(traffic: dict, ahead: int = 1) -> float:
    """Share of a sequence's data tokens at which a vocabulary head is
    required: the first head predicts the next token (``ahead`` 1), a
    further prediction head the one after it (``ahead`` 2)."""
    if traffic["objective"] == "causal_lm":
        t = traffic["seq_len"]
        return (t - ahead) / t  # the last positions predict nothing
    if traffic["objective"] == "masked_lm":
        return float(traffic["mask_fraction"])
    if traffic["objective"] == "block_diffusion":
        # a block's tokens are masked with probability t ~ U(t_min, 1]
        return (1.0 + traffic["t_min"]) / 2
    raise ValueError(f"unknown objective {traffic['objective']!r}")


def positions_per_token(traffic: dict) -> int:
    """Positions every layer runs for one data token: a block-diffusion
    step runs the noisy copy of a sequence beside the clean one."""
    return 2 if traffic["objective"] == "block_diffusion" else 1


def visible_pairs(model: dict, traffic: dict) -> float:
    """(query, key) pairs of one sequence that the mask shows: what the
    scores and the weighted values are required over.

    Full: T^2. Causal: counted as half of that, as since PR 22 (the
    diagonal's T/2 are left out). Block diffusion, block length b, over
    the 2T positions ``[noisy ; clean]`` with blk(i) = (i mod T) // b: a
    noisy query sees the noisy keys of its own block (T*b pairs) and the
    clean keys of earlier blocks (T^2/2 - T*b/2), a clean query the
    clean keys of its own and earlier blocks (T^2/2 + T*b/2):
    T^2 + T*b."""
    t = traffic["seq_len"]
    block = model.get("diffusion_block")
    if block:
        return t * t + t * block
    return t * t * (0.5 if model["causal"] else 1.0)


def head_dim(model: dict) -> int:
    if model.get("head_dim"):
        return model["head_dim"]
    width, rest = divmod(model["hidden_size"], model["num_heads"])
    if rest:
        raise ValueError(
            f"hidden_size {model['hidden_size']} over num_heads "
            f"{model['num_heads']} is no whole head width and the model "
            f"group states none (head_dim, or qk_nope_head_dim + "
            f"qk_rope_head_dim and v_head_dim)")
    return width


def kv_heads(model: dict) -> int:
    return model.get("num_kv_heads") or model["num_heads"]


def qk_head_dim(model: dict) -> int:
    """Width of one head's query and key: what the scores are taken
    over."""
    if "qk_nope_head_dim" in model:
        return model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return head_dim(model)


def v_head_dim(model: dict) -> int:
    """Width of one head's value and output."""
    return model.get("v_head_dim") or head_dim(model)


def projection_macs(model: dict) -> int:
    """Multiply-adds a token of one layer's attention projections."""
    h, heads = model["hidden_size"], model["num_heads"]
    qk, v = qk_head_dim(model), v_head_dim(model)
    out = heads * v * h
    if "kv_lora_rank" not in model:
        # q at h x heads·qk, k at h x kv_heads·qk, v at h x kv_heads·v
        return h * heads * qk + kv_heads(model) * h * (qk + v) + out
    # latent attention: the query through its latent (or full rank); one
    # latent and one rope key for all heads; keys' no-rope part and
    # values expanded from the latent for every head
    q_rank, kv_rank = model.get("q_lora_rank"), model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    query = (h * q_rank + q_rank * heads * qk) if q_rank \
        else h * heads * qk
    return (query + h * (kv_rank + rope) + kv_rank * heads * (nope + v)
            + out)


def mlp_macs(model: dict) -> tuple:
    """Multiply-adds a token of (a plain MLP, the last kind of layer's
    MLP): the same where there are no experts. An expert layer is its
    shared experts for every token, the router at its published width,
    and ``experts_per_token * experts_held / num_experts`` routed
    experts: the expectation, under even routing, of how many of a
    token's experts live on this chip (all of them where all are held).
    How near routing is to even is for the program's counter of tokens
    a held expert to show; the required work does not follow it."""
    h = model["hidden_size"]
    matrices = 3 if model.get("activation") == "swiglu" else 2
    dense = matrices * h * int(h * model["mlp_ratio"])
    experts = model.get("num_experts", 0)
    if not experts:
        return dense, dense
    one = matrices * h * model["expert_mlp_dim"] \
        if "expert_mlp_dim" in model else dense
    routed = (model["experts_per_token"]
              * model.get("experts_held", experts) / experts)
    return dense, (model.get("shared_experts", 0) * one + routed * one
                   + h * experts)


def attention_flops_per_layer(model: dict, traffic: dict) -> float:
    """Scores at the query-and-key width and weighted values at the
    value width, a data token: 2·heads·width each for every pair the
    mask shows (``visible_pairs``) of a sequence, over its T data
    tokens: 2·T·heads·widths halved under a causal mask,
    2·(T + b)·heads·widths under a block-diffusion one."""
    return (2 * (visible_pairs(model, traffic) / traffic["seq_len"])
            * model["num_heads"]
            * (qk_head_dim(model) + v_head_dim(model)))


def forward_flops_per_token(model: dict, traffic: dict) -> dict:
    """Forward operations per data token, by part: ``blocks``, the
    matrix multiplications of the ``num_layers`` layers
    (``dense_layers`` of them with a plain MLP) at every position a
    data token runs (``positions_per_token``); ``attention``, their
    scores and weighted values over the pairs the mask shows; ``head``,
    the vocabulary head at the positions that have a target; and, where
    there are ``mtp_layers``, ``mtp``: for each one block of the last
    kind, the product that takes the hidden state beside the next
    token's embedding from 2h to h, its attention, and the head once
    more at the positions that have a token two ahead. The keys read
    are listed at the top of this file."""
    h = model["hidden_size"]
    layers = model["num_layers"]
    dense_layers = model.get("dense_layers", 0) \
        if model.get("num_experts", 0) else layers
    projections = projection_macs(model)
    dense, last = mlp_macs(model)
    positions = positions_per_token(traffic)
    blocks = positions * 2 * (
        layers * projections + dense_layers * dense
        + (layers - dense_layers) * last)
    attention = attention_flops_per_layer(model, traffic)
    head = 2 * h * model["vocab_size"]
    parts = {"blocks": blocks, "attention": layers * attention,
             "head": head * head_positions_per_token(traffic)}
    if model.get("mtp_layers", 0):
        parts["mtp"] = model["mtp_layers"] * (
            positions * (2 * (projections + last) + 2 * 2 * h * h)
            + attention
            + head * head_positions_per_token(traffic, ahead=2))
    return parts


def train_flops_per_token(model: dict, traffic: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(model, traffic).values())


def attention_kernel_work(model: dict, traffic: dict) -> dict:
    """Required operations and least HBM bytes of one training step's
    attention on one chip, over ``num_layers`` and ``mtp_layers``.

    Operations: forward QK^T and PV, backward dV, dP, dQ, dK: six
    products per head over the pairs the mask shows
    (``visible_pairs``), QK^T, dQ and dK over the query-and-key width,
    PV, dV and dP over the value width (the flash backward's recomputed
    scores are not required work). Bytes: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv:
    twelve bf16 arrays, each moved once, of P positions a sequence (T,
    or 2T where a data token runs two). q and dq are (B, heads, P,
    query-and-key width), o and do (B, heads, P, value width), k and dk
    (B, kv_heads, P, query-and-key width), v and dv (B, kv_heads, P,
    value width)."""
    b = traffic["batch_per_chip"]
    positions = positions_per_token(traffic) * traffic["seq_len"]
    heads = model["num_heads"]
    widths = qk_head_dim(model) + v_head_dim(model)
    layers = model["num_layers"] + model.get("mtp_layers", 0)
    flops = (layers * 3 * 2 * b * heads * visible_pairs(model, traffic)
             * widths)
    nbytes = (layers * 3 * (heads + kv_heads(model)) * b * positions
              * widths * BF16_BYTES)
    return {"flops": float(flops), "bytes": float(nbytes)}


def roofline_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """Least time the chip could take for ``work`` and which peak
    bounds it."""
    by_flops = work["flops"] / peak["bf16_flops_per_s"]
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    if by_flops >= by_bytes:
        return by_flops, "compute"
    return by_bytes, "hbm"
