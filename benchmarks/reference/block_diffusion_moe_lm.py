"""Plain reference for the ``block_diffusion_moe_lm`` family: a
Qwen3-MoE block (RMSNorm, grouped-query attention with a stated head
width and per-head RMS norms on q and k before the rotary positions, no
bias; a routed MLP of SwiGLU experts, softmax over all of the router's
experts, the top k, their weights renormalised over the chosen; an
untied head) trained by diffusion over blocks, as one chip's share of a
deployment: of the router's ``num_experts`` this chip holds
``experts_held`` from ``first_expert``, and a token's result is the sum
over its choices that live here; what the absent experts would add is
left out, as in the program. A share has no exchange, so the gradient
through a token's weights, which needs every chosen expert's result, is
left out with it: where fewer experts are held than the router has, the
router's scores are constants of the backward pass (the router is not
trained, and passes no gradient to its input).

To the contract at the top of ``transformer_lm.py``: float32 under
``jax.default_matmul_precision("highest")``, nothing imported from the
program, the program's parameter tree in (``tok_emb/embedding``,
``block_<i>/{ln_attn, attn/{query, key, value, out}/kernel,
attn/{q_norm, k_norm}/scale, ln_mlp, mlp/{router/kernel, gate, up,
down}}``, ``ln_final``, ``lm_head/kernel``; ``gate`` and ``up`` are
``[held, h, m]``, ``down`` ``[held, m, h]``). It uses the allowances
the contract gives a reference and nothing else: ``jax.checkpoint``
around each layer, ``jax.lax.map`` over heads, over blocks of queries
inside a head and over blocks of rows at the head, and a ``lax.scan``
over the held experts whose weights are the scanned operand (a mapped
function that closes over a weight keeps a copy of it a step): every
held expert is computed densely for every token and selected by the
choices. They change what is kept for the backward pass, not one number
that is computed.

Block diffusion (batch ``(x0, m, w)``, T data tokens a sequence, block
length b = the model group's ``diffusion_block``): the input is
``[x_t ; x0]``, x_t the mask token (the last row held) where ``m``,
positions ``[0..T-1 ; 0..T-1]``. With blk(i) = (i mod T) // b, query q
sees key k iff both are noisy and blk(q) = blk(k), or q is noisy, k
clean and blk(k) < blk(q), or both are clean and blk(k) <= blk(q). The
loss is the sum over the noisy half of m · w · (-log softmax(h W)[x0])
over the B·T data tokens; no shift, and no auxiliary router term (the
source states none).

A model that chooses: ``TAKES_CHOICES``. ``choice_scores`` returns each
layer's router probabilities under the name the job gives the program's
choices (``block_<i>/mlp/experts/0``); ``mean_loss(..., choices=)``
takes the k experts of every position as given and computes their
weights itself, from its own scores.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

TAKES_CHOICES = True
# data tokens a chip takes in one call of ``nll_sum``: two sequences of
# 4,096 (16,384 positions), the cell's whole batch
BLOCK_TOKENS = 8192
# the most queries of a head, and the most rows of the vocabulary
# head, that are computed at once
QUERY_BLOCK = 1024
ROW_BLOCK = 2048


def arguments(model: dict, traffic: dict) -> dict:
    if traffic["objective"] != "block_diffusion":
        raise ValueError(
            f"this family has no objective {traffic['objective']!r}")
    if not model["diffusion_block"] or not model["qk_norm"] \
            or model["tie_embeddings"]:
        raise ValueError(
            "this family is a block-diffusion model (diffusion_block) "
            "with q/k norms and an untied head")
    return dict(block=model["diffusion_block"],
                num_layers=model["num_layers"],
                kv_heads=model["num_kv_heads"],
                theta=model["rope_theta"], eps=model["layernorm_epsilon"],
                num_experts=model["num_experts"],
                held=model["experts_held"], first_expert=0,
                per_token=model["experts_per_token"],
                renormalise=model["norm_topk_prob"])


def choice_name(layer: int) -> str:
    return f"block_{layer}/mlp/experts/0"


def _divisor(n: int, most: int) -> int:
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def _mapped(fn, blocks, *arrays):
    """``fn`` over ``blocks`` equal parts of the arrays' first axis,
    one part at a time, keeping only the parts for the backward pass;
    the results joined along that axis."""
    parts = tuple(a.reshape(blocks, a.shape[0] // blocks, *a.shape[1:])
                  for a in arrays)
    out = jax.lax.map(lambda xs: jax.checkpoint(fn)(*xs), parts)
    return out.reshape(-1, *out.shape[2:])


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotate(x, positions, theta):
    """x: [B, P, heads, d]; positions: [P]."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def visible(q_index, k_index, *, t: int, block: int):
    """[Q, K] bool: which keys a query sees under the block-diffusion
    mask over the 2·t positions ``[noisy ; clean]``."""
    q, k = q_index[:, None], k_index[None, :]
    q_noisy, k_noisy = q < t, k < t
    q_blk, k_blk = (q % t) // block, (k % t) // block
    return ((q_noisy & k_noisy & (q_blk == k_blk))
            | (q_noisy & ~k_noisy & (k_blk < q_blk))
            | (~q_noisy & ~k_noisy & (k_blk <= q_blk)))


def _attend(q, k, v, *, t, block):
    """q, k, v: [heads, B, P, d] -> [heads, B, P, d], a head at a time
    and inside it a block of queries at a time."""
    positions = q.shape[2]
    rows = _divisor(positions, QUERY_BLOCK)
    keys = jnp.arange(positions)

    def head(qh, kh, vh):  # [B, P, d]
        def queries(qb, index):  # [rows, B, d], [rows]
            s = jnp.einsum("qbd,bkd->bqk", qb, kh) / math.sqrt(
                qb.shape[-1])
            s = jnp.where(visible(index, keys, t=t, block=block)[None],
                          s, -jnp.inf)
            return jnp.einsum("bqk,bkd->qbd", jax.nn.softmax(s, -1), vh)

        out = _mapped(queries, positions // rows,
                      qh.transpose(1, 0, 2), keys)
        return out.transpose(1, 0, 2)

    return jax.lax.map(lambda xs: jax.checkpoint(head)(*xs), (q, k, v))


def router_scores(y, m):
    """[..., e] float32: the router's probabilities over all of its
    experts."""
    return jax.nn.softmax(y @ m["router"]["kernel"], axis=-1)


def _routed(y, m, choices, *, first_expert, per_token, renormalise):
    """[N, h] -> ([N, h], the router's scores [N, e]): the sum over a
    token's choices that live here of weight x down(silu(gate y) * up y).
    ``choices`` [N, k] are the experts taken for each token (the
    reference's own top k where None); their weights are the reference's
    own scores at them."""
    scores = router_scores(y, m)
    held = m["gate"].shape[0]
    if held < scores.shape[-1]:
        # a share has no exchange: its router is not trained, and the
        # scores are constants of the backward pass (module docstring)
        scores = jax.lax.stop_gradient(scores)
    if choices is None:
        choices = jax.lax.top_k(scores, per_token)[1]
    weights = jnp.take_along_axis(scores, choices, axis=-1)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # [held, N]: the weight with which a token takes each held expert
    share = jnp.sum(
        weights[None] * (choices[None] == (
            first_expert + jnp.arange(held))[:, None, None]), axis=-1)

    def expert(y, gate, up, down, share):
        return share[:, None] * (
            (jax.nn.silu(y @ gate) * (y @ up)) @ down)

    def step(total, xs):
        return total + jax.checkpoint(expert)(y, *xs), None

    total, _ = jax.lax.scan(
        step, jnp.zeros_like(y), (m["gate"], m["up"], m["down"], share))
    return total, scores


def _block(x, p, positions, choices, *, kv_heads, theta, eps, t, block,
           **routing):
    """One layer: ([B, P, h], the router's scores [B, P, e])."""
    y = _rms(x, p["ln_attn"]["scale"], eps)
    a = p["attn"]
    q = jnp.einsum("bth,hnd->btnd", y, a["query"]["kernel"])
    k = jnp.einsum("bth,hnd->btnd", y, a["key"]["kernel"])
    v = jnp.einsum("bth,hnd->btnd", y, a["value"]["kernel"])
    if k.shape[2] != kv_heads:
        raise ValueError(f"{k.shape[2]} key heads in the parameters, "
                         f"{kv_heads} in the configuration")
    # one scale of the head's width for all heads, then the rotation
    q = _rotate(_rms(q, a["q_norm"]["scale"], eps), positions, theta)
    k = _rotate(_rms(k, a["k_norm"]["scale"], eps), positions, theta)
    # each key and value head serves heads / kv_heads query heads
    k = jnp.repeat(k, q.shape[2] // kv_heads, axis=2)
    v = jnp.repeat(v, q.shape[2] // kv_heads, axis=2)
    o = _attend(*(z.transpose(2, 0, 1, 3) for z in (q, k, v)),
                t=t, block=block).transpose(1, 2, 0, 3)
    x = x + jnp.einsum("bqnd,ndh->bqh", o, a["out"]["kernel"])
    y = _rms(x, p["ln_mlp"]["scale"], eps)
    rows = y.reshape(-1, y.shape[-1])
    chosen = None if choices is None else choices.reshape(
        rows.shape[0], -1)
    out, scores = _routed(rows, p["mlp"], chosen, **routing)
    return x + out.reshape(x.shape), scores.reshape(*x.shape[:2], -1)


def hidden(params, tokens, positions, choices, *, num_layers, **kw):
    """([B, P, h] float32, the final norm's output on ``tokens`` at
    ``positions`` ([P]); each layer's router scores by name).
    ``choices`` is None (every layer takes its own top k) or the
    experts to take, by the same names."""
    x = params["tok_emb"]["embedding"][tokens]
    scores = {}
    for i in range(num_layers):
        given = None if choices is None else choices[choice_name(i)]
        x, scores[choice_name(i)] = jax.checkpoint(
            lambda x, p, given: _block(x, p, positions, given, **kw))(
                x, params[f"block_{i}"], given)
    return _rms(x, params["ln_final"]["scale"], kw["eps"]), scores


def _weighted_nll(rows, head, targets, weights):
    """Sum over the rows of weight · (-log softmax(row · head)[target]),
    a block of rows at a time."""
    def part(x, target, weight):
        lg = x @ head
        nll = jax.scipy.special.logsumexp(lg, axis=-1) \
            - jnp.take_along_axis(lg, target[:, None], -1)[:, 0]
        return jnp.sum(weight * nll)[None]

    n = rows.shape[0]
    return jnp.sum(_mapped(part, n // _divisor(n, ROW_BLOCK), rows,
                           targets, weights))


def _forward(params, batch, choices, *, block, num_experts, held, **kw):
    """(the noisy half's hidden state [B, T, h], the head's kernel,
    every layer's router scores) on the step's input ``[x_t ; x0]``."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    mlp = p["block_0"]["mlp"]
    if mlp["router"]["kernel"].shape[1] != num_experts \
            or mlp["gate"].shape[0] != held:
        raise ValueError(
            f"a router over {mlp['router']['kernel'].shape[1]} experts "
            f"and {mlp['gate'].shape[0]} held in the parameters, "
            f"{num_experts} and {held} in the configuration")
    head = p["lm_head"]["kernel"]
    x0, m, _ = batch
    t = x0.shape[1]
    tokens = jnp.concatenate(
        [jnp.where(m, head.shape[1] - 1, x0), x0], axis=1)
    x, scores = hidden(p, tokens, jnp.tile(jnp.arange(t), 2), choices,
                       t=t, block=block, **kw)
    return x[:, :t], head, scores


def choice_scores(params, batch, **kw):
    """{name: [B, 2T, e] float32}: every layer's router probabilities on
    the reference's own pass (each layer fed by the layers before it at
    the reference's own choices)."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, batch, None, **kw)[2]


def nll_sum(params, batch, *, choices=None, **kw):
    with jax.default_matmul_precision("highest"):
        x, head, _ = _forward(params, batch, choices, **kw)
        x0, m, w = batch
        n, t = x0.shape
        total = _weighted_nll(
            x.reshape(n * t, -1), head, x0.reshape(-1),
            (m * w).reshape(-1).astype(jnp.float32))
        return total, jnp.float32(n * t)


def mean_loss(params, batch, **kw):
    total, count = nll_sum(params, batch, **kw)
    return total / count
