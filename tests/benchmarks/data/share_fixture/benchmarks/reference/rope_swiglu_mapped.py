"""Plain reference of the share fixture's family, written to fit a
share's size: the fixture's block (RMSNorm, rotary positions,
grouped-query attention, SwiGLU MLP, no bias, a head of its own or the
token embedding's transpose) under a causal mask or a block-diffusion
one, for the objectives ``causal_lm`` and ``block_diffusion``.

It uses the allowances the contract at the top of
``benchmarks/reference/transformer_lm.py`` gives a reference and
nothing else: ``jax.checkpoint`` around each layer, ``jax.lax.map``
over heads, over blocks of queries inside a head and over blocks of
rows at the head, each mapped function under ``jax.checkpoint``. They
change what is kept for the backward pass, not one number that is
computed: the [P, P] scores of a head and the [N, V] logits never exist
whole. The layers are not stacked (a stacked copy of the weights is a
tree more), and the MLP is not mapped over rows: compiled for a
described v5e at 644 M parameters and 16,384 positions, mapping it kept
7 GiB more than leaving it whole (11.7 against 4.6 GiB of temporaries
for the gradient; PERF.md section 4).

float32 under ``jax.default_matmul_precision("highest")``, nothing
imported from the program. Parameter tree as the fixture's
(``tests/benchmarks/data/fixture/benchmarks/reference/rope_swiglu_lm.py``).

Block diffusion (batch ``(x0, m, w)``, T data tokens a sequence, block
length b = the model group's ``diffusion_block``): the input is
``[x_t ; x0]``, x_t the mask token (the last row held) where ``m``,
positions ``[0..T-1 ; 0..T-1]``. With blk(i) = (i mod T) // b, query q
sees key k iff both are noisy and blk(q) = blk(k), or q is noisy, k
clean and blk(k) < blk(q), or both are clean and blk(k) <= blk(q). The
loss is the sum over the noisy half of m · w · (-log softmax(h W)[x0])
over the B·T data tokens; no shift.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# the most queries of a head, and the most rows of the vocabulary
# head, that are computed at once
QUERY_BLOCK = 1024
ROW_BLOCK = 2048


def arguments(model: dict, traffic: dict) -> dict:
    objective = traffic["objective"]
    block = model.get("diffusion_block", 0)
    if objective not in ("causal_lm", "block_diffusion"):
        raise ValueError(f"this family has no objective {objective!r}")
    if (objective == "block_diffusion") != bool(block) or (
            objective == "causal_lm" and not model["causal"]):
        raise ValueError(
            f"objective {objective!r} with causal {model['causal']!r} "
            f"and diffusion_block {block!r}")
    return dict(objective=objective, block=block,
                num_layers=model["num_layers"],
                kv_heads=model["num_kv_heads"],
                tied=model["tie_embeddings"],
                theta=model["rope_theta"], eps=model["layernorm_epsilon"])


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


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def _rotate(x, positions, theta):
    """x: [B, P, heads, d]; positions: [P]."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def visible(q_index, k_index, *, t: int, block: int):
    """[Q, K] bool: which keys a query sees. ``block`` 0: the causal
    mask over ``t`` positions. Otherwise the block-diffusion mask over
    the 2·t positions ``[noisy ; clean]``."""
    q, k = q_index[:, None], k_index[None, :]
    if not block:
        return k <= q
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


def _block(x, p, positions, *, kv_heads, theta, eps, t, block):
    y = _rms(x, p["ln_attn"], eps)
    a = p["attn"]
    q = _rotate(jnp.einsum("bth,hnd->btnd", y, a["query"]["kernel"]),
                positions, theta)
    k = _rotate(jnp.einsum("bth,hnd->btnd", y, a["key"]["kernel"]),
                positions, theta)
    v = jnp.einsum("bth,hnd->btnd", y, a["value"]["kernel"])
    if k.shape[2] != kv_heads:
        raise ValueError(f"{k.shape[2]} key heads in the parameters, "
                         f"{kv_heads} in the configuration")
    # each key and value head serves heads / kv_heads query heads
    k = jnp.repeat(k, q.shape[2] // kv_heads, axis=2)
    v = jnp.repeat(v, q.shape[2] // kv_heads, axis=2)
    o = _attend(*(z.transpose(2, 0, 1, 3) for z in (q, k, v)),
                t=t, block=block).transpose(1, 2, 0, 3)
    x = x + jnp.einsum("bqnd,ndh->bqh", o, a["out"]["kernel"])
    y = _rms(x, p["ln_mlp"], eps)
    m = p["mlp"]
    h = jax.nn.silu(y @ m["gate"]["kernel"]) * (y @ m["up"]["kernel"])
    return x + h @ m["fc2"]["kernel"]


def hidden(params, tokens, positions, *, num_layers, t, block, **kw):
    """[B, P, h] float32: the final norm's output on ``tokens`` at
    ``positions`` ([P])."""
    x = params["tok_emb"]["embedding"][tokens]
    for i in range(num_layers):
        x = jax.checkpoint(
            lambda x, p: _block(x, p, positions, t=t, block=block, **kw))(
                x, params[f"block_{i}"])
    return _rms(x, params["ln_final"], kw["eps"])


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


def nll_sum(params, batch, *, objective, block, tied, **kw):
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        head = p["tok_emb"]["embedding"].T if tied \
            else p["lm_head"]["kernel"]
        n, t = batch[0].shape
        if objective == "causal_lm":
            tokens, = batch
            x = hidden(p, tokens, jnp.arange(t), t=t, block=0, **kw)
            # every position's row, the last of a sequence at weight 0
            targets = jnp.roll(tokens, -1, axis=1)
            weights = jnp.broadcast_to(jnp.arange(t) < t - 1, (n, t))
            count = n * (t - 1)
        else:
            x0, m, w = batch
            mask_token = head.shape[1] - 1
            tokens = jnp.concatenate(
                [jnp.where(m, mask_token, x0), x0], axis=1)
            x = hidden(p, tokens, jnp.tile(jnp.arange(t), 2), t=t,
                       block=block, **kw)[:, :t]
            targets, weights, count = x0, m * w, n * t
        total = _weighted_nll(
            x.reshape(n * t, -1), head, targets.reshape(-1),
            weights.reshape(-1).astype(jnp.float32))
        return total, jnp.float32(count)


def mean_loss(params, batch, **kw):
    total, count = nll_sum(params, batch, **kw)
    return total / count
