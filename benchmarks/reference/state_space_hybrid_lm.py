"""Plain reference for the ``state_space_hybrid_lm`` family: a causal
decoder whose layers differ in kind, a Mamba-2 state-space mixer in
most and grouped-query attention with **no position code** in some, a
SwiGLU MLP in every one, RMSNorm, no bias but the convolution's, a tied
head, and four scalar multipliers (the published ``granitemoehybrid``
block with no experts; Hugging Face ``GraniteMoeHybrid*``):

    x0 = embedding_multiplier * E[tokens]
    x += residual_multiplier * Mixer_i(rms(x))
    x += residual_multiplier * W_down (silu(W_gate y) * (W_up y)),  y = rms(x)
    logits = rms(x) E^T / logits_scaling

An ``attention`` layer: q, k, v, o without bias, each key and value head
serving heads / kv_heads query heads, causal softmax(q k^T *
attention_multiplier) v; nothing is added to or rotated in q and k.

A ``mamba2`` layer, with d_inner = heads * d_head and g groups of state
width N:

    [z | xBC | dt] = W_in y              widths d_inner | d_inner + 2 g N | heads
    xBC = silu(conv(xBC) + b)            depthwise, d_conv taps, zeros before 0
    [x | B | C] = xBC                    widths d_inner | g N | g N
    dt = softplus(dt + dt_bias);  a = -exp(A_log)          a head
    S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T             d_head x N a head
    y_t = S_t C_t + D x_t
    out = W_out (g * rsqrt(mean(g^2 over d_inner) + eps) * w),  g = y * silu(z)

**The recurrence runs position by position** (a ``lax.scan`` over t of
the two lines above), not in the chunked algebra the program uses: the
two share no derivation.

To the contract at the top of ``transformer_lm.py``: float32 under
``jax.default_matmul_precision("highest")``, nothing imported from the
program, the program's parameter tree in (``tok_emb/embedding``,
``block_<i>/{ln_attn, ln_mlp}/scale``, ``block_<i>/mlp/{gate, up,
fc2}/kernel``, ``ln_final/scale``; an attention layer's
``attn/{query, key, value, out}/kernel``; a state-space layer's
``mamba/{in_proj/kernel, conv_kernel [taps, channels], conv_bias,
dt_bias, A_log, D, norm_scale, out_proj/kernel}``, where the
convolution's last tap multiplies the current position). It uses the
allowances the contract gives and nothing else: ``jax.checkpoint``
around each layer and around a group of positions of the recurrence,
``jax.lax.map`` over the sequences of the recurrence, over heads and
blocks of queries of attention and over blocks of rows at the head.
They change what is kept for the backward pass, not one number that is
computed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the most positions of the recurrence whose states are kept at once for
# the backward pass, the most queries of a head and the most rows of the
# vocabulary head that are computed at once
POSITION_GROUP = 128
QUERY_BLOCK = 1024
ROW_BLOCK = 2048


def arguments(model: dict, traffic: dict) -> dict:
    if traffic["objective"] != "causal_lm" or not model["causal"]:
        raise ValueError("this family is a causal language model")
    if (model["position"], model["norm"], model["activation"],
            model["tie_embeddings"]) != ("none", "rmsnorm", "swiglu", True):
        raise ValueError(
            "this family has no position code, RMSNorm, a SwiGLU MLP and "
            "a tied head")
    return dict(
        num_layers=model["num_layers"],
        layer_types=tuple(model["layer_types"]),
        kv_heads=model["num_kv_heads"], eps=model["layernorm_epsilon"],
        mamba=dict(heads=model["mamba_n_heads"],
                   d_head=model["mamba_d_head"],
                   d_state=model["mamba_d_state"],
                   groups=model["mamba_n_groups"],
                   taps=model["mamba_d_conv"]),
        embedding_multiplier=model["embedding_multiplier"],
        residual_multiplier=model["residual_multiplier"],
        attention_multiplier=model["attention_multiplier"],
        logits_scaling=model["logits_scaling"])


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


def _attend(q, k, v, scale):
    """q, k, v: [heads, B, T, d] -> [heads, B, T, d], a head at a time
    and inside it a block of queries at a time."""
    t = q.shape[2]
    rows = _divisor(t, QUERY_BLOCK)
    keys = jnp.arange(t)

    def head(qh, kh, vh):  # [B, T, d]
        def queries(qb, index):  # [rows, B, d], [rows]
            s = jnp.einsum("qbd,bkd->bqk", qb, kh) * scale
            s = jnp.where((keys[None, :] <= index[:, None])[None], s,
                          -jnp.inf)
            return jnp.einsum("bqk,bkd->qbd", jax.nn.softmax(s, -1), vh)

        out = _mapped(queries, t // rows, qh.transpose(1, 0, 2), keys)
        return out.transpose(1, 0, 2)

    return jax.lax.map(lambda xs: jax.checkpoint(head)(*xs), (q, k, v))


def _attention(y, a, *, kv_heads, scale):
    q = jnp.einsum("bth,hnd->btnd", y, a["query"]["kernel"])
    k = jnp.einsum("bth,hnd->btnd", y, a["key"]["kernel"])
    v = jnp.einsum("bth,hnd->btnd", y, a["value"]["kernel"])
    if k.shape[2] != kv_heads:
        raise ValueError(f"{k.shape[2]} key heads in the parameters, "
                         f"{kv_heads} in the configuration")
    k = jnp.repeat(k, q.shape[2] // kv_heads, axis=2)
    v = jnp.repeat(v, q.shape[2] // kv_heads, axis=2)
    o = _attend(*(z.transpose(2, 0, 1, 3) for z in (q, k, v)),
                scale).transpose(1, 2, 0, 3)
    return jnp.einsum("bqnd,ndh->bqh", o, a["out"]["kernel"])


def _recurrence(x, dt, a, b, c):
    """One sequence, position by position: x [T, G, R, P] (G groups of R
    heads), dt [T, G, R], a [G, R], b and c [T, G, N] (a group's, shared
    by its heads) -> [T, G, R, P], S_t C_t with S_t = exp(dt_t a)
    S_{t-1} + dt_t x_t B_t^T and S_{-1} = 0."""
    t, g, r, p = x.shape
    n = b.shape[-1]
    size = _divisor(t, POSITION_GROUP)

    def position(state, step):
        x_t, dt_t, b_t, c_t = step
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("grpn,gn->grp", state, c_t)

    @jax.checkpoint
    def group(state, steps):
        return jax.lax.scan(position, state, steps)

    steps = tuple(z.reshape(t // size, size, *z.shape[1:])
                  for z in (x, dt, b, c))
    _, y = jax.lax.scan(group, jnp.zeros((g, r, p, n), jnp.float32),
                        steps)
    return y.reshape(t, g, r, p)


def _convolved(x, kernel, bias):
    """x [B, T, C], kernel [taps, C], bias [C]: y_t = bias + sum_j
    kernel[j] x[t - (taps - 1) + j], zeros before position 0."""
    taps, t = kernel.shape[0], x.shape[1]
    x = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(kernel[j] * x[:, j:j + t] for j in range(taps))


def mamba_mixer(y, m, *, heads, d_head, d_state, groups, taps, eps):
    """One state-space mixer: y [B, T, h] (the block's normed input)
    and the layer's ``mamba`` parameters -> [B, T, h]."""
    bsz, t, _ = y.shape
    d_inner, bc = heads * d_head, groups * d_state
    if m["conv_kernel"].shape != (taps, d_inner + 2 * bc) \
            or m["A_log"].shape != (heads,):
        raise ValueError(
            f"a convolution of {m['conv_kernel'].shape} and "
            f"{m['A_log'].shape[0]} heads in the parameters, "
            f"{(taps, d_inner + 2 * bc)} and {heads} in the configuration")
    zxbcdt = y @ m["in_proj"]["kernel"]
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * bc], -1)
    xbc = jax.nn.silu(_convolved(xbc, m["conv_kernel"], m["conv_bias"]))
    x, b, c = jnp.split(xbc, [d_inner, d_inner + bc], -1)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    per_group = heads // groups
    x = x.reshape(bsz, t, groups, per_group, d_head)
    a = -jnp.exp(m["A_log"]).reshape(groups, per_group)
    s = jax.lax.map(
        lambda seq: _recurrence(seq[0], seq[1], a, seq[2], seq[3]),
        (x, dt.reshape(bsz, t, groups, per_group),
         b.reshape(bsz, t, groups, d_state),
         c.reshape(bsz, t, groups, d_state)))
    s = s + m["D"].reshape(groups, per_group, 1) * x
    g = s.reshape(bsz, t, d_inner) * jax.nn.silu(z)
    return _rms(g, m["norm_scale"], eps) @ m["out_proj"]["kernel"]


def _block(x, p, kind, *, kv_heads, eps, mamba, attention_multiplier,
           residual_multiplier):
    y = _rms(x, p["ln_attn"]["scale"], eps)
    if kind == "attention":
        mixed = _attention(y, p["attn"], kv_heads=kv_heads,
                           scale=attention_multiplier)
    elif kind == "mamba2":
        mixed = mamba_mixer(y, p["mamba"], eps=eps, **mamba)
    else:
        raise ValueError(f"this family has no layer of kind {kind!r}")
    x = x + residual_multiplier * mixed
    y = _rms(x, p["ln_mlp"]["scale"], eps)
    m = p["mlp"]
    h = jax.nn.silu(y @ m["gate"]["kernel"]) * (y @ m["up"]["kernel"])
    return x + residual_multiplier * (h @ m["fc2"]["kernel"])


def hidden(params, tokens, *, num_layers, layer_types,
           embedding_multiplier, logits_scaling, **kw):
    """([B, T, h] float32: the final norm's output over
    ``logits_scaling``, the tied head's kernel [V, h])."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    if len(layer_types) != num_layers:
        raise ValueError(f"{len(layer_types)} kinds for {num_layers} "
                         f"layers")
    x = embedding_multiplier * p["tok_emb"]["embedding"][tokens]
    for i, kind in enumerate(layer_types):
        x = jax.checkpoint(
            lambda x, bp, kind=kind: _block(x, bp, kind, **kw))(
                x, p[f"block_{i}"])
    x = _rms(x, p["ln_final"]["scale"], kw["eps"]) / logits_scaling
    return x, p["tok_emb"]["embedding"]


def _nll(rows, head, targets):
    """Sum over the rows of -log softmax(row . head^T)[target], a block
    of rows at a time."""
    def part(x, target):
        lg = x @ head.T
        return jnp.sum(
            jax.scipy.special.logsumexp(lg, axis=-1)
            - jnp.take_along_axis(lg, target[:, None], -1)[:, 0])[None]

    n = rows.shape[0]
    return jnp.sum(_mapped(part, n // _divisor(n, ROW_BLOCK), rows,
                           targets))


def nll_sum(params, batch, **kw):
    """(sum of the negative log likelihoods, positions that count) of
    one block of sequences ``(tokens,)``: each position predicts the
    next token, the last one nothing."""
    with jax.default_matmul_precision("highest"):
        tokens = batch[0]
        x, head = hidden(params, tokens, **kw)
        n, t = tokens.shape
        total = _nll(x[:, :-1].reshape(n * (t - 1), -1), head,
                     tokens[:, 1:].reshape(-1))
        return total, jnp.float32(n * (t - 1))


def mean_loss(params, batch, **kw):
    total, count = nll_sum(params, batch, **kw)
    return total / count
