"""Plain reference of the fixture's family: a causal decoder block with
RMSNorm, rotary positions, grouped-query attention, a SwiGLU MLP, no
bias anywhere and an output head of its own (not the token embedding's
transpose). It exists to show the harness a second family, found by
name alone; its contract is the one written at the top of
``benchmarks/reference/transformer_lm.py``.

float32 under ``jax.default_matmul_precision("highest")``, nothing
imported from the program. It takes the program's parameter tree:
``tok_emb/embedding``, ``block_<i>/{ln_attn/scale, attn/{query,key,
value,out}/kernel, ln_mlp/scale, mlp/{gate,up,fc2}/kernel}``,
``ln_final/scale``, ``lm_head/kernel``. The rotation pairs the two
halves of a head (dimension ``j`` with ``j + d/2``), angle
``position / theta ** (2j / d)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def arguments(model: dict, traffic: dict) -> dict:
    if traffic["objective"] != "causal_lm" or not model["causal"]:
        raise ValueError("this family is a causal language model")
    return dict(num_layers=model["num_layers"],
                kv_heads=model["num_kv_heads"],
                theta=model["rope_theta"], eps=model["layernorm_epsilon"])


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def _rotate(x, theta):
    """x: [B, T, heads, d]."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _block(x, p, *, kv_heads, theta, eps):
    y = _rms(x, p["ln_attn"], eps)
    a = p["attn"]
    q = _rotate(jnp.einsum("bth,hnd->btnd", y, a["query"]["kernel"]),
                theta)
    k = _rotate(jnp.einsum("bth,hnd->btnd", y, a["key"]["kernel"]), theta)
    v = jnp.einsum("bth,hnd->btnd", y, a["value"]["kernel"])
    if k.shape[2] != kv_heads:
        raise ValueError(f"{k.shape[2]} key heads in the parameters, "
                         f"{kv_heads} in the configuration")
    # each key and value head serves heads / kv_heads query heads
    k = jnp.repeat(k, q.shape[2] // kv_heads, axis=2)
    v = jnp.repeat(v, q.shape[2] // kv_heads, axis=2)
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(q.shape[-1])
    t = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("bqnd,ndh->bqh", o, a["out"]["kernel"])
    y = _rms(x, p["ln_mlp"], eps)
    m = p["mlp"]
    h = jax.nn.silu(y @ m["gate"]["kernel"]) * (y @ m["up"]["kernel"])
    return x + h @ m["fc2"]["kernel"]


def logits(params, tokens, *, num_layers, **kw):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = p["tok_emb"]["embedding"][tokens]
    for i in range(num_layers):
        x = _block(x, p[f"block_{i}"], **kw)
    return _rms(x, p["ln_final"], kw["eps"]) @ p["lm_head"]["kernel"]


def nll_sum(params, batch, **kw):
    with jax.default_matmul_precision("highest"):
        lg = logits(params, batch[0], **kw)[:, :-1]
        targets = batch[0][:, 1:]
        nll = jax.scipy.special.logsumexp(lg, axis=-1) \
            - jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
        return jnp.sum(nll), jnp.float32(nll.size)


def mean_loss(params, batch, **kw):
    total, count = nll_sum(params, batch, **kw)
    return total / count
