"""Fused LM-head + cross-entropy: vocab-blocked, logits never hit HBM.

The reference has no model compute (it wraps framework models), so this
is a TPU-first addition in the same spirit as the flash kernels: the
transformer family's other memory cliff. A materialized [B·T, V] logits
tensor is 750 MB for BERT-L (V=30k, T=512, B=24) and ~4 GB at Llama-3
scale (V=128k, T=8k) — written once forward, re-read by logsumexp, and
re-materialized backward. Here the head matmul and the loss fuse into
one `lax.scan` over vocab blocks: each step computes an [N, Vb] logits
block on the MXU, folds it into online logsumexp + target-logit
accumulators, and discards it; the backward recomputes blocks from the
saved logsumexp and accumulates dX / dW the same way. Peak live memory
is O(N·Vb) instead of O(N·V).

No Pallas needed: the block matmuls are already ideal MXU shapes and XLA
fuses the elementwise epilogues; the win is purely not materializing V.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import scopes

NEG_INF = -1e30


def _pad_w(w, block: int):
    """Pad [h, V] on V to a block multiple (blocks are then read in
    place with dynamic slices — no [nb, h, Vb] transposed copy, which at
    Llama-3 scale would be a ~2 GB rearrangement per pass)."""
    v = w.shape[1]
    pad = (-v) % block
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    return w, v


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _fused_ce(x, w, targets, valid, gscale, weight, block_vocab):
    loss, _ = _fused_ce_fwd(x, w, targets, valid, gscale, weight,
                            block_vocab)
    return loss


def _fused_ce_fwd(x, w, targets, valid, gscale, weight, block_vocab):
    n, h = x.shape
    wp, v = _pad_w(w, block_vocab)
    nb = wp.shape[1] // block_vocab
    xc = x  # keep model dtype into the MXU; accumulate in f32

    def step(carry, base):
        m, l, tgt = carry
        w_blk = lax.dynamic_slice_in_dim(wp, base, block_vocab, axis=1)
        logits = jnp.dot(
            xc, w_blk.astype(xc.dtype),
            preferred_element_type=jnp.float32,
        )  # [N, Vb]
        cols = base + lax.broadcasted_iota(
            jnp.int32, logits.shape, 1
        )
        logits = jnp.where(cols < v, logits, NEG_INF)  # vocab padding
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1
        )
        # target logit if it falls in this block
        in_blk = (targets >= base) & (targets < base + block_vocab)
        local = jnp.clip(targets - base, 0, block_vocab - 1)
        t_here = jnp.take_along_axis(
            logits, local[:, None], axis=-1
        )[:, 0]
        tgt = jnp.where(in_blk, t_here, tgt)
        return (m_new, l, tgt), None

    bases = jnp.arange(nb, dtype=jnp.int32) * block_vocab
    m0 = jnp.full((n,), NEG_INF, jnp.float32)
    (m, l, tgt), _ = lax.scan(
        step, (m0, jnp.zeros((n,), jnp.float32), jnp.full((n,), NEG_INF)),
        bases,
    )
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    nll = jnp.where(valid, lse - tgt, 0.0)
    if weight is not None:  # a row's own weight, [N] float32
        nll = nll * weight
    nll_sum = jnp.sum(nll)
    loss = nll_sum * gscale
    return loss, (x, w, targets, valid, lse, gscale, weight, nll_sum)


def _fused_ce_bwd(block_vocab, residuals, g):
    x, w, targets, valid, lse, gscale, weight, nll_sum = residuals
    n, h = x.shape
    wp, v = _pad_w(w, block_vocab)
    nb = wp.shape[1] // block_vocab
    # d loss / d logit_ib = gscale · (softmax_ib − onehot_ib) per valid
    # row, times the incoming cotangent (and the row's own weight, which
    # so reaches its row of both products below)
    row = (
        g * gscale * jnp.where(valid, 1.0, 0.0)
    ).astype(jnp.float32)
    if weight is not None:
        row = row * weight

    def step(carry, base):
        dx, dwp = carry
        w_blk = lax.dynamic_slice_in_dim(wp, base, block_vocab, axis=1)
        logits = jnp.dot(
            x, w_blk.astype(x.dtype), preferred_element_type=jnp.float32
        )
        cols = base + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        p = jnp.where(
            cols < v, jnp.exp(logits - lse[:, None]), 0.0
        )
        onehot = (cols == targets[:, None]).astype(jnp.float32)
        ds = (p - onehot) * row[:, None]  # [N, Vb] f32
        dsx = ds.astype(x.dtype)
        dx = dx + jnp.dot(
            dsx, w_blk.astype(x.dtype).T,
            preferred_element_type=jnp.float32,
        )
        dw_blk = jnp.dot(
            x.T, dsx, preferred_element_type=jnp.float32
        )  # [h, Vb]
        dwp = lax.dynamic_update_slice_in_dim(dwp, dw_blk, base, axis=1)
        return (dx, dwp), None

    bases = jnp.arange(nb, dtype=jnp.int32) * block_vocab
    (dx, dwp), _ = lax.scan(
        step,
        (jnp.zeros((n, h), jnp.float32),
         jnp.zeros(wp.shape, jnp.float32)),
        bases,
    )
    dw = dwp[:, :v]
    return (
        dx.astype(x.dtype), dw.astype(w.dtype), None, None,
        # gscale is differentiable (a caller may thread dynamic loss
        # scaling through it): d loss / d gscale = Σ nll, saved forward
        g * nll_sum,
        # the rows' weights are data (a noise level's 1/t), not trained
        None if weight is None else jnp.zeros_like(weight),
    )


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


@jax.named_scope(scopes.LOSS_HEAD)
def fused_linear_cross_entropy(
    hidden, w, targets, *, valid: Optional[jnp.ndarray] = None,
    weight: Optional[jnp.ndarray] = None,
    block_vocab: int = 8192, mean: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cross-entropy of `hidden @ w` against `targets` without ever
    materializing the [N, V] logits.

    Args:
      hidden: [..., h] pre-head activations (any leading shape).
      w: [h, V] head kernel — for tied embeddings pass
        `params["tok_emb"]["embedding"].T`.
      targets: [...] int class ids (same leading shape as hidden).
      valid: [...] bool; False rows contribute zero (padding / unmasked
        MLM positions). Default: all valid.
      weight: [...] float; a row's negative log likelihood counts this
        many times in the loss (a block-diffusion step's 1/t). Default:
        once.
      block_vocab: vocab tile width (the live-memory knob).
      mean: divide by the number of valid rows (like the model losses);
        False returns the (weighted) sum.

    Returns (loss, n_valid).
    """
    h = hidden.shape[-1]
    x = hidden.reshape(-1, h)
    t = targets.reshape(-1).astype(jnp.int32)
    va = (
        jnp.ones(t.shape, bool) if valid is None else valid.reshape(-1)
    )
    # normalization parity with the model losses (causal_lm_loss /
    # mlm_loss): out-of-range non-sentinel ids contribute zero NLL but
    # still count in the denominator and the returned n
    in_range = (t >= 0) & (t < w.shape[1])
    contrib = va & in_range
    t = jnp.where(in_range, t, 0)
    n_valid = jnp.sum(va)
    denom = jnp.maximum(n_valid, 1).astype(jnp.float32)
    gscale = (1.0 / denom) if mean else jnp.float32(1.0)
    if weight is not None:
        weight = weight.reshape(-1).astype(jnp.float32)
    loss = _fused_ce(x, w, t, contrib, gscale, weight, int(block_vocab))
    return loss, n_valid


def fused_causal_lm_loss(
    hidden, w, tokens, *, ignore_index: int = -1,
    block_vocab: int = 8192,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Next-token LM loss from pre-head activations — the fused
    counterpart of models.transformer.causal_lm_loss(logits, tokens):
    positions predict tokens[:, 1:], `ignore_index` targets drop out,
    and the result is averaged over valid positions.

    `hidden`: [B, T, h] (model __call__ with return_hidden=True);
    `w`: [h, V] head kernel (tied: params["tok_emb"]["embedding"].T).
    Returns (loss, n_tokens)."""
    with jax.named_scope(scopes.LOSS_HEAD):
        targets = tokens[:, 1:]
        valid = targets != ignore_index
        hidden = hidden[:, :-1]
    return fused_linear_cross_entropy(
        hidden, w, targets, valid=valid, block_vocab=block_vocab,
    )
