"""Vocab-blocked fused LM-head cross-entropy vs the materializing math
(ops/fused_cross_entropy.py): values and both gradients must match the
naive logsumexp computation that builds the full [N, V] logits."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.ops.fused_cross_entropy import fused_linear_cross_entropy


def _naive(hidden, w, targets, valid=None, mean=True):
    """Materializing oracle with the MODEL losses' normalization: the
    user `valid` mask defines the denominator; out-of-range ids inside
    it contribute zero NLL but still count (causal_lm_loss semantics)."""
    x = hidden.reshape(-1, hidden.shape[-1]).astype(jnp.float32)
    logits = x @ w.astype(jnp.float32)
    t = targets.reshape(-1)
    va = jnp.ones(t.shape, bool) if valid is None else valid.reshape(-1)
    in_range = (t >= 0) & (t < w.shape[1])
    tc = jnp.where(in_range, t, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
    nll = jnp.where(va & in_range, lse - tgt, 0.0)
    denom = jnp.maximum(jnp.sum(va), 1)
    return jnp.sum(nll) / (denom if mean else 1)


@pytest.mark.parametrize("block", [16, 64, 128])
def test_matches_naive_values_and_grads(block):
    rng = np.random.RandomState(0)
    N, H, V = 24, 32, 100  # V not a multiple of any block size
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(H, V)) * 0.1, jnp.float32)
    t = jnp.asarray(rng.randint(0, V, N))

    def fused(x, w):
        loss, _ = fused_linear_cross_entropy(x, w, t, block_vocab=block)
        return loss

    def naive(x, w):
        return _naive(x, w, t)

    lf, gf = jax.value_and_grad(fused, argnums=(0, 1))(x, w)
    ln, gn = jax.value_and_grad(naive, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-5)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5)


def test_masked_and_out_of_range_targets():
    """Invalid rows (MLM unmasked positions, -1 sentinels) contribute
    exactly zero loss and zero gradient."""
    rng = np.random.RandomState(1)
    N, H, V = 16, 16, 50
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(H, V)) * 0.1, jnp.float32)
    t = jnp.asarray(rng.randint(0, V, N)).at[3].set(-1)
    valid = jnp.asarray(rng.rand(N) < 0.5)

    def fused(x, w):
        loss, n = fused_linear_cross_entropy(
            x, w, t, valid=valid, block_vocab=32
        )
        return loss

    def naive(x, w):
        return _naive(x, w, t, valid=valid)

    lf, gf = jax.value_and_grad(fused, argnums=(0, 1))(x, w)
    ln, gn = jax.value_and_grad(naive, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-5)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5)
    # rows the mask kills must get zero dx
    dx = np.asarray(gf[0])
    dead = ~np.asarray(valid) | (np.asarray(t) < 0)
    np.testing.assert_allclose(dx[dead], 0.0, atol=1e-7)


def test_out_of_range_counts_in_denominator():
    """Normalization parity with causal_lm_loss: a non-sentinel id >= V
    (valid=True) contributes zero NLL but still counts in n and the
    mean's denominator."""
    rng = np.random.RandomState(5)
    N, H, V = 8, 8, 10
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(H, V)) * 0.1, jnp.float32)
    t = jnp.asarray(rng.randint(0, V, N)).at[0].set(V + 3)
    loss, n = fused_linear_cross_entropy(x, w, t, block_vocab=4)
    assert int(n) == N  # the corrupt id still counted
    ref = _naive(x, w, t)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)


def test_bf16_hidden_path():
    """Model-dtype activations: the matmuls run bf16→f32 like the head
    they replace; values agree with the f32 naive loss at bf16
    tolerance."""
    rng = np.random.RandomState(2)
    N, H, V = 32, 64, 80
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(H, V)) * 0.1, jnp.float32)
    t = jnp.asarray(rng.randint(0, V, N))
    loss, n = fused_linear_cross_entropy(x, w, t, block_vocab=32)
    ref = _naive(x.astype(jnp.float32), w, t)
    assert int(n) == N
    np.testing.assert_allclose(float(loss), float(ref), rtol=2e-2)


def test_sum_mode_and_count():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.normal(size=(4, 6, 8)), jnp.float32)  # [B,T,H]
    w = jnp.asarray(rng.normal(size=(8, 20)) * 0.1, jnp.float32)
    t = jnp.asarray(rng.randint(0, 20, (4, 6)))
    s_loss, n = fused_linear_cross_entropy(x, w, t, mean=False)
    m_loss, _ = fused_linear_cross_entropy(x, w, t, mean=True)
    assert int(n) == 24
    np.testing.assert_allclose(float(s_loss) / 24, float(m_loss),
                               rtol=1e-6)


def test_fused_causal_lm_loss_matches_model_loss():
    """fused_causal_lm_loss(hidden, w, tokens) equals
    causal_lm_loss(logits, tokens) for a real tied-embedding
    transformer at f32."""
    import dataclasses

    from horovod_tpu.models import GPT2_SMALL, Transformer
    from horovod_tpu.models.transformer import causal_lm_loss
    from horovod_tpu.ops.fused_cross_entropy import fused_causal_lm_loss

    cfg = dataclasses.replace(
        GPT2_SMALL, num_layers=2, hidden_size=64, num_heads=4,
        max_seq_len=32, vocab_size=96, dtype=jnp.float32,
    )
    model = Transformer(cfg)
    rng = np.random.RandomState(7)
    toks = jnp.asarray(rng.randint(0, 96, (3, 32)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]

    logits = model.apply({"params": params}, toks)
    ref, n_ref = causal_lm_loss(logits, toks)

    hidden = model.apply({"params": params}, toks, return_hidden=True)
    w = params["tok_emb"]["embedding"].T
    fused, n_fused = fused_causal_lm_loss(hidden, w, toks, block_vocab=32)
    assert int(n_ref) == int(n_fused)
    np.testing.assert_allclose(float(fused), float(ref), rtol=1e-5)


# -- a row's own weight (a block-diffusion step's 1/t) ------------------------

def _weighted_case(dtype=jnp.float32):
    rng = np.random.RandomState(3)
    N, H, V = 40, 32, 100
    x = jnp.asarray(rng.normal(size=(2, N // 2, H)), dtype)
    w = jnp.asarray(rng.normal(size=(H, V)) * 0.1, jnp.float32)
    t = jnp.asarray(rng.randint(0, V, (2, N // 2)))
    valid = jnp.asarray(rng.rand(2, N // 2) < 0.6)
    weight = jnp.asarray(1.0 / rng.uniform(0.1, 1.0, (2, N // 2)),
                         jnp.float32)
    return x, w, t, valid, weight


def _dense_weighted(x, w, t, valid, weight, mean):
    logits = x.astype(jnp.float32) @ w
    nll = jax.scipy.special.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, t[..., None], -1)[..., 0]
    total = jnp.sum(jnp.where(valid, weight * nll, 0.0))
    return total / jnp.maximum(jnp.sum(valid), 1) if mean else total


@pytest.mark.parametrize("mean", [False, True])
def test_per_row_weight_matches_the_dense_expression(mean):
    """`weight` multiplies a row's nll in the loss and its row of both
    backward products; `mean=False` returns the weighted sum."""
    x, w, t, valid, weight = _weighted_case()

    def fused(x, w):
        return fused_linear_cross_entropy(
            x, w, t, valid=valid, weight=weight, block_vocab=32,
            mean=mean)[0]

    def dense(x, w):
        return _dense_weighted(x, w, t, valid, weight, mean)

    got, (gx, gw) = jax.value_and_grad(fused, argnums=(0, 1))(x, w)
    want, (wx, ww) = jax.value_and_grad(dense, argnums=(0, 1))(x, w)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_allclose(gx, wx, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gw, ww, rtol=1e-4, atol=1e-6)
    # a weight of one is no weight, and the count is the valid rows'
    ones, n = fused_linear_cross_entropy(
        x, w, t, valid=valid, weight=jnp.ones_like(weight), mean=mean)
    plain, _ = fused_linear_cross_entropy(x, w, t, valid=valid, mean=mean)
    assert float(ones) == pytest.approx(float(plain), rel=1e-6)
    assert int(n) == int(valid.sum())


def test_per_row_weight_is_data_and_gets_no_gradient():
    x, w, t, valid, weight = _weighted_case()
    g = jax.grad(lambda wt: fused_linear_cross_entropy(
        x, w, t, valid=valid, weight=wt, mean=False)[0])(weight)
    assert float(jnp.abs(g).max()) == 0.0


def test_without_a_weight_the_loss_lowers_to_the_text_it_had():
    """The accepted cells call the head without `weight`: the lowered
    text of its value and gradient is the one of before the argument
    (SHA-256 of `lower().as_text()` taken on the parent commit of PR 33
    and on this tree, jax 0.9.0, the one installation here)."""
    import hashlib

    from horovod_tpu.ops.fused_cross_entropy import fused_causal_lm_loss

    x = jnp.zeros((2, 16, 32), jnp.bfloat16)
    w = jnp.zeros((32, 100), jnp.float32)
    t = jnp.zeros((2, 16), jnp.int32)
    m = jnp.zeros((2, 16), bool)
    pinned = {
        "0ccd44855bdc6f94b0083d07db43d964580182d5e0d80fc438a5bc9f33f8fbf3":
            lambda x, w: fused_linear_cross_entropy(
                x, w, t, valid=m, block_vocab=64)[0],
        "ce6a2a1c79ebfa4bd8d5eb4d144de9fd95da5d61a9684a2408205b8d2e5dcf8b":
            lambda x, w: fused_causal_lm_loss(x, w, t, block_vocab=64)[0],
    }
    for digest, fn in pinned.items():
        text = jax.jit(jax.value_and_grad(fn, argnums=(0, 1))).lower(
            x, w).as_text()
        assert hashlib.sha256(text.encode()).hexdigest() == digest
    # and with one the text differs: the multiplications are there
    weighted = jax.jit(jax.value_and_grad(
        lambda x, w: fused_linear_cross_entropy(
            x, w, t, valid=m, weight=jnp.ones((2, 16)), block_vocab=64)[0],
        argnums=(0, 1))).lower(x, w).as_text()
    assert hashlib.sha256(weighted.encode()).hexdigest() not in pinned
