"""The state-space mixer (``horovod_tpu/models/mamba.py``) against the
recurrence run position by position.

The chunked scan and the plain reference of the benchmark
(``benchmarks/reference/state_space_hybrid_lm.mamba_mixer``: a
``lax.scan`` over t of S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T, y_t =
S_t C_t + D x_t) share no derivation, so agreement of the output and of
every parameter's gradient holds the chunk algebra, the padding, the
groups; seeded weights, float32 (tight) and
bf16 (the job's kind of limit).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from horovod_tpu.models import mamba  # noqa: E402
from horovod_tpu.utils import metrics  # noqa: E402

REFERENCE = harness.load_reference("state_space_hybrid_lm")
HIDDEN = 32
SIZES = dict(n_heads=4, d_head=16, d_state=8, d_conv=4, n_groups=1)


def mixer(dtype=jnp.float32, chunk=16, **sizes):
    return mamba.Mamba2Mixer(hidden_size=HIDDEN, chunk_size=chunk,
                             dtype=dtype, **{**SIZES, **sizes})


def seeded(t, seed=0, batch=2, **sizes):
    """(input [B, T, hidden], the mixer's parameters with every
    one-per-head and convolution parameter drawn, not at its tidy
    initial value, a cotangent for the output)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    u = jax.random.normal(keys[0], (batch, t, HIDDEN), jnp.float32)
    params = mixer(**sizes).init(keys[1], u)["params"]
    params = dict(params)
    h = {**SIZES, **sizes}["n_heads"]
    params["D"] = 1.0 + 0.5 * jax.random.normal(keys[2], (h,))
    params["A_log"] = params["A_log"] + 0.3 * jax.random.normal(
        keys[3], (h,))
    params["norm_scale"] = 1.0 + 0.2 * jax.random.normal(
        keys[4], params["norm_scale"].shape)
    return u, params, jax.random.normal(keys[5], u.shape, jnp.float32)


def reference(params, u, **sizes):
    s = {**SIZES, **sizes}
    with jax.default_matmul_precision("highest"):
        return REFERENCE.mamba_mixer(
            u, params, heads=s["n_heads"], d_head=s["d_head"],
            d_state=s["d_state"], groups=s["n_groups"], taps=s["d_conv"],
            eps=1e-5)


def relative(a, b):
    a, b = (np.asarray(z, np.float64) for z in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def both(t, dtype, chunk, **sizes):
    """((output, gradients) of the mixer, of the reference)."""
    u, params, ct = seeded(t, **sizes)
    model = mixer(dtype, chunk, **sizes)

    def system(p, u):
        return jnp.sum(model.apply({"params": p}, u).astype(
            jnp.float32) * ct)

    def plain(p, u):
        return jnp.sum(reference(p, u, **sizes) * ct)

    out = model.apply({"params": params}, u)
    want = reference(params, u, **sizes)
    return ((out, jax.grad(system, (0, 1))(params, u)),
            (want, jax.grad(plain, (0, 1))(params, u)))


# T below a chunk, equal to one, a multiple of it, and no multiple of it
LENGTHS = [(8, 16), (16, 16), (48, 16), (40, 16), (50, 16)]


@pytest.mark.parametrize("t,chunk", LENGTHS)
def test_float32_output_and_every_gradient_are_the_recurrences(t, chunk):
    (out, grads), (want, want_grads) = both(t, jnp.float32, chunk)
    assert out.shape == want.shape and relative(out, want) < 2e-5
    got = jax.tree_util.tree_leaves_with_path(grads)
    ref = jax.tree_util.tree_leaves(want_grads)
    assert len(got) == len(ref) == 9  # eight parameters and the input
    for (path, g), r in zip(got, ref):
        assert relative(g, r) < 1e-4, jax.tree_util.keystr(path)


@pytest.mark.parametrize("t,chunk", [(8, 16), (48, 16), (50, 16)])
def test_bf16_output_and_every_gradient_are_near_the_recurrences(t, chunk):
    """bf16 keeps 8 bits: a value is off by up to 2^-9. The gradient is
    held as the job holds it, by the norm over all leaves under 3e-2;
    a leaf alone (four numbers a head) is held to 1e-1."""
    (out, grads), (want, want_grads) = both(t, jnp.bfloat16, chunk)
    assert out.dtype == jnp.bfloat16
    assert relative(out.astype(jnp.float32), want) < 1e-2
    got, ref = (jax.tree_util.tree_leaves(g) for g in (grads, want_grads))
    flat = [np.concatenate([np.ravel(np.asarray(x, np.float64))
                            for x in leaves]) for leaves in (got, ref)]
    assert relative(*flat) < 3e-2
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            ref):
        assert relative(g, r) < 1e-1, jax.tree_util.keystr(path)


@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 2e-5),
                                         (jnp.bfloat16, 1e-2)])
def test_the_chunk_size_changes_no_result(dtype, limit):
    u, params, _ = seeded(64)
    outs = [mixer(dtype, chunk).apply({"params": params}, u).astype(
        jnp.float32) for chunk in (16, 64, 256)]
    want = reference(params, u)
    for out in outs:
        assert relative(out, want) < limit
    assert relative(outs[0], outs[1]) < limit
    assert relative(outs[1], outs[2]) < limit


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_groups_of_heads_share_their_b_and_c(groups):
    (out, grads), (want, want_grads) = both(48, jnp.float32, 16,
                                            n_groups=groups)
    assert relative(out, want) < 2e-5
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert relative(g, r) < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_change_at_position_i_moves_no_output_before_i(dtype):
    """Convolution and scan are causal, across chunk boundaries too:
    the outputs before the changed position are the same bits."""
    u, params, _ = seeded(48)
    model = mixer(dtype, 16)
    out = model.apply({"params": params}, u)
    for i in (0, 5, 16, 17, 47):
        moved = model.apply({"params": params},
                            u.at[:, i].add(1.0))
        assert np.array_equal(np.asarray(out[:, :i], np.float32),
                              np.asarray(moved[:, :i], np.float32)), i
        assert not np.array_equal(np.asarray(out[:, i], np.float32),
                                  np.asarray(moved[:, i], np.float32)), i


def test_the_convolution_reads_zeros_before_position_zero():
    x = jnp.arange(1.0, 7.0).reshape(1, 6, 1)
    kernel = jnp.array([[1.0], [10.0], [100.0], [1000.0]])
    y = mamba.causal_conv(x, kernel, jnp.array([0.5]))
    # the last tap multiplies the current position
    assert y[0, :, 0].tolist() == [1000.5, 2100.5, 3210.5, 4321.5,
                                   5432.5, 6543.5]


def test_initial_values_are_the_configurations_assumed():
    params = mixer().init(jax.random.PRNGKey(3),
                          jnp.zeros((1, 8, HIDDEN)))["params"]
    assert set(params) == {"in_proj", "conv_kernel", "conv_bias",
                           "dt_bias", "A_log", "D", "norm_scale",
                           "out_proj"}
    assert params["in_proj"]["kernel"].shape == (
        HIDDEN, 2 * 64 + 2 * 8 + 4)
    assert params["conv_kernel"].shape == (4, 64 + 16)
    np.testing.assert_allclose(np.exp(params["A_log"]), [1, 2, 3, 4],
                               rtol=1e-6)
    assert np.all(np.asarray(params["D"]) == 1)
    assert np.all(np.asarray(params["norm_scale"]) == 1)
    dt = np.asarray(jax.nn.softplus(params["dt_bias"]))
    lo, hi = mamba.DT_INIT_RANGE
    assert np.all((dt >= lo * 0.999) & (dt <= hi * 1.001))
    assert np.all(np.abs(np.asarray(params["conv_kernel"])) <= 0.5)
    assert all(v.dtype == jnp.float32
               for v in jax.tree_util.tree_leaves(params))


def test_the_scans_gauges_say_what_it_was_built_for():
    was = metrics.enabled()
    metrics.enable()
    metrics.registry.clear()
    try:
        u, params, _ = seeded(40)
        jax.eval_shape(lambda p, u: mixer(chunk=16).apply(
            {"params": p}, u), params, u)
        snap = metrics.registry.snapshot()
    finally:
        metrics.registry.clear()
        if not was:
            metrics.disable()
    got = {name: value for name, series in snap.items()
           if name.startswith("hvd_mamba_") for value in series.values()}
    # 40 positions padded to three chunks of 16; 4 x 16 x 8 float32
    assert got == {"hvd_mamba_chunk": 16,
                   "hvd_mamba_chunks_per_sequence": 3,
                   "hvd_mamba_state_bytes_per_sequence": 4 * 16 * 8 * 4}
