"""Compressed collective data plane (docs/compression.md).

Covers the ISSUE-8 acceptance surface:
  * int8 quantize/dequant round-trip error bounds;
  * HOROVOD_COMPRESSION=none bitwise parity on the eager path
    (fast-path AND negotiated) and the SPMD path;
  * error-feedback residual carry across steps (optimizer-state leaves
    on SPMD, executor-held buffers on eager);
  * hierarchical outer-hop-only compression numerics vs the flat psum;
  * a small-MLP convergence test under int8+EF;
  * wire-byte accounting (logical vs sent) and knob/CLI plumbing.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.core.knobs import Knobs
from horovod_tpu.core.state import global_state
from horovod_tpu.optim import compression as comp
from horovod_tpu.ops import hierarchical


def _set_knobs(**kw):
    st = global_state()
    st.knobs = dataclasses.replace(st.knobs, **kw)


def _run8(body, per_rank_in, out_spec=P()):
    mesh = hvd.mesh()
    return jax.jit(
        shard_map(lambda x: body(x[0]), mesh=mesh, in_specs=P("hvd"),
                  out_specs=out_spec, check_vma=False)
    )(per_rank_in)


# ------------------------------------------------------------ primitives


def test_quantize_roundtrip_error_bound():
    rng = np.random.RandomState(0)
    for block in (64, 256):
        x = rng.uniform(-3, 3, (block * 7 + 13,)).astype(np.float32)
        dq = np.asarray(comp.quantize_dequantize(x, block))
        # per-block symmetric int8: |err| <= scale/2 = amax_block/254
        b = np.pad(x, (0, -len(x) % block)).reshape(-1, block)
        bound = np.repeat(np.abs(b).max(axis=1) / 254.0 + 1e-7, block)
        assert (np.abs(np.pad(x, (0, -len(x) % block)).reshape(-1)
                       - np.pad(dq, (0, -len(dq) % block)).reshape(-1))
                <= bound).all()


def test_int8_compressor_roundtrip():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(3, 77).astype(np.float32))
    wire, ctx = hvd.Compression.int8.compress(x)
    assert wire.dtype == jnp.int8
    back = hvd.Compression.int8.decompress(wire, ctx)
    assert back.shape == x.shape and back.dtype == x.dtype
    assert float(jnp.abs(back - x).max()) <= float(
        jnp.abs(x).max()) / 127.0
    # non-floating payloads pass through untouched
    ints = jnp.arange(10, dtype=jnp.int32)
    w2, c2 = hvd.Compression.int8.compress(ints)
    assert c2 is None and (np.asarray(w2) == np.asarray(ints)).all()


def test_zero_block_quantizes_to_zero():
    q, s = comp.quantize_blocks(jnp.zeros((512,), jnp.float32), 256)
    assert (np.asarray(q) == 0).all()
    assert (np.asarray(s) == 1.0).all()  # guarded divide
    assert (np.asarray(comp.dequantize_blocks(q, s, 256)) == 0).all()


def test_wire_sent_bytes():
    int8 = comp.parse_wire("int8")
    assert comp.wire_sent_bytes(1000, 4, None) == 4000
    assert comp.wire_sent_bytes(1000, 4, comp.parse_wire("bf16")) == 2000
    # padded payload + one f32 scale per 256-block
    assert comp.wire_sent_bytes(1000, 4, int8) == 1024 + 4 * 4
    assert 4000 / comp.wire_sent_bytes(1000, 4, int8) > 3.5


def test_parse_wire_and_knobs():
    assert comp.parse_wire("none") is None
    assert comp.parse_wire("bfloat16").kind == "bf16"  # legacy name
    spec = comp.parse_wire("int8", 128)
    assert spec.block == 128 and spec.error_feedback
    assert not comp.parse_wire("int8-raw").error_feedback
    with pytest.raises(ValueError):
        comp.parse_wire("int4")
    k = Knobs(compression="int8", compression_block=64)
    assert comp.resolve_wire(k) == comp.WireSpec("int8", 64, True)
    # legacy wire-dtype knob maps when HOROVOD_COMPRESSION is unset
    k2 = Knobs(compression="none", compression_wire_dtype="bfloat16")
    assert comp.resolve_wire(k2).kind == "bf16"
    assert hvd.Compression.from_knobs(Knobs()) is hvd.Compression.none
    assert (hvd.Compression.from_knobs(Knobs(compression="int8"))
            is hvd.Compression.int8)


def test_cli_env_mapping():
    from horovod_tpu.runner.util.config_parser import ARG_TO_ENV

    assert ARG_TO_ENV["compression"] == "HOROVOD_COMPRESSION"
    assert ARG_TO_ENV["compression_block"] == "HOROVOD_COMPRESSION_BLOCK"


def test_knobs_from_env(monkeypatch):
    monkeypatch.setenv("HOROVOD_COMPRESSION", "int8")
    monkeypatch.setenv("HOROVOD_COMPRESSION_BLOCK", "128")
    k = Knobs.from_env()
    assert k.compression == "int8" and k.compression_block == 128


# ------------------------------------------------- SPMD collective forms


def test_quantized_psum_close_to_psum(hvd8):
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.uniform(-2, 2, (8, 1000)).astype(np.float32))
    exact = np.asarray(_run8(lambda v: jax.lax.psum(v, "hvd"), x))
    q = np.asarray(_run8(
        lambda v: comp.quantized_psum(v, "hvd", 8, 128), x))
    tol = 8 * 2.0 / 127 * 2  # two quantization stages over 8 ranks
    assert np.abs(q - exact).max() <= tol
    assert not np.array_equal(q, exact)  # it really quantized


# An independent model of the int8 exchange, NumPy float32 throughout:
# per-block symmetric quantization with float32 scales, the exchange as
# plain indexing over the ranks, dequantize-accumulate in rank order.
_F32 = np.float32


def _np_quantize(flat, block):
    blocks = flat.reshape(-1, block)
    amax = np.abs(blocks).max(axis=1)
    scale = np.where(amax > 0, amax * _F32(1.0 / 127.0),
                     _F32(1.0)).astype(_F32)
    q = np.clip(np.rint(blocks / scale[:, None]), -127, 127)
    return q.astype(np.int8).reshape(-1), scale


def _np_dequantize(q, scale, block):
    return (q.reshape(-1, block).astype(_F32)
            * scale[:, None]).reshape(-1)


def _np_accumulate(parts):
    acc = np.zeros_like(parts[0])
    for part in parts:
        acc = acc + part
    return acc


def _np_quantized_psum(xs, block, residual):
    """xs, residual: (ranks, L). Returns (y (L,), new residual)."""
    n, L = xs.shape
    m = -(-L // (n * block)) * n * block
    padded = np.zeros((n, m), _F32)
    padded[:, :L] = xs if residual is None else xs + residual
    sent = [_np_quantize(padded[r], block) for r in range(n)]
    deq = np.stack([_np_dequantize(q, s, block) for q, s in sent])
    chunk = m // n
    gathered = []
    for j in range(n):  # rank j reduces chunk j of every rank
        shard = _np_accumulate(
            [deq[r, j * chunk:(j + 1) * chunk] for r in range(n)])
        gathered.append(_np_dequantize(*_np_quantize(shard, block),
                                       block))
    return np.concatenate(gathered)[:L], (padded - deq)[:, :L]


def _np_quantized_reduce_scatter_rows(rows, block, residual):
    """rows: (ranks, n, k); residual: (ranks, n, k2). Returns the
    (ranks, k) shards, rank r holding the sum of every rank's row r,
    and the new residual."""
    n, _, k = rows.shape
    k2 = -(-k // block) * block
    padded = np.zeros((n, n, k2), _F32)
    padded[:, :, :k] = rows
    if residual is not None:
        padded = padded + residual
    deq = np.stack([
        _np_dequantize(*_np_quantize(padded[p].reshape(-1), block),
                       block).reshape(n, k2) for p in range(n)])
    shards = np.stack([
        _np_accumulate([deq[p, r] for p in range(n)])
        for r in range(n)])
    return shards[:, :k], padded - deq


@pytest.mark.parametrize("block", [64, 256])
@pytest.mark.parametrize("with_residual", [False, True])
@pytest.mark.parametrize("exchange", ["psum", "reduce_scatter_rows"])
def test_int8_exchange_matches_numpy_model(hvd8, exchange,
                                           with_residual, block):
    """Three steps of each int8 collective on the 8-device world against
    the NumPy model above, the residual (when carried) fed from one
    step into the next on both sides. A code that differs by one is an
    error of amax/127 ~ 0.1 here; float32 rounding is ~1e-6."""
    mesh = hvd.mesh()
    rng = np.random.RandomState(20)
    if exchange == "psum":
        shape, res_shape = (8, 1000), (8, 1000)

        def body(x, r):
            out = comp.quantized_psum(
                x[0], "hvd", 8, block,
                residual=r[0] if with_residual else None)
            y, nr = out if with_residual else (out, r[0])
            return y, nr[None]

        out_specs = (P(), P("hvd"))
        model = _np_quantized_psum
    else:
        k2 = -(-300 // block) * block
        shape, res_shape = (8, 8, 300), (8, 8, k2)

        def body(x, r):
            out = comp.quantized_reduce_scatter_rows(
                x[0], "hvd", block,
                residual=r[0] if with_residual else None)
            y, nr = out if with_residual else (out, r[0])
            return y[None], nr[None]

        out_specs = (P("hvd"), P("hvd"))
        model = _np_quantized_reduce_scatter_rows
    step = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("hvd"), P("hvd")),
        out_specs=out_specs, check_vma=False))
    res = np.zeros(res_shape, _F32)
    want_res = res.copy() if with_residual else None
    for _ in range(3):
        x = rng.uniform(-2, 2, shape).astype(_F32)
        y, res = step(x, res)
        want_y, new_res = model(x, block, want_res)
        np.testing.assert_allclose(np.asarray(y), want_y, rtol=0,
                                   atol=1e-4)
        if with_residual:
            want_res = new_res
            np.testing.assert_allclose(np.asarray(res), want_res,
                                       rtol=0, atol=1e-5)
            assert np.abs(want_res).max() > 1e-4  # something is carried


def test_hierarchical_outer_int8_close_to_flat(hvd8):
    """Outer-hop-only compression: ICI legs full precision, DCN leg
    quantized — the result stays within one quantization stage of the
    flat psum (the inner reduce is exact)."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.uniform(-2, 2, (8, 999)).astype(np.float32))
    exact = np.asarray(_run8(lambda v: jax.lax.psum(v, "hvd"), x))
    spec = comp.parse_wire("int8", 128)
    for block in (2, 4):
        hq = np.asarray(_run8(lambda v: hierarchical.hierarchical_psum(
            v, ("hvd",), {"hvd": 8}, block, wire=spec), x))
        # inner sums of `block` ranks are exact; the outer gather
        # quantizes per-slice partial sums of magnitude <= 8*2
        assert np.abs(hq - exact).max() <= 2 * 8 * 2.0 / 127


def test_hierarchical_outer_bf16_close_to_flat(hvd8):
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.uniform(-2, 2, (8, 256)).astype(np.float32))
    exact = np.asarray(_run8(lambda v: jax.lax.psum(v, "hvd"), x))
    hb = np.asarray(_run8(lambda v: hierarchical.hierarchical_psum(
        v, ("hvd",), {"hvd": 8}, 4, wire=comp.parse_wire("bf16")), x))
    assert np.allclose(hb, exact, rtol=2e-2, atol=1e-1)


def test_hierarchical_wire_none_unchanged(hvd8):
    """wire=None must stay exactly the pre-compression hierarchy."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.uniform(-2, 2, (8, 64)).astype(np.float32))
    a = np.asarray(_run8(lambda v: hierarchical.hierarchical_psum(
        v, ("hvd",), {"hvd": 8}, 4), x))
    b = np.asarray(_run8(lambda v: hierarchical.hierarchical_psum(
        v, ("hvd",), {"hvd": 8}, 4, wire=None), x))
    assert np.array_equal(a, b)


def test_grad_path_hierarchical_routing_under_int8(hvd8):
    """With the hierarchy knob on, the int8 grad path routes through the
    outer-leg-compressed hierarchy and stays close to the exact mean."""
    _set_knobs(hierarchical_allreduce=True, hierarchical_local_size=4)
    rng = np.random.RandomState(6)
    g = jnp.asarray(rng.uniform(-1, 1, (8, 500)).astype(np.float32))
    opt = hvd.DistributedOptimizer(optax.sgd(1.0),
                                   compression=hvd.Compression.int8_raw)
    state = opt.init({"g": jnp.zeros((500,), jnp.float32)})

    def body(v):
        u, _ = opt.update({"g": v}, state, {"g": jnp.zeros_like(v)})
        return u["g"]

    red = np.asarray(_run8(body, g))
    exact = -np.asarray(g).mean(axis=0)  # sgd(1.0) update = -mean grad
    assert np.abs(red - exact).max() <= 4 * 8 / 127 / 8


# ------------------------------------------------------- SPMD none parity


def test_spmd_none_bitwise_parity(hvd8):
    """compression=None (knob none) must produce bit-identical updates
    to the explicit pre-PR Compression.none path."""
    rng = np.random.RandomState(7)
    g = jnp.asarray(rng.randn(8, 300).astype(np.float32))

    def updates_for(compression):
        opt = hvd.DistributedOptimizer(optax.sgd(0.1),
                                       compression=compression)
        state = opt.init({"g": jnp.zeros((300,), jnp.float32)})

        def body(v):
            u, _ = opt.update({"g": v}, state,
                              {"g": jnp.zeros_like(v)})
            return u["g"]

        return np.asarray(_run8(body, g))

    assert np.array_equal(updates_for(None),
                          updates_for(hvd.Compression.none))


# --------------------------------------------------------- error feedback


def test_error_feedback_residual_carries_across_steps(hvd8):
    """EF contract: the residual state leaves are non-zero after a step,
    change across steps, and make the RUNNING MEAN of compressed
    reductions converge to the exact value (unbiasedness) where the raw
    int8 wire keeps a persistent bias."""
    rng = np.random.RandomState(8)
    g = jnp.asarray(rng.uniform(-1, 1, (8, 400)).astype(np.float32))
    exact = np.asarray(g).mean(axis=0)
    mesh = hvd.mesh()

    def reductions(compression, steps=16):
        opt = hvd.DistributedOptimizer(optax.sgd(1.0),
                                       compression=compression)
        state = opt.init({"g": jnp.zeros((400,), jnp.float32)})
        specs = hvd.error_feedback_specs(state)

        def body(v, s):
            u, s = opt.update({"g": v[0]}, s, {"g": jnp.zeros_like(v[0])})
            return -u["g"], s  # sgd(1.0): -update == reduced grad

        js = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("hvd"), specs),
            out_specs=(P(), specs), check_vma=False))
        outs, s = [], state
        for _ in range(steps):
            r, s = js(g, s)
            outs.append(np.asarray(r))
        return outs, s

    ef_outs, ef_state = reductions(hvd.Compression.int8)
    raw_outs, _ = reductions(hvd.Compression.int8_raw)

    res = np.asarray(ef_state.residual["g"])
    assert res.shape == (8, 400)  # one row per rank
    assert np.abs(res).max() > 0  # residual actually carried

    ef_mean_err = np.abs(np.mean(ef_outs, axis=0) - exact).max()
    raw_mean_err = np.abs(np.mean(raw_outs, axis=0) - exact).max()
    step_err = np.abs(ef_outs[0] - exact).max()
    # EF's mean error collapses well below a single step's quantization
    # error; the raw wire's bias persists at the single-step scale
    assert ef_mean_err < step_err / 3
    assert ef_mean_err < raw_mean_err


def test_error_feedback_requires_specs(hvd8):
    """A full (n, ...) residual leaf inside shard_map means the caller
    forgot error_feedback_specs — fail at the cause."""
    opt = hvd.DistributedOptimizer(optax.sgd(0.1),
                                   compression=hvd.Compression.int8)
    state = opt.init({"g": jnp.zeros((64,), jnp.float32)})
    mesh = hvd.mesh()
    g = jnp.zeros((8, 64), jnp.float32)

    def body(v, s):
        u, s = opt.update({"g": v[0]}, s, {"g": jnp.zeros_like(v[0])})
        return u["g"], s

    with pytest.raises(ValueError, match="error_feedback_specs"):
        jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("hvd"), P()),
            out_specs=(P(), P()), check_vma=False))(g, state)


# ------------------------------------------------ small-MLP convergence


def test_small_mlp_converges_under_int8_ef(hvd8):
    """Acceptance: a small MLP trained under int8+EF reaches a final
    loss comparable to full precision."""
    mesh = hvd.mesh()
    rng = np.random.RandomState(9)
    params = {
        "w1": jnp.asarray(rng.randn(32, 32).astype(np.float32) * 0.3),
        "b1": jnp.zeros((32,), jnp.float32),
        "w2": jnp.asarray(rng.randn(32, 4).astype(np.float32) * 0.3),
    }
    x = jnp.asarray(rng.randn(8, 16, 32).astype(np.float32))
    y = jnp.asarray(rng.randn(8, 16, 4).astype(np.float32))

    def loss_fn(p, xb, yb):
        h = jnp.tanh(xb @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - yb) ** 2)

    def train(compression, steps=40):
        opt = hvd.DistributedOptimizer(optax.adam(3e-2),
                                       compression=compression)
        state = opt.init(params)
        specs = hvd.error_feedback_specs(state)

        def step(p, s, xb, yb):
            l, g = jax.value_and_grad(loss_fn)(p, xb[0], yb[0])
            u, s = opt.update(g, s, p)
            return optax.apply_updates(p, u), s, jax.lax.pmean(
                l, "hvd").reshape(1)

        js = jax.jit(shard_map(
            step, mesh=mesh, in_specs=(P(), specs, P("hvd"), P("hvd")),
            out_specs=(P(), specs, P()), check_vma=False))
        p, s = params, state
        first = last = None
        for _ in range(steps):
            p, s, l = js(p, s, x, y)
            if first is None:
                first = float(l[0])
            last = float(l[0])
        return first, last

    f0, l0 = train(hvd.Compression.none)
    f8, l8 = train(hvd.Compression.int8)
    assert l8 < f8 * 0.5  # it converges
    assert l8 <= l0 * 1.2 + 1e-3  # and lands near full precision


# --------------------------------------------------------- ZeRO / eager


def test_zero_compressed_reduce_scatter_close(hvd8):
    mesh = hvd.mesh()
    rng = np.random.RandomState(10)
    params = {"w": jnp.asarray(rng.randn(96, 4).astype(np.float32))}
    g = jnp.asarray(rng.uniform(-1, 1, (8, 96, 4)).astype(np.float32))

    def update_for(compression):
        opt = hvd.ShardedOptimizer(optax.sgd(1.0),
                                   compression=compression)
        state = opt.init(params)
        specs = hvd.sharded_state_specs(state)

        def body(p, s, v):
            u, s = opt.update({"w": v[0]}, s, p)
            return u["w"], s

        js = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(), specs, P("hvd")),
            out_specs=(P(), specs), check_vma=False))
        return np.asarray(js(params, state, g)[0])

    base = update_for(hvd.Compression.none)
    for compression in (hvd.Compression.bf16, hvd.Compression.int8):
        out = update_for(compression)
        assert np.abs(out - base).max() <= 8 * 1.0 / 127 / 8 * 4
        assert not np.array_equal(out, base)
    # state layout must be identical regardless of wire
    opt_a = hvd.ShardedOptimizer(optax.adam(1e-2),
                                 compression=hvd.Compression.none)
    opt_b = hvd.ShardedOptimizer(optax.adam(1e-2),
                                 compression=hvd.Compression.int8)
    la = jax.tree_util.tree_map(jnp.shape, opt_a.init(params))
    lb = jax.tree_util.tree_map(jnp.shape, opt_b.init(params))
    assert la == lb


def test_eager_none_bitwise_fastpath_and_negotiated():
    """HOROVOD_COMPRESSION=none on the eager runtime: fast-path AND
    negotiated results are bitwise identical to the uncompressed
    plane's exact loopback sum."""
    from horovod_tpu.ops.eager_runtime import EagerRuntime

    rt = EagerRuntime(0, 1, cycle_ms=1.0, fast_path=True,
                      fast_path_warmup=2, wire="none")
    try:
        x = np.random.RandomState(11).randn(257).astype(np.float32)
        outs = []
        for _ in range(6):
            h = rt.allreduce_async("t", x)
            outs.append(np.asarray(rt.synchronize(h, timeout_s=30)))
        assert rt.fast_path_stats()["active"]  # steady state reached
        assert rt.fast_path_stats()["plan_wire_key"] is None
        rt.set_fast_path(False)
        h = rt.allreduce_async("t", x)
        negotiated = np.asarray(rt.synchronize(h, timeout_s=30))
        for o in outs:
            assert np.array_equal(o, x)  # world-1 SUM == x, bitwise
        assert np.array_equal(negotiated, x)
    finally:
        rt.shutdown()


def test_eager_int8_wire_counters_and_ef_buffers():
    """Loopback executor under the int8 wire: the wire-byte counters
    report the >=3.5x ratio, results stay in quantization tolerance,
    and the executor carries error-feedback buffers across steps."""
    from horovod_tpu.ops.eager_runtime import EagerRuntime
    from horovod_tpu.utils import metrics

    metrics.enable()
    rt = EagerRuntime(0, 1, cycle_ms=1.0, fast_path=True,
                      fast_path_warmup=2, wire="int8")
    try:
        x = np.random.RandomState(12).randn(1000).astype(np.float32)

        def counters():
            snap = metrics.registry.snapshot()
            return (sum(snap.get("hvd_wire_bytes_logical_total",
                                 {}).values()),
                    sum(snap.get("hvd_wire_bytes_sent_total",
                                 {}).values()))

        l0, s0 = counters()
        outs = []
        for _ in range(8):
            h = rt.allreduce_async("t", x)
            outs.append(np.asarray(rt.synchronize(h, timeout_s=30)))
        l1, s1 = counters()
        assert (l1 - l0) / (s1 - s0) >= 3.5
        amax = np.abs(x).max()
        assert np.abs(outs[0] - x).max() <= 4 * amax / 127
        # EF: the residual buffer exists and the mean over steps beats
        # a single step's quantization error
        assert rt._executor._residuals
        mean_err = np.abs(np.mean(outs, axis=0) - x).max()
        assert mean_err < np.abs(outs[0] - x).max() or mean_err < 1e-4
        # plan froze under the int8 wire
        assert rt.fast_path_stats()["plan_wire_key"][0] == "int8"
    finally:
        rt.shutdown()
        metrics.disable()
        metrics.registry.clear()


def test_block_knob_reaches_spmd_wire_spec(hvd8):
    """HOROVOD_COMPRESSION_BLOCK must reach the SPMD/ZeRO paths through
    the knob-resolved compressor, not be shadowed by a class default —
    eager and SPMD must quantize on the same grid."""
    _set_knobs(compression="int8", compression_block=64)
    spec = comp.compressor_wire_spec(hvd.Compression.from_knobs())
    assert spec.block == 64
    assert comp.resolve_wire().block == 64  # executors agree
    # the ctx carries the grid, so decompress survives a knob change
    x = jnp.asarray(np.random.RandomState(0).randn(100).astype(np.float32))
    wire, ctx = hvd.Compression.int8.compress(x)
    _set_knobs(compression_block=256)
    back = hvd.Compression.int8.decompress(wire, ctx)
    assert float(jnp.abs(back - x).max()) <= float(jnp.abs(x).max()) / 127


def test_adasum_under_int8_knob_falls_back(hvd8):
    """op=ADASUM under the int8 knob must fall back to the uncompressed
    plane on every path instead of tracing live[0] off an empty axis
    list (or cast-reducing an int8 payload)."""
    _set_knobs(compression="int8")
    g = jnp.asarray(np.random.RandomState(0).randn(8, 64)
                    .astype(np.float32))
    opt = hvd.DistributedOptimizer(optax.sgd(1.0),
                                   op=hvd.ReduceOp.ADASUM)
    state = opt.init({"g": jnp.zeros((64,), jnp.float32)})

    def body(v):
        u, _ = opt.update({"g": v}, state, {"g": jnp.zeros_like(v)})
        return u["g"]

    out = np.asarray(_run8(body, g))  # must trace and run
    assert np.isfinite(out).all()


def test_error_feedback_with_grad_accumulation(hvd8):
    """int8+EF composes with backward_passes_per_step > 1: the specs
    helper recurses through the accumulation wrapper and the residual
    still carries across sync steps."""
    mesh = hvd.mesh()
    rng = np.random.RandomState(13)
    g = jnp.asarray(rng.uniform(-1, 1, (8, 128)).astype(np.float32))
    opt = hvd.DistributedOptimizer(optax.sgd(1.0),
                                   compression=hvd.Compression.int8,
                                   backward_passes_per_step=2)
    state = opt.init({"g": jnp.zeros((128,), jnp.float32)})
    specs = hvd.error_feedback_specs(state)

    def body(v, s):
        u, s = opt.update({"g": v[0]}, s, {"g": jnp.zeros_like(v[0])})
        return u["g"], s

    js = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("hvd"), specs),
        out_specs=(P(), specs), check_vma=False))
    s = state
    for _ in range(4):  # two full accumulate->sync cycles
        u, s = js(g, s)
    res = np.asarray(s.inner.residual["g"])
    assert res.shape == (8, 128) and np.abs(res).max() > 0
    exact = -np.asarray(g).mean(axis=0)
    assert np.abs(np.asarray(u) - exact).max() <= 8.0 / 127


def test_fusion_bucket_plan_unchanged_by_wire(monkeypatch):
    """(logical, wire) bucket keys: grouping BOUNDARIES are identical
    with compression on and off — the wire half never splits a dtype
    group, it only tags it (the ZeRO layout invariant)."""
    from horovod_tpu.ops.fusion import pytree_bucket_plan

    tree = {"a": jnp.zeros((100,), jnp.float32),
            "b": jnp.zeros((50,), jnp.float32),
            "c": jnp.zeros((10,), jnp.int32)}
    _, plans_off = pytree_bucket_plan(tree, threshold_bytes=1 << 20)
    monkeypatch.setenv("HOROVOD_COMPRESSION", "int8")
    _, plans_on = pytree_bucket_plan(tree, threshold_bytes=1 << 20)
    assert plans_off == plans_on
