"""Fused computation-collective backend (ops/pallas_collectives.py,
docs/fused_collectives.md).

Interpret-mode kernels on the 8-device CPU mesh — the same kernel
bodies Mosaic compiles on TPU, so these parity assertions are the
numerics contract, not an approximation of it:

  * fp32 fused reduce-scatter (pack epilogue + psum_scatter) is
    BITWISE-equal to the unfused `_pad_rows` path;
  * the int8+EF fused quantized reduce-scatter / psum carry the
    IDENTICAL residual trajectory across steps (error feedback stays
    unbiased under the fused backend);
  * the fused decode KV-append+attention matches
    ``SlottedKVCache.update`` + ``cached_attention`` bitwise (fp32 KV,
    and codes/scales on the int8 cache);
  * the autotuner registers ``fused_collectives`` as a dimension
    (incumbent-seeded, never-worse) and the knob is inert when off
    (lowering hash unchanged after fused builds run in-process).
"""

import dataclasses
import hashlib
import os

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.core.state import global_state
from horovod_tpu.optim import compression as comp
from horovod_tpu.optim import zero as zero_mod
from horovod_tpu.ops import pallas_collectives as pc


def _set_knobs(**kw):
    st = global_state()
    st.knobs = dataclasses.replace(st.knobs, **kw)


def _fused(on: bool):
    _set_knobs(fused_collectives=on)


# ------------------------------------------------------- collective parity


def test_fused_reduce_scatter_fp32_bitwise(hvd8):
    """The pack-epilogue + psum_scatter fp32 reduce-scatter is bitwise
    under the fused backend (the ZeRO/FSDP uncompressed wire)."""
    mesh = hvd.mesh()
    n = hvd.size()
    rng = np.random.RandomState(0)
    buckets = jnp.asarray(rng.randn(n, 999).astype(np.float32))

    def step(bs):
        rows = pc.maybe_pack_rows(bs[0], n)
        return zero_mod._scatter_bucket(rows, "hvd", n, None)[None]

    def run(on):
        _fused(on)
        return np.asarray(jax.jit(shard_map(
            step, mesh=mesh, in_specs=(P("hvd"),), out_specs=P("hvd"),
            check_vma=False))(buckets))

    off, on = run(False), run(True)
    assert (off == on).all()


def test_fused_quantized_rs_rows_residual_trajectory(hvd8):
    """int8+EF reduce-scatter rows: shards AND the carried residual are
    bitwise-identical fused vs unfused over 3 steps."""
    mesh = hvd.mesh()
    n = hvd.size()
    block, k = 32, 100
    k2 = -(-k // block) * block
    rng = np.random.RandomState(1)
    steps = [jnp.asarray(rng.randn(n, n, k).astype(np.float32))
             for _ in range(3)]

    def traj(on):
        _fused(on)

        def one(rw, rs):
            s, nr = comp.quantized_reduce_scatter_rows(
                rw[0], "hvd", block, residual=rs[0])
            return s[None], nr[None]

        g = jax.jit(shard_map(
            one, mesh=mesh, in_specs=(P("hvd"), P("hvd")),
            out_specs=(P("hvd"), P("hvd")), check_vma=False))
        res = jnp.zeros((n, n, k2), jnp.float32)
        shards = []
        for rows in steps:
            s, res = g(rows, res)
            shards.append(np.asarray(s))
        return shards, np.asarray(res)

    s_off, r_off = traj(False)
    s_on, r_on = traj(True)
    for a, b in zip(s_off, s_on):
        assert (a == b).all()
    assert (r_off == r_on).all()


def test_fused_quantized_psum_residual_trajectory(hvd8):
    """int8+EF quantized_psum (staged backward / DCN outer-leg wire):
    outputs and residual trajectory bitwise over 3 steps."""
    mesh = hvd.mesh()
    n = hvd.size()
    rng = np.random.RandomState(2)
    xs = [jnp.asarray(rng.randn(n, 777).astype(np.float32))
          for _ in range(3)]

    def traj(on):
        _fused(on)

        def one(v, r):
            y, nr = comp.quantized_psum(v[0], "hvd", n, 32,
                                        residual=r[0])
            return y[None], nr[None]

        g = jax.jit(shard_map(
            one, mesh=mesh, in_specs=(P("hvd"), P("hvd")),
            out_specs=(P("hvd"), P("hvd")), check_vma=False))
        res = jnp.zeros((n, 777), jnp.float32)
        ys = []
        for x in xs:
            y, res = g(x, res)
            ys.append(np.asarray(y))
        return ys, np.asarray(res)

    y_off, r_off = traj(False)
    y_on, r_on = traj(True)
    for a, b in zip(y_off, y_on):
        assert (a == b).all()
    assert (r_off == r_on).all()


def test_matmul_reduce_scatter_parity(hvd8):
    """The grad-matmul → reduce-scatter epilogue: the fused kernel's
    dot + pack matches jnp.dot + _pad_rows bitwise, through both the
    plain and the int8 wire."""
    mesh = hvd.mesh()
    n = hvd.size()
    rng = np.random.RandomState(3)
    a = jnp.asarray(rng.randn(n, 24, 33).astype(np.float32))
    b = jnp.asarray(rng.randn(n, 33, 16).astype(np.float32))

    for wire in (None, comp.parse_wire("int8", 32)):
        def step(av, bv):
            return pc.matmul_reduce_scatter(av[0], bv[0], "hvd", n,
                                            wire=wire)[None]

        def run(on):
            _fused(on)
            return np.asarray(jax.jit(shard_map(
                step, mesh=mesh, in_specs=(P("hvd"), P("hvd")),
                out_specs=P("hvd"), check_vma=False))(a, b))

        off, on = run(False), run(True)
        assert (off == on).all(), f"wire={wire}"


# ------------------------------------------------------------ decode parity


def _decode_run(dtype, fused):
    """Prefill + one append_attend step; returns (attn out, buffers)."""
    from horovod_tpu.serving.decode import KVCacheSpec, SlottedKVCache

    os.environ["HOROVOD_FUSED_COLLECTIVES"] = "1" if fused else "0"
    try:
        spec = KVCacheSpec(slots=2, layers=2, kv_heads=2, max_len=32,
                           head_dim=16, dtype=dtype, block=8,
                           compute_dtype=jnp.float32)
        cache = SlottedKVCache(spec, spec.allocate())
        rs = np.random.RandomState(11)
        k0 = jnp.asarray(rs.randn(2, 6, 2, 16).astype(np.float32))
        v0 = jnp.asarray(rs.randn(2, 6, 2, 16).astype(np.float32))
        p0 = jnp.asarray(np.tile(np.arange(6), (2, 1)).astype(np.int32))
        cache.update(0, k0, v0, p0)
        q = jnp.asarray(rs.randn(2, 1, 4, 16).astype(np.float32))
        kn = jnp.asarray(rs.randn(2, 1, 2, 16).astype(np.float32))
        vn = jnp.asarray(rs.randn(2, 1, 2, 16).astype(np.float32))
        pos = jnp.full((2, 1), 6, jnp.int32)
        out = cache.append_attend(0, q, kn, vn, pos)
        return (np.asarray(out),
                {k: np.asarray(v) for k, v in cache.buffers.items()})
    finally:
        os.environ.pop("HOROVOD_FUSED_COLLECTIVES", None)


def test_decode_append_attend_fp32_bitwise():
    o_off, b_off = _decode_run("fp32", False)
    o_on, b_on = _decode_run("fp32", True)
    assert (o_off == o_on).all()
    for name in b_off:
        assert (b_off[name] == b_on[name]).all(), name


def test_decode_append_attend_int8_bitwise():
    """int8 KV: the fused kernel quantizes-on-write with the same block
    math, so codes, scales AND the attention output are bitwise."""
    o_off, b_off = _decode_run("int8", False)
    o_on, b_on = _decode_run("int8", True)
    assert (o_off == o_on).all()
    for name in ("k", "v", "k_scale", "v_scale"):
        assert (b_off[name] == b_on[name]).all(), name


# --------------------------------------------------- autotuner integration


def test_autotune_dimension_registered(tmp_path):
    from horovod_tpu.core.knobs import Knobs
    from horovod_tpu.ops.autotune import TUNABLE_KNOBS, OnlineTuner

    assert "fused_collectives" in TUNABLE_KNOBS
    knobs = Knobs()
    tuner = OnlineTuner(
        knobs, thresholds=[knobs.fusion_threshold_bytes], warmup=0,
        measure=1, tune_ordered=False, tune_overlap=False,
        tune_fused_collectives=True,
        cache_path=str(tmp_path / "cache.json"), fingerprint="t-fused")
    assert "fused_collectives" in tuner.tuned_knobs()
    dims = dict(tuner._dimension_candidates(
        {k: getattr(knobs, k) for k in tuner.tuned_knobs()}))
    assert dims["fused_collectives"] == [{"fused_collectives": True}]


def test_autotune_selection_never_worse(tmp_path):
    """The fused dimension is incumbent-seeded: whatever the race on
    this host decides, the pinned config's measured time is <= the
    incumbent's (the never-worse contract, docs/autotune.md)."""
    from horovod_tpu.core.knobs import Knobs
    from horovod_tpu.ops.autotune import OnlineTuner

    knobs = Knobs()
    tuner = OnlineTuner(
        knobs, thresholds=[knobs.fusion_threshold_bytes], warmup=0,
        measure=2, tune_ordered=False, tune_overlap=False,
        tune_fused_collectives=True,
        cache_path=str(tmp_path / "cache.json"), fingerprint="t-nw")

    def factory(overrides):
        step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
        return lambda: step(jnp.ones((64, 64), jnp.float32))

    config = tuner.tune(factory)
    assert "fused_collectives" in config
    trials = {bool(r["fused_collectives"]): r["step_s"]
              for r in tuner.trials
              if r.get("dimension") == "fused_collectives"
              and "step_s" in r}
    incumbent = next(r["step_s"] for r in tuner.trials
                     if r.get("dimension") == "fusion_threshold_bytes")
    selected = trials.get(bool(config["fused_collectives"]), incumbent)
    assert selected <= incumbent


def test_knob_off_lowering_hash_unchanged(hvd8):
    """HOROVOD_FUSED_COLLECTIVES off is inert: the knob-off lowering of
    an int8 ZeRO reduce-scatter step is byte-identical before and after
    fused builds run in the same process — and the knob-on lowering
    differs (the routing is alive)."""
    mesh = hvd.mesh()
    n = hvd.size()
    wire = comp.parse_wire("int8", 32)
    buckets = jnp.asarray(np.ones((n, 999), np.float32))

    def step(bs):
        rows = pc.maybe_pack_rows(bs[0], n)
        return zero_mod._scatter_bucket(rows, "hvd", n, wire)[None]

    def lower_hash():
        js = jax.jit(shard_map(step, mesh=mesh, in_specs=(P("hvd"),),
                               out_specs=P("hvd"), check_vma=False))
        return hashlib.sha256(
            js.lower(buckets).as_text().encode()).hexdigest()

    _fused(False)
    before = lower_hash()
    _fused(True)
    fused = lower_hash()
    _fused(False)
    after = lower_hash()
    assert before == after
    assert before != fused
