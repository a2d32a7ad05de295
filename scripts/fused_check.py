#!/usr/bin/env python
"""Gate for the fused computation-collective Pallas backend
(ops/pallas_collectives.py, docs/fused_collectives.md).

Verifies, on the CPU loopback world (interpret-mode kernels — the same
kernel bodies Mosaic compiles on TPU):

1. fp32 fused reduce-scatter (pack epilogue + psum_scatter) is
   BITWISE-equal to the unfused path;
2. the int8+EF fused quantized reduce-scatter / psum carry the
   IDENTICAL residual trajectory across steps;
3. the fused decode KV-append+attention is bitwise on fp32 KV (and on
   the int8 cache's codes/scales);
4. the knob is inert when off: the knob-off lowering hash of an int8
   ZeRO step is unchanged before/after fused builds run in-process;
5. the fused/unfused A/B on the loopback world, written to
   ``FUSED_AB_r09.json``: step times, an exposed-wire proxy, and the
   autotune ``fused_collectives`` dimension's selection — the pinned
   configuration is never worse than the incumbent (incumbent-seeded
   argmin).

Usage::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/fused_check.py --check
"""

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS",
    (os.environ.get("XLA_FLAGS", "")
     + " --xla_force_host_platform_device_count=8").strip())

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


_ARTIFACT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "FUSED_AB_r09.json")

_NOTE = (
    "Fused computation-collective A/B on the CPU loopback world "
    "(interpret-mode Pallas — same kernel bodies Mosaic compiles on "
    "TPU, so parity rows are the real numerics contract while timing "
    "rows are a loopback proxy, not TPU speedup). off/on = "
    "HOROVOD_FUSED_COLLECTIVES; every surface is bitwise-equal by "
    "construction (shared block math, docs/fused_collectives.md). "
    "exposed_wire_frac_proxy = (step_ms - compute_ms) / step_ms with "
    "compute_ms measured on the identical step with the collective "
    "removed. autotune = the fused_collectives tuner dimension on this "
    "world: incumbent-seeded argmin, so selected_ms <= incumbent_ms "
    "(never-worse) regardless of which backend wins the race."
)


def _set_fused(on: bool) -> None:
    os.environ["HOROVOD_FUSED_COLLECTIVES"] = "1" if on else "0"


def _clear_fused() -> None:
    os.environ.pop("HOROVOD_FUSED_COLLECTIVES", None)


def _mesh():
    return Mesh(np.array(jax.devices()), ("d",))


def _bitwise(a, b) -> bool:
    return bool((np.asarray(a) == np.asarray(b)).all())


# ---------------------------------------------------------------------------
# 1+2: collective parity (fp32 bitwise, int8+EF residual trajectory)
# ---------------------------------------------------------------------------


def check_collective_parity(failures):
    from horovod_tpu.optim import compression as comp
    from horovod_tpu.optim import zero as zero_mod
    from horovod_tpu.ops import pallas_collectives as pc

    mesh = _mesh()
    n = len(jax.devices())
    rng = np.random.RandomState(0)
    block = 32

    # fp32 reduce-scatter: fused pack epilogue + psum_scatter
    buckets = jnp.asarray(rng.randn(n, 999).astype(np.float32))

    def rs_step(bs):
        rows = pc.maybe_pack_rows(bs[0], n)
        return zero_mod._scatter_bucket(rows, "d", n, None)[None]

    f = jax.jit(shard_map(rs_step, mesh=mesh, in_specs=(P("d"),),
                          out_specs=P("d"), check_vma=False))
    _set_fused(False)
    off = f(buckets)
    _set_fused(True)
    on = jax.jit(shard_map(rs_step, mesh=mesh, in_specs=(P("d"),),
                           out_specs=P("d"), check_vma=False))(buckets)
    _clear_fused()
    if not _bitwise(off, on):
        failures.append("fp32 fused reduce-scatter is not bitwise-equal "
                        "to the unfused path")
    print(f"fp32 reduce-scatter bitwise: {_bitwise(off, on)}")

    # int8+EF reduce-scatter rows: 3-step residual trajectory
    k = 100
    k2 = -(-k // block) * block
    steps = [jnp.asarray(rng.randn(n, n, k).astype(np.float32))
             for _ in range(3)]

    def traj(fused):
        _set_fused(fused)
        try:
            def one(rw, rs):
                s, nr = comp.quantized_reduce_scatter_rows(
                    rw[0], "d", block, residual=rs[0])
                return s[None], nr[None]

            g = jax.jit(shard_map(
                one, mesh=mesh, in_specs=(P("d"), P("d")),
                out_specs=(P("d"), P("d")), check_vma=False))
            res = jnp.zeros((n, n, k2), jnp.float32)
            shards = []
            for rows in steps:
                s, res = g(rows, res)
                shards.append(np.asarray(s))
            return shards, np.asarray(res)
        finally:
            _clear_fused()

    s_off, r_off = traj(False)
    s_on, r_on = traj(True)
    ok = all(_bitwise(a, b) for a, b in zip(s_off, s_on))
    ok = ok and _bitwise(r_off, r_on)
    if not ok:
        failures.append("int8+EF fused reduce-scatter diverged from the "
                        "unfused residual trajectory")
    print(f"int8+EF reduce-scatter residual trajectory bitwise: {ok}")

    # int8+EF psum trajectory
    xs = [jnp.asarray(rng.randn(n, 777).astype(np.float32))
          for _ in range(3)]

    def ptraj(fused):
        _set_fused(fused)
        try:
            def one(v, r):
                y, nr = comp.quantized_psum(v[0], "d", n, block,
                                            residual=r[0])
                return y[None], nr[None]

            g = jax.jit(shard_map(
                one, mesh=mesh, in_specs=(P("d"), P("d")),
                out_specs=(P("d"), P("d")), check_vma=False))
            res = jnp.zeros((n, 777), jnp.float32)
            ys = []
            for x in xs:
                y, res = g(x, res)
                ys.append(np.asarray(y))
            return ys, np.asarray(res)
        finally:
            _clear_fused()

    y_off, pr_off = ptraj(False)
    y_on, pr_on = ptraj(True)
    ok = all(_bitwise(a, b) for a, b in zip(y_off, y_on))
    ok = ok and _bitwise(pr_off, pr_on)
    if not ok:
        failures.append("int8+EF fused quantized_psum diverged from the "
                        "unfused residual trajectory")
    print(f"int8+EF psum residual trajectory bitwise: {ok}")

    # matmul → reduce-scatter epilogue (int8 wire)
    wire = comp.parse_wire("int8", block)
    a = jnp.asarray(rng.randn(n, 24, 33).astype(np.float32))
    bmats = jnp.asarray(rng.randn(n, 33, 16).astype(np.float32))

    def mm(av, bv):
        return pc.matmul_reduce_scatter(av[0], bv[0], "d", n,
                                        wire=wire)[None]

    _set_fused(False)
    m_off = jax.jit(shard_map(mm, mesh=mesh, in_specs=(P("d"), P("d")),
                              out_specs=P("d"), check_vma=False))(
        a, bmats)
    _set_fused(True)
    m_on = jax.jit(shard_map(mm, mesh=mesh, in_specs=(P("d"), P("d")),
                             out_specs=P("d"), check_vma=False))(
        a, bmats)
    _clear_fused()
    if not _bitwise(m_off, m_on):
        failures.append("fused matmul→reduce-scatter epilogue is not "
                        "bitwise-equal to dot + pack + scatter")
    print(f"matmul epilogue reduce-scatter bitwise: {_bitwise(m_off, m_on)}")


# ---------------------------------------------------------------------------
# 3: decode append+attend parity
# ---------------------------------------------------------------------------


def check_decode_parity(failures):
    from horovod_tpu.serving.decode import KVCacheSpec, SlottedKVCache

    rng = np.random.RandomState(3)
    for dt in ("fp32", "int8"):
        def run(fused):
            _set_fused(fused)
            try:
                spec = KVCacheSpec(slots=2, layers=2, kv_heads=2,
                                   max_len=32, head_dim=16, dtype=dt,
                                   block=8, compute_dtype=jnp.float32)
                cache = SlottedKVCache(spec, spec.allocate())
                rs = np.random.RandomState(11)
                k0 = jnp.asarray(rs.randn(2, 6, 2, 16).astype(np.float32))
                v0 = jnp.asarray(rs.randn(2, 6, 2, 16).astype(np.float32))
                p0 = jnp.asarray(np.tile(np.arange(6), (2, 1)).astype(
                    np.int32))
                cache.update(0, k0, v0, p0)
                q = jnp.asarray(rs.randn(2, 1, 4, 16).astype(np.float32))
                kn = jnp.asarray(rs.randn(2, 1, 2, 16).astype(np.float32))
                vn = jnp.asarray(rs.randn(2, 1, 2, 16).astype(np.float32))
                pos = jnp.full((2, 1), 6, jnp.int32)
                out = cache.append_attend(0, q, kn, vn, pos)
                return np.asarray(out), {k: np.asarray(v) for k, v
                                         in cache.buffers.items()}
            finally:
                _clear_fused()

        o_off, b_off = run(False)
        o_on, b_on = run(True)
        ok = _bitwise(o_off, o_on) and all(
            _bitwise(b_off[kk], b_on[kk]) for kk in b_off)
        if not ok:
            failures.append(
                f"fused decode append+attend ({dt}) is not bitwise vs "
                "update + cached_attention")
        print(f"decode append+attend bitwise ({dt}): {ok}")


# ---------------------------------------------------------------------------
# 4: knob-off inertness (lowering hash)
# ---------------------------------------------------------------------------


def check_knob_inertness(failures):
    from horovod_tpu.optim import compression as comp
    from horovod_tpu.optim import zero as zero_mod
    from horovod_tpu.ops import pallas_collectives as pc

    mesh = _mesh()
    n = len(jax.devices())
    wire = comp.parse_wire("int8", 32)
    buckets = jnp.asarray(np.ones((n, 999), np.float32))

    def step(bs):
        rows = pc.maybe_pack_rows(bs[0], n)
        return zero_mod._scatter_bucket(rows, "d", n, wire)[None]

    def lower_hash():
        js = jax.jit(shard_map(step, mesh=mesh, in_specs=(P("d"),),
                               out_specs=P("d"), check_vma=False))
        return hashlib.sha256(
            js.lower(buckets).as_text().encode()).hexdigest()

    _set_fused(False)
    h_before = lower_hash()
    _set_fused(True)
    h_fused = lower_hash()
    _set_fused(False)
    h_after = lower_hash()
    _clear_fused()
    print(f"knob-off hash {h_before[:12]} / fused {h_fused[:12]} / "
          f"off-again {h_after[:12]}")
    if h_before != h_after:
        failures.append("knob-off lowering changed after fused builds "
                        "ran — the selection layer leaks state")
    if h_before == h_fused:
        failures.append("fused knob did not change the lowering — the "
                        "routing is dead and the A/B measures nothing")


# ---------------------------------------------------------------------------
# 5: loopback A/B + autotune selection, artifact FUSED_AB_r09.json
# ---------------------------------------------------------------------------


def _mini_step(mesh, n, wire, with_collective=True):
    """A loopback train-step proxy: a matmul chain (compute) whose
    gradient bucket rides the int8+EF-less quantized reduce-scatter.
    Small enough to time in CI, shaped like the staged data plane."""
    from horovod_tpu.optim import zero as zero_mod
    from horovod_tpu.ops import pallas_collectives as pc

    def body(w, x):
        h = x
        for _ in range(4):
            h = jnp.tanh(h @ w)
        loss = jnp.sum(h * h)
        g = jax.grad(lambda wv: jnp.sum(
            jnp.tanh(x @ wv) ** 2))(w)
        if not with_collective:
            return loss, g.reshape(-1)[: g.size // n]
        rows = pc.maybe_pack_rows(g.reshape(-1), n)
        red = zero_mod._scatter_bucket(rows, "d", n, wire)
        return loss, red

    def sm(wv, xv):
        return body(wv[0], xv[0])

    return jax.jit(shard_map(
        lambda wv, xv: tuple(o[None] for o in sm(wv, xv)),
        mesh=mesh, in_specs=(P("d"), P("d")),
        out_specs=(P("d"), P("d")), check_vma=False))


def _time_step(step, args, warmup=2, iters=10):
    for _ in range(warmup):
        jax.block_until_ready(step(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(step(*args))
    return (time.perf_counter() - t0) / iters * 1e3


def exposed_wire_ab(failures, write_artifact=True):
    from horovod_tpu.core.knobs import Knobs
    from horovod_tpu.optim import compression as comp
    from horovod_tpu.ops.autotune import OnlineTuner

    mesh = _mesh()
    n = len(jax.devices())
    wire = comp.parse_wire("int8", 256)
    rng = np.random.RandomState(5)
    w = jnp.asarray(rng.randn(n, 256, 256).astype(np.float32) * 0.05)
    x = jnp.asarray(rng.randn(n, 64, 256).astype(np.float32))

    runs = []
    times = {}
    for label, fused in (("off", False), ("on", True)):
        _set_fused(fused)
        try:
            step = _mini_step(mesh, n, wire)
            step_ms = _time_step(step, (w, x))
            compute = _mini_step(mesh, n, wire, with_collective=False)
            compute_ms = _time_step(compute, (w, x))
        finally:
            _clear_fused()
        exposed = max(0.0, (step_ms - compute_ms) / step_ms)
        times[label] = step_ms
        runs.append({
            "fused": fused,
            "step_time_ms": round(step_ms, 3),
            "compute_only_ms": round(compute_ms, 3),
            "exposed_wire_frac_proxy": round(exposed, 4),
        })
        print(f"A/B {label}: step {step_ms:.2f} ms, compute "
              f"{compute_ms:.2f} ms, exposed proxy {exposed:.3f}")

    # the autotune dimension on this world: incumbent-seeded argmin
    knobs = Knobs()
    tuner = OnlineTuner(
        knobs, thresholds=[knobs.fusion_threshold_bytes],
        warmup=1, measure=3, tune_ordered=False, tune_overlap=False,
        tune_fused_collectives=True, fingerprint="fused-ab-loopback")

    def factory(overrides):
        _set_fused(bool(knobs.fused_collectives))
        step = _mini_step(mesh, n, wire)
        _clear_fused()

        def run():
            return step(w, x)

        return run

    config = tuner.tune(factory)
    trials = {bool(r["fused_collectives"]): r["step_s"]
              for r in tuner.trials
              if r.get("dimension") == "fused_collectives"
              and "step_s" in r}
    incumbent_s = None
    for r in tuner.trials:
        if r.get("dimension") == "fusion_threshold_bytes":
            incumbent_s = r["step_s"]
            break
    selected = bool(config["fused_collectives"])
    selected_s = trials.get(selected, incumbent_s)
    never_worse = (incumbent_s is None or selected_s is None
                   or selected_s <= incumbent_s)
    if not never_worse:
        failures.append(
            "autotune pinned a fused_collectives setting that measured "
            f"worse than the incumbent ({selected_s} > {incumbent_s})")
    print(f"autotune: pinned fused_collectives={selected}, "
          f"incumbent {incumbent_s and round(incumbent_s * 1e3, 2)} ms, "
          f"selected {selected_s and round(selected_s * 1e3, 2)} ms")

    if write_artifact:
        doc = {
            "note": _NOTE,
            "topology": f"cpu host mesh ({n} devices)",
            "wire": "int8 block=256",
            "runs": runs,
            "autotune": {
                "tuned_knob": "fused_collectives",
                "incumbent": False,
                "pinned": selected,
                "incumbent_step_s": incumbent_s,
                "candidate_step_s": {str(k): v
                                     for k, v in trials.items()},
                "never_worse": bool(never_worse),
            },
        }
        with open(_ARTIFACT, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"wrote {_ARTIFACT}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="run all gates, exit non-zero on failure")
    ap.add_argument("--no-artifact", action="store_true",
                    help="skip writing FUSED_AB_r09.json")
    args = ap.parse_args(argv)

    failures = []
    check_collective_parity(failures)
    check_decode_parity(failures)
    check_knob_inertness(failures)
    exposed_wire_ab(failures, write_artifact=not args.no_artifact)

    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("\nfused_check: ALL PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
