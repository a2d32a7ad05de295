#!/usr/bin/env python
"""Eager data-plane vs SPMD-path throughput on the real chip.

The reference's *product* is the eager path: every Torch/TF user runs
per-tensor enqueue -> background-loop negotiation -> executor dispatch
(/root/reference/horovod/torch/mpi_ops.py:107-151; benchmarked by
examples/pytorch/pytorch_synthetic_benchmark.py). This script measures
OUR equivalent end-to-end: a small MLP trains one step either

  spmd  - the jit/shard_map DistributedOptimizer step (compile-time
          fusion, zero per-step dispatch) - the headline path, or
  eager - forward/backward jit-compiled locally, then EVERY gradient
          leaf enqueued through hvd.allreduce_async into the native
          negotiation runtime and executed by the XlaExecutor
          (per-batch program-cache lookup + host<->device copies),
          then a jit optimizer apply.

and reports steps/sec for both, their ratio, and where the eager
overhead goes (negotiation vs executor dispatch vs copies), for the
BENCH_r{N}.json eager_path block.

Run on the TPU chip:  python scripts/eager_path_bench.py
(Also runs on CPU worlds for smoke: --steps 5.)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jax import shard_map  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    # the native runtime must be live BEFORE hvd.init wires the world
    os.environ.setdefault("HVD_TPU_NATIVE", "1")

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.core.state import global_state
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()
    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()

    # ---- model: MLP regression, grads ~ the per-leaf sizes a torch
    # user's layer-by-layer hooks would enqueue
    W, L, B = args.width, args.layers, args.batch
    rng = np.random.RandomState(0)
    params = {
        f"layer_{i}": {
            "w": jnp.asarray(rng.randn(W, W).astype(np.float32) * 0.02),
            "b": jnp.zeros((W,), jnp.float32),
        }
        for i in range(L)
    }
    x_host = rng.randn(B * max(n, 1), W).astype(np.float32)
    y_host = rng.randn(B * max(n, 1), W).astype(np.float32)

    def apply_fn(p, x):
        h = x
        for i in range(L):
            h = jnp.tanh(h @ p[f"layer_{i}"]["w"] + p[f"layer_{i}"]["b"])
        return h

    def loss_fn(p, x, y):
        return jnp.mean((apply_fn(p, x) - y) ** 2)

    opt = optax.sgd(0.01)

    # ---- SPMD path: one compiled step, fusion + collective inside
    dopt = hvd.DistributedOptimizer(optax.sgd(0.01))
    dstate = dopt.init(params)

    def spmd_step(p, s, x, y):
        l, g = jax.value_and_grad(loss_fn)(p, x, y)
        u, s = dopt.update(g, s, p)
        return optax.apply_updates(p, u), s, jax.lax.psum(l, "hvd").reshape(1)

    js = jax.jit(shard_map(
        spmd_step, mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P()), check_vma=False))
    shard = NamedSharding(mesh, P("hvd"))
    xd = jax.device_put(x_host, shard)
    yd = jax.device_put(y_host, shard)
    compiled = js.lower(params, dstate, xd, yd).compile()

    p1, s1 = params, dstate
    for _ in range(args.warmup):
        p1, s1, l = compiled(p1, s1, xd, yd)
    float(l[0])
    t0 = time.perf_counter()
    for _ in range(args.steps):
        p1, s1, l = compiled(p1, s1, xd, yd)
    float(l[0])
    spmd_s = (time.perf_counter() - t0) / args.steps

    # ---- eager path: local jit grad, per-leaf async enqueue through
    # the native negotiation loop + XlaExecutor, jit apply
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    apply_updates = jax.jit(
        lambda p, u: optax.apply_updates(p, u))
    est = opt.init(params)

    @jax.jit
    def opt_update(g, s, p):
        return opt.update(g, s, p)

    x_local = jnp.asarray(x_host[:B])
    y_local = jnp.asarray(y_host[:B])

    rt = global_state().eager_runtime
    coord0 = (rt._native.coord_cycle_stats()
              if rt is not None else {})

    def eager_step(p, s):
        l, g = grad_fn(p, x_local, y_local)
        leaves, treedef = jax.tree_util.tree_flatten(g)
        # the torch-adapter architecture: one async handle per tensor,
        # synchronize in submission order (mpi_ops.py:107-151)
        handles = [
            hvd.allreduce_async(leaf, name=f"g{i}", op=hvd.Average)
            for i, leaf in enumerate(leaves)
        ]
        red = [jnp.asarray(hvd.synchronize(h)) for h in handles]
        g = jax.tree_util.tree_unflatten(treedef, red)
        u, s = opt_update(g, s, p)
        return apply_updates(p, u), s, l

    def fp_snap():
        return (rt.metrics_snapshot() if rt is not None else {})

    fp0 = fp_snap()
    n_leaves = len(jax.tree_util.tree_leaves(params))
    enqueues = {"n": 0}

    p2, s2 = params, est
    # warmup timed SEPARATELY: these steps pay full negotiation while
    # the steady-state detector counts repeats; the steady window below
    # runs off the frozen plan (HOROVOD_EAGER_FAST_PATH=1 default) —
    # reporting both lets BENCH_r{N} attribute negotiation savings vs
    # execution savings (ISSUE 4 satellite)
    t0 = time.perf_counter()
    for _ in range(args.warmup):
        p2, s2, l = eager_step(p2, s2)
        enqueues["n"] += n_leaves
    float(l)
    eager_warm_s = (time.perf_counter() - t0) / max(args.warmup, 1)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        p2, s2, l = eager_step(p2, s2)
        enqueues["n"] += n_leaves
    float(l)
    eager_s = (time.perf_counter() - t0) / args.steps

    # A/B on the SAME runtime: toggle the plan cache off and repeat the
    # steady window — this is the per-tensor negotiated number the fast
    # path is measured against (cross-process drift can't fake it)
    negotiated_s = None
    if rt is not None:
        rt.set_fast_path(False)
        p2n, s2n = params, opt.init(params)
        for _ in range(args.warmup):
            p2n, s2n, l = eager_step(p2n, s2n)
        float(l)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            p2n, s2n, l = eager_step(p2n, s2n)
        float(l)
        negotiated_s = (time.perf_counter() - t0) / args.steps
        rt.set_fast_path(True)

    coord1 = (rt._native.coord_cycle_stats()
              if rt is not None else {})

    # ---- flight-recorder overhead A/B (docs/flight.md acceptance
    # gate): the same steady fast-path step with the recorder on vs
    # off. The recorder's hot-path cost is one enabled-check branch +
    # a deque append per enqueue/exec event, so "on" must sit within
    # 2% of "off"; HOROVOD_FLIGHT_RECORDER=0 additionally takes the
    # single-branch no-op path (asserted by tests/test_flight.py).
    from horovod_tpu.utils import flight as _flightmod

    flight_was_enabled = _flightmod.enabled()

    def _steady_eager():
        p, s = params, opt.init(params)
        for _ in range(max(args.warmup, 6)):
            p, s, l = eager_step(p, s)
            enqueues["n"] += n_leaves
        float(l)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            p, s, l = eager_step(p, s)
            enqueues["n"] += n_leaves
        float(l)
        return (time.perf_counter() - t0) / args.steps

    # interleave the arms and keep each arm's best pass: a background
    # scheduler hiccup landing in one arm would otherwise masquerade
    # as recorder overhead (the gate is a 2% bound — far below run-to-
    # run noise on a shared host)
    flight_on_s, flight_off_s = float("inf"), float("inf")
    for _ in range(2):
        _flightmod.enable()
        flight_on_s = min(flight_on_s, _steady_eager())
        _flightmod.disable()
        flight_off_s = min(flight_off_s, _steady_eager())
    if flight_was_enabled:
        _flightmod.enable()
    flight_block = {
        "steady_step_ms_on": round(flight_on_s * 1e3, 3),
        "steady_step_ms_off": round(flight_off_s * 1e3, 3),
        "overhead_frac": round(flight_on_s / flight_off_s - 1.0, 4),
        "events_buffered": _flightmod.event_count(),
    }

    # ---- health-monitor overhead A/B (docs/health.md acceptance
    # gate): the same steady fast-path step, now wrapped in
    # metrics.step() with metrics enabled in BOTH arms (the health
    # monitor rides the metrics step-record stream — its marginal cost
    # is the observer call + detector/rule-engine update per step), vs
    # the identical instrumented step with health off (observer slot
    # None: one load + is-None check). "on" must sit within the flight
    # recorder's 2% envelope.
    from horovod_tpu import health as _healthmod
    from horovod_tpu.utils import metrics as _hm_metrics

    _hm_metrics_was = _hm_metrics.enabled()
    _health_was = _healthmod.enabled()

    def _steady_eager_instrumented():
        p, s = params, opt.init(params)
        for _ in range(max(args.warmup, 6)):
            with _hm_metrics.step():
                p, s, l = eager_step(p, s)
            enqueues["n"] += n_leaves
        float(l)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            with _hm_metrics.step():
                p, s, l = eager_step(p, s)
            enqueues["n"] += n_leaves
        float(l)
        return (time.perf_counter() - t0) / args.steps

    _hm_metrics.enable()
    health_on_s, health_off_s = float("inf"), float("inf")
    for _ in range(2):
        _healthmod.enable()
        health_on_s = min(health_on_s, _steady_eager_instrumented())
        _healthmod.disable()
        health_off_s = min(health_off_s, _steady_eager_instrumented())
    if _health_was:
        _healthmod.enable()
    if not _hm_metrics_was:
        _hm_metrics.disable()
    health_block = {
        "steady_step_ms_on": round(health_on_s * 1e3, 3),
        "steady_step_ms_off": round(health_off_s * 1e3, 3),
        "overhead_frac": round(health_on_s / health_off_s - 1.0, 4),
        "incidents": _healthmod.incident_count(),
    }

    # ---- grouped eager path: the torch-adapter group API — ONE
    # all-or-nothing negotiation round and one fused executor batch for
    # all leaves (grouped_allreduce_async), vs 8 per-tensor rounds above
    def eager_grouped_step(p, s):
        l, g = grad_fn(p, x_local, y_local)
        leaves, treedef = jax.tree_util.tree_flatten(g)
        h = hvd.grouped_allreduce_async(leaves, op=hvd.Average,
                                        name="ggrp")
        red = [jnp.asarray(r) for r in hvd.synchronize(h)]
        g = jax.tree_util.tree_unflatten(treedef, red)
        u, s = opt_update(g, s, p)
        return apply_updates(p, u), s, l

    p4, s4 = params, opt.init(params)
    # grouped warmup needs its own steady-state relearn (new names ⇒
    # the per-tensor plan was invalidated); K+2 repeats cover it
    for _ in range(max(args.warmup, 6)):
        p4, s4, l = eager_grouped_step(p4, s4)
        enqueues["n"] += n_leaves
    float(l)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        p4, s4, l = eager_grouped_step(p4, s4)
        enqueues["n"] += n_leaves
    float(l)
    grouped_s = (time.perf_counter() - t0) / args.steps

    # ---- pure runtime round-trip: enqueue+synchronize one tiny
    # PRE-COMPUTED tensor — no grad compute to wait on, so this is the
    # floor cost of (coordinator cycle + worker wakeup + executor
    # dispatch) alone, separating runtime latency from device-wait
    # inside "negotiate_execute" below.
    tiny = jnp.ones((8,), jnp.float32)
    jax.block_until_ready(tiny)
    # measure the NEGOTIATED round trip: the plan cache would turn this
    # into a dict store + dispatch and hide the number being probed
    if rt is not None:
        rt.set_fast_path(False)
    for _ in range(args.warmup):
        hvd.synchronize(hvd.allreduce_async(tiny, name="rtt"))
    t0 = time.perf_counter()
    for _ in range(args.steps):
        hvd.synchronize(hvd.allreduce_async(tiny, name="rtt"))
    rtt_s = (time.perf_counter() - t0) / args.steps
    if rt is not None:
        rt.set_fast_path(True)

    # ---- phase decomposition: time each phase of the SAME pipelined
    # step (no extra barriers: each would add a host sync the pipelined
    # step does not pay).
    # grad/apply measure async dispatch. With the plan cache active the
    # step's blocking point MOVES: the last enqueue dispatches the
    # cached plan inline (so "enqueue" absorbs the wait for grads on
    # device + the executor dispatch) and synchronize() just hands back
    # stored futures, so "negotiate_execute" collapses toward zero —
    # exactly the negotiation cost the fast path removed. With
    # HOROVOD_EAGER_FAST_PATH=0 the old attribution (blocking inside
    # synchronize) returns. The phases sum to the pipelined step time.
    def timed_eager_step(p, s, acc):
        t = time.perf_counter()
        l, g = grad_fn(p, x_local, y_local)
        acc["grad_dispatch"] += time.perf_counter() - t

        t = time.perf_counter()
        leaves, treedef = jax.tree_util.tree_flatten(g)
        handles = [
            hvd.allreduce_async(leaf, name=f"g{i}", op=hvd.Average)
            for i, leaf in enumerate(leaves)
        ]
        acc["enqueue"] += time.perf_counter() - t

        t = time.perf_counter()
        red = [jnp.asarray(hvd.synchronize(h)) for h in handles]
        acc["negotiate_execute"] += time.perf_counter() - t

        t = time.perf_counter()
        g = jax.tree_util.tree_unflatten(treedef, red)
        u, s = opt_update(g, s, p)
        p = apply_updates(p, u)
        acc["apply_dispatch"] += time.perf_counter() - t
        return p, s, l

    phases = {"grad_dispatch": 0.0, "enqueue": 0.0,
              "negotiate_execute": 0.0, "apply_dispatch": 0.0}
    p3, s3 = params, opt.init(params)
    # re-reach steady state first (the rtt section changed the
    # sequence), so the breakdown describes the fast-path step
    warm = {k: 0.0 for k in phases}
    for _ in range(max(args.warmup, 6)):
        p3, s3, _ = timed_eager_step(p3, s3, warm)
        enqueues["n"] += n_leaves
    for _ in range(args.steps):
        p3, s3, _ = timed_eager_step(p3, s3, phases)
        enqueues["n"] += n_leaves
    breakdown = {k: round(v / args.steps * 1e3, 2)
                 for k, v in phases.items()}

    # ---- replication overhead A/B (docs/recovery.md acceptance
    # gate): the same steady eager step plus a state.commit() per
    # step, with async peer snapshot replication on vs off. The
    # commit hook's critical-path cost is a dict-reference stash + a
    # condition notify (pickling/chunking/shipping run on the
    # replicator thread, coalescing to the newest snapshot when it
    # falls behind), so "on" must sit within 3% of "off";
    # HOROVOD_REPLICATION=0 additionally takes the single-branch
    # no-op path (asserted by tests/test_recovery.py).
    replication_block = None
    _partner_proc = None
    try:
        import json as _json
        import subprocess
        import textwrap

        from horovod_tpu.elastic import replication as _rep
        from horovod_tpu.elastic.state import TpuState
        from horovod_tpu.runner.http.http_server import (
            KVStoreServer as _KV,
        )

        _rkv = _KV()
        _rkv_port = _rkv.start_server()
        # the ring partner's replica store lives in its own PROCESS,
        # as in production (another rank on another host) — an
        # in-process server would bill the partner's receive CPU to
        # this trainer and fake replication overhead
        _repo_dir = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        _partner_proc = subprocess.Popen(
            [sys.executable, "-c", textwrap.dedent("""
                import sys, time
                sys.path.insert(0, sys.argv[1])
                from horovod_tpu.runner.http.http_server import (
                    KVStoreServer)
                kv = KVStoreServer()
                print(kv.start_server(), flush=True)
                time.sleep(3600)
            """), _repo_dir],
            stdout=subprocess.PIPE, text=True)
        _partner_port = int(_partner_proc.stdout.readline())
        _rep._http_put(
            "127.0.0.1", _rkv_port, _rep.STORE_SCOPE, "rank_1",
            _json.dumps([("127.0.0.1", _partner_port)]).encode())
        _rstate = TpuState(params=params)

        rep_stats = {}
        rep_on_wall = [0.0]

        def _steady_commit(arm_on):
            t_arm0 = time.perf_counter()
            if arm_on:
                _rep.configure(
                    enabled_override=True, rank=0, size=2, partners=1,
                    rendezvous_addr="127.0.0.1",
                    rendezvous_port=_rkv_port)
            else:
                _rep.stop()
            p, s = params, opt.init(params)
            for _ in range(max(args.warmup, 6)):
                p, s, l = eager_step(p, s)
                _rstate.params = p
                _rstate.commit()
            float(l)
            # per-step MEDIAN, not window mean: the duty-cycled
            # replicator touches at most ~d of wall time, so the
            # steady-state step reading must not be dominated by the
            # one step a ship (or a scheduler hiccup) lands on
            times = []
            for _ in range(args.steps):
                t0 = time.perf_counter()
                p, s, l = eager_step(p, s)
                _rstate.params = p
                _rstate.commit()
                float(l)
                times.append(time.perf_counter() - t0)
            times.sort()
            dt = times[len(times) // 2]
            if arm_on:  # accumulate across on-arm passes (each pass
                # reconfigures and gets a fresh replicator)
                for k, v in _rep.replicator().stats.items():
                    rep_stats[k] = (
                        v if k == "last_epoch"
                        else rep_stats.get(k, 0) + v)
                rep_on_wall[0] += time.perf_counter() - t_arm0
            return dt

        # interleave arms, min of per-pass medians (the flight-
        # recorder A/B's noise discipline), and report the off-arm
        # pass-to-pass spread as the harness noise floor: on a busy
        # 2-core host A/A spread runs ~10%, far above the 3% gate, so
        # the wall number must be read against noise_frac while the
        # structural bound (replicator busy_s vs wall, capped by the
        # duty cycle) is exact
        ons, offs = [], []
        for _ in range(3):
            ons.append(_steady_commit(True))
            offs.append(_steady_commit(False))
        rep_on_s, rep_off_s = min(ons), min(offs)
        _rep.reset()
        _partner_proc.terminate()
        _rkv.shutdown_server()
        replication_block = {
            "commit_step_ms_on": round(rep_on_s * 1e3, 3),
            "commit_step_ms_off": round(rep_off_s * 1e3, 3),
            "overhead_frac": round(rep_on_s / rep_off_s - 1.0, 4),
            "noise_frac": round(max(offs) / min(offs) - 1.0, 4),
            "replicator_busy_frac": round(
                rep_stats.get("busy_s", 0.0)
                / max(rep_on_wall[0], 1e-9), 4),
            "replicator": {
                k: (round(v, 3) if k == "busy_s" else int(v))
                for k, v in rep_stats.items()
            },
        }
    except Exception as e:  # bench must survive a broken loopback env
        replication_block = {"error": repr(e)}
    finally:
        if _partner_proc is not None:
            _partner_proc.terminate()

    # ---- compression A/B (docs/compression.md acceptance gate): the
    # same steady eager step under each wire mode — none vs bf16 vs
    # int8 — reporting steady step time and the wire-byte counters
    # (hvd_wire_bytes_{logical,sent}_total). Metrics stay enabled for
    # all three arms so the instrumentation cost cancels; each arm
    # re-reaches steady state first (set_wire flushes the plan cache).
    compression_block = None
    if rt is not None:
        from horovod_tpu.utils import metrics as _metricsmod

        _metrics_was = _metricsmod.enabled()
        _wire_was = rt._executor_wire()  # restore the configured wire
        try:
            _metricsmod.enable()

            def _wire_counters():
                snap = _metricsmod.registry.snapshot()

                def tot(name):
                    fam = snap.get(name, {})
                    return float(sum(fam.values())) if fam else 0.0

                return (tot("hvd_wire_bytes_logical_total"),
                        tot("hvd_wire_bytes_sent_total"))

            compression_block = {}
            for mode in ("none", "bf16", "int8"):
                rt.set_wire(mode)
                p6, s6 = params, opt.init(params)
                for _ in range(max(args.warmup, 6)):
                    p6, s6, l = eager_grouped_step(p6, s6)
                    enqueues["n"] += n_leaves
                float(l)
                l0, b0 = _wire_counters()
                t0 = time.perf_counter()
                for _ in range(args.steps):
                    p6, s6, l = eager_grouped_step(p6, s6)
                    enqueues["n"] += n_leaves
                float(l)
                dt = (time.perf_counter() - t0) / args.steps
                l1, b1 = _wire_counters()
                logical, sent = l1 - l0, b1 - b0
                compression_block[mode] = {
                    "steady_step_ms": round(dt * 1e3, 3),
                    "wire_bytes_logical": int(logical),
                    "wire_bytes_sent": int(sent),
                    "wire_ratio": round(logical / sent, 3) if sent else None,
                }
        except Exception as e:  # bench must survive a broken env
            compression_block = {"error": repr(e)}
        finally:
            # the rest of the bench must measure the wire the user
            # configured (HOROVOD_COMPRESSION), with the pre-A/B
            # instrumentation state — also on the exception path
            try:
                rt.set_wire(_wire_was)
            except Exception:
                pass
            if not _metrics_was:
                _metricsmod.disable()

    fp1 = fp_snap()
    fast_path = None
    if fp1:
        hits = int(fp1.get("fast_path_hits", 0)
                   - fp0.get("fast_path_hits", 0))
        fast_path = {
            "enabled": bool(rt is not None and rt.fast_path_stats()
                            ["enabled"]),
            "hit_rate": round(hits / max(enqueues["n"], 1), 4),
            "hits": hits,
            "steps": int(fp1.get("fast_path_steps", 0)
                         - fp0.get("fast_path_steps", 0)),
            "invalidations": int(
                fp1.get("fast_path_invalidations", 0)
                - fp0.get("fast_path_invalidations", 0)),
            "activations": int(
                fp1.get("fast_path_activations", 0)
                - fp0.get("fast_path_activations", 0)),
            "negotiation_bypassed_bytes": int(
                fp1.get("negotiation_bypassed_bytes", 0)
                - fp0.get("negotiation_bypassed_bytes", 0)),
        }

    report = {
        "what": "per-step wall time, 4x1024 MLP batch %d, single chip"
                % B,
        "backend": jax.default_backend(),
        "native_eager": rt is not None,
        "grad_tensors_per_step": n_leaves,
        "spmd_step_ms": round(spmd_s * 1e3, 2),
        # steady-state (plan-cache) step vs its own warmup (full
        # negotiation) vs the A/B with the cache toggled off
        "eager_step_ms": round(eager_s * 1e3, 2),
        "eager_warmup_step_ms": round(eager_warm_s * 1e3, 2),
        "eager_negotiated_step_ms": (
            round(negotiated_s * 1e3, 2)
            if negotiated_s is not None else None),
        "eager_over_spmd": round(eager_s / spmd_s, 2),
        "eager_grouped_step_ms": round(grouped_s * 1e3, 2),
        "eager_grouped_over_spmd": round(grouped_s / spmd_s, 2),
        "cache_hits": int(rt.cache_hits()) if rt is not None else None,
        "fast_path": fast_path,
        "flight_recorder": flight_block,
        "health": health_block,
        "replication": replication_block,
        "compression": compression_block,
        "runtime_roundtrip_ms": round(rtt_s * 1e3, 2),
        "phase_breakdown_ms": breakdown,
    }
    if coord1:
        cyc = max(coord1["cycles"] - coord0.get("cycles", 0), 1)
        report["coordinator"] = {
            "cycles_during_eager": int(cyc),
            "cpu_us_per_cycle": round(
                (coord1["work_us"] - coord0.get("work_us", 0)) / cyc, 1),
        }
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    hvd.shutdown()


if __name__ == "__main__":
    main()
