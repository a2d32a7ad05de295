#!/usr/bin/env python
"""Perf-regression gate: record a baseline, check every PR against it.

Until PR 10 nothing guarded performance across PRs — the bench
trajectory was empty and a control-plane regression (fast path silently
disengaging, profiler overhead leaking into every step) would only
surface in a manual bench run. This script is the 9th
``run_all_checks.py`` gate:

* ``--record`` runs a deterministic loopback measurement and writes
  the artifact to ``PERF_BASELINE.json`` (committed to the repo);
* ``--check`` re-runs the measurement and compares:
  - **structural** numbers (machine-independent) gate tightly:
    fast-path hit rate, steady-state negotiated bytes (must be 0),
    profiler duty-cycle bound, off-path step-hook cost, attribution
    sanity (fractions in [0,1], compute > 0) — no MFU: this is a CPU
    loopback and utils/prof.py publishes hvd_mfu on a TPU only;
  - **timing** gates loosely (the committed baseline comes from a
    different machine): step-time p50 must stay under
    ``baseline x HOROVOD_PERF_TOLERANCE`` (default 4.0).

The measurement is the unified-observability stack end-to-end: a
jitted matmul step + an 8-tensor fast-path allreduce sequence through
the EagerRuntime, marked with ``hvd.metrics.step()``, sampled by the
continuous profiler (``utils/prof.py``) — so the gate also proves the
profiler's own contract (samples taken, attribution produced, overhead
inside the duty cycle, OFF path a no-op).

``--trace-smoke`` runs the world-2 merged-trace smoke instead: two
loopback EagerRuntime workers with host timeline + flight recorder +
sampled device profiling, merged by ``scripts/trace_merge.py`` — the
merged Perfetto trace must parse and contain host, device and flight
events from BOTH ranks on one aligned clock (docs/timeline.md).

Usage:
    python scripts/perf_baseline.py --record [--out PERF_BASELINE.json]
    python scripts/perf_baseline.py --check
    python scripts/perf_baseline.py --trace-smoke
"""

import argparse
import json
import math
import multiprocessing as mp
import os
import shutil
import socket
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

BASELINE_PATH = os.path.join(_REPO, "PERF_BASELINE.json")

STEPS = 24
WARMUP = 4            # measurement excludes compile + fast-path warmup
TENSORS_PER_STEP = 8
MATMUL_N = 256
PROF_EVERY = 4
PROF_DUTY = 0.5       # generous: the gate proves the bound, not speed
OFF_PATH_ITERS = 4000
OFF_PATH_BUDGET_US = 50.0   # step-hook cost with everything off


# the one nearest-rank quantile used across scripts/: the committed
# baseline p50 must stay comparable with metrics_summary's rendering
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from metrics_summary import percentile as _sorted_percentile  # noqa: E402


def _percentile(vals, q):
    return _sorted_percentile(sorted(vals), q)


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

def measure() -> dict:
    """One deterministic loopback run of the instrumented step loop."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.eager_runtime import EagerRuntime
    from horovod_tpu.utils import metrics, prof

    # -- off-path cost first: nothing armed, the step hook must be
    # a branch + a couple of loads (the always-on discipline every
    # PR-1/PR-5 layer follows)
    metrics.reset()
    prof.reset()
    t0 = time.perf_counter()
    for _ in range(OFF_PATH_ITERS):
        with metrics.step():
            pass
    off_path_us = (time.perf_counter() - t0) / OFF_PATH_ITERS * 1e6

    prof_dir = tempfile.mkdtemp(prefix="hvd_perf_prof_")
    metrics.enable()
    prof.configure(every=PROF_EVERY, duty_cycle=PROF_DUTY,
                   directory=prof_dir)
    flops = 2.0 * MATMUL_N ** 3  # one jitted matmul per step
    prof.set_step_flops(flops)

    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((MATMUL_N, MATMUL_N), jnp.float32)
    f(x).block_until_ready()  # compile outside the measurement

    rt = EagerRuntime(0, 1, fast_path=True, fast_path_warmup=3)
    rng = np.random.RandomState(11)
    names = [f"g{i}" for i in range(TENSORS_PER_STEP)]
    payloads = [rng.randn(1024).astype(np.float32) for _ in names]

    step_times = []
    steady_bytes = []
    t_run0 = time.perf_counter()
    try:
        for step in range(STEPS):
            b0 = rt.bytes_negotiated()
            t1 = time.perf_counter()
            with metrics.step():
                f(x).block_until_ready()
                hs = {n: rt.allreduce_async(n, payloads[i])
                      for i, n in enumerate(names)}
                for n in names:
                    rt.synchronize(hs[n], timeout_s=30.0)
            dt = time.perf_counter() - t1
            if step >= WARMUP:
                step_times.append(dt)
                steady_bytes.append(rt.bytes_negotiated() - b0)
        prof.join(timeout_s=30.0)
        wall_s = time.perf_counter() - t_run0
        snap = rt.metrics_snapshot()
    finally:
        rt.shutdown()

    total_collectives = STEPS * TENSORS_PER_STEP
    hit_rate = snap.get("fast_path_hits", 0) / total_collectives
    psum = prof.summary()

    artifact = {
        "what": "perf baseline (loopback instrumented step loop)",
        "schema": 1,
        "steps": STEPS,
        "warmup": WARMUP,
        "tensors_per_step": TENSORS_PER_STEP,
        "matmul_n": MATMUL_N,
        "step_time_ms": {
            "p50": round(_percentile(step_times, 0.5) * 1e3, 3),
            "p90": round(_percentile(step_times, 0.9) * 1e3, 3),
            "mean": round(sum(step_times) / len(step_times) * 1e3, 3),
        },
        "fast_path": {
            "hit_rate": round(hit_rate, 4),
            "steady_bytes_negotiated": int(sum(steady_bytes)),
            "active": int(snap.get("fast_path_active", 0)),
        },
        "attribution": psum.get("attribution"),
        "prof": {
            "every": PROF_EVERY,
            "duty_cycle": PROF_DUTY,
            "samples": psum["samples"],
            "overhead_s": psum["overhead_s"],
            "overhead_frac": round(psum["overhead_s"] / wall_s, 4),
            "errors": psum["errors"],
        },
        "off_path_step_hook_us": round(off_path_us, 3),
        "wall_s": round(wall_s, 3),
        "env": {
            "cpus": os.cpu_count(),
            "platform": jax.default_backend(),
        },
    }
    prof.reset()
    metrics.reset()
    shutil.rmtree(prof_dir, ignore_errors=True)  # MBs of .xplane.pb
    return artifact


# ---------------------------------------------------------------------------
# structural + regression gates
# ---------------------------------------------------------------------------

def structural_failures(art: dict) -> list:
    """Machine-independent invariants every build must hold."""
    fails = []
    fp = art["fast_path"]
    if fp["hit_rate"] < 0.75:
        fails.append(f"fast-path hit rate {fp['hit_rate']} < 0.75 "
                     "(plan cache not engaging)")
    if fp["steady_bytes_negotiated"] != 0:
        fails.append(
            f"steady-state negotiated bytes "
            f"{fp['steady_bytes_negotiated']} != 0 (negotiation not "
            "bypassed after warmup)")
    attr = art.get("attribution")
    if not attr:
        fails.append("no sampled-step attribution produced")
    else:
        for k in ("compute_frac", "exposed_wire_frac", "idle_frac"):
            v = attr.get(k)
            if v is None or not (0.0 <= v <= 1.0):
                fails.append(f"attribution {k} out of range: {v}")
        if attr.get("compute_frac", 0) <= 0:
            fails.append("attribution found no compute in the sampled "
                         "step")
    p = art["prof"]
    if p["samples"] < 1:
        fails.append("profiler took no samples")
    if p["errors"]:
        fails.append(f"profiler noted {p['errors']} errors")
    # the duty bound, checked as sample CAPACITY so it is live even
    # when one expensive sample saturates the run (the common case on
    # slow CPU boxes): each sample cycle consumes cost T plus the
    # mandated idle T*(1/d - 1) = T/d of wall, so at most
    # ceil(wall * d / T) samples fit (+1 boundary slack). A gate that
    # stopped waiting would take every N-th step (steps/every samples)
    # and trip this immediately.
    if p["samples"] >= 1 and p["overhead_s"] > 0:
        per_sample = p["overhead_s"] / p["samples"]
        max_fit = math.ceil(
            art["wall_s"] * p["duty_cycle"] / per_sample) + 1
        if p["samples"] > max_fit:
            fails.append(
                f"{p['samples']} samples at ~{per_sample:.3f}s each "
                f"exceed the duty-cycle capacity {max_fit} of a "
                f"{art['wall_s']}s run (duty {p['duty_cycle']} not "
                "gating)")
    if art["off_path_step_hook_us"] > OFF_PATH_BUDGET_US:
        fails.append(
            f"off-path step hook costs "
            f"{art['off_path_step_hook_us']:.1f}us > "
            f"{OFF_PATH_BUDGET_US}us (the disabled profiler must be "
            "a no-op)")
    return fails


def regression_failures(art: dict, baseline: dict,
                        tolerance: float) -> list:
    fails = []
    b_p50 = baseline["step_time_ms"]["p50"]
    m_p50 = art["step_time_ms"]["p50"]
    if m_p50 > b_p50 * tolerance:
        fails.append(
            f"step time p50 {m_p50:.2f}ms exceeds baseline "
            f"{b_p50:.2f}ms x{tolerance} — perf regression (or set "
            "HOROVOD_PERF_TOLERANCE for a slower machine)")
    b_hit = baseline["fast_path"]["hit_rate"]
    m_hit = art["fast_path"]["hit_rate"]
    if m_hit < b_hit - 0.05:
        fails.append(f"fast-path hit rate {m_hit} fell below baseline "
                     f"{b_hit} - 0.05")
    return fails


# ---------------------------------------------------------------------------
# world-2 merged-trace smoke
# ---------------------------------------------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _trace_worker(rank, size, nport, kv_port, workdir, q):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.eager_runtime import EagerRuntime
    from horovod_tpu.utils import flight, metrics, prof
    from horovod_tpu.utils.timeline import Timeline

    metrics.enable()
    flight.configure(enabled_override=True, rank=rank,
                     sink_addr="127.0.0.1", sink_port=kv_port,
                     directory=os.path.join(workdir, "flight"),
                     handlers=False)
    tl_path = os.path.join(workdir, f"timeline_rank{rank}.json")
    tl = Timeline(tl_path)
    prof_dir = os.path.join(workdir, "prof")
    prof.configure(every=1, duty_cycle=1.0, directory=prof_dir)

    # a host timeline needs the runtime to see it: install as the
    # process-global timeline the emit sites resolve
    from horovod_tpu.core.state import global_state

    global_state().timeline = tl

    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()
    rt = EagerRuntime(rank, size, "127.0.0.1", nport, cycle_ms=1.0,
                      fast_path=False)
    rng = np.random.RandomState(3)
    try:
        for step in range(3):
            with metrics.step():
                f(x).block_until_ready()
                hs = {
                    f"g{i}": rt.allreduce_async(
                        f"g{i}", rng.randn(64).astype(np.float32))
                    for i in range(4)
                }
                for n, h in hs.items():
                    rt.synchronize(h, timeout_s=30.0)
            prof.join(timeout_s=30.0)
        flight.dump("trace_smoke")
        tl.stop()
        q.put((rank, "done", {
            "timeline": tl_path,
            "prof": os.path.join(prof_dir, f"rank{rank}"),
            "samples": prof.sample_count(),
        }))
    except Exception as e:  # pragma: no cover - diagnostic path
        q.put((rank, "error", repr(e)))
    finally:
        rt.shutdown()
        prof.reset()


def trace_smoke() -> int:
    """World-2 loopback: host + device + flight events from both ranks
    merge onto one clock-aligned Perfetto trace."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from horovod_tpu.runner.http.http_server import KVStoreServer

    kv = KVStoreServer()
    kv_port = kv.start_server()
    nport = _free_port()
    workdir = tempfile.mkdtemp(prefix="hvd_trace_smoke_")

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_trace_worker,
                    args=(r, 2, nport, kv_port, workdir, q))
        for r in range(2)
    ]
    failures = []
    results = {}
    try:
        for p in procs:
            p.start()
        deadline = time.monotonic() + 180.0
        while len(results) < 2 and time.monotonic() < deadline:
            try:
                rank, kind, payload = q.get(timeout=5.0)
            except Exception:
                continue
            results[rank] = (kind, payload)
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
    finally:
        kv.shutdown_server()

    for r in range(2):
        if r not in results:
            failures.append(f"rank {r} never reported")
        elif results[r][0] != "done":
            failures.append(f"rank {r} failed: {results[r][1]}")
        elif results[r][1].get("samples", 0) < 1:
            failures.append(f"rank {r} captured no profiler samples")
    if failures:
        for f in failures:
            print("FAIL:", f)
        return 1

    # merge through the real CLI surface
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_merge", os.path.join(_REPO, "scripts", "trace_merge.py"))
    tm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tm)
    merged = os.path.join(workdir, "merged.json")
    report_path = os.path.join(workdir, "merge_report.json")
    rc = tm.main([
        "--timeline", results[0][1]["timeline"],
        "--timeline", results[1][1]["timeline"],
        "--flight", os.path.join(workdir, "flight"),
        "--xplane", results[0][1]["prof"],
        "--xplane", results[1][1]["prof"],
        "--out", merged, "--json", report_path,
    ])
    if rc != 0:
        print("FAIL: trace_merge exited", rc)
        return 1
    with open(report_path) as f:
        report = json.load(f)
    with open(merged) as f:
        trace = json.load(f)  # the merged trace must parse
    if report["ranks"] != [0, 1]:
        failures.append(f"merged ranks {report['ranks']} != [0, 1]")
    for r in range(2):
        for kind in ("host", "device", "flight"):
            if not report["by_source"].get(f"rank{r}/{kind}"):
                failures.append(
                    f"merged trace lacks rank{r}/{kind} events: "
                    f"{report['by_source']}")
    if not isinstance(trace.get("traceEvents"), list) or not \
            trace["traceEvents"]:
        failures.append("merged trace has no traceEvents")
    summary = {
        "what": "world-2 merged-trace smoke",
        "by_source": report["by_source"],
        "span_s": report.get("span_s"),
        "clock_offsets_s": report.get("clock_offsets_s"),
        "out": merged,
        "ok": not failures,
    }
    print(json.dumps(summary, indent=1))
    for f in failures:
        print("FAIL:", f)
    return 1 if failures else 0


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--record", action="store_true",
                      help="measure and write the baseline artifact")
    mode.add_argument("--check", action="store_true",
                      help="measure and gate against the committed "
                           "baseline")
    mode.add_argument("--trace-smoke", action="store_true",
                      help="world-2 merged-trace smoke instead of the "
                           "perf measurement")
    ap.add_argument("--out", default=BASELINE_PATH,
                    help="baseline path (--record) / comparison source "
                         "(--check)")
    ap.add_argument("--tolerance", type=float, default=float(
        os.environ.get("HOROVOD_PERF_TOLERANCE", "4.0")),
        help="step-time regression multiplier vs baseline "
             "(HOROVOD_PERF_TOLERANCE, default 4.0)")
    args = ap.parse_args(argv)

    if args.trace_smoke:
        return trace_smoke()

    art = measure()
    fails = structural_failures(art)

    if args.record:
        if fails:
            print(json.dumps(art, indent=1))
            for f in fails:
                print("FAIL (refusing to record a broken baseline):", f)
            return 1
        art["recorded_unix"] = time.time()
        with open(args.out, "w") as f:
            json.dump(art, f, indent=1)
            f.write("\n")
        print(json.dumps(art, indent=1))
        print(f"perf baseline recorded: {args.out}")
        return 0

    # --check
    try:
        with open(args.out) as f:
            baseline = json.load(f)
    except (OSError, ValueError) as e:
        print(f"perf check FAILED: cannot read baseline {args.out}: {e}")
        return 1
    fails += regression_failures(art, baseline, args.tolerance)
    print(json.dumps({
        "what": "perf regression check",
        "measured": {
            "step_time_ms_p50": art["step_time_ms"]["p50"],
            "fast_path_hit_rate": art["fast_path"]["hit_rate"],
            "compute_frac": (art.get("attribution") or {}).get(
                "compute_frac"),
            "exposed_wire_frac": (art.get("attribution") or {}).get(
                "exposed_wire_frac"),
            "prof_overhead_frac": art["prof"]["overhead_frac"],
            "off_path_step_hook_us": art["off_path_step_hook_us"],
        },
        "baseline_step_time_ms_p50": baseline["step_time_ms"]["p50"],
        "tolerance": args.tolerance,
        "ok": not fails,
    }, indent=1))
    for f in fails:
        print("FAIL:", f)
    if not fails:
        print("perf check OK")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
