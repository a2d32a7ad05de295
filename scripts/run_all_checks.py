#!/usr/bin/env python
"""Umbrella local PR gate: run every smoke check with one command.

The repo's check scripts each gate one subsystem; this script runs the
whole family and exits nonzero if ANY fails, so one command gates a PR
locally before the full pytest tier:

* ``metrics`` — a tiny loopback run with ``HOROVOD_TPU_METRICS_FILE``
  set, then ``scripts/metrics_summary.py --check`` on the JSONL
  (telemetry flowed);
* ``chaos`` — ``scripts/chaos_check.py`` (elastic recovery under
  worker kill + HTTP error rates + discovery flap);
* ``eager_fastpath`` — ``scripts/eager_fastpath_check.py`` (plan cache
  engages, bitwise parity, zero steady negotiated bytes);
* ``serving`` — an in-process engine+batcher+server driven by
  ``scripts/serving_loadgen.py --check`` (traffic succeeds, batching
  metrics live);
* ``flight`` — ``scripts/flight_check.py`` (world-2 stall autopsy:
  straggler named, dumps aggregated, rank-labeled /metrics);
* ``recovery`` — ``scripts/recovery_check.py`` (world-2 loopback
  kill-and-recover: the respawned rank restores from the surviving
  peer's replica through the recovery ladder);
* ``compression`` — ``scripts/compression_check.py`` (world-2 loopback
  compressed data plane: int8 wire-byte ratio >= 3.5x, bf16 ~2x, and
  HOROVOD_COMPRESSION=none bitwise-exact parity);
* ``overlap`` — ``scripts/overlap_check.py --schedule-ab --cpu`` on the
  MLP-sized ``tiny`` vehicle (backward-interleaved scheduler: schedule
  on/off bitwise parity over plain + ZeRO + int8, and the staged mode
  provably pins backward compute behind the first gradient
  collective);
* ``fsdp`` — ``scripts/fsdp_check.py --check`` (fully-sharded
  parameters: prefetch-vs-upfront AND regather-vs-saved bitwise
  parity on plain + int8 wires, forward gather + backward
  reduce-scatter pin structure, measured per-device param bytes ≤
  replicated/world + one bucket, the pre-opt HLO peak-liveness proof
  of the regather within-step bound, the host-offload smoke, and the
  HOROVOD_FSDP/REGATHER/OFFLOAD knobs inert on non-FSDP lowerings);
* ``autotune`` — ``scripts/autotune_check.py --check`` (closed-loop
  autotuner: world-2 loopback sweep with skewed per-rank timings pins
  identical winners on both ranks, the pinned config is never worse
  than the incumbent default, a cache-hit rerun performs 0 tuning
  compiles, pin-then-rebuild is bitwise, and the decision trail is
  visible in /metrics + the StepStats JSONL + metrics_summary);
* ``decode`` — ``scripts/decode_check.py --check`` (continuous-
  batching generation: mixed-length streaming requests >= 2x aggregate
  tokens/sec over a static-batch baseline on the same engine, greedy
  outputs bitwise-equal to the one-at-a-time reference with fp32 KV,
  int8 KV within the documented tolerance, and the replica autoscaler
  grows then SIGTERM-drains (exit 83) a world-2 replica off the live
  queue-wait/occupancy gauges with zero client-visible failures);
* ``multipod`` — ``scripts/multipod_check.py --check`` (multi-pod
  federation on simulated pods: per-pod relays cut the root server's
  request count by >= the pod fan-in factor with a pod-labeled
  aggregated /metrics, the localK outer loop trains inside the
  documented envelope of the sync baseline over the int8 DCN leg,
  K=1 is bitwise-identical to the plain SPMD path, and a root
  failover with relays attached loses nothing);
* ``health`` — ``scripts/health_check.py`` (fleet-health monitor:
  world-2 loopback run where an injected rank-1 delay degrades the
  root's live ``GET /health`` verdict naming rank 1, the
  ``hvd_alert_active`` gauge fires then clears on the aggregated
  scrape, the incident JSONL carries the fire/clear pair, and the
  anomaly-triggered flight dump lands on the sink);
* ``perf`` — ``scripts/perf_baseline.py --check`` (the perf-regression
  gate: structural invariants — fast-path engaged, zero steady
  negotiated bytes, profiler sampled + attributed inside its duty
  cycle, off-path step hook a no-op — plus step-time
  p50 vs the committed ``PERF_BASELINE.json`` under
  ``HOROVOD_PERF_TOLERANCE``), then ``--trace-smoke`` (world-2
  loopback merged Perfetto trace holds host + device + flight events
  from both ranks on one aligned clock).

Usage:
    python scripts/run_all_checks.py [--only NAME ...] [--skip NAME ...]
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import textwrap
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

_SCRIPTS = os.path.join(_REPO, "scripts")


def _env():
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _run(argv, timeout_s=600, env=None):
    proc = subprocess.run(
        argv, env=env or _env(), cwd=_REPO, timeout=timeout_s,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    return proc.returncode, proc.stdout


# ---------------------------------------------------------------------------
# the gates
# ---------------------------------------------------------------------------

def check_metrics() -> "tuple[int, str]":
    """Produce a metrics JSONL with a tiny loopback run, then gate it
    with metrics_summary --check."""
    with tempfile.TemporaryDirectory(prefix="hvd_checks_") as d:
        jsonl = os.path.join(d, "run.jsonl")
        src = textwrap.dedent(f"""
            import jax.numpy as jnp
            import horovod_tpu as hvd
            hvd.init()
            for _ in range(3):
                with hvd.metrics.step():
                    hvd.allreduce(jnp.ones((64,), jnp.float32))
            hvd.shutdown()
        """)
        env = _env()
        env["HOROVOD_TPU_METRICS_FILE"] = jsonl
        rc, out = _run([sys.executable, "-c", src], env=env)
        if rc != 0:
            return rc, out
        rc2, out2 = _run([
            sys.executable, os.path.join(_SCRIPTS, "metrics_summary.py"),
            jsonl, "--check",
        ])
        return rc2, out + out2


def check_chaos():
    return _run([sys.executable, os.path.join(_SCRIPTS, "chaos_check.py")])


def check_eager_fastpath():
    return _run([
        sys.executable, os.path.join(_SCRIPTS, "eager_fastpath_check.py"),
        "--check",
    ])


def check_serving():
    """Spin up engine → batcher → ServingServer in-process and fire
    serving_loadgen --check at it (the same wire surface the replica
    entrypoint serves, without needing an orbax checkpoint)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from horovod_tpu.serving.batcher import DynamicBatcher
    from horovod_tpu.serving.engine import InferenceEngine
    from horovod_tpu.serving.server import ServingServer
    from horovod_tpu.utils import metrics

    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(8, 4).astype(np.float32))
    engine = InferenceEngine(
        lambda p, x: jnp.tanh(x @ p), w, buckets=(1, 4, 8),
        feature_shape=(8,),
    )
    metrics.enable()
    batcher = DynamicBatcher(engine, max_batch=8, max_wait_ms=2.0,
                             queue_limit=64).start()
    server = ServingServer(batcher.__call__, port=0)
    port = server.start()
    try:
        url = f"http://127.0.0.1:{port}"
        return _run([
            sys.executable, os.path.join(_SCRIPTS, "serving_loadgen.py"),
            "--url", url, "--requests", "40", "--concurrency", "4",
            "--input-shape", "8", "--examples", "1:4",
            "--secret-env", "", "--scrape", f"{url}/metrics", "--check",
        ])
    finally:
        server.shutdown()
        batcher.close(drain=False)
        metrics.reset()


def check_flight():
    return _run([sys.executable, os.path.join(_SCRIPTS, "flight_check.py"),
                 "--check"])


def check_recovery():
    return _run([
        sys.executable, os.path.join(_SCRIPTS, "recovery_check.py"),
        "--check",
    ])


def check_compression():
    return _run([
        sys.executable, os.path.join(_SCRIPTS, "compression_check.py"),
        "--check",
    ])


def check_overlap():
    """Schedule-on/off A/B on the CPU host mesh: bitwise parity + the
    pinned-dependency structure (the 8th gate; the v5e AOT numbers come
    from the same script without --cpu)."""
    env = _env()
    if "xla_force_host_platform_device_count" not in env.get(
            "XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
    with tempfile.TemporaryDirectory(prefix="hvd_overlap_") as d:
        return _run([
            sys.executable, os.path.join(_SCRIPTS, "overlap_check.py"),
            "--schedule-ab", "--cpu", "--check", "--model", "tiny",
            "--fusion-mb", "0.02",
            "--out", os.path.join(d, "SCHEDULE_AB.json"),
        ], env=env)


def check_fsdp():
    """The fully-sharded-parameter gate: parity vs the gathered
    reference AND regather-vs-saved, pin structure both directions,
    memory bound, peak-liveness proof, offload smoke, knob hashes."""
    env = _env()
    if "xla_force_host_platform_device_count" not in env.get(
            "XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
    return _run([
        sys.executable, os.path.join(_SCRIPTS, "fsdp_check.py"),
        "--check",
    ], env=env)


def check_autotune():
    """The closed-loop autotuner gate: agreement, never-worse,
    warm start, pin-then-rebuild determinism, decision trail."""
    env = _env()
    if "xla_force_host_platform_device_count" not in env.get(
            "XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=2"
                            ).strip()
    return _run([
        sys.executable, os.path.join(_SCRIPTS, "autotune_check.py"),
        "--check",
    ], env=env)


def check_decode():
    """The continuous-batching decode gate: parity, int8 KV
    tolerance, >= 2x over static batching, autoscale grow/drain."""
    env = _env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return _run([
        sys.executable, os.path.join(_SCRIPTS, "decode_check.py"),
        "--check",
    ], env=env)


def check_multipod():
    """The multi-pod federation gate: relay fan-in reduction,
    localK convergence envelope, K=1 bitwise parity, root failover
    with relays attached."""
    env = _env()
    if "xla_force_host_platform_device_count" not in env.get(
            "XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
    return _run([
        sys.executable, os.path.join(_SCRIPTS, "multipod_check.py"),
        "--check",
    ], env=env)


def check_health():
    """The fleet-health monitor gate: live straggler naming,
    alert fire/clear, incident records, anomaly-triggered capture."""
    return _run([
        sys.executable, os.path.join(_SCRIPTS, "health_check.py"),
        "--check",
    ])


def check_perf():
    """The perf-regression gate + the merged-trace smoke (one gate:
    both run the unified-observability stack end-to-end)."""
    rc, out = _run([
        sys.executable, os.path.join(_SCRIPTS, "perf_baseline.py"),
        "--check",
    ])
    if rc != 0:
        return rc, out
    rc2, out2 = _run([
        sys.executable, os.path.join(_SCRIPTS, "perf_baseline.py"),
        "--trace-smoke",
    ])
    return rc2, out + out2


GATES = [
    ("metrics", check_metrics),
    ("chaos", check_chaos),
    ("eager_fastpath", check_eager_fastpath),
    ("serving", check_serving),
    ("flight", check_flight),
    ("recovery", check_recovery),
    ("compression", check_compression),
    ("overlap", check_overlap),
    ("fsdp", check_fsdp),
    ("autotune", check_autotune),
    ("decode", check_decode),
    ("multipod", check_multipod),
    ("health", check_health),
    ("perf", check_perf),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", action="append", default=[],
                    help="run only gates whose name contains this")
    ap.add_argument("--skip", action="append", default=[],
                    help="skip gates whose name contains this")
    ap.add_argument("--verbose", action="store_true",
                    help="print each gate's full output, not just "
                         "failures")
    args = ap.parse_args(argv)

    selected = [
        (name, fn) for name, fn in GATES
        if (not args.only or any(o in name for o in args.only))
        and not any(s in name for s in args.skip)
    ]
    if not selected:
        print("run_all_checks: no gates selected", file=sys.stderr)
        return 2

    outcomes = {}
    t_all = time.perf_counter()
    for name, fn in selected:
        t0 = time.perf_counter()
        try:
            rc, out = fn()
        except Exception as e:  # a crashed gate is a failed gate
            rc, out = 1, f"gate raised: {e!r}"
        dt = time.perf_counter() - t0
        outcomes[name] = rc
        status = "OK" if rc == 0 else f"FAIL (exit {rc})"
        print(f"[{name}] {status} in {dt:.1f}s")
        if rc != 0 or args.verbose:
            print(textwrap.indent(out.rstrip(), "    "))
    failed = [n for n, rc in outcomes.items() if rc != 0]
    print(json.dumps({
        "what": "umbrella smoke gates",
        "outcomes": outcomes,
        "wall_s": round(time.perf_counter() - t_all, 1),
        "ok": not failed,
    }))
    if failed:
        print("run_all_checks FAILED:", ", ".join(failed))
        return 1
    print(f"run_all_checks OK: {len(outcomes)} gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
