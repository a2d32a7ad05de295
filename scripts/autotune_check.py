#!/usr/bin/env python
"""Closed-loop autotuner smoke gate (the 11th run_all_checks gate).

Two phases (docs/autotune.md):

**World-2 loopback agreement** — two OnlineTuner processes sweep the
same candidate list with DELIBERATELY skewed per-rank timings (each
rank's step sleeps a candidate-dependent amount, inverted between the
ranks, so their local argmins disagree). The rank-0-wins agreement
protocol must make both ranks pin IDENTICAL winners, and both ranks'
compile-override sequences must be identical after every agreement
point — the property that guarantees no rank ever compiles a
rank-mismatched collective structure. Each rank then re-tunes against
its warm-start cache and must pin the same configuration with ZERO
tuning compiles.

**Real-step loopback sweep** — a jit/shard_map MLP train step over a
2-device CPU world is swept with the incumbent default seeded first:

* never-worse guarantee: the pinned configuration's measured steady
  step time is <= the incumbent default's trial time (incumbent
  seeding makes this structural; the gate verifies it held);
* cache-hit rerun performs 0 tuning compiles;
* pin-then-rebuild determinism: with the numerics-changing dimensions
  off, the step built through the factory under the pinned
  configuration is BITWISE equal to the same configuration compiled
  directly from the knobs;
* decision trail: hvd_autotune_* series appear in /metrics (and lint),
  ``autotune`` event lines land in the StepStats JSONL, and
  scripts/metrics_summary.py renders the sweep table.

Exits 0 and prints a JSON summary on success; exits 1 with the first
failed assertion otherwise.

Usage:
    python scripts/autotune_check.py [--check] [--out AUTOTUNE.json]
"""

import argparse
import json
import multiprocessing as mp
import os
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2").strip()

#: per-rank candidate sleep maps (seconds) — rank 1's ordering is the
#: INVERSE of rank 0's, so the local argmins disagree and only the
#: agreement protocol can make the pins match. 1 MiB is the true winner
#: (rank 0 is the coordinator whose measurements decide).
_SLEEPS = {
    0: {1 << 20: 0.002, 128 << 20: 0.010},
    1: {1 << 20: 0.010, 128 << 20: 0.002},
}


def _world2_worker(rank, q01, ret):
    """One loopback tuner rank: skewed sweep + cache-hit rerun."""
    try:
        import jax.numpy as jnp

        from horovod_tpu.core.knobs import Knobs
        from horovod_tpu.ops.autotune import OnlineTuner
        from horovod_tpu.utils import metrics

        metrics.enable()

        def agree(best, best_t):
            # rank-0-wins over a loopback channel (the in-process stand-in
            # for the broadcast_object discipline)
            if rank == 0:
                q01.put((best, best_t))
                return best, best_t
            return q01.get(timeout=60)

        knobs = Knobs()  # incumbent: 128 MiB threshold, ordered on
        compile_log = []

        def factory(overrides):
            compile_log.append(dict(overrides))
            delay = _SLEEPS[rank][knobs.fusion_threshold_bytes]

            def step():
                time.sleep(delay)
                return jnp.zeros(())

            return step

        cache = os.path.join(tempfile.mkdtemp(prefix="hvd_at_"),
                             f"cache{rank}.json")
        tuner = OnlineTuner(
            knobs, thresholds=[knobs.fusion_threshold_bytes, 1 << 20],
            warmup=0, measure=3, tune_overlap=False,
            cache_path=cache, fingerprint="world2check", agree_fn=agree)
        config = tuner.tune(factory)

        # cache-hit rerun: zero tuning compiles, same pinned config
        knobs2 = Knobs()

        def must_not_compile(overrides):
            raise AssertionError("warm-started rerun invoked the factory")

        tuner2 = OnlineTuner(
            knobs2, thresholds=[knobs2.fusion_threshold_bytes, 1 << 20],
            warmup=0, measure=3, tune_overlap=False,
            cache_path=cache, fingerprint="world2check", agree_fn=agree)
        config2 = tuner2.tune(must_not_compile)
        assert tuner2.compiles == 0, (
            f"rank {rank}: warm-started rerun performed "
            f"{tuner2.compiles} compiles")
        assert tuner2.pin_source == "cache", tuner2.pin_source
        assert config2 == config, (config2, config)
        assert knobs2.fusion_threshold_bytes == \
            config["fusion_threshold_bytes"]

        scrape = metrics.scrape()
        assert "hvd_autotune_trials_total" in scrape
        assert "hvd_autotune_dimension" in scrape
        lint = metrics.lint_exposition(scrape)
        assert not lint, lint[:3]

        # the candidate this rank's OWN clock preferred
        local = {r["fusion_threshold_bytes"]: r["step_s"]
                 for r in tuner.trials
                 if r.get("dimension") == "fusion_threshold_bytes"}
        ret.put((rank, "ok", {
            "config": config,
            "compiles": compile_log,
            "trials": tuner.trials,
            "local_argmin": min(local, key=local.get),
        }))
    except Exception as e:
        import traceback

        ret.put((rank, "fail", f"{e!r}\n{traceback.format_exc()}"))


def check_world2_agreement(failures, report):
    ctx = mp.get_context("spawn")
    q01, ret = ctx.Queue(), ctx.Queue()
    procs = [ctx.Process(target=_world2_worker, args=(r, q01, ret))
             for r in (0, 1)]
    for p in procs:
        p.start()
    results = {}
    for _ in procs:
        try:
            rank, status, payload = ret.get(timeout=120)
        except Exception:
            failures.append("world-2 worker did not report")
            break
        if status != "ok":
            failures.append(f"world-2 rank {rank} failed: {payload}")
        else:
            results[rank] = payload
    for p in procs:
        p.join(timeout=30)
        if p.is_alive():
            p.terminate()
            failures.append("world-2 worker hung")
    if len(results) != 2:
        return
    r0, r1 = results[0], results[1]
    if r0["config"] != r1["config"]:
        failures.append(
            f"ranks pinned DIFFERENT winners: {r0['config']} vs "
            f"{r1['config']}")
    if r0["compiles"] != r1["compiles"]:
        failures.append(
            "ranks compiled different candidate sequences — a "
            "rank-mismatched collective structure would hang: "
            f"{r0['compiles']} vs {r1['compiles']}")
    # the skew was real: rank 1's own clock preferred the OTHER
    # candidate, yet it pinned rank 0's winner
    if r1["local_argmin"] == r0["config"]["fusion_threshold_bytes"]:
        failures.append(
            "rank 1's local argmin matched rank 0's — the skew did not "
            "bite, agreement untested")
    if r0["config"]["fusion_threshold_bytes"] != 1 << 20:
        failures.append(
            f"rank 0's measured winner should be 1 MiB, pinned "
            f"{r0['config']}")
    report["world2"] = {
        "pinned": r0["config"],
        "identical_compile_sequences": r0["compiles"] == r1["compiles"],
        "rank1_local_argmin": r1["local_argmin"],
        "trials_per_rank": len(r0["trials"]),
    }


def _mlp_factory(mesh, params, state, dopt, compile_log):
    """Real-step factory: shard_map MLP + DistributedOptimizer over the
    2-device loopback world (fixed state: candidates must be
    numerically comparable and the pin-then-rebuild check bitwise)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def build_step(overrides):
        compile_log.append(dict(overrides))

        def step(p, s, x, y):
            def loss_fn(p):
                h = jnp.tanh(x @ p["a"])
                return jnp.mean((h @ p["b"] - y) ** 2)

            loss, g = jax.value_and_grad(loss_fn)(p)
            u, _ = dopt.update(g, s, p)
            import optax

            return (optax.apply_updates(p, u),
                    jax.lax.pmean(loss, "hvd").reshape(1))

        js = jax.jit(shard_map(
            step, mesh=mesh,
            in_specs=(P(), P(), P("hvd"), P("hvd")),
            out_specs=(P(), P()), check_vma=False))
        return lambda x, y: js(params, state, x, y)

    return build_step


def check_real_step(failures, report, jsonl):
    import jax
    import numpy as np
    import optax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops.autotune import OnlineTuner
    from horovod_tpu.utils import metrics

    hvd.shutdown()
    hvd.init()
    metrics.enable()
    metrics.step_stats.open_log(jsonl)
    mesh = hvd.mesh()
    knobs = hvd.core.state.global_state().knobs

    rng = np.random.RandomState(0)
    params = {"a": jnp.asarray(rng.randn(64, 64).astype(np.float32)),
              "b": jnp.asarray(rng.randn(64, 64).astype(np.float32))}
    sh = NamedSharding(mesh, P("hvd"))
    x = jax.device_put(rng.randn(32, 64).astype(np.float32), sh)
    y = jax.device_put(rng.randn(32, 64).astype(np.float32), sh)
    dopt = hvd.DistributedOptimizer(optax.sgd(0.01))
    state = dopt.init(params)

    from horovod_tpu.ops.fusion import model_fingerprint

    fingerprint = model_fingerprint(params)
    compile_log = []
    factory = _mlp_factory(mesh, params, state, dopt, compile_log)
    cache = os.path.join(tempfile.mkdtemp(prefix="hvd_at_"),
                         "cache.json")
    incumbent = knobs.fusion_threshold_bytes
    tuner = OnlineTuner(
        knobs, thresholds=[incumbent, 64 << 10],
        warmup=1, measure=4, cache_path=cache)
    config = tuner.tune(factory, x, y, fingerprint=fingerprint)

    # never-worse: the incumbent was seeded and timed; the pinned
    # winner's measured time cannot exceed it
    inc_rows = [r["step_s"] for r in tuner.trials
                if r.get("fusion_threshold_bytes") == incumbent
                and "step_s" in r
                and r.get("dimension") == "fusion_threshold_bytes"]
    win_rows = [r["step_s"] for r in tuner.trials
                if "step_s" in r
                and r.get("fusion_threshold_bytes")
                == config["fusion_threshold_bytes"]
                and r.get("dimension") == "fusion_threshold_bytes"]
    if not inc_rows or not win_rows:
        failures.append("sweep did not time the incumbent and winner")
    elif min(win_rows) > min(inc_rows):
        failures.append(
            f"never-worse violated: winner {min(win_rows):.6f}s > "
            f"incumbent {min(inc_rows):.6f}s")

    # cache-hit rerun: zero compiles
    rerun_log = []
    tuner2 = OnlineTuner(
        knobs, thresholds=[knobs.fusion_threshold_bytes, 64 << 10],
        warmup=1, measure=4, cache_path=cache)
    config2 = tuner2.tune(
        _mlp_factory(mesh, params, state, dopt, rerun_log),
        x, y, fingerprint=fingerprint)
    if tuner2.compiles != 0 or rerun_log:
        failures.append(
            f"cache-hit rerun compiled {tuner2.compiles} candidates")
    if config2 != config:
        failures.append(
            f"cache-hit rerun pinned {config2} != swept {config}")

    # pin-then-rebuild determinism (numerics dimensions are off): the
    # factory build under the pinned config must be bitwise equal to a
    # direct build from the pinned knobs
    saved = {k: getattr(knobs, k) for k in config}
    step_a = factory(dict(config))
    out_a = jax.device_get(step_a(x, y))
    for k, v in config.items():
        setattr(knobs, k, v)
    step_b = _mlp_factory(mesh, params, state, dopt, [])(dict(config))
    out_b = jax.device_get(step_b(x, y))
    for k, v in saved.items():
        setattr(knobs, k, v)
    from overlap_check import trees_bitwise_equal

    bitwise = trees_bitwise_equal(out_a, out_b)
    if not bitwise:
        failures.append(
            "pin-then-rebuild NOT bitwise: the factory build under the "
            "pinned config differs from the direct-knobs build")

    # decision trail: /metrics series + lint
    scrape = metrics.scrape()
    for series in ("hvd_autotune_trials_total", "hvd_autotune_best_step_s",
                   "hvd_autotune_dimension"):
        if series not in scrape:
            failures.append(f"{series} missing from /metrics")
    lint = metrics.lint_exposition(scrape)
    if lint:
        failures.append(f"/metrics does not lint: {lint[:3]}")

    metrics.step_stats.close_log()
    report["real_step"] = {
        "pinned": config,
        "incumbent_step_s": round(min(inc_rows), 6) if inc_rows else None,
        "winner_step_s": round(min(win_rows), 6) if win_rows else None,
        "sweep_compiles": len(compile_log),
        "rerun_compiles": len(rerun_log),
        "bitwise_pin_rebuild": bitwise,
        "trials": [
            {k: (v if not isinstance(v, float) else round(v, 6))
             for k, v in r.items()} for r in tuner.trials],
    }
    hvd.shutdown()


def check_jsonl_trail(failures, report, jsonl):
    """The StepStats JSONL carries autotune event lines and
    metrics_summary renders them (and still gates --check green)."""
    events = []
    try:
        with open(jsonl) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("event") == "autotune":
                    events.append(rec["autotune"])
    except OSError as e:
        failures.append(f"cannot read step JSONL: {e}")
        return
    kinds = {e.get("kind") for e in events}
    if "trial" not in kinds or "pin" not in kinds:
        failures.append(
            f"JSONL decision trail incomplete: kinds {sorted(kinds)}")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    summary = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts",
                                      "metrics_summary.py"), jsonl],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=60)
    if summary.returncode != 0:
        failures.append(
            f"metrics_summary failed on the sweep JSONL:\n"
            f"{summary.stdout}")
    elif "autotune sweep" not in summary.stdout:
        failures.append("metrics_summary did not render the sweep table")
    gate = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts",
                                      "metrics_summary.py"), jsonl,
         "--check"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=60)
    if gate.returncode != 0:
        failures.append(
            f"metrics_summary --check rejected the sweep JSONL:\n"
            f"{gate.stdout}")
    report["jsonl"] = {"autotune_events": len(events),
                       "kinds": sorted(k for k in kinds if k)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="gate mode: exit nonzero on any failure")
    ap.add_argument("--out", default="",
                    help="also write the sweep artifact here")
    args = ap.parse_args(argv)

    failures = []
    report = {"what": "closed-loop autotuner smoke gate",
              "time_unix": time.time()}
    check_world2_agreement(failures, report)
    jsonl = os.path.join(tempfile.mkdtemp(prefix="hvd_at_"),
                         "sweep.jsonl")
    if not failures:
        check_real_step(failures, report, jsonl)
        check_jsonl_trail(failures, report, jsonl)
    report["ok"] = not failures

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
    if failures:
        for fmsg in failures:
            print("autotune check FAILED:", fmsg)
        return 1
    print(json.dumps(report, indent=1, sort_keys=True))
    print("autotune check OK: world-2 agreement, never-worse pin, "
          "cache warm start (0 compiles), bitwise pin-then-rebuild, "
          "decision trail")
    return 0


if __name__ == "__main__":
    sys.exit(main())
