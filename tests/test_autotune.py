"""SPMD-path and eager-path parameter tuners (ops/autotune.py).

Reference: /root/reference/horovod/common/parameter_manager.{cc,h} tunes
the hot path's knobs online. Our hot path is the compiled SPMD step, so
SPMDStepTuner recompiles per candidate via a user step-factory and pins
winners into the global knobs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

import horovod_tpu as hvd
from horovod_tpu.core.knobs import Knobs
from horovod_tpu.core.state import global_state
from horovod_tpu.ops.autotune import ParameterManager, SPMDStepTuner


def _mlp_world():
    hvd.init()
    mesh = hvd.mesh()
    rng = np.random.RandomState(0)
    params = {
        "a": jnp.asarray(rng.randn(64, 64).astype(np.float32)),
        "b": jnp.asarray(rng.randn(64, 64).astype(np.float32)),
        "c": jnp.zeros((64,), jnp.float32),
    }
    x = rng.randn(8 * 16, 64).astype(np.float32)
    y = rng.randn(8 * 16, 64).astype(np.float32)
    sh = NamedSharding(mesh, P("hvd"))
    return mesh, params, jax.device_put(x, sh), jax.device_put(y, sh)


def _make_factory(mesh, params, compile_log):
    """Step factory contract: knobs already hold the candidate overrides
    when this runs; (re)trace and return a runnable step."""
    dopt = hvd.DistributedOptimizer(optax.sgd(0.01))
    state = dopt.init(params)

    def build_step(overrides):
        compile_log.append(dict(overrides))

        def step(p, s, x, y):
            def loss_fn(p):
                h = jnp.tanh(x @ p["a"])
                return jnp.mean((h @ p["b"] + p["c"] - y) ** 2)

            l, g = jax.value_and_grad(loss_fn)(p)
            u, s2 = dopt.update(g, s, p)
            del s2  # fixed state: candidates must be numerically comparable
            return optax.apply_updates(p, u), jax.lax.pmean(l, "hvd").reshape(1)

        js = jax.jit(shard_map(
            step, mesh=mesh,
            in_specs=(P(), P(), P("hvd"), P("hvd")),
            out_specs=(P(), P()), check_vma=False))
        return lambda p, x, y: js(p, state, x, y)

    return build_step


def test_spmd_tuner_pins_winner_and_logs(tmp_path):
    mesh, params, x, y = _mlp_world()
    knobs = global_state().knobs
    before_thresh = knobs.fusion_threshold_bytes
    before_ordered = knobs.ordered_buckets
    compiles = []
    log = tmp_path / "autotune.csv"
    tuner = SPMDStepTuner(
        thresholds=[1 << 20, 128 << 20],
        warmup=1, measure=2, log_path=str(log),
    )
    best = tuner.tune(_make_factory(mesh, params, compiles), params, x, y)

    # coordinate descent: 2 thresholds + 1 ordered flip = 3 compiles,
    # not the 2x2 product
    assert len(compiles) == 3
    assert best["fusion_threshold_bytes"] in (1 << 20, 128 << 20)
    # winners pinned into the live knobs
    assert knobs.fusion_threshold_bytes == best["fusion_threshold_bytes"]
    assert knobs.ordered_buckets == best["ordered_buckets"]
    # every trial recorded with its timing
    assert len(tuner.trials) == 3
    assert all(t["step_s"] > 0 for t in tuner.trials)
    text = log.read_text()
    assert "fusion_threshold_bytes" in text and "# pinned" in text
    # the factory saw each candidate's overrides in the knobs at build time
    assert compiles[0]["fusion_threshold_bytes"] == 1 << 20
    knobs.fusion_threshold_bytes = before_thresh
    knobs.ordered_buckets = before_ordered


def test_spmd_tuner_candidates_numerically_equivalent():
    """Bucket size / ordering must not change the math — every candidate
    step applies the identical update."""
    mesh, params, x, y = _mlp_world()
    outs = []
    compiles = []
    factory = _make_factory(mesh, params, compiles)

    class Capture(SPMDStepTuner):
        def _time_candidate(self, build_step, args, overrides):
            dt = super()._time_candidate(build_step, args, overrides)
            saved = self._apply(overrides)
            try:
                p2, loss = build_step(dict(overrides))(*args)
            finally:
                self._apply(saved)
            outs.append((jax.device_get(p2), float(loss[0])))
            return dt

    tuner = Capture(thresholds=[1 << 20, 256 << 20], warmup=0, measure=1)
    tuner.tune(factory, params, x, y)
    ref_p, ref_l = outs[0]
    for p2, l2 in outs[1:]:
        assert l2 == pytest.approx(ref_l, rel=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
            ref_p, p2)


def test_spmd_tuner_restores_knobs_between_candidates():
    knobs = Knobs()
    knobs.fusion_threshold_bytes = 7 << 20
    seen = []

    tuner = SPMDStepTuner(knobs=knobs, thresholds=[1 << 20, 2 << 20],
                          warmup=0, measure=1, tune_ordered=False)

    def factory(overrides):
        seen.append(knobs.fusion_threshold_bytes)
        return lambda: jnp.zeros(())

    best = tuner.tune(factory)
    # the incumbent 7 MB is seeded into the sweep (tuning can never pin
    # something slower than the user's setting), then each trial's knob
    # held that candidate's value
    assert seen == [7 << 20, 1 << 20, 2 << 20]
    # after tune() only the winner persists
    assert knobs.fusion_threshold_bytes == best["fusion_threshold_bytes"]


def test_spmd_tuner_hierarchical_dimension():
    knobs = Knobs()
    calls = []

    def factory(overrides):
        calls.append(dict(overrides))
        return lambda: jnp.zeros(())

    tuner = SPMDStepTuner(knobs=knobs, thresholds=[knobs.fusion_threshold_bytes,
                                                   1 << 20],
                          warmup=0, measure=1, tune_ordered=False,
                          tune_hierarchical=True, hier_blocks=[2, 4])
    tuner.tune(factory)
    # 2 thresholds + 2 hierarchical blocks
    assert len(calls) == 4
    assert calls[2]["hierarchical_allreduce"] is True
    assert calls[2]["hierarchical_local_size"] == 2
    assert calls[3]["hierarchical_local_size"] == 4
    # factory saw the knob values live
    assert knobs.hierarchical_allreduce in (True, False)


def test_spmd_tuner_wire_dimension():
    """The wire-dtype dimension times each HOROVOD_COMPRESSION candidate
    through the factory (knobs.compression carries the candidate at
    trace time) and pins a winner from the candidate set."""
    knobs = Knobs()
    calls = []

    def factory(overrides):
        calls.append(dict(overrides))
        return lambda: jnp.zeros(())

    tuner = SPMDStepTuner(
        knobs=knobs, thresholds=[knobs.fusion_threshold_bytes],
        warmup=0, measure=1, tune_ordered=False,
        tune_wire=True, wire_candidates=["none", "bf16", "int8"])
    winners = tuner.tune(factory)
    # 1 threshold + 2 non-incumbent wire candidates ("none" is the
    # incumbent and is already timed by the threshold dim)
    assert len(calls) == 3
    assert calls[1]["compression"] == "bf16"
    assert calls[2]["compression"] == "int8"
    assert winners["compression"] in ("none", "bf16", "int8")
    assert knobs.compression == winners["compression"]  # pinned


def test_parameter_manager_pins_best_threshold(tmp_path):
    knobs = Knobs()
    knobs.autotune = True
    knobs.autotune_warmup_samples = 0
    knobs.autotune_steps_per_sample = 1
    knobs.autotune_log = str(tmp_path / "pm.csv")
    pm = ParameterManager(knobs)
    # walk every candidate; constant byte volume means earlier (smaller
    # elapsed per sample is noise) — just assert it pins and logs. Each
    # candidate switch inserts one skipped (recompile/warmup) window
    # before its scored window, so the walk takes ~2 windows per
    # remaining candidate.
    n_candidates = 9
    for _ in range(2 * n_candidates + 2):
        pm.record_bytes(1 << 20)
        pm.tick()
    assert pm._pinned
    assert pm.fusion_threshold_bytes() in [
        1 << 20, 2 << 20, 4 << 20, 8 << 20, 16 << 20,
        32 << 20, 64 << 20, 128 << 20, 256 << 20]
    assert "# pinned" in (tmp_path / "pm.csv").read_text()


def test_parameter_manager_drops_first_post_switch_window(tmp_path):
    """The first sample window after a threshold switch carries the
    candidate's recompile/warmup wall time; scoring it would bias the
    bytes/sec comparison against every later candidate. The window must
    be dropped: its bytes never appear in any logged score."""
    knobs = Knobs()
    knobs.autotune = True
    knobs.autotune_warmup_samples = 0
    knobs.autotune_steps_per_sample = 1
    knobs.autotune_log = str(tmp_path / "pm.csv")
    pm = ParameterManager(knobs)

    # window 1: scored at the initial candidate (no switch yet)
    first = pm.fusion_threshold_bytes()
    pm.record_bytes(100)
    pm.tick()
    assert pm._log_rows == [(first, pm._log_rows[0][1])]
    switched = pm.fusion_threshold_bytes()
    assert switched != first
    assert pm._skip_window

    # window 2: the POISONED one — huge byte count that would dominate
    # any score; it must vanish, not be credited to the new candidate
    pm.record_bytes(10**12)
    pm.tick()
    assert len(pm._log_rows) == 1  # nothing scored
    assert pm._bytes_in_sample == 0  # accumulators reset
    assert not pm._skip_window

    # window 3: scored normally for the new candidate
    pm.record_bytes(200)
    pm.tick()
    assert len(pm._log_rows) == 2
    assert pm._log_rows[1][0] == switched
    assert pm._best[1] in (first, switched)


# ---------------------------------------------------------------------------
# Closed-loop OnlineTuner (ops/autotune.py, docs/autotune.md)
# ---------------------------------------------------------------------------

import dataclasses
import json
import queue
import threading
import time

from horovod_tpu.ops.autotune import (KNOB_SCHEMA_VERSION, TUNABLE_KNOBS,
                                      OnlineTuner, TuneCache, cache_key,
                                      warm_start)
from horovod_tpu.ops.fusion import model_fingerprint
from horovod_tpu.utils import metrics as metrics_mod


def test_spmd_tuner_survives_failing_candidate():
    """A candidate that fails to compile (OOM / compile error on an
    aggressive threshold) must be recorded as an error trial, restore
    the saved knobs, and let the dimension continue — not abort the
    sweep mid-dimension (which would desync the agreement protocol:
    other ranks keep walking toward the broadcast)."""
    knobs = Knobs()
    agreements = []

    def agree(best, best_t):
        agreements.append(dict(best))
        return best, best_t

    def factory(overrides):
        if overrides["fusion_threshold_bytes"] == 2 << 20:
            raise MemoryError("candidate OOM")
        return lambda: jnp.zeros(())

    tuner = SPMDStepTuner(
        knobs=knobs,
        thresholds=[knobs.fusion_threshold_bytes, 2 << 20, 1 << 20],
        warmup=0, measure=1, tune_ordered=True, agree_fn=agree)
    best = tuner.tune(factory)

    # the failing candidate was logged, not raised
    errs = [r for r in tuner.trials if "error" in r]
    assert len(errs) == 1
    assert errs[0]["fusion_threshold_bytes"] == 2 << 20
    assert "MemoryError" in errs[0]["error"]
    # the sweep continued: the candidate after the failure was timed
    assert any(r.get("fusion_threshold_bytes") == 1 << 20
               and "step_s" in r for r in tuner.trials)
    # the failed candidate can never win, and knobs hold the winner
    assert best["fusion_threshold_bytes"] != 2 << 20
    assert knobs.fusion_threshold_bytes == best["fusion_threshold_bytes"]
    # every dimension still reached its agreement point
    assert len(agreements) == 2  # thresholds + ordered flip


@pytest.mark.parametrize("tuner_cls", [SPMDStepTuner, OnlineTuner])
def test_agreed_step_time_is_the_next_dimensions_baseline(tuner_cls,
                                                          tmp_path):
    """What agreement returns replaces BOTH the winners and their time
    (ADVICE.md round 5: `agree` once shipped `best` without `best_t`):
    a rank whose root reports a faster baseline must hold the next
    dimension's candidates to that baseline, not to a time of its own,
    and log it with the pinned winners."""
    knobs = Knobs()
    incumbent = knobs.ordered_buckets
    log = tmp_path / "tune.csv"

    def root_was_faster(best, best_t):
        return best, 1e-9  # no local candidate can beat this

    tuner = tuner_cls(
        knobs, thresholds=[knobs.fusion_threshold_bytes], warmup=0,
        measure=1, tune_ordered=True, agree_fn=root_was_faster,
        log_path=str(log),
        **({"tune_overlap": False} if tuner_cls is OnlineTuner else {}))
    best = tuner.tune(lambda overrides: lambda: jnp.zeros(()))
    assert best["ordered_buckets"] == incumbent
    assert "step_s=0.000000" in log.read_text().splitlines()[-1]


def test_spmd_tuner_all_failing_dimension_pins_incumbent():
    knobs = Knobs()
    incumbent = knobs.fusion_threshold_bytes

    def factory(overrides):
        raise RuntimeError("nothing compiles today")

    tuner = SPMDStepTuner(knobs=knobs,
                          thresholds=[incumbent, 1 << 20],
                          warmup=0, measure=1, tune_ordered=False)
    best = tuner.tune(factory)
    assert best["fusion_threshold_bytes"] == incumbent
    assert knobs.fusion_threshold_bytes == incumbent


# per-candidate sleeps, INVERTED between ranks: local argmins disagree,
# so only the rank-0-wins agreement can make the pins identical
_SKEW = {
    0: {128 << 20: 0.004, 1 << 20: 0.0005},
    1: {128 << 20: 0.0005, 1 << 20: 0.004},
}


def _skewed_rank(rank, q01, results, cache_path):
    knobs = Knobs()
    compile_log = []

    def agree(best, best_t):
        if rank == 0:
            q01.put((best, best_t))
            return best, best_t
        return q01.get(timeout=30)

    def factory(overrides):
        compile_log.append(dict(overrides))
        delay = _SKEW[rank][knobs.fusion_threshold_bytes]

        def step():
            time.sleep(delay)
            return jnp.zeros(())

        return step

    tuner = OnlineTuner(
        knobs, thresholds=[knobs.fusion_threshold_bytes, 1 << 20],
        warmup=0, measure=2, tune_overlap=False,
        cache_path=cache_path, fingerprint="w2test", agree_fn=agree)
    config = tuner.tune(factory)
    local = {r["fusion_threshold_bytes"]: r["step_s"]
             for r in tuner.trials
             if r.get("dimension") == "fusion_threshold_bytes"}
    results[rank] = {
        "config": config,
        "compiles": compile_log,
        "local_argmin": min(local, key=local.get),
        "knob": knobs.fusion_threshold_bytes,
    }


def test_world2_agreement_pins_identical_winners(tmp_path):
    """World-2 loopback with deliberately skewed per-rank candidate
    timings: both ranks must pin IDENTICAL winners (rank 0's), and the
    compile-override sequences must match exactly after every
    agreement point — the invariant that no rank ever compiles a
    rank-mismatched collective structure."""
    q01, results = queue.Queue(), {}
    threads = [
        threading.Thread(target=_skewed_rank,
                         args=(r, q01, results,
                               str(tmp_path / f"cache{r}.json")))
        for r in (0, 1)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert set(results) == {0, 1}
    r0, r1 = results[0], results[1]
    # the skew bit: each rank's own clock preferred a different winner
    assert r0["local_argmin"] == 1 << 20
    assert r1["local_argmin"] == 128 << 20
    # ... yet both pinned rank 0's (the coordinator's) pick
    assert r0["config"] == r1["config"]
    assert r0["config"]["fusion_threshold_bytes"] == 1 << 20
    assert r0["knob"] == r1["knob"] == 1 << 20
    # identical candidate sequences => identical compiled structures
    assert r0["compiles"] == r1["compiles"]


def test_online_tuner_cache_warm_start_zero_compiles(tmp_path):
    cache = str(tmp_path / "cache.json")
    knobs = Knobs()

    def factory(overrides):
        return lambda: jnp.zeros(())

    t1 = OnlineTuner(knobs, thresholds=[knobs.fusion_threshold_bytes,
                                        1 << 20],
                     warmup=0, measure=1, cache_path=cache,
                     fingerprint="fp-a")
    cfg = t1.tune(factory)
    assert t1.pin_source == "sweep" and t1.compiles > 0

    knobs2 = Knobs()

    def must_not_build(overrides):
        raise AssertionError("warm start must not compile")

    t2 = OnlineTuner(knobs2, thresholds=[knobs2.fusion_threshold_bytes,
                                         1 << 20],
                     warmup=0, measure=1, cache_path=cache,
                     fingerprint="fp-a")
    cfg2 = t2.tune(must_not_build)
    assert t2.compiles == 0 and t2.pin_source == "cache"
    assert cfg2 == cfg
    assert knobs2.fusion_threshold_bytes == cfg["fusion_threshold_bytes"]

    # fingerprint mismatch = different model => full re-tune
    knobs3 = Knobs()
    calls = []

    def factory3(overrides):
        calls.append(dict(overrides))
        return lambda: jnp.zeros(())

    t3 = OnlineTuner(knobs3, thresholds=[knobs3.fusion_threshold_bytes,
                                         1 << 20],
                     warmup=0, measure=1, cache_path=cache,
                     fingerprint="fp-OTHER")
    t3.tune(factory3)
    assert t3.pin_source == "sweep" and calls


@pytest.mark.parametrize("name", TUNABLE_KNOBS)
def test_tunable_knob_is_a_knobs_field(name):
    """What the tuner may pin (and the cache's staleness check admits)
    is a field of Knobs: a knob removed from one and not the other
    fails here by name."""
    assert name in {f.name for f in dataclasses.fields(Knobs)}


@pytest.mark.parametrize("stale", [
    {"config": {"fusion_threshold_bytes": 1 << 20},
     "schema": KNOB_SCHEMA_VERSION + 1},
    # what a cache written before PR 29 holds: schema 2, pinning the
    # knob of the removed --fused-collectives (spelled from the flag, so
    # that a grep of the tree for the knob's name finds nothing)
    {"config": {"fusion_threshold_bytes": 1 << 20,
                "fused-collectives".replace("-", "_"): True},
     "schema": 2},
], ids=["newer-schema", "schema-2-pins-removed-knob"])
def test_online_tuner_stale_schema_retunes_loudly(tmp_path, stale):
    """A cache entry from another knob-schema generation must re-tune
    (never silently reuse) and say so."""
    cache = str(tmp_path / "cache.json")
    knobs = Knobs()
    key = cache_key("fp-a")
    TuneCache(cache).store(key, dict(stale, time_unix=1.0))
    calls = []

    def factory(overrides):
        calls.append(dict(overrides))
        return lambda: jnp.zeros(())

    t = OnlineTuner(knobs, thresholds=[knobs.fusion_threshold_bytes],
                    warmup=0, measure=1, tune_ordered=False,
                    tune_overlap=False, cache_path=cache,
                    fingerprint="fp-a")
    t.tune(factory)
    assert t.pin_source == "sweep" and calls  # re-tuned
    # ... and the rewritten entry is consumable again
    entry = TuneCache(cache).lookup(key)
    assert entry is not None
    assert entry["schema"] == KNOB_SCHEMA_VERSION
    assert set(entry["config"]) <= set(TUNABLE_KNOBS)
    assert knobs.fusion_threshold_bytes != 1 << 20  # never applied


def test_online_tuner_optin_dimensions_walk():
    """fsdp prefetch / wire dtype / block / fast-path warmup candidates
    only enter the sweep when their dimension is enabled — and the
    quantization-block dimension only when the wire pinned a
    block-quantized compressor (a dead knob must not burn compiles or
    let noise pin an arbitrary block)."""
    knobs = Knobs()
    calls = []
    # a clock the step itself advances: with measure=1 a 2 ms sleep on
    # the real clock was outweighed by six test workers' scheduling
    # noise, and the ranking must not depend on the machine's load
    now = [0.0]

    def int8_wins(overrides):
        calls.append(dict(overrides))
        slow = 0.020 if knobs.compression != "int8" else 0.001

        def step():
            now[0] += slow
            return jnp.zeros(())

        return step

    t = OnlineTuner(
        knobs, thresholds=[knobs.fusion_threshold_bytes],
        warmup=0, measure=1, tune_ordered=False, tune_overlap=False,
        tune_fsdp_prefetch=True, prefetch_depths=[0, 1, 2],
        tune_wire=True, wire_candidates=["none", "int8"],
        block_candidates=[128, 256], warmup_k_candidates=[3, 8],
        clock=lambda: now[0])
    cfg = t.tune(int8_wins)
    dims = {r.get("dimension") for r in t.trials}
    assert "fsdp_prefetch" in dims
    assert "compression" in dims
    assert cfg["compression"] == "int8"
    assert "compression_block" in dims  # live knob under int8
    assert "eager_fast_path_warmup" in dims
    # incumbents excluded from their own dimension's candidate list
    assert sum(1 for r in t.trials
               if r.get("dimension") == "fsdp_prefetch") == 2
    for k in ("fsdp_prefetch", "compression", "compression_block",
              "eager_fast_path_warmup"):
        assert k in cfg
        assert getattr(knobs, k) == cfg[k]

    # wire pinned "none" => the block dimension is skipped entirely
    knobs2 = Knobs()

    def none_wins(overrides):
        slow = 0.020 if knobs2.compression == "int8" else 0.001

        def step():
            now[0] += slow
            return jnp.zeros(())

        return step

    t2 = OnlineTuner(
        knobs2, thresholds=[knobs2.fusion_threshold_bytes],
        warmup=0, measure=1, tune_ordered=False, tune_overlap=False,
        tune_wire=True, wire_candidates=["none", "int8"],
        block_candidates=[128, 256], warmup_k_candidates=[3, 8],
        clock=lambda: now[0])
    cfg2 = t2.tune(none_wins)
    assert cfg2["compression"] == "none"
    dims2 = {r.get("dimension") for r in t2.trials}
    assert "compression_block" not in dims2
    assert knobs2.compression_block == Knobs().compression_block


def test_online_tuner_decision_trail(tmp_path):
    """Every trial and pin lands in the registry and as autotune event
    lines in the StepStats JSONL."""
    jsonl = tmp_path / "steps.jsonl"
    metrics_mod.reset()
    metrics_mod.enable()
    metrics_mod.step_stats.open_log(str(jsonl))
    try:
        knobs = Knobs()

        def factory(overrides):
            return lambda: jnp.zeros(())

        t = OnlineTuner(knobs,
                        thresholds=[knobs.fusion_threshold_bytes,
                                    1 << 20],
                        warmup=0, measure=2)
        t.tune(factory)
        snap = metrics_mod.registry.snapshot()
        trials = snap.get("hvd_autotune_trials_total", {})
        assert sum(trials.values()) == len(t.trials)
        assert "hvd_autotune_best_step_s" in snap
        dim = snap.get("hvd_autotune_dimension", {})
        assert dim.get("fusion_threshold_bytes") == float(
            knobs.fusion_threshold_bytes)
        scrape = metrics_mod.scrape()
        assert not metrics_mod.lint_exposition(scrape)
        metrics_mod.step_stats.close_log()
        events = [json.loads(line)["autotune"]
                  for line in jsonl.read_text().splitlines()
                  if json.loads(line).get("event") == "autotune"]
        kinds = {e["kind"] for e in events}
        assert "trial" in kinds and "pin" in kinds
        finals = [e for e in events if e.get("dimension") == "final"]
        assert finals and finals[-1]["config"] == t.pinned
    finally:
        metrics_mod.reset()


def test_model_fingerprint_identity():
    a = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((7,), jnp.int32)}
    b = {"w": jnp.ones((4, 4)), "b": jnp.ones((7,), jnp.int32)}
    assert model_fingerprint(a) == model_fingerprint(b)  # value-free
    # shape-inferred trees fingerprint identically to concrete ones
    abstract = jax.eval_shape(lambda: a)
    assert model_fingerprint(abstract) == model_fingerprint(a)
    c = {"w": jnp.zeros((4, 5)), "b": jnp.zeros((7,), jnp.int32)}
    d = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((7,), jnp.float32)}
    e = {"w2": jnp.zeros((4, 4)), "b": jnp.zeros((7,), jnp.int32)}
    fps = {model_fingerprint(t) for t in (a, c, d, e)}
    assert len(fps) == 4  # shape, dtype and path all distinguish


def test_warm_start_numerics_opt_in(tmp_path):
    """Cached numerics-changing winners (wire dtype/block, fast-path
    warmup) transfer only under the explicit opt-in."""
    cache = str(tmp_path / "cache.json")
    tree = {"w": jnp.zeros((8, 8))}
    fp = model_fingerprint(tree)
    TuneCache(cache).store(cache_key(fp), {
        "config": {"fusion_threshold_bytes": 1 << 20,
                   "compression": "int8", "compression_block": 128,
                   "eager_fast_path_warmup": 8},
        "schema": KNOB_SCHEMA_VERSION, "step_s": 0.001,
        "time_unix": 1.0})

    knobs = Knobs()
    cfg = warm_start(tree, knobs, cache_path=cache)
    assert cfg == {"fusion_threshold_bytes": 1 << 20}
    assert knobs.compression == "none"  # untouched

    knobs2 = Knobs()
    cfg2 = warm_start(tree, knobs2, cache_path=cache,
                      allow_numerics=True)
    assert cfg2["compression"] == "int8"
    assert knobs2.compression == "int8"
    assert knobs2.compression_block == 128
    assert knobs2.eager_fast_path_warmup == 8


def test_tune_lm_train_step_pins_and_warm_starts(hvd8, tmp_path):
    """parallel/train.tune_lm_train_step rebuilds the REAL train step
    per candidate (the overlap-schedule dimension recompiles through
    make_lm_train_step) and a second run warm-starts from the cache
    with zero tuning compiles."""
    import optax

    from horovod_tpu.models.transformer import TransformerConfig
    from horovod_tpu.parallel.train import tune_lm_train_step

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            hidden_size=32, max_seq_len=16,
                            dtype=jnp.float32)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (16, 16)), jnp.int32)
    cache = str(tmp_path / "cache.json")
    mesh = hvd8.mesh()

    t1 = OnlineTuner(thresholds=[8 << 10], warmup=0, measure=2,
                     tune_ordered=False, tune_overlap=True,
                     overlap_modes=["off", "stage"], cache_path=cache)
    init_fn, step_fn, _, pinned = tune_lm_train_step(
        cfg, lambda: hvd8.DistributedOptimizer(optax.sgd(0.1)), mesh,
        jax.random.PRNGKey(0), toks, tuner=t1)
    assert t1.pin_source == "sweep"
    assert not [r for r in t1.trials if "error" in r]
    assert pinned["overlap_schedule"] in ("off", "stage")
    params, state = init_fn(jax.random.PRNGKey(0), toks)
    _, _, loss = step_fn(params, state, toks)
    assert np.isfinite(float(loss))

    t2 = OnlineTuner(thresholds=[8 << 10], warmup=0, measure=2,
                     tune_ordered=False, tune_overlap=True,
                     overlap_modes=["off", "stage"], cache_path=cache)
    _, _, _, pinned2 = tune_lm_train_step(
        cfg, lambda: hvd8.DistributedOptimizer(optax.sgd(0.1)), mesh,
        jax.random.PRNGKey(0), toks, tuner=t2)
    assert t2.compiles == 0 and t2.pin_source == "cache"
    assert pinned2 == {k: pinned[k] for k in pinned2}


def test_all_failing_sweep_emits_parseable_jsonl(tmp_path):
    """An all-candidates-failed sweep must not leak Infinity into the
    JSONL event lines (json.dumps would emit a bare non-RFC token)."""
    jsonl = tmp_path / "steps.jsonl"
    metrics_mod.reset()
    metrics_mod.enable()
    metrics_mod.step_stats.open_log(str(jsonl))
    try:
        knobs = Knobs()

        def factory(overrides):
            raise RuntimeError("nothing compiles")

        t = OnlineTuner(knobs,
                        thresholds=[knobs.fusion_threshold_bytes,
                                    1 << 20],
                        warmup=0, measure=1)
        cfg = t.tune(factory)
        assert cfg["fusion_threshold_bytes"] == \
            Knobs().fusion_threshold_bytes  # incumbent kept
        metrics_mod.step_stats.close_log()

        def no_constants(name):
            raise AssertionError(f"non-RFC JSON token {name} in JSONL")

        pins = []
        for line in jsonl.read_text().splitlines():
            rec = json.loads(line, parse_constant=no_constants)
            if rec.get("event") == "autotune" and \
                    rec["autotune"]["kind"] in ("pin", "reject"):
                pins.append(rec["autotune"])
        assert pins and all(p["step_s"] is None for p in pins)
    finally:
        metrics_mod.reset()
