"""Fully-sharded parameters / ZeRO-3 (optim/fsdp.py, docs/fsdp.md).

Correctness bar: the prefetch-interleaved FSDP step is bitwise the
gathered (up-front) reference — params rows, optimizer state including
the int8 error-feedback residual, loss — and agrees with the
truly-unsharded staged ShardedOptimizer step to state/loss bitwise and
params within one rounding of the applied update — 2 relative ulps
with a 1e-7 cancellation floor (the shard-local apply's fma
contraction on the CPU barrier-expanding pipeline; see
fsdp.apply_shard_updates). Memory
bar: per-device resident parameter bytes == sharded size, bounded by
replicated/world + one bucket. Schedule bar: prefetched gathers are
pinned behind forward compute (producer-closure proof), the up-front
lowering's are not. scripts/fsdp_check.py gates the same properties
on every PR.
"""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

import horovod_tpu as hvd
from horovod_tpu.models import Transformer
from horovod_tpu.models.transformer import TransformerConfig, causal_lm_loss
from horovod_tpu.optim import fsdp as fsdp_mod

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

TINY = TransformerConfig(
    vocab_size=64, num_layers=2, num_heads=2, hidden_size=32,
    max_seq_len=16, dtype=jnp.float32,
)
_THRESH = 8 << 10


def _vehicle(hvd8):
    m = Transformer(TINY)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, TINY.vocab_size, (16, 16)),
        jnp.int32)
    params = m.init(jax.random.PRNGKey(0), toks[:2])["params"]
    layout = fsdp_mod.fsdp_layout(params, world=8,
                                  fusion_threshold_bytes=_THRESH)
    return m, toks, params, layout


def _stages_for(m):
    def stages(b):
        return hvd.overlap.transformer_lm_stages(
            m, b, lambda lg, _b=b: causal_lm_loss(lg, _b)[0])

    return stages


def _fsdp_step(m, layout, mode, compression=None, prefetch=None):
    opt = hvd.FullyShardedOptimizer(
        optax.adamw(1e-3), fusion_threshold_bytes=_THRESH,
        compression=compression)
    vag = fsdp_mod.fsdp_value_and_grad(
        _stages_for(m), opt, layout, mode=mode, prefetch=prefetch)

    def step(r, s, b):
        l, g = vag(r, b, opt_state=s)
        upd, s2 = opt.update(g, s, fsdp_mod.local_shards(r, layout))
        return (fsdp_mod.apply_shard_updates(r, upd, layout), s2,
                jax.lax.psum(l, "hvd").reshape(1))

    return opt, step


def _jit(step, layout, state_specs):
    return jax.jit(shard_map(
        step, mesh=hvd.mesh(),
        in_specs=(fsdp_mod.param_row_specs(layout), state_specs,
                  P("hvd")),
        out_specs=(fsdp_mod.param_row_specs(layout), state_specs, P()),
        check_vma=False))


def _bitwise(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)))


def test_layout_shard_unshard_roundtrip(hvd8):
    """The layout is the single authority: shard → unshard is bitwise
    identity, per-rank widths are ceil(len/world), and the abstract
    template reproduces every leaf's shape/dtype."""
    _, _, params, layout = _vehicle(hvd8)
    rows = fsdp_mod.shard_params(params, layout)
    assert len(rows) == len(layout.plans)
    for i, k in enumerate(layout.ks):
        r = rows[fsdp_mod.bucket_name(i)]
        assert r.shape == (8, k)
        assert 8 * k >= layout.lens[i]
    back = fsdp_mod.unshard_params(rows, layout)
    assert _bitwise(params, back)
    abs_p = fsdp_mod.abstract_params(layout)
    for a, b in zip(jax.tree_util.tree_leaves(abs_p),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert layout.shard_bytes * 8 >= layout.param_bytes
    assert layout.max_bucket_bytes <= layout.param_bytes


# three compiled steps; the run_all_checks `fsdp` gate asserts the
# same parity on every PR (tier-1 budget, PR-9 precedent) — tier-1
# keeps the routed train-step test below as its compiled coverage
@pytest.mark.slow
def test_prefetch_bitwise_vs_gathered_and_ulp_vs_replicated(hvd8):
    """The numerics contract (docs/fsdp.md): prefetch == up-front
    gathered reference BITWISE (params/state/loss), and vs the
    truly-unsharded staged ShardedOptimizer step the optimizer state
    and loss are bitwise with params within one ROUNDING of the
    applied update — 2 relative float32 ulps plus a 1e-7 absolute
    floor for p ≈ -u cancellation, where a one-rounding difference in
    u legitimately exceeds any ulp count of the tiny result
    (apply-site fma contraction on the CPU pipeline)."""
    m, toks, params, layout = _vehicle(hvd8)
    rows = fsdp_mod.shard_params(params, layout)

    outs = {}
    for mode in ("prefetch", "upfront"):
        opt, step = _fsdp_step(m, layout, mode)
        state = opt.init(params)
        js = _jit(step, layout, hvd.sharded_state_specs(state))
        outs[mode] = js(rows, state, toks)
    assert _bitwise(outs["prefetch"][0], outs["upfront"][0]), \
        "params rows diverged"
    assert _bitwise(outs["prefetch"][1], outs["upfront"][1]), \
        "optimizer state diverged"
    assert _bitwise(outs["prefetch"][2], outs["upfront"][2]), \
        "loss diverged"

    zopt = hvd.ShardedOptimizer(optax.adamw(1e-3),
                                fusion_threshold_bytes=_THRESH)
    zstate = zopt.init(params)
    zvag = hvd.overlap.staged_value_and_grad(_stages_for(m), opt=zopt,
                                             mode="stage")

    def zstep(p, s, b):
        l, g = zvag(p, b, opt_state=s)
        upd, s2 = zopt.update(g, s, p)
        return (optax.apply_updates(p, upd), s2,
                jax.lax.psum(l, "hvd").reshape(1))

    zspecs = hvd.sharded_state_specs(zstate)
    js_z = jax.jit(shard_map(
        zstep, mesh=hvd.mesh(), in_specs=(P(), zspecs, P("hvd")),
        out_specs=(P(), zspecs, P()), check_vma=False))
    out_z = js_z(params, zstate, toks)
    assert _bitwise(outs["prefetch"][1], out_z[1]), "state vs zero"
    assert _bitwise(outs["prefetch"][2], out_z[2]), "loss vs zero"
    gathered = fsdp_mod.unshard_params(outs["prefetch"][0], layout)

    def _assert_one_rounding(a, b):
        a, b = np.asarray(a), np.asarray(b)
        assert np.allclose(a, b, rtol=2.0 ** -22, atol=1e-7), \
            f"beyond one update rounding: max {np.abs(a - b).max()}"

    jax.tree_util.tree_map(_assert_one_rounding, gathered, out_z[0])


# int8's quantized collectives compile ~3x slower on the 1-core box;
# the run_all_checks `fsdp` gate also asserts this parity, so the
# pytest variant rides the slow tier (PR-9 precedent)
@pytest.mark.slow
def test_int8_error_feedback_parity_and_residual(hvd8):
    """The int8 wire runs WITH error feedback on the FSDP path — the
    rank-private residual rides the staged quantized reduce-scatters
    identically in both modes, and is nonzero after a step (the wire
    actually quantized something)."""
    m, toks, params, layout = _vehicle(hvd8)
    rows = fsdp_mod.shard_params(params, layout)
    outs = {}
    for mode in ("prefetch", "upfront"):
        opt, step = _fsdp_step(m, layout, mode,
                               compression=hvd.Compression.int8)
        state = opt.init(params)
        assert isinstance(state, fsdp_mod.FsdpEFState)
        js = _jit(step, layout, hvd.sharded_state_specs(state))
        outs[mode] = js(rows, state, toks)
    for i in range(3):
        assert _bitwise(outs["prefetch"][i], outs["upfront"][i]), i
    res = [np.asarray(r) for r in outs["prefetch"][1].residual]
    assert any(np.abs(r).sum() > 0 for r in res), \
        "error-feedback residual stayed zero"


# two lowers; the fsdp gate's --fsdp-ab preopt analysis asserts the
# same structure on every PR (tier-1 budget)
@pytest.mark.slow
def test_gather_pin_structure(hvd8):
    """The schedule property on the pre-optimization module: with
    prefetch the parameter all-gathers sit in forward compute's
    CONSUMER side (dots in their producer closure — no scheduler may
    hoist them to t=0); the up-front reference's gathers depend on
    nothing. The backward reduce-scatters keep the PR 9 pin in both."""
    sys.path.insert(0, str(_REPO_ROOT / "scripts"))
    from overlap_check import analyze_gather_preopt, analyze_preopt

    m, toks, params, layout = _vehicle(hvd8)
    rows = fsdp_mod.shard_params(params, layout)
    for mode, pinned in (("prefetch", True), ("upfront", False)):
        opt, step = _fsdp_step(m, layout, mode)
        state = opt.init(params)
        js = _jit(step, layout, hvd.sharded_state_specs(state))
        hlo = js.lower(rows, state, toks).compiler_ir(
            dialect="hlo").as_hlo_text()
        r = analyze_gather_preopt(hlo, min_elems=64)
        assert r["param_all_gathers"] >= 3, r
        if pinned:
            assert r["gathers_pinned_behind_compute"] > 0, r
            assert r["fwd_dots_pinned_before_last_gather"] > 0, r
        else:
            assert r["gathers_pinned_behind_compute"] == 0, r
        rb = analyze_preopt(hlo, min_elems=64)
        assert rb["gradient_all_reduces"] >= 3, rb
        if pinned:
            assert rb["dots_pinned_after_first_all_reduce"] > 0, rb


def test_measured_per_device_bytes_bounded(hvd8):
    """The HBM claim, measured: per-device resident parameter bytes of
    the placed row dict ≤ replicated/world + one bucket."""
    _, _, params, layout = _vehicle(hvd8)
    rows = fsdp_mod.shard_params(params, layout)
    sh = fsdp_mod.param_row_shardings(layout, hvd.mesh())
    placed = {k: jax.device_put(v, sh[k]) for k, v in rows.items()}
    dev0 = jax.devices()[0]
    per_dev = sum(
        s.data.size * s.data.dtype.itemsize
        for v in placed.values() for s in v.addressable_shards
        if s.device == dev0)
    assert per_dev == layout.shard_bytes
    assert per_dev <= layout.param_bytes / 8 + layout.max_bucket_bytes


def test_update_contract_errors(hvd8):
    """Misuse fails at the cause with a docs pointer, not deep in a
    trace (the zero.py error-discipline precedent)."""
    _, _, params, layout = _vehicle(hvd8)
    opt = hvd.FullyShardedOptimizer(optax.adamw(1e-3),
                                    fusion_threshold_bytes=_THRESH)
    state = opt.init(params)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    with pytest.raises(ValueError, match="staged gradient shards"):
        opt.update(grads, state, params)
    # a full (n, k) state leaf (forgotten sharded_state_specs) raises
    from horovod_tpu.ops.overlap import StagedShards

    shards = [jnp.zeros((k,), d)
              for k, d in zip(layout.ks, layout.dtypes)]
    with pytest.raises(ValueError, match="sharded_state_specs"):
        opt.update(StagedShards(shards), state, shards)
    with pytest.raises(ValueError, match="world size > 1"):
        fsdp_mod.fsdp_layout(params, world=1)
    with pytest.raises(ValueError, match="single-rank"):
        fsdp_mod.reshard_rows(
            fsdp_mod.shard_params(params, layout), layout, 1)
    with pytest.raises(ValueError, match="FullyShardedOptimizer"):
        fsdp_mod.fsdp_value_and_grad(
            lambda b: [], hvd.ShardedOptimizer(optax.sgd(0.1)), layout)


def test_reshard_rows_across_world_sizes(hvd8):
    """Elastic resize of the parameter rows: every true element
    survives the 8 → 4 → 8 move (the zero.reshard_state twin)."""
    _, _, params, layout = _vehicle(hvd8)
    rows = fsdp_mod.shard_params(params, layout)
    r4 = fsdp_mod.reshard_rows(rows, layout, 4)
    for i, L in enumerate(layout.lens):
        assert r4[fsdp_mod.bucket_name(i)].shape == (4, -(-L // 4))
    layout4 = layout._replace(
        world=4, ks=tuple(-(-L // 4) for L in layout.lens))
    back = fsdp_mod.unshard_params(r4, layout4)
    assert _bitwise(params, back)


def test_sharded_optimizer_params_sharded_entry(hvd8):
    """ShardedOptimizer(params_sharded=True) is the same optimizer as
    FullyShardedOptimizer (interchangeable entry points)."""
    opt = hvd.ShardedOptimizer(optax.adamw(1e-3), params_sharded=True)
    info = opt.update._hvd_overlap_info
    assert info["kind"] == "fsdp"


def test_make_lm_train_step_routes_fsdp_and_knob_gates(hvd8):
    """parallel/train.make_lm_train_step routes an fsdp>1 mesh with a
    FullyShardedOptimizer through the sharded step (init returns the
    row dict, one step trains and records the FSDP telemetry); the
    HOROVOD_FSDP=0 knob makes that configuration raise loudly; a
    non-FSDP optimizer is untouched by the knob."""
    import json as _json

    from horovod_tpu.core.state import global_state
    from horovod_tpu.parallel.mesh import make_mesh
    from horovod_tpu.parallel.train import make_lm_train_step
    from horovod_tpu.utils import metrics

    toks = jnp.asarray(
        np.random.RandomState(1).randint(0, TINY.vocab_size, (16, 16)),
        jnp.int32)
    mesh = make_mesh(dp=1, fsdp=8)
    opt = hvd.FullyShardedOptimizer(
        optax.adamw(1e-3), axis_name="fsdp",
        fusion_threshold_bytes=_THRESH)

    metrics.enable()
    try:
        init_fn, step_fn, _ = make_lm_train_step(TINY, opt, mesh)
        rows, state = init_fn(jax.random.PRNGKey(0), toks[:2])
        # init returns the SHARDED row dict, not a params pytree
        assert all(k.startswith("bucket_") for k in rows)
        r2, s2, loss = step_fn(rows, state, toks)
        assert np.isfinite(float(loss))
        snap = metrics.registry.snapshot()
        assert snap.get("hvd_hbm_param_bytes"), sorted(snap)
        assert snap.get("hvd_fsdp_gather_bytes_total"), sorted(snap)
        # regather is the default policy: the backward re-issue
        # telemetry must flow through the routed step too
        assert snap.get("hvd_fsdp_regather_bytes_total"), sorted(snap)
    finally:
        metrics.reset()

    knobs = global_state().knobs
    knobs.fsdp = False
    try:
        with pytest.raises(ValueError, match="HOROVOD_FSDP"):
            make_lm_train_step(TINY, opt, mesh)
    finally:
        knobs.fsdp = True
    # axis mismatch raises with the fix spelled out
    with pytest.raises(ValueError, match="axis_name"):
        make_lm_train_step(
            TINY,
            hvd.FullyShardedOptimizer(optax.adamw(1e-3),
                                      axis_name="dp"),
            mesh)
    # sequence parallelism is rejected loudly (no silent fallback)
    sp_mesh = make_mesh(dp=1, fsdp=4, sp=2)
    with pytest.raises(ValueError, match="sequence"):
        make_lm_train_step(
            TINY,
            hvd.FullyShardedOptimizer(optax.adamw(1e-3),
                                      axis_name="fsdp"),
            sp_mesh, sequence_parallel="ring")


def test_knobs_defaults_and_parser():
    from horovod_tpu.core.knobs import Knobs
    from horovod_tpu.runner.util.config_parser import ARG_TO_ENV

    k = Knobs()
    assert k.fsdp is True
    assert k.fsdp_prefetch == 1
    assert k.fsdp_regather is True
    assert k.fsdp_offload is False
    assert k.fsdp_offload_duty == 1.0
    assert ARG_TO_ENV["fsdp"] == "HOROVOD_FSDP"
    assert ARG_TO_ENV["fsdp_prefetch"] == "HOROVOD_FSDP_PREFETCH"
    assert ARG_TO_ENV["fsdp_regather"] == "HOROVOD_FSDP_REGATHER"
    assert ARG_TO_ENV["fsdp_offload"] == "HOROVOD_FSDP_OFFLOAD"
    assert ARG_TO_ENV["fsdp_offload_duty"] == "HOROVOD_FSDP_OFFLOAD_DUTY"
