"""ZeRO-1 sharded optimizer states (optim/zero.py).

The reference ships reducescatter/allgather as "ZeRO-style building
blocks" (SURVEY §2.5, reference operations.cc:1725,1532); this is the
optimizer built on them. Correctness bar: a ShardedOptimizer step is
numerically the allreduce step (reduce-scatter + all-gather of an
elementwise update == allreduce), with state memory 1/N per rank.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

import horovod_tpu as hvd


def _world():
    hvd.init()
    mesh = hvd.mesh()
    rng = np.random.RandomState(0)
    # deliberately NOT divisible by 8: exercises shard padding
    params = {
        "w": jnp.asarray(rng.randn(37, 11).astype(np.float32)),
        "b": jnp.asarray(rng.randn(11).astype(np.float32)),
        "s": jnp.asarray(rng.randn(3).astype(np.float32)),
    }
    x = rng.randn(8 * 8, 37).astype(np.float32)
    y = rng.randn(8 * 8, 11).astype(np.float32)
    sh = NamedSharding(mesh, P("hvd"))
    return mesh, params, jax.device_put(x, sh), jax.device_put(y, sh)


def _loss(p, x, y):
    return jnp.mean((x @ p["w"] + p["b"] + jnp.sum(p["s"]) - y) ** 2)


def _run_steps(mesh, opt, state_specs, params, x, y, steps=3):
    state = None

    def step(p, s, x, y):
        l, g = jax.value_and_grad(_loss)(p, x, y)
        upd, s = opt.update(g, s, p)
        return optax.apply_updates(p, upd), s, jax.lax.pmean(
            l, "hvd").reshape(1)

    state = opt.init(params)
    js = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), state_specs, P("hvd"), P("hvd")),
        out_specs=(P(), state_specs, P()), check_vma=False))
    p = params
    for _ in range(steps):
        p, state, l = js(p, state, x, y)
    return jax.device_get(p), float(l[0])


@pytest.mark.parametrize("make_opt", [
    lambda: optax.adam(0.05),
    lambda: optax.sgd(0.05, momentum=0.9),
], ids=["adam", "sgd_momentum"])
def test_sharded_matches_allreduce_training(make_opt):
    mesh, params, x, y = _world()
    zopt = hvd.ShardedOptimizer(make_opt())
    zstate = zopt.init(params)
    zspecs = hvd.sharded_state_specs(zstate)
    p_zero, l_zero = _run_steps(mesh, zopt, zspecs, params, x, y)

    dopt = hvd.DistributedOptimizer(make_opt())
    dspecs = P()
    p_ref, l_ref = _run_steps(mesh, dopt, dspecs, params, x, y)

    assert l_zero == pytest.approx(l_ref, rel=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                atol=1e-6),
        p_zero, p_ref)


def test_state_is_sharded_one_row_per_rank():
    _, params, _, _ = _world()
    opt = hvd.ShardedOptimizer(optax.adam(0.01))
    state = opt.init(params)
    n = hvd.size()
    size = sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
    # default threshold (128 MB) >> this model: one bucket, k=ceil(P/n)
    k = -(-size // n)
    big = [l for l in jax.tree_util.tree_leaves(state)
           if hasattr(l, "ndim") and l.ndim == 2]
    assert big, "expected (n, k) state leaves (adam m and v)"
    for l in big:
        assert l.shape == (n, k)
    specs = hvd.sharded_state_specs(state)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P))
    assert P("hvd") in spec_leaves  # m/v shard
    assert P() in spec_leaves      # adam count replicates


def test_sharded_multibucket_matches_allreduce_training():
    """A tiny fusion threshold forces several backward-ordered buckets
    (the overlap-chained reduce-scatter path); the math must still be
    exactly the allreduce step's."""
    mesh, params, x, y = _world()
    zopt = hvd.ShardedOptimizer(optax.adam(0.05),
                                fusion_threshold_bytes=256)
    zstate = zopt.init(params)
    # multiple buckets actually materialized
    assert sum(1 for l in jax.tree_util.tree_leaves(zstate)
               if hasattr(l, "ndim") and l.ndim == 2) > 2
    zspecs = hvd.sharded_state_specs(zstate)
    p_zero, l_zero = _run_steps(mesh, zopt, zspecs, params, x, y)

    dopt = hvd.DistributedOptimizer(optax.adam(0.05))
    p_ref, l_ref = _run_steps(mesh, dopt, P(), params, x, y)
    assert l_zero == pytest.approx(l_ref, rel=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                atol=1e-6),
        p_zero, p_ref)


def test_sharded_buckets_stay_separate_in_hlo():
    """The chained per-bucket reduce-scatters must survive as separate
    collectives in the lowered step (the overlap property: bucket j's
    scatter depends only on its own gradients + the chain edge) —
    mirror of test_overlap_schedule's level-1 assertion for the
    allreduce path."""
    mesh, params, x, y = _world()
    opt = hvd.ShardedOptimizer(optax.adam(0.05),
                               fusion_threshold_bytes=256)
    state = opt.init(params)
    specs = hvd.sharded_state_specs(state)

    def step(p, s, x, y):
        l, g = jax.value_and_grad(_loss)(p, x, y)
        upd, s = opt.update(g, s, p)
        return optax.apply_updates(p, upd), s, jax.lax.pmean(
            l, "hvd").reshape(1)

    js = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), specs, P("hvd"), P("hvd")),
        out_specs=(P(), specs, P()), check_vma=False))
    txt = js.lower(params, state, x, y).as_text()
    # this model buckets to [s+b], [w] at a 256-byte threshold (w is a
    # single leaf and cannot split): two scatters, one chain barrier
    n_rs = txt.count("reduce_scatter")
    assert n_rs >= 2, f"expected per-bucket reduce-scatters, got {n_rs}"
    assert "optimization_barrier" in txt


def test_single_rank_world_passthrough(monkeypatch):
    import horovod_tpu.ops.collectives as coll

    hvd.init()
    monkeypatch.setattr(coll, "_group_size", lambda ps, ax: 1)
    opt = hvd.ShardedOptimizer(optax.adam(0.01))
    params = {"w": jnp.ones((4,))}
    state = opt.init(params)
    # state matches the plain optimizer structure (no (n, k) reshaping)
    ref = optax.adam(0.01).init(params)
    assert jax.tree_util.tree_structure(state) == \
        jax.tree_util.tree_structure(ref)
    g = {"w": jnp.full((4,), 0.5)}
    upd, _ = opt.update(g, state, params)
    ref_upd, _ = optax.adam(0.01).update(g, ref, params)
    np.testing.assert_allclose(np.asarray(upd["w"]),
                               np.asarray(ref_upd["w"]), rtol=1e-6)


def test_forgotten_sharded_state_specs_raises_clearly():
    """Running inside shard_map WITHOUT sharded_state_specs hands every
    device the full (world, k) state; the failure must name the missing
    spec at the cause, not surface as a baffling broadcast/unflatten
    shape error later (ADVICE.md #4)."""
    mesh, params, x, y = _world()
    zopt = hvd.ShardedOptimizer(optax.adam(0.05))
    with pytest.raises(ValueError, match="sharded_state_specs"):
        # P() replicates the state instead of slicing rows per device
        _run_steps(mesh, zopt, P(), params, x, y, steps=1)


def test_update_outside_mesh_raises():
    _, params, _, _ = _world()
    opt = hvd.ShardedOptimizer(optax.adam(0.01))
    state = opt.init(params)
    g = jax.tree_util.tree_map(jnp.ones_like, params)
    with pytest.raises(RuntimeError, match="shard_map"):
        opt.update(g, state, params)


def test_reshard_state_across_world_sizes(monkeypatch):
    """Elastic resize: (n1, k1) state re-slices to (n2, k2) with
    k2 = ceil(size/n2) — the exact width update_fn recomputes from the
    grads — and every parameter's slot value survives the move."""
    import horovod_tpu.ops.collectives as coll
    from horovod_tpu.optim.zero import reshard_state

    hvd.init()
    rng = np.random.RandomState(1)
    params = {"w": jnp.asarray(rng.randn(13, 7).astype(np.float32)),
              "b": jnp.asarray(rng.randn(9).astype(np.float32))}
    size = 13 * 7 + 9  # 100, not divisible by either world size

    monkeypatch.setattr(coll, "_group_size", lambda ps, ax: 8)
    opt = hvd.ShardedOptimizer(optax.adam(0.01))
    s8 = opt.init(params)
    # default threshold: one bucket. Stamp recognizable values into it.
    flat_vals = jnp.arange(size, dtype=jnp.float32)
    k1 = -(-size // 8)
    mu = jnp.zeros((8 * k1,)).at[:size].set(flat_vals).reshape(8, k1)
    s8 = jax.tree_util.tree_map(
        lambda l: mu if (hasattr(l, "shape") and l.shape == (8, k1))
        else l, s8)

    s4 = reshard_state(s8, params, 8, 4)
    k2 = -(-size // 4)
    for l in jax.tree_util.tree_leaves(s4):
        if hasattr(l, "ndim") and l.ndim == 2:
            assert l.shape == (4, k2)
            np.testing.assert_array_equal(
                np.asarray(l).reshape(-1)[:size], np.asarray(flat_vals))
    # round trip back
    s8b = reshard_state(s4, params, 4, 8)
    for l in jax.tree_util.tree_leaves(s8b):
        if hasattr(l, "ndim") and l.ndim == 2:
            assert l.shape == (8, k1)
            np.testing.assert_array_equal(
                np.asarray(l).reshape(-1)[:size], np.asarray(flat_vals))

    with pytest.raises(ValueError, match="size-1"):
        reshard_state(s8, params, 8, 1)
    # wrong old_world must fail loudly, not pass the stale layout
    with pytest.raises(ValueError, match="no state leaf"):
        reshard_state(s8, params, 16, 4)
