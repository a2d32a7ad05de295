"""DistributedOptimizer / gradient reduction tests.

Reference analog: the optimizer/grad-correctness parts of
test/parallel/test_torch.py (gradient averaging matches manual math,
backward_passes_per_step) and test_tensorflow.py DistributedGradientTape.
"""

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.optim.compression import Compression


def make_step(opt, mesh, params):
    """SPMD training step: per-device batch, distributed update."""

    def loss_fn(p, x, y):
        pred = x @ p["w"] + p["b"]
        return jnp.mean((pred - y) ** 2)

    def step(p, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        updates, opt_state = opt.update(grads, opt_state, p)
        p = optax.apply_updates(p, updates)
        return p, opt_state, hvd.allreduce(loss, op=hvd.Average)

    return jax.jit(
        shard_map(
            step,
            mesh=mesh,
            in_specs=(P(), P(), P("hvd"), P("hvd")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )


def _data(seed=0, n=64, d=4):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d).astype(np.float32)
    w_true = rng.randn(d, 1).astype(np.float32)
    y = x @ w_true + 0.01 * rng.randn(n, 1).astype(np.float32)
    return x, y


def test_distributed_sgd_matches_full_batch(hvd8):
    """Distributed data-parallel SGD step == single-process full-batch step:
    the fundamental DP equivalence the reference's DistributedOptimizer
    guarantees (torch/optimizer.py:36)."""
    x, y = _data()
    params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros((1,))}

    base = optax.sgd(0.1)
    dist = hvd.DistributedOptimizer(optax.sgd(0.1))

    # distributed: batch split over 8 devices
    step = make_step(dist, hvd.mesh(), params)
    opt_state = dist.init(params)
    p1, _, loss1 = step(params, opt_state, jnp.asarray(x), jnp.asarray(y))

    # single-process full batch
    def loss_fn(p):
        pred = jnp.asarray(x) @ p["w"] + p["b"]
        return jnp.mean((pred - jnp.asarray(y)) ** 2)

    g = jax.grad(loss_fn)(params)
    upd, _ = base.update(g, base.init(params), params)
    p2 = optax.apply_updates(params, upd)

    np.testing.assert_allclose(
        np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(p1["b"]), np.asarray(p2["b"]), rtol=1e-5, atol=1e-6
    )


def test_distributed_optimizer_converges(hvd8):
    x, y = _data()
    params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros((1,))}
    opt = hvd.DistributedOptimizer(optax.adam(0.05))
    step = make_step(opt, hvd.mesh(), params)
    opt_state = opt.init(params)
    losses = []
    for _ in range(60):
        params, opt_state, loss = step(
            params, opt_state, jnp.asarray(x), jnp.asarray(y)
        )
        losses.append(float(loss))
    assert losses[-1] < 0.05 * losses[0]


def test_compression_bf16(hvd8):
    x, y = _data()
    params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros((1,))}
    opt = hvd.DistributedOptimizer(
        optax.sgd(0.1), compression=Compression.bf16
    )
    step = make_step(opt, hvd.mesh(), params)
    opt_state = opt.init(params)
    p1, _, _ = step(params, opt_state, jnp.asarray(x), jnp.asarray(y))
    # grads survive the bf16 wire within bf16 tolerance
    assert np.all(np.isfinite(np.asarray(p1["w"])))
    assert np.abs(np.asarray(p1["w"])).sum() > 0


def test_gradient_predivide_factor(hvd8):
    x, y = _data()
    params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros((1,))}
    ref = hvd.DistributedOptimizer(optax.sgd(0.1))
    pre = hvd.DistributedOptimizer(
        optax.sgd(0.1), gradient_predivide_factor=4.0
    )
    s1 = make_step(ref, hvd.mesh(), params)
    s2 = make_step(pre, hvd.mesh(), params)
    p1, _, _ = s1(params, ref.init(params), jnp.asarray(x), jnp.asarray(y))
    p2, _, _ = s2(params, pre.init(params), jnp.asarray(x), jnp.asarray(y))
    np.testing.assert_allclose(
        np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-4
    )


def test_backward_passes_per_step(hvd8):
    """k accumulation steps then one applied update — after k steps the
    result equals one step on the k-step mean gradient
    (torch/optimizer.py backward_passes_per_step)."""
    x, y = _data()
    params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros((1,))}
    k = 2
    opt = hvd.DistributedOptimizer(optax.sgd(0.1), backward_passes_per_step=k)
    step = make_step(opt, hvd.mesh(), params)
    opt_state = opt.init(params)

    p = params
    p, opt_state, _ = step(p, opt_state, jnp.asarray(x), jnp.asarray(y))
    # after 1 of 2 passes: no update applied
    np.testing.assert_array_equal(np.asarray(p["w"]), 0.0)
    p, opt_state, _ = step(p, opt_state, jnp.asarray(x), jnp.asarray(y))
    # now the update fired
    assert np.abs(np.asarray(p["w"])).sum() > 0


def test_distributed_value_and_grad(hvd8):
    from horovod_tpu.optim.distributed import distributed_value_and_grad

    def loss_fn(w, x):
        return jnp.sum(w * x)

    vag = distributed_value_and_grad(loss_fn)
    mesh = hvd.mesh()

    def body(w, x):
        loss, g = vag(w, x[0])
        return loss.reshape(1), g

    w = jnp.ones(3)
    x = jnp.stack([jnp.full((3,), float(r)) for r in range(8)])
    loss, g = jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(), P("hvd")), out_specs=(P("hvd"), P()),
            check_vma=False,
        )
    )(w, x)
    # grad of sum(w*x) wrt w is x; averaged over ranks = mean(0..7) = 3.5
    np.testing.assert_allclose(np.asarray(g), np.full((3,), 3.5), rtol=1e-6)


def test_broadcast_parameters(hvd8):
    params = {"w": jnp.arange(4.0), "b": jnp.zeros(2)}
    out = hvd.broadcast_parameters(params, root_rank=0)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(4.0))


def test_broadcast_object_single_controller(hvd8):
    obj = {"epoch": 3, "lr": 0.1}
    assert hvd.broadcast_object(obj, root_rank=0) == obj


def test_allgather_object_single_controller(hvd8):
    objs = hvd.allgather_object({"r": 1})
    assert len(objs) == 8
    assert all(o == {"r": 1} for o in objs)


def test_single_rank_group_skips_reduction_machinery():
    """A live mesh axis of size 1 (the single-chip bench world) must
    skip fusion-bucket packing and compression entirely — the traced
    BERT step spent ~4% of device time packing buckets nothing rode
    (docs/benchmarks.md). With sgd(lr=1) the update equals -grad
    bit-identically; bf16 wire compression would have rounded."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    import horovod_tpu as hvd

    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    hvd.init(mesh=mesh)
    opt = hvd.DistributedOptimizer(
        optax.sgd(1.0), compression=hvd.Compression.bf16)
    params = {"w": jnp.asarray(
        np.random.RandomState(0).randn(7, 13), jnp.float32)}
    state = opt.init(params)
    grads = {"w": jnp.asarray(
        np.random.RandomState(1).randn(7, 13), jnp.float32)}

    def upd(g, s, p):
        u, _ = opt.update(g, s, p)
        return u

    out = jax.jit(
        shard_map(
            upd, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_vma=False,
        )
    )(grads, state, params)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  -np.asarray(grads["w"]))
