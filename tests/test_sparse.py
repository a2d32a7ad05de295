"""Sparse (IndexedSlices) allreduce: the gathered-slices reduction for
embedding-heavy models (reference tensorflow/__init__.py:56,
torch/mpi_ops.py:556). The correctness bar: densified sparse allreduce
== dense allreduce of the same gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

import horovod_tpu as hvd
from horovod_tpu.ops.sparse import (
    IndexedSlices,
    dense_to_sparse,
    sparse_allreduce,
    sparse_to_dense,
)

V, D = 16, 4  # vocab x embedding dim


def _embedding_grads(rank: int, nnz: int = 3):
    """Rank-distinct embedding gradient: nnz rows touched."""
    r = np.random.RandomState(100 + rank)
    ids = r.choice(V, size=nnz, replace=False).astype(np.int32)
    vals = r.randn(nnz, D).astype(np.float32)
    dense = np.zeros((V, D), np.float32)
    dense[ids] = vals
    return ids, vals, dense


def test_spmd_sparse_matches_dense(hvd8):
    """Inside shard_map: per-device IndexedSlices gradients; densified
    sparse average must equal the dense average."""
    mesh = hvd.mesh()
    n = hvd.size()
    all_ids = np.stack([_embedding_grads(r)[0] for r in range(n)])
    all_vals = np.stack([_embedding_grads(r)[1] for r in range(n)])
    dense_avg = np.mean(
        np.stack([_embedding_grads(r)[2] for r in range(n)]), axis=0
    )

    def step(ids, vals):
        sl = IndexedSlices(vals[0], ids[0], (V, D))
        red = sparse_allreduce(sl, op=hvd.Average)
        return sparse_to_dense(red)

    fn = jax.jit(
        shard_map(
            step, mesh=mesh, in_specs=(P("hvd"), P("hvd")),
            out_specs=P(), check_vma=False,
        )
    )
    ids_s = jax.device_put(all_ids, NamedSharding(mesh, P("hvd")))
    vals_s = jax.device_put(all_vals, NamedSharding(mesh, P("hvd")))
    out = np.asarray(fn(ids_s, vals_s))
    np.testing.assert_allclose(out, dense_avg, rtol=1e-5)


def test_allreduce_routes_indexed_slices(hvd8):
    """hvd.allreduce(IndexedSlices) takes the sparse path (TF parity)."""
    ids, vals, dense = _embedding_grads(0)
    sl = IndexedSlices(jnp.asarray(vals), jnp.asarray(ids), (V, D))
    red = hvd.allreduce(sl, op=hvd.Average)
    assert isinstance(red, IndexedSlices)
    # single-controller eager: every rank holds the same slices, so the
    # gathered result is n copies and the average densifies to the input
    out = np.asarray(sparse_to_dense(red))
    np.testing.assert_allclose(out, dense, rtol=1e-5)


def test_sparse_sum_keeps_duplicates(hvd8):
    ids, vals, dense = _embedding_grads(1)
    sl = IndexedSlices(jnp.asarray(vals), jnp.asarray(ids), (V, D))
    red = sparse_allreduce(sl, op=hvd.Sum)
    n = hvd.size()
    assert red.values.shape[0] == n * len(ids)
    out = np.asarray(sparse_to_dense(red))
    np.testing.assert_allclose(out, n * dense, rtol=1e-5)


def test_dense_to_sparse_roundtrip(hvd8):
    _, _, dense = _embedding_grads(2)
    sl = dense_to_sparse(jnp.asarray(dense))
    assert sl.values.shape[0] == 3  # nnz rows extracted
    np.testing.assert_allclose(
        np.asarray(sparse_to_dense(sl)), dense, rtol=1e-6
    )


def test_sparse_rejects_min_max(hvd8):
    ids, vals, _ = _embedding_grads(0)
    sl = IndexedSlices(jnp.asarray(vals), jnp.asarray(ids), (V, D))
    with pytest.raises(ValueError):
        sparse_allreduce(sl, op=hvd.Max)


def test_nested_indexed_slices_in_pytree(hvd8):
    """IndexedSlices nested in a gradient pytree must take the sparse
    path, not have its int32 indices averaged as data."""
    ids, vals, dense = _embedding_grads(4)
    tree = {
        "emb": IndexedSlices(jnp.asarray(vals), jnp.asarray(ids), (V, D)),
        "w": jnp.ones((3,)),
    }
    out = hvd.allreduce(tree, op=hvd.Average)
    assert isinstance(out["emb"], IndexedSlices)
    np.testing.assert_array_equal(
        np.asarray(out["emb"].indices)[: len(ids)], ids
    )
    np.testing.assert_allclose(
        np.asarray(sparse_to_dense(out["emb"])), dense, rtol=1e-5
    )
    np.testing.assert_allclose(np.asarray(out["w"]), np.ones((3,)))


def test_grouped_allreduce_mixed_sparse_dense(hvd8):
    ids, vals, dense = _embedding_grads(5)
    outs = hvd.grouped_allreduce(
        [jnp.ones((4,)),
         IndexedSlices(jnp.asarray(vals), jnp.asarray(ids), (V, D)),
         jnp.full((2,), 2.0)],
        op=hvd.Average,
    )
    np.testing.assert_allclose(np.asarray(outs[0]), np.ones((4,)))
    assert isinstance(outs[1], IndexedSlices)
    np.testing.assert_allclose(
        np.asarray(sparse_to_dense(outs[1])), dense, rtol=1e-5
    )
    assert outs[1].dense_shape == (V, D)  # shape untouched by fusion
    np.testing.assert_allclose(np.asarray(outs[2]), np.full((2,), 2.0))


def test_adasum_rejects_sparse(hvd8):
    ids, vals, _ = _embedding_grads(0)
    sl = IndexedSlices(jnp.asarray(vals), jnp.asarray(ids), (V, D))
    import jax as _jax
    from jax.sharding import PartitionSpec as _P

    fn = shard_map(
        lambda: hvd.allreduce(
            {"e": IndexedSlices(jnp.asarray(vals), jnp.asarray(ids),
                                (V, D))},
            op=hvd.Adasum,
        ),
        mesh=hvd.mesh(), in_specs=(), out_specs=_P(), check_vma=False,
    )
    with pytest.raises(ValueError, match="sparse"):
        fn()


def test_torch_sparse_optimizer_gradient(hvd8):
    """DistributedOptimizer routes sparse embedding grads through the
    gathered-slices path (reference optimizer.py:189)."""
    torch = pytest.importorskip("torch")
    import horovod_tpu.torch as thvd

    emb = torch.nn.Embedding(V, D, sparse=True)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(emb.parameters(), lr=0.1),
        named_parameters=[("emb.weight", emb.weight)],
    )
    before = emb.weight.detach().clone()
    ids = torch.tensor([1, 3, 5])
    loss = emb(ids).sum()
    loss.backward()
    opt.step()
    after = emb.weight.detach()
    # touched rows moved by lr (grad of sum = ones), untouched rows fixed
    for r in (1, 3, 5):
        np.testing.assert_allclose(
            (before[r] - after[r]).numpy(), np.full((D,), 0.1), rtol=1e-5
        )
    np.testing.assert_allclose(after[0].numpy(), before[0].numpy())


def test_torch_sparse_allreduce_matches_dense(hvd8):
    torch = pytest.importorskip("torch")
    import horovod_tpu.torch as thvd

    ids, vals, dense = _embedding_grads(3)
    st = torch.sparse_coo_tensor(
        torch.from_numpy(ids.astype(np.int64))[None],
        torch.from_numpy(vals),
        size=(V, D),
    )
    red = thvd.sparse_allreduce(st, name="emb.grad")
    out = red.coalesce().to_dense().numpy()
    np.testing.assert_allclose(out, dense, rtol=1e-5)


def test_async_sparse_routing_with_native_runtime(hvd8):
    """With the native eager runtime active, allreduce_async on an
    IndexedSlices must route through the sparse path (the dense wire
    format can't carry it), and non-sparse async ops must reject it
    loudly instead of flattening indices into collectives."""
    from horovod_tpu.core.state import global_state
    from horovod_tpu.ops import collectives as C
    from horovod_tpu.ops.eager_runtime import EagerRuntime

    st = global_state()
    rt = EagerRuntime(0, 1, cycle_ms=1.0, cache_capacity=8)
    st.eager_runtime = rt
    try:
        ids, vals, dense = _embedding_grads(0)
        slc = IndexedSlices(
            values=jnp.asarray(vals), indices=jnp.asarray(ids),
            dense_shape=(V, D),
        )
        # the native runtime is a world of 1, so the gathered slices are
        # exactly this rank's contribution (routing through the sparse
        # path, not the dense wire format, is what's under test)
        h = C.allreduce_async(slc, op=C.ReduceOp.SUM, name="emb")
        out = C.synchronize(h)
        np.testing.assert_allclose(
            np.asarray(sparse_to_dense(out)), dense, rtol=1e-5
        )
        for fn in (C.allgather_async, lambda t: C.broadcast_async(t, 0),
                   C.reducescatter_async, C.alltoall_async):
            with pytest.raises(TypeError, match="IndexedSlices"):
                fn(slc)
    finally:
        st.eager_runtime = None
        rt.shutdown()
