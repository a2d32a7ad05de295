"""EagerRuntime pipeline tests: enqueue → negotiate (native) → fuse →
execute → synchronize, single-process world (the multi-process negotiation
itself is covered by test_native_runtime.py)."""

import numpy as np
import pytest

from horovod_tpu.core.exceptions import HorovodInternalError
from horovod_tpu.ops.eager_runtime import EagerRuntime


@pytest.fixture
def rt():
    r = EagerRuntime(0, 1, cycle_ms=1.0, cache_capacity=32)
    yield r
    r.shutdown()


def test_allreduce_roundtrip(rt):
    x = np.arange(8, dtype=np.float32)
    h = rt.allreduce_async("t1", x)
    out = rt.synchronize(h)
    np.testing.assert_allclose(out, x)  # sum over world of 1


def test_allreduce_average_and_scales(rt):
    x = np.ones((4,), dtype=np.float32) * 2
    h = rt.allreduce_async("t2", x, average=True)
    np.testing.assert_allclose(rt.synchronize(h), x)
    h = rt.enqueue("t3", x, prescale=0.5, postscale=4.0)
    np.testing.assert_allclose(rt.synchronize(h), x * 0.5 * 4.0)


def test_many_tensors_all_complete(rt):
    handles = {
        f"g{i}": rt.allreduce_async(f"g{i}", np.full((16,), i, np.float32))
        for i in range(20)
    }
    for i, (name, h) in enumerate(handles.items()):
        np.testing.assert_allclose(
            rt.synchronize(h), np.full((16,), i, np.float32)
        )


def test_cache_hits_accumulate(rt):
    for _ in range(3):
        h = rt.allreduce_async("steady", np.ones((8,), np.float32))
        rt.synchronize(h)
    assert rt.cache_hits() >= 2


def test_barrier(rt):
    rt.barrier(timeout_s=10.0)


def test_bytes_negotiated_counts(rt):
    h = rt.allreduce_async("b", np.ones((1024,), np.float32))
    rt.synchronize(h)
    assert rt.bytes_negotiated() >= 4096


def test_allgather_roundtrip(rt):
    x = np.arange(6, dtype=np.float32).reshape(3, 2)
    h = rt.allgather_async("ag", x)
    np.testing.assert_allclose(rt.synchronize(h), x)  # world of 1


def test_alltoall_even(rt):
    x = np.arange(8, dtype=np.float32)
    h = rt.alltoall_async("a2a", x)
    out, recv = rt.synchronize(h)
    np.testing.assert_allclose(out, x)
    assert list(recv) == [8]


def test_alltoall_uneven_splits(rt):
    x = np.arange(10, dtype=np.float32)
    h = rt.alltoall_async("a2a_u", x, splits=[10])
    out, recv = rt.synchronize(h)
    np.testing.assert_allclose(out, x)
    assert list(recv) == [10]


def test_alltoall_bad_splits_raises(rt):
    h = rt.alltoall_async("a2a_bad", np.ones((10,), np.float32),
                          splits=[3])  # sums to 3, dim0 is 10
    with pytest.raises(HorovodInternalError):
        rt.synchronize(h)


def test_unknown_op_raises_not_passthrough():
    """ADVICE/VERDICT r1: executors must refuse unknown ops rather than
    'succeed' with garbage."""
    from horovod_tpu._native import ExecutionBatch
    from horovod_tpu.ops.eager_runtime import LoopbackExecutor

    batch = ExecutionBatch(
        batch_id=1, op=99, reduce_op=1, root_rank=0, prescale=1.0,
        postscale=1.0, dtype=7, total_bytes=4, names=["z"], handles=[1],
        first_shape=[1], error_reason="",
    )
    with pytest.raises(HorovodInternalError):
        LoopbackExecutor(1)(batch, {"z": np.ones((1,), np.float32)})


def test_hier_reduce_leaf_matches_flat_psum(hvd8):
    """The autotuned hierarchical allreduce leaf (XlaExecutor
    _hier_reduce_leaf — live during the Bayes search, round 4) is
    value-equal to the flat psum for every block size that divides the
    world."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops.eager_runtime import XlaExecutor

    # the executor's leaves are written against its own 'proc' axis
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]), ("proc",))
    x = jnp.asarray(np.random.RandomState(0).randn(8, 16), jnp.float32)
    ex = XlaExecutor.__new__(XlaExecutor)  # only the leaf is exercised
    for block in (2, 4):
        leaf = ex._hier_reduce_leaf(
            reduce_op=0, prescale=2.0, postscale=0.5, n=8, block=block)  # AVERAGE

        def wrapped(v):
            return leaf(v.reshape(-1)).reshape(v.shape)

        def flat(v):
            return (jax.lax.psum(v * 2.0, "proc") / 8 * 0.5)

        out_h = jax.jit(shard_map(
            wrapped, mesh=mesh, in_specs=P("proc"), out_specs=P("proc"),
            check_vma=False))(x)
        out_f = jax.jit(shard_map(
            flat, mesh=mesh, in_specs=P("proc"), out_specs=P("proc"),
            check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(out_h), np.asarray(out_f),
                                   rtol=1e-6, atol=1e-6)
