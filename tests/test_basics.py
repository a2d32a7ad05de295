"""init/rank/size/topology tests.

Reference analog: the query surface exercised throughout
test/parallel/test_torch.py (hvd.rank/size/local_rank) and
test/single/test_run.py's topology helpers.
"""

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.core.basics import _parse_mesh_spec


def test_init_and_sizes(hvd8):
    assert hvd.is_initialized()
    assert hvd.size() == 8
    assert hvd.local_size() == 8
    assert hvd.cross_size() == 1
    assert hvd.cross_rank() == 0
    assert hvd.rank() == 0  # controller owns device 0
    assert hvd.local_rank() == 0
    assert hvd.is_homogeneous()


def test_not_initialized_raises():
    with pytest.raises(hvd.HorovodTpuError):
        hvd.size()


def test_double_init_is_noop(hvd8):
    hvd.init()
    assert hvd.size() == 8


def test_build_flags(hvd8):
    assert hvd.xla_built() and hvd.xla_enabled()
    assert not hvd.mpi_built()
    assert not hvd.nccl_built()
    assert not hvd.gloo_built()


def test_rank_inside_shard_map(hvd8):
    mesh = hvd.mesh()

    def body(x):
        return x + hvd.rank()

    out = jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"))
    )(jnp.zeros(8))
    np.testing.assert_array_equal(np.asarray(out), np.arange(8.0))


def test_local_rank_inside_shard_map(hvd8):
    mesh = hvd.mesh()

    def body(x):
        return x + hvd.local_rank()

    out = jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"))
    )(jnp.zeros(8))
    np.testing.assert_array_equal(np.asarray(out), np.arange(8.0) % 8)


def test_mesh_spec_parsing():
    assert _parse_mesh_spec("dp=8", 8) == ((8,), ("dp",))
    assert _parse_mesh_spec("dp=4,tp=2", 8) == ((4, 2), ("dp", "tp"))
    assert _parse_mesh_spec("dp=-1,tp=2", 8) == ((4, 2), ("dp", "tp"))
    with pytest.raises(ValueError):
        _parse_mesh_spec("dp=3", 8)
    with pytest.raises(ValueError):
        _parse_mesh_spec("dp=-1,tp=-1", 8)


def test_custom_mesh_spec(monkeypatch):
    monkeypatch.setenv("HOROVOD_MESH", "dp=4,tp=2")
    hvd.init()
    assert hvd.mesh().axis_names == ("dp", "tp")
    assert hvd.size() == 8  # dp_axis defaults to all axes
    hvd.shutdown()


def test_init_with_comm_rejected():
    with pytest.raises(ValueError):
        hvd.init(comm=object())


def test_topology_op_family(hvd8):
    """In-graph topology queries (reference tensorflow/mpi_ops.py
    rank_op/size_op/...): plain jnp values eagerly, traced values that
    resolve per-device inside shard_map."""
    import jax
    from jax.sharding import PartitionSpec as P

    assert int(hvd.size_op()) == 8
    assert int(hvd.local_size_op()) == 8
    assert int(hvd.rank_op()) == 0  # coordinator-owned outside spmd
    assert int(hvd.local_rank_op()) == 0
    assert int(hvd.process_set_included_op(0)) == 1

    ps = hvd.add_process_set([1, 3, 5])
    try:
        assert int(hvd.size_op(process_set_id=ps.process_set_id)) == 3

        def f():
            # traced forms: per-device rank, set-rank table lookup,
            # inclusion mask
            return (hvd.rank_op().reshape(1),
                    hvd.rank_op(ps.process_set_id).reshape(1),
                    hvd.process_set_included_op(
                        ps.process_set_id).reshape(1))

        r, sr, inc = jax.jit(shard_map(
            f, mesh=hvd.mesh(), in_specs=(),
            out_specs=(P("hvd"), P("hvd"), P("hvd")),
            check_vma=False))()
        assert list(r) == list(range(8))
        assert list(inc) == [0, 1, 0, 1, 0, 1, 0, 0]
        assert [int(sr[g]) for g in (1, 3, 5)] == [0, 1, 2]
        # non-members carry the documented -1 sentinel (mask with
        # process_set_included_op before indexing)
        assert [int(sr[g]) for g in (0, 2, 4, 6, 7)] == [-1] * 5
    finally:
        hvd.remove_process_set(ps)


def test_mpi_threads_supported_parity():
    assert hvd.mpi_threads_supported() is False
