"""Collective op correctness sweeps.

Reference analog: test/parallel/test_torch.py:1-4066 — op × dtype ×
dimension sweeps for allreduce (average/sum/min/max/product, prescale/
postscale, grouped), allgather, broadcast, alltoall, reducescatter,
barrier; per-rank distinct values; process-set variants.

Per-rank values are expressed the SPMD way: a [8, ...] array sharded over
the mesh, with shard_map giving each device "its rank's tensor".
"""

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd

DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32]
DIMS = [1, 2, 3]


def run_spmd(hvd8, body, per_rank_in, out_spec=P()):
    """Run `body` under shard_map feeding each device its row of
    per_rank_in ([8, ...])."""
    mesh = hvd.mesh()
    wrapped = lambda x: body(x[0])
    return jax.jit(
        shard_map(
            wrapped, mesh=mesh, in_specs=P("hvd"), out_specs=out_spec,
            check_vma=False,
        )
    )(per_rank_in)


def per_rank_values(shape, dtype, seed=0):
    """[8, *shape] array, rank i's tensor = i-dependent values."""
    rng = np.random.RandomState(seed)
    if jnp.issubdtype(dtype, jnp.floating):
        vals = rng.uniform(-2, 2, size=(8,) + shape)
    else:
        vals = rng.randint(-10, 10, size=(8,) + shape)
    return jnp.asarray(vals).astype(dtype)


# ---------------------------------------------------------------------------
# allreduce
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("dim", DIMS)
def test_allreduce_sum(hvd8, dtype, dim):
    shape = (4,) * dim
    x = per_rank_values(shape, dtype)
    out = run_spmd(hvd8, lambda t: hvd.allreduce(t, op=hvd.Sum), x)
    expect = np.sum(np.asarray(x.astype(jnp.float32)), axis=0)
    rtol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)), expect, rtol=rtol, atol=1e-2
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_allreduce_average(hvd8, dtype):
    x = per_rank_values((8, 8), dtype)
    out = run_spmd(hvd8, lambda t: hvd.allreduce(t, op=hvd.Average), x)
    expect = np.mean(np.asarray(x.astype(jnp.float32)), axis=0)
    rtol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)), expect, rtol=rtol, atol=1e-2
    )


def test_allreduce_default_is_average(hvd8):
    x = per_rank_values((16,), jnp.float32)
    out = run_spmd(hvd8, lambda t: hvd.allreduce(t), x)
    np.testing.assert_allclose(
        np.asarray(out), np.mean(np.asarray(x), axis=0), rtol=1e-5
    )


@pytest.mark.parametrize("op,npfn", [(hvd.Min, np.min), (hvd.Max, np.max)])
def test_allreduce_minmax(hvd8, op, npfn):
    x = per_rank_values((5, 3), jnp.float32)
    out = run_spmd(hvd8, lambda t: hvd.allreduce(t, op=op), x)
    np.testing.assert_allclose(np.asarray(out), npfn(np.asarray(x), axis=0))


def test_allreduce_product(hvd8):
    x = per_rank_values((6,), jnp.float32)
    out = run_spmd(hvd8, lambda t: hvd.allreduce(t, op=hvd.Product), x)
    np.testing.assert_allclose(
        np.asarray(out), np.prod(np.asarray(x), axis=0), rtol=1e-4
    )


def test_allreduce_prescale_postscale(hvd8):
    x = per_rank_values((10,), jnp.float32)
    out = run_spmd(
        hvd8,
        lambda t: hvd.allreduce(
            t, op=hvd.Sum, prescale_factor=0.5, postscale_factor=4.0
        ),
        x,
    )
    expect = np.sum(np.asarray(x) * 0.5, axis=0) * 4.0
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5)


def test_allreduce_average_and_op_conflict(hvd8):
    with pytest.raises(ValueError):
        hvd.allreduce(jnp.zeros(3), average=True, op=hvd.Sum)


def test_allreduce_pytree(hvd8):
    tree = {
        "a": per_rank_values((4,), jnp.float32),
        "b": [per_rank_values((2, 2), jnp.float32, seed=1)],
    }
    mesh = hvd.mesh()
    out = jax.jit(
        shard_map(
            lambda t: hvd.allreduce(
                jax.tree_util.tree_map(lambda v: v[0], t), op=hvd.Sum
            ),
            mesh=mesh,
            in_specs=P("hvd"),
            out_specs=P(),
        )
    )(tree)
    np.testing.assert_allclose(
        np.asarray(out["a"]), np.sum(np.asarray(tree["a"]), axis=0), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(out["b"][0]),
        np.sum(np.asarray(tree["b"][0]), axis=0),
        rtol=1e-5,
    )


def test_grouped_allreduce(hvd8):
    xs = [
        per_rank_values((4,), jnp.float32, seed=i) for i in range(3)
    ] + [per_rank_values((2, 3), jnp.bfloat16, seed=7)]
    mesh = hvd.mesh()

    def body(ts):
        return hvd.grouped_allreduce([t[0] for t in ts], op=hvd.Sum)

    outs = jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("hvd"), out_specs=P())
    )(xs)
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(
            np.asarray(o.astype(jnp.float32)),
            np.sum(np.asarray(x.astype(jnp.float32)), axis=0),
            rtol=5e-2,
        )


def test_grouped_allreduce_average(hvd8):
    xs = [per_rank_values((4,), jnp.float32, seed=i) for i in range(2)]
    mesh = hvd.mesh()

    def body(ts):
        return hvd.grouped_allreduce([t[0] for t in ts], op=hvd.Average)

    outs = jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("hvd"), out_specs=P())
    )(xs)
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(
            np.asarray(o), np.mean(np.asarray(x), axis=0), rtol=1e-5
        )


def test_grouped_allgather_packed_single_collective(hvd8):
    """Values match per-tensor allgather AND the group lowers to ONE
    all-gather HLO per dtype (reference operations.cc:1725 negotiates
    grouped allgathers as one unit; here the pack is compile-time)."""
    xs = [per_rank_values((2, 3), jnp.float32, seed=1),
          per_rank_values((1, 5), jnp.float32, seed=2),
          per_rank_values((4,), jnp.float32, seed=3)]
    mesh = hvd.mesh()

    def body(ts):
        return hvd.grouped_allgather([t[0] for t in ts])

    jf = jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("hvd"), out_specs=P(),
                  check_vma=False)
    )
    outs = jf(xs)
    for x, o in zip(xs, outs):
        flat = np.asarray(x)  # [8, ...] per-rank values
        expect = flat.reshape((-1,) + flat.shape[2:])
        np.testing.assert_allclose(np.asarray(o), expect, rtol=1e-6)
    hlo = jf.lower(xs).as_text()
    import re

    n_ag = len(re.findall(r'"all_gather|stablehlo\.all_gather', hlo))
    assert n_ag == 1, f"expected ONE packed all-gather, found {n_ag}"


def test_grouped_reducescatter_packed_single_collective(hvd8):
    """Values match per-tensor reducescatter AND the group lowers to ONE
    reduce-scatter HLO (reference operations.cc:1532)."""
    xs = [per_rank_values((8, 2), jnp.float32, seed=1),
          per_rank_values((16,), jnp.float32, seed=2)]
    mesh = hvd.mesh()

    def body(ts):
        outs = hvd.grouped_reducescatter(
            [t[0] for t in ts], op=hvd.Sum)
        singles = [hvd.reducescatter(t[0], op=hvd.Sum) for t in ts]
        return outs, singles

    jf = jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("hvd"),
                  out_specs=P("hvd"), check_vma=False)
    )
    outs, singles = jf(xs)
    for o, s in zip(outs, singles):
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(s), rtol=1e-5)
    hlo = jf.lower(xs).as_text()
    import re

    n_rs = len(re.findall(
        r'"reduce_scatter|stablehlo\.reduce_scatter', hlo))
    # one packed collective for the group + one per single reference op
    assert n_rs == 1 + len(xs), f"expected packed group, found {n_rs}"


# ---------------------------------------------------------------------------
# allgather / broadcast
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
def test_allgather(hvd8, dtype):
    x = per_rank_values((3, 2), dtype)
    out = run_spmd(hvd8, lambda t: hvd.allgather(t), x)
    expect = np.asarray(x).reshape(24, 2)
    np.testing.assert_array_equal(np.asarray(out), expect)


@pytest.mark.parametrize("root", [0, 3, 7])
def test_broadcast(hvd8, root):
    x = per_rank_values((4, 4), jnp.float32)
    out = run_spmd(hvd8, lambda t: hvd.broadcast(t, root_rank=root), x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x)[root])


def test_broadcast_int(hvd8):
    x = per_rank_values((5,), jnp.int32)
    out = run_spmd(hvd8, lambda t: hvd.broadcast(t, root_rank=2), x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x)[2])


# ---------------------------------------------------------------------------
# reducescatter / alltoall
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_reducescatter_sum(hvd8, dtype):
    x = per_rank_values((16, 3), dtype)
    out = run_spmd(
        hvd8, lambda t: hvd.reducescatter(t, op=hvd.Sum), x, out_spec=P("hvd")
    )
    expect = np.sum(np.asarray(x.astype(jnp.float32)), axis=0)
    rtol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)), expect, rtol=rtol, atol=1e-2
    )


def test_reducescatter_average_default(hvd8):
    x = per_rank_values((8, 2), jnp.float32)
    out = run_spmd(hvd8, lambda t: hvd.reducescatter(t), x, out_spec=P("hvd"))
    np.testing.assert_allclose(
        np.asarray(out), np.mean(np.asarray(x), axis=0), rtol=1e-5
    )


def test_reducescatter_indivisible_raises(hvd8):
    x = per_rank_values((6, 2), jnp.float32)  # 6 % 8 != 0
    with pytest.raises(Exception):
        run_spmd(hvd8, lambda t: hvd.reducescatter(t), x, out_spec=P("hvd"))


def test_alltoall_equal_splits(hvd8):
    # rank r sends value r*8+j in chunk j; after exchange rank r holds
    # chunk r from every peer.
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)  # [rank, 8]
    out = run_spmd(hvd8, lambda t: hvd.alltoall(t), x, out_spec=P("hvd"))
    got = np.asarray(out).reshape(8, 8)
    expect = np.arange(64, dtype=np.float32).reshape(8, 8).T
    np.testing.assert_array_equal(got, expect)


# ---------------------------------------------------------------------------
# join / masked allreduce / barrier
# ---------------------------------------------------------------------------

def test_masked_allreduce(hvd8):
    x = per_rank_values((4,), jnp.float32)
    mesh = hvd.mesh()

    def body(t):
        t = t[0]
        valid = hvd.rank() < 6  # ranks 6,7 "joined"
        return hvd.masked_allreduce(t * 0 + hvd.rank(), valid)

    out = jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("hvd"), out_specs=P())
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.full((4,), 15.0 / 6.0),
                               rtol=1e-5)


def test_join_eager(hvd8):
    assert hvd.join() == 0


def test_barrier(hvd8):
    hvd.barrier()  # must not deadlock or raise


# ---------------------------------------------------------------------------
# async handles
# ---------------------------------------------------------------------------

def test_async_allreduce_and_synchronize(hvd8):
    h = hvd.allreduce_async(jnp.ones(4), op=hvd.Sum)
    assert isinstance(h, int)
    out = hvd.synchronize(h)
    np.testing.assert_allclose(np.asarray(out), np.full((4,), 8.0))


def test_poll(hvd8):
    h = hvd.allreduce_async(jnp.ones(4), op=hvd.Sum)
    # must eventually be ready and synchronizable
    hvd.poll(h)
    hvd.synchronize(h)


# ---------------------------------------------------------------------------
# eager (top-level) semantics: replicated single-controller world
# ---------------------------------------------------------------------------

def test_eager_allreduce_sum(hvd8):
    x = jnp.ones((3, 3))
    out = hvd.allreduce(x, op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(out), 8 * np.ones((3, 3)))


def test_eager_allreduce_average(hvd8):
    x = jnp.full((4,), 2.0)
    out = hvd.allreduce(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_eager_allgather(hvd8):
    x = jnp.arange(6.0).reshape(3, 2)
    out = hvd.allgather(x)
    assert out.shape == (24, 2)
    np.testing.assert_allclose(np.asarray(out), np.tile(np.asarray(x), (8, 1)))


def test_eager_broadcast(hvd8):
    x = jnp.arange(5.0)
    out = hvd.broadcast(x, root_rank=3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_eager_alltoall_uneven_splits(hvd8):
    """Review fix: identical-tensor semantics mean the received data is
    each peer's chunk-0 tiled, not a prefix slice."""
    x = jnp.arange(16.0).reshape(16, 1)
    out, received = hvd.alltoall(x, splits=[2] + [2] * 7)
    np.testing.assert_array_equal(np.asarray(received), np.full(8, 2))
    expect = np.tile(np.arange(2.0).reshape(2, 1), (8, 1))
    np.testing.assert_array_equal(np.asarray(out), expect)


def test_eager_alltoall_uneven_splits_process_set(hvd8):
    """Ragged alltoall on a process set without the native runtime now
    routes through the LoopbackExecutor (round 4: the tile(chunk0)
    fabrication is gone); replicated-buffer semantics: the received
    data is column `local rank` of the splits matrix."""
    ps = hvd.add_process_set([0, 2, 4])
    x = jnp.arange(12.0).reshape(6, 2)
    out, received = hvd.alltoall(x, splits=[1, 2, 3], process_set=ps)
    # our set-local rank is 0: every (identical) peer sends its first
    # 1 row; received splits = column 0 of the all-equal matrix
    np.testing.assert_array_equal(np.asarray(received), np.full(3, 1))
    np.testing.assert_array_equal(
        np.asarray(out), np.tile(np.asarray(x[:1]), (3, 1)))
