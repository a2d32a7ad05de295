"""SyncBatchNorm, data loaders, callbacks (tier-2 style: 8-device
virtual mesh via conftest)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.data import (
    AsyncDataLoaderMixin,
    BaseDataLoader,
    ElasticSampler,
    ShardedDataLoader,
)
from horovod_tpu.callbacks import (
    LearningRateScheduleCallback,
    LearningRateWarmupCallback,
    MetricAverageCallback,
)


# ------------------------------------------------------- SyncBatchNorm


def test_sync_batch_norm_matches_global_stats(hvd8):
    """Per-device shards with different stats: SyncBatchNorm must normalize
    with the GLOBAL batch statistics (reference torch/sync_batch_norm.py
    semantics)."""
    mesh = hvd.mesh()
    ax = hvd.dp_axis_names()[0]
    rng = np.random.RandomState(0)
    # 8 shards with very different means
    x = (rng.rand(64, 16).astype(np.float32)
         + np.repeat(np.arange(8), 8)[:, None] * 10)

    model = hvd.SyncBatchNorm(use_running_average=False, momentum=0.9)
    variables = model.init(jax.random.PRNGKey(0), x[:8])

    def fwd(xs):
        y, updates = model.apply(
            variables, xs, mutable=["batch_stats"]
        )
        return y, updates["batch_stats"]

    sharded = jax.jit(
        shard_map(
            fwd, mesh=mesh, in_specs=P(ax),
            out_specs=(P(ax), P()), check_vma=False,
        )
    )
    xs = jax.device_put(x, NamedSharding(mesh, P(ax)))
    y, stats = sharded(xs)
    y = np.asarray(y)

    # expected: plain batchnorm over the WHOLE batch
    mean = x.mean(0)
    var = x.var(0)
    expect = (x - mean) / np.sqrt(var + model.epsilon)
    np.testing.assert_allclose(y, expect, atol=1e-3)
    # running stats updated toward global mean
    np.testing.assert_allclose(
        np.asarray(stats["mean"]), 0.1 * mean, rtol=1e-3
    )


def test_sync_batch_norm_local_fallback(hvd8):
    """Outside shard_map: plain local batch norm."""
    x = np.random.RandomState(1).rand(16, 8).astype(np.float32)
    model = hvd.SyncBatchNorm(use_running_average=False)
    variables = model.init(jax.random.PRNGKey(0), x)
    y, _ = model.apply(variables, x, mutable=["batch_stats"])
    expect = (x - x.mean(0)) / np.sqrt(x.var(0) + model.epsilon)
    np.testing.assert_allclose(np.asarray(y), expect, atol=1e-4)


# ------------------------------------------------------- data loaders


class RangeLoader(BaseDataLoader):
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def _iterate(self):
        for i in range(self.n):
            yield i


class AsyncRangeLoader(AsyncDataLoaderMixin, RangeLoader):
    pass


def test_async_loader_preserves_order():
    loader = AsyncRangeLoader(50, async_loader_queue_size=4)
    assert list(loader) == list(range(50))
    loader.close()


def test_async_loader_sync_mode():
    loader = AsyncRangeLoader(10, async_loader_queue_size=0)
    assert list(loader) == list(range(10))


def test_sharded_loader_places_on_mesh(hvd8):
    batches = [np.ones((16, 4), np.float32) * i for i in range(3)]
    loader = ShardedDataLoader(batches)
    out = list(loader)
    assert len(out) == 3
    for i, b in enumerate(out):
        assert isinstance(b, jax.Array)
        assert len(b.sharding.device_set) == 8
        np.testing.assert_allclose(np.asarray(b), batches[i])


def test_elastic_sampler_skips_processed():
    s = ElasticSampler(dataset_size=20, shuffle=False)
    s.set_world(0, 2)
    first = list(s)[:3]
    assert first == [0, 2, 4]
    s.record_batch(0, 3)  # both replicas consumed 3 → 6 globally
    s.set_world(0, 2)  # resize triggers reset with processed skip
    assert not (set(range(6)) & set(s.indices))
    # state roundtrip — identical on every rank (global cursor, not
    # rank-local index sets), so broadcasting rank 0's state is lossless
    state = s.state_dict()
    s2 = ElasticSampler(dataset_size=20, shuffle=False)
    s2.load_state_dict(state)
    assert set(s2.processed_indices) == set(range(6))


def test_elastic_sampler_state_rank_symmetric():
    """Every rank's state_dict must agree after the same recorded batches,
    so an elastic resync (broadcast of rank 0's state) loses nothing."""
    states = []
    for rank in range(4):
        s = ElasticSampler(dataset_size=32, shuffle=True, seed=7)
        s.set_world(rank, 4)
        s.record_batch(0, 2)
        s.record_batch(1, 2)
        states.append(s.state_dict())
    assert all(st == states[0] for st in states)
    assert states[0]["processed_num"] == 16  # 2 batches × 2 × 4 replicas


# ------------------------------------------------------- callbacks


def test_warmup_scale_ramps_to_size(hvd8):
    cb = LearningRateWarmupCallback(warmup_epochs=5)
    assert cb.scale(0) == pytest.approx(1.0)
    assert cb.scale(5) == pytest.approx(8.0)  # world of 8
    assert 1.0 < cb.scale(2.5) < 8.0
    sched = cb.as_schedule(steps_per_epoch=10, base_lr=0.1)
    assert float(sched(0)) == pytest.approx(0.1)
    assert float(sched(50)) == pytest.approx(0.8)


def test_schedule_callback_windows():
    cb = LearningRateScheduleCallback(
        multiplier=lambda e: 0.1, start_epoch=2, end_epoch=4
    )
    assert cb.scale(1) == 1.0
    assert cb.scale(2) == pytest.approx(0.1)
    assert cb.scale(4) == 1.0


def test_metric_average_callback(hvd8):
    logs = {"loss": 2.0, "name": "x"}
    MetricAverageCallback().on_epoch_end(0, logs)
    assert logs["loss"] == pytest.approx(2.0)  # replicated world: identity
    assert logs["name"] == "x"


def test_elastic_sampler_pad_shortfall_keeps_shards_equal():
    """Near epoch end: fewer remaining samples than replicas must still
    give every replica the same shard length (lockstep SPMD loops)."""
    lengths = []
    for rank in range(8):
        s = ElasticSampler(dataset_size=11, shuffle=False)
        s.processed_num = 8  # 3 remain, 8 replicas
        s.set_world(rank, 8)
        lengths.append(len(s))
    assert len(set(lengths)) == 1 and lengths[0] > 0


def test_async_loader_propagates_errors():
    class Boom(BaseDataLoader):
        def __len__(self):
            return 2

        def _iterate(self):
            yield 1
            raise RuntimeError("io error")

    class AsyncBoom(AsyncDataLoaderMixin, Boom):
        pass

    loader = AsyncBoom(async_loader_queue_size=2)
    with pytest.raises(RuntimeError, match="io error"):
        list(loader)


def test_async_loader_abandoned_iteration_releases_thread():
    import time

    loader = AsyncRangeLoader(10000, async_loader_queue_size=2)
    for i in loader:
        if i == 3:
            break
    time.sleep(0.5)
    assert not loader._async_thread.is_alive()


def test_elastic_callbacks_commit_and_cursors(hvd8):
    """CommitStateCallback / UpdateBatchStateCallback /
    UpdateEpochStateCallback (reference _keras/elastic.py): commits
    every N batches, batch cursor resumes mid-epoch, epoch counts
    globally across resets."""
    import horovod_tpu as hvd
    from horovod_tpu.callbacks import (
        CommitStateCallback,
        UpdateBatchStateCallback,
        UpdateEpochStateCallback,
    )

    state = hvd.elastic.TpuState(step=0)
    commits = []
    orig_commit = state.commit
    state.commit = lambda: (commits.append(True), orig_commit())

    cb_commit = CommitStateCallback(state, batches_per_commit=2)
    cb_batch = UpdateBatchStateCallback(state)
    cb_epoch = UpdateEpochStateCallback(state)

    cb_commit.on_train_begin()
    for b in range(5):
        state.step += 1
        cb_batch.on_batch_end(b)
        cb_commit.on_batch_end(b)
    # 5 batches at 2/commit -> commits after b=1 and b=3
    assert len(commits) == 2
    assert state.batch == 4
    # restore rolls the batch cursor back to the last commit
    state.step = 99
    state.restore()
    assert state.step == 4  # committed after batch 3 (steps 1..4)
    assert state.batch == 3

    cb_epoch.on_epoch_end(0)
    cb_batch.on_epoch_end(0)
    cb_commit.on_epoch_end(0)
    assert state.epoch == 1 and state.batch == 0
    assert len(commits) == 3


def test_device_prefetch_orders_and_places(hvd8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.data import device_prefetch

    sh = NamedSharding(hvd.mesh(), P("hvd"))
    batches = [{"x": np.full((16, 4), i, np.float32),
                "n": np.int32(i)} for i in range(5)]
    out = list(device_prefetch(iter(batches), sharding=sh, size=2))
    assert len(out) == 5
    for i, b in enumerate(out):
        assert isinstance(b["x"], jax.Array)
        assert b["x"].sharding == sh
        np.testing.assert_allclose(np.asarray(b["x"]), batches[i]["x"])
        assert int(b["n"]) == i


def test_device_prefetch_zero_size_still_places(hvd8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.data import device_prefetch

    sh = NamedSharding(hvd.mesh(), P("hvd"))
    src = [np.ones((16, 2), np.float32) * i for i in range(3)]
    out = list(device_prefetch(iter(src), sharding=sh, size=0))
    assert [int(b[0, 0]) for b in out] == [0, 1, 2]
    # size=0 disables the lookahead only — placement still applies
    assert all(isinstance(b, jax.Array) and b.sharding == sh
               for b in out)


def test_device_prefetch_incompatible_leaf_rides_replicated(hvd8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.data import device_prefetch

    sh = NamedSharding(hvd.mesh(), P("hvd"))
    # 'pos' has a leading dim (10) the 8-way batch sharding cannot
    # split: it must land replicated, not crash the batch
    batches = [{"x": np.ones((16, 4), np.float32),
                "pos": np.arange(10)}]
    (b,) = list(device_prefetch(iter(batches), sharding=sh, size=2))
    assert b["x"].sharding == sh
    assert isinstance(b["pos"], jax.Array)
    np.testing.assert_array_equal(np.asarray(b["pos"]), np.arange(10))
