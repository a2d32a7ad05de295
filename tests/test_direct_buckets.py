"""A bucket as a group of arrays (ops/fusion.pack_groups_by_plan,
optim/distributed._reduce_grad_tree): a large leaf rides its bucket's
all-reduce in its own shape, the small leaves are still packed.

The benchmark holds the REDUCED gradient to nothing at n > 1 (PERF.md
section 7), so these tests do: the reduced tree equals the exact
reduction of the per-device trees, and equals bit for bit what the
all-packed reduction returns, for every op, wire and option that takes
the group form; what needs one contiguous array (the int8 wire, Adasum,
ZeRO) still lowers a flat bucket.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.core.state import global_state
from horovod_tpu.ops import fusion
from horovod_tpu.utils import metrics

# The module's constant is sized for real matrices; here a "large" leaf
# is one of 16 KiB, so that eight devices' trees stay small.
SMALL_CONSTANT = 16 << 10
NOTHING_DIRECT = 1 << 60
PY_FLOAT = 1.5  # a Python-float leaf: the same constant on every device
N = 8


def per_device_trees(seed=0):
    """One tree a device, stacked on a leading axis of 8: a rank-2 leaf
    over the constant, one exactly at it in bf16, one under it, a rank-1
    leaf over it (rank decides too), a small vector and a scalar."""
    rng = np.random.RandomState(seed)

    def f32(*shape):
        return jnp.asarray(rng.randn(N, *shape), jnp.float32)

    return {
        "block_0": {"kernel": f32(96, 64),            # 24 KiB: direct
                    "bias": f32(64)},
        "block_1": {"kernel": f32(8, 16),             # 512 B: packed
                    "long_vector": f32(8192)},        # 32 KiB, rank 1
        "half": jnp.asarray(rng.randn(N, 64, 128),    # 16 KiB: at it
                            jnp.bfloat16),
        "scale": f32(),
    }


def reduce_tree(monkeypatch, constant, stacked, out_spec=P(), calls=1,
                lowered=False, make_opt=hvd.DistributedOptimizer, **kw):
    """`calls` updates of an identity optimizer wrapped by `make_opt`
    over the per-device trees, under `constant` as the direct-leaf
    size: the last update's result (or the step's lowered text)."""
    monkeypatch.setattr(fusion, "DIRECT_MIN_BYTES", constant)
    opt = make_opt(optax.identity(), **kw)

    def local(stacked):
        g = jax.tree_util.tree_map(lambda x: x[0], stacked)
        g["py_float"] = PY_FLOAT
        state = opt.init(g)
        for _ in range(calls):
            out, state = opt.update(g, state, g)
        if out_spec != P():  # per-device results, stacked again
            out = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], out)
        return out

    step = jax.jit(shard_map(
        local, mesh=hvd.mesh(), in_specs=(P("hvd"),), out_specs=out_spec,
        check_vma=False))
    if lowered:
        return step.lower(stacked).as_text()
    return jax.device_get(step(stacked))


def exact(stacked, reducer):
    """The reduction over devices in float64, per leaf."""
    return {k: exact(v, reducer) if isinstance(v, dict)
            else reducer(np.asarray(v, np.float64))
            for k, v in stacked.items()}


def assert_bitwise(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def assert_close(got, want, rtol, atol):
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        g = got
        for key in path:
            g = g[key.key]
        np.testing.assert_allclose(
            np.asarray(g, np.float64), w, rtol=rtol, atol=atol,
            err_msg=jax.tree_util.keystr(path))


CASES = {
    # name: (optimizer arguments, reduction over devices, rtol of fp32
    # leaves against it, ordered_buckets, updates)
    "average": ({}, lambda x: x.mean(0), 1e-6, True, 1),
    "sum": ({"op": hvd.Sum}, lambda x: x.sum(0), 1e-6, True, 1),
    "min": ({"op": hvd.Min}, lambda x: x.min(0), 0, True, 1),
    "max": ({"op": hvd.Max}, lambda x: x.max(0), 0, True, 1),
    "fp16_wire": ({"compression": hvd.Compression.fp16},
                  lambda x: x.mean(0), 2e-2, True, 1),
    "bf16_wire": ({"compression": hvd.Compression.bf16},
                  lambda x: x.mean(0), 1e-1, True, 1),
    "predivide": ({"gradient_predivide_factor": 2.0},
                  lambda x: x.mean(0), 1e-6, True, 1),
    "unordered": ({}, lambda x: x.mean(0), 1e-6, False, 1),
    "two_passes": ({"backward_passes_per_step": 2},
                   lambda x: x.mean(0), 1e-6, True, 2),
}


@pytest.mark.parametrize("threshold", [8 << 10, 64 << 20],
                         ids=["many_buckets", "one_bucket"])
@pytest.mark.parametrize("case", CASES)
def test_reduced_tree_is_exact_and_bitwise_the_packed_one(
        hvd8, monkeypatch, case, threshold):
    kw, reducer, rtol, ordered, calls = CASES[case]
    monkeypatch.setattr(global_state().knobs, "ordered_buckets", ordered)
    stacked = per_device_trees(seed=len(case))
    runs = {constant: reduce_tree(
        monkeypatch, constant, stacked, calls=calls,
        fusion_threshold_bytes=threshold, **kw)
        for constant in (SMALL_CONSTANT, NOTHING_DIRECT)}
    grouped, packed = runs[SMALL_CONSTANT], runs[NOTHING_DIRECT]
    assert_bitwise(grouped, packed)
    want = exact(stacked, reducer)
    bf16 = {"half": want.pop("half")}
    assert float(grouped.pop("py_float")) == pytest.approx(
        reducer(np.full((N,), PY_FLOAT)), rel=max(rtol, 1e-6))
    assert_close({"half": grouped.pop("half")}, bf16, rtol=max(rtol, 5e-2),
                 atol=0.1 if rtol else 0)  # eight bf16 values summed in bf16
    # fp32 leaves: eight values of order one
    assert_close(grouped, want, rtol=rtol, atol=8 * rtol)


def test_process_set_members_get_their_mean(hvd8, monkeypatch):
    ps = hvd.add_process_set([0, 2, 4, 6])
    stacked = per_device_trees(seed=3)
    runs = [reduce_tree(monkeypatch, constant, stacked, out_spec=P("hvd"),
                        process_set=ps, fusion_threshold_bytes=8 << 10)
            for constant in (SMALL_CONSTANT, NOTHING_DIRECT)]
    assert_bitwise(*runs)
    members = list(ps.ranks)
    want = exact(stacked, lambda x: x[members].mean(0))
    want.pop("half")
    got = jax.tree_util.tree_map(lambda x: x[members[1]], runs[0])
    got.pop("half"), got.pop("py_float")
    assert_close(got, want, rtol=1e-6, atol=1e-6)


def flat_bucket_concatenates(text, elements):
    """Concatenations in the lowered text whose result is one flat
    array of `elements`."""
    return re.findall(
        rf"stablehlo\.concatenate.*-> tensor<{elements}x", text)


F32_ELEMENTS = 96 * 64 + 64 + 8 * 16 + 8192 + 1 + 1  # with py_float


@pytest.mark.parametrize("keeper", ["int8_wire", "adasum", "zero"])
def test_who_needs_one_contiguous_array_keeps_the_flat_bucket(
        hvd8, monkeypatch, keeper):
    kw = {"int8_wire": {"compression": hvd.Compression.int8_raw},
          "adasum": {"op": hvd.Adasum},
          "zero": {"make_opt": hvd.ShardedOptimizer}}[keeper]
    stacked = per_device_trees()
    text = reduce_tree(monkeypatch, SMALL_CONSTANT, stacked, lowered=True,
                       fusion_threshold_bytes=64 << 20, **kw)
    assert flat_bucket_concatenates(text, F32_ELEMENTS), keeper
    # and the plain all-reduce does not build it
    plain = reduce_tree(monkeypatch, SMALL_CONSTANT, stacked, lowered=True,
                        fusion_threshold_bytes=64 << 20)
    assert not flat_bucket_concatenates(plain, F32_ELEMENTS)
    assert flat_bucket_concatenates(plain, F32_ELEMENTS - 96 * 64)


def test_direct_leaf_is_neither_packed_nor_sliced(hvd8, monkeypatch):
    """In the lowered step the direct leaf meets no reshape to 1-D, no
    concatenate and no dynamic_slice: its all-reduce takes and returns
    its own shape. One bucket's operands share one barrier."""
    stacked = per_device_trees()
    text = reduce_tree(monkeypatch, SMALL_CONSTANT, stacked, lowered=True,
                       fusion_threshold_bytes=64 << 20)
    assert re.search(r"all_reduce.*\n(?:.*\n){0,6}?.*tensor<96x64xf32>\) -> "
                     r"tensor<96x64xf32>", text)
    assert f"tensor<{96 * 64}xf32>" not in text
    packed = reduce_tree(monkeypatch, NOTHING_DIRECT, stacked, lowered=True,
                         fusion_threshold_bytes=64 << 20)
    assert f"tensor<{96 * 64}xf32>" in packed
    # two dtypes, two buckets, whatever their form: one ordering edge
    assert text.count("optimization_barrier") == 1
    assert packed.count("optimization_barrier") == 1


@pytest.fixture
def fusion_gauges():
    was = metrics.enabled()
    metrics.enable()
    metrics.registry.clear()

    def read():
        snap = metrics.registry.snapshot()
        return tuple(int(snap[name][""]) if name in snap else None
                     for name in ("hvd_fusion_direct_bytes",
                                  "hvd_fusion_packed_bytes",
                                  "hvd_fusion_direct_leaves"))

    yield read
    metrics.registry.clear()
    if not was:
        metrics.disable()


def test_gauges_say_how_the_tree_split(hvd8, monkeypatch, fusion_gauges):
    stacked = per_device_trees()
    reduce_tree(monkeypatch, SMALL_CONSTANT, stacked, lowered=True)
    small_f32 = (64 + 8 * 16 + 8192 + 1 + 1) * 4
    assert fusion_gauges() == (96 * 64 * 4 + 64 * 128 * 2, small_f32, 2)
    reduce_tree(monkeypatch, NOTHING_DIRECT, stacked, lowered=True)
    assert fusion_gauges() == (0, 96 * 64 * 4 + 64 * 128 * 2 + small_f32, 0)


def test_gpt2_medium_splits_as_the_constant_was_set_for(monkeypatch):
    """GPT-2-medium's gradient tree under the module's constant: every
    matrix and both tables ride direct (4 to 196 MiB), the vectors and
    the [16, 64] biases (4 to 16 KiB) are packed; the same split for
    every constant from 16 KiB to 4 MiB."""
    from horovod_tpu.models.transformer import GPT2_MEDIUM, Transformer

    params = jax.eval_shape(
        Transformer(GPT2_MEDIUM).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    leaves = jax.tree_util.tree_leaves(params)

    def nbytes(some):
        return sum(leaf.size * leaf.dtype.itemsize for leaf in some)

    direct = list(filter(fusion.rides_direct, leaves))
    assert len(direct) == 24 * 6 + 2
    assert all(leaf.ndim >= 2 and nbytes([leaf]) >= 4 << 20
               for leaf in direct)
    assert nbytes(leaves) - nbytes(direct) < 1.5 * (1 << 20)
    assert nbytes(direct) > 1350 * (1 << 20)
    for constant in (16 << 10, 4 << 20):
        monkeypatch.setattr(fusion, "DIRECT_MIN_BYTES", constant)
        assert list(filter(fusion.rides_direct, leaves)) == direct
