#!/usr/bin/env python3
"""Hold the reduced gradient to its exact mean on the devices at hand,
and time the reduction alone.

    python scripts/reduce_check.py [--seed N] [--sweep]
        [--out chiprun_out/reduce_check.jsonl]

The benchmark's `correct` compares the loss function's gradient on one
device and holds the REDUCED gradient to nothing at n > 1 (PERF.md
section 7, way (b)). This is that check as a builder's run:
`DistributedOptimizer(optax.identity()).update` in a `shard_map` over
seeded per-device trees of GPT-2-medium's parameter shapes (fp32,
1,353.5 MiB a device), each device's tree cut out of one draw made on
the device from (seed, its index), against the mean every device works out for itself
from all n trees with no collective. The limit is 1e-5 of the largest
mean, as an absolute error; a 16-bit wire is about 2e-3 off, and the
bf16 wire is run as the control that has to FAIL, so a pass means the
check can see. The reduced tree is also compared bit for bit with the
all-packed reduction's (every leaf flattened into its bucket, the
arithmetic of before PR 30). Then the same reduction is timed alone (host clock
around `--calls` calls, each closed by `block_until_ready`): what the
optimizer wrap costs with nothing to fuse into.

`--sweep`: the reduction alone, timed under other values of
`ops/fusion.DIRECT_MIN_BYTES` (the module patched from here, as a test
would) and with every leaf direct, over GPT-2-medium's tree and over
narrower trees of the same layout (widths 512 and 256, whose matrices
are 256 KiB to 4 MiB and fall on both sides of the constant); values
that split a tree alike share one timing.
A tree whose module has no such constant (a checkout from before PR 30)
is timed as it is.

Needs more than one device and says which it ran on; only a run on the
chip gives a time worth keeping. Eight virtual CPU devices
(`XLA_FLAGS=--xla_force_host_platform_device_count=8`, `--width 128`)
rehearse it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

LIMIT = 1e-5
KIB, MIB = 1 << 10, 1 << 20
EVERY_LEAF = "every_leaf"  # a sweep value: rank and size ignored


def parameter_shapes(width: int):
    """The parameter tree of GPT-2-medium (width 1024), or of the same
    24 layers at another width (16 heads kept, vocabulary 50,257)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import GPT2_MEDIUM, Transformer

    cfg = dataclasses.replace(GPT2_MEDIUM, hidden_size=width)
    return jax.eval_shape(
        Transformer(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]


def build(shapes, seed: int, n: int, compression=None):
    """(draw, reduce, check): jitted programs over the world's mesh.
    `draw()` gives the per-device trees stacked on a leading axis,
    `reduce(stacked)` the reduced tree, `check(reduced)` the largest
    error and the largest mean, a device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd

    opt = hvd.DistributedOptimizer(optax.identity(),
                                   compression=compression)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    mesh = hvd.mesh()

    offsets = np.cumsum([0] + [leaf.size for leaf in leaves])
    key = jax.random.PRNGKey(seed)

    def tree_of(flat):
        """The tree cut out of one flat draw (one draw a device: a
        draw a leaf is a program of its own a leaf to compile)."""
        return treedef.unflatten([
            flat[lo:hi].reshape(leaf.shape)
            for leaf, lo, hi in zip(leaves, offsets, offsets[1:])])

    def flat_of(device):
        return jax.random.normal(jax.random.fold_in(key, device),
                                 (int(offsets[-1]),), jnp.float32)

    def draw_local():
        own = tree_of(flat_of(jax.lax.axis_index("hvd")))
        return jax.tree_util.tree_map(lambda x: x[None], own)

    def reduce_local(stacked):
        grads = jax.tree_util.tree_map(lambda x: x[0], stacked)
        return opt.update(grads, opt.init(grads), grads)[0]

    def check_local(reduced):
        # every device's draw again here, and no collective
        mean = tree_of(sum(flat_of(d) for d in range(n)) / n)
        worst, largest = (
            jnp.max(jnp.stack(jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(fn, reduced, mean))))
            for fn in (lambda r, m: jnp.max(jnp.abs(r - m)),
                       lambda r, m: jnp.max(jnp.abs(m))))
        return worst[None], largest[None]

    def over_mesh(fn, in_specs, out_specs):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))

    return (over_mesh(draw_local, (), P("hvd")),
            over_mesh(reduce_local, (P("hvd"),), P()),
            over_mesh(check_local, (P(),), (P("hvd"), P("hvd"))))


def time_reduce(reduce, stacked, calls: int) -> float:
    """Milliseconds a call, host clock, after three calls to warm up."""
    import jax

    for _ in range(3):
        jax.block_until_ready(reduce(stacked))
    t0 = time.perf_counter()
    for _ in range(calls):  # each closed: the CPU rehearsal needs it
        jax.block_until_ready(reduce(stacked))
    return (time.perf_counter() - t0) / calls * 1e3


def bits_apart(a, b):
    """How many elements of two trees differ, and by how much at most."""
    import jax
    import jax.numpy as jnp

    pairs = list(zip(*map(jax.tree_util.tree_leaves, (a, b))))
    return (sum(jnp.sum(x != y) for x, y in pairs),
            jnp.max(jnp.stack([jnp.max(jnp.abs(x - y)) for x, y in pairs])))


@contextlib.contextmanager
def patched(value):
    """`ops/fusion` under another direct-leaf rule, for a `with`."""
    from horovod_tpu.ops import fusion

    was = fusion.DIRECT_MIN_BYTES, fusion.rides_direct
    if value == EVERY_LEAF:
        fusion.rides_direct = lambda leaf: True
    else:
        fusion.DIRECT_MIN_BYTES = value
    try:
        yield
    finally:
        fusion.DIRECT_MIN_BYTES, fusion.rides_direct = was


def tree_mib(shapes) -> float:
    import jax

    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(shapes)) / MIB


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--width", type=int, default=1024,
                    help="1024 is GPT-2-medium; smaller rehearses")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    import horovod_tpu as hvd
    from horovod_tpu.ops import fusion
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()  # before anything compiles
    hvd.init()
    n = hvd.size()
    device = jax.devices()[0]
    if n < 2:
        print("reduce_check: one device, nothing is reduced",
              file=sys.stderr)
        return 2
    lines = []

    def emit(**row):
        row = {"device": f"{n} x {device.device_kind} ({device.platform})",
               "seed": args.seed, **row}
        lines.append(row)
        print(json.dumps(row), flush=True)

    constant = getattr(fusion, "DIRECT_MIN_BYTES", None)
    shapes = parameter_shapes(args.width)
    mib = tree_mib(shapes)
    draw, reduce, check = build(shapes, args.seed, n)
    stacked = draw()
    reduced = reduce(stacked)
    worst, largest = (float(max(x)) for x in check(reduced))
    ok = worst <= LIMIT * largest
    emit(check="reduced tree against its exact mean", width=args.width,
         tree_mib=mib, direct_min_bytes=constant, worst_abs_error=worst,
         largest_mean=largest, limit=LIMIT * largest, passed=ok)

    from horovod_tpu.optim.compression import Compression

    _, reduce16, _ = build(shapes, args.seed, n, Compression.bf16)
    worst16, _ = (float(max(x)) for x in check(reduce16(stacked)))
    seen = worst16 > LIMIT * largest
    emit(check="control: the bf16 wire has to fail", width=args.width,
         worst_abs_error=worst16, limit=LIMIT * largest, failed=seen)
    del reduce16

    if constant is not None:
        # every leaf packed is the flat bucket's arithmetic: the same
        # n values an element, but the chip may add them in another
        # order in another layout (informative, not part of the verdict)
        with patched(1 << 60):
            packed = build(shapes, args.seed, n)[1](stacked)
        differing, apart = jax.jit(bits_apart)(reduced, packed)
        emit(check="bits against the all-packed reduction",
             width=args.width, elements_that_differ=int(differing),
             of=int(sum(l.size for l in jax.tree_util.tree_leaves(shapes))),
             worst_abs_difference=float(apart))
        del packed
    del reduced

    emit(timed="reduction alone", width=args.width, tree_mib=mib,
         direct_min_bytes=constant,
         ms_per_call=time_reduce(reduce, stacked, args.calls))

    if args.sweep and constant is not None:
        for width in (w for w in (1024, 512, 256) if w <= args.width):
            shapes = parameter_shapes(width)
            leaves = jax.tree_util.tree_leaves(shapes)
            stacked, timed = None, {}  # frees the last width's trees
            for value in (64 * KIB, MIB, constant, 8 * MIB, EVERY_LEAF):
                with patched(value):
                    direct = sum(map(fusion.rides_direct, leaves))
                    if direct not in timed:  # one program a split
                        draw, reduce, _ = build(shapes, args.seed, n)
                        stacked = draw() if stacked is None else stacked
                        timed[direct] = time_reduce(reduce, stacked,
                                                    args.calls)
                emit(timed="reduction alone, sweep", width=width,
                     tree_mib=tree_mib(shapes), direct_min_bytes=value,
                     direct_leaves=direct, ms_per_call=timed[direct])

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.writelines(json.dumps(row) + "\n" for row in lines)
    return 0 if ok and seen else 1


if __name__ == "__main__":
    sys.exit(main())
