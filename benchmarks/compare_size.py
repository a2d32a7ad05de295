#!/usr/bin/env python3
"""What the comparison with the reference needs of a chip, for one
cell: the reference check's ``compare`` program (the system's gradient,
the reference's and their difference, two sequences) and one block of
the reference's loss over the global batch, each compiled, asked for
its ``memory_analysis()`` and, on a chip, run and timed.

    python3 benchmarks/compare_size.py --root <root> <cell> [--sequences n]

On a TPU it compiles for the attached chip and runs both programs
(under a ``timeout``: a program that does not fit has hung this runtime
in shutdown, PERF.md section 6, PR 21). Anywhere else
(``JAX_PLATFORMS=cpu``) it compiles for a described ``v5e:2x2`` chip
and runs nothing: the compiler's refusal and its byte counts cost no
chip time, and are not a chip run. Run by hand, by a PR that sizes a
configuration; nothing reads its output.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmarks import harness  # noqa: E402
from benchmarks.rehearse_compile import mosaic_kernels  # noqa: E402


def memory_of(compiled) -> dict:
    """The compiler's byte counts of one program, in GiB, and what the
    chip must hold to run it (as ``step_hbm_gib`` is reckoned)."""
    mem = compiled.memory_analysis()
    parts = {"argument": mem.argument_size_in_bytes,
             "output": mem.output_size_in_bytes,
             "alias": mem.alias_size_in_bytes,
             "temp": mem.temp_size_in_bytes,
             "code": mem.generated_code_size_in_bytes}
    needs = sum(parts.values()) - 2 * parts["alias"]  # aliased: once
    return {**{k: v / harness.GIB for k, v in parts.items()},
            "needs_gib": needs / harness.GIB}


def timed(compiled, *args) -> tuple:
    """(result, seconds of the first call, of the second)."""
    import jax

    seconds = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        seconds.append(time.perf_counter() - t0)
    return out, *seconds


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cell")
    p.add_argument("--root", default=harness.ROOT)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--sequences", type=int, default=2,
                   help="sequences in the compare program (the "
                        "reference check takes 2)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.jobs import dp_train

    found = harness.load_cell(args.cell, args.root)
    traffic, sizes = found["traffic"], dict(found["config"]["model"])
    reference = harness.load_reference(found["config"]["family"], args.root)
    on_chip = jax.default_backend() == "tpu"
    if on_chip:
        device = jax.devices()[0]
    else:
        from jax.experimental import topologies
        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    mesh = Mesh(np.array([device]), ("hvd",))
    rep = NamedSharding(mesh, P())
    print(f"{args.cell}: {device.device_kind}, "
          + ("attached" if on_chip else "described, not attached: "
             "nothing runs and no number below is a chip run"),
          flush=True)

    _, model, plain = dp_train.make_model(sizes, traffic)
    takes_choices = getattr(reference, "TAKES_CHOICES", False)
    loss_fn = dp_train.make_loss_fn(model, traffic,
                                    with_choices=takes_choices)
    init = jax.jit(plain.init, out_shardings=rep)
    key = jax.random.PRNGKey(args.seed)
    one = jnp.zeros((1, traffic["seq_len"]), jnp.int32)
    if on_chip:
        params = jax.block_until_ready(init(key, one)["params"])
    else:
        params = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
            jax.eval_shape(plain.init, key, one)["params"])
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(params))
    print(f"parameters {count} = {4 * count / harness.GIB:.3f} GiB in "
          f"float32", flush=True)

    def placed(host):
        if on_chip:
            return tuple(jax.device_put(a, rep) for a in host)
        return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)
                     for a in host)

    result = {"cell": args.cell, "device": device.device_kind,
              "attached": on_chip, "parameters": count}
    compare, _ = dp_train.make_compare(reference, loss_fn, sizes, traffic)
    batch = placed(dp_train.make_batch(
        sizes, traffic, args.sequences, args.seed + 1))
    block_fn, blk = dp_train.reference_block(
        reference, sizes, traffic, mesh)
    part = placed(dp_train.make_batch(sizes, traffic, blk, args.seed))
    programs = (
        (f"compare_{args.sequences}_sequences", compare, batch),
        (f"reference_block_{blk}_sequences", block_fn, part))
    for name, program, arrays in programs:
        t0 = time.perf_counter()
        with contextlib.nullcontext() if on_chip else mosaic_kernels():
            compiled = program.lower(params, *arrays).compile()
        entry = {"compile_s": time.perf_counter() - t0,
                 **memory_of(compiled)}
        if on_chip:
            out, entry["first_call_s"], entry["second_call_s"] = timed(
                compiled, params, *arrays)
            entry["returned"] = [
                float(x) for x in jax.tree_util.tree_leaves(out)]
            entry["memory_stats_peak_gib"] = (
                device.memory_stats() or {}).get(
                    "peak_bytes_in_use", 0) / harness.GIB
        result[name] = entry
        print(name, json.dumps(entry), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
