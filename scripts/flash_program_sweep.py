#!/usr/bin/env python3
"""Time the three flash-attention kernels alone on the chip, over the
number of (batch, head) instances a program handles or over which tiles
pay for the mask.

    python scripts/flash_program_sweep.py [--sweep instances|tiles]
        [--shapes s128,s512,gpt2] [--parent .parent]
        [--out chiprun_out/flash_program_sweep.jsonl]

`--sweep instances` (the default): for each shape (the benchmark
cells' attention calls and a few more: head width 64, bf16) and each
number of instances `g`, the chooser of
`ops/pallas_attention.py` is replaced by `g`, the forward, dq and dkv
calls are compiled apart (a backward whose other result is unused is
dropped by the compiler) with no compiler option, and each is traced
over `--calls` calls: `kernel_ms` is the Mosaic call's device time a
call, `whole_ms` everything the call runs (the copies XLA puts around a
kernel called alone, the backward's Δ). Every `g`'s results are compared
bit for bit with `g = 1`'s, and with `--parent` (a checkout of another
commit) with that commit's, whose kernels are timed the same way. A `g`
the compiler refuses (VMEM) is printed as refused: that is where the
budget of `_instances_per_program` has to stay under. The line marked
`chosen` is what the chooser itself picks.

`--sweep tiles`: for each shape, at the instances the chooser picks,
the kernels are timed once for each of `TILES`, the kernels' module
patched: `every_tile_masked` (every tile that runs is masked, in one
loop, and the forward selects twice: the kernels of before PR 28 but
for how the mask is built), `one_select` (the same without the
forward's second select where no row can be empty), `classes_in_loops`
(`_tile_ranges` classes the tiles, each class in a loop of its own) and
`classes` (the module as it is: a class that is one tile in every
program runs without a loop). Results are compared bit for bit with
the first row's and with `--parent`'s.

Exits non-zero without a TPU: a time from anywhere else is not a kernel
time.
"""

from __future__ import annotations

import argparse
import importlib.util
import inspect
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# name -> (B, H, T, causal, instances a program to try)
SHAPES = {
    "s128": (104, 16, 128, False, (1, 2, 4, 8, 16, 32)),
    "s512": (26, 16, 512, False, (1, 2, 4, 8)),
    "gpt2": (16, 16, 1024, True, (1, 2, 4)),
    "n1024": (16, 16, 1024, False, (1, 2, 4)),
    "c512": (26, 16, 512, True, (1, 2, 4, 8)),
    "c128": (104, 16, 128, True, (1, 8, 16)),
    "s256": (52, 16, 256, False, (1, 4, 8, 16)),
    "c2048": (8, 16, 2048, True, (1, 2)),
}
HEAD, BLOCK = 64, 512


def patched(pa, **values):
    """Set attributes of module `pa`; returns the undo."""
    was = {name: getattr(pa, name) for name in values}
    for name, value in values.items():
        setattr(pa, name, value)
    return lambda: [setattr(pa, name, value) for name, value in was.items()]


def in_loops(pa):
    """Every range of tiles in a loop, also one that is one tile in
    every program (the loops of before PR 28)."""
    trips = pa._trips
    return patched(pa, _trips=lambda every: tuple(
        lengths if lengths == {0} else None for lengths in trips(every)))


def one_masked_loop(pa, second_select=False):
    """Every tile that runs masked, in one loop; `second_select`: the
    forward selects twice in every tile (with it, the kernels of before
    PR 28 but for how the mask is built)."""
    ranges = pa._tile_ranges

    def all_masked(*a, **kw):
        (lo, _, _), (_, hi, _) = ranges(*a, **kw)
        return [(lo, hi, True), (hi, hi, False)]

    undo = [in_loops(pa), patched(pa, _tile_ranges=all_masked)]
    if second_select:
        undo.append(patched(pa, _rows_may_see_no_key=lambda **geometry: True))
    return lambda: [u() for u in reversed(undo)]


# name -> patch of the kernels' module that returns its undo
TILES = {
    "every_tile_masked": lambda pa: one_masked_loop(pa, second_select=True),
    "one_select": one_masked_loop,
    "classes_in_loops": in_loops,
    "classes": lambda pa: lambda: None,
}


def load_parent(checkout):
    """The other checkout's kernels, as a module beside this one's."""
    spec = importlib.util.spec_from_file_location(
        "horovod_tpu.ops.pallas_attention_parent",
        os.path.join(checkout, "horovod_tpu", "ops", "pallas_attention.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def kernels(pa, shape):
    """The three calls of module `pa` as jitted functions of random
    inputs, and those inputs."""
    import jax
    import jax.numpy as jnp

    b, h, t, causal, _ = shape
    block = min(t, BLOCK)
    static = (causal, HEAD ** -0.5, 0, 0, block, block)
    if "diffusion" in inspect.signature(pa._flash_bwd).parameters:
        static += (0,)  # since PR 33: no block-diffusion mask
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(key, (b, h, t, HEAD), jnp.bfloat16)
                   for key in keys)

    def backward(q, k, v, out, lse, do):
        return pa._flash_bwd(*static, (q, k, v, out, lse), do)

    fwd = jax.jit(lambda q, k, v: pa._flash_fwd(q, k, v, *static)[1][3:])
    out, lse = fwd(q, k, v)
    return {
        "fwd": (fwd, (q, k, v)),
        "dq": (jax.jit(lambda *a: backward(*a)[0]), (q, k, v, out, lse, do)),
        "dkv": (jax.jit(lambda *a: backward(*a)[1:]),
                (q, k, v, out, lse, do)),
    }


def measure(pa, shape, calls):
    """{kernel: (kernel ms a call, whole ms a call, results as flat
    numpy arrays)}: device times from a profiler trace of `calls`
    calls, the kernel's being its Mosaic custom call alone and the
    whole everything the call runs on the device (the copies XLA puts
    around a kernel called alone, the backward's Δ)."""
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from benchmarks import trace

    found = {}
    for name, (fn, args) in kernels(pa, shape).items():
        result = jax.block_until_ready(fn(*args))
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            for _ in range(calls):
                last = fn(*args)
            jax.block_until_ready(last)
            jax.profiler.stop_trace()
            planes = ProfileData.from_file(trace.find_xplane(tmp)).planes
        lines = {line.name: list(line.events) for plane in planes
                 if trace.DEVICE_PLANE.match(plane.name)
                 for line in plane.lines}
        kernel_ns = sum(e.duration_ns for e in lines[trace.OP_LINE]
                        if trace.opcode_of(e.name) == "custom-call")
        whole_ns = sum(e.duration_ns for e in lines[trace.MODULE_LINE])
        found[name] = (
            kernel_ns / calls / 1e6, whole_ns / calls / 1e6,
            [np.asarray(x).reshape(-1).view(np.uint16)
             if x.dtype.itemsize == 2 else np.asarray(x).reshape(-1)
             for x in jax.tree_util.tree_leaves(result)])
    return found


def same_bits(a, b):
    import numpy as np

    return all(np.array_equal(x, y) for x, y in zip(a, b))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sweep", choices=("instances", "tiles"),
                    default="instances")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--parent", default=None)
    ap.add_argument("--out", default=os.path.join(
        "chiprun_out", "flash_program_sweep.jsonl"))
    args = ap.parse_args(argv)

    import jax

    from horovod_tpu.ops import pallas_attention as pa

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU here (platform {device.platform}): nothing measured")
        return 1
    from horovod_tpu.utils import metrics

    # a parent's kernels call this commit's recorder, with its own
    # arguments; nothing reads the gauges here
    metrics.record_flash_programs = lambda *a, **kw: None
    parent = load_parent(args.parent) if args.parent else None
    chooser = pa._instances_per_program
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as out:

        def emit(**line):
            line["device"] = device.device_kind
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")

        for name in args.shapes.split(","):
            shape = SHAPES[name]
            b, h, t, causal, sweep = shape
            block = min(t, BLOCK)
            chosen = {k: chooser(k, b, h, block, t, HEAD, 2)
                      for k in ("fwd", "dq", "dkv")}
            before = measure(parent, shape, args.calls) \
                if parent else {}
            for k, (ms, whole, _) in before.items():
                emit(shape=name, kernel=k, commit="parent", kernel_ms=ms,
                     whole_ms=whole)
            if args.sweep == "tiles":
                first = None
                for tiles, patch in TILES.items():
                    undo = patch(pa)
                    pa._flash_fwd.clear_cache()  # traced once a shape
                    pa._flash_bwd.clear_cache()
                    try:
                        found = measure(pa, shape, args.calls)
                    except Exception as e:  # Mosaic's refusal, printed
                        emit(shape=name, tiles=tiles, refused=str(e)[-220:])
                        continue
                    finally:
                        undo()
                    first = first or found
                    for k, (ms, whole, bits) in found.items():
                        emit(shape=name, kernel=k, tiles=tiles,
                             g=chosen[k][0] * chosen[k][1], kernel_ms=ms,
                             whole_ms=whole,
                             same_bits_as_first=same_bits(bits, first[k][2]),
                             **({"same_bits_as_parent":
                                 same_bits(bits, before[k][2])}
                                if before else {}))
                    emit(shape=name, kernel="all", tiles=tiles,
                         kernel_ms=sum(ms for ms, _, _ in found.values()))
                pa._flash_fwd.clear_cache()
                pa._flash_bwd.clear_cache()
                continue
            one = None
            for g in sweep:
                pa._instances_per_program = \
                    lambda *a, g=g, h=h: (max(g // h, 1), min(g, h))
                pa._flash_fwd.clear_cache()  # traced once a shape
                pa._flash_bwd.clear_cache()
                try:
                    found = measure(pa, shape, args.calls)
                except Exception as e:  # Mosaic's refusal, printed
                    emit(shape=name, g=g, refused=str(e)[-220:])
                    continue
                finally:
                    pa._instances_per_program = chooser
                one = one or found
                for k, (ms, whole, bits) in found.items():
                    emit(shape=name, kernel=k, g=g, kernel_ms=ms, whole_ms=whole,
                         programs=b * h // g * (t // block),
                         chosen=chosen[k][0] * chosen[k][1] == g,
                         same_bits_as_g1=same_bits(bits, one[k][2]),
                         **({"same_bits_as_parent":
                             same_bits(bits, before[k][2])}
                            if before else {}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
