#!/usr/bin/env python3
"""Time the flash-attention kernels alone on the chip, the forward and
the one backward call, over the number of (batch, head) instances a
program handles or over which tiles pay for the mask.

    python scripts/flash_program_sweep.py [--sweep instances|tiles]
        [--shapes s128,s512,gpt2,sdar] [--kernels fwd,bwd]
        [--parent .parent] [--out chiprun_out/flash_program_sweep.jsonl]

`--sweep instances` (the default): for each shape (the benchmark
cells' attention calls first, then a few more; bf16) and each number
of instances `g`, the chooser of `ops/pallas_attention.py` is replaced
by `g` for the kernels of `--kernels` (the others keep what the chooser
picks), the forward and the backward call are compiled apart with no
compiler option, and each is traced over `--calls` calls: `kernel_ms`
is the Mosaic call's device time a call, `whole_ms` everything the call
runs (the copies XLA puts around a kernel called alone, the backward's
Δ, the sum over a group's dk and dv). Every `g`'s results are compared
bit for bit with `g = 1`'s, and with `--parent` (a checkout of another
commit) with that commit's, whose kernels are timed the same way: a
commit from before PR 34 has two backward kernels, dq and dkv, each
compiled apart (a backward whose other result is unused is dropped by
the compiler), and the one backward call is held to both's bits and
beside the sum of their times. A `g` the compiler refuses (VMEM) is
printed as refused. The line marked `chosen` is what the chooser itself
picks. Last comes the table the chooser's comment quotes: the backward
by shape and `g`, ms a call, beside the parent's.

`--sweep tiles`: for each shape, at the instances the chooser picks,
the kernels are timed once for each of `TILES`, the kernels' module
patched: `every_tile_masked` (every tile that runs is masked, in one
loop, and the forward selects twice: the kernels of before PR 28 but
for how the mask is built), `one_select` (the same without the
forward's second select where no row can be empty), `classes_in_loops`
(`_tile_ranges` classes the tiles, each class in a loop of its own) and
`classes` (the module as it is: a class that is one tile in every
program runs without a loop). Results are compared bit for bit with
the first row's and with `--parent`'s. Not for the block-diffusion
shape, whose ranges are three or four.

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

# name -> (B, H, T, causal, instances a program to try[, key-value
# heads, head width, block-diffusion block]): T is the positions a call
# runs, 2 x 4096 under the block-diffusion mask. The first four are the
# benchmark cells' attention calls.
SHAPES = {
    "s128": (104, 16, 128, False, (1, 2, 4, 8, 16, 32)),
    "s512": (26, 16, 512, False, (1, 2, 4, 8)),
    "gpt2": (16, 16, 1024, True, (1, 2, 4)),
    "sdar": (2, 32, 8192, False, (1, 2), 4, 128, 4),
    "n1024": (16, 16, 1024, False, (1, 2, 4)),
    "c512": (26, 16, 512, True, (1, 2, 4, 8)),
    "c128": (104, 16, 128, True, (1, 8, 16)),
    "s256": (52, 16, 256, False, (1, 4, 8, 16)),
    "c2048": (8, 16, 2048, True, (1, 2)),
}
HEAD, BLOCK = 64, 512


def geometry(shape):
    """(B, H, T, causal, sweep, key-value heads, head width, diffusion
    block) of a row of `SHAPES`."""
    b, h, t, causal, sweep, *rest = shape
    return (b, h, t, causal, sweep, *(rest or (h, HEAD, 0)))


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
    """The calls of module `pa` as jitted functions of random inputs,
    and those inputs: the forward and the one backward call, or the two
    backward calls of a commit that has two (each compiled apart: a
    backward whose other result is unused is dropped by the compiler)."""
    import jax
    import jax.numpy as jnp

    b, h, t, causal, _, kv_heads, d, diffusion = geometry(shape)
    block = min(t // 2 if diffusion else t, BLOCK)
    static = (causal, d ** -0.5, 0, 0, block, block)
    if "diffusion" in inspect.signature(pa._flash_bwd).parameters:
        static += (diffusion,)  # since PR 33
    elif diffusion:
        raise ValueError("this commit has no block-diffusion mask")
    if "window" in inspect.signature(pa._flash_bwd).parameters:
        static += (0,)  # since PR 48: no window at these shapes
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, do = (jax.random.normal(key, (b, h, t, d), jnp.bfloat16)
             for key in keys[:2])
    k, v = (jax.random.normal(key, (b, kv_heads, t, d), jnp.bfloat16)
            for key in keys[2:])

    def backward(q, k, v, out, lse, do):
        return pa._flash_bwd(*static, (q, k, v, out, lse), do)

    fwd = jax.jit(lambda q, k, v: pa._flash_fwd(q, k, v, *static)[1][3:])
    out, lse = fwd(q, k, v)
    args = (q, k, v, out, lse, do)
    found = {"fwd": (fwd, (q, k, v))}
    if hasattr(pa, "_flash_bwd_dq_kernel"):  # before PR 34
        found["dq"] = (jax.jit(lambda *a: backward(*a)[0]), args)
        found["dkv"] = (jax.jit(lambda *a: backward(*a)[1:]), args)
    else:
        found["bwd"] = (jax.jit(backward), args)
    return found


def backward_of(found):
    """(kernel ms, (dq, dk, dv) as flat arrays) of the whole backward in
    what `measure` found, whichever kernels the commit has."""
    if "bwd" in found:
        return found["bwd"][0], found["bwd"][2]
    return (found["dq"][0] + found["dkv"][0],
            found["dq"][2] + found["dkv"][2])


def measure(pa, shape, calls, only=None):
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
        if only and ("fwd" if name == "fwd" else "bwd") not in only:
            continue
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
    ap.add_argument("--kernels", default="fwd,bwd",
                    help="which of fwd,bwd to time and to sweep")
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

    # a parent's kernels set their gauges through this commit's
    # `utils/metrics` (their relative import lands here), with their own
    # arguments: `trace_gauge` since PR 52, `record_flash_programs`
    # before it; nothing reads the gauges here
    metrics.trace_gauge = metrics.record_flash_programs = \
        lambda *a, **kw: None
    parent = load_parent(args.parent) if args.parent else None
    chooser = pa._instances_per_program
    only = tuple(args.kernels.split(","))
    table = []  # the backward's rows, printed last
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as out:

        def emit(**line):
            line["device"] = device.device_kind
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")

        def against_parent(before, k, bits):
            """Whether `bits` of kernel `k` are the parent's, which may
            have two backward kernels where this commit has one."""
            if not before:
                return {}
            theirs = backward_of(before)[1] if k == "bwd" else before[k][2]
            return {"same_bits_as_parent": same_bits(bits, theirs)}

        for name in args.shapes.split(","):
            shape = SHAPES[name]
            b, h, t, causal, sweep, kv_heads, d, diffusion = geometry(shape)
            block = min(t // 2 if diffusion else t, BLOCK)
            chosen = {k: chooser(k, b, h, block, t, d, 2, h // kv_heads)
                      for k in only}
            before = measure(parent, shape, args.calls, only) \
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
                        found = measure(pa, shape, args.calls, only)
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
                             **against_parent(before, k, bits))
                    emit(shape=name, kernel="all", tiles=tiles,
                         kernel_ms=sum(ms for ms, _, _ in found.values()))
                pa._flash_fwd.clear_cache()
                pa._flash_bwd.clear_cache()
                continue
            one = None
            for g in sweep:
                pa._instances_per_program = \
                    lambda k, *a, g=g, h=h, **kw: (
                        (max(g // h, 1), min(g, h)) if k in only
                        else chooser(k, *a, **kw))
                pa._flash_fwd.clear_cache()  # traced once a shape
                pa._flash_bwd.clear_cache()
                try:
                    found = measure(pa, shape, args.calls, only)
                except Exception as e:  # Mosaic's refusal, printed
                    emit(shape=name, g=g, refused=str(e)[-220:])
                    table.append((name, g, None, None, ""))
                    continue
                finally:
                    pa._instances_per_program = chooser
                one = one or found
                for k, (ms, whole, bits) in found.items():
                    is_chosen = chosen[k][0] * chosen[k][1] == g
                    emit(shape=name, kernel=k, g=g, kernel_ms=ms,
                         whole_ms=whole, chosen=is_chosen,
                         same_bits_as_g1=same_bits(bits, one[k][2]),
                         **against_parent(before, k, bits))
                    if k == "bwd":
                        table.append((
                            name, g, ms,
                            backward_of(before)[0] if before else None,
                            "chosen" if is_chosen else ""))
    # the table the chooser's comment quotes: the one backward call by
    # instances a program, beside the parent's two where it has two
    if table:
        print(f"{'shape':8}{'g':>4}{'bwd ms':>10}{'parent ms':>11}"
              f"{'ratio':>8}")
    for name, g, ms, theirs, mark in table:
        if ms is None:
            print(f"{name:8}{g:>4}   refused")
            continue
        print(f"{name:8}{g:>4}{ms:>10.3f}"
              + (f"{theirs:>11.3f}{ms / theirs:>8.3f}" if theirs
                 else f"{'':>19}") + f"  {mark}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
