#!/usr/bin/env python3
"""Time the pass between attention's projections and the flash kernels
alone on the chip (`ops/attention_prep.qk_prep`), forward and backward,
beside the jitted array passes it replaces.

    python scripts/attention_prep_sweep.py [--shapes sdar,k4,llama]
        [--rows 128,256,512] [--heads 1,4,all] [--calls 20]
        [--out chiprun_out/attention_prep_sweep.jsonl]

For each shape (q ``[B, T, H, D]`` with k ``[B, T, KH, D]``, bf16: the
one call a layer makes in `sdar_bd_s4096`, the same with q as narrow as
its k, and Llama's rope without q/k norms) the array passes are
`RMSNorm` → `apply_rope` → `transpose(0, 2, 1, 3)` of q and of k as
`models/transformer.Attention` writes them, jitted together, and their
`jax.vjp` at cotangents in the kernels' layout. Both paths are handed q
and k as the step's projection products write them (compiled for the
chip: ``[B, T, H, D]`` stored head-major, which is the kernels'
``[B, H, T, D]`` transposed in name only) and return d(raw q), d(raw k)
the same way, so neither pays for a copy the step does not make. The
pass is compiled with no compiler option for each `rows` (positions a
program takes, all heads of them) and `heads` (heads an iteration of the
kernel's loop handles; `all`: no loop) and both are traced over `--calls` calls: `ms` is everything the call runs on the
device, `kernel_ms` the Mosaic call alone, `gb_s` the bytes the work
needs (each array read once and written once: `least_mib`) over `ms`.
The pass's results are held against the array passes': the share of
elements that differ and the largest difference. The line marked
`chosen` is what the module itself picks. Last comes the table
`PERF.md` quotes.

Exits non-zero without a TPU: a time from anywhere else is not a
device time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# name -> (B, T, H, KH, D, q/k norms, rope)
SHAPES = {
    "sdar": (2, 8192, 32, 4, 128, True, True),
    "k4": (2, 8192, 4, 4, 128, True, True),
    "llama": (2, 8192, 32, 32, 128, False, True),
}
EPS, MAX_LEN, THETA = 1e-6, 8192, 1e6


def inputs(shape):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import rope_frequencies

    b, t, h, kh, d, norm, rope = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    # stored head-major, as the step's products write them
    q = jax.random.normal(keys[0], (b, h, t, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, kh, t, d), jnp.bfloat16)
    scales = tuple(1 + 0.1 * jax.random.normal(key, (d,), jnp.float32)
                   for key in keys[2:4]) if norm else (None, None)
    gq = jax.random.normal(keys[4], (b, h, t, d), jnp.bfloat16)
    gk = jax.random.normal(keys[5], (b, kh, t, d), jnp.bfloat16)
    # the cell's positions: [noisy ; clean] halves repeat them
    positions = jnp.broadcast_to(jnp.arange(t)[None] % (t // 2), (b, t))
    tables = rope_frequencies(d, MAX_LEN, THETA) if rope else None
    return (q, k, *scales), (gq, gk), positions, tables


def array_passes(tables, positions):
    """What `Attention` runs without the pass, as a function of (q, k,
    q_scale, k_scale)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import apply_rope

    def one(x, scale):
        if scale is not None:
            xf = x.astype(jnp.float32)
            y = xf * jax.lax.rsqrt(
                jnp.mean(xf * xf, axis=-1, keepdims=True) + EPS)
            x = (y * scale).astype(x.dtype)
        if tables is not None:
            x = apply_rope(x, *tables, positions)
        return x.transpose(0, 2, 1, 3)

    return lambda q, k, qs, ks: (one(q, qs), one(k, ks))


def the_pass(tables, positions, rows):
    from horovod_tpu.ops import attention_prep as ap

    def fn(q, k, qs, ks):
        rope = ap.rope_rows(*tables, positions) if tables else None
        return ap.qk_prep(q, k, qs, ks, rope, EPS, rows)

    return fn


def head_major(fn):
    """`fn` of (q, k, scales) in the model's ``[B, T, H, D]``, as a
    function of q and k stored ``[B, H, T, D]``."""
    return lambda q, k, qs, ks: fn(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), qs, ks)


def both_directions(fn):
    """(forward, backward) of `fn` as jitted functions: the backward is
    the `jax.vjp` alone, its forward's results unused."""
    import jax

    def backward(args, grads):
        live = [i for i, a in enumerate(args) if a is not None]

        def of_live(*given):
            full = list(args)
            for i, a in zip(live, given):
                full[i] = a
            return fn(*full)

        return jax.vjp(of_live, *(args[i] for i in live))[1](grads)

    return jax.jit(lambda args: fn(*args)), jax.jit(backward)


def measure(fn, args, calls):
    """(ms a call of everything the call runs on the device, ms of its
    Mosaic calls alone, the results as float32 numpy arrays)."""
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from benchmarks import trace

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
    return (whole_ns / calls / 1e6, kernel_ns / calls / 1e6,
            [np.asarray(x, dtype=np.float32)
             for x in jax.tree_util.tree_leaves(result)])


def differences(mine, theirs):
    """(share of elements that differ, largest difference relative to
    the largest value) over all results."""
    import numpy as np

    differ = sum(int(np.sum(a != b)) for a, b in zip(mine, theirs))
    size = sum(a.size for a in mine)
    worst = max(float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))
                for a, b in zip(mine, theirs))
    return differ / size, worst


def main(argv=None):
    ap_ = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap_.add_argument("--shapes", default=",".join(SHAPES))
    ap_.add_argument("--rows", default="128,256,512")
    ap_.add_argument("--heads", default="1,4,all")
    ap_.add_argument("--calls", type=int, default=20)
    ap_.add_argument("--out", default=os.path.join(
        "chiprun_out", "attention_prep_sweep.jsonl"))
    args = ap_.parse_args(argv)

    import jax

    from horovod_tpu.ops import attention_prep as ap

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU here (platform {device.platform}): nothing measured")
        return 1
    all_heads = 10 ** 6  # more than any shape has: the loop is not made
    sweep = sorted({(int(r), all_heads if h == "all" else int(h))
                    for r in args.rows.split(",")
                    for h in args.heads.split(",")} | {(ap._ROWS, ap._HEADS)})
    chosen_heads = ap._HEADS
    table = []
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as out:

        def emit(**line):
            line["device"] = device.device_kind
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")

        for name in args.shapes.split(","):
            shape = SHAPES[name]
            b, t, h, kh, d = shape[:5]
            primals, grads, positions, tables = inputs(shape)
            # each of q and k read once and written once; backward, the
            # cotangent and the raw result read and one written
            moved = b * t * (h + kh) * d * 2
            least = {"fwd": 2 * moved, "bwd": 3 * moved}
            theirs = {}
            for direction, fn in zip(("fwd", "bwd"), both_directions(
                    head_major(array_passes(tables, positions)))):
                call = (primals,) if direction == "fwd" else (primals, grads)
                ms, _, results = measure(fn, call, args.calls)
                theirs[direction] = (ms, results)
                emit(shape=name, direction=direction, path="array passes",
                     ms=ms, least_mib=least[direction] / 2**20,
                     gb_s=least[direction] / ms / 1e6)
            for rows, heads in sweep:
                chosen = (rows, heads) == (ap._ROWS, chosen_heads)
                ap._HEADS = heads
                fns = both_directions(head_major(
                    the_pass(tables, positions, rows)))
                for direction, fn in zip(("fwd", "bwd"), fns):
                    call = (primals,) if direction == "fwd" \
                        else (primals, grads)
                    try:
                        ms, kernel_ms, results = measure(fn, call,
                                                         args.calls)
                    except Exception as e:  # Mosaic's refusal, printed
                        emit(shape=name, direction=direction, rows=rows,
                             heads=heads, refused=str(e)[-220:])
                        continue
                    share, worst = differences(results,
                                               theirs[direction][1])
                    emit(shape=name, direction=direction, path="one pass",
                         rows=rows, heads=heads, chosen=chosen, ms=ms,
                         kernel_ms=kernel_ms,
                         gb_s=least[direction] / ms / 1e6,
                         times_faster=theirs[direction][0] / ms,
                         differ_share=share, worst_relative=worst)
                    table.append((name, direction, rows,
                                  "all" if heads == all_heads else heads, ms,
                                  kernel_ms, theirs[direction][0],
                                  least[direction] / ms / 1e6,
                                  "chosen" if chosen else ""))
    ap._HEADS = chosen_heads
    print(f"{'shape':7}{'dir':>4}{'rows':>6}{'heads':>6}{'ms':>9}"
          f"{'kernel':>9}{'arrays':>9}{'x':>7}{'GB/s':>8}")
    for name, direction, rows, heads, ms, kernel_ms, theirs_ms, gb_s, mark \
            in table:
        print(f"{name:7}{direction:>4}{rows:>6}{heads:>6}{ms:>9.3f}"
              f"{kernel_ms:>9.3f}{theirs_ms:>9.3f}{theirs_ms / ms:>7.2f}"
              f"{gb_s:>8.1f}  {mark}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
