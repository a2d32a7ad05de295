#!/usr/bin/env python3
"""Time the state-space recurrence alone on the chip
(`models/mamba.ssd_scan`), forward and forward + backward, as the two
kernels of `ops/ssd_scan.py` beside the plain chunked form they stand
for.

    python scripts/ssd_scan_sweep.py [--heads 8,16,32] [--buffers 1,2]
        [--calls 10] [--out chiprun_out/ssd_scan_sweep.jsonl]

The shape is `granite_h_lm`'s layer: one sequence of 8,192 positions,
64 heads of 64, state 128, one group, chunks of 256, bf16, with dt and
a in the ranges the model draws them from. The plain form is
`ssd_scan` with the kernels refused (`_scan_chunks`, differentiated by
JAX), the control; the kernels are swept over the heads a program takes
and the copies of an x block its pipeline keeps. Forward is y alone;
forward + backward is y and the gradients by x, dt, a, B, C, D at a
float32 cotangent, one jitted call. Each is traced over `--calls`
calls: `ms` is everything the call runs on the device, `kernel_ms` its
Mosaic calls alone. The kernels' results are held against the plain
form's by the norm of the difference over the norm. The line marked
`chosen` is what the module itself picks; last come the table and the
gate `PERF.md` quotes (the kernels at least twice as fast as the plain
form, forward and forward + backward).

Exits non-zero without a TPU: a time from anywhere else is not a
device time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# B, T, H, P, G, N, chunk
SHAPE = (1, 8192, 64, 64, 1, 128, 256)


def inputs(shape=SHAPE, dtype=None):
    """(x, dt, a, b, c, d) and a cotangent for y."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import mamba

    dtype = dtype or jnp.bfloat16
    bsz, t, h, p, g, n, _ = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    x = jax.random.normal(keys[0], (bsz, t, h * p), jnp.float32).astype(
        dtype)
    lo, hi = (jnp.log(v) for v in mamba.DT_INIT_RANGE)
    dt = jnp.exp(jax.random.uniform(keys[1], (bsz, t, h), jnp.float32,
                                    lo, hi))
    a = -jnp.arange(1, h + 1, dtype=jnp.float32)
    b, c = (jax.random.normal(key, (bsz, t, g, n), jnp.float32).astype(dtype)
            for key in keys[2:4])
    d = 1 + 0.1 * jax.random.normal(keys[4], (h,), jnp.float32)
    return (x, dt, a, b, c, d), jax.random.normal(
        keys[5], (bsz, t, h * p), jnp.float32)


def both_directions(shape=SHAPE):
    """(forward, forward + backward) of `ssd_scan` as the mixer calls
    it, each jitted anew (what the module's constants say when they are
    first called): x comes `[B, T, H·P]`, as the convolution leaves it,
    and y goes back so, as the gate reads it; the view by heads between
    is the mixer's, and whichever form runs lays its memory out as it
    does in the step."""
    import jax

    from horovod_tpu.models import mamba

    bsz, t, h, p, _, _, chunk = shape

    def scan(x, *rest):
        return mamba.ssd_scan(x.reshape(bsz, t, h, p), *rest,
                              chunk).reshape(bsz, t, h * p)

    def with_backward(args, ct):
        y, vjp = jax.vjp(scan, *args)
        return y, vjp(ct)

    return jax.jit(lambda args: scan(*args)), jax.jit(with_backward)


def worst_difference(mine, theirs):
    """The largest, over the results, of |mine - theirs| / |theirs| by
    the norm."""
    import numpy as np

    return max(float(np.linalg.norm((a - b).astype(np.float64))
                     / max(np.linalg.norm(b.astype(np.float64)), 1e-30))
               for a, b in zip(mine, theirs))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heads", default="8,16,32")
    ap.add_argument("--buffers", default="1,2")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--out", default=os.path.join(
        "chiprun_out", "ssd_scan_sweep.jsonl"))
    args = ap.parse_args(argv)

    import jax

    from horovod_tpu.ops import ssd_scan as kernels
    # (ms a call of everything it runs on the device, ms of its Mosaic
    # calls alone, the results as float32 numpy arrays), from a trace
    from scripts.attention_prep_sweep import measure

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU here (platform {device.platform}): nothing measured")
        return 1
    operands, ct = inputs()
    chosen = (kernels._HEADS_BLOCK, kernels._BUFFERS)
    sweep = sorted({(int(h), int(n)) for h in args.heads.split(",")
                    for n in args.buffers.split(",")} | {chosen})
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    table = []
    with open(args.out, "w") as out:

        def emit(**line):
            line["device"] = device.device_kind
            out.write(json.dumps(line) + "\n")
            out.flush()
            print(json.dumps(line), flush=True)
            table.append(line)

        def run(form, **labels):
            forward, with_backward = both_directions()
            f_ms, f_kernel, f_out = measure(forward, (operands,), args.calls)
            b_ms, b_kernel, b_out = measure(
                with_backward, (operands, ct), args.calls)
            return dict(form=form, forward_ms=f_ms,
                        forward_kernel_ms=f_kernel, both_ms=b_ms,
                        both_kernel_ms=b_kernel, **labels), f_out + b_out

        takes = kernels.supports
        kernels.supports = lambda *_: False
        try:
            plain, plain_out = run("plain")
        finally:
            kernels.supports = takes
        emit(**plain)
        for heads, buffers in sweep:
            kernels._HEADS_BLOCK, kernels._BUFFERS = heads, buffers
            labels = dict(heads=heads, buffers=buffers)
            try:
                line, got = run("kernels", **labels,
                                chosen=(heads, buffers) == chosen)
            except Exception as e:  # a Mosaic refusal is a finding
                emit(form="kernels", **labels,
                     refused=str(e).splitlines()[0][:300])
                continue
            finally:
                kernels._HEADS_BLOCK, kernels._BUFFERS = chosen
            emit(**line, worst_difference=worst_difference(got, plain_out),
                 forward_speedup=plain["forward_ms"] / line["forward_ms"],
                 both_speedup=plain["both_ms"] / line["both_ms"])
    print("\n| form | heads a program | copies of x | forward ms (kernel) |"
          " forward + backward ms (kernels) | against plain |")
    print("|---|---|---|---|---|---|")
    for line in table:
        if "refused" in line:
            print(f"| kernels | {line['heads']} | {line['buffers']} | "
                  f"refused: {line['refused']} | | |")
            continue
        mark = " (chosen)" if line.get("chosen") else ""
        print(f"| {line['form']}{mark} | {line.get('heads', '')} | "
              f"{line.get('buffers', '')} | {line['forward_ms']:.3f} "
              f"({line['forward_kernel_ms']:.3f}) | {line['both_ms']:.3f} "
              f"({line['both_kernel_ms']:.3f}) | "
              + (f"{line['forward_speedup']:.2f}x / "
                 f"{line['both_speedup']:.2f}x, differs "
                 f"{line['worst_difference']:.2e} |"
                 if "both_speedup" in line else "|"))
    mine = [line for line in table if line.get("chosen")]
    met = bool(mine) and mine[0]["forward_speedup"] >= 2 \
        and mine[0]["both_speedup"] >= 2
    print(f"\ngate (the chosen kernels at least twice as fast, forward and "
          f"forward + backward): {'met' if met else 'NOT met'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
