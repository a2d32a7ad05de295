#!/usr/bin/env python3
"""Time the routed experts' products alone on the chip: the three
kernels of `ops/grouped_matmul.py` beside `lax.ragged_dot` behind its
cast of the experts (what `models/moe.expert_product` ran before them)
and beside `megablox` as the installed jax has it.

    python scripts/grouped_matmul_sweep.py [--shapes sdar_up,...]
        [--tiles 256x256,512x256,...] [--calls 10]
        [--out chiprun_out/grouped_matmul_sweep.jsonl]

A shape is one product of a cell's layer, bf16 rows through float32
experts: `sdar_up` 16,384 x 2,048 -> 768 and `sdar_down` 16,384 x 768
-> 2,048 in 16 groups (`sdar_bd_s4096`), `trinity_up` 8,192 x 2,048 ->
1,024 and `trinity_down` 8,192 x 1,024 -> 2,048 in 16 groups
(`trinity_mini_s8192`). Each is timed with the rows divided evenly (as
both cells' seeded routers divide them) and unevenly (seeded shares
that are no multiple of anything, one group empty). Three forms, each
jitted alone: `product` (rows x W), `rows_gradient` (d_out x W^T) and
`weights_gradient` (rows^T x d_out inside each group, float32 as the
optimizer takes it). `ragged_dot` is the control: its forms are
`jax.vjp`'s of `lax.ragged_dot(rows, w.astype(bf16), groups)`, the
casts included as the step runs them. `megablox` is `gmm` / `tgmm` at
`--megablox-tiling`, handed the bf16 copy ready made (its time is the
kernel's alone and flatters it by the cast). Ours are swept over
`rows x piece` (rows of a visit's tile x rows of one product inside
it; the module's budget lifted, so that a tile too large is the
compiler's refusal), on even groups; the pair the module picks for the
shape runs on both divisions.
Each is traced over `--calls` calls: `ms` is everything the call runs
on the device, `peak_pct` 2 m a c operations over `ms` against the
chip's 197 TFLOP/s. Results are held against the control's by the norm
of the difference over the norm. Last comes the table the module's
header quotes.

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

# name -> (rows, contraction, width, groups)
SHAPES = {
    "sdar_up": (16384, 2048, 768, 16),
    "sdar_down": (16384, 768, 2048, 16),
    "trinity_up": (8192, 2048, 1024, 16),
    "trinity_down": (8192, 1024, 2048, 16),
}
FORMS = ("product", "rows_gradient", "weights_gradient")
PEAK_FLOPS = 197e12  # TPU v5e, bf16 (benchmarks/peaks.py)


def divisions(m, g):
    """{"even": m / g rows a group, "uneven": seeded shares of m with
    the third group empty}, int32 `[g]` each."""
    import numpy as np

    rng = np.random.default_rng(0)
    share = rng.uniform(0.3, 1.7, g)
    share[2] = 0.0
    sizes = np.floor(share / share.sum() * m).astype(np.int32)
    sizes[-1] += m - sizes.sum()
    return {"even": np.full(g, m // g, np.int32), "uneven": sizes}


def inputs(shape):
    import jax
    import jax.numpy as jnp

    m, a, c, g = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    rows = jax.random.normal(keys[0], (m, a), jnp.float32).astype(
        jnp.bfloat16)
    weights = jax.random.normal(keys[1], (g, a, c), jnp.float32) \
        * a ** -0.5
    d_out = jax.random.normal(keys[2], (m, c), jnp.float32).astype(
        jnp.bfloat16)
    return rows, weights, d_out


def control_forms():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def product(rows, weights, d_out, groups):
        return lax.ragged_dot(rows, weights.astype(rows.dtype), groups,
                              preferred_element_type=rows.dtype)

    def rows_gradient(rows, weights, d_out, groups):
        return jax.vjp(lambda r: product(r, weights, d_out, groups),
                       rows)[1](d_out)[0]

    def weights_gradient(rows, weights, d_out, groups):
        return jax.vjp(lambda w: product(rows, w, d_out, groups),
                       weights)[1](d_out)[0].astype(jnp.float32)

    return dict(product=product, rows_gradient=rows_gradient,
                weights_gradient=weights_gradient)


def our_forms():
    from horovod_tpu.ops import grouped_matmul as gm

    return dict(
        product=lambda rows, weights, d_out, groups: gm._product(
            rows, weights, groups, False, False),
        rows_gradient=lambda rows, weights, d_out, groups: gm._product(
            d_out, weights, groups, True, False),
        weights_gradient=lambda rows, weights, d_out, groups:
        gm._weights_gradient(rows, d_out, groups, False))


def megablox_forms(tiling):
    """`gmm` / `tgmm` of the installed jax on a bf16 copy of the experts
    made beforehand (`low`)."""
    import importlib

    import jax.numpy as jnp

    # the module: the package's own `gmm` is the function of that name
    mb = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")

    def tiles(m, k, n):
        return tuple(min(t, d) for t, d in zip(tiling, (m, k, n)))

    def product(rows, low, d_out, groups):
        return mb.gmm(rows, low, groups, rows.dtype,
                      tiles(*rows.shape, low.shape[2]))

    def rows_gradient(rows, low, d_out, groups):
        return mb.gmm(d_out, low, groups, rows.dtype,
                      tiles(*d_out.shape, low.shape[1]), transpose_rhs=True)

    def weights_gradient(rows, low, d_out, groups):
        return mb.tgmm(rows.swapaxes(0, 1), d_out, groups, jnp.float32,
                       tiles(*rows.shape, d_out.shape[1]))

    return dict(product=product, rows_gradient=rows_gradient,
                weights_gradient=weights_gradient)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--tiles", default="256x256,512x128,512x256,512x512,"
                                       "1024x256,1024x512")
    ap.add_argument("--megablox-tiling", default="512,1024,1024")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--out", default=os.path.join(
        "chiprun_out", "grouped_matmul_sweep.jsonl"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import grouped_matmul as gm
    # (ms a call of everything it runs on the device, ms of its Mosaic
    # calls alone, the results as float32 numpy arrays), from a trace
    from scripts.attention_prep_sweep import measure
    # the largest |mine - theirs| / |theirs| by the norm over the results
    from scripts.ssd_scan_sweep import worst_difference

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU here (platform {device.platform}): nothing measured")
        return 1
    module = (gm._ROWS, gm._PIECE, gm._VMEM_BUDGET)
    tiles = {tuple(int(n) for n in t.split("x"))
             for t in args.tiles.split(",") if t}
    tiling = tuple(int(n) for n in args.megablox_tiling.split(","))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    table = []
    with open(args.out, "w") as out:

        def emit(**line):
            line["device"] = device.device_kind
            out.write(json.dumps(line) + "\n")
            out.flush()
            print(json.dumps(line), flush=True)
            table.append(line)

        for name in args.shapes.split(","):
            m, a, c, g = SHAPES[name]
            rows, weights, d_out = inputs(SHAPES[name])
            low = weights.astype(jnp.bfloat16)
            for division, sizes in divisions(m, g).items():
                groups = jnp.asarray(sizes)
                control = {}

                def run(impl, forms, experts, **labels):
                    jax.clear_caches()
                    for form in FORMS:
                        line = dict(shape=name, division=division,
                                    impl=impl, form=form, **labels)
                        try:
                            ms, _, got = measure(
                                jax.jit(forms[form]),
                                (rows, experts, d_out, groups), args.calls)
                        except Exception as e:  # a refusal is a finding
                            emit(**line, refused=str(e).splitlines()[0][:300])
                            continue
                        control.setdefault(form, got)
                        emit(**line, ms=ms, peak_pct=100 * 2 * m * a * c
                             / PEAK_FLOPS / (ms / 1e3),
                             worst_difference=worst_difference(
                                 got, control[form]))

                run("ragged_dot", control_forms(), weights)
                run("megablox", megablox_forms(tiling), low,
                    tiling="x".join(map(str, tiling)))
                # what the module picks for this shape, by its own rule
                chosen = (gm._row_tile(m, a, c, rows.dtype.itemsize),
                          gm._PIECE)
                for tile_rows, piece in sorted(tiles | {chosen}):
                    if division != "even" and (tile_rows, piece) != chosen:
                        continue
                    if m % tile_rows or tile_rows % piece:
                        continue
                    if (tile_rows, piece) != chosen:
                        # this tile and no other, whatever the budget
                        # says: the compiler's refusal is the finding
                        gm._ROWS, gm._PIECE, gm._VMEM_BUDGET = (
                            (tile_rows,), piece, 2**40)
                    try:
                        run("ours", our_forms(), weights, rows=tile_rows,
                            piece=piece,
                            chosen=(tile_rows, piece) == chosen)
                    finally:
                        gm._ROWS, gm._PIECE, gm._VMEM_BUDGET = module
    columns = FORMS
    print("\n| shape | division | kernel | " + " | ".join(
        f"{form} ms (% of peak)" for form in columns) + " | differs |")
    print("|---|---|---|" + "---|" * (len(columns) + 1))
    cells = {}
    for line in table:
        kernel = line["impl"] + (
            f" {line['rows']}x{line['piece']}"
            + (" (chosen)" if line["chosen"] else "")
            if line["impl"] == "ours" else "")
        cells.setdefault((line["shape"], line["division"], kernel),
                         {})[line["form"]] = line
    for (shape, division, kernel), forms in cells.items():
        print(f"| {shape} | {division} | {kernel} | " + " | ".join(
            "" if f not in forms else
            "refused" if "refused" in forms[f] else
            f"{forms[f]['ms']:.3f} ({forms[f]['peak_pct']:.0f})"
            for f in columns) + " | " + "{:.1e}".format(max(
                line.get("worst_difference", 0.0)
                for line in forms.values())) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
