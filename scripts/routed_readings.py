#!/usr/bin/env python3
"""The three readings the first cell whose program chooses owes
(PERF.md section 7), taken through the benchmark's own pieces at the
cell's size on the chip; it changes no limit and nothing reads it.

    chiprun --chips 1 -- python scripts/routed_readings.py \\
        --seeds 12 --control-seeds 3 --json chiprun_out/readings.json

For each seed: the cell's parameters and two sequences as
``dp_train.reference_check`` draws them, the system's loss function
(``dp_train.make_loss_fn``, bf16, flash attention, fused cross entropy)
and the cell's plain reference.

(a) the gradient's relative error against the reference at the system's
    choices (what the harness compares under ``GRAD_RTOL``) beside the
    free comparison, the reference at its own choices;
(b) the two shares of ``reference_choices`` (``choices_agreement``: the
    tokens at which the reference's top k is the system's set, and the
    floor ``NEAR_TIE`` gives) beside two routers at fault, top 7 and the
    renormalisation left out, each with every number the harness
    compares; and the largest difference, over the tokens and as a
    share of the spread between a token's best and worst score, between
    the bf16 pass's scores and the reference's at the k-th and (k+1)-th
    expert: what rounding can flip, which a band has to be set from;
(c) a lower precision in the expert product alone (accumulated in bf16
    over blocks of 128 terms; an 8-bit float product) by the gradient's
    global norm and by the worst leaf of the expert weights;
and the rows each held expert saw (least, mean, most over the layers'
experts) against the expected load.

``--rehearse`` runs the tiny presets anywhere (the numbers then say
nothing of the chip or of the cell's size).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CELL = "sdar_bd_s4096"


def controls():
    """name -> a stand-in for ``models/moe.expert_product``."""
    import jax.numpy as jnp
    from jax import lax

    def bf16_accumulated(rows, weights, groups, block=128):
        # the contraction in blocks of 128 terms, each block's sum
        # rounded to bf16 and the blocks added in bf16
        w = weights.astype(rows.dtype)
        total = None
        for lo in range(0, rows.shape[1], block):
            part = lax.ragged_dot(rows[:, lo:lo + block],
                                  w[:, lo:lo + block], groups,
                                  preferred_element_type=jnp.bfloat16)
            total = part if total is None else total + part
        return total.astype(rows.dtype)

    def eight_bit(rows, weights, groups):
        f8 = jnp.float8_e4m3fn
        return lax.ragged_dot(
            rows.astype(f8).astype(rows.dtype),
            weights.astype(f8).astype(rows.dtype), groups,
            preferred_element_type=rows.dtype)

    return {"bf16_accumulation": bf16_accumulated, "8_bit_product": eight_bit}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=33_000_101)
    p.add_argument("--json", default=None)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--controls-only", action="store_true",
                   help="only the routers at fault and the lower-precision "
                        "controls, on every seed")
    p.add_argument("--rows-only", action="store_true",
                   help="only the rows each held expert saw, from one "
                        "forward pass a seed")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import traverse_util

    from benchmarks import harness
    from benchmarks.jobs import dp_train
    from horovod_tpu.models import moe

    if not args.rehearse and jax.default_backend() != "tpu":
        sys.exit("the readings are of the chip; --rehearse runs the tiny "
                 "presets anywhere")
    found = harness.load_cell(CELL)
    sizes, traffic = dict(found["config"]["model"]), found["traffic"]
    if args.rehearse:
        sizes.update(found["config"]["tiny"])
        traffic = {**traffic, **traffic["tiny"]}
    reference = harness.load_reference(found["config"]["family"])
    kw = reference.arguments(sizes, traffic)
    k, held, e = (sizes["experts_per_token"], sizes["experts_held"],
                  sizes["num_experts"])
    t0 = time.perf_counter()

    def log(text):
        print(f"[{time.perf_counter() - t0:7.1f}s] {text}", flush=True)

    def system(model_sizes):
        """Of the system's loss function built from ``model_sizes``:
        the model for parameter init, ``(loss, choices), gradient``, the
        routers' own scores of the same arithmetic on the same input by
        the choices' names, and ``(loss, choices)`` alone."""
        _, model, plain = dp_train.make_model(model_sizes, traffic)
        loss_fn = dp_train.make_loss_fn(model, traffic, with_choices=True)

        def scores(p, x0, m, _):
            # the step's input as ``dp_train._block_diffusion_loss``
            # builds it; Flax keeps every router's logits
            n, t = x0.shape
            tokens = jnp.concatenate(
                [jnp.where(m, model_sizes["vocab_size"] - 1, x0), x0], 1)
            positions = jnp.broadcast_to(
                jnp.tile(jnp.arange(t), 2)[None], (n, 2 * t))
            _, kept = model.apply(
                {"params": p}, tokens, positions=positions,
                return_hidden=True, mutable=["intermediates"],
                capture_intermediates=lambda mdl, _: mdl.name == "router")
            return {name.replace("router/__call__", "experts/0"):
                    jax.nn.softmax(logits[0], -1)
                    for name, logits in traverse_util.flatten_dict(
                        kept["intermediates"], sep="/").items()}

        return (plain, jax.jit(jax.value_and_grad(loss_fn, has_aux=True)),
                jax.jit(scores), jax.jit(loss_fn))

    @jax.jit
    def ref_grad(p, b, choices):
        """Loss and gradient of the reference, at ``choices`` or, given
        None, at its own."""
        given = {} if choices is None else {"choices": choices}
        return jax.value_and_grad(lambda q: reference.mean_loss(
            q, b, **kw, **given))(p)

    @jax.jit
    def relative_errors(g_sys, g_ref):
        """(global, {leaf: relative error}) of the gradient."""
        diff = jax.tree_util.tree_map(
            lambda a, r: a.astype(jnp.float32) - r, g_sys, g_ref)
        leaves = jax.tree_util.tree_map(
            lambda d, r: jnp.linalg.norm(d) / jnp.linalg.norm(r),
            diff, g_ref)
        return optax.global_norm(diff) / optax.global_norm(g_ref), leaves

    @jax.jit
    def boundary(sys_scores, ref_scores):
        """Over the tokens of every layer: the reference's gap between
        its k-th and (k+1)-th score and the largest difference between
        the two passes' scores at those two experts, both as shares of
        the spread between the token's best and worst score."""
        gaps, moved = [], []
        for name, ref in ref_scores.items():
            ours = sys_scores[name].reshape(ref.shape)
            ranked, index = jax.lax.top_k(ref, k + 1)
            spread = ranked[..., 0] - jnp.min(ref, -1)
            at = jnp.take_along_axis(ours, index[..., k - 1:], -1)
            gaps.append((ranked[..., k - 1] - ranked[..., k]) / spread)
            moved.append(jnp.max(jnp.abs(at - ranked[..., k - 1:]), -1)
                         / spread)
        return jnp.concatenate([g.reshape(-1) for g in gaps]), \
            jnp.concatenate([m.reshape(-1) for m in moved])

    def against_reference(g_sys, choices, params, batch):
        """The reference's loss at ``choices`` (its own where None), and
        ``relative_errors`` of ``g_sys``, a tree on the host, against
        its gradient: beside the system's gradient the reference's pass
        does not fit the chip (8.6 GiB of temporaries next to two trees
        of 2.4)."""
        l_ref, g_ref = ref_grad(params, batch, choices)
        g_err, leaves = relative_errors(g_sys, g_ref)
        return float(l_ref), float(g_err), jax.device_get(leaves)

    def compared(l_sys, l_ref, g_err, counts):
        agree, near, count = (int(x) for x in counts)
        return {"reference_loss": abs(l_sys - l_ref) / abs(l_ref),
                "reference_gradient": g_err,
                "reference_choices": agree / count,
                "floor": (count - near) / count}

    plain, sound, sound_scores, forward = system(sizes)
    variants = {
        "no_renormalisation": system({**sizes, "norm_topk_prob": False})[1],
        "top_7": system({**sizes, "experts_per_token": k - 1})[1],
    }
    # traced at their first call, with the stand-in in place
    controlled = {name: (stand_in, system(sizes)[1])
               for name, stand_in in controls().items()}
    score_fn = jax.jit(lambda p, b: reference.choice_scores(p, b, **kw))
    expert_leaves = ("gate", "up", "down")
    out = {"cell": CELL, "rehearsal": args.rehearse,
           "limits": {"GRAD_RTOL": dp_train.GRAD_RTOL,
                      "LOSS_RTOL": dp_train.LOSS_RTOL,
                      "NEAR_TIE": dp_train.NEAR_TIE},
           "seeds": []}
    init = jax.jit(plain.init)

    def worst_expert_leaf(leaves):
        flat = traverse_util.flatten_dict(leaves, sep="/")
        worst = max((float(v), n) for n, v in flat.items()
                    if n.rsplit("/", 1)[-1] in expert_leaves)
        return {"worst_expert_leaf": worst[0], "leaf": worst[1],
                "worst_leaf_of_all": max(
                    (float(v), n) for n, v in flat.items())}

    def rows_seen(choices):
        """What the held experts of each layer were sent."""
        seen = np.stack([
            np.bincount(np.asarray(c).reshape(-1), minlength=e)[:held]
            for c in choices.values()])  # [layers, held]
        positions = next(iter(choices.values())).size // k
        return {"expected": positions * k / e,
                "least": int(seen.min()), "mean": float(seen.mean()),
                "most": int(seen.max()),
                "a_layer": [int(x) for x in seen.sum(1)],
                "a_layer_expected": positions * k * held / e,
                "rows_static": moe.rows_static(positions, k, held, e)[1]}

    for i in range(args.seeds):
        seed = args.first_seed + 1000 * i
        params = init(jax.random.PRNGKey(seed), jnp.zeros(
            (1, traffic["seq_len"]), jnp.int32))["params"]
        batch = tuple(jnp.asarray(a) for a in dp_train.make_batch(
            sizes, traffic, 2, seed + 1))
        if args.rows_only:
            entry = {"seed": seed, "rows_a_held_expert": rows_seen(
                forward(params, *batch)[1])}
            log(f"seed {seed}: {entry['rows_a_held_expert']}")
            out["seeds"].append(entry)
            continue
        if args.controls_only:
            # a process of its own for the last part: beside the programs
            # of the first the stand-ins' passes do not fit the chip
            ref_scores, entry = score_fn(params, batch), {"seed": seed}
        else:
            (l_sys, choices), g_sys = sound(params, *batch)
            l_sys, g_sys = float(l_sys), jax.device_get(g_sys)
            sys_scores = sound_scores(params, *batch)
            ref_scores = score_fn(params, batch)
            counts = dp_train.choices_agreement(ref_scores, choices)
            l_ref, g_err, leaves = against_reference(
                g_sys, choices, params, batch)
            entry = {"seed": seed, "imposed": {
                **compared(l_sys, l_ref, g_err, counts),
                **worst_expert_leaf(leaves)}}
            l_free, g_err_free, leaves = against_reference(
                g_sys, None, params, batch)
            entry["free"] = {
                "reference_loss": abs(l_sys - l_free) / abs(l_free),
                "reference_gradient": g_err_free,
                **worst_expert_leaf(leaves)}
            del g_sys
            gaps, moved = (np.asarray(x) for x in boundary(
                sys_scores, ref_scores))
            entry["boundary"] = {
                "tokens": int(gaps.size),
                "scores_moved_max": float(moved.max()),
                "scores_moved_p999": float(np.quantile(moved, 0.999)),
                "scores_moved_median": float(np.median(moved)),
                "gap_under_near_tie": float(np.mean(gaps < dp_train.NEAR_TIE)),
                "gap_under_twice_moved_max": float(
                    np.mean(gaps < 2 * moved.max())),
                "flipped": float(np.mean(gaps < 2 * moved)),
            }
            entry["rows_a_held_expert"] = rows_seen(choices)
            log(f"seed {seed}: imposed "
                f"{entry['imposed']['reference_gradient']:.4e} free "
                f"{entry['free']['reference_gradient']:.4e}; choices "
                f"{entry['imposed']['reference_choices']:.4f} floor "
                f"{entry['imposed']['floor']:.4f}; scores moved at most "
                f"{entry['boundary']['scores_moved_max']:.3e} of the spread; "
                f"rows an expert {entry['rows_a_held_expert']}")
        if args.controls_only or i >= args.seeds - args.control_seeds:
            # The last seeds. Every program loaded so far is dropped
            # first, each stand-in's after its pass, and the
            # reference's for eight choices before the one for seven:
            # loaded code fills what the reference's pass needs
            jax.clear_caches()
            real = moe.expert_product
            passes = [(name, real, fn) for name, fn in variants.items()]
            passes[1:1] = [(name, stand_in, fn)
                           for name, (stand_in, fn) in controlled.items()]
            for name, product, fn in passes:  # top 7 last
                if name == "top_7":
                    ref_grad.clear_cache()
                moe.expert_product = product
                try:
                    (l_v, chosen), g_v = fn(params, *batch)
                finally:
                    moe.expert_product = real
                g_v = jax.device_get(g_v)
                fn.clear_cache()
                l_r, err, leaves = against_reference(
                    g_v, chosen, params, batch)
                del g_v
                if name in variants:
                    entry[name] = compared(
                        float(l_v), l_r, err,
                        dp_train.choices_agreement(ref_scores, chosen))
                else:
                    entry[name] = {
                        "reference_loss": abs(float(l_v) - l_r) / abs(l_r),
                        "reference_gradient": err,
                        **worst_expert_leaf(leaves)}
                log(f"  {'router at fault' if name in variants else 'control'}"
                    f", {name}: {entry[name]}")
        out["seeds"].append(entry)
        if args.json:  # after every seed: a pass that does not fit the
            # chip ends the process, and took two calls' readings with it
            os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                        exist_ok=True)
            with open(args.json, "w") as f:
                json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
