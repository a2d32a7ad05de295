#!/usr/bin/env python
"""World-local FSDP loopback gate (the 10th run_all_checks.py gate).

Seven properties of the fully-sharded parameter path (optim/fsdp.py,
docs/fsdp.md), all on the 8-device virtual CPU host mesh:

1. **Bitwise parity vs the gathered reference** — one executed step of
   the prefetch-interleaved FSDP path equals the naive
   gather-everything-up-front reference bit for bit (params rows,
   optimizer state incl. the int8 error-feedback residual, loss), on
   the plain AND int8 wires, plus the gather pin structure
   (`overlap_check.fsdp_ab --cpu --check` drives this);
2. **Replicated-path agreement** — against the truly-unsharded staged
   ShardedOptimizer step: optimizer state and loss bitwise, gathered
   params within ONE ROUNDING of the applied update — 2 relative
   float32 ulps plus a 1e-7 absolute cancellation floor (the
   shard-local apply's fma contraction on the CPU barrier-expanding
   pipeline; bitwise on the TPU pipeline — see
   fsdp.apply_shard_updates);
3. **Measured memory bound** — per-device resident parameter bytes of
   the initialized train state ≤ replicated_bytes/world + one bucket;
4. **Knob-off lowering hash** — flipping HOROVOD_FSDP (and the
   regather/offload knobs) does not perturb a non-FSDP
   (ShardedOptimizer) step: identical lowered HLO text hashes with
   the knobs flipped (today's paths stay bit-for-bit);
5. **Regather ≡ saved-gather bitwise** — the backward-regather policy
   (HOROVOD_FSDP_REGATHER, the default) executes bit-identically to
   the saved-gather lowering (params rows, optimizer state incl. the
   int8 error-feedback residual, loss) on the plain AND int8 wires,
   and HOROVOD_FSDP_REGATHER=0 reproduces the saved-gather lowering
   hash-identically;
6. **Measured peak liveness** — pre-opt HLO live-interval analysis
   (overlap_check.analyze_liveness_preopt): under regather no
   gathered bucket stays live from forward to backward — max
   simultaneously-live gathers ≤ prefetch depth + O(1) working set,
   while the saved-gather lowering holds every bucket live at the
   forward→backward boundary (the negative control);
7. **Offload smoke** — HOROVOD_FSDP_OFFLOAD=1 (host-RAM carry
   offload) executes and stays bitwise-equal to offload-off.

Usage:
    python scripts/fsdp_check.py --check
"""

import argparse
import hashlib
import json
import os
import sys

if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P
from jax import shard_map



from overlap_check import trees_bitwise_equal as _bitwise  # noqa: E402


def _one_rounding_close(a, b):
    """The fma-contracted shard-local apply differs from the
    post-gather apply by at most ONE rounding of the applied update
    (see fsdp.apply_shard_updates). Gate that precisely: 2 relative
    float32 ulps (rtol 2^-22) plus a 1e-7 absolute floor — the floor
    is load-bearing, not slack: where p ≈ -u cancels, a one-rounding
    difference in u legitimately exceeds any fixed ulp count of the
    tiny RESULT, so a pure spacing-of-result bound would false-fail
    exactly the well-behaved cases."""
    a, b = np.asarray(a), np.asarray(b)
    return bool(np.allclose(a, b, rtol=2.0 ** -22, atol=1e-7))


def check_parity_and_pins(args, failures):
    """Property 1: delegate to the overlap_check FSDP A/B in gate
    mode (bitwise parity plain+int8, gather/backward pin structure)."""
    from overlap_check import fsdp_ab

    ns = argparse.Namespace(
        cpu=True, check=True, model="tiny", fusion_mb=args.fusion_mb,
        batch_per_chip=0, topology="v5e:2x4", out=args.out or "")
    rc = fsdp_ab(ns)
    if rc != 0:
        failures.append("fsdp_ab parity/pin gate failed (see above)")


def check_replicated_agreement(failures):
    """Property 2: FSDP vs the unsharded staged ShardedOptimizer step
    over the same buckets — state/loss bitwise, params within one
    rounding of the update."""
    import horovod_tpu as hvd
    from horovod_tpu.models import Transformer
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                causal_lm_loss)
    from horovod_tpu.optim import fsdp as fsdp_mod

    TINY = TransformerConfig(
        vocab_size=64, num_layers=4, num_heads=2, hidden_size=32,
        max_seq_len=16, dtype=jnp.float32)
    TH = 8 << 10
    mesh = hvd.mesh()
    m = Transformer(TINY)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (16, 16)), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), toks[:2])["params"]
    layout = fsdp_mod.fsdp_layout(params, world=8,
                                  fusion_threshold_bytes=TH)

    def stages_for(b):
        return hvd.overlap.transformer_lm_stages(
            m, b, lambda lg, _b=b: causal_lm_loss(lg, _b)[0])

    fopt = hvd.FullyShardedOptimizer(optax.adamw(1e-3),
                                     fusion_threshold_bytes=TH)
    fstate = fopt.init(params)
    fvag = fsdp_mod.fsdp_value_and_grad(stages_for, fopt, layout)
    rows = fsdp_mod.shard_params(params, layout)

    def fstep(r, s, b):
        l, g = fvag(r, b, opt_state=s)
        upd, s2 = fopt.update(g, s, fsdp_mod.local_shards(r, layout))
        return (fsdp_mod.apply_shard_updates(r, upd, layout), s2,
                jax.lax.psum(l, "hvd").reshape(1))

    js_f = jax.jit(shard_map(
        fstep, mesh=mesh,
        in_specs=(fsdp_mod.param_row_specs(layout),
                  hvd.sharded_state_specs(fstate), P("hvd")),
        out_specs=(fsdp_mod.param_row_specs(layout),
                   hvd.sharded_state_specs(fstate), P()),
        check_vma=False))
    out_f = js_f(rows, fstate, toks)

    zopt = hvd.ShardedOptimizer(optax.adamw(1e-3),
                                fusion_threshold_bytes=TH)
    zstate = zopt.init(params)
    zvag = hvd.overlap.staged_value_and_grad(stages_for, opt=zopt,
                                             mode="stage")

    def zstep(p, s, b):
        l, g = zvag(p, b, opt_state=s)
        upd, s2 = zopt.update(g, s, p)
        return (optax.apply_updates(p, upd), s2,
                jax.lax.psum(l, "hvd").reshape(1))

    js_z = jax.jit(shard_map(
        zstep, mesh=mesh,
        in_specs=(P(), hvd.sharded_state_specs(zstate), P("hvd")),
        out_specs=(P(), hvd.sharded_state_specs(zstate), P()),
        check_vma=False))
    out_z = js_z(params, zstate, toks)

    if not _bitwise(out_f[1], out_z[1]):
        failures.append("FSDP vs replicated: optimizer state diverged")
    if not _bitwise(out_f[2], out_z[2]):
        failures.append("FSDP vs replicated: loss diverged")
    gathered = fsdp_mod.unshard_params(out_f[0], layout)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(gathered)[0],
            jax.tree_util.tree_flatten_with_path(out_z[0])[0]):
        if not _one_rounding_close(a, b):
            failures.append(
                f"FSDP vs replicated params beyond one rounding of "
                f"the update at {jax.tree_util.keystr(pa)}: max "
                f"{np.abs(np.asarray(a) - np.asarray(b)).max()}")
            break
    print("replicated agreement: state/loss bitwise, params within "
          "one rounding of the update (2 rel ulps + 1e-7 floor)")
    return layout, rows, fstate


def check_memory_bound(layout, rows, failures):
    """Property 3: measured per-device resident parameter bytes."""
    import horovod_tpu as hvd
    from horovod_tpu.optim import fsdp as fsdp_mod

    mesh = hvd.mesh()
    shardings = fsdp_mod.param_row_shardings(layout, mesh)
    placed = {k: jax.device_put(v, shardings[k]) for k, v in rows.items()}
    dev0 = jax.devices()[0]
    per_dev = 0
    for v in placed.values():
        for s in v.addressable_shards:
            if s.device == dev0:
                per_dev += s.data.size * s.data.dtype.itemsize
    bound = layout.param_bytes / layout.world + layout.max_bucket_bytes
    print(json.dumps({
        "replicated_param_bytes": layout.param_bytes,
        "per_device_resident_bytes": per_dev,
        "bound_replicated_over_world_plus_bucket": int(bound),
        "reduction_x": round(layout.param_bytes / max(per_dev, 1), 2),
    }))
    if per_dev > bound:
        failures.append(
            f"per-device resident param bytes {per_dev} exceed "
            f"replicated/world + one bucket = {int(bound)}")


def check_knob_hash(failures):
    """Property 4: HOROVOD_FSDP never perturbs non-FSDP lowerings."""
    import horovod_tpu as hvd
    from horovod_tpu.core.state import global_state
    from horovod_tpu.models import Transformer
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                causal_lm_loss)

    TINY = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, hidden_size=32,
        max_seq_len=16, dtype=jnp.float32)
    mesh = hvd.mesh()
    m = Transformer(TINY)
    toks = jnp.ones((16, 16), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), toks[:2])["params"]

    def build():
        opt = hvd.ShardedOptimizer(optax.adamw(1e-3),
                                   fusion_threshold_bytes=8 << 10)
        state = opt.init(params)
        specs = hvd.sharded_state_specs(state)

        def step(p, s, b):
            def loss_fn(p):
                return causal_lm_loss(m.apply({"params": p}, b), b)[0]

            l, g = jax.value_and_grad(loss_fn)(p)
            upd, s2 = opt.update(g, s, p)
            return optax.apply_updates(p, upd), s2

        js = jax.jit(shard_map(
            step, mesh=mesh, in_specs=(P(), specs, P("hvd")),
            out_specs=(P(), specs), check_vma=False))
        return js.lower(params, state, toks).as_text()

    knobs = global_state().knobs
    old = (knobs.fsdp, knobs.fsdp_regather, knobs.fsdp_offload,
           knobs.fsdp_offload_duty)
    try:
        knobs.fsdp = True
        h_on = hashlib.sha256(build().encode()).hexdigest()
        knobs.fsdp = False
        h_off = hashlib.sha256(build().encode()).hexdigest()
        knobs.fsdp = old[0]
        knobs.fsdp_regather = not old[1]
        knobs.fsdp_offload = True
        knobs.fsdp_offload_duty = 0.5
        h_new = hashlib.sha256(build().encode()).hexdigest()
    finally:
        (knobs.fsdp, knobs.fsdp_regather, knobs.fsdp_offload,
         knobs.fsdp_offload_duty) = old
    print(f"knob-off lowering hash: on={h_on[:12]} off={h_off[:12]} "
          f"regather/offload-flipped={h_new[:12]}")
    if h_on != h_off:
        failures.append(
            "HOROVOD_FSDP flip changed a non-FSDP step's lowered HLO "
            "— the knob is no longer inert on existing paths")
    if h_new != h_on:
        failures.append(
            "HOROVOD_FSDP_REGATHER/OFFLOAD flip changed a non-FSDP "
            "step's lowered HLO — the new knobs leak outside the "
            "FSDP staged path")


def check_regather(args, failures):
    """Properties 5–7: the backward-regather + offload policies.

    Executes one step of the tiny vehicle under five lowerings —
    saved-gather, regather, regather+offload (plain wire) and
    saved/regather (int8 wire, EF residual in state) — and asserts
    pairwise bitwise equality; proves the within-step peak bound
    structurally on the pre-opt HLO (live-interval max overlap); and
    pins HOROVOD_FSDP_REGATHER=0 to the explicit regather=False
    lowering hash."""
    import horovod_tpu as hvd
    from horovod_tpu.core.state import global_state
    from horovod_tpu.optim import fsdp as fsdp_mod
    from overlap_check import (_model_pieces, analyze_liveness_preopt,
                               build_fsdp_step)

    mesh = hvd.mesh()
    nchips = len(jax.devices())
    cfg, model_obj, _, bpc = _model_pieces("tiny", 0)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(
            0, cfg.vocab_size, (bpc * nchips, cfg.max_seq_len)),
        jnp.int32)
    params = model_obj.init(jax.random.PRNGKey(0), toks[:1])["params"]

    def _exec(js, layout, compression):
        comp = (hvd.Compression.lookup(compression)
                if compression else None)
        opt = hvd.FullyShardedOptimizer(
            optax.adamw(1e-4),
            fusion_threshold_bytes=int(args.fusion_mb * (1 << 20)),
            compression=comp)
        r = js(fsdp_mod.shard_params(params, layout),
               opt.init(params), toks)
        jax.block_until_ready(r)
        return r

    results, liveness, lower_hash = {}, {}, {}
    for key, comp, kw in (
            ("saved", None, dict(regather=False)),
            ("regather", None, dict(regather=True)),
            ("offload", None, dict(regather=True, offload=True)),
            ("saved_int8", "int8", dict(regather=False)),
            ("regather_int8", "int8", dict(regather=True))):
        js, rows_s, state_s, toks_s, layout = build_fsdp_step(
            "tiny", mesh, nchips, args.fusion_mb, 0,
            compression=comp, **kw)
        low = js.lower(rows_s, state_s, toks_s)
        if comp is None:
            liveness[key] = analyze_liveness_preopt(
                low.compiler_ir(dialect="hlo").as_hlo_text())
            lower_hash[key] = hashlib.sha256(
                low.as_text().encode()).hexdigest()
        results[key] = _exec(js, layout, comp)

    for a, b, lbl in (("saved", "regather", "plain wire"),
                      ("saved_int8", "regather_int8", "int8+EF wire"),
                      ("regather", "offload", "offload on/off")):
        for i, part in enumerate(("params rows", "optimizer state",
                                  "loss")):
            if not _bitwise(results[a][i], results[b][i]):
                failures.append(
                    f"regather A/B ({lbl}): {part} NOT bitwise equal "
                    f"({a} vs {b})")

    # HOROVOD_FSDP_REGATHER=0 must reproduce the explicit
    # regather=False lowering hash-identically
    knobs = global_state().knobs
    old = knobs.fsdp_regather
    try:
        knobs.fsdp_regather = False
        js_k, rows_s, state_s, toks_s, _ = build_fsdp_step(
            "tiny", mesh, nchips, args.fusion_mb, 0)
        h_knob = hashlib.sha256(
            js_k.lower(rows_s, state_s, toks_s).as_text().encode()
        ).hexdigest()
    finally:
        knobs.fsdp_regather = old
    if h_knob != lower_hash["saved"]:
        failures.append(
            "HOROVOD_FSDP_REGATHER=0 lowering differs from explicit "
            "regather=False — the knob no longer reproduces the "
            "saved-gather lowering bit-for-bit")

    # structural peak-liveness proof: saved mode holds every bucket
    # live across the forward→backward boundary (negative control);
    # regather's max overlap stays within prefetch depth + the O(1)
    # gather/consume working set, and it issues MORE gathers than
    # buckets (the re-issue itself, visible in the instruction count)
    n_buckets = liveness["saved"]["param_all_gathers"]
    depth = int(getattr(global_state().knobs, "fsdp_prefetch", 1) or 1)
    bound = depth + 3
    print(json.dumps({
        "buckets": n_buckets,
        "liveness": {k: {"gathers": v["param_all_gathers"],
                         "max_live": v["max_live_gathers"]}
                     for k, v in liveness.items()},
        "peak_live_bound_regather": bound,
    }))
    if liveness["saved"]["max_live_gathers"] < n_buckets:
        failures.append(
            f"negative control broken: saved-gather mode keeps only "
            f"{liveness['saved']['max_live_gathers']} of {n_buckets} "
            f"gathers live at peak — the liveness analyzer no longer "
            f"sees the forward→backward retention it must refute")
    for key in ("regather", "offload"):
        if liveness[key]["max_live_gathers"] > bound:
            failures.append(
                f"{key}: {liveness[key]['max_live_gathers']} gathered "
                f"buckets simultaneously live in the pre-opt HLO — "
                f"exceeds prefetch depth + working set ({bound}); a "
                f"gathered bucket survives the forward→backward "
                f"boundary")
        if liveness[key]["param_all_gathers"] <= n_buckets:
            failures.append(
                f"{key}: only {liveness[key]['param_all_gathers']} "
                f"all-gathers for {n_buckets} buckets — backward is "
                f"not re-issuing the collective")
    print("regather: bitwise parity (plain, int8+EF, offload), "
          "knob-off hash, peak-liveness bound hold")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="gate mode: exit nonzero on any failure")
    ap.add_argument("--fusion-mb", type=float, default=0.02)
    ap.add_argument("--out", default="",
                    help="also write the fsdp A/B artifact here")
    args = ap.parse_args(argv)

    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init()
    failures = []
    check_parity_and_pins(args, failures)
    layout, rows, _ = check_replicated_agreement(failures)
    check_memory_bound(layout, rows, failures)
    check_knob_hash(failures)
    check_regather(args, failures)
    hvd.shutdown()
    if failures:
        for f in failures:
            print("fsdp check FAILED:", f)
        return 1
    print("fsdp check OK: parity, pins, memory bound, knob hash, "
          "regather parity + peak liveness, offload smoke")
    return 0


if __name__ == "__main__":
    sys.exit(main())
