#!/usr/bin/env python
"""Comm/compute overlap analysis on REAL model train steps → OVERLAP_r{N}.json.

AOT-compiles the DistributedOptimizer train step for a real v5e
topology (jax.experimental.topologies — needs a TPU client but not the
physical chips; --topology v5e:16x16 compiles the full 256-chip
BASELINE-scale program) and measures the *overlap window*: the fraction
of backward compute the optimized schedule places AFTER the first
gradient all-reduce issues. 0% = all collectives serialize behind the
whole backward pass; the reference's fusion cycle exists to widen
exactly this window (/root/reference/horovod/common/controller.cc:830,
docs/benchmarks.rst:8-13's 90%-scaling claim).

Models are the real benchmark configs (BERT-Large 24L/1024H mlm,
GPT-2-medium 24L/1024H causal — the same steps examples/
bert_pretraining.py and gpt2_pretraining.py time), not toys.

Usage:
    python scripts/overlap_check.py --model bert-large --out OVERLAP_r05.json
    python scripts/overlap_check.py --model gpt2-medium --topology v5e:16x16
    python scripts/overlap_check.py --model bert-large --sweep   # order x threshold
    python scripts/overlap_check.py --schedule-ab --out SCHEDULE_AB_r06.json
    python scripts/overlap_check.py --schedule-ab --cpu --model tiny --check
"""

import argparse
import dataclasses
import json
import os
import re
import sys
import time

# the CPU A/B mode (--cpu) runs on an 8-device virtual host mesh; the
# flag must be in place before any jax backend initializes
if "--cpu" in sys.argv and "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if "--cpu" in sys.argv:
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P
from jax import shard_map


def _model_pieces(model_name, batch_per_chip):
    """(cfg, model, loss_of_logits, batch_per_chip) for a benchmark
    vehicle; loss_of_logits(logits, tok) -> scalar is shared by the
    monolithic loss and the staged head stage so both trace the same
    ops."""
    from horovod_tpu.models.transformer import (
        BERT_LARGE, GPT2_MEDIUM, Bert, Transformer, TransformerConfig,
        causal_lm_loss, mlm_loss,
    )

    if model_name == "bert-large":
        cfg = dataclasses.replace(BERT_LARGE, max_seq_len=512)
        model = Bert(cfg)
        bpc = batch_per_chip or 8

        def loss_of_logits(logits, tok):
            loss, _ = mlm_loss(logits, tok, tok % 7 == 0)
            return loss
    elif model_name == "gpt2-medium":
        # remat + small per-chip batch: the overlap analysis cares about
        # the gradient all-reduce schedule, not the attention flavor —
        # plain XLA attention at the bench's batch 16 holds 16 GB of
        # f32 score buffers and cannot AOT-compile on a 16 GB chip
        cfg = dataclasses.replace(
            GPT2_MEDIUM, max_seq_len=1024, remat=True)
        model = Transformer(cfg)
        bpc = batch_per_chip or 4

        def loss_of_logits(logits, tok):
            loss, _ = causal_lm_loss(logits, tok)
            return loss
    elif model_name == "toy":
        cfg = TransformerConfig(
            vocab_size=512, num_layers=4, num_heads=8, hidden_size=512,
            max_seq_len=128, dtype=jnp.bfloat16)
        model = Transformer(cfg)
        bpc = batch_per_chip or 2

        def loss_of_logits(logits, tok):
            return jnp.mean((logits.astype(jnp.float32) - 1.0) ** 2)
    elif model_name == "tiny":
        # MLP-sized vehicle for the CPU schedule-ab gate in
        # run_all_checks.py: compiles in seconds, still 4 stacked
        # blocks + tied embeddings (the tied-grad completion edge the
        # scheduler must respect)
        cfg = TransformerConfig(
            vocab_size=64, num_layers=4, num_heads=2, hidden_size=32,
            max_seq_len=16, dtype=jnp.float32)
        model = Transformer(cfg)
        bpc = batch_per_chip or 2

        def loss_of_logits(logits, tok):
            loss, _ = causal_lm_loss(logits, tok)
            return loss
    else:
        raise ValueError(model_name)
    return cfg, model, loss_of_logits, bpc


def build_step(model_name, mesh, nchips, fusion_mb, batch_per_chip,
               zero=False, schedule="off", compression=None):
    """The REAL train step: same model config, loss, optimizer and
    sharding as the corresponding examples/ benchmark. With ``zero``,
    the ShardedOptimizer (bucketed reduce-scatter) path instead of the
    all-reduce path. ``schedule`` != "off" reroutes the backward
    through the backward-interleaved collective scheduler
    (hvd.overlap, docs/overlap.md); "off" is byte-for-byte the
    monolithic trace. ``compression`` names a wire ("int8", "bf16");
    None keeps the knob default."""
    import horovod_tpu as hvd

    cfg, model, loss_of_logits, bpc = _model_pieces(
        model_name, batch_per_chip)
    T = cfg.max_seq_len

    def loss_fn(p, tok):
        return loss_of_logits(model.apply({"params": p}, tok), tok)

    comp = hvd.Compression.lookup(compression) if compression else None

    toks_s = jax.ShapeDtypeStruct((bpc * nchips, T), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, T), jnp.int32)))["params"]
    if zero:
        opt = hvd.ShardedOptimizer(
            optax.adamw(1e-4),
            fusion_threshold_bytes=int(fusion_mb * (1 << 20)),
            compression=comp)
    else:
        opt = hvd.DistributedOptimizer(
            optax.adamw(1e-4),
            fusion_threshold_bytes=int(fusion_mb * (1 << 20)),
            compression=comp)
    state = jax.eval_shape(lambda: opt.init(jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), params)))
    if zero:
        state_specs = hvd.sharded_state_specs(state)
    else:
        state_specs = hvd.error_feedback_specs(state)

    if schedule != "off":
        # the head loss closes over the batch, so stages rebuild per
        # traced batch value
        svag = hvd.overlap.staged_value_and_grad(
            lambda b: hvd.overlap.transformer_lm_stages(
                model, b, lambda lg, _b=b: loss_of_logits(lg, _b)),
            opt=opt, mode=schedule)

        def step(p, s, b):
            l, g = svag(p, b, opt_state=s)
            upd, s = opt.update(g, s, p)
            return optax.apply_updates(p, upd), s, jax.lax.psum(
                l, "hvd").reshape(1)
    else:
        def step(p, s, b):
            l, g = jax.value_and_grad(loss_fn)(p, b)
            upd, s = opt.update(g, s, p)
            return optax.apply_updates(p, upd), s, jax.lax.psum(
                l, "hvd").reshape(1)

    js = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P(), state_specs, P("hvd")),
        out_specs=(P(), state_specs, P()), check_vma=False))
    return js, params, state, toks_s


def _ar_elems(line):
    """Result element count of an all-reduce HLO line (0 if unparsable)."""
    m = re.search(r'= \(?[a-z0-9]+\[([\d,]*)\]', line)
    if not m:
        return 0
    dims = m.group(1)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def analyze(txt, collective="all-reduce", min_elems: int = 10_000):
    """Schedule + dependency analysis of an optimized
    (is_scheduled=true) module, restricted to the ENTRY computation so
    fusion-body instructions don't pollute the counts.

    Two metrics:
    - overlap_window_frac: fraction of backward compute ops the
      SCHEDULER placed after the first gradient all-reduce. Bounded on
      this XLA build by the memory-minimizing list scheduler treating
      sync collectives as free-floating (see OVERLAP_r05.json note).
    - overlappable_frac: fraction of backward compute the first
      all-reduce does NOT transitively depend on — the schedule-
      independent STRUCTURAL bound that bucket availability ordering
      (ops/fusion._backward_availability_order) widens. This is the
      property the reference's backward-order grad hooks buy it.

    Only GRADIENT-bucket all-reduces count: the scalar loss psum is also
    an all-reduce and the scheduler can float it anywhere after forward,
    which silently fakes an overlap window (the round-4 artifact
    reported 8/203 backward ops after the 'first all-reduce' — that was
    partly the loss)."""
    all_lines = txt.splitlines()
    start = next(i for i, l in enumerate(all_lines)
                 if l.startswith("ENTRY"))
    lines = all_lines[start:]
    coll_re = rf' {collective}(-start)?\('
    ars = [i for i, l in enumerate(lines)
           if re.search(coll_re, l) and _ar_elems(l) >= min_elems]
    small_ars = [i for i, l in enumerate(lines)
                 if re.search(coll_re, l) and _ar_elems(l) < min_elems]
    bwd = [i for i, l in enumerate(lines)
           if "op_name=" in l and "transpose" in l
           and re.search(r' (dot|fusion|convolution|custom-call)\(', l)]
    after = sum(1 for b in bwd if b > ars[0]) if ars else 0

    # def-use graph of the entry computation -> transitive producer set
    # of the first gradient all-reduce
    defs, ops = {}, {}
    pat_lhs = re.compile(r'^\s*%([\w.-]+) = ')
    pat_ref = re.compile(r'%([\w.-]+)')
    for i, l in enumerate(lines):
        m = pat_lhs.match(l)
        if not m:
            continue
        defs[m.group(1)] = i
        body = l.split(" = ", 1)[1]
        ops[i] = pat_ref.findall(body)
    overlappable = None
    if ars:
        seen, stack = set(), [ars[0]]
        while stack:
            i = stack.pop()
            if i in seen:
                continue
            seen.add(i)
            for ref in ops.get(i, ()):
                j = defs.get(ref)
                if j is not None and j not in seen:
                    stack.append(j)
        free = [b for b in bwd if b not in seen]
        overlappable = round(len(free) / len(bwd), 4) if bwd else 0.0
    return {
        "scheduled": "is_scheduled=true" in txt,
        "bucket_all_reduces_in_optimized_hlo": len(ars),
        "scalar_all_reduces_excluded": len(small_ars),
        "backward_compute_ops": len(bwd),
        "backward_ops_scheduled_after_first_all_reduce": after,
        "overlap_window_frac": round(after / len(bwd), 4) if bwd else 0.0,
        "overlappable_frac": overlappable,
        "first_all_reduce_before_last_backward_op":
            bool(ars) and bool(bwd) and ars[0] < bwd[-1],
    }


_PAT_LHS = re.compile(r'^\s*%?([\w.-]+) = ')
_PAT_CALLS = re.compile(r'(?:to_apply|calls)=%?([\w.-]+)')


def _split_computations(txt):
    """Pre-opt HLO text → {computation name: body lines}. Computation
    headers sit at column 0 and end with '{'; bodies are indented and
    close with a column-0 '}'."""
    comps, name, body = {}, None, []
    for line in txt.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            head = line.strip().rstrip("{").strip()
            if head.startswith("ENTRY "):
                head = head[len("ENTRY "):]
            name = head.split(" ")[0].split("(")[0].lstrip("%")
            body = comps.setdefault(name, [])
        elif line.startswith("}"):
            name = None
        elif name is not None:
            body.append(line)
    return comps


def _comp_dot_counts(comps):
    """Per-computation dot/convolution count INCLUDING transitively
    called computations (remat bodies are calls in the pre-opt module,
    and their dots are the rematerialized backward compute)."""
    own = {}
    calls = {}
    for name, body in comps.items():
        own[name] = sum(1 for l in body
                        if re.search(r' (dot|convolution)\(', l))
        cs = set()
        for l in body:
            cs.update(_PAT_CALLS.findall(l))
        calls[name] = cs
    memo = {}

    def total(name, visiting=()):
        if name in memo:
            return memo[name]
        if name in visiting or name not in own:
            return 0
        t = own[name] + sum(total(c, visiting + (name,))
                            for c in calls[name])
        memo[name] = t
        return t

    return own, calls, total


def analyze_preopt(txt, min_elems: int = 10_000):
    """Structural analysis of the PRE-optimization HLO: how much
    compute sits in the first gradient all-reduce's transitive
    CONSUMER closure. Those ops must schedule after the collective
    under ANY correct scheduler — the forced-overlap proof that
    survives pipelines whose barrier expander erases
    optimization_barrier post-opt (XLA CPU), where the scheduled-module
    window is unreadable. With the backward-interleaved schedule the
    closure holds the later backward segments (dots_pinned ≫ 0); the
    monolithic chain's closure holds only barrier/update arithmetic
    (dots_pinned == 0). Analysis runs inside the computation holding
    the gradient collectives (the shard_map body), following
    to_apply/calls edges so remat'd backward dots count."""
    comps = _split_computations(txt)
    own_dots, _calls, total_dots = _comp_dot_counts(comps)

    def _grad_ars(body):
        # all-reduce (plain), reduce-scatter (ZeRO), all-to-all (the
        # int8 quantized wire's first exchange leg)
        return [i for i, l in enumerate(body)
                if re.search(r' (all-reduce|reduce-scatter|all-to-all)\(',
                             l)
                and _ar_elems(l) >= min_elems]

    # the computation carrying the gradient collectives
    best, ars = None, []
    for name, body in comps.items():
        a = _grad_ars(body)
        if len(a) > len(ars):
            best, ars = name, a
    out = {
        "gradient_all_reduces": len(ars),
        "opt_barriers": 0,
        "dots_total": 0,
        "dots_pinned_after_first_all_reduce": 0,
        "pinned_dot_frac": 0.0,
    }
    if best is None:
        return out
    body = comps[best]
    out["opt_barriers"] = sum(1 for l in body if " opt-barrier(" in l)
    dots_total = total_dots(best)
    out["dots_total"] = dots_total
    if not dots_total:
        return out
    defs, cons_of = {}, {}
    for i, l in enumerate(body):
        m = _PAT_LHS.match(l)
        if not m:
            continue
        defs[m.group(1)] = i
        # operand references: pre-opt instruction names are
        # `word.number` tokens (Arg_67.1374, dot.1763, call.1703);
        # to_apply=region targets match too but never resolve to an
        # instruction def, so they add no edges
        for ref in re.findall(r'([A-Za-z_][\w-]*\.\d+)',
                              l.split(" = ", 1)[1]):
            cons_of.setdefault(ref, []).append(i)
    # consumer closure of the first gradient collective
    names_by_line = {v: k for k, v in defs.items()}
    seen = {ars[0]}
    stack = [ars[0]]
    while stack:
        i = stack.pop()
        name = names_by_line.get(i)
        if name is None:
            continue
        for c in cons_of.get(name, ()):
            if c not in seen:
                seen.add(c)
                stack.append(c)
    pinned = 0
    for i in sorted(seen):
        l = body[i]
        if re.search(r' (dot|convolution)\(', l):
            pinned += 1
        for callee in _PAT_CALLS.findall(l):
            pinned += total_dots(callee)
    out["dots_pinned_after_first_all_reduce"] = pinned
    out["pinned_dot_frac"] = round(pinned / dots_total, 4)
    return out


def compile_and_analyze(model, mesh, nchips, fusion_mb, batch_per_chip,
                        zero=False, schedule="off", compression=None,
                        preopt=False, min_elems=10_000):
    js, params, state, toks_s = build_step(
        model, mesh, nchips, fusion_mb, batch_per_chip, zero=zero,
        schedule=schedule, compression=compression)
    low = js.lower(params, state, toks_s)
    # the ZeRO path's gradient collectives are per-bucket
    # reduce-scatters in the lowered program, but this XLA TPU build
    # decomposes reduce-scatter into all-reduce + slice in the
    # optimized module (verified: 0 reduce-scatter ops, bucket-count
    # all-reduces), so the schedule analysis reads all-reduces for
    # both paths; the post-update all-gathers are a separate op name
    # and never pollute the count
    r = analyze(low.compile().as_text(), min_elems=min_elems)
    if preopt:
        r["preopt"] = analyze_preopt(
            low.compiler_ir(dialect="hlo").as_hlo_text(),
            min_elems=min_elems)
    return r


def analyze_gather(txt, min_elems: int = 256):
    """Scheduled-module analysis of the FSDP forward (docs/fsdp.md):
    how much forward compute does the optimized schedule place BEFORE
    the LAST parameter all-gather issues — i.e. compute available to
    hide the gathers behind. The naive gather-everything-up-front
    lowering scores ~0 (every gather precedes all compute, and a full
    replicated copy of the model is live from t=0); the
    prefetch-interleaved schedule spreads the gathers through the
    forward and scores high. Plain-wire steps only: the int8 backward
    wire emits its own all-gathers and would pollute the count."""
    all_lines = txt.splitlines()
    start = next(i for i, l in enumerate(all_lines)
                 if l.startswith("ENTRY"))
    lines = all_lines[start:]
    ags = [i for i, l in enumerate(lines)
           if re.search(r' all-gather(-start)?\(', l)
           and _ar_elems(l) >= min_elems]
    fwd = [i for i, l in enumerate(lines)
           if "op_name=" in l and "transpose" not in l
           and re.search(r' (dot|fusion|convolution|custom-call)\(', l)]
    before = sum(1 for f in fwd if ags and f < ags[-1])
    return {
        "scheduled": "is_scheduled=true" in txt,
        "param_all_gathers_in_optimized_hlo": len(ags),
        "forward_compute_ops": len(fwd),
        "forward_ops_scheduled_before_last_all_gather": before,
        "gather_window_frac": round(before / len(fwd), 4) if fwd
        else 0.0,
    }


def analyze_gather_preopt(txt, min_elems: int = 256):
    """Structural analysis of the PRE-optimization HLO for the FSDP
    forward: how many forward dots sit in each parameter all-gather's
    transitive PRODUCER closure. A gather whose producers include
    compute cannot be hoisted to t=0 by ANY correct scheduler — the
    anti-hoist mirror of analyze_preopt's consumer-closure proof, and
    the evidence that survives pipelines whose barrier expander erases
    optimization_barrier post-opt (XLA CPU). With prefetch the LAST
    bucket's gather depends on nearly the whole forward
    (pinned_fwd_dot_frac ≫ 0); the up-front lowering's gathers depend
    on nothing (0 pinned)."""
    comps = _split_computations(txt)

    def _gathers(body):
        return [i for i, l in enumerate(body)
                if re.search(r' all-gather\(', l)
                and _ar_elems(l) >= min_elems]

    best, ags = None, []
    for name, body in comps.items():
        a = _gathers(body)
        if len(a) > len(ags):
            best, ags = name, a
    out = {
        "param_all_gathers": len(ags),
        "gathers_pinned_behind_compute": 0,
        "fwd_dots_total": 0,
        "fwd_dots_pinned_before_last_gather": 0,
        "pinned_fwd_dot_frac": 0.0,
    }
    if best is None:
        return out
    body = comps[best]
    fwd_dots = [i for i, l in enumerate(body)
                if re.search(r' (dot|convolution)\(', l)
                and "transpose" not in l]
    out["fwd_dots_total"] = len(fwd_dots)
    # def/operand maps (pre-opt names are word.number tokens)
    defs = {}
    refs_of = {}
    for i, l in enumerate(body):
        m = _PAT_LHS.match(l)
        if not m:
            continue
        defs[m.group(1)] = i
        refs_of[i] = re.findall(r'([A-Za-z_][\w-]*\.\d+)',
                                l.split(" = ", 1)[1])

    def producer_closure(start_i):
        seen, stack = set(), [start_i]
        while stack:
            i = stack.pop()
            if i in seen:
                continue
            seen.add(i)
            for ref in refs_of.get(i, ()):
                j = defs.get(ref)
                if j is not None and j not in seen:
                    stack.append(j)
        return seen

    fwd_set = set(fwd_dots)
    pinned_gathers = 0
    for g in ags:
        if producer_closure(g) & fwd_set:
            pinned_gathers += 1
    out["gathers_pinned_behind_compute"] = pinned_gathers
    if ags:
        last = producer_closure(ags[-1]) & fwd_set
        out["fwd_dots_pinned_before_last_gather"] = len(last)
        if fwd_dots:
            out["pinned_fwd_dot_frac"] = round(
                len(last) / len(fwd_dots), 4)
    return out


_PAT_VIEW = re.compile(
    r' (dynamic-slice|slice|reshape|bitcast|copy|transpose)\(')


def analyze_liveness_preopt(txt, min_elems: int = 256):
    """Within-step liveness of gathered parameter buckets in the
    PRE-optimization HLO: each parameter all-gather's text-order live
    interval runs from its definition to the LAST line where the
    gathered value — or any view-like alias of it (dynamic-slice,
    slice, reshape, bitcast, copy, transpose) — appears as an
    operand. Pre-opt text preserves trace order, so the maximum
    number of simultaneously-live intervals is the within-step peak
    gathered-bucket count the lowering commits to before any
    scheduler runs: the saved-gather policy keeps every forward
    gather's buffer alive across the forward→backward boundary
    (max_live ≈ bucket count), the regather policy drops each bucket
    at its last same-phase use and re-issues the collective on
    backward (max_live ≈ prefetch depth + O(1) working set). An
    operand use inside a called computation is charged to the call
    line — remat bodies stay opaque, the call itself is the use."""
    comps = _split_computations(txt)

    def _gathers(body):
        return [i for i, l in enumerate(body)
                if re.search(r' all-gather\(', l)
                and _ar_elems(l) >= min_elems]

    best, ags = None, []
    for name, body in comps.items():
        a = _gathers(body)
        if len(a) > len(ags):
            best, ags = name, a
    out = {"param_all_gathers": len(ags), "max_live_gathers": 0,
           "live_intervals": []}
    if best is None:
        return out
    body = comps[best]
    lhs, refs = [], []
    for l in body:
        m = _PAT_LHS.match(l)
        lhs.append(m.group(1) if m else None)
        refs.append(re.findall(r'([A-Za-z_][\w-]*\.\d+)',
                               l.split(" = ", 1)[1])
                    if m and " = " in l else [])
    intervals = []
    for g in ags:
        aliases = {lhs[g]}
        end = g
        for i in range(g + 1, len(body)):
            if not aliases.intersection(refs[i]):
                continue
            end = i
            if lhs[i] and _PAT_VIEW.search(body[i]):
                aliases.add(lhs[i])
        intervals.append((g, end))
    events = []
    for s, e in intervals:
        events.append((s, 1))
        events.append((e + 1, -1))
    live = peak = 0
    for _, d in sorted(events):
        live += d
        peak = max(peak, live)
    out["max_live_gathers"] = peak
    out["live_intervals"] = [[s, e] for s, e in intervals]
    return out


def build_fsdp_step(model_name, mesh, nchips, fusion_mb, batch_per_chip,
                    mode="prefetch", compression=None, prefetch=None,
                    regather=None, offload=None):
    """The FSDP train step over sharded parameter rows: same model
    config/loss/optimizer as build_step, parameters living as
    per-bucket row shards (optim/fsdp.py). ``mode="upfront"`` is the
    naive gather-everything-at-t0 reference; ``"prefetch"`` the
    interleaved schedule; ``regather``/``offload`` thread through to
    the staged path (None = session knobs, docs/fsdp.md). Returns
    (jitted step, rows, state, token shape, layout)."""
    import horovod_tpu as hvd
    from horovod_tpu.optim import fsdp as fsdp_mod

    cfg, model, loss_of_logits, bpc = _model_pieces(
        model_name, batch_per_chip)
    T = cfg.max_seq_len
    comp = hvd.Compression.lookup(compression) if compression else None
    toks_s = jax.ShapeDtypeStruct((bpc * nchips, T), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, T), jnp.int32)))["params"]
    opt = hvd.FullyShardedOptimizer(
        optax.adamw(1e-4),
        fusion_threshold_bytes=int(fusion_mb * (1 << 20)),
        compression=comp)
    layout = fsdp_mod.fsdp_layout(
        params, world=nchips,
        fusion_threshold_bytes=int(fusion_mb * (1 << 20)))
    rows_s = {
        k: jax.ShapeDtypeStruct((nchips, layout.ks[i]),
                                layout.dtypes[i])
        for i, k in enumerate(
            fsdp_mod.bucket_name(j) for j in range(len(layout.plans)))
    }
    state = jax.eval_shape(lambda: opt.init(jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), params)))
    state_specs = hvd.sharded_state_specs(state)
    row_specs = fsdp_mod.param_row_specs(layout)

    def stages_for(b):
        return hvd.overlap.transformer_lm_stages(
            model, b, lambda lg, _b=b: loss_of_logits(lg, _b))

    vag = fsdp_mod.fsdp_value_and_grad(stages_for, opt, layout,
                                       mode=mode, prefetch=prefetch,
                                       regather=regather,
                                       offload=offload)

    def step(r, s, b):
        l, g = vag(r, b, opt_state=s)
        upd, s = opt.update(g, s, fsdp_mod.local_shards(r, layout))
        r = fsdp_mod.apply_shard_updates(r, upd, layout)
        return r, s, jax.lax.psum(l, "hvd").reshape(1)

    js = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(row_specs, state_specs, P("hvd")),
        out_specs=(row_specs, state_specs, P()), check_vma=False))
    return js, rows_s, state, toks_s, layout


def _fsdp_compile_and_analyze(model, mesh, nchips, fusion_mb,
                              batch_per_chip, mode, compression=None,
                              min_elems=256):
    js, rows_s, state, toks_s, _ = build_fsdp_step(
        model, mesh, nchips, fusion_mb, batch_per_chip, mode=mode,
        compression=compression)
    low = js.lower(rows_s, state, toks_s)
    # serialize the pre-opt module ONCE (tens of MB on the real
    # vehicles) and feed both analyzers
    preopt_txt = low.compiler_ir(dialect="hlo").as_hlo_text()
    r = analyze_gather(low.compile().as_text(), min_elems=min_elems)
    r["preopt"] = analyze_gather_preopt(preopt_txt,
                                        min_elems=min_elems)
    # the backward half still rides the staged reduce-scatter path —
    # reuse the consumer-closure proof so one artifact shows both
    # directions pinned
    r["preopt_backward"] = analyze_preopt(preopt_txt,
                                          min_elems=min_elems)
    return r


def trees_bitwise_equal(a, b):
    """Structure + leaf-wise np.array_equal over two pytrees — the
    shared parity predicate of the fsdp/overlap/autotune gates
    (scripts/fsdp_check.py and scripts/autotune_check.py import it so
    the gates can never drift in strictness). Structures are compared
    first: a bare leaf-zip would truncate at the shorter list and call
    structurally different outputs "bitwise"."""
    import numpy as np

    if (jax.tree_util.tree_structure(a)
            != jax.tree_util.tree_structure(b)):
        return False
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)))


def _fsdp_cpu_exec_ab(model, mesh, nchips, fusion_mb, batch_per_chip,
                      compression, steps=4):
    """Execute upfront/prefetch steps on the CPU host mesh: bitwise
    parity of one step (params rows, optimizer state, loss) + median
    wall step time for each mode."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.optim import fsdp as fsdp_mod

    cfg, model_obj, _, bpc = _model_pieces(model, batch_per_chip)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (bpc * nchips, cfg.max_seq_len)),
        jnp.int32)
    params = model_obj.init(jax.random.PRNGKey(0), toks[:1])["params"]
    comp = hvd.Compression.lookup(compression) if compression else None
    out, results = {}, {}
    for mode in ("upfront", "prefetch"):
        js, _, _, _, layout = build_fsdp_step(
            model, mesh, nchips, fusion_mb, batch_per_chip, mode=mode,
            compression=compression)
        opt = hvd.FullyShardedOptimizer(
            optax.adamw(1e-4),
            fusion_threshold_bytes=int(fusion_mb * (1 << 20)),
            compression=comp)
        rows = fsdp_mod.shard_params(params, layout)
        state = opt.init(params)
        r = js(rows, state, toks)
        jax.block_until_ready(r)
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            r2 = js(rows, state, toks)
            jax.block_until_ready(r2)
            times.append(time.perf_counter() - t0)
        results[mode] = r
        out[f"step_time_ms_{mode}"] = round(_median(times) * 1e3, 2)
    out["params_bitwise_equal"] = trees_bitwise_equal(
        results["upfront"][0], results["prefetch"][0])
    out["state_bitwise_equal"] = trees_bitwise_equal(
        results["upfront"][1], results["prefetch"][1])
    out["loss_bitwise_equal"] = trees_bitwise_equal(
        results["upfront"][2], results["prefetch"][2])
    return out


_FSDP_AB_NOTE = (
    "FSDP A/B: off = naive gather-everything-up-front lowering (every "
    "parameter all-gather unpinned at t=0 — a full replicated copy of "
    "the model is live for the whole step); on = prefetch-interleaved "
    "forward (hvd.fsdp, docs/fsdp.md) — bucket k+1's all-gather is "
    "pinned BEHIND the activation entering segment k via "
    "optimization_barrier, so it cannot hoist to t=0 yet overlaps "
    "segment k's compute, and the gathered buffer drops after last "
    "use. gather_window_frac = forward compute the optimized schedule "
    "places before the last parameter all-gather (compute available "
    "to hide gathers); preopt.pinned_fwd_dot_frac = forward dots in "
    "the last gather's transitive PRODUCER closure — a dependency any "
    "correct scheduler must respect, the anti-hoist lower bound that "
    "survives barrier-expanding backends. preopt_backward shows the "
    "reduce-scatters still pin backward compute (the PR 9 property, "
    "now on the FSDP path). step_time_ms rows appear only in --cpu "
    "mode."
)


def fsdp_ab(args):
    """--fsdp-ab: prefetch-vs-upfront A/B of the fully-sharded
    parameter step into one JSON artifact (the `fsdp` run_all_checks
    gate drives the --cpu --check form via scripts/fsdp_check.py)."""
    import horovod_tpu as hvd

    if args.cpu:
        hvd.shutdown()
        hvd.init()
        mesh = hvd.mesh()
        nchips = len(jax.devices())
        topo_name = f"cpu host mesh ({nchips} devices)"
    else:
        from jax.experimental import topologies

        topology = args.topology.split(",")[0]
        topo = topologies.get_topology_desc(
            topology_name=topology, platform="tpu")
        nchips = len(topo.devices)
        mesh = topologies.make_mesh(topo, (nchips,), ("hvd",))
        hvd.shutdown()
        hvd.init(mesh=mesh)
        topo_name = f"{topology} ({nchips} chips, AOT)"

    rows, failures = [], []
    for model in args.model.split(","):
        min_elems = 256 if model in ("tiny", "toy") else 10_000
        row = {
            "model": model, "topology": topo_name,
            "fusion_mb": args.fusion_mb, "wire": "none",
        }
        t0 = time.perf_counter()
        off = _fsdp_compile_and_analyze(
            model, mesh, nchips, args.fusion_mb, args.batch_per_chip,
            "upfront", min_elems=min_elems)
        on = _fsdp_compile_and_analyze(
            model, mesh, nchips, args.fusion_mb, args.batch_per_chip,
            "prefetch", min_elems=min_elems)
        row["off"] = off
        row["on"] = on
        row["window_delta"] = round(
            on["gather_window_frac"] - off["gather_window_frac"], 4)
        row["compile_wall_s"] = round(time.perf_counter() - t0, 1)
        if args.cpu:
            row["exec"] = _fsdp_cpu_exec_ab(
                model, mesh, nchips, args.fusion_mb,
                args.batch_per_chip, None)
            row["exec_int8"] = _fsdp_cpu_exec_ab(
                model, mesh, nchips, args.fusion_mb,
                args.batch_per_chip, "int8")
        rows.append(row)
        print(json.dumps(row), flush=True)

        if args.check:
            # the pinned fraction scales with depth: the last-needed
            # bucket's gather pins everything before its prefetch
            # boundary, ~ (S-3)/S of forward for S stages — ≥ 0.5 on
            # the 26-stage BERT-L vehicle, structurally ~0.25 on the
            # 6-stage tiny gate vehicle
            floor = 0.2 if model in ("tiny", "toy") else 0.5
            pin_on = on["preopt"]["pinned_fwd_dot_frac"]
            pin_off = off["preopt"]["gathers_pinned_behind_compute"]
            if pin_on < floor:
                failures.append(
                    f"{model}: prefetch pins only {pin_on} of forward "
                    f"compute before the last gather (floor {floor})")
            if pin_off != 0:
                failures.append(
                    f"{model}: upfront lowering unexpectedly pins "
                    f"{pin_off} gathers — off is no longer the naive "
                    f"reference")
            if on["preopt_backward"][
                    "dots_pinned_after_first_all_reduce"] <= 0:
                failures.append(
                    f"{model}: FSDP backward pins no compute behind "
                    f"the first reduce-scatter")
            if args.cpu:
                for key in ("exec", "exec_int8"):
                    e = row[key]
                    if not (e["params_bitwise_equal"]
                            and e["state_bitwise_equal"]
                            and e["loss_bitwise_equal"]):
                        failures.append(
                            f"{model}/{key}: prefetch vs upfront NOT "
                            f"bitwise equal")

    doc = {"note": _FSDP_AB_NOTE, "runs": rows}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
    if args.check:
        if failures:
            for fmsg in failures:
                print("fsdp-ab check FAILED:", fmsg)
            return 1
        print(f"fsdp-ab check OK: {len(rows)} A/B rows, bitwise "
              f"parity + gather pin structure hold"
              + (f", artifact {args.out}" if args.out else ""))
    return 0


_NOTE = (
    "overlap_window_frac = fraction of backward compute ops the "
    "optimized schedule places after the first gradient all-reduce "
    "issues; overlappable_frac = fraction the first all-reduce does "
    "not transitively depend on (the schedule-independent bound that "
    "backward-availability bucket ordering widens). "
    "optimization_barrier chaining keeps one all-reduce per fusion "
    "bucket. This XLA build emits TPU all-reduce synchronously in HLO "
    "(no start/done pair surfaces) - schedule position is the "
    "observable overlap property."
)

_AB_NOTE = (
    "schedule A/B: off = monolithic backward (today's trace, "
    "bit-for-bit); on = backward-interleaved collective scheduler "
    "(HOROVOD_OVERLAP_SCHEDULE, hvd.overlap) — backward traced in "
    "fusion-bucket-aligned segments, each bucket's collective issued "
    "at its availability boundary and pinned before the next "
    "segment's compute through the inter-segment cotangent. "
    "preopt.dots_pinned... counts compute in the first gradient "
    "collective's transitive CONSUMER closure in the unoptimized "
    "module: a dependency ANY correct scheduler must respect, so "
    "pinned_dot_frac lower-bounds the achievable window on every "
    "backend (including ones whose barrier expander hides the "
    "post-opt evidence). step_time_ms rows appear only in --cpu mode "
    "(AOT programs for v5e cannot execute here)."
)


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _cpu_exec_ab(model, mesh, nchips, fusion_mb, batch_per_chip, zero,
                 schedule, compression, steps=4):
    """Execute off/on steps on the CPU host mesh: bitwise parity of one
    step + median wall step time for each mode."""
    import numpy as np

    cfg, m, _, bpc = _model_pieces(model, batch_per_chip)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (bpc * nchips, cfg.max_seq_len)),
        jnp.int32)
    out = {}
    results = {}
    for mode_name, sched in (("off", "off"), ("on", schedule)):
        js, params_s, state_s, _ = build_step(
            model, mesh, nchips, fusion_mb, batch_per_chip, zero=zero,
            schedule=sched, compression=compression)
        m2 = _model_pieces(model, batch_per_chip)[1]
        params = m2.init(jax.random.PRNGKey(0), toks[:1])["params"]
        import horovod_tpu as hvd
        comp = hvd.Compression.lookup(compression) if compression else None
        if zero:
            opt = hvd.ShardedOptimizer(
                optax.adamw(1e-4),
                fusion_threshold_bytes=int(fusion_mb * (1 << 20)),
                compression=comp)
        else:
            opt = hvd.DistributedOptimizer(
                optax.adamw(1e-4),
                fusion_threshold_bytes=int(fusion_mb * (1 << 20)),
                compression=comp)
        state = opt.init(params)
        r = js(params, state, toks)
        jax.block_until_ready(r)
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            r2 = js(params, state, toks)
            jax.block_until_ready(r2)
            times.append(time.perf_counter() - t0)
        results[mode_name] = r
        out[f"step_time_ms_{mode_name}"] = round(_median(times) * 1e3, 2)
    leaves_a = jax.tree_util.tree_leaves(results["off"][0])
    leaves_b = jax.tree_util.tree_leaves(results["on"][0])
    out["params_bitwise_equal"] = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(leaves_a, leaves_b))
    out["loss_bitwise_equal"] = bool(np.array_equal(
        np.asarray(results["off"][2]), np.asarray(results["on"][2])))
    out["step_time_delta_frac"] = round(
        (out["step_time_ms_on"] - out["step_time_ms_off"])
        / max(out["step_time_ms_off"], 1e-9), 4)
    return out


def schedule_ab(args):
    """--schedule-ab: scheduled-vs-unscheduled A/B over the benchmark
    matrix into one JSON artifact (the 8th run_all_checks gate drives
    the --cpu --check form)."""
    import horovod_tpu as hvd
    from horovod_tpu.core.state import global_state

    mode = hvd.overlap.normalize_mode(args.overlap_schedule or "stage")
    if mode == "off":
        raise SystemExit(
            "--schedule-ab compares an active schedule against off; "
            "pass --overlap-schedule stage|double (or omit it)")
    paths = []
    for p in args.paths.split(","):
        p = p.strip()
        if p == "plain":
            paths.append(("allreduce", False, None))
        elif p == "zero":
            paths.append(("zero", True, None))
        elif p == "int8":
            paths.append(("allreduce+int8", False, "int8"))
        elif p in ("bf16", "fp16"):
            paths.append((f"allreduce+{p}", False, p))
        elif p == "zero-int8":
            paths.append(("zero+int8", True, "int8"))
        else:
            raise SystemExit(f"unknown --paths entry {p!r}")

    if args.cpu:
        hvd.shutdown()
        hvd.init()
        mesh = hvd.mesh()
        nchips = len(jax.devices())
        topo_name = f"cpu host mesh ({nchips} devices)"
    else:
        from jax.experimental import topologies

        topology = args.topology.split(",")[0]
        topo = topologies.get_topology_desc(
            topology_name=topology, platform="tpu")
        nchips = len(topo.devices)
        mesh = topologies.make_mesh(topo, (nchips,), ("hvd",))
        hvd.shutdown()
        hvd.init(mesh=mesh)
        topo_name = f"{topology} ({nchips} chips, AOT)"

    rows = []
    failures = []
    for model in args.model.split(","):
        for path_name, zero, wire in paths:
            row = {
                "model": model, "optimizer": path_name,
                "wire": wire or "none", "schedule_mode": mode,
                "topology": topo_name, "fusion_mb": args.fusion_mb,
            }
            t0 = time.perf_counter()
            # small vehicles' buckets sit under the 10k-element
            # gradient-AR floor real models use
            min_elems = 256 if model in ("tiny", "toy") else 10_000
            off = compile_and_analyze(
                model, mesh, nchips, args.fusion_mb,
                args.batch_per_chip, zero=zero, schedule="off",
                compression=wire, preopt=True,
                min_elems=min_elems)
            on = compile_and_analyze(
                model, mesh, nchips, args.fusion_mb,
                args.batch_per_chip, zero=zero, schedule=mode,
                compression=wire, preopt=True,
                min_elems=min_elems)
            row["off"] = off
            row["on"] = on
            row["window_delta"] = round(
                on["overlap_window_frac"] - off["overlap_window_frac"],
                4)
            row["compile_wall_s"] = round(time.perf_counter() - t0, 1)
            if args.cpu:
                row["exec"] = _cpu_exec_ab(
                    model, mesh, nchips, args.fusion_mb,
                    args.batch_per_chip, zero, mode, wire)
            rows.append(row)
            print(json.dumps(row), flush=True)

            if args.check:
                pin_on = on.get("preopt", {}).get(
                    "dots_pinned_after_first_all_reduce", 0)
                pin_off = off.get("preopt", {}).get(
                    "dots_pinned_after_first_all_reduce", 0)
                if pin_on <= 0:
                    failures.append(
                        f"{model}/{path_name}: schedule-on pins no "
                        f"backward compute behind the first collective")
                if pin_off != 0:
                    failures.append(
                        f"{model}/{path_name}: schedule-off "
                        f"unexpectedly pins compute ({pin_off} dots) — "
                        f"off is no longer today's trace")
                if args.cpu and not (
                        row["exec"]["params_bitwise_equal"]
                        and row["exec"]["loss_bitwise_equal"]):
                    failures.append(
                        f"{model}/{path_name}: schedule on/off params "
                        f"or loss NOT bitwise equal")

    doc = {"note": _AB_NOTE, "schedule_mode": mode, "runs": rows}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
    if args.check:
        if failures:
            for fmsg in failures:
                print("schedule-ab check FAILED:", fmsg)
            return 1
        print(f"schedule-ab check OK: {len(rows)} A/B rows, "
              f"bitwise parity + pinned structure hold"
              + (f", artifact {args.out}" if args.out else ""))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    ap.add_argument("--topology", default="v5e:2x4",
                    help="comma list of AOT topologies, e.g. v5e:2x4 "
                         "(8 chips) or v5e:16x16 (256 chips - the "
                         "BASELINE scale)")
    ap.add_argument("--model", default="bert-large",
                    help="comma list of: toy, tiny, bert-large, "
                         "gpt2-medium")
    ap.add_argument("--fusion-mb", type=float, default=128,
                    help="fusion threshold in MB; fractions allowed "
                         "for the small A/B vehicles (default = the "
                         "knob default)")
    ap.add_argument("--batch-per-chip", type=int, default=0)
    ap.add_argument("--zero", action="store_true",
                    help="analyze the ShardedOptimizer (ZeRO-1 bucketed "
                         "reduce-scatter) step instead of all-reduce")
    ap.add_argument("--overlap-schedule", default="",
                    choices=["", "off", "stage", "double"],
                    help="trace the step through the backward-"
                         "interleaved collective scheduler "
                         "(hvd.overlap, docs/overlap.md)")
    ap.add_argument("--schedule-ab", action="store_true",
                    help="scheduled-vs-unscheduled A/B over --model x "
                         "--paths into one artifact (--out)")
    ap.add_argument("--fsdp-ab", action="store_true",
                    help="prefetch-vs-upfront A/B of the fully-sharded "
                         "parameter step (hvd.fsdp, docs/fsdp.md) into "
                         "one artifact (--out)")
    ap.add_argument("--paths", default="plain,zero,int8",
                    help="--schedule-ab optimizer paths: plain, zero, "
                         "int8, bf16, zero-int8")
    ap.add_argument("--cpu", action="store_true",
                    help="run the A/B on the 8-device virtual CPU host "
                         "mesh (executes steps: bitwise parity + step "
                         "times) instead of AOT-compiling for v5e")
    ap.add_argument("--check", action="store_true",
                    help="gate mode for --schedule-ab: exit nonzero "
                         "unless parity + pinned structure hold")
    ap.add_argument("--sweep", action="store_true",
                    help="bucket order x fusion threshold table instead "
                         "of a single artifact")
    args = ap.parse_args(argv)

    import horovod_tpu as hvd
    from horovod_tpu.core.state import global_state

    if args.schedule_ab:
        return schedule_ab(args)
    if args.fsdp_ab:
        return fsdp_ab(args)

    from jax.experimental import topologies

    rows = []
    for topology in args.topology.split(","):
        topo = topologies.get_topology_desc(
            topology_name=topology, platform="tpu")
        nchips = len(topo.devices)
        mesh = topologies.make_mesh(topo, (nchips,), ("hvd",))
        hvd.shutdown()
        hvd.init(mesh=mesh)
        knobs = global_state().knobs

        if args.sweep:
            for backward in (False, True):
                for mb in (4, 16, 32):
                    knobs.bucket_backward_order = backward
                    r = compile_and_analyze(
                        args.model.split(",")[0], mesh, nchips, mb,
                        args.batch_per_chip)
                    r.update(bucket_backward_order=backward,
                             fusion_mb=mb)
                    rows.append(r)
                    print(json.dumps(r), flush=True)
            print("\norder  mb   ARs  window")
            for r in rows:
                print(
                    f"{'bwd' if r['bucket_backward_order'] else 'fwd':5}"
                    f"{r['fusion_mb']:4}  "
                    f"{r['bucket_all_reduces_in_optimized_hlo']:4} "
                    f"{r['overlap_window_frac']:7.1%}")
            return

        for model in args.model.split(","):
            r = compile_and_analyze(
                model, mesh, nchips, args.fusion_mb,
                args.batch_per_chip, zero=args.zero,
                schedule=args.overlap_schedule or "off")
            r.update({
                "optimizer": "zero" if args.zero else "allreduce",
                "model": model,
                "topology": f"{topology} ({nchips} chips, AOT)",
                "fusion_mb": args.fusion_mb,
                "bucket_backward_order": knobs.bucket_backward_order,
                "ordered_buckets_knob": knobs.ordered_buckets,
                "overlap_schedule": args.overlap_schedule or "off",
            })
            rows.append(r)
            print(json.dumps(r), flush=True)

    doc = {"note": _NOTE, "runs": rows}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")


if __name__ == "__main__":
    sys.exit(main() or 0)
