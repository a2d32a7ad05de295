"""Backward-interleaved collective scheduler (HOROVOD_OVERLAP_SCHEDULE).

The monolithic SPMD step hands XLA one backward pass and a chain of
per-bucket collectives, and hopes the scheduler interleaves them. It
doesn't: on the real BERT-Large step AOT-compiled for v5e, the first
gradient all-reduce depends on only ~9% of backward compute
(``overlappable_frac 0.91``) yet the memory-minimizing scheduler places
just 26% of backward after it — and 1.6% on the ZeRO path
(OVERLAP_r05.json). The reference never had this problem: its grad
hooks fire *during* backward and the background loop launches each
fused response as soon as its tensors arrive (torch/optimizer.py:176,
controller.cc:830). This module is the compile-time equivalent of that
runtime behavior:

* the backward pass is traced as a sequence of **segments** (reverse
  layer order — the order backward actually runs) via per-segment
  ``jax.vjp`` over a stage decomposition of the forward;
* each fusion bucket's collective is issued at the first segment
  boundary where all of its gradients exist (the same
  backward-availability bucket plan ``ops/fusion.py`` builds);
* the issued collective is **pinned before the next segment's compute**
  by routing the inter-segment cotangent through
  ``lax.optimization_barrier`` with the collective's result — a real
  dependency edge every scheduler must respect, so the scheduled
  window can no longer collapse below the structural bound;
* ``double`` mode additionally defers the optimizer's consumption of
  early buckets until the last segment retires, so update arithmetic
  cannot interleave into mid-backward and raise peak memory.

The user-facing optimizer API is unchanged: ``DistributedOptimizer``/
``ShardedOptimizer.update`` accept the staged gradients this module
produces and skip their own reduction (the collectives already ran
inside the backward, on the same compressed wire — int8
quantize/dequantize rides inside the staged segment). With the knob
off, callers keep their monolithic ``jax.value_and_grad`` path, which
is bit-for-bit today's trace. See docs/overlap.md.
"""

from __future__ import annotations

from typing import Any, Callable, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import collectives
from .collectives import ReduceOp
from .fusion import (bucket_issue_schedule, bucket_prefetch_schedule,
                     bucket_regather_schedule, pack_buckets_by_plan,
                     plan_bucket_lengths, pytree_bucket_plan,
                     unflatten_buckets_by_plan)

_MODES = ("off", "stage", "double")


def normalize_mode(value) -> str:
    """Map knob spellings onto the canonical mode names: ``off``
    (default), ``stage`` (backward-interleaved issue), ``double``
    (+ deferred optimizer consumption). Accepts 0/1/on/off aliases so
    ``HOROVOD_OVERLAP_SCHEDULE=1`` does the expected thing."""
    v = str(value or "off").strip().lower()
    if v in ("", "0", "false", "no", "off", "none"):
        return "off"
    if v in ("1", "true", "yes", "on", "stage"):
        return "stage"
    if v in ("2", "double", "double-buffer", "double_buffer"):
        return "double"
    raise ValueError(
        f"unknown overlap schedule {value!r} — expected one of "
        f"{_MODES} (HOROVOD_OVERLAP_SCHEDULE, docs/overlap.md)")


def schedule_mode(knobs=None) -> str:
    """The process-wide schedule mode, knob-resolved."""
    if knobs is None:
        from ..core.state import global_state

        knobs = global_state().knobs
    return normalize_mode(getattr(knobs, "overlap_schedule", "off"))


def active(knobs=None) -> bool:
    """True when the backward-interleaved schedule is on — the branch
    callers take between their monolithic step (off: bit-for-bit
    today's trace) and :func:`staged_value_and_grad`."""
    return schedule_mode(knobs) != "off"


class Stage(NamedTuple):
    """One forward segment: ``fwd(sub_params, carry) -> carry`` where
    ``sub_params`` is ``{key: params[key]}`` for this stage's top-level
    ``keys``. The first stage closes over the batch (its carry is a
    dummy scalar); the last stage returns the scalar loss. Backward
    runs the stages in reverse, one ``jax.vjp`` each."""

    name: str
    keys: tuple
    fwd: Callable


class StagedGrads:
    """Gradients reduced *inside* the backward by the staged scheduler.
    ``DistributedOptimizer.update`` unwraps this and skips its own
    reduction. Same-trace carrier only — do not pass across a jit
    boundary."""

    __slots__ = ("tree", "new_residual")

    def __init__(self, tree, new_residual=None):
        self.tree = tree
        self.new_residual = new_residual


class StagedShards:
    """Per-bucket averaged gradient shards produced by the staged
    scheduler on the ZeRO/FSDP paths (already reduce-scattered).
    ``ShardedOptimizer.update`` / ``FullyShardedOptimizer.update``
    consume the shards directly. ``new_residuals`` carries the updated
    rank-private error-feedback rows on the FSDP int8 wire (None
    elsewhere — ZeRO-1 runs the int8 exchange without a residual,
    docs/zero.md)."""

    __slots__ = ("shards", "new_residuals")

    def __init__(self, shards, new_residuals=None):
        self.shards = list(shards)
        self.new_residuals = (None if new_residuals is None
                              else list(new_residuals))


# ---------------------------------------------------------------------------
# reducer introspection
# ---------------------------------------------------------------------------

def _reducer_info(opt) -> dict:
    """The reduction recipe attached by DistributedOptimizer /
    ShardedOptimizer to their update fn (kind, op, compression, axes,
    threshold...). Raising here — not deep in the trace — when the
    optimizer can't ride the staged schedule."""
    if opt is None:
        from ..optim.compression import Compression

        return dict(kind="allreduce", op=ReduceOp.AVERAGE,
                    compression=Compression.from_knobs(),
                    process_set=None, axis_name=None,
                    fusion_threshold_bytes=None,
                    gradient_predivide_factor=1.0,
                    backward_passes_per_step=1, error_feedback=False,
                    plain=True)
    info = getattr(getattr(opt, "update", None), "_hvd_overlap_info",
                   None)
    if info is None:
        raise ValueError(
            "staged_value_and_grad needs an hvd.DistributedOptimizer or "
            "hvd.ShardedOptimizer (or opt=None for a bare averaged "
            "reduce); got an optimizer without overlap metadata — "
            "docs/overlap.md")
    info = dict(info)
    info["plain"] = False
    unsupported = check_supported(info)
    if unsupported:
        raise ValueError(
            f"the backward-interleaved schedule does not support this "
            f"optimizer configuration: {unsupported} (docs/overlap.md)")
    return info


def check_supported(info) -> Optional[str]:
    """None when the staged schedule can drive this reducer; otherwise
    a human-readable reason (used both to raise explicitly and to fall
    back silently in auto-wiring like parallel/train.py)."""
    if info is None:
        return "optimizer carries no overlap metadata"
    if info.get("backward_passes_per_step", 1) != 1:
        return ("backward_passes_per_step > 1 accumulates locally "
                "before reducing; the staged schedule reduces every "
                "step")
    if info["kind"] == "allreduce" and info["op"] not in (
            ReduceOp.SUM, ReduceOp.AVERAGE):
        return f"reduce op {info['op']} (only SUM/AVERAGE stage)"
    ps = info.get("process_set")
    if ps is not None and getattr(ps, "process_set_id", 0) != 0:
        return "proper-subset process sets"
    return None


# ---------------------------------------------------------------------------
# stage decompositions
# ---------------------------------------------------------------------------

def transformer_lm_stages(model, tokens, loss_fn, positions=None,
                          mask=None) -> List[Stage]:
    """Decompose a ``models.transformer.Transformer`` forward + loss
    into backward segments: embed → block_0..N → head(+loss). Built
    from the SAME pieces the monolithic ``model.apply`` is made of
    (models/transformer.py: ``Embedding``, ``build_block`` over
    ``layer_specs``, ``LmHead``, each applied alone over its sub-tree
    of the parameters), so composing the stages reproduces the
    monolithic forward op-for-op — the property the bitwise
    schedule-on/off parity tests rest on.

    ``loss_fn(logits) -> scalar`` closes over the labels/targets.
    """
    from ..models.transformer import (
        Embedding, LmHead, build_block, embedding_keys, head_keys,
        layer_specs)

    cfg = model.cfg
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))

    def embed_fwd(sub, carry):
        return Embedding(cfg).apply({"params": sub}, tokens, positions)

    stages = [Stage("embed", embedding_keys(cfg), embed_fwd)]

    for spec in layer_specs(cfg):
        key = f"block_{spec.index}"

        def blk_fwd(sub, carry, _key=key, _spec=spec):
            return build_block(cfg, _spec, model.attention_fn).apply(
                {"params": sub[_key]}, carry, positions, mask)

        stages.append(Stage(key, (key,), blk_fwd))

    def head_fwd(sub, carry):
        return loss_fn(LmHead(cfg).apply({"params": sub}, carry))

    stages.append(Stage("head", head_keys(cfg), head_fwd))
    return stages


def stack_stages(input_fn: Callable, layers: Sequence, head_fn: Callable,
                 head_keys: tuple = ()) -> List[Stage]:
    """Stage decomposition for a plain layer stack (the overlap gate's
    MLP vehicle, or any hand-segmented model):

    * ``input_fn() -> carry`` closes over the batch (a no-param stage);
    * ``layers`` is a sequence of ``(key, fwd)`` where
      ``fwd(layer_params, carry) -> carry`` receives ``params[key]``;
    * ``head_fn(sub, carry) -> scalar loss`` receives ``{k: params[k]}``
      for ``head_keys``.
    """
    stages = [Stage("input", (), lambda sub, c: input_fn())]
    for key, fwd in layers:
        stages.append(Stage(
            key, (key,),
            lambda sub, c, _f=fwd, _k=key: _f(sub[_k], c)))
    stages.append(Stage("head", tuple(head_keys), head_fn))
    return stages


# ---------------------------------------------------------------------------
# the staged value-and-grad
# ---------------------------------------------------------------------------

def _leaf_index_maps(params, stages):
    """Full-tree leaf bookkeeping: (path->idx, per-leaf contributing
    stage ids). A leaf referenced by several stages (tied embeddings)
    accumulates one grad contribution per stage and becomes
    bucket-ready only after its LAST contributing stage."""
    paths_leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    path_to_idx = {jax.tree_util.keystr(p): i
                   for i, (p, _) in enumerate(paths_leaves)}
    leaf_stages: List[list] = [[] for _ in paths_leaves]
    top_keys = set()
    for si, st in enumerate(stages):
        top_keys.update(st.keys)
        sub = {k: params[k] for k in st.keys}
        for p, _ in jax.tree_util.tree_flatten_with_path(sub)[0]:
            leaf_stages[path_to_idx[jax.tree_util.keystr(p)]].append(si)
    missing = [k for k in params if k not in top_keys]
    if missing:
        raise ValueError(
            f"stage decomposition covers no gradients for top-level "
            f"param keys {missing} — the staged backward would drop "
            f"them; add them to a stage or turn the overlap schedule "
            f"off for this model")
    return path_to_idx, leaf_stages


def _stage_cost_bytes(params, stages):
    """Backward-compute cost proxy per stage: bytes of the parameters
    the stage's segment differentiates (transformer block backward
    FLOPs scale with the block's weights). Drives the static pinned
    fraction behind hvd_overlap_window_frac."""
    costs = []
    for st in stages:
        sub = {k: params[k] for k in st.keys}
        costs.append(sum(
            int(np.prod(jnp.shape(l) or (1,))) *
            np.dtype(jnp.result_type(l)).itemsize
            for l in jax.tree_util.tree_leaves(sub)))
    return costs


def _pack_bucket(leaf_grads, bplan):
    flats = [leaf_grads[i].reshape(-1) for (i, _, _, _) in bplan]
    return jnp.concatenate(flats) if len(flats) > 1 else flats[0]


def _barrier_pair(a, b):
    a2, _ = jax.lax.optimization_barrier((a, b))
    return a2


def _loss_seed_dtype(loss):
    d = jnp.result_type(loss)
    return d if jnp.issubdtype(d, jnp.inexact) else jnp.float32


def staged_value_and_grad(stages_fn: Callable, opt=None,
                          mode: Optional[str] = None):
    """Build ``vag(params, *batch, opt_state=None) -> (loss, grads)``
    tracing the backward in bucket-aligned segments with each bucket's
    collective issued at its availability boundary and pinned before
    the next segment's compute.

    ``stages_fn(*batch) -> list[Stage]`` decomposes the forward (e.g.
    :func:`transformer_lm_stages` partial-applied over the model and
    loss). ``opt`` is the hvd optimizer whose ``update`` will consume
    the result — its attached reduction recipe (op, wire, threshold,
    ZeRO vs all-reduce) drives the staged collectives; ``opt=None``
    reduces with the knob-resolved wire at AVERAGE and returns a plain
    (already reduced) grad pytree.

    Under an error-feedback compressor pass the optimizer state:
    ``loss, g = vag(params, batch, opt_state=state)`` — the residual
    rides the staged quantized collectives and the updated residual
    returns inside the staged grads, exactly as the monolithic
    ``_ef_update`` would have produced (bitwise, asserted in
    tests/test_overlap_schedule.py).
    """
    info = _reducer_info(opt)

    def vag(params, *batch, opt_state=None):
        m = normalize_mode(mode) if mode is not None else schedule_mode()
        if m == "off":
            raise ValueError(
                "staged_value_and_grad called with the overlap schedule "
                "off — branch on hvd.overlap.active() and keep the "
                "monolithic value_and_grad path when it is (off must "
                "stay bit-for-bit today's trace)")
        stages = stages_fn(*batch)
        return _run_staged(stages, params, info, m, opt_state)

    return vag


def _run_staged(stages: Sequence[Stage], params, info: dict, mode: str,
                opt_state):
    from ..core.state import global_state
    from ..optim import distributed as dist
    from ..optim.compression import compressor_wire_spec

    if not isinstance(params, dict):
        params = dict(params)

    kind = info["kind"]
    axis_name = info.get("axis_name")
    live = collectives._bound_axes(collectives._resolve_axis(axis_name))
    if not live:
        raise RuntimeError(
            "the backward-interleaved schedule issues per-segment "
            "collectives and must run inside shard_map/jit with the "
            "data-parallel mesh axis bound (like ShardedOptimizer.update)"
        )
    n = collectives._group_size(info.get("process_set"), axis_name)
    if n <= 1:
        raise RuntimeError(
            "overlap schedule on a size-1 group: nothing to overlap — "
            "run with the schedule off on single-rank worlds")

    treedef, plans = pytree_bucket_plan(
        params, threshold_bytes=info.get("fusion_threshold_bytes"),
        backward_order=info.get("bucket_backward_order"))
    lens = plan_bucket_lengths(plans)

    # ---- forward: one vjp per segment ----------------------------------
    path_to_idx, leaf_stages = _leaf_index_maps(params, stages)
    vjps = []
    carry = jnp.zeros((), jnp.float32)  # dummy diffable carry, stage 0
    for st in stages:
        sub = {k: params[k] for k in st.keys}

        def f(sub, carry, _st=st):
            return _st.fwd(sub, carry)

        carry, vjp = jax.vjp(f, sub, carry)
        vjps.append(vjp)
    loss = carry
    if jnp.ndim(loss) != 0:
        raise ValueError(
            f"the last stage must return a scalar loss; got shape "
            f"{jnp.shape(loss)}")

    # ---- reducer setup --------------------------------------------------
    ordered = global_state().knobs.ordered_buckets
    pre = post = None
    res_buckets = None
    compression = wire = None
    int8_wire = False
    eff_op = None
    ax = live[0]
    if kind == "allreduce":
        compression = info["compression"]
        op = info["op"]
        predivide = info.get("gradient_predivide_factor", 1.0)
        wire = compressor_wire_spec(compression)
        int8_wire = wire is not None and wire.kind == "int8"
        eff_op = op
        if predivide != 1.0 and op == ReduceOp.AVERAGE:
            pre, post = 1.0 / predivide, predivide / n
            eff_op = ReduceOp.SUM
        if info.get("error_feedback") and int8_wire:
            if opt_state is None:
                raise ValueError(
                    "this DistributedOptimizer carries error-feedback "
                    "state; pass opt_state= to the staged "
                    "value_and_grad so the residual rides the staged "
                    "quantized collectives (docs/overlap.md)")
            res_local = dist._residual_rows(opt_state, params)
            if res_local is not None:
                res_buckets = pack_buckets_by_plan(res_local, plans)
    else:  # zero
        from ..optim import zero as zero_mod
        from ..optim.compression import Compression

        comp = info.get("compression")
        comp = Compression.from_knobs() if comp is None else comp
        wire = compressor_wire_spec(comp)

    # ---- backward: reverse segments, issue buckets at readiness --------
    backward_stage_order = list(reversed(range(len(stages))))
    schedule = bucket_issue_schedule(plans, leaf_stages,
                                     backward_stage_order)
    costs = _stage_cost_bytes(params, stages)
    nleaves = len(leaf_stages)
    leaf_grads: List[Any] = [None] * nleaves
    reduced: List[Any] = [None] * len(plans)
    new_res_buckets: List[Any] = [None] * len(plans)
    bucket_meta: List[tuple] = [(0, 0, False)] * len(plans)
    chain = None
    last_bi = None
    first_issue_step = None
    ct = jnp.ones((), _loss_seed_dtype(loss))
    for step_i, si in enumerate(backward_stage_order):
        g_sub, ct_in = vjps[si](ct)
        for p, g in jax.tree_util.tree_flatten_with_path(g_sub)[0]:
            i = path_to_idx[jax.tree_util.keystr(p)]
            leaf_grads[i] = g if leaf_grads[i] is None \
                else leaf_grads[i] + g
        for bi in schedule[step_i]:
            bucket = _pack_bucket(leaf_grads, plans[bi])
            bucket_meta[bi] = (
                int(bucket.size), bucket.dtype.itemsize,
                bool(jnp.issubdtype(bucket.dtype, jnp.floating)))
            if pre is not None:
                bucket = bucket * jnp.asarray(pre, bucket.dtype)
            if ordered and chain is not None:
                bucket = _barrier_pair(bucket, chain)
            if kind == "allreduce":
                r_b = res_buckets[bi] if res_buckets is not None else None
                red, token, new_r = dist._reduce_bucket(
                    bucket, eff_op, compression, wire, int8_wire, live,
                    n, info.get("process_set"), axis_name,
                    res_bucket=r_b)
                new_res_buckets[bi] = new_r
            else:
                rows = zero_mod._pad_rows(bucket, n)
                red = zero_mod._scatter_bucket(rows, ax, n, wire)
                token = red
            reduced[bi] = red
            chain = token
            last_bi = bi
            if first_issue_step is None:
                first_issue_step = step_i
        # the pin: segment si-1's backward compute must schedule after
        # every collective issued so far — a genuine dependency edge
        # (not just collective-to-collective ordering), routed through
        # the inter-segment cotangent
        if si > 0 and chain is not None and hasattr(ct_in, "dtype") \
                and jnp.issubdtype(ct_in.dtype, jnp.inexact):
            ct_in = _barrier_pair(ct_in, chain)
        ct = ct_in
    missing = [bi for bi, r in enumerate(reduced) if r is None]
    if missing:
        raise AssertionError(
            f"buckets {missing} never became available — stage "
            f"decomposition does not cover their leaves")

    if mode == "double" and chain is not None:
        # double-buffered grads: the optimizer consumes nothing until
        # the LAST segment's collective retires, so update arithmetic
        # can't interleave into mid-backward
        reduced = [r if bi == last_bi else _barrier_pair(r, chain)
                   for bi, r in enumerate(reduced)]

    # static pinned fraction: share of backward cost the schedule
    # forces after the first issued collective (the lower bound any
    # correct scheduler must grant the overlap window)
    total_cost = float(sum(costs)) or 1.0
    pinned_frac = sum(
        costs[si] for step_i, si in enumerate(backward_stage_order)
        if first_issue_step is not None and step_i > first_issue_step
    ) / total_cost

    _record_staged_step(bucket_meta, wire, pinned_frac)

    if kind == "zero":
        for shard, L in zip(reduced, lens):
            k = -(-L // n)
            if shard.shape != (k,):
                raise AssertionError((shard.shape, k))
        return loss, StagedShards(reduced)

    if post is not None:
        reduced = [r * jnp.asarray(post, r.dtype) for r in reduced]
    tree = unflatten_buckets_by_plan(reduced, treedef, plans,
                                    nleaves)
    new_res = None
    if res_buckets is not None:
        filled = [nr if nr is not None else rb
                  for nr, rb in zip(new_res_buckets, res_buckets)]
        res_tree = unflatten_buckets_by_plan(filled, treedef,
                                             plans, nleaves)
        new_res = jax.tree_util.tree_map(
            lambda r: r.astype(jnp.float32)[None], res_tree)
    if info.get("plain"):
        return loss, tree
    return loss, StagedGrads(tree, new_res)


# ---------------------------------------------------------------------------
# the FSDP (fully-sharded parameter) staged value-and-grad
# ---------------------------------------------------------------------------

def fsdp_staged_value_and_grad(stages_fn: Callable, opt,
                               layout=None, prefetch=None,
                               regather=None, offload=None):
    """Build ``vag(rows, *batch, opt_state=None) -> (loss,
    StagedShards)`` over fully-sharded parameter rows
    (optim/fsdp.py): the forward's per-bucket parameter all-gathers
    are prefetch-interleaved with compute — the mirror of the staged
    backward — and the backward's reduce-scatters ride the existing
    staged path.

    The forward pin is the inverse of the backward's: where the
    backward pins each issued collective BEFORE the next segment's
    compute (so the schedule cannot serialize collectives after
    backward), the forward pins each prefetched gather BEHIND the
    activation entering the current segment (so the schedule cannot
    hoist every gather to t=0 and hold a replicated copy of the model
    — the memory property that makes FSDP fit models replication
    can't). Gather bucket k+1 issues at segment k's boundary, overlaps
    segment k's compute, and its buffer is dropped after its last
    forward use, so the gather working set stays ~one bucket above the
    sharded size. ``prefetch`` (default the HOROVOD_FSDP_PREFETCH
    knob) is the gather look-ahead in stages; 0 serializes each gather
    at its need boundary.

    ``regather`` (default the HOROVOD_FSDP_REGATHER knob, on)
    differentiates *through* the gather: the forward runs primal-only
    — no vjp residual captures gathered weights — and the backward
    re-issues each bucket's all-gather at its backward-first-use
    boundary (fusion.bucket_regather_schedule), pinned behind the
    incoming cotangent, then runs the IDENTICAL pack → zero._pad_rows
    → zero._scatter_bucket chain, so values stay bitwise the
    saved-gather mode's on plain and int8+error-feedback wires while
    within-step peak param liveness drops to sharded + the bucket
    working set (docs/fsdp.md). ``regather=False`` takes the
    saved-gather code path verbatim — bit-for-bit its lowering.
    ``offload`` (default the HOROVOD_FSDP_OFFLOAD knob, off; regather
    mode only) additionally moves stage-boundary activation carries to
    pinned host memory on forward and prefetches each back one
    backward stage ahead, duty-bounded by HOROVOD_FSDP_OFFLOAD_DUTY; a
    no-op on backends without an addressable host memory space.

    ``opt`` must be a FullyShardedOptimizer; its
    ``update(staged, state, params=shards)`` consumes the result. Under
    the int8 error-feedback wire pass ``opt_state=`` so the residual
    rides the staged quantized reduce-scatters (bitwise contract and
    A/B evidence: docs/fsdp.md, scripts/fsdp_check.py).
    """
    info = _reducer_info(opt)
    if info["kind"] != "fsdp":
        raise ValueError(
            "fsdp_staged_value_and_grad needs a FullyShardedOptimizer "
            "(ShardedOptimizer(params_sharded=True)); got kind "
            f"{info['kind']!r} — docs/fsdp.md")
    if layout is None:
        raise ValueError(
            "fsdp_staged_value_and_grad requires the FsdpLayout the "
            "parameter rows were sharded with (optim.fsdp.fsdp_layout)")

    def vag(rows, *batch, opt_state=None):
        stages = stages_fn(*batch)
        return _run_fsdp_staged(stages, layout, rows, info, opt_state,
                                prefetch, regather, offload)

    return vag


def _run_fsdp_staged(stages: Sequence[Stage], layout, rows, info: dict,
                     opt_state, prefetch, regather=None, offload=None):
    from ..core.state import global_state
    from ..optim import fsdp as fsdp_mod
    from ..optim import zero as zero_mod

    if regather is None:
        regather = bool(getattr(global_state().knobs, "fsdp_regather",
                                True))
    if regather:
        # recompute-through-the-gather policy; the saved-gather path
        # below stays byte-for-byte today's trace (the knob-off
        # lowering-hash contract, scripts/fsdp_check.py)
        return _run_fsdp_regather(stages, layout, rows, info, opt_state,
                                  prefetch, offload)

    axis_name = info.get("axis_name")
    live = collectives._bound_axes(collectives._resolve_axis(axis_name))
    if len(live) != 1:
        raise RuntimeError(
            "the FSDP staged step shards parameters over exactly one "
            f"live data-parallel axis; got live axes {live} — run "
            "inside shard_map with the fsdp/dp mesh axis bound")
    ax = live[0]
    n = collectives._group_size(info.get("process_set"), axis_name)
    if n != layout.world:
        raise ValueError(
            f"parameter rows were sharded for world {layout.world} but "
            f"the live group size is {n} — reshard with "
            "fsdp.reshard_rows before re-entering the train loop")
    wire = info.get("wire")
    ef = bool(info.get("error_feedback"))
    if prefetch is None:
        prefetch = int(getattr(global_state().knobs, "fsdp_prefetch", 1))
    depth = max(int(prefetch), 0)

    shards = fsdp_mod.local_shards(rows, layout)
    plans = list(layout.plans)
    lens = list(layout.lens)
    abs_params = fsdp_mod.abstract_params(layout)
    path_to_idx, leaf_stages = _leaf_index_maps(abs_params, stages)
    S = len(stages)
    need = bucket_prefetch_schedule(plans, [min(s) for s in leaf_stages],
                                    S)
    leaf_loc = {}
    for bi, bp in enumerate(plans):
        for (i, off, sz, shp) in bp:
            leaf_loc[i] = (bi, off, sz, shp)
    # last forward stage touching any leaf of each bucket — the point
    # after which its gathered buffer is dropped
    last_use = [
        max(max(leaf_stages[i]) for (i, _, _, _) in bp) for bp in plans
    ]

    # ---- forward: prefetch-interleaved per-bucket all-gathers ----------
    gathered = {}

    def _gather(bi, pin):
        row = shards[bi]
        if pin is not None and hasattr(pin, "dtype") and \
                jnp.issubdtype(pin.dtype, jnp.inexact):
            # the anti-hoist pin: this gather depends on the activation
            # entering the CURRENT segment, so no scheduler may issue
            # it before the previous segment retired — yet the current
            # segment's compute does not depend on it, so they overlap
            row = _barrier_pair(row, pin)
        full = jax.lax.all_gather(row, ax, tiled=True)
        return full[: lens[bi]]

    carry = jnp.zeros((), jnp.float32)
    vjps = []
    for s, st in enumerate(stages):
        for bi in need[s]:
            if bi not in gathered:  # the fill (or depth 0): need it NOW
                gathered[bi] = _gather(bi, carry if s else None)
        for d in range(1, depth + 1):
            if s + d >= S:
                break
            for bi in need[s + d]:
                if bi not in gathered:
                    gathered[bi] = _gather(bi, carry if s else None)
        sub_abs = {k: abs_params[k] for k in st.keys}
        paths, sub_def = jax.tree_util.tree_flatten_with_path(sub_abs)
        leaves = []
        for p, _sds in paths:
            bi, off, sz, shp = leaf_loc[
                path_to_idx[jax.tree_util.keystr(p)]]
            leaves.append(jax.lax.dynamic_slice_in_dim(
                gathered[bi], off, sz).reshape(shp))
        sub = jax.tree_util.tree_unflatten(sub_def, leaves)

        def f(sub, carry, _st=st):
            return _st.fwd(sub, carry)

        carry, vjp = jax.vjp(f, sub, carry)
        vjps.append(vjp)
        # drop gathered buffers past their last forward use — the
        # bounded working set (backward re-reads the per-stage sub
        # leaves the vjp residuals captured, not these buffers)
        for bi in [b for b in list(gathered) if last_use[b] == s]:
            del gathered[bi]
    loss = carry
    if jnp.ndim(loss) != 0:
        raise ValueError(
            f"the last stage must return a scalar loss; got shape "
            f"{jnp.shape(loss)}")

    # ---- backward: staged reduce-scatters at availability boundaries ---
    res_mats = None
    if ef:
        if opt_state is None:
            raise ValueError(
                "this FullyShardedOptimizer carries error-feedback "
                "state; pass opt_state= to the staged value_and_grad "
                "so the residual rides the staged quantized "
                "reduce-scatters (docs/fsdp.md)")
        res_mats = fsdp_mod._residual_mats(opt_state, layout, wire.block)
        if res_mats is None:
            raise ValueError(
                "opt_state carries no FsdpEFState residual but the "
                "optimizer was built on the int8 error-feedback wire")
    ordered = global_state().knobs.ordered_buckets
    backward_stage_order = list(reversed(range(S)))
    schedule = bucket_issue_schedule(plans, leaf_stages,
                                     backward_stage_order)
    costs = _stage_cost_bytes(abs_params, stages)
    leaf_grads: List[Any] = [None] * layout.nleaves
    reduced: List[Any] = [None] * len(plans)
    new_res: List[Any] = [None] * len(plans)
    bucket_meta: List[tuple] = [(0, 0, False)] * len(plans)
    chain = None
    first_issue_step = None
    ct = jnp.ones((), _loss_seed_dtype(loss))
    for step_i, si in enumerate(backward_stage_order):
        g_sub, ct_in = vjps[si](ct)
        for p, g in jax.tree_util.tree_flatten_with_path(g_sub)[0]:
            i = path_to_idx[jax.tree_util.keystr(p)]
            leaf_grads[i] = g if leaf_grads[i] is None \
                else leaf_grads[i] + g
        for bi in schedule[step_i]:
            bucket = _pack_bucket(leaf_grads, plans[bi])
            bucket_meta[bi] = (
                int(bucket.size), bucket.dtype.itemsize,
                bool(jnp.issubdtype(bucket.dtype, jnp.floating)))
            if ordered and chain is not None:
                bucket = _barrier_pair(bucket, chain)
            rows_b = zero_mod._pad_rows(bucket, n)
            if ef:
                red, nr = zero_mod._scatter_bucket(
                    rows_b, ax, n, wire, residual=res_mats[bi])
                new_res[bi] = nr.reshape(1, -1)
            else:
                red = zero_mod._scatter_bucket(rows_b, ax, n, wire)
            reduced[bi] = red
            chain = red
            if first_issue_step is None:
                first_issue_step = step_i
        if si > 0 and chain is not None and hasattr(ct_in, "dtype") \
                and jnp.issubdtype(ct_in.dtype, jnp.inexact):
            ct_in = _barrier_pair(ct_in, chain)
        ct = ct_in
    missing = [bi for bi, r in enumerate(reduced) if r is None]
    if missing:
        raise AssertionError(
            f"buckets {missing} never became available — stage "
            f"decomposition does not cover their leaves")

    total_cost = float(sum(costs)) or 1.0
    pinned_frac = sum(
        costs[si] for step_i, si in enumerate(backward_stage_order)
        if first_issue_step is not None and step_i > first_issue_step
    ) / total_cost
    _record_staged_step(bucket_meta, wire, pinned_frac)
    gather_bytes = sum(
        n * k * np.dtype(d).itemsize
        for k, d in zip(layout.ks, layout.dtypes))
    _record_fsdp_step(layout.shard_bytes, gather_bytes)

    for shard, L in zip(reduced, lens):
        k = -(-L // n)
        if shard.shape != (k,):
            raise AssertionError((shard.shape, k))
    return loss, StagedShards(reduced,
                              new_residuals=new_res if ef else None)


def _offload_stage_set(n_stages: int, duty: float):
    """Which stage-boundary carries move to host under
    HOROVOD_FSDP_OFFLOAD: the eligible set excludes stage 0 (its carry
    is the dummy scalar seed) and the last stage (its carry is
    re-consumed immediately by the first backward segment); of the
    rest, the EARLIEST stages offload first — their carries wait
    longest for backward, the long-stage tail — up to ``duty`` of the
    set, the offload analog of the replicator's bounded duty cycle."""
    eligible = list(range(1, n_stages - 1))
    if not eligible or duty <= 0.0:
        return set()
    k = int(np.ceil(min(duty, 1.0) * len(eligible)))
    return set(eligible[:k])


def _carry_put(c, space):
    """tree-wide device_put into a memory space (`jax.memory.Space.Host`
    out, `.Device` back). A backend that cannot do it raises at compile
    time — HOROVOD_FSDP_OFFLOAD either offloads or fails."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, space), c)


def _carry_bytes(c) -> int:
    leaves = jax.tree_util.tree_leaves(c)
    return sum(
        int(getattr(l, "size", 1)) *
        np.dtype(getattr(l, "dtype", jnp.float32)).itemsize
        for l in leaves)


def _run_fsdp_regather(stages: Sequence[Stage], layout, rows,
                       info: dict, opt_state, prefetch, offload):
    """The regather FSDP step (HOROVOD_FSDP_REGATHER, docs/fsdp.md):
    differentiate *through* the per-bucket all-gather. The forward runs
    stages 0..S-2 primal-only — the only values surviving toward
    backward are the stage-boundary activation carries, never gathered
    weights — and the backward walks the stages in reverse, re-issuing
    each bucket's all-gather at its backward-first-use boundary
    (fusion.bucket_regather_schedule; pinned behind the incoming
    cotangent so no scheduler may hoist it into forward), rebuilding
    that segment's vjp against the freshly gathered rows, and feeding
    the resulting bucket through the IDENTICAL pack → zero._pad_rows
    → zero._scatter_bucket chain as the saved-gather path. The LAST
    stage is the forward/backward boundary itself: its vjp is built
    once at backward step 0 and its primal output is the returned loss
    — the same subgraph (live residuals, same gather pin) the
    saved-gather mode traces for it, which is what keeps the loss
    bitwise (a recomputed loss stage compiles with dead residuals and
    can drift a final-reduction ulp). Same ops on same values
    throughout, so params/state/EF residual/loss stay bitwise-equal on
    plain and int8 wires while no gathered bucket buffer is live
    across the forward→backward span: within-step peak param liveness
    ≤ sharded + the prefetch-depth bucket working set. Under
    ``offload`` the carries additionally move to pinned host memory at
    each boundary and prefetch back one backward stage ahead."""
    from ..core.state import global_state
    from ..optim import fsdp as fsdp_mod
    from ..optim import zero as zero_mod

    axis_name = info.get("axis_name")
    live = collectives._bound_axes(collectives._resolve_axis(axis_name))
    if len(live) != 1:
        raise RuntimeError(
            "the FSDP staged step shards parameters over exactly one "
            f"live data-parallel axis; got live axes {live} — run "
            "inside shard_map with the fsdp/dp mesh axis bound")
    ax = live[0]
    n = collectives._group_size(info.get("process_set"), axis_name)
    if n != layout.world:
        raise ValueError(
            f"parameter rows were sharded for world {layout.world} but "
            f"the live group size is {n} — reshard with "
            "fsdp.reshard_rows before re-entering the train loop")
    wire = info.get("wire")
    ef = bool(info.get("error_feedback"))
    knobs = global_state().knobs
    if prefetch is None:
        prefetch = int(getattr(knobs, "fsdp_prefetch", 1))
    depth = max(int(prefetch), 0)
    if offload is None:
        offload = bool(getattr(knobs, "fsdp_offload", False))
    duty = float(getattr(knobs, "fsdp_offload_duty", 1.0))

    shards = fsdp_mod.local_shards(rows, layout)
    plans = list(layout.plans)
    lens = list(layout.lens)
    abs_params = fsdp_mod.abstract_params(layout)
    path_to_idx, leaf_stages = _leaf_index_maps(abs_params, stages)
    S = len(stages)
    need = bucket_prefetch_schedule(plans, [min(s) for s in leaf_stages],
                                    S)
    leaf_loc = {}
    for bi, bp in enumerate(plans):
        for (i, off, sz, shp) in bp:
            leaf_loc[i] = (bi, off, sz, shp)
    # forward drop boundary: the last PRIMAL stage (≤ S-2) touching any
    # leaf of the bucket — stage S-1 runs at backward step 0, so a
    # bucket only it uses is never forward-needed (None). Backward drop
    # boundary: the FIRST forward stage touching the bucket (the last
    # backward segment that reads it).
    fwd_last = []
    for bp in plans:
        uses = [s for (i, _, _, _) in bp for s in leaf_stages[i]
                if s < S - 1]
        fwd_last.append(max(uses) if uses else None)
    first_use = [
        min(min(leaf_stages[i]) for (i, _, _, _) in bp) for bp in plans
    ]
    bkt_bytes = [
        n * k * np.dtype(d).itemsize
        for k, d in zip(layout.ks, layout.dtypes)
    ]

    gathered = {}

    def _gather(bi, pin):
        row = shards[bi]
        if pin is not None and hasattr(pin, "dtype") and \
                jnp.issubdtype(pin.dtype, jnp.inexact):
            # forward: the anti-hoist pin behind the activation
            # entering the current segment; backward: behind the
            # incoming cotangent (step 0: behind the carry entering the
            # last stage — the ct seed is a constant, no scheduler
            # edge), so the re-gather cannot migrate into the forward
            # and restore the very liveness this mode removes
            row = _barrier_pair(row, pin)
        full = jax.lax.all_gather(row, ax, tiled=True)
        return full[: lens[bi]]

    def _sub_for(si):
        sub_abs = {k: abs_params[k] for k in stages[si].keys}
        paths, sub_def = jax.tree_util.tree_flatten_with_path(sub_abs)
        leaves = []
        for p, _sds in paths:
            bi, off, sz, shp = leaf_loc[
                path_to_idx[jax.tree_util.keystr(p)]]
            leaves.append(jax.lax.dynamic_slice_in_dim(
                gathered[bi], off, sz).reshape(shp))
        return jax.tree_util.tree_unflatten(sub_def, leaves)

    offload_set = _offload_stage_set(S, duty) if offload else set()
    offload_bytes = 0

    # ---- forward: stages 0..S-2 primal-only; nothing but the
    # inter-stage carries survives toward backward -----------------------
    carries: List[Any] = [None] * S
    carry = jnp.zeros((), jnp.float32)
    for s in range(S - 1):
        st = stages[s]
        for bi in need[s]:
            if bi not in gathered:
                gathered[bi] = _gather(bi, carry if s else None)
        for d in range(1, depth + 1):
            if s + d >= S:
                break
            for bi in need[s + d]:
                if bi not in gathered:
                    gathered[bi] = _gather(bi, carry if s else None)
        if s in offload_set:
            carries[s] = _carry_put(carry, jax.memory.Space.Host)
            offload_bytes += _carry_bytes(carry)
        else:
            carries[s] = carry

        def f(sub, carry, _st=st):
            return _st.fwd(sub, carry)

        # primal through jax.vjp with the vjp function DROPPED: the
        # residuals are dead code (no gathered weights survive to
        # backward), but the primal follows the exact linearization
        # trace the saved-gather mode's forward does — custom-jvp
        # primals (log_softmax et al.) can differ in the last ulp from
        # plain execution, and the bitwise contract forbids that
        carry = jax.vjp(f, _sub_for(s), carry)[0]
        for bi in [b for b in list(gathered) if fwd_last[b] == s]:
            del gathered[bi]
    # the carry entering the last stage: the forward/backward boundary
    # value (never offloaded — backward step 0 consumes it immediately)
    carries[S - 1] = carry

    # ---- backward: re-gather at backward-first-use, rebuild the
    # segment vjp against the fresh rows, reduce-scatter as before -------
    res_mats = None
    if ef:
        if opt_state is None:
            raise ValueError(
                "this FullyShardedOptimizer carries error-feedback "
                "state; pass opt_state= to the staged value_and_grad "
                "so the residual rides the staged quantized "
                "reduce-scatters (docs/fsdp.md)")
        res_mats = fsdp_mod._residual_mats(opt_state, layout, wire.block)
        if res_mats is None:
            raise ValueError(
                "opt_state carries no FsdpEFState residual but the "
                "optimizer was built on the int8 error-feedback wire")
    ordered = global_state().knobs.ordered_buckets
    backward_stage_order = list(reversed(range(S)))
    schedule = bucket_issue_schedule(plans, leaf_stages,
                                     backward_stage_order)
    regather_need = bucket_regather_schedule(
        plans, [max(s) for s in leaf_stages], S)
    costs = _stage_cost_bytes(abs_params, stages)
    leaf_grads: List[Any] = [None] * layout.nleaves
    reduced: List[Any] = [None] * len(plans)
    new_res: List[Any] = [None] * len(plans)
    bucket_meta: List[tuple] = [(0, 0, False)] * len(plans)
    chain = None
    first_issue_step = None
    loss = None
    ct = None
    regather_bytes = 0
    fetched = {}

    def _restore(si):
        c = carries[si]
        return (_carry_put(c, jax.memory.Space.Device)
                if si in offload_set else c)

    for step_i, si in enumerate(backward_stage_order):
        # step 0's gathers carry the saved-mode last-stage pin (the
        # carry entering stage S-1; None when S == 1 — the seed is a
        # constant); later steps pin behind the incoming cotangent
        pin = ct if step_i else (carries[si] if si else None)
        for bi in regather_need[step_i]:
            if bi not in gathered:
                gathered[bi] = _gather(bi, pin)
                if step_i or fwd_last[bi] is not None:
                    regather_bytes += bkt_bytes[bi]
        for d in range(1, depth + 1):
            if step_i + d >= S:
                break
            for bi in regather_need[step_i + d]:
                if bi not in gathered:
                    gathered[bi] = _gather(bi, pin)
                    regather_bytes += bkt_bytes[bi]
        carry_in = fetched.pop(si, None)
        if carry_in is None:
            carry_in = _restore(si)
        # host→HBM prefetch one backward stage ahead: the next
        # segment's carry transfers while this segment computes
        if step_i + 1 < S:
            nxt = backward_stage_order[step_i + 1]
            if nxt not in fetched:
                fetched[nxt] = _restore(nxt)

        def f(sub, carry, _st=stages[si]):
            return _st.fwd(sub, carry)

        if step_i == 0:
            # the last stage runs HERE, once: primal out is the loss,
            # residuals feed this step's backward — the saved-gather
            # mode's exact last-stage subgraph (bitwise loss)
            loss, vjp = jax.vjp(f, _sub_for(si), carry_in)
            if jnp.ndim(loss) != 0:
                raise ValueError(
                    f"the last stage must return a scalar loss; got "
                    f"shape {jnp.shape(loss)}")
            ct = jnp.ones((), _loss_seed_dtype(loss))
        else:
            _, vjp = jax.vjp(f, _sub_for(si), carry_in)
        g_sub, ct_in = vjp(ct)
        for p, g in jax.tree_util.tree_flatten_with_path(g_sub)[0]:
            i = path_to_idx[jax.tree_util.keystr(p)]
            leaf_grads[i] = g if leaf_grads[i] is None \
                else leaf_grads[i] + g
        for bi in schedule[step_i]:
            bucket = _pack_bucket(leaf_grads, plans[bi])
            bucket_meta[bi] = (
                int(bucket.size), bucket.dtype.itemsize,
                bool(jnp.issubdtype(bucket.dtype, jnp.floating)))
            if ordered and chain is not None:
                bucket = _barrier_pair(bucket, chain)
            rows_b = zero_mod._pad_rows(bucket, n)
            if ef:
                red, nr = zero_mod._scatter_bucket(
                    rows_b, ax, n, wire, residual=res_mats[bi])
                new_res[bi] = nr.reshape(1, -1)
            else:
                red = zero_mod._scatter_bucket(rows_b, ax, n, wire)
            reduced[bi] = red
            chain = red
            if first_issue_step is None:
                first_issue_step = step_i
        if si > 0 and chain is not None and hasattr(ct_in, "dtype") \
                and jnp.issubdtype(ct_in.dtype, jnp.inexact):
            ct_in = _barrier_pair(ct_in, chain)
        ct = ct_in
        # drop re-gathered buffers once backward passes the bucket's
        # FIRST forward stage — the bounded backward working set
        for bi in [b for b in list(gathered) if first_use[b] == si]:
            del gathered[bi]
    missing = [bi for bi, r in enumerate(reduced) if r is None]
    if missing:
        raise AssertionError(
            f"buckets {missing} never became available — stage "
            f"decomposition does not cover their leaves")

    total_cost = float(sum(costs)) or 1.0
    pinned_frac = sum(
        costs[si] for step_i, si in enumerate(backward_stage_order)
        if first_issue_step is not None and step_i > first_issue_step
    ) / total_cost
    _record_staged_step(bucket_meta, wire, pinned_frac)
    gather_bytes = sum(
        n * k * np.dtype(d).itemsize
        for k, d in zip(layout.ks, layout.dtypes))
    # ≤ one re-gather per bucket per backward (exactly one for buckets
    # the primal stages used; head-only buckets gather once total)
    _record_fsdp_step(layout.shard_bytes, gather_bytes,
                      regather_bytes=regather_bytes,
                      offload_bytes=offload_bytes)

    for shard, L in zip(reduced, lens):
        k = -(-L // n)
        if shard.shape != (k,):
            raise AssertionError((shard.shape, k))
    return loss, StagedShards(reduced,
                              new_residuals=new_res if ef else None)


def _record_fsdp_step(param_bytes: int, gather_bytes: int,
                      regather_bytes: int = 0, offload_bytes: int = 0):
    """Execution-time FSDP telemetry: the per-device resident parameter
    bytes (the HBM win), the full-precision bytes the forward
    all-gathers re-materialize each step (the wire rent paid for it),
    plus — regather mode — the backward re-gather bytes and the
    stage-carry bytes offloaded to host RAM: hvd_hbm_param_bytes /
    hvd_fsdp_gather_bytes_total / hvd_fsdp_regather_bytes_total /
    hvd_fsdp_offload_bytes_total and the StepStats JSONL fields
    (docs/metrics.md)."""
    import functools

    from ..utils import metrics as _metrics

    if not _metrics.enabled():
        return
    from jax.experimental import io_callback

    io_callback(functools.partial(
        _metrics.record_fsdp_step, int(param_bytes), int(gather_bytes),
        int(regather_bytes), int(offload_bytes)),
        None)


def _record_staged_step(bucket_meta, wire, pinned_frac):
    """Execution-time telemetry parity with the monolithic paths: the
    autotuner observation, grad/wire byte counters, and the
    hvd_overlap_window_frac gauge (the schedule's static pin).
    ``bucket_meta`` is (elements, itemsize, is_floating) per bucket;
    ``wire`` is the WireSpec the staged collectives actually move
    (resolved once in _run_staged for both allreduce and ZeRO)."""
    import functools

    from ..core.state import global_state
    from ..utils import metrics as _metrics

    pm = global_state().parameter_manager
    if pm is None and not _metrics.enabled():
        return
    from jax.experimental import io_callback

    total = sum(e * it for e, it, _ in bucket_meta)
    if pm is not None:
        io_callback(functools.partial(pm.observe, total), None)
    if _metrics.enabled():
        io_callback(functools.partial(
            _metrics.record_grad_reduction, total, len(bucket_meta)),
            None)
        from ..optim.compression import wire_sent_bytes

        sent = sum(
            wire_sent_bytes(e, it, wire if fl else None)
            for e, it, fl in bucket_meta)
        io_callback(functools.partial(
            _metrics.record_wire_bytes, total, sent), None)
        io_callback(functools.partial(
            _metrics.record_overlap_window, float(pinned_frac)), None)
