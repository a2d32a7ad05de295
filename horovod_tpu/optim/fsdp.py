"""ZeRO-3 / FSDP: fully-sharded parameters over the data-parallel axis.

`zero.py` stops at ZeRO-1 — optimizer state shards 1/N per rank but the
parameters themselves stay replicated, which is the repo's hard scale
ceiling: a model that does not fit replicated per chip is out of reach
("Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training", PAPERS.md 2004.13336, is the seed idea; this module goes
past it to full parameter sharding). Here parameters live as the SAME
per-bucket padded row stacks the ZeRO state uses — `(n, k_i)` arrays,
row r = rank r's shard, laid out by `ops/fusion.py`'s
backward-availability bucket plan — and the train step:

  * **forward**: all-gathers each bucket's shard back to full precision
    at (or one stage before) the first forward stage that touches any
    of its leaves (`fusion.bucket_prefetch_schedule` — the mirror of
    the backward issue schedule), prefetch-interleaved with compute by
    `ops/overlap.py`'s staged runner: gather k+1 is pinned behind the
    activation entering segment k via `lax.optimization_barrier`, so it
    cannot hoist to t=0 (the gather-everything-up-front lowering that
    costs a full replicated copy of the model) yet overlaps segment k's
    compute. Gathered buffers are dropped after their last forward use,
    so the forward's gather working set stays ~one bucket above the
    sharded size. Under the default regather policy
    (HOROVOD_FSDP_REGATHER) the forward is primal-only — no vjp
    residual captures gathered weights — and the backward re-issues
    each bucket's all-gather at its backward-first-use boundary
    (`fusion.bucket_regather_schedule`), so WITHIN-STEP peak param
    liveness is sharded + the prefetch-depth bucket working set, not
    just the resident bound; the old honest limit (vjp residuals
    holding gathered slices forward→backward, peak reaching the
    replicated size) now applies only to HOROVOD_FSDP_REGATHER=0,
    which keeps the saved-gather lowering bit-for-bit.
    HOROVOD_FSDP_OFFLOAD additionally parks stage-boundary activation
    carries in pinned host RAM until backward, duty-bounded;
  * **backward**: the reduce-scatters ride the existing staged path —
    each gradient bucket `psum_scatter`s at its availability boundary
    (`optim.zero._scatter_bucket`, the shared data plane), including
    the int8 block-quantized wire with error feedback living on the
    rank-private residual shard (`FsdpEFState`);
  * **update**: the inner optax optimizer updates only this rank's
    shard (state sharded exactly as ZeRO-1's) and the update applies to
    the LOCAL shard — no update all-gather, parameters never
    re-materialize replicated.

Entry points: :func:`FullyShardedOptimizer` (or the equivalent
``ShardedOptimizer(params_sharded=True)``), consumed automatically by
``parallel/train.make_lm_train_step`` on ``fsdp>1`` meshes
(HOROVOD_FSDP knob, docs/fsdp.md). Numerics contract: bitwise parity
of params/state/loss against the gathered (replicated-parameter)
reference on the plain and int8 wires — `scripts/fsdp_check.py` gates
it, `tests/test_fsdp.py` asserts it.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import collectives
from . import zero as zero_mod


class FsdpLayout(NamedTuple):
    """The sharded-parameter layout authority: derived data-free from
    (params pytree structure, leaf shapes/dtypes, fusion threshold,
    bucket ordering, world size), so the optimizer, the staged runner,
    the checkpointer and `reshard_rows` all agree on it. `plans` is the
    `fusion.pytree_bucket_plan` per-bucket leaf layout; `lens[i]` the
    true element count of bucket i; `ks[i] = ceil(lens[i]/world)` the
    per-rank shard width."""

    treedef: Any
    plans: tuple
    lens: tuple
    ks: tuple
    dtypes: tuple
    world: int
    nleaves: int

    @property
    def param_bytes(self) -> int:
        """Unsharded parameter bytes (the replicated footprint)."""
        return sum(int(L) * np.dtype(d).itemsize
                   for L, d in zip(self.lens, self.dtypes))

    @property
    def shard_bytes(self) -> int:
        """Per-rank resident parameter bytes under this layout."""
        return sum(int(k) * np.dtype(d).itemsize
                   for k, d in zip(self.ks, self.dtypes))

    @property
    def max_bucket_bytes(self) -> int:
        """Largest single gathered bucket — the forward prefetch
        working-set increment above the sharded size."""
        return max((int(n) * self.world * np.dtype(d).itemsize
                    for n, d in zip(self.ks, self.dtypes)), default=0)


def bucket_name(i: int) -> str:
    return f"bucket_{i:04d}"


def fsdp_layout(params, world: Optional[int] = None, axis_name=None,
                fusion_threshold_bytes=None,
                bucket_backward_order=None) -> FsdpLayout:
    """Build the layout for a params pytree (real arrays or
    `jax.ShapeDtypeStruct`s — the plan is data-free). `world` defaults
    to the live data-parallel group size, like ShardedOptimizer."""
    from ..ops.fusion import plan_bucket_lengths, pytree_bucket_plan

    if world is None:
        world = zero_mod._world(axis_name)
    world = int(world)
    if world <= 1:
        raise ValueError(
            "fsdp_layout needs a world size > 1 — a size-1 world has "
            "nothing to shard (use the plain optimizer paths)")
    treedef, plans = pytree_bucket_plan(
        params, threshold_bytes=fusion_threshold_bytes,
        backward_order=bucket_backward_order)
    lens = plan_bucket_lengths(plans)
    leaves = jax.tree_util.tree_leaves(params)
    dtypes = tuple(np.dtype(jnp.result_type(leaves[bp[0][0]]))
                   for bp in plans)
    return FsdpLayout(
        treedef=treedef,
        plans=tuple(tuple(bp) for bp in plans),
        lens=tuple(int(L) for L in lens),
        ks=tuple(-(-int(L) // world) for L in lens),
        dtypes=dtypes,
        world=world,
        nleaves=len(leaves),
    )


def abstract_params(layout: FsdpLayout):
    """The full params pytree as ShapeDtypeStructs — the structural
    template the staged runner's stage/leaf maps are built from without
    ever materializing a replica."""
    leaves: List[Any] = [None] * layout.nleaves
    for bi, bp in enumerate(layout.plans):
        for (i, _off, _sz, shape) in bp:
            leaves[i] = jax.ShapeDtypeStruct(tuple(shape),
                                             layout.dtypes[bi])
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def shard_params(params, layout: FsdpLayout):
    """Full params pytree → `{bucket_NNNN: (world, k_i)}` row dict
    (zero-padded; row r is rank r's shard). Shapes are exactly the
    ZeRO-1 state rows', so `hvd.sharded_state_specs`-style `P(ax)`
    specs shard them one row per device."""
    from ..ops.fusion import pack_buckets_by_plan

    buckets = pack_buckets_by_plan(params, layout.plans)
    return {bucket_name(i): zero_mod._pad_rows(b, layout.world)
            for i, b in enumerate(buckets)}


def unshard_params(rows, layout: FsdpLayout):
    """Row dict → full params pytree. This MATERIALIZES a replica —
    parity tests and small-model export only; training never calls it
    (the staged runner gathers bucket-by-bucket instead)."""
    from ..ops.fusion import unflatten_buckets_by_plan

    buckets = [jnp.asarray(rows[bucket_name(i)]).reshape(-1)[: L]
               for i, L in enumerate(layout.lens)]
    return unflatten_buckets_by_plan(buckets, layout.treedef,
                                     layout.plans, layout.nleaves)


def local_shards(rows, layout: FsdpLayout) -> List:
    """The device-local `(k_i,)` shards, in bucket order, from the row
    dict as it arrives inside shard_map (each `(world, k)` leaf sliced
    to its `(1, k)` row by the `P(ax)` in_specs)."""
    out = []
    for i in range(len(layout.plans)):
        r = jnp.asarray(rows[bucket_name(i)])
        if r.ndim == 2 and r.shape[0] == 1:
            out.append(r.reshape(-1))
        elif r.ndim == 1:
            out.append(r)
        else:
            raise ValueError(
                f"{bucket_name(i)} arrived with shape {tuple(r.shape)} "
                "— inside shard_map each parameter row stack must be "
                "sharded one (1, k) row per device; pass "
                "hvd.fsdp.param_row_specs(layout) as its in/out specs")
    return out


def apply_shard_updates(rows, updates: List, layout: FsdpLayout):
    """Apply per-bucket update shards to the local parameter shards
    (the FSDP analog of `optax.apply_updates`, which it delegates to so
    the arithmetic is bit-identical to the replicated path's). Returns
    a row dict with each leaf's incoming shape preserved.

    The updates are routed through `optimization_barrier` first: the
    replicated paths apply updates AFTER an all-gather, whose program
    boundary keeps the optimizer's final `-lr * x` multiply and the
    `p + u` add as two separately-rounded ops, while the shard-local
    apply would otherwise let the compiler contract them into one fma
    — a 1-ulp/step drift from the replicated reference. The barrier
    holds on the TPU pipeline (bitwise there); XLA CPU's barrier
    expander erases it post-opt (the overlap_check caveat), so on CPU
    the cross-layout comparison is exact for state and loss but
    within one rounding of the applied update on params (gated at 2
    relative ulps + a 1e-7 cancellation floor) — the parity GATE
    therefore runs against the gathered (`mode="upfront"`) reference,
    which shares this apply and is bitwise on every backend
    (scripts/fsdp_check.py)."""
    import optax

    shards = local_shards(rows, layout)
    updates = list(jax.lax.optimization_barrier(tuple(updates)))
    new = optax.apply_updates(shards, updates)
    return {bucket_name(i): s.reshape(
        jnp.asarray(rows[bucket_name(i)]).shape)
        for i, s in enumerate(new)}


def param_row_specs(layout: FsdpLayout, axis_name=None):
    """`{bucket_NNNN: P(ax)}` — shard_map in/out specs for the row
    dict (leading row dim over the data-parallel axis)."""
    from jax.sharding import PartitionSpec as P

    axes = collectives._resolve_axis(axis_name)
    ax = axes[0] if axes else "hvd"
    return {bucket_name(i): P(ax) for i in range(len(layout.plans))}


def param_row_shardings(layout: FsdpLayout, mesh, axis_name=None):
    """NamedShardings for host-level placement / checkpoint restore of
    the row dict (each bucket's rows sharded over the data axis, so no
    host ever holds a full replica)."""
    from jax.sharding import NamedSharding

    specs = param_row_specs(layout, axis_name)
    return {k: NamedSharding(mesh, s) for k, s in specs.items()}


def reshard_rows(rows, layout: FsdpLayout, new_world: int):
    """Re-slice the parameter rows across a world-size change (elastic
    resize) — the parameter twin of `zero.reshard_state`. Shapes only,
    no collectives; returns rows laid out for `new_world`."""
    if new_world == layout.world:
        return dict(rows)
    if new_world <= 1:
        raise ValueError(
            "resizing to a single-rank world un-shards the parameters "
            "— use unshard_params and the plain optimizer paths")
    out = {}
    for i, L in enumerate(layout.lens):
        flat = jnp.asarray(rows[bucket_name(i)]).reshape(-1)[: L]
        k2 = -(-L // new_world)
        padded = jnp.zeros((new_world * k2,), flat.dtype).at[: L].set(flat)
        out[bucket_name(i)] = padded.reshape(new_world, k2)
    return out


class FsdpEFState(NamedTuple):
    """FullyShardedOptimizer state under the int8 error-feedback wire:
    the inner (ZeRO-layout) optimizer state plus one residual leaf per
    bucket. Residual leaves are `(world, world*k2_i)` float32 — row r
    is rank r's PRIVATE quantization error over the whole padded row
    stack it quantizes (`k2_i` = the block-padded shard width), shard
    them one row per device with `hvd.sharded_state_specs` exactly like
    the inner rows. Rank-private by construction: each rank compensates
    only the contribution it quantized, never a peer's."""

    inner: Any
    residual: Any


def _residual_mats(state, layout: FsdpLayout, block: int):
    """The rank-private residual as per-bucket `(world, k2)` matrices
    (reshaped from the `(1, world*k2)` rows shard_map delivers), or
    None when the state carries no residual."""
    if not isinstance(state, FsdpEFState):
        return None
    n = layout.world
    mats = []
    for i, k in enumerate(layout.ks):
        k2 = -(-k // block) * block
        r = jnp.asarray(state.residual[i])
        if r.ndim == 2 and r.shape[0] == 1:
            r = r.reshape(-1)
        if r.shape != (n * k2,):
            raise ValueError(
                f"error-feedback residual for {bucket_name(i)} has "
                f"shape {tuple(jnp.shape(state.residual[i]))}, "
                f"expected a (1, {n * k2}) row — a compression-block "
                "knob change between init and update, or missing "
                "sharded_state_specs on the optimizer state")
        mats.append(r.reshape(n, k2))
    return mats


def FullyShardedOptimizer(optimizer, axis_name=None,
                          fusion_threshold_bytes=None,
                          bucket_backward_order=None,
                          compression=None):
    """Wrap an elementwise optax optimizer for fully-sharded (ZeRO-3)
    training: parameters AND optimizer state live as per-bucket row
    shards, 1/N per rank.

    Contract differences from ShardedOptimizer, stated plainly:

    * ``init(params)`` accepts the full params pytree (or its
      `eval_shape`) and lays the state out exactly as ZeRO-1 does —
      `(n, k_i)` rows per bucket, plus `FsdpEFState` residual rows
      under the int8 error-feedback wire;
    * ``update(grads, state, params)`` consumes the **staged shards**
      the FSDP runner produced (`ops/overlap.fsdp_staged_value_and_grad`
      or the gathered reference `fsdp.fsdp_value_and_grad(mode=
      "upfront")`) — the reduce-scatters already ran inside the
      backward; ``params`` is the list of this rank's `(k_i,)` shards
      (`fsdp.local_shards`); the return is ``(update_shards, state)``
      with NO all-gather — apply with `fsdp.apply_shard_updates`.
      A full gradient pytree here raises with a pointer: the layout
      authority lives with the step builder, not this transform.

    ``compression`` resolves the HOROVOD_COMPRESSION knob at
    construction (like DistributedOptimizer); the int8 wire runs WITH
    error feedback on the rank-private shard — the layout freedom
    ZeRO-1 didn't have (docs/zero.md's caveat does not apply here).
    """
    import optax

    from .compression import Compression, compressor_wire_spec

    comp = Compression.from_knobs() if compression is None else compression
    wire = compressor_wire_spec(comp)
    ef = wire is not None and wire.kind == "int8" and wire.error_feedback

    def _layout_for(params):
        return fsdp_layout(
            params, world=zero_mod._world(axis_name),
            fusion_threshold_bytes=fusion_threshold_bytes,
            bucket_backward_order=bucket_backward_order)

    def init_fn(params):
        n = zero_mod._world(axis_name)
        if n <= 1:
            return optimizer.init(params)
        layout = _layout_for(params)
        from ..ops.fusion import pack_buckets_by_plan

        bs = pack_buckets_by_plan(params, layout.plans)
        inner = optimizer.init(
            [zero_mod._pad_rows(b, n) for b in bs])
        if not ef:
            return inner
        residual = [
            jnp.zeros((n, n * (-(-k // wire.block) * wire.block)),
                      jnp.float32)
            for k in layout.ks
        ]
        return FsdpEFState(inner=inner, residual=residual)

    def update_fn(grads, state, params=None, **extra):
        n = zero_mod._world(axis_name)
        if n <= 1:
            return optimizer.update(grads, state, params, **extra)
        from ..ops.overlap import StagedShards

        if not isinstance(grads, StagedShards):
            raise ValueError(
                "FullyShardedOptimizer.update consumes staged gradient "
                "shards (the reduce-scatters run inside the backward); "
                "build the step through hvd.overlap."
                "fsdp_staged_value_and_grad or fsdp.fsdp_value_and_grad "
                "— a full gradient pytree cannot drive it (docs/fsdp.md)")
        if params is None or not isinstance(params, (list, tuple)):
            raise ValueError(
                "FullyShardedOptimizer.update requires params= the list "
                "of this rank's parameter shards (fsdp.local_shards)")
        g_shards = grads.shards
        p_shards = list(params)
        if len(g_shards) != len(p_shards) or any(
                jnp.shape(g) != jnp.shape(p)
                for g, p in zip(g_shards, p_shards)):
            raise ValueError(
                "staged gradient shards do not match the parameter "
                "shards' bucket layout — the staged value_and_grad "
                "must be built from the SAME layout (docs/fsdp.md)")
        inner_state = state
        if isinstance(state, FsdpEFState):
            if grads.new_residuals is None:
                raise ValueError(
                    "this FullyShardedOptimizer carries error-feedback "
                    "state but the staged shards arrived without an "
                    "updated residual; pass opt_state= to the staged "
                    "value_and_grad (docs/fsdp.md)")
            inner_state = state.inner
        # (1, k) state rows -> (k,) for the elementwise inner update;
        # a full (n, k) leaf means the caller forgot
        # sharded_state_specs — fail at the cause (zero.py's guard)
        for path, s in jax.tree_util.tree_flatten_with_path(
                inner_state)[0]:
            if (hasattr(s, "ndim") and s.ndim == 2 and s.shape[0] == n):
                raise ValueError(
                    "FullyShardedOptimizer.update received an unsharded "
                    f"state leaf {jax.tree_util.keystr(path)} of shape "
                    f"{tuple(s.shape)} — shard the optimizer state with "
                    "hvd.sharded_state_specs(state) so each device "
                    "receives its own (1, k) row.")
        local_state = jax.tree_util.tree_map(
            lambda s: s.reshape(-1) if (
                hasattr(s, "ndim") and s.ndim == 2 and s.shape[0] == 1
            ) else s,
            inner_state)
        upd_shards, new_local = optimizer.update(
            g_shards, local_state, p_shards, **extra)
        new_inner = jax.tree_util.tree_map(
            lambda nl, ol: nl.reshape(ol.shape) if (
                hasattr(ol, "ndim") and ol.ndim == 2
            ) else nl,
            new_local, inner_state)
        if isinstance(state, FsdpEFState):
            new_state = FsdpEFState(
                inner=new_inner, residual=list(grads.new_residuals))
        else:
            new_state = new_inner
        return list(upd_shards), new_state

    # reduction recipe for the staged runner (ops/overlap.py)
    update_fn._hvd_overlap_info = dict(
        kind="fsdp", compression=comp, axis_name=axis_name,
        fusion_threshold_bytes=fusion_threshold_bytes,
        bucket_backward_order=bucket_backward_order,
        process_set=None, backward_passes_per_step=1,
        error_feedback=ef, wire=wire,
    )
    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


def fsdp_value_and_grad(stages_fn, opt, layout: FsdpLayout,
                        mode: str = "prefetch", prefetch=None,
                        regather=None, offload=None):
    """Build ``vag(rows, *batch, opt_state=None) -> (loss,
    StagedShards)`` over fully-sharded parameter rows.

    ``mode="prefetch"`` (the real path) delegates to
    `ops/overlap.fsdp_staged_value_and_grad`: segmented forward,
    per-bucket all-gathers prefetch-interleaved with compute, staged
    backward reduce-scatters — and, under ``regather`` (default the
    HOROVOD_FSDP_REGATHER knob, on), a primal-only forward with the
    backward re-issuing each bucket's gather at its backward-first-use
    boundary so no gathered weights survive forward→backward;
    ``offload`` additionally moves stage-boundary carries to host RAM
    (HOROVOD_FSDP_OFFLOAD). ``mode="upfront"`` is the **gathered
    reference**: every bucket all-gathered unpinned at t=0, one
    monolithic `jax.value_and_grad` over the replicated tree, then the
    ordered monolithic scatter chain — the naive lowering the A/B
    artifact compares against and the bitwise-parity oracle
    `scripts/fsdp_check.py` gates with. All modes share every reduce
    and update op, which is what makes parity exact."""
    from ..ops import overlap as overlap_mod

    if mode == "prefetch":
        return overlap_mod.fsdp_staged_value_and_grad(
            stages_fn, opt, layout, prefetch=prefetch,
            regather=regather, offload=offload)
    if mode != "upfront":
        raise ValueError(f"unknown fsdp mode {mode!r} "
                         "(expected prefetch|upfront)")

    info = overlap_mod._reducer_info(opt)
    if info["kind"] != "fsdp":
        raise ValueError(
            "fsdp_value_and_grad needs a FullyShardedOptimizer "
            "(ShardedOptimizer(params_sharded=True)); got kind "
            f"{info['kind']!r}")

    def vag(rows, *batch, opt_state=None):
        from ..core.state import global_state
        from ..ops.overlap import StagedShards

        ax = zero_mod._live_axis(info.get("axis_name"))
        if ax is None:
            raise RuntimeError(
                "fsdp_value_and_grad must run inside shard_map/jit "
                "with the data-parallel mesh axis bound")
        n = layout.world
        wire = info.get("wire")
        ef = bool(info.get("error_feedback"))
        shards = local_shards(rows, layout)
        # the naive lowering: gather EVERYTHING up front, unpinned —
        # a full replicated copy of the model lives for the whole step
        full_bufs = [
            jax.lax.all_gather(s, ax, tiled=True)[: L]
            for s, L in zip(shards, layout.lens)
        ]
        from ..ops.fusion import (pack_buckets_by_plan,
                                  unflatten_buckets_by_plan)

        params = unflatten_buckets_by_plan(
            full_bufs, layout.treedef, list(layout.plans),
            layout.nleaves)
        stages = stages_fn(*batch)

        def full_loss(p):
            carry = jnp.zeros((), jnp.float32)
            for st in stages:
                carry = st.fwd({k: p[k] for k in st.keys}, carry)
            return carry

        loss, grads = jax.value_and_grad(full_loss)(params)
        gb = pack_buckets_by_plan(grads, list(layout.plans))
        res_mats = (_residual_mats(opt_state, layout, wire.block)
                    if ef else None)
        if ef and res_mats is None:
            raise ValueError(
                "this FullyShardedOptimizer carries error-feedback "
                "state; pass opt_state= so the residual rides the "
                "quantized reduce-scatters (docs/fsdp.md)")
        ordered = (global_state().knobs.ordered_buckets and len(gb) > 1)
        reduced, new_res, prev = [], [], None
        for bi, b in enumerate(gb):
            rws = zero_mod._pad_rows(b, n)
            if ordered and prev is not None:
                rws, _ = jax.lax.optimization_barrier((rws, prev))
            if ef:
                s, nr = zero_mod._scatter_bucket(
                    rws, ax, n, wire, residual=res_mats[bi])
                new_res.append(nr.reshape(1, -1))
            else:
                s = zero_mod._scatter_bucket(rws, ax, n, wire)
            prev = s
            reduced.append(s)
        return loss, StagedShards(
            reduced, new_residuals=new_res if ef else None)

    return vag
