"""ZeRO-1-style sharded optimizer states over the data-parallel axis.

SURVEY §2.5 frames the reference's first-class reducescatter/allgather
as "ZeRO-style building blocks" (reference operations.cc:1725,1532) —
but the reference stops at the blocks; users hand-roll the optimizer.
On TPU the composition is reduce-scatter + all-gather riding ICI, so
this module ships it:

  * gradients are packed into the same backward-availability-ordered
    fusion buckets the all-reduce path uses (ops/fusion.py), and each
    bucket is `psum_scatter`'d — chained through optimization_barrier
    (knobs.ordered_buckets) so bucket k's reduce-scatter can issue
    while backward for earlier layers is still computing, the SAME
    comm/compute-overlap structure as DistributedOptimizer
    (docs/benchmarks.md);
  * the inner optax optimizer updates ONLY this rank's shard of each
    bucket — its state (Adam's m/v, momentum, ...) lives sharded,
    cutting optimizer-state HBM by the world size (BERT-L Adam fp32
    m+v: 2.7 GB → 334 MB on 8 chips);
  * the update shards are all-gathered back so `update()` still
    returns a full updates pytree (drop-in optax contract, same call
    shape as DistributedOptimizer).

Usage (single-controller SPMD, inside shard_map like
DistributedOptimizer):

    opt = hvd.ShardedOptimizer(optax.adam(1e-3))
    state = opt.init(params)                # leaves sharded over ranks
    specs = hvd.sharded_state_specs(state)  # P("hvd") / P() per leaf

    def step(p, s, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        upd, s = opt.update(g, s, p)
        return optax.apply_updates(p, upd), s, ...

    from jax import shard_map
    jax.jit(shard_map(step, mesh=mesh,
                      in_specs=(P(), specs, P("hvd"), P("hvd")),
                      out_specs=(P(), specs, ...), check_vma=False))

State layout: the inner optimizer is initialized on a LIST of
per-bucket `(n, k_i)` arrays (`k_i = ceil(bucket_len / n)`, row r =
rank r's shard), so its array-shaped state leaves mirror that list.
The bucketization is deterministic in (pytree structure, dtypes,
fusion threshold, bucket ordering), which is what makes init/update/
reshard agree on the layout.

Constraints (documented, asserted): the inner optimizer must be
elementwise in its state (adam/adamw/sgd/momentum/rmsprop... — anything
whose state leaves mirror the flat parameter vector); factored-state
optimizers (adafactor) need the parameter structure and cannot shard
this way. One live data-parallel axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import collectives


def _live_axis(axis_name):
    axes = collectives._resolve_axis(axis_name)
    live = collectives._bound_axes(axes)
    if len(live) > 1:
        raise ValueError(
            "ShardedOptimizer shards over exactly one data-parallel "
            f"axis; got live axes {live}")
    return live[0] if live else None


def _world(axis_name) -> int:
    n = collectives._group_size(None, axis_name)
    return max(int(n), 1)


def _plan(params, threshold_bytes, backward_order=None):
    """The layout authority: ALWAYS computed from the params pytree
    (data-free), so a grad-dtype cast (bf16 grads on fp32 params) can
    never shift bucket boundaries away from the state layout."""
    from ..ops.fusion import pytree_bucket_plan

    return pytree_bucket_plan(params, threshold_bytes=threshold_bytes,
                              backward_order=backward_order)


def _pack(tree, plan):
    from ..ops.fusion import pack_pytree_by_plan

    return pack_pytree_by_plan(tree, plan)


def _pad_rows(b, n):
    """1-D bucket → (n, k) rows, zero-padded; row r is rank r's shard."""
    k = -(-int(b.size) // n)
    out = jnp.zeros((n * k,), b.dtype).at[: b.size].set(b)
    return out.reshape(n, k)


def _scatter_bucket(rows, ax, n, wire, residual=None):
    """Reduce-scatter one padded (n, k) gradient bucket to this rank's
    AVERAGED (k,) shard on the configured wire — the shared per-bucket
    data plane of the monolithic chain (update_fn), the
    backward-interleaved scheduler (ops/overlap.py), and the FSDP
    backward (optim/fsdp.py), extracted verbatim so all three trace
    identical collectives.

    ``residual`` (int8 wire only) is this rank's error-feedback shard
    over the padded row stack; when given, the return is
    ``(shard, new_residual)`` — the FSDP path carries it
    (docs/fsdp.md), the ZeRO-1 path never passes it (the residual
    would change its state layout, docs/zero.md)."""
    from .compression import quantized_reduce_scatter_rows, wire_applies

    if wire_applies(wire, rows.dtype) and wire.kind == "int8":
        # block-quantized exchange; the shard SUM comes back in
        # f32 and averages exactly like the uncompressed path
        if residual is not None:
            shard, new_res = quantized_reduce_scatter_rows(
                rows, ax, wire.block, residual=residual)
            return (shard / n).astype(rows.dtype), new_res
        return (quantized_reduce_scatter_rows(
            rows, ax, wire.block) / n).astype(rows.dtype)
    if residual is not None:
        raise ValueError(
            "error-feedback residual passed for a non-int8 wire — only "
            "the quantized exchange produces an error to feed back")
    if wire_applies(wire, rows.dtype):
        return (jax.lax.psum_scatter(
            rows.astype(wire.wire_dtype).reshape(-1), ax,
            scatter_dimension=0, tiled=True) / n
        ).astype(rows.dtype)
    return jax.lax.psum_scatter(
        rows.reshape(-1), ax, scatter_dimension=0, tiled=True) / n


def _as_staged_shards(grads):
    from ..ops.overlap import StagedShards

    return grads if isinstance(grads, StagedShards) else None


def ShardedOptimizer(optimizer, axis_name=None,
                     fusion_threshold_bytes=None,
                     bucket_backward_order=None,
                     compression=None,
                     params_sharded=False):
    """Wrap an elementwise optax optimizer so its state is sharded 1/N
    per rank (ZeRO stage 1). Returns an optax GradientTransformation
    whose `update()` reduce-scatters gradient buckets (backward-ordered,
    overlap-chained), updates the local shards, and all-gathers the
    updates. `fusion_threshold_bytes` / `bucket_backward_order` default
    to the global knobs, like DistributedOptimizer — pin them
    explicitly when the state must be restorable in a process whose
    knobs may differ (see reshard_state).

    `compression` (default: the HOROVOD_COMPRESSION knob) puts the
    gradient reduce-scatter on the compressed wire
    (docs/compression.md): cast wires (bf16/fp16) run the psum_scatter
    in the cast dtype; the int8 wire block-quantizes each rank's rows
    for the exchange (optim.compression.quantized_reduce_scatter_rows —
    row padding is internal, so the sharded state LAYOUT is identical
    to the uncompressed plane). The update all-gather stays full
    precision (it carries the applied update, not a SUM), and the int8
    reduce-scatter runs without error feedback — the residual would
    need a state-layout change; use DistributedOptimizer for int8+EF.
    ``none`` is bitwise-identical to the pre-compression behavior.

    ``params_sharded=True`` escalates from ZeRO-1 to ZeRO-3: it returns
    :func:`horovod_tpu.optim.fsdp.FullyShardedOptimizer` over the same
    arguments — parameters themselves live sharded as per-bucket rows
    and the train step gathers them bucket-by-bucket in the forward
    (docs/fsdp.md). The two spellings are interchangeable entry points
    to the same optimizer."""
    import optax

    if params_sharded:
        from .fsdp import FullyShardedOptimizer

        return FullyShardedOptimizer(
            optimizer, axis_name=axis_name,
            fusion_threshold_bytes=fusion_threshold_bytes,
            bucket_backward_order=bucket_backward_order,
            compression=compression)

    def init_fn(params):
        n = _world(axis_name)
        if n <= 1:
            return optimizer.init(params)
        bs, _ = _pack(params, _plan(params, fusion_threshold_bytes,
                                    bucket_backward_order))
        return optimizer.init([_pad_rows(b, n) for b in bs])

    def update_fn(grads, state, params=None, **extra):
        n = _world(axis_name)
        if n <= 1:
            if _as_staged_shards(grads) is not None:
                raise RuntimeError(
                    "staged gradient shards on a size-1 world — the "
                    "overlap schedule cannot have produced these here")
            return optimizer.update(grads, state, params, **extra)
        if params is None:
            raise ValueError(
                "ShardedOptimizer.update requires params (the local "
                "parameter shards are sliced from them)")
        ax = _live_axis(axis_name)
        if ax is None:
            raise RuntimeError(
                "ShardedOptimizer.update must run inside shard_map/jit "
                "with the data-parallel mesh axis bound (it issues "
                "psum_scatter/all_gather)")
        plan = _plan(params, fusion_threshold_bytes,
                     bucket_backward_order)
        staged = _as_staged_shards(grads)
        from ..core.state import global_state

        if staged is not None:
            r = jax.lax.axis_index(ax)
            # the backward-interleaved scheduler (ops/overlap.py)
            # already reduce-scattered each bucket inside the backward;
            # consume its shards after validating they match THIS
            # plan's layout (same params + threshold + ordering)
            pb, unflatten = _pack(params, plan)
            lens = [int(b.size) for b in pb]
            g_shards = staged.shards
            if len(g_shards) != len(lens) or any(
                    s.shape != (-(-L // n),)
                    for s, L in zip(g_shards, lens)):
                raise ValueError(
                    "staged gradient shards do not match this "
                    "ShardedOptimizer's bucket layout — the staged "
                    "value_and_grad must be built from the SAME "
                    "optimizer (docs/overlap.md)")
        else:
            gb, unflatten = _pack(grads, plan)
            pb, _ = _pack(params, plan)
            lens = [int(b.size) for b in gb]
            ordered = (global_state().knobs.ordered_buckets
                       and len(gb) > 1)
            r = jax.lax.axis_index(ax)

            # chained per-bucket reduce-scatter: bucket j's collective
            # depends only on ITS gradients (+ the chain edge), so it
            # issues while backward for later buckets still computes —
            # the same structural overlap as optim/distributed.py's
            # all-reduce chain, asserted in tests/test_zero.py
            from .compression import compressor_wire_spec, Compression

            comp = (Compression.from_knobs() if compression is None
                    else compression)
            wire = compressor_wire_spec(comp)

            g_shards, prev = [], None
            for b in gb:
                rows = _pad_rows(b, n)
                if ordered and prev is not None:
                    rows, _ = jax.lax.optimization_barrier((rows, prev))
                s = _scatter_bucket(rows, ax, n, wire)
                prev = s
                g_shards.append(s)
        p_shards = [
            jax.lax.dynamic_slice_in_dim(
                _pad_rows(b, n).reshape(-1), r * _k(b, n), _k(b, n))
            for b in pb
        ]
        # state rows arrive (1, k_i) per device via sharded_state_specs;
        # flatten to (k_i,) for the inner elementwise update. A full
        # (world, k_i) leaf here means the caller ran inside shard_map
        # WITHOUT sharded_state_specs — every device got the whole
        # state, and the elementwise update would broadcast (n, k)
        # against (k,) grad shards, surfacing only as a baffling shape
        # error in unflatten/all_gather far from the cause. Fail at the
        # cause instead.
        for path, s in jax.tree_util.tree_flatten_with_path(state)[0]:
            if (n > 1 and hasattr(s, "ndim") and s.ndim == 2
                    and s.shape[0] == n):
                raise ValueError(
                    "ShardedOptimizer.update received an unsharded "
                    f"state leaf {jax.tree_util.keystr(path)} of shape "
                    f"{tuple(s.shape)} — first dim equals the "
                    f"data-parallel world size ({n}) instead of 1. "
                    "Shard the optimizer state in your shard_map "
                    "in_specs with hvd.sharded_state_specs(state) so "
                    "each device receives its own (1, k) row."
                )
        local_state = jax.tree_util.tree_map(
            lambda s: s.reshape(-1) if (
                hasattr(s, "ndim") and s.ndim == 2 and s.shape[0] == 1
            ) else s,
            state)
        upd_shards, new_local = optimizer.update(
            g_shards, local_state, p_shards, **extra)
        # restore each leaf to its incoming row shape (template = the
        # incoming state, so no shape sniffing)
        new_state = jax.tree_util.tree_map(
            lambda nl, ol: nl.reshape(ol.shape) if (
                hasattr(ol, "ndim") and ol.ndim == 2
            ) else nl,
            new_local, state)
        reduced = [
            jax.lax.all_gather(s, ax, tiled=True)[: L]
            for s, L in zip(upd_shards, lens)
        ]
        return unflatten(reduced), new_state

    # reduction recipe for the backward-interleaved scheduler
    # (ops/overlap.py staged_value_and_grad introspects it)
    update_fn._hvd_overlap_info = dict(
        kind="zero", compression=compression, axis_name=axis_name,
        fusion_threshold_bytes=fusion_threshold_bytes,
        bucket_backward_order=bucket_backward_order,
        process_set=None, backward_passes_per_step=1,
    )
    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


def _k(b, n) -> int:
    return -(-int(b.size) // n)


def reshard_state(state, params, old_world: int, new_world: int,
                  fusion_threshold_bytes=None, bucket_backward_order=None):
    """Re-shard a ShardedOptimizer state across a world-size change
    (elastic resize: the reference's elastic reset re-broadcasts
    optimizer state, common/elastic.py — here the state LAYOUT is
    world-size-dependent, so a resize must re-slice it). `params` (the
    pytree the optimizer was built for) plus the SAME fusion threshold
    and bucket ordering the state was built under reproduce the
    bucketization (both default to the live knobs — pass them
    explicitly when restoring in a process whose knobs may differ from
    the saving process's), so each `(old_world, k_i)` leaf is re-sliced
    to the `(new_world, k_i')` grid the new world's update step will
    recompute. Shapes only — the plan is data-free and no collectives
    run — so call it on the restored host-side state inside the elastic
    reset callback before re-entering the train loop."""
    if old_world == new_world:
        return state
    if old_world <= 1 or new_world <= 1:
        raise ValueError(
            "reshard_state converts between sharded layouts; a size-1 "
            "world uses the plain (unsharded) inner state — re-init "
            "the optimizer instead")
    _, plans = _plan(params, fusion_threshold_bytes,
                     backward_order=bucket_backward_order)
    lens = [sum(n for (_, _, n, _) in bp) for bp in plans]
    k_old = [-(-L // old_world) for L in lens]
    k_new = [-(-L // new_world) for L in lens]
    matched = [0]

    def leaf(path, s):
        if not (hasattr(s, "ndim") and s.ndim == 2
                and s.shape[0] == old_world):
            return s
        # the bucket index is the state leaf's position in the list
        # mirroring the params proxy — the last SequenceKey in its path
        idx = None
        for key in reversed(path):
            if isinstance(key, jax.tree_util.SequenceKey):
                idx = key.idx
                break
        if idx is None or idx >= len(lens) or \
                s.shape != (old_world, k_old[idx]):
            raise ValueError(
                f"state leaf at {jax.tree_util.keystr(path)} has shape "
                f"{s.shape}, which does not match bucket {idx} of the "
                f"({old_world}-world, threshold-derived) layout — wrong "
                "old_world, wrong params, or a different fusion "
                "threshold than the state was built with")
        matched[0] += 1
        flat = s.reshape(-1)[: lens[idx]]
        out = jnp.zeros((new_world * k_new[idx],), flat.dtype)
        out = out.at[: lens[idx]].set(flat)
        return out.reshape(new_world, k_new[idx])

    out = jax.tree_util.tree_map_with_path(leaf, state)
    if not matched[0]:
        # a wrong old_world / params would otherwise pass the stale
        # layout through silently and fail far away in shard_map
        raise ValueError(
            f"no state leaf has the {old_world}-row bucketed layout "
            f"implied by old_world={old_world} and these params — "
            "wrong old_world, wrong params, or not a ShardedOptimizer "
            "state")
    return out


def sharded_state_specs(state, axis_name=None):
    """Pytree of PartitionSpec for a ShardedOptimizer state: (n, k_i)
    leaves shard their leading dim over the data-parallel axis (one row
    per rank), scalars (e.g. Adam's count) replicate. Pass as the
    state's in_specs/out_specs in shard_map."""
    from jax.sharding import PartitionSpec as P

    axes = collectives._resolve_axis(axis_name)
    ax = axes[0] if axes else "hvd"
    n = _world(axis_name)

    def spec(leaf):
        if hasattr(leaf, "ndim") and leaf.ndim == 2 and leaf.shape[0] == n:
            return P(ax)
        return P()

    return jax.tree_util.tree_map(spec, state)
