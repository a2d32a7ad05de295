"""DistributedOptimizer / DistributedGradientTape for JAX.

Reference user surface:
  * torch `_DistributedOptimizer` (/root/reference/horovod/torch/optimizer.py:36)
    — per-parameter grad hooks fire async all-reduces as backprop produces
    gradients, `backward_passes_per_step` accumulates locally before
    reducing, `synchronize()` joins before `step()`.
  * TF `DistributedOptimizer` / `_DistributedGradientTape`
    (/root/reference/horovod/tensorflow/__init__.py:742,873).

TPU-native shape: JAX has no autograd hooks and needs none — the gradient
pytree is available as a value, and the reduction becomes part of the
compiled step, where XLA overlaps collectives with remaining backprop
automatically (latency-hiding scheduler), achieving what the reference's
hook+background-thread machinery does by hand. The wrapper is an *optax
gradient transformation*:

    opt  = hvd.DistributedOptimizer(optax.adam(1e-3 * hvd.size()))
    # inside pjit/shard_map training step:
    updates, opt_state = opt.update(grads, opt_state, params)

It fuses gradients into threshold-bounded buckets (ops/fusion.py), applies
wire compression, all-reduces each bucket with one XLA collective, and
supports Average/Sum/Adasum and process sets.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core.state import global_state
from ..ops import collectives
from ..ops.adasum import adasum_allreduce
from ..ops.collectives import ReduceOp
from ..ops.fusion import (flatten_pytree_buckets, pack_groups_by_plan,
                          pack_pytree_by_plan, pytree_bucket_plan)
from ..utils import scopes
from .compression import (Compression, NoneCompressor, WireSpec,
                          compressor_wire_spec, quantized_psum,
                          wire_sent_bytes)


def _int8_bucket_allreduce(bucket, live, wire: WireSpec, residual):
    """SUM one fused bucket over the live axes with the int8 wire:
    hierarchical routing (full-precision ICI reduce-scatter, quantized
    DCN outer leg) when the hierarchy knob is on or the mesh factors the
    world into 2+ axes, the flat EQuARX two-phase form otherwise.
    Returns ``(reduced, new_residual)`` when `residual` is given."""
    from ..core import basics
    from ..ops import hierarchical

    sizes = basics.bound_axis_sizes()
    knobs = global_state().knobs
    if (len(live) > 1
            or hierarchical.hierarchy_enabled_for("allreduce", None)):
        return hierarchical.hierarchical_psum(
            bucket, live, sizes, knobs.hierarchical_local_size,
            wire=wire, residual=residual)
    return quantized_psum(bucket, live[0], sizes[live[0]], wire.block,
                          residual=residual)


def _reduce_bucket(b, op, compression, wire: Optional[WireSpec],
                   int8_wire: bool, live, n, process_set, axis_name,
                   res_bucket=None):
    """Reduce ONE bucket on the configured wire — the shared
    per-bucket data plane of the monolithic chain (_reduce_grad_tree)
    and the backward-interleaved scheduler (ops/overlap.py), extracted
    verbatim so both trace identical collectives. `b` is the bucket as
    one fused 1-D array, or as a group (a tuple: its direct leaves in
    their own shapes, then its packed small operand —
    ops/fusion.pack_groups_by_plan); a group's operands are cast,
    reduced and scaled one by one, the same elementwise arithmetic and
    the same sums as over the flat array, and come back as a tuple.

    Returns ``(reduced, chain_token, new_residual)``: `reduced` is the
    decompressed result, `chain_token` the value the ordered-bucket
    barrier chain threads (the pre-decompress payload, preserving the
    exact HLO the chain emitted before the extraction), `new_residual`
    the updated error-feedback bucket (or `res_bucket` unchanged on
    paths that don't consume it)."""
    def allreduce_payload(x):
        """The plain all-reduce of a wire payload, array or tuple."""
        return collectives.allreduce(
            x,
            op=ReduceOp.SUM if op == ReduceOp.AVERAGE else op,
            process_set=process_set,
            axis_name=axis_name,
            postscale_factor=(1.0 / n) if op == ReduceOp.AVERAGE else 1.0,
        )

    if isinstance(b, tuple):
        wires, ctxs = zip(*map(compression.compress, b))
        red = allreduce_payload(wires)
        return (tuple(map(compression.decompress, red, ctxs)), red,
                res_bucket)
    b_float = jnp.issubdtype(b.dtype, jnp.floating)
    if int8_wire and b_float and live:
        # quantized SUM over the live axes (flat EQuARX form or
        # hierarchical DCN-outer-leg routing); AVERAGE divides the
        # dequantized sum — the quantized payload itself always
        # carries the SUM contribution
        out = _int8_bucket_allreduce(b, live, wire, res_bucket)
        if res_bucket is not None:
            red, new_r = out
        else:
            red, new_r = out, None
        if op == ReduceOp.AVERAGE:
            red = (red / n).astype(b.dtype)
        return red, red, new_r
    if int8_wire:
        # int8 never cast-reduces (an int8 SUM would overflow and
        # mix per-rank scales): any bucket falling through here —
        # non-floating, or an eager fallthrough that skipped the
        # grouped enqueue — moves uncompressed (residual unchanged)
        wire_b, ctx = b, None
    else:
        wire_b, ctx = compression.compress(b)
    if op == ReduceOp.ADASUM:
        if not live:
            red = wire_b
        else:
            red = adasum_allreduce(wire_b, live[0],
                                   process_set=process_set)
    else:
        red = allreduce_payload(wire_b)
    return compression.decompress(red, ctx), red, res_bucket


_WIRE_MISMATCH_WARNED = [False]


def _warn_wire_mismatch_once(requested: str, executor: str) -> None:
    """An explicit `compression=` argument disagrees with the eager
    executor's knob-resolved wire: on the native eager path the
    EXECUTOR owns the wire, so the knob wins — make the conflict loud
    once instead of silently training under a different wire than the
    constructor asked for."""
    if _WIRE_MISMATCH_WARNED[0]:
        return
    _WIRE_MISMATCH_WARNED[0] = True
    from ..utils.logging import get_logger

    get_logger().warning(
        "DistributedOptimizer compression=%r does not match the eager "
        "executor's HOROVOD_COMPRESSION wire (%r); the executor's wire "
        "wins on the native eager path. Set HOROVOD_COMPRESSION=%s (or "
        "drop the explicit compression argument) so both agree — "
        "docs/compression.md.", requested, executor, requested)


_TUNED_MASK_WARNED = [False]


def _warn_tuned_threshold_masked_once(explicit: int) -> None:
    """An explicit per-optimizer ``fusion_threshold_bytes`` outranks the
    global knob — which is exactly where the closed-loop autotuner
    (ops/autotune.py) pins its winners. When tuning (or a warm-start
    cache) is active, the pinned bucket size would be silently masked
    by the constructor argument: say so once (docs/autotune.md)."""
    if _TUNED_MASK_WARNED[0]:
        return
    knobs = global_state().knobs
    if not (knobs.autotune or getattr(knobs, "autotune_cache", "")):
        return
    _TUNED_MASK_WARNED[0] = True
    from ..utils.logging import get_logger

    get_logger().warning(
        "DistributedOptimizer was built with an explicit "
        "fusion_threshold_bytes=%d while autotuning is active "
        "(HOROVOD_AUTOTUNE / HOROVOD_AUTOTUNE_CACHE): the explicit "
        "value masks the tuner's pinned bucket size for this "
        "optimizer. Drop the argument to let the tuned knob apply — "
        "docs/autotune.md.", explicit)


_STATELESS_EF_WARNED = [False]


def _warn_stateless_ef_once() -> None:
    """An error-feedback compressor reached a stateless reduce surface
    (DistributedGradientTape / distributed_value_and_grad) on the SPMD
    path: the quantized SUM runs un-debiased there (int8-raw
    semantics). Say so once instead of silently accumulating bias."""
    if _STATELESS_EF_WARNED[0]:
        return
    _STATELESS_EF_WARNED[0] = True
    from ..utils.logging import get_logger

    get_logger().warning(
        "int8 wire compression is running WITHOUT error feedback on "
        "this path: DistributedGradientTape/distributed_value_and_grad "
        "carry no residual state, so quantization bias accumulates "
        "across steps. Use hvd.DistributedOptimizer(compression="
        "Compression.int8) (with hvd.error_feedback_specs inside "
        "shard_map) for the unbiased wire — docs/compression.md."
    )


def _reduce_grad_tree(
    grads,
    op: ReduceOp,
    compression,
    process_set,
    axis_name,
    fusion_threshold_bytes: Optional[int],
    residual=None,
):
    """Fused, compressed all-reduce of a gradient pytree.

    ``compression=None`` resolves the knob-selected compressor
    (HOROVOD_COMPRESSION, docs/compression.md). With ``residual`` (an
    error-feedback pytree congruent to `grads`, f32 leaves) the return
    value is ``(reduced, new_residual)`` — only meaningful under the
    int8 wire on the SPMD path; other paths pass the residual through
    unchanged (the eager executors hold their own wire residuals).
    """
    if compression is None:
        compression = Compression.from_knobs()

    def _ret(red, new_res=None):
        if residual is None:
            return red
        return red, (new_res if new_res is not None else residual)

    axes = collectives._resolve_axis(axis_name)
    live = collectives._bound_axes(axes)
    if not live and global_state().world_size() <= 1:
        return _ret(grads)  # single rank: nothing to reduce

    n = collectives._group_size(process_set, axis_name)
    if n <= 1:
        # a live mesh axis of size 1 (single-chip bench world): the
        # collective is an identity, so skip the fusion-bucket
        # pack/unpack too — the traced BERT step spent ~4% of device
        # time packing buckets nothing would ever ride (docs/benchmarks.md)
        return _ret(grads)

    wire = compressor_wire_spec(compression)
    int8_wire = wire is not None and wire.kind == "int8"
    if int8_wire and (
        op not in (ReduceOp.SUM, ReduceOp.AVERAGE)
        or (live and process_set is not None
            and process_set.process_set_id != 0)
        or (not live and global_state().eager_runtime is None)
    ):
        # the quantized collective addresses whole axes with SUM
        # semantics; exotic reduce ops (ADASUM/MIN/...), SPMD
        # proper-subset process sets, and the single-controller eager
        # simulation fall back to the uncompressed plane. The one case
        # that keeps int8 alive without a live axis is the native eager
        # runtime, whose EXECUTOR owns the wire (including subset
        # batches over their sub-mesh).
        compression = NoneCompressor
        wire, int8_wire = None, False

    if (int8_wire and live and residual is None
            and getattr(compression, "error_feedback", False)):
        _warn_stateless_ef_once()

    plan = pytree_bucket_plan(grads, threshold_bytes=fusion_threshold_bytes)
    # A bucket is a GROUP of arrays wherever its reduction is elementwise
    # over a bound mesh axis: a large leaf rides the bucket's all-reduce
    # in its own shape and only the small leaves are packed. What needs
    # one contiguous array keeps the flat bucket: the int8 wire (blocks
    # and residual are laid out over it), Adasum (dot products over it),
    # the two-level all-reduce (a reduce-scatter cuts it in n) and the
    # native eager runtime (negotiation per tensor is the latency
    # fusion is for).
    from ..ops import hierarchical

    grouped = bool(live) and not int8_wire and op in (
        ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.MIN, ReduceOp.MAX
    ) and not hierarchical.hierarchy_enabled_for("allreduce", process_set)
    res_buckets = res_unflatten = None
    with jax.named_scope(scopes.HVD_PACK):
        buckets, unflatten = (pack_groups_by_plan if grouped
                              else pack_pytree_by_plan)(grads, plan)
        if residual is not None and int8_wire and live:
            # residual rides the SAME bucket layout as the gradients,
            # so a leaf's error lands back on that leaf at unflatten time
            res_buckets, res_unflatten = pack_pytree_by_plan(
                residual, plan)
    # Native eager world (top-level update, no bound mesh axis): submit
    # the WHOLE per-step bucket set through one batched enqueue round
    # (EagerRuntime.enqueue_batch via grouped_allreduce_async) instead
    # of one blocking negotiate-execute round trip per bucket — the
    # per-bucket serial synchronize was pure latency stacking, and the
    # single grouped submission is also the shape the steady-state plan
    # cache freezes after warmup.
    if (not live
            and collectives._native_rt_for_async(process_set) is not None
            and op != ReduceOp.ADASUM
            and len(buckets) > 0):
        rt_wire = getattr(global_state().eager_runtime,
                          "_executor_wire", lambda: None)()
        # whenever the executor carries ANY wire, it owns compression
        # for these buckets (pre-casting would stack two lossy wires);
        # a kind mismatch against an explicit compressor arg means the
        # knob wins — say so instead of silently double/un-compressing
        executor_owns_wire = wire is not None and rt_wire is not None
        if (wire is not None and rt_wire is not None
                and rt_wire.kind != wire.kind):
            _warn_wire_mismatch_once(wire.kind, rt_wire.kind)
        if int8_wire and rt_wire is None:
            # the int8 collective needs executor support; without the
            # knob the executor reduces at full precision
            _warn_wire_mismatch_once(wire.kind, "none")
        wires, ctxs = [], []
        for b in buckets:
            if int8_wire or executor_owns_wire:
                # the executor compresses once per fused batch (int8:
                # quantize + runtime-held error-feedback residual;
                # casts: one bucket-wide cast) — pre-compressing here
                # would double-apply the wire and make the
                # hvd_wire_bytes counters read an already-cast payload
                # as the logical baseline (ratio 1x instead of 2x)
                w, c = b, None
            else:
                w, c = compression.compress(b)
            wires.append(w)
            ctxs.append(c)
        h = collectives.grouped_allreduce_async(
            wires,
            op=ReduceOp.SUM if op == ReduceOp.AVERAGE else op,
            postscale_factor=(1.0 / n) if op == ReduceOp.AVERAGE
            else 1.0,
            name="hvd.grad", process_set=process_set,
        )
        reduced = [
            jnp.asarray(r) if (int8_wire or executor_owns_wire)
            else compression.decompress(jnp.asarray(r), c)
            for r, c in zip(collectives.synchronize(h), ctxs)
        ]
        from ..utils import metrics as _metrics

        if _metrics.enabled():
            total = sum(int(b.size) * b.dtype.itemsize for b in buckets)
            _metrics.record_grad_reduction(total, len(buckets))
        return _ret(unflatten(reduced))
    # Ordered buckets (reference semantics: fused responses execute in
    # controller order, operations.cc PerformOperation): chain bucket k
    # on bucket k-1's result through an optimization_barrier. What the
    # chain does: it keeps the buckets separate and in plan order.
    # Without it XLA's all-reduce combiner merges every bucket into ONE
    # variadic all-reduce over all gradients; with it, bucket k's
    # collective stays an instruction of its own whose only inputs are
    # its own gradients plus the ordering edge (a group's operands pass
    # the barrier together and may be combined with each other, not
    # with another bucket's). What it does not do: make the collectives
    # overlap the backward pass. On the chip they compile to synchronous
    # all-reduces after the backward pass and none of their time is
    # hidden (PERF.md section 5, every ledger line since PR 22);
    # tests/test_overlap_schedule.py's tier-1 test holds the structure
    # in the lowered module, and its compiled-schedule test is in the
    # slow tier, compiled for a described chip and never run on one.
    ordered = global_state().knobs.ordered_buckets and len(buckets) > 1
    reduced = []
    new_res_buckets = []
    prev = None
    with jax.named_scope(scopes.HVD_ALLREDUCE):
        for i, b in enumerate(buckets):
            if ordered and prev is not None:
                b, _ = jax.lax.optimization_barrier((b, prev))
            r_b = res_buckets[i] if res_buckets is not None else None
            red, prev, new_r = _reduce_bucket(
                b, op, compression, wire, int8_wire, live, n,
                process_set, axis_name, res_bucket=r_b)
            if res_buckets is not None:
                new_res_buckets.append(new_r)
            reduced.append(red)
    pm = global_state().parameter_manager
    from ..utils import metrics as _metrics

    if pm is not None or _metrics.enabled():
        # io_callback fires at *execution* time, once per real step, so the
        # tuner (and the metrics layer) observes actual throughput even
        # inside a jitted train step (a bare call here would only run once,
        # at trace time). Note: an already-compiled step keeps its bucket
        # structure; the tuned threshold applies to eager ops and
        # subsequent compilations — and a step compiled with metrics OFF
        # stays uninstrumented until recompiled.
        # a grouped bucket's operands count one by one (same bytes)
        arrays = jax.tree_util.tree_leaves(buckets)
        total = sum(int(b.size) * b.dtype.itemsize for b in arrays)
        from jax.experimental import io_callback

        if pm is not None:
            io_callback(functools.partial(pm.observe, total), None)
        if _metrics.enabled():
            io_callback(
                functools.partial(
                    _metrics.record_grad_reduction, total, len(buckets)
                ),
                None,
            )
            # wire accounting: what this step's gradient set would move
            # at logical precision vs what the compressed plane sends
            sent = sum(
                wire_sent_bytes(
                    int(b.size), b.dtype.itemsize,
                    wire if (wire is not None
                             and jnp.issubdtype(b.dtype, jnp.floating))
                    else None)
                for b in arrays
            )
            io_callback(
                functools.partial(
                    _metrics.record_wire_bytes, total, sent),
                None,
            )
    with jax.named_scope(scopes.HVD_UNPACK):
        if res_unflatten is not None and residual is not None:
            return _ret(unflatten(reduced),
                        res_unflatten(new_res_buckets))
        return _ret(unflatten(reduced))


class _AccumState(NamedTuple):
    inner: Any
    acc: Any
    counter: jnp.ndarray


class _EFState(NamedTuple):
    """DistributedOptimizer state under an error-feedback compressor:
    the inner optimizer state plus the per-leaf quantization residual.
    Residual leaves carry a leading world dimension — row r is rank r's
    private residual — and must be sharded one-row-per-device inside
    shard_map via :func:`error_feedback_specs` (the residual is
    device-varying: each rank compensates ITS OWN contribution's
    quantization error)."""

    inner: Any
    residual: Any


def _ef_row(r, g):
    """Squeeze one (1, ...) residual row (this device's shard of the
    world-dim residual) to the leaf shape; raise at the cause when the
    caller forgot error_feedback_specs."""
    if (hasattr(r, "ndim") and r.ndim == jnp.ndim(g) + 1
            and r.shape[0] == 1):
        return r[0]
    raise ValueError(
        "error-feedback residual leaf has shape "
        f"{getattr(r, 'shape', None)} — expected a (1, ...) row "
        "per device. Shard the optimizer state in your "
        "shard_map in_specs with hvd.error_feedback_specs(state)"
        " so each rank keeps its own residual row."
    )


def _residual_rows(state, grads_template):
    """This rank's error-feedback residual, squeezed to leaf shapes —
    or None when `state` carries no residual. Shared by _ef_update and
    the backward-interleaved scheduler (ops/overlap.py), so the staged
    quantized collectives consume exactly the rows the monolithic path
    would."""
    if isinstance(state, _AccumState):
        state = state.inner
    if not isinstance(state, _EFState):
        return None
    return jax.tree_util.tree_map(_ef_row, state.residual,
                                  grads_template)


def _staged_apply(staged, state, params, update_inner, **extra):
    """Consume gradients the backward-interleaved scheduler already
    reduced (ops/overlap.py StagedGrads): skip this optimizer's own
    reduction and run the inner update directly. Under error feedback
    the staged machinery produced the updated residual alongside."""
    if isinstance(state, _AccumState):
        raise ValueError(
            "staged (overlap-scheduled) gradients cannot drive a "
            "backward_passes_per_step > 1 optimizer — local "
            "accumulation reduces every k steps, the staged schedule "
            "reduces every step (docs/overlap.md)")
    if isinstance(state, _EFState):
        if staged.new_residual is None:
            raise ValueError(
                "staged gradients arrived without an updated "
                "error-feedback residual; pass opt_state= to the "
                "staged value_and_grad (docs/overlap.md)")
        updates, new_inner = update_inner(staged.tree, state.inner,
                                          params, **extra)
        return updates, _EFState(new_inner, staged.new_residual)
    return update_inner(staged.tree, state, params, **extra)


def _as_staged(grads):
    from ..ops.overlap import StagedGrads

    return grads if isinstance(grads, StagedGrads) else None


def error_feedback_specs(state, axis_name=None):
    """PartitionSpecs for a DistributedOptimizer state: residual leaves
    shard their leading world dim over the data-parallel axis (one row
    per rank, like ZeRO's sharded_state_specs); everything else
    replicates. Pass as the state's in/out specs in shard_map when the
    optimizer was built with an error-feedback compressor
    (Compression.int8). Recurses through the gradient-accumulation
    wrapper, so it works for any backward_passes_per_step."""
    from jax.sharding import PartitionSpec as P

    if isinstance(state, _AccumState):
        return _AccumState(
            inner=error_feedback_specs(state.inner, axis_name),
            acc=jax.tree_util.tree_map(lambda _: P(), state.acc),
            counter=P(),
        )
    if not isinstance(state, _EFState):
        return jax.tree_util.tree_map(lambda _: P(), state)
    axes = collectives._resolve_axis(axis_name)
    ax = axes[0] if len(axes) == 1 else tuple(axes)
    return _EFState(
        inner=jax.tree_util.tree_map(lambda _: P(), state.inner),
        residual=jax.tree_util.tree_map(lambda _: P(ax), state.residual),
    )


def DistributedOptimizer(
    optimizer,
    named_parameters=None,
    compression=None,
    backward_passes_per_step: int = 1,
    op: ReduceOp = ReduceOp.AVERAGE,
    gradient_predivide_factor: float = 1.0,
    process_set=None,
    axis_name=None,
    fusion_threshold_bytes: Optional[int] = None,
):
    """Wrap an optax optimizer so `update()` all-reduces gradients first.

    Arg-for-arg parity with torch/optimizer.py:36 (`named_parameters` is
    accepted and ignored — jaxpr names come from the pytree; torch needs it
    for hook registration). `gradient_predivide_factor` splits the average
    into pre/post scaling (optimizer.py:196-207): prescale = 1/(f·n)… here
    pre = 1/f applied before reduction, post = f/n after, matching the
    reference's numerics.

    ``compression=None`` (default) resolves the HOROVOD_COMPRESSION knob
    at construction — ``none`` reproduces the uncompressed plane bit for
    bit. An error-feedback compressor (``Compression.int8``) wraps the
    state in :class:`_EFState` carrying the per-leaf quantization
    residual; inside shard_map pass :func:`error_feedback_specs` for the
    state so each device keeps its own residual row (docs/compression.md).
    """
    del named_parameters
    import optax

    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    if fusion_threshold_bytes is not None:
        _warn_tuned_threshold_masked_once(fusion_threshold_bytes)
    if compression is None:
        compression = Compression.from_knobs()
    # error feedback exists to de-bias the quantized SUM; ops the int8
    # wire never carries (ADASUM/MIN/...) run uncompressed and must not
    # allocate residual state the reduce would never touch
    ef = bool(getattr(compression, "error_feedback", False)) and op in (
        ReduceOp.SUM, ReduceOp.AVERAGE)

    def inner_update(*args, **extra):
        """The wrapped optimizer's update, under its own scope."""
        with jax.named_scope(scopes.HVD_INNER_UPDATE):
            return optimizer.update(*args, **extra)

    def reduce_fn(grads, residual=None):
        """-> reduced, or (reduced, new_residual) when residual given."""
        g = grads
        if gradient_predivide_factor != 1.0 and op == ReduceOp.AVERAGE:
            n = collectives._group_size(process_set, axis_name)
            pre = 1.0 / gradient_predivide_factor
            post = gradient_predivide_factor / n
            with jax.named_scope(scopes.HVD_ALLREDUCE):
                g = jax.tree_util.tree_map(
                    lambda x: x * jnp.asarray(pre, jnp.result_type(x)), g
                )
            out = _reduce_grad_tree(
                g, ReduceOp.SUM, compression, process_set, axis_name,
                fusion_threshold_bytes, residual=residual,
            )
            g, new_res = out if residual is not None else (out, None)
            with jax.named_scope(scopes.HVD_ALLREDUCE):
                g = jax.tree_util.tree_map(
                    lambda x: x * jnp.asarray(post, jnp.result_type(x)), g
                )
            return (g, new_res) if residual is not None else g
        return _reduce_grad_tree(
            g, op, compression, process_set, axis_name,
            fusion_threshold_bytes, residual=residual,
        )

    def _maybe_ef_init(params, inner):
        if not ef:
            return inner
        n = collectives._group_size(process_set, axis_name)
        if n <= 1:
            return inner
        if global_state().eager_runtime is not None:
            # native eager world: the EXECUTOR holds the per-bucket
            # wire residuals (docs/compression.md) — an optimizer-state
            # copy would be n x model-size of f32 that nothing ever
            # reads. (A native-eager process that also runs SPMD steps
            # therefore gets int8 WITHOUT state error feedback on that
            # path — documented tradeoff.)
            return inner
        residual = jax.tree_util.tree_map(
            lambda p: jnp.zeros((n,) + tuple(jnp.shape(p)), jnp.float32),
            params,
        )
        return _EFState(inner=inner, residual=residual)

    def _ef_update(grads, state, params, update_inner, **extra):
        """Shared EF step: squeeze this rank's residual row, reduce with
        error feedback, restore the row. On the eager path the executors
        own the wire residual, so the state rows pass through."""
        live = collectives._bound_axes(
            collectives._resolve_axis(axis_name))
        if not live:
            reduced = reduce_fn(grads)
            updates, new_inner = update_inner(reduced, state.inner,
                                              params, **extra)
            return updates, _EFState(new_inner, state.residual)

        res_local = jax.tree_util.tree_map(_ef_row, state.residual,
                                           grads)
        reduced, new_res = reduce_fn(grads, res_local)
        updates, new_inner = update_inner(reduced, state.inner, params,
                                          **extra)
        new_res = jax.tree_util.tree_map(
            lambda r: r.astype(jnp.float32)[None], new_res)
        return updates, _EFState(new_inner, new_res)

    overlap_info = dict(
        kind="allreduce", op=op, compression=compression,
        process_set=process_set, axis_name=axis_name,
        fusion_threshold_bytes=fusion_threshold_bytes,
        gradient_predivide_factor=gradient_predivide_factor,
        backward_passes_per_step=backward_passes_per_step,
        error_feedback=ef,
    )

    if backward_passes_per_step == 1:

        def init_fn(params):
            return _maybe_ef_init(params, optimizer.init(params))

        def update_fn(grads, state, params=None, **extra):
            staged = _as_staged(grads)
            if staged is not None:
                # the backward-interleaved scheduler already reduced
                # these inside the backward (ops/overlap.py)
                return _staged_apply(staged, state, params,
                                     inner_update, **extra)
            if isinstance(state, _EFState):
                return _ef_update(grads, state, params, inner_update,
                                  **extra)
            reduced = reduce_fn(grads)
            return inner_update(reduced, state, params, **extra)

        # reduction recipe for the backward-interleaved scheduler
        # (ops/overlap.py staged_value_and_grad introspects it)
        update_fn._hvd_overlap_info = overlap_info
        return optax.GradientTransformationExtraArgs(init_fn, update_fn)

    # Local aggregation: accumulate k passes locally, reduce once
    # (torch/optimizer.py backward_passes_per_step delay counters;
    # tensorflow/gradient_aggregation.py). lax.cond keeps it jittable.
    k = backward_passes_per_step

    def init_fn(params):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        return _AccumState(
            inner=_maybe_ef_init(params, optimizer.init(params)),
            acc=zeros,
            counter=jnp.zeros((), jnp.int32),
        )

    def update_fn(grads, state, params=None, **extra):
        if _as_staged(grads) is not None:
            raise ValueError(
                "staged (overlap-scheduled) gradients cannot drive a "
                "backward_passes_per_step > 1 optimizer "
                "(docs/overlap.md)")
        acc = jax.tree_util.tree_map(lambda a, g: a + g, state.acc, grads)
        counter = state.counter + 1
        do_sync = counter >= k

        def sync_branch(operand):
            acc, inner = operand
            mean = jax.tree_util.tree_map(lambda a: a / k, acc)
            if isinstance(inner, _EFState):
                updates, new_inner = _ef_update(
                    mean, inner, params, inner_update, **extra)
                zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
                return updates, new_inner, zeros
            reduced = reduce_fn(mean)
            updates, new_inner = inner_update(
                reduced, inner, params, **extra
            )
            zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return updates, new_inner, zeros

        def hold_branch(operand):
            acc, inner = operand
            zeros_upd = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return zeros_upd, inner, acc

        updates, new_inner, new_acc = jax.lax.cond(
            do_sync, sync_branch, hold_branch, (acc, state.inner)
        )
        new_counter = jnp.where(do_sync, 0, counter)
        return updates, _AccumState(new_inner, new_acc, new_counter)

    update_fn._hvd_overlap_info = overlap_info
    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


class DistributedGradientTape:
    """JAX analog of hvd.DistributedGradientTape
    (tensorflow/__init__.py:873): wraps a value_and_grad function so the
    returned gradients are already all-reduced.

        vag = hvd.DistributedGradientTape(jax.value_and_grad(loss_fn))
        loss, grads = vag(params, batch)
    """

    def __init__(
        self,
        value_and_grad_fn: Callable,
        compression=None,
        op: ReduceOp = ReduceOp.AVERAGE,
        process_set=None,
        axis_name=None,
        fusion_threshold_bytes: Optional[int] = None,
    ):
        self._fn = value_and_grad_fn
        self._compression = compression
        self._op = op
        self._process_set = process_set
        self._axis_name = axis_name
        self._fusion = fusion_threshold_bytes

    def __call__(self, *args, **kwargs):
        out, grads = self._fn(*args, **kwargs)
        grads = _reduce_grad_tree(
            grads, self._op, self._compression, self._process_set,
            self._axis_name, self._fusion,
        )
        return out, grads


def distributed_value_and_grad(
    fun: Callable,
    argnums=0,
    has_aux: bool = False,
    op: ReduceOp = ReduceOp.AVERAGE,
    compression=None,
    process_set=None,
    axis_name=None,
    **vag_kwargs,
):
    """`jax.value_and_grad` whose gradients arrive all-reduced — the
    functional spelling of DistributedGradientTape."""
    vag = jax.value_and_grad(fun, argnums=argnums, has_aux=has_aux,
                             **vag_kwargs)

    def wrapped(*args, **kwargs):
        out, grads = vag(*args, **kwargs)
        grads = _reduce_grad_tree(
            grads, op, compression, process_set, axis_name, None
        )
        return out, grads

    return wrapped
