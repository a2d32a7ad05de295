"""Tensor fusion: pack many small tensors into few big collectives.

Reference: the fusion buffer + response fusion machinery —
/root/reference/horovod/common/fusion_buffer_manager.h:30 (persistent
128 MB buffer per device), controller.cc:830 (FuseResponses: same
dtype/device, fused size ≤ HOROVOD_FUSION_THRESHOLD), and the batched D2D
scatter/gather CUDA kernels (cuda/cuda_kernels.cu:48-260).

TPU-native shape: fusion is *compile-time packing*, not a runtime buffer.
Tensors are grouped by dtype into buckets bounded by the fusion threshold
and one XLA collective runs per bucket. What is packed depends on who
reduces the bucket. A reduce-scatter, a quantised wire or Adasum needs one
contiguous array: leaves are flattened, concatenated and sliced back out
(pack_pytree_by_plan). The packing is not free: on the chip a matrix and
a 1-D array of its elements are tiled differently, so every flatten and
every slice is a relayout copy, and GPT-2-medium's 1.4 GB of gradients
spent 21.8 ms a step in them (PERF.md section 5). The plain all-reduce
therefore takes a bucket as a GROUP of arrays (pack_groups_by_plan): a
large leaf rides it in its own shape, as the reference sends a tensor over
the threshold alone and uncopied, and only the small leaves, the ones
fusion exists for, share a packed operand. The bucket structure (a) keeps
collectives separate and ordered, (b) gives the autotuner a knob
(ops/autotune.py), exactly the role HOROVOD_FUSION_THRESHOLD plays in the
reference.
"""

from __future__ import annotations

import hashlib
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def model_fingerprint(tree) -> str:
    """Stable identity of a model's bucketable structure: sha256 over
    the pytree treedef plus every leaf's (path, shape, dtype) — exactly
    the inputs :func:`pytree_bucket_plan` derives a bucket plan from,
    so two models share a fingerprint iff they produce identical plans
    at every threshold. Value-free and process-stable: the autotuner's
    warm-start cache keys on it (ops/autotune.py, docs/autotune.md).
    Works on concrete arrays and ShapeDtypeStructs alike (serving
    replicas fingerprint restored params; trainers can fingerprint
    ``jax.eval_shape`` output before any init)."""
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    h = hashlib.sha256(str(treedef).encode())
    for path, leaf in paths_leaves:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(repr((tuple(jnp.shape(leaf)),
                       str(jnp.result_type(leaf)))).encode())
    return h.hexdigest()[:16]


def _threshold_bytes() -> int:
    from ..core.state import global_state

    st = global_state()
    if st.parameter_manager is not None:
        return st.parameter_manager.fusion_threshold_bytes()
    return st.knobs.fusion_threshold_bytes


def _active_wire():
    """The process-wide wire spec, resolved ONCE per fusion plan (a
    typo'd HOROVOD_COMPRESSION propagates loudly here rather than
    silently training uncompressed — parse_wire's contract)."""
    from ..optim.compression import resolve_wire

    return resolve_wire()


def _wire_key_for(dtype, spec) -> tuple:
    """Bucket grouping key: (logical dtype, wire dtype). The compressed
    data plane (optim/compression.py, HOROVOD_COMPRESSION) applies to
    floating payloads only, so a bucket's members always share both the
    logical dtype they are sliced back to AND the dtype they move as —
    the invariant the executors' one-cast/one-quantize-per-bucket rule
    rests on. With compression off the wire half is None and grouping
    is byte-identical to the uncompressed plane. (Today the wire half
    is derivable from the dtype — one process-wide spec — so grouping
    boundaries never move; the key keeps that invariant explicit for
    when per-bucket wire policies arrive.)"""
    dt = np.dtype(dtype)
    if spec is None or not np.issubdtype(dt, np.floating):
        return (dt, None)
    return (dt, spec.kind)


def _record_fusion(n_tensors: int, n_buckets: int, threshold: int,
                   bucket_bytes: Sequence[int] = ()) -> None:
    """Timeline instant marking a (compile-time) fusion plan — the analog
    of the reference's MEMCPY_IN/OUT_FUSION_BUFFER runtime phases, which
    here are copies inside the compiled step (`hvd_pack`, `hvd_unpack`).
    Also feeds
    the live telemetry (utils/metrics.py): plan/bucket counters + the
    fill-ratio histogram from per-bucket byte totals."""
    from ..utils import metrics
    from ..utils.timeline import active_timeline

    metrics.record_fusion_plan(n_tensors, n_buckets, threshold,
                               bucket_bytes)
    tl = active_timeline()
    if tl is not None:
        tl.instant("fusion", "FUSION_PLAN", args={
            "tensors": n_tensors, "buckets": n_buckets,
            "threshold_bytes": threshold,
        })


def fuse_apply(
    tensors: Sequence,
    fn: Callable,
    threshold_bytes: int | None = None,
) -> List:
    """Apply collective `fn` (1-D array -> 1-D array) over fused buckets.

    Tensors are bucketed greedily in submission order within each dtype
    (mirroring FuseResponses' in-order lookahead, controller.cc:830-905);
    each bucket's flat concat is passed to `fn`; outputs are unpacked to the
    original shapes and order.
    """
    if threshold_bytes is None:
        threshold_bytes = _threshold_bytes()

    arrs = [jnp.asarray(t) for t in tensors]
    wire = _active_wire()
    by_dtype: dict = {}
    for i, a in enumerate(arrs):
        by_dtype.setdefault(_wire_key_for(a.dtype, wire), []).append(i)

    out: List = [None] * len(arrs)
    for (dtype, _wire), idxs in by_dtype.items():
        itemsize = np.dtype(dtype).itemsize
        bucket: List[int] = []
        bucket_bytes = 0
        filled: List[int] = []  # per-flushed-bucket byte totals (metrics)

        def flush(bucket: List[int], nbytes: int):
            if not bucket:
                return
            filled.append(nbytes)
            flats = [arrs[i].reshape(-1) for i in bucket]
            fused = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
            red = fn(fused)
            off = 0
            for i in bucket:
                n = arrs[i].size
                out[i] = jax.lax.dynamic_slice_in_dim(red, off, n).reshape(
                    arrs[i].shape
                )
                off += n

        n_buckets = 1
        for i in idxs:
            nbytes = arrs[i].size * itemsize
            if bucket and bucket_bytes + nbytes > threshold_bytes:
                flush(bucket, bucket_bytes)
                bucket, bucket_bytes = [], 0
                n_buckets += 1
            bucket.append(i)
            bucket_bytes += nbytes
        flush(bucket, bucket_bytes)
        _record_fusion(len(idxs), n_buckets, threshold_bytes, filled)
    return out


def _backward_availability_order(paths) -> List[int]:
    """Leaf ordering that approximates when backward produces each
    gradient (earliest first):

    1. head-side leaves (no layer index in the path): final norms, cls
       heads — backward reaches them first;
    2. numbered layers, DESCENDING (layer N's backward runs before
       layer N-1's);
    3. embeddings last — their gradient closes at the very end of
       backward (the input-lookup contribution), even when a tied head
       also feeds them early.

    Ties break by reversed traversal order. A numbered name counts as a
    layer only when its alphabetic prefix occurs with >= 2 distinct
    indices across the tree (block_0..block_23) — Flax auto-names like
    a single Dense_0 head carry an index without being part of a stack,
    and sending that large earliest-ready gradient to the tail bucket
    would invert rule 1. The reference gets this ordering for free: its
    grad hooks fire in backward execution order (torch/optimizer.py:176)
    and the controller negotiates in arrival order. Misplacing a small
    leaf (e.g. a CNN stem conv) only nudges a bucket boundary; the rule
    exists to keep LARGE late-ready leaves (embeddings) out of the
    chain's head bucket."""
    import re as _re

    pat = _re.compile(r"([a-z_]+?)_?(\d+)")
    infos = []
    stacks: dict = {}  # alphabetic prefix -> set of indices seen
    for p in paths:
        s = jax.tree_util.keystr(p).lower()
        m = pat.search(s)
        infos.append((s, m))
        if m:
            stacks.setdefault(m.group(1), set()).add(int(m.group(2)))
    keys = []
    for i, (s, m) in enumerate(infos):
        if "emb" in s:
            keys.append((2, 0, -i))
        elif m and len(stacks[m.group(1)]) >= 2:
            keys.append((1, -int(m.group(2)), -i))
        else:
            keys.append((0, 0, -i))
    return sorted(range(len(paths)), key=lambda i: keys[i])


def pytree_bucket_plan(tree, threshold_bytes: int | None = None,
                       backward_order: bool | None = None):
    """Data-free bucketization: the same grouping flatten_pytree_buckets
    applies, computed from leaf shapes/dtypes only (no concatenation,
    no device work — reshard paths need just the bucket lengths).
    Returns (treedef, plans) where `plans` is one list per bucket of
    (leaf_idx, offset, size, shape) tuples. Deterministic in (pytree
    structure, leaf shapes/dtypes, threshold, ordering) — the property
    that lets init/update/reshard agree on a layout."""
    if threshold_bytes is None:
        threshold_bytes = _threshold_bytes()
    if backward_order is None:
        from ..core.state import global_state

        backward_order = global_state().knobs.bucket_backward_order

    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [l for _, l in paths_leaves]
    if backward_order:
        order = _backward_availability_order(
            [p for p, _ in paths_leaves])
    else:
        order = range(len(leaves))

    def _dtype(leaf):
        # jnp.result_type, not np.asarray: a python float is float64 to
        # numpy but packs as float32 under default JAX config
        # (pack_pytree_by_plan goes through jnp.asarray) — grouping by
        # the numpy dtype would split such a leaf into a spurious
        # mis-sized bucket of its own
        return np.dtype(jnp.result_type(leaf))

    wire = _active_wire()
    by_dtype: dict = {}
    for i in order:
        by_dtype.setdefault(
            _wire_key_for(_dtype(leaves[i]), wire), []).append(i)

    plans = []
    plan_bytes: List[int] = []  # parallel to `plans` (metrics fill ratio)
    for (dtype, _wire), idxs in by_dtype.items():
        itemsize = dtype.itemsize
        cur_plan, cur_bytes, off = [], 0, 0

        def flush():
            nonlocal cur_plan, cur_bytes, off
            if cur_plan:
                plans.append(cur_plan)
                plan_bytes.append(cur_bytes)
            cur_plan, cur_bytes, off = [], 0, 0

        for i in idxs:
            shape = jnp.shape(leaves[i])
            size = int(np.prod(shape)) if shape else 1
            nbytes = size * itemsize
            if cur_plan and cur_bytes + nbytes > threshold_bytes:
                flush()
            cur_plan.append((i, off, size, shape))
            off += size
            cur_bytes += nbytes
        flush()
    _record_fusion(len(leaves), len(plans), threshold_bytes, plan_bytes)
    return treedef, plans


def plan_bucket_lengths(plans) -> List[int]:
    """Element count per bucket of a pytree_bucket_plan — the layout
    widths ZeRO shard math and the staged scheduler both derive from."""
    return [sum(n for (_, _, n, _) in bp) for bp in plans]


def bucket_issue_schedule(plans, leaf_stages, backward_stage_order):
    """When does each fusion bucket become issuable during a segmented
    backward pass?

    ``leaf_stages[i]`` lists the stage ids contributing gradient to
    leaf ``i`` (tied embeddings list two: the head's early contribution
    and the input lookup's final one). ``backward_stage_order`` is the
    order the segments' backward runs (reverse of forward). Returns one
    list per backward step: the bucket indices whose every leaf has
    received ALL its contributions by the end of that step — the
    compile-time mirror of the reference controller marking a fused
    response ready once all its tensors arrived (controller.cc:830).
    Pure bookkeeping (no device work); raises if any bucket never
    completes, which means the stage decomposition does not cover its
    leaves."""
    remaining = [len(s) for s in leaf_stages]
    stage_to_leaves: dict = {}
    for i, sids in enumerate(leaf_stages):
        for si in sids:
            stage_to_leaves.setdefault(si, []).append(i)
    pending = list(range(len(plans)))
    schedule = []
    for si in backward_stage_order:
        for i in stage_to_leaves.get(si, ()):
            remaining[i] -= 1
        now = [bi for bi in pending
               if all(remaining[i] == 0 for (i, _, _, _) in plans[bi])]
        for bi in now:
            pending.remove(bi)
        schedule.append(now)
    if pending:
        raise ValueError(
            f"buckets {pending} never complete under this stage "
            "decomposition — some of their leaves receive no gradient "
            "contribution from any stage")
    return schedule


def bucket_prefetch_schedule(plans, leaf_first_stage, n_stages: int):
    """When must each fusion bucket's parameter all-gather COMPLETE
    during a segmented forward pass? The mirror of
    :func:`bucket_issue_schedule` for the FSDP prefetch direction
    (ops/overlap.py, docs/fsdp.md): a bucket is *needed* at the first
    forward stage that touches ANY of its leaves — where the backward
    direction waits for the LAST contribution, the forward direction
    must be ready for the FIRST use. The tied-embedding bucket is the
    canonical asymmetry: it completes last on backward (the input
    lookup's gradient closes at the final segment) but is needed first
    on forward (the embedding stage reads it at step 0).

    ``leaf_first_stage[i]`` is the first forward stage using leaf ``i``
    (``min`` of its contributing stages). Returns one list per forward
    stage: the bucket indices first needed at that stage — gather them
    no later than that stage's boundary; gather them one stage earlier
    to prefetch.

    Implemented by driving :func:`bucket_issue_schedule` itself in the
    forward (prefetch) direction: traversing the stages in REVERSE
    forward order, a bucket "completes" exactly when its smallest
    first-use stage is reached, so the issue schedule read backwards is
    the need schedule."""
    rev = bucket_issue_schedule(
        plans, [[s] for s in leaf_first_stage],
        list(reversed(range(n_stages))))
    return list(reversed(rev))


def bucket_regather_schedule(plans, leaf_last_stage, n_stages: int):
    """When must each fusion bucket's parameter all-gather be RE-ISSUED
    during a segmented backward pass under the regather policy
    (HOROVOD_FSDP_REGATHER, ops/overlap.py, docs/fsdp.md)? The third
    direction of :func:`bucket_issue_schedule`: the backward walks the
    stages in reverse, and a bucket's weights are first needed at the
    LAST forward stage touching any of its leaves — the earliest point
    the reversed traversal reaches it. The tied-embedding bucket is
    again the canonical asymmetry: it is needed FIRST on backward (the
    head's matmul transpose reads it in the first backward segment)
    even though its gradient completes LAST.

    ``leaf_last_stage[i]`` is the last forward stage using leaf ``i``
    (``max`` of its contributing stages). Returns one list per BACKWARD
    step (index 0 = the last forward stage's backward): the bucket
    indices whose re-gather must have completed by that step. Each
    bucket appears exactly once — the exactly-once re-gather per
    backward the bitwise contract rides on. Implemented by driving
    :func:`bucket_issue_schedule` in the backward direction after
    lifting every leaf to its BUCKET's largest last-use stage — the
    issue scheduler waits for ALL leaves, which in the reversed
    traversal is the smallest stage, so without the lift a bucket
    whose leaves end in different stages would be scheduled at its
    LATEST-reached leaf instead of its first backward use. The result
    is already in backward-step order."""
    lifted = list(leaf_last_stage)
    for bp in plans:
        m = max(leaf_last_stage[i] for (i, _, _, _) in bp)
        for (i, _, _, _) in bp:
            lifted[i] = m
    return bucket_issue_schedule(
        plans, [[s] for s in lifted],
        list(reversed(range(n_stages))))


def pack_buckets_by_plan(tree, plans):
    """Bucket payloads of `tree`'s leaves under a pytree_bucket_plan's
    per-bucket leaf layout (the pack half of pack_pytree_by_plan)."""
    leaves = jax.tree_util.tree_leaves(tree)
    buckets = []
    for bplan in plans:
        flats = [jnp.asarray(leaves[i]).reshape(-1)
                 for (i, _, _, _) in bplan]
        buckets.append(
            jnp.concatenate(flats) if len(flats) > 1 else flats[0])
    return buckets


def unflatten_buckets_by_plan(buckets, treedef, plans, nleaves):
    """Restore a pytree from per-bucket payloads laid out by a
    pytree_bucket_plan (the unflatten half of pack_pytree_by_plan)."""
    new_leaves = [None] * nleaves
    for bucket, bplan in zip(buckets, plans):
        for (i, off, n, shape) in bplan:
            new_leaves[i] = jax.lax.dynamic_slice_in_dim(
                bucket, off, n
            ).reshape(shape)
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


# A leaf of rank >= 2 and at least this many bytes rides its bucket's
# all-reduce as an operand of its own (pack_groups_by_plan). Fusion is
# for tensors that are small against a collective's latency; a matrix
# past that regime gains nothing from sharing a buffer and, on the
# chip, pays a relayout each way: a [4096, 1024] array and a 1-D array
# of its elements are tiled differently, so `reshape(-1)` is a copy.
# Set from a sweep on four chips (PERF.md section 6, PR 30): every
# value from 16 KiB to 4 MiB splits GPT-2-medium's and BERT-Large's
# trees alike (matrices direct, vectors packed), and that split beats
# both all packed and every leaf direct. On narrower trees the more
# matrices ride direct the faster, down to 256 KiB ones, so the sweep
# alone would put this lower; it stays above 1 MiB because
# tests/test_overlap_schedule.py counts one all_reduce a bucket in the
# lowered step of a 512-wide model (PERF.md section 7).
DIRECT_MIN_BYTES = 2 << 20


def rides_direct(leaf) -> bool:
    """Does this leaf ride its bucket's all-reduce in its own shape?
    Decided by what the leaf shows: its rank and its bytes."""
    return (jnp.ndim(leaf) >= 2 and
            leaf.size * leaf.dtype.itemsize >= DIRECT_MIN_BYTES)


def pack_groups_by_plan(tree, plan):
    """`tree`'s leaves as one GROUP of arrays per bucket of a
    pytree_bucket_plan: the bucket's direct leaves (rides_direct) in
    plan order, each as it is, then one packed 1-D operand holding the
    bucket's other leaves, flattened and concatenated; a bucket with no
    small leaf has no packed operand. Same buckets, same order and same
    bytes as pack_pytree_by_plan, with nothing copied for the leaves
    that never needed fusing. Returns (groups, unflatten);
    `unflatten(reduced_groups)` restores the tree."""
    from ..utils import metrics

    treedef, plans = plan
    leaves = [jnp.asarray(l) for l in jax.tree_util.tree_leaves(tree)]
    groups, layouts = [], []
    for bplan in plans:
        direct, packed = [], []
        for (i, _, _, _) in bplan:
            (direct if rides_direct(leaves[i]) else packed).append(i)
        group = [leaves[i] for i in direct]
        if packed:
            flats = [leaves[i].reshape(-1) for i in packed]
            group.append(
                jnp.concatenate(flats) if len(flats) > 1 else flats[0])
        groups.append(tuple(group))
        layouts.append((direct, packed))

    def nbytes(which):
        return sum(leaves[i].size * leaves[i].dtype.itemsize
                   for layout in layouts for i in layout[which])

    metrics.record_fusion_groups(
        nbytes(0), nbytes(1), sum(len(direct) for direct, _ in layouts))
    shapes = [leaf.shape for leaf in leaves]  # all unflatten keeps of them

    def unflatten(reduced_groups):
        new_leaves = [None] * len(shapes)
        for group, (direct, packed) in zip(reduced_groups, layouts):
            for i, red in zip(direct, group):
                new_leaves[i] = red
            off = 0
            for i in packed:
                n = int(np.prod(shapes[i]))
                new_leaves[i] = jax.lax.dynamic_slice_in_dim(
                    group[-1], off, n).reshape(shapes[i])
                off += n
        return jax.tree_util.tree_unflatten(treedef, new_leaves)

    return groups, unflatten


def pack_pytree_by_plan(tree, plan):
    """Pack `tree`'s leaves into buckets following a pytree_bucket_plan
    (possibly computed from a DIFFERENT tree of the same structure —
    e.g. grads packed by the params' plan, so a grad-dtype cast can
    never shift the bucket boundaries the optimizer state was laid out
    with). Returns (buckets, unflatten)."""
    treedef, plans = plan
    nleaves = len(jax.tree_util.tree_leaves(tree))
    buckets = pack_buckets_by_plan(tree, plans)

    def unflatten(reduced_buckets):
        return unflatten_buckets_by_plan(
            reduced_buckets, treedef, plans, nleaves)

    return buckets, unflatten


def flatten_pytree_buckets(tree, threshold_bytes: int | None = None,
                           backward_order: bool | None = None):
    """Bucket an arbitrary pytree (e.g. a grad pytree) for fused reduction.

    Returns (buckets, unflatten) where `buckets` is a list of 1-D arrays
    (per-dtype, threshold-bounded) and `unflatten(reduced_buckets)` restores
    the original pytree. Used by the DistributedOptimizer gradient
    transformation (optim/distributed.py), the analog of the reference's
    grad-hook + fusion-buffer path (torch/optimizer.py:176).

    With ``backward_order`` (default: knobs.bucket_backward_order) leaves
    are bucketed in estimated backward-availability order (last layer
    first, embeddings last — `_backward_availability_order`), the order
    the reference gets for free from its grad hooks firing during
    backward. It decides which bucket the ordered-bucket chain releases
    first, and so how much of the backward pass a scheduler COULD run
    beside the collectives (the compiled step on the chip runs none:
    optim/distributed.py's comment on the chain)."""
    return pack_pytree_by_plan(
        tree, pytree_bucket_plan(tree, threshold_bytes, backward_order))
