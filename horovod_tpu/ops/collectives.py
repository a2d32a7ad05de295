"""Collective operations: the TPU data plane.

Reference surface: /root/reference/horovod/torch/mpi_ops.py (allreduce /
allgather / broadcast / alltoall / reducescatter, grouped + async variants,
prescale/postscale factors, process sets) executed through the C++ op layer
(/root/reference/horovod/common/ops/collective_operations.h:38-351,
nccl_operations.cc:175-246).

TPU-native architecture
-----------------------
There is no background proxy thread and no NCCL stream machinery here. A
collective has two execution forms:

* **SPMD form** (primary, the performance path): called inside
  ``shard_map``/``pjit`` with the data-parallel mesh axis bound, each op is
  a single XLA collective HLO (`lax.psum`, `lax.all_gather`,
  `lax.psum_scatter`, `lax.all_to_all`, `lax.ppermute`) that XLA schedules
  directly onto ICI — the role NCCL plays in the reference, minus the
  callback detour the reference needs for its XLA path
  (xla_mpi_ops.cc:195-603; SURVEY.md §3.5 notes the TPU build should lower
  natively — this is that lowering).

* **Eager form**: called on concrete ``jax.Array``s at top level. The op
  jit-compiles a tiny shard_map program over the (sub-)mesh and runs it
  immediately. Compilations are cached by (op, shape, dtype, set), playing
  the role of the reference's ResponseCache steady-state fast path
  (response_cache.h:45): the first call of a signature pays negotiation
  (here: compilation), subsequent calls are cheap dispatches.

Process sets map to ``axis_index_groups`` (SPMD form) or sub-meshes (eager
form) — see core/process_sets.py. Ops whose XLA form requires equal-size
replica groups (allgather/alltoall/reducescatter) use a scatter+psum
formulation for proper-subset process sets.
"""

from __future__ import annotations

import enum
import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import basics
from ..core.exceptions import HorovodInternalError
from ..core.process_sets import ProcessSet, global_process_set
from ..core.state import global_state


class ReduceOp(enum.IntEnum):
    """Reduction op ids, value-compatible with the reference
    (horovod/torch/mpi_ops.py:60-66: Average=0, Sum=1, Adasum=2, Min=3,
    Max=4, Product=5)."""

    AVERAGE = 0
    SUM = 1
    ADASUM = 2
    MIN = 3
    MAX = 4
    PRODUCT = 5


Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM
Min = ReduceOp.MIN
Max = ReduceOp.MAX
Product = ReduceOp.PRODUCT


# ---------------------------------------------------------------------------
# axis / process-set plumbing
# ---------------------------------------------------------------------------

def _default_axis() -> Tuple[str, ...]:
    st = global_state()
    if st.initialized:
        return st.dp_axis
    return ("hvd",)


def _resolve_axis(axis_name) -> Tuple[str, ...]:
    if axis_name is None:
        axes = _default_axis()
    elif isinstance(axis_name, str):
        axes = (axis_name,)
    else:
        axes = tuple(axis_name)
    return axes


def _bound_axes(axes: Tuple[str, ...]) -> Tuple[str, ...]:
    sizes = basics.bound_axis_sizes()
    return tuple(ax for ax in axes if ax in sizes)


def _axis_size(axes: Tuple[str, ...]) -> int:
    sizes = basics.bound_axis_sizes()
    n = 1
    for ax in axes:
        n *= sizes[ax]
    return n


def _set_groups(ps: Optional[ProcessSet], world: int):
    if ps is None:
        return None, world
    groups = ps.axis_index_groups(world)
    return groups, ps.size()


def _set_local_index(ps: ProcessSet, axis: str):
    """Traced set-local rank for the current device; 0 for non-members."""
    world = _axis_size((axis,))
    table = np.zeros((world,), dtype=np.int32)
    for i, r in enumerate(ps.ranks):
        table[r] = i
    return jnp.asarray(table)[lax.axis_index(axis)]


def _member_mask(ps: ProcessSet, axis: str):
    """Traced bool: is the current device a member of the set?"""
    world = _axis_size((axis,))
    table = np.zeros((world,), dtype=bool)
    for r in ps.ranks:
        table[r] = True
    return jnp.asarray(table)[lax.axis_index(axis)]


def _check_subset_axes(groups, axes):
    if groups is not None and len(axes) > 1:
        raise HorovodInternalError(
            "process sets require a single data-parallel axis"
        )


# ---------------------------------------------------------------------------
# SPMD-form primitives (inside shard_map)
# ---------------------------------------------------------------------------

def _spmd_allreduce_leaf(x, op, axes, ps, prescale, postscale):
    world = _axis_size(axes)
    groups, nset = _set_groups(ps, world)
    _check_subset_axes(groups, axes)
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)
    if prescale != 1.0:
        x = x * jnp.asarray(prescale, dtype=x.dtype)
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.ADASUM):
        # ADASUM at the lax level degenerates to a sum here; the adaptive
        # combining lives in ops/adasum.py and is dispatched by allreduce()
        # before reaching this leaf.
        from . import hierarchical

        if hierarchical.hierarchy_enabled_for("allreduce", ps):
            y = hierarchical.hierarchical_psum(
                x, axes, basics.bound_axis_sizes(),
                global_state().knobs.hierarchical_local_size,
            )
        else:
            y = lax.psum(x, axis_arg, axis_index_groups=groups)
        if op == ReduceOp.AVERAGE:
            if groups is None:
                y = (y / nset).astype(x.dtype)
            else:
                # non-members (singleton groups) keep their input unchanged
                # rather than dividing their own value by the set size
                div = jnp.where(_member_mask(ps, axes[0]), nset, 1)
                y = (y / div).astype(x.dtype)
    elif op == ReduceOp.MIN:
        y = lax.pmin(x, axis_arg, axis_index_groups=groups)
    elif op == ReduceOp.MAX:
        y = lax.pmax(x, axis_arg, axis_index_groups=groups)
    elif op == ReduceOp.PRODUCT:
        # No pprod HLO; gather then reduce locally, then a masked psum from
        # each group's root re-establishes replication (jax's VMA checker
        # tracks all_gather outputs as device-varying). PRODUCT is a rare
        # op (parity item from torch/mpi_ops.py:60, not a hot path).
        g = lax.all_gather(x, axis_arg, axis_index_groups=groups)
        y = jnp.prod(g, axis=0).astype(x.dtype)
        if len(axes) == 1:
            idx = lax.axis_index(axes[0])
        else:
            sizes = basics.bound_axis_sizes()
            idx = lax.axis_index(axes[0])
            for ax in axes[1:]:
                idx = idx * sizes[ax] + lax.axis_index(ax)
        if groups is None:
            root_of = jnp.zeros((world,), dtype=jnp.int32)
        else:
            table = np.zeros((world,), dtype=np.int32)
            for grp in groups:
                for r in grp:
                    table[r] = grp[0]
            root_of = jnp.asarray(table)
        mask = (idx == root_of[idx]).astype(y.dtype)
        y = lax.psum(y * mask, axis_arg, axis_index_groups=groups)
    else:
        raise ValueError(f"unknown reduce op {op}")
    if postscale != 1.0:
        y = y * jnp.asarray(postscale, dtype=y.dtype)
    return y


def _spmd_allgather_leaf(x, axes, ps):
    world = _axis_size(axes)
    groups, nset = _set_groups(ps, world)
    _check_subset_axes(groups, axes)
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)
    if groups is None:
        # NOTE: the result is replicated in value but jax's VMA checker
        # types all_gather output as device-varying; callers returning it
        # through shard_map out_specs=P() should pass check_vma=False or
        # psum-mask it (see the PRODUCT branch of _spmd_allreduce_leaf).
        from . import hierarchical

        if hierarchical.hierarchy_enabled_for("allgather", ps):
            return hierarchical.hierarchical_allgather(
                x, axes, basics.bound_axis_sizes(),
                global_state().knobs.hierarchical_local_size,
            )
        return lax.all_gather(x, axis_arg, tiled=True)
    # Proper subset: XLA all-gather wants equal-size groups; emulate with
    # scatter-into-zeros + group psum (constant extra FLOPs, one collective).
    d0 = x.shape[0]
    out = jnp.zeros((nset * d0,) + x.shape[1:], dtype=x.dtype)
    idx = _set_local_index(ps, axes[0])
    out = lax.dynamic_update_slice_in_dim(out, x, idx * d0, axis=0)
    return lax.psum(out, axes[0], axis_index_groups=groups)


def _spmd_broadcast_leaf(x, root_rank, axes, ps):
    world = _axis_size(axes)
    groups, _ = _set_groups(ps, world)
    _check_subset_axes(groups, axes)
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)
    if len(axes) == 1:
        idx = lax.axis_index(axes[0])
    else:
        sizes = basics.bound_axis_sizes()
        idx = lax.axis_index(axes[0])
        for ax in axes[1:]:
            idx = idx * sizes[ax] + lax.axis_index(ax)
    mask = (idx == root_rank).astype(x.dtype)
    y = lax.psum(x * mask, axis_arg, axis_index_groups=groups)
    if groups is not None:
        # non-members' singleton-group psum is zero; keep their input
        y = jnp.where(_member_mask(ps, axes[0]), y, x)
    return y


def _spmd_reducescatter_leaf(x, op, axes, ps, prescale, postscale):
    world = _axis_size(axes)
    groups, nset = _set_groups(ps, world)
    _check_subset_axes(groups, axes)
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)
    if x.shape[0] % nset:
        raise HorovodInternalError(
            f"reducescatter dim0 {x.shape[0]} not divisible by set size {nset}"
        )
    if prescale != 1.0:
        x = x * jnp.asarray(prescale, dtype=x.dtype)
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("reducescatter supports Sum and Average (as the reference: collective_operations.h:342)")
    if groups is None:
        y = lax.psum_scatter(x, axis_arg, scatter_dimension=0, tiled=True)
    else:
        # subset form: group psum, then slice own chunk
        full = lax.psum(x, axes[0], axis_index_groups=groups)
        chunk = x.shape[0] // nset
        idx = _set_local_index(ps, axes[0])
        y = lax.dynamic_slice_in_dim(full, idx * chunk, chunk, axis=0)
    if op == ReduceOp.AVERAGE:
        y = (y / nset).astype(x.dtype)
    if postscale != 1.0:
        y = y * jnp.asarray(postscale, dtype=y.dtype)
    return y


def _spmd_alltoall_leaf(x, axes, ps):
    world = _axis_size(axes)
    groups, nset = _set_groups(ps, world)
    _check_subset_axes(groups, axes)
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)
    if x.shape[0] % nset:
        raise HorovodInternalError(
            f"alltoall dim0 {x.shape[0]} not divisible by set size {nset}"
        )
    if groups is None:
        return lax.all_to_all(
            x, axis_arg, split_axis=0, concat_axis=0, tiled=True
        )
    # Subset alltoall via one-hot matrix exchange: build [nset, chunk, ...]
    # where slot j holds the chunk destined to set-member j, rotate via
    # psum of masked scatter. One collective; complement ranks unaffected.
    chunk = x.shape[0] // nset
    parts = x.reshape((nset, chunk) + x.shape[1:])
    idx = _set_local_index(ps, axes[0])  # my set-local rank
    # out[j] should receive parts[j] from member j's buffer at slot my idx.
    # Scatter parts[j] -> buffer[j, my_idx] then psum over the set.
    buf = jnp.zeros((nset, nset, chunk) + x.shape[1:], dtype=x.dtype)
    buf = lax.dynamic_update_slice(
        buf,
        parts[:, None],
        (0, idx) + (0,) * (parts.ndim - 1),
    )
    buf = lax.psum(buf, axes[0], axis_index_groups=groups)
    out = buf[idx]  # [nset, chunk, ...] — chunk j from member j
    return out.reshape((nset * chunk,) + x.shape[1:])


# ---------------------------------------------------------------------------
# eager-form execution (top level, concrete arrays)
# ---------------------------------------------------------------------------
#
# Single-controller semantics: the controller's value stands for every
# rank's value (all ranks submit identical tensors), so eager SUM == x*n,
# AVERAGE == x, allgather == n-fold tile. In multi-controller mode
# (jax.process_count() > 1) each controller contributes its process-local
# value and the op is a real cross-process collective compiled over the
# global mesh. The jit cache is keyed by shape/dtype/op — the steady-state
# fast path analog of the reference's ResponseCache (response_cache.h:45).

def _build_perrank_program(op_kind: str, mesh, axes, op: int,
                           prescale: float, postscale: float, root: int):
    """jit(shard_map) program treating a [world, ...] stack as 'rank i's
    tensor on device i'. `root` is an index along `axes`. Shared by the
    global eager path and the process-set sub-mesh path."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    # The per-rank stack is laid out [world, ...] and sharded on dim 0, so
    # each device's shard_map block is [1, ...]: squeeze it so the leaf
    # sees exactly "this rank's tensor", like a Horovod process would.
    if op_kind == "allreduce":
        def fn(x):
            return _spmd_allreduce_leaf(
                x[0], ReduceOp(op), axes, None, prescale, postscale
            )
        in_spec, out_spec = P(axes), P()
    elif op_kind == "allgather":
        def fn(x):
            return _spmd_allgather_leaf(x[0], axes, None)
        in_spec, out_spec = P(axes), P()
    elif op_kind == "broadcast":
        def fn(x):
            return _spmd_broadcast_leaf(x[0], root, axes, None)
        in_spec, out_spec = P(axes), P()
    elif op_kind == "reducescatter":
        def fn(x):
            return _spmd_reducescatter_leaf(
                x[0], ReduceOp(op), axes, None, prescale, postscale
            )
        in_spec, out_spec = P(axes), P(axes)
    elif op_kind == "alltoall":
        def fn(x):
            return _spmd_alltoall_leaf(x[0], axes, None)
        in_spec, out_spec = P(axes), P(axes)
    else:
        raise ValueError(op_kind)

    return jax.jit(
        shard_map(
            fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
            # allgather/broadcast outputs are value-replicated but typed
            # device-varying by the VMA checker; these programs are
            # framework-internal, so skip the static check.
            check_vma=False,
        )
    )


@functools.lru_cache(maxsize=1024)
def _eager_subset_program(op_kind: str, ranks: tuple, op: int,
                          prescale: float, postscale: float,
                          root_local: int, epoch: int):
    """Eager collective over a process set's sub-mesh: the set's devices
    ARE the communicator (core/process_sets.py eager form), so the leaf
    runs group-free over a dedicated "hvd" axis of exactly |set| devices.
    """
    del epoch
    from jax.sharding import Mesh

    st = global_state()
    flat = np.asarray(st.mesh.devices).reshape(-1)
    sub = Mesh(flat[np.asarray(ranks, dtype=np.int64)], ("hvd",))
    return _build_perrank_program(
        op_kind, sub, ("hvd",), op, prescale, postscale, root_local
    )


@functools.lru_cache(maxsize=4096)
def _eager_program(op_kind: str, ndev: int, op: int, prescale: float,
                   postscale: float, root_rank: int, epoch: int,
                   hier_key=()):
    # epoch: cache-buster across elastic re-init. hier_key: the hierarchical
    # knob values baked into the traced program — toggling the knobs at
    # runtime must not silently keep the old flat/hierarchical routing.
    del epoch, hier_key
    st = global_state()
    mesh = st.mesh
    axes = ("hvd",) if mesh is None else tuple(mesh.axis_names)
    return _build_perrank_program(
        op_kind, mesh, axes, op, prescale, postscale, root_rank
    )


def _hier_knob_key():
    """The knob values that alter traced collective routing
    (ops/hierarchical.py gates) — part of every eager program cache key."""
    k = global_state().knobs
    return (bool(k.hierarchical_allreduce), bool(k.hierarchical_allgather),
            int(k.hierarchical_local_size))


def _eager_perrank(op_kind: str, stacked, op=ReduceOp.SUM, prescale=1.0,
                   postscale=1.0, root_rank=0):
    """Run a collective treating ``stacked[i]`` as rank i's tensor.

    The tensor is laid out [world, ...] and sharded one-slice-per-device
    along the mesh; the shard_map body then sees exactly rank i's tensor on
    device i — the precise analog of N processes each submitting a tensor.
    Used by eager ops, tests and broadcast_parameters.
    """
    st = global_state()
    mesh = st.mesh
    ndev = int(np.prod(mesh.devices.shape))
    prog = _eager_program(
        op_kind, ndev, int(op), float(prescale), float(postscale),
        int(root_rank), st.epoch, _hier_knob_key(),
    )
    from contextlib import nullcontext

    from ..utils.timeline import active_timeline

    tl = active_timeline()
    # host-side span around the XLA dispatch (reference analog: the
    # NCCL_* op activity, timeline.cc; device time is in xplane)
    with tl.activity(op_kind, "XLA_COLLECTIVE") if tl else nullcontext():
        out = prog(stacked)
    if jax.default_backend() == "cpu":
        # On the virtual CPU mesh two concurrently-executing multi-partition
        # programs can starve each other's collective rendezvous when the
        # host has fewer cores than devices (XLA InProcessCommunicator needs
        # all partitions running at once). Blocking eager results before
        # returning serializes eager collectives against subsequent jit
        # dispatches. TPU streams don't have this hazard; no cost there.
        jax.block_until_ready(out)
    return out


def _is_perrank(x, nset: int) -> bool:
    return hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == nset


# auto-name fallback per op kind: call order must agree across ranks for
# unnamed tensors (the reference's same caveat — torch/mpi_ops.py derives
# a per-handle name when none is given)
_AUTO_NAME_COUNTERS: dict = {}


def _auto_name(op_kind: str) -> str:
    import itertools

    c = _AUTO_NAME_COUNTERS.setdefault(op_kind, itertools.count())
    return f"{op_kind}.noname.{next(c)}"


_NATIVE_OPS = {
    "allreduce": 0,      # OP_ALLREDUCE
    "allgather": 1,      # OP_ALLGATHER
    "broadcast": 2,      # OP_BROADCAST
    "alltoall": 3,       # OP_ALLTOALL
    "reducescatter": 4,  # OP_REDUCESCATTER
}


def _record_collective_leaf(op_kind: str, tensor) -> None:
    """Telemetry for one issued eager collective (utils/metrics.py).
    Counted at the dispatch site so the /metrics counters equal exactly
    the collectives this process issued; the traced SPMD path is
    accounted per executed step instead (optim/distributed.py)."""
    from ..utils import metrics

    if not metrics.enabled():
        return
    if hasattr(tensor, "dtype") and hasattr(tensor, "nbytes"):
        dtype, nbytes = str(tensor.dtype), int(tensor.nbytes)
    else:
        # jnp.result_type, not the numpy dtype: the collective packs via
        # jnp.asarray, so a python float moves as float32 under default
        # JAX config while numpy would call (and size) it float64
        dt = np.dtype(jnp.result_type(tensor))
        dtype = str(dt)
        nbytes = int(np.asarray(tensor).size) * dt.itemsize
    metrics.record_collective(op_kind, dtype, nbytes)


def _contains_indexed_slices(tensor) -> bool:
    from .sparse import IndexedSlices

    leaves = jax.tree_util.tree_leaves(
        tensor, is_leaf=lambda x: isinstance(x, IndexedSlices)
    )
    return any(isinstance(l, IndexedSlices) for l in leaves)


def _reject_indexed_slices(tensor, op_name: str) -> None:
    """Ops without sparse semantics must fail loudly at the call site —
    tree-flattening an IndexedSlices would run collectives over its
    int indices and static dense_shape and return corrupt slices."""
    if _contains_indexed_slices(tensor):
        raise TypeError(
            f"{op_name} does not accept IndexedSlices; sparse tensors "
            "reduce via allreduce/sparse_allreduce "
            "(reference tensorflow/__init__.py:56)"
        )


def _leaf_namer(name):
    """Per-leaf names for pytree ops: the first leaf keeps the user name,
    later leaves get `.k` suffixes (deterministic pytree order keeps the
    suffixes rank-consistent)."""
    import itertools

    c = itertools.count()

    def next_name():
        i = next(c)
        if name is None:
            return None
        return name if i == 0 else f"{name}.{i}"

    return next_name


def _native_eager(rt, op_kind, tensor, op=ReduceOp.SUM, prescale=1.0,
                  postscale=1.0, root_rank=0, name=None, splits=None,
                  process_set_id=0):
    """Route one top-level collective through the background negotiation
    runtime: enqueue → controller negotiation → fused XLA execution →
    synchronize (reference operations.cc:1400 EnqueueTensorAllreduces →
    :273 PerformOperation; SURVEY.md §3.2)."""
    x = np.asarray(tensor)
    handle = rt.enqueue(
        name or _auto_name(op_kind), x, _NATIVE_OPS[op_kind],
        reduce_op=int(op), root_rank=int(root_rank),
        prescale=float(prescale), postscale=float(postscale),
        splits=splits, process_set_id=process_set_id,
    )
    out = rt.synchronize(handle)
    if op_kind == "alltoall":
        recv = None
        if isinstance(out, tuple):
            out, recv = out
        return jnp.asarray(out), (
            jnp.asarray(recv) if recv is not None else None
        )
    return jnp.asarray(out)


def _eager_collective(op_kind, tensor, op=ReduceOp.SUM, prescale=1.0,
                      postscale=1.0, root_rank=0, process_set=None,
                      name=None):
    _record_collective_leaf(op_kind, tensor)
    st = global_state()
    ps = process_set
    if ps is not None and ps.process_set_id == 0:
        ps = None

    rt = st.eager_runtime
    if rt is not None:
        sid = 0
        if ps is not None:
            # per-set negotiation in the native runtime (reference
            # process_set.h:89): the set must have been registered on
            # every rank (add_process_set does this when the runtime is
            # live); member ranks negotiate among themselves and execute
            # over the set's sub-mesh
            sid = ps.process_set_id
            if rt.process_set_members(sid) is None:
                raise HorovodInternalError(
                    f"process set {sid} is not registered with the "
                    "native runtime; call hvd.add_process_set on every "
                    "rank first (reference process_sets.py:123)"
                )
        out = _native_eager(
            rt, op_kind, tensor, op, prescale, postscale, root_rank, name,
            process_set_id=sid,
        )
        return out[0] if op_kind == "alltoall" else out

    n = st.world_size() if ps is None else ps.size()

    if ps is not None:
        # Eager subset ops run over the set's sub-mesh — a real
        # communicator of exactly the member devices (the reference needs
        # a whole per-set controller for this, process_set.h:26).
        x = jnp.asarray(tensor)
        root_local = ps.rank(root_rank) if op_kind == "broadcast" else 0
        prog = _eager_subset_program(
            op_kind, tuple(ps.ranks), int(op), float(prescale),
            float(postscale), int(root_local), st.epoch,
        )
        stacked = jnp.broadcast_to(x[None], (n,) + x.shape)
        out = prog(stacked)
        if jax.default_backend() == "cpu":
            jax.block_until_ready(out)  # see _eager_perrank note
        if op_kind == "reducescatter":
            return out[: x.shape[0] // n]
        if op_kind == "alltoall":
            return out[: x.shape[0]]
        return out

    x = jnp.asarray(tensor)
    # Replicated single-controller semantics: synthesize the per-rank stack.
    if op_kind in ("allreduce", "allgather", "broadcast"):
        stacked = jnp.broadcast_to(x[None], (n,) + x.shape)
        out = _eager_perrank(op_kind, stacked, op, prescale, postscale, root_rank)
        return out
    elif op_kind == "reducescatter":
        stacked = jnp.broadcast_to(x[None], (n,) + x.shape)
        out = _eager_perrank(op_kind, stacked, op, prescale, postscale)
        # out is [world * (d0/world), ...] sharded; controller returns the
        # rank-0 chunk to match per-process semantics.
        chunk = x.shape[0] // n
        return out[:chunk]
    elif op_kind == "alltoall":
        stacked = jnp.broadcast_to(x[None], (n,) + x.shape)
        out = _eager_perrank(op_kind, stacked)
        return out[: x.shape[0]]
    raise ValueError(op_kind)


# ---------------------------------------------------------------------------
# public API — allreduce family
# ---------------------------------------------------------------------------

def _dispatch(tensor, spmd_fn, eager_fn, axes, is_leaf=None):
    """Route to SPMD form when the dp axis is bound, else eager form."""
    live = _bound_axes(axes)
    if live:
        return jax.tree_util.tree_map(
            lambda x: spmd_fn(x, live), tensor, is_leaf=is_leaf
        )
    return jax.tree_util.tree_map(eager_fn, tensor, is_leaf=is_leaf)


def allreduce(
    tensor,
    average: Optional[bool] = None,
    name: Optional[str] = None,
    op: Optional[ReduceOp] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
    axis_name=None,
):
    """All-reduce a tensor (or pytree) across the data-parallel world.

    API parity: horovod/torch/mpi_ops.py:255 (allreduce) — `average` is the
    deprecated bool alias for op=Average/Sum, `name` is accepted for
    compatibility (XLA names come from jaxpr provenance), prescale/postscale
    mirror the fused scalar multiplies (collective_operations.h:91
    ScaleBuffer), and `process_set` restricts participation.
    """
    if op is None:
        op = ReduceOp.AVERAGE if (average is None or average) else ReduceOp.SUM
    elif average is not None:
        raise ValueError("specify either average= or op=, not both")
    from .sparse import IndexedSlices, sparse_allreduce

    _is_sparse_leaf = lambda x: isinstance(x, IndexedSlices)  # noqa: E731

    if isinstance(tensor, IndexedSlices):
        # sparse gradients reduce by gathering slices from all ranks
        # (reference tensorflow/__init__.py:56)
        return sparse_allreduce(
            tensor, op=op, name=name, process_set=process_set,
            axis_name=axis_name,
        )
    if op == ReduceOp.ADASUM:
        from .adasum import adasum_allreduce

        def _adasum_leaf_guard(x):
            if isinstance(x, IndexedSlices):
                raise ValueError(
                    "adasum does not support sparse (IndexedSlices) "
                    "gradients; use op=Average/Sum"
                )
            return x

        axes = _resolve_axis(axis_name)
        live = _bound_axes(axes)
        if live:
            return jax.tree_util.tree_map(
                lambda x: adasum_allreduce(
                    _adasum_leaf_guard(x), live[0], process_set=process_set
                ),
                tensor, is_leaf=_is_sparse_leaf,
            )
        if global_state().eager_runtime is not None:
            # negotiated path: real multi-process adasum via the executor
            return jax.tree_util.tree_map(
                lambda x: _eager_collective(
                    "allreduce", _adasum_leaf_guard(x), op,
                    prescale_factor, postscale_factor,
                    process_set=process_set, name=name,
                ),
                tensor, is_leaf=_is_sparse_leaf,
            )
        # eager single-controller: identical tensors ⇒ adasum(a,a) == a
        return tensor

    axes = _resolve_axis(axis_name)
    ps = process_set

    # nested IndexedSlices are leaves, never flattened — tree_map over a
    # NamedTuple would otherwise average the int32 indices across ranks
    def spmd(x, live):
        if isinstance(x, IndexedSlices):
            return sparse_allreduce(x, op=op, process_set=ps,
                                    axis_name=axis_name)
        return _spmd_allreduce_leaf(
            x, op, live, ps, prescale_factor, postscale_factor
        )

    namer = _leaf_namer(name)

    def eager(x):
        leaf_name = namer()
        if isinstance(x, IndexedSlices):
            return sparse_allreduce(x, op=op, name=leaf_name,
                                    process_set=ps, axis_name=axis_name)
        return _eager_collective(
            "allreduce", x, op, prescale_factor, postscale_factor,
            process_set=ps, name=leaf_name,
        )

    return _dispatch(tensor, spmd, eager, axes, is_leaf=_is_sparse_leaf)


def grouped_allreduce(
    tensors: Sequence,
    average: Optional[bool] = None,
    name: Optional[str] = None,
    op: Optional[ReduceOp] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
    axis_name=None,
) -> List:
    """Fused all-reduce of a list of tensors.

    Reference: torch/mpi_ops.py:555 grouped_allreduce + the fusion buffer
    (FuseResponses controller.cc:830, fusion_buffer_manager.h:30). Here the
    fusion is explicit and compile-time: tensors are flattened and packed
    into per-dtype buckets bounded by HOROVOD_FUSION_THRESHOLD, one XLA
    collective per bucket, then unpacked. See ops/fusion.py.
    """
    from .fusion import fuse_apply
    from .sparse import IndexedSlices

    if op is None:
        op = ReduceOp.AVERAGE if (average is None or average) else ReduceOp.SUM

    def reducer(flat_bucket):
        return allreduce(
            flat_bucket,
            op=ReduceOp.SUM if op == ReduceOp.AVERAGE else op,
            prescale_factor=prescale_factor,
            postscale_factor=(
                postscale_factor / _group_size(process_set, axis_name)
                if op == ReduceOp.AVERAGE
                else postscale_factor
            ),
            process_set=process_set,
            axis_name=axis_name,
        )

    tensors = list(tensors)
    # native eager world, all-dense: one group-tagged negotiation round
    # (all-or-nothing) + fused execution, same as the async surface —
    # the compile-time bucketing below is the jit/SPMD form
    if (not _bound_axes(_resolve_axis(axis_name))
            and _native_rt_for_async(process_set) is not None
            and not _contains_indexed_slices(tensors)):
        return synchronize(grouped_allreduce_async(
            tensors, op=op, name=name, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, process_set=process_set))
    # IndexedSlices members can't ride the fusion buffer (their indices
    # and static dense_shape would be summed as data); route each through
    # the sparse path, fuse only the dense members (reference
    # tensorflow/__init__.py:249 handles grouped IndexedSlices the same
    # way: per-member allgathers)
    results: list = [None] * len(tensors)
    namer = _leaf_namer(name)
    dense_idx = []
    for i, t in enumerate(tensors):
        leaf_name = namer()
        if isinstance(t, IndexedSlices):
            results[i] = allreduce(
                t, op=op, name=leaf_name, process_set=process_set,
                axis_name=axis_name,
            )
        else:
            dense_idx.append(i)
    if dense_idx:
        dense_out = fuse_apply([tensors[i] for i in dense_idx], reducer)
        for i, r in zip(dense_idx, dense_out):
            results[i] = r
    return results


def _group_size(ps: Optional[ProcessSet], axis_name) -> int:
    if ps is not None and ps.process_set_id != 0:
        return ps.size()
    axes = _resolve_axis(axis_name)
    live = _bound_axes(axes)
    if live:
        return _axis_size(live)
    return global_state().world_size()


def allgather(
    tensor,
    name: Optional[str] = None,
    process_set: Optional[ProcessSet] = None,
    axis_name=None,
):
    """Concatenate each rank's tensor along dim 0
    (torch/mpi_ops.py:752 allgather). SPMD shapes are rank-uniform by
    construction; ragged first dims are an eager-runtime feature
    (ops/eager_runtime.py)."""
    _reject_indexed_slices(tensor, "allgather")
    axes = _resolve_axis(axis_name)
    ps = process_set
    namer = _leaf_namer(name)

    def spmd(x, live):
        return _spmd_allgather_leaf(x, live, ps)

    def eager(x):
        return _eager_collective("allgather", x, process_set=ps,
                                 name=namer())

    return _dispatch(tensor, spmd, eager, axes)


def broadcast(
    tensor,
    root_rank: int = 0,
    name: Optional[str] = None,
    process_set: Optional[ProcessSet] = None,
    axis_name=None,
):
    """Broadcast root_rank's tensor to every rank
    (torch/mpi_ops.py:858). root_rank is a *global* rank, also for process
    sets (matching the reference's semantics)."""
    _reject_indexed_slices(tensor, "broadcast")
    axes = _resolve_axis(axis_name)
    ps = process_set
    if ps is not None and ps.process_set_id != 0 and root_rank not in ps.ranks:
        raise HorovodInternalError(
            f"broadcast root {root_rank} not in process set {ps.ranks}"
        )
    namer = _leaf_namer(name)

    def spmd(x, live):
        return _spmd_broadcast_leaf(x, root_rank, live, ps)

    def eager(x):
        return _eager_collective("broadcast", x, root_rank=root_rank,
                                 process_set=ps, name=namer())

    return _dispatch(tensor, spmd, eager, axes)


def reducescatter(
    tensor,
    op: ReduceOp = ReduceOp.AVERAGE,
    name: Optional[str] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
    axis_name=None,
):
    """Reduce then scatter chunks of dim 0 (torch/mpi_ops.py:1022);
    rank i receives chunk i. Default op is Average like the reference."""
    _reject_indexed_slices(tensor, "reducescatter")
    axes = _resolve_axis(axis_name)
    ps = process_set
    namer = _leaf_namer(name)

    def spmd(x, live):
        return _spmd_reducescatter_leaf(
            x, op, live, ps, prescale_factor, postscale_factor
        )

    def eager(x):
        return _eager_collective(
            "reducescatter", x, op, prescale_factor, postscale_factor,
            process_set=ps, name=namer(),
        )

    return _dispatch(tensor, spmd, eager, axes)


def _by_dtype_groups(arrs):
    """Index groups per dtype, preserving submission order within each —
    the reference fuses same-dtype responses only (controller.cc:830)."""
    groups: dict = {}
    for i, a in enumerate(arrs):
        groups.setdefault(a.dtype, []).append(i)
    return groups


def grouped_reducescatter(tensors, op=ReduceOp.AVERAGE, name=None,
                          prescale_factor=1.0, postscale_factor=1.0,
                          process_set=None, axis_name=None):
    """Fused reduce-scatter of a list of tensors.

    Reference: group negotiation + fused execution
    (/root/reference/horovod/common/operations.cc:1532
    EnqueueTensorReducescatters releases the members all-or-nothing and
    FuseResponses packs them; torch/mpi_ops.py grouped_reducescatter).
    Under jit the group packs rank-major into ONE reduce-scatter HLO per
    dtype; through the native runtime the members enqueue under one
    group tag so one negotiation cycle covers the whole group.
    """
    tensors = list(tensors)
    if not tensors:
        return []
    for t in tensors:
        _reject_indexed_slices(t, "grouped_reducescatter")
    axes = _resolve_axis(axis_name)
    live = _bound_axes(axes)
    ps = process_set
    if live:
        n = _group_size(ps, axis_name)
        arrs = [jnp.asarray(t) for t in tensors]
        results: list = [None] * len(arrs)
        for dtype, idxs in _by_dtype_groups(arrs).items():
            for i in idxs:
                if arrs[i].shape[0] % n:
                    raise HorovodInternalError(
                        f"grouped_reducescatter dim0 {arrs[i].shape[0]} "
                        f"not divisible by set size {n}")
            # rank-major packing: chunk k of every member, concatenated —
            # a tiled reduce-scatter then hands rank k exactly its chunks
            # of every member in one collective
            per_rank = [arrs[i].reshape(n, -1) for i in idxs]
            packed = jnp.concatenate(per_rank, axis=1).reshape(-1)
            red = _spmd_reducescatter_leaf(
                packed, op, live, ps, prescale_factor, postscale_factor)
            off = 0
            for i in idxs:
                a = arrs[i]
                m = a.size // n
                out_shape = (a.shape[0] // n,) + a.shape[1:]
                results[i] = lax.dynamic_slice_in_dim(
                    red, off, m).reshape(out_shape)
                off += m
        return results
    rt = _native_rt_for_async(ps)
    if rt is not None:
        # one group-tagged negotiation round (all-or-nothing), then the
        # executor fuses the batch — the runtime mirror of the packing
        return synchronize(grouped_reducescatter_async(
            tensors, op=op, name=name, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, process_set=ps))
    namer = _leaf_namer(name)
    return [reducescatter(t, op=op, name=namer(),
                          prescale_factor=prescale_factor,
                          postscale_factor=postscale_factor,
                          process_set=ps, axis_name=axis_name)
            for t in tensors]


def grouped_allgather(tensors, name=None, process_set=None,
                      axis_name=None):
    """Fused allgather of a list of tensors.

    Reference: /root/reference/horovod/common/operations.cc:1725
    (EnqueueTensorAllgathers — one all-or-nothing group) +
    torch/mpi_ops.py grouped_allgather. Under jit the group packs into
    ONE all-gather HLO per dtype; through the native runtime the members
    ride one group-tagged negotiation cycle.
    """
    tensors = list(tensors)
    if not tensors:
        return []
    for t in tensors:
        _reject_indexed_slices(t, "grouped_allgather")
    axes = _resolve_axis(axis_name)
    live = _bound_axes(axes)
    ps = process_set
    if live:
        n = _group_size(ps, axis_name)
        arrs = [jnp.asarray(t) for t in tensors]
        results: list = [None] * len(arrs)
        for dtype, idxs in _by_dtype_groups(arrs).items():
            flats = [arrs[i].reshape(-1) for i in idxs]
            packed = (jnp.concatenate(flats)
                      if len(flats) > 1 else flats[0])
            total = packed.shape[0]
            # [n, total]: row k = rank k's contiguous block; ONE slice
            # per member (not per member x rank — at n=256 that would
            # bloat the trace by ~n ops per member)
            g = _spmd_allgather_leaf(packed, live, ps).reshape(n, total)
            off = 0
            for i in idxs:
                a = arrs[i]
                # member i's column slab across ranks, folded back to
                # dim-0 concatenation (allgather semantics)
                slab = lax.dynamic_slice_in_dim(g, off, a.size, axis=1)
                results[i] = slab.reshape((n * a.shape[0],) + a.shape[1:])
                off += a.size
        return results
    rt = _native_rt_for_async(ps)
    if rt is not None:
        return synchronize(grouped_allgather_async(
            tensors, name=name, process_set=ps))
    namer = _leaf_namer(name)
    return [allgather(t, name=namer(), process_set=ps,
                      axis_name=axis_name) for t in tensors]


def alltoall(
    tensor,
    splits=None,
    name: Optional[str] = None,
    process_set: Optional[ProcessSet] = None,
    axis_name=None,
):
    """Exchange dim-0 chunks between ranks (torch/mpi_ops.py:1102).

    Equal splits (splits=None): one XLA all-to-all HLO — dim 0 must divide
    by the set size. Uneven `splits` are supported in the eager runtime
    (true ragged exchange, ops/eager_runtime.py) and via the padded SPMD
    helper `horovod_tpu.parallel.ulysses.padded_alltoall` — SPMD programs
    are shape-uniform across ranks, so raggedness needs an explicit static
    bound there (SURVEY.md §5.7).

    Returns the exchanged tensor; with `splits` also returns
    received_splits, matching the reference's (output, received_splits).
    """
    _reject_indexed_slices(tensor, "alltoall")
    axes = _resolve_axis(axis_name)
    ps = process_set

    if splits is not None:
        splits = jnp.asarray(splits, dtype=jnp.int32)
        live = _bound_axes(axes)
        if live:
            raise HorovodInternalError(
                "uneven alltoall inside SPMD requires "
                "parallel.ulysses.padded_alltoall (static max chunk); "
                "equal-split alltoall lowers to one HLO"
            )
        rt = global_state().eager_runtime
        if rt is not None:
            # true ragged exchange: the controller negotiates the full
            # splits matrix (in set-local coordinates for non-global
            # sets, controller.cc BuildResponse), the executor
            # pads/slices around one uniform all_to_all HLO over the
            # set's sub-mesh (reference operations.cc:1858)
            sid = 0
            if ps is not None and ps.process_set_id != 0:
                sid = ps.process_set_id
                if rt.process_set_members(sid) is None:
                    raise HorovodInternalError(
                        f"process set {sid} is not registered with the "
                        "native runtime; call hvd.add_process_set on "
                        "every rank first (reference process_sets.py:123)"
                    )
            _record_collective_leaf("alltoall", tensor)
            out, recv = _native_eager(
                rt, "alltoall", tensor, name=name,
                splits=[int(s) for s in np.asarray(splits)],
                process_set_id=sid,
            )
            return out, recv
        # eager single-controller (no native runtime): run the batch
        # through the LoopbackExecutor — the same implementation every
        # single-process world uses (identical replicated buffers, the
        # received layout is column `rank` of the splits matrix) — rather
        # than a hand-built special case.
        from .eager_runtime import ExecutionBatch, LoopbackExecutor
        from .._native import OP_ALLTOALL

        n = _group_size(ps, axis_name)
        rank_local = 0 if ps is None else ps.rank(basics.rank())
        x = np.asarray(tensor)
        _record_collective_leaf("alltoall", x)
        batch = ExecutionBatch(
            batch_id=0, op=OP_ALLTOALL, reduce_op=0, root_rank=0,
            prescale=1.0, postscale=1.0, dtype=str(x.dtype),
            total_bytes=x.nbytes, names=["alltoall"], handles=[0],
            first_shape=list(x.shape), error_reason="",
            all_splits=[int(s) for s in np.asarray(splits)] * n,
        )
        out, received_splits = LoopbackExecutor(n, rank_local)(
            batch, {"alltoall": x})["alltoall"]
        return jnp.asarray(out), jnp.asarray(received_splits)

    namer = _leaf_namer(name)

    def spmd(x, live):
        return _spmd_alltoall_leaf(x, live, ps)

    def eager(x):
        return _eager_collective("alltoall", x, process_set=ps,
                                 name=namer())

    return _dispatch(tensor, spmd, eager, axes)


def alltoall_splits_exchange(splits, live, ps):
    """Exchange split sizes (row i of the implied matrix): each rank learns
    how much every peer will send it. One small all_to_all."""
    return _spmd_alltoall_leaf(splits.reshape(-1, 1), live, ps).reshape(-1)


# ---------------------------------------------------------------------------
# join / barrier
# ---------------------------------------------------------------------------

def join(device=None) -> int:
    """Ragged-end data parallelism (torch/mpi_ops.py:1250, JoinOp
    collective_operations.h:325): ranks that exhausted their data "join";
    the others keep all-reducing with zero contributions from joined ranks.

    Under single-controller SPMD there are no raggedly-finishing processes —
    uneven data is handled *inside* the step via masking (see
    `masked_allreduce`), the idiomatic XLA form. Eagerly this is therefore
    a synchronization no-op returning the last joined rank (0). The
    multi-controller eager runtime implements true join accounting: joined
    ranks contribute zeros to collectives still pending on other ranks.
    """
    del device
    rt = global_state().eager_runtime
    if rt is not None and not basics.in_spmd_context():
        return rt.join_sync()
    barrier()
    return 0


def masked_allreduce(tensor, valid, axis_name=None, process_set=None):
    """SPMD-native 'join': average over only the ranks where `valid` is
    true. ``out = psum(x*valid) / psum(valid)`` — equivalent to the
    reference's join-with-zero-contribution + recount semantics."""
    axes = _bound_axes(_resolve_axis(axis_name))
    if not axes:
        return tensor
    v = jnp.asarray(valid)

    def leaf(x):
        num = _spmd_allreduce_leaf(
            x * v.astype(x.dtype), ReduceOp.SUM, axes, process_set, 1.0, 1.0
        )
        den = _spmd_allreduce_leaf(
            v.astype(jnp.float32), ReduceOp.SUM, axes, process_set, 1.0, 1.0
        )
        return (num / jnp.maximum(den, 1.0).astype(x.dtype)).astype(x.dtype)

    return jax.tree_util.tree_map(leaf, tensor)


def barrier(process_set: Optional[ProcessSet] = None) -> None:
    """Block until all ranks arrive (torch/mpi_ops.py:1330, BarrierOp).
    Eager: a scalar psum across the mesh, blocked on. SPMD: XLA's program
    order already synchronizes; emit an optimization barrier no-op."""
    if basics.in_spmd_context():
        return
    st = global_state()
    if not st.initialized:
        return
    if st.eager_runtime is not None and (
        process_set is None or process_set.process_set_id == 0
    ):
        st.eager_runtime.barrier()
        return
    out = _eager_collective("allreduce", jnp.zeros(()), ReduceOp.SUM,
                            process_set=process_set)
    jax.block_until_ready(out)


# ---------------------------------------------------------------------------
# async handles
# ---------------------------------------------------------------------------
#
# Two async regimes (reference torch/mpi_ops.py:107-151 allreduce_async_ →
# handle → synchronize/poll; handle_manager.h:31):
#
# * single-controller: JAX dispatch is asynchronous by construction — the
#   op returns a future-backed Array immediately and the handle just wraps
#   it.
# * native runtime: the async op ENQUEUES into the background negotiation
#   runtime without executing, exactly the reference's enqueue model. This
#   is load-bearing, not parity sugar: ranks may submit tensors in
#   different orders, and only non-blocking submission lets the controller
#   see everything and order it (a blocking submit-then-wait would
#   deadlock on reordered peers).

class _NativeAsync:
    """A pending native-runtime collective: per-leaf native handles plus
    the treedef to rebuild the user's pytree at synchronize time."""

    def __init__(self, rt, op_kind, treedef, handles, with_splits=False):
        self.rt = rt
        self.op_kind = op_kind
        self.treedef = treedef
        self.handles = handles
        # alltoall parity: only a splits call returns (out, recv_splits);
        # a plain alltoall returns the tensor alone, native or not
        self.with_splits = with_splits


class _HandleManager:
    def __init__(self):
        self._next = 0
        self._values = {}

    def allocate(self, value) -> int:
        h = self._next
        self._next += 1
        self._values[h] = value
        return h

    def get(self, h: int):
        return self._values[h]

    def release(self, h: int):
        return self._values.pop(h)


_handles = _HandleManager()


def _async(fn, *args, **kw) -> int:
    return _handles.allocate(fn(*args, **kw))


def _native_rt_for_async(process_set=None):
    """The native runtime, when this call should route through it.
    Subset ops require their set to be registered with the runtime
    (add_process_set registers on every rank). An unregistered set under
    a live runtime fails HERE, eagerly — the sync sub-mesh fallback
    would re-enter _eager_collective and raise the same error from the
    worker thread at synchronize time, which only obscures the fix."""
    st = global_state()
    rt = st.eager_runtime
    if rt is None or basics.in_spmd_context():
        return None
    if process_set is not None and process_set.process_set_id != 0:
        if rt.process_set_members(process_set.process_set_id) is None:
            raise HorovodInternalError(
                f"process set {process_set.process_set_id} is not "
                "registered with the native runtime; call "
                "hvd.add_process_set on every rank first (reference "
                "process_sets.py:123)"
            )
    return rt


def _native_async(rt, op_kind, tensor, op=ReduceOp.SUM, prescale=1.0,
                  postscale=1.0, root_rank=0, name=None,
                  splits=None, grouped=False, process_set_id=0) -> int:
    # The negotiated wire path is dense-only; flattening an
    # IndexedSlices here would enqueue its int indices and dense_shape
    # as independent collectives. Sparse allreduce_async falls back to
    # the sync sparse path before reaching this point; everything else
    # must fail loudly.
    _reject_indexed_slices(tensor, f"native async {op_kind}")
    leaves, treedef = jax.tree_util.tree_flatten(tensor)
    for leaf in leaves:
        _record_collective_leaf(op_kind, leaf)
    namer = _leaf_namer(name)
    names = [namer() or _auto_name(op_kind) for _ in leaves]
    group, group_size = None, 0
    if grouped and len(names) > 1:
        # all-or-nothing readiness (reference group_table.h:25): the tag
        # is derived from the member names so every rank computes the
        # same group identity without a registration round-trip
        import hashlib

        group = hashlib.sha1(
            "|".join(names).encode()
        ).hexdigest()[:16]
        group_size = len(names)
    # ONE batched enqueue for the whole leaf set: the runtime amortizes
    # its lock/queue round (and the fast-path bookkeeping) across the
    # set instead of paying it per tensor — a DistributedOptimizer's
    # per-step gradient set is 8+ leaves, and per-leaf rounds were the
    # dominant enqueue cost (BENCH_r05 phase breakdown). jax arrays pass
    # through on-device (eager_runtime keeps them there end-to-end);
    # everything else is host-materialized once inside enqueue_batch.
    hs = rt.enqueue_batch([
        dict(
            name=leaf_name, tensor=leaf, op=_NATIVE_OPS[op_kind],
            reduce_op=int(op), root_rank=int(root_rank),
            prescale=float(prescale), postscale=float(postscale),
            splits=splits, group=group, group_size=group_size,
            process_set_id=process_set_id,
        )
        for leaf_name, leaf in zip(names, leaves)
    ])
    return _handles.allocate(
        _NativeAsync(rt, op_kind, treedef, hs,
                     with_splits=splits is not None)
    )



def _ps_id(process_set) -> int:
    return process_set.process_set_id if process_set is not None else 0


def allreduce_async(tensor, average=None, name=None, op=None,
                    prescale_factor=1.0, postscale_factor=1.0,
                    process_set=None, axis_name=None) -> int:
    if op is None:
        op = ReduceOp.AVERAGE if (average is None or average) else ReduceOp.SUM
    elif average is not None:
        raise ValueError("specify either average= or op=, not both")
    rt = _native_rt_for_async(process_set)
    # IndexedSlices reduce via the gather-based sparse path (reference
    # torch/mpi_ops.py:556 sparse_allreduce_async), which the sync
    # allreduce() already routes; the native dense wire path can't
    # carry them.
    if rt is not None and not _contains_indexed_slices(tensor):
        return _native_async(
            rt, "allreduce", tensor, op, prescale_factor,
            postscale_factor, name=name, process_set_id=_ps_id(process_set),
        )
    return _async(allreduce, tensor, op=op, name=name,
                  prescale_factor=prescale_factor,
                  postscale_factor=postscale_factor,
                  process_set=process_set, axis_name=axis_name)


def allgather_async(tensor, name=None, process_set=None,
                    axis_name=None) -> int:
    rt = _native_rt_for_async(process_set)
    if rt is not None:
        return _native_async(rt, "allgather", tensor, name=name,
                             process_set_id=_ps_id(process_set))
    return _async(allgather, tensor, name=name, process_set=process_set,
                  axis_name=axis_name)


def broadcast_async(tensor, root_rank: int = 0, name=None,
                    process_set=None, axis_name=None) -> int:
    rt = _native_rt_for_async(process_set)
    if rt is not None:
        return _native_async(rt, "broadcast", tensor, root_rank=root_rank,
                             name=name,
                             process_set_id=_ps_id(process_set))
    return _async(broadcast, tensor, root_rank=root_rank, name=name,
                  process_set=process_set, axis_name=axis_name)


def alltoall_async(tensor, splits=None, name=None, process_set=None,
                   axis_name=None) -> int:
    rt = _native_rt_for_async(process_set)
    if rt is not None:
        sp = (
            [int(s) for s in np.asarray(splits)]
            if splits is not None else None
        )
        return _native_async(rt, "alltoall", tensor, name=name, splits=sp,
                             process_set_id=_ps_id(process_set))
    return _async(alltoall, tensor, splits=splits, name=name,
                  process_set=process_set, axis_name=axis_name)


def reducescatter_async(tensor, op: ReduceOp = ReduceOp.AVERAGE, name=None,
                        prescale_factor=1.0, postscale_factor=1.0,
                        process_set=None, axis_name=None) -> int:
    rt = _native_rt_for_async(process_set)
    if rt is not None:
        return _native_async(rt, "reducescatter", tensor, op,
                             prescale_factor, postscale_factor, name=name,
                             process_set_id=_ps_id(process_set))
    return _async(reducescatter, tensor, op=op, name=name,
                  prescale_factor=prescale_factor,
                  postscale_factor=postscale_factor,
                  process_set=process_set, axis_name=axis_name)


def grouped_allreduce_async(tensors, average=None, name=None, op=None,
                            prescale_factor=1.0, postscale_factor=1.0,
                            process_set=None, axis_name=None) -> int:
    if op is None:
        op = ReduceOp.AVERAGE if (average is None or average) else ReduceOp.SUM
    elif average is not None:
        raise ValueError("specify either average= or op=, not both")
    tensors = list(tensors)
    rt = _native_rt_for_async(process_set)
    if rt is not None and not _contains_indexed_slices(tensors):
        # one enqueue per tensor, tagged as a group: the controller holds
        # all members until every one is globally ready (all-or-nothing,
        # group_table.h:25) and FuseResponses packs them into fused
        # batches — the real runtime fusion path, not the compile-time
        # bucketing of ops/fusion.py
        return _native_async(
            rt, "allreduce", tensors, op, prescale_factor,
            postscale_factor, name=name, grouped=True,
            process_set_id=_ps_id(process_set),
        )
    return _async(grouped_allreduce, tensors, op=op, name=name,
                  prescale_factor=prescale_factor,
                  postscale_factor=postscale_factor,
                  process_set=process_set, axis_name=axis_name)


def grouped_allgather_async(tensors, name=None, process_set=None,
                            axis_name=None) -> int:
    """Grouped allgather through one all-or-nothing negotiation round
    (reference operations.cc:1725, torch/mpi_ops.py)."""
    tensors = list(tensors)
    rt = _native_rt_for_async(process_set)
    if rt is not None and not _contains_indexed_slices(tensors):
        return _native_async(
            rt, "allgather", tensors, name=name, grouped=True,
            process_set_id=_ps_id(process_set),
        )
    return _async(grouped_allgather, tensors, name=name,
                  process_set=process_set, axis_name=axis_name)


def grouped_reducescatter_async(tensors, op: ReduceOp = ReduceOp.AVERAGE,
                                name=None, prescale_factor=1.0,
                                postscale_factor=1.0, process_set=None,
                                axis_name=None) -> int:
    """Grouped reduce-scatter through one all-or-nothing negotiation
    round (reference operations.cc:1532, torch/mpi_ops.py)."""
    tensors = list(tensors)
    rt = _native_rt_for_async(process_set)
    if rt is not None and not _contains_indexed_slices(tensors):
        return _native_async(
            rt, "reducescatter", tensors, op, prescale_factor,
            postscale_factor, name=name, grouped=True,
            process_set_id=_ps_id(process_set),
        )
    return _async(grouped_reducescatter, tensors, op=op, name=name,
                  prescale_factor=prescale_factor,
                  postscale_factor=postscale_factor,
                  process_set=process_set, axis_name=axis_name)


def poll(handle: int) -> bool:
    """True if the async op completed (torch/mpi_ops.py:1210)."""
    v = _handles.get(handle)
    if isinstance(v, _NativeAsync):
        return all(v.rt.poll(h) for h in v.handles)
    try:
        leaves = jax.tree_util.tree_leaves(v)
        return all(getattr(l, "is_ready", lambda: True)() for l in leaves)
    except Exception:
        return True


def synchronize(handle: int):
    """Wait for and return the result (torch/mpi_ops.py:1226)."""
    v = _handles.release(handle)
    if isinstance(v, _NativeAsync):
        outs = []
        for h in v.handles:
            r = v.rt.synchronize(h)
            if v.op_kind == "alltoall" and isinstance(r, tuple):
                if v.with_splits:
                    r = tuple(jnp.asarray(e) for e in r)
                else:
                    r = jnp.asarray(r[0])
            else:
                r = jnp.asarray(r)
            outs.append(r)
        return jax.tree_util.tree_unflatten(v.treedef, outs)
    jax.block_until_ready(v)
    return v
