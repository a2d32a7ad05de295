"""Hierarchical (two-level) collectives: the ICI×DCN scaling lever.

Reference: /root/reference/horovod/common/ops/nccl_operations.h:227
(`NCCLHierarchicalAllreduce`: intra-node ncclReduceScatter → cross-node
MPI allreduce of the residual → intra-node ncclAllGather) and
`MPIHierarchicalAllgather` in mpi_operations.cc (node-leader gather +
shared-memory window). Selected by `HOROVOD_HIERARCHICAL_ALLREDUCE` /
`HOROVOD_HIERARCHICAL_ALLGATHER` (operations.cc:551-565).

TPU translation: "node" becomes "slice" — the fast inner domain is the
ICI torus, the slow outer domain is DCN. The structure is the same and
for the same reason: the bandwidth-bound outer leg must move 1/k of the
bytes (k = inner-domain size), so

    allreduce(x)  =  all_gather_inner( psum_outer( rs_inner(x) ) )
    allgather(x)  =  all_gather_outer( all_gather_inner(x) )

Two forms:

* **two axes** — the reduction world is already factored into mesh axes
  (inner = last axis, laid out innermost on the torus by
  parallel/mesh.py): collectives address whole axes, no groups needed.
* **one axis + block size** — the world is one flat axis whose ranks
  0..n-1 pack `block` consecutive ranks per inner domain (the launcher's
  rank model: local ranks are contiguous, hosts are the outer level —
  runner/util/hosts.py SlotInfo). Inner groups are contiguous blocks,
  outer groups are strided, expressed as `axis_index_groups`.

Numerics are identical to the flat psum (sum reassociation over a
partition of the world); a structure test asserts the emitted HLO
differs (reduce-scatter+all-gather vs one all-reduce).

**Compression-aware routing** (`wire=` — optim/compression.py WireSpec,
docs/compression.md): the ICI inner legs (reduce-scatter, all-gather)
always run at full logical precision — ICI bandwidth is cheap and the
inner reduce seeds the outer leg's values — while the bandwidth-bound
DCN outer leg moves the compressed payload:

  * cast wires (bf16/fp16): the outer psum runs in the cast dtype;
  * int8: each slice quantizes its inner-reduced shard per block, the
    outer leg all-gathers quantized shards + scales (~1/4 of the
    full-precision bytes on the leg that dominates at scale), and each
    rank dequant-accumulates locally. With ``residual`` the shard
    payload is error-compensated and the new residual is returned
    (error feedback; the residual lives on the first ``shard_len``
    entries of the caller's flat buffer — the shard is rank-private, so
    the layout is internal).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import basics
from ..core.exceptions import HorovodInternalError


def _flatten_pad(x, multiple: int):
    """Flatten to 1-D and zero-pad so the length divides `multiple`."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    rem = n % multiple
    if rem:
        flat = jnp.pad(flat, (0, multiple - rem))
    return flat, n


def _block_groups(world: int, block: int) -> Tuple[list, list]:
    """(inner, outer) axis_index_groups for contiguous blocks of `block`
    ranks: inner = [0..b-1], [b..2b-1], ...; outer = strided across
    blocks at equal offset (the cross-node communicator of the
    reference's rank model, controller.h:120-132)."""
    inner = [list(range(i, i + block)) for i in range(0, world, block)]
    nblocks = world // block
    outer = [
        [off + b * block for b in range(nblocks)] for off in range(block)
    ]
    return inner, outer


def resolve_block(world: int, block: int = 0) -> int:
    """Pick the inner-domain size: explicit knob value, else the process-
    local device count (ICI domain ≈ node), else no hierarchy (1)."""
    if block <= 0:
        try:
            block = basics.local_size()
        except Exception:
            return 1
    if block <= 1 or block >= world or world % block:
        return 1
    return block


def _outer_wire_sum(rs, outer_ax, groups, n_outer: int, wire, residual):
    """SUM of the inner-reduced shard `rs` over the outer (DCN) leg with
    `wire` compression. Returns the summed shard, plus the new residual
    when `residual` (f32, rs-shaped) was given (int8 only)."""
    import jax.numpy as jnp

    if wire.kind in ("fp16", "bf16"):
        y = lax.psum(rs.astype(wire.wire_dtype), outer_ax,
                     axis_index_groups=groups).astype(rs.dtype)
        return (y, None) if residual is not None else y
    if wire.kind != "int8":
        raise HorovodInternalError(f"unknown wire kind {wire.kind}")
    from ..optim import compression as _comp

    flat = rs.astype(jnp.float32).reshape(-1)
    L = flat.shape[0]
    if residual is not None:
        flat = flat + residual.astype(jnp.float32).reshape(-1)[:L]
    padded = _comp._pad_flat(flat, wire.block)
    q, s = _comp.quantize_blocks(padded, wire.block)
    # the DCN leg: quantized shards + scales, gathered (not reduced) —
    # each rank dequant-accumulates the n_outer contributions locally
    qg = lax.all_gather(q, outer_ax, axis_index_groups=groups)
    sg = lax.all_gather(s, outer_ax, axis_index_groups=groups)
    deq = _comp.dequantize_blocks(
        qg.reshape(-1), sg.reshape(-1), wire.block)
    y = deq.reshape(n_outer, -1).sum(axis=0)[:L].reshape(
        rs.shape).astype(rs.dtype)
    if residual is None:
        return y
    new_res = (padded - _comp.dequantize_blocks(q, s, wire.block))[:L]
    return y, new_res.reshape(rs.shape)


def _stash_shard_residual(x, shard_res, shard_len: int):
    """Park the rank-private shard residual in the head of an x-shaped
    f32 buffer (shard_len <= x.size always: shard_len = ceil(L/k))."""
    import jax.numpy as jnp

    buf = jnp.zeros((int(np.prod(jnp.shape(x))) or 1,), jnp.float32)
    buf = buf.at[:shard_len].set(shard_res.reshape(-1)[:shard_len])
    return buf.reshape(jnp.shape(x))


def hierarchical_psum(x, axes: Sequence[str], axis_sizes, block: int = 0,
                      wire=None, residual=None):
    """Two-level sum of `x` over `axes`, equal in value to
    ``lax.psum(x, axes)`` (exactly with ``wire=None``, to wire-
    quantization tolerance otherwise).

    axes: 1 axis (split by `block` via groups) or 2+ axes (last axis =
    inner/ICI level, the rest = outer). axis_sizes: name -> extent.
    wire: optional optim.compression.WireSpec — the DCN outer leg moves
    the compressed payload (module docstring); inner ICI legs stay full
    precision. residual (int8 error feedback): f32 array of x's shape;
    the call then returns ``(y, new_residual)``.
    """
    if residual is not None and (wire is None or wire.kind != "int8"):
        raise HorovodInternalError(
            "error-feedback residual requires an int8 wire")
    axes = tuple(axes)
    if len(axes) >= 2:
        inner_ax = axes[-1]
        outer_ax = axes[:-1] if len(axes) > 2 else axes[0]
        k = axis_sizes[inner_ax]
        n_outer = 1
        for ax in (axes[:-1] if len(axes) > 2 else (axes[0],)):
            n_outer *= axis_sizes[ax]
        flat, n = _flatten_pad(x, k)
        rs = lax.psum_scatter(flat, inner_ax, scatter_dimension=0,
                              tiled=True)
        if wire is None:
            ar = lax.psum(rs, outer_ax)
        elif residual is not None:
            shard_len = rs.shape[0]
            ar, res_shard = _outer_wire_sum(
                rs, outer_ax, None, n_outer, wire,
                residual.reshape(-1)[:shard_len])
        else:
            ar = _outer_wire_sum(rs, outer_ax, None, n_outer, wire, None)
        out = lax.all_gather(ar, inner_ax, tiled=True)
        y = out[:n].reshape(x.shape)
        if residual is not None:
            return y, _stash_shard_residual(x, res_shard, rs.shape[0])
        return y

    axis = axes[0]
    world = axis_sizes[axis]
    block = resolve_block(world, block)
    if block == 1:
        if wire is None:
            return lax.psum(x, axis)
        # degenerate hierarchy (no inner domain): whole-wire compression
        # for the flat world — the EQuARX two-phase form for int8, a
        # cast-reduce-cast for the float wires
        if wire.kind == "int8":
            from ..optim import compression as _comp

            return _comp.quantized_psum(x, axis, world, wire.block,
                                        residual=residual)
        y = lax.psum(x.astype(wire.wire_dtype), axis).astype(x.dtype)
        return y
    inner, outer = _block_groups(world, block)
    n_outer = world // block
    flat, n = _flatten_pad(x, block)
    rs = lax.psum_scatter(flat, axis, scatter_dimension=0, tiled=True,
                          axis_index_groups=inner)
    if wire is None:
        ar = lax.psum(rs, axis, axis_index_groups=outer)
    elif residual is not None:
        shard_len = rs.shape[0]
        ar, res_shard = _outer_wire_sum(
            rs, axis, outer, n_outer, wire,
            residual.reshape(-1)[:shard_len])
    else:
        ar = _outer_wire_sum(rs, axis, outer, n_outer, wire, None)
    out = lax.all_gather(ar, axis, tiled=True, axis_index_groups=inner)
    y = out[:n].reshape(x.shape)
    if residual is not None:
        return y, _stash_shard_residual(x, res_shard, rs.shape[0])
    return y


def hierarchical_allgather(x, axes: Sequence[str], axis_sizes,
                           block: int = 0):
    """Two-level dim-0 concatenation equal in value to a flat tiled
    ``lax.all_gather`` over `axes` (rank order = outer-major, matching
    the flat gather's index order)."""
    axes = tuple(axes)
    if len(axes) >= 2:
        inner_ax = axes[-1]
        g = lax.all_gather(x, inner_ax, tiled=True)
        for ax in reversed(axes[:-1]):
            g = lax.all_gather(g, ax, tiled=True)
        return g

    axis = axes[0]
    world = axis_sizes[axis]
    block = resolve_block(world, block)
    if block == 1:
        return lax.all_gather(x, axis, tiled=True)
    inner, outer = _block_groups(world, block)
    g = lax.all_gather(x, axis, tiled=True, axis_index_groups=inner)
    # outer gather concatenates blocks in block order == global rank order
    return lax.all_gather(g, axis, tiled=True, axis_index_groups=outer)


def hierarchy_enabled_for(op_kind: str, ps) -> bool:
    """Knob gate: hierarchical routing applies to global-set SUM/AVERAGE
    allreduce and allgather (the reference restricts likewise:
    nccl_operations.h:227 is allreduce-only sum; MPIHierarchicalAllgather
    requires the global communicator). The global set may be expressed
    either as None or as an explicit ProcessSet with id 0."""
    from ..core.state import global_state

    st = global_state()
    if ps is not None and getattr(ps, "process_set_id", None) == 0:
        ps = None
    if ps is not None or not st.initialized:
        return False
    k = st.knobs
    if op_kind == "allreduce":
        return bool(k.hierarchical_allreduce)
    if op_kind == "allgather":
        return bool(k.hierarchical_allgather)
    return False
