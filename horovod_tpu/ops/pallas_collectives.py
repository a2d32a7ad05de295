"""Fused computation-collective Pallas kernels (the PR 19 tentpole).

Three fusion surfaces over the existing int8/bucket data planes:

(a) **quantize-in-collective** — the block quantize, error-feedback
    residual computation, dequant-accumulate and final dequantize of
    :func:`optim.compression.quantized_psum` /
    :func:`quantized_reduce_scatter_rows` run as Pallas kernels around
    the *same* ``lax.all_to_all`` / ``all_gather`` exchanges, instead of
    separate XLA programs before and after the collective. The kernel
    bodies call the shared shape-polymorphic block math
    (``compression.block_quantize`` / ``block_dequantize``), so the
    fused path is **bitwise identical** to the unfused one — same
    values, same error-feedback residual trajectory
    (tests/test_pallas_collectives.py asserts this, interpret mode).

(b) **producer epilogue → reduce-scatter first hop** — the bucket
    pack (pad + ``(n, k)`` ring-shard row layout, ``zero._pad_rows``)
    runs as a Pallas epilogue on the producer side via
    :func:`maybe_pack_rows`, and :func:`matmul_reduce_scatter` fuses a
    grad-matmul's output tiles directly into the pack + first ring hop
    for explicit-matmul producers.

(c) **fused decode attention + KV-append** — :func:`decode_append_attend`
    merges the slotted cache's one-hot KV write (int8
    quantize-on-write), the dequantize, and the cached attention into
    one kernel per batch row (grid over B), removing the
    update/dequantize round-trip per token (serving/decode.py).

Selection: :func:`fused_enabled` reads ``knobs.fused_collectives``
(``HOROVOD_FUSED_COLLECTIVES`` / ``--fused-collectives``); the routing
lives inside the existing entry points so every call site keeps its
numerics contract with the knob off (knob-off lowering is unchanged —
asserted by the lowering-hash test). Off-TPU the kernels run under
``interpret=True`` — same discipline as pallas_attention.py — so tier-1
CPU parity tests execute the real kernel bodies.

The autotuner exposes the knob as an incumbent-seeded dimension
(``tune_fused_collectives``, ops/autotune.py), so on real hardware the
fused path is only pinned where measured never-worse. See
docs/fused_collectives.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ..optim import compression as _comp
from ._pallas import interpret

__all__ = [
    "fused_enabled",
    "fused_quantized_psum",
    "fused_quantized_reduce_scatter_rows",
    "maybe_pack_rows",
    "pack_rows_fused",
    "matmul_reduce_scatter",
    "decode_append_attend",
]


def fused_enabled(knobs=None) -> bool:
    """Whether the fused Pallas backend is selected: explicit `knobs`,
    else the initialized global knobs, else the raw env (check scripts
    and tests flip HOROVOD_FUSED_COLLECTIVES before hvd.init)."""
    if knobs is None:
        from ..core.state import global_state

        st = global_state()
        if st.initialized:
            knobs = st.knobs
    if knobs is not None:
        return bool(getattr(knobs, "fused_collectives", False))
    from ..core.knobs import _env_bool

    return _env_bool("FUSED_COLLECTIVES", False)


def _record_trace(surface: str) -> None:
    # trace-time breadcrumb: which fused surfaces this process lowered
    # (a counter per surface + the enabled gauge; execution-time wire
    # accounting is unchanged — the fused path moves the same bytes)
    from ..utils import metrics as _metrics

    _metrics.record_fused_collective(surface)


# ---------------------------------------------------------------------------
# kernel bodies — thin wrappers over the shared block math so the fused
# and unfused paths execute literally the same expressions
# ---------------------------------------------------------------------------


def _quant_kernel(x_ref, q_ref, s_ref, *, block: int):
    row = x_ref[0]  # (C,) f32, block | C
    q, s = _comp.block_quantize(row.reshape(-1, block))
    q_ref[0] = q.reshape(row.shape)
    s_ref[0] = s


def _quant_ef_kernel(x_ref, q_ref, s_ref, e_ref, *, block: int):
    # quantize + error-feedback residual in one pass: the residual is
    # exactly payload - dequantize(quantize(payload)), rank-private
    row = x_ref[0]
    blocks = row.reshape(-1, block)
    q, s = _comp.block_quantize(blocks)
    q_ref[0] = q.reshape(row.shape)
    s_ref[0] = s
    e_ref[0] = row - _comp.block_dequantize(q, s).reshape(row.shape)


def _accum_kernel(q_ref, s_ref, o_ref, *, block: int):
    # the ring step's local reduce: dequantize every peer's shard and
    # accumulate in f32 — same reshape/sum as the unfused
    # dequantize_blocks(...).reshape(n, k2).sum(axis=0)
    q = q_ref[...]  # (n, C) int8
    s = s_ref[...]  # (n, C // block) f32
    deq = _comp.block_dequantize(
        q.reshape(-1, block), s.reshape(-1)).reshape(q.shape)
    o_ref[...] = jnp.sum(deq, axis=0, keepdims=True)


def _dequant_kernel(q_ref, s_ref, o_ref, *, block: int):
    q = q_ref[...]  # (1, m) int8
    s = s_ref[...]
    o_ref[...] = _comp.block_dequantize(
        q.reshape(-1, block), s.reshape(-1)).reshape(q.shape)


def _pack_kernel(x_ref, o_ref):
    # zero._pad_rows epilogue: zero-fill + copy-in, same expression
    x = x_ref[...]  # (1, L)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype).at[
        0, : x.shape[1]].set(x[0].astype(o_ref.dtype))


def _matmul_pack_kernel(a_ref, b_ref, o_ref):
    # grad-matmul whose output tiles land directly in the ring-shard
    # row layout — the reduce-scatter's first hop reads o_ref as-is.
    # Whole-operand kernel: callers bound a/b to VMEM-sized buckets.
    g = jnp.dot(a_ref[...], b_ref[...],
                preferred_element_type=jnp.float32)
    flat = g.reshape(-1)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype).at[
        0, : flat.shape[0]].set(flat.astype(o_ref.dtype))


# ---------------------------------------------------------------------------
# kernel wrappers
# ---------------------------------------------------------------------------


def _quantize_rows(rows, block: int):
    """Per-row block quantize of an ``(R, C)`` f32 stack (block | C):
    ``(q int8 (R, C), scales f32 (R, C/block))``. Grid over rows — each
    program quantizes one ring shard."""
    R, C = rows.shape
    nb = C // block
    return pl.pallas_call(
        functools.partial(_quant_kernel, block=block),
        grid=(R,),
        in_specs=[pl.BlockSpec((1, C), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, C), lambda i: (i, 0)),
                   pl.BlockSpec((1, nb), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, nb), jnp.float32)],
        interpret=interpret(),
    )(rows)


def _quantize_ef_rows(rows, block: int):
    """:func:`_quantize_rows` + the error-feedback residual
    ``rows - dequantize(q, s)`` computed in the same kernel pass."""
    R, C = rows.shape
    nb = C // block
    return pl.pallas_call(
        functools.partial(_quant_ef_kernel, block=block),
        grid=(R,),
        in_specs=[pl.BlockSpec((1, C), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, C), lambda i: (i, 0)),
                   pl.BlockSpec((1, nb), lambda i: (i, 0)),
                   pl.BlockSpec((1, C), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, nb), jnp.float32),
                   jax.ShapeDtypeStruct((R, C), jnp.float32)],
        interpret=interpret(),
    )(rows)


def _accum_rows(q, s, block: int):
    """Dequant-accumulate an ``(n, C)`` int8 stack (the all_to_all
    result) to the local f32 ``(C,)`` shard."""
    n, C = q.shape
    out = pl.pallas_call(
        functools.partial(_accum_kernel, block=block),
        out_shape=jax.ShapeDtypeStruct((1, C), jnp.float32),
        interpret=interpret(),
    )(q, s)
    return out.reshape(C)


def _dequantize_flat(q, s, block: int):
    """Dequantize a flat int8 payload + scales to f32 (same values as
    ``compression.dequantize_blocks``)."""
    m = q.shape[0]
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, block=block),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.float32),
        interpret=interpret(),
    )(q.reshape(1, m), s.reshape(1, -1))
    return out.reshape(m)


# ---------------------------------------------------------------------------
# (a) quantize-in-collective
# ---------------------------------------------------------------------------


def fused_quantized_psum(x, axis: str, n: int, block: int,
                         residual=None):
    """Fused backend of :func:`compression.quantized_psum` — called by
    it when :func:`fused_enabled`; same EQuARX exchange structure, with
    the quantize/EF, local-reduce and dequant stages as Pallas kernels.
    Bitwise-identical to the unfused path (shared block math)."""
    _record_trace("quantized_psum")
    orig_dtype = x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    L = flat.shape[0]
    if residual is not None:
        flat = flat + residual.astype(jnp.float32).reshape(-1)
    padded = _comp._pad_flat(flat, n * block)
    m = padded.shape[0]
    rows = padded.reshape(n, m // n)  # row r = the shard rank r gets
    if residual is None:
        q2, s2 = _quantize_rows(rows, block)
        err2 = None
    else:
        q2, s2, err2 = _quantize_ef_rows(rows, block)
    # same tiled exchanges as the unfused path: row-major (n, C) flat
    # layout is exactly the chunking all_to_all tiles over
    qg = lax.all_to_all(q2.reshape(-1), axis,
                        split_axis=0, concat_axis=0, tiled=True)
    sg = lax.all_to_all(s2.reshape(-1), axis,
                        split_axis=0, concat_axis=0, tiled=True)
    shard = _accum_rows(qg.reshape(n, m // n),
                        sg.reshape(n, (m // n) // block), block)
    q3, s3 = _quantize_rows(shard.reshape(1, -1), block)
    qa = lax.all_gather(q3.reshape(-1), axis, tiled=True)
    sa = lax.all_gather(s3.reshape(-1), axis, tiled=True)
    y = _dequantize_flat(qa, sa, block)[:L].reshape(x.shape).astype(
        orig_dtype)
    if residual is None:
        return y
    new_res = err2.reshape(-1)[:L].reshape(x.shape)
    return y, new_res


def fused_quantized_reduce_scatter_rows(rows_f, axis: str, n: int,
                                        k: int, k2: int, block: int,
                                        with_residual: bool = False):
    """Fused backend of :func:`compression.quantized_reduce_scatter_rows`.
    ``rows_f`` is the f32 ``(n, k2)`` padded row stack with the
    error-feedback residual already added (the caller validates shapes
    and performs the compensation add — this keeps the unfused
    expression order, hence bitwise parity). Returns ``shard[:k]`` or
    ``(shard[:k], new_residual (n, k2))``."""
    _record_trace("reduce_scatter_rows")
    if with_residual:
        q2, s2, err2 = _quantize_ef_rows(rows_f, block)
    else:
        q2, s2 = _quantize_rows(rows_f, block)
        err2 = None
    qg = lax.all_to_all(q2.reshape(-1), axis,
                        split_axis=0, concat_axis=0, tiled=True)
    sg = lax.all_to_all(s2.reshape(-1), axis,
                        split_axis=0, concat_axis=0, tiled=True)
    shard = _accum_rows(qg.reshape(n, k2),
                        sg.reshape(n, k2 // block), block)
    if with_residual:
        return shard[:k], err2
    return shard[:k]


# ---------------------------------------------------------------------------
# (b) producer epilogue → reduce-scatter first hop
# ---------------------------------------------------------------------------


def pack_rows_fused(bucket, n: int):
    """Pallas epilogue form of ``zero._pad_rows``: flatten, zero-pad
    and lay a bucket out as the ``(n, k)`` ring-shard rows the
    reduce-scatter's first hop consumes, in one kernel on the producer
    side. Bitwise-identical layout (same zeros/at/set expression)."""
    b = bucket.reshape(-1)
    L = int(b.shape[0])
    k = -(-L // n)
    out = pl.pallas_call(
        _pack_kernel,
        out_shape=jax.ShapeDtypeStruct((1, n * k), b.dtype),
        interpret=interpret(),
    )(b.reshape(1, L))
    return out.reshape(n, k)


def maybe_pack_rows(bucket, n: int):
    """The pack-epilogue selection point used by the staged scheduler
    and the monolithic ZeRO/FSDP paths: fused Pallas pack when the knob
    is on, ``zero._pad_rows`` (unchanged lowering) when off."""
    if fused_enabled():
        _record_trace("pack_epilogue")
        return pack_rows_fused(bucket, n)
    from ..optim import zero as zero_mod

    return zero_mod._pad_rows(bucket, n)


def _matmul_pack(a, b, n: int):
    """``a @ b`` (f32 accumulate) packed into the ``(n, k)`` ring-shard
    layout in one kernel — the fused epilogue under
    :func:`matmul_reduce_scatter`."""
    size = int(a.shape[0]) * int(b.shape[1])
    k = -(-size // n)
    packed = pl.pallas_call(
        _matmul_pack_kernel,
        out_shape=jax.ShapeDtypeStruct((1, n * k), jnp.float32),
        interpret=interpret(),
    )(a, b)
    return packed.reshape(n, k)


def matmul_reduce_scatter(a, b, axis: str, n: int, wire=None,
                          residual=None):
    """Grad-matmul → ring reduce-scatter with a fused epilogue:
    ``a @ b`` (f32 accumulate on the MXU) lands its output tiles
    directly in the ``(n, k)`` ring-shard layout inside one Pallas
    kernel, and the reduce-scatter's first hop reads them as-is — the
    final bucket's wire starts without a separate pack program. The
    wire leg delegates to ``zero._scatter_bucket`` so every WireSpec
    (cast, int8, int8+EF) keeps its exact semantics, including the /n
    mean and residual carry; with the fused knob on, the int8 leg
    routes through :func:`fused_quantized_reduce_scatter_rows`.

    Knob off: the same values via plain ``jnp.dot`` + ``_pad_rows`` —
    the fused path is bitwise-equal (same dot, same pack expression).
    Whole-operand kernel: callers bound ``a``/``b`` to bucket-sized
    (VMEM-resident) operands, which is what the staged scheduler's
    final-segment grads are."""
    from ..optim import zero as zero_mod

    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(
            "matmul_reduce_scatter takes 2-D operands, got "
            f"{a.shape} @ {b.shape}")
    if fused_enabled():
        _record_trace("matmul_epilogue")
        rows = _matmul_pack(a, b, n)
    else:
        g = jnp.dot(a, b, preferred_element_type=jnp.float32)
        rows = zero_mod._pad_rows(g.reshape(-1), n)
    return zero_mod._scatter_bucket(rows, axis, n, wire,
                                    residual=residual)


# ---------------------------------------------------------------------------
# (c) fused decode attention + KV-append
# ---------------------------------------------------------------------------


def _append_attend_kernel(q_ref, kc_ref, vc_ref, kn_ref, vn_ref,
                          oh_ref, valid_ref, ko_ref, vo_ref, out_ref,
                          *, rep: int, scale: float, compute_dtype):
    """One batch row: one-hot KV merge (SlottedKVCache.update's exact
    expressions, per-b) + cached_attention, fp/bf16 cache."""
    oh = oh_ref[0]  # (T, M) f32
    cov = jnp.clip(jnp.sum(oh, axis=0), 0.0, 1.0)  # (M,)
    keep = (1.0 - cov)[None, :, None]  # (1, M, 1) ≡ keep[b]

    def merge(cache_khmd, new_tkd):
        delta = jnp.einsum("tm,tkd->kmd", oh, new_tkd.astype(jnp.float32))
        return cache_khmd.astype(jnp.float32) * keep + delta

    mk = merge(kc_ref[0], kn_ref[0]).astype(kc_ref.dtype)
    mv = merge(vc_ref[0], vn_ref[0]).astype(vc_ref.dtype)
    ko_ref[0] = mk
    vo_ref[0] = mv
    kf = mk.astype(compute_dtype)
    vf = mv.astype(compute_dtype)
    if rep != 1:
        kf = jnp.repeat(kf, rep, axis=0)
        vf = jnp.repeat(vf, rep, axis=0)
    q = q_ref[0]  # (T, H, D)
    logits = jnp.einsum("thd,hmd->htm", q, kf).astype(jnp.float32) * scale
    logits = jnp.where(valid_ref[0][None] != 0, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out_ref[0] = jnp.einsum("htm,hmd->thd", probs, vf)


def _append_attend_int8_kernel(q_ref, kc_ref, ks_ref, vc_ref, vs_ref,
                               kn_ref, vn_ref, oh_ref, valid_ref,
                               ko_ref, kso_ref, vo_ref, vso_ref,
                               out_ref, *, block: int, rep: int,
                               scale: float, compute_dtype):
    """int8 cache variant: quantize-on-write of the new rows, code and
    scale merges, dequantize and attention — all in-kernel."""
    oh = oh_ref[0]
    cov = jnp.clip(jnp.sum(oh, axis=0), 0.0, 1.0)
    keep = (1.0 - cov)[None, :, None]

    def merge(cache_khm_x, new_tk_x):
        delta = jnp.einsum("tm,tkd->kmd", oh,
                           new_tk_x.astype(jnp.float32))
        return cache_khm_x.astype(jnp.float32) * keep + delta

    def write(new_tkd, code_cache, scale_cache):
        # _quantize_rows: blocks tile the last axis (block | D)
        T, KH, D = new_tkd.shape
        codes, scales = _comp.block_quantize(
            new_tkd.astype(jnp.float32).reshape(-1, block))
        codes = codes.reshape(T, KH, D)
        scales = scales.reshape(T, KH, D // block)
        merged_codes = jnp.round(merge(code_cache, codes)).astype(
            jnp.int8)
        merged_scales = merge(scale_cache, scales)
        # _dequantize_rows over the merged slice
        KHc, M, _ = code_cache.shape
        full = (merged_codes.astype(jnp.float32).reshape(
            KHc, M, D // block, block)
            * merged_scales.astype(jnp.float32)[..., None]).reshape(
            KHc, M, D)
        return merged_codes, merged_scales, full

    mkc, mks, kfull = write(kn_ref[0], kc_ref[0], ks_ref[0])
    mvc, mvs, vfull = write(vn_ref[0], vc_ref[0], vs_ref[0])
    ko_ref[0] = mkc
    kso_ref[0] = mks
    vo_ref[0] = mvc
    vso_ref[0] = mvs
    kf = kfull.astype(compute_dtype)
    vf = vfull.astype(compute_dtype)
    if rep != 1:
        kf = jnp.repeat(kf, rep, axis=0)
        vf = jnp.repeat(vf, rep, axis=0)
    q = q_ref[0]
    logits = jnp.einsum("thd,hmd->htm", q, kf).astype(jnp.float32) * scale
    logits = jnp.where(valid_ref[0][None] != 0, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out_ref[0] = jnp.einsum("htm,hmd->thd", probs, vf)


def decode_append_attend(cache, layer: int, q, k_new, v_new,
                         positions):
    """Fused append+attend over a ``serving.decode.SlottedKVCache``:
    merge the new K/V rows into layer ``layer`` (int8
    quantize-on-write when the cache is int8), rebind the cache
    buffers, and return the attention output ``[B, T, H, D]`` — one
    kernel per batch row instead of the update → dequantize → attention
    round-trip. Knob off: exactly ``cache.update`` +
    ``cached_attention`` (unchanged lowering)."""
    from ..models.transformer import cached_attention

    if not fused_enabled():
        k_full, v_full, valid = cache.update(layer, k_new, v_new,
                                             positions)
        return cached_attention(q, k_full, v_full, valid)

    _record_trace("decode_append_attend")
    spec = cache.spec
    M = spec.max_len
    B, T, H, D = q.shape
    KH = spec.kv_heads
    rep = H // KH
    scale = 1.0 / np.sqrt(D)
    compute_dtype = spec.compute_dtype or jnp.float32
    # same one-hot / validity math as SlottedKVCache.update — computed
    # once, broadcast into the per-batch kernel programs
    oh = jax.nn.one_hot(positions, M, dtype=jnp.float32)  # [B,T,M]
    m_idx = jnp.arange(M, dtype=positions.dtype)
    valid = (m_idx[None, None, :] <= positions[:, :, None]).astype(
        jnp.int8)

    def spec_b(shape):
        # per-batch program i sees its own [1, ...] slice
        nd = len(shape)
        return pl.BlockSpec((1,) + shape[1:],
                            lambda i, _nd=nd: (i,) + (0,) * (_nd - 1))

    kb = cache.buffers["k"][:, layer]  # [B,KH,M,D]
    vb = cache.buffers["v"][:, layer]
    if spec.dtype == "int8":
        block = spec.resolved_block
        ksb = cache.buffers["k_scale"][:, layer]  # [B,KH,M,NB]
        vsb = cache.buffers["v_scale"][:, layer]
        args = (q, kb, ksb, vb, vsb, k_new, v_new, oh, valid)
        outs = [jax.ShapeDtypeStruct(kb.shape, jnp.int8),
                jax.ShapeDtypeStruct(ksb.shape, jnp.float32),
                jax.ShapeDtypeStruct(vb.shape, jnp.int8),
                jax.ShapeDtypeStruct(vsb.shape, jnp.float32),
                jax.ShapeDtypeStruct(q.shape, q.dtype)]
        mk, mks, mv, mvs, out = pl.pallas_call(
            functools.partial(_append_attend_int8_kernel, block=block,
                              rep=rep, scale=scale,
                              compute_dtype=compute_dtype),
            grid=(B,),
            in_specs=[spec_b(a.shape) for a in args],
            out_specs=[spec_b(s.shape) for s in outs],
            out_shape=outs,
            interpret=interpret(),
        )(*args)
        cache.buffers["k"] = cache.buffers["k"].at[:, layer].set(mk)
        cache.buffers["v"] = cache.buffers["v"].at[:, layer].set(mv)
        cache.buffers["k_scale"] = cache.buffers["k_scale"].at[
            :, layer].set(mks)
        cache.buffers["v_scale"] = cache.buffers["v_scale"].at[
            :, layer].set(mvs)
        return out

    args = (q, kb, vb, k_new, v_new, oh, valid)
    outs = [jax.ShapeDtypeStruct(kb.shape, kb.dtype),
            jax.ShapeDtypeStruct(vb.shape, vb.dtype),
            jax.ShapeDtypeStruct(q.shape, q.dtype)]
    mk, mv, out = pl.pallas_call(
        functools.partial(_append_attend_kernel, rep=rep, scale=scale,
                          compute_dtype=compute_dtype),
        grid=(B,),
        in_specs=[spec_b(a.shape) for a in args],
        out_specs=[spec_b(s.shape) for s in outs],
        out_shape=outs,
        interpret=interpret(),
    )(*args)
    cache.buffers["k"] = cache.buffers["k"].at[:, layer].set(mk)
    cache.buffers["v"] = cache.buffers["v"].at[:, layer].set(mv)
    return out
