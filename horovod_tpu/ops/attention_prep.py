"""One Pallas pass between attention's projections and the flash kernels.

A model with per-head q/k RMS norms or rope has, between the q and k
projections' results ``[B, T, H, D]`` and the flash kernels' layout
``[B, H, T, D]``, a norm over the head's width, a rotation of its two
halves and a transpose. Written as array operations
(``models/transformer.RMSNorm`` → ``apply_rope`` → ``transpose``) the
norm and the rotation are passes of their own over float32 copies,
because neither folds into a matrix product's fusion. Here they are one
pass a direction, over arrays that are in the kernels' layout on both
sides of it:

* the transposes are array operations still, in front of the forward
  call and behind the backward one, because there they cost nothing: the
  compiler has the projection's product write ``[B, H, T, D]`` and the
  backward products read it (it folds a transpose into a product's
  fusion, as in the models that have neither norms nor rope). Seen as
  ``[B, T, H·D]`` instead, so that a program could pick a head by its
  lanes, the product's result is another tiling of memory and the
  compiler copies all of it in front of every call (PERF.md section 6,
  PR 42);
* forward (``QK_PREP_FWD``): a program takes `rows` positions of all
  heads, block ``(1, H, rows, D)``. In float32: the RMS norm over the D
  lanes times ``scale``, rounded to the array's dtype as ``RMSNorm``
  rounds it; rope as ``x·cos + roll(x, D/2)·sin`` with the sign of
  ``rotate_half`` carried by the sine row (``[-sin, sin]``), a lane roll
  and no split or concatenate (`D` is whole lane tiles); rounded again
  as ``apply_rope`` rounds. q and k (fewer heads) ride the same call; v
  needs no arithmetic and stays the compiler's;
* backward (``QK_PREP_BWD``): the same pass the other way. It reads
  dq' and dk' (what the flash backward returns), the raw projection
  results and the same cos/sin rows, rebuilds the norm's statistics,
  and writes d(raw q), d(raw k) and one partial sum of d``scale`` a
  program (summed outside). The roundings between the steps are the
  ones autodiff of the array path makes (the cotangent of a bf16 value
  is bf16), so the two paths agree to the last bf16 place.

Residuals are the raw projection results and the cos/sin rows: nothing
in float32 and nothing the block does not hold anyway.

The arithmetic is one algorithm with two static switches, whether
there is a norm and whether there is rope: Llama's rope without q/k
norms is the same kernel with the norm left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import scopes
from ._pallas import interpret

# Rows (positions) a program takes, all heads of them, and heads one
# iteration of the kernel's loop over heads handles side by side.
# Measured on a v5e (`scripts/attention_prep_sweep.py`; PERF.md section
# 6, PR 42; `sdar_bd_s4096`'s call, q 32 heads and k 4 of 128 at 2 x
# 8,192 positions, ms a call forward / backward; the array passes take
# 2.65 / 3.46):
#
#   rows   1 head       4 heads      all in line
#   128    1.02 / 0.96  0.66 / 0.73  0.72 / 0.74
#   256    0.61 / 0.70  0.50 / 0.72  0.50 / 0.71
#   512    0.49 / 0.72  0.49 / 0.72  0.49 / 0.72
#
# From 256 rows and 4 heads on the pass moves its bytes at 600-630 GB/s,
# what a plain copy reaches on this chip; less of either leaves the
# norm's chain (reduce, rsqrt, broadcast, round) too few independent
# rows to fill its latencies. More only compiles longer.
_ROWS = 256
_HEADS = 4
# The call states the VMEM it needs (its blocks twice, for the
# pipeline's two copies, and as much again for the body's float32
# values), this much at least, so that it does not depend on what the
# step is compiled with.
_VMEM_LIMIT_LEAST = 16 * 2**20


def supports(head_width: int) -> bool:
    """Whether a head is whole lane tiles, so that the rotation of its
    halves is a lane roll."""
    return head_width % 128 == 0


def rope_rows(cos, sin, positions):
    """Rope's rows as the pass takes them, ``[B, T, 2·D]`` float32:
    ``[cos, cos, -sin, sin]`` of ``[max_len, D/2]`` tables at
    ``positions`` ``[B, T]`` (one gather), so that ``apply_rope``'s
    ``[x1·c - x2·s, x2·c + x1·s]`` is ``x·cos + roll(x, D/2)·sin`` with
    the row's first D lanes as cos and its last D as sin."""
    return jnp.concatenate([cos, cos, -sin, sin], axis=-1)[positions]


def _over_heads(n, body, carry=None):
    """`carry = body(h, carry)` for each of `n` heads: `_HEADS` of them
    side by side in an iteration of a loop, or all in line where that
    does not divide them."""
    if n % _HEADS or n <= _HEADS:
        for h in range(n):
            carry = body(h, carry)
        return carry

    def step(i, carry):
        for j in range(_HEADS):
            carry = body(i * _HEADS + j, carry)
        return carry

    return lax.fori_loop(0, n // _HEADS, step, carry)


def _half_roll(x):
    return pltpu.roll(x, x.shape[-1] // 2, x.ndim - 1)


def _normed(x, eps):
    """(x·r, r) of float32 rows: r = rsqrt(mean(x²) + eps)."""
    r = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r, r


def _fwd_kernel(*refs, heads, d, norm, rope, eps):
    """refs: q, k [1, heads, rows, d]; with `norm` the two scales [1,
    d]; with `rope` its rows [1, rows, 2·d]; then q', k' as q, k."""
    refs = list(refs)
    x_refs = [refs.pop(0), refs.pop(0)]
    scale_refs = [refs.pop(0), refs.pop(0)] if norm else [None, None]
    if rope:
        rope_ref = refs.pop(0)
        cos, sin = rope_ref[0, :, :d], rope_ref[0, :, d:]
    for x_ref, scale_ref, o_ref, n in zip(x_refs, scale_refs, refs, heads):
        dtype = o_ref.dtype

        def head(h, _):
            y = x_ref[0, h].astype(jnp.float32)
            if norm:
                y = (_normed(y, eps)[0] * scale_ref[...]).astype(
                    dtype).astype(jnp.float32)
            if rope:
                y = y * cos + _half_roll(y) * sin
            o_ref[0, h] = y.astype(dtype)

        _over_heads(n, head)


def _bwd_kernel(*refs, heads, d, norm, rope, eps, t):
    """refs: dq', dk', q, k [1, heads, rows, d]; with `norm` the two
    scales [1, d]; with `rope` its rows [1, rows, 2·d]; then dq, dk as
    q, k and with `norm` the program's partial d(scale) of each [1, 1,
    1, d]. `t` is the number of real rows: the last program's block may
    hang over it, and its rows past the end (anything may be read there)
    stay out of the partial sums."""
    refs = list(refs)
    g_refs = [refs.pop(0), refs.pop(0)]
    x_refs = [refs.pop(0), refs.pop(0)]
    scale_refs = [refs.pop(0), refs.pop(0)] if norm else [None, None]
    if rope:
        rope_ref = refs.pop(0)
        cos, sin = rope_ref[0, :, :d], rope_ref[0, :, d:]
    dx_refs = [refs.pop(0), refs.pop(0)]
    ds_refs = refs if norm else [None, None]
    rows = x_refs[0].shape[2]
    ragged = t % rows != 0
    if ragged:
        real = (lax.broadcasted_iota(jnp.int32, (rows, d), 0)
                < t - pl.program_id(1) * rows)
    for g_ref, x_ref, scale_ref, dx_ref, ds_ref, n in zip(
            g_refs, x_refs, scale_refs, dx_refs, ds_refs, heads):
        dtype = dx_ref.dtype

        def head(h, d_scale):
            g = g_ref[0, h].astype(jnp.float32)
            if rope:
                # the transpose of y·cos + roll(y)·sin: roll is its own
                # inverse and roll(sin) = -sin
                g = g * cos - _half_roll(g) * sin
            if norm:
                # the cotangent of RMSNorm's bf16 result is bf16
                g = g.astype(dtype).astype(jnp.float32)
                x = x_ref[0, h].astype(jnp.float32)
                y, r = _normed(x, eps)
                d_scale = d_scale + (jnp.where(real, g * y, 0.0) if ragged
                                     else g * y)
                g = g * scale_ref[...]
                g = g * r - x * (r * r * r * jnp.mean(
                    g * x, axis=-1, keepdims=True))
            dx_ref[0, h] = g.astype(dtype)
            return d_scale

        d_scale = _over_heads(
            n, head, jnp.zeros((rows, d), jnp.float32) if norm else None)
        if norm:
            ds_ref[0, 0] = jnp.sum(d_scale, axis=0, keepdims=True)


def _plan(residuals, rows, copies):
    """What the two calls share, from what the forward rule keeps (q, k,
    the scales, rope's rows): q and k in the kernels' layout, the grid
    (B, row blocks), their block spec, the specs and the operands of the
    scales and rope's rows, the compiler's parameters for a program that
    holds `copies` blocks of q's and of k's size, and the kernels'
    static arguments."""
    q, k, q_scale, k_scale, rope = residuals
    q, k = (x.transpose(0, 2, 1, 3) for x in (q, k))
    b, _, t, d = q.shape
    heads = (q.shape[1], k.shape[1])
    norm, has_rope = q_scale is not None, rope is not None
    # all of `t` where it fits (a block equal to the array needs no tile
    # alignment); else the last program's block hangs over the end, read
    # padded and written clipped
    rows = min(t, rows)
    arrays = [pl.BlockSpec((1, n, rows, d), lambda i, j: (i, 0, j, 0))
              for n in heads]
    specs, operands = [], []
    if norm:
        specs += [pl.BlockSpec((1, d), lambda i, j: (0, 0))] * 2
        operands += [s.reshape(1, d).astype(jnp.float32)
                     for s in (q_scale, k_scale)]
    if has_rope:
        specs.append(pl.BlockSpec((1, rows, 2 * d), lambda i, j: (i, j, 0)))
        operands.append(rope)
    block_bytes = copies * rows * sum(heads) * d * q.dtype.itemsize \
        + has_rope * rows * 2 * d * 4
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=max(_VMEM_LIMIT_LEAST, 4 * block_bytes))
    static = dict(heads=heads, d=d, norm=norm, rope=has_rope)
    return q, k, (b, pl.cdiv(t, rows)), arrays, specs, operands, params, \
        static


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def qk_prep(q, k, q_scale, k_scale, rope, eps, rows=_ROWS):
    """q ``[B, T, H, D]``, k ``[B, T, KH, D]`` (the projections'
    results) → q', k' in the flash kernels' ``[B, H, T, D]``: per-head
    RMS norm with `q_scale` / `k_scale` ``[D]`` (both None: no norm),
    then rope with `rope` ``[B, T, 2·D]`` from `rope_rows` (None: no
    rope). `D` has to be whole lane tiles (`supports`)."""
    return _qk_prep_fwd(q, k, q_scale, k_scale, rope, eps, rows)[0]


def _qk_prep_fwd(q, k, q_scale, k_scale, rope, eps, rows):
    residuals = (q, k, q_scale, k_scale, rope)
    q, k, grid, arrays, specs, operands, params, static = _plan(
        residuals, rows, copies=2)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, **static),
        grid=grid,
        in_specs=arrays + specs,
        out_specs=arrays,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k)],
        compiler_params=params,
        interpret=interpret(),
        name=scopes.QK_PREP_FWD,
    )(q, k, *operands)
    return tuple(out), residuals


def _qk_prep_bwd(eps, rows, residuals, grads):
    q, k, grid, arrays, specs, operands, params, static = _plan(
        residuals, rows, copies=3)
    d, norm = static["d"], static["norm"]
    partial = pl.BlockSpec((1, 1, 1, d), lambda i, j: (i, j, 0, 0))
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, t=q.shape[2], **static),
        grid=grid,
        in_specs=arrays + arrays + specs,
        out_specs=arrays + [partial] * (2 * norm),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k)]
        + [jax.ShapeDtypeStruct((*grid, 1, d), jnp.float32)] * (2 * norm),
        compiler_params=params,
        interpret=interpret(),
        name=scopes.QK_PREP_BWD,
    )(*(g.astype(x.dtype) for g, x in zip(grads, (q, k))), q, k, *operands)
    dq, dk = (x.transpose(0, 2, 1, 3) for x in out[:2])
    *_, q_scale, k_scale, rope = residuals
    d_scales = [jnp.sum(p, axis=(0, 1, 2)).astype(s.dtype)
                for p, s in zip(out[2:], (q_scale, k_scale))] \
        if norm else [None, None]
    return (dq, dk, *d_scales,
            None if rope is None else jnp.zeros_like(rope))


qk_prep.defvjp(_qk_prep_fwd, _qk_prep_bwd)
