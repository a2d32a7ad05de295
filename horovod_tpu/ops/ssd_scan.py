"""The chunked state-space recurrence as one Pallas kernel a direction.

`models/mamba._scan_chunks` written as array operations builds, for
every head and chunk, a `chunk x chunk` float32 decay tile, writes it
to HBM, reads it back to multiply by `C B^T`, writes the product and
reads that into the matrix product: 512 MiB a layer at 64 heads and
8,192 positions, and the like again for the tiles' gradients. A tile is
256 KiB. Here it is made in VMEM, used and dropped:

* the grid is (batch, groups, chunks, head blocks), the last two walked
  in order. A group's `C B^T` is made once a chunk (at the first head
  block) and kept in scratch; the float32 state of all the group's
  heads is carried in scratch from chunk to chunk, as `lax.scan`'s
  carry is in the plain form. It is kept transposed, `[head blocks, N,
  heads x P]`: then every product with it takes B or C (`[L, N]`, the
  small operand) as the one the compiler transposes;
* forward (`SSD_SCAN_FWD`), a head: the decay tile from the cumulative
  sums (a row and a column of them; the mask stands before the
  exponential), `y = ((C B^T * decay) -> dtype) @ (dt x -> dtype) +
  exp(cum) * (C @ S_entering) + D x`. A head block together: `C @ S`,
  and the state's update `S = exp(cum_last) S + B^T @ (dt x *
  exp(cum_last - cum) -> dtype)`. It writes y and the state every chunk
  starts from (the backward's residual);
* backward (`SSD_SCAN_BWD`): the chunks in reverse carrying dS, the
  tiles rebuilt (never y) and rebuilt transposed, `[s, l]`, so that
  each of a head's three products feeds the MXU as it stands. It makes
  dx, d(dt), d(cum), a running sum of dD, and dB and dC summed over the
  group's heads in output blocks that stay in VMEM across the head
  blocks of a chunk;
* layouts are the mixer's: x and y `[B, T, H * P]` (a head block is
  whole lane tiles of it), B and C `[B, T, G * N]`. dt and the
  cumulative sums `[B, T, H]` float32 (2 MiB) are handed over as `[B,
  H, T]`, a head's chunk a row; the column the tile also needs is a
  small transpose in the kernel, and d(dt), d(cum) come back the same
  way.

What is float32 and what is rounded to the model's dtype is what
`_scan_chunks` states, place for place; in the backward every matrix
product's operands are the model's dtype and every sum float32. The
cumulative sums themselves are `models/mamba._log_decay_sums`'s, made
outside, and JAX differentiates them and `a * dt`.
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
# a[m, d] · b[n, d]^T and a[m, n]^T · b[m, d] without a transpose made
# by hand; of `_dot_tn`'s operands the compiler transposes the first,
# which is the smaller of the two wherever it is called here
from .pallas_attention import _dot_nt, _dot_tn

_LANES = 128
# Heads a program takes, and copies of a block of x (and of dy) its
# pipeline keeps in flight (1 or 2 is what this compiler takes).
# Measured on a v5e (`scripts/ssd_scan_sweep.py`; PERF.md section 6,
# PR 46; `granite_h_lm`'s layer: 64 heads of 64 over 8,192 positions,
# state 128, chunks of 256; ms a call of the forward kernel / of both
# kernels; the plain form takes 3.95 / 9.35 for all it runs):
#
#   heads   1 copy          2 copies
#   8       1.506 / 4.161   1.007 / 3.226
#   16      1.320 / 3.930   0.961 / 3.143
#
# 32 heads compile for 24 s and were not timed. More heads a program
# are fewer programs (256 a call at 8) and fewer C B^T-sized scratch
# round trips a head; the body, unrolled over the block, traces and
# compiles in proportion.
_HEADS_BLOCK = 16
_BUFFERS = 2
# What a call may charge VMEM with (`_vmem_charge`), and the least it
# states as its limit so that it does not depend on what the step is
# compiled with.
_VMEM_BUDGET = 40 * 2**20
_VMEM_LIMIT_LEAST = 32 * 2**20


def heads_block(heads_in_group: int, d_head: int):
    """Heads a program takes of a group's: whole sublane tiles of dt's
    rows (eight at least) and whole lane tiles of x, `_HEADS_BLOCK`
    where that divides the group; None where no block does."""
    for hb in (_HEADS_BLOCK, 8):
        if heads_in_group % hb == 0 and (hb * d_head) % _LANES == 0:
            return hb
    return None


def _vmem_charge(chunk, hb, d_head, d_state, heads_in_group, itemsize):
    """Bytes the backward call (the larger) holds: its blocks twice for
    the pipeline, its scratch, and the body's float32 values."""
    wide, f32 = chunk * hb * d_head, 4
    tile = chunk * chunk * f32
    blocks = (wide * (2 * itemsize + f32)  # x, dx, dy
              + hb * d_head * d_state * f32  # the entering state
              + 2 * chunk * d_state * (itemsize + f32)  # B, C, dB, dC
              + 4 * hb * chunk * f32)  # dt, cum and their gradients
    scratch = (heads_in_group * d_head * d_state * f32 + 2 * tile
               + 2 * wide * itemsize)
    values = 6 * tile + 3 * wide * f32
    return 2 * blocks + scratch + values


def supports(chunk: int, d_head: int, d_state: int, heads_in_group: int,
             dtype) -> bool:
    """Whether the kernels take this shape: a chunk and a state that are
    whole lane tiles, a head block that is (`heads_block`), a floating
    dtype, and what a call holds within the VMEM budget."""
    hb = heads_block(heads_in_group, d_head)
    return (chunk % _LANES == 0 and d_state % _LANES == 0
            and hb is not None
            and jnp.issubdtype(dtype, jnp.floating)
            and _vmem_charge(chunk, hb, d_head, d_state, heads_in_group,
                             jnp.dtype(dtype).itemsize) <= _VMEM_BUDGET)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _total(a):
    """The sum of a 2-D array, `[1, 1]`."""
    return jnp.sum(jnp.sum(a, axis=1, keepdims=True), axis=0, keepdims=True)


def _causal(chunk, later=0):
    """The tile's mask s <= l, with l along axis `later`."""
    return (lax.broadcasted_iota(jnp.int32, (chunk, chunk), later)
            >= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1 - later))


def _decay(causal, cols, rows, i):
    """Head i's tile exp(cum_l - cum_s) for s <= l, else 0: the mask
    stands before the exponential (above the diagonal the difference is
    positive and may overflow)."""
    return jnp.exp(jnp.where(causal, cols[:, i:i + 1] - rows[i:i + 1, :],
                             -jnp.inf))


def _through(last, hb, p):
    """exp(cum_last) of each head along the lanes of its state, `[1,
    hb·p]` from `last` `[1, hb]`: a head's value goes along the lanes
    here and along the sublanes where it multiplies the state (Mosaic
    broadcasts a `[1, 1]` along one of the two minor axes at a time)."""
    head = lax.broadcasted_iota(jnp.int32, (1, hb * p), 1) // p
    wide = jnp.zeros((1, hb * p), jnp.float32)
    for i in range(hb):
        wide = jnp.where(head == i, last[:, i:i + 1], wide)
    return jnp.exp(wide)


def _fwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, d_ref, y_ref,
                entering_ref, state, cb, w, *, hb, p):
    """Blocks: x, y [1, L, hb·p]; dt, cum [1, hb, L]; B, C [1, L, N]; D
    [1, hb·p] (a head's D along its lanes); entering [1, 1, N, hb·p].
    Scratch: state [head blocks, N, hb·p] float32 (a head's state
    transposed, so that only B and C are ever transposed for a
    product), cb [L, L] float32, w [L, hb·p] in the model's dtype."""
    k, j = pl.program_id(2), pl.program_id(3)
    dtype = x_ref.dtype
    chunk = x_ref.shape[1]
    b, c = b_ref[0], c_ref[0]

    @pl.when(k == 0)
    def _():
        state[j] = jnp.zeros(state.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _():
        cb[...] = _dot_nt(c, b)

    rows = cum_ref[0]  # a head's chunk along the lanes
    cols = rows.T  # [L, hb]: along the sublanes
    last = cols[chunk - 1:chunk, :]
    grown, to_end = jnp.exp(cols), jnp.exp(last - cols)
    dt = dt_ref[0].T
    entering = state[j]
    entering_ref[0, 0] = entering
    from_state = _dot(c, entering.astype(dtype))  # [L, hb·p]
    causal = _causal(chunk)
    for i in range(hb):
        at = slice(i * p, (i + 1) * p)
        x = x_ref[0, :, at].astype(jnp.float32)
        xdt = x * dt[:, i:i + 1]
        w[:, at] = (xdt * to_end[:, i:i + 1]).astype(dtype)
        y_ref[0, :, at] = _dot(
            (cb[...] * _decay(causal, cols, rows, i)).astype(dtype),
            xdt.astype(dtype)) + grown[:, i:i + 1] * from_state[:, at] \
            + d_ref[:, at] * x
    state[j] = _through(last, hb, p) * entering + _dot_tn(b, w[...])


def _bwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, d_ref, entering_ref,
                dy_ref, dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, dd_ref,
                d_state, cb, d_cb, w, dz, dd, *, hb, p):
    """Blocks as the forward's, with dy and dx as x, d(dt) and d(cum)
    as dt, dB and dC [1, L, N] float32 (they stay across a chunk's head
    blocks and gather the group's heads) and dD [1, 1, hb·p] (a head's
    along its lanes, summed over the chunks so far). Scratch: d_state
    as the forward's state (the gradient by the state a chunk ends
    with), cb and d_cb [L, L] float32 (C B^T and its gradient,
    transposed), w and dz [L, hb·p] in the model's dtype, dd [head
    blocks, 1, hb·p] float32."""
    k, j = pl.program_id(2), pl.program_id(3)
    dtype = x_ref.dtype
    chunk = x_ref.shape[1]
    f32 = jnp.float32
    b, c = b_ref[0], c_ref[0]

    @pl.when(k == 0)
    def _():
        d_state[j] = jnp.zeros(d_state.shape[1:], f32)
        dd[j] = jnp.zeros(dd.shape[1:], f32)

    @pl.when(j == 0)
    def _():
        cb[...] = _dot_nt(b, c)  # transposed: [s, l]
        d_cb[...] = jnp.zeros(d_cb.shape, f32)
        db_ref[...] = jnp.zeros(db_ref.shape, f32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, f32)

    rows = cum_ref[0]
    cols = rows.T
    last = cols[chunk - 1:chunk, :]
    grown, to_end = jnp.exp(cols), jnp.exp(last - cols)
    through = _through(last, hb, p)
    dt = dt_ref[0].T
    entering = entering_ref[0, 0]
    entering_low = entering.astype(dtype)
    d_ended = d_state[j]
    d_ended_low = d_ended.astype(dtype)
    from_state = _dot(c, entering_low)  # [L, hb·p]
    d_w = _dot(b, d_ended_low)  # [L, hb·p]: by (dt x * to_end)
    # by exp(cum_last) in the state's update, a head's along its lanes
    d_through = jnp.sum(d_ended * entering, axis=0, keepdims=True) * through
    # every tile here is the forward's transposed, [s, l]: then each
    # product a head makes feeds the MXU as it stands
    causal = _causal(chunk, later=1)
    lane = lax.broadcasted_iota(jnp.int32, (chunk, hb), 1)
    is_last = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    d_dt = jnp.zeros((chunk, hb), f32)
    d_cols = jnp.zeros((chunk, hb), f32)
    for i in range(hb):
        at = slice(i * p, (i + 1) * p)
        x = x_ref[0, :, at].astype(f32)
        xdt = x * dt[:, i:i + 1]
        dy = dy_ref[0, :, at]
        dy_low = dy.astype(dtype)
        decay = jnp.exp(jnp.where(
            causal, rows[i:i + 1, :] - cols[:, i:i + 1], -jnp.inf))
        masked = cb[...] * decay
        d_masked = _dot_nt(xdt.astype(dtype), dy_low)
        d_cb[...] += d_masked * decay
        d_seg = d_masked * masked  # by cum_l - cum_s, at [s, l]
        # a row of d(cum): a position's own row of the forward's tile
        dcum_ref[0, i:i + 1, :] = jnp.sum(d_seg, axis=0, keepdims=True)
        d_xdt = _dot(masked.astype(dtype), dy_low) \
            + d_w[:, at] * to_end[:, i:i + 1]
        dx_ref[0, :, at] = (d_xdt * dt[:, i:i + 1]
                            + d_ref[:, at] * dy).astype(dtype)
        dd[j, :, at] += jnp.sum(dy * x, axis=0, keepdims=True)
        dz[:, at] = (dy * grown[:, i:i + 1]).astype(dtype)
        w[:, at] = (xdt * to_end[:, i:i + 1]).astype(dtype)
        # and a column: the tile's column of a position, exp(cum) in
        # front of the entering state's part, exp(cum_last - cum) in
        # the state's update; cum_last is the chunk's last entry
        d_to_end = jnp.sum(d_w[:, at] * xdt, axis=1, keepdims=True) \
            * to_end[:, i:i + 1]
        d_last = _total(d_to_end) + jnp.sum(d_through[:, at], axis=1,
                                            keepdims=True)
        d_col = jnp.sum(dy * from_state[:, at], axis=1, keepdims=True) \
            * grown[:, i:i + 1] - jnp.sum(d_seg, axis=1, keepdims=True) \
            - d_to_end + jnp.where(is_last, d_last, 0.0)
        d_cols = jnp.where(lane == i, d_col, d_cols)
        d_dt = jnp.where(lane == i, jnp.sum(d_xdt * x, axis=1,
                                            keepdims=True), d_dt)
    ddt_ref[0] = d_dt.T
    dcum_ref[0] += d_cols.T
    dd_ref[0] = dd[j]
    dc_ref[0] += _dot_nt(dz[...], entering_low)
    db_ref[0] += _dot_nt(w[...], d_ended_low)
    d_state[j] = through * d_ended + _dot_tn(c, dz[...])

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        d_cb_low = d_cb[...].astype(dtype)
        dc_ref[0] += _dot_tn(d_cb_low, b)
        db_ref[0] += _dot(d_cb_low, c)


def _plan(x, dt, b, chunk, hb, buffers, reverse):
    """What the two calls share: a head's width, the grid, the block
    specs of an array like x `[B, T, H·P]` (read through `buffers`
    copies, and written), like dt's rows `[B, H, T]`, like B `[B, T,
    G·N]`, of D's row `[1, H·P]` and of the entering states `[B,
    chunks, N, H·P]`, the scratch both have, and the compiler's
    parameters."""
    bsz, t, h = dt.shape
    p = x.shape[2] // h
    groups, n = b.shape[2:]
    per_group = h // groups
    blocks, chunks = per_group // hb, t // chunk

    def at(k):
        return chunks - 1 - k if reverse else k

    def wide_spec(**mode):
        return pl.BlockSpec(
            (1, chunk, hb * p),
            lambda i, g, k, j: (i, at(k), g * blocks + j), **mode)

    wide = wide_spec()
    read = wide if buffers == 2 else wide_spec(
        pipeline_mode=pl.Buffered(buffers))
    rows = pl.BlockSpec(
        (1, hb, chunk), lambda i, g, k, j: (i, g * blocks + j, at(k)))
    shared = pl.BlockSpec((1, chunk, n), lambda i, g, k, j: (i, at(k), g))
    skip = pl.BlockSpec((1, hb * p), lambda i, g, k, j: (0, g * blocks + j))
    states = pl.BlockSpec(
        (1, 1, n, hb * p), lambda i, g, k, j: (i, at(k), 0, g * blocks + j))
    scratch = [pltpu.VMEM((blocks, n, hb * p), jnp.float32),
               pltpu.VMEM((chunk, chunk), jnp.float32)]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary",
                             "arbitrary"),
        vmem_limit_bytes=max(_VMEM_LIMIT_LEAST, _vmem_charge(
            chunk, hb, p, n, per_group, x.dtype.itemsize)))
    return (p, (bsz, groups, chunks, blocks), read, wide, rows, shared,
            skip, states, scratch, params)


def _operands(x, dt, cum, b, c, d):
    """The arrays as the kernels take them: dt and cum `[B, H, T]`, B
    and C `[B, T, G·N]`, D a head's along its lanes `[1, H·P]`."""
    bsz, t, width = x.shape
    return (x, dt.transpose(0, 2, 1), cum.transpose(0, 2, 1),
            b.reshape(bsz, t, -1), c.reshape(bsz, t, -1),
            jnp.repeat(d.astype(jnp.float32), width // d.shape[0])[None])


def _static(x, dt, b):
    """What the two calls are traced for besides their arguments'
    shapes: heads a program, copies of x a pipeline keeps, and whether
    the kernels run interpreted."""
    per_group = dt.shape[2] // b.shape[2]
    return (heads_block(per_group, x.shape[2] // dt.shape[2]), _BUFFERS,
            interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd_chunks(x, dt, cum, b, c, d, chunk):
    """The chunked recurrence with D x: x `[B, T, H·P]` (the layout the
    convolution leaves), dt and cum `[B, T, H]` float32 (cum: a · dt
    summed inclusively inside each chunk), b and c `[B, T, G, N]`, d
    `[H]`, T a multiple of `chunk`, the shape one that `supports` takes
    -> y `[B, T, H·P]` float32 (the layout the gate reads)."""
    return _ssd_fwd(x, dt, cum, b, c, d, chunk)[0]


def _ssd_fwd(x, dt, cum, b, c, d, chunk):
    y, entering = _forward(x, dt, cum, b, c, d, chunk, *_static(x, dt, b))
    return y, (x, dt, cum, b, c, d, entering)


def _ssd_bwd(chunk, residuals, dy):
    x, dt, _, b = residuals[:4]
    return _backward(*residuals, dy, chunk, *_static(x, dt, b))


# Both directions are traced once for each shape and static argument and
# inlined where they are called, as the flash calls are
# (`ops/pallas_attention.py`): a model's layers call them with the same
# shapes, and tracing a kernel body that holds a block of heads in line
# costs a second each time (nine layers and eight second runs of them:
# 12 s of a step's lowering otherwise; PERF.md section 6, PR 46). No
# scope is opened in either: the caller's (`models/mamba.Mamba2Mixer`
# opens `MAMBA_SCAN` around `ssd_scan`) reaches the forward rule and,
# carried by JAX to the call's transpose, the backward rule
# (`tests/test_step_scopes.py` holds that it does).
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9), inline=True)
def _forward(x, dt, cum, b, c, d, chunk, hb, buffers, interpreted):
    p, grid, read, wide, rows, shared, skip, states, scratch, params = \
        _plan(x, dt, b, chunk, hb, buffers, reverse=False)
    bsz, t, width = x.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, p=p),
        grid=grid,
        in_specs=[read, rows, rows, shared, shared, skip],
        out_specs=[wide, states],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, jnp.float32),
            jax.ShapeDtypeStruct((bsz, t // chunk, b.shape[3], width),
                                 jnp.float32)],
        scratch_shapes=scratch + [pltpu.VMEM((chunk, hb * p), x.dtype)],
        compiler_params=params,
        interpret=interpreted,
        name=scopes.SSD_SCAN_FWD,
    )(*_operands(x, dt, cum, b, c, d))


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11), inline=True)
def _backward(x, dt, cum, b, c, d, entering, dy, chunk, hb, buffers,
              interpreted):
    p, grid, read, wide, rows, shared, skip, states, scratch, params = \
        _plan(x, dt, b, chunk, hb, buffers, reverse=True)
    operands = _operands(x, dt, cum, b, c, d)
    bsz, _, width = x.shape
    low = pltpu.VMEM((chunk, hb * p), x.dtype)
    like_rows = jax.ShapeDtypeStruct(operands[1].shape, jnp.float32)
    like_shared = jax.ShapeDtypeStruct(operands[3].shape, jnp.float32)
    dx, d_dt, d_cum, db, dc, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p),
        grid=grid,
        in_specs=[read, rows, rows, shared, shared, skip, states, read],
        out_specs=[wide, rows, rows, shared, shared, pl.BlockSpec(
            (1, 1, hb * p), lambda i, g, k, j: (i, 0, g * grid[3] + j))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   like_rows, like_rows, like_shared, like_shared,
                   jax.ShapeDtypeStruct((bsz, 1, width), jnp.float32)],
        scratch_shapes=scratch + [
            pltpu.VMEM((chunk, chunk), jnp.float32), low, low,
            pltpu.VMEM((grid[3], 1, hb * p), jnp.float32)],
        compiler_params=params,
        interpret=interpreted,
        name=scopes.SSD_SCAN_BWD,
    )(*operands, entering, dy.astype(jnp.float32))
    return (dx, d_dt.transpose(0, 2, 1), d_cum.transpose(0, 2, 1),
            db.reshape(b.shape).astype(b.dtype),
            dc.reshape(c.shape).astype(c.dtype),
            dd.reshape(bsz, d.shape[0], -1).sum((0, 2)).astype(d.dtype))


ssd_chunks.defvjp(_ssd_fwd, _ssd_bwd)
