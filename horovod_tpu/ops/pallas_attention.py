"""Pallas TPU flash attention (a forward and one backward kernel).

The reference has no attention kernels (it wraps framework models;
its native compute is limited to fusion-buffer/scale CUDA kernels,
/root/reference/horovod/common/ops/cuda/cuda_kernels.cu:48-260). This is a
TPU-first addition: the transformer family's hot op as Pallas kernels —
blockwise online-softmax attention (Flash Attention) tiled for MXU/VMEM:

* grid over (batch blocks, head blocks, query blocks; key-value blocks
  in the backward): a program handles a block of `gb x gh` consecutive
  (batch, head) instances side by side, each exactly as a program of its
  own would, with K/V streaming through VMEM in `block_k`-sized tiles
  (Q/dO in `block_q`-sized ones in the backward). One short-sequence
  instance is a chain of dependent steps (matrix product, row maximum,
  exponential, row sum, matrix product) whose latencies leave the units
  idle; independent instances in one loop body fill them.
  `_instances_per_program` picks the block from the shapes and the
  dtype: the largest of 16 instances at most whose work stays where more
  instances still pay and whose charge fits the kernel's VMEM budget —
  16 heads at T=128, 4 at T=512, 2 at T=1024 (4 in the backward). The
  arrays stay [B, H, T, D]: seen as [B·H, T, D] the kernels ran the
  same, but the compiler laid the step around them out differently and
  lost more than they gained. Trace-time gauges
  `hvd_flash_instances_per_program` / `hvd_flash_programs_per_call` say
  what each kernel got;
* causal masking on *global* positions, so sequence-parallel callers
  (ring attention) pass `query_offset`/`key_offset` and reuse the same
  kernel for off-diagonal blocks. A tile pays for the mask only where
  the mask can be false in it: `_tile_ranges` classes each program's
  tiles from the static arguments and `program_id` — wholly above the
  diagonal: not run; wholly at or under it with no padded key: the
  unmasked body a non-causal call runs; crossed by the diagonal or
  holding the padded tail (the last kv tile): masked, by one compare
  and one select (the forward a second only where a row can have seen
  no key yet). A class that is one tile in every program, as the
  diagonal tile of square blocks is, runs without a loop. Gauges
  `hvd_flash_tiles_per_call` / `hvd_flash_boundary_tiles_per_call`
  count a call's tiles and the masked ones;
* a block-diffusion mask (`diffusion_block`) is one more classification
  in `_tile_ranges` beside causal: over the 2T positions [noisy ; clean]
  a noisy q tile runs the noisy kv tiles of its rows' blocks (masked),
  the clean kv tiles wholly before them (unmasked) and those its first
  block crosses (masked); a clean q tile runs the clean tiles
  block-causally and no noisy one: T^2 + T*b of the 4T^2 pairs;
* a sliding window (`window` = w: query q sees key k where
  0 <= q - k < w) is a fourth classification (`_window_ranges`): the
  tiles wholly before the window are not run, those either edge crosses
  are masked, the rest run the unmasked body; at T = 8,192, w = 2,048
  and tiles of 512 a forward call runs 70 tiles where the diagonal alone
  runs 136. Gauges `hvd_flash_window` /
  `hvd_flash_window_tiles_per_call` /
  `hvd_flash_window_causal_tiles_per_call` say what a windowed call got;
* key-value heads may be fewer than query heads: both kernels read the
  head `h // (heads / kv_heads)` through their block maps, the backward
  writes one dk, dv partial a query head and the group's are summed
  outside, so no K or V repeated to every query head exists in HBM;
* f32 accumulators over bf16 inputs (MXU-native mixed precision);
* the forward emits per-row logsumexp; the backward is ONE more flash
  kernel (since PR 34; two before, which each rebuilt every tile): a
  program owns a kv block, streams the Q/dO tiles, rebuilds each
  probability tile from (q, k, lse) once and makes dV, dK and dQ from
  it, five matrix products and one exponential pass a tile. dK and dV
  are the program's carries; dQ of all the instances' rows is summed in
  an f32 VMEM scratch that lives across the kv axis of the grid (stated
  sequential) and is rounded once, at the last kv block. The attention
  matrix is never materialized in HBM in either direction, so
  training-time HBM traffic stays O(T·D) instead of O(T²).

Compiled by Mosaic on TPU; `interpret=True` only on the CPU test mesh, which
runs the same kernel bodies (ops/_pallas.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import scopes
from ._pallas import interpret

NEG_INF = -1e30


def _reference_attention(q, k, v, causal, scale, query_offset, key_offset):
    """Plain-jnp attention used as the numerics oracle in tests.
    [B, H, Tq, D] x [B, H, Tk, D]."""
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        qpos = query_offset + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape[-2:], 0
        )
        kpos = key_offset + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape[-2:], 1
        )
        logits = jnp.where(qpos[None, None] >= kpos[None, None],
                           logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))


def _block_of(positions, block: int):
    """positions // block for non-negative int32 positions."""
    if block & (block - 1) == 0:
        return lax.shift_right_logical(positions, block.bit_length() - 1)
    return lax.div(positions, jnp.int32(block))


def _diffusion_tile_mask(block_q, block_k, q_base, k_base, half, block):
    """The block-diffusion mask of one [block_q, block_k] tile that lies
    in one half of the 2·`half` positions on either side (`half` is a
    multiple of both block sizes). With blk(i) = (i mod half) // block, a
    row sees a column iff both are noisy and blk is equal, or the column
    is clean and its blk is less than the row's, or equal to it too where
    the row is clean. A clean row sees no noisy column; `_tile_ranges`
    runs no such tile."""
    q_clean, k_clean = q_base // half, k_base // half  # 0 or 1, scalars
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    ahead = (_block_of(rows + (q_base - q_clean * half), block)
             - _block_of(cols + (k_base - k_clean * half), block))
    # noisy keys: the row's own block, 0 <= ahead <= 0; clean keys: a
    # block before the row's, or the row's too for a clean row,
    # 1 - q_clean <= ahead. As two compares against scalars (Mosaic
    # selects no vector of booleans by a scalar)
    least = k_clean * (1 - q_clean)
    most = k_clean * (2 * half)
    return jnp.logical_and(ahead >= least, ahead <= most)


def _tile_mask(block_q, block_k, q_base, k_base, *, causal, q_offset,
               k_offset, kv_len, padded, diffusion=None, window=0):
    """Validity mask for one [block_q, block_k] logits tile that
    `_tile_ranges` calls masked: the diagonal crosses it (`causal`), an
    edge of the `window` does, or it holds padded keys (`padded`, static:
    kv_len is less than the padded length), so at least one of them is
    set; or, under `diffusion` (half, block), the block-diffusion mask
    of the tile.

    `q_base`/`k_base` are the tile's local starting rows/cols; global
    positions add the caller's sequence offsets (ring attention). Row r
    sees column c iff q_offset + q_base + r >= k_offset + k_base + c: one
    compare of the iota difference r - c, the same in every tile, against
    a scalar; under a window of w also iff that difference of positions
    is less than w, a second compare of the same iota difference."""
    if diffusion:
        return _diffusion_tile_mask(block_q, block_k, q_base, k_base,
                                    *diffusion)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = None
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        ahead = rows - cols
        behind = (k_offset + k_base) - (q_offset + q_base)
        mask = ahead >= behind
        if window:
            mask = jnp.logical_and(mask, ahead < behind + window)
    if padded:
        real = cols < kv_len - k_base
        mask = real if mask is None else jnp.logical_and(mask, real)
    return mask


def _dot_nt(a, b):
    """a[m, d] · b[n, d]ᵀ → [m, n] without materializing the transpose
    (contract the last dims; Mosaic feeds the MXU directly)."""
    return lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dot_tn(a, b):
    """a[m, n]ᵀ · b[m, d] → [n, d] without materializing the transpose."""
    return lax.dot_general(
        a, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _clip(x, lo, hi):
    """`jnp.clip`, and plain `min`/`max` where nothing is traced: the
    tiles are counted at trace time with the arithmetic the kernels
    run."""
    if any(isinstance(a, jax.Array) for a in (x, lo, hi)):
        return jnp.clip(x, lo, hi)
    return min(max(x, lo), hi)


def _diffusion_ranges(over, base, block_q, block_k, half, block):
    """`_tile_ranges` under the block-diffusion mask over 2·`half`
    positions [noisy ; clean], `half` a multiple of both block sizes,
    blk(i) = (i mod half) // `block`. Arithmetic that holds for a traced
    `base` and for a Python int alike.

    `over == "kv"`, a q block with rows of blocks first..last:
      * noisy: the noisy kv tiles that hold a key of those blocks, all
        called masked (where `block` spans whole tiles the mask is all
        true there: rare, and only a select lost);
      * the clean kv tiles: unmasked while every key's block is before
        the first row's (or is it, for a clean q block), then masked up
        to the last row's block.
    `over == "q"`, a kv block: noisy, the noisy q tiles of its keys'
    blocks, masked; clean, the noisy q tiles from the first that has a
    row past its first key's block, masked until every row is past its
    last key's, then the clean q tiles the same way with a key's own
    block seen too. The masked noisy q tiles are one range for both
    kinds of kv block: one tile in every program where `block` divides
    equal tiles."""
    def ceil_div(a, b):
        return -(-a // b)

    clean = base // half  # 0 for a noisy block, 1 for a clean one
    start = base - clean * half
    if over == "kv":
        n = half // block_k
        first, last = start // block, (start + block_q - 1) // block
        same_lo = (first * block) // block_k
        same_hi = _clip((last * block + block - 1) // block_k + 1, 0, n)
        unmasked = _clip(((first + clean) * block) // block_k, 0, n)
        limit = _clip(ceil_div((last + clean) * block, block_k),
                      unmasked, n)
        return [(same_lo, same_lo + (1 - clean) * (same_hi - same_lo), True),
                (n, n + unmasked, False), (n + unmasked, n + limit, True)]
    n = half // block_q
    first, last = start // block, (start + block_k - 1) // block
    same_lo = (first * block) // block_q
    same_hi = _clip((last * block + block - 1) // block_q + 1, 0, n)
    # of a clean kv block: the first q tile with a row that sees its
    # first key, and the first whose every row sees its last, among the
    # noisy q tiles (a row sees the blocks before its own) and among the
    # clean ones (and its own)
    noisy_lo = _clip(((first + 1) * block) // block_q, 0, n)
    noisy_all = _clip(ceil_div((last + 1) * block, block_q), noisy_lo, n)
    clean_lo = _clip((first * block) // block_q, 0, n)
    clean_all = _clip(ceil_div(last * block, block_q), clean_lo, n)
    return [(same_lo + clean * (noisy_lo - same_lo),
             same_hi + clean * (noisy_all - same_hi), True),
            (noisy_all, noisy_all + clean * (n - noisy_all), False),
            (n + clean_lo, n + clean_lo + clean * (clean_all - clean_lo),
             True),
            (n + clean_all, n + clean_all + clean * (n - clean_all), False)]


def _window_ranges(over, base, block_q, block_k, num_tiles, *, window,
                   q_offset, k_offset, kv_len, padded):
    """`_tile_ranges` under a causal mask with a window of `window`
    positions: row q sees key k iff 0 <= q - k < window, on global
    positions. Arithmetic that holds for a traced `base` and for a
    Python int alike. Three ranges, in the order of the tiles: masked
    (the window's far edge crosses them), unmasked, masked (the diagonal
    crosses them, or they hold padded keys); the tile of rows
    [q0, q0 + block_q) and columns [k0, k0 + block_k), with s =
    q_offset - k_offset,
      * runs      iff its last row sees its first key under the diagonal,
                  s + q0 + block_q - 1 >= k0, and its first row sees its
                  last key inside the window,
                  s + q0 - (k0 + block_k - 1) < window;
      * is masked iff its first row does not see its last key under the
                  diagonal, s + q0 < k0 + block_k - 1, or its last row
                  does not see its first key inside the window,
                  s + q0 + block_q - 1 - k0 >= window, or it holds a
                  padded key.
    A window narrower than a tile leaves no unmasked one."""
    shift = q_offset - k_offset
    if over == "kv":
        whole = kv_len // block_k  # leading kv tiles with no padded key
        limit = _clip((shift + base + block_q - 1) // block_k + 1,
                      0, num_tiles)
        first = _clip((shift + base - window + 1) // block_k, 0, limit)
        inside = _clip((shift + base + block_q - 1 - window) // block_k + 1,
                       first, limit)
        under = _clip((shift + base + 1) // block_k, 0, whole)
        under = _clip(under, inside, limit)
        return [(first, inside, True), (inside, under, False),
                (under, limit, True)]
    first = _clip((base - shift) // block_q, 0, num_tiles)
    # the q tiles past the last whose first row sees the block's last
    # key inside the window
    limit = _clip((window + base + block_k - 2 - shift) // block_q + 1,
                  first, num_tiles)
    # ceil((k0 + block_k - 1 - shift) / block_q)
    under = _clip(-((shift - base - block_k + 1) // block_q), first, limit)
    inside = _clip((window + base - shift) // block_q, under, limit)
    if padded:  # the kv block with the padded keys: every tile masked
        under = _clip(under, limit * (base + block_k > kv_len), limit)
        inside = _clip(inside, under, limit)
    return [(first, under, True), (under, inside, False),
            (inside, limit, True)]


def _tile_ranges(over, base, block_q, block_k, num_tiles, *, causal,
                 q_offset, k_offset, kv_len, padded, diffusion=None,
                 window=0):
    """The tiles one program runs, in the order it runs them, as
    `(lo, hi, masked)` ranges of tile indices. `over == "kv"`: the
    program owns the q block at local row `base` and streams the kv tiles
    (the forward); `over == "q"`: it owns the kv block at local column
    `base` and streams the q tiles (the backward). `base` is traced (from
    `program_id`) or a Python int, `num_tiles` the padded streamed length
    in tiles, `padded` whether kv_len is less than the padded key length.

    On *global* positions the tile of rows [q0, q0 + block_q) and columns
    [k0, k0 + block_k)
      * runs      iff its last row sees its first key, or nothing is
                  causal: q_offset + q0 + block_q - 1 >= k_offset + k0;
      * is masked iff its first row does not see its last key:
                  q_offset + q0 < k_offset + k0 + block_k - 1,
                  or it holds a padded key: k0 + block_k > kv_len.
    In an unmasked tile the mask would be all true, so it runs the body a
    non-causal, unpadded call runs. Both kernels take their ranges
    from here, so forward and backward can never cover different tiles,
    and the gauges count them from here. Two ranges, either of which may
    be empty; under `diffusion` (half, block) the three or four of
    `_diffusion_ranges`, under a `window` the three of `_window_ranges`."""
    if diffusion:
        return _diffusion_ranges(over, base, block_q, block_k, *diffusion)
    if window:
        return _window_ranges(over, base, block_q, block_k, num_tiles,
                              window=window, q_offset=q_offset,
                              k_offset=k_offset, kv_len=kv_len,
                              padded=padded)
    shift = q_offset - k_offset
    if over == "kv":
        whole = kv_len // block_k  # leading kv tiles with no padded key
        if causal:
            limit = _clip((shift + base + block_q - 1) // block_k + 1,
                          0, num_tiles)
            unmasked = _clip((shift + base + 1) // block_k, 0, whole)
            ranges = [(0, unmasked, False), (unmasked, limit, True)]
        else:
            ranges = [(0, whole, False), (whole, num_tiles, True)]
    else:
        first, unmasked = 0, 0
        if causal:
            first = _clip((base - shift) // block_q, 0, num_tiles)
            # ceil((k0 + block_k - 1 - shift) / block_q)
            unmasked = _clip(-((shift - base - block_k + 1) // block_q),
                             first, num_tiles)
        if padded:  # the kv block with the padded keys: every tile masked
            unmasked = _clip(unmasked, num_tiles * (base + block_k > kv_len),
                             num_tiles)
        ranges = [(first, unmasked, True), (unmasked, num_tiles, False)]
    return ranges


def _every_program(over, own_blocks, block_q, block_k, num_tiles,
                   **geometry):
    """`_tile_ranges` of each of the `own_blocks` programs of one (batch,
    head) instance, on Python ints: what is static about them."""
    own_rows = block_q if over == "kv" else block_k
    return [_tile_ranges(over, j * own_rows, block_q, block_k, num_tiles,
                         **geometry) for j in range(own_blocks)]


def _trips(every):
    """For each of the ranges, the set of lengths it has over an
    instance's programs (`_every_program`): `_run_instances` leaves out a
    range that is empty in every program and does not loop over one that
    is one tile in every program."""
    return tuple(frozenset(r[n][1] - r[n][0] for r in every)
                 for n in range(len(every[0])))


def _count_tiles(every):
    """(tiles, masked tiles) one (batch, head) instance runs."""
    tiles = sum(hi - lo for r in every for lo, hi, _ in r)
    return tiles, sum(hi - lo for r in every for lo, hi, masked in r
                      if masked)


def _rows_may_see_no_key(*, causal, q_offset, k_offset, diffusion=None,
                         block_q=None, block_k=None, window=0, **_):
    """Whether a row can come to a tile with every key it has met so far
    masked, that tile's included. Only where queries start before the
    keys: otherwise every row sees key 0 (never a padded one) in tile 0,
    the first it runs, and from then on its running maximum is a score:
    a masked lane's `exp(NEG_INF - m)` is exactly 0 without a select.
    Under a block-diffusion mask the first tile a q block runs holds a
    key of every row's own block (a noisy row's own noisy key, a clean
    row's clean one, or clean keys before it) where the blocks are equal
    and whole diffusion blocks or whole parts of one; otherwise a row
    may wait for its block's tile. Under a window the first tile a q
    block runs is the one its FIRST row's window begins in, and a later
    row's window may begin past that tile's end."""
    if diffusion:
        block = diffusion[1]
        return block_q != block_k or (block_q % block and block % block_q)
    if window:
        return True
    return causal and q_offset < k_offset


def _kv_of(q_ref, k_ref):
    """Maps a program's instance (batch, query head) to its place in the
    program's K/V block, which holds one head for each
    `query heads / kv heads` of the block's query heads (all of them
    where the counts are equal)."""
    heads_per_kv = q_ref.shape[1] // k_ref.shape[1]
    return lambda i: (i[0], i[1] // heads_per_kv)


def _instances(gb, gh):
    """The (batch, head) places of a program's block of instances."""
    return [(b, h) for b in range(gb) for h in range(gh)]


def _run_instances(gb, gh, ranges, trips, start, tile, finish, mask):
    """The loops around one program's `gb x gh` (batch, head) instances.
    For each instance `i = (batch, head)` of the block: `fixed, carry =
    start(i)`; for every range `(lo, hi, masked)` of `ranges`
    (`_tile_ranges`), in order, and every tile `t` in [lo, hi) `carry =
    tile(i, t, fixed, carry, m)`; then `finish(i, fixed, carry)`. `m` is
    `mask(t)`, built once a tile for all the instances, in a masked range
    and None in an unmasked one: the ranges share the carries, so only
    the tiles the mask can be false in pay for it.

    By `trips` (`_trips`) a range that is empty in every program is left
    out, and one that is one tile in every program (the diagonal tile of
    square blocks, the only tile of a sequence of one block) runs
    as straight-line code between `start` and the next loop or `finish`:
    a loop whose trip count the program computes costs 0.2 to 0.6 us
    each time it is entered, more with more carries (PERF.md section 6,
    PR 28), which is more than the mask it would save.

    The instances go through a tile side by side, as straight-line code
    in one loop body: their chains of matrix product, row reduction and
    exponential do not depend on one another, so the scheduler fills
    one's latencies with another's work (a loop over the instances does
    not: PERF.md section 6, PR 25). No instance's own operations or
    their order change, so results are the same bit for bit however
    many there are, and one is the kernel of one instance a program."""
    instances = _instances(gb, gh)
    fixed, carries = zip(*(start(i) for i in instances))

    for (lo, hi, masked), lengths in zip(ranges, trips):
        def body(t, carries, masked=masked):
            m = mask(t) if masked else None
            return tuple(tile(i, t, f, carry, m)
                         for i, f, carry in zip(instances, fixed, carries))

        if lengths == {1}:
            carries = body(lo, carries)
        elif lengths != {0}:
            carries = lax.fori_loop(lo, hi, body, tuple(carries))
    for i, f, carry in zip(instances, fixed, carries):
        finish(i, f, carry)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                      scale: float, geometry: dict, trips: tuple):
    """One (gb x gh instances, q-block) program: for each instance stream
    K/V tiles, online softmax.

    q_ref: [gb, gh, block_q, D]; k_ref/v_ref: [gb, gh, Tk_padded, D];
    o_ref: [gb, gh, block_q, D]; lse_ref: [gb, gh, 1, block_q] f32 per-row
    logsumexp of the scaled logits (the backward kernel rebuilds P tiles
    from it). `geometry` is what `_tile_ranges` takes, `trips` what
    `_trips` says of its ranges."""
    gb, gh, block_q, d = q_ref.shape
    q_base = pl.program_id(2) * block_q
    # a tile pays for the mask (compare + select on the VPU) only where
    # the mask can be false in it; non-causal and unpadded (the
    # BERT/encoder path) none does
    ranges = _tile_ranges("kv", q_base, block_q, block_k,
                          k_ref.shape[2] // block_k, **geometry)
    empty_rows = _rows_may_see_no_key(block_q=block_q, block_k=block_k,
                                      **geometry)
    kv = _kv_of(q_ref, k_ref)

    def mask(kb):
        return _tile_mask(block_q, block_k, q_base, kb * block_k, **geometry)

    def start(i):
        # keep matmul inputs in the model dtype (bf16 → bf16 MXU path)
        # with f32 accumulation via preferred_element_type; scale folds
        # into q
        q = (q_ref[i].astype(jnp.float32) * scale).astype(q_ref.dtype)
        acc0 = jnp.zeros((block_q, d), jnp.float32)
        m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q,), jnp.float32)
        return q, (acc0, m0, l0)

    def tile(i, kb, q, carry, mask):
        acc, m_prev, l_prev = carry
        k_tile = k_ref[(*kv(i), pl.ds(kb * block_k, block_k))]
        v_tile = v_ref[(*kv(i), pl.ds(kb * block_k, block_k))]
        s = _dot_nt(q, k_tile)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        # explicit mask on p: for a row with no key yet m_new == NEG_INF
        # and exp(s - m_new) would be exp(0) == 1, silently averaging V —
        # the masked entries must contribute exactly zero. Where no row
        # can be such a row, exp(NEG_INF - m_new) is that zero already
        if mask is not None and empty_rows:
            p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(
            p.astype(v_tile.dtype), v_tile,
            preferred_element_type=jnp.float32,
        )
        return acc, m_new, l_new

    def finish(i, q, carry):
        acc, m, l = carry
        # fully-masked rows (causal + offsets) have l == 0: output zeros,
        # and lse == NEG_INF so the backward rebuilds p == 0 for them too
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[i] = (acc / safe_l[:, None]).astype(o_ref.dtype)
        lse_ref[(*i, 0)] = jnp.where(l > 0, m + jnp.log(safe_l), NEG_INF)

    _run_instances(gb, gh, ranges, trips, start, tile, finish, mask)


def _flash_bwd_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dq_ref, *scratch, block_q: int,
                      scale: float, geometry: dict, trips: tuple):
    """dQ, dK and dV from one probability tile: the program owns one kv
    block of gb x gh instances and streams the Q/dO tiles.

    A tile: S = QKᵀ, P = exp(S − lse) (masked only in the ranges
    `_tile_ranges` marks), dP = dO·Vᵀ, dS = P ∘ (dP − Δ) with
    Δ = rowsum(dO ∘ O), once; then dV += Pᵀ·dO, dK += dSᵀ·Q and
    dQ[the tile's rows] += dS·K: five matrix products and one
    exponential pass. dK and dV are the program's carries, scaled, cast
    and written when its tiles are done. dQ of every row of the
    instances is `dq_acc`, f32 [gb, gh, Tq_p, D] in VMEM, which lives
    across the grid's kv axis (the last, stated sequential): zeroed at
    kv block 0, added to a tile, and scaled and cast into `dq_ref` once,
    at the last kv block: `dq_ref`'s block does not depend on the kv
    axis, so it goes to HBM when the instances change. A row's dQ is
    summed in f32 over all of its key tiles, in the order of the kv
    axis, before its one rounding; a row no tile visits (a padded query,
    one that sees no key) keeps the zero. Where the kv axis is one block
    the call has no scratch: a tile's dS·K is its rows' whole dQ and is
    scaled, cast and written at once, into a `dq_ref` zeroed first (the
    scratch's zeroing, adding and reading back were 6 to 8% of the
    kernel at T=128 and T=512).

    Padded q rows carry dO == 0 and Δ == 0, so they add exactly nothing
    to dK and dV. An instance is a (batch, query head): where key-value
    heads are fewer, `dk_ref`/`dv_ref` hold one partial a query head, of
    its kv head's block (`_kv_of`), and the caller sums a group's."""
    gb, gh, tq_p, d = q_ref.shape
    block_k = k_ref.shape[2]
    kv = _kv_of(q_ref, k_ref)
    j = pl.program_id(2)
    k_base = j * block_k
    # the K-padding mask guards this kv block's own padded rows; padded
    # q rows are harmless because their dO and Δ are zero — so the mask
    # is only needed for causal or padded-K tiles. q tiles entirely
    # above the diagonal (max(gq) < min(gk)) contribute nothing to this
    # kv block and do not run
    ranges = _tile_ranges("q", k_base, block_q, block_k, tq_p // block_q,
                          **geometry)

    dq_acc = scratch[0] if scratch else None
    if dq_acc is None:
        dq_ref[...] = jnp.zeros(dq_ref.shape, dq_ref.dtype)
    else:
        @pl.when(j == 0)
        def _():
            dq_acc[...] = jnp.zeros(dq_acc.shape, dq_acc.dtype)

    def mask(qb):
        return _tile_mask(block_q, block_k, qb * block_q, k_base, **geometry)

    def start(i):
        zeros = jnp.zeros((block_k, d), jnp.float32)
        return (k_ref[kv(i)], v_ref[kv(i)]), (zeros, zeros)

    def tile(i, qb, kv, carry, mask):
        k, v = kv
        dk_acc, dv_acc = carry
        rows = pl.ds(qb * block_q, block_q)
        q_tile = q_ref[(*i, rows)]
        do_tile = do_ref[(*i, rows)]
        lse_tile = lse_ref[(*i, 0, rows)]
        delta_tile = delta_ref[(*i, 0, rows)]
        qs = (q_tile.astype(jnp.float32) * scale).astype(q_tile.dtype)
        s = _dot_nt(qs, k)
        p = jnp.exp(s - lse_tile[:, None])
        if mask is not None:
            # masked lanes: exp(s - lse) is not 0, and may overflow to
            # +inf (lse == NEG_INF rows, which no unmasked tile holds);
            # the where() selects 0 before anything multiplies it
            p = jnp.where(mask, p, 0.0)
        dv_acc = dv_acc + _dot_tn(p.astype(do_tile.dtype), do_tile)
        dp = _dot_nt(do_tile, v)
        ds = (p * (dp - delta_tile[:, None])).astype(q_tile.dtype)
        dk_acc = dk_acc + _dot_tn(ds, q_tile)
        dq = jnp.dot(ds, k, preferred_element_type=jnp.float32)
        if dq_acc is None:
            dq_ref[(*i, rows)] = (dq * scale).astype(dq_ref.dtype)
        else:
            dq_acc[(*i, rows)] += dq
        return dk_acc, dv_acc

    def finish(i, kv, carry):
        dk_acc, dv_acc = carry
        dk_ref[i] = (dk_acc * scale).astype(dk_ref.dtype)
        dv_ref[i] = dv_acc.astype(dv_ref.dtype)

    _run_instances(gb, gh, ranges, trips, start, tile, finish, mask)

    if dq_acc is None:
        return

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        def cast(qb, _):
            rows = pl.ds(qb * block_q, block_q)
            for i in _instances(gb, gh):
                dq_ref[(*i, rows)] = (dq_acc[(*i, rows)] * scale).astype(
                    dq_ref.dtype)

        lax.fori_loop(0, tq_p // block_q, cast, None)


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# How many instances a program takes. Measured on a v5e (PERF.md section
# 6, PR 25 and PR 28: kernels alone, head width 64, bf16, time of the
# three kernels of then against one instance a program): what pays is
# instances side by side, and it pays by how little one instance does,
# its latencies being what the others fill — T=128 0.38x at 16 instances
# (0.43x at 8, 0.34x at 32), T=256 0.58x at 16, T=512 0.87x at 4 (0.85x
# at 8), T=512 causal 0.88x at 4; two tiles of 512 x 512 for a q block,
# T=1024: 0.986x at 2, unmasked or causal, and 4 causal did not fit the
# VMEM those kernels had. Fewer programs alone, 16 instances in a loop,
# gave 0.83x at T=128 and 0.99x at T=512.
#
# The one backward kernel (PR 34; `scripts/flash_program_sweep.py
# --kernels bwd --parent`, the benchmark cells' shapes, bf16, ms a call
# of the Mosaic call alone; every row the same bits as one instance a
# program and as the two kernels it replaced, whose dq + dkv at what
# they chose is the first column):
#
#   shape (B, H, T, D, mask)         two   one kernel, by instances
#   104, 16,  128,  64, none        0.686  1: 1.093  2: 0.752  4: 0.634
#                                          8: 0.598  16: 0.589  32: 0.589
#    26, 16,  512,  64, none        1.085  1: 0.933  2: 0.881  4: 0.845
#                                          8: 0.834
#    16, 16, 1024,  64, causal      2.471  1: 1.831  2: 1.760  4: 1.671
#     2, 32 over 4, 8192, 128, b=4 16.518  1: 11.904  2: 11.473
#
# and where the kv axis is one block, with a tile's dS·K written straight
# into dq and no scratch: T=128 0.552 at 8, 0.542 at 16, 0.543 at 32;
# T=512 0.834 at 2, 0.795 at 4, 0.784 at 8. More instances pay 3 to 5%
# a doubling as far as measured, so the backward takes twice the
# forward's work a program (4 at T=1024; the step compiles no longer for
# it, 44.4 s against 48.0 for a described v5e), and its VMEM is its own:
# 16 at T=128, 4 at T=512, 4 at T=1024, 1 at 8,192 positions (2 there
# would be a 36 MiB charge).
#
# Work: in units of one 128 x 128 score tile over all of a block's tiles,
# causal or not: a causal program runs half of them on average, and a
# masked tile is only one the diagonal crosses or one that holds padded
# keys, a compare and a select more. A program takes at most this much;
# an instance that is more than half of it goes alone.
_PROGRAM_TILE_UNITS = {"fwd": 64, "bwd": 128}
# Bodies side by side: each instance is one more body to trace and
# compile, and at head width 64 the VMEM charge stops at 16 to 20.
_MOST_INSTANCES = 16
# VMEM: the pipeline keeps two copies of every block of a program, so a
# program's blocks are charged twice, each padded to VMEM's tiles of 8
# sublanes of 32 bits by 128 lanes; a scratch is charged once. Compiled
# for a described v5e with no compiler option (16 MiB of scoped VMEM a
# kernel), the smallest charge Mosaic refused was 14.5 MiB and what it
# says it needs has run up to 1.1 times the charge (the kernels' own f32
# tiles are on the same stack), so half the 16 MiB is the forward's
# budget: it holds with the options any caller compiles with. The
# backward call states its own limit, twice its charge and
# `_VMEM_LIMIT_LEAST` at least, so its budget is all of the 16 MiB; an
# instance that is over it goes alone, and its call asks for what that
# takes (one instance at 8,192 positions and width 128 is charged
# 18 MiB).
_VMEM_BLOCK_BUDGET = {"fwd": 8 * 2**20, "bwd": 16 * 2**20}
_VMEM_LIMIT_LEAST = 16 * 2**20

# kernel -> (blocks of the program's own rows, blocks of all the rows of
# the other side, row statistics (lse, Δ), which side those follow, f32
# scratch arrays of all the rows of the other side)
_KERNEL_BLOCKS = {
    "fwd": (2, 2, 1, "own", 0),    # q, o | k, v | lse
    "bwd": (4, 3, 2, "other", 1),  # k, v, dk, dv | q, dO, dq | lse, Δ | dq
}


def _vmem_bytes(rows, cols, itemsize):
    """Bytes a [rows, cols] block takes in VMEM: tiles of 8 sublanes of
    32 bits (16 rows of bf16) by 128 lanes."""
    sublanes = 8 * (4 // itemsize)
    return (-(-rows // sublanes) * sublanes) * (-(-cols // 128) * 128) \
        * itemsize


def _vmem_charge(kernel, own_rows, other_rows, d, itemsize):
    """Bytes of VMEM one instance of `kernel` is charged: its blocks
    (`_KERNEL_BLOCKS`) twice, for the pipeline's two copies, and its
    scratch once."""
    n_own, n_other, n_stats, stats_side, n_scratch = _KERNEL_BLOCKS[kernel]
    stats_rows = own_rows if stats_side == "own" else other_rows
    return 2 * (
        n_own * _vmem_bytes(own_rows, d, itemsize)
        + n_other * _vmem_bytes(other_rows, d, itemsize)
        + n_stats * _vmem_bytes(1, stats_rows, 4)
    ) + n_scratch * _vmem_bytes(other_rows, d, 4)


def _instances_per_program(kernel, batch, heads, own_rows, other_rows, d,
                           itemsize, heads_per_kv=1):
    """The block `(gb, gh)` of consecutive (batch, head) instances one
    program of `kernel` ("fwd" or "bwd") handles, side by side: the
    largest that tiles [batch, heads] in rows (`gh` divides `heads`, or
    is all of them with `gb` dividing `batch`), of `_MOST_INSTANCES` at
    most, whose work stays within the kernel's `_PROGRAM_TILE_UNITS` and
    whose charge (`_vmem_charge`) fits its `_VMEM_BLOCK_BUDGET` — and
    (1, 1) when no larger one does, which is the kernel of one instance
    a program.

    `own_rows` is the program's own block (block_q; block_k for the
    backward), `other_rows` the padded length it streams over (Tk_p;
    Tq_p for the backward), `d` the head width the call has (a block is
    charged its lanes, 128 for 64 and for 128 alike). Where
    `heads_per_kv` query heads share a key-value head, a block of heads
    is whole groups or a whole part of one, so that its K/V block is
    whole heads. A function of shapes and dtype alone: short sequences
    get many instances a program, long ones one, with nothing to set."""
    per_instance = _vmem_charge(kernel, own_rows, other_rows, d, itemsize)
    units = -(-own_rows // 128) * -(-other_rows // 128)
    most = min(_MOST_INSTANCES, _PROGRAM_TILE_UNITS[kernel] // units,
               _VMEM_BLOCK_BUDGET[kernel] // per_instance)
    blocks = [(1, gh) for gh in range(1, heads + 1) if heads % gh == 0
              and (gh % heads_per_kv == 0 or heads_per_kv % gh == 0)]
    blocks += [(gb, heads) for gb in range(2, batch + 1) if batch % gb == 0]
    return max((blk for blk in blocks if blk[0] * blk[1] <= most),
               key=lambda blk: blk[0] * blk[1], default=(1, 1))


def _kv_block(gb, gh, heads_per_kv, rows, d, row_block):
    """The BlockSpec of a K or V array `[B, heads / heads_per_kv, T, D]`
    for programs of `gb x gh` (batch, query head) instances: the kv heads
    of the block's query heads, `rows` of them from row block
    `row_block(j)`. Equal head counts keep the map they always had."""
    if heads_per_kv == 1:
        return pl.BlockSpec((gb, gh, rows, d),
                            lambda b, h, j: (b, h, row_block(j), 0))
    gkh = max(gh // heads_per_kv, 1)
    return pl.BlockSpec(
        (gb, gkh, rows, d),
        lambda b, h, j: (b, (h * gh) // (heads_per_kv * gkh),
                         row_block(j), 0))


def _grid(kernel, shape, block_q, block_k, other_rows, itemsize, geometry,
          heads_per_kv=1):
    """`(gb, gh)`, the grid and the `trips` (`_trips`) of `kernel` over a
    padded [B, H, T, D] array of `shape` whose T is the program's own
    side, `other_rows` the padded length it streams over and `geometry`
    what `_tile_ranges` takes. Recorded in the trace-time gauges
    (`utils/metrics.trace_gauge`: all of it is static, so nothing runs
    in the step) by `kernel`, "fwd" or "bwd", the one backward kernel
    that makes dq, dk and dv: instances a program, programs a call, and
    the score tiles a call runs with those of them that pay for the
    mask (boundary tiles, which the causal diagonal crosses or which
    hold padded keys); for a call under a window, the window and the
    tiles the diagonal alone would have run beside those it runs."""
    from ..utils import metrics

    b, h, t, d = shape
    over = "q" if kernel == "bwd" else "kv"
    own_rows, other_block = \
        (block_k, block_q) if over == "q" else (block_q, block_k)
    gb, gh = _instances_per_program(kernel, b, h, own_rows, other_rows, d,
                                    itemsize, heads_per_kv)
    grid = (b // gb, h // gh, t // own_rows)
    every = _every_program(over, t // own_rows, block_q, block_k,
                           other_rows // other_block, **geometry)
    tiles, masked_tiles = _count_tiles(every)
    window, causal_tiles = geometry["window"], 0
    if window:
        # what the diagonal alone would have run of the same call
        causal_tiles = b * h * _count_tiles(_every_program(
            over, t // own_rows, block_q, block_k,
            other_rows // other_block, **{**geometry, "window": 0}))[0]
    gauges = [
        ("hvd_flash_instances_per_program",
         "(batch, head) instances one program of the flash kernel handles",
         gb * gh),
        ("hvd_flash_programs_per_call",
         "Programs in the grid of one call of the flash kernel",
         grid[0] * grid[1] * grid[2]),
        ("hvd_flash_tiles_per_call",
         "Score tiles one call of the flash kernel runs", b * h * tiles),
        ("hvd_flash_boundary_tiles_per_call",
         "Tiles of one call of the flash kernel that are masked",
         b * h * masked_tiles)]
    if window:
        # three gauges only a call under a window sets (a model's full
        # layers trace their calls beside them): the ratio of the last
        # two is what the window's tile range saves
        gauges += [
            ("hvd_flash_window",
             "Positions a query sees in the last traced flash call under "
             "a sliding window", window),
            ("hvd_flash_window_tiles_per_call",
             "Score tiles one flash call under a sliding window runs",
             b * h * tiles),
            ("hvd_flash_window_causal_tiles_per_call",
             "Score tiles the causal range alone would run of a flash "
             "call under a sliding window", causal_tiles)]
    for name, help, value in gauges:
        metrics.trace_gauge(name, help, value, kernel=kernel)
    return gb, gh, grid, _trips(every)


def _geometry(causal, query_offset, key_offset, kv_len, tk_p, diffusion,
              window=0):
    """What `_tile_ranges` and `_tile_mask` take of a call. `diffusion`
    is the block length b of a block-diffusion mask over the tk_p = 2T
    positions, which then stands in place of `causal`; 0 for none.
    `window` is the positions a causal query sees, itself among them; 0
    for all before it."""
    return dict(causal=causal and not diffusion, q_offset=query_offset,
                k_offset=key_offset, kv_len=kv_len, padded=kv_len < tk_p,
                diffusion=(tk_p // 2, diffusion) if diffusion else None,
                window=window)


def _flash_core(qq, kk, vv, kv_len, causal, scale, query_offset,
                key_offset, block_q, block_k, diffusion=0, window=0):
    """Padded [B, H, Tq_p, D] x [B, KH, Tk_p, D] → (out, lse); kv_len is
    the true (unpadded) key length. Grid (B / gb, H / gh, q-blocks): each
    program holds a block of gb x gh instances
    (`_instances_per_program`); 4-D arrays tile legally because (T, D)
    are the minor-most dims in this layout."""
    b, h, tq_p, d = qq.shape
    tk_p, heads_per_kv = kk.shape[2], h // kk.shape[1]
    geometry = _geometry(causal, query_offset, key_offset, kv_len, tk_p,
                         diffusion, window)
    gb, gh, grid, trips = _grid("fwd", qq.shape, block_q, block_k, tk_p,
                                qq.dtype.itemsize, geometry, heads_per_kv)
    kernel = functools.partial(_flash_fwd_kernel, block_k=block_k,
                               scale=scale, geometry=geometry, trips=trips)
    rows = pl.BlockSpec((gb, gh, block_q, d), lambda b, h, j: (b, h, j, 0))
    whole = _kv_block(gb, gh, heads_per_kv, tk_p, d, lambda j: 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[rows, whole, whole],
        out_specs=[
            rows,
            pl.BlockSpec((gb, gh, 1, block_q), lambda b, h, j: (b, h, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq_p, d), qq.dtype),
            jax.ShapeDtypeStruct((b, h, 1, tq_p), jnp.float32),
        ],
        interpret=interpret(),
        name=scopes.FLASH_FWD,
    )(qq, kk, vv)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash(q, k, v, causal, scale, query_offset, key_offset,
           block_q, block_k, diffusion, window):
    """[B, H, T, D] x [B, KH, T, D] flash attention core (bhtd
    layout)."""
    out, _ = _flash_fwd(q, k, v, causal, scale, query_offset, key_offset,
                        block_q, block_k, diffusion, window)
    return out


# Both directions are traced once for each shape and static argument and
# inlined where they are called (no scope of their own in `op_name`): a
# model's layers call them with the same shapes, and tracing a kernel
# body that holds several instances side by side costs as many times one
# instance's.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9, 10),
                   inline=True)
def _flash_fwd(q, k, v, causal, scale, query_offset, key_offset,
               block_q, block_k, diffusion, window):
    tq, tk = q.shape[2], k.shape[2]
    with jax.named_scope(scopes.ATTN_PREP):
        qq = _pad_to(q, 2, block_q)
        kk = _pad_to(k, 2, block_k)
        vv = _pad_to(v, 2, block_k)
    # the kernel call stands outside the scope (utils/scopes.py's rule)
    out_p, lse_p = _flash_core(
        qq, kk, vv, tk, causal=causal, scale=scale,
        query_offset=query_offset, key_offset=key_offset,
        block_q=block_q, block_k=block_k, diffusion=diffusion,
        window=window,
    )
    with jax.named_scope(scopes.ATTN_PREP):
        out = out_p[:, :, :tq]
        return out, (q, k, v, out, lse_p[:, :, :, :tq])


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6, 7),
                   inline=True)
def _flash_bwd(causal, scale, query_offset, key_offset, block_q, block_k,
               diffusion, window, residuals, g):
    q, k, v = residuals[:3]
    out, lse = residuals[3:]
    b, h, tq, d = q.shape
    kh, tk = k.shape[1:3]
    heads_per_kv = h // kh
    with jax.named_scope(scopes.ATTN_PREP):
        # Δ_i = Σ_d dO_i ∘ O_i — one cheap fused elementwise pass in XLA,
        # stored alongside lse as [B, H, 1, T]
        delta = jnp.sum(
            g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        )[:, :, None, :]
        qq = _pad_to(q, 2, block_q)
        do = _pad_to(g.astype(q.dtype), 2, block_q)
        lse_p = _pad_to(lse, 3, block_q)
        delta_p = _pad_to(delta, 3, block_q)
        kk = _pad_to(k, 2, block_k)
        vv = _pad_to(v, 2, block_k)
    tq_p, tk_p = qq.shape[2], kk.shape[2]
    itemsize = q.dtype.itemsize
    geometry = _geometry(causal, query_offset, key_offset, tk, tk_p,
                         diffusion, window)

    # an instance is a (batch, query head): fewer kv heads get one
    # partial dk, dv a query head, summed over each group below
    gb, gh, grid, trips = _grid("bwd", (b, h, tk_p, d), block_q, block_k,
                                tq_p, itemsize, geometry, heads_per_kv)
    kernel = functools.partial(_flash_bwd_kernel, block_q=block_q,
                               scale=scale, geometry=geometry, trips=trips)
    own = _kv_block(gb, gh, heads_per_kv, block_k, d, lambda j: j)
    rows = pl.BlockSpec((gb, gh, block_k, d), lambda b, h, j: (b, h, j, 0))
    stats = pl.BlockSpec((gb, gh, 1, tq_p), lambda b, h, j: (b, h, 0, 0))
    whole = pl.BlockSpec((gb, gh, tq_p, d), lambda b, h, j: (b, h, 0, 0))
    dk, dv, dq = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[own, own, whole, whole, stats, stats],
        out_specs=[rows, rows, whole],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tk_p, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, tk_p, d), v.dtype),
            jax.ShapeDtypeStruct((b, h, tq_p, d), q.dtype),
        ],
        # one kv block: a tile's dS·K is its rows' whole dq, no scratch
        scratch_shapes=[pltpu.VMEM((gb, gh, tq_p, d), jnp.float32)]
        if grid[2] > 1 else [],
        # dq is summed over the kv axis in the scratch: that axis runs
        # in order on one core. The call states the VMEM it needs: its
        # charge and as much again for the kernel's own f32 tiles, so
        # that it does not depend on what the step is compiled with
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(_VMEM_LIMIT_LEAST, 2 * gb * gh * _vmem_charge(
                "bwd", block_k, tq_p, d, itemsize))),
        interpret=interpret(),
        name=scopes.FLASH_BWD,
    )(kk, vv, qq, do, lse_p, delta_p)
    with jax.named_scope(scopes.ATTN_PREP):
        if heads_per_kv > 1:
            dk, dv = (
                jnp.sum(x.reshape(b, kh, heads_per_kv, tk_p, d), axis=2,
                        dtype=jnp.float32).astype(x.dtype)
                for x in (dk, dv))
        return dq[:, :, :tq], dk[:, :, :tk], dv[:, :, :tk]


_flash.defvjp(_flash_fwd, _flash_bwd)


def _pick_block(requested, t):
    """Block size for a sequence of length `t`: a single equal-to-array
    block when it fits (Mosaic allows non-multiple-of-8 blocks only when
    they equal the array dim), otherwise the tile-aligned candidate that
    minimizes padding waste — T=520 runs 128-blocks (120 rows padding),
    not 512-blocks (504 rows)."""
    if t <= requested:
        return max(t, 8)
    candidates = [b for b in (128, 256, 512) if b <= requested]
    if not candidates:
        return max(requested, 8)  # caller asked for a small custom block
    best = None
    for b in candidates:
        waste = (-t) % b
        if best is None or (waste, -b) < best[0]:
            best = ((waste, -b), b)
    return best[1]


def flash_attention_bhtd(
    q, k, v, *, causal: bool = True, scale: Optional[float] = None,
    query_offset: int = 0, key_offset: int = 0,
    block_q: int = 512, block_k: int = 512, diffusion_block: int = 0,
    window: int = 0,
):
    """Flash attention over [B, H, T, D] tensors — the kernels' native
    layout ((T, D) minor dims tile legally on TPU). Layout-aware callers
    skip the transpose pairs the [B, T, H, D] wrapper needs. GQA kv heads
    (fewer than q heads, matched on axis 1) are read by the kernels' block
    maps, a group of consecutive query heads to a kv head; they are never
    repeated. `diffusion_block` b > 0: the T = 2·half positions are
    [noisy ; clean] under the block-diffusion mask of block length b
    (`_diffusion_tile_mask`), in place of `causal`; `half` has to be
    whole tiles. `window` w > 0: a causal query sees the w latest
    positions, itself among them (key k of query q where 0 <= q - k < w,
    on global positions); a window that no query's distance to its first
    key reaches is the causal call, bit for bit."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} "
                         f"key-value heads")
    if window:
        if window < 0 or not causal or diffusion_block:
            raise ValueError(
                f"a window of {window} positions is a causal mask's: "
                f"causal {causal}, diffusion_block {diffusion_block}")
        if window > query_offset + q.shape[2] - 1 - key_offset:
            window = 0  # every key at or before a query is inside it
    if diffusion_block:
        half = q.shape[2] // 2
        if (q.shape[2] != k.shape[2] or q.shape[2] % 2
                or half % diffusion_block or query_offset or key_offset):
            raise ValueError(
                f"a block-diffusion mask of block {diffusion_block} takes "
                f"q and k of the same 2T positions, T a multiple of the "
                f"block, and no offsets; got {q.shape[2]} and {k.shape[2]}")
        # tiles lie in one half: one block size, picked for the half
        block_q = block_k = _pick_block(min(block_q, block_k), half)
        if half % block_q:
            raise ValueError(
                f"a block-diffusion mask over 2 x {half} positions needs "
                f"halves of whole tiles; the tile is {block_q}")
    else:
        block_q = _pick_block(block_q, q.shape[2])
        block_k = _pick_block(block_k, k.shape[2])
    return _flash(
        q, k, v, causal, float(scale),
        int(query_offset), int(key_offset), int(block_q), int(block_k),
        int(diffusion_block), int(window),
    )


def flash_attention_from_bhtd(q, k, v, **kwargs):
    """`flash_attention_bhtd` of q, k, v that are in the kernels'
    ``[B, H, T, D]`` already, with the result in the model's
    ``[B, T, H, D]``: for a caller that makes the kernels' layout itself
    (`ops/attention_prep.qk_prep` writes q and k there in the pass that
    norms and rotates them)."""
    out = flash_attention_bhtd(q, k, v, **kwargs)
    with jax.named_scope(scopes.ATTN_PREP):
        return out.transpose(0, 2, 1, 3)


def flash_attention(
    q, k, v, *, causal: bool = True, scale: Optional[float] = None,
    query_offset: int = 0, key_offset: int = 0,
    block_q: int = 512, block_k: int = 512, diffusion_block: int = 0,
    window: int = 0,
):
    """Flash attention over [B, T, H, D] tensors (model layout).

    kv heads may be fewer than q heads (GQA): the kernels read a group's
    shared head (no repeat), and dK/dV come back summed over the group.
    `query_offset`/`key_offset` shift the global positions used
    for the causal mask — the hook ring attention uses for rotated KV
    blocks. `diffusion_block`, `window`: see `flash_attention_bhtd`."""
    with jax.named_scope(scopes.ATTN_PREP):
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    return flash_attention_from_bhtd(
        q, k, v, causal=causal, scale=scale,
        query_offset=query_offset, key_offset=key_offset,
        block_q=block_q, block_k=block_k, diffusion_block=diffusion_block,
        window=window,
    )


def make_flash_attention_fn(causal: bool = True, block_q: int = 512,
                            block_k: int = 512, diffusion_block: int = 0):
    """attention_fn for models.Transformer (pluggable attention slot).
    block_q/block_k expose the kernel tile sizes for sweeps
    (HOROVOD_FLASH_BLOCK_Q/K env override them for quick experiments);
    `diffusion_block` is a block-diffusion model's block length (its
    `TransformerConfig.diffusion_block`), whose mask then stands in
    place of `causal`. A window is no argument here but one of the call
    (`fn(q, k, v, window=w)`, `fn.from_bhtd(q, k, v, window=w)`): a model
    hands every layer this one function, and its window layers differ
    from its full ones by that argument alone.

    The function takes q, k, v in the model's [B, T, H, D]; its
    attribute `from_bhtd` is the same attention of q, k, v that are in
    the kernels' [B, H, T, D] already (the result comes back in the
    model's layout either way). `models.transformer.Attention` reads
    the attribute as the offer of the kernels' layout: where it has per-
    head q/k norms or rope to run anyway, one pass writes q and k there
    (`ops/attention_prep.py`) and the three transposes in front of the
    kernels are not made. A function without the attribute (ring,
    Ulysses) is called with the model's layout as ever.

    Measured dead end for the record: projecting q/k/v straight into the
    kernels' bhtd layout via einsum (skipping the transpose pairs XLA
    materializes around each attention call) moved BERT-L throughput
    -1.5% — XLA pays the same relayout inside the projection einsum. So
    where nothing stands between the projections and the kernels (no
    q/k norms, no rope: the compiler folds the transposes into the
    products' fusions, `attn_prep_ms` 0.0-0.2 ms in the dense cells)
    the [B, T, H, D] wrapper + explicit transposes is the fast path;
    where norms or rope stand there the transposes are passes of their
    own over float32 copies, and `from_bhtd` behind the one pass is
    (PERF.md section 6, PR 42)."""
    import os

    kwargs = dict(
        causal=causal,
        block_q=int(os.environ.get("HOROVOD_FLASH_BLOCK_Q", block_q)),
        block_k=int(os.environ.get("HOROVOD_FLASH_BLOCK_K", block_k)),
        diffusion_block=diffusion_block)

    def fn(q, k, v, window: int = 0):
        return flash_attention(q, k, v, window=window, **kwargs)

    fn.from_bhtd = functools.partial(flash_attention_from_bhtd, **kwargs)
    return fn
