"""The routed experts' products as Pallas kernels of the repo's own.

`models/moe.RoutedMlp` sends rows that are ordered expert-major through
the matrix of the expert whose group they lie in. As `lax.ragged_dot`
behind `weights.astype(rows.dtype)` that is a compiler-made kernel at
under two fifths of the MXU's peak, and in front of every product a pass
that reads all the float32 experts and writes them again in the rows'
dtype (a custom call takes no cast into its fusion). Here it is three
kernels, forward and both gradients, that read the float32 experts in
place:

* the walk is a list of visits, made outside from `groups` and handed
  to the kernel as prefetched scalars: a visit is a row tile and a
  group that has rows in it, row tiles in order and a tile's groups in
  order. A tile that lies in one group is visited once; one that a
  boundary cuts is visited once a group, and a visit writes (or sums)
  its own group's rows alone. So any `groups` is exact: empty groups,
  groups that are no multiple of the tile, groups smaller than a tile.
  The number of visits is the grid's own (dynamic) extent. Inside a
  visit the tile is worked in pieces of `_PIECE` rows by a loop (one
  copy of a piece's product in the kernel's code), and a piece with no
  row of the group is skipped: a boundary costs a piece of work, not a
  tile;
* the product (`GROUPED_MATMUL_FWD`: gate, up, down; and with the weight
  transposed the gradient into the rows): a visit's blocks are the row
  tile over the whole contraction and the group's float32 matrix (all
  of it, or whole lane tiles of its output width where all does not
  fit). With a group's visits consecutive the matrix's block index
  repeats, and the pipeline fetches an expert once a group, not once a
  row tile. At a group's first visit the block is rounded into a VMEM
  scratch in the rows' dtype, exactly as `weights.astype(rows.dtype)`
  rounds, and every piece's product reads that. Rows past the last
  group's end are a group of their own with no matrix, and read zero as
  `ragged_dot`'s do;
* the gradient into the weights (`GROUPED_MATMUL_DW`): `rows^T x d_out`
  summed inside each group. The output block is the group's float32
  matrix, resident across the group's visits and summed into in place;
  every group is visited, an empty one to be zeroed. It leaves the
  kernel as float32 straight from the accumulator.

Precision is `ragged_dot`'s on the cast experts and no lower: operands
in the rows' dtype, float32 accumulation on the MXU, the product's
result in the rows' dtype.
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
from .pallas_attention import _dot_nt, _dot_tn

_LANES = 128
# Rows of a visit's tile (what the pipeline moves at a step): the first
# of these that divides the rows and whose blocks, whole experts, fit
# `_VMEM_BUDGET` in all three forms; the last with the experts in blocks
# of their width where none does. And rows of one product inside a tile
# (what a boundary between groups costs, and the kernel's code: the
# loop over a tile's pieces holds one product). Measured on a v5e
# (`scripts/grouped_matmul_sweep.py`; PERF.md section 6, PR 49, calls 1
# and 2; ms a call and share of the MXU's peak, 16 groups; product /
# gradient into the rows / gradient into the weights):
#
#   16,384 x 2,048 -> 768 (`sdar_bd_s4096`), rows in even groups
#   ragged_dot + cast  0.608 / 0.542 / 0.677  (43 / 48 / 39%)
#   megablox, no cast  0.326 / 0.370 / 0.423  (80 / 71 / 62%)
#   ours 256 x 256     0.412 / 0.440 / 0.410  (63 / 59 / 64%)
#   ours 512 x 128     0.390 / 0.439 / 0.384  (67 / 60 / 68%)
#   ours 512 x 256     0.386 / 0.434 / 0.377  (68 / 60 / 69%)
#   ours 1024 x 256    0.305 / 0.314 / 0.317  (86 / 83 / 83%)  chosen
#   ours 1024 x 512    0.301 / 0.310 / 0.315  (87 / 84 / 83%)
#   the same, rows in uneven groups (one empty; what the cell's step
#   runs: a share's seeded router is even over the chips, not over the
#   experts held here)
#   ragged_dot + cast  0.776 / 0.702 / 0.899  (34 / 37 / 29%)
#   megablox, no cast  0.450 / 0.422 / 0.552  (58 / 62 / 47%)
#   ours 1024 x 256    0.412 / 0.457 / 0.474  (63 / 57 / 55%)  chosen
#
#   8,192 x 2,048 -> 1,024 (`trinity_mini_s8192`), even | uneven
#   ragged_dot + cast  0.447 / 0.435 / 0.477 (39 / 40 / 37%) | 0.640 / 0.629 / 0.692
#   megablox, no cast  0.213 / 0.220 / refused (82 / 79%)    | 0.377 / 0.359 / refused
#   ours 256 x 256     0.348 / 0.367 / 0.347 (50 / 48 / 50%)
#   ours 512 x 256     0.266 / 0.273 / 0.293 (66 / 64 / 60%) | 0.366 / 0.394 / 0.410  chosen
#   ours 1024 x 256    refused / 0.274 / 0.297
#
# `megablox` is the installed jax's `gmm` / `tgmm` at (512, 1024, 1024)
# on a bf16 copy made beforehand: the copy it needs is 0.13 and 0.25 ms
# more. An expert's fetch (6.3 MB, 7.7 us) is issued during the last
# visit before it: with 1,024 rows a group in tiles of 512 every second
# visit waits for it, in tiles of 1,024 the one visit a group hides it.
# At 512 rows a group and experts of 8.4 MB the product moves 185 MB for
# 0.174 ms of MXU work and runs at 695 GB/s: the HBM's rate, whatever
# the tile (and 1,024 rows of it with a whole expert are more VMEM than
# the compiler gives: the budget refuses them). Pieces of 512 are 1%
# faster on even groups and 60% more code (0.74 MB a call against 0.47,
# sixty-six calls a step, all of it in `step_hbm_gib`). Not swept:
# pieces of 128 on uneven groups, where a boundary costs a piece.
_ROWS = (1024, 512)
_PIECE = 256
# What a call may charge VMEM with (`_charge`, which counts what it can
# name: the compiler needed more than 34 MiB where it counted 34), and
# the limit every call states, so that it does not depend on what the
# step is compiled with.
_VMEM_BUDGET = 32 * 2**20
_VMEM_LIMIT = 48 * 2**20


def _charge(rows, contraction, width, itemsize, dw=False):
    """Bytes a call holds for a tile of `rows` rows and one block of
    `width` output columns: its blocks twice for the pipeline, the
    product's rounded copy of the matrix, and the body's float32
    values."""
    f32 = 4
    blocks = 2 * (rows * (contraction + width) * itemsize
                  + contraction * width * f32)
    if dw:
        # a piece transposed, and a product's result beside the sum
        return (blocks + _PIECE * contraction * itemsize
                + contraction * width * f32)
    return blocks + contraction * width * itemsize + 2 * _PIECE * width * f32


def _forms(a, c):
    """(contraction, width, whether the weights' gradient) of the three
    forms of `[m, a] x [g, a, c]`."""
    return (a, c, False), (c, a, False), (a, c, True)


def _width_tile(rows, contraction, width, itemsize, dw=False):
    """The output columns a block takes: all of them, or the largest
    whole-lane-tile divisor of them that `_VMEM_BUDGET` holds; None
    where not even one lane tile does."""
    for parts in range(1, width // _LANES + 1):
        if width % (parts * _LANES) == 0 and _charge(
                rows, contraction, width // parts, itemsize,
                dw) <= _VMEM_BUDGET:
            return width // parts
    return None


def _row_tile(m, a, c, itemsize):
    """Rows of a visit's tile for all three forms of `[m, a] x [g, a,
    c]` (the same in `a` and `c`): the first of `_ROWS` that divides `m`
    and holds whole experts within the budget; else the last of them
    with the experts in blocks of their width; None where `m` is no
    whole number of those or not even a lane tile of an expert fits."""
    if m <= 0:
        return None
    for rows in _ROWS:
        if m % rows == 0 and all(
                _charge(rows, k, n, itemsize, dw) <= _VMEM_BUDGET
                for k, n, dw in _forms(a, c)):
            return rows
    rows = _ROWS[-1]
    if m % rows or None in [
            _width_tile(rows, k, n, itemsize, dw)
            for k, n, dw in _forms(a, c)]:
        return None
    return rows


def supports(m: int, a: int, c: int, dtype) -> bool:
    """Whether the kernels take `[m, a] x [g, a, c]`: both widths whole
    lane tiles, `m` a whole number of row tiles, a floating dtype, all
    three forms within the VMEM budget (`_row_tile`), and a backend
    that compiles or interprets them."""
    return bool(
        a % _LANES == 0 and c % _LANES == 0
        and jnp.issubdtype(dtype, jnp.floating)
        and jax.default_backend() in ("tpu", "cpu")
        and _row_tile(m, a, c, jnp.dtype(dtype).itemsize))


def _sums(x):
    """`cumsum` of a short vector as one comparison and one sum, which
    the compiler fuses with what follows (a scan is a loop to it)."""
    i = jnp.arange(x.shape[0])
    return jnp.sum(jnp.where(i[None] <= i[:, None], x[None], 0), axis=1)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _visits(groups, m, rows, every_group):
    """The walk over `m` rows in groups of `groups` rows: (bounds of the
    groups' rows, the group and the row tile (of `rows` rows) of each
    visit, how many visits there are). A group is visited once for each
    row tile it has rows in. With `every_group` an empty one is visited
    once too (at any tile: no row of it is there); without, the rows
    past the last group's end are one more group, so that every row is
    some visit's. Comparisons and sums over `[visits, groups]` alone,
    no gather, search or loop; and a function of its own to the step
    (not inlined: a step's two hundred calls trace and lower one copy
    of these forty operations, not two hundred)."""
    groups = groups.astype(jnp.int32)
    if not every_group:
        groups = jnp.concatenate(
            [groups, m - jnp.sum(groups, keepdims=True)])
    g, tiles = groups.shape[0], m // rows
    ends = _sums(groups)
    first = jnp.minimum((ends - groups) // rows, tiles - 1)
    touched = jnp.where(groups > 0, -(-ends // rows) - first,
                        int(every_group))
    last_visit = _sums(touched)
    visit = jnp.arange(tiles + g)
    group = jnp.minimum(
        jnp.sum(visit[:, None] >= last_visit[None], axis=1), g - 1)
    # the group's first tile, and the visit's place among the group's
    of_group = group[:, None] == jnp.arange(g)[None]
    tile = jnp.minimum(visit + jnp.sum(jnp.where(
        of_group, (first - last_visit + touched)[None], 0), axis=1),
        tiles - 1)
    bounds = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return tuple(x.astype(jnp.int32)
                 for x in (bounds, group, tile, last_visit[-1]))


def _first_visit(group_ref):
    """Whether this visit is the first of its group."""
    visit = pl.program_id(1)
    return (visit == 0) | (
        group_ref[jnp.maximum(visit - 1, 0)] != group_ref[visit])


def _pieces(bounds_ref, group_ref, tile_ref, rows, body):
    """`body(rows, whole, inside)` for every piece of this visit's tile
    (of `rows` rows) that has a row of its group, in a loop (one copy of the body's
    code, whatever the tile): `rows` the piece's slice of the tile,
    `whole` whether every row of it is the group's, `inside(width)` the
    `[_PIECE, width]` mask of those that are."""
    visit = pl.program_id(1)
    group = group_ref[visit]
    lo, hi = bounds_ref[group], bounds_ref[group + 1]
    row0 = tile_ref[visit] * rows

    def piece(i, _):
        first = row0 + i * _PIECE

        @pl.when((first < hi) & (first + _PIECE > lo))
        def _():
            def inside(width):
                row = first + lax.broadcasted_iota(
                    jnp.int32, (_PIECE, width), 0)
                return (row >= lo) & (row < hi)

            body(pl.ds(pl.multiple_of(i * _PIECE, _PIECE), _PIECE),
                 (first >= lo) & (first + _PIECE <= hi), inside)

    lax.fori_loop(0, rows // _PIECE, piece, None)


def _product_kernel(bounds_ref, group_ref, tile_ref, x_ref, w_ref, o_ref,
                    w_low, *, g, transposed):
    """A visit of the product: x `[rows, contraction]`, w the group's
    float32 block (`[contraction, width]`, or `[width, contraction]`
    where `transposed`), o `[rows, width]`, `w_low` the scratch that
    holds w in x's dtype from the group's first visit on. Group `g` is
    the rows past the last group's end and has no matrix: zeros."""
    group = group_ref[pl.program_id(1)]

    @pl.when(_first_visit(group_ref) & (group < g))
    def _():
        w_low[...] = w_ref[...].astype(w_low.dtype)

    @pl.when(_first_visit(group_ref) & (group == g))
    def _():
        w_low[...] = jnp.zeros_like(w_low)

    def piece(rows, whole, inside):
        if transposed:
            out = _dot_nt(x_ref[rows, :], w_low[...])
        else:
            out = jnp.dot(x_ref[rows, :], w_low[...],
                          preferred_element_type=jnp.float32)

        @pl.when(whole)
        def _():
            o_ref[rows, :] = out.astype(o_ref.dtype)

        @pl.when(~whole)
        def _():
            # the other rows are another visit's: left as they are
            o_ref[rows, :] = jnp.where(
                inside(out.shape[1]), out,
                o_ref[rows, :].astype(jnp.float32)).astype(o_ref.dtype)

    _pieces(bounds_ref, group_ref, tile_ref, x_ref.shape[0], piece)


def _dw_kernel(bounds_ref, group_ref, tile_ref, x_ref, dy_ref, o_ref):
    """A visit of the weights' gradient: x `[rows, a]`, dy `[rows,
    width]`, o the group's `[a, width]` float32, zeroed at the group's
    first visit and summed into after. The narrower of the two operands
    carries the mask of a piece's rows."""
    @pl.when(_first_visit(group_ref))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def masked(ref, rows, inside):
        return jnp.where(inside(ref.shape[1]),
                         ref[rows, :].astype(jnp.float32), 0.0).astype(
                             ref.dtype)

    def piece(rows, _, inside):
        if x_ref.shape[1] <= dy_ref.shape[1]:
            x, dy = masked(x_ref, rows, inside), dy_ref[rows, :]
        else:
            x, dy = x_ref[rows, :], masked(dy_ref, rows, inside)
        o_ref[...] += _dot_tn(x, dy)

    _pieces(bounds_ref, group_ref, tile_ref, x_ref.shape[0], piece)


def _tile_rows(j, v, bounds, group, tile):
    """Index of a visit's row tile over a whole width."""
    return tile[v], 0


def _tile_block(j, v, bounds, group, tile):
    """Index of a visit's row tile at block `j` of the width."""
    return tile[v], j


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnums=(3, 4), inline=True)
def _product(x, weights, groups, transposed, interpreted):
    """x `[m, k]` through each group's matrix, `weights[group]` or with
    `transposed` its transpose -> `[m, n]` in x's dtype."""
    m, k = x.shape
    g = weights.shape[0]
    n = weights.shape[1 if transposed else 2]
    rows = _row_tile(m, k, n, x.dtype.itemsize)
    tn = _width_tile(rows, k, n, x.dtype.itemsize)
    # the rows past the last group's end: a group of their own, `g`
    bounds, group, tile, visits = _visits(groups, m, rows, False)

    def of_group(j, v, bounds, group, tile):
        expert = jnp.minimum(group[v], g - 1)
        return (expert, j, 0) if transposed else (expert, 0, j)

    return pl.pallas_call(
        functools.partial(_product_kernel, g=g, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, visits),
            in_specs=[
                pl.BlockSpec((rows, k), _tile_rows),
                pl.BlockSpec((None, tn, k) if transposed else (None, k, tn),
                             of_group)],
            out_specs=pl.BlockSpec((rows, tn), _tile_block),
            scratch_shapes=[pltpu.VMEM(
                (tn, k) if transposed else (k, tn), x.dtype)]),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=_PARAMS,
        interpret=interpreted,
        name=scopes.GROUPED_MATMUL_FWD,
    )(bounds, group, tile, x, weights)


@functools.partial(jax.jit, static_argnums=(3,), inline=True)
def _weights_gradient(x, dy, groups, interpreted):
    """`x[rows of a group]^T x dy[rows of a group]` for every group:
    `[m, a]`, `[m, c]` -> `[g, a, c]` float32."""
    (m, a), c = x.shape, dy.shape[1]
    rows = _row_tile(m, a, c, x.dtype.itemsize)
    tc = _width_tile(rows, a, c, x.dtype.itemsize, dw=True)
    bounds, group, tile, visits = _visits(groups, m, rows, True)
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(c // tc, visits),
            in_specs=[pl.BlockSpec((rows, a), _tile_rows),
                      pl.BlockSpec((rows, tc), _tile_block)],
            out_specs=pl.BlockSpec(
                (None, a, tc),
                lambda j, v, bounds, group, tile: (group[v], 0, j))),
        out_shape=jax.ShapeDtypeStruct((groups.shape[0], a, c), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpreted,
        name=scopes.GROUPED_MATMUL_DW,
    )(bounds, group, tile, x, dy)


@jax.custom_vjp
def grouped_matmul(rows, weights, groups):
    """`[m, a] x [g, a, c] -> [m, c]`: each row through the matrix of
    the group it lies in (`groups` `[g]` are the groups' row counts, in
    order; rows past their sum read zero). `weights` are read as they
    are stored and rounded to the rows' dtype in VMEM; float32
    accumulation, the result in the rows' dtype. The shape has to be
    one that `supports` takes."""
    return _product(rows, weights, groups, False, interpret())


def _grouped_matmul_fwd(rows, weights, groups):
    return grouped_matmul(rows, weights, groups), (rows, weights, groups)


def _grouped_matmul_bwd(residuals, d_out):
    rows, weights, groups = residuals
    d_out = d_out.astype(rows.dtype)
    return (_product(d_out, weights, groups, True, interpret()),
            _weights_gradient(rows, d_out, groups, interpret()).astype(
                weights.dtype),
            None)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
