"""The block-diffusion mask in the flash kernels (`_tile_ranges`' third
classification), key-value heads read through the block maps, and the
default attention's mask from the equation: against one another and
against a brute-force count, in interpret mode on the CPU."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import flops  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    diffusion_mask, dot_product_attention)
from horovod_tpu.ops import pallas_attention as pa  # noqa: E402
from horovod_tpu.utils import metrics  # noqa: E402


def equation(t, b):
    """The 2t x 2t mask, written out from the statement."""
    seen = np.zeros((2 * t, 2 * t), bool)
    for q in range(2 * t):
        for k in range(2 * t):
            q_noisy, k_noisy = q < t, k < t
            q_blk, k_blk = (q % t) // b, (k % t) // b
            seen[q, k] = ((q_noisy and k_noisy and q_blk == k_blk)
                          or (q_noisy and not k_noisy and k_blk < q_blk)
                          or (not q_noisy and not k_noisy
                              and k_blk <= q_blk))
    return seen


@pytest.mark.parametrize("t,b", [(16, 4), (16, 1), (16, 16), (24, 3)])
def test_default_attention_builds_the_mask_from_the_equation(t, b):
    mask = np.asarray(diffusion_mask(2 * t, b))
    np.testing.assert_array_equal(mask, equation(t, b))
    # T^2 + T*b of the 4T^2 pairs: what `flops.visible_pairs` counts
    assert mask.sum() == flops.visible_pairs(
        {"diffusion_block": b, "causal": False}, {"seq_len": t})
    # through the attention: a query's output moves with a key it sees
    # and with no other
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2 * t, 2, 8))
    v = jnp.eye(2 * t)[None, :, None, :].repeat(2, 2)  # one-hot values
    out = dot_product_attention(q, q, v, causal=True, diffusion_block=b)
    np.testing.assert_array_equal(np.asarray(out[0, :, 0] > 0), mask)


# (T, block b, block_q, block_k)
RANGE_CASES = [(64, 4, 16, 16), (64, 1, 16, 16), (64, 64, 16, 16),
               (64, 32, 16, 16), (48, 3, 16, 16), (96, 6, 32, 16),
               (96, 4, 16, 32), (64, 8, 8, 16), (60, 5, 20, 10)]


@pytest.mark.parametrize("t,b,bq,bk", RANGE_CASES)
def test_tile_ranges_cover_the_mask_and_nothing_else(t, b, bq, bk):
    """Every tile that holds a visible pair runs, exactly once; a tile
    called unmasked is all visible; no tile runs that shows nothing: for
    the q blocks of the forward and for the kv blocks of the backward."""
    seen = equation(t, b)
    geometry = pa._geometry(False, 0, 0, 2 * t, 2 * t, b)
    for over, own, other in (("kv", bq, bk), ("q", bk, bq)):
        for j in range(2 * t // own):
            ran = {}
            for lo, hi, masked in pa._tile_ranges(
                    over, j * own, bq, bk, 2 * t // other, **geometry):
                for tile in range(lo, hi):
                    assert tile not in ran
                    ran[tile] = masked
            for tile in range(2 * t // other):
                rows, cols = (j * bq, tile * bk) if over == "kv" \
                    else (tile * bq, j * bk)
                part = seen[rows:rows + bq, cols:cols + bk]
                assert (tile in ran) == bool(part.any()), (over, j, tile)
                if tile in ran and not ran[tile]:
                    assert part.all(), (over, j, tile)
            # and the mask of a tile is the equation's
            for tile, masked in ran.items():
                rows, cols = (j * bq, tile * bk) if over == "kv" \
                    else (tile * bq, j * bk)
                got = pa._tile_mask(bq, bk, rows, cols, **geometry)
                np.testing.assert_array_equal(
                    np.asarray(got), seen[rows:rows + bq, cols:cols + bk])


def _flash_and_plain(t, b, heads, kv_heads, d, block, causal=False):
    keys = jax.random.split(jax.random.PRNGKey(t + b + d), 4)
    positions = 2 * t if b else t
    q, ct = (jax.random.normal(k, (2, positions, heads, d))
             for k in keys[:2])
    k, v = (jax.random.normal(k, (2, positions, kv_heads, d))
            for k in keys[2:])

    def flash(q, k, v):
        return pa.flash_attention(q, k, v, causal=causal, block_q=block,
                                  block_k=block, diffusion_block=b)

    def plain(q, k, v):
        return dot_product_attention(q, k, v, causal=causal,
                                     diffusion_block=b)

    return flash, plain, (q, k, v), ct


# block length b = 1, 4 and T; head widths 64 and 128; 4 of 8 kv heads
@pytest.mark.parametrize("b", [1, 4, 32])
@pytest.mark.parametrize("d", [64, 128])
def test_flash_with_the_mask_against_the_default_attention(b, d):
    """Forward and the backward's dq, dk and dv (the group's partials
    summed) against the `xla` attention under the same mask."""
    flash, plain, args, ct = _flash_and_plain(32, b, 8, 4, d, 16)
    out, vjp = jax.vjp(flash, *args)
    want, vjp_plain = jax.vjp(plain, *args)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref, name in zip(vjp(ct), vjp_plain(ct), ("dq", "dk", "dv")):
        assert got.shape == ref.shape, name
        np.testing.assert_allclose(got, ref, atol=2e-4, err_msg=name)


def test_flash_with_blocks_that_do_not_divide_a_tile():
    flash, plain, args, ct = _flash_and_plain(48, 3, 4, 2, 64, 16)
    out, vjp = jax.vjp(flash, *args)
    want, vjp_plain = jax.vjp(plain, *args)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref in zip(vjp(ct), vjp_plain(ct)):
        np.testing.assert_allclose(got, ref, atol=2e-4)


@pytest.mark.parametrize("kv_heads", [1, 2, 8])
def test_kv_heads_are_read_through_the_block_maps_not_repeated(kv_heads):
    """A causal call with fewer key-value heads: the same numbers as the
    default attention, and the kernels are given K and V with their own
    head count (no array repeated to the query heads goes in)."""
    flash, plain, args, ct = _flash_and_plain(
        32, 0, 8, kv_heads, 64, 16, causal=True)
    out, vjp = jax.vjp(flash, *args)
    want, vjp_plain = jax.vjp(plain, *args)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref in zip(vjp(ct), vjp_plain(ct)):
        np.testing.assert_allclose(got, ref, atol=2e-4)
    jaxpr = jax.make_jaxpr(
        lambda *a: jax.vjp(flash, *a)[1](ct))(*args)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 2  # the forward, and one backward call
    for call in calls:
        heads = sorted({v.aval.shape[1] for v in call.invars
                        if v.aval.shape[-1] == 64})
        assert heads == sorted({8, kv_heads}), heads


def test_instances_a_program_are_whole_groups_or_parts_of_one():
    # T=128: 16 instances a program where the heads are their own kv
    # heads, as before; with 8 query heads a kv head a block of heads is
    # a whole part of a group or whole groups
    assert pa._instances_per_program("fwd", 4, 16, 128, 128, 64, 2) \
        == (1, 16)
    for heads_per_kv in (2, 4, 8, 16):
        gb, gh = pa._instances_per_program(
            "fwd", 4, 16, 128, 128, 64, 2, heads_per_kv)
        assert gh % heads_per_kv == 0 or heads_per_kv % gh == 0
    assert pa._instances_per_program(
        "fwd", 4, 12, 128, 128, 64, 2, heads_per_kv=4) == (1, 12)
    # the charge takes the width it is given: 64 and 128 fill the same
    # 128 lanes, 256 twice as many
    narrow, wide, wider = (pa._instances_per_program(
        "bwd", 26, 16, 512, 512, d, 2) for d in (64, 128, 256))
    assert narrow == wide == (1, 4) and wider == (1, 2)
    # the cell: 8,192 positions at width 128, one instance a program
    for kernel in ("fwd", "bwd"):
        assert pa._instances_per_program(
            kernel, 2, 32, 512, 8192, 128, 2, heads_per_kv=8) == (1, 1)


def test_tile_gauges_count_the_new_ranges_at_the_cells_shape():
    """Two sequences of 2 x 4,096 positions, 32 heads of 128 over 4:
    each of the two kernels runs 80 of an instance's 256 tiles (8
    noisy q blocks 2..9 tiles, 8 clean ones 1..8), 24 of them masked."""
    pa._flash_fwd.clear_cache()
    pa._flash_bwd.clear_cache()
    was = metrics.enabled()
    metrics.enable()
    metrics.registry.clear()
    try:
        q = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.bfloat16)
        fn = pa.make_flash_attention_fn(causal=False, diffusion_block=4)
        jax.eval_shape(jax.grad(
            lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)), q, k, k)
        snap = metrics.registry.snapshot()
    finally:
        metrics.registry.clear()
        pa._flash_fwd.clear_cache()
        pa._flash_bwd.clear_cache()
        if not was:
            metrics.disable()
    for kernel in ("fwd", "bwd"):
        got = tuple(int(snap[name][kernel]) for name in (
            "hvd_flash_instances_per_program", "hvd_flash_programs_per_call",
            "hvd_flash_tiles_per_call",
            "hvd_flash_boundary_tiles_per_call"))
        assert got == (1, 2 * 32 * 16, 64 * 80, 64 * 24), (kernel, got)
    # about a quarter of the 2T x 2T tiles: (T^2 + T*b) / 4T^2 of the
    # pairs, and the tiles the blocks' diagonals cross
    assert 64 * 80 / (64 * 256) == 0.3125


def test_a_mask_the_tiles_cannot_hold_is_refused():
    q = jnp.zeros((1, 48, 2, 64))
    with pytest.raises(ValueError, match="multiple of the block"):
        pa.flash_attention(q, q, q, diffusion_block=5)
    with pytest.raises(ValueError, match="no offsets"):
        pa.flash_attention(q, q, q, diffusion_block=4, query_offset=8)
    with pytest.raises(ValueError, match="halves of whole tiles"):
        # T = 520 picks tiles of 128
        big = jnp.zeros((1, 1040, 1, 64))
        pa.flash_attention(big, big, big, diffusion_block=4)
    with pytest.raises(ValueError, match="3 key-value heads"):
        pa.flash_attention(jnp.zeros((1, 16, 8, 64)),
                           jnp.zeros((1, 16, 3, 64)),
                           jnp.zeros((1, 16, 3, 64)))


# (T, block b, tile): a diffusion block that is no whole number of
# tiles (1.5 and 2.5 of them), and tiles that are no whole number of
# blocks
@pytest.mark.parametrize("t,b,block", [(48, 24, 16), (80, 40, 16),
                                       (48, 3, 16), (96, 48, 32)])
@pytest.mark.parametrize("kv_heads", [4, 1])
def test_one_backward_kernel_under_a_mask_the_tiles_do_not_divide(
        t, b, block, kv_heads):
    """The one backward kernel where a tile's width does not divide the
    diffusion block (a block's edge falls inside a tile, and a noisy q
    tile's rows wait for their block's kv tile), grouped key-value
    heads or not: dq from the scratch, dk and dv from the carries,
    against the `xla` attention under the same mask."""
    flash, plain, args, ct = _flash_and_plain(t, b, 4, kv_heads, 64, block)
    assert (b % block or block % b) and 2 * t // block > 2
    for got, ref, name in zip(jax.vjp(flash, *args)[1](ct),
                              jax.vjp(plain, *args)[1](ct),
                              ("dq", "dk", "dv")):
        assert np.isfinite(np.asarray(got)).all(), name
        np.testing.assert_allclose(got, ref, atol=2e-4, err_msg=name)
