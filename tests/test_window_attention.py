"""A sliding window in the flash kernels (`_tile_ranges`' fourth
classification, `_window_ranges`): against a mask written out from the
statement (a causal query q sees key k where 0 <= q - k < w), against a
brute-force count of tiles, against the causal call it has to equal bit
for bit once the window spans the sequence, with key-value heads
grouped, padded lengths and the offsets a sequence-parallel caller
passes; in interpret mode on the CPU."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from horovod_tpu.models.transformer import (  # noqa: E402
    dot_product_attention)
from horovod_tpu.ops import pallas_attention as pa  # noqa: E402
from horovod_tpu.utils import metrics  # noqa: E402


def equation(tq, tk, w, q_offset=0, k_offset=0):
    """The tq x tk mask, written out from the statement."""
    seen = np.zeros((tq, tk), bool)
    for q in range(tq):
        for k in range(tk):
            seen[q, k] = 0 <= (q_offset + q) - (k_offset + k) < w
    return seen


def brute_force(q, k, v, seen):
    """softmax(q k^T / sqrt(d)) v over the keys `seen` shows, float32;
    q [B, T, H, D], k and v [B, T, KH, D]."""
    rep = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, rep, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(seen[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("t,w", [(16, 1), (16, 5), (16, 16), (16, 40)])
def test_default_attention_builds_the_window_from_the_equation(t, w):
    q = jax.random.normal(jax.random.PRNGKey(0), (1, t, 2, 8))
    v = jnp.eye(t)[None, :, None, :].repeat(2, 2)  # one-hot values
    out = dot_product_attention(q, q, v, causal=True, window=w)
    np.testing.assert_array_equal(np.asarray(out[0, :, 0] > 0),
                                  equation(t, t, w))
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, q, v, causal=False, window=w)


# (Tq, Tk unpadded, window, block_q, block_k, q_offset, k_offset): a
# window narrower than a tile, of whole tiles, of no whole tiles, wider
# than the sequence; unequal blocks; a padded tail; a ring's offsets
RANGE_CASES = [
    (64, 64, 5, 16, 16, 0, 0), (64, 64, 16, 16, 16, 0, 0),
    (64, 64, 32, 16, 16, 0, 0), (64, 64, 23, 16, 16, 0, 0),
    (64, 64, 1, 16, 16, 0, 0), (64, 64, 100, 16, 16, 0, 0),
    (96, 96, 40, 32, 16, 0, 0), (96, 96, 40, 16, 32, 0, 0),
    (60, 60, 17, 16, 16, 0, 0), (50, 50, 33, 16, 8, 0, 0),
    (32, 32, 24, 16, 16, 32, 0), (32, 32, 24, 16, 16, 64, 32),
    (32, 32, 40, 16, 16, 32, 0),
]


@pytest.mark.parametrize("tq,tk,w,bq,bk,q_off,k_off", RANGE_CASES)
def test_tile_ranges_cover_the_window_and_nothing_else(
        tq, tk, w, bq, bk, q_off, k_off):
    """Every tile that holds a visible pair runs, exactly once and in
    order; a tile called unmasked is all visible; no tile runs that
    shows nothing: for the q blocks of the forward and for the kv blocks
    of the backward; and the mask of a tile is the equation's."""
    tq_p, tk_p = -(-tq // bq) * bq, -(-tk // bk) * bk
    seen = np.zeros((tq_p, tk_p), bool)
    # padded keys are seen by no row; padded rows see what their
    # positions would (their dO is zero, so they add nothing)
    seen[:, :tk] = equation(tq_p, tk, w, q_off, k_off)
    geometry = pa._geometry(True, q_off, k_off, tk, tk_p, 0, w)
    for over, own, other, n_own, n_other in (
            ("kv", bq, bk, tq_p // bq, tk_p // bk),
            ("q", bk, bq, tk_p // bk, tq_p // bq)):
        for j in range(n_own):
            ran, last = {}, -1
            for lo, hi, masked in pa._tile_ranges(
                    over, j * own, bq, bk, n_other, **geometry):
                for tile in range(lo, hi):
                    assert tile > last and tile not in ran
                    ran[tile], last = masked, tile
            for tile in range(n_other):
                rows, cols = (j * bq, tile * bk) if over == "kv" \
                    else (tile * bq, j * bk)
                part = seen[rows:rows + bq, cols:cols + bk]
                assert (tile in ran) == bool(part.any()), (over, j, tile)
                if tile in ran and not ran[tile]:
                    assert part.all(), (over, j, tile)
                if tile in ran:
                    got = pa._tile_mask(bq, bk, rows, cols, **geometry)
                    np.testing.assert_array_equal(np.asarray(got), part)


@pytest.mark.parametrize("t,w,block,tiles", [
    # ISSUE 48: T = 8,192, w = 2,048, tiles of 512: 70 of the causal 136
    (8192, 2048, 512, (70, 136)), (4096, 2048, 512, (30, 36)),
    (2048, 2048, 512, (10, 10)), (8192, 2048, 256, (252, 528)),
    (8192, 100, 512, (31, 136)),
])
def test_window_tile_counts_against_a_brute_force_count(t, w, block, tiles):
    """Tiles one instance runs, forward and backward, counted from the
    ranges and from the mask itself, beside the causal range's."""
    n = t // block
    row = np.arange(t)
    seen = (row[:, None] - row[None] >= 0) & (row[:, None] - row[None] < w)
    touched = seen.reshape(n, block, n, block).any(axis=(1, 3))
    whole = seen.reshape(n, block, n, block).all(axis=(1, 3))
    geometry = pa._geometry(True, 0, 0, t, t, 0, w if w < t else 0)
    for over in ("kv", "q"):
        every = pa._every_program(over, n, block, block, n, **geometry)
        ran, masked = pa._count_tiles(every)
        assert ran == touched.sum() == tiles[0]
        assert masked == (touched & ~whole).sum()
        causal = pa._every_program(over, n, block, block, n,
                                   **{**geometry, "window": 0})
        assert pa._count_tiles(causal)[0] == tiles[1] == n * (n + 1) // 2


def _inputs(t, heads, kv_heads, d, seed=0, tk=None):
    keys = jax.random.split(jax.random.PRNGKey(seed + t + d), 4)
    q, ct = (jax.random.normal(k, (2, t, heads, d)) for k in keys[:2])
    k, v = (jax.random.normal(k, (2, tk or t, kv_heads, d))
            for k in keys[2:])
    return (q, k, v), ct


# w < block, w a multiple of the block, w not one, w one short of T;
# four query heads to a key-value head
@pytest.mark.parametrize("w", [5, 16, 32, 23, 63])
@pytest.mark.parametrize("d", [64, 128])
def test_flash_with_a_window_against_a_brute_force_mask(w, d):
    """Forward and the backward's dq, dk and dv (the group's partials
    summed) against plain attention under the mask of the equation."""
    t, block = 64, 16
    args, ct = _inputs(t, 8, 2, d)
    seen = jnp.asarray(equation(t, t, w))
    out, vjp = jax.vjp(lambda q, k, v: pa.flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, window=w),
        *args)
    want, vjp_plain = jax.vjp(
        lambda q, k, v: brute_force(q, k, v, seen), *args)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref, name in zip(vjp(ct), vjp_plain(ct), ("dq", "dk", "dv")):
        assert got.shape == ref.shape, name
        np.testing.assert_allclose(got, ref, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("t,w,bq,bk", [(60, 17, 16, 16), (96, 40, 32, 16),
                                       (96, 40, 16, 32), (50, 7, 16, 16)])
def test_flash_with_a_window_padded_lengths_and_unequal_blocks(t, w, bq, bk):
    args, ct = _inputs(t, 4, 2, 64)
    seen = jnp.asarray(equation(t, t, w))
    out, vjp = jax.vjp(lambda q, k, v: pa.flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk, window=w), *args)
    want, vjp_plain = jax.vjp(
        lambda q, k, v: brute_force(q, k, v, seen), *args)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref in zip(vjp(ct), vjp_plain(ct)):
        np.testing.assert_allclose(got, ref, atol=2e-4)


def test_flash_with_a_window_on_a_rings_offsets():
    """Queries 32..63 against keys 0..31 (a rotated block): a window of
    24 shows a query the keys from q - 23 on, and the rows from 55 on
    none at all: their output is zero."""
    t, w = 32, 24
    args, ct = _inputs(t, 4, 2, 64)
    seen = equation(t, t, w, q_offset=t)
    out, vjp = jax.vjp(lambda q, k, v: pa.flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16, window=w,
        query_offset=t), *args)
    shown = jnp.asarray(seen.any(axis=1))
    # a row that sees no key: softmax over nothing; the kernels say 0
    want, vjp_plain = jax.vjp(lambda q, k, v: jnp.where(
        shown[None, :, None, None], brute_force(
            q, k, v, jnp.asarray(seen | ~seen.any(axis=1)[:, None])), 0.0),
        *args)
    assert not seen[23:].any() and seen[:23].any(axis=1).all()
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref in zip(vjp(ct), vjp_plain(ct)):
        np.testing.assert_allclose(got, ref, atol=2e-4)


@pytest.mark.parametrize("w", [64, 65, 1000])
def test_a_window_that_spans_the_sequence_is_the_causal_call_bit_for_bit(w):
    args, ct = _inputs(64, 8, 2, 64)

    def call(window):
        return jax.vjp(lambda q, k, v: pa.flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16, window=window),
            *args)

    out, vjp = call(w)
    want, vjp_causal = call(0)
    np.testing.assert_array_equal(out, want)
    for got, ref in zip(vjp(ct), vjp_causal(ct)):
        np.testing.assert_array_equal(got, ref)


def test_a_window_is_a_causal_masks_and_an_argument_of_the_call():
    (q, k, v), _ = _inputs(32, 2, 2, 64)
    with pytest.raises(ValueError, match="causal"):
        pa.flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        pa.flash_attention(jnp.tile(q, (1, 2, 1, 1)),
                           jnp.tile(k, (1, 2, 1, 1)),
                           jnp.tile(v, (1, 2, 1, 1)), causal=True,
                           diffusion_block=4, window=8)
    fn = pa.make_flash_attention_fn(causal=True, block_q=16, block_k=16)
    want = brute_force(q, k, v, jnp.asarray(equation(32, 32, 8)))
    np.testing.assert_allclose(fn(q, k, v, window=8), want, atol=2e-5)
    np.testing.assert_allclose(
        fn.from_bhtd(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
                     window=8), want, atol=2e-5)
    np.testing.assert_allclose(
        fn(q, k, v), brute_force(q, k, v, jnp.asarray(
            equation(32, 32, 32))), atol=2e-5)


def test_the_gauges_of_a_windowed_call():
    """A call under a window leaves its window, its tiles and the
    causal range's in gauges of its own, by kernel; the full layers of
    the same model trace their calls beside it and do not touch them."""
    was = metrics.enabled()
    metrics.enable()
    metrics.registry.clear()
    pa._flash_fwd.clear_cache()
    pa._flash_bwd.clear_cache()
    try:
        (q, k, v), _ = _inputs(64, 4, 2, 64)

        def loss(window):
            return lambda q: jnp.sum(pa.flash_attention(
                q, k, v, causal=True, block_q=16, block_k=16,
                window=window))

        jax.grad(loss(20))(q)
        jax.grad(loss(0))(q)  # a full layer, traced after
        snap = metrics.registry.snapshot()
        for kernel in ("fwd", "bwd"):
            # 2 x 4 instances; the window's 9 of the diagonal's 10 tiles
            assert {name: int(snap[name][kernel]) for name in (
                "hvd_flash_window", "hvd_flash_window_tiles_per_call",
                "hvd_flash_window_causal_tiles_per_call",
                "hvd_flash_tiles_per_call")} == {
                    "hvd_flash_window": 20,
                    "hvd_flash_window_tiles_per_call": 8 * 9,
                    "hvd_flash_window_causal_tiles_per_call": 8 * 10,
                    "hvd_flash_tiles_per_call": 8 * 10}
    finally:
        metrics.registry.clear()
        pa._flash_fwd.clear_cache()
        pa._flash_bwd.clear_cache()
        if not was:
            metrics.disable()
