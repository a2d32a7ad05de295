"""Flash-attention kernel numerics vs the reference math (interpret mode
on the CPU mesh; the same kernel compiles on TPU)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.ops.pallas_attention import (
    _reference_attention,
    flash_attention,
    make_flash_attention_fn,
)


def _rand(shape, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).normal(size=shape).astype(np.float32)
    )


def _ref_btHD(q, k, v, causal, q_off=0, k_off=0):
    d = q.shape[-1]
    hq, hk = q.shape[2], k.shape[2]
    if hk != hq:
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    out = _reference_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal, 1.0 / d ** 0.5, q_off, k_off,
    )
    return out.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [True, False])
def test_matches_reference(causal):
    q = _rand((2, 128, 4, 32), 0)
    k = _rand((2, 128, 4, 32), 1)
    v = _rand((2, 128, 4, 32), 2)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = _ref_btHD(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_unpadded_lengths():
    """T not a multiple of the block size exercises the padding mask."""
    q = _rand((1, 100, 2, 16), 3)
    k = _rand((1, 100, 2, 16), 4)
    v = _rand((1, 100, 2, 16), 5)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = _ref_btHD(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_gqa_repeats_kv():
    q = _rand((1, 64, 8, 16), 6)
    k = _rand((1, 64, 2, 16), 7)
    v = _rand((1, 64, 2, 16), 8)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = _ref_btHD(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_query_offset_for_ring_blocks():
    """Off-diagonal ring-attention block: queries at global offset see all
    earlier keys."""
    q = _rand((1, 32, 2, 16), 9)
    k = _rand((1, 32, 2, 16), 10)
    v = _rand((1, 32, 2, 16), 11)
    out = flash_attention(
        q, k, v, causal=True, query_offset=32, key_offset=0,
        block_q=32, block_k=32,
    )
    ref = _ref_btHD(q, k, v, True, q_off=32, k_off=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_gradients_flow():
    q = _rand((1, 64, 2, 16), 12)
    k = _rand((1, 64, 2, 16), 13)
    v = _rand((1, 64, 2, 16), 14)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_btHD(q, k, v, True).astype(q.dtype) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_reference(causal):
    """The flash backward kernel (dq, dk and dv from probability tiles
    rebuilt from lse) against the materializing reference VJP."""
    q = _rand((2, 96, 2, 32), 30)
    k = _rand((2, 96, 2, 32), 31)
    v = _rand((2, 96, 2, 32), 32)
    ct = _rand((2, 96, 2, 32), 33)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=32,
                               block_k=32)

    def ref(q, k, v):
        return _ref_btHD(q, k, v, causal).astype(q.dtype)

    _, vjp_f = jax.vjp(flash, q, k, v)
    _, vjp_r = jax.vjp(ref, q, k, v)
    for a, b in zip(vjp_f(ct), vjp_r(ct)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_backward_unpadded_and_offset():
    """Backward with T not a block multiple AND ring offsets: padded q
    rows and fully-masked rows must contribute zero gradient."""
    q = _rand((1, 50, 2, 16), 40)
    k = _rand((1, 70, 2, 16), 41)
    v = _rand((1, 70, 2, 16), 42)
    ct = _rand((1, 50, 2, 16), 43)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, query_offset=16,
                               block_q=32, block_k=32)

    def ref(q, k, v):
        return _ref_btHD(q, k, v, True, q_off=16).astype(q.dtype)

    _, vjp_f = jax.vjp(flash, q, k, v)
    _, vjp_r = jax.vjp(ref, q, k, v)
    for a, b in zip(vjp_f(ct), vjp_r(ct)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_backward_fully_masked_block_zero_grads():
    """All keys after all queries: output is zero and so are all grads
    (lse == -inf rows must not produce NaNs via exp overflow)."""
    q = _rand((1, 8, 2, 16), 44)
    k = _rand((1, 8, 2, 16), 45)
    v = _rand((1, 8, 2, 16), 46)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, key_offset=8,
                            block_q=8, block_k=8) ** 2
        )

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g_ in grads:
        np.testing.assert_allclose(np.asarray(g_), 0.0, atol=1e-7)


def test_backward_gqa():
    """GQA: dK/dV of repeated heads sum back onto the shared kv heads."""
    q = _rand((1, 32, 4, 16), 50)
    k = _rand((1, 32, 2, 16), 51)
    v = _rand((1, 32, 2, 16), 52)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=16, block_k=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_btHD(q, k, v, True).astype(q.dtype) ** 2)

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_pluggable_into_transformer():
    from horovod_tpu.models import GPT2_SMALL, Transformer
    import dataclasses

    cfg = dataclasses.replace(
        GPT2_SMALL, num_layers=2, hidden_size=64, num_heads=4,
        max_seq_len=64, vocab_size=128, dtype=jnp.float32,
    )
    model = Transformer(cfg, attention_fn=make_flash_attention_fn(True))
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (2, 64)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), toks)
    logits = model.apply(params, toks)
    assert logits.shape == (2, 64, 128)
    assert np.isfinite(np.asarray(logits)).all()

    ref_model = Transformer(cfg)
    ref_logits = ref_model.apply(params, toks)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), atol=2e-3
    )


def test_fully_masked_rows_output_zero():
    """Ring off-diagonal block where all keys are AFTER all queries: every
    row is fully masked and must output exactly zero (not mean of V)."""
    q = _rand((1, 8, 2, 16), 20)
    k = _rand((1, 8, 2, 16), 21)
    v = _rand((1, 8, 2, 16), 22)
    out = flash_attention(
        q, k, v, causal=True, query_offset=0, key_offset=8,
        block_q=8, block_k=8,
    )
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-7)


# -- several (batch, head) instances a program ------------------------------
#
# The chooser takes the largest block of [B, H] inside its limits. These
# shapes are a unit or two of work an instance and far inside the VMEM
# budget, so `_MOST_INSTANCES` alone sets the most a program may take: 1
# (one instance a program, the grid of before), 2, or all B*H.

from horovod_tpu.ops import pallas_attention as pa  # noqa: E402
from horovod_tpu.utils import metrics  # noqa: E402

KERNELS = ("fwd", "bwd")

# name -> (q shape, kv shape [B, T, H, D], query_offset, key_offset, block)
INSTANCE_CASES = {
    # 3 x 5: no block but one instance under a limit of 2
    "b3_h5": ((3, 64, 5, 16), (3, 64, 5, 16), 0, 0, 32),
    # T not a block multiple, Tq != Tk, ring offset
    "unpadded_offset": ((2, 50, 2, 16), (2, 70, 2, 16), 16, 0, 32),
    # every key after every query when causal: rows with no key at all
    "fully_masked": ((2, 8, 2, 16), (2, 8, 2, 16), 0, 8, 8),
    "gqa": ((1, 32, 4, 16), (1, 32, 2, 16), 0, 0, 16),
}


@pytest.fixture
def flash_gauges():
    """The registry, recording, and empty of the flash kernels' gauges;
    the two directions forget what they traced (they are traced once a
    shape, and the gauges are written while tracing)."""
    pa._flash_fwd.clear_cache()
    pa._flash_bwd.clear_cache()
    was = metrics.enabled()
    metrics.enable()
    metrics.registry.clear()

    def read(*names):
        names = names or ("hvd_flash_instances_per_program",
                          "hvd_flash_programs_per_call")
        snap = metrics.registry.snapshot()
        return {kernel: tuple(int(snap[name][kernel]) for name in names)
                for kernel in KERNELS if kernel in snap.get(names[0], {})}

    yield read
    metrics.registry.clear()
    pa._flash_fwd.clear_cache()
    pa._flash_bwd.clear_cache()
    if not was:
        metrics.disable()


@pytest.mark.parametrize("case", INSTANCE_CASES)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("most", [1, 2, None], ids=["g1", "g2", "gall"])
def test_instances_per_program_match_reference(monkeypatch, flash_gauges,
                                               most, causal, case):
    """Forward and backward against the reference whatever the number of
    instances a program: side by side, no instance changes."""
    q_shape, kv_shape, q_off, k_off, block = INSTANCE_CASES[case]
    if most is not None:
        monkeypatch.setattr(pa, "_MOST_INSTANCES", most)
    q, k, v, ct = (_rand(s, 60 + i) for i, s in
                   enumerate((q_shape, kv_shape, kv_shape, q_shape)))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, query_offset=q_off,
                               key_offset=k_off, block_q=block,
                               block_k=block)

    def ref(q, k, v):
        out = _ref_btHD(q, k, v, causal, q_off, k_off).astype(q.dtype)
        if causal and case == "fully_masked":
            out = out * 0.0  # the reference averages V over no key
        return out

    out, vjp_f = jax.vjp(flash, q, k, v)
    expected, vjp_r = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)
    for a, b in zip(vjp_f(ct), vjp_r(ct)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    # the grid each kernel was built with: heads first, then batches
    b, h = q_shape[0], q_shape[2]
    instances = b * h
    g = max(d for d in [d for d in range(1, h + 1) if h % d == 0]
            + [d * h for d in range(1, b + 1) if b % d == 0]
            if d <= (most or instances))
    q_blocks, k_blocks = -(-q_shape[1] // block), -(-kv_shape[1] // block)
    assert flash_gauges() == {
        "fwd": (g, instances // g * q_blocks),
        "bwd": (g, instances // g * k_blocks)}


# cell -> (B, H, T, block, causal) of its attention calls at head width
# 64 in bf16, and the instances a program the forward and the backward
# kernel get there (the backward takes twice the work a program and has
# its own VMEM: PR 34's sweep)
CELL_SHAPES = {
    "gpt2m_dp1": ((16, 16, 1024, 512, True), (2, 4)),
    "gpt2m_dp4": ((16, 16, 1024, 512, True), (2, 4)),
    "bertl_s512": ((26, 16, 512, 512, False), (4, 4)),
    "bertl_s128": ((104, 16, 128, 128, False), (16, 16)),
}


@pytest.mark.parametrize("cell", CELL_SHAPES)
def test_chooser_divides_and_stays_inside_its_limits(cell):
    (b, h, t, block, causal), expected = CELL_SHAPES[cell]
    for kernel, want in zip(KERNELS, expected):
        gb, gh = pa._instances_per_program(kernel, b, h, block, t, 64, 2)
        g = gb * gh
        assert g == want and b % gb == 0 and h % gh == 0, (kernel, g)
        assert gb == 1 or gh == h  # consecutive instances
        n_own, n_other, _, _, n_scratch = pa._KERNEL_BLOCKS[kernel]
        blocks = g * (2 * (n_own * pa._vmem_bytes(block, 64, 2)
                           + n_other * pa._vmem_bytes(t, 64, 2))
                      + n_scratch * pa._vmem_bytes(t, 64, 4))
        assert blocks <= g * pa._vmem_charge(kernel, block, t, 64, 2) \
            <= pa._VMEM_BLOCK_BUDGET[kernel], (kernel, g, blocks)
        units = (block // 128) * (t // 128)
        assert g == 1 or g * units <= pa._PROGRAM_TILE_UNITS[kernel]
        assert g <= pa._MOST_INSTANCES


@pytest.mark.parametrize("why, args", [
    ("more heads than a program takes, and a prime number of them",
     (64, 17, 128, 128, 64, 2)),
    ("one instance is all the work a program should do",
     (16, 16, 512, 4096, 64, 2)),
    ("one instance fills the VMEM budget",
     (16, 16, 128, 128, 4096, 4)),
])
def test_chooser_returns_one_where_it_must(why, args):
    for kernel in KERNELS:
        assert pa._instances_per_program(kernel, *args) == (1, 1), \
            (why, kernel)


def test_gauges_read_what_the_chooser_chose(flash_gauges):
    """Under jit the gauges are set while tracing, once, and read the
    chooser's own answer for the shapes of the call."""
    b, t, h, d = 4, 256, 6, 16
    q, k, v = (_rand((b, t, h, d), 70 + i) for i in range(3))
    loss = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128) ** 2)))
    loss(q, k, v)
    want = {kernel: pa._instances_per_program(
        kernel, b, h, 128, t, d, 4) for kernel in KERNELS}
    assert flash_gauges() == {
        kernel: (gb * gh, b * h // (gb * gh) * (t // 128))
        for kernel, (gb, gh) in want.items()}
    assert all(gb * gh > 1 for gb, gh in want.values()), want
    metrics.registry.clear()
    loss(q, k, v)  # compiled: nothing of the step writes a gauge
    assert flash_gauges() == {}


# -- a tile pays for the mask only where the mask can be false in it --------
#
# `_tile_ranges` classes each program's tiles: unmasked (wholly at or
# under the diagonal, no padded key), masked (the diagonal crosses it, or
# it holds padded keys), or not run (wholly above the diagonal).

# name -> (Tq, Tk, causal, query_offset, key_offset, block_q, block_k)
TILE_CASES = {
    # 1, 2 and 3 blocks a side: masked only; + an unmasked and a skipped
    # tile; + a program with two unmasked tiles
    "causal_1_block": (128, 128, True, 0, 0, 128, 128),
    "causal_2_blocks": (256, 256, True, 0, 0, 128, 128),
    "causal_3_blocks": (384, 384, True, 0, 0, 128, 128),
    # the diagonal crosses two kv tiles of a q block / two q tiles of a kv
    # block
    "wide_q_block": (256, 256, True, 0, 0, 128, 64),
    "wide_k_block": (256, 256, True, 0, 0, 64, 128),
    # ring attention's blocks: wholly under the diagonal (no masked tile),
    # straddling it off the tiles' corners, wholly above it (nothing runs)
    "ring_under": (128, 256, True, 256, 0, 64, 64),
    "ring_straddling": (192, 192, True, 96, 0, 64, 64),
    "ring_above": (128, 128, True, 0, 128, 64, 64),
    # padded keys: only the last kv tile is masked
    "padded_keys": (200, 200, False, 0, 0, 128, 128),
    "causal_padded_keys": (200, 200, True, 0, 0, 128, 128),
    # queries that start before the keys: rows that see no key in their
    # first tiles, or in none (the forward keeps its second select)
    "rows_without_keys": (128, 128, True, 0, 96, 64, 64),
}


def _tile_case(case):
    tq, tk, causal, q_off, k_off, block_q, block_k = TILE_CASES[case]
    q, ct = _rand((2, tq, 2, 16), 80), _rand((2, tq, 2, 16), 83)
    k, v = _rand((2, tk, 2, 16), 81), _rand((2, tk, 2, 16), 82)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, query_offset=q_off,
                               key_offset=k_off, block_q=block_q,
                               block_k=block_k)

    def ref(q, k, v):
        out = _ref_btHD(q, k, v, causal, q_off, k_off)
        if causal:  # the reference averages V over no key
            sees = (q_off + jnp.arange(tq) >= k_off).astype(out.dtype)
            out = out * sees[None, :, None, None]
        return out

    return flash, ref, (q, k, v), ct


@pytest.mark.parametrize("case", TILE_CASES)
def test_tile_classes_match_reference(flash_gauges, case):
    """Forward and all three gradients against the reference, whatever
    mix of unmasked, masked and skipped tiles a program runs."""
    flash, ref, args, ct = _tile_case(case)
    out, vjp_f = jax.vjp(flash, *args)
    expected, vjp_r = jax.vjp(ref, *args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)
    for a, b in zip(vjp_f(ct), vjp_r(ct)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.parametrize("case", TILE_CASES)
def test_tile_classes_same_bits_as_every_tile_masked(monkeypatch,
                                                     flash_gauges, case):
    """What the classes leave out is work whose result is known: with
    every tile that runs masked and the forward's second select kept
    everywhere (the kernels of before) the results are the same bits."""
    flash, _, args, ct = _tile_case(case)
    out, vjp = jax.vjp(flash, *args)
    grads = vjp(ct)

    ranges = pa._tile_ranges

    def every_tile_masked(*a, **kw):
        (lo, _, _), (_, hi, _) = ranges(*a, **kw)
        return [(lo, hi, True), (hi, hi, False)]

    monkeypatch.setattr(pa, "_tile_ranges", every_tile_masked)
    monkeypatch.setattr(pa, "_rows_may_see_no_key",
                        lambda **geometry: True)
    pa._flash_fwd.clear_cache()
    pa._flash_bwd.clear_cache()
    out_masked, vjp_masked = jax.vjp(flash, *args)
    for a, b in zip((out, *grads), (out_masked, *vjp_masked(ct))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# name -> ((B, H, T, block, causal, query_offset), (tiles, masked tiles) a
# call of each kernel) at head width 64 in bf16
TILE_COUNTS = {
    # 16 x 16 instances x 3 tiles, 2 of them on the diagonal
    "gpt2m": ((16, 16, 1024, 512, True, 0), (768, 512)),
    "bertl_s512": ((26, 16, 512, 512, False, 0), (416, 0)),
    "bertl_s128": ((104, 16, 128, 128, False, 0), (1664, 0)),
    # a ring block wholly under the diagonal: all 4 tiles, none masked
    "ring_under": ((16, 16, 1024, 512, True, 1024), (1024, 0)),
    # ... and one wholly above it: nothing runs
    "ring_above": ((16, 16, 1024, 512, True, -1024), (0, 0)),
}


@pytest.mark.parametrize("shape", TILE_COUNTS)
def test_gauges_count_tiles_and_boundary_tiles(flash_gauges, shape):
    """Traced, not run: the gauges are arithmetic on the call's shapes."""
    (b, h, t, block, causal, q_off), want = TILE_COUNTS[shape]
    x = jax.ShapeDtypeStruct((b, t, h, 64), jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=causal, query_offset=q_off, block_q=block,
        block_k=block).astype(jnp.float32)), argnums=(0, 1, 2)), x, x, x)
    assert flash_gauges("hvd_flash_tiles_per_call",
                        "hvd_flash_boundary_tiles_per_call") == \
        {kernel: want for kernel in KERNELS}


# -- one backward kernel: dq, dk and dv from one probability tile ------------
#
# A program owns a kv block and streams the q tiles; dk and dv are its
# carries, dq of all the instance's rows is summed in an f32 scratch that
# lives across the grid's kv axis and is cast once, at the last kv block.


def _bwd_case(b, h, tq, tk, d, seed, dtype=jnp.float32, kv_heads=None):
    """q, k, v, dO in the kernels' [B, H, T, D] layout."""
    shapes = ((b, h, tq, d), (b, kv_heads or h, tk, d),
              (b, kv_heads or h, tk, d), (b, h, tq, d))
    return tuple(_rand(s, seed + i).astype(dtype)
                 for i, s in enumerate(shapes))


# (block_q, block_k) of a sequence of 128: one block, 2, 4 and 8 kv
# blocks, square and unequal
CUTS = [(128, 128), (32, 128), (32, 64), (32, 32), (32, 16), (64, 64),
        (64, 32), (16, 64), (128, 32)]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_q,block_k", CUTS)
def test_backward_does_not_depend_on_how_the_sequence_is_cut(
        causal, block_q, block_k):
    """From one forward's residuals the three results are the same
    however the backward cuts the sequence: a row's dq is one f32 sum
    over its key tiles wherever the kv axis is cut, dk and dv one over
    the q tiles. Within 1e-6 of the uncut call's for dq (2e-6 for dk
    and dv, sums over both sequences' rows) and not to the bit: the
    sums are split at other places, and the CPU's matrix product rounds
    by the shape it is given (dk and dv keep their bits from 128 keys a
    block to 64 and lose them at 32)."""
    q, k, v, ct = _bwd_case(2, 2, 128, 128, 16, 90)
    static = (causal, 0.25, 0, 0)
    _, residuals = pa._flash_fwd(q, k, v, *static, 32, 32, 0, 0)
    whole = pa._flash_bwd(*static, 128, 128, 0, 0, residuals, ct)
    cut = pa._flash_bwd(*static, block_q, block_k, 0, 0, residuals, ct)
    for got, want, name in zip(cut, whole, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0,
            atol=1e-6 if name == "dq" else 2e-6, err_msg=name)
    if block_q == 32 and block_k == 64:  # the q tiles of (32, 128)
        for got, want in zip(cut[1:], pa._flash_bwd(
                *static, 32, 128, 0, 0, residuals, ct)[1:]):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.fixture
def backward_calls(monkeypatch):
    """What each `pallas_call` of the kernels' module returned, padded
    rows and all (the module's functions slice them off)."""
    seen = []
    real = pa.pl.pallas_call

    def spy(*a, **kw):
        call = real(*a, **kw)

        def run(*args):
            seen.append(call(*args))
            return seen[-1]

        return run

    monkeypatch.setattr(pa.pl, "pallas_call", spy)
    return seen


# name -> (Tq, Tk, key_offset, block, rows of q that see no key)
ZERO_ROW_CASES = {
    # rows 0..15 see no key, inside a tile that runs
    "part_of_a_tile": (50, 70, 16, 32, 16),
    # rows 0..39: q tile 0 runs in no kv block, its scratch rows are
    # never added to; tile 1 holds rows of both kinds and the padding
    "a_tile_no_program_runs": (50, 70, 40, 32, 40),
    # every row: nothing runs at all, the scratch is zeroed and cast
    "no_tile_at_all": (50, 70, 128, 32, 50),
    # one kv block
    "one_kv_block": (50, 30, 16, 32, 16),
}


@pytest.mark.parametrize("case", ZERO_ROW_CASES)
@pytest.mark.parametrize("most", [1, None], ids=["g1", "gall"])
def test_backward_rows_no_key_sees_are_exact_zeros_in_dq(
        monkeypatch, backward_calls, most, case):
    """Padded query rows and rows that see no key get exact zeros in dq,
    in the padded array the call itself returns: the scratch is zeroed
    at the first kv block of every block of instances (interpreted, it
    starts as NaN, and with one instance a program it still holds the
    instance before), not left."""
    tq, tk, k_off, block, blind = ZERO_ROW_CASES[case]
    if most is not None:
        monkeypatch.setattr(pa, "_MOST_INSTANCES", most)
    q, k, v, ct = _bwd_case(2, 2, tq, tk, 16, 100)
    static = (True, 0.25, 0, k_off, block, block)
    fwd, bwd = pa._flash_fwd.__wrapped__, pa._flash_bwd.__wrapped__
    _, residuals = fwd(q, k, v, *static, 0, 0)
    dq, dk, dv = bwd(*static, 0, 0, residuals, ct)
    dk_p, dv_p, dq_p = backward_calls[-1]
    assert dq_p.shape[2] == -(-tq // block) * block > tq
    np.testing.assert_array_equal(np.asarray(dq_p[:, :, :tq]),
                                  np.asarray(dq))
    np.testing.assert_array_equal(np.asarray(dq_p[:, :, tq:]), 0.0)
    np.testing.assert_array_equal(np.asarray(dq_p[:, :, :blind]), 0.0)
    assert np.isfinite(np.asarray(dq_p)).all()
    if blind < tq:
        assert np.abs(np.asarray(dq[:, :, blind:])).min(axis=-1).max() > 0
    # and the three against the reference
    def ref(q, k, v):
        out = _reference_attention(q, k, v, True, 0.25, 0, k_off)
        sees = (jnp.arange(tq) >= k_off).astype(out.dtype)
        return out * sees[None, None, :, None]

    for got, want in zip((dq, dk, dv), jax.vjp(ref, q, k, v)[1](ct)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("most", [1, 2, 4, 8], ids=lambda g: f"g{g}")
def test_backward_grouped_kv_heads_whole_and_split_over_programs(
        monkeypatch, flash_gauges, most, causal):
    """8 query heads over 2 key-value heads: a program's block of heads
    is both groups (8), one whole group (4), half a group (2) or one
    head (1), and dq, dk (the group's partials summed) and dv are the
    reference's, and the same bits as one head a program."""
    q, k, v, ct = (x.transpose(0, 2, 1, 3) for x in _bwd_case(
        1, 8, 64, 64, 16, 110, kv_heads=2))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=32,
                               block_k=32)

    monkeypatch.setattr(pa, "_MOST_INSTANCES", most)
    got = jax.vjp(flash, q, k, v)[1](ct)
    assert flash_gauges()["bwd"] == (most, 8 // most * 2)
    want = jax.vjp(lambda *a: _ref_btHD(*a, causal), q, k, v)[1](ct)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
    monkeypatch.setattr(pa, "_MOST_INSTANCES", 1)
    pa._flash_fwd.clear_cache()
    pa._flash_bwd.clear_cache()
    for a, b in zip(got, jax.vjp(flash, q, k, v)[1](ct)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _rms_error(x, ref):
    e = np.asarray(x, np.float32) - np.asarray(ref)
    return float(np.sqrt((e ** 2).mean() / (np.asarray(ref) ** 2).mean()))


@pytest.mark.parametrize("seed", [120, 130])
def test_backward_dq_is_not_rounded_between_kv_blocks(seed):
    """bf16 inputs over 32 kv blocks: dq against the f32 reference is
    off by what one cast to bf16 allows (2^-8, relative, of the root
    mean square: it reads 0.0024 with the operands' own rounding)
    however many kv blocks there are. The control that has to fail
    rounds dq a kv block: the same kernel called once a block of keys
    and the bf16 partials added in bf16, which is what a dq partial
    written a block, or a bf16 accumulator, would give (0.0073)."""
    t, d, block_q, block_k = 256, 64, 64, 8
    q, k, v, ct = _bwd_case(1, 2, t, t, d, seed, jnp.bfloat16)
    static = (False, d ** -0.5, 0, 0, block_q, block_k, 0, 0)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, ct)]
    ref = jax.vjp(lambda q, k, v: _reference_attention(
        q, k, v, *static[:4]), *f32[:3])[1](f32[3])[0]
    _, residuals = pa._flash_fwd(q, k, v, *static)
    dq = pa._flash_bwd(*static, residuals, ct)[0]
    assert dq.dtype == jnp.bfloat16
    assert _rms_error(dq, ref) < 2 ** -8

    rounded = jnp.zeros_like(dq)
    for j in range(0, t, block_k):
        part = (q, k[:, :, j:j + block_k], v[:, :, j:j + block_k],
                *residuals[3:])
        rounded = rounded + pa._flash_bwd(*static, part, ct)[0]
    assert rounded.dtype == jnp.bfloat16
    assert _rms_error(rounded, ref) > 1.5 * 2 ** -8
