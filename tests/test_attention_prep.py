"""The one pass between attention's projections and the flash kernels
(``ops/attention_prep.py``) against the array passes it replaces.

Pallas interpret mode on the CPU, head width 128 (the pass takes heads
of whole lane tiles). The reference is what
``models/transformer.Attention`` runs without the pass: ``RMSNorm`` →
``apply_rope`` → ``transpose(0, 2, 1, 3)``, and ``jax.grad`` of it.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer as tr
from horovod_tpu.models.transformer import (
    Attention, RMSNorm, Transformer, TransformerConfig, apply_rope,
    fuses_qk_prep, rope_frequencies)
from horovod_tpu.ops import attention_prep as ap
from horovod_tpu.ops.pallas_attention import make_flash_attention_fn
from horovod_tpu.utils import scopes

D, EPS, MAX_LEN = 128, 1e-6, 64
# one bf16 place: results of either path are rounded to bf16 at the same
# points, so they differ by the last place where a float32 sum was
# ordered otherwise
BF16_PLACE = 2.0 ** -7

VARIANTS = {"norm+rope": (True, True), "rope": (False, True),
            "norm": (True, False)}
HEADS = {"32over4": (32, 4), "equal": (4, 4)}


def _positions(kind, b, t):
    if kind == "arange":
        return jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    # the block-diffusion cell's [noisy ; clean] halves repeat them,
    # and a second sequence starts elsewhere
    half = -(-t // 2)
    row = jnp.arange(t) % half
    return jnp.stack([(row + 3 * i) % MAX_LEN for i in range(b)])


def _case(heads, t, norm, rope, positions="halves", b=2, seed=0):
    h, kh = HEADS[heads]
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (b, t, h, D), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, t, kh, D), jnp.bfloat16)
    scales = tuple(1 + 0.2 * jax.random.normal(key, (D,), jnp.float32)
                   for key in keys[2:4]) if norm else (None, None)
    grads = (jax.random.normal(keys[4], (b, h, t, D), jnp.bfloat16),
             jax.random.normal(keys[5], (b, kh, t, D), jnp.bfloat16))
    tables = rope_frequencies(D, MAX_LEN, 10000.0) if rope else None
    return (q, k, *scales), grads, tables, _positions(positions, b, t)


def _array_passes(tables, positions):
    """`Attention`'s array passes: `RMSNorm`'s own arithmetic (the
    module, with the scale as its parameter), `apply_rope`, transpose."""
    norm = RMSNorm(epsilon=EPS, dtype=jnp.bfloat16)

    def one(x, scale):
        if scale is not None:
            x = norm.apply({"params": {"scale": scale}}, x)
        if tables is not None:
            x = apply_rope(x, *tables, positions)
        return x.transpose(0, 2, 1, 3)

    return lambda q, k, qs, ks: (one(q, qs), one(k, ks))


def _one_pass(tables, positions, rows):
    def fn(q, k, qs, ks):
        rope = ap.rope_rows(*tables, positions) if tables else None
        return ap.qk_prep(q, k, qs, ks, rope, EPS, rows)

    return fn


def _close(mine, theirs, what):
    mine, theirs = (np.asarray(x, dtype=np.float32) for x in (mine, theirs))
    assert mine.shape == theirs.shape, what
    np.testing.assert_allclose(
        mine, theirs, rtol=BF16_PLACE,
        atol=BF16_PLACE * float(np.max(np.abs(theirs))) / 64, err_msg=what)


# T: whole blocks of 16 rows; a last block that hangs over the end (40 =
# 2 x 16 + 8); one block that is the whole array and no multiple of 8
@pytest.mark.parametrize("t,rows", [(32, 16), (40, 16), (20, 256)])
@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("variant", VARIANTS)
def test_forward_is_the_array_passes_to_the_last_bf16_place(
        variant, heads, t, rows):
    norm, rope = VARIANTS[variant]
    primals, _, tables, positions = _case(heads, t, norm, rope)
    mine = _one_pass(tables, positions, rows)(*primals)
    theirs = _array_passes(tables, positions)(*primals)
    for a, b, name in zip(mine, theirs, "qk"):
        assert a.dtype == b.dtype == jnp.bfloat16
        _close(a, b, f"{name}' {variant} {heads}")
    # nearly every element is the array passes' to the bit
    same = np.mean([np.mean(np.asarray(a) == np.asarray(b))
                    for a, b in zip(mine, theirs)])
    assert same > 0.99, same


@pytest.mark.parametrize("t,rows", [(32, 16), (40, 16), (20, 256)])
@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("variant", VARIANTS)
def test_gradients_are_jax_grad_of_the_array_passes(variant, heads, t, rows):
    """d(raw q), d(raw k) and, with the norm, both d(scale), for
    cotangents in the kernels' layout; a last block that hangs over the
    end adds nothing of what lies past it to d(scale)."""
    norm, rope = VARIANTS[variant]
    primals, grads, tables, positions = _case(heads, t, norm, rope)
    live = [i for i, a in enumerate(primals) if a is not None]

    def loss(fn):
        def of_live(*given):
            full = list(primals)
            for i, a in zip(live, given):
                full[i] = a
            return sum(jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))
                       for o, g in zip(fn(*full), grads))
        return of_live

    given = [primals[i] for i in live]
    mine = jax.grad(loss(_one_pass(tables, positions, rows)),
                    tuple(range(len(live))))(*given)
    theirs = jax.grad(loss(_array_passes(tables, positions)),
                      tuple(range(len(live))))(*given)
    names = ("d raw q", "d raw k", "d q_scale", "d k_scale")
    for a, b, name in zip(mine, theirs, names):
        assert a.dtype == b.dtype and np.all(np.isfinite(np.asarray(
            a, dtype=np.float32))), name
        _close(a, b, f"{name} {variant} {heads}")


@pytest.mark.parametrize("kind", ["arange", "halves"])
def test_positions_need_not_be_arange(kind):
    primals, _, tables, positions = _case("32over4", 32, True, True, kind)
    mine = _one_pass(tables, positions, 16)(*primals)
    theirs = _array_passes(tables, positions)(*primals)
    for a, b in zip(mine, theirs):
        _close(a, b, kind)
    if kind == "halves":  # and they matter: arange's rows are others
        other = _one_pass(tables, _positions("arange", 2, 32), 16)(*primals)
        assert not np.array_equal(np.asarray(other[0]), np.asarray(mine[0]))


def test_rope_rows_carry_rotate_halfs_sign():
    cos, sin = rope_frequencies(D, MAX_LEN, 10000.0)
    positions = _positions("halves", 2, 24)
    rows = ap.rope_rows(cos, sin, positions)
    assert rows.shape == (2, 24, 2 * D) and rows.dtype == jnp.float32
    c, s = rows[..., :D], rows[..., D:]
    np.testing.assert_array_equal(c[..., :D // 2], c[..., D // 2:])
    np.testing.assert_array_equal(s[..., :D // 2], -s[..., D // 2:])
    np.testing.assert_array_equal(s[..., D // 2:], sin[positions])


def test_a_head_has_to_be_whole_lane_tiles():
    assert ap.supports(128) and ap.supports(256)
    assert not ap.supports(64) and not ap.supports(96)


# -- at Attention's level ----------------------------------------------------

SMALL = TransformerConfig(
    vocab_size=96, num_layers=2, num_heads=4, num_kv_heads=2,
    hidden_size=64, head_dim=128, max_seq_len=MAX_LEN, norm="rmsnorm",
    position="rope", activation="swiglu", tie_embeddings=False,
    qk_norm=True, layernorm_epsilon=EPS)
CONFIGS = {
    "norm+rope": SMALL,
    "rope": dataclasses.replace(SMALL, qk_norm=False),
    "norm": dataclasses.replace(SMALL, position="learned"),
}


def _flash(cfg):
    return make_flash_attention_fn(causal=cfg.causal)


def _tokens(b=2, t=24):
    return jax.random.randint(jax.random.PRNGKey(1), (b, t), 0, 96)


def _paths(tree):
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


def _pass_calls(fn, *args):
    """Names of the `pallas_call`s `fn` traces, loops and rematerialised
    blocks included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else [value]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from walk(inner)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("variant", CONFIGS)
def test_the_parameter_tree_is_the_array_paths_path_for_path(variant):
    cfg, toks = CONFIGS[variant], _tokens()
    plain = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0), toks)
    fused = jax.eval_shape(Transformer(cfg, attention_fn=_flash(cfg)).init,
                           jax.random.PRNGKey(0), toks)
    assert _paths(fused) == _paths(plain)
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), fused) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), plain)
    if cfg.qk_norm:
        assert "['params']['block_0']['attn']['q_norm']['scale']" in \
            _paths(fused)


def _loss(model):
    def fn(params, toks):
        hidden = model.apply(params, toks, return_hidden=True)
        return jnp.mean(hidden.astype(jnp.float32) ** 2)
    return fn


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("variant", CONFIGS)
def test_transformer_agrees_with_the_default_attention(variant, remat):
    """Output and every leaf of `jax.grad` of a small routed-free
    `Transformer` (float32, so that what is compared is the two paths'
    arithmetic and not bf16's rounding) through the flash function and
    the one pass against `attention_fn=None`, whose q/k norms and rope
    are the array passes; under `nn.remat` too, where the last block
    keeps the flash calls' results and rebuilds the pass."""
    cfg = dataclasses.replace(CONFIGS[variant], dtype=jnp.float32,
                              remat=remat)
    toks = _tokens()
    plain, fused = Transformer(cfg), Transformer(cfg,
                                                 attention_fn=_flash(cfg))
    params = plain.init(jax.random.PRNGKey(0), toks)
    params = jax.tree_util.tree_map(  # scales away from their ones
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                              p.shape, p.dtype), params)
    assert scopes.QK_PREP_FWD in _pass_calls(
        lambda p: fused.apply(p, toks), params)
    np.testing.assert_allclose(
        np.asarray(fused.apply(params, toks, return_hidden=True)),
        np.asarray(plain.apply(params, toks, return_hidden=True)),
        rtol=2e-4, atol=2e-5)
    g_plain = jax.grad(_loss(plain))(params, toks)
    g_fused = jax.grad(_loss(fused))(params, toks)
    assert _paths(g_fused) == _paths(g_plain)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_plain),
                            jax.tree_util.tree_leaves(g_fused)):
        scale = float(jnp.max(jnp.abs(a)))
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=2e-3, atol=2e-4 * scale,
            err_msg=jax.tree_util.keystr(path))


def _attention_calls(cfg, attention_fn, kv_cache=None):
    b, t = 2, 16
    x = jnp.zeros((b, t, cfg.hidden_size), cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    module = Attention(cfg, attention_fn=attention_fn)
    params = jax.eval_shape(
        Attention(cfg).init, jax.random.PRNGKey(0), x, positions)
    return _pass_calls(
        lambda p: module.apply(p, x, positions, kv_cache=kv_cache), params)


class _Cache:
    """The serving path's carrier, as far as `Attention` reads it."""

    def update(self, layer, k, v, positions):
        valid = positions[:, :, None] >= jnp.arange(k.shape[1])[None, None]
        return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), valid


def _ring_like(q, k, v):
    """An attention function that takes the model's layout and offers
    no other (ring, Ulysses)."""
    return tr.dot_product_attention(q, k, v, causal=True)


KEEP_ARRAY_PASSES = {
    "head width 64": lambda: (dataclasses.replace(SMALL, head_dim=64),
                              _flash(SMALL), None),
    "kv_cache": lambda: (SMALL, _flash(SMALL), _Cache()),
    "ring": lambda: (SMALL, _ring_like, None),
    "default attention": lambda: (SMALL, None, None),
    "no norms, learned positions": lambda: (
        dataclasses.replace(SMALL, qk_norm=False, position="learned"),
        _flash(SMALL), None),
}


@pytest.mark.parametrize("case", KEEP_ARRAY_PASSES)
def test_what_the_pass_cannot_serve_keeps_the_array_passes(case):
    cfg, attention_fn, kv_cache = KEEP_ARRAY_PASSES[case]()
    assert not fuses_qk_prep(cfg, attention_fn, kv_cache)
    calls = _attention_calls(cfg, attention_fn, kv_cache)
    assert not {scopes.QK_PREP_FWD, scopes.QK_PREP_BWD} & set(calls), calls


@pytest.mark.parametrize("variant", CONFIGS)
def test_the_flash_function_at_head_width_128_takes_the_pass(variant):
    cfg = CONFIGS[variant]
    assert fuses_qk_prep(cfg, _flash(cfg))
    assert _attention_calls(cfg, _flash(cfg)) == [
        scopes.QK_PREP_FWD, scopes.FLASH_FWD]


def test_a_padding_mask_is_refused_on_the_fused_path_too():
    cfg, x = SMALL, jnp.zeros((1, 8, 64), jnp.bfloat16)
    positions = jnp.arange(8)[None]
    module = Attention(cfg, attention_fn=_flash(cfg))
    with pytest.raises(ValueError, match="padding mask"):
        jax.eval_shape(module.init, jax.random.PRNGKey(0), x, positions,
                       jnp.ones((1, 8), bool))


def test_the_last_block_keeps_the_flash_calls_and_not_the_pass():
    """`_last_block_keeps` by kernel name: the two flash calls' results
    are kept, the one pass's (q and k after norm and rope, 144 MiB in
    `sdar_bd_s4096`) are rebuilt; and in the gradient of a model under
    `remat` the last block's second run holds the pass again and no
    flash forward, the others' hold both."""
    class Prim:
        def __init__(self, name):
            self.name = name

    keeps = tr._last_block_keeps
    for name, want in ((scopes.FLASH_FWD, True), (scopes.FLASH_BWD, True),
                       (scopes.QK_PREP_FWD, False),
                       (scopes.QK_PREP_BWD, False), (None, False)):
        assert keeps(Prim("pallas_call"), name=name) is want, name
    assert keeps(Prim("dot_general")) and keeps(Prim("top_k"))
    assert not keeps(Prim("mul")) and not keeps(Prim("transpose"))

    cfg = dataclasses.replace(SMALL, num_layers=3, remat=True)
    model = Transformer(cfg, attention_fn=_flash(cfg))
    params = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0),
                            _tokens())
    calls = _pass_calls(jax.grad(_loss(model)), params, _tokens())
    count = {name: calls.count(name) for name in set(calls)}
    layers = cfg.num_layers
    assert count == {
        scopes.QK_PREP_FWD: 2 * layers,      # every block rebuilds it
        scopes.FLASH_FWD: 2 * layers - 1,    # the last block does not
        scopes.QK_PREP_BWD: layers, scopes.FLASH_BWD: layers}, count
