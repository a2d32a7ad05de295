"""The routed MLP (`models/moe.RoutedMlp`) and the model around it
against the plain reference `benchmarks/reference/block_diffusion_moe_lm.py`,
at small sizes on the CPU with seeded float32 weights."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks.jobs import dp_train  # noqa: E402
from horovod_tpu.models import moe  # noqa: E402
from horovod_tpu.models.moe import RoutedMlp  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    Transformer, TransformerConfig)
from horovod_tpu.utils import metrics  # noqa: E402

REFERENCE = harness.load_reference("block_diffusion_moe_lm")
H, M, E, K = 16, 8, 128, 8


def layer(held=16, first=0, e=E, k=K, **kw):
    return RoutedMlp(num_experts=e, experts_held=held, experts_per_token=k,
                     mlp_dim=M, first_expert=first, dtype=jnp.float32, **kw)


def seeded(e=E, tokens=48, seed=0):
    """An uncut layer's parameters (all `e` experts) and its input."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, H))
    whole = layer(held=e, e=e, k=min(K, e))
    return whole.init(jax.random.PRNGKey(seed + 1), x)["params"], x


def share_of(params, first, held):
    """The parameters of the share that holds `held` experts from
    `first`: the whole router, a slice of every expert matrix."""
    cut = {name: params[name][first:first + held]
           for name in ("gate", "up", "down")}
    return {"router": params["router"], **cut}


def reference_layer(params, x, choices=None, first=0, k=K):
    with jax.default_matmul_precision("highest"):
        return REFERENCE._routed(
            x, params, choices, first_expert=first, per_token=k,
            renormalise=True)[0]


def test_eight_shares_add_up_to_the_uncut_layer_of_the_reference():
    """Eight chips of 16 experts each, `first_expert` 0, 16, ..., 112:
    their parts of the result, summed, are what the reference gives for
    the layer with all 128 experts."""
    params, x = seeded()
    total = sum(
        layer(first=first).apply({"params": share_of(params, first, 16)}, x)
        for first in range(0, E, 16))
    want = reference_layer(params, x)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    # and one share alone is the reference's share, not the whole
    one = layer(first=32).apply({"params": share_of(params, 32, 16)}, x)
    np.testing.assert_allclose(
        one, reference_layer(share_of(params, 32, 16), x, first=32),
        rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(one - want).max()) > 1e-3


def test_a_layer_that_holds_every_expert_is_the_gated_mix_of_them():
    """The dense statement: with all experts held the result is the sum
    over a token's k experts of renormalised weight x expert(x)."""
    params, x = seeded(e=4)
    got = layer(held=4, e=4, k=2).apply({"params": params}, x)
    probs = jax.nn.softmax(x @ params["router"]["kernel"], -1)
    weights, chosen = jax.lax.top_k(probs, 2)
    weights = weights / weights.sum(-1, keepdims=True)
    every = jnp.einsum(
        "tem,emh->teh",
        jax.nn.silu(jnp.einsum("th,ehm->tem", x, params["gate"]))
        * jnp.einsum("th,ehm->tem", x, params["up"]), params["down"])
    want = sum(weights[:, j, None]
               * jnp.take_along_axis(every, chosen[:, j, None, None], 1)[:, 0]
               for j in range(2))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def rigged(params, sign):
    """The router made to send every token to the first 16 experts
    (`sign` +1) or to none of them (-1): inputs are positive below."""
    kernel = params["router"]["kernel"]
    bias = jnp.where(jnp.arange(E) < 16, sign * 5.0, 0.0)
    return {**params, "router": {"kernel": kernel + bias[None] / H}}


def test_every_token_routed_here_is_computed_exactly_none_dropped(
        monkeypatch):
    """All 8 choices of all tokens live here: 8 x the expected rows, eight
    products of `rows_static`, the last seven under their `cond`. The
    result and the gradients are the reference's."""
    monkeypatch.setattr(moe, "ROWS_MULTIPLE", 8)  # products of 48 rows
    params, x = seeded()
    x = jnp.abs(x) + 0.5
    params = rigged(params, +1)
    mine = share_of(params, 0, 16)
    assert moe.rows_static(x.shape[0], K, 16, E) == (48.0, 48, 384)

    def loss(fn):
        return lambda p, x: jnp.sum(jnp.sin(fn(p, x)))

    system = loss(lambda p, x: layer().apply({"params": p}, x))
    plain = loss(lambda p, x: reference_layer(p, x))
    out, sown = layer().apply({"params": mine}, x, mutable=["choices"])
    assert int(jnp.sum(sown["choices"]["experts"][0] < 16)) == 48 * K
    np.testing.assert_allclose(out, reference_layer(mine, x), rtol=2e-5,
                               atol=2e-6)
    got = jax.grad(system, argnums=(0, 1))(mine, x)
    want = jax.grad(plain, argnums=(0, 1))(mine, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)


def test_no_token_routed_here_gives_zero_and_no_gradient_to_the_experts():
    params, x = seeded()
    x = jnp.abs(x) + 0.5
    mine = share_of(rigged(params, -1), 0, 16)
    out, sown = layer().apply({"params": mine}, x, mutable=["choices"])
    assert int(jnp.sum(sown["choices"]["experts"][0] < 16)) == 0
    assert float(jnp.abs(out).max()) == 0.0
    grads = jax.grad(lambda p: jnp.sum(
        layer().apply({"params": p}, x) ** 2))(mine)
    assert all(float(jnp.abs(grads[n]).max()) == 0.0
               for n in ("gate", "up", "down"))


@pytest.mark.parametrize("seed", [0, 1, 2147483801])
def test_a_shares_seeded_router_sends_it_the_even_share_on_every_seed(seed):
    """A share's seeded router repeats its held columns chip by chip, so
    every token's top 8 of 128 are one expert a chip, whatever the token:
    the rows routed to each of the eight shares are the even share to
    the row, on inputs that are alike as on inputs that are not."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (48, H))
    alike = x[:1] + 0.01 * x
    params = layer().init(jax.random.PRNGKey(seed + 1), x)["params"]
    kernel = params["router"]["kernel"]
    assert kernel.shape == (H, E)
    np.testing.assert_array_equal(kernel, jnp.tile(kernel[:, :16], (1, 8)))
    assert float(jnp.std(kernel)) == pytest.approx(0.02, rel=0.2)
    for tokens in (x, alike):
        _, sown = layer().apply({"params": params}, tokens,
                                mutable=["choices"])
        chips = np.asarray(sown["choices"]["experts"][0]) // 16
        assert (np.sort(chips, axis=-1) == np.arange(8)).all()
    # an uncut layer's router is seeded column by column
    whole = layer(held=E).init(jax.random.PRNGKey(seed + 1), x)["params"]
    assert float(jnp.abs(whole["router"]["kernel"][:, :16]
                         - whole["router"]["kernel"][:, 16:32]).max()) > 0


def test_a_shares_router_is_not_trained_and_an_uncut_layers_is():
    """No exchange, no gradient through the scores: a share's router has
    a zero gradient and passes none to its input, in the program as in
    the reference; the layer that holds every expert trains its router."""
    params, x = seeded()
    mine = share_of(params, 0, 16)

    def through(fn):
        return jax.grad(lambda p, x: jnp.sum(jnp.sin(fn(p, x))),
                        argnums=(0, 1))

    got, got_x = through(
        lambda p, x: layer().apply({"params": p}, x))(mine, x)
    want, want_x = through(lambda p, x: reference_layer(p, x))(mine, x)
    for grads in (got, want):
        assert float(jnp.abs(grads["router"]["kernel"]).max()) == 0.0
        assert float(jnp.abs(grads["gate"]).max()) > 0
    np.testing.assert_allclose(got_x, want_x, rtol=2e-4, atol=2e-6)
    whole, whole_ref = (
        fn(params, x)[0]["router"]["kernel"] for fn in (
            through(lambda p, x: layer(held=E).apply({"params": p}, x)),
            through(lambda p, x: reference_layer(p, x))))
    assert float(jnp.abs(whole).max()) > 0
    np.testing.assert_allclose(whole, whole_ref, rtol=2e-4, atol=2e-6)


def test_choices_are_sown_as_integers_and_no_auxiliary_term_is():
    params, x = seeded()
    x = x.reshape(2, 24, H)
    out, sown = layer().apply(
        {"params": share_of(params, 0, 16)}, x,
        mutable=["choices", "losses"])
    assert out.shape == x.shape and set(sown) == {"choices"}
    (chosen,) = sown["choices"]["experts"]
    assert chosen.shape == (2, 24, K) and chosen.dtype == jnp.int32
    # k distinct experts of the router's 128 for every token
    assert int(chosen.min()) >= 0 and int(chosen.max()) < E
    assert all(len(set(row)) == K
               for row in np.asarray(chosen).reshape(-1, K))


def test_weights_are_left_as_the_softmax_gives_them_without_renormalising():
    params, x = seeded()
    mine = share_of(params, 0, 16)
    got = layer(norm_topk_prob=False).apply({"params": mine}, x)
    with jax.default_matmul_precision("highest"):
        want = REFERENCE._routed(x, mine, None, first_expert=0,
                                 per_token=K, renormalise=False)[0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(got - reference_layer(mine, x)).max()) > 1e-4


@pytest.mark.parametrize("tokens,k,held,e,want", [
    # the cell: 16,384 positions, 16 of 128 held, top 8
    (16384, 8, 16, 128, (16384.0, 16384, 131072)),
    # every expert held: every pair is routed here, one product
    (1024, 2, 4, 4, (2048.0, 2048, 2048)),
    # fewer held than a token chooses
    (512, 8, 4, 128, (128.0, 512, 2048)),
    # an even share under one row is still a product of some rows
    (8, 1, 1, 128, (0.0625, 8, 8)),
])
def test_rows_static(tokens, k, held, e, want):
    assert moe.rows_static(tokens, k, held, e) == want


def test_trace_time_gauges_say_what_the_layer_was_built_for():
    was = metrics.enabled()
    metrics.enable()
    metrics.registry.clear()
    try:
        params, x = seeded()
        layer().apply({"params": share_of(params, 0, 16)}, x)
        snap = metrics.registry.snapshot()
    finally:
        metrics.registry.clear()
        if not was:
            metrics.disable()
    got = {name: value for name, series in snap.items()
           if name.startswith("hvd_moe_") for value in series.values()}
    assert got == {"hvd_moe_experts_held": 16, "hvd_moe_router_width": 128,
                   "hvd_moe_rows_expected": 48, "hvd_moe_rows_static": 384,
                   "hvd_moe_shared_experts": 0, "hvd_moe_score_func": 1}
    assert set(snap["hvd_moe_score_func"]) == {"softmax"}


@pytest.mark.parametrize("sizes,want", [
    # 256 tokens x 4 choices x 4 of 16 held = 256 rows, rounded to one
    # row tile of 512; hidden 128 and experts of 256: a shape the
    # kernels take (`ops/grouped_matmul.supports`)
    (dict(hidden_size=128, expert_mlp_dim=256), (2, 0)),
    # the same rows through experts of 64: no whole lane tile
    (dict(hidden_size=128, expert_mlp_dim=64), (0, 2)),
    # two dense layers first: one routed layer is left of three
    (dict(hidden_size=128, expert_mlp_dim=256, num_layers=3,
          dense_layers=2), (1, 0)),
])
def test_the_models_gauges_say_how_many_layers_products_are_kernels(
        sizes, want):
    """`hvd_moe_expert_kernel_layers` / `hvd_moe_expert_plain_layers`,
    set while the model is traced, from shapes alone
    (`moe.experts_run_as_kernels`)."""
    cfg = TransformerConfig(**{**SIZES, "max_seq_len": 128, **sizes})
    model = Transformer(cfg)
    toks = jnp.zeros((2, 128), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), toks)
    was = metrics.enabled()
    metrics.enable()
    metrics.registry.clear()
    try:
        jax.eval_shape(lambda p: model.apply(p, toks), params)
        snap = metrics.registry.snapshot()
    finally:
        metrics.registry.clear()
        if not was:
            metrics.disable()
    got = {name: value for name, series in snap.items()
           if name.startswith("hvd_moe_expert_")
           for value in series.values()}
    assert got == {"hvd_moe_expert_kernel_layers": want[0],
                   "hvd_moe_expert_plain_layers": want[1]}


def test_a_layer_is_refused_experts_it_cannot_hold():
    params, x = seeded()
    with pytest.raises(ValueError, match="experts_held 16 from "
                                         "first_expert 120"):
        layer(first=120).apply({"params": share_of(params, 0, 16)}, x)


# -- the model around it -----------------------------------------------------

SIZES = dict(
    vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
    hidden_size=16, mlp_ratio=3.0, max_seq_len=32, norm="rmsnorm",
    position="rope", activation="swiglu", causal=False, diffusion_block=4,
    qk_norm=True, tie_embeddings=False, rope_theta=1e6,
    layernorm_epsilon=1e-6, num_experts=16, experts_held=4,
    experts_per_token=4, expert_mlp_dim=8, norm_topk_prob=True)
TRAFFIC = {"objective": "block_diffusion", "seq_len": 16, "t_min": 0.1,
           "attention": "xla", "loss_head": "fused_ce",
           "batch_per_chip": 2}


def test_head_dim_is_a_field_stated_apart_from_the_quotient():
    cfg = TransformerConfig(**SIZES)
    assert {k: v for k, v in dataclasses.asdict(cfg).items()
            if k in SIZES} == SIZES
    assert cfg.head_width == 8 != cfg.hidden_size // cfg.num_heads
    # unstated it is the quotient, and follows a `replace`
    plain = TransformerConfig(hidden_size=64, num_heads=4)
    assert plain.head_dim is None and plain.head_width == 16
    assert dataclasses.replace(plain, hidden_size=128).head_width == 32
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    attn = params["block_0"]["attn"]
    assert attn["query"]["kernel"].shape == (16, 4, 8)
    assert attn["key"]["kernel"].shape == (16, 2, 8)
    assert attn["out"]["kernel"].shape == (4, 8, 16)
    assert attn["q_norm"]["scale"].shape == (8,) == \
        attn["k_norm"]["scale"].shape
    mlp = params["block_0"]["mlp"]
    assert mlp["router"]["kernel"].shape == (16, 16)
    assert mlp["gate"].shape == (4, 16, 8) and mlp["down"].shape == (4, 8, 16)


@pytest.mark.parametrize("attention", ["xla", "flash"])
def test_model_against_the_reference_at_imposed_choices(attention):
    """Loss and gradient of the job's loss function (the model built
    from the group, float32 here) against the plain reference, which is
    given the system's choices; and the reference's own scores make
    those choices."""
    sizes = {**SIZES, "dtype": jnp.float32}
    traffic = {**TRAFFIC, "attention": attention}
    _, model, plain = dp_train.make_model(sizes, traffic)
    batch = tuple(jnp.asarray(a) for a in dp_train.make_batch(
        sizes, traffic, 2, seed=3))
    params = plain.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    # seeded weights with a spread: a unit norm scale hides its gradient
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(
            jax.random.PRNGKey(p.size), p.shape), params)
    loss_fn = dp_train.make_loss_fn(model, traffic, with_choices=True)
    (loss, choices), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, *batch)
    kw = REFERENCE.arguments(SIZES, traffic)
    assert sorted(choices) == [REFERENCE.choice_name(i) for i in range(2)]
    want, want_g = jax.value_and_grad(lambda p: REFERENCE.mean_loss(
        p, batch, choices=choices, **kw))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    diff = jax.tree_util.tree_map(lambda a, b: a - b, grads, want_g)
    assert float(optax.global_norm(diff)
                 / optax.global_norm(want_g)) < 2e-4
    # every leaf has a gradient on both sides, the new ones too; but a
    # share's router, which is not trained, has none on either
    for side in (grads, want_g):
        for path, g in jax.tree_util.tree_leaves_with_path(side):
            routers = "router" in jax.tree_util.keystr(path)
            assert (float(jnp.abs(g).max()) > 0) != routers
    agree, near, count = dp_train.choices_agreement(
        REFERENCE.choice_scores(params, batch, **kw), choices)
    assert int(count) == 2 * 2 * 32 and int(agree) >= int(count) - 2


def test_the_reference_takes_no_other_family():
    with pytest.raises(ValueError, match="causal_lm"):
        REFERENCE.arguments(SIZES, {**TRAFFIC, "objective": "causal_lm"})
    with pytest.raises(ValueError, match="q/k norms"):
        REFERENCE.arguments({**SIZES, "qk_norm": False}, TRAFFIC)
    with pytest.raises(KeyError):
        REFERENCE.arguments({k: v for k, v in SIZES.items()
                             if k != "experts_held"}, TRAFFIC)
