"""Model family shape/numerics smoke tests + distributed training step."""

import dataclasses

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import (
    Bert,
    MnistNet,
    ResNet50,
    Transformer,
    TransformerConfig,
    causal_lm_loss,
    mlm_loss,
)

TINY_GPT = TransformerConfig(
    vocab_size=128, num_layers=2, num_heads=4, hidden_size=64,
    max_seq_len=32, dtype=jnp.float32,
)
TINY_LLAMA = dataclasses.replace(
    TINY_GPT, norm="rmsnorm", position="rope", activation="swiglu",
    tie_embeddings=False, num_kv_heads=2,
)
TINY_BERT = dataclasses.replace(TINY_GPT, causal=False)


def test_mnist_net_shapes():
    m = MnistNet()
    x = jnp.zeros((4, 28, 28, 1))
    params = m.init(jax.random.PRNGKey(0), x)
    out = m.apply(params, x)
    assert out.shape == (4, 10)


def test_resnet50_shapes():
    m = ResNet50(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 64, 64, 3))
    variables = m.init(jax.random.PRNGKey(0), x, train=False)
    out = m.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    assert "batch_stats" in variables


def test_gpt2_forward_and_loss():
    m = Transformer(TINY_GPT)
    toks = jnp.ones((2, 16), dtype=jnp.int32)
    params = m.init(jax.random.PRNGKey(0), toks)
    logits = m.apply(params, toks)
    assert logits.shape == (2, 16, 128)
    loss, n = causal_lm_loss(logits, toks)
    assert np.isfinite(float(loss))


def test_llama_forward():
    m = Transformer(TINY_LLAMA)
    toks = jnp.ones((2, 16), dtype=jnp.int32)
    params = m.init(jax.random.PRNGKey(0), toks)
    logits = m.apply(params, toks)
    assert logits.shape == (2, 16, 128)
    # GQA params: kv heads = 2
    k_kernel = params["params"]["block_0"]["attn"]["key"]["kernel"]
    assert k_kernel.shape == (64, 2, 16)


def test_bert_mlm():
    m = Transformer(TINY_BERT)
    toks = jnp.ones((2, 16), dtype=jnp.int32)
    params = m.init(jax.random.PRNGKey(0), toks)
    logits = m.apply(params, toks)
    mask = jnp.zeros((2, 16), dtype=bool).at[:, 3].set(True)
    loss, n = mlm_loss(logits, toks, mask)
    assert np.isfinite(float(loss))
    assert int(n) == 2


def test_causality():
    """Future tokens must not influence past logits in causal mode."""
    m = Transformer(TINY_GPT)
    t1 = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], dtype=jnp.int32)
    t2 = t1.at[0, -1].set(99)
    params = m.init(jax.random.PRNGKey(0), t1)
    l1 = m.apply(params, t1)
    l2 = m.apply(params, t2)
    np.testing.assert_allclose(
        np.asarray(l1[0, :-1]), np.asarray(l2[0, :-1]), rtol=1e-5
    )


def _remat_pair(num_layers):
    """A tiny model without `remat` and with it, one parameter tree."""
    cfg = dataclasses.replace(TINY_GPT, num_layers=num_layers)
    plain = Transformer(cfg)
    remat = Transformer(dataclasses.replace(cfg, remat=True))
    toks = jnp.asarray(
        np.random.RandomState(num_layers).randint(0, 128, size=(2, 8)),
        dtype=jnp.int32)
    return plain, remat, plain.init(jax.random.PRNGKey(0), toks), toks


def _head_loss(model, return_hidden):
    """A scalar loss over the model's own dense logits, or over the
    hidden state handed to the fused cross entropy (the tied head)."""
    from horovod_tpu.ops.fused_cross_entropy import \
        fused_linear_cross_entropy

    def loss(params, toks):
        out = model.apply(params, toks, return_hidden=return_hidden)
        if not return_hidden:
            return causal_lm_loss(out, toks)[0]
        w = params["params"]["tok_emb"]["embedding"].T
        return fused_linear_cross_entropy(
            out[:, :-1], w, toks[:, 1:], block_vocab=64)[0]
    return loss


def _eqns(jaxpr, name):
    """Equations of primitive ``name`` in the jaxpr and those inside."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, name)


def _grad_jaxpr(model, return_hidden, params, toks):
    return jax.make_jaxpr(jax.grad(_head_loss(model, return_hidden)))(
        params, toks).jaxpr


def _paths(tree):
    return [jax.tree_util.keystr(k)
            for k, _ in jax.tree_util.tree_leaves_with_path(tree)]


@pytest.mark.parametrize("return_hidden", [False, True])
@pytest.mark.parametrize("num_layers", [1, 2, 3])
def test_remat_matches_no_remat(num_layers, return_hidden):
    """`remat` changes what is kept for the backward pass and nothing
    that is computed: the outputs, the parameter tree's paths and every
    leaf of a scalar loss's gradient are the un-rematerialised model's,
    on the dense-logits path (every block rematerialised) and on the
    hidden-state path (the last block kept)."""
    plain, remat, params, toks = _remat_pair(num_layers)
    np.testing.assert_allclose(
        np.asarray(plain.apply(params, toks, return_hidden=return_hidden)),
        np.asarray(remat.apply(params, toks, return_hidden=return_hidden)),
        rtol=1e-5,
    )
    assert _paths(remat.init(jax.random.PRNGKey(0), toks)) == _paths(params)
    g_plain = jax.grad(_head_loss(plain, return_hidden))(params, toks)
    g_remat = jax.grad(_head_loss(remat, return_hidden))(params, toks)
    assert _paths(g_remat) == _paths(g_plain)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_plain),
                            jax.tree_util.tree_leaves(g_remat)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-7,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("return_hidden", [False, True])
@pytest.mark.parametrize("num_layers", [1, 2, 3])
def test_remat_keeps_the_last_block_before_a_callers_head(
        num_layers, return_hidden):
    """The gradient's jaxpr holds one `remat2` equation a block under
    `remat` and none without. Where the model builds the logits itself
    every one of them keeps nothing; where the caller takes the hidden
    state to its own head the last block's has the policy that keeps
    its matrix products, so the gradient runs a block's forward
    products again once for every block but the last."""
    from horovod_tpu.models.transformer import _last_block_keeps

    plain, remat, params, toks = _remat_pair(num_layers)
    j_plain = _grad_jaxpr(plain, return_hidden, params, toks)
    j_remat = _grad_jaxpr(remat, return_hidden, params, toks)
    assert not list(_eqns(j_plain, "remat2"))
    policies = [e.params["policy"] for e in _eqns(j_remat, "remat2")]
    kept = int(return_hidden)
    # in the order the backward pass runs them: the last block first
    assert policies == [_last_block_keeps] * kept \
        + [None] * (num_layers - kept)

    # a block's second run is seven products: q, k, v, scores, values,
    # out and fc1 (fc2's result is dead code in it)
    dots = [len(list(_eqns(j, "dot_general"))) for j in (j_plain, j_remat)]
    assert dots[1] - dots[0] == 7 * (num_layers - kept)


@pytest.mark.parametrize("remat,return_hidden,want", [
    (True, True, (4, 1)), (True, False, (5, 0)),
    (False, True, (0, 0)), (False, False, (0, 0))])
def test_remat_gauges_say_what_was_rematerialised_and_kept(
        remat, return_hidden, want):
    """`hvd_remat_blocks` / `hvd_remat_blocks_kept`, set while the model
    is traced, on both head paths and without `remat`."""
    from horovod_tpu.utils import metrics

    cfg = dataclasses.replace(TINY_GPT, num_layers=5, remat=remat)
    model = Transformer(cfg)
    toks = jnp.ones((1, 8), dtype=jnp.int32)
    was = metrics.enabled()
    metrics.enable()
    metrics.registry.clear()
    try:
        jax.eval_shape(
            lambda t: model.init(jax.random.PRNGKey(0), t,
                                 return_hidden=return_hidden), toks)
        snap = metrics.registry.snapshot()
    finally:
        metrics.registry.clear()
        if not was:
            metrics.disable()
    got = {name: value for name, series in snap.items()
           if name.startswith("hvd_remat_") for value in series.values()}
    assert got == {"hvd_remat_blocks": want[0],
                   "hvd_remat_blocks_kept": want[1]}


@pytest.mark.parametrize("flash,want", [(True, (3, 0)), (False, (0, 3))])
def test_attn_prep_gauges_say_how_many_layers_took_the_one_pass(flash, want):
    """`hvd_attn_prep_fused_layers` / `hvd_attn_prep_plain_layers`, set
    while the model is traced (once a trace of the model, whatever
    `remat` traces again): heads of 128 with q/k norms and rope run the
    one pass of `ops/attention_prep.py` behind the flash function and
    the array passes behind the default attention."""
    from horovod_tpu.ops.pallas_attention import make_flash_attention_fn
    from horovod_tpu.utils import metrics

    cfg = dataclasses.replace(
        TINY_LLAMA, num_layers=3, head_dim=128, qk_norm=True, remat=True)
    model = Transformer(
        cfg, attention_fn=make_flash_attention_fn() if flash else None)
    toks = jnp.ones((1, 8), dtype=jnp.int32)
    params = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0),
                            toks)
    was = metrics.enabled()
    metrics.enable()
    metrics.registry.clear()
    try:
        jax.eval_shape(jax.grad(lambda p: jnp.sum(model.apply(
            p, toks, return_hidden=True))), params)
        snap = metrics.registry.snapshot()
    finally:
        metrics.registry.clear()
        if not was:
            metrics.disable()
    got = {name: value for name, series in snap.items()
           if name.startswith("hvd_attn_prep_") for value in series.values()}
    assert got == {"hvd_attn_prep_fused_layers": want[0],
                   "hvd_attn_prep_plain_layers": want[1]}


def test_distributed_gpt2_train_step(hvd8):
    """End-to-end: tiny GPT-2 DP training step across the 8-device mesh
    with DistributedOptimizer — loss decreases."""
    m = Transformer(TINY_GPT)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 128, size=(16, 16)), dtype=jnp.int32)
    params = m.init(jax.random.PRNGKey(0), toks[:2])
    opt = hvd.DistributedOptimizer(optax.adam(1e-3))
    opt_state = opt.init(params)

    def step(p, s, batch):
        def loss_fn(p):
            logits = m.apply(p, batch)
            loss, _ = causal_lm_loss(logits, batch)
            return loss

        loss, g = jax.value_and_grad(loss_fn)(p)
        upd, s = opt.update(g, s, p)
        p = optax.apply_updates(p, upd)
        return p, s, hvd.allreduce(loss)

    jstep = jax.jit(
        shard_map(
            step, mesh=hvd.mesh(),
            in_specs=(P(), P(), P("hvd")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )
    losses = []
    for _ in range(5):
        params, opt_state, loss = jstep(params, opt_state, toks)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


@pytest.mark.slow  # ~30s of InceptionV3 compile for a forward-shape
# smoke of long-stable model code; slow tier per the tier-1 budget
# precedent (this host now runs the suite ~12% slower than the PR-10
# record and prior HEAD already measured 872.9s vs the 870s gate)
def test_inception_v3_forward():
    """InceptionV3 (models/inception.py): published 23.8M params, 1000-way
    logits from 299px input (BASELINE.md row 1's scaling model)."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import InceptionV3

    m = InceptionV3(num_classes=10, dtype=jnp.float32)
    # 160px (not the native 299) keeps the CPU forward cheap; every
    # stem/reduction stage still sees a valid grid
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 160, 160, 3)))
    out, _ = m.apply(v, jnp.ones((2, 160, 160, 3)), train=True,
                     mutable=["batch_stats"])
    assert out.shape == (2, 10)
    assert bool(jnp.isfinite(out).all())


def test_vgg16_forward_and_param_count():
    """VGG-16 (models/vgg.py): the 138M-parameter allreduce stress model
    (BASELINE.md row 3)."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import VGG16

    m = VGG16(num_classes=1000, dtype=jnp.float32)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(v["params"]))
    assert abs(n - 138.36e6) < 0.5e6, n  # published VGG-16 size
    out = m.apply(v, jnp.ones((2, 224, 224, 3)), train=False)
    assert out.shape == (2, 1000)
    assert bool(jnp.isfinite(out).all())


def test_synthetic_benchmark_model_flag():
    """The --model sweep runs every reference tf_cnn_benchmarks name on a
    tiny config (examples/resnet50_synthetic.py)."""
    from horovod_tpu.utils.script_loader import load_example

    bench = load_example("resnet50_synthetic")
    # tiny: 1 iter x 1 batch of 2 at 64px; vgg16 exercises the
    # no-batch-stats path (inception3's full train-step compile costs
    # minutes on the CPU test world — its forward is covered above)
    per_chip, mfu = bench.main(
        ["--model", "vgg16", "--image-size", "64",
         "--batch-size", "2", "--num-warmup-batches", "1",
         "--num-batches-per-iter", "1", "--num-iters", "1",
         "--num-classes", "10"]
    )
    assert per_chip > 0 and mfu is None  # CPU: not measured


def test_resnet_space_to_depth_stem():
    """stem="space_to_depth" (the MLPerf TPU transform: 2x2 unshuffle +
    4x4/s1 conv) keeps the stem's output geometry and trains finitely."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import ResNet
    from horovod_tpu.models.resnet import space_to_depth

    x = jnp.arange(2 * 8 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 8, 3)
    y = space_to_depth(x, 2)
    assert y.shape == (2, 4, 4, 12)
    # block contents: output pixel (0,0) holds input (0,0),(0,1),(1,0),(1,1)
    assert jnp.array_equal(y[0, 0, 0, :3], x[0, 0, 0])
    assert jnp.array_equal(y[0, 0, 0, 3:6], x[0, 0, 1])
    assert jnp.array_equal(y[0, 0, 0, 6:9], x[0, 1, 0])

    m = ResNet(stage_sizes=[1, 1], num_classes=10, num_filters=8,
               dtype=jnp.float32, stem="space_to_depth")
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)))
    out, _ = m.apply(v, jnp.ones((2, 64, 64, 3)), train=True,
                     mutable=["batch_stats"])
    assert out.shape == (2, 10)
    assert bool(jnp.isfinite(out).all())


def _norm_reference(kind, x, params, eps):
    """Both norm kinds in float32 jax.numpy, written out here."""
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        return xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, -1, keepdims=True) + eps) * params["scale"]
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, -1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * params["scale"]
            + params["bias"])


@pytest.mark.parametrize("what", ["value", "gradient"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", ["layernorm", "rmsnorm"])
def test_norm_matches_float32_reference(kind, dtype, what):
    """The one norm every block builds (`transformer._norm`): output in
    the activations' dtype, statistics and parameters in float32."""
    from horovod_tpu.models.transformer import _norm

    cfg = dataclasses.replace(TINY_GPT, norm=kind, dtype=dtype)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 16, 64) * 2 + 0.5, dtype)
    params = {"scale": jnp.asarray(rng.rand(64) + 0.5, jnp.float32)}
    if kind == "layernorm":
        params["bias"] = jnp.asarray(rng.randn(64), jnp.float32)
    weight = jnp.asarray(rng.randn(2, 16, 64), jnp.float32)
    module = _norm(cfg, "ln")
    assert jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype),
        module.init(jax.random.PRNGKey(0), x)["params"]
    ) == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    # bfloat16 keeps 8 bits: the output is rounded once, and so is the
    # gradient that comes back to the activations
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7

    def apply(p, x):
        return module.apply({"params": p}, x)

    def reference(p, x):
        return _norm_reference(kind, x, p, cfg.layernorm_epsilon)

    if what == "value":
        got, want = apply(params, x), reference(params, x)
        assert got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want),
            atol=tol * float(jnp.abs(want).max()), rtol=0)
        return

    def loss(fn):
        return lambda p, x: jnp.sum(fn(p, x).astype(jnp.float32) * weight)

    got = jax.grad(loss(apply), argnums=(0, 1))(params, x)
    want = jax.grad(loss(reference), argnums=(0, 1))(params, x)
    assert got[1].dtype == dtype
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol * float(jnp.abs(b).max()), rtol=0)
