"""Sequence parallelism + sharding rules tests.

Ring attention and Ulysses must reproduce dense attention exactly
(same math, different schedule) — the long-context capability the
reference lacks (SURVEY.md §5.7).
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.transformer import dot_product_attention
from horovod_tpu.parallel import (
    make_lm_train_step,
    make_mesh,
    make_param_shardings,
    padded_alltoall,
    ring_attention,
    ulysses_attention,
)
from horovod_tpu.models import TransformerConfig


def _qkv(B=2, T=32, H=4, D=8, seed=0, kv_heads=None):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, T, kv_heads or H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, T, kv_heads or H, D).astype(np.float32))
    return q, k, v


def _sp_mesh():
    import jax

    devs = np.asarray(jax.devices())
    return Mesh(devs.reshape(8), ("sp",))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(hvd8, causal):
    q, k, v = _qkv()
    mesh = _sp_mesh()
    spec = P(None, "sp", None, None)
    out = jax.jit(
        shard_map(
            lambda a, b, c: ring_attention(a, b, c, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    )(q, k, v)
    expect = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=2e-4, atol=2e-5
    )


def test_ring_attention_gqa(hvd8):
    q, k, v = _qkv(kv_heads=2)
    mesh = _sp_mesh()
    spec = P(None, "sp", None, None)
    out = jax.jit(
        shard_map(
            lambda a, b, c: ring_attention(a, b, c, causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    )(q, k, v)
    expect = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_dense(hvd8, causal):
    q, k, v = _qkv(H=8)  # heads divisible by sp=8
    mesh = _sp_mesh()
    spec = P(None, "sp", None, None)
    out = jax.jit(
        shard_map(
            lambda a, b, c: ulysses_attention(a, b, c, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    )(q, k, v)
    expect = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=2e-4, atol=2e-5
    )


def test_padded_alltoall(hvd8):
    mesh = _sp_mesh()
    # every rank sends j rows to peer j (row value = 100*src + dst)
    splits = jnp.arange(8, dtype=jnp.int32)  # rank-independent splits

    def body(x):
        out, rsplits = padded_alltoall(x[0], splits, max_split=8,
                                       axis_name="sp")
        return out[None], rsplits[None]

    total = int(np.sum(np.arange(8)))
    x = np.zeros((8, total, 1), np.float32)
    for src in range(8):
        off = 0
        for dst in range(8):
            x[src, off : off + dst] = 100 * src + dst
            off += dst
    out, rsplits = jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=P("sp"),
            out_specs=(P("sp"), P("sp")), check_vma=False,
        )
    )(jnp.asarray(x))
    out = np.asarray(out).reshape(8, 8, 8)  # [dst, src, max_split]
    rsplits = np.asarray(rsplits).reshape(8, 8)
    for dst in range(8):
        # every peer sent `dst` rows to dst
        np.testing.assert_array_equal(rsplits[dst], np.full(8, dst))
        for src in range(8):
            valid = out[dst, src, :dst]
            np.testing.assert_array_equal(
                valid, np.full((dst, 1), 100 * src + dst).reshape(-1)
                if dst else valid
            )


def test_make_param_shardings_tp_rules(hvd8):
    mesh = make_mesh(dp=2, tp=4)
    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, hidden_size=32,
        max_seq_len=16, dtype=jnp.float32,
    )
    from horovod_tpu.models import Transformer

    m = Transformer(cfg)
    toks = jnp.ones((2, 8), dtype=jnp.int32)
    params = m.init(jax.random.PRNGKey(0), toks)["params"]
    sh = make_param_shardings(params, mesh)
    q_spec = sh["block_0"]["attn"]["query"]["kernel"].spec
    assert "tp" in str(q_spec)
    ln_spec = sh["ln_final"]["scale"].spec
    assert ln_spec == P()


def test_full_dp_tp_train_step(hvd8):
    """End-to-end pjit train step on a dp=2 × tp=4 mesh."""
    mesh = make_mesh(dp=2, tp=4)
    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, hidden_size=32,
        max_seq_len=16, dtype=jnp.float32,
    )
    opt = optax.adam(1e-3)
    init_fn, step_fn, batch_sh = make_lm_train_step(cfg, opt, mesh)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (8, 16)), dtype=jnp.int32
    )
    toks = jax.device_put(toks, batch_sh)
    params, opt_state = init_fn(jax.random.PRNGKey(0), toks[:2])
    losses = []
    for _ in range(4):
        params, opt_state, loss = step_fn(params, opt_state, toks)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    # param sharding actually applied: query kernel is sharded over tp
    q = params["block_0"]["attn"]["query"]["kernel"]
    assert "tp" in str(q.sharding.spec)


def test_full_dp_sp_ring_train_step(hvd8):
    """dp=2 × sp=4 with manual ring attention nested in the jit step."""
    mesh = make_mesh(dp=2, sp=4)
    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, hidden_size=32,
        max_seq_len=32, dtype=jnp.float32,
    )
    opt = optax.adam(1e-3)
    init_fn, step_fn, batch_sh = make_lm_train_step(
        cfg, opt, mesh, sequence_parallel="ring"
    )
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (4, 32)), dtype=jnp.int32
    )
    toks = jax.device_put(toks, batch_sh)
    params, opt_state = init_fn(jax.random.PRNGKey(0), toks[:2])
    losses = []
    for _ in range(4):
        params, opt_state, loss = step_fn(params, opt_state, toks)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_ulysses_gqa_indivisible_kv_heads(hvd8):
    """Review fix: kh=4 with sp=8 must expand kv to full head count."""
    q, k, v = _qkv(H=8, kv_heads=4)
    mesh = _sp_mesh()
    spec = P(None, "sp", None, None)
    out = jax.jit(
        shard_map(
            lambda a, b, c: ulysses_attention(a, b, c, causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    )(q, k, v)
    expect = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=2e-4, atol=2e-5
    )


def test_data_axes_helper(hvd8):
    from horovod_tpu.parallel import data_axes

    assert data_axes(make_mesh(dp=8)) == ("dp",)
    assert data_axes(make_mesh(dp=2, tp=4)) == ("dp",)
    assert data_axes(make_mesh(dp=2, fsdp=2, tp=2)) == ("dp", "fsdp")
    assert data_axes(make_mesh(dp=1, tp=8)) == ()


# ------------------------------------------------- pipeline parallelism
# (beyond the reference: SURVEY.md §2.5 lists PP as absent in Horovod)


@pytest.mark.parametrize("changes", [
    {},
    # the stream's two scalings: the pipeline's embedding and head are
    # the serial model's own pieces (models/transformer.py)
    dict(embedding_multiplier=12.0, logits_scaling=8.0),
    # a stage scans one block over its stack whatever the layers' kind,
    # where all of them are built alike
    dict(layer_types=("window_attention",) * 4, sliding_window=8),
    dict(layer_types=("mamba2",) * 4, position="none", mamba_n_heads=4,
         mamba_d_head=32, mamba_d_state=8, mamba_chunk_size=16),
], ids=["plain", "both_multipliers", "all_window_attention", "all_mamba2"])
def test_pipeline_matches_serial_forward_and_grads(changes):
    """GPipe over pp=4 must be numerically the serial model: same
    logits, same gradients through the ppermute schedule."""
    import dataclasses

    from horovod_tpu.models.transformer import (
        GPT2_SMALL,
        Transformer,
        causal_lm_loss,
    )
    from horovod_tpu.parallel.mesh import make_mesh
    from horovod_tpu.parallel.pipeline import pipeline_lm_apply

    cfg = dataclasses.replace(
        GPT2_SMALL, num_layers=4, hidden_size=64, num_heads=2,
        vocab_size=96, max_seq_len=32, dtype=jnp.float32, **changes,
    )
    model = Transformer(cfg)
    B, T = 8, 32
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 96, (B, T)), jnp.int32
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)
    )["params"]
    mesh = make_mesh(pp=4, dp=2)

    logits_serial = model.apply({"params": params}, toks)
    logits_pipe = jax.jit(
        lambda p, t: pipeline_lm_apply(cfg, p, t, mesh,
                                       num_microbatches=2)
    )(params, toks)
    np.testing.assert_allclose(
        np.asarray(logits_pipe), np.asarray(logits_serial),
        rtol=2e-4, atol=2e-4,
    )

    def loss_serial(p):
        return causal_lm_loss(model.apply({"params": p}, toks), toks)[0]

    def loss_pipe(p):
        return causal_lm_loss(
            pipeline_lm_apply(cfg, p, toks, mesh, num_microbatches=2),
            toks,
        )[0]

    g1 = jax.grad(loss_serial)(params)
    g2 = jax.jit(jax.grad(loss_pipe))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=3e-3, atol=3e-4
        ),
        g1, g2,
    )


def test_pipeline_stack_round_trip():
    from horovod_tpu.models.transformer import GPT2_SMALL, Transformer
    import dataclasses

    from horovod_tpu.parallel.pipeline import (
        stack_block_params,
        unstack_block_params,
    )

    cfg = dataclasses.replace(
        GPT2_SMALL, num_layers=3, hidden_size=32, num_heads=1,
        vocab_size=64, max_seq_len=16,
    )
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
    )["params"]
    stacked, rest = stack_block_params(params)
    rebuilt = unstack_block_params(stacked, rest)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        params, rebuilt,
    )


def test_pipeline_training_converges():
    """A pipelined train step actually learns (optimizer over the
    stacked+rest params, pp=2 x dp=4)."""
    import dataclasses

    import optax

    from horovod_tpu.models.transformer import GPT2_SMALL, Transformer, causal_lm_loss
    from horovod_tpu.parallel.mesh import make_mesh
    from horovod_tpu.parallel.pipeline import pipeline_lm_apply

    cfg = dataclasses.replace(
        GPT2_SMALL, num_layers=2, hidden_size=64, num_heads=2,
        vocab_size=64, max_seq_len=16, dtype=jnp.float32,
    )
    mesh = make_mesh(pp=2, dp=4)
    B, T = 8, 16
    r = np.random.RandomState(0)
    table = r.randint(0, 64, (64,))
    toks = np.zeros((B, T), dtype=np.int32)
    toks[:, 0] = r.randint(0, 64, B)
    for t in range(1, T):
        toks[:, t] = table[toks[:, t - 1]]
    toks = jnp.asarray(toks)

    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)
    )["params"]
    opt = optax.adam(3e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, s):
        def loss_fn(p):
            return causal_lm_loss(
                pipeline_lm_apply(cfg, p, toks, mesh,
                                  num_microbatches=2),
                toks,
            )[0]

        l, g = jax.value_and_grad(loss_fn)(p)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, l

    first = None
    for _ in range(25):
        params, opt_state, loss = step(params, opt_state)
        if first is None:
            first = float(loss)
    assert float(loss) < first - 0.5, (first, float(loss))


def _tiny_lm(layers=4, B=8, T=32, vocab=96):
    import dataclasses

    from horovod_tpu.models.transformer import GPT2_SMALL, Transformer

    cfg = dataclasses.replace(
        GPT2_SMALL, num_layers=layers, hidden_size=64, num_heads=2,
        vocab_size=vocab, max_seq_len=T, dtype=jnp.float32,
    )
    model = Transformer(cfg)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, vocab, (B, T)), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32))["params"]
    return cfg, model, toks, params


def _assert_1f1b_matches_serial(pp, dp, microbatches, layers=4, B=8):
    """The 1F1B schedule's manual VJP must reproduce jax.grad of the
    serial model exactly (loss and every gradient leaf)."""
    from horovod_tpu.models.transformer import causal_lm_loss
    from horovod_tpu.parallel.mesh import make_mesh
    from horovod_tpu.parallel.pipeline import pipeline_lm_train_step_1f1b

    cfg, model, toks, params = _tiny_lm(layers=layers, B=B)
    mesh = make_mesh(pp=pp, dp=dp)

    def loss_serial(p):
        return causal_lm_loss(model.apply({"params": p}, toks), toks)[0]

    l1, g1 = jax.value_and_grad(loss_serial)(params)
    l2, g2 = jax.jit(lambda p, t: pipeline_lm_train_step_1f1b(
        cfg, p, t, mesh, num_microbatches=microbatches))(params, toks)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=3e-3, atol=3e-4),
        g1, g2)


def test_1f1b_matches_serial_grads():
    _assert_1f1b_matches_serial(pp=2, dp=4, microbatches=4)


def test_1f1b_ring_buffer_reuse_many_microbatches():
    """M ≫ S: in-flight state is bounded by the size-S input ring (the
    1F1B memory property) and the ring reuse must not corrupt grads."""
    _assert_1f1b_matches_serial(pp=2, dp=4, microbatches=8, B=16)


def test_1f1b_deep_pipeline_short_batch():
    """S > M: warmup/drain dominates; the slot algebra must still line
    up when the pipeline is deeper than the microbatch count."""
    _assert_1f1b_matches_serial(pp=4, dp=2, microbatches=2, layers=4)


def test_1f1b_training_converges():
    import dataclasses

    import optax

    from horovod_tpu.parallel.mesh import make_mesh
    from horovod_tpu.parallel.pipeline import pipeline_lm_train_step_1f1b

    cfg, model, toks, params = _tiny_lm(layers=2, B=8)
    mesh = make_mesh(pp=2, dp=4)
    opt = optax.adam(1e-2)
    state = opt.init(params)

    @jax.jit
    def step(p, s, t):
        loss, g = pipeline_lm_train_step_1f1b(
            cfg, p, t, mesh, num_microbatches=4)
        upd, s = opt.update(g, s, p)
        return optax.apply_updates(p, upd), s, loss

    first = None
    for _ in range(30):
        params, state, loss = step(params, state, toks)
        first = float(loss) if first is None else first
    assert float(loss) < first * 0.5, (first, float(loss))


def test_1f1b_fully_padded_microbatch():
    """A microbatch whose targets are ALL ignore_index must contribute 0
    to the summed valid-token denominator — not the phantom 1 that
    causal_lm_loss's max(n, 1) clamp would add — or loss and gradients
    diverge from the serial model (ADVICE.md #1)."""
    from horovod_tpu.models.transformer import causal_lm_loss
    from horovod_tpu.parallel.mesh import make_mesh
    from horovod_tpu.parallel.pipeline import pipeline_lm_train_step_1f1b

    cfg, model, toks, params = _tiny_lm(layers=4, B=8)
    toks = np.array(toks)
    # rows 6-7 form the LAST microbatch at M=4; padding every target
    # position (toks[:, 1:]) makes its valid count exactly zero
    toks[-2:, 1:] = -1
    toks = jnp.asarray(toks)
    mesh = make_mesh(pp=2, dp=4)

    def loss_serial(p):
        return causal_lm_loss(model.apply({"params": p}, toks), toks)[0]

    l1, g1 = jax.value_and_grad(loss_serial)(params)
    l2, g2 = jax.jit(lambda p, t: pipeline_lm_train_step_1f1b(
        cfg, p, t, mesh, num_microbatches=4))(params, toks)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=3e-3, atol=3e-4),
        g1, g2)


def test_1f1b_uneven_padding_across_microbatches():
    """ignore_index padding concentrated in some microbatches: the
    schedule must normalize by the TOTAL valid count, not average
    per-microbatch means (which silently diverges from the serial
    model when n_valid varies by microbatch)."""
    from horovod_tpu.models.transformer import causal_lm_loss
    from horovod_tpu.parallel.mesh import make_mesh
    from horovod_tpu.parallel.pipeline import pipeline_lm_train_step_1f1b

    cfg, model, toks, params = _tiny_lm(layers=4, B=8)
    toks = np.array(toks)
    # pad most of the LAST two rows (the last microbatch at M=4, mb=2)
    toks[-2:, 5:] = -1
    toks = jnp.asarray(toks)
    mesh = make_mesh(pp=2, dp=4)

    def loss_serial(p):
        return causal_lm_loss(model.apply({"params": p}, toks), toks)[0]

    l1, g1 = jax.value_and_grad(loss_serial)(params)
    l2, g2 = jax.jit(lambda p, t: pipeline_lm_train_step_1f1b(
        cfg, p, t, mesh, num_microbatches=4))(params, toks)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=3e-3, atol=3e-4),
        g1, g2)
