"""A layer has a kind (``TransformerConfig.layer_types``): the whole
patterned model against the benchmark's plain reference, the four
multipliers and the position code ``none``, the callers that assume one
kind of block, and every existing preset's lowered step against the
parent's.
"""

import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from horovod_tpu.models import mamba, transformer  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    Transformer, TransformerConfig, causal_lm_loss)
from horovod_tpu.utils import metrics, scopes  # noqa: E402

REFERENCE = harness.load_reference("state_space_hybrid_lm")
PATTERN = ["mamba2", "mamba2", "attention", "mamba2"]
# the cell's model group at a small size: every switch and multiplier
# as the configuration file states it
MODEL = dict(
    vocab_size=96, num_layers=4, layer_types=PATTERN, num_heads=4,
    num_kv_heads=2, hidden_size=32, mlp_ratio=2.0, max_seq_len=64,
    norm="rmsnorm", position="none", activation="swiglu", causal=True,
    tie_embeddings=True, layernorm_epsilon=1e-5, mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=8, mamba_expand=2, mamba_d_conv=4,
    mamba_n_groups=1, mamba_chunk_size=16, embedding_multiplier=12,
    residual_multiplier=0.22, attention_multiplier=0.015625,
    logits_scaling=8, remat=True)
TRAFFIC = {"objective": "causal_lm", "seq_len": 40, "batch_per_chip": 2}
# float32 against float32: what is left is the order of the sums. Read
# on three seeds: the sound model's gradient is off by 4.9e-7 to 5.1e-7
# of its norm and its worst leaf by 1.2e-6 to 8.5e-6; with a * dt summed
# in bf16, 8.3e-5 to 1.6e-4 and 1.2e-2 to 2.3e-1; with a multiplier
# left out, 0.35 to 7.0. The limits stand between the readings
TIGHT = 1e-5
TIGHT_LEAF = 1e-4


def built(dtype=jnp.float32, seed=0, **changes):
    cfg = TransformerConfig(**{**MODEL, **changes}, dtype=dtype)
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 40), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(seed), tokens)["params"]
    return cfg, model, params, tokens


def system_loss(model, params, tokens):
    return causal_lm_loss(model.apply({"params": params}, tokens),
                          tokens)[0]


def reference_loss(params, tokens, **changes):
    kw = REFERENCE.arguments({**MODEL, **changes}, TRAFFIC)
    return REFERENCE.mean_loss(params, (tokens,), **kw)


def errors(model, params, tokens, **reference_changes):
    """(relative error of the loss, of the gradient by its global norm,
    the worst leaf's with its path)."""
    l_sys, g_sys = jax.value_and_grad(
        lambda p: system_loss(model, p, tokens))(params)
    l_ref, g_ref = jax.value_and_grad(
        lambda p: reference_loss(p, tokens, **reference_changes))(params)
    leaves = jax.tree_util.tree_leaves_with_path(g_sys)
    ref = jax.tree_util.tree_leaves(g_ref)
    assert len(leaves) == len(ref)

    def norm(xs):
        return float(np.sqrt(sum(
            np.sum(np.square(np.asarray(x, np.float64))) for x in xs)))

    diff = [np.asarray(g, np.float64) - np.asarray(r, np.float64)
            for (_, g), r in zip(leaves, ref)]
    worst = max(
        (norm([d]) / max(norm([r]), 1e-30), jax.tree_util.keystr(path))
        for d, (path, _), r in zip(diff, leaves, ref))
    return (abs(float(l_sys) - float(l_ref)) / abs(float(l_ref)),
            norm(diff) / norm(ref), worst)


def test_the_patterned_model_is_the_reference_in_float32():
    """Loss and every gradient leaf: the chunked scan, the attention
    layer with no position code and a scale of its own, the four
    multipliers, the tied head."""
    _, model, params, tokens = built()
    loss, gradient, (worst, where) = errors(model, params, tokens)
    assert loss < 1e-6
    assert gradient < TIGHT
    assert worst < TIGHT_LEAF, where


def test_the_patterned_model_is_near_the_reference_in_bf16():
    """Under the job's limits (5e-4 on the loss, 3e-2 on the gradient's
    norm), as the benchmark holds the cell on the chip."""
    _, model, params, tokens = built(jnp.bfloat16)
    loss, gradient, _ = errors(model, params, tokens)
    assert loss < 5e-4
    assert gradient < 3e-2


@pytest.mark.parametrize("key,neutral", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", None), ("logits_scaling", 1.0)])
def test_a_multiplier_left_out_fails_the_comparison(key, neutral):
    """Each of the four is seen: the program at the neutral value
    against the reference at the configuration's."""
    _, model, params, tokens = built(**{key: neutral})
    loss, gradient, _ = errors(model, params, tokens)
    assert loss < 1e-2  # the loss alone would not see it
    assert gradient > 1000 * TIGHT


def test_a_cumulative_sum_in_bf16_fails_the_comparison(monkeypatch):
    """a * dt summed over a chunk in bf16 (the rest in float32) is a
    lower precision than the configuration states, and the float32
    comparison sees it."""
    monkeypatch.setattr(
        mamba, "_log_decay_sums",
        lambda log_decay: jnp.cumsum(
            log_decay.astype(jnp.bfloat16), axis=2).astype(jnp.float32))
    _, model, params, tokens = built()
    _, gradient, (worst, _) = errors(model, params, tokens)
    assert gradient > 5 * TIGHT
    assert worst > 50 * TIGHT_LEAF


def test_no_position_code_means_no_table_and_no_rotation(monkeypatch):
    def no_rope(*_):
        raise AssertionError("position 'none' built rope's frequencies")

    monkeypatch.setattr(transformer, "rope_frequencies", no_rope)
    monkeypatch.setattr(transformer, "apply_rope", no_rope)
    _, model, params, tokens = built()
    assert "pos_emb" not in params
    assert set(params["block_2"]["attn"]) == {"query", "key", "value",
                                              "out"}
    model.apply({"params": params}, tokens)
    # and the model does not tell one position from another but by
    # order: without a table or a rotation, a sequence shifted along
    # the positions reads the same at the same tokens
    longer = jnp.concatenate([tokens, tokens[:, :8]], axis=1)
    a = model.apply({"params": params}, longer)[:, :40]
    b = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_a_block_is_named_for_its_kind():
    _, _, params, _ = built()
    for i, kind in enumerate(PATTERN):
        block = params[f"block_{i}"]
        assert set(block) == {"ln_attn", "ln_mlp", "mlp",
                              "attn" if kind == "attention" else "mamba"}
        assert set(block["mlp"]) == {"gate", "up", "fc2"}


def test_layer_types_reads_back_equal_to_the_files_list():
    cfg = TransformerConfig(**MODEL)
    assert isinstance(cfg.layer_types, tuple)
    assert cfg.layer_types == PATTERN and not cfg.layer_types != PATTERN
    assert cfg.layer_types == tuple(PATTERN)
    assert dataclasses.asdict(cfg)["layer_types"] == PATTERN
    assert hash(cfg) == hash(TransformerConfig(**MODEL))
    assert cfg.layer_kinds == tuple(PATTERN)
    plain = TransformerConfig(num_layers=3)
    assert plain.layer_types is None
    assert plain.layer_kinds == ("attention",) * 3


@pytest.mark.parametrize("changes,words", [
    ({"layer_types": PATTERN[:3]}, "names 3 layers"),
    ({"layer_types": ["mamba2", "mamba", "attention", "mamba2"]},
     r"\['mamba'\] among them"),
    ({"mamba_n_heads": 3}, "the inner stream has one width")])
def test_a_pattern_the_model_cannot_build_is_refused(changes, words):
    with pytest.raises(ValueError, match=words):
        TransformerConfig(**{**MODEL, **changes})


def gauges(trace) -> dict:
    """``{gauge: {label or '': value}}`` of what ``trace()`` records."""
    was = metrics.enabled()
    metrics.enable()
    metrics.registry.clear()
    try:
        trace()
        return metrics.registry.snapshot()
    finally:
        metrics.registry.clear()
        if not was:
            metrics.disable()


def test_the_gauges_at_the_cells_shape():
    """Nine state-space layers and one attention layer, 32 chunks of
    256, a state of 64 x 64 x 128 float32 a sequence, one attention
    layer with nothing between its projections and its kernels: the
    real configuration, traced without a weight."""
    found = harness.load_cell("granite_h_lm")
    sizes, traffic = found["config"]["model"], found["traffic"]
    model = Transformer(TransformerConfig(**sizes))
    tokens = jnp.zeros((traffic["batch_per_chip"], traffic["seq_len"]),
                       jnp.int32)
    got = gauges(lambda: jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t,
                             return_hidden=True), tokens))
    assert got["hvd_layers"] == {"mamba2": 9, "attention": 1}
    assert {name: series[""] for name, series in got.items()
            if name != "hvd_layers"} == {
        "hvd_mamba_chunk": 256, "hvd_mamba_chunks_per_sequence": 32,
        "hvd_mamba_state_bytes_per_sequence": 64 * 64 * 128 * 4,
        "hvd_attn_prep_fused_layers": 0, "hvd_attn_prep_plain_layers": 1,
        "hvd_mamba_scan_kernel_layers": 9, "hvd_mamba_scan_plain_layers": 0,
        "hvd_remat_blocks": 9, "hvd_remat_blocks_kept": 1}


def test_the_tiny_pattern_runs_the_plain_scan_and_says_so():
    """Heads of 16 over a state of 8 in chunks of 16: no shape the
    scan's kernels take. A model with no state-space layer sets neither
    gauge."""
    _, model, params, tokens = built()
    got = gauges(lambda: jax.eval_shape(
        lambda p: model.apply({"params": p}, tokens), params))
    assert (got["hvd_mamba_scan_kernel_layers"][""],
            got["hvd_mamba_scan_plain_layers"][""]) == (0, 3)
    cfg = dataclasses.replace(transformer.GPT2_SMALL, **TINY)
    plain = Transformer(cfg)
    got = gauges(lambda: jax.eval_shape(
        plain.init, jax.random.PRNGKey(0), jnp.zeros((2, 16), jnp.int32)))
    assert not [name for name in got if name.startswith("hvd_mamba_")]


@pytest.mark.parametrize("name,kept", [
    ("ssd_scan_fwd", True), ("ssd_scan_bwd", False), ("flash_fwd", True),
    ("flash_bwd", True), ("qk_prep_fwd", False)])
def test_the_last_block_keeps_the_scans_forward_call(name, kept):
    """`_last_block_keeps` by kernel name: y and the states between
    chunks are kept from the block's first run, as the flash calls'
    results are; the backward kernel's are nothing a second run
    makes."""
    class Prim:
        name = "pallas_call"

    assert name in {scopes.SSD_SCAN_FWD, scopes.SSD_SCAN_BWD,
                    scopes.FLASH_FWD, scopes.FLASH_BWD, scopes.QK_PREP_FWD}
    assert transformer._last_block_keeps(Prim(), name=name) is kept


def test_the_real_configuration_has_the_parameters_the_issue_counted():
    sizes = harness.load_cell("granite_h_lm")["config"]["model"]
    model = Transformer(TransformerConfig(**sizes))
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 256), jnp.int32))["params"]

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    assert count(params["block_0"]) == 76_182_976
    assert count(params["block_5"]) == 60_821_504
    assert count(params) == 772_160_448


# -- the callers that assume one kind of block -------------------------------

@pytest.mark.parametrize("changes", [
    dict(remat=False),
    # every block rebuilt whole, as the model that builds the logits
    dict(remat=True),
    # a plain MLP in the first layer and routed ones behind it
    dict(remat=False, num_experts=4, experts_per_token=2,
         expert_mlp_dim=32, dense_layers=1, norm_topk_prob=True),
], ids=["patterned", "rematerialised", "routed_behind_a_dense_layer"])
def test_the_overlap_stages_give_each_block_its_kind(changes):
    """`ops/overlap.transformer_lm_stages` composes to the model's own
    forward pass on a patterned model, multipliers included: its blocks
    are `build_block` of the model's own `layer_specs`."""
    from horovod_tpu.ops import overlap

    _, model, params, tokens = built(**changes)
    stages = overlap.transformer_lm_stages(
        model, tokens, lambda logits: causal_lm_loss(logits, tokens)[0])
    assert [s.name for s in stages] == [
        "embed", "block_0", "block_1", "block_2", "block_3", "head"]
    carry = None
    for stage in stages:
        carry = stage.fwd({k: params[k] for k in stage.keys}, carry)
    assert float(carry) == pytest.approx(
        float(system_loss(model, params, tokens)), rel=1e-6)


# the five configurations of `benchmarks/configs`: the kinds of their
# layers in order (None = every layer `attention`), the layers with the
# routed MLP, and whether the configuration rematerialises
CONFIGURED = {
    "gpt2-medium": (None, [], False),
    "bert-large": (None, [], False),
    "sdar-30b-a3b-chat": (None, [0, 1, 2, 3, 4, 5], True),
    "granite-4.0-h-micro": (
        ["mamba2"] * 5 + ["attention"] + ["mamba2"] * 4, [], True),
    "trinity-mini": (
        ["window_attention"] * 3 + ["attention"] + ["window_attention"] * 2,
        [2, 3, 4, 5], True),
}


@pytest.mark.parametrize("callers_head", [False, True])
@pytest.mark.parametrize("name", CONFIGURED)
def test_the_specs_of_a_benchmark_configuration(name, callers_head):
    """`layer_specs` of each configuration a cell runs, as the model
    that builds the logits and as the cells' step, which takes the
    hidden state to the fused cross entropy: only there, and only under
    `remat`, the last block keeps its products."""
    kinds, routed, remat = CONFIGURED[name]
    cfg = TransformerConfig(**harness.load_json(
        ROOT, "benchmarks", "configs", name + ".json")["model"])
    specs = transformer.layer_specs(cfg, callers_head=callers_head)
    n = cfg.num_layers
    assert [spec.index for spec in specs] == list(range(n))
    assert [spec.kind for spec in specs] == (kinds or ["attention"] * n)
    assert [spec.index for spec in specs if spec.routed] == routed
    kept = int(remat and callers_head)
    assert [spec.remat for spec in specs] == (
        [transformer.REBUILD_ALL] * (n - kept)
        + [transformer.KEEP_PRODUCTS] * kept if remat else [None] * n)
    # and the block is built as its spec says: `nn.remat`'s class in
    # place of `Block` itself
    for spec in specs:
        block = transformer.build_block(cfg, spec)
        assert (type(block) is not transformer.Block) == remat
        assert block.spec == spec


def test_the_pipeline_refuses_a_stack_of_unlike_blocks():
    from jax.sharding import Mesh

    from horovod_tpu.parallel import pipeline

    cfg = TransformerConfig(**MODEL)
    mesh = Mesh(np.array(jax.devices()[:2]), ("pp",))
    with pytest.raises(ValueError, match="stack of unlike blocks") as e:
        pipeline._check_pp(cfg, mesh, "pipeline_lm_apply")
    assert "'attention', 'mamba2'" in str(e.value)
    # and a plain model passes as before
    pipeline._check_pp(TransformerConfig(num_layers=4), mesh, "x")


def test_serving_refuses_a_state_space_layer_and_says_what_is_missing():
    from horovod_tpu.serving import decode

    _, model, params, _ = built(remat=False)
    with pytest.raises(ValueError, match="recurrent state") as e:
        decode.GenerationEngine(model, params)
    assert "layers [0, 1, 3]" in str(e.value)
    assert "convolution" in str(e.value)
    # the model's own cache path says the same, whoever calls it
    with pytest.raises(ValueError, match="recurrent state"):
        model.apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                    kv_cache=object())


# -- every existing preset lowers as the parent's ------------------------------

# sha256 of the lowered text (`jit(...).lower(...).as_text()`) of each
# preset's tiny step, made from the parent commit 4359ae1 (PR 44) by
# the script this test repeats: the five cells' tiny steps as
# `tests/test_step_scopes.tiny_step` builds them, and four presets of
# `models/transformer.py` no cell runs. With no new key set a
# configuration lowers to the same text, so no number a cell prints can
# have moved. PR 46 (the scan's kernels, one more name in
# `_last_block_keeps`, two gauges for a model with `mamba2` layers)
# leaves all nine as they were, and so does PR 48 (a window, a gate,
# four norms, the routed MLP's new forms: every default is neutral).
# PR 49 re-pins `sdar_bd_s4096` and no other: its tiny step sends 512
# rows of 128 through experts of 768, a shape `ops/grouped_matmul.py`'s
# kernels take, so its expert products lower to those (interpreted)
# calls in place of `ragged_dot` behind a cast, which is the change,
# and an iteration of the loop over further products is rematerialised
# with its condition (`models/moe.py`: the float32 experts stay the
# loop's constants); the eight others build no routed MLP and are the
# parent's text. PR 52 adds the two newest cells' tiny steps, made on
# its parent d932e39 before it moved anything, and leaves the nine as
# they were: it builds the stack in one place and changes no step
PARENT_LOWERED = {
    "gpt2m_dp1":
        "cca38d2c0a7ce2ca00e1ea5711e301ebefc16f951e4472d5ff65984aa35c9ce1",
    "gpt2m_dp4":
        "5da32f0231efebad584f7272eea43a171bd8f5794f58b81afd3795f6b5cfbbe8",
    "bertl_s512":
        "a7b200dc3c9ae82e7ab7edbd966ee2870466672a138091c4f44e7ab5105ddcac",
    "bertl_s128":
        "297571072ebf97776a0c6afc3e9baeae9940f0cf8d1a65f4a798e0f8cfb614d7",
    "sdar_bd_s4096":
        "d3f634d6a066d6108279d25f79068be950086041230c4286514efb299a67feb2",
    "llama2_tiny":
        "7d5055b39cd6228c8ae21b4e6338a4a46d1085cb92fea6d737f62eb2132ec050",
    "llama3_tiny_remat_hidden":
        "edde1931ad5fab6d4f36139d5e2624bc863364ea3888acb66f2ab9c9238e116d",
    "gpt2_small_tiny_remat":
        "e8376d1268d665b48e9a80eb80679319cce1ee6620d1c7dced3e58bd475e1cc9",
    "bert_base_tiny":
        "bf9f6c46b9bd9ff37578aad801f97e779aa48d0c912348ee532623df87402a00",
    "granite_h_lm":
        "4b07e2731f152d51ec6a8c9891e9ac71cc50e9563bb752554a1682c9f223d5b7",
    "trinity_mini_s8192":
        "6970441779e73079530fcb2cae7879500990adadb7c260666d63a769859d806f",
}
CELL_DEVICES = {"gpt2m_dp1": 1, "gpt2m_dp4": 4, "bertl_s512": 1,
                "bertl_s128": 1, "sdar_bd_s4096": 1, "granite_h_lm": 1,
                "trinity_mini_s8192": 1}
TINY = dict(vocab_size=64, num_layers=2, num_heads=4, hidden_size=32,
            max_seq_len=16)
PRESETS = {
    "llama2_tiny": (dataclasses.replace(transformer.LLAMA2_7B, **TINY),
                    False),
    "llama3_tiny_remat_hidden": (dataclasses.replace(
        transformer.LLAMA3_8B, num_kv_heads=2, remat=True, **TINY), True),
    "gpt2_small_tiny_remat": (dataclasses.replace(
        transformer.GPT2_SMALL, remat=True, **TINY), False),
    "bert_base_tiny": (dataclasses.replace(transformer.BERT_BASE, **TINY),
                       False),
}


def lowered_preset(cfg, return_hidden: bool) -> str:
    model = Transformer(cfg)
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 16), jnp.int32))["params"]

    def loss(p, t):
        out = model.apply({"params": p}, t, return_hidden=return_hidden)
        if return_hidden:
            return jnp.mean(out.astype(jnp.float32) ** 2)
        return causal_lm_loss(out, t)[0]

    return jax.jit(jax.value_and_grad(loss)).lower(params, tokens).as_text()


@pytest.mark.parametrize("preset", PARENT_LOWERED)
def test_an_existing_preset_lowers_to_the_parents_text(preset):
    if preset in CELL_DEVICES:
        from test_step_scopes import tiny_step

        step, args = tiny_step(preset, CELL_DEVICES[preset])
        text = step.lower(*args).as_text()
    else:
        text = lowered_preset(*PRESETS[preset])
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_LOWERED[preset]
