"""Each plain reference against the program's ``Transformer`` at a tiny
size on the CPU: loss and gradient.

Both sides compute in float32 here (the program's activations are set
to float32 for the test), so they differ only in the order of
floating-point operations: XLA's fused softmax and LayerNorm against the
reference's written-out ones, and the flash kernel's blockwise softmax.
float32 rounds at 6e-8 and the sums run over a few hundred terms, so
the loss has to agree to 1e-5 relative and the gradient's global norm
to 1e-4; a missing term (a bias, a mask, the final LayerNorm, the
wrong GELU) is orders of magnitude above that.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks.jobs import dp_train  # noqa: E402
from benchmarks.reference import transformer_lm as reference  # noqa: E402

LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4


@pytest.mark.parametrize("config,mix,attention,head", [
    ("gpt2-medium", "lm_s1024_b16_dp1", "xla", "dense"),
    ("gpt2-medium", "lm_s1024_b16_dp1", "flash", "fused_ce"),
    ("bert-large", "mlm_s512_b26_dp1", "xla", "dense"),
    ("bert-large", "mlm_s128_b104_dp1", "flash", "dense"),
])
def test_program_agrees_with_plain_reference(config, mix, attention,
                                             head):
    import jax
    import jax.numpy as jnp
    import optax

    cfg_file = harness.load_json(harness.HERE, "configs", config + ".json")
    traffic = harness.load_json(harness.HERE, "traffic", mix + ".json")
    traffic = {**traffic, **traffic["tiny"], "attention": attention,
               "loss_head": head}
    sizes = {**cfg_file["model"], **cfg_file["tiny"],
             "dtype": jnp.float32}
    cfg, model, plain = dp_train.make_model(sizes, traffic)
    loss_fn = dp_train.make_loss_fn(model, traffic)
    batch = tuple(jnp.asarray(a) for a in dp_train.make_batch(
        sizes, traffic, 3, seed=7))
    params = plain.init(jax.random.PRNGKey(7), batch[0][:1])["params"]
    # biases and LayerNorm offsets start at zero: move them, or a
    # reference that dropped one would still agree
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 1000))
    params = jax.tree_util.tree_map(
        lambda x: x + 0.02 * jax.random.normal(next(keys), x.shape),
        params)

    l_sys, g_sys = jax.value_and_grad(loss_fn)(params, *batch)
    l_ref, g_ref = jax.value_and_grad(lambda p: reference.mean_loss(
        p, batch, objective=traffic["objective"],
        num_layers=cfg.num_layers, causal=cfg.causal,
        eps=cfg.layernorm_epsilon))(params)
    assert float(l_sys) == pytest.approx(float(l_ref), rel=LOSS_RTOL)
    diff = jax.tree_util.tree_map(lambda a, b: a - b, g_sys, g_ref)
    err = float(optax.global_norm(diff) / optax.global_norm(g_ref))
    assert err <= GRAD_RTOL, err


def test_reference_causal_mask_hides_the_future():
    """Changing a later token leaves earlier positions' logits alone in
    the causal reference and changes them in the bidirectional one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg_file = harness.load_json(harness.HERE, "configs", "gpt2-medium.json")
    traffic = harness.load_json(harness.HERE, "traffic",
                                "lm_s1024_b16_dp1.json")
    traffic = {**traffic, **traffic["tiny"], "attention": "xla"}
    sizes = {**cfg_file["model"], **cfg_file["tiny"]}
    cfg, _, plain = dp_train.make_model(sizes, traffic)
    tok = jnp.asarray(dp_train.make_batch(sizes, traffic, 1, seed=3)[0])
    params = plain.init(jax.random.PRNGKey(3), tok)["params"]
    other = tok.at[0, -1].set((tok[0, -1] + 1) % sizes["vocab_size"])
    for causal, same in ((True, True), (False, False)):
        a, b = (reference.logits(params, t, num_layers=cfg.num_layers,
                                 causal=causal, eps=1e-5)
                for t in (tok, other))
        assert np.array_equal(np.asarray(a[0, :-1]),
                              np.asarray(b[0, :-1])) is same
