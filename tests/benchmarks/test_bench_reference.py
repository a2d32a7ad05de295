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
FIXTURE = os.path.join(ROOT, "tests", "benchmarks", "data", "fixture")


def tiny_case(root, config, mix, **traffic_keys):
    """A configuration's file, its reference (found by its family) and
    the traffic at the tiny presets, activations in float32."""
    import jax.numpy as jnp

    cfg_file = harness.load_json(root, "benchmarks", "configs",
                                 config + ".json")
    traffic = harness.load_json(root, "benchmarks", "traffic",
                                mix + ".json")
    traffic = {**traffic, **traffic["tiny"], **traffic_keys}
    sizes = {**cfg_file["model"], **cfg_file["tiny"],
             "dtype": jnp.float32}
    return (sizes, traffic,
            harness.load_reference(cfg_file["family"], root))


@pytest.mark.parametrize("root,config,mix,attention,head", [
    (ROOT, "gpt2-medium", "lm_s1024_b16_dp1", "xla", "dense"),
    (ROOT, "gpt2-medium", "lm_s1024_b16_dp1", "flash", "fused_ce"),
    (ROOT, "bert-large", "mlm_s512_b26_dp1", "xla", "dense"),
    (ROOT, "bert-large", "mlm_s128_b104_dp1", "flash", "dense"),
    # the fixture's family: RMSNorm, rotary, 2 kv heads for 4... at the
    # tiny preset 1 for 2, SwiGLU, and a head of its own, both ways
    (FIXTURE, "fixture-lm", "lm_s256_b4", "xla", "dense"),
    (FIXTURE, "fixture-lm", "lm_s256_b4", "flash", "fused_ce"),
], ids=lambda x: None if os.path.isabs(str(x)) else str(x))
def test_program_agrees_with_plain_reference(root, config, mix, attention,
                                             head):
    import jax
    import jax.numpy as jnp
    import optax

    sizes, traffic, reference = tiny_case(
        root, config, mix, attention=attention, loss_head=head)
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
        p, batch, **reference.arguments(sizes, traffic)))(params)
    assert float(l_sys) == pytest.approx(float(l_ref), rel=LOSS_RTOL)
    diff = jax.tree_util.tree_map(lambda a, b: a - b, g_sys, g_ref)
    err = float(optax.global_norm(diff) / optax.global_norm(g_ref))
    assert err <= GRAD_RTOL, err


def test_a_term_a_module_sows_is_part_of_the_loss_the_job_trains_on():
    """A module of the program that adds to the loss (a router's
    load-balancing term) sows the term into the Flax collection
    ``dp_train.AUX_LOSSES``: the job's loss is the head's plus every
    term sown, value and gradient, whichever head the cell names; with
    nothing sown it is the head's loss alone."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import Transformer

    class Sowing(nn.Module):
        cfg: object

        @nn.compact
        def __call__(self, tokens, return_hidden=False):
            inner = Transformer(self.cfg, name="inner")
            hidden = inner(tokens, return_hidden=True)
            for weight in (0.25, 0.5):
                self.sow(dp_train.AUX_LOSSES, f"term_{weight}",
                         weight * jnp.mean(jnp.square(hidden)))
            return inner(tokens, return_hidden=return_hidden)

    for head in ("dense", "fused_ce"):
        sizes, traffic, _ = tiny_case(
            FIXTURE, "fixture-lm", "lm_s256_b4", attention="xla",
            loss_head=head)
        cfg, model, _ = dp_train.make_model(sizes, traffic)
        tok = jnp.asarray(dp_train.make_batch(sizes, traffic, 2, seed=4)[0])
        params = model.init(jax.random.PRNGKey(4), tok)["params"]
        plain = dp_train.make_loss_fn(model, traffic)

        class Inner:  # the sowing module's parameters sit under "inner"
            cfg = model.cfg

            @staticmethod
            def apply(variables, *args, **kw):
                return Sowing(cfg).apply(
                    {"params": {"inner": variables["params"]}}, *args, **kw)

        sowing = dp_train.make_loss_fn(Inner, traffic)

        def aux(p):
            hidden = model.apply({"params": p}, tok, return_hidden=True)
            return 0.75 * jnp.mean(jnp.square(hidden))

        want, want_g = jax.value_and_grad(
            lambda p: plain(p, tok) + aux(p))(params)
        got, got_g = jax.value_and_grad(sowing)(params, tok)
        assert float(aux(params)) > 1e-3
        assert float(got) == pytest.approx(float(want), rel=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(got_g),
                        jax.tree_util.tree_leaves(want_g)):
            assert jnp.allclose(a, b, rtol=1e-4, atol=1e-7)


def test_reference_takes_the_global_batch_in_blocks_of_its_own_size():
    """The reference's loss over the global batch is taken in blocks of
    8192 tokens a chip, or of the ``BLOCK_TOKENS`` the reference module
    states (a term over all of a chip's tokens needs them in one
    block); either way it is the whole batch's mean loss."""
    import time
    import types

    import jax
    import numpy as np
    from jax.sharding import Mesh

    sizes, traffic, reference = tiny_case(
        FIXTURE, "fixture-lm", "lm_s256_b4", attention="xla",
        loss_head="dense", batch_per_chip=4)
    seq = traffic["seq_len"]
    _, _, plain = dp_train.make_model(sizes, traffic)
    host_batch = dp_train.make_batch(sizes, traffic, 4, seed=2)
    params = plain.init(jax.random.PRNGKey(2), host_batch[0][:1])["params"]
    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    run = harness.Run(
        started=time.perf_counter(), workload="fixture_dp1", chips=1,
        traffic=traffic, model_sizes=sizes, seed=0, seconds=0,
        trace=False, rehearse=True)
    whole = float(reference.mean_loss(
        params, host_batch, **reference.arguments(sizes, traffic)))

    def blocks_of(**stated):
        shapes = []

        def nll_sum(p, b, **kw):
            shapes.append(b[0].shape)  # once a block shape: it is jitted
            return reference.nll_sum(p, b, **kw)

        module = types.SimpleNamespace(
            arguments=reference.arguments, nll_sum=nll_sum, **stated)
        loss = dp_train.reference_global_loss(
            run, module, params, host_batch, sizes, traffic, mesh, 1)
        assert loss == pytest.approx(whole, rel=1e-6)
        return shapes

    assert blocks_of() == [(4, seq)]
    assert blocks_of(BLOCK_TOKENS=2 * seq) == [(2, seq)]
    assert blocks_of(BLOCK_TOKENS=seq) == [(1, seq)]


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
