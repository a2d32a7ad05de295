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
            self.sow(dp_train.CHOICES, "experts",
                     jnp.argsort(hidden[..., :4], -1)[..., :2])
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
        # the reference check's variant: the same loss, and beside it
        # what that pass sowed into CHOICES, which the step's never sees
        (loss, chosen), _ = jax.value_and_grad(dp_train.make_loss_fn(
            Inner, traffic, with_choices=True), has_aux=True)(params, tok)
        assert float(loss) == float(got)
        assert list(chosen) == ["experts/0"]
        assert chosen["experts/0"].shape == (*tok.shape, 2)


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


# -- a model that chooses, without the program ------------------------------
#
# A stub system and a stub reference: embedding, one layer in which a
# gate picks the two of four expert matrices a token goes through, a
# head. The system computes in float32 but for the gate's scores, which
# it rounds to bfloat16 as a bf16 model's router sees them.

STUB_V, STUB_H, STUB_T, STUB_E, STUB_K = 32, 64, 64, 4, 2
STUB_SIZES = {"vocab_size": STUB_V}
STUB_TRAFFIC = {"objective": "causal_lm", "seq_len": STUB_T}
RIGGED_IDS = (0, 1, 2)  # tokens whose second and third score all but tie
# the rigged tokens' scores: expert 2 leads, and expert 1 is ahead of
# expert 0 by less than bfloat16 keeps at 1.0, so the rounded scores tie
# and ``top_k`` gives the lower index; ``apart`` puts them clearly apart
TIED_SCORES = (1.0, 1.0 + 2.0 ** -10, 2.0, 0.0)
APART_SCORES = (1.0, 1.25, 2.0, 0.0)


def stub_model(expert_dtype=None, router_fault=False):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    class Gated(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(STUB_V, STUB_H, name="tok_emb")(tokens)
            router = self.param(
                "router", nn.initializers.normal(1.0), (STUB_H, STUB_E))
            experts = self.param(
                "experts", nn.initializers.normal(STUB_H ** -0.5),
                (STUB_E, STUB_H, STUB_H))
            scores = (x @ router).astype(jnp.bfloat16)
            if router_fault:  # a router at fault: the rigged ids' second
                # and third expert change places whatever their scores
                scores = jnp.where((tokens < len(RIGGED_IDS))[..., None],
                                   scores[..., jnp.array([1, 0, 2, 3])],
                                   scores)
            pick = jax.lax.top_k(scores, STUB_K)[1]
            self.sow(dp_train.CHOICES, "experts", pick)
            w = experts[pick]
            if expert_dtype is not None:  # the product's inputs, lower
                x_in, w = (a.astype(expert_dtype).astype(jnp.float32)
                           for a in (x, w))
            else:
                x_in = x
            x = x + jnp.tanh(jnp.einsum("bth,btehk->btk", x_in, w))
            return nn.Dense(STUB_V, name="lm_head")(x)

    return Gated()


def stub_nll(logits, tokens):
    import jax
    import jax.numpy as jnp
    lse = jax.scipy.special.logsumexp(logits[:, :-1], -1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits[:, :-1], tokens[:, 1:, None], -1)[..., 0])


class StubReference:
    """The stub's plain reference, all float32. ``calls`` notes how the
    job called it."""

    __name__ = "stub_reference"

    def __init__(self, takes_choices, scores_name="experts/0"):
        self.calls = []
        self.scores_name = scores_name
        if takes_choices:
            self.TAKES_CHOICES = True
            self.choice_scores = self._choice_scores

    @staticmethod
    def arguments(model, traffic):
        return {"objective": traffic["objective"]}

    @staticmethod
    def _forward(p, tokens, choices=None):
        import jax
        import jax.numpy as jnp
        x = p["tok_emb"]["embedding"][tokens]
        scores = jnp.dot(x, p["router"], precision="highest")
        pick = jax.lax.top_k(scores, STUB_K)[1]
        if choices is not None:
            pick = choices["experts/0"]
        x = x + jnp.tanh(jnp.einsum(
            "bth,btehk->btk", x, p["experts"][pick], precision="highest"))
        logits = jnp.dot(x, p["lm_head"]["kernel"], precision="highest")
        return logits + p["lm_head"]["bias"], scores

    def _choice_scores(self, p, batch, **kw):
        self.calls.append(("choice_scores", sorted(kw)))
        return {self.scores_name: self._forward(p, batch[0])[1]}

    def mean_loss(self, p, batch, **kw):
        self.calls.append(("mean_loss", sorted(kw)))
        return stub_nll(self._forward(p, batch[0], kw.get("choices"))[0],
                        batch[0])


def stub_check(reference, *, rigged=TIED_SCORES, seed=11, **model_kw):
    """``dp_train.reference_check`` of the stub system against
    ``reference``: the checks' outcomes, and what was compared."""
    import time

    import jax
    import jax.numpy as jnp

    model = stub_model(**model_kw)
    tokens = jnp.asarray(dp_train.make_batch(
        STUB_SIZES, STUB_TRAFFIC, 2, seed + 1)[0])
    params = model.init(jax.random.PRNGKey(seed), tokens)["params"]
    # rig a few token ids: the embedding of least norm whose scores are
    # the ones wanted
    emb = params["tok_emb"]["embedding"]
    wanted = jnp.linalg.pinv(params["router"].T) @ jnp.asarray(rigged)
    for i in RIGGED_IDS:
        emb = emb.at[i].set(wanted)
    params = {**params, "tok_emb": {"embedding": emb}}
    assert set(RIGGED_IDS) <= set(map(int, tokens.ravel()))

    def loss_fn(p, tok):
        logits, sown = model.apply(
            {"params": p}, tok, mutable=[dp_train.CHOICES])
        return stub_nll(logits, tok), dp_train.named_choices(sown)

    takes_choices = getattr(reference, "TAKES_CHOICES", False)
    run = harness.Run(
        started=time.perf_counter(), workload="stub", chips=1,
        traffic=STUB_TRAFFIC, model_sizes=STUB_SIZES, seed=seed,
        seconds=0, trace=False, rehearse=True)
    dp_train.reference_check(
        run, reference,
        loss_fn if takes_choices else lambda p, t: loss_fn(p, t)[0],
        params, STUB_SIZES, STUB_TRAFFIC)
    return run.checks, run.compared


def test_a_flipped_choice_fails_the_free_comparison_and_not_the_imposed():
    """Where the system's rounded scores tie and float32's do not, a
    token goes through another expert: compared freely, as a reference
    without the flag is, the gradient is a whole expert's contribution
    off and the old limit fails. Given the system's choices the same
    arithmetic passes, and the share of choices the reference would
    have made itself is held to the floor its own near ties give."""
    free = StubReference(takes_choices=False)
    checks, _ = stub_check(free)
    # (one expert of two, at a tenth of the tokens, moves the loss by
    # 3e-4 and the gradient by 0.13)
    assert set(checks) == {"reference_loss", "reference_gradient"}
    assert checks["reference_gradient"] is False
    # a module without the flag is called exactly as before: once, with
    # its own arguments and nothing else
    assert free.calls == [("mean_loss", ["objective"])]

    imposed = StubReference(takes_choices=True)
    checks, compared = stub_check(imposed)
    assert checks == {"reference_choices": True, "reference_loss": True,
                      "reference_gradient": True}
    assert imposed.calls == [("choice_scores", ["objective"]),
                             ("mean_loss", ["choices", "objective"])]
    # the rigged ids flipped and the rest agree; every flip is a near
    # tie by the job's rule, so the floor is at or under the share
    share, floor = compared["reference_choices"]
    assert 0.8 < share < 0.95 and 0.8 < floor <= share
    # the limits are the job's whatever the module states
    imposed.LIMITS = {"loss_rtol": 1.0, "grad_rtol": 1.0, "why": "wider"}
    _, compared = stub_check(imposed, expert_dtype="float8_e4m3fn")
    assert compared["reference_gradient"][1] == dp_train.GRAD_RTOL == 3e-2
    assert compared["reference_loss"][1] == dp_train.LOSS_RTOL == 5e-4


def test_a_router_at_fault_is_not_passed_by_being_given_its_choices():
    """The imposed comparison cannot see a router that chooses wrongly:
    the arithmetic at its choices agrees. The share does: tokens whose
    scores lie clearly apart and that the system routes otherwise pull
    it under the floor, which no near tie lowers for them."""
    reference = StubReference(takes_choices=True)
    checks, compared = stub_check(
        reference, rigged=APART_SCORES, router_fault=True)
    assert checks == {"reference_choices": False, "reference_loss": True,
                      "reference_gradient": True}
    share, floor = compared["reference_choices"]
    assert share < 0.95 < floor
    # the same scores through a sound router: every choice agrees
    checks, compared = stub_check(reference, rigged=APART_SCORES)
    assert checks["reference_choices"] is True
    assert compared["reference_choices"][0] > 0.95


def test_the_imposed_comparison_still_sees_a_lower_precision():
    """Imposing the choices does not blind the comparison: with the
    expert product's inputs in an 8-bit float, the step below the
    bfloat16 the limits were measured on (``dp_train``'s comment), the
    gradient is far past GRAD_RTOL at the system's own choices; with
    them in bfloat16, which the limits were set to pass, it is inside.
    A product accumulated in bfloat16 over this stub's 64 terms reads
    5e-3, inside too: it takes the sums over thousands of tokens of a
    real step to be seen, so the 8-bit float is the control here."""
    import jax.numpy as jnp

    low = StubReference(takes_choices=True)
    checks, _ = stub_check(low, expert_dtype=jnp.float8_e4m3fn)
    assert checks["reference_choices"] is True
    assert checks["reference_gradient"] is False
    checks, _ = stub_check(StubReference(takes_choices=True),
                           expert_dtype=jnp.bfloat16)
    assert checks == {"reference_choices": True, "reference_loss": True,
                      "reference_gradient": True}


def test_the_program_has_to_sow_the_choices_the_reference_names():
    """A reference that takes choices and a program that sows them
    under other names (or sows none): not `correct`, and the reference
    is compared freely, as there is nothing to give it."""
    reference = StubReference(takes_choices=True, scores_name="router/0")
    checks, _ = stub_check(reference)
    assert checks["reference_choices"] is False
    assert checks["reference_gradient"] is False
    assert reference.calls[-1] == ("mean_loss", ["objective"])


def test_no_reference_module_states_limits_or_takes_choices_yet():
    """``transformer_lm`` and the fixture's reference state no limits
    (the job's hold for every family, and nothing reads a module's) and
    make no choices."""
    for root, family in ((ROOT, "transformer_lm"),
                         (FIXTURE, "rope_swiglu_lm")):
        module = harness.load_reference(family, root)
        assert not hasattr(module, "LIMITS")
        assert not hasattr(module, "TAKES_CHOICES")
    assert (dp_train.LOSS_RTOL, dp_train.GRAD_RTOL) == (5e-4, 3e-2)
