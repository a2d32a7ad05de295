"""Data-parallel training of a configuration of the program's
``Transformer``, whatever its ``family``.

The step is built the way ``examples/gpt2_pretraining.py`` and
``examples/bert_pretraining.py`` build theirs (a copy: their loops run a
fixed number of iterations inside ``main()`` and cannot be timed for
``--seconds``): ``hvd.init`` → ``hvd.DistributedOptimizer(optax.adamw)``
→ ``hvd.broadcast_parameters`` → a ``shard_map`` step over the ``hvd``
axis, AOT-compiled with ``xla_tpu_scoped_vmem_limit_kib=65536`` and
called as an executable. Nothing here sets a ``HOROVOD_*`` variable or
a knob: a cell runs the program's defaults.

Everything the cell's parameters select is in its traffic file
(``objective``, one of the rows of OBJECTIVES below, and what that
row reads; ``seq_len``, the data tokens of a sequence;
``batch_per_chip``, ``attention``, ``loss_head``,
``learning_rate``); the model's sizes are in its
configuration file and are built as written, and the plain reference
the run is compared with is the file ``benchmarks/reference/<family>.py``
that the configuration's ``family`` names (its contract is written at
the top of ``transformer_lm.py``). The loop's numbers below define the
metrics and are the same in every cell. One process drives every chip
of the cell.
"""

from __future__ import annotations

import math
import statistics
import time
from typing import Callable, NamedTuple

import numpy as np

from benchmarks import harness

# Agreement of the system's loss function (bf16 activations, flash
# attention, fused or dense cross entropy, fp32 parameters) with the
# float32 reference at the published width and depth, two sequences.
# Measured on the chip (PR 22, TPU v5 lite, seeds 1-3): the loss agreed
# to 1.2e-5 (GPT-2-medium) and 1.2e-4 (BERT-Large) relative, the
# gradient's global norm to 1.23e-2 .. 1.30e-2. bf16 keeps 8 bits, so
# each activation is off by up to 2^-9 = 0.2% and 24 layers of them add
# to about 1% in the gradient (a six-layer model of 644 M parameters at
# 8,192 positions read 1.17e-2 too, PR 32: between 6 and 24 layers the
# error does not grow with depth). The limits are four times the loss error
# and a little over twice the gradient error seen: activations in an
# 8-bit float (2^-4 a value) or gradients accumulated in bf16 are many
# times past them, and float32 activations would pass far inside.
LOSS_RTOL = 5e-4
GRAD_RTOL = 3e-2
# step 0 on the whole global batch against the reference forward pass
# over the same batch in blocks: measured 3e-6 and 6e-6 relative (more
# positions average the rounding out), limit 1e-4. It holds the compiled
# step's forward pass at the real batch shape and the average over
# chips (a loss summed over chips, or a chip's share left out of the
# mean, is far outside). At random weights every sequence has nearly
# the same loss, so a sequence on the wrong chip is NOT seen here: the
# placement checks below see that.
GLOBAL_LOSS_RTOL = 1e-4
# tokens a chip takes in one reference call: [8192, V] float32 logits
# are 1.6 GB at V=50k. A reference module that states BLOCK_TOKENS of
# its own is given that many
REFERENCE_BLOCK_TOKENS = 8192
# A module of the program that adds a term to the loss (a router's
# load-balancing term) sows it into this Flax collection; the loss a
# job trains on is the head's loss plus every term sown, and the
# reference's ``mean_loss`` returns the same sum
AUX_LOSSES = "losses"
# A module of the program that makes a discrete choice (a router's k
# experts of e for a token) sows it into this Flax collection: integer
# arrays ``[..., k]`` whose last axis holds one token's k choices. The
# step makes the collection mutable nowhere, so there the sowing is
# nothing; for a reference that ``TAKES_CHOICES``
# (``reference/transformer_lm.py``) the reference check makes it
# mutable in the pass whose gradient it compares
CHOICES = "choices"
# Such a model is compared at its own choices, and how many of them the
# float32 reference would have made itself is held to a floor that the
# job derives and no module states: one, less the share of tokens at
# which the reference's own scores all but tie, the gap between its
# k-th and its (k+1)-th score under NEAR_TIE of the spread between its
# best and its worst. bf16 keeps 8 bits: a score read from bf16
# activations is off by 2^-9 of its size at the least and, by the sum
# over layers that puts 1% into the gradient (above), by up to about
# 2^-7; a token's scores spread about as wide as they are large, and
# 2^-6 of the spread is twice that. A token outside that band that the
# system routes otherwise is a router at fault (another k, a correction
# left out), not rounding. Arithmetic, NOT a measurement: no program
# sows choices yet, and the first cell whose program does has to read
# both shares on the chip beside a router at fault (PERF.md section 7).
# The band grows with the number of scores: of seeded normal scores it
# holds 3% of tokens at 4 top 2 and 72% at 128 top 8, where the floor
# holds almost nothing; it has to be set from the rounding then
NEAR_TIE = 2.0 ** -6

# The loop. These are part of what the metrics mean, so no cell sets
# them: ``tokens_per_s_per_chip`` is the median over chunks of
# CHUNK_STEPS calls, each chunk closed by one host sync;
# ``loss_step_16`` is the loss step LOSS_STEP returns, counted from the
# seed's initial parameters with the warm-up steps included, so a run
# makes at least LOSS_STEP + 1 steps whatever ``--seconds`` says; the
# profiler sees TRACED_STEPS steps after the window.
WARMUP_STEPS = 4
CHUNK_STEPS = 4
LOSS_STEP = 16
TRACED_STEPS = 6
# batches a block-diffusion cell draws from its seed, to keep the one
# whose weights average nearest 1 (``_block_diffusion_batch``)
BALANCE_DRAWS = 32


def make_model(model_sizes: dict, traffic: dict):
    """The program's ``Transformer`` at the configuration's sizes, with
    the attention the traffic names; also returned without the kernel,
    for parameter init (the same tree, no kernel compiled at [1, T])."""
    from horovod_tpu.models.transformer import (
        Transformer, TransformerConfig)

    cfg = TransformerConfig(**model_sizes)
    if traffic["seq_len"] > cfg.max_seq_len:
        raise ValueError(
            f"the traffic's seq_len {traffic['seq_len']} is longer than "
            f"the configuration's max_seq_len {cfg.max_seq_len}")
    attention_fn = None
    if traffic["attention"] == "flash":
        from horovod_tpu.ops.pallas_attention import (
            make_flash_attention_fn)
        # a model group that states a block-diffusion mask gets the
        # kernels' (the default attention reads it from ``cfg``)
        block = model_sizes.get("diffusion_block")
        attention_fn = make_flash_attention_fn(
            causal=cfg.causal,
            **({"diffusion_block": block} if block else {}))
    elif traffic["attention"] != "xla":
        raise ValueError(f"unknown attention {traffic['attention']!r}")
    return cfg, Transformer(cfg, attention_fn=attention_fn), \
        Transformer(cfg)


def named_choices(sown) -> dict:
    """What a pass's modules sowed into CHOICES, as ``{path: integer
    array}`` with the path's names joined by ``/``: the modules' names,
    the name sown under, and the position in the tuple Flax's ``sow``
    keeps (``block_1/mlp/router/experts/0``)."""
    from flax import traverse_util

    by_name = traverse_util.flatten_dict(
        dict(sown).get(CHOICES, {}), sep="/")
    return {f"{name}/{i}": array for name, kept in by_name.items()
            for i, array in enumerate(kept)}


class Objective(NamedTuple):
    """One row of OBJECTIVES: what a training objective is to this job.
    ``batch(rng, shape, model_sizes, traffic)`` draws the host arrays
    of ``shape = (sequences, seq_len)`` that follow the parameters in a
    call of the loss; ``loss(heads, cfg, traffic)`` builds
    ``loss_fn(params, *batch)`` from the program's entry points, with
    ``heads`` (``make_loss_fn``) for the model's output and what its
    modules sowed; ``n_batch_args`` is how many arrays a batch has."""
    batch: Callable
    loss: Callable
    n_batch_args: int


def _causal_lm_batch(rng, shape, model_sizes, traffic):
    return (rng.integers(0, model_sizes["vocab_size"], shape,
                         dtype=np.int32),)


def _causal_lm_loss(heads, cfg, traffic):
    from horovod_tpu.models.transformer import causal_lm_loss
    from horovod_tpu.ops.fused_cross_entropy import fused_causal_lm_loss

    def loss_fn(p, tok):
        if heads.fused:
            args, sown = heads.hidden_and_head(p, tok)
            return heads.result(fused_causal_lm_loss(*args, tok)[0], sown)
        logits, sown = heads.apply(p, tok)
        return heads.result(causal_lm_loss(logits, tok)[0], sown)
    return loss_fn


def _masked_lm_batch(rng, shape, model_sizes, traffic):
    """``(tokens, labels, mask)``. Every seed labels the same number of
    positions, ``mask_fraction`` of the batch rounded, at places the
    seed draws: a Bernoulli mask labels 1,997 +- 41 of 13,312, and a
    batch with more labels to learn is behind at every later step
    (0.0009 nats a position at step 16 in ``bertl_s128``, PERF.md
    section 6, PR 44)."""
    vocab = model_sizes["vocab_size"]
    tokens = rng.integers(0, vocab, shape, dtype=np.int32)
    labels = rng.integers(0, vocab, shape, dtype=np.int32)
    size = shape[0] * shape[1]
    mask = np.zeros(size, dtype=bool)
    mask[rng.permutation(size)[:round(traffic["mask_fraction"] * size)]] \
        = True
    return tokens, labels, mask.reshape(shape)


def _masked_lm_loss(heads, cfg, traffic):
    from horovod_tpu.models.transformer import mlm_loss
    from horovod_tpu.ops.fused_cross_entropy import (
        fused_linear_cross_entropy)

    def loss_fn(p, tok, lab, msk):
        if heads.fused:
            args, sown = heads.hidden_and_head(p, tok)
            return heads.result(fused_linear_cross_entropy(
                *args, lab, valid=msk)[0], sown)
        logits, sown = heads.apply(p, tok)
        return heads.result(mlm_loss(logits, lab, msk)[0], sown)
    return loss_fn


def _block_diffusion_batch(rng, shape, model_sizes, traffic):
    """``(x0, m, w)``: the clean tokens, drawn from the rows held less
    the last, which is the mask token; for each sequence and block of
    ``diffusion_block`` tokens one noise level t ~ U(``t_min``, 1];
    ``m``, which of a block's tokens are masked, Bernoulli(t); ``w`` =
    1/t, a masked token's weight in the loss. The mean of ``m * w``
    over the batch scales its loss at every step (1 in expectation,
    +- 1.4% at 8,192 tokens and ``t_min`` 0.1), so of BALANCE_DRAWS
    batches from the seed the one whose mean is nearest 1 is taken:
    every seed's batch weighs the same to about 0.05% (PERF.md section
    6, PR 44)."""
    block, t_min = model_sizes.get("diffusion_block"), traffic["t_min"]
    if not block or shape[1] % block:
        raise ValueError(
            f"objective 'block_diffusion': the traffic's seq_len "
            f"{shape[1]} is no multiple of the model group's "
            f"diffusion_block {block!r}")
    if not 0.0 <= t_min < 1.0:
        raise ValueError(f"t_min {t_min!r} is not in [0, 1)")
    best = None
    for _ in range(BALANCE_DRAWS):
        x0 = rng.integers(0, model_sizes["vocab_size"] - 1, shape,
                          dtype=np.int32)
        level = 1.0 - (1.0 - t_min) * rng.random(
            (shape[0], shape[1] // block))
        level = np.repeat(level, block, axis=1)
        m, w = rng.random(shape) < level, (1.0 / level).astype(np.float32)
        off = abs(float(np.mean(m * w, dtype=np.float64)) - 1.0)
        if best is None or off < best[0]:
            best = off, (x0, m, w)
    return best[1]


def _block_diffusion_loss(heads, cfg, traffic):
    """The step's input is ``[x_t ; x0]``, 2T positions numbered
    ``[0..T-1 ; 0..T-1]``: the noisy copy (the mask token where ``m``)
    beside the clean one, under the model's block-diffusion mask. The
    loss is taken at the noisy half alone, no shift: the sum over its
    masked positions of ``w`` times the negative log likelihood of
    ``x0``, over all B·T data tokens."""
    import jax.numpy as jnp

    from horovod_tpu.ops.fused_cross_entropy import (
        fused_linear_cross_entropy)

    if not heads.fused:
        raise ValueError(
            "objective 'block_diffusion' takes loss_head 'fused_ce': "
            "the head runs at the noisy half's positions alone")
    mask_token = cfg.vocab_size - 1

    def loss_fn(p, x0, m, w):
        n, t = x0.shape
        tokens = jnp.concatenate(
            [jnp.where(m, mask_token, x0), x0], axis=1)
        positions = jnp.broadcast_to(
            jnp.tile(jnp.arange(t), 2)[None], (n, 2 * t))
        (hidden, kernel), sown = heads.hidden_and_head(
            p, tokens, positions=positions)
        total = fused_linear_cross_entropy(
            hidden[:, :t], kernel, x0, valid=m, weight=w, mean=False)[0]
        return heads.result(total / x0.size, sown)
    return loss_fn


# A fourth objective is one more row. What a row asks of the program
# that the program does not have yet is written in PERF.md section 4
# under the name the row calls it by
OBJECTIVES = {
    "causal_lm": Objective(_causal_lm_batch, _causal_lm_loss, 1),
    "masked_lm": Objective(_masked_lm_batch, _masked_lm_loss, 3),
    "block_diffusion": Objective(
        _block_diffusion_batch, _block_diffusion_loss, 3),
}


def objective_of(traffic: dict) -> Objective:
    if traffic["objective"] not in OBJECTIVES:
        raise ValueError(
            f"unknown objective {traffic['objective']!r}: "
            f"benchmarks/jobs/dp_train.py has the rows "
            f"{sorted(OBJECTIVES)}")
    return OBJECTIVES[traffic["objective"]]


class Heads(NamedTuple):
    """What an objective's loss builds on: ``apply(p, tokens, **kw)``,
    the model's output and what its modules sowed;
    ``hidden_and_head(p, tokens, **kw)``, the final hidden state and
    the head's kernel ``[h, V]`` (the token embedding's transpose where
    the head is tied) and what was sown; ``result(loss, sown)``, the
    loss with every term sown into AUX_LOSSES added; ``fused``, whether
    the cell's ``loss_head`` is the fused cross entropy."""
    apply: Callable
    hidden_and_head: Callable
    result: Callable
    fused: bool


def make_loss_fn(model, traffic: dict, with_choices: bool = False):
    """``loss(params, *batch)`` as the examples define it, by the
    traffic's row of OBJECTIVES. With ``with_choices`` (the reference
    check's, never the step's) the same pass also makes CHOICES mutable
    and the function returns ``(loss, named_choices)``, for
    ``jax.value_and_grad(..., has_aux=True)``."""
    import jax

    objective, head = objective_of(traffic), traffic["loss_head"]
    if head not in ("fused_ce", "dense"):
        raise ValueError(f"unknown loss_head {head!r}")
    mutable = [AUX_LOSSES, CHOICES] if with_choices else [AUX_LOSSES]

    def apply(p, tok, **kw):
        out, sown = model.apply({"params": p}, tok, mutable=mutable,
                                **kw)
        terms = jax.tree_util.tree_leaves(dict(sown).get(AUX_LOSSES, {}))
        return out, ((sum(terms) if terms else None),
                     named_choices(sown))

    def hidden_and_head(p, tok, **kw):
        hidden, sown = apply(p, tok, return_hidden=True, **kw)
        if model.cfg.tie_embeddings:
            return (hidden, p["tok_emb"]["embedding"].T), sown
        return (hidden, p["lm_head"]["kernel"]), sown

    def result(loss, sown):
        aux, choices = sown
        loss = loss if aux is None else loss + aux
        return (loss, choices) if with_choices else loss

    return objective.loss(
        Heads(apply, hidden_and_head, result, head == "fused_ce"),
        model.cfg, traffic)


def choices_agreement(scores: dict, system: dict):
    """Over every array of the system's choices ``[..., k]`` and the
    reference's scores ``[..., e]`` under the same name: the number of
    tokens at which the top k of the scores is the system's set (the
    order inside a token's k does not count), the number at which the
    scores all but tie (NEAR_TIE), and the number of tokens."""
    import jax
    import jax.numpy as jnp

    agree = near = count = 0
    for name, chosen in system.items():
        s = scores[name].astype(jnp.float32)
        k = chosen.shape[-1]
        ranked, own = jax.lax.top_k(s, k + 1)
        agree += jnp.sum(jnp.all(
            jnp.sort(own[..., :k], -1) == jnp.sort(chosen, -1), -1))
        near += jnp.sum(ranked[..., k - 1] - ranked[..., k]
                        < NEAR_TIE * (ranked[..., 0] - jnp.min(s, -1)))
        count += s[..., 0].size
    return agree, near, count


def make_batch(model_sizes: dict, traffic: dict, n_seq: int, seed: int):
    """The seeded batch on the host, ``n_seq`` sequences: what the
    traffic's row of OBJECTIVES draws (uniform random tokens; for
    masked LM labels and a mask of a fixed count too; for block
    diffusion the clean tokens, which of them are masked, and their
    weights)."""
    return objective_of(traffic).batch(
        np.random.default_rng(seed), (n_seq, traffic["seq_len"]),
        model_sizes, traffic)


def make_step(loss_fn, opt, mesh, n: int, n_batch_args: int):
    """The jitted ``shard_map`` train step: parameters and optimizer
    state replicated and donated, the batch split over ``hvd``."""
    import jax
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def step_fn(p, s, *batch):
        loss, g = jax.value_and_grad(loss_fn)(p, *batch)
        upd, s = opt.update(g, s, p)
        p = optax.apply_updates(p, upd)
        return p, s, jax.lax.psum(loss, "hvd").reshape(1) / n

    return jax.jit(
        shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), P()) + (P("hvd"),) * n_batch_args,
            out_specs=(P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )


STEP_MODULE_HINT = "step_fn"


def compile_step(lowered, for_tpu: bool):
    """The lowered step compiled with the examples' TPU option."""
    if for_tpu:
        return lowered.compile(compiler_options={
            "xla_tpu_scoped_vmem_limit_kib": "65536"})
    return lowered.compile()


def make_compare(reference, loss_fn, model_sizes: dict, traffic: dict):
    """The jitted program of the reference check,
    ``compare(params, *batch)``: the system's loss, the reference's,
    the norm of the two gradients' difference over the norm of the
    reference's, and for a reference that ``TAKES_CHOICES`` the counts
    of ``choices_agreement`` (None where the two sides name their
    choices otherwise). Also returned: a dictionary that holds, once
    the program is traced, what each side names its choices."""
    import jax
    import optax

    kw = reference.arguments(model_sizes, traffic)
    takes_choices = getattr(reference, "TAKES_CHOICES", False)
    names = {}

    @jax.jit
    def compare(p, *b):
        out, g_sys = jax.value_and_grad(
            loss_fn, has_aux=takes_choices)(p, *b)
        l_sys, system = out if takes_choices else (out, {})
        scores = reference.choice_scores(p, b, **kw) \
            if takes_choices else {}
        names.update(system=sorted(system), reference=sorted(scores))
        # other names, or none: nothing to give the reference, which is
        # then compared freely, and `reference_choices` fails below
        matched = bool(system) and set(system) == set(scores)
        imposed = {"choices": system} if matched else {}
        # the reference's pass starts once the system's gradient is
        # whole: left to itself the compiler runs the two side by side
        # and keeps both passes' activations at once (at a share's 644 M
        # parameters 13.3 GiB of temporaries against 10.3 with the
        # barrier, compiled for a described v5e; PERF.md section 4)
        p_ref, g_sys = jax.lax.optimization_barrier((p, g_sys))
        l_ref, g_ref = jax.value_and_grad(
            lambda q: reference.mean_loss(q, b, **kw, **imposed))(p_ref)
        diff = jax.tree_util.tree_map(
            lambda a, r: a.astype(jax.numpy.float32) - r, g_sys, g_ref)
        return (l_sys, l_ref,
                optax.global_norm(diff) / optax.global_norm(g_ref),
                choices_agreement(scores, system) if matched else None)

    return compare, names


def reference_check(run, reference, loss_fn, params, model_sizes,
                    traffic):
    """Loss and gradient of the system's own loss function against the
    plain float32 reference, published width and depth, two seeded
    sequences of the cell's length, one device.

    A reference that ``TAKES_CHOICES`` is compared at the system's
    choices, and ``loss_fn`` is then the one that returns them beside
    the loss (``make_loss_fn(..., with_choices=True)``), so that they
    are those of the very pass whose gradient is compared. Where a
    router's k-th and (k+1)-th score are closer than bf16 rounds, the
    float32 reference picks another expert and the two gradients differ
    by an expert's whole contribution, which says nothing of the
    arithmetic; how many of the system's choices the reference would
    have made itself is held to the floor NEAR_TIE gives."""
    import jax

    batch = tuple(jax.numpy.asarray(a) for a in make_batch(
        model_sizes, traffic, 2, run.seed + 1))
    takes_choices = getattr(reference, "TAKES_CHOICES", False)
    compare, names = make_compare(reference, loss_fn, model_sizes,
                                  traffic)
    with run.span("reference_check"):
        *numbers, counts = compare(params, *batch)
        l_sys, l_ref, g_err = (float(x) for x in numbers)
    run.log(f"reference check: loss {l_sys:.5f} vs float32 reference "
            f"{l_ref:.5f}, gradient relative error {g_err:.3e}")
    run.check("reference_loss",
              math.isfinite(l_sys)
              and abs(l_sys - l_ref) <= LOSS_RTOL * abs(l_ref),
              f"{l_sys} vs {l_ref}",
              value=abs(l_sys - l_ref) / abs(l_ref), limit=LOSS_RTOL)
    run.check("reference_gradient", g_err <= GRAD_RTOL, f"{g_err}",
              value=g_err, limit=GRAD_RTOL)
    if not takes_choices:
        return
    if counts is None:
        run.check("reference_choices", False,
                  f"the program sowed {names['system']} into "
                  f"{CHOICES!r} and the reference scores "
                  f"{names['reference']}")
        return
    agree, near, count = (int(x) for x in counts)
    share, floor = agree / count, (count - near) / count
    run.log(f"reference check: {len(names['system'])} arrays of "
            f"choices, the float32 reference makes {100 * share:.3f}% "
            f"of the system's itself; its scores all but tie at "
            f"{100 - 100 * floor:.3f}% of the tokens")
    run.check("reference_choices", agree >= count - near,
              f"{share} < {floor}", value=share, limit=floor)


def reference_block(reference, model_sizes: dict, traffic: dict, mesh):
    """The jitted program of one block of the reference's loss over the
    global batch, ``block(params, *part) -> (sum, count)``, and how
    many of a chip's sequences a block takes: the most that divide its
    batch and hold at most the reference's BLOCK_TOKENS data tokens
    (REFERENCE_BLOCK_TOKENS where it states none)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    kw = reference.arguments(model_sizes, traffic)
    block_tokens = getattr(reference, "BLOCK_TOKENS",
                           REFERENCE_BLOCK_TOKENS)
    seq, per_chip = traffic["seq_len"], traffic["batch_per_chip"]
    if seq > block_tokens:
        name = getattr(reference, "__name__", reference)
        raise ValueError(
            f"one sequence of the traffic, seq_len {seq} data tokens, "
            f"is longer than the {block_tokens} tokens a chip takes in "
            f"one call of the reference {name!r} (its BLOCK_TOKENS, or "
            f"dp_train.REFERENCE_BLOCK_TOKENS): the reference takes "
            f"whole sequences")
    shard = NamedSharding(mesh, P("hvd"))
    block_fn = jax.jit(
        lambda p, *b: reference.nll_sum(p, b, **kw),
        in_shardings=(NamedSharding(mesh, P()),)
        + (shard,) * objective_of(traffic).n_batch_args,
        out_shardings=NamedSharding(mesh, P()))
    return block_fn, max(
        d for d in range(1, per_chip + 1)
        if per_chip % d == 0 and d * seq <= block_tokens)


def reference_global_loss(run, reference, params, host_batch,
                          model_sizes, traffic, mesh, n: int):
    """The reference's mean loss over the whole global batch, in blocks
    of at most REFERENCE_BLOCK_TOKENS data tokens a chip (each chip
    takes the sequences the step will give it)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    block_fn, blk = reference_block(reference, model_sizes, traffic,
                                    mesh)
    shard = NamedSharding(mesh, P("hvd"))
    per_chip = traffic["batch_per_chip"]
    total = count = 0.0
    with run.span("reference_global_loss"):
        for lo in range(0, per_chip, blk):
            rows = np.concatenate([
                np.arange(d * per_chip + lo, d * per_chip + lo + blk)
                for d in range(n)])
            part = tuple(jax.device_put(a[rows], shard)
                         for a in host_batch)
            s, c = block_fn(params, *part)
            total += float(s)
            count += float(c)
    return total / max(count, 1.0)


def build(run, model_sizes: dict, traffic: dict, mesh=None):
    """Model, optimizer and jitted step on ``mesh`` (default: the world
    ``hvd.init()`` finds)."""
    import optax

    import horovod_tpu as hvd

    objective = objective_of(traffic)  # refused before any device work
    with run.span("init"):
        hvd.init(mesh=mesh)
        n, mesh = hvd.size(), hvd.mesh()
        cfg, model, plain_model = make_model(model_sizes, traffic)
        opt = hvd.DistributedOptimizer(
            optax.adamw(traffic["learning_rate"]))
        loss_fn = make_loss_fn(model, traffic)
        step = make_step(loss_fn, opt, mesh, n, objective.n_batch_args)
    return dict(n=n, mesh=mesh, cfg=cfg, model=model,
                plain_model=plain_model, opt=opt, loss_fn=loss_fn,
                step=step)


def run_cell(run, model_sizes: dict, traffic: dict) -> dict:
    """One run of the cell: set-up with the correctness checks, the
    timed window, and with ``run.trace`` a traced window after it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    built = build(run, model_sizes, traffic)
    n, mesh = built["n"], built["mesh"]
    step, opt = built["step"], built["opt"]
    if n != run.chips:
        raise SystemExit(
            f"the cell asks for {run.chips} chip(s) and hvd.init() "
            f"found {n}")
    seq, per_chip = traffic["seq_len"], traffic["batch_per_chip"]
    tokens_per_step = n * per_chip * seq

    with run.span("init"):
        params = jax.jit(built["plain_model"].init)(
            jax.random.PRNGKey(run.seed),
            jnp.zeros((1, seq), dtype=jnp.int32))["params"]
        jax.block_until_ready(params)

    reference = harness.load_reference(run.config["family"], run.root)
    loss_fn = built["loss_fn"]
    if getattr(reference, "TAKES_CHOICES", False):
        loss_fn = make_loss_fn(built["model"], traffic, with_choices=True)
    reference_check(run, reference, loss_fn, params, model_sizes, traffic)

    with run.span("init"):
        opt_state = opt.init(params)
        params = hvd.broadcast_parameters(params, root_rank=0)
        jax.block_until_ready(params)
        host_batch = make_batch(model_sizes, traffic, n * per_chip,
                                run.seed)
        shard = NamedSharding(mesh, P("hvd"))
        batch = tuple(jax.device_put(a, shard) for a in host_batch)

    ref_loss0 = reference_global_loss(
        run, reference, params, host_batch, model_sizes, traffic, mesh, n)

    on_tpu = jax.default_backend() == "tpu"
    with run.span("lower"):
        lowered = step.lower(params, opt_state, *batch)
    with run.span("compile"):
        compiled = compile_step(lowered, on_tpu)
    run.hlo_text = compiled.as_text()
    run.memory = compiled.memory_analysis()
    run.step_module_hint = STEP_MODULE_HINT

    losses = []  # device arrays, read after the window has closed

    def run_steps(k, annotate=False):
        nonlocal params, opt_state
        for _ in range(k):
            with run.span("step_call", annotate=annotate):
                params, opt_state, loss = compiled(
                    params, opt_state, *batch)
            losses.append(loss)
        with run.span("wait_loss", annotate=annotate):
            np.asarray(losses[-1])  # host sync closes the chunk

    with run.span("warmup"):
        run_steps(WARMUP_STEPS)

    chunk_s = []
    run.begin_window()
    t_end = time.perf_counter() + run.seconds
    while True:
        t0 = time.perf_counter()
        run_steps(CHUNK_STEPS)
        t1 = time.perf_counter()
        chunk_s.append(t1 - t0)
        if t1 >= t_end and len(losses) > LOSS_STEP:
            break
    run.end_window()

    rates = [CHUNK_STEPS * tokens_per_step / s / n for s in chunk_s]
    rate = statistics.median(rates)
    run.log(f"window: {len(chunk_s)} chunks of {CHUNK_STEPS} steps, "
            f"{sum(chunk_s):.2f} s; tokens/s/chip median {rate:.1f} "
            f"min {min(rates):.1f} max {max(rates):.1f}")

    if run.trace:
        with run.profiler():
            run_steps(TRACED_STEPS, annotate=True)

    host_losses = [float(np.asarray(x)[0]) for x in losses]
    run.log("losses: " + " ".join(f"{x:.4f}" for x in host_losses[:20]))
    loss0, loss16 = host_losses[0], host_losses[LOSS_STEP]
    run.log(f"step 0 loss {loss0:.5f} vs float32 reference over the "
            f"global batch {ref_loss0:.5f}")
    run.check("global_batch_loss",
              abs(loss0 - ref_loss0) <= GLOBAL_LOSS_RTOL * abs(ref_loss0),
              f"{loss0} vs {ref_loss0}",
              value=abs(loss0 - ref_loss0) / abs(ref_loss0),
              limit=GLOBAL_LOSS_RTOL)
    failed = sum(not math.isfinite(x) for x in host_losses)
    run.check("losses_finite", failed == 0, f"{failed} not finite",
              value=failed, limit=0)
    run.check("loss_falls", loss16 < loss0, f"{loss0} -> {loss16}",
              value=loss16, limit=loss0)
    run.check("no_compile_in_window", run.window_compiles == 0,
              f"{run.window_compiles} compilations",
              value=run.window_compiles, limit=0)
    if on_tpu:
        from benchmarks import hlo
        run.check("mosaic_kernels_compiled",
                  hlo.MOSAIC_TARGET in run.hlo_text,
                  "no tpu_custom_call in the compiled step")
    placement_checks(run, n, batch, params, losses[-1])

    mem = run.memory
    step_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  - mem.alias_size_in_bytes + mem.temp_size_in_bytes
                  + mem.generated_code_size_in_bytes)
    run.log(f"compiled step per device: arguments "
            f"{mem.argument_size_in_bytes} outputs "
            f"{mem.output_size_in_bytes} aliased "
            f"{mem.alias_size_in_bytes} temporaries "
            f"{mem.temp_size_in_bytes} code "
            f"{mem.generated_code_size_in_bytes} bytes")
    run.step_bytes = step_bytes
    run.tokens_per_s_per_chip = rate
    run.step_seconds = tokens_per_step / n / rate
    return {
        "attempted": len(host_losses), "failed": failed,
        "metrics": {
            "tokens_per_s_per_chip": (rate, "tokens/s/chip"),
            "step_hbm_gib": (step_bytes / harness.GIB, "GiB"),
            "loss_step_16": (loss16, "nats"),
        },
        # neither a time nor a size on a device: a rehearsal may print it
        "platform_free": {"loss_step_16"},
    }


def _bit_sums(params, run_mesh):
    """[n devices, n leaves] uint32: each device's wrapping sum of the
    bits of its own copy of every parameter."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(p):
        return jnp.stack([
            jnp.sum(jax.lax.bitcast_convert_type(
                x.astype(jnp.float32), jnp.uint32), dtype=jnp.uint32)
            for x in jax.tree_util.tree_leaves(p)])[None]

    return jax.jit(shard_map(local, mesh=run_mesh, in_specs=(P(),),
                             out_specs=P("hvd"), check_vma=False))(params)


def placement_checks(run, n, batch, params, loss):
    """chip_smoke.py's assertions about where things live."""
    import jax

    per_device = {s.device: float(np.asarray(s.data)[0])
                  for s in loss.addressable_shards}
    run.check("loss_on_every_device", len(per_device) == n,
              f"{len(per_device)} of {n}")
    run.check("loss_equal_on_every_device",
              len(set(per_device.values())) == 1, f"{per_device}")
    if n == 1:
        return
    placed = batch[0].sharding.devices_indices_map(batch[0].shape)
    run.check("batch_split_over_devices",
              len(placed) == n
              and len(set(map(str, placed.values()))) == n, f"{placed}")
    leaves = jax.tree_util.tree_leaves(params)
    run.check("parameters_replicated",
              all(x.sharding.is_fully_replicated
                  and len(x.sharding.device_set) == n for x in leaves),
              "a parameter is not on every device")
    # bitwise equal on every device after the window: each device sums
    # its own copy's bits and the host compares the n sums of each leaf
    sums = np.asarray(_bit_sums(params, run_mesh=batch[0].sharding.mesh))
    different = int(np.sum(np.any(sums != sums[:1], axis=0)))
    run.check("parameters_bitwise_equal", different == 0,
              f"{different} leaves differ between devices",
              value=different, limit=0)
    from benchmarks import hlo
    run.check("allreduce_in_step", len(hlo.allreduces(run.hlo_text)) > 0,
              "no all-reduce in the compiled multi-chip step")
