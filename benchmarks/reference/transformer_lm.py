"""Plain reference for the ``transformer_lm`` family: GPT-2 (causal)
and the repo's BERT-shaped encoder (bidirectional), forward pass and
loss in straightforward ``jax.numpy``.

float32 throughout under ``jax.default_matmul_precision("highest")``
(on a TPU a float32 product otherwise runs in bfloat16 passes); no
kernel, no flax module, nothing imported from the program. It takes the
program's parameter tree (flax names: ``tok_emb/embedding``, ``pos_emb``,
``block_<i>/{ln_attn,attn/{query,key,value,out},ln_mlp,mlp/{fc1,fc2}}``,
``ln_final``) so that the same seeded weights go through both.

The equations are the published pre-LayerNorm GPT-2 block (Radford et
al. 2019; Hugging Face ``GPT2Block``): x += Attn(LN(x)); x += MLP(LN(x));
final LN; logits through the tied token embedding. The encoder is the
same block without the causal mask: that is how this repo's BERT
departs from the published post-LN model, and the configuration file
lists it. The layers run under ``lax.scan`` with ``jax.checkpoint``:
that changes what is kept for the backward pass, not one number of it.

**What a reference module is** (this one and every
``benchmarks/reference/<family>.py``; a job finds the file by the
configuration's ``family`` and knows no family's name):

* ``arguments(model, traffic) -> dict``: the keyword arguments the two
  functions below take, read from the configuration's ``model`` group
  as it is run and from the cell's traffic. A key the module needs and
  the group lacks is a ``KeyError``: a reference assumes no default of
  the program's;
* ``mean_loss(params, batch, **arguments) -> scalar``: the loss the
  program's loss function returns on ``batch``, every term of it (a
  router's load-balancing term too), differentiable in ``params``;
* ``nll_sum(params, batch, **arguments) -> (sum, count)`` over one
  block of whole sequences, such that the sums over a batch's blocks,
  divided, are the batch's loss. A term that does not add up over
  blocks (one taken over all of a chip's tokens) needs the chip's batch
  in one block: the module then states ``BLOCK_TOKENS``, the tokens a
  chip takes in one call (8192 where it states none);
* ``params`` is the program's parameter tree, as the program made it
  from the seed, and ``batch`` the job's, by the traffic's objective
  (``dp_train.OBJECTIVES``): ``(tokens,)`` for ``causal_lm``,
  ``(tokens, labels, mask)`` for ``masked_lm``, ``(x0, m, w)`` for
  ``block_diffusion`` (clean tokens, which are masked, their weights
  1/t; the module builds the step's input ``[x_t ; x0]``, its
  positions and its mask itself, and returns the sum over the noisy
  half of m · w · nll and the count B·T);
* ``BLOCK_TOKENS``, like the traffic's ``seq_len``, counts **data
  tokens**, whatever positions a data token runs (two under
  ``block_diffusion``); one sequence longer than the block is refused
  by name (``dp_train.reference_block``): the reference takes whole
  sequences;
* float32 throughout under ``jax.default_matmul_precision("highest")``,
  and nothing imported from the program;
* what a reference may do to fit a chip and still be plain: it may
  change what is **kept** for the backward pass, never what is
  **computed**. ``jax.checkpoint`` around a layer or a mapped function,
  and ``jax.lax.map`` over heads, over blocks of queries or over blocks
  of rows, are that: every number is the same sum of the same float32
  terms. (This module's ``lax.scan`` over stacked layers is the same
  kind; at a share's size the stacked copy of the weights is a tree too
  many, and the layers are looped.) A blockwise softmax, a lower
  precision, a fused kernel or anything of the program's is not. A
  mapped function that closes over a weight keeps more than it saves:
  read at 644 M parameters, an MLP mapped over blocks of rows kept 7
  GiB more than the MLP whole (``tests/benchmarks/data/share_fixture``
  has a reference written to this, and ``benchmarks/compare_size.py``
  reads what a comparison needs of a chip before a chip is asked);
* a model that makes discrete choices (a router's k experts of e for a
  token) cannot be compared through them: where two scores are closer
  than bfloat16 rounds, float32 picks another expert, and the gradients
  then differ by a whole expert's contribution, which says nothing of
  the arithmetic. Its modules sow each choice into the Flax collection
  ``choices`` (integer arrays ``[..., k]``, the last axis one token's k
  choices), and its reference module states ``TAKES_CHOICES = True``
  and has besides: ``choice_scores(params, batch, **arguments)``, the
  float32 scores ``[..., e]`` (finite, k < e) whose top k the
  reference would choose itself, as ``{path: array}`` under the names
  the job gives the program's (``dp_train.named_choices``: the modules'
  names, the name sown under and ``0``, joined by ``/``); and
  ``mean_loss(..., choices=the system's, by those names)``, which takes
  the choices as given and computes everything else, the chosen
  experts' weights too, itself. The reference check reads the system's
  choices from the pass whose gradient it compares, and compares loss
  and gradient at them under the limits of every family. The share of
  tokens at which the top k of ``choice_scores`` is the system's set is
  held to a floor that the job derives from those scores
  (``dp_train.NEAR_TIE``) and the module does not state. A module
  without the flag is called as before, and ``nll_sum`` is always
  called without choices;
* the limits on the loss and the gradient are the job's, for every
  family: a module states none, and one that a family's readings on the
  chip show to be wrong is changed by a ``benchmark`` PR, with those
  readings in ``PERF.md``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def arguments(model: dict, traffic: dict) -> dict:
    return dict(objective=traffic["objective"],
                num_layers=model["num_layers"], causal=model["causal"],
                eps=model["layernorm_epsilon"])


def _ln(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, *, causal, eps):
    y = _ln(x, p["ln_attn"], eps)
    a = p["attn"]
    q = jnp.einsum("bth,hnd->btnd", y, a["query"]["kernel"]) \
        + a["query"]["bias"]
    k = jnp.einsum("bth,hnd->btnd", y, a["key"]["kernel"]) \
        + a["key"]["bias"]
    v = jnp.einsum("bth,hnd->btnd", y, a["value"]["kernel"]) \
        + a["value"]["bias"]
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        t = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnqk,bknd->bqnd", w, v)
    x = x + jnp.einsum("bqnd,ndh->bqh", o, a["out"]["kernel"]) \
        + a["out"]["bias"]
    y = _ln(x, p["ln_mlp"], eps)
    m = p["mlp"]
    h = _gelu_tanh(y @ m["fc1"]["kernel"] + m["fc1"]["bias"])
    return x + h @ m["fc2"]["kernel"] + m["fc2"]["bias"]


def logits(params, tokens, *, num_layers, causal, eps):
    """[B, T, V] float32 logits of the parameter tree on ``tokens``."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    t = tokens.shape[1]
    x = p["tok_emb"]["embedding"][tokens] + p["pos_emb"][:t]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[p[f"block_{i}"] for i in range(num_layers)])

    @jax.checkpoint
    def layer(x, bp):
        return _block(x, bp, causal=causal, eps=eps), None

    x, _ = jax.lax.scan(layer, x, stacked)
    x = _ln(x, p["ln_final"], eps)
    return x @ p["tok_emb"]["embedding"].T


def _nll(lg, targets):
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    return lse - jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]


def nll_sum(params, batch, *, objective, num_layers, causal, eps):
    """(sum of the negative log likelihoods, number of positions that
    count) of one block of sequences. ``batch`` is ``(tokens,)`` for
    ``causal_lm`` (each position predicts the next token) and
    ``(tokens, labels, mask)`` for ``masked_lm`` (labels at the masked
    positions)."""
    with jax.default_matmul_precision("highest"):
        lg = logits(params, batch[0], num_layers=num_layers,
                    causal=causal, eps=eps)
        if objective == "causal_lm":
            nll = _nll(lg[:, :-1], batch[0][:, 1:])
            return jnp.sum(nll), jnp.float32(nll.size)
        if objective == "masked_lm":
            _, labels, mask = batch
            nll = jnp.where(mask, _nll(lg, labels), 0.0)
            return jnp.sum(nll), jnp.sum(mask).astype(jnp.float32)
    raise ValueError(f"unknown objective {objective!r}")


def mean_loss(params, batch, **kw):
    total, count = nll_sum(params, batch, **kw)
    return total / jnp.maximum(count, 1.0)
