"""Plain reference for the ``window_gated_moe_lm`` family: a causal
decoder of window and full attention layers with an output gate, four
norms a layer, leading dense layers and then a sigmoid-scored routed MLP
beside a shared expert (the published ``afmoe`` block, Hugging Face
``Afmoe*``, as one chip's share of a deployment):

    x0 = embedding_multiplier * E[tokens]
    x += N2(Attn_i(N1 x));  x += N4(Mlp_i(N3 x))      four RMS norms
    logits = N(x) W_head                              untied head

``Attn``: q, k, v without bias, each key and value head serving heads /
kv_heads query heads; an RMS norm of q and of k over the head's width
(one scale for all heads); rotary positions **in the kinds of layer the
group's ``rope_kinds`` names and in no other** (the window layers);
scores / sqrt(head width); in a ``window_attention`` layer query q sees
key k where 0 <= q - k < ``sliding_window``, in an ``attention`` layer
where k <= q; the heads' output times sigmoid(W_g y), W_g ``hidden x
heads x head width`` on the same normed input y; the output projection.

``Mlp``: in the first ``dense_layers`` layers W_down(silu(W_gate y) *
W_up y). After them, with s = sigmoid(W_r y) in float32 over all of the
router's experts and b the choice's correction (``expert_bias``, one
number an expert): the k largest of s + b are chosen; their weights are
s at the chosen (b corrects the choice, never the weight), over their
sum where ``norm_topk_prob``, times ``routed_scaling_factor``; the
result is the weighted sum of the chosen SwiGLU experts **that live
here** plus one shared SwiGLU MLP every token goes through.

The share, as the program has it (``horovod_tpu/models/moe.py``) and as
``block_diffusion_moe_lm.py`` states it: of the router's ``num_experts``
this chip holds ``experts_held`` from ``first_expert``; what the absent
experts would add is left out; the shared expert is computed whole on
every chip; and where fewer experts are held than the router has, the
scores are constants of the backward pass (no exchange brings the absent
experts' results, so the router is not trained and passes no gradient
to its input). b has no gradient anywhere. The sum over a deployment's
shares of the routed parts, with the shared expert counted once, is the
uncut layer (``tests/test_window_gated_moe.py`` holds that).

Departures from the source, all of them: the share above; no rule that
moves b between steps (the source's trainer moves it by the experts'
load at rate ``load_balance_coeff``; b stays what the parameters say,
zero at the seed); groups of experts are one (``n_group`` = 1), so the
choice is the top k of one array; the denominator of the
renormalisation has no 1e-20 added (s > 0).

To the contract at the top of ``transformer_lm.py``: float32 under
``jax.default_matmul_precision("highest")``, nothing imported from the
program, the program's parameter tree in (``tok_emb/embedding``,
``block_<i>/{ln_attn, ln_post_attn, ln_mlp, ln_post_mlp}/scale``,
``block_<i>/attn/{query, key, value, gate, out}/kernel``,
``attn/{q_norm, k_norm}/scale``; a dense layer's ``mlp/{gate, up,
fc2}/kernel``; a routed layer's ``mlp/{router/kernel, expert_bias,
gate, up, down, shared_gate/kernel, shared_up/kernel,
shared_down/kernel}`` with ``gate`` and ``up`` ``[held, h, m]`` and
``down`` ``[held, m, h]``; ``ln_final/scale``, ``lm_head/kernel``). It
uses the allowances the contract gives and nothing else:
``jax.checkpoint`` around each layer, ``jax.lax.map`` over heads, over
blocks of queries inside a head and over blocks of rows at the head,
and a ``lax.scan`` over the held experts whose weights are the scanned
operand: every held expert is computed densely for every token and
selected by the choices. The window is a mask built from its equation
over all the keys. They change what is kept for the backward pass, not
one number that is computed.

A model that chooses: ``TAKES_CHOICES``. ``choice_scores`` returns each
routed layer's s + b under the name the job gives the program's choices
(``block_<i>/mlp/experts/0``); ``mean_loss(..., choices=)`` takes the k
experts of every position as given and computes their weights itself,
from its own s.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

TAKES_CHOICES = True
# tokens a chip takes in one call of ``nll_sum``: one sequence of 8,192
BLOCK_TOKENS = 8192
# the most queries of a head, and the most rows of the vocabulary
# head, that are computed at once
QUERY_BLOCK = 1024
ROW_BLOCK = 2048

WINDOW, FULL = "window_attention", "attention"


def arguments(model: dict, traffic: dict) -> dict:
    if traffic["objective"] != "causal_lm" or not model["causal"]:
        raise ValueError("this family is a causal language model")
    if (model["norm"], model["activation"], model["tie_embeddings"],
            model["qk_norm"], model["attn_output_gate"],
            model["post_norms"], model["score_func"],
            model["position"]) != ("rmsnorm", "swiglu", False, True, True,
                                   True, "sigmoid", "rope"):
        raise ValueError(
            "this family has RMSNorm, four norms a layer, SwiGLU, an "
            "untied head, q/k norms, an output gate, rotary positions "
            "and a sigmoid-scored router")
    return dict(
        num_layers=model["num_layers"],
        layer_types=tuple(model["layer_types"]),
        rope_kinds=tuple(model["rope_kinds"]),
        window=model["sliding_window"],
        kv_heads=model["num_kv_heads"], theta=model["rope_theta"],
        eps=model["layernorm_epsilon"],
        dense_layers=model["dense_layers"],
        num_experts=model["num_experts"], held=model["experts_held"],
        first_expert=0, per_token=model["experts_per_token"],
        renormalise=model["norm_topk_prob"],
        scale=model["routed_scaling_factor"],
        shared_experts=model["shared_experts"],
        embedding_multiplier=model["embedding_multiplier"])


def choice_name(layer: int) -> str:
    return f"block_{layer}/mlp/experts/0"


def _divisor(n: int, most: int) -> int:
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def _mapped(fn, blocks, *arrays):
    """``fn`` over ``blocks`` equal parts of the arrays' first axis,
    one part at a time, keeping only the parts for the backward pass;
    the results joined along that axis."""
    parts = tuple(a.reshape(blocks, a.shape[0] // blocks, *a.shape[1:])
                  for a in arrays)
    out = jax.lax.map(lambda xs: jax.checkpoint(fn)(*xs), parts)
    return out.reshape(-1, *out.shape[2:])


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotate(x, theta):
    """x: [B, T, heads, d], positions 0..T-1."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def visible(q_index, k_index, window: int):
    """[Q, K] bool: query q sees key k where 0 <= q - k, and where
    ``window`` is not 0 also q - k < window."""
    ahead = q_index[:, None] - k_index[None, :]
    return (ahead >= 0) & (ahead < window) if window else ahead >= 0


def _attend(q, k, v, window: int):
    """q, k, v: [heads, B, T, d] -> [heads, B, T, d], a head at a time
    and inside it a block of queries at a time, each over all the keys
    under the mask."""
    t = q.shape[2]
    rows = _divisor(t, QUERY_BLOCK)
    keys = jnp.arange(t)

    def head(qh, kh, vh):  # [B, T, d]
        def queries(qb, index):  # [rows, B, d], [rows]
            s = jnp.einsum("qbd,bkd->bqk", qb, kh) / math.sqrt(
                qb.shape[-1])
            s = jnp.where(visible(index, keys, window)[None], s, -jnp.inf)
            return jnp.einsum("bqk,bkd->qbd", jax.nn.softmax(s, -1), vh)

        out = _mapped(queries, t // rows, qh.transpose(1, 0, 2), keys)
        return out.transpose(1, 0, 2)

    return jax.lax.map(lambda xs: jax.checkpoint(head)(*xs), (q, k, v))


def attention(y, a, *, kv_heads, eps, theta, window, rotates):
    """One attention mixer on the normed input y [B, T, h]: ``window``
    0 is a full (causal) layer; ``rotates`` whether q and k carry the
    position code."""
    q = jnp.einsum("bth,hnd->btnd", y, a["query"]["kernel"])
    k = jnp.einsum("bth,hnd->btnd", y, a["key"]["kernel"])
    v = jnp.einsum("bth,hnd->btnd", y, a["value"]["kernel"])
    if k.shape[2] != kv_heads:
        raise ValueError(f"{k.shape[2]} key heads in the parameters, "
                         f"{kv_heads} in the configuration")
    # one scale of the head's width for all heads, then the rotation
    q = _rms(q, a["q_norm"]["scale"], eps)
    k = _rms(k, a["k_norm"]["scale"], eps)
    if rotates:
        q, k = _rotate(q, theta), _rotate(k, theta)
    # each key and value head serves heads / kv_heads query heads
    k = jnp.repeat(k, q.shape[2] // kv_heads, axis=2)
    v = jnp.repeat(v, q.shape[2] // kv_heads, axis=2)
    o = _attend(*(z.transpose(2, 0, 1, 3) for z in (q, k, v)),
                window).transpose(1, 2, 0, 3)
    o = o * jax.nn.sigmoid(
        jnp.einsum("bth,hnd->btnd", y, a["gate"]["kernel"]))
    return jnp.einsum("bqnd,ndh->bqh", o, a["out"]["kernel"])


def router_scores(y, m):
    """([..., e], [..., e]) float32: s, each expert's sigmoid score, and
    s + b, what the choice is the top k of."""
    s = jax.nn.sigmoid(y @ m["router"]["kernel"])
    return s, s + jax.lax.stop_gradient(m["expert_bias"])


def shared_expert(y, m):
    """The MLP every token goes through beside its routed experts."""
    return (jax.nn.silu(y @ m["shared_gate"]["kernel"])
            * (y @ m["shared_up"]["kernel"])) @ m["shared_down"]["kernel"]


def routed_experts(y, m, choices, *, first_expert, per_token,
                   renormalise, scale):
    """[N, h] -> ([N, h], s + b [N, e]): the sum over a token's choices
    that live here of weight x down(silu(gate y) * up y). ``choices``
    [N, k] are the experts taken for each token (the reference's own
    top k of s + b where None); their weights are the reference's own
    s at them."""
    s, corrected = router_scores(y, m)
    held = m["gate"].shape[0]
    if held < s.shape[-1]:
        # a share has no exchange: its router is not trained, and the
        # scores are constants of the backward pass (module docstring)
        s = jax.lax.stop_gradient(s)
    if choices is None:
        choices = jax.lax.top_k(corrected, per_token)[1]
    weights = jnp.take_along_axis(s, choices, axis=-1)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * scale
    # [held, N]: the weight with which a token takes each held expert
    share = jnp.sum(
        weights[None] * (choices[None] == (
            first_expert + jnp.arange(held))[:, None, None]), axis=-1)

    def expert(y, gate, up, down, share):
        return share[:, None] * (
            (jax.nn.silu(y @ gate) * (y @ up)) @ down)

    def step(total, xs):
        return total + jax.checkpoint(expert)(y, *xs), None

    total, _ = jax.lax.scan(
        step, jnp.zeros_like(y), (m["gate"], m["up"], m["down"], share))
    return total, corrected


def _block(x, p, kind, routed, choices, *, kv_heads, theta, eps, window,
           rope_kinds, shared_experts, **routing):
    """One layer: ([B, T, h], s + b [B, T, e] of a routed layer or
    None)."""
    if kind not in (WINDOW, FULL):
        raise ValueError(f"this family has no layer of kind {kind!r}")
    y = _rms(x, p["ln_attn"]["scale"], eps)
    mixed = attention(y, p["attn"], kv_heads=kv_heads, eps=eps,
                      theta=theta, window=window if kind == WINDOW else 0,
                      rotates=kind in rope_kinds)
    x = x + _rms(mixed, p["ln_post_attn"]["scale"], eps)
    y = _rms(x, p["ln_mlp"]["scale"], eps)
    m, scores = p["mlp"], None
    if routed:
        rows = y.reshape(-1, y.shape[-1])
        chosen = None if choices is None else choices.reshape(
            rows.shape[0], -1)
        out, scores = routed_experts(rows, m, chosen, **routing)
        if shared_experts:
            out = out + shared_expert(rows, m)
        out = out.reshape(x.shape)
        scores = scores.reshape(*x.shape[:2], -1)
    else:
        out = (jax.nn.silu(y @ m["gate"]["kernel"])
               * (y @ m["up"]["kernel"])) @ m["fc2"]["kernel"]
    return x + _rms(out, p["ln_post_mlp"]["scale"], eps), scores


def hidden(params, tokens, choices, *, num_layers, layer_types,
           dense_layers, num_experts, held, embedding_multiplier, **kw):
    """([B, T, h] float32, the final norm's output; the head's kernel;
    each routed layer's s + b by name). ``choices`` is None (every
    layer takes its own top k) or the experts to take, by the same
    names."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    if len(layer_types) != num_layers:
        raise ValueError(f"{len(layer_types)} kinds for {num_layers} "
                         f"layers")
    for i in range(dense_layers, num_layers):
        mlp = p[f"block_{i}"]["mlp"]
        if mlp["router"]["kernel"].shape[1] != num_experts \
                or mlp["gate"].shape[0] != held:
            raise ValueError(
                f"layer {i}: a router over "
                f"{mlp['router']['kernel'].shape[1]} experts and "
                f"{mlp['gate'].shape[0]} held in the parameters, "
                f"{num_experts} and {held} in the configuration")
    x = embedding_multiplier * p["tok_emb"]["embedding"][tokens]
    scores = {}
    for i, kind in enumerate(layer_types):
        routed = i >= dense_layers
        given = choices[choice_name(i)] if routed and choices is not None \
            else None
        x, s = jax.checkpoint(
            lambda x, bp, given, kind=kind, routed=routed: _block(
                x, bp, kind, routed, given, **kw))(
                    x, p[f"block_{i}"], given)
        if routed:
            scores[choice_name(i)] = s
    return (_rms(x, p["ln_final"]["scale"], kw["eps"]),
            p["lm_head"]["kernel"], scores)


def _nll(rows, head, targets):
    """Sum over the rows of -log softmax(row . head)[target], a block
    of rows at a time."""
    def part(x, target):
        lg = x @ head
        return jnp.sum(
            jax.scipy.special.logsumexp(lg, axis=-1)
            - jnp.take_along_axis(lg, target[:, None], -1)[:, 0])[None]

    n = rows.shape[0]
    return jnp.sum(_mapped(part, n // _divisor(n, ROW_BLOCK), rows,
                           targets))


def choice_scores(params, batch, **kw):
    """{name: [B, T, e] float32}: every routed layer's s + b on the
    reference's own pass (each layer fed by the layers before it at the
    reference's own choices)."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, batch[0], None, **kw)[2]


def nll_sum(params, batch, *, choices=None, **kw):
    """(sum of the negative log likelihoods, positions that count) of
    one block of sequences ``(tokens,)``: each position predicts the
    next token, the last one nothing."""
    with jax.default_matmul_precision("highest"):
        tokens = batch[0]
        x, head, _ = hidden(params, tokens, choices, **kw)
        n, t = tokens.shape
        total = _nll(x[:, :-1].reshape(n * (t - 1), -1), head,
                     tokens[:, 1:].reshape(-1))
        return total, jnp.float32(n * (t - 1))


def mean_loss(params, batch, **kw):
    total, count = nll_sum(params, batch, **kw)
    return total / count
