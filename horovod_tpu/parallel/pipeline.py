"""Pipeline parallelism: GPipe microbatch schedule over a `pp` mesh axis.

Beyond the reference: Horovod has no pipeline layer at all (SURVEY.md
§2.5 — TP/PP absent; users hand-roll on process sets). TPU-native
pipelining is a natural extension of the same design language as the
rest of `parallel/`: a `shard_map` over the `pp` axis in which every
stage runs the SAME traced program, activations hop stage→stage with
`lax.ppermute`, and the whole schedule sits inside one jitted train
step so XLA overlaps the point-to-point transfers with stage compute.

Shape of the thing (the scaling-book recipe):

  * layer weights are STACKED: each transformer block's params become
    leading-dim `L` arrays, sharded `P("pp")` on that dim — stage `i`
    holds layers `[i*L/S, (i+1)*L/S)`, and inside the shard_map applies
    its local stack with `lax.scan` (one compiled block body, not L
    unrolled copies);
  * the batch is split into `M` microbatches; tick `t` of `M + S - 1`
    feeds microbatch `t` into stage 0 while stages `1..S-1` consume the
    activation ppermuted from their predecessor on tick `t-1` (the
    GPipe bubble is the first/last `S-1` ticks);
  * embedding and LM head stay OUTSIDE the pipelined region (they are
    not per-layer weights); the last stage's outputs are returned to
    every rank with a masked psum.

Backward needs no separate schedule: `ppermute` and `scan` are
differentiable, so `jax.grad` of a pipelined loss replays the schedule
in reverse — the 1F1B-style overlap falls out of XLA's scheduling of
the transposed program.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models.transformer import (
    Embedding, LmHead, TransformerConfig, build_block, embedding_keys,
    head_keys, layer_specs)


def stack_block_params(params: dict, prefix: str = "block_"):
    """Split a Transformer param dict into (stacked_blocks, rest):
    `stacked_blocks` has every `block_i` subtree stacked on a new
    leading layer dim (requires homogeneous blocks — true for this
    model family); `rest` keeps embedding/head/final-norm params."""
    blocks = {k: v for k, v in params.items() if k.startswith(prefix)}
    rest = {k: v for k, v in params.items() if not k.startswith(prefix)}
    orderd = [blocks[f"{prefix}{i}"] for i in range(len(blocks))]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *orderd
    )
    return stacked, rest


def unstack_block_params(stacked, rest: dict, prefix: str = "block_"):
    """Inverse of stack_block_params (checkpoint interchange)."""
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    out = dict(rest)
    for i in range(n):
        out[f"{prefix}{i}"] = jax.tree_util.tree_map(
            lambda x: x[i], stacked
        )
    return out


def gpipe(
    block_apply: Callable,
    stacked_params,
    h,
    *extra,
    axis: str = "pp",
    num_microbatches: int = 2,
):
    """GPipe schedule — call INSIDE shard_map over `axis`.

    `block_apply(block_params, h, *extra) -> h` applies one layer;
    `stacked_params` is this stage's local `[L_local, ...]` stack;
    `h` is the full-batch input `[B, ...]` (replicated across stages);
    returns the full-batch output, valid on every stage (masked psum
    from the last stage).
    """
    S = lax.psum(1, axis)
    idx = lax.axis_index(axis)
    M = num_microbatches
    B = h.shape[0]
    assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
    mb = B // M
    xs = h.reshape((M, mb) + h.shape[1:])

    def stage(p_stack, u, *e):
        # this stage's layers, one compiled body via scan
        def body(carry, p):
            return block_apply(p, carry, *e), None

        out, _ = lax.scan(body, u, p_stack)
        return out

    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    def tick(carry, t):
        recv, outs = carry
        # stage 0 ingests microbatch t (zeros once the batch is drained —
        # bubble ticks); later stages consume their predecessor's send
        feed = xs[jnp.minimum(t, M - 1)]
        live = jnp.asarray(t < M, dtype=h.dtype)
        u = jnp.where(idx == 0, feed * live, recv)
        y = stage(stacked_params, u, *extra)
        nxt = lax.ppermute(y, axis, fwd_perm)
        # last stage completes microbatch t-(S-1) at tick t
        done_slot = t - (S - 1)
        outs = lax.cond(
            done_slot >= 0,
            lambda o: lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(done_slot, 0), axis=0
            ),
            lambda o: o,
            outs,
        )
        return (nxt, outs), None

    outs0 = jnp.zeros((M, mb) + h.shape[1:], dtype=h.dtype)
    (_, outs), _ = lax.scan(
        tick, (jnp.zeros((mb,) + h.shape[1:], h.dtype), outs0),
        jnp.arange(M + S - 1),
    )
    # only the LAST stage's collected outputs are the real ones
    mask = (idx == (S - 1)).astype(h.dtype)
    outs = lax.psum(outs * mask, axis)
    return outs.reshape((B,) + h.shape[1:])


def _lm_pipeline_pieces(cfg, mesh, who, params, attention_fn, tokens,
                        num_microbatches):
    """Shared plumbing for the GPipe and 1F1B LM entry points: the
    param-tree split (stacked blocks / embed / head), the single-block
    apply closure, and the position arrays, all from the pieces
    `Transformer` itself is made of (models/transformer.py), so that a
    key the param layout grows reaches both schedules or neither."""
    spec = _check_pp(cfg, mesh, who)
    stacked, rest = stack_block_params(params)
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    embed_params = {k: rest[k] for k in embedding_keys(cfg)}
    # untied models never read tok_emb in the head — including it would
    # make 1F1B carry + psum a dead vocab x hidden zero-grad buffer
    head_params = {k: rest[k] for k in head_keys(cfg)}

    def block_apply(p_block, h, pos):
        return build_block(cfg, spec, attention_fn).apply(
            {"params": p_block}, h, pos)

    # positions per MICROBATCH: activations flow through the schedule
    # in [B/M, T, H] slices and every microbatch shares the same arange
    # rows, so one slice serves all ticks
    pos_mb = positions[: B // num_microbatches]
    return (stacked, embed_params, head_params, block_apply, positions,
            pos_mb)


def _check_pp(cfg, mesh, who):
    """The one `LayerSpec` every stage scans over its stacked blocks
    (`stack_block_params`; every stage runs the same traced program),
    or a refusal where the model's layers are not all built alike or
    the schedule has not been held to what the block holds."""
    # not `callers_head`: under `remat` every block is rebuilt whole, as
    # in the serial model that builds the logits (a pipelined big model
    # without remat would OOM where the serial path fits), and the last
    # block is built like the others
    specs = layer_specs(cfg)
    if len({dataclasses.replace(spec, index=0) for spec in specs}) > 1:
        routed = [spec.index for spec in specs if spec.routed]
        raise ValueError(
            f"{who} stacks a stage's blocks and scans one block over "
            f"them; this model's layers are not built alike: their "
            f"kinds are {sorted({spec.kind for spec in specs})}"
            + (f" and layers {routed} alone have the routed MLP, behind "
               f"leading `dense_layers`"
               if 0 < len(routed) < len(specs) else "")
            + ". What is missing: a stage that holds a stack of unlike "
            "blocks (a tree a kind and a tree for the leading dense "
            "layers, run in the pattern's order)")
    unheld = [f"{name} {getattr(cfg, name)!r}" for name, neutral in (
        ("attn_output_gate", False), ("post_norms", False),
        ("score_func", "softmax"), ("routed_scaling_factor", 1.0),
        ("shared_experts", 0)) if getattr(cfg, name) != neutral]
    if unheld:
        raise ValueError(
            f"{who} has been held to no model with "
            f"{', '.join(unheld)}: its stacked block and its schedule "
            f"are tested on the plain block alone")
    assert "pp" in mesh.shape, (
        f"{who} needs a 'pp' mesh axis; got {mesh.axis_names}")
    S = mesh.shape["pp"]
    assert cfg.num_layers % S == 0, (
        f"{cfg.num_layers} layers not divisible by {S} pipeline stages")
    return specs[0]


def pipeline_lm_apply(
    cfg: TransformerConfig,
    params: dict,
    tokens,
    mesh: Mesh,
    num_microbatches: int = 2,
    attention_fn: Optional[Callable] = None,
):
    """Full LM forward with the block stack pipelined over `pp`.

    `params` is the ordinary Transformer param dict (un-stacked);
    embedding + positions + final norm + head run replicated outside
    the pipelined region. Returns logits [B, T, V].
    """
    stacked, embed_params, head_params, block_apply, positions, pos_mb = (
        _lm_pipeline_pieces(cfg, mesh, "pipeline_lm_apply", params,
                            attention_fn, tokens, num_microbatches))

    h = Embedding(cfg).apply({"params": embed_params}, tokens, positions)

    pipelined = shard_map(
        functools.partial(
            gpipe, block_apply, num_microbatches=num_microbatches
        ),
        mesh=mesh,
        in_specs=(P("pp"), P(), P()),
        out_specs=P(),
        axis_names=frozenset({"pp"}),
        check_vma=False,
    )
    h = pipelined(stacked, h, pos_mb)
    return LmHead(cfg).apply({"params": head_params}, h)


def one_f_one_b(
    block_apply: Callable,
    loss_head_fn: Callable,
    stacked_params,
    xs,
    labels,
    head_params,
    *extra,
    axis: str = "pp",
    num_microbatches: int = 2,
):
    """1F1B pipeline TRAIN schedule — call INSIDE shard_map over `axis`.

    GPipe (above) runs all M forwards, then autodiff replays all M
    backwards — every stage holds O(M) live microbatch state. 1F1B
    interleaves: stage `s` starts microbatch b's backward as soon as
    its gradient arrives, bounding in-flight microbatches at `S - s`
    (so O(S) ≤ O(M) activation memory, the reason 1F1B exists —
    PipeDream/Megatron's steady-state schedule). Because JAX autodiff
    cannot interleave forward and backward of one traced function, this
    IS the train step: forward, loss, and manual VJP backward run in a
    single slot-clocked scan, and the function returns gradients.

    Slot algebra (stage s, microbatch m, S stages, 2(M+S-1) slots):
      forward  of m at slot  s + 2m
      backward of m at slot  2S - 1 - s + 2m
    Forwards sit on parity s, backwards on the opposite parity, so a
    stage runs at most one op per slot, gradient for microbatch b
    arrives from stage s+1 exactly one slot before stage s's backward
    of b, and in-flight residuals never exceed S — the ring buffer of
    stage INPUTS (size S) is the only stored activation state.
    Backward recomputes the stage forward under `jax.vjp` (per-stage
    remat: memory O(S·mb) regardless of M, compute the same as a
    rematerialized GPipe step).

    `block_apply(p_block, h, *extra) -> h` applies one layer (no
    collectives over `axis` inside). `loss_head_fn(head_params, y_mb,
    labels_mb) -> (loss_SUM, n_valid)` runs the head + loss on the LAST
    stage's output; it must return the un-normalized sum plus the valid
    count (NOT a per-microbatch mean — with ignore_index padding the
    valid count varies per microbatch, and averaging M means would
    silently diverge from the serial sum/total); its parameter gradient
    is returned so tied heads work. Returns `(loss_sum, n_valid_total,
    d_stacked_local, d_head, d_xs)`: every gradient is of the loss
    SUM — divide by `n_valid_total` for the serial model's mean-loss
    gradients.
    """
    S = lax.psum(1, axis)
    idx = lax.axis_index(axis)
    M = num_microbatches
    B = xs.shape[0]
    assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
    mb = B // M
    x_mb = xs.reshape((M, mb) + xs.shape[1:])
    l_mb = labels.reshape((M, mb) + labels.shape[1:])

    def stage(p_stack, u):
        def body(carry, p):
            return block_apply(p, carry, *extra), None

        out, _ = lax.scan(body, u, p_stack)
        return out

    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    bwd_perm = [(i + 1, i) for i in range(S - 1)]
    zero_dp = jax.tree_util.tree_map(jnp.zeros_like, stacked_params)
    zero_dhp = jax.tree_util.tree_map(jnp.zeros_like, head_params)
    act_shape = (mb,) + xs.shape[1:]

    def slot(carry, t):
        tf = t - idx
        is_f = (tf >= 0) & (tf % 2 == 0) & (tf // 2 < M)
        f = jnp.clip(tf // 2, 0, M - 1)
        tb = t - (2 * S - 1 - idx)
        is_b = (tb >= 0) & (tb % 2 == 0) & (tb // 2 < M)
        b = jnp.clip(tb // 2, 0, M - 1)

        def fwd_op(op):
            (in_buf, fwd_recv, bwd_recv, dp_acc, dhp_acc, dh_buf,
             loss_acc, cnt_acc) = op
            u = jnp.where(idx == 0, x_mb[f], fwd_recv)
            in_buf = lax.dynamic_update_index_in_dim(
                in_buf, u, f % S, axis=0)
            # the last stage's forward output is consumed by nobody
            # (its backward recomputes inside the fused vjp) — skip the
            # stage compute there instead of feeding a dead ppermute
            y = lax.cond(
                idx == S - 1,
                lambda u: jnp.zeros(act_shape, xs.dtype),
                lambda u: stage(stacked_params, u),
                u)
            return (in_buf, fwd_recv, bwd_recv, dp_acc, dhp_acc,
                    dh_buf, loss_acc, cnt_acc,
                    y, jnp.zeros(act_shape, xs.dtype))

        def bwd_op(op):
            (in_buf, fwd_recv, bwd_recv, dp_acc, dhp_acc, dh_buf,
             loss_acc, cnt_acc) = op
            u = lax.dynamic_index_in_dim(
                in_buf, b % S, axis=0, keepdims=False)

            def last_stage(_):
                def fused(p, hp, u):
                    s, n = loss_head_fn(hp, stage(p, u), l_mb[b])
                    return s, n

                lb, vjp, nb = jax.vjp(
                    fused, stacked_params, head_params, u,
                    has_aux=True)
                dp, dhp, du = vjp(jnp.float32(1.0))
                return dp, dhp, du, lb, nb.astype(jnp.float32)

            def mid_stage(_):
                _, vjp = jax.vjp(stage, stacked_params, u)
                dp, du = vjp(bwd_recv.astype(xs.dtype))
                return (dp, zero_dhp, du, jnp.float32(0.0),
                        jnp.float32(0.0))

            dp_c, dhp_c, du, lb, nb = lax.cond(
                idx == S - 1, last_stage, mid_stage, None)
            dh_buf = jnp.where(
                idx == 0,
                lax.dynamic_update_index_in_dim(
                    dh_buf, du.astype(dh_buf.dtype), b, axis=0),
                dh_buf)
            dp_acc = jax.tree_util.tree_map(jnp.add, dp_acc, dp_c)
            dhp_acc = jax.tree_util.tree_map(jnp.add, dhp_acc, dhp_c)
            return (in_buf, fwd_recv, bwd_recv, dp_acc, dhp_acc,
                    dh_buf, loss_acc + lb, cnt_acc + nb,
                    jnp.zeros(act_shape, xs.dtype), du)

        def idle_op(op):
            return op + (jnp.zeros(act_shape, xs.dtype),
                         jnp.zeros(act_shape, xs.dtype))

        (in_buf, _, _, dp_acc, dhp_acc, dh_buf, loss_acc, cnt_acc,
         y_send, du_send) = lax.cond(
            is_f, fwd_op,
            lambda op: lax.cond(is_b, bwd_op, idle_op, op),
            carry)

        # collectives OUTSIDE the conds: every stage permutes every slot
        fwd_recv = lax.ppermute(y_send, axis, fwd_perm)
        bwd_recv = lax.ppermute(du_send, axis, bwd_perm)
        return (in_buf, fwd_recv, bwd_recv, dp_acc, dhp_acc, dh_buf,
                loss_acc, cnt_acc), None

    carry0 = (
        jnp.zeros((S,) + act_shape, xs.dtype),        # input ring
        jnp.zeros(act_shape, xs.dtype),               # fwd_recv
        jnp.zeros(act_shape, xs.dtype),               # bwd_recv
        zero_dp, zero_dhp,
        jnp.zeros((M,) + act_shape, jnp.float32),     # d_xs (stage 0)
        jnp.float32(0.0),                             # loss sum
        jnp.float32(0.0),                             # valid count
    )
    (_, _, _, dp_acc, dhp_acc, dh_buf, loss_acc, cnt_acc), _ = lax.scan(
        slot, carry0, jnp.arange(2 * (M + S - 1)))

    # only the last stage computed losses / head grads; only stage 0
    # holds d_xs — psum replicates each to every stage
    loss = lax.psum(loss_acc, axis)
    count = lax.psum(cnt_acc, axis)
    d_head = jax.tree_util.tree_map(
        lambda g: lax.psum(g, axis), dhp_acc)
    d_xs = lax.psum(dh_buf, axis).reshape((B,) + xs.shape[1:])
    return loss, count, dp_acc, d_head, d_xs


def pipeline_lm_train_step_1f1b(
    cfg: TransformerConfig,
    params: dict,
    tokens,
    mesh: Mesh,
    num_microbatches: int = 2,
    attention_fn: Optional[Callable] = None,
):
    """Full causal-LM train step with the 1F1B schedule: returns
    `(mean_loss, grads)` where `grads` matches the ordinary Transformer
    param dict. Embedding runs (replicated) outside the pipelined
    region with its backward driven by the schedule's `d_xs`; the head
    + loss run inside the last stage so backward starts the moment a
    microbatch's forward completes. Loss/grads normalize by the TOTAL
    valid-token count (not per-microbatch means), so ignore_index
    padding distributed unevenly across microbatches still reproduces
    the serial model exactly."""
    from ..models.transformer import causal_lm_loss

    M = num_microbatches
    stacked, embed_params, head_params, block_apply, positions, pos_mb = (
        _lm_pipeline_pieces(cfg, mesh, "pipeline_lm_train_step_1f1b",
                            params, attention_fn, tokens, M))

    def loss_head_fn(hp, y_mb, toks_mb):
        logits = LmHead(cfg).apply({"params": hp}, y_mb)
        mean, n = causal_lm_loss(logits, toks_mb)
        # UNCLAMPED valid count for the summed denominator:
        # causal_lm_loss clamps n to >= 1 (safe for its own mean), but a
        # fully-padded microbatch must contribute 0 — not a phantom 1 —
        # to the cross-microbatch count, or loss/grads diverge from the
        # serial model. mean * n is still the exact nll sum (0 when no
        # token is valid).
        n_raw = jnp.sum(toks_mb[:, 1:] != -1).astype(jnp.float32)
        return mean * n, n_raw  # (sum, count) — see one_f_one_b's contract

    def embed_fwd(ep):
        return Embedding(cfg).apply({"params": ep}, tokens, positions)

    h, embed_vjp = jax.vjp(embed_fwd, embed_params)

    pipelined = shard_map(
        functools.partial(
            one_f_one_b, block_apply, loss_head_fn,
            axis="pp", num_microbatches=M),
        mesh=mesh,
        in_specs=(P("pp"), P(), P(), P(), P()),
        out_specs=(P(), P(), P("pp"), P(), P()),
        axis_names=frozenset({"pp"}),
        check_vma=False,
    )
    loss_sum, count, d_stacked, d_head, d_xs = pipelined(
        stacked, h, tokens, head_params, pos_mb)
    (d_embed,) = embed_vjp(d_xs.astype(h.dtype))

    count = jnp.maximum(count, 1.0)
    grads = unstack_block_params(
        jax.tree_util.tree_map(lambda g: g / count, d_stacked), {})
    for src in (d_embed, d_head):
        for k, v in src.items():
            g = jax.tree_util.tree_map(lambda x: x / count, v)
            grads[k] = (jax.tree_util.tree_map(jnp.add, grads[k], g)
                        if k in grads else g)
    return loss_sum / count, grads
