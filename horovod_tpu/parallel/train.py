"""Assembled distributed training steps (pjit auto-partitioning + manual
sequence-parallel attention).

This is the jit-mode answer to the reference's runtime pipeline
(SURVEY.md §3.2): where Horovod negotiates readiness and fuses tensors in
a background thread per step, the TPU path compiles the *entire* training
step once — shardings from parallel/sharding.py tell XLA's SPMD
partitioner where tensors live, and it inserts/fuses the collectives
(gradient psums ride the dp/fsdp axes; tp collectives stay inside layers;
sp attention is manual ring/Ulysses via nested shard_map).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.transformer import Transformer, TransformerConfig, causal_lm_loss
from . import sharding as sharding_lib
from .mesh import data_axes, make_mesh
from .ring_attention import ring_attention
from .ulysses import ulysses_attention


def sp_attention_fn(mesh: Mesh, kind: str = "ring", causal: bool = True):
    """Attention fn running manually over the 'sp' axis, nested inside an
    otherwise auto-partitioned jit (shard_map axis_names={'sp'})."""

    def inner(q, k, v):
        if kind == "ring":
            return ring_attention(q, k, v, axis_name="sp", causal=causal)
        return ulysses_attention(q, k, v, axis_name="sp", causal=causal)

    spec = P(None, "sp", None, None)
    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=frozenset({"sp"}),
        check_vma=False,
    )


def make_lm_train_step(
    cfg: TransformerConfig,
    optimizer,
    mesh: Mesh,
    rules: Optional[Sequence] = None,
    sequence_parallel: Optional[str] = None,  # None | "ring" | "ulysses"
    donate: bool = True,
):
    """Build (init_fn, step_fn, batch_sharding) for causal-LM training.

    step_fn(params, opt_state, tokens) -> (params, opt_state, loss) is
    jitted with parameter shardings from the rules; tokens are sharded
    [batch over dp/fsdp, seq over sp].
    """
    rules = sharding_lib.TRANSFORMER_RULES if rules is None else rules
    attention_fn = (
        sp_attention_fn(mesh, sequence_parallel, cfg.causal)
        if sequence_parallel
        else None
    )
    model = Transformer(cfg, attention_fn=attention_fn)

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axes = data_axes(mesh)
    batch_spec_entries: list = [batch_axes if batch_axes else None]
    if sizes.get("sp", 1) > 1:
        batch_spec_entries.append("sp")
    batch_spec = P(*batch_spec_entries)
    batch_sharding = NamedSharding(mesh, batch_spec)

    def init_fn(rng, sample_tokens):
        # Shape-infer first, then jit-init directly into the target
        # shardings: parameters materialize sharded, never resident on one
        # device (required for >HBM models like Llama-7B).
        abs_params = jax.eval_shape(
            lambda r, s: model.init(r, s)["params"], rng, sample_tokens
        )
        shardings = sharding_lib.make_param_shardings(abs_params, mesh, rules)
        abs_opt = jax.eval_shape(optimizer.init, abs_params)
        opt_shardings = _opt_state_shardings(
            abs_opt, abs_params, shardings, mesh
        )

        @functools.partial(
            jax.jit, out_shardings=(shardings, opt_shardings)
        )
        def _init(r, s):
            params = model.init(r, s)["params"]
            return params, optimizer.init(params)

        return _init(rng, sample_tokens)

    def loss_fn(params, tokens):
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        logits = model.apply({"params": params}, tokens, positions)
        loss, _ = causal_lm_loss(logits, tokens)
        return loss

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        import optax

        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    donate_argnums = (0, 1) if donate else ()
    fsdp_fns = _maybe_fsdp_step_fn(
        cfg, model, optimizer, mesh, batch_spec, sequence_parallel,
        donate)
    if fsdp_fns is not None:
        fsdp_init_fn, fsdp_step_fn = fsdp_fns
        return fsdp_init_fn, fsdp_step_fn, batch_sharding
    staged_fn = _maybe_staged_step_fn(
        model, optimizer, mesh, batch_spec, sequence_parallel, donate)
    if staged_fn is not None:
        return init_fn, staged_fn, batch_sharding
    step_fn = jax.jit(step, donate_argnums=donate_argnums)
    return init_fn, step_fn, batch_sharding


def tune_lm_train_step(
    cfg: TransformerConfig,
    optimizer_factory: Callable[[], Any],
    mesh: Mesh,
    rng,
    sample_tokens,
    tuner=None,
    rules: Optional[Sequence] = None,
    sequence_parallel: Optional[str] = None,
    donate: bool = True,
    **tuner_kwargs,
):
    """Closed-loop autotune of the causal-LM train step
    (ops/autotune.OnlineTuner, docs/autotune.md): coordinate-descend the
    data-plane knobs by rebuilding the REAL step through
    :func:`make_lm_train_step` per candidate — the factory route is what
    lets compile-time knobs (overlap schedule, FSDP prefetch depth, wire
    dtype) actually take effect, since a traced step bakes its
    collective structure in. Returns ``(init_fn, step_fn,
    batch_sharding, config)`` where the first three are a fresh
    :func:`make_lm_train_step` build under the pinned winners and
    ``config`` is the pinned configuration.

    ``optimizer_factory`` is called once per candidate (and once for the
    final build): an optimizer's state tree can depend on the knobs
    being tuned (an error-feedback wire adds residual state), so the
    optimizer must be REBUILT, not reused, per candidate.

    The model fingerprint for the warm-start cache comes from the
    shape-inferred parameter pytree, so a run against a cached
    (model, topology) key pins the stored winners and performs zero
    tuning compiles."""
    from ..ops import autotune as autotune_mod
    from ..ops.fusion import model_fingerprint

    model = Transformer(cfg)
    abs_params = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.ones((1, cfg.max_seq_len), jnp.int32))["params"])
    fingerprint = model_fingerprint(abs_params)
    if tuner is None:
        tuner = autotune_mod.OnlineTuner(**tuner_kwargs)

    def build_step(overrides):
        # knobs already hold `overrides`; donate=False so the candidate
        # step can run warmup+measure iterations on the same arrays
        opt = optimizer_factory()
        init_fn, step_fn, _ = make_lm_train_step(
            cfg, opt, mesh, rules=rules,
            sequence_parallel=sequence_parallel, donate=False)
        params, opt_state = init_fn(rng, sample_tokens)

        def step(tokens):
            return step_fn(params, opt_state, tokens)

        return step

    config = tuner.tune(build_step, sample_tokens,
                        fingerprint=fingerprint)
    init_fn, step_fn, batch_sharding = make_lm_train_step(
        cfg, optimizer_factory(), mesh, rules=rules,
        sequence_parallel=sequence_parallel, donate=donate)
    return init_fn, step_fn, batch_sharding, config


def _count_weighted_stages(model, want, n_world):
    """Stage builder closing over a token batch: each shard's mean loss
    weighted by its share of the global valid-token count, so AVERAGE-
    reduced gradients and the psum/n_world loss reproduce the
    monolithic step's single global mean even when ignore_index padding
    is uneven across shards (shared by the staged and FSDP step
    builders — with equal per-shard counts w == 1.0 exactly)."""
    from ..models.transformer import causal_lm_loss
    from ..ops import overlap as overlap_mod

    def stages_for(tokens):
        # clamp only the global denominator: a zero-valid shard must
        # contribute weight 0, not inflate the world count by 1
        c = jnp.sum(tokens[:, 1:] != -1).astype(jnp.float32)
        w = c * n_world / jnp.maximum(jax.lax.psum(c, want), 1.0)

        def head_loss(logits, _tk=tokens, _w=w):
            loss, _ = causal_lm_loss(logits, _tk)
            return loss * _w

        return overlap_mod.transformer_lm_stages(model, tokens,
                                                 head_loss)

    return stages_for


def _maybe_fsdp_step_fn(cfg, model, optimizer, mesh, batch_spec,
                        sequence_parallel, donate):
    """When the optimizer is a FullyShardedOptimizer
    (`ShardedOptimizer(params_sharded=True)`), build the
    fully-sharded-parameter train step (optim/fsdp.py, docs/fsdp.md):
    parameters live as per-bucket row shards over the data/fsdp mesh
    axis, the forward prefetch-gathers them bucket-by-bucket
    interleaved with compute, the backward reduce-scatters ride the
    staged path, and the update applies to the local shard. Returns
    ``(init_fn, step_fn)`` — init_fn yields the SHARDED row dict, not
    a replicated params pytree, so the whole train state is ~1/world
    per device. Anything this step cannot drive raises loudly (an
    fsdp-kind optimizer has no monolithic fallback: its update consumes
    staged shards only); non-FSDP optimizers return None and take
    today's paths bit-for-bit regardless of the HOROVOD_FSDP knob."""
    import functools

    from ..core.state import global_state
    from jax import shard_map as _shard_map
    from ..ops import collectives as _coll
    from ..ops import overlap as overlap_mod
    from ..optim import fsdp as fsdp_mod
    from ..optim.zero import sharded_state_specs

    info = getattr(getattr(optimizer, "update", None),
                   "_hvd_overlap_info", None)
    if info is None or info.get("kind") != "fsdp":
        return None
    knobs = global_state().knobs
    if not knobs.fsdp:
        raise ValueError(
            "HOROVOD_FSDP=0 but the optimizer is a "
            "FullyShardedOptimizer — its update consumes staged shards "
            "and cannot ride the monolithic paths; turn the knob on or "
            "use ShardedOptimizer/DistributedOptimizer (docs/fsdp.md)")
    if sequence_parallel is not None:
        raise ValueError(
            "the FSDP step does not compose with manual sequence "
            "parallelism yet — use ShardedOptimizer or the auto-pjit "
            "path for sp meshes (docs/fsdp.md)")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = data_axes(mesh)
    extra = [a for a, s in sizes.items()
             if s > 1 and a not in ("dp", "fsdp")]
    if extra or len(axes) != 1:
        raise ValueError(
            f"the FSDP step shards parameters over exactly one live "
            f"data axis; mesh has data axes {axes} and extra live axes "
            f"{extra} (docs/fsdp.md)")
    want = _coll._resolve_axis(info.get("axis_name"))
    if set(want) != set(axes):
        raise ValueError(
            f"FullyShardedOptimizer reduces over axes {want} but the "
            f"batch is sharded over {axes} — construct it with "
            f"axis_name={axes[0]!r}")
    ax = axes[0]
    n_world = sizes[ax]  # > 1: data_axes only returns live axes

    abs_params = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.ones((1, cfg.max_seq_len), jnp.int32))["params"])
    layout = fsdp_mod.fsdp_layout(
        abs_params, world=n_world,
        fusion_threshold_bytes=info.get("fusion_threshold_bytes"),
        bucket_backward_order=info.get("bucket_backward_order"))
    row_specs = fsdp_mod.param_row_specs(layout, info.get("axis_name"))
    row_shardings = {k: NamedSharding(mesh, s)
                     for k, s in row_specs.items()}

    def fsdp_init_fn(rng, sample_tokens):
        abs_opt = jax.eval_shape(optimizer.init, abs_params)
        state_specs = sharded_state_specs(abs_opt,
                                          info.get("axis_name"))
        state_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), state_specs,
            is_leaf=lambda x: isinstance(x, P))

        @functools.partial(
            jax.jit, out_shardings=(row_shardings, state_shardings))
        def _init(r, s):
            params = model.init(r, s)["params"]
            return (fsdp_mod.shard_params(params, layout),
                    optimizer.init(params))

        return _init(rng, sample_tokens)

    svag = overlap_mod.fsdp_staged_value_and_grad(
        _count_weighted_stages(model, want, n_world), optimizer,
        layout, prefetch=knobs.fsdp_prefetch,
        regather=knobs.fsdp_regather, offload=knobs.fsdp_offload)

    def fsdp_step(rows, opt_state, tokens):
        loss, g = svag(rows, tokens, opt_state=opt_state)
        upd, opt_state = optimizer.update(
            g, opt_state, fsdp_mod.local_shards(rows, layout))
        rows = fsdp_mod.apply_shard_updates(rows, upd, layout)
        loss = jax.lax.psum(loss, want) / n_world
        return rows, opt_state, loss.reshape(())

    cache = {}

    def step_fn(rows, opt_state, tokens):
        key = jax.tree_util.tree_structure(opt_state)
        if key not in cache:
            state_specs = sharded_state_specs(opt_state,
                                              info.get("axis_name"))
            fn = _shard_map(
                fsdp_step, mesh=mesh,
                in_specs=(row_specs, state_specs, batch_spec),
                out_specs=(row_specs, state_specs, P()),
                check_vma=False)
            cache[key] = jax.jit(
                fn, donate_argnums=(0, 1) if donate else ())
        return cache[key](rows, opt_state, tokens)

    return fsdp_init_fn, step_fn


def _maybe_staged_step_fn(model, optimizer, mesh, batch_spec,
                          sequence_parallel, donate):
    """When HOROVOD_OVERLAP_SCHEDULE is active and this step can ride
    it — an hvd optimizer (DistributedOptimizer/ShardedOptimizer), a
    pure data-parallel mesh, no sequence parallelism — build the step
    through the backward-interleaved collective scheduler
    (ops/overlap.py) inside shard_map over the data axes. Anything the
    scheduler can't drive falls back to the monolithic auto-pjit step
    unchanged (bit-for-bit today's trace), so flipping the knob is
    always safe."""
    from jax import shard_map as _shard_map
    from ..ops import collectives as _coll
    from ..ops import overlap as overlap_mod

    if sequence_parallel is not None or not overlap_mod.active():
        return None
    info = getattr(getattr(optimizer, "update", None),
                   "_hvd_overlap_info", None)
    if info is None or overlap_mod.check_supported(info) is not None:
        return None
    if info.get("kind") == "fsdp":
        # fully-sharded optimizers are routed by _maybe_fsdp_step_fn
        # (which raises rather than falling back when it can't drive
        # them); never hand one to the replicated staged step
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if any(s > 1 for a, s in sizes.items() if a != "dp"):
        # tp/sp shard activations and fsdp shards params/opt state; the
        # staged shard_map declares params replicated (in/out P()), so
        # only a pure data-parallel world can ride it
        return None
    axes = data_axes(mesh)
    if not axes:
        return None
    want = _coll._resolve_axis(info.get("axis_name"))
    if set(want) != set(axes):
        # the staged collectives must reduce over exactly the axes the
        # batch is sharded over — a partial reduction would leave
        # gradients diverging across an unreduced data axis
        return None
    n_world = 1
    for a in want:
        n_world *= sizes.get(a, 1)
    if n_world <= 1:
        return None

    svag = overlap_mod.staged_value_and_grad(
        _count_weighted_stages(model, want, n_world), opt=optimizer)

    def staged_step(params, opt_state, tokens):
        import optax

        loss, grads = svag(params, tokens, opt_state=opt_state)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # count-weighted mean of shard means == the monolithic step's
        # global mean over valid tokens (exact arithmetic; each shard's
        # loss already carries its w from stages_for)
        loss = jax.lax.psum(loss, want) / n_world
        return params, opt_state, loss.reshape(())

    cache = {}

    def step_fn(params, opt_state, tokens):
        key = jax.tree_util.tree_structure(opt_state)
        if key not in cache:
            if info["kind"] == "zero":
                from ..optim.zero import sharded_state_specs

                state_specs = sharded_state_specs(
                    opt_state, info.get("axis_name"))
            else:
                from ..optim.distributed import error_feedback_specs

                state_specs = error_feedback_specs(
                    opt_state, info.get("axis_name"))
            fn = _shard_map(
                staged_step, mesh=mesh,
                in_specs=(P(), state_specs, batch_spec),
                out_specs=(P(), state_specs, P()),
                check_vma=False)
            cache[key] = jax.jit(
                fn, donate_argnums=(0, 1) if donate else ())
        return cache[key](params, opt_state, tokens)

    return step_fn


def _opt_state_shardings(opt_state, params, param_shardings, mesh):
    """Match optimizer-state leaves that mirror params (momentum etc.) to
    the param shardings; everything else replicated."""
    # shape-based matching: leaves with a param's shape get its sharding
    shape_map = {}
    for l, s in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(param_shardings),
    ):
        shape_map.setdefault(np.shape(l), s)
    rep = NamedSharding(mesh, P())

    def leaf(x):
        return shape_map.get(np.shape(x), rep)

    return jax.tree_util.tree_map(leaf, opt_state)
