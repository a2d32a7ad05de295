"""Model save/load with DistributedOptimizer rehydration.

Reference: /root/reference/horovod/keras/__init__.py:181 (`load_model`)
and horovod/_keras/__init__.py — a saved Keras model's optimizer is
deserialized from the file and transparently re-wrapped in
`DistributedOptimizer`, so slot state (momenta, Adam moments) carries
into retraining.

TPU-native form: JAX models are pytrees, optimizers are optax
transformations. `save_model` writes an orbax checkpoint of
{params, opt_state} plus a JSON spec of the inner optimizer (name +
kwargs) and the DistributedOptimizer wrapper config; `load_model`
rebuilds the optax optimizer from the spec, re-wraps it in
`DistributedOptimizer` with the same wrapper config, and restores the
optimizer state into the rebuilt transform's own structure — the exact
analog of the reference's wrap_optimizer deserialization hook.

Rank discipline matches the reference's idiom: call `save_model` on
rank 0 only; call `load_model` on every rank (each reads the same
checkpoint; parameters are already identical so no broadcast is needed,
but `hvd.broadcast_parameters` after load stays harmless).
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, NamedTuple, Optional

from .optim.compression import Compression
from .optim.distributed import DistributedOptimizer
from .utils import faults, retry

_SPEC_FILE = "horovod_tpu_model.json"
_TREE_DIR = "tree"


def _ckpt_io(point: str, fn: Callable, *args, **kwargs):
    """Checkpoint I/O under the shared retry policy: a transiently
    failing filesystem (GCS 5xx surfacing as OSError, NFS hiccup) backs
    off and retries instead of losing the checkpoint; the
    ``checkpoint.save`` / ``checkpoint.restore`` fault points exercise
    exactly this path (tests/test_faults.py)."""
    def _do():
        faults.inject(point)
        return fn(*args, **kwargs)

    return retry.default_policy().call(_do, point=point)

_COMPRESSION_NAMES = {
    Compression.none: "none",
    Compression.fp16: "fp16",
    Compression.bf16: "bf16",
    Compression.int8: "int8",
    Compression.int8_raw: "int8-raw",
}
_COMPRESSION_BY_NAME = {v: k for k, v in _COMPRESSION_NAMES.items()}


class LoadedModel(NamedTuple):
    """What retraining needs: parameters, a ready DistributedOptimizer,
    its restored state, and user metadata."""

    params: Any
    optimizer: Any           # optax transform wrapped in DistributedOptimizer
    opt_state: Any           # restored slot state (None if none was saved)
    metadata: Dict[str, Any]


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer()


def save_model(
    path: str,
    params: Any,
    opt_state: Any = None,
    optimizer_spec: Optional[tuple] = None,
    compression=Compression.none,
    backward_passes_per_step: int = 1,
    op=None,
    gradient_predivide_factor: float = 1.0,
    metadata: Optional[Dict[str, Any]] = None,
) -> None:
    """Save params (+ optimizer slot state and its rebuild spec).

    `optimizer_spec` is `(name, kwargs)` naming an `optax` factory, e.g.
    ``("adam", {"learning_rate": 1e-3})`` — the serializable identity of
    the optimizer, playing the role of Keras's optimizer config in the
    reference's save file (keras/__init__.py:181 relies on it to rebuild
    and re-wrap). Custom factories save by name and load via
    `load_model(custom_optimizers={name: factory})`.
    """
    from .ops.collectives import ReduceOp

    path = os.path.abspath(path)
    os.makedirs(path, exist_ok=True)
    if compression not in _COMPRESSION_NAMES:
        # a silently-dropped custom compressor would change wire
        # numerics on reload with no error
        raise ValueError(
            "save_model can only serialize the built-in Compression "
            "variants (none/fp16/bf16/int8/int8-raw); re-wrap custom "
            "compressors yourself after load_model"
        )
    if op is None:
        op = ReduceOp.AVERAGE  # DistributedOptimizer's default
    spec: Dict[str, Any] = {
        "format": 1,
        "has_opt_state": opt_state is not None,
        "metadata": metadata or {},
        "wrapper": {
            "compression": _COMPRESSION_NAMES[compression],
            "backward_passes_per_step": int(backward_passes_per_step),
            "op": int(op),
            "gradient_predivide_factor": float(gradient_predivide_factor),
        },
    }
    if optimizer_spec is not None:
        name, kwargs = optimizer_spec
        spec["optimizer"] = {"name": str(name), "kwargs": dict(kwargs)}
    with open(os.path.join(path, _SPEC_FILE), "w") as f:
        json.dump(spec, f, indent=2, sort_keys=True)
    tree = {"params": params}
    if opt_state is not None:
        tree["opt_state"] = opt_state
    ckptr = _checkpointer()
    tree_path = os.path.join(path, _TREE_DIR)

    def _save():
        ckptr.save(tree_path, tree, force=True)
        ckptr.wait_until_finished()

    _ckpt_io("checkpoint.save", _save)


def orbax_rung(path: str, attrs: Optional[Dict[str, str]] = None):
    """Build the orbax rung of the layered recovery ladder
    (elastic/replication.py): a callable ``fn(state) -> bool`` that
    restores a saved checkpoint's trees into elastic-state attributes
    when the fresher rungs (peer replica, emergency snapshot) fall
    through.

    ``attrs`` maps state attribute name → checkpoint tree key (default
    ``{"params": "params", "opt_state": "opt_state"}``, matching
    :func:`save_model`); attributes the checkpoint does not carry are
    left untouched. Attach it before ``hvd.elastic.run``::

        state = hvd.elastic.TpuState(params=params, opt_state=opt_state)
        state.orbax_restore = hvd.checkpoint.orbax_rung("/ckpt/latest")
    """
    mapping = dict(attrs) if attrs else {
        "params": "params", "opt_state": "opt_state",
    }

    def _restore(state) -> bool:
        import jax
        import numpy as np

        ckptr = _checkpointer()
        raw = _ckpt_io(
            "checkpoint.restore", ckptr.restore,
            os.path.join(os.path.abspath(path), _TREE_DIR),
        )
        restored = False
        for attr, key in mapping.items():
            if key not in raw or attr not in state._known:
                continue
            host = jax.tree_util.tree_map(
                lambda x: np.asarray(x), raw[key])
            setattr(state, attr, host)
            restored = True
        return restored

    return _restore


_FSDP_SPEC_FILE = "horovod_tpu_fsdp.json"


def save_fsdp(path: str, rows, layout, opt_state: Any = None,
              metadata: Optional[Dict[str, Any]] = None) -> None:
    """Save FSDP-sharded parameter rows (+ the sharded optimizer
    state) WITHOUT materializing a full replica on any host: the row
    dict's leaves are jax.Arrays sharded one row per device over the
    data axis (optim/fsdp.py), and orbax writes each host's addressable
    shards directly — the save is keyed by the shard spec, never
    gathered (docs/recovery.md documents the on-disk layout).

    ``layout`` is the FsdpLayout the rows were sharded with; its
    world/bucket geometry is serialized to ``horovod_tpu_fsdp.json`` so
    :func:`load_fsdp` can rebuild the restore template (and refuse a
    mismatched world loudly instead of de-padding garbage). Call on
    every host (orbax coordinates the multi-host write); restore with
    ``load_fsdp`` on every host.
    """
    path = os.path.abspath(path)
    os.makedirs(path, exist_ok=True)
    import numpy as np

    spec: Dict[str, Any] = {
        "format": 1,
        "kind": "fsdp_rows",
        "world": int(layout.world),
        "has_opt_state": opt_state is not None,
        "buckets": [
            {
                "index": i,
                "len": int(L),
                "k": int(k),
                "dtype": np.dtype(d).name,
            }
            for i, (L, k, d) in enumerate(
                zip(layout.lens, layout.ks, layout.dtypes))
        ],
        "metadata": metadata or {},
    }
    with open(os.path.join(path, _FSDP_SPEC_FILE), "w") as f:
        json.dump(spec, f, indent=2, sort_keys=True)
    tree: Dict[str, Any] = {"params_rows": dict(rows)}
    if opt_state is not None:
        tree["opt_state"] = opt_state
    ckptr = _checkpointer()
    tree_path = os.path.join(path, _TREE_DIR)

    def _save():
        ckptr.save(tree_path, tree, force=True)
        ckptr.wait_until_finished()

    _ckpt_io("checkpoint.save", _save)


def load_fsdp(path: str, mesh, axis_name: Optional[str] = None,
              abstract_state: Any = None):
    """Restore FSDP-sharded parameter rows saved by :func:`save_fsdp`,
    placed DIRECTLY into their `P(ax)` shardings — each host reads only
    the shards it owns, so no full replica ever exists in host or
    device memory (the property the FSDP scale story rests on).

    ``abstract_state`` (e.g. ``jax.eval_shape(optimizer.init,
    abs_params)``) supplies the optimizer-state restore template when
    the checkpoint carries one; its `(world, k)` leaves restore sharded
    one row per device, everything else replicated. Returns
    ``(rows, opt_state, metadata)`` — ``opt_state`` is None when the
    save carried none or no template was given.
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .optim.fsdp import bucket_name

    path = os.path.abspath(path)
    with open(os.path.join(path, _FSDP_SPEC_FILE)) as f:
        spec = json.load(f)
    axes = [a for a, s in zip(mesh.axis_names, mesh.devices.shape)
            if s > 1] if axis_name is None else [axis_name]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ax = axes[0] if axes else mesh.axis_names[0]
    world = int(spec["world"])
    if sizes.get(ax, 1) != world:
        raise ValueError(
            f"checkpoint at {path} was sharded for world {world} but "
            f"mesh axis {ax!r} has size {sizes.get(ax, 1)} — restore "
            "on the matching mesh, or restore there and re-slice with "
            "hvd.fsdp.reshard_rows (docs/recovery.md)")
    row_sh = NamedSharding(mesh, P(ax))
    rep = NamedSharding(mesh, P())
    rows_tmpl = {
        bucket_name(b["index"]): jax.ShapeDtypeStruct(
            (world, b["k"]), np.dtype(b["dtype"]), sharding=row_sh)
        for b in spec["buckets"]
    }
    template: Dict[str, Any] = {"params_rows": rows_tmpl}
    has_state = bool(spec.get("has_opt_state"))
    ckptr = _checkpointer()
    tree_path = os.path.join(path, _TREE_DIR)

    def leaf_tmpl(l):
        shape = tuple(np.shape(l))
        sh = row_sh if (len(shape) == 2 and shape[0] == world) else rep
        return jax.ShapeDtypeStruct(
            shape, np.dtype(getattr(l, "dtype", np.float32)),
            sharding=sh)

    if has_state:
        if abstract_state is None:
            # no structure template: shapes from checkpoint metadata
            # (no array bytes), restored in orbax's own tree shape —
            # pass abstract_state for the optimizer's exact structure
            meta = ckptr.metadata(tree_path)
            meta_tree = (meta.item_metadata.tree
                         if hasattr(meta, "item_metadata") else meta)
            abstract_state = meta_tree["opt_state"]
        template["opt_state"] = jax.tree_util.tree_map(
            leaf_tmpl, abstract_state)
    restored = _ckpt_io(
        "checkpoint.restore", ckptr.restore, tree_path, template,
    )
    return (
        restored["params_rows"],
        restored.get("opt_state") if has_state else None,
        dict(spec.get("metadata", {})),
    )


def load_params(path: str):
    """Params-only restore: (params, metadata) as host arrays, no
    optimizer rebuild. The inference-side counterpart of load_model —
    transformers (spark estimator models) need weights, not momenta."""
    import jax

    path = os.path.abspath(path)
    with open(os.path.join(path, _SPEC_FILE)) as f:
        spec = json.load(f)
    ckptr = _checkpointer()
    raw = _ckpt_io(
        "checkpoint.restore", ckptr.restore, os.path.join(path, _TREE_DIR)
    )
    import numpy as np

    params = jax.tree_util.tree_map(lambda x: np.asarray(x), raw["params"])
    return params, dict(spec.get("metadata", {}))


def load_model(
    path: str,
    custom_optimizers: Optional[Dict[str, Callable]] = None,
    compression=None,
    **distributed_kwargs,
) -> LoadedModel:
    """Load a saved model and re-wrap its optimizer in
    DistributedOptimizer (reference keras/__init__.py:181).

    The inner optimizer is rebuilt from the saved spec — `optax.<name>`
    by default, or `custom_optimizers[name]` (the reference's
    `custom_optimizers` hook). The wrapper config (compression,
    backward_passes_per_step, predivide) is restored from the save
    unless overridden here; the restored `opt_state` drops into the
    rebuilt transform, so momenta/moments continue across the reload.
    """
    import optax

    path = os.path.abspath(path)
    with open(os.path.join(path, _SPEC_FILE)) as f:
        spec = json.load(f)

    from .ops.collectives import ReduceOp

    wrapper = dict(spec.get("wrapper", {}))
    if compression is None:
        compression = _COMPRESSION_BY_NAME.get(
            wrapper.get("compression", "none"), Compression.none
        )
    wrapper_kwargs = {
        "backward_passes_per_step": int(
            wrapper.get("backward_passes_per_step", 1)
        ),
        "op": ReduceOp(int(wrapper.get("op", int(ReduceOp.AVERAGE)))),
        "gradient_predivide_factor": float(
            wrapper.get("gradient_predivide_factor", 1.0)
        ),
    }
    wrapper_kwargs.update(distributed_kwargs)

    opt_spec = spec.get("optimizer")
    if opt_spec is None:
        raise ValueError(
            f"checkpoint at {path} was saved without an optimizer_spec; "
            "pass one to save_model to enable optimizer rehydration"
        )
    name, kwargs = opt_spec["name"], opt_spec.get("kwargs", {})
    if custom_optimizers and name in custom_optimizers:
        inner = custom_optimizers[name](**kwargs)
    elif hasattr(optax, name):
        inner = getattr(optax, name)(**kwargs)
    else:
        raise ValueError(
            f"unknown optimizer '{name}'; pass custom_optimizers="
            f"{{'{name}': factory}} (reference load_model "
            "custom_optimizers, keras/__init__.py:181)"
        )
    optimizer = DistributedOptimizer(
        inner, compression=compression, **wrapper_kwargs
    )

    # Restore against the rebuilt transform's own structure: orbax needs
    # a target template, and init(params) IS the authoritative shape of
    # this optimizer's state for these parameters.
    import jax

    ckptr = _checkpointer()
    tree_path = os.path.join(path, _TREE_DIR)
    # restored leaves come back as host arrays (numpy) so the training
    # step's jit places everything uniformly — orbax's own device
    # placement of a template-restored tree can mix shardings
    import numpy as np

    def _to_host(tree):
        return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)

    # ONE data read: parameter shapes come from checkpoint metadata (no
    # array bytes), and the rebuilt optimizer's own init supplies the
    # authoritative opt_state structure for the restore template
    meta_tree = ckptr.metadata(tree_path).item_metadata.tree
    params_tmpl = jax.tree_util.tree_map(
        lambda m: jax.ShapeDtypeStruct(tuple(m.shape), m.dtype),
        meta_tree["params"],
    )
    template = {"params": params_tmpl}
    if spec.get("has_opt_state"):
        template["opt_state"] = jax.eval_shape(optimizer.init, params_tmpl)
    restored = _ckpt_io(
        "checkpoint.restore", ckptr.restore, tree_path, template
    )
    params = _to_host(restored["params"])
    opt_state = (
        _to_host(restored["opt_state"])
        if spec.get("has_opt_state") else None
    )
    return LoadedModel(
        params=params,
        optimizer=optimizer,
        opt_state=opt_state,
        metadata=dict(spec.get("metadata", {})),
    )
