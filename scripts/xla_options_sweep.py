#!/usr/bin/env python
"""Sweep TPU compiler options on the CNN benchmark step.

Per-compile ``compiler_options`` on ``lowered.compile`` reach libtpu
(PR 21 chip run: ``xla_tpu_scoped_vmem_limit_kib`` is accepted) — the
round-4 working set includes the fusion-shaping knobs
(xla_tpu_scoped_vmem_limit_kib, xla_jf_conv_input/output_fusion,
xla_tpu_rwb_fusion, ...). This script AOT-compiles a replica of the train
step bench.py measures (same model/loss/shard_map/donation; keep it in
sync with examples/resnet50_synthetic.py when that changes) under each
candidate option set and times real steps, because docs/benchmarks.md's
trace analysis says the CNN gap lives in conv+BN fusion codegen
quality — exactly what these knobs move.

Usage:
    python scripts/xla_options_sweep.py --model resnet50 --batch-size 256
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

import horovod_tpu as hvd
from horovod_tpu.models import InceptionV3, ResNet50, VGG16

_MODELS = {
    "resnet50": (ResNet50, 224),
    "inception3": (InceptionV3, 299),
    "vgg16": (VGG16, 224),
}

SWEEP = [
    ("baseline", {}),
    ("vmem32m", {"xla_tpu_scoped_vmem_limit_kib": "32768"}),
    ("vmem64m", {"xla_tpu_scoped_vmem_limit_kib": "65536"}),
    ("no_conv_input_fusion", {"xla_jf_conv_input_fusion": "false"}),
    ("no_conv_output_fusion", {"xla_jf_conv_output_fusion": "false"}),
    ("no_rwb_fusion", {"xla_tpu_rwb_fusion": "false"}),
    ("licm4", {"xla_tpu_licm_size_inflation_ratio": "4"}),
    ("fusion_cost_model",
     {"xla_tpu_enable_experimental_fusion_cost_model": "true"}),
    ("nested_loop_fusion",
     {"xla_tpu_enable_multi_level_nested_loop_fusion": "true"}),
    ("vmem64m_cost_model",
     {"xla_tpu_scoped_vmem_limit_kib": "65536",
      "xla_tpu_enable_experimental_fusion_cost_model": "true"}),
]


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", choices=sorted(_MODELS), default="resnet50")
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--s2d-stem", action="store_true")
    p.add_argument("--only", default="",
                   help="comma-separated subset of sweep names")
    args = p.parse_args(argv)

    if args.s2d_stem and not args.model.startswith("resnet"):
        raise SystemExit("--s2d-stem applies to the resnet family")
    hvd.init()
    mesh = hvd.mesh()
    n = hvd.size()
    model_cls, size = _MODELS[args.model]
    kw = {"stem": "space_to_depth"} if args.s2d_stem else {}
    model = model_cls(num_classes=1000, dtype=jnp.bfloat16, **kw)
    rng = jax.random.PRNGKey(0)
    # per-RANK batch (matching the example's semantics): the global
    # batch is batch_size * n, so per-chip workload equals bench.py's
    xb = np.random.rand(
        args.batch_size * n, size, size, 3).astype(np.float32)
    yb = np.random.randint(0, 1000, args.batch_size * n)
    variables = jax.jit(model.init)(
        rng, jnp.zeros((1, size, size, 3), jnp.bfloat16))
    params0 = variables["params"]
    bs0 = variables.get("batch_stats", {})
    has_bn = "batch_stats" in variables
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    state0 = opt.init(params0)

    def loss_fn(p, bs, x, y):
        if has_bn:
            logits, new_state = model.apply(
                {"params": p, "batch_stats": bs}, x, train=True,
                mutable=["batch_stats"])
            bs = new_state["batch_stats"]
        else:
            logits = model.apply({"params": p}, x, train=True)
        onehot = jax.nn.one_hot(y, 1000)
        loss = -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1))
        return loss, bs

    def step_fn(p, bs, s, x, y):
        (l, bs), g = jax.value_and_grad(loss_fn, has_aux=True)(
            p, bs, x, y)
        upd, s = opt.update(g, s, p)
        return optax.apply_updates(p, upd), bs, s, jax.lax.psum(
            l, "hvd").reshape(1)

    # donation matches the example exactly — the options being swept
    # trade codegen shape against live-HBM pressure, so the timed
    # program must have the benchmark's memory profile
    jitted = jax.jit(
        shard_map(step_fn, mesh=mesh,
                      in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
                      out_specs=(P(), P(), P(), P()),
                      check_vma=False),
        donate_argnums=(0, 1, 2))
    lowered = jitted.lower(
        params0, bs0, state0,
        jax.ShapeDtypeStruct(xb.shape, jnp.bfloat16),
        jax.ShapeDtypeStruct(yb.shape, jnp.int32))

    shard = NamedSharding(mesh, P("hvd"))
    xs = jax.device_put(xb.astype(jnp.bfloat16), shard)
    ys = jax.device_put(yb, shard)

    only = {s for s in args.only.split(",") if s}
    results = {}
    for name, opts in SWEEP:
        if only and name not in only:
            continue
        try:
            compiled = (lowered.compile(compiler_options=opts)
                        if opts else lowered.compile())
        except Exception as e:
            print(f"{name}: COMPILE FAILED {str(e)[:90]}", flush=True)
            continue
        # fresh copies per config: the donated originals are consumed
        params = jax.tree.map(jnp.copy, params0)
        bs = jax.tree.map(jnp.copy, bs0)
        state = jax.tree.map(jnp.copy, state0)
        for _ in range(3):
            params, bs, state, loss = compiled(params, bs, state, xs, ys)
        float(loss[0])
        t0 = time.perf_counter()
        for _ in range(args.steps):
            params, bs, state, loss = compiled(params, bs, state, xs, ys)
        float(loss[0])
        dt = time.perf_counter() - t0
        del params, bs, state
        rate = args.batch_size * n * args.steps / dt / max(n, 1)
        results[name] = round(rate, 1)
        print(f"{name}: {rate:.1f} img/s/chip", flush=True)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
