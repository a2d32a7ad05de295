#!/usr/bin/env python
"""Capture an xplane device trace of one synthetic-benchmark model step.

Drives the same vehicle as bench.py (examples/resnet50_synthetic.py /
bert_pretraining.py would be equivalent) but wraps the timed window in
``jax.profiler.trace`` so the XLA op-level schedule on the real chip can
be inspected. Pair with scripts/xplane_summary.py to get the per-op-
category time breakdown that MFU work starts from.

Usage:
    python scripts/profile_cnn.py --model resnet50 --batch-size 256 \
        --logdir /tmp/xplane_resnet
"""

import argparse
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


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", choices=sorted(_MODELS), default="resnet50")
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--logdir", default="/tmp/xplane_cnn")
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--s2d-stem", action="store_true")
    args = p.parse_args(argv)

    hvd.init()
    mesh = hvd.mesh()
    n = hvd.size()

    model_cls, size = _MODELS[args.model]
    if args.s2d_stem and not args.model.startswith("resnet"):
        raise SystemExit("--s2d-stem applies to the resnet family")
    kw = {"stem": "space_to_depth"} if args.s2d_stem else {}
    model = model_cls(num_classes=1000, dtype=jnp.bfloat16, **kw)
    rng = jax.random.PRNGKey(0)
    xb = np.random.rand(args.batch_size * n, size, size, 3).astype(np.float32)
    yb = np.random.randint(0, 1000, args.batch_size * n)

    variables = jax.jit(model.init)(
        rng, jnp.zeros((1, size, size, 3), dtype=jnp.bfloat16))
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    has_bn = "batch_stats" in variables
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    opt_state = opt.init(params)

    def loss_fn(p_, bs, x, y):
        if has_bn:
            logits, new_state = model.apply(
                {"params": p_, "batch_stats": bs}, x, train=True,
                mutable=["batch_stats"])
            bs = new_state["batch_stats"]
        else:
            logits = model.apply({"params": p_}, x, train=True)
        onehot = jax.nn.one_hot(y, 1000)
        loss = -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1))
        return loss, bs

    def step_fn(p_, bs, s, x, y):
        (loss, bs), g = jax.value_and_grad(loss_fn, has_aux=True)(
            p_, bs, x, y)
        upd, s = opt.update(g, s, p_)
        p_ = optax.apply_updates(p_, upd)
        return p_, bs, s, jax.lax.psum(loss, "hvd").reshape(1) / n

    step = jax.jit(
        shard_map(step_fn, mesh=mesh,
                      in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
                      out_specs=(P(), P(), P(), P()),
                      check_vma=False),
        donate_argnums=(0, 1, 2))

    shard = NamedSharding(mesh, P("hvd"))
    xs = jax.device_put(xb.astype(jnp.bfloat16), shard)
    ys = jax.device_put(yb, shard)

    for _ in range(4):  # warmup: compile + autotune settle
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, xs, ys)
    float(loss[0])

    t0 = time.perf_counter()
    with jax.profiler.trace(args.logdir):
        for _ in range(args.steps):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, xs, ys)
        float(loss[0])
    dt = time.perf_counter() - t0
    print(f"traced {args.steps} steps in {dt:.3f}s "
          f"({args.batch_size * n * args.steps / dt:.1f} img/s) "
          f"-> {args.logdir}", flush=True)


if __name__ == "__main__":
    main()
