"""Synthetic CNN benchmark (images/sec + MFU) — ResNet-50 by default.

Mirrors the reference vehicle
(examples/pytorch/pytorch_synthetic_benchmark.py: torchvision model by
--model, synthetic ImageNet batches, images/sec over timed windows,
optional fp16 wire), in the TPU-first shape: bf16 model, one jitted
shard_map train step, XLA collectives over the mesh, optional bf16 wire
compression in the optimizer transform. --model covers the reference's
headline scaling trio (docs/benchmarks.rst:8-13): resnet50/101/152,
inception3 (299px) and vgg16.

Run:
    python examples/resnet50_synthetic.py --num-iters 5
    python examples/resnet50_synthetic.py --model vgg16
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
from horovod_tpu.models import (
    InceptionV3, ResNet50, ResNet101, ResNet152, VGG16,
)
from horovod_tpu.utils import compile_cache
from horovod_tpu.utils.mfu import cnn_train_flops, format_mfu, mfu_or_none

_MODELS = {
    "resnet50": (ResNet50, 224),
    "resnet101": (ResNet101, 224),
    "resnet152": (ResNet152, 224),
    "inception3": (InceptionV3, 299),
    "vgg16": (VGG16, 224),
}


def main(argv=None, stats=None):
    p = argparse.ArgumentParser(
        description="horovod_tpu synthetic CNN benchmark "
                    "(--model resnet50/101/152, inception3, vgg16)"
    )
    p.add_argument("--model", choices=sorted(_MODELS), default="resnet50",
                   help="reference tf_cnn_benchmarks model name")
    p.add_argument("--batch-size", type=int, default=128,
                   help="per-rank batch size")
    p.add_argument("--image-size", type=int, default=0,
                   help="0 = the model's native resolution")
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--num-warmup-batches", type=int, default=3)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=4)
    p.add_argument("--s2d-stem", action="store_true",
                   help="space-to-depth stem (2x2 unshuffle + 4x4/s1 "
                        "conv; the TPU MLPerf transform of the 7x7/s2 "
                        "3-channel stem). resnet family only")
    p.add_argument("--bf16-allreduce", action="store_true",
                   help="bfloat16 wire compression for gradients "
                        "(the reference's --fp16-allreduce)")
    args = p.parse_args(argv)

    compile_cache.enable()
    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()

    model_cls, native_size = _MODELS[args.model]
    if not args.image_size:
        args.image_size = native_size
    model_kw = {}
    if args.s2d_stem:
        if not args.model.startswith("resnet"):
            raise SystemExit("--s2d-stem applies to the resnet family")
        model_kw["stem"] = "space_to_depth"
    model = model_cls(num_classes=args.num_classes, dtype=jnp.bfloat16,
                      **model_kw)
    rng = jax.random.PRNGKey(0)
    local = np.random.RandomState(hvd.rank() if hvd.cross_size() > 1 else 0)
    xb = local.rand(
        args.batch_size * n, args.image_size, args.image_size, 3
    ).astype(np.float32)
    yb = local.randint(0, args.num_classes, args.batch_size * n)

    variables = jax.jit(model.init)(
        rng, jnp.zeros((1, args.image_size, args.image_size, 3),
                       dtype=jnp.bfloat16)
    )
    # VGG has no BatchNorm: keep the step signature uniform with an
    # empty stats pytree
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    has_bn = "batch_stats" in variables
    compression = (
        hvd.Compression.bf16 if args.bf16_allreduce else hvd.Compression.none
    )
    opt = hvd.DistributedOptimizer(
        optax.sgd(0.1, momentum=0.9), compression=compression
    )
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)

    def loss_fn(p, bs, x, y):
        if has_bn:
            logits, new_state = model.apply(
                {"params": p, "batch_stats": bs}, x.astype(jnp.bfloat16),
                train=True, mutable=["batch_stats"],
            )
            bs = new_state["batch_stats"]
        else:
            logits = model.apply(
                {"params": p}, x.astype(jnp.bfloat16), train=True
            )
        onehot = jax.nn.one_hot(y, args.num_classes)
        loss = -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1))
        return loss, bs

    def step_fn(p, bs, s, x, y):
        (loss, bs), g = jax.value_and_grad(loss_fn, has_aux=True)(
            p, bs, x, y
        )
        upd, s = opt.update(g, s, p)
        p = optax.apply_updates(p, upd)
        return p, bs, s, jax.lax.psum(loss, "hvd").reshape(1) / n

    step = jax.jit(
        shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1, 2),
    )

    shard = NamedSharding(mesh, P("hvd"))
    # store the image batch in the model's compute dtype: half the HBM
    # footprint and read traffic for the largest input buffer (the
    # in-step astype becomes a no-op)
    xs = jax.device_put(xb.astype(jnp.bfloat16), shard)
    ys = jax.device_put(yb, shard)

    # AOT-compile and call the executable directly. Inception's
    # conv+BN mega-fusions are VMEM-pressure-sensitive: in round 4
    # xla_tpu_scoped_vmem_limit_kib=65536 was +3.7% at batch 256 and
    # 2.9x at batch 192 (the r4 cliff was two mis-tiled 35x35x64
    # fusions at 119ms/step each, docs/benchmarks.md); ResNet measured
    # WORSE with it, so the bump is per-model.
    lowered = step.lower(params, batch_stats, opt_state, xs, ys)
    if jax.default_backend() == "tpu" and args.model == "inception3":
        step = lowered.compile(
            compiler_options={"xla_tpu_scoped_vmem_limit_kib": "65536"})
    else:
        step = lowered.compile()

    if hvd.rank() == 0:
        print(f"model: {args.model}, batch {args.batch_size} x {n} ranks, "
              f"image {args.image_size}px", flush=True)
    for _ in range(args.num_warmup_batches):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, xs, ys
        )
    if args.num_warmup_batches:
        float(loss[0])  # host sync

    rates = []
    for it in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, xs, ys
            )
        float(loss[0])  # host sync closes the timing window
        dt = time.perf_counter() - t0
        rate = args.batch_size * n * args.num_batches_per_iter / dt
        rates.append(rate)
        if hvd.rank() == 0:
            print(f"iter {it}: {rate:.1f} img/sec total", flush=True)

    total = float(np.median(rates))
    per_chip = total / max(n, 1)  # n = total chips in the world
    if stats is not None:  # per-iter spread for bench.py's JSON
        stats["rates_per_chip"] = [r / max(n, 1) for r in rates]
    mfu = mfu_or_none(
        cnn_train_flops(args.model, per_chip, args.image_size))
    if hvd.rank() == 0:
        print(
            f"total img/sec on {n} rank(s): {total:.1f} "
            f"({per_chip:.1f}/chip, {format_mfu(mfu)})",
            flush=True,
        )
    return per_chip, mfu


if __name__ == "__main__":
    main()
