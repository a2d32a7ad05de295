"""MNIST data-parallel training — the framework's hello-world.

Mirrors the reference smoke config (BASELINE.json:
examples/pytorch/pytorch_mnist.py — hvd.init, DistributedOptimizer,
broadcast of initial state, rank-0-only checkpointing/logging), built
TPU-first: one jitted shard_map step over the `hvd` mesh axis, batch
sharded along dim 0, gradients averaged by the optimizer transform.

Data is synthetic "MNIST-like" digits rendered procedurally (this repo
builds with zero egress — no dataset download), deterministic per rank.

Run:
    python examples/mnist.py --epochs 2
    hvdrun -np 2 -H localhost:2 python examples/mnist.py
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

import horovod_tpu as hvd
from horovod_tpu.utils import compile_cache


class ConvNet(nn.Module):
    """The reference example's small convnet shape (two conv + two dense)."""

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(32, (3, 3))(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = nn.Conv(64, (3, 3))(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(128)(x)
        x = nn.relu(x)
        return nn.Dense(10)(x)


def synthetic_mnist(n: int, seed: int):
    """Procedural digit-ish images: each class is a fixed random template
    plus noise, so the task is learnable and accuracy is meaningful."""
    rng = np.random.RandomState(1234)  # shared templates
    templates = rng.rand(10, 28, 28, 1).astype(np.float32)
    r = np.random.RandomState(seed)
    labels = r.randint(0, 10, n)
    images = templates[labels] + 0.3 * r.rand(n, 28, 28, 1).astype(np.float32)
    return images, labels


def main(argv=None):
    p = argparse.ArgumentParser(description="horovod_tpu MNIST example")
    p.add_argument("--batch-size", type=int, default=64,
                   help="per-rank batch size")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--momentum", type=float, default=0.5)
    p.add_argument("--train-size", type=int, default=2048)
    p.add_argument("--test-size", type=int, default=512)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--save", default="", help="rank-0 checkpoint path")
    args = p.parse_args(argv)

    compile_cache.enable()
    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()

    model = ConvNet()
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng, jnp.zeros((1, 28, 28, 1)))["params"]
    # scale LR by world size, broadcast initial state from rank 0 — the
    # canonical recipe (reference pytorch_mnist.py)
    opt = hvd.DistributedOptimizer(
        optax.sgd(args.lr * n, momentum=args.momentum)
    )
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt_state = hvd.broadcast_parameters(opt_state, root_rank=0)

    def loss_fn(p, xb, yb):
        logits = model.apply({"params": p}, xb)
        onehot = jax.nn.one_hot(yb, 10)
        loss = -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1))
        acc = jnp.mean((jnp.argmax(logits, -1) == yb).astype(jnp.float32))
        return loss, acc

    def step_fn(p, s, xb, yb):
        (loss, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(p, xb, yb)
        upd, s = opt.update(g, s, p)
        p = optax.apply_updates(p, upd)
        metrics = jax.lax.psum(jnp.stack([loss, acc]), "hvd") / n
        return p, s, metrics

    step = jax.jit(
        shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), P(), P("hvd"), P("hvd")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )

    # each SPMD rank sees its own shard; build the global batch host-side
    images, labels = synthetic_mnist(args.train_size * n, seed=args.seed)
    test_x, test_y = synthetic_mnist(args.test_size, seed=args.seed + 1)
    shard = NamedSharding(mesh, P("hvd"))
    steps_per_epoch = args.train_size // args.batch_size

    eval_fn = jax.jit(lambda p, xb, yb: loss_fn(p, xb, yb))

    for epoch in range(args.epochs):
        t0 = time.time()
        perm = np.random.RandomState(epoch).permutation(len(images))
        metrics = jnp.zeros((2,))
        for i in range(steps_per_epoch):
            sel = perm[i * args.batch_size * n:(i + 1) * args.batch_size * n]
            xb = jax.device_put(images[sel], shard)
            yb = jax.device_put(labels[sel], shard)
            params, opt_state, metrics = step(params, opt_state, xb, yb)
        test_loss, test_acc = eval_fn(
            params, jnp.asarray(test_x), jnp.asarray(test_y)
        )
        if hvd.rank() == 0:
            tr_loss, tr_acc = np.asarray(metrics)
            print(
                f"epoch {epoch}: train_loss={tr_loss:.4f} "
                f"train_acc={tr_acc:.3f} test_loss={float(test_loss):.4f} "
                f"test_acc={float(test_acc):.3f} "
                f"({time.time() - t0:.1f}s)",
                flush=True,
            )

    if args.save and hvd.rank() == 0:
        # rank-0-only checkpointing, as the reference examples do
        np.save(args.save, jax.device_get(params), allow_pickle=True)
        print(f"saved checkpoint to {args.save}", flush=True)
    return float(test_acc)


if __name__ == "__main__":
    main()
