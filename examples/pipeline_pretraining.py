"""Pipeline-parallel causal-LM pretraining over a pp x dp mesh.

User-facing vehicle for `parallel/pipeline.py` — the subsystem the
reference leaves to users entirely (SURVEY §2.5: no TP/PP layer;
hand-rolled on process sets). Two schedules:

  * ``--schedule gpipe``: forward pipelined (`pipeline_lm_apply`),
    backward via jax.grad replaying the ticks in reverse;
  * ``--schedule 1f1b`` (default): the fused memory-bounded train step
    (`pipeline_lm_train_step_1f1b`) — per-microbatch backward starts as
    soon as its gradient arrives, activation state O(stages) (measured:
    PIPELINE_MEM_r05.json, docs/pipeline.md).

Runs anywhere a mesh fits: the 8-device virtual CPU world
(tests/conftest.py tier), one TPU host's chips, or a pod slice.

Run:
    python examples/pipeline_pretraining.py --pp 2 --steps 8
    python examples/pipeline_pretraining.py --schedule gpipe --pp 2
"""

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.utils import compile_cache
from horovod_tpu.models.transformer import (
    GPT2_SMALL,
    Transformer,
    causal_lm_loss,
)
from horovod_tpu.parallel.mesh import make_mesh
from horovod_tpu.parallel.pipeline import (
    pipeline_lm_apply,
    pipeline_lm_train_step_1f1b,
)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="pipeline-parallel GPT-2 pretraining")
    p.add_argument("--schedule", choices=("1f1b", "gpipe"),
                   default="1f1b")
    p.add_argument("--pp", type=int, default=2, help="pipeline stages")
    p.add_argument("--microbatches", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=8,
                   help="global batch size")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=1e-3)
    args = p.parse_args(argv)
    if args.steps < 2:
        p.error("--steps must be >= 2 (step 0 is the compile step and "
                "is excluded from the timed window)")

    compile_cache.enable()
    hvd.init()
    n = hvd.size()
    assert n % args.pp == 0, (n, args.pp)
    dp = n // args.pp
    mesh = make_mesh(pp=args.pp, dp=dp)

    heads = max(2, args.hidden // 64)
    cfg = dataclasses.replace(
        GPT2_SMALL, num_layers=args.layers, hidden_size=args.hidden,
        num_heads=heads, max_seq_len=args.seq_len, vocab_size=512,
        dtype=jnp.float32,
    )
    model = Transformer(cfg)
    B, T = args.batch_size, args.seq_len
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T)), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32))["params"]
    params = hvd.broadcast_parameters(params, root_rank=0)
    # re-commit onto the pipeline mesh: broadcast_parameters places on
    # the global "hvd" mesh, and mixing two device meshes in one jit
    # program trips XLA's partitioner (dedup_meshes sub-axis check).
    # The batch shards over dp (the pipeline shard_maps only make "pp"
    # manual, so XLA auto-partitions the dp dimension — real data
    # parallelism, not dp-replicated redundant compute).
    from jax.sharding import NamedSharding, PartitionSpec as P

    params = jax.device_put(params, NamedSharding(mesh, P()))
    toks = jax.device_put(toks, NamedSharding(mesh, P("dp")))
    opt = optax.adam(args.lr)
    state = opt.init(params)
    M = args.microbatches

    if args.schedule == "1f1b":

        @jax.jit
        def step(p, s, t):
            loss, g = pipeline_lm_train_step_1f1b(
                cfg, p, t, mesh, num_microbatches=M)
            upd, s = opt.update(g, s, p)
            return optax.apply_updates(p, upd), s, loss

    else:

        def loss_fn(p, t):
            logits = pipeline_lm_apply(
                cfg, p, t, mesh, num_microbatches=M)
            return causal_lm_loss(logits, t)[0]

        @jax.jit
        def step(p, s, t):
            loss, g = jax.value_and_grad(loss_fn)(p, t)
            upd, s = opt.update(g, s, p)
            return optax.apply_updates(p, upd), s, loss

    first = None
    t0 = None
    for i in range(args.steps):
        params, state, loss = step(params, state, toks)
        loss.block_until_ready()
        if first is None:
            first = float(loss)
            t0 = time.perf_counter()  # exclude compile from the rate
        if hvd.rank() == 0:
            print(f"step {i}: loss {float(loss):.4f}", flush=True)
    dt = max(time.perf_counter() - t0, 1e-9)
    tok_s = B * T * max(args.steps - 1, 1) / dt
    if hvd.rank() == 0:
        print(f"{args.schedule} pp={args.pp} dp={dp} M={M}: "
              f"{tok_s:,.0f} tokens/sec, loss {first:.3f} -> "
              f"{float(loss):.3f}", flush=True)
    return first, float(loss)


if __name__ == "__main__":
    main()
