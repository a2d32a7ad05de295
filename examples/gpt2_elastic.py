"""Elastic GPT-2 training — survive hosts joining and leaving.

The BASELINE.json config "Elastic Horovod GPT-2 with dynamic TPU-slice
resize" (reference examples/elastic/pytorch/
pytorch_synthetic_benchmark_elastic.py:1): training state lives in a
`hvd.elastic.TpuState`, the loop is wrapped in `@hvd.elastic.run`, and
`state.commit()` snapshots at batch boundaries so a world change replays
at most one commit interval. On resize the wrapper restores committed
state, re-initializes the mesh, and re-syncs from rank 0.

Run (static):
    python examples/gpt2_elastic.py --steps 50
Run (elastic):
    hvdrun -np 2 --min-np 1 --max-np 4 \
        --host-discovery-script ./discover.sh \
        python examples/gpt2_elastic.py
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
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

import horovod_tpu as hvd
from horovod_tpu.utils import compile_cache
from horovod_tpu.models.transformer import (
    GPT2_SMALL,
    Transformer,
    causal_lm_loss,
)


def build_step(model, opt, n, mesh):
    def loss_fn(p, tok):
        logits = model.apply({"params": p}, tok)
        loss, _ = causal_lm_loss(logits, tok)
        return loss

    def step_fn(p, s, tok):
        loss, g = jax.value_and_grad(loss_fn)(p, tok)
        upd, s = opt.update(g, s, p)
        p = optax.apply_updates(p, upd)
        return p, s, jax.lax.psum(loss, "hvd").reshape(1) / n

    return jax.jit(
        shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), P(), P("hvd")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )


def main(argv=None):
    p = argparse.ArgumentParser(description="elastic GPT-2 example")
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--commit-every", type=int, default=10)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--flash", action="store_true",
                   help="Pallas flash-attention kernels (fwd + bwd; "
                        "causal tile-skipping, ~2x attention at T>=1k)")
    args = p.parse_args(argv)

    compile_cache.enable()
    hvd.init()

    cfg = dataclasses.replace(
        GPT2_SMALL,
        num_layers=args.layers,
        hidden_size=args.hidden,
        num_heads=max(1, args.hidden // 64),
        vocab_size=args.vocab,
        max_seq_len=args.seq_len,
    )
    attention_fn = None
    if args.flash:
        from horovod_tpu.ops.pallas_attention import make_flash_attention_fn
        attention_fn = make_flash_attention_fn(causal=True)
    model = Transformer(cfg, attention_fn=attention_fn)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, args.seq_len), dtype=jnp.int32)
    )["params"]
    # optax.adam's state layout doesn't depend on the LR, so init with the
    # current world's optimizer; train() rebuilds it per world size
    opt_state = hvd.DistributedOptimizer(
        optax.adam(args.lr * hvd.size())
    ).init(params)

    state = hvd.elastic.TpuState(
        params=params, opt_state=opt_state, step=0, last_loss=float("nan")
    )

    @hvd.elastic.run
    def train(state):
        # (re)build for the CURRENT world — size, mesh, and the LR scale
        # all change across resizes
        n = hvd.size()
        mesh = hvd.mesh()
        opt = hvd.DistributedOptimizer(optax.adam(args.lr * n))
        step = build_step(model, opt, n, mesh)
        r = np.random.RandomState(0)
        toks = r.randint(
            0, args.vocab, (args.batch_size * n, args.seq_len)
        )
        tok = jax.device_put(toks, NamedSharding(mesh, P("hvd")))
        loss = None
        while state.step < args.steps:
            state.params, state.opt_state, loss = step(
                state.params, state.opt_state, tok
            )
            state.step += 1
            if state.step % args.commit_every == 0:
                # host-sync only at commit boundaries: per-step float()
                # would serialize the async dispatch pipeline
                state.last_loss = float(loss[0])
                # snapshot + surface pending host updates (the elastic
                # heartbeat; reference common/elastic.py:60)
                state.commit()
                if hvd.rank() == 0:
                    print(
                        f"step {state.step}: loss {state.last_loss:.4f} "
                        f"(world {n})",
                        flush=True,
                    )
        if loss is not None:
            state.last_loss = float(loss[0])
        # state, not a local: a re-entry after the final commit's interrupt
        # skips the loop entirely
        return state.last_loss

    t0 = time.time()
    final = train(state)
    if hvd.rank() == 0:
        print(
            f"done: {args.steps} steps, final loss {final:.4f} "
            f"({time.time() - t0:.1f}s)",
            flush=True,
        )
    return final


if __name__ == "__main__":
    main()
