"""GPT-2 training with Adasum gradient combining — convergence smoke.

The BASELINE.json config "Adasum allreduce on Llama-2 7B
(reducescatter+allgather path)" exercised at GPT-2 scale: the same
op=Adasum path (ops/adasum.py recursive-doubling combine; hierarchical
reduce-scatter → adasum → allgather variant available via
hierarchical_adasum). Adasum needs no LR rescaling by world size — that
is its point (reference docs/adasum_user_guide.rst) — so the LR here is
NOT multiplied by hvd.size().

Run:
    python examples/adasum_gpt2.py --steps 30
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


def main(argv=None):
    p = argparse.ArgumentParser(description="GPT-2 + Adasum smoke")
    p.add_argument("--batch-size", type=int, default=4,
                   help="per-rank batch size")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--flash", action="store_true",
                   help="Pallas flash-attention kernels (fwd + bwd; "
                        "causal tile-skipping, ~2x attention at T>=1k)")
    p.add_argument("--fused-ce", action="store_true",
                   help="vocab-blocked fused LM-head cross-entropy")
    args = p.parse_args(argv)

    compile_cache.enable()
    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()

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

    B, T = args.batch_size * n, args.seq_len
    # a learnable synthetic language: tokens follow a fixed random bigram
    # table, so the model has real structure to fit
    r = np.random.RandomState(0)
    table = r.randint(0, args.vocab, (args.vocab, 4))
    toks = np.zeros((B, T), dtype=np.int64)
    toks[:, 0] = r.randint(0, args.vocab, B)
    choice = r.randint(0, 4, (B, T))
    for t in range(1, T):
        toks[:, t] = table[toks[:, t - 1], choice[:, t]]

    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, T), dtype=jnp.int32)
    )["params"]
    # Adasum: NO lr scaling by world size
    opt = hvd.DistributedOptimizer(optax.adam(args.lr), op=hvd.Adasum)
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)

    if args.fused_ce:
        from horovod_tpu.ops.fused_cross_entropy import (
            fused_causal_lm_loss,
        )

        def loss_fn(p, tok):
            hidden = model.apply({"params": p}, tok, return_hidden=True)
            loss, _ = fused_causal_lm_loss(
                hidden, p["tok_emb"]["embedding"].T, tok,
                block_vocab=512,
            )
            return loss
    else:
        def loss_fn(p, tok):
            logits = model.apply({"params": p}, tok)
            loss, _ = causal_lm_loss(logits, tok)
            return loss

    def step_fn(p, s, tok):
        loss, g = jax.value_and_grad(loss_fn)(p, tok)
        upd, s = opt.update(g, s, p)
        p = optax.apply_updates(p, upd)
        return p, s, jax.lax.psum(loss, "hvd").reshape(1) / n

    step = jax.jit(
        shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), P(), P("hvd")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )

    tok = jax.device_put(toks, NamedSharding(mesh, P("hvd")))
    first = None
    t0 = time.time()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tok)
        lv = float(loss[0])
        if first is None:
            first = lv
        if hvd.rank() == 0 and (i % 10 == 0 or i == args.steps - 1):
            print(f"step {i}: loss {lv:.4f}", flush=True)
    if hvd.rank() == 0:
        print(
            f"loss {first:.4f} -> {lv:.4f} in {args.steps} steps "
            f"({time.time() - t0:.1f}s, adasum over {n} ranks)",
            flush=True,
        )
    return first, lv


if __name__ == "__main__":
    main()
