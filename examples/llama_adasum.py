"""Llama-2 fine-tuning with Adasum gradient combining.

The BASELINE.json config "Adasum allreduce on Llama-2 7B
(reducescatter+allgather path)" on the actual Llama-2 architecture
(models/transformer.py LLAMA2_7B: RMSNorm, RoPE, SwiGLU, untied head —
a different model path than the GPT-2 adasum smoke). Depth/width scale
via flags: the full 7B does not fit one chip's HBM with Adam state, so
single-chip runs use a reduced config; at pod scale the same step runs
under parallel/train.py's tp/fsdp sharding with the identical Adasum
optimizer transform (hierarchical_adasum rides reduce-scatter →
serial adasum → allgather across DCN, ops/hierarchical.py:82).

Adasum needs no LR rescaling by world size (reference
docs/adasum_user_guide.rst) — the LR here is NOT multiplied by size.

Run:
    python examples/llama_adasum.py --steps 20          # reduced Llama
    python examples/llama_adasum.py --layers 2 --hidden 256  # smoke
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
    LLAMA2_7B,
    Transformer,
    causal_lm_loss,
)
from horovod_tpu.utils.mfu import count_params


def main(argv=None):
    p = argparse.ArgumentParser(description="Llama-2 + Adasum")
    p.add_argument("--batch-size", type=int, default=2,
                   help="per-rank batch size")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--layers", type=int, default=4,
                   help="depth (LLAMA2_7B has 32; 4 fits one chip)")
    p.add_argument("--hidden", type=int, default=1024,
                   help="width (LLAMA2_7B has 4096)")
    p.add_argument("--vocab", type=int, default=2048,
                   help="vocab (LLAMA2_7B has 32000)")
    p.add_argument("--remat", action="store_true",
                   help="per-block rematerialization; every block here, "
                        "the model builds the logits itself")
    p.add_argument("--flash", action="store_true",
                   help="Pallas flash-attention kernels (fwd + bwd)")
    p.add_argument("--bf16-allreduce", action="store_true",
                   help="bfloat16 wire compression for the adasum path")
    args = p.parse_args(argv)

    compile_cache.enable()
    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()

    cfg = dataclasses.replace(
        LLAMA2_7B,
        num_layers=args.layers,
        hidden_size=args.hidden,
        num_heads=max(1, args.hidden // 128),
        num_kv_heads=None,
        mlp_ratio=LLAMA2_7B.mlp_ratio,
        vocab_size=args.vocab,
        max_seq_len=args.seq_len,
        remat=args.remat,
    )
    attention_fn = None
    if args.flash:
        from horovod_tpu.ops.pallas_attention import make_flash_attention_fn
        attention_fn = make_flash_attention_fn(causal=True)
    model = Transformer(cfg, attention_fn=attention_fn)

    B, T = args.batch_size * n, args.seq_len
    # learnable synthetic language (fixed random bigram table)
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
    compression = (
        hvd.Compression.bf16 if args.bf16_allreduce else hvd.Compression.none
    )
    # Adasum: NO lr scaling by world size
    opt = hvd.DistributedOptimizer(
        optax.adam(args.lr), op=hvd.Adasum, compression=compression
    )
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)

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
        ),
        donate_argnums=(0, 1),
    )

    if hvd.rank() == 0:
        print(
            f"Llama {cfg.num_layers}L/{cfg.hidden_size}H "
            f"({count_params(params) / 1e6:.0f}M params), batch "
            f"{args.batch_size} x {n} ranks, seq {T}, adasum",
            flush=True,
        )
    tok = jax.device_put(toks, NamedSharding(mesh, P("hvd")))
    first = None
    # first step compiles; time the rest
    params, opt_state, loss = step(params, opt_state, tok)
    first = float(loss[0])
    t0 = time.time()
    for i in range(1, args.steps):
        params, opt_state, loss = step(params, opt_state, tok)
        lv = float(loss[0])
        if hvd.rank() == 0 and (i % 10 == 0 or i == args.steps - 1):
            print(f"step {i}: loss {lv:.4f}", flush=True)
    dt = time.time() - t0
    tput = B * T * (args.steps - 1) / dt if args.steps > 1 else 0.0
    if hvd.rank() == 0:
        print(
            f"loss {first:.4f} -> {lv:.4f} in {args.steps} steps; "
            f"{tput:.0f} tokens/sec total over {n} rank(s)",
            flush=True,
        )
    return first, lv


if __name__ == "__main__":
    main()
