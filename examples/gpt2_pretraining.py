"""GPT-2 causal-LM pretraining benchmark (tokens/sec/chip + MFU).

The causal half of the transformer benchmark pair (BERT-L is
examples/bert_pretraining.py): bf16 GPT-2-medium (355M) on synthetic
token batches, DistributedOptimizer gradient fusion, optional pallas
flash attention (causal diagonal tile-skipping) and vocab-blocked fused
LM-head cross-entropy. Reference vehicle: the synthetic-data benchmark
the reference publishes numbers from
(/root/reference/examples/pytorch/pytorch_synthetic_benchmark.py:1),
pointed at a causal LM.

Run:
    python examples/gpt2_pretraining.py --num-iters 3 --flash --fused-ce
    python examples/gpt2_pretraining.py --layers 2 --hidden 256  # smoke
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
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.transformer import (
    GPT2_MEDIUM,
    Transformer,
    causal_lm_loss,
)
from horovod_tpu.utils import compile_cache
from horovod_tpu.utils.mfu import (
    count_params,
    format_mfu,
    mfu_or_none,
    transformer_train_flops,
)


def main(argv=None, stats=None):
    p = argparse.ArgumentParser(
        description="horovod_tpu GPT-2 causal pretraining benchmark"
    )
    p.add_argument("--batch-size", type=int, default=16,
                   help="per-rank batch size")
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--num-warmup-batches", type=int, default=2)
    p.add_argument("--num-batches-per-iter", type=int, default=5)
    p.add_argument("--num-iters", type=int, default=3)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--layers", type=int, default=0,
                   help="override depth (0 = GPT-2-medium's 24)")
    p.add_argument("--hidden", type=int, default=0,
                   help="override width (0 = GPT-2-medium's 1024)")
    p.add_argument("--remat", action="store_true",
                   help="per-block rematerialization (HBM-bound configs); "
                        "with --fused-ce the last block keeps its kernel "
                        "and matmul results, its backward runs first")
    p.add_argument("--flash", action="store_true",
                   help="Pallas causal flash-attention kernels (fwd+bwd)")
    p.add_argument("--fused-ce", action="store_true",
                   help="vocab-blocked fused LM-head cross-entropy")
    args = p.parse_args(argv)

    compile_cache.enable()
    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()

    cfg = GPT2_MEDIUM
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    if args.hidden:
        heads = max(1, args.hidden // 64)
        cfg = dataclasses.replace(
            cfg, hidden_size=args.hidden, num_heads=heads
        )
    cfg = dataclasses.replace(
        cfg, max_seq_len=args.seq_len, remat=args.remat,
    )
    attention_fn = None
    if args.flash:
        from horovod_tpu.ops.pallas_attention import make_flash_attention_fn
        attention_fn = make_flash_attention_fn(causal=True)
    model = Transformer(cfg, attention_fn=attention_fn)

    rng = np.random.RandomState(hvd.rank() if hvd.cross_size() > 1 else 0)
    B, T = args.batch_size * n, args.seq_len
    tokens = rng.randint(0, cfg.vocab_size, (B, T))

    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, T), dtype=jnp.int32)
    )["params"]
    n_params = count_params(params)
    opt = hvd.DistributedOptimizer(optax.adamw(args.lr))
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)

    if args.fused_ce:
        from horovod_tpu.ops.fused_cross_entropy import (
            fused_causal_lm_loss,
        )

        def loss_fn(p, tok):
            hidden = model.apply({"params": p}, tok, return_hidden=True)
            loss, _ = fused_causal_lm_loss(
                hidden, p["tok_emb"]["embedding"].T, tok)
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
        ),
        donate_argnums=(0, 1),
    )

    tok = jax.device_put(tokens, NamedSharding(mesh, P("hvd")))

    # AOT-compile and call the executable directly (the scoped-VMEM
    # bump is the round-4 transformer setting, see bert_pretraining.py)
    t0 = time.perf_counter()
    lowered = step.lower(params, opt_state, tok)
    if jax.default_backend() == "tpu":
        step = lowered.compile(
            compiler_options={"xla_tpu_scoped_vmem_limit_kib": "65536"})
    else:
        step = lowered.compile()
    compile_seconds = time.perf_counter() - t0

    if hvd.rank() == 0:
        print(
            f"GPT-2 {cfg.num_layers}L/{cfg.hidden_size}H "
            f"({n_params / 1e6:.0f}M params), batch {args.batch_size} x "
            f"{n} ranks, seq {T}",
            flush=True,
        )
    for _ in range(args.num_warmup_batches):
        params, opt_state, loss = step(params, opt_state, tok)
    if args.num_warmup_batches:
        float(loss[0])  # host sync

    rates, losses = [], []
    for it in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params, opt_state, loss = step(params, opt_state, tok)
        float(loss[0])  # host sync closes the timing window
        dt = time.perf_counter() - t0
        rate = B * T * args.num_batches_per_iter / dt
        rates.append(rate)
        losses.append(float(loss[0]))
        if hvd.rank() == 0:
            print(f"iter {it}: {rate:.0f} tokens/sec total "
                  f"(loss {losses[-1]:.3f})", flush=True)

    total = float(np.median(rates))
    per_chip = total / max(n, 1)
    mfu = mfu_or_none(transformer_train_flops(n_params, per_chip))
    if hvd.rank() == 0:
        print(
            f"tokens/sec on {n} rank(s): {total:.0f} "
            f"({per_chip:.0f}/chip, {format_mfu(mfu)})",
            flush=True,
        )
    if stats is not None:
        stats["rates_per_chip"] = [r / max(n, 1) for r in rates]
        # what chip_smoke.py inspects: the executable that ran, where
        # its operands live (shardings only — bench.py keeps this dict
        # while the next vehicle needs the HBM), the last loss as every
        # device holds it, and the loss after every timed iteration
        stats.update(
            compiled=step, compile_seconds=compile_seconds,
            batch_sharding=tok.sharding, batch_shape=tok.shape,
            param_shardings=jax.tree_util.tree_map(
                lambda x: x.sharding, params),
            loss=loss, losses=losses,
        )
    return per_chip, mfu


if __name__ == "__main__":
    main()
