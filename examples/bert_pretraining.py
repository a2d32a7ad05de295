"""BERT-Large masked-LM pretraining benchmark (tokens/sec/chip + MFU).

The BASELINE.json config "BERT-Large pretraining (PyTorch
DistributedOptimizer + grad tensor-fusion)" in TPU-first form: bf16
BERT-L (models/transformer.py BERT_LARGE), synthetic token batches,
DistributedOptimizer whose gradient fusion packs buckets into single XLA
collectives (ops/fusion.py — the compile-time mirror of the reference's
fusion buffer, controller.cc:830).

Run:
    python examples/bert_pretraining.py --num-iters 3
    python examples/bert_pretraining.py --layers 2 --hidden 256  # smoke
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
from horovod_tpu.models.transformer import BERT_LARGE, Bert, mlm_loss
from horovod_tpu.utils import compile_cache
from horovod_tpu.utils.mfu import (
    count_params,
    format_mfu,
    mfu_or_none,
    transformer_train_flops,
)


def main(argv=None, stats=None):
    p = argparse.ArgumentParser(
        description="horovod_tpu BERT-Large pretraining benchmark"
    )
    p.add_argument("--batch-size", type=int, default=8,
                   help="per-rank batch size")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--mask-frac", type=float, default=0.15)
    p.add_argument("--num-warmup-batches", type=int, default=2)
    p.add_argument("--num-batches-per-iter", type=int, default=5)
    p.add_argument("--num-iters", type=int, default=3)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--layers", type=int, default=0,
                   help="override depth (0 = BERT-Large's 24)")
    p.add_argument("--hidden", type=int, default=0,
                   help="override width (0 = BERT-Large's 1024)")
    p.add_argument("--remat", action="store_true",
                   help="per-block rematerialization (HBM-bound configs); "
                        "with --fused-ce the last block keeps its kernel "
                        "and matmul results, its backward runs first")
    p.add_argument("--flash", action="store_true",
                   help="Pallas flash-attention kernels (fwd + bwd) in "
                        "place of XLA dot-product attention")
    p.add_argument("--fused-ce", action="store_true",
                   help="vocab-blocked fused LM-head cross-entropy "
                        "(logits never materialize in HBM)")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO-1 sharded optimizer states "
                        "(hvd.ShardedOptimizer): Adam m/v split 1/N "
                        "across ranks")
    p.add_argument("--autotune-spmd", action="store_true",
                   help="SPMDStepTuner sweep (bucket size + overlap "
                        "chain) before the timed run; winners are "
                        "pinned into the knobs the final compile reads")
    args = p.parse_args(argv)

    compile_cache.enable()
    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()

    cfg = BERT_LARGE
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    if args.hidden:
        heads = max(1, args.hidden // 64)
        cfg = dataclasses.replace(
            cfg, hidden_size=args.hidden, num_heads=heads
        )
    cfg = dataclasses.replace(
        cfg, max_seq_len=args.seq_len, remat=args.remat)
    attention_fn = None
    if args.flash:
        from horovod_tpu.ops.pallas_attention import make_flash_attention_fn
        attention_fn = make_flash_attention_fn(causal=False)
    model = Bert(cfg, attention_fn=attention_fn)

    rng = np.random.RandomState(hvd.rank() if hvd.cross_size() > 1 else 0)
    B, T = args.batch_size * n, args.seq_len
    tokens = rng.randint(0, cfg.vocab_size, (B, T))
    labels = rng.randint(0, cfg.vocab_size, (B, T))
    mask = rng.rand(B, T) < args.mask_frac

    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, T), dtype=jnp.int32)
    )["params"]
    n_params = count_params(params)
    if args.zero:
        # ZeRO-1: Adam m/v sharded 1/N per rank (optim/zero.py)
        opt = hvd.ShardedOptimizer(optax.adamw(args.lr))
    else:
        opt = hvd.DistributedOptimizer(optax.adamw(args.lr))
    opt_state = opt.init(params)
    state_specs = (hvd.sharded_state_specs(opt_state)
                   if args.zero else P())
    params = hvd.broadcast_parameters(params, root_rank=0)

    if args.fused_ce:
        from horovod_tpu.ops.fused_cross_entropy import (
            fused_linear_cross_entropy,
        )

        def loss_fn(p, tok, lab, msk):
            hidden = model.apply({"params": p}, tok, return_hidden=True)
            w = p["tok_emb"]["embedding"].T  # tied head
            loss, _ = fused_linear_cross_entropy(hidden, w, lab,
                                                 valid=msk)
            return loss
    else:
        def loss_fn(p, tok, lab, msk):
            logits = model.apply({"params": p}, tok)
            loss, _ = mlm_loss(logits, lab, msk)
            return loss

    def step_fn(p, s, tok, lab, msk):
        loss, g = jax.value_and_grad(loss_fn)(p, tok, lab, msk)
        upd, s = opt.update(g, s, p)
        p = optax.apply_updates(p, upd)
        return p, s, jax.lax.psum(loss, "hvd").reshape(1) / n

    step = jax.jit(
        shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), state_specs, P("hvd"), P("hvd"), P("hvd")),
            out_specs=(P(), state_specs, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )

    shard = NamedSharding(mesh, P("hvd"))
    tok = jax.device_put(tokens, shard)
    lab = jax.device_put(labels, shard)
    msk = jax.device_put(mask, shard)

    if args.autotune_spmd:
        # each candidate is a fresh trace (no donation — the tuner
        # re-runs one candidate's step many times on the same buffers);
        # the winning knobs persist for the donating AOT compile below
        def build_step(overrides):
            js = jax.jit(shard_map(
                step_fn, mesh=mesh,
                in_specs=(P(), state_specs, P("hvd"), P("hvd"),
                          P("hvd")),
                out_specs=(P(), state_specs, P()), check_vma=False))
            return js.lower(params, opt_state, tok, lab, msk).compile()

        winners = hvd.SPMDStepTuner(
            thresholds=[16 << 20, 64 << 20, 128 << 20, 256 << 20],
            warmup=1, measure=4,
        ).tune(build_step, params, opt_state, tok, lab, msk)
        if hvd.rank() == 0:
            print(f"autotune-spmd pinned: {winners}", flush=True)

    # AOT-compile and call the executable directly. The scoped-VMEM
    # bump measured a repeatable ~+1% for the transformer fusion shapes
    # in round 4 (3x paired runs; ResNet prefers the default, see
    # scripts/xla_options_sweep.py) — TPU-only option.
    lowered = step.lower(params, opt_state, tok, lab, msk)
    if jax.default_backend() == "tpu":
        step = lowered.compile(
            compiler_options={"xla_tpu_scoped_vmem_limit_kib": "65536"})
    else:
        step = lowered.compile()

    if hvd.rank() == 0:
        print(
            f"BERT {cfg.num_layers}L/{cfg.hidden_size}H "
            f"({n_params / 1e6:.0f}M params), batch {args.batch_size} x "
            f"{n} ranks, seq {T}",
            flush=True,
        )
    for _ in range(args.num_warmup_batches):
        params, opt_state, loss = step(params, opt_state, tok, lab, msk)
    if args.num_warmup_batches:
        float(loss[0])  # host sync

    rates = []
    for it in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params, opt_state, loss = step(params, opt_state, tok, lab, msk)
        float(loss[0])  # host sync closes the timing window
        dt = time.perf_counter() - t0
        rate = B * T * args.num_batches_per_iter / dt
        rates.append(rate)
        if hvd.rank() == 0:
            print(f"iter {it}: {rate:.0f} tokens/sec total "
                  f"(loss {float(loss[0]):.3f})", flush=True)

    total = float(np.median(rates))
    per_chip = total / max(n, 1)  # n = total chips in the world
    mfu = mfu_or_none(transformer_train_flops(n_params, per_chip))
    if hvd.rank() == 0:
        print(
            f"tokens/sec on {n} rank(s): {total:.0f} "
            f"({per_chip:.0f}/chip, {format_mfu(mfu)})",
            flush=True,
        )
    if stats is not None:  # per-iter spread for bench.py's JSON
        stats["rates_per_chip"] = [r / max(n, 1) for r in rates]
    return per_chip, mfu


if __name__ == "__main__":
    main()
