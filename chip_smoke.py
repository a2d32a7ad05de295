#!/usr/bin/env python
"""Does the training main path still start on the chip?

Drives examples/gpt2_pretraining.py's own ``main()`` — ``hvd.init`` →
``hvd.DistributedOptimizer(optax.adamw)`` → ``hvd.broadcast_parameters``
→ the AOT-compiled ``shard_map`` step — for GPT-2-medium at full width
(24 layers, hidden 1024, 16 heads, V=50,257, sequence 1024, bf16, flash
attention, fused cross entropy) on however many TPU chips
``jax.devices()`` shows, in this one process. Before that it holds the
Mosaic-compiled kernels to the repo's plain-XLA reference on a small
input. Without a TPU it fails; nothing is caught, so any failed phase
is a non-zero exit. The last line of stdout is the verdict:

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

    python chip_smoke.py

Speeds printed on the way are information, not benchmark results.
"""

import dataclasses
import json
import math
import sys

from horovod_tpu.utils import compile_cache
from horovod_tpu.utils.script_loader import load_example

# per-chip batch of bench.py's GPT-2-medium vehicle. On 4 x v5e with the
# n>1 fusion buckets allocated the step holds 3.97 GiB of arguments and
# reserves 10.77 GiB of temporaries per chip (PR 21 chip run): it fits
# the 16 GB chip, with little to spare
BATCH_PER_CHIP = 16
WARMUP_STEPS = 2
TIMED_STEPS = 6
GIB = 1 << 30


def _require(ok, what):
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def reference_check():
    """Flash attention + fused cross entropy (the Mosaic-compiled fast
    path) against plain XLA attention + dense cross entropy: loss and
    gradient of a 2-layer GPT-2-medium-width model on one small batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models.transformer import (
        GPT2_MEDIUM, Transformer, causal_lm_loss)
    from horovod_tpu.ops.fused_cross_entropy import fused_causal_lm_loss
    from horovod_tpu.ops.pallas_attention import make_flash_attention_fn

    T = 256
    cfg = dataclasses.replace(GPT2_MEDIUM, num_layers=2, max_seq_len=T)
    plain = Transformer(cfg)
    fast = Transformer(
        cfg, attention_fn=make_flash_attention_fn(causal=True))
    tok = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (2, T)))
    params = jax.jit(plain.init)(jax.random.PRNGKey(0), tok)["params"]

    def ref_loss(p):
        return causal_lm_loss(plain.apply({"params": p}, tok), tok)[0]

    def fast_loss(p):
        hidden = fast.apply({"params": p}, tok, return_hidden=True)
        return fused_causal_lm_loss(
            hidden, p["tok_emb"]["embedding"].T, tok)[0]

    l0, g0 = jax.jit(jax.value_and_grad(ref_loss))(params)
    l1, g1 = jax.jit(jax.value_and_grad(fast_loss))(params)
    l0, l1 = float(l0), float(l1)

    def norm(tree):
        return math.sqrt(sum(
            float(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)))

    g_err = norm(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        g1, g0)) / norm(g0)
    print(f"reference check: loss {l1:.4f} vs plain-XLA {l0:.4f}, "
          f"gradient relative error {g_err:.2e}", flush=True)
    # bf16 activations: the two lowerings round differently
    _require(math.isfinite(l1) and abs(l1 - l0) <= 2e-2 * abs(l0),
             f"fast-path loss {l1} disagrees with reference {l0}")
    _require(g_err <= 5e-2,
             f"fast-path gradient off the reference by {g_err:.3f}")


def check_run(stats, n):
    """The trainer's own evidence (``stats`` from the example's main)
    held to what a run on ``n`` chips must show."""
    import jax
    import numpy as np

    losses = stats["losses"]
    print("losses: " + " ".join(f"{x:.4f}" for x in losses), flush=True)
    _require(len(losses) >= TIMED_STEPS, f"only {len(losses)} steps ran")
    _require(all(math.isfinite(x) for x in losses),
             f"non-finite loss in {losses}")
    # the batch repeats, so the loss falls
    _require(losses[-1] < losses[0],
             f"loss did not fall: {losses[0]} -> {losses[-1]}")

    hlo = stats["compiled"].as_text()
    _require("tpu_custom_call" in hlo,
             "no Mosaic custom call in the compiled step: the flash "
             "kernels were interpreted or replaced")

    # the runtime reserves a program's temporaries apart from the buffer
    # allocator: peak_bytes_in_use does not contain them (PR 21 chip run)
    mem = stats["compiled"].memory_analysis()
    print(f"compiled step per device: arguments "
          f"{mem.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
          f"{mem.temp_size_in_bytes / GIB:.2f} GiB", flush=True)
    peaks = []
    for d in jax.devices():
        peak = d.memory_stats()["peak_bytes_in_use"]
        peaks.append(peak)
        print(f"peak HBM {d}: {peak / GIB:.2f} GiB in buffers",
              flush=True)
    # fp32 parameters alone are 1.4 GB on every chip
    _require(min(peaks) > GIB,
             f"a chip held no model: peak bytes per device {peaks}")

    loss_shards = stats["loss"].addressable_shards
    per_device = {s.device: float(np.asarray(s.data)[0])
                  for s in loss_shards}
    _require(len(per_device) == n,
             f"loss lives on {len(per_device)} devices, not {n}")
    _require(len(set(per_device.values())) == 1,
             f"loss differs across devices: {per_device}")

    if n > 1:
        placed = stats["batch_sharding"].devices_indices_map(
            stats["batch_shape"])
        _require(len(placed) == n and len(set(map(str, placed.values())))
                 == n, f"batch not split over {n} devices: {placed}")
        _require(
            all(s.is_fully_replicated and len(s.device_set) == n
                for s in jax.tree_util.tree_leaves(
                    stats["param_shardings"])),
            f"parameters are not present on all {n} devices")
        _require("all-reduce" in hlo,
                 "no all-reduce in the compiled multi-chip step")


def main():
    cache_dir = compile_cache.enable()  # before anything compiles
    import jax

    devices = jax.devices()
    dev, n = devices[0], len(devices)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind}, {n} devices)")
    print(f"platform {dev.platform}, device kind {dev.device_kind}, "
          f"{n} device(s), jax {jax.__version__}, compile cache "
          f"{cache_dir}", flush=True)

    reference_check()

    stats = {}
    per_chip, mfu = load_example("gpt2_pretraining").main(
        ["--batch-size", str(BATCH_PER_CHIP),
         "--num-warmup-batches", str(WARMUP_STEPS),
         "--num-iters", str(TIMED_STEPS), "--num-batches-per-iter", "1",
         "--flash", "--fused-ce"],
        stats=stats,
    )
    print(f"train step compile: {stats['compile_seconds']:.1f} s "
          f"(lower + compile; warm when the cache above held it)",
          flush=True)
    print(f"information only: {per_chip:.0f} tokens/s/chip, MFU "
          f"{mfu:.3f} on {n} x {dev.device_kind}, batch "
          f"{BATCH_PER_CHIP}/chip", flush=True)
    check_run(stats, n)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))


if __name__ == "__main__":
    main()
