#!/usr/bin/env python
"""Headline benchmarks: ResNet-50 synthetic images/sec/chip (primary
metric, matching the reference's only published absolute throughput) plus
BERT-Large pretraining tokens/sec/chip — the two model families
BASELINE.json names — with measured MFU for both, and the reference's
scaling trio completed by Inception V3 and VGG-16 (BASELINE.md rows 1,3).

Vehicles live in examples/ (resnet50_synthetic.py, bert_pretraining.py),
mirroring the reference's examples/pytorch/pytorch_synthetic_benchmark.py
and the BERT-L pretraining config; bench.py drives them and emits ONE
JSON line.

Methodology (round 4): every headline metric reports its per-iteration
min/median/max so sub-noise "improvements" are visible as such (the
BERT band across r3 runs was ±2%); Inception carries a batch-size
sweep because its throughput cliffs away from the 256 sweet spot
(~3.3x drop at 192/320 on v5e) and a regression there would otherwise
hide. `flop_accounting` tags the MFU basis: CNNs count fwd MACs x 2
FLOPs x 3 (fwd+bwd), transformers 6·N·D (see utils/mfu.py; the MAC x 2
basis landed in r3 — earlier rounds understated CNN MFU 2x).

Baseline denominator: the reference's published ResNet-101 throughput,
1656.82 images/sec on 16 Pascal GPUs (docs/benchmarks.rst:40) = 103.55
images/sec/GPU; vs_baseline = ours / 103.55.

Config provenance (measured on v5e, round 4): ResNet batch 256 +
space-to-depth stem (256 > 128/512/1024; s2d +1.5%); BERT batch 26 +
flash attention (26 > 24/27/28/30/32 after the single-chip
fusion-bucket skip freed HBM). Steps execute through AOT-compiled
executables with >= 12-batch timing windows.

One process per chip: the eager probe is a child that needs the chip,
so it runs (and exits) before this process first touches JAX, and a
failed child fails the run.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from horovod_tpu.utils import compile_cache
from horovod_tpu.utils.script_loader import load_example

BASELINE_IMG_PER_SEC_PER_CHIP = 1656.82 / 16  # docs/benchmarks.rst:40-43


def _spread(stats):
    rates = stats.get("rates_per_chip", [])
    if not rates:
        return {}
    return {
        "min": round(min(rates), 1),
        "median": round(sorted(rates)[len(rates) // 2], 1),
        "max": round(max(rates), 1),
        "iters": len(rates),
    }


def _eager_path_block():
    """Eager data-plane vs SPMD ratio (VERDICT r5 #3), measured in a
    subprocess so the native runtime initializes cleanly and its device
    buffers die with the process. The grouped-vs-ungrouped eager A/B
    runs inside that ONE process (scripts/eager_path_bench.py measures
    per-tensor, grouped, and the RTT probe back-to-back on the same
    runtime), and both numbers land in this block as eager_step_ms /
    eager_grouped_step_ms — cross-process drift can no longer fake a
    grouping win; docs/benchmarks.md quotes whatever this artifact
    records."""
    import subprocess

    env = dict(os.environ)
    env["HVD_TPU_NATIVE"] = "1"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "scripts", "eager_path_bench.py")],
        stdout=subprocess.PIPE, text=True, timeout=900, env=env,
        check=True,
    ).stdout
    return json.loads(out[out.index("{"):])


def main():
    resnet = load_example("resnet50_synthetic")
    bert = load_example("bert_pretraining")
    gpt = load_example("gpt2_pretraining")

    # before this process touches JAX (the child needs the chip) and
    # before the big models allocate: the eager-vs-SPMD ratio probe
    eager_path = _eager_path_block()
    compile_cache.enable()

    rs, bs, gs, is_, vs = {}, {}, {}, {}, {}
    img_per_chip, resnet_mfu = resnet.main(
        ["--num-iters", "5", "--num-batches-per-iter", "16",
         "--num-warmup-batches", "3", "--batch-size", "256",
         "--s2d-stem"],
        stats=rs,
    )
    tok_per_chip, bert_mfu = bert.main(
        ["--num-iters", "4", "--num-batches-per-iter", "12",
         "--num-warmup-batches", "2", "--batch-size", "26", "--flash"],
        stats=bs,
    )
    # causal half of the transformer pair (round-5: proper vehicle +
    # config re-swept, see docs/benchmarks.md)
    gpt_per_chip, gpt_mfu = gpt.main(
        ["--num-iters", "3", "--num-batches-per-iter", "10",
         "--num-warmup-batches", "2", "--batch-size", "16", "--flash",
         "--fused-ce"],
        stats=gs,
    )
    # the scaling trio's other two models (secondary evidence)
    inc_per_chip, inc_mfu = resnet.main(
        ["--model", "inception3", "--num-iters", "3",
         "--num-batches-per-iter", "12", "--num-warmup-batches", "3",
         "--batch-size", "256"],
        stats=is_,
    )
    vgg_per_chip, vgg_mfu = resnet.main(
        ["--model", "vgg16", "--num-iters", "3",
         "--num-batches-per-iter", "12", "--num-warmup-batches", "3",
         "--batch-size", "128"],
        stats=vs,
    )
    # Inception batch-size sensitivity: the 256 sweet spot is sharp
    # (r3: 192/320 crater ~3.3x); record the cliff so it can regress
    # visibly. Short windows — these are canaries, not headlines.
    batch_sensitivity = {}
    for b in (192, 320):
        per_chip, _ = resnet.main(
            ["--model", "inception3", "--num-iters", "2",
             "--num-batches-per-iter", "4", "--num-warmup-batches", "2",
             "--batch-size", str(b)])
        batch_sensitivity[str(b)] = round(per_chip, 1)
    batch_sensitivity["256"] = round(inc_per_chip, 1)

    print(
        json.dumps(
            {
                "metric": "resnet50_synthetic_images_per_sec_per_chip",
                "value": round(img_per_chip, 2),
                "unit": "images/sec/chip",
                "vs_baseline": round(
                    img_per_chip / BASELINE_IMG_PER_SEC_PER_CHIP, 3
                ),
                "extra_metrics": {
                    "resnet50_mfu": round(resnet_mfu, 4),
                    "resnet50_spread": _spread(rs),
                    "bertlarge_pretrain_tokens_per_sec_per_chip": round(
                        tok_per_chip, 1
                    ),
                    "bertlarge_mfu": round(bert_mfu, 4),
                    "bertlarge_spread": _spread(bs),
                    "gpt2_medium_tokens_per_sec_per_chip": round(
                        gpt_per_chip, 1
                    ),
                    "gpt2_medium_mfu": round(gpt_mfu, 4),
                    "gpt2_medium_spread": _spread(gs),
                    "eager_path": eager_path,
                    "inception3_images_per_sec_per_chip": round(
                        inc_per_chip, 1
                    ),
                    "inception3_mfu": round(inc_mfu, 4),
                    "inception3_spread": _spread(is_),
                    "inception3_batch_sensitivity": batch_sensitivity,
                    "vgg16_images_per_sec_per_chip": round(
                        vgg_per_chip, 1
                    ),
                    "vgg16_mfu": round(vgg_mfu, 4),
                    "vgg16_spread": _spread(vs),
                    "flop_accounting": "cnn=2*MACs*3(fwd+bwd) "
                                       "transformer=6ND (r3+)",
                },
            }
        )
    )


if __name__ == "__main__":
    main()
