#!/usr/bin/env python
"""Capture an xplane trace of the BERT-L pretraining step (the bench.py
config) for MFU analysis. Pair with scripts/xplane_summary.py."""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from horovod_tpu.utils.script_loader import load_example


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--logdir", default="/tmp/xplane_bert")
    p.add_argument("--batch-size", type=int, default=24)
    p.add_argument("--example", default="bert_pretraining",
                   help="transformer example to trace "
                        "(bert_pretraining | gpt2_pretraining)")
    p.add_argument("--extra", default="--flash",
                   help="comma-separated flags forwarded to "
                        "bert_pretraining, e.g. --extra=--flash,--fused-ce")
    args = p.parse_args(argv)

    bert = load_example(args.example)
    # warm up compile outside the trace window, then trace one short run
    extra = [f for f in args.extra.split(",") if f]
    common = ["--num-iters", "1", "--num-batches-per-iter", "3",
              "--num-warmup-batches", "2", "--batch-size",
              str(args.batch_size)] + extra
    bert.main(common)
    with jax.profiler.trace(args.logdir):
        bert.main(common)
    print(f"-> {args.logdir}", flush=True)


if __name__ == "__main__":
    main()
