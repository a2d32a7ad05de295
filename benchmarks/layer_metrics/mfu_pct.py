"""model: model-FLOP utilisation. The benchmark's own required
operations per token (``benchmarks/flops.py``) times the untraced
window's tokens/s/chip, over the peak of the device kind."""

from benchmarks import flops, harness


def read(run):
    if run.rehearse or run.tokens_per_s_per_chip is None:
        return None
    peak = harness.peak_of(run.device_kind)
    per_token = flops.train_flops_per_token(run.model_sizes, run.traffic)
    return (100.0 * per_token * run.tokens_per_s_per_chip
            / peak["bf16_flops_per_s"])
