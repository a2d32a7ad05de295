"""Example-script smoke tests (reference tier-3 pattern: the examples ARE
the integration surface users copy; SURVEY.md §4). Each runs in-process
on the 8-device CPU mesh with tiny configs."""

import numpy as np
import pytest

from horovod_tpu.utils.script_loader import load_example as _load


def test_mnist_example_learns():
    acc = _load("mnist").main(
        ["--epochs", "1", "--train-size", "512", "--test-size", "128"]
    )
    # synthetic templates are separable: one epoch should beat chance by far
    assert acc > 0.5


def test_adasum_gpt2_converges():
    first, last = _load("adasum_gpt2").main(["--steps", "20"])
    assert last < first - 0.5, (first, last)


@pytest.mark.slow  # ~26s; the base adasum_gpt2 convergence stays
# tier-1 and the flash kernels' correctness is tier-1-covered by
# test_pallas_attention — the flash×Adasum cross-variant rides the
# slow tier (budget repair, PR-1/5/9 precedent: tier-1 measured 873s
# at prior HEAD on this host vs the 870s gate before this PR's tests)
def test_adasum_gpt2_flash_converges():
    """--flash swaps in the Pallas kernels (interpret mode on CPU) and
    the Adasum training curve must still descend the same way."""
    first, last = _load("adasum_gpt2").main(
        ["--steps", "12", "--seq-len", "64", "--layers", "2", "--flash"]
    )
    assert last < first - 0.3, (first, last)


def test_elastic_gpt2_runs_to_completion():
    final = _load("gpt2_elastic").main(["--steps", "12", "--commit-every", "4"])
    assert np.isfinite(final)


def test_bert_pretraining_tiny():
    per_chip, mfu = _load("bert_pretraining").main(
        ["--layers", "2", "--hidden", "128", "--seq-len", "64",
         "--batch-size", "2", "--num-iters", "1",
         "--num-batches-per-iter", "2", "--num-warmup-batches", "1"]
    )
    assert per_chip > 0
    assert mfu is None  # CPU mesh: MFU is not measured


def test_gpt2_pretraining_tiny(capsys):
    """chip_smoke.py's vehicle on the CPU mesh: same main(), tiny
    shape, flash kernels interpreted; MFU is reported as not measured
    and the stats chip_smoke.py inspects are filled."""
    stats = {}
    per_chip, mfu = _load("gpt2_pretraining").main(
        ["--layers", "2", "--hidden", "128", "--seq-len", "64",
         "--batch-size", "1", "--num-iters", "2",
         "--num-batches-per-iter", "1", "--num-warmup-batches", "1",
         "--flash", "--fused-ce"],
        stats=stats,
    )
    assert per_chip > 0 and mfu is None
    assert "MFU not measured" in capsys.readouterr().out
    assert len(stats["losses"]) == 2 and np.isfinite(stats["losses"]).all()
    assert stats["compile_seconds"] > 0
    assert "all-reduce" in stats["compiled"].as_text()
    assert len(stats["batch_sharding"].device_set) == 8
    assert len(stats["loss"].addressable_shards) == 8


@pytest.mark.slow  # ~65s of ResNet-50 AOT compile — the single
# largest tier-1 test; moved to the slow tier to keep the gate inside
# its time budget (the PR-1 precedent for multi-minute AOT compiles)
def test_resnet_synthetic_tiny():
    per_chip, mfu = _load("resnet50_synthetic").main(
        ["--batch-size", "2", "--image-size", "32", "--num-iters", "1",
         "--num-batches-per-iter", "1", "--num-warmup-batches", "1",
         "--num-classes", "10", "--bf16-allreduce"]
    )
    assert per_chip > 0


def test_llama_adasum_converges():
    """BASELINE config 4's architecture for real: RMSNorm/RoPE/SwiGLU
    Llama with the Adasum optimizer path, at smoke scale."""
    first, last = _load("llama_adasum").main(
        ["--steps", "14", "--layers", "2", "--hidden", "256",
         "--vocab", "256", "--seq-len", "64", "--batch-size", "1"]
    )
    assert last < first - 0.3, (first, last)


@pytest.mark.slow  # ~28s; same budget-repair rationale as the gpt2
# flash variant above — base Llama Adasum convergence stays tier-1,
# remat-over-flash-custom_vjp is also exercised by the slow tier and
# the pallas kernel suites
def test_llama_adasum_flash_remat_converges():
    """--flash under the Llama path covers the hairy combinations: RoPE'd
    q/k into the kernels, RMSNorm residuals, and nn.remat wrapping the
    flash custom_vjp (rematerialization over custom-VJP blocks is a
    classic breakage point)."""
    first, last = _load("llama_adasum").main(
        ["--steps", "12", "--layers", "2", "--hidden", "256",
         "--vocab", "256", "--seq-len", "64", "--batch-size", "1",
         "--flash", "--remat"]
    )
    assert last < first - 0.3, (first, last)


def test_pipeline_pretraining_1f1b_learns():
    first, last = _load("pipeline_pretraining").main(
        ["--steps", "14", "--pp", "2", "--microbatches", "4",
         "--layers", "2", "--seq-len", "64"])
    assert last < first - 0.5, (first, last)


def test_pipeline_pretraining_gpipe_learns():
    first, last = _load("pipeline_pretraining").main(
        ["--schedule", "gpipe", "--steps", "14", "--pp", "2",
         "--microbatches", "4", "--layers", "2", "--seq-len", "64"])
    assert last < first - 0.5, (first, last)
