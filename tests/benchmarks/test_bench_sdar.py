"""The block-diffusion MoE share as the benchmark holds it: its
configuration file against its source, its cell's arithmetic, and the
three readers it brings. No file of the harness is edited for it; the
harness's own parametrised tests pick the entries up as well."""

import copy
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops, harness, published, scopes  # noqa: E402
from benchmarks.jobs import dp_train  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    moe_dispatch_ms, moe_experts_ms, moe_experts_roofline)

BENCH = harness.load_json(ROOT, "BENCHMARK.json")
CELL = "sdar_bd_s4096"
ENTRY = next(c for c in BENCH["configs"] if c["name"] == "sdar-30b-a3b-chat")
BODY = harness.load_json(ROOT, ENTRY["file"])
FOUND = harness.load_cell(CELL)


def test_the_file_holds_every_published_width_and_three_cuts():
    published.check(ENTRY, BODY)
    model = BODY["model"]
    assert ENTRY["source"] == BODY["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
        "config.json")
    assert BODY["family"] == "block_diffusion_moe_lm"
    assert (model["hidden_size"], model["num_heads"], model["num_kv_heads"],
            model["head_dim"], model["expert_mlp_dim"], model["num_experts"],
            model["experts_per_token"], model["diffusion_block"]) == (
        2048, 32, 4, 128, 768, 128, 8, 4)
    assert model["qk_norm"] and model["norm_topk_prob"] \
        and not model["tie_embeddings"] and not model["causal"]
    assert ENTRY["reduced"] == [c["key"] for c in BODY["reduced"]] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert {c["key"]: (c["published"], c["held"])
            for c in BODY["reduced"]} == {
        "num_hidden_layers": (48, 6), "num_experts": (128, 16),
        "vocab_size": (151936, 18992)}
    assert BODY["deployment"]["chips"] == 8
    assert {"diffusion_block", "noise_schedule", "router_aux_loss",
            "parameter_dtype"} <= set(BODY["assumed"])
    # the same body as the harness's own fixture of this share, but for
    # its name, its source and what a cell had to settle
    fixture = harness.load_json(
        ROOT, "tests", "benchmarks", "data",
        "block_diffusion_share.json")["body"]
    settled = {"remat"}
    assert {k: v for k, v in model.items() if k not in settled} \
        == fixture["model"]
    assert BODY["tiny"] == fixture["tiny"]
    assert BODY["not_held"] == fixture["not_held"]
    assert published.source_of(BODY) == published.source_of(fixture)


@pytest.mark.parametrize("key,group_key,value", [
    ("head_dim", "head_dim", 64),
    ("hidden_size", "hidden_size", 1024),
    ("moe_intermediate_size", "expert_mlp_dim", 384),
    ("num_experts_per_tok", "experts_per_token", 4),
])
def test_a_width_cut_is_refused_by_the_keys_name(key, group_key, value):
    """Written down or not, in the file and in the entry: a width, or
    the experts a token is sent to, is never cut."""
    body, entry = copy.deepcopy(BODY), copy.deepcopy(ENTRY)
    was = body[key]
    body["model"][group_key] = value
    with pytest.raises(ValueError, match=f"key '{key}'.*does not name"):
        published.check(entry, body)  # not written down
    body[key] = value  # a cut key holds what is run
    body["reduced"].append({"key": key, "published": was, "held": value,
                            "why": "to fit"})
    entry["reduced"].append(key)
    with pytest.raises(ValueError, match=f"key '{key}'.*never cut"):
        published.check(entry, body)
    # and the router's own width stays as published where experts are cut
    body, entry = copy.deepcopy(BODY), copy.deepcopy(ENTRY)
    body["model"]["num_experts"] = 16
    with pytest.raises(ValueError, match="key 'num_experts'.*router"):
        published.check(entry, body)


def test_the_cells_traffic_and_arithmetic():
    traffic, model = FOUND["traffic"], FOUND["config"]["model"]
    assert (traffic["job"], traffic["objective"], traffic["seq_len"],
            traffic["batch_per_chip"], traffic["t_min"],
            traffic["attention"], traffic["loss_head"],
            traffic["learning_rate"]) == (
        "dp_train", "block_diffusion", 4096, 2, 0.1, "flash", "fused_ce",
        1e-4)
    assert FOUND["cell"]["chips"] == 1
    parts = flops.forward_flops_per_token(model, traffic)
    assert parts["blocks"] == 572_522_496
    assert parts["attention"] == 403_046_400
    assert parts["head"] == pytest.approx(77_791_232 * 0.55)
    work = flops.attention_kernel_work(model, traffic)
    assert work["flops"] == pytest.approx(9.905e12, rel=1e-3)
    # 645.6 M parameters: six layers' shares and an eighth of embedding
    # and head
    layer = (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2048 * 128
             + 16 * 3 * 2048 * 768 + 2 * 2048 + 2 * 128)
    assert 6 * layer + 2 * 18992 * 2048 + 2048 == 645_623_296


def test_the_reference_takes_the_cells_batch_in_one_block():
    reference = harness.load_reference(BODY["family"])
    assert reference.TAKES_CHOICES is True
    assert all(callable(getattr(reference, f)) for f in (
        "arguments", "mean_loss", "nll_sum", "choice_scores"))
    traffic = FOUND["traffic"]
    assert reference.BLOCK_TOKENS >= \
        traffic["seq_len"] * traffic["batch_per_chip"]
    kw = reference.arguments(FOUND["config"]["model"], traffic)
    assert (kw["num_layers"], kw["num_experts"], kw["held"],
            kw["per_token"], kw["block"], kw["first_expert"]) == (
        6, 128, 16, 8, 4, 0)
    # it imports nothing of the program or the harness
    with open(os.path.join(ROOT, "benchmarks", "reference",
                           BODY["family"] + ".py")) as f:
        text = f.read()
    assert "horovod_tpu" not in text.split('"""', 2)[2]
    assert "import benchmarks" not in text and "from benchmarks" not in text
    # the job names the choices as the reference does
    assert dp_train.named_choices({"choices": {"block_3": {"mlp": {
        "experts": ("x",)}}}}) == {reference.choice_name(3): "x"}


def test_expert_work_counts_the_expected_load():
    """16,384 positions send 8 x 16 / 128 = one row each to the held
    experts: 16,384 rows a layer through three products of 2048 x 768,
    forward and two backward products each, in six layers."""
    work = moe_experts_roofline.expert_work(
        FOUND["config"]["model"], FOUND["traffic"])
    rows = 16384
    assert work["flops"] == 6 * 3 * 3 * 2 * rows * 2048 * 768
    assert work["bytes"] == 6 * 3 * 3 * 2 * (
        rows * (2048 + 768) + 16 * 2048 * 768)
    peak = harness.peak_of("TPU v5 lite")
    least, bound = flops.roofline_seconds(work, peak)
    assert bound == "compute" and least == pytest.approx(14.13e-3, rel=1e-3)
    # every expert held: every choice is a row here
    whole = {**FOUND["config"]["model"], "experts_held": 128}
    assert moe_experts_roofline.expert_work(whole, FOUND["traffic"])[
        "flops"] == 8 * work["flops"]


def a_run(**kw):
    run = harness.Run(
        started=time.perf_counter(), workload=CELL, chips=1,
        config=FOUND["config"], traffic=FOUND["traffic"],
        model_sizes=FOUND["config"]["model"], seed=0, seconds=0,
        trace=True, rehearse=True)
    run.device_kind = "TPU v5 lite"
    for key, value in kw.items():
        setattr(run, key, value)
    return run


def test_readers_read_their_scope_and_nothing_without_one(monkeypatch):
    # one traced step: 3 ms of expert products forward, 7 backward, 2 ms
    # of dispatch, 5 ms under the module's bare name
    tables = {0: [{("forward", scopes.program.MOE_EXPERTS, None): 3e6,
                   ("backward", scopes.program.MOE_EXPERTS, None): 7e6,
                   ("forward", scopes.program.MOE_DISPATCH, None): 2e6,
                   ("forward", "mlp", None): 5e6}]}
    run = a_run(scope_tables=tables)
    assert moe_experts_ms.read(run) == pytest.approx(10.0)
    assert moe_dispatch_ms.read(run) == pytest.approx(2.0)
    # the products themselves are Mosaic calls the TPU compiler makes
    # and names itself: found by their stem among the step's kernels
    run = a_run(scope_tables=tables, reduced_trace={
        "kernel_ms_by_stem": {"ragged-dot-none": 40.0, "attn": 300.0}})
    assert moe_experts_ms.read(run) == pytest.approx(50.0)
    share = moe_experts_roofline.read(run)
    assert share == pytest.approx(100 * 14.1276 / 50.0, rel=1e-4)
    # no trace (a rehearsal): nothing, and nothing raised
    empty = a_run(scope_tables={})
    assert moe_experts_ms.read(empty) is None
    assert moe_dispatch_ms.read(empty) is None
    assert moe_experts_roofline.read(empty) is None
    # a program from before the scopes had names: nothing either
    monkeypatch.setattr(scopes, "program", types.SimpleNamespace(
        LOSS_HEAD="loss_head"))
    for reader in (moe_experts_ms, moe_dispatch_ms, moe_experts_roofline):
        assert reader.read(a_run(scope_tables=tables)) is None


def test_the_three_metrics_are_the_routed_cells_alone():
    # (named `..._are_the_cells_alone` until PR 44, and asserted too that
    # the three were the last of `per_layer`: false once any PR adds a
    # metric, since the driver takes new entries at the end of a list)
    mine = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert [(m["name"], m["unit"], m["layer"], m["source"]) for m in mine] \
        == [("moe_experts_ms", "ms/step", "model", "device_trace"),
            ("moe_dispatch_ms", "ms/step", "model", "device_trace"),
            ("moe_experts_roofline", "%", "kernels", "device_trace")]
    assert all(m["moves"] == "tokens_per_s_per_chip" for m in mine)
    for cell in ("gpt2m_dp1", "bertl_s128"):
        names = {m["name"] for m in harness.load_cell(cell)["per_layer"]}
        assert not names & {m["name"] for m in mine}
    assert {m["name"] for m in mine} <= {
        m["name"] for m in FOUND["per_layer"]}
    assert len(FOUND["cell"]["why"]) <= 200
