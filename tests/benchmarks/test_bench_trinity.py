"""``trinity-mini`` as a cell (PR 48): the configuration held to the
catalog's row, its reference under the family's name, the four readers
it brings (``attn_window_kernel_ms``, ``attn_window_kernel_roofline``,
``moe_shared_ms``, ``post_norm_ms``) on a hand-made step with known
answers, and a rehearsal end to end. Nothing here is a device
number."""

import json
import os
import subprocess
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops, harness, published  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    attn_window_kernel_ms, attn_window_kernel_roofline, moe_shared_ms,
    post_norm_ms)
from horovod_tpu.utils import scopes as program  # noqa: E402

CELL = "trinity_mini_s8192"
SHARE = harness.load_json(ROOT, "tests", "benchmarks", "data",
                          "window_gated_moe_share.json")
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
# what the cell brought: the metrics that list it and no other
BROUGHT = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
NEW = tuple(m["name"] for m in BROUGHT)
FOUND = harness.load_cell(CELL)
MODEL, TRAFFIC = FOUND["config"]["model"], FOUND["traffic"]
ENTRY = next(c for c in BENCH["configs"] if c["name"] == "trinity-mini")


@pytest.fixture(autouse=True)
def the_harness_own_kinds():
    flops.kinds_root(None)
    yield
    flops.kinds_root(None)


# -- the entries ----------------------------------------------------------------

def test_the_entries_are_what_the_contract_takes():
    assert ENTRY == {
        "name": "trinity-mini",
        "source": "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/"
                  "config.json",
        "file": "benchmarks/configs/trinity-mini.json",
        "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
        "why": ENTRY["why"]}
    cell = FOUND["cell"]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == ("trinity_mini_s8192", "trinity-mini", "lm_s8192_b1_dp1", 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(0 < len(e["why"]) <= 200 for e in (ENTRY, cell))
    assert NEW == ("attn_window_kernel_ms", "attn_window_kernel_roofline",
                   "moe_shared_ms", "post_norm_ms")
    # each at the end of its list
    assert BENCH["configs"][-1] == ENTRY
    assert BENCH["workloads"][-1] == cell
    assert BENCH["per_layer"][-len(NEW):] == BROUGHT
    for m in BROUGHT:
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # one four-chip cell, as before
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4] == [
        "gpt2m_dp4"]


@pytest.mark.parametrize("metric", NEW)
def test_a_readers_docstring_starts_with_its_layer_and_it_is_not_free(
        metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    module = sys.modules[harness.load_reader(metric).__module__]
    assert module.__doc__.split(":")[0] == entry["layer"]
    assert not getattr(module, "PLATFORM_FREE", False)


def test_the_cell_is_offered_its_four_and_the_29_without_a_list():
    offered = [m["name"] for m in FOUND["per_layer"]]
    everywhere = [m["name"] for m in BENCH["per_layer"]
                  if "workloads" not in m]
    assert len(everywhere) == 29
    assert set(offered) == set(everywhere) | set(NEW)


# -- the configuration ------------------------------------------------------------

def test_the_file_is_the_fixtures_body_with_the_name_and_what_the_program_needs():
    config, body = FOUND["config"], SHARE["body"]
    for key, value in body.items():
        if key in ("name", "source", "note", "model", "tiny", "assumed",
                   "reduced"):
            continue
        assert config[key] == value, key
    assert [{k: v for k, v in r.items() if k != "why"}
            for r in config["reduced"]] == [
        {k: v for k, v in r.items() if k != "why"} for r in body["reduced"]]
    # the model group: the fixture's, and the keys the program's fields
    # brought (the activations' dtype stated because `tiny` states its)
    added = {"rope_kinds": ["window_attention"], "post_norms": True,
             "remat": True, "dtype": "bfloat16"}
    assert config["model"] == {**body["model"], **added}
    assert {k: v for k, v in config["tiny"].items() if k != "dtype"} == \
        body["tiny"]
    assert set(config["assumed"]) >= {
        "equations", "router_in_a_share", "remat", "expert_bias_update",
        "parameter_dtype"}
    assert config["family"] == "window_gated_moe_lm"
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "reference", config["family"] + ".py"))
    published.check(ENTRY, config)


def test_every_number_of_the_catalogs_row_is_in_the_file():
    """The keys `reduced` lists are the only ones that differ."""
    import json

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    config = FOUND["config"]
    assert config["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differ == set(ENTRY["reduced"])
    assert {r["key"]: r["published"] for r in config["reduced"]} == {
        k: row["config"][k] for k in differ}


def test_operations_per_token_are_the_issues():
    parts = flops.forward_flops_per_token(MODEL, TRAFFIC)
    assert {k: int(v) for k, v in parts.items()} == {
        "blocks": 580_911_104, "attention": 213_878_784,
        "head": 102_485_792}
    assert flops.layers_by_kind(MODEL) == {
        "window_attention": 5, "attention": 1}


# -- the readers ----------------------------------------------------------------------

def made_run(model=MODEL, traffic=TRAFFIC, rehearse=False, tmp="/nowhere"):
    run = harness.Run(
        started=time.perf_counter(), workload=CELL, chips=1,
        traffic=traffic, model_sizes=model, seed=0, seconds=10,
        trace=True, rehearse=rehearse, config=FOUND["config"], root=ROOT)
    run.device_kind = "TPU v5 lite"
    run.logged = []
    run.log = run.logged.append
    run.trace_dir = os.path.join(tmp, "no_trace_here")
    return run


FWD = "jit(step_fn)/jvp(Transformer)"
BWD = "jit(step_fn)/transpose(jvp(Transformer))"
CALL = ('custom-call(%p), custom_call_target="tpu_custom_call", '
        'metadata={op_name="')


def test_the_windows_kernels_are_found_by_their_blocks():
    lines = ["HloModule jit_step_fn", "",
             "ENTRY %main (p: bf16[8,128]) -> bf16[8,128] {",
             "  %p = bf16[8,128]{1,0} parameter(0)"]
    names = [
        f"{FWD}/block_0/attn/{program.FLASH_FWD}/pallas_call",   # window
        f"{FWD}/block_3/attn/{program.FLASH_FWD}/pallas_call",   # full
        f"{BWD}/block_4/attn/{program.FLASH_BWD}/pallas_call",   # window
        f"{BWD}/jvp(Transformer)/checkpoint/rematted_computation/"
        f"block_2/attn/{program.FLASH_FWD}/pallas_call",          # window
        f"{FWD}/block_1/attn/{program.ATTN_PREP}/"
        f"{program.QK_PREP_FWD}/pallas_call",       # layer attn_prep: not
        "ragged-dot-none",                           # an expert product
        f"{FWD}/block_13/attn/{program.FLASH_FWD}/pallas_call",  # no such
    ]
    for i, name in enumerate(names):
        lines.append(f"  %call.{i} = bf16[8,128]{{1,0}} {CALL}{name}\"}}")
    lines += ["  ROOT %r = bf16[8,128]{1,0} copy(%p)", "}"]
    layers = attn_window_kernel_ms.window_layers(MODEL)
    assert layers == [0, 1, 2, 4, 5]
    found = attn_window_kernel_ms.window_calls("\n".join(lines), layers)
    assert found == {
        "call.0": ("forward", "attn", "window_attention"),
        "call.2": ("backward", "attn", "window_attention"),
        "call.3": ("backward", "attn", "window_attention")}
    assert attn_window_kernel_ms.window_layers({"num_layers": 2}) == []


def test_the_windows_time_and_its_share_of_the_roofline():
    run = made_run()
    fwd, bwd = (("forward", "attn", attn_window_kernel_ms.KIND),
                ("backward", "attn", attn_window_kernel_ms.KIND))
    run.window_kernel_tables = {
        0: [{fwd: 30e6, bwd: 70e6}, {fwd: 30e6, bwd: 74e6},
            {fwd: 31e6, bwd: 71e6}],
        1: [{fwd: 90e6}, {fwd: 91e6}, {fwd: 92e6}]}
    assert attn_window_kernel_ms.read(run) == pytest.approx(102.0)
    # five window layers x 3 passes x 2 x 32 heads x the window's pairs
    # x (128 + 128) over 197 TFLOP/s
    pairs = 8192 * 2048 - 2048 * 2047 // 2 - 8192 // 2
    operations = 5 * 3 * 2 * 32 * pairs * 256
    assert operations == pytest.approx(5 * 7.214e11, rel=1e-3)
    assert attn_window_kernel_roofline.read(run) == pytest.approx(
        100 * 1e3 * operations / 197e12 / 102.0)
    assert any("window attention kernels' roofline" in line
               for line in run.logged)
    # kernels that ran the causal range's tiles read low, never over 100
    assert 0 < attn_window_kernel_roofline.read(run) < 100
    empty = made_run()
    empty.window_kernel_tables = {}
    assert attn_window_kernel_ms.read(empty) is None
    assert attn_window_kernel_roofline.read(empty) is None


def test_nothing_is_read_without_a_trace_or_a_window_layer(tmp_path):
    run = made_run(tmp=str(tmp_path))
    run.hlo_text = "HloModule x"
    assert attn_window_kernel_ms.read(run) is None
    plain = made_run({k: v for k, v in MODEL.items()
                      if k != "layer_types"}, tmp=str(tmp_path))
    assert attn_window_kernel_ms.by_window(plain) == {}


@pytest.mark.parametrize("reader,scope", [
    (moe_shared_ms, "MOE_SHARED"), (post_norm_ms, "POST_NORM")])
def test_a_scopes_reader_takes_its_layer_in_both_directions(
        monkeypatch, reader, scope):
    from benchmarks import scopes

    layer = getattr(program, scope)
    run = made_run()
    run.scope_tables = {0: [{
        ("forward", layer, None): 2e6, ("backward", layer, None): 5e6,
        ("forward", "mlp", None): 11e6, ("backward", "norm", None): 3e6,
        ("forward", program.MOE_EXPERTS, None): 7e6}]}
    assert reader.read(run) == pytest.approx(7.0)
    # a step with no such layer, and a program without the name
    run.scope_tables = {0: [{("forward", "mlp", None): 11e6}]}
    assert reader.read(run) is None
    monkeypatch.setattr(scopes, "program", types.SimpleNamespace())
    assert reader.read(run) is None


def test_the_scopes_reduction_knows_the_two_new_layers():
    from benchmarks import scopes

    assert scopes.classify(
        f"{FWD}/block_2/mlp/{program.MOE_SHARED}/shared_up/dot_general") \
        == ("forward", program.MOE_SHARED)
    assert scopes.classify(
        f"{BWD}/block_2/{program.POST_NORM}/ln_post_mlp/mul") == (
        "backward", program.POST_NORM)
    assert scopes.classify(f"{FWD}/block_2/ln_mlp/mul") == (
        "forward", "norm")


# -- the rehearsal --------------------------------------------------------------------

# `run.py` with its trace under a directory of the test's own, as
# `test_bench_names.REHEARSE_ELSEWHERE` runs it
REHEARSE = (
    f"import sys; sys.path.insert(0, {ROOT!r}); "
    "from benchmarks import harness, run; "
    "harness.TRACE_ROOT = sys.argv.pop(1); sys.exit(run.main())")


def test_the_cell_is_rehearsed_untraced(tmp_path):
    """The tiny preset through `run.py` and `jobs/dp_train.py` as they
    are: the system's loss and gradient against the family's reference
    at the system's choices, the reference's share of those choices,
    the global batch, 17 steps. The traced rehearsal is
    `test_bench_harness.py::test_rehearsal_runs_end_to_end`'s case of
    this cell, the first of its family."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    done = subprocess.run(
        [sys.executable, "-c", REHEARSE, str(tmp_path), "--workload", CELL,
         "--seed", "5", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 17
    compared = line["compared"]
    assert {"reference_loss", "reference_gradient", "reference_choices",
            "global_batch_loss", "loss_falls",
            "no_compile_in_window"} <= set(compared)
    assert compared["reference_gradient"]["limit"] == 3e-2
    assert all(c["ok"] for c in compared.values())
    assert set(line["metrics"]) == {"loss_step_16"}
