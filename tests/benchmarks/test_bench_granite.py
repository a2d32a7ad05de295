"""The cell ``granite_h_lm`` as the benchmark takes it: a configuration
held to its source and counted by kind, its traffic, its reference
found by the family's name, five readers over the state-space mixer's
scopes, and their entries at the end of ``per_layer``."""

import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops, harness, published, scopes  # noqa: E402
from horovod_tpu.utils import scopes as program  # noqa: E402

BENCH = harness.load_json(ROOT, "BENCHMARK.json")
CELL, CONFIG = "granite_h_lm", "granite-4.0-h-micro"
ROUTED_CELL = "sdar_bd_s4096"
FIXTURE = harness.load_json(ROOT, "tests", "benchmarks", "data",
                            "state_space_hybrid_share.json")
METRICS = ("mamba_proj_ms", "mamba_scan_ms", "mamba_conv_gate_ms",
           "mamba_proj_roofline", "mamba_scan_roofline")
MULTIPLIERS = {"embedding_multiplier": 12, "residual_multiplier": 0.22,
               "attention_multiplier": 0.015625, "logits_scaling": 8}


@pytest.fixture(autouse=True)
def the_harness_own_kinds():
    flops.kinds_root(None)
    yield
    flops.kinds_root(None)


def found():
    return harness.load_cell(CELL)


# -- the configuration and the cell -------------------------------------------

def test_the_configuration_is_held_to_its_source_and_counted_by_kind():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    body = harness.load_json(ROOT, entry["file"])
    published.check(entry, body)
    assert entry == BENCH["configs"][-1]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert [(c["key"], c["published"], c["held"]) for c in body["reduced"]
            ] == [("num_hidden_layers", 40, 10), ("vocab_size", 100352,
                                                 12544)]
    parts = flops.forward_flops_per_token(body["model"], found()["traffic"])
    assert parts == {"blocks": 1_492_699_136, "mamba2": 18_874_368,
                     "attention": 33_554_432,
                     "head": pytest.approx(51_373_952)}
    assert sum(parts.values()) == pytest.approx(1_596_501_888)
    assert flops.layers_by_kind(body["model"]) == {"mamba2": 9,
                                                   "attention": 1}


def test_the_file_is_the_fixtures_body_with_what_the_program_reads_added():
    """``tests/benchmarks/data/state_space_hybrid_share.json`` is this
    row's cut (PR 44): every key of the source and of the cut as there,
    the model group with the four multipliers and ``remat`` besides."""
    body = harness.load_json(ROOT, "benchmarks", "configs",
                             CONFIG + ".json")
    was = FIXTURE["body"]
    ours = {"name", "source", "note", "model", "tiny", "assumed"}
    assert {k: v for k, v in body.items() if k not in ours} == \
        {k: v for k, v in was.items() if k not in ours}
    assert body["family"] == "state_space_hybrid_lm"
    assert body["model"] == {**was["model"], **MULTIPLIERS, "remat": True}
    # the multipliers are the source's own keys' values
    assert all(body[k] == v for k, v in MULTIPLIERS.items())
    assert set(body["assumed"]) == {"parameter_dtype", "equations",
                                    "initialisation", "remat",
                                    "multipliers"}
    # the tiny preset keeps a pattern of both kinds
    assert body["tiny"]["layer_types"].count("attention") == 1
    assert len(body["tiny"]["layer_types"]) == body["tiny"]["num_layers"]


def test_the_fixture_still_reads_as_pr_44_left_it():
    """What the case `[None-None-None]` of
    `test_the_hybrid_is_held_to_its_source_and_refused_by_the_keys_name`
    held of the fixture before its family had a reference
    (tests/conftest.py): the body passes, 33 source keys, 14 of them
    `not_held`, the pattern and the seven keys of the state-space kind
    beside the file's own rows'; and no BENCHMARK.json names its
    entry."""
    entry, body = FIXTURE["entry"], FIXTURE["body"]
    published.check(entry, body)
    source = published.source_of(body)
    assert len(source) == 33 and len(body["not_held"]) == 14
    mamba = {k for row in flops.load_kind("mamba2").ROWS for k in row.keys}
    assert len(mamba) == 7
    assert set(source) == (set(source) & published.KNOWN) | mamba | {
        "layer_types"} | set(body["not_held"])
    assert entry["name"] not in repr(BENCH)
    # and the real configuration's source is the same 33 keys
    real = harness.load_json(ROOT, "benchmarks", "configs",
                             CONFIG + ".json")
    assert set(published.source_of(real)) == set(source)
    assert real["not_held"] == body["not_held"]


def test_the_cell_is_one_chip_and_its_traffic_is_the_issues():
    cell = found()["cell"]
    assert cell == BENCH["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "lm_s8192_b1_dp1", 1)
    traffic = found()["traffic"]
    assert {k: traffic[k] for k in (
        "job", "objective", "seq_len", "batch_per_chip", "learning_rate",
        "attention", "loss_head")} == {
        "job": "dp_train", "objective": "causal_lm", "seq_len": 8192,
        "batch_per_chip": 1, "learning_rate": 1e-4, "attention": "flash",
        "loss_head": "fused_ce"}
    assert traffic["tiny"] == {"seq_len": 64, "batch_per_chip": 2}
    # what the fixture's traffic body said, and a note of its own
    assert {k: v for k, v in traffic.items() if k not in (
        "note", "input")} == {k: v for k, v in FIXTURE["traffic"].items()
                              if k != "note"}


def test_the_reference_reads_every_size_and_defaults_none():
    reference = harness.load_reference(
        found()["config"]["family"])
    model, traffic = found()["config"]["model"], found()["traffic"]
    kw = reference.arguments(model, traffic)
    assert kw["layer_types"] == tuple(model["layer_types"])
    assert kw["mamba"] == {"heads": 64, "d_head": 64, "d_state": 128,
                           "groups": 1, "taps": 4}
    assert {k: kw[k] for k in MULTIPLIERS} == MULTIPLIERS
    for key in ("layer_types", "mamba_d_state", "mamba_n_groups",
                "num_kv_heads", *MULTIPLIERS):
        with pytest.raises(KeyError, match=key):
            reference.arguments(
                {k: v for k, v in model.items() if k != key}, traffic)
    with pytest.raises(ValueError, match="no position code"):
        reference.arguments({**model, "position": "rope"}, traffic)
    # nothing of the program's: the module imports jax alone
    with open(reference.__file__) as f:
        source = f.read()
    assert "horovod_tpu" not in source.split('"""', 2)[2]
    assert "lax.scan(position" in source  # position by position


# -- the readers ----------------------------------------------------------------

def by_scope_run(cell, table):
    """A traced run of ``cell`` whose one device's one step reads
    ``table``: ``{(phase, layer): ms}``."""
    got = harness.load_cell(cell)
    run = harness.Run(
        started=time.perf_counter(), workload=cell, chips=1,
        traffic=got["traffic"], model_sizes=got["config"]["model"],
        seed=0, seconds=10, trace=True, rehearse=False)
    run.device_kind = "TPU v5 lite"
    run.logged = []
    run.log = run.logged.append
    run.scope_tables = {0: [{(phase, layer, None): ms * 1e6
                             for (phase, layer), ms in table.items()}]}
    return run


HYBRID_STEP = {
    ("forward", program.MAMBA_PROJ): 30.0,
    ("backward", program.MAMBA_PROJ): 90.0,
    ("forward", program.MAMBA_SCAN): 20.0,
    ("backward", program.MAMBA_SCAN): 60.0,
    ("forward", program.MAMBA_CONV): 3.0,
    ("backward", program.MAMBA_CONV): 5.0,
    ("forward", program.MAMBA_GATE): 2.0,
    ("backward", program.MAMBA_GATE): 6.0,
    ("forward", "mlp"): 50.0, ("backward", program.ATTN_PROJ): 9.0,
}


def test_the_five_readers_on_a_hybrids_step():
    run = by_scope_run(CELL, HYBRID_STEP)
    read = {m: harness.load_reader(m)(run) for m in METRICS}
    assert read["mamba_proj_ms"] == pytest.approx(120.0)
    assert read["mamba_scan_ms"] == pytest.approx(80.0)
    assert read["mamba_conv_gate_ms"] == pytest.approx(16.0)
    # the projections: 2048 x 8512 and 4096 x 2048, 8,192 positions,
    # nine layers, three passes; compute-bound
    macs = 2048 * 8512 + 4096 * 2048
    least_ms = 1e3 * 9 * 3 * 2 * 8192 * macs / 197e12
    assert read["mamba_proj_roofline"] == pytest.approx(
        100 * least_ms / 120.0)
    # the recurrence: nine times 351,272,960 bytes over 819 GB/s, 3.9 ms
    assert 9 * 351_272_960 / 819e9 == pytest.approx(3.86e-3, rel=1e-3)
    assert read["mamba_scan_roofline"] == pytest.approx(
        100 * 1e3 * 9 * 351_272_960 / 819e9 / 80.0)
    assert all(0 < read[m] < 100 for m in METRICS if "roofline" in m)
    assert any("hbm-bound" in line for line in run.logged)


def test_every_reader_reads_nothing_on_a_model_with_no_such_layer():
    """One test calls every new reader on a block-diffusion run: the
    routed cell's step has none of the four scopes."""
    run = by_scope_run(ROUTED_CELL, {
        ("forward", "attn"): 40.0, ("backward", program.MOE_EXPERTS): 55.0,
        ("backward", program.ATTN_PROJ): 60.0})
    assert [harness.load_reader(m)(run) for m in METRICS] == [None] * 5
    assert run.logged == []


def test_every_reader_reads_nothing_without_a_trace_or_without_the_names(
        monkeypatch):
    run = by_scope_run(CELL, HYBRID_STEP)
    run.scope_tables = {}  # no trace, or no TPU plane (a rehearsal)
    assert [harness.load_reader(m)(run) for m in METRICS] == [None] * 5
    # the parent's program: scopes, but none of the mixer's
    old = types.SimpleNamespace(**{
        k: v for k, v in vars(program).items()
        if k.isupper() and not k.startswith("MAMBA_")})
    old.LAYER_SCOPES = tuple(s for s in program.LAYER_SCOPES
                             if not s.startswith("mamba_"))
    monkeypatch.setattr(scopes, "program", old)
    scopes.classify.cache_clear()
    try:
        run = by_scope_run(CELL, HYBRID_STEP)
        assert [harness.load_reader(m)(run) for m in METRICS] == [None] * 5
    finally:
        monkeypatch.undo()
        scopes.classify.cache_clear()


@pytest.mark.parametrize("op_name,want", [
    ("jit(step_fn)/jvp(Transformer)/block_0/mamba/mamba_proj/in_proj/"
     "dot_general", ("forward", "mamba_proj")),
    ("jit(step_fn)/transpose(jvp(Transformer))/block_3/mamba/mamba_scan/"
     "checkpoint/while/body/dot_general", ("backward", "mamba_scan")),
    ("jit(step_fn)/transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/"
     "rematted_computation/block_1/mamba/mamba_conv/logistic",
     ("backward", "mamba_conv")),
    ("jit(step_fn)/jvp(Transformer)/block_1/mamba/mamba_gate/rsqrt",
     ("forward", "mamba_gate")),
    ("jit(step_fn)/jvp(Transformer)/block_1/mlp/gate/dot_general",
     ("forward", "mlp")),
])
def test_classify_knows_the_mixers_four_scopes(op_name, want):
    assert scopes.classify(op_name) == want


# -- the entries ------------------------------------------------------------------

def test_the_five_entries_stand_at_the_end_and_are_the_cells_alone():
    last = BENCH["per_layer"][-5:]
    assert [m["name"] for m in last] == list(METRICS)
    for m in last:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
        roofline = m["name"].endswith("_roofline")
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if roofline else ("ms/step", "lower"))
        assert m["layer"] == ("kernels" if m["name"]
                              == "mamba_scan_roofline" else "model")
        reader = sys.modules[harness.load_reader(m["name"]).__module__]
        assert m["layer"] in reader.__doc__.split(":")[0]
        assert not getattr(reader, "PLATFORM_FREE", False)
    offered = {m["name"] for m in found()["per_layer"]}
    assert set(METRICS) <= offered
    # what is read in every cell is read here too; the dense MLP's two
    # list four cells and wait for a `benchmark` PR (PERF.md section 7)
    assert {"mfu_pct", "attn_kernel_roofline", "attn_proj_roofline",
            "device_idle_pct"} <= offered
    assert not {"mlp_ms", "mlp_roofline", "moe_experts_ms"} & offered
    for w in BENCH["workloads"][:-1]:
        assert not set(METRICS) & {
            m["name"] for m in harness.load_cell(w["name"])["per_layer"]}


def test_six_cells_one_at_four_chips():
    assert [w["name"] for w in BENCH["workloads"]] == [
        "gpt2m_dp1", "gpt2m_dp4", "bertl_s512", "bertl_s128",
        "sdar_bd_s4096", CELL]
    assert [w["chips"] for w in BENCH["workloads"]].count(4) == 1
