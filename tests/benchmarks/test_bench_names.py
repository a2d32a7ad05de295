"""The readers that put names on what lies between the kernels (PR 41):
attention's projections, attention's layout work, the dense MLP, and
the two flash kernels by the names the program gives them
(``horovod_tpu/utils/scopes.py``: ``ATTN_PROJ``, ``ATTN_PREP``,
``FLASH_FWD``, ``FLASH_BWD``), on a hand-made step with known answers,
as ``test_bench_scopes.py`` reads its own."""

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

from benchmarks import flops, harness, kernel_names, scopes  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    attn_proj_roofline, mlp_roofline)
from horovod_tpu.utils import scopes as program  # noqa: E402

US = 1000  # the events below are written in microseconds
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
ROUTED_CELL = "sdar_bd_s4096"
DENSE_CELLS = [c for c in CELLS if c != ROUTED_CELL]
BY_SCOPE = ("attn_proj_ms", "attn_prep_ms", "mlp_ms")
BY_NAME = ("attn_bwd_kernel_ms", "attn_fwd_recompute_kernel_ms")
ROOFLINES = ("attn_proj_roofline", "mlp_roofline")
NEW_METRICS = BY_SCOPE + ROOFLINES + BY_NAME
DENSE_ONLY = ("mlp_ms", "mlp_roofline")

FWD = "jit(step_fn)/jvp(Transformer)/block_0"
BWD = "jit(step_fn)/transpose(jvp(Transformer))/block_0"
# a rematerialised block's second forward, as the compiled step of
# `sdar_bd_s4096` names it (compiled for a described v5e, PR 41)
AGAIN = ("jit(step_fn)/transpose(jvp(Transformer))/jvp(Transformer)/"
         "checkpoint/rematted_computation/block_0")
CALL = ('custom-call(%p), custom_call_target="tpu_custom_call", '
        'metadata={op_name="')
PAIR = "(bf16[2,2,128,64]{3,2,1,0}, f32[2,2,1,128]{3,2,1,0})"
TRIPLE = ("(bf16[2,2,128,64]{3,2,1,0}, bf16[2,2,128,64]{3,2,1,0}, "
          "bf16[2,2,128,64]{3,2,1,0})")


def fusion(name, op_name):
    return (f'  %{name} = bf16[8,128]{{1,0}} fusion(%p), kind=kOutput, '
            f'metadata={{op_name="{op_name}"}}')


# one layer of a step under `remat`: the projections, the layout work
# and the kernels of attention, the MLP, both directions, and one
# instruction the plain-XLA attention would leave under the bare `attn`
HLO = "\n".join([
    "HloModule jit_step_fn, is_scheduled=true",
    "",
    "ENTRY %main.7 (p: bf16[8,128]) -> bf16[8,128] {",
    "  %p = bf16[8,128]{1,0} parameter(0)",
    fusion("fusion.1", f"{FWD}/attn/{program.ATTN_PROJ}/query/dot_general"),
    fusion("fusion.2", f"{FWD}/attn/{program.ATTN_PREP}/transpose"),
    f'  %flash_fwd.1 = {PAIR} {CALL}{FWD}/attn/{program.FLASH_FWD}'
    '/pallas_call"}',
    fusion("fusion.3", f"{FWD}/attn/{program.ATTN_PROJ}/out/dot_general"),
    fusion("fusion.4", f"{FWD}/mlp/fc1/dot_general"),
    f'  %flash_fwd.2 = {PAIR} {CALL}{AGAIN}/attn/{program.FLASH_FWD}'
    '/pallas_call"}',
    fusion("fusion.5", f"{BWD}/mlp/fc2/dot_general"),
    f'  %flash_bwd.3 = {TRIPLE} {CALL}{BWD}/attn/{program.FLASH_BWD}'
    '/pallas_call"}',
    fusion("fusion.6", f"{BWD}/attn/{program.ATTN_PREP}/reduce_sum"),
    fusion("fusion.7", f"{BWD}/attn/{program.ATTN_PROJ}/value/dot_general"),
    fusion("fusion.8", f"{BWD}/attn/mul"),
    "  ROOT %tuple.9 = (bf16[8,128]{1,0}) tuple(%fusion.8)",
    "}", ""])


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


STEP = [
    ev("fusion.1", 0, 7), ev("fusion.2", 7, 3), ev("flash_fwd.1", 10, 20),
    ev("fusion.3", 30, 5), ev("fusion.4", 35, 11),
    ev("flash_fwd.2", 46, 19), ev("fusion.5", 65, 13),
    ev("flash_bwd.3", 78, 31), ev("fusion.6", 109, 4),
    ev("fusion.7", 113, 9), ev("fusion.8", 122, 2),
]
MODULES = [ev("jit_step_fn(123)", 0, 130)]
# microseconds a step, by metric
READS = {
    "attn_proj_ms": 7 + 5 + 9, "attn_prep_ms": 3 + 4, "mlp_ms": 11 + 13,
    "attn_bwd_kernel_ms": 31, "attn_fwd_recompute_kernel_ms": 19,
    # the accepted readers on the same step
    "attn_fwd_kernel_ms": 20, "attn_bwd_dkv_kernel_ms": 19 + 31,
    "attn_bwd_dq_kernel_ms": 0,
}


def device(ops=STEP, modules=MODULES):
    return {"ops": list(ops), "modules": list(modules), "opcodes": {}}


class FakeRun:
    step_module_hint = "step_fn"
    hlo_text = HLO
    device_kind = "TPU v5 lite"
    rehearse = False

    def __init__(self, trace_dir, cell="gpt2m_dp1", **kw):
        found = harness.load_cell(cell)
        self.model_sizes = found["config"]["model"]
        self.traffic = found["traffic"]
        self.trace_dir = str(trace_dir)
        self.logged = []
        for key, value in kw.items():
            setattr(self, key, value)

    def log(self, text):
        self.logged.append(text)


@pytest.fixture
def loaded(monkeypatch):
    """One device's trace of the step above, as ``trace.load`` gives
    it; the returned list counts the loads."""
    loads = []

    def load(path):
        loads.append(path)
        return {0: device()}, [], []

    monkeypatch.setattr(scopes.trace, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(scopes.trace, "load", load)
    return loads


# -- the hand-made step ------------------------------------------------------

@pytest.mark.parametrize("op_name,want", [
    (f"{FWD}/attn/attn_proj/query/dot_general", ("forward", "attn_proj")),
    (f"{BWD}/attn/attn_proj/out/reduce_sum", ("backward", "attn_proj")),
    (f"{FWD}/attn/attn_prep/q_norm/rsqrt", ("forward", "attn_prep")),
    (f"{BWD}/attn/attn_prep/transpose", ("backward", "attn_prep")),
    (f"{AGAIN}/attn/attn_prep/concatenate", ("backward", "attn_prep")),
    # a kernel call stands outside both: layer `attn`, as ever
    (f"{FWD}/attn/flash_fwd/pallas_call", ("forward", "attn")),
    (f"{AGAIN}/attn/flash_fwd/pallas_call", ("backward", "attn")),
    (f"{BWD}/attn/flash_bwd/pallas_call", ("backward", "attn")),
    # the plain-XLA attention's own operations stay in `attn`
    (f"{FWD}/attn/jit(_where)/select_n", ("forward", "attn")),
    (f"{FWD}/mlp/fc1/dot_general", ("forward", "mlp")),
    # a routed MLP's scopes come first under `mlp`, as before
    (f"{FWD}/mlp/moe_experts/mul", ("forward", "moe_experts")),
])
def test_classify_knows_attentions_two_scopes(op_name, want):
    assert scopes.classify(op_name) == want


def test_named_calls_are_found_by_op_name_or_stem():
    assert kernel_names.kernel_names() == ("flash_fwd", "flash_bwd") == (
        program.FLASH_FWD, program.FLASH_BWD)
    assert kernel_names.named_calls(HLO) == {
        "flash_fwd.1": ("forward", "attn", "flash_fwd"),
        "flash_fwd.2": ("backward", "attn", "flash_fwd"),
        "flash_bwd.3": ("backward", "attn", "flash_bwd")}
    # the name lost from the op_name (the compiler's own metadata), the
    # instruction still carries it as its stem; a Mosaic call of another
    # name (the compiler's ragged dot) and a fusion named like a kernel
    # are none
    text = HLO.replace(f"/{program.FLASH_BWD}/pallas_call", "/pallas_call") \
        + (f'  %ragged-dot-none.4 = bf16[8,128]{{1,0}} {CALL}'
           'ragged-dot-none"}\n') + fusion(
            "flash_bwd.5", f"{BWD}/attn/flash_bwd/mul") + "\n"
    assert kernel_names.named_calls(text) == kernel_names.named_calls(HLO)


@pytest.mark.parametrize("metric", READS)
def test_reader_on_the_hand_made_step(metric, tmp_path, loaded):
    run = FakeRun(tmp_path)
    value = harness.load_reader(metric)(run)
    assert value is not None  # 0.0 is a reading
    assert value * 1000 == pytest.approx(READS[metric], abs=1e-9)


def test_the_named_kernels_add_up_to_what_arity_reads(tmp_path, loaded):
    run = FakeRun(tmp_path)
    read = {m: harness.load_reader(m)(run) for m in READS}
    # a `flash_fwd` call in the backward phase is told from a
    # `flash_bwd` call and from a forward `flash_fwd`, which arity and
    # phase alone cannot do
    assert read["attn_bwd_kernel_ms"] + read[
        "attn_fwd_recompute_kernel_ms"] == pytest.approx(
        read["attn_bwd_dkv_kernel_ms"])
    assert read["attn_fwd_recompute_kernel_ms"] != read["attn_fwd_kernel_ms"]
    # and with the projections and the layout work they are the whole
    # of attention but for what the bare `attn` keeps (2 us here)
    whole = scopes.read(run, lambda phase, layer, kernel: layer in (
        "attn", program.ATTN_PROJ, program.ATTN_PREP))
    assert (read["attn_proj_ms"] + read["attn_prep_ms"]
            + read["attn_fwd_kernel_ms"] + read["attn_bwd_dkv_kernel_ms"]
            + 0.002) == pytest.approx(whole)
    # the trace was loaded once by each pass and kept on the run
    assert len(loaded) == 2
    assert sum("kernel names:" in text for text in run.logged) == 1
    assert "backward/flash_bwd x1, backward/flash_fwd x1, " \
        "forward/flash_fwd x1" in "".join(run.logged)


def test_nothing_rematerialised_reads_exactly_zero(tmp_path, loaded):
    text = "\n".join(line for line in HLO.splitlines()
                     if "%flash_fwd.2 " not in line)
    run = FakeRun(tmp_path, hlo_text=text)
    assert harness.load_reader("attn_fwd_recompute_kernel_ms")(run) == 0.0
    assert harness.load_reader("attn_bwd_kernel_ms")(run) * 1000 == \
        pytest.approx(31)


def test_two_steps_two_devices_report_the_worst_device(
        tmp_path, monkeypatch):
    two = STEP + [(n, s + 130 * US, d) for n, s, d in STEP]
    mods = MODULES + [ev("jit_step_fn(123)", 130, 130)]
    slow = device([(n, 2 * s, 2 * d) for n, s, d in two],
                  [(n, 2 * s, 2 * d) for n, s, d in mods])
    monkeypatch.setattr(scopes.trace, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(scopes.trace, "load", lambda path: (
        {0: device(two, mods), 1: slow}, [], []))
    run = FakeRun(tmp_path)
    assert {d: len(s) for d, s in kernel_names.by_name(run).items()} == {
        0: 2, 1: 2}
    assert harness.load_reader("attn_bwd_kernel_ms")(run) * 1000 == \
        pytest.approx(62)


# -- the two shares of a peak ------------------------------------------------

TABLES = {0: [{("forward", "attn_proj", None): 33.77e6,
               ("backward", "attn_proj", None): 57.53e6,
               ("forward", "mlp", None): 43.19e6,
               ("backward", "mlp", None): 75.41e6}]}


@pytest.mark.parametrize("cell,metric,measured,least_ms,operations,nbytes", [
    ("gpt2m_dp1", "attn_proj_roofline", 91.3, 50.231496,
     9_895_604_649_984, 19_931_332_608),
    ("gpt2m_dp1", "mlp_roofline", 118.6, 100.462991,
     19_791_209_299_968, 25_367_150_592),
    ("bertl_s128", "attn_proj_roofline", 91.3, 40.813090,
     8_040_178_778_112, 16_307_453_952),
    ("bertl_s512", "mlp_roofline", 118.6, 81.626180,
     16_080_357_556_224, 20_837_302_272),
    # 16,384 positions x 6 layers, 32 heads of 128 over 4
    (ROUTED_CELL, "attn_proj_roofline", 91.3, 56.510433,
     11_132_555_231_232, 10_947_133_440),
])
def test_roofline_readers(cell, metric, measured, least_ms, operations,
                          nbytes, tmp_path):
    run = FakeRun(tmp_path, cell, scope_tables=TABLES)
    share = harness.load_reader(metric)(run)
    assert share == pytest.approx(100 * least_ms / measured, rel=1e-6)
    assert 0 < share < 100
    # the log line states operations, bytes and the least time, as
    # `attn_kernel_roofline`'s does
    (said,) = run.logged
    assert f"{float(operations):.4g} operations" in said
    assert f"{float(nbytes):.4g} bytes" in said
    assert f"least {least_ms:.3f} ms (compute-bound)" in said
    assert f"{measured:.3f} ms measured" in said


def test_no_dense_mlp_no_share_of_its_peak(tmp_path):
    run = FakeRun(tmp_path, ROUTED_CELL, scope_tables={0: [{
        ("forward", "mlp", None): 5e6}]})
    assert harness.load_reader("mlp_ms")(run) == pytest.approx(5.0)
    assert harness.load_reader("mlp_roofline")(run) is None
    assert run.logged == []


@pytest.mark.parametrize("config", ["gpt2-medium", "bert-large",
                                    "sdar-30b-a3b-chat"])
def test_the_products_are_what_flops_counts(config):
    """The rooflines' operations are ``flops.py``'s own counts, so that
    ``attn_proj`` + ``mlp`` cannot drift from what ``mfu_pct`` counts
    as ``blocks``; the weights' shapes the bytes come from multiply out
    to the same counts."""
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    model = harness.load_json(ROOT, entry["file"])["model"]
    cell = next(w for w in BENCH["workloads"] if w["config"] == config)
    traffic = harness.load_cell(cell["name"])["traffic"]
    assert sum(k * n for k, n in attn_proj_roofline.products(model)) == \
        flops.projection_macs(model)
    assert sum(k * n for k, n in mlp_roofline.products(model)) == \
        flops.mlp_macs(model)[0]
    blocks = flops.forward_flops_per_token(model, traffic)["blocks"]
    if not model.get("num_experts"):
        assert 2 * model["num_layers"] * (
            flops.projection_macs(model) + flops.mlp_macs(model)[0]) \
            == blocks == 603_979_776
    else:  # the routed cell: the projections' part of its blocks
        dense, routed = flops.mlp_macs(model)
        assert 2 * 2 * model["num_layers"] * (
            flops.projection_macs(model) + routed) == blocks


def test_latent_projections_read_no_share_rather_than_a_wrong_one(tmp_path):
    """No cell has latent attention; the reader knows the plain
    projections' shapes alone and says nothing of any others."""
    model = harness.load_json(
        ROOT, "tests", "benchmarks", "data",
        "latent_shared_expert_share.json")["body"]["model"]
    assert "kv_lora_rank" in model
    assert attn_proj_roofline.products(model) is None
    run = FakeRun(tmp_path, model_sizes=model, scope_tables={0: [{
        ("forward", "attn_proj", None): 5e6}]})
    assert harness.load_reader("attn_proj_ms")(run) == pytest.approx(5.0)
    assert harness.load_reader("attn_proj_roofline")(run) is None
    assert run.logged == []


# -- nothing to read ---------------------------------------------------------

@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_without_a_tpu_plane_reads_nothing(metric, tmp_path):
    # no trace file at all, as in an untraced run
    run = FakeRun(tmp_path)
    assert harness.load_reader(metric)(run) is None
    # a trace with no TPU plane, as a rehearsal on the CPU leaves
    run = FakeRun(tmp_path, rehearse=True)
    os.makedirs(tmp_path / "plugins" / "profile" / "t0")
    (tmp_path / "plugins" / "profile" / "t0" / "x.xplane.pb").write_bytes(
        b"")
    assert harness.load_reader(metric)(run) is None
    assert run.logged == []


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_on_a_program_without_the_names(metric, tmp_path, loaded,
                                               monkeypatch):
    # the parent of PR 41: scopes, but none of attention's and no
    # kernel names. The readers of a name print nothing and do not
    # raise; the dense MLP is Flax's own `mlp` and reads as it is
    old = types.SimpleNamespace(**{
        k: v for k, v in vars(program).items() if k.isupper()
        and k not in ("ATTN_PROJ", "ATTN_PREP", "FLASH_FWD", "FLASH_BWD")})
    old.LAYER_SCOPES = (program.MOE_DISPATCH, program.MOE_EXPERTS)
    monkeypatch.setattr(scopes, "program", old)
    scopes.classify.cache_clear()
    try:
        value = harness.load_reader(metric)(FakeRun(tmp_path))
    finally:
        monkeypatch.undo()
        scopes.classify.cache_clear()
    if metric in DENSE_ONLY:
        assert value is not None
    else:
        assert value is None
    # a program from before its scopes had names at all: nothing
    monkeypatch.setattr(scopes, "program", None)
    assert harness.load_reader(metric)(FakeRun(tmp_path)) is None


def test_every_new_reader_takes_a_bare_run():
    run = harness.Run(
        started=time.perf_counter(), workload="bare", chips=1,
        traffic=harness.load_cell("gpt2m_dp1")["traffic"],
        model_sizes=harness.load_cell("gpt2m_dp1")["config"]["model"],
        seed=0, seconds=10, trace=True, rehearse=False)
    run.device_kind = "TPU v5 lite"
    assert [harness.load_reader(m)(run) for m in NEW_METRICS] == [
        None] * len(NEW_METRICS)


# -- the entries -------------------------------------------------------------

@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_entry(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "tokens_per_s_per_chip"
    assert (entry["unit"], entry["better"]) == (
        ("%", "higher") if metric in ROOFLINES else ("ms/step", "lower"))
    assert entry["layer"] == ("kernels" if metric in BY_NAME else "model")
    if metric in DENSE_ONLY:
        assert entry["workloads"] == DENSE_CELLS
    else:
        assert "workloads" not in entry  # read in every cell
    assert os.path.exists(os.path.join(
        harness.HERE, "layer_metrics", metric + ".py"))
    reader = sys.modules[harness.load_reader(metric).__module__]
    # a rehearsal prints none of them
    assert not getattr(reader, "PLATFORM_FREE", False)
    assert entry["layer"] in reader.__doc__.split(":")[0]
    # and each cell is offered the reader where it has the layer
    for cell in CELLS:
        offered = {m["name"] for m in harness.load_cell(cell)["per_layer"]}
        assert (metric in offered) == (
            cell != ROUTED_CELL or metric not in DENSE_ONLY)


def test_the_entries_keep_their_order_and_none_is_one_cells_alone():
    """By name and relative order; where they stand among the other
    entries is for no test of this file to hold."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert [n for n in names if n in NEW_METRICS] == [
        "attn_proj_ms", "attn_proj_roofline", "attn_prep_ms", "mlp_ms",
        "mlp_roofline", "attn_bwd_kernel_ms", "attn_fwd_recompute_kernel_ms"]
    assert not [m["name"] for m in BENCH["per_layer"]
                if m["name"] in NEW_METRICS
                and m.get("workloads") == [ROUTED_CELL]]


# -- a rehearsal prints no device metric -------------------------------------

# `run.py` with its trace under a directory of the test's own: another
# file's traced rehearsal of the same cell, run beside this one by
# another worker, would clear `.bench_trace/<cell>` under it
REHEARSE_ELSEWHERE = (
    f"import sys; sys.path.insert(0, {ROOT!r}); "
    "from benchmarks import harness, run; "
    "harness.TRACE_ROOT = sys.argv.pop(1); sys.exit(run.main())")


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_prints_none_of_them(cell, tmp_path):
    chips = next(w["chips"] for w in BENCH["workloads"]
                 if w["name"] == cell)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    done = subprocess.run(
        [sys.executable, "-c", REHEARSE_ELSEWHERE, str(tmp_path),
         "--workload", cell, "--seed", "41", "--seconds", "1", "--trace",
         "1", "--rehearse"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == {"allreduce_ops_per_step",
                                    "allreduce_mib_per_step"}
    assert "kernel names:" not in done.stdout
