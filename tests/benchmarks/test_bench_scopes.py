"""The reduction of a traced step by the program's scopes
(``benchmarks/scopes.py``) and the readers over it, on hand-made HLO
text and events with known answers, and on one step recorded on the
chip."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness, scopes  # noqa: E402
from horovod_tpu.utils import scopes as program  # noqa: E402

US = 1000  # the events below are written in microseconds
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
NEW_METRICS = (
    "forward_ms", "backward_ms", "optimizer_ms", "bucket_pack_unpack_ms",
    "loss_head_ms", "norm_ms", "attn_fwd_kernel_ms",
    "attn_bwd_dq_kernel_ms", "attn_bwd_dkv_kernel_ms")


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


# real op_name strings, from the tiny steps lowered on the CPU and the
# two-layer step compiled for a described v5e (PR 24)
@pytest.mark.parametrize("op_name,want", [
    ("jit(step_fn)/shard_map/jvp(Transformer)/block_3/attn/query/"
     "dot_general", ("forward", "attn")),
    ("jit(step_fn)/jvp(Transformer)/block_0/attn/pallas_call",
     ("forward", "attn")),
    ("jit(step_fn)/transpose(jvp(Transformer))/block_1/attn/pallas_call",
     ("backward", "attn")),
    ("jit(step_fn)/shard_map/transpose(jvp(Transformer))/block_3/ln_mlp/"
     "mul", ("backward", "norm")),
    ("jit(step_fn)/jvp(Transformer)/block_0/ln_attn/div",
     ("forward", "norm")),
    ("transpose(jvp(Transformer))/ln_final/reduce_sum",
     ("backward", "norm")),
    ("jit(step_fn)/jvp(Transformer)/block_7/mlp/fc1/dot_general",
     ("forward", "mlp")),
    ("jit(step_fn)/transpose(jvp(Transformer))/block_7/mlp/fc2/"
     "reduce_sum", ("backward", "mlp")),
    ("jit(step_fn)/jvp(Transformer)/tok_emb/jit(_take)/gather",
     ("forward", "embed")),
    ("jit(step_fn)/transpose(jvp(Transformer))/tok_emb/jit(_take)/"
     "scatter-add", ("backward", "embed")),
    # position embedding and residual adds have no module of their own
    ("jit(step_fn)/jvp(Transformer)/gather", ("forward", "other")),
    ("jit(step_fn)/jvp(Transformer)/block_2/add", ("forward", "other")),
    # the fused cross entropy: its scope is the outermost, so the
    # transforms wrap it
    ("jit(step_fn)/shard_map/jvp(loss_head)/while/body/closed_call/"
     "dot_general", ("forward", "loss_head")),
    ("jit(step_fn)/shard_map/transpose(jvp(loss_head))/while",
     ("backward", "loss_head")),
    # the dense head: inside the module, and before the embedding it
    # shares its table with
    ("jit(step_fn)/jvp(Transformer)/loss_head/tok_emb.attend/"
     "dot_general", ("forward", "loss_head")),
    ("jit(step_fn)/transpose(jvp(Transformer))/loss_head/tok_emb.attend/"
     "dot_general", ("backward", "loss_head")),
    ("jit(step_fn)/transpose(jvp(loss_head))/jit(take_along_axis)/"
     "scatter-add", ("backward", "loss_head")),
    # an empty scope: differentiated, named by nothing (the job's own
    # transpose of the embedding)
    ("jit(step_fn)/shard_map/jvp()/transpose", ("forward", "other")),
    ("jit(step_fn)/transpose(jvp())/mul", ("backward", "other")),
    ("jit(step_fn)/shard_map/hvd_pack/concatenate",
     ("optimizer", "hvd_pack")),
    ("jit(step_fn)/shard_map/hvd_allreduce/mul",
     ("optimizer", "hvd_allreduce")),
    ("jit(step_fn)/shard_map/hvd_allreduce/psum",
     ("optimizer", "hvd_allreduce")),
    ("jit(step_fn)/shard_map/hvd_unpack/dynamic_slice",
     ("optimizer", "hvd_unpack")),
    ("jit(step_fn)/hvd_inner_update/integer_pow",
     ("optimizer", "hvd_inner_update")),
    # apply_updates and the loss's psum: the step function's own lines
    ("jit(step_fn)/add", ("optimizer", "other")),
    ("jit(step_fn)/shard_map/psum", ("optimizer", "other")),
    # an argument's name is no scope
    ("p['block_3']['attn']['key']['kernel']", ("optimizer", "other")),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name) == want


def old_classify(op_name):
    """``scopes.classify`` as it stood before it took a layer's scope
    from the program (PR 24's rule, word for word)."""
    import re
    if "transpose(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    else:
        phase = "optimizer"
    parts = set(re.split(r"[/()]", op_name))
    for scope in ("loss_head", "hvd_pack", "hvd_allreduce", "hvd_unpack",
                  "hvd_inner_update"):
        if scope in parts:
            return phase, scope
    if parts.intersection(("ln_attn", "ln_mlp", "ln_final")):
        return phase, "norm"
    for layer in ("attn", "mlp"):
        if layer in parts:
            return phase, layer
    if any(p.startswith("tok_emb") for p in parts):
        return phase, "embed"
    return phase, "other"


@pytest.fixture
def layer_scopes(monkeypatch):
    """Puts names into the program's ``LAYER_SCOPES`` for one test."""
    def put(*names):
        monkeypatch.setattr(program, "LAYER_SCOPES", names, raising=False)
        scopes.classify.cache_clear()
    yield put
    monkeypatch.undo()
    scopes.classify.cache_clear()


def test_a_layers_scope_comes_from_the_program(layer_scopes):
    # names the program does not list (it lists the routed MLP's and
    # attention's own since PR 33 and PR 41)
    assert not {"ssm_scan", "ssm_gate"} & set(program.LAYER_SCOPES)
    under_mlp = ("jit(step_fn)/shard_map/jvp(Transformer)/block_0/mlp/"
                 "ssm_scan/dot_general")
    gate = ("jit(step_fn)/transpose(jvp(Transformer))/block_0/mlp/"
            "ssm_gate/reduce_sum")
    # a name the program does not list: Flax's module names, as ever
    assert scopes.classify(under_mlp) == ("forward", "mlp")
    layer_scopes("ssm_gate", "ssm_scan")
    assert scopes.classify(under_mlp) == ("forward", "ssm_scan")
    assert scopes.classify(gate) == ("backward", "ssm_gate")
    # after the five scopes there are: the loss head keeps what is its
    assert scopes.classify(
        "jit(step_fn)/jvp(loss_head)/ssm_scan/dot_general") == (
        "forward", "loss_head")
    # and a reader of the layer is one line over scopes.read
    found = scopes.tables({0: device()}, "step_fn", HLO.replace(
        "block_0/ln_attn/mul", "block_0/mlp/ssm_scan/mul"))
    assert us(found, lambda p, l, k: l == "ssm_scan") == 10
    assert us(found, lambda p, l, k: l == "norm") == 0


def test_classify_answers_as_before_for_every_recorded_op_name(
        layer_scopes):
    d = harness.load_json(ROOT, "tests", "benchmarks", "data",
                          "gpt2m_dp1_scoped_step.json")
    recorded = {d["paths"][enc[0]] + "/" + enc[1]
                for enc in d["op_names"] if enc is not None}
    assert len(recorded) > 300
    for names in ((), ("moe_router", "moe_experts")):
        layer_scopes(*names)
        assert {n: scopes.classify(n) for n in recorded} == {
            n: old_classify(n) for n in recorded}


def test_scope_names_are_the_programs():
    assert (program.LOSS_HEAD, program.HVD_PACK, program.HVD_ALLREDUCE,
            program.HVD_UNPACK, program.HVD_INNER_UPDATE) == (
        "loss_head", "hvd_pack", "hvd_allreduce", "hvd_unpack",
        "hvd_inner_update")


# a fusion, a while with its body, a tuple-valued and a single-valued
# custom call, a ROOT, an instruction with no metadata
HLO = """\
HloModule jit_step_fn, is_scheduled=true

%fused_computation.3 (param_0.1: bf16[8,128]) -> bf16[8,128] {
  %param_0.1 = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %convert.2 = f32[8,128]{1,0:T(8,128)} convert(%param_0.1), metadata={op_name="jit(step_fn)/jvp(Transformer)/block_0/attn/query/dot_general"}
  ROOT %multiply.9 = bf16[8,128]{1,0:T(8,128)(2,1)} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step_fn)/jvp(Transformer)/block_0/ln_attn/mul" stack_frame_id=4}
}

%fused_computation.9 (param_0.2: f32[8,128]) -> f32[8,128] {
  %param_0.2 = f32[8,128]{1,0:T(8,128)} parameter(0)
  %multiply.4 = f32[8,128]{1,0:T(8,128)} multiply(%param_0.2, %param_0.2), metadata={op_name="jit(step_fn)/jvp(Transformer)/ln_final/mul"}
  ROOT %dot.5 = f32[8,128]{1,0:T(8,128)} dot(%multiply.4, %param_0.2), metadata={op_name="jit(step_fn)/jvp(loss_head)/while/body/closed_call/dot_general"}
}

%body.1 (arg: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %arg = (s32[], f32[8,128]{1,0}) parameter(0)
  %fusion.21 = f32[8,128]{1,0:T(8,128)} fusion(%arg), kind=kOutput, calls=%fused_computation.9, metadata={op_name="jit(step_fn)/jvp(loss_head)/while/body/closed_call/dot_general"}
  ROOT %tuple.4 = (s32[], f32[8,128]{1,0}) tuple(%arg, %fusion.21)
}

ENTRY %main.7 (p: bf16[8,128]) -> (bf16[8,128], f32[1]) {
  %p = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="p['ln']['scale']"}
  %fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%p), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step_fn)/jvp(Transformer)/block_0/ln_attn/mul" stack_frame_id=4}
  %attn.6 = (bf16[2,2,128,64]{3,2,1,0:T(8,128)(2,1)}, f32[2,2,1,128]{3,2,1,0:T(1,128)}) custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(Transformer)/block_0/attn/pallas_call"}
  %attn.8 = bf16[2,2,128,64]{3,2,1,0:T(8,128)(2,1)} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/transpose(jvp(Transformer))/block_0/attn/pallas_call"}
  %attn.9 = (bf16[2,2,128,64]{3,2,1,0:T(8,128)(2,1)}, bf16[2,2,128,64]{3,2,1,0:T(8,128)(2,1)S(1)}) custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/transpose(jvp(Transformer))/block_0/attn/pallas_call"}
  %while.28 = (s32[], f32[8,128]{1,0}) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(step_fn)/jvp(loss_head)/while"}
  %copy-start.5 = (bf16[8,128]{1,0:T(8,128)(2,1)S(1)}, bf16[8,128]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%fusion.3)
  %copy-done.5 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.5)
  %psum.91 = f32[1024]{0:T(1024)} all-reduce(%fusion.40), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_0.1, metadata={op_name="jit(step_fn)/shard_map/hvd_allreduce/psum"}
  ROOT %tuple.9 = (bf16[8,128]{1,0:T(8,128)(2,1)}, f32[1]{0:T(128)}) tuple(%copy-done.5, %psum.91)
}
"""


@pytest.mark.parametrize("name,want", [
    ("fusion.3", "jit(step_fn)/jvp(Transformer)/block_0/ln_attn/mul"),
    ("multiply.9", "jit(step_fn)/jvp(Transformer)/block_0/ln_attn/mul"),
    ("while.28", "jit(step_fn)/jvp(loss_head)/while"),
    ("fusion.21",
     "jit(step_fn)/jvp(loss_head)/while/body/closed_call/dot_general"),
    ("attn.9",
     "jit(step_fn)/transpose(jvp(Transformer))/block_0/attn/pallas_call"),
    ("p", "p['ln']['scale']"),
    ("copy-done.5", None),  # the compiler's own: no metadata
    ("tuple.9", None),      # the ROOT
    ("main.7", None),       # a computation is no instruction
])
def test_op_names(name, want):
    assert scopes.op_names(HLO).get(name) == want


def test_held_lists_what_a_fusion_holds():
    assert scopes.held(HLO) == {
        "fusion.3": {("forward", "norm"), ("forward", "attn")},
        "fusion.21": {("forward", "loss_head"), ("forward", "norm")}}


def test_tuple_valued_tells_dkv_from_dq():
    tuples = scopes.tuple_valued(HLO)
    assert {"attn.6", "attn.9", "while.28", "tuple.9", "tuple.4",
            "copy-start.5"} <= tuples
    assert not {"attn.8", "fusion.3", "copy-done.5", "psum.91"} & tuples
    mosaic = {"attn.6", "attn.8", "attn.9"}
    kinds = {n: scopes.kernel_kind(
        n, *scopes.classify(scopes.op_names(HLO)[n]), mosaic, tuples)
        for n in mosaic | {"fusion.3"}}
    assert kinds == {"attn.6": "attn_fwd", "attn.8": "attn_bwd_dq",
                     "attn.9": "attn_bwd_dkv", "fusion.3": None}
    # a Mosaic call of another layer is none of the three
    assert scopes.kernel_kind("norm.1", "forward", "norm", {"norm.1"},
                              set()) is None


# one 100 us step of the HLO above: the norm [0,10], the forward kernel
# [10,30], the fused cross entropy's while [30,50] whose body's fusion
# runs twice for 8 us (so 4 us are the while's own), dq [50,60], dkv
# [60,75], a copy-done with no name [75,77] right before the all-reduce
# [77,90], nothing after 90
STEP = [
    ev("fusion.3", 0, 10),
    ev("attn.6", 10, 20),
    ev("while.28", 30, 20),
    ev("fusion.21", 31, 8),
    ev("fusion.21", 40, 8),
    ev("attn.8", 50, 10),
    ev("attn.9", 60, 15),
    ev("copy-done.5", 75, 2),
    ev("psum.91", 77, 13),
]
MODULES = [ev("jit_step_fn(123)", 0, 100)]
OPCODES = {"psum.91": "all-reduce"}


def device(ops=STEP, modules=MODULES, scale=1):
    return {"ops": [(n, s * scale, d * scale) for n, s, d in ops],
            "modules": [(n, s * scale, d * scale) for n, s, d in modules],
            "opcodes": OPCODES}


def us(by_device, select, notes=False):
    return scopes.milliseconds(by_device, select, notes) * 1000


@pytest.mark.parametrize("select,want", [
    (lambda p, l, k: p == "forward", 10 + 20 + 20),
    (lambda p, l, k: p == "backward", 10 + 15),
    # the copy-done borrows the all-reduce's scope; the all-reduce
    # itself is in no phase
    (lambda p, l, k: p == "optimizer", 2),
    (lambda p, l, k: l == "hvd_allreduce", 2),
    # the while's own 4 us and its body's 16, counted once
    (lambda p, l, k: l == "loss_head", 20),
    # the norm's own fusion, and not the fusion that holds a norm's
    # operation under another name
    (lambda p, l, k: l == "norm", 10),
    (lambda p, l, k: k == "attn_fwd", 20),
    (lambda p, l, k: k == "attn_bwd_dq", 10),
    (lambda p, l, k: k == "attn_bwd_dkv", 15),
    (lambda p, l, k: k is not None, 45),
    (lambda p, l, k: l in ("hvd_pack", "hvd_unpack"), 0),
    # every entry together is the busy time less the collective's
    (lambda p, l, k: True, 90 - 13),
])
def test_one_step_by_scope(select, want):
    found = scopes.tables({0: device()}, "step_fn", HLO)
    assert list(found) == [0] and len(found[0]) == 1
    assert us(found, select) == pytest.approx(want)


@pytest.mark.parametrize("select,want", [
    (lambda p, l, k: (p, l, k) == scopes.BORROWED, 2),
    # the norm's fusion holds an operation of the attention's query
    # projection: at most its 10 us belong there; the loss head's body
    # fusion holds one of the final norm: at most its 2 x 8 us
    (lambda p, l, k: (p, l) == ("holds forward", "attn"), 10),
    (lambda p, l, k: (p, l) == ("holds forward", "norm"), 16),
    (lambda p, l, k: True, 2 + 10 + 16),
])
def test_one_step_notes(select, want):
    found = scopes.tables({0: device()}, "step_fn", HLO)
    assert us(found, select, notes=True) == pytest.approx(want)


def test_two_devices_two_steps_report_the_worst_device():
    # device 1 runs everything twice as slowly, but for the dq kernel
    # of its second step, which is 8 us shorter: the median of two
    # steps is their mean
    two = STEP + [(n, s + 100 * US, d) for n, s, d in STEP]
    mods = MODULES + [ev("jit_step_fn(123)", 100, 100)]
    slow = device(two, mods, scale=2)
    slow["ops"] = [
        (n, s, d - (8 * US if n == "attn.8" and s > 200 * US else 0))
        for n, s, d in slow["ops"]]
    found = scopes.tables({0: device(two, mods), 1: slow}, "step_fn", HLO)
    assert {d: len(s) for d, s in found.items()} == {0: 2, 1: 2}
    assert us(found, lambda p, l, k: k == "attn_bwd_dq") == 20 - 4
    assert us(found, lambda p, l, k: l == "loss_head") == 40
    one = scopes.tables({0: device(two, mods)}, "step_fn", HLO)
    assert us(one, lambda p, l, k: k == "attn_bwd_dq") == 10


def test_unnamed_instruction_at_the_end_counts_with_the_last_named():
    ops = [ev("fusion.3", 0, 10), ev("copy-done.5", 10, 5)]
    found = scopes.tables({0: device(ops)}, "step_fn", HLO)
    assert us(found, lambda p, l, k: l == "norm") == 15
    assert us(found, lambda p, l, k: (p, l, k) == scopes.BORROWED,
              notes=True) == 5


class FakeRun:
    step_module_hint = "step_fn"
    hlo_text = HLO

    def __init__(self, trace_dir):
        self.trace_dir = str(trace_dir)
        self.logged = []

    def log(self, text):
        self.logged.append(text)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_without_a_tpu_plane_reads_nothing(metric, tmp_path):
    # no trace file at all, as in an untraced run
    run = FakeRun(tmp_path)
    assert harness.load_reader(metric)(run) is None
    # a trace with no TPU plane, as a rehearsal on the CPU leaves
    run = FakeRun(tmp_path)
    os.makedirs(tmp_path / "plugins" / "profile" / "t0")
    (tmp_path / "plugins" / "profile" / "t0" / "x.xplane.pb").write_bytes(
        b"")
    assert harness.load_reader(metric)(run) is None
    assert run.logged == []


READS = {
    "forward_ms": 50, "backward_ms": 25, "optimizer_ms": 2,
    "bucket_pack_unpack_ms": 0, "loss_head_ms": 20, "norm_ms": 10,
    "attn_fwd_kernel_ms": 20, "attn_bwd_dq_kernel_ms": 10,
    "attn_bwd_dkv_kernel_ms": 15,
}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_on_a_loaded_trace(metric, tmp_path, monkeypatch):
    monkeypatch.setattr(scopes.trace, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(scopes.trace, "load",
                        lambda path: ({0: device()}, [], []))
    run = FakeRun(tmp_path)
    value = harness.load_reader(metric)(run)
    assert value * 1000 == pytest.approx(READS[metric])
    assert value is not None  # 0.0 at one chip is a reading, not None
    # the trace is loaded once a run, and the split is left beside it
    monkeypatch.setattr(scopes.trace, "load", None)
    assert harness.load_reader("forward_ms")(run) * 1000 == \
        pytest.approx(50)
    assert len(run.logged) == 1 and "scopes:" in run.logged[0]
    left = json.load(open(tmp_path / "scopes.json"))
    assert left["op_names"]["while.28"].endswith("jvp(loss_head)/while")
    assert left["tuple_valued"] == ["attn.6", "attn.9"]
    assert left["held"]["fusion.3"] == [["forward", "attn"],
                                        ["forward", "norm"]]


def test_reader_reads_nothing_from_a_program_without_scope_names(
        tmp_path, monkeypatch):
    # the parent of PR 24 has no horovod_tpu/utils/scopes.py: the new
    # benchmark files laid over it print none of these and do not raise
    monkeypatch.setattr(scopes, "program", None)
    monkeypatch.setattr(scopes.trace, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(scopes.trace, "load",
                        lambda path: ({0: device()}, [], []))
    for metric in NEW_METRICS:
        assert harness.load_reader(metric)(FakeRun(tmp_path)) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_entry(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "tokens_per_s_per_chip"
    assert (entry["unit"], entry["better"]) == ("ms/step", "lower")
    assert "workloads" not in entry  # read in every cell
    reader = sys.modules[harness.load_reader(metric).__module__]
    # a rehearsal prints none of them
    assert not getattr(reader, "PLATFORM_FREE", False)
    assert entry["layer"] in reader.__doc__.split(":")[0]


# -- one step recorded on the chip -------------------------------------------

def recorded_step():
    """``tests/benchmarks/data/gpt2m_dp1_scoped_step.json``: names are
    numbered and each ``op_name`` is split at its last ``/`` into a
    numbered path and the primitive, and what a fusion holds is a list
    of numbered classes, to keep the file small."""
    d = harness.load_json(ROOT, "tests", "benchmarks", "data",
                          "gpt2m_dp1_scoped_step.json")
    names = d["names"]
    op_name = {
        names[i]: d["paths"][enc[0]] + "/" + enc[1]
        for i, enc in enumerate(d["op_names"]) if enc is not None}
    ops = [(names[i], s, dur) for i, s, dur in d["ops"]]
    inside = {names[int(i)]: {tuple(d["classes"][c]) for c in ids}
              for i, ids in d["held"].items()}
    window = (d["modules"][0][1], d["modules"][0][1] + d["modules"][0][2])
    table = scopes.step_table(ops, window, d["opcodes"], scopes.Compiled(
        op_name, set(d["tuple_valued"]), set(d["kernel_names"]), inside))
    return d, ops, op_name, table


def table_ms(table, select, notes=False):
    return scopes.milliseconds({0: [table]}, select, notes)


def test_recorded_chip_step_by_scope():
    d, ops, op_name, table = recorded_step()
    assert "gpt2m_dp1" in d["what"] and "TPU v5 lite" in d["what"]
    assert os.path.getsize(os.path.join(
        ROOT, "tests", "benchmarks", "data",
        "gpt2m_dp1_scoped_step.json")) < 400_000
    from benchmarks import trace
    busy = trace.total(trace.merge(trace.span_of(e) for e in ops)) / 1e6
    phase = {p: table_ms(table, lambda ph, l, k, p=p: ph == p)
             for p in scopes.PHASES}
    # 1. the phases (and the collectives, none at one chip) are the time
    # the device is busy
    assert d["opcodes"] == {}
    assert sum(phase.values()) == pytest.approx(busy, rel=1e-6)
    assert busy == pytest.approx(d["reported"]["device_busy_ms"],
                                 rel=2e-3)
    # 2. the three kernels are attn_kernel_ms
    kernels = {k: table_ms(table, lambda ph, l, kk, k=k: kk == k)
               for k in (scopes.KERNEL_FWD, scopes.KERNEL_DQ,
                         scopes.KERNEL_DKV)}
    assert sum(kernels.values()) == pytest.approx(
        d["reported"]["attn_kernel_ms"], rel=1e-3)
    assert all(v > 10 for v in kernels.values())
    # 24 layers, one call each a step
    calls = {}
    for name in d["kernel_names"]:
        kind = scopes.kernel_kind(
            name, *scopes.classify(op_name[name]),
            set(d["kernel_names"]), set(d["tuple_valued"]))
        calls[kind] = calls.get(kind, 0) + 1
    assert calls == {scopes.KERNEL_FWD: 24, scopes.KERNEL_DQ: 24,
                     scopes.KERNEL_DKV: 24}
    # 3. no bucket at one chip
    assert table_ms(table, lambda ph, l, k: l in (
        program.HVD_PACK, program.HVD_UNPACK, program.HVD_ALLREDUCE)) == 0
    # 4. what the run itself reported for this cell (median of six
    # steps) is what this one step gives
    for metric, select in [
            ("forward_ms", lambda ph, l, k: ph == "forward"),
            ("backward_ms", lambda ph, l, k: ph == "backward"),
            ("optimizer_ms", lambda ph, l, k: ph == "optimizer"),
            ("loss_head_ms", lambda ph, l, k: l == program.LOSS_HEAD),
            ("norm_ms", lambda ph, l, k: l == "norm")]:
        assert table_ms(table, select) == pytest.approx(
            d["reported"][metric], rel=5e-3, abs=0.02), metric
    # the fused cross entropy's two loops are the loss head
    assert 45 < d["reported"]["loss_head_ms"] < 55
    # instructions with no op_name (copy-done, slice-done) are a small
    # part, and every one of them found a neighbour
    assert 0 < table_ms(table, lambda *key: key == scopes.BORROWED,
                        notes=True) < 0.02 * busy
    # AdamW rides in fusions named for the backward matmuls, the norms
    # in fusions named for their neighbours: the notes say in how much
    # time, at most
    assert table_ms(table, lambda ph, l, k: (ph, l) == (
        "holds optimizer", program.HVD_INNER_UPDATE), notes=True) \
        > 10 * phase["optimizer"]
    assert table_ms(table, lambda ph, l, k: l == "norm", notes=True) \
        > 100 * d["reported"]["norm_ms"]
