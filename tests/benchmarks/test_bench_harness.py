"""The benchmark's data files resolve, its names hold to the contract,
and ``run.py --rehearse`` runs cells end to end on the CPU at the tiny
preset. No test here describes a TPU topology."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness, published  # noqa: E402

BENCH = harness.load_json(ROOT, "BENCHMARK.json")
# a BENCHMARK.json-shaped file with a configuration of a second family
# (RMSNorm, rotary, grouped-query, SwiGLU, a head of its own), cut in
# depth, with its reference, its traffic and one cell: what the harness
# has to take as data. No file under benchmarks/ knows a name of it
FIXTURE = os.path.join(ROOT, "tests", "benchmarks", "data", "fixture")
FIXTURE_BENCH = harness.load_json(FIXTURE, "BENCHMARK.json")
# an entry and a file's body (no cell): the public keys of a
# latent-attention, shared-expert model with a leading dense layer and
# a second prediction head, cut to one chip's share of eight
SHARE = harness.load_json(ROOT, "tests", "benchmarks", "data",
                          "latent_shared_expert_share.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
# every cell and configuration of both files, with the root it is under
ROOTED_CELLS = [pytest.param(root, w["name"], id=w["name"])
                for root, bench in ((ROOT, BENCH), (FIXTURE, FIXTURE_BENCH))
                for w in bench["workloads"]]
ROOTED_CONFIGS = [pytest.param(root, c, id=c["name"])
                  for root, bench in ((ROOT, BENCH),
                                      (FIXTURE, FIXTURE_BENCH))
                  for c in bench["configs"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert {w["chips"] for w in BENCH["workloads"]} <= {1, 4}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("root,cell", ROOTED_CELLS)
def test_cell_resolves_to_files(root, cell):
    found = harness.load_cell(cell, root)
    traffic, config = found["traffic"], found["config"]
    # the job is the harness's code; the reference comes with the data
    assert os.path.exists(os.path.join(
        harness.HERE, "jobs", traffic["job"] + ".py"))
    reference = harness.load_reference(config["family"], root)
    assert all(callable(getattr(reference, f))
               for f in ("arguments", "mean_loss", "nll_sum"))
    assert set(config["tiny"]) <= set(config["model"])
    assert set(traffic["tiny"]) <= set(traffic)
    names = {m["name"] for m in found["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert found["per_layer"]
    assert len(found["cell"]["why"]) <= 200
    # a cell runs the program's defaults: no knob in its data
    assert "HOROVOD_" not in json.dumps(traffic)
    # the loop's numbers define the metrics: they are the job's
    assert not {"chunk_steps", "warmup_steps", "min_steps",
                "traced_steps"} & set(traffic)


@pytest.mark.parametrize("root,cell", ROOTED_CELLS)
def test_model_is_built_from_the_config_file_as_written(root, cell):
    import dataclasses

    from benchmarks.jobs import dp_train
    found = harness.load_cell(cell, root)
    sizes, traffic = found["config"]["model"], found["traffic"]
    cfg = dp_train.make_model(sizes, traffic)[0]
    assert {k: v for k, v in dataclasses.asdict(cfg).items()
            if k in sizes} == sizes
    # a shorter sequence reads the first rows of the position table; a
    # longer one is refused, not given a longer table
    assert traffic["seq_len"] <= cfg.max_seq_len
    with pytest.raises(ValueError, match="max_seq_len"):
        dp_train.make_model(
            sizes, {**traffic, "seq_len": cfg.max_seq_len + 1})
    # the reference reads its arguments from the same group
    reference = harness.load_reference(found["config"]["family"], root)
    assert reference.arguments(sizes, traffic)["num_layers"] == \
        cfg.num_layers


@pytest.mark.parametrize("root,config", ROOTED_CONFIGS)
def test_config_file_holds_the_published_sizes(root, config):
    """The model group equals the source key for key, but for the keys
    ``reduced`` names, in the file and in the entry alike
    (``benchmarks/published.py``)."""
    body = harness.load_json(root, config["file"])
    assert config["file"].startswith("benchmarks/")
    published.check(config, body)
    assert [c["key"] for c in body["reduced"]] == config["reduced"]
    for cut in body["reduced"]:
        assert cut["why"] and cut["held"] < cut["published"]


def test_the_two_whole_configurations_pass_with_nothing_reduced():
    for name in ("gpt2-medium", "bert-large"):
        entry = next(c for c in BENCH["configs"] if c["name"] == name)
        body = harness.load_json(ROOT, entry["file"])
        assert body["reduced"] == entry["reduced"] == []
        pub, model = body["published"], body["model"]
        assert model["num_layers"] == pub.get(
            "n_layer", pub.get("num_hidden_layers")) == 24


def test_fixture_is_cut_in_depth_and_of_another_family():
    entry = FIXTURE_BENCH["configs"][0]
    body = harness.load_json(FIXTURE, entry["file"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert body["family"] != "transformer_lm"
    assert body["model"]["tie_embeddings"] is False
    assert (body["reduced"][0]["published"], body["reduced"][0]["held"],
            body["model"]["num_layers"]) == (6, 2, 2)
    # no file of the harness knows a name of the fixture
    names = [body["family"], entry["name"],
             FIXTURE_BENCH["workloads"][0]["name"],
             FIXTURE_BENCH["workloads"][0]["traffic"]]
    for base, _, files in os.walk(harness.HERE):
        for f in files:
            if f.endswith((".py", ".json")):
                text = open(os.path.join(base, f)).read()
                assert not [n for n in names if n in text], (f, names)


def _cut(key, published_value, held):
    return {"key": key, "published": published_value, "held": held,
            "why": "a test"}


def _fixture_variant(change):
    """The fixture's entry and file after ``change(entry, body)``."""
    entry = json.loads(json.dumps(FIXTURE_BENCH["configs"][0]))
    body = harness.load_json(FIXTURE, entry["file"])
    change(entry, body)
    return entry, body


def _share_variant(change):
    """The share's entry and body after ``change(entry, body)``."""
    entry, body = (json.loads(json.dumps(SHARE[k]))
                   for k in ("entry", "body"))
    change(entry, body)
    return entry, body


def _depth_differs(entry, body):
    body["model"]["num_layers"] = 1


def _width_listed(entry, body):
    body["hidden_size"] = body["model"]["hidden_size"] = 128
    body["model"]["mlp_ratio"] = 4.0
    body["reduced"].append(_cut("hidden_size", 256, 128))
    entry["reduced"].append("hidden_size")


def _experts_per_token_listed(entry, body):
    body["num_experts_per_tok"] = body["model"]["experts_per_token"] = 2
    body["reduced"].append(_cut("num_experts_per_tok", 8, 2))
    entry["reduced"].append("num_experts_per_tok")


def _count_without_deployment(entry, body):
    body["vocab_size"] = body["model"]["vocab_size"] = 256
    body["reduced"].append(_cut("vocab_size", 1024, 256))
    entry["reduced"].append("vocab_size")


def _entry_and_file_disagree(entry, body):
    entry["reduced"] = []


def _half_a_period(entry, body):
    body["num_hidden_layers"] = body["model"]["num_layers"] = 1
    body["reduced"][0]["held"] = 1


def _unknown_key(entry, body):
    body["sliding_window"] = 64
    body["reduced"].append(_cut("sliding_window", 128, 64))
    entry["reduced"].append("sliding_window")


def _recut(body, key, held):
    """``key`` of the share's body and its `reduced` entry hold
    ``held``."""
    body[key] = held
    next(c for c in body["reduced"] if c["key"] == key)["held"] = held


def _latent_width_changed(entry, body):
    body["model"]["kv_lora_rank"] = 256


def _head_width_listed(entry, body):
    body["v_head_dim"] = body["model"]["v_head_dim"] = 128
    body["reduced"].append(_cut("v_head_dim", 256, 128))
    entry["reduced"].append("v_head_dim")


def _expert_width_changed(entry, body):
    body["model"]["expert_mlp_dim"] = 768


def _router_cut_with_the_experts(entry, body):
    body["model"]["num_experts"] = 8


def _seven_experts(entry, body):
    _recut(body, "n_routed_experts", 7)
    body["model"]["experts_held"] = 7


def _vocabulary_under_an_eighth(entry, body):
    _recut(body, "vocab_size", 19359)
    body["model"]["vocab_size"] = 19359


def _three_layers_after_the_dense_one(entry, body):
    _recut(body, "num_hidden_layers", 4)
    body["model"]["num_layers"] = 4


def _depth_splits_a_period(entry, body):
    body["layer_period"] = 3  # 1 dense + 4 is one period and a third


def _dense_layer_left_out(entry, body):
    body["model"]["dense_layers"] = 0


def _shared_expert_listed(entry, body):
    body["n_shared_experts"] = body["model"]["shared_experts"] = 0
    body["reduced"].append(_cut("n_shared_experts", 1, 0))
    entry["reduced"].append("n_shared_experts")


def _second_head_left_out(entry, body):
    del body["model"]["mtp_layers"]


def _unknown_key_not_listed(entry, body):
    body["sliding_window"] = 4096


def _known_key_listed_as_not_held(entry, body):
    body["not_held"]["kv_lora_rank"] = "not a size"


def _not_held_without_a_reason(entry, body):
    body["not_held"]["rope_theta"] = ""


NEVER = "is never cut"
REFUSALS = [(_fixture_variant, *case) for case in [
    (_depth_differs, "num_hidden_layers", "does not name it|model group"),
    (_width_listed, "hidden_size", NEVER),
    (_experts_per_token_listed, "num_experts_per_tok", NEVER),
    (_count_without_deployment, "vocab_size", "deployment"),
    (_entry_and_file_disagree, "num_hidden_layers", "BENCHMARK.json"),
    (_half_a_period, "num_hidden_layers", "layer_period"),
    (_unknown_key, "sliding_window", "no row"),
]] + [(_share_variant, *case) for case in [
    (_latent_width_changed, "kv_lora_rank", "published 512"),
    (_head_width_listed, "v_head_dim", NEVER),
    (_expert_width_changed, "moe_intermediate_size", "published 1536"),
    (_router_cut_with_the_experts, "n_routed_experts",
     "published 64.*router"),
    (_seven_experts, "n_routed_experts", "floor.*8 experts"),
    (_vocabulary_under_an_eighth, "vocab_size", "floor.*19360"),
    (_three_layers_after_the_dense_one, "num_hidden_layers",
     "floor.*four layers"),
    (_depth_splits_a_period, "num_hidden_layers", "layer_period"),
    (_dense_layer_left_out, "first_k_dense_replace", "published 1"),
    (_shared_expert_listed, "n_shared_experts", NEVER),
    (_second_head_left_out, "num_nextn_predict_layers", "published 1"),
    (_unknown_key_not_listed, "sliding_window", "not_held"),
    (_known_key_listed_as_not_held, "kv_lora_rank", "no row knows"),
    (_not_held_without_a_reason, "rope_theta", "reason"),
]]


@pytest.mark.parametrize(
    "variant,change,key,why", REFUSALS,
    ids=[change.__name__ for _, change, _, _ in REFUSALS])
def test_a_cut_that_is_not_written_down_or_not_allowed_is_refused(
        variant, change, key, why):
    with pytest.raises(ValueError, match=f"key '{key}'.*({why})"):
        published.check(*variant(change))


def test_one_chips_share_of_a_latent_shared_expert_model_is_held():
    """The share's body passes as it is: depth cut to the dense layer
    and four expert layers, 8 of 64 experts held under a router of 64,
    an eighth of the vocabulary, every width and every mechanism's key
    as published, every other key of the source under ``not_held``.
    It is a body and an entry, no cell: no BENCHMARK.json names it."""
    entry, body = _share_variant(lambda entry, body: None)
    published.check(entry, body)
    model = body["model"]
    assert (model["num_experts"], model["experts_held"]) == (64, 8)
    assert [c["key"] for c in body["reduced"]] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    source = set(published.source_of(body))
    assert source == (source & published.KNOWN) | set(body["not_held"])
    for bench in (BENCH, FIXTURE_BENCH):
        assert entry["name"] not in json.dumps(bench)
    # the same file in its other form, a `published` group, where a cut
    # key holds the published value: held the same way
    grouped = {k: v for k, v in body.items() if k not in source}
    grouped["published"] = {
        **{k: body[k] for k in source},
        **{c["key"]: c["published"] for c in body["reduced"]}}
    published.check(entry, grouped)
    grouped["model"]["num_experts"] = 8
    with pytest.raises(ValueError, match="key 'n_routed_experts'"):
        published.check(entry, grouped)


@pytest.mark.parametrize("root,config", ROOTED_CONFIGS)
def test_every_key_of_a_source_is_held_or_listed(root, config):
    """No key of a source is passed in silence: a row of
    ``published.ROWS`` holds it, or the file's ``not_held`` says why it
    says nothing of the shape."""
    body = harness.load_json(root, config["file"])
    source = published.source_of(body)
    assert set(source) - published.KNOWN == set(body["not_held"])
    assert all(body["not_held"].values())
    del body["not_held"]
    with pytest.raises(ValueError, match="not_held"):
        published.check(config, body)


def test_a_count_is_cut_in_a_file_that_states_its_deployment():
    def change(entry, body):
        _count_without_deployment(entry, body)
        body["deployment"] = {
            "chips": 4, "divided": "vocabulary rows split four ways"}
    published.check(*_fixture_variant(change))
    # and the cell is then refused where it is loaded, not only here
    with pytest.raises(ValueError, match="vocab_size"):
        published.check(*_fixture_variant(_count_without_deployment))


def test_a_published_group_is_held_like_top_level_keys():
    """The two forms a file may take: the source's keys in a
    ``published`` group (a cut key holds the published value there) or
    at the top level (a cut key holds what is run)."""
    entry = json.loads(json.dumps(BENCH["configs"][0]))
    body = harness.load_json(ROOT, entry["file"])
    depth = "n_layer" if "n_layer" in body["published"] else \
        "num_hidden_layers"
    body["model"]["num_layers"] = 12
    with pytest.raises(ValueError, match=f"key '{depth}'"):
        published.check(entry, body)
    body["reduced"] = [_cut(depth, 24, 12)]
    entry["reduced"] = [depth]
    published.check(entry, body)
    body["reduced"] = [_cut(depth, 36, 12)]
    with pytest.raises(ValueError, match=f"key '{depth}'"):
        published.check(entry, body)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    end_to_end = metric in BENCH["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) <= allowed
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        # the reader is found by the metric's name
        assert callable(harness.load_reader(metric["name"]))
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_names_are_unique_and_well_formed():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for path in BENCH["paths"]:
        for base, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in base:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def run_py(*args, devices=1, program=None):
    """``benchmarks/run.py`` (or ``program``, a script's text) with
    ``args`` in a process of its own on ``devices`` CPU devices."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    what = ["-c", program] if program else [
        os.path.join(ROOT, "benchmarks", "run.py")]
    return subprocess.run(
        [sys.executable, *what, *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)


def kind_of(root, cell):
    """What a rehearsal stands for: the job, the family and whether
    there is more than one chip."""
    found = harness.load_cell(cell, root)
    return (found["traffic"]["job"], found["config"]["family"],
            found["cell"]["chips"] > 1)


def rehearsals():
    """The three cells rehearsed since PR 22, and one traced rehearsal
    of the first cell of every further kind (job, family, more than one
    chip) in either file: a cell of a new job or family is rehearsed
    without an edit here."""
    fixed = [(ROOT, "gpt2m_dp1", 1, 0), (ROOT, "bertl_s128", 1, 1),
             (ROOT, "gpt2m_dp4", 4, 1)]
    seen = {kind_of(root, cell) for root, cell, _, _ in fixed}
    found = []
    for root, bench in ((ROOT, BENCH), (FIXTURE, FIXTURE_BENCH)):
        for w in bench["workloads"]:
            kind = kind_of(root, w["name"])
            if kind not in seen:
                seen.add(kind)
                found.append((root, w["name"], w["chips"], 1))
    return fixed + found


REHEARSALS = rehearsals()
SEED = "5"
SOUND_LOSS = {}  # cell -> loss_step_16 of its untraced rehearsal


def test_every_kind_of_cell_is_rehearsed_and_one_is_traced():
    kinds = {kind_of(root, cell) for root, cell, _, _ in REHEARSALS}
    for root, bench in ((ROOT, BENCH), (FIXTURE, FIXTURE_BENCH)):
        assert {kind_of(root, w["name"])
                for w in bench["workloads"]} <= kinds
    assert ("dp_train", "rope_swiglu_lm", False) in kinds
    assert any(traced for _, _, _, traced in REHEARSALS)
    assert {cell for _, cell, _, _ in REHEARSALS} >= {
        "gpt2m_dp1", "bertl_s128", "gpt2m_dp4", "fixture_dp1"}


@pytest.mark.parametrize("root,cell,devices,traced", REHEARSALS,
                         ids=[r[1] for r in REHEARSALS])
def test_rehearsal_runs_end_to_end(root, cell, devices, traced):
    bench = harness.load_json(root, "BENCHMARK.json")
    done = run_py("--workload", cell, "--seed", SEED, "--seconds", "1",
                  "--trace", str(traced), "--rehearse", "--root", root,
                  devices=devices)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    # each number `correct` compared beside its limit, last in the
    # line and as the last lines of stderr
    assert list(line)[-1] == "compared"
    compared = line["compared"]
    assert {"reference_loss", "reference_gradient", "global_batch_loss",
            "loss_falls", "no_compile_in_window"} <= set(compared)
    assert compared["reference_gradient"]["limit"] == 3e-2
    assert all(set(c) == {"value", "limit", "ok"} and c["ok"]
               for c in compared.values())
    said = done.stderr.strip().splitlines()[-len(compared):]
    assert [s.split(":")[0] for s in said] == [
        f"compared {name}" for name in compared]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 17
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": devices}
    declared = {m["name"]: m["unit"] for m in (
        bench["per_layer"] if traced else bench["end_to_end"])}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == declared[name]
    # a rehearsal prints no time, rate or size of a device
    if traced:
        assert set(line["metrics"]) == {"allreduce_ops_per_step",
                                        "allreduce_mib_per_step"}
        mib = line["metrics"]["allreduce_mib_per_step"]["value"]
        # one chip reduces the scalar loss alone; four reduce every
        # fp32 gradient of the tiny model (about 0.5 M parameters)
        assert (mib > 1.5) if devices == 4 else (mib < 1e-3)
    else:
        assert set(line["metrics"]) == {"loss_step_16"}
        SOUND_LOSS[cell] = line["metrics"]["loss_step_16"]["value"]
    assert "check no_compile_in_window: ok" in done.stdout
    for check in ("reference_loss", "reference_gradient",
                  "global_batch_loss"):
        assert f"check {check}: ok" in done.stdout


def test_without_a_tpu_the_benchmark_refuses():
    done = run_py("--workload", "gpt2m_dp1", "--seed", "0",
                  "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr and "cpu" in done.stderr
    assert not done.stdout.strip().startswith("{")
    assert '"correct"' not in done.stdout


# What the ``loss_step_16`` gate and ``correct`` see of a faulty
# gradient, and what they do not (PERF.md's mutation table): a rehearsal
# with the gradients that reach AdamW mutated. The job looks
# ``optax.adamw`` up when it builds its optimizer.
MUTATED_RUN = """
import sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp, optax
MUTATIONS = {{
    "none": lambda x: x,  # the chain alone changes nothing
    "bf16": lambda x: x.astype(jnp.bfloat16).astype(x.dtype),
    "zero": jnp.zeros_like,
}}
mutate = MUTATIONS[sys.argv.pop(1)]
real_adamw = optax.adamw
optax.adamw = lambda lr: optax.chain(
    optax.stateless(lambda g, p: jax.tree_util.tree_map(mutate, g)),
    real_adamw(lr))
from benchmarks import run
sys.exit(run.main(sys.argv[1:]))
""".format(root=ROOT)


def loss_with_gradients(mutation):
    done = run_py(mutation, "--workload", "gpt2m_dp1", "--seed", SEED,
                  "--seconds", "0.1", "--trace", "0", "--rehearse",
                  program=MUTATED_RUN)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    return line["correct"], line["metrics"]["loss_step_16"]["value"]


def test_what_the_loss_gate_sees_of_a_faulty_gradient():
    bound = next(m["bound"] for m in BENCH["end_to_end"]
                 if m["name"] == "loss_step_16")
    # the sound run is the rehearsal above, where that test has run
    sound = SOUND_LOSS.get("gpt2m_dp1") or loss_with_gradients("none")[1]
    # an update that is lost: far past the bound, and not `correct`
    # either, because the loss does not fall
    correct, loss = loss_with_gradients("zero")
    assert loss > sound * (1 + bound) and not correct
    # gradients rounded to bfloat16, as a 16-bit wire would: under a
    # thousandth of the bound. No bound on this quantity sees a wire's
    # precision; that takes a check of the reduced gradient itself
    # (PERF.md, Open questions)
    correct, loss = loss_with_gradients("bf16")
    assert correct and abs(loss - sound) < 1e-3 * bound * sound
