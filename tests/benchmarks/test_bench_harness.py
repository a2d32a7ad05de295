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

from benchmarks import harness  # noqa: E402

BENCH = harness.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


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


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files(cell):
    found = harness.load_cell(cell)
    traffic, config = found["traffic"], found["config"]
    assert os.path.exists(os.path.join(
        harness.HERE, "jobs", traffic["job"] + ".py"))
    assert os.path.exists(os.path.join(
        harness.HERE, "reference", config["family"] + ".py"))
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


@pytest.mark.parametrize("cell", CELLS)
def test_model_is_built_from_the_config_file_as_written(cell):
    import dataclasses

    from benchmarks.jobs import dp_train
    found = harness.load_cell(cell)
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


@pytest.mark.parametrize("config", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_config_file_holds_the_published_sizes(config):
    body = harness.load_json(ROOT, config["file"])
    assert config["file"].startswith("benchmarks/")
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"] == []
    model, pub = body["model"], body["published"]
    depth = pub.get("n_layer", pub.get("num_hidden_layers"))
    width = pub.get("n_embd", pub.get("hidden_size"))
    heads = pub.get("n_head", pub.get("num_attention_heads"))
    inner = pub.get("intermediate_size") or pub.get("n_inner") \
        or 4 * width
    assert (model["num_layers"], model["hidden_size"],
            model["num_heads"]) == (depth, width, heads)
    assert model["hidden_size"] * model["mlp_ratio"] == inner
    assert model["vocab_size"] == pub["vocab_size"]


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


REHEARSALS = [("gpt2m_dp1", 1, 0), ("bertl_s128", 1, 1),
              ("gpt2m_dp4", 4, 1)]
SEED = "5"
SOUND_LOSS = {}  # cell -> loss_step_16 of its untraced rehearsal


@pytest.mark.parametrize("cell,devices,traced", REHEARSALS)
def test_rehearsal_runs_end_to_end(cell, devices, traced):
    done = run_py("--workload", cell, "--seed", SEED, "--seconds", "1",
                  "--trace", str(traced), "--rehearse", devices=devices)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 17
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": devices}
    declared = {m["name"]: m["unit"] for m in (
        BENCH["per_layer"] if traced else BENCH["end_to_end"])}
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
