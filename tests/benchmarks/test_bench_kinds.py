"""A layer's kind is data (``layer_types`` in the ``model`` group) and a
file (``benchmarks/layer_kinds/<kind>.py``): the contract every kind's
file keeps, the count of a state-space hybrid by its kinds
(``data/state_space_hybrid_share.json``: an entry, a configuration's
body and a traffic body, no cell), the hold ``published.check`` has on
a pattern, a kind that comes as a file under another root with no edit
to any file, a window's pairs against a brute-force count of the mask,
and every count there was before a layer had a kind, pinned by ``repr``
to the parent's files. Nothing here is a device number.

``python tests/benchmarks/test_bench_kinds.py <tree>`` prints the
pinned counts as the ``benchmarks/`` of another checkout gives them
(``data/flops_pinned_a7a5f5c.json`` is the parent's, a7a5f5c)."""

import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
AS_SCRIPT = __name__ == "__main__"
sys.path.insert(0, os.path.abspath(sys.argv[1]) if AS_SCRIPT else ROOT)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks import flops, harness, published  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    attn_proj_roofline, mlp_roofline, moe_experts_roofline)

DATA = os.path.join(ROOT, "tests", "benchmarks", "data")
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
HYBRID = harness.load_json(DATA, "state_space_hybrid_share.json")
MODEL, TRAFFIC = HYBRID["body"]["model"], HYBRID["traffic"]
KINDS = sorted(f[:-3] for f in os.listdir(
    os.path.join(ROOT, "benchmarks", "layer_kinds")) if f.endswith(".py"))
PEAK = harness.peak_of("TPU v5 lite")


@pytest.fixture(autouse=True)
def the_harness_own_kinds():
    """Every test starts and ends with kinds looked for among the
    harness's own alone."""
    flops.kinds_root(None)
    yield
    flops.kinds_root(None)


class Recording(dict):
    """A model group that notes which of its keys are asked for."""

    def __init__(self, *args):
        super().__init__(*args)
        self.asked = set()

    def __getitem__(self, key):
        self.asked.add(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        self.asked.add(key)
        return super().get(key, default)

    def __contains__(self, key):
        self.asked.add(key)
        return super().__contains__(key)


# -- the contract of a kind's file -------------------------------------------

def test_the_three_kinds_there_are():
    assert KINDS == flops.kinds_there() == [
        "attention", "mamba2", "window_attention"]


@pytest.mark.parametrize("name", KINDS)
def test_a_kinds_file_keeps_the_contract(name):
    """What the top of ``flops.py`` says a kind's file has, for every
    file in the directory."""
    kind = flops.load_kind(name)
    assert kind is flops.load_kind(name)  # loaded once
    assert kind.KEYS and all(
        isinstance(k, str) and isinstance(why, str) and why
        for k, why in kind.KEYS.items())
    assert kind.BOOKED_UNDER in ("attention", name)
    assert kind.SOURCE_NAMES and all(
        isinstance(s, str) for s in kind.SOURCE_NAMES)
    assert isinstance(kind.ROWS, tuple) and all(
        isinstance(row, published.Row) for row in kind.ROWS)
    # the functions ask the group for the listed keys and hidden_size
    variants = [{**MODEL, "sliding_window": 2048}]
    if kind.BOOKED_UNDER == "attention":
        latent = harness.load_json(
            DATA, "latent_shared_expert_share.json")["body"]["model"]
        variants += [{**latent, "sliding_window": 2048},
                     {**MODEL, "head_dim": 64, "sliding_window": 64}]
    asked = set()
    for model in variants:
        model = Recording(model)
        macs = kind.mixer_macs(model)
        mixing = kind.mixing_flops(model, TRAFFIC)
        work = kind.kernel_work(model, TRAFFIC)
        weights = kind.products(model)
        asked |= model.asked
        assert macs > 0 and mixing > 0
        assert work is None or (
            set(work) == {"flops", "bytes"} and min(work.values()) > 0)
        assert weights is None or all(
            k > 0 and n > 0 for k, n in weights)
    assert asked - {"hidden_size"} == set(kind.KEYS), (
        asked - set(kind.KEYS), set(kind.KEYS) - asked)
    # a kernel's operations are the mixing's, three passes over the step
    if work is not None:
        tokens = TRAFFIC["batch_per_chip"] * TRAFFIC["seq_len"]
        assert work["flops"] == 3 * tokens * mixing


def test_a_sources_spelling_is_one_kinds():
    spelt = [s for name in KINDS
             for s in flops.load_kind(name).SOURCE_NAMES]
    assert len(spelt) == len(set(spelt))
    assert {"attention", "full_attention", "sliding_attention",
            "mamba"} <= set(spelt)
    # and no two kinds' rows, or a kind's and the file's own, hold one key
    keys = [key for row in published.ROWS for key in row.keys] + [
        key for name in KINDS for row in flops.load_kind(name).ROWS
        for key in row.keys]
    assert len(keys) == len(set(keys))


def test_the_docstring_and_the_kinds_list_every_key_read():
    """``flops.py``'s list and the kinds' ``KEYS`` divide the keys that
    the sums ask a model group for; no key is read in silence."""
    import re
    listed = set(re.findall(
        r"``(\w+)``", flops.__doc__.split("absence means")[1]))
    listed -= {"swiglu", "attention"}  # values, not keys
    model = Recording({**MODEL, "num_experts": 0})
    flops.train_flops_per_token(model, TRAFFIC)
    flops.attention_kernel_work(model, TRAFFIC)
    kinds = set(flops.load_kind("attention").KEYS) | set(
        flops.load_kind("mamba2").KEYS)
    assert model.asked <= listed | kinds
    assert "layer_types" in listed and not listed & kinds


# -- the hybrid, counted by kind ---------------------------------------------

def test_operations_per_token_of_a_state_space_hybrid():
    # by hand (ISSUE 44), h=2048, T=8192, causal, one period 9 + 1:
    #   state-space mixer: in_proj 2048 x (2*4096 + 2*128 + 64 = 8512)
    #     = 17,432,576; convolution 4 x (4096 + 256) = 17,408; out_proj
    #     4096 x 2048 = 8,388,608: 25,838,592 multiply-adds a position
    #   attention mixer: q 2048*2048 + k, v 2*2048*512 + o 2048*2048
    #     = 10,485,760
    #   every layer's SwiGLU MLP 3*2048*8192 = 50,331,648
    #   attention, one layer: 2 * 8192/2 * 32 * (64 + 64)
    #   the recurrence, nine layers: update and readout of a 64 x 64 x
    #     128 state, 2 * 2*64*64*128 = 2,097,152 a token
    #   head 2*2048*12544 at 8191 of 8192 positions
    mamba2, attention = (flops.load_kind(k) for k in
                         ("mamba2", "attention"))
    assert mamba2.mixer_macs(MODEL) == 17_432_576 + 17_408 + 8_388_608 \
        == 25_838_592
    assert attention.mixer_macs(MODEL) == flops.projection_macs(MODEL) \
        == 10_485_760
    assert flops.mlp_macs(MODEL) == (50_331_648, 50_331_648)
    f = flops.forward_flops_per_token(MODEL, TRAFFIC)
    assert f == {"blocks": 1_492_699_136, "attention": 33_554_432,
                 "mamba2": 18_874_368, "head": 51_373_952}
    assert f["blocks"] == 2 * (9 * (25_838_592 + 50_331_648)
                               + 10_485_760 + 50_331_648)
    assert f["mamba2"] == 9 * 2_097_152
    assert sum(f.values()) == 1_596_501_888
    assert flops.train_flops_per_token(MODEL, TRAFFIC) == 3 * 1_596_501_888
    # the same group with every layer counted as attention, as the
    # parent's file counted it (ISSUE 44's arithmetic)
    plain = {k: v for k, v in MODEL.items() if k != "layer_types"}
    g = flops.forward_flops_per_token(plain, TRAFFIC)
    assert g == {"blocks": 1_216_348_160, "attention": 335_544_320,
                 "head": 51_373_952} and sum(g.values()) == 1_603_266_432
    # one period, nine and one, by the pattern's own count
    assert flops.layers_by_kind(MODEL) == {"mamba2": 9, "attention": 1}
    assert flops.layers_of(MODEL) == {attention: 1}
    assert flops.layers_of(MODEL, "mamba2") == {mamba2: 9}


def test_the_hybrids_attention_work_is_one_layers():
    plain = {k: v for k, v in MODEL.items() if k != "layer_types"}
    one = flops.attention_kernel_work(MODEL, TRAFFIC)
    ten = flops.attention_kernel_work(plain, TRAFFIC)
    assert one == {"flops": 3 * 2 * 32 * (8192 ** 2 / 2) * 128,
                   "bytes": 3 * (32 + 8) * 8192 * 128 * 2}
    assert {k: 10 * v for k, v in one.items()} == ten
    # and so are the projections the reader counts
    assert attn_proj_roofline.work(MODEL, TRAFFIC) == {
        "flops": 3 * 2 * 8192 * 10_485_760,
        "bytes": 3 * 2 * (8192 * (2048 + 2048) * 2 + 8192 * (2048 + 512)
                          * 2 + 2 * 2048 * 2048 + 2 * 2048 * 512)}
    assert {k: 10 * v for k, v in attn_proj_roofline.work(
        MODEL, TRAFFIC).items()} == attn_proj_roofline.work(plain, TRAFFIC)
    # no layer that runs the attention kernels: nothing, not a zero
    none = {**MODEL, "layer_types": ["mamba2"] * 10}
    assert attn_proj_roofline.work(none, TRAFFIC) is None
    assert flops.attention_kernel_work(none, TRAFFIC) == {
        "flops": 0.0, "bytes": 0.0}


def test_the_recurrences_kernel_work():
    # one layer, one step of 8,192 positions: three passes of the
    # update and readout; forward x, dt, B, C in and y out, backward
    # those and dy in, dx, ddt, dB, dC out, in bf16
    w = flops.load_kind("mamba2").kernel_work(MODEL, TRAFFIC)
    assert w == {"flops": 3 * 8192 * 2_097_152,
                 "bytes": 8192 * 2 * (
                     (4096 + 64 + 128 + 128 + 4096)
                     + (4096 + 64 + 128 + 128) + 4096
                     + (4096 + 64 + 128 + 128))}
    least, bound = flops.roofline_seconds(w, PEAK)
    assert bound == "hbm" and least == pytest.approx(
        8192 * 42_880 / 819e9)
    # under a block-diffusion step a data token runs two positions
    twice = flops.load_kind("mamba2").kernel_work(
        MODEL, {**TRAFFIC, "objective": "block_diffusion"})
    assert twice == {k: 2 * v for k, v in w.items()}
    # an inner stream of two widths is refused, not rounded
    with pytest.raises(ValueError, match="mamba_expand 2 x hidden_size "
                                         "2048 is not mamba_n_heads 32"):
        flops.forward_flops_per_token(
            {**MODEL, "mamba_n_heads": 32}, TRAFFIC)


def test_the_readers_count_the_hybrid_by_kind():
    """``mfu_pct`` and the two attention rooflines have no ``workloads``
    list: a traced run of a hybrid's cell goes through them, and they
    count its layers as they are."""
    run = harness.Run(
        started=time.perf_counter(), workload="hybrid", chips=1,
        traffic=TRAFFIC, model_sizes=MODEL, seed=0, seconds=10,
        trace=True, rehearse=False)
    run.device_kind = "TPU v5 lite"
    run.tokens_per_s_per_chip = 10_000.0
    run.log = lambda text: None
    run.reduced_trace = {"kernel_ms_by_layer": {"attn": 10.0}}
    assert harness.load_reader("mfu_pct")(run) == pytest.approx(
        100 * 3 * 1_596_501_888 * 10_000 / 197e12)
    share = harness.load_reader("attn_kernel_roofline")(run)
    assert share == pytest.approx(
        100 * (3 * 2 * 32 * (8192 ** 2 / 2) * 128 / 197e12) / 0.010)
    assert 0 < share < 100  # ten times that with every layer counted


def test_a_pattern_is_one_name_a_layer():
    assert flops.layer_kinds({"num_layers": 3}) == ("attention",) * 3
    with pytest.raises(ValueError, match="layer_types names 9 layers "
                                         "and num_layers is 10"):
        flops.layer_kinds({**MODEL, "layer_types": ["mamba2"] * 9})
    with pytest.raises(ValueError, match="layer kind 'hyena'.*the kinds "
                                         "there are .*'mamba2'"):
        flops.train_flops_per_token(
            {**MODEL, "layer_types": ["hyena"] * 10}, TRAFFIC)


# -- the hold on a file -------------------------------------------------------

def _moved(entry, body):
    body["model"]["layer_types"] = (
        ["mamba2"] * 4 + ["attention"] + ["mamba2"] * 5)


def _eight_and_two(entry, body):
    body["model"]["layer_types"] = (
        ["mamba2"] * 5 + ["attention"] + ["mamba2"] * 3 + ["attention"])


def _five_deep(entry, body):
    body["model"].update(layer_types=["mamba2"] * 5, num_layers=5)
    body["num_hidden_layers"] = body["reduced"][0]["held"] = 5


def _a_narrower_state(entry, body):
    body["model"]["mamba_d_state"] = 64


def _no_period(entry, body):
    body["layer_period"] = 5


def _an_unknown_kind(entry, body):
    body["model"]["layer_types"][0] = "hyena"


def _a_spelling_no_kind_has(entry, body):
    body["layer_types"][3] = "conv"


def _no_pattern_in_the_model_group(entry, body):
    del body["model"]["layer_types"]


def _the_list_cut_with_the_depth(entry, body):
    body["layer_types"] = body["layer_types"][:10]


def _the_list_under_reduced(entry, body):
    body["reduced"].append({"key": "layer_types", "published": 40,
                            "held": 10, "why": "-"})
    entry["reduced"].append("layer_types")


def _another_chunk(entry, body):
    body["model"]["mamba_chunk_size"] = 128


def _a_width_cut(entry, body):
    body["mamba_d_state"] = body["model"]["mamba_d_state"] = 64
    body["reduced"].append({"key": "mamba_d_state", "published": 128,
                            "held": 64, "why": "-"})
    entry["reduced"].append("mamba_d_state")


def _heads_cut_to_two_widths(entry, body):
    body["mamba_n_heads"] = body["model"]["mamba_n_heads"] = 32
    body["reduced"].append({"key": "mamba_n_heads", "published": 64,
                            "held": 32, "why": "-"})
    entry["reduced"].append("mamba_n_heads")


def _a_key_the_kind_reads_is_missing(entry, body):
    del body["model"]["mamba_d_conv"]


def _another_mlp_width(entry, body):
    body["shared_intermediate_size"] = 4096


@pytest.mark.parametrize("change,key,why", [
    (None, None, None),
    (_moved, "layer_types", "runs 4 x mamba2, attention, 5 x mamba2, "
     "which is not the first 10 layers of the published 5 x mamba2, "
     "attention, 9 x mamba2, attention"),
    (_eight_and_two, "layer_types", "not the first 10 layers"),
    (_five_deep, "num_hidden_layers", "whole periods"),
    (_a_narrower_state, "mamba_d_state", "published 128"),
    (_no_period, "layer_period", "5 is no period of the published"),
    (_an_unknown_kind, "layer_types", "layer kind 'hyena'"),
    (_a_spelling_no_kind_has, "layer_types", "names a layer 'conv'"),
    (_no_pattern_in_the_model_group, "layer_types", "runs 10 x attention"),
    (_the_list_cut_with_the_depth, "layer_types", "names 10 layers and "
     "its depth is 40"),
    (_the_list_under_reduced, "layer_types", "never listed in `reduced`"),
    (_another_chunk, "mamba_chunk_size", "published 256"),
    (_a_width_cut, "mamba_d_state", "never cut"),
    (_heads_cut_to_two_widths, "layer_types", "mamba_n_heads 32 x "
     "mamba_d_head 64"),
    (_a_key_the_kind_reads_is_missing, "layer_types",
     "lacks 'mamba_d_conv'"),
    (_another_mlp_width, "shared_intermediate_size", "published 4096"),
], ids=lambda x: getattr(x, "__name__", None))
def test_the_hybrid_is_held_to_its_source_and_refused_by_the_keys_name(
        change, key, why):
    entry, body = copy.deepcopy((HYBRID["entry"], HYBRID["body"]))
    if change is None:
        published.check(entry, body)
        source = published.source_of(body)
        # the catalog row's 33 keys: 14 under not_held, the pattern,
        # seven of the state-space kind's, the rest the file's own rows'
        assert len(source) == 33 and len(body["not_held"]) == 14
        mamba = {k for row in flops.load_kind("mamba2").ROWS
                 for k in row.keys}
        assert mamba == {k for k in source if k.startswith("mamba_")} - {
            "mamba_conv_bias", "mamba_proj_bias"} and len(mamba) == 7
        assert set(source) == (set(source) & published.KNOWN) | mamba | {
            "layer_types"} | set(body["not_held"])
        assert [c["key"] for c in body["reduced"]] == entry["reduced"]
        assert len(body["layer_types"]) == 40 and body["layer_types"][5::10] \
            == ["attention"] * 4 and set(body["layer_types"]) == {
                "mamba", "attention"}
        assert body["model"]["layer_types"] == (
            ["mamba2"] * 5 + ["attention"] + ["mamba2"] * 4)
        # a body, an entry and a traffic body: no file of the benchmark
        # names it, and no reference of its family exists
        roots = [ROOT] + [os.path.join(DATA, d)
                          for d in ("fixture", "share_fixture")]
        for root in roots:
            assert entry["name"] not in json.dumps(
                harness.load_json(root, "BENCHMARK.json"))
            assert not os.path.exists(os.path.join(
                root, "benchmarks", "reference", body["family"] + ".py"))
        return
    change(entry, body)
    with pytest.raises(ValueError,
                       match=f"key '{key}'.*{why}"):
        published.check(entry, body)


def _alternating(held, sliding_window=2048):
    """A body of eight published layers, window and full attention in
    turn, cut to ``held``: the second drawn row's pattern in small."""
    entry = {"name": "alternating", "source": "a test's own",
             "file": "-", "reduced": ["num_hidden_layers"],
             "why": "-"}
    body = {
        "source": "a test's own", "family": "-",
        "num_hidden_layers": held, "hidden_size": 1024,
        "num_attention_heads": 16, "sliding_window": 2048,
        "layer_types": ["sliding_attention", "full_attention"] * 4,
        "layer_period": 2,
        "model": {"num_layers": held, "hidden_size": 1024,
                  "num_heads": 16, "causal": True,
                  "sliding_window": sliding_window,
                  "layer_types": ["window_attention", "attention"]
                  * (held // 2)},
        "reduced": [{"key": "num_hidden_layers", "published": 8,
                     "held": held, "why": "-"}],
    }
    return entry, body


def test_a_patterned_model_keeps_four_layers_experts_or_none():
    published.check(*_alternating(4))
    with pytest.raises(ValueError, match="key 'num_hidden_layers'.*under "
                       "the floor of a cut, 4: four layers after the "
                       "leading dense ones in a model of more than one "
                       "kind"):
        published.check(*_alternating(2))
    # a kind's rows are held where the pattern names the kind
    with pytest.raises(ValueError, match="key 'sliding_window'.*"
                                         "published 2048"):
        published.check(*_alternating(4, sliding_window=1024))
    # a model of one kind, no experts: a cut to one layer stays allowed
    entry, body = _alternating(1)
    body["layer_types"] = ["full_attention"] * 8
    body["layer_period"] = 1
    body["model"]["layer_types"] = ["attention"]
    del body["sliding_window"]
    published.check(entry, body)
    # and so does leaving the key out of a model group of that one kind
    del body["model"]["layer_types"]
    published.check(entry, body)
    # a pattern the source does not have holds nothing
    entry, body = _alternating(4)
    del body["layer_types"], body["sliding_window"]
    with pytest.raises(ValueError, match="key 'layer_types'.*the source "
                                         "has no `layer_types`"):
        published.check(entry, body)


def test_a_file_with_no_pattern_is_held_by_the_files_own_rows_alone():
    for entry in BENCH["configs"]:
        body = harness.load_json(ROOT, entry["file"])
        assert "layer_types" not in published.source_of(body)
        assert "layer_types" not in body["model"]
        published.check(entry, body)
    for share in ("block_diffusion_share", "latent_shared_expert_share"):
        d = harness.load_json(DATA, share + ".json")
        published.check(d["entry"], d["body"])


# -- a kind comes as a file ---------------------------------------------------

SHORT_CONV = '''
"""A gated short convolution (a test's own kind): two projections in,
a depthwise convolution of ``conv_taps`` taps, one projection out."""
from benchmarks import published

KEYS = {"conv_taps": "taps of the depthwise convolution; required"}
BOOKED_UNDER = "short_conv"
SOURCE_NAMES = ("conv",)
ROWS = (published.Row(("conv_L_cache",), published.WIDTH,
                      lambda m: m.get("conv_taps")),)


def products(model):
    h = model["hidden_size"]
    return [(h, 3 * h), (h, h)]


def mixer_macs(model):
    h = model["hidden_size"]
    return 4 * h * h + model["conv_taps"] * h


def mixing_flops(model, traffic):
    return 2.0 * model["hidden_size"]  # the gates, elementwise


def kernel_work(model, traffic):
    return None
'''


def test_a_kind_written_under_another_root_is_found_and_counted(tmp_path):
    """The claim this exists for: a new architecture's kind of layer
    comes as one more file. A root of its own (a BENCHMARK.json, a
    configuration, a traffic file and ``benchmarks/layer_kinds/
    short_conv.py``) is loaded through ``harness.load_cell``, held by
    ``published.check`` with the kind's own row, and counted by every
    sum, with no file of the harness edited or patched."""
    model = {"num_layers": 4, "hidden_size": 1024, "num_heads": 16,
             "mlp_ratio": 4.0, "vocab_size": 32000, "causal": True,
             "max_seq_len": 4096, "tie_embeddings": True, "conv_taps": 3,
             "layer_types": ["short_conv", "short_conv", "attention",
                             "short_conv"]}
    source = {"num_hidden_layers": 4, "hidden_size": 1024,
              "num_attention_heads": 16, "intermediate_size": 4096,
              "vocab_size": 32000, "max_position_embeddings": 4096,
              "tie_word_embeddings": True, "conv_L_cache": 3,
              "layer_types": ["conv", "conv", "full_attention", "conv"]}
    config = {"source": "a test's own", "family": "none", **source,
              "layer_period": 4, "model": model, "tiny": {},
              "reduced": [], "not_held": {}}
    traffic = {"job": "dp_train", "objective": "causal_lm",
               "seq_len": 1024, "batch_per_chip": 8, "tiny": {}}
    bench = {"configs": [{"name": "convs", "source": "a test's own",
                          "file": "benchmarks/configs/convs.json",
                          "reduced": [], "why": "-"}],
             "workloads": [{"name": "convs_s1024", "config": "convs",
                            "traffic": "lm", "chips": 1, "why": "-"}],
             "end_to_end": BENCH["end_to_end"],
             "per_layer": BENCH["per_layer"]}
    for path, text in (
            ("BENCHMARK.json", json.dumps(bench)),
            ("benchmarks/configs/convs.json", json.dumps(config)),
            ("benchmarks/traffic/lm.json", json.dumps(traffic)),
            ("benchmarks/layer_kinds/short_conv.py", SHORT_CONV)):
        os.makedirs(os.path.dirname(tmp_path / path), exist_ok=True)
        (tmp_path / path).write_text(text)
    # before the root is the run's, the name is refused, by name
    with pytest.raises(ValueError, match="layer kind 'short_conv'"):
        flops.train_flops_per_token(model, traffic)
    found = harness.load_cell("convs_s1024", str(tmp_path))
    model, traffic = found["config"]["model"], found["traffic"]
    assert flops.kinds_there() == sorted(KINDS + ["short_conv"])
    h = 1024
    f = flops.forward_flops_per_token(model, traffic)
    assert f == {
        "blocks": 2 * (3 * (4 * h * h + 3 * h) + 4 * h * h
                       + 4 * 2 * h * 4 * h),
        "short_conv": 3 * 2.0 * h,
        "attention": 2 * (1024 / 2) * 16 * (64 + 64),
        "head": 2 * h * 32000 * 1023 / 1024}
    # the attention readers count the one attention layer
    assert flops.attention_kernel_work(model, traffic) == {
        "flops": 3 * 2 * 8 * 16 * (1024 ** 2 / 2) * 128,
        "bytes": 3 * (16 + 16) * 8 * 1024 * 128 * 2}
    assert attn_proj_roofline.work(model, traffic)["flops"] == \
        3 * 2 * 8 * 1024 * 4 * h * h
    # its own row holds its own size, by the key's name
    config["model"]["conv_taps"] = 4
    (tmp_path / "benchmarks/configs/convs.json").write_text(
        json.dumps(config))
    with pytest.raises(ValueError, match="key 'conv_L_cache'.*published 3"):
        harness.load_cell("convs_s1024", str(tmp_path))
    # and the checkout's own cells do not see it
    harness.load_cell("gpt2m_dp1")
    assert flops.kinds_there() == KINDS


# -- a window's pairs ---------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t,w", [(8, 1), (8, 3), (16, 4), (12, 12),
                                 (12, 40), (64, 17), (64, 63)])
def test_a_windows_pairs_are_a_brute_force_count_of_the_mask(t, w, causal):
    window = flops.load_kind("window_attention")
    q, k = np.indices((t, t))
    mask = (q - k < w) & (k <= q) if causal else np.abs(q - k) < w
    model = {"causal": causal, "sliding_window": w}
    traffic = {"objective": "causal_lm", "seq_len": t}
    # ``flops.visible_pairs``' convention: under a causal mask the
    # diagonal's T/2 are left out, under a full one every pair counts
    assert window.visible_pairs(model, traffic) == \
        int(mask.sum()) - (t / 2 if causal else 0)
    if w >= t:  # a window of the sequence's length reads as no window
        assert window.visible_pairs(model, traffic) == \
            flops.visible_pairs(model, traffic)
    assert mask.any(axis=1).all()  # no query is blind


def test_window_attention_is_attention_over_fewer_pairs():
    attention, window = (flops.load_kind(k) for k in
                         ("attention", "window_attention"))
    model = {"hidden_size": 2048, "num_heads": 32, "num_kv_heads": 4,
             "head_dim": 128, "causal": True, "sliding_window": 2048}
    traffic = {"objective": "causal_lm", "seq_len": 8192,
               "batch_per_chip": 2}
    assert window.mixer_macs(model) == attention.mixer_macs(model)
    assert window.products(model) == attention.products(model)
    pairs = 8192 * 2048 - 2048 * 2047 / 2 - 8192 / 2
    assert window.visible_pairs(model, traffic) == pairs
    assert window.mixing_flops(model, traffic) == \
        2 * pairs / 8192 * 32 * 256
    full, seen = (k.kernel_work(model, traffic)
                  for k in (attention, window))
    assert seen["bytes"] == full["bytes"]
    assert seen["flops"] / full["flops"] == pairs / (8192 ** 2 / 2)
    # at a window of the sequence and more, to the digit what attention
    # reads, in every count
    wide = {**model, "sliding_window": 8192}
    assert window.kernel_work(wide, traffic) == full
    assert window.mixing_flops(wide, traffic) == \
        attention.mixing_flops(wide, traffic)
    # three window layers and a full one, the second drawn row's period
    pattern = {**model, "num_layers": 4, "mlp_ratio": 3.0,
               "vocab_size": 1000, "activation": "swiglu",
               "layer_types": ["window_attention"] * 3 + ["attention"]}
    f = flops.forward_flops_per_token(pattern, traffic)
    assert f["attention"] == 3 * window.mixing_flops(model, traffic) \
        + attention.mixing_flops(model, traffic)
    work = flops.attention_kernel_work(pattern, traffic)
    assert work["flops"] == 3 * seen["flops"] + full["flops"]
    assert work["bytes"] == 4 * full["bytes"]
    assert attn_proj_roofline.work(pattern, traffic) == \
        attn_proj_roofline.work({**pattern, "layer_types": ["attention"]
                                 * 4}, traffic)
    with pytest.raises(ValueError, match="block-diffusion"):
        window.visible_pairs({**model, "diffusion_block": 4}, traffic)


# -- every count there was, pinned --------------------------------------------

def pinned_cases() -> dict:
    """``{name: (model group, traffic)}``: the five cells and the two
    shares the tests keep."""
    cases = {}
    for w in BENCH["workloads"]:
        entry = next(c for c in BENCH["configs"]
                     if c["name"] == w["config"])
        cases[w["name"]] = (
            harness.load_json(ROOT, entry["file"])["model"],
            harness.load_json(ROOT, "benchmarks", "traffic",
                              w["traffic"] + ".json"))
    share = harness.load_json(DATA, "block_diffusion_share.json")
    cases["block_diffusion_share"] = (share["body"]["model"],
                                      share["traffic"])
    cases["latent_shared_expert_share"] = (
        harness.load_json(DATA, "latent_shared_expert_share.json")[
            "body"]["model"],
        {"objective": "causal_lm", "seq_len": 4096, "batch_per_chip": 4})
    return cases


def said(f, *args) -> str:
    try:
        return repr(f(*args))
    except Exception as e:  # the refusal is the pinned answer
        return f"{type(e).__name__}: {e}"


def projections_work(model, traffic):
    if hasattr(attn_proj_roofline, "work"):
        return attn_proj_roofline.work(model, traffic)
    weights = attn_proj_roofline.products(model)  # the parent's reader
    return weights and attn_proj_roofline.dense_work(
        flops.projection_macs(model), weights,
        attn_proj_roofline.positions_per_step(traffic),
        model["num_layers"])


def mlps_work(model, traffic):
    layers = model.get("dense_layers", 0) \
        if model.get("num_experts", 0) else model["num_layers"]
    return attn_proj_roofline.dense_work(
        flops.mlp_macs(model)[0], mlp_roofline.products(model),
        attn_proj_roofline.positions_per_step(traffic), layers)


def counts(model, traffic) -> dict:
    """Every public function of ``flops.py`` as the parent had them and
    the work the roofline readers count, by ``repr``."""
    r = {f: said(getattr(flops, f), traffic)
         for f in ("head_positions_per_token", "positions_per_token")}
    r["head_positions_per_token.ahead2"] = said(
        flops.head_positions_per_token, traffic, 2)
    for f in ("head_dim", "kv_heads", "qk_head_dim", "v_head_dim",
              "projection_macs", "mlp_macs"):
        r[f] = said(getattr(flops, f), model)
    for f in ("visible_pairs", "attention_flops_per_layer",
              "forward_flops_per_token", "train_flops_per_token",
              "attention_kernel_work"):
        r[f] = said(getattr(flops, f), model, traffic)
    r["roofline_seconds"] = said(lambda: flops.roofline_seconds(
        flops.attention_kernel_work(model, traffic), PEAK))
    r["attn_proj_roofline.work"] = said(projections_work, model, traffic)
    r["attn_proj_roofline.products"] = said(
        attn_proj_roofline.products, model)
    r["mlp_roofline.work"] = said(mlps_work, model, traffic)
    if model.get("num_experts"):
        r["moe_experts_roofline.expert_work"] = said(
            moe_experts_roofline.expert_work, model, traffic)
    return r


PINNED = {} if AS_SCRIPT else harness.load_json(
    DATA, "flops_pinned_a7a5f5c.json")


def test_the_pin_is_of_the_five_cells_and_the_two_shares():
    assert set(PINNED) == set(pinned_cases()) == {
        w["name"] for w in BENCH["workloads"]} | {
        "block_diffusion_share", "latent_shared_expert_share"}
    assert all(len(r) >= 18 for r in PINNED.values())


@pytest.mark.parametrize("case", sorted(PINNED))
def test_every_count_reads_as_the_parents_files_read_it(case):
    """``repr``-equal to what ``benchmarks/flops.py`` and the readers
    of a7a5f5c (PR 42) gave: no number a cell prints moved when a
    layer's kind became a file."""
    model, traffic = pinned_cases()[case]
    assert "layer_types" not in model
    assert counts(model, traffic) == PINNED[case]
    # and naming every layer's kind changes nothing
    named = {**model, "layer_types": ["attention"] * model["num_layers"]}
    assert counts(named, traffic) == PINNED[case]


if AS_SCRIPT:
    json.dump({name: counts(*case)
               for name, case in pinned_cases().items()},
              sys.stdout, indent=1, sort_keys=True)
    print()
