"""The benchmark's trace reduction, HLO counters and operation
arithmetic, on hand-made inputs with known answers."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops, harness, hlo, trace  # noqa: E402

US = 1000  # the events below are written in microseconds


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


# one 100 us step on one device: compute [0,40], an asynchronous
# all-reduce whose start is [40,42] and done [70,80] with compute
# [42,60] between them, a synchronous all-gather [80,85], a while
# [85,95] with two body fusions, and nothing in [95,100]
STEP_OPS = [
    ev("fusion.1", 0, 40),
    ev("all-reduce-start.1", 40, 2),
    ev("fusion.2", 42, 18),
    ev("all-reduce-done.1", 70, 10),
    ev("all-gather.3", 80, 5),
    ev("while.1", 85, 10),
    ev("fusion.3", 85, 4),
    ev("custom-call.7", 90, 5),
]
STEP_MODULES = [ev("jit_step_fn(123)", 0, 100)]


@pytest.mark.parametrize("spans,want", [
    ([(0, 5), (3, 8), (10, 12)], [(0, 8), (10, 12)]),
    ([(5, 5), (1, 2)], [(1, 2)]),
    ([], []),
])
def test_merge(spans, want):
    assert trace.merge(spans) == want


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 4), (6, 12)], [(0, 2), (4, 6)]),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
])
def test_subtract(a, b, want):
    assert trace.subtract(a, b) == want


@pytest.mark.parametrize("name,want", [
    ("all-reduce.12", ("all-reduce", "sync")),
    ("all-reduce-start", ("all-reduce", "start")),
    ("all-gather-done.3", ("all-gather", "done")),
    ("collective-permute-start.1", ("collective-permute", "start")),
    ("fusion.3", None),
    ("all-reduce-scatter-fusion", None),
])
def test_collective_kind(name, want):
    assert trace.collective_kind(name) == want


def test_instruction_name_of_a_device_event():
    assert trace.instruction_name(
        "%attn.72 = bf16[16,16,1024,64]{3,2,1,0} custom-call(%a), "
        "custom_call_target=\"tpu_custom_call\"") == "attn.72"
    assert trace.instruction_name("jit_step_fn(96)") == "jit_step_fn(96)"


def test_start_done_pair_is_one_interval():
    spans = trace.merge(trace.collective_spans(STEP_OPS))
    # the pair is in flight from 40 to 80; the all-gather joins it
    assert spans == [(40 * US, 85 * US)]


def test_done_without_start_counts_from_itself():
    assert trace.collective_spans([ev("all-reduce-done.1", 7, 3)]) == [
        (7 * US, 10 * US)]


def test_leaves_drop_the_parent():
    names = [e[0] for e in trace.leaves(STEP_OPS)]
    assert "while.1" not in names and "fusion.3" in names


def test_self_seconds_subtract_children():
    got = trace.self_seconds_by_name(STEP_OPS)
    assert got["while.1"] == pytest.approx(1e-6)  # 10 - 4 - 5
    assert got["fusion.1"] == pytest.approx(40e-6)


@pytest.mark.parametrize("name,want", [
    ("fusion.5839", "fusion"), ("attn.72", "attn"),
    ("convolution_add_fusion.20.remat", "convolution_add_fusion"),
    ("broadcast_in_dim.260.clone", "broadcast_in_dim"),
    ("all-reduce-start.1", "all-reduce-start"), ("while", "while"),
])
def test_stem(name, want):
    assert trace.stem(name) == want


def test_one_step_one_device():
    r = trace.reduce_device(STEP_OPS, trace.step_windows(
        STEP_MODULES, "step_fn"), kernel_names=["custom-call.7"])
    (s,) = r["steps"]
    assert s["busy_ns"] == (60 + 25) * US  # [0,60] and [70,95]
    assert s["collective_ns"] == 45 * US  # [40,85]
    # not covered by fusion.2 [42,60]: [40,42], [60,85]
    assert s["exposed_collective_ns"] == 27 * US
    assert s["kernel_ns"] == {"custom-call": 5 * US}
    assert r["gaps"] == [(60 * US, 70 * US), (95 * US, 100 * US)]


def shifted(events, by_us):
    return [(n, s + by_us * US, d) for n, s, d in events]


def test_two_devices_two_steps_worst_device_and_gap():
    # device 1 runs the same two steps but its first fusion takes 10 us
    # less, so its busy time is lower and its idle share is higher
    dev0_ops = STEP_OPS + shifted(STEP_OPS, 100)
    dev1_step = [ev("fusion.1", 10, 30)] + STEP_OPS[1:]
    dev1_ops = dev1_step + shifted(dev1_step, 100)
    modules = STEP_MODULES + shifted(STEP_MODULES, 100)
    host = [("bench:step_call", 0, 20 * US),
            ("bench:wait_loss", 20 * US, 200 * US)]
    r = trace.reduce(
        {0: {"modules": modules, "ops": dev0_ops},
         1: {"modules": modules, "ops": dev1_ops}},
        host, "step_fn", kernel_names=["custom-call.7"])
    assert r["devices"] == 2 and r["traced_steps"] == 2
    assert r["device_busy_ms"] == pytest.approx(0.085)  # device 0
    assert r["collective_ms"] == pytest.approx(0.045)
    assert r["exposed_collective_ms"] == pytest.approx(0.027)
    assert r["kernel_ms"] == pytest.approx(0.005)
    assert r["kernel_ms_by_stem"] == {"custom-call": pytest.approx(0.005)}
    assert r["step_period_ms"] == pytest.approx(0.1)
    # device 0 is idle 30 of 200 us, device 1 50 of 200
    assert r["device_idle_pct"] == pytest.approx(25.0)
    assert r["busy_s"] == pytest.approx((170e-6 + 150e-6) / 2)
    assert r["window_s"] == pytest.approx(200e-6)
    # the breakdown is device 1's. Its longest gap joins the end of
    # step one [95,100] to the late start of step two [100,110], while
    # the host waited for the loss; [0,10] lies under the step call
    # three fusions of two steps under one stem
    assert r["device_ops"][0] == ["fusion x3", pytest.approx(104e-6)]
    assert r["idle_gaps"][0] == ["bench:wait_loss", pytest.approx(15e-6)]
    assert ["bench:step_call", pytest.approx(10e-6)] in r["idle_gaps"]
    assert len(r["idle_gaps"]) == 5


class FakeRun:
    """What a reader needs of a run."""

    def __init__(self, reduced_trace):
        self.reduced_trace = reduced_trace
        self.logged = []

    def log(self, text):
        self.logged.append(text)


def test_attention_metric_leaves_out_another_kernel_family():
    # a step with two attention calls and a Pallas norm beside them,
    # all three Mosaic calls by the HLO; a fusion that shares the
    # attention stem's name but is no Mosaic call is not a kernel
    from benchmarks.layer_metrics import attn_kernel_ms, mosaic_kernel_ms
    ops = [ev("fusion.1", 0, 20), ev("attn.3", 20, 30),
           ev("layer_norm.9", 50, 8), ev("attn.4", 58, 12),
           ev("attn_mask_fusion.2", 70, 5)]
    r = trace.reduce(
        {0: {"modules": [ev("jit_step_fn", 0, 80)], "ops": ops}}, [],
        "step_fn", kernel_names=["attn.3", "attn.4", "layer_norm.9"])
    assert r["kernel_ms_by_stem"] == {
        "attn": pytest.approx(0.042), "layer_norm": pytest.approx(0.008)}
    run = FakeRun(r)
    assert attn_kernel_ms.read(run) == pytest.approx(0.042)
    assert mosaic_kernel_ms.read(run) == pytest.approx(0.050)
    assert "layer_norm 0.008 ms" in run.logged[0]
    # no attention stem among the kernels: the reader has nothing to
    # read and the harness leaves the metric out
    r = trace.reduce(
        {0: {"modules": [ev("jit_step_fn", 0, 80)], "ops": ops}}, [],
        "step_fn", kernel_names=["layer_norm.9"])
    assert attn_kernel_ms.read(FakeRun(r)) is None
    assert mosaic_kernel_ms.read(FakeRun(r)) == pytest.approx(0.008)
    assert attn_kernel_ms.read(FakeRun({})) is None
    assert mosaic_kernel_ms.read(FakeRun({})) is None
    # a kernel that was given a name is called by it (``flash_fwd.2``,
    # PERF.md) and is found by the layer of its op_name all the same;
    # a kernel of another layer whose stem says ``attn`` is not
    from benchmarks import scopes
    hlo_text = """
ENTRY %main {
  %flash_fwd.2 = bf16[2]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(Transformer)/block_0/attn/flash_fwd"}
  %attn.3 = bf16[2]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/transpose(jvp(Transformer))/block_0/attn/pallas_call"}
  %attn.7 = bf16[2]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(Transformer)/block_0/ln_attn/pallas_call"}
  %layer_norm.9 = bf16[2]{0} custom-call(%x), custom_call_target="tpu_custom_call"
}
"""
    layers = scopes.kernel_layers(hlo_text)
    assert layers == {"flash_fwd.2": "attn", "attn.3": "attn",
                      "attn.7": "norm"}
    ops = [ev("flash_fwd.2", 0, 20), ev("attn.3", 20, 30),
           ev("layer_norm.9", 50, 8), ev("attn.7", 58, 12)]
    r = trace.reduce(
        {0: {"modules": [ev("jit_step_fn", 0, 80)], "ops": ops}}, [],
        "step_fn", kernel_names=hlo.mosaic_call_names(hlo_text),
        kernel_layers=layers)
    assert r["kernel_ms_by_layer"] == {
        "attn": pytest.approx(0.050), "norm": pytest.approx(0.012),
        "layer_norm": pytest.approx(0.008)}
    assert attn_kernel_ms.read(FakeRun(r)) == pytest.approx(0.050)
    assert mosaic_kernel_ms.read(FakeRun(r)) == pytest.approx(0.070)


def test_no_device_plane_reduces_to_nothing():
    assert trace.reduce({}, [], "step_fn") == {}


def test_one_chip_step_has_zero_collective_time():
    ops = [ev("fusion.1", 0, 40), ev("custom-call.2", 40, 10)]
    r = trace.reduce({0: {"modules": [ev("jit_step_fn", 0, 50)],
                          "ops": ops}}, [], "step_fn")
    assert r["collective_ms"] == 0.0 and r["exposed_collective_ms"] == 0.0
    assert r["device_idle_pct"] == pytest.approx(0.0)


def test_collective_named_by_the_program_is_found_by_its_opcode():
    # jax.lax.psum's all-reduce is named psum.<n> in the compiled step
    ops = [ev("fusion.1", 0, 10), ev("psum.91", 10, 5),
           ev("fusion.2", 15, 5)]
    assert trace.collective_spans(ops) == []
    r = trace.reduce_device(ops, [(0, 20 * US)], [],
                            opcodes={"psum.91": "all-reduce"})
    assert r["steps"][0]["collective_ns"] == 5 * US
    assert r["steps"][0]["exposed_collective_ns"] == 5 * US


@pytest.mark.parametrize("text,want", [
    ("%psum.91 = f32[32243712]{0:T(1024)} all-reduce(f32[32243712]"
     "{0:T(1024)} %fusion.1), channel_id=3", "all-reduce"),
    ("%attn.72 = (bf16[16,16,1024,64]{3,2,1,0:T(8,128)(2,1)}, f32[16]"
     "{0:T(1,128)}) custom-call(bf16[2]{0} %f)", "custom-call"),
    ("%x = (f32[2]{0}, u32[]{:S(2)}) all-reduce-start(f32[2]{0} %y)",
     "all-reduce-start"),
    ("all-gather-done.3", "all-gather-done.3"),
])
def test_opcode_of_a_device_event(text, want):
    assert trace.opcode_of(text) == want


def test_recorded_chip_step():
    """One train step of gpt2m_dp4 as the chip's trace has it (device
    0; my chip run, PR 22): 11 gradient all-reduces and the loss's, all
    synchronous and after the backward pass, so every nanosecond of them
    is exposed; 72 Mosaic kernel calls."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "gpt2m_dp4_step.json")
    with open(path) as f:
        rec = json.load(f)
    ops = [tuple(o) for o in rec["ops"]]
    modules = [tuple(m) for m in rec["modules"]]
    assert len(trace.collective_spans(ops, rec["opcodes"])) == 12
    r = trace.reduce({0: {"modules": modules, "ops": ops,
                          "opcodes": rec["opcodes"]}}, [], "step_fn",
                     kernel_names=rec["kernel_names"])
    assert r["device_busy_ms"] == pytest.approx(432.333984)
    assert r["collective_ms"] == pytest.approx(24.585723)
    assert r["exposed_collective_ms"] == pytest.approx(24.585723)
    assert r["kernel_ms"] == pytest.approx(104.297232)
    assert r["kernel_ms_by_stem"] == {"attn": pytest.approx(104.297232)}
    assert r["kernel_ms_by_layer"] == r["kernel_ms_by_stem"]
    assert r["device_idle_pct"] == pytest.approx(0.0177047, rel=1e-4)
    assert r["device_ops"][0] == ["attn x72", pytest.approx(0.104297232)]
    # the two while loops of the fused cross entropy are parents: their
    # time is their bodies', and is counted once
    assert sum(s for _, s in r["device_ops"]) < 0.4324


HLO_TEXT = """
ENTRY %main {
  %all-reduce-start.1 = f32[1024,256]{1,0} all-reduce-start(%fusion.3), channel_id=1
  %all-reduce-done.1 = f32[1024,256]{1,0} all-reduce-done(%all-reduce-start.1)
  %all-reduce.2 = (f32[8]{0}, bf16[4,4]{1,0}, f32[]) all-reduce(%a, %b, %c), to_apply=%sum
  %custom-call.7 = bf16[16,16,1024,64]{3,2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", backend_config={}
  %custom-call.9 = f32[4]{0} custom-call(%x), custom_call_target="Sharding"
  ROOT %custom-call.11 = (bf16[2]{0}) custom-call(%y), custom_call_target="tpu_custom_call"
}
"""


def test_hlo_allreduces_and_mosaic_calls():
    assert hlo.allreduces(HLO_TEXT) == [
        1024 * 256 * 4, 8 * 4 + 16 * 2 + 4]
    assert hlo.mosaic_call_names(HLO_TEXT) == [
        "custom-call.7", "custom-call.11"]


def sizes(name):
    return harness.load_json(harness.HERE, "configs", name + ".json")[
        "model"]


def traffic(name):
    return harness.load_json(harness.HERE, "traffic", name + ".json")


def test_gpt2_medium_operations_per_token():
    # by hand, h=1024, m=4096, 24 layers, T=1024, V=50257:
    #   blocks    24 * 2 * (4*1024^2 + 2*1024*4096) = 603,979,776
    #   attention 24 * 4 * 1024 * 1024 / 2          =  50,331,648
    #   head      2 * 1024 * 50257 * 1023/1024      = 102,825,822
    f = flops.forward_flops_per_token(
        sizes("gpt2-medium"), traffic("lm_s1024_b16_dp1"))
    assert f["blocks"] == 603_979_776
    assert f["attention"] == 50_331_648
    assert f["head"] == pytest.approx(102_825_822)
    total = flops.train_flops_per_token(
        sizes("gpt2-medium"), traffic("lm_s1024_b16_dp1"))
    assert total == pytest.approx(2.2714e9, rel=1e-4)


@pytest.mark.parametrize("mix,attention,total", [
    # full attention 24 * 4 * T * 1024; head 0.15 * 2*1024*30522
    ("mlm_s512_b26_dp1", 50_331_648, 1.9911e9),
    ("mlm_s128_b104_dp1", 12_582_912, 1.8778e9),
])
def test_bert_large_operations_per_token(mix, attention, total):
    f = flops.forward_flops_per_token(sizes("bert-large"), traffic(mix))
    assert f["attention"] == attention
    assert f["head"] == pytest.approx(0.15 * 2 * 1024 * 30522)
    assert flops.train_flops_per_token(
        sizes("bert-large"), traffic(mix)) == pytest.approx(
            total, rel=1e-4)


# shapes as dictionaries of the keys flops.py reads: one whole expert
# layer (all 64 experts of width 1024 on this chip, 8 a token, SwiGLU,
# 16 heads of 128, V=50,304 untied, seq 4096) and a grouped-query block
EXPERT_LAYER = {
    "hidden_size": 2048, "num_heads": 16, "mlp_ratio": 0.5,
    "activation": "swiglu", "num_experts": 64, "experts_per_token": 8,
    "vocab_size": 50304, "causal": True, "num_layers": 1}
GQA_LAYER = {
    "hidden_size": 4096, "num_heads": 32, "num_kv_heads": 8,
    "mlp_ratio": 3.5, "activation": "swiglu", "vocab_size": 32000,
    "causal": True, "num_layers": 1}
SEQ_4096 = {"objective": "causal_lm", "seq_len": 4096,
            "batch_per_chip": 4}


@pytest.mark.parametrize("model,blocks,attention,head", [
    # projections 4 * 2048^2 = 16,777,216; eight experts of three
    # 2048 x 1024 matrices 50,331,648; router 2048 * 64 = 131,072;
    # twice their sum. Attention 4 * 4096 * 2048 / 2. Head
    # 2 * 2048 * 50304 * 4095/4096
    (EXPERT_LAYER, 134_479_872, 16_777_216, 205_994_880),
    # q and out 2 * 4096^2, k and v 2 * 4096 * 8 * 128 (a quarter);
    # three 4096 x 14336 matrices; head 2 * 4096 * 32000 * 4095/4096
    (GQA_LAYER, 2 * (33_554_432 + 8_388_608 + 176_160_768), 33_554_432,
     262_080_000),
    # a dense MLP where experts_per_token is 0, and a head width that
    # is stated and is not hidden / heads
    ({**EXPERT_LAYER, "num_experts": 0, "experts_per_token": 0},
     2 * (16_777_216 + 6_291_456), 16_777_216, 205_994_880),
    ({**GQA_LAYER, "head_dim": 64},
     2 * (16_777_216 + 4_194_304 + 176_160_768), 16_777_216,
     262_080_000),
], ids=["expert_layer", "gqa_layer", "expert_layer_made_dense", "stated_head_dim"])
def test_operations_per_token_of_the_shapes_to_come(model, blocks,
                                                    attention, head):
    f = flops.forward_flops_per_token(model, SEQ_4096)
    assert (f["blocks"], f["attention"], f["head"]) == (
        blocks, attention, head)
    assert flops.train_flops_per_token(model, SEQ_4096) == 3 * (
        blocks + attention + head)


def test_expert_layer_counts_the_active_experts_alone():
    assert flops.train_flops_per_token(EXPERT_LAYER, SEQ_4096) == \
        1_071_755_904
    assert sum(flops.forward_flops_per_token(
        EXPERT_LAYER, SEQ_4096).values()) == 357_251_968
    # the count before PR 26 (two MLP matrices, one MLP a token, no
    # router) said 264,715,136: 26% under
    old = 2 * (4 * 2048 * 2048 + 2 * 2048 * 1024) + 16_777_216 \
        + 205_994_880
    assert old == 264_715_136 and 0.25 < 1 - old / 357_251_968 < 0.27
    # and the experts the layer holds but does not send a token to are
    # not required work
    held = flops.forward_flops_per_token(
        {**EXPERT_LAYER, "experts_per_token": 64}, SEQ_4096)["blocks"]
    assert held == 2 * (16_777_216 + 64 * 6_291_456 + 131_072)


# one chip's share of eight of a latent-attention, shared-expert model
# (the body the harness's tests hold to its source), and the same keys
# uncut: all 47 layers, all 64 experts, the whole vocabulary
SHARE = harness.load_json(
    harness.ROOT, "tests", "benchmarks", "data",
    "latent_shared_expert_share.json")["body"]["model"]
WHOLE = {**{k: v for k, v in SHARE.items() if k != "experts_held"},
         "num_layers": 47, "vocab_size": 154880}


def test_operations_per_token_of_one_chips_share_by_part():
    # by hand, h=2048, 20 heads, T=4096, multiply-adds a token:
    #   latent projections a layer
    #     query        2048*768 + 768*20*(192+64) = 1,572,864 + 3,932,160
    #     latent + rope key   2048*(512+64)       = 1,179,648
    #     keys' and values' expansion 512*20*(192+256) = 4,587,520
    #     out          20*256*2048                = 10,485,760
    #                                        sum  = 21,757,952
    #   dense MLP, three matrices 3*2048*10240    = 62,914,560
    #   expert layer: the shared expert 3*2048*1536 = 9,437,184, half a
    #     routed expert (4 a token * 8 held / 64) 4,718,592, the router
    #     at its 64 outputs 131,072               = 14,286,848
    #   blocks 2 * (5*21,757,952 + 62,914,560 + 4*14,286,848)
    #   attention a layer: scores over 256, values over 256, halved by
    #     the mask: 2*4096*20*(256+256)/2 = 41,943,040; five layers
    #   head 2*2048*19360 = 79,298,560, at 4095 of 4096 positions
    #   the second head: one more expert layer's block 2*(21,757,952 +
    #     14,286,848) = 72,089,600, the 4096 -> 2048 product
    #     2*4096*2048 = 16,777,216, its attention 41,943,040, and the
    #     head at 4094 of 4096 positions 79,298,560 - 38,720
    f = flops.forward_flops_per_token(SHARE, SEQ_4096)
    assert f == {"blocks": 457_703_424, "attention": 209_715_200,
                 "head": 79_279_200, "mtp": 210_069_696}
    assert sum(f.values()) == 956_767_520
    assert flops.train_flops_per_token(SHARE, SEQ_4096) == 2_870_302_560
    # what the count before this PR said of the same share, on the only
    # model group published.py then let the file have: four experts a
    # token at the dense layer's width, all on this chip, four full
    # 2048 x 5120 projections, no shared expert, no dense layer, no
    # second head: 3.37 times the work
    old = (2 * 5 * (4 * 2048 * 5120 + 4 * 3 * 2048 * 10240 + 2048 * 8)
           + 5 * 4 * 4096 * 20 * 256 / 2 + 79_279_200)
    assert old == 3_225_171_040 and 3.37 < old / 956_767_520 < 3.38


def test_operations_per_token_of_the_same_model_uncut():
    # by hand, as above but for the layers and what they hold:
    #   an expert layer with all 64 held: shared 9,437,184 + four
    #     routed 37,748,736 + router 131,072      = 47,316,992
    #   blocks 2 * (47*21,757,952 + 62,914,560 + 46*47,316,992)
    #        = 2 * (1,022,623,744 + 62,914,560 + 2,176,581,632)
    #   attention 47 * 41,943,040
    #   head 2*2048*154880 = 634,388,480, less 154,880 (1 of 4096)
    #   second head 2*(21,757,952 + 47,316,992) = 138,149,888, plus
    #     16,777,216, plus 41,943,040, plus 634,388,480 - 309,760
    f = flops.forward_flops_per_token(WHOLE, SEQ_4096)
    assert f == {"blocks": 6_524_239_872, "attention": 1_971_322_880,
                 "head": 634_233_600, "mtp": 830_948_864}
    assert sum(f.values()) == 9_960_745_216
    # the share holds an eighth of the experts and of the vocabulary
    # and 5 of 47 layers, yet 9.6% of the operations: attention, the
    # shared expert and the dense layer are not divided
    assert 0.096 < 956_767_520 / sum(f.values()) < 0.0961


def test_a_full_rank_query_and_plain_heads_of_two_widths():
    # a latent-attention model whose query is full rank (q_lora_rank
    # null): 2048*20*256 in place of the two latent products
    full = flops.projection_macs({**SHARE, "q_lora_rank": None})
    assert full == 21_757_952 - 5_505_024 + 2048 * 20 * 256
    # plain projections at unequal widths: q and k at 192, v and out at
    # 128, 8 key and value heads for 32
    plain = {"hidden_size": 4096, "num_heads": 32, "num_kv_heads": 8,
             "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
             "v_head_dim": 128}
    assert flops.projection_macs(plain) == 4096 * (
        32 * 192 + 8 * 192 + 8 * 128 + 32 * 128)
    # no head width stated and none to be had: refused, not rounded
    with pytest.raises(ValueError, match="no whole head width"):
        flops.head_dim({"hidden_size": 2048, "num_heads": 20})


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


def test_flops_docstring_lists_every_key_it_reads():
    """The next key is not read in silence either: what the module's
    docstring lists, with the ``KEYS`` of the one kind of layer these
    models have (``layer_kinds/attention.py``: the list was one until a
    layer's kind became a file), is what the functions ask a model
    group for, over the kinds of model there are."""
    listed = set(re.findall(
        r"``(\w+)``", flops.__doc__.split("absence means")[1]))
    listed -= {"swiglu", "attention"}  # values, not keys
    kinds = set(flops.load_kind("attention").KEYS)
    assert not listed & kinds
    asked = set()
    for model in (SHARE, {**SHARE, "q_lora_rank": None}, EXPERT_LAYER,
                  GQA_LAYER, {**GQA_LAYER, "head_dim": 64},
                  sizes("gpt2-medium"), sizes("bert-large")):
        model = Recording(model)
        flops.train_flops_per_token(model, SEQ_4096)
        flops.attention_kernel_work(model, SEQ_4096)
        asked |= model.asked
    listed |= kinds
    assert asked == listed, (asked - listed, listed - asked)
    # and the share's file states every one of them but the plain head
    # width its latent attention has no use for, a mask it has not, and
    # a pattern: its layers are all of the one kind
    assert listed - set(SHARE) == {"head_dim", "diffusion_block",
                                   "layer_types"}


def test_attention_kernel_work_of_two_head_widths_and_a_second_head():
    # the share at batch 4, T=4096: six layers' attention (five and the
    # second head's), three products over the query-and-key width 256
    # and three over the value width 256, halved by the mask; of the
    # twelve arrays q, dq, k, dk at 20 heads of 256 and o, do, v, dv at
    # 20 heads of 256
    w = flops.attention_kernel_work(SHARE, SEQ_4096)
    assert w["flops"] == 6 * 3 * 2 * 4 * 20 * 4096 * 4096 * (256 + 256) / 2
    assert w["bytes"] == 6 * 3 * (20 + 20) * 4 * 4096 * (256 + 256) * 2
    # the two widths apart: 192 + 64 for q and k, 128 for v and o
    narrow = {**SHARE, "v_head_dim": 128, "mtp_layers": 0}
    w = flops.attention_kernel_work(narrow, SEQ_4096)
    assert w["flops"] == 5 * 2 * 4 * 20 * 4096 * 4096 * (
        3 * 256 + 3 * 128) / 2
    assert w["bytes"] == 5 * 4 * 4096 * 2 * (
        3 * 20 * 256 + 3 * 20 * 256 + 3 * 20 * 128 + 3 * 20 * 128)
    f = flops.forward_flops_per_token(narrow, SEQ_4096)
    assert f["attention"] == 5 * 2 * 4096 * 20 * (256 + 128) / 2
    assert "mtp" not in f


def test_attention_kernel_work_of_grouped_query_heads():
    # 32 query heads of 128, 8 key and value heads, batch 4, T=4096:
    # operations by the query heads; of the twelve arrays q, o, do, dq
    # and forward's q, o are 32 heads wide, k, v, dk, dv and forward's
    # k, v are 8
    w = flops.attention_kernel_work(GQA_LAYER, SEQ_4096)
    assert w["flops"] == 6 * 2 * 4 * 32 * 4096 * 4096 * 128 / 2
    assert w["bytes"] == 6 * (32 + 8) * 4 * 4096 * 128 * 2
    # heads of 128 at hidden 2048: the stated head width is what counts
    w = flops.attention_kernel_work(EXPERT_LAYER, SEQ_4096)
    assert w["flops"] == 6 * 2 * 4 * 16 * 4096 * 4096 * 128 / 2
    assert w["bytes"] == 12 * 4 * 16 * 4096 * 128 * 2


def test_attention_kernel_work_and_roofline():
    # GPT-2-medium, batch 16: 24 layers * 6 products * 2*16*16*1024^2*64
    # halved by the causal mask; 12 arrays of 16*16*1024*64 bf16 a layer
    w = flops.attention_kernel_work(
        sizes("gpt2-medium"), traffic("lm_s1024_b16_dp1"))
    assert w["flops"] == 24 * 6 * 2 * 16 * 16 * 1024 * 1024 * 64 / 2
    assert w["bytes"] == 24 * 12 * 16 * 16 * 1024 * 64 * 2
    least, bound = flops.roofline_seconds(
        w, harness.peak_of("TPU v5 lite"))
    assert bound == "compute"
    assert least == pytest.approx(w["flops"] / 197e12)
    # BERT at T=128 moves more bytes than it computes: HBM-bound
    w = flops.attention_kernel_work(
        sizes("bert-large"), traffic("mlm_s128_b104_dp1"))
    assert flops.roofline_seconds(
        w, harness.peak_of("TPU v5 lite"))[1] == "hbm"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peak_of("TPU v9 imaginary")
