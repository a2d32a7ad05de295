"""Unified observability (PR 10): the xplane parser, the sampled-step
attribution math, the continuous profiler's cost contract, and the
cross-rank trace merger.

The xplane decoder (utils/xplane.py) is exercised against
hand-encoded protobuf bytes (the wire format is fixed by xplane.proto)
and — when TensorFlow happens to be installed — cross-checked against
the TF-generated parser on the same bytes, proving the no-TF fallback
decodes identically. The profiler (utils/prof.py) is tested with an
injected fake clock and stubbed capture calls so the duty-cycle gate is
deterministic; one slow-marked e2e drives a real ``jax.profiler``
capture through parse → attribute → merge (the perf gate,
scripts/perf_baseline.py, runs the same path in run_all_checks.py).
"""

import importlib.util
import json
import os
import struct
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.utils import metrics, prof, xplane  # noqa: E402


# ---------------------------------------------------------------------------
# a minimal protobuf ENCODER for the XSpace schema — the test-side twin
# of the decoder under test (field numbers from xplane.proto)
# ---------------------------------------------------------------------------

def _vint(n):
    out = b""
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _field(fn, payload):
    return _vint(fn << 3 | 2) + _vint(len(payload)) + payload


def _varint_field(fn, v):
    return _vint(fn << 3) + _vint(v)


def _event(meta_id, offset_ps, dur_ps, stats=b""):
    return (_varint_field(1, meta_id) + _varint_field(2, offset_ps)
            + _varint_field(3, dur_ps) + stats)


def _stat_str(meta_id, s):
    return _field(4, _varint_field(1, meta_id) + _field(5, s.encode()))


def _line(line_id, name, timestamp_ns, events):
    b = _varint_field(1, line_id) + _field(2, name.encode())
    b += _varint_field(3, timestamp_ns)
    for ev in events:
        b += _field(4, ev)
    return b


def _meta_entry(fn, mid, name):
    inner = _varint_field(1, mid) + _field(2, name.encode())
    return _field(fn, _varint_field(1, mid) + _field(2, inner))


def _plane(plane_id, name, lines, event_meta=(), stat_meta=()):
    b = _varint_field(1, plane_id) + _field(2, name.encode())
    for ln in lines:
        b += _field(3, ln)
    for mid, mname in event_meta:
        b += _meta_entry(4, mid, mname)
    for mid, mname in stat_meta:
        b += _meta_entry(5, mid, mname)
    return b


def _xspace(planes):
    return b"".join(_field(1, p) for p in planes)


def _tpu_capture_bytes():
    """One TPU device plane: 'XLA Ops' line with a matmul (0-100us), an
    all-reduce overlapping its tail (80-180us), and an Async DMA line
    that must be excluded from attribution."""
    em = [(1, "fusion.1"), (2, "all-reduce.3"), (3, "copy-start.2")]
    sm = [(7, "hlo_category")]
    ops_line = _line(1, "XLA Ops", 1_000_000, [
        _event(1, 0, 100_000_000, _stat_str(7, "convolution")),
        _event(2, 80_000_000, 100_000_000),
    ])
    dma_line = _line(2, "Async XLA Ops", 1_000_000, [
        _event(3, 0, 500_000_000),
    ])
    host_line = _line(3, "python-thread", 1_000_000, [
        _event(1, 0, 50_000_000),
    ])
    return _xspace([
        _plane(1, "/device:TPU:0", [ops_line, dma_line], em, sm),
        _plane(2, "/host:CPU", [host_line], em, sm),
    ])


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------

def test_parse_xspace_structure():
    xs = xplane.parse_xspace(_tpu_capture_bytes())
    assert [p.name for p in xs.planes] == ["/device:TPU:0", "/host:CPU"]
    dev = xs.planes[0]
    assert dev.event_metadata[2].name == "all-reduce.3"
    assert dev.stat_metadata[7].name == "hlo_category"
    ops = [ln for ln in dev.lines if ln.name == "XLA Ops"][0]
    assert ops.timestamp_ns == 1_000_000
    assert [e.duration_ps for e in ops.events] == [100_000_000] * 2
    assert ops.events[0].stats[0].str_value == "convolution"


def test_parse_cross_checked_against_tensorflow_proto():
    tf_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2",
        reason="TF not installed — the decoder's no-TF mode is the "
               "point; structure is covered by the hand-encoded test")
    data = _tpu_capture_bytes()
    theirs = tf_pb2.XSpace.FromString(data)
    ours = xplane.parse_xspace(data)
    assert len(ours.planes) == len(theirs.planes)
    for op, tp in zip(ours.planes, theirs.planes):
        assert op.name == tp.name
        assert {k: m.name for k, m in op.event_metadata.items()} == {
            k: m.name for k, m in tp.event_metadata.items()}
        assert len(op.lines) == len(tp.lines)
        for ol, tl in zip(op.lines, tp.lines):
            assert ol.name == tl.name
            assert ol.timestamp_ns == tl.timestamp_ns
            assert [(e.metadata_id, e.offset_ps, e.duration_ps)
                    for e in ol.events] == [
                (e.metadata_id, e.offset_ps, e.duration_ps)
                for e in tl.events]
    # and the reverse: TF re-serializes to bytes we decode identically
    assert xplane.parse_xspace(
        theirs.SerializeToString()).planes[0].name == "/device:TPU:0"


def test_load_xspace_missing_capture_raises_actionable():
    with pytest.raises(xplane.XPlaneUnavailable) as ei:
        xplane.load_xspace("/nonexistent/logdir")
    assert "jax.profiler.trace" in str(ei.value)


def test_corrupt_pb_raises_xplane_unavailable(tmp_path):
    bad = tmp_path / "x.xplane.pb"
    bad.write_bytes(b"\xff" * 64)  # endless continuation bits
    with pytest.raises(xplane.XPlaneUnavailable):
        xplane.load_xspace(str(bad))


# ---------------------------------------------------------------------------
# op extraction + attribution math
# ---------------------------------------------------------------------------

def test_op_events_selects_sync_device_line_only():
    xs = xplane.parse_xspace(_tpu_capture_bytes())
    ops = xplane.op_events(xs)
    # the Async DMA line and the host python thread are both excluded
    assert [o["name"] for o in ops] == ["fusion.1", "all-reduce.3"]
    assert [o["collective"] for o in ops] == [False, True]
    # absolute microseconds: line timestamp_ns + offset_ps
    assert ops[0]["start_us"] == pytest.approx(1_000.0)
    assert ops[1]["start_us"] == pytest.approx(1_080.0)
    with_async = xplane.op_events(xs, include_async=True)
    assert "copy-start.2" in [o["name"] for o in with_async]


def test_op_events_excludes_module_and_framework_lines():
    """'XLA Modules' / 'TensorFlow Ops' lines span whole steps; booking
    them as compute would report perfect overlap no matter how much
    wire time the step pays."""
    em = [(1, "fusion.1"), (2, "all-reduce.3"), (9, "jit_train_step")]
    ops_line = _line(1, "XLA Ops", 1_000_000, [
        _event(1, 0, 100_000_000),
        _event(2, 100_000_000, 100_000_000),  # fully exposed wire
    ])
    mod_line = _line(4, "XLA Modules", 1_000_000, [
        _event(9, 0, 200_000_000),  # the whole step as ONE span
    ])
    fw_line = _line(5, "TensorFlow Ops", 1_000_000, [
        _event(9, 0, 200_000_000),
    ])
    xs = xplane.parse_xspace(_xspace([
        _plane(1, "/device:TPU:0", [ops_line, mod_line, fw_line], em)]))
    ops = xplane.op_events(xs)
    assert [o["name"] for o in ops] == ["fusion.1", "all-reduce.3"]
    attr = xplane.attribute(ops)
    assert attr["exposed_collective_us"] == pytest.approx(100.0)
    assert attr["measured_overlap_frac"] == pytest.approx(0.0)


def test_attribute_by_plane_sees_cross_chip_stragglers():
    """Per-plane attribution: chip A busy computing must not mask chip
    B's exposed collective wait (the straggler signal)."""
    ops = [
        {"name": "fusion.1", "cat": "x", "start_us": 0.0, "dur_us": 100.0,
         "collective": False, "plane": "/device:TPU:0"},
        {"name": "all-reduce.3", "cat": "x", "start_us": 0.0,
         "dur_us": 100.0, "collective": True, "plane": "/device:TPU:1"},
    ]
    flat = xplane.attribute(ops)  # one merged axis: wire looks hidden
    assert flat["measured_overlap_frac"] == pytest.approx(1.0)
    attr = xplane.attribute_by_plane(ops)
    assert attr["planes"] == 2
    assert attr["measured_overlap_frac"] == pytest.approx(0.0)
    assert attr["exposed_collective_us"] == pytest.approx(100.0)
    # per-plane fracs average with equal weight: one chip all compute,
    # one chip all exposed wire
    assert attr["compute_frac"] == pytest.approx(0.5)
    assert attr["exposed_wire_frac"] == pytest.approx(0.5)
    assert set(attr["per_plane"]) == {"/device:TPU:0", "/device:TPU:1"}
    # single-plane input degrades to attribute() exactly
    solo = [o for o in ops if o["plane"] == "/device:TPU:0"]
    assert xplane.attribute_by_plane(solo) == xplane.attribute(solo)


def test_attribute_exposed_vs_overlapped_collective():
    xs = xplane.parse_xspace(_tpu_capture_bytes())
    attr = xplane.attribute(xplane.op_events(xs))
    # compute 0-100, collective 80-180: 20us hidden, 80us exposed,
    # device wall 180us, no gaps
    assert attr["device_wall_us"] == pytest.approx(180.0)
    assert attr["compute_us"] == pytest.approx(100.0)
    assert attr["collective_us"] == pytest.approx(100.0)
    assert attr["exposed_collective_us"] == pytest.approx(80.0)
    assert attr["idle_us"] == pytest.approx(0.0)
    assert attr["compute_frac"] == pytest.approx(100 / 180, abs=1e-4)
    assert attr["exposed_wire_frac"] == pytest.approx(80 / 180, abs=1e-4)
    assert attr["measured_overlap_frac"] == pytest.approx(0.2)


def test_attribute_host_gap_and_idle():
    ops = [
        {"name": "a", "cat": "x", "start_us": 0.0, "dur_us": 10.0,
         "collective": False},
        {"name": "b", "cat": "x", "start_us": 30.0, "dur_us": 10.0,
         "collective": False},
    ]
    attr = xplane.attribute(ops, host_wall_us=80.0)
    assert attr["device_wall_us"] == pytest.approx(40.0)
    assert attr["idle_us"] == pytest.approx(20.0)  # the 10-30 gap
    assert attr["host_wall_us"] == pytest.approx(80.0)
    assert attr["host_gap_frac"] == pytest.approx(40 / 80)
    assert attr["compute_frac"] == pytest.approx(20 / 80)
    # fully-hidden wire reads 1.0; no collectives reads None
    assert attr["measured_overlap_frac"] is None


def test_merge_intervals_and_intersection():
    assert xplane.merge_intervals([]) == []
    assert xplane.merge_intervals([(0, 1), (1, 2), (5, 6), (4, 5.5)]) == [
        (0, 2), (4, 6)]
    assert xplane._intersect([(0, 10)], [(5, 15), (20, 30)]) == [(5, 10)]


# ---------------------------------------------------------------------------
# the continuous profiler's cost contract (fake clock, stubbed capture)
# ---------------------------------------------------------------------------

@pytest.fixture
def clean_prof():
    prof.reset()
    metrics.reset()
    yield
    prof.reset()
    metrics.reset()


def _stub_capture(monkeypatch, clock, capture_cost_s=0.5, parse_cost_s=0.0):
    """Replace jax.profiler start/stop and the off-thread parse with
    deterministic fakes that advance the injected clock."""
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: clock.__setitem__(0, clock[0]
                                                    + capture_cost_s))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: clock.__setitem__(0, clock[0]
                                                  + capture_cost_s))

    def fake_spawn(token, host_wall_s):
        prof._finish_sample(token.capture_overhead_s + parse_cost_s)

    monkeypatch.setattr(prof, "_spawn_parse", fake_spawn)
    monkeypatch.setattr(prof, "_write_sidecar", lambda t, w: None)


def test_off_by_default_and_no_wrapper_registered(clean_prof):
    from horovod_tpu.core.knobs import Knobs

    prof.configure(Knobs())  # prof_every defaults to 0
    assert not prof.active()
    assert metrics._step_wrapper is None
    # metrics.step() stays on its no-op fast path: nothing counts steps
    with metrics.step():
        pass
    assert prof.summary()["steps"] == 0


def test_duty_cycle_gates_the_next_sample(clean_prof, monkeypatch, tmp_path):
    clock = [100.0]
    prof.configure(every=1, duty_cycle=0.5, directory=str(tmp_path),
                   clock=lambda: clock[0])
    _stub_capture(monkeypatch, clock)  # 0.5s start + 0.5s stop = 1.0s
    assert prof.active() and metrics._step_wrapper is not None

    with metrics.step():
        clock[0] += 0.1
    assert prof.sample_count() == 1
    assert prof.overhead_s() == pytest.approx(1.0)
    # duty 0.5 → after a 1.0s sample the gate stays shut 1.0s; a step
    # arriving inside the budget window must NOT sample
    with metrics.step():
        clock[0] += 0.1
    assert prof.sample_count() == 1
    clock[0] += 1.0  # idle past the budget window
    with metrics.step():
        clock[0] += 0.1
    assert prof.sample_count() == 2
    assert prof.overhead_s() == pytest.approx(2.0)


def test_sampling_respects_every_n(clean_prof, monkeypatch, tmp_path):
    clock = [0.0]
    prof.configure(every=3, duty_cycle=0.9, directory=str(tmp_path),
                   clock=lambda: clock[0])
    _stub_capture(monkeypatch, clock, capture_cost_s=0.001)
    for _ in range(9):
        with metrics.step():
            clock[0] += 1.0
    assert prof.summary()["steps"] == 9
    assert prof.sample_count() == 3  # steps 3, 6, 9


class _FakeV5e:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_peak_flops_resolves_from_device_kind_only():
    from horovod_tpu.utils import mfu

    assert mfu.peak_flops_per_chip("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="Quantum 9000"):
        mfu.peak_flops_per_chip("Quantum 9000")
    # no default generation: the CPU test world is an unknown device
    with pytest.raises(ValueError, match="cpu"):
        mfu.peak_flops_per_chip()
    assert mfu.mfu_or_none(1e12) is None
    assert mfu.format_mfu(None) == "MFU not measured"


def test_mfu_not_published_off_tpu(clean_prof):
    """A CPU world has no published peak: declaring the model cost arms
    the step wrapper but no hvd_mfu ever appears."""
    clock = [0.0]
    metrics.enable()
    prof.configure(every=0, clock=lambda: clock[0])
    prof.set_step_flops(1e9, n_chips=1)
    with metrics.step():
        clock[0] += 0.010
    assert prof.last_mfu() is None
    assert "hvd_mfu" not in metrics.registry.snapshot()


def test_mfu_gauge_and_jsonl(clean_prof, tmp_path, monkeypatch):
    import jax

    from horovod_tpu.utils import mfu

    clock = [50.0]
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeV5e()])
    peak = mfu.peak_flops_per_chip()
    assert peak == 197e12
    metrics.enable()
    log = str(tmp_path / "steps.jsonl")
    metrics.step_stats.open_log(log)
    prof.configure(every=0, clock=lambda: clock[0])
    # 1% of peak at a 10ms step on one chip
    prof.set_step_flops(0.01 * peak * 0.010, n_chips=1)
    assert prof.active()  # MFU-only mode still needs the step wrapper
    with metrics.step():
        clock[0] += 0.010
    assert prof.last_mfu() == pytest.approx(0.01, rel=1e-6)
    snap = metrics.registry.snapshot()
    assert snap["hvd_mfu"][""] == pytest.approx(0.01, rel=1e-6)
    metrics.step_stats.close_log()
    rec = json.loads(open(log).read().splitlines()[0])
    assert rec["mfu"] == pytest.approx(0.01, rel=1e-6)


def test_record_step_attribution_exports_gauges(clean_prof):
    metrics.enable()
    metrics.record_step_attribution({
        "compute_frac": 0.7, "exposed_wire_frac": 0.1,
        "idle_frac": 0.05, "measured_overlap_frac": 0.8,
        "sampled_step": 12,
    })
    snap = metrics.registry.snapshot()
    assert snap["hvd_step_compute_frac"][""] == 0.7
    assert snap["hvd_step_exposed_wire_frac"][""] == 0.1
    assert snap["hvd_step_idle_frac"][""] == 0.05
    assert snap["hvd_overlap_window_measured_frac"][""] == 0.8


def test_sample_dir_retention(clean_prof, tmp_path):
    """A continuous run keeps only the newest K capture dirs — tmpdir
    must not grow without bound — and newest means mtime, so a
    restarted run's fresh low-step captures beat a dead run's stale
    high-step leftovers in the same root."""
    import time as _time

    prof.configure(every=1, directory=str(tmp_path))
    root = prof.default_dir()
    os.makedirs(root, exist_ok=True)
    t0 = _time.time()
    # step101: a previous run's stale leftover (oldest mtime, biggest N)
    for i, n in enumerate([101] + list(range(1, 13))):
        d = os.path.join(root, f"step{n}")
        os.makedirs(d)
        os.utime(d, (t0 + i, t0 + i))
    open(os.path.join(root, "not_a_step"), "w").close()  # untouched
    prof._prune_samples()
    kept = sorted(os.listdir(root))
    assert "not_a_step" in kept
    steps = sorted(int(d[4:]) for d in kept if d.startswith("step"))
    assert steps == [5, 6, 7, 8, 9, 10, 11, 12]  # newest 8 by mtime


def test_disarm_returns_to_noop_fast_path(clean_prof):
    """Turning sampling AND MFU off must unregister the step wrapper —
    metrics.step() goes back to the no-op branch, not a per-step
    token allocation."""
    prof.configure(every=2, duty_cycle=0.5)
    assert prof.active() and metrics._step_wrapper is not None
    prof.configure(every=0)
    assert not prof.active() and metrics._step_wrapper is None
    prof.set_step_flops(100.0)  # MFU-only mode re-arms...
    assert prof.active() and metrics._step_wrapper is not None
    prof.set_step_flops(0.0)    # ...and clearing it disarms again
    assert not prof.active() and metrics._step_wrapper is None
    with metrics.step():
        pass
    assert prof.summary()["steps"] == 0


def test_shutdown_unregisters_wrapper(clean_prof, monkeypatch, tmp_path):
    clock = [0.0]
    prof.configure(every=1, duty_cycle=0.9, directory=str(tmp_path),
                   clock=lambda: clock[0])
    _stub_capture(monkeypatch, clock, capture_cost_s=0.001)
    with metrics.step():
        clock[0] += 0.01
    assert prof.sample_count() == 1
    prof.on_shutdown()
    assert not prof.active()
    assert metrics._step_wrapper is None
    with metrics.step():
        clock[0] += 0.01
    assert prof.summary()["steps"] == 1  # no longer counting


# ---------------------------------------------------------------------------
# trace merger (scripts/trace_merge.py) on synthetic sources
# ---------------------------------------------------------------------------

def _load_trace_merge():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "trace_merge.py")
    spec = importlib.util.spec_from_file_location("trace_merge", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_timeline(path, rank, t0_unix, events):
    """A host timeline file as utils/timeline.py writes it: the
    CLOCK_ANCHOR instant first, then B/E spans on a relative axis."""
    evs = [{"ph": "i", "name": "CLOCK_ANCHOR", "ts": 1000.0, "pid": 1,
            "tid": "clock",
            "args": {"time_unix": t0_unix, "rank": rank, "pid": 1}}]
    for name, ts_rel_us, ph in events:
        evs.append({"ph": ph, "name": name, "ts": 1000.0 + ts_rel_us,
                    "pid": 1, "tid": "t"})
    with open(path, "w") as f:
        json.dump(evs, f)


def _write_flight(path, rank, t0_unix, offset_s):
    lines = [json.dumps({"flight_header": 1, "rank": rank,
                         "reason": "test", "clock_offset_s": offset_s,
                         "time_unix": t0_unix, "events": 1})]
    lines.append(json.dumps({"seq": 0, "t_mono": 1.0,
                             "t_wall": t0_unix + 0.010,
                             "kind": "exec", "name": "g0"}))
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def _write_prof_sample(d, rank, t0_unix, offset_s):
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "hvd_prof_meta.json"), "w") as f:
        json.dump({"hvd_prof_meta": 1, "rank": rank, "step": 2,
                   "t_start_unix": t0_unix,
                   "t_stop_unix": t0_unix + 0.2,
                   "clock_offset_s": offset_s}, f)
    pb_dir = os.path.join(d, "plugins", "profile", "run")
    os.makedirs(pb_dir, exist_ok=True)
    with open(os.path.join(pb_dir, "host.xplane.pb"), "wb") as f:
        f.write(_tpu_capture_bytes())


def test_trace_merge_aligns_ranks_on_one_clock(tmp_path):
    tm = _load_trace_merge()
    t0 = 1_700_000_000.0
    # rank 1's wall clock runs 2s BEHIND the driver: offset +2.0
    _write_timeline(str(tmp_path / "tl_rank0.json"), 0, t0,
                    [("STEP", 0.0, "B"), ("STEP", 100.0, "E")])
    _write_timeline(str(tmp_path / "tl_rank1.json"), 1, t0 - 2.0,
                    [("STEP", 50.0, "B"), ("STEP", 150.0, "E")])
    _write_flight(str(tmp_path / "flight_rank0.jsonl"), 0, t0, 0.0)
    _write_flight(str(tmp_path / "flight_rank1.jsonl"), 1, t0 - 2.0, 2.0)
    _write_prof_sample(str(tmp_path / "prof" / "rank0" / "step2"), 0,
                       t0 + 0.001, 0.0)
    merged = str(tmp_path / "merged.json")
    report_p = str(tmp_path / "report.json")
    rc = tm.main([
        "--timeline", str(tmp_path / "tl_rank0.json"),
        "--timeline", str(tmp_path / "tl_rank1.json"),
        "--flight", str(tmp_path / "flight_rank0.jsonl"),
        "--flight", str(tmp_path / "flight_rank1.jsonl"),
        "--xplane", str(tmp_path / "prof"),
        "--out", merged, "--json", report_p,
    ])
    assert rc == 0
    report = json.load(open(report_p))
    assert report["ranks"] == [0, 1]
    assert report["by_source"] == {
        "rank0/host": 2, "rank1/host": 2,
        "rank0/flight": 1, "rank1/flight": 1,
        "rank0/device": 2,
    }
    trace = json.load(open(merged))
    evs = [e for e in trace["traceEvents"] if e["ph"] != "M"]
    # rank 1's STEP begin was stamped t0-2.0+50us on ITS clock; shifted
    # by its +2.0 offset it lands 50us after rank 0's STEP begin on the
    # merged axis — the aligned-clock property the smoke gate asserts
    b0 = next(e for e in evs if e["pid"] == 0 and e["name"] == "STEP"
              and e["ph"] == "B")
    b1 = next(e for e in evs if e["pid"] == 1 and e["name"] == "STEP"
              and e["ph"] == "B")
    assert b1["ts"] - b0["ts"] == pytest.approx(50.0, abs=1.0)
    # device ops become X completes with their xplane durations
    dev = [e for e in evs if e["pid"] == 0
           and e["tid"].startswith("device:")]
    assert {e["name"] for e in dev} == {"fusion.1", "all-reduce.3"}
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in dev)
    coll = next(e for e in dev if e["name"] == "all-reduce.3")
    assert coll["cat"] == "collective"
    # flight instants carry their detail payload
    fl = [e for e in evs if e["tid"] == "flight"]
    assert len(fl) == 2 and all(e["ph"] == "i" for e in fl)


def test_trace_merge_skips_sample_without_wall_anchor(tmp_path, capsys):
    """A torn/missing hvd_prof_meta.json must not place the sample's
    ops at the 1970 epoch and stretch the merged axis by decades."""
    tm = _load_trace_merge()
    d = str(tmp_path / "rank0" / "step2")
    _write_prof_sample(d, 0, 1_700_000_000.0, 0.0)
    with open(os.path.join(d, "hvd_prof_meta.json"), "w") as f:
        f.write('{"hvd_prof_meta": 1, "rank": 0')  # truncated JSON
    assert tm.load_xplane_sample(d) is None
    assert "wall anchor" in capsys.readouterr().err


def test_trace_merge_refuses_anchorless_timeline(tmp_path, capsys):
    tm = _load_trace_merge()
    legacy = str(tmp_path / "legacy.json")
    with open(legacy, "w") as f:
        json.dump([{"ph": "i", "name": "X", "ts": 0.0, "pid": 1,
                    "tid": "t"}], f)
    rc = tm.main(["--timeline", legacy,
                  "--out", str(tmp_path / "m.json")])
    assert rc == 1  # no mergeable source at all
    assert "CLOCK_ANCHOR" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# real capture e2e (slow: jax.profiler sessions cost seconds on CPU;
# the perf gate runs this same path in run_all_checks.py)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_profiler_e2e_real_capture(clean_prof, tmp_path):
    import jax
    import jax.numpy as jnp

    metrics.enable()
    prof.configure(every=2, duty_cycle=1.0, directory=str(tmp_path))
    prof.set_step_flops(2.0 * 128 ** 3, n_chips=1)
    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()
    for _ in range(2):
        with metrics.step():
            f(x).block_until_ready()
        prof.join(timeout_s=60.0)
    s = prof.summary()
    assert s["samples"] == 1 and s["errors"] == 0
    attr = prof.last_attribution()
    assert attr and attr["compute_frac"] > 0
    assert 0.0 <= attr["exposed_wire_frac"] <= 1.0
    assert attr["sampled_step"] == 2
    assert prof.last_mfu() is None  # CPU world: MFU is not measured
    # the sidecar anchors the capture for trace_merge
    sample_dirs = []
    for root, _dirs, files in os.walk(str(tmp_path)):
        if "hvd_prof_meta.json" in files:
            sample_dirs.append(root)
    assert len(sample_dirs) == 1
    meta = json.load(open(os.path.join(sample_dirs[0],
                                       "hvd_prof_meta.json")))
    assert meta["rank"] == prof._flight.rank()
    assert meta["t_stop_unix"] >= meta["t_start_unix"]
    tm = _load_trace_merge()
    sm = tm.load_xplane_sample(sample_dirs[0])
    assert sm is not None and sm["events"]
