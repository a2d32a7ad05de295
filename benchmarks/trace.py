"""The one reduction from a profiler trace to per-layer numbers.

Input is what ``jax.profiler`` writes (``*.xplane.pb``), read with
``jax.profiler.ProfileData``. An event here is ``(name, start_ns,
duration_ns)``. Everything below :func:`load` is plain interval
arithmetic over such lists, so the tests drive it with hand-made events.

What a TPU trace looks like (jax 0.9.0 / libtpu 0.0.34, looked at by
hand in PR 22): one plane ``/device:TPU:<n>`` per chip; its line
``XLA Modules`` has one event per executed program and ``XLA Ops`` one
per HLO instruction, nested where an instruction has a body (``while``).
An event's name is the instruction's whole text
(``%psum.91 = f32[...] all-reduce(...)``): its name and its opcode are
cut from that. An instruction's name does not say what it is
(``jax.lax.psum`` leaves ``psum.<n>``, a Pallas call in ``attn`` leaves
``attn.<n>``), so collectives are found by opcode and kernels by the
names the compiled HLO gives for its ``tpu_custom_call``s; a kernel's
time is kept under its instruction's stem (``attn``) and under the
layer its ``op_name`` puts it in (``benchmarks/scopes.kernel_layers``),
so that one kernel family's metric does not take in another's calls. An
asynchronous collective is a ``<kind>-start`` and a later
``<kind>-done`` with compute in between, in flight from the beginning of
the first to the end of the second; this repo's gradient all-reduces are
synchronous today.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Iterable, Sequence

Event = tuple  # (name, start_ns, duration_ns)
Interval = tuple  # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|ragged-all-to-all)"
    r"(-start|-done)?(\.\d+)?$")


# -- intervals ---------------------------------------------------------------

def merge(spans: Iterable[Interval]) -> list:
    """Union of intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted((s, e) for s, e in spans if e > s):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(spans: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in spans))


def clip(spans: Iterable[Interval], window: Interval) -> list:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in spans
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> list:
    """The part of ``a`` that ``b`` does not cover (both merged)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def span_of(ev: Event) -> Interval:
    return (ev[1], ev[1] + ev[2])


# -- operations --------------------------------------------------------------

def collective_kind(opcode: str):
    """(kind, phase) of a collective's opcode, phase one of 'start',
    'done', 'sync'; None for any other instruction. An instruction's
    name does not say what it is (the gradient all-reduces of a
    ``jax.lax.psum`` are named ``psum.<n>``): ask with the opcode, or
    with a name only where the name is the opcode plus a number."""
    m = COLLECTIVE.match(opcode)
    if not m:
        return None
    return m.group(1), (m.group(2) or "-sync")[1:]


def collective_spans(ops: Sequence[Event], opcodes=None) -> list:
    """One interval per collective: a synchronous instruction is its
    own interval; a ``-done`` closes the oldest open ``-start`` of its
    kind and the interval runs from that start's beginning to the
    done's end. A ``-done`` with no open start (the trace began between
    them) counts from its own beginning. ``opcodes`` maps instruction
    names to opcodes; a name not in it is taken as its own opcode."""
    opcodes = opcodes or {}
    spans, open_starts = [], {}
    for ev in sorted(ops, key=lambda e: e[1]):
        kp = collective_kind(opcodes.get(ev[0], ev[0]))
        if kp is None:
            continue
        kind, phase = kp
        if phase == "sync":
            spans.append(span_of(ev))
        elif phase == "start":
            open_starts.setdefault(kind, []).append(ev)
        else:
            pending = open_starts.get(kind)
            begin = pending.pop(0)[1] if pending else ev[1]
            spans.append((begin, ev[1] + ev[2]))
    for pending in open_starts.values():  # never closed inside the trace
        spans.extend(span_of(ev) for ev in pending)
    return spans


def leaves(ops: Sequence[Event]) -> list:
    """Instructions that contain no other instruction (a ``while``
    spans its body's instructions on the same line). Events of one line
    nest and never partly overlap, so a parent is an event whose
    successor begins before it ends."""
    ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [ev for i, ev in enumerate(ordered)
            if i + 1 == len(ordered)
            or ordered[i + 1][1] >= ev[1] + ev[2]]


def self_seconds_by_name(ops: Sequence[Event]) -> dict:
    """Seconds per instruction name, a parent's time less its
    children's, so that nothing is counted twice."""
    ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
    acc: dict = {}
    stack: list = []  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            acc[name] = acc.get(name, 0.0) + self_ns / 1e9

    for name, start, dur in ordered:
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, float(dur)])
    close(float("inf"))
    return acc


_SUFFIX = re.compile(r"(\.\d+|\.remat\d*|\.clone)+$")


def stem(name: str) -> str:
    """``fusion.5839`` → ``fusion``, ``attn.72`` → ``attn``,
    ``convolution_add_fusion.20.remat`` → ``convolution_add_fusion``:
    the compiler numbers instructions, and the number changes with
    every change to the program."""
    return _SUFFIX.sub("", name)


def step_windows(modules: Sequence[Event], hint: str) -> list:
    """Intervals of the train step's executions on one device: the
    module events whose name contains ``hint``, or every module event
    when none does."""
    named = [m for m in modules if hint in m[0]]
    return sorted(span_of(m) for m in (named or modules))


def reduce_device(ops: Sequence[Event], windows: Sequence[Interval],
                  kernel_names: Sequence[str], opcodes=None,
                  kernel_layers=None) -> dict:
    """Per traced step of one device, in nanoseconds: time in which any
    instruction ran, time in which a collective was in flight, the part
    of that with no other instruction running, and the summed durations
    of the instructions named in ``kernel_names``, by their stem and by
    their layer (``kernel_layers``: instruction → layer; one it does
    not name is of the layer its stem spells)."""
    opcodes = opcodes or {}
    kernel_layers = kernel_layers or {}
    coll = merge(collective_spans(ops, opcodes))
    leaf = leaves(ops)
    compute = merge(span_of(e) for e in leaf
                    if collective_kind(opcodes.get(e[0], e[0])) is None)
    busy = merge(span_of(e) for e in ops)
    exposed = subtract(coll, compute)
    wanted = set(kernel_names)
    kernels: dict = {}  # stem -> intervals
    by_layer: dict = {}  # layer -> intervals
    for e in leaf:
        if e[0] in wanted:
            kernels.setdefault(stem(e[0]), []).append(span_of(e))
            by_layer.setdefault(kernel_layers.get(e[0], stem(e[0])),
                                []).append(span_of(e))
    steps = []
    for w in windows:
        steps.append({
            "busy_ns": total(clip(busy, w)),
            "collective_ns": total(clip(coll, w)),
            "exposed_collective_ns": total(clip(exposed, w)),
            "kernel_ns": {k: total(clip(v, w))
                          for k, v in kernels.items()},
            "kernel_layer_ns": {k: total(clip(v, w))
                                for k, v in by_layer.items()},
        })
    out = {"steps": steps}
    if windows:
        span = (windows[0][0], windows[-1][1])
        out["span"] = span
        out["busy_in_span_ns"] = total(clip(busy, span))
        out["gaps"] = subtract([span], busy)
        # start to start of consecutive executions
        out["period_ns"] = [b[0] - a[0]
                            for a, b in zip(windows, windows[1:])]
    return out


def _median(xs):
    return statistics.median(xs) if xs else None


def reduce(devices: dict, host_spans: Sequence[Event], hint: str,
           kernel_names: Sequence[str] = (), kernel_layers=None) -> dict:
    """The trace's numbers for the layer metrics.

    ``devices`` maps a device id to ``{"modules": [...], "ops": [...],
    "opcodes": {name: opcode}}`` (the last may be left out).
    Per-step values are the median over the traced steps, taken on each
    device, and the worst device is reported. ``busy_s`` is the mean
    over devices of the time an instruction ran between the first traced
    step's beginning and the last one's end; ``window_s`` is that
    span's length."""
    per_dev = {}
    for dev, lines in devices.items():
        windows = step_windows(lines["modules"], hint)
        if not windows or not lines["ops"]:
            continue
        per_dev[dev] = reduce_device(lines["ops"], windows, kernel_names,
                                     lines.get("opcodes"), kernel_layers)
        per_dev[dev]["ops"] = lines["ops"]
    if not per_dev:
        return {}

    def worst(key, of=lambda v: v):
        return max(_median([of(s[key]) for s in d["steps"]])
                   for d in per_dev.values())

    def kernel_ms_by(key):
        found = sorted({k for d in per_dev.values() for s in d["steps"]
                        for k in s[key]})
        return {k: worst(key, lambda v, k=k: v.get(k, 0.0)) / 1e6
                for k in found}

    spans = [d["span"][1] - d["span"][0] for d in per_dev.values()]
    idle = [1.0 - d["busy_in_span_ns"] / (d["span"][1] - d["span"][0])
            for d in per_dev.values()]
    periods = [p for d in per_dev.values() for p in d["period_ns"]]
    out = {
        "devices": len(per_dev),
        "traced_steps": min(len(d["steps"]) for d in per_dev.values()),
        "device_busy_ms": worst("busy_ns") / 1e6,
        "collective_ms": worst("collective_ns") / 1e6,
        "exposed_collective_ms": worst("exposed_collective_ns") / 1e6,
        # every Mosaic call, each stem's own and each layer's own
        "kernel_ms": worst("kernel_ns", lambda v: sum(v.values())) / 1e6,
        "kernel_ms_by_stem": kernel_ms_by("kernel_ns"),
        "kernel_ms_by_layer": kernel_ms_by("kernel_layer_ns"),
        "device_idle_pct": 100.0 * max(idle),
        "step_period_ms": (_median(periods) or 0.0) / 1e6,
        "busy_s": statistics.fmean(
            d["busy_in_span_ns"] for d in per_dev.values()) / 1e9,
        "window_s": max(spans) / 1e9,
    }
    # the breakdown comes from the device that was idle longest
    dev = max(per_dev, key=lambda k: 1.0 - per_dev[k]["busy_in_span_ns"]
              / (per_dev[k]["span"][1] - per_dev[k]["span"][0]))
    d = per_dev[dev]
    in_span = [e for e in d["ops"]
               if e[1] >= d["span"][0] and e[1] + e[2] <= d["span"][1]]
    # seconds over the traced steps by instruction stem, with how many
    # instructions of that stem one step runs: 72 near-equal kernel
    # calls are one line, not the whole list
    by_stem: dict = {}
    for name, secs in self_seconds_by_name(in_span).items():
        entry = by_stem.setdefault(stem(name), [0.0, 0])
        entry[0] += secs
        entry[1] += 1
    out["device_ops"] = [
        [f"{n} x{count}", secs] for n, (secs, count) in sorted(
            by_stem.items(), key=lambda kv: -kv[1][0])[:10]]
    gaps = sorted(d["gaps"], key=lambda g: g[0] - g[1])[:5]
    out["idle_gaps"] = [[open_span(host_spans, g), (g[1] - g[0]) / 1e9]
                        for g in gaps]
    return out


def open_span(host_spans: Sequence[Event], gap: Interval) -> str:
    """Name of the benchmark's host span that overlaps ``gap`` most (the
    innermost where several do); ``"no_span"`` when none was open."""
    best, best_key = "no_span", (0.0, 0.0)
    for name, start, dur in host_spans:
        ov = min(gap[1], start + dur) - max(gap[0], start)
        if ov > 0 and (ov, -dur) > best_key:
            best, best_key = name, (ov, -dur)
    return best


# -- reading the file --------------------------------------------------------

def instruction_name(event_name: str) -> str:
    """A device event is named by its whole HLO instruction
    (``%fusion.3 = bf16[...] fusion(...)``); the instruction's name is
    what precedes `` = ``, without the ``%``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def opcode_of(event_name: str) -> str:
    """``all-reduce`` of ``%psum.91 = f32[...]{...} all-reduce(...)``:
    the first lower-case word before a ``(`` that follows white space
    (the layouts' ``T(8,128)`` follow none)."""
    _, _, rest = event_name.partition(" = ")
    m = _OPCODE.search(" " + rest)
    return m.group(1) if m else instruction_name(event_name)


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str):
    """``(devices, host_spans, lines_seen)`` of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_spans, seen = {}, [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            seen.append(f"{plane.name}|{line.name}")
            if m and line.name in (MODULE_LINE, OP_LINE):
                dev = devices.setdefault(int(m.group(1)), {
                    "modules": [], "ops": [], "opcodes": {}})
                key = "modules" if line.name == MODULE_LINE else "ops"
                events = [e for e in line.events if e.duration_ns > 0]
                dev[key] = [
                    (instruction_name(e.name), e.start_ns, e.duration_ns)
                    for e in events]
                if key == "ops":
                    dev["opcodes"] = {
                        instruction_name(e.name): op for e in events
                        if collective_kind(op := opcode_of(e.name))}
            elif plane.name == HOST_PLANE:
                host_spans.extend(
                    (e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    return devices, host_spans, seen
