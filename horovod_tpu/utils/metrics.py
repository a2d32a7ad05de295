"""Unified runtime telemetry: metrics registry + per-step stats.

The reference's only observability surfaces are offline — the Chrome-trace
timeline (timeline.cc) and the stall inspector's log warnings
(stall_inspector.cc). This module is the live counterpart: a thread-safe
registry of counters, gauges and fixed-bucket histograms that the hot
paths (ops/collectives.py, ops/eager_runtime.py, ops/fusion.py,
optim/distributed.py, elastic transitions, the native runtime's
cycle/cache stats) feed while training runs, exposed as

  * Prometheus text format on ``GET /metrics`` — mounted on the
    rendezvous/KV HTTP server (runner/http/http_server.py) and, with
    ``HOROVOD_METRICS_PORT``, on a standalone per-worker endpoint;
  * an optional JSON-lines per-step log (``HOROVOD_TPU_METRICS_FILE``)
    rendered by ``scripts/metrics_summary.py``.

Cost discipline: everything is OFF by default and every hot-path record
function begins with a module-level ``if not _enabled: return`` — the
whole subsystem costs one predicted-not-taken branch + a function call
(<1 µs) per site when disabled (tests/test_metrics.py asserts this).
Enabled, updates are dict lookups + float adds under per-family locks;
no I/O happens on the hot path (the JSONL writer runs at step
boundaries, the HTTP server in its own thread).
"""

from __future__ import annotations

import bisect
import contextlib
import json
import os
import re
import threading
import time
import urllib.request
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from . import flight as _flight

# ---------------------------------------------------------------------------
# module-level enable gate (the no-op fast path)
# ---------------------------------------------------------------------------

_enabled = False
_configured = False  # True when init()/configure() turned metrics on


def enabled() -> bool:
    """Whether telemetry is recording. Hot paths check this themselves;
    callers composing larger records (e.g. a stats dict) should gate on
    it to skip the assembly work too."""
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

# Latency histogram buckets (seconds): 50µs .. 10s, roughly 1-2.5-5 per
# decade — wide enough for host-side negotiation AND whole-step times.
LATENCY_BUCKETS: Tuple[float, ...] = (
    50e-6, 100e-6, 250e-6, 500e-6, 1e-3, 2.5e-3, 5e-3, 10e-3, 25e-3,
    50e-3, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)

# Fill-ratio buckets (dimensionless 0..1] for fusion-buffer utilization.
RATIO_BUCKETS: Tuple[float, ...] = (
    0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0,
)


def _escape_label(v: str) -> str:
    return v.replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n")


def _fmt(v: float) -> str:
    if v != v:
        return "NaN"
    if v in (float("inf"), float("-inf")):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class _Counter:
    __slots__ = ("value", "_lock")

    def __init__(self, lock: threading.Lock) -> None:
        self.value = 0.0
        self._lock = lock

    def inc(self, v: float = 1.0) -> None:
        with self._lock:
            self.value += v


class _Gauge:
    __slots__ = ("value", "_lock")

    def __init__(self, lock: threading.Lock) -> None:
        self.value = 0.0
        self._lock = lock

    def set(self, v: float) -> None:
        self.value = float(v)

    def inc(self, v: float = 1.0) -> None:
        with self._lock:
            self.value += v


class _Histogram:
    __slots__ = ("buckets", "counts", "sum", "count", "_lock")

    def __init__(self, buckets: Sequence[float],
                 lock: threading.Lock) -> None:
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self.counts = [0] * (len(self.buckets) + 1)  # last = +Inf
        self.sum = 0.0
        self.count = 0
        self._lock = lock

    def observe(self, v: float) -> None:
        with self._lock:
            self.counts[bisect.bisect_left(self.buckets, v)] += 1
            self.sum += v
            self.count += 1


class MetricFamily:
    """One named metric with a fixed label set; children keyed by the
    label-value tuple (the Prometheus data model)."""

    def __init__(self, name: str, kind: str, help: str,
                 labelnames: Sequence[str] = (),
                 buckets: Optional[Sequence[float]] = None):
        if kind not in ("counter", "gauge", "histogram"):
            raise ValueError(f"unknown metric kind {kind!r}")
        self.name = name
        self.kind = kind
        self.help = help
        self.labelnames = tuple(labelnames)
        self._buckets = tuple(buckets) if buckets else LATENCY_BUCKETS
        self._lock = threading.Lock()
        self._children: Dict[tuple, object] = {}

    def labels(self, *values, **kv):
        if kv:
            if values:
                raise ValueError("pass label values positionally OR by name")
            values = tuple(kv[n] for n in self.labelnames)
        key = tuple(str(v) for v in values)
        if len(key) != len(self.labelnames):
            raise ValueError(
                f"{self.name} expects labels {self.labelnames}, got {key}"
            )
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.get(key)
                if child is None:
                    # children share the family lock: updates are
                    # read-modify-write sequences (value += v, bucket +
                    # sum + count), so concurrent recorders would lose
                    # increments without it
                    child = {
                        "counter": lambda: _Counter(self._lock),
                        "gauge": lambda: _Gauge(self._lock),
                        "histogram": lambda: _Histogram(
                            self._buckets, self._lock),
                    }[self.kind]()
                    self._children[key] = child
        return child

    # no-label conveniences
    def inc(self, v: float = 1.0) -> None:
        self.labels().inc(v)

    def set(self, v: float) -> None:
        self.labels().set(v)

    def observe(self, v: float) -> None:
        self.labels().observe(v)

    # -- rendering ---------------------------------------------------------

    def _labelstr(self, key: tuple, extra: str = "") -> str:
        parts = [
            f'{n}="{_escape_label(v)}"'
            for n, v in zip(self.labelnames, key)
        ]
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""

    def render(self) -> List[str]:
        lines = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} {self.kind}",
        ]
        with self._lock:
            items = sorted(self._children.items())
        for key, child in items:
            if self.kind in ("counter", "gauge"):
                lines.append(
                    f"{self.name}{self._labelstr(key)} {_fmt(child.value)}"
                )
            else:
                with self._lock:  # consistent (counts, sum, count) triple
                    counts = list(child.counts)
                    hsum, hcount = child.sum, child.count
                cum = 0
                for b, c in zip(child.buckets, counts):
                    cum += c
                    le = 'le="' + _fmt(b) + '"'
                    lines.append(
                        f"{self.name}_bucket{self._labelstr(key, le)} {cum}"
                    )
                cum += counts[-1]
                inf_labels = self._labelstr(key, 'le="+Inf"')
                lines.append(f"{self.name}_bucket{inf_labels} {cum}")
                lines.append(
                    f"{self.name}_sum{self._labelstr(key)} {_fmt(hsum)}"
                )
                lines.append(
                    f"{self.name}_count{self._labelstr(key)} {hcount}"
                )
        return lines

    def snapshot(self) -> dict:
        out = {}
        with self._lock:
            items = sorted(self._children.items())
        for key, child in items:
            k = ",".join(key)
            if self.kind in ("counter", "gauge"):
                out[k] = child.value
            else:
                with self._lock:
                    out[k] = {"count": child.count, "sum": child.sum}
        return out


class MetricsRegistry:
    """Thread-safe family registry + pre-scrape collector hooks."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._families: Dict[str, MetricFamily] = {}
        self._collectors: List[Callable[[], None]] = []

    def _family(self, name: str, kind: str, help: str,
                labelnames: Sequence[str] = (),
                buckets: Optional[Sequence[float]] = None) -> MetricFamily:
        fam = self._families.get(name)
        if fam is None:
            with self._lock:
                fam = self._families.get(name)
                if fam is None:
                    fam = MetricFamily(name, kind, help, labelnames, buckets)
                    self._families[name] = fam
        if fam.kind != kind or fam.labelnames != tuple(labelnames):
            raise ValueError(
                f"metric {name} re-registered with different "
                f"kind/labels ({fam.kind}/{fam.labelnames} vs "
                f"{kind}/{tuple(labelnames)})"
            )
        return fam

    def counter(self, name, help="", labelnames=()) -> MetricFamily:
        return self._family(name, "counter", help, labelnames)

    def gauge(self, name, help="", labelnames=()) -> MetricFamily:
        return self._family(name, "gauge", help, labelnames)

    def histogram(self, name, help="", labelnames=(),
                  buckets=None) -> MetricFamily:
        return self._family(name, "histogram", help, labelnames, buckets)

    def register_collector(self, fn: Callable[[], None]) -> None:
        """`fn` runs before every render/snapshot — the pull hook for
        sources that keep their own cumulative state (native runtime)."""
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def unregister_collector(self, fn: Callable[[], None]) -> None:
        with self._lock:
            if fn in self._collectors:
                self._collectors.remove(fn)

    def collect(self) -> None:
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn()
            except Exception:
                pass  # a dead provider must not break the scrape

    def render(self) -> str:
        self.collect()
        lines: List[str] = []
        with self._lock:
            fams = sorted(self._families.values(), key=lambda f: f.name)
        for fam in fams:
            lines.extend(fam.render())
        return "\n".join(lines) + ("\n" if lines else "")

    def snapshot(self) -> dict:
        self.collect()
        with self._lock:
            fams = sorted(self._families.values(), key=lambda f: f.name)
        return {f.name: f.snapshot() for f in fams}

    def clear(self) -> None:
        with self._lock:
            self._families.clear()
            self._collectors.clear()


registry = MetricsRegistry()


PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


def scrape() -> str:
    """Prometheus text exposition of the process-local registry."""
    return registry.render()


def exposition(
    pushed: Optional[Dict[str, bytes]] = None,
) -> Tuple[str, bytes]:
    """(content-type, body) for serving a scrape over HTTP — the one
    definition the standalone endpoint, the serving server and the
    rendezvous server all mount (runner/http/http_server.py).

    With ``pushed`` (rank label → exposition payload, as collected by
    the rendezvous server from worker ``PUT /metrics_push/<rank>``
    calls) the scrape is **cluster-aggregated**: this process's series
    stay unlabeled and every pushed series gains a ``rank="<r>"``
    label, so one endpoint answers for the whole world. A pod relay
    (multipod/relay.py) forwards its pod's pushes under
    ``<rank>@<pod>`` keys; those series additionally gain a
    ``pod="<pod>"`` label, so the aggregated scrape rolls up by pod
    with one PromQL ``sum by (pod)``."""
    if not pushed:
        return PROM_CONTENT_TYPE, scrape().encode()
    payloads: List[Tuple[str, str]] = [("", scrape())]
    for rank_label in sorted(pushed, key=lambda r: (len(r), r)):
        body = pushed[rank_label]
        text = (body.decode("utf-8", "replace")
                if isinstance(body, (bytes, bytearray)) else str(body))
        payloads.append((rank_label, text))
    return PROM_CONTENT_TYPE, merge_expositions(payloads).encode()


#: rendezvous KV scope worker metric pushes land in (the aggregation
#: source for the rendezvous /metrics mount)
METRICS_PUSH_SCOPE = "metrics_push"


def merge_expositions(payloads: Iterable[Tuple[str, str]]) -> str:
    """Merge Prometheus text payloads into one exposition, injecting a
    ``rank`` label into every sample of a non-empty-labeled payload
    (and a ``pod`` label when the payload key is ``<rank>@<pod>`` —
    the relay-forwarded form, multipod/relay.py). Families are
    regrouped so HELP/TYPE headers appear once, before all of a
    family's samples (what parsers and :func:`lint_exposition`
    require)."""
    help_: Dict[str, str] = {}
    type_: Dict[str, str] = {}
    samples: Dict[str, List[str]] = {}
    order: List[str] = []
    for rank_label, text in payloads:
        fam: Optional[str] = None
        for line in text.splitlines():
            if line.startswith("# HELP ") or line.startswith("# TYPE "):
                name, _, tail = line[7:].partition(" ")
                if not name:
                    continue
                target = help_ if line.startswith("# HELP ") else type_
                target.setdefault(name, tail)
                fam = name
                continue
            if not line.strip() or line.startswith("#"):
                continue
            key, _, val = line.rpartition(" ")
            if not key:
                continue
            name, brace, labels = key.partition("{")
            family = (
                fam if fam and name in (
                    fam, fam + "_bucket", fam + "_sum", fam + "_count")
                else name
            )
            if rank_label:
                rank_part, _, pod_part = str(rank_label).partition("@")
                extra = f'rank="{_escape_label(rank_part)}"'
                if pod_part:
                    extra += f',pod="{_escape_label(pod_part)}"'
                inner = labels[:-1] if brace else ""
                line = (
                    f"{name}{{"
                    + (inner + "," if inner else "")
                    + extra + f"}} {val}"
                )
            bucket = samples.get(family)
            if bucket is None:
                bucket = samples[family] = []
                order.append(family)
            bucket.append(line)
    out: List[str] = []
    for family in order:
        if family in help_:
            out.append(f"# HELP {family} {help_[family]}")
        if family in type_:
            out.append(f"# TYPE {family} {type_[family]}")
        out.extend(samples[family])
    return "\n".join(out) + ("\n" if out else "")


# -- exposition lint (test helper; docs/metrics.md) -------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"           # metric name
    r"(?:\{(.*)\})?"                          # optional label block
    r" (NaN|[+-]?Inf|-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)$"
)
_LABEL_RE = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"$')
_PROM_KINDS = ("counter", "gauge", "histogram", "summary", "untyped")


def _split_labels(block: str) -> List[str]:
    """Split 'a="x",b="y,z"' on commas outside quotes."""
    parts, buf, in_q, esc = [], [], False, False
    for ch in block:
        if esc:
            buf.append(ch)
            esc = False
        elif ch == "\\":
            buf.append(ch)
            esc = True
        elif ch == '"':
            buf.append(ch)
            in_q = not in_q
        elif ch == "," and not in_q:
            parts.append("".join(buf))
            buf = []
        else:
            buf.append(ch)
    if buf:
        parts.append("".join(buf))
    return parts


def lint_exposition(text: str) -> List[str]:
    """Validate Prometheus text exposition; returns a list of problems
    (empty = parseable). Checks: sample-line grammar, label syntax,
    TYPE kinds, TYPE-before-samples, duplicate series, and histogram
    bucket monotonicity with a closing ``le="+Inf"``. Used by the
    regression tests that scrape /metrics under concurrent registry
    mutation — both the process-local and the rank-aggregated output
    must stay parseable at any instant."""
    errors: List[str] = []
    typed: Dict[str, str] = {}
    seen: set = set()
    hist: Dict[Tuple[str, str], List[Tuple[float, float]]] = {}
    for i, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            name, _, tail = line[7:].partition(" ")
            if not name:
                errors.append(f"line {i}: malformed comment header")
                continue
            if line.startswith("# TYPE "):
                if tail not in _PROM_KINDS:
                    errors.append(f"line {i}: unknown TYPE {tail!r}")
                if name in typed:
                    errors.append(f"line {i}: duplicate TYPE for {name}")
                typed[name] = tail
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            errors.append(f"line {i}: unparseable sample: {line!r}")
            continue
        name, labels, val = m.groups()
        label_parts = _split_labels(labels) if labels else []
        for part in label_parts:
            if not _LABEL_RE.match(part):
                errors.append(f"line {i}: bad label {part!r}")
        key = (name, labels or "")
        if key in seen:
            errors.append(f"line {i}: duplicate series {name}{{{labels}}}")
        seen.add(key)
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in typed:
                family = name[: -len(suffix)]
                break
        if family not in typed:
            errors.append(
                f"line {i}: sample {name} precedes its TYPE header")
        if typed.get(family) == "histogram" and name == family + "_bucket":
            le, rest = None, []
            for part in label_parts:
                if part.startswith('le="'):
                    le = part[4:-1]
                else:
                    rest.append(part)
            if le is None:
                errors.append(f"line {i}: histogram bucket missing le=")
            else:
                hist.setdefault((family, ",".join(rest)), []).append(
                    (float("inf") if le == "+Inf" else float(le),
                     float(m.group(3)))
                )
    for (family, series), buckets in hist.items():
        buckets.sort(key=lambda b: b[0])
        if not buckets or buckets[-1][0] != float("inf"):
            errors.append(
                f'{family}{{{series}}}: histogram lacks le="+Inf"')
        cum = -1.0
        for le, v in buckets:
            if v < cum:
                errors.append(
                    f"{family}{{{series}}}: bucket counts not "
                    f"cumulative at le={le}")
                break
            cum = v
    return errors


# ---------------------------------------------------------------------------
# per-step aggregation
# ---------------------------------------------------------------------------

class StepStats:
    """Accumulates per-interval telemetry between ``begin_step`` /
    ``end_step`` and emits one JSONL record per step: step time,
    collective count/bytes by (op, dtype), fusion fill ratio, cache hit
    rate, negotiation latency, eager queue depth, elastic transitions —
    the live analog of replaying a timeline after the run."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._log_fh = None
        self._log_path = ""
        self.step = 0
        self._t0: Optional[float] = None
        self._last_native: Dict[str, float] = {}
        self._reset_interval()

    def _reset_interval(self) -> None:
        self.collectives: Dict[str, List[float]] = {}  # op/dtype -> [n, B]
        self.neg_count = 0
        self.neg_sum = 0.0
        self.fusion_plans = 0
        self.fusion_buckets = 0
        self.fusion_fill_sum = 0.0
        self.grad_bytes = 0
        self.wire_logical = 0
        self.wire_sent = 0
        self.overlap_window = None  # staged-scheduler pin (0..1)
        self.fsdp_param_bytes = None  # per-device resident param bytes
        self.fsdp_gather_bytes = 0    # forward all-gather bytes
        self.fsdp_regather_bytes = 0  # backward re-gather bytes
        self.fsdp_offload_bytes = 0   # stage carries parked in host RAM
        self.mfu = None             # model-FLOPs utilization (0..1)
        self.attribution = None     # sampled device attribution dict
        self.queue_depth = 0
        self.elastic_events: List[str] = []
        self.retries: Dict[str, int] = {}       # point -> count
        self.retry_giveups: Dict[str, int] = {}

    # -- accumulation hooks (called by the module record_* functions) ------

    def add_collective(self, op: str, dtype: str, nbytes: int) -> None:
        with self._lock:
            ent = self.collectives.setdefault(f"{op}/{dtype}", [0, 0])
            ent[0] += 1
            ent[1] += int(nbytes)

    def add_negotiation(self, seconds: float) -> None:
        with self._lock:
            self.neg_count += 1
            self.neg_sum += seconds

    def add_fusion(self, n_buckets: int, fill_sum: float) -> None:
        with self._lock:
            self.fusion_plans += 1
            self.fusion_buckets += n_buckets
            self.fusion_fill_sum += fill_sum

    def add_grad_bytes(self, nbytes: int) -> None:
        with self._lock:
            self.grad_bytes += int(nbytes)

    def add_wire(self, logical: int, sent: int) -> None:
        with self._lock:
            self.wire_logical += int(logical)
            self.wire_sent += int(sent)

    def set_overlap_window(self, frac: float) -> None:
        with self._lock:
            self.overlap_window = float(frac)

    def add_fsdp(self, param_bytes: int, gather_bytes: int,
                 regather_bytes: int = 0, offload_bytes: int = 0) -> None:
        with self._lock:
            self.fsdp_param_bytes = int(param_bytes)
            self.fsdp_gather_bytes += int(gather_bytes)
            self.fsdp_regather_bytes += int(regather_bytes)
            self.fsdp_offload_bytes += int(offload_bytes)

    def set_mfu(self, mfu: float) -> None:
        with self._lock:
            self.mfu = float(mfu)

    def set_attribution(self, attribution: dict) -> None:
        """Latest sampled-step device attribution (utils/prof.py). The
        sample parses asynchronously, so it lands in the record of the
        step interval during which parsing finished — the record's
        ``attribution.sampled_step`` names the step actually
        measured."""
        with self._lock:
            self.attribution = dict(attribution)

    def add_elastic_event(self, kind: str) -> None:
        with self._lock:
            self.elastic_events.append(kind)

    def add_retry(self, point: str) -> None:
        with self._lock:
            self.retries[point] = self.retries.get(point, 0) + 1

    def add_retry_giveup(self, point: str) -> None:
        with self._lock:
            self.retry_giveups[point] = (
                self.retry_giveups.get(point, 0) + 1
            )

    def set_queue_depth(self, n: int) -> None:
        self.queue_depth = int(n)

    # -- step boundary ------------------------------------------------------

    def emit_event(self, kind: str, payload: dict) -> None:
        """Write one out-of-band event line to the JSONL: decision-trail
        records (the autotuner's trial/pin/reject blocks) that must not
        wait for a training-step boundary to flush. Event lines carry
        ``{"event": kind, kind: payload}`` instead of the step fields;
        scripts/metrics_summary.py separates them from step records."""
        with self._lock:
            if self._log_fh is None:
                return
            rec = {"event": kind, "time_unix": time.time(),
                   kind: dict(payload)}
            self._log_fh.write(json.dumps(rec) + "\n")
            self._log_fh.flush()

    def open_log(self, path: str) -> None:
        with self._lock:
            if self._log_fh is not None:
                self._log_fh.close()
            self._log_path = path
            self._log_fh = open(path, "a")

    def close_log(self) -> None:
        with self._lock:
            if self._log_fh is not None:
                self._log_fh.close()
                self._log_fh = None
                self._log_path = ""

    def begin_step(self) -> None:
        self._t0 = time.perf_counter()

    def end_step(self, extra: Optional[dict] = None) -> dict:
        """Close the interval: compute the record, emit JSONL, feed the
        step-level registry series, reset accumulators."""
        now = time.perf_counter()
        dt = (now - self._t0) if self._t0 is not None else 0.0
        self._t0 = None
        native = _native_stats_snapshot()
        with self._lock:
            self.step += 1
            coll = {
                k: {"count": int(v[0]), "bytes": int(v[1])}
                for k, v in sorted(self.collectives.items())
            }
            n_coll = sum(v[0] for v in self.collectives.values())
            record = {
                "step": self.step,
                "time_unix": time.time(),
                "step_time_s": dt,
                "collectives": coll,
                "negotiation": {
                    "count": self.neg_count, "sum_s": self.neg_sum,
                },
                "fusion": {
                    "plans": self.fusion_plans,
                    "buckets": self.fusion_buckets,
                    "fill_ratio_mean": (
                        self.fusion_fill_sum / self.fusion_buckets
                        if self.fusion_buckets else 0.0
                    ),
                },
                "grad_bytes": self.grad_bytes,
                "queue_depth": self.queue_depth,
                "elastic_events": list(self.elastic_events),
            }
            if self.wire_logical or self.wire_sent:
                record["wire"] = {
                    "logical_bytes": self.wire_logical,
                    "sent_bytes": self.wire_sent,
                }
            if self.overlap_window is not None:
                record["overlap_window_frac"] = self.overlap_window
            if self.fsdp_param_bytes is not None:
                record["fsdp"] = {
                    "hbm_param_bytes": self.fsdp_param_bytes,
                    "gather_bytes": self.fsdp_gather_bytes,
                    "regather_bytes": self.fsdp_regather_bytes,
                    "offload_bytes": self.fsdp_offload_bytes,
                }
            if self.mfu is not None:
                record["mfu"] = self.mfu
            if self.attribution is not None:
                record["attribution"] = self.attribution
            if self.retries:
                record["retries"] = dict(self.retries)
            if self.retry_giveups:
                record["retry_giveups"] = dict(self.retry_giveups)
            if _pod_label:
                # federation view: the pod this process belongs to
                # (multipod/topology.py) — scripts/metrics_summary.py
                # rolls step records up per pod on it
                record["pod"] = _pod_label
            if native:
                delta = {
                    k: native[k] - self._last_native.get(k, 0.0)
                    for k in ("cache_hits", "bytes_negotiated",
                              "stall_warnings")
                    if k in native
                }
                hits = delta.get("cache_hits", 0.0)
                record["native"] = {
                    **{k: int(v) for k, v in delta.items()},
                    # hit RATE relative to collectives issued this step;
                    # the native cache has no per-lookup counter, so this
                    # is the closest well-defined live ratio
                    "cache_hit_rate": (
                        min(hits / n_coll, 1.0) if n_coll else 0.0
                    ),
                }
                if "cycles" in native:
                    record["native"]["coord_cycles"] = int(native["cycles"])
                self._last_native = native
            if extra:
                record.update(extra)
            # write under the lock: close_log (hvd.shutdown, possibly
            # another thread) also takes it, so the handle can't be
            # closed between the check and the write
            if self._log_fh is not None:
                self._log_fh.write(json.dumps(record) + "\n")
                self._log_fh.flush()
            self._reset_interval()
        if _enabled:
            registry.counter(
                "hvd_steps_total", "Completed training steps").inc()
            registry.histogram(
                "hvd_step_seconds", "Step wall time").observe(dt)
        obs = _step_observer
        if obs is not None:
            try:
                obs(record)
            except Exception:
                # a broken detector must never take down the step loop
                pass
        return record


step_stats = StepStats()


# -- step wrapper hook (the continuous profiler rides step()) ---------------
#
# utils/prof.py registers an object with begin_step()/end_step(token)
# here, so ``with hvd.metrics.step():`` is the single user-visible step
# boundary for BOTH per-step stats and sampled device profiling — no
# second context manager to adopt. None (the default) costs one load +
# is-None check per step.

_step_wrapper = None

# health/ rides the same slots: the step observer receives each
# completed step's record dict (AFTER the JSONL write), the serving
# observer each serving latency sample. None (default) costs one load
# + is-None check — the monitor's entire disabled-path budget.
_step_observer = None
_serving_observer = None


def set_step_wrapper(wrapper) -> None:
    """Install/remove (None) the step wrapper. ``wrapper.begin_step()``
    runs before the step body (returning an opaque token),
    ``wrapper.end_step(token)`` after it but BEFORE the StepStats
    record closes — anything it pushes into ``step_stats`` lands in
    the current step's JSONL record."""
    global _step_wrapper
    _step_wrapper = wrapper


def set_step_observer(fn) -> None:
    """Install/remove (None) the step-record observer: ``fn(record)``
    runs after each StepStats record closes, outside the stats lock.
    The health monitor's detector feed (horovod_tpu/health)."""
    global _step_observer
    _step_observer = fn


def set_serving_observer(fn) -> None:
    """Install/remove (None) the serving-latency observer:
    ``fn(kind, slo, seconds)`` with kind in ttft | tpot | queue_wait |
    request. The health monitor's SLO burn-rate feed."""
    global _serving_observer
    _serving_observer = fn


@contextlib.contextmanager
def step(extra: Optional[dict] = None):
    """Mark one training step: ``with hvd.metrics.step(): step_fn(...)``.
    No-ops entirely when metrics are disabled, no step log is open and
    no step wrapper (sampled profiler) is installed."""
    # snapshot both gates once: a concurrent enable()/disable()/reset()
    # mid-step must not split a begin from its end (lost JSONL record /
    # bogus zero-length step)
    w = _step_wrapper
    en = _enabled
    if not en and w is None:
        yield step_stats
        return
    token = w.begin_step() if w is not None else None
    if en:
        step_stats.begin_step()
    try:
        yield step_stats
    finally:
        if w is not None:
            w.end_step(token)
        if en:
            step_stats.end_step(extra)


# ---------------------------------------------------------------------------
# hot-path record functions (each begins with the no-op fast path)
# ---------------------------------------------------------------------------

def record_collective(op: str, dtype: str, nbytes: int) -> None:
    """One issued collective (eager/native dispatch site)."""
    if not _enabled:
        return
    registry.counter(
        "hvd_collectives_total",
        "Collectives issued, by op and dtype", ("op", "dtype"),
    ).labels(op, dtype).inc()
    registry.counter(
        "hvd_collective_bytes_total",
        "Payload bytes of issued collectives, by op and dtype",
        ("op", "dtype"),
    ).labels(op, dtype).inc(nbytes)
    step_stats.add_collective(op, dtype, nbytes)


def record_negotiation_latency(seconds: float) -> None:
    """Enqueue → negotiated-batch-received latency for one tensor."""
    if not _enabled:
        return
    registry.histogram(
        "hvd_negotiation_seconds",
        "Enqueue-to-negotiated latency in the eager runtime",
    ).observe(seconds)
    step_stats.add_negotiation(seconds)


def record_batch_execution(op: str, n_tensors: int, nbytes: int,
                           seconds: float) -> None:
    """One negotiated fused batch executed by the data plane."""
    if not _enabled:
        return
    registry.histogram(
        "hvd_batch_execution_seconds",
        "Fused-batch execution wall time, by op", ("op",),
    ).labels(op).observe(seconds)
    registry.counter(
        "hvd_fused_tensors_total",
        "Tensors carried by executed fused batches", ("op",),
    ).labels(op).inc(n_tensors)
    registry.counter(
        "hvd_fused_batch_bytes_total",
        "Bytes carried by executed fused batches", ("op",),
    ).labels(op).inc(nbytes)


def record_fusion_plan(n_tensors: int, n_buckets: int, threshold: int,
                       bucket_bytes: Sequence[int] = ()) -> None:
    """One (compile-time) fusion plan: bucket count + fill ratios."""
    if not _enabled:
        return
    registry.counter(
        "hvd_fusion_plans_total", "Fusion plans computed").inc()
    registry.counter(
        "hvd_fusion_buckets_total", "Fusion buckets produced"
    ).inc(n_buckets)
    registry.counter(
        "hvd_fusion_tensors_total", "Tensors entering fusion plans"
    ).inc(n_tensors)
    fill_sum = 0.0
    hist = registry.histogram(
        "hvd_fusion_fill_ratio",
        "Bucket bytes / fusion threshold per produced bucket",
        buckets=RATIO_BUCKETS,
    )
    for b in bucket_bytes:
        r = min(b / threshold, 1.0) if threshold else 0.0
        hist.observe(r)
        fill_sum += r
    step_stats.add_fusion(n_buckets, fill_sum)


def record_fusion_groups(direct_bytes: int, packed_bytes: int,
                         direct_leaves: int) -> None:
    """How one gradient tree split when its buckets were built as
    groups of arrays (ops/fusion.pack_groups_by_plan): the bytes and
    the count of the leaves that ride their bucket's all-reduce in
    their own shape, and the bytes still flattened and concatenated.
    Recorded at TRACE time, like `trace_gauge`'s below: arithmetic on
    the tree's shapes, the last traced tree's, nothing inside the
    step."""
    if not _enabled:
        return
    registry.gauge(
        "hvd_fusion_direct_bytes",
        "Gradient bytes that ride their bucket's all-reduce unpacked"
    ).set(direct_bytes)
    registry.gauge(
        "hvd_fusion_packed_bytes",
        "Gradient bytes flattened and concatenated into packed operands"
    ).set(packed_bytes)
    registry.gauge(
        "hvd_fusion_direct_leaves",
        "Gradient leaves that ride their bucket's all-reduce unpacked"
    ).set(direct_leaves)


def record_grad_reduction(nbytes: int, n_buckets: int) -> None:
    """One executed gradient reduction (io_callback from the compiled
    step — fires per real step, not per trace)."""
    if not _enabled:
        return
    registry.counter(
        "hvd_grad_reduced_bytes_total",
        "Gradient bytes moved by executed reductions").inc(nbytes)
    registry.counter(
        "hvd_grad_reductions_total", "Executed gradient reductions").inc()
    step_stats.add_grad_bytes(nbytes)


def record_wire_bytes(logical: int, sent: int) -> None:
    """One compressed-data-plane transfer (docs/compression.md): what
    the payload occupies at logical precision vs what actually moves
    under the HOROVOD_COMPRESSION wire (payload + scales). The two
    counters are equal on the uncompressed plane; their ratio is the
    live compression factor scripts/metrics_summary.py reports and
    scripts/compression_check.py gates on."""
    if not _enabled:
        return
    registry.counter(
        "hvd_wire_bytes_logical_total",
        "Collective payload bytes at logical precision").inc(int(logical))
    registry.counter(
        "hvd_wire_bytes_sent_total",
        "Collective payload bytes on the compressed wire").inc(int(sent))
    step_stats.add_wire(int(logical), int(sent))


def trace_gauge(name: str, help: str, value, **labels) -> None:
    """Set one gauge at TRACE time, like the fused collectives'
    breadcrumb: the caller has worked `value` out from static shapes
    while its program was traced, so the gauge says what the last
    traced call built and nothing runs in the step. The module that
    knows the fact declares the gauge (its name, help text and
    labels, listed in docs/metrics.md); this is only the enabled check
    and the registry."""
    if not _enabled:
        return
    family = registry.gauge(name, help, labelnames=tuple(labels))
    (family.labels(**labels) if labels else family).set(value)


def record_overlap_window(frac: float) -> None:
    """The backward-interleaved scheduler's per-step overlap pin
    (ops/overlap.py): the fraction of backward compute the staged
    schedule forces after the first gradient collective — the lower
    bound any correct scheduler must grant the overlap window. Only
    recorded when HOROVOD_OVERLAP_SCHEDULE is active; its absence in
    the JSONL marks an unscheduled run (docs/overlap.md)."""
    if not _enabled:
        return
    registry.gauge(
        "hvd_overlap_window_frac",
        "Backward fraction pinned after the first gradient collective "
        "by the overlap schedule").set(float(frac))
    step_stats.set_overlap_window(frac)


def record_fsdp_step(param_bytes: int, gather_bytes: int,
                     regather_bytes: int = 0,
                     offload_bytes: int = 0) -> None:
    """One executed fully-sharded-parameter step (optim/fsdp.py,
    io_callback from the compiled step): the per-device parameter bytes
    RESIDENT in HBM (the sharded footprint — under FSDP ~1/world of
    the replicated size; the durable memory win) and the full-precision
    parameter bytes the forward all-gathers re-materialized this step
    (the recurring wire rent paid for it). Their ratio per step is
    ~world: FSDP trades gather bandwidth for resident HBM. Regather
    mode (HOROVOD_FSDP_REGATHER) pays the rent twice —
    ``regather_bytes`` counts the backward re-issued gathers that cap
    within-step peak liveness — and ``offload_bytes`` counts
    stage-boundary activation carries parked in host RAM under
    HOROVOD_FSDP_OFFLOAD (docs/fsdp.md)."""
    if not _enabled:
        return
    registry.gauge(
        "hvd_hbm_param_bytes",
        "Per-device parameter bytes resident in HBM (sharded "
        "footprint under FSDP; replicated size otherwise)").set(
            float(param_bytes))
    registry.counter(
        "hvd_fsdp_gather_bytes_total",
        "Full-precision parameter bytes materialized by FSDP forward "
        "all-gathers").inc(float(gather_bytes))
    if regather_bytes:
        registry.counter(
            "hvd_fsdp_regather_bytes_total",
            "Full-precision parameter bytes re-materialized by FSDP "
            "backward re-gathers (regather mode)").inc(
                float(regather_bytes))
    if offload_bytes:
        registry.counter(
            "hvd_fsdp_offload_bytes_total",
            "Stage-boundary activation bytes offloaded to host RAM "
            "per step (HOROVOD_FSDP_OFFLOAD)").inc(float(offload_bytes))
    step_stats.add_fsdp(param_bytes, gather_bytes, regather_bytes,
                        offload_bytes)


def record_mfu(mfu: float) -> None:
    """Model-FLOPs utilization for the step just closed: declared model
    FLOPs / (step time x chips x peak chip FLOP/s) — utils/mfu.py peak
    tables, computed by the continuous profiler (utils/prof.py) once
    ``hvd.prof.set_step_flops`` declared the model's per-step cost."""
    if not _enabled:
        return
    registry.gauge(
        "hvd_mfu",
        "Model-FLOPs utilization of the last completed step").set(
            float(mfu))
    step_stats.set_mfu(mfu)


def record_step_attribution(attribution: dict) -> None:
    """One sampled-step device attribution (utils/prof.py →
    utils/xplane.attribute): where the step's wall time went —
    compute, EXPOSED collective wire (collective time not hidden under
    compute), idle. ``measured_overlap_frac`` is the measured twin of
    the structural ``hvd_overlap_window_frac`` pin (docs/overlap.md):
    structural says how much overlap the schedule permits, this says
    how much the device actually achieved."""
    if not _enabled:
        return
    if "compute_frac" in attribution:
        registry.gauge(
            "hvd_step_compute_frac",
            "Compute fraction of the last sampled step's wall time",
        ).set(float(attribution["compute_frac"]))
    if "exposed_wire_frac" in attribution:
        registry.gauge(
            "hvd_step_exposed_wire_frac",
            "Exposed (un-overlapped) collective fraction of the last "
            "sampled step's wall time",
        ).set(float(attribution["exposed_wire_frac"]))
    if "idle_frac" in attribution:
        registry.gauge(
            "hvd_step_idle_frac",
            "Device-idle fraction of the last sampled step's wall "
            "time").set(float(attribution["idle_frac"]))
    overlap = attribution.get("measured_overlap_frac")
    # -1 = the sampled window held no collectives (overlap undefined);
    # leaving the previous sample's value would pair a stale overlap
    # with this sample's fresh compute/exposed/idle gauges
    registry.gauge(
        "hvd_overlap_window_measured_frac",
        "Measured overlapped share of collective time in the last "
        "sampled step (1.0 = wire fully hidden under compute; -1 = no "
        "collectives in the sample; the measured twin of "
        "hvd_overlap_window_frac)",
    ).set(-1.0 if overlap is None else float(overlap))
    step_stats.set_attribution(attribution)


def record_autotune_trial(dimension: str, step_s: Optional[float],
                          mfu: Optional[float] = None,
                          error: Optional[str] = None,
                          overrides: Optional[dict] = None) -> None:
    """One autotuner candidate measured (or failed) by the closed-loop
    tuner (ops/autotune.py): counts into
    ``hvd_autotune_trials_total{dimension}`` (errors additionally into
    ``hvd_autotune_trial_errors_total``) and lands as an ``autotune``
    event line in the StepStats JSONL — the decision trail
    scripts/metrics_summary.py renders as the sweep table."""
    if not _enabled:
        return
    registry.counter(
        "hvd_autotune_trials_total",
        "Autotune candidates measured, by sweep dimension",
        ("dimension",),
    ).labels(dimension).inc()
    if error is not None:
        registry.counter(
            "hvd_autotune_trial_errors_total",
            "Autotune candidates that failed to compile/run, by "
            "dimension", ("dimension",),
        ).labels(dimension).inc()
    payload = {"kind": "trial", "dimension": dimension}
    if overrides:
        payload["overrides"] = {k: v for k, v in overrides.items()}
    if step_s is not None:
        payload["step_s"] = float(step_s)
    if mfu is not None:
        payload["mfu"] = float(mfu)
    if error is not None:
        payload["error"] = error
    step_stats.emit_event("autotune", payload)


def record_autotune_pin(dimension: str, config: dict,
                        step_s: Optional[float],
                        accepted: bool = True,
                        source: str = "sweep") -> None:
    """One per-dimension agreement outcome (pin when the dimension
    improved on the incumbent, reject when it kept it) or a
    warm-start/final pin: ``hvd_autotune_best_step_s`` tracks the
    agreed best step time and ``hvd_autotune_dimension{dimension=<knob>}``
    carries every pinned knob's numeric value (strings enumerate per
    ops/autotune._ENUM_VALUES). ``step_s`` None = no candidate of the
    dimension measured successfully (all failed): the gauge keeps its
    last value and the JSONL event carries null — a bare ``Infinity``
    token would make the line unparseable to RFC-8259 readers."""
    if not _enabled:
        return
    from ..ops.autotune import _numeric

    if step_s is not None and step_s == step_s and step_s not in (
            float("inf"), float("-inf")):
        registry.gauge(
            "hvd_autotune_best_step_s",
            "Agreed best measured step seconds of the autotune sweep "
            "(the warm-start entry's recorded time on cache pins)",
        ).set(float(step_s))
    else:
        step_s = None
    gauge = registry.gauge(
        "hvd_autotune_dimension",
        "Pinned autotune knob values, by knob (strings enumerate: "
        "overlap off/stage/double=0/1/2, compression "
        "none/fp16/bf16/int8/int8-raw=0..4)", ("dimension",))
    for k, v in config.items():
        gauge.labels(k).set(_numeric(k, v))
    step_stats.emit_event("autotune", {
        "kind": "pin" if accepted else "reject",
        "dimension": dimension,
        "config": {k: v for k, v in config.items()},
        "step_s": step_s,
        "source": source,
    })


def record_timeline_activity(activity: str, seconds: float) -> None:
    """Bridge: a closed timeline span (utils/timeline.py) lands in a
    latency histogram keyed by its activity name."""
    if not _enabled:
        return
    registry.histogram(
        "hvd_timeline_activity_seconds",
        "Host-side timeline phase durations, by activity", ("activity",),
    ).labels(activity).observe(seconds)


def record_retry(point: str) -> None:
    """One backed-off retry of a control-plane call (utils/retry.py),
    labeled by call point (http.put, checkpoint.save, ...)."""
    _flight.record("retry", point)  # flight recorder has its own gate
    if not _enabled:
        return
    registry.counter(
        "hvd_retries_total",
        "Control-plane retries, by call point", ("point",),
    ).labels(point).inc()
    step_stats.add_retry(point)


def record_retry_giveup(point: str) -> None:
    """A retried call that exhausted its attempts/deadline and
    re-raised."""
    _flight.record("retry_giveup", point)
    if not _enabled:
        return
    registry.counter(
        "hvd_retry_giveups_total",
        "Control-plane retry give-ups, by call point", ("point",),
    ).labels(point).inc()
    step_stats.add_retry_giveup(point)


def record_fault(point: str, action: str) -> None:
    """One injected fault fired (utils/faults.py), by injection point
    and action — lets chaos runs prove the faults actually happened."""
    _flight.record("fault", point, action=action)
    if not _enabled:
        return
    registry.counter(
        "hvd_faults_injected_total",
        "Injected faults fired, by point and action",
        ("point", "action"),
    ).labels(point, action).inc()


def record_stall_abort() -> None:
    """A stalled collective converted into HorovodInternalError by the
    negotiation watchdog (HOROVOD_STALL_ABORT_S)."""
    if not _enabled:
        return
    registry.counter(
        "hvd_stall_aborts_total",
        "Collectives aborted by the stall watchdog").inc()


def record_recovery_rung(rung: str) -> None:
    """One state recovery resolved by the layered recovery ladder
    (elastic/replication.py), labeled by the rung that supplied the
    restored snapshot: peer / emergency / orbax / local / none."""
    _flight.record("recovery", rung)
    if not _enabled:
        return
    registry.counter(
        "hvd_recovery_rung_total",
        "State recoveries, by ladder rung (peer/emergency/orbax/"
        "local/none)", ("rung",),
    ).labels(rung).inc()
    step_stats.add_elastic_event(f"recovery:{rung}")


def record_replication(nbytes: int, n_partners: int) -> None:
    """One committed snapshot shipped to ring partners by the async
    replicator (elastic/replication.py)."""
    if not _enabled:
        return
    registry.counter(
        "hvd_replication_snapshots_total",
        "Committed snapshots replicated to ring partners").inc()
    registry.counter(
        "hvd_replication_bytes_total",
        "Snapshot payload bytes shipped to ring partners",
    ).inc(nbytes * max(n_partners, 1))


def record_replication_error() -> None:
    """A snapshot replication attempt that could not reach any ring
    partner (best-effort: training continues)."""
    if not _enabled:
        return
    registry.counter(
        "hvd_replication_errors_total",
        "Snapshot replications that reached no ring partner").inc()


def record_elastic_event(kind: str) -> None:
    """An elastic lifecycle transition (reset, hosts-updated, round,
    blacklist, ...)."""
    _flight.record("elastic", kind)
    if not _enabled:
        return
    registry.counter(
        "hvd_elastic_events_total",
        "Elastic lifecycle transitions, by event", ("event",),
    ).labels(kind).inc()
    step_stats.add_elastic_event(kind)


def set_queue_depth(n: int) -> None:
    """Pending tensors in the eager runtime's input table."""
    if not _enabled:
        return
    registry.gauge(
        "hvd_eager_queue_depth",
        "Tensors enqueued and awaiting negotiation/execution").set(n)
    step_stats.set_queue_depth(n)


# ---------------------------------------------------------------------------
# inference-serving record functions (serving/ — engine, batcher,
# server, replica dispatch). Same discipline as the training sites:
# every function starts with the disabled fast path.
# ---------------------------------------------------------------------------

def record_serving_request(seconds: float, code: int) -> None:
    """One completed front-end request (server.py), by HTTP status."""
    if not _enabled:
        return
    registry.counter(
        "hvd_serving_requests_total",
        "Serving requests completed, by HTTP status", ("code",),
    ).labels(str(code)).inc()
    registry.histogram(
        "hvd_serving_request_seconds",
        "End-to-end serving request latency, by HTTP status", ("code",),
    ).labels(str(code)).observe(seconds)


def record_serving_queue_wait(seconds: float,
                              slo: str = "standard") -> None:
    """Admission-to-dispatch wait of one request in the dynamic
    batcher's queue, by SLO class (serving/scheduler.py names the
    class; the one-shot predict batcher is all ``standard``)."""
    if not _enabled:
        return
    registry.histogram(
        "hvd_serving_queue_wait_seconds",
        "Request wait in the dynamic-batching queue, by SLO class",
        ("slo",),
    ).labels(slo).observe(seconds)
    obs = _serving_observer
    if obs is not None:
        obs("queue_wait", slo, seconds)


def record_serving_ttft(seconds: float, slo: str = "standard") -> None:
    """Time-to-first-token: request admission to first emitted token
    (prefill complete), by SLO class — ROADMAP item 3's scoreboard
    series; the health burn-rate rules consume it."""
    if not _enabled:
        return
    registry.histogram(
        "hvd_serving_ttft_seconds",
        "Time to first token per request, by SLO class", ("slo",),
    ).labels(slo).observe(seconds)
    obs = _serving_observer
    if obs is not None:
        obs("ttft", slo, seconds)


def record_serving_tpot(seconds: float, slo: str = "standard") -> None:
    """Time-per-output-token: one decode iteration's wall time billed
    to each live sequence it advanced, by SLO class."""
    if not _enabled:
        return
    registry.histogram(
        "hvd_serving_tpot_seconds",
        "Time per output token for live sequences, by SLO class",
        ("slo",),
    ).labels(slo).observe(seconds)
    obs = _serving_observer
    if obs is not None:
        obs("tpot", slo, seconds)


def record_serving_batch(bucket: int, n_real: int) -> None:
    """One executed inference batch: the chosen padded bucket and how
    many real examples it carried (the rest is padding waste)."""
    if not _enabled:
        return
    registry.counter(
        "hvd_serving_batches_total",
        "Inference batches executed, by padded bucket", ("bucket",),
    ).labels(str(bucket)).inc()
    registry.counter(
        "hvd_serving_examples_total",
        "Real examples served through executed batches").inc(n_real)
    registry.counter(
        "hvd_serving_padding_examples_total",
        "Padding examples added to reach the bucket size",
    ).inc(max(bucket - n_real, 0))
    registry.histogram(
        "hvd_serving_batch_fill_ratio",
        "Real examples / padded bucket size per executed batch",
        buckets=RATIO_BUCKETS,
    ).observe(n_real / bucket if bucket else 0.0)


def record_serving_compile(bucket: int, seconds: float) -> None:
    """One bucket executable AOT-compiled by the inference engine."""
    if not _enabled:
        return
    registry.counter(
        "hvd_serving_compiles_total",
        "Bucket executables AOT-compiled, by bucket", ("bucket",),
    ).labels(str(bucket)).inc()
    registry.histogram(
        "hvd_serving_compile_seconds",
        "AOT compile wall time per bucket executable",
    ).observe(seconds)


def set_serving_inflight(n: int, replica: str = "") -> None:
    """Requests currently executing, per replica ('' = this process)."""
    if not _enabled:
        return
    registry.gauge(
        "hvd_serving_inflight",
        "In-flight serving requests, by replica", ("replica",),
    ).labels(replica).set(n)


def record_serving_failover(replica: str) -> None:
    """A replica dropped from dispatch after a failed request (the
    request itself is retried on another replica)."""
    if not _enabled:
        return
    registry.counter(
        "hvd_serving_failovers_total",
        "Replicas ejected from dispatch after a failure", ("replica",),
    ).labels(replica).inc()


# -- autoregressive decode (serving/decode.py + serving/scheduler.py) --------

def record_decode_prefill(bucket: int, seconds: float) -> None:
    """One prompt prefilled into a claimed slot, by prompt-length
    bucket."""
    if not _enabled:
        return
    registry.counter(
        "hvd_serving_decode_prefills_total",
        "Prompts prefilled into cache slots, by prompt-length bucket",
        ("bucket",),
    ).labels(str(bucket)).inc()
    registry.histogram(
        "hvd_serving_decode_prefill_seconds",
        "Prefill executable wall time per admitted prompt",
    ).observe(seconds)


def record_decode_iteration(slots: int, seconds: float) -> None:
    """One decode iteration executed (every slot advances one
    position; callers ignore inactive slots' outputs)."""
    if not _enabled:
        return
    registry.counter(
        "hvd_serving_decode_iterations_total",
        "Decode iterations executed").inc()
    registry.histogram(
        "hvd_serving_decode_iteration_seconds",
        "Decode-iteration executable wall time",
    ).observe(seconds)


def record_decode_tokens(n: int) -> None:
    """Tokens actually delivered to live sequences this iteration
    (excludes inactive-slot ride-along outputs)."""
    if not _enabled:
        return
    registry.counter(
        "hvd_serving_decode_tokens_total",
        "Tokens generated for live sequences").inc(n)


def set_decode_slots(total: int, occupied: int, queued: int) -> None:
    """Slot occupancy + queued prefills after a scheduler iteration —
    the live signals the replica autoscaler scales on
    (docs/generation.md)."""
    if not _enabled:
        return
    g = registry.gauge(
        "hvd_serving_decode_slots",
        "Decode cache slots, by state", ("state",))
    g.labels("total").set(total)
    g.labels("occupied").set(occupied)
    registry.gauge(
        "hvd_serving_decode_queued_prefills",
        "Requests admitted but waiting for a free slot").set(queued)
    registry.gauge(
        "hvd_serving_decode_slot_occupancy",
        "Occupied fraction of decode cache slots").set(
            occupied / total if total else 0.0)


def record_decode_eviction(reason: str) -> None:
    """One sequence leaving its slot (or the queue), by reason:
    eos / length / deadline / shed / drain."""
    _flight.record("decode_evict", reason)
    if not _enabled:
        return
    registry.counter(
        "hvd_serving_decode_evictions_total",
        "Sequences evicted from decode, by reason", ("reason",),
    ).labels(reason).inc()


def record_autoscale(action: str) -> None:
    """One autoscaler decision acted on (grow / shrink)."""
    _flight.record("autoscale", action)
    if not _enabled:
        return
    registry.counter(
        "hvd_serving_autoscale_events_total",
        "Replica autoscaler actions, by direction", ("action",),
    ).labels(action).inc()


def set_serving_replicas(n: int) -> None:
    """Live replicas currently in dispatch rotation (front door)."""
    if not _enabled:
        return
    registry.gauge(
        "hvd_serving_replicas",
        "Replicas in the dispatch rotation").set(n)


# -- fleet-health monitor (horovod_tpu/health, docs/health.md) ---------------

def set_alert_active(rule: str, active: bool) -> None:
    """1 while the named health SLO rule fires, 0 once it clears."""
    if not _enabled:
        return
    registry.gauge(
        "hvd_alert_active",
        "1 while the named health rule fires, by rule", ("rule",),
    ).labels(rule).set(1.0 if active else 0.0)


def record_health_anomaly(cls: str) -> None:
    """One classified detector anomaly (straggler-host / slow-link /
    input-bound / compute-regression / queue-saturation)."""
    if not _enabled:
        return
    registry.counter(
        "hvd_health_anomalies_total",
        "Detector anomalies, by classified cause", ("cause",),
    ).labels(cls).inc()


def record_health_incident(rule: str, state: str) -> None:
    """One alert transition (fire or clear) written to the incident
    log, by rule and transition."""
    if not _enabled:
        return
    registry.counter(
        "hvd_health_incidents_total",
        "Health alert transitions, by rule and state",
        ("rule", "state"),
    ).labels(rule, state).inc()


# ---------------------------------------------------------------------------
# native runtime stats bridge (pull model)
# ---------------------------------------------------------------------------

_native_provider: Optional[Callable[[], dict]] = None


def set_native_stats_provider(fn: Optional[Callable[[], dict]]) -> None:
    """The eager runtime registers its cumulative-stats snapshot here
    (ops/eager_runtime.py); gauges update on every scrape."""
    global _native_provider
    _native_provider = fn
    if fn is not None:
        registry.register_collector(_collect_native)


def _native_stats_snapshot() -> Dict[str, float]:
    fn = _native_provider
    if fn is None:
        return {}
    try:
        return {k: float(v) for k, v in fn().items()}
    except Exception:
        return {}


_NATIVE_GAUGES = {
    "cache_hits": ("hvd_cache_hits_total",
                   "Response-cache hits (native runtime, cumulative)"),
    "bytes_negotiated": ("hvd_bytes_negotiated_total",
                         "Tensor bytes negotiated (cumulative)"),
    "stall_warnings": ("hvd_stall_warnings_total",
                       "Stall-inspector warnings (cumulative)"),
    "queue_depth": ("hvd_eager_queue_depth",
                    "Tensors enqueued and awaiting negotiation/execution"),
    "fast_path_hits": (
        "hvd_eager_fast_path_hits_total",
        "Eager collectives that bypassed negotiation via the "
        "steady-state plan cache (cumulative)"),
    "fast_path_steps": (
        "hvd_eager_fast_path_steps_total",
        "Whole steps executed off a cached plan (cumulative)"),
    "fast_path_activations": (
        "hvd_eager_fast_path_activations_total",
        "Plans frozen after steady-state warmup (cumulative)"),
    "fast_path_invalidations": (
        "hvd_eager_fast_path_invalidations_total",
        "Cached plans dropped (deviation/churn/fault, cumulative)"),
    "fast_path_active": (
        "hvd_eager_fast_path_active",
        "1 while a frozen plan is live, 0 otherwise"),
    "negotiation_bypassed_bytes": (
        "hvd_eager_negotiation_bypassed_bytes_total",
        "Tensor bytes whose negotiation the plan cache skipped "
        "(cumulative; the fast-path analog of "
        "hvd_bytes_negotiated_total)"),
    "cycles": ("hvd_coord_cycles_total",
               "Coordinator negotiation cycles (rank 0)"),
    "busy_cycles": ("hvd_coord_busy_cycles_total",
                    "Coordinator cycles that produced responses (rank 0)"),
    "wait_us": ("hvd_coord_wait_seconds_total",
                "Coordinator wall time blocked on worker frames (rank 0)"),
    "work_us": ("hvd_coord_work_seconds_total",
                "Coordinator CPU work per cycle, summed (rank 0)"),
    "bytes_rx": ("hvd_coord_bytes_rx_total",
                 "Control-plane bytes received by the coordinator"),
    "bytes_tx": ("hvd_coord_bytes_tx_total",
                 "Control-plane bytes sent by the coordinator"),
    "cache_hit_positions": ("hvd_coord_cache_hit_positions_total",
                            "Cache-hit positions in coordinator cycles"),
    "responses": ("hvd_coord_responses_total",
                  "Responses emitted by the coordinator"),
}


def _collect_native() -> None:
    if not _enabled:
        return
    stats = _native_stats_snapshot()
    for key, (name, help) in _NATIVE_GAUGES.items():
        if key in stats:
            v = stats[key]
            if key in ("wait_us", "work_us"):
                v = v / 1e6
            registry.gauge(name, help).set(v)


# ---------------------------------------------------------------------------
# standalone HTTP endpoint (per-worker; the rendezvous server mounts the
# same scrape under /metrics — runner/http/http_server.py)
# ---------------------------------------------------------------------------

_http_server = None
_http_thread = None


def start_http_server(port: int = 0) -> int:
    """Serve ``GET /metrics`` on a dedicated port; returns the bound
    port. Idempotent per process."""
    global _http_server, _http_thread
    if _http_server is not None:
        return _http_server.server_address[1]
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_GET(self):
            if self.path.split("?", 1)[0].rstrip("/") in ("", "/metrics"):
                ctype, body = exposition()
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                body = b"not found"
                self.send_response(404)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        def log_message(self, *args):
            pass

    _http_server = ThreadingHTTPServer(("0.0.0.0", port), _Handler)
    _http_thread = threading.Thread(
        target=_http_server.serve_forever, daemon=True, name="hvd-metrics",
    )
    _http_thread.start()
    return _http_server.server_address[1]


def stop_http_server() -> None:
    global _http_server, _http_thread
    if _http_server is not None:
        _http_server.shutdown()
        _http_server.server_close()
        _http_server = None
        _http_thread = None


# ---------------------------------------------------------------------------
# worker → rendezvous metrics push (the aggregation feed). Each worker
# PUTs its exposition under /metrics_push/<rank> at most once per
# HOROVOD_METRICS_PUSH_INTERVAL_S; the rendezvous /metrics mount merges
# the pushed payloads into one rank-labeled scrape (docs/metrics.md).
# ---------------------------------------------------------------------------

_push_thread: Optional[threading.Thread] = None
_push_stop: Optional[threading.Event] = None
_push_policy = None
_push_outage = None

# pod label of this process under a multipod topology ("" = single
# pod); stamps step records and names this pod in docs/telemetry
_pod_label = ""


def set_pod_label(label: str) -> None:
    global _pod_label
    _pod_label = str(label or "")


def pod_label() -> str:
    return _pod_label


def _push_degradation():
    """Lazy (import-cycle-safe) bounded policy + outage tracker for the
    push loop: a rendezvous outage costs one quick in-interval retry
    and ONE warning, not a warning per interval — the next interval's
    push is the real retry ladder (docs/recovery.md)."""
    global _push_policy, _push_outage
    if _push_policy is None:
        import logging

        from . import retry as _retry

        _push_policy = _retry.RetryPolicy(
            max_attempts=2, base_delay_s=0.1, max_delay_s=0.25)
        _push_outage = _retry.Outage(
            logging.getLogger("horovod_tpu.metrics"),
            "metrics push to the rendezvous store")
    return _push_policy, _push_outage


def push_once(addr: str, port: int, rank: int) -> bool:
    """One exposition PUT to the rendezvous store. Best-effort under a
    bounded RetryPolicy with log-spam suppression: a dead driver must
    never stall a worker, and a driver outage warns once (utils/
    retry.Outage), not once per push interval."""
    body = scrape().encode()
    policy, outage = _push_degradation()

    def _do() -> None:
        req = urllib.request.Request(
            f"http://{addr}:{port}/{METRICS_PUSH_SCOPE}/{rank}",
            data=body, method="PUT",
        )
        with urllib.request.urlopen(req, timeout=2.0):
            pass

    try:
        policy.call(_do, point="metrics.push")
        outage.success()
        return True
    except Exception as e:
        outage.failure(e)
        return False


def start_metrics_push(addr: str, port: int, rank: int,
                       interval_s: float = 5.0) -> None:
    """Start (or restart) the background push loop: one immediate push,
    then one per interval, plus a final flush on stop so short-lived
    workers still publish their last state."""
    global _push_thread, _push_stop
    stop_metrics_push()
    stop = threading.Event()

    def loop():
        push_once(addr, port, rank)
        while not stop.wait(max(interval_s, 0.05)):
            push_once(addr, port, rank)
        push_once(addr, port, rank)

    t = threading.Thread(target=loop, daemon=True,
                         name="hvd-metrics-push")
    t.start()
    _push_thread, _push_stop = t, stop


def stop_metrics_push() -> None:
    global _push_thread, _push_stop
    if _push_thread is not None:
        _push_stop.set()
        _push_thread.join(timeout=5)
        _push_thread = None
        _push_stop = None


def http_port() -> Optional[int]:
    return _http_server.server_address[1] if _http_server else None


# ---------------------------------------------------------------------------
# lifecycle wiring (core/basics.py calls these)
# ---------------------------------------------------------------------------

def configure(knobs) -> None:
    """Turn telemetry on per the knobs (HOROVOD_METRICS /
    HOROVOD_TPU_METRICS_FILE / HOROVOD_METRICS_PORT). A knob-less world
    leaves any manual ``enable()`` untouched."""
    global _configured
    want = bool(
        getattr(knobs, "metrics_enabled", False)
        or getattr(knobs, "metrics_file", "")
        or getattr(knobs, "metrics_port", 0)
    )
    if not want:
        return
    _configured = True
    enable()
    if getattr(knobs, "metrics_file", ""):
        step_stats.open_log(knobs.metrics_file)
    if getattr(knobs, "metrics_port", 0):
        start_http_server(knobs.metrics_port)
    # launcher-spawned worker: feed the rendezvous server's aggregated
    # /metrics (the driver process itself has no rank env and does not
    # push — its registry is the unlabeled series of the merge).
    # Under a multipod topology the push targets the pod's RELAY, not
    # the root — the relay batches the pod's expositions into one
    # upward PUT so the root sees O(pods) pushers (multipod/relay.py).
    interval = float(
        getattr(knobs, "metrics_push_interval_s", 0.0) or 0.0)
    try:
        from ..multipod.relay import push_endpoint

        endpoint = push_endpoint()
    except Exception:
        endpoint = None
    try:
        # separate guard: a malformed multipod env (bad pod id, a pod
        # count that doesn't divide the world) must cost the pod
        # label, never the push loop itself
        from ..multipod.topology import pod_topology_from_env

        topo = pod_topology_from_env()
        if topo is not None:
            set_pod_label(topo.pod_label())
    except Exception:
        pass
    rank = (os.environ.get("HVD_TPU_RANK")
            or os.environ.get("HOROVOD_RANK"))
    if interval > 0 and endpoint is not None and rank is not None:
        try:
            start_metrics_push(
                endpoint[0], endpoint[1], int(rank), interval)
        except ValueError:
            pass


def on_shutdown() -> None:
    """hvd.shutdown(): flush/close the step log and endpoint; disable
    only if configure() was what enabled us."""
    global _configured
    stop_metrics_push()  # joins after a final flush
    step_stats.close_log()
    stop_http_server()
    set_native_stats_provider(None)
    if _configured:
        _configured = False
        disable()


def reset() -> None:
    """Test hook: clear every family, provider and accumulator and
    return to the disabled state."""
    global _configured, _push_policy, _push_outage
    _push_policy = _push_outage = None
    set_pod_label("")
    set_step_wrapper(None)
    set_step_observer(None)
    set_serving_observer(None)
    on_shutdown()
    disable()
    _configured = False
    registry.clear()
    step_stats.close_log()
    step_stats.step = 0
    step_stats._last_native = {}
    step_stats._reset_interval()
