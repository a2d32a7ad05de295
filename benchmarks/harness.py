"""What every job gets from the harness: host spans, checks, the
window's compile counter, the profiler, and the cell's files."""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
GIB = float(1 << 30)
MIB = float(1 << 20)
# profiler output of the last traced run of each cell; fixed, inside the
# checkout, git-ignored
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell's entry of ``<root>/BENCHMARK.json`` with its
    configuration and traffic files, all found by name under ``root``
    (the checkout, but for the tests' fixture) and the configuration
    held to its source (``benchmarks/published.py``). A kind of layer
    the configuration names is looked for under ``root`` first
    (``flops.kinds_root``)."""
    from benchmarks import flops, published

    flops.kinds_root(root)
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    config = load_json(root, config_entry["file"])
    published.check(config_entry, config)
    traffic = load_json(root, "benchmarks", "traffic",
                        cell["traffic"] + ".json")

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_job(name: str):
    return importlib.import_module(f"benchmarks.jobs.{name}")


def load_reference(family: str, root: str = ROOT):
    """The plain reference of a configuration's ``family``:
    ``<root>/benchmarks/reference/<family>.py``, loaded from that file
    (its contract is written at the top of ``transformer_lm.py``)."""
    path = os.path.join(root, "benchmarks", "reference", family + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_reference_{family}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric: str):
    """The reader of one per-layer metric: ``layer_metrics/<name>.py``
    with ``read(run) -> float | None``."""
    return importlib.import_module(
        f"benchmarks.layer_metrics.{metric}").read


def peak_of(device_kind: str) -> dict:
    peaks = load_json(HERE, "peaks.json")
    if device_kind not in peaks:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in "
            f"benchmarks/peaks.json: add them with their source")
    return peaks[device_kind]


class Run:
    """One run of one cell: what the job records and the readers read.

    A span is ``(name, start, end)`` on ``time.perf_counter``; spans of
    one name add up. ``annotate=True`` also writes the span into the
    profiler's trace as ``bench:<name>``, on the device trace's clock.
    """

    def __init__(self, *, started, workload, chips, traffic, model_sizes,
                 seed, seconds, trace, rehearse, config=None, root=ROOT):
        self.started = started
        self.workload = workload
        self.chips = chips
        # the configuration file whole (a caller that only builds the
        # step gives none), and its ``model`` group as run
        self.config, self.root = config, root
        self.traffic, self.model_sizes = traffic, model_sizes
        self.seed, self.seconds = seed, seconds
        self.trace, self.rehearse = trace, rehearse
        self.spans: list = []
        self.checks: dict = {}
        self.compared: dict = {}  # check -> (number compared, its limit)
        self.window = None  # (start, end)
        self.window_compiles = 0
        self.compiles = self.cache_misses = 0
        self._in_window = False
        self.trace_dir = os.path.join(TRACE_ROOT, workload)
        # set by the job
        self.hlo_text = ""
        self.memory = None
        self.step_module_hint = ""
        self.step_bytes = 0
        self.tokens_per_s_per_chip = None
        self.step_seconds = None
        # set by the harness after the job
        self.device_kind = None
        self.reduced_trace: dict = {}
        self.memory_stats_peak = None

    # -- what a job calls ---------------------------------------------------

    def log(self, text: str) -> None:
        print(f"[{time.perf_counter() - self.started:8.2f}s] {text}",
              flush=True)

    @contextlib.contextmanager
    def span(self, name: str, annotate: bool = False):
        ctx = contextlib.nullcontext()
        if annotate:
            import jax
            ctx = jax.profiler.TraceAnnotation("bench:" + name)
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))

    def check(self, name: str, ok: bool, detail: str = "", *,
              value=None, limit=None) -> None:
        """One part of ``correct``; where it compares a number with a
        limit, both go into the result's line."""
        self.checks[name] = bool(ok)
        if value is not None:
            self.compared[name] = (float(value), float(limit))
        if not ok:
            self.log(f"CHECK FAILED {name}: {detail}")

    def begin_window(self) -> None:
        self._in_window = True
        self.window = (time.perf_counter(), None)

    def end_window(self) -> None:
        self.window = (self.window[0], time.perf_counter())
        self._in_window = False

    @contextlib.contextmanager
    def profiler(self):
        """A device trace of what runs inside, without the Python
        tracer (it slows the host and fills the file)."""
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            yield
        finally:
            with self.span("stop_trace"):
                jax.profiler.stop_trace()

    # -- what the harness and the readers use -------------------------------

    def listen_for_compiles(self) -> None:
        from jax import monitoring

        def on_duration(name, _secs, **_kw):
            if name == COMPILE_EVENT:
                self.compiles += 1
                if self._in_window:
                    self.window_compiles += 1

        def on_event(name, **_kw):
            if name == CACHE_MISS_EVENT:
                self.cache_misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def span_seconds(self, name: str) -> float | None:
        found = [e - s for n, s, e in self.spans if n == name]
        return sum(found) if found else None

    def spans_in_window(self, name: str) -> list:
        lo, hi = self.window
        return [e - s for n, s, e in self.spans
                if n == name and s >= lo and e <= hi]

    @property
    def setup_seconds(self) -> float:
        return self.window[0] - self.started


def require_devices(chips: int, rehearse: bool):
    """The devices JAX found: exactly ``chips`` of them, and TPU chips
    unless this is a rehearsal."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if not rehearse and dev.platform != "tpu":
        sys.exit(f"the benchmark needs a TPU, JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind}, {len(devices)} "
                 f"devices); --rehearse runs the tiny preset anywhere")
    if len(devices) != chips:
        sys.exit(f"the cell asks for {chips} chip(s), JAX found "
                 f"{len(devices)} x {dev.device_kind} ({dev.platform}); a "
                 f"rehearsal on the CPU takes XLA_FLAGS="
                 f"--xla_force_host_platform_device_count={chips}")
    return devices
