"""Continuous step profiler: sampled device capture + live attribution.

The host timeline (utils/timeline.py), metrics (utils/metrics.py) and
flight recorder (utils/flight.py) all stop at the host: none of them
sees what the TPU actually executed, so statements like "the overlap
window is 0.89" rested on AOT schedule analysis, not measured device
events. This module closes that gap in the always-on, bounded-overhead
mold of Google's fleet-wide continuous profiling (PAPERS.md: "Profiling
a warehouse-scale computer"): every ``HOROVOD_PROF_EVERY``-th step is
wrapped in ``jax.profiler`` device tracing, the resulting xplane is
parsed off-thread (utils/xplane.py — no TensorFlow needed), and the
sampled step is attributed into **compute / exposed-collective /
host-gap / idle** buckets that feed the live registry:

* ``hvd_step_compute_frac`` / ``hvd_step_exposed_wire_frac`` /
  ``hvd_step_idle_frac`` — where the sampled step's wall time went;
* ``hvd_overlap_window_measured_frac`` — the measured twin of PR 9's
  structural ``hvd_overlap_window_frac``: how much collective time the
  device really hid under compute;
* ``hvd_mfu`` — model-FLOPs utilization every step (not only sampled
  ones), once :func:`set_step_flops` declares the model's per-step
  cost (utils/mfu.py owns the peak tables); published on a TPU only.

Cost discipline (the PR-6 replicator's duty-cycle model): sampling is
OFF by default; when off, the per-step hook is a single predicted
branch (asserted by tests/test_prof.py). When on, each sample's
measured overhead T (trace start/stop + off-thread parse CPU) charges
a budget — the next sample cannot start until ``T*(1/d - 1)`` wall
seconds pass (``HOROVOD_PROF_DUTY_CYCLE``, default 2%), so profiling
consumes at most ~d of the run no matter how slow parsing is.

Each sample directory (``HOROVOD_PROF_DIR``, default
``<tmpdir>/hvd_prof/rank<r>``) carries a ``hvd_prof_meta.json`` sidecar
(rank, step, wall-clock window, /clock offset to the driver) so
``scripts/trace_merge.py`` can place its device ops on the same
clock-aligned axis as host timelines and flight dumps
(docs/timeline.md).

The profiler rides the existing step boundary: ``with
hvd.metrics.step():`` is the only annotation needed (the module
registers a step wrapper with utils/metrics.py at ``hvd.init``).
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Callable, List, Optional

from . import flight as _flight
from . import metrics as _metrics

# ---------------------------------------------------------------------------
# module state (the no-op fast path)
# ---------------------------------------------------------------------------

_active = False          # True iff sampling and/or MFU accounting is on
_configured = False      # True when configure() (hvd.init) armed us
_every = 0               # sample every N-th step; 0 = sampling off
_duty = 0.02             # max fraction of wall time spent profiling
_dir = ""                # sample-capture root
_step_flops = 0.0        # model FLOPs per optimizer step (whole batch)
_n_chips = 0             # devices dividing the FLOPs; 0 = auto
_lock = threading.Lock()
_counter = 0             # steps seen
_samples = 0             # captures taken
_next_ok_t = 0.0         # monotonic floor for the next sample
_inflight = False        # a capture/parse is outstanding
_force_next = False      # capture the NEXT step regardless of cadence
_parse_thread: Optional[threading.Thread] = None
_last_attribution: Optional[dict] = None
_last_mfu: Optional[float] = None
_overhead_s = 0.0        # cumulative measured profiling overhead
_errors = 0
_clock: Callable[[], float] = time.monotonic   # injectable for tests


def active() -> bool:
    return _active


def sample_count() -> int:
    return _samples


def overhead_s() -> float:
    """Cumulative measured profiling overhead (capture + parse CPU) —
    the numerator of the duty-cycle bound."""
    return _overhead_s


def last_attribution() -> Optional[dict]:
    """The most recent sampled-step attribution (utils/xplane.attribute
    output + ``sampled_step``/``mfu`` context), or None before the
    first completed sample."""
    return _last_attribution


def last_mfu() -> Optional[float]:
    return _last_mfu


def set_step_flops(flops: float, n_chips: int = 0) -> None:
    """Declare the model's FLOPs per optimizer step (whole global
    batch; utils/mfu.py transformer_train_flops / cnn_train_flops are
    the standard sources). Enables the per-step ``hvd_mfu`` gauge:
    mfu = flops / (step_time x chips x peak chip FLOP/s). ``n_chips``
    0 = all visible devices."""
    global _step_flops, _n_chips, _peak_total
    _step_flops = float(flops)
    _n_chips = int(n_chips)
    _peak_total = None  # chip count may have changed; recompute lazily
    if _configured:
        _update_activation()


def step_flops() -> float:
    return _step_flops


_peak_total: Optional[float] = None  # cached chips x peak FLOP/s


def _peak_total_flops() -> float:
    """chips x peak per-chip FLOP/s, 0.0 off a TPU (hvd_mfu is then not
    published: a ratio against an assumed peak is not a measurement;
    an unknown TPU generation raises in utils/mfu.py) — resolved once
    (jax device query + device-kind parsing are not per-step costs) and
    cached until set_step_flops/reset invalidates."""
    global _peak_total
    if _peak_total is not None:
        return _peak_total
    from . import mfu as _mfu

    peak = _mfu.tpu_peak_or_none()
    if peak is None:
        _peak_total = 0.0
    else:
        import jax

        n = _n_chips if _n_chips > 0 else jax.device_count()
        _peak_total = n * peak
    return _peak_total


def default_dir() -> str:
    base = _dir or os.path.join(tempfile.gettempdir(), "hvd_prof")
    r = _flight.rank()
    return os.path.join(base, f"rank{max(r, 0)}")


# ---------------------------------------------------------------------------
# the step wrapper (registered with utils/metrics.set_step_wrapper)
# ---------------------------------------------------------------------------

class _Token:
    __slots__ = ("t0", "t0_wall", "logdir", "step",
                 "capture_overhead_s", "mfu")

    def __init__(self, t0: float, t0_wall: float,
                 logdir: Optional[str], step: int):
        self.t0 = t0
        self.t0_wall = t0_wall
        self.logdir = logdir
        self.step = step
        self.capture_overhead_s = 0.0
        self.mfu: Optional[float] = None


class _StepWrapper:
    """What utils/metrics.step() drives: one begin/end pair per step."""

    def begin_step(self):
        if not _active:
            return None
        return _begin_step()

    def end_step(self, token) -> None:
        if token is not None:
            _end_step(token)


_wrapper = _StepWrapper()


def _begin_step() -> _Token:
    global _counter, _inflight, _samples, _force_next
    with _lock:
        _counter += 1
        step = _counter
        sample = not _inflight and (
            (_every > 0
             and step % _every == 0
             and _clock() >= _next_ok_t)
            # anomaly-triggered forensics (health/): a requested
            # capture bypasses the cadence and the duty-budget floor —
            # the one step that explains an alert is worth its cost
            or _force_next
        )
        if sample:
            _inflight = True
            _samples += 1
            _force_next = False
    logdir = None
    if sample:
        logdir = os.path.join(default_dir(), f"step{step}")
        t0 = _clock()
        try:
            import jax

            os.makedirs(logdir, exist_ok=True)
            jax.profiler.start_trace(logdir)
        except Exception:
            _note_error()
            with _lock:
                _samples -= 1  # a failed capture is not a sample
            # charge the failed attempt to the duty budget: a
            # persistently failing capture (unwritable dir, wedged
            # profiler session) backs off under the same bound instead
            # of paying makedirs + raise on every N-th step forever
            _finish_sample(_clock() - t0)
            logdir = None
        tok = _Token(_clock(), time.time(), logdir, step)
        tok.capture_overhead_s = _clock() - t0
        return tok
    return _Token(_clock(), time.time(), None, step)


def _end_step(token: _Token) -> None:
    dt = _clock() - token.t0
    if _step_flops > 0 and dt > 0 and _peak_total_flops() > 0:
        # stamped on the token too: the async parse must attach THIS
        # step's MFU to the attribution record, not whatever later
        # step last updated the global by the time parsing finishes
        token.mfu = _step_flops / (dt * _peak_total_flops())
        _record_mfu(token.mfu)
    if token.logdir is None:
        return
    t0 = _clock()
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception:
        _note_error()
        _finish_sample(token.capture_overhead_s + (_clock() - t0))
        return
    token.capture_overhead_s += _clock() - t0
    _spawn_parse(token, dt)


def _record_mfu(mfu: float) -> None:
    global _last_mfu
    _last_mfu = mfu
    _metrics.record_mfu(mfu)


def _write_sidecar(token: _Token, host_wall_s: float) -> None:
    """The clock anchor trace_merge.py aligns device ops with: the
    capture's wall window on this rank plus the /clock offset onto the
    driver's axis (same probe as flight dumps)."""
    meta = {
        "hvd_prof_meta": 1,
        "rank": _flight.rank(),
        "step": token.step,
        "t_start_unix": token.t0_wall,
        "t_stop_unix": time.time(),
        "host_wall_s": round(host_wall_s, 6),
    }
    meta.update(_flight.clock_probe())
    # atomic write: trace_merge.py places this sample's device ops by
    # t_start_unix, so a torn sidecar must not exist under its final
    # name (the merger skips samples with no valid anchor)
    path = os.path.join(token.logdir, "hvd_prof_meta.json")
    try:
        with open(path + ".tmp", "w") as f:
            json.dump(meta, f)
            f.write("\n")
        os.replace(path + ".tmp", path)
    except OSError:
        _note_error()


def _spawn_parse(token: _Token, host_wall_s: float) -> None:
    """Sidecar write + parse + attribute off-thread: the training step
    resumes immediately; the sidecar's /clock probe (a bounded HTTP
    round-trip) and the parse CPU both charge the duty-cycle budget
    when the thread finishes."""
    global _parse_thread

    def work():
        t0 = _clock()
        try:
            _write_sidecar(token, host_wall_s)
            _parse_sample(token, host_wall_s)
        except Exception:
            _note_error()
        finally:
            _finish_sample(
                token.capture_overhead_s + (_clock() - t0))

    try:
        t = threading.Thread(target=work, daemon=True,
                             name="hvd-prof-parse")
        t.start()
    except Exception:
        # thread exhaustion must not crash the user's training step or
        # wedge sampling (_inflight would stay set forever)
        _note_error()
        _finish_sample(token.capture_overhead_s)
        return
    _parse_thread = t


#: capture dirs kept per rank — a continuous run must not grow tmpdir
#: without bound (each sample's .xplane.pb is megabytes); the newest K
#: stay available for trace_merge.py
_KEEP_SAMPLES = 8


def _prune_samples() -> None:
    """Drop all but the newest ``_KEEP_SAMPLES`` step<N> capture dirs
    under this rank's root (runs on the parse thread, off the step
    path). Newest by mtime, not step number: a restarted run's fresh
    low-step captures must survive a dead run's stale high-step
    leftovers in the same (default, shared-tmpdir) root."""
    import re
    import shutil

    root = default_dir()
    entries = []
    try:
        for name in os.listdir(root):
            if re.fullmatch(r"step\d+", name):
                try:
                    entries.append(
                        (os.path.getmtime(os.path.join(root, name)),
                         name))
                except OSError:
                    continue
    except OSError:
        return
    entries.sort()
    for _, name in entries[:-_KEEP_SAMPLES or None]:
        shutil.rmtree(os.path.join(root, name), ignore_errors=True)


def _parse_sample(token: _Token, host_wall_s: float) -> None:
    global _last_attribution
    from . import xplane

    _prune_samples()
    xs, _ = xplane.load_xspace(token.logdir)
    ops = xplane.op_events(xs)
    if not ops:
        raise xplane.XPlaneUnavailable("capture holds no op events")
    attr = xplane.attribute_by_plane(ops, host_wall_us=host_wall_s * 1e6)
    attr["sampled_step"] = token.step
    if token.mfu is not None:
        attr["mfu"] = round(token.mfu, 6)
    _last_attribution = attr
    _metrics.record_step_attribution(attr)
    _flight.record("prof_sample", f"step{token.step}",
                   compute_frac=attr["compute_frac"],
                   exposed_wire_frac=attr["exposed_wire_frac"])


def _finish_sample(overhead_s: float) -> None:
    """Charge the duty budget and reopen the sampling gate: after a
    sample costing T the next one waits T*(1/d - 1), so profiling's
    share of wall time stays ≤ d."""
    global _inflight, _next_ok_t, _overhead_s
    with _lock:
        _overhead_s += overhead_s
        if _duty > 0:
            _next_ok_t = _clock() + overhead_s * (1.0 / _duty - 1.0)
        _inflight = False
    # a forced (anomaly-triggered) capture may have armed the wrapper
    # with sampling otherwise off: drop back to the knob-driven state
    if not _force_next:
        _update_activation()


def _note_error() -> None:
    global _errors
    _errors += 1


# ---------------------------------------------------------------------------
# manual step marking (for callers not using hvd.metrics.step())
# ---------------------------------------------------------------------------

class _StepCtx:
    __slots__ = ("_token",)

    def __enter__(self):
        self._token = _wrapper.begin_step()
        return self

    def __exit__(self, *exc):
        _wrapper.end_step(self._token)
        return False


def step() -> "_StepCtx":
    """Standalone step boundary for code that does not use
    ``hvd.metrics.step()`` (which already drives the profiler). Do not
    nest the two — each entry counts one step."""
    return _StepCtx()


# ---------------------------------------------------------------------------
# lifecycle (core/basics.py calls configure/on_shutdown)
# ---------------------------------------------------------------------------

def _activate() -> None:
    global _active
    _active = True
    _metrics.set_step_wrapper(_wrapper)


def _update_activation() -> None:
    """Arm or disarm to match the current knobs: sampling or MFU wanted
    → wrapper installed; neither → fully off (metrics.step() back to
    its no-op fast path, not a per-step token allocation)."""
    global _active
    if _every > 0 or _step_flops > 0:
        _activate()
    elif _active:
        _active = False
        if _metrics._step_wrapper is _wrapper:
            _metrics.set_step_wrapper(None)


def request_sample(reason: str = "") -> None:
    """Force a device capture on the NEXT step, bypassing the
    ``prof_every`` cadence and the duty-budget floor (one outstanding
    capture at a time still applies). The health monitor calls this
    when an alert fires so the xplane trace of a degraded step exists
    before anyone goes looking for it. Arms the step wrapper if
    sampling was otherwise off; after the forced capture the
    knob-driven activation state is restored."""
    global _force_next
    _force_next = True
    _flight.record("prof_request", reason or "manual")
    _activate()


def configure(knobs=None, *, every: Optional[int] = None,
              duty_cycle: Optional[float] = None,
              directory: Optional[str] = None,
              clock: Optional[Callable[[], float]] = None) -> None:
    """Arm the profiler from the knob snapshot (hvd.init) or explicit
    overrides (tests/benches). ``HOROVOD_PROF_EVERY=0`` (the default)
    leaves the whole subsystem a no-op — no wrapper is registered
    unless sampling or MFU accounting is wanted."""
    global _configured, _every, _duty, _dir, _clock
    _every = int(every if every is not None
                 else getattr(knobs, "prof_every", 0) or 0)
    if duty_cycle is not None:
        _duty = float(duty_cycle)
    else:
        knob_duty = getattr(knobs, "prof_duty_cycle", None)
        # 0 is a valid value (gate disabled); only None falls back
        _duty = 0.02 if knob_duty is None else float(knob_duty)
    if directory is not None:
        _dir = directory
    elif knobs is not None:
        # re-read like every/duty: a re-init with a different
        # HOROVOD_PROF_DIR must not keep capturing under the old root
        _dir = getattr(knobs, "prof_dir", "") or ""
    if clock is not None:
        _clock = clock
    _configured = True
    _update_activation()


def join(timeout_s: float = 10.0) -> None:
    """Wait for an outstanding sample parse (tests / run teardown)."""
    t = _parse_thread
    if t is not None and t.is_alive():
        t.join(timeout=timeout_s)


def summary() -> dict:
    """Point-in-time profiler state (benches, perf_baseline.py)."""
    return {
        "active": _active,
        "every": _every,
        "duty_cycle": _duty,
        "steps": _counter,
        "samples": _samples,
        "overhead_s": round(_overhead_s, 6),
        "errors": _errors,
        "mfu": _last_mfu,
        "attribution": _last_attribution,
    }


def on_shutdown() -> None:
    """hvd.shutdown(): stop sampling; leave counters for inspection."""
    global _active, _configured
    join(timeout_s=5.0)
    if _configured:
        _configured = False
        _active = False
        if _metrics._step_wrapper is _wrapper:
            _metrics.set_step_wrapper(None)


def reset() -> None:
    """Test hook: return to the disabled, unconfigured state."""
    global _active, _configured, _every, _duty, _dir, _step_flops
    global _n_chips, _counter, _samples, _next_ok_t, _inflight
    global _last_attribution, _last_mfu, _overhead_s, _errors, _clock
    global _parse_thread, _peak_total, _force_next
    join(timeout_s=5.0)
    _active = False
    _configured = False
    _every = 0
    _duty = 0.02
    _dir = ""
    _step_flops = 0.0
    _n_chips = 0
    _peak_total = None
    _counter = 0
    _samples = 0
    _next_ok_t = 0.0
    _inflight = False
    _force_next = False
    _parse_thread = None
    _last_attribution = None
    _last_mfu = None
    _overhead_s = 0.0
    _errors = 0
    _clock = time.monotonic
    if _metrics._step_wrapper is _wrapper:
        _metrics.set_step_wrapper(None)
