#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload gpt2m_dp1 --seed 3 --seconds 10 --trace 0

Finds the cell in ``BENCHMARK.json``, its configuration in
``benchmarks/configs/`` (held to its source, ``benchmarks/published.py``),
its traffic in ``benchmarks/traffic/``, the job the traffic names in
``benchmarks/jobs/``, the plain reference the configuration's ``family``
names in ``benchmarks/reference/`` and, with ``--trace 1``, one reader
per per-layer metric in ``benchmarks/layer_metrics/``. The data files
(not the jobs or the readers) are looked for under ``--root``, which is
the checkout unless a test points it at its fixture.
The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, traced ``breakdown``, and last
``compared``: each number ``correct`` compared, beside its limit (the
last lines of stderr say the same). With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics. Without a TPU it exits non-zero.

``--rehearse`` runs the job at the ``tiny`` presets of the
configuration and the traffic on whatever platform JAX finds (Pallas
kernels interpreted on the CPU) and prints the same last line with the
platform named and no time, rate or size of a device in it: it is for
the CPU tests and for finding faults before chip time is spent.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmarks import harness  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--root", default=harness.ROOT,
                   help="where BENCHMARK.json and the cell's data files "
                        "are: the checkout, but for the tests' fixture")
    return p.parse_args(argv)


def enable_compile_cache() -> str:
    """The program's own helper places the cache (the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``);
    every program is kept, however quickly it compiled, so that
    parameter init and the reference are found again too."""
    import jax

    from horovod_tpu.utils import compile_cache

    where = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def reduce_trace(run) -> None:
    """Reads the traced window's file into ``run.reduced_trace``; a
    trace with no TPU plane (a rehearsal) leaves it empty."""
    from benchmarks import hlo, scopes, trace

    path = trace.find_xplane(run.trace_dir)
    if path is None:
        run.log(f"no trace file under {run.trace_dir}")
        return
    devices, host_spans, seen = trace.load(path)
    run.log(f"trace {path}: {os.path.getsize(path)} bytes, device planes "
            f"{sorted(devices)}, {len(host_spans)} host spans")
    kernels = hlo.mosaic_call_names(run.hlo_text)
    run.reduced_trace = trace.reduce(
        devices, host_spans, run.step_module_hint, kernel_names=kernels,
        kernel_layers=scopes.kernel_layers(run.hlo_text))
    # for reading by hand, beside the trace: the lines the file has and
    # one traced step of the first device with times from its beginning
    summary = {"lines": seen, "kernel_names": kernels,
               "reduced": run.reduced_trace, "host_spans": host_spans}
    for dev in sorted(devices)[:1]:
        windows = trace.step_windows(devices[dev]["modules"],
                                     run.step_module_hint)
        if windows:
            lo, hi = windows[len(windows) // 2]
            summary["recorded_step"] = {
                "device": dev, "opcodes": devices[dev]["opcodes"],
                "modules": [[n, s - lo, d]
                            for n, s, d in devices[dev]["modules"]
                            if lo <= s < hi],
                "ops": [[n, s - lo, d] for n, s, d in devices[dev]["ops"]
                        if lo <= s < hi]}
    with open(os.path.join(run.trace_dir, "summary.json"), "w") as f:
        json.dump(summary, f)


def main(argv=None) -> int:
    args = parse_args(argv)
    found = harness.load_cell(args.workload, args.root)
    cell, config, traffic = (found["cell"], found["config"],
                             found["traffic"])
    model_sizes = dict(config["model"])
    if args.rehearse:
        model_sizes.update(config["tiny"])
        traffic = {**traffic, **traffic["tiny"]}

    # a rehearsal leaves the persistent cache alone: CPU entries are of
    # no use to a chip run
    cache_dir = None if args.rehearse else enable_compile_cache()
    devices = harness.require_devices(cell["chips"], args.rehearse)
    dev = devices[0]

    run = harness.Run(
        started=STARTED, workload=args.workload, chips=cell["chips"],
        config=config, traffic=traffic, model_sizes=model_sizes,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        rehearse=args.rehearse, root=args.root)
    run.device_kind = dev.device_kind
    run.listen_for_compiles()
    run.log(f"cell {args.workload}: {cell['config']} x {cell['traffic']} "
            f"on {len(devices)} x {dev.device_kind} ({dev.platform}), "
            f"seed {args.seed}, {args.seconds} s, trace {args.trace}, "
            f"compile cache {cache_dir}"
            + (", REHEARSAL at the tiny preset" if args.rehearse else ""))

    result = harness.load_job(traffic["job"]).run_cell(
        run, model_sizes, traffic)

    run.log(f"set-up {run.setup_seconds:.2f} s: "
            + ", ".join(f"{n} {run.span_seconds(n):.2f}" for n in (
                "init", "reference_check", "reference_global_loss",
                "lower", "compile", "warmup")
                if run.span_seconds(n) is not None)
            + f"; {run.compiles} programs through the compiler, "
            f"{run.cache_misses} not found in the cache")

    stats = [d.memory_stats() or {} for d in devices]
    run.memory_stats_peak = max(
        (s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if not args.rehearse:
        # buffers' peak leaves out a running program's temporaries on
        # this runtime (PERF.md); what the chip holds while the step
        # runs is the larger of the two
        device["memory_peak_bytes"] = int(max(
            run.memory_stats_peak, run.step_bytes))

    metrics = {"setup_s": (run.setup_seconds, "s"), **result["metrics"]}
    free = set(result["platform_free"])
    breakdown = None
    if run.trace:
        reduce_trace(run)
        metrics, free = {}, set()
        for m in found["per_layer"]:
            reader = harness.load_reader(m["name"])
            value = reader(run)
            if value is not None:
                metrics[m["name"]] = (value, m["unit"])
                if getattr(sys.modules[reader.__module__],
                           "PLATFORM_FREE", False):
                    free.add(m["name"])
        rt = run.reduced_trace
        if rt:
            device["busy_s"], device["window_s"] = (rt["busy_s"],
                                                    rt["window_s"])
            breakdown = {"device_ops": rt["device_ops"],
                         "idle_gaps": rt["idle_gaps"]}
            run.log(f"traced {rt['traced_steps']} steps on "
                    f"{rt['devices']} device(s): step period "
                    f"{rt['step_period_ms']:.3f} ms against "
                    f"{1e3 * run.step_seconds:.3f} ms untraced")
    else:
        wanted = {m["name"] for m in found["end_to_end"]}
        metrics = {k: v for k, v in metrics.items() if k in wanted}
    if args.rehearse:
        metrics = {k: v for k, v in metrics.items() if k in free}

    for name, ok in run.checks.items():
        run.log(f"check {name}: {'ok' if ok else 'FAILED'}")
    line = {
        "correct": all(run.checks.values()) and result["failed"] == 0,
        "attempted": result["attempted"], "failed": result["failed"],
        "metrics": {k: {"value": v, "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    # a number that is not finite would not be JSON: it goes as text
    line["compared"] = {
        name: {"value": value if math.isfinite(value) else repr(value),
               "limit": limit, "ok": run.checks[name]}
        for name, (value, limit) in run.compared.items()}
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
