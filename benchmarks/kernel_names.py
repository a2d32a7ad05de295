"""The traced step's Mosaic calls by the names the program gave them.

``horovod_tpu/utils/scopes.py`` holds the ``name=`` of the program's
``pl.pallas_call``s (``FLASH_FWD``, ``FLASH_BWD``). A name becomes the
innermost scope of the call's ``op_name``
(``.../block_3/attn/flash_bwd/pallas_call``) and the TPU compiler
names the instruction by it (``flash_bwd.7``): a call is recognised by
the constant among its ``op_name``'s parts, or as its instruction's
stem where it carries no ``op_name``. Its phase is its ``op_name``'s
(``benchmarks/scopes.classify``): a forward kernel in the backward
phase is one a rematerialised block runs again.

``benchmarks/scopes.read`` offers a reader ``(phase, layer, kernel)``
with the kernel told by phase and arity; this pass tells it by name
and offers ``(phase, "attn", name)``. The rest is ``scopes``' own:
self time of the events that begin inside a step, median over the
traced steps, worst device.
"""

from __future__ import annotations

import collections
import time

from benchmarks import hlo, scopes, trace

LAYER = "attn"


def kernel_names() -> tuple:
    """The kernel names the program states; none for a program from
    before it had any."""
    found = (getattr(scopes.program, "FLASH_FWD", None),
             getattr(scopes.program, "FLASH_BWD", None))
    return tuple(n for n in found if n)


def named_calls(hlo_text: str) -> dict:
    """Mosaic call → ``(phase, "attn", name)`` for the calls that
    carry one of the program's kernel names."""
    wanted = set(kernel_names())
    op_names = scopes.op_names(hlo_text)
    found = {}
    for call in hlo.mosaic_call_names(hlo_text):
        op_name = op_names.get(call, "")
        name = next(iter(wanted.intersection(
            scopes._PARTS.split(op_name) + [trace.stem(call)])), None)
        if name:
            found[call] = (scopes.classify(op_name)[0], LAYER, name)
    return found


def step_table(ops, window, calls: dict) -> dict:
    """``{(phase, "attn", name): ns}`` of one device's step."""
    lo, hi = window
    events = [e for e in ops if lo <= e[1] < hi]
    table = {key: 0.0 for key in calls.values()}
    for call, secs in trace.self_seconds_by_name(events).items():
        if call in calls:
            table[calls[call]] += secs * 1e9
    return table


def by_name(run) -> dict:
    """``{device: [step_table, ...]}``, loaded once and kept on the
    run. Empty without the program's names, without a trace, and where
    the trace has no TPU plane (a rehearsal)."""
    found = getattr(run, "kernel_name_tables", None)
    if found is not None:
        return found
    found = {}
    path = trace.find_xplane(run.trace_dir) if kernel_names() else None
    if path is not None:
        t0 = time.perf_counter()
        devices, _, _ = trace.load(path)
        calls = named_calls(run.hlo_text)
        for dev, lines in devices.items():
            windows = trace.step_windows(lines["modules"],
                                         run.step_module_hint)
            if windows and lines["ops"]:
                found[dev] = [step_table(lines["ops"], w, calls)
                              for w in windows]
        if found:
            by_key = collections.Counter(calls.values())
            run.log(f"kernel names: third load of the trace and reduction "
                    f"{time.perf_counter() - t0:.2f} s; calls a step by "
                    f"phase/name: " + ", ".join(
                        f"{p}/{n} x{c}"
                        for (p, _, n), c in sorted(by_key.items())))
    run.kernel_name_tables = found
    return found


def read(run, select) -> float | None:
    """Milliseconds a step of the named calls ``select(phase, layer,
    name)`` takes; None where :func:`by_name` is empty."""
    return scopes.milliseconds(by_name(run), select)
