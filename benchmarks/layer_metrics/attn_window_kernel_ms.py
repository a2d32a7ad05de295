"""kernels: milliseconds a step spends in the flash-attention kernels of
the ``window_attention`` layers alone, forward, backward and a
rematerialised block's second forward, worst device, median over traced
steps.

The rule: a Mosaic call (``tpu_custom_call`` in the compiled step's
HLO) whose ``op_name`` puts it in layer ``attn`` (as ``attn_kernel_ms``
takes them, ``benchmarks/scopes.classify``) AND names a ``block_<i>``
that the ``model`` group's ``layer_types`` calls ``window_attention``.
The full layers' calls, which ``attn_kernel_ms`` holds beside these, are
left out. Nothing for a model with no such layer, without a trace, and
where the trace has no TPU plane (a rehearsal)."""

from __future__ import annotations

import re
import time

from benchmarks import hlo, kernel_names, scopes, trace

KIND = "window_attention"
LAYER = "attn"
_BLOCK = re.compile(r"^block_(\d+)$")


def window_layers(model: dict) -> list:
    """The indices of the model's ``window_attention`` layers."""
    return [i for i, kind in enumerate(model.get("layer_types") or ())
            if kind == KIND]


def window_calls(hlo_text: str, layers) -> dict:
    """Mosaic call -> ``(phase, "attn", KIND)`` for the calls of layer
    ``attn`` under a ``block_<i>`` with i among ``layers``."""
    wanted, found = set(layers), {}
    op_names = scopes.op_names(hlo_text)
    for call in hlo.mosaic_call_names(hlo_text):
        op_name = op_names.get(call)
        phase, layer = scopes.classify(op_name or "")
        if not op_name or layer != LAYER:
            continue
        blocks = {int(m.group(1)) for m in map(
            _BLOCK.match, scopes._PARTS.split(op_name)) if m}
        if blocks & wanted:
            found[call] = (phase, LAYER, KIND)
    return found


def by_window(run) -> dict:
    """``{device: [step_table, ...]}`` of the window layers' calls,
    loaded once and kept on the run; empty where there is nothing to
    read."""
    found = getattr(run, "window_kernel_tables", None)
    if found is not None:
        return found
    found = {}
    layers = window_layers(run.model_sizes)
    path = trace.find_xplane(run.trace_dir) \
        if layers and scopes.program and run.hlo_text else None
    if path is not None:
        t0 = time.perf_counter()
        devices, _, _ = trace.load(path)
        calls = window_calls(run.hlo_text, layers)
        for dev, lines in devices.items():
            windows = trace.step_windows(lines["modules"],
                                         run.step_module_hint)
            if calls and windows and lines["ops"]:
                found[dev] = [kernel_names.step_table(lines["ops"], w, calls)
                              for w in windows]
        if found:
            run.log(f"window kernels: a further load of the trace and "
                    f"reduction {time.perf_counter() - t0:.2f} s; "
                    f"{len(calls)} Mosaic calls a step under the "
                    f"{len(layers)} window layers {layers}")
    run.window_kernel_tables = found
    return found


def read(run):
    return scopes.milliseconds(
        by_window(run), lambda phase, layer, kernel: kernel == KIND)
