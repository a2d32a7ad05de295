"""The traced step's time by the scopes the program names.

Every instruction of the compiled step carries, as ``op_name`` in its
metadata, the stack of names it was traced under:
``jit(step_fn)/shard_map/transpose(jvp(Transformer))/block_3/ln_mlp/mul``.
Flax writes each module's name there, JAX wraps the stack in ``jvp(``
and ``transpose(`` for the two directions of differentiation, and
``horovod_tpu/utils/scopes.py`` names what no module covers (the loss
head, the optimizer wrap's pack / all-reduce / unpack / inner update).
This file joins ``run.hlo_text`` (instruction name → ``op_name``) with
the trace (instruction name → time, ``benchmarks/trace.py``) and sums
each traced step's time by phase, layer and attention kernel.

Where ``op_name`` is read: the compiled executable's text. Compiled
here for a described v5e (PR 24, jax 0.9.0 / libtpu 0.0.34) every
fusion, ``while`` and Mosaic call of the step carries it (a fusion has
its root's); the compiler's own copies, slices and bitcasts
(``copy-done``, ``slice-done``) carry none.

The rules, all of them:

* an instruction counts under its OWN ``op_name``. A fusion has one,
  its root's, though it may hold operations of several scopes (at one
  chip AdamW sits in the weight-gradient matmuls' fusions and the norms
  in their neighbours'; at four the AdamW fusions end in
  ``apply_updates``' add and are named by it). The keys ``holds ...``
  say how much time is in fusions that hold a scope's operations under
  another name: a bound on what this rule may have put elsewhere;
* an instruction with no ``op_name`` counts with the next instruction
  of its device that has one (the schedule puts a ``copy-done`` right
  before its consumer), at a step's end with the last; the key
  ``BORROWED`` says how much time that is;
* time is self time (``trace.self_seconds_by_name``): a ``while``'s
  less its body's, so the parts add up to the time the device is busy;
* a collective (by its opcode, ``trace.collective_kind``) is in no
  phase: ``collective_ms`` has it;
* the three flash kernels have no names of their own (a ``name=`` on
  their ``pl.pallas_call`` becomes the innermost scope and with it the
  instruction's stem: ``flash_fwd.2`` for ``attn.6``, compiled here
  both ways; since PR 26 ``attn_kernel_ms`` finds them by their layer,
  :func:`kernel_layers`, so they may be given names). A Mosaic call of
  layer ``attn`` is the forward kernel if
  its phase is ``forward``; of the backward two, dq returns one array
  and dkv a pair. (Under ``remat`` the recomputed forward kernel would
  be a pair in the backward phase too; no cell uses ``remat``.)

Per-step values are the median over the traced steps on each device,
and the worst device is reported, as ``trace.reduce`` does.
"""

from __future__ import annotations

import functools
import json
import os
import re
import statistics
import time
from typing import NamedTuple

from benchmarks import hlo, trace

try:
    from horovod_tpu.utils import scopes as program
except ImportError:  # a program from before its scopes had names
    program = None

PHASES = ("forward", "backward", "optimizer")
NORM_MODULES = ("ln_attn", "ln_mlp", "ln_final")
KERNEL_FWD, KERNEL_DQ, KERNEL_DKV = "attn_fwd", "attn_bwd_dq", "attn_bwd_dkv"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PARTS = re.compile(r"[/()]")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


@functools.lru_cache(maxsize=1)
def _instructions(hlo_text: str) -> tuple:
    """``(computation, name, tuple-valued, op_name, called
    computation)`` of every instruction; read once a text (a step's is
    tens of megabytes, its kernels' bodies included)."""
    out, computation = [], None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            if line.rstrip().endswith("{"):
                head = line.removeprefix("ENTRY ").split(" ", 1)[0]
                computation = head.lstrip("%")
            continue
        head, sep, rest = line.strip().partition(" = ")
        if sep and "(" in rest:
            op_name, calls = _OP_NAME.search(rest), _CALLS.search(rest)
            out.append((computation,
                        head.removeprefix("ROOT ").lstrip("%"),
                        rest.startswith("("),
                        op_name and op_name.group(1),
                        calls and calls.group(1)))
    return tuple(out)


def op_names(hlo_text: str) -> dict:
    """Instruction name → ``op_name`` of its metadata, for the
    instructions that have one."""
    return {name: op_name
            for _, name, _, op_name, _ in _instructions(hlo_text)
            if op_name}


def tuple_valued(hlo_text: str) -> set:
    """Names of the instructions whose result is a tuple."""
    return {name for _, name, is_tuple, _, _ in _instructions(hlo_text)
            if is_tuple}


def held(hlo_text: str) -> dict:
    """Fusion name → the classes of the operations inside it (the
    ``op_name``s of the computation it ``calls=``)."""
    inside: dict = {}
    for computation, _, _, op_name, _ in _instructions(hlo_text):
        if op_name:
            inside.setdefault(computation, set()).add(classify(op_name))
    return {name: inside.get(calls, set())
            for _, name, _, _, calls in _instructions(hlo_text) if calls}


@functools.lru_cache(maxsize=None)
def classify(op_name: str) -> tuple:
    """``(phase, layer)`` of an ``op_name``. Phase: ``backward`` under a
    ``transpose(``, ``forward`` under a ``jvp(`` alone, else
    ``optimizer`` (whatever the step does outside the differentiated
    loss: the optimizer wrap, AdamW, ``apply_updates``). Layer: the
    first found among the name's parts of the program's five scopes,
    then the scopes the program lists as its layers' own
    (``LAYER_SCOPES`` in ``horovod_tpu/utils/scopes.py``, a tuple of
    names: ``.../mlp/moe_experts/...`` is layer ``moe_experts``, and a
    reader of it is one line over :func:`read`; a program that has no
    such tuple has no such layers), then Flax's module names; ``other``
    when none is."""
    if "transpose(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    else:
        phase = "optimizer"
    parts = set(_PARTS.split(op_name))
    for scope in (program.LOSS_HEAD, program.HVD_PACK,
                  program.HVD_ALLREDUCE, program.HVD_UNPACK,
                  program.HVD_INNER_UPDATE,
                  *getattr(program, "LAYER_SCOPES", ())):
        if scope in parts:
            return phase, scope
    if parts.intersection(NORM_MODULES):
        return phase, "norm"
    for layer in ("attn", "mlp"):
        if layer in parts:
            return phase, layer
    if any(p.startswith("tok_emb") for p in parts):
        return phase, "embed"
    return phase, "other"


def kernel_kind(name: str, phase: str, layer: str, mosaic: set,
                tuples: set):
    """Which flash kernel the instruction ``name`` is, or None."""
    if name not in mosaic or layer != "attn":
        return None
    if phase == "forward":
        return KERNEL_FWD
    return KERNEL_DKV if name in tuples else KERNEL_DQ


def kernel_layers(hlo_text: str) -> dict:
    """Mosaic call → the layer of its ``op_name``, for the calls that
    have one; empty for a program without scope names."""
    if program is None:
        return {}
    names = op_names(hlo_text)
    return {k: classify(names[k])[1]
            for k in hlo.mosaic_call_names(hlo_text) if k in names}


class Compiled(NamedTuple):
    """What the reduction reads from the compiled step's HLO text."""
    names: dict   # instruction → op_name
    tuples: set   # instructions whose result is a tuple
    mosaic: set   # Mosaic (Pallas) custom calls
    inside: dict  # fusion → classes of the operations it holds


def compiled(hlo_text: str) -> Compiled:
    return Compiled(op_names(hlo_text), tuple_valued(hlo_text),
                    set(hlo.mosaic_call_names(hlo_text)), held(hlo_text))


BORROWED = ("borrowed", None, None)


def step_table(ops, window, opcodes: dict, step: Compiled) -> dict:
    """``{(phase, layer, kernel): ns}`` of one device's step: the self
    time of the events that begin inside ``window``, collectives left
    out. Two kinds of key are not phases and repeat time counted above:
    ``BORROWED``, the instructions that have no ``op_name`` and were
    counted with a neighbour; ``("holds <phase>", layer, None)``, the
    fusions that hold operations of that phase and layer
    but are named for another: how much time the rule "its own
    ``op_name``" may have put elsewhere, at most."""
    lo, hi = window
    names = step.names
    events = sorted((e for e in ops if lo <= e[1] < hi),
                    key=lambda e: (e[1], -e[2]))
    order = [e[0] for e in events]
    classes = {n: classify(names[n]) for n in set(order) if n in names}
    for sweep in (reversed(order), order):  # the next one, else the last
        near = None
        for name in sweep:
            if name in names:
                near = classes[name]
            elif near is not None:
                classes.setdefault(name, near)
    table: dict = {}
    for name, secs in trace.self_seconds_by_name(events).items():
        if trace.collective_kind(opcodes.get(name, name)):
            continue
        own = classes.get(name, ("optimizer", "other"))
        keys = [(*own, kernel_kind(name, *own, step.mosaic, step.tuples))]
        if name not in names:
            keys.append(BORROWED)
        keys += [("holds " + phase, layer, None)
                 for phase, layer in step.inside.get(name, ())
                 if (phase, layer) != own]
        for key in keys:
            table[key] = table.get(key, 0.0) + secs * 1e9
    return table


def tables(devices: dict, hint: str, hlo_text: str) -> dict:
    """``{device: [step_table, ...]}`` over each device's traced steps."""
    step = compiled(hlo_text)
    out = {}
    for dev, lines in devices.items():
        windows = trace.step_windows(lines["modules"], hint)
        if windows and lines["ops"]:
            out[dev] = [step_table(lines["ops"], w,
                                   lines.get("opcodes") or {}, step)
                        for w in windows]
    return out


def milliseconds(by_device: dict, select, notes: bool = False):
    """Sum of the entries ``select(phase, layer, kernel)`` takes, a
    step: median over steps, worst device. None without a table. Only
    the phases' entries are offered to ``select``; ``notes=True``
    offers the others (``BORROWED``, ``holds``) instead, whose second
    place is a layer too."""
    if not by_device:
        return None
    return max(
        statistics.median(
            sum(ns for key, ns in step.items()
                if (key[0] in PHASES) != notes and select(*key))
            for step in steps)
        for steps in by_device.values()) / 1e6


def by_scope(run) -> dict:
    """The run's tables, loaded once and kept on the run. Empty when
    the program has no ``horovod_tpu/utils/scopes.py`` (the names are a
    contract: without it nothing is read), when there is no trace, or
    when the trace has no TPU plane (a rehearsal)."""
    found = getattr(run, "scope_tables", None)
    if found is not None:
        return found
    found = {}
    path = trace.find_xplane(run.trace_dir) if program else None
    if path is not None:
        t0 = time.perf_counter()
        devices, _, _ = trace.load(path)
        found = tables(devices, run.step_module_hint, run.hlo_text)
        if found:
            _report(run, found, time.perf_counter() - t0)
    run.scope_tables = found
    return found


def read(run, select) -> float | None:
    return milliseconds(by_scope(run), select)


def _report(run, found: dict, seconds: float) -> None:
    """Logs the whole split once and leaves it beside the trace, with
    the names it was made from, for reading by hand."""
    keys = sorted({k for steps in found.values() for s in steps
                   for k in s}, key=str)
    split = {"/".join(map(str, k)): milliseconds(
        found, lambda *key, k=k: key == k, notes=k[0] not in PHASES)
        for k in keys}
    run.log(f"scopes: second load of the trace and reduction "
            f"{seconds:.2f} s; ms a step by phase/layer/kernel: "
            + ", ".join(f"{k} {v:.3f}" for k, v in split.items()))
    step = compiled(run.hlo_text)
    with open(os.path.join(run.trace_dir, "scopes.json"), "w") as f:
        json.dump({"split_ms": split, "reduction_s": seconds,
                   "op_names": step.names,
                   "held": {k: sorted(v) for k, v in step.inside.items()},
                   "tuple_valued": sorted(step.mosaic & step.tuples)}, f)
