"""What the compiled train step's HLO text says: all-reduce
instructions with the bytes they carry, and the names of the Mosaic
(Pallas) custom calls, which is how the trace reduction finds the
kernels' events."""

from __future__ import annotations

import re

_ALLREDUCE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (.+?) all-reduce(?:-start)?\(")
_SHAPE = re.compile(
    r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)"
    r"\[([\d,]*)\]")
_MOSAIC = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*custom_call_target=\"tpu_custom_call\"")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
MOSAIC_TARGET = "tpu_custom_call"


def shape_bytes(type_text: str) -> int:
    """Bytes of an HLO result type, a tuple's members summed."""
    n = 0
    for dtype, dims in _SHAPE.findall(type_text):
        count = 1
        for d in filter(None, dims.split(",")):
            count *= int(d)
        n += count * _BYTES[dtype]
    return n


def allreduces(hlo_text: str) -> list[int]:
    """Bytes reduced by each all-reduce instruction (an asynchronous
    one is counted at its ``-start``). An all-reduce's result has the
    shape of its operands."""
    return [shape_bytes(m.group(1))
            for m in map(_ALLREDUCE.match, hlo_text.splitlines()) if m]


def mosaic_call_names(hlo_text: str) -> list[str]:
    return [m.group(1)
            for m in map(_MOSAIC.match, hlo_text.splitlines()) if m]
