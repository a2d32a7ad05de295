"""Gradient compression for the collective wire.

Reference: /root/reference/horovod/torch/compression.py:20-74 — a
`Compressor` interface with `none` and `fp16` implementations applied
before enqueue and decompressed after.

This module grew from that cast-only surface into the compressed data
plane (docs/compression.md):

* **Cast compressors** (`fp16`, `bf16`): the wire dtype is a float cast;
  the reduce runs over the cast payload and the result is cast back.
  bfloat16 is the TPU-native choice (f32 exponent range, no loss-scale
  bookkeeping); float16 is kept for reference parity.
* **`Int8BlockCompressor`**: block-quantized int8 with per-block scales
  over the flattened payload. An int8 wire cannot be SUM-reduced in the
  wire dtype (overflow, per-rank scales), so the collective itself
  changes shape: `quantized_psum` expresses the EQuARX structure
  (EQuARX: Efficient Quantized AllReduce in XLA, PAPERS.md) —
  quantize → exchange shards → local dequant-accumulate → requantize →
  all-gather → dequant — in pure jnp/lax, so it traces under jit and
  shard_map and needs no custom kernels. Wire footprint per leg is
  ~size/4 + scales vs 2×size for a full-precision ring: ~3.9× fewer
  bytes at the default 256-element block.
* **Error feedback**: quantization error is carried across steps (the
  residual is added to the next step's payload before quantizing) so a
  compressed SUM stays unbiased. On the SPMD path the residual lives as
  optimizer-state leaves (optim/distributed.py `_EFState`); on the
  eager path the executor holds per-bucket residual buffers
  (ops/eager_runtime.py `XlaExecutor._wire_residuals` /
  `LoopbackExecutor._residuals`).

Compression composes with fusion: buckets are quantized/cast once per
fused bucket, reduced, and restored — never per tensor.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax


class Compressor:
    @staticmethod
    def compress(tensor):
        raise NotImplementedError

    @staticmethod
    def decompress(tensor, ctx):
        raise NotImplementedError


class NoneCompressor(Compressor):
    """Identity (compression.py:27)."""

    kind = "none"
    error_feedback = False

    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


class FP16Compressor(Compressor):
    """Cast floating tensors to float16 on the wire (compression.py:46)."""

    wire_dtype = jnp.float16
    kind = "fp16"
    error_feedback = False

    @classmethod
    def compress(cls, tensor):
        if jnp.issubdtype(tensor.dtype, jnp.floating):
            return tensor.astype(cls.wire_dtype), tensor.dtype
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor if ctx is None else tensor.astype(ctx)


class BF16Compressor(FP16Compressor):
    """TPU-native wire compression: bfloat16 keeps f32 range, halves ICI
    bytes. Extension beyond the reference's fp16."""

    wire_dtype = jnp.bfloat16
    kind = "bf16"


# ---------------------------------------------------------------------------
# int8 block quantization primitives
# ---------------------------------------------------------------------------

DEFAULT_BLOCK = 256
_SCALE_BYTES = 4  # float32 scale per block


def _pad_flat(flat, multiple: int):
    """Zero-pad a 1-D array so `multiple` divides its length."""
    n = flat.shape[0]
    rem = n % multiple
    if rem:
        flat = jnp.pad(flat, (0, multiple - rem))
    return flat


def _check_block(block, length: int, what: str) -> int:
    """Trace-time validation of an int8 quantization block: a positive
    int that divides `length` (the already-padded payload). A block
    that doesn't divide would silently pad a payload a caller already
    padded to ITS layout — shifting block boundaries away from the
    residual/state layout it carries — so reject loudly instead."""
    block = int(block)
    if block <= 0:
        raise ValueError(
            f"{what}: quantization block must be a positive int, "
            f"got {block}")
    if length % block:
        raise ValueError(
            f"{what}: block {block} does not divide the padded payload "
            f"length {length} — the caller's row/residual layout and "
            f"the wire's block grid would disagree (silently padding "
            f"again would double-pad; fix the block or the layout)")
    return block


def quantize_blocks(flat, block: int) -> Tuple:
    """Per-block symmetric int8 quantization of a 1-D float array whose
    length is a multiple of `block`. Returns ``(q int8 [m], scales f32 [m/block])``
    with ``x ≈ q * scale`` per block; all-zero blocks get scale 1 so the
    divide is always defined.

    The scale is ``amax * (1.0 / 127.0)``, NOT ``amax / 127.0``: XLA
    rewrites constant-divisor division to a reciprocal multiply inside
    compiled programs but not in the op-by-op path, so the division
    form would put a jitted and an eager quantization one ulp apart on
    ~4% of blocks. The multiply is correctly rounded and identical
    everywhere.
    """
    blocks = flat.astype(jnp.float32).reshape(-1, block)
    amax = jnp.max(jnp.abs(blocks), axis=-1)
    scale = jnp.where(amax > 0, amax * (1.0 / 127.0), 1.0)
    q = jnp.clip(jnp.round(blocks / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale


def dequantize_blocks(q, scales, block: int):
    """Inverse of :func:`quantize_blocks` (float32 output)."""
    blocks = q.reshape(-1, block).astype(jnp.float32)
    return (blocks * scales.astype(jnp.float32)[..., None]).reshape(-1)


def quantize_dequantize(x, block: int = DEFAULT_BLOCK):
    """One quantization round trip (float32 output, same shape): the
    value a peer would reconstruct from our wire payload. Used by the
    loopback executor's wire simulation and by error-feedback residual
    computation (the residual is exactly ``x - quantize_dequantize(x)``).
    """
    flat = jnp.asarray(x).astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    padded = _pad_flat(flat, block)
    q, s = quantize_blocks(padded, block)
    return dequantize_blocks(q, s, block)[:n].reshape(jnp.shape(x))


class Int8BlockCompressor(Compressor):
    """Block-quantized int8 payload with per-block float32 scales.

    The `compress`/`decompress` pair implements the reference Compressor
    contract for point-to-point uses (round-trip tests, broadcast-style
    wires). SUM collectives must NOT reduce the int8 payload directly —
    route through :func:`quantized_psum` (SPMD) or the executor wire
    path (eager), which quantize → reduce in f32 → requantize.
    """

    kind = "int8"
    error_feedback = True
    # 0 = resolve HOROVOD_COMPRESSION_BLOCK at use; subclass with a
    # positive value to pin a block size in code
    block = 0

    @classmethod
    def resolved_block(cls) -> int:
        if cls.block and cls.block > 0:
            return int(cls.block)
        from ..core.state import global_state

        return int(global_state().knobs.compression_block
                   or DEFAULT_BLOCK)

    @classmethod
    def compress(cls, tensor):
        if not jnp.issubdtype(jnp.result_type(tensor), jnp.floating):
            return tensor, None
        block = cls.resolved_block()
        x = jnp.asarray(tensor)
        flat = x.astype(jnp.float32).reshape(-1)
        padded = _pad_flat(flat, block)
        q, s = quantize_blocks(padded, block)
        return q, (s, x.dtype, x.shape, flat.shape[0], block)

    @classmethod
    def decompress(cls, tensor, ctx):
        if ctx is None:
            return tensor
        # the block rides the ctx so a knob change between compress and
        # decompress cannot desynchronize the grid
        scales, dtype, shape, n, block = ctx
        out = dequantize_blocks(tensor, scales, block)[:n]
        return out.reshape(shape).astype(dtype)


class Int8BlockRawCompressor(Int8BlockCompressor):
    """int8 wire without error feedback — A/B and debugging only (the
    quantization bias accumulates over steps without the residual)."""

    error_feedback = False


# ---------------------------------------------------------------------------
# wire spec: the process-wide description of the compressed data plane
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WireSpec:
    """What moves on the wire for floating SUM/AVERAGE collectives:
    `kind` in {"fp16","bf16","int8"}, `block` the int8 scale granularity,
    `error_feedback` whether residuals carry across steps. `None` stands
    for the uncompressed plane (HOROVOD_COMPRESSION=none) everywhere a
    WireSpec is accepted."""

    kind: str
    block: int = DEFAULT_BLOCK
    error_feedback: bool = False

    @property
    def key(self) -> tuple:
        """Hashable cache-key component (executor programs, plans,
        fusion buckets)."""
        return (self.kind, self.block, self.error_feedback)

    @property
    def wire_dtype(self):
        return {"fp16": jnp.float16, "bf16": jnp.bfloat16,
                "int8": jnp.int8}[self.kind]


_LEGACY_WIRE_NAMES = {"bfloat16": "bf16", "float16": "fp16",
                      "bf16": "bf16", "fp16": "fp16"}


def parse_wire(name: str, block: int = 0) -> Optional[WireSpec]:
    """Parse a HOROVOD_COMPRESSION value into a WireSpec (None for the
    uncompressed plane). Raises on unknown names so a typo'd knob fails
    loudly instead of silently training uncompressed."""
    name = (name or "").strip().lower()
    block = int(block) if block and int(block) > 0 else DEFAULT_BLOCK
    if name in ("", "none", "off", "0"):
        return None
    if name in _LEGACY_WIRE_NAMES:
        return WireSpec(_LEGACY_WIRE_NAMES[name], block)
    if name == "int8":
        return WireSpec("int8", block, error_feedback=True)
    if name in ("int8-raw", "int8_raw"):
        return WireSpec("int8", block, error_feedback=False)
    raise ValueError(
        f"unknown HOROVOD_COMPRESSION value {name!r}; expected one of "
        "none, fp16, bf16, int8, int8-raw"
    )


def resolve_wire(knobs=None) -> Optional[WireSpec]:
    """The active wire spec: explicit `knobs`, else the initialized
    global knobs, else the raw env (bare EagerRuntime construction in
    check scripts/tests runs before hvd.init). The legacy
    HOROVOD_COMPRESSION_WIRE_DTYPE knob maps onto the cast kinds when
    HOROVOD_COMPRESSION itself is unset."""
    if knobs is None:
        from ..core.state import global_state

        st = global_state()
        if st.initialized:
            knobs = st.knobs
    if knobs is not None:
        name = knobs.compression
        if name in ("", "none") and knobs.compression_wire_dtype:
            name = knobs.compression_wire_dtype
        return parse_wire(name, knobs.compression_block)
    from ..core.knobs import _env, _env_int

    name = _env("COMPRESSION", "") or ""
    if name in ("", "none"):
        name = _env("COMPRESSION_WIRE_DTYPE", "") or name
    return parse_wire(name, _env_int("COMPRESSION_BLOCK", DEFAULT_BLOCK))


def wire_applies(spec: Optional[WireSpec], dtype) -> bool:
    """True when `spec` transforms payloads of `dtype`: the compressed
    plane only touches floating payloads (integer buckets always move
    uncompressed), and ``None`` is the uncompressed plane everywhere.
    The shared guard for the per-bucket reduce paths — the monolithic
    chains and the backward-interleaved scheduler (ops/overlap.py)
    dispatch on the same predicate, so a bucket can never compress on
    one path and not the other."""
    return spec is not None and jnp.issubdtype(jnp.dtype(dtype),
                                               jnp.floating)


def wire_sent_bytes(n_elements: int, logical_itemsize: int,
                    spec: Optional[WireSpec]) -> int:
    """Bytes one contribution of `n_elements` occupies on the wire under
    `spec` (payload + scales), vs ``n_elements * logical_itemsize``
    logically — the pair behind hvd_wire_bytes_{logical,sent}_total."""
    if spec is None:
        return int(n_elements) * int(logical_itemsize)
    if spec.kind in ("fp16", "bf16"):
        return int(n_elements) * 2
    padded = -(-int(n_elements) // spec.block) * spec.block
    return padded + (padded // spec.block) * _SCALE_BYTES


# ---------------------------------------------------------------------------
# quantized collectives (pure lax — trace under jit and shard_map)
# ---------------------------------------------------------------------------

def quantized_psum(x, axis: str, n: int, block: int = DEFAULT_BLOCK,
                   residual=None):
    """SUM of `x` over mesh axis `axis` (size `n`) with an int8
    block-quantized wire — the EQuARX structure in pure lax:

      1. quantize the (padded) payload per block;
      2. `all_to_all` the quantized shards + scales, so rank r holds
         every rank's shard r (~size/4 bytes on the wire);
      3. dequantize and accumulate locally in f32 (the reduce);
      4. requantize the reduced shard and `all_gather` it + its scales
         (~size/4 bytes again);
      5. dequantize locally.

    Value equals ``lax.psum(x, axis)`` up to two block-quantization
    stages of error. With ``residual`` (a float32 array of `x`'s shape,
    the previous step's quantization error) the payload is
    error-compensated and the call returns ``(y, new_residual)`` so the
    caller can carry it — compressed SUM then stays unbiased across
    steps (error feedback).
    """
    orig_dtype = x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    L = flat.shape[0]
    if residual is not None:
        if int(residual.size) != L:
            # a residual sized for some OTHER padding (e.g. a padded
            # row stack) would silently truncate here and the error
            # feedback would compensate the wrong elements
            raise ValueError(
                f"quantized_psum: residual has {int(residual.size)} "
                f"elements but the payload has {L}; the residual must "
                "carry exactly the unpadded payload's error")
        flat = flat + residual.astype(jnp.float32).reshape(-1)
    padded = _pad_flat(flat, n * int(block))
    m = padded.shape[0]
    block = _check_block(block, m, "quantized_psum")
    q, s = quantize_blocks(padded, block)
    # tiled all_to_all on the flat payload: chunk j of ours goes to rank
    # j; we receive every rank's chunk `rank` back-to-back. Scales ride
    # the same exchange (n divides m/block because n*block divides m).
    qg = lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=True)
    sg = lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=True)
    shard = dequantize_blocks(qg, sg, block).reshape(n, m // n).sum(axis=0)
    q2, s2 = quantize_blocks(shard, block)
    qa = lax.all_gather(q2, axis, tiled=True)
    sa = lax.all_gather(s2, axis, tiled=True)
    y = dequantize_blocks(qa, sa, block)[:L].reshape(x.shape).astype(
        orig_dtype)
    if residual is None:
        return y
    new_res = (padded - dequantize_blocks(q, s, block))[:L].reshape(x.shape)
    return y, new_res


def quantized_reduce_scatter_rows(rows, axis: str,
                                  block: int = DEFAULT_BLOCK,
                                  residual=None):
    """SUM-reduce-scatter of a ``(n, k)`` row stack over mesh axis
    `axis`: rank r receives ``sum_ranks(rows[r])`` as a float32 ``(k,)``
    shard, with each row block-quantized for the exchange (the ZeRO
    reduce-scatter wire, optim/zero.py). Rows are padded to the block
    internally, so `k` — and therefore the sharded optimizer-state
    layout — is unchanged by compression.

    With ``residual`` (float32 ``(n, ceil(k/block)*block)``, this
    rank's previous-step quantization error over its WHOLE padded row
    stack — the rank-private error-feedback shard the FSDP path
    carries, optim/fsdp.py) the payload is error-compensated before
    quantizing and the call returns ``(shard, new_residual)`` so the
    compressed reduce-scatter stays unbiased across steps. The residual
    is rank-private by construction: each rank compensates only the
    contribution it quantizes, never a peer's."""
    n, k = rows.shape
    block = int(block)
    if block <= 0:
        raise ValueError(
            "quantized_reduce_scatter_rows: quantization block must be "
            f"a positive int, got {block}")
    k2 = -(-k // block) * block
    _check_block(block, k2, "quantized_reduce_scatter_rows")
    if residual is not None and tuple(residual.shape) != (n, k2):
        # the residual layout is the PADDED row stack; any other shape
        # means the caller padded for a different block and a silent
        # reshape would feed the error back onto the wrong blocks
        raise ValueError(
            "quantized_reduce_scatter_rows: residual shape "
            f"{tuple(residual.shape)} does not match the padded row "
            f"stack ({n}, {k2}) for block {block}")
    if k2 != k:
        rows = jnp.pad(rows, ((0, 0), (0, k2 - k)))
    rows_f = rows.astype(jnp.float32)
    if residual is not None:
        rows_f = rows_f + residual.astype(jnp.float32)
    q, s = quantize_blocks(rows_f.reshape(-1), block)
    # row-major layout: row r occupies [r*k2, (r+1)*k2) and block
    # divides k2, so blocks never straddle rows and the tiled all_to_all
    # (chunk r = row r, scales likewise) keeps payload/scales aligned
    qg = lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=True)
    sg = lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=True)
    shard = dequantize_blocks(qg, sg, block).reshape(n, k2).sum(axis=0)
    if residual is None:
        return shard[:k]
    new_res = rows_f - dequantize_blocks(q, s, block).reshape(n, k2)
    return shard[:k], new_res


class Compression:
    """Namespace mirroring hvd.Compression (compression.py:69-74),
    grown with the int8 members and knob resolution."""

    none = NoneCompressor
    fp16 = FP16Compressor
    bf16 = BF16Compressor
    int8 = Int8BlockCompressor
    int8_raw = Int8BlockRawCompressor

    _BY_KIND = {
        "none": NoneCompressor,
        "fp16": FP16Compressor,
        "bf16": BF16Compressor,
        "int8": Int8BlockCompressor,
        "int8-raw": Int8BlockRawCompressor,
        "int8_raw": Int8BlockRawCompressor,
    }

    @classmethod
    def lookup(cls, name: str):
        spec = parse_wire(name)
        if spec is None:
            return NoneCompressor
        if spec.kind == "int8" and not spec.error_feedback:
            return Int8BlockRawCompressor
        return cls._BY_KIND[spec.kind]

    @classmethod
    def from_knobs(cls, knobs=None):
        """The knob-selected compressor (HOROVOD_COMPRESSION /
        legacy HOROVOD_COMPRESSION_WIRE_DTYPE) — what a `compression=
        None` DistributedOptimizer resolves to."""
        spec = resolve_wire(knobs)
        if spec is None:
            return NoneCompressor
        if spec.kind == "int8" and not spec.error_feedback:
            return Int8BlockRawCompressor
        return cls._BY_KIND[spec.kind]


def compressor_wire_spec(compression) -> Optional[WireSpec]:
    """WireSpec for a Compressor class/instance (None for the identity
    compressor) — the bridge from the user-facing Compression API to the
    wire plumbing."""
    kind = getattr(compression, "kind", "none")
    if kind == "none":
        return None
    block = int(getattr(compression, "block", 0) or 0)
    if block <= 0:
        from ..core.state import global_state

        block = int(global_state().knobs.compression_block
                    or DEFAULT_BLOCK)
    return WireSpec(kind, block,
                    bool(getattr(compression, "error_feedback", False)))


