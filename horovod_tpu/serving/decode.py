"""Autoregressive generation engine: AOT prefill/decode + slotted KV cache.

The one-shot serving engine (engine.py) answers ``predict`` — one
forward pass per request. The dominant production LM workload is
*decode*: one forward pass per generated TOKEN, hundreds per request,
with all the state between passes living in the KV cache. This module
is the mechanism layer for that workload (the policy layer — which
sequence runs when — is serving/scheduler.py):

* **Slotted KV cache** (:class:`SlottedKVCache`): one pair of
  ``(slots, layers, kv_heads, max_len, head_dim)`` buffers. A *slot* is
  a resident sequence's cache lane; slots are claimed at prefill,
  written in place every decode iteration, and recycled the moment a
  sequence finishes — no copy, no restart of co-resident sequences.
  Rows above a slot's current length hold the previous occupant's
  stale bytes; the attention validity mask (``position <= query
  position``) makes them unreachable, so recycling is free.
* **int8 block-quantized cache** (``HOROVOD_SERVING_KV_DTYPE=int8``):
  K/V rows are quantized with the same per-block symmetric int8
  primitives the collective wire uses (optim/compression.py
  ``quantize_blocks``/``dequantize_blocks``, docs/compression.md).
  Rows are quantized ONCE, on write; decode iterations dequantize for
  the attention read but never re-quantize old rows, so there is no
  step-over-step error accumulation — the cache holds exactly the
  codes written at append time (the error-feedback question the wire
  path has does not arise). ~4x cache HBM at a documented tolerance
  (docs/generation.md).
* **AOT executables**: like engine.py's batch-size buckets, programs
  are compiled up front and cached by shape — one *decode* program per
  ``(slots, max_len)`` bucket (one token for every slot per call) and
  one *prefill* program per prompt-length bucket (whole prompt through
  the model, K/V inserted into the claimed slot, first token emitted).
  ``HOROVOD_SERVING_DECODE_BUCKETS`` ("4x128,8x256") names the
  slot/len ladder; prefill lengths default to powers of two up to
  max_len.

The model side is ``models/transformer.py``'s ``kv_cache`` apply path:
this module owns the cache layout and quantization, the model stays a
pure function of (params, tokens, positions, cache).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import faults, metrics
from .engine import serving_knobs

KV_DTYPES = ("fp32", "bf16", "int8")


def parse_kv_dtype(name: Optional[str] = None) -> str:
    """``HOROVOD_SERVING_KV_DTYPE`` -> one of :data:`KV_DTYPES`."""
    if name is None:
        name = getattr(serving_knobs(), "serving_kv_dtype", "") or "fp32"
    name = str(name).strip().lower()
    aliases = {"float32": "fp32", "f32": "fp32", "bfloat16": "bf16",
               "": "fp32"}
    name = aliases.get(name, name)
    if name not in KV_DTYPES:
        raise ValueError(
            f"unknown KV cache dtype {name!r}; expected one of "
            f"{KV_DTYPES} (HOROVOD_SERVING_KV_DTYPE)")
    return name


def parse_decode_buckets(
        spec: Optional[str] = None) -> Tuple[Tuple[int, int], ...]:
    """``HOROVOD_SERVING_DECODE_BUCKETS`` ("4x128,8x256") -> sorted
    unique ``(slots, max_len)`` pairs."""
    if spec is None:
        spec = (getattr(serving_knobs(), "serving_decode_buckets", "")
                or "4x128")
    out = set()
    for part in str(spec).replace(";", ",").split(","):
        part = part.strip().lower()
        if not part:
            continue
        s, _, m = part.partition("x")
        try:
            pair = (int(s), int(m))
        except ValueError:
            raise ValueError(
                f"invalid decode bucket {part!r} in {spec!r}; expected "
                "SLOTSxMAXLEN, e.g. 4x128")
        if pair[0] < 1 or pair[1] < 2:
            raise ValueError(f"invalid decode bucket {part!r} in {spec!r}")
        out.add(pair)
    if not out:
        raise ValueError(f"empty decode bucket spec {spec!r}")
    return tuple(sorted(out))


def default_prefill_buckets(max_len: int) -> Tuple[int, ...]:
    """Power-of-two prompt-length ladder up to ``max_len`` (engine.py's
    bucket idea applied to sequence length)."""
    out = []
    b = 8
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(sorted(set(out)))


# ---------------------------------------------------------------------------
# slotted KV cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """Static shape/dtype contract of one slotted cache: buffers are
    ``(slots, layers, kv_heads, max_len, head_dim)``; ``dtype`` in
    {fp32, bf16, int8}; ``block`` the int8 quantization granularity
    along head_dim (0 = one scale per row, i.e. block = head_dim)."""

    slots: int
    layers: int
    kv_heads: int
    max_len: int
    head_dim: int
    dtype: str = "fp32"
    block: int = 0
    compute_dtype: Any = None  # jnp dtype the model computes in

    @property
    def resolved_block(self) -> int:
        b = int(self.block) if self.block else self.head_dim
        if b <= 0 or self.head_dim % b:
            # a block that does not divide head_dim cannot tile the
            # row; fall back to per-row scales rather than mis-scale
            b = self.head_dim
        return b

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.slots, self.layers, self.kv_heads, self.max_len,
                self.head_dim)

    @property
    def scale_shape(self) -> Tuple[int, ...]:
        return (self.slots, self.layers, self.kv_heads, self.max_len,
                self.head_dim // self.resolved_block)

    def buffer_structs(self) -> Dict[str, Any]:
        """jax.ShapeDtypeStruct per buffer — the AOT lowering inputs."""
        import jax
        import jax.numpy as jnp

        if self.dtype == "int8":
            return {
                "k": jax.ShapeDtypeStruct(self.shape, jnp.int8),
                "v": jax.ShapeDtypeStruct(self.shape, jnp.int8),
                "k_scale": jax.ShapeDtypeStruct(self.scale_shape,
                                                jnp.float32),
                "v_scale": jax.ShapeDtypeStruct(self.scale_shape,
                                                jnp.float32),
            }
        dt = jnp.bfloat16 if self.dtype == "bf16" else jnp.float32
        return {"k": jax.ShapeDtypeStruct(self.shape, dt),
                "v": jax.ShapeDtypeStruct(self.shape, dt)}

    def allocate(self) -> Dict[str, Any]:
        """Zero-initialized device buffers (stale rows are masked, so
        zeros are merely a defined starting point)."""
        import jax.numpy as jnp

        return {name: jnp.zeros(s.shape, s.dtype)
                for name, s in self.buffer_structs().items()}

    def nbytes(self) -> int:
        return sum(int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
                   for s in self.buffer_structs().values())


def _quantize_rows(x, block: int):
    """Per-block symmetric int8 quantization along the LAST axis of
    ``x`` (block divides it): the cache-row application of
    optim/compression.quantize_blocks. Returns (codes int8 same shape,
    scales f32 with last axis D/block)."""
    from ..optim.compression import quantize_blocks

    q, s = quantize_blocks(x.astype("float32").reshape(-1), block)
    return (q.reshape(x.shape),
            s.reshape(x.shape[:-1] + (x.shape[-1] // block,)))


def _dequantize_rows(q, s, block: int):
    """Inverse of :func:`_quantize_rows` (float32)."""
    import jax.numpy as jnp

    qf = q.astype(jnp.float32)
    shaped = qf.reshape(q.shape[:-1] + (q.shape[-1] // block, block))
    out = shaped * s.astype(jnp.float32)[..., None]
    return out.reshape(q.shape)


class SlottedKVCache:
    """Traced cache carrier for the model's ``kv_cache`` apply path.

    Constructed INSIDE a jitted function around the buffer arguments;
    ``update`` rebinds the buffers functionally (single-pass tracing
    makes attribute rebinding safe) and the caller returns
    ``cache.buffers`` as outputs, closing the loop.
    """

    def __init__(self, spec: KVCacheSpec, buffers: Dict[str, Any]):
        self.spec = spec
        self.buffers = dict(buffers)

    def update(self, layer: int, k_new, v_new, positions):
        """Append ``k_new``/``v_new`` ``[B, T, KH, D]`` at absolute
        ``positions`` ``[B, T]`` in layer ``layer``'s slice, returning
        ``(k_full, v_full, valid)``: the whole dequantized layer slice
        ``[B, KH, M, D]`` in the compute dtype and the position
        validity mask ``[B, T, M]``.

        The write is a one-hot merge: positions >= max_len produce an
        all-zero one-hot row (a saturated slot writes nothing instead
        of corrupting row 0), and the merge arithmetic runs in f32 —
        int8 codes are integers <= 127, exactly representable, so the
        round-trip through the merge is bit-exact for untouched rows.
        """
        import jax
        import jax.numpy as jnp

        spec = self.spec
        M = spec.max_len
        oh = jax.nn.one_hot(positions, M, dtype=jnp.float32)  # [B,T,M]
        cov = jnp.clip(jnp.sum(oh, axis=1), 0.0, 1.0)         # [B,M]
        keep = (1.0 - cov)[:, None, :, None]                  # [B,1,M,1]
        compute_dtype = spec.compute_dtype or jnp.float32

        def merge(cache_slice, new_btkd):
            # [B,KH,M,*] * keep + one-hot-scattered new rows
            delta = jnp.einsum("btm,btkd->bkmd",
                               oh, new_btkd.astype(jnp.float32))
            return cache_slice.astype(jnp.float32) * keep + delta

        outs = []
        for name, new in (("k", k_new), ("v", v_new)):
            buf = self.buffers[name]
            layer_slice = buf[:, layer]  # [B,KH,M,D]
            if spec.dtype == "int8":
                block = spec.resolved_block
                codes, scales = _quantize_rows(new, block)  # [B,T,KH,*]
                merged_codes = jnp.round(
                    merge(layer_slice, codes)).astype(jnp.int8)
                sbuf = self.buffers[name + "_scale"]
                merged_scales = merge(sbuf[:, layer], scales)
                self.buffers[name] = buf.at[:, layer].set(merged_codes)
                self.buffers[name + "_scale"] = sbuf.at[:, layer].set(
                    merged_scales)
                full = _dequantize_rows(merged_codes, merged_scales,
                                        block)
            else:
                merged = merge(layer_slice, new).astype(buf.dtype)
                self.buffers[name] = buf.at[:, layer].set(merged)
                full = merged
            outs.append(full.astype(compute_dtype))
        m_idx = jnp.arange(M, dtype=positions.dtype)
        valid = m_idx[None, None, :] <= positions[:, :, None]  # [B,T,M]
        return outs[0], outs[1], valid


# ---------------------------------------------------------------------------
# checkpoint metadata <-> TransformerConfig
# ---------------------------------------------------------------------------

#: serving-metadata model name for a generation-capable transformer LM
TRANSFORMER_LM = "transformer_lm"

_CFG_DTYPES = {"float32": "float32", "fp32": "float32",
               "bfloat16": "bfloat16", "bf16": "bfloat16"}


def config_to_meta(cfg) -> Dict[str, Any]:
    """TransformerConfig -> a JSON-safe dict for checkpoint metadata
    (the generation twin of engine.py's mlp ``features`` block)."""
    import jax.numpy as jnp

    d = dataclasses.asdict(cfg)
    d["dtype"] = ("bfloat16" if cfg.dtype == jnp.bfloat16 else "float32")
    return d


def config_from_meta(d: Dict[str, Any]):
    """Inverse of :func:`config_to_meta`."""
    import jax.numpy as jnp

    from ..models.transformer import TransformerConfig

    d = dict(d)
    name = _CFG_DTYPES.get(str(d.get("dtype", "bfloat16")).lower(),
                           "bfloat16")
    d["dtype"] = jnp.bfloat16 if name == "bfloat16" else jnp.float32
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    return TransformerConfig(**{k: v for k, v in d.items()
                                if k in fields})


# ---------------------------------------------------------------------------
# generation engine
# ---------------------------------------------------------------------------

class GenerationEngine:
    """AOT prefill + single-token greedy decode over a slotted cache.

    Mechanism only: ``claim_slot``/``release_slot`` hand out cache
    lanes, ``prefill`` runs a prompt into a claimed slot and returns
    the first generated token, ``decode`` advances EVERY slot one
    token (callers ignore outputs of inactive slots). The scheduler
    (serving/scheduler.py) owns which sequence occupies which slot and
    when; this class owns shapes, compilation and the cache.

    Thread-safety: one lock around execution (one accelerator per
    replica, same discipline as InferenceEngine); compilation has its
    own lock so a cold prefill bucket never stalls decode iterations.
    """

    MAX_CACHED_EXECUTABLES = 16

    def __init__(
        self,
        model,
        params: Any,
        *,
        slots: Optional[int] = None,
        max_len: Optional[int] = None,
        prefill_buckets: Optional[Sequence[int]] = None,
        kv_dtype: Optional[str] = None,
        kv_block: Optional[int] = None,
        eos_id: Optional[int] = None,
    ):
        import jax

        cfg = model.cfg
        if not cfg.causal:
            raise ValueError(
                "autoregressive generation needs a causal LM "
                "(TransformerConfig.causal=True)")
        from ..models.transformer import Transformer, cache_gaps

        gaps = cache_gaps(cfg)
        if gaps:
            raise ValueError("this model cannot be served: "
                             + "; ".join(gaps))
        # rematerialisation trades activation memory for backward
        # recompute; inference has no backward, and a rematerialised
        # block cannot abstractify the SlottedKVCache carrier — a
        # remat-trained checkpoint must still serve
        cfg = dataclasses.replace(cfg, remat=False)
        model = Transformer(cfg, attention_fn=model.attention_fn)
        sk = serving_knobs()
        if slots is None or max_len is None:
            # largest configured (slots, max_len) bucket: the decode
            # program every iteration runs; smaller buckets stay
            # available through the ladder spec for smaller replicas
            ladder = parse_decode_buckets()
            pick = ladder[-1]
            slots = slots if slots is not None else pick[0]
            max_len = max_len if max_len is not None else pick[1]
        if max_len > cfg.max_seq_len:
            raise ValueError(
                f"cache max_len {max_len} exceeds the model's "
                f"max_seq_len {cfg.max_seq_len} (rope/pos tables)")
        if kv_dtype is None:
            kv_dtype = parse_kv_dtype()
        if kv_block is None:
            kv_block = int(getattr(sk, "serving_kv_block", 0) or 0)
        self.model = model
        self.cfg = cfg
        self.eos_id = eos_id
        self.spec = KVCacheSpec(
            slots=int(slots), layers=cfg.num_layers,
            kv_heads=cfg.kv_heads, max_len=int(max_len),
            head_dim=cfg.head_width, dtype=parse_kv_dtype(kv_dtype),
            block=int(kv_block), compute_dtype=cfg.dtype,
        )
        self._params = jax.device_put(params)
        self._cache = self.spec.allocate()
        if prefill_buckets is None:
            knob = getattr(sk, "serving_prefill_buckets", "") or ""
            prefill_buckets = ([int(b) for b in
                                knob.replace(";", ",").split(",")
                                if b.strip()] if knob
                               else default_prefill_buckets(
                                   self.spec.max_len))
        self._prefill_buckets = tuple(sorted(set(
            int(b) for b in prefill_buckets
            if int(b) <= self.spec.max_len)))
        if not self._prefill_buckets:
            raise ValueError("no prefill bucket fits under max_len")
        self._exe: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._lock = threading.Lock()
        self._compile_lock = threading.Lock()
        self._free = list(range(self.spec.slots))
        self._slot_lock = threading.Lock()

    # -- construction from a checkpoint -------------------------------------

    @classmethod
    def from_checkpoint(cls, path: str, **kwargs) -> "GenerationEngine":
        """Restore a generation-capable LM checkpoint: metadata
        ``{"serving": {"model": "transformer_lm", "config": {...},
        "eos": id}}`` (save side: :func:`config_to_meta`)."""
        from ..checkpoint import load_params
        from ..models.transformer import Transformer
        from .engine import SERVING_META_KEY

        params, metadata = load_params(path)
        meta = dict(metadata.get(SERVING_META_KEY, {}))
        if meta.get("model") != TRANSFORMER_LM:
            raise ValueError(
                f"checkpoint is not a generation LM (metadata model = "
                f"{meta.get('model')!r}; expected {TRANSFORMER_LM!r})")
        cfg = config_from_meta(meta.get("config", {}))
        kwargs.setdefault("eos_id", meta.get("eos"))
        eng = cls(Transformer(cfg), params, **kwargs)
        eng.metadata = metadata
        return eng

    # -- shape bookkeeping ---------------------------------------------------

    @property
    def slots(self) -> int:
        return self.spec.slots

    @property
    def max_len(self) -> int:
        return self.spec.max_len

    @property
    def prefill_buckets(self) -> Tuple[int, ...]:
        return self._prefill_buckets

    @property
    def cached_executables(self) -> int:
        return len(self._exe)

    @property
    def free_slots(self) -> int:
        with self._slot_lock:
            return len(self._free)

    def claim_slot(self) -> Optional[int]:
        """Take a free cache lane (None when full); the claim is just
        index bookkeeping — the lane's stale rows are masked until the
        prefill overwrites them."""
        with self._slot_lock:
            return self._free.pop(0) if self._free else None

    def release_slot(self, slot: int) -> None:
        with self._slot_lock:
            if slot in self._free:
                raise ValueError(f"slot {slot} already free")
            self._free.append(int(slot))
            self._free.sort()

    def prefill_bucket_for(self, n: int) -> int:
        for b in self._prefill_buckets:
            if b >= n:
                return b
        raise ValueError(
            f"prompt of {n} tokens exceeds the top prefill bucket "
            f"{self._prefill_buckets[-1]} (cache max_len "
            f"{self.spec.max_len})")

    # -- compiled programs ---------------------------------------------------

    def _cache_structs(self):
        return self.spec.buffer_structs()

    def _executable(self, key: Tuple, build_fn):
        import jax

        with self._compile_lock:
            ex = self._exe.get(key)
            if ex is not None:
                self._exe.move_to_end(key)
                return ex
            t0 = time.perf_counter()
            fn, args = build_fn()
            # donate the cache buffers (arg 1 of both decode_fn and
            # prefill_fn): the caller rebinds self._cache to the
            # returned buffers and never reads the old ones, and
            # without donation every generated token would copy the
            # whole cache — the dominant HBM object here — doubling
            # its peak footprint. CPU has no donation (jax warns per
            # compile), so only the accelerator path asks for it.
            donate = ((1,) if jax.default_backend() != "cpu" else ())
            ex = jax.jit(fn, donate_argnums=donate).lower(
                *args).compile()
            self._exe[key] = ex
            while len(self._exe) > self.MAX_CACHED_EXECUTABLES:
                self._exe.popitem(last=False)
            metrics.record_serving_compile(
                key[1] if len(key) > 1 else self.spec.slots,
                time.perf_counter() - t0)
            return ex

    def _decode_exe(self, return_logits: bool = False):
        import jax
        import jax.numpy as jnp

        spec = self.spec

        def build():
            def decode_fn(params, buffers, tokens, lengths):
                cache = SlottedKVCache(spec, buffers)
                logits = self.model.apply(
                    {"params": params}, tokens[:, None],
                    positions=lengths[:, None], kv_cache=cache)
                last = logits[:, -1].astype(jnp.float32)
                nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
                if return_logits:
                    return cache.buffers, nxt, last
                # steady-state program: the [slots, vocab] logits
                # never leave the device — at production vocab sizes
                # that copy would be ~1 MB of device→host traffic per
                # generated token on the hottest loop in the system
                return cache.buffers, nxt

            s = jax.ShapeDtypeStruct
            return decode_fn, (
                self._params, self._cache_structs(),
                s((spec.slots,), jnp.int32), s((spec.slots,), jnp.int32))

        return self._executable(
            ("decode", spec.slots, spec.max_len, bool(return_logits)),
            build)

    def _prefill_exe(self, bucket: int):
        import jax
        import jax.numpy as jnp

        spec = self.spec
        local_spec = dataclasses.replace(
            spec, slots=1, max_len=bucket, dtype="fp32")

        def build():
            def prefill_fn(params, buffers, tokens, slot, length):
                # the prompt runs through a LOCAL fp32 cache (M = the
                # prompt bucket) — prefill attention is exactly the
                # causal forward, expressed through the same cache
                # path — then the computed rows are converted to the
                # slotted cache's storage (cast, or int8-quantized
                # once) and inserted at the claimed slot
                local = SlottedKVCache(
                    local_spec,
                    {n: jnp.zeros(s.shape, s.dtype) for n, s in
                     local_spec.buffer_structs().items()})
                pos = jnp.arange(bucket, dtype=jnp.int32)[None]
                logits = self.model.apply(
                    {"params": params}, tokens, positions=pos,
                    kv_cache=local)
                last = jnp.take_along_axis(
                    logits.astype(jnp.float32),
                    (length - 1)[None, None, None].astype(jnp.int32)
                    .repeat(logits.shape[-1], axis=-1),
                    axis=1)[0, 0]
                first = jnp.argmax(last).astype(jnp.int32)
                out = dict(buffers)
                zeros5 = (slot.astype(jnp.int32), 0, 0, 0, 0)
                for name in ("k", "v"):
                    rows = local.buffers[name]  # [1,L,KH,T,D] f32
                    if spec.dtype == "int8":
                        block = spec.resolved_block
                        codes, scales = _quantize_rows(rows, block)
                        out[name] = jax.lax.dynamic_update_slice(
                            out[name], codes, zeros5)
                        out[name + "_scale"] = (
                            jax.lax.dynamic_update_slice(
                                out[name + "_scale"], scales, zeros5))
                    else:
                        out[name] = jax.lax.dynamic_update_slice(
                            out[name],
                            rows.astype(out[name].dtype), zeros5)
                return out, first, last

            s = jax.ShapeDtypeStruct
            return prefill_fn, (
                self._params, self._cache_structs(),
                s((1, bucket), jnp.int32), s((), jnp.int32),
                s((), jnp.int32))

        return self._executable(("prefill", bucket), build)

    def warmup(self) -> None:
        """AOT-compile the decode program and every prefill bucket so
        the first request of each shape pays no compile."""
        self._decode_exe()
        for b in self._prefill_buckets:
            self._prefill_exe(b)

    # -- execution -----------------------------------------------------------

    def prefill(self, slot: int, tokens: Sequence[int]) -> Tuple[int,
                                                                 np.ndarray]:
        """Run ``tokens`` into slot ``slot``; returns ``(first_token,
        last_logits)`` — the greedy continuation and its logits (the
        tolerance tests compare these across KV dtypes)."""
        import jax.numpy as jnp

        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = tokens.shape[0]
        if n < 1:
            raise ValueError("prefill needs at least one prompt token")
        if n >= self.spec.max_len:
            raise ValueError(
                f"prompt of {n} tokens leaves no room to generate "
                f"under max_len {self.spec.max_len}")
        bucket = self.prefill_bucket_for(n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = tokens
        ex = self._prefill_exe(bucket)
        t0 = time.perf_counter()
        with self._lock:
            faults.inject("serving.decode_prefill", bucket=bucket)
            self._cache, first, last = ex(
                self._params, self._cache, jnp.asarray(padded),
                jnp.int32(slot), jnp.int32(n))
        first = int(first)
        metrics.record_decode_prefill(bucket, time.perf_counter() - t0)
        return first, np.asarray(last)

    def decode(self, tokens: np.ndarray, lengths: np.ndarray,
               return_logits: bool = False,
               slos: Optional[Sequence[str]] = None,
               ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """One iteration: append ``tokens[i]`` at position
        ``lengths[i]`` in every slot i and return ``(next_tokens,
        last_logits)`` (``[slots]``, and ``[slots, vocab]`` only under
        ``return_logits`` — the steady-state program keeps logits on
        device; the flag exists for the tolerance tests). Inactive
        slots ride along (their outputs are ignored; pass length 0 so
        their write lands in a row the next prefill overwrites).

        ``slos`` names the SLO class of each LIVE sequence this
        iteration advances (the scheduler passes one entry per
        occupied slot): the iteration's wall time is then billed to
        each as its time-per-output-token
        (``hvd_serving_tpot_seconds{slo=...}``)."""
        import jax.numpy as jnp

        tokens = np.asarray(tokens, np.int32).reshape(self.spec.slots)
        lengths = np.asarray(lengths, np.int32).reshape(self.spec.slots)
        ex = self._decode_exe(return_logits)
        t0 = time.perf_counter()
        with self._lock:
            faults.inject("serving.decode_step")
            out = ex(self._params, self._cache, jnp.asarray(tokens),
                     jnp.asarray(lengths))
            if return_logits:
                self._cache, nxt, last = out
            else:
                self._cache, nxt = out
                last = None
        dt = time.perf_counter() - t0
        metrics.record_decode_iteration(int(self.spec.slots), dt)
        if slos:
            # every live sequence got exactly one token out of this
            # iteration, so the iteration's wall time IS each one's
            # per-output-token latency
            for slo in slos:
                metrics.record_serving_tpot(dt, slo=slo)
        return (np.asarray(nxt),
                np.asarray(last) if last is not None else None)

    def cache_nbytes(self) -> int:
        return self.spec.nbytes()
