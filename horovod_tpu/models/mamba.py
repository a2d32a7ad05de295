"""The state-space mixer: a Mamba-2 layer that trains.

What stands in a `mamba2` layer of `models/transformer.Block` where an
`attention` layer has its `Attention` (`TransformerConfig.layer_types`).
One input projection makes a gate z, an inner stream, the groups' B and
C and a step size a head; a short causal depthwise convolution runs over
the stream, B and C; a recurrence a head keeps a state of `d_head x
d_state`; a gated norm and an output projection follow (Dao & Gu 2024,
"Transformers are SSMs"; the equations as Hugging Face's
`GraniteMoeHybridMambaLayer` runs them, docs/mamba.md):

    [z | xBC | dt] = W_in u              widths d_inner | d_inner + 2 g N | H
    xBC = silu(conv(xBC) + b)            d_conv taps, zeros before position 0
    [x | B | C] = xBC                    widths d_inner | g N | g N
    dt = softplus(dt + dt_bias)          a head
    a = -exp(A_log)                      a head
    S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T      P x N a head, S_{-1} = 0
    y_t = S_t C_t + D x_t
    out = W_out (rmsnorm(y * silu(z)) * w)          the norm over all d_inner

The recurrence runs in its chunked form ("state space duality",
`ssd_scan`): inside a chunk of `mamba_chunk_size` positions every
output is a masked, decayed product over the chunk's inputs, (C B^T *
decay) X, matrix products on the MXU; between chunks a state is
carried. What is float32: dt, a * dt, their cumulative sums, every
exponential, the carried state and every product's accumulator. The
products' operands are the model's dtype (bf16 in training).

Two forms of it, chosen by what `ssd_scan` sees in its arguments and by
nothing else (`ops/ssd_scan.supports`: a chunk and a state that are
whole lane tiles, heads of a group in blocks that are whole tiles, a
floating dtype; `scan_runs_as_kernels`): where the shape admits it,
one Pallas kernel forward and one backward (`ops/ssd_scan.py`, a
`jax.custom_vjp`), in which a head's decay tile, `C B^T`, their product
and the chunk's state live in VMEM and only x, dt, the cumulative sums,
B, C, y and the states between chunks cross HBM; elsewhere
`_scan_chunks`, plain `lax` that JAX differentiates, which is also what
the kernels' tests compare with. The roundings stand at the same places
in both. `_log_decay_sums` (and a * dt) stay outside the kernels in
float32, and JAX differentiates them.

Memory: in the plain form a chunk's decay tile is `chunk x chunk` a
head, [H, T / chunk, chunk, chunk] float32 a layer (0.5 GiB at 64 heads
and 8,192 positions), written to HBM and read back; the kernels never
write a tile. Their forward call returns y and the state every chunk
starts from ([T / chunk, d_state, H * d_head] float32, 64 MiB there),
the backward's residual beside the call's own arguments. The last block
under `remat` keeps its matrix products' results
(`models/transformer._last_block_keeps`) and the forward kernel's, by
its name, so that it does not run it a second time; every other
rematerialised block runs the forward kernel twice, as it ran the plain
scan twice.

Scopes (`utils/scopes.LAYER_SCOPES`; every operation of the module lies
in exactly one): `mamba_proj` the two projections, `mamba_conv` the
convolution, silu, the splits and dt's softplus, `mamba_scan` everything
from x, dt, B, C to y, the two kernels' calls included (`SSD_SCAN_FWD`,
`SSD_SCAN_BWD` stand inside it), `mamba_gate` the gate and the norm.
Trace-time gauges (set by `ssd_scan`, the last traced call's):
`hvd_mamba_chunk`, `hvd_mamba_chunks_per_sequence`,
`hvd_mamba_state_bytes_per_sequence`; and, set by
`models/transformer._report` for a model that has such layers (from
`Mamba2Mixer.scans_as_kernels`), `hvd_mamba_scan_kernel_layers` /
`hvd_mamba_scan_plain_layers`.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..ops import ssd_scan as ssd_kernels
from ..utils import metrics, scopes

# dt at initialisation is log-uniform in this range (Mamba-2's own
# recipe; the source's config.json does not state it)
DT_INIT_RANGE = (1e-3, 1e-1)


def _log_decay_sums(log_decay):
    """Inclusive sums of a * dt along a chunk (axis 2 of `[B, chunks,
    chunk, ...]`), in float32: position l's entry is the log of the
    decay from the chunk's start through l. A sum of up to a chunk's
    terms that later stands in an exponent: in bf16 its last place is
    2^-8 of the sum (`tests/test_layer_kinds.py` runs that and sees the
    comparison with the reference fail)."""
    return jnp.cumsum(log_decay.astype(jnp.float32), axis=2)


def _scan_chunks(x, dt, a, b, c, chunk: int):
    """The chunked recurrence of `g` groups of `r` heads each, a group's
    heads sharing its B and C.

    x `[B, T, g, r, P]` (the model's dtype), dt `[B, T, g, r]` float32
    (after softplus), a `[g, r]` float32 (negative), b and c `[B, T, g,
    N]`, T a multiple of `chunk` -> y `[B, T, g, r, P]` float32, without
    D x."""
    bsz, t, g, r, p = x.shape
    n = b.shape[-1]
    nc = t // chunk
    dtype, f32 = x.dtype, jnp.float32
    x = x.reshape(bsz, nc, chunk, g, r, p)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    dt = dt.reshape(bsz, nc, chunk, g, r)
    cum = _log_decay_sums(dt * a)  # [B, nc, chunk, g, r]
    xdt = x.astype(f32) * dt[..., None]  # dt_s x_s

    # inside a chunk: y_l += sum_{s <= l} exp(cum_l - cum_s) (C_l . B_s)
    # dt_s x_s. The mask stands before the exponential: above the
    # diagonal cum_l - cum_s is positive and may overflow
    scores = jnp.einsum("bklgn,bksgn->bkgls", c, b,
                        preferred_element_type=f32)
    seg = cum.transpose(0, 1, 3, 4, 2)  # [B, nc, g, r, chunk]
    seg = seg[..., :, None] - seg[..., None, :]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    y = jnp.einsum(
        "bkgrls,bksgrp->bklgrp",
        (scores[:, :, :, None] * decay).astype(dtype), xdt.astype(dtype),
        preferred_element_type=f32)

    # a chunk's own contribution to the state at its end:
    # sum_s exp(cum_last - cum_s) dt_s x_s B_s^T
    to_end = jnp.exp(cum[:, :, -1:] - cum)
    ended = jnp.einsum(
        "bksgrp,bksgn->bkgrpn",
        (xdt * to_end[..., None]).astype(dtype), b,
        preferred_element_type=f32)

    # between chunks: S_k = exp(cum_last of k) S_{k-1} + ended_k, carried
    # in float32; `entering[k]` is the state chunk k starts from
    def carry(state, step):
        through, ended_k = step
        return through[..., None, None] * state + ended_k, state

    _, entering = lax.scan(
        carry, jnp.zeros((bsz, g, r, p, n), f32),
        (jnp.moveaxis(jnp.exp(cum[:, :, -1]), 1, 0),
         jnp.moveaxis(ended, 1, 0)))

    # what the entering state adds: y_l += exp(cum_l) S_entering C_l
    y += jnp.exp(cum)[..., None] * jnp.einsum(
        "bklgn,kbgrpn->bklgrp", c, entering.astype(dtype),
        preferred_element_type=f32)
    return y.reshape(bsz, t, g, r, p)


def ssd_scan(x, dt, a, b, c, d, chunk: int):
    """y_t = S_t C_t + D x_t with S_t = exp(dt_t a) S_{t-1} + dt_t x_t
    B_t^T, in chunks of `chunk` positions (any T: a sequence shorter
    than a chunk is one chunk, one that is no multiple is padded with
    positions of dt = 0, which neither decay the state nor add to it).

    x `[B, T, H, P]`, dt `[B, T, H]` float32, a and d `[H]` float32, b
    and c `[B, T, G, N]` with G groups of H / G heads each -> `[B, T, H,
    P]` float32."""
    bsz, t, h, p = x.shape
    groups, n = b.shape[2:]
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (
            z.ndim - 2)) for z in (x, dt, b, c))
    padded = t + pad
    # trace-time gauges: the chunk (the configuration's, or the sequence
    # where that is shorter), the chunks with the padding, and the
    # float32 state between them (heads x d_head x d_state x 4)
    metrics.trace_gauge(
        "hvd_mamba_chunk",
        "Positions in one chunk of the state-space scan", chunk)
    metrics.trace_gauge(
        "hvd_mamba_chunks_per_sequence",
        "Chunks the state-space scan cuts a sequence into",
        padded // chunk)
    metrics.trace_gauge(
        "hvd_mamba_state_bytes_per_sequence",
        "Bytes of float32 state a sequence carries between chunks",
        h * p * n * jnp.dtype(jnp.float32).itemsize)
    per_group = h // groups
    if scan_runs_as_kernels(padded, chunk, p, n, per_group, x.dtype):
        cum = _log_decay_sums(
            (dt * a).reshape(bsz, padded // chunk, chunk, h))
        # in the layout the convolution leaves and the gate reads: seen
        # as [.., H, P] an array of [.., H * P] is another tiling of
        # memory
        y = ssd_kernels.ssd_chunks(
            x.reshape(bsz, padded, h * p), dt,
            cum.reshape(bsz, padded, h), b, c, d, chunk).reshape(x.shape)
    else:
        y = _scan_chunks(
            x.reshape(bsz, padded, groups, per_group, p),
            dt.reshape(bsz, padded, groups, per_group),
            a.reshape(groups, per_group), b, c, chunk
        ).reshape(bsz, padded, h, p) + d[:, None] * x.astype(jnp.float32)
    return y[:, :t] if pad else y


def scan_runs_as_kernels(t: int, chunk: int, d_head: int, d_state: int,
                         heads_in_group: int, dtype) -> bool:
    """Whether `ssd_scan` hands a sequence of `t` positions to the
    kernels of `ops/ssd_scan.py`: decided by shapes alone, with the
    chunk a shorter sequence is cut to."""
    return ssd_kernels.supports(min(chunk, t), d_head, d_state,
                                heads_in_group, dtype)


def causal_conv(x, kernel, bias):
    """Depthwise causal convolution: `x` `[B, T, C]`, `kernel` `[K, C]`,
    `bias` `[C]` -> `[B, T, C]` float32, y_t = bias + sum_j kernel[j] *
    x[t - (K - 1) + j] with zeros before position 0 (the last tap
    multiplies the current position)."""
    taps, t = kernel.shape[0], x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    y = bias.astype(jnp.float32)
    for j in range(taps):
        y = y + kernel[j].astype(jnp.float32) * xf[:, j:j + t]
    return y


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of dt drawn log-uniform in DT_INIT_RANGE."""
    lo, hi = (math.log(v) for v in DT_INIT_RANGE)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _conv_init(taps: int):
    """Uniform in +- 1 / sqrt(taps): the default of the framework the
    source was trained in, for a depthwise kernel and for its bias."""
    bound = 1.0 / math.sqrt(taps)
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


class Mamba2Mixer(nn.Module):
    """`[B, T, hidden] -> [B, T, hidden]`; the sizes are the
    configuration's `mamba_*` keys (`models/transformer.TransformerConfig`,
    spelt as `benchmarks/layer_kinds/mamba2.KEYS` spells them)."""

    hidden_size: int
    n_heads: int
    d_head: int
    d_state: int
    d_conv: int = 4
    n_groups: int = 1
    chunk_size: int = 256
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16

    def scans_as_kernels(self, t: int) -> bool:
        """`scan_runs_as_kernels` of this mixer over `t` positions."""
        return scan_runs_as_kernels(
            t, self.chunk_size, self.d_head, self.d_state,
            self.n_heads // self.n_groups, self.dtype)

    @nn.compact
    def __call__(self, u):
        h, p, n, g = self.n_heads, self.d_head, self.d_state, self.n_groups
        d_inner, bc = h * p, g * n
        if h % g:
            raise ValueError(f"{h} heads do not divide into {g} groups")
        bsz, t, _ = u.shape
        f32 = jnp.float32
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=self.dtype, param_dtype=f32,
            kernel_init=nn.initializers.xavier_uniform())
        with jax.named_scope(scopes.MAMBA_PROJ):
            zxbcdt = dense(2 * d_inner + 2 * bc + h, name="in_proj")(u)
        with jax.named_scope(scopes.MAMBA_CONV):
            z, xbc, dt = jnp.split(
                zxbcdt, [d_inner, 2 * d_inner + 2 * bc], axis=-1)
            conv_init = _conv_init(self.d_conv)
            xbc = nn.silu(causal_conv(
                xbc,
                self.param("conv_kernel", conv_init,
                           (self.d_conv, d_inner + 2 * bc), f32),
                self.param("conv_bias", conv_init,
                           (d_inner + 2 * bc,), f32))).astype(self.dtype)
            x, b, c = jnp.split(xbc, [d_inner, d_inner + bc], axis=-1)
            dt = jax.nn.softplus(dt.astype(f32) + self.param(
                "dt_bias", _dt_bias_init, (h,), f32))
        with jax.named_scope(scopes.MAMBA_SCAN):
            a = -jnp.exp(self.param(
                "A_log", lambda *_: jnp.log(jnp.arange(1, h + 1, dtype=f32)),
                (h,), f32))
            y = ssd_scan(
                x.reshape(bsz, t, h, p), dt, a, b.reshape(bsz, t, g, n),
                c.reshape(bsz, t, g, n),
                self.param("D", nn.initializers.ones, (h,), f32),
                self.chunk_size)
        with jax.named_scope(scopes.MAMBA_GATE):
            gated = y.reshape(bsz, t, d_inner) * nn.silu(z.astype(f32))
            gated = gated * lax.rsqrt(jnp.mean(
                gated * gated, axis=-1, keepdims=True) + self.epsilon)
            gated = (gated * self.param(
                "norm_scale", nn.initializers.ones, (d_inner,), f32)
            ).astype(self.dtype)
        with jax.named_scope(scopes.MAMBA_PROJ):
            return dense(self.hidden_size, name="out_proj")(gated)
