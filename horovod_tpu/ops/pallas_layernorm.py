"""Pallas TPU fused LayerNorm / RMSNorm (training fwd + bwd).

Why: the round-4 BERT-L xplane trace shows XLA's standalone LayerNorm
fusions running ~9× above the HBM floor (≈700 µs for a 50 MB read+write
pass on [24,512,1024]); across 49 norm sites that is ~15% of step time
(docs/benchmarks.md). Unlike the CNN case — where XLA hides BatchNorm
inside conv mega-fusions and a custom call only breaks that fusion —
transformer norms are standalone ops in default layouts, so a bandwidth-
shaped kernel is a clean win.

Design: one pass each direction, no saved statistics.

    fwd:  read x         → y = (x−μ)·rstd·γ (+β)          (1R + 1W)
    bwd:  read x, dy     → recompute μ/rstd per row (VPU-cheap),
          dx = rstd·(γdy − mean(γdy) − x̂·mean(γdy·x̂))     (2R + 1W)
          dγ += Σrows dy·x̂ ; dβ += Σrows dy               (accumulated
          across the sequential grid, same trick as pallas_batchnorm)

RMSNorm is the μ=0 / no-β specialization (`kind="rmsnorm"`), matching
models/transformer.py's RMSNorm.

The row dimension is everything but the trailing axis; rows are masked
with an iota guard on the tail block. When C % 128 != 0 the lane
padding is masked out of the row-wise reductions.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from ._pallas import interpret



def _row_block(c: int) -> int:
    target = (1024 * 1024) // (2 * c)
    return max(8, min(1024, (target // 8) * 8))


def _masks(shape, base, nrows, c_true):
    rows = lax.broadcasted_iota(jnp.int32, shape, 0) + base
    valid = rows < nrows
    if c_true != shape[1]:  # only when Mosaic pads lanes
        lanes = lax.broadcasted_iota(jnp.int32, shape, 1)
        valid = jnp.logical_and(valid, lanes < c_true)
    return valid


def _stats(xf, c, rms, eps):
    if rms:
        ms = jnp.sum(xf * xf, axis=1, keepdims=True) / c
        return jnp.zeros_like(ms), lax.rsqrt(ms + eps)
    mean = jnp.sum(xf, axis=1, keepdims=True) / c
    var = jnp.sum(xf * xf, axis=1, keepdims=True) / c - mean * mean
    return mean, lax.rsqrt(jnp.maximum(var, 0.0) + eps)


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, *, nrows, block_r, c_true,
                eps, rms):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    valid = _masks(x.shape, i * block_r, nrows, c_true)
    x = jnp.where(valid, x, 0.0)
    mean, rstd = _stats(x, c_true, rms, eps)
    y = (x - mean) * rstd * g_ref[...]
    if b_ref is not None:
        y = y + b_ref[...]
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, g_ref, dx_ref, dg_ref, db_ref, *, nrows,
                block_r, c_true, eps, rms):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        if db_ref is not None:
            db_ref[...] = jnp.zeros_like(db_ref)

    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    valid = _masks(x.shape, i * block_r, nrows, c_true)
    x = jnp.where(valid, x, 0.0)
    dy = jnp.where(valid, dy, 0.0)
    mean, rstd = _stats(x, c_true, rms, eps)
    xhat = (x - mean) * rstd
    gdy = dy * g_ref[...]
    s2 = jnp.sum(gdy * xhat, axis=1, keepdims=True) / c_true
    if rms:
        dx = rstd * (gdy - xhat * s2)
    else:
        s1 = jnp.sum(gdy, axis=1, keepdims=True) / c_true
        dx = rstd * (gdy - s1 - xhat * s2)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dg_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    if db_ref is not None:
        db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _run_fwd(x2, g2, b2, eps, rms, c_true):
    n2, c2 = x2.shape
    block_r = _row_block(c2)
    grid = (-(-n2 // block_r),)
    big = pl.BlockSpec((block_r, c2), lambda i: (i, 0))
    vec = pl.BlockSpec((1, c2), lambda i: (0, 0))
    kw = dict(nrows=n2, block_r=block_r, c_true=c_true, eps=eps, rms=rms)
    if b2 is None:
        def kernel(x_ref, g_ref, y_ref):
            _fwd_kernel(x_ref, g_ref, None, y_ref, **kw)
        args, in_specs = (x2, g2), [big, vec]
    else:
        def kernel(x_ref, g_ref, b_ref, y_ref):
            _fwd_kernel(x_ref, g_ref, b_ref, y_ref, **kw)
        args, in_specs = (x2, g2, b2), [big, vec, vec]
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=big,
        out_shape=jax.ShapeDtypeStruct((n2, c2), x2.dtype),
        interpret=interpret(),
    )(*args)


def _run_bwd(x2, dy2, g2, eps, rms, c_true, with_beta):
    n2, c2 = x2.shape
    block_r = _row_block(c2)
    grid = (-(-n2 // block_r),)
    big = pl.BlockSpec((block_r, c2), lambda i: (i, 0))
    vec = pl.BlockSpec((1, c2), lambda i: (0, 0))
    kw = dict(nrows=n2, block_r=block_r, c_true=c_true, eps=eps, rms=rms)
    if with_beta:
        def kernel(x_ref, dy_ref, g_ref, dx_ref, dg_ref, db_ref):
            _bwd_kernel(x_ref, dy_ref, g_ref, dx_ref, dg_ref, db_ref,
                        **kw)
        out_specs = [big, vec, vec]
        out_shape = [
            jax.ShapeDtypeStruct((n2, c2), x2.dtype),
            jax.ShapeDtypeStruct((1, c2), jnp.float32),
            jax.ShapeDtypeStruct((1, c2), jnp.float32),
        ]
    else:
        def kernel(x_ref, dy_ref, g_ref, dx_ref, dg_ref):
            _bwd_kernel(x_ref, dy_ref, g_ref, dx_ref, dg_ref, None, **kw)
        out_specs = [big, vec]
        out_shape = [
            jax.ShapeDtypeStruct((n2, c2), x2.dtype),
            jax.ShapeDtypeStruct((1, c2), jnp.float32),
        ]
    return pl.pallas_call(
        kernel, grid=grid, in_specs=[big, big, vec], out_specs=out_specs,
        out_shape=out_shape, interpret=interpret(),
    )(x2, dy2, g2)


def _vec(v, c2):
    return v.reshape(1, c2).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fln(x, gamma, beta, eps, rms):
    return _fln_f(x, gamma, beta, eps, rms)[0]


def _fln_f(x, gamma, beta, eps, rms):
    shape = x.shape
    c = shape[-1]
    x2 = x.reshape(-1, c)
    b2 = None if beta is None else _vec(beta, c)
    y2 = _run_fwd(x2, _vec(gamma, c), b2, eps, rms, c)
    return y2.reshape(shape), (x, gamma)


def _fln_b(eps, rms, saved, dy):
    x, gamma = saved
    shape = x.shape
    c = shape[-1]
    out = _run_bwd(x.reshape(-1, c), dy.reshape(-1, c), _vec(gamma, c),
                   eps, rms, c, with_beta=True)
    dx2, dg2, db2 = out
    return (dx2.reshape(shape), dg2.reshape(c).astype(gamma.dtype),
            db2.reshape(c).astype(gamma.dtype))


_fln.defvjp(_fln_f, _fln_b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fln_nobeta(x, gamma, eps, rms):
    return _fln_nobeta_f(x, gamma, eps, rms)[0]


def _fln_nobeta_f(x, gamma, eps, rms):
    shape = x.shape
    c = shape[-1]
    y2 = _run_fwd(x.reshape(-1, c), _vec(gamma, c), None, eps, rms, c)
    return y2.reshape(shape), (x, gamma)


def _fln_nobeta_b(eps, rms, saved, dy):
    x, gamma = saved
    shape = x.shape
    c = shape[-1]
    dx2, dg2 = _run_bwd(x.reshape(-1, c), dy.reshape(-1, c),
                        _vec(gamma, c), eps, rms, c, with_beta=False)
    return dx2.reshape(shape), dg2.reshape(c).astype(gamma.dtype)


_fln_nobeta.defvjp(_fln_nobeta_f, _fln_nobeta_b)


def fused_layer_norm(
    x: jax.Array,
    gamma: jax.Array,
    beta: Optional[jax.Array] = None,
    *,
    eps: float = 1e-5,
    kind: str = "layernorm",
) -> jax.Array:
    """LayerNorm (or RMSNorm) over the trailing axis as single-pass
    pallas kernels. ``beta=None`` omits the shift (RMSNorm never has
    one). Output dtype follows ``x``; statistics are f32."""
    if kind not in ("layernorm", "rmsnorm"):
        raise ValueError(f"unknown kind {kind!r}")
    rms = kind == "rmsnorm"
    if rms and beta is not None:
        raise ValueError("rmsnorm has no beta/shift parameter")
    if beta is None:
        return _fln_nobeta(x, gamma, float(eps), rms)
    return _fln(x, gamma, beta, float(eps), rms)


class FusedLayerNorm(nn.Module):
    """Drop-in ``nn.LayerNorm`` / models.transformer.RMSNorm replacement
    backed by the pallas kernels; param names match flax ("scale",
    "bias") so checkpoints interchange."""

    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    kind: str = "layernorm"
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        gamma = self.param("scale", nn.initializers.ones, (c,),
                           self.param_dtype)
        beta = None
        if self.kind == "layernorm" and self.use_bias:
            beta = self.param("bias", nn.initializers.zeros, (c,),
                              self.param_dtype)
        y = fused_layer_norm(x, gamma, beta, eps=self.epsilon,
                             kind=self.kind)
        return y.astype(self.dtype)
