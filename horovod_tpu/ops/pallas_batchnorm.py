"""Pallas TPU fused BatchNorm (training fwd + bwd) with relu/residual
epilogues.

Why this exists: the round-3 xplane trace of the ResNet-50 step showed
~48% of device time in XLA's BatchNorm statistics/backward reduce
fusions (`convert_reduce_fusion`) running at well under half of
achievable HBM bandwidth, while the convolutions themselves were near
peak (docs/benchmarks.md has the breakdown). The reference has no TPU
counterpart (its SyncBatchNorm, torch/sync_batch_norm.py, rides on
framework BN kernels); this is the TPU-first replacement for the BN hot
path: the same minimal pass structure XLA uses —

    fwd:  stats (1R)  →  normalize+act[+residual] (1R+1W)
    bwd:  dγ/dβ reduce (2R)  →  dx[+dres] (2R+1W[+1W])

— but with every per-channel constant folded ahead of time so each pass
is a single fused-multiply-add sweep at memory bandwidth:

    y   = act(x·s + t [+ res]);   s = γ·rstd, t = β − μ·s
    dx  = dy_eff·A + x·B + C      (A = γ·rstd, B/C fold μ, rstd, dγ, dβ)

with dy_eff = dy·1[x·s + t (+res) > 0] recomputing the relu mask from x
so the backward never reads y.

Channel handling: C < 128 with 128 % C == 0 folds rows into lanes
([N, C] → [N/f, C·f], exact, so C=64 stem/stage-1 tensors use full lane
width); other C run at their logical width (Mosaic pads lanes
internally). Row remainders are masked with an iota guard in every
reduce kernel.

Falls back to `interpret=True` off-TPU so the CPU test mesh runs the
same code path (same convention as pallas_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from ._pallas import interpret



def _ceil_to(n, m):
    return -(-n // m) * m


def _row_block(c2: int) -> int:
    """Rows per grid step: target ~1MB bf16 tiles, multiple of 8."""
    target = (1024 * 1024) // (2 * c2)
    return max(8, min(1024, (target // 8) * 8))


def _row_mask(shape, base, nrows):
    rows = lax.broadcasted_iota(jnp.int32, shape, 0) + base
    return rows < nrows


# -- kernels (all on 2-D [N, C2] views) ------------------------------------


def _stats_kernel(x_ref, sum_ref, sq_ref, *, nrows, block_r):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    x = x_ref[...].astype(jnp.float32)
    valid = _row_mask(x.shape, i * block_r, nrows)
    x = jnp.where(valid, x, 0.0)
    sum_ref[...] += jnp.sum(x, axis=0, keepdims=True)
    sq_ref[...] += jnp.sum(x * x, axis=0, keepdims=True)


def _apply_kernel(x_ref, s_ref, t_ref, y_ref, *, relu):
    y = x_ref[...].astype(jnp.float32) * s_ref[...] + t_ref[...]
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)


def _apply_res_kernel(x_ref, s_ref, t_ref, res_ref, y_ref, *, relu):
    y = (x_ref[...].astype(jnp.float32) * s_ref[...] + t_ref[...]
         + res_ref[...].astype(jnp.float32))
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_reduce_kernel(x_ref, dy_ref, s_ref, t_ref, u_ref, w_ref,
                       dg_ref, db_ref, *, nrows, block_r, relu,
                       res_ref=None):
    """dγ = Σ dy_eff·x̂, dβ = Σ dy_eff.  x̂ = x·u + w (u=rstd, w=−μ·rstd);
    relu mask recomputed as x·s + t (+res) > 0."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    rvalid = _row_mask(x_ref.shape, i * block_r, nrows)
    # zero padded rows of x too: 0·NaN from an out-of-bounds load would
    # otherwise poison the Σ dy_eff·x̂ accumulator
    x = jnp.where(rvalid, x_ref[...].astype(jnp.float32), 0.0)
    dy = dy_ref[...].astype(jnp.float32)
    valid = rvalid
    if relu:
        pre = x * s_ref[...] + t_ref[...]
        if res_ref is not None:
            pre = pre + jnp.where(
                rvalid, res_ref[...].astype(jnp.float32), 0.0)
        valid = jnp.logical_and(valid, pre > 0.0)
    dy_eff = jnp.where(valid, dy, 0.0)
    xhat = x * u_ref[...] + w_ref[...]
    dg_ref[...] += jnp.sum(dy_eff * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy_eff, axis=0, keepdims=True)


def _bwd_dx_kernel(x_ref, dy_ref, s_ref, t_ref, a_ref, b_ref, c_ref,
                   dx_ref, *, relu, res_ref=None, dres_ref=None):
    """dx = dy_eff·A + x·B + C (all per-channel consts pre-folded);
    dres = dy_eff."""
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    if relu:
        pre = x * s_ref[...] + t_ref[...]
        if res_ref is not None:
            pre = pre + res_ref[...].astype(jnp.float32)
        dy_eff = jnp.where(pre > 0.0, dy, 0.0)
    else:
        dy_eff = dy
    dx = dy_eff * a_ref[...] + x * b_ref[...] + c_ref[...]
    dx_ref[...] = dx.astype(dx_ref.dtype)
    if dres_ref is not None:
        dres_ref[...] = dy_eff.astype(dres_ref.dtype)


# -- 2-D view plumbing ------------------------------------------------------


class _View:
    """How [.., C] maps onto the kernel's [N2, C2] lane view."""

    def __init__(self, shape, c):
        n = 1
        for d in shape[:-1]:
            n *= d
        self.c = c
        if c % 128 == 0 or c >= 128:
            self.fold = 1
        elif 128 % c == 0 and n % (128 // c) == 0:
            self.fold = 128 // c
        else:
            self.fold = 1
        self.n2 = n // self.fold
        self.c2 = c * self.fold
        self.n = n

    def to2d(self, x):
        return x.reshape(self.n2, self.c2)

    def vec(self, v):
        """Per-channel [C] f32 → [1, C2] kernel operand."""
        if self.fold > 1:
            v = jnp.tile(v, self.fold)
        return v.reshape(1, self.c2).astype(jnp.float32)

    def unvec(self, v2):
        """[1, C2] kernel reduce output → [C]."""
        v2 = v2.reshape(self.c2)
        if self.fold > 1:
            v2 = v2.reshape(self.fold, self.c).sum(axis=0)
        return v2


def _grid_specs(view, n_big, extra_vecs):
    """(grid, in_specs head [x(,dy)(,res)] + vec specs, block_r)."""
    block_r = _row_block(view.c2)
    grid = (-(-view.n2 // block_r),)
    big = pl.BlockSpec((block_r, view.c2), lambda i: (i, 0))
    vec = pl.BlockSpec((1, view.c2), lambda i: (0, 0))
    return grid, [big] * n_big + [vec] * extra_vecs, big, vec, block_r


def _run_stats(x2, view):
    grid, in_specs, _, vec, block_r = _grid_specs(view, 1, 0)
    out = pl.pallas_call(
        functools.partial(_stats_kernel, nrows=view.n2, block_r=block_r),
        grid=grid,
        in_specs=in_specs,
        out_specs=[vec, vec],
        out_shape=[jax.ShapeDtypeStruct((1, view.c2), jnp.float32)] * 2,
        interpret=interpret(),
    )(x2)
    return view.unvec(out[0]), view.unvec(out[1])


def _run_apply(x2, s2, t2, res2, relu, view, out_dtype):
    if res2 is None:
        grid, in_specs, _, _, _ = _grid_specs(view, 1, 2)
        kernel = functools.partial(_apply_kernel, relu=relu)
        args = (x2, s2, t2)
    else:
        grid, specs, big, vec, _ = _grid_specs(view, 1, 2)
        in_specs = specs + [big]
        kernel = functools.partial(_apply_res_kernel, relu=relu)
        args = (x2, s2, t2, res2)
    big_out = pl.BlockSpec(
        (_row_block(view.c2), view.c2), lambda i: (i, 0))
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=big_out,
        out_shape=jax.ShapeDtypeStruct((view.n2, view.c2), out_dtype),
        interpret=interpret(),
    )(*args)


def _run_bwd_reduce(x2, dy2, s2, t2, u2, w2, res2, relu, view):
    grid, specs, big, vec, block_r = _grid_specs(view, 2, 4)
    kernel_kw = dict(nrows=view.n2, block_r=block_r, relu=relu)
    if res2 is None:
        def kernel(x_ref, dy_ref, s_ref, t_ref, u_ref, w_ref, dg, db):
            _bwd_reduce_kernel(x_ref, dy_ref, s_ref, t_ref, u_ref,
                               w_ref, dg, db, **kernel_kw)
        args = (x2, dy2, s2, t2, u2, w2)
        in_specs = specs
    else:
        def kernel(x_ref, dy_ref, s_ref, t_ref, u_ref, w_ref, res_ref,
                   dg, db):
            _bwd_reduce_kernel(x_ref, dy_ref, s_ref, t_ref, u_ref,
                               w_ref, dg, db, res_ref=res_ref,
                               **kernel_kw)
        args = (x2, dy2, s2, t2, u2, w2, res2)
        in_specs = specs + [big]
    out = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=[vec, vec],
        out_shape=[jax.ShapeDtypeStruct((1, view.c2), jnp.float32)] * 2,
        interpret=interpret(),
    )(*args)
    return view.unvec(out[0]), view.unvec(out[1])


def _run_bwd_dx(x2, dy2, s2, t2, a2, b2, c2v, res2, relu, view, dtype):
    grid, specs, big, vec, block_r = _grid_specs(view, 2, 5)
    big_out = pl.BlockSpec((block_r, view.c2), lambda i: (i, 0))
    if res2 is None:
        def kernel(x_ref, dy_ref, s_ref, t_ref, a_ref, b_ref, c_ref,
                   dx_ref):
            _bwd_dx_kernel(x_ref, dy_ref, s_ref, t_ref, a_ref, b_ref,
                           c_ref, dx_ref, relu=relu)
        return pl.pallas_call(
            kernel, grid=grid, in_specs=specs, out_specs=big_out,
            out_shape=jax.ShapeDtypeStruct((view.n2, view.c2), dtype),
            interpret=interpret(),
        )(x2, dy2, s2, t2, a2, b2, c2v), None

    def kernel(x_ref, dy_ref, s_ref, t_ref, a_ref, b_ref, c_ref,
               res_ref, dx_ref, dres_ref):
        _bwd_dx_kernel(x_ref, dy_ref, s_ref, t_ref, a_ref, b_ref,
                       c_ref, dx_ref, relu=relu, res_ref=res_ref,
                       dres_ref=dres_ref)
    dx, dres = pl.pallas_call(
        kernel, grid=grid, in_specs=specs + [big],
        out_specs=[big_out, big_out],
        out_shape=[jax.ShapeDtypeStruct((view.n2, view.c2), dtype)] * 2,
        interpret=interpret(),
    )(x2, dy2, s2, t2, a2, b2, c2v, res2)
    return dx, dres


# -- public op --------------------------------------------------------------


def _fbn_fwd_impl(x, gamma, beta, residual, eps, relu):
    shape = x.shape
    view = _View(shape, shape[-1])
    x2 = view.to2d(x)
    res2 = None if residual is None else view.to2d(residual)
    xsum, xsq = _run_stats(x2, view)
    n = float(view.n)
    mean = xsum / n
    var = jnp.maximum(xsq / n - mean * mean, 0.0)
    rstd = lax.rsqrt(var + eps)
    g32 = gamma.astype(jnp.float32)
    s = g32 * rstd
    t = beta.astype(jnp.float32) - mean * s
    y2 = _run_apply(x2, view.vec(s), view.vec(t), res2, relu, view,
                    x.dtype)
    return y2.reshape(shape), mean, var, rstd, s, t


def _fbn_bwd_impl(x, dy, gamma, residual, mean, rstd, s, t, relu):
    shape = x.shape
    view = _View(shape, shape[-1])
    x2, dy2 = view.to2d(x), view.to2d(dy)
    res2 = None if residual is None else view.to2d(residual)
    s2, t2 = view.vec(s), view.vec(t)
    u, w = rstd, -mean * rstd
    dgamma, dbeta = _run_bwd_reduce(
        x2, dy2, s2, t2, view.vec(u), view.vec(w), res2, relu, view)
    n = float(view.n)
    g32 = gamma.astype(jnp.float32)
    a = g32 * rstd
    b = rstd * (-a * dgamma / n)          # coeff of x via x̂ = x·rstd − μ·rstd
    c = -a * dbeta / n - (-mean * rstd) * a * dgamma / n
    dx2, dres2 = _run_bwd_dx(
        x2, dy2, s2, t2, view.vec(a), view.vec(b), view.vec(c), res2,
        relu, view, x.dtype)
    dx = dx2.reshape(shape)
    dres = None if dres2 is None else dres2.reshape(shape)
    return dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype), dres


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fbn(x, gamma, beta, eps, relu):
    y, mean, var, _, _, _ = _fbn_fwd_impl(x, gamma, beta, None, eps, relu)
    return y, mean, var


def _fbn_f(x, gamma, beta, eps, relu):
    y, mean, var, rstd, s, t = _fbn_fwd_impl(x, gamma, beta, None, eps,
                                             relu)
    return (y, mean, var), (x, gamma, mean, rstd, s, t)


def _fbn_b(eps, relu, saved, cts):
    x, gamma, mean, rstd, s, t = saved
    dy = cts[0]  # dmean/dvar cotangents intentionally dropped: stats
    # feed only stop_gradient'd running-average updates (flax BN same)
    dx, dgamma, dbeta, _ = _fbn_bwd_impl(
        x, dy, gamma, None, mean, rstd, s, t, relu)
    return dx, dgamma, dbeta


_fbn.defvjp(_fbn_f, _fbn_b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fbn_res(x, gamma, beta, residual, eps, relu):
    y, mean, var, _, _, _ = _fbn_fwd_impl(x, gamma, beta, residual, eps,
                                          relu)
    return y, mean, var


def _fbn_res_f(x, gamma, beta, residual, eps, relu):
    y, mean, var, rstd, s, t = _fbn_fwd_impl(x, gamma, beta, residual,
                                             eps, relu)
    return (y, mean, var), (x, gamma, residual, mean, rstd, s, t)


def _fbn_res_b(eps, relu, saved, cts):
    x, gamma, residual, mean, rstd, s, t = saved
    dy = cts[0]
    dx, dgamma, dbeta, dres = _fbn_bwd_impl(
        x, dy, gamma, residual, mean, rstd, s, t, relu)
    return dx, dgamma, dbeta, dres


_fbn_res.defvjp(_fbn_res_f, _fbn_res_b)


def fused_batch_norm(
    x: jax.Array,
    gamma: jax.Array,
    beta: jax.Array,
    *,
    eps: float = 1e-5,
    activation: Optional[str] = None,
    residual: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Training-mode BatchNorm over the last axis with optional fused
    relu and residual add:  ``y = act(x̂·γ + β [+ residual])``.

    Returns ``(y, batch_mean, batch_var)`` — variance is biased (N
    denominator), matching ``flax.linen.BatchNorm``. Gradients flow to
    ``x``, ``gamma``, ``beta`` and ``residual``; the returned statistics
    are for running-average updates and are treated as stop_gradient'd.
    """
    if activation not in (None, "relu"):
        raise ValueError(f"unsupported activation {activation!r}")
    relu = activation == "relu"
    if residual is None:
        return _fbn(x, gamma, beta, float(eps), relu)
    if residual.shape != x.shape:
        raise ValueError(
            f"residual shape {residual.shape} != x shape {x.shape}")
    return _fbn_res(x, gamma, beta, residual, float(eps), relu)


class FusedBatchNorm(nn.Module):
    """Drop-in ``flax.linen.BatchNorm`` replacement backed by the pallas
    kernels, with optional fused relu/residual epilogue.

    Training mode runs the fused stats→apply kernels; eval mode
    (``use_running_average=True``) is a plain per-channel affine (XLA
    fuses it fine — no kernel needed). Running statistics live in the
    ``batch_stats`` collection with flax's update rule."""

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: object = None
    param_dtype: object = jnp.float32
    scale_init: object = None
    activation: Optional[str] = None

    @nn.compact
    def __call__(self, x, residual=None, use_running_average=None):
        use_ra = (self.use_running_average
                  if use_running_average is None else use_running_average)
        c = x.shape[-1]
        scale_init = self.scale_init or nn.initializers.ones
        gamma = self.param("scale", scale_init, (c,), self.param_dtype)
        beta = self.param("bias", nn.initializers.zeros, (c,),
                          self.param_dtype)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, jnp.float32), (c,))
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, jnp.float32), (c,))
        if use_ra:
            rstd = lax.rsqrt(ra_var.value + self.epsilon)
            s = (gamma.astype(jnp.float32) * rstd)
            t = beta.astype(jnp.float32) - ra_mean.value * s
            y = x.astype(jnp.float32) * s + t
            if residual is not None:
                y = y + residual.astype(jnp.float32)
            if self.activation == "relu":
                y = jnp.maximum(y, 0.0)
            return y.astype(self.dtype or x.dtype)
        y, mean, var = fused_batch_norm(
            x, gamma, beta, eps=self.epsilon, activation=self.activation,
            residual=residual)
        if not self.is_initializing():
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1 - m) * lax.stop_gradient(
                mean)
            ra_var.value = m * ra_var.value + (1 - m) * lax.stop_gradient(
                var)
        return y
