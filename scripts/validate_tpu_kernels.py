#!/usr/bin/env python
"""On-device numerics validation for the pallas kernel family.

The test suite exercises these kernels in interpret mode on the CPU
mesh (tests/test_pallas_*.py) — the same code path, but not the Mosaic
compiler. This script re-runs the numerics oracles ON A REAL TPU so
Mosaic-specific issues (tiling, masked loads/stores, accumulation
order) can't hide. Run it on any TPU-attached environment:

    python scripts/validate_tpu_kernels.py

Exits non-zero on any mismatch, on any kernel Mosaic refuses to compile
(the family's verdict carries the compiler's message and the other
families still run) and when there is no TPU. Prints one PASS/FAIL line
per check and — with ``--json PATH`` (and always as stdout's last line)
— a machine-readable verdict ``{"backend", "device_kind",
"device_count", "ok", "checks": [{"name", "ok", "max_rel_err" |
"error"}, ...]}`` so CI can gate on it like the other check scripts.
ops/pallas_batchnorm.py is not validated here: a measured dead end
(243 ms/step against 98.5) that ROADMAP C8 deletes.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

RESULTS = []


def _check(name, got, want, atol, rtol=1e-3):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.max(np.abs(got - want) / (np.abs(want) + atol))
    ok = np.allclose(got, want, atol=atol, rtol=rtol)
    print(f"{'PASS' if ok else 'FAIL'} {name}: max rel err {err:.2e}",
          flush=True)
    RESULTS.append({"name": name, "ok": bool(ok),
                    "max_rel_err": float(err)})


def _family(name, fn):
    """Run one kernel family's checks. A crash — Mosaic refusing the
    kernel, typically — is that family's verdict, recorded with the
    compiler's own message; the remaining families still run."""
    try:
        fn()
    except Exception as e:  # noqa: BLE001 - reported, and fails the run
        msg = f"{type(e).__name__}: {e}"
        print(f"FAIL {name}: {msg[:1500]}", flush=True)
        RESULTS.append({"name": name, "ok": False, "error": msg[:4000]})


def _emit(json_path):
    dev = jax.devices()[0]
    ok = bool(RESULTS) and all(r["ok"] for r in RESULTS)
    print("ALL PASS" if ok else "FAILURES PRESENT", flush=True)
    verdict = {"backend": dev.platform, "device_kind": dev.device_kind,
               "device_count": jax.device_count(), "ok": ok,
               "checks": RESULTS}
    blob = json.dumps(verdict, sort_keys=True)
    if json_path:
        with open(json_path, "w") as f:
            f.write(blob + "\n")
    print(blob, flush=True)
    return ok


def _flash_checks(rng):
    """flash attention fwd+bwd vs jnp oracle (bf16 inputs, f32 oracle)"""
    from horovod_tpu.ops.pallas_attention import (
        _reference_attention, flash_attention)

    B, H, T, D = 2, 4, 512, 64
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)
    for causal in (False, True):
        def f(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=causal).astype(
                    jnp.float32) ** 2)

        def ref(q, k, v):
            qq, kk, vv = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
            o = _reference_attention(qq, kk, vv, causal, 1.0 / D ** 0.5,
                                     0, 0)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        o1 = jax.jit(f)(q, k, v)
        o0 = jax.jit(ref)(q, k, v)
        _check(f"flash fwd causal={causal}", o1, o0, atol=2.0, rtol=2e-2)
        g1 = jax.jit(jax.grad(f))(q, k, v)
        g0 = jax.jit(jax.grad(ref))(q, k, v)
        _check(f"flash dq causal={causal}",
               jnp.sum(jnp.abs(g1.astype(jnp.float32))),
               jnp.sum(jnp.abs(g0.astype(jnp.float32))),
               atol=1.0, rtol=2e-2)


def _layernorm_checks(rng):
    """fused LayerNorm / RMSNorm vs jnp oracle, f32"""
    from horovod_tpu.ops.pallas_layernorm import fused_layer_norm

    x2 = jnp.asarray(rng.randn(24 * 512, 1024), jnp.float32)
    g2 = jnp.asarray(rng.rand(1024) + 0.5, jnp.float32)
    b2 = jnp.asarray(rng.randn(1024), jnp.float32)

    def ln_ref(x, g, b):
        m = x.mean(-1, keepdims=True)
        vv = ((x - m) ** 2).mean(-1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(vv + 1e-5) * g + b

    y1 = jax.jit(lambda x, g, b: fused_layer_norm(x, g, b))(x2, g2, b2)
    y0 = jax.jit(ln_ref)(x2, g2, b2)
    _check("fused_ln fwd", y1, y0, atol=1e-4)
    gl1 = jax.jit(jax.grad(
        lambda *a: jnp.sum(fused_layer_norm(*a) ** 2),
        argnums=(0, 1, 2)))(x2, g2, b2)
    gl0 = jax.jit(jax.grad(lambda *a: jnp.sum(ln_ref(*a) ** 2),
                           argnums=(0, 1, 2)))(x2, g2, b2)
    for i, nm in enumerate(("dx", "dgamma", "dbeta")):
        _check(f"fused_ln {nm}", gl1[i], gl0[i], atol=1e-3, rtol=5e-3)

    def rms_ref(x, g):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                                 + 1e-5) * g

    y1 = jax.jit(lambda x, g: fused_layer_norm(
        x, g, kind="rmsnorm"))(x2, g2)
    y0 = jax.jit(rms_ref)(x2, g2)
    _check("fused_rms fwd", y1, y0, atol=1e-4)


def _cross_entropy_checks(rng):
    """fused vocab-blocked cross-entropy vs dense oracle"""
    from horovod_tpu.ops.fused_cross_entropy import (
        fused_linear_cross_entropy)

    N, Dh, V = 512, 256, 4099  # odd vocab exercises block masking
    h = jnp.asarray(rng.randn(N, Dh) * 0.2, jnp.float32)
    w = jnp.asarray(rng.randn(Dh, V) * 0.2, jnp.float32)
    labels = jnp.asarray(rng.randint(0, V, N))

    def ce_ref(h, w):
        logits = h @ w
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, labels[:, None], axis=-1))

    l1 = jax.jit(lambda h, w: fused_linear_cross_entropy(
        h, w, labels)[0])(h, w)
    l0 = jax.jit(ce_ref)(h, w)
    _check("fused_ce loss", l1, l0, atol=1e-4)


# The ops/pallas_collectives kernel family vs its XLA oracles, one
# family per kernel so one refusal does not hide the others. The
# contract is bitwise (atol is only allclose's denominator guard).
_BLOCK, _ROWS = 256, 4


def _quantize_checks(rng):
    from horovod_tpu.optim import compression as comp
    from horovod_tpu.ops import pallas_collectives as pc

    rows = jnp.asarray(rng.randn(_ROWS, 4 * _BLOCK).astype(np.float32))
    q0, s0 = jax.jit(
        lambda r: comp.quantize_blocks(r.reshape(-1), _BLOCK))(rows)

    def quantize():
        q1, s1 = jax.jit(lambda r: pc._quantize_rows(r, _BLOCK))(rows)
        _check("fused quantize codes", q1.reshape(-1), q0, atol=1e-6,
               rtol=0)
        _check("fused quantize scales", s1.reshape(-1), s0, atol=1e-6,
               rtol=0)

    def quantize_ef():
        _, _, e1 = jax.jit(
            lambda r: pc._quantize_ef_rows(r, _BLOCK))(rows)
        e0 = rows - comp.dequantize_blocks(
            q0, s0, _BLOCK).reshape(rows.shape)
        _check("fused quantize EF residual", e1, e0, atol=1e-6, rtol=0)

    def accumulate():
        acc1 = jax.jit(lambda q, s: pc._accum_rows(q, s, _BLOCK))(
            q0.reshape(_ROWS, -1), s0.reshape(_ROWS, -1))
        acc0 = comp.dequantize_blocks(q0, s0, _BLOCK).reshape(
            _ROWS, -1).sum(axis=0)
        _check("fused dequant-accumulate", acc1, acc0, atol=1e-6, rtol=0)

    _family("fused quantize", quantize)
    _family("fused quantize EF", quantize_ef)
    _family("fused dequant-accumulate", accumulate)


def _pack_checks(rng):
    from horovod_tpu.ops import pallas_collectives as pc
    from horovod_tpu.optim import zero as zero_mod

    def pack():
        bucket = jnp.asarray(rng.randn(1000).astype(np.float32))
        p1 = jax.jit(lambda b: pc.pack_rows_fused(b, _ROWS))(bucket)
        _check("fused pack epilogue", p1,
               zero_mod._pad_rows(bucket, _ROWS), atol=1e-6, rtol=0)

    def matmul_pack():
        a = jnp.asarray(rng.randn(64, 48).astype(np.float32))
        bm = jnp.asarray(rng.randn(48, 32).astype(np.float32))
        m1 = jax.jit(lambda a, b: pc._matmul_pack(a, b, _ROWS))(a, bm)
        m0 = zero_mod._pad_rows(
            jnp.dot(a, bm,
                    preferred_element_type=jnp.float32).reshape(-1),
            _ROWS)
        _check("fused matmul epilogue", m1, m0, atol=1e-5)

    _family("fused pack epilogue", pack)
    _family("fused matmul epilogue", matmul_pack)


def _with_fused(flag, fn):
    """fn() with HOROVOD_FUSED_COLLECTIVES pinned (the kernels' opt-in
    switch is read at trace time), restored afterwards."""
    old = os.environ.get("HOROVOD_FUSED_COLLECTIVES")
    os.environ["HOROVOD_FUSED_COLLECTIVES"] = "1" if flag else "0"
    try:
        return fn()
    finally:
        if old is None:
            os.environ.pop("HOROVOD_FUSED_COLLECTIVES", None)
        else:
            os.environ["HOROVOD_FUSED_COLLECTIVES"] = old


def _decode_checks(rng):
    from horovod_tpu.serving.decode import KVCacheSpec, SlottedKVCache

    for dt in ("fp32", "int8"):
        def attend(dt=dt):
            spec = KVCacheSpec(slots=2, layers=1, kv_heads=2,
                               max_len=128, head_dim=128, dtype=dt,
                               compute_dtype=jnp.float32)
            cf = SlottedKVCache(spec, spec.allocate())
            cu = SlottedKVCache(spec, spec.allocate())
            qd = jnp.asarray(rng.randn(2, 1, 4, 128).astype(np.float32))
            kn = jnp.asarray(rng.randn(2, 1, 2, 128).astype(np.float32))
            vn = jnp.asarray(rng.randn(2, 1, 2, 128).astype(np.float32))
            pos = jnp.zeros((2, 1), jnp.int32)
            of = _with_fused(
                True, lambda: cf.append_attend(0, qd, kn, vn, pos))
            ou = _with_fused(
                False, lambda: cu.append_attend(0, qd, kn, vn, pos))
            _check(f"fused decode append+attend ({dt})", of, ou,
                   atol=1e-6, rtol=0)

        _family(f"fused decode append+attend ({dt})", attend)


def _psum_checks(rng):
    """quantized_psum end to end under shard_map — needs > 1 device."""
    devs = jax.devices()
    if len(devs) == 1:
        print("SKIP fused collective end-to-end: single device",
              flush=True)
        return
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.optim import compression as comp

    w = len(devs)
    mesh = Mesh(np.array(devs), ("d",))
    x = jnp.asarray(rng.randn(w, 1000).astype(np.float32))

    def psum(fused):
        f = shard_map(
            lambda v: comp.quantized_psum(v[0], "d", w, _BLOCK)[None],
            mesh=mesh, in_specs=(P("d"),), out_specs=P("d"),
            check_vma=False)
        return _with_fused(fused, lambda: jax.jit(f)(x))

    _family("fused quantized_psum (end-to-end)", lambda: _check(
        "fused quantized_psum (end-to-end)", psum(True), psum(False),
        atol=1e-6, rtol=0))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="",
                    help="also write the JSON verdict to this path")
    args = ap.parse_args()
    rng = np.random.RandomState(0)
    if jax.default_backend() != "tpu":
        # interpret mode is the suite's job; without Mosaic there is
        # nothing this script can validate, and that is a failure
        print(f"FAIL no TPU attached (backend "
              f"{jax.default_backend()!r}): the kernels would run "
              f"interpreted", flush=True)
        _emit(args.json)
        return 1

    _family("flash attention", lambda: _flash_checks(rng))
    _family("fused layernorm", lambda: _layernorm_checks(rng))
    _family("fused cross-entropy", lambda: _cross_entropy_checks(rng))
    _quantize_checks(rng)
    _pack_checks(rng)
    _decode_checks(rng)
    _psum_checks(rng)

    return 0 if _emit(args.json) else 1


if __name__ == "__main__":
    sys.exit(main())
