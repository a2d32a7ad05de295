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
The family is what the benchmark's default step runs: flash attention,
at the three shapes the cells give it, and the fused cross entropy.
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

    # (batch, length, causal) of gpt2m_*, bertl_s512 and bertl_s128
    # (BENCHMARK.json), 16 heads of width 64
    H, D = 16, 64
    for B, T, causal in ((16, 1024, True), (26, 512, False),
                         (104, 128, False)):
        q = jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)

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
        _check(f"flash fwd T={T} causal={causal}", o1, o0, atol=2.0,
               rtol=2e-2)
        g1 = jax.jit(jax.grad(f))(q, k, v)
        g0 = jax.jit(jax.grad(ref))(q, k, v)
        _check(f"flash dq T={T} causal={causal}",
               jnp.sum(jnp.abs(g1.astype(jnp.float32))),
               jnp.sum(jnp.abs(g0.astype(jnp.float32))),
               atol=1.0, rtol=2e-2)


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
    _family("fused cross-entropy", lambda: _cross_entropy_checks(rng))

    return 0 if _emit(args.json) else 1


if __name__ == "__main__":
    sys.exit(main())
