"""End-to-end native eager pipeline: N real processes, the public hvd
API, the C++ negotiation control plane, and the XLA executor data plane.

This is the integration the reference calls its defining property: a
user's per-op eager calls flow through negotiation into the data plane
(/root/reference/horovod/common/operations.cc:273 PerformOperation, :1400
EnqueueTensorAllreduces). Workers submit tensors in DIFFERENT orders with
DISTINCT per-rank values; numeric results must still be correct — the
consistency only the controller can provide.

World mechanics: each worker is one JAX process with one CPU device,
joined through jax.distributed (gloo CPU collectives), exactly how the
launcher wires TPU pod hosts (SURVEY.md §2.6).
"""

import json
import os
import socket
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(_REPO, "tests", "native_eager_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _worker_env(rank: int, size: int, jax_port: int, native_port: int):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # no virtual 8-device split in workers
    # what runner/exec_run.py slot_env publishes
    env["HVD_TPU_NATIVE"] = "1"
    env["HVD_TPU_COORDINATOR_ADDRESS"] = f"127.0.0.1:{jax_port}"
    env["HVD_TPU_NUM_PROCESSES"] = str(size)
    env["HVD_TPU_PROCESS_ID"] = str(rank)
    env["HVD_TPU_NATIVE_COORDINATOR_ADDR"] = "127.0.0.1"
    env["HVD_TPU_NATIVE_COORDINATOR_PORT"] = str(native_port)
    return env


def _run_world(size: int, timeout_s: float = 240.0):
    jax_port, native_port = _free_port(), _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER],
            env=_worker_env(r, size, jax_port, native_port),
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=_REPO,
        )
        for r in range(size)
    ]
    outputs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout_s)
            outputs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    results = {}
    for r, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        line = next(
            (ln for ln in out.splitlines() if ln.startswith("RESULT ")), None
        )
        assert line is not None, f"rank {r} printed no RESULT:\n{out}"
        results[r] = json.loads(line[len("RESULT "):])
    return results


# world-3 (28s of subprocess spawns — the process-set scenarios need
# size >= 3) rides the slow tier so tier-1 stays inside its 870s
# budget (PR-1/PR-5 precedent: the largest test moves, coverage
# stays); world-2 keeps every other scenario in tier-1, and the
# subset logic world-3 adds is unit-covered by test_process_sets /
# test_native_runtime
@pytest.mark.parametrize(
    "size", [2, pytest.param(3, marks=pytest.mark.slow)])
def test_native_eager_end_to_end(size):
    out = _run_world(size)
    for r in range(size):
        for key in (
            "allreduce_ok", "average_ok", "allgather_ok", "broadcast_ok",
            "reducescatter_ok", "alltoall_ok", "grouped_ok",
            "grouped_sync_ok",
            "grouped_allgather_ok", "grouped_reducescatter_ok",
            "sparse_ok", "fast_path_ok", "dist_opt_ok",
            "compression_wire_ok", "process_set_ok", "join_ok",
        ):
            assert out[r][key], f"rank {r}: {key} failed: {out[r]}"
        # the steady-state layer saw real traffic
        assert out[r]["bytes_negotiated"] > 0
