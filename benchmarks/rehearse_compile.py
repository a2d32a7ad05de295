#!/usr/bin/env python3
"""Compile each cell's real train step for a described ``v5e:2x2``
without a chip, and print what the compiler says it needs.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile.py [cell ...]

Run by hand before chip time is spent: a batch that does not fit, or a
kernel Mosaic refuses, shows here. Nothing runs, so this says nothing
about results or times, and a compile that passes is not a chip run.
The program asks ``jax.default_backend()`` whether to interpret its
Pallas kernels and would see the CPU here; this script, not the
program, steers that (``ops/_pallas.interpret`` is replaced for the
length of the compile).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmarks import harness, hlo  # noqa: E402


@contextlib.contextmanager
def mosaic_kernels():
    """Inside, the program's Pallas kernels compile with Mosaic where
    the CPU is the default backend: the rule that says whether to
    interpret them is replaced, with every copy of it that a kernel
    module imported by name."""
    from horovod_tpu.ops import _pallas

    real_rule = _pallas.interpret
    holders = [m for m in list(sys.modules.values())
               if getattr(m, "__name__", "").startswith("horovod_tpu")
               and getattr(m, "interpret", None) is real_rule]
    for m in holders:
        m.interpret = lambda: False
    try:
        yield
    finally:
        for m in holders:
            m.interpret = real_rule


def compile_cell(name: str, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    found = harness.load_cell(name)
    cell, traffic = found["cell"], found["traffic"]
    sizes = found["config"]["model"]
    job = harness.load_job(traffic["job"])
    run = harness.Run(
        started=time.perf_counter(), workload=name, chips=cell["chips"],
        config=found["config"], traffic=traffic, model_sizes=sizes,
        seed=0, seconds=0, trace=False, rehearse=True)
    n = cell["chips"]
    mesh = Mesh(np.array(topo.devices[:n]), ("hvd",))
    hvd.shutdown()
    built = job.build(run, sizes, traffic, mesh=mesh)

    rep = NamedSharding(mesh, P())
    split = NamedSharding(mesh, P("hvd"))
    seq = traffic["seq_len"]

    def described(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    params = jax.eval_shape(
        built["plain_model"].init, jax.random.PRNGKey(0),
        jnp.zeros((1, seq), jnp.int32))["params"]
    opt_state = jax.eval_shape(built["opt"].init, params)
    batch = tuple(jax.ShapeDtypeStruct(
        (n * traffic["batch_per_chip"], seq), a.dtype, sharding=split)
        for a in job.make_batch(sizes, traffic, 1, 0))

    with mosaic_kernels():
        t0 = time.perf_counter()
        lowered = built["step"].lower(
            described(params, rep), described(opt_state, rep), *batch)
        t1 = time.perf_counter()
        compiled = job.compile_step(lowered, for_tpu=True)
        t2 = time.perf_counter()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    reduces = hlo.allreduces(text)
    step_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  - mem.alias_size_in_bytes + mem.temp_size_in_bytes
                  + mem.generated_code_size_in_bytes)
    return {
        "cell": name, "compiled_for": f"{n} x {topo.devices[0].device_kind}"
        " (described, not attached: not a chip run)",
        "lower_s_here": round(t1 - t0, 1), "compile_s_here": round(t2 - t1, 1),
        "argument_gib": mem.argument_size_in_bytes / harness.GIB,
        "temp_gib": mem.temp_size_in_bytes / harness.GIB,
        "step_hbm_gib": step_bytes / harness.GIB,
        "allreduce_ops": len(reduces),
        "allreduce_mib": sum(reduces) / harness.MIB,
        "mosaic_calls": len(hlo.mosaic_call_names(text)),
    }


def main(argv):
    from jax.experimental import topologies

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    names = argv or [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    for name in names:
        print(json.dumps(compile_cell(name, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
