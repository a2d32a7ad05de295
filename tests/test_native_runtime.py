"""Native control-plane runtime tests: real multi-process negotiation over
localhost TCP (reference tier-2 pattern, SURVEY.md §4: op sweeps under a
multi-rank world; here the world is N spawned processes, no jax needed).

The module avoids importing jax/horovod_tpu at top level so spawned
workers stay light; the native package is loaded by file path.
"""

import importlib.util
import multiprocessing as mp
import os
import socket
import time

import pytest

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "horovod_tpu", "_native",
)


def _load_native():
    spec = importlib.util.spec_from_file_location(
        "hvd_native_standalone", os.path.join(_NATIVE_DIR, "__init__.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _drain_until(rt, handles, timeout_s=30.0, execute=True):
    """Fetch batches until all handles are terminal; returns batch log."""
    log = []
    deadline = time.time() + timeout_s
    pending = set(handles)
    while pending and time.time() < deadline:
        batch = rt.next_batch(timeout_s=0.2)
        if batch is not None:
            log.append((batch.op, tuple(batch.names)))
            if execute:
                rt.batch_done(batch, ok=True)
        done = {
            h for h in pending
            if rt.poll(h) in (rt_mod_DONE, rt_mod_FAILED)
        }
        pending -= done
    return log


# poll state constants mirrored here to keep the worker picklable
rt_mod_DONE = 2
rt_mod_FAILED = -1


def _worker(rank, size, port, scenario, q, cycle_ms):
    native = _load_native()
    rt = native.NativeRuntime()
    rt.init(
        rank, size, "127.0.0.1", port,
        cycle_ms=cycle_ms,
        cache_capacity=64,
        stall_warning_s=60.0,
    )
    try:
        result = scenario(native, rt, rank, size)
        q.put((rank, "ok", result))
    except Exception as e:  # surfaced to the asserting parent
        q.put((rank, "err", repr(e)))
    finally:
        rt.shutdown()


def _run_world(size, scenario, timeout_s=60.0, cycle_ms=1.0):
    port = _free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_worker,
                    args=(r, size, port, scenario, q, cycle_ms))
        for r in range(size)
    ]
    for p in procs:
        p.start()
    results = {}
    deadline = time.time() + timeout_s
    while len(results) < size and time.time() < deadline:
        try:
            rank, status, payload = q.get(timeout=1.0)
            results[rank] = (status, payload)
        except Exception:
            pass
    for p in procs:
        p.join(timeout=5)
        if p.is_alive():
            p.terminate()
    assert len(results) == size, f"only {len(results)}/{size} reported"
    for rank, (status, payload) in results.items():
        assert status == "ok", f"rank {rank} failed: {payload}"
    return {r: payload for r, (_, payload) in results.items()}


# ---------------------------------------------------------- scenarios
# (top-level functions: spawn requires picklable targets)


def scenario_out_of_order(native, rt, rank, size):
    names = ["grad_a", "grad_b", "grad_c", "grad_d"]
    order = names if rank == 0 else list(reversed(names))
    handles = [
        rt.enqueue(n, native.OP_ALLREDUCE, "float32", [4, 4])
        for n in order
    ]
    log = _drain_until(rt, handles)
    states = [rt.poll(h) for h in handles]
    return {"log": log, "states": states}


def test_negotiation_orders_ranks_identically():
    """Ranks submit in opposite orders; the executed batch sequence must be
    identical (the controller's whole purpose, controller.h:74-111)."""
    out = _run_world(2, scenario_out_of_order)
    assert out[0]["log"] == out[1]["log"]
    all_names = [n for _, names in out[0]["log"] for n in names]
    assert sorted(all_names) == ["grad_a", "grad_b", "grad_c", "grad_d"]
    assert all(s == rt_mod_DONE for s in out[0]["states"])
    assert all(s == rt_mod_DONE for s in out[1]["states"])


def scenario_fusion(native, rt, rank, size):
    # second tensor has a different dtype: must not fuse with the others
    h1 = rt.enqueue("w1", native.OP_ALLREDUCE, "float32", [16])
    h2 = rt.enqueue("w2", native.OP_ALLREDUCE, "float64", [16])
    h3 = rt.enqueue("w3", native.OP_ALLREDUCE, "float32", [16])
    log = _drain_until(rt, [h1, h2, h3])
    return log


def test_fusion_groups_same_dtype_only():
    # A cycle that ends between two enqueues negotiates the first alone,
    # which says nothing of fusion (on a busy host one world in three at
    # a cycle of 1 ms, one in twelve at 100): such a world is run again.
    # No world may mix the dtypes, and one has to fuse w1 with w3.
    for _ in range(5):
        out = _run_world(2, scenario_fusion, cycle_ms=100.0)
        assert out[0] == out[1]
        groups = [set(names) for _, names in out[0]]
        assert all(g == {"w2"} or g <= {"w1", "w3"} for g in groups)
        if {"w1", "w3"} in groups:
            break
    else:
        pytest.fail(f"w1 and w3 never fused: {groups}")


def scenario_mismatch(native, rt, rank, size):
    shape = [4] if rank == 0 else [8]
    h = rt.enqueue("bad", native.OP_ALLREDUCE, "float32", shape)
    state = rt.wait(h, timeout_s=20.0)
    # execution-side must also see the error batch (or nothing at all)
    return {"state": state, "err": rt.last_error()}


def test_shape_mismatch_fails_on_all_ranks():
    """Mismatched shapes must raise consistently on every rank, not
    deadlock (reference negotiation error channel, controller.cc:497)."""
    out = _run_world(2, scenario_mismatch)
    for r in range(2):
        assert out[r]["state"] == rt_mod_FAILED


def scenario_cache(native, rt, rank, size):
    logs = []
    for step in range(3):
        hs = [
            rt.enqueue(f"g{i}", native.OP_ALLREDUCE, "float32", [8])
            for i in range(3)
        ]
        logs.append(_drain_until(rt, hs))
    return {"logs": logs, "cache_hits": rt.cache_hits()}


def test_response_cache_steady_state():
    """Repeat steps hit the response cache; batches stay identical
    (reference response_cache.h:45 fast path)."""
    out = _run_world(2, scenario_cache)
    for r in range(2):
        # steps 2 and 3 ran from cache: ≥6 hits (3 tensors × 2 steps)
        assert out[r]["cache_hits"] >= 6, out[r]
        all_step_names = [
            sorted(n for _, names in log for n in names)
            for log in out[r]["logs"]
        ]
        assert all_step_names[0] == all_step_names[1] == all_step_names[2]
    assert out[0]["logs"][1] == out[1]["logs"][1]


def scenario_ragged_allgather(native, rt, rank, size):
    """Ranks submit different dim-0 extents; the controller must collect
    per-rank sizes into the response (reference controller.cc:497)."""
    d0 = 3 + rank  # rank 0: 3 rows, rank 1: 4 rows
    h = rt.enqueue("rag", native.OP_ALLGATHER, "float32", [d0, 2])
    dims = []
    deadline = time.time() + 20
    while rt.poll(h) not in (rt_mod_DONE, rt_mod_FAILED):
        b = rt.next_batch(timeout_s=0.2)
        if b is not None:
            dims = b.rank_dim0
            rt.batch_done(b, ok=True)
        if time.time() > deadline:
            break
    return {"state": rt.poll(h), "rank_dim0": dims}


def test_ragged_allgather_negotiates_sizes():
    out = _run_world(2, scenario_ragged_allgather)
    for r in range(2):
        assert out[r]["state"] == rt_mod_DONE, out[r]
        assert out[r]["rank_dim0"] == [3, 4], out[r]


def scenario_uneven_alltoall(native, rt, rank, size):
    """Each rank's splits row reaches every rank as the full matrix."""
    splits = [1, 3] if rank == 0 else [2, 2]
    h = rt.enqueue("a2a", native.OP_ALLTOALL, "float32", [4, 2],
                   splits=splits)
    matrix = []
    deadline = time.time() + 20
    while rt.poll(h) not in (rt_mod_DONE, rt_mod_FAILED):
        b = rt.next_batch(timeout_s=0.2)
        if b is not None:
            matrix = b.all_splits
            rt.batch_done(b, ok=True)
        if time.time() > deadline:
            break
    return {"state": rt.poll(h), "all_splits": matrix}


def test_uneven_alltoall_negotiates_matrix():
    out = _run_world(2, scenario_uneven_alltoall)
    for r in range(2):
        assert out[r]["state"] == rt_mod_DONE, out[r]
        assert out[r]["all_splits"] == [1, 3, 2, 2], out[r]


def scenario_join(native, rt, rank, size):
    log = []
    if rank == 1:
        h = rt.enqueue("tail_grad", native.OP_ALLREDUCE, "float32", [4])
        log = _drain_until(rt, [h])
    jh = rt.join()
    deadline = time.time() + 20
    while rt.poll(jh) not in (rt_mod_DONE, rt_mod_FAILED):
        b = rt.next_batch(timeout_s=0.2)
        if b is not None:
            log.append((b.op, tuple(b.names)))
            rt.batch_done(b, ok=True)
        if time.time() > deadline:
            break
    return {"log": log, "join_state": rt.poll(jh)}


def scenario_cache_heterogeneous(native, rt, rank, size):
    """Heterogeneous shapes fuse into one response; cached per-tensor
    metadata must still be each tensor's own shape, so later rounds HIT
    instead of churning through invalidate/renegotiate (ADVICE r1 #1)."""
    shapes = {"h0": [4], "h1": [8], "h2": [2, 3]}
    for step in range(4):
        hs = [
            rt.enqueue(n, native.OP_ALLREDUCE, "float32", shp)
            for n, shp in shapes.items()
        ]
        _drain_until(rt, hs)
    return {"cache_hits": rt.cache_hits()}


def test_fused_heterogeneous_shapes_cache_correctly():
    out = _run_world(2, scenario_cache_heterogeneous)
    for r in range(2):
        # rounds 2-4 should be steady-state hits: ≥ 3 tensors × 2 rounds
        assert out[r]["cache_hits"] >= 6, out[r]


def scenario_coordinated_invalidation(native, rt, rank, size):
    """Shape change after caching: every rank must erase the entry in the
    same cycle and renegotiate (reference CacheCoordinator semantics)."""
    states = []
    for shape in ([4], [4], [6], [6]):  # cache, hit, invalidate, re-hit
        h = rt.enqueue("mut", native.OP_ALLREDUCE, "float32", shape)
        _drain_until(rt, [h])
        states.append(rt.poll(h))
    return {"states": states, "cache_hits": rt.cache_hits()}


def test_shape_change_invalidates_and_renegotiates():
    out = _run_world(2, scenario_coordinated_invalidation)
    for r in range(2):
        assert all(s == rt_mod_DONE for s in out[r]["states"]), out[r]
        assert out[r]["cache_hits"] >= 2, out[r]  # rounds 2 and 4 hit


def scenario_partial_hit_mismatch(native, rt, rank, size):
    """Rank 0 re-submits with the cached metadata (hit), rank 1 changes
    the shape (invalid). Previously rank 0's parked hit deadlocked; now
    the coordinated erase kicks both into negotiation, which surfaces a
    consistent shape-mismatch error — and the world stays usable."""
    h = rt.enqueue("p", native.OP_ALLREDUCE, "float32", [8])
    _drain_until(rt, [h])
    shape = [8] if rank == 0 else [5]
    h2 = rt.enqueue("p", native.OP_ALLREDUCE, "float32", shape)
    state2 = rt.wait(h2, timeout_s=20.0)
    while state2 == 1:  # BATCHED: drain the error batch if one appears
        b = rt.next_batch(timeout_s=0.2)
        if b is not None:
            rt.batch_done(b, ok=True)
        state2 = rt.wait(h2, timeout_s=5.0)
    h3 = rt.enqueue("q", native.OP_ALLREDUCE, "float32", [3])
    _drain_until(rt, [h3])
    return {"mismatch_state": state2, "after_state": rt.poll(h3)}


def test_partial_cache_hit_does_not_deadlock():
    out = _run_world(2, scenario_partial_hit_mismatch)
    for r in range(2):
        assert out[r]["mismatch_state"] == rt_mod_FAILED, out[r]
        assert out[r]["after_state"] == rt_mod_DONE, out[r]


def test_join_covers_missing_ranks():
    """Rank 1 has one extra batch; rank 0 joins — the tensor completes with
    rank 0 counted as a zero contributor, then join completes everywhere
    (reference JoinOp, collective_operations.h:325)."""
    out = _run_world(2, scenario_join)
    assert out[0]["join_state"] == rt_mod_DONE
    assert out[1]["join_state"] == rt_mod_DONE
    # rank 1 executed its tensor; rank 0 received the same batch (it must
    # contribute zeros for a tensor it never submitted)
    r1_names = [n for _, names in out[1]["log"] for n in names]
    assert "tail_grad" in r1_names
    r0_names = [n for _, names in out[0]["log"] for n in names]
    assert "tail_grad" in r0_names


def scenario_barrier(native, rt, rank, size):
    if rank == 1:
        time.sleep(0.3)  # stagger arrival
    h = rt.barrier()
    state = rt.wait(h, timeout_s=20.0)
    # drain the barrier batch
    b = rt.next_batch(timeout_s=1.0)
    if b is not None:
        rt.batch_done(b, ok=True)
    return state


def test_barrier_completes_on_all():
    out = _run_world(2, scenario_barrier)
    assert all(v in (1, 2) for v in out.values())


def scenario_world3(native, rt, rank, size):
    hs = [
        rt.enqueue(f"p{i}", native.OP_ALLREDUCE, "float32", [32])
        for i in range(5)
    ]
    log = _drain_until(rt, hs)
    return log


def test_three_rank_world():
    out = _run_world(3, scenario_world3)
    assert out[0] == out[1] == out[2]
    names = sorted(n for _, ns in out[0] for n in ns)
    assert names == ["p0", "p1", "p2", "p3", "p4"]


# ---------------------------------------------------------- groups


def scenario_grouped_complete(native, rt, rank, size):
    """All ranks submit the full group (in different orders): every member
    completes, released in the same negotiation cycle."""
    names = ["gm0", "gm1", "gm2"]
    order = names if rank == 0 else list(reversed(names))
    hs = [
        rt.enqueue(n, native.OP_ALLREDUCE, "float32", [8],
                   group="grp-a", group_size=3)
        for n in order
    ]
    log = _drain_until(rt, hs)
    return {"log": log, "states": [rt.poll(h) for h in hs]}


def test_grouped_members_complete_together():
    out = _run_world(2, scenario_grouped_complete)
    assert out[0]["log"] == out[1]["log"]
    all_names = sorted(n for _, names in out[0]["log"] for n in names)
    assert all_names == ["gm0", "gm1", "gm2"]
    assert all(s == rt_mod_DONE for s in out[0]["states"])
    # same dtype/op → the whole group fuses into ONE batch
    assert len(out[0]["log"]) == 1, out[0]["log"]


def scenario_grouped_partial(native, rt, rank, size):
    """Rank 1 submits only one member of a 2-group: the whole group must
    block (no member executes) and the stall shutdown must fail BOTH
    ranks consistently (group_table.h all-or-nothing + the negotiation
    error channel)."""
    hs = [rt.enqueue("pg0", native.OP_ALLREDUCE, "float32", [4],
                     group="grp-p", group_size=2)]
    if rank == 0:
        hs.append(rt.enqueue("pg1", native.OP_ALLREDUCE, "float32", [4],
                             group="grp-p", group_size=2))
    deadline = time.time() + 25
    pending = set(hs)
    while pending and time.time() < deadline:
        b = rt.next_batch(timeout_s=0.2)
        if b is not None:
            rt.batch_done(b, ok=True)
        done = {h for h in pending
                if rt.poll(h) in (rt_mod_DONE, rt_mod_FAILED)}
        pending -= done
    return {"states": [rt.poll(h) for h in hs]}


def _worker_stall(rank, size, port, scenario, q):
    """Worker with a short stall-shutdown so blocked groups error out."""
    native = _load_native()
    rt = native.NativeRuntime()
    rt.init(rank, size, "127.0.0.1", port, cycle_ms=1.0,
            cache_capacity=64, stall_warning_s=1.0, stall_shutdown_s=3.0)
    try:
        result = scenario(native, rt, rank, size)
        q.put((rank, "ok", result))
    except Exception as e:
        q.put((rank, "err", repr(e)))
    finally:
        rt.shutdown()


def test_grouped_partial_submission_blocks_and_errors():
    port = _free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_worker_stall,
                    args=(r, 2, port, scenario_grouped_partial, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    results = {}
    deadline = time.time() + 60
    while len(results) < 2 and time.time() < deadline:
        try:
            rank, status, payload = q.get(timeout=1.0)
            results[rank] = (status, payload)
        except Exception:
            pass
    for p in procs:
        p.join(timeout=5)
        if p.is_alive():
            p.terminate()
    assert len(results) == 2, f"only {len(results)}/2 reported"
    for rank, (status, payload) in results.items():
        assert status == "ok", f"rank {rank}: {payload}"
        # nothing may complete; the stall shutdown fails everything on
        # every rank — consistently, not by deadlock
        assert all(s == rt_mod_FAILED for s in payload["states"]), payload


def scenario_grouped_ag_rs_partial(native, rt, rank, size):
    """All-or-nothing also holds for allgather and reducescatter groups
    (reference operations.cc:1725, :1532): rank 1 withholds one member
    of each group — nothing executes, the stall shutdown fails all."""
    hs = [
        rt.enqueue("agp0", native.OP_ALLGATHER, "float32", [4],
                   group="grp-ag", group_size=2),
        rt.enqueue("rsp0", native.OP_REDUCESCATTER, "float32", [4],
                   group="grp-rs", group_size=2),
    ]
    if rank == 0:
        hs.append(rt.enqueue("agp1", native.OP_ALLGATHER, "float32",
                             [4], group="grp-ag", group_size=2))
        hs.append(rt.enqueue("rsp1", native.OP_REDUCESCATTER, "float32",
                             [4], group="grp-rs", group_size=2))
    deadline = time.time() + 25
    pending = set(hs)
    while pending and time.time() < deadline:
        b = rt.next_batch(timeout_s=0.2)
        if b is not None:
            rt.batch_done(b, ok=True)
        done = {h for h in pending
                if rt.poll(h) in (rt_mod_DONE, rt_mod_FAILED)}
        pending -= done
    return {"states": [rt.poll(h) for h in hs]}


def test_grouped_allgather_reducescatter_all_or_nothing():
    port = _free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_worker_stall,
                    args=(r, 2, port, scenario_grouped_ag_rs_partial, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    results = {}
    deadline = time.time() + 60
    while len(results) < 2 and time.time() < deadline:
        try:
            rank, status, payload = q.get(timeout=1.0)
            results[rank] = (status, payload)
        except Exception:
            pass
    for p in procs:
        p.join(timeout=5)
        if p.is_alive():
            p.terminate()
    assert len(results) == 2, f"only {len(results)}/2 reported"
    for rank, (status, payload) in results.items():
        assert status == "ok", f"rank {rank}: {payload}"
        assert all(s == rt_mod_FAILED for s in payload["states"]), payload


def scenario_group_mismatch(native, rt, rank, size):
    """Same tensor, different group metadata across ranks → consistent
    negotiated error."""
    gs = 2 if rank == 0 else 3
    h = rt.enqueue("gmx", native.OP_ALLREDUCE, "float32", [4],
                   group="grp-m", group_size=gs)
    h2 = rt.enqueue("gmx2", native.OP_ALLREDUCE, "float32", [4],
                    group="grp-m", group_size=gs)
    state = rt.wait(h, timeout_s=20.0)
    state2 = rt.wait(h2, timeout_s=20.0)
    return {"state": state, "state2": state2}


def test_group_metadata_mismatch_errors_consistently():
    out = _run_world(2, scenario_group_mismatch)
    for r in range(2):
        # the whole group fails — both members, on both ranks
        assert out[r]["state"] == rt_mod_FAILED, out[r]
        assert out[r]["state2"] == rt_mod_FAILED, out[r]


# ---------------------------------------------------------- autotune


def _worker_autotune(rank, size, port, scenario, q):
    """Worker with fast autotune settings: warmup 1 sample, 2 busy cycles
    per sample → the 2-phase sweep (6 thresholds + 5 cycles) pins after
    ~24 busy cycles."""
    native = _load_native()
    rt = native.NativeRuntime()
    rt.init(rank, size, "127.0.0.1", port, cycle_ms=1.0,
            cache_capacity=64, stall_warning_s=60.0,
            autotune=True, autotune_warmup=1,
            autotune_cycles_per_sample=2)
    try:
        q.put((rank, "ok", scenario(native, rt, rank, size)))
    except Exception as e:
        q.put((rank, "err", repr(e)))
    finally:
        rt.shutdown()


def scenario_autotune(native, rt, rank, size):
    """Steady traffic until the coordinator pins; every rank reads the
    distributed parameters. `hier_seen` records every hierarchical-mode
    value observed during the search — the widened space (round 4,
    reference parameter_manager.h:186) must actually flip it."""
    deadline = time.time() + 40
    step = 0
    hier_seen = set()
    while not rt.tuned_pinned() and time.time() < deadline:
        hs = [
            rt.enqueue(f"at{i}", native.OP_ALLREDUCE, "float32", [256])
            for i in range(3)
        ]
        _drain_until(rt, hs, timeout_s=10.0)
        hier_seen.add(bool(rt.tuned_hierarchical()))
        step += 1
    return {
        "pinned": rt.tuned_pinned(),
        "cycle_ms": rt.tuned_cycle_ms(),
        "threshold": rt.tuned_threshold(),
        "cache_enabled": bool(rt.tuned_cache_enabled()),
        "hierarchical": bool(rt.tuned_hierarchical()),
        "hier_local": rt.tuned_hier_block(),
        "hier_seen": sorted(hier_seen),
        "steps": step,
    }


def test_autotune_all_ranks_pin_identical_parameters():
    """The coordinator searches {threshold x cycle_ms} and distributes
    the applied values in every ResponseList — so agreement is by
    construction, matching the reference's broadcast of winning
    parameters (parameter_manager.cc:528)."""
    port = _free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_worker_autotune,
                    args=(r, 2, port, scenario_autotune, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    results = {}
    deadline = time.time() + 90
    while len(results) < 2 and time.time() < deadline:
        try:
            rank, status, payload = q.get(timeout=1.0)
            results[rank] = (status, payload)
        except Exception:
            pass
    for p in procs:
        p.join(timeout=5)
        if p.is_alive():
            p.terminate()
    assert len(results) == 2, f"only {len(results)}/2 reported"
    payloads = {}
    for rank, (status, payload) in results.items():
        assert status == "ok", f"rank {rank}: {payload}"
        assert payload["pinned"], payload
        payloads[rank] = payload
    # the agreement criterion: identical pinned parameters on all ranks
    assert payloads[0]["cycle_ms"] == payloads[1]["cycle_ms"], payloads
    assert payloads[0]["threshold"] == payloads[1]["threshold"], payloads
    assert payloads[0]["cycle_ms"] in (0.25, 0.5, 1.0, 2.5, 5.0)
    assert payloads[0]["threshold"] >= 1 << 20


# ---------------------------------------------------------- single process


def test_single_rank_world_immediate():
    native = _load_native()
    rt = native.NativeRuntime()
    rt.init(0, 1, cycle_ms=1.0)
    try:
        h = rt.enqueue("solo", native.OP_ALLREDUCE, "float32", [4])
        batch = rt.next_batch(timeout_s=5.0)
        assert batch is not None
        assert batch.names == ["solo"]
        rt.batch_done(batch, ok=True)
        assert rt.wait(h, timeout_s=5.0) == rt_mod_DONE
    finally:
        rt.shutdown()


def test_duplicate_name_rejected():
    native = _load_native()
    rt = native.NativeRuntime()
    rt.init(0, 1, cycle_ms=1000.0)  # slow cycle: both enqueues land together
    try:
        rt.enqueue("dup", native.OP_ALLREDUCE, "float32", [4])
        h2 = rt.enqueue("dup", native.OP_ALLREDUCE, "float32", [4])
        assert rt.poll(h2) == rt_mod_FAILED
        assert "dup" in rt.last_error()
    finally:
        rt.shutdown()


# ------------------------------------------------- per-set controllers
# (reference process_set.h:89: each set negotiates independently; here
# one transport carries every set's traffic, keyed by set id)


def scenario_overlapping_sets(native, rt, rank, size):
    # world 3; A=1:{0,1}, B=2:{1,2} — registration is world-wide
    ra = rt.register_set(1, [0, 1])
    rb = rt.register_set(2, [1, 2])
    reg_states = [rt.wait(ra, 30.0), rt.wait(rb, 30.0)]
    members = {1: rt.set_members(1), 2: rt.set_members(2)}
    handles = []
    # members submit only their sets' ops (qualified names, like the
    # Python EagerRuntime does); rank 1 overlaps both
    if rank in (0, 1):
        handles.append(rt.enqueue("ps1:x", native.OP_ALLREDUCE, "float32",
                                  [4], process_set_id=1))
    if rank in (1, 2):
        handles.append(rt.enqueue("ps2:y", native.OP_ALLREDUCE, "float32",
                                  [8], process_set_id=2))
    log = []
    import time as _t
    deadline = _t.time() + 30.0
    pending = set(handles)
    while pending and _t.time() < deadline:
        batch = rt.next_batch(timeout_s=0.2)
        if batch is not None:
            log.append((batch.op, tuple(batch.names),
                        batch.process_set_id, tuple(batch.set_ranks)))
            rt.batch_done(batch, ok=True)
        pending -= {h for h in pending
                    if rt.poll(h) in (rt_mod_DONE, rt_mod_FAILED)}
    states = [rt.poll(h) for h in handles]
    # hold the world open until every rank is done: shutdown is a
    # negotiated world-wide event, so an early-returning rank would kill
    # peers' in-flight subset ops
    _drain_until(rt, [rt.enqueue("fin", native.OP_ALLREDUCE, "float32",
                                 [2])], timeout_s=20.0)
    return {"reg": reg_states, "members": members, "log": log,
            "states": states}


def test_overlapping_sets_negotiate_independently():
    """Two overlapping sets: each negotiates among its own members, a
    rank sees only its sets' batches, and batches carry the set's
    sub-mesh membership (reference process_set.h:89)."""
    out = _run_world(3, scenario_overlapping_sets)
    for r in range(3):
        assert out[r]["reg"] == [rt_mod_DONE, rt_mod_DONE]
        assert out[r]["members"] == {1: [0, 1], 2: [1, 2]}
        assert all(s == rt_mod_DONE for s in out[r]["states"])
    sets_seen = lambda r: {e[2] for e in out[r]["log"]}
    assert sets_seen(0) == {1}      # never sees set 2's batches
    assert sets_seen(2) == {2}      # never sees set 1's batches
    assert sets_seen(1) == {1, 2}   # overlap executes both
    for e in out[1]["log"]:
        assert e[3] == ((0, 1) if e[2] == 1 else (1, 2))


def scenario_set_mismatch(native, rt, rank, size):
    ranks = [0, 1] if rank == 0 else [0]
    h = rt.register_set(1, ranks)
    state = rt.wait(h, 20.0)
    return {"state": state, "err": rt.last_error()}


def test_set_registration_mismatch_fails_consistently():
    """Mismatched membership across ranks fails registration on every
    rank through the ordinary metadata-validation channel."""
    out = _run_world(2, scenario_set_mismatch)
    for r in range(2):
        assert out[r]["state"] == rt_mod_FAILED


def scenario_nonmember_enqueue(native, rt, rank, size):
    h = rt.register_set(1, [0])
    assert rt.wait(h, 30.0) == rt_mod_DONE
    # BOTH ranks enqueue the same qualified name into set 1: the member's
    # op must complete even though the non-member's errors — per-rank
    # error targeting (Response.error_rank)
    hh = rt.enqueue("ps1:z", native.OP_ALLREDUCE, "float32", [4],
                    process_set_id=1)
    _drain_until(rt, [hh], timeout_s=20.0)
    state, err = rt.poll(hh), rt.last_error()
    # hold the world open (negotiated shutdown; see overlapping_sets)
    _drain_until(rt, [rt.enqueue("fin", native.OP_ALLREDUCE, "float32",
                                 [2])], timeout_s=20.0)
    return {"state": state, "err": err}


def test_nonmember_enqueue_fails_only_offender():
    out = _run_world(2, scenario_nonmember_enqueue)
    assert out[0]["state"] == rt_mod_DONE
    assert out[1]["state"] == rt_mod_FAILED
    assert "not a member" in out[1]["err"]


def scenario_set_cache(native, rt, rank, size):
    h = rt.register_set(1, [0, 1])
    assert rt.wait(h, 30.0) == rt_mod_DONE
    for _ in range(4):
        hs = []
        if rank in (0, 1):
            hs.append(rt.enqueue("ps1:g", native.OP_ALLREDUCE, "float32",
                                 [16], process_set_id=1))
        hs.append(rt.enqueue("glob", native.OP_ALLREDUCE, "float32", [16]))
        _drain_until(rt, hs, timeout_s=20.0)
    return {"cache_hits": rt.cache_hits()}


def test_subset_ops_ride_the_cache_fast_path():
    """Member-scoped cache agreement: subset tensors cache-hit for the
    members even though non-members never claim the position (a
    world-wide AND would disable the fast path for every subset op)."""
    out = _run_world(3, scenario_set_cache)
    assert out[0]["cache_hits"] >= 2   # member: ps1:g + glob hits
    assert out[1]["cache_hits"] >= 2
    assert out[2]["cache_hits"] >= 1   # non-member still hits on glob


def scenario_set_barrier(native, rt, rank, size):
    h = rt.register_set(1, [0, 2])
    assert rt.wait(h, 30.0) == rt_mod_DONE
    state = None
    if rank in (0, 2):
        hb = rt.enqueue("ps1:__barrier__", native.OP_BARRIER, "uint8", [],
                        process_set_id=1)
        _drain_until(rt, [hb], timeout_s=20.0)
        state = rt.poll(hb)
    # hold the world open (negotiated shutdown; see overlapping_sets):
    # the non-member completes this only after the members passed their
    # barrier and submitted theirs
    _drain_until(rt, [rt.enqueue("fin", native.OP_ALLREDUCE, "float32",
                                 [2])], timeout_s=20.0)
    return {"state": state}


def test_subset_barrier_completes_for_members_only():
    out = _run_world(3, scenario_set_barrier)
    assert out[0]["state"] == rt_mod_DONE
    assert out[2]["state"] == rt_mod_DONE
    assert out[1]["state"] is None


def scenario_deregister(native, rt, rank, size):
    h = rt.register_set(1, [0, 1])
    assert rt.wait(h, 30.0) == rt_mod_DONE
    stranded_state = None
    if rank == 0:
        # submitted on one rank only: the deregistration must fail it
        # instead of leaving it pending forever
        hs = rt.enqueue("ps1:stranded", native.OP_ALLREDUCE, "float32",
                        [4], process_set_id=1)
    hd = rt.deregister_set(1)
    state = rt.wait(hd, 30.0)
    if rank == 0:
        s = rt.wait(hs, 20.0)
        while s in (0, 1):
            batch = rt.next_batch(timeout_s=0.2)
            if batch is not None:
                rt.batch_done(batch, ok=True)
            s = rt.wait(hs, 5.0)
        stranded_state = s
    members = rt.set_members(1)
    return {"state": state, "stranded": stranded_state,
            "members": members, "err": rt.last_error()}


def test_deregistered_set_fails_stranded_tensors():
    out = _run_world(2, scenario_deregister)
    for r in range(2):
        assert out[r]["state"] == rt_mod_DONE
        assert out[r]["members"] is None
    assert out[0]["stranded"] == rt_mod_FAILED


# ------------------------------------------------ crash-mid-cycle
# (reference controller.cc:252-270 lost-connection path: a dead rank
# must surface as a consistent error on every survivor, never a hang)


def _worker_crash(rank, size, port, victim, q, barrier):
    import os
    import signal

    native = _load_native()
    rt = native.NativeRuntime()
    rt.init(rank, size, "127.0.0.1", port, cycle_ms=1.0, cache_capacity=64)
    # one completed collective proves the world was fully connected
    h = rt.enqueue("warm", native.OP_ALLREDUCE, "float32", [4])
    _drain_until(rt, [h], timeout_s=30.0)
    if rt.poll(h) != rt_mod_DONE:
        q.put((rank, "warm-failed", rt.last_error()))
        rt.shutdown()
        return
    # every rank must see its OWN warm complete before the victim
    # dies: rank 0's DONE only proves the coordinator got ITS result —
    # a coordinator victim SIGKILLing itself here could still beat the
    # workers' warm responses onto the wire, and their warm (not the
    # post-crash op this test is about) would fail. Out-of-band
    # barrier, because any in-band sync has the same race.
    try:
        barrier.wait(timeout=30.0)
    except Exception:
        q.put((rank, "warm-barrier-failed", rt.last_error()))
        return
    if rank == victim:
        os.kill(os.getpid(), signal.SIGKILL)  # no cleanup, mid-world
    # the post-crash op can surface the death at either API point:
    # enqueue itself raising "lost connection" (the background loop
    # already observed the dead transport) or a successful enqueue
    # whose handle polls FAILED. Both are the non-hang contract this
    # test asserts; which one a survivor sees is a pure timing race.
    try:
        h2 = rt.enqueue("after", native.OP_ALLREDUCE, "float32", [4])
    except RuntimeError as e:
        q.put((rank, rt_mod_FAILED, str(e)))
        return
    deadline = time.time() + 45.0
    state = rt.poll(h2)
    while state in (0, 1) and time.time() < deadline:
        batch = rt.next_batch(timeout_s=0.2)
        if batch is not None:
            rt.batch_done(batch, ok=True)
        state = rt.poll(h2)
    q.put((rank, state, rt.last_error()))
    # do NOT rt.shutdown(): the broken world's negotiated shutdown can't
    # complete; the background loop already exited via the error path


def _run_crash_world(size, victim, timeout_s=90.0):
    port = _free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    barrier = ctx.Barrier(size)
    procs = [
        ctx.Process(target=_worker_crash,
                    args=(r, size, port, victim, q, barrier))
        for r in range(size)
    ]
    for p in procs:
        p.start()
    results = {}
    deadline = time.time() + timeout_s
    while len(results) < size - 1 and time.time() < deadline:
        try:
            rank, state, err = q.get(timeout=1.0)
            results[rank] = (state, err)
        except Exception:
            pass
    for p in procs:
        p.join(timeout=10)
        if p.is_alive():
            p.terminate()
    return results


def test_worker_crash_mid_cycle_errors_survivors():
    """kill -9 a worker rank between collectives: every survivor's next
    op must FAIL with the lost-connection error, not hang (reference
    controller.cc:252-270)."""
    out = _run_crash_world(3, victim=2)
    assert sorted(out) == [0, 1], f"survivors missing: {out}"
    for r in (0, 1):
        state, err = out[r]
        assert state == rt_mod_FAILED, f"rank {r} state={state} err={err}"
        assert "lost connection" in err or "rank 2" in err, err


def test_coordinator_crash_errors_workers():
    """kill -9 the coordinator: workers' transport fails and their
    pending ops raise instead of blocking forever."""
    out = _run_crash_world(3, victim=0)
    assert sorted(out) == [1, 2], f"survivors missing: {out}"
    for r in (1, 2):
        state, err = out[r]
        assert state == rt_mod_FAILED, f"rank {r} state={state} err={err}"
        assert "lost connection" in err, err


# ------------------------------------------------- Bayesian autotune


def test_bayesian_tuner_finds_optimum():
    """The GP+EI searcher (bayes.cc — role parity with the reference's
    optim/bayesian_optimization.cc) localizes the maximum of a smooth
    2-D objective within a kernel length scale in ~15 samples."""
    import ctypes

    native = _load_native()
    lib = native.load()
    dims = 2
    lib.hvd_bayes_test_create(dims)
    try:
        buf = (ctypes.c_double * dims)()

        def objective(x0, x1):
            return -((x0 - 0.7) ** 2) - (x1 - 0.3) ** 2

        for _ in range(15):
            lib.hvd_bayes_test_next(buf, dims)
            x = list(buf)
            assert all(0.0 <= v <= 1.0 for v in x), x
            lib.hvd_bayes_test_observe(buf, dims, objective(*x))
        lib.hvd_bayes_test_best(buf, dims)
        best = list(buf)
        # optimum is (0.7, 0.3) with value 0; random search over 15
        # points would miss this bar most of the time
        assert objective(*best) > -0.02, best
    finally:
        lib.hvd_bayes_test_free()


def _worker_autotune_bayes(rank, size, port, scenario, q):
    """Same shape as _worker_autotune but with the GP+EI strategy."""
    native = _load_native()
    rt = native.NativeRuntime()
    rt.init(rank, size, "127.0.0.1", port, cycle_ms=1.0,
            cache_capacity=64, stall_warning_s=60.0,
            autotune=True, autotune_warmup=1,
            autotune_cycles_per_sample=2, autotune_bayes=True)
    try:
        q.put((rank, "ok", scenario(native, rt, rank, size)))
    except Exception as e:
        q.put((rank, "err", repr(e)))
    finally:
        rt.shutdown()


def test_bayesian_autotune_all_ranks_pin_identical_parameters():
    """HOROVOD_AUTOTUNE_BAYES: the coordinator's GP searches the joint
    {threshold x cycle} space (12 samples) and every rank pins the same
    continuous winner it distributed."""
    port = _free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_worker_autotune_bayes,
                    args=(r, 2, port, scenario_autotune, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    results = {}
    deadline = time.time() + 120
    while len(results) < 2 and time.time() < deadline:
        try:
            rank, status, payload = q.get(timeout=1.0)
            results[rank] = (status, payload)
        except Exception:
            pass
    for p in procs:
        p.join(timeout=5)
        if p.is_alive():
            p.terminate()
    assert len(results) == 2, f"only {len(results)}/2 reported"
    payloads = {}
    for rank, (status, payload) in results.items():
        assert status == "ok", f"rank {rank}: {payload}"
        assert payload["pinned"], payload
        payloads[rank] = payload
    assert payloads[0]["cycle_ms"] == payloads[1]["cycle_ms"], payloads
    assert payloads[0]["threshold"] == payloads[1]["threshold"], payloads
    # winners live in the continuous search ranges, not the descent grid
    assert 0.25 <= payloads[0]["cycle_ms"] <= 5.0, payloads
    assert (1 << 20) <= payloads[0]["threshold"] <= (256 << 20), payloads
    # widened space (reference parameter_manager.h:186): all ranks pin
    # the identical cache/hierarchical config, the search actually
    # explored both hierarchical modes (the seeding corners guarantee
    # it), and the inner-domain size stays in its 2..16 range
    for key in ("cache_enabled", "hierarchical", "hier_local"):
        assert payloads[0][key] == payloads[1][key], payloads
    assert payloads[0]["hier_seen"] == [False, True], payloads
    assert 2 <= payloads[0]["hier_local"] <= 16, payloads
