#!/usr/bin/env python
"""Multi-pod federation smoke gate (the 13th run_all_checks gate).

Simulates an N-pod fleet on this CPU host — pods as XLA replica groups
for the numerics, pods as relay servers + pusher threads for the
control plane — and gates the four multipod claims (docs/multipod.md):

1. **relay fan-in** — a 4-pod x 4-host world pushing metrics
   expositions through per-pod relays cuts the root server's request
   count by >= the pod fan-in factor (hosts per pod) versus every
   host pushing direct, and the root's aggregated /metrics carries
   ``pod=`` labels and lints clean;
2. **localK convergence envelope** — the local-SGD outer loop
   (K local steps per pod + cross-pod parameter averaging over the
   int8-quantized DCN leg, outer momentum) trains the toy regression
   to within the documented envelope of the fully-synchronous
   baseline (final localK loss <= ENVELOPE x sync loss + ABS_FLOOR);
3. **K=1 bitwise parity** — ``HOROVOD_MULTIPOD_SYNC=local1``
   normalizes to the plain synchronous path, so its trained
   parameters are bit-for-bit identical to the plain SPMD run;
4. **root failover with relays attached** — a root restart from its
   persisted state (the PR 7 same-port failover) loses nothing: pre-
   failover relayed records survive the restart, records pushed
   during the outage sit coalesced in the relay and land after it;
5. **sharded-root replica kill** (docs/control_plane.md) — SIGKILL
   1 of 3 supervised ShardReplicas: the ring successor fences at a
   bumped epoch before the supervisor's (deliberately slower) restart,
   every key stays readable with zero client giveups, a stale-epoch
   write bounces 409, and the restarted replica rejoins at a fresh
   epoch — plus the ``--root-replicas 1`` degrade staying on today's
   single-root path;
6. **supervised relay kill** — a ``relay.proc:kill`` fault inside the
   relay's forward loop is ridden by the launcher's ProcessSupervisor:
   backoff restart, flap counted in the exported metrics, and the next
   batched PUT landing on the correct shard owner.

Usage: python scripts/multipod_check.py [--check] [--out FILE.json]
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_FLAG = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

N_PODS = 4
HOSTS_PER_POD = 4
PUSHES_PER_HOST = 5

K_LOCAL = 4
STEPS = 120
OUTER_MOMENTUM = 0.5
# documented convergence envelope (docs/multipod.md): the localK final
# loss may trail the sync baseline by at most this factor (plus a
# floor for losses already at numerical zero)
ENVELOPE = 1.5
ABS_FLOOR = 1e-4


def _put(addr, port, path, body):
    from horovod_tpu.multipod.fanin import put_with_retry

    put_with_retry(addr, port, path, body)


# ---------------------------------------------------------------------------
# 1. relay fan-in reduction
# ---------------------------------------------------------------------------

def check_relay_fanin():
    from horovod_tpu.multipod.fanin import measure_fanin
    from horovod_tpu.utils import metrics

    m = measure_fanin(N_PODS, HOSTS_PER_POD,
                      pushes_per_host=PUSHES_PER_HOST)
    pushed = m.pop("pushed")
    _ctype, body = metrics.exposition(pushed)
    text = body.decode()
    lint = metrics.lint_exposition(text)
    pod_labeled = sum(
        1 for line in text.splitlines()
        if 'pod="pod' in line and 'rank="' in line)
    row = {
        "pods": N_PODS,
        "hosts": m["hosts"],
        "pushes_per_host": PUSHES_PER_HOST,
        "root_requests_direct": m["direct"]["root_requests"],
        "root_requests_relayed": m["relayed"]["root_requests"],
        "reduction_x": m["root_request_reduction_x"],
        "required_reduction_x": HOSTS_PER_POD,
        "aggregated_series_with_pod_label": pod_labeled,
        "exposition_lint_errors": lint,
        "all_ranks_aggregated": len(pushed) == N_PODS * HOSTS_PER_POD,
    }
    ok = (row["reduction_x"] >= HOSTS_PER_POD and not lint
          and pod_labeled > 0 and row["all_ranks_aggregated"])
    return ok, row


# ---------------------------------------------------------------------------
# 2 + 3. localK convergence + K=1 bitwise parity (8-dev CPU mesh)
# ---------------------------------------------------------------------------

def _build_world():
    import jax

    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init()
    assert hvd.size() == 8, "check expects 8 virtual devices"
    return hvd


def _train(hvd, sync_spec, steps=STEPS, lr=0.1, wire=None):
    """Toy linear regression, per-rank data shards; returns (final
    per-rank params ndarray, loss history). sync_spec routes through
    parse_sync_mode exactly as a user knob would."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from horovod_tpu.multipod.localsgd import (
        LocalSGD, OuterState, local_sgd_active, parse_sync_mode)
    from horovod_tpu.multipod.topology import PodTopology

    topo = PodTopology(n_pods=N_PODS, pod_id=0, world=8)
    active = local_sgd_active(topo, sync_spec)
    _mode, k = parse_sync_mode(sync_spec)
    ls = LocalSGD(topo, k, outer_momentum=OUTER_MOMENTUM,
                  wire=wire) if active else None

    rng = np.random.RandomState(0)
    w_true = rng.randn(6, 1).astype(np.float32)
    x_all = rng.randn(8, 32, 6).astype(np.float32)
    y_all = x_all @ w_true + 0.01 * rng.randn(8, 32, 1).astype(
        np.float32)
    mesh = hvd.mesh()

    def loss_fn(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    def plain_step(w, x, y):
        g = jax.grad(loss_fn)(w, x, y)
        g = jax.lax.pmean(g, "hvd")
        return w - lr * g

    def local_step(w, x, y):
        g = jax.grad(loss_fn)(w, x, y)
        g = ls.inner_mean(g)
        return w - lr * g

    inner = local_step if active else plain_step

    def body(w, x, y):
        # per-rank leading dim of 1 in, 1 out: the stacked global
        # arrays keep the (world, ...) shape across steps
        return inner(w[0], x[0], y[0])[None]

    step = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("hvd"),) * 3,
        out_specs=P("hvd"), check_vma=False))
    sync_step = None
    carries = bool(active and ls.carries_residual)
    if active and carries:
        def sync_body(w, a, v, r):
            p, st2 = ls.outer_sync(
                w[0], OuterState(anchor=a[0], velocity=v[0],
                                 residual=r[0]))
            return (p[None], st2.anchor[None], st2.velocity[None],
                    st2.residual[None])

        sync_step = jax.jit(shard_map(
            sync_body, mesh=mesh, in_specs=(P("hvd"),) * 4,
            out_specs=(P("hvd"),) * 4, check_vma=False))
    elif active:
        def sync_body(w, a, v):
            p, st2 = ls.outer_sync(
                w[0], OuterState(anchor=a[0], velocity=v[0]))
            return p[None], st2.anchor[None], st2.velocity[None]

        sync_step = jax.jit(shard_map(
            sync_body, mesh=mesh, in_specs=(P("hvd"),) * 3,
            out_specs=(P("hvd"),) * 3, check_vma=False))

    w0 = np.zeros((6, 1), np.float32)
    w = jnp.asarray(np.tile(w0[None], (8, 1, 1)))
    anchor = w
    vel = jnp.zeros_like(w)
    res = jnp.zeros_like(w) if carries else None
    x = jnp.asarray(x_all)
    y = jnp.asarray(y_all)
    losses = []
    for s in range(steps):
        w = step(w, x, y)
        if ls is not None and ls.should_sync(s):
            if carries:
                w, anchor, vel, res = sync_step(w, anchor, vel, res)
            else:
                w, anchor, vel = sync_step(w, anchor, vel)
        wl = np.asarray(w)
        losses.append(float(np.mean(
            (np.einsum("rbi,rio->rbo", np.asarray(x_all), wl)
             - y_all) ** 2)))
    return np.asarray(w), losses


def check_localsgd():
    from horovod_tpu.optim.compression import WireSpec

    hvd = _build_world()
    try:
        w_sync, loss_sync = _train(hvd, "sync")
        w_local, loss_local = _train(
            hvd, f"local{K_LOCAL}",
            wire=WireSpec("int8", 64, error_feedback=True))
        # K=1: parse_sync_mode normalizes local1 to sync → plain path
        w_k1, _ = _train(hvd, "local1")
    finally:
        hvd.shutdown()
    import numpy as np

    envelope_ok = (
        loss_local[-1] <= ENVELOPE * loss_sync[-1] + ABS_FLOOR)
    parity_ok = np.array_equal(w_k1, w_sync)
    pods_agree = bool(np.allclose(
        np.asarray(w_local).reshape(8, -1).std(axis=0).max(), 0.0,
        atol=1e-6))
    row = {
        "k": K_LOCAL,
        "outer_momentum": OUTER_MOMENTUM,
        "wire": "int8/64+ef",
        "steps": STEPS,
        "sync_final_loss": loss_sync[-1],
        "localk_final_loss": loss_local[-1],
        "envelope_factor": ENVELOPE,
        "envelope_ok": envelope_ok,
        "k1_bitwise_parity": parity_ok,
        "pods_agree_after_final_sync": pods_agree,
    }
    return (envelope_ok and parity_ok and pods_agree), row


# ---------------------------------------------------------------------------
# 4. root failover with relays attached
# ---------------------------------------------------------------------------

def check_failover():
    from horovod_tpu.multipod.relay import PodRelayServer
    from horovod_tpu.runner.http.http_server import KVStoreServer

    with tempfile.TemporaryDirectory(prefix="hvd_multipod_") as d:
        state = os.path.join(d, "root_state.pkl")
        root = KVStoreServer(state_path=state, flush_interval_s=0.05)
        rport = root.start_server()
        relay = PodRelayServer("pod0", ("127.0.0.1", rport),
                               flush_interval_s=0.05)
        lport = relay.start_server()
        try:
            _put("127.0.0.1", lport, "metrics_push/0", b"pre-failover")
            deadline = time.time() + 10.0
            while time.time() < deadline:
                with root.lock:
                    if root.store.get("metrics_push"):
                        break
                time.sleep(0.02)
            root.persist()
            root.shutdown_server()  # the outage

            # push during the outage: coalesces in the relay, forward
            # retries fail quietly (Outage discipline)
            _put("127.0.0.1", lport, "metrics_push/1", b"during-outage")
            time.sleep(0.3)

            # failover: a fresh server on the SAME state path rebinds
            # the persisted port (PR 7) and the relay reconnects
            root2 = KVStoreServer(state_path=state,
                                  flush_interval_s=0.05)
            port2 = root2.start_server()
            same_port = port2 == rport
            deadline = time.time() + 20.0
            got = {}
            while time.time() < deadline:
                relay.flush_once()
                with root2.lock:
                    got = dict(root2.store.get("metrics_push", {}))
                if "0@pod0" in got and "1@pod0" in got:
                    break
                time.sleep(0.05)
            restored = got.get("0@pod0") == b"pre-failover"
            recovered = got.get("1@pod0") == b"during-outage"
            root2.shutdown_server()
        finally:
            relay.shutdown_server()
    row = {
        "root_rebound_same_port": same_port,
        "pre_failover_record_restored": restored,
        "outage_record_delivered_after_failover": recovered,
    }
    return (same_port and restored and recovered), row


# ---------------------------------------------------------------------------
# 5. sharded root tier: SIGKILL a replica → fence + takeover + rejoin
# ---------------------------------------------------------------------------

def _fetch_shard_map(addr, port, timeout=3.0):
    import urllib.request

    with urllib.request.urlopen(
            f"http://{addr}:{port}/shard_map", timeout=timeout) as r:
        return json.loads(r.read())


def _wait_tier_ready(roots, deadline_s=20.0):
    deadline = time.time() + deadline_s
    pending = list(roots)
    while pending and time.time() < deadline:
        still = []
        for a, p in pending:
            try:
                _fetch_shard_map(a, p)
            except Exception:
                still.append((a, p))
        pending = still
        if pending:
            time.sleep(0.1)
    return not pending


def _wait_tier_state(roots, want_epoch, deadline_s,
                     want_alive=None, skip_ids=()):
    """Poll surviving roots until one serves a map at >= want_epoch
    (and, when given, with want_alive marked alive). Returns the
    winning map dict or None."""
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        for rid, (a, p) in enumerate(roots):
            if rid in skip_ids:
                continue
            try:
                m = _fetch_shard_map(a, p)
            except Exception:
                continue
            alive = {r["id"] for r in m["replicas"] if r["alive"]}
            if m["epoch"] >= want_epoch and (
                    want_alive is None or want_alive in alive):
                return m
        time.sleep(0.1)
    return None


def check_root_replica_kill():
    """SIGKILL 1 of 3 launcher-supervised root replicas. The
    supervisor's restart backoff (4s) deliberately exceeds the lease
    TTL (1.5s), so the tier must ride the outage the hard way: the
    victim's ring successor fences at a bumped epoch and serves its
    ranges from the write-through backups (zero lost scopes, zero
    client giveups), a stale epoch-0 replica write bounces 409, and
    the supervised restart then REJOINS at a fresh epoch with every
    key still readable."""
    import signal
    import urllib.error
    import urllib.request

    from horovod_tpu.multipod.fanin import _free_ports
    from horovod_tpu.runner.http.http_client import ShardClient
    from horovod_tpu.runner.supervisor import (
        ProcessSupervisor, python_child_argv)

    n, victim_id, lease = 3, 1, 1.5
    n_keys = 40
    row = {"replicas": n, "lease_ttl_s": lease,
           "supervisor_restart_delay_s": 4.0}
    with tempfile.TemporaryDirectory(prefix="hvd_cp_kill_") as d:
        ports = _free_ports(n)
        roots = [("127.0.0.1", p) for p in ports]
        spec = ",".join(f"{a}:{p}" for a, p in roots)
        # flap_window 0: a SIGKILL round must not look like a crash
        # loop; every restart waits exactly base_delay > lease TTL
        sup = ProcessSupervisor(base_delay_s=4.0, max_delay_s=8.0,
                                flap_window_s=0.0)
        try:
            for i in range(n):
                sup.add(f"root_{i}", python_child_argv(
                    "horovod_tpu.runner.http.http_server",
                    "--replica-id", str(i), "--roots", spec,
                    "--state-path", os.path.join(d, f"r{i}.pkl"),
                    "--lease-ttl", str(lease),
                    "--heartbeat-interval", "0.3"))
            sup.start()
            row["tier_ready"] = _wait_tier_ready(roots)

            client = ShardClient(roots, takeover_timeout_s=15.0)
            values = {f"k{i}": f"v{i}".encode()
                      for i in range(n_keys)}
            for k, v in values.items():
                client.put("elastic", k, v)

            os.kill(sup.stats()[f"root_{victim_id}"]["pid"],
                    signal.SIGKILL)
            t_kill = time.time()
            fenced = _wait_tier_state(
                roots, want_epoch=1, deadline_s=12.0,
                skip_ids=(victim_id,))
            row["takeover_epoch"] = fenced["epoch"] if fenced else None
            row["takeover_s"] = round(time.time() - t_kill, 2)

            giveups = 0
            reread = ShardClient(roots, takeover_timeout_s=15.0)
            for k, v in values.items():
                try:
                    if reread.get("elastic", k) != v:
                        giveups += 1
                except Exception:
                    giveups += 1
            row["post_takeover_giveups"] = giveups

            # a replica still at epoch 0 pushing state must be fenced
            survivor = next((a, p) for rid, (a, p) in enumerate(roots)
                            if rid != victim_id)
            code = 0
            try:
                req = urllib.request.Request(
                    f"http://{survivor[0]}:{survivor[1]}"
                    f"/_cp/sync/{victim_id}",
                    data=json.dumps({"epoch": 0, "entries": []}
                                    ).encode(),
                    method="PUT")
                with urllib.request.urlopen(req, timeout=5):
                    code = 200
            except urllib.error.HTTPError as e:
                code = e.code
            row["stale_write_status"] = code

            # the supervised restart lands (~4s) and rejoins the ring
            rejoined = _wait_tier_state(
                roots, want_epoch=2, deadline_s=25.0,
                want_alive=victim_id)
            row["rejoin_epoch"] = (rejoined["epoch"] if rejoined
                                   else None)
            giveups2 = 0
            again = ShardClient(roots, takeover_timeout_s=15.0)
            for k, v in values.items():
                try:
                    if again.get("elastic", k) != v:
                        giveups2 += 1
                except Exception:
                    giveups2 += 1
            row["post_rejoin_giveups"] = giveups2
            row["supervisor_restarts"] = (
                sup.stats()[f"root_{victim_id}"]["restarts"])
        finally:
            sup.shutdown()

    # --root-replicas 1 degrade: one plain (unsharded) root, the same
    # client — today's path, no shard map, verbs land direct
    from horovod_tpu.runner.http.http_server import KVStoreServer

    single = KVStoreServer(port=0)
    single.start_server()
    try:
        c1 = ShardClient([("127.0.0.1", single.port)])
        c1.put("elastic", "solo", b"1")
        row["single_root_degrade_ok"] = (
            c1.get("elastic", "solo") == b"1"
            and not c1.shard_map())
    finally:
        single.shutdown_server()

    ok = (row["tier_ready"]
          and row["takeover_epoch"] is not None
          and row["post_takeover_giveups"] == 0
          and row["stale_write_status"] == 409
          and row["rejoin_epoch"] is not None
          and row["post_rejoin_giveups"] == 0
          and row["supervisor_restarts"] >= 1
          and row["single_root_degrade_ok"])
    return ok, row


# ---------------------------------------------------------------------------
# 6. supervised relay killed by fault injection → backoff restart
# ---------------------------------------------------------------------------

def check_supervised_relay_kill():
    """A launcher-supervised pod relay killed from INSIDE its forward
    loop (``relay.proc:kill`` fault spec) restarts under the
    supervisor's backoff; the next batched PUT still lands on the
    correct shard owner, and the flap count is visible in the
    supervisor metrics the root's /metrics scrape aggregates."""
    import urllib.request

    from horovod_tpu.multipod.fanin import _free_ports
    from horovod_tpu.runner.http.http_server import ShardReplica
    from horovod_tpu.runner.supervisor import (
        ProcessSupervisor, python_child_argv)
    from horovod_tpu.utils import metrics as _metrics

    ports = _free_ports(3)
    roots = [("127.0.0.1", p) for p in ports[:2]]
    relay_port = ports[2]
    spec = ",".join(f"{a}:{p}" for a, p in roots)
    reps = [ShardReplica(i, roots) for i in range(2)]
    for r in reps:
        r.start_server()
    row = {}
    sup = ProcessSupervisor(base_delay_s=0.3, max_delay_s=2.0,
                            flap_window_s=5.0)
    env = dict(os.environ)
    # armed in the CHILD only: kill on the 2nd forward-loop pass
    env["HOROVOD_TPU_FAULT_SPEC"] = "relay.proc:kill:after=1:times=1"
    try:
        sup.add("relay_pod0", python_child_argv(
            "horovod_tpu.multipod.relay",
            "--pod-label", "pod0", "--roots", spec,
            "--port", str(relay_port),
            "--flush-interval", "0.1"), env=env)
        sup.start()

        def _relay_up(deadline_s=15.0):
            deadline = time.time() + deadline_s
            while time.time() < deadline:
                try:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{relay_port}/metrics",
                        timeout=1.0)
                    return True
                except Exception:
                    time.sleep(0.1)
            return False

        row["relay_up"] = _relay_up()
        # fault fires on the second forward pass (~0.2s in); wait for
        # the supervised restart
        deadline = time.time() + 20.0
        while time.time() < deadline:
            st = sup.stats()["relay_pod0"]
            if st["restarts"] >= 1 and st["alive"]:
                break
            time.sleep(0.1)
        st = sup.stats()["relay_pod0"]
        row["restarts"] = st["restarts"]
        row["flaps"] = st["flaps"]
        row["relay_back_up"] = _relay_up()

        # the NEXT batched PUT through the restarted relay lands on
        # its ring owner (no 421 bounce, value readable at the owner)
        _put("127.0.0.1", relay_port, "elastic/after_restart",
             b"post-restart")
        m = reps[0].membership
        own = m.owner_of("elastic", "after_restart")
        addr, port = m.addr_of(own)
        landed = False
        deadline = time.time() + 15.0
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://{addr}:{port}/elastic/after_restart",
                        timeout=2.0) as resp:
                    landed = resp.read() == b"post-restart"
                if landed:
                    break
            except Exception:
                pass
            time.sleep(0.1)
        row["post_restart_put_on_owner"] = landed
        text = _metrics.registry.render()
        row["flap_metric_exported"] = (
            'hvd_supervisor_flaps{proc="relay_pod0"}' in text)
    finally:
        sup.shutdown()
        for r in reps:
            r.shutdown_server()
    ok = (row.get("relay_up") and row.get("relay_back_up")
          and row.get("restarts", 0) >= 1
          and row.get("flaps", 0) >= 1
          and row.get("post_restart_put_on_owner")
          and row.get("flap_metric_exported"))
    return bool(ok), row


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero on any failed claim")
    ap.add_argument("--out", default="",
                    help="write the verdict JSON here too")
    args = ap.parse_args(argv)

    verdict = {"what": "multipod federation smoke "
                       f"({N_PODS} simulated pods)"}
    ok_all = True
    for name, fn in (("relay_fanin", check_relay_fanin),
                     ("localsgd", check_localsgd),
                     ("failover", check_failover),
                     ("root_replica_kill", check_root_replica_kill),
                     ("relay_kill", check_supervised_relay_kill)):
        t0 = time.perf_counter()
        try:
            ok, row = fn()
        except Exception as e:
            ok, row = False, {"error": repr(e)}
        row["ok"] = ok
        row["wall_s"] = round(time.perf_counter() - t0, 2)
        verdict[name] = row
        ok_all = ok_all and ok
        print(f"[{name}] {'OK' if ok else 'FAIL'} "
              f"in {row['wall_s']}s", flush=True)
    verdict["ok"] = ok_all
    txt = json.dumps(verdict, indent=1)
    print(txt)
    if args.out:
        with open(args.out, "w") as f:
            f.write(txt + "\n")
    if args.check and not ok_all:
        print("multipod check FAILED")
        return 1
    print("multipod check OK" if ok_all else
          "multipod check FAILED (advisory)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
