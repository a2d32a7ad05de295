"""Multi-pod federation: topology, relay control plane, local-SGD.

The simulated world is the usual 8-device CPU mesh (conftest) carved
into pods as replica groups, plus in-process KV/relay servers for the
control plane — the same construction scripts/multipod_check.py gates
end-to-end (docs/multipod.md).
"""

import json
import time
import urllib.request

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.core.exceptions import HorovodInternalError
from horovod_tpu.multipod.localsgd import (
    LocalSGD,
    OuterState,
    local_sgd_active,
    parse_sync_mode,
)
from horovod_tpu.multipod.relay import (
    PodRelayServer,
    push_endpoint,
    relay_endpoint_from_env,
)
from horovod_tpu.multipod.topology import (
    PodTopology,
    pod_block_groups,
    pod_topology,
    pod_topology_from_env,
)
from horovod_tpu.runner.http.http_server import KVStoreServer


def _put(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/{path}", data=body, method="PUT")
    with urllib.request.urlopen(req, timeout=5.0):
        pass


# ---------------------------------------------------------------- topology


class TestTopology:
    def test_members_and_groups(self):
        t = PodTopology(n_pods=4, pod_id=2, world=8)
        assert t.pod_size == 2
        assert t.members() == [4, 5]
        assert t.members(0) == [0, 1]
        assert t.pod_of_rank(5) == 2
        assert t.inner_groups() == [[0, 1], [2, 3], [4, 5], [6, 7]]
        assert t.outer_groups() == [[0, 2, 4, 6], [1, 3, 5, 7]]
        assert t.pod_label() == "pod2"

    def test_groups_partition_world(self):
        inner, outer = pod_block_groups(12, 3)
        assert sorted(r for g in inner for r in g) == list(range(12))
        assert sorted(r for g in outer for r in g) == list(range(12))

    def test_invalid_shapes_raise(self):
        with pytest.raises(HorovodInternalError):
            PodTopology(n_pods=3, pod_id=0, world=8)  # not divisible
        with pytest.raises(HorovodInternalError):
            PodTopology(n_pods=2, pod_id=2, world=8)  # id out of range

    def test_from_env(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_MULTIPOD_PODS", "4")
        monkeypatch.setenv("HOROVOD_SIZE", "16")
        monkeypatch.setenv("HOROVOD_RANK", "9")
        t = pod_topology_from_env()
        assert (t.n_pods, t.world, t.pod_id) == (4, 16, 2)
        monkeypatch.setenv("HOROVOD_MULTIPOD_POD_ID", "3")
        assert pod_topology_from_env().pod_id == 3

    def test_from_env_absent(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_MULTIPOD_PODS", raising=False)
        monkeypatch.delenv("HVD_TPU_MULTIPOD_PODS", raising=False)
        assert pod_topology_from_env() is None

    def test_pod_topology_from_knobs(self, hvd8):
        import dataclasses

        from horovod_tpu.core.state import global_state

        st = global_state()
        st.knobs = dataclasses.replace(st.knobs, multipod_pods=4)
        t = pod_topology()
        assert t is not None and t.n_pods == 4 and t.world == 8
        assert t.pod_size == 2

    def test_process_set_integration(self, hvd8):
        t = PodTopology(n_pods=4, pod_id=1, world=8)
        ps = t.process_set()
        assert ps.ranks == [2, 3]
        # idempotent: a second resolve returns the SAME registration
        assert t.process_set().process_set_id == ps.process_set_id
        groups = ps.axis_index_groups(8)
        assert [2, 3] in groups


# ------------------------------------------------------------------ relay


class TestRelay:
    def test_endpoint_resolution(self, monkeypatch):
        monkeypatch.delenv("HVD_TPU_RELAY_ADDR", raising=False)
        monkeypatch.delenv("HVD_TPU_RELAY_PORT", raising=False)
        monkeypatch.delenv("HOROVOD_RELAY_ADDR", raising=False)
        monkeypatch.delenv("HOROVOD_RELAY_PORT", raising=False)
        assert relay_endpoint_from_env() is None
        assert push_endpoint(root=("r", 1)) == ("r", 1)
        monkeypatch.setenv("HVD_TPU_RELAY_ADDR", "10.0.0.2")
        monkeypatch.setenv("HVD_TPU_RELAY_PORT", "7070")
        assert relay_endpoint_from_env() == ("10.0.0.2", 7070)
        # the relay wins over the root for pushes
        assert push_endpoint(root=("r", 1)) == ("10.0.0.2", 7070)

    def test_forward_batches_and_pod_labels(self):
        root = KVStoreServer()
        rport = root.start_server()
        relay = PodRelayServer("pod1", ("127.0.0.1", rport),
                               flush_interval_s=0.05)
        lport = relay.start_server()
        try:
            _put(lport, "metrics_push/3",
                 b"# HELP x y\n# TYPE x counter\nx 1\n")
            _put(lport, "replication/rank_3", b'{"epoch": 7}')
            deadline = time.time() + 10.0
            while time.time() < deadline:
                with root.lock:
                    if root.store.get("replication"):
                        break
                time.sleep(0.02)
            with root.lock:
                scopes = {k: dict(v) for k, v in root.store.items()}
            # metrics keys arrive pod-labeled, other scopes verbatim
            assert "3@pod1" in scopes["metrics_push"]
            assert scopes["replication"]["rank_3"] == b'{"epoch": 7}'
            # two worker PUTs became one root request
            assert root.request_count == 1
            assert relay.stats()["forwarded_entries"] == 2
        finally:
            relay.shutdown_server()
            root.shutdown_server()

    def test_aggregated_metrics_carry_pod_label(self):
        from horovod_tpu.utils import metrics

        ctype, body = metrics.exposition(
            {"3@pod1": b"# HELP x y\n# TYPE x counter\nx 1\n",
             "4": b"# HELP x y\n# TYPE x counter\nx 2\n"})
        text = body.decode()
        assert 'x{rank="3",pod="pod1"} 1' in text
        assert 'x{rank="4"} 2' in text
        assert metrics.lint_exposition(text) == []

    def test_coalescing_last_write_wins(self):
        root = KVStoreServer()
        rport = root.start_server()
        relay = PodRelayServer("pod0", ("127.0.0.1", rport),
                               flush_interval_s=30.0)  # no auto-flush
        lport = relay.start_server()
        try:
            for i in range(5):
                _put(lport, "metrics_push/0", f"v{i}".encode())
            assert relay.flush_once() == 1  # five pushes, one entry
            with root.lock:
                got = root.store["metrics_push"]["0@pod0"]
            assert got == b"v4"
        finally:
            relay.shutdown_server()
            root.shutdown_server()

    def test_outage_retains_pending_until_root_returns(self, tmp_path):
        state = str(tmp_path / "root.pkl")
        root = KVStoreServer(state_path=state, flush_interval_s=0.05)
        rport = root.start_server()
        relay = PodRelayServer("pod0", ("127.0.0.1", rport),
                               flush_interval_s=30.0)
        lport = relay.start_server()
        try:
            root.persist()
            root.shutdown_server()
            _put(lport, "flight/2", b"dump")
            assert relay.flush_once() == 0  # root down: re-merged
            assert relay.stats()["pending"] == 1
            root2 = KVStoreServer(state_path=state)
            assert root2.start_server() == rport  # same-port failover
            assert relay.flush_once() == 1
            with root2.lock:
                assert root2.store["flight"]["2"] == b"dump"
                # the root stamps relayed flight dumps exactly like
                # direct ones
                meta = json.loads(root2.store["flight_meta"]["2"])
            assert meta["bytes"] == 4
            root2.shutdown_server()
        finally:
            relay.shutdown_server()

    def test_forward_scope_filter(self):
        root = KVStoreServer()
        rport = root.start_server()
        relay = PodRelayServer("pod0", ("127.0.0.1", rport),
                               flush_interval_s=30.0,
                               forward_scopes=["metrics_push"])
        lport = relay.start_server()
        try:
            _put(lport, "metrics_push/0", b"m")
            _put(lport, "private_scope/k", b"v")
            assert relay.flush_once() == 1
            with root.lock:
                assert "private_scope" not in root.store
            # but the relay's own store holds it (pod-local KV)
            with relay.lock:
                assert relay.store["private_scope"]["k"] == b"v"
        finally:
            relay.shutdown_server()
            root.shutdown_server()


# --------------------------------------------------------------- localsgd


class TestLocalSGD:
    def test_parse_sync_mode(self):
        assert parse_sync_mode("sync") == ("sync", 1)
        assert parse_sync_mode("") == ("sync", 1)
        assert parse_sync_mode("local8") == ("local", 8)
        assert parse_sync_mode("LOCAL 4") == ("local", 4)
        # K<=1 normalizes to the plain path — the bitwise K=1 parity
        # guarantee is BY CONSTRUCTION (docs/multipod.md)
        assert parse_sync_mode("local1") == ("sync", 1)
        assert parse_sync_mode("local0") == ("sync", 1)
        with pytest.raises(HorovodInternalError):
            parse_sync_mode("bogus")

    def test_active_gate(self):
        multi = PodTopology(n_pods=4, pod_id=0, world=8)
        single = PodTopology(n_pods=1, pod_id=0, world=8)
        assert local_sgd_active(multi, "local4")
        assert not local_sgd_active(multi, "sync")
        assert not local_sgd_active(multi, "local1")
        assert not local_sgd_active(single, "local4")
        assert not local_sgd_active(None, "local4")

    def test_constructor_rejects_plain_configs(self):
        multi = PodTopology(n_pods=4, pod_id=0, world=8)
        single = PodTopology(n_pods=1, pod_id=0, world=8)
        with pytest.raises(HorovodInternalError):
            LocalSGD(multi, k=1)
        with pytest.raises(HorovodInternalError):
            LocalSGD(single, k=4)

    def test_should_sync_cadence(self):
        ls = LocalSGD(PodTopology(n_pods=2, pod_id=0, world=8), k=4)
        fired = [s for s in range(12) if ls.should_sync(s)]
        assert fired == [3, 7, 11]

    def test_inner_and_outer_means(self, hvd8):
        topo = PodTopology(n_pods=4, pod_id=0, world=8)
        ls = LocalSGD(topo, k=2)
        mesh = hvd.mesh()
        x = jnp.asarray(
            np.random.RandomState(0).uniform(-1, 1, (8, 6)),
            jnp.float32)

        def body(t):
            im = ls.inner_mean(t[0])
            return im[None], ls.cross_pod_mean(im)[None]

        im, cm = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("hvd"),
            out_specs=(P("hvd"), P("hvd")), check_vma=False))(x)
        xs = np.asarray(x)
        ref_in = np.stack(
            [xs[2 * (r // 2): 2 * (r // 2) + 2].mean(0)
             for r in range(8)])
        np.testing.assert_allclose(np.asarray(im), ref_in, atol=1e-6)
        ref_cross = np.stack(
            [np.mean([ref_in[(r % 2) + 2 * p] for p in range(4)], 0)
             for r in range(8)])
        np.testing.assert_allclose(np.asarray(cm), ref_cross,
                                   atol=1e-6)

    def test_outer_sync_is_averaging_without_momentum(self, hvd8):
        topo = PodTopology(n_pods=4, pod_id=0, world=8)
        ls = LocalSGD(topo, k=2)  # momentum 0, lr 1
        mesh = hvd.mesh()
        x = jnp.asarray(
            np.random.RandomState(1).uniform(-1, 1, (8, 5)),
            jnp.float32)

        def body(t):
            # the anchor is the LAST synchronized point (zeros here);
            # params have since drifted to t[0]. With momentum 0 and
            # outer_lr 1 the sync must land on the cross-pod average:
            # anchor + mean(p - anchor) = mean(p) for equal anchors.
            p = {"w": t[0]}
            st = OuterState(anchor={"w": jnp.zeros_like(t[0])},
                            velocity={"w": jnp.zeros_like(t[0])})
            p2, st2 = ls.outer_sync(p, st)
            return p2["w"][None], st2.anchor["w"][None]

        w2, anchor2 = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("hvd"),
            out_specs=(P("hvd"), P("hvd")), check_vma=False))(x)
        xs = np.asarray(x)
        ref = np.stack(
            [np.mean([xs[(r % 2) + 2 * p] for p in range(4)], 0)
             for r in range(8)])
        np.testing.assert_allclose(np.asarray(w2), ref, atol=1e-6)
        # the sync re-anchors at the new point
        np.testing.assert_allclose(np.asarray(anchor2), ref, atol=1e-6)

    def test_outer_sync_noop_when_already_anchored(self, hvd8):
        """Freshly init_outer'ed state (anchor == params) must make the
        first sync a no-op: nothing has drifted, nothing moves."""
        topo = PodTopology(n_pods=4, pod_id=0, world=8)
        ls = LocalSGD(topo, k=2)
        mesh = hvd.mesh()
        x = jnp.asarray(
            np.random.RandomState(2).uniform(-1, 1, (8, 5)),
            jnp.float32)

        def body(t):
            p = {"w": t[0]}
            p2, _ = ls.outer_sync(p, ls.init_outer(p))
            return p2["w"][None]

        w2 = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
            check_vma=False))(x)
        np.testing.assert_array_equal(np.asarray(w2), np.asarray(x))

    def test_outer_sync_tuple_structured_params(self, hvd8):
        """Tuple-shaped params pytrees (plain tuples / namedtuples)
        must come back with their own structure — the result
        extraction must never confuse a structural tuple with a
        per-leaf result pair."""
        topo = PodTopology(n_pods=4, pod_id=0, world=8)
        ls = LocalSGD(topo, k=2)
        mesh = hvd.mesh()
        x = jnp.asarray(
            np.random.RandomState(3).uniform(-1, 1, (8, 4)),
            jnp.float32)

        def body(t):
            p = (t[0], 2.0 * t[0])  # tuple pytree, distinct leaves
            zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
            p2, st2 = ls.outer_sync(
                p, OuterState(anchor=zeros, velocity=zeros))
            return p2[0][None], p2[1][None], st2.velocity[1][None]

        w0, w1, v1 = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("hvd"),
            out_specs=(P("hvd"),) * 3, check_vma=False))(x)
        xs = np.asarray(x)
        ref = np.stack(
            [np.mean([xs[(r % 2) + 2 * p] for p in range(4)], 0)
             for r in range(8)])
        np.testing.assert_allclose(np.asarray(w0), ref, atol=1e-6)
        # second leaf is its own average, NOT the first leaf's
        # velocity buffer
        np.testing.assert_allclose(np.asarray(w1), 2 * ref, atol=1e-6)
        np.testing.assert_allclose(np.asarray(v1), 2 * ref, atol=1e-6)

    def test_maybe_outer_sync_traced_cadence(self, hvd8):
        """maybe_outer_sync under jit with a traced step: OuterState
        must flow through lax.cond (it is a registered pytree), the
        sync firing only on every K-th step."""
        topo = PodTopology(n_pods=4, pod_id=0, world=8)
        ls = LocalSGD(topo, k=2)
        mesh = hvd.mesh()
        x = jnp.asarray(
            np.random.RandomState(4).uniform(-1, 1, (8, 4)),
            jnp.float32)

        def body(t, step):
            p = {"w": t[0]}
            zeros = {"w": jnp.zeros_like(t[0])}
            p2, st2 = ls.maybe_outer_sync(
                p, OuterState(anchor=zeros, velocity=zeros),
                step[0, 0])
            return p2["w"][None], st2.anchor["w"][None]

        run = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("hvd"), P("hvd")),
            out_specs=(P("hvd"), P("hvd")), check_vma=False))
        steps = jnp.zeros((8, 1), jnp.int32)
        # step 0: (0+1) % 2 != 0 → pass-through
        w_skip, _ = run(x, steps)
        np.testing.assert_array_equal(np.asarray(w_skip),
                                      np.asarray(x))
        # step 1: (1+1) % 2 == 0 → the cross-pod average
        w_sync, a_sync = run(x, steps + 1)
        xs = np.asarray(x)
        ref = np.stack(
            [np.mean([xs[(r % 2) + 2 * p] for p in range(4)], 0)
             for r in range(8)])
        np.testing.assert_allclose(np.asarray(w_sync), ref, atol=1e-6)
        np.testing.assert_allclose(np.asarray(a_sync), ref, atol=1e-6)

    def test_from_knobs_routing(self, hvd8):
        import dataclasses

        from horovod_tpu.core.state import global_state
        from horovod_tpu.multipod import localsgd

        st = global_state()
        # single pod: always the plain path
        assert localsgd.from_knobs() is None
        st.knobs = dataclasses.replace(
            st.knobs, multipod_pods=4, multipod_sync="local4",
            multipod_outer_momentum=0.5)
        ls = localsgd.from_knobs()
        assert ls is not None and ls.k == 4
        assert ls.outer_momentum == 0.5
        # sync spec: plain path even with pods declared
        st.knobs = dataclasses.replace(st.knobs, multipod_sync="sync")
        assert localsgd.from_knobs() is None


# ------------------------------------- int8 error feedback across syncs


class TestErrorFeedbackCarry:
    """PR 17 satellite: the int8 outer wire's quantization residual
    must CARRY across outer syncs (in OuterState) instead of being
    dropped — dropped residuals accumulate as a bias random-walk over
    syncs; carried residuals cancel, keeping the localK trajectory
    within one quantization step of fp32 outer averaging."""

    T_ROUNDS = 12
    DIM = 96

    def _run_rounds(self, ls, mesh, drifts):
        """T rounds of (drift by drifts[t], outer_sync); returns the
        final stacked (8, DIM) params."""
        carries = ls.carries_residual

        if carries:
            def body(w, a, v, r):
                p, st = ls.outer_sync(
                    w[0], OuterState(anchor=a[0], velocity=v[0],
                                     residual=r[0]))
                return (p[None], st.anchor[None], st.velocity[None],
                        st.residual[None])

            sync = jax.jit(shard_map(
                body, mesh=mesh, in_specs=(P("hvd"),) * 4,
                out_specs=(P("hvd"),) * 4, check_vma=False))
        else:
            def body(w, a, v):
                p, st = ls.outer_sync(
                    w[0], OuterState(anchor=a[0], velocity=v[0]))
                return p[None], st.anchor[None], st.velocity[None]

            sync = jax.jit(shard_map(
                body, mesh=mesh, in_specs=(P("hvd"),) * 3,
                out_specs=(P("hvd"),) * 3, check_vma=False))

        w = jnp.zeros((8, self.DIM), jnp.float32)
        a, v = w, jnp.zeros_like(w)
        r = jnp.zeros_like(w)
        for t in range(self.T_ROUNDS):
            w = w + drifts[t]
            if carries:
                w, a, v, r = sync(w, a, v, r)
            else:
                w, a, v = sync(w, a, v)
        return np.asarray(w)

    def _drifts(self):
        """(T, 8, DIM) per-rank drifts, equal within each pod (ranks
        2p, 2p+1) so the pods-agree invariant holds round over
        round."""
        rng = np.random.RandomState(7)
        per_pod = rng.uniform(
            -1, 1, (self.T_ROUNDS, 4, self.DIM)).astype(np.float32)
        return np.repeat(per_pod, 2, axis=1)

    def test_carried_residual_beats_dropping(self, hvd8):
        from horovod_tpu.optim.compression import WireSpec

        topo = PodTopology(n_pods=4, pod_id=0, world=8)
        mesh = hvd.mesh()
        drifts = self._drifts()

        w_fp = self._run_rounds(LocalSGD(topo, 2), mesh, drifts)
        w_ef = self._run_rounds(
            LocalSGD(topo, 2, wire=WireSpec("int8", 32,
                                            error_feedback=True)),
            mesh, drifts)
        w_drop = self._run_rounds(
            LocalSGD(topo, 2, wire=WireSpec("int8", 32)), mesh, drifts)

        err_ef = float(np.abs(w_ef - w_fp).max())
        err_drop = float(np.abs(w_drop - w_fp).max())
        # measurably closer to the fp32 outer average, not just equal
        assert err_ef < 0.8 * err_drop, (err_ef, err_drop)
        # and bounded by ~one quantization step, not a T-round walk
        assert err_ef < 0.05, err_ef

    def test_carry_is_unbiased_vs_fp32(self, hvd8):
        """Unbiasedness: the MEAN signed deviation from the fp32
        trajectory stays near zero with the carry (errors cancel),
        while dropping leaves a drifted estimate."""
        from horovod_tpu.optim.compression import WireSpec

        topo = PodTopology(n_pods=4, pod_id=0, world=8)
        mesh = hvd.mesh()
        drifts = self._drifts()

        w_fp = self._run_rounds(LocalSGD(topo, 2), mesh, drifts)
        w_ef = self._run_rounds(
            LocalSGD(topo, 2, wire=WireSpec("int8", 32,
                                            error_feedback=True)),
            mesh, drifts)
        bias_ef = float(np.abs(np.mean(w_ef - w_fp)))
        assert bias_ef < 5e-3, bias_ef
        # pods still agree bitwise after the final sync
        assert np.abs(w_ef.reshape(4, 2, -1)[:, 0]
                      - w_ef.reshape(4, 2, -1)[:, 1]).max() == 0.0

    def test_state_shapes_and_gating(self, hvd8):
        """carries_residual requires int8 AND error_feedback;
        init_outer materializes f32 zero residuals only then."""
        from horovod_tpu.optim.compression import WireSpec

        topo = PodTopology(n_pods=4, pod_id=0, world=8)
        params = {"w": jnp.ones((3, 2)), "b": jnp.ones((2,))}

        plain = LocalSGD(topo, 2).init_outer(params)
        assert plain.residual is None
        assert LocalSGD(
            topo, 2, wire=WireSpec("fp16")).carries_residual is False
        assert LocalSGD(
            topo, 2,
            wire=WireSpec("int8", 64)).carries_residual is False

        ls = LocalSGD(topo, 2, wire=WireSpec("int8", 64,
                                             error_feedback=True))
        st = ls.init_outer(params)
        assert st.residual is not None
        assert st.residual["w"].dtype == jnp.float32
        assert st.residual["w"].shape == (3, 2)
        assert float(jnp.abs(st.residual["b"]).max()) == 0.0
        # pytree round-trip keeps all three fields
        leaves, treedef = jax.tree_util.tree_flatten(st)
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert back.residual["w"].shape == (3, 2)


# ------------------------------------------- Adam m/v merge at syncs


class TestOptimizerMomentMerge:
    """PR 17 satellite: pod-local Adam moments are MERGED (averaged)
    at sync points rather than reset or left divergent."""

    def _mesh_and_ls(self):
        topo = PodTopology(n_pods=4, pod_id=0, world=8)
        return hvd.mesh(), LocalSGD(topo, 2)

    def test_merge_averages_mu_and_nu(self, hvd8):
        optax = pytest.importorskip("optax")
        mesh, ls = self._mesh_and_ls()
        params = {"w": jnp.ones((4,))}
        proto = optax.adam(1e-3).init(params)

        def body(mu, nu):
            node = proto[0]._replace(mu={"w": mu[0]},
                                     nu={"w": nu[0]})
            merged = ls.merge_optimizer_state((node, proto[1]))
            return merged[0].mu["w"][None], merged[0].nu["w"][None]

        f = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("hvd"),) * 2,
            out_specs=(P("hvd"),) * 2, check_vma=False))
        mu = jnp.asarray(
            np.arange(32, dtype=np.float32).reshape(8, 4))
        nu = 10.0 * mu + 1.0
        mo, no = (np.asarray(t) for t in f(mu, nu))
        mus, nus = np.asarray(mu), np.asarray(nu)
        for r in range(8):
            group = [(r % 2) + 2 * p for p in range(4)]
            np.testing.assert_allclose(
                mo[r], mus[group].mean(0), atol=1e-6)
            np.testing.assert_allclose(
                no[r], nus[group].mean(0), atol=1e-6)

    def test_merge_leaves_count_and_plain_leaves_alone(self, hvd8):
        optax = pytest.importorskip("optax")
        mesh, ls = self._mesh_and_ls()
        params = {"w": jnp.ones((4,))}
        proto = optax.adam(1e-3).init(params)

        def body(mu):
            node = proto[0]._replace(
                mu={"w": mu[0]}, count=jnp.asarray(17, jnp.int32))
            extra = {"lr": mu[0] * 2.0}  # non-adam leaf: untouched
            m_node, m_extra = ls.merge_optimizer_state((node, extra))
            return (m_node.count[None],
                    m_extra["lr"][None])

        f = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("hvd"),
            out_specs=(P("hvd"), P("hvd")), check_vma=False))
        mu = jnp.asarray(
            np.arange(32, dtype=np.float32).reshape(8, 4))
        counts, lrs = f(mu)
        assert np.all(np.asarray(counts) == 17)
        np.testing.assert_array_equal(
            np.asarray(lrs), np.asarray(mu) * 2.0)

    def test_k1_never_reaches_merge(self):
        """K=1 bitwise-parity gate: local1 normalizes to the plain
        synchronous path, LocalSGD is never constructed, so neither
        the residual carry nor the moment merge can perturb it."""
        from horovod_tpu.multipod.localsgd import (
            local_sgd_active, parse_sync_mode)

        assert parse_sync_mode("local1") == ("sync", 1)
        multi = PodTopology(n_pods=4, pod_id=0, world=8)
        assert not local_sgd_active(multi, "local1")
        with pytest.raises(HorovodInternalError):
            LocalSGD(multi, k=1)


# ---------------------------------------------------- retry (full jitter)


class TestRetryFleetDiscipline:
    def test_full_jitter_spreads_over_window(self):
        from horovod_tpu.utils.retry import RetryPolicy

        import random

        p = RetryPolicy(jitter="full", base_delay_s=1.0,
                        max_delay_s=1.0)
        rng = random.Random(0)
        delays = [p.delay_for_attempt(1, rng) for _ in range(200)]
        assert all(0.0 <= d <= 1.0 for d in delays)
        # bounded jitter never goes below 0.75*d; full jitter must
        assert min(delays) < 0.5
        assert max(delays) > 0.5

    def test_max_elapsed_caps_deadlineless_calls(self):
        from horovod_tpu.utils.retry import RetryPolicy

        t = [0.0]
        sleeps = []

        def clock():
            return t[0]

        def sleep(d):
            sleeps.append(d)
            t[0] += d

        p = RetryPolicy(max_attempts=100, base_delay_s=1.0,
                        max_delay_s=1.0, jitter_frac=0.0,
                        max_elapsed_s=3.5, clock=clock, sleep=sleep,
                        record_metrics=False)
        calls = [0]

        def fn():
            calls[0] += 1
            t[0] += 0.1  # each attempt costs wall time
            raise OSError("down")

        with pytest.raises(OSError):
            p.call(fn)
        # far fewer than max_attempts: the shared elapsed cap bound it
        assert calls[0] < 10

    def test_default_policy_full_jitter(self, monkeypatch):
        from horovod_tpu.utils import retry

        monkeypatch.delenv("HOROVOD_RETRY_JITTER", raising=False)
        retry.set_default_policy(None)
        try:
            p = retry.default_policy()
            assert p.jitter == "full"
            assert p.max_elapsed_s == 60.0
        finally:
            retry.set_default_policy(None)


# ----------------------------------------------------- metrics pod stamps


class TestPodTelemetry:
    def test_step_records_carry_pod(self, tmp_path):
        from horovod_tpu.utils import metrics

        metrics.reset()
        try:
            metrics.enable()
            metrics.set_pod_label("pod3")
            log = str(tmp_path / "steps.jsonl")
            metrics.step_stats.open_log(log)
            with metrics.step():
                pass
            with open(log) as f:
                rec = json.loads(f.readline())
            assert rec["pod"] == "pod3"
        finally:
            metrics.reset()
        assert metrics.pod_label() == ""  # reset clears the stamp

    def test_metrics_summary_pod_rollup(self, tmp_path, capsys):
        import sys

        sys.path.insert(0, "scripts")
        try:
            import metrics_summary
        finally:
            sys.path.pop(0)
        recs = []
        for pod in ("pod0", "pod1"):
            for i in range(3):
                recs.append({
                    "step": i + 1, "step_time_s": 0.01,
                    "collectives": {}, "pod": pod,
                })
        path = tmp_path / "m.jsonl"
        path.write_text(
            "".join(json.dumps(r) + "\n" for r in recs))
        rc = metrics_summary.main([str(path)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "per-pod rollup" in out
        assert "pod0" in out and "pod1" in out
