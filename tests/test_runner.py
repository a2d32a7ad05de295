"""Launcher-layer tests (tier-1; reference test/single/test_run.py pattern:
command/env construction with injected exec, no real ssh)."""

import functools
import os
import threading
import time

import pytest

from horovod_tpu.runner.util.hosts import (
    HostInfo,
    SlotInfo,
    get_host_assignments,
    parse_hosts,
)
from horovod_tpu.runner.util import config_parser, safe_shell_exec
from horovod_tpu.runner.util.network import (
    BasicClient,
    BasicService,
    Wire,
    find_free_port,
)
from horovod_tpu.runner.util.secret import make_secret_key
from horovod_tpu.runner.http import http_client
from horovod_tpu.runner.http.http_server import (
    RENDEZVOUS_SCOPE,
    KVStoreServer,
    RendezvousServer,
)
from horovod_tpu.runner import launch
from horovod_tpu.runner.exec_run import run_static, slot_env


# ---------------------------------------------------------------- hosts


def test_parse_hosts():
    hosts = parse_hosts("h1:4, h2:2,h3")
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("h1", 4), ("h2", 2), ("h3", 1),
    ]


def test_parse_host_files(tmp_path):
    f = tmp_path / "hostfile"
    f.write_text("# comment\nh1 slots=4\nh2:2\nh3\n")
    from horovod_tpu.runner.util.hosts import parse_host_files

    assert parse_host_files(str(f)) == "h1:4,h2:2,h3:1"


def test_host_assignments_basic():
    slots = get_host_assignments(parse_hosts("h1:2,h2:2"), 4)
    assert [s.rank for s in slots] == [0, 1, 2, 3]
    assert [s.hostname for s in slots] == ["h1", "h1", "h2", "h2"]
    assert [s.local_rank for s in slots] == [0, 1, 0, 1]
    assert [s.cross_rank for s in slots] == [0, 0, 1, 1]
    assert all(s.size == 4 and s.local_size == 2 for s in slots)


def test_host_assignments_max_np_truncates():
    slots = get_host_assignments(parse_hosts("h1:4,h2:4"), 2, max_np=3)
    assert len(slots) == 3
    assert [s.hostname for s in slots] == ["h1", "h1", "h1"]


def test_host_assignments_min_np_enforced():
    with pytest.raises(ValueError):
        get_host_assignments(parse_hosts("h1:2"), 4)


def test_host_assignments_rank_stability():
    """Surviving hosts keep their global ranks across a resize
    (reference elastic/driver.py:240)."""
    prior = {"h2": [2, 3], "h3": [4, 5]}
    slots = get_host_assignments(
        parse_hosts("h2:2,h3:2,h4:2"), 2, rank_assignments=prior
    )
    by_host = {}
    for s in slots:
        by_host.setdefault(s.hostname, []).append(s.rank)
    assert by_host["h2"] == [2, 3]
    assert by_host["h3"] == [4, 5]
    assert sorted(by_host["h4"]) == [0, 1]  # freed ranks reused


def test_slot_info_roundtrip():
    s = SlotInfo("h1", 3, 1, 1, 8, 4, 2)
    assert SlotInfo.from_response_string(s.to_response_string()) == s


# ---------------------------------------------------------------- network


def test_basic_service_ping_and_custom():
    key = make_secret_key()

    class EchoService(BasicService):
        def _handle(self, req, addr):
            if isinstance(req, dict):
                return {"echo": req}
            return super()._handle(req, addr)

    svc = EchoService("echo", key)
    try:
        client = BasicClient("echo", svc.addresses(), key)
        assert client.request({"x": 1}) == {"echo": {"x": 1}}
    finally:
        svc.shutdown()


def test_service_rejects_bad_hmac():
    key = make_secret_key()
    svc = BasicService("s", key)
    try:
        with pytest.raises(ConnectionError):
            BasicClient("s", svc.addresses(), b"wrong-key", attempts=1)
    finally:
        svc.shutdown()


def test_wire_detects_tamper():
    import io

    w_good, w_bad = Wire(b"k1"), Wire(b"k2")
    buf = io.BytesIO()
    w_good.write([1, 2], buf)
    buf.seek(0)
    with pytest.raises(PermissionError):
        w_bad.read(buf)


# ---------------------------------------------------------------- http kv


def test_kv_store_put_get_delete():
    server = KVStoreServer()
    port = server.start_server()
    try:
        assert http_client.get("127.0.0.1", port, "sc", "k") is None
        http_client.put("127.0.0.1", port, "sc", "k", b"v1")
        assert http_client.get("127.0.0.1", port, "sc", "k") == b"v1"
        http_client.delete("127.0.0.1", port, "sc", "k")
        assert http_client.get("127.0.0.1", port, "sc", "k") is None
    finally:
        server.shutdown_server()


def test_rendezvous_publishes_slots():
    server = RendezvousServer()
    slots = get_host_assignments(parse_hosts("h1:2"), 2)
    port = server.init(slots)
    try:
        raw = http_client.get(
            "127.0.0.1", port, RENDEZVOUS_SCOPE, "rank_1"
        )
        got = SlotInfo.from_response_string(raw.decode())
        assert got.rank == 1 and got.hostname == "h1"
        assert http_client.get(
            "127.0.0.1", port, RENDEZVOUS_SCOPE, "size"
        ) == b"2"
        # new round replaces assignments
        server.init(get_host_assignments(parse_hosts("h1:1"), 1))
        assert http_client.get(
            "127.0.0.1", port, RENDEZVOUS_SCOPE, "rank_1"
        ) is None
    finally:
        server.shutdown_server()


# ---------------------------------------------------------------- exec


def test_safe_shell_exec_runs_and_captures(capfd):
    ret = safe_shell_exec.execute(
        ["python", "-c", "print('hello-from-child')"], prefix="3"
    )
    assert ret == 0
    out = capfd.readouterr().out
    assert "[3]hello-from-child" in out


def test_safe_shell_exec_kill_on_event():
    ev = threading.Event()
    result = {}

    def run():
        result["code"] = safe_shell_exec.execute(
            ["python", "-c", "import time; time.sleep(60)"], events=[ev]
        )

    t = threading.Thread(target=run)
    t.start()
    time.sleep(0.5)
    ev.set()
    t.join(timeout=15)
    assert not t.is_alive()
    assert result["code"] != 0


# ---------------------------------------------------------------- config


def _args(**kw):
    defaults = dict(
        fusion_threshold_mb=None, cycle_time_ms=None, cache_capacity=None,
        timeline_filename=None, timeline_mark_cycles=None, autotune=None,
        autotune_log=None, compression_wire_dtype=None,
        hierarchical_allreduce=None, hierarchical_allgather=None,
        elastic_timeout=None, reset_limit=None, stall_check_disable=None,
        stall_warning_time_seconds=None, stall_shutdown_time_seconds=None,
        log_level=None, mesh=None,
    )
    defaults.update(kw)
    import argparse

    return argparse.Namespace(**defaults)


def test_env_from_args():
    env = config_parser.env_from_args(
        _args(fusion_threshold_mb=64, autotune=True, mesh="dp=4,tp=2"),
        {"BASE": "1"},
    )
    assert env["BASE"] == "1"
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(64 * 1024 * 1024)
    assert env["HOROVOD_AUTOTUNE"] == "1"
    assert env["HOROVOD_MESH"] == "dp=4,tp=2"
    assert "HOROVOD_CYCLE_TIME" not in env


def test_config_file(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("cycle-time-ms: 2.5\nautotune: true\nlog-level: INFO\n")
    args = launch.parse_args(
        ["--config-file", str(cfg), "--log-level", "DEBUG",
         "-np", "2", "python", "t.py"]
    )
    assert args.cycle_time_ms == 2.5
    assert args.autotune is True
    assert args.log_level == "DEBUG"  # CLI beats config file


@functools.lru_cache(maxsize=None)
def _sources_reading_env():
    """Every module of the package but the two that only declare the
    launcher's options (read once for all the cases below)."""
    import pathlib

    root = pathlib.Path(launch.__file__).resolve().parents[1]
    skip = {root / "runner" / "launch.py",
            root / "runner" / "util" / "config_parser.py"}
    return {str(f.relative_to(root)): f.read_text()
            for f in sorted(root.rglob("*.py")) if f not in skip}


@pytest.mark.parametrize("dest", sorted(config_parser.ARG_TO_ENV))
def test_launcher_option_is_declared_and_read(dest):
    """A knob lives in four files (core/knobs.py field and from_env,
    config_parser.ARG_TO_ENV, launch.py): one removed from some of them
    and not the others fails here by name. The environment name must be
    read by the package (knobs._env takes it without its prefix) and
    launch.py's parser must have the option."""
    import re

    var = config_parser.ARG_TO_ENV[dest]
    assert var.startswith("HOROVOD_")
    names = "|".join(re.escape(n) for n in (var, var[len("HOROVOD_"):]))
    quoted = re.compile(r"""["'](?:%s)["']""" % names)
    readers = [f for f, text in _sources_reading_env().items()
               if quoted.search(text)]
    assert readers, f"{var} ({dest}) is set by the launcher, read by nothing"
    args = launch.parse_args(["-np", "1", "python", "t.py"])
    assert hasattr(args, dest), f"launch.py has no option for {dest}"


@pytest.mark.parametrize("example, flag", [
    (None, "--fused-collectives=1"),
    ("bert_pretraining", "--fused-ln"),
    ("resnet50_synthetic", "--fused-bn"),
    ("resnet50_synthetic", "--one-by-one=dot"),
])
def test_removed_flag_is_unknown(example, flag, capsys):
    """The Pallas families PR 29 deleted left no option behind: their
    flags are refused by argparse, before anything is initialised."""
    from horovod_tpu.utils.script_loader import load_example

    with pytest.raises(SystemExit) as e:
        if example is None:
            launch.parse_args([flag, "-np", "1", "python", "t.py"])
        else:
            load_example(example).main([flag])
    assert e.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err


# ---------------------------------------------------------------- launch


def test_parse_args_static():
    args = launch.parse_args(
        ["-np", "4", "-H", "h1:2,h2:2", "python", "train.py", "--lr", "0.1"]
    )
    assert args.np == 4
    assert args.hosts == "h1:2,h2:2"
    assert args.command == ["python", "train.py", "--lr", "0.1"]
    assert not launch.is_elastic(args)


def test_parse_args_elastic():
    args = launch.parse_args(
        ["-np", "8", "--min-np", "4", "--max-np", "12",
         "--host-discovery-script", "./d.sh", "python", "train.py"]
    )
    assert launch.is_elastic(args)
    assert args.min_np == 4 and args.max_np == 12


def test_run_static_env_protocol():
    """Injected exec captures the per-slot env (reference gloo_run env
    protocol, gloo_run.py:66-101)."""
    captured = {}

    def fake_exec(command, env, slot, events):
        captured[slot.rank] = (command, env)
        return 0

    codes = run_static(
        ["python", "train.py"],
        parse_hosts("localhost:2"),
        2,
        env={},
        exec_fn=fake_exec,
    )
    assert codes == [0, 0]
    assert set(captured) == {0, 1}
    cmd, env0 = captured[0]
    assert cmd == ["python", "train.py"]
    assert env0["HOROVOD_RANK"] == "0"
    assert env0["HOROVOD_SIZE"] == "2"
    assert env0["HOROVOD_LOCAL_RANK"] == "0"
    assert env0["HVD_TPU_PROCESS_ID"] == "0"
    assert env0["HVD_TPU_NUM_PROCESSES"] == "2"
    assert "HVD_TPU_RENDEZVOUS_ADDR" in env0
    assert "HVD_TPU_SECRET_KEY" in env0
    _, env1 = captured[1]
    assert env1["HOROVOD_RANK"] == "1"
    assert env1["HOROVOD_LOCAL_RANK"] == "1"


def test_run_static_failure_kills_all():
    events_seen = []

    def fake_exec(command, env, slot, events):
        if slot.rank == 0:
            return 1  # fail immediately
        # wait for the kill event like a real worker would
        events_seen.append(events)
        deadline = time.time() + 10
        while time.time() < deadline:
            if any(e.is_set() for e in events):
                return 143
            time.sleep(0.05)
        return 0

    codes = run_static(
        ["x"], parse_hosts("localhost:2"), 2, env={}, exec_fn=fake_exec
    )
    assert codes[0] == 1
    assert codes[1] == 143  # terminated by the failure event


# ------------------------------------------------- NIC routability probe
# (reference driver_service.py:260 get_common_interfaces: tasks ring-
# probe each other's advertised interface addresses, the driver
# intersects the routable sets)


def test_ring_probe_filters_dark_interfaces():
    """Each task advertises a reachable NIC and a dark one (an address
    nothing routes); the ring intersection must keep only the NIC every
    hop could actually reach."""
    from horovod_tpu.runner.driver.probe import (
        TaskProbeService,
        find_common_nics,
    )
    from horovod_tpu.runner.util.secret import make_secret_key

    key = make_secret_key()
    tasks = [
        TaskProbeService(
            i, key,
            advertised={
                "eth0": "127.0.0.1",
                # dark NIC: an endpoint nothing listens on (the sandbox
                # NATs TEST-NET ips, so a dead local port is the
                # reliable unreachable address here)
                "ib0": ("127.0.0.1", find_free_port()),
            },
        )
        for i in range(3)
    ]
    try:
        addrs = [t.addresses() for t in tasks]
        nics = find_common_nics(addrs, key)
        assert nics == ["eth0"]
    finally:
        for t in tasks:
            t.shutdown()


def test_ring_probe_raises_without_common_interface():
    from horovod_tpu.runner.driver.probe import (
        TaskProbeService,
        find_common_nics,
    )
    from horovod_tpu.runner.util.secret import make_secret_key

    key = make_secret_key()
    tasks = [
        TaskProbeService(
            i, key, advertised={"ib0": ("127.0.0.1", find_free_port())}
        )
        for i in range(2)
    ]
    try:
        addrs = [t.addresses() for t in tasks]
        with pytest.raises(RuntimeError, match="no common routable"):
            find_common_nics(addrs, key)
    finally:
        for t in tasks:
            t.shutdown()


def test_probe_task_registration_flow():
    """Full driver flow with REAL probe-task subprocesses: driver
    launches them, they register, ring probe intersects, shutdown
    request ends them (reference _driver_fn, driver_service.py:163)."""
    import subprocess
    import sys

    from horovod_tpu.runner.driver.probe import get_common_interfaces
    from horovod_tpu.runner.util.secret import ENV_SECRET, make_secret_key

    key = make_secret_key()
    procs = []

    def launch(idx, host, driver_addresses):
        import base64
        import json

        b64 = base64.b64encode(
            json.dumps([list(a) for a in driver_addresses]).encode()
        ).decode()
        env = dict(os.environ)
        env[ENV_SECRET] = key.decode()
        env["PYTHONPATH"] = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-m",
             "horovod_tpu.runner.driver.probe_task", str(idx), b64,
             "--linger-s", "30"],
            env=env,
        ))

    # a fake remote hostname forces the probe path; the injected
    # launcher runs the tasks locally
    nics = get_common_interfaces(
        ["fake-remote-a", "fake-remote-b"], key,
        launch_task_fn=launch, timeout_s=30.0,
    )
    assert nics  # at least one common interface on one machine
    for p in procs:
        assert p.wait(timeout=15) == 0  # shutdown request ended them


def test_run_static_binds_probed_nic(monkeypatch):
    """launch_slots with explicit nics exports HOROVOD_NICS and binds
    the rendezvous address to the named NIC's ip."""
    import horovod_tpu.runner.driver.probe as probe_mod
    from horovod_tpu.runner.exec_run import launch_slots
    from horovod_tpu.runner.util.hosts import get_host_assignments

    monkeypatch.setattr(
        probe_mod, "interface_addresses",
        lambda nics=None: {"ethX": "127.0.0.1"},
    )
    seen = {}

    def fake_exec(command, env, slot, events):
        seen[slot.rank] = (env.get("HOROVOD_NICS"),
                           env.get("HVD_TPU_RENDEZVOUS_ADDR"))
        return 0

    assignments = get_host_assignments(parse_hosts("localhost:2"), 2, 2)
    codes = launch_slots(["x"], assignments, {}, exec_fn=fake_exec,
                         nics=["ethX"])
    assert codes == [0, 0]
    assert seen[0] == ("ethX", "127.0.0.1")
    assert seen[1] == ("ethX", "127.0.0.1")


def test_check_build_reports_capabilities(capsys):
    from horovod_tpu.runner.launch import run_commandline

    assert run_commandline(["--check-build"]) == 0
    out = capsys.readouterr().out
    assert "[X] JAX" in out
    assert "Native eager control plane" in out
    assert "Spark" in out and "Ray" in out
