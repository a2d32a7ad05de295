"""`hvdrun` — the horovodrun-equivalent CLI.

Reference: /root/reference/horovod/runner/launch.py — parse_args (:286),
`_run_static` (:583), `_run_elastic` (:676), `run_controller` (:734). The
controller-selection matrix (gloo/mpi/jsrun) collapses on TPU: the data
plane is always XLA collectives and bootstrap is always the rendezvous
HTTP store + JAX coordination service, so the remaining choice is
static vs elastic.

Usage:
    hvdrun -np 4 -H host1:1,host2:1,host3:1,host4:1 python train.py
    hvdrun -np 8 --min-np 4 --max-np 12 --host-discovery-script ./d.sh \
        python train.py
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from .util import config_parser
from .util.hosts import HostInfo, parse_host_files, parse_hosts


def parse_args(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu training job.",
    )
    p.add_argument("-v", "--version", action="store_true")
    p.add_argument("--check-build", dest="check_build",
                   action="store_true",
                   help="Print availability of frameworks, controllers "
                        "and ops, then exit (reference launch.py:110).")
    p.add_argument(
        "-np", "--num-proc", dest="np", type=int,
        help="Total number of worker processes (slots).",
    )
    p.add_argument(
        "-H", "--hosts", dest="hosts",
        help="Comma-separated host:slots list, e.g. h1:1,h2:1.",
    )
    p.add_argument(
        "-hostfile", "--hostfile", dest="hostfile",
        help="Hostfile with `host slots=N` lines.",
    )
    p.add_argument("--verbose", action="count", default=0)
    p.add_argument("--config-file", dest="config_file")

    # elastic (reference launch.py:676)
    p.add_argument("--min-np", dest="min_np", type=int)
    p.add_argument("--max-np", dest="max_np", type=int)
    p.add_argument(
        "--host-discovery-script", dest="host_discovery_script",
        help="Executable printing the current host:slots list, one per line.",
    )
    p.add_argument("--slots-per-host", dest="slots", type=int, default=1)
    p.add_argument("--elastic-timeout", dest="elastic_timeout", type=float)
    p.add_argument("--reset-limit", dest="reset_limit", type=int)
    p.add_argument(
        "--blacklist-cooldown-range", dest="cooldown_range", nargs=2,
        type=float, metavar=("MIN_S", "MAX_S"),
    )

    # runtime knobs → env (reference launch.py:286-580, config_parser)
    p.add_argument("--fusion-threshold-mb", dest="fusion_threshold_mb",
                   type=int)
    p.add_argument("--cycle-time-ms", dest="cycle_time_ms", type=float)
    p.add_argument("--cache-capacity", dest="cache_capacity", type=int)
    p.add_argument("--timeline-filename", dest="timeline_filename")
    p.add_argument("--timeline-mark-cycles", dest="timeline_mark_cycles",
                   action="store_true", default=None)
    p.add_argument("--autotune", dest="autotune", action="store_true",
                   default=None)
    p.add_argument("--autotune-bayes", dest="autotune_bayes",
                   action="store_true",
                   help="Bayesian (GP + expected-improvement) autotune "
                        "search instead of coordinate descent")
    p.add_argument("--autotune-log", dest="autotune_log")
    p.add_argument("--autotune-cache", dest="autotune_cache",
                   help="persistent warm-start cache for the "
                        "closed-loop OnlineTuner "
                        "(HOROVOD_AUTOTUNE_CACHE, docs/autotune.md): "
                        "winners persist per (model fingerprint, "
                        "topology); later runs and serving replicas "
                        "pin the cached configuration with zero "
                        "tuning compiles")
    p.add_argument("--autotune-mfu", dest="autotune_mfu",
                   choices=["0", "1"],
                   help="score autotune trials by measured hvd_mfu "
                        "when the continuous profiler is live "
                        "(HOROVOD_AUTOTUNE_MFU, default 1; the "
                        "step-time p50 via StepStats is always "
                        "recorded and is the fallback score)")
    p.add_argument("--autotune-wire", dest="autotune_wire",
                   choices=["0", "1"],
                   help="opt IN to the NUMERICS-CHANGING autotune "
                        "dimensions — wire dtype/block and eager "
                        "fast-path warmup K (HOROVOD_AUTOTUNE_WIRE, "
                        "default 0; int8 on the wire is lossy, so "
                        "the tuner never sweeps or warm-starts these "
                        "without explicit consent)")
    p.add_argument("--compression", dest="compression",
                   choices=["none", "fp16", "bf16", "int8", "int8-raw"],
                   help="compressed collective data plane "
                        "(HOROVOD_COMPRESSION, docs/compression.md): "
                        "cast wires halve gradient bytes, int8 "
                        "block-quantizes them ~4x with error feedback")
    p.add_argument("--compression-block", dest="compression_block",
                   type=int,
                   help="int8 quantization block (elements per scale, "
                        "HOROVOD_COMPRESSION_BLOCK, default 256)")
    p.add_argument("--overlap-schedule", dest="overlap_schedule",
                   choices=["off", "stage", "double"],
                   help="backward-interleaved collective scheduler "
                        "(HOROVOD_OVERLAP_SCHEDULE, docs/overlap.md): "
                        "'stage' issues each fusion bucket's "
                        "collective inside the backward, pinned before "
                        "the next segment's compute; 'double' also "
                        "defers optimizer consumption until the last "
                        "segment retires; default off")
    p.add_argument("--fsdp", dest="fsdp", choices=["0", "1"],
                   help="fully-sharded parameters / ZeRO-3 routing "
                        "(HOROVOD_FSDP, docs/fsdp.md): 1 (default) "
                        "routes FullyShardedOptimizer train steps "
                        "through the prefetch-interleaved FSDP path — "
                        "params + optimizer state ~1/world per chip; "
                        "0 disables routing (such a step then raises; "
                        "non-FSDP configs are untouched either way)")
    p.add_argument("--fsdp-prefetch", dest="fsdp_prefetch", type=int,
                   help="FSDP forward all-gather look-ahead in stages "
                        "(HOROVOD_FSDP_PREFETCH, default 1): bucket "
                        "k+1's parameter gather issues at segment k's "
                        "boundary and overlaps its compute; 0 "
                        "serializes gathers at their need boundaries")
    p.add_argument("--fsdp-regather", dest="fsdp_regather",
                   choices=["0", "1"],
                   help="FSDP backward re-gather policy "
                        "(HOROVOD_FSDP_REGATHER, docs/fsdp.md): 1 "
                        "(default) drops each gathered bucket at its "
                        "last forward use and re-issues the all-gather "
                        "at its backward-first-use boundary — "
                        "within-step peak param liveness capped at "
                        "sharded + one bucket working set, bitwise "
                        "equal to 0 (save gathered weights across the "
                        "whole step — the pre-regather lowering)")
    p.add_argument("--fsdp-offload", dest="fsdp_offload",
                   choices=["0", "1"],
                   help="FSDP host-RAM activation offload "
                        "(HOROVOD_FSDP_OFFLOAD, docs/fsdp.md): 1 parks "
                        "inter-stage carries in pinned host memory on "
                        "forward and prefetches each back one backward "
                        "segment ahead; bitwise no-op on values; "
                        "default 0")
    p.add_argument("--fsdp-offload-duty", dest="fsdp_offload_duty",
                   type=float,
                   help="fraction of eligible stage carries the "
                        "offload parks on the host "
                        "(HOROVOD_FSDP_OFFLOAD_DUTY, default 1.0): "
                        "earliest stages first — bound the host PCIe "
                        "duty cycle when full offload would not hide "
                        "under compute")
    p.add_argument("--compression-wire-dtype",
                   dest="compression_wire_dtype",
                   choices=["bfloat16", "float16"])
    p.add_argument("--fp16-allreduce", dest="compression_wire_dtype",
                   action="store_const", const="bfloat16",
                   help="bf16-on-the-wire gradient compression (TPU-native "
                        "form of the reference's fp16 allreduce).")
    p.add_argument("--hierarchical-allreduce",
                   dest="hierarchical_allreduce", action="store_true",
                   default=None)
    p.add_argument("--hierarchical-allgather",
                   dest="hierarchical_allgather", action="store_true",
                   default=None)
    p.add_argument("--hierarchical-local-size",
                   dest="hierarchical_local_size", type=int,
                   help="ranks per inner (ICI) domain for hierarchical "
                        "collectives; 0 = auto (local device count)")
    p.add_argument("--stall-check-disable", dest="stall_check_disable",
                   action="store_true", default=None)
    p.add_argument("--stall-warning-time-seconds",
                   dest="stall_warning_time_seconds", type=float)
    p.add_argument("--stall-shutdown-time-seconds",
                   dest="stall_shutdown_time_seconds", type=float)
    p.add_argument("--stall-abort-seconds", dest="stall_abort_s",
                   type=float,
                   help="Negotiation watchdog: a collective making no "
                        "progress for this long raises "
                        "HorovodInternalError so elastic training "
                        "restores and retries (0 = off).")

    # fault tolerance / chaos (docs/faults.md)
    p.add_argument("--fault-spec", dest="fault_spec",
                   help="Fault-injection spec for workers, e.g. "
                        "'http.put:error:0.3:seed=7' (docs/faults.md).")
    p.add_argument("--retry-max-attempts", dest="retry_max_attempts",
                   type=int,
                   help="Control-plane retry attempts (default 5).")
    p.add_argument("--retry-base-delay", dest="retry_base_delay",
                   type=float,
                   help="First control-plane backoff in seconds "
                        "(default 0.1).")
    p.add_argument("--retry-max-delay", dest="retry_max_delay",
                   type=float,
                   help="Control-plane backoff cap in seconds "
                        "(default 2.0).")
    p.add_argument("--vanish-grace", dest="vanish_grace", type=float,
                   help="Seconds a host may drop out of discovery "
                        "before its worker is counted failed "
                        "(default 5).")
    p.add_argument("--spawn-join", dest="spawn_join", type=float,
                   help="Post-round spawn-thread join budget in "
                        "seconds (default 30).")
    p.add_argument("--no-preemption", dest="preemption",
                   action="store_const", const="0", default=None,
                   help="Disable the SIGTERM preemption handler in "
                        "workers (elastic/preemption.py).")
    p.add_argument("--emergency-checkpoint", dest="emergency_checkpoint",
                   help="Rank-0 emergency snapshot path written on "
                        "preemption (SIGTERM).")
    p.add_argument("--replication", dest="replication",
                   action="store_const", const="1", default=None,
                   help="Async peer snapshot replication: every "
                        "state.commit() ships the committed snapshot "
                        "to ring-partner ranks so a respawned worker "
                        "restores from a surviving peer instead of "
                        "stale disk state (docs/recovery.md).")
    p.add_argument("--replication-partners", dest="replication_partners",
                   type=int,
                   help="Ring partners each rank replicates its "
                        "snapshot to (default 1).")
    p.add_argument("--rendezvous-state-dir", dest="rendezvous_state_dir",
                   help="Directory for the rendezvous server's atomic "
                        "on-disk state snapshot; a restarted driver "
                        "pointed at the same directory resumes the "
                        "same job on the same port (docs/recovery.md).")

    # sharded root control plane (docs/control_plane.md)
    p.add_argument("--root-replicas", dest="root_replicas", type=int,
                   help="Shard the root KV tier across N supervised "
                        "replica processes with consistent-hash "
                        "routing, lease/fencing takeover, and "
                        "write-through ring backups; hvdrun spawns, "
                        "backoff-restarts and reaps them. Default 1 = "
                        "today's single root, bit-for-bit "
                        "(docs/control_plane.md).")
    p.add_argument("--root-state-dir", dest="root_state_dir",
                   help="Directory for the root replicas' persisted "
                        "state snapshots (default: a fresh temp dir); "
                        "a supervisor-restarted replica reloads its "
                        "store from here before re-pulling deltas "
                        "from peers.")
    p.add_argument("--root-lease-ttl", dest="root_lease_ttl",
                   type=float,
                   help="Replica lease TTL in seconds (default 3.0): "
                        "a silent replica is fenced and taken over "
                        "after this long.")
    p.add_argument("--root-heartbeat", dest="root_heartbeat",
                   type=float,
                   help="Replica lease heartbeat cadence in seconds "
                        "(default 0.5).")
    p.add_argument("--pod-relays", dest="pod_relays", type=int,
                   help="Spawn N launcher-supervised per-pod relay "
                        "processes (multipod/relay.py) targeting the "
                        "root tier, replacing the operator-run relays "
                        "of docs/multipod.md; crashed relays restart "
                        "under backoff with flap counting.")
    p.add_argument("--prof-every", dest="prof_every", type=int,
                   help="Continuous step profiler: sample every N-th "
                        "step with device tracing and export compute/"
                        "exposed-wire/idle attribution + hvd_mfu "
                        "(0 = off; docs/timeline.md).")
    p.add_argument("--prof-dir", dest="prof_dir",
                   help="Root directory for sampled profiler captures "
                        "(default <tmpdir>/hvd_prof/rank<r>); feed it "
                        "to scripts/trace_merge.py.")
    p.add_argument("--prof-duty-cycle", dest="prof_duty_cycle",
                   type=float,
                   help="Cap on the fraction of wall time the sampled "
                        "profiler may consume (default 0.02).")
    p.add_argument("--flight-recorder", dest="flight_recorder",
                   action="store_const", const="1", default=None,
                   help="Force the control-plane flight recorder on in "
                        "workers (default on; docs/flight.md).")
    p.add_argument("--no-flight-recorder", dest="flight_recorder",
                   action="store_const", const="0",
                   help="Disable the flight recorder (its record sites "
                        "become single predicted branches).")
    p.add_argument("--flight-dir", dest="flight_dir",
                   help="Directory for rank-local flight dumps "
                        "(default <tmpdir>/hvd_flight); dumps also "
                        "ship to the rendezvous server.")
    p.add_argument("--log-level", dest="log_level",
                   choices=["TRACE", "DEBUG", "INFO", "WARNING", "ERROR",
                            "FATAL"])
    p.add_argument("--mesh", dest="mesh",
                   help='Mesh axis spec for workers, e.g. "dp=4,tp=2".')
    p.add_argument(
        "--network-interface", dest="nics",
        help="Comma-separated NICs to bind (recorded in env; XLA/DCN "
             "transport selection is automatic on TPU).",
    )

    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="Training command to run on every slot.")

    args = p.parse_args(argv)

    if args.config_file:
        full_argv = list(argv if argv is not None else sys.argv[1:])
        # only hvdrun's own flags count as explicit — the trainee command
        # captured by REMAINDER may contain identically-named flags
        own_argv = (
            full_argv[: len(full_argv) - len(args.command)]
            if args.command
            else full_argv
        )
        explicit = _explicit_dests(own_argv, p)
        config_parser.apply_config_file(args, args.config_file, explicit)
    return args


def _explicit_dests(argv, parser) -> set:
    """Dests the user set on the command line (beat the config file)."""
    explicit = set()
    for action in parser._actions:
        for opt in action.option_strings:
            if any(a == opt or a.startswith(opt + "=") for a in argv):
                explicit.add(action.dest)
    return explicit


def _reserve_ports(n: int) -> List[int]:
    """n distinct free ports, all reserved before any is handed out —
    the replica-id ↔ port mapping must be fixed before the first child
    spawns (HOROVOD_ROOT_ADDRS is positional)."""
    import socket

    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("0.0.0.0", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _advertise_addr(hosts: List[HostInfo]) -> str:
    """The address workers use to reach launcher-spawned control-plane
    processes: loopback for an all-local job, this host's name
    otherwise."""
    import socket

    names = {h.hostname for h in hosts}
    if names <= {"localhost", "127.0.0.1"}:
        return "127.0.0.1"
    return socket.gethostname()


def _wait_for_roots(roots: str, timeout_s: float = 20.0) -> None:
    """Block until every spawned replica answers /shard_map — workers
    must never race the tier's bind."""
    import urllib.request

    from .http.ring import parse_root_addrs
    from ..utils import retry as _retry

    deadline = _retry.Deadline(timeout_s)
    pending = list(parse_root_addrs(roots))
    while pending and not deadline.expired():
        addr, port = pending[0]
        try:
            with urllib.request.urlopen(
                    f"http://{addr}:{port}/shard_map", timeout=2.0):
                pass
            pending.pop(0)
        except Exception:
            import time as _time
            _time.sleep(0.1)
    if pending:
        raise TimeoutError(
            f"root replicas {pending} not serving within {timeout_s}s")


def _spawn_control_plane(args, env, hosts):
    """Spawn + supervise the control-plane tier hvdrun now owns
    (docs/control_plane.md): N sharded root replicas and per-pod
    relays, restarted under exponential backoff with flap counting
    (runner/supervisor.py), reaped on exit. Returns (supervisor|None,
    env) — env gains HOROVOD_ROOT_ADDRS / relay pointers for workers.
    With --root-replicas 1 and no relays, returns (None, env)
    untouched: today's single-root path, bit-for-bit."""
    n_roots = int(getattr(args, "root_replicas", 0) or 0)
    n_relays = int(getattr(args, "pod_relays", 0) or 0)
    if n_roots <= 1 and n_relays <= 0:
        return None, env
    import tempfile

    from ..core.knobs import Knobs
    from .supervisor import ProcessSupervisor, python_child_argv

    kb = Knobs.from_env()
    sup = ProcessSupervisor(
        base_delay_s=kb.supervisor_base_delay_seconds,
        max_delay_s=kb.supervisor_max_delay_seconds,
        flap_window_s=kb.supervisor_flap_window_seconds,
    )
    env = dict(env)
    addr = _advertise_addr(hosts)
    lease_ttl = (args.root_lease_ttl
                 if getattr(args, "root_lease_ttl", None)
                 else kb.root_lease_ttl_seconds)
    heartbeat = (args.root_heartbeat
                 if getattr(args, "root_heartbeat", None)
                 else kb.root_heartbeat_seconds)
    roots = None
    try:
        if n_roots > 1:
            ports = _reserve_ports(n_roots)
            roots = ",".join(f"{addr}:{p}" for p in ports)
            state_dir = (args.root_state_dir
                         or tempfile.mkdtemp(prefix="hvd_root_"))
            for i in range(n_roots):
                sup.add(
                    f"root.replica.{i}",
                    python_child_argv(
                        "horovod_tpu.runner.http.http_server",
                        "--replica-id", str(i),
                        "--roots", roots,
                        "--state-path",
                        os.path.join(state_dir, f"replica_{i}.pkl"),
                        "--lease-ttl", str(lease_ttl),
                        "--heartbeat-interval", str(heartbeat),
                        "--vnodes", str(kb.root_vnodes),
                    ))
            _wait_for_roots(roots)
            # the fleet-wide root-set contract: index = replica id;
            # http_client shard-routes any call aimed at these
            env["HOROVOD_ROOT_ADDRS"] = roots
        if n_relays > 0:
            relay_roots = roots
            if relay_roots is None:
                # single-root world: relays forward to the published
                # rendezvous address, exactly as operators did by hand
                raddr = env.get("HVD_TPU_RENDEZVOUS_ADDR") or env.get(
                    "HOROVOD_GLOO_RENDEZVOUS_ADDR")
                rport = env.get("HVD_TPU_RENDEZVOUS_PORT") or env.get(
                    "HOROVOD_GLOO_RENDEZVOUS_PORT")
                if not raddr or not rport:
                    raise ValueError(
                        "--pod-relays without --root-replicas needs a "
                        "published rendezvous address in the "
                        "environment")
                relay_roots = f"{raddr}:{rport}"
            rports = _reserve_ports(n_relays)
            for i in range(n_relays):
                sup.add(
                    f"relay.proc.pod{i}",
                    python_child_argv(
                        "horovod_tpu.multipod.relay",
                        "--pod-label", f"pod{i}",
                        "--roots", relay_roots,
                        "--port", str(rports[i]),
                    ))
            env["HOROVOD_RELAY_ADDRS"] = ",".join(
                f"pod{i}={addr}:{rports[i]}" for i in range(n_relays))
            if n_relays == 1:
                # single-pod: point every worker straight at it via the
                # existing relay discovery envs (multipod/relay.py)
                env["HOROVOD_RELAY_ADDR"] = addr
                env["HOROVOD_RELAY_PORT"] = str(rports[0])
    except Exception:
        sup.shutdown()
        raise
    sup.start()
    return sup, env


def _resolve_hosts(args) -> List[HostInfo]:
    if args.hostfile:
        return parse_hosts(parse_host_files(args.hostfile))
    if args.hosts:
        return parse_hosts(args.hosts)
    np = args.np or 1
    return [HostInfo("localhost", np)]


def is_elastic(args) -> bool:
    return bool(args.host_discovery_script or args.min_np or args.max_np)


def _run_static(args) -> int:
    from .exec_run import run_static

    hosts = _resolve_hosts(args)
    if args.np is None:
        args.np = sum(h.slots for h in hosts)
    env = config_parser.env_from_args(args, dict(os.environ))
    supervisor, env = _spawn_control_plane(args, env, hosts)
    try:
        codes = run_static(
            args.command, hosts, args.np, env=env,
            nics=args.nics.split(",") if args.nics else None,
        )
    finally:
        if supervisor is not None:
            supervisor.shutdown()
    # signal-killed workers report negative codes; any nonzero is failure
    failed = [c for c in codes if c != 0]
    return abs(failed[0]) if failed else (0 if codes else 1)


def _run_elastic(args) -> int:
    from .elastic.driver import ElasticDriver
    from .elastic.discovery import HostDiscoveryScript, HostManager
    from .elastic.settings import ElasticSettings

    if not args.host_discovery_script:
        raise ValueError(
            "elastic mode requires --host-discovery-script "
            "(reference launch.py:676)"
        )
    settings = ElasticSettings(
        min_np=args.min_np or args.np or 1,
        max_np=args.max_np,
        timeout_s=args.elastic_timeout or 600.0,
        reset_limit=args.reset_limit or 0,
        cooldown_range=tuple(args.cooldown_range)
        if args.cooldown_range else None,
        # None falls back to the HOROVOD_ELASTIC_* env knobs
        host_vanish_grace_s=args.vanish_grace,
        spawn_join_timeout_s=args.spawn_join,
    )
    discovery = HostDiscoveryScript(
        args.host_discovery_script, args.slots
    )
    env = config_parser.env_from_args(args, dict(os.environ))
    supervisor, env = _spawn_control_plane(
        args, env, _resolve_hosts(args))
    driver = ElasticDriver(
        HostManager(discovery, settings.cooldown_range),
        settings,
        command=args.command,
        env=env,
        nics=args.nics.split(",") if args.nics else None,
        rendezvous_state_dir=args.rendezvous_state_dir or None,
        control_supervisor=supervisor,
    )
    try:
        return driver.run()
    finally:
        if supervisor is not None:
            supervisor.shutdown()  # idempotent with driver.stop()


def _check_build() -> int:
    """Availability table (reference launch.py:110 check_build). On TPU
    the controller is the XLA coordination service and the tensor ops
    are XLA collectives — the table reports what this install can use."""
    import importlib.util

    from .. import __version__

    def have(mod: str) -> str:
        return "X" if importlib.util.find_spec(mod) is not None else " "

    def native() -> str:
        try:
            from .._native import build

            build()
            return "X"
        except Exception:
            return " "

    print(f"horovod_tpu v{__version__}:\n")
    print("Available Frameworks:")
    print(f"    [{have('jax')}] JAX")
    print(f"    [{have('flax')}] Flax")
    print(f"    [{have('torch')}] PyTorch")
    print("\nAvailable Controllers:")
    print(f"    [{have('jax')}] XLA coordination service (jax.distributed)")
    print(f"    [{native()}] Native eager control plane (libhvd_tpu_core)")
    print("\nAvailable Tensor Operations:")
    print(f"    [{have('jax')}] XLA collectives (ICI/DCN)")
    print(f"    [{native()}] Negotiated eager (XlaExecutor)")
    print("\nAvailable Integrations:")
    print(f"    [{have('pyspark')}] Spark")
    print(f"    [{have('ray')}] Ray")
    return 0


def run_commandline(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    if args.version:
        from .. import __version__

        print(__version__)
        return 0
    if args.check_build:
        return _check_build()
    if not args.command:
        print("hvdrun: no command given", file=sys.stderr)
        return 2
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if is_elastic(args):
        return _run_elastic(args)
    return _run_static(args)


def main() -> None:
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
