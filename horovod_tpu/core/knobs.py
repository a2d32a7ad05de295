"""Environment-variable configuration knobs.

The reference parses ~50 `HOROVOD_*` env knobs in C++
(/root/reference/horovod/common/common.h:115-148,
/root/reference/horovod/common/utils/env_parser.cc). This module is the
TPU-native equivalent: one typed registry, parsed once at `init()` and
re-readable at runtime. Knobs keep the `HOROVOD_` prefix so reference users'
launch scripts keep working; each knob also accepts an `HVD_TPU_` prefix
which takes priority.

Knobs that only make sense for CUDA stream machinery (e.g.
HOROVOD_NUM_NCCL_STREAMS) are intentionally absent; XLA owns scheduling on
TPU. Knobs controlling fusion/cache/cycle survive because the eager
(non-jit) path still uses a background-negotiation runtime, and the jit path
uses the fusion threshold for gradient bucketing.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env(name: str, default: Optional[str] = None) -> Optional[str]:
    """HVD_TPU_X beats HOROVOD_X beats default."""
    for prefix in ("HVD_TPU_", "HOROVOD_"):
        v = os.environ.get(prefix + name)
        if v is not None:
            return v
    return default


def _env_int(name: str, default: int) -> int:
    v = _env(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    v = _env(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        return default


def _env_bool(name: str, default: bool) -> bool:
    v = _env(name)
    if v is None or v == "":
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass
class Knobs:
    """Typed snapshot of all runtime knobs.

    Defaults mirror the reference where the concept carries over
    (fusion 128 MB: operations.cc:507; cycle time 1 ms: operations.cc:515;
    cache capacity 1024: global_state.h:89; stall warning 60 s:
    stall_inspector.h:75-83).
    """

    # --- fusion / bucketing (controller.cc:830 FuseResponses analog) ---
    fusion_threshold_bytes: int = 128 * 1024 * 1024
    batch_d2d_memcopies: bool = True
    # chain bucket k on bucket k-1's result (reference controller-order
    # execution) so XLA's combiner can't merge buckets into one
    # all-grads-gated all-reduce — the property that lets collectives
    # overlap backward compute (optim/distributed.py, overlap tests)
    ordered_buckets: bool = True
    # bucket the gradient pytree in backward-availability order (last
    # layer first, embeddings last — ops/fusion.py), so chained bucket
    # 0 holds the gradients backward produces FIRST. Measured on the
    # BERT-L train step at v5e:2x4, 128MB buckets: the first all-reduce
    # depends on only ~9% of backward (overlappable_frac 0.91,
    # OVERLAP_r05.json) vs ~62% with forward traversal order. The
    # compile-time mirror of the reference negotiating gradients in
    # hook/backward order (torch/optimizer.py grad hooks).
    bucket_backward_order: bool = True

    # --- background/eager runtime (operations.cc:515) ---
    cycle_time_ms: float = 1.0
    cache_capacity: int = 1024
    cache_enabled: bool = True

    # --- stall inspector (stall_inspector.h:75-83) ---
    stall_check_enabled: bool = True
    stall_warning_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0  # 0 = never shut down
    # negotiation watchdog (ops/eager_runtime.py): a collective wait
    # making no progress for this long raises HorovodInternalError so
    # the elastic run() wrapper restores-and-retries instead of hanging
    # forever. 0 = disabled (waits are bounded only by their callers).
    stall_abort_time_seconds: float = 0.0

    # --- timeline (timeline.h, operations.cc:1048) ---
    timeline_filename: str = ""
    timeline_mark_cycles: bool = False

    # --- autotune (parameter_manager.h:42; ops/autotune.py) ---
    autotune: bool = False
    autotune_bayes: bool = False  # GP+EI search (optim/bayesian_optimization.cc)
    autotune_log: str = ""
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    # persistent warm-start cache for the closed-loop OnlineTuner
    # (docs/autotune.md): winners persist per (model fingerprint,
    # topology) at this path; later runs and serving replicas pin the
    # cached configuration with zero tuning compiles. "" = no cache.
    autotune_cache: str = ""
    # score trials by measured hvd_mfu when the continuous profiler is
    # live (utils/prof.py set_step_flops); the step-time p50 via
    # metrics.StepStats is always recorded and is the fallback score
    autotune_mfu: bool = True
    # opt IN to the numerics-changing dimensions (wire dtype/block,
    # eager fast-path warmup K): int8 on the wire is lossy, so the
    # tuner never sweeps or warm-starts these without explicit consent
    autotune_wire: bool = False

    # --- numerics / wire format ---
    # fp16 ("compression") on the wire: reference torch/compression.py:20.
    # On TPU the native wire type is bfloat16.
    compression_wire_dtype: str = ""  # "", "bfloat16", "float16"
    # Compressed collective data plane (optim/compression.py,
    # docs/compression.md): "none" (bitwise-identical to the
    # uncompressed plane), "fp16"/"bf16" (cast-on-the-wire), "int8"
    # (block-quantized EQuARX-style quantize→reduce→requantize with
    # error feedback), "int8-raw" (int8 without error feedback — A/B
    # and debugging only). Reaches the gradient reduction paths
    # (optim/distributed.py, optim/zero.py), the hierarchical DCN
    # outer leg (ops/hierarchical.py), and the eager executors
    # (ops/eager_runtime.py).
    compression: str = "none"
    # per-block quantization granularity (elements per int8 scale)
    compression_block: int = 256

    # --- backward-interleaved collective scheduler (ops/overlap.py) ---
    # "off" (default): today's monolithic backward — the whole grad
    # pytree exists before the bucket chain issues, and the scheduled
    # overlap window is whatever XLA's memory-minimizing scheduler
    # grants (0.26 on BERT-L, 0.016 on the ZeRO path, OVERLAP_r05.json).
    # "stage": segment the backward into fusion-bucket-aligned stages
    # and pin each bucket's collective BEFORE the next segment's compute
    # via optimization_barrier on the inter-segment cotangent, so the
    # schedule is forced to interleave (docs/overlap.md). "double":
    # additionally defer the optimizer's consumption of early buckets
    # until the last segment retires (double-buffered grads). Off must
    # reproduce the unscheduled trace bit-for-bit (it takes the
    # identical code path).
    overlap_schedule: str = "off"

    # --- fully-sharded parameters (optim/fsdp.py, docs/fsdp.md) ---
    # Routing gate for FullyShardedOptimizer train steps: on (default),
    # parallel/train.make_lm_train_step routes an fsdp-kind optimizer
    # through the prefetch-interleaved FSDP step; off, such a step
    # raises instead of silently taking a wrong path. The knob never
    # perturbs non-FSDP configurations — with no FullyShardedOptimizer
    # in play every existing path lowers bit-for-bit the same HLO
    # regardless of its value (scripts/fsdp_check.py hashes this).
    fsdp: bool = True
    # Forward all-gather look-ahead in stages: bucket k+1's parameter
    # gather issues at segment k's boundary (pinned behind the
    # activation entering it) so it overlaps segment k's compute. 0
    # serializes each gather at its need boundary (debugging).
    fsdp_prefetch: int = 1
    # Backward re-gather (recompute-through-the-collective) policy for
    # the FSDP staged step (docs/fsdp.md): on (default), the forward
    # runs primal-only and the backward re-issues each bucket's
    # all-gather at its backward-first-use boundary — no vjp residual
    # holds gathered weights across the forward→backward span, so
    # within-step peak param liveness stays ≤ sharded + one bucket
    # working set. Off takes the saved-gather path verbatim (today's
    # lowering bit-for-bit; scripts/fsdp_check.py hashes this). Values
    # are bitwise-identical either way, plain and int8+EF wires alike.
    fsdp_regather: bool = True
    # Host-RAM offload of stage-boundary activations for the regather
    # step's long-stage tail: carries move to host memory at each
    # stage boundary on forward and prefetch back one stage ahead on
    # backward. Regather mode only; a backend that cannot place them
    # in host memory fails the compile.
    fsdp_offload: bool = False
    # Bounded offload duty: the fraction of eligible stage-boundary
    # carries actually offloaded, earliest stages first (they wait
    # longest for backward), capping host-link traffic per step the
    # way the replicator's duty cycle caps host CPU (docs/fsdp.md).
    fsdp_offload_duty: float = 1.0

    # --- hierarchy (operations.cc:551-565) ---
    # On TPU: "hierarchical" = reduce-scatter over ICI within a slice, then
    # all-reduce across slices over DCN, then all-gather over ICI
    # (ops/hierarchical.py). local_size: ranks per inner (ICI) domain when
    # the world is one flat axis; 0 = auto (process-local device count).
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    hierarchical_local_size: int = 0

    # --- elastic ---
    elastic_timeout_seconds: float = 600.0
    reset_limit: int = 0  # 0 = unlimited
    # (the driver-side HOROVOD_ELASTIC_VANISH_GRACE / _SPAWN_JOIN
    # windows live on ElasticSettings, not here — the elastic driver
    # runs in the launcher process, which never builds a Knobs)
    # SIGTERM/SIGINT preemption handler (elastic/preemption.py):
    # commit state + emergency checkpoint + exit with the
    # "host going away" code the driver does not blacklist
    preemption_enabled: bool = True
    emergency_checkpoint: str = ""  # rank-0 emergency snapshot path
    # async peer snapshot replication (elastic/replication.py): every
    # State.commit() ships the committed snapshot — chunked,
    # checksummed, epoch-stamped — to ring-partner ranks' host memory,
    # strictly off the training critical path. Off by default: the
    # disabled on_commit hook is a single predicted branch.
    replication_enabled: bool = False
    replication_partners: int = 1      # ring partners per rank
    replication_chunk_bytes: int = 1 << 20
    # bounded replication duty cycle: after a ship taking T seconds
    # the replicator idles T*(1/d - 1), so replication consumes at
    # most ~d of host CPU even with zero spare cores (the bench's 3%
    # commit+step overhead gate); fresher commits coalesce meanwhile
    replication_duty_cycle: float = 0.02
    # layered recovery ladder (docs/recovery.md): on restart, restore
    # from the freshest verified source (peer replica → emergency
    # snapshot → orbax) with checksum verification at each rung
    recovery_ladder: bool = True

    # --- fault injection (utils/faults.py) ---
    # canonical env HOROVOD_TPU_FAULT_SPEC; empty = disabled no-op
    fault_spec: str = ""

    # --- control-plane retry (utils/retry.py default policy) ---
    retry_max_attempts: int = 5
    retry_base_delay_seconds: float = 0.1
    retry_max_delay_seconds: float = 2.0
    # "full" (default): AWS-style full jitter — a fleet reconnecting
    # after a rendezvous failover spreads uniformly over the backoff
    # window instead of retrying in ±25% lockstep waves. "bounded"
    # restores the historical symmetric band.
    retry_jitter: str = "full"
    # shared cap on TOTAL elapsed retry time per call, applied even to
    # deadline-less call sites; <=0 disables
    retry_max_elapsed_seconds: float = 60.0

    # --- multi-pod federation (multipod/, docs/multipod.md) ---
    # pod count; 0/1 = single pod (no federation — every path below is
    # knob-free and identical to the pre-multipod world)
    multipod_pods: int = 0
    # cross-pod sync discipline: "sync" (every step spans the world) or
    # "localK" (e.g. "local8": K pod-local steps between cross-pod
    # parameter averages over DCN). K<=1 normalizes to sync, which is
    # what makes the K=1 parity guarantee bitwise (multipod/localsgd.py)
    multipod_sync: str = "sync"
    # outer-loop step size / momentum on the averaged update (SlowMo
    # family); defaults = plain parameter averaging
    multipod_outer_lr: float = 1.0
    multipod_outer_momentum: float = 0.0
    # worst-case DCN hops between pods (scaling-projection input)
    multipod_dcn_hops: int = 1

    # --- sharded root control plane (docs/control_plane.md) ---
    # replica count for the root KV tier; 0/1 = today's single root,
    # bit-for-bit (no ring, no leases, no extra processes)
    root_replicas: int = 1
    # the configured root set, "addr:port,addr:port,..." in replica-id
    # order (HOROVOD_ROOT_ADDRS — the launcher exports it fleet-wide;
    # setting it by hand points workers at an externally-run tier)
    root_addrs: str = ""
    # lease TTL: how long a replica's silence lasts before its ring
    # successor fences it and takes over. Availability/false-positive
    # dial: shorter = faster takeover, more sensitive to GC pauses
    root_lease_ttl_seconds: float = 3.0
    # lease heartbeat cadence; keep several beats inside one TTL so a
    # single dropped beat never looks like a death
    root_heartbeat_seconds: float = 0.5
    # virtual nodes per replica on the hash ring (load-spread quality
    # vs membership-record size)
    root_vnodes: int = 64
    # supervised child restart ladder (runner/supervisor.py):
    # base × multiplier^n capped at max; an exit within the flap
    # window counts a flap and grows the ladder, a longer run resets it
    supervisor_base_delay_seconds: float = 0.5
    supervisor_max_delay_seconds: float = 10.0
    supervisor_flap_window_seconds: float = 5.0

    # --- process sets ---
    dynamic_process_sets: bool = False

    # --- native eager runtime (HVD_TPU_NATIVE=1) ---
    # Routes top-level (non-jit) collectives through the C++ negotiation
    # runtime + XLA executor — the reference's background-loop
    # architecture (operations.cc:401). Off by default: single-controller
    # eager semantics don't need negotiation.
    native_eager: bool = False
    # Steady-state plan cache (HOROVOD_EAGER_FAST_PATH): after
    # eager_fast_path_warmup identical enqueue sequences the runtime
    # freezes the negotiated fusion buckets + controller order into an
    # ExecutionPlan and subsequent steps skip the coordinator round
    # trip entirely; any sequence deviation falls back to full
    # negotiation (docs/eager.md). 0 reproduces pre-cache behavior.
    eager_fast_path: bool = True
    eager_fast_path_warmup: int = 3

    # --- metrics / telemetry (utils/metrics.py) ---
    # live counters/gauges/histograms + /metrics endpoint; off by default
    # so the disabled fast path is the only cost
    metrics_enabled: bool = False
    # JSONL per-step log (canonical env name HOROVOD_TPU_METRICS_FILE;
    # HVD_TPU_METRICS_FILE / HOROVOD_METRICS_FILE also accepted)
    metrics_file: str = ""
    # standalone per-worker GET /metrics port; 0 = don't serve (the
    # rendezvous KV server mounts /metrics regardless)
    metrics_port: int = 0
    # workers push their exposition to the rendezvous KV at most once
    # per this interval; the rendezvous /metrics merges the pushes into
    # one rank-labeled cluster scrape (docs/metrics.md). 0 = no push.
    metrics_push_interval_s: float = 5.0

    # --- continuous step profiler (utils/prof.py, docs/timeline.md) ---
    # sample every N-th hvd.metrics.step() with jax.profiler device
    # tracing, parse the xplane off-thread (utils/xplane.py) and export
    # compute/exposed-wire/idle attribution + measured overlap gauges.
    # 0 = off (the per-step hook is a single predicted branch).
    prof_every: int = 0
    # sample-capture root; "" = <tmpdir>/hvd_prof/rank<r>
    prof_dir: str = ""
    # duty-cycle bound on measured profiling overhead (capture + parse
    # CPU): after a sample costing T the next waits T*(1/d - 1), the
    # PR-6 replicator's model
    prof_duty_cycle: float = 0.02

    # --- flight recorder (utils/flight.py, docs/flight.md) ---
    # bounded ring of control-plane events, dumped on stall abort /
    # executor error / SIGTERM / SIGUSR2 / crash and shipped to the
    # driver via PUT /flight/<rank>. ON by default (a black box that
    # is off when the plane crashes is no black box); =0 leaves a
    # single predicted branch per record site.
    flight_recorder: bool = True
    flight_dir: str = ""  # dump directory; "" = <tmpdir>/hvd_flight
    flight_capacity: int = 4096  # events kept in the ring

    # --- fleet-health monitor (horovod_tpu/health, docs/health.md) ---
    # live straggler/anomaly detection + SLO burn-rate alerting over
    # the StepStats/serving streams; off by default (the metrics-side
    # observer slot stays None — zero step-path cost)
    health_enabled: bool = False
    # rank-summary publish cadence to the fleet evaluator (the metrics
    # push / pod-relay route); also the serving rule-evaluation tick
    health_interval_s: float = 2.0
    # detector sliding-window size (steps) and warmup before envelopes
    # may fire
    health_window: int = 32
    health_min_steps: int = 8
    # step-time envelope factor vs the rolling median / the autotuner's
    # persisted per-(model, topology) baseline
    health_step_time_factor: float = 1.75
    # declarative rule spec (docs/health.md grammar); "" = DEFAULT_RULES
    health_rules: str = ""
    # JSONL incident log (fire/clear transitions); "" = step-log events
    # only (metrics_file out-of-band lines)
    health_incident_file: str = ""
    # anomaly-triggered forensics: flight dump + forced prof sample on
    # a firing rule
    health_capture: bool = True

    # --- logging ---
    log_level: str = "WARNING"
    log_hide_timestamp: bool = False
    # rank-prefixed stderr lines ("[rank N] ..."), resolved from the
    # launcher env without importing jax — makes interleaved
    # multi-rank stderr attributable (utils/logging.py)
    log_rank: bool = False

    # --- mesh / topology overrides ---
    # Comma-separated axis spec, e.g. "dp=8" or "dp=4,tp=2"; empty = one
    # flat data-parallel axis over all devices.
    mesh_spec: str = ""

    # --- inference serving (serving/) ---
    # padded batch-size buckets the engine AOT-compiles; requests are
    # coalesced into the smallest covering bucket (docs/serving.md)
    serving_buckets: str = "1,4,16,64"
    # dynamic-batching window: how long the batcher holds the first
    # request of a batch open for co-arrivals
    serving_max_wait_ms: float = 5.0
    # bounded admission queue (pending examples); beyond it submit
    # rejects instead of building unbounded latency
    serving_queue_limit: int = 256
    # default per-request deadline (queue wait + execution)
    serving_request_timeout_seconds: float = 30.0

    # --- autoregressive generation (serving/decode.py, scheduler.py,
    # docs/generation.md) ---
    # KV cache storage: fp32 | bf16 | int8 (int8 = block-quantized
    # with optim/compression.py's primitives, quantize-once-on-write)
    serving_kv_dtype: str = "fp32"
    # int8 scale granularity along head_dim; 0 = one scale per row
    serving_kv_block: int = 0
    # (slots x max_len) decode bucket ladder; the engine runs the
    # largest bucket and AOT-compiles one decode program per pair
    serving_decode_buckets: str = "4x128"
    # prompt-length prefill ladder; "" = powers of two up to max_len
    serving_prefill_buckets: str = ""
    # default generation cap when a request names no max_new_tokens
    serving_decode_max_new: int = 64
    # scheduler stats cadence: one "decode" StepStats JSONL event per
    # this many iterations (0 = no event lines)
    serving_decode_stats_every: int = 50
    # --- replica autoscaler (serving/replica_set.py ReplicaAutoscaler) ---
    serving_autoscale_interval_s: float = 2.0
    serving_autoscale_hi_occupancy: float = 0.85
    serving_autoscale_lo_occupancy: float = 0.25
    serving_autoscale_queue_wait_s: float = 0.5
    serving_autoscale_min_replicas: int = 1
    serving_autoscale_max_replicas: int = 4
    # consecutive over/under-threshold polls before acting
    serving_autoscale_sustain: int = 2
    # seconds after an action before the next is considered
    serving_autoscale_cooldown_s: float = 10.0

    @staticmethod
    def from_env() -> "Knobs":
        return Knobs(
            fusion_threshold_bytes=_env_int(
                "FUSION_THRESHOLD", 128 * 1024 * 1024
            ),
            batch_d2d_memcopies=_env_bool("BATCH_D2D_MEMCOPIES", True),
            ordered_buckets=_env_bool("ORDERED_BUCKETS", True),
            bucket_backward_order=_env_bool("BUCKET_BACKWARD_ORDER", True),
            cycle_time_ms=_env_float("CYCLE_TIME", 1.0),
            cache_capacity=_env_int("CACHE_CAPACITY", 1024),
            cache_enabled=_env_int("CACHE_CAPACITY", 1024) > 0,
            stall_check_enabled=not _env_bool("STALL_CHECK_DISABLE", False),
            stall_warning_time_seconds=_env_float(
                "STALL_CHECK_TIME_SECONDS", 60.0
            ),
            stall_shutdown_time_seconds=_env_float(
                "STALL_SHUTDOWN_TIME_SECONDS", 0.0
            ),
            stall_abort_time_seconds=_env_float("STALL_ABORT_S", 0.0),
            timeline_filename=_env("TIMELINE", "") or "",
            timeline_mark_cycles=_env_bool("TIMELINE_MARK_CYCLES", False),
            autotune=_env_bool("AUTOTUNE", False),
            autotune_bayes=_env_bool("AUTOTUNE_BAYES", False),
            autotune_log=_env("AUTOTUNE_LOG", "") or "",
            autotune_warmup_samples=_env_int("AUTOTUNE_WARMUP_SAMPLES", 3),
            autotune_steps_per_sample=_env_int(
                "AUTOTUNE_STEPS_PER_SAMPLE", 10
            ),
            autotune_cache=_env("AUTOTUNE_CACHE", "") or "",
            autotune_mfu=_env_bool("AUTOTUNE_MFU", True),
            autotune_wire=_env_bool("AUTOTUNE_WIRE", False),
            compression_wire_dtype=_env("COMPRESSION_WIRE_DTYPE", "") or "",
            compression=_env("COMPRESSION", "") or "none",
            compression_block=_env_int("COMPRESSION_BLOCK", 256),
            overlap_schedule=_env("OVERLAP_SCHEDULE", "") or "off",
            fsdp=_env_bool("FSDP", True),
            fsdp_prefetch=_env_int("FSDP_PREFETCH", 1),
            fsdp_regather=_env_bool("FSDP_REGATHER", True),
            fsdp_offload=_env_bool("FSDP_OFFLOAD", False),
            fsdp_offload_duty=_env_float("FSDP_OFFLOAD_DUTY", 1.0),
            hierarchical_allreduce=_env_bool("HIERARCHICAL_ALLREDUCE", False),
            hierarchical_allgather=_env_bool("HIERARCHICAL_ALLGATHER", False),
            hierarchical_local_size=_env_int("HIERARCHICAL_LOCAL_SIZE", 0),
            elastic_timeout_seconds=_env_float("ELASTIC_TIMEOUT", 600.0),
            reset_limit=_env_int("RESET_LIMIT", 0),
            preemption_enabled=_env_bool("PREEMPTION", True),
            emergency_checkpoint=_env("EMERGENCY_CHECKPOINT", "") or "",
            replication_enabled=_env_bool("REPLICATION", False),
            replication_partners=_env_int("REPLICATION_PARTNERS", 1),
            replication_chunk_bytes=_env_int(
                "REPLICATION_CHUNK_BYTES", 1 << 20
            ),
            replication_duty_cycle=_env_float(
                "REPLICATION_DUTY_CYCLE", 0.02
            ),
            recovery_ladder=_env_bool("RECOVERY_LADDER", True),
            # canonical name first so it wins when both are set
            fault_spec=(
                os.environ.get("HOROVOD_TPU_FAULT_SPEC", "")
                or _env("FAULT_SPEC")
                or ""
            ),
            retry_max_attempts=_env_int("RETRY_MAX_ATTEMPTS", 5),
            retry_base_delay_seconds=_env_float("RETRY_BASE_DELAY", 0.1),
            retry_max_delay_seconds=_env_float("RETRY_MAX_DELAY", 2.0),
            retry_jitter=_env("RETRY_JITTER", "full") or "full",
            retry_max_elapsed_seconds=_env_float(
                "RETRY_MAX_ELAPSED", 60.0
            ),
            multipod_pods=_env_int("MULTIPOD_PODS", 0),
            multipod_sync=_env("MULTIPOD_SYNC", "") or "sync",
            multipod_outer_lr=_env_float("MULTIPOD_OUTER_LR", 1.0),
            multipod_outer_momentum=_env_float(
                "MULTIPOD_OUTER_MOMENTUM", 0.0
            ),
            multipod_dcn_hops=_env_int("MULTIPOD_DCN_HOPS", 1),
            root_replicas=_env_int("ROOT_REPLICAS", 1),
            root_addrs=_env("ROOT_ADDRS", "") or "",
            root_lease_ttl_seconds=_env_float("ROOT_LEASE_TTL", 3.0),
            root_heartbeat_seconds=_env_float("ROOT_HEARTBEAT", 0.5),
            root_vnodes=_env_int("ROOT_VNODES", 64),
            supervisor_base_delay_seconds=_env_float(
                "SUPERVISOR_BASE_DELAY", 0.5),
            supervisor_max_delay_seconds=_env_float(
                "SUPERVISOR_MAX_DELAY", 10.0),
            supervisor_flap_window_seconds=_env_float(
                "SUPERVISOR_FLAP_WINDOW", 5.0),
            dynamic_process_sets=_env_bool("DYNAMIC_PROCESS_SETS", False),
            native_eager=_env_bool("NATIVE", False),
            eager_fast_path=_env_bool("EAGER_FAST_PATH", True),
            eager_fast_path_warmup=_env_int("EAGER_FAST_PATH_WARMUP", 3),
            metrics_enabled=_env_bool("METRICS", False),
            # canonical name first so it wins when both are set
            metrics_file=(
                os.environ.get("HOROVOD_TPU_METRICS_FILE", "")
                or _env("METRICS_FILE")
                or ""
            ),
            metrics_port=_env_int("METRICS_PORT", 0),
            metrics_push_interval_s=_env_float(
                "METRICS_PUSH_INTERVAL_S", 5.0
            ),
            prof_every=_env_int("PROF_EVERY", 0),
            prof_dir=_env("PROF_DIR", "") or "",
            prof_duty_cycle=_env_float("PROF_DUTY_CYCLE", 0.02),
            flight_recorder=_env_bool("FLIGHT_RECORDER", True),
            flight_dir=_env("FLIGHT_DIR", "") or "",
            flight_capacity=_env_int("FLIGHT_CAPACITY", 4096),
            health_enabled=_env_bool("HEALTH", False),
            health_interval_s=_env_float("HEALTH_INTERVAL_S", 2.0),
            health_window=_env_int("HEALTH_WINDOW", 32),
            health_min_steps=_env_int("HEALTH_MIN_STEPS", 8),
            health_step_time_factor=_env_float(
                "HEALTH_STEP_TIME_FACTOR", 1.75
            ),
            health_rules=_env("HEALTH_RULES", "") or "",
            health_incident_file=_env("HEALTH_INCIDENT_FILE", "") or "",
            health_capture=_env_bool("HEALTH_CAPTURE", True),
            log_level=_env("LOG_LEVEL", "WARNING") or "WARNING",
            log_hide_timestamp=_env_bool("LOG_HIDE_TIME", False),
            log_rank=_env_bool("LOG_RANK", False),
            mesh_spec=_env("MESH", "") or "",
            serving_buckets=_env("SERVING_BUCKETS", "1,4,16,64")
            or "1,4,16,64",
            serving_max_wait_ms=_env_float("SERVING_MAX_WAIT_MS", 5.0),
            serving_queue_limit=_env_int("SERVING_QUEUE_LIMIT", 256),
            serving_request_timeout_seconds=_env_float(
                "SERVING_REQUEST_TIMEOUT", 30.0
            ),
            serving_kv_dtype=_env("SERVING_KV_DTYPE", "fp32") or "fp32",
            serving_kv_block=_env_int("SERVING_KV_BLOCK", 0),
            serving_decode_buckets=_env(
                "SERVING_DECODE_BUCKETS", "4x128") or "4x128",
            serving_prefill_buckets=_env(
                "SERVING_PREFILL_BUCKETS", "") or "",
            serving_decode_max_new=_env_int("SERVING_DECODE_MAX_NEW", 64),
            serving_decode_stats_every=_env_int(
                "SERVING_DECODE_STATS_EVERY", 50
            ),
            serving_autoscale_interval_s=_env_float(
                "SERVING_AUTOSCALE_INTERVAL_S", 2.0
            ),
            serving_autoscale_hi_occupancy=_env_float(
                "SERVING_AUTOSCALE_HI_OCCUPANCY", 0.85
            ),
            serving_autoscale_lo_occupancy=_env_float(
                "SERVING_AUTOSCALE_LO_OCCUPANCY", 0.25
            ),
            serving_autoscale_queue_wait_s=_env_float(
                "SERVING_AUTOSCALE_QUEUE_WAIT_S", 0.5
            ),
            serving_autoscale_min_replicas=_env_int(
                "SERVING_AUTOSCALE_MIN_REPLICAS", 1
            ),
            serving_autoscale_max_replicas=_env_int(
                "SERVING_AUTOSCALE_MAX_REPLICAS", 4
            ),
            serving_autoscale_sustain=_env_int(
                "SERVING_AUTOSCALE_SUSTAIN", 2
            ),
            serving_autoscale_cooldown_s=_env_float(
                "SERVING_AUTOSCALE_COOLDOWN_S", 10.0
            ),
        )
