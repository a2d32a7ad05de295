"""Eager multi-controller runtime: negotiation-ordered collective execution.

Reference: the background-loop architecture of
/root/reference/horovod/common/operations.cc:401 (BackgroundThreadLoop →
ComputeResponseList → PerformOperation) seen from Python. The native
control plane (horovod_tpu/_native: TCP controller, response cache, fusion
planning, stall inspector) decides *which tensors are globally ready, in
what fused order*; this module owns the data plane — it pulls execution
batches and runs them.

Where the reference hands fused buffers to NCCL, the TPU data plane is a
pluggable executor:

* `LoopbackExecutor` — single-process worlds and tests: applies the
  collective semantics locally (sum×n for allreduce of replicated input,
  etc.) so the full enqueue→negotiate→fuse→execute→complete pipeline is
  exercised without a second accelerator.
* `XlaExecutor` — multi-controller worlds: builds one jit-compiled
  collective program per (op, dtype, world) over the *global* mesh and
  feeds it the process-local shards
  (`jax.make_array_from_single_device_arrays`). All processes execute the
  same batch order (the controller guarantees it), which is exactly the
  consistency XLA multi-controller execution requires.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core.exceptions import HorovodInternalError
from ..utils import faults as _faults
from ..utils import flight as _flight
from ..utils import metrics as _metrics
from .._native import (
    BATCHED,
    DONE,
    DTYPE_TO_NUMPY,
    FAILED,
    OP_ALLGATHER,
    OP_ALLREDUCE,
    OP_ALLTOALL,
    OP_BARRIER,
    OP_BROADCAST,
    OP_JOIN,
    OP_REDUCESCATTER,
    ExecutionBatch,
    NativeRuntime,
)

_REDUCE_AVERAGE = 0
_REDUCE_SUM = 1
_REDUCE_ADASUM = 2
_REDUCE_MIN = 3
_REDUCE_MAX = 4
_REDUCE_PRODUCT = 5

# op id -> (negotiation activity, execution activity) — the reference's
# per-tensor phase names (common.h:79-113, timeline.cc)
_OP_ACTIVITIES = {
    OP_ALLREDUCE: ("NEGOTIATE_ALLREDUCE", "ALLREDUCE"),
    OP_ALLGATHER: ("NEGOTIATE_ALLGATHER", "ALLGATHER"),
    OP_BROADCAST: ("NEGOTIATE_BROADCAST", "BROADCAST"),
    OP_ALLTOALL: ("NEGOTIATE_ALLTOALL", "ALLTOALL"),
    OP_REDUCESCATTER: ("NEGOTIATE_REDUCESCATTER", "REDUCESCATTER"),
}

# op id -> metric label (utils/metrics.py batch-execution series)
_OP_METRIC_NAMES = {
    OP_ALLREDUCE: "allreduce",
    OP_ALLGATHER: "allgather",
    OP_BROADCAST: "broadcast",
    OP_ALLTOALL: "alltoall",
    OP_REDUCESCATTER: "reducescatter",
}

# ops a frozen ExecutionPlan may replay without renegotiating: every
# field the executor needs (shapes, splits matrix, per-member dims,
# process-set membership) was captured from the negotiated batch and is
# invariant while the enqueue signatures stay invariant
_PLAN_OPS = frozenset(_OP_METRIC_NAMES)


class _PlanEntry:
    """One tensor slot of a frozen plan: the enqueue signature that must
    repeat for the slot to stay valid, plus the raw enqueue kwargs needed
    to replay the tensor through full negotiation on plan invalidation."""

    __slots__ = ("sig", "kwargs")

    def __init__(self, sig: tuple, kwargs: dict):
        self.sig = sig
        self.kwargs = kwargs


class ExecutionPlan:
    """A frozen steady-state step: the fusion buckets and controller
    ordering one negotiation round produced, replayable without the
    coordinator.

    Horovod's response cache (Sergeev & Del Balso 2018) skips re-sending
    tensor *metadata* for repeated sequences but still pays a wire round
    per cycle for bit-vector agreement; training steps are cyclic, so
    once K identical enqueue sequences have negotiated identically we can
    cache the entire *plan* — pre-sized fusion buckets in the
    controller's order — and skip the round-trip outright. Batches were
    captured from negotiated responses, so they are identical on every
    rank even when ranks enqueued in different orders; replaying them in
    plan order keeps the cross-process XLA program order consistent,
    which is the only consistency the data plane ever needed from the
    controller.

    ``wire_key`` captures the compressed-wire dtype the executor held at
    freeze time (optim/compression.py WireSpec.key, or None for the
    uncompressed plane): the same executor serves negotiated and
    bypassed steps, so a fast-path step is bitwise-identical to a
    negotiated step under the same compressor — and set_wire() flushes
    any plan frozen under a different wire."""

    def __init__(self, batches: List[ExecutionBatch],
                 entries: Dict[str, _PlanEntry], wire_key=None):
        self.batches = batches
        self.entries = entries
        self.names = frozenset(entries)
        self.total_bytes = sum(int(b.total_bytes) for b in batches)
        self.wire_key = wire_key


def _is_jax_array(x) -> bool:
    """Device-resident jax array? (kept on device end-to-end through
    the eager pipeline — see enqueue/_materialize)."""
    try:
        import jax

        return isinstance(x, jax.Array)
    except Exception:
        return False


def _timeline():
    """The active host-side timeline, or None (utils/timeline.py)."""
    from ..utils.timeline import active_timeline

    return active_timeline()


_RESIDUAL_EVICTION_WARNED = [False]


def _warn_residual_eviction_once() -> None:
    """The executor's bounded error-feedback store cycled an entry out:
    the evicted bucket restarts from a zero residual, degrading its
    wire toward int8-raw (bias accumulates). One loud line beats a
    silent numerics change."""
    if _RESIDUAL_EVICTION_WARNED[0]:
        return
    _RESIDUAL_EVICTION_WARNED[0] = True
    from ..utils.logging import get_logger

    get_logger().warning(
        "int8 error-feedback residual store exceeded its bound; "
        "evicted buckets restart error feedback from zero (the wire "
        "degrades toward int8-raw for them). This indicates bucket "
        "churn — more distinct fused buckets than the store holds — "
        "see docs/compression.md.")


def _resolve_executor_wire(wire):
    """Executor ctor plumbing: "auto" resolves the HOROVOD_COMPRESSION
    knob (or raw env before hvd.init — bare EagerRuntime construction in
    tests/check scripts); a string parses; a WireSpec/None passes
    through."""
    from ..optim import compression as _comp

    if wire == "auto":
        return _comp.resolve_wire()
    if isinstance(wire, str):
        return _comp.parse_wire(wire)
    return wire


def _batch_dtype_name(batch: ExecutionBatch) -> str:
    """Numpy dtype name of a batch's payload: native batches carry a
    numeric dtype code (DTYPE_TO_NUMPY key), python-built test batches
    carry the name directly."""
    return DTYPE_TO_NUMPY.get(batch.dtype, batch.dtype)


def _batch_itemsize(batch: ExecutionBatch) -> int:
    name = _batch_dtype_name(batch)
    try:
        return np.dtype(name).itemsize
    except TypeError:
        return 2 if name == "bfloat16" else 4


def _wire_applies(spec, batch: ExecutionBatch) -> bool:
    """The compressed wire covers floating SUM/AVERAGE allreduce
    payloads; everything else moves at logical precision."""
    if spec is None or batch.op != OP_ALLREDUCE:
        return False
    if batch.reduce_op not in (_REDUCE_SUM, _REDUCE_AVERAGE):
        return False
    name = _batch_dtype_name(batch)
    if name == "bfloat16":
        return True
    try:
        return bool(np.issubdtype(np.dtype(name), np.floating))
    except TypeError:
        return False


def _record_wire_batch(spec, batch: ExecutionBatch, n_elements: int
                       ) -> None:
    """hvd_wire_bytes_{logical,sent}_total for one executed allreduce
    batch — `sent` equals `logical` exactly on the uncompressed plane,
    which is what compression_check's none-parity assertion reads."""
    if not _metrics.enabled() or batch.op != OP_ALLREDUCE:
        return
    from ..optim.compression import wire_sent_bytes

    itemsize = _batch_itemsize(batch)
    logical = n_elements * itemsize
    sent = wire_sent_bytes(
        n_elements, itemsize, spec if _wire_applies(spec, batch) else None)
    _metrics.record_wire_bytes(logical, sent)


class LoopbackExecutor:
    """Executes batches with single-process semantics (every rank's
    contribution equals ours — the eager single-controller model of
    ops/collectives.py).

    `wire` ("auto" = the HOROVOD_COMPRESSION knob) simulates the
    compressed data plane so world-local runs exercise — and account —
    the same wire numerics the XLA executor produces: cast wires
    accumulate in the cast dtype; the int8 wire applies both EQuARX
    quantization stages (contribution and reduced shard) with
    executor-held error-feedback residuals keyed by tensor name."""

    def __init__(self, world_size: int, rank: int = 0, wire="auto"):
        self._n = world_size
        self._rank = rank
        self.wire = _resolve_executor_wire(wire)
        self._residuals: Dict[str, np.ndarray] = {}

    def set_wire(self, wire) -> None:
        self.wire = _resolve_executor_wire(wire)
        self._residuals = {}

    def _wire_allreduce(self, batch: ExecutionBatch, name: str, x):
        """Wire-compressed SUM/AVERAGE of n identical contributions."""
        from ..optim import compression as _comp

        import jax.numpy as jnp

        spec = self.wire
        n = self._set_world(batch)[0]
        scaled = np.asarray(x, dtype=np.float32) * batch.prescale
        if spec.kind == "int8":
            eff = scaled
            if spec.error_feedback:
                res = self._residuals.get(name)
                if res is not None and res.shape == eff.shape:
                    eff = eff + res
            dq1 = np.asarray(_comp.quantize_dequantize(eff, spec.block))
            if spec.error_feedback:
                self._residuals.pop(name, None)
                self._residuals[name] = eff - dq1
                while len(self._residuals) > 4096:
                    # bounded like the XLA executor's store: churn in
                    # tensor names must not pin residuals forever
                    self._residuals.pop(next(iter(self._residuals)))
                    _warn_residual_eviction_once()
            r = np.asarray(_comp.quantize_dequantize(dq1 * n, spec.block))
        else:
            w = jnp.asarray(scaled).astype(spec.wire_dtype)
            r = np.asarray((w * n).astype(jnp.float32))
        if batch.reduce_op == _REDUCE_AVERAGE:
            r = r / n
        return (r * batch.postscale).astype(np.asarray(x).dtype)

    def _set_world(self, batch: ExecutionBatch):
        """(size, local_rank) of the batch's process set — the set's
        member count and this rank's position in it; the global world
        when the batch is unscoped."""
        if batch.set_ranks:
            return len(batch.set_ranks), batch.set_ranks.index(self._rank)
        return self._n, self._rank

    def __call__(self, batch: ExecutionBatch, tensors: Dict[str, np.ndarray]
                 ) -> Dict[str, np.ndarray]:
        n, rank = self._set_world(batch)
        wired = _wire_applies(self.wire, batch)
        if batch.op == OP_ALLREDUCE:
            _record_wire_batch(
                self.wire, batch,
                sum(int(np.asarray(tensors[nm]).size)
                    for nm in batch.names if nm in tensors))
        out = {}
        for name in batch.names:
            if name not in tensors:
                continue
            x = tensors[name]
            if batch.op == OP_ALLREDUCE and wired:
                out[name] = self._wire_allreduce(batch, name, x)
            elif batch.op == OP_ALLREDUCE:
                scaled = x * batch.prescale
                # n identical contributions: sum = x*n, min/max/adasum = x,
                # product = x**n
                if batch.reduce_op == _REDUCE_PRODUCT:
                    r = scaled ** n
                elif batch.reduce_op in (
                    _REDUCE_ADASUM, _REDUCE_MIN, _REDUCE_MAX
                ):
                    r = scaled
                else:
                    r = scaled * n
                    if batch.reduce_op == _REDUCE_AVERAGE:
                        r = r / n
                out[name] = r * batch.postscale
            elif batch.op == OP_ALLGATHER:
                dims = batch.rank_dim0
                if dims and len(set(dims)) > 1:
                    # truly ragged peers cannot be simulated from our
                    # buffer alone — a fabricated result would have the
                    # negotiated total rows but garbage content
                    raise HorovodInternalError(
                        f"loopback executor cannot materialize ragged "
                        f"allgather '{name}' (negotiated dims {dims}); "
                        f"use the XLA executor (make_xla_executor)"
                    )
                out[name] = np.concatenate([x] * n, axis=0)
            elif batch.op == OP_BROADCAST:
                out[name] = x
            elif batch.op == OP_REDUCESCATTER:
                chunk = x.shape[0] // n
                r = x[:chunk] * batch.prescale * n
                if batch.reduce_op == _REDUCE_AVERAGE:
                    r = r / n
                out[name] = r * batch.postscale
            elif batch.op == OP_ALLTOALL:
                # identical inputs: each peer sends us the chunk destined
                # to our rank; with the negotiated splits matrix the recv
                # layout is column `rank` (reference operations.cc:1858)
                r = rank
                m = np.asarray(batch.all_splits, dtype=np.int64).reshape(
                    (n, n)
                )
                pieces, recv_splits = [], []
                for j in range(n):
                    # peer j's buffer == ours; its chunk to us starts at
                    # the sum of ITS splits before us (row j's prefix)
                    joffs = np.concatenate(([0], np.cumsum(m[j])))
                    pieces.append(x[joffs[r]:joffs[r] + m[j][r]])
                    recv_splits.append(int(m[j][r]))
                out[name] = (
                    np.concatenate(pieces, axis=0),
                    np.asarray(recv_splits, dtype=np.int64),
                )
            else:
                raise HorovodInternalError(
                    f"executor received unknown op {batch.op} for tensor "
                    f"'{name}' — refusing to pass input through unchanged"
                )
        return out


class EagerRuntime:
    """Per-process facade: enqueue named tensors, a worker thread executes
    negotiated batches in controller order, `synchronize` returns results.
    """

    def __init__(
        self,
        rank: int,
        size: int,
        coordinator_addr: str = "127.0.0.1",
        coordinator_port: int = 0,
        executor: Optional[Callable] = None,
        cycle_ms: float = 1.0,
        fusion_threshold: int = 128 << 20,
        cache_capacity: int = 1024,
        stall_warning_s: float = 60.0,
        stall_shutdown_s: float = 0.0,
        stall_abort_s: float = 0.0,
        autotune: bool = False,
        autotune_warmup: int = -1,
        autotune_cycles_per_sample: int = -1,
        autotune_bayes: bool = False,
        fast_path: bool = True,
        fast_path_warmup: int = 3,
        pipeline_depth: int = 2,
        wire="auto",
    ):
        self._native = NativeRuntime()
        self._native.init(
            rank, size, coordinator_addr, coordinator_port,
            cycle_ms=cycle_ms, fusion_threshold=fusion_threshold,
            cache_capacity=cache_capacity, stall_warning_s=stall_warning_s,
            stall_shutdown_s=stall_shutdown_s, autotune=autotune,
            autotune_warmup=autotune_warmup,
            autotune_cycles_per_sample=autotune_cycles_per_sample,
            autotune_bayes=autotune_bayes,
        )
        self._executor = executor or LoopbackExecutor(size, rank,
                                                      wire=wire)
        # identity for the flight recorder's cross-rank attribution
        # (utils/flight.py): the stall-abort straggler report needs to
        # know which peers exist and who we are
        self._rank = int(rank)
        self._size = int(size)
        # negotiation watchdog (HOROVOD_STALL_ABORT_S): a collective
        # wait with no observable progress for this long aborts with
        # HorovodInternalError instead of hanging — the elastic run()
        # wrapper's restore-and-retry needs a raise to catch. 0 = off.
        self._stall_abort_s = float(stall_abort_s)
        self._lock = threading.Lock()
        self._inputs: Dict[str, np.ndarray] = {}
        self._results: Dict[int, np.ndarray] = {}
        self._handle_name: Dict[int, str] = {}
        self._handle_op: Dict[int, int] = {}
        self._handle_ts: Dict[int, float] = {}  # enqueue stamps (metrics)
        self._last_cycle = -1
        self._last_exec_error = ""
        self._tuning_applied = False
        self._shutdown = threading.Event()
        # ---- steady-state plan cache (HOROVOD_EAGER_FAST_PATH) ----
        # All _fp_* state is guarded by self._lock; _fp_cond shares the
        # lock so fast-path waiters and the dispatching thread hand off
        # without a second mutex.
        self._fp_cond = threading.Condition(self._lock)
        self._fp_on = bool(fast_path)
        self._fp_warmup = max(1, int(fast_path_warmup))
        self._fp_plan: Optional[ExecutionPlan] = None
        # native data-op handles issued but not yet synchronize()d. The
        # capture/freeze gates key on THIS (not on worker-thread handle
        # bookkeeping): it mutates only in user-thread program order, so
        # under the SPMD contract (all ranks run the same program) every
        # rank evaluates the gates identically at the identical step —
        # a worker-timing-dependent gate could activate the plan on one
        # rank and not another, splitting the world between bypassed and
        # negotiated execution (a distributed hang).
        self._fp_outstanding: set = set()
        self._fp_window: Dict[str, Tuple[tuple, dict]] = {}
        self._fp_prev: Optional[Dict[str, Tuple[tuple, dict]]] = None
        self._fp_repeats = 0
        self._fp_capture: Optional[List[ExecutionBatch]] = None
        self._fp_capture_names: frozenset = frozenset()
        self._fp_step: Dict[str, Tuple[int, object]] = {}
        self._fp_inflight: Dict[str, Tuple[int, object]] = {}
        self._fp_dispatching = False
        self._fp_alias: Dict[int, int] = {}   # fast handle -> native handle
        self._fp_failed: Dict[int, str] = {}  # fast handle -> error
        self._fp_next_handle = -1  # native handles are >= 1
        self._fp_hits = 0
        self._fp_steps = 0
        self._fp_activations = 0
        self._fp_invalidations = 0
        self._fp_bypassed_bytes = 0
        self._fp_last_invalidation = ""
        # ---- pipelined negotiate/execute double buffer ----
        # The pop thread pulls cycle N+1's batches out of the native loop
        # while the execute thread is still running cycle N — a bounded
        # queue is the double buffer; a single execute thread preserves
        # controller order (the consistency XLA multi-controller needs).
        self._exec_q: "queue.Queue" = queue.Queue(
            maxsize=max(1, int(pipeline_depth)))
        self._worker = threading.Thread(
            target=self._run, daemon=True, name="hvd-eager-negotiator"
        )
        self._exec_worker = threading.Thread(
            target=self._exec_loop, daemon=True, name="hvd-eager-executor"
        )
        self._worker.start()
        self._exec_worker.start()
        # publish cumulative cycle/cache stats for /metrics scrapes
        # (pull model: gauges refresh at render time, utils/metrics.py)
        _metrics.set_native_stats_provider(self.metrics_snapshot)

    # ------------------------------------------------------------ enqueue

    @staticmethod
    def _qualify(name: str, process_set_id: int) -> str:
        """Set-qualified wire name: name-keyed tables (tensor queue,
        message tables, response cache, stall inspector) never collide
        across sets — the reference reaches the same end with whole
        per-set controller instances (process_set.h:89)."""
        return name if process_set_id == 0 else f"ps{process_set_id}:{name}"

    @staticmethod
    def _prep_entry(name, tensor, op, reduce_op, root_rank, prescale,
                    postscale, splits, group, group_size, process_set_id):
        """Fault hook + host/device array normalization + kwargs dict —
        the per-tensor front half shared by enqueue and enqueue_batch."""
        # chaos hook: `collective:delay` simulates slow negotiation,
        # `collective:error` a failed one — surfaced as the same
        # HorovodInternalError a real negotiation failure raises so
        # elastic recovery exercises its production path
        if _faults.enabled():
            try:
                _faults.inject("collective", name=name, op=op)
            except _faults.InjectedFault as e:
                raise HorovodInternalError(str(e)) from e
        # device-resident jax arrays are enqueued as-is — negotiation
        # only needs shape/dtype, and the XLA executor consumes device
        # buffers directly (no host round trip; the reference keeps GPU
        # tensors on GPU through NCCL the same way)
        arr = tensor if _is_jax_array(tensor) else np.asarray(tensor)
        kwargs = dict(
            op=op, reduce_op=reduce_op, root_rank=root_rank,
            prescale=float(prescale), postscale=float(postscale),
            splits=[int(s) for s in splits] if splits is not None else None,
            group=group, group_size=group_size,
            process_set_id=process_set_id,
        )
        return arr, kwargs

    def enqueue(self, name: str, tensor, op: int = OP_ALLREDUCE,
                reduce_op: int = _REDUCE_SUM, root_rank: int = 0,
                prescale: float = 1.0, postscale: float = 1.0,
                splits: Optional[List[int]] = None,
                group: Optional[str] = None, group_size: int = 0,
                process_set_id: int = 0) -> int:
        arr, kwargs = self._prep_entry(
            name, tensor, op, reduce_op, root_rank, prescale, postscale,
            splits, group, group_size, process_set_id)
        name = self._qualify(name, process_set_id)
        ready: tuple = ()
        try:
            with self._lock:
                handle, ready = self._enqueue_locked(name, arr, kwargs)
                depth = len(self._inputs) + len(self._fp_step)
            _metrics.set_queue_depth(depth)
        finally:
            # dispatch even when the enqueue raised: a step moved to
            # inflight (_fp_dispatching set) MUST execute or every
            # later plan step would be held forever
            for plan, step in ready:
                self._fp_dispatch(plan, step)
        return handle

    def enqueue_batch(self, entries: List[dict]) -> List[int]:
        """Batched enqueue: the whole per-step gradient set pays ONE
        lock/queue round instead of one per tensor. Each entry is a
        dict with the keyword arguments of :meth:`enqueue` plus the
        required ``name`` and ``tensor`` keys. Returns per-entry
        handles in entry order.

        This is the runtime half of the grouped surface: the torch
        adapter's grouped_allreduce (mpi_ops.py:555) submits N tensors
        in one native call; here collectives._native_async builds the
        entry list once and the runtime amortizes the lock acquisition,
        the fast-path bookkeeping, and the queue-depth update across
        the set."""
        prepared = []
        for e in entries:
            arr, kwargs = self._prep_entry(
                e["name"], e["tensor"], e.get("op", OP_ALLREDUCE),
                e.get("reduce_op", _REDUCE_SUM), e.get("root_rank", 0),
                e.get("prescale", 1.0), e.get("postscale", 1.0),
                e.get("splits"), e.get("group"), e.get("group_size", 0),
                e.get("process_set_id", 0))
            prepared.append(
                (self._qualify(e["name"], kwargs["process_set_id"]),
                 arr, kwargs))
        handles: List[int] = []
        ready_all: List[tuple] = []
        try:
            with self._lock:
                for name, arr, kwargs in prepared:
                    h, ready = self._enqueue_locked(name, arr, kwargs)
                    handles.append(h)
                    ready_all.extend(ready)
                depth = len(self._inputs) + len(self._fp_step)
            _metrics.set_queue_depth(depth)
        finally:
            # a later entry's native enqueue may raise AFTER an earlier
            # entry completed a plan step (moved to inflight with
            # _fp_dispatching set): the collected steps must still
            # dispatch, else their handles wait out their timeout and
            # no future plan step can ever dispatch
            for plan, step in ready_all:
                self._fp_dispatch(plan, step)
        return handles

    def _enqueue_locked(self, name: str, arr, kwargs: dict):
        """Route one tensor: plan fast path when a frozen plan covers it
        with an identical signature, full negotiation otherwise (with
        window bookkeeping so a steady state can be detected). Returns
        (handle, ready-steps-to-dispatch-after-unlock)."""
        if self._fp_on and kwargs["op"] in _PLAN_OPS:
            sig = self._fp_sig(arr, kwargs)
            if self._fp_plan is None and name in self._fp_window:
                # a name repeating = the previous step's sequence ended
                self._fp_close_window_locked()
            plan = self._fp_plan
            if plan is not None:
                entry = plan.entries.get(name)
                if (entry is not None and entry.sig == sig
                        and name not in self._fp_step):
                    return self._fp_hit_locked(name, arr)
                # sequence deviation (new tensor, shape change, repeat
                # before the step completed): drop the plan, push any
                # held tensors back through negotiation, renegotiate
                self._fp_flush_locked(f"deviation:{name}")
            self._fp_window[name] = (sig, dict(kwargs))
            if len(self._fp_window) > 4096:
                # an unbounded stream of fresh names (auto-named ops)
                # never closes a window — don't let the fingerprint
                # table grow with it
                self._fp_window = {}
                self._fp_prev = None
                self._fp_repeats = 0
        return self._native_enqueue_locked(name, arr, kwargs), ()

    def _native_enqueue_locked(self, name: str, arr, kwargs: dict) -> int:
        # input + handle bookkeeping must be visible before the worker
        # thread can snapshot them, so the WHOLE enqueue runs under the
        # runtime lock: on a fast-negotiating world (response-cache
        # hit, world=1, 1ms cycles) the background loop can emit the
        # batch microseconds after native.enqueue returns, and a worker
        # snapshot taken before our map writes would execute the batch
        # with zeros for our own tensor and store no result for the
        # handle (observed as an intermittent 'no result for handle N'
        # under load). The native enqueue itself only pushes onto the
        # C++ tensor queue — it never waits on this lock, so holding it
        # across the call cannot deadlock.
        prev_in = self._inputs.get(name)
        self._inputs[name] = arr
        try:
            handle = self._native.enqueue(
                name, kwargs["op"], str(arr.dtype), list(arr.shape),
                reduce_op=kwargs["reduce_op"],
                root_rank=kwargs["root_rank"],
                prescale=kwargs["prescale"], postscale=kwargs["postscale"],
                splits=kwargs["splits"], group=kwargs["group"],
                group_size=kwargs["group_size"],
                process_set_id=kwargs["process_set_id"],
            )
        except Exception:
            # restore rather than pop: a fast-path fallback may have
            # just replayed a same-named tensor whose input must survive
            if prev_in is not None:
                self._inputs[name] = prev_in
            else:
                self._inputs.pop(name, None)
            raise
        self._handle_name[handle] = name
        self._handle_op[handle] = kwargs["op"]
        if kwargs["op"] in _PLAN_OPS:
            self._fp_outstanding.add(handle)
        # flight ring (utils/flight.py): the enqueue is the unit the
        # cross-rank straggler analysis counts — "rank R has not
        # submitted tensor T" is literally a lagging enqueue count
        _flight.record("enqueue", name, op=kwargs["op"], handle=handle)
        if _metrics.enabled():  # stamp only when someone will read it
            self._handle_ts[handle] = time.perf_counter()
        # span opens only after the native enqueue accepted the tensor — a
        # raise above would otherwise leave an unclosed 'B' corrupting the
        # trace's track nesting
        tl = _timeline()
        if tl is not None and kwargs["op"] in _OP_ACTIVITIES:
            tl.activity_start(name, _OP_ACTIVITIES[kwargs["op"]][0],
                              args={"shape": list(arr.shape),
                                    "dtype": str(arr.dtype)})
        return handle

    # ------------------------------------------- steady-state fast path

    @staticmethod
    def _fp_sig(arr, kwargs: dict) -> tuple:
        """Rolling-fingerprint element: everything negotiation would
        look at. Two enqueues with equal signatures would negotiate
        identically, which is what makes replaying the cached plan
        sound."""
        sp = kwargs.get("splits")
        return (
            kwargs["op"], kwargs["reduce_op"], kwargs["root_rank"],
            kwargs["prescale"], kwargs["postscale"], str(arr.dtype),
            tuple(int(d) for d in arr.shape),
            tuple(sp) if sp is not None else None,
            kwargs.get("group"), kwargs.get("group_size", 0),
            kwargs["process_set_id"],
        )

    def _fp_close_window_locked(self) -> None:
        """A step sequence just ended (one of its names re-appeared):
        compare it with the previous sequence, count repeats, and drive
        the capture → freeze ladder. Window equality is ORDER-free (a
        name→signature map): ranks may legally enqueue the same step in
        different orders, and the plan's batch order comes from the
        captured negotiated responses, not from local submit order — so
        every rank freezes the identical plan at the identical step."""
        w = self._fp_window
        self._fp_window = {}
        prev = self._fp_prev
        same = (
            prev is not None and len(w) == len(prev)
            and all(n in prev and prev[n][0] == s
                    for n, (s, _) in w.items())
        )
        self._fp_repeats = self._fp_repeats + 1 if same else 1
        captured = self._fp_capture
        self._fp_capture = None
        self._fp_prev = w
        if same and captured is not None:
            self._fp_try_freeze_locked(captured, w)
        if (self._fp_plan is None
                and self._fp_repeats >= self._fp_warmup
                and not self._fp_outstanding):
            # K identical sequences seen and every issued handle already
            # synchronized (a PROGRAM-ORDER fact, identical on all ranks
            # — see _fp_outstanding): record the NEXT sequence's
            # negotiated batches as the plan
            self._fp_capture = []
            self._fp_capture_names = frozenset(w)

    def _fp_try_freeze_locked(self, captured: List[ExecutionBatch],
                              window: Dict[str, tuple]) -> None:
        """Freeze the captured negotiated round into an ExecutionPlan if
        it cleanly covers the window (every tensor exactly once, nothing
        foreign fused in, nothing still in flight)."""
        # Every input to this decision is identical on every rank by
        # construction: the captured batches are the coordinator's own
        # response stream (broadcast), the window is the (identical)
        # enqueue sequence, and _fp_outstanding mutates in program order
        # — so either every rank freezes this plan at this step or none
        # does. A rank-local (timing-dependent) veto here would split
        # the world between bypassed and negotiated execution.
        seen: List[str] = []
        for b in captured:
            seen.extend(b.names)
        if (len(seen) != len(set(seen)) or set(seen) != set(window)
                or self._fp_outstanding):
            return  # not a clean steady-state round; re-capture later
        if _faults.enabled():
            try:
                _faults.inject("eager.fast_path", tensors=len(window))
            except _faults.InjectedFault:
                # a chaos rule vetoed activation: stay on full
                # negotiation (correct, just slower) and restart warmup
                self._fp_invalidations += 1
                self._fp_last_invalidation = "fault_injected"
                self._fp_repeats = 0
                return
        entries = {
            n: _PlanEntry(sig, kw) for n, (sig, kw) in window.items()
        }
        wire = self._executor_wire()
        self._fp_plan = ExecutionPlan(
            list(captured), entries,
            wire_key=wire.key if wire is not None else None)
        self._fp_activations += 1
        _flight.record("plan_activate", batches=len(captured),
                       tensors=len(entries))
        tl = _timeline()
        if tl is not None:
            tl.instant("fast_path", "PLAN_ACTIVATED",
                       args={"batches": len(captured),
                             "tensors": len(entries)})

    def _fp_hit_locked(self, name: str, arr):
        """Negotiation bypassed: append the tensor straight into its
        pre-sized plan slot; when the step's last tensor lands, hand the
        whole step back for dispatch (outside the lock)."""
        plan = self._fp_plan
        h = self._fp_next_handle  # native handles are >= 1; ours < 0
        self._fp_next_handle -= 1
        self._fp_step[name] = (h, arr)
        self._fp_hits += 1
        # a bypassed enqueue still counts as a submission: peers on the
        # negotiated path must not read a fast-path rank as a straggler
        _flight.record("enqueue", name, handle=h, fast_path=True)
        ready = ()
        if (len(self._fp_step) == len(plan.names)
                and not self._fp_dispatching):
            if self._native.pending_joins() > 0:
                # a peer joined (stopped contributing): its pending join
                # is broadcast in every negotiation cycle, and only
                # negotiation's zero-contribution join semantics can
                # reconcile the world — push this whole step back
                # through the coordinator instead of dispatching a
                # collective the joiner will never issue. The signal is
                # advisory (a ~2-cycle propagation window exists in
                # which a step can still dispatch); the stall watchdog
                # owns that residual race — docs/eager.md "Join"
                self._fp_flush_locked("peer_join")
                return h, ()  # flush aliased h to a native handle
            step = self._fp_step
            self._fp_step = {}
            self._fp_inflight = step
            self._fp_dispatching = True
            ready = ((plan, step),)
        return h, ready

    def _fp_flush_locked(self, reason: str) -> None:
        """Fall off the fast path: replay any held (not yet dispatched)
        step tensors through full negotiation — their already-issued
        fast handles get aliased to the replayed native handles, so
        synchronize() on them keeps working — then invalidate the plan
        and reset the learning windows."""
        plan = self._fp_plan
        if plan is not None and self._fp_step:
            for name, (fh, arr) in list(self._fp_step.items()):
                try:
                    nh = self._native_enqueue_locked(
                        name, arr, plan.entries[name].kwargs)
                except Exception:
                    self._fp_failed[fh] = (
                        f"fast-path fallback re-enqueue failed for "
                        f"'{name}': {self._native.last_error()}"
                    )
                    continue
                self._fp_alias[fh] = nh
            self._fp_step = {}
        self._fp_invalidate_locked(reason)

    def _fp_invalidate_locked(self, reason: str) -> None:
        had_plan = self._fp_plan is not None
        self._fp_plan = None
        self._fp_capture = None
        self._fp_window = {}
        self._fp_prev = None
        self._fp_repeats = 0
        if had_plan:
            self._fp_invalidations += 1
            self._fp_last_invalidation = reason
            _flight.record("plan_invalidate", reason=reason)
            tl = _timeline()
            if tl is not None:
                tl.instant("fast_path", "PLAN_INVALIDATED",
                           args={"reason": reason})
        self._fp_cond.notify_all()

    def _fp_dispatch(self, plan: ExecutionPlan, step: Dict[str, tuple]
                     ) -> None:
        """Execute one cached-plan step in the calling thread: no
        coordinator round trip and no worker-thread handoff — the
        batches are replayed in frozen controller order, which keeps
        the cross-process XLA program order identical on every rank."""
        tl = _timeline()
        m_on = _metrics.enabled()
        handles = {n: h for n, (h, _) in step.items()}
        tensors_all = {n: t for n, (_, t) in step.items()}
        error = None
        for batch in plan.batches:
            execute = _OP_ACTIVITIES.get(batch.op, (None, None))[1]
            if tl is not None and execute is not None:
                for n in batch.names:
                    tl.activity_start(
                        n, execute,
                        args={"batch_id": batch.batch_id,
                              "fast_path": True,
                              "fused_with": len(batch.names)})
            if _flight.enabled():
                _flight.record(
                    "exec_begin", batch.names[0] if batch.names else "",
                    op=batch.op, n=len(batch.names),
                    bytes=int(batch.total_bytes),
                    names=list(batch.names), fast_path=True)
            try:
                tensors = {n: tensors_all[n] for n in batch.names}
                t0 = time.perf_counter() if m_on else 0.0
                results = self._executor(batch, tensors)
                if m_on:
                    _metrics.record_batch_execution(
                        _OP_METRIC_NAMES.get(batch.op, str(batch.op)),
                        len(batch.names), batch.total_bytes,
                        time.perf_counter() - t0)
                if _flight.enabled():
                    _flight.record(
                        "exec_end",
                        batch.names[0] if batch.names else "",
                        op=batch.op, names=list(batch.names),
                        fast_path=True)
                with self._lock:
                    for n in batch.names:
                        if n in results:
                            self._results[handles[n]] = results[n]
                        else:
                            self._fp_failed[handles[n]] = (
                                f"fast-path executor returned no result"
                                f" for '{n}'")
            except Exception as e:
                import traceback

                error = traceback.format_exc(limit=8)
                self._last_exec_error = error
                if _flight.enabled():
                    _flight.record(
                        "exec_error",
                        batch.names[0] if batch.names else "",
                        op=batch.op, fast_path=True,
                        error=str(e)[:200])
                    _flight.dump("executor_error")
            finally:
                if tl is not None and execute is not None:
                    for n in batch.names:
                        tl.activity_end(n, execute)
            if error is not None:
                break
        with self._fp_cond:
            if error is not None:
                for n, h in handles.items():
                    if h not in self._results and h not in self._fp_failed:
                        self._fp_failed[h] = (
                            "fast-path execution failed:\n" + error)
                if self._fp_plan is plan:
                    self._fp_invalidate_locked("executor_error")
            else:
                self._fp_steps += 1
                self._fp_bypassed_bytes += plan.total_bytes
            self._fp_inflight = {}
            self._fp_dispatching = False
            self._fp_cond.notify_all()

    def _fp_sync(self, handle: int, timeout_s: float):
        """Resolve a fast-path handle: (True, result) when the plan
        step already executed, (False, native_handle) when the tensor
        was (or is now being) replayed through negotiation."""
        deadline = time.monotonic() + timeout_s
        with self._fp_cond:
            while True:
                if handle in self._results:
                    return True, self._results.pop(handle)
                if handle in self._fp_failed:
                    raise HorovodInternalError(self._fp_failed.pop(handle))
                nh = self._fp_alias.pop(handle, None)
                if nh is not None:
                    return False, nh
                held = any(h == handle for h, _ in self._fp_step.values())
                if held and not self._fp_dispatching:
                    # the caller blocks before the plan step completed:
                    # this submit/sync interleaving is finer than the
                    # plan's step granularity — replay the held tensors
                    # through negotiation and wait there (the plan is
                    # dropped; steady state will re-learn)
                    self._fp_flush_locked("sync_before_step_complete")
                    continue
                inflight = any(
                    h == handle for h, _ in self._fp_inflight.values())
                if inflight or self._fp_dispatching:
                    if time.monotonic() >= deadline:
                        raise HorovodInternalError(
                            f"timed out waiting for fast-path handle "
                            f"{handle}")
                    self._fp_cond.wait(
                        min(0.25, max(0.01,
                                      deadline - time.monotonic())))
                    continue
                raise HorovodInternalError(
                    f"no result for handle {handle}: "
                    f"{self._native.last_error() or self._last_exec_error}"
                )

    def _fp_barrier(self, reason: str) -> None:
        """Topology/membership is about to change (process-set churn,
        join, explicit invalidation): push held fast-path tensors back
        through negotiation and drop the plan before the change lands."""
        with self._fp_cond:
            self._fp_flush_locked(reason)

    def invalidate_plan(self, reason: str = "user") -> None:
        """Public invalidation hook: drops the cached plan (if any) and
        resets steady-state detection. Held tensors are replayed through
        full negotiation; outstanding handles stay valid."""
        self._fp_barrier(reason)

    def set_fast_path(self, enabled: bool) -> None:
        """Toggle the steady-state fast path live (bench A/B surface).
        Disabling flushes the active plan so subsequent enqueues take
        the negotiated path exactly as with HOROVOD_EAGER_FAST_PATH=0."""
        with self._fp_cond:
            if not enabled:
                self._fp_flush_locked("disabled")
            self._fp_on = bool(enabled)

    def _executor_wire(self):
        return getattr(self._executor, "wire", None)

    def set_wire(self, wire) -> None:
        """Switch the executor's wire compression live (bench A/B
        surface; accepts a HOROVOD_COMPRESSION-style name, a WireSpec,
        or None). Any frozen plan was captured under the old wire, so
        the plan cache restarts — the change must land on every rank at
        the same program point, like every topology-shaped mutation.

        Refuses while collectives are outstanding: a batch negotiated
        before the flip could otherwise execute under the old wire on
        one rank and the new wire on another (the executor worker pops
        batches asynchronously), silently splitting the world's
        numerics. The gate keys on the program-order handle set
        (_fp_outstanding), so under the SPMD contract every rank
        accepts or refuses identically."""
        spec = _resolve_executor_wire(wire)
        set_fn = getattr(self._executor, "set_wire", None)
        if set_fn is None:
            raise HorovodInternalError(
                "this executor does not support wire compression")
        with self._lock:
            # _fp_outstanding (issued native handles not yet
            # synchronized) and _fp_step (a partial fast-path step)
            # both mutate only in user-thread program order
            if self._fp_outstanding or self._fp_step:
                raise HorovodInternalError(
                    f"set_wire with {len(self._fp_outstanding) + len(self._fp_step)} "
                    "outstanding collective handle(s): synchronize "
                    "every pending collective on every rank first, or "
                    "a batch could execute under different wires on "
                    "different ranks")
        self._fp_barrier("wire_change")
        set_fn(spec)

    def fast_path_stats(self) -> dict:
        with self._lock:
            wire = self._executor_wire()
            return {
                "enabled": self._fp_on,
                "active": self._fp_plan is not None,
                "hits": self._fp_hits,
                "steps": self._fp_steps,
                "activations": self._fp_activations,
                "invalidations": self._fp_invalidations,
                "bypassed_bytes": self._fp_bypassed_bytes,
                "last_invalidation": self._fp_last_invalidation,
                "warmup": self._fp_warmup,
                "wire": wire.kind if wire is not None else "none",
                "plan_wire_key": (self._fp_plan.wire_key
                                  if self._fp_plan is not None else None),
            }

    # --------------------------------------------------- process sets

    def register_process_set(self, set_id: int, ranks,
                             timeout_s: float = 60.0) -> None:
        """Negotiated registration: every world rank must call with
        identical membership before any rank's call returns (reference
        process_sets.py:123 add_process_set — synchronized registration).
        """
        # membership churn changes fusion/sub-mesh shape: any cached
        # plan (and steady-state learning) must restart from scratch
        self._fp_barrier("process_set_register")
        h = self._native.register_set(set_id, [int(r) for r in ranks])
        state = self._await_handle(h, timeout_s)
        self._native.release(h)
        if state != DONE:
            raise HorovodInternalError(
                f"process set {set_id} registration failed: "
                f"{self._native.last_error()}"
            )

    def deregister_process_set(self, set_id: int,
                               timeout_s: float = 60.0) -> None:
        self._fp_barrier("process_set_deregister")
        h = self._native.deregister_set(set_id)
        state = self._await_handle(h, timeout_s)
        self._native.release(h)
        if state != DONE:
            raise HorovodInternalError(
                f"process set {set_id} deregistration failed: "
                f"{self._native.last_error()}"
            )

    def process_set_members(self, set_id: int) -> Optional[List[int]]:
        """Sorted global ranks of a registered set; None if unknown."""
        return self._native.set_members(set_id)

    def allreduce_async(self, name: str, tensor, average: bool = False,
                        prescale: float = 1.0, postscale: float = 1.0,
                        process_set_id: int = 0) -> int:
        return self.enqueue(
            name, tensor, OP_ALLREDUCE,
            reduce_op=_REDUCE_AVERAGE if average else _REDUCE_SUM,
            prescale=prescale, postscale=postscale,
            process_set_id=process_set_id,
        )

    def allgather_async(self, name: str, tensor,
                        process_set_id: int = 0) -> int:
        """Ragged-capable: dim 0 may differ per rank; the controller
        negotiates per-rank sizes (reference controller.cc:497). Note the
        default LoopbackExecutor refuses truly ragged worlds (it cannot
        fabricate peers' data); the XLA executor handles them."""
        return self.enqueue(name, tensor, OP_ALLGATHER,
                            process_set_id=process_set_id)

    def alltoall_async(self, name: str, tensor, splits=None,
                       process_set_id: int = 0) -> int:
        """Uneven-capable: `splits[j]` rows go to set-member j;
        synchronize returns (output, received_splits) (reference
        operations.cc:1858)."""
        return self.enqueue(name, tensor, OP_ALLTOALL, splits=splits,
                            process_set_id=process_set_id)

    def broadcast_async(self, name: str, tensor, root_rank: int = 0,
                        process_set_id: int = 0) -> int:
        return self.enqueue(name, tensor, OP_BROADCAST, root_rank=root_rank,
                            process_set_id=process_set_id)

    def join(self) -> int:
        # a joining rank stops contributing: peers' sequences now
        # include tensors we never enqueue, which only negotiation's
        # zero-contribution join semantics can reconcile
        self._fp_barrier("join")
        return self._native.join()

    def join_sync(self, timeout_s: float = 60.0) -> int:
        """Join and block until every rank has joined (the worker thread
        auto-completes OP_JOIN batches). Returns 0 — per-rank join order
        is not tracked (reference returns the last joining rank purely as
        a curiosity, torch/mpi_ops.py:1250)."""
        self._fp_barrier("join")
        h = self._native.join()
        # a join handle stays PENDING until every rank has joined
        # (controller.cc kJoin emits only on full coverage) — keep waiting
        # through PENDING timeouts like synchronize does; the stall
        # watchdog / inspector own genuinely-stuck worlds
        state = self._await_handle(h, timeout_s)
        self._native.release(h)
        if state != DONE:
            raise HorovodInternalError(
                f"join failed: {self._native.last_error()}"
            )
        return 0

    def barrier(self, timeout_s: float = 60.0,
                process_set_id: int = 0) -> None:
        if process_set_id == 0:
            h = self._native.barrier()
        else:
            # per-set barrier: completes when every MEMBER has arrived
            # (reference process_set.h:89 — each set negotiates alone)
            h = self._native.enqueue(
                self._qualify("__barrier__", process_set_id),
                OP_BARRIER, "uint8", [],
                process_set_id=process_set_id,
            )
        state = self._native.wait(h, timeout_s)
        while state == BATCHED:
            state = self._native.wait(h, timeout_s)
        self._native.release(h)
        if state != DONE:
            raise HorovodInternalError(
                f"barrier failed: {self._native.last_error()}"
            )

    # --------------------------------------------------------- completion

    def poll(self, handle: int) -> bool:
        if handle < 0:  # fast-path handle
            with self._lock:
                if handle in self._results or handle in self._fp_failed:
                    return True
                nh = self._fp_alias.get(handle)
            if nh is None:
                return False
            handle = nh
        return self._native.poll(handle) in (DONE, FAILED)

    # -- stall watchdog ----------------------------------------------------

    def _progress_marker(self, handle: int) -> tuple:
        """Cheap observable-progress fingerprint for a pending wait.
        Deliberately excludes coordinator cycle counts — an idle
        coordinator keeps cycling while a lost peer stalls the world,
        and that must read as NO progress."""
        stats = {}
        try:
            stats = self._native.stats()
        except Exception:
            pass
        with self._lock:
            n_results = len(self._results)
        return (
            self._native.poll(handle),
            stats.get("responses", 0),
            stats.get("bytes_negotiated", 0),
            n_results,
        )

    def _abort_stalled(self, handle: int, waited_s: float) -> None:
        """Convert a stalled negotiation into HorovodInternalError:
        release the handle, close its bookkeeping/timeline span, raise
        — the elastic run() wrapper restores committed state and
        retries instead of hanging past every deadline. With the
        flight recorder on, the ring is dumped first and the message
        is upgraded to name the suspected straggler ranks and the
        tensors they have not submitted, cross-referenced against
        peers' last dumps (utils/flight.py, docs/flight.md)."""
        _metrics.record_stall_abort()
        self._native.release(handle)
        with self._lock:
            # everything still awaiting negotiation/execution — the
            # tensor set the straggler analysis attributes (snapshot
            # BEFORE popping the aborting handle's own input)
            pending = sorted(set(self._inputs) | set(self._fp_step))
            self._fp_outstanding.discard(handle)
            name = self._handle_name.pop(handle, None)
            op = self._handle_op.pop(handle, None)
            self._handle_ts.pop(handle, None)
            if name is not None:
                self._inputs.pop(name, None)
        straggler = ""
        if _flight.enabled():
            _flight.record("stall_abort", name or "", handle=handle,
                           waited_s=round(waited_s, 3))
            try:
                straggler = _flight.straggler_report(
                    pending, self._size, self._rank,
                    reason="stall_abort")
            except Exception:
                straggler = ""
        tl = _timeline()
        if tl is not None and name is not None and op in _OP_ACTIVITIES:
            tl.activity_end(name, _OP_ACTIVITIES[op][0])
            tl.instant(name, "STALL_ABORT")
        raise HorovodInternalError(
            f"collective stalled: handle {handle}"
            + (f" ({name})" if name else "")
            + f" made no progress for {waited_s:.1f}s "
            "(HOROVOD_STALL_ABORT_S watchdog; a peer likely died — "
            "elastic training will restore and retry)"
            + (f"; {straggler}" if straggler else "")
        )

    def _await_handle(self, handle: int, timeout_s: float,
                      results_gate: bool = False) -> int:
        """Block until the handle leaves PENDING/BATCHED (or, with
        ``results_gate``, until its result lands), aborting via the
        stall watchdog when enabled. Returns the last native state."""
        abort_s = self._stall_abort_s
        if abort_s <= 0:
            slice_s = timeout_s
            stall_at = None
        else:
            # short wait slices keep the watchdog responsive without
            # busy-spinning; progress checks run only on this slow path
            slice_s = max(min(timeout_s, abort_s / 4.0, 0.25), 0.01)
            stall_at = time.monotonic() + abort_s
        last_marker = None
        state = self._native.wait(handle, slice_s)
        while state in (0, BATCHED):  # pending or awaiting executor
            if results_gate:
                with self._lock:
                    if handle in self._results:
                        return state
            if stall_at is not None:
                marker = self._progress_marker(handle)
                if marker != last_marker:
                    last_marker = marker
                    stall_at = time.monotonic() + abort_s
                elif time.monotonic() >= stall_at:
                    self._abort_stalled(handle, abort_s)
            state = self._native.wait(handle, slice_s)
        return state

    def synchronize(self, handle: int, timeout_s: float = 60.0):
        if handle < 0:  # fast-path handle
            done, value = self._fp_sync(handle, timeout_s)
            if done:
                return value
            handle = value  # replayed through negotiation: wait there
        self._await_handle(handle, timeout_s, results_gate=True)
        failed = self._native.poll(handle) == FAILED
        self._native.release(handle)
        if failed:
            # a handle that never reached the executor failed in
            # negotiation: close its still-open NEGOTIATE span
            with self._lock:
                self._fp_outstanding.discard(handle)
                name = self._handle_name.pop(handle, None)
                op = self._handle_op.pop(handle, None)
                self._handle_ts.pop(handle, None)
                self._inputs.pop(name, None)
            tl = _timeline()
            if tl is not None and name is not None and op in _OP_ACTIVITIES:
                tl.activity_end(name, _OP_ACTIVITIES[op][0])
                tl.instant(name, "ERROR")
            raise HorovodInternalError(self._native.last_error())
        self._apply_pinned_tuning()
        with self._lock:
            self._fp_outstanding.discard(handle)
            if handle not in self._results:
                raise HorovodInternalError(
                    f"no result for handle {handle}: "
                    f"{self._native.last_error() or self._last_exec_error}"
                )
            return self._results.pop(handle)

    def _apply_pinned_tuning(self) -> None:
        """Once the coordinator pins autotune winners, steer the
        SPMD-side knobs so subsequently compiled steps pick up the tuned
        hierarchical routing (ops/hierarchical.py gates on these). Runs
        at most once, on the first synchronize() after the pin — the
        same moment the reference applies ParameterManager winners.
        Enabling autotune delegates these knobs to the tuner (reference
        semantics): a pinned winner overrides env-set values, including
        turning hierarchical OFF if flat scored better."""
        if self._tuning_applied or not self._native.tuned_pinned():
            return
        self._tuning_applied = True
        # only the 5-D Bayes search explores the hierarchical dims; the
        # 2-D coordinate-descent tuner leaves at_hierarchical_ at its
        # default, and applying that default here would silently disable
        # user-set HOROVOD_HIERARCHICAL_ALLREDUCE=1 (ADVICE r4 #2)
        if not self._native.tuned_bayes():
            return
        from ..core.state import global_state

        k = global_state().knobs
        k.hierarchical_allreduce = bool(self._native.tuned_hierarchical())
        local = int(self._native.tuned_hier_block())
        if local > 0:
            k.hierarchical_local_size = local

    # ------------------------------------------------------------- worker

    def _run(self) -> None:
        """Pop half of the pipelined worker: pull negotiated batches out
        of the native loop, stamp cycle markers / negotiation latency,
        close NEGOTIATE spans, then hand off to the execute thread. The
        bounded queue is the double buffer — while the execute thread
        runs cycle N's batch, this thread is already blocked in
        next_batch pulling cycle N+1 instead of serializing behind the
        executor dispatch."""
        try:
            while not self._shutdown.is_set():
                batch = self._native.next_batch(timeout_s=0.1)
                if batch is None:
                    continue
                # batch.tuned_hierarchical / tuned_hier_block were
                # stamped by the NATIVE loop at batch creation
                # (operations.cc Batch) — cycle-coherent with the
                # ResponseList that delivered them. Reading the
                # rank-local atomics here instead would let two ranks
                # stamp different routing for one negotiated batch
                # while workers lag the loop during a Bayes search
                # (ADVICE r4 #1).
                tl = _timeline()
                if tl is not None and batch.cycle != self._last_cycle:
                    # one marker per negotiation cycle, however many
                    # fused batches it produced (reference
                    # MarkCycleStart, operations.cc:734)
                    self._last_cycle = batch.cycle
                    tl.mark_cycle_start()
                if _flight.enabled():
                    # one event per negotiated batch received from the
                    # controller — the moment a tensor's negotiation
                    # ended on THIS rank
                    _flight.record(
                        "response",
                        batch.names[0] if batch.names else "",
                        op=batch.op, cycle=int(batch.cycle),
                        n=len(batch.names), names=list(batch.names))
                ours: List[str] = []
                if batch.op not in (OP_JOIN, OP_BARRIER):
                    # only tensors THIS rank enqueued get span events —
                    # a joined rank receives batches naming tensors it
                    # never started, and an E without a B corrupts the
                    # trace's track nesting
                    m_on = _metrics.enabled()
                    with self._lock:
                        ours = [
                            self._handle_name[h]
                            for h in batch.handles
                            if h in self._handle_name
                        ]
                        if m_on:
                            now = time.perf_counter()
                            for h in batch.handles:
                                ts = self._handle_ts.pop(h, None)
                                if ts is not None:
                                    _metrics.record_negotiation_latency(
                                        now - ts)
                    negotiate = _OP_ACTIVITIES.get(
                        batch.op, (None, None))[0]
                    if tl is not None and negotiate is not None:
                        # negotiation ended for every tensor in the
                        # fused batch; execution spans open in the
                        # execute thread (strictly after this put)
                        for n in ours:
                            tl.activity_end(n, negotiate)
                self._exec_q.put((batch, ours))
        finally:
            self._exec_q.put(None)

    def _exec_loop(self) -> None:
        """Execute half of the pipeline: runs batches in controller
        order (a single thread preserves it — the consistency XLA
        multi-controller execution requires) while _run pulls the next
        cycle's batches concurrently."""
        while True:
            item = self._exec_q.get()
            if item is None:
                return
            batch, ours = item
            if batch.op in (OP_JOIN, OP_BARRIER):
                # completed in controller order so a barrier cannot
                # overtake a data batch negotiated before it
                self._native.batch_done(batch, ok=True)
                continue
            tl = _timeline()
            execute = _OP_ACTIVITIES.get(batch.op, (None, None))[1]
            m_on = _metrics.enabled()
            if tl is not None and execute is not None:
                # the execution span carries the fused-batch composition
                # (reference: FuseResponses → per-tensor op activities)
                for n in ours:
                    tl.activity_start(
                        n, execute,
                        args={"batch_id": batch.batch_id,
                              "fused_with": len(batch.names)},
                    )
            if _flight.enabled():
                _flight.record(
                    "exec_begin", batch.names[0] if batch.names else "",
                    op=batch.op, n=len(batch.names),
                    bytes=int(batch.total_bytes),
                    names=list(batch.names))
            try:
                with self._lock:
                    tensors = {
                        n: self._inputs[n]
                        for n in batch.names if n in self._inputs
                    }
                t_exec = time.perf_counter() if m_on else 0.0
                results = self._executor(batch, tensors)
                if m_on:
                    _metrics.record_batch_execution(
                        _OP_METRIC_NAMES.get(batch.op, str(batch.op)),
                        len(batch.names), batch.total_bytes,
                        time.perf_counter() - t_exec,
                    )
                if _flight.enabled():
                    _flight.record(
                        "exec_end",
                        batch.names[0] if batch.names else "",
                        op=batch.op, names=list(batch.names))
                with self._lock:
                    for h in batch.handles:
                        name = self._handle_name.pop(h, None)
                        self._handle_op.pop(h, None)
                        # stamped-while-enabled handles whose
                        # negotiation ran after a disable() would
                        # otherwise linger
                        self._handle_ts.pop(h, None)
                        if name is not None and name in results:
                            self._results[h] = results[name]
                        self._inputs.pop(name, None)
                    if (self._fp_capture is not None
                            and batch.op in _PLAN_OPS):
                        # plan capture: record this negotiated batch as
                        # a frozen bucket IF it stays inside the
                        # captured sequence; a batch fusing a foreign
                        # tensor in means the round was not steady
                        bn = set(batch.names)
                        if bn <= self._fp_capture_names:
                            self._fp_capture.append(batch)
                        elif bn & self._fp_capture_names:
                            self._fp_capture = None
                    depth = len(self._inputs) + len(self._fp_step)
                _metrics.set_queue_depth(depth)
                self._native.batch_done(batch, ok=True)
            except Exception as e:
                # keep the executor's failure for synchronize()'s error
                # message — the native error channel only carries
                # negotiation/transport failures, so a swallowed
                # executor exception would surface as a bare
                # 'no result for handle N'
                import traceback

                self._last_exec_error = traceback.format_exc(limit=8)
                if _flight.enabled():
                    _flight.record(
                        "exec_error",
                        batch.names[0] if batch.names else "",
                        op=batch.op, error=str(e)[:200])
                    _flight.dump("executor_error")
                self._native.batch_done(batch, ok=False)
                with self._lock:
                    for h in batch.handles:
                        name = self._handle_name.pop(h, None)
                        self._handle_op.pop(h, None)
                        self._handle_ts.pop(h, None)
                        self._inputs.pop(name, None)
            finally:
                if tl is not None and execute is not None:
                    for n in ours:
                        tl.activity_end(n, execute)

    # ------------------------------------------------------------ stats

    def metrics_snapshot(self) -> dict:
        """Cumulative native cycle/cache stats + live queue depth — the
        pull source behind the hvd_cache_hits/hvd_coord_* gauges
        (utils/metrics.py set_native_stats_provider)."""
        s = self._native.stats()
        with self._lock:
            s["queue_depth"] = len(self._inputs) + len(self._fp_step)
            # steady-state fast path counters → the
            # hvd_eager_fast_path_* series (docs/metrics.md)
            s["fast_path_hits"] = self._fp_hits
            s["fast_path_steps"] = self._fp_steps
            s["fast_path_activations"] = self._fp_activations
            s["fast_path_invalidations"] = self._fp_invalidations
            s["fast_path_active"] = 1 if self._fp_plan is not None else 0
            s["negotiation_bypassed_bytes"] = self._fp_bypassed_bytes
        return s

    def cache_hits(self) -> int:
        return self._native.cache_hits()

    def bytes_negotiated(self) -> int:
        return self._native.bytes_negotiated()

    def stall_warnings(self) -> int:
        return self._native.stall_warnings()

    def tuned_parameters(self) -> dict:
        """Coordinator-distributed autotune values — identical on every
        rank by construction (the coordinator ships them in each
        ResponseList; reference parameter_manager.cc:528)."""
        return {
            "cycle_ms": self._native.tuned_cycle_ms(),
            "fusion_threshold_bytes": self._native.tuned_threshold(),
            "pinned": self._native.tuned_pinned(),
            "cache_enabled": self._native.tuned_cache_enabled(),
            "hierarchical_allreduce": self._native.tuned_hierarchical(),
            "hierarchical_local_size": self._native.tuned_hier_block(),
        }

    def shutdown(self) -> None:
        _metrics.set_native_stats_provider(None)
        with self._fp_cond:
            # fail any tensors still held in an incomplete plan step so
            # their waiters see a terminal state, mirroring the native
            # loop failing still-pending handles on shutdown
            self._fp_on = False
            held = list(self._fp_step.items()) + list(
                self._fp_inflight.items())
            for name, (h, _) in held:
                self._fp_failed.setdefault(h, "runtime shut down")
            self._fp_step = {}
            self._fp_plan = None
            self._fp_cond.notify_all()
        self._shutdown.set()
        self._native.shutdown()
        self._worker.join(timeout=5)
        self._exec_worker.join(timeout=5)


class XlaExecutor:
    """Multi-controller data plane: execute negotiated batches as XLA
    collectives over a one-device-per-process mesh.

    This is the TPU-native analog of the reference's enqueue↔execute
    handshake (/root/reference/horovod/common/operations.cc:273
    PerformOperation; tensorflow/xla_mpi_ops.cc:317 rendezvous): the
    controller has already fixed the fused batch order identically on
    every process, so each process can issue the same jit-compiled
    collective program in the same order — exactly the consistency XLA
    multi-controller execution requires. The negotiation world is
    *processes* (the reference's rank model): each process contributes its
    local tensor on its first local device over a dedicated ``proc`` mesh
    axis; remaining local devices are untouched (the SPMD path owns them).

    Fused allreduce batches are packed into one flat buffer per batch —
    one collective HLO for N tensors, the compile-time mirror of the
    reference's fusion buffer (fusion_buffer_manager.h:30).
    """

    def __init__(self, rank: int, world: int, wire="auto"):
        import jax
        from jax.sharding import Mesh

        # The controller's rank/world MUST be the jax process topology:
        # dim-0 slicing of gathered results and the alltoall recv-splits
        # column are indexed by this rank, so a mismatch silently reads
        # another process's data (ADVICE r2 #1).
        if rank != jax.process_index():
            raise HorovodInternalError(
                f"native runtime rank {rank} != jax.process_index() "
                f"{jax.process_index()}; the XLA executor requires the "
                "controller rank order to be the JAX process order"
            )
        if world != jax.process_count():
            raise HorovodInternalError(
                f"native runtime size {world} != jax.process_count() "
                f"{jax.process_count()}"
            )
        by_proc: Dict[int, object] = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, d)
        if sorted(by_proc) != list(range(world)):
            raise HorovodInternalError(
                f"process indices {sorted(by_proc)} are not contiguous "
                f"0..{world - 1}"
            )
        self._rank = rank
        self._world = world
        self._local_device = by_proc[rank]
        self._by_proc = by_proc
        self._mesh = Mesh(
            np.asarray([by_proc[p] for p in range(world)]), ("proc",)
        )
        # process-set sub-meshes, keyed by the sorted member tuple: a
        # subset batch executes over exactly the members' devices — the
        # sub-mesh IS the communicator (only member processes receive the
        # batch, and only they issue this program; reference gives each
        # set its own controller+communicator, process_set.h:89)
        self._set_meshes: Dict[tuple, object] = {}
        self._programs: Dict[tuple, Callable] = {}
        # per-mesh P("proc") sharding, built once: _global_stack runs
        # once per tensor per step, and rebuilding the NamedSharding
        # there was pure per-step dispatch overhead (visible on grouped
        # batches, which stack every member tensor back to back)
        self._proc_shardings: Dict[int, object] = {}
        # compressed data plane (optim/compression.py WireSpec): the
        # wire dtype is part of every fused-program cache key, and the
        # int8 error-feedback residuals live HERE, keyed per fused
        # bucket — the eager-path mirror of the SPMD path's
        # optimizer-state residual leaves (docs/compression.md)
        self.wire = _resolve_executor_wire(wire)
        self._wire_residuals: Dict[tuple, object] = {}

    def set_wire(self, wire) -> None:
        """Swap the wire spec (bench A/B; every process must switch at
        the same point in the batch stream — the runtime's set_wire
        flushes the plan first). Residuals from the old wire are
        dropped: they describe the old quantization grid."""
        self.wire = _resolve_executor_wire(wire)
        self._wire_residuals = {}

    # -------------------------------------------------------- plumbing

    def _batch_ctx(self, batch):
        """(mesh, world, my set-local rank, cache key tag) for a batch's
        process set; the global mesh for unscoped batches."""
        members = tuple(batch.set_ranks)
        if not members or list(members) == list(range(self._world)):
            return self._mesh, self._world, self._rank, ()
        if self._rank not in members:
            raise HorovodInternalError(
                f"rank {self._rank} received a batch for process set "
                f"{batch.process_set_id} (members {list(members)}) it "
                "does not belong to"
            )
        mesh = self._set_meshes.get(members)
        if mesh is None:
            from jax.sharding import Mesh

            mesh = Mesh(
                np.asarray([self._by_proc[p] for p in members]), ("proc",)
            )
            self._set_meshes[members] = mesh
        return mesh, len(members), members.index(self._rank), members

    def _global_stack(self, arr: np.ndarray, mesh=None, world=None):
        """Place this process's tensor as slice [local rank] of a
        [world, ...] global array sharded one-slice-per-process along
        ``proc``."""
        import jax
        import jax.numpy as jnp

        use_mesh = mesh if mesh is not None else self._mesh
        sharding = self._proc_shardings.get(id(use_mesh))
        if sharding is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(use_mesh, P("proc"))
            self._proc_shardings[id(use_mesh)] = sharding
        a = jnp.asarray(arr)
        return jax.make_array_from_single_device_arrays(
            ((world or self._world),) + a.shape,
            sharding,
            [jax.device_put(a[None], self._local_device)],
        )

    def _program(self, key, leaf, out_spec_sharded: bool, mesh=None,
                 arity: int = 1, out_specs=None):
        """jit(shard_map) over the proc mesh, cached by signature — the
        steady-state fast path (compilation plays the role the response
        cache plays for negotiation). With ``arity`` > 1 the program
        takes that many [world, ...] inputs and ``leaf`` sees one local
        slice per argument (fused-batch pack/unpack runs inside).
        ``out_specs`` (a PartitionSpec pytree) overrides the
        ``out_spec_sharded`` bool for mixed-replication outputs (the
        int8 wire returns replicated tensors plus a sharded per-rank
        residual)."""
        prog = self._programs.get(key)
        if prog is None:
            import jax
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            def body(*stacked):
                return leaf(*[s[0] for s in stacked])

            if out_specs is None:
                out_specs = P("proc") if out_spec_sharded else P()
            prog = jax.jit(
                shard_map(
                    body,
                    mesh=mesh if mesh is not None else self._mesh,
                    in_specs=tuple(P("proc") for _ in range(arity)),
                    out_specs=out_specs,
                    check_vma=False,
                )
            )
            self._programs[key] = prog
        return prog

    def _local_shard(self, out) -> np.ndarray:
        shards = [s for s in out.addressable_shards]
        assert len(shards) == 1, "proc mesh places one shard per process"
        return np.asarray(shards[0].data)

    # ------------------------------------------------------ op leaves

    def _hier_reduce_leaf(self, reduce_op: int, prescale: float,
                          postscale: float, n: int, block: int):
        """SUM/AVERAGE via the two-level ICI×DCN form
        (ops/hierarchical.hierarchical_psum) — value-equal to psum."""
        import jax.numpy as jnp

        def leaf(x):
            from .hierarchical import hierarchical_psum

            if prescale != 1.0:
                x = x * jnp.asarray(prescale, dtype=x.dtype)
            y = hierarchical_psum(x, ("proc",), {"proc": n}, block)
            if reduce_op == _REDUCE_AVERAGE:
                y = (y / n).astype(x.dtype)
            if postscale != 1.0:
                y = y * jnp.asarray(postscale, dtype=y.dtype)
            return y

        return leaf

    def _reduce_leaf(self, reduce_op: int, prescale: float,
                     postscale: float, n: Optional[int] = None):
        import jax.numpy as jnp
        from jax import lax

        n = n or self._world

        def leaf(x):
            if prescale != 1.0:
                x = x * jnp.asarray(prescale, dtype=x.dtype)
            if reduce_op in (_REDUCE_SUM, _REDUCE_AVERAGE):
                y = lax.psum(x, "proc")
                if reduce_op == _REDUCE_AVERAGE:
                    y = (y / n).astype(x.dtype)
            elif reduce_op == _REDUCE_MIN:
                y = lax.pmin(x, "proc")
            elif reduce_op == _REDUCE_MAX:
                y = lax.pmax(x, "proc")
            elif reduce_op == _REDUCE_PRODUCT:
                y = jnp.prod(
                    lax.all_gather(x, "proc"), axis=0
                ).astype(x.dtype)
            elif reduce_op == _REDUCE_ADASUM:
                from .adasum import adasum_allreduce

                y = adasum_allreduce(x, "proc")
            else:
                raise HorovodInternalError(
                    f"unknown reduce op {reduce_op}"
                )
            if postscale != 1.0:
                y = y * jnp.asarray(postscale, dtype=y.dtype)
            return y

        return leaf

    # ------------------------------------------------------- execution

    def _materialize(self, batch: ExecutionBatch,
                     tensors: Dict[str, np.ndarray]) -> List[np.ndarray]:
        """Per-tensor local inputs in batch order; zeros for tensors this
        process never enqueued (join semantics: a joined rank contributes
        zero tensors, reference collective_operations.h:325)."""
        np_dtype = DTYPE_TO_NUMPY.get(batch.dtype, "float32")
        if np_dtype == "bfloat16":
            import ml_dtypes

            np_dtype = ml_dtypes.bfloat16
        out = []
        for i, name in enumerate(batch.names):
            if name in tensors:
                t = tensors[name]
                # device-resident jax arrays stay on device (the
                # reference keeps GPU tensors on GPU through NCCL,
                # torch/mpi_ops.py) — np.asarray here would pull the
                # whole gradient to host just to push it back
                out.append(t if _is_jax_array(t) else np.asarray(t))
            else:
                shape = (
                    batch.shapes[i]
                    if i < len(batch.shapes)
                    else batch.first_shape
                )
                out.append(np.zeros(shape, dtype=np_dtype))
        return out

    def __call__(self, batch: ExecutionBatch,
                 tensors: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        op = batch.op
        if op == OP_ALLREDUCE:
            return self._run_allreduce(batch, tensors)
        if op == OP_REDUCESCATTER:
            return self._run_reducescatter(batch, tensors)
        if op == OP_ALLGATHER:
            return self._run_allgather(batch, tensors)
        if op == OP_BROADCAST:
            return self._run_broadcast(batch, tensors)
        if op == OP_ALLTOALL:
            return self._run_alltoall(batch, tensors)
        raise HorovodInternalError(
            f"executor received unknown op {op} for batch {batch.names} — "
            "refusing to pass input through unchanged"
        )

    def _run_allreduce(self, batch, tensors):
        from jax import lax
        import jax.numpy as jnp

        mesh, n, _, tag = self._batch_ctx(batch)
        inputs = self._materialize(batch, tensors)
        _record_wire_batch(self.wire, batch,
                           sum(int(np.size(x)) for x in inputs))
        wire = self.wire if _wire_applies(self.wire, batch) else None
        if wire is not None and wire.kind == "int8":
            return self._run_allreduce_int8(batch, tensors, inputs, mesh,
                                            n, tag)
        # autotuned hierarchical routing, stamped on the batch by the
        # NATIVE loop at batch creation (operations.cc Batch) so every
        # rank executes the sample point of the cycle that delivered it
        # — LIVE during the Bayes search so the x3/x4 dimensions score
        # real schedules, not noise (ADVICE r4). Global-set SUM/AVERAGE
        # only, mirroring ops/hierarchical.hierarchy_enabled_for.
        hier_block = 0
        if (getattr(batch, "tuned_hierarchical", False)
                and not tag
                and batch.reduce_op in (_REDUCE_SUM, _REDUCE_AVERAGE)):
            from .hierarchical import resolve_block

            hier_block = resolve_block(
                n, int(getattr(batch, "tuned_hier_block", 0)))
            if hier_block <= 1:
                hier_block = 0
        if hier_block:
            leaf = self._hier_reduce_leaf(
                batch.reduce_op, batch.prescale, batch.postscale, n,
                hier_block)
        else:
            leaf = self._reduce_leaf(
                batch.reduce_op, batch.prescale, batch.postscale, n
            )
        if wire is not None:
            # cast wire: ONE cast per fused bucket around the reduce —
            # the whole packed payload (prescale, psum, average divide,
            # postscale) runs in the wire dtype and casts back
            base_leaf, wd = leaf, wire.wire_dtype

            def leaf(x, _base=base_leaf, _wd=wd):
                return _base(x.astype(_wd)).astype(x.dtype)
        # Pack, reduce, and unpack INSIDE one program: one collective
        # HLO per fused batch (the reference memcpys into the fusion
        # buffer and issues one ncclAllReduce,
        # nccl_operations.cc:175-246) AND one device dispatch per batch
        # — host-side packing of device-resident gradients would pull
        # every tensor through the host,
        # and per-tensor result slicing would pay one dispatch per
        # gradient instead of per batch.
        # The bucket signature is memoized ON the batch: a cached-plan
        # step replays the same ExecutionBatch object every step, so
        # repeated grouped batches skip re-deriving the per-tensor spec
        # tuple and go straight to the cached fused program.
        memo = getattr(batch, "_ar_specs", None)
        if memo is None:
            memo = tuple((x.size, tuple(x.shape)) for x in inputs)
            batch._ar_specs = memo
        specs = memo

        def fused(*vs):
            flats = [v.reshape(-1) for v in vs]
            packed = (jnp.concatenate(flats)
                      if len(flats) > 1 else flats[0])
            red = leaf(packed)
            outs, off = [], 0
            for size, shape in specs:
                outs.append(lax.dynamic_slice_in_dim(
                    red, off, size).reshape(shape))
                off += size
            return tuple(outs)

        prog = self._program(
            ("allreduce", tag, specs, str(inputs[0].dtype),
             batch.reduce_op, batch.prescale, batch.postscale,
             hier_block, wire.key if wire is not None else None),
            fused, out_spec_sharded=False, mesh=mesh, arity=len(inputs),
        )
        res = prog(*[self._global_stack(x, mesh, n) for x in inputs])
        if not isinstance(res, (tuple, list)):
            res = (res,)
        out = {}
        for name, r in zip(batch.names, res):
            if name in tensors:
                out[name] = r
        return out

    def _run_allreduce_int8(self, batch, tensors, inputs, mesh, n, tag):
        """Fused allreduce on the int8 block-quantized wire: ONE program
        per fused bucket packs the tensors, adds the executor-held
        error-feedback residual, runs the quantized collective
        (hierarchical DCN-outer-leg routing when the coordinator pinned
        a hierarchy block, the flat EQuARX form otherwise), and slices
        the dequantized sum back out. The residual is a per-bucket
        device buffer keyed by the batch signature — the eager mirror of
        the SPMD path's optimizer-state residual (docs/compression.md)."""
        from jax import lax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..optim import compression as _comp
        from .hierarchical import hierarchical_psum, resolve_block

        spec = self.wire
        reduce_op = batch.reduce_op
        prescale, postscale = batch.prescale, batch.postscale
        hier_block = 0
        if getattr(batch, "tuned_hierarchical", False) and not tag:
            hier_block = resolve_block(
                n, int(getattr(batch, "tuned_hier_block", 0)))
            if hier_block <= 1:
                hier_block = 0
        memo = getattr(batch, "_ar_specs", None)
        if memo is None:
            memo = tuple((x.size, tuple(x.shape)) for x in inputs)
            batch._ar_specs = memo
        specs = memo
        total = sum(size for size, _ in specs)
        ef = spec.error_feedback
        rkey = (tuple(batch.names), specs, tag, spec.key, hier_block)

        def fused(*vs):
            if ef:
                vs, res = vs[:-1], vs[-1]
            else:
                res = None
            flats = [v.reshape(-1) for v in vs]
            packed = (jnp.concatenate(flats)
                      if len(flats) > 1 else flats[0])
            if prescale != 1.0:
                packed = packed * jnp.asarray(prescale, packed.dtype)
            if hier_block:
                out = hierarchical_psum(
                    packed, ("proc",), {"proc": n}, hier_block,
                    wire=spec, residual=res)
            else:
                out = _comp.quantized_psum(packed, "proc", n, spec.block,
                                           residual=res)
            y, new_res = out if ef else (out, None)
            if reduce_op == _REDUCE_AVERAGE:
                y = (y / n).astype(packed.dtype)
            if postscale != 1.0:
                y = y * jnp.asarray(postscale, y.dtype)
            outs, off = [], 0
            for size, shape in specs:
                outs.append(lax.dynamic_slice_in_dim(
                    y, off, size).reshape(shape))
                off += size
            if ef:
                return tuple(outs) + (new_res,)
            return tuple(outs)

        out_specs = tuple(P() for _ in specs)
        if ef:
            out_specs = out_specs + (P("proc"),)
        prog = self._program(
            ("allreduce_int8", tag, specs, str(inputs[0].dtype),
             reduce_op, prescale, postscale, hier_block, spec.key),
            fused, out_spec_sharded=False, mesh=mesh,
            arity=len(inputs) + (1 if ef else 0), out_specs=out_specs,
        )
        args = [self._global_stack(x, mesh, n) for x in inputs]
        if ef:
            res = self._wire_residuals.get(rkey)
            if res is None:
                res = jnp.zeros((total,), jnp.float32)
            args.append(self._global_stack(res, mesh, n))
        res_tuple = prog(*args)
        if ef:
            new_res = res_tuple[-1]
            res_tuple = res_tuple[:-1]
            # keep the residual on device, our shard only (the global
            # view is [world*total]; ours is the local addressable one).
            # Bound the store LRU-style: each entry is a bucket-sized
            # f32 device buffer, and plan churn (elastic reinit,
            # re-bucketing) would otherwise pin stale copies until OOM.
            # The cap (256) sits far above any real step's bucket count
            # (the residual working set is proportional to gradient
            # size, same as the SPMD path's state residual); hitting it
            # means eviction is silently degrading error feedback to
            # int8-raw for the cycled buckets — warn once.
            self._wire_residuals.pop(rkey, None)
            self._wire_residuals[rkey] = new_res.addressable_shards[0].data
            while len(self._wire_residuals) > 256:
                self._wire_residuals.pop(
                    next(iter(self._wire_residuals)))
                _warn_residual_eviction_once()
        out = {}
        for name, r in zip(batch.names, res_tuple):
            if name in tensors:
                out[name] = r
        return out

    def _run_reducescatter(self, batch, tensors):
        from jax import lax
        import jax.numpy as jnp

        mesh, n, _, tag = self._batch_ctx(batch)
        inputs = self._materialize(batch, tensors)
        reduce_op = batch.reduce_op
        prescale, postscale = batch.prescale, batch.postscale
        # pack the fused batch rank-major into ONE flat buffer so the
        # whole group runs as a single collective (reference: fused
        # responses memcpy into the fusion buffer and issue one
        # ncclReduceScatter): chunk k of every member concatenated, so a
        # tiled psum_scatter hands rank k exactly its chunks of every
        # member. Single-tensor batches reduce to the plain path.
        per_rank = [x.reshape(n, -1) for x in inputs]
        packed = (
            np.concatenate(per_rank, axis=1).reshape(-1)
            if len(per_rank) > 1 else per_rank[0].reshape(-1)
        )

        def leaf(v):
            if prescale != 1.0:
                v = v * jnp.asarray(prescale, dtype=v.dtype)
            y = lax.psum_scatter(
                v, "proc", scatter_dimension=0, tiled=True
            )
            if reduce_op == _REDUCE_AVERAGE:
                y = (y / n).astype(v.dtype)
            if postscale != 1.0:
                y = y * jnp.asarray(postscale, dtype=y.dtype)
            return y

        prog = self._program(
            ("reducescatter", tag, packed.shape, str(packed.dtype),
             reduce_op, prescale, postscale),
            leaf, out_spec_sharded=True, mesh=mesh,
        )
        res = np.asarray(
            self._local_shard(prog(self._global_stack(packed, mesh, n))))
        out, off = {}, 0
        for name, x in zip(batch.names, inputs):
            m = x.size // n
            if name in tensors:
                out[name] = res[off:off + m].reshape(
                    (x.shape[0] // n,) + x.shape[1:])
            off += m
        return out

    def _run_allgather(self, batch, tensors):
        from jax import lax

        mesh, n, _, tag = self._batch_ctx(batch)
        dims = [int(d) for d in batch.rank_dim0]  # set-local member order
        out = {}
        for i, name in enumerate(batch.names):
            x = (
                np.asarray(tensors[name]) if name in tensors
                else None
            )
            mx = max(dims) if dims else (x.shape[0] if x is not None else 0)
            # ragged: pad every contribution to the negotiated max dim-0,
            # gather uniformly, slice the real rows back out (reference
            # allgather size collection, controller.cc:497)
            if x is None:
                tail = tuple(
                    batch.shapes[i][1:] if i < len(batch.shapes)
                    else batch.first_shape[1:]
                )
                np_dtype = DTYPE_TO_NUMPY.get(batch.dtype, "float32")
                padded = np.zeros((mx,) + tail, dtype=np_dtype)
            elif x.shape[0] < mx:
                pad = [(0, mx - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
                padded = np.pad(x, pad)
            else:
                padded = x

            def leaf(v):
                return lax.all_gather(v, "proc", tiled=True)

            prog = self._program(
                ("allgather", tag, padded.shape, str(padded.dtype)),
                leaf, out_spec_sharded=False, mesh=mesh,
            )
            g = np.asarray(prog(self._global_stack(padded, mesh, n)))
            if name not in tensors:
                continue
            if dims and len(set(dims)) > 1:
                parts = [
                    g[r * mx:r * mx + dims[r]] for r in range(len(dims))
                ]
                out[name] = np.concatenate(parts, axis=0)
            else:
                out[name] = g
        return out

    def _run_broadcast(self, batch, tensors):
        from jax import lax
        import jax.numpy as jnp

        mesh, n, _, tag = self._batch_ctx(batch)
        inputs = self._materialize(batch, tensors)
        # root_rank is a GLOBAL rank (reference semantics, also for
        # process sets) — translate to the set-local mesh position
        root = batch.root_rank
        if tag:
            if root not in tag:
                raise HorovodInternalError(
                    f"broadcast root {root} is not a member of process "
                    f"set {batch.process_set_id} ({list(tag)})"
                )
            root = tag.index(root)
        out = {}
        for name, x in zip(batch.names, inputs):
            def leaf(v):
                mask = lax.axis_index("proc") == root
                if v.dtype == jnp.bool_:
                    # psum on bool promotes to int32; round-trip through
                    # int and cast back so the caller keeps its dtype
                    y = lax.psum(
                        jnp.where(mask, v, False).astype(jnp.int32), "proc"
                    )
                    return y.astype(jnp.bool_)
                return lax.psum(v * mask.astype(v.dtype), "proc")

            prog = self._program(
                ("broadcast", tag, x.shape, str(x.dtype), root),
                leaf, out_spec_sharded=False, mesh=mesh,
            )
            res = np.asarray(prog(self._global_stack(x, mesh, n)))
            if name in tensors:
                out[name] = res
        return out

    def _run_alltoall(self, batch, tensors):
        from jax import lax

        mesh, world, rank, tag = self._batch_ctx(batch)
        m = np.asarray(batch.all_splits, dtype=np.int64).reshape(
            (world, world)
        )
        recv_splits = m[:, rank]
        out = {}
        for name in batch.names:
            if name not in tensors:
                # a joined rank's row is all zeros; still participate
                x = np.zeros(
                    (0,) + tuple(batch.first_shape[1:]),
                    dtype=DTYPE_TO_NUMPY.get(batch.dtype, "float32"),
                )
            else:
                x = np.asarray(tensors[name])
            # pad each outgoing chunk to the matrix max, one uniform
            # all_to_all HLO, slice real rows back out (the static-shape
            # form XLA needs; reference operations.cc:1858 uneven splits)
            mx = int(m.max()) if m.size else 0
            offs = np.concatenate(([0], np.cumsum(m[rank])))
            chunks = []
            for j in range(world):
                c = x[offs[j]:offs[j + 1]]
                pad = [(0, mx - c.shape[0])] + [(0, 0)] * (c.ndim - 1)
                chunks.append(np.pad(c, pad))
            packed = np.concatenate(chunks, axis=0)

            def leaf(v):
                return lax.all_to_all(
                    v, "proc", split_axis=0, concat_axis=0, tiled=True
                )

            prog = self._program(
                ("alltoall", tag, packed.shape, str(packed.dtype)),
                leaf, out_spec_sharded=True, mesh=mesh,
            )
            res = self._local_shard(
                prog(self._global_stack(packed, mesh, world))
            )
            if name not in tensors:
                continue
            parts = [
                res[j * mx:j * mx + int(recv_splits[j])]
                for j in range(world)
            ]
            out[name] = (
                np.concatenate(parts, axis=0),
                recv_splits.copy(),
            )
        return out


def make_xla_executor(rank: Optional[int] = None,
                      world: Optional[int] = None) -> XlaExecutor:
    """Build the multi-controller XLA data plane. Requires
    jax.distributed to be initialized (hvd.init does this from the
    launcher-provided env; SURVEY.md §2.6 TPU equivalent row).

    rank/world default to — and are validated against — the JAX process
    topology; pass the EagerRuntime's configured values so a controller
    rank-order mismatch fails loudly instead of mis-slicing (ADVICE r2 #1).
    """
    import jax

    if rank is None:
        rank = jax.process_index()
    if world is None:
        world = jax.process_count()
    return XlaExecutor(rank, world)
