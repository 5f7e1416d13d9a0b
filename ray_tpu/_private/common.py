"""Shared data model: task/actor specs, resource sets, config.

Analog of the reference's src/ray/common/ (TaskSpec task/task_spec.h, fixed-point
resource arithmetic scheduling/fixed_point.h, RayConfig ray_config_def.h). Specs
are msgpack-serializable dicts with typed wrappers; resources use integer
fixed-point (1/10000 granularity) so fractional grants never drift.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Config. Pattern follows the reference's RAY_CONFIG table: every knob is
# overridable via environment variable RAY_TPU_<NAME>.
# ---------------------------------------------------------------------------

_CONFIG_DEFAULTS: Dict[str, Any] = {
    # Objects at or below this size live in the owner's in-process memory
    # store and move inline through RPCs; larger go to the shm store.
    "max_direct_call_object_size": 100 * 1024,
    # Default object store capacity fraction of system memory.
    "object_store_memory_fraction": 0.3,
    "object_store_memory_min": 64 * 1024 * 1024,
    # Worker lease / pool.
    # Zygote fork / worker process start: how long the raylet waits for the
    # forked pid before declaring the spawn wedged.
    "worker_start_timeout_s": 60.0,
    # How long an owner's idle leases park before returning to the raylet.
    # Bursty submitters reuse the full worker set across bursts; other
    # clients (and autoscaler idle scale-down) wait at most this long for
    # the pinned resources (in-flight lease requests force immediate
    # return).
    "worker_lease_idle_keep_s": 0.5,
    # Health checks (reference cadence: ray_config_def.h:847-853). The GCS
    # actively Pings every ALIVE node each period; `threshold` consecutive
    # misses mark it DEAD (catches wedged-but-connected raylets). period 0
    # disables active probing (connection loss still triggers death).
    "health_check_initial_delay_s": 5.0,
    "health_check_period_s": 3.0,
    "health_check_timeout_s": 10.0,
    "health_check_failure_threshold": 5,
    # Pubsub: per-subscriber bounded queue length; a subscriber falling this
    # far behind starts losing its OLDEST messages (publisher.h analog).
    "pubsub_max_buffered_msgs": 1000,
    # Task defaults.
    "default_max_task_retries": 3,
    # Lineage reconstruction: how many times a lost task-return object may be
    # recomputed by re-running its producing task (reference:
    # object_recovery_manager.h + task_manager.cc lineage bookkeeping).
    "max_lineage_reconstruction": 3,
    # Object transfer chunk size between nodes (the floor: adaptive sizing
    # scales the chunk with the object, see adaptive_chunk_size()).
    "object_chunk_size": 8 * 1024 * 1024,
    # Adaptive chunk cap: huge transfers use chunks up to this size so a
    # multi-GiB object doesn't pay per-chunk drain/round-trip overhead
    # hundreds of times. Blob frames stream chunks zero-copy, so a bigger
    # chunk costs no extra buffering on the send side.
    "object_chunk_size_max": 64 * 1024 * 1024,
    # Arena eviction: unpinned objects accessed within this window are never
    # evicted (their arena bytes could still be mid-read by a client).
    "object_store_eviction_grace_s": 10.0,
    # Scheduling: hybrid policy spills beyond this utilization (reference
    # scheduler_spread_threshold).
    "scheduler_spread_threshold": 0.5,
    "scheduler_top_k_fraction": 0.2,
    # Cluster-view delta batching: the GCS coalesces node resource/membership
    # changes for this long before publishing one versioned delta on
    # "syncer:nodes". 0 publishes immediately (one delta per mutation); at
    # hundreds of nodes batching caps the broadcast fan-out at
    # subscribers/batch_ms msgs/s instead of subscribers*grants/s.
    "scheduler_view_batch_ms": 0,
    # Raylet -> GCS UpdateResources debounce: once the dirty flag is set, a
    # raylet waits this long before reporting so a burst of grant/release
    # mutations folds into one round-trip instead of one each. 0 reports
    # per mutation (pre-PR-20 behavior); the idle 1 s heartbeat report is
    # unaffected either way.
    "raylet_report_debounce_s": 0.01,
    # Object spilling (reference: local_object_manager.cc +
    # external_storage.py): sealed objects are written to disk when the shm
    # arena fills and restored on access. Empty dir -> default under /tmp.
    "object_spilling_dir": "",
    # JSON spilling config selecting a registered external-storage backend,
    # e.g. '{"type": "filesystem", "params": {"directory_path": "/mnt/x"}}'
    # (reference: RAY_object_spilling_config). Empty -> filesystem under
    # object_spilling_dir.
    "object_spilling_config": "",
    # Spill/restore IO thread-pool width (reference: max_io_workers,
    # ray_config_def.h). IO runs off the raylet event loop so multi-GiB
    # spills never stall lease grants or RPCs.
    "max_io_workers": 4,
    # Bounded wait for the spill/restore IO pool to drain at node shutdown
    # (a wedged storage backend must not hang shutdown forever).
    "io_pool_shutdown_timeout_s": 10.0,
    # Proactive pressure loop: when arena occupancy exceeds this fraction the
    # raylet spills sealed-and-unpinned objects (largest-first) down to the
    # threshold without waiting for an allocation failure (reference:
    # object_spilling_threshold, ray_config_def.h). <= 0 disables the loop;
    # allocation-failure spilling still runs either way.
    "object_spilling_threshold": 0.8,
    # Pressure-loop poll interval.
    "object_spilling_poll_interval_s": 0.25,
    # Owner-side lineage cache budget: producing TaskSpecs retained for
    # reconstruction, LRU-pruned beyond this many bytes (reference:
    # RAY_max_lineage_bytes / lineage_pinning). Reconstruction of a pruned
    # object raises ObjectReconstructionFailedError.
    "lineage_bytes_limit": 64 * 1024 * 1024,
    # Cap on recursive lineage reconstruction: rebuilding a lost object may
    # find its producer's arguments also lost; each nesting level counts
    # toward this depth before the owner gives up with a typed error.
    "reconstruction_max_depth": 10,
    # serve: how long the controller waits for a replica to acknowledge a
    # user_config reconfigure before replacing it.
    "serve_reconfigure_timeout_s": 30.0,
    # serve: default end-to-end request budget the proxy stamps on ingress
    # requests (overridable per request via the serve-request-timeout-s
    # header). Rides the RPC TTL frames, so every downstream hop shrinks it.
    "serve_request_timeout_s": 60.0,
    # serve: default per-deployment router queue-depth cap (requests waiting
    # for a replica slot). Overflow sheds immediately with a typed
    # DeploymentOverloadedError, bounding memory under open-loop storms.
    # Per-deployment override: DeploymentConfig.max_queued_requests.
    "serve_max_queued_requests": 200,
    # serve: EWMA smoothing factor for the router's per-deployment service
    # time estimate (admission control sheds requests whose remaining
    # deadline budget cannot cover the estimate).
    "serve_admission_ewma_alpha": 0.2,
    # serve: admission safety factor — a request is shed unless its
    # remaining budget >= estimate * factor, so near-deadline requests
    # don't burn a replica slot only to be cut at the wire deadline.
    "serve_admission_safety_factor": 1.5,
    # serve: how often each router pushes queue-depth/ongoing metrics to the
    # controller (feeds the queue-driven autoscaler).
    "serve_router_metrics_interval_s": 0.5,
    # serve: how long a backpressured request waits for a freed replica slot
    # between admission re-checks.
    "serve_backpressure_poll_s": 0.5,
    # serve: controller-side timeout for one replica get_metrics sample.
    "serve_metrics_sample_timeout_s": 2.0,
    # serve: grace added on top of graceful_shutdown_timeout_s before the
    # controller force-kills a draining replica.
    "serve_shutdown_grace_s": 5.0,
    # serve: long-poll listen timeout (controller holds a listen open this
    # long before replying empty; clients immediately re-listen).
    "serve_long_poll_timeout_s": 30.0,
    # Create-request backpressure: how long ObjCreate waits for spill/eviction
    # to make room before failing (plasma create_request_queue.cc analog).
    "object_store_create_timeout_s": 30.0,
    # Task-event ring: max buffered owner-side task events between 1 Hz GCS
    # flushes; oldest drop first (reference: task_events_max_num_... knobs).
    "task_events_max_buffer": 10000,
    # Worker-side per-task profile events (deserialize/execute/store phase
    # timings in the chrome timeline). Off by default like the reference's
    # RAY_PROFILING — it adds one GCS event per task.
    "task_profile_events": False,
    # Native direct-call task channel (src/fastpath.cc): eligible
    # dependency-free tasks ride a C++-owned socket past the asyncio/msgpack
    # RPC stack (reference: the C++ direct task transport,
    # direct_task_transport.h:75). Auto-disabled per task when tracing or
    # profile events need the RPC path's instrumentation.
    "fastpath_enabled": True,
    # Max bytes of concurrent inbound object transfers a raylet admits
    # (reference: pull_manager.h bandwidth-capped pulls). Head-of-line
    # pulls exceed it rather than deadlock.
    "pull_max_bytes_in_flight": 256 * 1024 * 1024,
    # Inbound push-stream stall detection: a pull whose chunk assembly makes
    # no progress for this long (source died mid-push, chunks lost on a bad
    # link) aborts the assembly and re-requests the push instead of waiting
    # out the full blocking-get timeout + the 60s assembly janitor.
    "pull_stall_timeout_s": 5.0,
    # How many times a stalled push stream is re-requested before the pull
    # falls back to the request/reply chunk loop.
    "pull_max_rerequests": 2,
    # Fork workers from a preloaded zygote process (reference:
    # worker_pool.cc prestart) instead of cold `python -m` spawns —
    # ~10ms vs ~0.5-1.5s per worker, the difference between seconds and
    # minutes when a thousand actors start at once.
    "worker_zygote_enabled": True,
    # OTel-style task tracing spans with context propagation (reference:
    # ray.init(_tracing_startup_hook) + tracing_helper.py). Off by default.
    "task_trace_spans": False,
    # Sampled always-on tracing: fraction of new root traces recorded when
    # task_trace_spans is off (0.0 disables). The sampling decision is
    # deterministic on the root id, so every process on a request's path
    # independently agrees whether the trace exists (docs/observability.md
    # "Distributed tracing").
    "trace_sample_rate": 0.0,
    # Runtime-span ring: max spans buffered per process between flushes to
    # the GCS spans ring; oldest drop first (same shape as
    # task_events_max_buffer).
    "trace_span_buffer": 8192,
    # Push manager: max chunks in flight across ALL destination pushes from
    # one node (reference: push_manager.h max_chunks_in_flight). With 8 MiB
    # chunks the default bounds broadcast buffering at ~64 MiB.
    "push_manager_max_chunks": 8,
    # Memory monitor (reference: memory_monitor.h:52 + worker_killing_policy):
    # kill the newest leased worker when system memory use crosses the
    # threshold. interval 0 disables.
    "memory_monitor_interval_s": 1.0,
    "memory_usage_threshold": 0.95,
    # Pre-fault the shm arena's pages at raylet startup (background thread):
    # first-touch page allocation otherwise dominates large-object put latency
    # (~17 ms per 16 MiB on tmpfs). Off by default — it commits the whole
    # arena's physical memory and burns CPU proportional to capacity; prompt
    # free-span reuse makes steady-state puts hit warm pages anyway. Enable on
    # dedicated TPU hosts for cold-start-sensitive pipelines.
    "prefault_object_store": False,
    # GCS fault tolerance: persist control-plane state to a session-scoped
    # log file so a restarted GCS resumes with its actor/PG/KV/job tables
    # intact (reference: RedisStoreClient, redis_store_client.h:33). Cheap
    # (WAL write-through of few-hundred-byte records); disable for pure
    # in-memory control planes.
    "gcs_persistence": True,
    # Which durable store backs the GCS when persistence is on
    # (gcs_store.py): "wal" — append-only CRC-framed log with group commit
    # (one fsync per loop tick of mutations) and snapshot compaction;
    # "replicated" — that log mirrored to the HA standby (below);
    # "memory" — no durability even with a persist path (testing).
    "gcs_persist_backend": "wal",
    # Durability/sync policy for the durable backends
    # (docs/fault_tolerance.md): "batch" — group-commit fsync per loop tick
    # (an OS crash can lose the last tick; a process crash loses
    # nothing); "always" — fsync per record; "off" — never fsync (page
    # cache only).
    "gcs_store_sync": "batch",
    # WAL log-size threshold that triggers snapshot compaction (the full
    # table state is rewritten as one frame and the log truncated).
    "gcs_wal_compact_bytes": 4 * 1024 * 1024,
    # ---- HA control plane (gcs_ha.py, docs/fault_tolerance.md §HA). ----
    # Follower count for gcs_persist_backend=replicated. The group (primary
    # + followers) acks a group commit once a majority of members —
    # ⌈(n+1)/2⌉, the primary's own append included — holds it durably;
    # laggard members catch up asynchronously (per-member lag is exported
    # as gcs_replica_lag_seq). Default 2 → a 3-member group that tolerates
    # one slow/partitioned/lost member without stalling commits. With 1
    # follower the quorum is 2-of-2, i.e. the original synchronous
    # wait-for-all shipping.
    "gcs_replication_followers": 2,
    # How the warm standby receives the shipped stream (gcs_ha.py):
    # "rpc" — subscribe to the leader over ShipFrames/ShipSnapshot wire
    # RPCs (works across OS processes/hosts; falls back to file tailing
    # while the leader is unreachable); "file" — tail a follower log on
    # shared storage (original in-process mode).
    "gcs_standby_mode": "rpc",
    # Leadership lease duration. The leader re-asserts its leadership
    # record every lease/3; a standby promotes when the record's deadline
    # is this far in the past (plus one grace interval to absorb clock
    # skew between renew and tail-observation).
    "gcs_leader_lease_s": 2.0,
    # How often the warm standby polls the replicated log tail for new
    # frames and leadership-record changes.
    "gcs_standby_poll_s": 0.1,
    # Path of the leader pointer file ("host port\n", atomically replaced
    # on promotion) that cross-process clients resolve before re-dialing.
    # Empty → derived as <persist_path>.leader next to the store.
    "gcs_leader_file": "",
    # Echo captured worker stdout/stderr to the driver (reference:
    # ray.init(log_to_driver=True) + log_monitor.py streaming).
    "log_to_driver": True,
    # How long a caller waits for a PENDING/RESTARTING actor to come up
    # before failing the call (reference: gcs_client actor resolution).
    "actor_resolve_timeout_s": 300.0,
    # ---- RPC resilience budgets (reference: retryable_grpc_client.h +
    # gcs_rpc_client.h; every knob below replaces a former call-site
    # literal, enforced by the rpc-magic-timeout lint rule). ----
    # Control-plane probes and cancels (KillWorker, CancelWorkerLease,
    # CancelTask): quick request/reply, fail fast.
    "rpc_control_timeout_s": 10.0,
    # GCS-driven actor placement round trip (LeaseWorkerForActor): covers
    # lease queueing + worker spawn + CreateActor on the worker.
    "rpc_lease_timeout_s": 120.0,
    # Placement-group 2PC legs (Prepare/Commit/ReleasePGBundles).
    "rpc_pg_timeout_s": 30.0,
    # Raylet -> worker CreateActor (cold spawn + user __init__).
    "rpc_actor_create_timeout_s": 300.0,
    # Whole-object push between raylets (PushObject request/reply).
    "rpc_transfer_timeout_s": 120.0,
    # Per-chunk / per-stream-start transfers (FetchChunk, PushStart).
    "rpc_chunk_timeout_s": 60.0,
    # Client -> local raylet pull of a remote object (PullObject).
    "rpc_pull_timeout_s": 300.0,
    # Bulk senders' per-chunk TCP drain wait (push_manager): a destination
    # that keeps the socket above the high-water mark this long is wedged.
    "rpc_drain_timeout_s": 30.0,
    # Blocking ObjGet a puller falls back to when PullObject returned no
    # mapping (e.g. the seal is still in flight on the owner's connection).
    "rpc_object_get_timeout_s": 30.0,
    # Optional per-attempt cap on the retryable GCS channel: a lost reply
    # is re-issued (idempotent methods only) after this long instead of
    # riding out the caller's whole budget. 0 disables (production
    # default — the GCS channel carries long-polls like CreateActor
    # wait_alive); the chaos latency suite enables it.
    "rpc_default_timeout_s": 0.0,
    # Dial backoff (rpc.connect): full-jitter exponential, total-time cap.
    "rpc_dial_initial_backoff_s": 0.05,
    "rpc_dial_max_backoff_s": 1.0,
    "rpc_dial_total_s": 3.0,
    # Call-retry backoff (RetryableConnection) and the total budget a
    # caller waits out a GCS restart before the error surfaces.
    "rpc_retry_initial_backoff_s": 0.05,
    "rpc_retry_max_backoff_s": 2.0,
    "rpc_backoff_multiplier": 2.0,
    "rpc_reconnect_timeout_s": 30.0,
    # Deadline enforcement slack: a handler may finish (or unwind its
    # cancellation) this long past its wire deadline before the chaos
    # no-call-outlives-deadline invariant flags it.
    "rpc_deadline_grace_s": 0.5,
    # Worker subprocesses flush deadline_stats deltas (met/shed/enforced/
    # overruns) to the GCS at this cadence, plus once on Exit, so the
    # no-call-outlives-deadline invariant sees overruns inside
    # task-executing workers. 0 disables periodic reporting.
    "rpc_deadline_report_interval_s": 0.5,
    # Driver-side loop-thread bridge budgets (worker.py run_async): whole
    # cluster bring-up, and graceful shutdown before the loop is abandoned.
    "driver_bringup_timeout_s": 120.0,
    "driver_shutdown_timeout_s": 30.0,
    # ---- runtime telemetry plane (_private/telemetry.py). ----
    # Master switch for the per-process flush loops; the record hot paths
    # are unconditional (a bound-cell float add) and stay on regardless.
    "telemetry_enabled": True,
    # Per-process snapshot-and-reset flush cadence over ReportTelemetry.
    # 0 disables periodic flushing (exit flushes still run).
    "telemetry_flush_interval_s": 2.0,
    # Flight-recorder ring capacity (structured lifecycle events/process).
    "telemetry_flight_capacity": 4096,
    # A metrics snapshot (app-metric KV blob or telemetry gauge source)
    # older than this is treated as a dead process's leftovers: gauges are
    # dropped from /metrics and stale KV snapshots are GC'd.
    "metrics_stale_after_s": 30.0,
    # ---- Data-layer ingest pipeline (docs/perf.md "Ingest pipeline"). ----
    # How many block fetches iter_blocks/DataIterator keep in flight so
    # object-store pull overlaps batch assembly instead of serializing
    # against it. 1 reverts to serial get-per-block.
    "data_fetch_lookahead": 4,
    # streaming_split consumers iterate blocks in completion order (one
    # straggler read task delays only itself). Dataset-level iteration
    # (iter_batches/take/...) always preserves order regardless.
    "data_split_preserve_order": False,
}


class _Config:
    """Config table with RAY_TPU_* env overrides.

    Resolved values are cached on the instance (hot paths read config
    multiple times per task; an os.environ lookup per read costs ~1us
    each). Entry points that may run after test fixtures mutate the
    environment (ray_tpu.init, Cluster bring-up) call refresh().
    """

    def __getattr__(self, name: str):
        if name not in _CONFIG_DEFAULTS:
            raise AttributeError(name)
        env = os.environ.get(f"RAY_TPU_{name.upper()}")
        default = _CONFIG_DEFAULTS[name]
        if env is None:
            value = default
        elif isinstance(default, bool):
            value = env.lower() in ("1", "true", "yes")
        else:
            value = type(default)(env)
        self.__dict__[name] = value  # shadows __getattr__ until refresh()
        return value

    def refresh(self) -> None:
        self.__dict__.clear()


config = _Config()


def adaptive_chunk_size(total_size: int) -> int:
    """Transfer chunk size for an object of ``total_size`` bytes: the base
    ``object_chunk_size`` for small objects, scaling with the object (about
    a quarter of it) up to ``object_chunk_size_max``. Fewer, larger chunks
    amortize the per-chunk drain wait and control-frame overhead; blob
    framing keeps the send side zero-copy at any chunk size."""
    base = config.object_chunk_size
    cap = max(base, config.object_chunk_size_max)
    return max(base, min(cap, total_size // 4))


# ---------------------------------------------------------------------------
# Fixed-point resources (reference: src/ray/common/scheduling/fixed_point.h).
# ---------------------------------------------------------------------------

RESOURCE_UNIT = 10000  # 1.0 CPU == 10000 units


def to_fixed(amount: float) -> int:
    return int(round(amount * RESOURCE_UNIT))


def from_fixed(units: int) -> float:
    return units / RESOURCE_UNIT


class ResourceSet:
    """A bag of named resource quantities with exact arithmetic."""

    __slots__ = ("_units",)

    def __init__(self, amounts: Optional[Dict[str, float]] = None, _units=None):
        if _units is not None:
            self._units = {k: v for k, v in _units.items() if v != 0}
        else:
            self._units = {
                k: to_fixed(v) for k, v in (amounts or {}).items() if to_fixed(v) != 0
            }

    @classmethod
    def from_units(cls, units: Dict[str, int]) -> "ResourceSet":
        rs = cls.__new__(cls)
        rs._units = {k: v for k, v in units.items() if v != 0}
        return rs

    def to_units(self) -> Dict[str, int]:
        return dict(self._units)

    def to_dict(self) -> Dict[str, float]:
        return {k: from_fixed(v) for k, v in self._units.items()}

    def is_subset_of(self, other: "ResourceSet") -> bool:
        return all(other._units.get(k, 0) >= v for k, v in self._units.items())

    def __add__(self, other: "ResourceSet") -> "ResourceSet":
        units = dict(self._units)
        for k, v in other._units.items():
            nv = units.get(k, 0) + v
            if nv:
                units[k] = nv
            else:
                units.pop(k, None)
        rs = ResourceSet.__new__(ResourceSet)
        rs._units = units
        return rs

    def __sub__(self, other: "ResourceSet") -> "ResourceSet":
        units = dict(self._units)
        for k, v in other._units.items():
            nv = units.get(k, 0) - v
            if nv:
                units[k] = nv
            else:
                units.pop(k, None)
        rs = ResourceSet.__new__(ResourceSet)
        rs._units = units
        return rs

    def get(self, name: str) -> float:
        return from_fixed(self._units.get(name, 0))

    def is_empty(self) -> bool:
        return not self._units

    def nonnegative(self) -> bool:
        return all(v >= 0 for v in self._units.values())

    def keys(self):
        return self._units.keys()

    def __repr__(self):
        return f"ResourceSet({self.to_dict()})"

    def __eq__(self, other):
        return isinstance(other, ResourceSet) and self._units == other._units


# ---------------------------------------------------------------------------
# Specs. Kept as plain dicts on the wire; wrappers give attribute access.
# ---------------------------------------------------------------------------


@dataclass
class TaskSpec:
    """Everything a worker needs to execute one task invocation.

    Reference: TaskSpec proto (src/ray/protobuf/common.proto; max_task_retries
    at :666). args_blob is cloudpickle((args, kwargs)) with contained
    ObjectRefs reduced to descriptors; dependencies lists those refs so the
    executor resolves them before unpickling.
    """

    task_id: str  # hex
    job_id: str
    name: str
    func_id: str  # content hash; body in GCS function table
    args_blob: Optional[bytes]
    dependencies: List[Tuple[str, Tuple[str, int]]]  # (oid hex, owner addr)
    num_returns: int
    return_ids: List[str]
    resources: Dict[str, int]  # fixed-point units
    # Large-args path: the serialized (args, kwargs) lives in the shm store
    # under this id instead of args_blob.
    args_object: Optional[str] = None
    # Positions/keys of top-level ObjectRef arguments the executor resolves
    # to values before invoking the function (reference semantics).
    ref_positions: List[int] = field(default_factory=list)
    kw_ref_keys: List[str] = field(default_factory=list)
    max_retries: int = 0
    retry_exceptions: bool = False
    owner_addr: Optional[Tuple[str, int]] = None  # owner's object server
    # Actor fields.
    actor_id: Optional[str] = None
    actor_creation: bool = False
    actor_method: Optional[str] = None
    seq_no: int = -1
    caller_id: Optional[str] = None
    max_restarts: int = 0
    max_concurrency: int = 1
    max_task_retries: int = 0
    # Per-method concurrency groups (reference:
    # transport/concurrency_group_manager.cc): {"group": max_concurrency}.
    concurrency_groups: Optional[Dict[str, int]] = None
    concurrency_group: Optional[str] = None
    # Placement.
    pg_id: Optional[str] = None
    bundle_index: int = -1
    scheduling_strategy: Optional[dict] = None
    runtime_env: Optional[dict] = None
    # Named actor registration.
    actor_name: Optional[str] = None
    namespace: Optional[str] = None

    def to_wire(self) -> dict:
        return {k: getattr(self, k) for k in self.__dataclass_fields__}

    @classmethod
    def from_wire(cls, d: dict) -> "TaskSpec":
        known = {k: d[k] for k in cls.__dataclass_fields__ if k in d}
        return cls(**known)


@dataclass
class Bundle:
    """One placement-group bundle: a resource reservation on a single node."""

    resources: Dict[str, int]  # fixed-point
    node_id: Optional[str] = None  # filled once placed


@dataclass
class PlacementGroupSpec:
    pg_id: str
    bundles: List[Dict[str, int]]
    strategy: str  # PACK | SPREAD | STRICT_PACK | STRICT_SPREAD
    name: str = ""
    job_id: str = ""

    def to_wire(self) -> dict:
        return {
            "pg_id": self.pg_id,
            "bundles": self.bundles,
            "strategy": self.strategy,
            "name": self.name,
            "job_id": self.job_id,
        }

    @classmethod
    def from_wire(cls, d: dict) -> "PlacementGroupSpec":
        return cls(**d)


# ---------------------------------------------------------------------------
# Errors (analog of python/ray/exceptions.py).
# ---------------------------------------------------------------------------


class RayTpuError(Exception):
    pass


class TaskError(RayTpuError):
    """Wraps an exception raised by user task code; re-raised at ray.get."""

    def __init__(self, cause: BaseException, task_name: str = "", traceback_str: str = ""):
        self.cause = cause
        self.task_name = task_name
        self.traceback_str = traceback_str
        super().__init__(f"task {task_name!r} failed: {cause!r}\n{traceback_str}")


class WorkerCrashedError(RayTpuError):
    pass


class ActorDiedError(RayTpuError):
    pass


class ActorUnavailableError(RayTpuError):
    pass


class ObjectLostError(RayTpuError):
    pass


class ObjectReconstructionFailedError(ObjectLostError):
    """A lost object could not be rebuilt from lineage: the producing
    TaskSpec was pruned under lineage_bytes_limit, the producer was a
    ray.put / non-retriable actor task (no lineage exists), or the
    reconstruction recursion exceeded reconstruction_max_depth."""


class GetTimeoutError(RayTpuError, TimeoutError):
    pass


class TaskCancelledError(RayTpuError):
    pass


class PlacementGroupError(RayTpuError):
    pass
