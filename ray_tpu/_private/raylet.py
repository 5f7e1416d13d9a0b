"""Raylet: the per-node daemon.

TPU-native analog of the reference's raylet (src/ray/raylet/node_manager.cc):
worker-pool management, lease-based task scheduling with spillback, placement
group bundle 2PC resource accounting, and the node's shared-memory object
store (the plasma-store role: src/ray/object_manager/plasma/store.h — data
lives in one shm arena per node; a native StoreCore manages offsets, sealing,
pinning and LRU eviction; clients map the arena once and read/write at
offsets, zero-copy).

Accelerator detection: reports a ``TPU`` resource per local chip plus the
pod-slice gang resource ``TPU-{pod_type}-head`` on worker 0 of a slice,
mirroring the reference's TPUAcceleratorManager
(python/ray/_private/accelerators/tpu.py:75,382).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import os
import sys
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Set, Tuple

from ray_tpu._private import aiocheck, external_storage, rpc, shm, telemetry
from ray_tpu._private import pull_manager as pull_manager_mod
from ray_tpu._private.pull_manager import PullStalled
from ray_tpu._private.push_manager import PushManager
from ray_tpu._private.common import ResourceSet, adaptive_chunk_size, config
from ray_tpu._private.gcs import GcsClient
from ray_tpu._private.store_core import make_store_core
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

# Lease/worker-pool counters (cells bound per raylet in __init__ so
# in-process multi-raylet clusters attribute correctly) and object-store
# lifecycle counters. Gauges refresh from _tel_refresh_gauges at each
# pool/lease mutation.
_TEL_LEASE_GRANTED = telemetry.counter(
    "raylet", "lease_granted", "worker leases committed (grant ledger entries)"
)
_TEL_LEASE_RELEASED = telemetry.counter(
    "raylet", "lease_released", "leases released (worker returned or killed)"
)
_TEL_LEASE_CANCELLED = telemetry.counter(
    "raylet", "lease_cancelled", "queued lease requests cancelled"
)
_TEL_LEASE_DUPLICATE = telemetry.counter(
    "raylet", "lease_duplicate_avoided",
    "duplicate lease grants answered idempotently via the ledger",
)
_TEL_WORKERS_STARTED = telemetry.counter(
    "raylet", "workers_started", "worker processes spawned"
)
_TEL_WORKERS_EXITED = telemetry.counter(
    "raylet", "workers_exited", "worker processes reaped"
)
_TEL_WORKERS = telemetry.gauge("raylet", "workers", "worker processes attached")
_TEL_WORKERS_IDLE = telemetry.gauge(
    "raylet", "workers_idle", "idle pooled workers"
)
_TEL_LEASES_ACTIVE = telemetry.gauge("raylet", "leases_active", "live leases")
_TEL_LEASE_GRANT_LATENCY = telemetry.histogram(
    "raylet", "lease_grant_latency_s",
    "queue-to-grant latency of worker lease requests",
    buckets=telemetry.LATENCY_BUCKETS_S,
)
_TEL_LEASE_SPILLBACKS = telemetry.counter(
    "raylet", "lease_spillbacks",
    "lease requests redirected to another node (one per spillback hop)",
)
_TEL_LOCALITY_HITS = telemetry.counter(
    "raylet", "locality_hits",
    "lease requests placed on a node already holding the task's args",
)
_TEL_LOCALITY_MISSES = telemetry.counter(
    "raylet", "locality_misses",
    "lease requests with locality hints placed on a non-hinted node",
)
_TEL_NODE_UTIL = telemetry.gauge(
    "raylet", "node_utilization",
    "max per-resource utilization of this node (0..1)",
)
_TEL_OBJ_SEALED = telemetry.counter(
    "object", "sealed", "objects sealed in the local store"
)
_TEL_OBJ_EVICTED = telemetry.counter(
    "object", "evicted", "sealed objects LRU-evicted under allocation pressure"
)
_TEL_OBJ_SPILLED_BYTES = telemetry.counter(
    "object", "spilled_bytes", "bytes written to external spill storage"
)
_TEL_OBJ_RESTORED_BYTES = telemetry.counter(
    "object", "restored_bytes", "bytes restored from external spill storage"
)
_TEL_SPILL_LATENCY = telemetry.histogram(
    "object", "spill_latency_s", "external-storage write latency per object",
    buckets=telemetry.LATENCY_BUCKETS_S,
)
_TEL_RESTORE_LATENCY = telemetry.histogram(
    "object", "restore_latency_s", "external-storage read latency per object",
    buckets=telemetry.LATENCY_BUCKETS_S,
)
_TEL_ARENA_PRESSURE = telemetry.gauge(
    "object", "arena_pressure",
    "shm arena occupancy fraction (used/capacity) seen by the pressure loop",
)


def detect_tpu_resources() -> Dict[str, float]:
    """Probe local accelerators through the pluggable manager registry
    (reference: accelerators/__init__.py + TPUAcceleratorManager tpu.py:75 —
    env overrides, /dev/accel*, /dev/vfio, then GCE/GKE instance metadata
    for the pod slice). Daemons must not grab the chips, so nothing here
    touches the JAX runtime."""
    from ray_tpu._private.accelerators import detect_accelerator_resources

    return detect_accelerator_resources()


class ZygoteProc:
    """Process-like shim for a worker forked by the zygote (the asyncio
    subprocess API surface the raylet uses: pid/returncode/terminate/kill/
    wait + stdout/stderr StreamReaders). Exits arrive as zygote messages;
    wait() also polls the pid so a dead zygote cannot wedge teardown."""

    def __init__(self, pid: int, stdout, stderr):
        self.pid = pid
        self.returncode: Optional[int] = None
        self.stdout = stdout
        self.stderr = stderr
        self._exit_fut: asyncio.Future = asyncio.get_running_loop().create_future()

    def _report_exit(self, code: int) -> None:
        self.returncode = code
        if not self._exit_fut.done():
            self._exit_fut.set_result(code)

    def _signal(self, sig) -> None:
        if self.returncode is not None:
            raise ProcessLookupError(self.pid)
        os.kill(self.pid, sig)

    def terminate(self) -> None:
        import signal as _signal

        self._signal(_signal.SIGTERM)

    def kill(self) -> None:
        import signal as _signal

        self._signal(_signal.SIGKILL)

    async def wait(self) -> int:
        while self.returncode is None:
            try:
                return await asyncio.wait_for(asyncio.shield(self._exit_fut), 0.5)
            except asyncio.TimeoutError:
                try:
                    os.kill(self.pid, 0)
                except ProcessLookupError:
                    # Re-parented to init and reaped there (zygote gone).
                    self._report_exit(-1)
        return self.returncode


class _Zygote:
    """Owns the zygote process + its control socket; serializes fork
    requests (the zygote answers in order)."""

    def __init__(self, raylet: "Raylet"):
        self.raylet = raylet
        self.proc = None
        self.sock = None
        self.reader_task: Optional[asyncio.Task] = None
        self._pending: deque = deque()  # futures awaiting {"forked": pid}
        self._by_pid: Dict[int, ZygoteProc] = {}
        self._lock = asyncio.Lock()
        self.broken = False

    async def start(self, base_env: Dict[str, str]) -> None:
        import socket as _socket

        # Two channels (see worker_zygote.py): requests stay a plain
        # BLOCKING socket owned by us (asyncio must never flip its file
        # description to O_NONBLOCK — a nonblocking sendmsg under a fork
        # burst EAGAINs mid-message and corrupts the protocol); responses
        # are wrapped in an asyncio reader.
        req_ours, req_theirs = _socket.socketpair()
        resp_ours, resp_theirs = _socket.socketpair()
        self.sock = req_ours
        self.proc = await asyncio.create_subprocess_exec(
            sys.executable,
            "-m",
            "ray_tpu._private.worker_zygote",
            str(req_theirs.fileno()),
            str(resp_theirs.fileno()),
            env=base_env,
            pass_fds=[req_theirs.fileno(), resp_theirs.fileno()],
        )
        req_theirs.close()
        resp_theirs.close()
        # Keep the writer referenced: StreamWriter.__del__ closes the
        # transport, which would EOF the response channel.
        reader, self._writer = await asyncio.open_connection(sock=resp_ours)
        self.reader_task = rpc.spawn(self._read_loop(reader))

    async def _read_loop(self, reader) -> None:
        import json as _json

        try:
            while True:
                line = await reader.readline()
                if not line:
                    break
                msg = _json.loads(line)
                if "forked" in msg:
                    if self._pending:
                        fut = self._pending.popleft()
                        if not fut.done():
                            fut.set_result(msg["forked"])
                elif "exit" in msg:
                    proc = self._by_pid.pop(msg["exit"], None)
                    if proc is not None:
                        proc._report_exit(msg.get("code", -1))
        except Exception:
            pass
        finally:
            self.broken = True
            while self._pending:
                fut = self._pending.popleft()
                if not fut.done():
                    fut.set_exception(RuntimeError("zygote died"))

    async def fork_worker(self, env_overrides: Dict[str, str]) -> ZygoteProc:
        from ray_tpu._private.worker_zygote import send_msg

        out_r, out_w = os.pipe()
        err_r, err_w = os.pipe()
        try:
            async with self._lock:
                fut: asyncio.Future = asyncio.get_running_loop().create_future()
                self._pending.append(fut)
                try:
                    send_msg(
                        self.sock, {"env": env_overrides}, fds=[out_w, err_w]
                    )
                except BaseException:
                    # A failed/partial send corrupts the request framing and
                    # desynchronizes response matching: poison this zygote
                    # (callers fall back to exec spawn; a fresh zygote is
                    # started lazily) and drop the orphan future so later
                    # responses cannot misroute.
                    self.broken = True
                    try:
                        self._pending.remove(fut)
                    except ValueError:
                        pass
                    raise
            pid = await asyncio.wait_for(
                fut, timeout=config.worker_start_timeout_s
            )
        except BaseException:
            os.close(out_r)
            os.close(err_r)
            raise
        finally:
            os.close(out_w)
            os.close(err_w)
        loop = asyncio.get_running_loop()

        async def fd_reader(fd):
            reader = asyncio.StreamReader()
            protocol = asyncio.StreamReaderProtocol(reader)
            await loop.connect_read_pipe(lambda: protocol, os.fdopen(fd, "rb"))
            return reader

        proc = ZygoteProc(pid, await fd_reader(out_r), await fd_reader(err_r))
        self._by_pid[pid] = proc
        return proc

    async def stop(self) -> None:
        if self.reader_task is not None:
            self.reader_task.cancel()
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass
        if self.proc is not None:
            try:
                self.proc.terminate()
            except ProcessLookupError:
                pass
            try:
                await asyncio.wait_for(self.proc.wait(), 3)
            except asyncio.TimeoutError:
                try:
                    self.proc.kill()
                except ProcessLookupError:
                    pass
                await self.proc.wait()


class _SimWorkerConn:
    """Stand-in worker link for simulated-cluster raylets (sim_workers=True):
    satisfies the liveness checks the grant/duplicate/release paths make
    (closed flag, push_nowait) without a process or socket behind it."""

    __slots__ = ("closed",)

    def __init__(self):
        self.closed = False

    def push_nowait(self, method: str, payload: dict) -> None:
        pass

    def close(self) -> None:
        self.closed = True


class WorkerHandle:
    def __init__(self, worker_id: str, proc=None):
        self.worker_id = worker_id
        self.proc = proc
        self.conn: Optional[rpc.Connection] = None
        self.addr: Optional[Tuple[str, int]] = None
        self.fp_port: Optional[int] = None  # native fastpath channel port
        self.kill_requested = False  # kill arrived while fork in flight
        self.registered = asyncio.get_running_loop().create_future()
        self.lease_id: Optional[str] = None
        self.actor_id: Optional[str] = None
        self.job_id: Optional[str] = None
        self.demand: Optional[ResourceSet] = None
        self.idle_since = time.monotonic()


class LeaseRequest:
    def __init__(self, lease_id: str, demand: ResourceSet, payload: dict):
        self.lease_id = lease_id
        self.demand = demand
        self.payload = payload
        self.queued_at = time.monotonic()  # grant-latency histogram origin
        self.queued_wall = time.time()  # lease-lifecycle span origin
        # Trace context of the requesting frame (set by rpc dispatch around
        # the handler that constructs us). Grant-time spans are emitted long
        # after that dispatch task is gone, so the ctx is pinned here.
        self.trace_ctx = rpc._trace_ctx.get()
        self.grant_started: Optional[float] = None
        self.fut: asyncio.Future = asyncio.get_running_loop().create_future()


class _ArenaChunkSink:
    """Blob sink streaming one inbound PushChunk straight into the
    destination arena span. Every write re-validates the assembly: the
    condemned sweep or an abort can free (and something else reallocate)
    the span while the blob is mid-stream, and writing on would corrupt
    whoever reuses it. ``st`` identity is the guard — a fresh assembly for
    the same oid has a different dict."""

    __slots__ = ("raylet", "oid", "st", "pos")

    def __init__(self, raylet, oid: str, st: dict, off: int, size: int):
        self.raylet = raylet
        self.oid = oid
        self.st = st
        self.pos = st["offset"] + off

    def write(self, view) -> None:
        st = self.st
        if st is None:
            return
        r = self.raylet
        if r.push_assembly.get(self.oid) is not st:
            self.st = None  # aborted/superseded mid-blob: drop the rest
            return
        if self.oid in r.condemned:
            del r.push_assembly[self.oid]
            self.st = None
            return
        n = view.nbytes
        r.arena.view[self.pos : self.pos + n] = view
        self.pos += n
        st["recv"] += n
        st["last"] = time.monotonic()

    def done(self, ok: bool) -> None:
        st = self.st
        if st is None or self.raylet.push_assembly.get(self.oid) is not st:
            return
        if not ok:
            # Connection died mid-blob: the span holds a torn chunk.
            self.raylet._abort_push_assembly(self.oid)
            return
        if st["recv"] >= st["size"]:
            del self.raylet.push_assembly[self.oid]
            rpc.spawn(self.raylet._obj_seal(None, {"oid": self.oid}))


class Raylet:
    # Class-level fallbacks (unlabeled cells, placeholder node id) so
    # ledger/pool helpers stay callable on partially-constructed instances
    # (tests build bare Raylets with object.__new__); __init__ rebinds them
    # with the node label.
    node_id = "?"
    _tel_lease_granted = _TEL_LEASE_GRANTED.cell()
    _tel_lease_released = _TEL_LEASE_RELEASED.cell()
    _tel_lease_cancelled = _TEL_LEASE_CANCELLED.cell()
    _tel_lease_duplicate = _TEL_LEASE_DUPLICATE.cell()
    _tel_workers_started = _TEL_WORKERS_STARTED.cell()
    _tel_workers_exited = _TEL_WORKERS_EXITED.cell()
    _tel_workers = _TEL_WORKERS.cell()
    _tel_workers_idle = _TEL_WORKERS_IDLE.cell()
    _tel_leases_active = _TEL_LEASES_ACTIVE.cell()
    _tel_grant_latency = _TEL_LEASE_GRANT_LATENCY.cell()
    _tel_spillbacks = _TEL_LEASE_SPILLBACKS.cell()
    _tel_locality_hits = _TEL_LOCALITY_HITS.cell()
    _tel_locality_misses = _TEL_LOCALITY_MISSES.cell()
    _tel_node_util = _TEL_NODE_UTIL.cell()
    _tel_spilled_bytes = _TEL_OBJ_SPILLED_BYTES.cell()
    _tel_restored_bytes = _TEL_OBJ_RESTORED_BYTES.cell()
    _tel_spill_latency = _TEL_SPILL_LATENCY.cell()
    _tel_restore_latency = _TEL_RESTORE_LATENCY.cell()
    _tel_arena_pressure = _TEL_ARENA_PRESSURE.cell()

    # Mutation gate for the interleaving explorer (devtools/explore.py):
    # when True, both layers of the PR 2 duplicate-grant fix are disabled
    # (the ledger check in _is_duplicate_grant and the leases[] recovery
    # branch in _grant_inner), faithfully re-introducing the double-grant
    # bug so the explorer can prove it still finds it. Never set in
    # production code paths.
    _mutate_double_grant = False

    def __init__(
        self,
        gcs_addr: Tuple[str, int],
        session_name: str,
        host: str = "127.0.0.1",
        port: int = 0,
        resources: Optional[Dict[str, float]] = None,
        object_store_memory: Optional[int] = None,
        node_id: Optional[str] = None,
        labels: Optional[Dict[str, str]] = None,
        worker_env: Optional[Dict[str, str]] = None,
        sim_workers: bool = False,
        gcs_leader_file: Optional[str] = None,
    ):
        from ray_tpu._private.ids import NodeID

        # Simulated-cluster mode: grants attach in-process stub workers
        # instead of forking real worker subprocesses, so hundreds of
        # raylets fit in one process (tests/test_scale.py harness).
        self.sim_workers = sim_workers
        self._sim_worker_seq = 0
        # HA control plane: the leader pointer file this raylet (and its
        # workers, via env) re-resolves before every GCS redial, so a
        # failover re-targets the promoted standby (gcs_ha.py).
        self.gcs_leader_file = gcs_leader_file or config.gcs_leader_file or None

        self.node_id = node_id or NodeID.from_random().hex()
        self.session_name = session_name
        self.gcs_addr = gcs_addr
        self.labels = labels or {}
        self.worker_env = worker_env or {}
        self.server = rpc.Server(host, port)
        self.gcs: Optional[GcsClient] = None

        if resources is None:
            resources = {"CPU": float(os.cpu_count() or 1)}
            resources.update(detect_tpu_resources())
        resources.setdefault("node:" + self.node_id[:8], 1.0)
        self.total = ResourceSet(resources)
        self.available = ResourceSet(resources)

        # Object store.
        if object_store_memory is None:
            try:
                import psutil  # type: ignore

                mem = psutil.virtual_memory().total
            except ImportError:
                mem = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
            object_store_memory = max(
                config.object_store_memory_min,
                int(mem * config.object_store_memory_fraction),
            )
        self.store_capacity = object_store_memory
        # Arena store: one shm segment per node, offsets managed by the
        # (native) StoreCore — plasma's dlmalloc-over-mmap design. Created in
        # start(); obj_waiters holds futures blocking on unsealed objects,
        # obj_last_access drives the time-grace eviction filter.
        self.store = make_store_core(object_store_memory)
        self.arena_name = f"rt_{self.session_name[:10]}_{self.node_id[:10]}"
        self.arena: Optional[shm.Segment] = None
        self.obj_waiters: Dict[str, List[asyncio.Future]] = {}
        self.obj_last_access: Dict[str, float] = {}
        # Deleted objects are quarantined (not freed) for the grace window:
        # clients may still hold zero-copy views into their arena bytes.
        self.condemned: Dict[str, float] = {}
        # Spilled objects: oid -> (uri, size, pinned). Sealed objects are
        # written out via the pluggable ExternalStorage backend when the arena
        # fills and restored on access (reference: raylet LocalObjectManager
        # spill orchestration + python/ray/_private/external_storage.py).
        # Spill/restore IO runs on a thread pool, never on the event loop
        # (reference spills via async IO workers, local_object_manager.cc) —
        # `spilling` tracks in-flight writes (bytes still live in the arena
        # until the write lands), `restoring` coalesces concurrent reads.
        self.spilled: Dict[str, Tuple[str, int, bool]] = {}
        self.spilled_bytes = 0
        self.spilling: Dict[str, asyncio.Task] = {}
        self.restoring: Dict[str, asyncio.Future] = {}
        # Owner-pinned primary copies (PinObject): the spill scheduler and
        # LRU eviction never touch these, whatever the pressure — an owner
        # that pins is promising to unpin or delete.
        self.pinned_objects: set = set()
        base = config.object_spilling_dir or os.path.join(
            "/tmp", "ray_tpu_spill"
        )
        spill_ns = f"{self.session_name[:16]}_{self.node_id[:8]}"
        self.spill_dir = os.path.join(base, spill_ns)
        self.storage = external_storage.create_storage(
            config.object_spilling_config, self.spill_dir, namespace=spill_ns
        )
        self._io_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, config.max_io_workers),
            thread_name_prefix=f"spill-io-{self.node_id[:6]}",
        )
        # Cross-node transfer: source-side push fan-out with a global chunk
        # budget (reference: push_manager.h); `push_assembly` tracks inbound
        # pushes being written into unsealed spans.
        self.push_manager = PushManager(self)
        # Inbound transfer admission (reference: pull_manager.h prioritized,
        # bandwidth-capped pulls).
        from ray_tpu._private.pull_manager import PullManager

        self.pull_manager = PullManager(
            config.pull_max_bytes_in_flight,
            stall_timeout_s=config.pull_stall_timeout_s,
            max_rerequests=config.pull_max_rerequests,
        )
        # Preloaded fork server for fast worker spawn (reference:
        # worker_pool.cc prestart); started lazily on first spawn.
        self._zygote: Optional[_Zygote] = None
        self.push_assembly: Dict[str, Dict[str, int]] = {}
        # Per-worker stdout/stderr files (reference: session_latest/logs).
        import tempfile

        self.log_dir = os.path.join(
            tempfile.gettempdir(),
            f"ray_tpu_{self.session_name}",
            "logs",
            self.node_id[:8],
        )
        # Client holds (plasma's per-client buffer refcounts,
        # plasma/client.h): ObjGet increments for the calling connection,
        # ObjRelease decrements, disconnect clears. Held objects are never
        # freed/evicted, whatever their age.
        self.obj_holds: Dict[str, Dict[int, int]] = {}

        # Workers. Shared single-loop state mutated from many handlers;
        # aiocheck.track attributes mutations to asyncio tasks under
        # RAY_TPU_AIOCHECK=1 (no-op otherwise).
        self.workers: Dict[str, WorkerHandle] = aiocheck.track("raylet.workers")
        self.idle_workers: List[WorkerHandle] = []
        self.pending_leases: List[LeaseRequest] = []
        # Cluster-wide-infeasible leases parked off the FIFO grant queue
        # until the cluster scales (autoscaler demand input).
        self.infeasible_leases: List[LeaseRequest] = []
        self.leases: Dict[str, WorkerHandle] = aiocheck.track("raylet.leases")
        # Exactly-once grant ledger: every lease id this raylet has COMMITTED
        # to granting (recorded synchronously with the resource deduction,
        # before the async _grant task runs). A duplicated RequestWorkerLease
        # frame (retry, wire-level duplication — reproduced by the
        # RAY_TPU_AIOCHECK probe as a cross-task write-write on raylet.leases)
        # queues the same lease id twice; without the ledger the second grant
        # overwrites the first's leases[] entry and leaks that worker +
        # its resources forever. Bounded LRU: ids only need to outlive the
        # duplicate-arrival window, not the session.
        self.granted_lease_ids: "OrderedDict[str, bool]" = OrderedDict()
        # Actor lease ids whose grant+CreateActor is currently in flight:
        # distinguishes a wire-duplicated placement (mirror the original)
        # from a GCS re-placement of a completed lease (supersede it).
        self.actor_creations_in_flight: set = set()
        self.duplicate_lease_grants_avoided = 0
        # Grants spawned but not yet resolved: their resources are deducted
        # but the lease is not in `leases` yet, so ledger observers must
        # treat the node as busy while this is nonzero.
        self.grants_in_flight = 0

        # Telemetry cells bound to this raylet (in-process clusters run
        # several raylets in one registry; the label keeps them apart).
        _nid = self.node_id[:8]
        self._tel_lease_granted = _TEL_LEASE_GRANTED.cell(raylet=_nid)
        self._tel_lease_released = _TEL_LEASE_RELEASED.cell(raylet=_nid)
        self._tel_lease_cancelled = _TEL_LEASE_CANCELLED.cell(raylet=_nid)
        self._tel_lease_duplicate = _TEL_LEASE_DUPLICATE.cell(raylet=_nid)
        self._tel_workers_started = _TEL_WORKERS_STARTED.cell(raylet=_nid)
        self._tel_workers_exited = _TEL_WORKERS_EXITED.cell(raylet=_nid)
        self._tel_workers = _TEL_WORKERS.cell(raylet=_nid)
        self._tel_workers_idle = _TEL_WORKERS_IDLE.cell(raylet=_nid)
        self._tel_leases_active = _TEL_LEASES_ACTIVE.cell(raylet=_nid)
        self._tel_grant_latency = _TEL_LEASE_GRANT_LATENCY.cell(raylet=_nid)
        self._tel_spillbacks = _TEL_LEASE_SPILLBACKS.cell(raylet=_nid)
        self._tel_locality_hits = _TEL_LOCALITY_HITS.cell(raylet=_nid)
        self._tel_locality_misses = _TEL_LOCALITY_MISSES.cell(raylet=_nid)
        self._tel_node_util = _TEL_NODE_UTIL.cell(raylet=_nid)
        self._tel_spilled_bytes = _TEL_OBJ_SPILLED_BYTES.cell(raylet=_nid)
        self._tel_restored_bytes = _TEL_OBJ_RESTORED_BYTES.cell(raylet=_nid)
        self._tel_spill_latency = _TEL_SPILL_LATENCY.cell(raylet=_nid)
        self._tel_restore_latency = _TEL_RESTORE_LATENCY.cell(raylet=_nid)
        self._tel_arena_pressure = _TEL_ARENA_PRESSURE.cell(raylet=_nid)

        # Placement group bundles committed on this node:
        # pg_id -> {"base": ResourceSet deducted, "group": ResourceSet added}
        self.pg_prepared: Dict[str, ResourceSet] = {}
        self.pg_committed: Dict[str, Tuple[ResourceSet, ResourceSet]] = {}

        self._resources_dirty = asyncio.Event()
        # Full cluster view: pull-based with a ~1s TTL, consumed only by
        # cold paths (node affinity, label pick, locality hints beyond the
        # head, spillback fallback). The per-lease hot path never walks it.
        self._view: List[dict] = []
        self._view_time = 0.0
        self._view_map: Dict[str, dict] = {}
        self._view_addr: Dict[str, str] = {}  # "host:port" -> node_id
        self._view_fetched_epoch = -1
        self._view_fetch = None
        # Scheduling head (reference: ray_syncer.h:88, inverted): the GCS —
        # the one process that sees every resource report — keeps the
        # utilization-sorted order and broadcasts only the sorted head, so
        # a flush costs each subscriber O(head cap) instead of O(changed
        # nodes), and the per-lease pick walks the head: O(k), never
        # O(cluster). Each message replaces the previous head wholesale.
        self._head: List[dict] = []  # {node_id, addr, total, available, util}
        self._head_addr_map: Optional[Dict[str, dict]] = None  # lazy
        self._head_n = 0  # alive-node count cluster-wide
        self._head_version = -1
        # GCS shape epoch: bumped on membership/total-capacity change; keys
        # the SPREAD ring cache (ring membership only depends on totals, not
        # availability) and forces a full-view refetch when it moves.
        self._head_epoch = -1
        self._spread_rr = 0
        self._spread_ring: Optional[Tuple[int, tuple, list]] = None
        # Monotonic version on our own resource reports so the GCS can drop
        # stale/out-of-order updates.
        self._report_version = 0
        self._tasks: List[asyncio.Task] = []
        self._register_handlers()

    @property
    def store_used(self) -> int:
        return self.store.used

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> Tuple[str, int]:
        self.arena = shm.create(self.arena_name, self.store_capacity)
        if config.prefault_object_store:
            # Touch every arena page off the event loop so large-object puts
            # don't pay first-touch page faults (plasma_allocator.cc analog).
            import threading

            def _prefault(view=self.arena.view):
                try:
                    from ray_tpu._native import _shm as native_shm

                    native_shm.prefault(view, 4)
                except Exception:
                    try:
                        for off in range(0, len(view), 4096):
                            view[off] = view[off]
                    except Exception:
                        pass

            threading.Thread(target=_prefault, name="arena_prefault", daemon=True).start()
        addr = await self.server.start()
        self.server.on_disconnect(self._on_disconnect)
        # Duplex: the GCS calls back over this link (LeaseWorkerForActor,
        # KillWorker, PG prepare/commit), so expose our handlers on it.
        conn = await rpc.connect(*self.gcs_addr, handlers=self.server._handlers)
        resolver = None
        if self.gcs_leader_file:
            from ray_tpu._private import gcs_ha

            resolver = gcs_ha.file_resolver(self.gcs_leader_file)
        self.gcs = GcsClient(conn, resolver=resolver)
        self.addr = addr

        async def _register(client) -> None:
            # Initial registration AND post-GCS-restart re-registration
            # (reference: raylet side of NotifyGCSRestart,
            # node_manager.proto:373): a restarted GCS has no node table
            # until every raylet re-announces itself.
            payload = {
                "node_id": self.node_id,
                "addr": list(self.addr),
                "resources": self.total.to_units(),
                "labels": self.labels,
            }
            # Lease-picture rebuild: report the actor workers this node is
            # hosting so a restarted GCS confirms its restored-ALIVE actors
            # from re-registrations instead of probing each one.
            actors = [
                {"actor_id": h.actor_id, "worker_id": h.worker_id}
                for h in self.workers.values()
                if h.actor_id is not None
            ]
            if actors:
                payload["actors"] = actors
            await client.conn.call("RegisterNode", payload)
            # A restarted GCS numbers heads from zero: drop the stale head
            # and view so the next broadcast/pick resyncs from scratch.
            self._head_version = -1
            self._view_time = 0.0
            self._mark_dirty()

        self.gcs.on_reconnect(_register)
        await _register(self.gcs)
        await self.gcs.subscribe("syncer:nodes", self._on_view_head)
        self._tasks.append(rpc.spawn(self._resource_report_loop()))
        self._tasks.append(rpc.spawn(self._condemned_sweep_loop()))
        self._tasks.append(rpc.spawn(self._infeasible_retry_loop()))
        if config.memory_monitor_interval_s > 0:
            self._tasks.append(rpc.spawn(self._memory_monitor_loop()))
        if config.object_spilling_threshold > 0:
            self._tasks.append(rpc.spawn(self._pressure_loop()))
        logger.info(
            "raylet %s on %s:%s resources=%s",
            self.node_id[:8],
            addr[0],
            addr[1],
            self.total.to_dict(),
        )
        return addr

    async def stop(self) -> None:
        if self.gcs is not None:
            # Graceful departure: tell the GCS this node is leaving so the
            # dropped link is not reported as a health-check death.
            try:
                await asyncio.wait_for(
                    self.gcs.call("UnregisterNode", {"node_id": self.node_id}),
                    2,
                )
            except Exception:
                pass
            await self.gcs.close()  # before anything else: no re-registration
        for t in self._tasks:
            t.cancel()
        # Fail queued lease futures so their handler frames unwind now:
        # callers get a retryable error (or already saw the link drop) and
        # in-process harnesses (sim_cluster, chaos kill_raylet) don't
        # accumulate orphaned handler tasks until their loop closes.
        for req in self.pending_leases + self.infeasible_leases:
            if not req.fut.done():
                req.fut.set_exception(rpc.RpcError("raylet stopping"))
        self.pending_leases.clear()
        self.infeasible_leases.clear()
        procs = [w.proc for w in list(self.workers.values()) if w.proc is not None]
        for w in list(self.workers.values()):
            # Graceful first: the worker's Exit handler flushes and exits 0;
            # SIGTERM right behind it is the backstop for a wedged loop.
            if w.conn is not None and not w.conn.closed:
                try:
                    w.conn.push_nowait("Exit", {})
                except rpc.ConnectionLost:
                    pass
            self._kill_worker_proc(w)
        # Reap children through the event loop so their subprocess
        # transports close while the loop is alive — otherwise transport
        # __del__ at interpreter exit emits "child process exit status
        # already read" / "Event loop is closed" noise.
        if procs:
            try:
                await asyncio.wait_for(
                    asyncio.gather(*(p.wait() for p in procs), return_exceptions=True),
                    5,
                )
            except asyncio.TimeoutError:
                for p in procs:
                    try:
                        p.kill()
                    except ProcessLookupError:
                        pass
                await asyncio.gather(
                    *(p.wait() for p in procs), return_exceptions=True
                )
        if self._zygote is not None:
            try:
                await self._zygote.stop()
            except Exception:
                pass
            self._zygote = None
        # Quiesce spill IO before the arena unmaps: pool threads and
        # suspended spill/restore frames hold memoryview slices into it;
        # mmap.close() with exported views raises BufferError.
        spill_tasks = list(self.spilling.values())
        for t in spill_tasks:
            t.cancel()
        if spill_tasks:
            await asyncio.gather(*spill_tasks, return_exceptions=True)
        self.spilling.clear()
        # Delete each remaining spill file individually BEFORE destroy():
        # destroy() is a backstop (rmtree / delete_dir_contents) that some
        # backends implement partially or not at all, and a session sharing
        # an external bucket must not leak its per-object keys. The deletes
        # ride the IO pool; the bounded shutdown below drains them.
        del_futs = []
        for uri, _size, _pinned in self.spilled.values():
            try:
                del_futs.append(self._io_pool.submit(self.storage.delete, uri))
            except RuntimeError:
                break
        self.spilled.clear()
        self.spilled_bytes = 0
        self.pinned_objects.clear()
        if del_futs:
            try:
                await asyncio.wait_for(
                    asyncio.get_running_loop().run_in_executor(
                        None,
                        lambda: concurrent.futures.wait(
                            del_futs, timeout=config.io_pool_shutdown_timeout_s
                        ),
                    ),
                    timeout=config.io_pool_shutdown_timeout_s + 1,
                )
            except (asyncio.TimeoutError, RuntimeError):
                pass
        try:
            # Bounded: a wedged storage backend (stalled NFS/remote store)
            # must not hang node shutdown; the arena-close retry below copes
            # if a thread is abandoned mid-IO.
            await asyncio.wait_for(
                asyncio.get_running_loop().run_in_executor(
                    None,
                    lambda: self._io_pool.shutdown(wait=True, cancel_futures=True),
                ),
                timeout=config.io_pool_shutdown_timeout_s,
            )
        except (asyncio.TimeoutError, RuntimeError):
            logger.warning("spill IO pool did not quiesce; abandoning threads")
        for fut in list(self.restoring.values()):
            try:
                await asyncio.wait_for(asyncio.shield(fut), timeout=5)
            except Exception:
                pass
        try:
            self.storage.destroy()
        except Exception:
            pass
        self.push_assembly.clear()
        await self.push_manager.close()
        if self.arena is not None:
            for _ in range(100):
                try:
                    self.arena.close()
                    break
                except BufferError:
                    # An RPC handler frame still holds a view; it releases
                    # within a loop turn or two.
                    await asyncio.sleep(0.05)
            try:
                shm.unlink(self.arena_name)
            except Exception:
                pass
        await self.server.stop()
        if self.gcs is not None:
            await self.gcs.conn.close()

    def _register_handlers(self) -> None:
        s = self.server
        s.register("RegisterWorker", self._register_worker)
        s.register("RequestWorkerLease", self._request_worker_lease)
        s.register("CancelWorkerLease", self._cancel_worker_lease)
        s.register("ReturnWorker", self._return_worker)
        # Lease fast path: the unconstrained-grant/release/cancel cases run
        # inline from the read loop (no dispatch task, no deadline wrapper);
        # anything they can't settle synchronously falls through to the
        # async handlers registered above.
        s.register_sync("RequestWorkerLease", self._request_worker_lease_sync)
        s.register_sync("ReturnWorker", self._return_worker_sync)
        s.register_sync("CancelWorkerLease", self._cancel_worker_lease_sync)
        s.register("LeaseWorkerForActor", self._lease_worker_for_actor)
        s.register("KillWorker", self._kill_worker)
        s.register("ObjCreate", self._obj_create)
        s.register("ObjSeal", self._obj_seal)
        s.register("ObjGet", self._obj_get)
        s.register("ObjRelease", self._obj_release)
        s.register("ObjDelete", self._obj_delete)
        s.register("ObjContains", self._obj_contains)
        s.register("PullObject", self._pull_object)
        s.register("FetchChunk", self._fetch_chunk)
        s.register("SpillObjects", self._spill_objects)
        s.register("RestoreSpilled", self._restore_spilled)
        s.register("PinObject", self._pin_object)
        s.register("PushObject", self._push_object)
        s.register("PushStart", self._push_start)
        s.register_blob("PushChunk", self._push_chunk_sink)
        s.register("PreparePGBundles", self._prepare_pg)
        s.register("CommitPGBundles", self._commit_pg)
        s.register("ReleasePGBundles", self._release_pg)
        s.register("GetNodeStats", self._node_stats)
        s.register("GetLog", self._get_log)
        s.register("ListLogs", self._list_logs)
        s.register("Ping", self._ping)

    async def _ping(self, conn, p):
        return {"pong": True, "node_id": self.node_id}

    async def _list_logs(self, conn, p):
        """Log files captured on this node (reference: state API list_logs)."""
        try:
            names = sorted(os.listdir(self.log_dir))
        except OSError:
            names = []
        return {"node_id": self.node_id, "files": names}

    async def _get_log(self, conn, p):
        """Tail of one captured log (reference: state API get_log,
        python/ray/util/state/api.py:1183). Accepts a filename from
        ListLogs or a worker_id (+ stream)."""
        filename = p.get("filename")
        if filename is None and p.get("worker_id"):
            filename = os.path.basename(
                self._log_path(p["worker_id"], p.get("stream", "stderr"))
            )
        if filename is None or "/" in filename or ".." in filename:
            raise rpc.RpcError("GetLog needs a valid filename or worker_id")
        path = os.path.join(self.log_dir, filename)
        tail = int(p.get("tail") or 1000)

        def _read_tail() -> bytes:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - max(tail, 1) * 200))
                return f.read()

        try:
            # Log files can be large and live on slow disks; don't stall the
            # scheduler loop on the read.
            data = await asyncio.get_running_loop().run_in_executor(
                None, _read_tail
            )
        except OSError:
            return {"lines": [], "found": False}
        lines = data.decode("utf-8", "replace").splitlines()
        return {"lines": lines[-tail:], "found": True}

    # -- resource reporting --------------------------------------------------

    async def _resource_report_loop(self) -> None:
        debounce = config.raylet_report_debounce_s
        while True:
            # Hot path: under grant/release churn the dirty event is almost
            # always already set when we come back around — skip the
            # wait_for (a timer + waiter task per iteration, pure loop
            # churn) and optionally debounce so a burst of mutations folds
            # into one UpdateResources round-trip instead of one each.
            if not self._resources_dirty.is_set():
                # Park on the dirty event with a call_later heartbeat that
                # force-sets it after 1s — same "report at least every
                # second" behavior as wait_for(..., 1.0) without the wrapper
                # task wait_for creates per iteration (one extra task per
                # report at cluster scale).
                hb = asyncio.get_running_loop().call_later(
                    1.0, self._resources_dirty.set
                )
                try:
                    await self._resources_dirty.wait()
                finally:
                    hb.cancel()
            if debounce > 0 and self._resources_dirty.is_set():
                # Debounce on the wakeup path too: a lease cycle dirties the
                # ledger twice (grant, then release milliseconds later) —
                # reporting immediately on the first wake would send two
                # UpdateResources per lease where one suffices.
                await asyncio.sleep(debounce)
            self._resources_dirty.clear()
            self._tel_node_util.set(self._local_util())
            self._report_version += 1
            payload = {
                "node_id": self.node_id,
                "available": self.available.to_units(),
                "total": self.total.to_units(),
                "version": self._report_version,
            }
            # Steady state: reports ride as pushes — no reply frame, no
            # caller future, no timeout timer (the reference syncer's
            # ack-free stream). Safe because each report is the FULL
            # versioned resource state: a lost push is superseded by the
            # next report or the 1s idle heartbeat, and the GCS drops
            # out-of-order versions. Only when the link is down do we fall
            # back to gcs.call, whose retry machinery redials.
            try:
                conn = self.gcs.conn
                if conn is not None and not conn.closed:
                    conn.push_nowait("UpdateResources", payload)
                    continue
            except rpc.ConnectionLost:
                pass
            try:
                await self.gcs.call("UpdateResources", payload)
            except rpc.RpcError:
                logger.warning("gcs unreachable from raylet %s", self.node_id[:8])
                await asyncio.sleep(1.0)

    def _mark_dirty(self) -> None:
        # The node-util gauge refreshes in the report loop (once per
        # debounced report), not here: grant/release each mark dirty and
        # recomputing the max-ratio scan twice per lease is avoidable work
        # on the hot path.
        self._resources_dirty.set()

    # -- worker pool ---------------------------------------------------------

    async def _start_worker(self, container: Optional[dict] = None) -> WorkerHandle:
        from ray_tpu._private.ids import WorkerID

        worker_id = WorkerID.from_random().hex()
        env = dict(os.environ)
        # Ensure workers can import ray_tpu regardless of the driver's cwd.
        import ray_tpu

        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
        existing = env.get("PYTHONPATH", "")
        if pkg_root not in existing.split(os.pathsep):
            env["PYTHONPATH"] = (
                pkg_root + (os.pathsep + existing if existing else "")
            )
        env.update(self.worker_env)
        env.update(
            {
                "RAY_TPU_RAYLET_HOST": self.server.address[0],
                "RAY_TPU_RAYLET_PORT": str(self.server.address[1]),
                "RAY_TPU_GCS_HOST": self.gcs_addr[0],
                "RAY_TPU_GCS_PORT": str(self.gcs_addr[1]),
                "RAY_TPU_NODE_ID": self.node_id,
                "RAY_TPU_WORKER_ID": worker_id,
                "RAY_TPU_SESSION": self.session_name,
            }
        )
        if self.gcs_leader_file:
            env["RAY_TPU_GCS_LEADER_FILE"] = self.gcs_leader_file
        proc = None
        if container:
            # Containerized worker (reference: runtime_env/container.py):
            # the podman/docker argv wraps the same worker module; host
            # networking + /dev/shm keep RPC and plasma working.
            from ray_tpu.runtime_env.container import build_container_argv

            argv = build_container_argv(
                container, [sys.executable, "-m", "ray_tpu._private.worker_main"], env
            )
        elif config.worker_zygote_enabled:
            # Fork from the preloaded zygote (~10ms) instead of a cold exec
            # (~0.5-1.5s); fall back to exec if the zygote is broken.
            # The handle must be in self.workers BEFORE the fork: a forked
            # worker can connect and register faster than this coroutine
            # resumes, and _register_worker rejects unknown ids.
            handle = WorkerHandle(worker_id, None)
            self.workers[worker_id] = handle
            try:
                proc = await self._zygote_fork(env)
            except Exception as e:
                logger.warning("zygote fork failed (%r); exec fallback", e)
                proc = None
                del self.workers[worker_id]
            argv = [sys.executable, "-m", "ray_tpu._private.worker_main"]
        else:
            argv = [sys.executable, "-m", "ray_tpu._private.worker_main"]
        if proc is None:
            proc = await asyncio.create_subprocess_exec(
                *argv,
                env=env,
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.PIPE,
            )
        handle = self.workers.get(worker_id) or WorkerHandle(worker_id, None)
        handle.proc = proc
        self.workers[worker_id] = handle
        self._tel_workers_started.inc()
        telemetry.record_event(
            "raylet", "worker_started", worker_id=worker_id, node=self.node_id[:8]
        )
        self._tel_refresh_gauges()
        if handle.kill_requested:
            self._kill_worker_proc(handle)
        # Log pipeline (reference: log_monitor.py tailing session/logs/*):
        # worker output goes to per-worker session log files AND streams to
        # the driver via GCS pubsub.
        # Per-worker infrastructure tasks. The log pumps never touch
        # ledger/2PC state (a crashed pump loses log lines, nothing else);
        # the reaper IS the supervisor — worker exit drives the lease-ledger
        # repair in _handle_worker_exit, and there is no one to supervise
        # the supervisor.
        rpc.spawn(self._pump_worker_logs(handle, proc.stdout, "stdout"))  # rpc-flow: disable=unsupervised-spawn
        rpc.spawn(self._pump_worker_logs(handle, proc.stderr, "stderr"))  # rpc-flow: disable=unsupervised-spawn
        rpc.spawn(self._reap_worker(handle))  # rpc-flow: disable=unsupervised-spawn
        return handle

    async def _zygote_fork(self, env: Dict[str, str]) -> ZygoteProc:
        """Fork one worker from the (lazily started) zygote. env is the
        full worker environment; the base snapshot rides the zygote's own
        spawn, the per-worker delta rides the fork request."""
        z = self._zygote
        if z is None or z.broken:
            z = self._zygote = _Zygote(self)
            # the pool starts with the first worker asked for, not in init()
            with tracing.span("init.worker_pool"):
                await z.start(env)
        overrides = {
            k: v
            for k, v in env.items()
            if k.startswith("RAY_TPU_") or k not in os.environ
        }
        return await z.fork_worker(overrides)

    def _log_path(self, worker_id: str, stream: str) -> str:
        return os.path.join(
            self.log_dir, f"worker-{worker_id[:12]}.{'out' if stream == 'stdout' else 'err'}"
        )

    async def _pump_worker_logs(self, handle: WorkerHandle, pipe, stream: str) -> None:
        """Tail one worker pipe: append to the session log file, batch lines
        to the GCS ``logs`` pubsub channel (driver-side echo). Reference:
        python/ray/_private/log_monitor.py + worker stdout redirection."""
        os.makedirs(self.log_dir, exist_ok=True)
        path = self._log_path(handle.worker_id, stream)
        buf: List[str] = []
        last_flush = 0.0

        async def flush():
            nonlocal buf, last_flush
            if not buf or self.gcs is None:
                buf = []
                return
            lines, buf = buf, []
            last_flush = time.monotonic()
            try:
                await self.gcs.call(
                    "Publish",
                    {
                        "channel": "logs",
                        "msg": {
                            "worker_id": handle.worker_id,
                            "node_id": self.node_id,
                            "pid": handle.proc.pid,
                            "stream": stream,
                            "lines": lines,
                            "actor_id": handle.actor_id,
                            # Job attribution: known for actor workers (the
                            # creation spec carries job_id); pooled task
                            # workers serve whatever job leases them, so
                            # their lines are unattributed.
                            "job_id": handle.job_id,
                        },
                    },
                )
            except rpc.RpcError:
                pass

        carry = b""
        try:
            # Unbuffered append of already-read chunks to a local log file:
            # O(chunk) writes, and per-chunk executor hops would reorder the
            # pump. Accepted sync I/O.
            with open(path, "ab", buffering=0) as f:  # aio-lint: disable=blocking-call
                while True:
                    # Chunked read (not readline): immune to asyncio's 64 KiB
                    # line limit — a worker print()ing a huge repr must never
                    # kill the pump (an undrained pipe wedges the worker).
                    try:
                        chunk = await asyncio.wait_for(pipe.read(65536), timeout=0.5)
                    except asyncio.TimeoutError:
                        if buf and time.monotonic() - last_flush > 0.2:
                            await flush()
                        continue
                    if not chunk:
                        break
                    f.write(chunk)
                    carry += chunk
                    if len(carry) > (1 << 20):
                        # Pathological single line: ship it in pieces.
                        buf.append(carry.decode("utf-8", "replace"))
                        carry = b""
                    elif b"\n" in carry:
                        *lines, carry = carry.split(b"\n")
                        buf.extend(ln.decode("utf-8", "replace") for ln in lines)
                    if buf and (
                        len(buf) >= 100 or time.monotonic() - last_flush > 0.2
                    ):
                        await flush()
        except (OSError, ValueError, asyncio.CancelledError):
            pass
        finally:
            if carry:
                buf.append(carry.decode("utf-8", "replace"))
            await flush()

    async def _reap_worker(self, handle: WorkerHandle) -> None:
        await handle.proc.wait()
        self._handle_worker_exit(handle, f"exit code {handle.proc.returncode}")

    def _handle_worker_exit(self, handle: WorkerHandle, cause: str) -> None:
        if handle.worker_id not in self.workers:
            return
        del self.workers[handle.worker_id]
        if handle in self.idle_workers:
            self.idle_workers.remove(handle)
        self._tel_workers_exited.inc()
        telemetry.record_event(
            "raylet",
            "worker_exit",
            worker_id=handle.worker_id,
            node=self.node_id[:8],
            cause=cause,
        )
        self._tel_refresh_gauges()
        if handle.lease_id and handle.lease_id in self.leases:
            del self.leases[handle.lease_id]
            self._mark_lease_released(handle.lease_id)
            self._free_lease_resources(handle)
        if not handle.registered.done():
            handle.registered.set_exception(rpc.RpcError(f"worker died: {cause}"))
        if handle.actor_id:
            # _report_worker_death retries internally and the GCS also
            # learns of the death from the dropped worker connection —
            # ledger repair already happened above, synchronously.
            rpc.spawn(  # rpc-flow: disable=unsupervised-spawn
                self._report_worker_death(handle.worker_id, [handle.actor_id], cause)
            )

    async def _report_worker_death(self, worker_id, actor_ids, cause) -> None:
        try:
            await self.gcs.call(
                "ReportWorkerDied",
                {"worker_id": worker_id, "actor_ids": actor_ids, "cause": cause},
            )
        except rpc.RpcError:
            pass

    async def _register_worker(self, conn, p):
        handle = self.workers.get(p["worker_id"])
        if handle is None:
            raise rpc.RpcError("unknown worker")
        handle.conn = conn
        handle.addr = tuple(p["addr"])
        handle.fp_port = p.get("fp_port")
        conn.context["worker_id"] = p["worker_id"]
        if not handle.registered.done():
            handle.registered.set_result(handle)
        return {
            "node_id": self.node_id,
            "session_name": self.session_name,
            "gcs_addr": list(self.gcs_addr),
        }

    def _on_disconnect(self, conn: rpc.Connection) -> None:
        cid = id(conn)
        for oid, holds in list(self.obj_holds.items()):
            holds.pop(cid, None)
            if not holds:
                del self.obj_holds[oid]
        # Abort inbound pushes whose source link died: a half-assembled
        # unsealed span would otherwise stay unfetchable forever.
        for oid, st in list(self.push_assembly.items()):
            if st.get("conn") == cid:
                self._abort_push_assembly(oid)
        worker_id = conn.context.get("worker_id")
        if worker_id and worker_id in self.workers:
            handle = self.workers[worker_id]
            # Process may still be flushing; reaper handles true exit. If the
            # RPC link dropped but the process lives, kill it — a worker
            # without its raylet link is unmanageable.
            self._kill_worker_proc(handle)

    def _make_sim_worker(self) -> WorkerHandle:
        self._sim_worker_seq += 1
        wid = f"simw-{self.node_id[:8]}-{self._sim_worker_seq}"
        handle = WorkerHandle(wid)
        handle.sim = True  # type: ignore[attr-defined]
        handle.conn = _SimWorkerConn()  # type: ignore[assignment]
        handle.addr = tuple(self.addr)
        handle.registered.set_result(True)
        self.workers[wid] = handle
        self._tel_workers_started.inc()
        self._tel_refresh_gauges()
        return handle

    def _kill_worker_proc(self, handle: WorkerHandle) -> None:
        if getattr(handle, "sim", False):
            # Simulated worker: no process to reap — finalize synchronously
            # (conn closed + popped from the pool) so the exactly-once
            # invariants see the same end state a real exit produces.
            if handle.conn is not None:
                handle.conn.close()
            if self.workers.pop(handle.worker_id, None) is not None:
                self._tel_workers_exited.inc()
            self._tel_refresh_gauges()
            return
        if handle.proc is None:
            # Fork still in flight: remember the kill; _start_worker
            # delivers it the moment the pid is known.
            handle.kill_requested = True
            return
        try:
            handle.proc.terminate()
        except ProcessLookupError:
            pass

    async def _get_or_start_idle_worker(self) -> WorkerHandle:
        while self.idle_workers:
            handle = self.idle_workers.pop()
            if handle.worker_id in self.workers and handle.conn and not handle.conn.closed:
                return handle
        if self.sim_workers:
            return self._make_sim_worker()
        handle = await self._start_worker()
        await handle.registered
        return handle

    # -- leases --------------------------------------------------------------

    def _translate_pg_demand(self, demand: ResourceSet, pg_id, bundle_index) -> ResourceSet:
        """Rewrite resource names to the PG-scoped resources committed on this
        node (reference encodes bundles as CPU_group_<idx>_<pgid> custom
        resources; see placement_group_resource_manager.cc)."""
        if not pg_id:
            return demand
        units = {}
        for k, v in demand.to_units().items():
            if bundle_index is not None and bundle_index >= 0:
                units[f"{k}_group_{bundle_index}_{pg_id}"] = v
            else:
                units[f"{k}_group_{pg_id}"] = v
        # Gang membership marker so the lease only matches nodes w/ the PG.
        units[f"bundle_group_{pg_id}"] = 1
        return ResourceSet.from_units(units)

    # -- lease fast path (sync handlers, no dispatch task) -------------------

    def _lease_slow_path(self, conn, msgid, method: str, p: dict) -> None:
        """Hand a lease request the sync fast path cannot settle to the
        registered async handler, in its own dispatch task — exactly what
        rpc._on_message would have done had no sync handler existed. The
        ambient deadline/trace the sync dispatch established are re-read
        here and threaded through, so budgets and spans are unchanged."""
        rpc.spawn(  # rpc-flow: disable=unsupervised-spawn
            conn._dispatch(
                msgid, method, p, rpc.current_deadline(), rpc.current_trace_ctx()
            )
        )

    def _request_worker_lease_sync(self, conn, msgid, p) -> None:
        """Inline grant: the common case — an unconstrained lease that fits
        local resources with an idle (or sim) worker on hand and an empty
        queue — commits and replies without creating a single task. The
        semantic fast-path conditions mirror the async handler's
        local-grant route bit for bit: no strategy/locality/PG/spillback
        input (so no policy decision), hybrid policy would stay local
        (fits + util at or below the spread threshold), FIFO preserved
        (pending queue empty), no duplicate ledger hit, and no trace
        context (traced requests take the slow path so lease-lifecycle
        spans keep their exact shape). Everything else falls through to
        the async handler unchanged."""
        if (
            self.pending_leases
            or p.get("strategy")
            or p.get("locality")
            or p.get("spilled_from")
            or p.get("pg_id")
            or self._is_duplicate_grant(p["lease_id"])
            or rpc.current_trace_ctx() is not None
        ):
            self._lease_slow_path(conn, msgid, "RequestWorkerLease", p)
            return
        demand = ResourceSet.from_units(p.get("resources") or {})
        if not (
            demand.is_subset_of(self.available)
            and self._local_util() <= config.scheduler_spread_threshold
        ):
            self._lease_slow_path(conn, msgid, "RequestWorkerLease", p)
            return
        handle = None
        while self.idle_workers:
            h = self.idle_workers.pop()
            if h.worker_id in self.workers and h.conn and not h.conn.closed:
                handle = h
                break
        if handle is None:
            if self.sim_workers:
                handle = self._make_sim_worker()
            else:
                # Would need to spawn a worker process: async territory.
                self._lease_slow_path(conn, msgid, "RequestWorkerLease", p)
                return
        lease_id = p["lease_id"]
        self.available = self.available - demand
        self._mark_dirty()
        self._record_granted(lease_id)
        handle.lease_id = lease_id
        handle.demand = demand  # type: ignore[attr-defined]
        handle.leased_since = time.monotonic()  # type: ignore[attr-defined]
        handle.job_id = p.get("job_id") or handle.job_id
        self.leases[lease_id] = handle
        self._tel_refresh_gauges()
        self._tel_grant_latency.observe(0.0)
        conn.reply_nowait(
            msgid, "RequestWorkerLease", self._grant_reply(handle, lease_id)
        )

    def _return_worker_sync(self, conn, msgid, p) -> None:
        """ReturnWorker is synchronous end to end (ledger flip, resource
        refund, idle-pool push): reply inline, skip the dispatch task."""
        self._release_lease(p["lease_id"], p.get("dirty", False))
        conn.reply_nowait(msgid, "ReturnWorker", {"ok": True})

    def _cancel_worker_lease_sync(self, conn, msgid, p) -> None:
        conn.reply_nowait(
            msgid, "CancelWorkerLease", self._cancel_lease_inline(p["lease_id"])
        )

    async def _request_worker_lease(self, conn, p):
        if self._is_duplicate_grant(p["lease_id"]):
            # Duplicate of a lease this raylet already committed to granting
            # (wire-level frame duplication or a client retry): answer
            # idempotently instead of double-granting.
            return await self._duplicate_lease_reply(p["lease_id"])
        demand = ResourceSet.from_units(p.get("resources") or {})
        demand = self._translate_pg_demand(
            demand, p.get("pg_id"), p.get("bundle_index")
        )
        strategy = p.get("strategy") or {}
        # Node affinity (reference: scheduling_options.h NODE_AFFINITY).
        affinity = strategy.get("node_id")
        if affinity and affinity != self.node_id:
            target = None
            for n in await self._cluster_view():
                if n["node_id"] == affinity:
                    if demand.is_subset_of(ResourceSet.from_units(n["total"])):
                        target = {"node_id": affinity, "addr": n["addr"]}
                    break
            if target is not None:
                return self._spill_reply(target)
            if not strategy.get("soft"):
                raise rpc.RpcError(
                    f"node affinity target {affinity[:8]} not in cluster "
                    "or cannot fit the demand"
                )
            affinity = None  # soft fallback: schedule as if unconstrained
        elif affinity == self.node_id and not demand.is_subset_of(self.total):
            if not strategy.get("soft"):
                raise rpc.RpcError(
                    f"demand cannot fit on affinity target {affinity[:8]}"
                )
            affinity = None
        labels = strategy.get("labels")
        if labels:
            # Node-label policy (reference: scheduling_options.h NODE_LABEL
            # + NodeLabelSchedulingStrategy): hard expressions gate
            # eligibility; soft expressions rank among the eligible.
            from ray_tpu.util.scheduling_strategies import node_matches_labels

            if (
                p.get("spilled_from")
                and node_matches_labels(labels.get("hard") or {}, self.labels)
                and demand.is_subset_of(self.total)
            ):
                # Spilled here by a peer's label pick and we qualify: queue
                # locally instead of re-picking (avoids placement ping-pong
                # on lagging views).
                strategy = {k: v for k, v in strategy.items() if k != "labels"}
            else:
                target = await self._label_pick(demand, labels)
                if target is None:
                    raise rpc.RpcError(
                        f"no node matches label constraints {labels['hard']} "
                        "with capacity for the demand"
                    )
                if target["node_id"] != self.node_id:
                    return self._spill_reply(target)
                # Local node is the pick: fall through to queue here.
                strategy = {k: v for k, v in strategy.items() if k != "labels"}
        if not demand.is_subset_of(self.total):
            # Infeasible here — suggest spillback target from GCS view.
            target = await self._find_spillback_node(demand)
            if target is not None:
                return self._spill_reply(target)
            # Cluster-wide infeasible: park on a SIDE queue and wait rather
            # than fail — the demand shows up in pending_demand, the
            # autoscaler can add a node that fits, and the retry loop spills
            # the request there (reference: infeasible tasks warn and wait;
            # resource_demand_scheduler feeds on their shapes). Not on
            # pending_leases: the grant loop is FIFO and an unsatisfiable
            # head would block every feasible lease behind it.
            logger.warning(
                "infeasible resource demand %s on all current nodes; "
                "queueing until the cluster scales",
                demand.to_dict(),
            )
            req = LeaseRequest(p["lease_id"], demand, p)
            self.infeasible_leases.append(req)
            # Parking is the protocol: the demand feeds pending_demand /
            # the autoscaler, the retry loop spills the request once a
            # fitting node joins, and the client bounds the wait with its
            # lease RPC budget (duplicate-grant dedup makes retries safe).
            return await req.fut  # rpc-flow: disable=unbounded-await
        if not affinity and not p.get("spilled_from"):
            placed_by_locality = False
            hints = p.get("locality") or {}
            if hints:
                # Locality-aware placement (reference: locality-aware lease
                # policy): prefer a node already holding the task's args.
                # Counted once per lease — spilled-over requests never
                # re-enter this block.
                await self._cluster_view()
                pick = self._locality_pick(demand, hints)
                if pick is None:
                    self._tel_locality_misses.inc()
                elif pick["node_id"] != self.node_id:
                    self._tel_locality_hits.inc()
                    return self._spill_reply(pick)
                else:
                    self._tel_locality_hits.inc()
                    placed_by_locality = True
            if not placed_by_locality:
                # Scheduling policy (reference: hybrid_scheduling_policy.cc /
                # scheduling_policy.h SPREAD): decide local-vs-remote before
                # queueing. Spilled-over requests stay put to avoid ping-pong.
                target = await self._policy_pick(demand, strategy)
                if target is not None:
                    return self._spill_reply(target)
        req = LeaseRequest(p["lease_id"], demand, p)
        self.pending_leases.append(req)
        self._try_grant_leases()
        # Same parking contract as the infeasible queue above: resolved by
        # _grant (which repairs ledger state and resolves the future on
        # every failure path), bounded by the client's lease RPC budget.
        return await req.fut  # rpc-flow: disable=unbounded-await

    def _spill_reply(self, target: dict) -> dict:
        self._tel_spillbacks.inc()
        return {"spillback": target}

    # -- scheduling policy (reference: raylet/scheduling/policy/) ------------

    async def _infeasible_retry_loop(self) -> None:
        """Re-evaluate parked cluster-wide-infeasible leases: once a node
        that fits registers (autoscaler scale-up, manual join), spill the
        request to it. Local feasibility (this node grew) re-enters the
        normal grant queue."""
        while True:
            await asyncio.sleep(1.0)
            for req in list(self.infeasible_leases):
                if req.fut.done():
                    self.infeasible_leases.remove(req)
                    continue
                if req.demand.is_subset_of(self.total):
                    self.infeasible_leases.remove(req)
                    self.pending_leases.append(req)
                    self._try_grant_leases()
                    continue
                target = await self._find_spillback_node(req.demand)
                if target is None:
                    continue
                self.infeasible_leases.remove(req)
                if not req.fut.done():
                    req.fut.set_result(self._spill_reply(target))

    @staticmethod
    def _addr_key(addr) -> str:
        return f"{addr[0]}:{addr[1]}"

    def _on_view_head(self, msg: dict) -> None:
        """One scheduling-head broadcast from the GCS: {"v", "epoch", "n",
        "head"} where ``head`` is the head-cap least-utilized alive nodes in
        utilization order. State-based, not delta-based — each message
        replaces the previous head wholesale, so there is no sequence to
        gap-detect and a dropped broadcast only costs freshness until the
        next one. O(head cap) per flush regardless of cluster size."""
        v = msg.get("v", -1)
        if v <= self._head_version:
            return  # stale replay / out-of-order
        head = msg.get("head")
        if head is None:
            return
        self._head_version = v
        self._head = head
        self._head_n = msg.get("n", len(head))
        self._head_epoch = msg.get("epoch", -1)
        self._head_addr_map = None  # rebuilt lazily (locality path only)

    def _head_by_addr(self, key: str) -> Optional[dict]:
        m = self._head_addr_map
        if m is None:
            m = self._head_addr_map = {
                self._addr_key(n["addr"]): n for n in self._head
            }
        return m.get(key)

    async def _cluster_view(self) -> list:
        """Full GCS node view for the cold paths (node affinity, label
        pick, locality hints beyond the head, spillback fallback):
        pull-based with a ~1s TTL, refetched immediately when the GCS shape
        epoch moved past our snapshot (membership/total change — a ring or
        affinity decision must not run on departed-node data)."""
        now = time.monotonic()
        epoch_stale = (
            self._head_epoch >= 0
            and self._view_fetched_epoch != self._head_epoch
        )
        if now - self._view_time > 1.0 or epoch_stale:
            if self._view_fetch is None:
                self._view_fetch = rpc.spawn(self._fetch_view())
            # CancelledError propagates (handler cancellation must win);
            # fetch errors leave the stale view in place.
            await asyncio.shield(self._view_fetch)
        return self._view

    async def _fetch_view(self) -> None:
        try:
            reply = await self.gcs.call("GetAllNodes")
            alive = [n for n in reply["nodes"] if n["state"] == "ALIVE"]
            self._view = alive
            self._view_map = {n["node_id"]: n for n in alive}
            self._view_addr = {
                self._addr_key(n["addr"]): n["node_id"] for n in alive
            }
            self._view_time = time.monotonic()
            self._view_fetched_epoch = reply.get("epoch", -1)
        except rpc.RpcError:
            pass
        finally:
            self._view_fetch = None

    async def _node_by_id(self, node_id: str):
        for n in await self._cluster_view():
            if n["node_id"] == node_id:
                return {"node_id": node_id, "addr": n["addr"]}
        return None

    @staticmethod
    def _node_total_rs(node: dict) -> ResourceSet:
        """Lazily parsed ResourceSet for a view node's totals, cached on
        the node dict (which is replaced wholesale on every delta, so the
        cache invalidates for free)."""
        rs = node.get("_total_rs")
        if rs is None:
            rs = node["_total_rs"] = ResourceSet.from_units(node["total"])
        return rs

    @staticmethod
    def _node_avail_rs(node: dict) -> ResourceSet:
        rs = node.get("_avail_rs")
        if rs is None:
            rs = node["_avail_rs"] = ResourceSet.from_units(node["available"])
        return rs

    @staticmethod
    def _node_util(total: Dict[str, int], available: Dict[str, int]) -> float:
        util = 0.0
        for k, tot in total.items():
            if tot > 0 and not k.startswith("node:"):
                util = max(util, 1.0 - available.get(k, 0) / tot)
        return util

    def _local_util(self) -> float:
        # Read the unit dicts directly (no defensive copies): _node_util
        # only iterates, and this runs once per grant on the fast path.
        return self._node_util(self.total._units, self.available._units)

    async def _policy_pick(self, demand: ResourceSet, strategy: dict):
        """Pick a remote target per policy, or None to queue locally.

        Hybrid (default, reference hybrid_scheduling_policy.cc): pack locally
        while local utilization stays at or below the spread threshold; past
        it, move work to a random choice among the top-k least-utilized
        feasible nodes (randomization spreads herds of simultaneous
        schedulers). SPREAD: always place on the least-loaded feasible node,
        round-robin-ish via the same top-k randomization.

        Per-lease work is O(k), not O(nodes): candidates come from the
        GCS-sorted scheduling head the syncer broadcasts, and the SPREAD
        ring is cached per (shape epoch, demand shape).
        """
        import random

        spread = strategy.get("spread", False)
        local_fits = demand.is_subset_of(self.available)
        if spread:
            # SPREAD: rotate over every node whose TOTAL fits the demand
            # (a lagging availability view must not collapse the rotation
            # onto one node). Ring membership only changes with cluster
            # membership/capacity, so the full-view scan is paid per shape
            # epoch, not per lease.
            key = tuple(sorted(demand.to_units().items()))
            cached = self._spread_ring
            epoch = self._head_epoch
            if cached is not None and cached[0] == epoch and cached[1] == key:
                ring = cached[2]
            else:
                ring = [
                    n
                    for n in await self._cluster_view()
                    if demand.is_subset_of(self._node_total_rs(n))
                ]
                ring.sort(key=lambda n: n["node_id"])
                self._spread_ring = (epoch, key, ring)
            if not ring:
                return None
            pick = ring[self._spread_rr % len(ring)]
            self._spread_rr += 1
            if pick["node_id"] == self.node_id:
                return None
            return {"node_id": pick["node_id"], "addr": pick["addr"]}
        if local_fits and self._local_util() <= config.scheduler_spread_threshold:
            return None
        # Walk the GCS-sorted head ascending and stop after k feasible
        # candidates — the k least-utilized nodes that can run the demand
        # right now. Cold start (no broadcast yet): sort the pulled view.
        head = self._head
        n_alive = self._head_n
        if not head:
            head = sorted(
                await self._cluster_view(),
                key=lambda n: self._node_util(n["total"], n["available"]),
            )
            n_alive = len(head)
        k = max(1, int(n_alive * config.scheduler_top_k_fraction))
        cands = []
        for n in head:
            if n["node_id"] == self.node_id:
                continue
            if demand.is_subset_of(self._node_avail_rs(n)):
                cands.append(
                    (
                        n.get("util", self._node_util(n["total"], n["available"])),
                        n,
                    )
                )
                if len(cands) >= k:
                    break
        if not cands:
            return None
        below = [
            c for c in cands if c[0] < config.scheduler_spread_threshold
        ]
        pool = below or cands
        pick_util, pick = random.choice(pool)
        if local_fits and self._local_util() <= pick_util:
            return None  # we're no worse than the best remote; stay local
        return {"node_id": pick["node_id"], "addr": pick["addr"]}

    def _locality_pick(self, demand: ResourceSet, hints: Dict[str, float]):
        """Locality-aware placement: among the nodes named by the task's arg
        locations (addr-keyed weights from the owner), pick the
        heaviest-weighted one that can run the demand RIGHT NOW — requiring
        current availability keeps a saturated arg holder from queueing the
        lease behind its backlog. Returns the pick ({"node_id", "addr"};
        node_id == ours means stay local) or None when no hinted node is
        feasible (a locality miss; the regular policy decides)."""
        local_w = -1.0
        self_key = self._addr_key(self.server.address)
        if self_key in hints and demand.is_subset_of(self.available):
            local_w = hints[self_key]
        best_n = None
        best_w = -1.0
        for key, w in hints.items():
            if key == self_key:
                continue
            # Head entries carry fresher availability than the TTL'd view —
            # overlay them over the pulled snapshot.
            n = self._head_by_addr(key)
            if n is None:
                nid = self._view_addr.get(key)
                n = self._view_map.get(nid) if nid is not None else None
            if n is None or not demand.is_subset_of(self._node_avail_rs(n)):
                continue
            if w > best_w:
                best_n, best_w = n, w
        if local_w >= best_w and local_w >= 0:
            # Ties prefer local: the bytes are already here and the grant
            # skips a spillback hop.
            return {"node_id": self.node_id, "addr": list(self.server.address)}
        if best_n is not None:
            return {"node_id": best_n["node_id"], "addr": best_n["addr"]}
        return None

    async def _label_pick(self, demand: ResourceSet, labels: dict):
        """NODE_LABEL policy: hard-eligible nodes, soft-matching preferred,
        least-utilized wins (capacity-feasible now preferred over
        total-feasible). Returns None when no node can ever satisfy."""
        from ray_tpu.util.scheduling_strategies import node_matches_labels

        hard = labels.get("hard") or {}
        soft = labels.get("soft") or {}
        eligible = []
        for n in await self._cluster_view():
            if not node_matches_labels(hard, n.get("labels") or {}):
                continue
            if not demand.is_subset_of(ResourceSet.from_units(n["total"])):
                continue
            eligible.append(n)
        if not eligible:
            return None
        if soft:
            preferred = [
                n
                for n in eligible
                if node_matches_labels(soft, n.get("labels") or {})
            ]
            pool = preferred or eligible
        else:
            pool = eligible
        now_fits = [
            n
            for n in pool
            if demand.is_subset_of(ResourceSet.from_units(n["available"]))
        ]
        pool = now_fits or pool
        pool.sort(
            key=lambda n: self._node_util(n["total"], n["available"])
        )
        pick = pool[0]
        return {"node_id": pick["node_id"], "addr": pick["addr"]}

    async def _cancel_worker_lease(self, conn, p):
        """Cancel a queued (ungranted) lease request: the surplus-request
        drain that keeps recycled-lease pools from pinning the raylet queue
        (reference: NodeManagerService CancelWorkerLease)."""
        return self._cancel_lease_inline(p["lease_id"])

    def _cancel_lease_inline(self, lease_id: str) -> dict:
        if self.granted_lease_ids.get(lease_id):
            # Already committed to granting: too late to cancel. Any queued
            # duplicate of this id mirrors the grant reply instead — setting
            # it "cancelled" here could beat the grant reply to the shared
            # msgid and strand a granted worker the client abandoned.
            return {"ok": True}
        for req in list(self.pending_leases) + list(self.infeasible_leases):
            # Resolve EVERY queued copy: wire duplication can queue the same
            # lease id twice, and a survivor would be granted to a client
            # that has moved on.
            if req.lease_id == lease_id and not req.fut.done():
                req.fut.set_result({"cancelled": True})
        # Burn the id so a late-arriving duplicate frame cannot re-queue a
        # grantable request for it.
        self._burn_lease_id(lease_id)
        self._tel_lease_cancelled.inc()
        telemetry.record_event(
            "raylet", "lease_cancelled", lease_id=lease_id, node=self.node_id[:8]
        )
        return {"ok": True}

    _GRANT_LEDGER_CAP = 4096

    def _tel_refresh_gauges(self) -> None:
        """Re-sample the worker-pool/lease gauges (three float stores);
        called from every pool or lease-table mutation site."""
        self._tel_workers.set(len(self.workers))
        self._tel_workers_idle.set(len(self.idle_workers))
        self._tel_leases_active.set(len(self.leases))

    def _record_granted(self, lease_id: str) -> None:
        self.granted_lease_ids[lease_id] = True  # True = live (not released)
        self._tel_lease_granted.inc()
        telemetry.record_event(
            "raylet", "lease_granted", lease_id=lease_id, node=self.node_id[:8]
        )
        while len(self.granted_lease_ids) > self._GRANT_LEDGER_CAP:
            self.granted_lease_ids.popitem(last=False)

    def _mark_lease_released(self, lease_id: str) -> None:
        if lease_id in self.granted_lease_ids:
            self.granted_lease_ids[lease_id] = False

    def _burn_lease_id(self, lease_id: str) -> None:
        """Record a lease id as spent without a live grant (cancelled): task
        ids are single-use, so any later request for it is a duplicate and
        resolves ``cancelled`` instead of granting."""
        self.granted_lease_ids[lease_id] = False
        while len(self.granted_lease_ids) > self._GRANT_LEDGER_CAP:
            self.granted_lease_ids.popitem(last=False)

    def _is_duplicate_grant(self, lease_id: str) -> bool:
        """True when granting this id (again) would double-grant. Task lease
        ids are unique per request, so any ledger entry — live or released —
        marks a duplicate. Actor lease ids are legitimately reused on
        restart, so only a LIVE entry counts."""
        if self._mutate_double_grant:
            return False  # seeded bug: forget every previous grant
        state = self.granted_lease_ids.get(lease_id)
        if state is None:
            return False
        return state if lease_id.startswith("actor:") else True

    async def _duplicate_lease_reply(self, lease_id: str) -> dict:
        """Reply for a duplicate request for an already-committed lease id.

        The committed grant may still be in flight (worker spawning), and
        duplicated frames share a msgid — whichever reply lands first wins at
        the client. Answering ``cancelled`` while the real grant resolves
        would make the client abandon a lease the raylet then completes
        (wedged task + leaked worker), so wait for the outcome: reply
        idempotently with the granted worker, or ``cancelled`` once the
        grant failed or the lease was already released.
        """
        self.duplicate_lease_grants_avoided += 1
        self._tel_lease_duplicate.inc()
        telemetry.record_event(
            "raylet", "lease_duplicate", lease_id=lease_id, node=self.node_id[:8]
        )
        loop = asyncio.get_running_loop()
        deadline = loop.time() + 30.0
        while loop.time() < deadline:
            handle = self.leases.get(lease_id)
            if handle is not None and handle.addr is not None:
                # Mirror only a grant whose worker link is still up. A leased
                # worker that died keeps its leases[] entry until the reaper
                # runs, but callers learn of the death sooner (its GCS/raylet
                # conns drop on exit) and re-request the lease; mirroring the
                # doomed grant would hand them a dead worker they wait on
                # forever (seen as a GCS actor parked in RESTARTING).
                if (
                    handle.worker_id in self.workers
                    and handle.conn is not None
                    and not handle.conn.closed
                ):
                    return self._grant_reply(handle, lease_id)
            if not self.granted_lease_ids.get(lease_id, False):
                break  # grant failed, or lease released: nothing to mirror
            await asyncio.sleep(0.01)
        return {"cancelled": True}

    def _resolve_duplicate_lease(self, req: LeaseRequest) -> None:
        # Supervision is internal: the coroutine resolves req.fut on every
        # path, including exceptions from the mirror wait.
        rpc.spawn(self._resolve_duplicate_lease_async(req))  # rpc-flow: disable=unsupervised-spawn

    async def _resolve_duplicate_lease_async(self, req: LeaseRequest) -> None:
        try:
            reply = await self._duplicate_lease_reply(req.lease_id)
        except Exception as e:
            # A crashed mirror must still resolve the future — the client
            # is parked on it and would otherwise wait forever.
            if not req.fut.done():
                req.fut.set_exception(
                    rpc.RpcError(f"duplicate-lease resolution failed: {e!r}")
                )
            return
        if not req.fut.done():
            req.fut.set_result(reply)

    def _try_grant_leases(self) -> None:
        granted_any = True
        while granted_any and self.pending_leases:
            granted_any = False
            req = self.pending_leases[0]
            if req.fut.done():
                self.pending_leases.pop(0)
                granted_any = True
                continue
            if self._is_duplicate_grant(req.lease_id):
                # Already committed to granting this id (duplicated frame or
                # client retry): granting again would double-deduct resources
                # and overwrite leases[id], leaking the first worker.
                self.pending_leases.pop(0)
                self._resolve_duplicate_lease(req)
                granted_any = True
                continue
            if req.demand.is_subset_of(self.available):
                self.pending_leases.pop(0)
                self.available = self.available - req.demand
                self._mark_dirty()
                # Record the commitment BEFORE the async grant runs so a
                # same-id request queued behind us in this very loop pass is
                # already visible as a duplicate.
                self._record_granted(req.lease_id)
                req.grant_started = time.monotonic()
                self.grants_in_flight += 1
                # Supervision is internal: _grant_inner refunds resources,
                # clears the grant ledger, and resolves req.fut on every
                # failure path (except Exception) — this task IS the
                # grant's supervisor.
                rpc.spawn(self._grant(req))  # rpc-flow: disable=unsupervised-spawn
                granted_any = True

    async def _grant(self, req: LeaseRequest) -> None:
        try:
            await self._grant_inner(req)
        finally:
            # Resources are deducted at spawn time but only visible in
            # `leases` once the grant resolves; the counter lets observers
            # (quiescence checks, stats) see the in-between state.
            self.grants_in_flight -= 1

    async def _grant_inner(self, req: LeaseRequest) -> None:
        container = (
            ((req.payload.get("spec") or {}).get("runtime_env") or {})
            .get("container")
        )
        from ray_tpu.util import tracing

        try:
            with tracing.span_scope(
                "lease.worker_start", "lease", ctx=req.trace_ctx,
                lease_id=req.lease_id,
            ):
                if container:
                    # Containerized actors get a dedicated fresh worker
                    # booted inside the image — shared pool workers cannot
                    # switch containers mid-process.
                    handle = await self._start_worker(container=container)
                    await handle.registered
                else:
                    # A worker dying between spawn and registration is a
                    # transient of process storms, not a property of the
                    # lease: retry with a fresh worker before failing the
                    # request.
                    attempt = 0
                    while True:
                        try:
                            handle = await self._get_or_start_idle_worker()
                            break
                        except rpc.RpcError:
                            attempt += 1
                            if attempt >= 3:
                                raise
                            await asyncio.sleep(0.1 * attempt)
        except Exception as e:
            # Not just RpcError: worker spawn can raise OSError (exec
            # failure, fd exhaustion) and an escaping exception here would
            # leak the deducted resources and leave req.fut unresolved —
            # the client parks forever on a lease nobody is granting.
            self.available = self.available + req.demand
            self._mark_dirty()
            # The grant never happened: clear the ledger entry so a genuine
            # client retry with the same id is not refused forever.
            self.granted_lease_ids.pop(req.lease_id, None)
            if not req.fut.done():
                req.fut.set_exception(
                    e
                    if isinstance(e, rpc.RpcError)
                    else rpc.RpcError(f"lease grant failed: {e!r}")
                )
            return
        if req.lease_id in self.leases and not self._mutate_double_grant:
            # Double grant (two _grant tasks raced to the same lease id —
            # the write-write the AIOCHECK probe caught live). The first
            # write owns the lease; this grant is a no-op: re-credit the
            # demand and return the just-acquired worker to the pool.
            self.available = self.available + req.demand
            self._mark_dirty()
            if container:
                # Dedicated containerized worker: not pool-reusable.
                self._kill_worker_proc(handle)
            else:
                self._return_worker_to_pool(handle)
            self._resolve_duplicate_lease(req)
            self._try_grant_leases()
            return
        handle.lease_id = req.lease_id
        handle.demand = req.demand  # type: ignore[attr-defined]
        handle.leased_since = time.monotonic()  # type: ignore[attr-defined]
        handle.job_id = req.payload.get("job_id") or handle.job_id
        self.leases[req.lease_id] = handle
        self._tel_refresh_gauges()
        if not req.fut.done():
            now_m = time.monotonic()
            self._tel_grant_latency.observe(now_m - req.queued_at)
            if req.trace_ctx is not None:
                # Lease-lifecycle spans, parented into the requesting task's
                # trace: one umbrella span for request->grant, with the
                # queue wait and the grant work as its children.
                gs = req.grant_started if req.grant_started is not None else now_m
                sid = tracing.record_span(
                    "raylet.lease",
                    "lease",
                    req.queued_wall,
                    now_m - req.queued_at,
                    ctx=req.trace_ctx,
                    lease_id=req.lease_id,
                )
                child = (req.trace_ctx[0], sid)
                tracing.record_span(
                    "lease.queue",
                    "lease",
                    req.queued_wall,
                    gs - req.queued_at,
                    ctx=child,
                    lease_id=req.lease_id,
                )
                tracing.record_span(
                    "lease.grant",
                    "lease",
                    req.queued_wall + (gs - req.queued_at),
                    now_m - gs,
                    ctx=child,
                    lease_id=req.lease_id,
                    worker_id=handle.worker_id,
                )
            req.fut.set_result(self._grant_reply(handle, req.lease_id))
        else:  # caller gave up; return resources
            self._release_lease(req.lease_id, dirty=False)

    # Pre-packed grant-reply skeleton: the five keys (and the constant
    # granted=true) of every grant reply, packed once at import. Each grant
    # splices only its per-lease values between the skeleton segments —
    # byte-identical to msgpack-packing the dict (insertion order below
    # matches the segment order), as tests/test_fastpath_native.py asserts.
    _GRANT_SKEL = (
        b"\x85" + rpc._packb("granted") + b"\xc3" + rpc._packb("worker_id"),
        rpc._packb("worker_addr"),
        rpc._packb("lease_id"),
        rpc._packb("fp_port"),
    )

    def _grant_reply(self, handle: WorkerHandle, lease_id: str) -> dict:
        worker_addr = list(handle.addr)
        mapping = {
            "granted": True,
            "worker_id": handle.worker_id,
            "worker_addr": worker_addr,
            "lease_id": lease_id,
            "fp_port": handle.fp_port,
        }
        skel = self._GRANT_SKEL
        try:
            raw = b"".join(
                (
                    skel[0], rpc._packb(handle.worker_id),
                    skel[1], rpc._packb(worker_addr),
                    skel[2], rpc._packb(lease_id),
                    skel[3], rpc._packb(handle.fp_port),
                )
            )
        except Exception:  # unpackable oddity: let the frame packer handle it
            return mapping
        return rpc.PackedPayload(mapping, raw)

    def _return_worker_to_pool(self, handle: WorkerHandle) -> None:
        """Return a worker acquired for a grant that will not happen (the
        duplicate-grant no-op path). Mirrors the clean half of
        _release_lease without touching the lease table."""
        handle.lease_id = None
        handle.job_id = None
        if (
            handle.actor_id is None
            and handle.worker_id in self.workers
            and handle.conn is not None
            and not handle.conn.closed
        ):
            handle.idle_since = time.monotonic()
            self.idle_workers.append(handle)
        else:
            self._kill_worker_proc(handle)
        self._tel_refresh_gauges()

    def _free_lease_resources(self, handle: WorkerHandle) -> None:
        demand = getattr(handle, "demand", None)
        if demand is not None:
            self.available = self.available + demand
            handle.demand = None  # type: ignore[attr-defined]
            self._mark_dirty()
            self._try_grant_leases()

    def _release_lease(self, lease_id: str, dirty: bool) -> Optional[WorkerHandle]:
        handle = self.leases.pop(lease_id, None)
        self._mark_lease_released(lease_id)
        if handle is None:
            return None
        self._tel_lease_released.inc()
        telemetry.record_event(
            "raylet",
            "lease_released",
            lease_id=lease_id,
            node=self.node_id[:8],
            dirty=bool(dirty),
        )
        handle.lease_id = None
        if handle.actor_id is None:
            # Pooled worker returning to idle: drop the lease's job
            # attribution so log lines and the memory-kill policy never
            # blame a previous tenant.
            handle.job_id = None
        self._free_lease_resources(handle)
        if dirty or handle.actor_id:
            self._kill_worker_proc(handle)
        elif handle.worker_id in self.workers:
            handle.idle_since = time.monotonic()
            self.idle_workers.append(handle)
        self._tel_refresh_gauges()
        return handle

    async def _return_worker(self, conn, p):
        self._release_lease(p["lease_id"], p.get("dirty", False))
        return {"ok": True}

    async def _find_spillback_node(self, demand: ResourceSet):
        """Least-utilized peer whose TOTAL fits the demand, preferring one
        whose current availability fits. Served from the GCS-sorted
        scheduling head — the old implementation issued a GetAllNodes RPC
        and scanned every node per lease, which melts at hundreds of nodes.
        Only when nothing in the head fits (a demand shape the least-loaded
        nodes can't hold, e.g. a TPU lease amid idle CPU hosts) does it walk
        the full TTL'd view."""
        fallback = None
        for n in self._head:
            if n["node_id"] == self.node_id:
                continue
            if not demand.is_subset_of(self._node_total_rs(n)):
                continue
            if demand.is_subset_of(self._node_avail_rs(n)):
                return {"node_id": n["node_id"], "addr": n["addr"]}
            if fallback is None:
                fallback = {"node_id": n["node_id"], "addr": n["addr"]}
        if fallback is not None:
            return fallback
        best = None
        best_util = 2.0
        for n in await self._cluster_view():
            if n["node_id"] == self.node_id:
                continue
            if not demand.is_subset_of(self._node_total_rs(n)):
                continue
            util = self._node_util(n["total"], n["available"])
            if demand.is_subset_of(self._node_avail_rs(n)):
                util -= 1.0  # available-now beats merely total-feasible
            if util < best_util:
                best, best_util = n, util
        if best is not None:
            return {"node_id": best["node_id"], "addr": best["addr"]}
        return None

    async def _lease_worker_for_actor(self, conn, p):
        """GCS-driven actor placement: lease a worker and hand it the
        creation spec; the worker reports readiness to the GCS itself."""
        spec = p["spec"]
        demand = ResourceSet.from_units(spec.get("resources") or {})
        demand = self._translate_pg_demand(
            demand, spec.get("pg_id"), spec.get("bundle_index")
        )
        if not demand.is_subset_of(self.total):
            return {"granted": False}
        lease_id = "actor:" + spec["actor_id"]
        if lease_id in self.actor_creations_in_flight:
            # A wire-duplicated/retried placement racing the original: the
            # first grant (and its CreateActor) owns the worker — mirror its
            # outcome rather than double-granting.
            return await self._duplicate_lease_reply(lease_id)
        if self._is_duplicate_grant(lease_id):
            # No creation in flight, yet the id has a live lease: this is a
            # GCS-driven RE-placement (restart FSM, or post-failover
            # reconciliation that declared our node dead), not a duplicate
            # frame. The new placement is authoritative — reclaim the stale
            # instance and grant fresh. Detach actor_id first so reaping the
            # old proc isn't reported as an actor death (it moved, it didn't
            # die — a report would trigger a spurious second restart).
            stale = self.leases.get(lease_id)
            if stale is not None:
                stale.actor_id = None
                self._release_lease(lease_id, dirty=True)
            else:
                self._burn_lease_id(lease_id)
        self.actor_creations_in_flight.add(lease_id)
        try:
            req = LeaseRequest(lease_id, demand, p)
            self.pending_leases.append(req)
            self._try_grant_leases()
            reply = await req.fut
            if not reply.get("granted"):
                return reply
            handle = self.leases[req.lease_id]
            handle.actor_id = spec["actor_id"]
            handle.job_id = spec.get("job_id")
            try:
                await handle.conn.call(
                    "CreateActor",
                    {"spec": spec},
                    timeout=config.rpc_actor_create_timeout_s,
                )
            except rpc.RpcError as e:
                self._release_lease(req.lease_id, dirty=True)
                return {"granted": False, "error": str(e)}
            return {"granted": True, "worker_id": handle.worker_id}
        finally:
            self.actor_creations_in_flight.discard(lease_id)

    async def _kill_worker(self, conn, p):
        handle = self.workers.get(p["worker_id"])
        if p.get("probe"):
            # Liveness probe only (GCS post-restart actor reconciliation).
            alive = handle is not None and (
                handle.proc is None  # fork in flight but registered
                or handle.proc.returncode is None
            )
            return {"ok": True, "alive": alive}
        if handle is None:
            return {"ok": False}
        if p.get("force") and handle.proc is not None:
            # ray.kill(): SIGKILL, no atexit handlers (wire.py: KillWorker).
            # The wire checker surfaced that producers set force=True but the
            # handler always soft-terminated.
            try:
                handle.proc.kill()
            except ProcessLookupError:
                pass
        else:
            self._kill_worker_proc(handle)
        return {"ok": True}

    # -- object store --------------------------------------------------------
    # One shm arena per node; the StoreCore (C++ when built) owns offsets,
    # seal/pin state and LRU order — reference: plasma store
    # (object_lifecycle_manager.cc / plasma_allocator.cc / eviction_policy.cc).

    def _obj_meta(self, oid: str, info) -> dict:
        return {
            "arena": self.arena_name,
            "offset": info[0],
            "size": info[1],
        }

    async def _condemned_sweep_loop(self) -> None:
        while True:
            await asyncio.sleep(1.0)
            self._sweep_condemned()
            # Expire inbound pushes with no chunk progress (source wedged
            # without disconnecting): 60s of silence vastly exceeds any
            # chunk cadence the push budget allows.
            now = time.monotonic()
            for oid, st in list(self.push_assembly.items()):
                if now - st.get("last", now) > 60.0:
                    logger.warning(
                        "aborting stalled inbound push of %s (%d/%d bytes)",
                        oid[:12], st["recv"], st["size"],
                    )
                    self._abort_push_assembly(oid)

    def _sweep_condemned(self, force: bool = False) -> None:
        """Return quarantined spans to the allocator once the grace window has
        passed (no client should still be holding a view)."""
        now = time.monotonic()
        grace = config.object_store_eviction_grace_s
        for oid, t in list(self.condemned.items()):
            if (
                oid in self.obj_holds
                or oid in self.restoring
                or oid in self.push_assembly
            ):
                # A client still maps it, a restore IO thread is writing the
                # span, or an inbound push is mid-assembly — reclaim once
                # that settles (assemblies abort on the next chunk/expiry).
                continue
            if force or now - t >= grace:
                self.store.free(oid)
                del self.condemned[oid]

    def _delete_object(self, oid: str) -> None:
        """Logical delete: the object disappears from the directory now. With
        no client holds the span frees immediately (holds are the only source
        of zero-copy views, so nothing can still map the bytes); held objects
        are quarantined until the grace window passes. Immediate reuse keeps
        sustained large-put workloads on already-faulted arena pages."""
        self._drop_spilled(oid)
        self.pinned_objects.discard(oid)
        info = self.store.lookup(oid)
        if oid in self.condemned or info is None:
            return
        self.obj_last_access.pop(oid, None)
        for fut in self.obj_waiters.pop(oid, []):
            if not fut.done():
                fut.set_result(False)
        # Sealed + hold-free: nothing can still map the bytes (holds are the
        # only source of zero-copy reader views, and the writer's view is
        # gone once sealed). Unsealed objects may have a writer mid-memcpy
        # (e.g. a task return whose ref was dropped early) — quarantine those
        # for the grace window instead.
        if info[2] and oid not in self.obj_holds:
            self.store.free(oid)
        else:
            self.condemned[oid] = time.monotonic()

    def _try_alloc(self, oid: str, size: int, pin: bool) -> int:
        """Alloc with eviction retries. Victims: condemned objects past grace
        first, then LRU sealed+unpinned objects past grace, then SPILL of
        sealed objects (pinned primary copies included) to disk. Retrying
        alloc after every free makes the loop robust to rounding/fragmentation
        (byte accounting alone cannot prove a span fits)."""
        offset = self.store.alloc(oid, size, pin)
        if offset >= 0:
            return offset
        self._sweep_condemned()
        offset = self.store.alloc(oid, size, pin)
        if offset >= 0:
            return offset
        now = time.monotonic()
        grace = config.object_store_eviction_grace_s
        candidates = []
        for vic, last in self.obj_last_access.items():
            if (
                now - last < grace
                or vic in self.obj_holds
                or vic in self.spilling
                or vic in self.pinned_objects
            ):
                continue
            info = self.store.lookup(vic)
            if info is not None and info[2] and not info[3]:
                candidates.append((last, vic))
        candidates.sort()
        for _, vic in candidates:
            self.store.free(vic)
            _TEL_OBJ_EVICTED.inc()
            telemetry.record_event(
                "object", "freed", oid=vic[:16], node=self.node_id[:8],
                reason="lru_evict",
            )
            self.obj_last_access.pop(vic, None)
            offset = self.store.alloc(oid, size, pin)
            if offset >= 0:
                return offset
        # Still no room: start spilling sealed, unheld objects (LRU-first).
        # Spill IO is asynchronous (thread pool) — the span only frees once
        # the write lands, so report failure now and let the caller's retry
        # loop (ObjCreate backpressure / restore retries) pick up the freed
        # space. Reference: LocalObjectManager::SpillObjectsOfSize + async IO
        # workers (local_object_manager.cc).
        self._start_spills(size)
        return -1

    # -- spilling (reference: local_object_manager.cc, external_storage.py) --

    def _start_spills(self, need_bytes: int) -> None:
        """Schedule spill writes until in-flight spills cover ``need_bytes``
        (or no candidates remain). Largest-first: freeing the demanded bytes
        with the fewest IO round-trips minimizes per-object spill overhead
        and leaves the most small hot objects resident (reference:
        LocalObjectManager::SpillObjectsOfSize picks until the byte target).
        Ref-aware: never a client-held, condemned, pinned, or in-flight
        spilling/restoring object."""
        in_flight = 0
        for vic in self.spilling:
            info = self.store.lookup(vic)
            if info is not None:
                in_flight += info[1]
        if in_flight >= need_bytes:
            return
        candidates = []
        for vic, last in self.obj_last_access.items():
            if (
                vic in self.obj_holds
                or vic in self.condemned
                or vic in self.spilling
                or vic in self.restoring
                or vic in self.pinned_objects
            ):
                continue
            info = self.store.lookup(vic)
            if info is not None and info[2]:
                candidates.append((info[1], last, vic))
        # Largest first; LRU (oldest access) breaks size ties.
        candidates.sort(key=lambda c: (-c[0], c[1]))
        for vsize, _, vic in candidates:
            self.spilling[vic] = rpc.spawn(self._spill_task(vic))
            in_flight += vsize
            if in_flight >= need_bytes:
                break

    async def _spill_task(self, oid: str) -> None:
        """One spill write: copy arena bytes out via the storage backend on
        the IO pool, then free the span — unless the object was deleted or
        grabbed by a client while the write was in flight."""
        try:
            info = self.store.lookup(oid)
            if info is None or not info[2]:
                return
            off, size, _, pinned = info
            view = self.arena.view[off : off + size]
            loop = asyncio.get_running_loop()
            t0 = time.monotonic()
            try:
                uri = await loop.run_in_executor(
                    self._io_pool, self.storage.spill, oid, view
                )
            except Exception:
                logger.exception("spill of %s failed", oid[:12])
                return
            self._tel_spill_latency.observe(time.monotonic() - t0)
            # Re-check: a delete/condemn, a new client hold, or a
            # delete-then-recreate (same oid, new span — detectable as a
            # changed offset/size or an unsealed state) during the write
            # means the external copy is stale or the arena copy is still
            # the live one — discard the external copy.
            info2 = self.store.lookup(oid)
            if (
                info2 is None
                or info2[0] != off
                or info2[1] != size
                or not info2[2]
                or oid in self.condemned
                or oid in self.obj_holds
                or oid in self.spilled
            ):
                await loop.run_in_executor(self._io_pool, self.storage.delete, uri)
                return
            self.spilled[oid] = (uri, size, pinned)
            # Counter rides the keyed self.spilled entry: the re-check above
            # discards the duplicate copy when oid is already spilled, so a
            # retried SpillObjects cannot double-count.
            self.spilled_bytes += size  # exc-flow: disable=retry-unsafe-mutation
            self.store.free(oid)
            self.obj_last_access.pop(oid, None)
            self._tel_spilled_bytes.inc(size)
            telemetry.record_event(
                "object", "spilled", oid=oid[:16], size=size,
                node=self.node_id[:8],
            )
            tracing.record_span(
                "object.spill", "object", time.time() - (time.monotonic() - t0),
                time.monotonic() - t0, oid=oid[:16], size=size,
            )
            logger.info(
                "spilled %s (%d bytes) to %s; store %d/%d",
                oid[:12],
                size,
                uri.split("://", 1)[0],
                self.store.used,
                self.store_capacity,
            )
        finally:
            self.spilling.pop(oid, None)

    async def _restore_object(self, oid: str) -> Optional[int]:
        """Bring a spilled object back into the arena; returns offset or
        None (arena transiently full — caller retries). Concurrent restores
        of one object coalesce on a shared future; the read runs on the IO
        pool so the event loop never blocks on storage."""
        fut = self.restoring.get(oid)
        if fut is not None:
            return await asyncio.shield(fut)
        entry = self.spilled.get(oid)
        if entry is None:
            return None
        uri, size, pinned = entry
        offset = self._try_alloc(oid, size, pinned)
        if offset < 0:
            return None
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self.restoring[oid] = fut
        ok = False
        try:
            dest = self.arena.view[offset : offset + size]
            t0 = time.monotonic()
            try:
                n = await loop.run_in_executor(
                    self._io_pool, self.storage.restore, uri, dest
                )
                ok = n == size
            except external_storage.SpillIntegrityError as e:
                # Torn spill file: the external copy is garbage, so this is
                # NOT transient — drop the entry (and the bad bytes) so the
                # object reads as lost and the owner's lineage
                # reconstruction takes over instead of a retry loop sealing
                # corrupt data.
                logger.error("restore of %s hit torn spill file: %s", oid[:12], e)
                telemetry.record_event(
                    "object", "spill_corrupt", oid=oid[:16],
                    node=self.node_id[:8], expected=e.expected, actual=e.actual,
                )
                self._drop_spilled(oid)
                self.store.free(oid)
                fut.set_result(None)
                for w in self.obj_waiters.pop(oid, []):
                    if not w.done():
                        w.set_result(False)
                return None
            except Exception:
                logger.exception("restore of %s failed", oid[:12])
            if oid in self.condemned:
                # Deleted while the read was in flight: abandon the restore;
                # the condemned sweep reclaims the span now that we are no
                # longer writing it.
                self.store.free(oid)
                self.condemned.pop(oid, None)
                fut.set_result(None)
                return None
            if not ok or self.store.lookup(oid) is None:
                # IO errors are treated as transient (a remote backend can
                # 503): keep the spilled entry and the external copy so the
                # caller's backpressure loop can retry; only the caller's
                # deadline turns persistent failure into object-lost.
                self.store.free(oid)
                fut.set_result(None)
                return None
            self.store.seal(oid)
            self.obj_last_access[oid] = time.monotonic()
            self._tel_restore_latency.observe(time.monotonic() - t0)
            self._tel_restored_bytes.inc(size)
            telemetry.record_event(
                "object", "restored", oid=oid[:16], size=size,
                node=self.node_id[:8],
            )
            tracing.record_span(
                "object.restore", "object",
                time.time() - (time.monotonic() - t0),
                time.monotonic() - t0, oid=oid[:16], size=size,
            )
            if self.spilled.pop(oid, None) is not None:
                # Guarded by the keyed pop: the second application sees no
                # entry and skips the decrement.
                self.spilled_bytes -= size  # exc-flow: disable=retry-unsafe-mutation
            # Fire-and-forget: the external copy's deletion must not hold the
            # RPC reply (or fail it after a successful restore).
            try:
                self._io_pool.submit(self.storage.delete, uri)
            except RuntimeError:  # pool already shut down at teardown
                pass
            fut.set_result(offset)
            for w in self.obj_waiters.pop(oid, []):
                if not w.done():
                    w.set_result(True)
            return offset
        finally:
            if not fut.done():
                fut.set_result(None)
            self.restoring.pop(oid, None)

    async def _restore_with_backpressure(self, oid: str) -> None:
        """Restore a spilled object, retrying while the arena is transiently
        full (async spills free room within ~the IO latency). A restore
        failure here must stay transient, not become a spurious copy-lost:
        the bytes still exist in external storage."""
        deadline = time.monotonic() + config.object_store_create_timeout_s
        while oid in self.spilled and oid not in self.condemned:
            if await self._restore_object(oid) is not None:
                return
            if time.monotonic() >= deadline:
                return
            await asyncio.sleep(0.05)

    def _drop_spilled(self, oid: str) -> None:
        entry = self.spilled.pop(oid, None)
        if entry is None:
            return
        # Guarded by the keyed pop above: idempotent under re-delivery.
        self.spilled_bytes -= entry[1]  # exc-flow: disable=retry-unsafe-mutation
        uri = entry[0]
        try:
            self._io_pool.submit(self.storage.delete, uri)
        except RuntimeError:  # pool already shut down at teardown
            pass

    async def _pressure_loop(self) -> None:
        """Proactive spill-under-pressure (reference: LocalObjectManager
        triggered at object_spilling_threshold, local_object_manager.cc):
        instead of waiting for an allocation to fail — which serializes the
        spill IO latency into some put's backpressure loop — spill eligible
        objects (largest-first, via _start_spills) as soon as occupancy
        crosses the threshold, so steady-state oversubscribed workloads
        always find headroom."""
        threshold = config.object_spilling_threshold
        while True:
            await asyncio.sleep(config.object_spilling_poll_interval_s)
            cap = self.store_capacity
            used = self.store.used
            frac = used / cap if cap else 0.0
            self._tel_arena_pressure.set(frac)
            if frac <= threshold:
                continue
            # Spill down to the threshold watermark, counting writes
            # already in flight (they free their spans when the IO lands).
            self._start_spills(used - int(threshold * cap))

    async def _spill_objects(self, conn, p):
        """SpillObjects: owner/tooling directive to move named objects to
        external storage now. Idempotent: an already-spilled oid reports as
        spilled; an ineligible one (unsealed, held, pinned, condemned,
        mid-restore, or unknown) reports as rejected, never an error."""
        scheduled = []
        rejected = []
        for oid in p["oids"]:
            if oid in self.spilled:
                scheduled.append(oid)
                continue
            if oid in self.spilling:
                scheduled.append(oid)
                continue
            info = self.store.lookup(oid)
            if (
                info is None
                or not info[2]
                or oid in self.obj_holds
                or oid in self.condemned
                or oid in self.restoring
                or oid in self.pinned_objects
            ):
                rejected.append(oid)
                continue
            self.spilling[oid] = rpc.spawn(self._spill_task(oid))
            scheduled.append(oid)
        waits = [self.spilling[oid] for oid in scheduled if oid in self.spilling]
        if waits:
            await asyncio.gather(*waits, return_exceptions=True)
        return {
            "spilled": [oid for oid in scheduled if oid in self.spilled],
            "rejected": rejected,
        }

    async def _restore_spilled(self, conn, p):
        """RestoreSpilled: bring one spilled object back into the arena —
        the pull manager's owner-directed fallback before it declares an
        object lost. Coalesces with in-flight restores; a no-op (already
        resident) reports restored=True."""
        oid = p["oid"]
        await self._restore_with_backpressure(oid)
        info = self.store.lookup(oid)
        resident = (
            info is not None and info[2] and oid not in self.condemned
        )
        return {"restored": resident, "spilled": oid in self.spilled}

    async def _pin_object(self, conn, p):
        """PinObject: mark/unmark an object as a pinned primary copy. The
        spill scheduler and LRU eviction skip pinned oids entirely."""
        oid = p["oid"]
        if bool(p.get("pin", True)):
            if not self.store.contains(oid) and oid not in self.spilled:
                return {"ok": False}
            self.pinned_objects.add(oid)
        else:
            self.pinned_objects.discard(oid)
        return {"ok": True}

    # -- memory monitor (reference: memory_monitor.h + worker_killing_policy)

    def _system_memory_fraction(self) -> float:
        try:
            with open("/proc/meminfo") as f:
                info = {}
                for line in f:
                    parts = line.split()
                    info[parts[0].rstrip(":")] = int(parts[1])
            total = info.get("MemTotal", 0)
            avail = info.get("MemAvailable", 0)
            if total <= 0:
                return 0.0
            return 1.0 - avail / total
        except OSError:
            return 0.0

    async def _memory_monitor_loop(self) -> None:
        while True:
            await asyncio.sleep(config.memory_monitor_interval_s)
            frac = self._system_memory_fraction()
            if frac < config.memory_usage_threshold:
                continue
            victim = self._pick_memory_victim()
            if victim is None:
                continue
            logger.warning(
                "memory usage %.1f%% over threshold %.1f%%: killing worker "
                "%s (%s)",
                frac * 100,
                config.memory_usage_threshold * 100,
                victim.worker_id[:8],
                "newest task worker of largest owner group; owner retries "
                "per max_retries"
                if victim.actor_id is None
                else f"actor {victim.actor_id[:8]}; owner sees restart or "
                "ActorDiedError",
            )
            self._kill_worker_proc(victim)

    def _pick_memory_victim(self) -> Optional["WorkerHandle"]:
        """Group-by-owner fair killing (reference:
        worker_killing_policy_group_by_owner.h / worker_killing_policy.h:34).

        Task workers first (their owners retry per max_retries): group
        leased workers by owning job and pick the NEWEST worker from the
        LARGEST group — the job consuming the most workers sheds load first,
        so one memory-hungry job cannot starve every tenant on the node.
        Actor workers are eligible as a last resort, newest first (their
        owners see a restart or ActorDiedError) — a runaway actor must not
        OOM the node while the monitor watches."""
        newest = lambda h: getattr(h, "leased_since", h.idle_since)  # noqa: E731
        task_workers = [h for h in self.leases.values() if h.actor_id is None]
        if task_workers:
            groups: Dict[Optional[str], List[WorkerHandle]] = {}
            for h in task_workers:
                groups.setdefault(h.job_id, []).append(h)
            largest = max(
                groups.values(), key=lambda g: (len(g), max(newest(h) for h in g))
            )
            return max(largest, key=newest)
        actors = [h for h in self.workers.values() if h.actor_id is not None]
        if actors:
            return max(actors, key=newest)
        return None

    async def _obj_create(self, conn, p):
        """Create (or resolve an existing/spilled copy of) an object span.

        Runs as a retry loop with backpressure (plasma
        create_request_queue.cc analog): when the arena is transiently full
        of client-held objects, room appears as holds release, the eviction
        grace expires, or spill victims free up — so re-evaluate the full
        exists/spilled/alloc state each round rather than failing, since a
        concurrent deterministic recreate may land the object meanwhile."""
        oid, size = p["oid"], p["size"]
        pin = bool(p.get("pin", True))
        deadline = time.monotonic() + config.object_store_create_timeout_s
        while True:
            fut = self.restoring.get(oid)
            if fut is not None:
                # A restore IO thread is writing this span: let it finish
                # before any free/recreate decision (the restored bytes are
                # the deterministically identical object anyway).
                await asyncio.shield(fut)
                continue
            if oid in self.condemned:
                if oid in self.obj_holds:
                    # A client still maps the old (deterministically
                    # identical) bytes: resurrect the quarantined object
                    # instead of freeing a span someone is reading.
                    del self.condemned[oid]
                    self.obj_last_access[oid] = time.monotonic()
                else:
                    # Recreate of a just-deleted id: reclaim that span now.
                    self.store.free(oid)
                    del self.condemned[oid]
            if oid in self.spilled:
                # Deterministic recreate of a spilled object: restore it (may
                # fail transiently while the arena is full of held objects).
                await self._restore_object(oid)
            info = self.store.lookup(oid)
            if info is not None:
                self.obj_last_access[oid] = time.monotonic()
                meta = self._obj_meta(oid, info)
                meta.update({"exists": True, "sealed": info[2]})
                return meta
            if oid not in self.spilled:
                offset = self._try_alloc(oid, size, pin)
                if offset >= 0:
                    self.obj_last_access[oid] = time.monotonic()
                    telemetry.record_event(
                        "object",
                        "created",
                        oid=oid[:16],
                        size=size,
                        node=self.node_id[:8],
                    )
                    return {
                        "arena": self.arena_name,
                        "offset": offset,
                        "size": size,
                        "exists": False,
                    }
            if size > self.store_capacity or time.monotonic() >= deadline:
                raise rpc.RpcError(
                    f"object store full: need {size}, used {self.store.used} "
                    f"of {self.store_capacity} (fragmentation "
                    f"{self.store.fragmentation()[0]:.2f}; spilled "
                    f"{len(self.spilled)} objects / {self.spilled_bytes} "
                    "bytes; objects currently held by clients cannot be "
                    "spilled — raise object_store_memory or release holds)"
                )
            await asyncio.sleep(0.1)

    async def _obj_seal(self, conn, p):
        oid = p["oid"]
        if self.store.lookup(oid) is None:
            raise rpc.RpcError(f"seal of unknown object {oid[:12]}")
        self.store.seal(oid)
        _TEL_OBJ_SEALED.inc()
        telemetry.record_event(
            "object", "sealed", oid=oid[:16], node=self.node_id[:8]
        )
        self.obj_last_access[oid] = time.monotonic()
        for fut in self.obj_waiters.pop(oid, []):
            if not fut.done():
                fut.set_result(True)
        return {"ok": True}

    async def _obj_get(self, conn, p):
        """Resolve local objects; optionally block until sealed."""
        timeout = p.get("timeout")
        found, missing = {}, []
        deadline = time.monotonic() + timeout if timeout else None
        for oid in p["oids"]:
            if oid in self.spilled and oid not in self.condemned:
                # Restore backpressure: the arena may be transiently full of
                # client-held objects; holds release within ~1s (client flush
                # loops), so retry until the caller's deadline — but never
                # past the create-timeout cap: a timeout-less blocking get on
                # a persistently failing restore must surface as missing, not
                # hang the RPC forever.
                restore_cap = (
                    time.monotonic() + config.object_store_create_timeout_s
                )
                while (
                    await self._restore_object(oid) is None
                    and oid in self.spilled
                    and p.get("block", True)
                    and (deadline is None or time.monotonic() < deadline)
                    and time.monotonic() < restore_cap
                ):
                    await asyncio.sleep(0.05)
            info = None if oid in self.condemned else self.store.lookup(oid)
            if info is not None and not info[2] and p.get("block", True):
                fut = asyncio.get_running_loop().create_future()
                self.obj_waiters.setdefault(oid, []).append(fut)
                remaining = (
                    None if deadline is None else max(0, deadline - time.monotonic())
                )
                try:
                    await asyncio.wait_for(fut, remaining)
                except asyncio.TimeoutError:
                    pass
                info = None if oid in self.condemned else self.store.lookup(oid)
            if info is not None and info[2]:
                self.store.touch(oid)
                self.obj_last_access[oid] = time.monotonic()
                self._add_hold(conn, oid)
                found[oid] = self._obj_meta(oid, info)
            else:
                missing.append(oid)
        return {"found": found, "missing": missing}

    async def _obj_contains(self, conn, p):
        return {
            "contains": {
                oid: oid not in self.condemned
                and (self.store.contains(oid) or oid in self.spilled)
                for oid in p["oids"]
            }
        }

    async def _obj_release(self, conn, p):
        for oid in p.get("oids") or [p["oid"]]:
            holds = self.obj_holds.get(oid)
            if holds is not None:
                n = holds.get(id(conn), 0) - 1
                if n <= 0:
                    holds.pop(id(conn), None)
                else:
                    holds[id(conn)] = n
                if not holds:
                    del self.obj_holds[oid]
            if self.store.lookup(oid) is not None:
                self.store.touch(oid)
                self.obj_last_access[oid] = time.monotonic()
        return {"ok": True}

    async def _obj_delete(self, conn, p):
        for oid in p["oids"]:
            self._delete_object(oid)
        return {"ok": True}

    # -- cross-node transfer (reference: object_manager pull/push) -----------

    def _add_hold(self, conn, oid: str) -> None:
        holds = self.obj_holds.setdefault(oid, {})
        holds[id(conn)] = holds.get(id(conn), 0) + 1

    # -- inbound push handlers (reference: object_manager HandlePush) --------

    async def _push_object(self, conn, p):
        """Source side: stream our local copy of an object to a destination
        raylet. Triggered by the destination's pull; the push manager dedups
        concurrent requests and bounds chunks in flight across ALL
        destinations (broadcast-safe fan-out)."""
        await self.push_manager.push(p["oid"], tuple(p["to"]))
        return {"ok": True}

    async def _push_start(self, conn, p):
        """Destination side: allocate an unsealed span for an inbound push.
        Returns needed=False when the object already exists or another
        transfer is assembling it."""
        oid, size = p["oid"], p["size"]
        meta = await self._obj_create(conn, {"oid": oid, "size": size, "pin": False})
        if meta.get("exists") or oid in self.push_assembly:
            return {"needed": False}
        self.push_assembly[oid] = {
            "offset": meta["offset"],
            "size": size,
            "recv": 0,
            "conn": id(conn),
            "last": time.monotonic(),
        }
        return {"needed": True}

    def _push_chunk_sink(self, conn, p, size):
        """Destination side: blob sink factory for one inbound chunk. The
        chunk's bytes stream from the socket straight into the arena span at
        the assembly's write offset (one copy, NIC->arena) instead of
        materializing in a msgpack payload first. Returning None drains and
        discards the blob."""
        oid, off = p["oid"], p["offset"]
        st = self.push_assembly.get(oid)
        if st is None:
            return None  # assembly aborted (e.g. object deleted mid-push)
        if st.get("conn") != id(conn):
            # Chunk from a stale source (an aborted push's connection that
            # un-wedged after a fresh PushStart re-created the assembly):
            # counting it would seal before the live transfer's tail lands.
            return None
        if oid in self.condemned:
            # Deleted mid-assembly: stop writing before the condemned sweep
            # can free the span out from under us.
            del self.push_assembly[oid]
            return None
        if off != st["recv"] or off + size > st["size"]:
            # Out-of-order, duplicated, or over-long chunk. Writing it would
            # either punch a hole (sealing on byte count would then expose
            # uninitialized shm) or run past the span into a neighboring
            # object. The source sends strictly in order, so any deviation
            # means a corrupt/stale stream: abort the whole assembly and let
            # the next pull re-transfer from scratch.
            logger.warning(
                "aborting push assembly of %s: chunk offset %d (expected %d, size %d)",
                oid[:12], off, st["recv"], st["size"],
            )
            self._abort_push_assembly(oid)
            return None
        return _ArenaChunkSink(self, oid, st, off, size)

    def _abort_push_assembly(self, oid: str) -> None:
        """Drop a dead inbound push so the oid does not stay permanently
        unfetchable (exists-unsealed would make every future PushStart answer
        needed=False). Deleting the unsealed object quarantines the span;
        the next pull re-creates and re-transfers it."""
        if self.push_assembly.pop(oid, None) is not None:
            self._delete_object(oid)

    async def _pull_object(self, conn, p):
        """Fetch an object from a remote raylet into the local store.

        Fast path: ask the source to *push* (one-way chunk stream through its
        push manager — broadcast-friendly). Fallback: the legacy chunk pull
        (request/reply FetchChunk loop)."""
        oid = p["oid"]
        await self._restore_with_backpressure(oid)
        info = self.store.lookup(oid)
        if info is not None and info[2]:
            self._add_hold(conn, oid)
            return self._obj_meta(oid, info)
        remote = await rpc.connect(*p["from_addr"], retry=3)
        # Admission (reference: pull_manager.h): learn the size, then wait
        # for quota at this request's priority before moving any bytes.
        probe = await remote.call(
            "ObjGet", {"oids": [oid], "block": True, "timeout": 30}
        )
        probe_meta = probe["found"].get(oid)
        if probe_meta is None:
            # A spilled copy is a valid pull source: before declaring the
            # object absent, direct the holder to restore from its external
            # storage (the probe's internal restore can give up early when
            # its arena is persistently full — an explicit RestoreSpilled
            # retries with fresh backpressure budget).
            try:
                rest = await remote.call(
                    "RestoreSpilled", {"oid": oid},
                    timeout=config.rpc_transfer_timeout_s,
                )
            except (rpc.RpcError, asyncio.TimeoutError, OSError):
                rest = None
            if rest and rest.get("restored"):
                self.pull_manager.restore_fallbacks += 1
                pull_manager_mod._TEL_RESTORE_FALLBACKS.inc()
                probe = await remote.call(
                    "ObjGet", {"oids": [oid], "block": True, "timeout": 30}
                )
                probe_meta = probe["found"].get(oid)
        if probe_meta is None:
            await remote.close()
            raise rpc.RpcError(f"object {oid[:12]} not on remote node")
        pull_size = int(probe_meta.get("size", 0))
        await self.pull_manager.acquire(pull_size, p.get("purpose", "get"))
        try:
            def _recv_progress():
                st = self.push_assembly.get(oid)
                # Track the assembly's byte counter; before PushStart lands
                # (or after a seal removed the entry) report a sentinel so
                # only a *stuck mid-assembly* counter reads as no-progress.
                return -1 if st is None else st["recv"]

            def _sealed():
                info = self.store.lookup(oid)
                return info is not None and info[2] and oid not in self.condemned

            rerequests = 0
            while True:
                try:
                    await remote.call(
                        "PushObject",
                        {"oid": oid, "to": list(self.addr)},
                        timeout=config.rpc_transfer_timeout_s,
                    )
                    # Supervise the one-way chunk stream: a stream that stops
                    # mid-assembly (source death, chunk loss) is aborted and
                    # re-requested instead of riding out the blocking-get
                    # timeout + the 60s assembly janitor.
                    await self.pull_manager.watch_stream(
                        _recv_progress, _sealed, timeout=30
                    )
                    got = await self._obj_get(
                        conn, {"oids": [oid], "block": True, "timeout": 5}
                    )
                    found = got["found"].get(oid)
                    if found is not None:
                        return found  # _obj_get already holds it for this conn
                    break  # sealed then deleted underneath us: fall back
                except PullStalled as e:
                    self._abort_push_assembly(oid)
                    if rerequests >= self.pull_manager.max_rerequests:
                        logger.warning(
                            "push stream for %s stalled %d times (%s); "
                            "falling back to chunk pull",
                            oid[:12], rerequests + 1, e,
                        )
                        break
                    rerequests += 1
                    self.pull_manager.rerequested_streams += 1
                    pull_manager_mod._TEL_REREQUESTED.inc()
                    telemetry.record_event(
                        "object", "pull_rerequest", oid=oid[:16],
                        node=self.node_id[:8], attempt=rerequests,
                    )
                    logger.info(
                        "push stream for %s stalled (%s); re-requesting "
                        "(%d/%d)",
                        oid[:12], e, rerequests, self.pull_manager.max_rerequests,
                    )
                except (rpc.RpcError, asyncio.TimeoutError, OSError) as e:
                    logger.debug(
                        "push-based pull of %s failed (%s); falling back", oid[:12], e
                    )
                    break
            # block briefly: the owner's seal may still be in flight on its
            # raylet connection (puts seal via one-way push).
            reply = await remote.call(
                "ObjGet", {"oids": [oid], "block": True, "timeout": 5}
            )
            meta = reply["found"].get(oid)
            if meta is None:
                raise rpc.RpcError(f"object {oid[:12]} not on remote node")
            size = meta["size"]
            create = await self._obj_create(conn, {"oid": oid, "size": size, "pin": False})
            if create.get("sealed"):
                # Hold for the caller like the sibling paths: an unheld span
                # could be spilled/evicted before the puller reads it.
                self._add_hold(conn, oid)
                return create
            if create.get("exists"):
                # Another pull is filling it; wait for the seal and verify.
                await self._obj_get(conn, {"oids": [oid], "block": True, "timeout": 60})
                info = self.store.lookup(oid)
                if info is None or not info[2] or oid in self.condemned:
                    raise rpc.RpcError(
                        f"concurrent pull of {oid[:12]} did not complete"
                    )
                self._add_hold(conn, oid)
                return create
            offset = create["offset"]
            view = self.arena.view
            chunk = adaptive_chunk_size(size)
            done = 0
            while done < size:
                n = min(chunk, size - done)
                # Blob reply streamed straight into our arena span at the
                # object's offset: the socket bytes land in shm with no
                # intermediate msgpack buffer.
                sink = rpc.SpanSink(view, offset + done)
                await remote.call_into(
                    "FetchChunk",
                    {"oid": oid, "offset": done, "size": n},
                    sink,
                    timeout=config.rpc_chunk_timeout_s,
                )
                if sink.written != n:
                    raise rpc.RpcError(
                        f"short FetchChunk for {oid[:12]}: "
                        f"{sink.written}/{n} bytes at offset {done}"
                    )
                done += n
            await self._obj_seal(conn, {"oid": oid})
            self._add_hold(conn, oid)
            return create
        finally:
            self.pull_manager.release(pull_size)
            await remote.close()

    async def _fetch_chunk(self, conn, p):
        await self._restore_with_backpressure(p["oid"])
        info = self.store.lookup(p["oid"])
        if info is None or not info[2]:
            raise rpc.RpcError(f"object {p['oid'][:12]} not local")
        base = info[0] + p["offset"]
        n = p["size"]
        # Blob reply: the arena view is written to the transport before
        # _dispatch returns to the loop, so no hold is needed for the send.
        return rpc.Blob({"size": n}, self.arena.view[base : base + n])

    # -- placement group bundles ---------------------------------------------

    async def _prepare_pg(self, conn, p):
        pg_id = p["pg_id"]
        total_demand = ResourceSet()
        for _, units in p["bundles"].items():
            total_demand = total_demand + ResourceSet.from_units(units)
        if not total_demand.is_subset_of(self.available):
            return {"success": False}
        self.available = self.available - total_demand
        self.pg_prepared[pg_id] = total_demand
        # Remember per-bundle layout for commit.
        self.pg_prepared_bundles = getattr(self, "pg_prepared_bundles", {})
        self.pg_prepared_bundles[pg_id] = p["bundles"]
        self._mark_dirty()
        return {"success": True}

    async def _commit_pg(self, conn, p):
        pg_id = p["pg_id"]
        base = self.pg_prepared.pop(pg_id, None)
        bundles = getattr(self, "pg_prepared_bundles", {}).pop(pg_id, None)
        if base is None or bundles is None:
            return {"ok": False}
        group_units: Dict[str, int] = {f"bundle_group_{pg_id}": len(bundles) * 10000}
        for idx, units in bundles.items():
            for k, v in units.items():
                group_units[f"{k}_group_{idx}_{pg_id}"] = v
                group_units[f"{k}_group_{pg_id}"] = (
                    group_units.get(f"{k}_group_{pg_id}", 0) + v
                )
        group = ResourceSet.from_units(group_units)
        self.total = self.total + group
        self.available = self.available + group
        self.pg_committed[pg_id] = (base, group)
        self._mark_dirty()
        self._try_grant_leases()
        return {"ok": True}

    async def _release_pg(self, conn, p):
        pg_id = p["pg_id"]
        if pg_id in self.pg_prepared:
            self.available = self.available + self.pg_prepared.pop(pg_id)
            getattr(self, "pg_prepared_bundles", {}).pop(pg_id, None)
        if pg_id in self.pg_committed:
            base, group = self.pg_committed.pop(pg_id)
            self.total = self.total - group
            self.available = self.available - group + base
            # Kill workers leased against this PG's resources.
            for lease_id, handle in list(self.leases.items()):
                demand = getattr(handle, "demand", None)
                if demand and any(pg_id in k for k in demand.keys()):
                    self._release_lease(lease_id, dirty=True)
        self._mark_dirty()
        return {"ok": True}

    async def _node_stats(self, conn, p):
        out = {
            "node_id": self.node_id,
            "total": self.total.to_units(),
            "available": self.available.to_units(),
            "num_workers": len(self.workers),
            "num_idle": len(self.idle_workers),
            "num_leases": len(self.leases),
            "store_used": self.store_used,
            "store_capacity": self.store_capacity,
            "num_objects": self.store.num_objects,
            "pending_leases": len(self.pending_leases) + len(self.infeasible_leases),
            "spilled_objects": len(self.spilled),
            "spilled_bytes": self.spilled_bytes,
            "pinned_objects": len(self.pinned_objects),
            "push_stats": dict(self.push_manager.stats),
            # Unmet demand shapes for the autoscaler's bin-packing
            # (reference: resource_demand_scheduler reads task demands).
            # Infeasible shapes first — they are the scale-up signal.
            "pending_demand": [
                req.demand.to_units()
                for req in (self.infeasible_leases + self.pending_leases)[:20]
            ],
        }
        # Detail payloads for the state API (reference: raylet
        # GetTasksInfo/GetObjectsInfo, node_manager.proto:424-426).
        if p.get("include_workers"):
            idle = {w.worker_id for w in self.idle_workers}
            out["workers"] = [
                {
                    "worker_id": w.worker_id,
                    "pid": getattr(w.proc, "pid", None),
                    "actor_id": w.actor_id,
                    "lease_id": w.lease_id,
                    "state": "IDLE" if w.worker_id in idle else "BUSY",
                    "node_id": self.node_id,
                }
                for w in self.workers.values()
            ]
        if p.get("include_objects"):
            objs = []
            for oid in list(self.obj_last_access):
                info = self.store.lookup(oid)
                if info is None:
                    continue
                objs.append(
                    {
                        "object_id": oid,
                        "size": info[1],
                        "sealed": info[2],
                        "pinned": info[3],
                        "node_id": self.node_id,
                    }
                )
            out["objects"] = objs
        return out


async def main() -> None:
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--gcs-host", required=True)
    parser.add_argument("--gcs-port", type=int, required=True)
    parser.add_argument("--session", required=True)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--resources", default="")
    parser.add_argument("--object-store-memory", type=int, default=None)
    args = parser.parse_args()
    resources = None
    if args.resources:
        import json

        resources = json.loads(args.resources)
    raylet = Raylet(
        (args.gcs_host, args.gcs_port),
        args.session,
        host=args.host,
        port=args.port,
        resources=resources,
        object_store_memory=args.object_store_memory,
    )
    addr = await raylet.start()
    print(f"RAYLET_ADDR {addr[0]}:{addr[1]} NODE {raylet.node_id}", flush=True)
    await asyncio.Event().wait()


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    asyncio.run(main())
