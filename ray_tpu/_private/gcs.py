"""GCS: the cluster control service (control plane singleton).

TPU-native analog of the reference's gcs_server (src/ray/gcs/gcs_server/gcs_server.h:219):
one asyncio process holding cluster state — node membership, actor FSM with
restarts, placement-group 2PC, internal KV (which doubles as the function
table), pubsub, and the job table. Persistence is pluggable (reference:
store_client.h:33): in-memory by default, a group-commit log for GCS fault
tolerance — kill and restart the GCS and raylets/workers reconnect,
re-register, and detached actors survive (analog of the Redis-backed FT mode
+ NotifyGCSRestart reconnect protocol, node_manager.proto:373).

Health checking follows the reference's connection+liveness model
(gcs_health_check_manager.cc): raylets hold a persistent RPC connection and
push periodic resource updates; a dropped connection or missed deadline marks
the node dead, which drives actor restarts and PG rescheduling.
"""

from __future__ import annotations

import asyncio
import bisect
import logging
import time
from typing import Any, Dict, List, Optional, Set, Tuple

import msgpack

from collections import deque

from ray_tpu._private import aiocheck, rpc, telemetry, wire
from ray_tpu._private.pubsub import Publisher
from ray_tpu._private.common import PlacementGroupSpec, ResourceSet, config

logger = logging.getLogger(__name__)

# Subscriber-side gap detection (GcsClient): counted in the raylet/driver
# process that noticed the gap and flushed with its telemetry.
_TEL_SUB_GAP = telemetry.counter(
    "gcs_client",
    "pubsub_gap_snapshots",
    "pubsub seq gaps detected by a subscriber (each pulls a snapshot)",
)

# Actor FSM states (reference: gcs_actor_manager.cc). The legal transitions
# are declared machine-readably in ray_tpu/devtools/protocols.py and every
# assignment is checked against them at lint time.
DEPENDENCIES_UNREADY = "DEPENDENCIES_UNREADY"
PENDING_CREATION = "PENDING_CREATION"
ALIVE = "ALIVE"
RESTARTING = "RESTARTING"
DEAD = "DEAD"

# Node FSM states (reference: gcs_node_manager.cc). Same wire strings as the
# actor ALIVE/DEAD, but a separate two-state machine — keep distinct names so
# the protocol checker can tell the machines apart.
NODE_ALIVE = "ALIVE"
NODE_DEAD = "DEAD"

# Placement-group FSM states (reference: gcs_placement_group_mgr.cc).
PG_PENDING = "PENDING"
PG_CREATED = "CREATED"
PG_RESCHEDULING = "RESCHEDULING"
PG_REMOVED = "REMOVED"
PG_INFEASIBLE = "INFEASIBLE"


class NodeInfo:
    def __init__(self, node_id: str, addr, resources: Dict[str, int], labels, conn):
        self.node_id = node_id
        self.addr = tuple(addr)
        self.total = dict(resources)
        self.available = dict(resources)
        self.labels = labels or {}
        self.conn: rpc.Connection = conn
        self.state = NODE_ALIVE
        self.last_seen = time.monotonic()
        # Health-check manager state (reference: gcs_health_check_manager.cc).
        self.health_misses = 0
        self.health_probe_inflight = False
        # Last resource-report version accepted from this raylet (syncer
        # staleness guard, reference: ray_syncer.h versioned messages).
        self.report_version = -1

    def to_wire(self, include_conn=False) -> dict:
        return {
            "node_id": self.node_id,
            "addr": list(self.addr),
            "total": self.total,
            "available": self.available,
            "labels": self.labels,
            "state": self.state,
        }


class ActorInfo:
    def __init__(self, actor_id: str, spec: dict):
        self.actor_id = actor_id
        self.spec = spec  # actor-creation TaskSpec wire dict
        self.state = PENDING_CREATION
        self.addr: Optional[Tuple[str, int]] = None
        self.worker_id: Optional[str] = None
        self.node_id: Optional[str] = None
        self.num_restarts = 0
        self.max_restarts = spec.get("max_restarts", 0)
        self.name = spec.get("actor_name")
        self.namespace = spec.get("namespace") or "default"
        self.job_id = spec.get("job_id")
        self.detached = (spec.get("scheduling_strategy") or {}).get("detached", False)
        self.death_cause: Optional[str] = None
        self.pending: List[asyncio.Future] = []

    def to_wire(self) -> dict:
        return {
            "actor_id": self.actor_id,
            "state": self.state,
            "addr": list(self.addr) if self.addr else None,
            "worker_id": self.worker_id,
            "node_id": self.node_id,
            "num_restarts": self.num_restarts,
            "max_restarts": self.max_restarts,
            "name": self.name,
            "namespace": self.namespace,
            "job_id": self.job_id,
            "death_cause": self.death_cause,
            "class_name": self.spec.get("name"),
            "max_task_retries": self.spec.get("max_task_retries", 0),
        }


class PlacementGroupInfo:
    def __init__(self, spec: PlacementGroupSpec):
        self.spec = spec
        self.state = PG_PENDING
        self.bundle_nodes: List[Optional[str]] = [None] * len(spec.bundles)
        self.pending: List[asyncio.Future] = []


class GcsServer:
    """The control service. Start with `await GcsServer(...).start()`."""

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        session_name: str = "",
        persist_path: Optional[str] = None,
        persist_backend: Optional[str] = None,
        term: Optional[int] = None,
    ):
        from ray_tpu._private.gcs_store import ReplicatedStoreClient, make_store

        self.server = rpc.Server(host, port)
        self.session_name = session_name
        # Shared single-loop state: every handler below may touch these
        # across awaits. aiocheck.track is a no-op unless RAY_TPU_AIOCHECK=1,
        # in which case mutations are attributed to their asyncio task so
        # cross-task interleaving hazards surface at runtime.
        self.nodes: Dict[str, NodeInfo] = aiocheck.track("gcs.nodes")
        self.actors: Dict[str, ActorInfo] = aiocheck.track("gcs.actors")
        # (ns, name) -> actor_id
        self.named_actors: Dict[Tuple[str, str], str] = aiocheck.track(
            "gcs.named_actors"
        )
        self.kv: Dict[Tuple[str, str], bytes] = aiocheck.track("gcs.kv")
        # Bounded per-subscriber pubsub (reference: pubsub/publisher.h).
        self.publisher = Publisher()
        self.jobs: Dict[str, dict] = aiocheck.track("gcs.jobs")
        self.placement_groups: Dict[str, PlacementGroupInfo] = aiocheck.track(
            "gcs.placement_groups"
        )
        self.task_events: List[dict] = []  # ring buffer of task state events
        # Trace-span ring: submit/execute spans diverted from AddTaskEvents
        # plus runtime-internal spans delivered via ReportSpans, one store
        # for list_spans()/timeline()/critical_path().
        self.spans: List[dict] = []
        # Cluster-wide deadline-enforcement aggregate, fed by worker
        # subprocess flushes (ReportDeadlineStats deltas + exit-time flush).
        # The chaos no-call-outlives-deadline invariant reads `overruns`
        # here so worker-side overruns are visible, not just driver-side.
        self.worker_deadline_stats: Dict[str, Any] = {  # telemetry: allow-adhoc-stats
            "met": 0,
            "shed": 0,
            "enforced": 0,
            "overruns": [],  # (worker_id, method, seconds late)
        }
        # Cluster-wide runtime-telemetry aggregate keyed by
        # (component, node, name), fed by per-process ReportTelemetry
        # flushes (telemetry.py); the dashboard /metrics endpoint renders
        # it as Prometheus text next to the app-metric export.
        self.telemetry: Dict[str, Any] = telemetry.new_aggregate()
        # Merged flight-recorder ring: lifecycle events drained from every
        # reporting process, kept in arrival order (entries carry wall-clock
        # timestamps; the dump step sorts). Sized for a whole cluster.
        self.flight_events: deque = deque(
            maxlen=8 * config.telemetry_flight_capacity
        )
        # Service-latency histogram observed around every async handler
        # dispatch on this server (rpc.Connection dispatch_observer).
        lat = telemetry.histogram(
            "gcs",
            "rpc_latency_s",
            "GCS handler service latency by method",
            buckets=telemetry.LATENCY_BUCKETS_S,
        )
        _lat_cells: Dict[str, Any] = {}

        def _observe_latency(method: str, dt: float) -> None:
            cell = _lat_cells.get(method)
            if cell is None:
                cell = _lat_cells[method] = lat.cell(method=method)
            cell.observe(dt)

        self.server.dispatch_observer = _observe_latency
        # Monotonic cluster-view version; every membership/resource change
        # bumps it and broadcasts the scheduling head (reference:
        # ray_syncer.h:88 versioned sync streams). The GCS is the one place
        # that sees every resource report, so IT maintains the
        # utilization-sorted order incrementally (O(log n) bisect per
        # report) and subscribers receive only the sorted head — the
        # least-utilized candidate set every top-k/spillback pick needs.
        # Broadcasting full per-node deltas instead would cost every
        # subscriber O(dirty) decode+apply per flush, which measured
        # O(N^2) cluster-wide during lease storms.
        self.view_version = 0
        # Membership/total-capacity epoch: keys subscriber-side caches that
        # only depend on cluster shape (e.g. the SPREAD ring).
        self.view_epoch = 0
        self._util_sorted: List[Tuple[float, str]] = []  # (util, node_id)
        self._node_utils: Dict[str, float] = {}
        # Head batching (scheduler_view_batch_ms): mutations coalesce for
        # one window and flush as a single versioned head broadcast, so a
        # grant storm at N nodes costs subscribers/window broadcasts
        # instead of subscribers*grants.
        self._view_dirty = False
        self._view_flush_handle: Optional[asyncio.TimerHandle] = None
        # Structured events (reference: src/ray/util/event.cc): durable
        # JSONL + queryable ring, served via ListEvents.
        from ray_tpu._private.events import EventLogger

        self.events = EventLogger(session_name or "default", "GCS")
        self._pending_actor_queue: List[str] = []
        self._wake_scheduler = asyncio.Event()
        self._scheduler_task: Optional[asyncio.Task] = None
        self._bg_tasks: List[asyncio.Task] = []
        # True while stop() tears the server down. Connection drops during a
        # deliberate shutdown are us leaving, not peers dying — reacting to
        # them would persist bogus node-death state (actors marked
        # RESTARTING/DEAD) that a restarted GCS then faithfully reloads.
        self._stopping = False
        # Actors reloaded as ALIVE whose hosting raylet has not yet
        # re-registered and confirmed them (RegisterNode "actors" report).
        # Whatever remains when the reconcile sweep runs gets probed.
        self._restored_unconfirmed: Set[str] = set()
        # Persistence (reference: StoreClient, store_client.h:33). The live
        # state above stays the source of truth; mutations write through to
        # the store, and a restarted GCS reloads it (GCS fault tolerance).
        #
        # HA (gcs_persist_backend=replicated, docs/fault_tolerance.md §HA):
        # the store ships every write to follower logs and carries a
        # leadership term. ``term`` is set by a promoting standby; a fresh
        # start (or restart-in-place) re-asserts leadership at
        # recovered_term + 1 — every leadership is a new term, so a
        # survivor of the old one is fenced the moment we open the store.
        self.leader_term = 0
        self.fenced = False
        self._persist_path = persist_path
        self.store = make_store(
            persist_path,
            backend=persist_backend,
            term=term,
            on_fenced=self._on_store_fenced,
        )
        # Cross-process standbys subscribed to the quorum-acked commit
        # stream (ShipSubscribe); each push mirrors the raw WAL frames of
        # one group commit (gcs_ha.GcsStandby rpc mode).
        self._ship_subs: set = set()
        if isinstance(self.store, ReplicatedStoreClient):
            if term is None:
                self.store.set_term(self.store.term + 1)
            self.leader_term = self.store.term
            self.store.ship_listener = self._on_ship_commit
        self._load_from_store()
        self._register_handlers()

    def _spawn(self, coro) -> asyncio.Task:
        task = rpc.spawn(coro)
        self._bg_tasks.append(task)
        self._bg_tasks = [t for t in self._bg_tasks if not t.done()]
        return task

    def _spawn_pg_schedule(self, pg: "PlacementGroupInfo") -> asyncio.Task:
        """Supervised ``_schedule_pg`` spawn: a crashed scheduling task must
        not strand the PG in PENDING/RESCHEDULING with ``pg.pending``
        futures nobody will ever resolve (CreatePlacementGroup callers with
        ``wait_ready`` park on those)."""
        task = self._spawn(self._schedule_pg(pg))

        def _done(t: asyncio.Task, pg=pg) -> None:
            if t.cancelled() or t.exception() is None:
                return
            exc = t.exception()
            logger.error(
                "placement group %s scheduling crashed: %s",
                pg.spec.pg_id[:8],
                exc,
            )
            if pg.state in (PG_PENDING, PG_RESCHEDULING):
                pg.state = PG_INFEASIBLE
                self._persist_pg(pg)
            for fut in pg.pending:
                if not fut.done():
                    fut.set_exception(
                        rpc.RpcError(
                            f"placement group {pg.spec.pg_id[:8]} "
                            f"scheduling failed: {exc}"
                        )
                    )
            pg.pending.clear()

        task.add_done_callback(_done)
        return task

    # -- persistence (reference: gcs_table_storage.cc write-through) ---------

    def _persist_actor(self, actor: ActorInfo) -> None:
        rec = actor.to_wire()
        rec["spec"] = actor.spec
        self.store.put("actors", actor.actor_id, msgpack.packb(rec, use_bin_type=True))

    def _persist_named(self) -> None:
        rec = {f"{ns}\x00{name}": aid for (ns, name), aid in self.named_actors.items()}
        self.store.put("named", "all", msgpack.packb(rec, use_bin_type=True))

    def _persist_kv(self, ns: str, key: str, value: Optional[bytes]) -> None:
        skey = f"{ns}\x00{key}"
        if value is None:
            self.store.delete("kv", skey)
        else:
            self.store.put("kv", skey, value)

    def _persist_job(self, job_id: str) -> None:
        self.store.put(
            "jobs", job_id, msgpack.packb(self.jobs[job_id], use_bin_type=True)
        )

    def _persist_pg(self, pg: PlacementGroupInfo) -> None:
        rec = {
            "spec": pg.spec.to_wire(),
            "state": pg.state,
            "bundle_nodes": pg.bundle_nodes,
        }
        self.store.put("pgs", pg.spec.pg_id, msgpack.packb(rec, use_bin_type=True))

    def _load_from_store(self) -> None:
        """Reload control-plane state after a GCS restart. Node membership is
        not persisted — raylets re-register over their reconnect loop."""
        for skey, value in self.store.get_all("kv").items():
            ns, _, key = skey.partition("\x00")
            self.kv[(ns, key)] = value
        for job_id, blob in self.store.get_all("jobs").items():
            self.jobs[job_id] = msgpack.unpackb(blob, raw=False)
        named = self.store.get_all("named").get("all")
        if named:
            for skey, aid in msgpack.unpackb(named, raw=False).items():
                ns, _, name = skey.partition("\x00")
                self.named_actors[(ns, name)] = aid
        for actor_id, blob in self.store.get_all("actors").items():
            rec = msgpack.unpackb(blob, raw=False)
            actor = ActorInfo(actor_id, rec["spec"])
            # Restart restore: the persisted state was validated as a legal
            # FSM state when it was written, not re-derivable statically.
            actor.state = rec["state"]  # protocol: disable=protocol-unresolvable
            actor.addr = tuple(rec["addr"]) if rec.get("addr") else None
            actor.worker_id = rec.get("worker_id")
            actor.node_id = rec.get("node_id")
            actor.num_restarts = rec.get("num_restarts", 0)
            actor.max_restarts = rec.get("max_restarts", 0)
            actor.death_cause = rec.get("death_cause")
            self.actors[actor_id] = actor
            if actor.state in (PENDING_CREATION, RESTARTING):
                # Reconciliation: the creation was in flight when the GCS
                # died. Any lease it held lives (or died) with its raylet,
                # which will cancel/re-grant on re-registration — re-drive
                # the placement from a clean slate rather than trusting a
                # half-recorded grant.
                actor.addr = None
                actor.worker_id = None
                actor.node_id = None
                self._pending_actor_queue.append(actor_id)
            elif actor.state == ALIVE:
                self._restored_unconfirmed.add(actor_id)
        for pg_id, blob in self.store.get_all("pgs").items():
            rec = msgpack.unpackb(blob, raw=False)
            pg = PlacementGroupInfo(PlacementGroupSpec.from_wire(rec["spec"]))
            # Restart restore (see actor restore above).
            pg.state = rec["state"]  # protocol: disable=protocol-unresolvable
            pg.bundle_nodes = rec.get("bundle_nodes") or pg.bundle_nodes
            self.placement_groups[pg_id] = pg
        if self._pending_actor_queue:
            self._wake_scheduler.set()

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> Tuple[str, int]:
        addr = await self.server.start()
        self.server.on_disconnect(self._on_disconnect)
        self._scheduler_task = rpc.spawn(self._actor_scheduler_loop())
        if config.health_check_period_s > 0:
            self._spawn(self._health_check_loop())
        # Resume work interrupted by a restart: unplaced PGs re-enter the
        # scheduling loop, and actors recorded ALIVE are reconciled against
        # the nodes that actually re-register.
        for pg in self.placement_groups.values():
            if pg.state in (PG_PENDING, PG_RESCHEDULING):
                self._spawn(self._schedule_pg(pg))
        if any(a.state == ALIVE for a in self.actors.values()):
            self._spawn(self._reconcile_restored_actors())
        if any(g.state == PG_CREATED for g in self.placement_groups.values()):
            self._spawn(self._reconcile_restored_pgs())
        if self.leader_term:
            # HA: assert leadership (record + pointer file) before serving
            # traffic, then keep the lease renewed from a background loop.
            from ray_tpu._private import gcs_ha

            gcs_ha.write_leadership(self.store, self.leader_term, addr)
            gcs_ha.write_leader_file(
                gcs_ha.leader_file_path(self._persist_path), *addr
            )
            gcs_ha.note_role(leader=True)
            self._spawn(self._leader_lease_loop(addr))
        logger.info("gcs listening on %s:%s", *addr)
        return addr

    async def _leader_lease_loop(self, addr) -> None:
        """Re-assert the leadership record (term + deadline) every third of
        the lease. A write rejected by the store's fence means a standby
        promoted past us — ``_on_store_fenced`` demotes; this loop just
        stops renewing."""
        from ray_tpu._private import gcs_ha
        from ray_tpu._private.rpc import StaleLeaderError

        while not self._stopping and not self.fenced:
            await asyncio.sleep(config.gcs_leader_lease_s / 3.0)
            if self._stopping or self.fenced:
                return
            try:
                gcs_ha.write_leadership(self.store, self.leader_term, addr)
            except StaleLeaderError:
                return  # the store's on_fenced callback owns the demotion

    def _on_store_fenced(self) -> None:
        """Store callback: a write from our term bounced off a newer fence.
        We are no longer the leader — stop serving cleanly (reads included:
        a fenced GCS's view diverges from the real one immediately)."""
        if self.fenced or self._stopping:
            self.fenced = True
            return
        self.fenced = True
        logger.warning(
            "gcs leadership term %d fenced by a newer leader: demoting",
            self.leader_term,
        )
        from ray_tpu._private import gcs_ha

        gcs_ha.note_role(leader=False)
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return
        # Short drain window before tearing the server down: the write that
        # discovered the fence is mid-dispatch, and its typed
        # StaleLeaderError reply must reach the caller before the transport
        # closes. The store is already fenced, so nothing can be acked in
        # the window — only rejections and stale reads escape.
        loop.call_later(0.1, lambda: rpc.spawn(self.stop()))

    async def _health_check_loop(self) -> None:
        """Active node health probing (reference: gcs_health_check_manager.cc
        + knobs ray_config_def.h:847-853). Connection loss already triggers
        death handling; this catches the wedged-but-connected raylet — a
        stuck event loop that keeps its TCP session alive while serving
        nothing. Each node is Pinged every period; `health_check_failure_
        threshold` consecutive timeouts/errors mark it DEAD."""
        await asyncio.sleep(config.health_check_initial_delay_s)
        while True:
            await asyncio.sleep(config.health_check_period_s)
            for node in list(self.nodes.values()):
                if node.state != NODE_ALIVE or node.health_probe_inflight:
                    continue
                node.health_probe_inflight = True
                rpc.spawn(self._probe_node(node))

    async def _probe_node(self, node: NodeInfo) -> None:
        try:
            await node.conn.call(
                "Ping", {}, timeout=config.health_check_timeout_s
            )
            node.health_misses = 0
            node.last_seen = time.monotonic()
        except (rpc.RpcError, asyncio.TimeoutError, OSError):
            node.health_misses += 1
            logger.warning(
                "health check miss %d/%d for node %s",
                node.health_misses,
                config.health_check_failure_threshold,
                node.node_id[:8],
            )
            if (
                node.health_misses >= config.health_check_failure_threshold
                and node.state == NODE_ALIVE
            ):
                logger.error(
                    "node %s failed %d consecutive health checks: marking DEAD",
                    node.node_id[:8],
                    node.health_misses,
                )
                await self._handle_node_death(node.node_id)
                # Drop the (still-open) link: an unwedged raylet must learn
                # it was declared dead — its client reconnects and
                # re-registers as a fresh node rather than running zombie
                # actors against a DEAD entry forever.
                try:
                    await node.conn.close()
                except Exception:
                    pass
        finally:
            node.health_probe_inflight = False

    async def _reconcile_restored_actors(self) -> None:
        """Post-restart sweep: an actor restored as ALIVE whose node never
        re-registered (or whose worker died during the outage) is treated as
        a worker death, driving the normal restart/fail FSM. Actors already
        confirmed by their raylet's re-registration report (the "actors"
        field on RegisterNode) are skipped — at hundreds of nodes the
        confirmations shrink the probe storm to just the genuinely
        uncertain residue."""
        await asyncio.sleep(config.health_check_initial_delay_s)
        unconfirmed, self._restored_unconfirmed = (
            self._restored_unconfirmed,
            set(),
        )
        for actor_id in unconfirmed:
            actor = self.actors.get(actor_id)
            if actor is None or actor.state != ALIVE:
                continue
            node = self.nodes.get(actor.node_id) if actor.node_id else None
            dead = node is None or node.state != NODE_ALIVE
            if not dead and actor.addr:
                try:
                    conn = node.conn
                    reply = await conn.call(
                        "KillWorker",
                        {"worker_id": actor.worker_id, "probe": True},
                        timeout=config.rpc_control_timeout_s,
                    )
                    dead = not reply.get("alive", False)
                except rpc.RpcError:
                    dead = True
            if dead:
                await self._on_actor_worker_death(
                    actor, "node or worker lost while GCS was down"
                )

    async def _reconcile_restored_pgs(self) -> None:
        """The PG analog: a group restored as CREATED whose bundle nodes
        never re-registered lost those reservations with the raylet — the
        same CREATED -> RESCHEDULING transition a node death drives, then
        the normal 2PC re-placement."""
        await asyncio.sleep(config.health_check_initial_delay_s)
        for pg in list(self.placement_groups.values()):
            if pg.state != PG_CREATED:
                continue
            lost = any(
                nid
                and (
                    nid not in self.nodes
                    or self.nodes[nid].state != NODE_ALIVE
                )
                for nid in pg.bundle_nodes
            )
            if lost:
                pg.state = PG_RESCHEDULING
                self._persist_pg(pg)
                self._spawn(self._schedule_pg(pg))

    async def stop(self) -> None:
        self._stopping = True
        if self._view_flush_handle is not None:
            self._view_flush_handle.cancel()
            self._view_flush_handle = None
        if self._scheduler_task:
            self._scheduler_task.cancel()
        for t in self._bg_tasks:
            t.cancel()
        await self.server.stop()
        # Graceful shutdown owns the store handle: close() flushes+fsyncs
        # the group-commit tail.
        self.store.close()

    async def crash(self) -> None:
        """Abrupt death (kill -9 analog, driven by the chaos ``crash_gcs``
        nemesis): transports drop and the store sees ``crash()`` instead of
        ``close()`` — no WAL checkpoint, no compaction, no final fsync —
        so the on-disk state is exactly what a killed process leaves, and
        recovery (torn-tail truncation + the reconcile sweeps) has to earn
        the restart."""
        self._stopping = True
        if self._view_flush_handle is not None:
            self._view_flush_handle.cancel()
            self._view_flush_handle = None
        if self._scheduler_task:
            self._scheduler_task.cancel()
        for t in self._bg_tasks:
            t.cancel()
        await self.server.stop()
        self.store.crash()

    def _register_handlers(self) -> None:
        s = self.server
        s.register("RegisterNode", self._register_node)
        s.register("UnregisterNode", self._unregister_node)
        s.register("ListEvents", self._list_events)
        s.register("GetAllNodes", self._get_all_nodes)
        s.register("UpdateResources", self._update_resources)
        s.register_sync("UpdateResources", self._update_resources_sync)
        s.register("CreateActor", self._create_actor)
        s.register("GetActor", self._get_actor)
        s.register("GetNamedActor", self._get_named_actor)
        s.register("ListActors", self._list_actors)
        s.register("ListNamedActors", self._list_named_actors)
        s.register("ReportActorReady", self._report_actor_ready)
        s.register("ReportWorkerDied", self._report_worker_died)
        s.register("ReportDeadlineStats", self._report_deadline_stats)
        s.register("ReportTelemetry", self._report_telemetry)
        s.register("GetTelemetry", self._get_telemetry)
        s.register("KillActor", self._kill_actor)
        s.register("KVPut", self._kv_put)
        s.register("KVGet", self._kv_get)
        s.register("KVDel", self._kv_del)
        s.register("KVKeys", self._kv_keys)
        s.register("KVExists", self._kv_exists)
        s.register("Subscribe", self._subscribe)
        s.register("Unsubscribe", self._unsubscribe)
        s.register("Publish", self._publish)
        s.register("Snapshot", self._snapshot)
        s.register("RegisterJob", self._register_job)
        s.register("JobFinished", self._job_finished)
        s.register("ListJobs", self._list_jobs)
        s.register("CreatePlacementGroup", self._create_pg)
        s.register("WaitPlacementGroupReady", self._wait_pg_ready)
        s.register("RemovePlacementGroup", self._remove_pg)
        s.register("GetPlacementGroup", self._get_pg)
        s.register("ListPlacementGroups", self._list_pgs)
        s.register("AddTaskEvents", self._add_task_events)
        s.register("ListTaskEvents", self._list_task_events)
        s.register("ReportSpans", self._report_spans)
        s.register("ListSpans", self._list_spans)
        s.register("GetClusterStatus", self._cluster_status)
        s.register("Ping", self._ping)
        s.register("ShipSubscribe", self._ship_subscribe)
        s.register("ShipSnapshot", self._ship_snapshot)

    # -- HA replication stream (cross-process standby feed) ------------------

    async def _ship_subscribe(self, conn: rpc.Connection, p: dict) -> dict:
        """Subscribe a cross-process standby to the quorum-acked commit
        stream; every subsequent group commit is pushed as one ShipFrames
        frame. The reply's watermark tells the standby where the pushes
        start — it bootstraps the gap before it with ShipSnapshot."""
        from ray_tpu._private.gcs_store import ReplicatedStoreClient

        if not isinstance(self.store, ReplicatedStoreClient):
            return {"ok": False, "term": 0, "seq": 0}
        self._ship_subs.add(conn)
        return {"ok": True, "term": self.store.term, "seq": self.store.seq}

    async def _ship_snapshot(self, conn: rpc.Connection, p: dict) -> dict:
        from ray_tpu._private.gcs_store import ReplicatedStoreClient

        if not isinstance(self.store, ReplicatedStoreClient):
            return {"ok": False, "term": 0, "seq": 0, "snap": b""}
        snap, term, seq = self.store.snapshot_tables()
        return {"ok": True, "term": term, "seq": seq, "snap": snap}

    def _on_ship_commit(self, frames: bytes, term: int, seq: int, prev_seq: int) -> None:
        """store.ship_listener: fan one quorum-acked group commit out to
        subscribed standbys. Runs on the GCS loop (the flush is scheduled
        with call_soon), so push_nowait is safe; a dead subscriber is
        dropped by the disconnect callback."""
        if not self._ship_subs:
            return
        payload = {"frames": frames, "term": term, "seq": seq, "prev_seq": prev_seq}
        for conn in list(self._ship_subs):
            try:
                conn.push_nowait("ShipFrames", payload)
            except rpc.ConnectionLost:
                self._ship_subs.discard(conn)

    # -- nodes --------------------------------------------------------------

    @staticmethod
    def _util_of(total: Dict[str, int], available: Dict[str, int]) -> float:
        util = 0.0
        for k, tot in total.items():
            if tot > 0 and not k.startswith("node:"):
                util = max(util, 1.0 - available.get(k, 0) / tot)
        return util

    def _bump_view(self, node: "NodeInfo", membership: bool = False) -> None:
        """One cluster-view mutation: refresh the node's slot in the
        utilization-sorted index (O(log n)), then broadcast the scheduling
        head so every raylet's candidate set converges without polling.
        ``membership=True`` (join/death/total change) also bumps the shape
        epoch that invalidates subscriber-side rings. With
        scheduler_view_batch_ms > 0 the broadcast is coalesced into the
        next flush window instead of published immediately."""
        nid = node.node_id
        old = self._node_utils.pop(nid, None)
        if old is not None:
            i = bisect.bisect_left(self._util_sorted, (old, nid))
            if i < len(self._util_sorted) and self._util_sorted[i] == (old, nid):
                del self._util_sorted[i]
        if node.state == NODE_ALIVE:
            util = self._util_of(node.total, node.available)
            bisect.insort(self._util_sorted, (util, nid))
            self._node_utils[nid] = util
        if membership:
            # Monotonic broadcast version: a retried RegisterNode bumping it
            # twice only costs one extra (idempotent) view broadcast —
            # subscribers key on "newest epoch wins", gaps are meaningless.
            self.view_epoch += 1  # exc-flow: disable=retry-unsafe-mutation
        batch_ms = config.scheduler_view_batch_ms
        if batch_ms <= 0:
            self._publish_view_head()
            return
        self._view_dirty = True
        if self._view_flush_handle is None:
            self._view_flush_handle = asyncio.get_running_loop().call_later(
                batch_ms / 1000.0, self._flush_view_head
            )

    def _flush_view_head(self) -> None:
        self._view_flush_handle = None
        if not self._view_dirty or self._stopping:
            return
        self._view_dirty = False
        self._publish_view_head()

    # The head is capped: a pick only ever samples among the least-utilized
    # candidates, and past a few dozen the marginal spread quality is nil
    # while broadcast decode cost at N subscribers is linear in head size.
    _VIEW_HEAD_CAP = 16

    def _publish_view_head(self) -> None:
        """Broadcast {"v", "epoch", "n", "head"}: the n alive-node count
        plus the ``head`` least-utilized nodes in utilization order —
        everything the hybrid top-k pick and spillback targeting consume,
        sized O(head cap) regardless of cluster size."""
        # Monotonic, gap-tolerant (see view_epoch above): double-bump on a
        # retried registration is benign.
        self.view_version += 1  # exc-flow: disable=retry-unsafe-mutation
        self._publish_msg("syncer:nodes", self._view_head_msg())

    def _view_head_msg(self) -> dict:
        head = []
        for util, nid in self._util_sorted:
            node = self.nodes.get(nid)
            if node is None or node.state != NODE_ALIVE:
                continue
            head.append(
                {
                    "node_id": nid,
                    "addr": list(node.addr),
                    "total": node.total,
                    "available": node.available,
                    "util": util,
                }
            )
            if len(head) >= self._VIEW_HEAD_CAP:
                break
        return {
            "v": self.view_version,
            "epoch": self.view_epoch,
            "n": len(self._util_sorted),
            "head": head,
        }

    async def _register_node(self, conn, p):
        info = NodeInfo(p["node_id"], p["addr"], p["resources"], p.get("labels"), conn)
        self.nodes[p["node_id"]] = info
        conn.context["node_id"] = p["node_id"]
        self.events.emit(
            "NODE_ADDED",
            f"node {p['node_id'][:8]} joined",
            node_id=p["node_id"],
            resources=p["resources"],
        )
        # Lease-picture rebuild after a GCS restart: the raylet reports the
        # actor workers it is hosting, confirming restored-ALIVE actors
        # without the reconcile sweep having to probe each one (reference:
        # NotifyGCSRestart — raylets own the ground truth about workers).
        for rec in p.get("actors") or []:
            actor = self.actors.get(rec.get("actor_id") or "")
            if (
                actor is not None
                and actor.state == ALIVE
                and actor.node_id == p["node_id"]
                and actor.worker_id == rec.get("worker_id")
            ):
                self._restored_unconfirmed.discard(actor.actor_id)
        self._publish_msg("nodes", {"event": "added", "node": info.to_wire()})
        self._bump_view(info, membership=True)
        self._wake_scheduler.set()
        return {"ok": True, "session_name": self.session_name}

    async def _list_events(self, conn, p):
        return {
            "events": self.events.list(
                severity=p.get("severity"),
                label=p.get("label"),
                limit=p.get("limit", 1000),
            )
        }

    async def _unregister_node(self, conn, p):
        """Graceful node departure (reference: DrainNode/UnregisterNode in
        gcs_node_manager.cc): same state transition as a detected death —
        actors on the node still fail over — but logged as a planned exit,
        not a health-check death."""
        await self._handle_node_death(p["node_id"], graceful=True)
        return {"ok": True}

    async def _get_all_nodes(self, conn, p):
        return {
            "nodes": [n.to_wire() for n in self.nodes.values()],
            "v": self.view_version,
            "epoch": self.view_epoch,
        }

    async def _update_resources(self, conn, p):
        return self._apply_update_resources(p)

    def _update_resources_sync(self, conn, msgid, p):
        """Inline fast path: resource reports are the highest-volume RPC the
        GCS serves (every grant/release on every raylet lands here) and the
        handler never awaits — dispatch it from data_received with no task.
        Raylets normally send reports as pushes (msgid None, no reply): the
        report is state-full and versioned, so a lost one is superseded by
        the next — the reference syncer's ack-free stream."""
        reply = self._apply_update_resources(p)
        if msgid is not None:
            conn.reply_nowait(msgid, "UpdateResources", reply)

    def _apply_update_resources(self, p: dict) -> dict:
        node = self.nodes.get(p["node_id"])
        if node is not None:
            rv = p.get("version")
            if rv is not None and rv <= node.report_version:
                # Out-of-order/stale report (reference: syncer drops
                # messages older than the last accepted version).
                return {"ok": True, "stale": True}
            if rv is not None:
                node.report_version = rv
            total_changed = bool(p.get("total")) and node.total != p["total"]
            changed = node.available != p["available"] or total_changed
            node.available = p["available"]
            node.last_seen = time.monotonic()
            if p.get("total"):
                node.total = p["total"]
            if changed:
                # No-change heartbeats (idle 1s reports) must not fan out
                # O(N^2) deltas across the cluster.
                self._bump_view(node, membership=total_changed)
                self._wake_scheduler.set()
        return {"ok": True}

    def _on_disconnect(self, conn: rpc.Connection) -> None:
        self._ship_subs.discard(conn)
        if self._stopping:
            return
        node_id = conn.context.get("node_id")
        if node_id and node_id in self.nodes:
            try:
                asyncio.get_running_loop()
                rpc.spawn(self._handle_node_death(node_id))
            except RuntimeError:
                pass  # loop already stopped (interpreter shutdown)
        self.publisher.remove_subscriber(conn)

    async def _handle_node_death(self, node_id: str, graceful: bool = False) -> None:
        node = self.nodes.get(node_id)
        if node is None or node.state == NODE_DEAD:
            return
        node.state = NODE_DEAD
        if graceful:
            logger.info("node %s unregistered (graceful shutdown)", node_id[:8])
        else:
            logger.warning("node %s died", node_id[:8])
        self.events.emit(
            "NODE_REMOVED",
            f"node {node_id[:8]} {'unregistered' if graceful else 'died'}",
            severity="INFO" if graceful else "WARNING",
            node_id=node_id,
            graceful=graceful,
        )
        self._publish_msg(
            "nodes",
            {
                "event": "removed",
                "node": node.to_wire(),
                # Object-location hint: every plasma copy addressed at this
                # raylet died with the node. Owners subscribed to "nodes"
                # match their IN_PLASMA markers against it and kick lineage
                # reconstruction eagerly (reference: object directory
                # location eviction on node removal).
                "lost_object_addr": list(node.addr),
            },
        )
        self._bump_view(node, membership=True)
        # Fail/restart actors that lived there.
        for actor in list(self.actors.values()):
            if actor.node_id == node_id and actor.state in (ALIVE, PENDING_CREATION, RESTARTING):
                await self._on_actor_worker_death(actor, f"node {node_id[:8]} died")
        # PGs with bundles there go back to pending.
        for pg in self.placement_groups.values():
            if pg.state == PG_CREATED and node_id in pg.bundle_nodes:
                pg.state = PG_RESCHEDULING
                self._spawn_pg_schedule(pg)

    # -- actor FSM ----------------------------------------------------------

    async def _create_actor(self, conn, p):
        spec = p["spec"]
        actor_id = spec["actor_id"]
        # Idempotent upsert: a retried CreateActor (e.g. across a GCS
        # restart, where the reply was lost) must not double-enqueue or
        # collide with its own name registration.
        existing_self = self.actors.get(actor_id)
        if existing_self is not None:
            if p.get("wait_alive", True) and existing_self.state in (
                PENDING_CREATION,
                RESTARTING,
            ):
                fut = asyncio.get_running_loop().create_future()
                existing_self.pending.append(fut)
                # actor.pending futures are flushed on every FSM transition
                # (ALIVE, restart, death, node death); creation legitimately
                # outwaits cluster scale-up, and callers bound the wait with
                # their own rpc_actor_create_timeout_s budget.
                return await fut  # rpc-flow: disable=unbounded-await
            return {"actor": existing_self.to_wire()}
        actor = ActorInfo(actor_id, spec)
        if actor.name:
            key = (actor.namespace, actor.name)
            if key in self.named_actors and self.named_actors[key] != actor_id:
                existing_id = self.named_actors[key]
                existing = self.actors.get(existing_id)
                if existing is not None and existing.state != DEAD:
                    if p.get("get_if_exists"):
                        return {"existing": True, "actor": existing.to_wire()}
                    raise rpc.RpcError(f"actor name {actor.name!r} already taken")
            self.named_actors[key] = actor_id
            self._persist_named()
        self.actors[actor_id] = actor
        self._persist_actor(actor)
        # Keyed-guarded: a retried CreateActor returns from the idempotent
        # upsert branch above (self.actors membership) before reaching this
        # append, so the queue cannot double-enqueue.
        self._pending_actor_queue.append(actor_id)  # exc-flow: disable=retry-unsafe-mutation
        self._wake_scheduler.set()
        if p.get("wait_alive", True):
            fut = asyncio.get_running_loop().create_future()
            actor.pending.append(fut)
            # Same contract as the upsert branch above: pending futures are
            # flushed on every actor FSM transition, callers own the budget.
            return await fut  # rpc-flow: disable=unbounded-await
        return {"actor": actor.to_wire()}

    async def _actor_scheduler_loop(self) -> None:
        """Places pending actors on nodes as resources allow (analog of
        GcsActorScheduler). Placements run CONCURRENTLY under a bounded
        semaphore — each placement awaits a full lease -> worker spawn ->
        CreateActor round trip, and serializing those would make N actors
        cost N round trips of wall clock (the reference scheduler also
        leases in parallel). Runs whenever resources or the queue change."""
        sem = asyncio.Semaphore(64)
        placing: set = set()

        async def place_one(actor_id: str) -> None:
            async with sem:
                actor = self.actors.get(actor_id)
                if actor is None or actor.state not in (
                    PENDING_CREATION, RESTARTING,
                ):
                    placing.discard(actor_id)
                    return
                try:
                    placed = await self._try_place_actor(actor)
                except Exception:
                    # An unexpected error (e.g. a lease RPC timing out
                    # under extreme load) must requeue the actor, never
                    # kill placement — every pending actor depends on it.
                    logger.exception(
                        "placing actor %s failed; will retry", actor_id[:8]
                    )
                    placed = False
                placing.discard(actor_id)
                if not placed:
                    await asyncio.sleep(0.2)  # resources busy; retry paced
                    self._pending_actor_queue.append(actor_id)
                    self._wake_scheduler.set()

        while True:
            await self._wake_scheduler.wait()
            self._wake_scheduler.clear()
            queue, self._pending_actor_queue = self._pending_actor_queue, []
            requeue: List[str] = []
            for actor_id in queue:
                if actor_id in placing:
                    # A placement for this actor is already in flight; the
                    # event behind this entry (e.g. a second death) must
                    # not be dropped — re-examine it next round.
                    requeue.append(actor_id)
                    continue
                placing.add(actor_id)
                rpc.spawn(place_one(actor_id))
            if requeue:
                self._pending_actor_queue.extend(requeue)
                await asyncio.sleep(0.2)
                self._wake_scheduler.set()

    async def _try_place_actor(self, actor: ActorInfo) -> bool:
        demand = ResourceSet.from_units(actor.spec.get("resources") or {})
        strategy = actor.spec.get("scheduling_strategy") or {}
        candidates = [n for n in self.nodes.values() if n.state == NODE_ALIVE]
        if strategy.get("node_id"):
            candidates = [n for n in candidates if n.node_id == strategy["node_id"]]
        labels = strategy.get("labels")
        if labels:
            # NODE_LABEL actor placement (reference: GcsActorScheduler +
            # scheduling_options.h NODE_LABEL): hard gates, soft prefers.
            from ray_tpu.util.scheduling_strategies import node_matches_labels

            hard = labels.get("hard") or {}
            soft = labels.get("soft") or {}
            candidates = [
                n for n in candidates if node_matches_labels(hard, n.labels)
            ]
            if soft:
                preferred = [
                    n for n in candidates if node_matches_labels(soft, n.labels)
                ]
                candidates = preferred or candidates
        if actor.spec.get("pg_id"):
            pg = self.placement_groups.get(actor.spec["pg_id"])
            if pg is None or pg.state != PG_CREATED:
                return False
            idx = actor.spec.get("bundle_index", -1)
            nodes_ok = set(
                pg.bundle_nodes if idx < 0 else [pg.bundle_nodes[idx]]
            )
            candidates = [n for n in candidates if n.node_id in nodes_ok]
        feasible = [
            n
            for n in candidates
            if demand.is_subset_of(ResourceSet.from_units(n.total))
        ]
        if not feasible:
            if not candidates and strategy.get("node_id"):
                await self._fail_actor(actor, "node affinity target not found")
                return True
            return False
        available = [
            n
            for n in feasible
            if demand.is_subset_of(ResourceSet.from_units(n.available))
        ]
        if not available:
            return False
        # Pack: most-utilized feasible node first (reference hybrid policy).
        node = max(available, key=lambda n: _utilization(n))
        try:
            reply = await node.conn.call(
                "LeaseWorkerForActor",
                {"spec": actor.spec},
                timeout=config.rpc_lease_timeout_s,
            )
        except (rpc.RpcError, asyncio.TimeoutError) as e:
            # On timeout the raylet may still hold the queued lease: cancel
            # it so the requeued placement can't double-create the actor.
            try:
                await node.conn.call(
                    "CancelWorkerLease",
                    {"lease_id": "actor:" + actor.spec["actor_id"]},
                    timeout=config.rpc_control_timeout_s,
                )
            except Exception:
                pass
            logger.warning("actor lease on %s failed: %r", node.node_id[:8], e)
            return False
        if not reply.get("granted"):
            return False
        actor.node_id = node.node_id
        actor.worker_id = reply["worker_id"]
        return True

    async def _report_actor_ready(self, conn, p):
        actor = self.actors.get(p["actor_id"])
        if actor is None:
            return {"ok": False}
        if p.get("error"):
            await self._fail_actor(actor, p["error"], creation_failed=True)
            return {"ok": True}
        actor.state = ALIVE
        telemetry.record_event(
            "gcs", "actor_state", actor_id=actor.actor_id, state=ALIVE
        )
        actor.addr = tuple(p["addr"])
        actor.worker_id = p["worker_id"]
        actor.node_id = p["node_id"]
        self._persist_actor(actor)
        result = {"actor": actor.to_wire()}
        for fut in actor.pending:
            if not fut.done():
                fut.set_result(result)
        actor.pending.clear()
        self._publish_msg(f"actor:{actor.actor_id}", actor.to_wire())
        return {"ok": True}

    async def _on_actor_worker_death(self, actor: ActorInfo, cause: str) -> None:
        if actor.state == DEAD:
            return
        if actor.max_restarts == -1 or actor.num_restarts < actor.max_restarts:
            actor.num_restarts += 1
            actor.state = RESTARTING
            telemetry.record_event(
                "gcs",
                "actor_state",
                actor_id=actor.actor_id,
                state=RESTARTING,
                cause=cause,
            )
            actor.addr = None
            logger.info(
                "restarting actor %s (%d/%s): %s",
                actor.actor_id[:8],
                actor.num_restarts,
                actor.max_restarts,
                cause,
            )
            self._persist_actor(actor)
            self._publish_msg(f"actor:{actor.actor_id}", actor.to_wire())
            # Keyed-guarded: a retried ReportWorkerDied sees the actor
            # already RESTARTING (caller filters on ALIVE/PENDING_CREATION)
            # and never re-enters this branch.
            self._pending_actor_queue.append(actor.actor_id)  # exc-flow: disable=retry-unsafe-mutation
            self._wake_scheduler.set()
            self.events.emit(
                "ACTOR_RESTARTING",
                f"actor {actor.actor_id[:8]} restarting "
                f"({actor.num_restarts}/{actor.max_restarts}): {cause}",
                severity="WARNING",
                actor_id=actor.actor_id,
                cause=cause,
            )
        else:
            await self._fail_actor(actor, cause)

    async def _fail_actor(self, actor: ActorInfo, cause: str, creation_failed=False) -> None:
        actor.state = DEAD
        telemetry.record_event(
            "gcs", "actor_state", actor_id=actor.actor_id, state=DEAD, cause=cause
        )
        self.events.emit(
            "ACTOR_DEAD",
            f"actor {actor.actor_id[:8]} died: {cause}",
            # Deliberate kills are lifecycle, not failures.
            severity="INFO" if "ray.kill" in cause else "ERROR",
            actor_id=actor.actor_id,
            cause=cause,
        )
        actor.death_cause = cause
        # Write-through BEFORE acking waiters or publishing: a crash in the
        # window would hand callers a DEAD outcome that a restarted GCS
        # reloads as ALIVE/PENDING (exc_flow ack-before-persist).
        if actor.name and self.named_actors.get((actor.namespace, actor.name)) == actor.actor_id:
            del self.named_actors[(actor.namespace, actor.name)]
            self._persist_named()
        self._persist_actor(actor)
        for fut in actor.pending:
            if not fut.done():
                if creation_failed:
                    fut.set_exception(rpc.RpcError(f"actor creation failed: {cause}"))
                else:
                    fut.set_result({"actor": actor.to_wire()})
        actor.pending.clear()
        self._publish_msg(f"actor:{actor.actor_id}", actor.to_wire())

    async def _report_worker_died(self, conn, p):
        """Raylet reports a worker process exit (reference:
        WorkerInfoGcsService.ReportWorkerFailure)."""
        for actor_id in p.get("actor_ids", []):
            actor = self.actors.get(actor_id)
            if actor is not None and actor.state in (ALIVE, PENDING_CREATION):
                await self._on_actor_worker_death(
                    actor, p.get("cause") or "worker process died"
                )
        return {"ok": True}

    async def _report_deadline_stats(self, conn, p):
        """Accumulate a worker's deadline-enforcement deltas (worker-side
        rpc.deadline_stats snapshot-and-reset, flushed periodically and on
        exit by worker_main). Overruns carry the worker id so a violation
        names the process that outlived its deadline."""
        agg = self.worker_deadline_stats
        agg["met"] += int(p.get("met", 0))
        agg["shed"] += int(p.get("shed", 0))
        agg["enforced"] += int(p.get("enforced", 0))
        wid = p.get("worker_id", "?")
        for method, late in p.get("overruns", []):
            agg["overruns"].append((wid, method, float(late)))
        return {"ok": True}

    async def _report_telemetry(self, conn, p):
        """Fold one process's runtime-telemetry flush (additive counter/
        histogram deltas, gauge last-values, drained flight-recorder
        events) into the cluster aggregate. RETRY_NONE like
        ReportDeadlineStats: a dropped report rides the sender's next
        flush instead of being re-issued."""
        telemetry.ingest(self.telemetry, {"node": p["node"], "metrics": p["metrics"]})
        src = p["source"]
        for ts, comp, ev, fields in p.get("events", []):
            fields = dict(fields)
            fields.setdefault("source", src)
            self.flight_events.append((ts, comp, ev, fields))
        return {"ok": True}

    def _drain_local_telemetry(self) -> None:
        """Fold this process's own registry into the aggregate. Covers a
        GCS running without any co-resident flusher; when a flusher IS
        active in this process (in-process raylet/driver), it owns the
        drain — snapshot-and-reset makes either owner exactly-once."""
        if telemetry.flusher_active():
            return
        payload = telemetry.flush_delta("gcs", "gcs")
        if payload is None:
            return
        telemetry.ingest(self.telemetry, payload)
        for ts, comp, ev, fields in payload.get("events", []):
            fields = dict(fields)
            fields.setdefault("source", "gcs")
            self.flight_events.append((ts, comp, ev, fields))

    async def _get_telemetry(self, conn, p):
        """The runtime-metric aggregate plus the deadline-stats aggregate
        (dashboard /metrics render input)."""
        self._drain_local_telemetry()
        wds = self.worker_deadline_stats
        return {
            "telemetry": self.telemetry,
            "worker_deadline_stats": {
                "met": wds["met"],
                "shed": wds["shed"],
                "enforced": wds["enforced"],
                "overruns": [list(o) for o in wds["overruns"]],
            },
        }

    async def _get_actor(self, conn, p):
        actor = self.actors.get(p["actor_id"])
        if actor is None:
            return {"actor": None}
        return {"actor": actor.to_wire()}

    async def _get_named_actor(self, conn, p):
        actor_id = self.named_actors.get((p.get("namespace") or "default", p["name"]))
        if actor_id is None:
            return {"actor": None}
        return {"actor": self.actors[actor_id].to_wire()}

    async def _list_actors(self, conn, p):
        return {"actors": [a.to_wire() for a in self.actors.values()]}

    async def _list_named_actors(self, conn, p):
        """Live named actors, optionally filtered by namespace (parity:
        ray.util.list_named_actors)."""
        ns_filter = p.get("namespace")
        names = []
        for (ns, name), actor_id in self.named_actors.items():
            actor = self.actors.get(actor_id)
            if actor is None or actor.state == DEAD:
                continue
            if ns_filter is not None and ns != ns_filter:
                continue
            names.append(name if ns_filter is not None else f"{ns}:{name}")
        return {"names": names}

    async def _kill_actor(self, conn, p):
        actor = self.actors.get(p["actor_id"])
        if actor is None:
            return {"ok": False}
        no_restart = p.get("no_restart", True)
        if no_restart:
            actor.max_restarts = actor.num_restarts  # exhaust restarts
            self._persist_actor(actor)
        node = self.nodes.get(actor.node_id) if actor.node_id else None
        if node is not None and node.state == NODE_ALIVE and actor.worker_id:
            try:
                await node.conn.call(
                    "KillWorker",
                    {"worker_id": actor.worker_id, "force": True},
                    timeout=config.rpc_control_timeout_s,
                )
            except rpc.RpcError:
                pass
        if no_restart and actor.state != DEAD:
            await self._fail_actor(actor, "killed via ray.kill")
        return {"ok": True}

    # -- kv -----------------------------------------------------------------

    async def _kv_put(self, conn, p):
        key = (p.get("ns") or "", p["key"])
        if not p.get("overwrite", True) and key in self.kv:
            return {"added": False}
        self.kv[key] = p["value"]
        self._persist_kv(key[0], key[1], p["value"])
        return {"added": True}

    async def _kv_get(self, conn, p):
        return {"value": self.kv.get((p.get("ns") or "", p["key"]))}

    async def _kv_del(self, conn, p):
        ns = p.get("ns") or ""
        if p.get("prefix"):
            keys = [k for k in self.kv if k[0] == ns and k[1].startswith(p["key"])]
            for k in keys:
                del self.kv[k]
                self._persist_kv(k[0], k[1], None)
            return {"deleted": len(keys)}
        removed = self.kv.pop((ns, p["key"]), None) is not None
        if removed:
            self._persist_kv(ns, p["key"], None)
        return {"deleted": int(removed)}

    async def _kv_keys(self, conn, p):
        ns = p.get("ns") or ""
        prefix = p.get("prefix") or ""
        return {"keys": [k[1] for k in self.kv if k[0] == ns and k[1].startswith(prefix)]}

    async def _kv_exists(self, conn, p):
        return {"exists": (p.get("ns") or "", p["key"]) in self.kv}

    # -- pubsub -------------------------------------------------------------

    async def _subscribe(self, conn, p):
        seq = self.publisher.subscribe(p["channel"], conn)
        # The current channel seqno is the subscriber's gap-detection
        # baseline: a resubscribing client compares it with the last seq it
        # saw and pulls a snapshot if publishes happened in between. The
        # epoch distinguishes "same publisher, you missed n messages" from
        # "new publisher (GCS restart), seqs restarted — resync".
        return {
            "ok": True,
            "seq": seq,
            "pub_epoch": self.publisher.epoch,
            "leader_term": self.leader_term,
        }

    async def _unsubscribe(self, conn, p):
        self.publisher.unsubscribe(p["channel"], conn)
        return {"ok": True}

    async def _publish(self, conn, p):
        self._publish_msg(p["channel"], p["msg"])
        return {"ok": True}

    async def _snapshot(self, conn, p):
        """Current state behind a pubsub channel, in the same shape a
        publish on that channel carries — what a subscriber that detected
        a seq gap (dropped backlog here, or a missed window across a
        reconnect) pulls to resynchronize instead of trusting a stale
        picture. Channels that carry events rather than state (e.g.
        "nodes", "logs") have no snapshot and return None; their consumers
        resync via their own full reads (GetAllNodes)."""
        channel = p["channel"]
        snap = None
        if channel.startswith("actor:"):
            actor = self.actors.get(channel[len("actor:"):])
            snap = None if actor is None else actor.to_wire()
        elif channel.startswith("pg:"):
            pg = self.placement_groups.get(channel[len("pg:"):])
            snap = None if pg is None else {"state": pg.state}
        elif channel == "syncer:nodes":
            snap = self._view_head_msg()
        return {
            "snapshot": snap,
            "seq": self.publisher.seqnos.get(channel, 0),
            "pub_epoch": self.publisher.epoch,
            "leader_term": self.leader_term,
        }

    def _publish_msg(self, channel: str, msg: Any) -> None:
        """Non-blocking fan-out: per-subscriber bounded queues + dedicated
        drain tasks (a slow subscriber drops ITS backlog, never stalls the
        control plane). Under HA every control-plane record carries the
        leader term, so a subscriber can drop a stale pre-failover message
        that arrives after it has seen the new leader."""
        if self.leader_term and isinstance(msg, dict):
            msg = {**msg, "leader_term": self.leader_term}
        self.publisher.publish(channel, msg)

    # -- jobs ---------------------------------------------------------------

    async def _register_job(self, conn, p):
        self.jobs[p["job_id"]] = {
            "job_id": p["job_id"],
            "driver_addr": p.get("driver_addr"),
            "start_time": time.time(),
            "state": "RUNNING",
            "entrypoint": p.get("entrypoint", ""),
        }
        self._persist_job(p["job_id"])
        return {"ok": True}

    async def _job_finished(self, conn, p):
        job = self.jobs.get(p["job_id"])
        if job:
            job["state"] = "FINISHED"
            job["end_time"] = time.time()
            self._persist_job(p["job_id"])
        # Kill non-detached actors owned by the job.
        for actor in list(self.actors.values()):
            if actor.job_id == p["job_id"] and not actor.detached and actor.state != DEAD:
                await self._kill_actor(conn, {"actor_id": actor.actor_id, "no_restart": True})
        return {"ok": True}

    async def _list_jobs(self, conn, p):
        return {"jobs": list(self.jobs.values())}

    # -- placement groups (2PC driver; reference gcs_placement_group_scheduler.cc)

    async def _create_pg(self, conn, p):
        spec = PlacementGroupSpec.from_wire(p["spec"])
        pg = PlacementGroupInfo(spec)
        self.placement_groups[spec.pg_id] = pg
        self._persist_pg(pg)
        self._spawn_pg_schedule(pg)
        if p.get("wait_ready"):
            fut = asyncio.get_running_loop().create_future()
            pg.pending.append(fut)
            # pg.pending futures are resolved by _schedule_pg on creation,
            # infeasibility (PG_INFEASIBLE after its 120 s horizon), removal,
            # and — via _spawn_pg_schedule supervision — scheduler crashes.
            return await fut  # rpc-flow: disable=unbounded-await
        return {"pg_id": spec.pg_id, "state": pg.state}

    async def _schedule_pg(self, pg: PlacementGroupInfo) -> None:
        spec = pg.spec
        deadline = time.monotonic() + 120
        while pg.state in (PG_PENDING, PG_RESCHEDULING):
            placement = self._place_bundles(spec)
            if placement is not None:
                ok = await self._try_commit_pg(pg, placement)
                if pg.state == PG_REMOVED:
                    # Removed while the 2PC was in flight: drop the fresh
                    # reservations instead of resurrecting the PG.
                    if ok:
                        for nid in set(placement):
                            node = self.nodes.get(nid)
                            if node and node.state == NODE_ALIVE:
                                try:
                                    await node.conn.call(
                                        "ReleasePGBundles",
                                        {"pg_id": spec.pg_id},
                                        timeout=config.rpc_pg_timeout_s,
                                    )
                                except rpc.RpcError:
                                    pass
                    return
                if ok:
                    pg.state = PG_CREATED
                    pg.bundle_nodes = placement
                    self._persist_pg(pg)
                    for fut in pg.pending:
                        if not fut.done():
                            fut.set_result({"pg_id": spec.pg_id, "state": PG_CREATED})
                    pg.pending.clear()
                    self._publish_msg(f"pg:{spec.pg_id}", {"state": PG_CREATED})
                    self._wake_scheduler.set()
                    return
            if time.monotonic() > deadline:
                break
            await asyncio.sleep(0.2)
        if pg.state in (PG_PENDING, PG_RESCHEDULING):
            # Record terminal state so later WaitPlacementGroupReady calls
            # fail fast instead of parking a future nothing will resolve.
            pg.state = PG_INFEASIBLE
            self._persist_pg(pg)
            for fut in pg.pending:
                if not fut.done():
                    fut.set_exception(
                        rpc.RpcError(f"placement group {spec.pg_id[:8]} infeasible")
                    )
            pg.pending.clear()

    def _place_bundles(self, spec: PlacementGroupSpec) -> Optional[List[str]]:
        """Map bundles to nodes per strategy against the current resource view.
        Reference: bundle_scheduling_policy.cc (PACK/SPREAD/STRICT_*)."""
        alive = [n for n in self.nodes.values() if n.state == NODE_ALIVE]
        if not alive:
            return None
        avail = {n.node_id: ResourceSet.from_units(n.available) for n in alive}
        demands = [ResourceSet.from_units(b) for b in spec.bundles]
        placement: List[Optional[str]] = [None] * len(demands)

        def fits(nid, demand):
            return demand.is_subset_of(avail[nid])

        order = sorted(avail, key=lambda nid: -_utilization(self.nodes[nid]))
        if spec.strategy == "STRICT_PACK":
            for nid in order:
                total = ResourceSet()
                for d in demands:
                    total = total + d
                if total.is_subset_of(avail[nid]):
                    return [nid] * len(demands)
            return None
        if spec.strategy == "STRICT_SPREAD":
            if len(alive) < len(demands):
                return None
            used: Set[str] = set()
            for i, d in enumerate(demands):
                pick = next(
                    (nid for nid in order if nid not in used and fits(nid, d)), None
                )
                if pick is None:
                    return None
                placement[i] = pick
                used.add(pick)
                avail[pick] = avail[pick] - d
            return placement  # type: ignore[return-value]
        # PACK: prefer filling utilized nodes; SPREAD: prefer emptiest first.
        if spec.strategy == "SPREAD":
            order = list(reversed(order))
        for i, d in enumerate(demands):
            pick = next((nid for nid in order if fits(nid, d)), None)
            if pick is None:
                return None
            placement[i] = pick
            avail[pick] = avail[pick] - d
            if spec.strategy == "SPREAD":
                order.remove(pick)
                order.append(pick)  # round-robin
        return placement  # type: ignore[return-value]

    async def _try_commit_pg(self, pg: PlacementGroupInfo, placement: List[str]) -> bool:
        """Two-phase commit of bundle reservations across raylets."""
        spec = pg.spec
        by_node: Dict[str, List[int]] = {}
        for idx, nid in enumerate(placement):
            by_node.setdefault(nid, []).append(idx)
        prepared: List[str] = []
        for nid, idxs in by_node.items():
            node = self.nodes.get(nid)
            if node is None or node.state != NODE_ALIVE:
                break
            try:
                reply = await node.conn.call(
                    "PreparePGBundles",
                    {
                        "pg_id": spec.pg_id,
                        "bundles": {str(i): spec.bundles[i] for i in idxs},
                    },
                    timeout=config.rpc_pg_timeout_s,
                )
            except rpc.RpcError:
                break
            if not reply.get("success"):
                break
            prepared.append(nid)
        else:
            committed = True
            for nid in prepared:
                try:
                    await self.nodes[nid].conn.call(
                        "CommitPGBundles",
                        {"pg_id": spec.pg_id},
                        timeout=config.rpc_pg_timeout_s,
                    )
                except rpc.RpcError:
                    committed = False  # node died mid-commit: roll back all
                    break
            if committed:
                return True
        for nid in prepared:  # rollback
            try:
                await self.nodes[nid].conn.call(
                    "ReleasePGBundles",
                    {"pg_id": spec.pg_id},
                    timeout=config.rpc_pg_timeout_s,
                )
            except rpc.RpcError:
                pass
        return False

    async def _wait_pg_ready(self, conn, p):
        pg = self.placement_groups.get(p["pg_id"])
        if pg is None:
            raise rpc.RpcError(f"unknown placement group {p['pg_id'][:12]}")
        if pg.state == PG_CREATED:
            return {"pg_id": p["pg_id"], "state": PG_CREATED}
        if pg.state == PG_REMOVED:
            raise rpc.RpcError("placement group was removed")
        if pg.state == PG_INFEASIBLE:
            return {"pg_id": p["pg_id"], "state": PG_INFEASIBLE}
        fut = asyncio.get_running_loop().create_future()
        pg.pending.append(fut)
        if p.get("timeout") is not None:
            try:
                return await asyncio.wait_for(fut, p["timeout"])
            except asyncio.TimeoutError:
                if fut in pg.pending:
                    pg.pending.remove(fut)
                return {"pg_id": p["pg_id"], "state": pg.state}
        # pg.ready(timeout=None) is the blocking API: parking until the PG
        # reaches a terminal state is the contract. Every terminal path
        # resolves pg.pending — _schedule_pg success, _remove_pg, and the
        # _spawn_pg_schedule crash supervisor — so the future cannot strand.
        return await fut  # rpc-flow: disable=unbounded-await

    async def _remove_pg(self, conn, p):
        pg = self.placement_groups.get(p["pg_id"])
        if pg is None:
            return {"ok": False}
        pg.state = PG_REMOVED
        self._persist_pg(pg)
        # Wake any WaitPlacementGroupReady waiters parked while pending.
        for fut in pg.pending:
            if not fut.done():
                fut.set_exception(rpc.RpcError("placement group was removed"))
        pg.pending.clear()
        for nid in set(n for n in pg.bundle_nodes if n):
            node = self.nodes.get(nid)
            if node and node.state == NODE_ALIVE:
                try:
                    await node.conn.call(
                        "ReleasePGBundles",
                        {"pg_id": p["pg_id"]},
                        timeout=config.rpc_pg_timeout_s,
                    )
                except rpc.RpcError:
                    pass
        return {"ok": True}

    async def _get_pg(self, conn, p):
        pg = self.placement_groups.get(p["pg_id"])
        if pg is None:
            return {"pg": None}
        return {
            "pg": {
                "pg_id": pg.spec.pg_id,
                "state": pg.state,
                "strategy": pg.spec.strategy,
                "bundles": pg.spec.bundles,
                "bundle_nodes": pg.bundle_nodes,
                "name": pg.spec.name,
            }
        }

    async def _list_pgs(self, conn, p):
        return {
            "pgs": [
                (await self._get_pg(conn, {"pg_id": pid}))["pg"]
                for pid in self.placement_groups
            ]
        }

    # -- task events / status ----------------------------------------------

    async def _add_task_events(self, conn, p):
        for e in p["events"]:
            # Trace spans (state="SPAN" from make_submit_ctx/execute_scope)
            # live in their own ring beside the task-state events, so the
            # span store and task-event store trim independently and
            # ListSpans never scans lifecycle events.
            if e.get("state") == "SPAN":
                self.spans.append(e)
            else:
                self.task_events.append(e)
        if len(self.task_events) > 100000:
            self.task_events = self.task_events[-50000:]
        if len(self.spans) > 100000:
            self.spans = self.spans[-50000:]
        return {"ok": True}

    async def _list_task_events(self, conn, p):
        events = self.task_events
        if p.get("job_id"):
            events = [e for e in events if e.get("job_id") == p["job_id"]]
        return {"events": events[-(p.get("limit") or 1000):]}

    async def _report_spans(self, conn, p):
        """Fold one process's runtime-span flush into the span ring,
        stamping source attribution the way _report_telemetry stamps
        flight events. RETRY_NONE: an undelivered batch folds back into
        the sender's buffer and rides the next flush."""
        src, node = p["source"], p.get("node")
        for span in p["spans"]:
            span.setdefault("worker_id", src)
            if node is not None:
                span.setdefault("node_id", node)
            self.spans.append(span)
        if len(self.spans) > 100000:
            self.spans = self.spans[-50000:]
        return {"ok": True}

    def _drain_local_spans(self) -> None:
        """Fold this process's own span buffer into the ring at query time
        (freshness for in-process clusters). Skipped when a flusher is
        active here — it owns delivery; snapshot-and-reset makes either
        owner exactly-once."""
        from ray_tpu.util import tracing

        if tracing.flusher_active():
            return
        for span in tracing.span_flush_delta():
            span.setdefault("worker_id", "gcs")
            # Observability ring, not control-plane state: span_flush_delta
            # snapshots-and-resets, so a retried ListSpans drains an empty
            # delta; worst case is a duplicated trace row.
            self.spans.append(span)  # exc-flow: disable=retry-unsafe-mutation

    async def _list_spans(self, conn, p):
        """Server-side-filtered span read: the trace_id filter and limit
        run here, against the ring, so the client never receives the
        whole table (the satellite fix over the old ListTaskEvents
        scan-and-filter-client-side path)."""
        self._drain_local_spans()
        spans = self.spans
        if p.get("trace_id"):
            spans = [s for s in spans if s.get("trace_id") == p["trace_id"]]
        return {"spans": spans[-(p.get("limit") or 10000):]}

    async def _cluster_status(self, conn, p):
        return {
            "nodes": [n.to_wire() for n in self.nodes.values()],
            "actors": sum(1 for a in self.actors.values() if a.state == ALIVE),
            "placement_groups": sum(
                1 for g in self.placement_groups.values() if g.state == PG_CREATED
            ),
            "jobs": list(self.jobs.values()),
        }

    async def _ping(self, conn, p):
        return {"pong": True, "time": time.time()}


def _utilization(node: NodeInfo) -> float:
    util = 0.0
    for k, total in node.total.items():
        if total > 0:
            util = max(util, 1.0 - node.available.get(k, 0) / total)
    return util


class GcsClient:
    """Typed async client for the GCS (used by raylets, workers, drivers).

    Reconnecting: when the GCS restarts (fault-tolerance mode), the
    underlying ``rpc.RetryableConnection`` redials the same address with
    jittered backoff (``RetryPolicy.for_calls``), re-subscribes pubsub
    channels, fires registered ``on_reconnect`` callbacks (raylets
    re-register their node there), and transparently retries calls whose
    wire retry class permits it — every GCS handler is an idempotent
    upsert/read against keyed state, so the channel's default retry class
    is "safe". Analog of the reference's reconnect protocol around GCS
    restarts (NotifyGCSRestart, node_manager.proto:373; retryable gRPC
    client + gcs_rpc_client.h failover call queue)."""

    def __init__(self, conn: rpc.Connection, resolver=None):
        self.conn = conn
        self._resolver = resolver
        self._sub_handlers: Dict[str, List] = {}
        self._handlers = conn._handlers
        self._handlers.setdefault("Pub", self._on_pub)
        self._handlers.setdefault("PubBatch", self._on_pub_batch)
        # Sync fast path: pub deliveries dispatch inline from data_received
        # (no task per broadcast). The async registrations above stay as
        # fallback for connections without sync-handler support.
        self._sync_handlers = conn._sync_handlers
        self._sync_handlers.setdefault("Pub", self._on_pub_sync)
        self._sync_handlers.setdefault("PubBatch", self._on_pub_batch_sync)
        # Per-channel last-seen publish seqno + publisher epoch (gap
        # detection; see Publisher docstring and docs/fault_tolerance.md)
        # and leader term (HA: a term change is a new control plane — a
        # snapshot pull is mandatory even when epoch/seq happen to align).
        self._sub_seq: Dict[str, int] = {}
        self._sub_epoch: Dict[str, str] = {}
        self._sub_term: Dict[str, int] = {}
        self._on_reconnect: List = []
        # ``resolver``: async () -> (host, port) | None, consulted before
        # every redial so the client follows the current GCS leader across
        # failover instead of re-dialing the dead primary (gcs_ha.py).
        self._rc = rpc.RetryableConnection(
            self._redial,
            conn=conn,
            policy=rpc.RetryPolicy.for_calls(),
            default_retry=wire.RETRY_SAFE,
            on_reconnect=self._post_reconnect,
            name="gcs",
            resolver=resolver,
        )

    def on_reconnect(self, fn) -> None:
        """Register ``async fn(client)`` run after every successful redial."""
        self._on_reconnect.append(fn)

    @property
    def _closed(self) -> bool:
        return self._rc.closed

    async def close(self) -> None:
        """Terminal close: no reconnection afterwards. A stopping raylet must
        call this first, or a straggler RPC resurrects the 'dead' node in the
        GCS by re-registering through the reconnect path."""
        await self._rc.close()

    async def _redial(self, addr=None) -> rpc.Connection:
        addr = addr or self.conn.remote_addr or self.conn.peername
        if addr is None:
            raise rpc.ConnectionLost("gcs connection lost (no address to redial)")
        # With a resolver, each dial must give up fast: the resolved address
        # may be a dead primary whose leader file hasn't flipped yet, and
        # the resolver is only re-consulted between dial attempts — a 30s
        # dial budget would pin the dead address across the whole failover.
        # Without one the address is fixed, so patience is the right move
        # (a restarting GCS comes back on the same port).
        policy = (
            rpc.RetryPolicy.for_dial()
            if self._resolver is not None
            else rpc.RetryPolicy.for_calls()
        )
        conn = await rpc.connect(
            addr[0],
            addr[1],
            handlers=self._handlers,
            sync_handlers=self._sync_handlers,
            policy=policy,
        )
        conn.remote_addr = tuple(addr)
        return conn

    async def _post_reconnect(self, conn: rpc.Connection) -> None:
        # self.conn must point at the fresh link before the callbacks run:
        # they issue calls through this client (raylet re-registration).
        self.conn = conn
        for channel in list(self._sub_handlers):
            reply = await conn.call("Subscribe", {"channel": channel})
            self._check_resubscribe(channel, reply)
        for fn in self._on_reconnect:
            try:
                await fn(self)
            except Exception:
                logger.exception("gcs on_reconnect callback failed")
        addr = conn.remote_addr or conn.peername
        if addr is not None:
            logger.info("reconnected to gcs at %s:%s", *addr)

    def _check_resubscribe(self, channel: str, reply: dict) -> None:
        """Compare the resubscribe baseline with the last seq we saw: an
        advanced seq (missed publishes while disconnected) or a changed
        publisher epoch (GCS restart — seqs restarted from zero) both mean
        our picture may be stale, so pull a snapshot. A changed *leader
        term* (HA failover) is unconditionally stale: the new leader
        rebuilt its state from the replicated log, so even aligned seqnos
        describe a different history — the snapshot pull is mandatory."""
        seq, epoch = reply.get("seq"), reply.get("pub_epoch")
        term = reply.get("leader_term")
        if seq is None:
            return
        last = self._sub_seq.get(channel)
        last_term = self._sub_term.get(channel)
        stale = last is not None and (
            self._sub_epoch.get(channel) != epoch
            or seq > last
            or (term is not None and last_term is not None and term != last_term)
        )
        self._sub_seq[channel] = seq
        if epoch is not None:
            self._sub_epoch[channel] = epoch
        if term is not None:
            self._sub_term[channel] = term
        if stale:
            self._note_gap(channel, "resubscribe")

    async def _ensure_connected(self) -> rpc.Connection:
        return await self._rc._ensure_connected()

    def _on_pub_sync(self, conn, msgid, p):
        """Inline pub delivery from data_received — no task per push.
        Registered as a sync handler so a view-head broadcast costs zero
        task creations on each of N subscribers; async subscriber handlers
        still run (spawned), sync ones run inline."""
        self._dispatch_pub_sync(p["channel"], p["msg"], p.get("seq"))

    def _on_pub_batch_sync(self, conn, msgid, p):
        for channel, msg, seq in p["items"]:
            self._dispatch_pub_sync(channel, msg, seq)

    async def _on_pub(self, conn, p):
        await self._dispatch_pub(p["channel"], p["msg"], p.get("seq"))

    async def _on_pub_batch(self, conn, p):
        for channel, msg, seq in p["items"]:
            await self._dispatch_pub(channel, msg, seq)

    async def _dispatch_pub(self, channel: str, msg, seq) -> None:
        self._dispatch_pub_sync(channel, msg, seq)

    def _dispatch_pub_sync(self, channel: str, msg, seq) -> None:
        if isinstance(msg, dict) and "leader_term" in msg:
            term = msg["leader_term"]
            known = self._sub_term.get(channel)
            if known is not None and term < known:
                # Stale pre-failover message that outlived its leader
                # (buffered on the old link, delivered after promotion):
                # never deliver it — we already follow a newer term.
                self._note_gap(channel, "stale-term")
                return
            if known is None or term > known:
                self._sub_term[channel] = term
        if seq is not None:
            last = self._sub_seq.get(channel)
            if last is not None:
                if seq <= last:
                    return  # duplicate / already covered by a snapshot
                if seq > last + 1:
                    # The publisher shed part of OUR backlog (bounded-queue
                    # overflow): the stream is no longer a complete history,
                    # so resynchronize from a snapshot.
                    self._note_gap(channel, "overflow")
            self._sub_seq[channel] = seq
        self._deliver_sync(channel, msg)

    def _deliver_sync(self, channel: str, msg) -> None:
        for fn in list(self._sub_handlers.get(channel, [])):
            try:
                res = fn(msg)
                if asyncio.iscoroutine(res):
                    # Async subscriber handler: runs as its own task. Sync
                    # handlers (the hot view-head path) run inline.
                    rpc.spawn(res)
            except Exception:
                logger.exception("pubsub handler failed for %s", channel)

    async def _deliver(self, channel: str, msg) -> None:
        self._deliver_sync(channel, msg)

    def _note_gap(self, channel: str, cause: str) -> None:
        _TEL_SUB_GAP.cell(cause=cause).inc()
        logger.info("pubsub gap on %r (%s): pulling snapshot", channel, cause)
        rpc.spawn(self._pull_snapshot(channel))

    async def _pull_snapshot(self, channel: str) -> None:
        """Resync one channel: fetch the current state behind it and feed
        it to the handlers as if published. Channels without snapshot
        semantics return None (their consumers resync elsewhere)."""
        try:
            reply = await self.call("Snapshot", {"channel": channel})
        except (rpc.RpcError, asyncio.TimeoutError, OSError):
            logger.warning("snapshot pull for %r failed", channel)
            return
        seq, epoch = reply.get("seq"), reply.get("pub_epoch")
        if seq is not None and seq > self._sub_seq.get(channel, -1):
            self._sub_seq[channel] = seq
        if epoch is not None:
            self._sub_epoch[channel] = epoch
        term = reply.get("leader_term")
        if term is not None and term > self._sub_term.get(channel, -1):
            self._sub_term[channel] = term
        snap = reply.get("snapshot")
        if snap is not None:
            await self._deliver(channel, snap)

    async def subscribe(self, channel: str, handler, snapshot: bool = False) -> None:
        """Attach a handler. ``snapshot=True`` additionally delivers the
        channel's current state to THIS handler right after subscribing,
        closing the subscribe-after-publish race (the watcher that arrives
        late still observes the state it missed) — the general form of the
        one-shot GetActor the serve controller's death watch used to do."""
        fresh = channel not in self._sub_handlers
        self._sub_handlers.setdefault(channel, []).append(handler)
        conn = await self._ensure_connected()
        reply = await conn.call("Subscribe", {"channel": channel})
        seq, epoch = reply.get("seq"), reply.get("pub_epoch")
        if fresh and seq is not None:
            # Baseline only for a newly tracked channel: an existing
            # tracking regime may have deliveries in flight whose seqs a
            # forward jump here would wrongly mark as duplicates.
            self._sub_seq[channel] = seq
            if epoch is not None:
                self._sub_epoch[channel] = epoch
            if reply.get("leader_term") is not None:
                self._sub_term[channel] = reply["leader_term"]
        if snapshot:
            try:
                snap = (await self.call("Snapshot", {"channel": channel}))[
                    "snapshot"
                ]
            except (rpc.RpcError, asyncio.TimeoutError, OSError):
                snap = None
            if snap is not None:
                res = handler(snap)
                if asyncio.iscoroutine(res):
                    await res

    async def unsubscribe(self, channel: str, handler) -> None:
        """Detach one handler; drops the server-side subscription (and the
        reconnect re-subscribe) once the channel has no handlers left."""
        handlers = self._sub_handlers.get(channel)
        if handlers is None:
            return
        try:
            handlers.remove(handler)
        except ValueError:
            pass
        if handlers:
            return
        del self._sub_handlers[channel]
        conn = await self._ensure_connected()
        await conn.call("Unsubscribe", {"channel": channel})

    async def publish(self, channel: str, msg) -> None:
        await self.call("Publish", {"channel": channel, "msg": msg})

    async def kv_put(self, key: str, value: bytes, ns: str = "", overwrite=True) -> bool:
        r = await self.call(
            "KVPut", {"ns": ns, "key": key, "value": value, "overwrite": overwrite}
        )
        return r["added"]

    async def kv_get(self, key: str, ns: str = "") -> Optional[bytes]:
        return (await self.call("KVGet", {"ns": ns, "key": key}))["value"]

    async def kv_del(self, key: str, ns: str = "", prefix=False) -> int:
        return (await self.call("KVDel", {"ns": ns, "key": key, "prefix": prefix}))[
            "deleted"
        ]

    async def kv_exists(self, key: str, ns: str = "") -> bool:
        return (await self.call("KVExists", {"key": key, "ns": ns}))["exists"]

    async def kv_keys(self, prefix: str = "", ns: str = "") -> List[str]:
        return (await self.call("KVKeys", {"ns": ns, "prefix": prefix}))["keys"]

    async def call(self, method: str, payload=None, timeout=None):
        return await self._rc.call(method, payload, timeout)
