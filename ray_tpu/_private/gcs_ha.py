"""HA control plane: leadership records, leader resolution, warm standby.

The replicated store (gcs_store.ReplicatedStoreClient) gives the GCS a
log that survives machine loss; this module turns that into a highly
available control plane (reference: the GCS-backed-by-Redis deployment
plus its "who is leader" coordination, in miniature):

- **Leadership record**: the serving GCS writes ``meta/leadership`` —
  ``{term, deadline, host, port}`` — through the replicated store and
  renews it every third of ``gcs_leader_lease_s``. The write itself is
  the fencing primitive: it carries the writer's term, so a deposed
  leader's renewal bounces off the store's fence with StaleLeaderError
  and the GCS demotes (stops serving) instead of split-braining.
- **Warm standby** (``GcsStandby``): mirrors the leader's quorum-acked
  commit stream, watches the leadership record, and when the lease
  deadline expires unrenewed, promotes: claims the next term
  (gcs_store.try_claim_term — losers re-enter the watch loop) and builds
  a ``GcsServer`` over the replicated store at that term. Opening the
  store runs the quorum election: a majority of members must be
  reachable, and the highest (term, seq) among them is adopted — any ack
  quorum intersects any such majority, so every acknowledged record
  survives even when the single freshest file sits on an unreachable
  laggard. Opening also raises the fence on every reachable member
  before the first write, and the new server's fresh publisher epoch +
  term-stamped records drive every resubscribing client through a
  snapshot pull (docs/fault_tolerance.md).

  Two feed modes (``gcs_standby_mode``): ``"rpc"`` (default) subscribes
  to the leader over ShipFrames/ShipSnapshot wire RPCs — the standby can
  be its own OS process on another host (``python -m
  ray_tpu._private.gcs_ha``) — and falls back to file tailing while the
  leader is unreachable; ``"file"`` tails a follower log on shared
  storage (ReplicaTailer).
- **Leader pointer file**: ``<persist_path>.leader`` holds "host port",
  atomically replaced on every (re)election. ``file_resolver`` adapts it
  to RetryableConnection's pluggable resolver so raylets/workers re-dial
  the *current* leader, not the dead primary's address.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from typing import Optional, Tuple

import msgpack

from ray_tpu._private import telemetry
from ray_tpu._private.common import config

logger = logging.getLogger(__name__)

LEADERSHIP_TABLE = "meta"
LEADERSHIP_KEY = "leadership"

_TEL_ROLE = telemetry.gauge(
    "gcs", "role", "this process's GCS role: 1 leader, 0 standby/demoted"
)
_TEL_FAILOVERS = telemetry.counter(
    "gcs", "failovers", "standby promotions to leader"
)


def note_role(leader: bool) -> None:
    _TEL_ROLE.set(1.0 if leader else 0.0)


def note_failover() -> None:
    _TEL_FAILOVERS.inc()


# -- leadership record -------------------------------------------------------


def write_leadership(store, term: int, addr: Tuple[str, int]) -> None:
    """One lease assertion/renewal: term + fresh deadline, written through
    the (fencing) store. Raises StaleLeaderError if a newer leader exists."""
    rec = {
        "term": term,
        "deadline": time.time() + config.gcs_leader_lease_s,
        "host": addr[0],
        "port": addr[1],
    }
    store.put(
        LEADERSHIP_TABLE, LEADERSHIP_KEY, msgpack.packb(rec, use_bin_type=True)
    )
    # The record IS the lease: it must be on the followers before the
    # deadline means anything, not parked in the group-commit buffer.
    if hasattr(store, "flush"):
        store.flush()


def read_leadership(source) -> Optional[dict]:
    """Decode the leadership record from anything with ``get(table, key)``
    (a StoreClient or a ReplicaTailer)."""
    blob = source.get(LEADERSHIP_TABLE, LEADERSHIP_KEY)
    if not blob:
        return None
    return msgpack.unpackb(blob, raw=False)


# -- leader pointer file -----------------------------------------------------


def leader_file_path(persist_path: Optional[str]) -> Optional[str]:
    if config.gcs_leader_file:
        return config.gcs_leader_file
    if not persist_path:
        return None
    return persist_path + ".leader"


def write_leader_file(path: Optional[str], host: str, port: int) -> None:
    """Atomically publish the serving address (tmp + rename, so a reader
    never sees a half-written pointer)."""
    if not path:
        return
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(f"{host} {port}\n")
    os.replace(tmp, path)


def resolve_leader_file(path: Optional[str]) -> Optional[Tuple[str, int]]:
    if not path:
        return None
    try:
        with open(path) as f:
            host, port = f.read().split()
        return host, int(port)
    except (OSError, ValueError):
        return None


def file_resolver(path: Optional[str]):
    """RetryableConnection ``resolver`` over the leader pointer file; None
    (no file yet / unreadable) keeps the last known address."""

    async def _resolve() -> Optional[Tuple[str, int]]:
        return resolve_leader_file(path)

    return _resolve


# -- warm standby ------------------------------------------------------------


class _ShipMirror:
    """Standby-side state mirror fed by the leader's ShipFrames pushes:
    the cross-process analog of a follower applying its received stream.
    Same read interface as ReplicaTailer (``get``/``get_all``/``term``/
    ``seq``) so read_leadership works on either feed."""

    def __init__(self):
        self.tables: dict = {}
        self.term = 0
        self.seq = 0

    def apply_snapshot(self, snap: bytes, term: int, seq: int) -> None:
        self.tables = {
            t: dict(kv) for t, kv in msgpack.unpackb(snap, raw=False).items()
        }
        self.term = term
        self.seq = seq

    def apply_frames(self, data: bytes) -> None:
        from ray_tpu._private.gcs_store import apply_replicated

        self.tables, term, seq, _ = apply_replicated(self.tables, data)
        self.term = max(self.term, term)
        self.seq = max(self.seq, seq)

    def get(self, table: str, key: str):
        return self.tables.get(table, {}).get(key)

    def get_all(self, table: str) -> dict:
        return dict(self.tables.get(table, {}))


class GcsStandby:
    """Warm-standby GCS: mirrors the replicated log and promotes itself
    when the leader's lease expires unrenewed.

    The standby holds the whole control-plane state as a live mirror —
    fed over ShipFrames/ShipSnapshot RPCs from the leader (``mode="rpc"``,
    works across OS processes) or by tailing a follower log from shared
    storage (``mode="file"``); rpc mode falls back to the file tailer
    while the leader is unreachable. Promotion is therefore bounded by
    recovery *reconciliation* — requeueing in-flight actor/PG placements —
    not by replaying history. ``on_promote(server)`` fires after the new
    server is listening; ``promoted`` is set for waiters.

    Losing a promotion race (another standby claimed or fenced past us)
    re-enters the watch loop at the new term — the standby pool survives
    any number of consecutive failovers.
    """

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        session_name: str = "",
        persist_path: Optional[str] = None,
        on_promote=None,
        mode: Optional[str] = None,
    ):
        from ray_tpu._private.gcs_store import ReplicaTailer, follower_paths

        if not persist_path:
            raise ValueError("a standby requires a replicated persist path")
        self.host = host
        self.port = port
        self.session_name = session_name
        self.persist_path = persist_path
        self.mode = mode or config.gcs_standby_mode
        self.tailer = ReplicaTailer(follower_paths(persist_path)[0])
        self.mirror = _ShipMirror()
        # Stream-health counters (tests + debugging): frames/snapshots
        # received over the RPC feed.
        self.frames_received = 0
        self.snapshots_pulled = 0
        self.server = None  # GcsServer once promoted
        self.promoted = asyncio.Event()
        self._on_promote = on_promote
        self._task: Optional[asyncio.Task] = None
        self._stopped = False
        self._conn = None  # ShipFrames subscription to the leader
        self._need_snapshot = False

    async def start(self) -> "GcsStandby":
        from ray_tpu._private import rpc

        note_role(leader=False)
        self.tailer.poll()
        self._task = rpc.spawn(self._watch_loop())
        return self

    # -- rpc feed ------------------------------------------------------------

    async def _on_ship_frames(self, conn, p: dict) -> None:
        """Client-side push handler: one quorum-acked group commit. A
        watermark gap (we missed a window across a reconnect) flags a
        snapshot re-pull instead of splicing a hole into the mirror."""
        if p["prev_seq"] != self.mirror.seq:
            self._need_snapshot = True
            return
        self.mirror.apply_frames(p["frames"])
        self.frames_received += 1

    async def _ensure_stream(self) -> bool:
        """Dial the current leader (pointer file) and (re)subscribe;
        returns True while the RPC feed is live. Any failure leaves the
        file tailer as the feed for this poll round."""
        from ray_tpu._private import rpc

        if self._conn is not None and not self._conn.closed:
            if self._need_snapshot:
                await self._pull_snapshot(self._conn)
            return True
        self._conn = None
        addr = resolve_leader_file(leader_file_path(self.persist_path))
        if addr is None:
            return False
        try:
            conn = await rpc.connect(
                addr[0],
                addr[1],
                handlers={"ShipFrames": self._on_ship_frames},
                retry=1,
            )
            sub = await conn.call(
                "ShipSubscribe", {}, timeout=config.gcs_leader_lease_s
            )
            if not sub.get("ok"):
                await conn.close()
                return False
            await self._pull_snapshot(conn)
            self._conn = conn
            return True
        except (rpc.RpcError, OSError, asyncio.TimeoutError):
            return False

    async def _pull_snapshot(self, conn) -> None:
        snap = await conn.call(
            "ShipSnapshot", {}, timeout=config.gcs_leader_lease_s
        )
        if snap.get("ok"):
            self.mirror.apply_snapshot(snap["snap"], snap["term"], snap["seq"])
            self.snapshots_pulled += 1
            self._need_snapshot = False

    def _view(self, streaming: bool):
        """The freshest feed for leadership-record reads this round."""
        if streaming and self.mirror.seq >= self.tailer.seq:
            return self.mirror
        return self.tailer

    # -- watch loop ----------------------------------------------------------

    async def _watch_loop(self) -> None:
        from ray_tpu._private import rpc
        from ray_tpu._private.gcs_store import try_claim_term

        grace = config.gcs_leader_lease_s / 3.0
        while not self._stopped:
            await asyncio.sleep(config.gcs_standby_poll_s)
            streaming = False
            if self.mode == "rpc":
                try:
                    streaming = await self._ensure_stream()
                except rpc.ConnectionLost:
                    streaming = False
            if not streaming:
                self.tailer.poll()
            view = self._view(streaming)
            rec = read_leadership(view)
            if rec is None:
                continue  # no leader has ever asserted: nothing to succeed
            if time.time() <= rec["deadline"] + grace:
                continue
            # Election round: claim the next term atomically so racing
            # standbys cannot both open the store at the same term. The
            # loser re-enters the loop and sees either the winner's renewed
            # lease or a later expiry at a higher term.
            term = max(rec["term"], view.term) + 1
            if not try_claim_term(self.persist_path, term):
                continue
            try:
                await self._promote(term)
                return
            except Exception:
                # Lost the race past the claim (fenced by a higher term) or
                # a majority of members is unreachable (QuorumLostError):
                # stay armed and re-enter the loop at the new term.
                logger.exception(
                    "standby promotion at term %d failed; re-arming", term
                )
                continue

    async def _promote(self, term: int) -> None:
        from ray_tpu._private.gcs import GcsServer

        logger.warning(
            "gcs leader lease expired: standby promoting at term %d", term
        )
        t0 = time.perf_counter()
        server = GcsServer(
            self.host,
            self.port,
            session_name=self.session_name,
            persist_path=self.persist_path,
            persist_backend="replicated",
            term=term,
        )
        await server.start()  # writes leadership record + leader file
        self.server = server
        note_failover()
        telemetry.record_event(
            "gcs", "failover", term=term, promote_s=time.perf_counter() - t0
        )
        self.promoted.set()
        if self._on_promote is not None:
            res = self._on_promote(server)
            if asyncio.iscoroutine(res):
                await res

    async def stop(self) -> None:
        """Stop watching; if promoted, the served GcsServer is stopped too."""
        self._stopped = True
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        if self._conn is not None:
            await self._conn.close()
            self._conn = None
        if self.server is not None:
            await self.server.stop()


# -- OS-process standby entrypoint -------------------------------------------
#
# Run a standby as its own process (its own host, in a real deployment):
#
#     python -m ray_tpu._private.gcs_ha --persist-path /path/to/gcs.db
#
# The process arms a GcsStandby (rpc mode by default: it dials the leader
# from the pointer file and mirrors the quorum-acked stream), promotes on
# lease expiry, then keeps serving as the leader until SIGTERM/SIGINT.


def _main(argv=None) -> None:
    import argparse
    import signal

    ap = argparse.ArgumentParser(
        prog="python -m ray_tpu._private.gcs_ha",
        description="Run a warm-standby GCS as its own OS process.",
    )
    ap.add_argument("--persist-path", required=True,
                    help="replicated store path of the group to stand by for")
    ap.add_argument("--session", default="standby")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--mode", choices=("rpc", "file"), default=None,
                    help="stream feed (default: the gcs_standby_mode knob)")
    args = ap.parse_args(argv)

    async def _run() -> None:
        standby = GcsStandby(
            args.host,
            args.port,
            session_name=args.session,
            persist_path=args.persist_path,
            mode=args.mode,
        )
        await standby.start()
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        await standby.stop()

    asyncio.run(_run())


if __name__ == "__main__":
    _main()
