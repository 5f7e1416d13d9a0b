"""Node bring-up: owns the GCS + raylet for a head node.

Analog of the reference's python/ray/_private/node.py (start_ray_processes). Two
modes:
- in-loop (default): GCS and raylet run as asyncio servers on the driver's
  background event loop — same wire protocol as separate processes (workers
  still connect over TCP), minus process-spawn latency. This is also how
  cluster_utils boots extra "nodes" for multi-node tests.
- subprocess: daemons run as their own processes (``python -m
  ray_tpu._private.gcs`` / ``raylet``) for deployment-shaped setups.
"""

from __future__ import annotations

import asyncio
import os
import secrets
import time
from typing import Dict, Optional, Tuple

from ray_tpu._private.common import config
from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.raylet import Raylet
from ray_tpu.util import tracing


class Node:
    def __init__(
        self,
        *,
        head: bool = True,
        gcs_addr: Optional[Tuple[str, int]] = None,
        num_cpus: Optional[float] = None,
        num_tpus: Optional[float] = None,
        resources: Optional[Dict[str, float]] = None,
        object_store_memory: Optional[int] = None,
        session_name: Optional[str] = None,
        labels: Optional[Dict[str, str]] = None,
        worker_env: Optional[Dict[str, str]] = None,
    ):
        self.head = head
        self.session_name = session_name or f"s{int(time.time())}_{secrets.token_hex(4)}"
        self.gcs_server: Optional[GcsServer] = None
        self.gcs_addr = gcs_addr
        self.raylet: Optional[Raylet] = None
        self.raylet_addr: Optional[Tuple[str, int]] = None
        self._resources = dict(resources or {})
        if num_cpus is not None:
            self._resources["CPU"] = float(num_cpus)
        if num_tpus is not None:
            self._resources["TPU"] = float(num_tpus)
        if "CPU" not in self._resources:
            self._resources["CPU"] = float(os.cpu_count() or 1)
        if "TPU" not in self._resources:
            from ray_tpu._private.raylet import detect_tpu_resources

            self._resources.update(detect_tpu_resources())
        self.object_store_memory = object_store_memory
        self.labels = labels
        self.worker_env = worker_env
        self.gcs_standby = None  # GcsStandby when HA mode is on (head only)

    def gcs_persist_path(self) -> str:
        """Session-scoped store file backing GCS fault tolerance (the
        ``gcs_persist_backend`` knob's; gcs_store.make_store)."""
        import tempfile

        return os.path.join(
            tempfile.gettempdir(), f"ray_tpu_{self.session_name}", "gcs.db"
        )

    def ha_enabled(self) -> bool:
        """HA control plane: replicated store + warm standby + leader file
        (docs/fault_tolerance.md "HA deployment")."""
        return bool(
            config.gcs_persistence and config.gcs_persist_backend == "replicated"
        )

    def gcs_leader_file(self) -> Optional[str]:
        if not self.ha_enabled():
            return None
        from ray_tpu._private import gcs_ha

        return gcs_ha.leader_file_path(self.gcs_persist_path())

    async def _arm_standby(self) -> None:
        from ray_tpu._private.gcs_ha import GcsStandby

        self.gcs_standby = GcsStandby(
            session_name=self.session_name,
            persist_path=self.gcs_persist_path(),
        )
        await self.gcs_standby.start()

    async def start(self) -> None:
        if self.head:
            self.gcs_server = GcsServer(
                session_name=self.session_name,
                persist_path=(
                    self.gcs_persist_path() if config.gcs_persistence else None
                ),
            )
            with tracing.span("init.gcs"):
                self.gcs_addr = await self.gcs_server.start()
            if self.ha_enabled():
                await self._arm_standby()
        assert self.gcs_addr is not None
        self.raylet = Raylet(
            self.gcs_addr,
            self.session_name,
            resources=self._resources,
            object_store_memory=self.object_store_memory,
            labels=self.labels,
            worker_env=self.worker_env,
            gcs_leader_file=self.gcs_leader_file(),
        )
        with tracing.span("init.raylet"):
            self.raylet_addr = await self.raylet.start()

    async def stop(self) -> None:
        if self.raylet is not None:
            await self.raylet.stop()
        if self.gcs_standby is not None:
            # The promoted standby's server may be the very server we adopted
            # as gcs_server; detach it so it is stopped exactly once below.
            if self.gcs_standby.server is self.gcs_server:
                self.gcs_standby.server = None
            await self.gcs_standby.stop()
        if self.gcs_server is not None:
            await self.gcs_server.stop()
            if self.head and config.gcs_persistence:
                # Final shutdown: the session is over, drop its durable state
                # (restarts go through kill_gcs/restart_gcs, not stop()).
                # The loop is about to exit; there is nothing left to stall.
                import shutil

                shutil.rmtree(  # aio-lint: disable=blocking-call
                    os.path.dirname(self.gcs_persist_path()), ignore_errors=True
                )

    async def kill_gcs(self) -> None:
        """Fault-injection: stop the GCS process, keeping raylets/workers up."""
        assert self.gcs_server is not None
        await self.gcs_server.stop()

    async def crash_gcs(self, torn_tail: bool = False) -> None:
        """Fault-injection: hard-crash the GCS (kill -9 shaped) — no store
        checkpoint, no final fsync, no graceful teardown of persistence.
        ``torn_tail=True`` additionally appends a half-written record to the
        WAL, simulating power loss mid-write; recovery must truncate it."""
        assert self.gcs_server is not None
        await self.gcs_server.crash()
        if torn_tail and config.gcs_persistence:
            from ray_tpu._private.gcs_store import inject_torn_tail

            inject_torn_tail(self.gcs_persist_path())

    async def kill_gcs_host(self, timeout: float = 30.0) -> Tuple[str, int]:
        """Fault-injection: lose the whole GCS *machine* — the process dies
        hard AND its local log member is gone (disk went with the host).
        The warm standby notices the unrenewed lease, promotes over the
        surviving follower log at term+1, and the leader pointer file
        re-targets every client. Returns the new leader's address."""
        assert self.gcs_server is not None and self.gcs_standby is not None
        from ray_tpu._private.gcs_store import drop_host

        await self.gcs_server.crash()
        drop_host(self.gcs_persist_path())
        return await self.adopt_promoted_gcs(timeout)

    async def adopt_promoted_gcs(self, timeout: float = 30.0) -> Tuple[str, int]:
        """Wait for the armed standby to promote, adopt its server as this
        node's GCS, and re-arm a fresh standby. Used after any leader loss
        the standby must absorb — a killed host, or a leader that demoted
        itself on losing its replication majority."""
        assert self.gcs_standby is not None
        await asyncio.wait_for(self.gcs_standby.promoted.wait(), timeout)
        self.gcs_server = self.gcs_standby.server
        self.gcs_addr = self.gcs_server.server.address
        # Re-arm: a fresh standby guards the new leader so a second failover
        # works the same way.
        await self._arm_standby()
        return self.gcs_addr

    async def restart_gcs(self) -> None:
        """Restart the GCS on the same address from its persisted state.
        Raylets re-register over their reconnecting GCS clients; detached
        actors and KV survive (reference: GCS FT with Redis persistence +
        NotifyGCSRestart, node_manager.proto:373)."""
        assert self.gcs_addr is not None
        self.gcs_server = GcsServer(
            host=self.gcs_addr[0],
            port=self.gcs_addr[1],
            session_name=self.session_name,
            persist_path=(
                self.gcs_persist_path() if config.gcs_persistence else None
            ),
        )
        await self.gcs_server.start()

    @property
    def node_id(self) -> str:
        return self.raylet.node_id if self.raylet else ""
