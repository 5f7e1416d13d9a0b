"""Scalability-envelope tests (reference: release/benchmarks/README.md bars:
10k+ queued tasks per node, 40k actors, 1k PGs cluster-wide — scaled to a
single CI host). All run in tier-1 but the failover at a thousand raylets,
which is `slow`: `python -m pytest -m slow tests/test_scale.py -q`.
"""

import time

import pytest

import ray_tpu


@pytest.fixture
def big_cluster(shutdown_only, monkeypatch):
    monkeypatch.setenv("RAY_TPU_ACTOR_RESOLVE_TIMEOUT_S", "540")
    ray_tpu.init(num_cpus=256, num_tpus=0)
    yield


def test_10k_queued_tasks(big_cluster):
    """10,000 tasks queued at once all complete (reference bar: 1M queued on
    one m4.16xlarge; scaled to CI)."""

    @ray_tpu.remote(num_cpus=8)  # bound worker-process count to ~32
    def tick(i):
        return i

    refs = [tick.remote(i) for i in range(10_000)]
    out = ray_tpu.get(refs, timeout=150)
    assert out == list(range(10_000))


def test_200_actors(big_cluster):
    """200 concurrent actors all answer (reference bar: 40k cluster-wide)."""

    @ray_tpu.remote(num_cpus=0.5)
    class Cell:
        def __init__(self, i):
            self.i = i

        def who(self):
            return self.i

    actors = [Cell.remote(i) for i in range(200)]
    out = ray_tpu.get([a.who.remote() for a in actors], timeout=150)
    assert out == list(range(200))
    for a in actors:
        ray_tpu.kill(a)


def test_50_placement_groups(big_cluster):
    """50 simultaneous placement groups become ready and host work
    (reference bar: 1k+ cluster-wide)."""
    from ray_tpu.util.placement_group import placement_group, remove_placement_group
    from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy

    @ray_tpu.remote(num_cpus=1)
    def inside():
        return 1

    pgs = [placement_group([{"CPU": 1}]) for _ in range(50)]
    for pg in pgs:
        assert pg.wait(timeout=60)
    refs = [
        inside.options(
            scheduling_strategy=PlacementGroupSchedulingStrategy(placement_group=pg)
        ).remote()
        for pg in pgs
    ]
    assert sum(ray_tpu.get(refs, timeout=60)) == 50
    for pg in pgs:
        remove_placement_group(pg)


@pytest.mark.timeout(600)  # 8 s alone, 14 s beside five workers' files
def test_100k_queued_tasks(big_cluster):
    """100,000 tasks queued at once all complete (reference bar: 1M queued
    on one m4.16xlarge — this is the 10% point on a 1-core CI host)."""

    @ray_tpu.remote(num_cpus=8)  # bound worker-process count to ~32
    def tick(i):
        return i

    t0 = time.perf_counter()
    refs = [tick.remote(i) for i in range(100_000)]
    t_submit = time.perf_counter() - t0
    out = ray_tpu.get(refs, timeout=540)
    t_total = time.perf_counter() - t0
    assert out == list(range(100_000))
    print(
        f"\n100k queued tasks: submit {100_000 / t_submit:.0f}/s, "
        f"end-to-end {100_000 / t_total:.0f}/s"
    )


@pytest.mark.timeout(600)  # a thousand processes spawned: 8 to 17 s alone, 23 s loaded
def test_1000_actors(big_cluster):
    """1,000 concurrent actors all answer (reference bar: 40k across a
    64-host cluster). Worker-process spawn is the expected wall on one
    host; the print records where the control plane saturates."""

    @ray_tpu.remote(num_cpus=0.25)
    class Cell:
        def __init__(self, i):
            self.i = i

        def who(self):
            return self.i

    t0 = time.perf_counter()
    actors = [Cell.remote(i) for i in range(1000)]
    out = ray_tpu.get([a.who.remote() for a in actors], timeout=540)
    dt = time.perf_counter() - t0
    assert out == list(range(1000))
    print(f"\n1000 actors alive+answering in {dt:.0f}s ({1000 / dt:.1f}/s)")
    for a in actors:
        ray_tpu.kill(a)


@pytest.mark.timeout(600)  # 200 two-phase commits and 200 workers: 2 s alone, 4 s loaded
def test_200_placement_groups(big_cluster):
    """200 simultaneous placement groups become ready and host work
    (reference bar: 1k+ cluster-wide)."""
    from ray_tpu.util.placement_group import (
        placement_group,
        remove_placement_group,
    )
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy,
    )

    @ray_tpu.remote(num_cpus=1)
    def inside():
        return 1

    t0 = time.perf_counter()
    pgs = [placement_group([{"CPU": 1}]) for _ in range(200)]
    for pg in pgs:
        assert pg.wait(timeout=240)
    t_ready = time.perf_counter() - t0
    refs = [
        inside.options(
            scheduling_strategy=PlacementGroupSchedulingStrategy(
                placement_group=pg
            )
        ).remote()
        for pg in pgs
    ]
    assert sum(ray_tpu.get(refs, timeout=240)) == 200
    print(f"\n200 PGs ready in {t_ready:.1f}s")
    for pg in pgs:
        remove_placement_group(pg)


# -- simulated-cluster scheduler scale (ray_tpu._private.sim_cluster) --------


def _sim_schedule(cluster, client, n_tasks, concurrency=64, latencies=None):
    """Run n_tasks 1-CPU lease/release cycles round-robin over every node
    as the entry point, optionally recording per-lease grant latency."""
    import asyncio

    async def schedule_all():
        sem = asyncio.Semaphore(concurrency)
        entries = [tuple(r.addr) for r in cluster.raylets.values()]
        loop = asyncio.get_running_loop()

        async def one(i):
            async with sem:
                t0 = loop.time()
                grant = await client.lease(
                    {"CPU": 1.0}, entry_addr=entries[i % len(entries)]
                )
                if latencies is not None:
                    latencies.append(loop.time() - t0)
                await client.release(grant)

        await asyncio.gather(*(one(i) for i in range(n_tasks)))

    cluster.run(schedule_all(), timeout=120)


def test_sim_500_nodes_10k_tasks():
    """The headline bar: 500 in-process raylets stand up and 10,000 lease
    cycles schedule through the real spillback protocol."""
    from ray_tpu._private.sim_cluster import SimCluster, SimLeaseClient

    cluster = SimCluster(500).start()
    try:
        assert len(cluster.raylets) == 500
        client = SimLeaseClient(cluster)
        t0 = time.perf_counter()
        _sim_schedule(cluster, client, 10_000)
        dt = time.perf_counter() - t0
        print(
            f"\n10k tasks over 500 sim nodes in {dt:.1f}s "
            f"({10_000 / dt:.0f} leases/s)"
        )
        cluster.run(client.close(), timeout=30)
    finally:
        cluster.shutdown()


def _median_lease_latency_s(num_nodes, samples=1500):
    import statistics

    from ray_tpu._private.sim_cluster import SimCluster, SimLeaseClient

    cluster = SimCluster(num_nodes).start()
    try:
        client = SimLeaseClient(cluster)
        _sim_schedule(cluster, client, min(samples, 500))  # warmup
        lat = []
        _sim_schedule(cluster, client, samples, concurrency=16, latencies=lat)
        cluster.run(client.close(), timeout=30)
        return statistics.median(lat)
    finally:
        cluster.shutdown()


def test_sim_lease_latency_o_k_not_o_n():
    """The per-lease scheduling decision is O(k), not O(cluster): median
    grant latency at 500 nodes stays within 2x of 50 nodes. (The old
    GetAllNodes-per-lease path was O(N) and blew this bound by an order of
    magnitude.) A 250us absolute floor keeps sub-millisecond timing noise
    from flaking the ratio on a fast host."""
    m50 = _median_lease_latency_s(50)
    m500 = _median_lease_latency_s(500)
    print(f"\nmedian lease latency: 50 nodes {m50 * 1e3:.2f}ms, "
          f"500 nodes {m500 * 1e3:.2f}ms ({m500 / m50:.2f}x)")
    assert m500 <= max(2.0 * m50, m50 + 250e-6), (
        f"lease latency grew {m500 / m50:.1f}x from 50 to 500 nodes "
        f"({m50 * 1e3:.2f}ms -> {m500 * 1e3:.2f}ms): scheduling is "
        "scanning the cluster again"
    )


def test_sim_autoscaler_scales_to_500_nodes():
    """The autoscaler control loop drives the sim provider past 500 nodes
    on sustained synthetic demand, then runs a clean steady-state round on
    real harness stats."""
    from ray_tpu._private.sim_cluster import SimCluster, SimNodeProvider
    from ray_tpu.autoscaler.autoscaler import Autoscaler, AutoscalerConfig

    target = 500
    cluster = SimCluster(16).start()
    try:
        provider = SimNodeProvider(
            cluster,
            node_types={
                "sim.cpu4": {"resources": {"CPU": 4}, "max_workers": 2000}
            },
        )

        def state():
            stats = cluster.node_stats()
            if len(cluster.raylets) < target:
                # Sustained unmet demand until the fleet reaches target.
                stats[0]["pending_leases"] = 256
                stats[0]["pending_demand"] = [{"CPU": 10000}] * 64
            return stats

        asc = Autoscaler(
            provider,
            AutoscalerConfig(
                upscale_delay_s=0.0,
                idle_timeout_s=3600.0,
                max_launches_per_round=64,
            ),
            state_fn=state,
        )
        t0 = time.perf_counter()
        rounds = 0
        while len(cluster.raylets) < target and rounds < 40:
            asc.update()
            rounds += 1
        dt = time.perf_counter() - t0
        assert len(cluster.raylets) >= target, (
            f"autoscaler stalled at {len(cluster.raylets)} nodes "
            f"after {rounds} rounds"
        )
        print(
            f"\nautoscaled 16 -> {len(cluster.raylets)} sim nodes in "
            f"{rounds} rounds / {dt:.1f}s"
        )
        # Steady state: a round on real stats must neither launch nor kill.
        out = asc.update()
        assert out["launched"] == 0 and out["terminated"] == 0
    finally:
        cluster.shutdown()


# The GCS leader's lease. Beside a `-n 5` run of the compile-heavy files on 8
# cores (load 9 to 13) a hundred raylets' new leader held 0.1, 0.2, 0.3, 0.5,
# 1.0 and 2.0 s five times of five each and missed 0.02 s: ten times the room.
# A thousand's wave failed or never converged in whole runs (ROADMAP D11): `slow`.
LEASE_S = 1.0


@pytest.mark.parametrize("n", [100, pytest.param(
    1000, marks=[pytest.mark.slow, pytest.mark.timeout(1800)])])
def test_sim_failover_reconnect_storm(n):
    """HA failover at the scale bar: n in-process raylets lose the GCS
    *machine* (process + its replicated-log member), the warm standby
    promotes from the follower log, and the full n-raylet reconnect
    wave re-targets the new leader through the leader file — converging to
    a complete ALIVE node view without melting the control plane."""
    import asyncio
    import os
    import shutil
    import tempfile

    from ray_tpu._private import gcs_ha, rpc
    from ray_tpu._private.sim_cluster import SimCluster, SimLeaseClient

    probe_s = 90 if n == 100 else 600  # the hundred's fit the suite's 180 s
    tmp = tempfile.mkdtemp(prefix="ha_scale_")
    cluster = SimCluster(
        n,
        persist_path=os.path.join(tmp, "gcs.wal"),
        ha=True,
        env={
            "RAY_TPU_GCS_LEADER_LEASE_S": str(LEASE_S),
            "RAY_TPU_GCS_STANDBY_POLL_S": "0.05",
        },
    ).start()
    try:
        assert len(cluster.raylets) == n
        client = SimLeaseClient(cluster)
        _sim_schedule(cluster, client, 500)  # warm: every node registered
        t0 = time.perf_counter()
        assert cluster.run(cluster.kill_gcs_host_async(), timeout=probe_s / 5)
        t_promote = time.perf_counter() - t0

        async def converged() -> float:
            # Probe through the leader file like the raylets do: under the
            # reconnect wave a promoted leader can miss its own lease and a
            # second standby takes over, fencing term N and closing its
            # connections — re-resolve and re-dial instead of dying on the
            # demoted address. GetAllNodes is a read; re-issuing is safe.
            leader_file = cluster.gcs_leader_file()

            async def dial() -> "rpc.Connection":
                addr = gcs_ha.resolve_leader_file(leader_file)
                return await rpc.connect(*(addr or cluster.gcs_addr))

            conn = None
            try:
                deadline = asyncio.get_running_loop().time() + probe_s
                while True:
                    try:
                        if conn is None:
                            conn = await dial()
                        reply = await conn.call(
                            "GetAllNodes", timeout=probe_s / 10
                        )
                    except (rpc.RpcError, OSError):
                        if asyncio.get_running_loop().time() > deadline:
                            raise
                        if conn is not None:
                            await conn.close()
                            conn = None
                        await asyncio.sleep(0.25)
                        continue
                    alive = sum(
                        1 for node in reply["nodes"]
                        if node["state"] == "ALIVE"
                    )
                    if alive >= n:
                        return time.perf_counter() - t0
                    if asyncio.get_running_loop().time() > deadline:
                        raise AssertionError(
                            f"only {alive}/{n} nodes re-registered"
                        )
                    await asyncio.sleep(0.25)
            finally:
                if conn is not None:
                    await conn.close()

        t_converge = cluster.run(converged(), timeout=probe_s * 7 / 6)
        # The promoted leader still schedules: a fresh lease burst works.
        _sim_schedule(cluster, client, 500)
        cluster.run(client.close(), timeout=30)
        # The standby re-armed behind the new leader promotes only if that
        # leader missed its own lease under the wave.
        held = not cluster.gcs_standby.promoted.is_set()
        print(
            f"\n{n}-node failover: promoted in {t_promote:.2f}s, full "
            f"reconnect storm converged in {t_converge:.1f}s, the new leader "
            f"{'held' if held else 'missed'} its lease of {LEASE_S} s"
        )
    finally:
        cluster.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)


@pytest.mark.timeout(600)  # 2 GB through eight stores: 13 to 17 s alone or loaded
def test_256mb_broadcast_to_8_nodes(shutdown_only):
    """One 256 MB object broadcast to tasks pinned on 8 raylets — the
    PushManager fan-out pattern (reference bar: 1 GiB to 50+ nodes)."""
    import numpy as np

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy

    cluster = Cluster()
    head = cluster.add_node(num_cpus=2, object_store_memory=600 * 1024 * 1024)
    ray_tpu.init(address=cluster.address)
    nodes = [head] + [
        cluster.add_node(
            num_cpus=2, object_store_memory=600 * 1024 * 1024
        )
        for _ in range(7)
    ]

    @ray_tpu.remote(num_cpus=1)
    def digest(arr):
        return int(arr[0]), int(arr[-1]), arr.nbytes

    payload = np.arange(256 * 1024 * 1024 // 8, dtype=np.float64)
    ref = ray_tpu.put(payload)
    t0 = time.perf_counter()
    refs = [
        digest.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=n.node_id, soft=False
            )
        ).remote(ref)
        for n in nodes
    ]
    out = ray_tpu.get(refs, timeout=540)
    dt = time.perf_counter() - t0
    assert all(o == (0, len(payload) - 1, payload.nbytes) for o in out)
    total_gb = 256 / 1024 * len(nodes)
    print(
        f"\n256MB broadcast to {len(nodes)} nodes in {dt:.1f}s "
        f"({total_gb / dt:.2f} GB/s aggregate)"
    )
    cluster.shutdown()
