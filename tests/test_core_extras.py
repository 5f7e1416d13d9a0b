"""Core API extras: cancel, dynamic generators, ActorPool, Queue (analog of
python/ray/tests/test_cancel.py, test_generators.py, test_actor_pool.py,
test_queue.py)."""

import time

import numpy as np
import pytest


def test_cancel_running_task(ray_start_regular):
    import ray_tpu

    @ray_tpu.remote
    def spin(seconds):
        # Pure-Python loop: interruptible by PyThreadState_SetAsyncExc.
        deadline = time.monotonic() + seconds
        x = 0
        while time.monotonic() < deadline:
            x += 1
        return x

    ref = spin.remote(60)
    time.sleep(2)  # let it start
    ray_tpu.cancel(ref)
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(ref, timeout=30)


def test_cancel_queued_task(ray_start_regular):
    import ray_tpu

    @ray_tpu.remote(num_cpus=4)
    def hog():
        time.sleep(8)
        return "hog"

    @ray_tpu.remote(num_cpus=4)
    def queued():
        return "ran"

    h = hog.remote()
    time.sleep(0.5)
    q = queued.remote()  # cannot start: hog holds all CPUs
    ray_tpu.cancel(q)
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(q, timeout=30)
    assert ray_tpu.get(h, timeout=30) == "hog"


def test_dynamic_generators(ray_start_regular):
    import ray_tpu

    @ray_tpu.remote(num_returns="dynamic")
    def gen(n):
        for i in range(n):
            yield i * i

    ref = gen.remote(5)
    dyn = ray_tpu.get(ref)
    assert isinstance(dyn, ray_tpu.ObjectRefGenerator)
    assert len(dyn) == 5
    assert [ray_tpu.get(r) for r in dyn] == [0, 1, 4, 9, 16]


def test_dynamic_generator_large_items(ray_start_regular):
    import ray_tpu

    @ray_tpu.remote(num_returns="dynamic")
    def gen():
        for i in range(3):
            yield np.full((256, 256), i)  # 0.5MB each -> plasma path

    refs = list(ray_tpu.get(gen.remote()))
    for i, r in enumerate(refs):
        np.testing.assert_array_equal(ray_tpu.get(r), np.full((256, 256), i))


def test_actor_pool(ray_start_regular):
    import ray_tpu
    from ray_tpu.util import ActorPool

    @ray_tpu.remote
    class Worker:
        def double(self, x):
            return x * 2

    pool = ActorPool([Worker.remote() for _ in range(2)])
    results = list(pool.map(lambda a, v: a.double.remote(v), range(8)))
    assert results == [0, 2, 4, 6, 8, 10, 12, 14]
    unordered = sorted(
        pool.map_unordered(lambda a, v: a.double.remote(v), range(8))
    )
    assert unordered == [0, 2, 4, 6, 8, 10, 12, 14]


def test_queue(ray_start_regular):
    import ray_tpu
    from ray_tpu.util.queue import Empty, Full, Queue

    q = Queue(maxsize=2)
    q.put("a")
    q.put("b")
    with pytest.raises(Full):
        q.put("c", block=False)
    assert q.qsize() == 2 and q.full()
    assert q.get() == "a"
    assert q.get() == "b"
    with pytest.raises(Empty):
        q.get(block=False)

    # Cross-process: a task puts, driver gets.
    @ray_tpu.remote
    def producer(queue):
        for i in range(3):
            queue.put(i)
        return True

    # Drain while the producer runs: the third put blocks until the driver
    # frees a slot, so waiting on the task before draining would deadlock.
    ref = producer.remote(q)
    assert [q.get(timeout=10) for _ in range(3)] == [0, 1, 2]
    assert ray_tpu.get(ref)
    q.shutdown()


def test_streaming_generator_overlaps_producer(ray_start_regular):
    """Consumer receives early items while the producer is still yielding
    (reference: ReportGeneratorItemReturns streaming)."""
    import time as _time

    import ray_tpu

    @ray_tpu.remote
    def warm():
        return 1

    @ray_tpu.remote(num_returns="dynamic")
    def slow_gen():
        for i in range(4):
            yield i
            _time.sleep(0.8)

    ray_tpu.get(warm.remote())  # spawn the worker outside the timed window

    t0 = _time.monotonic()
    gen = ray_tpu.get(slow_gen.remote(), timeout=30)
    it = iter(gen)
    first = ray_tpu.get(next(it))
    first_latency = _time.monotonic() - t0
    assert first == 0
    # The full run takes >= 3*0.8s; getting item 0 must not wait for it.
    assert first_latency < 2.0, f"first item took {first_latency:.1f}s (not streamed)"
    rest = [ray_tpu.get(r) for r in it]
    assert rest == [1, 2, 3]


def test_streaming_generator_borrowed(ray_start_regular):
    """A generator handle passed to another process iterates via the owner
    (DynNext long-poll)."""
    import ray_tpu

    @ray_tpu.remote(num_returns="dynamic")
    def gen():
        for i in range(3):
            yield i * 10

    @ray_tpu.remote
    def consume(g):
        return [ray_tpu.get(r) for r in g]

    g = ray_tpu.get(gen.remote(), timeout=30)
    assert ray_tpu.get(consume.remote(g), timeout=60) == [0, 10, 20]


def test_streaming_generator_failure_propagates(ray_start_regular):
    """A generator that raises mid-stream terminates iteration with the
    task's error instead of hanging consumers."""
    import ray_tpu

    @ray_tpu.remote(num_returns="dynamic", max_retries=0)
    def bad_gen():
        yield 1
        raise ValueError("boom-mid-stream")

    gen = ray_tpu.get(bad_gen.remote(), timeout=30)
    it = iter(gen)
    assert ray_tpu.get(next(it), timeout=30) == 1
    with pytest.raises(Exception) as ei:
        ray_tpu.get(next(it), timeout=30)
    assert "boom-mid-stream" in str(ei.value)


def test_pull_manager_priority_and_quota():
    """Prioritized bandwidth-capped pull admission (reference:
    object_manager/pull_manager.h): quota bounds bytes in flight, a
    head-of-line oversized pull is never deadlocked, and gets outrank
    task-arg prefetches regardless of arrival order."""
    import asyncio

    from ray_tpu._private.pull_manager import PullManager

    async def scenario():
        pm = PullManager(100)
        order = []

        await pm.acquire(60, "get")       # admitted: 60 in flight
        await pm.acquire(30, "task_arg")  # admitted: 90 in flight

        async def queued(size, purpose, tag):
            await pm.acquire(size, purpose)
            order.append(tag)

        # Over quota now: these queue. task_arg arrives FIRST but the get
        # and wait must be admitted before it.
        t1 = asyncio.ensure_future(queued(50, "task_arg", "arg"))
        await asyncio.sleep(0.01)
        t2 = asyncio.ensure_future(queued(50, "get", "get"))
        t3 = asyncio.ensure_future(queued(50, "wait", "wait"))
        await asyncio.sleep(0.01)
        assert order == []
        assert pm.stats()["queued_pulls"] == 3

        pm.release(60)  # 30 in flight; head (get, 50) fits -> 80
        await asyncio.sleep(0.01)
        assert order == ["get"]
        pm.release(30)  # 50 in flight; wait (50) fits -> 100; arg must wait
        await asyncio.sleep(0.01)
        assert order == ["get", "wait"]
        pm.release(50)
        pm.release(50)
        await asyncio.sleep(0.01)
        assert order == ["get", "wait", "arg"]
        await asyncio.gather(t1, t2, t3)
        pm.release(50)  # the admitted task_arg pull finishes too

        # Oversized head-of-line pull: admitted alone rather than deadlocked.
        await pm.acquire(1000, "get")
        assert pm.stats()["bytes_in_flight"] == 1000
        pm.release(1000)
        assert pm.stats() == {
            "bytes_in_flight": 0, "active_pulls": 0, "queued_pulls": 0,
            "stalled_streams": 0, "rerequested_streams": 0,
            "restore_fallbacks": 0,
        }

    asyncio.run(scenario())


def test_fast_id_state_reseeds_after_fork():
    """Forked workers must not inherit the zygote's fast-id stream: shared
    prefix + counter makes two workers draw identical task ids, whose
    deterministic return-object ids then alias in the object store (the
    second task's output silently becomes the first task's bytes)."""
    import os

    from ray_tpu._private import ids

    r, w = os.pipe()
    pid = os.fork()
    if pid == 0:  # child
        try:
            os.write(w, ids.fast_unique_hex().encode())
        finally:
            os._exit(0)
    os.close(w)
    _, status = os.waitpid(pid, 0)
    assert status == 0
    child = os.read(r, 64).decode()
    os.close(r)
    parent = ids.fast_unique_hex()
    assert len(child) == 32 and len(parent) == 32
    # The 20-hex-char random prefix must differ post-fork (1 in 16^20
    # chance of a false pass by collision).
    assert child[:20] != parent[:20]
