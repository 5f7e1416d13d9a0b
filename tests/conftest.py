"""Test fixtures (analog of python/ray/tests/conftest.py).

JAX-facing tests run on a virtual 8-device CPU mesh so multi-chip sharding is
exercised without TPU hardware: JAX_PLATFORMS=cpu from the tier-1 command,
XLA_FLAGS before any jax import here, and ray_tpu.testing's worker_env for
spawned workers.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
# Persistent XLA compilation cache: jax-heavy tests (models/parallel/train)
# recompile identical programs every run; caching them is worth minutes.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/ray_tpu_jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import signal
import threading

import pytest

# The longest watchdog a test outside `slow` may carry: the whole run has
# 1,470 s, and a stalled test holds its worker and the files queued on it.
LONGEST_TIER1_WATCHDOG_S = 600


def pytest_addoption(parser):
    parser.addini(
        "timeout",
        "per-test timeout in seconds, enforced by the built-in SIGALRM "
        "watchdog below (pytest-timeout is not available in this image)",
        default="180",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "timeout(seconds): override the per-test watchdog timeout"
    )


@pytest.hookimpl(wrapper=True)
def pytest_pycollect_makeitem(collector):
    """A longer watchdog outside `slow` fails its file's collection."""
    made = yield
    for item in made if isinstance(made, list) else [made]:
        marker = isinstance(item, pytest.Item) and (
            not item.get_closest_marker("slow")
            and item.get_closest_marker("timeout"))
        if marker and marker.args and (
                float(marker.args[0]) > LONGEST_TIER1_WATCHDOG_S):
            raise collector.CollectError(
                f"{item.nodeid}: timeout({marker.args[0]:g}) is above "
                f"{LONGEST_TIER1_WATCHDOG_S} s; mark the test `slow` or "
                "shorten it")
    return made


# The files that compile the cells' whole steps for a described v5e: 400
# test-seconds each in a whole six-worker run, and by their names collected
# when 1,130 s of it are gone, so that two workers walked them to 1,483 s
# while four stood idle from 1,261 (PR 74's run; ROADMAP D11). `--dist
# loadfile` hands files out in the order they are collected: these go first.
FIRST_FILES = ("test_step_compile.py", "test_step_compile_walked.py")


def pytest_collection_modifyitems(items):
    """`FIRST_FILES`' cases ahead of the rest, every file's own order and
    the others' kept (a stable sort; every worker collects the same)."""
    items.sort(key=lambda item: item.path.name not in FIRST_FILES)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """Watchdog so one wedged test cannot hang the whole suite."""
    timeout = float(item.config.getini("timeout"))
    marker = item.get_closest_marker("timeout")
    if marker and marker.args:
        timeout = float(marker.args[0])
    if timeout <= 0 or threading.current_thread() is not threading.main_thread():
        yield
        return

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded watchdog timeout of {timeout:.0f}s"
        )

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, timeout)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def ray_start_regular():
    """Fresh single-node cluster per test (reference: conftest.py:419)."""
    import ray_tpu

    info = ray_tpu.init(num_cpus=4, num_tpus=0)
    yield info
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cpu_mesh_workers():
    """Cluster whose workers see 8 virtual CPU 'TPU' devices — used by
    train/collective tests to emulate an 8-chip host."""
    import ray_tpu
    from ray_tpu.testing import cpu_mesh_worker_env

    info = ray_tpu.init(
        num_cpus=8, num_tpus=8, worker_env=cpu_mesh_worker_env(8)
    )
    yield info
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    """Test calls init itself; fixture guarantees teardown (conftest.py:336)."""
    import ray_tpu

    yield None
    ray_tpu.shutdown()
