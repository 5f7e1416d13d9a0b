"""Core API end-to-end tests: tasks, objects, errors.

Models the reference's python/ray/tests/test_basic.py coverage.
"""

import time

import numpy as np
import pytest

import ray_tpu


def test_put_get(ray_start_regular):
    ref = ray_tpu.put(42)
    assert ray_tpu.get(ref) == 42


def test_put_get_large_numpy(ray_start_regular):
    arr = np.arange(1_000_000, dtype=np.float32)  # 4 MB -> shm path
    ref = ray_tpu.put(arr)
    out = ray_tpu.get(ref)
    np.testing.assert_array_equal(arr, out)


def test_simple_task(ray_start_regular):
    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(1)) == 2


def test_task_with_ref_arg(ray_start_regular):
    @ray_tpu.remote
    def f(x):
        return x * 2

    ref = ray_tpu.put(21)
    assert ray_tpu.get(f.remote(ref)) == 42


def test_chained_tasks(ray_start_regular):
    @ray_tpu.remote
    def inc(x):
        return x + 1

    ref = inc.remote(0)
    for _ in range(4):
        ref = inc.remote(ref)
    assert ray_tpu.get(ref) == 5


def test_many_parallel_tasks(ray_start_regular):
    @ray_tpu.remote
    def f(i):
        return i * i

    refs = [f.remote(i) for i in range(20)]
    assert ray_tpu.get(refs) == [i * i for i in range(20)]


def test_multiple_returns(ray_start_regular):
    @ray_tpu.remote(num_returns=3)
    def f():
        return 1, 2, 3

    a, b, c = f.remote()
    assert ray_tpu.get([a, b, c]) == [1, 2, 3]


def test_task_error_propagates(ray_start_regular):
    @ray_tpu.remote(max_retries=0)
    def boom():
        raise ValueError("broken")

    with pytest.raises(ValueError, match="broken"):
        ray_tpu.get(boom.remote())


def test_large_task_result(ray_start_regular):
    @ray_tpu.remote
    def make():
        return np.ones((512, 1024), dtype=np.float32)  # 2 MB

    out = ray_tpu.get(make.remote())
    assert out.shape == (512, 1024)
    assert out.dtype == np.float32


def test_large_task_arg(ray_start_regular):
    @ray_tpu.remote
    def total(x):
        return float(x.sum())

    arr = np.ones(500_000, dtype=np.float64)
    assert ray_tpu.get(total.remote(arr)) == 500_000.0


def test_wait(ray_start_regular):
    @ray_tpu.remote
    def fast():
        return "fast"

    @ray_tpu.remote
    def slow():
        time.sleep(5)
        return "slow"

    # Warm the worker pool so cold-start latency can't eat the wait window.
    assert ray_tpu.get(fast.remote()) == "fast"

    f, s = fast.remote(), slow.remote()
    ready, not_ready = ray_tpu.wait([f, s], num_returns=1, timeout=4)
    assert ready == [f]
    assert not_ready == [s]


def test_get_timeout(ray_start_regular):
    @ray_tpu.remote
    def slow():
        time.sleep(10)

    with pytest.raises(ray_tpu.GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=1)


def test_nested_tasks(ray_start_regular):
    @ray_tpu.remote
    def inner(x):
        return x + 1

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(inner.remote(x)) + 10

    assert ray_tpu.get(outer.remote(0)) == 11


def test_kwargs_and_defaults(ray_start_regular):
    @ray_tpu.remote
    def f(a, b=10, *, c=100):
        return a + b + c

    assert ray_tpu.get(f.remote(1)) == 111
    assert ray_tpu.get(f.remote(1, b=2, c=3)) == 6


def test_nested_object_ref_passthrough(ray_start_regular):
    @ray_tpu.remote
    def identity(d):
        # Nested refs arrive as refs, not values (reference semantics).
        assert isinstance(d["ref"], ray_tpu.ObjectRef)
        return ray_tpu.get(d["ref"])

    inner_ref = ray_tpu.put(7)
    assert ray_tpu.get(identity.remote({"ref": inner_ref})) == 7


def test_cluster_resources(ray_start_regular):
    res = ray_tpu.cluster_resources()
    assert res.get("CPU") == 4.0


def test_every_config_knob_has_a_reader():
    """A knob that no code under ray_tpu/ reads promises what nothing does
    (tests that set it then believe in a cap nobody enforces)."""
    import ast
    import pathlib

    from ray_tpu._private import common

    read = set()
    for path in pathlib.Path(ray_tpu.__file__).parent.rglob("*.py"):
        nodes = list(ast.walk(ast.parse(path.read_text())))
        # The names this module knows the table's instance by.
        names = {"config"} if path.samefile(common.__file__) else set()
        for node in nodes:
            if (isinstance(node, ast.ImportFrom) and node.module
                    and node.module.endswith("_private.common")):
                names |= {a.asname or a.name for a in node.names
                          if a.name == "config"}
        read |= {node.attr for node in nodes
                 if isinstance(node, ast.Attribute)
                 and isinstance(node.value, ast.Name)
                 and node.value.id in names}
    assert sorted(set(common._CONFIG_DEFAULTS) - read) == []
