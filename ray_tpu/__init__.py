"""ray_tpu: a TPU-native distributed compute framework.

Tasks, actors, and shared-memory objects with ownership-based reference
counting (the reference architecture of dream3d-ai/ray, rebuilt TPU-first),
plus ML libraries — train/tune/data/serve/rl — built on JAX/XLA/Pallas where
collectives lower to `jax.lax` ops over ICI inside compiled SPMD programs.

Public core API (analog of python/ray/_private/worker.py exports):

    import ray_tpu

    ray_tpu.init()

    @ray_tpu.remote
    def f(x):
        return x * 2

    ray_tpu.get(f.remote(2))  # 4
"""

import time as _time

_import_began = _time.perf_counter()

from ray_tpu._private.common import (
    ActorDiedError,
    ActorUnavailableError,
    GetTimeoutError,
    ObjectLostError,
    ObjectReconstructionFailedError,
    PlacementGroupError,
    RayTpuError,
    TaskCancelledError,
    TaskError,
    WorkerCrashedError,
)
from ray_tpu._private.core_worker import ObjectRef, ObjectRefGenerator
from ray_tpu._private.worker import (
    available_resources,
    cancel,
    cluster_resources,
    get,
    get_actor,
    init,
    is_initialized,
    kill,
    nodes,
    put,
    shutdown,
    wait,
)
from ray_tpu.actor import ActorClass, ActorHandle
from ray_tpu.remote_function import RemoteFunction
from ray_tpu.util import tracing as _tracing

# `import.ray_tpu`: this package's own import, the program's part of
# `process.before_init` (docs/observability.md, "The train path")
_tracing.observe("import.ray_tpu", _time.perf_counter() - _import_began)

__version__ = "0.1.0"


def remote(*args, **kwargs):
    """Decorator turning a function into a RemoteFunction or a class into an
    ActorClass. Usable bare (`@remote`) or with options
    (`@remote(num_cpus=2, num_tpus=1)`)."""

    def decorate(obj):
        if isinstance(obj, type):
            return ActorClass(obj, **kwargs)
        return RemoteFunction(obj, **kwargs)

    if len(args) == 1 and not kwargs and (callable(args[0]) or isinstance(args[0], type)):
        return decorate(args[0])
    if args:
        raise TypeError("remote() takes keyword options only, e.g. @remote(num_cpus=2)")
    return decorate


def method(**kwargs):
    """Decorator for actor methods to set defaults (e.g. num_returns)."""

    def deco(fn):
        fn._method_options = kwargs
        return fn

    return deco


__all__ = [
    "init",
    "shutdown",
    "remote",
    "method",
    "get",
    "put",
    "wait",
    "cancel",
    "kill",
    "ObjectRefGenerator",
    "get_actor",
    "nodes",
    "cluster_resources",
    "available_resources",
    "is_initialized",
    "ObjectRef",
    "ActorClass",
    "ActorHandle",
    "RemoteFunction",
    "RayTpuError",
    "TaskError",
    "ActorDiedError",
    "ActorUnavailableError",
    "WorkerCrashedError",
    "ObjectLostError",
    "ObjectReconstructionFailedError",
    "GetTimeoutError",
    "TaskCancelledError",
    "PlacementGroupError",
]
