"""BackendExecutor: gang bring-up + training drive loop (reference:
python/ray/train/_internal/backend_executor.py:66 — _create_placement_group
:206, start_training :436, get_next_results :559).

TPU failure model: any worker death invalidates the whole gang (a pod slice is
all-or-nothing), so recovery tears down and re-creates the entire WorkerGroup
and resumes from the latest checkpoint — per SURVEY.md §7, not the reference's
per-worker restart.
"""

from __future__ import annotations

import logging
import threading
import uuid
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

import ray_tpu
from ray_tpu.air.config import ScalingConfig
from ray_tpu.train import _session
from ray_tpu.train._session import TrialInfo
from ray_tpu.train._worker_group import WorkerGroup
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)


class Backend:
    """Framework-specific gang hooks (reference: train/backend.py Backend)."""

    def on_start(self, worker_group: WorkerGroup, backend_config) -> None:
        pass

    def on_training_start(self, worker_group: WorkerGroup, backend_config) -> None:
        pass

    def on_shutdown(self, worker_group: WorkerGroup, backend_config) -> None:
        pass


@dataclass
class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


@dataclass
class JaxConfig(BackendConfig):
    """JAX gang bootstrap (the analog of _TorchBackend's process-group setup,
    reference train/torch/config.py:65-147 — but collectives lower to XLA ops
    over ICI instead of NCCL).

    collective_backend:
      "xla"   — jax.distributed.initialize via GCS-KV rendezvous; one global
                Mesh spans all hosts (real TPU pods).
      "store" — named-actor store collectives (CPU fallback / CI).
      None    — no cross-worker collective group (single worker, or the user
                brings their own).
    """

    collective_backend: Optional[str] = "store"

    @property
    def backend_cls(self):
        return _JaxBackend


_compiles_watched = False
_JAX_SPANS = {  # `jax.monitoring`'s event: the span
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_CACHE_VERDICTS = {  # the event: (the span's `cache`, the counter)
    "/jax/compilation_cache/cache_hits": ("hit", "compile.cache_hits"),
    "/jax/compilation_cache/cache_misses": ("miss", "compile.cache_misses"),
}


def _watch_compiles() -> None:
    """Worker side: what JAX does to a program before it runs, as spans.
    `jax.trace` a function traced to a jaxpr, `jax.lower` a jaxpr lowered
    to a module, `jax.compile` a program compiled or loaded from the
    persistent cache, each with the attribute `function`; `jax.compile`
    with `cache` and the counters `compile.programs`, `compile.cache_hits`
    and `compile.cache_misses` (`jax.monitoring` tells of the cache's
    verdict just before the compilation ends).

    The table's seconds are *self* time. JAX reports an inner `jit` traced
    while an outer one is (and a small program run eagerly in a trace:
    traced, lowered and compiled inside it), the inner one first, so a span
    counts less what ended inside it on its thread, and a thread's seconds
    under the three names add up to the wall time it spent on them.
    `jax.monitoring` tells of a start by a scalar and of an end by a time
    span; both carry the start on `time.time()`'s clock, so a span lies in
    the ring where it happened. The first trace to start after the train
    function was called ends `train.before_first_program`
    (`RuntimeAccount.program_began`)."""
    global _compiles_watched
    if _compiles_watched:
        return
    _compiles_watched = True
    import jax.monitoring

    cache = [None]
    local = threading.local()  # .open: seconds inside each span open here

    def on_event(event: str, **_) -> None:
        if event in _CACHE_VERDICTS:
            cache[0], counter = _CACHE_VERDICTS[event]
            tracing.count(counter)

    def on_start(event: str, start: float, **_) -> None:
        name = _JAX_SPANS.get(event)
        if name is None:
            return
        try:
            local.open.append(0.0)
        except AttributeError:
            local.open = [0.0]
        session = _session._session if name == "jax.trace" else None
        if session is not None:
            session.runtime.program_began(start)

    def on_span(event: str, start: float, end: float, fun_name=None,
                **_) -> None:
        name = _JAX_SPANS.get(event)
        if name is None:
            return
        still_open = getattr(local, "open", None)
        inside = still_open.pop() if still_open else 0.0
        if still_open:
            still_open[-1] += end - start
        attrs = {"function": fun_name}
        if name == "jax.compile":
            tracing.count("compile.programs")
            attrs["cache"], cache[0] = cache[0] or "none", None
        tracing.observe(name, end - start, end=end, inside=inside, **attrs)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_scalar_listener(on_start)
    jax.monitoring.register_event_time_span_listener(on_span)


class _JaxBackend(Backend):
    def on_start(self, worker_group: WorkerGroup, backend_config: JaxConfig):
        worker_group.execute("apply", cloudpickle.dumps(_watch_compiles))
        be = backend_config.collective_backend
        if be is None or len(worker_group) <= 1:
            return
        group_name = f"train_{uuid.uuid4().hex[:8]}"
        self.group_name = group_name
        worker_group._collective_group = group_name
        refs = [
            w.init_collective.remote(len(worker_group), rank, be, group_name)
            for rank, w in enumerate(worker_group.workers)
        ]
        ray_tpu.get(refs)

    def on_shutdown(self, worker_group: WorkerGroup, backend_config: JaxConfig):
        name = getattr(worker_group, "_collective_group", None)
        if name:
            try:
                worker_group.execute("shutdown_collective", name)
            except Exception:
                pass


class TrainingFailedError(RuntimeError):
    pass


class BackendExecutor:
    def __init__(
        self,
        backend_config: BackendConfig,
        scaling_config: ScalingConfig,
        trial_info: TrialInfo,
        worker_env: Optional[Dict[str, str]] = None,
    ):
        self._backend_config = backend_config
        self._backend: Backend = backend_config.backend_cls()
        self._scaling = scaling_config
        self._trial_info = trial_info
        self._worker_env = worker_env
        self.worker_group: Optional[WorkerGroup] = None

    def start(self) -> None:
        tpus = self._scaling.total_resources.get("TPU")
        if tpus and not ray_tpu.cluster_resources().get("TPU"):
            # Not a TrainingFailedError: re-ganging cannot grow a chip, and
            # the placement group would only wait out its 120 s horizon.
            raise RuntimeError(
                f"the train gang asks for TPU {tpus:g} but no node of "
                "this cluster advertises a TPU: chip detection "
                "(TPU_VISIBLE_CHIPS, /dev/accel*, /dev/vfio) found none and "
                "init() was given none"
            )
        with tracing.span("train.worker_group_start"):  # to actors ready
            self.worker_group = WorkerGroup(
                self._scaling.num_workers,
                self._scaling.as_placement_group_bundles(),
                self._scaling.placement_strategy,
                worker_env=self._worker_env,
            )
        with tracing.span("train.backend_start"):
            self._backend.on_start(self.worker_group, self._backend_config)

    def start_training(
        self,
        train_fn: Callable,
        loop_config: Dict[str, Any],
        dataset_shards_per_rank: List[Dict[str, Any]],
        latest_checkpoint_path: Optional[str],
    ) -> None:
        wg = self.worker_group
        assert wg is not None, "start() must run first"
        group = getattr(wg, "_collective_group", None)
        with tracing.span("train.session_setup"):
            ray_tpu.get([
                w.setup_session.remote(
                    world_rank=rank,
                    world_size=len(wg),
                    local_rank=wg.local_ranks[rank],
                    local_world_size=wg.local_world_sizes[rank],
                    node_rank=wg.node_ranks[rank],
                    trial_info=self._trial_info,
                    latest_checkpoint_path=latest_checkpoint_path,
                    dataset_shards=dataset_shards_per_rank[rank],
                    loop_config=loop_config,
                    collective_group=group,
                )
                for rank, w in enumerate(wg.workers)
            ])
        self._backend.on_training_start(wg, self._backend_config)
        blob = cloudpickle.dumps(train_fn)
        self._run_refs = [w.run.remote(blob) for w in wg.workers]

    def get_next_results(self, timeout_per_poll: float = 10.0):
        """One TrainingResult per rank, or None once all ranks finished.

        Raises TrainingFailedError if ranks disagree (some reported, some
        finished) — same consistency check as the reference (:559).
        """
        wg = self.worker_group
        assert wg is not None
        results: List[Optional[dict]] = [None] * len(wg)
        done: List[bool] = [False] * len(wg)
        while True:
            pending_idx = [
                i for i in range(len(wg)) if results[i] is None and not done[i]
            ]
            if not pending_idx:
                break
            refs = [
                wg.workers[i].poll.remote(timeout_per_poll) for i in pending_idx
            ]
            replies = ray_tpu.get(refs)
            for i, rep in zip(pending_idx, replies):
                if "result" in rep:
                    results[i] = rep["result"]
                elif rep.get("done"):
                    done[i] = True
                    if rep.get("error"):
                        raise TrainingFailedError(
                            f"rank {i} failed: {rep['error']}"
                        )
        if all(done):
            return None
        if any(done):
            raise TrainingFailedError(
                "ranks out of sync: some workers finished while others "
                "reported a result (mismatched session.report calls)"
            )
        return results

    def finish_training(self) -> List[Optional[str]]:
        """Join run() on all ranks; returns per-rank traceback strings."""
        return ray_tpu.get(self._run_refs)

    def shutdown(self) -> None:
        if self.worker_group is not None:
            try:
                self._backend.on_shutdown(self.worker_group, self._backend_config)
            except Exception:
                pass
            self.worker_group.shutdown()
            self.worker_group = None
