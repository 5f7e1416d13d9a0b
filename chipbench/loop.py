"""What the train worker runs: the only process that touches the chip.

The same loop for every cell. What differs between cells comes from their
files: the configuration, the traffic mix, and the family module
(`loops/<family>.py`) that builds state and step from the program's pieces.

Set-up, in order: device check; parameters made on the device from the seed;
the comparison with the plain reference; the optimizer state; the cell's one
step shape compiled (from the persistent cache after a checkout's first run)
and warmed up on real batches. Then the window: chunks of consecutive steps,
the device waited for and the host clock read only at a chunk's end, one
`train.report` a chunk. Spans are `jax.profiler.TraceAnnotation`s around the
calls into each layer, with their host-clock totals kept beside.
"""

from __future__ import annotations

import contextlib
import faulthandler
import math
import time
from typing import Any, Dict, Iterator, List

SPANS = ("next_batch", "step_dispatch", "chunk_result_wait", "report")
CHECK_INDEX = 2**31 - 1  # the block number of the comparison's batch
RESIDENT_INDEX = 2**30   # the first block number of resident batches


class Spans:
    """Host-clock seconds by span name, and the same spans in the
    profiler's trace when one is being taken."""

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = {name: 0.0 for name in SPANS}

    @contextlib.contextmanager
    def __call__(self, name: str) -> Iterator[None]:
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.seconds[name] += time.perf_counter() - t0


def require_devices(chips: int) -> Dict[str, Any]:
    """The worker's devices must be TPUs, as many as the cell asks for. A
    test steers past this by replacing it; the program has no option."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu" or device["count"] != chips:
        raise RuntimeError(
            f"the train worker sees {device}; the cell needs platform 'tpu' "
            f"with {chips} device(s)"
        )
    return device


def held_in_window(stats: Dict[str, Any]) -> int:
    """What a chip holds while the window runs, from its allocator's
    statistics read after the window: live arrays (`bytes_in_use`) plus the
    loaded step's scratch, which the allocator holds apart as
    `bytes_reserved` for as long as the program is loaded. Set-up's own peak
    (`peak_bytes_in_use`: the comparison's gradients may have been more live
    arrays than the window holds) is the benchmark's and not the program's:
    it is on the worker's info line, and is no part of this figure."""
    return int(stats.get("bytes_in_use", 0)) + int(
        stats.get("bytes_reserved", 0))


def seed_key(seed: int):
    """A PRNG key from any seed up to 64 bits: the low 31 bits make the key,
    the rest is folded in."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def state_bytes_per_device(state, devices) -> Dict[str, Any]:
    """Bytes of the state's shards on each device, and of the whole state."""
    import jax

    per_device = {d.id: 0 for d in devices}
    whole = 0
    for leaf in jax.tree.leaves(state):
        whole += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] += shard.data.nbytes
    return {"whole": whole, "per_device": [per_device[d.id] for d in devices]}


def _batches(traffic, config, family, seed: int):
    """An endless stream of device batches, as the mix's `kind` says."""
    from ray_tpu import train

    from chipbench import traffic as traffic_lib

    if traffic["kind"] == "resident":
        placed = [
            family.to_device(traffic_lib.make_rows(
                traffic, config, seed, RESIDENT_INDEX + i,
                int(traffic["batch_rows"])))
            for i in range(int(traffic["resident_batches"]))
        ]
        while True:
            yield from placed
    shard = train.get_dataset_shard("train")
    while True:  # epoch after epoch, until the loop stops asking
        yield from shard.iter_batches(
            batch_size=int(traffic["batch_rows"]),
            prefetch_batches=int(traffic["prefetch_batches"]),
            drop_last=True,
            _finalize_fn=family.to_device,
        )


def train_loop(job: Dict[str, Any]) -> None:
    t_enter = time.time()
    import jax

    from ray_tpu import train

    from chipbench import compare, spec
    from chipbench import traffic as traffic_lib

    device = require_devices(job["chips"])
    devices = jax.devices()
    config, traffic, seed = job["config"], job["traffic"], job["seed"]

    # Every program compiled or loaded from the persistent cache, counted
    # as it happens; `cache` splits them into hits and misses.
    counted = {"compiles": 0}
    cache = {"hits": 0, "misses": 0}

    def on_duration(event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            counted["compiles"] += 1

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    stages: Dict[str, float] = {"gang_boot": t_enter - job["t_fit_called"]}
    family = spec.load_code(job["root"], "loops", config["family"]).build(
        config, traffic, devices)

    t0 = time.perf_counter()
    params = jax.block_until_ready(family.init_params(seed_key(seed)))
    params_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    raw = traffic_lib.make_rows(
        traffic, config, seed, CHECK_INDEX, int(config["check"]["rows"]))
    errors = family.check(params, family.check_batch(raw))
    stages["reference_check"] = time.perf_counter() - t0
    agrees = compare.within(errors, family.tolerance)

    t0 = time.perf_counter()
    state = jax.block_until_ready(family.init_state(params))
    del params
    stages["state_init"] = params_s + time.perf_counter() - t0
    placement = state_bytes_per_device(state, devices)

    batches = _batches(traffic, config, family, seed)
    t0 = time.perf_counter()
    first = next(batches)
    stages["first_batch"] = time.perf_counter() - t0
    # The first call compiles the cell's one step shape, or loads it from
    # the persistent cache; the second shows that the state a step returns
    # is laid out as the step takes it. A later compilation is counted, and
    # one inside the window makes the run incorrect.
    step = family.step
    t0 = time.perf_counter()
    state, out = step(state, first)
    state, out = step(state, next(batches))
    jax.block_until_ready(out)
    stages["compile"] = time.perf_counter() - t0
    del first
    t0 = time.perf_counter()
    for _ in range(int(traffic["warmup_steps"])):
        state, out = step(state, next(batches))
    jax.block_until_ready(out)
    stages["warmup"] = time.perf_counter() - t0

    units_per_step = traffic_lib.units_per_step(traffic)
    steps_per_chunk = int(traffic["steps_per_chunk"])
    trace_dir = job.get("trace_dir")
    trace_from, trace_chunks = 2, int(traffic["trace_chunks"])
    tracing, tracer_s = False, 0.0
    chunks: List[Dict[str, Any]] = []
    losses_bad = 0
    spans = Spans()
    compiles_before = counted["compiles"]
    stages["setup"] = time.time() - job["t_process_start"]
    t_window = t_chunk = time.perf_counter()
    while t_chunk - t_window < job["seconds"]:
        if trace_dir and len(chunks) == trace_from:
            # should the tracer hang, the threads' stacks say where
            stacks = open(trace_dir + "_stacks.txt", "w")
            faulthandler.dump_traceback_later(120, repeat=True, file=stacks)
            stop_trace = _stopper(stacks)
            jax.profiler.start_trace(
                trace_dir, profiler_options=_options(traffic))
            tracing = True
            tracer_s += time.perf_counter() - t_chunk
            t_chunk = time.perf_counter()
        outs = []
        for _ in range(steps_per_chunk):
            with spans("next_batch"):
                batch = next(batches)
            with spans("step_dispatch"):
                state, out = step(state, batch)
            outs.append(family.loss_of(out))
        with spans("chunk_result_wait"):
            losses = [float(x) for x in outs]
        t_end = time.perf_counter()
        chunk = {
            "chunk": len(chunks), "steps": steps_per_chunk,
            "units": units_per_step * steps_per_chunk,
            "seconds": t_end - t_chunk, "loss": losses[-1],
            "traced": tracing,
        }
        losses_bad += sum(not math.isfinite(x) for x in losses)
        chunks.append(chunk)
        with spans("report"):
            train.report(chunk)
        if tracing and len(chunks) == trace_from + trace_chunks:
            t0 = time.perf_counter()
            stop_trace()
            tracing = False
            tracer_s += time.perf_counter() - t0
        t_chunk = time.perf_counter()
    # the window's own time: what starting and stopping the tracer took is
    # the tracer's, in the traced run, and no part of any reading
    window_s = t_chunk - t_window - tracer_s
    if tracing:
        stop_trace()
    compiles_in_window = counted["compiles"] - compiles_before
    batches.close()

    memory = [d.memory_stats() or {} for d in devices]
    peak = max(held_in_window(m) for m in memory)
    train.report({
        "summary": True,
        "device": device,
        "stages": stages,
        "window_s": window_s,
        "tracer_s": tracer_s,
        "chunks": chunks,
        "spans": spans.seconds,
        "steps": len(chunks) * steps_per_chunk,
        "steps_failed": losses_bad,
        "compiles_in_window": compiles_in_window,
        "compile_cache": cache,
        "reference": {**errors, "tolerance": family.tolerance, "agrees": agrees},
        "state_bytes": placement,
        "flops_per_unit": family.flops_per_unit,
        "memory_peak_bytes": peak,
        "setup_peak_bytes_in_use": max(
            int(m.get("peak_bytes_in_use", 0)) for m in memory),
        "memory_stats": {k: int(v) for k, v in memory[0].items()
                         if isinstance(v, (int, float))},
    })


def _stopper(stacks):
    """Stop the trace and the watch that was set on it."""
    def stop() -> None:
        import jax

        jax.profiler.stop_trace()
        faulthandler.cancel_dump_traceback_later()
        stacks.close()

    return stop


def _options(traffic: Dict[str, Any]):
    """Python's call tracer off. The host tracer at level 1 records the
    loop's spans; a mix whose batches the runtime re-tiles on the host sets
    `host_tracer_level` to 0, because the runtime then writes one host event
    per tile (9 million for ten batches of 256 images), which stalls the
    traced steps and makes stopping the trace outlast the run."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = int(traffic.get("host_tracer_level", 1))
    return options
