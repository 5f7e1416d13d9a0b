#!/usr/bin/env python3
"""Nanoseconds of one span of the train path (`ray_tpu.util.tracing.span`).

    python3 benchmarks/train_span_ns.py

Four states, in this order in one process: `jax` not imported; imported
with no profiler session (the state of every step of every train job);
inside a session (the host tracer at level 1, Python's tracer off, as the
chip benchmark's traced run sets them); after the session. Then what a
`train.report` adds: the block `ray_tpu_runtime` (the table merged and
marked, two windows subtracted, `getrusage`, `/proc/pressure`), a garbage
collection's two callbacks, and what the worker's `jax.monitoring`
listeners add to one event of JAX's (a trace, a lowering or a compilation:
a scalar at its start, a time span at its end, as JAX records them), alone
and inside an open one. Last, what the step's own account costs
(`tracing.Step` with the model's `tracing.Account`, which
`_runtime._fold_steps` folds by): a jitted call bare and under `Step`, and
a report that folds a chunk of five steps' readings of a share's shape (26
routed layers of 64 experts) against one that folds none. Prints
one JSON object. The numbers are this host's: `PERF.md` has the chip
host's.
"""

import gc
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.train import _runtime  # noqa: E402
from ray_tpu.util import tracing  # noqa: E402


def per_call(fn, n):
    """Best of three: ns a call, an empty loop's share taken off."""
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        t1 = time.perf_counter()
        for _ in range(n):
            pass
        t2 = time.perf_counter()
        best = min(best, ((t1 - t0) - (t2 - t1)) / n * 1e9)
    return best


def one_span():
    with tracing.span("bench.span"):
        pass


def listener_ns(jax):
    """ns an event of `_watch_compiles`' two listeners, through
    `jax.monitoring`'s own `record_*`: an event alone, and one that starts
    and ends inside another (the self-time rule's path)."""
    from ray_tpu.train._backend_executor import _watch_compiles

    event = "/jax/core/compile/jaxpr_trace_duration"
    record_start = jax.monitoring.record_scalar
    record_span = jax.monitoring.record_event_time_span

    def one_event():
        record_start(event, 1.0, fun_name="f")
        record_span(event, 1.0, 1.5, fun_name="f")

    unwatched = per_call(one_event, 200_000)
    _watch_compiles()
    alone = per_call(one_event, 200_000)
    record_start(event, 0.5, fun_name="outer")
    nested = per_call(one_event, 200_000)
    record_span(event, 0.5, 2.0, fun_name="outer")
    return {"jax_event_ns.unwatched": unwatched,
            "jax_event_ns.watched": alone,
            "jax_event_ns.watched_inside_another": nested}


def step_account(jax):
    """ns a call of a jitted step bare and under `tracing.Step`; us a
    report with five steps' readings to fold, their arrays ready, less the
    five calls and the wait."""
    jnp = jax.numpy

    def fn(state, batch):
        load = jnp.full((26, 64), 1024, jnp.int32) + batch.astype(jnp.int32)[0]
        held = load[:, :8].sum(-1)
        return state + 1, {
            "loss": batch.sum(), "grad_norm": batch.max(),
            "aux_loss": batch.min(), "z_loss": batch.mean(),
            "expert_load": load, "held_slots": held,
            "dropped_slots": jnp.zeros_like(held)}

    jitted = jax.jit(fn)
    from ray_tpu.models import transformer

    step = tracing.Step(
        jitted, {"held_chunk": 11264}, transformer._STEP_ACCOUNT)
    state, batch = jnp.zeros(()), jnp.ones(8)
    jax.block_until_ready(step(state, batch))
    out = {"step_call_ns.bare_jit": per_call(
               lambda: jitted(state, batch), 20_000),
           "step_call_ns.under_step": per_call(
               lambda: step(state, batch), 20_000)}
    account = _runtime.RuntimeAccount()

    def chunk(report):
        for _ in range(5):
            made = step(state, batch)
        jax.block_until_ready(made)
        if report:
            account.block()

    folded = per_call(lambda: chunk(True), 1_000)
    out["report_five_steps_us"] = (
        folded - per_call(lambda: chunk(False), 1_000)) / 1e3
    return out


def main():
    out = {}
    out["span_ns.no_jax"] = per_call(one_span, 200_000)
    import jax

    out["span_ns.jax_no_session"] = per_call(one_span, 200_000)

    def annotation_alone():
        with jax.profiler.TraceAnnotation("bench.span"):
            pass

    out["trace_annotation_alone_ns.no_session"] = per_call(
        annotation_alone, 200_000)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=options)
        out["span_ns.session_on"] = per_call(one_span, 20_000)
        out["trace_annotation_alone_ns.session_on"] = per_call(
            annotation_alone, 20_000)
        jax.profiler.stop_trace()
    out["span_ns.after_session"] = per_call(one_span, 200_000)

    account = _runtime.RuntimeAccount()
    for name in ("data.batch_produce", "data.block_fetch", "data.finalize",
                 "data.batch_assemble", "data.batch_wait", "train.report"):
        with tracing.span(name):
            pass
    out["report_block_us"] = per_call(account.block, 2_000) / 1e3
    watched = per_call(lambda: gc.collect(0), 20_000)
    gc.callbacks.remove(_runtime._on_gc)
    out["gc_callbacks_ns_a_collection"] = watched - per_call(
        lambda: gc.collect(0), 20_000)
    out.update(listener_ns(jax))
    out.update(step_account(jax))
    out["device"] = jax.devices()[0].platform
    print(json.dumps(out))


if __name__ == "__main__":
    main()
