#!/usr/bin/env python3
"""Nanoseconds of one span of the train path (`ray_tpu.util.tracing.span`).

    python3 benchmarks/train_span_ns.py

Four states, in this order in one process: `jax` not imported; imported
with no profiler session (the state of every step of every train job);
inside a session (the host tracer at level 1, Python's tracer off, as the
chip benchmark's traced run sets them); after the session. Then what a
`train.report` adds: the block `ray_tpu_runtime` (the table merged and
marked, two windows subtracted, `getrusage`, `/proc/pressure`), and a
garbage collection's two callbacks. Prints one JSON object. The numbers
are this host's: `PERF.md` has the chip host's.
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
    out["device"] = jax.devices()[0].platform
    print(json.dumps(out))


if __name__ == "__main__":
    main()
