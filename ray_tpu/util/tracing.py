"""Distributed tracing: wire-propagated spans with runtime instrumentation.

Analog of python/ray/util/tracing/tracing_helper.py (:36-57), grown into a
full runtime tracing plane: when enabled (``RAY_TPU_TASK_TRACE_SPANS=1``
for always-on, or ``RAY_TPU_TRACE_SAMPLE_RATE`` for sampled always-on),
every task/actor submission carries a trace context inside the task wire
dict, the submitting side emits a ``submit`` span parented to the caller's
active span, and the executing worker emits an ``execute`` span parented to
the submit span — with the active-span contextvar set for the duration of
user code, so tasks submitted FROM a task chain into the same trace.

Beyond task spans, the runtime emits internal spans on its hot paths
(lease lifecycle, arg fetch, object get/put/pull/push, serve router and
batch queue, data stages, collective ops) via :func:`record_span` /
:func:`span_scope`. The active context additionally rides every RPC
request frame (``rpc.py`` slot 5, beside the deadline TTL), so a handler
on another process sees the caller's span as its ambient parent without
any per-method plumbing.

Two delivery pipelines, one store:

- task submit/execute spans ride the existing task-event pipeline
  (``record_task_event`` state="SPAN" -> AddTaskEvents), preserving the
  core worker's flush-on-exit guarantee;
- runtime spans buffer in a process-local ring (``trace_span_buffer``)
  and flush to the GCS via ``ReportSpans`` on the telemetry cadence,
  mirroring ``telemetry.start_flusher`` exactly (snapshot-and-reset
  delta, fold back on failure).

The GCS diverts both into one bounded ``spans`` ring surfaced through
``list_spans()`` / ``timeline()`` / ``critical_path()``. No OpenTelemetry
SDK dependency: the span model (trace_id / span_id / parent_span_id /
kind / start / duration) is OTLP-shaped so an exporter can translate 1:1.

The train path's spans (:func:`span`; docs/observability.md, "The train
path") are the same spans under one more rule: they do not wait for the
options above. Each adds to a process-local table of seconds by name and,
once ``jax`` is imported, is a ``jax.profiler.TraceAnnotation``, so that a
profiler session holds them beside the device's operations; every
``train.report`` carries the table.

The contextvar itself lives in ``ray_tpu._private.rpc`` (the bottom of
the import graph — the frame codec must read it, and importing this
module from rpc would cycle through ``ray_tpu.util``); this module owns
everything above the raw variable.
"""

from __future__ import annotations

import contextlib
import os
import random
import sys
import threading
import time
import zlib
from collections import deque
from typing import (
    Any, Callable, Dict, Iterable, Iterator, List, NamedTuple, Optional, Tuple)

from ray_tpu._private.common import config
from ray_tpu._private import rpc as _rpc

# The (trace_id, active_span_id) of the current task of execution — shared
# with the RPC layer, which stamps it onto outgoing request frames and
# restores it around incoming handlers.
_trace_ctx = _rpc._trace_ctx
_perf_counter = time.perf_counter

# Span-id generation: a module-level PRNG seeded from the OS once. The
# record path is perf-gated (trace_span_record_ns); os.urandom per span is
# a ~1us syscall, getrandbits is a single GIL-atomic C call. Uniqueness,
# not unpredictability, is what span ids need. Forked workers inherit the
# parent's PRNG state and would emit identical id sequences (colliding
# span ids corrupt the trace DAG), so children reseed at fork time.
_rand = random.Random(os.urandom(8))


def _reseed() -> None:
    _rand.seed(os.urandom(8))


if hasattr(os, "register_at_fork"):  # not on Windows
    os.register_at_fork(after_in_child=_reseed)


def enabled() -> bool:
    return bool(config.task_trace_spans) or config.trace_sample_rate > 0


def _new_id() -> str:
    return "%016x" % _rand.getrandbits(64)


def _sample(key: str) -> bool:
    """Deterministic root-sampling decision: every process hashing the same
    root key independently agrees whether the trace exists, so a sampled
    trace is always complete (no half-recorded requests)."""
    if config.task_trace_spans:
        return True
    rate = config.trace_sample_rate
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    return (zlib.crc32(key.encode()) & 0xFFFFFFFF) / 2**32 < rate


def current_context() -> Optional[tuple]:
    """(trace_id, span_id) of the active span, or None."""
    return _trace_ctx.get()


def set_context(ctx: Optional[tuple]):
    """Set the active span on the CURRENT thread/context; returns a reset
    token (or None if ctx is None). Needed because run_in_executor does not
    propagate contextvars onto pool threads — execution paths that hop
    threads re-set the span where user code actually runs."""
    if ctx is None:
        return None
    return _trace_ctx.set(ctx)


def reset_context(token) -> None:
    if token is not None:
        _trace_ctx.reset(token)


def ctx_from_wire(wire: Dict[str, Any]) -> Optional[tuple]:
    """(trace_id, span_id) from a task wire dict's trace_ctx, or None."""
    ctx = wire.get("trace_ctx")
    if not ctx:
        return None
    return (ctx["trace_id"], ctx["span_id"])


# ---------------------------------------------------------------------------
# Runtime-span ring + flusher (the telemetry-plane pattern: process-local
# bounded buffer, snapshot-and-reset delta flush, restore on failure).
# ---------------------------------------------------------------------------

_buf: "deque[dict]" = deque(maxlen=config.trace_span_buffer)
_buf_lock = threading.Lock()
_flusher_started = False


def _emit(
    name: str,
    kind: str,
    trace_id: str,
    span_id: str,
    parent: Optional[str],
    start: float,
    duration: float,
    attrs: Dict[str, Any],
) -> None:
    """Append one finished span to the ring: the one place a span's
    dictionary is built."""
    span = {
        "state": "SPAN",
        "name": name,
        "kind": kind,
        "span_id": span_id,
        "parent_span_id": parent,
        "trace_id": trace_id,
        "start": start,
        "duration": duration,
        "time": start + duration,
    }
    if attrs:
        span.update(attrs)
    with _buf_lock:
        _buf.append(span)


def record_span(
    name: str,
    kind: str,
    start: float,
    duration: float,
    ctx: Optional[tuple] = None,
    **attrs: Any,
) -> Optional[str]:
    """Record one runtime span parented into the active trace.

    ``ctx`` overrides the ambient context (for spans emitted after the
    originating context is gone, e.g. raylet grant-time spans parented to
    the lease request's captured context). Returns the new span_id, or
    None when there is no trace to join — runtime spans never create
    roots; that is :func:`root_scope`'s job."""
    if ctx is None:
        ctx = _trace_ctx.get()
        if ctx is None:
            return None
    span_id = _new_id()
    _emit(name, kind, ctx[0], span_id, ctx[1], start, duration, attrs)
    return span_id


class Span:
    """One span around a code region: ``__enter__`` starts it and
    ``__exit__`` finishes it, for every scope function of this module.

    ``trace`` is ``(trace_id, parent_span_id)`` or None. With a trace the
    span becomes the active context for its duration (nested spans, and RPC
    calls made inside, parent under it) and goes to the ring when it ends;
    ``with`` then yields ``(trace_id, span_id)``, and None otherwise.

    ``tabled`` marks a span of the train path (:func:`span`): whatever the
    tracing options say, its seconds go to this process's table
    (:func:`table`), and it is a ``jax.profiler.TraceAnnotation`` of the
    same name when ``jax`` has been imported. The annotation costs a flag
    test while no profiler session runs; in a session the span lands in the
    host planes of the same file as the device's operations, on one clock,
    on its thread's line. ``jax`` is never imported from here: a process
    that must stay without a backend stays so.
    """

    __slots__ = ("name", "kind", "attrs", "seconds", "_trace", "_tabled",
                 "_span_id", "_token", "_wall0", "_t0", "_annotation")

    def __init__(self, name: str, kind: str, trace: Optional[tuple],
                 attrs: Dict[str, Any], tabled: bool = False):
        self.name = name
        self.kind = kind
        self.attrs = attrs
        self._trace = trace
        self._tabled = tabled
        self._annotation = None

    def __enter__(self) -> Optional[tuple]:
        trace = self._trace
        ctx = None
        if trace is not None:
            self._span_id = _new_id()
            ctx = (trace[0], self._span_id)
            self._token = _trace_ctx.set(ctx)
            self._wall0 = time.time()
        if self._tabled:
            annotation = _TraceAnnotation or _annotation_class()
            if annotation is not None:
                self._annotation = annotation = annotation(self.name)
                annotation.__enter__()
            self._t0 = _perf_counter()
        return ctx

    def __exit__(self, *exc) -> None:
        if self._tabled:
            t1 = _perf_counter()
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
            self.seconds = seconds = t1 - self._t0  # for a caller that keeps the span
            _add(self.name, seconds, t1)
        trace = self._trace
        if trace is not None:
            _trace_ctx.reset(self._token)
            _emit(self.name, self.kind, trace[0], self._span_id, trace[1],
                  self._wall0, time.time() - self._wall0, self.attrs)


def _joined(ctx: Optional[tuple] = None) -> Optional[tuple]:
    """The trace a runtime span joins: ``ctx`` or the ambient context, and
    None when tracing is off or no trace is active."""
    if not enabled():
        return None
    return ctx if ctx is not None else _trace_ctx.get()


def _joined_or_rooted(key: str) -> Optional[tuple]:
    """As :func:`_joined`, but with no trace active a new one begins here,
    subject to the sampling decision on ``key``."""
    if not enabled():
        return None
    cur = _trace_ctx.get()
    if cur is not None:
        return cur
    return (_new_id(), None) if _sample(key) else None


def span_scope(name: str, kind: str, ctx: Optional[tuple] = None,
               **attrs: Any) -> Span:
    """Span around a runtime code region. Sets the active context to the
    new span for the duration, so nested spans — and RPC calls made inside
    — parent under it. No-op when tracing is off or no trace is active."""
    return Span(name, kind, _joined(ctx), attrs)


def root_scope(name: str, kind: str, key: Optional[str] = None,
               **attrs: Any) -> Span:
    """Span that CREATES a trace when none is active (subject to the
    sampling decision on ``key``). The serve router wraps each request in
    one of these, so a bare HTTP/handle call — no task ancestry — still
    yields a connected trace. Inside an existing trace it behaves exactly
    like :func:`span_scope`."""
    return Span(name, kind,
                _joined_or_rooted(key if key is not None else name), attrs)


def iter_scope(it: Iterable, name: str, kind: str = "data", **attrs: Any) -> Iterator:
    """Wrap an iterator in one span covering the whole iteration, with the
    span active while the iterator body runs — so every task a streaming
    executor submits joins a single trace. Creates a root (sampled on
    ``name``) when no trace is active."""
    with Span(name, kind, _joined_or_rooted(name), attrs):
        yield from it


def span(name: str, kind: str = "train", **attrs: Any) -> Span:
    """A span of the train path (docs/observability.md, "The train path"):
    always in this process's table and, once ``jax`` is imported, in the
    profiler's trace; in the ring as any runtime span, when tracing is on
    and a trace is active."""
    # `_joined()` spelled out: this is the one scope function on a step's path
    if config.task_trace_spans or config.trace_sample_rate > 0:
        return Span(name, kind, _trace_ctx.get(), attrs, True)
    return Span(name, kind, None, attrs, True)


def observe(name: str, seconds: float, kind: str = "train",
            end: Optional[float] = None, inside: float = 0.0,
            **attrs: Any) -> None:
    """A span of the train path that is known only once it has ended (a
    trace, a lowering or a compilation reported by ``jax.monitoring``, a
    garbage collection): it took ``seconds`` and ended at ``end`` on
    ``time.time()``'s clock, now if none is given. It reaches the table and
    the ring, not the profiler's trace, which cannot be told of a span
    after the fact.

    ``inside`` is the seconds of the observed spans that ended inside this
    one on its thread (a ``jit`` traced while another is). The table takes
    the span's *self* time, ``seconds - inside``, so that what one thread
    adds under nested names is the wall time it spent; the ring takes the
    whole span where it happened."""
    now = time.time()
    ago = 0.0 if end is None else now - end
    _add(name, seconds - inside, time.perf_counter() - ago)
    trace = _joined()
    if trace is not None:
        _emit(name, kind, trace[0], _new_id(), trace[1],
              now - ago - seconds, seconds, attrs)


# ---------------------------------------------------------------------------
# The table of the train path's spans: seconds by name, kept by every
# process whatever the tracing options say. Each thread adds to a table of
# its own (no lock on a span's path); :func:`table` merges them.
# ---------------------------------------------------------------------------

_local = threading.local()
# id(table) -> (its thread, {name: [count, seconds, longest, end of the
# longest on perf_counter's clock, the mark the longest belongs to]}). A dead
# thread's table is folded into `_retired`, so a job that starts a prefetch
# thread an epoch keeps one table a live thread.
_tables: Dict[int, tuple] = {}
_retired: Dict[str, list] = {}
_tables_lock = threading.Lock()
_mark = 0
_counters: Dict[str, int] = {}
_TraceAnnotation = None


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` once ``jax`` has been imported by
    whoever needs it, None before."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _TraceAnnotation = getattr(profiler, "TraceAnnotation", None)
    return _TraceAnnotation


def _add(name: str, seconds: float, end: float) -> None:
    try:
        rows = _local.rows
    except AttributeError:
        rows = _local.rows = {}
        with _tables_lock:
            _tables[id(rows)] = (threading.current_thread(), rows)
    row = rows.get(name)
    if row is None:
        rows[name] = [1, seconds, seconds, end, _mark]
        return
    row[0] += 1
    row[1] += seconds
    if row[4] != _mark:
        row[2], row[3], row[4] = seconds, end, _mark
    elif seconds > row[2]:
        row[2], row[3] = seconds, end


def _fold(into: Dict[str, list], rows: Dict[str, list]) -> None:
    for name, (count, seconds, longest, end, mark) in rows.items():
        have = into.get(name)
        if have is None:
            into[name] = [count, seconds, longest, end, mark]
            continue
        have[0] += count
        have[1] += seconds
        if (mark, longest) > (have[4], have[2]):
            have[2], have[3], have[4] = longest, end, mark


def table(mark: bool = False) -> Dict[str, list]:
    """``{name: [count, seconds, longest_seconds, time of the longest]}``
    of the train path's spans in this process, every thread's merged.
    Count and seconds run from the process's start, or in a driver from its
    last ``ray_tpu.shutdown()`` (:func:`clear_table`), so that a reader
    takes the difference of two readings for a window. The longest cannot
    be had that way: it is the longest since the last reading with ``mark`` (0
    where the name saw no span since then), with the wall-clock time at
    which it ended. One reader of a process marks: the train session, at
    every report."""
    global _mark
    merged: Dict[str, list] = {}
    with _tables_lock:
        for key, (thread, rows) in list(_tables.items()):
            # read `is_alive` first: a thread's last span precedes its end
            if thread.is_alive():
                _fold(merged, rows.copy())
            else:
                _fold(_retired, rows)
                del _tables[key]
        _fold(merged, _retired)
        current = _mark
        if mark:
            _mark += 1
    to_wall = time.time() - time.perf_counter()
    return {
        name: ([count, seconds, longest, end + to_wall] if at == current
               else [count, seconds, 0.0, 0.0])
        for name, (count, seconds, longest, end, at) in merged.items()
    }


def clear_table() -> None:
    """Empty the table, the counters and the steps not yet taken: the
    cluster this process drove has ended (``ray_tpu.shutdown()``), and the
    next one's ``init`` is not to be read together with this one's. A span
    that is open now is counted when it ends."""
    with _tables_lock:
        for _, rows in _tables.values():
            rows.clear()
        _retired.clear()
        _counters.clear()
    _steps.clear()


def count(name: str, n: float = 1) -> None:
    """Add to a counter of the train path (``compile.programs``, ...): a
    whole number, or where ratios are summed a float."""
    with _tables_lock:  # a compilation's pace, not a span's: a lock is cheap
        _counters[name] = _counters.get(name, 0) + n


def counters() -> Dict[str, float]:
    with _tables_lock:
        return dict(_counters)


# ---------------------------------------------------------------------------
# The step: the one boundary a train job crosses most often. The program sees
# its own steps here, whoever's loop calls them.
# ---------------------------------------------------------------------------

STEPS_KEPT = 64
READING_BYTES = 1 << 16  # a reading above this is no number to report
# (the step's number, its `Step`, its readings), oldest first, until the
# train session's account takes them (`take_steps`). Bounded: outside a
# session nothing takes them, and the oldest goes.
_steps: "deque[tuple]" = deque(maxlen=STEPS_KEPT)


class Account(NamedTuple):
    """What a report makes of a `Step`'s steps beside showing the last one's
    readings, stated by whoever built the step: the train session's account
    folds by it and knows no reading's name."""
    # {a reading `fold` is handed: whether a report still shows the last's}
    reads: Dict[str, bool]
    # (the step's `static`, {reading: the steps' stacked [S, ...]} of those
    # `reads` the steps made) -> ({counter: the number the steps add to it},
    # a list a step for the block's `steps`, or none)
    fold: Callable[[Dict[str, Any], Dict[str, Any]], Tuple[dict, List[list]]]


class Step:
    """A jitted train step `(state, batch) -> (state, readings)` as the
    program sees it run: a call is the span ``train.step`` (the host's
    seconds to hand the step to the runtime; its count is the program's own
    number of steps) and leaves the step's readings (those of
    ``READING_BYTES`` at most), device arrays that the call does not wait
    for, where the train session's account finds them
    (``ray_tpu/train/_runtime.py``). Their copies to the host are started
    here (``copy_to_host_async``: queued behind the step, waited for by
    nobody), so that a report reads them from the host's memory: fetched
    at the report they cost 0.15 ms an array on a v5e's host, 3.4 ms a
    chunk of five steps, with the device idle (PERF.md section 6, PR 68).
    ``static`` holds what the step's trace said of its program and no run
    changes, ``account`` what a report sums of the steps. Every other
    attribute (``lower``, ``trace``, ``_cache_size``, ...) is the jitted
    function's."""

    def __init__(self, jitted, static: Dict[str, Any],
                 account: Optional[Account] = None):
        self._jitted = jitted
        self.static = static
        self.account = account
        self.calls = 0

    def __call__(self, *args, **kwargs):
        with span("train.step"):
            out = self._jitted(*args, **kwargs)
        readings = out[1]
        # traced through (`jax.eval_shape(step, ...)`), it ran nothing
        if hasattr(next(iter(readings.values()), None), "copy_to_host_async"):
            kept = {name: x for name, x in readings.items()
                    if x.nbytes <= READING_BYTES}
            for x in kept.values():
                x.copy_to_host_async()
            self.calls += 1
            _steps.append((self.calls, self, kept))
        return out

    def __getattr__(self, name: str):
        if name == "_jitted":  # a copy not yet filled: no attribute, no loop
            raise AttributeError(name)
        return getattr(self._jitted, name)


def take_steps() -> List[tuple]:
    """The steps that ran since the last call, oldest first, the last
    ``STEPS_KEPT`` of them at most: theirs who calls."""
    taken = []
    while True:
        try:
            taken.append(_steps.popleft())
        except IndexError:
            return taken


def span_flush_delta() -> List[dict]:
    """Snapshot-and-reset the runtime-span buffer. The caller owns the
    returned spans; on delivery failure fold them back with
    :func:`restore_spans` so a transient GCS outage loses nothing."""
    with _buf_lock:
        if not _buf:
            return []
        spans = list(_buf)
        _buf.clear()
    return spans


def restore_spans(spans: List[dict]) -> None:
    """Fold an undelivered flush delta back into the buffer (oldest first,
    so ring eviction still drops the oldest)."""
    if not spans:
        return
    with _buf_lock:
        _buf.extendleft(reversed(spans))


async def flush_spans_once(call, source: str, node: Optional[str] = None) -> None:
    """One flush cycle: ship the span delta via ``call`` (an async
    ``(method, payload) ->`` RPC callable, e.g. ``gcs.call``)."""
    spans = span_flush_delta()
    if not spans:
        return
    try:
        await call("ReportSpans", {"source": source, "node": node, "spans": spans})
    except Exception:
        restore_spans(spans)
        raise


def start_span_flusher(call, source: str, node: Optional[str] = None) -> None:
    """Start the periodic span flusher on the running loop (idempotent per
    process, like ``telemetry.start_flusher``). Rides the telemetry flush
    cadence; gated on tracing being enabled at all."""
    global _flusher_started
    interval = config.telemetry_flush_interval_s
    if _flusher_started or not enabled() or interval <= 0:
        return
    _flusher_started = True

    async def _loop() -> None:
        import asyncio

        while True:
            await asyncio.sleep(interval)
            try:
                await flush_spans_once(call, source, node)
            except asyncio.CancelledError:
                raise
            except Exception:
                pass  # delta restored; retried next tick

    _rpc.spawn(_loop())


def flusher_active() -> bool:
    """True when this process runs a periodic span flusher (the GCS skips
    its query-time local drain in that case — the flusher owns delivery
    and carries the right source attribution)."""
    return _flusher_started


def stop_flusher() -> None:
    """Mark the flusher stopped. Called when the core worker closes: the
    flusher task dies with the event loop, and leaving the flag set would
    make a later init in the same process (tests, repeated drivers) skip
    both the restart and the GCS's query-time local drain."""
    global _flusher_started
    _flusher_started = False


def snapshot() -> List[dict]:
    """Non-destructive copy of the local buffer (chaos dumps)."""
    with _buf_lock:
        return list(_buf)


def reset() -> None:
    """Drop all buffered spans (chaos per-seed isolation)."""
    with _buf_lock:
        _buf.clear()


# ---------------------------------------------------------------------------
# Task-level spans (submit/execute) — these ride the task-event pipeline so
# they inherit its flush-on-exit and existing GCS plumbing.
# ---------------------------------------------------------------------------


def make_submit_ctx(core, task_id: str, name: str) -> Optional[Dict[str, str]]:
    """Record the submit-side span and return the wire trace context
    ({trace_id, span_id}) the executing worker will parent to. A submission
    with no active trace is a new root, created only when the sampling
    decision on ``task_id`` says so."""
    if not enabled():
        return None
    cur = _trace_ctx.get()
    if cur is None:
        if not _sample(task_id):
            return None
        trace_id = _new_id()
    else:
        trace_id = cur[0]
    span_id = _new_id()
    core.record_task_event(
        task_id,
        name,
        "SPAN",
        span_id=span_id,
        parent_span_id=cur[1] if cur else None,
        trace_id=trace_id,
        kind="submit",
        start=time.time(),
        duration=0.0,
    )
    return {"trace_id": trace_id, "span_id": span_id}


@contextlib.contextmanager
def execute_scope(core, wire: Dict[str, Any]):
    """Worker-side span around user code execution. Sets the active-span
    contextvar so nested submissions parent correctly (the propagation the
    reference does by injecting into TaskSpec and wrapping the function)."""
    ctx = wire.get("trace_ctx")
    if not ctx:
        yield
        return
    span_id = _new_id()
    token = _trace_ctx.set((ctx["trace_id"], span_id))
    t0 = time.time()
    try:
        yield
    finally:
        _trace_ctx.reset(token)
        core.record_task_event(
            wire["task_id"],
            wire.get("name") or wire.get("actor_method") or "task",
            "SPAN",
            span_id=span_id,
            parent_span_id=ctx["span_id"],
            trace_id=ctx["trace_id"],
            kind="execute",
            start=t0,
            duration=time.time() - t0,
        )
