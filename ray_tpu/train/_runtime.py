"""The train worker's account of its own time, carried by every report.

`_TrainSession.report` adds one key, `ray_tpu_runtime`, to the metrics it
enqueues (docs/observability.md, "The train path"):

    {"total":              table since the session began,
     "since_first_report": the same less its state at the first report,
     "interval":           the same less its state at the previous report,
     "counters":           {"compile.programs": ..., ...} since the session began,
     "rusage":             this interval's deltas of getrusage and /proc/pressure}

A table is `{span: [count, seconds, longest_seconds, time of the longest]}`
(`ray_tpu.util.tracing.table`). "Since the first report" is the steady state
of any training job: the first interval holds the compile. A name that saw
no span in a window is left out of that window's table.

When an interval lasts more than `SLOW_FACTOR` times the median of those
before it (and `SLOW_MIN_S`), the account records one flight-recorder event (`train`,
`slow_interval`) and logs one line with what this process did in the gap.
"""

from __future__ import annotations

import gc
import logging
import os
import resource
import statistics
import time
from collections import deque
from typing import Any, Dict, Optional

from ray_tpu._private import telemetry
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

KEY = "ray_tpu_runtime"
SLOW_FACTOR = 3.0
SLOW_MIN_SEEN = 5  # intervals seen before one can be called slow
SLOW_MIN_S = 0.1  # a loop that reports every millisecond jitters by 3x
GC_MIN_S = 1e-3  # a collection shorter than this is no span

_RUSAGE = ("ru_nivcsw", "ru_nvcsw", "ru_majflt", "ru_utime", "ru_stime")
_PRESSURE = ("cpu", "memory", "io")

Table = Dict[str, list]


_pressure_fds: Dict[str, int] = {}  # opened once: a report rereads them


def _usage() -> Dict[str, float]:
    """`getrusage` of this process and, where the kernel gives them, the
    stall totals of `/proc/pressure/*` in seconds (the whole machine's, or
    the container's): two small reads a report."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {name: getattr(ru, name) for name in _RUSAGE}
    if not _pressure_fds:
        for what in _PRESSURE:
            try:
                _pressure_fds[what] = os.open(
                    f"/proc/pressure/{what}", os.O_RDONLY)
            except OSError:
                _pressure_fds[what] = -1
    for what, fd in _pressure_fds.items():
        if fd < 0:
            continue
        try:  # "some avg10=0.00 avg60=0.00 avg300=0.00 total=123\nfull ..."
            for line in os.pread(fd, 512, 0).split(b"\n"):
                if line:
                    out[f"pressure.{what}.{line[:4].decode()}"] = (
                        int(line[line.rindex(b"=") + 1:]) / 1e6)
        except (OSError, ValueError):
            pass
    return out


def _less(now: Table, then: Table, longest: Table) -> Table:
    """`now`'s counts and seconds less `then`'s, for the names that saw a
    span between the two; the longest of each from `longest`."""
    out = {}
    for name, (count, seconds, *_) in now.items():
        before = then.get(name)
        if before is not None:
            count, seconds = count - before[0], seconds - before[1]
        if count > 0:
            out[name] = [count, seconds, *longest.get(name, (0.0, 0.0))[-2:]]
    return out


def _keep_longest(into: Table, interval: Table) -> None:
    for name, row in interval.items():
        if row[2] > into.get(name, (0.0, 0.0))[0]:
            into[name] = row[2:]


def _rounded(value: Any) -> Any:
    """The same record with its floats to the microsecond, for a log line."""
    if isinstance(value, float):
        return round(value, 6)
    if isinstance(value, dict):
        return {k: _rounded(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_rounded(v) for v in value]
    return value


_gc_t0 = 0.0


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
        return
    seconds = time.perf_counter() - _gc_t0
    if seconds >= GC_MIN_S:
        tracing.observe("py.gc", seconds, generation=info["generation"])


def watch_gc() -> None:
    """`py.gc`: collections of a millisecond or more, in a train worker."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


class RuntimeAccount:
    def __init__(self) -> None:
        watch_gc()
        self._began = self._previous = tracing.table(mark=True)
        self._first: Optional[Table] = None
        self._longest: Table = {}  # since the session began
        self._longest_steady: Table = {}  # since the first report
        self._counters_began = tracing.counters()
        self._usage = _usage()
        self._t_report = time.perf_counter()
        self._intervals: deque = deque(maxlen=64)
        self._reports = 0
        self._fn_called: list = []  # when, until the first program begins

    def train_fn_called(self) -> None:
        """The worker calls the user's train function now."""
        self._fn_called = [time.time()]

    def program_began(self, start: float) -> None:
        """JAX began to trace a function at `start` (`time.time()`'s
        clock; `_backend_executor._watch_compiles`). The first one after
        the train function was called ends `train.before_first_program`:
        the worker's first touch of the chip, the model's modules imported,
        meshes and closures built, which is user code that no span can
        wrap, between two moments the program does see."""
        if self._fn_called:
            try:
                called = self._fn_called.pop()  # one thread of two gets it
            except IndexError:
                return
            tracing.observe("train.before_first_program", start - called,
                            end=start)

    def block(self) -> Dict[str, Any]:
        """This report's block; called once a report."""
        now_s = time.perf_counter()
        seconds, self._t_report = now_s - self._t_report, now_s
        now = tracing.table(mark=True)
        interval = _less(now, self._previous, now)
        _keep_longest(self._longest, interval)
        if self._first is not None:
            _keep_longest(self._longest_steady, interval)
        usage = _usage()
        counters = tracing.counters()
        block = {
            "total": _less(now, self._began, self._longest),
            "since_first_report": _less(
                now, now if self._first is None else self._first,
                self._longest_steady),
            "interval": interval,
            "counters": {k: v - self._counters_began.get(k, 0)
                         for k, v in counters.items()},
            "rusage": {k: v - self._usage.get(k, 0) for k, v in usage.items()},
        }
        if self._reports:  # the first interval is set-up, and no yardstick
            self._check_slow(seconds, block)
            self._intervals.append(seconds)
        else:
            self._first = now
        self._reports += 1
        self._previous, self._usage = now, usage
        return block

    def _check_slow(self, seconds: float, block: Dict[str, Any]) -> None:
        if len(self._intervals) < SLOW_MIN_SEEN:
            return
        median = statistics.median(self._intervals)
        if seconds <= max(SLOW_FACTOR * median, SLOW_MIN_S):
            return
        record = {
            "seconds": seconds, "median": median, "report": self._reports,
            "spans": block["interval"],
            "gc_s": block["interval"].get("py.gc", (0, 0.0))[1],
            "rusage": block["rusage"],
            "compiles": block["interval"].get("jax.compile", (0,))[0],
        }
        telemetry.record_event("train", "slow_interval", **record)
        logger.warning("train slow_interval %s", _rounded(record))
