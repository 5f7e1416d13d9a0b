"""The train worker's account of its own time, carried by every report.

`_TrainSession.report` adds one key, `ray_tpu_runtime`, to the metrics it
enqueues (docs/observability.md, "The train path"):

    {"total":              table since the session began,
     "since_first_report": the same less its state at the first report,
     "interval":           the same less its state at the previous report,
     "counters":           {"compile.programs": ..., ...} since the session began,
     "counters_since_first_report": the same less their state at the first report,
     "readings":           the last read step's readings, but those its
                           account takes whole,
     "steps":              [step, *what its account says of it] of the last
                           64 read steps that have an account,
     "rusage":             this interval's deltas of getrusage and /proc/pressure}

A table is `{span: [count, seconds, longest_seconds, time of the longest]}`
(`ray_tpu.util.tracing.table`). "Since the first report" is the steady state
of any training job: the first interval holds the compile. A name that saw
no span in a window is left out of that window's table.

The steps account for themselves (`tracing.Step`, which `make_train_step`
hands out): a step leaves its readings, device arrays it does not wait for,
whose copies to the host it starts, and a report takes those that are
ready, reads them from the host's memory and folds them into the counter
`train.steps_read` and those the step's own account names (`_fold_steps`;
`tracing.Account`: this module knows no reading of any model). One that is
not ready stays for the next report: the account never waits for the device.

When an interval lasts more than `SLOW_FACTOR` times the median of those
before it (and `SLOW_MIN_S`), the account records one flight-recorder event (`train`,
`slow_interval`) and logs one line with what this process did in the gap,
and under `steps` the rise of the counters that the steps' accounts named.
"""

from __future__ import annotations

import gc
import itertools
import logging
import os
import resource
import statistics
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

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


def _risen(now: Dict[str, float], then: Dict[str, float]) -> Dict[str, float]:
    """The counters `now` less what they read `then`."""
    return {k: v - then.get(k, 0) for k, v in now.items()}


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


def _ready(readings: Dict[str, Any]) -> Optional[bool]:
    """Whether the device has made a step's readings: one program makes
    them all, so the first says it for all. None where it was deleted since
    (donated on): such a one cannot be asked."""
    first = next(iter(readings.values()))
    return None if first.is_deleted() else first.is_ready()


def _fold_steps(steps: List[tuple]) -> tuple:
    """`steps` (`tracing.take_steps`'s, their arrays ready) into the
    counters: `train.steps_read`, and whatever the account of a callable
    that states one sums of its steps (`tracing.Account`;
    docs/observability.md, "The train path"). Returns (the last step's
    readings, but those its account takes whole, as numbers and lists; the
    accounts' rows, each behind its step's number; the counters they
    named). The report waits on the loop's thread with the device idle, so a
    callable's steps are folded together: a few `numpy` calls a report."""
    sums: Dict[str, Any] = {}
    rows: List[list] = []
    for _, same in itertools.groupby(steps, key=lambda step: id(step[1])):
        numbers, (made, *_), readings = zip(*same)  # one callable's
        if made.account is None:
            continue
        # from the host's memory: `tracing.Step` started the copies
        stacked = {name: np.stack([np.asarray(r[name]) for r in readings])
                   for name in made.account.reads if name in readings[0]}
        added, of_steps = made.account.fold(made.static, stacked)
        for name, n in added.items():
            sums[name] = sums.get(name, 0) + n
        rows.extend([number, *row] for number, row in zip(numbers, of_steps))
    tracing.count("train.steps_read", len(steps))
    for name, n in sums.items():
        tracing.count(name, n)
    _, last, readings = steps[-1]
    shown = last.account.reads if last.account else {}
    return ({k: np.asarray(v).tolist() for k, v in readings.items()
             if shown.get(k, True)}, rows, set(sums))


class RuntimeAccount:
    def __init__(self) -> None:
        watch_gc()
        tracing.take_steps()  # an earlier session's, or nobody's
        self._began = self._previous = tracing.table(mark=True)
        self._first: Optional[Table] = None
        self._longest: Table = {}  # since the session began
        self._longest_steady: Table = {}  # since the first report
        self._counters_began = self._counters_previous = tracing.counters()
        self._counters_first: Optional[Dict[str, float]] = None
        self._unread: deque = deque(maxlen=tracing.STEPS_KEPT)  # not ready
        self._readings: Dict[str, Any] = {}
        self._steps: deque = deque(maxlen=tracing.STEPS_KEPT)
        self._accounted: set = set()  # the counters the steps' accounts named
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

    def _read_steps(self) -> None:
        """Fold the steps whose readings the device has made; the first
        that it has not, and those after it, wait for the next report."""
        self._unread.extend(tracing.take_steps())
        ready = []
        while self._unread:
            state = _ready(self._unread[0][2])
            if state is False:
                break
            step = self._unread.popleft()
            if state:
                ready.append(step)
        if ready:
            try:
                self._readings, rows, named = _fold_steps(ready)
            except RuntimeError:  # an array deleted since: the report stands
                return
            self._steps.extend(rows)
            self._accounted |= named

    def block(self) -> Dict[str, Any]:
        """This report's block; called once a report."""
        self._read_steps()
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
            "counters": _risen(counters, self._counters_began),
            "counters_since_first_report": _risen(
                counters, counters if self._counters_first is None
                else self._counters_first),
            "readings": self._readings,
            "steps": list(self._steps),
            "rusage": {k: v - self._usage.get(k, 0) for k, v in usage.items()},
        }
        if self._reports:  # the first interval is set-up, and no yardstick
            self._check_slow(seconds, block, counters)
            self._intervals.append(seconds)
        else:
            self._first, self._counters_first = now, counters
        self._reports += 1
        self._previous, self._usage = now, usage
        self._counters_previous = counters
        return block

    def _check_slow(self, seconds: float, block: Dict[str, Any],
                    counters: Dict[str, float]) -> None:
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
        risen = _risen(counters, self._counters_previous)
        steps = {k: risen[k] for k in sorted(self._accounted) if risen.get(k)}
        if steps:  # what the interval's steps did, by their own account
            record["steps"] = steps
        telemetry.record_event("train", "slow_interval", **record)
        logger.warning("train slow_interval %s", _rounded(record))
