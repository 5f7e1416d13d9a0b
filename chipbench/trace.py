"""From the profiler's `.xplane.pb` to numbers: device busy and idle time,
the operations that took most of it, time in events of a kind (Pallas
kernels, collectives), and the idle gaps by what the host was doing.

Two stages. `extract` reads the file with `jax.profiler.ProfileData` into
plain lists (the form of the recorded fixture beside this file); `reduce`
and the helpers below work on those lists alone, so they are tested without
JAX or a chip.

    {"devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "host_spans": [[name, start_ns, dur_ns], ...]}

A device's `ops` are the events of its "XLA Ops" line: one sequential stream
per TensorCore, in which a `while` (the scan over layers) or a fusion holds
the events of its body. Time is therefore counted by *self segments*: every
instant belongs to the innermost event that covers it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Sequence[Any]  # [name, start_ns, dur_ns]
Segment = Tuple[float, float, str]  # (start_ns, end_ns, innermost event)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
INSIDE_PROGRAM = "inside_the_step_s_program"
NO_SPAN = "no_span_of_the_loop"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(event_name: str) -> str:
    """An op event is named by its whole HLO line. Keep the instruction's
    name, and for a custom call its target: `checkpoint.20 [tpu_custom_call]`
    is a Pallas (Mosaic) kernel."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    target = _TARGET.search(event_name)
    return f"{name} [{target.group(1)}]" if target else name


def extract(path: str, span_names: Iterable[str]) -> Dict[str, Any]:
    """Stage one: the device planes' op and module lines and the host
    events named in `span_names`, as plain lists."""
    from jax.profiler import ProfileData

    wanted = set(span_names)
    out: Dict[str, Any] = {"devices": {}, "host_spans": []}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] += [
                        [short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)]
                        for e in line.events
                    ]
            out["devices"][plane.name] = {
                "ops": lines[OPS_LINE], "modules": lines[MODULES_LINE]}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host_spans"] += [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name in wanted
                ]
    return out


def self_segments(events: Iterable[Event]) -> List[Segment]:
    """Cut a line of possibly nested events into non-overlapping segments,
    each owned by the innermost event covering it. Of two events that
    overlap without nesting, the later one owns the overlap."""
    ordered = sorted(
        ((float(s), float(s) + float(d), str(n)) for n, s, d in events if d > 0),
        key=lambda e: (e[0], -e[1]),
    )
    segments: List[Segment] = []
    stack: List[Tuple[float, float, str]] = []
    cursor = 0.0

    def emit(until: float) -> None:
        nonlocal cursor
        # close what ends before `until`, innermost first
        while stack and stack[-1][1] <= until:
            _, end, name = stack.pop()
            if end > cursor:
                segments.append((cursor, end, name))
                cursor = end
        if stack and until > cursor:
            segments.append((cursor, until, stack[-1][2]))
            cursor = until

    for start, end, name in ordered:
        emit(start)
        cursor = max(cursor, start)
        while stack and end > stack[-1][1]:
            stack.pop()  # overlaps without nesting: the later event owns the rest
        stack.append((start, end, name))
    emit(float("inf"))
    return [s for s in segments if s[1] > s[0]]


def seconds_by_name(segments: Iterable[Segment]) -> Dict[str, float]:
    total: Dict[str, float] = {}
    for start, end, name in segments:
        total[name] = total.get(name, 0.0) + (end - start) / 1e9
    return total


def matching_seconds(segments: Iterable[Segment], patterns: Sequence[str]
                     ) -> float:
    """Self time of the events whose name matches any pattern (regex,
    searched anywhere in the name)."""
    regex = re.compile("|".join(f"(?:{p})" for p in patterns))
    return sum((end - start) / 1e9 for start, end, name in segments
               if regex.search(name))


def gaps(segments: Sequence[Segment], window: Tuple[float, float]
         ) -> List[Tuple[float, float]]:
    """The parts of the window in which no event of the line runs."""
    lo, hi = window
    out = []
    cursor = lo
    for start, end, _ in segments:
        if start > cursor:
            out.append((cursor, min(start, hi)))
        cursor = max(cursor, end)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(a, b) for a, b in out if b > a]


def _overlap(a: float, b: float, c: float, d: float) -> float:
    return max(0.0, min(b, d) - max(a, c))


def attribute_gaps(idle: Sequence[Tuple[float, float]],
                   modules: Iterable[Event], host_spans: Iterable[Event]
                   ) -> Dict[str, float]:
    """Seconds of idle time by cause. A gap inside a module's execution is
    the program's own (`inside_the_step_s_program`); the rest of each gap
    goes to the loop's spans by how much of it each covers, and what no
    span covers to `no_span_of_the_loop`."""
    mods = [(float(s), float(s) + float(d)) for _, s, d in modules]
    spans = [(str(n), float(s), float(s) + float(d)) for n, s, d in host_spans]
    out: Dict[str, float] = {}

    def add(name: str, ns: float) -> None:
        if ns > 0:
            out[name] = out.get(name, 0.0) + ns / 1e9

    for a, b in idle:
        inside = sum(_overlap(a, b, c, d) for c, d in mods)
        inside = min(inside, b - a)
        add(INSIDE_PROGRAM, inside)
        outside = (b - a) - inside
        if outside <= 0:
            continue
        covered = {}
        for name, c, d in spans:
            o = _overlap(a, b, c, d)
            if o > 0:
                covered[name] = covered.get(name, 0.0) + o
        total = sum(covered.values())
        scale = min(1.0, outside / total) if total else 0.0
        for name, o in covered.items():
            add(name, o * scale)
        add(NO_SPAN, outside - total * scale)
    return out


def top(pairs: Dict[str, float], n: int = 10) -> List[List[Any]]:
    return [[k, v] for k, v in
            sorted(pairs.items(), key=lambda kv: -kv[1])[:n]]


def reduce(extracted: Dict[str, Any]) -> Dict[str, Any]:
    """Stage two: what the readers and the last line need.

    The window runs from the first to the last instant of any kept event
    (device operations and loop spans). `busy_s` is the mean over the
    devices of the union of their operations; the breakdown's operations
    are summed over the devices and its gaps are the first device's."""
    devices = extracted["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    every = [e for d in devices.values() for e in d["ops"]]
    every += list(extracted["host_spans"])
    if not any(d["ops"] for d in devices.values()):
        raise ValueError("no operation ran on a device in the traced window")
    window = (min(float(e[1]) for e in every),
              max(float(e[1]) + float(e[2]) for e in every))
    per_device = {}
    ops_total: Dict[str, float] = {}
    for name in sorted(devices):
        segments = self_segments(devices[name]["ops"])
        per_device[name] = {
            "segments": segments,
            "busy_s": sum(e - s for s, e, _ in segments) / 1e9,
        }
        for op, seconds in seconds_by_name(segments).items():
            ops_total[op] = ops_total.get(op, 0.0) + seconds
    first = sorted(devices)[0]
    idle = gaps(per_device[first]["segments"], window)
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / len(per_device),
        "n_devices": len(per_device),
        "segments": {k: v["segments"] for k, v in per_device.items()},
        "device_ops": top(ops_total),
        "idle_gaps": top(attribute_gaps(
            idle, devices[first]["modules"], extracted["host_spans"])),
    }


def share(reduced: Dict[str, Any], patterns: Sequence[str], over: str
          ) -> Optional[float]:
    """Self time of the events matching `patterns`, all devices, over the
    devices' busy time (`over="busy"`) or over the traced window
    (`over="window"`). On the sequential op line nothing else runs while an
    event is the innermost one, so over the window this is, for collectives,
    the time they are exposed."""
    whole = reduced["busy_s"] if over == "busy" else reduced["window_s"]
    if whole <= 0:
        return None
    hit = sum(matching_seconds(s, patterns)
              for s in reduced["segments"].values())
    return hit / (whole * reduced["n_devices"])
