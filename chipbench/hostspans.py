"""The device's idle gaps by what the host was doing, thread by thread.

    python3 -m chipbench.hostspans <trace_dir> [--record <file.json>]

A traced run's `.xplane.pb` holds, in its host planes and on the clock of
the device's operations, the loop's four spans (`loop.SPANS`) and the
program's own (`ray_tpu/util/tracing.py` `span`: a `TraceAnnotation` once
`jax` is imported, so the profiler's session is their one switch). The
ledger's `idle_gaps` split the first device's idle time by the loop's four
alone, all threads in one list. This prints the same gaps (`trace.gaps` of
`trace.self_segments`, over the window `trace.reduce` takes) once for every
host thread that holds a span of either kind, by the innermost span that
covers each part of a gap (`trace.attribute_gaps`): the loop's thread says
what the loop waited in, the prefetch thread what ingest was doing
meanwhile.

`extract` returns `trace.extract`'s lists with one key more, the form of
the recorded fixture `fixtures/v5e_lfm2moe_host_threads.json`:

    {"devices": ..., "host_spans": ...,
     "host_threads": {"<line>#<n>": [[name, start_ns, dur_ns], ...]}}

A thread is a line of a host plane; the profiler names a line after the
process, so a thread is told from its neighbour by its place (`#<n>`) and
by the spans it holds. `--record` writes those lists with each device's
operations merged into runs (operations less than `RUN_GAP_NS` apart become
one event), which keeps the gaps and drops the 10,000 names.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Iterable, List

from chipbench import loop, trace

# ray_tpu/util/tracing.py's spans of the train worker
# (docs/observability.md, "The train path")
PROGRAM_SPANS = (
    "data.pipeline_start", "data.epoch_start", "data.batch_produce",
    "data.block_fetch", "data.batch_assemble", "data.finalize",
    "data.batch_wait", "train.report", "train.checkpoint_persist",
)
RUN_GAP_NS = 1000.0


def extract(path: str, names: Iterable[str]) -> Dict[str, Any]:
    """`trace.extract`, and the same host events once more by thread."""
    from jax.profiler import ProfileData

    wanted = set(names)
    out = trace.extract(path, wanted)
    threads: Dict[str, List[List[Any]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events if e.name in wanted]
            if events:
                threads[f"{line.name}#{n}"] = events
    out["host_threads"] = threads
    return out


def attribute(extracted: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Seconds of the first device's idle time by cause, for each host
    thread: `trace.attribute_gaps` on the thread's innermost spans."""
    devices = extracted["devices"]
    first = sorted(devices)[0]
    every = [e for d in devices.values() for e in d["ops"]]
    every += list(extracted["host_spans"])
    window = (min(float(e[1]) for e in every),
              max(float(e[1]) + float(e[2]) for e in every))
    idle = trace.gaps(trace.self_segments(devices[first]["ops"]), window)
    return {
        thread: trace.attribute_gaps(
            idle, devices[first]["modules"],
            [[name, start, end - start]
             for start, end, name in trace.self_segments(events)])
        for thread, events in sorted(extracted["host_threads"].items())
    }


def merged_runs(extracted: Dict[str, Any]) -> Dict[str, Any]:
    """The same lists with each device's operations merged into runs."""
    out = dict(extracted, devices={})
    for name, device in extracted["devices"].items():
        runs: List[List[Any]] = []
        for start, end, _ in trace.self_segments(device["ops"]):
            if runs and start - (runs[-1][1] + runs[-1][2]) < RUN_GAP_NS:
                runs[-1][2] = end - runs[-1][1]
            else:
                runs.append(["ops", start, end - start])
        out["devices"][name] = {"ops": runs, "modules": device["modules"]}
    return out


def main(argv: List[str]) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace_dir")
    parser.add_argument("--record", help="write the extracted lists here")
    args = parser.parse_args(argv)
    extracted = extract(trace.find_xplane(args.trace_dir),
                        loop.SPANS + PROGRAM_SPANS)
    if args.record:
        with open(args.record, "w") as f:
            json.dump(merged_runs(extracted), f)
    for thread, causes in attribute(extracted).items():
        held = sorted({e[0] for e in extracted["host_threads"][thread]})
        print(f"{thread}: holds {', '.join(held)}")
        for cause, seconds in trace.top(causes):
            print(f"  {seconds * 1e3:10.3f} ms  {cause}")


if __name__ == "__main__":
    main(sys.argv[1:])
