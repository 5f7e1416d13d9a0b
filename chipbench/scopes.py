"""Device self time by what the program calls its work: the scopes
(`jax.named_scope`) and kernel names (`pl.pallas_call(name=)`) that
`ray_tpu/models` and `ray_tpu/ops` set, and the phase of the step (forward,
recompute, backward, optimizer, or unnamed) that JAX's own name stack gives.

An event of the "XLA Ops" line has three stats of its own (offset, duration,
time scale) and no name stack. Its *metadata* has: in the `.xplane.pb` every
operation of a device plane is an `XEventMetadata` whose stat `tf_op` is the
instruction's `op_name`, the JAX name stack (`jit(step)/transpose(jvp())/
while/body/closed_call/checkpoint/attention/flash_bwd_dq/pallas_call:`).
`jax.profiler.ProfileData` does not reach a plane's metadata, so
`name_stacks` reads that map with a protobuf wire walker of its own, and the
rest of this file works on plain lists and dicts: the segments of
`trace.reduce` and `{event's short name: name stack}`. A program without
scopes (this benchmark laid over an older checkout) gives no table, and the
metrics that read one are left out.

    python3 -m chipbench.scopes .chipbench/<cell>/trace [--ops 3]
        [--run <the run's standard output> --workload <cell>]

prints the `device_scopes` line of a traced run: every row of phase x scope
with its seconds and its percent of busy time, and with `--ops` the
compiler's names of the operations that took most of each row. With `--run`
it also reads the metrics that wait under `chipbench/metrics/` with the
`entry` they will have in `BENCHMARK.json` (`readers/scope_share.py`,
`readers/kernel_roofline.py`): `run.py` cannot hand a reader the name stacks
yet (PERF.md section 7 names the edits).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import re
import sys
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from chipbench import loop, spec, trace

# What the program names, and where (docs/observability.md has the table;
# tests/test_device_scopes.py holds these against the lowered steps).
SCOPES = (
    # ray_tpu/models/transformer.py
    "embed", "attn_qkv", "attention", "attn_out", "mlp", "final_norm",
    "lm_head_ce", "optimizer",
    # ray_tpu/models/resnet.py
    "stem", "stage1", "stage2", "stage3", "stage4", "head", "conv", "bn",
)
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")  # ops/flash_attention.py
PHASES = ("forward", "recompute", "backward", "optimizer", "unnamed")
UNSCOPED = "unscoped"
PALLAS = "[tpu_custom_call]"  # how `trace.short_name` marks a Mosaic kernel

_NAMED = frozenset(SCOPES + KERNELS)

Row = List[Any]  # [phase, scope, seconds, percent_of_busy]


@functools.lru_cache(maxsize=None)
def classify(name_stack: Optional[str]) -> Tuple[str, str]:
    """(phase, scope) of one instruction, from its name stack.

    The stack is cut at `/`, `(` and `)`, so `transpose(jvp(stage3))/bn`
    gives `transpose, jvp, stage3, bn`; where the compiler merged two
    instructions and kept both stacks (`a;b`), the first counts. The scope
    is the path of the program's names among them, in order. The phase is
    `recompute` under `rematted_computation` (`jax.checkpoint`'s second
    forward), else `backward` under `transpose`, else `optimizer` under
    that scope, else `forward` under `jvp` or under any scope of a model.
    What is left of the named instructions lies outside the differentiated
    function and is the update of the state: the optimizer of a step that
    has no such scope (`chipbench/loops/resnet.py` builds the ResNet step)
    and the step counter. An instruction without a name stack is the
    compiler's own (on the chip: the `copy-done` and `slice-done` ends of
    asynchronous copies, casts of the entry's arguments) and is `unnamed`:
    no phase of the program can claim it."""
    first = (name_stack or "").split(";", 1)[0]  # of a merged instruction's
    parts = [p for p in re.split(r"[/()]", first) if p]
    scope = "/".join(p for p in parts if p in _NAMED) or UNSCOPED
    if not parts:
        phase = "unnamed"
    elif "rematted_computation" in parts:
        phase = "recompute"
    elif "transpose" in parts:
        phase = "backward"
    elif "jvp" in parts or (scope != UNSCOPED and "optimizer" not in parts):
        phase = "forward"
    else:
        phase = "optimizer"
    return phase, scope


def by_row(reduced: Dict[str, Any], stacks: Dict[str, str]
           ) -> Dict[Tuple[str, str], Dict[str, float]]:
    """Seconds of self time by (phase, scope) and, inside each, by the
    event's short name; all devices summed."""
    out: Dict[Tuple[str, str], Dict[str, float]] = {}
    for segments in reduced["segments"].values():
        for start, end, name in segments:
            ops = out.setdefault(classify(stacks.get(name)), {})
            ops[name] = ops.get(name, 0.0) + (end - start) / 1e9
    return out


def rows(reduced: Dict[str, Any], stacks: Dict[str, str]
         ) -> Optional[List[Row]]:
    """The `device_scopes` table, longest row first; None where no event
    lies under a name of the program."""
    totals = {key: sum(ops.values())
              for key, ops in by_row(reduced, stacks).items()}
    if all(scope == UNSCOPED for _, scope in totals):
        return None
    busy = sum(totals.values())
    return sorted(
        ([phase, scope, seconds, 100.0 * seconds / busy]
         for (phase, scope), seconds in totals.items()),
        key=lambda row: -row[2])


def share(table: Sequence[Row], phase: Optional[str] = None,
          scope: Optional[str] = None) -> float:
    """Percent of busy time in the rows of that phase (any, if None) whose
    scope path holds `scope` as a component (any, if None)."""
    return sum(
        row[3] for row in table
        if (phase is None or row[0] == phase)
        and (scope is None or scope in row[1].split("/")))


def kernel_events(reduced: Dict[str, Any], stacks: Dict[str, str],
                  kernel: str) -> Tuple[int, float]:
    """(events, seconds of self time) of the Mosaic kernel the program
    named `kernel`, all devices. A kernel's event holds no other event, so
    it is one segment."""
    count, seconds = 0, 0.0
    for segments in reduced["segments"].values():
        for start, end, name in segments:
            if not name.endswith(PALLAS):
                continue
            scope = classify(stacks.get(name))[1]
            if scope.split("/")[-1] == kernel:
                count += 1
                seconds += (end - start) / 1e9
    return count, seconds


# ----------------------------------------------- the .xplane.pb's programs

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a varint
    or fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def name_stacks(xplane_path: str) -> Dict[str, str]:
    """`{event's short name: name stack}` of every operation the device
    planes know. XSpace.planes(1); of an XPlane name(2), event_metadata(4)
    and stat_metadata(5), both maps from an id(1) to a message(2); of an
    XEventMetadata name(2), the whole HLO line that `trace.short_name` cuts
    to the name a segment carries, and stats(5); of an XStat metadata_id(1)
    and str_value(5) or ref_value(7). The stat named `tf_op` is the
    instruction's `op_name` with `:` and an op type after it."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, str] = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, value in _fields(plane):
            if f == 2:
                name = _text(value)
            elif f == 4:
                events.append(dict(_fields(value)).get(2))
            elif f == 5:
                entry = dict(_fields(value))
                stat_names[entry[1]] = _text(dict(_fields(entry[2])).get(2, b""))
        if not trace.DEVICE_PLANE.match(name):
            continue
        for metadata in events:
            line, stack = "", ""
            for f, value in _fields(metadata or b""):
                if f == 2:
                    line = _text(value)
                elif f == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    stack = (_text(stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7), ""))
            out[trace.short_name(line)] = stack.rsplit(":", 1)[0]
    return out


# ------------------------------------------------------- for the readers

def stacks_for(run: Dict[str, Any]) -> Optional[Dict[str, str]]:
    """The name stacks that go with `run["trace"]`, where it carries them
    under `name_stacks`; None for an untraced run or a trace without."""
    return (run.get("trace") or {}).get("name_stacks")


def rows_for(run: Dict[str, Any]) -> Optional[List[Row]]:
    stacks = stacks_for(run)
    return rows(run["trace"], stacks) if stacks else None


# ------------------------------------------------------------ by hand

def reduced_with_stacks(path: str) -> Dict[str, Any]:
    """A `.xplane.pb` reduced as `run.py` reduces it, with its name stacks."""
    reduced = trace.reduce(trace.extract(path, loop.SPANS))
    reduced["name_stacks"] = name_stacks(path)
    return reduced


def run_of(lines: Sequence[Dict[str, Any]], reduced: Dict[str, Any]
           ) -> Dict[str, Any]:
    """What a reader is handed, rebuilt from the lines a run wrote to its
    standard output: the chunk readings and the last line's device."""
    device = lines[-1]["device"]
    chunks = [{"chunk": l["chunk"], "steps": l["steps"],
               "seconds": l["seconds"],
               "units": round(l["rate"] * l["seconds"])}
              for l in lines if "chunk" in l]
    return {"chunks": chunks, "chips": device["count"], "device": device,
            "trace": reduced}


def waiting_metrics(root: str, workload: str, run: Dict[str, Any]
                    ) -> Dict[str, Dict[str, Any]]:
    """The metrics under `chipbench/metrics/` that carry the `entry` they
    await in `BENCHMARK.json` and list `workload`, read from `run`."""
    out = {}
    for name in sorted(os.listdir(os.path.join(root, spec.PACKAGE, "metrics"))):
        held = spec.read_json(root, spec.PACKAGE, "metrics", name)
        entry = held.get("entry")
        if not entry or workload not in entry["workloads"]:
            continue
        value = spec.read_metric(root, entry["name"], run)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def main(argv: List[str]) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace", help="a run's trace directory, or a .xplane.pb")
    parser.add_argument("--ops", type=int, default=0, metavar="N",
                        help="the N longest operations of every row")
    parser.add_argument("--run", metavar="FILE", help="the run's standard "
                        "output; with --workload, the metrics that read scopes")
    parser.add_argument("--workload")
    args = parser.parse_args(argv)
    path = (args.trace if args.trace.endswith(".pb")
            else trace.find_xplane(args.trace))
    reduced = reduced_with_stacks(path)
    stacks = reduced["name_stacks"]
    line: Dict[str, Any] = {"info": "device_scopes",
                            "rows": rows(reduced, stacks) or []}
    if not line["rows"]:
        print("no operation lies under a scope of the program: a step loaded "
              "from the compile cache carries the names of the revision that "
              "compiled it; trace the run again with "
              "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=1", file=sys.stderr)
    if args.ops:
        line["ops"] = {
            f"{phase} {scope}": trace.top(ops, args.ops)
            for (phase, scope), ops in by_row(reduced, stacks).items()}
    print(json.dumps(line))
    if args.run and args.workload:
        with open(args.run) as f:
            lines = [json.loads(text) for text in f if text.strip()]
        print(json.dumps({"info": "scope_metrics", "metrics": waiting_metrics(
            spec.ROOT, args.workload, run_of(lines, reduced))}))


if __name__ == "__main__":
    main(sys.argv[1:])
