#!/usr/bin/env python3
"""The bytes a compiled step moves through HBM under each named scope, with
no chip: read from XLA's dump of the step the compiler planned.

    JAX_PLATFORMS=cpu python3 benchmarks/lowering_seconds.py <cell> --repeat 1 --plan DIR
    python3 benchmarks/scope_bytes.py DIR mamba_conv mamba_norm [--largest 5]

DIR's `*jit_step*after_optimizations.txt` holds the program as it runs: what
crosses HBM is the operands and the result of every instruction that is not
inside a fusion (a fusion, a copy, a custom call: a Pallas kernel), and
nothing inside one does. A line a scope (the scope is a part of the
instruction's `op_name`: `jax.named_scope`'s) and phase (`forward`; `again`,
under `rematted_computation`; `backward`, under `transpose(jvp`): the
instructions, their bytes, and the milliseconds those take at HBM's rate,
which is the floor under the scope's time on the chip: the chip's own
fusions reach 67 to 80 % of it (PERF.md section 6, PR 63). `--largest` names
a scope's largest instructions. A while's body is counted once, whatever
its trips, and an operand that two instructions read twice: it is an upper
estimate where a fusion reads a slice of its operand.
"""

import argparse
import collections
import glob
import json
import os
import re
import sys

HBM_BYTES_PER_S = 819e9  # a v5e's, as `chipbench/peaks.json` has it
ITEM = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
        "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
# instructions that move nothing themselves: their operands' and results'
# bytes are those of the instructions around them
FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
        "while", "conditional", "call", "after-all", "partition-id",
        "replica-id", "iota"}
SHAPE = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(ITEM))
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[\w.-]+) = (.*?) ([\w-]+)\((.*?)\)(?:, |$)")


def shape_bytes(text: str) -> int:
    """The bytes of every array shape written in `text`."""
    total = 0
    for dtype, dims in SHAPE.findall(text):
        size = ITEM[dtype]
        for dim in filter(None, dims.split(",")):
            size *= int(dim)
        total += size
    return total


def phase_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "again"
    return "backward" if "transpose(jvp" in op_name else "forward"


def instructions(text: str):
    """(name, opcode, bytes of operands and result, op_name) of every
    instruction that stands outside a fusion and moves bytes."""
    fused = set(re.findall(r"calls=(%[\w.-]+)", text))
    outside, sizes = False, {}
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = re.match(r"(?:ENTRY )?(%[\w.-]+)", line)
            outside, sizes = bool(name) and name.group(1) not in fused, {}
            continue
        found = INSTRUCTION.match(line) if outside else None
        if not found:
            continue
        name, shape, opcode, operands = found.groups()
        sizes[name] = shape_bytes(shape)
        if opcode in FREE:
            continue
        moved = sizes[name] + sum(
            sizes.get(operand, 0)
            for operand in re.findall(r"%[\w.-]+", operands))
        op_name = re.search(r'op_name="([^"]*)"', line)
        yield name, opcode, moved, op_name.group(1) if op_name else ""


def by_scope(text: str, scopes):
    """{scope: {phase: [(bytes, name, opcode), ...]}} of the instructions
    whose `op_name` has the scope as one of its parts."""
    out = {scope: collections.defaultdict(list) for scope in scopes}
    for name, opcode, moved, op_name in instructions(text):
        parts = op_name.split("/")
        for scope in scopes:
            if scope in parts:
                out[scope][phase_of(op_name)].append((moved, name, opcode))
    return out


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("dump", help="the directory `--plan` wrote")
    parser.add_argument("scopes", nargs="+")
    parser.add_argument("--largest", type=int, default=0)
    args = parser.parse_args()
    found = sorted(glob.glob(os.path.join(
        args.dump, "*jit_step*after_optimizations.txt")))
    if not found:
        sys.exit(f"no *jit_step*after_optimizations.txt under {args.dump}")
    with open(found[-1]) as f:
        text = f.read()
    for scope, phases in by_scope(text, args.scopes).items():
        for phase in ("forward", "again", "backward"):
            moved = sorted(phases.get(phase, []), reverse=True)
            total = sum(m[0] for m in moved)
            line = {"scope": scope, "phase": phase, "instructions": len(moved),
                    "bytes": total,
                    "ms_at_hbm_rate": round(total / HBM_BYTES_PER_S * 1e3, 3)}
            if args.largest:
                line["largest"] = [
                    {"name": name, "opcode": opcode, "bytes": size}
                    for size, name, opcode in moved[:args.largest]]
            print("SCOPE " + json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
