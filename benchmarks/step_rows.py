#!/usr/bin/env python3
"""What the routing did in each step of a cell's run, beside the seconds of
the chunk the step ran in: the account the steps keep of themselves
(`ray_tpu/util/tracing.py` `Step`, `ray_tpu/train/_runtime.py`
`_fold_steps`; docs/observability.md, "The train path"), which every
`train.report` carries in its block `ray_tpu_runtime` and `chipbench.run`
does not print.

    python3 benchmarks/step_rows.py --workload nemotron3nano.tokens8k \\
        --seed 7 --seconds 10 --trace 0 [--rows rows.json]

Runs the cell as `python3 -m chipbench.run` does, with the same arguments
and the same last line on stdout (it fails at once without the cell's
chips), and with one function of `run.py` wrapped: `summary_of`, which sees
every report. For each chunk it writes to stderr the chunk's seconds a
step, the interval's spans `train.step`, `train.report` and `py.gc` (count
and seconds) and, of a share of the experts, each of its steps' row
`[step, chunks a layer, held rows a layer]`; the same and the steady
window's `moe.*` counters so far as JSON to `--rows`. A step's number is the program's own count of
its calls: the compile's two, the warm-up's, then `steps_per_chunk` a
chunk.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run, spec  # noqa: E402

BLOCK = "ray_tpu_runtime"


def chunk_lines(history, traffic):
    """A record a chunk report: its seconds a step, the steady window's
    counters as that report left them, and the rows of the steps it ran."""
    before = 2 + int(traffic["warmup_steps"])  # the compile's, the warm-up's
    per_chunk = int(traffic["steps_per_chunk"])
    rows = {}
    for metrics in history:  # a later report repeats a row: once each
        for row in (metrics.get(BLOCK) or {}).get("steps") or []:
            rows[row[0]] = row
    lines = []
    for metrics in history:
        if metrics.get("summary"):
            continue
        block = metrics.get(BLOCK) or {}
        first = before + metrics["chunk"] * per_chunk + 1
        lines.append({
            "chunk": metrics["chunk"],
            "ms_a_step": 1e3 * metrics["seconds"] / metrics["steps"],
            "steps": [rows[n] for n in range(first, first + per_chunk)
                      if n in rows],
            "counters_since_first_report": {
                k: v for k, v in (block.get(
                    "counters_since_first_report") or {}).items()
                if k.startswith(("moe.", "train.steps_read"))},
            # what the host did between the report before and this one
            **{name: (block.get("interval") or {}).get(name)
               for name in ("train.step", "train.report", "py.gc")},
        })
    return lines


def main(argv):
    rows_path = None
    if "--rows" in argv:
        at = argv.index("--rows")
        rows_path = os.path.abspath(argv[at + 1])
        argv = argv[:at] + argv[at + 2:]
    workload = argv[argv.index("--workload") + 1]
    traffic = spec.load_cell(spec.ROOT, workload)["traffic"]
    summary_of = run.summary_of

    def seen(result):
        summary = summary_of(result)
        lines = chunk_lines(result.metrics_history, traffic)
        for line in lines:
            print("chunk %(chunk)d: %(ms_a_step).2f ms a step" % line,
                  *(f"{name} {line[name][:2]}" for name in (
                      "train.step", "train.report", "py.gc") if line[name]),
                  file=sys.stderr)
            for number, chunks, held in line["steps"]:
                print(f"  step {number}: chunks {chunks} held rows {held}",
                      file=sys.stderr)
        last = (summary.get(BLOCK) or {})
        print("since the first report:", json.dumps(
            last.get("counters_since_first_report")), file=sys.stderr)
        print("the last step's readings:", json.dumps(last.get("readings")),
              file=sys.stderr)
        if rows_path:
            with open(rows_path, "w") as f:
                json.dump({"workload": workload, "chunks": lines,
                           "readings": last.get("readings"),
                           "spans": summary["spans"],
                           "window_s": summary["window_s"]}, f)
        return summary

    run.summary_of = seen
    run.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])
