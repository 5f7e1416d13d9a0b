"""Seconds the program's own spans counted under one name, from the block
`ray_tpu_runtime` that every `train.report` carries and the run's record
holds as the last report left it (docs/observability.md, "The train path").
`"span"` names the span; `"table"` says whose table and which part of it:
`"driver"` (the parent's spans since its process began: `init`, `train.*`),
`"total"` (the worker's since its session began) or `"setup"` (the worker's
before its first report: `total` less `since_first_report`, which leaves a
later epoch's restart or a recompile out of a set-up reading). 0 where the
record has no such block or the table no such span: a program older than
the spans, as a trace without a kernel reads 0 for the kernel's share."""

BLOCK = "ray_tpu_runtime"


def seconds(table, span):
    row = (table or {}).get(span)
    return float(row[1]) if row else 0.0


def read(run, params):
    block = run.get(BLOCK) or {}
    span = params["span"]
    if params["table"] == "setup":
        return max(0.0, seconds(block.get("total"), span)
                   - seconds(block.get("since_first_report"), span))
    return seconds(block.get(params["table"]), span)
