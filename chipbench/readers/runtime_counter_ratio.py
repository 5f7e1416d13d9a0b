"""A sum of the program's own counters over another, from the block
`ray_tpu_runtime` that every `train.report` carries and the run's record
holds as the last report left it (docs/observability.md, "The train path").
The counters are `counters_since_first_report`'s: the steady state, set-up's
steps left out, as `since_first_report` is for the spans. `"counters"` names
those summed above the line and `"over"` those below it; `"scale"` (100 for
a percentage) multiplies the ratio. 0 where the record has no such block,
the block no such counters (a program older than they are) or the
denominator counted nothing, as `runtime_span_seconds` reads 0 for a span
that was never seen."""

from chipbench.readers import runtime_span_seconds


def read(run, params):
    block = run.get(runtime_span_seconds.BLOCK) or {}
    counters = block.get("counters_since_first_report") or {}
    above, below = (sum(counters.get(name, 0) for name in params[side])
                    for side in ("counters", "over"))
    return float(params.get("scale", 1)) * above / below if below else 0.0
