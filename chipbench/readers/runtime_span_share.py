"""How near a span of the program comes to setting the step's pace, in
percent: the span's seconds a time (`since_first_report` of the block
`ray_tpu_runtime`: the steady state, set-up left out) over the median
chunk's seconds a step. For `data.batch_produce`, the prefetch thread's
time to make one batch (block fetch, assembly, the copy to the device; its
wait for room in the queue excluded): 100 means ingest sets the pace, 2
that it has fifty times the room. `"span"` names the span. 0 where the
record has no such block or the span was never seen."""

import statistics

from chipbench.readers import runtime_span_seconds


def read(run, params):
    block = run.get(runtime_span_seconds.BLOCK) or {}
    row = (block.get("since_first_report") or {}).get(params["span"])
    if not row or not row[0] or not run["chunks"]:
        return 0.0
    step_s = statistics.median(c["seconds"] / c["steps"] for c in run["chunks"])
    return 100.0 * (row[1] / row[0]) / step_s
