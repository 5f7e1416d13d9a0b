"""A grouped-matmul kernel's share of its roofline, in percent: the least
time the chip could take for the kernel's calls in the traced window over
the self time of the events that carry the kernel's name (`"event"`:
`moe_gmm` or `moe_tgmm`). The chip's compiler names a Mosaic custom call
after the `name=` of its `pallas_call`, so the events are found by name in
the reduced trace's segments (`moe_gmm.3 [tpu_custom_call]`); no name stack
is needed. A step calls the kernel once for each of `"products"`, a list of
[k, n] (for `moe_gmm` the forward products, the rematted forward's, and the
rows' gradients, whose k and n are swapped, which costs the same); the least
time of an event is the mean over that list (`moe_flops.gmm_call` on the
cell's rows a step, `"experts"` groups; per call the larger of operations
over the bf16 peak and bytes over the HBM peak). Rows a call: the step's
tokens over the cell's chips times `"experts_per_token"`. None where no
event carries the name."""

import re

from chipbench import flops, kernel_flops, moe_flops


def read(run, params):
    if not run.get("trace") or not run["chunks"]:
        return None
    named = re.compile(r"^%s(\.\d+)? \[tpu_custom_call\]$"
                       % re.escape(params["event"]))
    calls, seconds = 0, 0.0
    for segments in run["trace"]["segments"].values():
        for start, end, name in segments:
            if named.match(name):
                calls += 1
                seconds += (end - start) / 1e9
    if not calls or seconds <= 0:
        return None
    chunk = run["chunks"][0]
    rows = round(chunk["units"] / chunk["steps"] / run["chips"]
                 * params["experts_per_token"])
    peaks = flops.peaks_for(run["device"]["kind"])
    least = [kernel_flops.least_seconds(
        *moe_flops.gmm_call(rows, k, n, params["experts"]), peaks)[0]
        for k, n in params["products"]]
    return 100.0 * calls * (sum(least) / len(least)) / seconds
