"""A KDA kernel's share of its roofline, in percent: the least time the chip
could take for the kernel's calls in the traced window (per call the larger
of operations over the bf16 peak and bytes over the HBM peak, by
`kimi_linear_flops.kda_call` on the cell's shapes) over the self time of the
events that carry the `pallas_call`'s name, found by name in the reduced
trace's segments (`kda_fwd.3 [tpu_custom_call]`), as `gmm_roofline` finds
its own. `"kernel"` is `kda_fwd` or `kda_bwd`; `"heads"`, `"seq_len"`,
`"dk"`, `"dv"` and `"chunk"` the configuration's and the mix's (a test holds
them equal). With `"remat"` a layer calls `kda_fwd` twice a step, once
without and once with the chunks' entering states written: the least time of
an event is the mean of the two. The sequences of one call are the step's
tokens over `seq_len` over the cell's chips. None where no event carries the
name."""

import re

from chipbench import flops, kernel_flops, kimi_linear_flops


def read(run, params):
    if not run.get("trace") or not run["chunks"]:
        return None
    named = re.compile(r"^%s(\.\d+)? \[tpu_custom_call\]$"
                       % re.escape(params["kernel"]))
    calls, seconds = 0, 0.0
    for segments in run["trace"]["segments"].values():
        for start, end, name in segments:
            if named.match(name):
                calls += 1
                seconds += (end - start) / 1e9
    if not calls or seconds <= 0:
        return None
    chunk = run["chunks"][0]
    sequences = round(
        chunk["units"] / chunk["steps"] / params["seq_len"] / run["chips"])
    peaks = flops.peaks_for(run["device"]["kind"])
    forms = ((True, False) if params["kernel"] == "kda_fwd" and params.get(
        "remat") else (True,))
    least = [kernel_flops.least_seconds(*kimi_linear_flops.kda_call(
        params["kernel"], sequences * params["heads"], params["seq_len"],
        params["dk"], params["dv"], params["chunk"], states), peaks)[0]
        for states in forms]
    return 100.0 * calls * (sum(least) / len(least)) / seconds
