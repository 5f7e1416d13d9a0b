"""A kernel's share of its roofline as an EVA attention layer calls it, in
percent: the least time the chip could take for the kernel's calls in the
traced window (per call the larger of operations over the bf16 peak and
bytes over the HBM peak, by `evabyte_flops.py` on the cell's shapes) over
the self time of the events that carry the `pallas_call`'s name, found by
name in the reduced trace's segments (`flash_fwd_stair.3
[tpu_custom_call]`), as `diff_flash_roofline` finds its own. `"event"` is
the `pallas_call`'s name; `"part"` says which of the layer's calls it is and
so which count applies: `"summaries"` (`eva_summaries_fwd`, `_bwd`: one pass
over k and v), `"window"` (the causal flash kernels on the windows folded
into the batch) or `"stair"` (the flash kernels under the staircase over
the summaries); `"kernel"` the flash kernel without a suffix, or the
summaries' own name; `"d_model"`, `"n_heads"`, `"eva_window"`, `"eva_chunk"`
and `"seq_len"` the configuration's and the mix's (a test holds them
equal). None where no event carries the name."""

import re

from chipbench import evabyte_flops, flops, kernel_flops

_CALLS = {"summaries": evabyte_flops.summaries_call,
          "window": evabyte_flops.window_call,
          "stair": evabyte_flops.stair_call}


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
    sequences = chunk["units"] / chunk["steps"] / params["seq_len"] / run["chips"]
    ops, bytes_moved = _CALLS[params["part"]](
        params["kernel"], params, params["seq_len"], round(sequences))
    least, _ = kernel_flops.least_seconds(
        ops, bytes_moved, flops.peaks_for(run["device"]["kind"]))
    return 100.0 * calls * least / seconds
