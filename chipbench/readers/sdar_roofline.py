"""A staircase kernel's share of its roofline as a block-diffusion layer
calls it, in percent: the least time the chip could take for the kernel's
calls in the traced window (per call the larger of operations over the bf16
peak and bytes over the HBM peak, by `sdar_flops.stair_call` on the cell's
shapes) over the self time of the events that carry the `pallas_call`'s
name, found by name in the reduced trace's segments (`flash_fwd_stair.3
[tpu_custom_call]`), as `eva_roofline` finds its own. `"event"` is the
`pallas_call`'s name, `"kernel"` the flash kernel without its suffix;
`"n_heads"`, `"n_kv_heads"`, `"d_head"`, `"diffusion_block"` and `"seq_len"`
are the configuration's and the mix's (a test holds them equal). None where
no event carries the name, as on the parent's trace."""

import re

from chipbench import flops, kernel_flops, sdar_flops


def read(run, params):
    if not run.get("trace") or not run.get("chunks"):
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
    ops, bytes_moved = sdar_flops.stair_call(
        params["kernel"], round(sequences), params["n_heads"],
        params["n_kv_heads"], params["seq_len"], params["diffusion_block"],
        params["d_head"], params["d_head"])
    least, _ = kernel_flops.least_seconds(
        ops, bytes_moved, flops.peaks_for(run["device"]["kind"]))
    return 100.0 * calls * least / seconds
