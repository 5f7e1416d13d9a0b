"""A kernel of the Mamba-2 scan's share of its roofline as a mixer of the
`granite_hybrid` family calls it (one group of B and C for all its heads, a
tile of the heads a grid step), in percent: the least time the chip could
take for the kernel's calls in the traced window (per call the larger of
operations over the bf16 peak and bytes over the HBM peak, by
`granite_hybrid_flops.scan_call` on the cell's shapes, every operand once)
over the self time of the events that carry the `pallas_call`'s name, found
by name in the reduced trace's segments (`ssd_bwd.7 [tpu_custom_call]`), as
`sdar_roofline` finds its own. `"event"` is the `pallas_call`'s name;
`"mamba_heads"`, `"mamba_head_dim"`, `"ssm_groups"`, `"ssm_state"`,
`"ssd_chunk"`, `"heads_a_tile"` and `"seq_len"` are the configuration's, the
program's (`ops/ssd.py` `head_tile`) and the mix's (a test holds them
equal). None where no event carries the name, as on a trace of a cell
without the mixer."""

import re

from chipbench import flops, granite_hybrid_flops, kernel_flops


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
    ops, bytes_moved = granite_hybrid_flops.scan_call(
        params["event"], params, round(sequences), params["seq_len"],
        params["heads_a_tile"])
    least, _ = kernel_flops.least_seconds(
        ops, bytes_moved, flops.peaks_for(run["device"]["kind"]))
    return 100.0 * calls * least / seconds
