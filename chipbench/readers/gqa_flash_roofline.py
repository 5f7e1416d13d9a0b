"""A flash kernel's share of its roofline at grouped heads, in percent: the
least time the chip could take for the kernel's calls in the traced window
(per call the larger of operations over the bf16 peak and bytes over the HBM
peak, `mellum_flops.flash_call` on the cell's shapes: the pairs the mask
leaves, q, o, do and dq at the query heads, k, v, dk and dv at the key-value
heads, each once) over the self time of the events that carry the kernel's
name. The chip's compiler names a Mosaic custom call after the `name=` of its
`pallas_call`, so the events are found by name in the reduced trace's
segments (`flash_fwd.3 [tpu_custom_call]`, `flash_fwd_window.3
[tpu_custom_call]`), as `window_roofline` finds its own. `"event"` is the
`pallas_call`'s name, `"kernel"` the kernel without a suffix (`flash_fwd`,
`flash_bwd_dkv_dq`), `"window"` the band or null, `"n_heads"`,
`"n_kv_heads"`, `"head_dim"` and `"seq_len"` the configuration's and the
mix's (a test holds them equal); the sequences of one call are the step's
tokens over `seq_len` over the cell's chips. None where no event carries the
name."""

import re

from chipbench import flops, kernel_flops, mellum_flops


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
    ops, bytes_moved = mellum_flops.flash_call(
        params["kernel"], round(sequences), params["n_heads"],
        params["n_kv_heads"], params["seq_len"], params.get("window"),
        params["head_dim"])
    least, _ = kernel_flops.least_seconds(
        ops, bytes_moved, flops.peaks_for(run["device"]["kind"]))
    return 100.0 * calls * least / seconds
