"""A flash kernel's share of its roofline as a differential layer calls it,
in percent: grouped heads AND two widths (q and k one, the values another),
which `gqa_flash_roofline` (one width) and `window_roofline` (no groups)
each lack. The least time the chip could take for the kernel's calls in the
traced window (per call the larger of operations over the bf16 peak and
bytes over the HBM peak, `phi4flash_flops.flash_call` on the cell's shapes)
over the self time of the events that carry the kernel's name, found by name
in the reduced trace's segments (`flash_bwd_dkv.4 [tpu_custom_call]`), as
`gqa_flash_roofline` finds its own. `"event"` is the `pallas_call`'s name
(`flash_fwd`, `flash_fwd_window`), `"kernel"` the kernel without a suffix,
`"window"` the band or null, `"n_heads"`, `"n_kv_heads"`, `"qk_dim"`,
`"v_dim"` and `"seq_len"` the configuration's and the mix's (a test holds
them equal). None where no event carries the name."""

import re

from chipbench import flops, kernel_flops, phi4flash_flops


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
    ops, bytes_moved = phi4flash_flops.flash_call(
        params["kernel"], round(sequences), params["n_heads"],
        params["n_kv_heads"], params["seq_len"], params.get("window"),
        params["qk_dim"], params["v_dim"])
    least, _ = kernel_flops.least_seconds(
        ops, bytes_moved, flops.peaks_for(run["device"]["kind"]))
    return 100.0 * calls * least / seconds
