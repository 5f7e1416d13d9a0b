"""A windowed flash kernel's share of its roofline, in percent: the least
time the chip could take for the kernel's calls in the traced window (per
call the larger of operations over the bf16 peak and bytes over the HBM
peak, `laguna_flops.window_flash_call` on the cell's shapes: the band's
pairs, every operand and result once) over the self time of the events that
carry the kernel's name. The chip's compiler names a Mosaic custom call
after the `name=` of its `pallas_call`, so the events are found by name in
the reduced trace's segments (`flash_fwd_window.3 [tpu_custom_call]`), as
`gmm_roofline` finds its own; no name stack is needed. `"kernel"` is the
kernel without its suffix (`flash_fwd`, `flash_bwd_dkv_dq`), `"n_heads"` the
query heads of a layer under the window, `"window"`, `"qk_dim"`, `"v_dim"`
and `"seq_len"` the configuration's and the mix's (a test holds them equal);
the sequences of one call are the step's tokens over `seq_len` over the
cell's chips. None where no event carries the name."""

import re

from chipbench import flops, kernel_flops, laguna_flops


def read(run, params):
    if not run.get("trace") or not run["chunks"]:
        return None
    named = re.compile(r"^%s_window(\.\d+)? \[tpu_custom_call\]$"
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
    sequences = chunk["units"] / chunk["steps"] / params["seq_len"] / run["chips"]
    ops, bytes_moved = laguna_flops.window_flash_call(
        params["kernel"], round(sequences * params["n_heads"]),
        params["seq_len"], params["window"], params["qk_dim"], params["v_dim"])
    least, _ = kernel_flops.least_seconds(
        ops, bytes_moved, flops.peaks_for(run["device"]["kind"]))
    return 100.0 * calls * least / seconds
