"""A flash kernel's share of its roofline, in percent: the least time the
chip could take for the kernel's calls in the traced window (per call the
larger of operations over the bf16 peak and bytes over the HBM peak,
`kernel_flops.flash_call` on the cell's shapes) over the self time of the
events that carry the kernel's name (`"kernel"`). `n_heads`, `head_dim` and
`seq_len` are the configuration's and the mix's (a test holds them equal);
the sequences of one call are the step's tokens over `seq_len` over the
cell's chips, each chip's call covering its own shard of the batch. None
where no event carries the name."""

from chipbench import flops, kernel_flops, scopes


def read(run, params):
    stacks = scopes.stacks_for(run)
    if not stacks or not run["chunks"]:
        return None
    calls, seconds = scopes.kernel_events(
        run["trace"], stacks, params["kernel"])
    if not calls or seconds <= 0:
        return None
    chunk = run["chunks"][0]
    sequences = chunk["units"] / chunk["steps"] / params["seq_len"] / run["chips"]
    ops, bytes_moved = kernel_flops.flash_call(
        params["kernel"], round(sequences * params["n_heads"]),
        params["seq_len"], params["head_dim"])
    least, _ = kernel_flops.least_seconds(
        ops, bytes_moved, flops.peaks_for(run["device"]["kind"]))
    return 100.0 * calls * least / seconds
