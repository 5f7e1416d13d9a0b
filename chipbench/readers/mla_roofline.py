"""A flash kernel's share of its roofline where q and k are one width and v
another, in percent: `kernel_roofline`'s reading with the operations and
bytes of `mla_flops.flash_call` (`"qk_dim"`, `"v_dim"` beside `"kernel"`,
`"n_heads"` and `"seq_len"`, the configuration's and the mix's; a test holds
them equal). None where no event carries the kernel's name."""

from chipbench import flops, kernel_flops, mla_flops, scopes


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
    ops, bytes_moved = mla_flops.flash_call(
        params["kernel"], round(sequences * params["n_heads"]),
        params["seq_len"], params["qk_dim"], params["v_dim"])
    least, _ = kernel_flops.least_seconds(
        ops, bytes_moved, flops.peaks_for(run["device"]["kind"]))
    return 100.0 * calls * least / seconds
