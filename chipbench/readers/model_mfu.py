"""Model FLOP/s utilization, in percent: the operations forward and
backward need per image or token (the configuration's shapes, recompute not
counted) times the run's throughput (all work over the whole window), over
the cell's chips times the chip's published bf16 peak. An end-to-end
utilization, not a kernel's roofline share."""

from chipbench import chunks, flops


def read(run, params):
    if not run["chunks"] or run["window_s"] <= 0:
        return None
    peak = flops.peaks_for(run["device"]["kind"])["bf16_flops_per_s"]
    rate = chunks.total_rate(run["chunks"], run["window_s"])
    return 100.0 * run["flops_per_unit"] * rate / (run["chips"] * peak)
