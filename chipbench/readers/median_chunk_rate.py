"""The rate of the window's steady part: the median of its chunk readings
(work over seconds of each chunk), in images or tokens per second. A stall
does not move it; the run's throughput (`total_rate`) is what a stall
moves."""

from chipbench import chunks


def read(run, params):
    return chunks.median_rate(run["chunks"]) if run["chunks"] else None
