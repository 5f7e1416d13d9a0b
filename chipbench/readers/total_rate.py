"""The run's throughput: all the work of the window over all of its time,
stalls and the time between chunks included, in images or tokens per
second."""

from chipbench import chunks


def read(run, params):
    if not run["chunks"] or run["window_s"] <= 0:
        return None
    return chunks.total_rate(run["chunks"], run["window_s"])
