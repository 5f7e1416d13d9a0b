"""What the median of chunk readings hides, in percent of the window:
one minus total work over the window, over the median chunk rate."""

from chipbench import chunks


def read(run, params):
    if not run["chunks"]:
        return None
    return 100.0 * chunks.stall_share(run["chunks"], run["window_s"])
