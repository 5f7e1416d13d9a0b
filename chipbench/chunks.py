"""The arithmetic of a measured window: all work over all of its time, the
chunk readings and their median, and what lies between the two.

A window is cut into chunks of consecutive steps. The loop waits for the
device only at a chunk's end and reads the host clock there, so a chunk's
reading is its work over its seconds. The run's throughput is all the work
of the window over all of its time (`total_rate`): a user pays for the
window, stalls included. The median of the chunk readings is the rate of the
steady part, a per-layer metric; `stall_share` is the part of the window's
time the median does not account for.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Sequence


def chunk_rates(chunks: Sequence[Dict[str, Any]]) -> List[float]:
    """Work over seconds of every chunk: `units` is images or tokens."""
    return [c["units"] / c["seconds"] for c in chunks]


def median_rate(chunks: Sequence[Dict[str, Any]]) -> float:
    if not chunks:
        raise ValueError("a window without a finished chunk has no rate")
    return statistics.median(chunk_rates(chunks))


def total_rate(chunks: Sequence[Dict[str, Any]], window_s: float) -> float:
    """All the work of the window over all of its time, the gaps between
    chunks (report, bookkeeping) included."""
    return sum(c["units"] for c in chunks) / window_s


def stall_share(chunks: Sequence[Dict[str, Any]], window_s: float) -> float:
    """One minus total work over the window, over the median chunk rate:
    0 when every second of the window ran at the median rate, 0.1 when a
    tenth of the window went to stalls the median does not show."""
    return 1.0 - total_rate(chunks, window_s) / median_rate(chunks)
