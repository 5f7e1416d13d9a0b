"""What the fullest chip holds while the window runs, from `memory_stats()`
read after the window (`loop.held_in_window`: live arrays plus the loaded
step's reserved scratch), in GB (1e9 bytes)."""


def read(run, params):
    peak = run.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
