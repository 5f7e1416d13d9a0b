"""Host-clock time the loop spent inside one of its spans, in percent of
the window."""


def read(run, params):
    seconds = run["spans"].get(params["span"])
    if seconds is None or run["window_s"] <= 0:
        return None
    return 100.0 * seconds / run["window_s"]
