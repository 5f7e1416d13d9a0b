"""Device self time of the events whose names match `patterns`, in percent
of the devices' busy time (`"over": "busy"`) or of the traced window
(`"over": "window"`: for collectives, the time they are exposed)."""

from chipbench import trace


def read(run, params):
    if not run.get("trace"):
        return None
    share = trace.share(run["trace"], params["patterns"], params["over"])
    return None if share is None else 100.0 * share
