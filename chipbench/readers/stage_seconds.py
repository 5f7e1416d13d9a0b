"""A stage's wall time, as the worker's host clock read it."""


def read(run, params):
    return run["stages"].get(params["stage"])
