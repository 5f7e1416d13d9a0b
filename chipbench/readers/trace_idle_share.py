"""One minus the union of device-operation intervals over the traced
window, mean over the devices, in percent."""


def read(run, params):
    reduced = run.get("trace")
    if not reduced or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
