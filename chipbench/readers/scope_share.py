"""Device self time of the events in a phase of the step (`"phase"`:
forward, recompute, backward, optimizer or unnamed) and under a scope or kernel name
of the program (`"scope"`, matched against the components of an event's
scope path: `bn` matches `stage2/bn`), in percent of the devices' busy
time. Either parameter may be left out. `chipbench/scopes.py` has the
rules; the name stacks are the reduced trace's `name_stacks`. None where it
has none, or where the program sets no scope."""

from chipbench import scopes


def read(run, params):
    table = scopes.rows_for(run)
    if table is None:
        return None
    return scopes.share(table, params.get("phase"), params.get("scope"))
