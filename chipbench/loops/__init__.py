"""One module per model family: how the benchmark's generic loop
(`chipbench/loop.py`) builds that family's state and step from the program's
public pieces. A family module defines `build(config, traffic, devices)`,
which returns an object with

    mesh                      the `ray_tpu.parallel` mesh of the cell
    flops_per_unit            training operations per image or token
    tolerance                 {"loss_rel_err": .., "grad_rel_err": ..}: bounds on `check`'s keys
    init_params(key)          parameters on the device, one jitted call
    init_state(params)        the whole train state (optimizer included)
    step                      jitted (state, batch) -> (state, out)
    loss_of(out)              the loss array in a step's output
    to_device(raw)            a host batch (numpy columns) -> device batch
    check_batch(raw)          the device batch of the reference comparison
    state_shardings           the state's shardings: a pytree, or one for every leaf
    batch_shapes(rows)        a step's batch as shapes with shardings (AOT compiles)
    system_loss(params, batch)      the program's loss, as the step computes it
    reference_loss(params, batch)   the plain reference's
    check(params, batch)      the comparison's errors; `tolerance` names the
                              keys that are judged, the rest is information
"""
