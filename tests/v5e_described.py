"""The chip's compiler without the chip, for the tests that compile for it
(`tests/test_kernel_compile.py`, `tests/test_step_compile.py`,
`tests/test_step_compile_walked.py`): libtpu compiles for a described v5e
topology that is not attached, and a token cell's whole step is lowered and
compiled for it once a module (`token_steps`). A helper module and not
`conftest.py`: the fixtures are theirs who import them, and a file that does
not never loads the compiler's library."""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import pytest


@pytest.fixture(scope="module")
def v5e():
    """Four described v5e devices, with the compile cache off around the
    tests: an entry compiled for a described device is written but cannot be
    read back without a chip, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


# ------------------------- the token cells' steps with what remat keeps

HBM_LIMIT = int(15.75 * 2**30)  # a v5e's `bytes_limit`, to the GiB's hundredth


def token_cell_step(cell_name, devices, monkeypatch):
    """(lowered step of the cell at its real shapes on described devices,
    what the rule chose while it was traced), as `tr` stands patched."""
    from chipbench import loop, spec
    from ray_tpu.models import transformer as tr

    cell = spec.load_cell(spec.ROOT, cell_name)
    config, traffic = cell["config"], cell["traffic"]
    # "auto" asks the platform, which is the CPU here: steered in the test
    config["attention_impl"] = "pallas"
    chosen = []
    rule = tr.saved_activations

    def recording(*args):
        chosen.append(rule(*args))
        return chosen[-1]

    monkeypatch.setattr(tr, "saved_activations", recording)
    family = spec.load_code(spec.ROOT, "loops", config["family"]).build(
        config, traffic, list(devices[:cell["workload"]["chips"]]))
    key = jax.eval_shape(lambda: loop.seed_key(0))
    state = jax.eval_shape(
        family.init_state, jax.eval_shape(family.init_params, key))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, family.state_shardings)
    batch = family.batch_shapes(int(traffic["batch_rows"]))
    lowered = family.step.lower(state, batch)
    monkeypatch.setattr(tr, "saved_activations", rule)
    return lowered, chosen[0]


class Step:
    """A cell's step lowered once and compiled at most once, for every test
    of this module that reads it."""

    def __init__(self, lowered, chosen):
        self.lowered, self.chosen = lowered, chosen

    @functools.cached_property
    def compiled(self):
        return self.lowered.compile()


@pytest.fixture(scope="module")
def token_steps(v5e):
    """`step_of(cell, limited)`: the cell's `Step`, with the limit's reader
    patched to a v5e's (a described device reports none) where `limited`;
    one lowering and one compilation a (cell, limited) among the tests."""
    from ray_tpu.models import transformer as tr

    made = {}

    def step_of(cell_name, limited):
        if (cell_name, limited) not in made:
            with pytest.MonkeyPatch.context() as patch:
                if limited:
                    patch.setattr(tr, "_memory_limit", lambda mesh: HBM_LIMIT)
                made[cell_name, limited] = Step(
                    *token_cell_step(cell_name, v5e, patch))
        return made[cell_name, limited]

    return step_of


def calls(text, kernel):
    import re

    return len(re.findall(rf"%{kernel}(\.\d+)? = ", text))
