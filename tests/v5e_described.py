"""The chip's compiler without the chip, for the tests that compile for it
(`tests/test_kernel_compile.py`, `tests/test_step_compile.py`): libtpu
compiles for a described v5e topology that is not attached. A helper module
and not `conftest.py`: the fixture is theirs who import it, and a file that
does not never loads the compiler's library."""

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
