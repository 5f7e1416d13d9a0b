"""The three step programs of the benchmark's cells, compiled at their real
sizes for a described v5e that is not attached: what the chip's compiler
would refuse (a kernel's tiling, a program that does not fit 16 GB) shows
here at no chip time. Nothing runs, so nothing here is a time or a result.
All such compiles of the benchmark are in this one file; the topology is
described inside a fixture, never at import."""

import math
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import pytest

from chipbench import loop, spec
from chipbench import traffic as traffic_lib

HBM_BYTES = 15.75 * 2**30  # what a v5e chip offers a program


@pytest.fixture(scope="module")
def v5e():
    """Four described v5e devices; the compile cache is off around the
    tests, because an entry compiled for a described device is written but
    cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def compiled_step(cell_name, devices):
    cell = spec.load_cell(spec.ROOT, cell_name)
    config, traffic = cell["config"], cell["traffic"]
    chips = cell["workload"]["chips"]
    if config.get("attention_impl") == "auto":
        # "auto" asks the platform, which is the CPU here; on the chip it
        # resolves to the Pallas kernel. Steered here, not by the program.
        config["attention_impl"] = "pallas"
    family = spec.load_code(spec.ROOT, "loops", config["family"]).build(
        config, traffic, list(devices[:chips]))
    key = jax.eval_shape(lambda: loop.seed_key(0))
    params = jax.eval_shape(family.init_params, key)
    state = jax.eval_shape(family.init_state, params)
    shardings = family.state_shardings
    if not isinstance(shardings, dict):  # one sharding for every leaf
        shardings = jax.tree.map(lambda _: family.state_shardings, state)
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, shardings)
    batch = family.batch_shapes(int(traffic["batch_rows"]))
    return cell, family, state, family.step.lower(state, batch).compile()


@pytest.mark.parametrize("cell_name", [
    "resnet50.ingest", "mistral7b.tokens4k", "mistral7b.fsdp4"])
def test_step_compiles_for_v5e_and_fits(v5e, cell_name):
    cell, family, state, compiled = compiled_step(cell_name, v5e)
    # That the compile returned is the proof that the step fits: the chip's
    # compiler refuses a program whose live memory passes 15.75 GiB (it
    # refused this transformer step with einsum attention at 29.67 GiB).
    # `temp_size_in_bytes` is an upper bound that is not all live at once,
    # so only the resident part is held against the chip here.
    memory = compiled.memory_analysis()
    print(cell_name, memory)
    assert memory.alias_size_in_bytes > 0.9 * memory.output_size_in_bytes
    assert (memory.argument_size_in_bytes
            + memory.generated_code_size_in_bytes) < HBM_BYTES
    text = compiled.as_text()
    if cell["config"]["family"] == "transformer":
        assert "tpu_custom_call" in text  # the flash kernel is in the step
    if cell["workload"]["chips"] == 4:
        assert "all-gather" in text and "reduce-scatter" in text
        share = cell["config"]["state_share"]
        leaves = jax.tree.leaves(state)
        whole = sum(x.size * x.dtype.itemsize for x in leaves)
        per_device = sum(
            math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
            for x in leaves)
        assert abs(per_device / whole - share["expected"]) < share["tolerance"]
