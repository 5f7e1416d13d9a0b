"""The step of `olmoe.tokens4k` compiled at its real size for a described
v5e that is not attached: the grouped-matmul kernels' tiles, the f32 router
and the whole state of one OLMoE layer have to fit one chip's 15.75 GiB.
Nothing runs, so nothing here is a time or a result. The topology is
described inside a fixture, never at import."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import pytest

from chipbench import loop, spec

HBM_BYTES = 15.75 * 2**30  # what a v5e chip offers a program


@pytest.fixture(scope="module")
def v5e():
    """Described v5e devices; the compile cache is off around the test (an
    entry compiled for a described device cannot be read back)."""
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


def test_olmoe_step_compiles_for_v5e_and_fits(v5e):
    cell = spec.load_cell(spec.ROOT, "olmoe.tokens4k")
    config, traffic = cell["config"], cell["traffic"]
    # "auto" asks the platform, which is the CPU here; on the chip it
    # resolves to the Pallas kernels. Steered here, not by the program.
    config["attention_impl"] = "pallas"
    family = spec.load_code(spec.ROOT, "loops", config["family"]).build(
        config, traffic, list(v5e[:1]))
    key = jax.eval_shape(lambda: loop.seed_key(0))
    params = jax.eval_shape(family.init_params, key)
    state = jax.eval_shape(family.init_state, params)
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, family.state_shardings)
    batch = family.batch_shapes(int(traffic["batch_rows"]))
    compiled = family.step.lower(state, batch).compile()
    memory = compiled.memory_analysis()
    print(memory)
    assert memory.alias_size_in_bytes > 0.9 * memory.output_size_in_bytes
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.generated_code_size_in_bytes) < HBM_BYTES
    # 12 bytes a parameter of state: weights and AdamW's two moments
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * 625_616_896, rel=0.01)
    text = compiled.as_text()
    # forward, rematted forward and the rows' gradients; the weights' gradients
    assert text.count("%moe_gmm") >= 9 and text.count("%moe_tgmm") >= 3
    assert "%flash_fwd" in text and "%flash_bwd_dkv" in text
    out = jax.eval_shape(family.step, state, batch)[1]
    assert out["expert_load"].shape == (1, 64)
