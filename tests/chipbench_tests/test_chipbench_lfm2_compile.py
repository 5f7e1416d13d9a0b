"""The step of `lfm2moe.tokens8k` compiled at its real size for a described
v5e that is not attached: five unlike layers in two segments, the flash
kernels at heads of 64 over 8192 tokens, the grouped-matmul kernels inside the
loop over the chunks of held rows, and the whole state have to fit one chip's
15.75 GiB. Nothing runs,
so nothing here is a time or a result. The topology is described inside a
fixture, never at import."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import pytest

from chipbench import lfm2_flops, loop, spec

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


def test_lfm2_step_compiles_for_v5e_and_fits(v5e):
    cell = spec.load_cell(spec.ROOT, "lfm2moe.tokens8k")
    config, traffic = cell["config"], cell["traffic"]
    # "auto" asks the platform, which is the CPU here; on the chip it
    # resolves to the Pallas kernels. Steered here, not by the program.
    config["attention_impl"] = "pallas"
    family = spec.load_code(spec.ROOT, "loops", config["family"]).build(
        config, traffic, list(v5e[:1]))
    key = jax.eval_shape(lambda: loop.seed_key(0))
    made = jax.eval_shape(family.init_params, key)
    state = jax.eval_shape(family.init_state, made)
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
    n_params = lfm2_flops.lfm2_param_count(config)
    assert n_params == pytest.approx(469.3e6, rel=1e-3)
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * n_params, rel=0.01)
    text = compiled.as_text()
    assert "%moe_gmm" in text and "%moe_tgmm" in text
    assert "%flash_fwd" in text and "%flash_bwd_dkv" in text
    assert text.count("%moe_gmm") >= 8  # inside the loops over the chunks
    out = jax.eval_shape(family.step, state, batch)[1]
    assert out["expert_load"].shape == (4, 64)
    assert out["held_slots"].shape == out["dropped_slots"].shape == (4,)
    assert out["expert_bias_abs_max"].shape == ()
