"""The step of `lagunaxs2.tokens8k` compiled at its real sizes for a
described v5e that is not attached: a dense layer under full attention and
a period of three window-512 layers and a full one in one segment (48 or 64
query heads over 8 key-value heads through the flash kernels with and
without the window, the per-head gate, routed layers with the
grouped-matmul kernels inside the loop over the chunks of held rows) lower
and compile, the state is 12 bytes a parameter, and the compiler's plan
fits what a v5e offers a program. Nothing runs, so nothing here is a time
or a result. The topology is described inside a fixture, never at import."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import pytest

from chipbench import laguna_flops, loop, spec

HBM_BYTES = 15.84e9  # what a v5e chip offers a program (PERF.md section 2)


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


def test_laguna_step_compiles_for_v5e(v5e):
    cell = spec.load_cell(spec.ROOT, "lagunaxs2.tokens8k")
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
    # 12 bytes a parameter of state: weights and AdamW's two moments; the
    # gradients are in the program's scratch
    n_params = laguna_flops.laguna_param_count(config)
    assert n_params == 691623936
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * n_params, rel=0.01)
    assert memory.temp_size_in_bytes > 4 * n_params
    # with no device to ask for its limit the step keeps nothing beside the
    # blocks' inputs: the plan, state and scratch, fits the chip
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < HBM_BYTES)
    text = compiled.as_text()
    assert "%flash_fwd_window" in text and "%flash_bwd_dkv_dq_window" in text
    assert "%flash_fwd." in text or "%flash_fwd " in text  # the full layers'
    assert "%flash_bwd_dkv_dq." in text or "%flash_bwd_dkv_dq " in text
    assert "%moe_gmm" in text and "%moe_tgmm" in text
    # 2 sequences of 64 and of 48 query heads at 8192 tokens, 128 wide
    assert "bf16[128,8192,128]" in text and "bf16[96,8192,128]" in text
    out = jax.eval_shape(family.step, state, batch)[1]
    assert out["expert_load"].shape == (4, 256)
    assert out["held_slots"].shape == out["dropped_slots"].shape == (4,)
    assert out["aux_loss"].shape == ()
