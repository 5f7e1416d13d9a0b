"""The step of `dsv2lite.tokens8k` and the flash kernels at its two widths,
compiled at their real sizes for a described v5e that is not attached: q
and k 192 wide go to Mosaic as they are, the six latent-attention layers in
two segments with the grouped-matmul kernels inside the loop over the chunks
of held rows lower and compile, and the state is 12 bytes a parameter.
Nothing runs, so nothing here is a time or a result. Nor does this compile
answer whether the step fits: without a device to ask, the compiler plans
11.3 GB of scratch beside the 7.6 GB of state, and for the attached chip it
plans 7.8 GB and the step runs (PERF.md section 6, PR 34). The topology is
described inside a fixture, never at import."""

import importlib
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench import dsv2_flops, loop, spec

fa = importlib.import_module("ray_tpu.ops.flash_attention")

HBM_BYTES = 15.75 * 2**30  # what a v5e chip offers a program
BH, T, DQK, DV = 4 * 16, 8192, 192, 128


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


@pytest.mark.parametrize("name", ["fwd_lse", "bwd_dq", "bwd_dkv"])
def test_flash_kernel_compiles_at_two_widths(v5e, name):
    """The shape's own tiles, with the VMEM limit `flash_tiles` derives for
    q and k at two tiles of lanes."""
    one = SingleDeviceSharding(v5e[0])

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    qk, vo = sd((BH, T, DQK)), sd((BH, T, DV))
    row = sd((BH, T, 8), jnp.float32)
    kernel = dict(causal=True, scale=0.114721, block_q=None, block_k=None,
                  interpret=False)
    fn = {
        "fwd_lse": lambda q, k, v, do, lse, delta: fa._flash_fwd(
            q, k, v, with_lse=True, **kernel),
        "bwd_dq": lambda *a: fa._flash_bwd_dq(*a, **kernel),
        "bwd_dkv": lambda *a: fa._flash_bwd_dkv(*a, **kernel),
    }[name]
    compiled = jax.jit(fn).lower(qk, qk, vo, vo, row, row).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    out = jax.eval_shape(fn, qk, qk, vo, vo, row, row)
    widths = [x.shape[-1] for x in jax.tree.leaves(out)]
    assert widths == {"fwd_lse": [DV, 8], "bwd_dq": [DQK],
                      "bwd_dkv": [DQK, DV]}[name]


def test_dsv2_step_compiles_for_v5e(v5e):
    cell = spec.load_cell(spec.ROOT, "dsv2lite.tokens8k")
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
    # 12 bytes a parameter of state: weights and AdamW's two moments; with
    # the gradients, 16: 10.17 GB, which leaves a program 5.7 GB
    n_params = dsv2_flops.dsv2_param_count(config)
    assert n_params == 635466752
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * n_params, rel=0.01)
    assert memory.temp_size_in_bytes > 4 * n_params  # the gradients are in it
    assert 16 * n_params < HBM_BYTES - 5e9
    text = compiled.as_text()
    assert "%moe_gmm" in text and "%moe_tgmm" in text
    assert "%flash_fwd" in text and "%flash_bwd_dq" in text
    assert "%flash_bwd_dkv" in text
    assert "bf16[64,8192,192]" in text and "bf16[64,8192,128]" in text
    out = jax.eval_shape(family.step, state, batch)[1]
    assert out["expert_load"].shape == (5, 64)
    assert out["held_slots"].shape == out["dropped_slots"].shape == (5,)
    assert out["aux_loss"].shape == ()
