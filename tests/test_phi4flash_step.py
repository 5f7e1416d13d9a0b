"""The step of `phi4flash.tokens16k` as the chip runs it, compiled once at
its real sizes for a described v5e that is not attached, with the keep rule
handed the chip's limit: what the rule keeps, its sum beside the compiler's
plan, the kernels a differential layer's paired call lowers to, and the
carried values in the program. Nothing runs, so nothing here is a time or a
result. A file of its own, so that `--dist loadfile` can place its one
compilation; the topology is described inside a fixture, never at import."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import pytest

from chipbench import loop, spec
from ray_tpu.models import transformer as tr

CELL = "phi4flash.tokens16k"
CHIP_LIMIT = 16_909_336_064  # a v5e's `bytes_limit`, as its allocator reads
HBM_BYTES = 15.84e9  # what a v5e chip offers a program (PERF.md, "Units")
STATE = 12 * 697_094_272  # float32 weights and AdamW's two moments


@pytest.fixture(scope="module")
def step():
    """(the compiled step, what the rule chose for it, the rule's sum for
    that choice), the compile cache off around it (an entry compiled for a
    described device cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    cell = spec.load_cell(spec.ROOT, CELL)
    config, traffic = cell["config"], cell["traffic"]
    # "auto" asks the platform, which is the CPU here: steered in the test
    config["attention_impl"] = "pallas"
    chosen = []
    rule = tr.saved_activations

    def recording(cfg, tokens, resident, params, limit, ways):
        kept = rule(cfg, tokens, resident, params, limit, ways)
        terms = tr._terms(cfg, tokens, params, ways)
        chosen.append((terms.saved_bytes(kept),
                       resident + terms.fullest(kept).bytes))
        return kept

    with pytest.MonkeyPatch.context() as patch:
        # a described device reports no limit: the chip's is handed over
        patch.setattr(tr, "_memory_limit", lambda mesh: CHIP_LIMIT)
        patch.setattr(tr, "saved_activations", recording)
        family = spec.load_code(spec.ROOT, "loops", config["family"]).build(
            config, traffic, list(devices[:1]))
        key = jax.eval_shape(lambda: loop.seed_key(0))
        state = jax.eval_shape(
            family.init_state, jax.eval_shape(family.init_params, key))
        state = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            state, family.state_shardings)
        batch = family.batch_shapes(int(traffic["batch_rows"]))
        compiled = family.step.lower(state, batch).compile()
    yield (compiled, *chosen[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def test_the_plan_fits_under_the_rule_s_sum(step):
    """The compiler's plan with the rule's choice kept stands under what a
    v5e offers a program, and the rule's sum for that choice (the state and
    its fullest moment) stands at or over the plan: the rule errs to the
    full side."""
    compiled, kept, rule_sum = step
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes > 0.9 * memory.output_size_in_bytes
    assert memory.argument_size_in_bytes > STATE
    plan = memory.peak_memory_in_bytes
    assert 0.25 * 16.91e9 < plan <= HBM_BYTES - 0.05e9
    assert plan <= rule_sum <= CHIP_LIMIT - tr._SAVE_RESERVE
    assert next(iter(kept)) == "attn_ctx"


def test_a_differential_layer_is_one_paired_call(step):
    """Three layers call the kernels (one under the window, two whole): a
    forward a layer, made again only where `attn_ctx` is not kept, at 40
    query heads over 20 key heads 64 wide and values 128 wide."""
    text = step[0].as_text()
    kept = step[1]
    calls = {name: len(set(re.findall(rf"%{name}\.\d+ = ", text)))
             for name in ("flash_fwd", "flash_fwd_window")}
    again = 1 if "attn_ctx" in kept else 2
    assert calls == {"flash_fwd": 2 * again, "flash_fwd_window": again}
    assert "bf16[40,16384,64]" in text    # q: the pairs' first, then second
    assert "bf16[20,16384,64]" in text    # the paired keys
    assert "bf16[20,16384,128]" in text   # V, twice
    assert not re.search(r"(f32|bf16)\[(\d+,)*16384,16384\]", text)


def test_every_attention_layer_s_backward_is_the_one_kernel(step):
    """Heads of 64 under a group at T 16,384: the row's blocks leave the
    one kernel no tile, and its sums alone do since the row-long gradients
    leave a tile at a time padded to whole lanes (PR 75). `flash_bwd_dkv_dq`
    once a whole layer, `flash_bwd_dkv_dq_window` once, and neither of the
    pair that made every score tile twice; dq and dk leave 128 columns wide
    in whole tiles of rows and the program takes the 64."""
    text = step[0].as_text()
    calls = {name: len(set(re.findall(rf"%{name}\.\d+ = ", text)))
             for name in ("flash_bwd_dkv_dq", "flash_bwd_dkv_dq_window",
                          "flash_bwd_dq", "flash_bwd_dq_window",
                          "flash_bwd_dkv", "flash_bwd_dkv_window")}
    assert calls == {"flash_bwd_dkv_dq": 2, "flash_bwd_dkv_dq_window": 1,
                     "flash_bwd_dq": 0, "flash_bwd_dq_window": 0,
                     "flash_bwd_dkv": 0, "flash_bwd_dkv_window": 0}
    for made in re.findall(
            r"%flash_bwd_dkv_dq(?:_window)?\.\d+ = \((.*?)\) custom-call",
            text):
        assert re.findall(r"bf16\[[\d,]+\]", made) in (
            ["bf16[20,16896,128]"] * 2 + ["bf16[40,16384,128]"],   # 1024 x 768
            ["bf16[20,16384,128]"] * 2 + ["bf16[40,16384,128]"])   # 512 x 512


def test_no_array_of_a_sequence_s_states_nor_of_a_chunk_s_steps(step):
    """The scan's chunks: 128 entering states a layer (`[b, T / chunk, N,
    inner]`, as the kernels' forward writes them); never a chunk's 128
    steps (`[chunk, b, N, inner]`, which the `jax.numpy` backward stacks to
    HBM three times a chunk: 54 arrays of it in PR 61's text), never 16,384
    states."""
    text = step[0].as_text()
    assert re.search(r"f32\[1,128,16,5120\]", text)
    assert not re.search(r"f32\[128,1,16,5120\]", text)
    assert not re.search(r"f32\[(\d+,)*16384,(1,)?16,5120\]", text)
    assert not re.search(r"f32\[(\d+,)*16384,(1,)?5120,16\]", text)


# what the compiler planned for PR 61's step, whose scan was `jax.numpy`
# (builder's, PR 62: the same compile of the parent's tree), bytes
PLAN_BEFORE_THE_KERNELS = {"peak": 12_284_951_040, "temp": 4_744_045_056}


def test_the_two_mamba_layers_scan_by_the_kernels(step):
    """`selective_scan_fwd` twice and `selective_scan_bwd` twice, a layer
    each: `scan_out` is kept, its entering states with it, so the block
    made again runs no second forward scan; and the plan stands no higher
    than with the `jax.numpy` scan, the names set aside that the rule keeps
    since its sum was set right (PR 73: `mamba1_in` and `gmu_in`, 0.84
    GB)."""
    compiled, kept, _ = step
    assert "scan_out" in kept
    text = compiled.as_text()
    calls = {name: len(set(re.findall(rf"%{name}\.\d+ = ", text)))
             for name in ("selective_scan_fwd", "selective_scan_bwd")}
    assert calls == {"selective_scan_fwd": 2, "selective_scan_bwd": 2}
    memory = compiled.memory_analysis()
    since = kept["mamba1_in"] + kept["gmu_in"]
    assert since == 5 * 16384 * 5120 * 2
    assert memory.peak_memory_in_bytes - since <= 1.01 * (
        PLAN_BEFORE_THE_KERNELS["peak"])
    # (its scratch grows by 1.15 GB for them: with more held through the
    # backward the compiler schedules the feed-forwards' products apart)
    assert memory.temp_size_in_bytes - since <= (
        PLAN_BEFORE_THE_KERNELS["temp"] + 0.35e9)
