"""The step of `keyevl2.tokens16k` as the chip runs it, compiled once at its
real sizes for a described v5e that is not attached, with the keep rule
handed the chip's limit: the selection is made once a layer and its mask is
bits, kept with `attn_ctx` (`ops/sparse_attention.py`,
`models/transformer.py` `_SparseAttention`). Nothing runs, so nothing here is
a time or a result. A file of its own, so that `--dist loadfile` can place
its one compilation; the topology is described inside a fixture, never at
import."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import pytest

from chipbench import loop, spec
from ray_tpu.models import transformer as tr

CELL = "keyevl2.tokens16k"
CHIP_LIMIT = 16_909_336_064  # a v5e's `bytes_limit`, as its allocator reads
HBM_BYTES = 15.84e9  # what a v5e chip offers a program (PERF.md, "Units")
# o, lse and the indexer's gradients as the parent kept them, and a bit a
# (query, key) pair of one sequence, over six layers
ATTN_CTX = 1038090240 + 6 * 16384 * 16384 // 8


@pytest.fixture(scope="module")
def step():
    """(the compiled step, what the rule chose for it), the compile cache
    off around it (an entry compiled for a described device
    cannot be read back)."""
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
    chosen = []  # the bytes of what the rule kept, as the step's line has them
    rule = tr.saved_activations

    def recording(cfg, tokens, resident, params, limit, ways):
        kept = rule(cfg, tokens, resident, params, limit, ways)
        chosen.append(tr._terms(cfg, tokens, params, ways).saved_bytes(kept))
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
    yield compiled, chosen[0]
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", [
    "index_select", "flash_fwd_sparse", "index_loss",
    "flash_bwd_dkv_dq_sparse"])
def test_the_kernel_runs_once_a_layer(step, kernel):
    """`attn_ctx` kept: one instance in the scanned stack's program, the
    forward body's or the backward body's, and none made again."""
    text = step[0].as_text()
    assert len(set(re.findall(rf"%{kernel}\.\d+ = ", text))) == 1


@pytest.mark.parametrize("kernel", [
    "flash_bwd_dq_sparse", "flash_bwd_dkv_sparse"])
def test_no_kernel_makes_the_score_tiles_for_itself(step, kernel):
    """The backward is the one kernel (PR 49: its row-long dq, dk and dv
    leave VMEM a tile at a time): neither of the two that each made every
    tile stands in the program."""
    assert not re.search(rf"%{kernel}\.\d+ = ", step[0].as_text())


def test_the_mask_is_bits_and_nothing_is_a_sequence_squared(step):
    text = step[0].as_text()
    assert "s8[1,16,16384,128]" in text  # a layer's, key tiles of 1,024
    assert "s8[6,1,16,16384,128]" in text  # and the stack's, kept
    assert not re.search(r"s8\[(\d+,)*16384,1024\]", text)
    assert not re.search(r"(f32|bf16)\[(\d+,)*16384,16384\]", text)


def test_attn_ctx_is_kept_with_the_mask_s_bits(step):
    """What the step's "train step under remat keeps {...}" line names."""
    assert list(step[1].items()) == [("attn_ctx", ATTN_CTX)]


def test_the_plan_fits_what_a_v5e_offers_a_program(step):
    """The compiler's plan with `attn_ctx` kept: 15.49 GB at its fullest,
    where the parent's read 15.56 (the chip's peak stood 0.09 GB over
    that)."""
    memory = step[0].memory_analysis()
    assert memory.alias_size_in_bytes > 0.9 * memory.output_size_in_bytes
    assert 15.0e9 < memory.peak_memory_in_bytes <= HBM_BYTES - 0.05e9
