"""The train step over EVA attention layers: at a small size on the CPU (two
steps of `make_train_step`: a finite loss, the layers' two readings, one
compilation), and the step of `evabyte.tokens8k` as the chip runs it,
compiled once at its real sizes for a described v5e that is not attached,
with the keep rule handed the chip's limit: what the rule keeps, its sum
beside the compiler's plan, the kernels a layer's calls lower to, and no
score tensor in the program. Nothing of the second half runs, so nothing
there is a time or a result. A file of its own, so that `--dist loadfile`
can place its one compilation; the topology is described inside a fixture,
never at import."""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest

from chipbench import loop, spec
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as tr
from ray_tpu.parallel import make_mesh

CELL = "evabyte.tokens8k"
CHIP_LIMIT = 16_909_336_064  # a v5e's `bytes_limit`, as its allocator reads
HBM_BYTES = 15.84e9  # what a v5e chip offers a program (PERF.md, "Units")
STATE = 12 * 821_366_784  # float32 weights and AdamW's two moments


@pytest.mark.parametrize("scan_layers", [True, False])
def test_two_steps_at_a_small_size(scan_layers):
    """Walked or scanned: the same loss to rounding, the two readings a
    layer, and the second step runs the first's program."""
    cfg = TransformerConfig(
        vocab_size=40, d_model=64, n_layers=2, n_heads=4, d_ff=96,
        max_seq_len=64, layer_types=("eva_attention",) * 2, eva_window=16,
        eva_chunk=4, n_pred_heads=3, norm_unit_offset=True, norm_eps=1e-5,
        tied_embeddings=False, init_std=0.02, remat=True,
        attention_impl="xla", scan_layers=scan_layers)
    assert [(len(s.layout), s.periods) for s in tr.segments(cfg)] == (
        [(1, 2)] if scan_layers else [(2, 1)])
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    init_state, step, _ = make_train_step(cfg, mesh)
    state = init_state(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64 + 3), 0, 40)
    tokens, targets = tr.next_ids(ids, 3)
    batch = {"tokens": tokens, "targets": targets}
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **_: compiles.append(event) if event == (
            "/jax/core/compile/backend_compile_duration") else None)
    losses = []
    for _ in range(2):
        state, out = step(state, batch)
        losses.append(float(out["loss"]))
        assert set(out) == {"loss", "grad_norm", "eva_remote_mass",
                            "eva_chunk_entropy"}
        assert out["eva_remote_mass"].shape == (2,)
        assert out["eva_chunk_entropy"].shape == (2,)
    assert len(compiles) == 1
    assert all(math.isfinite(x) for x in losses) and losses[1] < losses[0]
    assert abs(losses[0] - math.log(40)) < 0.1  # three heads over 40 ids
    assert 0.2 < float(out["eva_remote_mass"][0]) < 0.6
    assert float(out["eva_chunk_entropy"][0]) <= math.log(4) + 1e-6


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
        assert batch["targets"].shape == (1, 8192, 8)
        compiled = family.step.lower(state, batch).compile()
    yield (compiled, *chosen[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def test_the_plan_fits_under_the_rule_s_sum(step):
    """The compiler's plan with the rule's choice kept stands under what a
    v5e offers a program, and the rule's sum for that choice (the state and
    its fullest moment, the last layer's backward) stands at or over the
    plan: the rule errs to the full side."""
    compiled, kept, rule_sum = step
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes > 0.9 * memory.output_size_in_bytes
    assert memory.argument_size_in_bytes > STATE
    plan = memory.peak_memory_in_bytes
    assert 0.75 * 16.91e9 < plan <= HBM_BYTES - 0.05e9
    assert plan <= rule_sum <= CHIP_LIMIT - tr._SAVE_RESERVE
    assert list(kept) == ["attn_ctx", "eva_summaries", "attn_res",
                          "attn_qkv", "mlp_gate", "mlp_up"]  # every name
    assert kept["eva_summaries"] == 4 * 2 * 512 * 4096 * 2


def test_a_layer_is_three_kernels_forward_and_three_backward(step):
    """Four walked layers: with the kernels' residuals and the summaries
    kept no forward kernel runs a second time."""
    text = step[0].as_text()
    calls = {name: len(set(re.findall(rf"%{name}\.\d+ = ", text)))
             for name in ("eva_summaries_fwd", "eva_summaries_bwd",
                          "flash_fwd", "flash_fwd_stair", "flash_bwd_dkv_dq",
                          "flash_bwd_dkv_dq_stair")}
    assert calls == dict.fromkeys(calls, 4)
    # the windows folded into the batch, q, k and o with the heads folded in
    # too; v where the model holds it, `[B, T, H D]`, and so the summaries'
    # values (their keys are the staircase's k): no v lies folded
    assert "bf16[128,2048,128]" in text and "bf16[32,512,128]" in text
    calls = re.findall(
        r"%flash_fwd(?:_stair)?\.\d+ = \(.*?\) custom-call\(.*?\), "
        r"custom_call_target=\"tpu_custom_call\", "
        r"operand_layout_constraints=\{(.*?)\}, frontend", text)
    assert len(calls) == 8
    assert {tuple(re.findall(r"bf16\[[\d,]+\]", call)) for call in calls} == {
        ("bf16[128,2048,128]", "bf16[128,2048,128]", "bf16[4,2048,4096]"),
        ("bf16[32,8192,128]", "bf16[32,512,128]", "bf16[1,512,4096]")}


def test_no_score_tensor_is_in_the_compiled_step(step):
    """Neither a window against itself, nor the queries against the
    summaries, nor the sequence against itself; and the head's logits are a
    chunk's, float32."""
    text = step[0].as_text()
    for pairs in ("2048,2048", "8192,512", "8192,8192", "2048,512",
                  "2048,384"):
        assert not re.search(r"\[(\d+,)*%s\]" % pairs, text), pairs
    assert "f32[2048,2560]" in text or "f32[2048,8,320]" in text
    assert not re.search(r"\[(1,)?8192,(2560|8,320)\]", text)
