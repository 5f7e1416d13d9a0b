"""The step of `solaropen2.tokens8k` as the chip runs it, compiled once at its
real sizes for a described v5e that is not attached, with the keep rule
handed the chip's limit: KDA's recurrence is the Pallas kernels `kda_fwd`
and `kda_bwd` behind a `custom_vjp` (`ops/kda.py`), the solve's custom call
is gone, the mixers' short convolutions are the kernels `kda_conv_fwd` and
`kda_conv_bwd` (`ops/mamba_passes.py`; PR 67), their output norms and gates
`kda_out_norm_fwd` and `kda_out_norm_bwd` (PR 69), and the rule prices the path
that runs (`models/transformer.py` `_KDA.holds`). Nothing runs, so nothing here is a time or a result. A file
of its own, so that `--dist loadfile` can place its one compilation; the
topology is described inside a fixture, never at import."""

import logging
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import pytest

from chipbench import loop, spec
from ray_tpu.models import transformer as tr
from ray_tpu.util import tracing

CELL = "solaropen2.tokens8k"
CHIP_LIMIT = 16_909_336_064  # a v5e's `bytes_limit`, as its allocator reads
HBM_BYTES = 15.84e9  # what a v5e chip offers a program (PERF.md, "Units")
KEPT = ("attn_ctx", "attn_res", "attn_qkv", "kda_res", "kda_qkv",
        "shared_gate", "shared_up")


@pytest.fixture(scope="module")
def step():
    """(the compiled step, the names the rule kept for it, the rule's sum
    for them: the state with the fullest of its moments; the step's log
    line and what its trace added to the counters), the compile cache
    off around it (an entry compiled for a described device cannot be read
    back)."""
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
        fullest = tr._terms(cfg, tokens, params, ways).fullest(kept)
        chosen.append((tuple(kept), resident + fullest.bytes, fullest.name))
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
        lines, before = [], tracing.counters()
        handler = logging.Handler()
        handler.emit = lambda record: lines.append(record.getMessage())
        logger = logging.getLogger("ray_tpu.models.transformer")
        level = logger.level
        logger.addHandler(handler)
        logger.setLevel(logging.INFO)
        try:
            compiled = family.step.lower(state, batch).compile()
        finally:
            logger.removeHandler(handler)
            logger.setLevel(level)
        counted = {name: n - before.get(name, 0)
                   for name, n in tracing.counters().items()}
    yield compiled, chosen[0], [
        line for line in lines if line.startswith("train step")], counted
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel,calls", [("kda_fwd", 6), ("kda_bwd", 3)])
def test_the_recurrence_is_the_kernels(step, kernel, calls):
    """Three KDA layers, none scanned: a forward each, the forward made
    again under remat (the rule keeps the layers' names, not the kernel's
    residuals) and a backward each."""
    text = step[0].as_text()
    assert len(set(re.findall(rf"%{kernel}\.\d+ = ", text))
               | set(re.findall(rf"%{kernel} = ", text))) == calls


@pytest.mark.parametrize("kernel,calls", [
    ("kda_conv_fwd", 18), ("kda_conv_bwd", 9)])
def test_the_short_convolutions_are_the_kernels(step, kernel, calls):
    """q's, k's and v's a layer: a forward each, made again, and a backward
    each; no float32 array of the streams' width under the scope and no
    padded copy is left of the `jax.numpy` lines. The step's line and its
    counters say so: a count a call a trace, and the three layers, one
    period each of one kind, share one trace of their block."""
    text = step[0].as_text()
    assert len(set(re.findall(rf"%{kernel}\.\d+ = ", text))
               | set(re.findall(rf"%{kernel} = ", text))) == calls
    assert not [line for line in text.splitlines()
                if "/kda_conv/" in line and "= f32[1,8192,1024]" in line]
    assert not re.search(r"bf16\[1,8195,1024\]", text)
    line, = step[2]
    assert ("; KDA's short convolutions: 3 calls by the kernels kda_conv_fwd "
            "and kda_conv_bwd, 0 by jax.numpy") in line
    assert step[3]["train.kda_conv_calls_kernels"] == 3
    assert not step[3].get("train.kda_conv_calls_numpy")


@pytest.mark.parametrize("kernel,calls", [
    ("kda_out_norm_fwd", 6), ("kda_out_norm_bwd", 3)])
def test_the_output_norms_and_gates_are_the_kernels(step, kernel, calls):
    """One call a layer: a forward, made again, and a backward; no float32
    array of the mixer's width under `kda_out` and no copy of one to or
    from the layout of `[B, T, H, dk]` is left of the `jax.numpy` lines;
    the step's line and its counters say so, a count a mixer a trace."""
    text = step[0].as_text()
    assert len(set(re.findall(rf"%{kernel}\.\d+ = ", text))
               | set(re.findall(rf"%{kernel} = ", text))) == calls
    assert not [line for line in text.splitlines()
                if "/kda_out/" in line
                and re.search(r"= f32\[(1,8192,1024|1,8192,8,128|"
                              r"1024,8,8,128)\]", line)]
    line, = step[2]
    assert line.endswith(
        "; KDA's output norms and gates: 1 calls by the kernels "
        "kda_out_norm_fwd and kda_out_norm_bwd, 0 by jax.numpy")
    assert step[3]["train.kda_out_norm_calls_kernels"] == 1
    assert not step[3].get("train.kda_out_norm_calls_numpy")


def test_the_solve_s_custom_call_is_gone(step):
    text = step[0].as_text()
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert "triangular-solve" not in text


def test_nothing_of_a_pair_tensor_s_shape_reaches_hbm(step):
    """`[.., SUB, SUB, dk]` float32 a sub-chunk, 537 MB a layer, was four
    times the parent's plan."""
    assert not re.search(r"f32\[(\d+,)*16,16,(8,)?128\]", step[0].as_text())


def test_the_rule_keeps_every_name(step):
    """What the step's "train step under remat keeps {...}" line names: as
    the parent, so the rule's choice is no second source of the gain."""
    assert step[1][0] == KEPT


def test_the_plan_fits_what_a_v5e_offers_a_program(step):
    """The compiler's plan with the seven names kept: 13.69 GB at its
    fullest since the mixers' output norms and gates are kernels (PR 69:
    the chip's peak followed, 15.263 -> 14.095 GB), where it read 15.00 (PR
    60) and the `jax.numpy` recurrence's 15.36 (PERF.md section 6, PR 55)."""
    memory = step[0].memory_analysis()
    assert memory.alias_size_in_bytes > 0.9 * memory.output_size_in_bytes
    assert 13.2e9 < memory.peak_memory_in_bytes <= 14.2e9 < HBM_BYTES


def test_the_rule_s_sum_beside_the_plan(step):
    """The rule prices KDA's backward at what the kernels leave in HBM
    (0.34 GB a layer for the `jax.numpy` form's 2.97), and since PR 73 the
    held experts' float32 gradient accumulators of the routed layers
    behind a layer with its own: in the plan all four layers' wait for the
    optimizer at the step's end (12 buffers of 168 MB live through the
    first layer's backward, the plan's fullest position), where `_terms`
    counted one layer's, 0.50 GB, and its fullest moment was the
    optimizer's, 13.45 GB, 0.23 GB under the plan and 0.64 under the
    chip. The fullest moment is the first layer's backward now, 14.19 GB:
    over the plan and 0.10 GB over the chip's peak (14.095 GB; the step's
    code is 0.24 GB of that)."""
    memory = step[0].memory_analysis()
    _, rules_sum, moment = step[1]
    assert moment == "layer 0"
    assert 14.1e9 < rules_sum < 14.4e9
    assert 0.0 < rules_sum - memory.peak_memory_in_bytes < 0.8e9
