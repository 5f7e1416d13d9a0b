"""The rule's arithmetic at a v5e's limit and the token cells' whole steps,
compiled for the chip without the chip (`tests/test_kernel_compile.py` has
the kernels alone; the two share the described topology and nothing else, so
that a step added here does not lengthen the file a worker walks the kernels
in). Each cell's step is compiled once a module (`token_steps`): with what
the rule keeps at the chip's limit it compiles and fits, and its text has
the kernels' calls the cell's layers should make."""

import jax
import jax.numpy as jnp
import pytest

from v5e_described import (  # noqa: F401 - the module's fixtures
    HBM_LIMIT, calls as _calls, token_cell_step as _token_cell_step,
    token_steps, v5e)


def test_what_the_rule_keeps_of_the_mixers_at_a_v5e_s_limit():
    """Arithmetic alone, `nemotron3nano.tokens8k` at 2 x 8192 tokens and a
    limit of 15.75 GiB. On the kernels' path the scan's part of a block's
    backward is the entering states and the columns and rows of dt and cum
    (0.75 GB) where the `jax.numpy` path holds [H, Q, Q] arrays (2.68 GB).
    The stack is one period of nine layers, which the rule walks a layer at
    a time (PR 54): its fullest moment is the last mixer's backward, with
    the names of the seven layers before it and no gradient but what the
    loops of the routed layers behind it have accumulated, which may wait
    for the optimizer (PR 73). With nothing kept the fullest moment is the
    first mixer's, behind which every routed layer's accumulators wait: the
    room is 2.79 GB and the rule keeps every name, 2.75 GB, with 1.59 GB
    left at the last mixer's moment; with the `jax.numpy` scan the first
    mixer's moment leaves 0.04 GB and the names end with `attn_qkv` (no
    cell runs that path). Since PR 63 the gated norm's kernels hold no
    float32 array of the mixer's width (`ops/mamba_passes.py`): 0.81 GB
    more room on their path."""
    from chipbench import spec
    from chipbench.loops import nemotron_h
    from ray_tpu.models import transformer as tr

    config = spec.load_cell(spec.ROOT, "nemotron3nano.tokens8k")["config"]
    tokens = 2 * 8192
    params = 4 * 666962944

    def kept(impl):
        cfg = nemotron_h.model_config(dict(config, attention_impl=impl))
        return cfg, tr.saved_activations(
            cfg, tokens, 3 * params, params, HBM_LIMIT)

    cfg, chosen = kept("pallas")
    terms = tr._terms(cfg, tokens, params)
    assert tr._scan_bytes_per_token(cfg) * tokens == 746586112
    assert terms.saved_bytes(chosen) == {
        "attn_ctx": 136314880, "attn_res": 88080384, "attn_qkv": 150994944,
        "mamba_in": 1350565888, "ssd_out": 536870912, "shared_up": 486539264}
    assert terms.fullest(chosen).name == "layer 7"
    assert terms.fullest().name == "layer 0"
    assert terms.room(3 * params, HBM_LIMIT) == 2786471936
    assert terms.room(3 * params, HBM_LIMIT, chosen) == 1588473856
    cfg, chosen = kept("xla")
    terms = tr._terms(cfg, tokens, params)
    assert tr._scan_bytes_per_token(cfg) * tokens == 64 * 128 * 20 * tokens
    assert terms.saved_bytes(chosen) == {
        "attn_ctx": 136314880, "attn_res": 88080384, "attn_qkv": 150994944}
    assert terms.room(3 * params, HBM_LIMIT) == 43397120


# ------------------------- the token cells' steps with what remat keeps

# after what the rule keeps at that limit: (flash_fwd calls in the text, the
# parent's; moe_gmm calls, the parent's). One scanned layer kind with
# attention in every cell; without `attn_ctx` the kernel runs in the second
# forward too, and `olmoe.tokens4k`'s gate and up products with it.
TOKEN_CELLS = {
    "mistral7b.tokens4k": ((1, 2), (0, 0)),
    "mistral7b.fsdp4": ((1, 2), (0, 0)),
    "olmoe.tokens4k": ((1, 2), (6, 8)),
    "lfm2moe.tokens8k": ((1, 2), (32, 32)),  # a share's layer has no names
    "nemotron3nano.tokens8k": ((1, 2), (20, 20)),  # a share, as above
}
# (`ssd_fwd`, `ssd_bwd`) calls in the text: four mixers, each run forward,
# forward again under remat (their `ssd_out` is kept since PR 54, but the
# backward wants the entering states, which only the forward kernel makes)
# and backward
SCAN_CALLS = {"nemotron3nano.tokens8k": (8, 4)}


@pytest.mark.parametrize("cell_name", list(TOKEN_CELLS))
def test_token_step_with_what_it_keeps_compiles_and_fits(
        token_steps, cell_name):
    """The reader patched to a v5e's limit (a described device reports
    none): the step the chip would run compiles, stays a GB under the limit
    by the compiler's own count, and runs the flash forward once a layer
    and the whole flash backward as one kernel."""
    step = token_steps(cell_name, limited=True)
    chosen, compiled = step.chosen, step.compiled
    assert next(iter(chosen)) == "attn_ctx" and "attn_res" in chosen
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            - memory.alias_size_in_bytes) <= HBM_LIMIT - 10**9
    text = compiled.as_text()
    (flash, _), (gmm, _) = TOKEN_CELLS[cell_name]
    assert _calls(text, "flash_fwd") == flash
    assert _calls(text, "flash_bwd_dkv_dq") == 1
    assert _calls(text, "flash_bwd_dq") == _calls(text, "flash_bwd_dkv") == 0
    assert _calls(text, "moe_gmm") == gmm
    if cell_name == "olmoe.tokens4k":
        assert {"moe_slots", "moe_gate", "moe_up"} <= set(chosen)
    scans = SCAN_CALLS.get(cell_name, (0, 0))
    assert (_calls(text, "ssd_fwd"), _calls(text, "ssd_bwd")) == scans
    if any(scans):
        assert "mamba_in" in chosen and "ssd_out" in chosen
        # a sequence's [n, H, Q, Q] of decays or masked scores is nowhere
        assert "f32[2,64,64,128,128]" not in text
        assert "bf16[2,64,64,128,128]" not in text


@pytest.mark.parametrize("cell_name", list(TOKEN_CELLS))
def test_token_step_without_a_limit_is_the_step_without_names(
        v5e, token_steps, monkeypatch, cell_name):
    """A described device reports no limit: nothing is chosen, no policy is
    passed, and the step lowers to the text of the program that has no
    names at all (the parent's, but for metadata)."""
    import re

    from ray_tpu.models import transformer as tr

    def text_of(lowered):
        # a function's name ends in a counter of the functions traced, and
        # a kernel's serialized body holds the locations it was traced at
        text = re.sub(r"@(\w+?)_\d+\b", r"@\1", lowered.as_text())
        return re.sub(r'backend_config = "[^"]*"', "", text)

    step = token_steps(cell_name, limited=False)
    assert step.chosen == {}
    monkeypatch.setattr(tr, "checkpoint_name", lambda x, name: x)
    without_names, _ = _token_cell_step(cell_name, v5e, monkeypatch)
    assert text_of(without_names) == text_of(step.lowered)


# ------------------- a block's weight matmuls from and to buffers of their own

# the scopes of a block's plain matmuls (docs/observability.md, "Device
# scopes"); the router's is float32 on purpose and is not `_own_weights`'
BLOCK_SCOPES = {"mlp", "attn_qkv", "attn_out", "short_conv", "latent_attention"}


def _computations(text):
    """{name: its lines} of a compiled module's text."""
    import re

    found, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            found[name] = []
        elif name is not None:
            found[name].append(line)
    return found


def _block_matmul_fusions(text):
    """(fusion's name, its result type, its fused computation's lines) of
    every fusion that holds a convolution under a block's scope."""
    import re

    computations = _computations(text)
    for lines in computations.values():
        for line in lines:
            fusion = re.match(
                r"\s*(?:ROOT )?%([\w.-]+) = (.*?) fusion\(.* calls=%([\w.-]+)",
                line)
            if not fusion:
                continue
            body = computations[fusion.group(3)]
            scopes = {
                part for inner in body if " convolution(" in inner
                for part in re.split(
                    r"[/()]", re.search(r'op_name="([^"]*)"', inner).group(1))}
            if scopes & BLOCK_SCOPES and "moe_router" not in scopes:
                yield fusion.group(1), fusion.group(2), body


@pytest.mark.parametrize("cell_name", ["lfm2moe.tokens8k", "mistral7b.tokens4k"])
def test_no_block_matmul_carries_an_update_of_the_state(
        token_steps, cell_name):
    """What PR 37 took out, held out: compiled for a v5e with what the rule
    keeps there, no fusion of a block's matmul also holds a dynamic update
    (the weight gradient written into the scanned stack) or writes more
    than one float32 array of a parameter's shape (AdamW's update of a
    one-layer segment's weight and moments, fused into its gradient's
    matmul). `mistral7b.tokens4k` had the first in 7 fusions of a layer,
    `lfm2moe.tokens8k` the second in 15."""
    import re

    step = token_steps(cell_name, limited=True)
    state_shapes = {
        ",".join(map(str, aval.shape))
        for aval in jax.tree.leaves(step.lowered.in_avals)
        if aval.dtype == jnp.float32 and aval.ndim >= 2}
    fusions = list(_block_matmul_fusions(step.compiled.as_text()))
    assert len(fusions) >= 25  # a layer's products, forward and backward
    for name, result, body in fusions:
        assert not any(" dynamic-update-slice(" in line for line in body), name
        written = [dims for dims in re.findall(r"f32\[([\d,]+)\]", result)
                   if dims in state_shapes]
        assert len(written) <= 1, (name, result)


@pytest.mark.timeout(600)  # a minute alone, three beside five busy workers
def test_a_share_s_rows_reach_their_tokens_by_moe_sum_in_mellum2_ep4(
        token_steps):
    """`mellum2.ep4`'s step compiled for four described v5e with the chip's
    limit handed to the keep rule: a layer's held rows are summed onto their
    tokens by `moe_sum`, once forward and once backward in each of four
    layers; no scatter-add and no float32 copy of the 180,224-row buffer is
    left under the combine or the dispatch; and the compiler plans no more
    memory than for the parent's step (8,507,300,352 bytes a chip, the same
    compile of PR 50's tree)."""
    import re

    compiled = token_steps("mellum2.ep4", limited=True).compiled
    text = compiled.as_text()
    assert _calls(text, "moe_sum") == 8
    under = [line for line in text.splitlines() if re.search(
        r'op_name="[^"]*(moe_combine|moe_dispatch)', line)]
    assert len(under) > 8
    assert not any(" scatter(" in line or " sort(" in line for line in under)
    assert not any("f32[180224,2304]" in line for line in under)
    assert compiled.memory_analysis().temp_size_in_bytes <= 8_507_300_352


def test_a_program_lowers_moe_sum_once_a_distinct_use(token_steps):
    """What a Pallas call site costs set-up (its body traced and lowered to
    Mosaic anew, 0.1 to 0.25 s of host time a site in every program that
    holds it, whether the executable then comes from the cache or not:
    PERF.md section 5) is paid once a distinct use, because `sum_held` is
    entered through one jitted function: `lfm2moe.tokens8k`'s lowered step
    holds two `moe_sum` payloads (the forward's weighted sum onto the
    loop's float32 buffer, the backward's of two buffers) where its
    compiled text calls the kernel eight times (four routed layers, once
    forward and once backward)."""
    import re

    step = token_steps("lfm2moe.tokens8k", limited=True)
    lowered = step.lowered.as_text()
    assert len(re.findall(r'kernel_name = "moe_sum"', lowered)) == 2
    assert len(re.findall(r"func\.func private @_sum_held", lowered)) == 2
    assert len(re.findall(r"call @_sum_held", lowered)) == 8
    assert _calls(step.compiled.as_text(), "moe_sum") == 8
