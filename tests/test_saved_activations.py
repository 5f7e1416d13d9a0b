"""What a rematerialised block keeps (`models/transformer.py`
`saved_activations`): the rule as a pure function on the token cells' shapes,
and on the CPU, with the memory reader patched to a limit the CPU does not
report, tiny models whose step keeps every name: the same loss, gradients
and parameters as the step that keeps nothing, and no second product of what
was kept under `rematted_computation`."""

import collections
import functools
import importlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as tr
from ray_tpu.parallel import make_mesh

fa = importlib.import_module("ray_tpu.ops.flash_attention")
FLASH = fa.flash_attention

LIMIT = int(15.75 * 2**30)  # what a v5e chip offers a program
CELLS = ("mistral7b.tokens4k", "mistral7b.fsdp4", "olmoe.tokens4k",
         "lfm2moe.tokens8k", "dsv2lite.tokens8k", "nemotron3nano.tokens8k",
         "lagunaxs2.tokens8k", "keyevl2.tokens16k", "mellum2.ep4",
         "solaropen2.tokens8k", "phi4flash.tokens16k", "evabyte.tokens8k",
         "kimilinear.tokens16k", "sdar.tokens16k")
# the cells whose routed layers run over an `expert` mesh axis, and its size
EXPERT_WAYS = {"mellum2.ep4": 4}
# `bytes_limit` as the chip reports it (PERF.md, "Units"), and the names
# each token cell's step keeps there, in the rule's order
CHIP_LIMIT = 16_909_336_064
KEPT = {
    "mistral7b.tokens4k": ("attn_ctx", "attn_res"),
    "mistral7b.fsdp4": ("attn_ctx", "attn_res", "attn_qkv", "mlp_gate"),
    "olmoe.tokens4k": ("attn_ctx", "moe_slots", "attn_res", "attn_qkv",
                       "moe_gate", "moe_up"),
    "lfm2moe.tokens8k": ("attn_ctx", "attn_res", "conv_res", "attn_qkv",
                         "conv_in", "mlp_gate", "mlp_up"),
    "dsv2lite.tokens8k": (),
    "nemotron3nano.tokens8k": ("attn_ctx", "attn_res", "attn_qkv",
                               "mamba_in", "ssd_out", "shared_up"),
    "lagunaxs2.tokens8k": ("attn_ctx", "attn_res", "attn_qkv", "shared_gate",
                           "shared_up", "mlp_gate", "mlp_up"),
    "keyevl2.tokens16k": ("attn_ctx",),
    "mellum2.ep4": ("attn_ctx", "attn_res", "attn_qkv"),
    "solaropen2.tokens8k": ("attn_ctx", "attn_res", "attn_qkv", "kda_res",
                            "kda_qkv", "shared_gate", "shared_up"),
    # six walked layers: `mlp_gate` (2.01 GB) is refused, its plan stands
    # at 15.38 GB (PERF.md section 6, "Keep rule: the moments")
    "phi4flash.tokens16k": ("attn_ctx", "attn_res", "attn_qkv", "scan_out",
                            "mamba1_in", "gmu_in"),
    # four walked layers, every name: the fullest moment is the last
    # layer's backward
    "evabyte.tokens8k": ("attn_ctx", "eva_summaries", "attn_res", "attn_qkv",
                         "mlp_gate", "mlp_up"),
    # a dense KDA layer and one period of four walked layers: every name,
    # the fullest moment the last layer's backward (KDA_PLAN)
    "kimilinear.tokens16k": ("attn_ctx", "attn_res", "attn_qkv", "kda_res",
                             "kda_qkv", "shared_gate", "shared_up",
                             "mlp_gate", "mlp_up"),
    # four scanned layers on a stream of two rows a token: `attn_qkv`
    # (1.34 GB) is refused by 0.05 GB; its plan fits, at 15.24 GB, and the
    # chip runs it 2.0 % slower (PERF.md section 6, PR 73)
    "sdar.tokens16k": ("attn_ctx", "attn_res"),
}


def cell_shapes(cell_name):
    """(cfg, the stream's rows a device, state bytes a device, parameter
    bytes a device, the size of its `expert` axis) of a token cell: AdamW
    over f32 weights, sharded over its chips; `rows_per_token` rows a token,
    as `make_train_step` hands them to the rule."""
    cell = spec.load_cell(spec.ROOT, cell_name)
    config, traffic = cell["config"], cell["traffic"]
    # "auto" asks the platform, which is the CPU here: the chip's path
    config["attention_impl"] = "pallas"
    cfg = spec.load_code(
        spec.ROOT, "loops", config["family"]).model_config(config)
    chips = cell["workload"]["chips"]
    params = jax.eval_shape(
        lambda: tr.transformer_init(jax.random.PRNGKey(0), cfg))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    tokens = (cfg.rows_per_token * int(traffic["batch_rows"])
              * int(traffic["units_per_row"]))
    return (cfg, tokens // chips, 12 * n_params // chips,
            4 * n_params // chips, EXPERT_WAYS.get(cell_name, 1))


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_rule_on_a_token_cell_s_shapes(cell_name):
    cfg, tokens, resident, params, ways = cell_shapes(cell_name)
    chosen = tr.saved_activations(cfg, tokens, resident, params, LIMIT, ways)
    terms = tr._terms(cfg, tokens, params, ways)
    every = terms.saved_bytes()
    # names in the rule's own order, kept or not (a stack that is run once),
    # each at the bytes its shape gives
    assert list(chosen) == list(every)[:len(chosen)]
    assert set(chosen.values()) <= {1}
    sizes = terms.saved_bytes(chosen)
    assert all(sizes[name] == every[name] for name in chosen)
    if chosen:
        assert next(iter(chosen)) == "attn_ctx"
    # the step that keeps them fits: its fullest moment leaves room
    assert terms.room(resident, LIMIT, chosen) >= 0
    room = terms.room(resident, LIMIT)
    if all(seg.periods > 1 for seg in tr.segments(cfg)):
        # every layer scanned: every term at once, as the rule has counted
        assert room == (LIMIT - resident - params - tr._SAVE_RESERVE
                        - terms.at_once)
        assert sum(sizes.values()) <= max(room, 0)
    # no limit to read, or no rematerialisation: nothing is kept
    assert tr.saved_activations(
        cfg, tokens, resident, params, None, ways) == {}
    plain = TransformerConfig(**{**cfg.__dict__, "remat": False})
    assert tr.saved_activations(
        plain, tokens, resident, params, LIMIT, ways) == {}


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_choice_grows_with_the_limit(cell_name):
    cfg, tokens, resident, params, ways = cell_shapes(cell_name)
    before = {}
    for limit in range(8 << 30, 40 << 30, 1 << 28):
        chosen = tr.saved_activations(
            cfg, tokens, resident, params, limit, ways)
        assert list(chosen)[:len(before)] == list(before)
        before = chosen
    # at 40 GiB: every name
    assert before == dict.fromkeys(
        tr._terms(cfg, tokens, params, ways).saved_bytes(), 1)


@pytest.mark.parametrize("cell_name", sorted(KEPT))
def test_what_each_token_cell_keeps_at_the_chip_s_limit(cell_name):
    """The names a cell's step logs on the chip, pinned, so that a change
    to a term of `_terms` or of `_Terms.moments` shows which cells it
    moves. k and v are counted at their own heads since the flash kernels
    read them there (`ops/flash_attention.py`): counted as a scanned stack
    is, every term at once, `lagunaxs2.tokens8k` (64 and 48 query heads
    over 8) had room for `attn_res` with 24 MB to spare, where the repeat's
    copies left it 0.31 GB short; walked a layer at a time, as the chip
    holds a stack that is not scanned, it keeps every name.
    `keyevl2.tokens16k` keeps with `attn_ctx` the selection's mask as bits,
    2 KB a token and layer beside o's 8 KB, and is 0.05 GB short of
    `attn_res` (0.40 GB), which the compiler's plan for a v5e has no room
    for (`_SparseAttention.holds`)."""
    cfg, tokens, resident, params, ways = cell_shapes(cell_name)
    chosen = tr.saved_activations(
        cfg, tokens, resident, params, CHIP_LIMIT, ways)
    assert tuple(chosen) == KEPT[cell_name]
    terms = tr._terms(cfg, tokens, params, ways)
    sizes = terms.saved_bytes(chosen)
    at_once = (CHIP_LIMIT - resident - params - tr._SAVE_RESERVE
               - terms.at_once)
    room = terms.room(resident, CHIP_LIMIT)
    if cell_name == "lagunaxs2.tokens8k":
        two = sizes["attn_ctx"] + sizes["attn_res"]
        assert 20e6 < at_once - two < 30e6
        # of the gradients the held experts' accumulators alone, which
        # may wait for the optimizer
        assert room > at_once + 0.8e9
    if cell_name == "keyevl2.tokens16k":
        assert room == at_once
        assert chosen == {"attn_ctx": 1}
        assert sizes == {"attn_ctx": 1038090240 + 6 * 16384 * 16384 // 8}
        assert 0.3e9 < room - sum(sizes.values()) < 402653184


# `peak_hbm_gb.tokens`, GB of 1e9, with the names of `KEPT` kept: the five
# cells whose choice PR 54 left as it was from the ledger's PR 53 lines, the
# four it moved from PR 54's chip runs (PERF.md section 6); the cells PR 73
# added from the ledger's PR 72 lines where its choice did not move and from
# its own chip runs where it did
CHIP_PEAK_GB = {
    "mistral7b.tokens4k": 15.510,
    "mistral7b.fsdp4": 14.937,
    "olmoe.tokens4k": 11.211,  # (ledger, PR 72; 11.276 at PR 53)
    "dsv2lite.tokens8k": 15.587,
    "keyevl2.tokens16k": 15.512,
    "lfm2moe.tokens8k": 12.581,  # 12,580,931,584 bytes
    # 13,427,617,280 (my chip runs, PR 63: the gated norm's kernels hold no
    # float32 array of the mixer's width; 14.396 before)
    "nemotron3nano.tokens8k": 13.428,
    "lagunaxs2.tokens8k": 14.512,  # 14,511,826,944
    "mellum2.ep4": 14.270,  # 14,269,986,816, the fullest of the four chips
    "solaropen2.tokens8k": 14.095,  # (ledger, PR 72)
    "kimilinear.tokens16k": 14.117,  # (ledger, PR 72)
    "sdar.tokens16k": 13.635,  # 13,634,795,008 (ledger, PR 72)
    # with `mamba1_in` and `gmu_in`: 14,169,885,184 (my chip runs, PR 73;
    # 13.132 before)
    "phi4flash.tokens16k": 14.170,
    # with `mlp_up`: 14,434,449,408 (my chip runs, PR 73; 14.190 before)
    "evabyte.tokens8k": 14.434,
}


@pytest.mark.parametrize("cell_name", sorted(CHIP_PEAK_GB))
def test_the_rule_s_sum_stands_at_or_above_the_chip_s_peak(cell_name):
    """What the rule adds up for the choice it makes, state and fullest
    moment, is no less than the chip then held, and no more than 1 GB
    over: under it a step is compiled to fit, far over it names are
    refused that the chip has room for."""
    cfg, tokens, resident, params, ways = cell_shapes(cell_name)
    chosen = tr.saved_activations(
        cfg, tokens, resident, params, CHIP_LIMIT, ways)
    assert tuple(chosen) == KEPT[cell_name]
    fullest = tr._terms(cfg, tokens, params, ways).fullest(chosen)
    predicted = (resident + fullest.bytes) / 1e9
    assert CHIP_PEAK_GB[cell_name] <= predicted <= CHIP_PEAK_GB[cell_name] + 1
    # and under the chip's limit, the runtime's GiB set aside
    assert predicted * 1e9 <= CHIP_LIMIT - tr._SAVE_RESERVE


def test_the_longest_walked_stack_keeps_nothing_where_the_sum_has_no_room():
    """`granite4hmicro.longctx`: ten walked layers at 32,768 rows. A layer's
    fullest moment is its feed-forward's backward, with what the mixer
    before it left for its own (`Sublayer.residuals`, 1.38 GB): state and
    moment are 16.09 GB, 0.26 GB over what the rule may ask for, so it
    keeps nothing. The compiler's plan with nothing kept is 15.84 GB with
    no fusion made again (`tests/test_step_compile_walked.py`) and the
    chip's peak 15,728,650,240 bytes (my chip runs, PR 74): the sum stands
    over the chip's peak by 0.36 GB, inside the GB it may. The attention
    layer's moment is 1.6 GB under a mixer layer's."""
    cfg, tokens, resident, params, ways = cell_shapes("granite4hmicro.longctx")
    assert (tokens, resident) == (32768, 12 * 797850560)
    assert tr.saved_activations(
        cfg, tokens, resident, params, CHIP_LIMIT, ways) == {}
    terms = tr._terms(cfg, tokens, params, ways)
    assert terms.fullest().name == "layer 9"
    predicted = (resident + terms.fullest().bytes) / 1e9
    assert 15.729 <= predicted <= 15.729 + 1
    assert -0.3e9 < terms.room(resident, CHIP_LIMIT) < -0.2e9
    moments = {m.name: m.bytes for m in terms.moments()}
    assert moments["layer 9"] == moments["layer 0"]
    assert 1.5e9 < moments["layer 9"] - moments["layer 5"] < 1.7e9
    assert tr._scan_bytes_per_token(cfg) == 17920  # two tiles of 32 heads


def test_scanned_stacks_have_the_room_they_had():
    """Where every segment has several periods the rule counts every term
    at once, as it did: the room to the byte. `dsv2lite.tokens8k`'s five
    scanned layers set its peak, behind one dense layer that is not
    scanned: it still keeps nothing (`attn_ctx` wants 0.818 GB)."""
    rooms = {"mistral7b.tokens4k": 800783872, "mistral7b.fsdp4": 3947609600,
             "keyevl2.tokens16k": 1558048256}
    for cell_name, room in rooms.items():
        cfg, tokens, resident, params, ways = cell_shapes(cell_name)
        assert all(seg.periods > 1 for seg in tr.segments(cfg))
        assert tr._terms(cfg, tokens, params, ways).room(
            resident, CHIP_LIMIT) == room
        moments = tr._moments(cfg, tokens, params, ways)
        assert [m.name for m in moments] == [
            "optimizer", "head", "layers 0-%d" % (cfg.n_layers - 1)]
    cfg, tokens, resident, params, ways = cell_shapes("dsv2lite.tokens8k")
    assert [(len(seg.layout), seg.periods) for seg in tr.segments(cfg)] == [
        (1, 1), (1, 5)]
    assert tr.saved_activations(
        cfg, tokens, resident, params, CHIP_LIMIT, ways) == {}
    terms = tr._terms(cfg, tokens, params, ways)
    assert terms.fullest().name == "layers 1-5"
    assert 0 <= terms.room(resident, CHIP_LIMIT) < 817889280


def test_a_stack_that_is_not_scanned_is_walked_a_layer_at_a_time():
    """`lagunaxs2.tokens8k`, five layers in two segments of one period: a
    moment a layer, last layer first; a kept name weighs on the layers
    after the ones that make it (a layer's own block has its names at their
    widths, kept or made again: once), so the first layer's moment does not
    move at all; no moment holds the gradients of all the layers."""
    cfg, tokens, resident, params, ways = cell_shapes("lagunaxs2.tokens8k")
    nothing = tr._moments(cfg, tokens, params, ways)
    assert [m.name for m in nothing] == [
        "optimizer", "head", "layer 4", "layer 3", "layer 2", "layer 1",
        "layer 0"]
    kept = tr._moments(cfg, tokens, params, ways,
                       {"attn_ctx": 1, "attn_qkv": 1})
    sizes = tr._terms(cfg, tokens, params, ways).saved_bytes()
    both = sizes["attn_ctx"] + sizes["attn_qkv"]
    by_name = {m.name: m.bytes for m in nothing}
    for moment in kept:
        grown = moment.bytes - by_name[moment.name]
        if moment.name == "optimizer":
            assert grown == 0 and moment.bytes == params
        elif moment.name == "head":
            assert grown == both
        elif moment.name == "layer 0":
            assert grown == 0
        else:
            assert 0 < grown < both
    grown = [m.bytes - by_name[m.name] for m in kept if "layer" in m.name]
    assert grown == sorted(grown, reverse=True)
    at_once = params + tr._terms(cfg, tokens, params).at_once
    # (the held experts' accumulators of the layers behind it, which may
    # wait for the optimizer, are the gradients a moment does hold)
    assert max(m.bytes for m in nothing) < at_once - 0.8e9


def test_a_share_of_the_experts_has_no_names():
    cfg, tokens, *_ = cell_shapes("lfm2moe.tokens8k")
    assert not any(name.startswith("moe_")
                   for name in tr._terms(cfg, tokens).names)
    whole = TransformerConfig(**{**cfg.__dict__, "experts_held": None})
    assert {"moe_slots", "moe_gate", "moe_up"} <= set(
        tr._terms(whole, tokens).names)


def test_bytes_follow_the_shapes():
    cfg, tokens, *_ = cell_shapes("mistral7b.tokens4k")
    sizes = tr._terms(cfg, tokens).saved_bytes()
    # two layers of 16,384 tokens: o [32 heads of 128] bf16 and one f32 lse
    assert sizes["attn_ctx"] == 2 * 16384 * 32 * (128 * 2 + 4)
    assert sizes["attn_qkv"] == 2 * 16384 * (32 + 2 * 8) * 128 * 2
    assert sizes["attn_res"] == 2 * 16384 * 4096 * 2
    assert sizes["mlp_gate"] == sizes["mlp_up"] == 2 * 16384 * 14336 * 2
    assert set(sizes) == {"attn_ctx", "attn_res", "attn_qkv", "mlp_gate",
                          "mlp_up"}


# ------------------------------------------------- tiny steps on the CPU

TINY = dict(vocab_size=96, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=64, max_seq_len=16, remat=True, attention_impl="xla",
            tied_embeddings=False, dtype=jnp.float32)
MODELS = {
    "dense": {},
    "routed": dict(n_experts=4, experts_per_token=2, qk_norm=True),
    "conv_attention": dict(
        n_layers=3, layer_types=("conv", "full_attention", "conv"),
        n_dense_layers=1, d_ff_dense=48, n_experts=4, experts_per_token=2,
        router_score="sigmoid", norm_topk_prob=True, qk_norm="head",
        router_aux_loss_coef=0.0, router_z_loss_coef=0.0),
}
# what a kept name spares the second forward: (scope, primitive or kernel)
SPARED = {
    "dense": [("attention", "flash_fwd"), ("attn_qkv", "dot_general"),
              ("attn_out", "dot_general"), ("mlp", "dot_general")],
    "routed": [("attn_qkv", "dot_general"), ("moe_router", "sort"),
               ("moe_experts", "ragged_dot_general")],
    "conv_attention": [("conv_in", "dot_general"), ("conv_out", "dot_general"),
                       ("attn_out", "dot_general"),
                       ("moe_experts", "ragged_dot_general")],
}


def tiny_step(extra, **config):
    cfg = TransformerConfig(**{**TINY, **extra, **config})
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    return cfg, make_train_step(cfg, mesh)


def tiny_batch():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 96)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def assert_same(new, old):
    """Leaf by leaf to float32 rounding: the same operations, fewer times."""
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b)


def keep_everything(monkeypatch):
    monkeypatch.setattr(tr, "_memory_limit", lambda mesh: 1 << 40)


@pytest.mark.parametrize("model", list(MODELS))
def test_a_step_that_keeps_every_name_is_the_same_step(monkeypatch, model):
    _, (init_state, step, _) = tiny_step(MODELS[model])
    plain_state, plain = step(init_state(jax.random.PRNGKey(0)), tiny_batch())
    keep_everything(monkeypatch)
    cfg, (init_state, step, _) = tiny_step(MODELS[model])
    # the rule did choose, every name this model has
    kept = tr.saved_activations(cfg, 32, 0, 0, 1 << 40)
    assert kept == dict.fromkeys(tr._terms(cfg, 32).names, 1)
    assert len(kept) >= 5
    state, out = step(init_state(jax.random.PRNGKey(0)), tiny_batch())
    for key in ("loss", "grad_norm"):
        np.testing.assert_allclose(out[key], plain[key], rtol=1e-6)
    assert_same(state["params"], plain_state["params"])


@pytest.mark.parametrize("model", list(MODELS))
def test_gradients_with_kept_names_are_the_gradients(model):
    cfg, (init_state, _, _) = tiny_step(MODELS[model])
    params = init_state(jax.random.PRNGKey(0))["params"]
    names = tuple(tr._terms(cfg, 32).names)
    loss, grads = jax.value_and_grad(tr.transformer_loss)(
        params, tiny_batch(), cfg)
    kept_loss, kept_grads = jax.value_and_grad(tr.transformer_loss)(
        params, tiny_batch(), cfg, saved_names=names)
    np.testing.assert_allclose(kept_loss, loss, rtol=1e-6)
    assert_same(kept_grads, grads)


def rematted(lowered):
    """(scopes, primitive) of every operation of the second forward."""
    stacks = set(re.findall(
        r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))
    out = set()
    for stack in stacks:
        parts = [p for p in re.split(r"[/()]", stack) if p]
        if "rematted_computation" in parts:
            out.add((frozenset(parts[:-1]), parts[-1]))
    return out


def lowered_tiny_step(model, monkeypatch):
    """The model's tiny step, lowered; the dense model's attention through
    the flash kernels in interpret mode, as the chip runs them (the grouped
    matmul takes its kernels where attention does, so the routed models
    stay on `ragged_dot` and plain attention, whose backward makes its
    scores again whatever is kept)."""
    config = {}
    if model == "dense":
        monkeypatch.setattr(
            fa, "flash_attention", functools.partial(FLASH, interpret=True))
        config = dict(attention_impl="pallas", max_seq_len=128)
    _, (init_state, step, _) = tiny_step(MODELS[model], **config)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, config.get("max_seq_len", 16)), jnp.int32)
    return step.lower(state, {"tokens": tokens, "targets": tokens})


@pytest.mark.parametrize("model", list(MODELS))
def test_what_is_kept_is_not_made_again(monkeypatch, model):
    def made_again(lowered):
        ops = rematted(lowered)
        return {(scope, prim) for scope, prim in SPARED[model]
                if any(scope in scopes and (prim == last or prim in scopes)
                       for scopes, last in ops)}

    # the step that keeps nothing makes every one of them twice
    assert made_again(lowered_tiny_step(model, monkeypatch)) == set(
        SPARED[model])
    keep_everything(monkeypatch)
    assert made_again(lowered_tiny_step(model, monkeypatch)) == set()


def test_names_alone_change_no_instruction(monkeypatch):
    """With no limit to read the step has the names and no policy: it lowers
    to the text of the program without the names."""
    texts = []
    for named in (True, False):
        if not named:
            monkeypatch.setattr(tr, "checkpoint_name", lambda x, name: x)
        _, (init_state, step, _) = tiny_step(MODELS["conv_attention"])
        state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
        tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
        text = step.lower(
            state, {"tokens": tokens, "targets": tokens}).as_text()
        # a function's name ends in a counter of the functions traced
        texts.append(re.sub(r"@(\w+?)_\d+\b", r"@\1", text))
    assert texts[0] == texts[1]


def test_the_choice_is_logged_when_the_step_is_traced(monkeypatch, caplog):
    keep_everything(monkeypatch)
    _, (init_state, step, _) = tiny_step({})
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    with caplog.at_level("INFO", logger=tr.logger.name):
        step.lower(state, {"tokens": tokens, "targets": tokens})
    lines = [r.getMessage() for r in caplog.records if "remat" in r.getMessage()]
    assert len(lines) == 1
    for name in ("attn_ctx", "attn_qkv", "attn_res", "mlp_gate", "mlp_up"):
        assert name in lines[0]
    assert str(1 << 40) in lines[0]  # the limit it read


def test_the_terms_are_computed_once_a_traced_step(monkeypatch):
    """What does not depend on the choice (`_terms`) is computed once while
    a step is traced, however many candidates the rule weighs and whatever
    the step's line reads afterwards."""
    calls = collections.Counter()
    for name in ("_exchange_bytes", "_head_bytes", "_boundary_bytes",
                 "_layer_widths"):
        def counting(*args, real=getattr(tr, name), name=name):
            calls[name] += 1
            return real(*args)

        monkeypatch.setattr(tr, name, counting)
    weighed = []
    moments = tr._Terms.moments
    monkeypatch.setattr(tr._Terms, "moments", lambda self, kept=None: (
        weighed.append(kept) or moments(self, kept)))
    keep_everything(monkeypatch)
    tr._terms.cache_clear()
    cfg, (init_state, step, _) = tiny_step(MODELS["conv_attention"])
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    step.lower(state, {"tokens": tokens, "targets": tokens})
    # a candidate a name, then the line's fullest moment and room before
    names = max(weighed, key=lambda kept: len(kept or ()))
    assert len(weighed) >= len(names) + 2 and len(names) >= 5
    assert calls == {"_exchange_bytes": 1, "_head_bytes": 1,
                     "_boundary_bytes": 1,
                     "_layer_widths": len(set(cfg.layers))}


def test_tokens_on_a_device_follow_the_mesh(monkeypatch):
    """The step hands the rule the tokens one device holds: the batch over
    the mesh's batch axes."""
    seen = {}

    def rule(cfg, tokens, resident, params, limit, expert_ways):
        seen.update(tokens=tokens, resident=resident, params=params,
                    expert_ways=expert_ways)
        return {}

    monkeypatch.setattr(tr, "saved_activations", rule)
    cfg = TransformerConfig(**TINY)
    mesh = make_mesh({"fsdp": 4}, devices=jax.devices()[:4])
    init_state, step, _ = make_train_step(cfg, mesh)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((8, 16), jnp.int32)
    step.lower(state, {"tokens": tokens, "targets": tokens})
    assert seen["tokens"] == 8 * 16 // 4
    assert seen["expert_ways"] == 1  # the size of the mesh's `expert` axis
    whole = sum(math.prod(x.shape) * x.dtype.itemsize
                for x in jax.tree.leaves(state["params"]))
    # the matrices are cut four ways, the norms' scales are whole
    assert whole / 4 <= seen["params"] < whole / 3
    assert 3 * seen["params"] <= seen["resident"] < 3 * seen["params"] + 64
