"""The `nemotron_h` family of the benchmark on the CPU: the model against its
plain reference at a tiny size, each wrong mathematics that has to fall
outside `TOLERANCE`, the tiny cell's loop end to end, the operation counts by
hand, and the new files' form."""

import contextlib
import copy
import dataclasses
import inspect
import json
import math
import os
import shutil
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare, loop, nemotron_h_flops, run, spec
from chipbench import traffic as traffic_lib
from chipbench_tiny import fake_reduced, fake_summary, run_loop_here

CELL = "nemotron3nano.tokens8k"
CONFIG = "nemotron-3-nano-30b-a3b-ep16"
BENCH = spec.load_benchmark(spec.ROOT)
FAULTS = ["skip_left_out", "gate_left_out", "sums_of_decay_in_bf16",
          "rotary_positions", "factor_left_out", "bias_ignored",
          "balance_over_normalised_scores", "bf16_everything"]


def tiny_nemotron(dtype="bfloat16", **over):
    """64 wide, the cell's nine sublayers, mixers of 4 heads of 16 with a
    state of 16 in 2 groups and chunks of 16, 4 of 16 experts held, 3 a
    token, attention of 4 heads of 16 over 2, sequences of 64."""
    cell = copy.deepcopy(spec.load_cell(spec.ROOT, CELL))
    config, traffic = cell["config"], cell["traffic"]
    config.update(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2,
                  d_head=16, d_ff=48, d_ff_shared=96, n_experts=16,
                  experts_held=[4, 4], experts_per_token=3, mamba_heads=4,
                  mamba_head_dim=16, ssm_state=16, ssm_groups=2, ssd_chunk=16,
                  max_seq_len=64, dtype=dtype,
                  check=dict(config["check"], rows=4, seq_len=48), **over)
    traffic["columns"]["tokens"]["shape"] = [65]
    traffic.update(units_per_row=64, blocks_per_epoch=5, steps_per_chunk=2,
                   warmup_steps=1, trace_chunks=2)
    return cell


def family_of(cell):
    return spec.load_code(spec.ROOT, "loops", cell["config"]["family"]).build(
        cell["config"], cell["traffic"], jax.devices()[:1])


def check_batch(cell, family, seed=11):
    raw = traffic_lib.make_rows(cell["traffic"], cell["config"], seed,
                                loop.CHECK_INDEX, cell["config"]["check"]["rows"])
    return family.check_batch(raw)


def wrong_systems(family):
    """{name: a context in which to call `family.errors_of`, and the system
    to hand it}: each computes something other than the published model."""
    from ray_tpu.models import transformer
    from ray_tpu.ops import moe

    system = family.system_loss_and_readings
    real_ssd, real_cumsum = transformer.ssd, jnp.cumsum
    real_balance = moe.load_balancing_loss

    def bf16_everything(made, batch, bias):
        low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), made)
        loss, readings = system(low, batch, bias)
        return loss.astype(jnp.bfloat16).astype(jnp.float32), readings

    def with_cfg(**over):
        cfg = dataclasses.replace(family.model_config, **over)
        return lambda params, batch, bias: (
            transformer.transformer_loss_and_readings(
                params, batch, cfg, mesh=family.mesh, expert_bias=bias))

    # the mixer as it is written, but for the gate
    stated = inspect.getsource(transformer._mamba_mixer)
    assert "(y * jax.nn.silu(z))" in stated
    scope = dict(vars(transformer))
    exec(stated.replace("(y * jax.nn.silu(z))", "y"), scope)

    return {
        "skip_left_out": (mock.patch.object(
            transformer, "ssd", lambda x, dt, A, B, C, D, **kw: real_ssd(
                x, dt, A, B, C, jnp.zeros_like(D), **kw)), system),
        "gate_left_out": (mock.patch.object(
            transformer, "_mamba_mixer", scope["_mamba_mixer"]), system),
        "sums_of_decay_in_bf16": (mock.patch.object(
            jnp, "cumsum", lambda a, **kw: real_cumsum(
                a.astype(jnp.bfloat16), **kw).astype(a.dtype)), system),
        "rotary_positions": (contextlib.nullcontext(), with_cfg(rope=True)),
        "factor_left_out": (contextlib.nullcontext(),
                            with_cfg(routed_scaling_factor=1.0)),
        "bias_ignored": (contextlib.nullcontext(),
                         lambda params, batch, bias: system(
                             params, batch, jnp.zeros_like(bias))),
        "balance_over_normalised_scores": (mock.patch.object(
            moe, "load_balancing_loss", lambda probs, load: real_balance(
                probs / probs.sum(-1, keepdims=True), load)), system),
        "bf16_everything": (contextlib.nullcontext(), bf16_everything),
    }


# ------------------------------------------------- the model and its reference

def test_tiny_cell_keeps_the_family_and_its_mechanisms():
    config = tiny_nemotron()["config"]
    assert config["family"] == "nemotron_h"
    assert config["sublayer_types"] == [
        "mamba2", "routed_ff", "mamba2", "routed_ff", "mamba2",
        "full_attention", "routed_ff", "mamba2", "routed_ff"]
    assert (config["router_score"], config["ff_activation"]) == ("sigmoid", "relu2")
    assert config["rope"] is False and config["expert_bias"] is True
    assert config["routed_scaling_factor"] == 2.5
    assert config["norm_topk_prob"] is True and config["norm_topk_eps"] == 1e-20
    assert config["n_shared_experts"] == 1 and config["tied_embeddings"] is False
    assert config["router_aux_loss_coef"] == 1e-4
    assert config["router_z_loss_coef"] == 0.0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype):
    cell = tiny_nemotron(dtype)
    family = family_of(cell)
    made = family.init_params(loop.seed_key(2**31 + 3))
    assert float(jnp.abs(made["expert_bias"]).max()) > 0.1  # drawn, not zero
    errors = family.check(made, check_batch(cell, family))
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 1e-4
        assert errors["router_flip_share"] == 0.0
        assert errors["aux_loss_rel_err"] < 1e-6
    else:
        assert errors["loss_rel_err"] < 10 * family.tolerance["loss_rel_err"]
        assert errors["router_flip_share"] < 0.05
        assert errors["grad_rel_err"] < 0.1  # nine tiny sublayers round harder
        assert errors["aux_loss_rel_err"] < 5e-3
    assert errors["dropped_slots"] == errors["unrouted_slots"] == 0.0
    assert errors["expert_load_max_over_mean"] >= 1.0
    assert 0 < errors["held_slots_mean"] < 4 * 48 * 3
    # the mean over four layers of E sum f P at sigmoid scores near a half
    assert 4.0 < errors["aux_loss_system"] < 16.0


@pytest.mark.parametrize("fault", FAULTS)
def test_wrong_mathematics_is_outside_the_tolerance(fault):
    """In float32, where the stated path agrees to rounding, so that what
    is left is the fault's own (`loops/nemotron_h.py` has the chip's
    readings at the published widths)."""
    cell = tiny_nemotron("float32")
    family = family_of(cell)
    made = family.init_params(loop.seed_key(2**31 + 3))
    batch = check_batch(cell, family)
    patched, system = wrong_systems(family)[fault]
    with patched:
        wrong = family.errors_of(system, made, batch)
    if fault == "sums_of_decay_in_bf16":
        # 48 tokens in chunks of 16 are short sums: over the stated path's
        # rounding a thousandfold here, over the bound at 8192 in chunks of
        # 128 on the chip
        assert wrong["grad_rel_err"] > 1e-3, wrong
        return
    assert not compare.within(wrong, family.tolerance), wrong
    if fault == "bias_ignored":  # held by the choice's own key alone
        assert wrong["router_flip_share"] > 5 * family.tolerance["router_flip_share"]
    elif fault == "balance_over_normalised_scores":  # by its own key alone
        assert wrong["aux_loss_rel_err"] > 0.5
        assert wrong["grad_rel_err"] < family.tolerance["grad_rel_err"]
    elif fault == "bf16_everything":
        assert wrong["loss_rel_err"] > family.tolerance["loss_rel_err"]
    else:
        assert wrong["grad_rel_err"] > 1.5 * family.tolerance["grad_rel_err"], wrong


def test_a_run_starts_from_the_bias_that_evens_the_load():
    """The step's own rule, applied in set-up: under random `relu2` weights
    a zero bias leaves some experts every token's favourites."""
    import numpy as np

    cell = tiny_nemotron("float32")
    assert cell["config"]["start"] == {
        "rounds": 200, "rows": 2, "seq_len": 2048, "rate_first": 0.02,
        "rate_last": 0.001}
    cell["config"]["start"].update(rounds=120, seq_len=512)
    family = family_of(cell)
    made = family.init_params(loop.seed_key(2**31 + 3))
    state = family.init_state(made)
    bias = state["expert_bias"]
    assert bias.shape == (4, 16) and 0.01 < float(jnp.abs(bias).max()) < 0.5
    assert int(state["step"]) == 0
    ids = jax.random.randint(jax.random.PRNGKey(5), (4, 513), 0, 256)
    batch = {"tokens": ids[:, :-1], "targets": ids[:, 1:]}

    def shares(bias):
        load = np.asarray(family.system_loss_and_readings(
            made["params"], batch, bias)[1]["expert_load"], np.float32)
        return load * 16 / load.sum(axis=1, keepdims=True)

    before, after = shares(jnp.zeros_like(bias)), shares(bias)
    assert before.max() > 1.5 and after.max() < 1.25 and after.min() > 0.75
    held = after[:, 4:8].mean(axis=1)  # experts 4 to 7
    assert np.abs(held - 1).max() < 0.1


def test_no_weight_decay_on_the_mixers_vectors_and_the_norms():
    cell = tiny_nemotron()
    loops = spec.load_code(spec.ROOT, "loops", "nemotron_h")
    family = family_of(cell)
    params = jax.eval_shape(
        lambda: family.init_params(loop.seed_key(1)))["params"]
    mask = loops.decayed(params, cell["config"]["optimizer"]["no_decay"])
    mixer, routed, attention = (mask["blocks"][0][i] for i in (0, 1, 5))
    assert not any(mixer[k] for k in (
        "A_log", "D", "dt_bias", "conv_b", "norm", "mixer_norm"))
    assert mixer["w_in"] and mixer["w_out"] and mixer["conv_w"]
    assert not routed["mlp_norm"] and routed["router"] and routed["w_up"]
    assert not attention["attn_norm"] and attention["wq"]
    assert mask["embed"] and mask["unembed"] and not mask["final_norm"]


def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_nemotron()
    reports = run_loop_here(monkeypatch, cell, seconds=0.4)
    summary = reports[-1]
    assert summary["summary"] and summary["steps_failed"] == 0
    assert summary["compiles_in_window"] == 0
    assert summary["steps"] == 2 * len(summary["chunks"]) >= 2
    assert all(math.isfinite(c["loss"]) for c in summary["chunks"])
    reference = summary["reference"]
    assert {"router_flip_share", "aux_loss_rel_err", "held_slots_mean",
            "dropped_slots"} <= set(reference)
    assert reference["dropped_slots"] == 0.0
    assert summary["flops_per_unit"] == (
        nemotron_h_flops.nemotron_h_flops_per_token(cell["config"], 64))
    summary["device"] = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    summary["memory_peak_bytes"] = 1
    summary["reference"]["agrees"] = True
    line = run.last_line(spec.ROOT, BENCH, cell, summary, None)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    json.dumps(line)


# ---------------------------------------------------------- operation counts

def test_flops_per_token_by_hand():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    parts = nemotron_h_flops.forward_parts(config, 8192)
    d = 2688
    # W_in 2688 x (4096 + 6144 + 64), W_out 4096 x 2688
    assert parts["mixer_projections"] == 4 * 2 * (d * 10304 + 4096 * d)  # 309.7 M
    # a chunk's scores 128 x (8 x 128), its product 128 x 4096, the state
    # and its contribution 4096 x 128 each, a token: 3.4 M a layer
    scan = 2 * 128 * (1024 + 4096) + 2 * 2 * 4096 * 128
    assert scan == 3407872 and parts["scan"] == 4 * scan
    # W_q and W_o 2688 x 4096, W_k and W_v 2688 x 256
    assert parts["attention_projections"] == 2 * (2 * d * 4096 + 2 * d * 256)
    assert parts["attention"] == 2 * 2 * 32 * 128 * 8193 / 2          # 67.1 M
    assert parts["router"] == 4 * 2 * d * 128
    assert parts["experts"] == 4 * (6 * 8 / 128) * 2 * 2 * d * 1856   # 29.9 M
    assert parts["shared_experts"] == 4 * 2 * 2 * d * 3712            # 159.6 M
    assert parts["head"] == 2 * d * 16384                             # 88.1 M
    total = sum(parts.values())
    assert total == pytest.approx(717.6e6, rel=1e-3)                  # the 718 M
    mixers = parts["mixer_projections"] + parts["scan"]
    routed = parts["router"] + parts["experts"] + parts["shared_experts"]
    attention = parts["attention_projections"] + parts["attention"]
    assert mixers / total == pytest.approx(0.45, abs=0.005)
    assert routed / total == pytest.approx(0.27, abs=0.005)
    assert attention / total == pytest.approx(0.16, abs=0.005)
    assert (mixers + routed) / total == pytest.approx(0.72, abs=0.005)
    assert nemotron_h_flops.nemotron_h_flops_per_token(config, 8192) == 3 * total


def test_flops_agree_with_the_program_s_own_count():
    from ray_tpu.models.transformer import flops_per_token

    cell = spec.load_cell(spec.ROOT, CELL)
    cfg = spec.load_code(spec.ROOT, "loops", "nemotron_h").model_config(
        cell["config"])
    for seq_len in (1024, 8192):
        assert flops_per_token(cfg, seq_len) == pytest.approx(
            nemotron_h_flops.nemotron_h_flops_per_token(
                cell["config"], seq_len), rel=1e-12)


def test_param_count_and_the_cut_s_arithmetic():
    """From the configuration's own keys: 667.0 M parameters, 10.67 GB."""
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    assert nemotron_h_flops.mixer_params(config) == 38744896
    assert nemotron_h_flops.attention_params(config) == 23399040
    assert nemotron_h_flops.routed_params(config) == 20302464 + 8 * 9977856
    n = nemotron_h_flops.nemotron_h_param_count(config)
    assert n == (4 * 38744896 + 23399040 + 4 * 100125312
                 + 2 * 16384 * 2688 + 2688) == 666962944
    assert 10.6e9 < 16 * n < 10.7e9
    assert "666,962,944" in config["deployment"]
    cfg = spec.load_code(spec.ROOT, "loops", "nemotron_h").model_config(config)
    from ray_tpu.models.transformer import transformer_init
    shapes = jax.eval_shape(lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) == n
    # the whole model, every published layer and expert and the vocabulary:
    # the 31.6 B it is described as, which is what says `d_inner` is 4096
    whole = dict(config, n_experts=128, experts_held=None, vocab_size=131072,
                 sublayer_types=[
                     {"M": "mamba2", "E": "routed_ff", "*": "full_attention"}[c]
                     for c in config["published"]["hybrid_override_pattern"]])
    assert nemotron_h_flops.nemotron_h_param_count(whole) == pytest.approx(
        31.58e9, rel=1e-3)
    # 16 experts a chip would not fit beside the gradients
    sixteen = dict(config, experts_held=[0, 16])
    assert 16 * nemotron_h_flops.nemotron_h_param_count(sixteen) > 15.7e9


def test_scan_operations_and_bytes_by_hand():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    parts = nemotron_h_flops.scan_parts(config, 2, 8192)
    tokens, chunks = 16384, 128
    assert parts["ssd_chunk"]["flops"] == tokens * 2 * 128 * (1024 + 4096)
    assert parts["ssd_state"]["flops"] == parts["ssd_out"]["flops"] == (
        tokens * 2 * 4096 * 128)
    x, bc, dt = tokens * 4096 * 2, tokens * 1024 * 2, tokens * 64 * 4
    states = chunks * 64 * 64 * 128 * 4
    assert parts["ssd_chunk"]["bytes"] == 2 * x + 2 * bc + dt
    assert parts["ssd_state"]["bytes"] == x + bc + dt + states
    assert parts["ssd_out"]["bytes"] == states + bc + dt + 2 * x
    # a tenth of a second of HBM for the three parts together would be 82 GB
    assert sum(p["bytes"] for p in parts.values()) == pytest.approx(1.35e9, rel=0.01)


# ------------------------------------------------------------ the new files

def test_configuration_holds_the_catalog_s_numbers():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    source = config["catalog_config"]
    reduced = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
               "n_routed_experts": 8, "vocab_size": 16384}
    entry = spec.by_name(BENCH["configs"], CONFIG, "config")
    assert entry["reduced"] == config["reduced"] == list(reduced)
    for key, value in source.items():
        assert config[key] == reduced.get(key, value), key
    # every width as published, in the keys the program reads
    assert config["d_model"] == source["hidden_size"] == 2688
    assert config["n_heads"] == source["num_attention_heads"] == 32
    assert config["n_kv_heads"] == source["num_key_value_heads"] == 2
    assert config["d_head"] == source["head_dim"] == 128
    assert config["mamba_heads"] == source["mamba_num_heads"] == 64
    assert config["mamba_head_dim"] == source["mamba_head_dim"] == 64
    assert config["ssm_state"] == source["ssm_state_size"] == 128
    assert config["ssm_groups"] == source["n_groups"] == 8
    assert config["mamba_conv_taps"] == source["conv_kernel"] == 4
    assert config["ssd_chunk"] == source["chunk_size"] == 128
    assert config["d_ff"] == source["moe_intermediate_size"] == 1856
    assert config["d_ff_shared"] == source["moe_shared_expert_intermediate_size"]
    assert config["n_experts"] == 128 == config["published"]["n_routed_experts"]
    assert config["experts_per_token"] == source["num_experts_per_tok"] == 6
    assert config["experts_held"] == [0, config["n_routed_experts"]]
    assert config["routed_scaling_factor"] == source["routed_scaling_factor"]
    assert config["norm_eps"] == source["layer_norm_epsilon"] == 1e-5
    assert config["ff_activation"] == source["mlp_hidden_act"] == "relu2"
    assert config["tied_embeddings"] == source["tie_word_embeddings"]
    assert config["mamba_dt_init"] == [
        source["time_step_min"], source["time_step_max"], source["time_step_floor"]]
    kinds = {"M": "mamba2", "E": "routed_ff", "*": "full_attention"}
    assert config["sublayer_types"] == [
        kinds[c] for c in config["hybrid_override_pattern"]]
    assert config["n_layers"] == config["num_hidden_layers"] == 9
    published = config["published"]
    assert published["hybrid_override_pattern"].startswith(
        config["hybrid_override_pattern"])
    assert len(published["hybrid_override_pattern"]) == 52
    assert published["chips_sharing_a_layer"] == 16
    assert published["vocab_size"] == 131072 == 8 * config["vocab_size"]
    assert published["layers_held"] == list(range(9))
    assert config["source"].startswith(entry["source"])
    assert {"rope", "d_inner", "gate_before_norm", "dt", "balance",
            "initialisers", "weight_decay", "optimizer", "ssd_chunk",
            "sequence", "remat", "dtype"} <= set(config["assumed"])
    assert "starts from the bias that evens the load" in config["assumed"]["balance"]
    assert config["check"] == {"rows": 2, "seq_len": 1024,
                               "expert_bias_std": 0.1}


def test_traffic_mix_is_the_issue_s():
    traffic = spec.load_cell(spec.ROOT, CELL)["traffic"]
    assert traffic["kind"] == "ingest"
    assert traffic_lib.units_per_step(traffic) == 16384
    assert (traffic["steps_per_chunk"], traffic["blocks_per_epoch"],
            traffic["trace_chunks"], traffic["warmup_steps"],
            traffic["prefetch_batches"], traffic["rows_per_block"],
            traffic["batch_rows"]) == (2, 256, 2, 2, 2, 2, 2)
    rows = traffic_lib.make_rows(
        traffic, {"vocab_size": 16384}, 2**31 + 9, 0, 2)["tokens"]
    assert rows.shape == (2, 8193) and 0 <= rows.min() and rows.max() < 16384


def test_the_cell_s_files_are_found_by_name_under_another_root(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    for kind, name in (("configs", CONFIG + ".json"),
                       ("traffic", "tokens-8k-16k.json")):
        os.makedirs(os.path.join(root, "chipbench", kind), exist_ok=True)
        shutil.copy(os.path.join(spec.ROOT, "chipbench", kind, name),
                    os.path.join(root, "chipbench", kind, name))
    cell = spec.load_cell(root, CELL)
    assert cell == spec.load_cell(spec.ROOT, CELL)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["traffic"] == "tokens-8k-16k"
    assert len(cell["workload"]["why"]) <= 200
    named = {m["name"] for m in spec.metrics_of(BENCH, CELL, "per_layer")}
    assert {"ingest_wait_share.tokens", "steady_rate.tokens",
            "stall_share.tokens", "model_mfu.tokens", "pallas_time_share.tokens",
            "device_idle_share.tokens", "peak_hbm_gb.tokens",
            "moe_gmm_time_share.tokens", "flash_time_share.tokens",
            "cluster_init_s", "compile_s", "first_batch_s", "setup_unnamed_s",
            "ingest_produce_share.tokens", "gang_boot_s", "state_init_s"} == named
    assert {m["name"] for m in spec.metrics_of(BENCH, CELL, "end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    # appended behind what was there: the eighth cell, the seventh configuration
    assert [w["name"] for w in BENCH["workloads"]].index(CELL) == 7
    assert [c["name"] for c in BENCH["configs"]].index(CONFIG) == 6
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


@pytest.mark.parametrize("name,scope", [
    ("mamba_time_share.tokens", "mamba"), ("ssd_time_share.tokens", "ssd")])
def test_waiting_metrics_carry_their_entry(name, scope):
    """Under the key `awaits`, as PR 27's, PR 32's and PR 34's are."""
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry = held["awaits"]
    assert "entry" not in held
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert entry["name"] == name and entry["unit"] == "%"
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_tokens_per_s"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert entry["better"] == "lower" and entry["source"] == "device_trace"
    assert name not in {m["name"] for m in BENCH["per_layer"]}  # still waiting
    assert held["reader"] == "scope_share" and held["params"] == {"scope": scope}
    cell = spec.load_cell(spec.ROOT, CELL)
    untraced = dict(fake_summary(cell), chips=1, trace=None)
    assert spec.read_metric(spec.ROOT, name, untraced) is None
    # the fixture's trace has no name stacks: nothing to read, and no raise
    bare = dict(untraced, trace=fake_reduced(1))
    assert spec.read_metric(spec.ROOT, name, bare) is None
    # a program that names its work: the scope's share of busy time
    stacks = {"fusion.1": f"jit(step)/jvp(mamba)/{scope}/dot_general",
              "kernel.2 [tpu_custom_call]": "jit(step)/jvp()/attention/x",
              "all-gather.3": "jit(step)/optimizer/add"}
    if scope == "ssd":
        stacks["fusion.1"] = "jit(step)/jvp(mamba)/ssd/ssd_chunk/dot_general"
    named = dict(untraced, trace=dict(fake_reduced(1), name_stacks=stacks))
    with mock.patch("chipbench.scopes._NAMED", frozenset(
            {"mamba", "ssd", "ssd_chunk", "attention", "optimizer"})):
        from chipbench import scopes
        scopes.classify.cache_clear()
        value = spec.read_metric(spec.ROOT, name, named)
    scopes.classify.cache_clear()
    assert value == pytest.approx(100.0 * 600 / 940)
