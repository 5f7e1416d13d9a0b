"""The `laguna` family of the benchmark on the CPU: the model against its
plain reference at a tiny size, each wrong mathematics that has to fall
outside `TOLERANCE`, the tiny cell's loop end to end, the operation counts by
hand, and the new files' form. `wrong_systems` is also what the builder's
chip run takes its wrong mathematics from, at the published widths."""

import copy
import dataclasses
import json
import math
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare, laguna_flops, loop, run, spec
from chipbench import traffic as traffic_lib
from chipbench_tiny import fake_reduced, fake_summary, run_loop_here

CELL = "lagunaxs2.tokens8k"
CONFIG = "laguna-xs.2-ep8"
BENCH = spec.load_benchmark(spec.ROOT)
FAULTS = [
    "window_one_key_too_wide", "window_one_key_too_narrow", "gate_dropped",
    "full_rotation_on_a_full_layer", "plain_frequencies_for_yarn",
    "no_attention_factor", "weights_not_normalised", "no_scaling_factor",
    "balance_loss_per_sequence", "bf16_everything"]
# the lists of BENCHMARK.json the cell joins (ISSUE 40, item 6)
LISTS = [
    "ingest_wait_share.tokens", "steady_rate.tokens", "stall_share.tokens",
    "model_mfu.tokens", "pallas_time_share.tokens", "device_idle_share.tokens",
    "peak_hbm_gb.tokens", "moe_gmm_time_share.tokens", "flash_time_share.tokens",
    "cluster_init_s", "compile_s", "first_batch_s", "setup_unnamed_s",
    "ingest_produce_share.tokens", "flash_window_time_share.tokens"]
WAITING = [
    "flash_fwd_roofline.window.tokens", "flash_bwd_dkv_dq_roofline.window.tokens",
    "moe_gmm_roofline.laguna.tokens", "moe_tgmm_roofline.laguna.tokens"]


def tiny_laguna(dtype="bfloat16", **over):
    """64 wide, heads of 16: a dense layer under full attention (4 query
    heads) and the period sliding, sliding, sliding (8 query heads, a window
    of 8), full; 2 key-value heads; 4 of 16 experts held, 3 a token, one
    shared; sequences of 64, compared at 32 (four windows deep)."""
    cell = copy.deepcopy(spec.load_cell(spec.ROOT, CELL))
    config, traffic = cell["config"], cell["traffic"]
    config.update(vocab_size=256, d_model=64, n_heads=4, n_heads_sliding=8,
                  n_kv_heads=2, d_head=16, d_ff=32, d_ff_dense=96,
                  d_ff_shared=32, max_seq_len=64, sliding_window=8,
                  n_experts=16, experts_held=[4, 4], experts_per_token=3,
                  dtype=dtype, check=dict(config["check"], rows=4, seq_len=32),
                  **over)
    # the ramp inside the tiny rotary width, and positions past the original
    config["rope_scaling"] = dict(
        config["rope_scaling"], original_max_position_embeddings=16)
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


# ------------------------------------- wrong mathematics, as wrong systems

def wrong_systems(cell, family):
    """{name: (the system to hand `family.errors_of`, the sliding layers'
    attention to hand it, None for the stated one)}: each computes something
    other than the published model."""
    from ray_tpu.models import transformer

    cfg, mesh = family.model_config, family.mesh
    system = family.system_loss_and_readings

    def with_cfg(**changed):
        wrong = dataclasses.replace(cfg, **changed)
        return lambda p, b: transformer.transformer_loss_and_readings(
            p, b, wrong, mesh=mesh)

    def window_of(window):
        return lambda q, k, v: transformer._attention(
            q, k, v, cfg, None, 1, mesh, window=window)

    def gate_dropped(p, b):
        blocks = [[{k: v for k, v in blk.items() if k != "w_gate_attn"}
                   for blk in seg] for seg in p["blocks"]]
        return system({**p, "blocks": blocks}, b)

    def bf16_everything(p, b):
        p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
        loss, readings = system(p, b)
        return loss.astype(jnp.bfloat16).astype(jnp.float32), readings

    scaling = dict(cfg.rope_scaling)
    window = cfg.sliding_window
    return {
        "window_one_key_too_wide": (
            with_cfg(sliding_window=window + 1), window_of(window + 1)),
        "window_one_key_too_narrow": (
            with_cfg(sliding_window=window - 1), window_of(window - 1)),
        "gate_dropped": (gate_dropped, None),
        "full_rotation_on_a_full_layer": (
            with_cfg(partial_rotary_factor=1.0), None),
        "plain_frequencies_for_yarn": (with_cfg(rope_scaling=None), None),
        "no_attention_factor": (with_cfg(rope_scaling=tuple(sorted(
            {**scaling, "attention_factor": 1.0}.items()))), None),
        "weights_not_normalised": (with_cfg(norm_topk_prob=False), None),
        "no_scaling_factor": (with_cfg(routed_scaling_factor=1.0), None),
        # every sequence's own counts and mean scores, summed over the layers
        "balance_loss_per_sequence": (with_cfg(seq_aux=True), None),
        "bf16_everything": (bf16_everything, None),
    }


def errors_of_wrong(family, wrong, params, batch):
    system, window_attention = wrong
    extra = {} if window_attention is None else {
        "window_attention": window_attention}
    return family.errors_of(system, params, batch, **extra)


# ------------------------------------------------------------ the comparison

def test_tiny_cell_keeps_the_family_and_its_mechanisms():
    config = tiny_laguna()["config"]
    assert config["family"] == "laguna"
    assert config["layer_types"] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert (config["n_dense_layers"], config["router_score"]) == (1, "sigmoid")
    assert config["n_shared_experts"] == 1 and config["attn_gate"] is True
    assert config["norm_topk_prob"] is True and config["tied_embeddings"] is False
    assert config["routed_scaling_factor"] == 2.5
    assert config["rope_scaling"]["rope_type"] == "yarn"
    assert config["partial_rotary_factor"] == 0.5
    assert config["check"]["seq_len"] == 4 * config["sliding_window"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype):
    cell = tiny_laguna(dtype)
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    errors = family.check(params, check_batch(cell, family))
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 1e-5
        assert errors["router_flip_share"] == 0.0
        assert errors["aux_loss_rel_err"] < 1e-6
        assert errors["window_edge_err"] < 1e-6
    else:
        assert errors["loss_rel_err"] < 3 * family.tolerance["loss_rel_err"]
        assert errors["router_flip_share"] < 0.05
        assert errors["grad_rel_err"] < 0.12  # five tiny layers round harder
        assert errors["aux_loss_rel_err"] < 2e-3
        assert errors["window_edge_err"] < family.tolerance["window_edge_err"]
    assert errors["dropped_slots"] == errors["unrouted_slots"] == 0.0
    assert errors["expert_load_max_over_mean"] >= 1.0
    assert 0 < errors["held_slots_mean"] < 4 * 32 * 3
    # E sum_e f_e P_e with sigmoid scores of about a half: about E / 2
    assert 0.3 * 16 < errors["aux_loss_system"] < 0.7 * 16


@pytest.mark.parametrize("fault", FAULTS)
def test_wrong_mathematics_is_outside_the_tolerance(fault):
    """In float32, where the stated path agrees to rounding, so that what
    is left is the fault's own: each reads over the bound of the key that
    holds it at the published widths (`loops/laguna.py` has the chip's
    readings)."""
    cell = tiny_laguna("float32")
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    batch = check_batch(cell, family)
    wrong = errors_of_wrong(
        family, wrong_systems(cell, family)[fault], params, batch)
    assert not compare.within(wrong, family.tolerance), wrong
    if fault.startswith("window"):  # held by the probe, whatever the loss says
        assert wrong["window_edge_err"] > 5 * family.tolerance["window_edge_err"]
    elif fault == "bf16_everything":
        assert wrong["loss_rel_err"] > family.tolerance["loss_rel_err"]
    elif fault == "balance_loss_per_sequence":  # held by its own key
        assert wrong["aux_loss_rel_err"] > 10 * family.tolerance["aux_loss_rel_err"]
        assert wrong["window_edge_err"] < 1e-6
    else:
        assert wrong["grad_rel_err"] > 2 * family.tolerance["grad_rel_err"], wrong
        assert wrong["window_edge_err"] < 1e-6


def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_laguna()
    reports = run_loop_here(monkeypatch, cell, seconds=0.4)
    summary = reports[-1]
    assert summary["summary"] and summary["steps_failed"] == 0
    assert summary["compiles_in_window"] == 0
    assert summary["steps"] == 2 * len(summary["chunks"]) >= 2
    assert all(math.isfinite(c["loss"]) for c in summary["chunks"])
    reference = summary["reference"]
    assert {"router_flip_share", "aux_loss_rel_err", "window_edge_err",
            "held_slots_mean", "dropped_slots"} <= set(reference)
    assert reference["dropped_slots"] == 0.0
    assert summary["flops_per_unit"] == laguna_flops.laguna_flops_per_token(
        cell["config"], 64)
    summary["device"] = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    summary["memory_peak_bytes"] = 1
    summary["reference"]["agrees"] = True
    line = run.last_line(spec.ROOT, BENCH, cell, summary, None)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    json.dumps(line)


# ---------------------------------------------------------- operation counts

def test_flops_per_token_by_hand():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    parts = laguna_flops.forward_parts(config, 8192)
    d = 2048
    full = 2 * d * 48 * 128 + 2 * d * 8 * 128 + d * 48
    sliding = 2 * d * 64 * 128 + 2 * d * 8 * 128 + d * 64
    assert laguna_flops.attention_params(config, "full_attention") == full
    assert laguna_flops.attention_params(config, "sliding_attention") == sliding
    assert (full, sliding) == (29458432, 37879808)
    assert parts["attention_projections"] == 2 * (2 * full + 3 * sliding)
    # the band: the first 512 queries' triangle, then 512 keys a query
    assert laguna_flops.band_pairs(8192, 512) == 131328 + 7680 * 512 == 4063488
    keys = laguna_flops.keys_per_query(8192, 512)
    assert keys == pytest.approx(496.03, abs=0.005)
    assert laguna_flops.keys_per_query(8192) == 4096.5
    assert laguna_flops.band_pairs(300, 512) == 300 * 301 // 2
    assert parts["full_attention"] == 2 * 4 * 48 * 128 * 4096.5    # 201.3 M
    assert parts["sliding_attention"] == 3 * 4 * 64 * 128 * keys   # 48.8 M
    assert parts["dense_ffn"] == 6 * d * 8192                      # 100.7 M
    assert parts["router"] == 4 * 2 * d * 256
    assert parts["experts"] == 4 * (8 * 32 / 256) * 6 * d * 512    # 25.2 M
    assert parts["shared_experts"] == 4 * 6 * d * 512              # 25.2 M
    assert parts["head"] == 2 * d * 12544                          # 51.4 M
    forward = sum(parts.values())
    assert forward == pytest.approx(802e6, rel=1e-3)  # the issue's figure
    total = laguna_flops.laguna_flops_per_token(config, 8192)
    assert total == 3 * forward == pytest.approx(2.405e9, rel=5e-4)
    share = {k: v / forward for k, v in parts.items()}
    assert share["attention_projections"] == pytest.approx(0.43, abs=0.005)
    assert share["full_attention"] == pytest.approx(0.25, abs=0.005)
    assert share["sliding_attention"] == pytest.approx(0.06, abs=0.003)
    assert sum(share[k] for k in (
        "attention_projections", "full_attention", "sliding_attention")) == (
            pytest.approx(0.74, abs=0.005))
    assert share["dense_ffn"] == pytest.approx(0.13, abs=0.005)
    assert share["head"] == pytest.approx(0.06, abs=0.005)
    # what the window leaves out: the three layers walked as causal ones
    assert 3 * 4 * 64 * 128 * 4096.5 == pytest.approx(403e6, rel=2e-3)


def test_flops_agree_with_the_program_s_own_count():
    from ray_tpu.models.transformer import flops_per_token

    cell = spec.load_cell(spec.ROOT, CELL)
    family = spec.load_code(spec.ROOT, "loops", "laguna")
    assert flops_per_token(family.model_config(cell["config"]), 8192) == (
        pytest.approx(laguna_flops.laguna_flops_per_token(cell["config"], 8192),
                      rel=1e-12))


def test_param_count_and_the_cut_s_arithmetic():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    n = laguna_flops.laguna_param_count(config)
    routed_ff = 524288 + 3145728 + 32 * 3145728
    assert routed_ff == 104333312
    dense = 29458432 + 50331648 + 2 * 2048
    sliding = 37879808 + routed_ff + 2 * 2048
    full = 29458432 + routed_ff + 2 * 2048
    assert (dense, sliding, full) == (79794176, 142217216, 133795840)
    assert n == dense + 3 * sliding + full + 2 * 12544 * 2048 + 2048 == 691623936
    assert 11.0 < 16 * n / 1e9 < 11.1
    assert 0.64 < 16 * n / 16.91e9 < 0.66  # 65 % of the chip, floor 25 %
    family = spec.load_code(spec.ROOT, "loops", "laguna")
    from ray_tpu.models.transformer import transformer_init
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0),
                                 family.model_config(config)))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) == n
    assert "691,623,936" in config["deployment"]


def test_the_whole_model_s_count_sizes_the_gate():
    """33.44 B with a gate a head; a gate as wide as the context or no gate
    at 48 heads everywhere miss the published 33.4 B."""
    published = spec.load_cell(spec.ROOT, CELL)["config"]["catalog_config"]
    per_head = laguna_flops.whole_model_params(published)
    assert per_head / 1e9 == pytest.approx(33.44, abs=0.005)
    assert laguna_flops.whole_model_params(
        published, gate="context") / 1e9 == pytest.approx(34.07, abs=0.005)
    assert laguna_flops.whole_model_params(
        published, gate="none", heads_per_layer=[48] * 40) / 1e9 == (
            pytest.approx(33.19, abs=0.005))
    # attention 1,431 M; 39 routed feed-forwards 31,550 M; dense 50 M;
    # embedding and head 411 M
    attention = 10 * 29458432 + 30 * 37879808
    assert attention / 1e6 == pytest.approx(1431, abs=0.5)
    routed = 39 * (2048 * 256 + 257 * 3 * 2048 * 512)
    assert routed / 1e6 == pytest.approx(31550, abs=0.5)
    assert per_head == (attention + routed + 50331648 + 2 * 100352 * 2048
                        + 2 * 40 * 2048 + 2048)


def test_windowed_kernel_operations_by_hand():
    pairs, bh = 4063488, 2 * 64
    ops, moved = laguna_flops.window_flash_call(
        "flash_fwd", bh, 8192, 512, 128, 128)
    assert ops == 2 * pairs * (128 + 128) * bh
    tensor, row = bh * 8192 * 128, bh * 8192 * 8 * 4
    assert moved == 4 * tensor * 2 + row  # q, k, v, o and lse
    ops, moved = laguna_flops.window_flash_call(
        "flash_bwd_dkv_dq", bh, 8192, 512, 128, 128)
    assert ops == 2 * pairs * 5 * 128 * bh
    assert moved == 4 * tensor * 2 + 2 * row + 3 * tensor * 2
    assert laguna_flops.window_flash_call(
        "flash_bwd_dq", bh, 8192, 512, 128, 128)[0] == 2 * pairs * 3 * 128 * bh
    assert laguna_flops.window_flash_call(
        "flash_bwd_dkv", bh, 8192, 512, 128, 128)[0] == 2 * pairs * 4 * 128 * bh
    # a step's forward pairs are the model's count: 3 layers, 16384 tokens
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    per_call = laguna_flops.window_flash_call(
        "flash_fwd", bh, 8192, 512, 128, 128)[0]
    assert 3 * per_call == pytest.approx(
        16384 * laguna_flops.forward_parts(config, 8192)["sliding_attention"],
        rel=1e-12)
    # a window no shorter than the sequence is the causal count
    assert laguna_flops.window_flash_call(
        "flash_fwd", bh, 512, 512, 128, 128)[0] == 2 * (512 * 513 // 2) * 256 * bh


# ------------------------------------------------------------ the new files

def test_configuration_holds_the_catalog_s_numbers():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    source = config["catalog_config"]
    reduced = {
        "num_hidden_layers": 5, "layer_types": source["layer_types"][:5],
        "num_attention_heads_per_layer": [48, 64, 64, 64, 48],
        "mlp_layer_types": ["dense"] + ["sparse"] * 4, "num_experts": 32,
        "vocab_size": 12544}
    entry = spec.by_name(BENCH["configs"], CONFIG, "config")
    assert entry["reduced"] == config["reduced"] == list(reduced)
    for key, value in source.items():
        assert config[key] == reduced.get(key, value), key
    for key in reduced:
        assert config["published"][key] == source[key], key
    # every width as published, in the keys the program reads
    assert config["d_model"] == source["hidden_size"] == 2048
    assert config["n_heads"] == source["num_attention_heads"] == 48
    assert config["n_heads_sliding"] == 64
    assert config["n_kv_heads"] == source["num_key_value_heads"] == 8
    assert config["d_head"] == source["head_dim"] == 128
    assert config["d_ff_dense"] == source["intermediate_size"] == 8192
    assert config["d_ff"] == source["moe_intermediate_size"] == 512
    assert config["d_ff_shared"] == source["shared_expert_intermediate_size"]
    assert config["n_experts"] == source["num_experts"] == 256
    assert config["experts_per_token"] == source["num_experts_per_tok"] == 8
    assert config["experts_held"] == [0, config["num_experts"]] == [0, 32]
    assert config["routed_scaling_factor"] == source["moe_routed_scaling_factor"]
    assert config["sliding_window"] == source["sliding_window"] == 512
    assert config["attn_gate"] is source["gating"] is True
    assert config["norm_eps"] == source["rms_norm_eps"]
    assert config["tied_embeddings"] == source["tie_word_embeddings"]
    # both rotary recipes, from the published group
    full = source["rope_parameters"]["full_attention"]
    sliding = source["rope_parameters"]["sliding_attention"]
    assert config["rope_theta"] == full["rope_theta"] == 500000
    assert config["partial_rotary_factor"] == full["partial_rotary_factor"] == 0.5
    assert config["rope_scaling"] == {
        k: v for k, v in full.items()
        if k not in ("rope_theta", "partial_rotary_factor")}
    assert config["rope_scaling"]["attention_factor"] == pytest.approx(
        0.1 * math.log(64) + 1)
    assert config["rope_theta_sliding"] == sliding["rope_theta"] == 10000
    assert sliding["partial_rotary_factor"] == 1  # the whole head turns
    # the cut: layers 0 to 4 of the published pattern, heads by layer type
    assert config["n_layers"] == config["num_hidden_layers"] == len(
        config["layer_types"]) == 5
    for kind, heads in zip(config["layer_types"],
                           config["num_attention_heads_per_layer"]):
        assert heads == config[
            "n_heads_sliding" if kind == "sliding_attention" else "n_heads"]
    assert config["n_dense_layers"] == config["mlp_layer_types"].count("dense")
    assert config["published"]["chips_sharing_a_layer"] == 8
    assert config["published"]["layers_held"] == [0, 1, 2, 3, 4]
    assert config["published"]["vocab_size"] == 100352 == 8 * config["vocab_size"]
    assert config["published"]["num_experts"] == 8 * config["num_experts"]
    assert config["source"].startswith(entry["source"])
    assert {"attention_gate", "router", "balance_loss", "norms_and_biases",
            "yarn", "rotary_layout", "window", "initialisers", "optimizer",
            "held_chunk", "remat", "dtype", "deployment", "sequences"} <= set(
                config["assumed"])
    assert config["check"]["seq_len"] >= 4 * config["sliding_window"]


def test_yarn_s_ramp_is_the_configuration_s():
    from chipbench.reference import laguna as reference

    config = spec.load_cell(spec.ROOT, CELL)["config"]
    inv_freq, factor, turned = reference.rotary_tables(config, "full_attention")
    assert turned == 64 and inv_freq.shape == (32,)
    assert factor == config["rope_scaling"]["attention_factor"]
    assert "(low 5, high 16 of 32" in config["assumed"]["yarn"]
    plain = 500000.0 ** (-jnp.arange(32) / 32)
    assert jnp.allclose(inv_freq[:6], plain[:6])           # kept
    assert jnp.allclose(inv_freq[16:], plain[16:] / 64)    # divided by 64
    inv_freq, factor, turned = reference.rotary_tables(
        config, "sliding_attention")
    assert (factor, turned) == (1.0, 128)
    assert jnp.allclose(inv_freq, 10000.0 ** (-jnp.arange(64) / 64))


def test_traffic_mix_is_the_issue_s():
    cell = spec.load_cell(spec.ROOT, CELL)
    traffic = cell["traffic"]
    assert cell["workload"]["traffic"] == "tokens-8k-16k-ep8"
    assert traffic["kind"] == "ingest"
    assert traffic_lib.units_per_step(traffic) == 16384
    assert (traffic["steps_per_chunk"], traffic["blocks_per_epoch"],
            traffic["trace_chunks"], traffic["warmup_steps"],
            traffic["prefetch_batches"], traffic["rows_per_block"],
            traffic["batch_rows"]) == (2, 256, 2, 2, 2, 2, 2)
    rows = traffic_lib.make_rows(
        traffic, {"vocab_size": 12544}, 2**31 + 9, 0, 2)["tokens"]
    assert rows.shape == (2, 8193) and 0 <= rows.min() and rows.max() < 12544


def test_the_cell_s_files_are_found_by_name_under_another_root(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    for kind, name in (("configs", CONFIG + ".json"),
                       ("traffic", "tokens-8k-16k-ep8.json")):
        os.makedirs(os.path.join(root, "chipbench", kind), exist_ok=True)
        shutil.copy(os.path.join(spec.ROOT, "chipbench", kind, name),
                    os.path.join(root, "chipbench", kind, name))
    cell = spec.load_cell(root, CELL)
    assert cell == spec.load_cell(spec.ROOT, CELL)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["config"] == CONFIG
    assert {m["name"] for m in spec.metrics_of(BENCH, CELL, "end_to_end")} == {
        "train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("name", LISTS + ["train_tokens_per_s"])
def test_the_cell_is_in_the_list(name):
    """`in`, never `==`: a test that pins a list to the cells of its day
    breaks at the next cell."""
    kind = "end_to_end" if name == "train_tokens_per_s" else "per_layer"
    entry = spec.by_name(BENCH[kind], name, "metric")
    assert CELL in entry["workloads"]
    assert entry in spec.metrics_of(BENCH, CELL, kind)


def test_the_cell_reports_the_metrics_that_have_no_list():
    named = {m["name"] for m in spec.metrics_of(BENCH, CELL, "per_layer")}
    assert {"gang_boot_s", "state_init_s"} <= named
    assert CELL in {w["name"] for w in BENCH["workloads"]}
    assert CONFIG in {c["name"] for c in BENCH["configs"]}
    assert spec.by_name(BENCH["workloads"], CELL, "workload")["chips"] == 1


def test_flash_window_time_share_reads_the_kernels_by_name():
    entry = spec.by_name(
        BENCH["per_layer"], "flash_window_time_share.tokens", "metric")
    assert CELL in entry["workloads"] and entry["unit"] == "%"
    assert entry["moves"] == "train_tokens_per_s" and entry["better"] == "lower"
    assert entry["layer"] in {
        m["layer"] for m in BENCH["per_layer"] if m is not entry}
    cell = spec.load_cell(spec.ROOT, CELL)
    run_ = dict(fake_summary(cell), chips=1, trace=fake_reduced(1))
    name = "flash_window_time_share.tokens"
    assert spec.read_metric(spec.ROOT, name, run_) == 0.0  # none: 0, no raise
    from chipbench import trace
    ops = [["fusion.1", 0, 400], ["flash_fwd.3 [tpu_custom_call]", 400, 100],
           ["flash_fwd_window.4 [tpu_custom_call]", 500, 100],
           ["flash_bwd_dkv_dq_window.5 [tpu_custom_call]", 600, 150],
           ["flash_bwd_dkv_dq.6 [tpu_custom_call]", 750, 150],
           ["moe_gmm.7 [tpu_custom_call]", 900, 100]]
    run_["trace"] = trace.reduce({"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_step", 0, 1000]]}}, "host_spans": []})
    assert spec.read_metric(spec.ROOT, name, run_) == pytest.approx(25.0)
    # the live share of all the flash kernels counts the windowed ones too
    assert spec.read_metric(
        spec.ROOT, "flash_time_share.tokens", run_) == pytest.approx(50.0)
    assert spec.read_metric(spec.ROOT, name, {"trace": None}) is None


@pytest.mark.parametrize("name", WAITING)
def test_waiting_metrics_carry_their_entry(name):
    """Under the key `awaits`, as PR 27's, PR 32's, PR 34's and PR 38's are."""
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry = held["awaits"]
    assert "entry" not in held
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert entry["name"] == name and entry["unit"] == "%"
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_tokens_per_s"
    assert entry["better"] == "higher"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert name not in {m["name"] for m in BENCH["per_layer"]}  # still waiting
    cell = spec.load_cell(spec.ROOT, CELL)
    config, params = cell["config"], held["params"]
    untraced = dict(fake_summary(cell), chips=1, trace=None)
    assert spec.read_metric(spec.ROOT, name, untraced) is None
    bare = dict(untraced, trace=fake_reduced(1))  # no such event: no raise
    assert spec.read_metric(spec.ROOT, name, bare) is None
    if held["reader"] == "gmm_roofline":
        first, n = config["experts_held"]
        assert params["experts"] == n == 32
        assert params["experts_per_token"] == (
            config["experts_per_token"] * n / config["n_experts"]) == 1.0
        d, f = config["d_model"], config["d_ff"]
        assert sorted(map(tuple, params["products"])) == sorted(
            [(d, f), (d, f), (f, d)])
    else:
        assert held["reader"] == "window_roofline"
        assert params["n_heads"] == config["n_heads_sliding"]
        assert params["window"] == config["sliding_window"]
        assert params["qk_dim"] == params["v_dim"] == config["d_head"]
        assert params["seq_len"] == cell["traffic"]["units_per_row"]


def test_window_roofline_reads_the_kernels_by_name():
    """One call of each windowed kernel at twice the time the chip's peak
    would need: the share reads 50; the causal kernels' events are not
    counted."""
    from chipbench import flops, kernel_flops, trace

    cell = spec.load_cell(spec.ROOT, CELL)
    peaks = flops.peaks_for("TPU v5 lite")
    kernels = ("flash_fwd", "flash_bwd_dkv_dq")
    least = {k: kernel_flops.least_seconds(
        *laguna_flops.window_flash_call(k, 128, 8192, 512, 128, 128), peaks)[0]
        for k in kernels}
    ops, at = [["flash_fwd.1 [tpu_custom_call]", 0, 1000],
               ["flash_bwd_dkv_dq.2 [tpu_custom_call]", 1000, 1000]], 2000
    for number, kernel in enumerate(kernels):
        ns = round(2 * least[kernel] * 1e9)
        ops.append([f"{kernel}_window.{number + 3} [tpu_custom_call]", at, ns])
        at += ns
    reduced = trace.reduce({"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_step", 0, at]]}}, "host_spans": []})
    summary = fake_summary(cell)
    for chunk in summary["chunks"]:
        chunk.update(steps=1, units=16384)
    run_ = dict(summary, chips=1, trace=reduced)
    for kernel in kernels:
        value = spec.read_metric(
            spec.ROOT, f"{kernel}_roofline.window.tokens", run_)
        assert value == pytest.approx(50.0, rel=1e-6), kernel


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(spec.ROOT, "chipbench", "reference", "laguna.py")
    with open(path) as f:
        source = f.read()
    assert "ray_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "(i - j < window)" in source and "mask = j <= i" in source
