"""The `lfm2_moe` family of the benchmark on the CPU: the model against its
plain reference at a tiny size, each wrong mathematics that has to fall
outside `TOLERANCE`, the tiny cell's loop end to end, the operation counts by
hand, and the new files' form. `wrong_systems` is also what the builder's
chip run takes its wrong mathematics from, at the published widths."""

import contextlib
import copy
import json
import math
import os
import shutil
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare, lfm2_flops, loop, run, spec
from chipbench import traffic as traffic_lib
from chipbench_tiny import run_loop_here

CELL = "lfm2moe.tokens8k"
BENCH = spec.load_benchmark(spec.ROOT)


def tiny_lfm2(dtype="bfloat16", **over):
    """64 wide, 5 layers as the cut has them, 4 of 16 experts held, 2 a
    token, sequences of 64."""
    cell = copy.deepcopy(spec.load_cell(spec.ROOT, CELL))
    config, traffic = cell["config"], cell["traffic"]
    config.update(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, d_ff=32,
                  d_ff_dense=96, max_seq_len=64, n_experts=16,
                  experts_held=[4, 4], experts_per_token=2, dtype=dtype,
                  check=dict(config["check"], rows=4, seq_len=32), **over)
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

def _biased_weights(logits, k, renormalize=False, *, score="softmax",
                    bias=None, eps=0.0):
    """The weights taken from the biased score."""
    probs = jax.nn.sigmoid(logits.astype(jnp.float32))
    weights, index = jax.lax.top_k(probs + bias, k)
    return probs, weights / (weights.sum(-1, keepdims=True) + eps), index


def _late_conv(x, blk, cfg):
    """Every tap one token late: u_{t-3}, u_{t-2}, u_{t-1}."""
    from ray_tpu.ops.fused import fused_rmsnorm

    dt = cfg.dtype
    y = fused_rmsnorm(x, blk["conv_norm"], eps=cfg.norm_eps)
    b, c, xs = jnp.split(y @ blk["conv_in"].astype(dt), 3, axis=-1)
    u = jnp.pad(b * xs, ((0, 0), (3, 0), (0, 0)))
    w = blk["conv_w"].astype(dt)
    conv = sum(w[i] * u[:, i:i + x.shape[1]] for i in range(3))
    return (c * conv) @ blk["conv_out"].astype(dt)


def _norm_over_the_projection(x, weight, *, eps=1e-6):
    """q and k normed over all their heads together."""
    from ray_tpu.ops.fused import fused_rmsnorm

    if x.ndim != 4:
        return fused_rmsnorm(x, weight, eps=eps)
    flat = x.reshape(*x.shape[:2], -1)
    scale = jnp.tile(weight, x.shape[2])
    return fused_rmsnorm(flat, scale, eps=eps).reshape(x.shape)


def wrong_systems(cell, family):
    """{name: a context in which to call `family.errors_of`, and the system
    to hand it}: each computes something other than the published model."""
    from ray_tpu.models import transformer
    from ray_tpu.ops import moe

    real_route = moe.route
    undivided = spec.load_code(spec.ROOT, "loops", "lfm2_moe").build(
        dict(cell["config"], norm_topk_prob=False), cell["traffic"],
        jax.devices()[:1])

    def bf16_everything(p, b, bias):
        p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
        loss, readings = family.system_loss_and_readings(p, b, bias)
        return loss.astype(jnp.bfloat16).astype(jnp.float32), readings

    system = family.system_loss_and_readings
    return {
        "weights_from_the_biased_score": (
            mock.patch.object(moe, "route", _biased_weights), system),
        "bias_ignored": (
            mock.patch.object(moe, "route", lambda *a, **kw: real_route(
                *a, **{**kw, "bias": None})), system),
        "weights_not_divided_by_their_sum": (
            contextlib.nullcontext(), undivided.system_loss_and_readings),
        "norm_over_the_projection": (
            mock.patch.object(transformer, "fused_rmsnorm",
                              _norm_over_the_projection), system),
        "convolution_one_tap_late": (
            mock.patch.object(transformer, "_short_conv", _late_conv), system),
        "bf16_everything": (contextlib.nullcontext(), bf16_everything),
    }


# ------------------------------------------------------------ the comparison

def test_tiny_cell_keeps_the_family_and_its_mechanisms():
    config = tiny_lfm2()["config"]
    assert config["family"] == "lfm2_moe"
    assert config["layer_types"] == ["conv", "full_attention", "conv", "conv", "conv"]
    assert (config["n_dense_layers"], config["router_score"]) == (1, "sigmoid")
    assert config["expert_bias"] is True and config["qk_norm"] == "head"
    assert config["norm_topk_prob"] is True and config["tied_embeddings"] is True


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype):
    cell = tiny_lfm2(dtype)
    family = family_of(cell)
    made = family.init_params(loop.seed_key(2**31 + 3))
    assert float(jnp.abs(made["expert_bias"]).max()) > 0.1  # a drawn bias
    errors = family.check(made, check_batch(cell, family))
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 1e-5
        assert errors["router_flip_share"] == 0.0
    else:
        assert errors["loss_rel_err"] < family.tolerance["loss_rel_err"]
        assert errors["router_flip_share"] < 0.05
        assert errors["grad_rel_err"] < 0.06  # five tiny layers round harder
    assert errors["dropped_slots"] == errors["unrouted_slots"] == 0.0
    assert errors["expert_load_max_over_mean"] >= 1.0
    assert 0 < errors["held_slots_mean"] < 4 * 32 * 2


def test_training_starts_from_a_zero_bias_and_the_comparison_from_a_drawn_one():
    cell = tiny_lfm2("float32")
    family = family_of(cell)
    made = family.init_params(loop.seed_key(5))
    state = family.init_state(made)
    assert state["expert_bias"].shape == (4, 16)
    assert float(jnp.abs(state["expert_bias"]).max()) == 0.0
    assert state["params"] is made["params"]
    batch = check_batch(cell, family)
    state, out = family.step(state, batch)
    assert float(jnp.abs(state["expert_bias"]).max()) == pytest.approx(1e-3)
    assert float(out["expert_bias_abs_max"]) == pytest.approx(1e-3)
    assert out["expert_load"].shape == (4, 16) and out["held_slots"].shape == (4,)
    assert int(out["dropped_slots"].sum()) == 0
    assert int(out["expert_load"].sum()) == 4 * 4 * 32 * 2


@pytest.mark.parametrize("fault", [
    "weights_from_the_biased_score", "bias_ignored",
    "weights_not_divided_by_their_sum", "norm_over_the_projection",
    "convolution_one_tap_late", "bf16_everything"])
def test_wrong_mathematics_is_outside_the_tolerance(fault):
    cell = tiny_lfm2()
    family = family_of(cell)
    made = family.init_params(loop.seed_key(2**31 + 3))
    batch = check_batch(cell, family)
    patched, system = wrong_systems(cell, family)[fault]
    with patched:
        wrong = family.errors_of(system, made, batch)
    assert not compare.within(wrong, family.tolerance), wrong
    if fault == "bias_ignored":  # held by the choice itself
        assert wrong["router_flip_share"] > 0.2
    elif fault == "weights_from_the_biased_score":  # a bias of 0.1 on scores near 0.6
        assert wrong["grad_rel_err"] > 2 * family.tolerance["grad_rel_err"], wrong
    elif fault != "bf16_everything":  # several times over the bound
        assert wrong["grad_rel_err"] > 3 * family.tolerance["grad_rel_err"], wrong


def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_lfm2()
    reports = run_loop_here(monkeypatch, cell, seconds=0.4)
    summary = reports[-1]
    assert summary["summary"] and summary["steps_failed"] == 0
    assert summary["compiles_in_window"] == 0
    assert summary["steps"] == 2 * len(summary["chunks"]) >= 2
    assert all(math.isfinite(c["loss"]) for c in summary["chunks"])
    reference = summary["reference"]
    assert {"router_flip_share", "held_slots_mean", "dropped_slots"} <= set(reference)
    assert reference["dropped_slots"] == 0.0
    assert summary["flops_per_unit"] == lfm2_flops.lfm2_flops_per_token(
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
    parts = lfm2_flops.forward_parts(config, 8192)
    d = 2048
    assert parts["conv_projections"] == 4 * 8 * d * d        # 134.2 M
    assert parts["attention_projections"] == 2 * d * (2048 + 2 * 512) + 2 * d * d
    assert parts["attention"] == 4 * 2048 * 8193 / 2
    assert parts["dense_ffn"] == 6 * d * 11776               # 144.7 M
    assert parts["router"] == 4 * 2 * d * 64
    assert parts["experts"] == 4 * (4 * 8 / 64) * 6 * d * 1536  # 37.7 M
    assert parts["head"] == 2 * d * 8192
    total = lfm2_flops.lfm2_flops_per_token(config, 8192)
    assert total == pytest.approx(1.218e9, rel=5e-4)  # the hand figure
    share = {k: v / sum(parts.values()) for k, v in parts.items()}
    assert share["dense_ffn"] == pytest.approx(0.36, abs=0.005)
    assert share["experts"] == pytest.approx(0.093, abs=0.003)
    assert share["head"] == pytest.approx(0.083, abs=0.003)
    assert share["attention"] == pytest.approx(0.083, abs=0.003)
    assert share["conv_projections"] + share["attention_projections"] == (
        pytest.approx(0.38, abs=0.005))
    assert 32768 * total == pytest.approx(39.9e12, rel=5e-3)  # a step


def test_flops_agree_with_the_program_s_own_count():
    from ray_tpu.models.transformer import flops_per_token

    cell = spec.load_cell(spec.ROOT, CELL)
    family = spec.load_code(spec.ROOT, "loops", "lfm2_moe")
    assert flops_per_token(family.model_config(cell["config"]), 8192) == (
        pytest.approx(lfm2_flops.lfm2_flops_per_token(cell["config"], 8192),
                      rel=1e-12))


def test_param_count_and_the_cut_s_arithmetic():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    n = lfm2_flops.lfm2_param_count(config)
    assert n == pytest.approx(469.3e6, rel=2e-4)
    assert 16 * n / 1e9 == pytest.approx(7.5, abs=0.02)  # 48 % of 15.75 GB
    assert 16 * n / 15.75e9 > 0.25  # over the floor
    two = lfm2_flops.lfm2_param_count(dict(
        config, layer_types=config["layer_types"] + config["layer_types"][1:]))
    assert 16 * two / 1e9 == pytest.approx(13.3, abs=0.05)  # no room left
    family = spec.load_code(spec.ROOT, "loops", "lfm2_moe")
    from ray_tpu.models.transformer import transformer_init
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0),
                                 family.model_config(config)))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) == n


# ------------------------------------------------------------ the new files

def test_configuration_holds_the_catalog_s_numbers():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    # the catalog row's `config`, whole. Not under OLMoE's key
    # `source_config`: `test_published_widths_are_kept` (the benchmark's)
    # reads that key with Mistral's and OLMoE's names for the widths
    source = config["catalog_config"]
    reduced = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
               "vocab_size": 8192,
               "layer_types": [source["layer_types"][i] for i in (0, 2, 3, 4, 5)]}
    entry = spec.by_name(BENCH["configs"], "lfm2-24b-a2b-l5-ep8", "config")
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(reduced)
    for key, value in source.items():
        assert config[key] == reduced.get(key, value), key
    # every width as published, in the keys the program reads
    assert config["d_model"] == source["hidden_size"]
    assert config["d_ff"] == source["moe_intermediate_size"]
    assert config["d_ff_dense"] == source["intermediate_size"]
    assert config["n_heads"] == source["num_attention_heads"]
    assert config["n_kv_heads"] == source["num_key_value_heads"]
    assert config["conv_taps"] == source["conv_L_cache"]
    assert config["n_experts"] == source["num_experts"] == 64  # the router's width
    assert config["experts_per_token"] == source["num_experts_per_tok"]
    assert config["experts_held"] == [0, config["num_experts"]]
    assert config["norm_eps"] == source["norm_eps"]
    assert config["rope_theta"] == source["rope_parameters"]["rope_theta"]
    assert config["expert_bias"] == source["use_expert_bias"]
    assert config["n_layers"] == config["num_hidden_layers"] == len(config["layer_types"])
    assert config["published"]["chips_sharing_a_layer"] == 8
    assert config["published"]["num_experts"] == 64
    assert config["source"].startswith(entry["source"])
    assert {"tied_embeddings", "norm_topk_eps", "qk_norm", "expert_bias_update_rate",
            "optimizer", "remat", "dtype"} <= set(config["assumed"])


def test_traffic_mix_is_the_issue_s():
    traffic = spec.load_cell(spec.ROOT, CELL)["traffic"]
    assert traffic["kind"] == "ingest"
    assert traffic_lib.units_per_step(traffic) == 32768
    assert (traffic["steps_per_chunk"], traffic["blocks_per_epoch"],
            traffic["trace_chunks"], traffic["warmup_steps"],
            traffic["prefetch_batches"], traffic["rows_per_block"]) == (
                2, 256, 2, 2, 2, 4)
    rows = traffic_lib.make_rows(
        traffic, {"vocab_size": 8192}, 2**31 + 9, 0, 4)["tokens"]
    assert rows.shape == (4, 8193) and 0 <= rows.min() and rows.max() < 8192


def test_the_cell_s_files_are_found_by_name_under_another_root(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    for kind, name in (("configs", "lfm2-24b-a2b-l5-ep8.json"),
                       ("traffic", "tokens-8k-32k.json")):
        os.makedirs(os.path.join(root, "chipbench", kind), exist_ok=True)
        shutil.copy(os.path.join(spec.ROOT, "chipbench", kind, name),
                    os.path.join(root, "chipbench", kind, name))
    cell = spec.load_cell(root, CELL)
    assert cell == spec.load_cell(spec.ROOT, CELL)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["traffic"] == "tokens-8k-32k"
    named = {m["name"] for m in spec.metrics_of(BENCH, CELL, "per_layer")}
    assert {"model_mfu.tokens", "moe_gmm_time_share.tokens", "peak_hbm_gb.tokens",
            "device_idle_share.tokens", "pallas_time_share.tokens"} <= named
    assert {m["name"] for m in spec.metrics_of(BENCH, CELL, "end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    assert len(BENCH["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


@pytest.mark.parametrize("name", [
    "moe_gmm_roofline.held.tokens", "moe_tgmm_roofline.held.tokens",
    "short_conv_time_share.tokens"])
def test_waiting_metrics_carry_their_entry(name):
    """Under the key `awaits`, as PR 27's are."""
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry = held["awaits"]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert entry["name"] == name and entry["unit"] == "%"
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_tokens_per_s"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert name not in {m["name"] for m in BENCH["per_layer"]}  # still waiting
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    if held["reader"] == "gmm_roofline":
        first, n = config["experts_held"]
        assert held["params"]["experts"] == n
        assert held["params"]["experts_per_token"] == (
            config["experts_per_token"] * n / config["n_experts"])
        d, f = config["d_model"], config["d_ff"]
        assert sorted(map(tuple, held["params"]["products"])) == sorted(
            [(d, f), (d, f), (f, d)])
    else:
        assert held["params"] == {"scope": "short_conv"}
        assert spec.read_metric(spec.ROOT, name, {"trace": None}) is None
