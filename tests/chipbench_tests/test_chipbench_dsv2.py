"""The `deepseek_v2` family of the benchmark on the CPU: the model against its
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

from chipbench import compare, dsv2_flops, kernel_flops, loop, mla_flops, run, spec
from chipbench import traffic as traffic_lib
from chipbench_tiny import fake_reduced, fake_summary, run_loop_here

CELL = "dsv2lite.tokens8k"
BENCH = spec.load_benchmark(spec.ROOT)
FAULTS = [
    "sigma_without_mscale", "plain_rope_frequencies", "rotary_over_all_columns",
    "kv_norm_left_out", "a_rotary_key_per_head", "shared_expert_left_out",
    "shared_expert_weighted_by_a_score", "weights_renormalised",
    "balance_loss_over_the_batch", "bf16_everything"]


def tiny_dsv2(dtype="bfloat16", **over):
    """64 wide, a dense layer and two routed ones, 4 of 16 experts held, 3 a
    token, 2 shared, heads of 16 + 8 rotary and 16, sequences of 64."""
    cell = copy.deepcopy(spec.load_cell(spec.ROOT, CELL))
    config, traffic = cell["config"], cell["traffic"]
    config.update(vocab_size=256, d_model=64, n_heads=4, d_ff=32, d_ff_dense=96,
                  max_seq_len=64, n_layers=3,
                  layer_types=["latent_attention"] * 3, n_experts=16,
                  experts_held=[4, 4], experts_per_token=3, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
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

def _wrong_latent_layer(fault):
    """`_latent_attention_layer` with one thing wrong: rotary positions over
    all of a head's columns, no norm on the latent, or a rotary key of its
    own for every head (the shared one, its columns turned by the head's
    number)."""
    from ray_tpu.models import transformer
    from ray_tpu.ops.fused import fused_rmsnorm

    def layer(x, blk, positions, cfg, mesh=None, keep_ctx=False):
        B, T, d = x.shape
        h, r = cfg.n_heads, cfg.kv_lora_rank
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        dt = cfg.dtype
        scaling = dict(cfg.rope_scaling)
        turn = lambda a: transformer._rope(  # noqa: E731
            a, positions, cfg.rope_theta, scaling)
        y = fused_rmsnorm(x, blk["attn_norm"], eps=cfg.norm_eps)
        q = (y @ blk["wq"].astype(dt)).reshape(B, T, h, nope + rope)
        down = y @ blk["wkv_a"].astype(dt)
        latent = down[..., :r]
        if fault != "kv_norm_left_out":
            latent = fused_rmsnorm(latent, blk["kv_norm"], eps=cfg.norm_eps)
        kv = (latent @ blk["wkv_b"].astype(dt)).reshape(B, T, h, nope + dv)
        k_pe = jnp.broadcast_to(down[..., None, r:], (B, T, h, rope))
        if fault == "a_rotary_key_per_head":
            k_pe = jnp.stack([jnp.roll(k_pe[:, :, i], i, axis=-1)
                              for i in range(h)], axis=2)
        k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
        if fault == "rotary_over_all_columns":
            q, k = turn(q), turn(k)
        else:
            q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
            k = jnp.concatenate([k[..., :nope], turn(k[..., nope:])], axis=-1)
        o = transformer._attention(
            q, k, kv[..., nope:], cfg, None, 1, mesh, keep_ctx,
            scale=transformer.yarn_softmax_scale(nope + rope, scaling))
        return x + o.reshape(B, T, h * dv) @ blk["wo"].astype(dt)

    return layer


def wrong_systems(cell, family):
    """{name: a context in which to call `family.errors_of`, and the system
    to hand it}: each computes something other than the published model."""
    from ray_tpu.models import transformer
    from ray_tpu.ops import moe

    real_frequencies = transformer.rope_frequencies
    real_routed = transformer._routed_ffn
    renormalised = spec.load_code(spec.ROOT, "loops", "deepseek_v2").build(
        dict(cell["config"], norm_topk_prob=True), cell["traffic"],
        jax.devices()[:1])
    system = family.system_loss_and_readings

    def shared_by_score(y, blk, cfg, mesh=None, bias=None):
        """`_block` adds S(y); this adds (p_1 - 1) S(y) first, p_1 the
        token's largest score: the shared experts weighted by it."""
        out, readings = real_routed(y, blk, cfg, mesh, bias)
        dt = cfg.dtype
        top = jax.nn.softmax(
            y.astype(jnp.float32) @ blk["router"], axis=-1).max(-1)[..., None]
        shared = (jax.nn.silu(y @ blk["ws_gate"].astype(dt))
                  * (y @ blk["ws_up"].astype(dt))) @ blk["ws_down"].astype(dt)
        return out + ((top - 1.0) * shared).astype(out.dtype), readings

    def without_shared(p, b):
        def drop(path, leaf):
            return leaf * 0 if "ws_down" in jax.tree_util.keystr(path) else leaf
        return system(jax.tree_util.tree_map_with_path(drop, p), b)

    def bf16_everything(p, b):
        p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
        loss, readings = system(p, b)
        return loss.astype(jnp.bfloat16).astype(jnp.float32), readings

    def patched(name, value, where=transformer):
        return mock.patch.object(where, name, value)

    wrong_layer = {fault: (patched("_latent_attention_layer",
                                   _wrong_latent_layer(fault)), system)
                   for fault in ("rotary_over_all_columns", "kv_norm_left_out",
                                 "a_rotary_key_per_head")}
    return {
        "sigma_without_mscale": (
            patched("yarn_softmax_scale", lambda width, scaling=None:
                    width ** -0.5), system),
        "plain_rope_frequencies": (
            patched("rope_frequencies", lambda width, theta, scaling=None:
                    real_frequencies(width, theta)), system),
        **wrong_layer,
        "shared_expert_left_out": (contextlib.nullcontext(), without_shared),
        "shared_expert_weighted_by_a_score": (
            patched("_routed_ffn", shared_by_score), system),
        "weights_renormalised": (
            contextlib.nullcontext(), renormalised.system_loss_and_readings),
        "balance_loss_over_the_batch": (
            patched("sequence_balancing_loss", lambda probs, load:
                    moe.load_balancing_loss(
                        probs.reshape(-1, probs.shape[-1]), load.sum(axis=0)),
                    where=moe), system),
        "bf16_everything": (contextlib.nullcontext(), bf16_everything),
    }


# ------------------------------------------------------------ the comparison

def test_tiny_cell_keeps_the_family_and_its_mechanisms():
    config = tiny_dsv2()["config"]
    assert config["family"] == "deepseek_v2"
    assert config["layer_types"] == ["latent_attention"] * 3
    assert (config["n_dense_layers"], config["router_score"]) == (1, "softmax")
    assert config["n_shared_experts"] == 2 and config["seq_aux"] is True
    assert config["norm_topk_prob"] is False and config["tied_embeddings"] is False
    assert config["rope_scaling"]["type"] == "yarn"
    assert config["router_aux_loss_coef"] == 0.001


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype):
    cell = tiny_dsv2(dtype)
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    errors = family.check(params, check_batch(cell, family))
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 1e-5
        assert errors["router_flip_share"] == 0.0
        assert errors["aux_loss_rel_err"] < 1e-6
    else:
        assert errors["loss_rel_err"] < 3 * family.tolerance["loss_rel_err"]
        assert errors["router_flip_share"] < 0.05
        assert errors["grad_rel_err"] < 0.08  # three tiny layers round harder
        assert errors["aux_loss_rel_err"] < 2e-3
    assert errors["dropped_slots"] == errors["unrouted_slots"] == 0.0
    assert errors["expert_load_max_over_mean"] >= 1.0
    assert 0 < errors["held_slots_mean"] < 4 * 32 * 3
    assert errors["aux_loss_system"] > 1.0  # two layers' sum, about 1 each


@pytest.mark.parametrize("fault", FAULTS)
def test_wrong_mathematics_is_outside_the_tolerance(fault):
    """In float32, where the stated path agrees to rounding, so that what
    is left is the fault's own: each reads over the bound of the key that
    holds it at the published widths (`loops/deepseek_v2.py` has the chip's
    readings)."""
    cell = tiny_dsv2("float32")
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    batch = check_batch(cell, family)
    patched, system = wrong_systems(cell, family)[fault]
    with patched:
        wrong = family.errors_of(system, params, batch)
    assert not compare.within(wrong, family.tolerance), wrong
    if fault == "balance_loss_over_the_batch":  # held by its own key alone
        assert wrong["aux_loss_rel_err"] > family.tolerance["aux_loss_rel_err"]
        assert wrong["grad_rel_err"] < family.tolerance["grad_rel_err"]
    elif fault == "bf16_everything":
        assert wrong["loss_rel_err"] > family.tolerance["loss_rel_err"]
    else:
        assert wrong["grad_rel_err"] > 2 * family.tolerance["grad_rel_err"], wrong


def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_dsv2()
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
    assert summary["flops_per_unit"] == dsv2_flops.dsv2_flops_per_token(
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
    parts = dsv2_flops.forward_parts(config, 8192)
    d = 2048
    # W_q 2048 x 3072, W_kva 2048 x 576, W_kvb 512 x 4096, W_o 2048 x 2048
    attention = 6291456 + 1179648 + 2097152 + 4194304
    assert dsv2_flops.attention_params(config) == attention == 13762560
    assert parts["attention_projections"] == 6 * 2 * attention     # 165.2 M
    assert parts["attention"] == 6 * 2 * 16 * (192 + 128) * 8193 / 2  # 251.7 M
    assert parts["dense_ffn"] == 6 * d * 10944                     # 134.5 M
    assert parts["router"] == 5 * 2 * d * 64
    assert parts["experts"] == 5 * (6 * 8 / 64) * 6 * d * 1408     # 64.9 M
    assert parts["shared_experts"] == 5 * 6 * d * 2816             # 173.0 M
    assert parts["head"] == 2 * d * 12800                          # 52.4 M
    forward = sum(parts.values())
    assert forward == pytest.approx(843e6, rel=1e-3)  # the issue's figure
    total = dsv2_flops.dsv2_flops_per_token(config, 8192)
    assert total == 3 * forward == pytest.approx(2.530e9, rel=5e-4)
    share = {k: v / forward for k, v in parts.items()}
    assert share["attention"] == pytest.approx(0.30, abs=0.005)
    assert share["attention"] + share["attention_projections"] == (
        pytest.approx(0.49, abs=0.006))
    assert share["shared_experts"] == pytest.approx(0.21, abs=0.006)
    assert share["dense_ffn"] == pytest.approx(0.16, abs=0.005)
    assert share["experts"] == pytest.approx(0.08, abs=0.004)
    assert share["head"] == pytest.approx(0.06, abs=0.003)
    assert 32768 * total == pytest.approx(82.9e12, rel=5e-3)  # a step


def test_flops_agree_with_the_program_s_own_count():
    from ray_tpu.models.transformer import flops_per_token

    cell = spec.load_cell(spec.ROOT, CELL)
    family = spec.load_code(spec.ROOT, "loops", "deepseek_v2")
    assert flops_per_token(family.model_config(cell["config"]), 8192) == (
        pytest.approx(dsv2_flops.dsv2_flops_per_token(cell["config"], 8192),
                      rel=1e-12))


def test_param_count_and_the_cut_s_arithmetic():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    n = dsv2_flops.dsv2_param_count(config)
    attention = 13762560 + 512  # with the latent's norm
    dense = attention + 3 * 2048 * 10944 + 2 * 2048
    routed = attention + 8 * 8650752 + 17301504 + 131072 + 2 * 2048
    assert dense == 81007104 and routed == 100405760
    assert n == dense + 5 * routed + 2 * 12800 * 2048 + 2048 == 635466752
    assert 16 * n / 1e9 == pytest.approx(10.17, abs=0.01)
    assert 0.59 < 16 * n / 16.91e9 < 0.61  # 60 % of the chip, floor 25 %
    family = spec.load_code(spec.ROOT, "loops", "deepseek_v2")
    from ray_tpu.models.transformer import transformer_init
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0),
                                 family.model_config(config)))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) == n


def test_kernel_operations_at_two_widths_by_hand():
    pairs = 8192 * 8193 // 2
    assert kernel_flops.causal_pairs(8192) == pairs
    bh = 4 * 16
    ops, moved = mla_flops.flash_call("flash_fwd", bh, 8192, 192, 128)
    assert ops == 2 * pairs * (192 + 128) * bh
    qk, vo, row = bh * 8192 * 192, bh * 8192 * 128, bh * 8192 * 8 * 4
    assert moved == (2 * qk + 2 * vo) * 2 + row  # q, k, v, o and lse
    ops, moved = mla_flops.flash_call("flash_bwd_dq", bh, 8192, 192, 128)
    assert ops == 2 * pairs * (2 * 192 + 128) * bh
    assert moved == (2 * qk + 2 * vo) * 2 + 2 * row + qk * 4  # dq in f32
    ops, moved = mla_flops.flash_call("flash_bwd_dkv", bh, 8192, 192, 128)
    assert ops == 2 * pairs * (2 * 192 + 2 * 128) * bh
    assert moved == (2 * qk + 2 * vo) * 2 + 2 * row + (qk + vo) * 4
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):  # one width
        assert mla_flops.flash_call(kernel, bh, 4096, 128, 128) == (
            kernel_flops.flash_call(kernel, bh, 4096, 128))
    # a step's forward pairs are the model's count: 6 layers, 32768 tokens
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    per_call = mla_flops.flash_call("flash_fwd", bh, 8192, 192, 128)[0]
    assert 6 * per_call == pytest.approx(
        32768 * dsv2_flops.forward_parts(config, 8192)["attention"], rel=1e-12)


# ------------------------------------------------------------ the new files

def test_configuration_holds_the_catalog_s_numbers():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    source = config["catalog_config"]
    reduced = {"num_hidden_layers": 6, "n_routed_experts": 8, "vocab_size": 12800}
    entry = spec.by_name(BENCH["configs"], "deepseek-v2-lite-ep8", "config")
    assert entry["reduced"] == config["reduced"] == list(reduced)
    for key, value in source.items():
        assert config[key] == reduced.get(key, value), key
    # every width as published, in the keys the program reads
    assert config["d_model"] == source["hidden_size"] == 2048
    assert config["n_heads"] == source["num_attention_heads"] == 16
    assert (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["kv_lora_rank"]) == (128, 64, 128, 512)
    assert config["d_ff_dense"] == source["intermediate_size"] == 10944
    assert config["d_ff"] == source["moe_intermediate_size"] == 1408
    assert config["n_experts"] == source["n_routed_experts"] == 64
    assert config["experts_per_token"] == source["num_experts_per_tok"] == 6
    assert config["n_shared_experts"] == source["n_shared_experts"] == 2
    assert config["experts_held"] == [0, config["n_routed_experts"]]
    assert config["n_dense_layers"] == source["first_k_dense_replace"] == 1
    assert config["norm_eps"] == source["rms_norm_eps"]
    assert config["rope_scaling"] == source["rope_scaling"]
    assert config["tied_embeddings"] == source["tie_word_embeddings"]
    assert config["router_score"] == source["scoring_func"]
    assert config["n_layers"] == config["num_hidden_layers"] == len(
        config["layer_types"])
    assert set(config["layer_types"]) == {"latent_attention"}
    assert config["published"]["chips_sharing_a_layer"] == 8
    assert config["published"]["n_routed_experts"] == 64
    assert config["published"]["vocab_size"] == 102400 == 8 * config["vocab_size"]
    assert config["source"].startswith(entry["source"])
    assert {"aux_loss_alpha", "yarn", "sigma", "rotary_layout", "balance_losses",
            "optimizer", "remat", "dtype", "deployment"} <= set(config["assumed"])
    assert config["check"] == {"rows": 2, "seq_len": 1024}
    assert "635,466,752" in config["deployment"]


def test_traffic_mix_is_the_issue_s():
    traffic = spec.load_cell(spec.ROOT, CELL)["traffic"]
    assert traffic["kind"] == "ingest"
    assert traffic_lib.units_per_step(traffic) == 32768
    assert (traffic["steps_per_chunk"], traffic["blocks_per_epoch"],
            traffic["trace_chunks"], traffic["warmup_steps"],
            traffic["prefetch_batches"], traffic["rows_per_block"],
            traffic["batch_rows"]) == (1, 128, 3, 2, 2, 4, 4)
    rows = traffic_lib.make_rows(
        traffic, {"vocab_size": 12800}, 2**31 + 9, 0, 4)["tokens"]
    assert rows.shape == (4, 8193) and 0 <= rows.min() and rows.max() < 12800


def test_the_cell_s_files_are_found_by_name_under_another_root(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    for kind, name in (("configs", "deepseek-v2-lite-ep8.json"),
                       ("traffic", "tokens-8k-32k-c1.json")):
        os.makedirs(os.path.join(root, "chipbench", kind), exist_ok=True)
        shutil.copy(os.path.join(spec.ROOT, "chipbench", kind, name),
                    os.path.join(root, "chipbench", kind, name))
    cell = spec.load_cell(root, CELL)
    assert cell == spec.load_cell(spec.ROOT, CELL)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["traffic"] == "tokens-8k-32k-c1"
    named = {m["name"] for m in spec.metrics_of(BENCH, CELL, "per_layer")}
    assert {"model_mfu.tokens", "moe_gmm_time_share.tokens", "peak_hbm_gb.tokens",
            "device_idle_share.tokens", "pallas_time_share.tokens",
            "flash_time_share.tokens", "ingest_wait_share.tokens",
            "steady_rate.tokens", "stall_share.tokens"} <= named
    assert {m["name"] for m in spec.metrics_of(BENCH, CELL, "end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    assert BENCH["workloads"][-1]["name"] == CELL  # appended, the last
    assert BENCH["configs"][-1]["name"] == "deepseek-v2-lite-ep8"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_flash_time_share_reads_the_kernels_by_name():
    entry = spec.by_name(BENCH["per_layer"], "flash_time_share.tokens", "metric")
    assert entry["workloads"] == [CELL] and entry["unit"] == "%"
    assert entry["moves"] == "train_tokens_per_s" and entry["better"] == "lower"
    cell = spec.load_cell(spec.ROOT, CELL)
    run_ = dict(fake_summary(cell), chips=1, trace=fake_reduced(1))
    assert spec.read_metric(spec.ROOT, "flash_time_share.tokens", run_) == 0.0
    from chipbench import trace
    ops = [["fusion.1", 0, 500], ["flash_fwd.3 [tpu_custom_call]", 500, 100],
           ["flash_bwd_dq.4 [tpu_custom_call]", 600, 100],
           ["flash_bwd_dkv.5 [tpu_custom_call]", 700, 200],
           ["moe_gmm.6 [tpu_custom_call]", 900, 100]]
    run_["trace"] = trace.reduce({"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_step", 0, 1000]]}}, "host_spans": []})
    assert spec.read_metric(
        spec.ROOT, "flash_time_share.tokens", run_) == pytest.approx(40.0)
    assert spec.read_metric(
        spec.ROOT, "flash_time_share.tokens", {"trace": None}) is None


@pytest.mark.parametrize("name", [
    "flash_fwd_roofline.mla.tokens", "flash_bwd_dq_roofline.mla.tokens",
    "flash_bwd_dkv_roofline.mla.tokens", "moe_gmm_roofline.dsv2.tokens",
    "moe_tgmm_roofline.dsv2.tokens", "latent_attention_time_share.tokens",
    "moe_shared_time_share.tokens"])
def test_waiting_metrics_carry_their_entry(name):
    """Under the key `awaits`, as PR 27's and PR 32's are."""
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry = held["awaits"]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert entry["name"] == name and entry["unit"] == "%"
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_tokens_per_s"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert name not in {m["name"] for m in BENCH["per_layer"]}  # still waiting
    cell = spec.load_cell(spec.ROOT, CELL)
    config, params = cell["config"], held["params"]
    untraced = dict(fake_summary(cell), chips=1, trace=None)
    assert spec.read_metric(spec.ROOT, name, untraced) is None
    # the fixture's trace has no name stacks: nothing to read, and no raise
    bare = dict(untraced, trace=fake_reduced(1))
    if held["reader"] == "gmm_roofline":
        first, n = config["experts_held"]
        assert params["experts"] == n
        assert params["experts_per_token"] == (
            config["experts_per_token"] * n / config["n_experts"]) == 0.75
        d, f = config["d_model"], config["d_ff"]
        assert sorted(map(tuple, params["products"])) == sorted(
            [(d, f), (d, f), (f, d)])
    elif held["reader"] == "mla_roofline":
        assert spec.read_metric(spec.ROOT, name, bare) is None
        assert params["n_heads"] == config["n_heads"]
        assert params["qk_dim"] == (config["qk_nope_head_dim"]
                                    + config["qk_rope_head_dim"])
        assert params["v_dim"] == config["v_head_dim"]
        assert params["seq_len"] == cell["traffic"]["units_per_row"]
        assert entry["better"] == "higher"
    else:
        assert spec.read_metric(spec.ROOT, name, bare) is None
        assert params["scope"] in ("latent_attention", "moe_shared")
        assert entry["better"] == "lower"


def test_mla_roofline_reads_a_trace_with_name_stacks():
    """One call of each kernel, at the time the chip's peak would need: the
    share reads 100; twice the time, 50."""
    from chipbench import flops, trace

    cell = spec.load_cell(spec.ROOT, CELL)
    peaks = flops.peaks_for("TPU v5 lite")
    names = {"flash_fwd": "flash_fwd.3", "flash_bwd_dq": "flash_bwd_dq.4",
             "flash_bwd_dkv": "flash_bwd_dkv.5"}
    least = {k: kernel_flops.least_seconds(
        *mla_flops.flash_call(k, 64, 8192, 192, 128), peaks)[0] for k in names}
    ops, at = [], 0
    for kernel, name in names.items():
        ns = round(2 * least[kernel] * 1e9)
        ops.append([f"{name} [tpu_custom_call]", at, ns])
        at += ns
    reduced = trace.reduce({"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_step", 0, at]]}}, "host_spans": []})
    reduced["name_stacks"] = {
        f"{name} [tpu_custom_call]":
            f"jit(step)/jvp(latent_attention)/attention/{kernel}/pallas_call"
        for kernel, name in names.items()}
    summary = fake_summary(cell)
    for chunk in summary["chunks"]:
        chunk.update(steps=1, units=32768)
    run_ = dict(summary, chips=1, trace=reduced)
    for kernel in names:
        value = spec.read_metric(
            spec.ROOT, f"{kernel}_roofline.mla.tokens", run_)
        assert value == pytest.approx(50.0, rel=1e-6), kernel
