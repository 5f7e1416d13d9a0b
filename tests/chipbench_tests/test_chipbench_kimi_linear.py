"""The `kimi_linear` family and its cell `kimilinear.tokens16k` (CPU only):
the configuration file's counts and widths against the catalog's row, the
operation counts by hand against the program's, the system against the plain
reference at a tiny size, each wrong mathematics and each lower precision
outside the tolerance, the shares of the routed layer adding up to the uncut
reference's layer, the cell's loop end to end, the last line's keys, and the
files and entries the cell was added by."""

import copy
import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare, kimi_linear_flops, loop, run, spec
from chipbench import traffic as traffic_lib
from chipbench_tiny import fake_reduced, fake_summary, run_loop_here

CELL = "kimilinear.tokens16k"
CONFIG = "kimi-linear-48b-a3b-l5-ep32"
TRAFFIC = "tokens-16k-16k-ep32"
BENCH = spec.load_benchmark(spec.ROOT)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the lists of BENCHMARK.json the cell joins (ISSUE 66, step 6)
LISTS = [
    "ingest_wait_share.tokens", "ingest_produce_share.tokens",
    "steady_rate.tokens", "stall_share.tokens", "model_mfu.tokens",
    "pallas_time_share.tokens", "flash_time_share.tokens",
    "moe_gmm_time_share.tokens", "moe_sum_time_share.tokens",
    "device_idle_share.tokens", "peak_hbm_gb.tokens", "cluster_init_s",
    "compile_s", "first_batch_s", "setup_unnamed_s", "trace_s", "lower_s",
    "pallas_trace_s", "before_first_program_s", "before_init_s"]
# the files that wait under `awaits`: (reader, better)
WAITING = {
    "kda_fwd_roofline.tokens": ("kda_roofline", "higher"),
    "kda_bwd_roofline.tokens": ("kda_roofline", "higher"),
    "flash_fwd_roofline.kimi.tokens": ("mla_roofline", "higher"),
    "moe_gmm_roofline.kimi.tokens": ("gmm_roofline", "higher"),
    "moe_tgmm_roofline.kimi.tokens": ("gmm_roofline", "higher"),
    "kda_time_share.kimi.tokens": ("scope_share", "lower"),
    "latent_attention_time_share.kimi.tokens": ("scope_share", "lower"),
}
FAULTS = [
    "beta_2_sigmoid", "columns_rotated", "scaling_factor_dropped",
    "weights_not_normalised", "weights_from_biased_scores", "bias_ignored",
    "latent_norm_dropped", "taps_dropped", "kda_float32_parts_in_bf16",
    "bf16_everything"]


def held_config():
    return spec.read_json(spec.ROOT, "chipbench", "configs", CONFIG + ".json")


def tiny_kimi(dtype="bfloat16", **over):
    """64 wide: KDA (dense feed-forward of 96), KDA, KDA, latent attention
    (4 heads of 16 + 8 and 16 over a latent of 32), KDA; KDA of 4 heads of
    16, chunks of 32; 4 of 16 experts held, 4 a token, one shared, the
    sigmoid router with its bias and the factor 2.446; sequences of 64,
    compared at 48 (two chunks, the second ragged)."""
    cell = copy.deepcopy(spec.load_cell(spec.ROOT, CELL))
    config, traffic = cell["config"], cell["traffic"]
    config.update(vocab_size=256, d_model=64, n_heads=4, kda_heads=4,
                  kda_head_dim=16, kda_gate_rank=8, kda_chunk=32,
                  kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16, d_ff=32, d_ff_dense=96, n_experts=16,
                  experts_held=[4, 4], experts_per_token=4, max_seq_len=64,
                  dtype=dtype, check={"rows": 2, "seq_len": 48,
                                      "expert_bias_std": 0.1},
                  start={"rounds": 24, "rate_first": 0.05,
                         "rate_last": 0.005}, **over)
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


# ----------------------------------------------- the configuration's file

def test_the_file_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog's `config` stands in the file under the
    same key with the same value, but for the four of `reduced`; of the
    nested group that is among them only the two layer lists differ. No
    width is among the four."""
    held = held_config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert held["source"].startswith(row["source_url"])
    entry = spec.by_name(BENCH["configs"], CONFIG, "config")
    assert entry["source"] == row["source_url"]
    assert held["catalog_config"] == row["config"]
    reduced = set(held["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size",
                       "linear_attn_config"} == set(entry["reduced"])
    for key, value in row["config"].items():
        if key in reduced:
            assert held[key] != value and held["published"][key] == value
        else:
            assert held[key] == value, key
    assert (held["num_hidden_layers"], held["num_experts"],
            held["vocab_size"]) == (5, 8, 20480)
    linear, published = held["linear_attn_config"], row["config"][
        "linear_attn_config"]
    assert linear == {**published, "kda_layers": [1, 2, 3, 5],
                      "full_attn_layers": [4]}
    # the chip's five are the model's first five, kinds and all
    assert [i in published["full_attn_layers"] for i in range(1, 6)] == [
        kind == "latent_attention" for kind in held["layer_types"]]
    assert "layer lists" in held["published"]["linear_attn_config_note"]


def test_every_width_the_program_runs_is_the_published_one():
    held = held_config()
    row = held["catalog_config"]
    linear = row["linear_attn_config"]
    assert held["d_model"] == row["hidden_size"] == 2304
    assert (held["kda_heads"], held["kda_head_dim"], held["kda_conv_taps"]) == (
        linear["num_heads"], linear["head_dim"],
        linear["short_conv_kernel_size"]) == (32, 128, 4)
    assert held["kda_gate_rank"] == linear["head_dim"]
    assert held["kda_allow_neg_eigval"] is False and "heads_held" not in held
    assert held["n_heads"] == row["num_attention_heads"] == 32
    assert (held["kv_lora_rank"], held["qk_nope_head_dim"],
            held["qk_rope_head_dim"], held["v_head_dim"]) == (512, 128, 64, 128)
    assert row["q_lora_rank"] is None and row["rope_scaling"] is None
    assert row["mla_use_nope"] is True and held["rope"] is False
    assert held["d_ff"] == row["moe_intermediate_size"] == 1024
    assert held["d_ff_dense"] == row["intermediate_size"] == 9216
    assert held["n_dense_layers"] == row["first_k_dense_replace"] == 1
    assert held["n_shared_experts"] == row["num_shared_experts"] == 1
    assert held["n_experts"] == row["num_experts"] == 256
    assert held["experts_per_token"] == row["num_experts_per_token"] == 8
    assert held["router_score"] == row["moe_router_activation_func"] == "sigmoid"
    assert held["expert_bias"] is True
    assert held["norm_topk_prob"] is row["moe_renormalize"] is True
    assert held["routed_scaling_factor"] == row["routed_scaling_factor"] == 2.446
    assert (row["num_expert_group"], row["topk_group"]) == (1, 1)
    assert held["norm_eps"] == row["rms_norm_eps"]
    assert held["tied_embeddings"] is row["tie_word_embeddings"] is False
    assert held["layer_types"] == ["kda", "kda", "kda", "latent_attention",
                                   "kda"]
    assert held["router_aux_loss_coef"] == held["router_z_loss_coef"] == 0.0
    # the shares: 8 of 256 experts, an eighth of the ids, every head
    assert held["experts_held"] == [0, 8]
    assert held["vocab_size"] * 8 == row["vocab_size"]
    ways = held["published"]["ways"]
    assert held["published"]["chips_sharing_a_layer"] == 32 == ways["experts"]
    assert (ways["heads"], ways["vocab"]) == (1, 8)
    assert 32 * held["experts_held"][1] == row["num_experts"]
    for word in ("assumed", "deployment", "check", "mesh", "optimizer"):
        assert word in held
    assert held["start"] == {"rounds": 100, "rate_first": 0.03,
                             "rate_last": 0.002}
    for key in ("kda", "kda_gates", "kda_bias", "kda_decay", "kda_beta",
                "kda_scale", "kda_norms", "kda_chunk", "mla", "mla_use_nope",
                "head_dim", "router", "balance", "sequence", "optimizer",
                "initialisers", "dtype", "remat"):
        assert key in held["assumed"], key
    assert held["check"] == {"rows": 1, "seq_len": 2048,
                             "expert_bias_std": 0.1}
    assert held["mesh"] == {"data": 1} and held["max_seq_len"] == 16384
    assert held["optimizer"]["no_decay"] == [
        "A_log", "dt_bias", "kda_conv", "g_bias", "norm"]


def test_the_state_is_602449792_parameters_9_64_gb():
    """The count by hand, the count of the program's own leaves, and the
    words of `deployment` agree."""
    from ray_tpu.models.transformer import transformer_init

    held = held_config()
    by_hand = kimi_linear_flops.state_params(held)
    assert by_hand == 602_449_792
    assert round(16 * by_hand / 1e9, 2) == 9.64
    family = spec.load_code(spec.ROOT, "loops", "kimi_linear")
    cfg = family.model_config(held)
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == by_hand
    (dense,), (kda_layer, _, mla_layer, last) = shapes["blocks"]

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    assert sum(x.size for name, x in kda_layer.items()
               if name.startswith("kda") and name != "kda_norm") == 39_518_368
    assert sum(mla_layer[name].size for name in (
        "wq", "wkv_a", "kv_norm", "wkv_b", "wo")) == 29_114_880
    assert sum(dense[name].size for name in (
        "w_gate", "w_up", "w_down")) == 63_700_992
    assert kda_layer["w_gate"].size // 8 * 3 == 7_077_888
    assert kda_layer["router"].size == 589_824
    assert count(dense) == 103_223_968
    assert count(kda_layer) == count(last) == 103_813_792
    assert count(mla_layer) == 93_410_304
    assert shapes["embed"].size == shapes["unembed"].size == 47_185_920
    for number in ("602,449,792", "39,518,368", "29,114,880", "63,700,992",
                   "7,077,888", "589,824", "103,223,968", "103,813,792",
                   "93,410,304", "47,185,920", "9.64 GB"):
        assert number in held["deployment"], number


def test_operations_by_hand_are_the_programs():
    """`kimi_linear_flops.py` counts from the shapes; the program counts
    from its records: the same number, part by part."""
    from ray_tpu.models.transformer import _fwd_flops_per_token, flops_per_token

    held = held_config()
    cfg = spec.load_code(spec.ROOT, "loops", "kimi_linear").model_config(held)
    parts = kimi_linear_flops.forward_parts(held, 16384)
    matmul, attention, head = _fwd_flops_per_token(cfg, 16384)
    assert head == parts["head"] == 2 * 2304 * 20480
    assert attention == parts["latent_pairs"] == 2 * 32 * 320 * 16385 / 2
    assert matmul == pytest.approx(
        sum(parts.values()) - parts["head"] - parts["latent_pairs"], rel=1e-12)
    assert kimi_linear_flops.flops_per_token(held, 16384) == pytest.approx(
        flops_per_token(cfg, 16384), rel=1e-12)
    # by hand: a head and token of the chunked form at C 64, d 128
    assert parts["kda_chunked"] == 4 * 32 * (10 * 64 * 128 + 6 * 128 * 128)
    assert parts["kda_matmuls"] == 4 * 2 * (
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32)
    assert parts["latent_matmuls"] == 2 * (
        2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304)
    assert parts["dense_ff"] == 2 * 3 * 2304 * 9216
    assert parts["held_experts"] == 4 * (8 * 8 / 256) * 2 * 3 * 2304 * 1024
    assert parts["shared_experts"] == 4 * 2 * 3 * 2304 * 1024
    assert parts["router"] == 4 * 2 * 2304 * 256
    total = sum(parts.values())
    assert 861e6 < total < 863e6  # 431 M multiply-adds
    shares = {name: value / total for name, value in parts.items()}
    # the shares the cell's `why` gives
    assert round(100 * (shares["kda_matmuls"] + shares["kda_chunked"])) == 39
    assert round(100 * (shares["latent_matmuls"] + shares["latent_pairs"])) == 26
    assert round(100 * shares["dense_ff"]) == 15
    assert round(100 * shares["head"]) == 11
    assert round(100 * (shares["shared_experts"] + shares["router"])) == 7
    assert round(100 * shares["held_experts"]) == 2
    cell = spec.by_name(BENCH["workloads"], CELL, "workload")
    for words in ("862 M", "39 %", "26 %", "15 %", "11 %", "512 rows",
                  "9.64 GB"):
        assert words in cell["why"], words
    assert 16384 * 8 * 8 // 256 // 8 == 512


def test_a_kda_call_by_hand():
    """`kda_call` at the cell's shape: the operations of the chunked form,
    and every operand once with its dtype."""
    ops, moved = kimi_linear_flops.kda_call("kda_fwd", 32, 16384, 128, 128, 64)
    assert ops == 32 * 16384 * 180_224
    tokens = 32 * 16384
    states = 32 * 256 * 128 * 128 * 4
    assert states == 536_870_912  # 537 MB a layer, float32
    assert moved == tokens * (256 * 2 + 128 * 2 + 128 * 4 + 4 + 128 * 2) + states
    _, plain = kimi_linear_flops.kda_call(
        "kda_fwd", 32, 16384, 128, 128, 64, states=False)
    assert plain == moved - states
    ops_b, moved_b = kimi_linear_flops.kda_call(
        "kda_bwd", 32, 16384, 128, 128, 64)
    assert ops_b == 3 * ops + tokens * 4 * 64 * 64
    assert moved_b == 2 * tokens * (512 + 256 + 512 + 4) + states + tokens * 256
    with pytest.raises(ValueError):
        kimi_linear_flops.kda_call("kda_out", 32, 16384, 128, 128, 64)
    from chipbench import flops, kernel_flops
    peaks = flops.peaks_for("TPU v5 lite")
    for kernel in ("kda_fwd", "kda_bwd"):  # bandwidth sets both
        assert kernel_flops.least_seconds(*kimi_linear_flops.kda_call(
            kernel, 32, 16384, 128, 128, 64), peaks)[1] == "memory"


# ---------------------------------------------------------- the comparison

def wrong_systems(cell, family):
    """{name: a system to hand `family.errors_of`}: each computes something
    other than the published model, or the stated one in a lower
    precision."""
    from ray_tpu.models import transformer
    from ray_tpu.ops import kda as kda_lib
    from ray_tpu.ops import moe

    cfg, mesh = family.model_config, family.mesh
    system = family.system_loss_and_readings

    def with_cfg(**changed):
        wrong = dataclasses.replace(cfg, **changed)
        return lambda p, b, bias: transformer.transformer_loss_and_readings(
            p, b, wrong, mesh=mesh, expert_bias=bias)

    def patched(module, name, replacement, inner=system):
        def run_patched(*args):
            real = getattr(module, name)
            setattr(module, name, replacement(real))
            try:
                return inner(*args)
            finally:
                setattr(module, name, real)
        return run_patched

    def bf16_everything(p, b, bias):
        p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
        loss, readings = system(p, b, bias)
        return loss.astype(jnp.bfloat16).astype(jnp.float32), readings

    def in_bf16(real):
        return jnp.bfloat16

    # the kernels' float32 is `_ACC`, their own: the `jax.numpy` form,
    # part for part the same mathematics, is the one that can say it
    def probe_in_bf16(*probe):  # the recurrence alone, as the probe runs it
        return kda_lib.kda(*probe, chunk=cfg.kda_chunk, impl="xla")[0]

    def numpy_form(real):
        return lambda *a, **kw: real(*a, **{**kw, "impl": "xla"})

    def biased_weights(real):  # the weights read off score + bias
        def route(logits, k, renormalize=False, **kw):
            bias = kw.get("bias")
            shifted = logits if bias is None else jax.scipy.special.logit(
                jnp.clip(jax.nn.sigmoid(logits) + bias, 1e-6, 1 - 1e-6))
            return real(shifted, k, renormalize, **{**kw, "bias": None})
        return route

    return {
        # Solar-Open2's key: beta in (0, 2)
        "beta_2_sigmoid": with_cfg(kda_allow_neg_eigval=True),
        # DeepSeek's form: the 64 columns and the shared key rotated
        "columns_rotated": with_cfg(rope=True),
        "scaling_factor_dropped": with_cfg(routed_scaling_factor=1.0),
        "weights_not_normalised": with_cfg(norm_topk_prob=False),
        "weights_from_biased_scores": patched(moe, "route", biased_weights),
        "bias_ignored": lambda p, b, bias: system(p, b, jnp.zeros_like(bias)),
        "latent_norm_dropped": patched(
            transformer, "fused_rmsnorm", lambda real: (
                lambda x, scale, eps=1e-6: (
                    x if scale.shape[-1] == cfg.kv_lora_rank
                    else real(x, scale, eps=eps)))),
        "taps_dropped": patched(  # this token's tap alone
            transformer, "_causal_taps", lambda real: lambda u, w: w[-1] * u),
        # the decays' sums, every exp, the solve and the states in bf16
        "kda_float32_parts_in_bf16": (
            patched(kda_lib, "_F32", in_bf16,
                    patched(transformer, "kda", numpy_form)),
            patched(kda_lib, "_F32", in_bf16, probe_in_bf16)),
        "bf16_everything": bf16_everything,
    }


def errors_of_wrong(family, wrong, made, batch):
    system, kda_fn = wrong if isinstance(wrong, tuple) else (wrong, None)
    extra = {} if kda_fn is None else {"kda_fn": kda_fn}
    return family.errors_of(system, made, batch, **extra)


def test_tiny_cell_keeps_the_family_and_its_mechanisms():
    config = tiny_kimi()["config"]
    assert config["family"] == "kimi_linear"
    assert config["layer_types"] == ["kda", "kda", "kda", "latent_attention",
                                     "kda"]
    assert config["rope"] is False and config["kda_allow_neg_eigval"] is False
    assert config["router_score"] == "sigmoid" and config["norm_topk_prob"]
    assert config["expert_bias"] and config["routed_scaling_factor"] == 2.446
    assert config["n_shared_experts"] == 1 and config["n_dense_layers"] == 1
    assert config["experts_held"] == [4, 4]
    assert config["check"]["seq_len"] > config["kda_chunk"]


@pytest.fixture(scope="module")
def in_float32():
    """(cell, family, parameters and bias, batch): made once for all the
    faults, so that the reference's programs are compiled once."""
    cell = tiny_kimi("float32")
    family = family_of(cell)
    return (cell, family, family.init_params(loop.seed_key(2**31 + 3)),
            check_batch(cell, family))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype, in_float32):
    if dtype == "float32":
        cell, family, made, batch = in_float32
    else:
        cell = tiny_kimi(dtype)
        family = family_of(cell)
        made = family.init_params(loop.seed_key(2**31 + 3))
        batch = check_batch(cell, family)
    errors = family.check(made, batch)
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 2e-5
        assert errors["router_flip_share"] == 0.0
        assert errors["kda_rel_err"] < 1e-5
        assert compare.within(errors, family.tolerance)
    else:
        assert errors["kda_rel_err"] < 2 * family.tolerance["kda_rel_err"]
        assert errors["loss_rel_err"] < 4 * family.tolerance["loss_rel_err"]
        assert errors["router_flip_share"] < 0.06
        # heads of 16 scaled to unit length round far harder than heads of
        # 128 (`loops/kimi_linear.py` has the chip's readings)
        assert errors["grad_rel_err"] < 0.35
    assert errors["dropped_slots"] == errors["unrouted_slots"] == 0.0
    assert errors["expert_load_max_over_mean"] >= 1.0
    assert 0 < errors["held_slots_mean"] < 2 * 48 * 4
    assert errors["kda_log_decay_min"] < 0.0
    assert 0.0 < errors["kda_beta_mean"] < 1.0
    assert errors["kda_beta_mean"] == pytest.approx(
        errors["kda_beta_mean_reference"], rel=1e-3)


@pytest.mark.parametrize("fault", FAULTS)
def test_wrong_mathematics_is_outside_the_tolerance(fault, in_float32):
    """In float32, where the stated path agrees to rounding, so that what
    is left is the fault's own (`loops/kimi_linear.py` has the chip's
    readings)."""
    cell, family, made, batch = in_float32
    wrong = errors_of_wrong(
        family, wrong_systems(cell, family)[fault], made, batch)
    assert not compare.within(wrong, family.tolerance), wrong
    if fault == "bf16_everything":
        assert wrong["loss_rel_err"] > family.tolerance["loss_rel_err"]
    elif fault == "kda_float32_parts_in_bf16":  # held by the probe alone
        assert wrong["kda_rel_err"] > family.tolerance["kda_rel_err"]
    elif fault == "bias_ignored":  # under the system's routing, the choice
        assert wrong["router_flip_share"] > family.tolerance[
            "router_flip_share"]
    else:
        assert wrong["kda_rel_err"] < 1e-4  # float32: the stated recurrence
        assert wrong["grad_rel_err"] > 1.5 * family.tolerance["grad_rel_err"], wrong


def test_the_reference_imports_nothing_of_the_program():
    import chipbench.reference.kimi_linear as reference

    source = open(reference.__file__).read()
    assert "ray_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    import inspect  # the recurrence, token by token: Solar's reference's
    assert "lax.scan(one_token" in inspect.getsource(reference.delta_rule)
    assert "ray_tpu" not in inspect.getsource(
        inspect.getmodule(reference.delta_rule)).split('"""', 2)[2]
    assert "_rope(" not in source and "jnp.cos" not in source  # no rotation
    # and its recurrence is the program's own, written apart
    from ray_tpu.ops.kda import kda_recurrent

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(key, (1, 128, 2, 8)) for key in ks[:3])
    g = -jax.random.uniform(ks[3], (1, 128, 2, 8))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, 128, 2)))
    ours, _ = kda_recurrent(q, k, v, g, beta)
    theirs = reference.delta_rule(q, k, v, g, beta)
    assert float(jnp.abs(ours - theirs).max()) < 1e-5


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4 under the sigmoid router with its bias,
    normalised weights and the factor 2.446: the four shares' partial
    results, the shared expert (and the residual) counted once, are the
    uncut reference's layer; and the program's share is the reference's
    share."""
    import chipbench.reference.kimi_linear as reference
    from ray_tpu.models import transformer

    cell = tiny_kimi("float32")
    config = cell["config"]
    whole = {**config, "experts_held": [0, 16]}
    cfg_whole = family_of({**cell, "config": whole}).model_config
    key = jax.random.PRNGKey(5)
    w = {name: leaf[0] for name, leaf in transformer._blocks_init(
        key, cfg_whole, transformer.LayerKind("kda", True), 1).items()}
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 24, 64))
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (16,))
    with jax.default_matmul_precision("highest"):
        uncut, picked = reference.routed_feed_forward(x, w, whole, bias=bias)
        y = reference._rmsnorm(x, w["mlp_norm"], config["norm_eps"])
        once = x + reference._swiglu(y, w["ws_gate"], w["ws_up"], w["ws_down"])
        total = once
        for first in range(0, 16, 4):
            share = {**config, "experts_held": [first, 4]}
            mine = {name: (leaf[first:first + 4] if name in (
                "w_gate", "w_up", "w_down") else leaf)
                for name, leaf in w.items()}
            part, _ = reference.routed_feed_forward(x, mine, share, bias=bias)
            total = total + (part - once)
            # the program, told the same share, computes the same part
            cfg = dataclasses.replace(cfg_whole, experts_held=(first, 4))
            routed, readings = transformer._routed_ffn(
                y, mine, cfg, bias=bias)
            shared = transformer._feed_forward(
                y, mine, cfg.dtype, ("shared_gate", "shared_up"), prefix="ws")
            assert float(jnp.abs(x + routed + shared - part).max()) < 1e-5
            assert int(readings["held_slots"]) == int(
                picked[..., first:first + 4].sum())
    assert float(jnp.abs(total - uncut).max()) < 1e-5
    assert float(jnp.abs(uncut - once).max()) > 0.1  # the experts add
    assert float(picked.sum()) == 2 * 24 * 4


# ------------------------------------------------------------- the cell

def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_kimi()
    reports = run_loop_here(monkeypatch, cell, seconds=0.4)
    summary = reports[-1]
    assert summary["summary"] and summary["steps_failed"] == 0
    assert summary["compiles_in_window"] == 0
    assert summary["steps"] == 2 * len(summary["chunks"]) >= 2
    assert all(math.isfinite(c["loss"]) for c in summary["chunks"])
    reference = summary["reference"]
    assert {"router_flip_share", "held_slots_mean", "dropped_slots",
            "kda_log_decay_min", "kda_beta_mean", "kda_rel_err",
            "kda_beta_mean_reference", "held_slots_max_over_even"} <= set(
                reference)
    assert reference["dropped_slots"] == 0.0
    assert reference["loss_rel_err"] < 4 * reference["tolerance"]["loss_rel_err"]
    assert summary["flops_per_unit"] == pytest.approx(
        kimi_linear_flops.flops_per_token(cell["config"], 64))
    assert all(c["units"] == 2 * 64 for c in summary["chunks"])


def test_the_step_reports_the_operators_readings(in_float32):
    cell, family, made, batch = in_float32
    # the step donates its state: a copy, the fixture's stay
    state = family.init_state(jax.tree.map(jnp.copy, made))
    # a run's start: not the drawn bias, the one that evens the seeded load
    start = state["expert_bias"]
    assert start.shape == (4, 16) and 0.0 < float(jnp.abs(start).max()) < 1.0
    assert not bool(jnp.allclose(start, made["expert_bias"]))

    def fullest(bias):
        load = family.system_loss_and_readings(
            made["params"], batch, bias)[1]["expert_load"]
        return float(jnp.max(load.max(-1) / load.mean(-1)))

    assert fullest(start) < fullest(jnp.zeros_like(start))
    state, out = family.step(state, batch)
    assert {"loss", "grad_norm", "expert_load", "held_slots", "dropped_slots",
            "kda_log_decay_min", "kda_beta_mean",
            "expert_bias_abs_max"} <= set(out)
    assert out["expert_load"].shape == (4, 16)  # the routed four of five
    assert out["held_slots"].shape == out["dropped_slots"].shape == (4,)
    assert out["kda_log_decay_min"].shape == () and out["kda_beta_mean"].shape == ()
    assert float(out["kda_log_decay_min"]) < 0 < float(out["kda_beta_mean"]) < 1
    assert float(out["expert_bias_abs_max"]) == pytest.approx(
        float(jnp.abs(state["expert_bias"]).max()))


@pytest.mark.parametrize("traced", [False, True])
def test_last_line_keys(traced):
    cell = spec.load_cell(spec.ROOT, CELL)
    reduced = fake_reduced(1) if traced else None
    line = run.last_line(spec.ROOT, BENCH, cell, fake_summary(cell), reduced)
    assert line["correct"] is True
    if traced:
        assert {"model_mfu.tokens", "peak_hbm_gb.tokens",
                "device_idle_share.tokens", "steady_rate.tokens",
                "pallas_time_share.tokens", "kda_kernel_time_share.tokens",
                "flash_time_share.tokens"} <= set(line["metrics"])
        assert line["metrics"]["kda_kernel_time_share.tokens"]["value"] == 0
        for name in WAITING:  # they wait
            assert name not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_the_cell_and_its_lists():
    cell = spec.by_name(BENCH["workloads"], CELL, "workload")
    assert cell == {**cell, "config": CONFIG, "traffic": TRAFFIC, "chips": 1}
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) == 15  # appended to the fifteen there were
    assert [c["name"] for c in BENCH["configs"]].index(CONFIG) == 14
    tokens = spec.by_name(BENCH["end_to_end"], "train_tokens_per_s", "metric")
    assert CELL in tokens["workloads"]
    assert tokens["workloads"].index(CELL) == tokens["workloads"].index(
        "evabyte.tokens8k") + 1
    for name in LISTS:
        lists = spec.by_name(BENCH["per_layer"], name, "metric")["workloads"]
        # appended: behind every cell of the fifteen that the list had
        assert lists.index(CELL) == len(set(lists) & set(names[:15])), name
    named = {m["name"] for m in spec.metrics_of(BENCH, CELL, "per_layer")}
    assert named == set(LISTS) | {"gang_boot_s", "state_init_s",
                                  "kda_kernel_time_share.tokens"}
    own = spec.by_name(
        BENCH["per_layer"], "kda_kernel_time_share.tokens", "metric")
    assert own == {
        "name": "kda_kernel_time_share.tokens", "unit": "%",
        "better": "lower", "source": "device_trace",
        "layer": spec.by_name(
            BENCH["per_layer"], "eva_time_share.tokens", "metric")["layer"],
        "moves": "train_tokens_per_s", "workloads": [CELL]}
    for name in ("flash_window_time_share.tokens", "eva_time_share.tokens",
                 "collective_time_share.tokens"):
        assert CELL not in spec.by_name(BENCH["per_layer"], name, "metric")[
            "workloads"]
    for text in (cell["why"], spec.by_name(
            BENCH["configs"], CONFIG, "config")["why"]):
        assert len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_traffic_is_one_sequence_of_16384_a_step():
    traffic = spec.load_cell(spec.ROOT, CELL)["traffic"]
    assert (traffic["kind"], traffic["unit"], traffic["batch_rows"],
            traffic["rows_per_block"], traffic["units_per_row"]) == (
                "ingest", "tokens", 1, 1, 16384)
    assert traffic["columns"] == {"tokens": {
        "dtype": "int32", "shape": [16385], "low": 0,
        "high": "config:vocab_size"}}
    assert (traffic["steps_per_chunk"], traffic["warmup_steps"],
            traffic["trace_chunks"], traffic["blocks_per_epoch"],
            traffic["prefetch_batches"]) == (1, 2, 2, 256, 2)
    rows = traffic_lib.make_rows(traffic, held_config(), 2**31 + 7, 0, 1)
    assert rows["tokens"].shape == (1, 16385)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 20480


def test_the_kernels_share_reads_kda_s_names_alone(tmp_path):
    """The cell's live metric, through the reader that was there, and read
    from a temporary root as the harness reads a cell's files: by name."""
    import shutil

    held = spec.read_json(
        spec.ROOT, "chipbench", "metrics", "kda_kernel_time_share.tokens.json")
    assert held["reader"] == "trace_share" and "awaits" not in held
    assert held["params"] == {"patterns": ["^kda_fwd", "^kda_bwd"],
                              "over": "busy"}
    ops = [["fusion.1", 0, 500], ["kda_fwd.2 [tpu_custom_call]", 500, 50],
           ["kda_fwd.7 [tpu_custom_call]", 550, 50],
           ["flash_fwd.5 [tpu_custom_call]", 600, 100],
           ["kda_bwd.6 [tpu_custom_call]", 700, 100]]
    from chipbench import trace

    reduced = trace.reduce({"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_step", 0, 1000]]}}, "host_spans": []})
    root = str(tmp_path)
    for kind in ("metrics", "readers", "configs", "traffic"):
        os.makedirs(os.path.join(root, "chipbench", kind))
    for path in ("BENCHMARK.json",
                 "chipbench/metrics/kda_kernel_time_share.tokens.json",
                 "chipbench/readers/trace_share.py",
                 "chipbench/configs/%s.json" % CONFIG,
                 "chipbench/traffic/%s.json" % TRAFFIC):
        shutil.copy(os.path.join(spec.ROOT, path), os.path.join(root, path))
    for at in (spec.ROOT, root):
        assert spec.read_metric(at, "kda_kernel_time_share.tokens", {
            "trace": reduced}) == pytest.approx(100.0 * 200 / 800)
        assert spec.read_metric(
            at, "kda_kernel_time_share.tokens", {"trace": None}) is None
    cell = spec.load_cell(root, CELL)
    assert cell["config"]["family"] == "kimi_linear"
    assert cell["traffic"]["units_per_row"] == 16384


@pytest.mark.parametrize("name", sorted(WAITING))
def test_the_waiting_files(name):
    """Under the key `awaits`, as PRs 55 to 64 left theirs; the files'
    shapes are the configuration's."""
    reader, better = WAITING[name]
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry, params = held["awaits"], held["params"]
    assert held["reader"] == reader
    assert entry == {**entry, "name": name, "unit": "%", "better": better,
                     "source": "device_trace", "workloads": [CELL],
                     "moves": "train_tokens_per_s"}
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert not any(m["name"] == name for m in BENCH["per_layer"])
    assert hasattr(spec.load_code(spec.ROOT, "readers", reader), "read")
    config = held_config()
    if reader == "kda_roofline":
        assert params == {
            "kernel": name.split("_roofline")[0], "heads": config["kda_heads"],
            "seq_len": 16384, "dk": config["kda_head_dim"],
            "dv": config["kda_head_dim"], "chunk": config["kda_chunk"],
            "remat": config["remat"]}
    elif reader == "mla_roofline":
        assert params == {
            "kernel": "flash_fwd", "n_heads": config["n_heads"],
            "qk_dim": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            "v_dim": config["v_head_dim"], "seq_len": 16384}
    elif reader == "gmm_roofline":
        d, f = config["d_model"], config["d_ff"]
        assert params == {
            "event": name.split("_roofline")[0], "experts": 8,
            "experts_per_token": 8 * 8 / 256,
            "products": [[d, f], [d, f], [f, d]]}
    else:
        assert params == {"scope": name.split("_time_share")[0]}
    assert len(WAITING) <= 8


@pytest.mark.parametrize("kernel", ["kda_fwd", "kda_bwd"])
def test_the_kda_roofline_reader(kernel):
    """The reader finds the kernel's events by name: calls that took twice
    their least time read 50 %; the forward's least time under remat is the
    mean of the call that writes the entering states and the one that does
    not."""
    from chipbench import flops, kernel_flops

    name = kernel + "_roofline.tokens"
    peaks = flops.peaks_for("TPU v5 lite")
    forms = (True, False) if kernel == "kda_fwd" else (True,)
    least = sum(kernel_flops.least_seconds(*kimi_linear_flops.kda_call(
        kernel, 32, 16384, 128, 128, 64, states), peaks)[0]
        for states in forms) / len(forms)
    named = kernel + ".7 [tpu_custom_call]"
    run_ = {"chips": 1, "device": {"kind": "TPU v5 lite"},
            "chunks": [{"units": 16384, "steps": 1}],
            "trace": {"segments": {"0": [
                [0, int(2e9 * least), named],
                [int(3e9 * least), int(5e9 * least), named],
                [0, 10, "moe_gmm.1 [tpu_custom_call]"]]}}}
    assert spec.read_metric(spec.ROOT, name, run_) == pytest.approx(
        50.0, rel=1e-6)
    run_["trace"]["segments"]["0"] = [
        [0, 10, kernel + "_other.1 [tpu_custom_call]"]]
    assert spec.read_metric(spec.ROOT, name, run_) is None
    assert spec.read_metric(spec.ROOT, name, {**run_, "trace": None}) is None
