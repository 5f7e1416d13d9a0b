"""The `moe_transformer` family of the benchmark on the CPU: the model against
its plain reference at a tiny size, what falls outside `TOLERANCE`, the tiny
cell's loop end to end, the operation counts by hand, the new readers on
made-up events, and the new files' form."""

import json
import math

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare, loop, moe_flops, run, spec, trace
from chipbench import traffic as traffic_lib
from chipbench_tiny import fake_summary, run_loop_here, tiny_cell

CELL = "olmoe.tokens4k"
BENCH = spec.load_benchmark(spec.ROOT)


def tiny_moe(dtype="bfloat16", **over):
    """64 wide, 8 experts, 2 a token, 2 layers."""
    cell = tiny_cell(CELL)
    cell["config"].update(n_experts=8, experts_per_token=2, dtype=dtype, **over)
    return cell


def family_of(cell):
    return spec.load_code(spec.ROOT, "loops", cell["config"]["family"]).build(
        cell["config"], cell["traffic"], jax.devices()[:1])


def check_batch(cell, family, seed=11):
    raw = traffic_lib.make_rows(cell["traffic"], cell["config"], seed,
                                loop.CHECK_INDEX, cell["config"]["check"]["rows"])
    return family.check_batch(raw)


def test_tiny_cell_keeps_the_family_and_gqa():
    config = tiny_cell(CELL)["config"]
    assert config["family"] == "moe_transformer"
    assert (config["n_heads"], config["n_kv_heads"]) == (4, 2)
    assert (config["n_experts"], config["experts_per_token"]) == (64, 8)
    assert config["qk_norm"] is True and config["norm_topk_prob"] is False


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype):
    cell = tiny_moe(dtype)
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    errors = family.check(params, check_batch(cell, family))
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 1e-5
        assert errors["router_flip_share"] == 0.0
    else:
        assert compare.within(errors, family.tolerance), errors
        assert errors["router_flip_share"] < 0.05
    assert errors["dropped_slots"] == 0.0
    assert errors["expert_load_max_over_mean"] >= 1.0


def test_gqa_with_64_experts_agrees_too():
    cell = tiny_cell(CELL)  # n_kv_heads 2 of 4, 64 experts, 8 a token
    family = family_of(cell)
    params = family.init_params(loop.seed_key(7))
    errors = family.check(params, check_batch(cell, family))
    assert compare.within(errors, family.tolerance), errors


def lower_precision(family):
    def bf16_only(p, b):  # weights, activations, logits and the loss in bf16
        p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
        loss, readings = family.system_loss_and_readings(p, b)
        return loss.astype(jnp.bfloat16).astype(jnp.float32), readings
    return bf16_only


WRONG = {
    "renormalised_topk": dict(norm_topk_prob=True),
    "no_qk_norm": dict(qk_norm=False),
    "one_expert_fewer": dict(experts_per_token=1),
    "no_router_losses": dict(router_aux_loss_coef=0.0, router_z_loss_coef=0.0),
}


def test_bf16_everything_is_outside_the_tolerance():
    cell = tiny_moe()
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    lower = family.errors_of(
        lower_precision(family), params, check_batch(cell, family))
    assert not compare.within(lower, family.tolerance), lower


@pytest.mark.parametrize("fault", WRONG)
def test_wrong_mathematics_is_outside_the_tolerance(fault):
    """Under the system's routing, as the comparison of gradients is made."""
    cell = tiny_moe()
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    batch = check_batch(cell, family)
    reference = spec.load_code(spec.ROOT, "reference", "moe_transformer")
    wrong_config = dict(cell["config"], **WRONG[fault])
    index = family.system_loss_and_readings(params, batch)[1]["expert_index"]
    index = index[..., :wrong_config["experts_per_token"]]  # the largest first

    def wrong_reference(p, b):
        return reference.loss(p, b, wrong_config, index)

    wrong = compare.loss_and_grad_errors(
        family.system_loss, wrong_reference, params, batch)
    assert not compare.within(wrong, family.tolerance), wrong
    if fault != "no_router_losses":  # several times over it
        assert wrong["grad_rel_err"] > 3 * family.tolerance["grad_rel_err"], wrong


def test_gradients_are_compared_under_one_routing():
    """A flipped slot moves a whole row between two experts' weight
    gradients: against the reference's own routing the distance is the
    router's noise, under the system's routing it is rounding."""
    cell = tiny_moe()
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    batch = check_batch(cell, family)
    errors = family.check(params, batch)
    assert errors["router_flip_share"] > 0.0  # this seed has flips
    free = compare.loss_and_grad_errors(
        family.system_loss, family.reference_loss, params, batch)
    assert errors["grad_rel_err"] < 0.5 * free["grad_rel_err"]
    assert errors["loss_rel_err_own_routing"] == free["loss_rel_err"]
    # the reference under its own choice, handed back to it, is itself
    reference = spec.load_code(spec.ROOT, "reference", "moe_transformer")
    f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    loss, chosen = reference.forward(f32, batch, cell["config"])
    own = jnp.argsort(~chosen, axis=-1, stable=True)[..., :2]
    again = reference.loss(f32, batch, cell["config"], own)
    assert float(again) == pytest.approx(float(loss), rel=1e-6)


def test_a_wrong_choice_of_experts_is_outside_the_tolerance():
    """The reference follows the system's routing, so the choice itself is
    held by `router_flip_share`: a router of the opposite sign picks the
    least likely experts."""
    cell = tiny_moe()
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))

    def opposite(p, b):
        blocks = dict(p["blocks"], router=-p["blocks"]["router"])
        return family.system_loss_and_readings(dict(p, blocks=blocks), b)

    errors = family.errors_of(opposite, params, check_batch(cell, family))
    assert errors["router_flip_share"] > 0.5
    assert errors["router_flip_share"] > family.tolerance["router_flip_share"]
    assert not compare.within(errors, family.tolerance)


def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_cell(CELL)
    reports = run_loop_here(monkeypatch, cell, seconds=0.4)
    summary = reports[-1]
    assert summary["summary"] and summary["steps_failed"] == 0
    assert summary["compiles_in_window"] == 0
    assert summary["steps"] == 2 * len(summary["chunks"]) >= 2
    assert all(math.isfinite(c["loss"]) for c in summary["chunks"])
    reference = summary["reference"]
    assert {"router_flip_share", "expert_load_max_over_mean",
            "dropped_slots"} <= set(reference)
    assert reference["dropped_slots"] == 0.0
    assert summary["flops_per_unit"] == moe_flops.moe_transformer_flops_per_token(
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
    parts = moe_flops.forward_parts(config, 4096)
    assert parts["attention_projections"] == 8 * 2048 * 2048
    assert parts["attention"] == 4 * 2048 * 4097 / 2
    assert parts["router"] == 2 * 2048 * 64
    assert parts["experts"] == 8 * 6 * 2048 * 1024
    assert parts["head"] == 2 * 2048 * 50304
    total = moe_flops.moe_transformer_flops_per_token(config, 4096)
    assert total == pytest.approx(1.072e9, rel=5e-4)  # the hand figure
    assert parts["head"] / sum(parts.values()) == pytest.approx(0.58, abs=0.005)
    # a step of the cell: 17.6 TFLOP, experts 4.95, head 10.1
    step = 4 * 4096
    assert step * total == pytest.approx(17.6e12, rel=5e-3)
    assert step * 3 * parts["experts"] == pytest.approx(4.95e12, rel=5e-3)
    assert step * 3 * parts["head"] == pytest.approx(10.1e12, rel=5e-3)


def test_flops_agree_with_the_program_s_own_count():
    from ray_tpu.models.transformer import flops_per_token

    cell = spec.load_cell(spec.ROOT, CELL)
    family = spec.load_code(spec.ROOT, "loops", "moe_transformer")
    assert flops_per_token(family.model_config(cell["config"]), 4096) == \
        moe_flops.moe_transformer_flops_per_token(cell["config"], 4096)


def test_param_count_and_the_cut_s_arithmetic():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    n = moe_flops.moe_transformer_param_count(config)
    assert n == 625_616_896  # 625.6 M: one layer of 419.6 M, 206.0 M embeddings
    assert 16 * n / 1e9 == pytest.approx(10.0, abs=0.02)
    two = moe_flops.moe_transformer_param_count(dict(config, n_layers=2))
    assert 16 * two / 1e9 == pytest.approx(16.7, abs=0.05)  # does not fit 15.75
    family = spec.load_code(spec.ROOT, "loops", "moe_transformer")
    from ray_tpu.models.transformer import transformer_init
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0),
                                 family.model_config(config)))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) == n


def test_gmm_call_by_hand():
    ops, bytes_moved = moe_flops.gmm_call(131072, 2048, 1024, 64)
    assert ops == 2 * 131072 * 2048 * 1024  # 550 GFLOP
    assert bytes_moved == 2 * (131072 * 2048 + 131072 * 1024 + 64 * 2048 * 1024)
    # the same whichever way the weight lies
    assert moe_flops.gmm_call(131072, 1024, 2048, 64) == (ops, bytes_moved)


# -------------------------------------------------------------- the readers

def made_up_run(kernel_us):
    ops = [["fusion.1", 0, 1000],
           ["moe_gmm.3 [tpu_custom_call]", 1000, kernel_us * 1000],
           ["moe_gmm.4 [tpu_custom_call]", 10_000_000, kernel_us * 1000],
           ["moe_tgmm.5 [tpu_custom_call]", 20_000_000, 2 * kernel_us * 1000],
           ["flash_fwd.6 [tpu_custom_call]", 50_000_000, 1000]]
    reduced = trace.reduce({
        "devices": {"/device:TPU:0": {"ops": ops, "modules": [
            ["jit_step", 0, 60_000_000]]}},
        "host_spans": [],
    })
    cell = spec.load_cell(spec.ROOT, CELL)
    summary = fake_summary(cell)
    summary["chunks"] = [{"chunk": 0, "steps": 4, "units": 4 * 16384,
                          "seconds": 1.0, "loss": 5.0, "traced": True}]
    return dict(summary, chips=1, trace=reduced)


def test_gmm_roofline_reader_on_made_up_events():
    # 550 GFLOP at 197 TFLOP/s is 2.79 ms: a 5.58 ms event is at 50 %
    least_ms = 2 * 131072 * 2048 * 1024 / 197e12 * 1e3
    run_ = made_up_run(kernel_us=round(2 * least_ms * 1000))
    gmm = spec.read_metric(spec.ROOT, "moe_gmm_roofline.tokens", run_)
    tgmm = spec.read_metric(spec.ROOT, "moe_tgmm_roofline.tokens", run_)
    assert gmm == pytest.approx(50.0, rel=1e-3)
    assert tgmm == pytest.approx(25.0, rel=1e-3)  # twice as long an event
    bare = dict(run_, trace=trace.reduce({
        "devices": {"d": {"ops": [["fusion.1", 0, 10]], "modules": []}},
        "host_spans": []}))
    assert spec.read_metric(spec.ROOT, "moe_gmm_roofline.tokens", bare) is None
    assert spec.read_metric(
        spec.ROOT, "moe_gmm_roofline.tokens", dict(run_, trace=None)) is None


def test_gmm_time_share_reads_the_kernels_and_zero_without_them():
    run_ = made_up_run(kernel_us=1000)
    share = spec.read_metric(spec.ROOT, "moe_gmm_time_share.tokens", run_)
    busy = 1 + 1000 + 1000 + 2000 + 1  # microseconds
    assert share == pytest.approx(100.0 * 4000 / busy, rel=1e-6)
    bare = dict(run_, trace=trace.reduce({
        "devices": {"d": {"ops": [["fusion.1", 0, 10]], "modules": []}},
        "host_spans": []}))
    assert spec.read_metric(spec.ROOT, "moe_gmm_time_share.tokens", bare) == 0.0


@pytest.mark.parametrize("name", ["moe_gmm_roofline.tokens",
                                  "moe_tgmm_roofline.tokens"])
def test_waiting_roofline_metrics_carry_their_entry(name):
    """Under the key `awaits`, not `entry`: `test_the_eleven_wait` (the
    benchmark's, not to be edited) counts the files that carry `entry`."""
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry = held["awaits"]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert entry["name"] == name and entry["unit"] == "%"
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_tokens_per_s"
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert entry["layer"] in layers  # spelled as the entries spell it
    assert name not in {m["name"] for m in BENCH["per_layer"]}  # still waiting
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    assert held["params"]["experts"] == config["n_experts"]
    assert held["params"]["experts_per_token"] == config["experts_per_token"]
    d, f = config["d_model"], config["d_ff"]
    assert sorted(map(tuple, held["params"]["products"])) == sorted(
        [(d, f), (d, f), (f, d)])


# ------------------------------------------------------------ the new files

def test_configuration_holds_the_catalog_s_numbers():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    source = config["source_config"]
    for key, value in source.items():
        if key == "num_hidden_layers":
            assert config[key] == config["n_layers"] == 1 < value
        else:
            assert config[key] == value, key
    assert config["n_experts"] == source["num_experts"]
    assert config["experts_per_token"] == source["num_experts_per_tok"]
    assert config["norm_topk_prob"] == source["norm_topk_prob"]
    assert config["max_seq_len"] == source["max_position_embeddings"]
    entry = spec.by_name(BENCH["configs"], "olmoe-1b-7b-l1", "config")
    assert config["source"].startswith(entry["source"])
    assert {"router_aux_loss_coef", "router_z_loss_coef", "optimizer",
            "deployment"} <= set(config["assumed"])


def test_traffic_mix_is_the_issue_s():
    traffic = spec.load_cell(spec.ROOT, CELL)["traffic"]
    assert traffic["kind"] == "ingest"
    assert traffic_lib.units_per_step(traffic) == 16384
    assert (traffic["steps_per_chunk"], traffic["blocks_per_epoch"],
            traffic["trace_chunks"], traffic["warmup_steps"]) == (4, 256, 2, 2)
    rows = traffic_lib.make_rows(
        traffic, {"vocab_size": 50304}, 2**31 + 9, 0, 4)["tokens"]
    assert rows.shape == (4, 4097) and 0 <= rows.min() and rows.max() < 50304
