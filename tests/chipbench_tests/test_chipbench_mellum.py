"""The `mellum` family of the benchmark on the CPU's virtual devices: the model
over a four-device `expert` mesh against its plain reference at a tiny size,
each wrong mathematics that has to fall outside `TOLERANCE`, the tiny cell's
loop end to end, the counts by hand, and the new files' form.
`wrong_systems` is also what the builder's chip run takes its wrong
mathematics from, at the published widths."""

import copy
import dataclasses
import json
import math
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare, loop, mellum_flops, run, spec, trace
from chipbench import traffic as traffic_lib
from chipbench_tiny import fake_reduced, fake_summary, run_loop_here

CELL = "mellum2.ep4"
CONFIG = "mellum2-12b-a2.5b-l4-ep4"
TRAFFIC = "tokens-16k-64k-ep4"
BENCH = spec.load_benchmark(spec.ROOT)
FAULTS = [
    "one_chip_s_partial_result_left_out", "balance_loss_over_a_chip_s_own_tokens",
    "weights_not_normalised",
    "no_attention_factor", "plain_frequencies_on_the_full_layer",
    "window_one_key_too_wide", "window_one_key_too_narrow", "bf16_everything"]
# the lists of BENCHMARK.json the cell joins (ISSUE 50, item 4)
LISTS = [
    "ingest_wait_share.tokens", "steady_rate.tokens", "stall_share.tokens",
    "model_mfu.tokens", "pallas_time_share.tokens",
    "collective_exposed_share.tokens", "device_idle_share.tokens",
    "peak_hbm_gb.tokens", "moe_gmm_time_share.tokens",
    "flash_time_share.tokens", "flash_window_time_share.tokens",
    "cluster_init_s", "compile_s", "first_batch_s", "setup_unnamed_s",
    "ingest_produce_share.tokens", "collective_time_share.tokens"]
WAITING = [
    "moe_gmm_roofline.mellum.tokens", "moe_tgmm_roofline.mellum.tokens",
    "flash_fwd_roofline.mellum.tokens",
    "flash_bwd_dkv_dq_roofline.mellum.tokens",
    "flash_fwd_roofline.window.mellum.tokens",
    "flash_bwd_dkv_dq_roofline.window.mellum.tokens"]


def tiny_mellum(dtype="bfloat16", **over):
    """64 wide, 8 query heads over 2 key-value heads of 16: the period
    sliding, sliding, sliding (a window of 8), full; 16 experts of 32, 3 a
    token, 4 a device of a mesh of four; sequences of 64, compared at 32
    (four windows deep), one a device."""
    cell = copy.deepcopy(spec.load_cell(spec.ROOT, CELL))
    config, traffic = cell["config"], cell["traffic"]
    config.update(vocab_size=256, d_model=64, n_heads=8, n_kv_heads=2,
                  d_head=16, d_ff=32, max_seq_len=64, sliding_window=8,
                  n_experts=16, experts_per_token=3, dtype=dtype,
                  check=dict(config["check"], rows=4, seq_len=32), **over)
    # the ramp inside the tiny rotary width, and positions past the original
    config["rope_scaling"] = dict(
        config["rope_scaling"], original_max_position_embeddings=16)
    traffic["columns"]["tokens"]["shape"] = [65]
    traffic.update(units_per_row=64, blocks_per_epoch=5, steps_per_chunk=2,
                   warmup_steps=1, trace_chunks=2)
    return cell


def family_of(cell):
    return spec.load_code(spec.ROOT, "loops", cell["config"]["family"]).build(
        cell["config"], cell["traffic"], jax.devices()[:4])


def check_batch(cell, family, seed=11):
    raw = traffic_lib.make_rows(cell["traffic"], cell["config"], seed,
                                loop.CHECK_INDEX, cell["config"]["check"]["rows"])
    return family.check_batch(raw)


# ------------------------------------- wrong mathematics, as wrong systems

def wrong_systems(family):
    """{name: keywords for `family.errors_of`}: each computes something
    other than the published model over the mesh."""
    from ray_tpu.models import transformer

    cfg, mesh = family.model_config, family.mesh
    system = family.system_loss_and_readings

    def with_cfg(**changed):
        wrong = dataclasses.replace(cfg, **changed)
        return lambda p, b: transformer.transformer_loss_and_readings(
            p, b, wrong, mesh=mesh)

    def window_of(window):
        return lambda q, k, v: transformer._attention(
            q, k, v, cfg, None, 1, None, window=window)

    def last_chip_left_out(fn):
        """`fn` with the exchange's sum short of the last chip's partial
        result."""
        def wrong(*args):
            real = jax.lax.psum_scatter

            def short(x, axis, **kw):
                last = jax.lax.axis_index(axis) == jax.lax.axis_size(axis) - 1
                return real(jnp.where(last, jnp.zeros_like(x), x), axis, **kw)

            jax.lax.psum_scatter = short
            try:
                return fn(*args)
            finally:
                jax.lax.psum_scatter = real
        return wrong

    def chip_s_own_balance(fn):
        """`fn` with the sums and means over the `expert` axis left out of
        the router's readings: every chip's balance loss is over its own
        tokens, and the step's is the first chip's."""
        def wrong(*args):
            real = jax.lax.psum, jax.lax.pmean
            jax.lax.psum = lambda x, axis: jax.tree.map(
                lambda leaf: leaf * jax.lax.axis_size(axis), x)
            jax.lax.pmean = lambda x, axis: x
            try:
                return fn(*args)
            finally:
                jax.lax.psum, jax.lax.pmean = real
        return wrong

    def bf16_everything(p, b):
        """The stated bf16 compute with bf16 weights as well: norms, router
        and head read rounded leaves. The loss's scalar stays float32: one
        rounded to bf16's grid would read its distance from the grid and
        nothing of the computation."""
        return system(jax.tree.map(lambda x: x.astype(jnp.bfloat16), p), b)

    scaling = dict(cfg.rope_scaling)
    window = cfg.sliding_window
    return {
        "one_chip_s_partial_result_left_out": dict(
            loss_and_readings=last_chip_left_out(system),
            routed_layer=last_chip_left_out(
                lambda y, leaves: transformer._routed_ffn(
                    y, leaves, cfg, mesh)[0])),
        "balance_loss_over_a_chip_s_own_tokens": dict(
            loss_and_readings=chip_s_own_balance(system)),
        "weights_not_normalised": dict(
            loss_and_readings=with_cfg(norm_topk_prob=False)),
        "no_attention_factor": dict(loss_and_readings=with_cfg(
            rope_scaling=tuple(sorted(
                {**scaling, "attention_factor": 1.0}.items())))),
        "plain_frequencies_on_the_full_layer": dict(
            loss_and_readings=with_cfg(rope_scaling=None)),
        "window_one_key_too_wide": dict(
            loss_and_readings=with_cfg(sliding_window=window + 1),
            window_attention=window_of(window + 1)),
        "window_one_key_too_narrow": dict(
            loss_and_readings=with_cfg(sliding_window=window - 1),
            window_attention=window_of(window - 1)),
        "bf16_everything": dict(
            loss_and_readings=bf16_everything,
            router_logits=lambda y, router: transformer._router_logits(
                y, router.astype(jnp.bfloat16))),
    }


def errors_of_wrong(family, wrong, params, batch):
    wrong = dict(wrong)
    return family.errors_of(wrong.pop("loss_and_readings"), params, batch,
                            **wrong)


# ------------------------------------------------------------ the comparison

def test_tiny_cell_keeps_the_family_and_its_mechanisms():
    config = tiny_mellum()["config"]
    assert config["family"] == "mellum" and config["mesh"] == {"expert": 4}
    assert config["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert config["router_score"] == "softmax"
    assert config["norm_topk_prob"] is True and config["tied_embeddings"] is False
    assert config["rope_scaling"]["rope_type"] == "yarn"
    assert "experts_held" not in config and "n_shared_experts" not in config
    assert config["check"]["seq_len"] == 4 * config["sliding_window"]
    assert config["check"]["rows"] == 4  # one a device: the exchange runs


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype):
    cell = tiny_mellum(dtype)
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    errors = family.check(params, check_batch(cell, family))
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 1e-5
        assert errors["router_flip_share"] == 0.0
        assert errors["aux_loss_rel_err"] < 1e-6
        assert errors["window_edge_err"] < 1e-6
        assert errors["exchange_rel_err"] < 1e-6
        assert errors["router_logits_rel_err"] < 1e-6
    else:
        assert errors["loss_rel_err"] < 3 * family.tolerance["loss_rel_err"]
        assert errors["router_flip_share"] < 0.05
        assert errors["grad_rel_err"] < 0.12  # four tiny layers round harder
        assert errors["aux_loss_rel_err"] < 2e-3
        assert errors["window_edge_err"] < family.tolerance["window_edge_err"]
        assert errors["exchange_rel_err"] < family.tolerance["exchange_rel_err"]
        assert errors["router_logits_rel_err"] < family.tolerance[
            "router_logits_rel_err"]
    assert errors["dropped_slots"] == errors["unrouted_slots"] == 0.0
    assert errors["expert_load_max_over_mean"] >= 1.0
    assert errors["chip_load_max_over_mean"] >= 1.0
    # E sum_e f_e P_e with a softmax over seeded logits: near 1
    assert 0.9 < errors["aux_loss_system"] < 1.5


@pytest.mark.parametrize("fault", FAULTS)
def test_wrong_mathematics_is_outside_the_tolerance(fault):
    """In float32, where the stated path agrees to rounding, so that what
    is left is the fault's own: each reads over the bound of the key that
    holds it at the published widths (`loops/mellum.py` has the chips'
    readings)."""
    # bf16 everywhere is the stated bf16 compute with bf16 weights as well
    cell = tiny_mellum("bfloat16" if fault == "bf16_everything" else "float32")
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    batch = check_batch(cell, family)
    wrong = errors_of_wrong(family, wrong_systems(family)[fault], params, batch)
    assert not compare.within(wrong, family.tolerance), wrong
    if fault.startswith("window"):  # held by the probe, whatever the loss says
        assert wrong["window_edge_err"] > 5 * family.tolerance["window_edge_err"]
        assert wrong["exchange_rel_err"] < 1e-6
    elif fault == "bf16_everything":  # held by the router's own key alone
        assert wrong["router_logits_rel_err"] > 10 * family.tolerance[
            "router_logits_rel_err"]
        assert wrong["window_edge_err"] < family.tolerance["window_edge_err"]
        assert wrong["exchange_rel_err"] < family.tolerance["exchange_rel_err"]
    elif fault.startswith("balance_loss"):  # held by its own key alone
        assert wrong["aux_loss_rel_err"] > 5 * family.tolerance["aux_loss_rel_err"]
        assert wrong["exchange_rel_err"] < 1e-6
    elif fault.startswith("one_chip"):  # held by the exchange's own key
        assert wrong["exchange_rel_err"] > 10 * family.tolerance["exchange_rel_err"]
        assert wrong["grad_rel_err"] > 2 * family.tolerance["grad_rel_err"]
        assert wrong["window_edge_err"] < 1e-6
    else:
        assert wrong["grad_rel_err"] > 2 * family.tolerance["grad_rel_err"], wrong
        assert wrong["window_edge_err"] < 1e-6
        assert wrong["exchange_rel_err"] < 1e-6


def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_mellum()
    reports = run_loop_here(monkeypatch, cell, seconds=0.4)
    summary = reports[-1]
    assert summary["summary"] and summary["steps_failed"] == 0
    assert summary["compiles_in_window"] == 0
    assert summary["steps"] == 2 * len(summary["chunks"]) >= 2
    assert all(math.isfinite(c["loss"]) for c in summary["chunks"])
    reference = summary["reference"]
    assert {"router_flip_share", "aux_loss_rel_err", "window_edge_err",
            "exchange_rel_err", "router_logits_rel_err",
            "chip_load_max_over_mean",
            "dropped_slots"} <= set(reference)
    assert reference["dropped_slots"] == 0.0
    assert summary["flops_per_unit"] == mellum_flops.mellum_flops_per_token(
        cell["config"], 64)
    # each device a quarter of the state, which `run.verdict` asks for
    placement = summary["state_bytes"]
    assert len(placement["per_device"]) == 4
    for held in placement["per_device"]:
        assert held / placement["whole"] == pytest.approx(0.25, abs=0.02)
    summary["device"] = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    summary["memory_peak_bytes"] = 1
    summary["reference"]["agrees"] = True
    assert run.verdict(summary, cell) == []
    line = run.last_line(spec.ROOT, BENCH, cell, summary, None)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    json.dumps(line)


# ---------------------------------------------------------- the counts

def test_param_counts_and_the_cut_s_arithmetic():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    assert mellum_flops.attention_params(config) == 21_233_664
    assert mellum_flops.expert_params(config) == 6_193_152
    assert mellum_flops.layer_params(config) == 417_747_456
    n = mellum_flops.mellum_param_count(config)
    assert n == 2_123_976_960
    assert f"{n:,}" in config["deployment"]
    assert round(16 * n / 1e9, 2) == 33.98 and round(4 * n / 1e9, 2) == 8.50
    whole, active = mellum_flops.whole_model_params(config["catalog_config"])
    assert round(whole / 1e9, 2) == 12.15 and round(active / 1e9, 2) == 2.44
    # the program makes as many
    from ray_tpu.models.transformer import transformer_init
    family = spec.load_code(spec.ROOT, "loops", "mellum")
    shapes = jax.eval_shape(lambda: transformer_init(
        jax.random.PRNGKey(0), family.model_config(config)))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) == n


def test_the_operation_shares_are_the_issue_s():
    cell = spec.load_cell(spec.ROOT, CELL)
    config, seq_len = cell["config"], cell["traffic"]["units_per_row"]
    parts = mellum_flops.forward_parts(config, seq_len)
    total = sum(parts.values())
    millions = {k: round(v / 1e6) for k, v in parts.items()}
    assert millions == {"attention_projections": 170, "full_attention": 134,
                        "sliding_attention": 49, "router": 1, "experts": 396,
                        "head": 453}
    assert round(total / 1e6) == 1203
    shares = {k: round(100 * v / total) for k, v in parts.items()}
    assert (shares["head"], shares["experts"], shares["attention_projections"],
            shares["full_attention"], shares["sliding_attention"]) == (
        38, 33, 14, 11, 4)
    assert mellum_flops.keys_per_query(seq_len, 1024) == pytest.approx(
        992.03, abs=0.01)
    assert mellum_flops.mellum_flops_per_token(config, seq_len) == 3 * total
    # the program's own count is the same
    from ray_tpu.models.transformer import flops_per_token
    family = spec.load_code(spec.ROOT, "loops", "mellum")
    assert flops_per_token(family.model_config(config), seq_len) == (
        pytest.approx(3 * total, rel=1e-9))
    # about 79 TFLOP a chip a step at 4 forward counts a token
    assert round(4 * total * 16384 / 1e12) == 79
    # the exchange: 226.5 MB into a chip a pass
    assert mellum_flops.exchange_bytes(16384, 4, 2304) == 226_492_416


def test_flash_operations_by_hand():
    fwd = mellum_flops.flash_call("flash_fwd", 1, 32, 4, 16384, None, 128)
    assert fwd[0] == 2.0 * 134_225_920 * 32 * 2 * 128
    band = mellum_flops.flash_call("flash_fwd", 1, 32, 4, 16384, 1024, 128)
    assert band[0] == 2.0 * 16_253_440 * 32 * 2 * 128
    at_q, at_kv, row = 32 * 16384 * 128, 4 * 16384 * 128, 32 * 16384 * 8 * 4
    assert fwd[1] == band[1] == (2 * at_q + 2 * at_kv) * 2 + row
    bwd = mellum_flops.flash_call(
        "flash_bwd_dkv_dq", 1, 32, 4, 16384, 1024, 128)
    assert bwd[0] == 2.0 * 16_253_440 * 32 * 5 * 128
    assert bwd[1] == (3 * at_q + 4 * at_kv) * 2 + 2 * row


def test_configuration_holds_the_catalog_s_numbers():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    source = config["catalog_config"]
    reduced = {"num_hidden_layers": 4, "layer_types": source["layer_types"][:4],
               "mlp_layer_types": ["sparse"] * 4}
    entry = spec.by_name(BENCH["configs"], CONFIG, "config")
    assert entry["reduced"] == config["reduced"] == list(reduced)
    assert entry["source"] in config["source"] and len(entry["source"]) <= 200
    for key, value in source.items():
        assert config[key] == reduced.get(key, value), key
    for key in reduced:
        assert config["published"][key] == source[key], key
    assert source["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 7
    # every width as published, in the keys the program reads
    assert config["d_model"] == source["hidden_size"] == 2304
    assert config["n_heads"] == source["num_attention_heads"] == 32
    assert config["n_kv_heads"] == source["num_key_value_heads"] == 4
    assert config["d_head"] == source["head_dim"] == 128
    assert config["d_ff"] == source["moe_intermediate_size"] == 896
    assert config["n_experts"] == source["num_experts"] == 64
    assert config["experts_per_token"] == source["num_experts_per_tok"] == 8
    assert config["sliding_window"] == source["sliding_window"] == 1024
    assert config["vocab_size"] == source["vocab_size"] == 98304
    full = source["rope_parameters"]["full_attention"]
    assert config["rope_theta"] == full["rope_theta"] == 500000
    assert config["rope_theta_sliding"] == source["rope_parameters"][
        "sliding_attention"]["rope_theta"] == 500000
    assert config["rope_scaling"] == {
        k: v for k, v in full.items() if k != "rope_theta"}
    assert config["rope_scaling"]["attention_factor"] == pytest.approx(
        0.1 * math.log(16) + 1)
    assert config["state_share"] == {"expected": 0.25, "tolerance": 0.02}
    assert {"balance_loss", "window", "rotary_layout", "initialisers",
            "optimizer", "layer_types", "mtp_head", "exchange", "head"} <= set(
        config["assumed"])


def test_traffic_mix_is_the_issue_s():
    cell = spec.load_cell(spec.ROOT, CELL)
    traffic = cell["traffic"]
    assert cell["workload"]["traffic"] == TRAFFIC
    assert (traffic["rows_per_block"], traffic["batch_rows"]) == (4, 4)
    assert traffic["columns"]["tokens"]["shape"] == [16385]
    assert traffic["units_per_row"] == cell["config"]["max_seq_len"] == 16384
    assert (traffic["steps_per_chunk"], traffic["warmup_steps"],
            traffic["trace_chunks"]) == (1, 2, 2)
    assert traffic_lib.units_per_step(traffic) == 65536
    slots = 65536 * cell["config"]["experts_per_token"]
    assert slots == 524288 and slots // 64 == 8192 and slots // 4 == 131072
    rows = traffic_lib.make_rows(traffic, cell["config"], 2**31 + 7, 0, 4)
    assert rows["tokens"].shape == (4, 16385)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 98304


def test_the_cell_s_files_are_found_by_name_under_another_root(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    for kind, name in (("configs", CONFIG + ".json"),
                       ("traffic", TRAFFIC + ".json")):
        os.makedirs(os.path.join(root, "chipbench", kind), exist_ok=True)
        shutil.copy(os.path.join(spec.ROOT, "chipbench", kind, name),
                    os.path.join(root, "chipbench", kind, name))
    cell = spec.load_cell(root, CELL)
    assert cell == spec.load_cell(spec.ROOT, CELL)
    assert cell["workload"]["chips"] == 4
    assert cell["workload"]["config"] == CONFIG
    assert {m["name"] for m in spec.metrics_of(BENCH, CELL, "end_to_end")} == {
        "train_tokens_per_s", "setup_s"}


def test_two_four_chip_cells_of_eleven():
    cells = BENCH["workloads"]
    assert CELL in {w["name"] for w in cells} and len(cells) >= 11
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert four == ["mistral7b.fsdp4", CELL]
    assert len(four) <= max(1, len(cells) // 4)
    for entry in (spec.by_name(cells, CELL, "workload"),
                  spec.by_name(BENCH["configs"], CONFIG, "config")):
        assert len(entry["why"]) <= 200


@pytest.mark.parametrize("name", LISTS + ["train_tokens_per_s"])
def test_the_cell_is_in_the_list(name):
    """`in`, never `==`: a test that pins a list to the cells of its day
    breaks at the next cell."""
    kind = "end_to_end" if name == "train_tokens_per_s" else "per_layer"
    entry = spec.by_name(BENCH[kind], name, "metric")
    assert CELL in entry["workloads"]
    assert entry in spec.metrics_of(BENCH, CELL, kind)


def test_collective_time_share_reads_the_exposed_share_s_events():
    """The events of `collective_exposed_share.tokens` and a `shard_map`'s
    `psum.N`, which those patterns miss, over the busy time where that one
    is over the window."""
    entry = spec.by_name(
        BENCH["per_layer"], "collective_time_share.tokens", "metric")
    exposed = spec.by_name(
        BENCH["per_layer"], "collective_exposed_share.tokens", "metric")
    assert entry["workloads"] == [CELL] and entry["unit"] == "%"
    assert entry["layer"] == exposed["layer"]
    assert (entry["moves"], entry["source"], entry["better"]) == (
        "train_tokens_per_s", "device_trace", "lower")
    files = [spec.read_json(spec.ROOT, "chipbench", "metrics", m["name"] + ".json")
             for m in (entry, exposed)]
    assert files[0]["reader"] == files[1]["reader"] == "trace_share"
    assert files[0]["params"]["patterns"] == files[1]["params"]["patterns"] + [
        "^psum"]
    assert (files[0]["params"]["over"], files[1]["params"]["over"]) == (
        "busy", "window")
    cell = spec.load_cell(spec.ROOT, CELL)
    untraced = dict(fake_summary(cell), chips=4, trace=None)
    assert spec.read_metric(spec.ROOT, entry["name"], untraced) is None
    run_ = dict(untraced, trace=fake_reduced(4))
    # 40 ns of all-gather in 940 busy of a window of 1010, on every device
    assert spec.read_metric(spec.ROOT, entry["name"], run_) == pytest.approx(
        100 * 40 / 940)
    assert spec.read_metric(spec.ROOT, exposed["name"], run_) == pytest.approx(
        100 * 40 / 1010)
    # with the head's gradient sum under `shard_map`, 30 ns on every device
    ops = [["fusion.1", 0, 600], ["kernel.2 [tpu_custom_call]", 600, 300],
           ["psum.255", 900, 30], ["all-gather.3", 950, 40]]
    summed = dict(untraced, trace=trace.reduce({
        "devices": {f"/device:TPU:{i}": {"ops": ops, "modules": [
            ["jit_step", 0, 1000]]} for i in range(4)},
        "host_spans": [["report", 990, 20]]}))
    assert spec.read_metric(spec.ROOT, entry["name"], summed) == pytest.approx(
        100 * 70 / 970)
    assert spec.read_metric(spec.ROOT, exposed["name"], summed) == pytest.approx(
        100 * 40 / 1010)
    line = run.last_line(spec.ROOT, BENCH, cell, fake_summary(cell),
                         fake_reduced(4))
    named = {m["name"] for m in spec.metrics_of(BENCH, CELL, "per_layer")}
    assert set(line["metrics"]) == named
    assert {"gang_boot_s", "state_init_s"} <= named
    assert all(0 <= m["value"] <= 100 for m in line["metrics"].values()
               if m["unit"] == "%")


@pytest.mark.parametrize("name", WAITING)
def test_waiting_metrics_carry_their_entry(name):
    """Under the key `awaits`, as the other families' rooflines are; the
    reader finds the kernel by its name and reads 50 where one call takes
    twice the least time."""
    from chipbench import flops, kernel_flops, moe_flops, trace

    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry = held["awaits"]
    assert "entry" not in held
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert entry["name"] == name and entry["unit"] == "%"
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_tokens_per_s"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert name not in {m["name"] for m in BENCH["per_layer"]}  # still waiting
    cell = spec.load_cell(spec.ROOT, CELL)
    config, params = cell["config"], held["params"]
    summary = fake_summary(cell)
    for chunk in summary["chunks"]:
        chunk.update(steps=1, units=65536)
    untraced = dict(summary, chips=4, trace=None)
    assert spec.read_metric(spec.ROOT, name, untraced) is None
    bare = dict(untraced, trace=fake_reduced(4))  # no such event: no raise
    assert spec.read_metric(spec.ROOT, name, bare) is None
    peaks = flops.peaks_for("TPU v5 lite")
    if held["reader"] == "gmm_roofline":
        assert params["experts"] == config["n_experts"] // 4 == 16
        assert params["experts_per_token"] == config["experts_per_token"] == 8
        d, f = config["d_model"], config["d_ff"]
        assert sorted(map(tuple, params["products"])) == sorted(
            [(d, f), (d, f), (f, d)])
        least = kernel_flops.least_seconds(
            *moe_flops.gmm_call(131072, d, f, 16), peaks)[0]
        event = params["event"]
    else:
        assert held["reader"] == "gqa_flash_roofline"
        assert params["n_heads"] == config["n_heads"]
        assert params["n_kv_heads"] == config["n_kv_heads"]
        assert params["head_dim"] == config["d_head"]
        assert params["seq_len"] == cell["traffic"]["units_per_row"]
        assert params["window"] in (None, config["sliding_window"])
        assert params["event"] == params["kernel"] + (
            "_window" if params["window"] else "")
        least = kernel_flops.least_seconds(*mellum_flops.flash_call(
            params["kernel"], 1, 32, 4, 16384, params["window"], 128), peaks)[0]
        event = params["event"]
    ns = round(2 * least * 1e9)
    other = event + "_window" if not event.endswith("_window") else event[:-7]
    reduced = trace.reduce({"devices": {"/device:TPU:0": {"ops": [
        [f"{event}.3 [tpu_custom_call]", 0, ns],
        [f"{other}.4 [tpu_custom_call]", ns, 1000]],
        "modules": [["jit_step", 0, ns + 1000]]}}, "host_spans": []})
    assert spec.read_metric(
        spec.ROOT, name, dict(untraced, trace=reduced)) == pytest.approx(
        50.0, rel=1e-6)


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(spec.ROOT, "chipbench", "reference", "mellum.py")
    with open(path) as f:
        source = f.read()
    assert "ray_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "MTP head" in source and "left out" in source
    for imported in ("laguna", "lfm2_moe", "transformer"):
        with open(os.path.join(spec.ROOT, "chipbench", "reference",
                               imported + ".py")) as f:
            assert "ray_tpu" not in f.read().split('"""', 2)[2]
