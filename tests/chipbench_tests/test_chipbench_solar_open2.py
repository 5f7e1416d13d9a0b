"""The `solar_open2` family and its cell `solaropen2.tokens8k` (CPU only):
the configuration file's counts and widths against the catalog's row, the
operation counts by hand against the program's, the system against the plain
reference at a tiny size, each wrong mathematics and each lower precision
outside the tolerance, the cell's loop end to end, the last line's keys, and
the files and entries the cell was added by."""

import copy
import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare, loop, run, solar_open2_flops, spec
from chipbench import traffic as traffic_lib
from chipbench_tiny import fake_reduced, fake_summary, run_loop_here

CELL = "solaropen2.tokens8k"
CONFIG = "solar-open2-250b-l4-tp8ep40"
BENCH = spec.load_benchmark(spec.ROOT)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the lists of BENCHMARK.json the cell joins (ISSUE 55, step 6)
LISTS = [
    "ingest_wait_share.tokens", "steady_rate.tokens", "stall_share.tokens",
    "model_mfu.tokens", "pallas_time_share.tokens", "device_idle_share.tokens",
    "peak_hbm_gb.tokens", "moe_gmm_time_share.tokens",
    "moe_sum_time_share.tokens", "flash_time_share.tokens", "cluster_init_s",
    "compile_s", "first_batch_s", "setup_unnamed_s",
    "ingest_produce_share.tokens", "trace_s", "lower_s", "pallas_trace_s",
    "before_first_program_s", "before_init_s"]
FAULTS = [
    "decay_a_head_not_a_channel", "beta_in_0_1", "keys_not_unit_length",
    "taps_dropped", "kda_gate_dropped", "attention_gate_dropped",
    "rotary_applied", "kda_float32_parts_in_bf16", "bf16_everything"]


def held_config():
    return spec.read_json(spec.ROOT, "chipbench", "configs", CONFIG + ".json")


def tiny_solar(dtype="bfloat16", **over):
    """64 wide, heads of 16: GQA (8 query heads over 2 key-value heads, 4
    held with their one key-value head), KDA, KDA, KDA (8 heads, 4 held,
    chunks of 32); 4 of 16 experts held, 4 a token, one shared; sequences of
    64, compared at 48 (two chunks, the second ragged)."""
    cell = copy.deepcopy(spec.load_cell(spec.ROOT, CELL))
    config, traffic = cell["config"], cell["traffic"]
    config.update(vocab_size=256, d_model=64, n_heads=8, n_kv_heads=2,
                  d_head=16, heads_held=[4, 4], kda_heads=8, kda_head_dim=16,
                  kda_gate_rank=8, kda_chunk=32, d_ff=32, d_ff_shared=32,
                  n_experts=16, experts_held=[4, 4], experts_per_token=4,
                  max_seq_len=64, dtype=dtype,
                  check={"rows": 2, "seq_len": 48}, **over)
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
    same key with the same value, but for the four of `reduced`; nested
    groups whole. No width is among the four."""
    held = held_config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert held["source"].startswith(row["source_url"])
    assert held["catalog_config"] == row["config"]
    reduced = set(held["reduced"])
    assert reduced == {"num_hidden_layers", "gqa_layers", "n_routed_experts",
                       "vocab_size"}
    for key, value in row["config"].items():
        if key in reduced:
            assert held[key] != value and held["published"][key] == value
        else:
            assert held[key] == value, key
    assert (held["num_hidden_layers"], held["gqa_layers"],
            held["n_routed_experts"], held["vocab_size"]) == (4, [0], 8, 24576)


def test_every_width_the_program_runs_is_the_published_one():
    held = held_config()
    row = held["catalog_config"]
    linear = row["linear_attn_config"]
    assert held["d_model"] == row["hidden_size"] == 4096
    assert held["d_head"] == row["head_dim"] == 128
    assert (held["n_heads"], held["n_kv_heads"]) == (
        row["num_attention_heads"], row["num_key_value_heads"]) == (64, 8)
    assert (held["kda_heads"], held["kda_head_dim"], held["kda_conv_taps"]) == (
        linear["num_heads"], linear["head_dim"],
        linear["short_conv_kernel_size"]) == (64, 128, 4)
    assert held["kda_gate_rank"] == linear["head_dim"]  # kda_use_full_proj false
    assert held["d_ff"] == held["d_ff_shared"] == row["moe_intermediate_size"]
    assert held["n_shared_experts"] == row["n_shared_experts"] == 1
    assert held["n_experts"] == row["n_routed_experts"] == 320
    assert held["experts_per_token"] == row["num_experts_per_tok"] == 8
    assert held["norm_eps"] == row["rms_norm_eps"]
    assert held["rope"] is row["use_rope"] is False
    assert held["tied_embeddings"] is row["tie_word_embeddings"] is False
    assert held["layer_types"] == ["full_attention", "kda", "kda", "kda"]
    assert row["gqa_layers"][:2] == [0, 4] and row["gqa_interval"] == 3
    # the shares: 8 of 64 heads, 8 of 320 experts, an eighth of the ids
    assert held["heads_held"] == [0, 8] and held["experts_held"] == [0, 8]
    assert held["vocab_size"] * 8 == row["vocab_size"]
    ways = held["published"]["ways"]
    assert held["published"]["chips_sharing_a_layer"] == 40 == ways["experts"]
    assert (ways["heads"], ways["vocab"]) == (8, 8)
    assert 40 * held["experts_held"][1] == row["n_routed_experts"]
    for word in ("assumed", "deployment", "check", "mesh", "optimizer"):
        assert word in held
    for key in ("kda_use_full_proj", "kda_bias", "kda_decay", "num_kv_heads",
                "kda_scale", "kda_chunk", "gqa_gate", "router", "balance_loss",
                "sequence", "optimizer", "initialisers", "dtype", "remat"):
        assert key in held["assumed"], key
    assert held["check"] == {"rows": 1, "seq_len": 2048}


def test_the_state_is_840874392_parameters_13_45_gb():
    """The count by hand, the count of the program's own leaves, and the
    words of `deployment` agree."""
    from ray_tpu.models.transformer import transformer_init

    held = held_config()
    by_hand = solar_open2_flops.state_params(held)
    assert by_hand == 840_874_392
    assert round(16 * by_hand / 1e9, 2) == 13.45
    family = spec.load_code(spec.ROOT, "loops", "solar_open2")
    cfg = family.model_config(held)
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == by_hand
    kda_layer = shapes["blocks"][0][1]
    assert sum(x.size for name, x in kda_layer.items()
               if name.startswith("kda") and name != "kda_norm") == 18_135_176
    gqa = shapes["blocks"][0][0]
    assert sum(gqa[name].size for name in (
        "wq", "wk", "wv", "wo", "w_gate_attn")) == 13_631_488
    assert sum(x.size for x in jax.tree.leaves(kda_layer)) == 161_011_848
    assert sum(x.size for x in jax.tree.leaves(gqa)) == 156_508_160
    for number in ("840,874,392", "18,135,176", "13,631,488", "161,011,848",
                   "156,508,160", "639,543,704", "13.45 GB"):
        assert number in held["deployment"], number


def test_operations_by_hand_are_the_programs():
    """`solar_open2_flops.py` counts from the shapes; the program counts
    from its records: the same number, part by part."""
    from ray_tpu.models.transformer import _fwd_flops_per_token, flops_per_token

    held = held_config()
    cfg = spec.load_code(spec.ROOT, "loops", "solar_open2").model_config(held)
    parts = solar_open2_flops.forward_parts(held, 8192)
    matmul, attention, head = _fwd_flops_per_token(cfg, 8192)
    assert head == parts["head"] == 2 * 4096 * 24576
    assert attention == parts["attention_pairs"] == 2 * 2 * 8 * 128 * 8193 / 2
    assert matmul == pytest.approx(
        sum(parts.values()) - parts["head"] - parts["attention_pairs"], rel=1e-12)
    assert solar_open2_flops.solar_open2_flops_per_token(
        held, 8192) == pytest.approx(flops_per_token(cfg, 8192), rel=1e-12)
    # by hand: a head and token of the chunked form at C 64, d 128
    assert parts["kda_chunked"] == 3 * 8 * (10 * 64 * 128 + 6 * 128 * 128)
    assert parts["kda_matmuls"] == 3 * 2 * (
        4 * 4096 * 1024 + 2 * (4096 * 128 + 128 * 1024) + 4096 * 8)
    assert parts["held_experts"] == 4 * (8 * 8 / 320) * 2 * 3 * 4096 * 1280
    assert parts["shared_experts"] == 4 * 2 * 3 * 4096 * 1280
    total = sum(parts.values())
    assert 519e6 < total < 521e6
    shares = {name: value / total for name, value in parts.items()}
    assert round(100 * shares["head"]) == 39
    assert round(100 * shares["shared_experts"]) == 24
    assert round(100 * (shares["kda_matmuls"] + shares["kda_chunked"])) == 22


# ---------------------------------------------------------- the comparison

def wrong_systems(cell, family):
    """{name: a system to hand `family.errors_of`}: each computes something
    other than the published model, or the stated one in a lower
    precision."""
    from ray_tpu.models import transformer
    from ray_tpu.ops import kda as kda_lib

    cfg, mesh = family.model_config, family.mesh
    system = family.system_loss_and_readings

    def with_cfg(**changed):
        wrong = dataclasses.replace(cfg, **changed)
        return lambda p, b: transformer.transformer_loss_and_readings(
            p, b, wrong, mesh=mesh)

    def patched(module, name, replacement, inner=system):
        def run_patched(*args):
            real = getattr(module, name)
            setattr(module, name, replacement(real))
            try:
                return inner(*args)
            finally:
                setattr(module, name, real)
        return run_patched

    def kda_with(change):
        return patched(transformer, "kda", lambda real: (
            lambda q, k, v, g, beta, **kw: real(*change(q, k, v, g, beta), **kw)))

    def without(leaf):
        def dropped(p, b):
            blocks = [[{k: v for k, v in blk.items() if k != leaf}
                       for blk in seg] for seg in p["blocks"]]
            return system({**p, "blocks": blocks}, b)
        return dropped

    def kda_gate_dropped(p, b):  # sigmoid(bias) = 1 where the bias is large
        blocks = [[{k: (jnp.zeros_like(v) if k == "kda_g2" else
                        v + 30.0 if k == "kda_g_bias" else v)
                    for k, v in blk.items()} for blk in seg]
                  for seg in p["blocks"]]
        return system({**p, "blocks": blocks}, b)

    def bf16_everything(p, b):
        p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
        loss, readings = system(p, b)
        return loss.astype(jnp.bfloat16).astype(jnp.float32), readings

    def in_bf16(real):
        return jnp.bfloat16

    def probe_in_bf16(*probe):  # the recurrence alone, as the probe runs it
        return kda_lib.kda(*probe, chunk=cfg.kda_chunk)[0]

    return {
        # what `ops/ssd.py` can say: one decay a head, the channels' mean
        "decay_a_head_not_a_channel": kda_with(lambda q, k, v, g, beta: (
            q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta)),
        "beta_in_0_1": kda_with(  # kda_allow_neg_eigval false
            lambda q, k, v, g, beta: (q, k, v, g, beta / 2)),
        "keys_not_unit_length": patched(
            transformer, "_unit_length", lambda real: lambda x, eps=0: x),
        "taps_dropped": patched(  # this token's tap alone
            transformer, "_causal_taps", lambda real: lambda u, w: w[-1] * u),
        "kda_gate_dropped": kda_gate_dropped,
        "attention_gate_dropped": without("w_gate_attn"),
        "rotary_applied": with_cfg(rope=True),
        # the decays' sums, every exp, the solve and the states in bf16
        "kda_float32_parts_in_bf16": (
            patched(kda_lib, "_F32", in_bf16),
            patched(kda_lib, "_F32", in_bf16, probe_in_bf16)),
        "bf16_everything": bf16_everything,
    }


def errors_of_wrong(family, wrong, params, batch):
    system, kda_fn = wrong if isinstance(wrong, tuple) else (wrong, None)
    extra = {} if kda_fn is None else {"kda_fn": kda_fn}
    return family.errors_of(system, params, batch, **extra)


def test_tiny_cell_keeps_the_family_and_its_mechanisms():
    config = tiny_solar()["config"]
    assert config["family"] == "solar_open2"
    assert config["layer_types"] == ["full_attention", "kda", "kda", "kda"]
    assert config["attn_gate"] == "elementwise" and config["rope"] is False
    assert config["router_score"] == "softmax" and config["norm_topk_prob"]
    assert config["n_shared_experts"] == 1
    assert config["heads_held"] == [4, 4] and config["experts_held"] == [4, 4]
    assert config["check"]["seq_len"] > config["kda_chunk"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype, in_float32):
    if dtype == "float32":
        cell, family, params, batch = in_float32
    else:
        cell = tiny_solar(dtype)
        family = family_of(cell)
        params = family.init_params(loop.seed_key(2**31 + 3))
        batch = check_batch(cell, family)
    errors = family.check(params, batch)
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 2e-5
        assert errors["router_flip_share"] == 0.0
        assert errors["aux_loss_rel_err"] < 1e-6
        assert errors["kda_rel_err"] < 1e-5
    else:
        assert errors["kda_rel_err"] < 2 * family.tolerance["kda_rel_err"]
        assert errors["loss_rel_err"] < 3 * family.tolerance["loss_rel_err"]
        assert errors["router_flip_share"] < 0.05
        # heads of 16 scaled to unit length round far harder than heads of
        # 128 (the chip reads 2.5e-2 at the published widths)
        assert errors["grad_rel_err"] < 0.3
        assert errors["aux_loss_rel_err"] < 2e-3
    assert errors["dropped_slots"] == errors["unrouted_slots"] == 0.0
    assert errors["expert_load_max_over_mean"] >= 1.0
    assert 0 < errors["held_slots_mean"] < 2 * 48 * 4
    assert errors["kda_log_decay_min"] < 0.0
    assert 0.0 < errors["kda_beta_mean"] < 2.0


@pytest.fixture(scope="module")
def in_float32():
    """(cell, family, parameters, batch): made once for all the faults, so
    that the reference's programs are compiled once."""
    cell = tiny_solar("float32")
    family = family_of(cell)
    return (cell, family, family.init_params(loop.seed_key(2**31 + 3)),
            check_batch(cell, family))


@pytest.mark.parametrize("fault", FAULTS)
def test_wrong_mathematics_is_outside_the_tolerance(fault, in_float32):
    """In float32, where the stated path agrees to rounding, so that what
    is left is the fault's own (`loops/solar_open2.py` has the chip's
    readings)."""
    cell, family, params, batch = in_float32
    wrong = errors_of_wrong(
        family, wrong_systems(cell, family)[fault], params, batch)
    assert not compare.within(wrong, family.tolerance), wrong
    if fault == "bf16_everything":
        assert wrong["loss_rel_err"] > family.tolerance["loss_rel_err"]
    elif fault == "kda_float32_parts_in_bf16":  # held by the probe alone
        # (48 tokens of heads 16 wide: the sums are short; the chip reads
        # this fault at the published shapes, `loops/solar_open2.py`)
        assert wrong["kda_rel_err"] > family.tolerance["kda_rel_err"]
    else:
        assert wrong["kda_rel_err"] < 1e-4  # float32: the stated recurrence
        assert wrong["grad_rel_err"] > 1.5 * family.tolerance["grad_rel_err"], wrong


def test_the_reference_imports_nothing_of_the_program():
    import chipbench.reference.solar_open2 as reference

    source = open(reference.__file__).read()
    assert "ray_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "lax.scan(one_token" in source  # the recurrence, token by token
    # and its recurrence is the program's own, written apart
    from ray_tpu.ops.kda import kda_recurrent

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(key, (1, 128, 2, 8)) for key in ks[:3])
    g = -jax.random.uniform(ks[3], (1, 128, 2, 8))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (1, 128, 2)))
    ours, _ = kda_recurrent(q, k, v, g, beta)
    theirs = reference.delta_rule(q, k, v, g, beta)
    assert float(jnp.abs(ours - theirs).max()) < 1e-5


# ------------------------------------------------------------- the cell

def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_solar()
    reports = run_loop_here(monkeypatch, cell, seconds=0.4)
    summary = reports[-1]
    assert summary["summary"] and summary["steps_failed"] == 0
    assert summary["compiles_in_window"] == 0
    assert summary["steps"] == 2 * len(summary["chunks"]) >= 2
    assert all(math.isfinite(c["loss"]) for c in summary["chunks"])
    reference = summary["reference"]
    assert {"router_flip_share", "aux_loss_rel_err", "held_slots_mean",
            "dropped_slots", "kda_log_decay_min", "kda_beta_mean",
            "kda_rel_err"} <= set(
                reference)
    assert reference["dropped_slots"] == 0.0
    assert reference["loss_rel_err"] < 3 * reference["tolerance"]["loss_rel_err"]
    assert summary["flops_per_unit"] == pytest.approx(
        solar_open2_flops.solar_open2_flops_per_token(cell["config"], 64))
    assert all(c["units"] == 2 * 64 for c in summary["chunks"])


def test_the_step_reports_the_operators_readings(in_float32):
    cell, family, params, batch = in_float32
    # the step donates its state: a copy, the fixture's stay
    state = family.init_state(jax.tree.map(jnp.copy, params))
    state, out = family.step(state, batch)
    assert {"loss", "grad_norm", "aux_loss", "expert_load", "held_slots",
            "dropped_slots", "kda_log_decay_min", "kda_beta_mean"} <= set(out)
    assert out["expert_load"].shape == (4, 16)  # every layer is routed
    assert out["kda_log_decay_min"].shape == () and out["kda_beta_mean"].shape == ()
    assert float(out["kda_log_decay_min"]) < 0 < float(out["kda_beta_mean"]) < 2


@pytest.mark.parametrize("traced", [False, True])
def test_last_line_keys(traced):
    cell = spec.load_cell(spec.ROOT, CELL)
    reduced = fake_reduced(1) if traced else None
    line = run.last_line(spec.ROOT, BENCH, cell, fake_summary(cell), reduced)
    assert line["correct"] is True
    if traced:
        assert {"model_mfu.tokens", "peak_hbm_gb.tokens",
                "device_idle_share.tokens", "steady_rate.tokens"} <= set(
                    line["metrics"])
        assert "kda_time_share.tokens" not in line["metrics"]  # it waits
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_the_cell_and_its_lists():
    cell = spec.by_name(BENCH["workloads"], CELL, "workload")
    assert cell == {**cell, "config": CONFIG, "traffic": "tokens-8k-8k-tp8",
                    "chips": 1}
    assert BENCH["workloads"][-1] is cell and BENCH["configs"][-1]["name"] == CONFIG
    assert len(BENCH["workloads"]) == 12
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 2
    tokens = spec.by_name(BENCH["end_to_end"], "train_tokens_per_s", "metric")
    assert tokens["workloads"][-1] == CELL
    for name in LISTS:
        assert spec.by_name(BENCH["per_layer"], name, "metric")[
            "workloads"][-1] == CELL, name
    for name in ("flash_window_time_share.tokens", "index_time_share.tokens",
                 "collective_time_share.tokens"):
        assert CELL not in spec.by_name(BENCH["per_layer"], name, "metric")[
            "workloads"]


def test_the_traffic_is_one_sequence_of_8192_a_step():
    traffic = spec.load_cell(spec.ROOT, CELL)["traffic"]
    assert (traffic["kind"], traffic["batch_rows"], traffic["rows_per_block"],
            traffic["units_per_row"]) == ("ingest", 1, 1, 8192)
    assert traffic["columns"]["tokens"]["shape"] == [8193]
    assert traffic["columns"]["tokens"]["high"] == "config:vocab_size"
    assert traffic["warmup_steps"] == 2 and traffic["blocks_per_epoch"] == 256
    rows = traffic_lib.make_rows(traffic, held_config(), 2**31 + 7, 0, 1)
    assert rows["tokens"].shape == (1, 8193)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 24576


def test_the_waiting_metric_file():
    """Under the key `awaits`, as PR 38's `ssd_time_share.tokens` is."""
    held = spec.read_json(spec.ROOT, "chipbench", "metrics",
                          "kda_time_share.tokens.json")
    entry = held["awaits"]
    assert held["reader"] == "scope_share" and held["params"] == {"scope": "kda"}
    assert entry["name"] == "kda_time_share.tokens" and entry["unit"] == "%"
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert not any(m["name"] == entry["name"] for m in BENCH["per_layer"])
    assert os.path.exists(os.path.join(
        spec.ROOT, "chipbench", "readers", held["reader"] + ".py"))
