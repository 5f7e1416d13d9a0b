"""The `ouro` family and its cell `ouro.tokens16k` (CPU only): the
configuration file's counts and widths against the catalog's row, the
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

from chipbench import compare, loop, ouro_flops, run, spec
from chipbench import traffic as traffic_lib
from chipbench_tiny import fake_reduced, fake_summary, run_loop_here

CELL = "ouro.tokens16k"
CONFIG = "ouro-2.6b-l8"
BENCH = spec.load_benchmark(spec.ROOT)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the lists of BENCHMARK.json the cell joins (ISSUE 57, step 7)
LISTS = [
    "ingest_wait_share.tokens", "steady_rate.tokens", "stall_share.tokens",
    "model_mfu.tokens", "pallas_time_share.tokens", "device_idle_share.tokens",
    "peak_hbm_gb.tokens", "trace_s", "lower_s", "pallas_trace_s",
    "before_first_program_s", "before_init_s", "flash_time_share.tokens",
    "compile_s", "cluster_init_s", "first_batch_s", "setup_unnamed_s",
    "ingest_produce_share.tokens"]
# step 5's five, and the stated path a precision lower
FAULTS = ["exit_in_bf16", "pass_dropped", "no_norm_between_passes",
          "weights_held_constant", "post_norms_dropped", "bf16_everything"]
WAITING = {
    "exit_time_share.tokens": "scope_sum_share",
    "lm_head_ce_time_share.ouro.tokens": "scope_share",
    "recompute_time_share.ouro.tokens": "scope_share",
    "flash_fwd_roofline.ouro.tokens": "kernel_roofline",
    "flash_bwd_dkv_dq_roofline.ouro.tokens": "gqa_flash_roofline",
}


def held_config():
    return spec.read_json(spec.ROOT, "chipbench", "configs", CONFIG + ".json")


def tiny_ouro(dtype="bfloat16", **over):
    """64 wide, 4 heads of 16 over 4 key-value heads, SwiGLU of 96, 2 layers
    run 4 times; sequences of 64, compared at 48."""
    cell = copy.deepcopy(spec.load_cell(spec.ROOT, CELL))
    config, traffic = cell["config"], cell["traffic"]
    config.update(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=4,
                  d_head=16, d_ff=96, n_layers=2,
                  layer_types=["full_attention"] * 2, max_seq_len=64,
                  dtype=dtype, check={"rows": 2, "seq_len": 48}, **over)
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
    same key with the same value, but for the two of `reduced`. No width is
    among the two."""
    held = held_config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert held["source"].startswith(row["source_url"])
    assert spec.by_name(BENCH["configs"], CONFIG, "config")["source"] == (
        row["source_url"])
    assert held["catalog_config"] == row["config"]
    reduced = set(held["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types"}
    for key, value in row["config"].items():
        if key in reduced:
            assert held[key] != value
        else:
            assert held[key] == value, key
    assert held["published"]["num_hidden_layers"] == 48
    assert held["num_hidden_layers"] == 8
    assert held["layer_types"] == ["full_attention"] * 8
    assert row["config"]["layer_types"] == ["full_attention"] * 48
    assert held["total_ut_steps"] == 4 and held["early_exit_threshold"] == 1


def test_every_width_the_program_runs_is_the_published_one():
    held = held_config()
    row = held["catalog_config"]
    assert held["d_model"] == row["hidden_size"] == 2048
    assert held["d_head"] == row["head_dim"] == 128
    assert (held["n_heads"], held["n_kv_heads"]) == (
        row["num_attention_heads"], row["num_key_value_heads"]) == (16, 16)
    assert held["d_ff"] == row["intermediate_size"] == 5632
    assert held["vocab_size"] == row["vocab_size"] == 49152
    assert held["rope_theta"] == row["rope_theta"] == 1_000_000
    assert held["norm_eps"] == row["rms_norm_eps"] == 1e-6
    assert held["loop_steps"] == row["total_ut_steps"] == 4
    assert held["tied_embeddings"] is row["tie_word_embeddings"] is False
    assert held["n_layers"] == held["num_hidden_layers"] == 8
    assert held["max_seq_len"] == 16384 <= row["max_position_embeddings"]
    assert held["post_norm"] is held["exit_gate"] is held["remat"] is True
    assert held["exit_entropy_coef"] == 0.05
    published = held["published"]
    assert published["layers_held"] == [0, 8]
    assert published["stages"] * published["layers_a_stage"] == 48
    for word in ("assumed", "deployment", "check", "mesh", "optimizer"):
        assert word in held
    for key in ("sandwich_norms", "final_norm_between_passes", "exit_gate",
                "loss", "attention_bias", "seq_len", "optimizer",
                "initialisers", "dtype", "remat", "early_exit_threshold"):
        assert key in held["assumed"], key
    assert held["check"] == {"rows": 1, "seq_len": 2048}
    assert held["mesh"] == {"data": 1}
    optimizer = held["optimizer"]
    assert (optimizer["b1"], optimizer["b2"], optimizer["weight_decay"]) == (
        0.9, 0.95, 0.1)
    assert set(optimizer["no_decay"]) == {"norm", "exit_w", "exit_b"}


def test_the_state_is_612438017_parameters_9_80_gb():
    """The count by hand, the count of the program's own leaves, and the
    words of `deployment` agree."""
    from ray_tpu.models.transformer import transformer_init

    held = held_config()
    by_hand = ouro_flops.state_params(held)
    assert by_hand == 612_438_017 == held["published"]["parameters_held"]
    assert by_hand == 8 * 51_388_416 + 2 * 100_663_296 + 2_048 + 2_049
    assert round(16 * by_hand / 1e9, 2) == 9.80
    cfg = spec.load_code(spec.ROOT, "loops", "ouro").model_config(held)
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == by_hand
    assert sum(x.size for x in jax.tree.leaves(shapes["blocks"])) == (
        8 * 51_388_416)
    assert shapes["exit_w"].shape == (2048,) and shapes["exit_b"].shape == ()
    # whole: 48 layers
    assert held["published"]["parameters_whole"] == (
        48 * 51_388_416 + 2 * 100_663_296 + 2_048 + 2_049) == 2_667_974_657
    for number in ("612,438,017", "51,388,416", "100,663,296", "9.80 GB",
                   "58 %", "Six equal stages of 8 layers", "ring",
                   "six times its share"):
        assert number in held["deployment"], number


def test_no_weight_decay_on_the_norms_and_the_gate():
    from chipbench.loops.nemotron_h import decayed
    from ray_tpu.models.transformer import transformer_init

    cell = tiny_ouro()
    cfg = family_of(cell).model_config
    params = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    mask = decayed(params, cell["config"]["optimizer"]["no_decay"])
    undecayed = {path[-1].key for path, keep in
                 jax.tree_util.tree_leaves_with_path(mask) if not keep}
    assert undecayed == {"attn_norm", "attn_post_norm", "mlp_norm",
                         "mlp_post_norm", "final_norm", "exit_w", "exit_b"}


def test_operations_by_hand_are_the_programs():
    """`ouro_flops.py` counts from the shapes; the program counts from its
    records, every layer and the head once a pass: the same number."""
    from ray_tpu.models.transformer import _fwd_flops_per_token, flops_per_token

    held = held_config()
    cfg = spec.load_code(spec.ROOT, "loops", "ouro").model_config(held)
    parts = ouro_flops.forward_parts(held, 16384)
    matmul, attention, head = _fwd_flops_per_token(cfg, 16384)
    assert matmul == parts["layer_matmuls"] == 32 * 2 * 51_380_224
    assert attention == parts["attention_pairs"] == (
        32 * 2 * 2 * 16 * 128 * 16385 / 2)
    assert head == parts["heads"] + parts["exit_gate"] == 4 * 2 * 2048 * 49153
    assert ouro_flops.ouro_flops_per_token(held, 16384) == pytest.approx(
        flops_per_token(cfg, 16384), rel=1e-12)
    total = sum(parts.values())
    assert 6_241e6 < total < 6_242e6
    assert round(100 * parts["layer_matmuls"] / total) == 53
    assert round(100 * parts["attention_pairs"] / total) == 34
    assert round(100 * parts["heads"] / total) == 13
    # a stack run once with one head: a quarter of the layers' operations
    once = dataclasses.replace(cfg, loop_steps=1, exit_gate=False)
    m1, a1, h1 = _fwd_flops_per_token(once, 16384)
    assert (4 * m1, 4 * a1) == (matmul, attention) and h1 == 2 * 2048 * 49152


# ---------------------------------------------------------- the comparison

def wrong_systems(cell, family):
    """{name: a system to hand `family.errors_of`}: each computes something
    other than the published model, or the stated one in a lower
    precision."""
    from ray_tpu.models import transformer

    cfg = family.model_config
    system = family.system_loss_and_readings

    def patched(name, replacement, inner=system):
        def run_patched(*args):
            real = getattr(transformer, name)
            setattr(transformer, name, replacement(real))
            try:
                return inner(*args)
            finally:
                setattr(transformer, name, real)
        return run_patched

    def in_bf16(real):
        return jnp.bfloat16

    def with_cfg(**fields):
        other = dataclasses.replace(cfg, **fields)
        return lambda p, b: transformer.transformer_loss_and_readings(
            p, b, other, mesh=family.mesh)

    def detached_weights(real):
        return lambda hidden, unembed, targets, weights, **kw: real(
            hidden, unembed, targets, jax.lax.stop_gradient(weights), **kw)

    def post_norms_dropped(p, b):
        blocks = {k: v for k, v in p["blocks"].items()
                  if not k.endswith("post_norm")}
        return system({**p, "blocks": blocks}, b)

    def bf16_everything(p, b):
        p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
        loss, readings = system(p, b)
        return loss.astype(jnp.bfloat16).astype(jnp.float32), readings

    return {
        # the gate's logits, the distribution and the entropy in bfloat16
        # (the step's, and the probe's as the probe runs it)
        "exit_in_bf16": (
            patched("_EXIT_F32", in_bf16),
            patched("_EXIT_F32", in_bf16, transformer.exit_probabilities)),
        "pass_dropped": with_cfg(loop_steps=cfg.loop_steps - 1),
        # a pass hands on the stream as its last layer left it
        "no_norm_between_passes": patched(
            "_next_pass_input", lambda real: lambda left, normed: left),
        # the exit distribution weights the passes and learns nothing
        # through them: the gate's gradient is the entropy's alone
        "weights_held_constant": patched(
            "weighted_lm_head_cross_entropy", detached_weights),
        "post_norms_dropped": post_norms_dropped,
        "bf16_everything": bf16_everything,
    }


def errors_of_wrong(family, wrong, params, batch, reference=None):
    system, exit_fn = wrong if isinstance(wrong, tuple) else (wrong, None)
    extra = {} if exit_fn is None else {"exit_fn": exit_fn}
    return family.errors_of(system, params, batch, reference, **extra)


def test_tiny_cell_keeps_the_family_and_its_mechanisms():
    config = tiny_ouro()["config"]
    assert config["family"] == "ouro"
    assert config["loop_steps"] == 4 and config["n_layers"] == 2
    assert config["post_norm"] and config["exit_gate"]
    assert config["exit_entropy_coef"] == 0.05


@pytest.fixture(scope="module")
def in_float32():
    cell = tiny_ouro("float32")
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    batch = check_batch(cell, family)
    return cell, family, params, batch


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype, in_float32):
    if dtype == "float32":
        cell, family, params, batch = in_float32
    else:
        cell = tiny_ouro(dtype)
        family = family_of(cell)
        params = family.init_params(loop.seed_key(2**31 + 3))
        batch = check_batch(cell, family)
    errors = family.check(params, batch)
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 2e-5
        assert errors["gate_grad_rel_err"] < 2e-5
        assert errors["ut_pass_loss_rel_err"] < 1e-6
        assert errors["exit_p_mean_abs_err"] < 1e-6
        assert errors["exit_rel_err"] < 1e-5
    else:
        assert compare.within(errors, family.tolerance), errors
    assert len(errors["ut_pass_loss"]) == len(errors["exit_p_mean"]) == 4
    assert sum(errors["exit_p_mean"]) == pytest.approx(1.0, abs=1e-5)
    assert 0.0 < errors["exit_entropy"] < math.log(4)
    assert errors["exit_entropy"] == pytest.approx(
        errors["exit_entropy_reference"], rel=1e-2)
    assert 0.0 < errors["gate_grad_share"] < 0.5


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_system_is_outside_the_tolerance(fault, in_float32):
    """In float32 the stated path agrees to rounding, so what is read is
    the fault's own; the lower precisions are read against the float32
    reference as the chip reads them."""
    cell, family, params, batch = in_float32
    reference = family.reference_side(params, batch)
    wrong = errors_of_wrong(
        family, wrong_systems(cell, family)[fault], params, batch, reference)
    assert not compare.within(wrong, family.tolerance), wrong
    if fault == "bf16_everything":
        assert wrong["loss_rel_err"] > family.tolerance["loss_rel_err"]
        assert wrong["exit_rel_err"] < 1e-5
    elif fault == "exit_in_bf16":
        # the loss hardly moves and the gradients little: held by the
        # probe alone
        assert wrong["exit_rel_err"] > family.tolerance["exit_rel_err"]
        assert wrong["loss_rel_err"] < family.tolerance["loss_rel_err"]
    elif fault == "weights_held_constant":
        assert wrong["loss_rel_err"] < 1e-6  # the loss is the stated one
        assert wrong["gate_grad_rel_err"] > 2 * family.tolerance[
            "gate_grad_rel_err"], wrong
    else:
        assert wrong["grad_rel_err"] > 1.5 * family.tolerance["grad_rel_err"], wrong


def test_the_reference_imports_nothing_of_the_program():
    import chipbench.reference.ouro as reference

    source = open(reference.__file__).read()
    code = source.split('"""', 2)[2]
    assert "ray_tpu" not in code and "import chipbench" not in code
    assert 'default_matmul_precision("highest")' in source
    # plain Python loops over the passes and the layers, whole logits
    assert 'for _ in range(config["loop_steps"])' in code
    assert 'for index in range(config["n_layers"])' in code
    assert "lax.scan" not in code and "jnp.float32" in code
    # the distribution sums to 1 and is finite at gates of +-30
    a = jnp.array([[30.0, -30.0, 0.0], [-30.0, 30.0, 0.0],
                   [0.0, 0.0, 0.0], [5.0, -5.0, 0.0]])
    p, log_p = reference.exit_distribution(a)
    assert bool(jnp.isfinite(log_p).all())
    assert jnp.allclose(p.sum(0), 1.0, atol=1e-6)


# ------------------------------------------------------------- the cell

def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_ouro()
    reports = run_loop_here(monkeypatch, cell, seconds=0.4)
    summary = reports[-1]
    assert summary["summary"] and summary["steps_failed"] == 0
    assert summary["compiles_in_window"] == 0
    assert summary["steps"] == 2 * len(summary["chunks"]) >= 2
    assert all(math.isfinite(c["loss"]) for c in summary["chunks"])
    reference = summary["reference"]
    assert {"gate_grad_rel_err", "gate_grad_share", "ut_pass_loss",
            "exit_p_mean", "exit_entropy", "ut_pass_loss_rel_err",
            "exit_rel_err"} <= set(reference)
    assert reference["agrees"] is True
    json.dumps(reference)  # the worker's record is one JSON line
    assert summary["flops_per_unit"] == pytest.approx(
        ouro_flops.ouro_flops_per_token(cell["config"], 64))
    assert all(c["units"] == 2 * 64 for c in summary["chunks"])


def test_the_step_reports_the_passes_readings(in_float32):
    cell, family, params, batch = in_float32
    # the step donates its state: a copy, the fixture's stay
    state = family.init_state(jax.tree.map(jnp.copy, params))
    state, out = family.step(state, batch)
    assert set(out) == {"loss", "grad_norm", "ut_pass_loss", "exit_p_mean",
                        "exit_entropy"}
    assert out["ut_pass_loss"].shape == out["exit_p_mean"].shape == (4,)
    assert out["exit_entropy"].shape == ()
    assert float(out["exit_p_mean"].sum()) == pytest.approx(1.0, abs=1e-5)
    # the loss is the passes' expected cross-entropy less beta H: between
    # the least pass's less beta log 4 and the largest's
    passes = [float(x) for x in out["ut_pass_loss"]]
    assert min(passes) - 0.05 * math.log(4) <= float(out["loss"]) <= max(passes)


@pytest.mark.parametrize("traced", [False, True])
def test_last_line_keys(traced):
    cell = spec.load_cell(spec.ROOT, CELL)
    reduced = fake_reduced(1) if traced else None
    line = run.last_line(spec.ROOT, BENCH, cell, fake_summary(cell), reduced)
    assert line["correct"] is True
    if traced:
        assert {"model_mfu.tokens", "peak_hbm_gb.tokens",
                "device_idle_share.tokens", "steady_rate.tokens",
                "pallas_time_share.tokens"} <= set(line["metrics"])
        for name in WAITING:  # they wait
            assert name not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_the_cell_and_its_lists():
    cell = spec.by_name(BENCH["workloads"], CELL, "workload")
    assert cell == {**cell, "config": CONFIG, "traffic": "tokens-16k-16k",
                    "chips": 1}
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) == 12  # appended to the twelve there were
    assert [c["name"] for c in BENCH["configs"]].index(CONFIG) == 11
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 2
    tokens = spec.by_name(BENCH["end_to_end"], "train_tokens_per_s", "metric")
    assert CELL in tokens["workloads"]
    for name in LISTS:
        assert CELL in spec.by_name(BENCH["per_layer"], name, "metric")[
            "workloads"], name
    named = {m["name"] for m in spec.metrics_of(BENCH, CELL, "per_layer")}
    assert named == set(LISTS) | {"gang_boot_s", "state_init_s"}
    for text in (cell["why"], spec.by_name(
            BENCH["configs"], CONFIG, "config")["why"]):
        assert len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_traffic_is_one_sequence_of_16384_a_step():
    traffic = spec.load_cell(spec.ROOT, CELL)["traffic"]
    assert (traffic["kind"], traffic["batch_rows"], traffic["rows_per_block"],
            traffic["units_per_row"]) == ("ingest", 1, 1, 16384)
    assert traffic["columns"]["tokens"]["shape"] == [16385]
    assert traffic["columns"]["tokens"]["high"] == "config:vocab_size"
    assert (traffic["steps_per_chunk"], traffic["warmup_steps"],
            traffic["prefetch_batches"], traffic["blocks_per_epoch"]) == (
                1, 2, 2, 256)
    rows = traffic_lib.make_rows(traffic, held_config(), 2**31 + 7, 0, 1)
    assert rows["tokens"].shape == (1, 16385)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 49152


@pytest.mark.parametrize("name", sorted(WAITING))
def test_the_waiting_metric_files(name):
    """Under the key `awaits`, as PR 55's `kda_time_share.tokens` is."""
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry = held["awaits"]
    assert held["reader"] == WAITING[name]
    assert entry["name"] == name and entry["unit"] == "%"
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["source"] == "device_trace"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert not any(m["name"] == name for m in BENCH["per_layer"])
    assert os.path.exists(os.path.join(
        spec.ROOT, "chipbench", "readers", held["reader"] + ".py"))
    if "roofline" in name:  # the cell's own shapes
        params = held["params"]
        assert (params["n_heads"], params["head_dim"], params["seq_len"]) == (
            16, 128, 16384)
        assert entry["better"] == "higher"
    else:
        assert entry["better"] == "lower"
