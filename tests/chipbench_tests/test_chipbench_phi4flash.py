"""The `phi4flash` family and its cell `phi4flash.tokens16k` (CPU only): the
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
import shutil

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare, loop, phi4flash_flops, run, spec
from chipbench import traffic as traffic_lib
from chipbench_tiny import fake_reduced, fake_summary, run_loop_here

CELL = "phi4flash.tokens16k"
CONFIG = "phi-4-mini-flash-l6-vp8"
BENCH = spec.load_benchmark(spec.ROOT)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KINDS = ["mamba1", "sliding_diff_attention", "mamba1_emit",
         "diff_attention_emit", "gmu", "cross_diff_attention"]
# the lists of BENCHMARK.json the cell joins: those `ouro.tokens16k` is in,
# and the windowed kernels' share
LISTS = [
    "ingest_wait_share.tokens", "steady_rate.tokens", "stall_share.tokens",
    "model_mfu.tokens", "pallas_time_share.tokens", "device_idle_share.tokens",
    "peak_hbm_gb.tokens", "trace_s", "lower_s", "pallas_trace_s",
    "before_first_program_s", "before_init_s", "flash_time_share.tokens",
    "compile_s", "cluster_init_s", "first_batch_s", "setup_unnamed_s",
    "ingest_produce_share.tokens", "flash_window_time_share.tokens"]
FAULTS = ["scan_in_bf16", "lambda_left_out", "window_off",
          "memory_after_the_gate", "cross_reads_its_own_kv",
          "layer_norm_bias_dropped", "bf16_everything"]
WAITING = {
    "selective_scan_time_share.tokens": "selective_scan",
    "mamba1_time_share.tokens": "mamba1",
    "gmu_time_share.tokens": "gmu",
    "diff_attention_time_share.tokens": "diff_attention",
}
# (the kernel, the band) of the six rooflines that wait beside them
ROOFLINES = {
    "%s_roofline.%sdiff.tokens" % (kernel, "window." if window else ""):
        (kernel, window)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
    for window in (None, 512)}


def held_config():
    return spec.read_json(spec.ROOT, "chipbench", "configs", CONFIG + ".json")


def tiny_phi4flash(dtype="bfloat16", **over):
    """64 wide, 8 query heads of 16 over 4 key heads, SwiGLU of 96, a mixer
    of 128 channels and 16 states, a window of 8, the six kinds of layer;
    sequences of 64, compared at 48 (scan chunks of 16)."""
    cell = copy.deepcopy(spec.load_cell(spec.ROOT, CELL))
    config, traffic = cell["config"], cell["traffic"]
    config.update(vocab_size=256, d_model=64, n_heads=8, n_kv_heads=4,
                  d_head=16, d_ff=96, mamba1_inner=128, mamba1_dt_rank=4,
                  sliding_window=8, scan_chunk=16, max_seq_len=64,
                  dtype=dtype, check={"rows": 2, "seq_len": 48,
                                      "bias_std": 0.1}, **over)
    traffic["columns"]["tokens"]["shape"] = [65]
    traffic.update(units_per_row=64, blocks_per_epoch=5, steps_per_chunk=2,
                   warmup_steps=1, trace_chunks=2)
    return cell


def family_of(cell, root=spec.ROOT):
    return spec.load_code(root, "loops", cell["config"]["family"]).build(
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
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert held["source"].startswith(row["source_url"])
    assert spec.by_name(BENCH["configs"], CONFIG, "config")["source"] == (
        row["source_url"])
    assert held["catalog_config"] == row["config"]
    reduced = set(held["reduced"])
    assert reduced == {"num_hidden_layers", "vocab_size"} == set(
        spec.by_name(BENCH["configs"], CONFIG, "config")["reduced"])
    for key, value in row["config"].items():
        if key in reduced:
            assert held[key] != value
        else:
            assert held[key] == value, key
    assert held["published"]["num_hidden_layers"] == 32
    assert held["num_hidden_layers"] == 6 and held["vocab_size"] == 25008
    assert 8 * 25008 == 200064 == held["published"]["vocab_size"]


def test_every_width_the_program_runs_is_the_published_one():
    held = held_config()
    row = held["catalog_config"]
    assert held["d_model"] == row["hidden_size"] == 2560
    assert (held["n_heads"], held["n_kv_heads"]) == (
        row["num_attention_heads"], row["num_key_value_heads"]) == (40, 20)
    assert held["d_head"] == 2560 // 40 == 64
    assert held["d_ff"] == row["intermediate_size"] == 10240
    assert held["sliding_window"] == row["sliding_window"] == 512
    assert held["norm_eps"] == row["layer_norm_eps"] == 1e-5
    assert held["mamba1_inner"] == 2 * 2560 and held["mamba1_state"] == 16
    assert held["mamba1_dt_rank"] == math.ceil(2560 / 16) == 160
    assert held["mamba1_conv_taps"] == 4
    assert held["tied_embeddings"] is row["tie_word_embeddings"] is True
    assert held["rope"] is False and held["layer_norm"] is True
    assert held["attn_bias"] is True and held["remat"] is True
    assert held["n_layers"] == held["num_hidden_layers"] == 6
    assert held["layer_types"] == KINDS
    assert held["layer_depths"] == held["published"]["layers_held"] == [
        0, 1, 16, 17, 18, 19]
    assert held["max_seq_len"] == 16384 <= row["max_position_embeddings"]
    published = held["published"]
    assert published["stages"] * published["layers_a_stage"] == 32
    assert published["token_ids_held"] == [0, 25008]
    assert published["chips_sharing_a_layer"] == 8
    for word in ("assumed", "deployment", "check", "mesh", "optimizer",
                 "skewed_by_the_cut"):
        assert word in held
    for key in ("mamba_sizes", "attention_biases", "head_pairing", "lambda",
                "memory", "gated_memory_unit", "cross_attention",
                "feed_forward_gate", "positions", "layer_norm", "seq_len",
                "optimizer", "initialisers", "dtype", "remat"):
        assert key in held["assumed"], key
    assert (held["check"]["rows"], held["check"]["seq_len"]) == (1, 4096)
    assert held["mesh"] == {"data": 1}
    optimizer = held["optimizer"]
    assert (optimizer["b1"], optimizer["b2"], optimizer["weight_decay"]) == (
        0.9, 0.95, 0.1)


def test_the_state_is_697094272_parameters_11_15_gb():
    """The count by hand, the count of the program's own leaves, and the
    words of `deployment` agree."""
    from ray_tpu.models.transformer import transformer_init

    held = held_config()
    by_hand = phi4flash_flops.state_params(held)
    assert by_hand == 697_094_272 == held["published"]["parameters_held"][
        "total"]
    assert by_hand == (2 * 119_895_040 + 2 * 98_322_304 + 104_867_840
                       + 91_766_144 + 64_020_480 + 5_120)
    assert round(16 * by_hand / 1e9, 2) == 11.15
    cfg = spec.load_code(spec.ROOT, "loops", "phi4flash").model_config(held)
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == by_hand
    assert sum(x.size for x in jax.tree.leaves(shapes["blocks"])) == (
        633_068_672)
    for number in ("697,094,272", "11.15 GB", "66 %", "eight stages",
                   "25,008 token ids", "0, 1, 16, 17, 18 and 19"):
        assert number in held["deployment"], number


def test_no_weight_decay_on_the_vectors():
    from chipbench.loops.nemotron_h import decayed
    from ray_tpu.models.transformer import transformer_init

    cell = tiny_phi4flash()
    cfg = family_of(cell).model_config
    params = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    mask = decayed(params, cell["config"]["optimizer"]["no_decay"])
    decayed_leaves = {path[-1].key for path, keep in
                      jax.tree_util.tree_leaves_with_path(mask) if keep}
    assert decayed_leaves == {
        "embed", "w_in", "w_x", "w_dt", "w_out", "wq", "wk", "wv", "wo",
        "gmu_in", "gmu_out", "w_gate", "w_up", "w_down"}


def test_operations_by_hand_are_the_programs():
    """`phi4flash_flops.py` counts from the shapes; the program counts from
    its records: the same number."""
    from ray_tpu.models.transformer import _fwd_flops_per_token, flops_per_token

    held = held_config()
    cfg = spec.load_code(spec.ROOT, "loops", "phi4flash").model_config(held)
    parts = phi4flash_flops.forward_parts(held, 16384)
    matmul, attention, head = _fwd_flops_per_token(cfg, 16384)
    assert matmul == parts["feed_forwards"] + parts["mixers"] == (
        2 * 632_750_080)
    assert attention == parts["whole_pairs"] + parts["window_pairs"]
    assert parts["whole_pairs"] == 2 * 2 * 40 * (64 + 128) * 16385 / 2
    assert parts["window_pairs"] == 2 * 40 * (64 + 128) * (
        512 * 513 / 2 + (16384 - 512) * 512) / 16384
    assert head == parts["head"] == 2 * 2560 * 25008
    assert phi4flash_flops.phi4flash_flops_per_token(held, 16384) == (
        pytest.approx(flops_per_token(cfg, 16384), rel=1e-12))
    total = sum(parts.values())
    assert 1_652e6 < total < 1_654e6
    assert round(100 * matmul / total) == 77
    assert round(100 * attention / total) == 16
    assert round(100 * head / total) == 8


# ---------------------------------------------------------- the comparison

def wrong_systems(cell, family):
    """{name: a system to hand `family.errors_of`, or (that, the scan for
    the probe)}: each computes something other than the published model,
    or the stated one in a lower precision."""
    from ray_tpu.models import transformer
    from ray_tpu.ops import selective_scan as scan_module

    cfg = family.model_config
    system = family.system_loss_and_readings

    def patched(module, name, replacement, inner=system):
        def run_patched(*args, **kw):
            real = getattr(module, name)
            setattr(module, name, replacement(real))
            try:
                return inner(*args, **kw)
            finally:
                setattr(module, name, real)
        return run_patched

    def bf16_everything(p, b):
        p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
        loss, readings = system(p, b)
        return loss.astype(jnp.bfloat16).astype(jnp.float32), readings

    def in_bf16(real):
        return jnp.bfloat16

    return {
        # the scan's decay, state and sums in bfloat16 (the step's, and the
        # probe's as the probe runs it)
        "scan_in_bf16": (
            patched(scan_module, "_F32", in_bf16),
            patched(scan_module, "_F32", in_bf16,
                    scan_module.selective_scan)),
        # lam is its constant part alone
        "lambda_left_out": patched(
            transformer, "_diff_lambda",
            lambda real: lambda blk, depth: (real(blk, depth)[1],) * 2),
        "window_off": lambda p, b: transformer.transformer_loss_and_readings(
            p, b, dataclasses.replace(cfg, sliding_window=0),
            mesh=family.mesh),
        # the gated memory units read the scan's output times silu(z)
        "memory_after_the_gate": patched(
            transformer, "_scan_memory", lambda real: lambda s, gated: gated),
        "layer_norm_bias_dropped": patched(
            transformer, "_layer_norm",
            lambda real: lambda x, scale, bias, eps: real(
                x, scale, jnp.zeros_like(bias), eps)),
        "bf16_everything": bf16_everything,
    }


def reference_with_own_kv(family, cell, params, batch):
    """The reference's loss, lambdas and gradients where a cross layer
    attends over keys and values made from its OWN normed input by the
    emitting layer's weights."""
    reference = spec.load_code(spec.ROOT, "reference", "phi4flash")
    config = cell["config"]

    def own(emitted, y, config):
        return reference.keys_and_values(y, emitted["w"], config)

    real = reference.cross_keys_and_values
    reference.cross_keys_and_values = own
    try:
        (loss, lams), grads = jax.jit(jax.value_and_grad(
            lambda p: reference.forward(p, batch, config), has_aux=True))(
                params)
    finally:
        reference.cross_keys_and_values = real
    return loss, lams, grads


def test_tiny_cell_keeps_the_family_and_its_mechanisms():
    config = tiny_phi4flash()["config"]
    assert config["family"] == "phi4flash"
    assert config["layer_types"] == KINDS
    assert config["layer_depths"] == [0, 1, 16, 17, 18, 19]
    assert config["layer_norm"] and config["attn_bias"]
    assert not config["rope"] and config["tied_embeddings"]


@pytest.fixture(scope="module")
def in_float32():
    cell = tiny_phi4flash("float32")
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    batch = check_batch(cell, family)
    return cell, family, params, batch


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype, in_float32):
    if dtype == "float32":
        cell, family, params, batch = in_float32
    else:
        cell = tiny_phi4flash(dtype)
        family = family_of(cell)
        params = family.init_params(loop.seed_key(2**31 + 3))
        batch = check_batch(cell, family)
    errors = family.check(params, batch)
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 2e-5
        assert errors["lambda_rel_err"] < 1e-6
        assert errors["scan_rel_err"] < 1e-5
    else:
        # 64 wide: the rounding of one element weighs more than on the chip
        assert errors["loss_rel_err"] < family.tolerance["loss_rel_err"]
        assert errors["grad_rel_err"] < 2 * family.tolerance["grad_rel_err"]
        assert errors["lambda_rel_err"] < family.tolerance["lambda_rel_err"]
        assert errors["scan_rel_err"] < family.tolerance["scan_rel_err"]
    assert len(errors["diff_lambda"]) == 3
    # the biases the initialiser leaves at zero are drawn
    blocks = jax.tree.leaves(params["blocks"])
    assert all(float(jnp.abs(x).max()) > 0 for x in blocks)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_system_is_outside_the_tolerance(fault, in_float32):
    """In float32 the stated path agrees to rounding, so what is read is
    the fault's own; the lower precisions are read against the float32
    reference as the chip reads them."""
    cell, family, params, batch = in_float32
    if fault == "cross_reads_its_own_kv":
        wrong = family.errors_of(
            family.system_loss_and_readings, params, batch,
            reference_with_own_kv(family, cell, params, batch))
    else:
        system = wrong_systems(cell, family)[fault]
        system, scan_fn = system if isinstance(system, tuple) else (
            system, None)
        extra = {} if scan_fn is None else {"scan_fn": scan_fn}
        wrong = family.errors_of(
            system, params, batch, family.reference_side(params, batch),
            **extra)
    assert not compare.within(wrong, family.tolerance), wrong
    if fault == "bf16_everything":
        assert wrong["loss_rel_err"] > family.tolerance["loss_rel_err"]
        assert wrong["scan_rel_err"] < 1e-5
    elif fault == "scan_in_bf16":
        assert wrong["scan_rel_err"] > family.tolerance["scan_rel_err"]
    elif fault == "lambda_left_out":
        assert wrong["lambda_rel_err"] > 10 * family.tolerance[
            "lambda_rel_err"]
    else:
        assert wrong["grad_rel_err"] > 1.5 * family.tolerance[
            "grad_rel_err"], wrong


def test_the_reference_imports_nothing_of_the_program():
    import chipbench.reference.phi4flash as reference

    source = open(reference.__file__).read()
    code = source.split('"""', 2)[2]
    assert "ray_tpu" not in code and "import chipbench" not in code
    assert 'default_matmul_precision("highest")' in source
    assert 'for kind, depth, w in zip(config["layer_types"]' in code
    assert "jax.lax.scan(step, h, tokens)" in code  # token by token
    assert "jnp.float32" in code and "bfloat16" not in code


# ------------------------------------------------------------- the cell

def test_the_cell_s_files_are_found_by_name_under_another_root(tmp_path):
    """The harness finds the cell by `BENCHMARK.json`'s names alone: a copy
    of the benchmark's files under another root builds the same family."""
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(spec.ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = spec.load_cell(root, CELL)
    assert cell["config"]["family"] == "phi4flash"
    assert cell["workload"]["traffic"] == "tokens-16k-16k-vp8"
    loops = spec.load_code(root, "loops", "phi4flash")
    assert loops.__file__.startswith(root)
    cfg = loops.model_config(cell["config"])
    assert cfg.layer_types == tuple(KINDS) and cfg.mamba1_inner == 5120
    names = [w["name"] for w in spec.load_benchmark(root)["workloads"]]
    assert CELL in names


def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_phi4flash()
    reports = run_loop_here(monkeypatch, cell, seconds=0.4)
    summary = reports[-1]
    assert summary["summary"] and summary["steps_failed"] == 0
    assert summary["compiles_in_window"] == 0
    assert summary["steps"] == 2 * len(summary["chunks"]) >= 2
    assert all(math.isfinite(c["loss"]) for c in summary["chunks"])
    reference = summary["reference"]
    assert {"lambda_rel_err", "scan_rel_err", "diff_lambda",
            "grad_rel_err"} <= set(reference)
    json.dumps(reference)  # the worker's record is one JSON line
    assert summary["flops_per_unit"] == pytest.approx(
        phi4flash_flops.phi4flash_flops_per_token(cell["config"], 64))
    assert all(c["units"] == 2 * 64 for c in summary["chunks"])


def test_the_step_reports_the_layers_lambda(in_float32):
    cell, family, params, batch = in_float32
    # the step donates its state: a copy, the fixture's stay
    state = family.init_state(jax.tree.map(jnp.copy, params))
    state, out = family.step(state, batch)
    assert set(out) == {"loss", "grad_norm", "diff_lambda"}
    assert out["diff_lambda"].shape == (3,)
    lam0 = [0.8 - 0.6 * math.exp(-0.3 * depth) for depth in (1, 17, 19)]
    for lam, start in zip(out["diff_lambda"], lam0):
        assert abs(float(lam) - start) < 0.6  # two exps of small dots apart


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
        for name in (*WAITING, *ROOFLINES):  # they wait
            assert name not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_the_cell_and_its_lists():
    cell = spec.by_name(BENCH["workloads"], CELL, "workload")
    assert cell == {**cell, "config": CONFIG, "traffic": "tokens-16k-16k-vp8",
                    "chips": 1}
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) == 13  # appended to the thirteen there were
    assert [c["name"] for c in BENCH["configs"]].index(CONFIG) == 12
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
            traffic["trace_chunks"], traffic["blocks_per_epoch"]) == (
                1, 2, 2, 256)
    rows = traffic_lib.make_rows(traffic, held_config(), 2**31 + 7, 0, 1)
    assert rows["tokens"].shape == (1, 16385)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 25008


@pytest.mark.parametrize("name", sorted(WAITING))
def test_the_waiting_metric_files(name):
    """Under the key `awaits`, as PR 55's `kda_time_share.tokens` is."""
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry = held["awaits"]
    assert held["reader"] == "scope_share"
    assert held["params"] == {"scope": WAITING[name]}
    assert entry["name"] == name and entry["unit"] == "%"
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["source"] == "device_trace" and entry["better"] == "lower"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert not any(m["name"] == name for m in BENCH["per_layer"])


@pytest.mark.parametrize("name", sorted(ROOFLINES))
def test_the_waiting_rooflines(name):
    """The flash kernels at grouped heads and two widths, whole and under
    the band: the file's shapes are the configuration's, the count is by
    hand, and the reader finds the kernel's events by name."""
    kernel, window = ROOFLINES[name]
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry, params = held["awaits"], held["params"]
    assert held["reader"] == "diff_flash_roofline"
    assert entry == {**entry, "name": name, "unit": "%", "better": "higher",
                     "source": "device_trace", "workloads": [CELL],
                     "moves": "train_tokens_per_s"}
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert not any(m["name"] == name for m in BENCH["per_layer"])
    config = held_config()
    assert params == {
        "event": kernel + ("_window" if window else ""), "kernel": kernel,
        "window": window, "n_heads": config["n_heads"],
        "n_kv_heads": config["n_kv_heads"], "qk_dim": config["d_head"],
        "v_dim": 2 * config["d_head"], "seq_len": 16384}
    assert window in (None, config["sliding_window"])
    pairs = 16384 * 16385 // 2 if window is None else (
        512 * 513 // 2 + (16384 - 512) * 512)
    assert phi4flash_flops.flash_pairs(16384, window) == pairs
    ops, moved = phi4flash_flops.flash_call(
        kernel, 1, 40, 20, 16384, window, 64, 128)
    widths = {"flash_fwd": 64 + 128, "flash_bwd_dq": 2 * 64 + 128,
              "flash_bwd_dkv": 2 * 64 + 2 * 128}[kernel]
    assert ops == 2.0 * pairs * 40 * widths
    q, o, k, v = (16384 * n for n in (40 * 64, 40 * 128, 20 * 64, 20 * 128))
    lse = 16384 * 40 * 8 * 4
    assert moved == {
        "flash_fwd": 2 * (q + k + v + o) + lse,
        "flash_bwd_dq": 2 * (q + k + v + o) + 2 * lse + 4 * q,
        "flash_bwd_dkv": 2 * (q + k + v + o) + 2 * lse + 4 * (k + v),
    }[kernel]
    # a call that took twice its least time reads 50 %; another kernel's
    # events and a run without a trace read nothing
    from chipbench import flops, kernel_flops
    least, _ = kernel_flops.least_seconds(
        ops, moved, flops.peaks_for("TPU v5 lite"))
    event = params["event"] + ".7 [tpu_custom_call]"
    run_ = {"chips": 1, "device": {"kind": "TPU v5 lite"},
            "chunks": [{"units": 16384, "steps": 1}],
            "trace": {"segments": {"0": [
                [0, int(2e9 * least), event],
                [int(3e9 * least), int(5e9 * least), event],
                [0, 10, "moe_gmm.1 [tpu_custom_call]"]]}}}
    assert spec.read_metric(spec.ROOT, name, run_) == pytest.approx(
        50.0, rel=1e-6)
    run_["trace"]["segments"]["0"] = [[0, 10, "flash_fwd_sparse.1 [tpu_custom_call]"]]
    assert spec.read_metric(spec.ROOT, name, run_) is None
    assert spec.read_metric(spec.ROOT, name, {**run_, "trace": None}) is None
