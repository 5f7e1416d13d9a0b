"""The `evabyte` family and its cell `evabyte.tokens8k` (CPU only): the
configuration file's counts and widths against the catalog's row, the
operation counts by hand against the program's, the system against the plain
reference at a tiny size, each wrong mathematics and each lower precision
outside the tolerance, the cell's loop end to end, the last line's keys, and
the files and entries the cell was added by."""

import contextlib
import copy
import json
import math
import os
import re
import shutil

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare, evabyte_flops, loop, run, spec
from chipbench import traffic as traffic_lib
from chipbench_tiny import fake_reduced, fake_summary, run_loop_here

CELL = "evabyte.tokens8k"
CONFIG = "evabyte-6.5b-l4-pp8"
TRAFFIC = "tokens-8k-8k-mbp8"
BENCH = spec.load_benchmark(spec.ROOT)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the lists of BENCHMARK.json the cell joins: those `phi4flash.tokens16k` is
# in but the windowed kernels' share, and the new kernels' own
LISTS = [
    "ingest_wait_share.tokens", "steady_rate.tokens", "stall_share.tokens",
    "model_mfu.tokens", "pallas_time_share.tokens", "device_idle_share.tokens",
    "peak_hbm_gb.tokens", "trace_s", "lower_s", "pallas_trace_s",
    "before_first_program_s", "before_init_s", "flash_time_share.tokens",
    "compile_s", "cluster_init_s", "first_batch_s", "setup_unnamed_s",
    "ingest_produce_share.tokens", "eva_time_share.tokens"]
FAULTS = ["logits_and_loss_in_bf16", "lse_in_bf16", "summaries_left_out",
          "own_window_s_chunks_let_in", "mu_dropped", "mean_pooling",
          "two_softmaxes_added", "every_head_on_the_next_byte"]
# {the waiting roofline: (the pallas_call's name, the part, the kernel)}
ROOFLINES = {
    "eva_summaries_fwd_roofline.tokens": (
        "eva_summaries_fwd", "summaries", "eva_summaries_fwd"),
    "eva_summaries_bwd_roofline.tokens": (
        "eva_summaries_bwd", "summaries", "eva_summaries_bwd"),
    "flash_fwd_roofline.stair.tokens": ("flash_fwd_stair", "stair", "flash_fwd"),
    "flash_bwd_dkv_dq_roofline.stair.tokens": (
        "flash_bwd_dkv_dq_stair", "stair", "flash_bwd_dkv_dq"),
    "flash_fwd_roofline.eva.tokens": ("flash_fwd", "window", "flash_fwd"),
    "flash_bwd_dkv_dq_roofline.eva.tokens": (
        "flash_bwd_dkv_dq", "window", "flash_bwd_dkv_dq"),
}


def held_config():
    return spec.read_json(spec.ROOT, "chipbench", "configs", CONFIG + ".json")


def tiny_evabyte(dtype="bfloat16", **over):
    """64 wide, 4 heads of 16, SwiGLU of 96, windows of 16 and chunks of 4,
    3 prediction heads over 40 ids, 2 layers; sequences of 64 (4 windows),
    compared whole."""
    cell = copy.deepcopy(spec.load_cell(spec.ROOT, CELL))
    config, traffic = cell["config"], cell["traffic"]
    config.update(vocab_size=40, d_model=64, n_heads=4, n_kv_heads=4,
                  d_ff=96, eva_window=16, eva_chunk=4, n_pred_heads=3,
                  n_layers=2, layer_types=["eva_attention"] * 2,
                  max_seq_len=64, dtype=dtype,
                  check={"rows": 2, "seq_len": 64}, **over)
    traffic["columns"]["tokens"]["shape"] = [67]
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
    same key with the same value, but for the depth. No width is reduced."""
    held = held_config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    entry = spec.by_name(BENCH["configs"], CONFIG, "config")
    assert held["source"].startswith(row["source_url"])
    assert entry["source"] == row["source_url"]
    assert held["catalog_config"] == row["config"]
    assert held["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (held[key], value) == (4, 32)
        else:
            assert held[key] == value, key
    assert held["published"]["num_hidden_layers"] == 32
    assert held["published"]["layers_held"] == [0, 1, 2, 3]


def test_every_width_the_program_runs_is_the_published_one():
    held = held_config()
    row = held["catalog_config"]
    cfg = spec.load_code(spec.ROOT, "loops", "evabyte").model_config(held)
    assert cfg.d_model == row["hidden_size"] == 4096
    assert cfg.n_heads == cfg.kv_heads == row["num_attention_heads"] == (
        row["num_key_value_heads"]) == 32
    assert cfg.head_dim == 128 and cfg.ff_dim == row["intermediate_size"]
    assert (cfg.eva_window, cfg.eva_chunk) == (
        row["window_size"], row["chunk_size"]) == (2048, 16)
    assert cfg.n_pred_heads == row["num_pred_heads"] == 8
    assert cfg.vocab_size == row["vocab_size"] == 320
    assert cfg.head_width == 2560 and not cfg.tied_embeddings
    assert cfg.rope_theta == row["rope_theta"] == 100000
    assert cfg.norm_eps == row["rms_norm_eps"] == 1e-5
    assert cfg.norm_unit_offset is row["norm_add_unit_offset"] is True
    assert cfg.init_std == row["init_std"] == 0.01275
    assert cfg.n_layers == held["num_hidden_layers"] == 4
    assert cfg.layer_types == ("eva_attention",) * 4 and cfg.remat
    assert cfg.max_seq_len == 8192 <= row["max_seq_length"]
    assert not cfg.scan_layers  # walked: a layer's gradient at a time
    optimizer = held["optimizer"]
    assert (optimizer["b1"], optimizer["b2"], optimizer["weight_decay"]) == (
        0.9, 0.95, 0.1)


def test_the_state_is_821366784_parameters_13_14_gb():
    """The count by hand, the count of the program's own leaves, and the
    words of `deployment` agree."""
    from ray_tpu.models.transformer import transformer_init

    held = held_config()
    by_hand = evabyte_flops.state_params(held)
    assert by_hand == 821_366_784 == held["published"]["parameters_held"][
        "total"]
    assert by_hand == 4 * 202_391_552 + 1_310_720 + 10_485_760 + 4_096
    assert 202_391_552 == 67_108_864 + 135_266_304 + 8_192 + 8_192 == (
        held["published"]["parameters_held"]["layer"])
    assert round(16 * by_hand / 1e9, 2) == 13.14
    assert 16 * by_hand == held["published"]["state_bytes_held"]
    assert 0.75 < 16 * by_hand / 16.91e9 < 0.78
    cfg = spec.load_code(spec.ROOT, "loops", "evabyte").model_config(held)
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == by_hand
    assert sum(x.size for x in jax.tree.leaves(shapes["blocks"])) == (
        809_566_208)
    assert 32 * 202_391_552 + 11_800_576 == 6_488_330_240
    for number in ("821,366,784", "13.14 GB", "77.7 %", "eight stages",
                   "layers 0 to 3", "103.8 GB"):
        assert number in held["deployment"], number


def test_no_weight_decay_on_the_vectors_and_the_norms():
    from chipbench.loops.nemotron_h import decayed
    from ray_tpu.models.transformer import transformer_init

    cell = tiny_evabyte()
    cfg = family_of(cell).model_config
    params = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    mask = decayed(params, cell["config"]["optimizer"]["no_decay"])
    flat = jax.tree_util.tree_leaves_with_path(mask)
    assert {path[-1].key for path, keep in flat if keep} == {
        "embed", "unembed", "wq", "wk", "wv", "wo", "w_gate", "w_up",
        "w_down"}
    assert {path[-1].key for path, keep in flat if not keep} == {
        "attn_norm", "mlp_norm", "final_norm", "eva_phi", "eva_mu"}


def test_operations_by_hand_are_the_programs():
    """`evabyte_flops.py` counts from the shapes; the program counts from
    its records: the same number."""
    from ray_tpu.models.transformer import _fwd_flops_per_token, flops_per_token

    held = held_config()
    cfg = spec.load_code(spec.ROOT, "loops", "evabyte").model_config(held)
    parts = evabyte_flops.forward_parts(held, 8192)
    matmul, attention, head = _fwd_flops_per_token(cfg, 8192)
    assert matmul == parts["attention_matmuls"] + parts["feed_forwards"] == (
        4 * 2 * 202_375_168)
    assert evabyte_flops.keys_per_query(held, 8192) == 1024.5 + 192
    assert attention == parts["eva_pairs"] == 4 * 16384 * 1216.5
    assert head == parts["head"] == 2 * 4096 * 2560
    assert evabyte_flops.evabyte_flops_per_token(held, 8192) == (
        pytest.approx(flops_per_token(cfg, 8192), rel=1e-12))
    total = sum(parts.values())
    assert 1_719e6 < total < 1_721e6
    assert round(100 * matmul / total) == 94
    assert round(1000 * attention / total) == 46
    assert round(1000 * head / total) == 12
    # a sequence of one window is plain causal attention
    assert evabyte_flops.keys_per_query(held, 2048) == 1024.5


def test_the_kernels_calls_by_hand():
    held = held_config()
    assert evabyte_flops.window_pairs(2048) == 2_098_176
    assert evabyte_flops.stair_pairs(8192, 2048, 16) == 2048 * 128 * 6 == (
        1_572_864)
    # both kinds of pair over the queries: the keys a query sees
    assert (4 * 2_098_176 + 1_572_864) / 8192 == 1216.5
    ops, moved = evabyte_flops.window_call("flash_fwd", held, 8192)
    assert ops == 2 * 2.0 * 2_098_176 * 128 * 128  # 4 windows x 32 heads
    tensor = 8192 * 4096
    assert moved == 4 * tensor * 2 + 8192 * 32 * 8 * 4
    ops, moved = evabyte_flops.window_call("flash_bwd_dkv_dq", held, 8192)
    assert ops == 5 * 2.0 * 2_098_176 * 128 * 128
    assert moved == 7 * tensor * 2 + 2 * 8192 * 32 * 8 * 4
    ops, moved = evabyte_flops.stair_call("flash_fwd", held, 8192)
    assert ops == 2 * 2.0 * 1_572_864 * 128 * 32
    assert moved == (2 * tensor + 2 * tensor // 16) * 2 + 8192 * 32 * 8 * 4
    ops, moved = evabyte_flops.stair_call("flash_bwd_dkv_dq", held, 8192)
    assert ops == 5 * 2.0 * 1_572_864 * 128 * 32
    assert moved == (3 * tensor + 4 * tensor // 16) * 2 + (
        2 * 8192 * 32 * 8 * 4)
    ops, moved = evabyte_flops.summaries_call("eva_summaries_fwd", held, 8192)
    assert (ops, moved) == (6.0 * tensor, 2 * tensor * 2 + 2 * tensor // 16 * 2)
    ops, moved = evabyte_flops.summaries_call("eva_summaries_bwd", held, 8192)
    assert (ops, moved) == (16.0 * tensor,
                            4 * tensor * 2 + 2 * tensor // 16 * 2)


# ---------------------------------------------------------- the comparison

@contextlib.contextmanager
def faulty(fault):
    """The program with one thing computed otherwise, for as long as the
    block lasts: what `family.errors_of` reads of it is the fault's, in the
    step's comparison and in the probe alike."""
    from ray_tpu.models import transformer
    from ray_tpu.ops import eva

    f32, bf16 = jnp.float32, jnp.bfloat16
    join, summaries = eva._join, eva.chunk_summaries

    def let_in(q, ks, vs, window, chunk, scale, *_):
        T = q.shape[1]
        whole = jnp.arange(T // chunk)[None, :] * chunk + chunk - 1 <= (
            jnp.arange(T)[:, None])
        with jax.named_scope("eva_stair"):
            return eva._partial_xla(q, ks, vs, whole, scale)

    patches = {
        "logits_and_loss_in_bf16": (transformer, "_LOGITS_F32", bf16),
        # `reduce_precision`: the chip's compiler takes a cast to bfloat16
        # and back out of the program
        "lse_in_bf16": (eva, "_join", lambda o_l, lse_l, o_r, lse_r: join(
            o_l, jax.lax.reduce_precision(lse_l, 8, 7), o_r,
            jax.lax.reduce_precision(lse_r, 8, 7))),
        "summaries_left_out": (
            eva, "_join", lambda o_l, lse_l, o_r, lse_r: (
                o_l.astype(f32), jnp.zeros_like(lse_l))),
        "own_window_s_chunks_let_in": (eva, "_stair_part", let_in),
        "mu_dropped": (eva, "chunk_summaries", lambda k, v, phi, mu, **kw: (
            summaries(k, v, phi, 0.0 * mu, **kw))),
        "mean_pooling": (eva, "chunk_summaries", lambda k, v, phi, mu, **kw: (
            summaries(k, v, 0.0 * phi, mu, **kw))),
        "two_softmaxes_added": (
            eva, "_join", lambda o_l, lse_l, o_r, lse_r: (
                o_l.astype(f32) + o_r.astype(f32),
                join(o_l, lse_l, o_r, lse_r)[1])),
        "every_head_on_the_next_byte": None,
    }
    patch = patches[fault]
    if patch is None:
        yield
        return
    module, name, replacement = patch
    real = getattr(module, name)
    setattr(module, name, replacement)
    try:
        yield
    finally:
        setattr(module, name, real)


def wrong_errors(fault, family, params, batch, reference_outputs=None):
    """`family.errors_of` of the system with `fault`."""
    system = family.system_loss_and_readings
    if fault == "every_head_on_the_next_byte":
        def system(p, b, right=system):  # noqa: F811
            return right(p, {**b, "targets": jnp.broadcast_to(
                b["targets"][..., :1], b["targets"].shape)})
    if fault == "logits_and_loss_in_bf16":
        def system(p, b, right=system):  # noqa: F811
            loss, readings = right(p, b)
            return loss.astype(jnp.bfloat16).astype(jnp.float32), readings
    with faulty(fault):
        return family.errors_of(system, params, batch, reference_outputs)


def test_tiny_cell_keeps_the_family_and_its_mechanisms():
    config = tiny_evabyte()["config"]
    assert config["family"] == "evabyte"
    assert config["layer_types"] == ["eva_attention"] * 2
    assert config["norm_unit_offset"] and not config["tied_embeddings"]
    assert not config["scan_layers"] and config["init_std"] == 0.01275


@pytest.fixture(scope="module")
def in_float32():
    cell = tiny_evabyte("float32")
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    batch = check_batch(cell, family)
    return cell, family, params, batch, family.reference_side(params, batch)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype, in_float32):
    if dtype == "float32":
        cell, family, params, batch, _ = in_float32
    else:
        cell = tiny_evabyte(dtype)
        family = family_of(cell)
        params = family.init_params(loop.seed_key(2**31 + 3))
        batch = check_batch(cell, family)
    assert batch["tokens"].shape == (2, 64)
    assert batch["targets"].shape == (2, 64, 3)
    from ray_tpu.models.transformer import next_ids
    raw = traffic_lib.make_rows(cell["traffic"], cell["config"], 11,
                                loop.CHECK_INDEX, 2)
    for ours, theirs in zip(next_ids(raw["tokens"], 3), (
            batch["tokens"], batch["targets"])):
        assert (ours == theirs).all()  # the loop's host copy of `next_ids`
    errors = family.check(params, batch)
    assert errors["eva_rel_err"] < 1e-5  # the probe is float32 either way
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 2e-5
    else:
        # 64 wide: the rounding of one element weighs more than on the chip
        assert errors["loss_rel_err"] < family.tolerance["loss_rel_err"]
        assert errors["grad_rel_err"] < 2 * family.tolerance["grad_rel_err"]
    for name in ("eva_remote_mass", "eva_chunk_entropy"):
        assert len(errors[name]) == len(errors[name + "_reference"]) == 2
        for ours, theirs in zip(errors[name], errors[name + "_reference"]):
            assert ours == pytest.approx(theirs, rel=2e-2)
    # seeded weights: the summaries' share of the softmax is near their
    # share of the keys a query sees, 6 of 14.5, and pooling is near the mean
    assert 0.3 < errors["eva_remote_mass"][0] < 0.5
    assert 0.95 * math.log(4) < errors["eva_chunk_entropy"][0] <= math.log(4)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_system_is_outside_the_tolerance(fault, in_float32):
    """In float32 the stated path agrees to rounding, so what is read is
    the fault's own; the lower precisions are read against the float32
    reference as the chip reads them."""
    cell, family, params, batch, reference_outputs = in_float32
    wrong = wrong_errors(fault, family, params, batch, reference_outputs)
    assert not compare.within(wrong, family.tolerance), wrong
    if fault == "logits_and_loss_in_bf16":
        assert wrong["loss_rel_err"] > family.tolerance["loss_rel_err"]
        assert wrong["eva_rel_err"] < 1e-5
    elif fault == "every_head_on_the_next_byte":
        assert wrong["grad_rel_err"] > 2 * family.tolerance["grad_rel_err"]
        assert wrong["eva_rel_err"] < 1e-5
    else:  # the attention's own: the probe reads it
        assert wrong["eva_rel_err"] > 5 * family.tolerance["eva_rel_err"]
    # and the stated path, after the patch is gone, is inside again
    if fault == FAULTS[-1]:
        assert compare.within(family.errors_of(
            family.system_loss_and_readings, params, batch,
            reference_outputs), family.tolerance)


def test_the_reference_imports_nothing_of_the_program():
    import chipbench.reference.evabyte as reference

    source = open(reference.__file__).read()
    code = source.split('"""', 2)[2]
    assert "ray_tpu" not in code and "import chipbench" not in code
    assert 'default_matmul_precision("highest")' in source
    assert not re.search(r"\blse\b|pallas|custom_vjp", code)
    assert "jax.nn.softmax(jnp.where(seen" in code  # one masked softmax
    assert "head[:, vocab * i:vocab * (i + 1)]" in code  # eight slices
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
    assert cell["config"]["family"] == "evabyte"
    assert cell["workload"]["traffic"] == TRAFFIC
    loops = spec.load_code(root, "loops", "evabyte")
    assert loops.__file__.startswith(root)
    cfg = loops.model_config(cell["config"])
    assert cfg.layer_types == ("eva_attention",) * 4 and cfg.eva_window == 2048
    names = [w["name"] for w in spec.load_benchmark(root)["workloads"]]
    assert CELL in names
    assert os.path.exists(os.path.join(
        root, "chipbench", "readers", "eva_roofline.py"))


def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_evabyte()
    reports = run_loop_here(monkeypatch, cell, seconds=0.4)
    summary = reports[-1]
    assert summary["summary"] and summary["steps_failed"] == 0
    assert summary["compiles_in_window"] == 0
    assert summary["steps"] == 2 * len(summary["chunks"]) >= 2
    assert all(math.isfinite(c["loss"]) for c in summary["chunks"])
    reference = summary["reference"]
    assert reference["agrees"]
    assert {"eva_rel_err", "grad_rel_err", "eva_remote_mass",
            "eva_chunk_entropy_reference"} <= set(reference)
    json.dumps(reference)  # the worker's record is one JSON line
    assert summary["flops_per_unit"] == pytest.approx(
        evabyte_flops.evabyte_flops_per_token(cell["config"], 64))
    assert all(c["units"] == 2 * 64 for c in summary["chunks"])


def test_the_step_reports_the_layers_readings(in_float32):
    cell, family, params, batch, _ = in_float32
    # the step donates its state: a copy, the fixture's stay
    state = family.init_state(jax.tree.map(jnp.copy, params))
    state, out = family.step(state, batch)
    assert set(out) == {"loss", "grad_norm", "eva_remote_mass",
                        "eva_chunk_entropy"}
    assert out["eva_remote_mass"].shape == out["eva_chunk_entropy"].shape == (
        2,)
    assert math.isfinite(float(out["loss"]))


@pytest.mark.parametrize("traced", [False, True])
def test_last_line_keys(traced):
    cell = spec.load_cell(spec.ROOT, CELL)
    reduced = fake_reduced(1) if traced else None
    line = run.last_line(spec.ROOT, BENCH, cell, fake_summary(cell), reduced)
    assert line["correct"] is True
    if traced:
        assert {"model_mfu.tokens", "peak_hbm_gb.tokens",
                "device_idle_share.tokens", "steady_rate.tokens",
                "pallas_time_share.tokens", "eva_time_share.tokens",
                "flash_time_share.tokens"} <= set(line["metrics"])
        assert line["metrics"]["eva_time_share.tokens"]["value"] == 0  # none
        for name in (*ROOFLINES, "eva_scope_time_share.tokens"):  # they wait
            assert name not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_the_cell_and_its_lists():
    cell = spec.by_name(BENCH["workloads"], CELL, "workload")
    assert cell == {**cell, "config": CONFIG, "traffic": TRAFFIC, "chips": 1}
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) == 14  # appended to the fourteen there were
    assert [c["name"] for c in BENCH["configs"]].index(CONFIG) == 13
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 2
    tokens = spec.by_name(BENCH["end_to_end"], "train_tokens_per_s", "metric")
    assert tokens["workloads"][-1] == CELL
    for name in LISTS:
        assert spec.by_name(BENCH["per_layer"], name, "metric")[
            "workloads"][-1] == CELL, name
    named = {m["name"] for m in spec.metrics_of(BENCH, CELL, "per_layer")}
    assert named == set(LISTS) | {"gang_boot_s", "state_init_s"}
    assert BENCH["per_layer"][-1]["name"] == "eva_time_share.tokens"
    window = spec.by_name(
        BENCH["per_layer"], "flash_window_time_share.tokens", "metric")
    assert CELL not in window["workloads"]  # no `_window` kernel runs
    for text in (cell["why"], spec.by_name(
            BENCH["configs"], CONFIG, "config")["why"]):
        assert len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_traffic_is_one_sequence_of_8192_a_step():
    traffic = spec.load_cell(spec.ROOT, CELL)["traffic"]
    assert (traffic["kind"], traffic["batch_rows"], traffic["rows_per_block"],
            traffic["units_per_row"]) == ("ingest", 1, 1, 8192)
    assert traffic["columns"]["tokens"]["shape"] == [8192 + 8]
    assert traffic["columns"]["tokens"]["high"] == "config:vocab_size"
    assert (traffic["steps_per_chunk"], traffic["warmup_steps"],
            traffic["trace_chunks"], traffic["blocks_per_epoch"],
            traffic["prefetch_batches"]) == (3, 2, 2, 256, 2)
    rows = traffic_lib.make_rows(traffic, held_config(), 2**31 + 7, 0, 1)
    assert rows["tokens"].shape == (1, 8200)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 320


def test_the_kernels_share_reads_the_new_names_alone():
    held = spec.read_json(
        spec.ROOT, "chipbench", "metrics", "eva_time_share.tokens.json")
    assert held["reader"] == "trace_share" and "awaits" not in held
    ops = [["fusion.1", 0, 500], ["eva_summaries_fwd.2 [tpu_custom_call]", 500, 50],
           ["flash_fwd_stair.3 [tpu_custom_call]", 550, 30],
           ["flash_bwd_dkv_dq_stair.4 [tpu_custom_call]", 580, 20],
           ["flash_fwd.5 [tpu_custom_call]", 600, 100],
           ["eva_summaries_bwd.6 [tpu_custom_call]", 700, 100]]
    from chipbench import trace

    reduced = trace.reduce({"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_step", 0, 1000]]}}, "host_spans": []})
    assert spec.read_metric(spec.ROOT, "eva_time_share.tokens", {
        "trace": reduced}) == pytest.approx(100.0 * 200 / 800)
    assert spec.read_metric(
        spec.ROOT, "eva_time_share.tokens", {"trace": None}) is None


def test_the_waiting_scope_share():
    name = "eva_scope_time_share.tokens"
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry = held["awaits"]
    assert held["reader"] == "scope_share" and held["params"] == {
        "scope": "eva"}
    assert entry == {**entry, "name": name, "unit": "%", "better": "lower",
                     "source": "device_trace", "workloads": [CELL],
                     "moves": "train_tokens_per_s"}
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert not any(m["name"] == name for m in BENCH["per_layer"])


@pytest.mark.parametrize("name", sorted(ROOFLINES))
def test_the_waiting_rooflines(name):
    """The file's shapes are the configuration's, and the reader finds the
    kernel's events by name: a call that took twice its least time reads
    50 %."""
    event, part, kernel = ROOFLINES[name]
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry, params = held["awaits"], held["params"]
    assert held["reader"] == "eva_roofline"
    assert entry == {**entry, "name": name, "unit": "%", "better": "higher",
                     "source": "device_trace", "workloads": [CELL],
                     "moves": "train_tokens_per_s"}
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    assert not any(m["name"] == name for m in BENCH["per_layer"])
    config = held_config()
    assert params == {
        "event": event, "part": part, "kernel": kernel,
        "d_model": config["d_model"], "n_heads": config["n_heads"],
        "eva_window": config["eva_window"], "eva_chunk": config["eva_chunk"],
        "seq_len": 8192}
    call = {"summaries": evabyte_flops.summaries_call,
            "window": evabyte_flops.window_call,
            "stair": evabyte_flops.stair_call}[part]
    ops, moved = call(kernel, config, 8192)
    from chipbench import flops, kernel_flops
    least, bound = kernel_flops.least_seconds(
        ops, moved, flops.peaks_for("TPU v5 lite"))
    # the summaries' passes and the staircase's forward (8192 rows of q and
    # o for 1.6 M pairs a head) are bandwidth's; the rest the MXU's
    assert bound == ("memory" if part == "summaries" or name == (
        "flash_fwd_roofline.stair.tokens") else "compute")
    named = event + ".7 [tpu_custom_call]"
    run_ = {"chips": 1, "device": {"kind": "TPU v5 lite"},
            "chunks": [{"units": 3 * 8192, "steps": 3}],
            "trace": {"segments": {"0": [
                [0, int(2e9 * least), named],
                [int(3e9 * least), int(5e9 * least), named],
                [0, 10, "moe_gmm.1 [tpu_custom_call]"]]}}}
    assert spec.read_metric(spec.ROOT, name, run_) == pytest.approx(
        50.0, rel=1e-6)
    run_["trace"]["segments"]["0"] = [
        [0, 10, event + "_sparse.1 [tpu_custom_call]"]]
    assert spec.read_metric(spec.ROOT, name, run_) is None
    assert spec.read_metric(spec.ROOT, name, {**run_, "trace": None}) is None
