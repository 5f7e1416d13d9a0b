"""The `sdar` family of the benchmark on the CPU: the model against its plain
reference at a tiny size, each wrong form that has to fall outside
`TOLERANCE`, the shares of the experts against the uncut layer, the tiny
cell's loop end to end, the operation and byte counts by hand, and the new
files' form. `wrong_systems` is also what the builder's chip run takes its
wrong forms from, at the published widths."""

import copy
import json
import math
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, loop, run, sdar_flops, spec
from chipbench import traffic as traffic_lib
from chipbench_tiny import fake_reduced, fake_summary, run_loop_here

CELL = "sdar.tokens16k"
CONFIG = "sdar-30b-a3b-l4-ep8"
TRAFFIC = "tokens-16k-16k-bd4"
BENCH = spec.load_benchmark(spec.ROOT)
FAULTS = [
    "noisy_row_sees_its_block_s_clean_copy", "clean_row_sees_a_noisy_row",
    "own_block_causal", "staircase_one_block_late",
    "noisy_half_at_later_positions",
    "weight_left_out", "loss_over_every_position", "logits_shifted_by_one",
    "mask_id_drawn_as_data", "bf16_everything"]
# the lists of BENCHMARK.json the cell joins (ISSUE 70, item 6)
LISTS = [
    "ingest_wait_share.tokens", "steady_rate.tokens", "stall_share.tokens",
    "model_mfu.tokens", "pallas_time_share.tokens", "device_idle_share.tokens",
    "peak_hbm_gb.tokens", "moe_gmm_time_share.tokens", "flash_time_share.tokens",
    "cluster_init_s", "compile_s", "first_batch_s", "setup_unnamed_s",
    "ingest_produce_share.tokens", "moe_sum_time_share.tokens", "trace_s",
    "lower_s", "pallas_trace_s", "before_first_program_s", "before_init_s",
    "moe_held_fill.tokens", "moe_extra_chunk_share.tokens",
    "moe_dropped_slot_share.tokens", "moe_load_max_over_mean.tokens",
    "step_dispatch_share.tokens", "bd_attention_time_share.tokens",
    "diffusion_masked_share.tokens", "diffusion_rows_per_token.tokens"]
WAITING = ["flash_fwd_roofline.sdar.tokens",
           "flash_bwd_dkv_dq_roofline.sdar.tokens"]


def tiny_sdar(dtype="bfloat16", **over):
    """64 wide, 2 layers: 8 query heads of 16 over 2 key-value heads, 4 of
    16 experts held, 3 a token; sequences of 64 in blocks of 4, compared at
    48; the mask's id is the vocabulary's last."""
    cell = copy.deepcopy(spec.load_cell(spec.ROOT, CELL))
    config, traffic = cell["config"], cell["traffic"]
    config.update(**{**dict(
        vocab_size=256, mask_token_id=255, d_model=64, n_heads=8,
        n_kv_heads=2, d_head=16, d_ff=32, max_seq_len=64, n_experts=16,
        experts_held=[4, 4], experts_per_token=3, dtype=dtype, n_layers=2,
        layer_types=["block_diffusion_attention"] * 2,
        check=dict(config["check"], rows=2, seq_len=48)), **over})
    for name, width in (("tokens", 64), ("noise", 64), ("level", 16)):
        traffic["columns"][name]["shape"] = [width]
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


# -------------------------------------------- wrong forms, as wrong systems

def wrong_systems(cell, family):
    """{name: (the system to hand `family.errors_of`, the inputs it makes
    of a batch where they are not the stated ones, what it does to the
    batch)}: each computes something other than the stated objective."""
    from chipbench.reference import sdar as reference
    from ray_tpu.models import transformer

    cfg = family.model_config
    system = family.system_loss_and_readings
    stated_inputs = transformer.diffusion_inputs

    def patched(name, value):
        """The stated system traced while `transformer.name` is `value`."""
        def run(p, b):
            stated = getattr(transformer, name)
            setattr(transformer, name, value)
            try:
                return system(p, b)
            finally:
                setattr(transformer, name, stated)

        return run

    def under_mask(mask_of):
        """The layers' attention as one dense softmax under `mask_of(row's
        and column's half, block and position)`."""
        def attention(q, k, v, *, block, **kw):
            rows = q.shape[1]
            length = rows // 2
            pos = jnp.arange(rows) % length
            clean = jnp.arange(rows) >= length
            keep = mask_of(clean[:, None], clean[None, :],
                           (pos // block)[:, None], (pos // block)[None, :],
                           pos[:, None], pos[None, :])
            # the reference's dense softmax, a block of rows at a time: at
            # the published widths the scores of 8,192 rows do not fit whole
            return reference.masked_attention(
                *(x.astype(jnp.float32) for x in (q, k, v)), keep
            ).astype(q.dtype)

        return patched("block_diffusion_attention", attention)

    def own(rc, cc, rb, cb, rp, cp):
        return (rc == cc) & (rb == cb)

    def with_inputs(change):
        """`diffusion_inputs` whose (rows, positions, weights, masked)
        `change` has altered."""
        def inputs(batch, cfg):
            return change(batch, *stated_inputs(batch, cfg))

        return patched("diffusion_inputs", inputs), inputs

    def every_position(batch, rows, positions, weights, masked):
        level = jnp.repeat(batch["level"], cfg.diffusion_block, axis=1)
        return rows, positions, (
            transformer.NOISE_LEVELS / level.astype(jnp.float32)
            / masked.size), masked

    head = transformer.weighted_lm_head_cross_entropy

    def shifted_head(hidden, unembed, targets, weights):
        return head(hidden, unembed, jnp.roll(targets, -1, axis=1), weights)

    def mask_id_as_data(batch):
        """Token ids drawn below `vocab_size`, not below the mask's id: here
        every eighth clean token is the mask's id."""
        tokens = np.array(batch["tokens"])
        tokens[:, ::8] = cfg.mask_token_id
        return {**batch, "tokens": jnp.asarray(tokens)}

    def bf16_everything(p, b):
        p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
        loss, readings = system(p, b)
        return loss.astype(jnp.bfloat16).astype(jnp.float32), readings

    same = lambda batch: batch  # noqa: E731
    return {
        # the staircase one block EARLY is this one: a clean row's own
        # block is in its own half already
        "noisy_row_sees_its_block_s_clean_copy": (under_mask(
            lambda rc, cc, rb, cb, rp, cp: own(rc, cc, rb, cb, rp, cp)
            | (cc & (cb < rb)) | (~rc & cc & (cb == rb))), None, same),
        "clean_row_sees_a_noisy_row": (under_mask(
            lambda rc, cc, rb, cb, rp, cp: own(rc, cc, rb, cb, rp, cp)
            | (cc & (cb < rb)) | (rc & ~cc & (cb == rb))), None, same),
        "own_block_causal": (under_mask(
            lambda rc, cc, rb, cb, rp, cp: (
                own(rc, cc, rb, cb, rp, cp) & (cp <= rp))
            | (cc & (cb < rb))), None, same),
        "staircase_one_block_late": (under_mask(
            lambda rc, cc, rb, cb, rp, cp: own(rc, cc, rb, cb, rp, cp)
            | (cc & (cb < rb - 1))), None, same),
        "noisy_half_at_later_positions": (*with_inputs(
            lambda batch, rows, positions, weights, masked: (
                rows, jnp.broadcast_to(jnp.arange(rows.shape[1]), rows.shape),
                weights, masked)), same),
        "weight_left_out": (*with_inputs(
            lambda batch, rows, positions, weights, masked: (
                rows, positions, masked / masked.size, masked)), same),
        "loss_over_every_position": (*with_inputs(every_position), same),
        "logits_shifted_by_one": (
            patched("weighted_lm_head_cross_entropy", shifted_head), None,
            same),
        "mask_id_drawn_as_data": (system, None, mask_id_as_data),
        "bf16_everything": (bf16_everything, None, same),
    }


def errors_of_wrong(family, wrong, params, batch):
    system, inputs, of_batch = wrong
    return family.errors_of(system, params, of_batch(batch),
                            system_inputs=inputs)


# ------------------------------------------------------------ the comparison

def test_tiny_cell_keeps_the_family_and_its_mechanisms():
    config = tiny_sdar()["config"]
    assert config["family"] == "sdar"
    assert config["layer_types"] == ["block_diffusion_attention"] * 2
    assert config["objective"] == "block_diffusion"
    assert config["diffusion_block"] == 4
    assert config["qk_norm"] == "head" and config["router_score"] == "softmax"
    assert config["norm_topk_prob"] is True and config["tied_embeddings"] is False


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_agrees_with_the_reference(dtype):
    cell = tiny_sdar(dtype)
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    errors = family.check(params, check_batch(cell, family))
    if dtype == "float32":  # the same mathematics to rounding
        assert errors["loss_rel_err"] < 1e-6 and errors["grad_rel_err"] < 1e-5
        assert errors["router_flip_share"] == 0.0
        assert errors["aux_loss_rel_err"] < 1e-6
        assert compare.within(errors, family.tolerance)
    else:
        assert errors["loss_rel_err"] < family.tolerance["loss_rel_err"]
        assert errors["router_flip_share"] < 0.05
        assert errors["grad_rel_err"] < 0.12  # tiny layers round harder
        assert errors["aux_loss_rel_err"] < 2e-3
    assert errors["masked_share"] == 0.0
    assert errors["dropped_slots"] == errors["unrouted_slots"] == 0.0
    assert errors["rows_per_token"] == 2.0
    assert 0.3 < errors["masked_tokens_share"] < 0.7
    assert 0.7 < errors["weight_mean"] < 1.4


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_form_is_outside_the_tolerance(fault):
    """In float32, where the stated path agrees to rounding, so that what
    is left is the fault's own: each fails the key that holds it."""
    cell = tiny_sdar("float32")
    family = family_of(cell)
    params = family.init_params(loop.seed_key(2**31 + 3))
    batch = check_batch(cell, family)
    wrong = errors_of_wrong(
        family, wrong_systems(cell, family)[fault], params, batch)
    assert not compare.within(wrong, family.tolerance), wrong
    tolerance = family.tolerance
    if fault == "mask_id_drawn_as_data":  # a position in 8, and no other key
        assert wrong["masked_share"] > 0.04
        assert wrong["grad_rel_err"] < 1e-4 and wrong["loss_rel_err"] < 1e-5
    elif fault == "bf16_everything":
        assert wrong["loss_rel_err"] > tolerance["loss_rel_err"]
    else:
        assert wrong["masked_share"] == 0.0
        assert wrong["grad_rel_err"] > tolerance["grad_rel_err"], wrong


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The reference's routed feed-forward over all 16 experts is the sum of
    what eight chips holding 2 each compute: what a share leaves out is what
    the other seven add, and nothing stands in for them."""
    from chipbench.reference import sdar as reference
    from ray_tpu.models.transformer import transformer_init

    cell = tiny_sdar("float32", experts_held=[0, 16])
    config = cell["config"]
    family = family_of(cell)
    params = transformer_init(jax.random.PRNGKey(5), family.model_config)
    w = {k: jnp.asarray(v[0], jnp.float32) for k, v in params["blocks"].items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        whole, picked, balance = reference.routed_feed_forward(x, w, config)
        parts = []
        for chip in range(8):
            held = {**config, "experts_held": [2 * chip, 2]}
            share = {**w, **{name: w[name][2 * chip:2 * chip + 2]
                             for name in ("w_gate", "w_up", "w_down")}}
            out, own, term = reference.routed_feed_forward(x, share, held)
            parts.append(out - x)
            assert bool(jnp.all(own == picked)) and float(term) == float(balance)
    assert jnp.allclose(sum(parts), whole - x, atol=1e-5)
    assert float(jnp.abs(parts[0]).max()) > 0
    assert int(picked.sum()) == 2 * 24 * 3


def test_the_reference_s_mask_is_the_program_s():
    from chipbench.reference import sdar as reference
    from ray_tpu.ops.block_diffusion import dense_mask

    for length, block in ((8, 4), (24, 4), (16, 2)):
        assert np.array_equal(np.asarray(reference.attention_mask(length, block)),
                              np.asarray(dense_mask(length, block)))


def test_loop_end_to_end_at_a_tiny_size(ray_start_regular, monkeypatch):
    cell = tiny_sdar()
    reports = run_loop_here(monkeypatch, cell, seconds=0.4)
    summary = reports[-1]
    assert summary["summary"] and summary["steps_failed"] == 0
    assert summary["compiles_in_window"] == 0
    assert summary["steps"] == 2 * len(summary["chunks"]) >= 2
    assert all(math.isfinite(c["loss"]) for c in summary["chunks"])
    assert summary["chunks"][0]["units"] == 2 * 64  # tokens, not rows
    reference = summary["reference"]
    assert {"router_flip_share", "aux_loss_rel_err", "masked_share",
            "masked_tokens_share", "weight_mean", "rows_per_token",
            "held_slots_mean", "dropped_slots"} <= set(reference)
    assert reference["masked_share"] == 0.0
    assert summary["flops_per_unit"] == sdar_flops.sdar_flops_per_token(
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
    parts = sdar_flops.forward_parts(config, 16384)
    d = 2048
    attention = 2 * d * 32 * 128 + 2 * d * 4 * 128
    assert sdar_flops.attention_params(config) == attention == 18874368
    assert parts["attention_projections"] == 4 * 2 * 2 * attention  # two rows
    # each row the clean rows before its block and its own block's 4
    assert sdar_flops.pairs_per_token(16384, 4) == 16388
    assert sdar_flops.stair_pairs(16384, 4) == sum(
        4 * (i // 4) for i in range(16384)) == 16384 * 16380 // 2
    assert 2 * sdar_flops.stair_pairs(16384, 4) + 2 * 4 * 16384 == (
        16384 * 16388) == 268500992  # twice a causal step's 134,225,920
    assert parts["pairs"] == 4 * 4 * 32 * 128 * 16388
    assert parts["router"] == 4 * 2 * 2 * d * 128
    assert parts["experts"] == 4 * 2 * (8 * 16 / 128) * 6 * d * 768  # 75.5 M
    assert parts["head"] == 2 * d * 18992                            # 77.8 M
    forward = sum(parts.values())
    total = sdar_flops.sdar_flops_per_token(config, 16384)
    assert total == 3 * forward == pytest.approx(4.600e9, rel=5e-4)
    share = {k: v / forward for k, v in parts.items()}
    assert share["pairs"] == pytest.approx(0.70, abs=0.005)
    assert share["attention_projections"] == pytest.approx(0.197, abs=0.005)
    assert share["head"] == pytest.approx(0.051, abs=0.002)


def test_flops_agree_with_the_program_s_own_count():
    from ray_tpu.models.transformer import flops_per_token

    cell = spec.load_cell(spec.ROOT, CELL)
    family = spec.load_code(spec.ROOT, "loops", "sdar")
    assert flops_per_token(family.model_config(cell["config"]), 16384) == (
        pytest.approx(sdar_flops.sdar_flops_per_token(
            cell["config"], 16384), rel=1e-12))


def test_param_count_and_the_cut_s_arithmetic():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    n = sdar_flops.state_params(config)
    layer = 18874368 + 256 + 262144 + 4096 + 75497472
    assert layer == 94638336
    assert n == 4 * layer + 2 * 18992 * 2048 + 2048 == 456346624
    assert 7.30 < 16 * n / 1e9 < 7.31
    assert 0.43 < 16 * n / 16.91e9 < 0.44  # 43 % of the chip, floor 25 %
    family = spec.load_code(spec.ROOT, "loops", "sdar")
    from ray_tpu.models.transformer import transformer_init
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0),
                                 family.model_config(config)))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) == n
    for number in ("456,346,624", "94,638,336", "18,874,368", "75,497,472"):
        assert number in config["deployment"], number
    # five and six layers, should a later cut want them
    assert 16 * (n + layer) / 1e9 == pytest.approx(8.82, abs=0.01)
    assert 16 * (n + 2 * layer) / 1e9 == pytest.approx(10.33, abs=0.01)


def test_the_whole_model_is_the_published_thirty_billion():
    published = spec.load_cell(spec.ROOT, CELL)["config"]["catalog_config"]
    whole = sdar_flops.whole_model_params(published)
    assert whole / 1e9 == pytest.approx(30.53, abs=0.005)
    layer = 18874368 + 256 + 262144 + 4096 + 128 * 3 * 2048 * 768
    assert whole == 48 * layer + 2 * 151936 * 2048 + 2048
    # about 3.3 B a token: 8 of 128 experts, the rest whole
    active = whole - 48 * 120 * 3 * 2048 * 768
    assert active / 1e9 == pytest.approx(3.35, abs=0.01)


def test_staircase_kernel_operations_and_bytes_by_hand():
    pairs, t = 16384 * 16380 // 2, 16384
    ops, moved = sdar_flops.stair_call("flash_fwd", 1, 32, 4, t, 4, 128, 128)
    assert ops == 2 * pairs * (128 + 128) * 64  # both halves' 32 heads
    q, kv, row = 64 * t * 128, 4 * t * 128, 64 * t * 8 * 4
    assert moved == (2 * q + 2 * kv) * 2 + row  # q, k, v, o, lse
    ops, moved = sdar_flops.stair_call(
        "flash_bwd_dkv_dq", 1, 32, 4, t, 4, 128, 128)
    assert ops == 2 * pairs * 5 * 128 * 64
    assert moved == (2 * q + 2 * kv) * 2 + 2 * row + (q + 2 * kv) * 2
    assert sdar_flops.stair_call(
        "flash_bwd_dq", 1, 32, 4, t, 4, 128, 128)[0] == 2 * pairs * 3 * 128 * 64
    assert sdar_flops.stair_call(
        "flash_bwd_dkv", 1, 32, 4, t, 4, 128, 128)[0] == 2 * pairs * 4 * 128 * 64
    # a step's forward staircase pairs and the own blocks' are the model's
    # count: 4 layers, 16384 tokens
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    per_call = sdar_flops.stair_call("flash_fwd", 1, 32, 4, t, 4, 128, 128)[0]
    own = 2 * t * 4 * 32 * 4 * 128  # 2 L rows x 4 keys x 32 heads x 2 x 2 x 128
    assert 4 * (per_call + own) == pytest.approx(
        t * sdar_flops.forward_parts(config, t)["pairs"], rel=1e-12)
    # the least time a v5e could take: 22.3 ms forward, 55.8 ms backward
    assert per_call / 197e12 == pytest.approx(22.3e-3, rel=5e-3)


# ------------------------------------------------------------ the new files

def test_configuration_holds_the_catalog_s_numbers():
    config = spec.load_cell(spec.ROOT, CELL)["config"]
    source = config["catalog_config"]
    reduced = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}
    entry = spec.by_name(BENCH["configs"], CONFIG, "config")
    assert entry["reduced"] == config["reduced"] == list(reduced)
    for key, value in source.items():
        assert config[key] == reduced.get(key, value), key
    for key in reduced:
        assert config["published"][key] == source[key], key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = [json.loads(line) for line in f if "SDAR-30B-A3B-Chat" in line]
    if catalog:  # the catalog's row, number for number
        assert catalog[0]["config"] == source
        assert config["source"].startswith(catalog[0]["source_url"])
        assert catalog[0]["not_given"] == ["block length", "noise schedule"]
    # every width as published, in the keys the program reads
    assert config["d_model"] == source["hidden_size"] == 2048
    assert config["n_heads"] == source["num_attention_heads"] == 32
    assert config["n_kv_heads"] == source["num_key_value_heads"] == 4
    assert config["d_head"] == source["head_dim"] == 128
    assert config["d_ff"] == source["moe_intermediate_size"] == 768
    assert config["n_experts"] == source["num_experts"] == 128
    assert config["experts_per_token"] == source["num_experts_per_tok"] == 8
    assert config["experts_held"] == [0, config["num_experts"]] == [0, 16]
    assert config["norm_topk_prob"] is source["norm_topk_prob"] is True
    assert config["rope_theta"] == source["rope_theta"] == 1000000
    assert source["rope_scaling"] is None and source["sliding_window"] is None
    assert config["norm_eps"] == source["rms_norm_eps"]
    assert config["tied_embeddings"] == source["tie_word_embeddings"]
    assert source["attention_bias"] is False and source["hidden_act"] == "silu"
    assert config["n_layers"] == config["num_hidden_layers"] == len(
        config["layer_types"]) == 4
    assert set(config["layer_types"]) == {"block_diffusion_attention"}
    assert config["objective"] == "block_diffusion"
    assert config["diffusion_block"] == 4
    assert config["mask_token_id"] == config["vocab_size"] - 1 == 18991
    assert config["published"]["chips_sharing_a_layer"] == 8
    assert config["published"]["layers_held"] == [0, 1, 2, 3]
    assert config["published"]["vocab_size"] == 151936 == 8 * config["vocab_size"]
    assert config["published"]["num_experts"] == 8 * config["num_experts"]
    assert config["source"].startswith(entry["source"])
    assert {"diffusion_block", "noise_schedule", "positions", "no_shift",
            "attention_mask", "balance_loss", "mask_token_id", "qk_norm",
            "router", "norms_and_biases", "rotary_layout", "initialisers",
            "optimizer", "held_chunk", "remat", "dtype", "deployment",
            "sequences"} <= set(config["assumed"])
    assert "recollection" in config["assumed"]["diffusion_block"]
    assert config["check"] == {"rows": 1, "seq_len": 4096}
    assert config["max_seq_len"] == 16384 and config["mesh"] == {"data": 1}


def test_traffic_mix_is_the_issue_s():
    cell = spec.load_cell(spec.ROOT, CELL)
    traffic = cell["traffic"]
    assert cell["workload"]["traffic"] == TRAFFIC
    assert traffic["kind"] == "ingest"
    assert traffic_lib.units_per_step(traffic) == 16384
    assert (traffic["steps_per_chunk"], traffic["blocks_per_epoch"],
            traffic["trace_chunks"], traffic["warmup_steps"],
            traffic["prefetch_batches"], traffic["rows_per_block"],
            traffic["batch_rows"]) == (1, 256, 2, 2, 2, 1, 1)
    rows = traffic_lib.make_rows(traffic, cell["config"], 2**31 + 9, 0, 1)
    assert set(rows) == {"tokens", "noise", "level"}
    assert rows["tokens"].shape == rows["noise"].shape == (1, 16384)
    assert rows["level"].shape == (1, 4096)
    assert all(x.dtype == np.int32 for x in rows.values())
    # the mask's id is never data; levels in [1, 2^24], draws in [0, 2^24)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 18991
    assert 0 <= rows["noise"].min() and rows["noise"].max() < 1 << 24
    assert 1 <= rows["level"].min() and rows["level"].max() <= 1 << 24
    masked = rows["noise"] < np.repeat(rows["level"], 4, axis=1)
    assert 0.45 < masked.mean() < 0.55  # about half the tokens a step


def test_the_cell_s_files_are_found_by_name_under_another_root(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    for kind, name in (("configs", CONFIG + ".json"),
                       ("traffic", TRAFFIC + ".json"),
                       ("metrics", "bd_attention_time_share.tokens.json"),
                       ("readers", "trace_share.py")):
        os.makedirs(os.path.join(root, "chipbench", kind), exist_ok=True)
        shutil.copy(os.path.join(spec.ROOT, "chipbench", kind, name),
                    os.path.join(root, "chipbench", kind, name))
    cell = spec.load_cell(root, CELL)
    assert cell == spec.load_cell(spec.ROOT, CELL)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["config"] == CONFIG
    assert {m["name"] for m in spec.metrics_of(BENCH, CELL, "end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    assert spec.read_metric(root, "bd_attention_time_share.tokens",
                            {"trace": fake_reduced(1)}) == 0.0


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
    four_chip = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(BENCH["workloads"]) >= 17 and len(four_chip) >= 2
    assert 4 * len(four_chip) <= len(BENCH["workloads"])


def test_the_new_metrics_on_a_made_up_trace():
    from chipbench import trace

    for name, better in (("bd_attention_time_share.tokens", "lower"),
                         ("diffusion_masked_share.tokens", "higher"),
                         ("diffusion_rows_per_token.tokens", "lower")):
        entry = spec.by_name(BENCH["per_layer"], name, "metric")
        assert entry["workloads"] == [CELL] and entry["better"] == better
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["layer"] in {
            m["layer"] for m in BENCH["per_layer"] if m is not entry}
    cell = spec.load_cell(spec.ROOT, CELL)
    run_ = dict(fake_summary(cell), chips=1, trace=fake_reduced(1))
    # none on a program without the kernels or the counters, as the parent:
    # 0, no raise
    for name in ("bd_attention_time_share.tokens",
                 "diffusion_masked_share.tokens",
                 "diffusion_rows_per_token.tokens"):
        assert spec.read_metric(spec.ROOT, name, run_) == 0.0
    assert spec.read_metric(
        spec.ROOT, "bd_attention_time_share.tokens", {"trace": None}) is None
    ops = [["fusion.1", 0, 300], ["flash_fwd.3 [tpu_custom_call]", 300, 100],
           ["flash_fwd_stair.4 [tpu_custom_call]", 400, 200],
           ["flash_bwd_dkv_dq_stair.5 [tpu_custom_call]", 600, 300],
           ["moe_gmm.10 [tpu_custom_call]", 900, 100]]
    run_["trace"] = trace.reduce({"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_step", 0, 1000]]}}, "host_spans": []})
    assert spec.read_metric(
        spec.ROOT, "bd_attention_time_share.tokens", run_) == pytest.approx(50.0)
    # the live share of all the flash kernels counts the staircase's too
    assert spec.read_metric(
        spec.ROOT, "flash_time_share.tokens", run_) == pytest.approx(60.0)
    run_["ray_tpu_runtime"] = {"counters_since_first_report": {
        "diffusion.tokens": 8 * 16384, "diffusion.masked_tokens": 65000,
        "diffusion.rows": 16 * 16384, "diffusion.weight_sum": 131000.0}}
    assert spec.read_metric(
        spec.ROOT, "diffusion_masked_share.tokens", run_) == pytest.approx(
            100 * 65000 / 131072)
    assert spec.read_metric(
        spec.ROOT, "diffusion_rows_per_token.tokens", run_) == 2.0


@pytest.mark.parametrize("name", WAITING)
def test_waiting_metrics_carry_their_entry(name):
    """Under the key `awaits`, as PRs 55 to 66 left theirs; the files'
    parameters are the configuration's and the mix's."""
    held = spec.read_json(spec.ROOT, "chipbench", "metrics", name + ".json")
    entry, params = held["awaits"], held["params"]
    assert "entry" not in held and held["reader"] == "sdar_roofline"
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert entry["name"] == name and entry["unit"] == "%"
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_tokens_per_s"
    assert entry["better"] == "higher" and entry["source"] == "device_trace"
    assert name not in {m["name"] for m in BENCH["per_layer"]}
    cell = spec.load_cell(spec.ROOT, CELL)
    config = cell["config"]
    for key in ("n_heads", "n_kv_heads", "d_head", "diffusion_block"):
        assert params[key] == config[key], key
    assert params["seq_len"] == cell["traffic"]["units_per_row"]
    assert params["event"] == params["kernel"] + "_stair"


def test_the_rooflines_read_the_kernels_by_name():
    from chipbench import flops, kernel_flops, trace

    cell = spec.load_cell(spec.ROOT, CELL)
    summary = dict(fake_summary(cell), chips=1)
    summary["chunks"] = [{"chunk": 0, "steps": 1, "units": 16384,
                          "seconds": 1.0, "loss": 5.0, "traced": True}]
    # nothing to read on a trace without the kernels, as the parent's
    summary["trace"] = fake_reduced(1)
    for name in WAITING:
        assert spec.read_metric(spec.ROOT, name, summary) is None
    fwd_ms, bwd_ms = 60e6, 120e6  # nanoseconds a call
    ops = [["flash_fwd_stair.4 [tpu_custom_call]", 0, fwd_ms],
           ["flash_fwd_stair.4 [tpu_custom_call]", fwd_ms, fwd_ms],
           ["flash_bwd_dkv_dq_stair.5 [tpu_custom_call]", 2 * fwd_ms, bwd_ms],
           ["flash_fwd.3 [tpu_custom_call]", 2 * fwd_ms + bwd_ms, 100]]
    summary["trace"] = trace.reduce({"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_step", 0, 4e8]]}}, "host_spans": []})
    peaks = flops.peaks_for("TPU v5 lite")
    for name, kernel, seconds in ((WAITING[0], "flash_fwd", 0.06),
                                  (WAITING[1], "flash_bwd_dkv_dq", 0.12)):
        least, _ = kernel_flops.least_seconds(
            *sdar_flops.stair_call(kernel, 1, 32, 4, 16384, 4, 128, 128), peaks)
        read = spec.read_metric(spec.ROOT, name, summary)
        assert read == pytest.approx(100 * least / seconds)
        assert 30 < read < 100


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(spec.ROOT, "chipbench", "reference", "sdar.py")
    with open(path) as f:
        source = f.read()
    assert "ray_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "bfloat16" not in source.split('"""', 2)[2]
