"""Device time by the program's scopes and by phase (`chipbench/scopes.py`),
the flash kernels' roofline arithmetic (`chipbench/kernel_flops.py`), the
two readers, the metric files that wait for their place in BENCHMARK.json,
and the recorded fixtures. No chip and no JAX backend: nothing here is a
time."""

import json
import os

import pytest

from chipbench import kernel_flops, scopes, spec, trace
from chipbench_tiny import fake_summary

ROOT = spec.ROOT
BENCH = spec.load_benchmark(ROOT)
FIXTURES = os.path.join(ROOT, "chipbench", "fixtures")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def waiting():
    """{metric name: its file} for the files that carry an `entry`."""
    out = {}
    for name in sorted(os.listdir(os.path.join(ROOT, "chipbench", "metrics"))):
        held = spec.read_json(ROOT, "chipbench", "metrics", name)
        if "entry" in held:
            out[name[:-len(".json")]] = held
    return out


# ------------------------------------------------------- phase and scope

@pytest.mark.parametrize("stack,phase,scope", [
    # the transformer step, as the chip's trace spells them
    ("jit(step)/jvp()/while/body/closed_call/attn_qkv/dot_general",
     "forward", "attn_qkv"),
    ("jit(step)/jvp()/while/body/closed_call/attention/flash_fwd/pallas_call",
     "forward", "attention/flash_fwd"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/flash_fwd/pallas_call",
     "recompute", "attention/flash_fwd"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/attention/"
     "flash_bwd_dq/pallas_call", "backward", "attention/flash_bwd_dq"),
    ("jit(step)/transpose(jvp(attention))/flash_bwd_dkv/pallas_call",
     "backward", "attention/flash_bwd_dkv"),
    ("jit(step)/transpose(jvp(lm_head_ce))/while/body/closed_call/checkpoint/"
     "rematted_computation/jit(take_along_axis)/gather",
     "recompute", "lm_head_ce"),
    # a scan inside a scan: still one scope, the innermost transform decides
    ("jit(step)/transpose(jvp())/while/body/while/body/mlp/mul",
     "backward", "mlp"),
    ("jit(step)/jvp(embed)/gather", "forward", "embed"),
    ("jit(step)/attn_qkv/iota", "forward", "attn_qkv"),  # hoisted, not differentiated
    ("jit(step)/optimizer/mul", "optimizer", "optimizer"),
    ("jit(step)/optimizer/jit(_where)/select_n", "optimizer", "optimizer"),
    ("jit(step)/transpose(jvp())/while", "backward", "unscoped"),
    ("jit(step)/jvp()/while/body/dynamic_slice", "forward", "unscoped"),
    # the ResNet step: the optimizer has no scope there
    ("jit(step)/jvp(stage3)/bn/jit(_var)/reduce_sum", "forward", "stage3/bn"),
    ("jit(step)/transpose(jvp(stage2))/conv/conv_general_dilated",
     "backward", "stage2/conv"),
    ("jit(step)/jvp(stem)/reduce_window", "forward", "stem"),
    ("jit(step)/transpose(jvp(head))/dot_general", "backward", "head"),
    ("jit(step)/jvp(jit(log_softmax))/sub", "forward", "unscoped"),
    ("jit(step)/mul", "optimizer", "unscoped"),
    # no name stack at all: what the compiler added
    ("", "unnamed", "unscoped"),
    (None, "unnamed", "unscoped"),
    # two instructions merged, both stacks kept: the first counts
    ("jit(step)/jvp(lm_head_ce)/reshape;jit(step)/jvp(lm_head_ce)",
     "forward", "lm_head_ce"),
    # a parameter's name is no scope, whatever it spells
    ("state['params']['embed']", "optimizer", "unscoped"),
])
def test_classify(stack, phase, scope):
    assert scopes.classify(stack) == (phase, scope)
    assert phase in scopes.PHASES


def made_up():
    """One device, 1000 ns: a while holding a kernel and a fusion, then an
    optimizer fusion, an event without a name stack, and a gap."""
    ops = [
        ["while.1", 0, 700],
        ["fusion.2", 0, 100],
        ["flash_bwd_dq.3 [tpu_custom_call]", 100, 400],
        ["flash_bwd_dq.3 [tpu_custom_call]", 500, 100],
        ["fusion.4", 700, 200],
        ["copy.5", 900, 50],
    ]
    stacks = {
        "while.1": "jit(step)/transpose(jvp())/while",
        "fusion.2": "jit(step)/transpose(jvp())/while/body/closed_call/"
                    "checkpoint/rematted_computation/mlp/dot_general",
        "flash_bwd_dq.3 [tpu_custom_call]":
            "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
            "attention/flash_bwd_dq/pallas_call",
        "fusion.4": "jit(step)/optimizer/mul",
        "copy.5": "",
    }
    reduced = trace.reduce({
        "devices": {"/device:TPU:0": {"ops": ops, "modules": [
            ["jit_step", 0, 1000]]}},
        "host_spans": [["report", 960, 40]],
    })
    return reduced, stacks


def test_rows_phases_sum_to_the_busy_time():
    reduced, stacks = made_up()
    table = scopes.rows(reduced, stacks)
    as_dict = {(phase, scope): (s, pct) for phase, scope, s, pct in table}
    assert as_dict == {
        ("backward", "attention/flash_bwd_dq"): (pytest.approx(500e-9), pytest.approx(500 / 9.5)),
        ("optimizer", "optimizer"): (pytest.approx(200e-9), pytest.approx(200 / 9.5)),
        ("recompute", "mlp"): (pytest.approx(100e-9), pytest.approx(100 / 9.5)),
        # the while's own time: what its body's events do not cover
        ("backward", "unscoped"): (pytest.approx(100e-9), pytest.approx(100 / 9.5)),
        ("unnamed", "unscoped"): (pytest.approx(50e-9), pytest.approx(50 / 9.5)),
    }
    assert [row[2] for row in table] == sorted((r[2] for r in table), reverse=True)
    assert sum(row[2] for row in table) == pytest.approx(reduced["busy_s"])
    assert sum(scopes.share(table, phase=p) for p in scopes.PHASES) == (
        pytest.approx(100.0))
    assert scopes.share(table, scope="attention") == pytest.approx(500 / 9.5)
    assert scopes.share(table, scope="flash_bwd_dq") == pytest.approx(500 / 9.5)
    assert scopes.share(table, phase="backward") == pytest.approx(600 / 9.5)
    assert scopes.share(table, phase="forward") == 0.0
    assert scopes.share(table, scope="flash") == 0.0  # components, not substrings
    assert scopes.kernel_events(reduced, stacks, "flash_bwd_dq") == (
        2, pytest.approx(500e-9))
    assert scopes.kernel_events(reduced, stacks, "flash_fwd") == (0, 0.0)


def test_a_program_without_scopes_gives_no_table_and_no_metric():
    reduced, stacks = made_up()
    bare = {name: "jit(step)/transpose(jvp())/mul" for name in stacks}
    assert scopes.rows(reduced, bare) is None
    for stacks_given in (bare, None):
        run = {"trace": dict(reduced), "chips": 1, "chunks": [
            {"units": 16384.0, "steps": 1}], "device": {"kind": "TPU v5 lite"}}
        if stacks_given:
            run["trace"]["name_stacks"] = stacks_given
        for name in waiting():
            assert spec.read_metric(ROOT, name, run) is None
    assert spec.read_metric(ROOT, "backward_time_share.tokens",
                            {"trace": None}) is None


# ----------------------------------------------- the profiler's file format

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _map_entry(number, key, message):
    return _field(number, _field(1, key) + _field(2, message))


def test_name_stacks_reads_tf_op_from_the_device_planes(tmp_path):
    stat_names = {1: "hlo_category", 2: "tf_op", 3: "program_id",
                  4: "jit(step)/optimizer/mul:"}

    def metadata(ident, line, stats):
        return _map_entry(4, ident, _field(1, ident) + _field(2, line)
                          + b"".join(_field(5, s) for s in stats))

    device = _field(2, "/device:TPU:0") + b"".join(
        _map_entry(5, k, _field(1, k) + _field(2, v))
        for k, v in stat_names.items())
    device += metadata(
        7, '%flash_fwd.16 = (bf16[8]{0}) custom-call(bf16[8]{0} %p), '
           'custom_call_target="tpu_custom_call"',
        [_field(1, 1) + _field(5, "custom-call"),
         _field(1, 2) + _field(5, "jit(step)/jvp()/attention/flash_fwd/"
                                  "pallas_call:"),
         _field(1, 3) + _field(3, 2**63 + 5)])
    device += metadata(8, "%fusion.9 = f32[4]{0} fusion(f32[4]{0} %q)",
                       [_field(1, 2) + _field(7, 4)])  # a ref_value
    device += metadata(9, "%copy.1 = f32[4]{0} copy(f32[4]{0} %r)", [])
    # a line with an event, which the reader has to step over
    device += _field(3, _field(2, "XLA Ops") + _field(4, _field(1, 7)
                                                     + _field(2, 10)))
    host = _field(2, "/host:CPU") + metadata(
        1, "%fusion.9 = not a device's", [_field(1, 2) + _field(5, "no:")])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, host) + _field(1, device))
    assert scopes.name_stacks(str(path)) == {
        "flash_fwd.16 [tpu_custom_call]":
            "jit(step)/jvp()/attention/flash_fwd/pallas_call",
        "fusion.9": "jit(step)/optimizer/mul",
        "copy.1": "",
    }


# ------------------------------------------------------ roofline arithmetic

def test_flash_call_by_hand():
    """The one-chip Mistral cell: 4 sequences x 32 heads of 128, 4096 long."""
    bh, t, d = 128, 4096, 128
    pairs = 8_390_656  # 4096 * 4097 / 2
    assert kernel_flops.causal_pairs(t) == pairs
    assert kernel_flops.causal_pairs(1) == 1 and kernel_flops.causal_pairs(2) == 3
    tensor = 67_108_864  # 128 * 4096 * 128 elements
    row = 16_777_216     # 128 * 4096 * 8 * 4 bytes
    fwd = kernel_flops.flash_call("flash_fwd", bh, t, d)
    dq = kernel_flops.flash_call("flash_bwd_dq", bh, t, d)
    dkv = kernel_flops.flash_call("flash_bwd_dkv", bh, t, d)
    # 2 * pairs * 128 = 2,148,007,936 operations a matmul a (batch, head)
    assert fwd[0] == 2 * 2_148_007_936 * 128 == 549_890_031_616
    assert dq[0] == 3 * 2_148_007_936 * 128 == 824_835_047_424
    assert dkv[0] == 4 * 2_148_007_936 * 128 == 1_099_780_063_232
    assert fwd[1] == 4 * tensor * 2 + row == 553_648_128
    assert dq[1] == 4 * tensor * 2 + 2 * row + tensor * 4 == 838_860_800
    assert dkv[1] == 4 * tensor * 2 + 2 * row + 2 * tensor * 4 == 1_107_296_256
    # 2.79, 4.19 and 5.58 ms of MXU time against 0.68, 1.02 and 1.35 ms of HBM
    for (ops, moved), ms in ((fwd, 2.7913), (dq, 4.1870), (dkv, 5.5826)):
        seconds, bound = kernel_flops.least_seconds(ops, moved, V5E)
        assert bound == "compute" and seconds * 1e3 == pytest.approx(ms, abs=1e-4)
    assert kernel_flops.least_seconds(1e6, 819e9, V5E) == (1.0, "memory")
    with pytest.raises(KeyError):
        kernel_flops.flash_call("flash", bh, t, d)


def test_kernel_roofline_reader_by_hand():
    """Two calls of 60 ms where the MXU needs 4.187 ms: 6.98 %; on four
    chips each call covers a quarter of the step's sequences."""
    ops = [["flash_bwd_dq.3 [tpu_custom_call]", 0, 60e6],
           ["fusion.1", 60e6, 1e6],
           ["flash_bwd_dq.3 [tpu_custom_call]", 61e6, 60e6]]
    stacks = {"flash_bwd_dq.3 [tpu_custom_call]":
              "jit(step)/transpose(jvp())/attention/flash_bwd_dq/pallas_call",
              "fusion.1": "jit(step)/jvp()/mlp/mul"}
    params = spec.read_json(ROOT, "chipbench", "metrics",
                            "flash_bwd_dq_roofline.tokens.json")["params"]
    reader = spec.load_code(ROOT, "readers", "kernel_roofline")
    for chips, tokens in ((1, 16384.0), (4, 65536.0)):
        reduced = trace.reduce({"devices": {
            f"/device:TPU:{i}": {"ops": ops, "modules": []}
            for i in range(chips)}, "host_spans": []})
        reduced["name_stacks"] = stacks
        run = {"trace": reduced, "chips": chips, "device": {"kind": "TPU v5 lite"},
               "chunks": [{"units": 2 * tokens, "steps": 2}]}
        want = 100.0 * (824_835_047_424 / 197e12) / 60e-3
        assert reader.read(run, params) == pytest.approx(want, rel=1e-12)
        assert reader.read(run, dict(params, kernel="flash_fwd")) is None
    with pytest.raises(KeyError):  # a device that is not in peaks.json
        reader.read(dict(run, device={"kind": "TPU v9"}), params)


# -------------------------------------------------------------- fixtures

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(FIXTURES, "v5e_mistral7b_l2_scopes.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_the_rows_the_run_reported(recorded):
    """Two of the four traced steps of this PR's `mistral7b.tokens4k` run on
    a v5e, with the name stack of every event's instruction, against the
    rows and metrics that run's whole traced window gave: the steps are
    alike to a hundredth of a point."""
    reduced = trace.reduce(recorded)
    stacks = recorded["name_stacks"]
    names = {e[0] for e in recorded["devices"]["/device:TPU:0"]["ops"]}
    assert names <= set(stacks) and len(names) > 400
    table = scopes.rows(reduced, stacks)
    assert sum(row[2] for row in table) == pytest.approx(reduced["busy_s"])
    assert sum(row[3] for row in table) == pytest.approx(100.0)
    got = {(phase, scope): pct for phase, scope, _, pct in table}
    reported = {(phase, scope): pct
                for phase, scope, _, pct in recorded["reported"]["rows"]}
    assert set(got) == set(reported)
    for key, pct in reported.items():
        assert got[key] == pytest.approx(pct, abs=0.02), key
    # at least 97 % of the busy time outside the optimizer is under a scope
    outside = [row for row in table if row[0] != "optimizer"]
    assert scopes.share(table, phase="unnamed") < 1.0
    unscoped = sum(row[3] for row in outside if row[1] == scopes.UNSCOPED)
    assert unscoped / sum(row[3] for row in outside) < 0.03
    # every Mosaic kernel's event carries one of the program's three names,
    # and inside and outside measure agree
    segments = reduced["segments"]["/device:TPU:0"]
    pallas = {name for _, _, name in segments if name.endswith(scopes.PALLAS)}
    assert {scopes.classify(stacks[n])[1].split("/")[-1] for n in pallas} == (
        set(scopes.KERNELS))
    by_kernel = sum(scopes.share(table, scope=k) for k in scopes.KERNELS)
    assert by_kernel == pytest.approx(
        100.0 * trace.share(reduced, [r"\[tpu_custom_call\]"], "busy"), abs=1e-9)
    assert by_kernel < scopes.share(table, scope="attention") < by_kernel + 2
    # the metrics, through their files and readers
    run = {"trace": dict(reduced, name_stacks=stacks), "chips": 1,
           "device": {"kind": "TPU v5 lite"},
           "chunks": [{"units": 16384.0, "steps": 1}]}
    metrics = recorded["reported"]["metrics"]
    assert set(metrics) == {name for name, held in waiting().items()
                            if "mistral7b.tokens4k" in held["entry"]["workloads"]}
    for name, value in metrics.items():
        assert spec.read_metric(ROOT, name, run) == pytest.approx(
            value["value"], abs=0.02), name
    assert 0 < spec.read_metric(ROOT, "flash_fwd_roofline.tokens", run) < 100


# every metric of the benchmark, read from the fixture PR 24 recorded with the
# run of `chipbench_tiny.fake_summary`: the values the parent of this PR gave
OLD_FIXTURE_READINGS = {
    "mistral7b.fsdp4:collective_exposed_share.tokens": 0.0,
    "mistral7b.fsdp4:device_idle_share.tokens": 0.2906199443978852,
    "mistral7b.fsdp4:gang_boot_s": 1.0,
    "mistral7b.fsdp4:ingest_wait_share.tokens": 1.65016501650165,
    "mistral7b.fsdp4:model_mfu.tokens": 0.012564708247474493,
    "mistral7b.fsdp4:pallas_time_share.tokens": 47.13473844207702,
    "mistral7b.fsdp4:peak_hbm_gb.tokens": 9.0,
    "mistral7b.fsdp4:setup_s": 30.0,
    "mistral7b.fsdp4:stall_share.tokens": 0.9900990099010021,
    "mistral7b.fsdp4:state_init_s": 2.0,
    "mistral7b.fsdp4:steady_rate.tokens": 100.0,
    "mistral7b.fsdp4:train_tokens_per_s": 99.009900990099,
    "mistral7b.tokens4k:device_idle_share.tokens": 0.2906199443978852,
    "mistral7b.tokens4k:gang_boot_s": 1.0,
    "mistral7b.tokens4k:ingest_wait_share.tokens": 1.65016501650165,
    "mistral7b.tokens4k:model_mfu.tokens": 0.05025883298989797,
    "mistral7b.tokens4k:pallas_time_share.tokens": 47.13473844207702,
    "mistral7b.tokens4k:peak_hbm_gb.tokens": 9.0,
    "mistral7b.tokens4k:setup_s": 30.0,
    "mistral7b.tokens4k:stall_share.tokens": 0.9900990099010021,
    "mistral7b.tokens4k:state_init_s": 2.0,
    "mistral7b.tokens4k:steady_rate.tokens": 100.0,
    "mistral7b.tokens4k:train_tokens_per_s": 99.009900990099,
    "resnet50.ingest:device_idle_share.images": 0.2906199443978852,
    "resnet50.ingest:gang_boot_s": 1.0,
    "resnet50.ingest:ingest_wait_share.images": 1.65016501650165,
    "resnet50.ingest:model_mfu.images": 0.05025883298989797,
    "resnet50.ingest:peak_hbm_gb.images": 9.0,
    "resnet50.ingest:setup_s": 30.0,
    "resnet50.ingest:stall_share.images": 0.9900990099010021,
    "resnet50.ingest:state_init_s": 2.0,
    "resnet50.ingest:steady_rate.images": 100.0,
    "resnet50.ingest:train_images_per_s": 99.009900990099,
    "resnet50.resident:device_idle_share.images": 0.2906199443978852,
    "resnet50.resident:gang_boot_s": 1.0,
    "resnet50.resident:ingest_wait_share.images": 1.65016501650165,
    "resnet50.resident:model_mfu.images": 0.05025883298989797,
    "resnet50.resident:peak_hbm_gb.images": 9.0,
    "resnet50.resident:setup_s": 30.0,
    "resnet50.resident:stall_share.images": 0.9900990099010021,
    "resnet50.resident:state_init_s": 2.0,
    "resnet50.resident:steady_rate.images": 100.0,
    "resnet50.resident:train_images_per_s": 99.009900990099,
}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_metric_reads_the_old_fixture_as_it_did(cell):
    with open(os.path.join(FIXTURES, "v5e_mistral7b_l2_two_steps.json")) as f:
        reduced = trace.reduce(json.load(f))
    loaded = spec.load_cell(ROOT, cell)
    run = dict(fake_summary(loaded), chips=loaded["workload"]["chips"],
               trace=reduced)
    got = {}
    for kind in ("per_layer", "end_to_end"):
        for name, value in spec.metric_lines(ROOT, BENCH, cell, kind, run).items():
            got[f"{cell}:{name}"] = value["value"]
    want = {k: v for k, v in OLD_FIXTURE_READINGS.items()
            if k.startswith(cell + ":")}
    assert got == want  # to the last digit


# ----------------------------------- the metrics that wait for their entry

ELEVEN = [
    "attention_time_share.tokens", "backward_time_share.images",
    "backward_time_share.tokens", "bn_time_share.images",
    "flash_bwd_dkv_roofline.tokens", "flash_bwd_dq_roofline.tokens",
    "flash_fwd_roofline.tokens", "lm_head_ce_time_share.tokens",
    "optimizer_time_share.images", "optimizer_time_share.tokens",
    "recompute_time_share.tokens",
]


def test_the_eleven_wait():
    assert sorted(waiting()) == ELEVEN


@pytest.mark.parametrize("name", ELEVEN)
def test_waiting_metric_is_ready_for_benchmark_json(name):
    held = waiting()[name]
    entry = held["entry"]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert entry["name"] == name and entry["unit"] == "%"
    assert entry["source"] == "device_trace"
    assert entry["better"] == ("higher" if name.endswith("_roofline.tokens")
                               else "lower")
    assert name not in {m["name"] for m in BENCH["per_layer"]}
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    moved = spec.by_name(BENCH["end_to_end"], entry["moves"], "metric")
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= set(moved["workloads"])
    assert hasattr(spec.load_code(ROOT, "readers", held["reader"]), "read")
    params = held["params"]
    if held["reader"] == "scope_share":
        assert set(params) <= {"phase", "scope"} and params
        assert params.get("phase", "forward") in scopes.PHASES
        assert params.get("scope", "bn") in scopes.SCOPES + scopes.KERNELS
        return
    # a kernel's shapes are the configuration's and the mix's, in every cell
    assert params["kernel"] in scopes.KERNELS
    for cell in entry["workloads"]:
        loaded = spec.load_cell(ROOT, cell)
        config, traffic = loaded["config"], loaded["traffic"]
        assert params["n_heads"] == config["n_heads"]
        assert params["head_dim"] == config["d_model"] // config["n_heads"]
        assert params["seq_len"] == traffic["units_per_row"] <= config["max_seq_len"]
        assert traffic["batch_rows"] % cells[cell]["chips"] == 0
