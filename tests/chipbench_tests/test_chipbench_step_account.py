"""The six per-layer metrics that read what the program's steps say of
themselves: the counters `moe.*` and `train.steps_read` and the span
`train.step`, in the block `ray_tpu_runtime` of the last report's metrics
(`ray_tpu/train/_runtime.py` `_fold_steps`, `ray_tpu/util/tracing.py`
`Step`), which `run.last_line` hands to a reader as part of the run's
record."""

import importlib
import os

import pytest

from chipbench import run, spec
from chipbench.readers import runtime_counter_ratio
from chipbench_tiny import fake_reduced, fake_summary

ROOT = spec.ROOT
BENCH = spec.load_benchmark(ROOT)
KERNELS = "kernels: ops/flash_attention.py, ops/fused.py"
SHARE = ["lfm2moe.tokens8k", "dsv2lite.tokens8k", "nemotron3nano.tokens8k",
         "lagunaxs2.tokens8k", "keyevl2.tokens16k", "mellum2.ep4",
         "solaropen2.tokens8k"]
ELEVEN = ["mistral7b.tokens4k", "mistral7b.fsdp4", "olmoe.tokens4k", *SHARE,
          "evabyte.tokens8k"]
# their own tests pin the exact set of these cells' per-layer metrics
PINNED = {"kimilinear.tokens16k", "phi4flash.tokens16k", "ouro.tokens16k"}
# name: (unit, better, source, layer, workloads)
ENTRIES = {
    "moe_held_fill.tokens": ("%", "higher", "program_counter", KERNELS, SHARE),
    "moe_extra_chunk_share.tokens": (
        "%", "lower", "program_counter", KERNELS, SHARE),
    "moe_dropped_slot_share.tokens": (
        "%", "lower", "program_counter", KERNELS, SHARE),
    "moe_load_max_over_mean.tokens": (
        "x", "lower", "program_counter", KERNELS, ["olmoe.tokens4k", *SHARE]),
    "moe_chip_load_max_over_mean.tokens": (
        "x", "lower", "program_counter", KERNELS, ["mellum2.ep4"]),
    "step_dispatch_share.tokens": (
        "%", "lower", "program_span", "train loop as a whole", ELEVEN),
}
# a run of 12 steps of 4 routed layers over 4 chips, 8 of them since the
# first report
BLOCK = {
    "total": {"train.step": [12, 1.2, 1.0, 1.0]},
    "since_first_report": {"train.step": [8, 0.02, 0.005, 3.0]},
    "interval": {},
    "counters": {"train.steps_read": 12, "moe.layer_steps": 48,
                 "moe.held_rows": 4800, "moe.buffer_rows": 9600},
    "counters_since_first_report": {
        "train.steps_read": 8, "moe.layer_steps": 32,
        "moe.fullest_expert_slots": 4800, "moe.even_expert_slots": 3200.0,
        "moe.held_slots": 6000, "moe.dropped_slots": 3,
        "moe.held_rows": 5997, "moe.buffer_rows": 8000,
        "moe.extra_chunk_layer_steps": 2,
        "moe.chip_load_max_over_mean_sum": 10.0},
    "readings": {}, "steps": [], "rusage": {},
}
WANT = {
    "moe_held_fill.tokens": 100 * 5997 / 8000,
    "moe_extra_chunk_share.tokens": 100 * 2 / 32,
    "moe_dropped_slot_share.tokens": 100 * 3 / 6000,
    "moe_load_max_over_mean.tokens": 1.5,
    "moe_chip_load_max_over_mean.tokens": 1.25,
    # 0.02 s over 8 steps, over a step of 0.5 s
    "step_dispatch_share.tokens": 0.5,
}


def record(cell, block):
    made = dict(fake_summary(spec.load_cell(ROOT, cell)), chips=1, trace=None)
    if block is not None:
        made["ray_tpu_runtime"] = block
    return made


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_on_a_record_with_the_block(name):
    got = spec.read_metric(ROOT, name, record("mellum2.ep4", BLOCK))
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(ENTRIES))
@pytest.mark.parametrize("block", [
    None,  # no block at all
    {k: v for k, v in BLOCK.items() if k in ("total", "counters")},  # older
    dict(BLOCK, since_first_report={}, counters_since_first_report={
        "train.steps_read": 0, "moe.layer_steps": 0}),  # one report
], ids=["no_block", "an_older_program", "nothing_counted"])
def test_reader_reads_0_where_there_is_nothing_to_read(name, block):
    got = spec.read_metric(ROOT, name, record("mellum2.ep4", block))
    assert isinstance(got, float) and got == 0.0


def test_the_ratio_is_of_sums_of_the_named_counters():
    made = record("olmoe.tokens4k", BLOCK)
    read = runtime_counter_ratio.read
    assert read(made, {"counters": ["moe.held_rows", "moe.dropped_slots"],
                       "over": ["moe.held_slots"]}) == pytest.approx(1.0)
    assert read(made, {"counters": ["moe.held_rows"],
                       "over": ["moe.held_slots", "moe.buffer_rows"],
                       "scale": 100}) == pytest.approx(100 * 5997 / 14000)
    # a counter the block lacks counts 0, above the line and below it
    assert read(made, {"counters": ["moe.no_such"], "over": ["moe.held_slots"]}
                ) == 0.0
    assert read(made, {"counters": ["moe.held_rows"], "over": ["moe.no_such"]}
                ) == 0.0
    # the steady state's, not the session's: `counters` is not read
    assert read(made, {"counters": ["moe.layer_steps"],
                       "over": ["train.steps_read"]}) == pytest.approx(4.0)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_metric_s_file(name):
    held = spec.read_json(ROOT, "chipbench", "metrics", name + ".json")
    assert set(held) == {"what", "reader", "params"}
    assert len(held["what"]) > 80
    if name.startswith("moe_"):
        assert held["reader"] == "runtime_counter_ratio"
        assert set(held["params"]) <= {"counters", "over", "scale"}
        named = held["params"]["counters"] + held["params"]["over"]
        assert all(n.startswith(("moe.", "train.")) and n in held["what"]
                   for n in named)
        assert held["params"].get("scale", 1) == (
            100 if ENTRIES[name][0] == "%" else 1)
    else:
        assert held["reader"] == "runtime_span_share"
        assert held["params"] == {"span": "train.step"}
    assert hasattr(spec.load_code(ROOT, "readers", held["reader"]), "read")


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_entry(name):
    entry = spec.by_name(BENCH["per_layer"], name, "metric")
    unit, better, source, layer, cells = ENTRIES[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "train_tokens_per_s", "workloads": cells}
    assert not PINNED & set(entry["workloads"])
    moved = spec.by_name(BENCH["end_to_end"], "train_tokens_per_s", "metric")
    assert set(entry["workloads"]) <= set(moved["workloads"])
    # the layer is one the benchmark already names, letter for letter
    others = {m["layer"] for m in BENCH["per_layer"] if m["name"] not in ENTRIES}
    assert layer in others


def test_the_six_are_the_list_s_last_and_in_this_order():
    assert [m["name"] for m in BENCH["per_layer"]][-6:] == list(ENTRIES)
    share = spec.by_name(BENCH["per_layer"], "moe_sum_time_share.tokens",
                         "metric")
    assert [c for c in share["workloads"] if c not in PINNED] == SHARE
    tokens = spec.by_name(BENCH["end_to_end"], "train_tokens_per_s", "metric")
    assert [c for c in tokens["workloads"] if c not in PINNED] == ELEVEN


@pytest.mark.parametrize("cell", ["olmoe.tokens4k", "solaropen2.tokens8k",
                                  "mellum2.ep4", "mistral7b.tokens4k"])
def test_a_traced_line_reports_the_cell_s_own(cell):
    loaded = spec.load_cell(ROOT, cell)
    summary = dict(fake_summary(loaded), ray_tpu_runtime=BLOCK)
    line = run.last_line(ROOT, BENCH, loaded, summary,
                         fake_reduced(loaded["workload"]["chips"]))
    want = {n for n, (*_, cells) in ENTRIES.items() if cell in cells}
    assert {n for n in ENTRIES if n in line["metrics"]} == want
    for name in want:
        assert line["metrics"][name] == {
            "value": pytest.approx(WANT[name]), "unit": ENTRIES[name][0]}
    # from a program older than the counters: every one reads 0, none raises
    older = dict(fake_summary(loaded))
    line = run.last_line(ROOT, BENCH, loaded, older,
                         fake_reduced(loaded["workload"]["chips"]))
    assert {line["metrics"][n]["value"] for n in want} == {0.0}
    untraced = run.last_line(ROOT, BENCH, loaded, summary, None)
    assert not set(ENTRIES) & set(untraced["metrics"])


@pytest.mark.parametrize("module", [
    "test_chipbench_kimi_linear", "test_chipbench_phi4flash",
    "test_chipbench_ouro"])
def test_the_pinned_cells_lists_stand(module):
    """The three cells whose tests pin their exact set of per-layer
    metrics report none of the six, and those tests still pass."""
    importlib.import_module(module).test_the_cell_and_its_lists()


def test_no_new_name_occurs_in_run_py_or_loop_py():
    text = open(os.path.join(ROOT, "chipbench", "run.py")).read()
    text += open(os.path.join(ROOT, "chipbench", "loop.py")).read()
    assert [n for n in ENTRIES if n in text] == []
