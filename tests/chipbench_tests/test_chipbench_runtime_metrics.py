"""The five per-layer metrics that read the program's own account of its
time: the block `ray_tpu_runtime` in the last report's metrics, which
`run.last_line` hands to a reader as part of the run's record."""

import os

import pytest

from chipbench import run, spec
from chipbench_tiny import fake_reduced, fake_summary

ROOT = spec.ROOT
BENCH = spec.load_benchmark(ROOT)
CELLS = ["olmoe.tokens4k", "lfm2moe.tokens8k", "dsv2lite.tokens8k"]
GANG = "entry and gang: ray_tpu.init, train/_backend_executor, _worker_group"
ENTRIES = {
    "cluster_init_s": ("s", "program_span", GANG, "setup_s"),
    "compile_s": ("s", "program_span",
                  "model set-up: models/*, make_train_step state", "setup_s"),
    "first_batch_s": ("s", "program_span", "ingest: ray_tpu.data", "setup_s"),
    "setup_unnamed_s": ("s", "host_clock", GANG, "setup_s"),
    "ingest_produce_share.tokens": (
        "%", "program_span", "ingest: ray_tpu.data", "train_tokens_per_s"),
}
STAGES = {"gang_boot": 1.5, "reference_check": 9.0, "state_init": 2.0,
          "first_batch": 1.0, "compile": 12.0, "warmup": 0.5, "setup": 40.0}
# {span: [count, seconds, longest_seconds, time of the longest]}
BLOCK = {
    "total": {"jax.compile": [9, 14.5, 9.0, 1.0],
              "data.pipeline_start": [2, 1.25, 1.0, 2.0],
              "data.batch_produce": [60, 3.0, 0.9, 2.0]},
    "since_first_report": {"jax.compile": [1, 0.5, 0.5, 3.0],
                           "data.pipeline_start": [1, 0.25, 0.25, 4.0],
                           "data.batch_produce": [40, 0.2, 0.01, 5.0]},
    "interval": {}, "counters": {}, "rusage": {},
    "driver": {"init": [1, 0.75, 0.75, 0.5], "init.gcs": [1, 0.25, 0.25, 0.4]},
}


def record(cell, with_block):
    loaded = spec.load_cell(ROOT, cell)
    made = dict(fake_summary(loaded), chips=1, trace=None)
    if with_block:
        made["stages"] = dict(STAGES)
        made["ray_tpu_runtime"] = BLOCK
    return made


@pytest.mark.parametrize("name,want", [
    ("cluster_init_s", 0.75),
    ("compile_s", 14.0),  # the steady state's recompile is not set-up's
    ("first_batch_s", 1.0),  # nor the second epoch's restart
    ("setup_unnamed_s", 40.0 - 26.0 - 0.75),
    # 0.2 s over 40 batches, over a step of 0.5 s: 1 %
    ("ingest_produce_share.tokens", 1.0),
])
def test_reader_on_a_record_with_the_block(name, want):
    assert spec.read_metric(ROOT, name, record(CELLS[0], True)) == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    ("cluster_init_s", 0.0), ("compile_s", 0.0), ("first_batch_s", 0.0),
    ("setup_unnamed_s", 27.0), ("ingest_produce_share.tokens", 0.0),
])
def test_reader_on_a_record_without_the_block(name, want):
    got = spec.read_metric(ROOT, name, record(CELLS[0], False))
    assert isinstance(got, float) and got == want


def test_the_named_parts_add_up_to_set_up():
    made = record(CELLS[1], True)
    named = sum(v for k, v in STAGES.items() if k != "setup")
    named += spec.read_metric(ROOT, "cluster_init_s", made)
    named += spec.read_metric(ROOT, "setup_unnamed_s", made)
    assert named == pytest.approx(STAGES["setup"])


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_entry(name):
    entry = spec.by_name(BENCH["per_layer"], name, "metric")
    unit, source, layer, moves = ENTRIES[name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": CELLS}
    assert BENCH["per_layer"].index(entry) >= len(BENCH["per_layer"]) - 5


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_line_reports_the_five(cell):
    loaded = spec.load_cell(ROOT, cell)
    summary = dict(fake_summary(loaded), stages=dict(STAGES),
                   ray_tpu_runtime=BLOCK)
    line = run.last_line(ROOT, BENCH, loaded, summary, fake_reduced(1))
    for name, (unit, *_) in ENTRIES.items():
        assert line["metrics"][name]["unit"] == unit
    assert line["metrics"]["cluster_init_s"]["value"] == 0.75
    untraced = run.last_line(ROOT, BENCH, loaded, summary, None)
    assert not set(ENTRIES) & set(untraced["metrics"])


def test_the_images_share_waits():
    """Under `awaits`, not `entry`: `test_the_eleven_wait` counts the files
    that carry `entry`. Its entry joins BENCHMARK.json with the other four
    cells of the five above."""
    name = "ingest_produce_share.images"
    held = spec.read_json(ROOT, "chipbench", "metrics", name + ".json")
    tokens = spec.read_json(
        ROOT, "chipbench", "metrics", "ingest_produce_share.tokens.json")
    assert held["reader"] == tokens["reader"] and held["params"] == tokens["params"]
    entry = held["awaits"]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert entry["name"] == name and entry["moves"] == "train_images_per_s"
    assert entry["layer"] == ENTRIES["ingest_produce_share.tokens"][2]
    assert name not in {m["name"] for m in BENCH["per_layer"]}
    moved = spec.by_name(BENCH["end_to_end"], entry["moves"], "metric")
    assert set(entry["workloads"]) <= set(moved["workloads"])


def test_no_new_name_occurs_in_run_py_or_loop_py():
    text = open(os.path.join(ROOT, "chipbench", "run.py")).read()
    text += open(os.path.join(ROOT, "chipbench", "loop.py")).read()
    names = list(ENTRIES) + ["ingest_produce_share.images"]
    assert [n for n in names if n in text] == []
