"""The five per-layer metrics of set-up that read the program's spans of
what comes before a program runs (`jax.trace`, `jax.lower`, `pallas.trace`,
`train.before_first_program`, `process.before_init`): each through the
reader `runtime_span_seconds`, in every cell of `BENCHMARK.json`."""

import pytest

from chipbench import run, spec
from chipbench_tiny import fake_reduced, fake_summary

ROOT = spec.ROOT
BENCH = spec.load_benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]  # as the file has them
GANG = "entry and gang: ray_tpu.init, train/_backend_executor, _worker_group"
SETUP = "model set-up: models/*, make_train_step state"
# name: (layer, table, span, the reading of BLOCK)
FIVE = {
    "trace_s": (SETUP, "setup", "jax.trace", 9.0),
    "lower_s": (SETUP, "setup", "jax.lower", 3.5),
    "pallas_trace_s": (SETUP, "setup", "pallas.trace", 3.0),
    "before_first_program_s": (
        GANG, "setup", "train.before_first_program", 6.25),
    "before_init_s": (GANG, "driver", "process.before_init", 4.5),
}
# {span: [count, seconds, longest_seconds, time of the longest]}
BLOCK = {
    "total": {"jax.trace": [2100, 9.75, 1.0, 1.0],
              "jax.lower": [12, 4.0, 0.9, 1.0],
              "pallas.trace": [49, 3.25, 0.2, 1.0],
              "train.before_first_program": [1, 6.25, 6.25, 1.0],
              "jax.compile": [12, 14.5, 9.0, 1.0]},
    # a shape traced anew in the steady state is not set-up's
    "since_first_report": {"jax.trace": [3, 0.75, 0.5, 2.0],
                           "jax.lower": [1, 0.5, 0.5, 2.0],
                           "pallas.trace": [1, 0.25, 0.25, 2.0]},
    "interval": {}, "counters": {}, "rusage": {},
    "driver": {"init": [1, 0.75, 0.75, 0.5],
               "process.before_init": [1, 4.5, 4.5, 0.4],
               "import.ray_tpu": [1, 0.5, 0.5, 0.4]},
}


def record(cell, with_block):
    loaded = spec.load_cell(ROOT, cell)
    made = dict(fake_summary(loaded), chips=loaded["workload"]["chips"],
                trace=None)
    if with_block:
        made["ray_tpu_runtime"] = BLOCK
    return made


@pytest.mark.parametrize("name", sorted(FIVE))
def test_reader_on_a_record_with_the_block(name):
    got = spec.read_metric(ROOT, name, record(CELLS[0], True))
    assert got == pytest.approx(FIVE[name][3])


@pytest.mark.parametrize("name", sorted(FIVE))
def test_reader_on_a_record_without_the_block(name):
    """A parent older than the spans: 0, as a float, and nothing raised."""
    got = spec.read_metric(ROOT, name, record(CELLS[-1], False))
    assert isinstance(got, float) and got == 0.0
    older = dict(record(CELLS[-1], False), ray_tpu_runtime={
        "total": {"jax.compile": [3, 1.0, 1.0, 1.0]},
        "since_first_report": {}, "driver": {"init": [1, 0.1, 0.1, 0.1]}})
    assert spec.read_metric(ROOT, name, older) == 0.0


@pytest.mark.parametrize("name", sorted(FIVE))
def test_entry(name):
    layer, table, span, _ = FIVE[name]
    entry = spec.by_name(BENCH["per_layer"], name, "metric")
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": "setup_s", "workloads": CELLS}
    assert layer in {m["layer"] for m in BENCH["per_layer"]
                     if m["name"] not in FIVE}
    held = spec.read_json(ROOT, "chipbench", "metrics", name + ".json")
    assert held["reader"] == "runtime_span_seconds"
    assert held["params"] == {"table": table, "span": span}
    assert span in held["what"]


def test_what_each_file_says_of_the_others():
    what = {name: spec.read_json(ROOT, "chipbench", "metrics", name + ".json")[
        "what"] for name in FIVE}
    assert "inside trace_s" in what["pallas_trace_s"]
    assert "self time" in what["trace_s"] and "self time" in what["lower_s"]
    for name in ("before_first_program_s", "before_init_s"):
        assert "part of setup_unnamed_s" in what[name]


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_line_reports_the_five(cell):
    loaded = spec.load_cell(ROOT, cell)
    summary = dict(fake_summary(loaded), ray_tpu_runtime=BLOCK)
    line = run.last_line(ROOT, BENCH, loaded, summary,
                         fake_reduced(loaded["workload"]["chips"]))
    for name, (_, _, _, want) in FIVE.items():
        assert line["metrics"][name] == {"value": want, "unit": "s"}
    untraced = run.last_line(ROOT, BENCH, loaded, summary, None)
    assert not set(FIVE) & set(untraced["metrics"])
    # the parent's line in this PR's check: its program has no such span
    bare = dict(fake_summary(loaded))
    line = run.last_line(ROOT, BENCH, loaded, bare,
                         fake_reduced(loaded["workload"]["chips"]))
    assert [line["metrics"][name]["value"] for name in FIVE] == [0.0] * 5
