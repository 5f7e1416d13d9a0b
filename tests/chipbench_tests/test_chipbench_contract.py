"""BENCHMARK.json against the contract's form, the files it names, and the
last line the command prints."""

import json
import os
import re
import subprocess
import sys
import time

import pytest

from chipbench import run, spec
from chipbench_tiny import fake_reduced, fake_summary

ROOT = spec.ROOT
BENCH = spec.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "-m", "chipbench.run"]
    assert all(one_line(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in BENCH["paths"])
    assert isinstance(BENCH["run_seconds"], int)
    # 2 + 14 x cells runs of run_seconds + 60 s, 180 s a cell to compile and
    # 1200 s spare must fit 43200 s with the full 24 cells
    cells = 24
    assert ((2 + 14 * cells) * (BENCH["run_seconds"] + 60) + cells * 180
            + 1200) <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert one_line(config["source"]) and one_line(config["why"])
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    held = spec.read_json(ROOT, config["file"])
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and key in held
        # a width is never reduced
        assert not re.search(r"(_dim|_rank|hidden_size|intermediate|head)", key)
    assert held["reduced"] == config["reduced"]
    assert {"family", "source", "deployment", "assumed", "check"} <= set(held)
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


def test_config_files_are_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)


def test_published_widths_are_kept():
    for entry in BENCH["configs"]:
        held = spec.read_json(ROOT, entry["file"])
        source = held.get("source_config")
        if not source:
            continue
        assert held["d_model"] == source["hidden_size"]
        assert held["d_ff"] == source["intermediate_size"]
        assert held["n_heads"] == source["num_attention_heads"]
        assert held["n_kv_heads"] == source["num_key_value_heads"]
        assert held["vocab_size"] == source["vocab_size"]
        assert held["norm_eps"] == source["rms_norm_eps"]
        assert held["rope_theta"] == source["rope_theta"]
        assert held["tied_embeddings"] == source["tie_word_embeddings"]
        assert held["n_layers"] == held["num_hidden_layers"] < source[
            "num_hidden_layers"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    loaded = spec.load_cell(ROOT, cell["name"])
    assert loaded["traffic"]["kind"] in ("ingest", "resident")
    family = loaded["config"]["family"]
    for kind in ("loops", "reference"):
        assert os.path.exists(
            os.path.join(ROOT, "chipbench", kind, family + ".py"))


def test_cells_are_unique_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(set(CELLS))
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(pairs) // 4)


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = ({"name", "unit", "better", "bound", "source"} if end_to_end
            else {"name", "unit", "better", "source", "layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert one_line(metric["layer"])
        moved = spec.by_name(BENCH["end_to_end"], metric["moves"], "metric")
        for cell in metric.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS)
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    held = spec.read_json(ROOT, "chipbench", "metrics", metric["name"] + ".json")
    assert hasattr(spec.load_code(ROOT, "readers", held["reader"]), "read")


def test_metric_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    setup = spec.by_name(BENCH["end_to_end"], "setup_s", "metric")
    assert "workloads" not in setup and setup["bound"] <= 0.1
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128


def test_layers_are_spelled_one_way():
    by_start = {}
    for metric in BENCH["per_layer"]:
        by_start.setdefault(metric["layer"].split(":")[0], set()).add(
            metric["layer"])
    assert all(len(v) == 1 for v in by_start.values())


def test_run_py_names_no_cell_model_or_metric():
    text = open(os.path.join(ROOT, "chipbench", "run.py")).read()
    text += open(os.path.join(ROOT, "chipbench", "loop.py")).read()
    names = CELLS + [c["name"] for c in BENCH["configs"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += ["resnet", "mistral", "transformer"]
    assert [n for n in names if n in text] == []


@pytest.mark.parametrize("traced", [0, 1], ids=["trace0", "trace1"])
@pytest.mark.parametrize("name", CELLS)
def test_last_line_key_and_metric_sets(name, traced):
    cell = spec.load_cell(ROOT, name)
    reduced = fake_reduced(cell["workload"]["chips"]) if traced else None
    line = run.last_line(ROOT, BENCH, cell, fake_summary(cell), reduced)
    text = json.dumps(line)
    assert "\n" not in text and json.loads(text) == line
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (keys | {"breakdown"} if traced else keys)
    device_keys = {"platform", "kind", "count", "memory_peak_bytes"}
    if traced:
        device_keys |= {"busy_s", "window_s"}
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in line["breakdown"].values())
    assert device_keys <= set(line["device"])
    kind = "per_layer" if traced else "end_to_end"
    want = {m["name"]: m["unit"] for m in spec.metrics_of(BENCH, name, kind)}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(set(v) == {"value", "unit"} and isinstance(v["value"], float)
               for v in line["metrics"].values())
    assert line["correct"] is True and line["attempted"] == 12
    if not traced:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    else:
        for share in ("model_mfu", "pallas_time_share"):
            for key, v in line["metrics"].items():
                if key.startswith(share):
                    assert 0 <= v["value"] <= 100


@pytest.mark.parametrize("fault,reason", [
    ({"compiles_in_window": 1}, "compilation"),
    ({"steps_failed": 2}, "non-finite"),
    ({"reference": {"agrees": False}}, "reference"),
    ({"device": {"platform": "cpu", "kind": "cpu", "count": 1}}, "ran on"),
    ({"chunks": []}, "no chunk"),
])
def test_a_fault_makes_the_run_incorrect(fault, reason):
    cell = spec.load_cell(ROOT, "resnet50.resident")
    summary = {**fake_summary(cell), **fault}
    reasons = run.verdict(summary, cell)
    assert len(reasons) == 1 and reason in reasons[0]


def test_uneven_state_makes_the_sharded_cell_incorrect():
    cell = spec.load_cell(ROOT, "mistral7b.fsdp4")
    summary = fake_summary(cell)
    assert run.verdict(summary, cell) == []
    summary["state_bytes"]["per_device"] = [400, 0, 0, 0]
    assert "state bytes" in run.verdict(summary, cell)[0]


@pytest.mark.parametrize("name", ["resnet50.ingest", "mistral7b.fsdp4"])
def test_command_fails_at_once_without_a_chip(name):
    env = {k: v for k, v in os.environ.items()
           if k not in ("TPU_VISIBLE_CHIPS", "RAY_TPU_CHIPS", "BENCH_RUN")}
    env["BENCH_RUN"] = "ignored"
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", name, "--seed",
         str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT,
    )
    assert out.returncode not in (0, None)
    assert time.monotonic() - t0 < 60
    for line in out.stdout.splitlines():  # info lines only, no result
        assert "correct" not in json.loads(line)
    assert "TPU chip(s) detected" in out.stderr


def test_unknown_cell_fails_without_a_result():
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "no.such",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert out.returncode != 0 and out.stdout == ""
    assert "no workload named" in out.stderr
