"""`chipbench/hostspans.py` on a recorded trace: two traced chunks of
`lfm2moe.tokens8k` on a v5e (my chip run, PR 36), the device's operations
merged into runs. The program's spans lie in the host planes of the file
that holds the device's operations, each on its thread's line."""

import json
import os

import pytest

from chipbench import hostspans, loop, spec, trace

FIXTURE = os.path.join(spec.ROOT, "chipbench", "fixtures",
                       "v5e_lfm2moe_host_threads.json")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def holder(recorded, span):
    held = [t for t, events in recorded["host_threads"].items()
            if any(e[0] == span for e in events)]
    return held


def test_each_span_lies_on_its_thread_s_line(recorded):
    (loop_thread,) = holder(recorded, "chunk_result_wait")
    (prefetch,) = holder(recorded, "data.batch_produce")
    fetchers = holder(recorded, "data.block_fetch")
    assert loop_thread != prefetch and len(fetchers) == 2
    assert not {loop_thread, prefetch} & set(fetchers)
    names = lambda t: {e[0] for e in recorded["host_threads"][t]}  # noqa: E731
    assert names(loop_thread) == set(loop.SPANS) | {"data.batch_wait",
                                                    "train.report"}
    assert names(prefetch) == {"data.batch_produce", "data.batch_assemble",
                               "data.finalize"}
    every = [e for events in recorded["host_threads"].values() for e in events]
    assert sorted(map(tuple, every)) == sorted(map(tuple, recorded["host_spans"]))


def test_the_program_s_report_lies_inside_the_loop_s(recorded):
    (loop_thread,) = holder(recorded, "report")
    events = recorded["host_threads"][loop_thread]
    outer = [(s, s + d) for n, s, d in events if n == "report"]
    inner = [(s, s + d) for n, s, d in events if n == "train.report"]
    assert len(inner) == len(outer) == 2
    for a, b in inner:
        assert any(c <= a and b <= d for c, d in outer)
    # and on the device's time base: inside the traced window of its operations
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    assert ops[0][1] < inner[0][0] < ops[-1][1] + ops[-1][2]


def test_idle_gaps_by_thread(recorded):
    split = hostspans.attribute(recorded)
    assert set(split) == set(recorded["host_threads"])
    totals = {round(sum(causes.values()), 9) for causes in split.values()}
    assert len(totals) == 1  # every thread accounts for the same idle time
    (idle_s,) = totals
    assert idle_s == pytest.approx(7.951e-3, rel=1e-3)
    (loop_thread,) = holder(recorded, "chunk_result_wait")
    (prefetch,) = holder(recorded, "data.batch_produce")
    ms = {k: round(v * 1e3, 3) for k, v in split[loop_thread].items()}
    assert ms == {
        "chunk_result_wait": 3.073, "step_dispatch": 2.041,
        "next_batch": 1.175, "data.batch_wait": 0.103,
        "train.report": 1.175, "report": 0.045,
        trace.NO_SPAN: 0.212, trace.INSIDE_PROGRAM: 0.128}
    assert round(split[prefetch]["data.batch_produce"] * 1e3, 3) == 2.884
    # the innermost span owns a gap: the loop's own split, which the ledger
    # keeps, is the sum over what lies inside each of its four
    reduced = dict(trace.reduce({
        "devices": recorded["devices"],
        "host_spans": [e for e in recorded["host_spans"] if e[0] in loop.SPANS],
    })["idle_gaps"])
    assert reduced["report"] == pytest.approx(
        split[loop_thread]["report"] + split[loop_thread]["train.report"])
    assert reduced["next_batch"] == pytest.approx(
        split[loop_thread]["next_batch"] + split[loop_thread]["data.batch_wait"])


def test_a_recording_is_already_merged(recorded):
    assert hostspans.merged_runs(recorded)["devices"] == recorded["devices"]
    raw = {"devices": {"/device:TPU:0": {
        "ops": [["a", 0.0, 10.0], ["b", 10.5, 5.0], ["c", 2000.0, 5.0]],
        "modules": [["jit", 0.0, 2005.0]]}}, "host_spans": [], "host_threads": {}}
    assert hostspans.merged_runs(raw)["devices"]["/device:TPU:0"]["ops"] == [
        ["ops", 0.0, 15.5], ["ops", 2000.0, 5.0]]


def test_the_program_s_names_are_the_documented_ones():
    text = open(os.path.join(spec.ROOT, "docs", "observability.md")).read()
    for name in hostspans.PROGRAM_SPANS:
        assert f"`{name}`" in text
    assert not set(hostspans.PROGRAM_SPANS) & set(loop.SPANS)
