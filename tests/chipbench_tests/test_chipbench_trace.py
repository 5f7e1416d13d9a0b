"""The reduction from a profiler trace to numbers: on made-up events whose
answers are known by hand, on the small trace recorded on the chip that is
kept beside the code, and on a trace taken here on the CPU for the reading
of the file format."""

import json
import os

import pytest

from chipbench import spec, trace

FIXTURE = os.path.join(spec.ROOT, "chipbench", "fixtures",
                       "v5e_mistral7b_l2_two_steps.json")


def merged_length(intervals):
    """Union of intervals by the textbook sweep, as a second opinion."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def test_self_segments_of_nested_events():
    events = [["while", 0, 100], ["a", 10, 20], ["b", 40, 30],
              ["inner", 45, 5], ["x", 120, 10]]
    segments = trace.self_segments(events)
    assert segments == [
        (0, 10, "while"), (10, 30, "a"), (30, 40, "while"), (40, 45, "b"),
        (45, 50, "inner"), (50, 70, "b"), (70, 100, "while"), (120, 130, "x")]
    by_name = trace.seconds_by_name(segments)
    assert by_name["while"] == pytest.approx(50e-9)
    assert by_name["b"] == pytest.approx(25e-9)
    assert sum(by_name.values()) == pytest.approx(110e-9)


def test_overlap_without_nesting_is_clipped_not_counted_twice():
    segments = trace.self_segments([["a", 0, 10], ["b", 5, 10]])
    assert sum(e - s for s, e, _ in segments) == merged_length([(0, 10), (5, 15)])


def test_gaps_and_their_attribution():
    segments = trace.self_segments([["op", 0, 100], ["op", 120, 10]])
    idle = trace.gaps(segments, (0, 150))
    assert idle == [(100, 120), (130, 150)]
    by_cause = trace.attribute_gaps(
        idle, modules=[["jit_step", 0, 105]],
        host_spans=[["report", 100, 15], ["next_batch", 125, 30]])
    # 5 ns of the first gap lie inside the module, the other 15 under report;
    # the second gap lies under next_batch
    assert by_cause == pytest.approx({
        trace.INSIDE_PROGRAM: 5e-9, "report": 15e-9, "next_batch": 20e-9})
    bare = trace.attribute_gaps([(0, 10)], [], [])
    assert bare == {trace.NO_SPAN: pytest.approx(10e-9)}


def two_devices():
    ops0 = [["fusion.1", 0, 400], ["k.2 [tpu_custom_call]", 400, 200],
            ["all-gather.3", 600, 100], ["fusion.4", 700, 200]]
    ops1 = [["fusion.1", 0, 400], ["k.2 [tpu_custom_call]", 400, 200],
            ["all-gather.3", 600, 300]]  # waits longer for its peer
    return {
        "devices": {
            "/device:TPU:0": {"ops": ops0, "modules": [["jit_step", 0, 900]]},
            "/device:TPU:1": {"ops": ops1, "modules": [["jit_step", 0, 900]]},
        },
        "host_spans": [["chunk_result_wait", 0, 950], ["report", 950, 50]],
    }


def test_reduce_busy_idle_kernels_and_exposed_collectives():
    reduced = trace.reduce(two_devices())
    assert reduced["n_devices"] == 2
    assert reduced["window_s"] == pytest.approx(1000e-9)
    assert reduced["busy_s"] == pytest.approx(900e-9)  # both busy 0..900
    idle_share = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle_share == pytest.approx(0.1)
    assert trace.share(reduced, [r"\[tpu_custom_call\]"], "busy") == (
        pytest.approx(400 / 1800))
    # exposed collective time: 100 ns on one device, 300 on the other
    assert trace.share(reduced, ["^all-gather"], "window") == pytest.approx(0.2)
    assert trace.share(reduced, ["^all-reduce"], "window") == 0.0
    assert reduced["device_ops"][0] == ["fusion.1", pytest.approx(800e-9)]
    gaps = dict(reduced["idle_gaps"])
    assert gaps == pytest.approx({"chunk_result_wait": 50e-9, "report": 50e-9})


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce({"devices": {}, "host_spans": []})
    with pytest.raises(ValueError, match="no operation ran"):
        trace.reduce({"devices": {"/device:TPU:0": {"ops": [], "modules": []}},
                      "host_spans": [["report", 0, 5]]})


def test_short_name():
    line = ('%checkpoint.20 = (f32[128,4096,128]{2,1,0}) custom-call(bf16[1]), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert trace.short_name(line) == "checkpoint.20 [tpu_custom_call]"
    assert trace.short_name("%fusion.3 = bf16[4] fusion(bf16[4] %p)") == "fusion.3"
    assert trace.short_name("all-gather.7") == "all-gather.7"


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def test_recorded_trace_busy_union_and_idle_share(recorded):
    """Two steps of the two-layer Mistral cell, recorded on a v5e."""
    reduced = trace.reduce(recorded)
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    assert len(ops) > 1000 and reduced["n_devices"] == 1
    want_busy = merged_length([(s, s + d) for _, s, d in ops]) / 1e9
    assert reduced["busy_s"] == pytest.approx(want_busy, rel=1e-9)
    # two steps of about 0.94 s, next to no idle time
    assert 1.85 < reduced["window_s"] < 1.92
    idle_share = 1 - reduced["busy_s"] / reduced["window_s"]
    assert 0.001 < idle_share < 0.01
    # the sum of self times is the union: nothing is counted twice
    segments = reduced["segments"]["/device:TPU:0"]
    assert sum(e - s for s, e, _ in segments) / 1e9 == pytest.approx(want_busy)


def test_recorded_trace_kernels_and_gaps(recorded):
    reduced = trace.reduce(recorded)
    share = trace.share(reduced, [r"\[tpu_custom_call\]"], "busy")
    assert 0.45 < share < 0.49  # the flash kernels: 47 % of busy time
    assert trace.share(reduced, ["^(all-gather|all-reduce)"], "window") == 0.0
    names = [name for name, _ in reduced["device_ops"]]
    assert len(names) == 10 and "[tpu_custom_call]" in names[0]
    gaps = dict(reduced["idle_gaps"])
    window_idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(window_idle, rel=1e-6)
    # the device waits while the host fetches a chunk's result
    assert max(gaps, key=gaps.get) == "chunk_result_wait"
    assert gaps[trace.INSIDE_PROGRAM] < 1e-3


def test_extract_reads_the_profilers_file(tmp_path):
    """The file format, on a trace taken here: the CPU has no device plane,
    so only the loop's spans come back."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("next_batch"):
        jnp.ones((8, 8)).sum().block_until_ready()
    with jax.profiler.TraceAnnotation("not_a_span_of_the_loop"):
        pass
    jax.profiler.stop_trace()
    out = trace.extract(trace.find_xplane(str(tmp_path)), ["next_batch"])
    assert out["devices"] == {}
    assert [e[0] for e in out["host_spans"]] == ["next_batch"]
    assert out["host_spans"][0][2] > 0
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path / "nothing"))
