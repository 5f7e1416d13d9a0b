"""The yardstick's arithmetic: all work over the window, chunk readings and
their median, operations per image and per token against hand counts, the
table of peaks, and the seeded traffic generator."""

import json

import numpy as np
import pytest

from chipbench import chunks, flops, spec
from chipbench import traffic as traffic_lib

ROOT = spec.ROOT


def steps_from_times(step_seconds, steps_per_chunk, units_per_step):
    """Chunks from a list of step durations: only whole chunks count."""
    chunks = []
    for lo in range(0, len(step_seconds) - steps_per_chunk + 1, steps_per_chunk):
        part = step_seconds[lo:lo + steps_per_chunk]
        chunks.append({
            "steps": steps_per_chunk,
            "units": units_per_step * steps_per_chunk,
            "seconds": sum(part),
        })
    return chunks


def test_chunks_from_step_times():
    out = steps_from_times([0.1] * 25, steps_per_chunk=10,
                                  units_per_step=256)
    assert len(out) == 2  # only whole chunks count
    assert out[0]["units"] == 2560 and out[0]["seconds"] == pytest.approx(1.0)
    assert chunks.chunk_rates(out) == pytest.approx([2560.0, 2560.0])


@pytest.mark.parametrize("stalled_s", [0.0, 0.5, 3.0])
def test_one_stalled_chunk_moves_the_throughput_and_not_the_median(stalled_s):
    steps = [0.1] * 100
    steps[37] += stalled_s  # one slow step inside the fourth chunk
    out = steps_from_times(steps, 10, 256)
    window = sum(steps)
    assert chunks.median_rate(out) == pytest.approx(2560.0)
    assert chunks.total_rate(out, window) == pytest.approx(
        25600 / (10.0 + stalled_s))
    assert chunks.stall_share(out, window) == pytest.approx(
        stalled_s / (10.0 + stalled_s))


def test_time_between_chunks_is_a_stall_too():
    out = steps_from_times([0.1] * 100, 10, 256)
    # 10 s inside chunks, 1 s of reports and bookkeeping between them
    assert chunks.stall_share(out, 11.0) == pytest.approx(1 / 11)
    assert chunks.median_rate(out) == pytest.approx(2560.0)


def test_a_run_slow_throughout_moves_the_median():
    out = steps_from_times([0.101] * 100, 10, 256)
    assert chunks.median_rate(out) == pytest.approx(2560 / 1.01)
    assert chunks.stall_share(out, 10.1) == pytest.approx(0.0, abs=1e-12)


def test_no_chunk_no_rate():
    with pytest.raises(ValueError):
        chunks.median_rate([])


@pytest.mark.parametrize("reader,params,want", [
    ("total_rate", {}, 600 / 6.06),
    ("median_chunk_rate", {}, 100.0),
    ("stall_share", {}, 100 * (1 - 600 / 6.06 / 100)),
    ("span_share", {"span": "next_batch"}, 100 * 0.1 / 6.06),
    ("stage_seconds", {"stage": "setup"}, 30.0),
    ("model_mfu", {}, 100 * 1e9 * (600 / 6.06) / 197e12),
    ("peak_hbm_gb", {}, 9.0),
    ("trace_idle_share", {}, None),
    ("trace_share", {"patterns": ["x"], "over": "busy"}, None),
])
def test_reader(reader, params, want):
    run = {
        "chunks": [{"units": 100.0, "seconds": 1.0}] * 6, "window_s": 6.06,
        "spans": {"next_batch": 0.1}, "stages": {"setup": 30.0},
        "flops_per_unit": 1e9, "chips": 1, "memory_peak_bytes": 9 * 10**9,
        "device": {"kind": "TPU v5 lite"}, "trace": None,
    }
    got = spec.load_code(ROOT, "readers", reader).read(run, params)
    assert got is None if want is None else got == pytest.approx(want)


def test_resnet50_operations_by_hand():
    config = spec.read_json(ROOT, "chipbench/configs/resnet50-v1.5.json")
    convs = flops.resnet_convs(config)
    assert len(convs) == 53  # 1 stem + 16 blocks x 3 + 4 projections
    assert convs[0] == (112, 7, 3, 64, 1)
    # first bottleneck: 1x1 64->64, 3x3 64->64, 1x1 64->256, projection 64->256
    assert convs[1:5] == [(56, 1, 64, 64, 1), (56, 3, 64, 64, 1),
                          (56, 1, 64, 256, 1), (56, 1, 64, 256, 1)]
    # v1.5: the first block of stage 2 strides on its 3x3, so its 1x1 still
    # sees 56x56
    assert convs[11:14] == [(56, 1, 256, 128, 1), (28, 3, 128, 128, 1),
                            (28, 1, 128, 512, 1)]
    stem = 2 * 112 * 112 * 7 * 7 * 3 * 64
    assert stem == 236_027_904
    macs = sum(s * s * k * k * i * o for s, k, i, o, _ in convs) + 2048 * 1000
    assert 4.05e9 < macs < 4.15e9  # the published 4.1 GMAC of ResNet-50 v1.5
    assert flops.resnet_flops_per_image(config) == pytest.approx(6.0 * macs)


def test_transformer_operations_by_hand():
    config = spec.read_json(ROOT, "chipbench/configs/mistral-7b-l2.json")
    d, f, v = 4096, 14336, 32000
    layer = 2 * d * (4096 + 2 * 1024) + 2 * 4096 * d + 2 * 3 * d * f
    attention = 2 * 2 * 4096 * (4096 + 1) / 2
    by_hand = 3 * (2 * (layer + attention) + 2 * d * v)
    assert flops.transformer_flops_per_token(config, 4096) == pytest.approx(by_hand)
    assert by_hand == pytest.approx(3.605e9, rel=1e-3)
    per_layer = d * 6144 + 4096 * d + 3 * d * f + 2 * d
    assert flops.transformer_param_count(config) == (
        2 * per_layer + 2 * v * d + d) == 698_372_096
    eight = spec.read_json(ROOT, "chipbench/configs/mistral-7b-l8-fsdp4.json")
    assert flops.transformer_param_count(eight) == 2_007_044_096


def test_flops_agree_with_the_programs_own_count():
    from chipbench.loops.transformer import model_config
    from ray_tpu.models.transformer import flops_per_token

    config = spec.read_json(ROOT, "chipbench/configs/mistral-7b-l2.json")
    assert flops.transformer_flops_per_token(config, 4096) == pytest.approx(
        flops_per_token(model_config(config), 4096))


def test_peaks_table():
    peaks = flops.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError, match="no peaks on record"):
        flops.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks_for("cpu")


@pytest.mark.parametrize("name", ["ingest-224", "resident-224",
                                  "tokens-4k-16k", "tokens-4k-32k"])
def test_traffic_is_seeded_and_of_fixed_shape(name):
    traffic = spec.read_json(ROOT, "chipbench", "traffic", name + ".json")
    config = {"num_classes": 1000, "vocab_size": 32000}
    big = 2**31 + 12345  # more than 32 signed bits hold
    a = traffic_lib.make_rows(traffic, config, big, 3, 2)
    b = traffic_lib.make_rows(traffic, config, big, 3, 2)
    c = traffic_lib.make_rows(traffic, config, big + 1, 3, 2)
    d = traffic_lib.make_rows(traffic, config, big, 4, 2)
    for column, spec_ in traffic["columns"].items():
        assert a[column].dtype == np.dtype(spec_["dtype"])
        assert a[column].shape == c[column].shape == (2, *spec_["shape"])
        assert np.array_equal(a[column], b[column])
        assert not np.array_equal(a[column], c[column])
        assert not np.array_equal(a[column], d[column])
        high = config.get(str(spec_["high"]).split(":")[-1], spec_["high"])
        assert a[column].min() >= 0 and a[column].max() < int(high)
    assert traffic_lib.units_per_step(traffic) == (
        traffic["batch_rows"] * traffic["units_per_row"])
    assert json.dumps(traffic)  # plain data


def test_block_maker_needs_no_jax():
    import subprocess
    import sys

    code = (
        "import sys, numpy as np\n"
        "from chipbench import spec, traffic\n"
        "t = spec.read_json(spec.ROOT, 'chipbench', 'traffic', 'tokens-4k-16k.json')\n"
        "make = traffic.block_maker(t, {'vocab_size': 32000}, 7)\n"
        "block = make({'id': np.array([5])})\n"
        "assert block['tokens'].shape == (4, 4097)\n"
        "raise SystemExit(7 if 'jax' in sys.modules else 0)\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, timeout=120)
    assert out.returncode == 0


@pytest.mark.parametrize("stats,want", [
    # ResNet: little live, the step's scratch reserved
    ({"bytes_in_use": 345441792, "peak_bytes_in_use": 502781440,
      "bytes_reserved": 9060533248, "peak_bytes_reserved": 9060533248},
     345441792 + 9060533248),
    # four-chip Mistral: set-up's comparison held more live arrays than the
    # window does; that peak is the benchmark's own and is not reported
    ({"bytes_in_use": 6069161472, "peak_bytes_in_use": 12079418368,
      "bytes_reserved": 5392547840, "peak_bytes_reserved": 5392547840},
     6069161472 + 5392547840),
    ({}, 0),
])
def test_the_reported_memory_is_what_the_window_holds(stats, want):
    from chipbench import loop

    assert loop.held_in_window(stats) == want
    assert loop.held_in_window(stats) <= 16909336064  # the chip's bytes_limit
