"""Tiny versions of the benchmark's cells for the CPU tests, and the
steering that lets the worker's loop run without a chip. The widths are
toys on purpose: these tests show control flow and arithmetic, never a
time."""

import copy
import time

from chipbench import loop, spec

CELLS = ["resnet50.ingest", "mistral7b.tokens4k", "resnet50.resident",
         "mistral7b.fsdp4"]


def tiny_cell(name, root=spec.ROOT):
    cell = copy.deepcopy(spec.load_cell(root, name))
    config, traffic = cell["config"], cell["traffic"]
    if config["family"] == "resnet":
        config.update(width=8, image_size=32, num_classes=10,
                      check=dict(config["check"], rows=4))
        traffic["columns"]["image"]["shape"] = [32 * 32 * 3]
        traffic.update(batch_rows=8, steps_per_chunk=2, warmup_steps=1,
                       trace_chunks=2)
        if traffic["kind"] == "ingest":
            traffic.update(rows_per_block=8, blocks_per_epoch=5)
    else:
        config.update(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=128, max_seq_len=64, n_layers=2,
                      check={"rows": 4, "seq_len": 32})
        traffic["columns"]["tokens"]["shape"] = [65]
        traffic.update(units_per_row=64, blocks_per_epoch=5,
                       steps_per_chunk=2, warmup_steps=1, trace_chunks=2)
    return cell


def job_for(cell, seconds=0.5, seed=2**31 + 5, root=spec.ROOT, trace_dir=None):
    return {
        "root": root, "workload": cell["workload"]["name"],
        "chips": cell["workload"]["chips"], "config": cell["config"],
        "traffic": cell["traffic"], "seed": seed, "seconds": seconds,
        "trace_dir": trace_dir, "t_process_start": time.time(),
        "t_fit_called": time.time(),
    }


def run_loop_here(monkeypatch, cell, **job_args):
    """The worker's loop in this process on CPU devices: the device
    assertion, `train.report` and the dataset shard are replaced by the
    test; the program has no option for any of it."""
    import jax

    from chipbench import traffic as traffic_lib
    from ray_tpu import train

    chips = cell["workload"]["chips"]
    monkeypatch.setattr(
        loop, "require_devices",
        lambda n: {"platform": "cpu", "kind": "cpu", "count": n})
    real_devices = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a: real_devices(*a)[:chips])
    reports = []
    monkeypatch.setattr(train, "report", lambda m, **kw: reports.append(m))
    job = job_for(cell, **job_args)
    if cell["traffic"]["kind"] == "ingest":
        shard = traffic_lib.dataset(
            cell["traffic"], cell["config"], job["seed"]).streaming_split(1)[0]
        monkeypatch.setattr(train, "get_dataset_shard",
                            lambda name="train": shard)
    loop.train_loop(job)
    return reports


def fake_summary(cell, chunks=6):
    per_chunk = 100.0
    return {
        "summary": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite",
                   "count": cell["workload"]["chips"], "extra": "dropped?"},
        "stages": {"gang_boot": 1.0, "state_init": 2.0, "setup": 30.0},
        "window_s": chunks * 1.01,
        "tracer_s": 0.0,
        "chunks": [{"chunk": i, "steps": 2, "units": per_chunk,
                    "seconds": 1.0, "loss": 5.0, "traced": False}
                   for i in range(chunks)],
        "spans": {"next_batch": 0.1, "step_dispatch": 0.2,
                  "chunk_result_wait": 5.0, "report": 0.01},
        "steps": 2 * chunks, "steps_failed": 0, "compiles_in_window": 0,
        "compile_cache": {"hits": 3, "misses": 0},
        "reference": {"agrees": True},
        "state_bytes": {"whole": 400, "per_device":
                        [100] * cell["workload"]["chips"]},
        "flops_per_unit": 1e9, "memory_peak_bytes": 9 * 10**9,
    }


def fake_reduced(chips):
    from chipbench import trace

    ops = [["fusion.1", 0, 600], ["kernel.2 [tpu_custom_call]", 600, 300],
           ["all-gather.3", 950, 40]]
    return trace.reduce({
        "devices": {f"/device:TPU:{i}": {"ops": ops, "modules": [
            ["jit_step", 0, 1000]]} for i in range(chips)},
        "host_spans": [["report", 990, 20]],
    })
