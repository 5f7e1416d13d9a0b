#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's job goes through the program's normal path: a `ray_tpu.data`
pipeline, `ray_tpu.init()`, a `JaxTrainer(use_tpu=True)` gang of one worker
that owns the cell's chips, `parallel.make_mesh`, `train.report`. This
process never initialises a JAX backend: the train worker holds the chip.

Standard output is claimed at start: fd 1 points at stderr for this process
and every child. The informational lines (one JSON object each: stages,
every chunk's reading, the reference comparison, the cache's hits) and the
last line are written to the saved descriptor only; the last line is written
after `ray_tpu.shutdown()` has returned, and nothing follows it. Any failure,
a run without the chips among them, ends in a traceback on stderr, a
non-zero exit and no last line.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from typing import Any, Callable, Dict, List  # noqa: E402

from chipbench import spec  # noqa: E402

TIME_LIMIT_S = 340  # the contract allows 360 ...
FIRST_RUN_TIME_LIMIT_S = 1150  # ... and 1200 where the compile cache is empty
SCRATCH = ".chipbench"  # under the checkout: traces and the trainer's storage

Emit = Callable[[Dict[str, Any]], None]


def detect_chips(root: str) -> int:
    """The runtime's own detector, in a child: no JAX, and nothing of the
    runtime imported here before the native extensions are built."""
    code = (
        "from ray_tpu._private.accelerators import TPUAcceleratorManager as M;"
        "print(M.detect_count())"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=root, check=True,
                         timeout=120, stdout=subprocess.PIPE)
    return int(out.stdout)


def build_native(root: str) -> str:
    """Build setup.py's extensions in place when they do not import: git
    tracks no `*.so`, so a fresh checkout has none."""
    probe = [sys.executable, "-c",
             "from ray_tpu._native import _shm, _store, _fastpath"]
    if subprocess.run(probe, cwd=root, stderr=subprocess.DEVNULL).returncode == 0:
        return "present"
    subprocess.run([sys.executable, "setup.py", "build_ext", "--inplace"],
                   cwd=root, check=True, timeout=600)
    subprocess.run(probe, cwd=root, check=True)
    return "built"


def run_job(job: Dict[str, Any], storage: str):
    """ingest -> JaxTrainer(use_tpu=True) -> result, on a started cluster."""
    from ray_tpu._private.chip_entry import assert_no_jax_backend
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    from chipbench import loop, traffic

    datasets = {}
    if job["traffic"]["kind"] == "ingest":
        datasets["train"] = traffic.dataset(
            job["traffic"], job["config"], job["seed"])
    assert_no_jax_backend()
    job["t_fit_called"] = time.time()
    return JaxTrainer(
        loop.train_loop,
        train_loop_config=job,
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True, tpu_chips_per_worker=job["chips"]),
        run_config=RunConfig(name="chipbench", storage_path=storage),
        datasets=datasets,
    ).fit()


def summary_of(result) -> Dict[str, Any]:
    summary = result.metrics
    if not summary or not summary.get("summary"):
        raise RuntimeError(f"no summary report from the train worker: {summary}")
    reported = [m for m in result.metrics_history if "summary" not in m]
    if len(reported) != len(summary["chunks"]):
        raise RuntimeError(
            f"{len(summary['chunks'])} chunks ran, {len(reported)} reports "
            "reached the parent")
    return summary


def verdict(summary: Dict[str, Any], cell: Dict[str, Any]) -> List[str]:
    """Why the run is not correct; empty when it is."""
    reasons = []
    device = summary["device"]
    if device["platform"] != "tpu" or device["count"] != cell["workload"]["chips"]:
        reasons.append(f"ran on {device}")
    if not summary["reference"]["agrees"]:
        reasons.append(f"disagrees with the reference: {summary['reference']}")
    if summary["steps_failed"]:
        reasons.append(f"{summary['steps_failed']} step(s) with a non-finite loss")
    if summary["compiles_in_window"]:
        reasons.append(
            f"{summary['compiles_in_window']} compilation(s) inside the window")
    if not summary["chunks"]:
        reasons.append("no chunk finished")
    share = cell["config"].get("state_share")
    if share:
        whole = summary["state_bytes"]["whole"]
        for held in summary["state_bytes"]["per_device"]:
            if abs(held / whole - share["expected"]) > share["tolerance"]:
                reasons.append(
                    f"a device holds {held} of {whole} state bytes, not about "
                    f"{share['expected']:.0%}")
                break
    return reasons


def reduce_trace(trace_dir: str) -> Dict[str, Any]:
    from chipbench import loop, trace

    return trace.reduce(trace.extract(trace.find_xplane(trace_dir), loop.SPANS))


def run_cell(args, root: str, emit: Emit) -> Dict[str, Any]:
    """Every stage in order; returns the last line's object."""
    bench = spec.load_benchmark(root)
    cell = spec.load_cell(root, args.workload)
    chips = cell["workload"]["chips"]

    detected = detect_chips(root)
    if detected < chips:
        raise RuntimeError(
            f"{detected} TPU chip(s) detected on this host, the cell "
            f"{args.workload} needs {chips}")
    emit({"stage": "native", "state": build_native(root)})

    import ray_tpu
    from ray_tpu._private.chip_entry import place_compile_cache

    cache_dir, was_empty = place_compile_cache()
    emit({"info": "compile_cache", "dir": cache_dir, "was_empty": was_empty})
    if not was_empty:  # nothing to compile: the shorter limit holds
        signal.alarm(max(1, int(
            TIME_LIMIT_S - (time.time() - T_PROCESS_START))))

    scratch = os.path.join(root, SCRATCH, args.workload)
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    trace_dir = os.path.join(scratch, "trace") if args.trace else None
    job = {
        "root": root, "workload": args.workload, "chips": chips,
        "config": cell["config"], "traffic": cell["traffic"],
        "seed": args.seed, "seconds": args.seconds, "trace_dir": trace_dir,
        "t_process_start": T_PROCESS_START,
    }
    t0 = time.time()
    ray_tpu.init()
    emit({"stage": "init", "seconds": time.time() - t0,
          "resources": ray_tpu.cluster_resources()})
    try:
        result = run_job(job, os.path.join(scratch, "storage"))
        summary = summary_of(result)
    finally:
        t0 = time.time()
        ray_tpu.shutdown()
        emit({"stage": "shutdown", "seconds": time.time() - t0})

    for chunk in summary["chunks"]:
        emit({"chunk": chunk["chunk"], "steps": chunk["steps"],
              "seconds": chunk["seconds"],
              "rate": chunk["units"] / chunk["seconds"],
              "loss": chunk["loss"], "traced": chunk["traced"]})
    emit({"info": "worker", **{k: summary[k] for k in (
        "stages", "window_s", "tracer_s", "spans", "steps",
        "compiles_in_window", "compile_cache", "reference", "state_bytes",
        "setup_peak_bytes_in_use", "memory_stats")}})
    reduced = reduce_trace(trace_dir) if trace_dir else None
    for reason in verdict(summary, cell):
        emit({"info": "not_correct", "reason": reason})
    return last_line(root, bench, cell, summary, reduced)


def last_line(root: str, bench: Dict[str, Any], cell: Dict[str, Any],
              summary: Dict[str, Any], reduced: Any) -> Dict[str, Any]:
    """The contract's object: a traced run (`reduced` is its reduced trace)
    reports the cell's per-layer metrics, an untraced one its end-to-end
    metrics."""
    name = cell["workload"]["name"]
    run = dict(summary, chips=cell["workload"]["chips"], trace=reduced)
    device = dict(summary["device"],
                  memory_peak_bytes=summary["memory_peak_bytes"])
    line: Dict[str, Any] = {
        "correct": not verdict(summary, cell),
        "attempted": summary["steps"],
        "failed": summary["steps_failed"],
        "metrics": spec.metric_lines(
            root, bench, name, "per_layer" if reduced else "end_to_end", run),
        "device": device,
    }
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    return line


def _group_members() -> List[int]:
    """Other live processes of this process group: what this run started."""
    me, group, found = os.getpid(), os.getpgrp(), []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == me:
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        if int(fields[2]) == group and fields[0] != "Z":
            found.append(int(entry))
    return found


def stop_children(grace_s: float) -> List[int]:
    """Wait for what this run started to leave; kill what does not."""
    deadline = time.monotonic() + grace_s
    while _group_members() and time.monotonic() < deadline:
        time.sleep(0.1)
    killed = _group_members()
    for pid in killed:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    return killed


def main(argv: List[str]) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    root = spec.ROOT

    # Claim standard output: from here on fd 1 is stderr, for this process
    # and every child. Only `emit` and the last line reach the real one.
    sys.stdout.flush()
    real_stdout = os.dup(1)
    os.dup2(2, 1)
    if os.getpgrp() != os.getpid():
        os.setpgrp()  # so that `stop_children` can tell what this run started

    def emit(obj: Dict[str, Any]) -> None:
        os.write(real_stdout, (json.dumps(obj) + "\n").encode())

    def on_signal(signum, frame):
        raise TimeoutError(f"signal {signum}: the run ran out of time")

    signal.signal(signal.SIGALRM, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    try:
        os.chdir(root)
        signal.alarm(FIRST_RUN_TIME_LIMIT_S)
        line = json.dumps(run_cell(args, root, emit))
    except BaseException:  # noqa: BLE001 - the exit code is the report
        traceback.print_exc()
        sys.stderr.flush()
        stop_children(grace_s=5)
        os._exit(1)
    signal.alarm(0)
    killed = stop_children(grace_s=20)
    if killed:
        emit({"info": "killed_after_shutdown", "pids": killed})
    os.write(real_stdout, (line + "\n").encode())
    os._exit(0)  # nothing runs after the last line


if __name__ == "__main__":
    main(sys.argv[1:])
