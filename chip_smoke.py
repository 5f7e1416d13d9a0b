#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # one chip: the training main path
    python3 chip_smoke.py --chips 4  # four chips: the sharded path only

With no arguments it drives the training main path once, through the entry
points a user calls and at the one full width the repo supports: a
`ray_tpu.data` pipeline (range -> map_batches -> streaming_split) makes
seeded token blocks; `ray_tpu.init()` finds the chip without JAX; a
`JaxTrainer(use_tpu=True)` gang of one worker builds the 12-layer,
d_model-768, vocab-50304 decoder with `make_train_step` on
`make_mesh({"data": 1})`, checks the flash kernel against the XLA reference,
takes one warm-up and STEPS timed steps at batch 32 x seq 1024, reports every
step and one checkpoint; the parent checks the metrics and loads the
checkpoint. With `--chips 4` it runs only the sharded path and what it is
compared with: the same model and batch on `make_mesh({"data": 2, "fsdp": 2})`
against a one-device mesh in the same worker, where every shard sits, and one
`MeshCollectives` allreduce and all-gather against NumPy.

Standard output is claimed at start: fd 1 is pointed at stderr for this
process and every child, so whatever a library, a worker, the log echo or an
exit hook prints lands on stderr. Informational lines (one JSON object each)
and the last line are written to the saved descriptor only. The last line is
`final_line()` of the device the train worker reported, written after
`ray_tpu.shutdown()` has returned; then the process leaves with `os._exit`.
Any failure ends in a traceback on stderr, a non-zero exit and no last line.

The parent never initialises a JAX backend (a chip belongs to one process):
exactly one process, the train worker, touches the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import traceback
from typing import Any, Callable, Dict, Iterator, List

ROOT = os.path.dirname(os.path.abspath(__file__))

# The GPT-2-small decoder (12 layers of 768, vocabulary 50304) at batch 32 x
# sequence 1024. `dtype` is a name so that the parent needs no JAX.
GPT2_SMALL = {
    "vocab_size": 50304,
    "d_model": 768,
    "n_layers": 12,
    "n_heads": 12,
    "max_seq_len": 1024,
    "dtype": "bfloat16",
    "remat": True,
}
BATCH = 32
STEPS = 8  # timed steps after the warm-up step
SHARDED_STEPS = 3
SEED = 0
LEARNING_RATE = 1e-3
MESH_AXES = {1: {"data": 1}, 4: {"data": 2, "fsdp": 2}}
TIME_LIMIT_S = 1080  # the contract allows 1200

# Flash kernel against mha(impl="xla") on bf16 inputs, as max|a-b| / max|b|.
# One bf16 ulp at the tensor's largest magnitude is 2**-8 = 3.9e-3; the two
# paths also round the softmax weights differently before the second matmul.
KERNEL_TOLERANCE = 2e-2
# Sharded against one device, same seed and batch. The first loss comes from
# identical weights, so only the order of bf16 reductions differs; later
# losses also carry three optimizer steps of that difference.
FIRST_LOSS_TOLERANCE = 2e-2
LAST_LOSS_BAND = 1e-1

Emit = Callable[[Dict[str, Any]], None]


def final_line(device: Dict[str, Any]) -> str:
    """The last line of standard output, from the device identity the train
    worker reported: these keys and no others."""
    return json.dumps(
        {
            "ok": True,
            "device": {
                "platform": str(device["platform"]),
                "kind": str(device["kind"]),
                "count": int(device["count"]),
            },
        }
    )


@contextlib.contextmanager
def stage(emit: Emit, name: str) -> Iterator[Dict[str, Any]]:
    """Time one stage and emit its wall time with whatever the body put in
    the yielded dict; a stage that raises emits nothing."""
    extra: Dict[str, Any] = {}
    t0 = time.perf_counter()
    yield extra
    emit({"stage": name, "seconds": round(time.perf_counter() - t0, 3), **extra})


# ------------------------------------------------------------------ parent


def detect_chips() -> int:
    """Ask the runtime's own detector (no JAX) how many chips this host has.
    In a child, because importing the runtime here would load the native
    extensions, or their pure-Python stand-ins, before `build_native` ran."""
    code = (
        "from ray_tpu._private.accelerators import TPUAcceleratorManager as M;"
        "print(M.detect_count())"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, check=True, timeout=120,
        stdout=subprocess.PIPE,
    )
    return int(out.stdout)


def build_native() -> str:
    """Build the three extensions of setup.py in place when they do not
    import: git tracks no `*.so`, so a fresh checkout has none."""
    probe = [
        sys.executable, "-c",
        "from ray_tpu._native import _shm, _store, _fastpath",
    ]
    if subprocess.run(probe, cwd=ROOT, stderr=subprocess.DEVNULL).returncode == 0:
        return "present"
    subprocess.run(
        [sys.executable, "setup.py", "build_ext", "--inplace"],
        cwd=ROOT, check=True, timeout=600,
    )
    subprocess.run(probe, cwd=ROOT, check=True)
    return "built"


def native_status() -> Dict[str, bool]:
    """Which native pieces this process runs, as the runtime's own flags say."""
    from ray_tpu._private import core_worker, rpc, serialization, shm, store_core

    return {
        "shm": bool(shm.NATIVE),
        "store": bool(store_core.NATIVE),
        "wire_codec": bool(rpc.native_wire_active()),
        "fastpath": bool(core_worker._fp_mod()),
        "copy_nt": serialization._copy_nt is not None,
    }


def require_native(where: str) -> Dict[str, bool]:
    live = native_status()
    if not all(live.values()):
        raise RuntimeError(f"{where} runs a pure-Python stand-in: {live}")
    return live


def token_blocks(vocab_size: int, seq_len: int, rows: int, seed: int):
    """map_batches fn: one block of `rows` sequences of seq_len + 1 tokens.
    Every block holds the same seeded sequences, so the worker trains on a
    repeated batch and the loss has to fall."""

    def make(batch: Dict[str, Any]) -> Dict[str, Any]:
        import numpy as np

        tokens = np.random.default_rng(seed).integers(
            0, vocab_size, (rows, seq_len + 1), dtype=np.int32
        )
        # Ingest tasks must not import JAX: they would take the chip.
        jax_loaded = np.full((rows,), "jax" in sys.modules, dtype=np.bool_)
        return {"tokens": tokens, "jax_loaded": jax_loaded}

    return make


def run_training(
    model: Dict[str, Any], batch: int, steps: int, chips: int, storage: str
):
    """ingest -> JaxTrainer(use_tpu=True) -> result, on a cluster the caller
    has started. One worker owns all `chips`."""
    from ray_tpu import data as rd
    from ray_tpu._private.chip_entry import assert_no_jax_backend
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    n_blocks = steps + 1  # one block is one batch; the first is the warm-up
    dataset = rd.range(n_blocks, parallelism=n_blocks).map_batches(
        token_blocks(model["vocab_size"], model["max_seq_len"], batch, SEED),
        batch_size=1,
    )
    assert_no_jax_backend()
    return JaxTrainer(
        train_loop,
        train_loop_config={
            "model": model, "batch": batch, "steps": steps, "chips": chips,
        },
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True, tpu_chips_per_worker=chips
        ),
        run_config=RunConfig(name="chip_smoke", storage_path=storage),
        datasets={"train": dataset},
    ).fit()


def param_count(model: Dict[str, Any]) -> int:
    """Parameters of transformer_init for this config, without JAX."""
    d, layers = model["d_model"], model["n_layers"]
    ff = int(8 * d / 3 + 127) // 128 * 128
    per_layer = 4 * d * d + 3 * d * ff + 2 * d
    return model["vocab_size"] * d + layers * per_layer + d


def check_result(result, model: Dict[str, Any], steps: int, chips: int
                 ) -> Dict[str, Any]:
    """The parent's view: the reports arrived, and the checkpoint loads."""
    import numpy as np

    history = result.metrics_history
    summary = result.metrics
    if not summary or not summary.get("summary"):
        raise RuntimeError(f"no summary report from the train worker: {summary}")
    step_reports = [m for m in history if "summary" not in m]
    if len(step_reports) != steps + 1:
        raise RuntimeError(
            f"expected {steps + 1} step reports, got {len(step_reports)}"
        )
    losses = [m["loss"] for m in step_reports]
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite loss in {losses}")
    if chips == 1:
        if result.checkpoint is None:
            raise RuntimeError("no checkpoint was reported")
        with result.checkpoint.as_directory() as d:
            with np.load(os.path.join(d, "params.npz")) as params:
                n = sum(int(params[k].size) for k in params.files)
                finite = all(np.isfinite(params[k]).all() for k in params.files)
        want = param_count(model)
        if not (n == want == summary["n_params"] and finite):
            raise RuntimeError(
                f"checkpoint holds {n} parameters (finite={finite}); the "
                f"config has {want}, the worker reported {summary['n_params']}"
            )
    return summary


# ------------------------------------------------------------ train worker


def device_identity() -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def check_flash_against_xla(shape=(2, 1024, 12, 64), interpret: bool = False
                            ) -> Dict[str, float]:
    """Flash forward and its gradients against mha(impl="xla") on bf16,
    causal. Returns max|a-b| / max|b| for out, dq, dk, dv; raises past
    KERNEL_TOLERANCE."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention, mha

    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v = (
        jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
        for key in (kq, kk, kv)
    )
    cotangent = jax.random.normal(kw, shape, jnp.float32)

    def run(attn):
        def scalar(q, k, v):
            out = attn(q, k, v)
            return (out.astype(jnp.float32) * cotangent).sum(), out

        (_, out), grads = jax.jit(
            jax.value_and_grad(scalar, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)
        return (out, *grads)

    got = run(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=interpret))
    want = run(lambda q, k, v: mha(q, k, v, causal=True, impl="xla"))
    errors = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        errors[name] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
    if not all(e <= KERNEL_TOLERANCE for e in errors.values()):
        raise RuntimeError(
            f"flash attention disagrees with the XLA reference: {errors} "
            f"(tolerance {KERNEL_TOLERANCE})"
        )
    return errors


def make_config(model: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig

    return TransformerConfig(**{**model, "dtype": jnp.dtype(model["dtype"])})


def train_step_on(cfg, mesh):
    """(init_state, step, shardings): make_train_step with the smoke's
    optimizer. The state is `init_state(jax.random.PRNGKey(SEED))`."""
    import optax

    from ray_tpu.models import make_train_step

    return make_train_step(cfg, mesh, optax.adamw(LEARNING_RATE))


def compile_step(step, state, batch):
    """The executable for these arguments and the seconds it took. The steps
    run this executable, so a step whose output sharding drifted from its
    input raises instead of recompiling unseen."""
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    return compiled, time.perf_counter() - t0


def split_tokens(tokens) -> Dict[str, Any]:
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def require_kernel(compiled) -> int:
    """The compiled step must hold the Pallas kernel: an XLA-einsum step
    must not pass as the flash step."""
    calls = compiled.as_text().count("tpu_custom_call")
    if not calls:
        raise RuntimeError("the compiled train step holds no tpu_custom_call")
    return calls


def placement_report(state, batch, shardings, mesh) -> Dict[str, Any]:
    """Where the sharded state and batch sit: every array as the rules
    (`shardings["state"]`) say, on every device of the mesh, one device's
    shards well short of the whole state, the tokens split one way per
    device. Code that has never seen four chips may put all on the first."""
    import jax

    devices = set(mesh.devices.flat)
    expected = whole = 0
    for leaf, rule in zip(
        jax.tree.leaves(state), jax.tree.leaves(shardings["state"]), strict=True
    ):
        if not leaf.sharding.is_equivalent_to(rule, leaf.ndim) or {
            s.device for s in leaf.addressable_shards
        } != devices:
            raise RuntimeError(
                f"a {leaf.shape} array sits as {leaf.sharding}, not as {rule}"
            )
        expected += math.prod(rule.shard_shape(leaf.shape)) * leaf.dtype.itemsize
        whole += leaf.nbytes
    rows = sorted(
        (s.index[0].start or 0, s.data.shape, s.device.id)
        for s in batch["tokens"].addressable_shards
    )
    if expected > 0.6 * whole or len({r[0] for r in rows}) != len(devices):
        raise RuntimeError(
            f"not spread over {len(devices)} devices: one holds {expected} of "
            f"{whole} state bytes, the tokens are split as {rows}"
        )
    return {
        "expected_bytes_per_device": expected,
        "whole_state_bytes": whole,
        "token_shards": [[r[0], list(r[1]), r[2]] for r in rows],
    }


def memory_stats(device) -> Dict[str, int]:
    stats = device.memory_stats()
    if not stats:
        raise RuntimeError(f"{device} reports no memory statistics")
    return stats


def require_device_memory(report: Dict[str, Any], devices) -> List[int]:
    """Per-device bytes in use against the sum of that device's shards: near
    the fsdp share on every device, not the whole model on the first."""
    expected = report["expected_bytes_per_device"]
    in_use = [int(memory_stats(d)["bytes_in_use"]) for d in devices]
    if not all(
        0.98 * expected <= b <= 1.25 * expected + (64 << 20) for b in in_use
    ):
        raise RuntimeError(
            f"bytes in use per device {in_use}; the shards of one device "
            f"sum to {expected} of {report['whole_state_bytes']}"
        )
    return in_use


def run_steps(compiled, state, batch, steps: int) -> List[float]:
    losses = []
    for _ in range(steps):
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


def compare_sharded(cfg, batch, devices, steps: int, on_chip: bool
                    ) -> Dict[str, Any]:
    """The same seed and batch on the mesh of all `devices` and on one."""
    import jax

    from ray_tpu.parallel import make_mesh

    out: Dict[str, Any] = {}
    losses = {}
    for name, devs in (("sharded", devices), ("single", devices[:1])):
        mesh = make_mesh(MESH_AXES[len(devs)], devices=devs)
        init_state, step, shardings = train_step_on(cfg, mesh)
        state = init_state(jax.random.PRNGKey(SEED))
        placed = jax.device_put(batch, shardings["tokens"])
        if name == "sharded":
            out.update(placement_report(state, placed, shardings, mesh))
            if on_chip:
                out["bytes_in_use_per_device"] = require_device_memory(
                    out, devs
                )
        compiled, compile_s = compile_step(step, state, placed)
        if on_chip:
            out[f"{name}_tpu_custom_calls"] = require_kernel(compiled)
        t0 = time.perf_counter()
        losses[name] = run_steps(compiled, state, placed, steps)
        out[f"{name}_compile_s"] = round(compile_s, 3)
        out[f"{name}_steps_s"] = round(time.perf_counter() - t0, 3)
        out[f"{name}_losses"] = losses[name]
        del state, placed, compiled  # free the devices for the next mesh
    sharded, single = losses["sharded"], losses["single"]
    if not all(math.isfinite(x) for x in sharded + single):
        raise RuntimeError(f"non-finite loss: {out}")
    if (
        abs(sharded[0] - single[0]) > FIRST_LOSS_TOLERANCE
        or abs(sharded[-1] - single[-1]) > LAST_LOSS_BAND
        or not sharded[-1] < sharded[0]
    ):
        raise RuntimeError(f"sharded and single-device losses part: {out}")
    return out


def check_collectives(devices) -> Dict[str, Any]:
    """One MeshCollectives allreduce and all-gather against NumPy."""
    import numpy as np
    from jax.sharding import Mesh

    from ray_tpu.util.collective.mesh_ops import MeshCollectives

    engine = MeshCollectives(Mesh(np.asarray(devices), ("world",)))
    rng = np.random.default_rng(SEED)
    parts = [
        rng.standard_normal((1024, 256)).astype(np.float32) for _ in devices
    ]
    staged = engine.stage_parts(parts)
    reduced = np.asarray(engine.allreduce(staged))
    gathered = np.asarray(engine.allgather(staged))
    np.testing.assert_allclose(reduced, np.sum(parts, axis=0), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(gathered, np.stack(parts))
    return {"world": len(devices), "allreduce": "ok", "allgather": "ok"}


def train_loop(config: Dict[str, Any]) -> None:
    """What the train worker runs: the only process that touches the chip."""
    import warnings

    # A deprecation JAX raises at this repo's code is a defect on this path.
    warnings.filterwarnings(
        "error", category=DeprecationWarning,
        module=r"ray_tpu|chip_smoke|__main__",
    )
    import jax

    device = device_identity()
    if device["platform"] != "tpu" or device["count"] != config["chips"]:
        raise RuntimeError(
            f"the train worker sees {device}; the smoke needs platform 'tpu' "
            f"with {config['chips']} device(s)"
        )
    import importlib.metadata

    import numpy as np

    from ray_tpu import train
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train import Checkpoint

    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    summary: Dict[str, Any] = {
        "summary": True,
        "device": device,
        "versions": {
            "jax": jax.__version__,
            "jaxlib": importlib.metadata.version("jaxlib"),
            "libtpu": importlib.metadata.version("libtpu"),
        },
        "native": require_native("the train worker"),
        "cache": cache,  # counted by on_event until the last report
    }
    cfg = make_config(config["model"])
    batch_size, steps = config["batch"], config["steps"]
    devices = jax.devices()
    mesh = make_mesh(MESH_AXES[config["chips"]], devices=devices)
    init_state, step, shardings = train_step_on(cfg, mesh)

    def to_device(raw):
        # Runs in the prefetch thread: the copy of batch k + 1 overlaps the
        # step on batch k.
        if np.asarray(raw["jax_loaded"]).any():
            raise RuntimeError("an ingest task had JAX loaded")
        return jax.device_put(
            split_tokens(np.asarray(raw["tokens"])), shardings["tokens"]
        )

    batches = train.get_dataset_shard("train").iter_batches(
        batch_size=batch_size, prefetch_batches=2, _finalize_fn=to_device,
    )

    if config["chips"] > 1:
        summary.update(compare_sharded(
            cfg, next(batches), devices, steps + 1, on_chip=True))
        summary["collectives"] = check_collectives(devices)
        for _ in batches:  # drain the pipeline so that it finishes
            pass
        for i, loss in enumerate(summary["sharded_losses"]):
            train.report({"step": i, "loss": loss})
        train.report(summary)
        return

    summary["kernel_vs_xla"] = check_flash_against_xla()
    state = init_state(jax.random.PRNGKey(SEED))
    summary["n_params"] = sum(
        int(x.size) for x in jax.tree.leaves(state["params"])
    )
    losses, step_s = [], []
    for i, batch in enumerate(batches):
        if i == 0:
            compiled, compile_s = compile_step(step, state, batch)
            summary["compile_s"] = round(compile_s, 3)
            summary["tpu_custom_calls"] = require_kernel(compiled)
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        loss = float(metrics["loss"])  # waits for the device
        step_s.append(time.perf_counter() - t0)
        if not math.isfinite(loss):
            raise RuntimeError(f"loss {loss} at step {i}")
        losses.append(loss)
        train.report({"step": i, "loss": loss, "step_s": step_s[-1]})
        if i == 0:
            t_timed = time.perf_counter()  # the warm-up step is not timed
    steps_wall = time.perf_counter() - t_timed
    if len(losses) != steps + 1 or not losses[-1] < losses[0]:
        raise RuntimeError(
            f"{steps + 1} steps on a repeated batch should end lower than "
            f"they began: {losses}"
        )
    summary.update(
        losses=losses,
        step_s=[round(s, 4) for s in step_s],
        steps_s=round(steps_wall, 3),
        steps_per_s=round(steps / steps_wall, 3),
        tokens_per_s=round(
            steps * batch_size * cfg.max_seq_len / steps_wall, 1
        ),
        peak_hbm_bytes=int(memory_stats(devices[0])["peak_bytes_in_use"]),
    )

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as d:
        flat = {
            jax.tree_util.keystr(path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                state["params"]
            )
        }
        np.savez(os.path.join(d, "params.npz"), **flat)
        summary["checkpoint_s"] = round(time.perf_counter() - t0, 3)
        train.report(summary, checkpoint=Checkpoint.from_directory(d))


# -------------------------------------------------------------------- main


def smoke(chips: int, emit: Emit) -> Dict[str, Any]:
    """Every stage in order; returns the device the train worker reported."""
    with stage(emit, "detect") as info:
        info["chips_detected"] = detected = detect_chips()
        if detected < chips:
            raise RuntimeError(
                f"{detected} TPU chip(s) detected on this host, {chips} "
                "needed (TPU_VISIBLE_CHIPS, /dev/accel*, /dev/vfio)"
            )
    with stage(emit, "build") as info:
        info["native"] = build_native()

    import ray_tpu
    from ray_tpu._private.chip_entry import place_compile_cache

    emit({"info": "native", "parent": require_native("the parent")})
    cache_dir, was_empty = place_compile_cache()
    emit({"info": "compile_cache", "dir": cache_dir, "was_empty": was_empty})

    storage = tempfile.mkdtemp(prefix="chip_smoke_")
    steps = STEPS if chips == 1 else SHARDED_STEPS
    with stage(emit, "boot") as info:
        ray_tpu.init()
        info["resources"] = resources = ray_tpu.cluster_resources()
    try:
        if resources.get("TPU") != detected:
            raise RuntimeError(
                f"the node advertises {resources}, {detected} chip(s) detected"
            )
        with stage(emit, "train"):
            result = run_training(GPT2_SMALL, BATCH, steps, chips, storage)
        with stage(emit, "check"):
            summary = check_result(result, GPT2_SMALL, steps, chips)
        emit({"info": "worker", **summary})
        for name in ("compile", "steps", "checkpoint"):
            if f"{name}_s" in summary:
                emit({"stage": name, "seconds": summary[f"{name}_s"]})
    finally:
        with stage(emit, "shutdown"):
            ray_tpu.shutdown()
        shutil.rmtree(storage, ignore_errors=True)
    return summary["device"]


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
        with contextlib.suppress(ProcessLookupError):
            os.kill(pid, signal.SIGKILL)
    return killed


def main(argv: List[str]) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=sorted(MESH_AXES), default=1)
    args = parser.parse_args(argv)

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
        raise TimeoutError(f"signal {signum}: the smoke ran out of time")

    signal.signal(signal.SIGALRM, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    signal.alarm(TIME_LIMIT_S)
    try:
        device = smoke(args.chips, emit)
        line = final_line(device)
    except BaseException:  # noqa: BLE001 - the exit code is the report
        traceback.print_exc()
        sys.stderr.flush()
        stop_children(grace_s=5)
        os._exit(1)
    signal.alarm(0)
    killed = stop_children(grace_s=15)
    if killed:
        emit({"info": "killed_after_shutdown", "pids": killed})
    os.write(real_stdout, (line + "\n").encode())
    os._exit(0)  # nothing runs after the last line


if __name__ == "__main__":
    # Run as the module `chip_smoke`, so that the train worker imports
    # `train_loop` by name, from the checkout, as it does under the tests.
    import chip_smoke

    chip_smoke.main(sys.argv[1:])
