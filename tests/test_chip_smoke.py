"""chip_smoke.py on the CPU: its last line, its failure without a chip, its
stages at a tiny size, and the rules it rests on (compile-cache placement,
fail-at-once without a TPU). What only the chip can show is chip_smoke.py's
own job."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import chip_smoke
from ray_tpu._private import chip_entry
from ray_tpu.air import Result, RunConfig, ScalingConfig
from ray_tpu.train import Checkpoint
from ray_tpu.train.jax import JaxTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
            max_seq_len=64, dtype="float32", remat=True)


def test_final_line_has_exactly_the_contract_keys():
    line = chip_smoke.final_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "coords": (0, 0, 0), "seconds": 1.5}  # extras must not leak
    )
    assert "\n" not in line
    obj = json.loads(line)
    assert set(obj) == {"ok", "device"}
    assert set(obj["device"]) == {"platform", "kind", "count"}
    assert obj == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert json.loads(json.dumps(obj)) == obj


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["one", "four"])
def test_command_fails_at_once_without_a_chip(argv):
    env = {k: v for k, v in os.environ.items()
           if k not in ("TPU_VISIBLE_CHIPS", "RAY_TPU_CHIPS")}
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=120, env=env, cwd="/",
    )
    assert out.returncode not in (0, None)
    assert time.monotonic() - t0 < 30  # at detection, not a 120 s time-out
    # Standard output carries the smoke's own JSON lines and nothing else:
    # every byte a child or a library wrote is on stderr.
    for line in out.stdout.splitlines():
        assert "ok" not in json.loads(line)
    assert "TPU chip(s) detected" in out.stderr
    assert "Traceback" in out.stderr


def test_stages_reach_the_worker_device_assertion(tmp_path):
    """The cluster declares a TPU resource over CPU workers, so ingest, the
    placement group, the lease and the gang all go through; the worker's
    first act, the platform assertion, is what fails. Run apart: the gang's
    parent must hold no JAX backend (`run_training` asserts it), and this
    process may long since have initialised the CPU one."""
    code = (
        "import json, sys\n"
        "import chip_smoke, ray_tpu\n"
        "from ray_tpu.testing import cpu_mesh_worker_env\n"
        "from ray_tpu.train import TrainingFailedError\n"
        "ray_tpu.init(num_cpus=8, num_tpus=8,\n"
        "             worker_env=cpu_mesh_worker_env(8))\n"
        "try:\n"
        f"    chip_smoke.run_training({TINY!r}, batch=4, steps=2, chips=1,\n"
        f"                            storage={str(tmp_path)!r})\n"
        "except TrainingFailedError as e:\n"
        "    print(json.dumps({'error': str(e)}))\n"
        "    code = 7\n"
        "else:\n"
        "    code = 0\n"
        "ray_tpu.shutdown()\n"
        "raise SystemExit(code)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 7, out.stderr[-2000:]
    error = json.loads(out.stdout.splitlines()[-1])["error"]
    assert "the train worker sees" in error
    assert "'platform': 'cpu'" in error


def test_use_tpu_without_a_chip_fails_at_once(ray_start_regular, tmp_path):
    trainer = JaxTrainer(
        lambda config: None,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(name="t_no_tpu", storage_path=str(tmp_path)),
    )
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="no node of this cluster advertises"):
        trainer.fit()
    assert time.monotonic() - t0 < 10  # not the placement group's 120 s


def test_check_result_loads_the_checkpoint(tmp_path):
    steps = 2
    n = chip_smoke.param_count(TINY)
    np.savez(tmp_path / "params.npz", a=np.zeros(n - 3, np.float32),
             b=np.ones(3, np.float32))
    summary = {"summary": True, "n_params": n,
               "device": {"platform": "tpu", "kind": "k", "count": 1}}
    history = [{"step": i, "loss": 6.0 - i} for i in range(steps + 1)]

    def result(**kw):
        fields = dict(metrics=summary, checkpoint=Checkpoint(str(tmp_path)),
                      path=str(tmp_path), error=None,
                      metrics_history=history + [summary])
        return Result(**{**fields, **kw})

    assert chip_smoke.check_result(result(), TINY, steps, 1) is summary
    with pytest.raises(RuntimeError, match="step reports"):
        chip_smoke.check_result(
            result(metrics_history=history[1:] + [summary]), TINY, steps, 1)
    with pytest.raises(RuntimeError, match="no checkpoint"):
        chip_smoke.check_result(result(checkpoint=None), TINY, steps, 1)
    np.savez(tmp_path / "params.npz", a=np.zeros(n - 1, np.float32))
    with pytest.raises(RuntimeError, match="checkpoint holds"):
        chip_smoke.check_result(result(), TINY, steps, 1)


def test_param_count_matches_the_model():
    import jax

    from ray_tpu.models.transformer import transformer_init

    for model in (TINY, chip_smoke.GPT2_SMALL):
        shapes = jax.eval_shape(
            lambda: transformer_init(
                jax.random.PRNGKey(0), chip_smoke.make_config(model))
        )
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        assert chip_smoke.param_count(model) == n


def test_flash_checker_in_interpret_mode():
    errors = chip_smoke.check_flash_against_xla(
        shape=(1, 256, 2, 64), interpret=True)
    assert set(errors) == {"out", "dq", "dk", "dv"}
    assert all(e <= chip_smoke.KERNEL_TOLERANCE for e in errors.values())


def test_sharded_comparison_on_virtual_devices():
    """The four-chip phase's own logic on four virtual CPU devices: the
    placement report, the pinned state layout (the executable is reused for
    every step) and the loss comparison."""
    import jax

    devices = jax.devices()[:4]
    tokens = np.random.default_rng(0).integers(0, 512, (8, 65), dtype=np.int32)
    out = chip_smoke.compare_sharded(
        chip_smoke.make_config(TINY), chip_smoke.split_tokens(tokens),
        devices, steps=3, on_chip=False,
    )
    assert out["expected_bytes_per_device"] < 0.6 * out["whole_state_bytes"]
    assert [row[:2] for row in out["token_shards"]] == [
        [0, [2, 64]], [2, [2, 64]], [4, [2, 64]], [6, [2, 64]]]
    assert out["sharded_losses"][-1] < out["sharded_losses"][0]
    assert chip_smoke.check_collectives(devices)["world"] == 4


def test_compile_cache_rule(monkeypatch, tmp_path):
    # set from outside: used as is, nothing set in code
    monkeypatch.setenv(chip_entry.CACHE_ENV, str(tmp_path))
    assert chip_entry.place_compile_cache() == (str(tmp_path), True)
    (tmp_path / "entry").write_text("x")
    assert chip_entry.place_compile_cache() == (str(tmp_path), False)
    assert os.environ[chip_entry.CACHE_ENV] == str(tmp_path)
    # unset: one fixed path inside the checkout, the same on every call
    monkeypatch.delenv(chip_entry.CACHE_ENV)
    path, _ = chip_entry.place_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert os.environ[chip_entry.CACHE_ENV] == path
    monkeypatch.delenv(chip_entry.CACHE_ENV)
    assert chip_entry.place_compile_cache()[0] == path


def test_worker_zygote_preload_imports_no_jax():
    """Every worker is forked from a zygote that has imported worker_main:
    a backend initialised there would be inherited by all of them."""
    code = (
        "import sys\n"
        "from ray_tpu._private import worker_main, worker_zygote\n"
        "raise SystemExit(7 if 'jax' in sys.modules else 0)\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, timeout=120)
    assert out.returncode == 0


def test_no_jax_backend_assertion():
    """Run apart: this process has long since initialised the CPU backend."""
    code = (
        "from ray_tpu._private.chip_entry import assert_no_jax_backend as a\n"
        "a()\n"
        "import jax\n"
        "a()\n"  # importing is allowed
        "jax.devices()\n"
        "try:\n"
        "    a()\n"
        "except RuntimeError:\n"
        "    raise SystemExit(7)\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, timeout=120)
    assert out.returncode == 7
