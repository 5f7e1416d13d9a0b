"""The train path times itself (docs/observability.md, "The train path"):
the spans of `ray_tpu/util/tracing.py` where the train path's work happens,
the `ray_tpu_runtime` block every `train.report` carries, and the record a
slow report interval leaves."""

import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private import telemetry
from ray_tpu.train import _runtime
from ray_tpu.train._session import _TrainSession
from ray_tpu.util import tracing
from ray_tpu.util.state import api as state_api

KEY = _runtime.KEY
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER_SPANS = {
    "jax.trace", "jax.lower", "pallas.trace", "train.before_first_program",
    "jax.compile", "data.pipeline_start", "data.epoch_start",
    "data.batch_produce", "data.block_fetch", "data.batch_assemble",
    "data.finalize", "data.batch_wait", "train.report",
    "train.checkpoint_persist", "py.gc",
}
DRIVER_SPANS = {
    "init", "init.gcs", "init.raylet", "init.worker_pool",
    "train.worker_group_start", "train.backend_start", "train.session_setup",
}
SETUP_SPANS = {  # every one ends before the first report
    "jax.trace", "jax.lower", "jax.compile", "pallas.trace",
    "train.before_first_program",
}
REPORTS = 4


def _make_loop():
    """Defined inside a function, so that it pickles by value."""

    def _loop(config):
        import gc
        import os
        import tempfile

        import jax
        import jax.numpy as jnp

        from ray_tpu import train
        from ray_tpu.ops import flash_attention

        q = jnp.ones((1, 128, 1, 128), jnp.float32)
        jax.jit(lambda q: flash_attention(q, q, q, interpret=True))(q)
        step = jax.jit(lambda x: (x * 2.0).sum())
        shard = train.get_dataset_shard("train")
        for _ in range(REPORTS):  # an epoch a report
            total = 0.0
            for batch in shard.iter_batches(
                    batch_size=8, prefetch_batches=2,
                    _finalize_fn=lambda b: jnp.asarray(b["id"], jnp.float32)):
                total += float(step(batch))
            gc.collect()  # a process that holds jax takes milliseconds over it
            with tempfile.TemporaryDirectory() as d:
                with open(os.path.join(d, "w"), "w") as f:
                    f.write("x")
                train.report({"total": total},
                             checkpoint=train.Checkpoint.from_directory(d))

    return _loop


def _fit(tmp_path):
    from ray_tpu import data as rd
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    return JaxTrainer(
        _make_loop(),
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="spans", storage_path=str(tmp_path)),
        datasets={"train": rd.range(32, parallelism=4)},
    ).fit()


@pytest.fixture(scope="module")
def untraced_run(tmp_path_factory):
    """One tiny JaxTrainer run on the CPU through ray_tpu.data, tracing off."""
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        result = _fit(tmp_path_factory.mktemp("untraced"))
        time.sleep(1.0)  # a span flusher, were one running, would have fired
        return result, state_api.list_spans(), tracing.snapshot()
    finally:
        ray_tpu.shutdown()


def test_every_report_carries_the_block(untraced_run):
    result = untraced_run[0]
    assert len(result.metrics_history) == REPORTS
    for metrics in result.metrics_history:
        block = metrics[KEY]
        assert {"total", "since_first_report", "interval", "counters",
                "rusage"} <= set(block)
        assert {"ru_nivcsw", "ru_nvcsw", "ru_majflt", "ru_utime",
                "ru_stime"} <= set(block["rusage"])
    assert result.metrics[KEY] is result.metrics_history[-1][KEY]
    assert result.metrics["total"] == sum(range(32)) * 2.0


def test_the_block_holds_every_span_of_the_train_path(untraced_run):
    block = untraced_run[0].metrics[KEY]
    assert WORKER_SPANS <= set(block["total"])
    assert DRIVER_SPANS <= set(block["driver"])
    for table in (block["total"], block["driver"]):
        for count, seconds, longest, when in table.values():
            assert count >= 1 and seconds >= longest >= 0.0
    count, seconds, longest, when = block["driver"]["init"]
    assert count == 1 and seconds > 0 and abs(when - time.time()) < 600
    assert block["total"]["data.pipeline_start"][0] == REPORTS
    assert block["total"]["train.report"][0] == REPORTS - 1  # the last is open
    assert block["total"]["train.checkpoint_persist"][0] == REPORTS
    assert block["total"]["data.batch_produce"][0] >= 4 * REPORTS
    assert block["counters"]["compile.programs"] >= 1
    assert block["counters"]["compile.programs"] == block["total"]["jax.compile"][0]


def test_since_first_report_is_total_less_the_first_reports(untraced_run):
    history = untraced_run[0].metrics_history
    first, last = history[0][KEY], history[-1][KEY]
    assert first["since_first_report"] == {}
    assert first["interval"] == first["total"]
    for name, (count, seconds, longest, when) in last["total"].items():
        began = first["total"].get(name, [0, 0.0])
        steady = last["since_first_report"].get(name, [0, 0.0, 0.0, 0.0])
        assert steady[0] == count - began[0]
        assert steady[1] == pytest.approx(seconds - began[1], abs=1e-9)
        assert steady[2] <= longest
    # tracing, lowering and compiling are set-up's: the steady state holds none
    assert not SETUP_SPANS & set(last["since_first_report"])
    assert last["interval"]["data.pipeline_start"][0] == 1


def test_what_comes_before_a_program_runs_is_in_the_block(untraced_run):
    total = untraced_run[0].metrics[KEY]["total"]
    # once a session: the train function called to the first trace's start
    count, seconds, longest, when = total["train.before_first_program"]
    assert count == 1 and 0.0 <= seconds == longest < 60.0
    assert total["pallas.trace"][0] == 1  # one kernel call site traced
    assert total["jax.trace"][1] > total["pallas.trace"][1] > 0.0
    # every program compiled was lowered, and every one lowered was traced
    assert (total["jax.trace"][0] >= total["jax.lower"][0]
            >= total["jax.compile"][0] >= 2)
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        assert total[name][1] >= total[name][2] > 0.0  # self time, not below 0


def test_an_inner_jit_s_trace_counts_once():
    """JAX reports the inner `jit`'s trace and then the outer's, which
    holds it: the table's seconds are each one's own."""
    import jax

    from ray_tpu.train._backend_executor import _watch_compiles

    _watch_compiles()

    @jax.jit
    def inner(x):
        time.sleep(0.2)
        return x

    @jax.jit
    def outer(x):
        time.sleep(0.1)
        return inner(x)

    x = jax.ShapeDtypeStruct((3,), "float32")
    before = tracing.table().get("jax.trace", [0, 0.0])
    t0 = time.perf_counter()
    outer.trace(x)
    wall = time.perf_counter() - t0
    count, seconds, longest, when = tracing.table()["jax.trace"]
    assert count - before[0] == 2
    assert 0.3 <= seconds - before[1] <= wall
    assert wall - (seconds - before[1]) < 0.1  # twice the inner's would be 0.2
    assert abs(when - time.time()) < 60


def test_observe_takes_the_span_s_own_end_and_its_self_time(monkeypatch):
    monkeypatch.setattr(tracing.config, "task_trace_spans", True)
    tracing.reset()
    token = tracing.set_context(("t" * 16, "p" * 16))
    try:
        ended = time.time() - 5.0
        tracing.table(mark=True)
        tracing.observe("test.observed", 2.0, end=ended, inside=0.5, function="f")
    finally:
        tracing.reset_context(token)
    count, seconds, longest, when = tracing.table(mark=True)["test.observed"]
    assert (count, seconds, longest) == (1, 1.5, 1.5)
    assert abs(when - ended) < 0.05
    (span,) = [s for s in tracing.snapshot() if s["name"] == "test.observed"]
    tracing.reset()
    # the ring takes the whole span, where it happened
    assert span["duration"] == 2.0 and span["function"] == "f"
    assert abs(span["start"] - (ended - 2.0)) < 0.05
    assert abs(span["time"] - ended) < 0.05


def test_with_tracing_off_the_ring_receives_nothing(untraced_run):
    _, listed, local = untraced_run
    assert listed == [] and local == []


def test_with_tracing_on_the_new_spans_reach_list_spans(
        monkeypatch, shutdown_only, tmp_path):
    monkeypatch.setenv("RAY_TPU_TASK_TRACE_SPANS", "1")
    ray_tpu.init(num_cpus=4, num_tpus=0)
    _fit(tmp_path)
    want = {"train.report", "train.checkpoint_persist", "data.pipeline_start",
            "data.batch_wait", "data.batch_produce", "data.block_fetch",
            "data.batch_assemble", "data.finalize", "jax.compile",
            "jax.trace", "jax.lower", "pallas.trace",
            "train.before_first_program"}
    deadline = time.time() + 25
    while time.time() < deadline:
        spans = state_api.list_spans()
        if want <= {s["name"] for s in spans}:
            break
        time.sleep(0.25)
    by_name = {s["name"]: s for s in spans}
    assert want <= set(by_name), sorted(want - set(by_name))
    # the prefetch thread's spans join the trace of the loop's thread
    assert (by_name["data.batch_produce"]["trace_id"]
            == by_name["train.report"]["trace_id"])
    assert by_name["jax.compile"]["cache"] in ("hit", "miss", "none")
    assert by_name["pallas.trace"]["kernel"] == "flash_fwd"
    # a span that `jax.monitoring` told of lies where it happened: the trace
    # of the program that holds the kernel around the kernel's, its lowering
    # after its trace, its compilation after that; and the first trace of
    # all begins where `train.before_first_program` ends
    kernel = by_name["pallas.trace"]
    trace = min((s for s in spans if s["name"] == "jax.trace"
                 and s["start"] <= kernel["start"]
                 and kernel["time"] <= s["time"]), key=lambda s: s["start"])
    lower, compiled = (
        min((s for s in spans if s["name"] == name
             and s["function"] == f"jit({trace['function']})"
             and s["start"] >= trace["time"]), key=lambda s: s["start"])
        for name in ("jax.lower", "jax.compile"))
    assert trace["time"] <= lower["start"] <= lower["time"] <= compiled["start"]
    first = min(s["start"] for s in spans if s["name"] == "jax.trace")
    assert by_name["train.before_first_program"]["time"] == pytest.approx(
        first, abs=1e-3)


def test_the_table_ends_with_the_cluster(shutdown_only):
    """A process that starts a second cluster (a test worker under `--dist
    loadfile`, a notebook) reports that cluster's `init` alone."""
    ray_tpu.init(num_cpus=1, num_tpus=0)
    tracing.count("compile.programs")
    assert tracing.table()["init"][0] >= 1
    ray_tpu.shutdown()
    assert not DRIVER_SPANS & set(tracing.table())
    assert "compile.programs" not in tracing.counters()
    ray_tpu.init(num_cpus=1, num_tpus=0)
    count, seconds, longest, when = tracing.table()["init"]
    assert count == 1 and seconds == longest > 0


def test_init_leaves_jax_unimported():
    code = ("import sys, ray_tpu; ray_tpu.init(num_cpus=1, num_tpus=0);"
            "from ray_tpu.util import tracing;"
            "assert tracing.table()['init'][0] == 1;"
            "ray_tpu.shutdown(); sys.exit('jax' in sys.modules)")
    done = subprocess.run([sys.executable, "-c", code], timeout=120)
    assert done.returncode == 0


_TWO_JOBS = """
import json, sys, time
time.sleep(0.5)
import ray_tpu
from ray_tpu.air import ScalingConfig
from ray_tpu.train.jax import JaxTrainer

def loop():
    from ray_tpu import train
    train.report({"x": 1})

def job():
    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        fit = JaxTrainer(loop, scaling_config=ScalingConfig(num_workers=1)).fit()
        return fit.metrics["ray_tpu_runtime"]["driver"]
    finally:
        ray_tpu.shutdown()

print(json.dumps([job(), job()]))
"""


def test_the_driver_s_start_is_in_the_first_job_s_table_alone(tmp_path):
    """`process.before_init` and `import.ray_tpu` happen once a process:
    the cluster that the process starts first reports them."""
    done = subprocess.run(
        [sys.executable, "-c", _TWO_JOBS], timeout=300, cwd=str(tmp_path),
        stdout=subprocess.PIPE, env={**os.environ, "PYTHONPATH": ROOT})
    assert done.returncode == 0
    first, second = json.loads(done.stdout.splitlines()[-1])
    assert first["process.before_init"][0] == first["import.ray_tpu"][0] == 1
    # the sleep before the import, the import, and the interpreter's start
    assert (30.0 > first["process.before_init"][1]
            > 0.5 + first["import.ray_tpu"][1] > 0.5)
    assert DRIVER_SPANS <= set(second)
    assert not {"process.before_init", "import.ray_tpu"} & set(second)


def _slow_events():
    return [fields for _, component, event, fields in
            telemetry.flight().snapshot()
            if (component, event) == ("train", "slow_interval")]


def test_a_slow_interval_leaves_one_record_that_names_the_span(caplog):
    telemetry.flight().clear()
    session = _TrainSession()
    for i in range(7):
        time.sleep(0.02)
        session.report({"i": i})
    assert _slow_events() == []
    with tracing.span("data.batch_wait"):
        time.sleep(0.4)
    with caplog.at_level("WARNING", logger=_runtime.__name__):
        session.report({"i": 7})
    for i in range(3):
        time.sleep(0.02)
        session.report({"i": 8 + i})
    (record,) = _slow_events()
    assert record["seconds"] > 0.4 > 3 * record["median"]
    count, seconds, longest, when = record["spans"]["data.batch_wait"]
    assert count == 1 and longest == seconds >= 0.4
    assert abs(when - time.time()) < 60
    assert set(record) == {"seconds", "median", "report", "spans", "gc_s",
                           "rusage", "compiles"}
    assert record["report"] == 7 and record["compiles"] == 0
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1 and "slow_interval" in lines[0]
    assert "data.batch_wait" in lines[0]


def test_a_users_own_key_is_kept():
    session = _TrainSession()
    session.report({KEY: "mine", "x": 1})
    assert session.result_queue.get_nowait().metrics == {KEY: "mine", "x": 1}
    session.report({"x": 2})
    assert set(session.result_queue.get_nowait().metrics[KEY]) >= {"total"}


def test_the_longest_is_the_interval_s_own():
    """Counts and seconds are differences of two readings; the longest is
    kept since the reader's last mark, whatever came before."""
    tracing.table(mark=True)
    with tracing.span("test.longest"):
        time.sleep(0.05)
    assert tracing.table(mark=True)["test.longest"][2] >= 0.05
    with tracing.span("test.longest"):
        pass
    count, seconds, longest, when = tracing.table(mark=True)["test.longest"]
    assert count == 2 and seconds >= 0.05 > longest > 0.0
    assert tracing.table()["test.longest"][2:] == [0.0, 0.0]


def test_a_dead_thread_s_spans_stay_in_the_table():
    import threading

    def work():
        with tracing.span("test.thread"):
            pass

    for _ in range(3):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    assert tracing.table()["test.thread"][0] == 3
    assert tracing.table()["test.thread"][0] == 3  # folded once, not again
    assert all(thread.is_alive() for thread, _ in tracing._tables.values())


def test_a_span_s_dictionary_is_built_in_one_place():
    import inspect

    source = inspect.getsource(tracing)
    assert source.count('"parent_span_id":') == 1  # `_emit`, for every scope
    for scope in (tracing.span_scope("a", "k"), tracing.root_scope("a", "k"),
                  tracing.span("a")):
        assert isinstance(scope, tracing.Span)


def test_no_span_is_lost_while_the_table_is_read():
    """More threads than cores add to their own tables, end, and are folded,
    while a reader merges and marks as fast as it can."""
    import os
    import threading

    threads, spans_each = 2 * (os.cpu_count() or 4), 2000
    before = tracing.table().get("test.stress", [0])[0]
    done = threading.Event()
    seen = []

    def work():
        for _ in range(spans_each):
            with tracing.span("test.stress"):
                pass
        tracing.count("test.stress", 1)

    def read():
        while not done.is_set():
            seen.append(tracing.table(mark=True).get("test.stress", [0])[0])

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read)
        reader.start()
        workers = [threading.Thread(target=work) for _ in range(threads)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(timeout=60)
        done.set()
        reader.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not reader.is_alive() and not any(t.is_alive() for t in workers)
    assert tracing.table()["test.stress"][0] - before == threads * spans_each
    assert tracing.counters()["test.stress"] >= threads
    assert seen == sorted(seen)  # a reading never goes back


# --- the step accounts for itself: `tracing.Step`, its `tracing.Account` ---

ROUTED = dict(
    vocab_size=128, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=16,
    max_seq_len=32, n_experts=8, experts_per_token=2, norm_topk_prob=True,
    tied_embeddings=False, attention_impl="xla", router_aux_loss_coef=0.01,
    remat=True)
# (what a routed layer holds, the mesh's axis and its devices)
ROUTED_CELLS = {
    "share": ((2, 2), "data", 1),       # two of eight experts: `held_slots`
    "all_held": (None, "data", 1),      # olmoe's shape: no `moe.held_*`
    "expert_axis": (None, "expert", 4),  # a chip a share: `chip_load`
}


class _Job:
    """A tiny routed train step on the CPU, its state and one batch."""

    def __init__(self, held, axis, ways):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu.models import TransformerConfig, make_train_step
        from ray_tpu.ops import moe
        from ray_tpu.parallel import make_mesh

        if len(jax.devices()) < ways:
            pytest.skip(f"{ways} devices for the `{axis}` axis")
        self.asked = []  # what the routed layers' trace asked of `held_chunk`
        held_chunk = moe.held_chunk

        def recorded(*args, **kwargs):
            self.asked.append(held_chunk(*args, **kwargs))
            return self.asked[-1]

        self.cfg = TransformerConfig(
            **ROUTED, dtype=jnp.float32, experts_held=held)
        mesh = make_mesh({axis: ways}, jax.devices()[:ways])
        init, self.step, shardings = make_train_step(
            self.cfg, mesh, optax.adamw(1e-3))
        self.state = init(jax.random.PRNGKey(0))
        self.batch = {"tokens": jax.device_put(
            jax.random.randint(jax.random.PRNGKey(2), (4, 33), 0, 128),
            shardings["tokens"])}
        with pytest.MonkeyPatch.context() as patch:
            # buffers of a few rows, so that a layer walks a second chunk
            patch.setattr(moe, "_ROW_TILE", 8)
            patch.setattr(moe, "held_chunk", recorded)
            self.lowered = self.step.lower(self.state, self.batch).as_text()
            self.run(1)

    def run(self, steps):
        """`steps` steps, waited for on every device (`device_get` alone
        waits for the one it reads from); their readings on the host."""
        import jax

        outs = []
        for _ in range(steps):
            self.state, out = self.step(self.state, self.batch)
            outs.append(out)
        return jax.device_get(jax.block_until_ready(outs))


@pytest.fixture(scope="module")
def jobs():
    made = {}

    def job(name):
        if name not in made:
            made[name] = _Job(*ROUTED_CELLS[name])
        return made[name]

    return job


def _moe(counters):
    return {k: v for k, v in counters.items()
            if k.startswith("moe.") or k == "train.steps_read"}


def _by_hand(outs, chunk):
    """The counters the step's account names (`ops/moe.py` `layer_steps`),
    summed from steps' outputs."""
    import numpy as np

    want = dict.fromkeys((
        "moe.layer_steps", "moe.fullest_expert_slots",
        "moe.even_expert_slots", "moe.held_slots", "moe.dropped_slots",
        "moe.held_rows", "moe.buffer_rows", "moe.extra_chunk_layer_steps",
        "moe.chip_load_max_over_mean_sum"), 0)
    want["train.steps_read"] = len(outs)
    for out in outs:
        for layer, load in enumerate(out["expert_load"]):
            want["moe.layer_steps"] += 1
            want["moe.fullest_expert_slots"] += int(max(load))
            want["moe.even_expert_slots"] += sum(map(int, load)) / len(load)
            if "held_slots" not in out:
                continue
            held = np.atleast_1d(out["held_slots"][layer])  # a device each
            dropped = np.atleast_1d(out["dropped_slots"][layer])
            chunks = [max(1, -(-int(h - d) // chunk))
                      for h, d in zip(held, dropped)]
            want["moe.held_slots"] += int(held.sum())
            want["moe.dropped_slots"] += int(dropped.sum())
            want["moe.held_rows"] += int((held - dropped).sum())
            want["moe.buffer_rows"] += sum(chunks) * chunk
            want["moe.extra_chunk_layer_steps"] += max(chunks) > 1
        if "chip_load_max_over_mean" in out:
            load = out["chip_load"].astype(float)  # [L, chips]
            want["moe.chip_load_max_over_mean_sum"] += float(
                (load.max(-1) / load.mean(-1)).mean())
    return want


@pytest.mark.parametrize("cell", ROUTED_CELLS)
def test_the_step_is_the_jitted_function_under_a_span(jobs, cell):
    import jax

    job = jobs(cell)
    step = job.step
    assert isinstance(step, tracing.Step)
    bare = step._jitted
    assert step.lower.__self__ is bare and step.trace.__self__ is bare
    assert job.lowered == bare.lower(job.state, job.batch).as_text()
    calls = tracing.table().get("train.step", [0])[0]
    cached, kept = step._cache_size(), list(tracing._steps)
    # traced through, a step ran nothing and leaves the account nothing
    shapes = jax.eval_shape(step, job.state, job.batch)[1]
    assert shapes["loss"].shape == () and list(tracing._steps) == kept
    number = step.calls
    job.run(3)
    assert step.calls == number + 3 and step._cache_size() == cached
    assert tracing.table()["train.step"][0] == calls + 1 + 3
    assert [n for n, _, _ in tracing._steps][-3:] == [
        number + 1, number + 2, number + 3]
    # the rows of a share's buffers are what its layers' trace asked for
    share = cell != "all_held"
    assert set(job.asked) == ({step.static["held_chunk"]} if share else set())
    assert set(step.static) == ({"held_chunk"} if share else set())
    from ray_tpu.models import transformer
    assert step.account is transformer._STEP_ACCOUNT


@pytest.mark.parametrize("cell", ROUTED_CELLS)
def test_a_report_folds_the_steps_readings_into_the_counters(jobs, cell):
    job = jobs(cell)
    account = _runtime.RuntimeAccount()
    outs = job.run(5)
    block = account.block()
    want = _by_hand(outs, job.step.static.get("held_chunk"))
    got = _moe(block["counters"])
    assert set(got) <= set(want)
    assert {k: got.get(k, 0) for k in want} == pytest.approx(want)
    assert got["train.steps_read"] == block["total"]["train.step"][0] == 5
    share = cell != "all_held"
    assert (got.get("moe.held_rows", 0) > 0) == share
    assert ("moe.chip_load_max_over_mean_sum" in got) == (
        cell == "expert_axis")
    if share:  # tiles of 8 rows: a layer-step in two walks a second chunk
        assert 0 < got["moe.extra_chunk_layer_steps"] < got["moe.layer_steps"]
        assert got["moe.held_rows"] < got["moe.buffer_rows"]
        assert got["moe.dropped_slots"] == 0
    # a row a step of a share: [step, chunks a layer, held rows a layer]
    assert len(block["steps"]) == (5 if share else 0)
    for (number, chunks, rows), out in zip(block["steps"], outs):
        assert len(chunks) == len(rows) == job.cfg.n_layers
        held = (out["held_slots"] - out["dropped_slots"]).reshape(
            job.cfg.n_layers, -1).max(-1)
        assert rows == held.tolist()
    assert [row[0] for row in block["steps"]] == list(
        range(job.step.calls - 4, job.step.calls + 1))[:len(block["steps"])]
    # what no counter sums, as the last step left it
    assert block["readings"]["grad_norm"] == pytest.approx(
        float(outs[-1]["grad_norm"]))
    # ... and what the step's account takes whole is not shown
    taken = {name for name, shown in job.step.account.reads.items()
             if not shown}
    assert taken == {"expert_load", "held_slots", "dropped_slots",
                     "chip_load_max_over_mean"}
    assert not set(block["readings"]) & taken
    json.dumps(block)  # numbers and lists: a report's metrics


class _Late:
    """A reading the device has not made yet, until `ready` is set."""

    def __init__(self, array):
        self.array, self.ready, self.size = array, False, array.size

    def is_ready(self):
        return self.ready

    def is_deleted(self):
        return False

    def __array__(self, *args, **kwargs):
        assert self.ready, "the account waited for the device"
        return self.array.__array__(*args, **kwargs)


def test_a_step_that_is_not_ready_waits_for_the_next_report(jobs):
    job = jobs("share")
    account = _runtime.RuntimeAccount()
    before = _moe(tracing.counters())
    first, late, third = job.run(3)
    first, late, third = list(tracing._steps)[-3:]
    tracing._steps.clear()
    held = {k: _Late(v) for k, v in late[2].items()}
    tracing._steps.extend([first, (late[0], late[1], held), third])
    block = account.block()
    read = {k: v - before[k] for k, v in _moe(tracing.counters()).items()}
    assert read["train.steps_read"] == 1  # the third stands behind the late
    assert [row[0] for row in block["steps"]] == [first[0]]
    assert len(account._unread) == 2 and not tracing._steps
    assert account.block()["steps"] == block["steps"]  # still not ready
    for reading in held.values():
        reading.ready = True
    block = account.block()
    assert [row[0] for row in block["steps"]] == [first[0], late[0], third[0]]
    assert block["counters"]["train.steps_read"] == 3
    assert not account._unread


def test_with_no_session_the_steps_kept_are_bounded():
    import jax

    step = tracing.Step(
        jax.jit(lambda state, batch: (state + 1, {"loss": batch.sum()})), {})
    tracing.take_steps()
    state = 0
    for _ in range(tracing.STEPS_KEPT + 6):
        state, out = step(state, jax.numpy.ones(3))
    assert int(state) == step.calls == tracing.STEPS_KEPT + 6
    assert [n for n, _, _ in tracing._steps] == list(
        range(7, tracing.STEPS_KEPT + 7))
    taken = tracing.take_steps()
    assert len(taken) == tracing.STEPS_KEPT and not tracing._steps
    # a session's account begins with none of them
    step(state, jax.numpy.ones(3))
    account = _runtime.RuntimeAccount()
    assert not tracing._steps and not account._unread
    # a reading deleted since is dropped, and the report stands: the first
    # of a step's, which is the one asked whether it is ready, or another
    both = tracing.Step(jax.jit(lambda state, batch: (
        state + 1, {"loss": batch.sum(), "grad_norm": batch.max()})), {})
    for made, name in ((step, "loss"), (both, "grad_norm")):
        _, out = made(state, jax.numpy.ones(3))
        out[name].delete()
        block = account.block()
        assert block["counters"].get("train.steps_read", 0) == 0
        assert not account._unread and block["readings"] == {}


def test_counters_since_first_report_leave_the_first_reports_out(jobs):
    job = jobs("share")
    session = _TrainSession()
    job.run(2)
    session.report({"i": 0})
    job.run(3)
    session.report({"i": 1})
    first, last = (session.result_queue.get_nowait().metrics[KEY]
                   for _ in range(2))
    assert first["counters"]["train.steps_read"] == 2
    assert not any(first["counters_since_first_report"].values())
    assert last["counters"]["train.steps_read"] == 5
    steady = last["counters_since_first_report"]
    assert steady["train.steps_read"] == 3
    assert steady["train.steps_read"] == last["since_first_report"][
        "train.step"][0]
    for name, n in _moe(last["counters"]).items():
        assert steady[name] == pytest.approx(n - first["counters"][name])
    assert steady["moe.layer_steps"] == 3 * job.cfg.n_layers
    assert [row[0] for row in last["steps"]][-3:] == list(
        range(job.step.calls - 2, job.step.calls + 1))


def test_a_slow_interval_says_what_its_steps_routed(jobs):
    job = jobs("share")
    telemetry.flight().clear()
    session = _TrainSession()
    for i in range(7):
        time.sleep(0.02)
        session.report({"i": i})
    outs = job.run(2)
    time.sleep(0.4)
    session.report({"i": 7})
    (record,) = _slow_events()
    want = _by_hand(outs, job.step.static["held_chunk"])
    assert record["steps"] == pytest.approx(
        {k: v for k, v in want.items() if v and k.startswith("moe.")})
    assert record["spans"]["train.step"][0] == 2


# --- the runtime folds by the step's own account, and knows no model ---

def _step_of(readings_of, account=None):
    """A `tracing.Step` whose `i`-th call reads `readings_of(i)`."""
    import jax

    jitted = jax.jit(lambda i, _: (i + 1, readings_of(i)))
    return tracing.Step(jitted, {"scale": 10}, account)


def test_a_step_is_folded_by_its_own_account_and_one_without_by_none():
    import jax.numpy as jnp

    def fold(static, stacked):
        assert set(stacked) == {"x", "y"}  # those of `reads` the steps made
        assert stacked["x"].shape == (3, 2)
        return ({"x.sum": stacked["x"].sum().item() * static["scale"]},
                [[y] for y in stacked["y"].tolist()])

    mine = tracing.Account({"x": True, "y": False, "absent": False}, fold)
    step = _step_of(lambda i: {
        "loss": 1.0 / (i + 1), "x": jnp.stack([i, 2 * i]), "y": i + 0.5}, mine)
    bare = _step_of(lambda i: {"loss": 3.0 - i, "x": i})
    account = _runtime.RuntimeAccount()
    before = tracing.counters()
    state = 1
    for _ in range(3):
        state, _ = step(state, 0)
    block = account.block()
    assert block["counters"]["x.sum"] == (1 + 2 + 3) * 3 * 10
    assert block["counters"]["train.steps_read"] == 3
    # `y` is the account's whole; `x` is summed and the last one's shown
    assert block["readings"] == {"loss": pytest.approx(1 / 4), "x": [3, 6]}
    assert block["steps"] == [[1, 1.5], [2, 2.5], [3, 3.5]]
    assert account._accounted == {"x.sum"}
    for _ in range(2):
        bare(5, 0)
    block = account.block()
    assert block["counters"]["train.steps_read"] == 5
    assert block["counters"]["x.sum"] == 180  # a reading's name is nothing
    assert block["readings"] == {"loss": -2.0, "x": 5}
    assert len(block["steps"]) == 3
    assert set(tracing.counters()) - set(before) <= {
        "x.sum", "train.steps_read"}


def test_the_runtime_names_no_reading_and_no_counter_of_a_model():
    """`ray_tpu/train/` folds by what a step hands it: no name of a model's
    readings outside prose, no import of the model or the kernels."""
    import ast
    import glob
    import io
    import tokenize

    path = os.path.join(ROOT, "ray_tpu", "train", "_runtime.py")
    with open(path) as f:
        source = f.read()
    docstrings = {
        (node.body[0].lineno, node.body[0].col_offset)
        for node in ast.walk(ast.parse(source))
        if isinstance(node, (ast.Module, ast.FunctionDef, ast.ClassDef))
        and ast.get_docstring(node, clean=False) is not None}
    code = [tok.string for tok in tokenize.generate_tokens(
        io.StringIO(source).readline)
        if tok.type != tokenize.COMMENT and tok.start not in docstrings]
    for word in ("expert", "moe", "diffusion", "held"):
        assert not [t for t in code if word in t.lower()], word
    for module in glob.glob(
            os.path.join(ROOT, "ray_tpu", "train", "**", "*.py"),
            recursive=True):
        with open(module) as f:
            for node in ast.walk(ast.parse(f.read())):
                names = ([a.name for a in node.names]
                         if isinstance(node, ast.Import) else
                         [node.module or ""]
                         if isinstance(node, ast.ImportFrom) else [])
                for name in names:
                    assert not name.startswith(
                        ("ray_tpu.models", "ray_tpu.ops")), (module, name)


# What PR 71's `_fold_steps` (the parent's, which named the readings itself)
# made of these very readings: three steps, numbered 7 to 9, of three routed
# layers over four experts with buffers of 16 rows, held rows of none, one
# under, at and one over a whole chunk, and three slots dropped.
_HELD = [[[0, 15], [16, 17], [33, 5]], [[15, 15], [16, 0], [32, 31]],
         [[17, 16], [1, 0], [48, 49]]]
_LOAD = [[[5, 3, 9, 7], [6, 6, 6, 6], [0, 12, 1, 11]],
         [[4, 4, 8, 8], [2, 10, 6, 6], [7, 5, 9, 3]],
         [[6, 7, 5, 6], [1, 1, 11, 11], [12, 0, 0, 12]]]
_ROUTED = {"train.steps_read": 3, "moe.layer_steps": 9,
           "moe.fullest_expert_slots": 84, "moe.even_expert_slots": 54.0}
_LAST = {"loss": 2.0, "grad_norm": 1.25, "aux_loss": 0.25}
PARENT_BLOCKS = {
    "expert_axis": {
        "counters": {
            **_ROUTED, "moe.chip_load_max_over_mean_sum": 3.527777910232544,
            "moe.held_slots": 326, "moe.held_rows": 323,
            "moe.dropped_slots": 3, "moe.buffer_rows": 464,
            "moe.extra_chunk_layer_steps": 5},
        "readings": {**_LAST, "chip_load": [[13, 11], [2, 22], [12, 12]]},
        "steps": [[7, [1, 2, 3], [15, 17, 33]], [8, [1, 1, 2], [15, 16, 32]],
                  [9, [2, 1, 4], [17, 1, 49]]]},
    "share_under_block_diffusion": {
        "counters": {
            **_ROUTED, "moe.held_slots": 178, "moe.held_rows": 175,
            "moe.dropped_slots": 3, "moe.buffer_rows": 240,
            "moe.extra_chunk_layer_steps": 4, "diffusion.tokens": 192,
            "diffusion.masked_tokens": 93, "diffusion.weight_sum": 183.0,
            "diffusion.rows": 384},
        "readings": {
            **_LAST, "diffusion_tokens": 64, "diffusion_masked_tokens": 32,
            "diffusion_weight_sum": 61.5, "diffusion_rows": 128},
        "steps": [[7, [1, 1, 3], [0, 16, 33]], [8, [1, 1, 2], [15, 16, 29]],
                  [9, [2, 1, 3], [17, 1, 48]]]},
}


def _parent_s_readings(cell, s):
    """Step `s`'s readings, as PR 72's session gave them to the parent's
    `_fold_steps` to take `PARENT_BLOCKS`."""
    import numpy as np

    held, load = np.array(_HELD, np.int32), np.array(_LOAD, np.int32)
    dropped = np.zeros_like(held)
    dropped[1, 2, 1] = 3
    readings = {
        "loss": np.float32(2.5 - s / 4), "grad_norm": np.float32(1 + s / 8),
        "aux_loss": np.float32(0.125 * s), "expert_load": load[s]}
    if cell == "expert_axis":
        chip = load.reshape(3, 3, 2, 2).sum(-1)[s]
        readings.update(
            held_slots=held[s], dropped_slots=dropped[s], chip_load=chip,
            chip_load_max_over_mean=(
                chip.max(-1) / chip.mean(-1)).astype(np.float32))
    else:
        readings.update(
            held_slots=held[s, :, 0], dropped_slots=dropped[s, :, 1],
            diffusion_tokens=np.int32(64),
            diffusion_masked_tokens=np.int32(30 + s),
            diffusion_weight_sum=np.float32(60.5 + s / 2),
            diffusion_rows=np.int32(128))
    return readings


@pytest.mark.parametrize("cell", PARENT_BLOCKS)
def test_the_model_s_account_makes_the_block_the_runtime_s_own_made(cell):
    import jax

    from ray_tpu.models import transformer

    step = tracing.Step(
        jax.jit(lambda s, readings: (s, readings)), {"held_chunk": 16},
        transformer._STEP_ACCOUNT)
    step.calls = 6
    account = _runtime.RuntimeAccount()
    before = tracing.counters()
    for s in range(3):
        step(s, _parent_s_readings(cell, s))
    block = account.block()
    want = PARENT_BLOCKS[cell]
    risen = {k: v - before.get(k, 0) for k, v in tracing.counters().items()
             if k.startswith(("moe.", "diffusion.", "train.steps_read"))}
    risen = {k: v for k, v in risen.items() if v}
    assert risen == want["counters"]
    assert {k: type(v) for k, v in risen.items()} == {
        k: type(v) for k, v in want["counters"].items()}
    assert block["readings"] == want["readings"]
    assert block["steps"] == want["steps"]
    assert account._accounted == set(want["counters"]) - {"train.steps_read"}
    json.dumps(block)
