"""Host seconds a cell's programs take to trace and lower, with no chip: the
step (`family.step.lower`) and the comparison's `value_and_grad(system_loss)`
at the check's shapes, for a described v5e 2x2, compile cache off. It is what
`setup_s` pays outside any `jax.compile` span, warm or cold (PERF.md section
5); the chip's host is about half as fast as a sandbox's CPU.

    JAX_PLATFORMS=cpu python3 benchmarks/lowering_seconds.py \
        lfm2moe.tokens8k dsv2lite.tokens8k [--tree .parent_tree]

A line a (cell, program): the seconds of each of `--repeat` lowerings, JAX's
caches cleared before each (the first also pays the process's imports), their
least, the Pallas payloads in the lowered text by kernel name, and the text's
sha256 with the payloads blanked and as they are (two trees whose first agree
lower the same program but for the kernels' source locations; two processes
of one tree must agree on both, or the compile cache never hits).

With `--plan DIR` the step is also compiled, once, and a line says what the
compiler plans for the chip: all its bytes and the scratch among them, the
`.remat` fusions it made to fit, the seconds of the compile; DIR takes XLA's
dump of the step's module, whose `*buffer-assignment.txt` and
`*memory-usage-report.txt` name every buffer (PERF.md section 7, PR 58).
`--keep none` (or `--keep attn_ctx,attn_res`) takes the keep rule's place
with that choice of names: the plan of the step that keeps nothing is what
a new cell is sized by before anything else is built on it (PR 74).
"""

import argparse
import collections
import glob
import hashlib
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("cells", nargs="+")
    parser.add_argument("--tree", default=".", help="the checkout to lower")
    parser.add_argument("--repeat", type=int, default=3)
    parser.add_argument("--plan", metavar="DIR", help="compile the step too "
                        "and dump its module's buffer assignment here")
    parser.add_argument("--keep", default="rule", help="the names a "
                        "rematerialised block keeps: the rule's own, 'none' "
                        "or names with commas between")
    args = parser.parse_args()
    root = os.path.abspath(args.tree)
    sys.path.insert(0, root)
    if args.plan:  # read when the backend starts: before jax is imported
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + (
            f" --xla_dump_to={os.path.abspath(args.plan)}"
            " --xla_dump_hlo_as_text --xla_dump_hlo_module_re=.*step.*")

    import jax
    from jax.experimental import topologies

    from chipbench import loop, spec
    from ray_tpu.models import transformer

    jax.config.update("jax_enable_compilation_cache", False)
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    # a described device reports no limit; the chip's goes to the keep rule
    transformer._memory_limit = lambda mesh: 16_910_000_000
    if args.keep != "rule":  # in the rule's place, for what a plan would be
        kept = {} if args.keep == "none" else dict.fromkeys(
            args.keep.split(","), 1)
        transformer.saved_activations = lambda *a, **kw: dict(kept)

    def described(shapes, shardings):
        if isinstance(shardings, jax.sharding.Sharding):  # one for the lot
            shardings = jax.tree.map(lambda x: shardings, shapes)
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            shapes, shardings)

    def report(cell, program, lower):
        seconds = []
        for _ in range(args.repeat):
            jax.clear_caches()
            t0 = time.perf_counter()
            lowered = lower()
            seconds.append(round(time.perf_counter() - t0, 3))
        text = lowered.as_text()
        kernels = collections.Counter(
            re.findall(r'kernel_name = "(\w+)"', text))
        blank = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
        print("LOWERED " + json.dumps({
            "tree": args.tree, "cell": cell, "program": program,
            "keep": args.keep,
            "least_s": min(seconds), "seconds": seconds,
            "payloads": dict(sorted(kernels.items())),
            "sha256": hashlib.sha256(blank.encode()).hexdigest()[:16],
            "sha256_with_payloads": hashlib.sha256(
                text.encode()).hexdigest()[:16],
        }), flush=True)
        if not (args.plan and program == "step"):
            return
        t0 = time.perf_counter()
        compiled = lowered.compile()
        seconds = round(time.perf_counter() - t0, 3)
        # the heap XLA packed, which `memory_analysis()` does not give
        report = sorted(glob.glob(os.path.join(
            args.plan, "*jit_step*memory-usage-report.txt")))[-1]
        with open(report) as f:
            sizes = f.read()
        print("PLANNED " + json.dumps({
            "tree": args.tree, "cell": cell, "compile_s": seconds,
            "total_bytes": int(re.search(
                r"Total bytes used: (\d+)", sizes).group(1)),
            "scratch": re.search(
                r"size ([\d.]+\w+), preallocated-temp", sizes).group(1),
            "remat_fusions": len(re.findall(
                r"^\s*%?[\w.-]*\.remat[\w.]* = ", compiled.as_text(), re.M)),
            "report": report,
        }), flush=True)

    for cell_name in args.cells:
        cell = spec.load_cell(root, cell_name)
        config, traffic = cell["config"], cell["traffic"]
        config["attention_impl"] = "pallas"  # "auto" asks the CPU here
        family = spec.load_code(root, "loops", config["family"]).build(
            config, traffic, list(devices[:cell["workload"]["chips"]]))
        key = jax.eval_shape(lambda: loop.seed_key(0))
        made = jax.eval_shape(family.init_params, key)
        state = described(
            jax.eval_shape(family.init_state, made), family.state_shardings)
        batch = family.batch_shapes(int(traffic["batch_rows"]))
        report(cell_name, "step", lambda: family.step.lower(state, batch))
        if "tokens" not in batch:  # the check below is the token families'
            continue
        # the weights where the state holds them, the check's rows and length
        shardings = family.state_shardings
        if isinstance(shardings, dict):
            shardings = ({k: shardings[k] for k in made} if set(made) <= set(
                shardings) else shardings["params"])
        made = described(made, shardings)
        # the step's batch at the check's length (a head of several
        # positions [rows, length, heads]; a block-diffusion batch has a
        # noise draw a token and a level a block, cut as the tokens are)
        of_check = family.batch_shapes(int(config["check"]["rows"]))
        length, whole = int(config["check"]["seq_len"]), batch["tokens"].shape[1]
        check = {
            name: jax.ShapeDtypeStruct(
                (x.shape[0], x.shape[1] * length // whole, *x.shape[2:]),
                x.dtype, sharding=x.sharding)
            for name, x in of_check.items()
            if name in ("tokens", "targets", "noise", "level")}
        report(cell_name, "check_value_and_grad", lambda: jax.jit(
            jax.value_and_grad(family.system_loss)).lower(made, check))


if __name__ == "__main__":
    main()
