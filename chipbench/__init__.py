"""chipbench: the benchmark of ray_tpu's training path on the chip.

`python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` once. Everything that belongs to one
configuration, traffic mix, metric, model family or reference sits in a file
of its own under this directory and is found by its name (README.md).
"""
