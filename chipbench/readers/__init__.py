"""One module per way of reading a metric. A reader defines
`read(run, params)`: `run` is the record of one run (the worker's summary:
`chunks`, `window_s`, `spans`, `stages`, `flops_per_unit`,
`memory_peak_bytes`, `device`, `chips`, and `trace`, the reduced profiler
trace or None), `params` come from the metric's file. It returns a number,
or None when there is nothing to read, and the metric is then left out."""
