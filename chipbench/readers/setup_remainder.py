"""What of set-up no stage of the loop and no span of the program covers
yet: the stage `setup` (process start to the window's first step) less the
stages listed in `"stages"` less the seconds of the parent's span
`"driver_span"` (`runtime_span_seconds`' reading of the `driver` table; 0 on
a record without the block). What is left is process start, chip detection,
the native build check, imports, and whatever else a later span should
name. A stage the record lacks counts as 0."""

from chipbench.readers import runtime_span_seconds


def read(run, params):
    stages = run["stages"]
    named = sum(stages.get(stage, 0.0) for stage in params["stages"])
    named += runtime_span_seconds.read(
        run, {"table": "driver", "span": params["driver_span"]})
    return stages["setup"] - named
