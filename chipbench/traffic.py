"""The one general traffic generator. A traffic mix is a data file,
`traffic/<name>.json`; nothing here knows a mix, a model or a cell by name.

A mix describes rows (named columns with a dtype, a per-row shape and a
range), how rows reach the train loop (`"kind": "ingest"`: seeded blocks made
by parallel `map_batches` tasks and streamed through the object store;
`"kind": "resident"`: a few seeded batches placed on the device once), and
how the loop is cut into chunks. The seed changes the values and never the
shapes, the sizes or the order of work: every seed gives the same amount of
work.

This module imports no JAX: block makers run in ingest tasks, which must not
touch the chip.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import numpy as np


def _bound(value: Any, config: Dict[str, Any]) -> int:
    """A range bound: a number, or `"config:<key>"` for a size of the model
    (a token id below the vocabulary size)."""
    if isinstance(value, str) and value.startswith("config:"):
        return int(config[value.split(":", 1)[1]])
    return int(value)


def _row_shape(column: Dict[str, Any], config: Dict[str, Any]):
    return tuple(_bound(s, config) for s in column.get("shape", []))


def make_rows(traffic: Dict[str, Any], config: Dict[str, Any], seed: int,
              index: int, rows: int) -> Dict[str, np.ndarray]:
    """`rows` rows of every column, from (seed, index) alone."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), int(index)]))
    out = {}
    for name in sorted(traffic["columns"]):
        column = traffic["columns"][name]
        shape = (rows, *_row_shape(column, config))
        dtype = np.dtype(column["dtype"])
        low = _bound(column.get("low", 0), config)
        high = _bound(column["high"], config)
        if dtype == np.uint8 and (low, high) == (0, 256):
            # the cheapest generator that still writes every byte
            data = np.frombuffer(rng.bytes(int(np.prod(shape))), dtype=np.uint8)
            out[name] = data.reshape(shape)
        else:
            out[name] = rng.integers(low, high, shape, dtype=dtype)
    return out


def block_maker(traffic: Dict[str, Any], config: Dict[str, Any], seed: int
                ) -> Callable[[Dict[str, Any]], Dict[str, np.ndarray]]:
    """`map_batches` function: the block whose number arrives in `id`."""
    rows = int(traffic["rows_per_block"])

    def make(batch: Dict[str, Any]) -> Dict[str, np.ndarray]:
        index = int(np.asarray(batch["id"]).reshape(-1)[0])
        return make_rows(traffic, config, seed, index, rows)

    return make


def dataset(traffic: Dict[str, Any], config: Dict[str, Any], seed: int):
    """One epoch of an ingest mix as a lazy `ray_tpu.data` pipeline: one
    read task and one map task per block. The loop iterates it again and
    again until the window ends, as a job iterates epochs. The pipeline's
    window of blocks in flight is its parallelism, so an epoch's blocks
    bound what the object store holds."""
    from ray_tpu import data as rd

    n = int(traffic["blocks_per_epoch"])
    return rd.range(n, parallelism=n).map_batches(
        block_maker(traffic, config, seed), batch_size=1)


def units_per_step(traffic: Dict[str, Any]) -> int:
    """Images or tokens one step trains on."""
    return int(traffic["batch_rows"]) * int(traffic["units_per_row"])
