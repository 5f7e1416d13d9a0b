"""What an entry point that drives the chip (`chipbench/run.py`,
`chip_smoke.py`) settles before it starts workers. This module imports
nothing from JAX.

**The compile cache.** One rule, applied before JAX is imported and before
`ray_tpu.init()`, so every worker (which inherits the driver's environment,
`Raylet._start_worker`) reads the same directory:

- `JAX_COMPILATION_CACHE_DIR` is set: that directory is used and nothing
  else is set in code. Whoever runs the program placed the cache.
- it is not set: `<checkout>/.jax_cache`. The path is part of the cache's
  key, so it is fixed — never made from `tempfile`, a pid or the clock — and
  a second run on the same machine finds what the first compiled.

The cache's key leaves the program's metadata out (JAX's default), so an
executable loaded from it carries the name stacks of whichever revision
compiled it first: a profiler trace of a step whose instructions did not
change shows that revision's `jax.named_scope`s, or none
(docs/observability.md, "Device scopes"). Whoever profiles sets
`JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=1` for that run, and pays one
compilation; it is not set here, because every moved source line would then
compile every program again.

**One process for each chip.** A process that has initialised a JAX backend
holds the chip, and a worker that needs it then fails or hangs. The parent
of a train worker therefore stays off JAX, and says so with
`assert_no_jax_backend()` before the worker starts.
"""

from __future__ import annotations

import os
import sys
from typing import Tuple

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def place_compile_cache() -> Tuple[str, bool]:
    """Apply the cache rule. Returns (directory, was_empty): whether the
    directory held no entry yet, i.e. whether this run compiles cold."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        checkout = os.path.dirname(  # <checkout>/ray_tpu/_private/<this file>
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        path = os.environ[CACHE_ENV] = os.path.join(checkout, ".jax_cache")
    was_empty = not (os.path.isdir(path) and os.listdir(path))
    return path, was_empty


def assert_no_jax_backend() -> None:
    """Raise if this process has initialised a JAX backend. Importing jax
    (for a dtype, a config dataclass) is allowed; touching a device is not."""
    if "jax" not in sys.modules:
        return
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "this process has initialised a JAX backend and so holds the "
            "chip; the worker that needs it would fail or hang"
        )
