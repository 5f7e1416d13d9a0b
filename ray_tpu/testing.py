"""Test helpers: force JAX onto a virtual multi-device CPU mesh.

Tests run with no chip; sharding logic is validated on an N-device CPU mesh
via --xla_force_host_platform_device_count. Forcing the CPU is
`JAX_PLATFORMS=cpu` plus that XLA flag: in the environment for fresh worker
processes, and as a config update for a process that has already imported
jax.
"""

from __future__ import annotations

import os
from typing import Dict


def cpu_mesh_worker_env(num_devices: int = 8) -> Dict[str, str]:
    """Env for spawned worker processes so jax inside them sees N CPU devices."""
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={num_devices}",
        # The force flag is ignored when jax.distributed initializes the
        # multi-process CPU client; this knob covers that path too.
        "JAX_NUM_CPU_DEVICES": str(num_devices),
    }


def force_cpu_mesh(num_devices: int = 8) -> None:
    """Force the CURRENT process's jax onto N virtual CPU devices.

    Must run before first backend use (first jit/device access).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"--xla_force_host_platform_device_count={num_devices}"
    kept = [
        f
        for f in flags.split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    os.environ["XLA_FLAGS"] = " ".join(kept + [want])
    import jax

    jax.config.update("jax_platforms", "cpu")
