"""The comparison that decides the numerical part of `correct`: the
system's loss and gradients on one seeded batch against the family's plain
reference, before the measured window."""

from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp


def reference_outputs(reference_loss: Callable, params: Any, batch: Any):
    """The reference's loss and gradients, kept on the device."""
    return jax.jit(jax.value_and_grad(reference_loss))(params, batch)


def errors_against(reference, system_loss: Callable, params: Any, batch: Any
                   ) -> Dict[str, float]:
    """Relative errors of a system loss against `reference_outputs`.

    `loss_rel_err` is |l_sys - l_ref| / |l_ref|. `grad_rel_err` is the
    distance between the two gradients over all parameters,
    sqrt(sum ||g_sys - g_ref||^2) / sqrt(sum ||g_ref||^2). Only scalars
    leave the device."""
    l_ref, g_ref = reference
    l_sys, g_sys = jax.jit(jax.value_and_grad(system_loss))(params, batch)

    @jax.jit
    def distances(a, b):
        num = sum(jnp.sum((x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)
                  for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
        den = sum(jnp.sum(y.astype(jnp.float32) ** 2) for y in jax.tree.leaves(b))
        return num, den

    num, den = distances(g_sys, g_ref)
    l_sys, l_ref, num, den = (float(v) for v in (l_sys, l_ref, num, den))
    return {
        "loss_system": l_sys,
        "loss_reference": l_ref,
        "loss_rel_err": abs(l_sys - l_ref) / abs(l_ref),
        "grad_rel_err": math.sqrt(num) / math.sqrt(den) if den > 0 else math.inf,
    }


def loss_and_grad_errors(system_loss: Callable, reference_loss: Callable,
                         params: Any, batch: Any) -> Dict[str, float]:
    return errors_against(
        reference_outputs(reference_loss, params, batch), system_loss,
        params, batch)


def within(errors: Dict[str, float], tolerance: Dict[str, float]) -> bool:
    return all(math.isfinite(errors[k]) and errors[k] <= tolerance[k]
               for k in tolerance)
