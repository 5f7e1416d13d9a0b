"""The `ouro` family: `ray_tpu.models.transformer` as a stack of
sandwich-normed attention layers that is run `loop_steps` times over the same
weights (a `lax.scan` over the passes), the final norm after every pass, one
untied head and one exit gate reading every pass's normed stream, and one
loss over the passes under the exit distribution the gate makes, less its
entropy (Ouro-2.6B, arXiv:2510.25741) through `make_train_step` on the
configuration's mesh. bf16 compute over f32 master weights, the flash
kernels where `attention_impl` resolves to them, the four heads as one call
of the weighted chunked cross-entropy over the stacked streams, the gate,
the distribution and the entropy in float32, AdamW with no weight decay on
the norms and the gate.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chipbench import ouro_flops
from chipbench.loops.nemotron_h import decayed
from chipbench.reference import ouro as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models.transformer import (
    exit_probabilities, transformer_init, transformer_loss_and_readings)
from ray_tpu.parallel import make_mesh

# System (bf16 matmuls and activations, the flash kernels, the four heads in
# one weighted chunked call, the gate, the distribution, the entropy and the
# loss in float32) against the f32 reference (a Python loop over passes and
# layers, whole logits) on 1 seeded 2048-token sequence with random weights
# at Ouro-2.6B's widths: 8 layers run 4 times, 32 layer applications.
# Readings on the chip (my chip runs, PR 57; PERF.md section 6): the stated
# path over eighteen seeds, each lower precision or wrong mathematics at
# three.
# - `loss_rel_err` 3.5e-6 to 7.91e-5. A step whose weights, logits and loss
#   are bf16 as well reads 1.25e-3, 1.61e-3 and 2.63e-3 and fails, by this
#   key alone (its gradients' distances are the stated path's to the third
#   digit): the bound that tells precisions apart, as in the other
#   transformer families, 3.8 times the largest stated reading and a quarter
#   of the smallest bf16 one.
# - `grad_rel_err` 2.19e-2 to 3.90e-2: 32 applications of bf16 matmuls, where
#   `mistral-7b-l8-fsdp4`'s eight read 2.4e-2; the passes share weights, so
#   the roundings of four uses add in one leaf. The nearest wrong
#   mathematics are a pass dropped (three passes for four) and the exit
#   weights held constant in the backward, 0.215 each at their lowest; the
#   final norm left out between the passes reads 0.49 to 0.70, the sandwich
#   norms left out 1.09 to 1.13. The bound stands 2.05 times over the largest
#   stated reading and 2.7 times under the smallest wrong one. It does not
#   tell the gate's float32 parts from bf16 ones (2.43e-2 to 2.87e-2 with
#   them in bf16, inside the stated range): `exit_rel_err` holds that.
# - `gate_grad_rel_err` 1.01e-2 to 2.86e-2, the distance over `exit_w` and
#   `exit_b` alone. Their share of the whole gradient's norm
#   (`gate_grad_share`) is 3.5 to 13.7 %: at the low end the weights held
#   constant would move the whole tree's distance by less than its bound,
#   and reads 0.29 to 0.51 here (a pass dropped 0.14 to 0.32, no norm
#   between the passes 0.27 to 0.37). The bound is 2.8 times the largest
#   stated reading and 3.6 times under the fault it is there for.
# - `exit_rel_err` 0 at every seed (the chip computes the gate's float32
#   multiply-and-sum and the reference's product at the highest precision
#   alike; 1e-7 on a CPU): the gate's logits, the exit distribution and its
#   entropy as the step computes them against the reference's on a probe.
#   With those parts in bf16 it reads 2.36e-3 to 2.43e-3 and fails, by this
#   key alone (`loss_rel_err` 2.0e-6 to 3.4e-5 then, `gate_grad_rel_err`
#   1.7e-2 to 1.8e-2). The bound is 4.7 times under the bf16 reading.
# At the tests' tiny size (64 wide) the stated path in bf16 reads
# `grad_rel_err` 2.5e-2 and the probe in bf16 2.4e-3; the CPU tests hold
# each wrong mathematics to these bounds in float32, where the stated path
# agrees to rounding and what is left is the fault's own.
TOLERANCE = {"loss_rel_err": 3e-4, "grad_rel_err": 8e-2,
             "gate_grad_rel_err": 8e-2, "exit_rel_err": 5e-4}

# the program's fields, under the configuration file's own keys
_CONFIG_KEYS = (
    "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_head",
    "d_ff", "max_seq_len", "rope_theta", "norm_eps", "tied_embeddings",
    "loop_steps", "post_norm", "exit_gate", "exit_entropy_coef", "remat",
    "attention_impl",
)
_GATE = ("exit_w", "exit_b")


def model_config(config: Dict[str, Any]) -> TransformerConfig:
    return TransformerConfig(
        dtype=jnp.dtype(config["dtype"]),
        layer_types=tuple(config["layer_types"]),
        **{k: config[k] for k in _CONFIG_KEYS if k in config})


def build(config: Dict[str, Any], traffic: Dict[str, Any], devices) -> Any:
    cfg = model_config(config)
    mesh = make_mesh(config["mesh"], devices=devices)
    opt_cfg = config["optimizer"]
    # a warm-up: the window's steps are a run's first (`assumed.optimizer`)
    optimizer = optax.adamw(
        optax.linear_schedule(
            0.0, opt_cfg["learning_rate"], opt_cfg["warmup_steps"]),
        b1=opt_cfg["b1"], b2=opt_cfg["b2"],
        weight_decay=opt_cfg["weight_decay"],
        mask=lambda params: decayed(params, opt_cfg["no_decay"]))
    _, step, shardings = make_train_step(cfg, mesh, optimizer)
    state_shard = shardings["state"]
    seq_len = int(traffic["units_per_row"])

    # the state is made where it will live, in two jitted calls from the key
    init_params = jax.jit(lambda key: transformer_init(key, cfg),
                          out_shardings=state_shard["params"])

    def init_state(params):
        opt, count = jax.jit(
            lambda p: (optimizer.init(p), jnp.zeros((), jnp.int32)),
            out_shardings=(state_shard["opt"], state_shard["step"]),
        )(params)
        return {"params": params, "opt": opt, "step": count}

    def to_device(raw, seq_len=None):
        tokens = np.asarray(raw["tokens"])
        if seq_len is not None:
            tokens = tokens[:, :seq_len + 1]
        return jax.device_put(
            {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]},
            shardings["tokens"])

    def batch_shapes(n):
        ids = jax.ShapeDtypeStruct((n, seq_len), jnp.int32,
                                   sharding=shardings["tokens"])
        return {"tokens": ids, "targets": ids}

    check_len = config["check"]["seq_len"]

    def system_loss_and_readings(params, batch):
        return transformer_loss_and_readings(params, batch, cfg, mesh=mesh)

    def reference_loss(params, batch):
        return reference.loss(params, batch, config)

    @jax.jit
    def reference_side(params, batch):
        (loss, readings), grads = jax.value_and_grad(
            lambda p: reference.terms(p, batch, config), has_aux=True)(params)
        return loss, readings, grads

    def system_side_of(loss_and_readings):
        """The system's loss, readings and gradients as one program."""
        @jax.jit
        def system_side(params, batch):
            (loss, readings), grads = jax.value_and_grad(
                loss_and_readings, has_aux=True)(params, batch)
            return loss, readings, grads

        return system_side

    @jax.jit
    def distances(ours, theirs, readings, reference_readings):
        def squares(tree):
            return sum(jnp.sum(y ** 2) for y in jax.tree.leaves(tree))

        def apart(tree, other):
            return squares(jax.tree.map(
                lambda x, y: x.astype(jnp.float32) - y, tree, other))

        gate_ours = {k: ours[k] for k in _GATE}
        gate_theirs = {k: theirs[k] for k in _GATE}
        # a system that drops a pass is held to the passes it has
        n = min(len(readings["ut_pass_loss"]),
                len(reference_readings["ut_pass_loss"]))
        readings = {k: v[:n] if v.ndim else v for k, v in readings.items()}
        reference_readings = {k: v[:n] if v.ndim else v
                              for k, v in reference_readings.items()}
        passes = reference_readings["ut_pass_loss"]
        return {
            "grad_rel_err": jnp.sqrt(apart(ours, theirs) / squares(theirs)),
            "gate_grad_rel_err": jnp.sqrt(
                apart(gate_ours, gate_theirs) / squares(gate_theirs)),
            "gate_grad_share": jnp.sqrt(
                squares(gate_theirs) / squares(theirs)),
            "ut_pass_loss_rel_err": jnp.max(
                jnp.abs(readings["ut_pass_loss"] - passes) / passes),
            "exit_p_mean_abs_err": jnp.max(jnp.abs(
                readings["exit_p_mean"] - reference_readings["exit_p_mean"])),
            "exit_entropy_reference": reference_readings["exit_entropy"],
            # the step's three readings, as the system reads them here
            **{name: readings[name] for name in (
                "ut_pass_loss", "exit_p_mean", "exit_entropy")},
        }

    def exit_rel_err(params, tokens, exit_fn=exit_probabilities):
        """The distance of the gate, the exit distribution and its entropy
        as the step computes them (`exit_fn`: `exit_probabilities`) from the
        reference's, on seeded streams of the passes' shape at unit RMS in
        the compute dtype under the run's own gate: p and the entropy a
        token, over the reference's norm."""
        streams = jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(0), tokens[0, 0]),
            (cfg.loop_steps, 1, check_len, cfg.d_model)).astype(cfg.dtype)
        # the gate's seeded weights scaled up and its bias moved off 0: the
        # logits spread over +-4 and every pass gets some probability
        w, b = 2.0 * params["exit_w"], params["exit_b"] + 0.5
        p, entropy = jax.jit(exit_fn)(streams, w, b)

        @jax.jit
        def theirs(streams, w, b):
            with jax.default_matmul_precision("highest"):
                q, log_q = reference.exit_distribution(
                    streams.astype(jnp.float32) @ w + b)
            return q, -(q * log_q).sum(0)

        q, reference_entropy = theirs(streams, w, b)
        return jnp.sqrt(
            (jnp.sum((p - q) ** 2) + jnp.sum((entropy - reference_entropy) ** 2))
            / (jnp.sum(q ** 2) + jnp.sum(reference_entropy ** 2)))

    def errors_of(loss_and_readings, params, batch, reference_outputs=None,
                  exit_fn=exit_probabilities):
        """The comparison of a system `(params, batch) -> (loss, readings)`
        with the reference: two programs, each a loss, its readings and its
        gradients, and their distances. `reference_outputs` is what
        `reference_side(params, batch)` gave, where several systems are
        held against one reference; `exit_fn` is the system's
        `exit_probabilities`, for the probe."""
        batch = {"tokens": batch["tokens"], "targets": batch["targets"]}
        l_ref, r_ref, g_ref = reference_outputs or reference_side(
            params, batch)
        l_sys, r_sys, g_sys = system_side_of(loss_and_readings)(params, batch)
        info = distances(g_sys, g_ref, r_sys, r_ref)
        del g_sys, g_ref
        info["exit_rel_err"] = exit_rel_err(params, batch["tokens"], exit_fn)
        info = {k: np.asarray(v).tolist() for k, v in info.items()}
        l_sys, l_ref = float(l_sys), float(l_ref)
        return {"loss_system": l_sys, "loss_reference": l_ref,
                "loss_rel_err": abs(l_sys - l_ref) / abs(l_ref), **info}

    def check(params, batch):
        """Judged: `loss_rel_err`; `grad_rel_err`, the distance of the
        gradients over all the parameters; and `gate_grad_rel_err`, the
        same distance over the exit gate's two leaves alone (2,049 of 612 M
        values, whose share of the whole gradient's norm is
        `gate_grad_share`); and `exit_rel_err`, the gate's logits, the exit
        distribution and its entropy as the step computes them against the
        reference's on a probe: the loss and the gradients
        of the whole model do not tell those float32 parts from bfloat16
        ones, this key does.
        Information: the largest relative error of a pass's mean
        cross-entropy, the largest error of a pass's mean exit probability,
        the reference's entropy, and the step's three readings on this
        batch: `ut_pass_loss` and `exit_p_mean` (a list, a pass each) and
        `exit_entropy`."""
        return errors_of(system_loss_and_readings, params, batch)

    return SimpleNamespace(
        mesh=mesh,
        batch_shapes=batch_shapes,
        state_shardings=state_shard,
        flops_per_unit=ouro_flops.ouro_flops_per_token(config, seq_len),
        tolerance=TOLERANCE,
        init_params=init_params,
        init_state=init_state,
        step=step,
        loss_of=lambda out: out["loss"],
        to_device=to_device,
        check_batch=lambda raw: to_device(raw, check_len),
        system_loss=lambda params, batch: system_loss_and_readings(
            params, batch)[0],
        reference_loss=reference_loss,
        check=check,
        system_loss_and_readings=system_loss_and_readings,
        errors_of=errors_of,
        reference_side=reference_side,
        system_side_of=system_side_of,
        exit_rel_err=exit_rel_err,
        model_config=cfg,
    )
