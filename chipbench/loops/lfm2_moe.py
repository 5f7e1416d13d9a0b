"""The `lfm2_moe` family: `ray_tpu.models.transformer` as a stack of unlike
layers (gated short convolutions and GQA attention with per-head QK-norm, a
leading dense layer, routed layers that hold a share of the experts under a
sigmoid router with a selection bias: LFM2-24B-A2B) through
`make_train_step` on the configuration's mesh. bf16 compute over f32 master
weights, a float32 router, the grouped-matmul kernels of `ray_tpu/ops/moe.py`
over the held rows and the flash kernel where `attention_impl` resolves to
them, the chunked LM-head cross-entropy over the tied embedding, AdamW, and
the selection bias as state the optimizer does not own.

What `init_params` returns, and `check` and `init_state` take, is the pair
`{"params", "expert_bias"}`: the weights, and a selection bias drawn at
`check.expert_bias_std` for the comparison, which a zero bias would not hold
to account for the selection. Training starts from a zero bias, as
arXiv:2408.15664 starts it, so `init_state` keeps the weights and not the
drawn bias: a drawn bias of 0.1 makes the held experts' load, and with it
the step's work, a matter of the seed (PERF.md section 6, PR 32).
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chipbench import compare, lfm2_flops
from chipbench.reference import lfm2_moe as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models.transformer import (
    expert_bias_init, transformer_init, transformer_loss_and_readings)
from ray_tpu.parallel import make_mesh

# System (bf16 matmuls and activations, the grouped-matmul and flash kernels,
# a float32 router, f32 loss) against the f32 reference on 2 seeded
# 1024-token sequences with random weights at LFM2-24B-A2B's widths: 5
# layers, 8 of 64 experts, a selection bias drawn at standard deviation 0.1.
#
# Loss and gradients are compared under one routing, the system's, for
# `loops/moe_transformer.py`'s reason: the system's router sees bf16
# activations, and a slot that flips moves a whole row between two experts'
# weight gradients (or, here, into or out of the held share altogether).
# Readings on the chip (my chip runs, PR 32; PERF.md section 6): the stated
# path over 20 seeds, each wrong mathematics at 1 to 4 seeds.
# - `loss_rel_err` 4.1e-7 to 1.107e-4. A step whose weights, activations,
#   router, logits and loss are bf16 as well reads 2.59e-3 and fails: the
#   bound that tells precisions apart, as in the other transformer families.
#   Weights not divided by their sum read 9.3e-4, a convolution one tap
#   late 3.1e-3.
# - `grad_rel_err` 2.821e-2 to 3.289e-2 (five layers of bf16 matmuls and the
#   convolutions' elementwise products in bf16; Mistral's eight layers read
#   2.4e-2). Weights taken from the biased score read 4.027e-2, 4.127e-2,
#   4.131e-2 and 5.442e-2: a bias of 0.1 on scores near 0.6, divided by
#   their sum again, moves the gradient by about as much as the rounding
#   does, so the bound stands a tenth over the largest stated reading and a
#   tenth under the smallest wrong one, and cannot stand further from both.
#   The stated readings' spread is 0.13e-2 about 3.06e-2: the bound is four
#   and a half of it away. A norm over the whole projection for the per-head
#   one reads 6.88e-2 to 7.10e-2, weights not divided by their sum 0.68, a
#   convolution one tap late 1.41. Like OLMoE's, the bound does not tell a
#   bf16 backward from the stated one (bf16-everything: 2.96e-2).
# - `router_flip_share` 1.36e-2 to 1.89e-2 (OLMoE: 0.38e-2 to 0.77e-2; here
#   a flip also follows where two *biased* scores tie). Under the system's
#   routing nothing else holds the choice itself: a router that ignores the
#   bias reads 0.486, and fails by this key alone (its `grad_rel_err` is
#   3.12e-2: the reference follows whatever was chosen).
# At the tests' tiny size the stated path reads `grad_rel_err` up to 4.4e-2
# (64 wide: the rounding of one element weighs more), over this bound; the
# CPU tests hold it to 6e-2 and each wrong mathematics to this bound.
TOLERANCE = {"loss_rel_err": 3e-4, "grad_rel_err": 3.65e-2,
             "router_flip_share": 3e-2}

_CONFIG_KEYS = (
    "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff",
    "max_seq_len", "rope_theta", "remat", "attention_impl", "norm_eps",
    "tied_embeddings", "n_experts", "experts_per_token", "norm_topk_prob",
    "qk_norm", "router_aux_loss_coef", "router_z_loss_coef", "layer_types",
    "conv_taps", "n_dense_layers", "d_ff_dense", "router_score",
    "norm_topk_eps", "expert_bias", "expert_bias_update_rate", "experts_held",
)


def model_config(config: Dict[str, Any]) -> TransformerConfig:
    values = {k: config[k] for k in _CONFIG_KEYS if k in config}
    for key in ("layer_types", "experts_held"):
        values[key] = tuple(values[key])
    return TransformerConfig(dtype=jnp.dtype(config["dtype"]), **values)


def build(config: Dict[str, Any], traffic: Dict[str, Any], devices) -> Any:
    cfg = model_config(config)
    mesh = make_mesh(config["mesh"], devices=devices)
    opt_cfg = config["optimizer"]
    optimizer = optax.adamw(
        opt_cfg["learning_rate"], weight_decay=opt_cfg["weight_decay"])
    _, step, shardings = make_train_step(cfg, mesh, optimizer)
    state_shard = shardings["state"]
    seq_len = int(traffic["units_per_row"])
    bias_std = float(config["check"]["expert_bias_std"])

    def make(key):
        bias = bias_std * jax.random.normal(
            jax.random.fold_in(key, 1), expert_bias_init(cfg).shape, jnp.float32)
        return {"params": transformer_init(key, cfg), "expert_bias": bias}

    # the state is made where it will live, in two jitted calls from the key
    init_params = jax.jit(make, out_shardings={
        "params": state_shard["params"],
        "expert_bias": state_shard["expert_bias"]})

    def init_state(made):
        opt, count, bias = jax.jit(
            lambda p: (optimizer.init(p), jnp.zeros((), jnp.int32),
                       expert_bias_init(cfg)),
            out_shardings=(state_shard["opt"], state_shard["step"],
                           state_shard["expert_bias"]),
        )(made["params"])
        return {"params": made["params"], "opt": opt, "step": count,
                "expert_bias": bias}

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

    def system_loss_and_readings(params, batch, expert_bias):
        return transformer_loss_and_readings(
            params, batch, cfg, mesh=mesh, expert_bias=expert_bias)

    def reference_loss(params, batch, expert_index=None, expert_bias=None):
        return reference.loss(params, batch, config, expert_index, expert_bias)

    def errors_of(loss_and_readings, made, batch):
        """The comparison of a system `(params, batch, expert_bias) ->
        (loss, readings)` with the reference under the system's routing."""
        params, bias = made["params"], made["expert_bias"]
        first, held = cfg.held

        @jax.jit
        def routing(params, batch, bias):
            _, readings = loss_and_readings(params, batch, bias)
            index = readings["expert_index"]  # [L, tokens, k]
            ours = jax.nn.one_hot(
                index, cfg.n_experts, dtype=jnp.int32).sum(-2) > 0
            own_loss, theirs = reference.forward(
                params, batch, config, expert_bias=bias)
            flips = jnp.logical_and(ours, jnp.logical_not(theirs)).sum()
            load = readings["expert_load"].astype(jnp.float32)  # [L, E]
            slots = index.size / index.shape[0]
            return index, own_loss, {
                "router_flip_share": flips / index.size,
                "expert_load_max_over_mean": jnp.max(
                    load.max(axis=-1) / load.mean(axis=-1)),
                "unrouted_slots": index.size - load.sum(),
                "dropped_slots": readings["dropped_slots"].sum(),
                "held_slots_mean": readings["held_slots"].mean(),
                "held_slots_max_over_even": readings["held_slots"].max() / (
                    slots * held / cfg.n_experts),
            }

        index, own_loss, info = routing(params, batch, bias)
        # the choice and the bias ride in the batch: as constants of the
        # reference's program they would make every seed a miss of the
        # compile cache
        errors = compare.loss_and_grad_errors(
            lambda p, b: loss_and_readings(
                p, {"tokens": b["tokens"], "targets": b["targets"]},
                b["expert_bias"])[0],
            lambda p, b: reference_loss(p, b, b["expert_index"]),
            params, {**batch, "expert_index": index, "expert_bias": bias})
        own_loss = float(own_loss)
        return {**errors, **{k: float(v) for k, v in info.items()},
                "loss_rel_err_own_routing":
                    abs(errors["loss_system"] - own_loss) / abs(own_loss)}

    def check(made, batch):
        """Judged: `loss_rel_err` and `grad_rel_err`, the reference taking
        the system's choice of experts, and `router_flip_share`, the share
        of the slots whose expert the reference, given the same bias, did
        not choose for that token. Information: the loss's error against
        the reference under its own routing, the largest load over the mean
        load, the held slots a layer (their mean, and the largest over the
        even share), and the slots that were routed nowhere or held and not
        computed (both always 0)."""
        return errors_of(system_loss_and_readings, made, batch)

    return SimpleNamespace(
        mesh=mesh,
        batch_shapes=batch_shapes,
        state_shardings=state_shard,
        flops_per_unit=lfm2_flops.lfm2_flops_per_token(config, seq_len),
        tolerance=TOLERANCE,
        init_params=init_params,
        init_state=init_state,
        step=step,
        loss_of=lambda out: out["loss"],
        to_device=to_device,
        check_batch=lambda raw: to_device(raw, check_len),
        system_loss=lambda made, batch: system_loss_and_readings(
            made["params"], batch, made["expert_bias"])[0],
        reference_loss=lambda made, batch: reference_loss(
            made["params"], batch, expert_bias=made["expert_bias"]),
        check=check,
        system_loss_and_readings=system_loss_and_readings,
        errors_of=errors_of,
        model_config=cfg,
    )
