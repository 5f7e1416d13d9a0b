"""The `deepseek_v2` family: `ray_tpu.models.transformer` as a stack of
latent-attention layers (a low-rank key-value projection, heads of 128 + 64
rotary query-key columns and 128 value columns, YaRN's frequencies), a
leading dense layer, routed layers that hold a share of the experts beside
two shared experts under a softmax router, and a per-sequence balance loss
(DeepSeek-V2-Lite) through `make_train_step` on the configuration's mesh.
bf16 compute over f32 master weights, a float32 router, the flash kernels at
two widths and the grouped-matmul kernels of `ray_tpu/ops/moe.py` over the
held rows where `attention_impl` resolves to them, the chunked LM-head
cross-entropy over the untied head, AdamW.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chipbench import compare, dsv2_flops
from chipbench.reference import deepseek_v2 as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models.transformer import (
    transformer_init, transformer_loss_and_readings)
from ray_tpu.parallel import make_mesh

# System (bf16 matmuls and activations, the flash kernels at 192 and 128
# wide and the grouped-matmul kernels, a float32 router, f32 loss) against
# the f32 reference on 2 seeded 1024-token sequences with random weights at
# DeepSeek-V2-Lite's widths: 6 layers, 8 of 64 experts held, 2 shared.
#
# Loss and gradients are compared under one routing, the system's, for
# `loops/moe_transformer.py`'s reason: the system's router sees bf16
# activations, and a slot that flips moves a whole row between two experts'
# weight gradients (or into or out of the held share altogether).
# Readings on the chip (my chip runs, PR 34; PERF.md section 6): the stated
# path over 24 seeds, each wrong mathematics at 2 seeds.
# - `loss_rel_err` 4.4e-6 to 1.437e-4. A step whose weights, activations,
#   router, logits and loss are bf16 as well reads 6.29e-4 and 2.46e-3 and
#   fails: the bound that tells precisions apart, as in the other
#   transformer families, twice the largest stated reading and under
#   half the smallest bf16 one.
# - `grad_rel_err` 4.297e-2 to 4.556e-2 (six layers of bf16 matmuls, and
#   attention's scores at 192 wide in bf16; LFM2's five layers read 3.3e-2).
#   The nearest wrong mathematics is the renormalised weights at 0.2078 and
#   0.2133; the latent's norm left out reads 0.414 and 0.421, the scale
#   without YaRN's factor squared 0.957 and 0.962, the shared experts
#   weighted by the largest score 0.962 and 0.969 and left out 0.997 and
#   1.003, plain frequencies for YaRN's 1.026 and 1.028, a rotary key of
#   its own for every head 1.349 and 1.348, rotary positions over all 192
#   columns 1.407 and 1.388. The bound stands a third over the largest
#   stated reading and 3.5 times under the smallest wrong one. Like the
#   other families', it does not tell a bf16 backward from the stated one
#   (bf16-everything: 4.356e-2 and 4.393e-2).
# - `router_flip_share` 1.873e-2 to 2.082e-2 (6 of 64 by a softmax whose
#   sixth and seventh scores lie close; LFM2: 1.4e-2 to 1.9e-2). Under the
#   system's routing nothing else holds the choice itself. The nearest wrong
#   reading is the renormalised weights' 5.8e-2 and 6.4e-2 (the choice moves
#   with the layers before it); the others read 0.156 to 0.825.
# - `aux_loss_rel_err` 2.3e-5 to 2.889e-4: the system's balance loss (the
#   layers' sum, before `alpha`) against the reference's per-sequence one
#   under the same choice. At `alpha` 0.001 the loss cannot hold it: the
#   balance loss taken over the batch and not per sequence reads
#   `loss_rel_err` 2.1e-5 and 7.3e-5 and `grad_rel_err` 4.40e-2 and 4.43e-2,
#   the stated path's, and this key 9.605e-2 and 0.1033 (two sequences of
#   1024 tokens: a sequence's own counts and its own mean scores go
#   together, and the batch's product of means loses that). The bound is
#   17 times the largest stated reading and 19 times under the wrong one.
# At the tests' tiny size the stated path in bf16 reads `grad_rel_err` up to
# 3.6e-2 and `loss_rel_err` 3.4e-4 (means over 128 tokens); the CPU tests
# hold each wrong mathematics to these bounds in float32, where the stated
# path agrees to rounding and what is left is the fault's own.
TOLERANCE = {"loss_rel_err": 3e-4, "grad_rel_err": 6e-2,
             "router_flip_share": 3e-2, "aux_loss_rel_err": 5e-3}

# the program's field, and config.json's own key where the file has it
# under that name
_CONFIG_KEYS = (
    "vocab_size", "d_model", "n_layers", "n_heads", "d_ff", "max_seq_len",
    "rope_theta", "remat", "attention_impl", "norm_eps", "tied_embeddings",
    "n_experts", "experts_per_token", "norm_topk_prob", "router_score",
    "router_aux_loss_coef", "router_z_loss_coef", "layer_types",
    "n_dense_layers", "d_ff_dense", "experts_held", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_scaling",
    "n_shared_experts", "seq_aux",
)


def model_config(config: Dict[str, Any]) -> TransformerConfig:
    values = {k: config[k] for k in _CONFIG_KEYS if k in config}
    for key in ("layer_types", "experts_held"):
        values[key] = tuple(values[key])
    values["rope_scaling"] = tuple(sorted(values["rope_scaling"].items()))
    return TransformerConfig(dtype=jnp.dtype(config["dtype"]), **values)


def build(config: Dict[str, Any], traffic: Dict[str, Any], devices) -> Any:
    cfg = model_config(config)
    mesh = make_mesh(config["mesh"], devices=devices)
    opt_cfg = config["optimizer"]
    # the published warm-up: the window's steps are a run's first, and at a
    # constant rate from step 0 the router collapses inside it (`assumed`)
    optimizer = optax.adamw(
        optax.linear_schedule(
            0.0, opt_cfg["learning_rate"], opt_cfg["warmup_steps"]),
        b1=opt_cfg["b1"], b2=opt_cfg["b2"],
        weight_decay=opt_cfg["weight_decay"])
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

    def reference_loss(params, batch, expert_index=None):
        return reference.loss(params, batch, config, expert_index)

    def errors_of(loss_and_readings, params, batch):
        """The comparison of a system `(params, batch) -> (loss, readings)`
        with the reference under the system's routing."""
        first, held = cfg.held

        @jax.jit
        def routing(params, batch):
            _, readings = loss_and_readings(params, batch)
            index = readings["expert_index"]  # [L, tokens, k]
            ours = jax.nn.one_hot(
                index, cfg.n_experts, dtype=jnp.int32).sum(-2) > 0
            own_loss, theirs, _ = reference.forward(params, batch, config)
            _, _, balance = reference.forward(params, batch, config, index)
            flips = jnp.logical_and(ours, jnp.logical_not(theirs)).sum()
            load = readings["expert_load"].astype(jnp.float32)  # [L, E]
            slots = index.size / index.shape[0]
            return index, own_loss, {
                "router_flip_share": flips / index.size,
                "aux_loss_rel_err": jnp.abs(
                    readings["aux_loss"] - balance) / balance,
                "aux_loss_system": readings["aux_loss"],
                "expert_load_max_over_mean": jnp.max(
                    load.max(axis=-1) / load.mean(axis=-1)),
                "unrouted_slots": index.size - load.sum(),
                "dropped_slots": readings["dropped_slots"].sum(),
                "held_slots_mean": readings["held_slots"].mean(),
                "held_slots_max_over_even": readings["held_slots"].max() / (
                    slots * held / cfg.n_experts),
            }

        index, own_loss, info = routing(params, batch)
        # the choice rides in the batch: as a constant of the reference's
        # program it would make every seed a miss of the compile cache
        errors = compare.loss_and_grad_errors(
            lambda p, b: loss_and_readings(
                p, {"tokens": b["tokens"], "targets": b["targets"]})[0],
            lambda p, b: reference_loss(p, b, b["expert_index"]),
            params, {**batch, "expert_index": index})
        own_loss = float(own_loss)
        return {**errors, **{k: float(v) for k, v in info.items()},
                "loss_rel_err_own_routing":
                    abs(errors["loss_system"] - own_loss) / abs(own_loss)}

    def check(params, batch):
        """Judged: `loss_rel_err` and `grad_rel_err`, the reference taking
        the system's choice of experts; `router_flip_share`, the share of
        the slots whose expert the reference did not choose for that token;
        and `aux_loss_rel_err`, the system's balance loss (the sum over the
        layers, before `alpha`) against the reference's per-sequence one
        under the same choice, which the loss at `alpha` 0.001 cannot hold.
        Information: the loss's error against the reference under its own
        routing, the largest load over the mean load, the held slots a
        layer (their mean, and the largest over the even share), and the
        slots that were routed nowhere or held and not computed (both
        always 0)."""
        return errors_of(system_loss_and_readings, params, batch)

    return SimpleNamespace(
        mesh=mesh,
        batch_shapes=batch_shapes,
        state_shardings=state_shard,
        flops_per_unit=dsv2_flops.dsv2_flops_per_token(config, seq_len),
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
        model_config=cfg,
    )
