"""The `nemotron_h` family: `ray_tpu.models.transformer` as a stack of
sublayers, each a Mamba-2 mixer (the chunked scan of `ray_tpu/ops/ssd.py`),
GQA attention without rotary positions, or a routed feed-forward alone:
ungated `relu2` experts of which this chip holds a share, one shared expert
beside them, a sigmoid router with a selection bias and the factor 2.5 on
its weights (NVIDIA-Nemotron-3-Nano-30B-A3B) through `make_train_step` on
the configuration's mesh. bf16 compute over f32 master weights, a float32
router, the scan's decays, sums and chunk states in float32, the flash
kernels and the grouped-matmul kernels of `ray_tpu/ops/moe.py` over the
held rows where `attention_impl` resolves to them, the chunked LM-head
cross-entropy over the untied head, AdamW with no weight decay on the
mixers' `A_log`, `D`, `dt_bias`, the convolution's bias and the norms, and
the selection bias as state the optimizer does not own.

What `init_params` returns, and `check` and `init_state` take, is the pair
`{"params", "expert_bias"}`, as in `loops/lfm2_moe.py`: the weights, and a
selection bias drawn at `check.expert_bias_std` for the comparison, which a
zero bias would not hold to account for the selection. `init_state` keeps
the weights and not the drawn bias: training starts from the bias that
evens the experts' load on the seeded weights (`balanced_bias`), which is
where the published rule holds it for all of a run but its first hundreds
of steps. An ungated `relu2` feed-forward adds the same vector to every
token (its hidden units' mean is not zero), so under random weights some
experts are every token's favourites: from a zero bias the 8 held experts
took more than 1.25 even shares in none to three of the four layers by the
seed, each such layer's second chunk of held rows cost 10.5 ms a step, and
six seeds' rates spread by 3.8 % (my chip runs, PR 38).
"""

from __future__ import annotations

import logging
from types import SimpleNamespace
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chipbench import compare, nemotron_h_flops
from chipbench.reference import nemotron_h as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models.transformer import (
    expert_bias_init, transformer_init, transformer_loss_and_readings)
from ray_tpu.ops import moe
from ray_tpu.parallel import make_mesh

logger = logging.getLogger(__name__)

# System (bf16 matmuls and activations, the chunked scan with float32
# decays, the flash and grouped-matmul kernels, a float32 router, f32 loss)
# against the f32 reference (the recurrence token by token) on 2 seeded
# 1024-token sequences with random weights at Nemotron-3-Nano's widths: 9
# sublayers, 8 of 128 experts, a selection bias drawn at standard deviation
# 0.1. Loss and gradients are compared under one routing, the system's, for
# `loops/moe_transformer.py`'s reason.
# Readings on the chip (my chip runs, PR 38; PERF.md section 6): the stated
# path over 21 seeds, each lower precision or wrong mathematics at 2.
# - `loss_rel_err` 3.3e-6 to 6.13e-5. A step whose weights, activations,
#   router, logits and loss are bf16 as well reads 1.88e-3 and 1.89e-3 and
#   fails, by this key alone (its `grad_rel_err` is 1.90e-2 and 1.99e-2):
#   the bound that tells precisions apart, as in the other transformer
#   families, five times the largest stated reading and a sixth of the
#   bf16 one. The scan alone in bf16 reads 4.1e-5 and 1.04e-4: the loss
#   does not hold the scan, the next two keys do.
# - `grad_rel_err` 1.794e-2 to 2.282e-2 (nine sublayers of bf16 matmuls;
#   DeepSeek's six layers read 4.3e-2, with attention's scores in all six).
#   The nearest lower precision is weights rounded to bf16 with no float32
#   master copy, 4.07e-2 and 4.41e-2; the whole scan in bf16 (decays, sums,
#   exponentials, accumulators, chunk states) reads 6.46e-2 and 7.73e-2,
#   the running sums of `dt A` alone in bf16 9.63e-2 and 9.71e-2 (a sum of
#   128 steps rounded to 8 bits, in an exponent); the nearest wrong
#   mathematics is rotary positions applied, 9.01e-2 and 9.32e-2. The
#   factor 2.5 left out reads 0.253 and 0.320, the gate after the norm
#   0.446 and 0.448, the skip `D x` left out 1.19 and 1.22, the gate left
#   out 1.27. The bound stands 1.31 times over the largest stated reading
#   (five and a half of the stated readings' standard deviations, 1.3e-3,
#   over it) and 1.36 times under the smallest lower-precision one.
# - `router_flip_share` 1.011e-2 to 1.353e-2 (6 of 128 by sigmoid scores
#   plus a bias: the sixth and seventh lie close). The scan in bf16 reads
#   3.38e-2 and 3.54e-2, its sums alone 3.70e-2 and 3.73e-2, rotary
#   positions 2.44e-2 and 2.46e-2 (the choice moves with the layers before
#   it); a router that ignores the bias fails by this key alone. The bound
#   stands 1.48 times over the largest stated reading and 1.22 times under
#   the smallest wrong one.
# - `aux_loss_rel_err` 0 to 5.55e-5: the system's balance loss (the
#   routed layers' mean, before its coefficient) against the reference's
#   under the same choice. The scores divided by their sum over the
#   experts before the mean (DeepSeek-V3's form) read 0.984 here, and
#   `loss_rel_err` 6.5e-4 and 7.0e-4 (the term is 64 x 1e-4 of a loss of
#   10.2: at a tenth of the coefficient the loss would not hold it, this
#   key does), with the stated path's readings in the other two keys. The
#   bound is 18 times the largest stated reading.
# At the tests' tiny size the stated path in bf16 reads `grad_rel_err` up
# to 7.2e-2 (32 wide: the rounding of one element weighs more); the CPU
# tests hold each wrong mathematics to these bounds in float32, where the
# stated path agrees to rounding and what is left is the fault's own.
TOLERANCE = {"loss_rel_err": 3e-4, "grad_rel_err": 3e-2,
             "router_flip_share": 2e-2, "aux_loss_rel_err": 1e-3}

# the program's fields, under the configuration file's own keys
_CONFIG_KEYS = (
    "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_head",
    "d_ff", "d_ff_shared", "max_seq_len", "rope", "rope_theta", "remat",
    "attention_impl", "norm_eps", "tied_embeddings", "n_experts",
    "experts_per_token", "norm_topk_prob", "norm_topk_eps", "router_score",
    "router_aux_loss_coef", "router_z_loss_coef", "routed_scaling_factor",
    "expert_bias", "expert_bias_update_rate", "experts_held",
    "n_shared_experts", "ff_activation", "sublayer_types", "mamba_heads",
    "mamba_head_dim", "ssm_state", "ssm_groups", "mamba_conv_taps",
    "ssd_chunk", "mamba_dt_init", "rescale_prenorm_residual",
)


def model_config(config: Dict[str, Any]) -> TransformerConfig:
    values = {k: config[k] for k in _CONFIG_KEYS if k in config}
    for key in ("sublayer_types", "experts_held", "mamba_dt_init"):
        values[key] = tuple(values[key])
    return TransformerConfig(dtype=jnp.dtype(config["dtype"]), **values)


def decayed(params, no_decay):
    """A tree of bools like `params`: False for a leaf whose name holds one
    of `no_decay` (the mixers' vectors, the convolution's bias, the norms)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: not any(
            word in str(getattr(path[-1], "key", "")) for word in no_decay),
        params)


def build(config: Dict[str, Any], traffic: Dict[str, Any], devices) -> Any:
    cfg = model_config(config)
    mesh = make_mesh(config["mesh"], devices=devices)
    opt_cfg = config["optimizer"]
    # the warm-up a run's first steps see (`assumed`)
    optimizer = optax.adamw(
        optax.linear_schedule(
            0.0, opt_cfg["learning_rate"], opt_cfg["warmup_steps"]),
        b1=opt_cfg["b1"], b2=opt_cfg["b2"],
        weight_decay=opt_cfg["weight_decay"],
        mask=lambda params: decayed(params, opt_cfg["no_decay"]))
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

    start = config["start"]

    def balanced_bias(params):
        """(the selection bias a run starts from, the load [L, E] it leaves
        on one more round's sequences): the step's own rule (`moe.update_expert_bias`: a rate up
        for an expert under the mean load, a rate down for one over it)
        applied `start.rounds` times from zero, on seeded sequences of
        `start.seq_len` uniform ids, forward passes alone, the rate falling
        from `start.rate_first` to `start.rate_last`."""
        rounds = int(start["rounds"])
        ratio = start["rate_last"] / start["rate_first"]

        def load_under(bias, i):
            ids = jax.random.randint(
                jax.random.fold_in(jax.random.PRNGKey(0), i),
                (int(start["rows"]), int(start["seq_len"]) + 1), 0,
                cfg.vocab_size)
            return transformer_loss_and_readings(
                params, {"tokens": ids[:, :-1], "targets": ids[:, 1:]}, cfg,
                mesh=mesh, expert_bias=bias)[1]["expert_load"]

        def one_round(i, bias):
            rate = start["rate_first"] * ratio ** (i / (rounds - 1))
            return moe.update_expert_bias(bias, load_under(bias, i), rate)

        bias = jax.lax.fori_loop(0, rounds, one_round, expert_bias_init(cfg))
        return bias, load_under(bias, rounds)

    def init_state(made):
        opt, count, (bias, load) = jax.jit(
            lambda p: (optimizer.init(p), jnp.zeros((), jnp.int32),
                       balanced_bias(p)),
            out_shardings=(state_shard["opt"], state_shard["step"],
                           (state_shard["expert_bias"],) * 2),
        )(made["params"])
        if not isinstance(load, jax.core.Tracer):  # not under `eval_shape`
            first, held = cfg.held
            shares = np.asarray(load, np.float32) * cfg.n_experts / load[0].sum()
            mine = shares[:, first:first + held].mean(axis=1)
            logger.info(
                "the run starts from a selection bias of at most %.4f: an "
                "expert's load is %.3f to %.3f even shares, the %d held "
                "experts' %.3f to %.3f a layer", jnp.abs(bias).max(),
                shares.min(), shares.max(), held, mine.min(), mine.max())
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
            own_loss, theirs, _ = reference.forward(
                params, batch, config, expert_bias=bias)
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
        the system's choice of experts; `router_flip_share`, the share of
        the slots whose expert the reference, given the same bias, did not
        choose for that token; and `aux_loss_rel_err`, the system's balance
        loss (the layers' mean, before its coefficient) against the
        reference's under the same choice, which the loss at a coefficient
        of 1e-4 cannot hold. Information: the loss's error against the
        reference under its own routing, the largest load over the mean
        load, the held slots a layer (their mean, and the largest over the
        even share), and the slots that were routed nowhere or held and not
        computed (both always 0)."""
        return errors_of(system_loss_and_readings, made, batch)

    return SimpleNamespace(
        mesh=mesh,
        batch_shapes=batch_shapes,
        state_shardings=state_shard,
        flops_per_unit=nemotron_h_flops.nemotron_h_flops_per_token(
            config, seq_len),
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
