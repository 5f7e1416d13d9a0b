"""The `kimi_linear` family: `ray_tpu.models.transformer` as a stack of Kimi
Delta Attention layers (`ray_tpu/ops/kda.py`: a matrix state updated by a
delta rule under a decay a key channel, in its chunked form, all 32 heads,
beta a sigmoid in (0, 1)) and one latent-attention layer without positions
(`mla_use_nope`: a low-rank key-value projection, heads of 128 + 64
query-key columns and 128 value columns, nothing rotated), three to one,
over a leading dense feed-forward and routed feed-forwards that hold 8 of
256 experts beside one shared expert under a sigmoid router with a
selection bias, normalised weights and the factor 2.446
(Kimi-Linear-48B-A3B) through `make_train_step` on the configuration's
mesh. bf16 compute over f32 master weights, a float32 router, KDA's decays,
sums, solve and chunk states in float32, the flash kernels at 192 / 128 and
the grouped-matmul kernels of `ray_tpu/ops/moe.py` over the held rows where
`attention_impl` resolves to them, the chunked LM-head cross-entropy over
the untied head, AdamW with no weight decay on `A_log`, `dt_bias`, the taps,
the gate's bias and the norms, and the selection bias as state the
optimizer does not own.

What `init_params` returns, and `check` and `init_state` take, is the pair
`{"params", "expert_bias"}`, as in `loops/lfm2_moe.py`: the weights, and a
selection bias drawn at `check.expert_bias_std` for the comparison, which a
zero bias would not hold to account for the selection. `init_state` keeps
the weights and not the drawn bias: training starts from the bias that
evens the experts' load on the seeded weights (`balanced_bias`, after
`loops/nemotron_h.py`'s), which is where the published rule holds it for
all of a run but its first hundreds of steps. Under seeded weights and
uniform ids the tokens of a sequence share most of their stream, so a few
experts a layer are every token's favourites (the fullest expert took 20 to
24 times the mean load on the comparison's batch): from a zero bias the 8
held experts' rows were 0.6 to 3.7 even shares a layer by the seed, a step
took 789.9 to 796.6 ms with them, and four seeds' rates ranged over 0.86 %
(my chip runs, PR 66).
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chipbench import kimi_linear_flops
from chipbench.loops.nemotron_h import decayed
from chipbench.loops.solar_open2 import kda_probe as solar_probe
from chipbench.reference import kimi_linear as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models.transformer import (
    expert_bias_init, transformer_init, transformer_loss_and_readings)
from ray_tpu.ops import moe
from ray_tpu.ops.kda import kda
from ray_tpu.parallel import make_mesh


# System (bf16 matmuls and activations, the chunked KDA with float32
# decays, solve and states, the flash kernels at 192 / 128 and the
# grouped-matmul kernels, a float32 router, f32 loss) against the f32
# reference (the recurrence token by token, a full softmax over unrotated
# keys) on 1 seeded 2048-token sequence (32 chunks: the state crosses
# chunks) with random weights at Kimi-Linear-48B-A3B's widths: 5 layers, all
# 32 KDA heads, 8 of 256 experts, a selection bias drawn at standard
# deviation 0.1. Loss and gradients are compared under one routing, the
# system's, for `loops/moe_transformer.py`'s reason: the system's router
# sees bf16 activations, and a slot that flips moves a whole row between two
# experts' weight gradients (or into or out of the held share).
# Readings on the chip (my chip runs, PR 66; PERF.md section 6): the stated
# path over twenty-one seeds, each lower precision or wrong mathematics at two
# (`tests/chipbench_tests/test_chipbench_kimi_linear.py` `wrong_systems` has
# them; the CPU tests hold each to these bounds in float32, where the stated
# path agrees to rounding and what is left is the fault's own).
# - `loss_rel_err` 9.2e-8 to 9.18e-5. A step whose weights, activations,
#   router, logits and loss are bf16 as well reads 2.02e-3 and 1.58e-3 and
#   fails, by this key alone (its `grad_rel_err` is 4.29e-2 and 4.15e-2,
#   the stated path's): the bound that tells precisions apart, as in the other
#   transformer families, 3.3 times the largest stated reading and a fifth
#   of the smaller bf16 one.
# - `grad_rel_err` 3.736e-2 to 4.249e-2 (five layers of bf16 matmuls, the
#   flash kernels' scores at 192 wide in bf16; Solar's four layers read 2.5e-2,
#   DeepSeek-V2-Lite's six 4.3e-2 to 4.6e-2). Beta as `2 sigmoid` (Solar's
#   key) reads 1.075 and 1.081, the factor 2.446 dropped 0.105 and 0.147;
#   the 64 columns rotated (DeepSeek's form) 7.59e-2 and 7.75e-2: one layer
#   of five, whose scores at seeded weights are small either way, so this
#   key alone would stand only 1.27 times under it, and `latent_grad_rel_err`
#   holds that layer. The bound stands 1.4 times over the largest stated
#   reading. It does not tell the recurrence's float32 parts from bf16 ones
#   (5.59e-2 and 5.28e-2); `kda_rel_err` holds that.
# - `latent_grad_rel_err` 2.343e-2 to 2.721e-2: the same distance over the
#   latent-attention layer's own leaves alone. The 64 columns rotated read
#   0.1103 and 0.1153 and fail by this key and by `grad_rel_err`; beta as
#   `2 sigmoid` 0.601 and 0.592 (the layer's input has moved). The bound is
#   1.8 times the largest stated reading and 2.2 times under the
#   rotated one. The latent's norm left out reads 3.79e-2 and 3.10e-2 here and
#   4.15e-2 and 3.97e-2 over all the leaves, inside both bounds: at seeded
#   weights the latent's RMS is within a few percent of 1 and the norm all
#   but the identity; the CPU tests hold it at weights where it is not.
# - `kda_rel_err` 3.520e-3 to 3.678e-3: the recurrence as the step runs it
#   against the reference's on `kda_probe` (what is read is the rounding of the
#   matmuls' bf16 operands), Solar's readings to two digits at four times
#   the heads. With the decays' sums, every exp, the solve and the chunk
#   states in bf16 (the `jax.numpy` form, part for part the kernels'
#   mathematics: the kernels' float32 is their own `_ACC`) it reads 1.360e-2
#   and 1.377e-2 and fails, by this key alone. The bound is 1.8 times the
#   largest stated reading and 2.1 times under the smaller bf16 one.
# - `router_flip_share` 1.52e-2 to 1.72e-2 (8 of 256 by a sigmoid plus a
#   bias whose eighth and ninth lie close; the reference's own rule on the
#   stream the system's routing made). With the reference under its own
#   routing throughout, a third program that the first runs had, the stated
#   path read 1.75e-2 to 2.21e-2 at eight seeds, a router that ignores the
#   bias 0.552 (it fails by this key alone), beta as `2 sigmoid` 0.367 and
#   0.368, the factor dropped 2.8e-2 and 4.1e-2, the columns rotated 2.3e-2
#   and 2.4e-2 (the choice moves with the layers before it). The bound is
#   1.7 times the largest stated reading.
# Information, not judged: weights read off the biased scores are inside
# every bound on the chip (4.13e-2 for the stated 3.89e-2 at the same seed: a
# bias of 0.1 on weights that are normalised afterwards); the CPU tests hold
# them in float32 (0.237).
TOLERANCE = {"loss_rel_err": 3e-4, "grad_rel_err": 6e-2,
             "latent_grad_rel_err": 5e-2, "router_flip_share": 3e-2,
             "kda_rel_err": 6.5e-3}

# the program's fields, under the configuration file's own keys
_CONFIG_KEYS = (
    "vocab_size", "d_model", "n_layers", "n_heads", "layer_types", "rope",
    "kda_heads", "kda_head_dim", "kda_conv_taps", "kda_gate_rank",
    "kda_chunk", "kda_allow_neg_eigval", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "n_dense_layers", "d_ff_dense", "d_ff",
    "n_experts", "experts_held", "experts_per_token", "norm_topk_prob",
    "router_score", "expert_bias", "expert_bias_update_rate",
    "routed_scaling_factor", "n_shared_experts", "router_aux_loss_coef",
    "router_z_loss_coef", "max_seq_len", "norm_eps", "tied_embeddings",
    "remat", "attention_impl",
)


# a latent-attention layer's own leaves (`latent_grad_rel_err`)
_LATENT_LEAVES = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")


def model_config(config: Dict[str, Any]) -> TransformerConfig:
    values = {k: config[k] for k in _CONFIG_KEYS if k in config}
    for key in ("layer_types", "experts_held"):
        values[key] = tuple(values[key])
    return TransformerConfig(dtype=jnp.dtype(config["dtype"]), **values)


def kda_probe(cfg: TransformerConfig, seq_len: int, key):
    """`loops/solar_open2.py`'s probe at this model's heads (q and k of unit
    length, v, log decays of a thousandth to 1.6 of a nat a token) with beta
    `sigmoid(normal)`, in (0, 1): half of Solar's `2 sigmoid`, bit for bit."""
    *rest, beta = solar_probe(cfg, seq_len, key)
    return (*rest, beta / 2)


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
        """The selection bias a run starts from: the step's own rule
        (`moe.update_expert_bias`: a rate up for an expert under the mean
        load, a rate down for one over it) applied `start.rounds` times from
        zero, each time on the load of one seeded sequence of the
        comparison's length of uniform ids, the rate falling from
        `start.rate_first` to `start.rate_last`. The loads are read off the
        comparison's own program (`stated_side`: its gradients go unused),
        so that a run compiles and keeps no program for this alone: a
        forward program of its own was 22.8 MB of a compile cache of 190 MiB
        beside the step's 56.1, the comparison's 47.7 and the reference's
        51.2, and with it no run found any program of the run before
        (`setup_s` 294 to 311 s where a warm run's is 90; my chip runs,
        PR 66)."""
        rounds = int(start["rounds"])
        ratio = start["rate_last"] / start["rate_first"]
        ids = np.asarray(jax.random.randint(
            jax.random.PRNGKey(0), (rounds, 1, check_len + 1), 0,
            cfg.vocab_size))
        move = jax.jit(moe.update_expert_bias)
        bias = jax.device_put(
            expert_bias_init(cfg), state_shard["expert_bias"])
        for i in range(rounds):
            batch = to_device({"tokens": ids[i]})
            load = stated_side(params, batch, bias)[1]["expert_load"]
            bias = move(bias, load,
                        start["rate_first"] * ratio ** (i / (rounds - 1)))
        return bias

    def init_state(made):
        opt, count = jax.jit(
            lambda p: (optimizer.init(p), jnp.zeros((), jnp.int32)),
            out_shardings=(state_shard["opt"], state_shard["step"]),
        )(made["params"])
        if isinstance(count, jax.core.Tracer):  # under `eval_shape`
            bias = expert_bias_init(cfg)
        else:
            bias = balanced_bias(made["params"])
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

    def system_side_of(loss_and_readings):
        """The system's loss, readings and gradients as one program."""
        @jax.jit
        def system_side(params, batch, bias):
            (loss, readings), grads = jax.value_and_grad(
                loss_and_readings, has_aux=True)(params, batch, bias)
            return loss, readings, grads

        return system_side

    @jax.jit
    def reference_side(params, batch, index, bias):
        # the choice is an argument: as a constant of the reference's
        # program it would make every seed a miss of the compile cache
        def loss_and_own(p):
            loss, own_choice, beta = reference.forward(
                p, batch, config, index, bias)
            return loss, (own_choice, beta)

        return jax.value_and_grad(loss_and_own, has_aux=True)(params)

    # the stated system's side, one program for the comparison and for
    # `balanced_bias`
    stated_side = system_side_of(system_loss_and_readings)

    def system_kda(q, k, v, g, beta):
        """The KDA layers' recurrence as the step runs it."""
        return kda(q, k, v, g, beta, chunk=cfg.kda_chunk)[0]

    def kda_rel_err(kda_fn, tokens):
        """The distance of `kda_fn` from the reference's recurrence, token
        by token, on `kda_probe`, over the reference's norm."""
        probe = kda_probe(cfg, check_len, jax.random.fold_in(
            jax.random.PRNGKey(0), tokens[0, 0]))
        ours = jax.jit(kda_fn)(*probe).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            theirs = jax.jit(reference.delta_rule)(
                *(x.astype(jnp.float32) for x in probe))
        return jnp.linalg.norm(ours - theirs) / jnp.linalg.norm(theirs)

    def errors_of(loss_and_readings, made, batch, kda_fn=system_kda):
        """The comparison of a system `(params, batch, expert_bias) ->
        (loss, readings)` with the reference under the system's routing,
        and of its recurrence `kda_fn` with the reference's on a probe.
        Two programs: the system's loss, readings and gradients; the
        reference's loss and gradients under the system's choice of
        experts, with the choice its own rule makes, given the same bias,
        at every routed layer of that same stream (a third program, the
        reference under its own routing throughout, as the other routed
        families run, cost 22 s of a first run's 340: `chipbench/run.py`)."""
        params, bias = made["params"], made["expert_bias"]
        held = cfg.held[1]

        @jax.jit
        def distances(ours, theirs, readings, index, own_choice):
            def apart(ours, theirs):
                num = sum(jnp.sum((x.astype(jnp.float32) - y) ** 2)
                          for x, y in zip(jax.tree.leaves(ours),
                                          jax.tree.leaves(theirs)))
                den = sum(jnp.sum(y ** 2) for y in jax.tree.leaves(theirs))
                return jnp.sqrt(num) / jnp.sqrt(den)

            def latent(grads):  # the latent-attention layers' own leaves
                return [{name: tree[name] for name in _LATENT_LEAVES}
                        for segment in grads["blocks"] for tree in segment
                        if "wkv_a" in tree]

            chose = jax.nn.one_hot(
                index, cfg.n_experts, dtype=jnp.int32).sum(-2) > 0
            flips = jnp.logical_and(chose, jnp.logical_not(own_choice)).sum()
            load = readings["expert_load"].astype(jnp.float32)  # [L, E]
            slots = index.size / index.shape[0]
            return {
                "grad_rel_err": apart(ours, theirs),
                "latent_grad_rel_err": apart(latent(ours), latent(theirs)),
                "router_flip_share": flips / index.size,
                "expert_load_max_over_mean": jnp.max(
                    load.max(axis=-1) / load.mean(axis=-1)),
                "unrouted_slots": index.size - load.sum(),
                "dropped_slots": readings["dropped_slots"].sum(),
                "held_slots_mean": readings["held_slots"].mean(),
                "held_slots_max_over_even": readings["held_slots"].max() / (
                    slots * held / cfg.n_experts),
                "kda_log_decay_min": readings["kda_log_decay_min"],
                "kda_beta_mean": readings["kda_beta_mean"],
            }

        batch = {"tokens": batch["tokens"], "targets": batch["targets"]}
        side = (stated_side if loss_and_readings is system_loss_and_readings
                else system_side_of(loss_and_readings))
        l_sys, readings, g_sys = side(params, batch, bias)
        index = readings["expert_index"]  # [routed layers, tokens, k]
        (l_ref, (own_choice, beta_ref)), g_ref = reference_side(
            params, batch, index, bias)
        info = distances(g_sys, g_ref, readings, index, own_choice)
        del g_sys, g_ref
        info["kda_rel_err"] = kda_rel_err(kda_fn, batch["tokens"])
        info["kda_beta_mean_reference"] = beta_ref
        info = {k: float(v) for k, v in info.items()}
        l_sys, l_ref = float(l_sys), float(l_ref)
        return {"loss_system": l_sys, "loss_reference": l_ref,
                "loss_rel_err": abs(l_sys - l_ref) / abs(l_ref), **info}

    def check(made, batch):
        """Judged: `loss_rel_err` and `grad_rel_err`, the reference taking
        the system's choice of experts; `latent_grad_rel_err`, the same
        distance over the latent-attention layer's own leaves alone (`W_q`,
        `W_kva`, the latent's norm, `W_kvb`, `W_o`: one layer of five at the
        start of training moves the whole model's distance little, and this
        key holds what that layer computes); `router_flip_share`, the share of
        the slots whose expert the reference's own rule, given the same bias
        and the same stream, did not choose for that token; and
        `kda_rel_err`, the recurrence as the step
        runs it (the chunked form, bf16 operands, float32 decays, solve and
        states) against the reference's, token by token, on a probe of the
        layers' own shapes (`kda_probe`): the loss and the gradients of the
        whole model do not tell the recurrence's float32 parts from bf16
        ones, this key does. Information: the largest load over the mean
        load, the held slots a layer (their mean, and the largest over the
        even share), the slots that were routed nowhere or held and not
        computed (both always 0), and the KDA layers' readings on this
        batch: `kda_log_decay_min` (the most negative running log decay at a
        chunk's end) and `kda_beta_mean`, beside the reference's own mean
        beta."""
        errors = errors_of(system_loss_and_readings, made, batch)
        # the reference's programs go with the comparison: a loaded
        # program's scratch stays reserved on the device, and the window's
        # `memory_peak_bytes` would read that beside the step's
        reference_side.clear_cache()
        return errors

    return SimpleNamespace(
        mesh=mesh,
        batch_shapes=batch_shapes,
        state_shardings=state_shard,
        flops_per_unit=kimi_linear_flops.flops_per_token(config, seq_len),
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
        kda_rel_err=kda_rel_err,
        reference_side=reference_side,
        system_side_of=system_side_of,
        model_config=cfg,
    )
