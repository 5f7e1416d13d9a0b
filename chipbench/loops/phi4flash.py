"""The `phi4flash` family: `ray_tpu.models.transformer` as a stack whose
second half reads what its first half made (Phi-4-mini-flash-reasoning;
SambaY, arXiv:2507.06607) through `make_train_step` on the configuration's
mesh: Mamba-1 mixers (the chunked selective scan of
`ray_tpu/ops/selective_scan.py`, float32) and differential attention under
a window, one Mamba-1 layer that also emits its scan's output as the
memory, one whole differential attention layer that also emits its keys and
values, then gated memory units and cross differential attention layers
that read those two; LayerNorm with a bias everywhere, a bias on
attention's projections, no rotary positions, the tied head over the
stage's slice of the vocabulary. bf16 compute over f32 master weights, the
flash kernels where `attention_impl` resolves to them (a differential
layer's two softmaxes as one grouped-query call), the chunked LM-head
cross-entropy, AdamW with no weight decay on `A_log`, `D`, the taps, the
biases, the lambdas and the norms.

`init_params` draws the biases (LayerNorm's, the projections', the taps')
at `check.bias_std` where the initialiser has zeros, so that the comparison
holds them to account: a step that dropped a bias of zeros would read as
the stated one. Training starts from the same values.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chipbench import phi4flash_flops
from chipbench.loops.nemotron_h import decayed
from chipbench.reference import phi4flash as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models.transformer import (
    transformer_init, transformer_loss_and_readings)
from ray_tpu.ops.selective_scan import selective_scan
from ray_tpu.parallel import make_mesh

# System (bf16 matmuls and activations, the chunked scan in float32, the
# flash kernels, f32 loss) against the f32 reference (the recurrence token by
# token, every pair its own masked softmax) on 1 seeded 4096-token sequence
# with random weights at Phi-4-mini-flash's widths: six layers, one of each
# kind. Readings on the chip (my chip runs, PR 61; PERF.md section 6): the
# stated path at fifteen seeds, each lower precision or wrong mathematics at
# two.
# - `loss_rel_err` 2.7e-7 to 4.78e-5. A step whose weights, logits and loss
#   are bf16 as well reads 1.28e-3 and 2.60e-3 and fails, by this key alone
#   (its gradients' distance is the stated path's, 2.81e-2 and 2.86e-2): the
#   bound that tells precisions apart, as in the other transformer families,
#   6.3 times the largest stated reading and 4.3 times under the smaller
#   bf16 one.
# - `grad_rel_err` 2.60e-2 to 3.02e-2 (six layers of bf16 matmuls). The
#   nearest wrong mathematics is `lam` left at its constant part, 8.7e-2 and
#   1.05e-1; a cross layer attending over keys and values made from its own
#   input by the emitter's weights reads 0.144 and 0.160, the memory taken
#   after the gate 0.368 and 0.371, the window off 0.619 and 0.650,
#   LayerNorm's bias dropped 0.899 and 0.911. The bound stands 1.66 times
#   over the largest stated reading and 1.75 times under the smallest wrong
#   one. It does not tell the scan's float32 parts from bf16 ones (3.07e-2
#   and 3.34e-2 with them in bf16): `scan_rel_err` holds that.
# - `lambda_rel_err` 0 at every seed (the same float32 dots and exps on both
#   sides; 1e-7 on a CPU): the differential layers' `lam` as the step reads
#   it against the reference's, the largest relative error over the layers.
#   With the learned part left out (`lam = lam0`) it reads 0.126 and 0.229.
# - `scan_rel_err` 0 at every seed (the chunked scan and the recurrence do
#   the same float32 operations in the same order; 1e-7 on a CPU): the scan
#   as the step computes it against the reference's recurrence token by
#   token, on a seeded probe of 4096 tokens at the mixer's widths. With the
#   scan's decay, state and sums in bf16 it reads 2.046e-3 and 2.048e-3 and
#   fails, by this key alone (`loss_rel_err` 8.7e-6 and 1.6e-5 then): the
#   skip `D u`, which is exact, is most of the output's norm. The bound is
#   four times under the bf16 reading.
# At the tests' tiny size (64 wide) the stated path in bf16 reads
# `grad_rel_err` up to 8e-2; the CPU tests hold each wrong mathematics to
# these bounds in float32, where the stated path agrees to rounding and what
# is left is the fault's own.
TOLERANCE = {"loss_rel_err": 3e-4, "grad_rel_err": 5e-2,
             "lambda_rel_err": 1e-3, "scan_rel_err": 5e-4}

# the program's fields, under the configuration file's own keys
_CONFIG_KEYS = (
    "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_head",
    "d_ff", "max_seq_len", "rope", "norm_eps", "tied_embeddings", "remat",
    "attention_impl", "sliding_window", "layer_norm", "attn_bias",
    "mamba1_inner", "mamba1_state", "mamba1_dt_rank", "mamba1_conv_taps",
    "scan_chunk",
)


def model_config(config: Dict[str, Any]) -> TransformerConfig:
    return TransformerConfig(
        dtype=jnp.dtype(config["dtype"]),
        layer_types=tuple(config["layer_types"]),
        layer_depths=tuple(config["layer_depths"]),
        mamba_dt_init=tuple(config["mamba_dt_init"]),
        **{k: config[k] for k in _CONFIG_KEYS if k in config})


def with_drawn_biases(params, key, std: float):
    """`params` with every bias that starts at zero drawn at `std`: the
    leaves whose name ends in `_bias` (LayerNorm's) or is `b` and a letter
    (the projections') or `conv_b`; `dt_bias` has its own initialiser."""
    def zero_bias(path) -> bool:
        name = str(getattr(path[-1], "key", ""))
        return name != "dt_bias" and (
            name.endswith("_bias") or name == "conv_b"
            or (len(name) == 2 and name[0] == "b"))

    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree.unflatten(tree, [
        std * jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                leaf.dtype) if zero_bias(path) else leaf
        for i, (path, leaf) in enumerate(flat)])


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
    bias_std = float(config["check"]["bias_std"])

    # the state is made where it will live, in two jitted calls from the key
    init_params = jax.jit(
        lambda key: with_drawn_biases(
            transformer_init(key, cfg), jax.random.fold_in(key, 1), bias_std),
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
        (loss, lams), grads = jax.value_and_grad(
            lambda p: reference.forward(p, batch, config), has_aux=True)(params)
        return loss, lams, grads

    def system_side_of(loss_and_readings):
        """The system's loss, its layers' `lam` and its gradients as one
        program."""
        @jax.jit
        def system_side(params, batch):
            (loss, readings), grads = jax.value_and_grad(
                loss_and_readings, has_aux=True)(params, batch)
            return loss, readings["diff_lambda"], grads

        return system_side

    @jax.jit
    def distances(ours, theirs, lams, reference_lams):
        def squares(tree):
            return sum(jnp.sum(y ** 2) for y in jax.tree.leaves(tree))

        apart = squares(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y, ours, theirs))
        return {
            "grad_rel_err": jnp.sqrt(apart / squares(theirs)),
            "lambda_rel_err": jnp.max(
                jnp.abs(lams - reference_lams) / jnp.abs(reference_lams)),
            "diff_lambda": lams,
        }

    def scan_rel_err(tokens, scan_fn=selective_scan):
        """The distance of the scan as the step computes it (`scan_fn`:
        `selective_scan`, at the configuration's chunk) from the
        reference's recurrence, on a seeded probe of one sequence at the
        mixer's widths: `u`, `B` and `C` at unit scale in the compute dtype,
        the step size and the decay over their initialisers' ranges."""
        inner, N = cfg.mamba1_inner, cfg.mamba1_state
        keys = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(0), tokens[0, 0]), 5)
        u = jax.random.normal(keys[0], (1, check_len, inner)).astype(cfg.dtype)
        B, C = (jax.random.normal(k, (1, check_len, N)).astype(cfg.dtype)
                for k in keys[1:3])
        dt = jnp.exp(jax.random.uniform(
            keys[3], (1, check_len, inner), jnp.float32,
            jnp.log(cfg.mamba_dt_init[0]), jnp.log(cfg.mamba_dt_init[1])))
        A = -jnp.broadcast_to(
            jnp.arange(1, N + 1, dtype=jnp.float32), (inner, N))
        D = jnp.ones((inner,), jnp.float32)
        ours = jax.jit(lambda *a: scan_fn(*a, chunk=cfg.scan_chunk)[0])(
            u, dt, A, B, C, D).astype(jnp.float32)
        theirs = jax.jit(reference.recurrence)(
            *(x.astype(jnp.float32) for x in (u, dt, A, B, C, D)))
        return jnp.sqrt(jnp.sum((ours - theirs) ** 2) / jnp.sum(theirs ** 2))

    def errors_of(loss_and_readings, params, batch, reference_outputs=None,
                  scan_fn=selective_scan):
        """The comparison of a system `(params, batch) -> (loss, readings)`
        with the reference: two programs, each a loss, the layers' `lam`
        and the gradients, and their distances. `reference_outputs` is what
        `reference_side(params, batch)` gave, where several systems are
        held against one reference; `scan_fn` is the system's
        `selective_scan`, for the probe."""
        batch = {"tokens": batch["tokens"], "targets": batch["targets"]}
        l_ref, lam_ref, g_ref = reference_outputs or reference_side(
            params, batch)
        l_sys, lam_sys, g_sys = system_side_of(loss_and_readings)(
            params, batch)
        info = distances(g_sys, g_ref, lam_sys, lam_ref)
        del g_sys, g_ref
        info["scan_rel_err"] = scan_rel_err(batch["tokens"], scan_fn)
        info = {k: np.asarray(v).tolist() for k, v in info.items()}
        l_sys, l_ref = float(l_sys), float(l_ref)
        return {"loss_system": l_sys, "loss_reference": l_ref,
                "loss_rel_err": abs(l_sys - l_ref) / abs(l_ref), **info}

    def check(params, batch):
        """Judged: `loss_rel_err`; `grad_rel_err`, the distance of the
        gradients over all the parameters; `lambda_rel_err`, the largest
        relative error of a differential layer's `lam` as the step reads
        it; and `scan_rel_err`, the chunked scan against the recurrence
        token by token on a probe. Information: the step's `diff_lambda`
        on this batch, a layer each."""
        return errors_of(system_loss_and_readings, params, batch)

    return SimpleNamespace(
        mesh=mesh,
        batch_shapes=batch_shapes,
        state_shardings=state_shard,
        flops_per_unit=phi4flash_flops.phi4flash_flops_per_token(
            config, seq_len),
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
        scan_rel_err=scan_rel_err,
        model_config=cfg,
    )
