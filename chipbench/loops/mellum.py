"""The `mellum` family: `ray_tpu.models.transformer` as Mellum2-12B-A2.5B's
stack (window-1024 and full attention 3 : 1, 32 query heads over 4 key-value
heads of 128 on both, rotary by layer type: plain theta 500,000 under the
window, YaRN's frequencies with an explicit `attention_factor` on a full
layer; every layer routed, 8 of 64 experts of width 896 by a softmax router
whose chosen weights are normalised, no shared expert, no dense layer, untied
head) through `make_train_step` on the configuration's mesh, which has an
`expert` axis: a chip holds 16 whole experts of every layer, its own
sequences and a quarter of everything else, and the routed layer's exchange
runs under `shard_map` (`models/transformer.py` `_routed_ffn`). bf16 compute
over f32 master weights, a float32 router, the flash kernels with and without
the window and the grouped-matmul kernels of `ray_tpu/ops/moe.py` over the
rows routed to a chip's experts where `attention_impl` resolves to them, the
chunked LM-head cross-entropy over the untied head, AdamW.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chipbench import mellum_flops
from chipbench.loops.laguna import window_edge_probe
from chipbench.reference import laguna as band_reference
from chipbench.reference import mellum as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models.transformer import (
    _attention, _routed_ffn, _router_logits, transformer_init,
    transformer_loss_and_readings)
from ray_tpu.parallel import make_mesh

# System (bf16 matmuls and activations, the flash kernels with and without
# the window, the grouped-matmul kernels over the rows the exchange brings, a
# float32 router, f32 loss) against the f32 reference of the whole layer on 4
# seeded 2048-token sequences, one a chip (two windows deep: the band's lower
# edge falls inside tiles and between them; the exchange runs in it), with
# random weights at Mellum2's widths: 4 layers, all 64 experts, the whole
# vocabulary, on the cell's four chips.
#
# Loss and gradients are compared under one routing, the system's, for
# `loops/moe_transformer.py`'s reason: the system's router sees bf16
# activations, and a slot that flips moves a whole row between two experts'
# weight gradients.
# Readings on the four chips (my chip runs, PR 50; PERF.md section 6): the
# stated path over the 9 seeds of the cell's first runs (and 3 more since,
# inside the same ranges), each wrong mathematics at one seed (a four-chip
# call is four times the chip time) and the bf16 control at three, all at
# the published widths on 4 rows of 2,048.
# - `loss_rel_err` 0 to 2.18e-5. One chip's partial result left out reads
#   3.1e-4, the chosen weights not normalised 9.2e-4, the balance loss over
#   a chip's own tokens 1.3e-3: the bound is 9 times the largest stated
#   reading and 1.6 times under the smallest wrong one. It does not tell
#   precisions apart: with every leaf bf16 it reads 6.6e-6 to 1.48e-5
#   (three seeds), inside the stated range. (A loss scalar rounded to bf16
#   reads its own distance from a grid of 0.0625 at 12, anything up to
#   2.6e-3, and nothing of the computation: 5.35e-4 at one seed, where
#   another seed's loss of 11.99936 would have read 5.3e-5.)
# - `grad_rel_err` 2.909e-2 to 3.154e-2 (four layers of bf16 matmuls;
#   Laguna-XS.2's five read 3.8e-2 to 4.2e-2). The nearest wrong mathematics
#   is YaRN's cos and sin without `attention_factor` at 0.1254; plain
#   frequencies on the full layer read 0.1974, one chip's partial result left
#   out of the sum 0.6131, the chosen weights not normalised 0.6617. The
#   bound stands 1.9 times over the largest stated reading and 2.1 times
#   under the smallest wrong one. It does not tell a bf16 backward from the
#   stated one (bf16 everywhere: 3.10e-2), and it does not hold the window
#   to a key (the probe does).
# - `window_edge_err` 1.6476e-3 to 1.6526e-3 (what is read is the rounding
#   of p and v to bf16). A window of 1025 reads 0.1228, one of 1023 0.1393:
#   the bound is 12 times the stated reading and 6 times under the smaller
#   wrong one. This key alone holds the window's edge.
# - `router_flip_share` 7.72e-3 to 8.92e-3 (8 of 64 by a softmax, on each
#   layer's own input under the system's routing). Nothing else holds the
#   choice itself. The nearest wrong reading is the missing
#   `attention_factor` at 1.74e-2 (the choice moves with the layers before
#   it); plain frequencies read 2.37e-2, the partial result left out 0.203,
#   weights not normalised 0.265. The bound is 1.46 times the largest stated
#   reading, twelve of the seeds' standard deviations over it, and 1.34
#   times under the smallest wrong one.
# - `aux_loss_rel_err` 4.0e-7 to 2.91e-4: the system's balance loss over the
#   mesh's whole batch (the mean over the layers, before its coefficient)
#   against the reference's under the same choice; a softmax over 64 experts
#   whose fullest takes four times the mean rounds harder than the other
#   families' sigmoid scores (Laguna-XS.2: 2.6e-6 to 2.6e-5). The wrong
#   mathematics this key is for, the balance loss over a chip's own tokens
#   and not the mesh's batch, reads 0.5565 (and 1.3e-3 in `loss_rel_err`);
#   the nearest wrong reading is one chip's partial result left out at
#   2.9e-2 (the routing moves), the weights not normalised read 6.7e-2. The
#   bound is 6.9 times the largest stated reading and 14 times under the
#   smallest wrong one.
# - `exchange_rel_err` 3.338e-3 to 3.342e-3: the routed layer over the mesh
#   against the whole layer on one device, the same seeded rows: what is
#   read is the partial results' rounding to bf16 and their sum in bf16
#   (the float32 CPU tests read 4e-8). One chip's partial result left out of
#   the sum reads 0.5141. The bound is the two readings' geometric mean,
#   12 times over the one and 13 times under the other. This key alone holds
#   the exchange apart from everything else in the step.
# - `router_logits_rel_err` 1.0145e-7 to 1.0218e-7 (twelve seeds, one chip:
#   the probe is one device's): layer 0's router on 2,048 seeded bf16 rows,
#   float32 at `Precision.HIGHEST`, against the same product in float64 on
#   the host. The stated path is bf16 wherever a bf16 program is, but for
#   the norms' statistics, the kernels' softmax, the head's logsumexp and
#   the router, and of those the router's logits alone leave the program
#   element by element; so this key is the one that tells the stated
#   precision from the one below it. The leaf rounded to bf16, which is all
#   that a bf16 program's router differs by, reads 1.6576e-3 to 1.6738e-3
#   (four seeds), and so, to the digit, do a bf16 dot as well and float32
#   operands at the TPU's default precision (one bf16 pass). The bound is
#   98 times the largest stated reading and 166 times under the smallest
#   lower one. Through `errors_of` on the four chips the bf16 control (the
#   stated step with every leaf bf16, its loss scalar float32) came out not
#   correct at three seeds of three, by this key (1.6576e-3, 1.6738e-3,
#   1.6668e-3) and by no other: `loss_rel_err` 6.6e-6 to 1.48e-5,
#   `grad_rel_err` 3.02e-2 to 3.10e-2, `router_flip_share` 8.16e-3 to
#   8.35e-3, `aux_loss_rel_err` 3.0e-5 to 1.55e-4, the two probes' readings
#   the stated ones. What the key does not hold: a router at three bf16
#   passes (`Precision.HIGH`; not measured), and the precision of anything
#   but the router.
# At the tests' tiny size the stated path in bf16 reads `grad_rel_err` up to
# 5e-2 (four layers 64 wide, means over 128 tokens); the CPU tests hold each
# wrong mathematics to these bounds in float32, where the stated path agrees
# to rounding and what is left is the fault's own.
TOLERANCE = {"loss_rel_err": 2e-4, "grad_rel_err": 6e-2,
             "router_flip_share": 1.3e-2, "aux_loss_rel_err": 2e-3,
             "window_edge_err": 2e-2, "exchange_rel_err": 4e-2,
             "router_logits_rel_err": 1e-5}

# the program's field, and config.json's own key where the file has it
# under that name
_CONFIG_KEYS = (
    "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_head",
    "d_ff", "max_seq_len", "rope_theta", "rope_theta_sliding", "rope_scaling",
    "sliding_window", "remat", "attention_impl", "norm_eps",
    "tied_embeddings", "n_experts", "experts_per_token", "norm_topk_prob",
    "router_score", "router_aux_loss_coef", "router_z_loss_coef",
    "layer_types",
)


def model_config(config: Dict[str, Any]) -> TransformerConfig:
    values = {k: config[k] for k in _CONFIG_KEYS if k in config}
    values["layer_types"] = tuple(values["layer_types"])
    values["rope_scaling"] = tuple(sorted(values["rope_scaling"].items()))
    return TransformerConfig(dtype=jnp.dtype(config["dtype"]), **values)


def build(config: Dict[str, Any], traffic: Dict[str, Any], devices) -> Any:
    cfg = model_config(config)
    mesh = make_mesh(config["mesh"], devices=devices)
    opt_cfg = config["optimizer"]
    # a warm-up: the window's steps are a run's first (`assumed.optimizer`)
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

    stream = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
        *shardings["tokens"].spec, None))

    def sequences_over_chips(x):
        """The reference's stream [b, t, d] laid as the system's: a chip
        its own sequences."""
        return jax.lax.with_sharding_constraint(x, stream)

    def reference_loss(params, batch, expert_index=None):
        return reference.loss(params, batch, config, expert_index,
                              sequences_over_chips)

    def system_window_attention(q, k, v):
        """The sliding layers' attention as the step runs it on a chip: the
        kernel the configuration's `attention_impl` resolves to, under its
        window. The probe is one sequence, so one device's."""
        return _attention(q, k, v, cfg, None, 1, None,
                          window=cfg.sliding_window)

    def window_edge_err(window_attention, tokens):
        """The distance of `window_attention` from the reference's band on
        `loops/laguna.py`'s `window_edge_probe`, over the reference's norm."""
        # the first id as a number of the host's: taken as an array it lies
        # on all four chips, the probe after it, and the kernel is then
        # asked to run over a mesh outside any `shard_map`
        q, k, v = window_edge_probe(
            cfg, check_len, jax.random.fold_in(jax.random.PRNGKey(0),
                                               int(tokens[0, 0])))
        ours = jax.jit(window_attention)(q, k, v).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            theirs = band_reference.band_attention(
                *(x.astype(jnp.float32) for x in (q, k, v)),
                config["sliding_window"])
        return jnp.linalg.norm(ours - theirs) / jnp.linalg.norm(theirs)

    def system_routed_layer(y, leaves):
        return _routed_ffn(y, leaves, cfg, mesh)[0]

    def probe_rows(key, tokens, rows):
        """Seeded normed rows [rows, check_len, d] in the compute dtype.
        The first id is taken as a number of the host's: as an array it
        lies on all four chips and so would all that is made from it."""
        return jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(key), int(tokens[0, 0])),
            (rows, check_len, cfg.d_model), cfg.dtype)

    def router_logits_rel_err(router_logits, params, tokens):
        """The router's precision alone: layer 0's logits [t, 64] as
        `router_logits` makes them from one sequence of seeded normed rows
        and the `router` leaf (the step's: `_router_logits`) against the
        same rows (bf16 values, so exact in any wider type) times the
        float32 leaf in float64 on the host; the distance over that
        product's norm. On one device: nothing of the router crosses
        chips."""
        y = probe_rows(2, tokens, 1)[0]
        router = params["blocks"][0][0]["router"][0]
        ours = jax.jit(router_logits)(
            *jax.device_put((y, router), mesh.devices.flat[0]))
        theirs = (np.asarray(y.astype(jnp.float32), np.float64)
                  @ np.asarray(router, np.float64))
        return (np.linalg.norm(np.asarray(ours, np.float64) - theirs)
                / np.linalg.norm(theirs))

    def exchange_rel_err(routed_layer, params, tokens):
        """The exchange alone: layer 0's routed feed-forward over the mesh
        (`routed_layer`: as the step runs it, each chip its own sequence and
        its 16 experts) against the same layer whole on one device, all 64
        experts there and no exchange, on the same seeded normed rows; the
        distance over the one device's norm."""
        blk = params["blocks"][0][0]
        leaves = {name: blk[name][0]
                  for name in ("router", "w_gate", "w_up", "w_down")}
        y = jax.device_put(probe_rows(1, tokens, tokens.shape[0]), stream)
        ours = jax.jit(routed_layer)(y, leaves)
        one = mesh.devices.flat[0]
        theirs = jax.jit(lambda y, leaves: _routed_ffn(y, leaves, cfg)[0])(
            *jax.device_put((y, leaves), one))
        ours = jax.device_put(ours, one).astype(jnp.float32)
        theirs = theirs.astype(jnp.float32)
        return jnp.linalg.norm(ours - theirs) / jnp.linalg.norm(theirs)

    # The gradients of both sides are laid out as the parameters are, a
    # quarter a chip: left to the partitioner the reference's came out whole
    # on every chip (12.7 GB of scratch where 8.3 were free: my chip run,
    # PR 50). The layout is the harness's; the reference names no device.
    grads_as_params = state_shard["params"]

    def system_side_of(loss_and_readings):
        def side(params, batch):
            (loss, readings), grads = jax.value_and_grad(
                loss_and_readings, has_aux=True)(params, batch)
            return loss, readings, grads

        return jax.jit(side, out_shardings=(None, None, grads_as_params))

    def reference_side(params, batch, index):
        # the choice is an argument: as a constant of the reference's
        # program it would make every seed a miss of the compile cache
        def loss_and_balance(p):
            loss, own_choice, balance = reference.forward(
                p, batch, config, index, sequences_over_chips)
            return loss, (balance, own_choice)

        (loss, (balance, own_choice)), grads = jax.value_and_grad(
            loss_and_balance, has_aux=True)(params)
        return loss, balance, own_choice, grads

    reference_side = jax.jit(
        reference_side, out_shardings=(None, None, None, grads_as_params))

    def errors_of(loss_and_readings, params, batch,
                  window_attention=system_window_attention,
                  routed_layer=system_routed_layer,
                  router_logits=_router_logits):
        """The comparison of a system `(params, batch) -> (loss, readings)`
        with the reference under the system's routing, of its sliding
        attention with the reference's on the window's edge, of its routed
        layer over the mesh with the whole layer on one device, and of its
        router's logits with their float64 product on the host.
        Two programs for the first: the system's loss, readings and
        gradients; and the reference's loss, balance loss and gradients
        under the system's choice of experts, with what its own scores would
        have chosen in each layer on the same input. The other routed
        families run the reference a second time under its own routing for
        that; here a program less is 16 s of a cold set-up that has 340."""
        system_side = system_side_of(loss_and_readings)

        @jax.jit
        def distances(ours, theirs, readings, index, balance, own_choice):
            num = sum(jnp.sum((x.astype(jnp.float32) - y) ** 2) for x, y in zip(
                jax.tree.leaves(ours), jax.tree.leaves(theirs)))
            den = sum(jnp.sum(y ** 2) for y in jax.tree.leaves(theirs))
            chose = jax.nn.one_hot(
                index, cfg.n_experts, dtype=jnp.int32).sum(-2) > 0
            flips = jnp.logical_and(chose, jnp.logical_not(own_choice)).sum()
            load = readings["expert_load"].astype(jnp.float32)  # [L, E]
            return {
                "grad_rel_err": jnp.sqrt(num) / jnp.sqrt(den),
                "router_flip_share": flips / index.size,
                "aux_loss_rel_err": jnp.abs(
                    readings["aux_loss"] - balance) / balance,
                "aux_loss_system": readings["aux_loss"],
                "expert_load_max_over_mean": jnp.max(
                    load.max(axis=-1) / load.mean(axis=-1)),
                "chip_load_max_over_mean": jnp.max(
                    readings["chip_load_max_over_mean"]),
                "unrouted_slots": index.size - load.sum(),
                "dropped_slots": readings["dropped_slots"].sum(),
            }

        batch = {"tokens": batch["tokens"], "targets": batch["targets"]}
        l_sys, readings, g_sys = system_side(params, batch)
        index = readings["expert_index"]  # [L, tokens, k]
        l_ref, balance, own_choice, g_ref = reference_side(
            params, batch, index)
        info = distances(g_sys, g_ref, readings, index, balance, own_choice)
        del g_sys, g_ref
        info["window_edge_err"] = window_edge_err(
            window_attention, batch["tokens"])
        info["exchange_rel_err"] = exchange_rel_err(
            routed_layer, params, batch["tokens"])
        info["router_logits_rel_err"] = router_logits_rel_err(
            router_logits, params, batch["tokens"])
        info = {k: float(v) for k, v in info.items()}
        l_sys, l_ref = float(l_sys), float(l_ref)
        return {"loss_system": l_sys, "loss_reference": l_ref,
                "loss_rel_err": abs(l_sys - l_ref) / abs(l_ref), **info}

    def check(params, batch):
        """Judged: `loss_rel_err` and `grad_rel_err`, the reference taking
        the system's choice of experts; `router_flip_share`, the share of
        the slots whose expert the reference's own scores did not choose for
        that token in that layer, on the layer's input under the system's
        routing;
        `aux_loss_rel_err`, the system's balance loss over the mesh's batch
        (the mean over the layers, before its coefficient) against the
        reference's under the same choice; `window_edge_err`, the sliding
        layers' attention as the step runs it against the reference's band
        on a probe that the window's edge decides; `exchange_rel_err`,
        the routed layer over the mesh against the whole layer on one
        device; and `router_logits_rel_err`, layer 0's router logits on
        seeded rows against the same product in float64 on the host, which
        holds the router to float32. Information: the largest expert's and
        the fullest chip's load over the mean, and the slots that were
        routed nowhere or held and not computed (both always 0)."""
        return errors_of(system_loss_and_readings, params, batch)

    return SimpleNamespace(
        mesh=mesh,
        batch_shapes=batch_shapes,
        state_shardings=state_shard,
        flops_per_unit=mellum_flops.mellum_flops_per_token(config, seq_len),
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
        system_side_of=system_side_of,
        reference_side=reference_side,
        window_edge_err=window_edge_err,
        exchange_rel_err=exchange_rel_err,
        router_logits_rel_err=router_logits_rel_err,
        model_config=cfg,
    )
