"""The `transformer` family: `ray_tpu.models.transformer` through
`make_train_step` on the configuration's mesh, as `chip_smoke.py` trains it.
bf16 compute over f32 master weights, AdamW, the flash kernel where
`attention_impl` resolves to it, the chunked LM-head cross-entropy."""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chipbench import compare, flops
from chipbench.reference import transformer as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models.transformer import transformer_init, transformer_loss
from ray_tpu.parallel import make_mesh

# System (bf16 matmuls and activations, the flash kernel, f32 loss) against
# the f32 reference on a few seeded 512-token sequences with random weights.
# The loss is a mean over thousands of tokens and averages the bf16 roundings
# out: measured 7e-6 to 5.5e-5 on the chip at Mistral widths (16 runs) and 1.1e-4 at
# the tests' tiny size (PERF.md section 6). A step whose weights, logits and
# loss were bf16 as well reads 1e-3 or more (the tests show it) and fails:
# the loss bound is the one that tells precisions apart.
# Every matmul rounds its inputs and output to bf16 (2^-9 relative), so the
# gradient's distance sits near one bf16 ulp and grows slowly with depth:
# measured on the chip 1.55e-2-1.58e-2 with 2 layers and 2.36e-2-2.39e-2
# with 8 layers sharded over four chips (my chip runs, PR 24), and 1.8e-2
# (2 layers) and 3.4e-2 (8 layers) at the tests' tiny size. Wrong mathematics (a missing mask, another
# rotation, a dropped layer) reads 0.3 and more, which the bound tells apart.
TOLERANCE = {"loss_rel_err": 5e-4, "grad_rel_err": 8e-2}

_CONFIG_KEYS = (
    "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff",
    "max_seq_len", "rope_theta", "remat", "attention_impl", "norm_eps",
    "tied_embeddings",
)


def model_config(config: Dict[str, Any]) -> TransformerConfig:
    return TransformerConfig(
        dtype=jnp.dtype(config["dtype"]),
        **{k: config[k] for k in _CONFIG_KEYS if k in config},
    )


def build(config: Dict[str, Any], traffic: Dict[str, Any], devices) -> Any:
    cfg = model_config(config)
    mesh = make_mesh(config["mesh"], devices=devices)
    opt_cfg = config["optimizer"]
    optimizer = optax.adamw(
        opt_cfg["learning_rate"], weight_decay=opt_cfg["weight_decay"])
    _, step, shardings = make_train_step(cfg, mesh, optimizer)
    state_shard = shardings["state"]

    # The state is made where it will live, in two jitted calls from the key:
    # `make_train_step`'s own init_state makes every weight whole on one
    # device first, which 8 layers do not survive.
    init_params = jax.jit(
        lambda key: transformer_init(key, cfg),
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
        ids = jax.ShapeDtypeStruct((n, int(traffic["units_per_row"])),
                                   jnp.int32, sharding=shardings["tokens"])
        return {"tokens": ids, "targets": ids}

    check_len = config["check"]["seq_len"]

    def system_loss(params, batch):
        return transformer_loss(params, batch, cfg, mesh=mesh)

    def reference_loss(params, batch):
        return reference.loss(params, batch, config)

    return SimpleNamespace(
        mesh=mesh,
        batch_shapes=batch_shapes,
        state_shardings=state_shard,
        flops_per_unit=flops.transformer_flops_per_token(
            config, int(traffic["units_per_row"])),
        tolerance=TOLERANCE,
        init_params=init_params,
        init_state=init_state,
        step=step,
        loss_of=lambda out: out["loss"],
        to_device=to_device,
        check_batch=lambda raw: to_device(raw, check_len),
        system_loss=system_loss,
        reference_loss=reference_loss,
        check=lambda params, batch: compare.loss_and_grad_errors(
            system_loss, reference_loss, params, batch),
    )
