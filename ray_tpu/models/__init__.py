"""ray_tpu.models: flagship model families, built mesh-first.

Each model is a pure-functional JAX module: `init(rng, cfg)` returns a param
pytree, `apply(params, batch, cfg)` the forward, and `make_train_step` a
jittable (donated, sharded) update. Parallelism is expressed as PartitionSpec
annotations against the canonical mesh axes (ray_tpu.parallel.mesh), so the
same model runs single-chip through multi-pod.
"""

from ray_tpu.models.transformer import (
    TransformerConfig,
    transformer_apply,
    transformer_init,
    transformer_loss,
    transformer_loss_and_readings,
    make_train_step,
    param_shardings,
)
from ray_tpu.models.resnet import ResNetConfig, resnet_apply, resnet_init

__all__ = [
    "TransformerConfig",
    "transformer_init",
    "transformer_apply",
    "transformer_loss",
    "transformer_loss_and_readings",
    "make_train_step",
    "param_shardings",
    "ResNetConfig",
    "resnet_init",
    "resnet_apply",
]
