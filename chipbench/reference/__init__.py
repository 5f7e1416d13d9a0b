"""Plain references: a family's loss in straightforward float32 `jax.numpy`,
with no kernels, remat, cache or sharding rules, written from the published
description and not from the program's code."""
