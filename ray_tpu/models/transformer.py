"""Decoder-only transformer (GPT family), designed mesh-first.

The flagship model: pre-norm decoder blocks with RoPE, grouped-query
attention, SwiGLU MLP, bf16 compute / f32 master weights. Layers are stacked
into one pytree and iterated with `lax.scan`, so compile time is O(1) in
depth and XLA pipelines the weight prefetch.

Parallelism (ray_tpu.parallel.mesh axes):
  data/fsdp — batch split; fsdp additionally shards params (ZeRO-3 style)
  tensor    — heads + mlp hidden + vocab split (Megatron layout)
  sequence  — context parallelism; attention switches to ring_attention

Capability analog of what the reference reaches only through integrations
(SURVEY §5 long-context note: reference ships no native SP); here it is
native. Reference GPT-2 fine-tune workload: BASELINE.json config #5.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.ops.flash_attention import mha, resolve_impl
from ray_tpu.ops.fused import (
    fused_rmsnorm,
    lm_head_cross_entropy,
    softmax_cross_entropy,
)
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel.ring_attention import ring_attention


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 6
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # None => MHA
    d_ff: Optional[int] = None  # None => 4 * d_model (SwiGLU sized 2/3)
    max_seq_len: int = 2048
    rope_theta: float = 10_000.0
    dtype: Any = jnp.bfloat16  # compute/activation dtype
    remat: bool = False  # jax.checkpoint each block
    attention_impl: str = "auto"  # auto | pallas | xla | ring
    norm_eps: float = 1e-6
    tied_embeddings: bool = True

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        return int(8 * self.d_model / 3 + 127) // 128 * 128  # SwiGLU, 128-mult


# ------------------------------------------------------------------ params

def transformer_init(rng, cfg: TransformerConfig) -> Dict[str, Any]:
    """f32 master params. Block params are stacked on a leading layer axis."""
    k_emb, k_blk, k_out = jax.random.split(rng, 3)
    d, h, hk, dh, f = (
        cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.ff_dim,
    )

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

    L = cfg.n_layers
    ks = jax.random.split(k_blk, 7)
    blocks = {
        "attn_norm": jnp.ones((L, d), jnp.float32),
        "wq": dense(ks[0], (L, d, h * dh), d),
        "wk": dense(ks[1], (L, d, hk * dh), d),
        "wv": dense(ks[2], (L, d, hk * dh), d),
        "wo": dense(ks[3], (L, h * dh, d), h * dh),
        "mlp_norm": jnp.ones((L, d), jnp.float32),
        "w_gate": dense(ks[4], (L, d, f), d),
        "w_up": dense(ks[5], (L, d, f), d),
        "w_down": dense(ks[6], (L, f, d), f),
    }
    params = {
        "embed": jax.random.normal(
            k_emb, (cfg.vocab_size, d), jnp.float32
        ) * 0.02,
        "blocks": blocks,
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tied_embeddings:
        params["unembed"] = dense(k_out, (d, cfg.vocab_size), d)
    return params


_LOGICAL_AXES = {
    "embed": ("vocab", "embed"),
    "unembed": ("embed", "vocab"),
    "final_norm": (None,),
    "blocks": {
        "attn_norm": ("layers", None),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv"),
        "wv": ("layers", "embed", "kv"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", None),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    },
}


def param_shardings(mesh, cfg: TransformerConfig):
    """NamedSharding pytree matching transformer_init's structure, derived
    from the logical-axis table + default_transformer_rules."""
    rules = mesh_lib.default_transformer_rules(mesh)

    def build(node):
        if isinstance(node, dict):
            return {k: build(v) for k, v in node.items()}
        return NamedSharding(mesh, rules.spec(node))

    table = dict(_LOGICAL_AXES)
    if cfg.tied_embeddings:
        table.pop("unembed", None)
    return build(table)


# ----------------------------------------------------------------- forward

def _rope(x, positions, theta: float):
    """Rotary embedding on [B, T, H, Dh] with integer positions [B, T]."""
    B, T, H, Dh = x.shape
    half = Dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _attention(q, k, v, cfg: TransformerConfig, seq_axis: Optional[str],
               seq_size: int, mesh=None):
    if cfg.attention_impl == "ring" and seq_axis is not None:
        # Inside shard_map over the sequence axis: exact ring attention.
        rep = cfg.n_heads // k.shape[2]
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return ring_attention(
            q, k, v, axis_name=seq_axis, axis_size=seq_size, causal=True
        )
    impl = resolve_impl(
        cfg.attention_impl if cfg.attention_impl in ("pallas", "xla") else "auto"
    )
    attn = partial(mha, causal=True, impl=impl)
    if impl == "pallas" and mesh is not None and mesh.size > 1:
        # XLA cannot partition a Mosaic kernel ("wrap the call in a
        # shard_map"), so map it ourselves over the axes attention is
        # independent along: batch (data/fsdp) and heads (tensor).
        spec = mesh_lib.default_transformer_rules(mesh).spec(
            ("batch", None, "heads", None)
        )
        attn = jax.shard_map(
            attn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    return attn(q, k, v)


def _block(x, blk, positions, cfg: TransformerConfig,
           seq_axis: Optional[str], seq_size: int, mesh=None):
    B, T, d = x.shape
    h, hk, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    dt = cfg.dtype

    # The scopes name the step's device work in a profiler trace
    # (docs/observability.md, "Device scopes"); they are metadata only.
    with jax.named_scope("attn_qkv"):
        y = fused_rmsnorm(x, blk["attn_norm"], eps=cfg.norm_eps)
        q = (y @ blk["wq"].astype(dt)).reshape(B, T, h, dh)
        k = (y @ blk["wk"].astype(dt)).reshape(B, T, hk, dh)
        v = (y @ blk["wv"].astype(dt)).reshape(B, T, hk, dh)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    with jax.named_scope("attention"):
        o = _attention(q, k, v, cfg, seq_axis, seq_size, mesh)
    with jax.named_scope("attn_out"):
        x = x + o.reshape(B, T, h * dh) @ blk["wo"].astype(dt)

    with jax.named_scope("mlp"):
        y = fused_rmsnorm(x, blk["mlp_norm"], eps=cfg.norm_eps)
        gate = jax.nn.silu(y @ blk["w_gate"].astype(dt))
        up = y @ blk["w_up"].astype(dt)
        x = x + (gate * up) @ blk["w_down"].astype(dt)
    return x


def transformer_hidden(params, tokens, cfg: TransformerConfig,
                       positions=None, seq_axis: Optional[str] = None,
                       seq_size: int = 1, mesh=None):
    """Forward through the blocks: [B, T] tokens -> [B, T, d] normed hidden.

    When called under shard_map with the sequence sharded, pass seq_axis and
    positions holding GLOBAL positions so RoPE and causal masks are correct.
    When called under a jit that shards over `mesh`, pass the mesh: the
    Pallas attention kernel is mapped over its batch and head axes.
    """
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]

    blk_fn = partial(
        _block, cfg=cfg, seq_axis=seq_axis, seq_size=seq_size, mesh=mesh
    )
    if cfg.remat:
        blk_fn = jax.checkpoint(blk_fn, static_argnums=())

    def scan_body(x, blk):
        return blk_fn(x, blk, positions), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    with jax.named_scope("final_norm"):
        return fused_rmsnorm(x, params["final_norm"], eps=cfg.norm_eps)


def _unembed(params, cfg: TransformerConfig):
    return params["embed"].T if cfg.tied_embeddings else params["unembed"]


def transformer_apply(params, tokens, cfg: TransformerConfig,
                      positions=None, seq_axis: Optional[str] = None,
                      seq_size: int = 1, mesh=None):
    """Forward: [B, T] int32 tokens -> [B, T, vocab] logits (f32)."""
    x = transformer_hidden(
        params, tokens, cfg, positions=positions, seq_axis=seq_axis,
        seq_size=seq_size, mesh=mesh,
    )
    return (x @ _unembed(params, cfg).astype(cfg.dtype)).astype(jnp.float32)


def transformer_loss(params, batch, cfg: TransformerConfig, **kw):
    """Next-token CE. batch: {'tokens': [B, T+1] or ('tokens','targets')}.

    Uses the chunked LM-head CE (ops/fused.py lm_head_cross_entropy): the
    [B*T, V] f32 logits are never materialized, which at GPT-2 vocab sizes
    is the difference between HBM-bound and MXU-bound training steps."""
    if "targets" in batch:
        tokens, targets = batch["tokens"], batch["targets"]
    else:
        tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    hidden = transformer_hidden(params, tokens, cfg, **kw)
    with jax.named_scope("lm_head_ce"):
        loss, _ = lm_head_cross_entropy(
            hidden, _unembed(params, cfg), targets)
    return loss


# -------------------------------------------------------------- train step

def make_train_step(cfg: TransformerConfig, mesh, optimizer=None):
    """Build (init_state, step) jitted over the mesh.

    state = {'params': f32 sharded, 'opt': optax state, 'step': scalar}
    step(state, batch) -> (state, metrics); params/opt donated.

    DP/FSDP/TP come from the in/out shardings (XLA inserts psum /
    all-gather / reduce-scatter over ICI); if the mesh has a 'sequence'
    axis the batch spec additionally shards T.
    """
    import optax

    if optimizer is None:
        optimizer = optax.adamw(3e-4, weight_decay=0.01)

    p_shard = param_shardings(mesh, cfg)
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("data", "fsdp") if a in names) or None
    seq_ax = "sequence" if "sequence" in names else None
    tok_sharding = NamedSharding(mesh, P(batch_axes, seq_ax))
    repl = NamedSharding(mesh, P())

    # The state's layout is pinned on both sides of the step. Left to the
    # compiler, a step's output sharding can differ from its input's (it
    # spread the replicated norm moments over fsdp), and the next call then
    # compiles a second program unseen.
    opt_shard = optax.tree_map_params(
        optimizer,
        lambda _, sharding: sharding,
        jax.eval_shape(
            lambda: optimizer.init(
                transformer_init(jax.random.PRNGKey(0), cfg)
            )
        ),
        p_shard,
        transform_non_params=lambda _: repl,
    )
    state_shard = {"params": p_shard, "opt": opt_shard, "step": repl}

    def init_state(rng):
        params = transformer_init(rng, cfg)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s), params, p_shard
        )
        opt = jax.jit(optimizer.init, out_shardings=opt_shard)(params)
        return {"params": params, "opt": opt,
                "step": jax.device_put(jnp.zeros((), jnp.int32), repl)}

    def loss_fn(params, batch):
        return transformer_loss(params, batch, cfg, mesh=mesh)

    @partial(jax.jit, donate_argnums=(0,), out_shardings=(state_shard, repl))
    def step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        with jax.named_scope("optimizer"):
            updates, opt = optimizer.update(
                grads, state["opt"], state["params"]
            )
            params = optax.apply_updates(state["params"], updates)
            gnorm = optax.global_norm(grads)
        return (
            {"params": params, "opt": opt, "step": state["step"] + 1},
            {"loss": loss, "grad_norm": gnorm},
        )

    return init_state, step, {"tokens": tok_sharding, "replicated": repl,
                              "params": p_shard, "state": state_shard}


def _fwd_flops_per_token(cfg: TransformerConfig, seq_len: int):
    """(matmul fwd flops/token per layer, causal attn fwd flops/token per
    layer, lm-head fwd flops/token)."""
    d, f = cfg.d_model, cfg.ff_dim
    h, hk, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    per_layer = 2 * d * (h * dh + 2 * hk * dh) + 2 * h * dh * d + 2 * 3 * d * f
    # Causal attention: token t attends to t+1 keys, so the average query
    # sees (seq_len + 1) / 2 positions; qk^T and pv each cost 2*h*dh flops
    # per (query, key) pair. The flash kernel really skips the masked-out
    # tiles, so crediting full seq_len here would overcount ~2x.
    attn = 2 * 2 * h * dh * ((seq_len + 1) / 2)
    embed = 2 * d * cfg.vocab_size
    return per_layer, attn, embed


def flops_per_token(cfg: TransformerConfig, seq_len: int) -> float:
    """USEFUL train FLOPs/token: 6ND rule + CAUSAL attention quadratic term.

    1 forward + backward at 2x forward (the PaLM / scaling-book accounting).
    Recomputation (remat, flash-backward recompute) is deliberately
    excluded — this is the numerator for useful-MFU. Use
    hardware_flops_per_token for what the chip actually executes.
    """
    per_layer, attn, embed = _fwd_flops_per_token(cfg, seq_len)
    return 3 * (cfg.n_layers * (per_layer + attn) + embed)


def hardware_flops_per_token(
    cfg: TransformerConfig, seq_len: int, remat: Optional[bool] = None
) -> float:
    """Actually-executed train FLOPs/token, including recomputation:

    - the pallas flash-attention backward recomputes the attention forward
      (recompute custom_vjp in ops/flash_attention.py): +1 attention fwd
      per layer, always;
    - per-block remat (cfg.remat) recomputes the whole block forward during
      the backward: +1 block fwd per layer.

    hardware-MFU = hardware_flops_per_token * tokens/s / peak must come out
    below 1.0 — the sanity bound useful-MFU alone cannot provide. It is an
    analytic figure: it counts what the step asks for, not what the chip
    does, and a value near 1 says nothing about any kernel (the 0.97 once
    quoted for the GPT-2-width step stood beside a flash kernel far from its
    roofline). What is measured on the chip: `model_mfu.tokens`, and per
    kernel `flash_fwd_roofline.tokens`, `flash_bwd_dq_roofline.tokens`,
    `flash_bwd_dkv_roofline.tokens`, with `recompute_time_share.tokens` for
    what remat's second forward costs (BENCHMARK.json, PERF.md section 3).
    """
    if remat is None:
        remat = cfg.remat
    per_layer, attn, embed = _fwd_flops_per_token(cfg, seq_len)
    fwd_layer = per_layer + attn
    extra = cfg.n_layers * attn  # flash bwd recompute
    if remat:
        extra += cfg.n_layers * fwd_layer  # block fwd recompute
    return 3 * (cfg.n_layers * fwd_layer + embed) + extra
