"""Decoder-only transformer (GPT family), designed mesh-first.

The flagship model: pre-norm decoder blocks with RoPE, grouped-query
attention, SwiGLU MLP, bf16 compute / f32 master weights. Layers are stacked
into one pytree and iterated with `lax.scan`, so compile time is O(1) in
depth and XLA pipelines the weight prefetch.

The block's feed-forward is dense SwiGLU or, with `n_experts`, a dropless
routed one (`ops/moe.py`): a float32 softmax router, the `experts_per_token`
largest probabilities as weights, every slot computed by a grouped matmul,
and the router's load-balancing and z losses added to the loss. `qk_norm`
puts an RMSNorm with a learned scale on the whole q and k projections before
the heads are split and rotated. Both are OLMoE's (arXiv:2409.02060).

Parallelism (ray_tpu.parallel.mesh axes):
  data/fsdp — batch split; fsdp additionally shards params (ZeRO-3 style)
  tensor    — heads + mlp hidden + vocab split (Megatron layout)
  sequence  — context parallelism; attention switches to ring_attention

Capability analog of what the reference reaches only through integrations
(SURVEY §5: it ships no native SP); here it is native. Cells that train it:
`mistral7b.tokens4k`, `mistral7b.fsdp4`, `olmoe.tokens4k` (BENCHMARK.json).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.ops import moe
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
    # auto | pallas | xla | ring; a routed feed-forward's grouped matmul
    # takes its kernels where attention does
    attention_impl: str = "auto"
    norm_eps: float = 1e-6
    tied_embeddings: bool = True
    n_experts: int = 0  # 0 => dense SwiGLU; else d_ff is one expert's width
    experts_per_token: int = 1
    norm_topk_prob: bool = False  # divide the chosen weights by their sum
    qk_norm: bool = False  # RMSNorm on the q and k projections
    router_aux_loss_coef: float = 0.01  # load balancing, mean over layers
    router_z_loss_coef: float = 0.001  # logsumexp(router logits)^2

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
    # a routed feed-forward stacks its experts behind the layer axis
    ff = (L, cfg.n_experts) if cfg.n_experts else (L,)
    blocks = {
        "attn_norm": jnp.ones((L, d), jnp.float32),
        "wq": dense(ks[0], (L, d, h * dh), d),
        "wk": dense(ks[1], (L, d, hk * dh), d),
        "wv": dense(ks[2], (L, d, hk * dh), d),
        "wo": dense(ks[3], (L, h * dh, d), h * dh),
        "mlp_norm": jnp.ones((L, d), jnp.float32),
        "w_gate": dense(ks[4], (*ff, d, f), d),
        "w_up": dense(ks[5], (*ff, d, f), d),
        "w_down": dense(ks[6], (*ff, f, d), f),
    }
    if cfg.n_experts:
        blocks["router"] = dense(
            jax.random.fold_in(k_blk, 7), (L, d, cfg.n_experts), d)
    if cfg.qk_norm:
        blocks["q_norm"] = jnp.ones((L, h * dh), jnp.float32)
        blocks["k_norm"] = jnp.ones((L, hk * dh), jnp.float32)
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
_ROUTED_AXES = {
    "w_gate": ("layers", "experts", "embed", "mlp"),
    "w_up": ("layers", "experts", "embed", "mlp"),
    "w_down": ("layers", "experts", "mlp", "embed"),
    "router": ("layers", "embed", None),
}
_QK_NORM_AXES = {"q_norm": ("layers", "heads"), "k_norm": ("layers", "kv")}


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
    table["blocks"] = {
        **table["blocks"],
        **(_ROUTED_AXES if cfg.n_experts else {}),
        **(_QK_NORM_AXES if cfg.qk_norm else {}),
    }
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
    impl = _kernel_impl(cfg)
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


def _kernel_impl(cfg: TransformerConfig) -> str:
    """'pallas' or 'xla', for attention and the grouped matmul alike."""
    return resolve_impl(
        cfg.attention_impl if cfg.attention_impl in ("pallas", "xla") else "auto"
    )


def _routed_ffn(y, blk, cfg: TransformerConfig, mesh=None):
    """The routed feed-forward on normed activations `y` [B, T, d]: the sum
    over a token's `experts_per_token` experts of p_e * SwiGLU_e(y), and the
    layer's router readings {aux_loss, z_loss, expert_load [E],
    expert_index [B T, k]}. Dropless: `expert_load` sums to B T k."""
    B, T, d = y.shape
    impl = _kernel_impl(cfg)
    if impl == "pallas" and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "the grouped-matmul kernels are not mapped over a mesh of "
            f"{mesh.size} devices yet (ROADMAP R1: expert parallelism)"
        )
    tokens = y.reshape(B * T, d)
    with jax.named_scope("moe_router"):
        # float32 at full precision: with bf16 logits the k-th and the next
        # expert swap on rounding
        logits = jnp.dot(
            tokens.astype(jnp.float32), blk["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        probs, weights, index = moe.route(
            logits, cfg.experts_per_token, cfg.norm_topk_prob)
        slots = moe.sort_slots(index, cfg.n_experts)
        readings = {
            "aux_loss": moe.load_balancing_loss(probs, slots.group_sizes),
            "z_loss": moe.router_z_loss(logits),
            "expert_load": slots.group_sizes,
            "expert_index": index,
        }
    with jax.named_scope("moe_dispatch"):
        xs = moe.dispatch(tokens, slots.order, slots.inverse)
    with jax.named_scope("moe_experts"):
        gmm = partial(moe.grouped_matmul, group_sizes=slots.group_sizes,
                      impl=impl)
        hidden = jax.nn.silu(gmm(xs, blk["w_gate"])) * gmm(xs, blk["w_up"])
    # names its own operations `moe_experts` and `moe_combine`, backward too
    out = moe.project_and_combine(hidden, blk["w_down"], weights, slots,
                                  impl=impl)
    return out.reshape(B, T, d), readings


def _block(x, blk, positions, cfg: TransformerConfig,
           seq_axis: Optional[str], seq_size: int, mesh=None):
    """One block: (x, the routed feed-forward's readings or None)."""
    B, T, d = x.shape
    h, hk, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    dt = cfg.dtype

    # The scopes name the step's device work in a profiler trace
    # (docs/observability.md, "Device scopes"); they are metadata only.
    with jax.named_scope("attn_qkv"):
        y = fused_rmsnorm(x, blk["attn_norm"], eps=cfg.norm_eps)
        q, k = y @ blk["wq"].astype(dt), y @ blk["wk"].astype(dt)
        if cfg.qk_norm:
            with jax.named_scope("qk_norm"):
                q = fused_rmsnorm(q, blk["q_norm"], eps=cfg.norm_eps)
                k = fused_rmsnorm(k, blk["k_norm"], eps=cfg.norm_eps)
        q = q.reshape(B, T, h, dh)
        k = k.reshape(B, T, hk, dh)
        v = (y @ blk["wv"].astype(dt)).reshape(B, T, hk, dh)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    with jax.named_scope("attention"):
        o = _attention(q, k, v, cfg, seq_axis, seq_size, mesh)
    with jax.named_scope("attn_out"):
        x = x + o.reshape(B, T, h * dh) @ blk["wo"].astype(dt)

    readings = None
    with jax.named_scope("mlp"):
        y = fused_rmsnorm(x, blk["mlp_norm"], eps=cfg.norm_eps)
        if cfg.n_experts:
            routed, readings = _routed_ffn(y, blk, cfg, mesh)
            x = x + routed
        else:
            gate = jax.nn.silu(y @ blk["w_gate"].astype(dt))
            up = y @ blk["w_up"].astype(dt)
            x = x + (gate * up) @ blk["w_down"].astype(dt)
    return x, readings


def _hidden_and_readings(params, tokens, cfg: TransformerConfig,
                         positions=None, seq_axis: Optional[str] = None,
                         seq_size: int = 1, mesh=None):
    """`transformer_hidden`, and the routed feed-forwards' readings stacked
    on a leading layer axis (None for a dense model)."""
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
        return blk_fn(x, blk, positions)

    x, readings = jax.lax.scan(scan_body, x, params["blocks"])
    with jax.named_scope("final_norm"):
        x = fused_rmsnorm(x, params["final_norm"], eps=cfg.norm_eps)
    return x, readings


def transformer_hidden(params, tokens, cfg: TransformerConfig, **kw):
    """Forward through the blocks: [B, T] tokens -> [B, T, d] normed hidden.

    Keywords: `positions`, `seq_axis`, `seq_size`, `mesh`. When called under
    shard_map with the sequence sharded, pass seq_axis and positions holding
    GLOBAL positions so RoPE and causal masks are correct. When called under
    a jit that shards over `mesh`, pass the mesh: the Pallas attention
    kernel is mapped over its batch and head axes.
    """
    return _hidden_and_readings(params, tokens, cfg, **kw)[0]


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


def transformer_loss_and_readings(params, batch, cfg: TransformerConfig, **kw):
    """(loss, readings). Next-token CE; batch: {'tokens': [B, T+1] or
    ('tokens','targets')}.

    Uses the chunked LM-head CE (ops/fused.py lm_head_cross_entropy): the
    [B*T, V] f32 logits are never materialized, which at GPT-2 vocab sizes
    is the difference between HBM-bound and MXU-bound training steps.

    A model with routed experts adds `router_aux_loss_coef` times the
    load-balancing loss and `router_z_loss_coef` times the router z-loss,
    each a mean over the layers, and its readings are `aux_loss` and
    `z_loss` (those means), `expert_load` [L, E] (slots per expert; every
    row sums to B T k) and `expert_index` [L, B T, k]. A dense model's
    readings are empty."""
    if "targets" in batch:
        tokens, targets = batch["tokens"], batch["targets"]
    else:
        tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    hidden, readings = _hidden_and_readings(params, tokens, cfg, **kw)
    with jax.named_scope("lm_head_ce"):
        loss, _ = lm_head_cross_entropy(
            hidden, _unembed(params, cfg), targets)
    if readings is None:
        return loss, {}
    readings = dict(readings, aux_loss=readings["aux_loss"].mean(),
                    z_loss=readings["z_loss"].mean())
    loss = (loss + cfg.router_aux_loss_coef * readings["aux_loss"]
            + cfg.router_z_loss_coef * readings["z_loss"])
    return loss, readings


def transformer_loss(params, batch, cfg: TransformerConfig, **kw):
    """The loss of `transformer_loss_and_readings` alone."""
    return transformer_loss_and_readings(params, batch, cfg, **kw)[0]


# -------------------------------------------------------------- train step

# what a routed model's step reports beside loss and grad_norm
_STEP_READINGS = ("aux_loss", "z_loss", "expert_load")


def make_train_step(cfg: TransformerConfig, mesh, optimizer=None):
    """Build (init_state, step) jitted over the mesh.

    state = {'params': f32 sharded, 'opt': optax state, 'step': scalar}
    step(state, batch) -> (state, metrics); params/opt donated. metrics are
    loss and grad_norm, and with routed experts aux_loss, z_loss and
    expert_load [L, E].

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
        loss, readings = transformer_loss_and_readings(
            params, batch, cfg, mesh=mesh)
        return loss, {k: readings[k] for k in _STEP_READINGS if k in readings}

    @partial(jax.jit, donate_argnums=(0,), out_shardings=(state_shard, repl))
    def step(state, batch):
        (loss, readings), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], batch)
        with jax.named_scope("optimizer"):
            updates, opt = optimizer.update(
                grads, state["opt"], state["params"]
            )
            params = optax.apply_updates(state["params"], updates)
            gnorm = optax.global_norm(grads)
        return (
            {"params": params, "opt": opt, "step": state["step"] + 1},
            {"loss": loss, "grad_norm": gnorm, **readings},
        )

    return init_state, step, {"tokens": tok_sharding, "replicated": repl,
                              "params": p_shard, "state": state_shard}


def _fwd_flops_per_token(cfg: TransformerConfig, seq_len: int):
    """(matmul fwd flops/token per layer, causal attn fwd flops/token per
    layer, lm-head fwd flops/token)."""
    d, f = cfg.d_model, cfg.ff_dim
    h, hk, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    ff = 2 * 3 * d * f
    if cfg.n_experts:  # active parameters: the router and a token's experts
        ff = 2 * d * cfg.n_experts + cfg.experts_per_token * ff
    per_layer = 2 * d * (h * dh + 2 * hk * dh) + 2 * h * dh * d + ff
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
    excluded — this is the numerator for useful-MFU.
    """
    per_layer, attn, embed = _fwd_flops_per_token(cfg, seq_len)
    return 3 * (cfg.n_layers * (per_layer + attn) + embed)
