"""The names the program gives its device work: every `jax.named_scope` of
the two model families and the flash kernels' names stand in the lowered
step's name stacks, and every name a benchmark metric matches is one of
them, so that a rename in the program fails here and not in a metric. CPU
only: the steps are tiny and the kernels run in interpret mode."""

import functools
import glob
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as transformer_module
from ray_tpu.models.resnet import ResNetConfig, resnet_apply, resnet_init
from ray_tpu.ops import moe
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRANSFORMER_SCOPES = {"embed", "attn_qkv", "attention", "attn_out", "mlp",
                      "final_norm", "lm_head_ce", "optimizer"}
RESNET_SCOPES = {"stem", "stage1", "stage2", "stage3", "stage4", "head",
                 "conv", "bn"}
# the backward is `flash_bwd_dkv_dq` where a row's dq fits VMEM, and the two
# kernels it stands for where not (`ops/flash_attention.py`
# `flash_bwd_kernels`)
KERNELS = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd_dkv_dq"}
# the routed feed-forward's, inside `mlp`, and QK-norm's, inside `attn_qkv`
MOE_SCOPES = {"moe_router", "moe_dispatch", "moe_experts", "moe_combine",
              "qk_norm"}
MOE_KERNELS = {"moe_gmm", "moe_tgmm"}
# the gated short convolution's (`conv_in`, `conv_gate`, `conv_out` inside
# `short_conv`), and the step's update of the routers' selection bias
LFM2_SCOPES = {"short_conv", "conv_in", "conv_gate", "conv_out", "expert_bias"}
# latent attention's parts inside `latent_attention` (beside `attn_qkv`,
# `attention` and `attn_out`), and the shared experts inside `mlp`
DSV2_SCOPES = {"latent_attention", "kv_down", "kv_up", "moe_shared"}
# the Mamba-2 mixer's parts inside `mamba`, and the scan's inside `ssd`
NEMOTRON_SCOPES = {"mamba", "mamba_in", "mamba_conv", "ssd", "ssd_chunk",
                   "ssd_state", "ssd_out", "mamba_norm", "mamba_out"}
# sparse attention's parts inside `sparse_attention` (beside `attn_qkv`,
# `attention` and `attn_out`): the indexer's projections, scores and
# selection inside `indexer`, and the index loss. `index_select` and
# `index_loss` are also the names of the two kernels that take those parts
# on the chip (`index_scores` is the `jax.numpy` path's alone)
KEYE_SCOPES = {"sparse_attention", "indexer", "index_qk", "index_scores",
               "index_select", "index_loss"}
# KDA's parts inside `kda` (`kda_chunk`, `kda_state` and a second `kda_out`
# are `ops/kda.py`'s own), and the elementwise gate on attention's context
SOLAR_SCOPES = {"kda", "kda_in", "kda_conv", "kda_gates", "kda_chunk",
                "kda_state", "kda_out"}
# the Mamba-1 mixer's parts inside `mamba1`, differential attention's scope
# (round `attn_qkv`, `attention`, `attn_out`) with the pair's norm, a cross
# layer's scope round `attention`, and the gated memory unit's (PR 61;
# `shared_emit`, the carried values' cast for a scan of several readers, is
# in `tests/test_phi4flash.py`: one reader each here)
PHI4FLASH_SCOPES = {"mamba1", "mamba1_in", "mamba1_conv", "selective_scan",
                    "mamba1_out", "diff_attention", "diff_norm",
                    "cross_attention", "gmu"}
# EVA attention's scope (round `attn_qkv` and `attn_out`) and its four parts,
# `ops/eva.py`'s own, with the summaries' kernel pair (PR 64); the window's
# and the staircase's kernels carry the flash kernels' names, the second's
# with `_stair`
EVABYTE_SCOPES = {"eva", "eva_summaries", "eva_window", "eva_stair",
                  "eva_join"}
EVA_KERNELS = {"eva_summaries_fwd", "eva_summaries_bwd"}
# block diffusion's (PR 70): the noise and the loss round the stack, the
# layer's scope (round `attn_qkv` and `attn_out`) with `bd_attention` and its
# three parts, `ops/block_diffusion.py`'s own; the staircase's kernels carry
# the flash kernels' names with `_stair`, as EVA's do
SDAR_SCOPES = {"block_diffusion_attention", "bd_noise", "bd_attention",
               "bd_stair", "bd_own_block", "bd_join", "bd_loss"}
# `ops/kda.py`'s two kernels: on the CPU the recurrence is the `jax.numpy`
# form's, so no step lowered here names them (`tests/test_kernel_compile.py`
# compiles them, and finds them by these names)
KDA_KERNELS = {"kda_fwd", "kda_bwd"}
# the KDA mixer's short convolutions on the kernels' path (PR 67;
# `ops/mamba_passes.py` under the mixer's name): the `jax.numpy` lines'
# operations stand under `kda_conv` on the CPU
KDA_CONV_KERNELS = {"kda_conv_fwd", "kda_conv_bwd"}
# the routed layer's exchange over an `expert` mesh axis, inside
# `mlp/shard_map` beside `moe_router` (PR 50)
EXCHANGE_SCOPES = {"moe_gather", "moe_scatter"}
SPARSE_KERNELS = {"index_select", "index_loss", "flash_fwd_sparse",
                  "flash_bwd_dkv_dq_sparse"}
# the scan's kernels, where its shapes tile and the operators are Pallas's;
# `ssd_chunk`, `ssd_state`, `ssd_out` are the `jax.numpy` path's
SSD_KERNELS = {"ssd_fwd", "ssd_bwd"}
VOCAB = 96  # the tiny steps' one dimension of this size: it finds the head


def stacks_in(text):
    """Every name stack of a lowered module's locations. A location that is
    one bare name is a Python frame of a traceback, not a stack (a stack
    ends in its primitive, behind a `/`): a function of the program may be
    named as a scope is (`ops/sparse_attention.py` `index_select`), and a
    jaxpr that `jax.jit` cached under another test keeps that test's
    frames."""
    return {s for s in re.findall(r'loc\("([^"]+)"', text) if "/" in s}


def name_stacks(lowered):
    return stacks_in(lowered.as_text(debug_info=True))


def components(stacks):
    return {part for stack in stacks for part in re.split(r"[/()]", stack)}


def lowered_transformer_step(tokens=(2, 16), **routed):
    cfg = TransformerConfig(**{**dict(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=16, remat=True, attention_impl="xla",
        tied_embeddings=False), **routed})
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    init_state, step, _ = make_train_step(cfg, mesh)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct(tokens, jnp.int32)
    return step.lower(state, {"tokens": tokens, "targets": tokens})


def lowered_resnet_step():
    cfg = ResNetConfig(depth=18, num_classes=10, width=8)
    params = jax.eval_shape(lambda: resnet_init(jax.random.PRNGKey(0), cfg))

    def loss(params, images):
        logits, new = resnet_apply(params, images, cfg, train=True)
        return logits.sum(), new

    images = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    return jax.jit(jax.grad(loss, has_aux=True)).lower(params, images)


def lowered_moe_step():
    return lowered_transformer_step(n_experts=4, experts_per_token=2,
                                    qk_norm=True)


def lowered_lfm2_step():
    """A stack of unlike layers: a dense conv layer, then attention and conv
    with routed experts of which a share is held."""
    row_tile = moe._ROW_TILE
    moe._ROW_TILE = 8  # chunks of 8 rows of the tiny step's 64 slots
    try:
        return lowered_transformer_step(
            n_layers=3, layer_types=("conv", "full_attention", "conv"),
            n_dense_layers=1, d_ff_dense=48, n_experts=4, experts_per_token=2,
            experts_held=(1, 1), router_score="sigmoid", expert_bias=True,
            norm_topk_prob=True, qk_norm="head", router_aux_loss_coef=0.0,
            router_z_loss_coef=0.0)
    finally:
        moe._ROW_TILE = row_tile


def lowered_mellum_step():
    """Window and full attention, every layer routed, over a mesh whose
    `expert` axis has four devices: the routed layer's exchange."""
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=16, max_seq_len=16, remat=True, attention_impl="xla",
        tied_embeddings=False, n_experts=8, experts_per_token=2,
        norm_topk_prob=True, sliding_window=4, router_z_loss_coef=0.0,
        layer_types=("sliding_attention", "full_attention"))
    mesh = make_mesh({"expert": 4}, devices=jax.devices()[:4])
    init_state, step, shardings = make_train_step(cfg, mesh)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((4, 16), jnp.int32,
                                  sharding=shardings["tokens"])
    return step.lower(state, {"tokens": tokens, "targets": tokens})


def lowered_dsv2_step():
    """A dense latent-attention layer, then two with routed experts of which
    a share is held, shared experts beside them, the balance loss per
    sequence."""
    row_tile = moe._ROW_TILE
    moe._ROW_TILE = 8
    try:
        return lowered_transformer_step(
            n_layers=3, n_kv_heads=None, layer_types=("latent_attention",) * 3,
            n_dense_layers=1, d_ff_dense=48, n_experts=4, experts_per_token=2,
            experts_held=(1, 1), n_shared_experts=2, seq_aux=True,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, router_aux_loss_coef=0.001, router_z_loss_coef=0.0,
            rope_scaling=(("beta_fast", 32), ("beta_slow", 1), ("factor", 40),
                          ("mscale", 0.707), ("mscale_all_dim", 0.707),
                          ("original_max_position_embeddings", 8),
                          ("type", "yarn")))
    finally:
        moe._ROW_TILE = row_tile


def lowered_keye_step(**how):
    """Two layers of sparse attention over routed experts of which a share
    is held, the indexer keeping 8 of a query's keys."""
    row_tile = moe._ROW_TILE
    moe._ROW_TILE = 8
    try:
        return lowered_transformer_step(**{**dict(
            layer_types=("sparse_attention",) * 2, qk_norm="head",
            index_heads=2, index_head_dim=8, index_topk=8, n_experts=4,
            experts_per_token=2, experts_held=(1, 1), norm_topk_prob=True,
            router_aux_loss_coef=0.001, router_z_loss_coef=0.0), **how})
    finally:
        moe._ROW_TILE = row_tile


def lowered_keye_kernel_step():
    """The same layer at a sequence the kernels take, in interpret mode:
    steered here, since "pallas" does not lower for a CPU."""
    real = transformer_module.sparse_attention
    transformer_module.sparse_attention = lambda *a, **kw: real(
        *a, **{**kw, "interpret": True})
    try:
        return lowered_keye_step(n_layers=1,
                                 layer_types=("sparse_attention",),
                                 max_seq_len=128, tokens=(1, 128))
    finally:
        transformer_module.sparse_attention = real


def lowered_nemotron_step():
    """One sublayer a layer: mixers, attention without rotation and routed
    layers of ungated experts of which a share is held, one shared expert
    beside them."""
    row_tile = moe._ROW_TILE
    moe._ROW_TILE = 8
    try:
        return lowered_transformer_step(
            n_layers=5, d_head=16, rope=False, sublayer_types=(
                "mamba2", "routed_ff", "mamba2", "full_attention", "routed_ff"),
            ff_activation="relu2", n_experts=4, experts_per_token=2,
            experts_held=(1, 1), n_shared_experts=1, d_ff_shared=48,
            router_score="sigmoid", expert_bias=True, norm_topk_prob=True,
            routed_scaling_factor=2.5, router_aux_loss_coef=1e-4,
            router_z_loss_coef=0.0, mamba_heads=4, mamba_head_dim=8,
            ssm_state=16, ssm_groups=2, ssd_chunk=8)
    finally:
        moe._ROW_TILE = row_tile


def lowered_solar_step():
    """Attention without rotation under an elementwise gate and two KDA
    layers, a share of every mixer's heads and of the experts held, one
    shared expert beside them."""
    row_tile = moe._ROW_TILE
    moe._ROW_TILE = 8
    try:
        return lowered_transformer_step(
            n_layers=3, d_head=8, n_heads=8, rope=False,
            layer_types=("full_attention", "kda", "kda"), heads_held=(4, 4),
            attn_gate="elementwise", kda_heads=8, kda_head_dim=8,
            kda_gate_rank=4, kda_chunk=16, n_experts=4, experts_per_token=2,
            experts_held=(1, 1), n_shared_experts=1, d_ff_shared=48,
            norm_topk_prob=True, router_z_loss_coef=0.0)
    finally:
        moe._ROW_TILE = row_tile


def lowered_kimi_step():
    """A dense KDA layer with beta in (0, 1), then routed KDA, latent
    attention without positions and KDA: a share of the experts held under
    a sigmoid router with its selection bias, one shared expert beside
    them."""
    row_tile = moe._ROW_TILE
    moe._ROW_TILE = 8
    try:
        return lowered_transformer_step(
            n_layers=4, n_kv_heads=None, rope=False,
            layer_types=("kda", "kda", "latent_attention", "kda"),
            kda_heads=4, kda_head_dim=8, kda_gate_rank=4, kda_chunk=16,
            kda_allow_neg_eigval=False, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, n_dense_layers=1, d_ff_dense=48,
            n_experts=4, experts_per_token=2, experts_held=(1, 1),
            n_shared_experts=1, router_score="sigmoid", expert_bias=True,
            norm_topk_prob=True, routed_scaling_factor=2.446,
            router_aux_loss_coef=0.0, router_z_loss_coef=0.0)
    finally:
        moe._ROW_TILE = row_tile


def lowered_phi4flash_step():
    """One of each kind of a stack whose second half reads what its first
    half made: Mamba-1 and differential attention under a window, the two
    emitters, a gated memory unit and a cross layer."""
    return lowered_transformer_step(
        n_layers=6, d_head=8, n_heads=4, rope=False, tied_embeddings=True,
        layer_types=("mamba1", "sliding_diff_attention", "mamba1_emit",
                     "diff_attention_emit", "gmu", "cross_diff_attention"),
        layer_depths=(0, 1, 16, 17, 18, 19), sliding_window=4,
        mamba1_inner=64, mamba1_state=8, mamba1_dt_rank=2, scan_chunk=8,
        layer_norm=True, attn_bias=True)


def lowered_evabyte_step():
    """Two EVA attention layers over two windows of 16 (chunks of 8, heads
    of 8), the kernels in interpret mode: steered here, since "pallas" does
    not lower for a CPU."""
    from ray_tpu.ops import eva

    real = transformer_module.eva_attention
    transformer_module.eva_attention = lambda *a, **kw: eva.eva_attention(
        *a, **{**kw, "impl": "xla", "interpret": True})
    try:
        cfg = TransformerConfig(
            vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4, d_ff=64,
            max_seq_len=32, remat=True, attention_impl="xla",
            tied_embeddings=False, layer_types=("eva_attention",) * 2,
            eva_window=16, eva_chunk=8, n_pred_heads=2, norm_unit_offset=True)
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        init_state, step, _ = make_train_step(cfg, mesh)
        state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
        return step.lower(state, {
            "tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
            "targets": jax.ShapeDtypeStruct((2, 32, 2), jnp.int32)})
    finally:
        transformer_module.eva_attention = real


def lowered_sdar_step():
    """Two block-diffusion layers over a share of the experts on sequences
    of 32 in blocks of 4, the staircase's kernels in interpret mode: steered
    here, since "pallas" does not lower for a CPU."""
    from ray_tpu.ops import block_diffusion

    real = transformer_module.block_diffusion_attention
    transformer_module.block_diffusion_attention = (
        lambda *a, **kw: block_diffusion.block_diffusion_attention(
            *a, **{**kw, "impl": "xla", "interpret": True}))
    try:
        cfg = TransformerConfig(
            vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=16, max_seq_len=32, remat=True, attention_impl="xla",
            tied_embeddings=False, qk_norm="head", n_experts=8,
            experts_per_token=2, experts_held=(0, 4),
            layer_types=("block_diffusion_attention",) * 2,
            objective="block_diffusion", diffusion_block=4,
            mask_token_id=VOCAB - 1)
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        init_state, step, _ = make_train_step(cfg, mesh)
        state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
        ids = jax.ShapeDtypeStruct((2, 32), jnp.int32)
        return step.lower(state, {
            "tokens": ids, "noise": ids,
            "level": jax.ShapeDtypeStruct((2, 8), jnp.int32)})
    finally:
        transformer_module.block_diffusion_attention = real


def lowered_nemotron_kernel_step():
    """A mixer alone at sizes that tile (a chunk and a state of 128, a group
    of two heads of 64), the scan's kernels in interpret mode: steered
    here, since "pallas" does not lower for a CPU."""
    scan = transformer_module.ssd
    transformer_module.ssd = functools.partial(scan, interpret=True)
    try:
        return lowered_transformer_step(
            n_layers=1, sublayer_types=("mamba2",), mamba_heads=2,
            mamba_head_dim=64, ssm_state=128, ssm_groups=1, ssd_chunk=128,
            max_seq_len=128, tokens=(1, 128))
    finally:
        transformer_module.ssd = scan


# the two families with KDA layers, each with its other mixer, at heads of
# one lane tile
KDA_FAMILIES = {
    "solar_open2": dict(
        layer_types=("full_attention", "kda"), heads_held=(2, 2),
        attn_gate="elementwise", kda_heads=4),
    "kimi_linear": dict(
        layer_types=("kda", "latent_attention"), n_kv_heads=None,
        kda_heads=2, kda_allow_neg_eigval=False, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8),
}


def lowered_kda_conv_kernel_step(family):
    """A family's KDA layer at streams that tile, its short convolutions'
    kernels and its output norm's (PR 69) in interpret mode: steered here,
    as the scan's above."""
    steered = {"causal_conv_silu": functools.partial(
                   transformer_module.causal_conv_silu, interpret=True),
               "group_rmsnorm_gated": functools.partial(
                   transformer_module.group_rmsnorm_gated, interpret=True),
               "_kda_conv_kernels": lambda cfg, T=None: True,
               "_kda_out_norm_kernels": lambda cfg, T=None: True}
    with pytest.MonkeyPatch.context() as patch:
        for name, value in steered.items():
            patch.setattr(transformer_module, name, value)
        return lowered_transformer_step(
            n_layers=2, rope=False, kda_head_dim=128, kda_gate_rank=4,
            kda_chunk=16, **KDA_FAMILIES[family])


def lowered_moe_kernels():
    x = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 16, 8), jnp.float32)

    def loss(x, w):
        sizes = jnp.asarray([8, 0, 20, 4], jnp.int32)
        return moe.grouped_matmul(x, w, sizes, interpret=True).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w)


def lowered_project_and_combine():
    """The one operation alone, kernels in interpret mode, under a scope
    `step` so that what it names itself shows below it."""
    hidden = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    w_down = jax.ShapeDtypeStruct((4, 16, 8), jnp.float32)
    weights = jax.ShapeDtypeStruct((16, 2), jnp.float32)

    def loss(hidden, w_down, weights):
        slots = moe.sort_slots(jnp.arange(32).reshape(16, 2) % 4, 4)
        with jax.named_scope("step"):
            return moe.project_and_combine(
                hidden, w_down, weights, slots, interpret=True).sum()

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        hidden, w_down, weights)


def lowered_flash_kernels(v_dim=64):
    """The forward and the backward these shapes take: the one kernel."""
    q = jax.ShapeDtypeStruct((1, 128, 2, 64), jnp.float32)
    v = jax.ShapeDtypeStruct((1, 128, 2, v_dim), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, v)


def lowered_two_width_kernels():
    return lowered_flash_kernels(v_dim=32)


def lowered_two_backward_kernels():
    """The backward of a row whose dq does not fit VMEM: the plan's answer
    steered here, since no shape a CPU lowers in a test is that long."""
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    plan = fa.flash_bwd_kernels
    fa.flash_bwd_kernels = lambda *a, **kw: ("flash_bwd_dq", "flash_bwd_dkv")
    try:
        return lowered_flash_kernels()
    finally:
        fa.flash_bwd_kernels = plan


FAMILIES = {
    "transformer": (lowered_transformer_step, TRANSFORMER_SCOPES),
    "moe_transformer": (lowered_moe_step, TRANSFORMER_SCOPES | MOE_SCOPES),
    "lfm2_moe": (lowered_lfm2_step,
                 TRANSFORMER_SCOPES | MOE_SCOPES | LFM2_SCOPES),
    "deepseek_v2": (lowered_dsv2_step,
                    TRANSFORMER_SCOPES | (MOE_SCOPES - {"qk_norm"}) | DSV2_SCOPES),
    "nemotron_h": (lowered_nemotron_step,
                   TRANSFORMER_SCOPES | (MOE_SCOPES - {"qk_norm"})
                   | {"moe_shared", "expert_bias"} | NEMOTRON_SCOPES),
    "keye_vl2": (lowered_keye_step,
                 TRANSFORMER_SCOPES | MOE_SCOPES | KEYE_SCOPES),
    "mellum": (lowered_mellum_step,
               TRANSFORMER_SCOPES | (MOE_SCOPES - {"qk_norm"})
               | EXCHANGE_SCOPES | {"sliding_attention"}),
    "solar_open2": (lowered_solar_step,
                    TRANSFORMER_SCOPES | (MOE_SCOPES - {"qk_norm"})
                    | {"moe_shared", "attn_gate"} | SOLAR_SCOPES),
    "phi4flash": (lowered_phi4flash_step,
                  TRANSFORMER_SCOPES | {"sliding_attention"}
                  | PHI4FLASH_SCOPES),
    # nothing of its own: KDA's scopes beside latent attention's in one step
    "kimi_linear": (lowered_kimi_step,
                    TRANSFORMER_SCOPES | (MOE_SCOPES - {"qk_norm"})
                    | {"expert_bias"} | SOLAR_SCOPES | DSV2_SCOPES),
    "resnet": (lowered_resnet_step, RESNET_SCOPES),
}
# the scopes that one family alone has, but for those that another family
# has of them
OWN_SCOPES = {"lfm2_moe": LFM2_SCOPES, "deepseek_v2": DSV2_SCOPES,
              "nemotron_h": NEMOTRON_SCOPES, "keye_vl2": KEYE_SCOPES,
              "mellum": EXCHANGE_SCOPES, "solar_open2": SOLAR_SCOPES,
              "phi4flash": PHI4FLASH_SCOPES,
              # no family above: `test_eva_attention_names_its_parts` lowers it
              "evabyte": EVABYTE_SCOPES | EVA_KERNELS,
              # nor here: `test_block_diffusion_names_its_parts` lowers it
              "sdar": SDAR_SCOPES,
              "kda's kernels": KDA_KERNELS | KDA_CONV_KERNELS}
ALSO_HAS = {"nemotron_h": {"moe_shared", "expert_bias"},
            "solar_open2": {"moe_shared"},
            "kimi_linear": SOLAR_SCOPES | DSV2_SCOPES | {"expert_bias"}}


@pytest.fixture(scope="module")
def texts():
    return {name: lower().as_text(debug_info=True)
            for name, (lower, _) in FAMILIES.items()}


@pytest.fixture(scope="module")
def stacks(texts):
    return {name: stacks_in(text) for name, text in texts.items()}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_lowered_step_holds_every_scope(stacks, family):
    want = FAMILIES[family][1]
    assert want <= components(stacks[family])
    # forward, backward and recompute need no scope of the program: JAX's
    # own name stack wraps a scope's operations
    assert any("transpose(jvp(" in s for s in stacks[family])
    if family != "resnet":
        assert any("rematted_computation" in s and "mlp" in s
                   for s in stacks[family])
        # the whole of _attention lies under its scope, wrappers included
        assert any(re.search(r"attention/.*transpose", s) for s in stacks[family])
    for other, own in OWN_SCOPES.items():
        if family != other:
            assert not (own - ALSO_HAS.get(family, set())) & components(
                stacks[family])
    if family == "transformer":  # the dense step names nothing of the routed
        assert not (MOE_SCOPES | MOE_KERNELS) & components(stacks[family])
    elif family == "mellum":
        # the exchange beside the router inside the layer's `shard_map`
        # (whose body is lowered as a function of its own, so its stacks
        # start at the scope): each way in the forward, and in the backward
        # each as the other's transpose
        found = stacks[family]
        for stack in ("moe_gather/all_gather", "moe_scatter/reduce_scatter",
                      "moe_scatter/all_gather", "moe_gather/reduce_scatter"):
            assert stack in found, stack
        assert not any("moe_router/moe_gather" in s for s in found)
        assert any(s.startswith("while/body/moe_experts") for s in found)
        assert any(s.startswith("mlp/shard_map") for s in found)
        # the head takes its own chunks under its own `shard_map`
        assert any("lm_head_ce" in s and "shard_map" in s for s in found)
    elif family == "lfm2_moe":
        # the convolution's three parts inside `short_conv`, in the scan's
        # body forward, made again, and backward (under `checkpoint`)
        for inner in ("conv_in", "conv_gate", "conv_out"):
            for prefix in ("", "checkpoint/rematted_computation/", "checkpoint/"):
                assert any(s.startswith(f"{prefix}short_conv/{inner}/")
                           for s in stacks[family]), (inner, prefix)
        # the routed feed-forward of a share under `mlp`: its pieces inside
        # the loop over the chunks of held rows, forward and backward
        for inner in ("moe_dispatch", "moe_experts", "moe_combine"):
            assert any(s.startswith(f"mlp/while/body/{inner}")
                       for s in stacks[family]), inner
            assert any(re.match(rf"checkpoint/mlp/while/body/.*{inner}", s)
                       for s in stacks[family]), inner
        assert any(s.startswith("mlp/moe_router/") for s in stacks[family])
        assert any(re.search(r"attn_qkv\)*/qk_norm", s) for s in stacks[family])
        # the bias is moved outside the differentiated function
        assert any(s.endswith("expert_bias/sign") for s in stacks[family])
    elif family == "deepseek_v2":
        # the layer's five parts inside `latent_attention`, in the scan's
        # body forward, made again, and backward
        for inner in ("attn_qkv", "kv_down", "kv_up", "attention", "attn_out"):
            for prefix in ("", "checkpoint/rematted_computation/", "checkpoint/"):
                assert any(s.startswith(f"{prefix}latent_attention/{inner}/")
                           for s in stacks[family]), (inner, prefix)
        # the shared experts under `mlp`, beside the share's loop
        for prefix in ("", "checkpoint/rematted_computation/", "checkpoint/"):
            assert any(s.startswith(f"{prefix}mlp/moe_shared/dot_general")
                       for s in stacks[family]), prefix
        assert any(s.startswith("mlp/while/body/moe_experts")
                   for s in stacks[family])
        assert any(s.startswith("mlp/moe_router/") for s in stacks[family])
    elif family == "keye_vl2":
        # the layer's parts inside `sparse_attention`, in the scan's body
        # forward, made again, and backward; the `jax.numpy` path's blocks
        # are a loop inside it
        for inner in ("attn_qkv", "indexer/index_qk", "attn_out"):
            for prefix in ("", "checkpoint/rematted_computation/", "checkpoint/"):
                assert any(s.startswith(f"{prefix}sparse_attention/{inner}/")
                           for s in stacks[family]), (inner, prefix)
        for inner in ("indexer/index_scores", "indexer/index_select",
                      "attention", "index_loss"):
            # (a block of queries is a `jax.checkpoint` of its own, whose
            # operations carry the scopes from `checkpoint/` down)
            assert any(re.search(rf"(^|/){inner}/", s)
                       for s in stacks[family]), inner
        # the indexer's input is cut off: nothing of `index_qk` is under the
        # stream's backward but its own weights' gradients
        assert any(re.search(r"attn_qkv\)*/qk_norm", s) for s in stacks[family])
        assert any(s.startswith("mlp/while/body/moe_experts")
                   for s in stacks[family])
    elif family == "solar_open2":
        # the mixer's parts inside `kda`, forward, made again and backward
        # (the output projection's product is not made again: the stream
        # after the mixer is kept where there is room, and the backward
        # needs the operands)
        for inner in ("kda_in", "kda_conv", "kda_gates", "kda_chunk",
                      "kda_state", "kda_out"):
            for prefix in ("", "checkpoint/rematted_computation/", "checkpoint/"):
                assert any(s.startswith(f"{prefix}kda/{inner}/")
                           for s in stacks[family]), (inner, prefix)
        # the solve is `kda_chunk`'s (the tiny step's one chunk a sequence
        # leaves `kda_state`'s scan no loop to name)
        assert any(re.match(r"kda/kda_chunk/triangular_solve", s)
                   for s in stacks[family])
        assert any(s.startswith("attn_gate/") for s in stacks[family])
        assert any(s.startswith("mlp/moe_shared/") for s in stacks[family])
    elif family == "kimi_linear":
        found = stacks[family]
        # each mixer's parts under its own scope, forward, made again and
        # backward, in the dense layer's period and in the routed one's
        for outer, parts in (
                ("kda", ("kda_in", "kda_conv", "kda_gates", "kda_chunk",
                         "kda_state", "kda_out")),
                ("latent_attention", ("attn_qkv", "kv_down", "kv_up",
                                      "attention", "attn_out"))):
            for inner in parts:
                for prefix in ("", "checkpoint/rematted_computation/",
                               "checkpoint/"):
                    assert any(s.startswith(f"{prefix}{outer}/{inner}/")
                               for s in found), (outer, inner, prefix)
        # nothing is rotated: no angle is made anywhere in the step
        assert not any(re.search(r"/(cos|sin)$", s) for s in found)
        assert any(re.search(r"/(cos|sin)$", s)
                   for s in stacks["deepseek_v2"])
        # no plain attention layer: `attention` stands under
        # `latent_attention` alone
        assert not any(s.startswith("attention/") for s in found)
        # the leading dense feed-forward and the routed ones under `mlp`
        assert any(s.startswith("mlp/moe_router/") for s in found)
        assert any(s.startswith("mlp/while/body/moe_experts") for s in found)
        assert any(s.startswith("mlp/moe_shared/") for s in found)
        assert any(s.endswith("expert_bias/sign") for s in found)
    elif family == "nemotron_h":
        # the mixer's five parts inside `mamba`, forward, made again, and
        # backward; the scan's three inside `ssd`
        for inner in ("mamba_in", "mamba_conv", "ssd", "mamba_norm", "mamba_out"):
            for prefix in ("", "checkpoint/rematted_computation/", "checkpoint/"):
                if (inner, prefix[11:]) == ("mamba_out", "rematted_computation/"):
                    continue  # the backward needs its operands, not its product
                assert any(s.startswith(f"{prefix}mamba/{inner}/")
                           for s in stacks[family]), (inner, prefix)
        for inner in ("ssd_chunk", "ssd_state", "ssd_out"):
            assert any(s.startswith(f"mamba/ssd/{inner}/")
                       for s in stacks[family]), inner
            assert any(s.startswith(f"checkpoint/mamba/ssd/{inner}/")
                       for s in stacks[family]), inner
        # the recurrence over the chunks' states is a loop inside `ssd_state`
        assert any(re.match(r"checkpoint/(rematted_computation/)?mamba/ssd/"
                            r"ssd_state/while/body/", s) for s in stacks[family])
        # a routed layer alone keeps `mlp` and its pieces; the shared expert
        # beside the share's loop; attention keeps its three scopes
        assert any(s.startswith("mlp/moe_router/") for s in stacks[family])
        assert any(s.startswith("mlp/while/body/moe_experts")
                   for s in stacks[family])
        assert any(s.startswith("mlp/moe_shared/dot_general")
                   for s in stacks[family])
        assert any(s.endswith("expert_bias/sign") for s in stacks[family])
        assert any(s.startswith("attn_qkv/") for s in stacks[family])
        assert any(s.startswith("attn_out/") for s in stacks[family])
    elif family == "moe_transformer":
        # the routed feed-forward stays under `mlp`, QK-norm under `attn_qkv`
        for inner in sorted(MOE_SCOPES - {"qk_norm"}):
            assert any(re.search(rf"mlp\)*/{inner}", s) for s in stacks[family])
        assert any(re.search(r"attn_qkv\)*/qk_norm", s) for s in stacks[family])
        assert any("rematted_computation/mlp/moe_experts" in s
                   for s in stacks[family])
        # `project_and_combine` keeps no [T k, d] rows: nothing of the
        # combine is made again, and its backward (under `checkpoint`, not
        # under `rematted_computation`) names itself as its forward does
        assert not [s for s in stacks[family]
                    if "rematted_computation" in s and "moe_combine" in s]
        backward = {s.split("checkpoint/mlp/", 1)[1] for s in stacks[family]
                    if s.startswith("checkpoint/mlp/")}
        assert {"moe_combine/gather", "moe_combine/reduce_sum"} <= backward
        assert any(re.fullmatch(r"moe_experts/.*ragged_dot_general", s)
                   for s in backward)
    elif family == "phi4flash":
        found = stacks[family]
        # the scan is a loop, forward, made again and backward
        for phase in ("", "rematted_computation/", "checkpoint/"):
            assert any(s.startswith(phase + "mamba1/selective_scan/while")
                       or f"/{phase}mamba1/selective_scan/while" in s
                       for s in found), phase
        # the window's scope stands outside the differential layer's, a
        # cross layer's round its kernel call alone
        assert any("sliding_attention/diff_attention/attention/" in s
                   for s in found)
        assert any("diff_attention/cross_attention/attention/" in s
                   for s in found)
        assert not any("cross_attention/attn_qkv" in s for s in found)
        assert any(s.endswith("diff_attention/diff_norm/exp") for s in found)
        assert any(re.search(r"gmu\)*/dot_general", s) for s in found)
    else:
        assert any(re.search(r"stage2\)*/bn/", s) for s in stacks[family])
        assert any(re.search(r"stem\)*/conv/conv_general_dilated", s)
                   for s in stacks[family])


@pytest.mark.parametrize("family", ["transformer", "moe_transformer"])
def test_the_head_makes_its_logits_once(texts, stacks, family):
    """`lm_head_cross_entropy` forms its gradients in the chunk's forward:
    nothing of the head is rematerialised, a chunk (the tiny step has one)
    has three matmuls with the vocabulary dimension (logits, the gradient to
    the hidden rows, the gradient to the weight) and not a fourth, and they
    run under the scope `lm_head_ce`, which is where a trace's
    `lm_head_ce_time_share.tokens` looks for them."""
    assert not [s for s in stacks[family]
                if "rematted_computation" in s and "lm_head_ce" in s]
    text = texts[family]
    vocab_dot = rf"stablehlo\.dot_general[^\n]*[<x]{VOCAB}x"
    holders = [f for f in re.split(r"\n(?=\s*func\.func )", text)
               if re.search(vocab_dot, f)]
    assert len(holders) == 1  # the scan's body, a function of its own
    assert len(re.findall(vocab_dot, holders[0])) == 3
    name = re.match(r"\s*func\.func \w+ @(\w+)", holders[0]).group(1)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    calls = re.findall(rf"call @{name}\([^\n]*loc\((#loc\d+)\)", text)
    assert calls and all("lm_head_ce" in locs[ref] for ref in calls)


ONE_BACKWARD = {"flash_fwd", "flash_bwd_dkv_dq"}


@pytest.mark.parametrize("lower,names", [
    (lowered_flash_kernels, ONE_BACKWARD),
    (lowered_two_width_kernels, ONE_BACKWARD),
    (lowered_two_backward_kernels, KERNELS - {"flash_bwd_dkv_dq"}),
    (lowered_moe_kernels, MOE_KERNELS)],
    ids=["flash", "flash_two_widths", "flash_two_backward_kernels", "moe"])
def test_the_kernels_carry_their_names_in_interpret_mode(lower, names):
    found = components(name_stacks(lower()))
    assert names <= found
    assert not (KERNELS - names) & found


def test_the_scan_s_kernels_sit_under_the_scan_s_scope():
    """On the kernels' path `ssd_fwd` is under `mamba/ssd` in the forward
    and in the forward made again, `ssd_bwd` in the backward, so that
    `ssd_time_share.tokens` and `mamba_time_share.tokens` read them as they
    are; the `jax.numpy` scopes are that path's alone (the family's step
    above holds them)."""
    stacks = name_stacks(lowered_nemotron_kernel_step())
    for want in ("mamba/ssd/ssd_fwd/pallas_call",
                 "checkpoint/rematted_computation/mamba/ssd/ssd_fwd/pallas_call",
                 "checkpoint/mamba/ssd/ssd_bwd/pallas_call"):
        assert want in stacks, want
    found = components(stacks)
    assert SSD_KERNELS <= found
    assert not {"ssd_chunk", "ssd_state", "ssd_out"} & found
    # what is left beside the kernels under `ssd`: the softplus, `dt A` and
    # its running sum, the layouts, dD
    assert "mamba/ssd/jit(cumsum)" in stacks
    assert not [s for s in stacks if "ssd_bwd" in s and "rematted" in s]


@pytest.mark.parametrize("family", sorted(KDA_FAMILIES))
def test_kda_s_short_convolutions_sit_under_their_scope(family):
    """On the kernels' path `kda_conv_fwd` is under `kda/kda_conv` in the
    forward and in the forward made again, `kda_conv_bwd` in the backward,
    where a split by scope reads `kda_conv` and `pallas_time_share.tokens`
    the kernels; `kda_kernel_time_share.tokens` (`^kda_fwd`, `^kda_bwd`)
    and `mamba_pass_time_share.tokens` (`^mamba_conv_`, `^mamba_norm_`)
    match neither name. Nothing else is left under the scope but the taps'
    cast and slices (and their gradient's pad), the reshapes and the
    partial sums' sum."""
    stacks = name_stacks(lowered_kda_conv_kernel_step(family))
    # the calls, under the mixer's scope: `_conv_call` is a `jit` of its
    # own, lowered as a function whose stacks start at its own scope
    for prefix in ("", "checkpoint/rematted_computation/", "checkpoint/"):
        assert f"{prefix}kda/kda_conv/jit(_conv_call)" in stacks, prefix
    for kernel in KDA_CONV_KERNELS:
        assert any(s.startswith(f"kda_conv/{kernel}/") for s in stacks)
        assert not re.match(r"^kda_fwd|^kda_bwd|^mamba_conv_|^mamba_norm_",
                            kernel)
    assert KDA_CONV_KERNELS <= components(stacks)
    under = {s.rsplit("/", 1)[-1] for s in stacks
             if re.search(r"(^|/)kda/kda_conv/[^/]+$", s)}
    assert under and not under & {
        "logistic", "rsqrt", "dynamic_slice", "mul", "integer_pow"}, under


@pytest.mark.parametrize("family", sorted(KDA_FAMILIES))
def test_kda_s_output_norm_sits_under_kda_out(family):
    """On the kernels' path (PR 69) `kda_out_norm_fwd` is under
    `kda/kda_out` in the forward and in the forward made again,
    `kda_out_norm_bwd` in the backward, so a split by scope reads them as
    `kda_out` beside the `kda_o` product; neither name matches
    `kda_kernel_time_share.tokens` or `mamba_pass_time_share.tokens`.
    Nothing of the norm's or the gate's arithmetic is left beside them."""
    stacks = name_stacks(lowered_kda_conv_kernel_step(family))
    for prefix, call in (("", "_gated_fwd"), ("checkpoint/", "_gated_bwd"),
                         ("checkpoint/rematted_computation/", "_gated_fwd")):
        assert f"{prefix}kda/kda_out/jit({call})" in stacks, prefix
    for kernel in ("kda_out_norm_fwd", "kda_out_norm_bwd"):
        assert any(s.startswith(f"kda_out_norm/{kernel}/") for s in stacks)
        assert not re.match(r"^kda_fwd|^kda_bwd|^mamba_conv_|^mamba_norm_",
                            kernel)
    under = {s.rsplit("/", 1)[-1] for s in stacks
             if re.search(r"(^|/)kda/kda_out/[^/]+$", s)}
    assert "dot_general" in under and not under & {
        "logistic", "rsqrt", "mul", "integer_pow", "reduce_sum"}, under


def test_eva_attention_names_its_parts():
    """Under `eva`: the projections, the summaries' kernel, the window's
    causal flash kernel, the staircase's, the join and the output
    projection, in the forward, in the forward made again and, through the
    `custom_vjp`s, in the backward; the head of several positions under
    `lm_head_ce`."""
    stacks = name_stacks(lowered_evabyte_step())
    forward = ("eva/attn_qkv/dot_general",
               "eva/eva_summaries/eva_summaries_fwd/pallas_call",
               "eva/eva_window/flash_fwd/pallas_call",
               "eva/eva_stair/flash_fwd_stair/pallas_call",
               "eva/eva_join/exp", "eva/attn_out/dot_general")
    for want in forward:
        for phase in ("", "checkpoint/rematted_computation/"):
            assert any(s.endswith("/" + phase + want) or s == phase + want
                       for s in stacks), phase + want
    for want in ("eva/eva_summaries/eva_summaries_bwd/pallas_call",
                 "eva/eva_window/flash_bwd_dkv_dq/pallas_call",
                 "eva/eva_stair/flash_bwd_dkv_dq_stair/pallas_call"):
        assert any(s.endswith("checkpoint/" + want) for s in stacks), want
        assert not [s for s in stacks if want in s and "rematted" in s]
    assert any(re.search(r"transpose\(jvp\(.*eva_join", s) or (
        "checkpoint/eva/eva_join" in s) for s in stacks)
    found = components(stacks)
    assert EVABYTE_SCOPES | EVA_KERNELS <= found
    assert {"flash_fwd_stair", "flash_bwd_dkv_dq_stair"} <= found
    assert "attention" not in found  # plain attention's scope is not EVA's
    assert any("lm_head_ce" in s and "dot_general" in s for s in stacks)


def test_block_diffusion_names_its_parts():
    """`bd_noise` before the stack and `bd_loss` round the head; under
    `block_diffusion_attention` the projections, `bd_attention` with the
    staircase's flash kernel under `bd_stair`, the own block and the join
    (inside the loop over chunks of rows), and the output projection, in
    the forward, in the forward made again and in the backward."""
    stacks = name_stacks(lowered_sdar_step())
    layer = "block_diffusion_attention/"
    forward = (layer + "attn_qkv/dot_general",
               layer + "bd_attention/bd_stair/flash_fwd_stair/pallas_call",
               layer + "attn_out/dot_general")
    for want in forward:
        for phase in ("", "checkpoint/rematted_computation/"):
            assert any(s.endswith("/" + phase + want) or s == phase + want
                       for s in stacks), phase + want
    want = layer + "bd_attention/bd_stair/flash_bwd_dkv_dq_stair/pallas_call"
    assert any(s.endswith("checkpoint/" + want) for s in stacks)
    assert not [s for s in stacks if want in s and "rematted" in s]
    # the own block and the join run inside the loop over chunks of rows,
    # whose body is lowered as a function of its own: its stacks start at
    # the scope, forward, made again and backward
    for part, op in (("bd_own_block", "reduce_sum"), ("bd_join", "exp")):
        for phase in ("", "checkpoint/rematted_computation/", "checkpoint/"):
            assert phase + part + "/" + op in stacks, phase + part
    found = components(stacks)
    assert SDAR_SCOPES <= found
    assert {"flash_fwd_stair", "flash_bwd_dkv_dq_stair"} <= found
    assert "attention" not in found and "eva_join" not in found
    # the chunked head's loop under both scopes (a chunk's matmuls are a
    # function of their own, whose stacks start bare)
    assert any(re.search(r"bd_loss\)?/lm_head_ce/while/body/", s)
               for s in stacks)
    # the routed feed-forward of a share runs on the stream's 2 L rows
    assert any("mlp/moe_router/" in s for s in stacks)


def test_sparse_attention_s_kernels_sit_under_its_scopes():
    """On the kernels' path `index_select` is under `indexer/index_select`,
    `flash_fwd_sparse` under `attention` and `index_loss` under
    `index_loss`, forward and in the forward made again; the backward's
    flash kernel under the masked call's own `attention`. The live shares
    find them by these names."""
    stacks = name_stacks(lowered_keye_kernel_step())
    for want in (
            "sparse_attention/indexer/index_select/index_select/pallas_call",
            "sparse_attention/attention/flash_fwd_sparse/pallas_call",
            "sparse_attention/index_loss/index_loss/pallas_call",
            "checkpoint/rematted_computation/sparse_attention/indexer/"
            "index_select/index_select/pallas_call"):
        assert any(s.endswith(want) for s in stacks), want
    assert any(re.search(r"checkpoint/.*attention/flash_bwd_dkv_dq_sparse/"
                         r"pallas_call", s) for s in stacks)
    found = components(stacks)
    assert SPARSE_KERNELS <= found
    assert not KERNELS & found and "index_scores" not in found


def test_project_and_combine_names_its_backward():
    """Which scope each operation of the backward carries: both kernels
    under `moe_experts`; the gather of `dy`, the row sum that is the
    weights' gradient and the gather of its scalars under `moe_combine`."""
    stacks = name_stacks(lowered_project_and_combine())
    backward = {s.split("transpose(jvp(step))/", 1)[1] for s in stacks
                if "transpose(jvp(step))/" in s}
    for want in ("moe_experts/moe_gmm/pallas_call",
                 "moe_experts/moe_tgmm/pallas_call", "moe_experts/mul",
                 "moe_combine/gather", "moe_combine/reduce_sum"):
        assert want in backward, (want, sorted(backward))
    forward = {s.split("/jvp(step)/", 1)[1] for s in stacks
               if "/jvp(step)/" in s}
    assert "moe_experts/moe_gmm/pallas_call" in forward
    assert "moe_combine/gather" in forward and "moe_combine/mul" in forward
    assert not [s for s in forward if "moe_tgmm" in s]


def test_resnet_stem_has_its_conv_scope():
    cfg = ResNetConfig(depth=18, num_classes=10, width=8)
    params = jax.eval_shape(lambda: resnet_init(jax.random.PRNGKey(0), cfg))
    images = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    lowered = jax.jit(lambda p, x: resnet_apply(p, x, cfg)[0]).lower(params, images)
    assert any(s.endswith("stem/conv/conv_general_dilated")
               for s in name_stacks(lowered))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_name_a_metric_matches_is_a_name_of_the_program(stacks, family):
    from chipbench import scopes

    program = (TRANSFORMER_SCOPES | RESNET_SCOPES | KERNELS | LFM2_SCOPES
               | DSV2_SCOPES | NEMOTRON_SCOPES | KEYE_SCOPES | SOLAR_SCOPES
               | PHI4FLASH_SCOPES | EVABYTE_SCOPES | EVA_KERNELS
               | KDA_KERNELS | KDA_CONV_KERNELS)
    assert set(scopes.SCOPES) == TRANSFORMER_SCOPES | RESNET_SCOPES
    # the benchmark's list is PR 25's three until a `benchmark` issue adds
    # the fourth (PERF.md section 7); its time share matches by prefix
    assert set(scopes.KERNELS) == KERNELS - {"flash_bwd_dkv_dq"}
    suffix = ".images.json" if family == "resnet" else ".tokens.json"
    in_family = components(stacks[family]) | KERNELS
    named = 0
    for path in glob.glob(os.path.join(ROOT, "chipbench", "metrics", "*.json")):
        with open(path) as f:
            params = json.load(f).get("params", {})
        for key in ("scope", "kernel"):
            if key in params:
                assert params[key] in program, path
                elsewhere = any(
                    params[key] in own - ALSO_HAS.get(family, set())
                    and family != other for other, own in OWN_SCOPES.items())
                if path.endswith(suffix) and not elsewhere:
                    assert params[key] in in_family, path
                    named += 1
    assert named >= 1
