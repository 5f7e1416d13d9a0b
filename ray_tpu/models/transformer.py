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

A stack of unlike layers (`layer_types`, `n_dense_layers`) is a sequence of
segments: runs of whole periods of one layout, each scanned, so compile time
is O(periods' lengths). A layer's operator is attention or a gated short
convolution (`_short_conv`), its feed-forward dense (the leading
`n_dense_layers`) or routed. The router may score by sigmoid and choose by
score plus a bias that the step moves toward balance and no gradient
reaches (`expert_bias`), `qk_norm="head"` norms every head over its own
width, and `experts_held = (first, n)` makes every routed layer compute `n`
of the `n_experts` it routes over, as one chip of an expert-parallel
deployment does. Those are LFM2's (`lfm2_moe`; the bias is
arXiv:2408.15664's). A model of one kind of layer is one segment whose
period is one layer, and its parameters are the one stacked tree
`params["blocks"]`; otherwise `params["blocks"]` is a list of segments, each
a list with one such tree per layer of its period.

A third operator is multi-head latent attention (`"latent_attention"` in
`layer_types`; DeepSeek-V2's, arXiv:2405.04434): keys and values come from
one low-rank projection of the token (`wkv_a` to a latent of `kv_lora_rank`
and one rotary key of `qk_rope_head_dim` that every head shares; a norm;
`wkv_b` up to every head's `qk_nope_head_dim` key columns and `v_head_dim`
value columns), a head of q and k is its unrotated columns and then the
rotary ones, and `rope_scaling` blends YaRN's frequencies and enters the
softmax's scale. This is the decompressed form, which training and prefill
use; the flash kernels take the two widths as they are. A routed layer may
have shared experts beside the routed ones (`n_shared_experts`: one dense
SwiGLU every token goes through, unweighted), and `seq_aux` takes the
router's balance loss per sequence and sums it over the layers.

A layer may also be ONE sublayer, `x + f(norm(x))` with `f` an operator or
a feed-forward alone (`sublayer_types`, one name a layer: `nemotron_h`'s
`hybrid_override_pattern`); a layer of `layer_types` is two of them, and
periods are found over whichever the stack is made of. The fourth operator
is the Mamba-2 mixer (`"mamba2"`; arXiv:2405.21060): one projection to a
gate, to x, B and C and to a step size a head, a short causal convolution
over x, B and C, the selective state-space scan in its chunked form
(`ops/ssd.py`), the gate before a grouped RMSNorm, and the output
projection. Beside it: plain attention that does not rotate (`rope=False`;
the mixers carry position) at heads of their own width (`d_head`), the
ungated feed-forward `W_down relu(W_up x)^2` (`ff_activation="relu2"`) for
dense, shared and routed experts alike, a shared expert of its own width
(`d_ff_shared`), and `routed_scaling_factor` on the chosen experts'
weights. Those are Nemotron-3-Nano's (`nemotron_h`).

A fifth operator is attention under a window (`"sliding_attention"` in
`layer_types`, `sliding_window`): plain attention in which a token sees
itself and the `sliding_window - 1` before it, through the flash kernels'
band (`ops/flash_attention.py` `window=`), in one stack with full attention.
The two differ by operator in more than the mask: the query heads' number
(`n_heads` for `full_attention`, `n_heads_sliding` under the window, over
the same `n_kv_heads` of the same width, so `wq`, `wo` and the gate differ
in shape by layer type and `segments` scans the alike ones together) and
the rotary recipe (`_PlainAttention.rotary`: theta, the share of a head's
columns that turn, `partial_rotary_factor`, and for full attention
`rope_scaling`, YaRN's frequencies with an explicit `attention_factor`,
through the one `_rope` latent attention uses). `attn_gate` multiplies every
head's context by one sigmoid gate a head and token, from the normed input
(`w_gate_attn` `[d, heads]`). Those are Laguna-XS.2's (`laguna`).

A sixth operator is attention over the keys a learned indexer selects
(`"sparse_attention"` in `layer_types`; DeepSeek sparse attention, as
DeepSeek-V3.2-Exp describes it): plain attention's q, k and v, and beside
them from the *detached* normed input `index_heads` index queries of
`index_head_dim` (`wq_idx`), one index key for all of them under a
LayerNorm (`wk_idx`, `k_idx_norm`, `k_idx_bias`) and a weight a head
(`w_idx`, over the root of heads times width), the queries and the key
rotated as q and k are. A query keeps the `index_topk` earlier keys of
largest `sum_j w_j relu(q_idx_j . k_idx)` and attends to those alone
(`ops/sparse_attention.py`). The selection passes no gradient: the indexer
learns from a loss of its own, the KL divergence from the attention's
head-averaged probabilities on the kept keys (detached) to the softmax of
the index scores there, a mean over layers and tokens that is added to the
step's loss (`index_loss` among the readings, with `index_keys_min_gap` and
`index_keys_max_gap`: the least and the most a row kept beyond
`min(t + 1, index_topk)`, both 0). Cross-entropy moves no indexer leaf and
the index loss moves nothing else. Those are Keye-VL-2.0's (`keye_vl2`).

A seventh operator is Kimi Delta Attention (`"kda"` in `layer_types`;
arXiv:2510.26692): from the normed input `kda_heads` heads of queries, keys
and values `kda_head_dim` wide, each through a causal convolution of
`kda_conv_taps` taps a channel (`_causal_taps`) and a silu, q and k then
scaled to unit length a head; a log decay a *channel* of the key,
`-exp(A_log) softplus(W_f2 W_f1 x + dt_bias)`, and `beta = 2 sigmoid(W_b
x)` a head, in float32; the delta-rule recurrence over a matrix state in its
chunked form (`ops/kda.py`); an RMS norm over each head's output under a
low-rank sigmoid gate, and the output projection. `heads_held = (first, n)`
makes attention and this operator hold `n` of their heads, `first` onward,
as one chip of a tensor-parallel deployment does: the heads' columns of the
input projections (for attention the key-value heads those query heads
read), their rows of the output projection, and the mixer's output is the
partial sum over them; nothing stands in for the other chips.
`attn_gate="elementwise"` gates plain attention's context by a sigmoid as
wide as the context. Those are Solar-Open2-250B's (`solar_open2`).

A stack may be run several times over the same weights (`loop_steps`;
arXiv:2510.25741's looped language model): the walk over the segments is
one `lax.scan` over the passes, the final norm after every pass and its
output the next pass's input, and every pass's normed stream comes back. A
weight's gradient is the sum over its uses, made under `remat` by a
backward of the stack's own that adds each use's into the one sum
(`_looped_under_remat`); a kept name is held once a layer for each of the
last passes that keep it. `post_norm` norms plain attention's and the dense
feed-forward's output before it joins the stream (four norms a layer).
With `exit_gate` one `Linear(d_model, 1)` reads every pass's stream, its
sigmoids make a distribution over the pass a token leaves after
(`exit_distribution`), and the loss is the passes' cross-entropies under it
less `exit_entropy_coef` times its entropy (`_exit_loss`), the passes'
heads as one call of the weighted chunked cross-entropy (`ops/fused.py`);
the step reads `ut_pass_loss`, `exit_p_mean` and `exit_entropy`. Those are
Ouro-2.6B's (`ouro`). Not under a `sequence` or an `expert` axis, with
`heads_held` or over layers that make readings yet (`_refuse_unmapped_loop`).

A layer may read what an earlier layer made. A record states the named
values its forward emits and reads (`Sublayer.emits`, `reads`); the walk
carries them beside the stream, from layer to layer inside a period and as
constants of the later segments' scans (a reader's cotangents are summed
by the scan, in float32: `_for_readers`), and under `remat` an emitted
value is held once, an argument of every reader's checkpoint. A reader
before its emitter, a value emitted twice or read by nobody is a
`ValueError` from `cfg.layers` (`_check_carried`); `segments` cuts a run
that carries values and has no period of its own into its repeated
stretches, so that the layers before the emitters and the readers after
them are each one scan. Not with `loop_steps` > 1, under a `sequence` axis
or with `heads_held` (`_refuse_unmapped_loop`, `cfg.layers`). The operators
that use it: the Mamba-1 mixer (`"mamba1"`; arXiv:2312.00752: a decay a
channel and state, so no matmul form; the chunked scan of
`ops/selective_scan.py`), which as `"mamba1_emit"` also emits its scan's
output as `scan_memory`; differential attention (arXiv:2410.05258:
`softmax(q1 k1^T) V - lam softmax(q2 k2^T) V` over paired heads, one
grouped-query call of the flash kernels with values twice a head's width,
an RMS norm a pair), whole (`"diff_attention"`), under the window
(`"sliding_diff_attention"`), emitting its keys and values as `attn_kv`
(`"diff_attention_emit"`) or with a query projection alone over the emitted
ones (`"cross_diff_attention"`); and the gated memory unit (`"gmu"`:
`(silu(y W_in) * scan_memory) W_out`). `layer_norm` makes the blocks' norms
and the final one LayerNorm with a bias, `attn_bias` gives differential
attention's projections a bias, and `layer_depths` says where each layer
stood in the stack it was cut from (`lam0 = 0.8 - 0.6 exp(-0.3 depth)`).
Those are SambaY's (arXiv:2507.06607; the benchmark's family `phi4flash`).

An operator of pooled keys is EVA attention (`"eva_attention"` in
`layer_types`; arXiv:2302.04542, as EvaByte holds it): plain attention's q, k
and v at as many key heads as query heads, rotated, and two learned vectors
a head (`eva_phi`, `eva_mu`, `[heads, head_dim]`, float32, no matmul's
weights). A query's softmax runs over keys of two kinds at once: the tokens
of its own window of `eva_window`, causal (block-diagonal: nothing of the
window before), and the summaries of every chunk of `eva_chunk` tokens of
every earlier window, each a softmax-weighted sum of the chunk's keys (by
`phi . k`) plus `mu`, with the same weights' sum of its values
(`ops/eva.py`: the summaries' kernel pair, the causal flash kernel on the
windows folded into the batch, the flash kernel under a staircase over the
summaries, and the join of the two partial softmaxes by their lse). The
layer reads `eva_remote_mass` and `eva_chunk_entropy`. A sequence is whole
windows or no longer than one (then plain causal attention).
`norm_unit_offset` makes every RMSNorm's scale `1 + g` with `g` starting at
0, `init_std` draws every matrix at one standard deviation, and
`n_pred_heads` > 1 makes the head predict that many positions a token: the
unembedding is `[d_model, n_pred_heads x vocab_size]`, position `t`'s
targets are ids `t + 1 .. t + n_pred_heads`, and the loss is the mean of
the heads' cross-entropies (`ops/fused.py` `multi_head_cross_entropy`). Those
are EvaByte's (the benchmark's family `evabyte`).

A stack may train by block diffusion and not by next-token prediction
(`objective="block_diffusion"`; BD3-LMs, arXiv:2503.09573, as SDAR,
arXiv:2510.06303, adopts it). A batch is `{"tokens" [B, L], "noise" [B, L],
"level" [B, L / diffusion_block]}`, integers: token `i` of block `b = i //
diffusion_block` is masked where `noise_i < level_b`, at the block's noise
level `t_b = level_b / 2^24` (`diffusion_inputs`). The stack runs on `2 L`
rows for `L` tokens, the noisy copy (`mask_token_id` at the masked
positions) and then the clean one, both at positions `0 .. L - 1`; every
layer's operator is `"block_diffusion_attention"`: plain attention's leaves
and projections under the mask of `ops/block_diffusion.py` (a row sees the
clean rows of the blocks before its own and its own half's rows of its own
block, both directions: the flash kernels' staircase at steps of one block
over the clean keys, the two halves folded into the query heads; the own
block in `jax.numpy`; the two joined by their lse). The head reads the noisy
half alone, with no shift, and the loss is `1 / (B L) sum_i m_i / t_b(i)
ce_i` through the weighted chunked cross-entropy (`ops/fused.py`), plus what
the layers' readings add over all `2 L` rows; the step reads
`diffusion_tokens`, `diffusion_masked_tokens`, `diffusion_weight_sum` and
`diffusion_rows`. Not with another operator in the stack, `loop_steps` > 1,
`n_pred_heads` > 1 or over a mesh of several devices yet. `embed_init_std`
draws the embedding at a scale of its own (1 in the benchmark's cell: a
row's own token then sets its routing, where at the default 0.02 a seeded
stack's rows all follow their context's average to the same few experts).
Those are SDAR-30B-A3B's (the benchmark's family `sdar`).

Under a mesh with an `expert` axis (`make_mesh({"expert": n})`) a routed
stack is expert-parallel, nothing of a layer left out: a device holds
`n_experts / n` whole experts of every layer (the experts' leaves cut on
their `experts` axis, never gathered), its own sequences of the batch (the
axis is a batch axis for everything outside the routed layer) and an n-th of
every other matrix (cut on `embed`, gathered for use and its gradient
scattered, as `fsdp` does). The routed layer runs in a `shard_map` over the
axis (`_routed_ffn`, `_routed_rows`): a device routes its own tokens in
float32; an all-gather brings every device all the axis's normed rows,
weights and choices (`moe_gather`); the device computes the rows routed to
its experts as any share does (`experts_of_share` with `held =
(n_held x axis_index, n_held)`, the grouped-matmul kernels in buffers of
`held_chunk` rows); a reduce-scatter sums the partial results on each
token's own device (`moe_scatter`). Dropless on every device; the balance
loss, the z loss and `expert_load` are over the mesh's batch, `held_slots`,
`dropped_slots` and `chip_load` a device each. The head is gathered whole
for the loss and every device takes the chunks of its own sequences
(`_head_loss`). All 64 experts of Mellum2-12B-A2.5B's layers, window-1024
and full attention 3 : 1, train so over the four chips of one host
(`mellum`).

With `remat` each block runs under `jax.checkpoint`: its input is kept and
its values are made again in the backward pass, but for the named ones
(`checkpoint_name`) that `make_train_step`'s step finds room for on the
device it is traced for (`saved_activations`: from the widths, the tokens
and the state a device holds, the device's memory limit and which of
`segments`' runs a scan stacks, whose moments `_moments` walks in the
backward's order; nothing where no limit can be read). A stack that is run
`loop_steps` times keeps a name for the last k of its passes, the most that
fit: the backward reaches them first.

Every weight of a block's plain matmuls leaves its gradient's matmul as an
array of its own in the compute dtype, and in a segment of one period also
reaches its own matmuls as one (`_own_weights`), so that no cast, update of
the scanned stack or optimizer update is fused into a matmul. The routed
experts' weights are `ops/moe.py`'s.

Adding a layer kind. What an operator or a feed-forward is, is stated once:
a `Sublayer` record in `_OPERATORS` or `_FEED_FORWARDS` (below the forward
functions) holds its leaves (how they are drawn, their logical axes, which
are plain matmuls' weights), its forward, the `checkpoint_name`s it makes
with their widths, its parameters, what its backward holds, and its
operations. `TransformerConfig.layers`, `_blocks_init`, `param_shardings`,
`_own_weights`, `_block`, `saved_activations` and `flops_per_token` read
the tables and name no kind: a new kind is one record, its names in
`_SAVE_ORDER`, the fields it reads in `TransformerConfig`, and a case in
`tests/test_layer_kinds.py`, which holds a record's statements to one
another. The forward functions stay in this module under their names: the
benchmark's tests patch them here.

Parallelism (ray_tpu.parallel.mesh axes):
  data/fsdp — batch split; fsdp additionally shards params (ZeRO-3 style)
  expert    — a routed layer's experts split, whole experts a device, and
              its exchange under shard_map; for everything else one more
              fsdp axis (batch split, params sharded). Alone in its mesh:
              a second axis of several devices beside it is not mapped yet
  tensor    — heads + mlp hidden + vocab split (Megatron layout)
  sequence  — context parallelism; attention switches to ring_attention

Capability analog of what the reference reaches only through integrations
(SURVEY §5: it ships no native SP); here it is native. Cells that train it:
`mistral7b.tokens4k`, `mistral7b.fsdp4`, `olmoe.tokens4k`,
`lfm2moe.tokens8k`, `dsv2lite.tokens8k`, `nemotron3nano.tokens8k`,
`lagunaxs2.tokens8k`, `keyevl2.tokens16k`, `solaropen2.tokens8k`,
`ouro.tokens16k`, `phi4flash.tokens16k`, `evabyte.tokens8k`, and
`sdar.tokens16k`, and `mellum2.ep4` on the four chips of an `expert` axis
(BENCHMARK.json).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import logging
import math
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.ops import moe
from ray_tpu.ops.block_diffusion import (
    block_diffusion_attention, own_join_untiled, pairs_per_token)
from ray_tpu.ops.eva import eva_attention
from ray_tpu.ops.flash_attention import mha, resolve_impl
from ray_tpu.ops.kda import SUB as _KDA_SUB, kda, kda_untiled
from ray_tpu.ops.mamba_passes import (
    _log_pass, causal_conv_silu, conv_untiled, gated_group_rmsnorm,
    group_rmsnorm_gated, norm_untiled)
from ray_tpu.ops.sparse_attention import keys_kept, sparse_attention
from ray_tpu.ops.selective_scan import (
    channel_block, selective_scan, selective_scan_untiled)
from ray_tpu.ops.ssd import head_tile, scan_untiled, ssd
from ray_tpu.ops.fused import (
    HEAD_CHUNK,
    _own_buffer,
    _own_cotangent,
    fused_rmsnorm,
    lm_head_cross_entropy,
    multi_head_cross_entropy,
    softmax_cross_entropy,
    weighted_lm_head_cross_entropy,
)
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel.ring_attention import ring_attention
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)


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
    # jax.checkpoint each block: its input is the checkpoint; what else a
    # block keeps the step derives (`saved_activations`)
    remat: bool = False
    # auto | pallas | xla | ring; a routed feed-forward's grouped matmul
    # takes its kernels where attention does
    attention_impl: str = "auto"
    norm_eps: float = 1e-6
    tied_embeddings: bool = True
    n_experts: int = 0  # 0 => dense SwiGLU; else d_ff is one expert's width
    experts_per_token: int = 1
    norm_topk_prob: bool = False  # divide the chosen weights by their sum
    # RMSNorm on q and k: over the whole projection (True, "projection") or
    # over each head's width with one scale for all heads ("head")
    qk_norm: Union[bool, str] = False
    router_aux_loss_coef: float = 0.01  # load balancing, mean over layers
    router_z_loss_coef: float = 0.001  # logsumexp(router logits)^2
    # an operator of `_OPERATORS` per layer ("full_attention" |
    # "sliding_attention" | "sparse_attention" | "conv" |
    # "latent_attention" | "mamba2" | "kda" | "mamba1" | "mamba1_emit" |
    # "diff_attention" | "sliding_diff_attention" | "diff_attention_emit" |
    # "cross_diff_attention" | "gmu" | "eva_attention" |
    # "block_diffusion_attention"); () => attention everywhere
    layer_types: Tuple[str, ...] = ()
    conv_taps: int = 3  # the short convolution's reach, this token included
    n_dense_layers: int = 0  # with n_experts: leading layers with a dense FF
    d_ff_dense: Optional[int] = None  # their width; None => ff_dim
    router_score: str = "softmax"  # softmax | sigmoid
    norm_topk_eps: float = 0.0  # added to the sum norm_topk_prob divides by
    expert_bias: bool = False  # choose by score + bias; the step moves it
    expert_bias_update_rate: float = 1e-3
    # (first, n): compute experts first..first+n-1 of the n_experts routed over
    experts_held: Optional[Tuple[int, int]] = None
    # latent attention, by config.json's own keys: the latent's width, a
    # head's unrotated and rotary query-key columns, its value columns
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # ((key, value), ...) of config.json's `rope_scaling` (type "yarn"), for
    # the latent attention's rotary columns; None => plain frequencies
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    # with n_experts: one dense SwiGLU of this many experts' width beside
    # the routed ones, unweighted
    n_shared_experts: int = 0
    # the balance loss per sequence, summed over the layers (else over the
    # batch, mean over the layers)
    seq_aux: bool = False
    # one sublayer a layer, `x + f(norm(x))`: an operator ("mamba2" |
    # "full_attention" | "conv" | "latent_attention") or a feed-forward
    # ("dense_ff" | "routed_ff") alone; () => `layer_types`, whose every
    # layer is two sublayers, an operator and then a feed-forward
    sublayer_types: Tuple[str, ...] = ()
    d_head: Optional[int] = None  # a head's width; None => d_model / n_heads
    # plain attention rotates q and k by position, latent attention a
    # head's `qk_rope_head_dim` columns and the shared key; False
    # (`use_rope` false, `mla_use_nope`): the columns enter the scores as
    # they leave their projections, and `rope_scaling` scales nothing
    rope: bool = True
    # "swiglu": silu(gate) * up; "relu2": relu(up)^2, ungated (no `w_gate`):
    # the dense, the shared and the routed experts' alike
    ff_activation: str = "swiglu"
    routed_scaling_factor: float = 1.0  # on the chosen experts' weights
    d_ff_shared: Optional[int] = None  # None => n_shared_experts * ff_dim
    # the Mamba-2 mixer (arXiv:2405.21060), by config.json's own keys:
    # `mamba_num_heads` heads of `mamba_head_dim` channels, a state of
    # `ssm_state_size` a channel, `n_groups` groups of B and C, `conv_kernel`
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    mamba_conv_taps: int = 4
    ssd_chunk: int = 128  # tokens a chunk of the scan (`ops/ssd.py`)
    # dt's initial range and floor: `time_step_min`, `_max`, `_floor`
    mamba_dt_init: Tuple[float, float, float] = (1e-3, 0.1, 1e-4)
    # the mixers' output projections start divided by sqrt(n_layers)
    rescale_prenorm_residual: bool = False
    # "sliding_attention": causal attention in which a token sees itself and
    # the `sliding_window - 1` before it, at a head count of its own (None =>
    # `n_heads`; `n_kv_heads` and the head's width are shared) and a rotary
    # recipe of its own: its theta (None => `rope_theta`) over a whole head,
    # at plain frequencies
    sliding_window: int = 0
    n_heads_sliding: Optional[int] = None
    rope_theta_sliding: Optional[float] = None
    # the share of a head's columns, the first ones, that full attention
    # turns, at `rope_theta` and, where there is one, `rope_scaling`
    partial_rotary_factor: float = 1.0
    # a sigmoid gate on plain attention's output, from the normed input:
    # one a head and token (True: `w_gate_attn` [d, heads]) or one a column
    # of the context ("elementwise": [d, heads x head_dim])
    attn_gate: Union[bool, str] = False
    # "sparse_attention": `index_heads` index queries of `index_head_dim`
    # over one index key a token; a query attends to the `index_topk`
    # earlier keys its index scores rank first
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # "kda" (arXiv:2510.26692), by `linear_attn_config`'s own keys:
    # `num_heads` heads of `head_dim`, `short_conv_kernel_size` taps; the
    # low-rank gates' rank (None => `kda_head_dim`), the tokens a chunk of
    # `ops/kda.py`. The decay's `A_log` and `dt_bias` start as the Mamba-2
    # mixer's do (`mamba_dt_init`)
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_taps: int = 4
    kda_gate_rank: Optional[int] = None
    kda_chunk: int = 64
    # beta = 2 sigmoid(W_b u) in (0, 2) (`kda_allow_neg_eigval`: the factor
    # I - beta k k^T may turn a state's component along k); False: sigmoid,
    # in (0, 1), the paper's
    kda_allow_neg_eigval: bool = True
    # (first, n): plain attention and "kda" hold heads first..first+n-1 of
    # theirs, and attention the key-value heads those query heads read
    heads_held: Optional[Tuple[int, int]] = None
    # the whole stack is run this many times over the same weights, the
    # final norm after every pass, its output the next pass's input
    # (arXiv:2510.25741's `total_ut_steps`)
    loop_steps: int = 1
    # an RMS norm on a sublayer's output before it joins the stream (plain
    # attention's and the dense feed-forward's: four norms a layer)
    post_norm: bool = False
    # with `loop_steps` > 1: one `Linear(d_model, 1)` with a bias reads every
    # pass's normed stream, and the loss is the passes' cross-entropies
    # under the exit distribution its sigmoids make, less
    # `exit_entropy_coef` times that distribution's entropy
    exit_gate: bool = False
    exit_entropy_coef: float = 0.0
    # the blocks' norms and the final one are LayerNorm with a scale and a
    # bias (`<norm>_bias` beside every scale), for the records that take it
    # (`takes_layer_norm`); False => RMSNorm
    layer_norm: bool = False
    # a bias on differential attention's q, k, v and output projections
    attn_bias: bool = False
    # the Mamba-1 mixer (arXiv:2312.00752; "mamba1"): `mamba1_inner`
    # channels (the expansion of `d_model`), a state of `mamba1_state` a
    # channel, `mamba1_dt_rank` columns for the step size (None =>
    # ceil(d_model / 16)), `mamba1_conv_taps` taps, the tokens a chunk of
    # the scan (`ops/selective_scan.py`); dt starts as `mamba_dt_init` says
    mamba1_inner: int = 0
    mamba1_state: int = 16
    mamba1_dt_rank: Optional[int] = None
    mamba1_conv_taps: int = 4
    scan_chunk: int = 128
    # the depth each layer stands at in the stack it was cut from, for the
    # constants that depend on it (differential attention's `lam0`); () =>
    # its index in this stack
    layer_depths: Tuple[int, ...] = ()
    # "eva_attention" (arXiv:2302.04542), by config.json's own keys
    # `window_size` and `chunk_size`: a causal softmax inside a window of
    # `eva_window` tokens joined with the summaries of every earlier
    # window's chunks of `eva_chunk` tokens
    eva_window: int = 0
    eva_chunk: int = 0
    # the head predicts this many positions a token (`num_pred_heads`): the
    # unembedding is [d_model, n_pred_heads x vocab_size], untied
    n_pred_heads: int = 1
    # every RMSNorm's scale is 1 + g, g at 0 to start (`norm_add_unit_offset`),
    # for the records that take it (`takes_unit_offset`)
    norm_unit_offset: bool = False
    # every matrix (the blocks' matmul weights, the embedding, the head) is
    # drawn normal at this standard deviation; None => at 1 / sqrt(fan-in),
    # the embedding at 0.02
    init_std: Optional[float] = None
    # False: a run of alike layers is ONE period, walked layer by layer, not
    # a scan over its periods. A scan's gradient is one stacked buffer, held
    # from the backward's first step beside the whole stack's weights in the
    # compute dtype (cast once ahead of the loop); walked, a layer's gradient
    # is made when the backward reaches it and AdamW's update follows at
    # once, a layer's bytes for the stack's, at the price of a trace and a
    # lowering a layer (`_terms` has both moments)
    scan_layers: bool = True
    # "next_token": position t predicts token t + 1 under a causal operator.
    # "block_diffusion" (arXiv:2503.09573): a batch carries its noise
    # (`diffusion_inputs`), the stack runs on a noisy and a clean copy of
    # every sequence, 2 L rows for L tokens, under
    # "block_diffusion_attention" in every layer, and the masked positions of
    # the noisy copy predict their own token, weighted by 1 / t; blocks of
    # `diffusion_block` tokens share a noise level, and `mask_token_id`
    # stands at a masked position
    objective: str = "next_token"
    diffusion_block: int = 0
    mask_token_id: Optional[int] = None
    # the embedding is drawn normal at this standard deviation; None => 0.02.
    # At 0.02 a seeded stack's stream is its context's average (attention's
    # output is two orders over a token's own row), every row of a sequence
    # routes to the same few experts, and a share's held rows swing between
    # none and three even shares from step to step; at 1 a row's own token
    # sets its routing, as in any trained checkpoint, and the load is even
    embed_init_std: Optional[float] = None
    # the four multipliers of a stack parametrised for width (muP, as
    # config.json's keys of the same names have them): the embedding's rows
    # times `embedding_multiplier`; what a sublayer adds to the stream times
    # `residual_multiplier`, for the records that take it
    # (`takes_multipliers`); plain attention's scores times
    # `attention_multiplier` in place of `1 / sqrt(head_dim)` (None); the
    # logits over `logits_scaling`. At 1 and None no instruction is added
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0

    @property
    def rows_per_token(self) -> int:
        """The rows the stack runs for a token of a sequence: the noisy and
        the clean copy under block diffusion."""
        return 2 if self.objective == "block_diffusion" else 1

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    def heads(self, op: str) -> int:
        """The query heads of a layer whose operator is `op`, an attention
        of `_OPERATORS`."""
        return _OPERATORS[op].heads(self)

    @property
    def head_width(self) -> int:
        """The unembedding's columns: a vocabulary a predicted position."""
        return self.vocab_size * self.n_pred_heads

    @property
    def shared_dim(self) -> int:
        return self.d_ff_shared or self.n_shared_experts * self.ff_dim

    @property
    def gated(self) -> bool:
        if self.ff_activation not in ("swiglu", "relu2"):
            raise ValueError(f"ff_activation {self.ff_activation!r}")
        return self.ff_activation == "swiglu"

    @property
    def ff_matrices(self) -> int:
        """A feed-forward's weight matrices: gate, up and down, or two."""
        return 3 if self.gated else 2

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def dt_rank(self) -> int:
        """The columns Mamba-1's step size is projected through."""
        return self.mamba1_dt_rank or -(-self.d_model // 16)

    @property
    def depths(self) -> Tuple[int, ...]:
        depths = tuple(self.layer_depths) or tuple(range(self.n_layers))
        if len(depths) != self.n_layers:
            raise ValueError(
                f"layer_depths names {len(depths)} layers, n_layers is "
                f"{self.n_layers}")
        return depths

    @property
    def mamba_conv_dim(self) -> int:
        """The channels the mixer's convolution runs over: x, B and C."""
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        return int(8 * self.d_model / 3 + 127) // 128 * 128  # SwiGLU, 128-mult

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.experts_held or (0, self.n_experts))

    @property
    def layers(self) -> Tuple["LayerKind", ...]:
        """Every layer's kind, first to last. A name that the tables do not
        have is an error: `layer_types` and `sublayer_types` reach this from
        a deployment's `config.json`."""
        if self.sublayer_types:
            field_, names = "sublayer_types", self.sublayer_types
            known = {**_OPERATORS, **_FEED_FORWARDS}
        else:
            field_, known = "layer_types", _OPERATORS
            names = self.layer_types or ("full_attention",) * self.n_layers
        if len(names) != self.n_layers:
            raise ValueError(
                f"{field_} names {len(names)} layers, n_layers is "
                f"{self.n_layers}")
        for name in names:
            if name not in known:
                raise ValueError(
                    f"{field_} names {name!r}, which is none of "
                    f"{sorted(known)}")
            if (self.heads_held and name in _OPERATORS
                    and not _OPERATORS[name].takes_heads_held):
                raise ValueError(
                    f"heads_held {tuple(self.heads_held)} with the operator "
                    f"{name!r}, which holds all its heads or none: a share "
                    "of the heads is plain attention's and kda's")
        if self.sublayer_types:
            kinds = tuple(
                LayerKind(None, name == "routed_ff", True)
                if name in _FEED_FORWARDS else LayerKind(name, False, False)
                for name in names)
        else:
            kinds = tuple(
                LayerKind(op,
                          bool(self.n_experts) and i >= self.n_dense_layers)
                for i, op in enumerate(names))
        if self.post_norm:
            for kind in set(kinds):
                for sub in _sublayers(kind):
                    if not sub.takes_post_norm:
                        raise ValueError(
                            f"post_norm with a {type(sub).__name__[1:]} "
                            "sublayer, which has no norm on its output: "
                            "plain attention and the dense feed-forward do")
        if self.layer_norm:
            for kind in set(kinds):
                for sub in _sublayers(kind):
                    if not sub.takes_layer_norm:
                        raise ValueError(
                            f"layer_norm with a {type(sub).__name__[1:]} "
                            "sublayer, whose norm is RMSNorm alone")
        if self.norm_unit_offset:
            for kind in set(kinds):
                for sub in _sublayers(kind):
                    if not sub.takes_unit_offset:
                        raise ValueError(
                            f"norm_unit_offset with a "
                            f"{type(sub).__name__[1:]} sublayer: the scale "
                            "1 + g is EVA attention's and the dense "
                            "feed-forward's RMSNorm's")
        if (self.attention_multiplier is not None
                and self.attention_impl == "ring"):
            raise ValueError(
                f"attention_multiplier {self.attention_multiplier} with "
                "attention_impl 'ring', whose scores' scale is its own")
        if (self.residual_multiplier != 1
                or self.attention_multiplier is not None):
            for kind in set(kinds):
                for sub in _sublayers(kind):
                    if not sub.takes_multipliers:
                        raise ValueError(
                            f"residual_multiplier {self.residual_multiplier} "
                            f"or attention_multiplier "
                            f"{self.attention_multiplier} with a "
                            f"{type(sub).__name__[1:]} sublayer: the "
                            "multipliers are written for plain attention, "
                            "the Mamba-2 mixer and the dense feed-forward")
        if self.objective not in ("next_token", "block_diffusion"):
            raise ValueError(f"objective {self.objective!r}")
        if self.objective == "block_diffusion":
            others = sorted({kind.op for kind in kinds if kind.op not in (
                None, "block_diffusion_attention")})
            if others:
                raise ValueError(
                    f"objective 'block_diffusion' over the operators "
                    f"{others}: a causal operator (or a recurrence) would "
                    "let a noisy row see what the mask hides; every layer's "
                    "operator is 'block_diffusion_attention'")
        for sub in {sub for kind in kinds for sub in _sublayers(kind)}:
            sub.check(self)
        _check_carried(kinds)
        return kinds

    @property
    def n_routed_layers(self) -> int:
        return sum(kind.routed for kind in self.layers)


class LayerKind(NamedTuple):
    """A layer's key into the two tables of sublayers (`_sublayers`)."""
    op: Optional[str]  # of `_OPERATORS`; None: a feed-forward alone
    # the feed-forward, of `_FEED_FORWARDS`: "routed_ff", or "dense_ff"
    routed: bool
    ff: bool = True  # False: the layer is an operator alone


class Segment(NamedTuple):
    """`periods` repetitions of the layers in `layout`, scanned as one."""
    layout: Tuple[LayerKind, ...]
    periods: int


def segments(cfg: TransformerConfig) -> List[Segment]:
    """The stack as runs of whole periods: it is cut where the feed-forward
    changes kind (a layer that is an operator alone stays in the run it
    stands in), and each run is as many repetitions of its shortest period
    as make it up (one repetition of all of it, if it has none shorter). A
    run in which a layer reads what another made cannot repeat as a whole
    (the emitter stands in it once): where it has no period of its own it
    is cut into its repeated stretches (`_stretches`), so that the layers
    before the emitters and the readers after them are each scanned.
    Without `scan_layers` every run is one period of all its layers."""
    kinds = cfg.layers
    runs, start, routed = [], 0, None
    for i, kind in enumerate(kinds):
        if kind.ff and routed not in (None, kind.routed):
            runs.append(kinds[start:i])
            start = i
        if kind.ff:
            routed = kind.routed
    runs.append(kinds[start:])
    out = []
    for run in runs:
        n = len(run)
        period = next(p for p in range(1, n + 1) if n % p == 0 and all(
            run[i] == run[i % p] for i in range(n)))
        carries = any(sub.emits or sub.reads
                      for kind in run for sub in _sublayers(kind))
        if not cfg.scan_layers:
            out.append(Segment(tuple(run), 1))
        elif period < n or not carries:
            out.append(Segment(run[:period], n // period))
        else:
            out.extend(_stretches(run))
    return out


def _stretches(run: Tuple[LayerKind, ...]) -> List[Segment]:
    """A run with no period of its own as stretches, first to last: where a
    stretch of layers is repeated at least twice on end, the longest such
    (of equals the shortest period) is a segment of that many periods; the
    layers between two such are a segment of one period."""
    out, loose, i, n = [], [], 0, len(run)
    while i < n:
        best = (0, 0)  # (layers covered, -period)
        for p in range(1, (n - i) // 2 + 1):
            k = 1
            while run[i + k * p:i + (k + 1) * p] == run[i:i + p]:
                k += 1
            if k > 1:
                best = max(best, (k * p, -p))
        covered, p = best[0], -best[1]
        if not covered:
            loose.append(run[i])
            i += 1
            continue
        if loose:
            out.append(Segment(tuple(loose), 1))
            loose = []
        out.append(Segment(tuple(run[i:i + p]), covered // p))
        i += covered
    if loose:
        out.append(Segment(tuple(loose), 1))
    return out


def _check_carried(kinds: Tuple[LayerKind, ...]) -> None:
    """What the layers emit and read (`Sublayer.emits`, `reads`) must make
    a stack that can be walked: a value is emitted once, before every layer
    that reads it, and some layer reads it."""
    emitted: Dict[str, int] = {}
    read = set()
    for i, kind in enumerate(kinds):
        for sub in _sublayers(kind):
            for name in sub.reads:
                if name not in emitted:
                    raise ValueError(
                        f"layer {i} ({kind.op}) reads {name!r}, which no "
                        "layer before it emits")
                read.add(name)
            for name in sub.emits:
                if name in emitted:
                    raise ValueError(
                        f"layer {i} ({kind.op}) emits {name!r}, which layer "
                        f"{emitted[name]} emits already")
                emitted[name] = i
    unread = sorted(set(emitted) - read)
    if unread:
        raise ValueError(
            f"layer {emitted[unread[0]]} emits {unread[0]!r}, which no "
            "layer after it reads")


# ---------------------------------------------- the sublayers' forwards

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(width: int, theta: float, scaling=None):
    """(the `width / 2` rotary frequencies, float32; what cos and sin are
    multiplied by). Plain: `theta ** (-2 i / width)` and 1. With
    `scaling`, a mapping of config.json's `rope_scaling` of type "yarn"
    (arXiv:2309.00071), the frequencies that turn more than `beta_fast`
    times over the original context are kept, those that turn fewer than
    `beta_slow` times are divided by `factor`, and a linear ramp blends
    the ones between; cos and sin are scaled by `attention_factor` where
    the mapping has one, else by the ratio of `yarn_softmax_scale`'s two
    factors, `mscale` and `mscale_all_dim`."""
    half = width // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if not scaling:
        return freqs, 1.0
    low, high = yarn_ramp_bounds(width, theta, scaling)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3),
        0.0, 1.0)
    factor = scaling["factor"]
    blended = freqs / factor * ramp + freqs * (1.0 - ramp)
    if "attention_factor" in scaling:
        return blended, float(scaling["attention_factor"])
    return blended, (_yarn_mscale(factor, scaling.get("mscale", 1.0))
                     / _yarn_mscale(factor, scaling.get("mscale_all_dim", 0.0)))


def yarn_ramp_bounds(width: int, theta: float, scaling) -> Tuple[int, int]:
    """The first frequency YaRN's ramp moves and the first it moves all the
    way: the pairs that make `beta_fast` and `beta_slow` turns over the
    original context, rounded outward and kept inside the `width / 2`."""
    span = scaling["original_max_position_embeddings"]

    def pair(turns):
        return width * math.log(span / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    return (max(math.floor(pair(scaling.get("beta_fast", 32))), 0),
            min(math.ceil(pair(scaling.get("beta_slow", 1))), width - 1))


def yarn_softmax_scale(width: int, scaling=None) -> float:
    """The scale of the scores of heads `width` wide: `width ** -0.5`, times
    the square of YaRN's `mscale_all_dim` factor where there is scaling."""
    scale = width ** -0.5
    if scaling and scaling.get("mscale_all_dim"):
        scale *= _yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def _rope(x, positions, theta: float, scaling=None):
    """Rotary embedding on [B, T, H, Dh] with integer positions [B, T], at
    `rope_frequencies(Dh, theta, scaling)`."""
    B, T, H, Dh = x.shape
    half = Dh // 2
    freqs, mscale = rope_frequencies(Dh, theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _rotate(x, positions, theta: float, share: float = 1.0, scaling=None):
    """`_rope` on the first `share` of the columns of every head of `x`
    [B, T, H, Dh]; the rest are left as they are."""
    turned = int(x.shape[-1] * share)
    if turned == x.shape[-1]:
        return _rope(x, positions, theta, scaling)
    return jnp.concatenate(
        [_rope(x[..., :turned], positions, theta, scaling), x[..., turned:]],
        axis=-1)


def _attention(q, k, v, cfg: TransformerConfig, seq_axis: Optional[str],
               seq_size: int, mesh=None, keep_ctx: bool = False,
               scale: Optional[float] = None, window: Optional[int] = None):
    """Causal attention of q, k [B, T, H, D] and v [B, T, H, Dv]; `scale`
    is the scores', `1 / sqrt(D)` where None; under `window` a query sees
    itself and the `window - 1` keys before it."""
    if cfg.attention_impl == "ring" and seq_axis is not None:
        # Inside shard_map over the sequence axis: exact ring attention.
        rep = q.shape[2] // k.shape[2]
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return ring_attention(
            q, k, v, axis_name=seq_axis, axis_size=seq_size, causal=True
        )
    impl = _kernel_impl(cfg)
    attn = partial(mha, causal=True, impl=impl, keep_ctx=keep_ctx, scale=scale,
                   window=window)
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


def _attention_layer(x, blk, positions, cfg: TransformerConfig,
                     seq_axis: Optional[str], seq_size: int, mesh=None,
                     keep_ctx: bool = False, *, op: "_PlainAttention"):
    """x + attention(norm(x)): projections, QK-norm, RoPE, the kernel, and
    with `w_gate_attn` the heads' gates on its output. `op` is the record
    of "full_attention" or of "sliding_attention": the heads' number, the
    rotary recipe and the window are the operator's."""
    B, T, d = x.shape
    h, dh = op.heads(cfg), cfg.head_dim
    dt = cfg.dtype
    y, q, k, v = _qkv(x, blk, positions, cfg, op)
    with jax.named_scope("attention"):
        o = _attention(q, k, v, cfg, seq_axis, seq_size, mesh, keep_ctx,
                       scale=cfg.attention_multiplier, window=op.window(cfg))
    if "w_gate_attn" in blk:  # a column a head, or one a column of a head
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(y @ blk["w_gate_attn"].astype(dt))
            o = o * (gate.reshape(B, T, h, dh)
                     if cfg.attn_gate == "elementwise" else gate[..., None])
    with jax.named_scope("attn_out"):
        out = o.reshape(B, T, h * dh) @ blk["wo"].astype(dt)
        if "attn_post_norm" in blk:
            out = _post_norm(out, blk["attn_post_norm"], cfg)
        return checkpoint_name(x + _to_the_stream(out, cfg), "attn_res")


def _to_the_stream(out, cfg: "TransformerConfig"):
    """What a sublayer adds to the stream: its output times
    `residual_multiplier`; at 1 the output itself, and no instruction."""
    return out if cfg.residual_multiplier == 1 else (
        out * cfg.residual_multiplier)


def _post_norm(y, scale, cfg: "TransformerConfig"):
    """A sublayer's output normed before it joins the stream
    (`post_norm`)."""
    with jax.named_scope("post_norm"):
        return fused_rmsnorm(y, scale, eps=cfg.norm_eps)


def _qkv(x, blk, positions, cfg: TransformerConfig, op: "_PlainAttention"):
    """(the normed input, q [B, T, heads, dh], k and v [B, T, kv heads, dh])
    of plain attention: the projections, QK-norm and RoPE by `op`'s
    recipe."""
    B, T, d = x.shape
    h, hk, dh = op.heads(cfg), op.kv_heads(cfg), cfg.head_dim
    theta, share, scaling = op.rotary(cfg)
    dt = cfg.dtype
    per_head = cfg.qk_norm == "head"

    def qk_norm(q, k):
        with jax.named_scope("qk_norm"):
            return (fused_rmsnorm(q, blk["q_norm"], eps=cfg.norm_eps),
                    fused_rmsnorm(k, blk["k_norm"], eps=cfg.norm_eps))

    with jax.named_scope("attn_qkv"):
        y = fused_rmsnorm(x, blk["attn_norm"], eps=cfg.norm_eps)
        # named as the products leave the MXU: QK-norm's backward takes
        # them, and the norm and RoPE after them are elementwise
        q = checkpoint_name(y @ blk["wq"].astype(dt), "attn_qkv")
        k = checkpoint_name(y @ blk["wk"].astype(dt), "attn_qkv")
        if cfg.qk_norm and not per_head:  # over the whole projection
            q, k = qk_norm(q, k)
        q = q.reshape(B, T, h, dh)
        k = k.reshape(B, T, hk, dh)
        if per_head:  # every head over its own dh, one scale for all heads
            q, k = qk_norm(q, k)
        v = checkpoint_name(y @ blk["wv"].astype(dt), "attn_qkv").reshape(
            B, T, hk, dh)
        if cfg.rope:
            q = _rotate(q, positions, theta, share, scaling)
            k = _rotate(k, positions, theta, share, scaling)
    return y, q, k, v


# what cuts the indexer's input off from the stream, under a name of its
# own: a test takes it away to show what the comparison reads then
_detached = jax.lax.stop_gradient


def _layer_norm(x, scale, bias, eps: float):
    """LayerNorm over the last axis in float32, in x's dtype."""
    x32 = x.astype(jnp.float32)
    centred = x32 - x32.mean(axis=-1, keepdims=True)
    normed = centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)
    return (normed * scale + bias).astype(x.dtype)


def _block_norm(x, blk, name: str, cfg: "TransformerConfig"):
    """A sublayer's norm of its input under the scale `blk[name]`: RMSNorm,
    or with `layer_norm` LayerNorm with the bias `blk[name + "_bias"]`."""
    if cfg.layer_norm:
        return _layer_norm(x, blk[name], blk[name + "_bias"], cfg.norm_eps)
    return fused_rmsnorm(x, _norm_scale(blk[name], cfg), eps=cfg.norm_eps)


def _norm_scale(g, cfg: "TransformerConfig"):
    """An RMSNorm's scale from its leaf: the leaf, or with
    `norm_unit_offset` 1 + the leaf."""
    return 1.0 + g if cfg.norm_unit_offset else g


def _norm_leaves(name: str, cfg: "TransformerConfig", L: int):
    """`L` layers' leaves of the norm `name` (`_block_norm`)."""
    start = jnp.zeros if cfg.norm_unit_offset else jnp.ones
    leaves = {name: start((L, cfg.d_model), jnp.float32)}
    if cfg.layer_norm:
        leaves[name + "_bias"] = jnp.zeros((L, cfg.d_model), jnp.float32)
    return leaves


def _norm_axes(name: str, cfg: "TransformerConfig"):
    names = (name, name + "_bias") if cfg.layer_norm else (name,)
    return dict.fromkeys(names, ("layers", None))


def _sparse_attention_layer(x, blk, positions, cfg: TransformerConfig,
                            mesh=None, keep_ctx: bool = False, *,
                            op: "_SparseAttention"):
    """(x + attention(norm(x)) over the keys the layer's indexer selects,
    the layer's readings {index_loss, index_keys_min_gap,
    index_keys_max_gap, index_keep [B, T, T] int8: 1 where a query keeps a
    key}). The indexer reads the normed input *detached*: the
    index loss moves its leaves and nothing else, and nothing else moves
    them. Its queries and its key turn by position as q and k do, over all
    their columns."""
    B, T, d = x.shape
    h, dh = op.heads(cfg), cfg.head_dim
    hi, di = cfg.index_heads, cfg.index_head_dim
    dt = cfg.dtype
    impl = _kernel_impl(cfg)
    if impl == "pallas" and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "the kernels of sparse attention are not mapped over a mesh of "
            f"{mesh.size} devices yet")
    y, q, k, v = _qkv(x, blk, positions, cfg, op)
    theta = op.rotary(cfg)[0]
    with jax.named_scope("indexer"), jax.named_scope("index_qk"):
        u = _detached(y)
        q_idx = (u @ blk["wq_idx"].astype(dt)).reshape(B, T, hi, di)
        k_idx = _layer_norm(u @ blk["wk_idx"].astype(dt), blk["k_idx_norm"],
                            blk["k_idx_bias"], cfg.norm_eps)
        w_idx = (u @ blk["w_idx"].astype(dt)).astype(jnp.float32) * (
            (hi * di) ** -0.5)
        q_idx = _rope(q_idx, positions, theta)
        k_idx = _rope(k_idx[:, :, None], positions, theta)[:, :, 0]
    # names its own operations `indexer` (`index_scores`, `index_select`),
    # `attention` and `index_loss`, backward too
    o, index_loss, gap, keep = sparse_attention(
        q, k, v, q_idx, k_idx, w_idx, topk=cfg.index_topk, impl=impl,
        keep_ctx=keep_ctx)
    with jax.named_scope("attn_out"):
        x = checkpoint_name(
            x + o.reshape(B, T, h * dh) @ blk["wo"].astype(dt), "attn_res")
    return x, {"index_loss": index_loss, "index_keys_min_gap": gap.min(),
               "index_keys_max_gap": gap.max(), "index_keep": keep}


def _latent_attention_layer(x, blk, positions, cfg: TransformerConfig,
                            mesh=None, keep_ctx: bool = False):
    """x + latent_attention(norm(x)), decompressed: every head's keys and
    values are made from the token's latent and the heads attend as in any
    multi-head attention, with q and k `qk_nope_head_dim + qk_rope_head_dim`
    wide and v `v_head_dim` wide. Rotary positions turn the last
    `qk_rope_head_dim` columns of a head of q, and the one key of that width
    that `wkv_a` makes beside the latent and all heads share; without
    `cfg.rope` (`mla_use_nope`) nothing is turned or scaled: those columns
    and that key stand in the scores as they leave their projections, and
    the layers around this one carry position."""
    B, T, d = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = cfg.dtype
    scaling = dict(cfg.rope_scaling) if cfg.rope and cfg.rope_scaling else None
    with jax.named_scope("attn_qkv"):
        y = fused_rmsnorm(x, blk["attn_norm"], eps=cfg.norm_eps)
        q = checkpoint_name(y @ blk["wq"].astype(dt), "attn_qkv").reshape(
            B, T, h, nope + rope)
        if cfg.rope:
            q = jnp.concatenate(
                [q[..., :nope],
                 _rope(q[..., nope:], positions, cfg.rope_theta, scaling)],
                axis=-1)
    with jax.named_scope("kv_down"):
        down = checkpoint_name(y @ blk["wkv_a"].astype(dt), "attn_qkv")
        latent = fused_rmsnorm(down[..., :r], blk["kv_norm"], eps=cfg.norm_eps)
        k_pe = down[..., None, r:]
        if cfg.rope:
            k_pe = _rope(k_pe, positions, cfg.rope_theta, scaling)
    with jax.named_scope("kv_up"):
        kv = checkpoint_name(latent @ blk["wkv_b"].astype(dt), "attn_qkv"
                             ).reshape(B, T, h, nope + dv)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, h, rope))], axis=-1)
        v = kv[..., nope:]
    with jax.named_scope("attention"):
        o = _attention(q, k, v, cfg, None, 1, mesh, keep_ctx,
                       scale=yarn_softmax_scale(nope + rope, scaling))
    with jax.named_scope("attn_out"):
        return checkpoint_name(
            x + o.reshape(B, T, h * dv) @ blk["wo"].astype(dt), "attn_res")


def _routed_ffn(y, blk, cfg: TransformerConfig, mesh=None, bias=None):
    """The routed feed-forward on normed activations `y` [B, T, d]: the sum
    over a token's `experts_per_token` experts of p_e * SwiGLU_e(y), and the
    layer's router readings {aux_loss, z_loss, expert_load [E],
    expert_index [B T, k]}. Dropless: `expert_load` sums to B T k.
    `aux_loss` is the balance loss over the batch or, with `seq_aux`, the
    mean over the B sequences of each one's own.

    A layer that holds a share of the experts (`w_gate` has fewer than the
    router's width) sums over the held among a token's experts and leaves
    the others' terms out; its readings gain `held_slots` (the slots whose
    expert it holds) and `dropped_slots` (those of them it did not compute:
    0). `bias` [E] is the router's selection bias.

    Under a mesh with an `expert` axis of `n` devices the layer runs in a
    `shard_map` over it (`_routed_rows`): a device routes its own sequences,
    holds `E / n` whole experts and is such a share for the tokens of all
    `n`, which an all-gather brings and whose partial results a
    reduce-scatter sums on each token's own device. The batch's readings
    are over the mesh's batch; `held_slots` and `dropped_slots` are [n], a
    device each, and `chip_load` [n] is `expert_load` summed by device."""
    impl = _kernel_impl(cfg)
    axis = mesh_lib.expert_axis(mesh)
    if axis is None:
        if impl == "pallas" and mesh is not None and mesh.size > 1:
            raise NotImplementedError(
                "the grouped-matmul kernels are mapped over an `expert` "
                f"axis, which the mesh {dict(mesh.shape)} does not have "
                "(ROADMAP R1: expert parallelism)"
            )
        return _routed_rows(y, blk, cfg, impl, bias)
    ways = mesh.size  # the axis is alone in its mesh
    if cfg.n_experts % ways or blk["w_down"].shape[0] != cfg.n_experts:
        raise ValueError(
            f"{blk['w_down'].shape[0]} of {cfg.n_experts} experts over an "
            f"`{axis}` axis of {ways}: the axis cuts all of a layer's "
            "experts into whole and equal shares")
    # the router whole (gathered where it is sharded), the experts cut
    leaves = {name: blk[name] for name in _RoutedFF.moe_weights if name in blk}
    if bias is not None:
        leaves["bias"] = bias
    own, whole = P(axis), P()
    routed, readings = jax.shard_map(
        lambda y, leaves: _routed_rows(
            y, leaves, cfg, impl, leaves.get("bias"), axis),
        mesh=mesh,
        in_specs=(own, {name: whole if name in ("router", "bias") else own
                        for name in leaves}),
        out_specs=(own, {"aux_loss": whole, "z_loss": whole,
                         "expert_load": whole, "expert_index": own,
                         "held_slots": own, "dropped_slots": own}),
        check_vma=False,
    )(y, leaves)
    readings["chip_load"] = readings["expert_load"].reshape(ways, -1).sum(-1)
    return routed, readings


def _router_logits(tokens, router):
    """The router's scores before its softmax or sigmoid, [T, E]: float32
    at full precision whatever the compute dtype: with bf16 logits the
    k-th and the next expert swap on rounding."""
    return jnp.dot(
        tokens.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def _buffer_rows(cfg: TransformerConfig, rows: int, sequences: int,
                 ways: int = 1) -> int:
    """`ops/moe.py` `buffer_rows` of `cfg`'s routed layers."""
    return moe.buffer_rows(
        rows, sequences, ways, experts_per_token=cfg.experts_per_token,
        held=cfg.held[1], n_experts=cfg.n_experts,
        load_held_even=cfg.expert_bias)


def _routed_rows(y, blk, cfg: TransformerConfig, impl: str, bias=None,
                 axis: Optional[str] = None):
    """`_routed_ffn` on one device. With `axis` the device is one of an
    expert axis inside a `shard_map`: `y` is its own sequences, `blk` its
    `E / n` experts (device c's are `c E / n` onward) and the router whole.
    It routes its own tokens; the tokens, weights and choices of all the
    axis are gathered (`moe_gather`), the held rows computed as any share's,
    and the partial results summed onto each token's own device
    (`moe_scatter`). The gather and the scatter are each other's
    transposes, so the backward is the same exchange the other way."""
    B, T, d = y.shape
    tokens = y.reshape(B * T, d)
    with jax.named_scope("moe_router"):
        logits = _router_logits(tokens, blk["router"])
        probs, weights, index = moe.route(
            logits, cfg.experts_per_token, cfg.norm_topk_prob,
            score=cfg.router_score, bias=bias, eps=cfg.norm_topk_eps)
        if cfg.routed_scaling_factor != 1.0:
            weights = weights * cfg.routed_scaling_factor
    n_held = blk["w_down"].shape[0]
    share = n_held < cfg.n_experts
    own_index = index
    if axis is None:
        first = cfg.held[0]
    else:
        first = jax.lax.axis_index(axis) * n_held
        with jax.named_scope("moe_gather"):
            tokens, weights, index = (
                jax.lax.all_gather(x, axis, tiled=True)
                for x in (tokens, weights, index))
    with jax.named_scope("moe_router"):  # the slots' sort and the readings
        slots = moe.sort_slots(index, cfg.n_experts,
                               (first, n_held) if share else None)
        if not share:
            slots = moe.Slots(
                *(checkpoint_name(s, "moe_slots") for s in slots))
        if cfg.seq_aux:  # every sequence's own counts, over all E experts
            per_sequence = moe.sequence_load(own_index, cfg.n_experts, B)
            load = per_sequence.sum(axis=0) if share else slots.group_sizes
            aux_loss = moe.sequence_balancing_loss(
                probs.reshape(B, T, -1), per_sequence)
        else:
            load = (moe.expert_load(own_index, cfg.n_experts) if share
                    else slots.group_sizes)
        if axis is not None:
            # over the mesh's batch: every device has as many tokens and
            # sequences, so a mean of the devices' means is the batch's
            load = jax.lax.psum(load, axis)
            if cfg.seq_aux:
                aux_loss = jax.lax.pmean(aux_loss, axis)
            else:  # one row: the mean of the router's scores over the mesh
                probs = jax.lax.pmean(probs.mean(axis=0), axis)[None]
        if not cfg.seq_aux:
            aux_loss = moe.load_balancing_loss(probs, load)
        z_loss = moe.router_z_loss(logits)
        if axis is not None:
            z_loss = jax.lax.pmean(z_loss, axis)
        readings = {
            "aux_loss": aux_loss,
            "z_loss": z_loss,
            "expert_load": load,
            "expert_index": own_index,
        }
        if share:
            held_rows = slots.group_sizes.sum()
            readings["held_slots"] = jnp.logical_and(
                index >= first, index < first + n_held).sum(dtype=jnp.int32)
            readings["dropped_slots"] = readings["held_slots"] - held_rows
            if axis is not None:  # a device each
                readings["held_slots"] = readings["held_slots"][None]
                readings["dropped_slots"] = readings["dropped_slots"][None]

    if share:  # names its own operations as below, a chunk of rows at a time
        ways = 1 if axis is None else jax.lax.axis_size(axis)
        out = moe.experts_of_share(
            tokens, blk.get("w_gate"), blk["w_up"], blk["w_down"], weights,
            slots, impl=impl,
            chunk=_buffer_rows(cfg, tokens.shape[0], B * ways, ways))
        if axis is not None:
            with jax.named_scope("moe_scatter"):
                out = jax.lax.psum_scatter(
                    out, axis, scatter_dimension=0, tiled=True)
        return out.reshape(B, T, d), readings
    with jax.named_scope("moe_dispatch"):
        xs = moe.dispatch(tokens, slots.order, slots.inverse)
    with jax.named_scope("moe_experts"):
        gmm = partial(moe.grouped_matmul, group_sizes=slots.group_sizes,
                      impl=impl)
        if "w_gate" in blk:
            gate = jax.nn.silu(
                checkpoint_name(gmm(xs, blk["w_gate"]), "moe_gate"))
            hidden = gate * checkpoint_name(gmm(xs, blk["w_up"]), "moe_up")
        else:
            hidden = moe.relu2(checkpoint_name(gmm(xs, blk["w_up"]), "moe_up"))
    # names its own operations `moe_experts` and `moe_combine`, backward too
    out = moe.project_and_combine(hidden, blk["w_down"], weights, slots,
                                  impl=impl)
    return out.reshape(B, T, d), readings


def _causal_taps(u, w):
    """The causal convolution per channel of `u` [B, T, C] with taps `w`
    [taps, C], `c_t = sum_i w_i u_{t - taps + 1 + i}`, `u` zero before the
    sequence: `taps` shifted multiply-adds in u's dtype."""
    taps = w.shape[0]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[i] * jax.lax.dynamic_slice_in_dim(padded, i, u.shape[1], 1)
               for i in range(taps))


def _short_conv(x, blk, cfg: TransformerConfig):
    """The gated short convolution on `x` [B, T, d] (LFM2's operator): the
    normed input projected to three streams B, C, X; `u = B * X`; a causal
    convolution of `conv_taps` taps per channel, `c_t = sum_i w_i
    u_{t - taps + 1 + i}` with u zero before the sequence; `(C * c) W_out`.
    The convolution is `conv_taps` shifted multiply-adds in the compute
    dtype, which XLA fuses with the gates into one pass over [B, T, d]."""
    dt = cfg.dtype
    with jax.named_scope("conv_in"):
        y = fused_rmsnorm(x, blk["conv_norm"], eps=cfg.norm_eps)
        b, c, xs = jnp.split(
            checkpoint_name(y @ blk["conv_in"].astype(dt), "conv_in"), 3,
            axis=-1)
    with jax.named_scope("conv_gate"):
        gated = c * _causal_taps(b * xs, blk["conv_w"].astype(dt))
    with jax.named_scope("conv_out"):
        return gated @ blk["conv_out"].astype(dt)


def _gated_norm_kernels(cfg: TransformerConfig,
                        T: Optional[int] = None) -> bool:
    """Whether the Mamba-2 mixer's gated norm runs as `ops/mamba_passes.py`'s
    kernels: the operators resolve to Pallas and the shape tiles."""
    return _kernel_impl(cfg) == "pallas" and not norm_untiled(
        cfg.mamba_inner, cfg.ssm_groups, T)


def _mamba_mixer(x, blk, cfg: TransformerConfig):
    """The Mamba-2 mixer on `x` [B, T, d] (arXiv:2405.21060, as
    `nemotron_h` holds it): `[z | xBC | dt] = norm(x) W_in`; a causal
    convolution of `mamba_conv_taps` taps per channel over x, B and C with a
    bias, then silu; `dt = softplus(dt + dt_bias)` and `A = -exp(A_log)` a
    head, in float32; the scan (`ops/ssd.py`); the gate before the norm,
    `GroupRMSNorm(y * silu(z))` over `ssm_groups` groups with one learned
    scale; `W_out`."""
    B, T, d = x.shape
    H, P, G, N = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    inner, dt_ = cfg.mamba_inner, cfg.dtype
    with jax.named_scope("mamba_in"):
        u = fused_rmsnorm(x, blk["mixer_norm"], eps=cfg.norm_eps)
        z, xbc, dt = jnp.split(
            checkpoint_name(u @ blk["w_in"].astype(dt_), "mamba_in"),
            (inner, inner + cfg.mamba_conv_dim), axis=-1)
    with jax.named_scope("mamba_conv"):
        xs, b, c = causal_conv_silu(
            xbc, blk["conv_w"].astype(dt_), blk["conv_b"],
            splits=(inner, G * N, G * N), impl=_kernel_impl(cfg))
    with jax.named_scope("ssd"):
        step = jax.nn.softplus(
            dt.astype(jnp.float32) + blk["dt_bias"].astype(jnp.float32))
        y = checkpoint_name(ssd(
            xs.reshape(B, T, H, P), step,
            -jnp.exp(blk["A_log"].astype(jnp.float32)),
            b.reshape(B, T, G, N), c.reshape(B, T, G, N), blk["D"],
            chunk=cfg.ssd_chunk, impl=_kernel_impl(cfg)),
            "ssd_out").reshape(B, T, inner)
    with jax.named_scope("mamba_norm"):
        if _gated_norm_kernels(cfg, T):
            y = gated_group_rmsnorm(y, z, blk["norm"], G, cfg.norm_eps,
                                    impl=_kernel_impl(cfg))
        else:
            # the `jax.numpy` line stands here and not behind the call: the
            # cell's comparison is tried on this text with the gate taken
            # out (tests/chipbench_tests/test_chipbench_nemotron_h.py)
            gated = (y * jax.nn.silu(z)).reshape(B, T, G, inner // G)
            y = fused_rmsnorm(gated, blk["norm"].reshape(G, inner // G),
                              eps=cfg.norm_eps).reshape(B, T, inner)
    with jax.named_scope("mamba_out"):
        return y @ blk["w_out"].astype(dt_)


def _mamba1_mixer(x, blk, cfg: TransformerConfig):
    """(the Mamba-1 mixer on `x` [B, T, d], the memory an emitting layer
    hands on: the scan's output `s` [B, T, inner] in the compute dtype)
    (arXiv:2312.00752): `[u | z] = norm(x) W_in`; a causal convolution of `mamba1_conv_taps` taps a channel over
    `u` with a bias, then silu; `[r | B | C] = u W_x`, `r` of `dt_rank`
    columns, `B` and `C` of `mamba1_state`; `dt = softplus(r W_dt +
    dt_bias)` and `A = -exp(A_log)` a channel and state, in float32; the
    scan (`ops/selective_scan.py`: its kernels where the step's operators
    resolve to Pallas and the shape tiles, `jax.numpy` on the CPU and
    elsewhere), whose output `s` holds the skip `D u`; `(s * silu(z))
    W_out`."""
    N, R = cfg.mamba1_state, cfg.dt_rank
    dt_, f32 = cfg.dtype, jnp.float32
    with jax.named_scope("mamba1_in"):
        y = _block_norm(x, blk, "mixer_norm", cfg)
        u, z = jnp.split(
            checkpoint_name(y @ blk["w_in"].astype(dt_), "mamba1_in"), 2,
            axis=-1)
    with jax.named_scope("mamba1_conv"):
        u = jax.nn.silu(_causal_taps(u, blk["conv_w"].astype(dt_))
                        + blk["conv_b"].astype(dt_))
        r, b, c = jnp.split(u @ blk["w_x"].astype(dt_), (R, R + N), axis=-1)
        step = jax.nn.softplus((r @ blk["w_dt"].astype(dt_)).astype(f32)
                               + blk["dt_bias"].astype(f32))
    # names its own operations `selective_scan`, backward too
    s, _ = selective_scan(u, step, -jnp.exp(blk["A_log"].astype(f32)), b, c,
                          blk["D"], chunk=cfg.scan_chunk,
                          impl=_kernel_impl(cfg))
    s = checkpoint_name(s.astype(dt_), "scan_out")
    with jax.named_scope("mamba1_out"):
        gated = s * jax.nn.silu(z)
        return gated @ blk["w_out"].astype(dt_), _scan_memory(s, gated)


def _scan_memory(s, gated):
    """What an emitting Mamba-1 layer hands the gated memory units: the
    scan's output `s`, not the one the gate `z` has multiplied. Under a
    name of its own: a test hands on the other to show what the comparison
    reads then."""
    return s


def diff_lambda_init(depth):
    """Differential attention's `lam0` at a layer's depth in its stack
    (arXiv:2410.05258): 0.8 - 0.6 exp(-0.3 depth)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * depth)


def _diff_lambda(blk, depth):
    """(lam, lam0) of a differential layer, float32 scalars: `lam =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lam0`. Under a name of its own: a
    test takes the learned part away to show what the comparison reads
    then."""
    f32 = jnp.float32
    lam0 = diff_lambda_init(depth.astype(f32))
    dots = [jnp.sum(blk["lam_q" + i].astype(f32) * blk["lam_k" + i].astype(f32))
            for i in "12"]
    return jnp.exp(dots[0]) - jnp.exp(dots[1]) + lam0, lam0


def _pair_heads(x):
    """The heads of `x` [B, T, H, w] with the even ones first, then the odd
    ones: a differential pair's first and second softmax read head 2 i and
    head 2 i + 1, and the kernel takes the two as the halves of one
    grouped-query problem."""
    return jnp.concatenate([x[:, :, 0::2], x[:, :, 1::2]], axis=2)


def _diff_attention_layer(x, blk, cfg: TransformerConfig, site: "_Site", *,
                          op: "_DiffAttention"):
    """(x + differential attention(norm(x)), {diff_lambda, and where the
    record emits, attn_kv}) (arXiv:2410.05258, as SambaY holds it,
    arXiv:2507.06607). `n_heads` query heads and `n_kv_heads` key and value
    heads `head_dim` wide, each projection with a bias under `attn_bias`,
    no rotation. Heads pair, even with odd: pair i has `q1 = q_(2i)`, `q2 =
    q_(2i+1)` and reads the key pair j = i // (pairs a key pair), `k1 =
    k_(2j)`, `k2 = k_(2j+1)`, `V = [v_(2j) | v_(2j+1)]`, twice `head_dim`
    wide. `a1 = softmax(q1 k1^T / sqrt(head_dim)) V`, `a2` likewise, causal
    and under the record's window: the two as ONE call of the attention
    kernel, the first softmaxes' heads and then the second's over keys laid
    out the same way and V twice. `o = (1 - lam0) RMSNorm(a1 - lam a2)` over
    the pair's width with one learned scale a layer; `W_o`. A cross layer
    has `W_q` and `W_o` alone and reads the keys and values another layer
    emitted (`site.shared["attn_kv"]`: the keys paired already, V once)."""
    B, T, d = x.shape
    h, hk, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    dt = cfg.dtype

    def projected(y, name):
        out = checkpoint_name(y @ blk["w" + name].astype(dt), "attn_qkv")
        if "b" + name in blk:
            out = out + blk["b" + name].astype(dt)
        return out

    with jax.named_scope("attn_qkv"):
        y = _block_norm(x, blk, "attn_norm", cfg)
        q = _pair_heads(projected(y, "q").reshape(B, T, h, dh))
        if op.cross:
            k, v = (a.astype(dt) for a in site.shared["attn_kv"])
        else:
            k = _pair_heads(projected(y, "k").reshape(B, T, hk, dh))
            v = projected(y, "v").reshape(B, T, hk // 2, 2 * dh)
    scope = (jax.named_scope("cross_attention") if op.cross
             else contextlib.nullcontext())
    with scope, jax.named_scope("attention"):
        o = _attention(q, k, jnp.concatenate([v, v], axis=2), cfg, None, 1,
                       site.mesh, site.keep_ctx, window=op.window(cfg))
    with jax.named_scope("diff_norm"):
        lam, lam0 = _diff_lambda(blk, site.depth)
        f32 = jnp.float32
        apart = o[:, :, :h // 2].astype(f32) - lam * o[:, :, h // 2:].astype(f32)
        o = ((1.0 - lam0) * fused_rmsnorm(
            apart, blk["diff_norm"], eps=cfg.norm_eps)).astype(dt)
    with jax.named_scope("attn_out"):
        out = o.reshape(B, T, h * dh) @ blk["wo"].astype(dt)
        if "bo" in blk:
            out = out + blk["bo"].astype(dt)
        x = checkpoint_name(x + out, "attn_res")
    made = {"diff_lambda": lam}
    if op.emits:  # the keys paired already, V once
        made["attn_kv"] = (k, v)
    return x, made


def _gmu(x, blk, cfg: TransformerConfig, memory):
    """The gated memory unit on `x` [B, T, d] (arXiv:2507.06607): `(silu(
    norm(x) W_in) * m) W_out`, `m` [B, T, inner] the memory another layer
    emitted."""
    dt = cfg.dtype
    y = _block_norm(x, blk, "gmu_norm", cfg)
    gate = jax.nn.silu(
        checkpoint_name(y @ blk["gmu_in"].astype(dt), "gmu_in"))
    return (gate * memory.astype(dt)) @ blk["gmu_out"].astype(dt)


def _eva_attention_layer(x, blk, cfg: TransformerConfig, site: "_Site"):
    """(x + EVA attention(norm(x)), {eva_remote_mass, eva_chunk_entropy})
    (arXiv:2302.04542, as EvaByte holds it): `n_heads` heads of q, k and v
    `head_dim` wide, q and k rotated over all their columns at `rope_theta`;
    `ops/eva.py` has the attention (the chunks' summaries under the layer's
    `eva_phi` and `eva_mu`, made from the rotated keys; the window's own
    causal softmax; the staircase over the summaries; their join), which
    names its own operations `eva_summaries`, `eva_window`, `eva_stair` and
    `eva_join`, backward too; `W_o`."""
    B, T, d = x.shape
    h, dh = cfg.n_heads, cfg.head_dim
    dt = cfg.dtype
    impl = _kernel_impl(cfg)
    if impl == "pallas" and site.mesh is not None and site.mesh.size > 1:
        raise NotImplementedError(
            "the kernels of EVA attention are not mapped over a mesh of "
            f"{site.mesh.size} devices yet")
    with jax.named_scope("attn_qkv"):
        y = _block_norm(x, blk, "attn_norm", cfg)
        q, k, v = (checkpoint_name(y @ blk[name].astype(dt), "attn_qkv"
                                   ).reshape(B, T, h, dh)
                   for name in ("wq", "wk", "wv"))
        q = _rope(q, site.positions, cfg.rope_theta)
        k = _rope(k, site.positions, cfg.rope_theta)
    o, readings = eva_attention(
        q, k, v, blk["eva_phi"], blk["eva_mu"], window=cfg.eva_window,
        chunk=cfg.eva_chunk, impl=impl, keep_ctx=site.keep_ctx)
    with jax.named_scope("attn_out"):
        out = o.reshape(B, T, h * dh) @ blk["wo"].astype(dt)
        return checkpoint_name(x + out, "attn_res"), readings


def eva_keys_per_query(seq_len: int, window: int, chunk: int) -> float:
    """The keys a query of EVA attention sees, on average: the causal half
    of its window, `(window + 1) / 2`, and the `window / chunk` summaries of
    each of the `(seq_len / window - 1) / 2` windows before it; a sequence no
    longer than a window is plain causal attention."""
    if seq_len <= window:
        return (seq_len + 1) / 2
    return (window + 1) / 2 + window // chunk * (seq_len // window - 1) / 2


def _block_diffusion_attention_layer(x, blk, cfg: TransformerConfig,
                                     site: "_Site", *,
                                     op: "_BlockDiffusionAttention"):
    """x + block-diffusion attention(norm(x)) on the doubled stream `x`
    [B, 2 L, d], the noisy half first: plain attention's projections,
    QK-norm and RoPE (`_qkv`; `site.positions` holds `0 .. L - 1` twice), and
    `ops/block_diffusion.py`'s attention, which names its own operations
    `bd_stair`, `bd_own_block` and `bd_join`, backward too; `W_o`."""
    B, R, d = x.shape
    h, dh = op.heads(cfg), cfg.head_dim
    dt = cfg.dtype
    impl = _kernel_impl(cfg)
    if site.mesh is not None and site.mesh.size > 1:
        raise NotImplementedError(
            "block-diffusion attention is not mapped over a mesh of "
            f"{site.mesh.size} devices yet: the two halves of a sequence "
            "and its clean keys lie on one device")
    _, q, k, v = _qkv(x, blk, site.positions, cfg, op)
    with jax.named_scope("bd_attention"):
        o = block_diffusion_attention(
            q, k, v, block=cfg.diffusion_block, impl=impl,
            keep_ctx=site.keep_ctx)
        tracing.count("train.bd_own_join_calls_kernels"
                      if _bd_own_join_kernels(cfg, R // 2)
                      else "train.bd_own_join_calls_numpy")
    with jax.named_scope("attn_out"):
        out = o.reshape(B, R, h * dh) @ blk["wo"].astype(dt)
        return checkpoint_name(x + out, "attn_res")


def _bd_own_join_kernels(cfg: TransformerConfig, L: int) -> bool:
    """Whether a block-diffusion layer's own block and join run as
    `ops/block_diffusion.py`'s kernels (`bd_own_join_fwd`,
    `bd_own_join_bwd`) on halves of `L` rows: the operators resolve to
    Pallas and the shape tiles."""
    op = _OPERATORS["block_diffusion_attention"]
    return _kernel_impl(cfg) == "pallas" and not own_join_untiled(
        L, op.heads(cfg), op.kv_heads(cfg), cfg.head_dim,
        cfg.diffusion_block, _item(cfg))


def _unit_length(x, eps: float = 1e-6):
    """Every head of `x` [..., dk] over its own length, in float32."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(
        jnp.sum(x32 * x32, axis=-1, keepdims=True) + eps)).astype(x.dtype)


def _kda_conv_kernels(cfg: TransformerConfig,
                      T: Optional[int] = None) -> bool:
    """Whether the KDA mixer's short convolutions run as
    `ops/mamba_passes.py`'s kernels (`kda_conv_fwd`, `kda_conv_bwd`): the
    operators resolve to Pallas and the shape tiles."""
    return _kernel_impl(cfg) == "pallas" and not _kda_conv_untiled(cfg, T)


def _kda_conv_untiled(cfg: TransformerConfig, T: Optional[int] = None):
    return conv_untiled(
        cfg.kda_conv_taps, (_OPERATORS["kda"].heads(cfg) * cfg.kda_head_dim,),
        T, cfg.kda_head_dim)


def _kda_out_norm_kernels(cfg: TransformerConfig,
                          T: Optional[int] = None) -> bool:
    """Whether the KDA mixer's output norm and gate run as
    `ops/mamba_passes.py`'s kernels (`kda_out_norm_fwd`,
    `kda_out_norm_bwd`): the operators resolve to Pallas and the shape
    tiles."""
    return _kernel_impl(cfg) == "pallas" and not _kda_out_norm_untiled(
        cfg, T)


def _kda_out_norm_untiled(cfg: TransformerConfig, T: Optional[int] = None):
    H = _OPERATORS["kda"].heads(cfg)
    return norm_untiled(H * cfg.kda_head_dim, H, T, "kda_out_norm")


def _calls_said(before) -> str:
    """What a trace added to the counts of the KDA mixers' short
    convolutions (three calls a mixer), of their output norms and gates
    (one), of the block-diffusion layers' own blocks and joins (one a
    layer) and of the Mamba-2 mixers' scans (`ops/ssd.py` counts them: one a
    mixer and trace of it) since the counters read `before`, by the path
    each call took,
    for the step's log line; nothing where no such layer was traced."""
    now = tracing.counters()
    said = ""
    for what, name in (("KDA's short convolutions", "kda_conv"),
                       ("KDA's output norms and gates", "kda_out_norm"),
                       ("block diffusion's own blocks and joins",
                        "bd_own_join"),
                       ("Mamba-2's scans", "ssd")):
        kernels, numpy = (
            now.get(counter, 0) - before.get(counter, 0)
            for counter in (f"train.{name}_calls_kernels",
                            f"train.{name}_calls_numpy"))
        if kernels + numpy:
            said += ("; %s: %d calls by the kernels %s_fwd and "
                     "%s_bwd, %d by jax.numpy" % (
                         what, kernels, name, name, numpy))
    return said


def _kda_mixer(x, blk, cfg: TransformerConfig):
    """(the KDA mixer on `x` [B, T, d], its readings {kda_log_decay_min,
    kda_beta_mean}) over the `H` heads the layer holds (arXiv:2510.26692):
    `q`, `k`, `v` = silu(conv(norm(x) W)), a causal convolution of
    `kda_conv_taps` taps a channel without a bias, q and k then of unit
    length a head; the log decay a key channel `g = -exp(A_log)
    softplus(W_f2 (W_f1 u) + dt_bias)` and `beta = 2 sigmoid(W_b u)` (without
    `kda_allow_neg_eigval`, `sigmoid(W_b u)`) a head, in float32; the recurrence in its chunked form (`ops/kda.py`);
    `RMSNorm(o)` over each head's width with one learned scale, times
    `sigmoid(W_g2 (W_g1 u) + b_g)`; `W_o`. The three narrow products
    (`W_f1`, `W_g1`, `W_b`) are one matmul. Where the operators are Pallas's
    and the shapes tile, the short convolutions and the output norm with its
    gate are `ops/mamba_passes.py`'s one-pass kernels on `[B, T, H dk]`
    (`_kda_conv_kernels`, `_kda_out_norm_kernels`); elsewhere the
    `jax.numpy` lines below."""
    B, T, d = x.shape
    H, dk = _OPERATORS["kda"].heads(cfg), cfg.kda_head_dim
    rank = blk["kda_f1"].shape[-1]
    dt = cfg.dtype
    with jax.named_scope("kda_in"):
        u = fused_rmsnorm(x, blk["kda_norm"], eps=cfg.norm_eps)
        q, k, v = (checkpoint_name(u @ blk[name].astype(dt), "kda_qkv")
                   for name in ("kda_q", "kda_k", "kda_v"))
        narrow = u @ jnp.concatenate(
            [blk[name].astype(dt) for name in ("kda_f1", "kda_g1", "kda_b")],
            axis=-1)
        f_low, g_low, b_low = jnp.split(narrow, (rank, 2 * rank), axis=-1)
    with jax.named_scope("kda_conv"):
        taps = blk["kda_conv"].astype(dt)  # [3, taps, H dk]: q's, k's, v's
        if _kda_conv_kernels(cfg, T):
            # a stream's taps, silu and (q, k) unit length a head: one read
            # and one write of it, a call a stream (`ops/mamba_passes.py`)
            q, k, v = (causal_conv_silu(
                s, taps[i], unit=dk * (i < 2), name="kda_conv",
                impl=_kernel_impl(cfg)).reshape(B, T, H, dk)
                for i, s in enumerate((q, k, v)))
            tracing.count("train.kda_conv_calls_kernels", 3)
        else:
            # the `jax.numpy` lines stand here and not behind the call: the
            # cells' comparisons are tried on this text with the taps or the
            # unit length taken out (tests/chipbench_tests/
            # test_chipbench_solar_open2.py, test_chipbench_kimi_linear.py)
            q, k, v = (jax.nn.silu(_causal_taps(s, taps[i])
                                   ).reshape(B, T, H, dk)
                       for i, s in enumerate((q, k, v)))
            q, k = _unit_length(q), _unit_length(k)
            _log_pass("kda_conv", _kernel_impl(cfg) == "pallas",
                      _kda_conv_untiled(cfg, T), (B, T, H * dk),
                      (cfg.kda_conv_taps, (H * dk,), dk), jnp.dtype(dt).name)
            tracing.count("train.kda_conv_calls_numpy", 3)
    with jax.named_scope("kda_gates"):
        f32 = jnp.float32
        log_decay = -jnp.exp(blk["kda_A_log"].astype(f32))[:, None] * (
            jax.nn.softplus(
                (f_low @ blk["kda_f2"].astype(dt)).astype(f32)
                + blk["kda_dt_bias"].astype(f32)).reshape(B, T, H, dk))
        beta = jax.nn.sigmoid(b_low.astype(f32))
        if cfg.kda_allow_neg_eigval:
            beta = 2.0 * beta
        gate = g_low @ blk["kda_g2"].astype(dt)
        out_kernels = _kda_out_norm_kernels(cfg, T)
        if not out_kernels:  # else `kda_out`'s kernels make the sigmoid
            gate = jax.nn.sigmoid(
                gate.astype(f32) + blk["kda_g_bias"].astype(f32)).astype(dt)
    # names its own operations `kda_chunk`, `kda_state` and `kda_out`
    o, _, log_decay_min = kda(q, k, v, log_decay, beta, chunk=cfg.kda_chunk,
                              impl=_kernel_impl(cfg))
    with jax.named_scope("kda_out"):
        if out_kernels:
            # the norm a head, its scale and the gate: one read of o and of
            # the gate's pre-activation and one write, on `[B, T, H dk]` as
            # `kda` and the matmul leave them (`ops/mamba_passes.py`)
            o = group_rmsnorm_gated(
                o.reshape(B, T, H * dk), gate, blk["kda_g_bias"],
                blk["kda_out_norm"], H, cfg.norm_eps, impl=_kernel_impl(cfg))
            tracing.count("train.kda_out_norm_calls_kernels")
        else:
            # the `jax.numpy` lines stand here, as `kda_conv`'s do
            o = fused_rmsnorm(o, blk["kda_out_norm"], eps=cfg.norm_eps)
            o = o.reshape(B, T, H * dk) * gate
            _log_pass("kda_out_norm", _kernel_impl(cfg) == "pallas",
                      _kda_out_norm_untiled(cfg, T), (B, T, H * dk), (H,),
                      jnp.dtype(dt).name)
            tracing.count("train.kda_out_norm_calls_numpy")
        y = o @ blk["kda_o"].astype(dt)
    return y, {"kda_log_decay_min": log_decay_min,
               "kda_beta_mean": beta.mean()}


def _feed_forward(y, blk, dt, names, prefix: str = "w"):
    """A dense feed-forward on normed `y`: `(silu(y W_gate) * y W_up)
    W_down`, or without a gate's weights `relu(y W_up)^2 W_down`. `names` is
    the products' `checkpoint_name`s, `prefix` "w" or, for the shared
    experts' weights, "ws"."""
    up = prefix + "_up"
    if prefix + "_gate" in blk:
        gate = jax.nn.silu(checkpoint_name(
            y @ blk[prefix + "_gate"].astype(dt), names[0]))
        hidden = gate * checkpoint_name(y @ blk[up].astype(dt), names[1])
    else:
        hidden = moe.relu2(checkpoint_name(y @ blk[up].astype(dt), names[1]))
    return hidden @ blk[prefix + "_down"].astype(dt)


def keys_per_query(seq_len: int, window: Optional[int] = None) -> float:
    """The keys a query of a causal sequence of `seq_len` sees, on average:
    `(seq_len + 1) / 2`, and under a window that is shorter than the
    sequence the band's pairs over its queries (the first `window` queries
    see a triangle, every later one `window` keys)."""
    if not window or window >= seq_len:
        return (seq_len + 1) / 2
    return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len


def _scan_bytes_per_token(cfg: TransformerConfig) -> int:
    """Bytes a token that the Mamba-2 mixer's scan holds in HBM in its
    backward, by the path `ssd` takes (`ops/ssd.py`); the Mamba-1 mixer's
    (`ops/selective_scan.py`: a chunk's steps made again, the chunks'
    entering states) is in `_Mamba1.holds`. `jax.numpy`: the [H, Q, Q] arrays
    of a chunk, Q values a token and head: the decays and their gradient in
    float32, the masked scores and theirs in the compute dtype and in
    float32. The kernels keep those in VMEM. What they leave is each
    chunk's entering state, `H P N / Q` float32 values a token, and dt and
    cum with their cotangents: as columns `[b, G, T, R]` float32, which lie
    in HBM at a tile's 128 lanes a group (the four the kernels read and
    write and three that the transposes from and to `[b, T, H]` make),
    and as rows and plain `[b, T, H]` arrays, two `H` wide all told. Where
    a group's heads are taken in tiles (`head_tile`) every tile is such a
    group, and each writes a `dB` and a `dC` of its own."""
    item = _item(cfg)
    H, Q, G = cfg.mamba_heads, cfg.ssd_chunk, cfg.ssm_groups
    N, P = cfg.ssm_state, cfg.mamba_head_dim
    if _kernel_impl(cfg) != "pallas" or scan_untiled(Q, N, H // G, P):
        return H * Q * (4 * 4 + 2 * item)
    tiles = H // head_tile(Q, N, H // G, P, item)  # of all the groups
    own = 2 * tiles * N * 4 if tiles > G else 0  # float32, to be summed
    return 4 * (cfg.mamba_inner * N // Q + 7 * tiles * 128 + 2 * H) + own


# ------------------------------------------------- the kinds of sublayer

def _dense(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def _dt_bias(key, shape, cfg: "TransformerConfig"):
    """A step size's bias as the mixers draw it: `softplus(bias)` is
    log-uniform in `mamba_dt_init`'s range and no less than its floor."""
    dt_min, dt_max, dt_floor = cfg.mamba_dt_init
    dt = jnp.maximum(dt_floor, jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(dt_min), math.log(dt_max))))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse


def _tile_lanes(width: int) -> int:
    """`width` as HBM tiles a minor dimension: whole tiles of 128 lanes."""
    return -(-width // 128) * 128


def _item(cfg: TransformerConfig) -> int:
    return jnp.dtype(cfg.dtype).itemsize


class _Site(NamedTuple):
    """Where a block runs: what a sublayer's forward takes beside the
    stream, the block's weights and the configuration."""
    positions: Any  # [B, T] integers; under a sequence axis the global ones
    bias: Any = None  # [E]: a routed feed-forward's selection bias
    seq_axis: Optional[str] = None
    seq_size: int = 1
    mesh: Any = None
    # the attention kernel names its backward's residuals `attn_ctx`
    keep_ctx: bool = False
    # {name: value} of what the sublayers `reads`, as earlier layers emitted
    # it (float32 where several scanned layers read one value: their
    # cotangents are summed in it)
    shared: Any = None
    # the layer's depth in the stack it was cut from, a float32 scalar, for
    # a record that `reads_depth`
    depth: Any = None


class Reading(NamedTuple):
    """One reading a sublayer's `forward` makes a layer, and what
    `transformer_loss_and_readings` makes of the layers' (`_settled`)."""
    name: str
    # how the layers' [L, ...] are joined: a key of `_OVER_LAYERS`
    over_layers: str = "stacked"
    of: Optional[str] = None  # not `forward`'s: read off this one, stacked
    # what it adds to the loss: True, itself; a field of the configuration,
    # that coefficient times it; None, nothing. A record's terms are added
    # while any of its coefficients is not 0
    adds: Union[None, bool, str] = None
    step: bool = True  # the train step reports it (`_STEP_READINGS`)
    # what a report makes of the steps' (`_STEP_ACCOUNT`): None, it shows the
    # last step's; a counter's name, it sums the steps' into it too; True, it
    # shows none and hands them, stacked [S, L, ...], to its record's
    # `account(static, stacked)`, a `tracing.Account`'s `fold`
    over_steps: Union[None, bool, str] = None


def _fullest_over_mean(load):
    load = load.astype(jnp.float32)
    return load.max(axis=-1) / load.mean(axis=-1)


# a reading of the L layers that make it, [L, ...], as the model's
_OVER_LAYERS = {
    "stacked": lambda x, cfg: x,
    "mean": lambda x, cfg: x.mean(),
    "min": lambda x, cfg: x.min(),
    "max": lambda x, cfg: x.max(),
    # the mean, or with `seq_aux` the sum, as the published code adds each
    # layer's own
    "sum under seq_aux": lambda x, cfg: (
        jnp.sum(x) if cfg.seq_aux else jnp.mean(x)),
    # [L, n], a device each: a layer's fullest device over its mean, [L]
    "fullest over mean": lambda x, cfg: _fullest_over_mean(x),
}


class Sublayer:
    """One kind of sublayer, `x -> x + f(norm(x))`: an operator (a record of
    `_OPERATORS`) or a feed-forward (of `_FEED_FORWARDS`). It states once
    what `_blocks_init`, `param_shardings`, `_own_weights`, `_block`, the
    rule of what a rematerialised block keeps (`saved_activations`),
    `transformer_loss_and_readings`, the train step and `flops_per_token`
    read of it; none of them names a kind.

    Its leaves are three statements that `tests/test_layer_kinds.py` holds
    to one another: `init` makes them, `axes` has exactly their keys, and
    `matmuls` are among them. Widths are elements of the compute dtype a
    token; operations are forward ones a token."""

    # the leaves that are weights of plain matmuls, in the order
    # `_own_weights` takes them
    matmuls: Tuple[str, ...] = ()
    # the weights `ops/moe.py` takes as they are: counted in `params`,
    # never handed a buffer
    moe_weights: Tuple[str, ...] = ()
    # the `checkpoint_name`s `forward` may make: every one is in
    # `_SAVE_ORDER`, or the module does not import
    names: Tuple[str, ...] = ()
    # the readings `forward` may make, each with how the layers' are joined
    # and what it adds to the loss
    readings: Tuple[Reading, ...] = ()
    # why `forward` cannot be mapped over a sequence axis; None: it can
    no_sequence_axis: Optional[str] = None
    # an operator that `heads_held` makes hold a share of its heads
    takes_heads_held: bool = False
    # a sublayer that `post_norm` gives a norm on its output
    takes_post_norm: bool = False
    # a sublayer whose norm `layer_norm` makes a LayerNorm with a bias
    takes_layer_norm: bool = False
    # the named values `forward` makes for later layers (in what it returns
    # beside the stream, under these names) and those it takes of earlier
    # ones (`site.shared`); `carried` has an emitted value's width
    emits: Tuple[str, ...] = ()
    reads: Tuple[str, ...] = ()
    # `forward` takes the layer's depth (`site.depth`)
    reads_depth: bool = False
    # a sublayer whose RMSNorm `norm_unit_offset` scales by 1 + g
    takes_unit_offset: bool = False
    # a sublayer that adds `residual_multiplier` times its output and, where
    # it has scores, scales them by `attention_multiplier`
    takes_multipliers: bool = False

    def check(self, cfg: TransformerConfig) -> None:
        """Raises `ValueError` where `cfg` is none the record can run
        (`cfg.layers` asks every record of the stack)."""

    def carried(self, cfg: TransformerConfig) -> Dict[str, int]:
        """{name: width} of what a layer `emits`, in elements of the
        compute dtype a token."""
        return {}

    def init(self, key, cfg: TransformerConfig, L: int) -> Dict[str, Any]:
        """`L` stacked layers' leaves, float32, from the layer's key. The
        key is split in seven for every kind alike (the operator's draws are
        the first four, the feed-forward's the last three) and folded with 7,
        8 and 9 for the router, the shared experts and the heads' gate: the
        seeded values are the checkpoint's format."""
        raise NotImplementedError

    def axes(self, cfg: TransformerConfig) -> Dict[str, Tuple]:
        """{leaf: its logical axes}, for `default_transformer_rules`."""
        raise NotImplementedError

    def forward(self, x, blk, cfg: TransformerConfig, site: _Site):
        """(the stream after the sublayer, residual added; {name: value} of
        the record's `readings`, None if it states none), under the
        sublayer's `jax.named_scope`s: they name the step's device work in a profiler
        trace (docs/observability.md, "Device scopes") and are metadata
        only. Optional leaves are found by presence: no `w_gate_attn`, no
        gate; no `w_gate` or `ws_gate`, `relu2`; no `ws_up`, no shared
        experts."""
        raise NotImplementedError

    def widths(self, cfg: TransformerConfig) -> Dict[str, int]:
        """{name: width} of the `names` a layer under `cfg` makes."""
        raise NotImplementedError

    def params(self, cfg: TransformerConfig) -> int:
        """A layer's `matmuls` and `moe_weights`, in elements."""
        raise NotImplementedError

    def holds(self, cfg: TransformerConfig) -> int:
        """What its backward holds at once beside the named values and its
        normed input (`_KindTerms.block`)."""
        raise NotImplementedError

    def residuals(self, cfg: TransformerConfig) -> int:
        """What its forward leaves for its own backward beside its names
        and its normed input, which a walked layer still holds while the
        sublayers AFTER it run their backward (`_terms`): none, for the
        records whose plans showed none."""
        return 0

    def flops(self, cfg: TransformerConfig, seq_len: int):
        """(matmul operations, causal attention's): of plain matmuls alone,
        two a parameter."""
        return 2 * self.params(cfg), 0


class _PlainAttention(Sublayer):
    """Attention over `n_kv_heads` key-value heads `head_dim` wide, whole
    (`full_attention`) or under `sliding_window` (`sliding_attention`): two
    records over the same leaves and `_attention_layer`. The query heads'
    number, the rotary recipe and the window are the record's."""

    matmuls = ("wq", "wk", "wv", "wo", "w_gate_attn")
    names = ("attn_ctx", "attn_res", "attn_qkv")
    takes_heads_held = True
    takes_post_norm = True
    takes_multipliers = True

    def __init__(self, sliding: bool):
        self.sliding = sliding
        if sliding:
            self.no_sequence_axis = (
                "attention under a window is not mapped over a sequence "
                "axis: ring attention has no band")

    def all_heads(self, cfg) -> int:
        """The operator's query heads, on however many chips."""
        if self.sliding:
            return cfg.n_heads_sliding or cfg.n_heads
        return cfg.n_heads

    def heads(self, cfg) -> int:
        """The query heads a layer holds: `heads_held`'s, else all."""
        return cfg.heads_held[1] if cfg.heads_held else self.all_heads(cfg)

    def kv_heads(self, cfg) -> int:
        """The key-value heads a layer holds: those its query heads read.
        A share is whole groups of query heads, or part of one group."""
        if not cfg.heads_held:
            return cfg.kv_heads
        first, n = cfg.heads_held
        group = self.all_heads(cfg) // cfg.kv_heads
        whole = first % group == 0 and n % group == 0
        inside = first // group == (first + n - 1) // group
        if not 0 <= first < first + n <= self.all_heads(cfg) or not (
                whole or inside):
            raise ValueError(
                f"heads_held {(first, n)} of {self.all_heads(cfg)} query "
                f"heads in groups of {group} a key-value head: a share is "
                "whole groups, or lies inside one")
        return n // group if whole else 1

    def rotary(self, cfg):
        """(theta, the share of a head's columns that turns, `rope_scaling`
        as a mapping or None): under the window its own theta over a whole
        head at plain frequencies."""
        if self.sliding:
            return cfg.rope_theta_sliding or cfg.rope_theta, 1.0, None
        return (cfg.rope_theta, cfg.partial_rotary_factor,
                dict(cfg.rope_scaling) if cfg.rope_scaling else None)

    def window(self, cfg) -> Optional[int]:
        return cfg.sliding_window if self.sliding else None

    def _gate_width(self, cfg) -> int:
        """`w_gate_attn`'s columns: none, one a head, or the context's."""
        if cfg.attn_gate not in (False, True, "elementwise"):
            raise ValueError(f"attn_gate {cfg.attn_gate!r}")
        if not cfg.attn_gate:
            return 0
        return self.heads(cfg) * (
            cfg.head_dim if cfg.attn_gate == "elementwise" else 1)

    def init(self, key, cfg, L):
        d, dh, h = cfg.d_model, cfg.head_dim, self.heads(cfg)
        hk = self.kv_heads(cfg)
        ks = jax.random.split(key, 7)
        leaves = {
            "attn_norm": jnp.ones((L, d), jnp.float32),
            "wq": _dense(ks[0], (L, d, h * dh), d),
            "wk": _dense(ks[1], (L, d, hk * dh), d),
            "wv": _dense(ks[2], (L, d, hk * dh), d),
            # a share's fan-in is all the operator's heads': the partial
            # sums of the shares add up to a product of that width
            "wo": _dense(ks[3], (L, h * dh, d), self.all_heads(cfg) * dh),
        }
        if cfg.attn_gate:
            leaves["w_gate_attn"] = _dense(
                jax.random.fold_in(key, 9), (L, d, self._gate_width(cfg)), d)
        if cfg.post_norm:
            leaves["attn_post_norm"] = jnp.ones((L, d), jnp.float32)
        if cfg.qk_norm:
            per_head = cfg.qk_norm == "head"
            leaves["q_norm"] = jnp.ones(
                (L, dh if per_head else h * dh), jnp.float32)
            leaves["k_norm"] = jnp.ones(
                (L, dh if per_head else hk * dh), jnp.float32)
        return leaves

    def axes(self, cfg):
        table = {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv"),
            "wv": ("layers", "embed", "kv"),
            "wo": ("layers", "heads", "embed"),
        }
        if cfg.attn_gate:  # a column a head, or the heads' columns
            table["w_gate_attn"] = ("layers", "embed", "heads")
        if cfg.post_norm:
            table["attn_post_norm"] = ("layers", None)
        if cfg.qk_norm == "head":  # one scale for all heads
            table.update(q_norm=("layers", None), k_norm=("layers", None))
        elif cfg.qk_norm:
            table.update(q_norm=("layers", "heads"), k_norm=("layers", "kv"))
        return table

    def forward(self, x, blk, cfg, site):
        # a full layer's scopes stand at the block's top: a trace splits the
        # two kinds of layer by this scope
        scope = (jax.named_scope("sliding_attention") if self.sliding
                 else contextlib.nullcontext())
        with scope:
            return _attention_layer(
                x, blk, site.positions, cfg, site.seq_axis, site.seq_size,
                site.mesh, site.keep_ctx, op=self), None

    def widths(self, cfg):
        h, dh = self.heads(cfg), cfg.head_dim
        return {
            # o and lse as one float32 column
            "attn_ctx": h * _tile_lanes(dh) + h * 4 // _item(cfg),
            "attn_res": cfg.d_model,
            "attn_qkv": (h + 2 * self.kv_heads(cfg)) * dh,
        }

    def params(self, cfg):
        d, dh, h = cfg.d_model, cfg.head_dim, self.heads(cfg)
        return (d * (h + 2 * self.kv_heads(cfg)) * dh + h * dh * d
                + d * self._gate_width(cfg))

    def holds(self, cfg):
        """q, k and v as the kernel takes them (q at the operator's heads, k
        and v at the key-value heads, which the kernels' index maps share
        among a group), lse and delta at a tile's 128 lanes."""
        h = self.heads(cfg)
        return ((h + 2 * self.kv_heads(cfg)) * _tile_lanes(cfg.head_dim)
                + 2 * h * 128 * 4 // _item(cfg)
                # a gate as wide as the context: its product
                + (self._gate_width(cfg)
                   if cfg.attn_gate == "elementwise" else 0)
                # `wo`'s product before its norm
                + (cfg.d_model if cfg.post_norm else 0))

    def flops(self, cfg, seq_len):
        # qk^T and pv each cost 2 h dh operations a (query, key) pair, over
        # the pairs the causal mask (and the window's band) leaves: the
        # flash kernel really skips the masked-out tiles, so crediting all
        # of seq_len would overcount about twofold
        return 2 * self.params(cfg), (
            2 * 2 * self.heads(cfg) * cfg.head_dim
            * keys_per_query(seq_len, self.window(cfg)))


class _SparseAttention(_PlainAttention):
    """Plain attention over the keys a learned indexer selects
    (`_sparse_attention_layer`): a third record over `_PlainAttention`'s
    leaves, at full attention's heads and rotary recipe, with the
    indexer's: `wq_idx` [d, index_heads x index_head_dim], `wk_idx`
    [d, index_head_dim] and the LayerNorm on the key it makes
    (`k_idx_norm`, `k_idx_bias`), `w_idx` [d, index_heads]."""

    matmuls = (*_PlainAttention.matmuls, "wq_idx", "wk_idx", "w_idx")
    readings = (
        # the indexers' own loss, over the layers and the tokens
        Reading("index_loss", "mean", adds=True),
        # over rows and layers, the least and the most keys a row kept
        # beyond what it should: 0
        Reading("index_keys_min_gap", "min"),
        Reading("index_keys_max_gap", "max"),
        # [L, B, T, T] int8: 1 where a query keeps a key
        Reading("index_keep", step=False),
    )
    # the index loss reads the probabilities of every head
    takes_heads_held = False
    takes_post_norm = False  # `_sparse_attention_layer` has none
    takes_multipliers = False
    no_sequence_axis = (
        "sparse attention is not mapped over a sequence axis: a query "
        "chooses among all the keys before it, and the selection and the "
        "index loss hold a block of queries against every one of them")

    def __init__(self):
        super().__init__(sliding=False)

    def _indexer(self, cfg) -> int:
        """The indexer's three matrices, in elements."""
        hi, di = cfg.index_heads, cfg.index_head_dim
        return cfg.d_model * (hi * di + di + hi)

    def init(self, key, cfg, L):
        d, hi, di = cfg.d_model, cfg.index_heads, cfg.index_head_dim
        if not (hi and di and cfg.index_topk):
            raise ValueError(
                "sparse_attention needs index_heads, index_head_dim and "
                f"index_topk, not {hi}, {di} and {cfg.index_topk}")
        leaves = super().init(key, cfg, L)
        # 10, 11, 12: numbers no other kind folds in
        leaves["wq_idx"] = _dense(
            jax.random.fold_in(key, 10), (L, d, hi * di), d)
        leaves["wk_idx"] = _dense(jax.random.fold_in(key, 11), (L, d, di), d)
        leaves["w_idx"] = _dense(jax.random.fold_in(key, 12), (L, d, hi), d)
        leaves["k_idx_norm"] = jnp.ones((L, di), jnp.float32)
        leaves["k_idx_bias"] = jnp.zeros((L, di), jnp.float32)
        return leaves

    def axes(self, cfg):
        # the indexer is whole on every device: 16 heads of 64 over one key
        # are not worth a cut, and the selection wants every head's score
        return {
            **super().axes(cfg),
            "wq_idx": ("layers", "embed", None),
            "wk_idx": ("layers", "embed", None),
            "w_idx": ("layers", "embed", None),
            "k_idx_norm": ("layers", None),
            "k_idx_bias": ("layers", None),
        }

    def forward(self, x, blk, cfg, site):
        with jax.named_scope("sparse_attention"):
            return _sparse_attention_layer(
                x, blk, site.positions, cfg, site.mesh, site.keep_ctx,
                op=self)

    def widths(self, cfg):
        hi, di = cfg.index_heads, cfg.index_head_dim
        widths = super().widths(cfg)
        # beside o and lse the indexer's three gradients, which the forward
        # makes (`q_idx`'s and `k_idx`'s, and `w_idx`'s in float32), and the
        # selection's mask, a bit a key of the longest sequence
        widths["attn_ctx"] += (hi * di + di + hi * 4 // _item(cfg)
                               + cfg.max_seq_len // 8 // _item(cfg))
        return widths

    def params(self, cfg):
        return super().params(cfg) + self._indexer(cfg)

    def holds(self, cfg):
        """Plain attention's; the index queries, key and weights as the
        kernels take them; the selection's mask, a bit a key of the longest
        sequence (the one a backward layer reads, made again or kept), and a
        byte a key beside it, priced from the compiler's plan for a
        described v5e: where two int8 masks were priced, the plan with
        nothing kept fell by one, less the bits (0.235 GB of 15.05), when
        the mask became bits. The other byte stands for what the plan holds
        and no record prices, the held rows' buffers at 3.25 even shares
        among it (0.24 GB): without it the rule keeps `attn_res` too and the
        plan stands 0.08 GB under what a v5e offers a program, the chip's
        peak over it. Then `index_loss`'s own operands and
        results (q heads first, lse a row, the three gradients); and what
        the compiler holds for a scanned stack of these layers and the rule
        has no other term for: every layer's weights in the compute dtype,
        the feed-forwards' too, hoisted out of the loop, spread over a
        sequence's tokens (1.16 GB of the 6.66 GB of scratch it plans for
        `keyevl2.tokens16k`'s step on a described v5e; PERF.md section 6,
        "Keep rule: a scanned stack's sum")."""
        h, hi, di = self.heads(cfg), cfg.index_heads, cfg.index_head_dim
        item = _item(cfg)
        operands = hi * _tile_lanes(di) + _tile_lanes(di) + hi * 4 // item
        mask = (cfg.max_seq_len // 8 + cfg.max_seq_len) // item
        index_loss = (h * cfg.head_dim + h * 4 // item + hi * di
                      + di * 4 // item + 128 * 4 // item)
        hoisted = sum(_layer_widths(cfg, kind)[1]
                      for kind in cfg.layers) // cfg.max_seq_len
        return super().holds(cfg) + operands + mask + index_loss + hoisted

    def flops(self, cfg, seq_len):
        # what the equations need and no more: q k^T and p v over the kept
        # pairs, the index score over the causal ones; the dense walk under
        # the mask computes every causal pair, which is headroom
        h, dh = self.heads(cfg), cfg.head_dim
        return 2 * self.params(cfg), (
            2 * 2 * h * dh * keys_kept(seq_len, cfg.index_topk) / seq_len
            + 2 * cfg.index_heads * cfg.index_head_dim
            * keys_per_query(seq_len))


class _LatentAttention(Sublayer):
    """Multi-head latent attention, decompressed
    (`_latent_attention_layer`)."""

    matmuls = ("wq", "wo", "wkv_a", "wkv_b")
    names = ("attn_ctx", "attn_res", "attn_qkv")
    no_sequence_axis = "latent attention is not mapped over a sequence axis"

    def heads(self, cfg) -> int:
        return cfg.n_heads

    def init(self, key, cfg, L):
        d, h = cfg.d_model, cfg.n_heads
        r, nope, rope, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        ks = jax.random.split(key, 7)
        return {
            "attn_norm": jnp.ones((L, d), jnp.float32),
            "wq": _dense(ks[0], (L, d, h * (nope + rope)), d),
            # the latent, then the one rotary key all heads share
            "wkv_a": _dense(ks[1], (L, d, r + rope), d),
            "kv_norm": jnp.ones((L, r), jnp.float32),
            # per head: its unrotated key columns, then its value columns
            "wkv_b": _dense(ks[2], (L, r, h * (nope + dv)), r),
            "wo": _dense(ks[3], (L, h * dv, d), h * dv),
        }

    def axes(self, cfg):
        # cut along the heads where a product's columns (rows, for `wo`) are
        # the heads'; the down projection to the latent and the shared key
        # is whole
        return {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wkv_a": ("layers", "embed", None),
            "kv_norm": ("layers", None),
            "wkv_b": ("layers", None, "heads"),
            "wo": ("layers", "heads", "embed"),
        }

    def forward(self, x, blk, cfg, site):
        with jax.named_scope("latent_attention"):
            return _latent_attention_layer(
                x, blk, site.positions, cfg, site.mesh, site.keep_ctx), None

    def widths(self, cfg):
        h, rope = cfg.n_heads, cfg.qk_rope_head_dim
        return {
            "attn_ctx": h * _tile_lanes(cfg.v_head_dim) + h * 4 // _item(cfg),
            "attn_res": cfg.d_model,
            # out of `wq`, `wkv_a` and `wkv_b`
            "attn_qkv": (h * (cfg.qk_nope_head_dim + rope)
                         + cfg.kv_lora_rank + rope
                         + h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        }

    def params(self, cfg):
        d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        return (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + dv)
                + h * dv * d)

    def holds(self, cfg):
        """k and v at as many heads as q, q and k at two tiles of lanes; lse
        and delta at a tile's 128 lanes."""
        h = cfg.n_heads
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return (h * (2 * _tile_lanes(qk) + _tile_lanes(cfg.v_head_dim))
                + 2 * h * 128 * 4 // _item(cfg))

    def flops(self, cfg, seq_len):
        h = cfg.n_heads
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        # scores over q and k's width, the values over v's
        return 2 * self.params(cfg), (
            2 * h * (qk + cfg.v_head_dim) * keys_per_query(seq_len))


class _ShortConv(Sublayer):
    """The gated short convolution (`_short_conv`)."""

    matmuls = ("conv_in", "conv_out")
    names = ("conv_res", "conv_in")
    no_sequence_axis = (
        "the short convolution is not mapped over a sequence axis")

    def init(self, key, cfg, L):
        d = cfg.d_model
        ks = jax.random.split(key, 7)
        return {
            "conv_norm": jnp.ones((L, d), jnp.float32),
            "conv_in": _dense(ks[0], (L, d, 3 * d), d),
            "conv_w": _dense(ks[1], (L, cfg.conv_taps, d), cfg.conv_taps),
            "conv_out": _dense(ks[3], (L, d, d), d),
        }

    def axes(self, cfg):
        # the three streams of `conv_in` are split after the product and the
        # convolution is per channel: neither is cut along the channels
        return {
            "conv_norm": ("layers", None),
            "conv_in": ("layers", "embed", None),
            "conv_w": ("layers", None, None),
            "conv_out": ("layers", None, "embed"),
        }

    def forward(self, x, blk, cfg, site):
        with jax.named_scope("short_conv"):
            return checkpoint_name(
                x + _short_conv(x, blk, cfg), "conv_res"), None

    def widths(self, cfg):
        return {"conv_res": cfg.d_model, "conv_in": 3 * cfg.d_model}

    def params(self, cfg):
        return 4 * cfg.d_model * cfg.d_model

    def holds(self, cfg):
        return 3 * cfg.d_model  # the gate's product, the taps' sum, the gated


class _Mamba2(Sublayer):
    """The Mamba-2 mixer (`_mamba_mixer`)."""

    matmuls = ("w_in", "w_out")
    names = ("mamba_in", "ssd_out")
    no_sequence_axis = "the Mamba-2 mixer is not mapped over a sequence axis"
    takes_multipliers = True

    def init(self, key, cfg, L):
        """`A = -exp(A_log)` starts uniform in [-16, -1], `softplus(dt_bias)`
        log-uniform in `mamba_dt_init`'s range and no less than its floor,
        the skip `D` at 1 (the published initialiser's)."""
        d, H = cfg.d_model, cfg.mamba_heads
        inner, conv = cfg.mamba_inner, cfg.mamba_conv_dim
        taps = cfg.mamba_conv_taps
        k_in, k_conv, k_a, k_dt, k_out = jax.random.split(
            jax.random.split(key, 7)[0], 5)
        w_out = _dense(k_out, (L, inner, d), inner)
        if cfg.rescale_prenorm_residual:
            w_out = w_out / math.sqrt(cfg.n_layers)
        return {
            "mixer_norm": jnp.ones((L, d), jnp.float32),
            # the gate z, then x, B and C (the convolution's channels), then dt
            "w_in": _dense(k_in, (L, d, inner + conv + H), d),
            "conv_w": _dense(k_conv, (L, taps, conv), taps),
            "conv_b": jnp.zeros((L, conv), jnp.float32),
            "dt_bias": _dt_bias(k_dt, (L, H), cfg),
            "A_log": jnp.log(
                jax.random.uniform(k_a, (L, H), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((L, H), jnp.float32),
            "norm": jnp.ones((L, inner), jnp.float32),
            "w_out": w_out,
        }

    def axes(self, cfg):
        # cut along the heads where a leaf is the heads'; `w_in`'s columns
        # are three streams that are split after the product, and the
        # convolution runs over x, B and C together: neither is cut
        return {
            "mixer_norm": ("layers", None),
            "w_in": ("layers", "embed", None),
            "conv_w": ("layers", None, None),
            "conv_b": ("layers", None),
            "dt_bias": ("layers", "heads"),
            "A_log": ("layers", "heads"),
            "D": ("layers", "heads"),
            "norm": ("layers", "heads"),
            "w_out": ("layers", "heads", "embed"),
        }

    def forward(self, x, blk, cfg, site):
        with jax.named_scope("mamba"):
            return x + _to_the_stream(_mamba_mixer(x, blk, cfg), cfg), None

    def _wide(self, cfg) -> int:
        """`w_in`'s columns: the gate, x, B and C, and dt a head."""
        return cfg.mamba_inner + cfg.mamba_conv_dim + cfg.mamba_heads

    def widths(self, cfg):
        return {"mamba_in": self._wide(cfg), "ssd_out": cfg.mamba_inner}

    def params(self, cfg):
        return cfg.d_model * self._wide(cfg) + cfg.mamba_inner * cfg.d_model

    def holds(self, cfg):
        """The convolution's results and their cotangents, the gated output
        and the normed one, what the scan holds by the path it takes, and
        by the path the gated norm takes (`ops/mamba_passes.py`): under
        autodiff the three float32 arrays of the mixer's width that its
        backward holds (the compiler's plan for a described v5e,
        `nemotron3nano.tokens8k`, PR 54: `mamba_out`'s cotangent and the
        norm's two products, 0.27 GB each at 16,384 tokens, in a backward
        of 2.77 GB where the other terms count 1.59 and the names 0.47);
        the kernels hold none (the same plan with them, PR 63: 0.95 GB
        less scratch, where the three count 0.81)."""
        norm = 3 * cfg.mamba_inner * 4 // _item(cfg)
        if _gated_norm_kernels(cfg):
            norm = 0
        return (2 * cfg.mamba_conv_dim + 2 * cfg.mamba_inner + norm
                + _scan_bytes_per_token(cfg) // _item(cfg))

    def residuals(self, cfg):
        """Through a feed-forward's backward behind it in the layer: the
        convolution's results, the scan's entering states (float32, a
        chunk), the normed output that `w_out`'s gradient takes, and the
        gate `z` and `xBC` as arrays of their own, the kernels' operands,
        beside the product `mamba_in` they are cut from (the compiler's plan
        for a described v5e, `granite4hmicro.longctx`, PR 74: 0.29, 0.27,
        0.27 and 0.55 GB at 32,768 tokens, all live with the feed-forward's
        three products at the step's fullest; a stack of `sublayer_types`
        has no sublayer behind a mixer)."""
        states = 4 * cfg.mamba_inner * cfg.ssm_state // cfg.ssd_chunk
        return (2 * cfg.mamba_conv_dim + 2 * cfg.mamba_inner
                + states // _item(cfg))

    def flops(self, cfg, seq_len):
        inner, N = cfg.mamba_inner, cfg.ssm_state
        # the scan as it is computed, whole chunks: the scores of a chunk,
        # their product with the inputs, the chunk's state and the state's
        # contribution
        scan = (2 * cfg.ssd_chunk * (cfg.ssm_groups * N + inner)
                + 2 * 2 * inner * N)
        return 2 * self.params(cfg) + scan, 0


class _KDA(Sublayer):
    """Kimi Delta Attention (`_kda_mixer`) over the heads `heads_held`
    names, all `kda_heads` where it names none."""

    matmuls = ("kda_q", "kda_k", "kda_v", "kda_o", "kda_f1", "kda_f2",
               "kda_g1", "kda_g2", "kda_b")
    names = ("kda_res", "kda_qkv")
    readings = (
        Reading("kda_beta_mean", "mean"),
        # the most negative running log decay at a chunk's end, over layers,
        # heads and channels
        Reading("kda_log_decay_min", "min"),
    )
    no_sequence_axis = "kda is not mapped over a sequence axis"
    takes_heads_held = True

    def heads(self, cfg) -> int:
        if not cfg.heads_held:
            return cfg.kda_heads
        first, n = cfg.heads_held
        if not 0 <= first < first + n <= cfg.kda_heads:
            raise ValueError(
                f"heads_held {(first, n)} of {cfg.kda_heads} kda heads")
        return n

    def _rank(self, cfg) -> int:
        return cfg.kda_gate_rank or cfg.kda_head_dim

    def init(self, key, cfg, L):
        """The decay starts as the Mamba-2 mixer's does: `exp(A_log)` a
        head uniform in [1, 16], `softplus(dt_bias)` a channel log-uniform
        in `mamba_dt_init`'s range and no less than its floor. The gates'
        second factors draw at their rank's fan-in, the output gate's bias
        at 0, and `kda_o` at the fan-in of all the operator's heads."""
        d, H, dk, r = cfg.d_model, self.heads(cfg), cfg.kda_head_dim, (
            self._rank(cfg))
        if not (cfg.kda_heads and dk):
            raise ValueError("kda needs kda_heads and kda_head_dim, not "
                             f"{cfg.kda_heads} and {dk}")
        wide, taps = H * dk, cfg.kda_conv_taps
        ks = jax.random.split(jax.random.split(key, 7)[0], 12)
        return {
            "kda_norm": jnp.ones((L, d), jnp.float32),
            "kda_q": _dense(ks[0], (L, d, wide), d),
            "kda_k": _dense(ks[1], (L, d, wide), d),
            "kda_v": _dense(ks[2], (L, d, wide), d),
            "kda_conv": _dense(ks[3], (L, 3, taps, wide), taps),
            "kda_f1": _dense(ks[4], (L, d, r), d),
            "kda_f2": _dense(ks[5], (L, r, wide), r),
            "kda_g1": _dense(ks[6], (L, d, r), d),
            "kda_g2": _dense(ks[7], (L, r, wide), r),
            "kda_g_bias": jnp.zeros((L, wide), jnp.float32),
            "kda_b": _dense(ks[8], (L, d, H), d),
            "kda_A_log": jnp.log(jax.random.uniform(
                ks[9], (L, H), jnp.float32, 1.0, 16.0)),
            "kda_dt_bias": _dt_bias(ks[10], (L, wide), cfg),
            "kda_out_norm": jnp.ones((L, dk), jnp.float32),
            "kda_o": _dense(ks[11], (L, wide, d), cfg.kda_heads * dk),
        }

    def axes(self, cfg):
        # cut along the heads where a leaf is the heads'; the gates' first
        # factors and the output norm's one scale are whole
        heads = ("layers", "embed", "heads")
        return {
            "kda_norm": ("layers", None),
            "kda_q": heads, "kda_k": heads, "kda_v": heads,
            "kda_conv": ("layers", None, None, "heads"),
            "kda_f1": ("layers", "embed", None),
            "kda_f2": ("layers", None, "heads"),
            "kda_g1": ("layers", "embed", None),
            "kda_g2": ("layers", None, "heads"),
            "kda_g_bias": ("layers", "heads"),
            "kda_b": heads,
            "kda_A_log": ("layers", "heads"),
            "kda_dt_bias": ("layers", "heads"),
            "kda_out_norm": ("layers", None),
            "kda_o": ("layers", "heads", "embed"),
        }

    def forward(self, x, blk, cfg, site):
        with jax.named_scope("kda"):
            y, readings = _kda_mixer(x, blk, cfg)
            return checkpoint_name(x + y, "kda_res"), readings

    def widths(self, cfg):
        return {"kda_res": cfg.d_model,
                "kda_qkv": 3 * self.heads(cfg) * cfg.kda_head_dim}

    def params(self, cfg):
        d, H, r = cfg.d_model, self.heads(cfg), self._rank(cfg)
        wide = H * cfg.kda_head_dim
        return 4 * d * wide + 2 * d * r + 2 * r * wide + d * H

    def holds(self, cfg):
        """In elements of the compute dtype a token, `wide` the held heads'
        width: q, k and v out of the convolution and its silu, q and k at
        unit length, the gate and the gated output (7 wide; 5 where the
        convolutions run as `kda_conv_fwd`, which writes q and k at unit
        length and leaves the silu's in VMEM), and by the
        path `kda` takes (`ops/kda.py`) the recurrence's own. The kernels:
        their result and the cotangents of o, q, k and v (5 wide), the log
        decay and its cotangent in float32, and a chunk's entering state
        (`dk` float32 values a channel and chunk); every pair tensor, scaled
        copy and the solve stay in VMEM. `jax.numpy`: in float32 the log
        decay, its running sum, the three decayed copies of q and k beside
        one of the keys a sub-chunk, and each one's cotangent; the pair
        tensors of a sub-chunk, `SUB` values a channel and token each
        (every pair's decay and its product with the rows, for k with k
        and for q with k), with their cotangents; a chunk's entering state
        and the fresh values. (PERF.md section 6, PR 60, has both against
        the compiler's plans.)"""
        wide, dk = self.heads(cfg) * cfg.kda_head_dim, cfg.kda_head_dim
        f32 = 4 // _item(cfg) or 1
        states = f32 * wide * dk // cfg.kda_chunk
        streams = (5 if _kda_conv_kernels(cfg) else 7) * wide
        if _kernel_impl(cfg) == "pallas" and not kda_untiled(
                cfg.kda_chunk, dk, dk, _item(cfg)):
            return streams + 5 * wide + 2 * f32 * wide + states
        copies = 2 + 3 + cfg.kda_chunk // _KDA_SUB
        return (streams + 2 * f32 * copies * wide
                + 4 * f32 * _KDA_SUB * wide + states + f32 * wide)

    def flops(self, cfg, seq_len):
        H, dk, C = self.heads(cfg), cfg.kda_head_dim, cfg.kda_chunk
        # the chunked form as it is computed, whole chunks, a head: the
        # pair products of k with k and of q with k, `W` and `U`, the
        # scores' product with the fresh values (2 C dk each); `W S`, `q S`
        # and the state's update (2 dk dk each)
        chunked = H * (5 * 2 * C * dk + 3 * 2 * dk * dk)
        return 2 * self.params(cfg) + chunked, 0


class _Mamba1(Sublayer):
    """The Mamba-1 mixer (`_mamba1_mixer`); as `mamba1_emit` it also emits
    the scan's output, before the gate, as `scan_memory`."""

    matmuls = ("w_in", "w_x", "w_dt", "w_out")
    names = ("mamba1_in", "scan_out")
    no_sequence_axis = "the Mamba-1 mixer is not mapped over a sequence axis"
    takes_layer_norm = True

    def __init__(self, emits: bool):
        self.emits = ("scan_memory",) if emits else ()

    def carried(self, cfg):
        return {name: cfg.mamba1_inner for name in self.emits}

    def init(self, key, cfg, L):
        """`A = -exp(A_log)` starts at -1 .. -`mamba1_state` along a
        channel's states, `softplus(dt_bias)` log-uniform in
        `mamba_dt_init`'s range and no less than its floor, `w_dt` uniform
        within `dt_rank ** -0.5`, the skip `D` at 1 (the published
        initialiser's)."""
        d, inner, N, R = (cfg.d_model, cfg.mamba1_inner, cfg.mamba1_state,
                          cfg.dt_rank)
        if not inner:
            raise ValueError("mamba1 needs mamba1_inner, not 0")
        taps = cfg.mamba1_conv_taps
        k_in, k_conv, k_x, k_dtw, k_dt, k_out = jax.random.split(
            jax.random.split(key, 7)[0], 6)
        w_out = _dense(k_out, (L, inner, d), inner)
        if cfg.rescale_prenorm_residual:
            w_out = w_out / math.sqrt(cfg.n_layers)
        return {
            **_norm_leaves("mixer_norm", cfg, L),
            "w_in": _dense(k_in, (L, d, 2 * inner), d),  # u, then the gate z
            "conv_w": _dense(k_conv, (L, taps, inner), taps),
            "conv_b": jnp.zeros((L, inner), jnp.float32),
            "w_x": _dense(k_x, (L, inner, R + 2 * N), inner),  # r, B, C
            "w_dt": jax.random.uniform(
                k_dtw, (L, R, inner), jnp.float32, -R ** -0.5, R ** -0.5),
            "dt_bias": _dt_bias(k_dt, (L, inner), cfg),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                (L, inner, N)),
            "D": jnp.ones((L, inner), jnp.float32),
            "w_out": w_out,
        }

    def axes(self, cfg):
        # cut along the channels where a leaf is the channels'; `w_in`'s
        # columns are two streams that are split after the product
        return {
            **_norm_axes("mixer_norm", cfg),
            "w_in": ("layers", "embed", None),
            "conv_w": ("layers", None, "heads"),
            "conv_b": ("layers", "heads"),
            "w_x": ("layers", "heads", None),
            "w_dt": ("layers", None, "heads"),
            "dt_bias": ("layers", "heads"),
            "A_log": ("layers", "heads", None),
            "D": ("layers", "heads"),
            "w_out": ("layers", "heads", "embed"),
        }

    def forward(self, x, blk, cfg, site):
        with jax.named_scope("mamba1"):
            y, s = _mamba1_mixer(x, blk, cfg)
            return x + y, {"scan_memory": s} if self.emits else None

    def widths(self, cfg):
        inner = cfg.mamba1_inner
        # the scan's output and its chunks' entering states, float32
        states = (4 // _item(cfg) or 1) * cfg.mamba1_state * inner
        return {"mamba1_in": 2 * inner,
                "scan_out": inner + states // cfg.scan_chunk}

    def params(self, cfg):
        inner, R = cfg.mamba1_inner, cfg.dt_rank
        return (cfg.d_model * 3 * inner
                + inner * (2 * R + 2 * cfg.mamba1_state))

    def holds(self, cfg):
        """In elements of the compute dtype a token, as the compiler's plan
        for `phi4flash.tokens16k` holds them at the backward scan (a
        described v5e, PR 73: 2.01 GB at 16,384 tokens where ten arrays of
        `inner`, five of them float32, priced 2.58): u and the gate apart
        from `w_in`'s product, the convolution's silu, the scan's du and
        two cotangents of `w_out`'s (6 inner), `w_x`'s product and its
        cotangent; in float32 the convolution's sum, the scan's d delta and
        the gated output's cotangent (3 inner); a
        chunk's entering state; and by the path `selective_scan` takes
        (`ops/selective_scan.py`): `jax.numpy`, spread over a sequence's
        tokens, the steps of the one chunk the backward makes again (the
        decay, the state before and after: three `[chunk, N, inner]`
        float32); the kernels, which keep those in VMEM, the blocks' parts
        of `dB` and `dC`."""
        inner, N = cfg.mamba1_inner, cfg.mamba1_state
        f32 = 4 // _item(cfg) or 1
        if _kernel_impl(cfg) == "pallas" and not selective_scan_untiled(
                cfg.scan_chunk, N, inner, _item(cfg)):
            steps = 2 * N * (inner // channel_block(inner))
        else:
            steps = 3 * cfg.scan_chunk * N * inner // cfg.max_seq_len
        return (6 * inner + 2 * (cfg.dt_rank + 2 * N) + 3 * f32 * inner
                + f32 * (N * inner // cfg.scan_chunk + steps))


class _DiffAttention(Sublayer):
    """Differential attention (`_diff_attention_layer`): four records over
    one forward. `diff_attention` over the whole causal context and
    `sliding_diff_attention` under `sliding_window`; `diff_attention_emit`,
    the whole one that also emits its keys and values as `attn_kv`; and
    `cross_diff_attention`, which has `W_q` and `W_o` alone and reads
    `attn_kv`."""

    matmuls = ("wq", "wk", "wv", "wo")
    names = ("attn_ctx", "attn_res", "attn_qkv")
    # a layer's `lam`, [L]
    readings = (Reading("diff_lambda"),)
    no_sequence_axis = (
        "differential attention is not mapped over a sequence axis")
    takes_layer_norm = True
    reads_depth = True

    def __init__(self, sliding: bool = False, emits: bool = False,
                 cross: bool = False):
        self.sliding, self.cross = sliding, cross
        self.emits = ("attn_kv",) if emits else ()
        self.reads = ("attn_kv",) if cross else ()
        if cross:
            self.matmuls = ("wq", "wo")

    def heads(self, cfg) -> int:
        return cfg.n_heads

    def window(self, cfg) -> Optional[int]:
        return (cfg.sliding_window or None) if self.sliding else None

    def carried(self, cfg):
        return {name: 2 * cfg.kv_heads * cfg.head_dim for name in self.emits}

    def _kv(self, cfg) -> int:
        """The key and value heads the layer projects: none of a cross
        layer's."""
        return 0 if self.cross else cfg.kv_heads

    def init(self, key, cfg, L):
        d, dh, h, hk = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.kv_heads
        if h % 2 or hk % 2 or (h // 2) % (hk // 2):
            raise ValueError(
                f"differential attention pairs {h} query heads over {hk} key "
                "heads: both even, and whole pairs of queries a pair of keys")
        ks = jax.random.split(jax.random.split(key, 7)[0], 8)
        leaves = {
            **_norm_leaves("attn_norm", cfg, L),
            "wq": _dense(ks[0], (L, d, h * dh), d),
            "wo": _dense(ks[3], (L, h * dh, d), h * dh),
            **{"lam_" + name: 0.1 * jax.random.normal(
                ks[4 + i], (L, dh), jnp.float32)
               for i, name in enumerate(("q1", "k1", "q2", "k2"))},
            "diff_norm": jnp.ones((L, 2 * dh), jnp.float32),
        }
        if not self.cross:
            leaves["wk"] = _dense(ks[1], (L, d, hk * dh), d)
            leaves["wv"] = _dense(ks[2], (L, d, hk * dh), d)
        if cfg.attn_bias:
            for name in self._biases():
                leaves[name] = jnp.zeros(
                    (L, leaves["w" + name[1:]].shape[-1]), jnp.float32)
        return leaves

    def _biases(self) -> Tuple[str, ...]:
        return ("bq", "bo") if self.cross else ("bq", "bk", "bv", "bo")

    def axes(self, cfg):
        table = {
            **_norm_axes("attn_norm", cfg),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv"),
            "wv": ("layers", "embed", "kv"),
            "wo": ("layers", "heads", "embed"),
            **dict.fromkeys(("lam_q1", "lam_k1", "lam_q2", "lam_k2",
                             "diff_norm"), ("layers", None)),
        }
        if cfg.attn_bias:
            table.update(bq=("layers", "heads"), bk=("layers", "kv"),
                         bv=("layers", "kv"), bo=("layers", None))
        if self.cross:
            for name in ("wk", "wv", "bk", "bv"):
                table.pop(name, None)
        return table

    def forward(self, x, blk, cfg, site):
        scope = (jax.named_scope("sliding_attention") if self.sliding
                 else contextlib.nullcontext())
        with scope, jax.named_scope("diff_attention"):
            return _diff_attention_layer(x, blk, cfg, site, op=self)

    def widths(self, cfg):
        h, dh = cfg.n_heads, cfg.head_dim
        return {
            # o at the pair's width a head, and lse as one float32 column
            "attn_ctx": h * _tile_lanes(2 * dh) + h * 4 // _item(cfg),
            "attn_res": cfg.d_model,
            "attn_qkv": (h + 2 * self._kv(cfg)) * dh,
        }

    def params(self, cfg):
        d, dh, h = cfg.d_model, cfg.head_dim, cfg.n_heads
        return d * (h + 2 * self._kv(cfg)) * dh + h * dh * d

    def holds(self, cfg):
        """q and the paired k as the kernel takes them, V twice at the
        pair's width; lse and delta at a tile's 128 lanes; the kernel's o,
        the difference in float32 and the normed pairs."""
        h, hk, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        f32 = 4 // _item(cfg) or 1
        return ((h + hk) * _tile_lanes(dh) + hk * _tile_lanes(2 * dh)
                + 2 * h * 128 * f32 + h * 2 * dh + (f32 + 1) * h * dh)

    def flops(self, cfg, seq_len):
        # a pair of softmaxes is two maps: every query head's q k^T over
        # `head_dim` and p V over the pair's width, over the pairs the
        # causal mask (and the window's band) leaves
        h, dh = cfg.n_heads, cfg.head_dim
        return 2 * self.params(cfg), (
            2 * h * (dh + 2 * dh) * keys_per_query(seq_len, self.window(cfg)))


class _GMU(Sublayer):
    """The gated memory unit (`_gmu`): reads `scan_memory`."""

    matmuls = ("gmu_in", "gmu_out")
    names = ("gmu_in",)
    reads = ("scan_memory",)
    no_sequence_axis = (
        "the gated memory unit is not mapped over a sequence axis")
    takes_layer_norm = True

    def init(self, key, cfg, L):
        d, inner = cfg.d_model, cfg.mamba1_inner
        k_in, k_out = jax.random.split(jax.random.split(key, 7)[0])
        return {
            **_norm_leaves("gmu_norm", cfg, L),
            "gmu_in": _dense(k_in, (L, d, inner), d),
            "gmu_out": _dense(k_out, (L, inner, d), inner),
        }

    def axes(self, cfg):
        return {
            **_norm_axes("gmu_norm", cfg),
            "gmu_in": ("layers", "embed", "heads"),
            "gmu_out": ("layers", "heads", "embed"),
        }

    def forward(self, x, blk, cfg, site):
        with jax.named_scope("gmu"):
            return x + _gmu(x, blk, cfg, site.shared["scan_memory"]), None

    def widths(self, cfg):
        return {"gmu_in": cfg.mamba1_inner}

    def params(self, cfg):
        return 2 * cfg.d_model * cfg.mamba1_inner

    def holds(self, cfg):
        # the gate's silu, the gated memory and the memory's cotangent
        return 3 * cfg.mamba1_inner


class _EvaAttention(Sublayer):
    """EVA attention (`_eva_attention_layer`): plain attention's four
    matrices at as many key heads as query heads, and the two vectors a
    head that make the chunks' summaries, which are no matmul's weights
    and are never decayed."""

    matmuls = ("wq", "wk", "wv", "wo")
    names = ("attn_ctx", "eva_summaries", "attn_res", "attn_qkv")
    # a layer's share of a query's softmax that the summaries take, and its
    # chunks' mean entropy: [L] each
    readings = (Reading("eva_remote_mass"), Reading("eva_chunk_entropy"))
    no_sequence_axis = (
        "EVA attention is not mapped over a sequence axis: a window's "
        "queries read the summaries of every earlier window, which other "
        "devices hold")
    takes_unit_offset = True

    def heads(self, cfg) -> int:
        return cfg.n_heads

    def check(self, cfg):
        W, C = cfg.eva_window, cfg.eva_chunk
        if W < 1 or C < 1 or W % C:
            raise ValueError(
                f"eva_attention needs eva_window and eva_chunk, whole chunks "
                f"a window, not {W} and {C}")
        if cfg.kv_heads != cfg.n_heads:
            raise ValueError(
                f"eva_attention with {cfg.kv_heads} key heads for "
                f"{cfg.n_heads} query heads: a summary is a head's own")
        if cfg.max_seq_len > W and cfg.max_seq_len % W:
            raise ValueError(
                f"eva_attention with sequences of {cfg.max_seq_len} under a "
                f"window of {W}: a sequence is whole windows, or no longer "
                "than one")

    def init(self, key, cfg, L):
        d, dh, h = cfg.d_model, cfg.head_dim, cfg.n_heads
        ks = jax.random.split(key, 7)
        k_phi, k_mu = jax.random.split(jax.random.fold_in(key, 13))

        def vector(key):  # normal, clipped to [-1, 1], times dh ** -0.5
            return jnp.clip(jax.random.normal(key, (L, h, dh), jnp.float32),
                            -1.0, 1.0) * dh ** -0.5

        return {
            **_norm_leaves("attn_norm", cfg, L),
            "wq": _dense(ks[0], (L, d, h * dh), d),
            "wk": _dense(ks[1], (L, d, h * dh), d),
            "wv": _dense(ks[2], (L, d, h * dh), d),
            "wo": _dense(ks[3], (L, h * dh, d), h * dh),
            "eva_phi": vector(k_phi),
            "eva_mu": vector(k_mu),
        }

    def axes(self, cfg):
        return {
            **_norm_axes("attn_norm", cfg),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv"),
            "wv": ("layers", "embed", "kv"),
            "wo": ("layers", "heads", "embed"),
            "eva_phi": ("layers", "heads", None),
            "eva_mu": ("layers", "heads", None),
        }

    def forward(self, x, blk, cfg, site):
        with jax.named_scope("eva"):
            return _eva_attention_layer(x, blk, cfg, site)

    def widths(self, cfg):
        h, dh = cfg.n_heads, cfg.head_dim
        return {
            # the two parts' o, and each one's lse as one float32 column
            "attn_ctx": 2 * (h * _tile_lanes(dh) + h * 4 // _item(cfg)),
            # the chunks' keys and values: a chunk-th of k and v
            "eva_summaries": 2 * h * dh // cfg.eva_chunk,
            "attn_res": cfg.d_model,
            "attn_qkv": 3 * h * dh,
        }

    def params(self, cfg):
        return 4 * cfg.d_model * cfg.n_heads * cfg.head_dim

    def holds(self, cfg):
        """q, k and v as the kernels take them; lse and delta at a tile's
        128 lanes, of the window's call and of the staircase's; the two
        parts' o and their cotangents beside the joined one's; the
        summaries and their cotangents."""
        h, dh = cfg.n_heads, cfg.head_dim
        return (3 * h * _tile_lanes(dh) + 4 * h * 128 * 4 // _item(cfg)
                + 5 * h * dh + 4 * h * dh // cfg.eva_chunk)

    def flops(self, cfg, seq_len):
        # q k^T and p v over the pairs a query has: its window's causal
        # half and the earlier windows' summaries; the summaries' own pass
        # (6 operations a token and channel) is bandwidth and not counted
        return 2 * self.params(cfg), (
            2 * 2 * cfg.n_heads * cfg.head_dim
            * eva_keys_per_query(seq_len, cfg.eva_window, cfg.eva_chunk))


class _BlockDiffusionAttention(_PlainAttention):
    """Block-diffusion attention (`_block_diffusion_attention_layer`): a
    fourth record over `_PlainAttention`'s leaves, at full attention's heads
    and rotary recipe, on a stream of two rows a token under the mask of
    `ops/block_diffusion.py`. Widths are a row's, as every record's are a
    row of the stream's: the rule counts the stream's rows
    (`TransformerConfig.rows_per_token`)."""

    matmuls = ("wq", "wk", "wv", "wo")  # no gate on its context
    takes_heads_held = False  # the halves are folded over all the heads
    takes_post_norm = False  # `_block_diffusion_attention_layer` has none
    takes_multipliers = False
    no_sequence_axis = (
        "block-diffusion attention is not mapped over a sequence axis: a "
        "row reads the clean rows of every earlier block and its own "
        "block's, which other devices hold")

    def __init__(self):
        super().__init__(sliding=False)

    def check(self, cfg):
        if cfg.objective != "block_diffusion":
            raise ValueError(
                "block_diffusion_attention under the objective "
                f"{cfg.objective!r}: its rows are a noisy and a clean copy "
                "of a sequence, which the objective 'block_diffusion' makes")
        block, mask_id = cfg.diffusion_block, cfg.mask_token_id
        if block < 1 or cfg.max_seq_len % block:
            raise ValueError(
                f"diffusion_block {block} under sequences of "
                f"{cfg.max_seq_len}: a sequence is whole blocks")
        if mask_id is None or not 0 <= mask_id < cfg.vocab_size:
            raise ValueError(
                f"mask_token_id {mask_id} is no id of a vocabulary of "
                f"{cfg.vocab_size}")
        if cfg.loop_steps > 1 or cfg.n_pred_heads > 1 or cfg.attn_gate:
            raise ValueError(
                "block_diffusion_attention with loop_steps > 1, "
                "n_pred_heads > 1 or attn_gate: none is made for two rows "
                "a token yet")

    def forward(self, x, blk, cfg, site):
        with jax.named_scope("block_diffusion_attention"):
            return _block_diffusion_attention_layer(
                x, blk, cfg, site, op=self), None

    def holds(self, cfg):
        """Plain attention's (q, k and v as the kernels take them, q with
        its halves folded into the heads; lse and delta at a tile's 128
        lanes); round the own block and the join, by the path they take
        (`_bd_own_join_kernels`): by the kernels `bd_own_join_fwd` and
        `bd_own_join_bwd` the staircase's o's cotangent, the joined o and
        its, and the pair's dq beside the staircase's, four of q's width in
        its dtype, every float32 value a head and row VMEM's (the compiler's
        plan for a described v5e, `benchmarks/lowering_seconds.py --plan`:
        13.67 GB where the lines' step planned 14.58, PERF.md section 6, PR
        71); by the `jax.numpy` lines the own block's scores and weights,
        `diffusion_block` float32 values a head each, and the two parts' o
        with their cotangents beside the joined one's, the own block's in
        float32; or, if they are larger, over a share of the experts the
        held rows' buffers of a sequence's `held_chunk` rows (two of the
        stream's width and the feed-forward's products) and the rows'
        float32 sum, spread over the sequence's rows: no record prices them
        in a scanned stack (`_RoutedFF.holds` of a share is 0, and
        `_SparseAttention.holds` stands a byte a key for them), two rows a
        token double them, 1.36 and 0.27 GB at 32,768 rows over 16 of 128
        experts, and they are the feed-forward's backward's, where the
        join's four arrays are gone: the plan's fullest position is there
        (PR 73). And what the compiler holds for a scanned stack of these
        layers beside the one the block has: the other layers' weights in
        the compute dtype, hoisted out of the loop (0.74 GB for four layers
        in `sdar.tokens16k`'s plan). The sum stands 0.91 GB over the chip
        that way (1.15 before) and refuses `attn_qkv` by 0.05 GB, which is
        what the chip wants: at PR 70 the plan with the name stood at 15.99
        GB of the 16.91 a v5e offers a program, fitted by fusions the
        compiler made again; since PR 71's kernels it is 15.24 GB with
        none, the chip holds 15.18, and the step is 2.0 % slower than the
        one that makes q, k and v again (PERF.md section 6, PRs 70 and
        73)."""
        h, dh, item = self.heads(cfg), cfg.head_dim, _item(cfg)
        rows = cfg.rows_per_token * cfg.max_seq_len
        buffers = 0
        if cfg.n_experts and cfg.held[1] < cfg.n_experts:
            buffers = (_held_buffer_bytes(cfg, rows, 1) // (rows * item)
                       + cfg.d_model * 4 // item)
        if _bd_own_join_kernels(cfg, cfg.max_seq_len):
            round_the_join = h * dh * 4
        else:
            round_the_join = (2 * cfg.diffusion_block * h * 4 // item
                              + h * dh * (3 + 2 * 4 // item))
        ff = _FEED_FORWARDS["routed_ff" if cfg.n_experts else "dense_ff"]
        hoisted = ((cfg.n_layers - 1) * (self.params(cfg) + ff.params(cfg))
                   // rows)
        return super().holds(cfg) + max(round_the_join, buffers) + hoisted

    def flops(self, cfg, seq_len):
        # a token's operations: both of its rows through the projections,
        # and q k^T and p v over the pairs the mask leaves, `seq_len +
        # diffusion_block` a head for the two rows (`pairs_per_token`): the
        # staircase skips the tiles above its diagonal as the causal walk
        # does. The stack's two rows a token: `_fwd_flops_per_token`
        return 2 * self.params(cfg), (
            2 * 2 * self.heads(cfg) * cfg.head_dim
            * pairs_per_token(seq_len, cfg.diffusion_block)
            / cfg.rows_per_token)


def _ff_gates(cfg) -> Tuple[str, ...]:
    """The products of a feed-forward that have names, but for `up`."""
    return ("gate",) if cfg.gated else ()


class _DenseFF(Sublayer):
    """The dense feed-forward (`_feed_forward`): `ff_dim` wide, and
    `d_ff_dense` where it leads a stack of routed ones."""

    matmuls = ("w_gate", "w_up", "w_down")
    names = ("mlp_gate", "mlp_up")
    takes_post_norm = True
    takes_layer_norm = True
    takes_unit_offset = True
    takes_multipliers = True

    def _width(self, cfg) -> int:
        if cfg.n_experts and cfg.d_ff_dense is not None:
            return cfg.d_ff_dense
        return cfg.ff_dim

    def init(self, key, cfg, L):
        d, f = cfg.d_model, self._width(cfg)
        ks = jax.random.split(key, 7)
        leaves = _norm_leaves("mlp_norm", cfg, L)
        if cfg.gated:
            leaves["w_gate"] = _dense(ks[4], (L, d, f), d)
        leaves["w_up"] = _dense(ks[5], (L, d, f), d)
        leaves["w_down"] = _dense(ks[6], (L, f, d), f)
        if cfg.post_norm:
            leaves["mlp_post_norm"] = jnp.ones((L, d), jnp.float32)
        return leaves

    def axes(self, cfg):
        table = {
            **_norm_axes("mlp_norm", cfg),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        }
        if not cfg.gated:  # an ungated feed-forward has no gate's weights
            table.pop("w_gate")
        if cfg.post_norm:
            table["mlp_post_norm"] = ("layers", None)
        return table

    def forward(self, x, blk, cfg, site):
        with jax.named_scope("mlp"):
            y = _block_norm(x, blk, "mlp_norm", cfg)
            out = _feed_forward(y, blk, cfg.dtype, ("mlp_gate", "mlp_up"))
            if "mlp_post_norm" in blk:
                out = _post_norm(out, blk["mlp_post_norm"], cfg)
            return x + _to_the_stream(out, cfg), None

    def widths(self, cfg):
        return {"mlp_" + name: self._width(cfg)
                for name in (*_ff_gates(cfg), "up")}

    def params(self, cfg):
        return cfg.ff_matrices * cfg.d_model * self._width(cfg)

    def holds(self, cfg):
        # the hidden product; `w_down`'s before its norm
        return self._width(cfg) + (cfg.d_model if cfg.post_norm else 0)


class _RoutedFF(Sublayer):
    """The routed feed-forward (`_routed_ffn`) over the `held` of
    `n_experts`, and with `n_shared_experts` one dense feed-forward beside
    it that every token goes through, unweighted."""

    matmuls = ("ws_gate", "ws_up", "ws_down")
    moe_weights = ("router", "w_gate", "w_up", "w_down")
    names = ("moe_slots", "moe_gate", "moe_up", "shared_gate", "shared_up")
    readings = (
        Reading("aux_loss", "sum under seq_aux", adds="router_aux_loss_coef"),
        Reading("z_loss", "mean", adds="router_z_loss_coef"),
        Reading("expert_index", step=False),  # [L, B T, k]
        # [L, E] slots, every row sums to B T k; of a share of the experts
        # (`experts_held`, an `expert` axis), [L] or a device each [L, n],
        # the slots whose expert it holds and those of them it did not
        # compute (0); under an `expert` axis `expert_load` by device [L, n]
        *(Reading(name, over_steps=True)
          for name in ("expert_load", "held_slots", "dropped_slots")),
        Reading("chip_load"),
        Reading("chip_load_max_over_mean", "fullest over mean", "chip_load",
                over_steps=True),
    )

    def account(self, static, stacked):
        """`ops/moe.py` `layer_steps`, a share's over the buffers' rows that
        its trace asked for."""
        load = stacked["expert_load"]
        of_share = {name: stacked[name].reshape(*load.shape[:2], -1)
                    for name in ("held_slots", "dropped_slots")
                    if name in stacked}
        return moe.layer_steps(
            load, **of_share, chunk=static.get("held_chunk"),
            chip_load_max_over_mean=stacked.get("chip_load_max_over_mean"))

    def init(self, key, cfg, L):
        d, f, held = cfg.d_model, cfg.ff_dim, cfg.held[1]
        ks = jax.random.split(key, 7)
        # the (held) experts are stacked behind the layer axis
        leaves = {"mlp_norm": jnp.ones((L, d), jnp.float32)}
        if cfg.gated:
            leaves["w_gate"] = _dense(ks[4], (L, held, d, f), d)
        leaves["w_up"] = _dense(ks[5], (L, held, d, f), d)
        leaves["w_down"] = _dense(ks[6], (L, held, f, d), f)
        leaves["router"] = _dense(
            jax.random.fold_in(key, 7), (L, d, cfg.n_experts), d)
        if cfg.n_shared_experts:
            fs = cfg.shared_dim
            kg, ku, kd = jax.random.split(jax.random.fold_in(key, 8), 3)
            if cfg.gated:
                leaves["ws_gate"] = _dense(kg, (L, d, fs), d)
            leaves["ws_up"] = _dense(ku, (L, d, fs), d)
            leaves["ws_down"] = _dense(kd, (L, fs, d), fs)
        return leaves

    def axes(self, cfg):
        table = {
            "mlp_norm": ("layers", None),
            "w_gate": ("layers", "experts", "embed", "mlp"),
            "w_up": ("layers", "experts", "embed", "mlp"),
            "w_down": ("layers", "experts", "mlp", "embed"),
            "router": ("layers", "embed", None),
        }
        if cfg.n_shared_experts:
            table.update({
                "ws_gate": ("layers", "embed", "mlp"),
                "ws_up": ("layers", "embed", "mlp"),
                "ws_down": ("layers", "mlp", "embed"),
            })
        if not cfg.gated:
            table.pop("w_gate")
            table.pop("ws_gate", None)
        return table

    def forward(self, x, blk, cfg, site):
        with jax.named_scope("mlp"):
            y = fused_rmsnorm(x, blk["mlp_norm"], eps=cfg.norm_eps)
            routed, readings = _routed_ffn(y, blk, cfg, site.mesh, site.bias)
            if "ws_up" in blk:  # every token, unweighted, whole on a share
                with jax.named_scope("moe_shared"):
                    routed = routed + _feed_forward(
                        y, blk, cfg.dtype, ("shared_gate", "shared_up"), "ws")
            return x + routed, readings

    def widths(self, cfg):
        k, f = cfg.experts_per_token, cfg.ff_dim
        widths = {}
        # a layer that holds a share of the experts is one operation whose
        # backward makes its rows again chunk by chunk: it has no names
        if cfg.held[1] == cfg.n_experts:
            widths["moe_slots"] = 2 * k * 4 // _item(cfg)
            widths.update(
                {"moe_" + name: k * f for name in (*_ff_gates(cfg), "up")})
        if cfg.n_shared_experts:  # dense work on every token, share or not
            widths.update({"shared_" + name: cfg.shared_dim
                           for name in (*_ff_gates(cfg), "up")})
        return widths

    def params(self, cfg):
        d, mats = cfg.d_model, cfg.ff_matrices
        return (d * cfg.n_experts + cfg.held[1] * mats * d * cfg.ff_dim
                + (mats * d * cfg.shared_dim if cfg.n_shared_experts else 0))

    def holds(self, cfg):
        """The dispatched rows, their cotangent and the experts' hidden
        product, where the layer holds every expert (`olmoe.tokens4k`'s plan
        has four arrays of the dispatched rows' size in the experts'
        backward and none of attention's cotangents beside them); the shared
        experts' hidden product. A share's held rows' buffers are the
        layer's frame's (`_terms`, `_held_buffer_bytes`), not a width a
        token."""
        rows = (cfg.experts_per_token * (2 * cfg.d_model + cfg.ff_dim)
                if cfg.held[1] == cfg.n_experts else 0)
        return rows + (cfg.shared_dim if cfg.n_shared_experts else 0)

    def flops(self, cfg, seq_len):
        d, mats = cfg.d_model, cfg.ff_matrices
        # active parameters: the router and the held among a token's experts
        matmul = 2 * d * cfg.n_experts + (
            cfg.experts_per_token * cfg.held[1] / max(cfg.n_experts, 1)
            * 2 * mats * d * cfg.ff_dim)
        if cfg.n_shared_experts:
            matmul += 2 * mats * d * cfg.shared_dim  # every token, whole
        return matmul, 0


# The two tables. A `model_config` PR that draws a new kind of layer adds
# its record here (and its `checkpoint_name`s to `_SAVE_ORDER`): the names
# are those `layer_types` and `sublayer_types` may hold.
_OPERATORS: Dict[str, Sublayer] = {
    "full_attention": _PlainAttention(sliding=False),
    "sliding_attention": _PlainAttention(sliding=True),
    "sparse_attention": _SparseAttention(),
    "latent_attention": _LatentAttention(),
    "conv": _ShortConv(),
    "mamba2": _Mamba2(),
    "kda": _KDA(),
    "mamba1": _Mamba1(emits=False),
    "mamba1_emit": _Mamba1(emits=True),
    "diff_attention": _DiffAttention(),
    "sliding_diff_attention": _DiffAttention(sliding=True),
    "diff_attention_emit": _DiffAttention(emits=True),
    "cross_diff_attention": _DiffAttention(cross=True),
    "gmu": _GMU(),
    "eva_attention": _EvaAttention(),
    "block_diffusion_attention": _BlockDiffusionAttention(),
}
_FEED_FORWARDS: Dict[str, Sublayer] = {
    "dense_ff": _DenseFF(),
    "routed_ff": _RoutedFF(),
}
# every record, the operators' first
_RECORDS = (*_OPERATORS.values(), *_FEED_FORWARDS.values())


def _sublayers(kind: LayerKind) -> Tuple[Sublayer, ...]:
    """The records of a layer of `kind`: its operator's, then its
    feed-forward's."""
    operator = () if kind.op is None else (_OPERATORS[kind.op],)
    if not kind.ff:
        return operator
    return (*operator,
            _FEED_FORWARDS["routed_ff" if kind.routed else "dense_ff"])


# ------------------------------------------------------------------ params

def _blocks_init(k_blk, cfg: TransformerConfig, kind: LayerKind, L: int):
    """`L` layers of one kind, every leaf stacked on a leading layer axis."""
    return {name: leaf for sub in _sublayers(kind)
            for name, leaf in sub.init(k_blk, cfg, L).items()}


def _one_kind(segs: List[Segment]) -> bool:
    return len(segs) == 1 and len(segs[0].layout) == 1


def transformer_init(rng, cfg: TransformerConfig) -> Dict[str, Any]:
    """f32 master params. Block params are stacked on a leading layer axis:
    one tree for a model of one kind of layer, else a list of segments, each
    a list of one tree per layer of its period, stacked over its periods."""
    k_emb, k_blk, k_out = jax.random.split(rng, 3)
    d = cfg.d_model
    segs = segments(cfg)
    if _one_kind(segs):
        blocks = _blocks_init(k_blk, cfg, segs[0].layout[0], cfg.n_layers)
    else:
        blocks = [
            [_blocks_init(jax.random.fold_in(jax.random.fold_in(k_blk, si), pi),
                          cfg, kind, seg.periods)
             for pi, kind in enumerate(seg.layout)]
            for si, seg in enumerate(segs)]
    params = {
        "embed": jax.random.normal(
            k_emb, (cfg.vocab_size, d), jnp.float32
        ) * (0.02 if cfg.embed_init_std is None else cfg.embed_init_std),
        "blocks": blocks,
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if cfg.layer_norm:
        params["final_norm_bias"] = jnp.zeros((d,), jnp.float32)
    if cfg.norm_unit_offset:  # the scale is 1 + g
        params["final_norm"] = jnp.zeros((d,), jnp.float32)
    if cfg.n_pred_heads > 1 and (cfg.tied_embeddings or cfg.exit_gate):
        raise ValueError(
            f"n_pred_heads {cfg.n_pred_heads} with tied embeddings or an exit "
            "gate: a head of several positions is a matrix of its own, and "
            "the exit loss reads one target a position")
    if not cfg.tied_embeddings:
        params["unembed"] = _dense(k_out, (d, cfg.head_width), d)
    if cfg.exit_gate:  # one `Linear(d, 1)` for all the passes
        params["exit_w"] = _dense(jax.random.fold_in(k_out, 1), (d,), d)
        params["exit_b"] = jnp.zeros((), jnp.float32)
    return _at_init_std(params, cfg)


def _at_init_std(params, cfg: TransformerConfig):
    """`params` with every matrix at `init_std`: the draws are the ones they
    were, rescaled from `1 / sqrt(fan-in)` (a matrix's rows) and, the
    embedding's, from 0.02. None: as they are."""
    if cfg.init_std is None:
        return params
    if cfg.embed_init_std is not None:
        raise ValueError(
            f"init_std {cfg.init_std} and embed_init_std "
            f"{cfg.embed_init_std}: init_std draws the embedding too")
    std = cfg.init_std
    kinds = [kind for seg in segments(cfg) for kind in seg.layout]
    trees = [blk for blks in _segment_trees(params["blocks"]) for blk in blks]
    for kind, blk in zip(kinds, trees):
        for name in own_buffer_weights(blk, kind):
            blk[name] = blk[name] * (std * math.sqrt(blk[name].shape[-2]))
    params["embed"] = params["embed"] * (std / 0.02)
    if "unembed" in params:
        params["unembed"] = params["unembed"] * (std * math.sqrt(cfg.d_model))
    return params


def expert_bias_init(cfg: TransformerConfig):
    """The routers' selection bias, [routed layers, n_experts] float32:
    zeros, as training starts. State of the step, not a parameter."""
    return jnp.zeros((cfg.n_routed_layers, cfg.n_experts), jnp.float32)


_LOGICAL_AXES = {
    "embed": ("vocab", "embed"),
    "unembed": ("embed", "vocab"),
    "final_norm": (None,),
    "final_norm_bias": (None,),
    "exit_w": (None,),
    "exit_b": (),
}


def _block_axes(cfg: TransformerConfig, kind: LayerKind):
    return {name: axes for sub in _sublayers(kind)
            for name, axes in sub.axes(cfg).items()}


def param_shardings(mesh, cfg: TransformerConfig):
    """NamedSharding pytree matching transformer_init's structure, derived
    from the logical-axis table + default_transformer_rules."""
    rules = mesh_lib.default_transformer_rules(mesh)

    def build(node):
        if isinstance(node, dict):
            return {k: build(v) for k, v in node.items()}
        if isinstance(node, list):
            return [build(v) for v in node]
        return NamedSharding(mesh, rules.spec(node))

    table = dict(_LOGICAL_AXES)
    if cfg.tied_embeddings:
        table.pop("unembed", None)
    if not cfg.exit_gate:
        del table["exit_w"], table["exit_b"]
    if not cfg.layer_norm:
        del table["final_norm_bias"]
    segs = segments(cfg)
    if _one_kind(segs):
        table["blocks"] = _block_axes(cfg, segs[0].layout[0])
    else:
        table["blocks"] = [[_block_axes(cfg, kind) for kind in seg.layout]
                           for seg in segs]
    return build(table)


# ------------------------------------------------- the block and the stack

def own_buffer_weights(blk, kind: LayerKind) -> Tuple[str, ...]:
    """The leaves of a block of `kind` that `_own_weights` hands its
    matmuls: the weights of its plain matmuls (`Sublayer.matmuls`) that it
    holds. The routed experts' go to `ops/moe.py` as they are, and the
    norms' vectors and the router are no such matmul."""
    return tuple(name for sub in _sublayers(kind) for name in sub.matmuls
                 if name in blk)


def _own_weights(blk, kind: LayerKind, dt, sliced: bool):
    """`blk` with the weights of its plain matmuls in the compute dtype,
    each behind a barrier, so that the sites' `blk[name].astype(dt)` finds
    them made.

    Left to itself XLA fuses what takes a weight's gradient into the matmul
    that makes it: the cast to float32 and the dynamic update of the scanned
    stack's gradient, and in a segment of one period (whose loop it
    unrolls) AdamW's whole update of the weight and its two moments. The
    TPU compiler then tiles that matmul worse: 37 to 53 % of the MXU's peak
    with AdamW inside and 69 to 80 % with the update, for 69 to 85 % into a
    buffer (PERF.md section 6, PR 37; the head's had the same in PR 28).
    `_own_cotangent` puts the barrier on the gradient alone: the matmul
    writes a `[d, f]` array in `dt`, and what was fused in runs after it at
    the memory's pace.

    Where the weight arrives whole (`sliced` false: a segment of one
    period) `_own_buffer` also makes the cast an array of its own for the
    forward, the rematerialised and the input-gradient matmuls, which read
    a float32 `[1, d, f]` through a fused cast at half the pace (12.0 ms for
    17.9). A slice of a scanned stack is left to the compiler, which makes
    the slice and the cast one operand of the matmul: there the copy costs
    6 bytes an element a pass and the chip shows no matmul the faster for
    it (`mistral7b.tokens4k`: -0.7 %)."""
    own = _own_cotangent if sliced else _own_buffer
    return {**blk, **{name: own(blk[name].astype(dt))
                      for name in own_buffer_weights(blk, kind)}}


def _periods(blk) -> int:
    """The length of a block tree's leading layer axis."""
    return jax.tree.leaves(blk)[0].shape[0]


def _segment_trees(blocks):
    """`params["blocks"]` as its segments, each one tree per layer of its
    period: a model of one kind of layer is one segment of it."""
    return [[blocks]] if isinstance(blocks, dict) else blocks


def own_buffers(blocks, cfg: TransformerConfig) -> Tuple[int, int, int]:
    """(the buffers `_own_weights` makes for the layers of
    `params["blocks"]`: one for every matmul weight's gradient and one more
    for every weight of a segment of one period; their bytes in the compute
    dtype, whole as a matmul takes them; the widest layer's weights'
    bytes)."""
    item = _item(cfg)
    count = total = widest = 0
    kinds = [kind for seg in segments(cfg) for kind in seg.layout]
    trees = [blk for blks in _segment_trees(blocks) for blk in blks]
    for kind, blk in zip(kinds, trees):
        names = own_buffer_weights(blk, kind)
        periods = _periods(blk)
        layer = item * sum(math.prod(blk[name].shape[1:]) for name in names)
        each = 2 if periods == 1 else 1
        count += each * periods * len(names)
        total += each * periods * layer
        widest = max(widest, layer)
    return count, total, widest


def _block(x, blk, positions, bias, cfg: TransformerConfig, kind: LayerKind,
           seq_axis: Optional[str], seq_size: int, mesh=None,
           keep_ctx: bool = False, sliced: bool = False, shared=None,
           depth=None):
    """One block of `kind`: (x, the sublayers' readings or None), through
    its sublayers' forwards in turn. `keep_ctx`: the attention kernel names
    its backward's residuals `attn_ctx`. `sliced`: `blk` is one of several
    periods of a scanned stack (`_own_weights`). `shared`: what the
    sublayers read of earlier layers; `depth`: the layer's, where a
    sublayer reads it."""
    return _block_and_emitted(
        x, blk, positions, bias, cfg, kind, seq_axis, seq_size, mesh,
        keep_ctx, sliced, shared, depth)[:2]


def _block_and_emitted(x, blk, positions, bias, cfg: TransformerConfig,
                       kind: LayerKind, seq_axis: Optional[str],
                       seq_size: int, mesh=None, keep_ctx: bool = False,
                       sliced: bool = False, shared=None, depth=None):
    """`_block`'s two and {name: value} of what the block's sublayers emit
    for later layers (`Sublayer.emits`): what the walk runs."""
    blk = _own_weights(blk, kind, cfg.dtype, sliced)
    site = _Site(positions, bias, seq_axis, seq_size, mesh, keep_ctx, shared,
                 depth)
    sublayers = _sublayers(kind)
    if seq_axis is not None:
        for sub in sublayers:
            if sub.no_sequence_axis:
                raise NotImplementedError(sub.no_sequence_axis)
    if cfg.heads_held and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            f"heads_held {tuple(cfg.heads_held)} is one chip's share of the "
            f"heads: no `tensor` axis sums the partial results over a mesh "
            f"of {mesh.size} devices yet")
    readings, emitted = None, {}
    for sub in sublayers:  # an operator's readings and the feed-forward's
        x, made = sub.forward(x, blk, cfg, site)
        if sub.emits:  # among what it made, under their names
            made = dict(made)
            emitted.update({name: made.pop(name) for name in sub.emits})
        if made:
            readings = made if readings is None else {**readings, **made}
    return x, readings, emitted


def _layer_axis(readings, stack: bool):
    """Layers' readings ({name: array with a leading layer axis}) as one
    such mapping, each reading over the layers that make it (a routed
    layer's, an operator's): `stack` interleaves them (the layers of a
    period, scanned over the periods), else they follow one another
    (segments)."""
    def joined(xs):
        if len(xs) == 1:
            return xs[0]
        if stack:
            return jnp.stack(xs, axis=1).reshape(-1, *xs[0].shape[1:])
        return jnp.concatenate(xs, axis=0)

    names = dict.fromkeys(name for made in readings for name in made)
    return {name: joined([made[name] for made in readings if name in made])
            for name in names}


def _hidden_and_readings(params, tokens, cfg: TransformerConfig,
                         positions=None, seq_axis: Optional[str] = None,
                         seq_size: int = 1, mesh=None, expert_bias=None,
                         saved_names: Tuple[str, ...] = ()):
    """`transformer_hidden`, and the routed feed-forwards' readings stacked
    on a leading layer axis (None for a dense model). `expert_bias`
    [routed layers, E] is the routers' selection bias. Under `cfg.remat` a
    block's input is its checkpoint; `saved_names` are the activations kept
    beside it (`saved_activations`; or a plain tuple of names, at every
    pass), none by default.

    With `loop_steps` > 1 the walk over the segments is run that many times
    over the same `params["blocks"]`, the final norm after every pass and
    its output the next pass's input, as one `lax.scan` over the passes
    (the program is traced and lowered once, not `loop_steps` times), and
    what comes back is every pass's normed stream,
    `[loop_steps, B, T, d]`: a weight's gradient is the sum over its
    uses. Under `cfg.remat` the looped stack is differentiated by its own
    backward (`_looped_under_remat`), which keeps a name for the last of
    the passes that `saved_names` gives it; without `remat` JAX
    differentiates the two scans."""
    _refuse_unmapped_loop(cfg, seq_axis, mesh)
    kept = _kept(cfg, saved_names)  # the one form, from here down
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        if cfg.embedding_multiplier != 1:
            x = x * cfg.embedding_multiplier

    # no policy at all for an empty choice: the step is then the one that
    # keeps nothing, instruction for instruction
    policy = (jax.checkpoint_policies.save_only_these_names(*kept)
              if kept else None)

    def block_that_keeps(names, kind: LayerKind, sliced: bool, **kw):
        return partial(_block_and_emitted, cfg=cfg, kind=kind,
                       seq_axis=seq_axis, seq_size=seq_size, mesh=mesh,
                       keep_ctx="attn_ctx" in names, sliced=sliced, **kw)

    def scan_body(sliced: bool, layout: Tuple[LayerKind, ...], shared):
        def block_fn(kind: LayerKind):
            blk_fn = block_that_keeps(kept, kind, sliced)
            if cfg.remat:
                blk_fn = jax.checkpoint(blk_fn, policy=policy,
                                        static_argnums=())
            return blk_fn

        blk_fns = {kind: block_fn(kind) for kind in set(layout)}

        def body(x, period):
            blks, biases, depths = period
            readings, carried, emitted = [], dict(shared), {}
            for kind, blk, bias, depth in zip(layout, blks, biases, depths):
                taken = {name: carried[name] for sub in _sublayers(kind)
                         for name in sub.reads}
                beside = {**({"shared": taken} if taken else {}),
                          **({} if depth is None else {"depth": depth})}
                x, reading, made = blk_fns[kind](
                    x, blk, positions, bias, **beside)
                carried.update(made)
                emitted.update(made)
                if reading is not None:
                    readings.append(reading)
            return x, (readings, emitted)

        return body

    def walk(x):
        """The stream through every segment once, and the layers'
        readings. What a layer emits (`Sublayer.emits`) goes beside the
        stream to the layers that read it: inside a period from layer to
        layer, and from a segment to the later ones, whose scans take it as
        a constant (its cotangent is the sum over the readers, in float32
        where a scan of several periods makes it: the value is handed over
        in float32 then)."""
        readings, routed_before, shared, first = [], 0, {}, 0
        for seg, blks in zip(segments(cfg), _segment_trees(params["blocks"])):
            periods = _periods(blks[0])
            # this segment's rows of the bias, one [periods, E] per routed
            # layer of its period
            biases = [None] * len(blks)
            routed = [i for i, kind in enumerate(seg.layout) if kind.routed]
            if expert_bias is not None and routed:
                rows = expert_bias[
                    routed_before:routed_before + periods * len(routed)
                ].reshape(periods, len(routed), -1)
                routed_before += periods * len(routed)
                for j, i in enumerate(routed):
                    biases[i] = rows[:, j]
            # the depths of the layers that read theirs, [periods] each
            depths = [
                jnp.asarray(cfg.depths[first + i:first + periods * len(blks)
                                       :len(blks)], jnp.float32)
                if any(sub.reads_depth for sub in _sublayers(kind)) else None
                for i, kind in enumerate(seg.layout)]
            first += periods * len(blks)
            x, (of_period, emitted) = jax.lax.scan(
                scan_body(periods > 1, seg.layout,
                          _for_readers(shared, seg)),
                x, (blks, biases, depths))
            # an emitter stands in a segment of one period (`_check_carried`)
            shared.update(jax.tree.map(lambda a: a[0], emitted))
            if of_period:
                readings.append(_layer_axis(of_period, stack=True))
        return x, _layer_axis(readings, stack=False) if readings else None

    def final_norm(x, scale=params["final_norm"]):
        with jax.named_scope("final_norm"):
            if cfg.layer_norm:
                return _layer_norm(x, scale, params["final_norm_bias"],
                                   cfg.norm_eps)
            return fused_rmsnorm(x, _norm_scale(scale, cfg), eps=cfg.norm_eps)

    if cfg.loop_steps == 1:
        x, readings = walk(x)
        return final_norm(x), readings

    if cfg.remat:
        def block_of(kind: LayerKind, sliced: bool, names: Tuple[str, ...]):
            fn = block_that_keeps(names, kind, sliced, bias=None)

            def block(x, blk, positions):
                with jax.named_scope("ut_pass"):
                    return fn(x, blk, positions)[0]

            return block

        # the norm is made again in the backward, as a block is
        streams = _looped_under_remat(
            x, _segment_trees(params["blocks"]), params["final_norm"],
            positions, segments(cfg), cfg.loop_steps, kept, block_of,
            jax.checkpoint(final_norm))
        return streams, None

    def one_pass(x, _):
        with jax.named_scope("ut_pass"):
            x, _ = walk(x)
        normed = final_norm(x)
        return _next_pass_input(x, normed), normed

    _, streams = jax.lax.scan(one_pass, x, None, length=cfg.loop_steps)
    return streams, None


def _for_readers(shared, seg: Segment):
    """Of the values emitted so far, those that the layers of `seg` read,
    as its scan takes them: in float32 where it has several periods, so
    that the scan's sum of the readers' cotangents is a float32 one."""
    reads = {name for kind in seg.layout for sub in _sublayers(kind)
             for name in sub.reads if name in shared}
    if seg.periods == 1:
        return {name: shared[name] for name in reads}
    with jax.named_scope("shared_emit"):
        return {name: jax.tree.map(lambda a: a.astype(jnp.float32),
                                   shared[name]) for name in reads}


def _next_pass_input(left, normed):
    """What a pass hands the next: the stream as the final norm leaves it,
    not as the last layer left it. Under a name of its own: a test hands on
    the other to show what the comparison reads then."""
    return normed


class _Halves(NamedTuple):
    """A block under `jax.checkpoint` cut where `jax.vjp` cuts it
    (`_halves`)."""
    forward: Any  # ClosedJaxpr: the flat arguments -> (out, *leaves)
    pull: Any     # the pullback's treedef: its leaves are `sources`'
    # of each leaf, the flat argument it is (its index) or the
    # `checkpoint_name` it was kept under
    sources: Tuple[Union[int, str], ...]

    def kept(self, leaves) -> Dict[str, list]:
        """{name: its values among `leaves`, in the program's order}."""
        out: Dict[str, list] = {}
        for leaf, source in zip(leaves, self.sources):
            if isinstance(source, str):
                out.setdefault(source, []).append(leaf)
        return out


def _halves(fn, names: Tuple[str, ...], *args) -> _Halves:
    """`fn(*args) -> array` under `jax.checkpoint` keeping `names`, as the
    two halves `jax.vjp` makes of it, so that one loop can run the first and
    another the second: the forward as a jaxpr that also hands out what the
    pullback holds, and the pullback as the treedef those leaves fill. What
    such a pullback holds is its arguments and the kept names' values, in
    the program's order; which is which is read off the jaxpr (a kept value
    comes out of a `name` equation)."""
    policy = (jax.checkpoint_policies.save_only_these_names(*names)
              if names else None)
    checkpointed = jax.checkpoint(fn, policy=policy)
    seen = {}

    def forward(*args):
        out, pull = jax.vjp(checkpointed, *args)
        leaves, seen["pull"] = jax.tree.flatten(pull)
        return out, leaves

    closed = jax.make_jaxpr(forward)(*args)
    jaxpr = closed.jaxpr
    made_by = {out: eqn for eqn in jaxpr.eqns for out in eqn.outvars}

    def source(var):
        if var in jaxpr.invars:
            return jaxpr.invars.index(var)
        eqn = made_by.get(var)
        while eqn is not None and eqn.primitive.name != "name":
            # the barrier a kept value passes on its way out
            eqn = made_by.get(eqn.invars[0]) if len(eqn.invars) == 1 else None
        if eqn is None or eqn.params["name"] not in names:
            raise NotImplementedError(
                f"a block's pullback holds {var.aval.str_short()}, which is "
                f"neither one of its arguments nor one of {names}")
        return eqn.params["name"]

    return _Halves(closed, seen["pull"],
                   tuple(source(var) for var in jaxpr.outvars[1:]))


def _looped_under_remat(x, trees, norm_scale, positions, segs, passes: int,
                        kept: Dict[str, int], block_of, final_norm):
    """The stack of `segs` (`segments(cfg)`; `trees` their stacked weights)
    run `passes` times from `x`, the final norm after every pass: the
    passes' normed streams `[passes, B, T, d]`, as one unit of
    differentiation.

    JAX's own transpose of a scan over the passes round a scan over the
    layers holds the layers' float32 gradient twice (the pass in hand beside
    the sum over the passes) and slices the pass's blocks' inputs out of all
    of them. Here the forward is those two scans and keeps the blocks'
    inputs `[passes, periods, B, T, d]`, the final norm's inputs and, of
    each name of `kept` ({name: k}), the values of its last k passes; the
    backward is a loop over the passes from the last to the first and
    inside it one over a segment's periods, which reads a block's input and
    its weights by index, makes the block again under `jax.checkpoint` (the
    names the pass keeps read by index too, the others made again), pulls
    the stream's cotangent back through it and adds the weights' gradient
    into its row of the one accumulator the loops carry.
    `block_of(kind, sliced, names)` is a block `(x, blk, positions) -> x`
    that keeps `names`; `final_norm(x, scale)`."""
    first = {name: passes - k for name, k in kept.items()}  # pass to keep it
    # the passes at which more is kept than in the pass before, and what a
    # pass keeps then: one backward of the block each, chosen by the pass
    grows = sorted(set(first.values()) - {0})
    choices = [tuple(name for name in kept if first[name] <= at)
               for at in (0, *grows)]

    def shape_of(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    halves = [[[_halves(
        block_of(kind, seg.periods > 1, names), names, shape_of(x),
        shape_of(jax.tree.map(lambda w: w[0], blk)), shape_of(positions))
        for names in choices] for kind, blk in zip(seg.layout, blks)]
        for seg, blks in zip(segs, trees)]

    def row(name, periods, p, j):
        """Where pass p's period j lies among a kept name's values; a pass
        that does not keep the name lands on the first row, which the first
        pass that does writes after it."""
        return jnp.clip((p - first[name]) * periods + j, 0,
                        kept[name] * periods - 1)

    def run(x, trees, norm_scale, positions, keep: bool):
        """(the passes' streams; with `keep` what the backward reads: the
        blocks' inputs, the final norm's, the kept names' values)."""
        # the kept names' values of the passes that keep them, no row yet
        store = [[{name: [jnp.zeros((kept[name] * seg.periods, *a.shape),
                                    a.dtype) for a in avals]
                   for name, avals in of_choice[-1].kept(
                       of_choice[-1].forward.out_avals[1:]).items()}
                  for of_choice in of_seg]
                 for seg, of_seg in zip(segs, halves)] if keep else None

        def one_pass(carry, p):
            x, store = carry
            inputs = []
            for s, (seg, blks) in enumerate(zip(segs, trees)):
                def period(carry, of_period, s=s, seg=seg):
                    x, stored = carry
                    blks, j = of_period
                    ins, stored = [], list(stored) if keep else None
                    for i, blk in enumerate(blks):
                        ins.append(x)
                        half = halves[s][i][-1]
                        x, *leaves = jax.core.eval_jaxpr(
                            half.forward.jaxpr, half.forward.consts,
                            *jax.tree.leaves((x, blk, positions)))
                        if not keep:
                            continue
                        made = half.kept(leaves)
                        at = {name: row(name, seg.periods, p, j)
                              for name in made}
                        stored[i] = {
                            name: [buf.at[at[name]].set(value) for buf, value
                                   in zip(stored[i][name], made[name])]
                            for name in made}
                    return (x, stored), ins

                (x, of_seg), ins = jax.lax.scan(
                    period, (x, store[s] if keep else None),
                    (blks, jnp.arange(seg.periods)))
                if keep:
                    store = [*store[:s], of_seg, *store[s + 1:]]
                inputs.append(ins)
            normed = final_norm(x, norm_scale)
            return (_next_pass_input(x, normed), store), (inputs, x, normed)

        (_, store), (inputs, ends, streams) = jax.lax.scan(
            one_pass, (x, store), jnp.arange(passes))
        return streams, (inputs, ends, store)

    @jax.custom_vjp
    def stack(x, trees, norm_scale, positions):
        return run(x, trees, norm_scale, positions, keep=False)[0]

    def forward(x, trees, norm_scale, positions):
        streams, made = run(x, trees, norm_scale, positions, keep=True)
        return streams, (made, trees, norm_scale, positions)

    def pass_end(x, scale):
        normed = final_norm(x, scale)
        return _next_pass_input(x, normed), normed

    def backward(held, d_streams):
        (inputs, ends, store), trees, norm_scale, positions = held

        def pulled(s, i, choice, ct, x, blk, p, j):
            """The block's pullback at `ct` under the names of `choice`,
            the kept values read from their rows."""
            half = halves[s][i][choice]
            flat = jax.tree.leaves((x, blk, positions))
            rows = {name: iter(bufs) for name, bufs in store[s][i].items()}
            leaves = [
                flat[source] if isinstance(source, int) else
                next(rows[source])[row(source, segs[s].periods, p, j)]
                for source in half.sources]
            d_x, d_blk, _ = jax.tree.unflatten(half.pull, leaves)(ct)
            return d_x, d_blk

        def one_pass(choice, last, n, carry):
            ct, grads, d_scale = carry
            p = last - n
            ct, d = jax.vjp(pass_end, ends[p], norm_scale)[1](
                (ct, d_streams[p]))
            d_scale = d_scale + d
            grads = list(grads)
            for s in reversed(range(len(segs))):
                seg = segs[s]

                def period(m, carry, s=s, seg=seg):
                    ct, of_seg = carry
                    j = seg.periods - 1 - m
                    of_seg = list(of_seg)
                    for i in reversed(range(len(seg.layout))):
                        ct, d_blk = pulled(
                            s, i, choice, ct, inputs[s][i][p, j],
                            jax.tree.map(lambda w: w[j], trees[s][i]), p, j)
                        of_seg[i] = jax.tree.map(
                            lambda total, d: total.at[j].add(d),
                            of_seg[i], d_blk)
                    return ct, of_seg

                ct, grads[s] = jax.lax.fori_loop(
                    0, seg.periods, period, (ct, grads[s]))
            return ct, grads, d_scale

        carry = (jnp.zeros_like(d_streams[0]),
                 jax.tree.map(jnp.zeros_like, trees),
                 jnp.zeros_like(norm_scale))
        # a loop for each run of passes that keep the same names, the last
        # passes' first: two branches of one loop's body would each hold
        # their block's values through the other's turn
        starts = (0, *grows, passes)
        for choice in reversed(range(len(choices))):
            start, after = starts[choice], starts[choice + 1]
            carry = jax.lax.fori_loop(
                0, after - start, partial(one_pass, choice, after - 1), carry)
        ct, grads, d_scale = carry
        return ct, grads, d_scale, None

    stack.defvjp(forward, backward)
    return stack(x, trees, norm_scale, positions)


def _refuse_unmapped_loop(cfg: TransformerConfig, seq_axis, mesh) -> None:
    """What `loop_steps` > 1 is not made and tested with yet, and what a
    stack whose layers read one another's values is not."""
    if cfg.loop_steps < 1:
        raise ValueError(f"loop_steps {cfg.loop_steps}")
    carried = sorted({name for kind in cfg.layers for sub in _sublayers(kind)
                      for name in sub.emits})
    if carried and cfg.loop_steps > 1:
        raise NotImplementedError(
            f"loop_steps {cfg.loop_steps} over layers that emit {carried}: "
            "the looped stack's own backward carries the stream alone")
    if carried and seq_axis is not None:
        raise NotImplementedError(
            f"a `{seq_axis}` axis over layers that emit {carried}: a reader "
            "takes the emitter's whole sequence, which no device holds")
    if cfg.exit_gate and cfg.loop_steps == 1:
        raise ValueError(
            "exit_gate with loop_steps 1: the exit distribution is over "
            "the passes of a stack that is run several times")
    if cfg.loop_steps == 1:
        return
    axes = dict(mesh.shape) if mesh is not None else {}
    unmapped = [name for name in ("sequence", "expert")
                if axes.get(name, 1) > 1]
    if seq_axis is not None or unmapped:
        raise NotImplementedError(
            f"loop_steps {cfg.loop_steps} under a "
            f"`{seq_axis or unmapped[0]}` axis: the passes' streams and the "
            "one loss over them are not mapped over it yet")
    made = sorted({r.name for kind in cfg.layers for sub in _sublayers(kind)
                   for r in sub.readings})
    if made:
        raise NotImplementedError(
            f"loop_steps {cfg.loop_steps} over layers that make readings "
            f"({made}): they are one a layer, not one a layer a pass, yet")
    if cfg.heads_held:
        raise NotImplementedError(
            f"loop_steps {cfg.loop_steps} with heads_held "
            f"{tuple(cfg.heads_held)}: a share of the heads hands on a "
            "partial sum, which no pass can norm and take as its input")


def transformer_hidden(params, tokens, cfg: TransformerConfig, **kw):
    """Forward through the blocks: [B, T] tokens -> [B, T, d] normed hidden.

    Keywords: `positions`, `seq_axis`, `seq_size`, `mesh`, `expert_bias`,
    `saved_names`. When called under shard_map with the sequence sharded,
    pass seq_axis and positions holding GLOBAL positions so RoPE and causal
    masks are correct. When called under
    a jit that shards over `mesh`, pass the mesh: the Pallas attention
    kernel is mapped over its batch and head axes. With `loop_steps` > 1:
    the last pass's.
    """
    hidden = _hidden_and_readings(params, tokens, cfg, **kw)[0]
    return hidden if cfg.loop_steps == 1 else hidden[-1]


def _unembed(params, cfg: TransformerConfig):
    """The head `[d_model, head_width]`, float32, over `logits_scaling`:
    the division is the weights' (once a step over the head, exact at a
    power of two), so the chunked cross-entropy, its backward and every
    other reader of the head multiply the scaled logits and no
    `[tokens, vocab]` array is made for it."""
    w = params["embed"].T if cfg.tied_embeddings else params["unembed"]
    return w if cfg.logits_scaling == 1 else w / cfg.logits_scaling


def transformer_apply(params, tokens, cfg: TransformerConfig,
                      positions=None, seq_axis: Optional[str] = None,
                      seq_size: int = 1, mesh=None):
    """Forward: [B, T] int32 tokens -> [B, T, vocab] logits (f32)."""
    x = transformer_hidden(
        params, tokens, cfg, positions=positions, seq_axis=seq_axis,
        seq_size=seq_size, mesh=mesh,
    )
    return (x @ _unembed(params, cfg).astype(cfg.dtype)).astype(jnp.float32)


def _head_loss(hidden, unembed, targets, mesh=None):
    """The mean next-token cross-entropy through `lm_head_cross_entropy`.
    Under an `expert` axis each device takes the chunks of its own
    sequences against the head gathered whole (float32, once a step), and
    the head's gradient is summed over the axis in float32: left to the
    partitioner the scan over chunks runs every chunk on every device and
    all-reduces its logits, 0.4 GB a chunk at a vocabulary of 98,304
    (`mistral7b.fsdp4`'s S4, which stays as it is)."""
    axis = mesh_lib.expert_axis(mesh)
    if axis is None:
        return lm_head_cross_entropy(hidden, unembed, targets)[0]

    def own_chunks(hidden, unembed, targets):
        loss, count = lm_head_cross_entropy(hidden, unembed, targets)
        return jax.lax.psum(loss * count, axis) / jax.lax.psum(count, axis)

    return jax.shard_map(
        own_chunks, mesh=mesh, in_specs=(P(axis), P(), P(axis)),
        out_specs=P(), check_vma=False)(hidden, unembed, targets)


# the dtype of the exit gate's logits, the exit distribution and its entropy,
# under a name of its own: a test lowers it to show what the comparison
# reads then
_EXIT_F32 = jnp.float32


def exit_distribution(gate_logits):
    """(p, log p) [T, ...] float32: the distribution over the pass a token
    leaves after, from the T passes' gate logits `a` [T, ...]. With
    `lambda(t) = sigmoid(a(t))`: `p(t) = lambda(t) prod_{j<t} (1 -
    lambda(j))` for `t < T`, and the last pass takes what is left, `p(T) =
    prod_{j<T} (1 - lambda(j))`: the last gate's reading is unused. The
    logarithm is formed from log-sigmoids, never as the log of a product,
    so a gate at +-30 gives a finite one."""
    a = gate_logits.astype(_EXIT_F32)
    stay = jax.nn.log_sigmoid(-a)  # log (1 - lambda)
    stayed = jnp.cumsum(  # sum over j < t: the last gate is in none
        jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]]), axis=0)
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(a[:-1]) + stayed[:-1], stayed[-1:]], axis=0)
    return jnp.exp(log_p), log_p


def exit_probabilities(streams, exit_w, exit_b):
    """(p [T, ...], the entropy of p a token [...]) of the T passes' normed
    streams `[T, ..., d]` under the gate `exit_w` [d], `exit_b`: the gate's
    logits, the exit distribution and its entropy as the step computes
    them, float32 whatever the streams' dtype."""
    with jax.named_scope("exit_gate"):
        # a multiply and a sum, not a matmul: no float32 copy of the streams
        logits = (streams.astype(_EXIT_F32)
                  * exit_w.astype(_EXIT_F32)).sum(-1) + exit_b.astype(_EXIT_F32)
    with jax.named_scope("exit_loss"):
        p, log_p = exit_distribution(logits)
        return p, -(p * log_p).sum(0)


_EXIT_READINGS = ("ut_pass_loss", "exit_p_mean", "exit_entropy")


def _exit_loss(streams, params, targets, cfg: TransformerConfig,
               ignore_index: int = -100):
    """(`1/N sum_i [sum_t p(t)_i ce(t)_i - beta H(p_i)]`, the readings
    {ut_pass_loss [T], exit_p_mean [T], exit_entropy}) of the T passes'
    normed streams `[T, B, S, d]`: arXiv:2510.25741's first-stage
    objective, the expected cross-entropy under the exit distribution less
    `exit_entropy_coef` times its entropy (the KL to a uniform prior, up
    to a constant). The gate, the distribution and the entropy are float32.
    The four heads are ONE call of the weighted chunked cross-entropy over
    the streams stacked as T N tokens (targets tiled, weights `p(t)_i /
    N`): one float32 accumulator for the unembedding's gradient, and the
    gate learns through the weights (`d loss / d weight_i` is token i's
    cross-entropy)."""
    passes = streams.shape[0]
    mask = (targets != ignore_index).astype(jnp.float32)
    count = jnp.maximum(mask.sum(), 1.0)
    p, entropy = exit_probabilities(
        streams, params["exit_w"], params["exit_b"])
    with jax.named_scope("exit_loss"):
        entropy = ((entropy * mask).sum() / count).astype(jnp.float32)
        weights = (p * (mask / count)).astype(jnp.float32)
    with jax.named_scope("lm_head_ce"):
        expected, ce = weighted_lm_head_cross_entropy(
            streams, _unembed(params, cfg),
            jnp.broadcast_to(targets, (passes, *targets.shape)), weights)
    loss = expected - cfg.exit_entropy_coef * entropy
    return loss, dict(zip(_EXIT_READINGS, (
        ce.sum((1, 2)) / count,  # 0 at an ignored target
        (p * mask).sum((1, 2)) / count, entropy)))


def transformer_loss_and_readings(params, batch, cfg: TransformerConfig, **kw):
    """(loss, readings). Next-token CE; batch: {'tokens': [B, T+1] or
    ('tokens','targets')}.

    Uses the chunked LM-head CE (ops/fused.py lm_head_cross_entropy): the
    [B*T, V] f32 logits are never materialized, which at GPT-2 vocab sizes
    is the difference between HBM-bound and MXU-bound training steps.

    The readings are what the records of `cfg.layers` state
    (`Sublayer.readings`: a routed feed-forward's, sparse attention's,
    kda's), each over the L layers that make it, joined as its record says
    (a mean, a least, a most, or stacked [L, ...]), and the loss has what
    the record says the reading adds: the routers' balance and z losses at
    the configuration's coefficients (0 adds nothing), the indexers' own
    loss at 1. A dense model's readings are empty. `expert_bias=` [L, E] is
    the routers' selection bias, for a model that has one. A stack that is
    run `loop_steps` times under an `exit_gate` has `_exit_loss`'s loss and
    readings (`_EXIT_READINGS`); without the gate the last pass's
    cross-entropy."""
    if cfg.objective == "block_diffusion":
        return _block_diffusion_loss(params, batch, cfg, **kw)
    if "targets" in batch:
        tokens, targets = batch["tokens"], batch["targets"]
    elif cfg.n_pred_heads > 1:
        tokens, targets = next_ids(batch["tokens"], cfg.n_pred_heads)
    else:
        tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    hidden, readings = _hidden_and_readings(params, tokens, cfg, **kw)
    if cfg.exit_gate:
        return _exit_loss(hidden, params, targets, cfg)
    if cfg.loop_steps > 1:  # no gate: the last pass's head alone
        hidden = hidden[-1]
    with jax.named_scope("lm_head_ce"):
        if cfg.n_pred_heads > 1:
            loss = _multi_head_loss(hidden, _unembed(params, cfg), targets,
                                    cfg, kw.get("mesh"))
        else:
            loss = _head_loss(hidden, _unembed(params, cfg), targets,
                              kw.get("mesh"))
    return _settled(loss, readings or {}, cfg)


NOISE_LEVELS = 1 << 24  # a block's level is a whole number of these parts


def diffusion_inputs(batch, cfg: TransformerConfig):
    """(rows [B, 2 L] ids, positions [B, 2 L], weights [B, L] float32,
    masked [B, L] bool) of a block-diffusion batch `{"tokens" [B, L],
    "noise" [B, L] in [0, 2^24), "level" [B, L / diffusion_block] in
    [1, 2^24]}`, integers. Token `i` of block `b = i // diffusion_block` is
    masked where `noise_i < level_b`: with probability `t_b = level_b /
    2^24`, the block's noise level, when `noise` is uniform. `rows` is the
    noisy copy (`mask_token_id` where masked) and then the clean one, both
    at positions `0 .. L - 1`; `weights_i = m_i / t_b(i) / (B L)`, BD3-LMs'
    bound under a linear schedule (arXiv:2503.09573), so that the loss is
    their sum with the masked positions' cross-entropies. Integers in, so
    that another implementation reads the same mask to the bit: 2^24 and
    every level are exact in float32."""
    tokens, noise, level = batch["tokens"], batch["noise"], batch["level"]
    B, L = tokens.shape
    block = cfg.diffusion_block
    if noise.shape != (B, L) or level.shape != (B, L // block) or L % block:
        raise ValueError(
            f"a block-diffusion batch of tokens {tokens.shape} in blocks of "
            f"{block} takes noise of that shape and a level a block, not "
            f"{noise.shape} and {level.shape}")
    with jax.named_scope("bd_noise"):
        level = jnp.repeat(level, block, axis=1)
        masked = noise < level
        noisy = jnp.where(masked, jnp.asarray(cfg.mask_token_id, tokens.dtype),
                          tokens)
        weights = jnp.where(
            masked, NOISE_LEVELS / level.astype(jnp.float32), 0.0) / (B * L)
        positions = jnp.broadcast_to(
            jnp.tile(jnp.arange(L, dtype=jnp.int32), 2), (B, 2 * L))
        return (jnp.concatenate([noisy, tokens], axis=1), positions, weights,
                masked)


# the loss's own readings of a block-diffusion step, each summed over steps
# into the counter `diffusion.<what>` and the last step's shown
_DIFFUSION_READINGS = tuple(
    Reading("diffusion_" + what, over_steps="diffusion." + what)
    for what in ("tokens", "masked_tokens", "weight_sum", "rows"))


def _block_diffusion_loss(params, batch, cfg: TransformerConfig, **kw):
    """(`1 / (B L) sum_i m_i / t_b(i) ce_i` plus what the layers' readings
    add, the readings) of a block-diffusion batch (`diffusion_inputs`): the
    stack on the doubled stream, the head over the noisy half's L rows
    alone, position `i` predicting token `i` (no shift), each masked
    position weighted by its block's `1 / t`. The clean half's last hidden
    rows reach no head: they are computed, their keys and values are what
    the noisy rows read. The layers' readings (the routers' balance loss) are
    over all 2 L rows. The readings gain `_DIFFUSION_READINGS`: the step's
    tokens, how many of them were masked, the sum of `m_i / t` (its mean a
    token is 1 in expectation) and the rows the stack ran."""
    mesh = kw.get("mesh")
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "the objective 'block_diffusion' is not mapped over a mesh of "
            f"{mesh.size} devices yet: the head reads one half of every "
            "sequence's rows")
    rows, positions, weights, masked = diffusion_inputs(batch, cfg)
    tokens = batch["tokens"]
    L = tokens.shape[1]
    hidden, readings = _hidden_and_readings(
        params, rows, cfg, positions=positions, **kw)
    with jax.named_scope("bd_loss"), jax.named_scope("lm_head_ce"):
        loss, _ = weighted_lm_head_cross_entropy(
            hidden[:, :L], _unembed(params, cfg), tokens, weights)
    loss, readings = _settled(loss, readings or {}, cfg)
    with jax.named_scope("bd_noise"):
        readings.update(zip((r.name for r in _DIFFUSION_READINGS), (
            jnp.asarray(tokens.size, jnp.int32),
            masked.sum(dtype=jnp.int32),
            weights.sum() * tokens.size,
            jnp.asarray(rows.size, jnp.int32))))
    return loss, readings


def next_ids(rows, heads: int):
    """(tokens [B, T], targets [B, T, heads]) of rows of `T + heads` ids:
    position `t`'s targets are ids `t + 1 .. t + heads`, what a head of
    `heads` positions predicts."""
    T = rows.shape[1] - heads
    return rows[:, :T], jnp.stack(
        [rows[:, 1 + i:1 + i + T] for i in range(heads)], axis=-1)


# the dtype a chunk's logits leave the MXU in (`fp32_logits`), under a name
# of its own: a test lowers it to show what the comparison reads then
_LOGITS_F32 = jnp.float32


def _multi_head_loss(hidden, unembed, targets, cfg: TransformerConfig,
                     mesh=None):
    """The mean of the `n_pred_heads` heads' cross-entropies
    (`multi_head_cross_entropy`), `targets` [B, T, heads]."""
    if cfg.loop_steps > 1 or mesh_lib.expert_axis(mesh) is not None:
        raise NotImplementedError(
            f"n_pred_heads {cfg.n_pred_heads} over a looped stack or under "
            "an `expert` axis: the head of several positions is not made "
            "for them yet")
    if targets.shape[-1] != cfg.n_pred_heads:
        raise ValueError(
            f"targets {targets.shape} for {cfg.n_pred_heads} heads: the "
            "last axis is a target a head")
    return multi_head_cross_entropy(hidden, unembed, targets,
                                    logits_dtype=_LOGITS_F32)


def _settled(loss, readings, cfg: TransformerConfig):
    """(the loss with what the layers' readings add to it, the readings
    joined over the layers), as the records state it (`Sublayer.readings`):
    the operators' first, then the feed-forwards', each record's joins and
    then its terms."""
    readings = dict(readings)
    # records of one forward state the same readings: once
    for of_record in dict.fromkeys(sub.readings for sub in _RECORDS):
        stated = [r for r in of_record if (r.of or r.name) in readings]
        for r in stated:
            readings[r.name] = _OVER_LAYERS[r.over_layers](
                readings[r.of or r.name], cfg)
        terms = [(r.adds is True or getattr(cfg, r.adds), readings[r.name])
                 for r in stated if r.adds]
        if any(coef for coef, _ in terms):
            for coef, value in terms:
                loss = loss + (value if coef is True else coef * value)
    return loss, readings


def transformer_loss(params, batch, cfg: TransformerConfig, **kw):
    """The loss of `transformer_loss_and_readings` alone."""
    return transformer_loss_and_readings(params, batch, cfg, **kw)[0]


# ---------------------------------------- what a rematerialised block keeps

# The activations a block under `jax.checkpoint` may keep beside its input,
# by the names the model and the flash kernel's forward rule give them
# (`checkpoint_name`), in the order they are kept: by the time a byte kept
# saves (PERF.md section 6, "Keep rule: `_SAVE_ORDER`", has the ms a GB).
_SAVE_ORDER = (
    "attn_ctx",   # the kernel's o [B H, T, dv] and lse as one f32 column
                  # (sparse attention: and the indexer's three gradients and
                  # the selection's mask as bits; EVA: of both its parts;
                  # block diffusion: of the staircase's part, a row each)
    "eva_summaries",  # EVA's chunk keys and values: a chunk-th of k and v
    "moe_slots",  # the sorted slots: no second sort (integers, small)
    "attn_res",   # the stream after attention: no second `wo` product
    "conv_res",   # the stream after the short convolution: no `conv_out`
    "attn_qkv",   # the q, k, v products, before QK-norm, RoPE and GQA's repeat
                  # (latent attention: out of `wq`, `wkv_a` and `wkv_b`)
    "conv_in",    # the three streams out of `conv_in`
    "kda_res",    # the stream after kda: no second `kda_o` product
    "kda_qkv",    # kda's q, k, v products, before the convolution
    "mamba_in",   # the mixer's gate, x, B, C and dt out of `w_in`
    "ssd_out",    # the scan's output, before the gate and the norm
    "scan_out",   # the Mamba-1 scan's output, before the gate, and its
                  # chunks' entering states: no second forward scan
    "mamba1_in",  # the Mamba-1 mixer's u and gate out of `w_in`
    "gmu_in",     # the gated memory unit's gate product, before the silu
    "moe_gate",   # the experts' gate product [slots, f]
    "moe_up",     # and their up product
    "shared_gate",  # the shared experts' gate product, before the silu
    "shared_up",    # and their up product
    "mlp_gate",   # the dense feed-forward's gate product, before the silu
    "mlp_up",     # and its up product
)
_UNRANKED = {name for sub in _RECORDS for name in sub.names} - set(_SAVE_ORDER)
if _UNRANKED:  # the rule would pass such a name by and never keep it
    raise ValueError(
        f"a sublayer makes the names {sorted(_UNRANKED)}, which "
        "_SAVE_ORDER does not rank")
_SAVE_RESERVE = 1 << 30  # the step stays this far under the device's limit
# what a program holds beside its heap: the compiler's scratch of its own
# (128 MiB in every plan for a v5e) and its code, a layer that is inlined
_PROGRAM_BYTES = 128 << 20
_LAYER_CODE_BYTES = 32 << 20


def _kept(cfg: TransformerConfig, saved_names) -> Dict[str, int]:
    """What a step keeps, in the one form everything below `make_train_step`
    reads: {a name of `_SAVE_ORDER`: the number of the stack's last passes
    that keep it}, in `_SAVE_ORDER`'s order; 1 for a stack that is run once,
    and a name kept at no pass is not in it. `saved_names` is such a mapping
    or a plain tuple of names, which means every pass of `loop_steps`."""
    if not isinstance(saved_names, dict):
        saved_names = dict.fromkeys(saved_names, cfg.loop_steps)
    for name, k in saved_names.items():
        if name not in _SAVE_ORDER or not 0 <= k <= cfg.loop_steps:
            raise ValueError(f"{name} kept at {k} of {cfg.loop_steps} passes"
                             " (or it is not a name of _SAVE_ORDER)")
    return {name: saved_names[name] for name in _SAVE_ORDER
            if saved_names.get(name)}


def _layer_widths(cfg: TransformerConfig, kind: LayerKind):
    """(values a token of one layer of `kind`: {name: width in elements of
    the compute dtype}, the layer's parameters)."""
    widths, params = {}, 0
    for sub in _sublayers(kind):
        widths.update(sub.widths(cfg))
        params += sub.params(cfg)
    return widths, params


def _exchange_bytes(cfg: TransformerConfig, tokens: int, ways: int) -> int:
    """What a routed layer's exchange holds on a device of an `expert` axis
    of `ways` devices, `tokens` tokens each, beside what a share's layer
    holds: the gathered rows of all the axis, their partial results (or, in
    the backward, their gradient) summed in float32 and the pass's own in
    float32, and the held rows' buffers of `held_chunk` rows: two of the
    stream's width and the feed-forward's products (against the compiler's
    plan: PERF.md section 6, "Keep rule: `_exchange_bytes`")."""
    if ways == 1 or not cfg.n_routed_layers:
        return 0
    rows = ways * tokens
    return (rows * cfg.d_model * (_item(cfg) + 2 * 4)
            + _held_buffer_bytes(cfg, tokens, ways))


def _held_buffer_bytes(cfg: TransformerConfig, tokens: int, ways: int) -> int:
    """A share's held rows' buffers of `held_chunk` rows, of the rows of
    `ways` devices of `tokens` tokens each: two of the stream's width and
    the feed-forward's products."""
    chunk = _buffer_rows(cfg, ways * tokens, ways * max(
        1, tokens // (cfg.rows_per_token * cfg.max_seq_len)), ways)
    return (chunk * (2 * cfg.d_model + cfg.ff_matrices * cfg.ff_dim)
            * _item(cfg))


@lru_cache(maxsize=None)
def _whole_param_bytes(cfg: TransformerConfig) -> int:
    """float32 bytes of `cfg`'s parameters, all of them on one device."""
    return 4 * sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))))


def _head_bytes(cfg: TransformerConfig, tokens: int, param_bytes: int,
                expert_ways: int) -> int:
    """The head's chunk in its backward: a chunk's logits, their gradient
    and its cast, the unembedding's cast and the normed stream (a looped
    stack's head reads every pass's: `loop_steps` of them)."""
    item = _item(cfg)
    unembed = (cfg.head_width * cfg.d_model * item * param_bytes
               // _whole_param_bytes(cfg))
    if expert_ways > 1:  # whole: float32, its cast, its float32 gradient
        unembed = cfg.head_width * cfg.d_model * (4 + item + 4)
    return (HEAD_CHUNK * cfg.head_width * (4 + 4 + item) + unembed
            + cfg.loop_steps * tokens * cfg.d_model * item)


def _carried_bytes(cfg: TransformerConfig, tokens: int) -> Dict[str, int]:
    """{name: bytes} of the values the layers emit for later ones
    (`Sublayer.emits`), each held once from its emitter's forward to its
    backward, with the sum of its readers' cotangents beside it: in the
    compute dtype, and both in float32 where the readers are a scan of
    several periods (`_for_readers`)."""
    item, out = _item(cfg), {}
    widths = {name: width for kind in cfg.layers for sub in _sublayers(kind)
              for name, width in sub.carried(cfg).items()}
    for seg in segments(cfg):
        for kind in seg.layout:
            for sub in _sublayers(kind):
                for name in sub.reads:
                    each = 4 if seg.periods > 1 else item
                    out[name] = max(out.get(name, 0),
                                    2 * each * widths[name] * tokens)
    return out


def _boundary_bytes(cfg: TransformerConfig, tokens: int) -> int:
    """The blocks' inputs, which a rematerialised stack keeps whatever else
    it keeps, and the stream that leaves the last: once a layer a pass of
    `loop_steps`, `loop_steps n_layers + 1` in all; and what the layers
    carry to later ones beside the stream (`_carried_bytes`). A looped stack also
    holds through its backward, a pass each, the final norm's input and the
    cotangent of the normed stream that the head and the gate read; the
    normed streams themselves are the head's (`_head_bytes`) and gone when
    the stack's backward starts."""
    streams = cfg.loop_steps * cfg.n_layers + 1
    if cfg.loop_steps > 1:
        streams += 2 * cfg.loop_steps
    return (streams * tokens * cfg.d_model * _item(cfg)
            + sum(_carried_bytes(cfg, tokens).values()))


class _Moment(NamedTuple):
    """A moment of the step and what a device holds then beside the state."""
    name: str  # "head", "optimizer", "layers 1-5" (a scan), "layer 4"
    bytes: int


class _Terms(NamedTuple):
    """The rule's terms for one traced step (`_terms` adds them up once, for
    whatever is kept), bytes on a device, and the step's moments for a choice
    `kept` ({name: passes}, `_kept`'s form).

    The condition the terms must keep: they err to the full side. A name
    too few costs a percent; a step that asks for the chip's last GiB is
    compiled to fit and runs slower than the one that keeps nothing. How
    far to the full side: for the choice the rule makes the sum of the state
    and the fullest moment stands at or over the chip's peak and no more
    than 1 GB over it in every token cell, and no less than the compiler's
    plan less 0.2 GB (`tests/test_saved_activations.py` `CHIP_PEAK_GB`,
    `tests/test_step_compile.py`). The sums against the chips' peaks and
    the compiler's plans: PERF.md section 6, "Keep rule: a scanned stack's
    sum" and "Keep rule: the moments" (PR 73 read the walked layers' terms
    off the plans of eleven cells at once)."""
    passes: int  # `loop_steps`
    names: Dict[str, int]  # {name: all the layers' bytes of it a pass}
    made: Tuple[Dict[str, int], ...]  # a layer each: {name: its bytes a pass}
    # the step's moments in the backward's order, each (its name, what a
    # device holds then beside its state and the kept names of other layers,
    # how many of the first layers' kept names it holds beside that)
    frames: Tuple[Tuple[str, int, int], ...]
    boundaries: int  # `_boundary_bytes`
    head: int  # `_head_bytes`
    exchange: int  # `_exchange_bytes`
    loops: int  # a looped stack's weights in the compute dtype
    at_once: int  # a scanned stack's sum: every term but the kept names

    def saved_bytes(self, kept: Optional[Dict[str, int]] = None):
        """{name: the bytes a device holds of it} for the choice `kept`;
        None: every name the layers make, at every pass. A name's bytes are
        its width times the tokens times the layers that make it, a pass."""
        if kept is None:
            kept = dict.fromkeys(self.names, self.passes)
        return {name: k * self.names[name] for name, k in kept.items()}

    def moments(self, kept: Optional[Dict[str, int]] = None) -> List[_Moment]:
        """The moments at which the step that keeps `kept` may be fullest,
        each with what a device holds then beside its state."""
        of_layer = [sum(k * made.get(name, 0)
                        for name, k in (kept or {}).items())
                    for made in self.made]
        return [_Moment(name, held + sum(of_layer[:layers]))
                for name, held, layers in self.frames]

    def fullest(self, kept: Optional[Dict[str, int]] = None) -> _Moment:
        """The fullest of `moments`; of equals, the first in the backward."""
        return max(self.moments(kept), key=lambda moment: moment.bytes)

    def room(self, resident_bytes: int, limit_bytes: int,
             kept: Optional[Dict[str, int]] = None) -> int:
        """What the step that keeps `kept` leaves of `limit_bytes` when it
        is fullest, the state's `resident_bytes` and `_SAVE_RESERVE` set
        aside: with nothing kept, the room the names may take."""
        return (limit_bytes - _SAVE_RESERVE - resident_bytes
                - self.fullest(kept).bytes)


@lru_cache(maxsize=None)
def _terms(cfg: TransformerConfig, tokens: int,
           param_bytes: Optional[int] = None, expert_ways: int = 1) -> _Terms:
    """The rule's terms for `tokens` tokens on a device that holds
    `param_bytes` of the parameters (None: all of them), which its gradients
    take again, and is one of an `expert` axis of `expert_ways`: the routed
    layers are then a device's share of them, with their exchange, and the
    head is gathered whole. The moments, in the backward's order over
    `segments(cfg)`:

    A segment of several periods is a scan over stacked layers (and under
    `loop_steps` the passes are a scan, so every segment is): its gradient
    is one buffer, whole from the scan's first step, which the compiler
    holds beside every kept name all through the backward. Its moment is
    every term at once: the blocks' inputs, every kept name, every
    gradient, the larger of the model's widest block and the head's chunk,
    and a looped stack's weights in the compute dtype, cast once ahead of
    both loops (`at_once`; the backward of `_looped_under_remat` holds
    nothing else of its own). A model all of whose segments are scanned has
    that one sum.

    A segment of one period is a scan of length 1, which XLA inlines: each
    layer's gradient is a buffer of its own, made when the backward reaches
    the layer, and since the step clips nothing AdamW's update of a weight
    follows its gradient at once. The backward walks from the last layer to
    the first, so layer i's moment holds the blocks' inputs; the kept names
    of the layers before it; its own block (below), which has the layer's
    own names at their widths whether they were kept or are made again: a
    kept name is a residual the backward reads where it would have made it,
    live once; the head's gradient (made first, and live to the end); the
    gradients of the scanned segments behind it; of the gradients of the
    layers no scan stacks what a loop accumulates: a share's held experts',
    float32, live from the first chunk of held rows, its own and those of
    the routed layers behind it, which may wait for the optimizer to the
    step's end (they do in `solaropen2.tokens8k`'s plan, for two layers in
    `lagunaxs2.tokens8k`'s, not in `lfm2moe.tokens8k`'s; where the
    parameters are sharded the block has the whole gradient already); and
    the program's own beside its heap (`_PROGRAM_BYTES`, and a layer's
    code, twice where it routes: 27 to 59 MB a layer in the plans).

    A walked layer's block is the larger of its sublayers' backward
    moments, the feed-forward's and then the operator's: a sublayer's
    moment has the names made up to it, what it alone holds
    (`Sublayer.holds`) and what the sublayers before it left for their own
    backward (`Sublayer.residuals`: the Mamba-2 mixer's), a share's
    feed-forward's on one device also its
    held rows' buffers and their float32 sum (an `expert` axis' exchange
    has both). The operator's cotangents are not live in the
    feed-forward's backward, nor the feed-forward's products in the
    operator's (the plans of `phi4flash.tokens16k` and `evabyte.tokens8k`;
    of what EVA's backward holds 0.67 of 1.09 GB is made again ahead of
    the feed-forward's backward, and the sum still stands over that plan).
    A scanned segment goes on counting both sublayers at once: its sums
    stand within 0.1 GB of the chips' peaks that way.

    The head's moment has every kept name and every gradient but those of
    the layers no scan stacks; the optimizer's has every gradient and no
    kept name. (PERF.md section 6, "Keep rule: the moments" and "Keep rule:
    a looped stack's weights", has the plans these were read off: PR 54's
    of five cells, PR 58's, and PR 73's of every cell with a walked
    segment, with today's names and with the names the rule now adds.)"""
    whole = _whole_param_bytes(cfg)
    if param_bytes is None:
        param_bytes = whole
    sharded = param_bytes < whole
    exchange = _exchange_bytes(cfg, tokens, expert_ways)
    head = _head_bytes(cfg, tokens, param_bytes, expert_ways)
    # the head's gradient once the head is done: a device's share of it
    unembed = 4 * cfg.head_width * cfg.d_model * param_bytes // whole
    if expert_ways > 1 and cfg.n_experts:
        # as one device of the axis runs it: its routed layers are a share's
        # (one operation that makes no names and holds no whole layer's rows)
        cfg = dataclasses.replace(
            cfg, experts_held=(0, cfg.n_experts // expert_ways))
    on_device = _whole_param_bytes(cfg)
    item, d = _item(cfg), cfg.d_model
    names, params, block, walked = {}, {}, {}, {}  # of a layer, by its kind
    for kind in dict.fromkeys(cfg.layers):
        widths, params[kind] = _layer_widths(cfg, kind)
        names[kind] = {name: tokens * item * w for name, w in widths.items()}
        # the block in its backward, every value at once: the stream's
        # cotangent and, of each of its sublayers, the normed input, the
        # named values and what the kind says it holds beside them
        # (`Sublayer.holds`); with the compute-dtype copy of its weights
        # and, where the parameters are sharded, the same weights gathered
        # whole and their float32 gradient before it is scattered; a routed
        # block on an `expert` axis with its exchange
        subs = _sublayers(kind)
        held = [sub.holds(cfg) for sub in subs]
        rest = (tokens * (len(subs) + 1) * d * item + params[kind] * item
                + (params[kind] * (item + 4) if sharded else 0)
                + (exchange if kind.routed else 0))
        block[kind] = tokens * (sum(widths.values()) + sum(held)) * item + rest
        # where no scan stacks the layer: the larger of its sublayers'
        # backward moments, each the names made up to it and what it alone
        # holds; a share's feed-forward's on one device with its held rows'
        # buffers and their float32 sum (an axis' exchange has both)
        made = itertools.accumulate(
            sum(sub.widths(cfg).values()) for sub in subs)
        # and what the sublayers before it left for their own backward
        left = itertools.accumulate(
            (sub.residuals(cfg) for sub in subs), initial=0)
        moments = [tokens * (m + h + l) * item
                   for m, h, l in zip(made, held, left)]
        if kind.routed and expert_ways == 1 and cfg.held[1] < cfg.n_experts:
            moments[-1] += _held_buffer_bytes(cfg, tokens, 1) + tokens * d * 4
        walked[kind] = max(moments) + rest

    def gradient(kind: LayerKind) -> int:
        return 4 * params[kind] * param_bytes // on_device

    boundaries = _boundary_bytes(cfg, tokens)
    loops = (item * param_bytes * sum(params[kind] for kind in cfg.layers)
             // on_device if cfg.loop_steps > 1 else 0)
    at_once = boundaries + max(*block.values(), head) + loops
    # of a routed layer's gradient, what a loop accumulates: a share's held
    # experts'
    accumulated = (4 * cfg.held[1] * cfg.ff_matrices * d * cfg.ff_dim
                   if not sharded and cfg.held[1] < cfg.n_experts else 0)
    segs = [(seg, seg.periods > 1 or cfg.loop_steps > 1)
            for seg in segments(cfg)]
    # the gradients of the layers no scan stacks: none is made before its
    # layer's backward
    inlined = sum(gradient(kind) for seg, scan in segs if not scan
                  for kind in seg.layout)
    frames = [("optimizer", param_bytes, 0),
              ("head", boundaries + head + param_bytes - inlined,
               cfg.n_layers)]
    # the program's own beside its heap, where its layers are inlined
    program = _PROGRAM_BYTES + _LAYER_CODE_BYTES * sum(
        1 + kind.routed for seg, scan in segs if not scan
        for kind in seg.layout)
    first, behind, waiting = cfg.n_layers, 0, 0  # from the last layer back
    for seg, scan in reversed(segs):
        layers = len(seg.layout) * seg.periods
        first -= layers
        if scan:
            frames.append(("layers %d-%d" % (first, first + layers - 1),
                           at_once + param_bytes, cfg.n_layers))
            behind += seg.periods * sum(map(gradient, seg.layout))
            continue
        for i in reversed(range(layers)):
            kind = seg.layout[i]
            waiting += accumulated if kind.routed else 0
            frames.append(("layer %d" % (first + i),
                           boundaries + unembed + behind + walked[kind]
                           + waiting + program, first + i))
    made = tuple(names[kind] for kind in cfg.layers)
    return _Terms(
        cfg.loop_steps,
        {name: sum(of.get(name, 0) for of in made) for name in _SAVE_ORDER
         if any(name in of for of in made)},
        made, tuple(frames), boundaries, head, exchange, loops, at_once)


def _moments(cfg: TransformerConfig, tokens: int, param_bytes: int,
             expert_ways: int = 1, kept=None) -> List[_Moment]:
    """`_Terms.moments` for the choice `kept`, from the step's shapes."""
    return _terms(cfg, tokens, param_bytes, expert_ways).moments(kept)


def saved_activations(cfg: TransformerConfig, tokens_per_device: int,
                      resident_bytes: int, param_bytes: int,
                      limit_bytes: Optional[int],
                      expert_ways: int = 1) -> Dict[str, int]:
    """What a rematerialised block keeps beside its input, {name: the number
    of the stack's last passes that keep it} (`_kept`'s form): the names of
    `_SAVE_ORDER`, in that order, for as long as they fit the device.

    `cfg.remat` says that a block's input is its checkpoint; what is kept
    beyond it follows from what the step is traced with. A choice fits
    while it leaves room (`_Terms.room`): `resident_bytes` (the state on
    the device), the fullest of the step's moments with those names kept
    (`_Terms.moments`: over `segments(cfg)`, from `param_bytes`, the
    parameters' bytes on the device, which its gradients take again) and
    `_SAVE_RESERVE` stay within `limit_bytes` (the device's
    `bytes_limit`); on an `expert` axis of `expert_ways` devices the routed
    layers are a device's share of them. The first name that does not fit
    ends the choice, so a larger limit only ever adds names. A stack that
    is run `loop_steps` times keeps a name for the last k of its passes,
    each name the most that leave room, and the first name that gets fewer
    than all ends the choice; a stack that is run once keeps a name (at 1)
    or does not. With no limit to read (the CPU, a described topology) or
    without `remat` nothing is kept, and the step is the one without a
    policy. The bytes of a choice: `_Terms.saved_bytes`."""
    if limit_bytes is None or not cfg.remat:
        return {}
    terms = _terms(cfg, tokens_per_device, param_bytes, expert_ways)
    kept: Dict[str, int] = {}
    for name in terms.names:
        k = next((k for k in range(cfg.loop_steps, 0, -1) if terms.room(
            resident_bytes, limit_bytes, {**kept, name: k}) >= 0), 0)
        if k:
            kept[name] = k
        if k < cfg.loop_steps:
            break
    return kept


def _memory_limit(mesh) -> Optional[int]:
    """`bytes_limit` of the mesh's first device: what its allocator may hand
    out. None where the device reports none (the CPU, a described
    topology)."""
    try:
        stats = mesh.devices.flat[0].memory_stats()
    except jax.errors.JaxRuntimeError:  # a described device is not asked
        return None
    return (stats or {}).get("bytes_limit")


# -------------------------------------------------------------- train step

# what the step reports beside loss and grad_norm: what the records state
# and the exit loss's
_STEP_READINGS = (
    *dict.fromkeys(
        r.name for sub in _RECORDS for r in sub.readings if r.step),
    *_EXIT_READINGS, *(r.name for r in _DIFFUSION_READINGS))


# the readings of which a report makes more than showing the last step's,
# and the records that account for some of their own
_OVER_STEPS = [r for r in (*(r for sub in _RECORDS for r in sub.readings),
                           *_DIFFUSION_READINGS) if r.over_steps]
_OWN_ACCOUNTS = [(sub, own) for sub in _RECORDS if (own := {
    r.name for r in sub.readings if r.over_steps is True})]


def _account_of_steps(static, stacked):
    """`_STEP_ACCOUNT`'s `fold`: of the steps' readings `stacked`, those a
    record accounts for itself and those summed into a counter, as
    `Reading.over_steps` states it where the reading is."""
    sums, rows = {}, []
    for sub, own in _OWN_ACCOUNTS:
        if not own.isdisjoint(stacked):
            sums, rows = sub.account(static, stacked)
    sums.update((r.over_steps, stacked[r.name].sum().item())
                for r in _OVER_STEPS
                if r.over_steps is not True and r.name in stacked)
    return sums, rows


# what a report makes of the steps of `make_train_step`: the train session's
# account (`ray_tpu/train/_runtime.py`) folds by it and names no reading
_STEP_ACCOUNT = tracing.Account(
    {r.name: r.over_steps is not True for r in _OVER_STEPS},
    _account_of_steps)


def make_train_step(cfg: TransformerConfig, mesh, optimizer=None):
    """Build (init_state, step) jitted over the mesh.

    state = {'params': f32 sharded, 'opt': optax state, 'step': scalar}
    step(state, batch) -> (state, metrics); params/opt donated. `step` is
    the jitted function under `tracing.Step`: a call is the span
    `train.step` and leaves `metrics` for the train session's account, which
    folds them by `step.account` (`_STEP_ACCOUNT`); `lower`, `trace` and
    every other attribute are the jitted function's;
    `step.static["held_chunk"]` is, once a share's step is traced, the rows
    of the buffers its routed layers walk their held rows in. metrics are
    loss and grad_norm, and of `_STEP_READINGS` (what the stack's records
    state, the exit loss's and block diffusion's) those the step makes.
    With `cfg.expert_bias` the state has 'expert_bias'
    [L, E] float32, which the optimizer does not own: after the optimizer's
    update the step moves it by `expert_bias_update_rate` toward the experts
    that this step's load left short, and reports expert_bias_abs_max.

    DP/FSDP/TP come from the in/out shardings (XLA inserts psum /
    all-gather / reduce-scatter over ICI); if the mesh has a 'sequence'
    axis the batch spec additionally shards T.
    """
    import optax

    if optimizer is None:
        optimizer = optax.adamw(3e-4, weight_decay=0.01)

    p_shard = param_shardings(mesh, cfg)
    names = mesh.axis_names
    batch_axes = tuple(a for a in mesh_lib.BATCH_AXES if a in names) or None
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
    if cfg.expert_bias:
        state_shard["expert_bias"] = repl

    def init_state(rng):
        params = transformer_init(rng, cfg)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s), params, p_shard
        )
        opt = jax.jit(optimizer.init, out_shardings=opt_shard)(params)
        state = {"params": params, "opt": opt,
                 "step": jax.device_put(jnp.zeros((), jnp.int32), repl)}
        if cfg.expert_bias:
            state["expert_bias"] = jax.device_put(expert_bias_init(cfg), repl)
        return state

    static = {}  # what the step's trace says of its program, for `Step`

    def loss_fn(params, batch, **kw):
        loss, readings = transformer_loss_and_readings(
            params, batch, cfg, mesh=mesh, **kw)
        if "held_slots" in readings:
            # the rows of a share's buffers, as `_routed_rows` asks for them:
            # of the rows and the sequences of the mesh's whole batch
            static["held_chunk"] = _buffer_rows(
                cfg, readings["expert_index"].shape[-2],
                batch["tokens"].shape[0],
                mesh.size if mesh_lib.expert_axis(mesh) else 1)
        return loss, {k: readings[k] for k in _STEP_READINGS if k in readings}

    def on_a_device(tree, shardings):
        """Bytes one device holds of `tree` laid out by `shardings`."""
        return sum(
            math.prod(s.shard_shape(x.shape)) * x.dtype.itemsize
            for x, s in zip(jax.tree.leaves(tree), jax.tree.leaves(shardings)))

    def saved_names(state, batch):
        """What the rematerialised blocks keep, chosen while the step is
        traced, from what it is traced with: the state's and the batch's
        shapes as the mesh lays them out, and the device's memory limit."""
        limit = _memory_limit(mesh)
        # the stream's rows: two a token under block diffusion
        tokens = cfg.rows_per_token * math.prod(
            tok_sharding.shard_shape(batch["tokens"].shape))
        resident = on_a_device(state, state_shard)
        params = on_a_device(state["params"], p_shard)
        ways = mesh.shape.get("expert", 1)
        kept = saved_activations(cfg, tokens, resident, params, limit, ways)
        # built once a traced step (the rule has, if it chose), and only
        # where something is read of them
        terms = partial(_terms, cfg, tokens, params, ways)
        saved = terms().saved_bytes(kept) if kept else {}
        said = saved or "nothing"
        if saved and cfg.loop_steps > 1:  # a looped stack: the passes too
            said = ", ".join("%s at %d of %d passes (%d bytes)" % (
                name, k, cfg.loop_steps, saved[name])
                for name, k in kept.items())
        keeps = "keeps every activation"
        if cfg.remat:
            keeps = ("under remat keeps %s: %d bytes a device beside the "
                     "blocks' inputs (%d tokens a device, state %d bytes, "
                     "bytes_limit %s)" % (said, sum(saved.values()), tokens,
                                          resident, limit))
        if cfg.remat and limit is not None:
            fullest = terms().fullest(kept)
            left = limit - _SAVE_RESERVE - resident - fullest.bytes
            keeps += ("; fullest at %s, %d bytes with the state; room %d "
                      "bytes before a name is kept, %d with these" % (
                          fullest.name, resident + fullest.bytes,
                          terms().room(resident, limit), left))
            tracing.count("train.saved_room_bytes", left)
        # static, so counted as the step is traced: once a step's program
        tracing.count("train.saved_names", len(kept))
        tracing.count("train.saved_bytes", sum(saved.values()))
        tracing.count("train.saved_passes", sum(kept.values()))
        carried = _carried_bytes(cfg, tokens)
        tracing.count("train.carried_bytes", sum(carried.values()))
        if carried:  # what the layers hand later ones beside the stream
            keeps += "; carries %s beside the stream, with the sums of "\
                "their readers' cotangents" % ", ".join(
                    "%s (%d bytes)" % item for item in carried.items())
        buffers, their_bytes, widest = own_buffers(
            state["params"]["blocks"], cfg)
        tracing.count("train.own_buffers", buffers)
        tracing.count("train.own_buffer_bytes", their_bytes)
        return kept, (
            "train step %s; its blocks' weight matmuls read and write %d "
            "buffers of their own, %d bytes in %s over %d layers (%d the "
            "widest layer's weights)" % (
                keeps, buffers, their_bytes, jnp.dtype(cfg.dtype).name,
                cfg.n_layers, widest))

    @partial(jax.jit, donate_argnums=(0,), out_shardings=(state_shard, repl))
    def step(state, batch):
        bias = ({"expert_bias": state["expert_bias"]} if cfg.expert_bias
                else {})
        kept, said = saved_names(state, batch)
        calls = tracing.counters()
        (loss, readings), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], batch, saved_names=kept, **bias)
        logger.info(said + _calls_said(calls))
        with jax.named_scope("optimizer"):
            updates, opt = optimizer.update(
                grads, state["opt"], state["params"]
            )
            params = optax.apply_updates(state["params"], updates)
            gnorm = optax.global_norm(grads)
        state = {"params": params, "opt": opt, "step": state["step"] + 1}
        if bias:
            with jax.named_scope("expert_bias"):
                state["expert_bias"] = moe.update_expert_bias(
                    bias["expert_bias"], readings["expert_load"],
                    cfg.expert_bias_update_rate)
                readings["expert_bias_abs_max"] = jnp.max(
                    jnp.abs(state["expert_bias"]))
        return state, {"loss": loss, "grad_norm": gnorm, **readings}

    return init_state, tracing.Step(step, static, _STEP_ACCOUNT), {
        "tokens": tok_sharding, "replicated": repl, "params": p_shard,
        "state": state_shard}


def _fwd_flops_per_token(cfg: TransformerConfig, seq_len: int):
    """(matmul fwd flops/token over the layers, causal attn fwd flops/token
    over the layers, lm-head fwd flops/token): every layer once a pass of
    `loop_steps`; under an `exit_gate` the head and the gate's `d_model`
    multiply-adds once a pass too. A record's operations are a row's: under
    block diffusion a token is two rows through every layer and one through
    the head."""
    matmul = attn = 0.0
    passes = cfg.loop_steps * cfg.rows_per_token
    for kind in cfg.layers:
        for sub in _sublayers(kind):
            of_matmuls, of_attention = sub.flops(cfg, seq_len)
            matmul += passes * of_matmuls
            attn += passes * of_attention
    head = 2 * cfg.d_model * cfg.head_width
    if cfg.exit_gate:
        head = cfg.loop_steps * (head + 2 * cfg.d_model)
    return matmul, attn, head


def flops_per_token(cfg: TransformerConfig, seq_len: int) -> float:
    """USEFUL train FLOPs/token: 6ND rule + CAUSAL attention quadratic term.

    1 forward + backward at 2x forward (the PaLM / scaling-book accounting).
    Recomputation (remat, flash-backward recompute) is deliberately
    excluded — this is the numerator for useful-MFU.
    """
    matmul, attn, embed = _fwd_flops_per_token(cfg, seq_len)
    return 3 * (matmul + attn + embed)
