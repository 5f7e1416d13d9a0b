"""Plain reference of the decoder the `moe_transformer` family trains:
OLMoE (arXiv:2409.02060, the published modelling code). Pre-norm blocks with
RMSNorm; an RMSNorm with a learned scale over the whole q and k projections
before the heads are split (QK-norm); rotary positions (half-split
rotation); causal softmax attention; a routed feed-forward: router logits
`y Wr` (no bias), a softmax over all experts, the `experts_per_token`
largest probabilities and their experts, the probabilities weighting the
experts as they are (`norm_topk_prob` false; divided by their sum when
true), `x + sum_k p_k Wdown_e(silu(Wgate_e y) * Wup_e y)`; a final RMSNorm
and an untied output head. The loss is the mean next-token cross-entropy
plus `router_aux_loss_coef` times the load-balancing loss
`E sum_e f_e P_e` (f_e the share of the T x k slots sent to expert e, P_e the
mean router probability of e) plus `router_z_loss_coef` times the router
z-loss `mean(logsumexp(logits)^2)`.

Everything is float32 at the highest matmul precision. Every expert is
applied to every token and the result is masked by the top-k choice: no
sort, no grouping, no kernel; attention is the full
softmax(QK^T/sqrt(d))V under a causal mask.

Departures from the published model, written down as the contract asks:
- Each auxiliary loss is computed per layer and the layers' values are
  averaged, as the training code the paper used does (megablocks'
  `batched_load_balancing_loss`). The Hugging Face port concatenates the
  layers' router outputs instead, which gives the same value when every
  layer sees the same number of tokens, times `experts_per_token` (it sums
  the k choices' shares where this divides by T k); the coefficient 0.01
  is the paper's, for the paper's formula.
- `clip_qkv` is null in the published configuration: nothing is clipped.
- No dropout, no bias, no padding mask: sequences are whole.
- With one layer (the benchmark's cut) the mean over layers is that layer.

Parameters use the program's layout (`transformer_init`): block weights
stacked on a leading layer axis, the experts' behind it (`w_gate`, `w_up`
[L, E, d, f], `w_down` [L, E, f, d]), `router` [L, d, E], `q_norm`, `k_norm`
[L, d], `embed` [vocab, d], `unembed` [d, vocab].
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.transformer import _rmsnorm, _rope


def forward(params: Dict[str, Any], batch: Dict[str, Any],
            config: Dict[str, Any], expert_index=None):
    """(loss, chosen): the loss, and which experts each token chose, as a
    bool array [layers, tokens, experts] (information for the comparison).

    `expert_index` [layers, tokens, experts_per_token], where given, takes
    the place of the reference's own top-k choice and nothing else: the
    probabilities, the weights and both auxiliary losses are still the
    reference's. The comparison of gradients hands over the system's choice,
    so that both sides differentiate one routing (a choice has no
    gradient, but a token whose eighth and ninth probabilities tie moves a
    whole row between two experts' weight gradients)."""
    d, h = config["d_model"], config["n_heads"]
    hk = config.get("n_kv_heads") or h
    dh = d // h
    n_experts, top = config["n_experts"], config["experts_per_token"]
    eps, theta = config["norm_eps"], config["rope_theta"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    tokens, targets = batch["tokens"], batch["targets"]
    b, t = tokens.shape
    aux, z, chosen = [], [], []
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        mask = jnp.tril(jnp.ones((t, t), bool))
        for layer in range(config["n_layers"]):
            w = {k: f32(v[layer]) for k, v in params["blocks"].items()}
            y = _rmsnorm(x, w["attn_norm"], eps)
            q, k, v = y @ w["wq"], y @ w["wk"], y @ w["wv"]
            if config.get("qk_norm"):
                q = _rmsnorm(q, w["q_norm"], eps)
                k = _rmsnorm(k, w["k_norm"], eps)
            q = _rope(q.reshape(b, t, h, dh), theta)
            k = _rope(k.reshape(b, t, hk, dh), theta)
            k = jnp.repeat(k, h // hk, axis=2)
            v = jnp.repeat(v.reshape(b, t, hk, dh), h // hk, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
            attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
            x = x + attn.reshape(b, t, h * dh) @ w["wo"]

            y = _rmsnorm(x, w["mlp_norm"], eps)
            logits = y @ w["router"]                       # [b, t, E]
            probs = jax.nn.softmax(logits, axis=-1)
            if expert_index is None:
                best = jnp.argsort(-probs, axis=-1)[..., :top]  # ties: lowest index
            else:
                best = expert_index[layer].reshape(b, t, top)
            picked = jax.nn.one_hot(best, n_experts).sum(axis=-2)  # [b, t, E]
            weights = probs * picked
            if config.get("norm_topk_prob"):
                weights = weights / weights.sum(axis=-1, keepdims=True)
            gate = jnp.einsum("btd,edf->btef", y, w["w_gate"])
            up = jnp.einsum("btd,edf->btef", y, w["w_up"])
            every = jnp.einsum("btef,efd->bted", jax.nn.silu(gate) * up,
                               w["w_down"])
            x = x + jnp.einsum("bted,bte->btd", every, weights)

            share = picked.sum(axis=(0, 1)) / (b * t * top)
            aux.append(n_experts * jnp.sum(share * probs.mean(axis=(0, 1))))
            z.append(jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2))
            chosen.append(picked.reshape(b * t, n_experts) > 0)
        x = _rmsnorm(x, f32(params["final_norm"]), eps)
        head = (f32(params["unembed"]) if "unembed" in params
                else f32(params["embed"]).T)
        logp = jax.nn.log_softmax(x @ head, axis=-1)
        ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    total = (ce + config["router_aux_loss_coef"] * sum(aux) / len(aux)
             + config["router_z_loss_coef"] * sum(z) / len(z))
    return total, jnp.stack(chosen)


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any],
         expert_index=None):
    """Cross-entropy of `batch["targets"]` given `batch["tokens"]` plus the
    router's two weighted auxiliary losses."""
    return forward(params, batch, config, expert_index)[0]
