"""Reference family ``olmoe``: OLMoE's block stack, from Muennighoff et al.
2024 (arXiv:2409.02060) and the ``olmoe`` model card, fed ``model.*``
tensors under their public Hugging Face names.

A block is pre-norm attention and a sparse mixture of experts:

* attention: ``q = RMSNorm(x Wq)`` and ``k = RMSNorm(x Wk)`` over the WHOLE
  projected width (all heads together, one learned scale each), before the
  split into heads and before RoPE; ``v = x Wv``; causal softmax attention;
  ``Wo``;
* experts: ``p = softmax(x Wg)`` over ``num_experts``; the
  ``num_experts_per_tok`` largest ``p`` are the combine weights, divided by
  their sum only where ``norm_topk_prob`` says so (published: false, so a
  token's weights sum to less than one); ``y = sum_e p_e Wdown_e(silu(
  Wgate_e x) * Wup_e x)``. Computed the plain way: every expert on every
  token, times a combine weight that is zero outside the token's top k.

THE LOSS is the one the published model was trained with: token
cross-entropy plus, for every block, a load-balancing term
``router_aux_loss_coef * E * sum_e f_e P_e`` and a router z-loss
``router_z_loss_coef * mean(logsumexp(logits)^2)``, with ``f_e`` the share of
tokens that chose expert ``e`` among their k (so the ``f_e`` add up to k, as
``transformers``' ``load_balancing_loss_func`` has it) and ``P_e`` the mean
router probability of ``e``. Both terms are means over the tokens of ONE
CALL of ``nll_sum``, and the call returns the summed token NLL plus
``tokens.size`` times them. ``check.py`` calls one sequence at a time and
divides the total by the token count; the program's step loss is the mean
over microbatches of ``ce + aux``, equal weights. The two are the same
number exactly where a microbatch is one sequence (``c1_s4k``: 4 sequences
in 4 microbatches); with more sequences a microbatch the program's ``f_e``
and ``P_e`` are taken over all of them together and this reference's are
not.

Where the numbers come from: ``router_aux_loss_coef`` 0.01 is in the
published ``config.json``; ``router_z_loss_coef`` 0.001 is the paper's
(section 4.1.6; ``config.json`` has no key for it). No other departure from
the published model: no clamp (``clip_qkv`` null), no bias, no shared
expert.
"""

from __future__ import annotations

from typing import Mapping, Optional

import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark.reference.plain import (
    Weights,
    causal_attention,
    merge_heads,
    rms_norm,
    rope,
    split_heads,
    token_nll_sum,
)


def sparse_experts(x, w: Weights, p: str, cfg: Mapping):
    """``x`` [tokens, hidden] -> (the experts' output, the two router terms
    of this block over these tokens)."""
    E, K = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = x @ w[p + "gate.weight"].T
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, K)
    if cfg.get("norm_topk_prob"):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_i, E, dtype=x.dtype)          # [T, K, E]
    combine = jnp.einsum("tk,tke->te", top_p, chosen)         # 0 off the top k
    out = jnp.zeros_like(x)
    for e in range(E):
        q = p + f"experts.{e}."
        y = (jax.nn.silu(x @ w[q + "gate_proj.weight"].T)
             * (x @ w[q + "up_proj.weight"].T)) @ w[q + "down_proj.weight"].T
        out = out + combine[:, e:e + 1] * y
    f = jnp.mean(jnp.sum(chosen, axis=1), axis=0)
    balance = cfg["router_aux_loss_coef"] * E * jnp.sum(
        f * jnp.mean(probs, axis=0))
    z = cfg["router_z_loss_coef"] * jnp.mean(
        jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    return out, balance + z


def hidden_states(w: Weights, cfg: Mapping, tokens, *,
                  layers: Optional[int] = None):
    """The final-norm hidden states [B, S, hidden] and the router terms of
    all blocks over the tokens of this call."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h = w["model.embed_tokens.weight"][tokens]
    router_terms = jnp.zeros((), h.dtype)
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        p = f"model.layers.{i}."
        a = rms_norm(h, w[p + "input_layernorm.weight"], eps)
        q = rms_norm(a @ w[p + "self_attn.q_proj.weight"].T,
                     w[p + "self_attn.q_norm.weight"], eps)
        k = rms_norm(a @ w[p + "self_attn.k_proj.weight"].T,
                     w[p + "self_attn.k_norm.weight"], eps)
        q = rope(split_heads(q, nh), theta)
        k = rope(split_heads(k, nkv), theta)
        v = split_heads(a @ w[p + "self_attn.v_proj.weight"].T, nkv)
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        h = h + merge_heads(causal_attention(q, k, v)) \
            @ w[p + "self_attn.o_proj.weight"].T
        m = rms_norm(h, w[p + "post_attention_layernorm.weight"], eps)
        y, terms = sparse_experts(m.reshape(-1, m.shape[-1]), w, p + "mlp.",
                                  cfg)
        h = h + y.reshape(m.shape)
        router_terms = router_terms + terms
    return rms_norm(h, w["model.norm.weight"], eps), router_terms


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    """Summed token NLL plus ``tokens.size`` times the router terms over
    the tokens of this call (see THE LOSS above)."""
    h, router_terms = hidden_states(w, cfg, tokens, layers=layers)
    return (token_nll_sum(h @ w["lm_head.weight"].T, labels)
            + tokens.size * router_terms)


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """Attention as the dense count has it; then the router and the
    ``num_experts_per_tok`` gated experts a token uses (three matrices of
    ``hidden x intermediate_size`` each), and the untied head. The q/k norms
    are not matmuls and are not counted."""
    experts = (config["num_experts_per_tok"] * 3 * 2 * sizes.hidden
               * config["intermediate_size"])
    router = 2 * sizes.hidden * config["num_experts"]
    return (sizes.layers * (flops.attention_flops_per_token(sizes)
                            + experts + router)
            + flops.head_flops_per_token(sizes))
