"""Reference family ``tiny_mixtral``: what a later PR adds for an
architecture the harness has never seen, kept here as a test fixture
(``tiny.add_new_family`` copies it into ``<copy>/benchmark/reference/``).

Mistral's block with a sparse mixture of experts for its MLP, from Jiang et
al. 2024 (arXiv:2401.04088) and the ``mixtral`` model card: a softmax router
over ``num_local_experts``, the ``num_experts_per_tok`` largest kept and
renormalised to sum to one, each expert a SwiGLU MLP. No auxiliary term: the
fixture's configuration switches the program's load-balance loss off, so
its reported loss is the token cross-entropy alone.
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


def _sparse_moe(x, w: Weights, p: str, experts: int, per_token: int):
    """Every expert on every token, weighted by the router's renormalised
    top-k probabilities (zero for the experts a token did not choose)."""
    probs = jax.nn.softmax(x @ w[p + "gate.weight"].T, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, per_token)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(experts):
        q = p + f"experts.{e}."
        y = (jax.nn.silu(x @ w[q + "w1.weight"].T)
             * (x @ w[q + "w3.weight"].T)) @ w[q + "w2.weight"].T
        share = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)
        out = out + share[..., None] * y
    return out


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h = w["model.embed_tokens.weight"][tokens]
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        p = f"model.layers.{i}."
        a = rms_norm(h, w[p + "input_layernorm.weight"], eps)
        q = rope(split_heads(a @ w[p + "self_attn.q_proj.weight"].T, nh),
                 theta)
        k = rope(split_heads(a @ w[p + "self_attn.k_proj.weight"].T, nkv),
                 theta)
        v = split_heads(a @ w[p + "self_attn.v_proj.weight"].T, nkv)
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        h = h + merge_heads(causal_attention(q, k, v)) \
            @ w[p + "self_attn.o_proj.weight"].T
        m = rms_norm(h, w[p + "post_attention_layernorm.weight"], eps)
        h = h + _sparse_moe(m, w, p + "block_sparse_moe.",
                            cfg["num_local_experts"],
                            cfg["num_experts_per_tok"])
    h = rms_norm(h, w["model.norm.weight"], eps)
    return token_nll_sum(h @ w["lm_head.weight"].T, labels)


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """Attention as the dense count has it; then the router and the
    ``num_experts_per_tok`` gated experts a token uses, not one MLP and not
    all ``num_local_experts``."""
    router = 2 * sizes.hidden * config["num_local_experts"]
    experts = (config["num_experts_per_tok"]
               * 2 * sizes.hidden * config["intermediate_size"] * 3)
    return (sizes.layers * (flops.attention_flops_per_token(sizes)
                            + router + experts)
            + flops.head_flops_per_token(sizes))
