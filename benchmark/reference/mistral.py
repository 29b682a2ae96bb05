"""Reference family ``mistral``: a Mistral block stack, from Jiang et al.
2023 (arXiv:2310.06825) and the ``mistral`` model card, fed ``model.*``
tensors under their public Hugging Face names.

Departure from the published model, because the program under test trains
that way and the comparison is of the same mathematics: no sliding window
(published 4096). With sequences of at most 4096 tokens the window and the
causal mask are the same mask.
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


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    """Sum of token negative log-likelihoods."""
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
        # grouped-query attention: each key-value head serves nh/nkv
        # consecutive query heads
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        a = merge_heads(causal_attention(q, k, v))
        h = h + a @ w[p + "self_attn.o_proj.weight"].T
        m = rms_norm(h, w[p + "post_attention_layernorm.weight"], eps)
        m = (jax.nn.silu(m @ w[p + "mlp.gate_proj.weight"].T)
             * (m @ w[p + "mlp.up_proj.weight"].T))
        h = h + m @ w[p + "mlp.down_proj.weight"].T
    h = rms_norm(h, w["model.norm.weight"], eps)
    head = (w["model.embed_tokens.weight"] if cfg.get("tie_word_embeddings")
            else w["lm_head.weight"])
    return token_nll_sum(h @ head.T, labels)


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """q, k, v, out, one gated three-matrix MLP and causal attention a
    block, and the untied head: the dense count as it is."""
    return flops.forward_flops_per_token(sizes)
