"""Reference family ``tiny_hybrid``: a stack whose blocks differ, kept here
as a test fixture (``tiny.add_hybrid_family`` copies it into
``<copy>/benchmark/reference/``): what a later PR adds for a model of
attention blocks among blocks of another kind, some of them windowed.

Mistral's block, with the configuration's ``layer_types`` saying for each
block whether it attends over the whole causal span (``attention``), over
its last ``sliding_window`` keys, itself included (``sliding_attention``),
or not at all (``mlp``: the block is its gated MLP alone). No published
model: the stand-in for a convolution or a scan is the simplest block that
does not attend, because the fixture is about where attention is COUNTED.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Optional

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


def attention_blocks(config: Mapping) -> List[Dict[str, int]]:
    """One entry a block that attends, in order."""
    return [{"window": config["sliding_window"]}
            if kind == "sliding_attention" else {}
            for kind in config["layer_types"] if kind != "mlp"]


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h = w["model.embed_tokens.weight"][tokens]
    for i, kind in enumerate(cfg["layer_types"][:layers]):
        p = f"model.layers.{i}."
        if kind != "mlp":
            a = rms_norm(h, w[p + "input_layernorm.weight"], eps)
            q = rope(split_heads(a @ w[p + "self_attn.q_proj.weight"].T, nh),
                     theta)
            k = rope(split_heads(a @ w[p + "self_attn.k_proj.weight"].T,
                                 nkv), theta)
            v = split_heads(a @ w[p + "self_attn.v_proj.weight"].T, nkv)
            k = jnp.repeat(k, nh // nkv, axis=1)
            v = jnp.repeat(v, nh // nkv, axis=1)
            window = (cfg["sliding_window"] if kind == "sliding_attention"
                      else None)
            h = h + merge_heads(causal_attention(q, k, v, window)) \
                @ w[p + "self_attn.o_proj.weight"].T
        m = rms_norm(h, w[p + "post_attention_layernorm.weight"], eps)
        m = (jax.nn.silu(m @ w[p + "mlp.gate_proj.weight"].T)
             * (m @ w[p + "mlp.up_proj.weight"].T))
        h = h + m @ w[p + "mlp.down_proj.weight"].T
    h = rms_norm(h, w["model.norm.weight"], eps)
    return token_nll_sum(h @ w["lm_head.weight"].T, labels)


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """A gated MLP in every block, attention (projections and its span) in
    the blocks ``sizes.attention`` lists, and the head."""
    mlp = 2 * sizes.hidden * sizes.ffn * sizes.ffn_matrices
    attention = sum(flops.attention_flops_per_token(sizes, entry)
                    for entry in sizes.attention_blocks())
    return sizes.layers * mlp + attention + flops.head_flops_per_token(sizes)
