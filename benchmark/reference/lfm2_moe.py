"""Reference family ``lfm2_moe``: LFM2-24B-A2B's block stack, written from
the published ``config.json`` (LiquidAI/LFM2-24B-A2B, ``model_type``
``lfm2_moe``) and, for the dense pieces, ``transformers``' ``Lfm2ShortConv``,
``Lfm2Attention`` and ``Lfm2DecoderLayer``; fed ``model.*`` tensors under
their public Hugging Face names. With ``a = RMSNorm(h)``, every projection
without bias:

* block: ``h <- h + Op(RMSNorm(h; operator_norm))``, then
  ``h <- h + FF(RMSNorm(h; ffn_norm))``;
* ``Op`` of a ``conv`` block: ``[B, C, X] = split3(a W_in)``; ``u = B * X``;
  ``c[t] = w[:, 0] u[t-2] + w[:, 1] u[t-1] + w[:, 2] u[t]`` (depthwise,
  causal, zero history before the sequence; ``w`` is
  ``conv.conv.weight[:, 0, :]``); ``Op = (C * c) W_out``. Written for any
  ``conv_L_cache`` as that many shifted products;
* ``Op`` of a ``full_attention`` block: ``q`` and ``k`` RMS-normed PER HEAD
  (one learned scale of ``head_dim`` a projection, over each head's own
  values), RoPE (rotate-half), causal softmax attention at
  ``head_dim ** -0.5``, grouped queries, ``out_proj``;
* ``FF`` of the first ``num_dense_layers`` blocks: ``W2(silu(W1 a) * W3 a)``;
* ``FF`` of every other block: ``s = sigmoid(a Wg)`` over all routed
  experts; the ``num_experts_per_tok`` chosen are the largest of ``s + b``
  (``b`` the expert bias: it steers the choice, never the weights);
  ``g_e = s_e / (sum of the chosen s + 1e-6) * routed_scaling_factor``;
  ``FF = sum over the chosen of g_e W2_e(silu(W1_e a) * W3_e a)``. Computed
  the plain way: every HELD expert on every token, times a weight that is
  zero unless the expert is among the token's chosen;
* after the last block ``RMSNorm(h; embedding_norm)`` and logits through the
  transposed embedding; the loss is token cross-entropy alone (the published
  config has no load-balancing or z-loss key).

DEPARTURES from the published model, each because the configuration's file
states it and the program under test runs the same:

* the share: the weights hold experts ``[first_expert_held, first_expert_held
  + num_experts)`` of the router's ``num_routed_experts``, under their
  published indices. The router scores all of them and keeps its
  ``num_experts_per_tok``; what the absent experts would have added is left
  out, and that partial sum is what goes on to the next block;
* the sliced vocabulary: ``vocab_size`` rows of the published 65536; ids,
  logits and the loss are over the slice;
* the FLOP count takes the held experts at their EXPECTED share of the
  routes, ``num_experts_per_tok * held / routed`` a token and block (what a
  balanced router sends them), not at what one batch happened to route.
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

ROUTER_EPS = 1e-6


def short_conv(a, w: Weights, p: str, taps: int):
    """The gated short convolution of one block; ``a`` [B, S, hidden]."""
    S = a.shape[1]
    gate_b, gate_c, x = jnp.split(a @ w[p + "in_proj.weight"].T, 3, axis=-1)
    u = gate_b * x
    kernel = w[p + "conv.weight"][:, 0, :]            # [hidden, taps]
    c = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j                           # tap j meets u[t - back]
        c = c + kernel[:, j] * jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :S]
    return (gate_c * c) @ w[p + "out_proj.weight"].T


def attention(a, w: Weights, p: str, cfg: Mapping):
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["norm_eps"], cfg["rope_parameters"]["rope_theta"]
    # split_heads gives [B, heads, S, head_dim]: the norm is over the last
    # axis, one head's values
    q = rms_norm(split_heads(a @ w[p + "q_proj.weight"].T, nh),
                 w[p + "q_layernorm.weight"], eps)
    k = rms_norm(split_heads(a @ w[p + "k_proj.weight"].T, nkv),
                 w[p + "k_layernorm.weight"], eps)
    v = split_heads(a @ w[p + "v_proj.weight"].T, nkv)
    q, k = rope(q, theta), rope(k, theta)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    return merge_heads(causal_attention(q, k, v)) @ w[p + "out_proj.weight"].T


def dense_mlp(a, w: Weights, p: str):
    return (jax.nn.silu(a @ w[p + "w1.weight"].T)
            * (a @ w[p + "w3.weight"].T)) @ w[p + "w2.weight"].T


def held_experts(cfg: Mapping) -> range:
    first = cfg.get("first_expert_held", 0)
    return range(first, first + cfg["num_experts"])


def sparse_experts(x, w: Weights, p: str, cfg: Mapping):
    """``x`` [tokens, hidden] -> what the held experts add."""
    E, K = cfg["num_routed_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid((x @ w[p + "gate.weight"].T).astype(jnp.float32))
    select = s + w[p + "expert_bias"] if cfg.get("use_expert_bias") else s
    _, top_i = jax.lax.top_k(select, K)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if cfg.get("norm_topk_prob"):
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + ROUTER_EPS)
    top_s = top_s * cfg.get("routed_scaling_factor", 1)
    combine = jnp.einsum("tk,tke->te", top_s,
                         jax.nn.one_hot(top_i, E, dtype=top_s.dtype))
    out = jnp.zeros_like(x)
    for e in held_experts(cfg):
        q = p + f"experts.{e}."
        y = (jax.nn.silu(x @ w[q + "w1.weight"].T)
             * (x @ w[q + "w3.weight"].T)) @ w[q + "w2.weight"].T
        out = out + combine[:, e:e + 1].astype(x.dtype) * y
    return out


def hidden_states(w: Weights, cfg: Mapping, tokens, *,
                  layers: Optional[int] = None):
    """The hidden states [B, S, hidden] after ``embedding_norm``."""
    eps = cfg["norm_eps"]
    h = w["model.embed_tokens.weight"][tokens]
    kinds = cfg["layer_types"]
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        p = f"model.layers.{i}."
        a = rms_norm(h, w[p + "operator_norm.weight"], eps)
        if kinds[i] == "conv":
            h = h + short_conv(a, w, p + "conv.", cfg["conv_L_cache"])
        elif kinds[i] == "full_attention":
            h = h + attention(a, w, p + "self_attn.", cfg)
        else:
            raise ValueError(f"layer_types[{i}] = {kinds[i]!r}")
        m = rms_norm(h, w[p + "ffn_norm.weight"], eps)
        if i < cfg["num_dense_layers"]:
            h = h + dense_mlp(m, w, p + "feed_forward.")
        else:
            y = sparse_experts(m.reshape(-1, m.shape[-1]), w,
                               p + "feed_forward.", cfg)
            h = h + y.reshape(m.shape)
    return rms_norm(h, w["model.embedding_norm.weight"], eps)


def logits(w: Weights, cfg: Mapping, tokens, *, layers: Optional[int] = None):
    return hidden_states(w, cfg, tokens, layers=layers) \
        @ w["model.embed_tokens.weight"].T


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    """Sum of token negative log-likelihoods (the embedding is tied)."""
    return token_nll_sum(logits(w, cfg, tokens, layers=layers), labels)


def attention_blocks(config: Mapping) -> List[Dict[str, int]]:
    """One empty entry (the model's own heads, the whole causal span) for
    each ``full_attention`` block of ``layer_types`` as run; a ``conv``
    block has none."""
    return [{} for kind in config["layer_types"] if kind == "full_attention"]


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """Blocks added up by kind. A block that attends: q, k, v, out and the
    causal attention (``flops.attention_flops_per_token``); a conv block:
    ``in_proj`` (hidden x 3 hidden) and ``out_proj`` (hidden x hidden), the
    depthwise taps being no matmul; a dense block's gated MLP of
    ``intermediate_size``; an expert block's router over all routed experts
    and the held experts at their expected share of the routes,
    ``num_experts_per_tok * held / routed`` a token; the tied head over the
    sliced vocabulary."""
    H = sizes.hidden
    conv = 2 * (3 * H * H + H * H)
    dense = 2 * 3 * H * config["intermediate_size"]
    routes = (config["num_experts_per_tok"] * config["num_experts"]
              / config["num_routed_experts"])
    experts = (2 * H * config["num_routed_experts"]
               + routes * 2 * 3 * H * config["moe_intermediate_size"])
    n = len(config["layer_types"])
    n_conv = sum(kind == "conv" for kind in config["layer_types"])
    n_dense = config["num_dense_layers"]
    return (sum(flops.attention_flops_per_token(sizes, entry)
                for entry in sizes.attention_blocks())
            + n_conv * conv + n_dense * dense + (n - n_dense) * experts
            + flops.head_flops_per_token(sizes))
