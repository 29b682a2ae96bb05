"""Reference family ``laguna``: Laguna-S-2.1's block stack, written from the
published ``config.json`` (poolside/Laguna-S-2.1, ``model_type`` ``laguna``)
key by key. Fed ``model.*`` tensors under llama's public names with
``self_attn.g_proj``, ``mlp.gate``, ``mlp.experts.{e}.{gate,up,down}_proj``
and ``mlp.shared_expert.{gate,up,down}_proj``. ``H`` hidden, RMSNorm with
``rms_norm_eps`` before each sub-layer, no bias anywhere
(``attention_bias: false``):

* block ``i`` is of kind ``layer_types[i]`` and has ``n_i =
  num_attention_heads_per_layer[i]`` query heads over ``num_key_value_heads``
  key-value heads of ``head_dim``: ``q = u W_q`` (``n_i`` heads), ``k = u
  W_k``, ``v = u W_v``, ``u`` the normed input; query head ``h`` reads
  key-value head ``h // (n_i / kv heads)``;
* a rotation a kind (``rope_parameters[kind]``), rotate-half, over the first
  ``partial_rotary_factor x head_dim`` values of a head, the rest passed
  through: ``rope_type`` ``default`` plain at ``rope_theta``; ``yarn`` with
  transformers' ``_compute_yarn_parameters`` over the ROTATED width (band
  ``j`` divided by ``factor`` where it turns fewer than ``beta_slow`` times
  within ``original_max_position_embeddings``, kept where more than
  ``beta_fast``, a linear ramp between), cos and sin times the STATED
  ``attention_factor``;
* the core: ``softmax(q k^T / sqrt(head_dim))`` over the causal span; in a
  ``sliding_attention`` block over the ``sliding_window`` newest keys of it,
  the query's own included;
* the gate (``gating: per-head``): ``g = sigmoid(u W_g)``, one logit a query
  head; head ``h``'s output is ``g_h o_h`` before ``W_o``;
* feed-forward of the blocks in ``mlp_only_layers``: SwiGLU of
  ``intermediate_size``; of every other: ``s = sigmoid(u W_r)`` over all
  ``num_routed_experts``, the ``num_experts_per_tok`` largest chosen, their
  scores over their sum (``norm_topk_prob``; + 1e-20) times
  ``moe_routed_scaling_factor``; ``y = sum_e w_e E_e(u) + S(u)``, every
  expert a SwiGLU of ``moe_intermediate_size``, the shared one of
  ``shared_expert_intermediate_size``, ungated. The plain way: every HELD
  expert on every token, times a weight that is zero unless it is among the
  chosen;
* final RMSNorm, untied head.

DEPARTURES from the published model, each because the configuration's file
states it and the program under test runs the same:

* the share: the weights hold experts ``[first_expert_held, + num_experts)``
  of the router's ``num_routed_experts``, under their published indices;
  what the absent experts would have added is left out, the shared expert is
  whole;
* the sliced vocabulary: ``vocab_size`` rows of the published 100352; ids,
  logits and the loss are over the slice;
* the FLOP count takes the held experts at their EXPECTED share of the
  routes, ``num_experts_per_tok x held / routed`` a token and block.
"""

from __future__ import annotations

import math
from typing import Dict, List, Mapping, Optional

import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark.reference.plain import (
    Weights,
    causal_attention,
    merge_heads,
    rms_norm,
    rotate_half,
    split_heads,
    token_nll_sum,
)

ROUTER_EPS = 1e-20


def rope_tables(seq: int, dim: int, rp: Mapping):
    """cos and sin [seq, dim] of one kind's rotation over ``dim`` rotated
    values of a head."""
    theta = rp["rope_theta"]
    freq = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inv_freq, scale = 1.0 / freq, 1.0
    if rp.get("rope_type", "default") == "yarn":
        factor, orig = rp["factor"], rp["original_max_position_embeddings"]

        def band(turns):
            return dim * math.log(orig / (turns * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(band(rp["beta_fast"])), 0)
        high = min(math.ceil(band(rp["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        keep = 1.0 - jnp.clip(
            (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
            0, 1)
        inv_freq = (1.0 / (factor * freq)) * (1 - keep) + (1.0 / freq) * keep
        scale = rp["attention_factor"]
    elif rp.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rotate(x, rp: Mapping):
    """x [B, heads, S, D]: the first ``partial_rotary_factor x D`` values of
    a head rotated, the rest as they are."""
    S, D = x.shape[-2], x.shape[-1]
    dim = int(D * rp.get("partial_rotary_factor", 1))
    cos, sin = rope_tables(S, dim, rp)
    # the tables at x's dtype, so that a reference asked for in bfloat16
    # stays bfloat16 past this line
    r = x[..., :dim]
    r = r * cos.astype(x.dtype) + rotate_half(r) * sin.astype(x.dtype)
    return jnp.concatenate([r, x[..., dim:]], axis=-1)


def block_kind(cfg: Mapping, i: int) -> str:
    return cfg["layer_types"][i]


def expand_kv(t, heads: int):
    """[B, kv heads, S, D] as [B, heads, S, D]: query head ``h`` reads
    key-value head ``h // (heads / kv heads)``."""
    return jnp.repeat(t, heads // t.shape[1], axis=1)


def attention(u, w: Weights, p: str, cfg: Mapping, i: int):
    n = cfg["num_attention_heads_per_layer"][i]
    nkv = cfg["num_key_value_heads"]
    kind = block_kind(cfg, i)
    rp = cfg["rope_parameters"][kind]
    q = rotate(split_heads(u @ w[p + "q_proj.weight"].T, n), rp)
    k = rotate(split_heads(u @ w[p + "k_proj.weight"].T, nkv), rp)
    v = split_heads(u @ w[p + "v_proj.weight"].T, nkv)
    k, v = expand_kv(k, n), expand_kv(v, n)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    o = causal_attention(q, k, v, window)                 # [B, n, S, D]
    if cfg.get("gating"):
        g = jax.nn.sigmoid(u @ w[p + "g_proj.weight"].T)  # [B, S, n]
        o = o * g.transpose(0, 2, 1)[..., None]
    return merge_heads(o) @ w[p + "o_proj.weight"].T


def swiglu(x, w: Weights, p: str):
    return (jax.nn.silu(x @ w[p + "gate_proj.weight"].T)
            * (x @ w[p + "up_proj.weight"].T)) @ w[p + "down_proj.weight"].T


def held_experts(cfg: Mapping) -> range:
    first = cfg.get("first_expert_held", 0)
    return range(first, first + cfg["num_experts"])


def routed_weights(x, w: Weights, p: str, cfg: Mapping):
    """[tokens, num_routed_experts]: a token's weight on each expert, zero
    off its chosen."""
    E, K = cfg["num_routed_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid((x @ w[p + "gate.weight"].T).astype(jnp.float32))
    top_s, top_i = jax.lax.top_k(s, K)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + ROUTER_EPS)
    top_s = top_s * cfg["moe_routed_scaling_factor"]
    return jnp.einsum("tk,tke->te", top_s,
                      jax.nn.one_hot(top_i, E, dtype=top_s.dtype))


def experts(x, w: Weights, p: str, cfg: Mapping, held=None, shared=True):
    """``x`` [tokens, hidden] -> what the ``held`` experts (default: this
    share's) and, with ``shared``, the shared expert add."""
    combine = routed_weights(x, w, p, cfg)
    out = swiglu(x, w, p + "shared_expert.") if shared else jnp.zeros_like(x)
    for e in held_experts(cfg) if held is None else held:
        out = out + combine[:, e:e + 1].astype(x.dtype) * swiglu(
            x, w, p + f"experts.{e}.")
    return out


def block(x, w: Weights, i: int, cfg: Mapping):
    p, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, w[p + "input_layernorm.weight"], eps), w,
                      p + "self_attn.", cfg, i)
    m = rms_norm(x, w[p + "post_attention_layernorm.weight"], eps)
    if i in cfg["mlp_only_layers"]:
        return x + swiglu(m, w, p + "mlp.")
    return x + experts(m.reshape(-1, m.shape[-1]), w, p + "mlp.",
                       cfg).reshape(m.shape)


def logits(w: Weights, cfg: Mapping, tokens, *, layers: Optional[int] = None):
    x = w["model.embed_tokens.weight"][tokens]
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        x = block(x, w, i, cfg)
    return rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"]) \
        @ w["lm_head.weight"].T


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    # the loss summed in float32 whatever the weights' precision (nothing
    # in float32): a reference asked for in bfloat16 then reads what
    # bfloat16 did to the logits, where a bfloat16 sum over 8192 tokens
    # would read the same multiple of 1/16 on every seed
    return token_nll_sum(
        logits(w, cfg, tokens, layers=layers).astype(jnp.float32), labels)


def attention_blocks(config: Mapping) -> List[Dict[str, int]]:
    """One entry a block: its own query heads, and the window of a
    ``sliding_attention`` block."""
    return [{"heads": n, **({"window": config["sliding_window"]}
                            if kind == "sliding_attention" else {})}
            for kind, n in zip(config["layer_types"],
                               config["num_attention_heads_per_layer"])]


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """Blocks added up. A block's attention: its projections at its own
    heads and its core over its span (``flops.attention_flops_per_token`` an
    entry of ``sizes.attention_blocks()``) and the gate's ``H x heads``; a
    dense block's SwiGLU; an expert block's router over all routed experts,
    the held experts at ``num_experts_per_tok x held / routed`` routes a
    token and the shared expert; the head."""
    H = sizes.hidden
    attn = sum(flops.attention_flops_per_token(sizes, a)
               + (2 * H * (a.heads or sizes.heads) if config.get("gating")
                  else 0)
               for a in sizes.attention_blocks())
    dense = 2 * 3 * H * config["intermediate_size"]
    routes = (config["num_experts_per_tok"] * config["num_experts"]
              / config["num_routed_experts"])
    sparse = (2 * H * config["num_routed_experts"]
              + routes * 2 * 3 * H * config["moe_intermediate_size"]
              + 2 * 3 * H * config["shared_expert_intermediate_size"])
    n_dense = len(config["mlp_only_layers"])
    return (attn + n_dense * dense
            + (config["num_hidden_layers"] - n_dense) * sparse
            + flops.head_flops_per_token(sizes))
