"""Reference family ``mellum``: Mellum2-12B-A2.5B's block stack, written from
the published ``config.json`` (JetBrains/Mellum2-12B-A2.5B-Instruct,
``model_type`` ``mellum``) key by key. Fed ``model.*`` tensors under llama's
public names with ``self_attn.{q,k}_norm``, ``mlp.gate`` and
``mlp.experts.{e}.{gate,up,down}_proj``. ``H`` hidden, RMSNorm with
``rms_norm_eps`` before each sub-layer, no bias anywhere
(``attention_bias: false``):

* ``q = u W_q`` (``num_attention_heads`` heads of ``head_dim``), ``k = u
  W_k``, ``v = u W_v`` (``num_key_value_heads`` heads), ``u`` the normed
  input; q and k pass an RMSNorm over a head's ``head_dim`` values, one
  learned scale for q and one for k (Qwen3's; assumed, the configuration's
  file says why); query head ``h`` reads key-value head ``h // (heads / kv
  heads)``;
* a rotation a kind of block (``rope_parameters[layer_types[i]]``),
  rotate-half over the whole head: ``default`` plain at ``rope_theta``;
  ``yarn`` by transformers' ``_compute_yarn_parameters`` (a band divided by
  ``factor`` where it turns fewer than ``beta_slow`` times within
  ``original_max_position_embeddings``, kept where more than ``beta_fast``,
  a linear ramp between), cos and sin times the STATED ``attention_factor``;
* the core: ``softmax(q k^T / sqrt(head_dim))`` over the causal span; in a
  ``sliding_attention`` block over the ``sliding_window`` newest keys of it,
  the query's own included;
* every block's feed-forward is ``sparse``: ``p = softmax(u W_r)`` over all
  ``num_experts`` in float32, the ``num_experts_per_tok`` largest chosen,
  divided by their sum where ``norm_topk_prob``; ``y = sum_e w_e E_e(u)``,
  each expert a SwiGLU of ``moe_intermediate_size``. The plain way: every
  expert on every token, times a weight that is zero unless it is among the
  chosen. ``intermediate_size`` belongs to no block;
* final RMSNorm, untied head; the loss is cross-entropy alone.

Nothing departs from the published model but the depth: the configuration
holds every expert and the whole vocabulary. ``cfg["experts_left_out"]`` (no
configuration's file has it; ``benchmark/mellum_controls.py`` sets it) names
experts whose share is left out of ``y``, to show that the comparison sees a
chip's experts missing.
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

# positions whose logits exist at once: 2048 x 98304 float32 is 0.75 GiB
HEAD_POSITIONS = 2048


def inverse_frequencies(dim: int, rp: Mapping):
    """(a band's angle a position [dim / 2], what cos and sin are multiplied
    by) of one kind's rotation."""
    theta = float(rp["rope_theta"])
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    kind = rp.get("rope_type", "default")
    if kind == "default":
        return plain, 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}")
    span = rp["original_max_position_embeddings"]

    def band_of(turns):
        # the band that turns ``turns`` times within the original span
        return dim * math.log(span / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(band_of(rp["beta_fast"])), 0)
    high = min(math.ceil(band_of(rp["beta_slow"])), dim - 1)
    high = high + 0.001 if high == low else high
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    # ramp 0: the band keeps its frequency; 1: divided by the factor
    return (plain * (1.0 - ramp) + plain / rp["factor"] * ramp,
            rp["attention_factor"])


def rotate(x, rp: Mapping):
    """x [B, heads, S, D] rotated over the whole head."""
    S, D = x.shape[-2], x.shape[-1]
    inv_freq, scale = inverse_frequencies(D, rp)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    # the tables at x's dtype: a reference asked for in bfloat16 stays so
    return x * cos.astype(x.dtype) + rotate_half(x) * sin.astype(x.dtype)


def attention(u, w: Weights, p: str, cfg: Mapping, i: int):
    n, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    kind, eps = cfg["layer_types"][i], cfg["rms_norm_eps"]
    rp = cfg["rope_parameters"][kind]
    q = split_heads(u @ w[p + "q_proj.weight"].T, n)
    k = split_heads(u @ w[p + "k_proj.weight"].T, nkv)
    v = split_heads(u @ w[p + "v_proj.weight"].T, nkv)
    q = rotate(rms_norm(q, w[p + "q_norm.weight"], eps), rp)
    k = rotate(rms_norm(k, w[p + "k_norm.weight"], eps), rp)
    k, v = (jnp.repeat(t, n // nkv, axis=1) for t in (k, v))
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    return merge_heads(causal_attention(q, k, v, window)) \
        @ w[p + "o_proj.weight"].T


def combine_weights(x, w: Weights, p: str, cfg: Mapping):
    """[tokens, num_experts]: a token's weight on each expert, zero off its
    chosen."""
    E, K = cfg["num_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(
        (x @ w[p + "gate.weight"].T).astype(jnp.float32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, K)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.einsum("tk,tke->te", top_p,
                      jax.nn.one_hot(top_i, E, dtype=top_p.dtype))


def experts(x, w: Weights, p: str, cfg: Mapping):
    """``x`` [tokens, hidden] -> what its chosen experts add."""
    combine = combine_weights(x, w, p, cfg)
    out = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):
        if e in cfg.get("experts_left_out", ()):
            continue
        q = p + f"experts.{e}."
        y = (jax.nn.silu(x @ w[q + "gate_proj.weight"].T)
             * (x @ w[q + "up_proj.weight"].T)) @ w[q + "down_proj.weight"].T
        out = out + combine[:, e:e + 1].astype(x.dtype) * y
    return out


def block(x, w: Weights, i: int, cfg: Mapping):
    p, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, w[p + "input_layernorm.weight"], eps), w,
                      p + "self_attn.", cfg, i)
    m = rms_norm(x, w[p + "post_attention_layernorm.weight"], eps)
    return x + experts(m.reshape(-1, m.shape[-1]), w, p + "mlp.",
                       cfg).reshape(m.shape)


def hidden(w: Weights, cfg: Mapping, tokens, *, layers: Optional[int] = None):
    """The final norm's output [B, S, H]."""
    x = w["model.embed_tokens.weight"][tokens]
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        x = block(x, w, i, cfg)
    return rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])


def logits(w: Weights, cfg: Mapping, tokens, *, layers: Optional[int] = None):
    return hidden(w, cfg, tokens, layers=layers) @ w["lm_head.weight"].T


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    # the head a stretch of positions at a time, and the loss summed in
    # float32 whatever the weights' precision: a reference asked for in
    # bfloat16 then reads what bfloat16 did to the logits
    x = hidden(w, cfg, tokens, layers=layers)
    total = jnp.zeros((), jnp.float32)
    for lo in range(0, x.shape[1], HEAD_POSITIONS):
        part = x[:, lo:lo + HEAD_POSITIONS] @ w["lm_head.weight"].T
        total = total + token_nll_sum(part.astype(jnp.float32),
                                      labels[:, lo:lo + HEAD_POSITIONS])
    return total


def attention_blocks(config: Mapping) -> List[Dict[str, int]]:
    """One entry a block: the window of a ``sliding_attention`` block."""
    return [{"window": config["sliding_window"]}
            if kind == "sliding_attention" else {}
            for kind in config["layer_types"]]


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """Blocks added up: a block's projections and its core over its span
    (``flops.attention_flops_per_token`` an entry of
    ``sizes.attention_blocks()``), its router over all experts and the
    ``num_experts_per_tok`` experts a token uses (three matrices of ``H x
    moe_intermediate_size`` each); the head."""
    H = sizes.hidden
    attn = sum(flops.attention_flops_per_token(sizes, a)
               for a in sizes.attention_blocks())
    sparse = (2 * H * config["num_experts"]
              + config["num_experts_per_tok"] * 2 * 3 * H
              * config["moe_intermediate_size"])
    return (attn + config["num_hidden_layers"] * sparse
            + flops.head_flops_per_token(sizes))
