"""Reference family ``olmo_hybrid``: Olmo-Hybrid-7B's block stack, written
from the published ``config.json`` (allenai/Olmo-Hybrid-7B, ``model_type``
``olmo_hybrid``), the Gated DeltaNet paper (arXiv:2412.06464) with ``beta``
doubled (arXiv:2411.12537), Hugging Face's ``Qwen3NextGatedDeltaNet``
(``torch_recurrent_gated_delta_rule``, ``Qwen3NextRMSNormGated``, ``l2norm``),
whose ``linear_*`` keys these are, and ``Olmo3Attention`` /
``Olmo3DecoderLayer``. Fed ``model.*`` tensors under their public names.
``H`` hidden, ``n`` heads, keys of ``dk`` under values of ``dv``; every
projection without bias; ``layer_types[i]`` names block ``i``:

* a ``linear_attention`` block, norms on the branches' INPUTS: ``h = x +
  GDN(RMSNorm(x))``, ``y = h + SwiGLU(RMSNorm(h))``. ``GDN(u)``: ``q~ =
  silu(conv(u W_q))``, ``k~ = silu(conv(u W_k))``, ``v = silu(conv(u W_v))``,
  each convolution causal and depthwise, ``linear_conv_kernel_dim`` taps,
  zeros before the sequence; a head's ``q = q~ / sqrt(|q~|^2 + 1e-6) *
  dk^-0.5``, ``k = k~ / sqrt(|k~|^2 + 1e-6)``; ``beta_t = sigmoid(u W_b)`` a
  head, times 2 under ``linear_allow_neg_eigval``; the log decay a head ``g_t
  = -exp(A_log) softplus(u W_a + dt_bias)``. Per head, with the state ``S``
  [dk, dv] (keys x values), zero before the sequence::

      S~  = exp(g_t) S_(t-1)
      S_t = S~ + k_t (beta_t (v_t - S~^T k_t))^T ;   o_t = S_t^T q_t

  computed AS THAT RECURRENCE, one position at a time (a ``lax.scan`` over
  positions), so that it shares nothing with the chunked form of the
  program under test. Then ``y = RMSNorm_dv(o; o_norm) * silu(u W_g)`` a
  head, the norm BEFORE the gate, and ``y W_o``;
* a ``full_attention`` block, norms on the branches' OUTPUTS (Olmo 3): ``h =
  x + RMSNorm(Attn(x))``, ``y = h + RMSNorm(SwiGLU(h))``. ``Attn``: ``q =
  RMSNorm_(n d)(x W_q)``, ``k = RMSNorm(x W_k)`` over the whole width before
  the split into heads, ``v = x W_v``; causal ``softmax(q k^T / sqrt(d)) v``
  WITHOUT rotation (``rope_parameters.rope_theta`` null); ``W_o``;
* a final RMSNorm, an untied head, token cross-entropy alone.

DEPARTURES from the published model, each because the configuration's file
states it and the program under test runs the same:

* depth: the first ``num_hidden_layers`` published blocks (``layer_types``
  cut to them);
* the sliced vocabulary: ``vocab_size`` rows of the published 100352; ids,
  logits and the loss are over the slice;
* the FLOP count takes the recurrence as the recurrence (``S~^T k``, the
  rank-one update and ``S^T q``, ``6 dk dv`` a head and token), not as the
  chunked form an implementation may choose, so that ``mfu_pct`` does not
  move with the chunk.
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
    split_heads,
    token_nll_sum,
)

L2_EPS = 1e-6


def causal_conv(u, kernel):
    """``u`` [B, S, C], ``kernel`` [C, 1, taps] (a depthwise ``Conv1d``'s):
    tap ``j`` meets ``u[t - (taps - 1 - j)]``, zeros before the sequence."""
    S, taps = u.shape[1], kernel.shape[-1]
    c = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j
        c = c + kernel[:, 0, j] * jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :S]
    return c


def short_conv(u, kernel):
    """A depthwise causal convolution and the SiLU behind it."""
    return jax.nn.silu(causal_conv(u, kernel))


def delta_rule(q, k, v, g, beta):
    """The recurrence, one position at a time. ``q``, ``k`` [B, S, n, dk],
    ``v`` [B, S, n, dv], ``g``, ``beta`` [B, S, n] -> ``o`` [B, S, n, dv]."""
    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None, None] * state       # S~ [B, n, dk, dv]
        delta = (v_t - jnp.einsum("bnkv,bnk->bnv", state, k_t)) \
            * b_t[..., None]
        state = state + k_t[..., None] * delta[..., None, :]
        return state, jnp.einsum("bnkv,bnk->bnv", state, q_t)

    zero = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], q.dtype)
    _, o = jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + L2_EPS)


def gated_norm(o, z, weight, eps):
    """``Qwen3NextRMSNormGated``: the norm a head, THEN the SiLU gate."""
    return rms_norm(o, weight, eps) * jax.nn.silu(z)


def gated_delta_net(u, w: Weights, p: str, cfg: Mapping):
    """The Gated DeltaNet mixer of one block; ``u`` [B, S, H]."""
    n, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    if cfg["linear_num_key_heads"] != n:
        raise ValueError("written for as many key heads as value heads")
    if w[p + "q_conv1d.weight"].shape[-1] != cfg["linear_conv_kernel_dim"]:
        raise ValueError("the convolutions' taps are not "
                         "linear_conv_kernel_dim")
    B, S, _ = u.shape
    q, k, v = (short_conv(
        u @ w[p + f"{m}_proj.weight"].T, w[p + f"{m}_conv1d.weight"]
    ).reshape(B, S, n, -1) for m in "qkv")
    q, k = unit(q) * dk ** -0.5, unit(k)
    beta = jax.nn.sigmoid(u @ w[p + "b_proj.weight"].T)
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(w[p + "A_log"]) * jax.nn.softplus(
        u @ w[p + "a_proj.weight"].T + w[p + "dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    z = (u @ w[p + "g_proj.weight"].T).reshape(B, S, n, dv)
    y = gated_norm(o, z, w[p + "o_norm.weight"], cfg["rms_norm_eps"])
    return y.reshape(B, S, n * dv) @ w[p + "o_proj.weight"].T


def qk_norm(t, weight, eps):
    """Olmo's RMSNorm over ALL heads' width of a projected q or k."""
    return rms_norm(t, weight, eps)


def attention(x, w: Weights, p: str, cfg: Mapping):
    """Olmo 3's attention without rotation; ``x`` [B, S, H]."""
    if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("written for rope_parameters.rope_theta null")
    nh, nkv, eps = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["rms_norm_eps"])
    q = split_heads(qk_norm(x @ w[p + "q_proj.weight"].T,
                            w[p + "q_norm.weight"], eps), nh)
    k = split_heads(qk_norm(x @ w[p + "k_proj.weight"].T,
                            w[p + "k_norm.weight"], eps), nkv)
    v = split_heads(x @ w[p + "v_proj.weight"].T, nkv)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    # causal_attention divides by sqrt(head width), the model's own scale
    return merge_heads(causal_attention(q, k, v)) @ w[p + "o_proj.weight"].T


def swiglu(x, w: Weights, p: str):
    return (jax.nn.silu(x @ w[p + "gate_proj.weight"].T)
            * (x @ w[p + "up_proj.weight"].T)) @ w[p + "down_proj.weight"].T


def block(x, w: Weights, i: int, cfg: Mapping):
    p, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    kind = cfg["layer_types"][i]
    norm = lambda name, t: rms_norm(t, w[p + name + ".weight"], eps)
    if kind == "linear_attention":
        h = x + gated_delta_net(norm("attention_layer_norm", x), w,
                                p + "linear_attn.", cfg)
        return h + swiglu(norm("feedforward_layer_norm", h), w, p + "mlp.")
    if kind == "full_attention":
        h = x + norm("post_attention_layernorm",
                     attention(x, w, p + "self_attn.", cfg))
        return h + norm("post_feedforward_layernorm",
                        swiglu(h, w, p + "mlp."))
    raise ValueError(f"block {i}: layer_types names {kind!r}")


def logits(w: Weights, cfg: Mapping, tokens, *, layers: Optional[int] = None):
    x = w["model.embed_tokens.weight"][tokens]
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        x = block(x, w, i, cfg)
    return rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"]) \
        @ w["lm_head.weight"].T


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    """Sum of token negative log-likelihoods."""
    return token_nll_sum(logits(w, cfg, tokens, layers=layers), labels)


def attention_blocks(config: Mapping) -> List[Dict[str, int]]:
    """One entry for each ``full_attention`` block as run, the model's own
    heads over the whole causal span; a ``linear_attention`` block has
    none."""
    return [{} for kind in config["layer_types"] if kind == "full_attention"]


def gdn_matmul_flops_per_token(config: Mapping) -> float:
    """The seven projection matrices of one Gated DeltaNet block as they
    are: q, k, v, the decay's and ``beta``'s, the output gate's and the
    output's; the depthwise taps and the gates' elementwise work are no
    matmuls."""
    H, n = config["hidden_size"], config["linear_num_value_heads"]
    kd = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    vd = n * config["linear_value_head_dim"]
    return 2 * (H * (2 * kd + vd) + 2 * H * n + H * vd + vd * H)


def recurrence_flops_per_token(config: Mapping) -> float:
    """The recurrence as the recurrence: ``S~^T k``, the rank-one update
    and ``S^T q``, 2 each per state element, ``6 dk dv`` a head; the decay
    of the state is elementwise and not counted."""
    return (6 * config["linear_key_head_dim"]
            * config["linear_value_head_dim"]
            * config["linear_num_value_heads"])


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """Blocks added up by kind: a ``linear_attention`` block's seven
    projections and the recurrence, an attending block's four projections
    and causal core (``flops.attention_flops_per_token`` an entry of
    ``sizes.attention_blocks()``), a SwiGLU of ``intermediate_size`` in
    every block, the head over the slice."""
    linear = sum(kind == "linear_attention"
                 for kind in config["layer_types"])
    attending = sum(flops.attention_flops_per_token(sizes, a)
                    for a in sizes.attention_blocks())
    mlp = 2 * 3 * sizes.hidden * config["intermediate_size"]
    return (linear * (gdn_matmul_flops_per_token(config)
                      + recurrence_flops_per_token(config))
            + attending + len(config["layer_types"]) * mlp
            + flops.head_flops_per_token(sizes))
