"""Reference family ``kimi_linear``: Kimi-Linear-48B-A3B's block stack,
written from the published ``config.json`` (moonshotai/
Kimi-Linear-48B-A3B-Instruct, ``model_type`` ``kimi_linear``), the Kimi
Linear report (arXiv:2510.26692) and the released ``modeling_kimi.py``; the
latent attention is DeepSeek-V2's (arXiv:2405.04434), the expert layer
DeepSeek-V3's (arXiv:2412.19437). Fed ``model.*`` tensors under their public
names. ``H`` hidden, ``n`` heads of ``d`` (``linear_attn_config``); every
projection without bias; blocks are numbered from 1 as
``linear_attn_config`` numbers them (block ``i`` is ``model.layers.{i-1}``):

* block: ``x = x + mixer(RMSNorm(x))``, ``x = x + ffn(RMSNorm(x))``; a final
  RMSNorm; an untied head;
* mixer of a block in ``kda_layers``, Kimi Delta Attention: ``q~ =
  silu(conv(x W_q))``, ``k~ = silu(conv(x W_k))``, ``v = silu(conv(x
  W_v))``, each convolution causal and depthwise, ``short_conv_kernel_size``
  taps, zeros before the sequence; a head's ``q = q~ / sqrt(|q~|^2 + 1e-6) *
  d^-0.5``, ``k = k~ / sqrt(|k~|^2 + 1e-6)``; the log decay a channel ``g_t
  = -exp(A_log) softplus(x W_fa W_fb + dt_bias)``; ``beta_t = sigmoid(x
  W_b)`` a head. Per head, with the state ``S`` [d, d] (keys x values),
  zero before the sequence::

      S~  = Diag(exp(g_t)) S_(t-1)
      S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T ;   o_t = S_t^T q_t

  computed AS THAT RECURRENCE, one position at a time (a ``lax.scan`` over
  positions), so that it shares nothing with the chunked form of the
  program under test. Then ``y = RMSNorm_d(o; o_norm) * sigmoid(x W_ga
  W_gb)`` a head and ``y W_o``;
* mixer of a block in ``full_attn_layers``, latent attention without
  positions (``mla_use_nope``) and without a low-rank query (``q_lora_rank``
  null): ``q = x W_q`` a head (``qk_nope_head_dim + qk_rope_head_dim``);
  ``[c_kv | k_pe] = x W_kva`` (``k_pe`` one for all heads); ``[k_nope | v] =
  RMSNorm(c_kv) W_kvb`` a head; ``k = [k_nope | k_pe]``; causal ``softmax(q
  k^T * 192^-0.5) v``; ``W_o``. ``rope_theta`` is in the file and unused;
* feed-forward of the first ``first_k_dense_replace`` blocks: SwiGLU of
  ``intermediate_size``; of every other: ``s = sigmoid(x W_g)`` over all
  ``num_routed_experts``; the ``num_experts_per_token`` chosen are the
  largest of ``s + b`` (``e_score_correction_bias``; ``num_expert_group =
  topk_group = 1``: no group limit); weights the unbiased ``s`` of the
  chosen over ``their sum + 1e-20`` (``moe_renormalize``), times
  ``routed_scaling_factor``; ``y = sum_e w_e E_e(x) + S(x)``, every expert
  and the shared one SwiGLU of ``moe_intermediate_size``. The plain way:
  every HELD expert on every token, times a weight that is zero unless it
  is among the chosen;
* the loss is token cross-entropy alone.

DEPARTURES from the published model, each because the configuration's file
states it and the program under test runs the same:

* the share: the weights hold experts ``[first_expert_held, + num_experts)``
  of the router's ``num_routed_experts``, under their published indices;
  what the absent experts would have added is left out, the shared expert is
  whole;
* the sliced vocabulary: ``vocab_size`` rows of the published 163840; ids,
  logits and the loss are over the slice;
* depth: the first ``num_hidden_layers`` published blocks;
* the FLOP count takes the recurrence as the recurrence (``S~^T k``, the
  rank-one update and ``S^T q``, ``6 d d`` a head and token), not as the
  chunked form an implementation may choose, so that ``mfu_pct`` does not
  move with the chunk; the held experts at their EXPECTED share of the
  routes, ``num_experts_per_token * held / routed`` a token and block.
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

ROUTER_EPS = 1e-20
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


def delta_rule(q, k, v, g, beta):
    """The recurrence, one position at a time. ``q``, ``k``, ``v``, ``g``
    [B, S, n, d], ``beta`` [B, S, n] -> ``o`` [B, S, n, d]."""
    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None] * state            # S~ [B, n, dk, dv]
        err = v_t - jnp.einsum("bnkv,bnk->bnv", state, k_t)
        state = state + (b_t[..., None] * k_t)[..., None] * err[..., None, :]
        return state, jnp.einsum("bnkv,bnk->bnv", state, q_t)

    zero = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], q.dtype)
    _, o = jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + L2_EPS)


def delta_attention(a, w: Weights, p: str, cfg: Mapping):
    """Kimi Delta Attention of one block; ``a`` [B, S, H]."""
    lin = cfg["linear_attn_config"]
    n, d = lin["num_heads"], lin["head_dim"]
    if w[p + "q_conv1d.weight"].shape[-1] != lin["short_conv_kernel_size"]:
        raise ValueError("the convolutions' taps are not "
                         "short_conv_kernel_size")
    B, S, _ = a.shape
    heads = lambda t: t.reshape(B, S, n, d)
    q, k, v = (heads(jax.nn.silu(causal_conv(
        a @ w[p + f"{m}_proj.weight"].T, w[p + f"{m}_conv1d.weight"])))
        for m in "qkv")
    q, k = unit(q) * d ** -0.5, unit(k)
    decay = jax.nn.softplus(
        (a @ w[p + "f_a_proj.weight"].T) @ w[p + "f_b_proj.weight"].T
        + w[p + "dt_bias"])
    g = -jnp.exp(w[p + "A_log"].reshape(n, 1)) * heads(decay)
    beta = jax.nn.sigmoid(a @ w[p + "b_proj.weight"].T)
    o = delta_rule(q, k, v, g, beta)
    z = heads((a @ w[p + "g_a_proj.weight"].T) @ w[p + "g_b_proj.weight"].T)
    y = rms_norm(o, w[p + "o_norm.weight"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(z)
    return y.reshape(B, S, n * d) @ w[p + "o_proj.weight"].T


def latent_attention(a, w: Weights, p: str, cfg: Mapping):
    if cfg["q_lora_rank"] is not None or not cfg["mla_use_nope"]:
        raise ValueError("written for q_lora_rank null and mla_use_nope")
    nh, dn = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    q = split_heads(a @ w[p + "q_proj.weight"].T, nh)       # [B, nh, S, 192]
    ckv, k_pe = jnp.split(a @ w[p + "kv_a_proj_with_mqa.weight"].T,
                          [cfg["kv_lora_rank"]], axis=-1)
    kv = split_heads(rms_norm(ckv, w[p + "kv_a_layernorm.weight"],
                              cfg["rms_norm_eps"])
                     @ w[p + "kv_b_proj.weight"].T, nh)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], k_nope.shape[:-1]
                                  + k_pe.shape[-1:])], axis=-1)
    # causal_attention divides by sqrt(192), the model's own scale
    return merge_heads(causal_attention(q, k, v)) @ w[p + "o_proj.weight"].T


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def held_experts(cfg: Mapping) -> range:
    first = cfg.get("first_expert_held", 0)
    return range(first, first + cfg["num_experts"])


def routed_weights(x, w: Weights, p: str, cfg: Mapping):
    """[tokens, num_routed_experts]: a token's weight on each expert, zero
    off its chosen."""
    E, K = cfg["num_routed_experts"], cfg["num_experts_per_token"]
    s = jax.nn.sigmoid((x @ w[p + "gate.weight"].T).astype(jnp.float32))
    _, top_i = jax.lax.top_k(s + w[p + "gate.e_score_correction_bias"], K)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if cfg["moe_renormalize"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + ROUTER_EPS)
    top_s = top_s * cfg["routed_scaling_factor"]
    return jnp.einsum("tk,tke->te", top_s,
                      jax.nn.one_hot(top_i, E, dtype=top_s.dtype))


def experts(x, w: Weights, p: str, cfg: Mapping, held=None, shared=True):
    """``x`` [tokens, hidden] -> what the ``held`` experts (default: this
    share's) and, with ``shared``, the shared expert add."""
    combine = routed_weights(x, w, p, cfg)
    s = p + "shared_experts."
    out = swiglu(x, w[s + "gate_proj.weight"], w[s + "up_proj.weight"],
                 w[s + "down_proj.weight"]) if shared else jnp.zeros_like(x)
    for e in held_experts(cfg) if held is None else held:
        at = p + f"experts.{e}."
        out = out + combine[:, e:e + 1].astype(x.dtype) * swiglu(
            x, w[at + "w1.weight"], w[at + "w3.weight"], w[at + "w2.weight"])
    return out


def block(x, w: Weights, i: int, cfg: Mapping):
    """Published block ``i + 1`` (``model.layers.{i}``)."""
    p, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    lin = cfg["linear_attn_config"]
    a = rms_norm(x, w[p + "input_layernorm.weight"], eps)
    if i + 1 in lin["kda_layers"]:
        x = x + delta_attention(a, w, p + "self_attn.", cfg)
    elif i + 1 in lin["full_attn_layers"]:
        x = x + latent_attention(a, w, p + "self_attn.", cfg)
    else:
        raise ValueError(f"block {i + 1} is in neither kda_layers nor "
                         "full_attn_layers")
    m = rms_norm(x, w[p + "post_attention_layernorm.weight"], eps)
    if i < cfg["first_k_dense_replace"]:
        return x + swiglu(m, w[p + "mlp.gate_proj.weight"],
                          w[p + "mlp.up_proj.weight"],
                          w[p + "mlp.down_proj.weight"])
    return x + experts(m.reshape(-1, m.shape[-1]), w,
                       p + "block_sparse_moe.", cfg).reshape(m.shape)


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
    """One entry for each block of ``full_attn_layers`` as run, q/k ``
    qk_nope_head_dim + qk_rope_head_dim`` wide and v ``v_head_dim``; a
    block of ``kda_layers`` has none."""
    entry = {"qk_head_dim": config["qk_nope_head_dim"]
             + config["qk_rope_head_dim"],
             "v_head_dim": config["v_head_dim"]}
    return [dict(entry)
            for _ in config["linear_attn_config"]["full_attn_layers"]]


def kda_matmul_flops_per_token(config: Mapping) -> float:
    """The nine projection matrices of one KDA block as they are: q, k, v,
    the decay's and the output gate's low-rank pairs, ``beta``'s and the
    output's; the depthwise taps and the gates' elementwise work are no
    matmuls."""
    H, lin = config["hidden_size"], config["linear_attn_config"]
    d = lin["head_dim"]
    inner = lin["num_heads"] * d
    return 2 * (3 * H * inner + 2 * (H * d + d * inner)
                + H * lin["num_heads"] + inner * H)


def recurrence_flops_per_token(config: Mapping) -> float:
    """The recurrence as the recurrence: ``S~^T k``, the rank-one update
    and ``S^T q``, 2 each per state element, ``6 d d`` a head; the decay of
    the state is elementwise and not counted."""
    lin = config["linear_attn_config"]
    return 6 * lin["head_dim"] * lin["head_dim"] * lin["num_heads"]


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """Blocks added up by kind. A KDA block: its nine projections and the
    recurrence. A latent block: its four projections AS THEY ARE (``W_q``,
    ``W_kva``, ``W_kvb``, ``W_o``; not full-rank k and v) and the causal
    core ``2 heads (192 + 128) pairs / seq`` an entry of
    ``sizes.attention_blocks()``. A dense block's SwiGLU; an expert block's
    router over all routed experts, the held experts at
    ``num_experts_per_token * held / routed`` routes a token and the shared
    expert; the head over the slice."""
    H, nh = sizes.hidden, config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    rkv = config["kv_lora_rank"]
    lin = config["linear_attn_config"]
    latent_proj = 2 * (H * nh * (dn + dr) + H * (rkv + dr)
                       + rkv * nh * (dn + dv) + nh * dv * H)
    cores = sum(2 * (a.heads or nh) * (a.qk_head_dim + a.v_head_dim)
                * flops.causal_pairs(sizes.seq, a.window) / sizes.seq
                for a in sizes.attention_blocks())
    dense = 2 * 3 * H * config["intermediate_size"]
    routes = (config["num_experts_per_token"] * config["num_experts"]
              / config["num_routed_experts"])
    expert = 2 * 3 * H * config["moe_intermediate_size"]
    sparse = (2 * H * config["num_routed_experts"] + routes * expert
              + config["num_shared_experts"] * expert)
    L, n_dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    return (len(lin["kda_layers"]) * (kda_matmul_flops_per_token(config)
                                      + recurrence_flops_per_token(config))
            + len(lin["full_attn_layers"]) * latent_proj + cores
            + n_dense * dense + (L - n_dense) * sparse
            + flops.head_flops_per_token(sizes))
