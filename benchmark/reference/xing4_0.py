"""Reference family ``xing4_0``: Xing4.0-29B-A4B's block stack, written from
the published ``config.json`` (XingChen-AGI/Xing4.0-29B-A4B, ``model_type``
``xing4_0``) and the papers its keys point to: the attention and the expert
layer are DeepSeek-V2/V3's (arXiv:2405.04434, arXiv:2412.19437; the keys
are ``DeepseekV3Config``'s), the multi-token-prediction block DeepSeek-V3
section 2.2, the residual path manifold-constrained hyper-connections
(arXiv:2512.24880, over arXiv:2409.19606). Fed ``model.*`` tensors under
their public names (DeepSeek-V3's released layout; the residual maps, which
no public checkpoint names, as ``attn_hc.*`` / ``mlp_hc.*``). ``T`` tokens,
``H`` hidden, ``n = hc_mult`` streams; every projection without bias:

* latent attention (``DeepseekV3Attention``): ``c_q = RMSNorm(x W_qa)``;
  ``[q_nope | q_rope] = c_q W_qb`` a head; ``[c_kv | k_rope] = x W_kva``
  (``k_rope`` one for all heads); ``[k_nope | v] = RMSNorm(c_kv) W_kvb`` a
  head; RoPE on ``q_rope`` and ``k_rope`` with YaRN's frequencies, the
  stored columns de-interleaved first (``x.view(.., d/2, 2).transpose``) as
  transformers' ``apply_rotary_pos_emb_interleave`` does; cos and sin times
  ``mscale / mscale_all_dim``; causal ``softmax(q k^T * qk^-0.5 * m^2) v``
  with ``m = 0.1 mscale_all_dim ln(factor) + 1``; ``W_o``;
* feed-forward of the first ``first_k_dense_replace`` blocks: SwiGLU of
  ``intermediate_size``; of every other: ``s = sigmoid(x W_g)`` over all
  ``num_routed_experts``; the ``num_experts_per_tok`` chosen are the largest
  of ``s + b`` (``e_score_correction_bias``; ``n_group = topk_group = 1``:
  no group limit); weights the unbiased ``s`` of the chosen over ``their
  sum + 1e-20`` (``norm_topk_prob``), times ``routed_scaling_factor``;
  ``y = sum_e w_e E_e(x) + S(x)``, every expert and the shared one SwiGLU
  of ``moe_intermediate_size``. The plain way: every HELD expert on every
  token, times a weight that is zero unless it is among the chosen;
* the residual, around each of a block's two sub-layers with maps of its
  own; ``X`` is ``[n, H]`` a token: ``u = RMSNorm(vec(X))`` over ``n H``
  without a learned scale; ``P = sigmoid(a_pre u phi_pre + b_pre)``, ``Q = 2
  sigmoid(a_post u phi_post + b_post)``, ``R = SK(exp(clip(a_res mat(u
  phi_res) + b_res)))``, ``SK`` normalising rows, then columns,
  ``hc_sinkhorn_iters`` times with ``hc_eps`` in each divisor; ``X' = R X +
  Q^T (x) F(P X)``, ``F`` the sub-layer with its input RMSNorm. The
  embedding enters as ``n`` copies; the streams are summed before the final
  norm;
* multi-token prediction, depth 1: with ``h_i`` that sum, ``h'_i = W_eh
  [RMSNorm(Emb(t_(i+1))) ; RMSNorm(h_i)]`` over the ``S - 1`` positions
  that have a ``t_(i+2)``, entered as ``n`` copies into one more expert
  block (index ``num_hidden_layers``), summed, normed (``shared_head.norm``)
  and read by the model's own head;
* THE LOSS: the mean token cross-entropy plus ``mtp_loss_lambda`` times the
  mean of the multi-token block's over its ``S - 1`` positions a sequence.
  ``nll_sum`` returns the first as a sum and adds ``tokens.size`` times the
  second term, so that the harness's division by the token count gives the
  loss (as ``olmoe.py`` adds its router terms).

DEPARTURES from the published model, each because the configuration's file
states it and the program under test runs the same:

* the share: the weights hold experts ``[first_expert_held, +
  n_routed_experts)`` of the router's ``num_routed_experts``, under their
  published indices; what the absent experts would have added is left out,
  the shared expert is whole;
* the sliced vocabulary: ``vocab_size`` rows of the published 131072; ids,
  logits and the loss are over the slice;
* the FLOP count takes the held experts at their EXPECTED share of the
  routes, ``num_experts_per_tok * held / routed`` a token and block, and the
  attention core of the multi-token block as a sixth block's worth of heads
  on the last entry of ``attention_blocks`` (see there).
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


def yarn_mscale(factor: float, m: float = 1.0) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_tables(seq: int, dim: int, theta: float, sc: Mapping):
    """cos and sin [seq, dim] of YaRN (transformers'
    ``_compute_yarn_parameters``): band ``i`` of ``dim / 2`` is divided by
    ``factor`` where it turns fewer than ``beta_slow`` times within the
    original context, kept where it turns more than ``beta_fast`` times, and
    ramped linearly between the two band indices."""
    factor, orig = sc["factor"], sc["original_max_position_embeddings"]

    def band(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(band(sc["beta_fast"])), 0)
    high = min(math.ceil(band(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freq = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    keep = 1.0 - jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv_freq = (1.0 / (factor * freq)) * (1 - keep) + (1.0 / freq) * keep
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    scale = (yarn_mscale(factor, sc["mscale"])
             / yarn_mscale(factor, sc["mscale_all_dim"]))
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rope_interleaved(x, cos, sin):
    """x [B, heads, S, D] with its pairs side by side (2i, 2i + 1), as the
    public projections store them."""
    *lead, d = x.shape
    x = jnp.swapaxes(x.reshape(*lead, d // 2, 2), -1, -2).reshape(*lead, d)
    # the tables are float32 whatever the caller's dtype: taken at x's, so
    # that a reference asked for in bfloat16 stays bfloat16 past this line
    # (float32 tables would promote q, k and every activation after them)
    return x * cos.astype(x.dtype) + rotate_half(x) * sin.astype(x.dtype)


def latent_attention(a, w: Weights, p: str, cfg: Mapping):
    nh, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    sc = cfg["rope_scaling"]
    cq = rms_norm(a @ w[p + "q_a_proj.weight"].T,
                  w[p + "q_a_layernorm.weight"], eps)
    q = split_heads(cq @ w[p + "q_b_proj.weight"].T, nh)    # [B, nh, S, 192]
    ckv, k_rope = jnp.split(a @ w[p + "kv_a_proj_with_mqa.weight"].T,
                            [cfg["kv_lora_rank"]], axis=-1)
    kv = split_heads(rms_norm(ckv, w[p + "kv_a_layernorm.weight"], eps)
                     @ w[p + "kv_b_proj.weight"].T, nh)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    cos, sin = yarn_tables(a.shape[1], dr, cfg["rope_theta"], sc)
    q_rope = rope_interleaved(q[..., dn:], cos, sin)
    k_rope = rope_interleaved(k_rope[:, None], cos, sin)    # one for all
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, q_rope.shape)],
                        axis=-1)
    # causal_attention divides by sqrt(192); the rest of the softmax scale
    # rides on q
    m = yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    return merge_heads(causal_attention(q * (m * m), k, v)) \
        @ w[p + "o_proj.weight"].T


def swiglu(x, w: Weights, p: str):
    return (jax.nn.silu(x @ w[p + "gate_proj.weight"].T)
            * (x @ w[p + "up_proj.weight"].T)) @ w[p + "down_proj.weight"].T


def held_experts(cfg: Mapping) -> range:
    first = cfg.get("first_expert_held", 0)
    return range(first, first + cfg["n_routed_experts"])


def routed_weights(x, w: Weights, p: str, cfg: Mapping):
    """[tokens, num_routed_experts]: a token's weight on each expert, zero
    off its chosen."""
    E, K = cfg["num_routed_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid((x @ w[p + "gate.weight"].T).astype(jnp.float32))
    _, top_i = jax.lax.top_k(s + w[p + "gate.e_score_correction_bias"], K)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + ROUTER_EPS)
    top_s = top_s * cfg["routed_scaling_factor"]
    return jnp.einsum("tk,tke->te", top_s,
                      jax.nn.one_hot(top_i, E, dtype=top_s.dtype))


def experts(x, w: Weights, p: str, cfg: Mapping, held=None, shared=True):
    """``x`` [tokens, hidden] -> what the ``held`` experts (default: this
    share's) and, with ``shared``, the shared expert add."""
    combine = routed_weights(x, w, p, cfg)
    out = swiglu(x, w, p + "shared_experts.") if shared \
        else jnp.zeros_like(x)
    for e in held_experts(cfg) if held is None else held:
        out = out + combine[:, e:e + 1].astype(x.dtype) * swiglu(
            x, w, p + f"experts.{e}.")
    return out


def sinkhorn_knopp(m, iters: int, eps: float):
    """[.., n, n] positive -> (nearly) doubly stochastic: rows, then
    columns, ``iters`` times."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hyper_maps(X, w: Weights, p: str, cfg: Mapping):
    """X [B, S, n, H] -> P [B, S, n], Q [B, S, n], R [B, S, n, n]."""
    B, S, n, H = X.shape
    u = rms_norm(X.reshape(B, S, n * H), 1.0, cfg["rms_norm_eps"])
    t = u @ w[p + "phi.weight"].T
    a, b = w[p + "alpha"], w[p + "bias"]
    pre = jax.nn.sigmoid(a[0] * t[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * t[..., n:2 * n] + b[n:2 * n])
    res = (a[2] * t[..., 2 * n:] + b[2 * n:]).reshape(B, S, n, n)
    res = jnp.exp(jnp.clip(res, cfg["mhc_h_res_clamp_min"],
                           cfg["mhc_h_res_clamp_max"]))
    return pre, post, sinkhorn_knopp(res, cfg["hc_sinkhorn_iters"],
                                     cfg["hc_eps"])


def hyper_sublayer(X, w: Weights, p: str, cfg: Mapping, f):
    """``X' = R X + Q^T (x) f(P X)``."""
    pre, post, res = hyper_maps(X, w, p, cfg)
    y = f(jnp.einsum("bsj,bsjh->bsh", pre, X))
    return (jnp.einsum("bsij,bsjh->bsih", res, X)
            + post[..., None] * y[:, :, None, :])


def block(X, w: Weights, i: int, cfg: Mapping):
    """Block ``i`` over the streams X [B, S, n, H]."""
    p, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    X = hyper_sublayer(
        X, w, p + "attn_hc.", cfg, lambda h: latent_attention(
            rms_norm(h, w[p + "input_layernorm.weight"], eps), w,
            p + "self_attn.", cfg))

    def feed_forward(h):
        m = rms_norm(h, w[p + "post_attention_layernorm.weight"], eps)
        # (the multi-token block, past the stack, is an expert block)
        if i < cfg["first_k_dense_replace"]:
            return swiglu(m, w, p + "mlp.")
        return experts(m.reshape(-1, m.shape[-1]), w, p + "mlp.",
                       cfg).reshape(m.shape)

    return hyper_sublayer(X, w, p + "mlp_hc.", cfg, feed_forward)


def streams(h, cfg: Mapping):
    return jnp.repeat(h[:, :, None, :], cfg["hc_mult"], axis=2)


def stack_output(w: Weights, cfg: Mapping, tokens, *,
                 layers: Optional[int] = None):
    """The streams' sum [B, S, hidden] after the stack, before the final
    norm."""
    X = streams(w["model.embed_tokens.weight"][tokens], cfg)
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        X = block(X, w, i, cfg)
    return jnp.sum(X, axis=2)


def logits(w: Weights, cfg: Mapping, tokens, *, layers: Optional[int] = None):
    return rms_norm(stack_output(w, cfg, tokens, layers=layers),
                    w["model.norm.weight"], cfg["rms_norm_eps"]) \
        @ w["lm_head.weight"].T


def mtp_logits(w: Weights, cfg: Mapping, h, next_tokens):
    """The multi-token block's logits: ``h`` [B, S', H] the stack's output
    at positions that have a token two ahead, ``next_tokens`` [B, S'] the
    token one ahead of each."""
    eps = cfg["rms_norm_eps"]
    L = cfg["num_hidden_layers"]
    p = f"model.layers.{L}."
    e = rms_norm(w["model.embed_tokens.weight"][next_tokens],
                 w[p + "enorm.weight"], eps)
    x = jnp.concatenate([e, rms_norm(h, w[p + "hnorm.weight"], eps)],
                        axis=-1) @ w[p + "eh_proj.weight"].T
    x = jnp.sum(block(streams(x, cfg), w, L, cfg), axis=2)
    return rms_norm(x, w[p + "shared_head.norm.weight"], eps) \
        @ w["lm_head.weight"].T


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    """Summed token NLL plus ``tokens.size`` times ``mtp_loss_lambda`` times
    the multi-token block's mean NLL over the positions of this call (see
    THE LOSS above)."""
    h = stack_output(w, cfg, tokens, layers=layers)
    main = token_nll_sum(
        rms_norm(h, w["model.norm.weight"], cfg["rms_norm_eps"])
        @ w["lm_head.weight"].T, labels)
    if not cfg["num_nextn_predict_layers"]:
        return main
    # position i: the token one ahead is labels[i], two ahead labels[i + 1]
    ahead = token_nll_sum(mtp_logits(w, cfg, h[:, :-1], labels[:, :-1]),
                          labels[:, 1:])
    return main + tokens.size * cfg["mtp_loss_lambda"] * ahead / (
        labels[:, 1:].size)


def attention_blocks(config: Mapping) -> List[Dict[str, int]]:
    """One entry a block of the stack, q/k 192 wide and v 128. The
    multi-token block attends too but is no block of the stack, and
    ``flops.Sizes.with_attention`` takes at most as many entries as the
    program's ``num_hidden_layers``: its core is counted on the LAST entry
    as a second block's worth of heads (``heads`` and ``kv_heads`` doubled),
    which every count that reads an entry (``flops.flash_step_cost``, the
    core below) takes linearly."""
    nh = config["num_attention_heads"]
    entry = {"qk_head_dim": config["qk_nope_head_dim"]
             + config["qk_rope_head_dim"],
             "v_head_dim": config["v_head_dim"]}
    out = [dict(entry) for _ in range(config["num_hidden_layers"])]
    if config["num_nextn_predict_layers"]:
        out[-1].update(heads=2 * nh, kv_heads=2 * nh)
    return out


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """Blocks added up. A block's attention: the five latent projections AS
    THEY ARE (``q_a``, ``q_b``, ``kv_a``, ``kv_b``, ``o``; not full-rank q,
    k and v) and the causal core ``2 heads (192 + 128) pairs / seq`` an
    entry of ``sizes.attention_blocks()``; its two residual maps' ``phi``
    products (``n H x (2 n + n n)`` each; the mixes are no matmuls); a dense
    block's SwiGLU; an expert block's router over all routed experts, the
    held experts at ``num_experts_per_tok * held / routed`` routes a token
    and the shared expert; the head. The multi-token block: ``eh_proj``, one
    more expert block (its core is on the last attention entry) and the head
    again, at the ``(seq - 1) / seq`` positions a token that have a target."""
    H, nh = sizes.hidden, config["num_attention_heads"]
    n = config["hc_mult"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    proj = 2 * (H * rq + rq * nh * (dn + dr) + H * (rkv + dr)
                + rkv * nh * (dn + dv) + nh * dv * H)
    cores = sum(2 * (a.heads or nh) * (a.qk_head_dim + a.v_head_dim)
                * flops.causal_pairs(sizes.seq, a.window) / sizes.seq
                for a in sizes.attention_blocks())
    maps = 2 * 2 * (n * H) * (2 * n + n * n)
    dense = 2 * 3 * H * config["intermediate_size"]
    routes = (config["num_experts_per_tok"] * config["n_routed_experts"]
              / config["num_routed_experts"])
    expert = 2 * 3 * H * config["moe_intermediate_size"]
    sparse = (2 * H * config["num_routed_experts"] + routes * expert
              + config["n_shared_experts"] * expert)
    L, n_dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    head = flops.head_flops_per_token(sizes)
    total = (L * (proj + maps) + cores + n_dense * dense
             + (L - n_dense) * sparse + head)
    if config["num_nextn_predict_layers"]:
        total += (sizes.seq - 1) / sizes.seq * (
            2 * (2 * H) * H + proj + maps + sparse + head)
    return total
