"""Reference family ``granite_hybrid``: Granite-4.0-H's block stack, written
from the published ``config.json`` (ibm-granite/granite-4.0-h-micro,
``model_type`` ``granitemoehybrid``) and ``transformers``'
``GraniteMoeHybrid*`` classes, whose Mamba layer is Bamba's Mamba-2 mixer
(read against its ``torch_forward``, the path without ``mamba_ssm``); fed
``model.*`` tensors under their public Hugging Face names. With
``num_local_experts`` 0 the feed-forward is the shared MLP alone. Every
projection without bias:

* model: ``h = embedding_multiplier * E[tokens]``; the blocks;
  ``RMSNorm(h)``; ``logits = (h E^T) / logits_scaling``; the loss is token
  cross-entropy alone;
* block, both kinds: ``h <- h + residual_multiplier * Op(RMSNorm(h;
  input_layernorm))``, then ``h <- h + residual_multiplier * W_out(silu(g)
  * u)`` with ``[g | u] = W_in RMSNorm(h; post_attention_layernorm)``
  (``shared_mlp.input_linear`` holds gate and up in one matrix);
* ``Op`` of an ``attention`` block: q, k, v, o, grouped queries, causal,
  ``softmax(attention_multiplier * q k^T)`` and NOT ``1 / sqrt(head_dim)``;
  ``position_embedding_type`` ``nope``: no rotation and no table, ``rope``:
  rotate-half on q and k;
* ``Op`` of a ``mamba`` block: ``[z | xBC | dt] = W_in a``; ``xBC =
  silu(conv1d_causal(xBC) + b)``, depthwise, ``mamba_d_conv`` taps, zero
  history before the sequence; ``[x | B | C] = xBC``, x as
  ``mamba_n_heads`` heads of ``mamba_d_head``, B and C of ``mamba_d_state``
  shared by all heads (``mamba_n_groups`` 1); ``dt = softplus(dt +
  dt_bias)`` a head, ``A = -exp(A_log)`` a head. Per head, with state ``S``
  [d_head, d_state], zero before the sequence::

      S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T ;   y_t = S_t C_t + D x_t

  computed AS THAT RECURRENCE, one position at a time (a ``lax.scan`` over
  positions), so that it shares nothing with the chunked matmul form of the
  program under test. Then ``y = RMSNorm(y * silu(z)) * w`` (the gate
  BEFORE the norm, one group over all channels, ``eps = rms_norm_eps``) and
  ``Op = W_out y``.

DEPARTURES from the published model, each because the configuration's file
states it and the program under test runs the same:

* the sliced vocabulary: ``vocab_size`` rows of the published 100352; ids,
  logits and the loss are over the slice;
* depth: the first ``num_hidden_layers`` published blocks;
* ``time_step_limit`` is HF's default (0, inf), which clamps nothing: no
  clamp is written;
* the attention scale is applied by multiplying q by ``attention_multiplier
  * sqrt(head_dim)`` before ``plain.causal_attention``, which divides the
  scores by ``sqrt(head_dim)``: the same product, and ``plain.py`` keeps
  its one signature;
* the FLOP count takes the recurrence as the recurrence (state update and
  read-out, ``4 x d_head x d_state`` a head and token), not as the chunked
  form an implementation may choose, so that ``mfu_pct`` does not move with
  the chunk.
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
    rope,
    split_heads,
    token_nll_sum,
)

# the published words of ``layer_types``
MAMBA, ATTENTION = "mamba", "attention"


def attention(a, w: Weights, p: str, cfg: Mapping):
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = split_heads(a @ w[p + "q_proj.weight"].T, nh)
    k = split_heads(a @ w[p + "k_proj.weight"].T, nkv)
    v = split_heads(a @ w[p + "v_proj.weight"].T, nkv)
    if cfg["position_embedding_type"] == "rope":
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    elif cfg["position_embedding_type"] != "nope":
        raise ValueError("position_embedding_type "
                         f"{cfg['position_embedding_type']!r}")
    # softmax(attention_multiplier * q k^T): causal_attention divides by
    # sqrt(head_dim)
    q = q * (cfg["attention_multiplier"] * math.sqrt(q.shape[-1]))
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    return merge_heads(causal_attention(q, k, v)) @ w[p + "o_proj.weight"].T


def selective_scan(x, dt, A, B, C):
    """The recurrence, one position at a time. ``x`` [batch, S, heads, P],
    ``dt`` [batch, S, heads], ``A`` [heads], ``B`` and ``C`` [batch, S, N]
    -> ``S_t C_t`` [batch, S, heads, P]."""
    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, None, None, :])
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    zero = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], x.dtype)
    _, y = jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def mamba2(a, w: Weights, p: str, cfg: Mapping):
    """The Mamba-2 mixer of one block; ``a`` [batch, S, hidden]."""
    batch, S, _ = a.shape
    heads, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    N, taps = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("written for mamba_n_groups 1 (B and C shared by "
                         "all heads)")
    inner = heads * P
    z, xbc, dt = jnp.split(a @ w[p + "in_proj.weight"].T,
                           [inner, 2 * inner + 2 * N], axis=-1)
    kernel = w[p + "conv1d.weight"][:, 0, :]          # [channels, taps]
    c = jnp.zeros_like(xbc)
    for j in range(taps):
        back = taps - 1 - j                           # tap j meets u[t - back]
        c = c + kernel[:, j] * jnp.pad(
            xbc, ((0, 0), (back, 0), (0, 0)))[:, :S]
    if cfg["mamba_conv_bias"]:
        c = c + w[p + "conv1d.bias"]
    x, B, C = jnp.split(jax.nn.silu(c), [inner, inner + N], axis=-1)
    x = x.reshape(batch, S, heads, P)
    dt = jax.nn.softplus(dt + w[p + "dt_bias"])
    y = selective_scan(x, dt, -jnp.exp(w[p + "A_log"]), B, C)
    y = y + w[p + "D"][:, None] * x
    y = rms_norm(y.reshape(batch, S, inner) * jax.nn.silu(z),
                 w[p + "norm.weight"], cfg["rms_norm_eps"])
    return y @ w[p + "out_proj.weight"].T


def shared_mlp(m, w: Weights, p: str):
    g, u = jnp.split(m @ w[p + "input_linear.weight"].T, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w[p + "output_linear.weight"].T


def hidden_states(w: Weights, cfg: Mapping, tokens, *,
                  layers: Optional[int] = None):
    """The hidden states [batch, S, hidden] after the final ``RMSNorm``."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    if cfg["num_local_experts"]:
        raise ValueError("written for num_local_experts 0 (the shared MLP "
                         "alone)")
    h = cfg["embedding_multiplier"] * w["model.embed_tokens.weight"][tokens]
    kinds = cfg["layer_types"]
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        p = f"model.layers.{i}."
        a = rms_norm(h, w[p + "input_layernorm.weight"], eps)
        if kinds[i] == MAMBA:
            h = h + res * mamba2(a, w, p + "mamba.", cfg)
        elif kinds[i] == ATTENTION:
            h = h + res * attention(a, w, p + "self_attn.", cfg)
        else:
            raise ValueError(f"layer_types[{i}] = {kinds[i]!r}")
        m = rms_norm(h, w[p + "post_attention_layernorm.weight"], eps)
        h = h + res * shared_mlp(m, w, p + "shared_mlp.")
    return rms_norm(h, w["model.norm.weight"], eps)


def logits(w: Weights, cfg: Mapping, tokens, *, layers: Optional[int] = None):
    return (hidden_states(w, cfg, tokens, layers=layers)
            @ w["model.embed_tokens.weight"].T) / cfg["logits_scaling"]


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    """Sum of token negative log-likelihoods (the embedding is tied)."""
    return token_nll_sum(logits(w, cfg, tokens, layers=layers), labels)


def attention_blocks(config: Mapping) -> List[Dict[str, int]]:
    """One empty entry (the model's own heads, the whole causal span) for
    each ``attention`` block of ``layer_types`` as run; a ``mamba`` block
    has none."""
    return [{} for kind in config["layer_types"] if kind == ATTENTION]


def mamba_matmul_flops_per_token(config: Mapping) -> float:
    """``in_proj`` (hidden x (2 inner + 2 groups x state + heads)) and
    ``out_proj`` (inner x hidden) of one mamba block; the depthwise taps
    are no matmul."""
    H = config["hidden_size"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    wide = (2 * inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
            + config["mamba_n_heads"])
    return 2 * H * wide + 2 * inner * H


def recurrence_flops_per_token(config: Mapping) -> float:
    """The recurrence as the recurrence: the state update ``dt x B^T`` and
    the read-out ``S C``, 2 each per state element, ``4 x d_head x d_state``
    a head; the decay of the state is elementwise and not counted."""
    return (4 * config["mamba_d_head"] * config["mamba_d_state"]
            * config["mamba_n_heads"])


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """Blocks added up by kind. A block that attends: q, k, v, out and the
    causal attention (``flops.attention_flops_per_token``); a mamba block:
    its two projections and the recurrence; every block's shared gated MLP
    of ``shared_intermediate_size``; the tied head over the sliced
    vocabulary."""
    n = len(config["layer_types"])
    n_mamba = sum(kind == MAMBA for kind in config["layer_types"])
    mlp = 2 * 3 * sizes.hidden * config["shared_intermediate_size"]
    return (sum(flops.attention_flops_per_token(sizes, entry)
                for entry in sizes.attention_blocks())
            + n_mamba * (mamba_matmul_flops_per_token(config)
                         + recurrence_flops_per_token(config))
            + n * mlp + flops.head_flops_per_token(sizes))
