"""Reference family ``nemotron_h``: the CAUSAL tower of Nemotron-Labs-
TwoTower-30B-A3B-Base, a NemotronH stack, written from the published
``config.json`` (nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16,
``model_type`` ``nemotron_h``), the Nemotron-H report (arXiv:2504.03624,
section 2.1) and ``transformers``' ``Mamba2Mixer`` / ``Zamba2RMSNormGated``
(the grouped mixer and its gated norm; ``transformers`` 4.57.6 has no
``nemotron_h``); fed ``backbone.*`` tensors under their public names. Every
projection without bias, ``eps = layer_norm_epsilon``:

* model: ``h = E[tokens]``; the blocks; ``RMSNorm(h; norm_f)``; ``logits = h
  W_head^T`` (untied); the loss is token cross-entropy alone;
* every block has ONE branch: ``h <- h + F(RMSNorm(h; norm))``, ``F`` by the
  block's letter of ``hybrid_override_pattern``;
* ``M``, a Mamba-2 mixer: ``[z | xBC | dt] = W_in u``; ``xBC =
  silu(conv1d_causal(xBC) + b)``, depthwise, ``conv_kernel`` taps, zero
  history before the sequence; ``[x | B | C] = xBC``, x as
  ``mamba_num_heads`` heads of ``mamba_head_dim``, B and C as ``n_groups``
  groups of ``ssm_state_size``, HEAD ``j`` READING GROUP ``j // (heads /
  n_groups)``; ``dt = softplus(dt + dt_bias)`` a head (``time_step_limit``
  (0, inf): no clamp), ``A = -exp(A_log)`` a head. Per head, with state ``S``
  [head_dim, state], zero before the sequence::

      S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T ;   y_t = S_t C_t + D x_t

  computed AS THAT RECURRENCE, one position at a time (a ``lax.scan`` over
  positions), so that it shares nothing with the chunked matmul form of the
  program under test. Then ``y = w * RMSNorm_group(y * silu(z))``: the gate
  BEFORE the norm, the mean square taken over each group's ``inner /
  n_groups`` channels; ``F = W_out y``;
* ``*``, attention: q, k, v, o, grouped queries (``head_dim`` its own key:
  32 x 128 over a hidden of 2688), causal softmax at ``head_dim ** -0.5``,
  NO positions (the report's section 2.1; ``rope_theta`` is read by no
  layer);
* ``-``, an MLP: ``W_down relu(W_up u)^2``, two matrices, no gate;
* ``E``, experts: ``s = sigmoid(W_r u)`` in float32 over all routed experts;
  the ``num_experts_per_tok`` chosen are the largest of ``s + b`` (``b`` =
  ``e_score_correction_bias``: it steers the choice, never the weights;
  ``n_group`` = ``topk_group`` = 1); ``g_e = s_e / (sum of the chosen s +
  1e-20) * routed_scaling_factor``; ``F = sum over the chosen of g_e
  W_down,e relu(W_up,e u)^2 + W_down,s relu(W_up,s u)^2``, the shared expert
  ``moe_shared_expert_intermediate_size`` wide. Computed the plain way:
  every HELD expert on every token, times a weight that is zero unless the
  expert is among the token's chosen.

DEPARTURES from the published model, each because the configuration's file
states it and the program under test runs the same:

* the second tower (an adaLN denoiser, block-diffusion decoding) has no key
  in ``config.json`` and is not here: this is next-token cross-entropy of
  the causal stack;
* the share: the weights hold experts ``[first_expert_held,
  first_expert_held + n_routed_experts)`` of the router's
  ``num_routed_experts``, under their published indices. The router scores
  all of them and keeps its ``num_experts_per_tok``; what the absent experts
  would have added is left out, and that partial sum is what goes on to the
  next block. The shared expert is whole;
* the sliced vocabulary: ``vocab_size`` rows of the published 131072;
* depth: the first ``num_hidden_layers`` letters of the pattern;
* the FLOP count takes the recurrence as the recurrence (``4 x head_dim x
  state`` a head and token) and the held experts at their EXPECTED share of
  the routes, ``num_experts_per_tok * held / routed`` a token and block.
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

# the letters of ``hybrid_override_pattern``
MAMBA, ATTENTION, MLP, EXPERTS = "M", "*", "-", "E"
ROUTER_EPS = 1e-20


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def attention(u, w: Weights, p: str, cfg: Mapping):
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = split_heads(u @ w[p + "q_proj.weight"].T, nh)
    k = split_heads(u @ w[p + "k_proj.weight"].T, nkv)
    v = split_heads(u @ w[p + "v_proj.weight"].T, nkv)
    if q.shape[-1] != cfg["head_dim"]:
        raise ValueError(f"q_proj gives heads of {q.shape[-1]}, head_dim is "
                         f"{cfg['head_dim']}")
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    return merge_heads(causal_attention(q, k, v)) @ w[p + "o_proj.weight"].T


def selective_scan(x, dt, A, B, C):
    """The recurrence, one position at a time. ``x`` [batch, S, heads, P],
    ``dt`` [batch, S, heads], ``A`` [heads], ``B`` and ``C`` [batch, S,
    groups, N]; head ``j`` reads group ``j // (heads / groups)`` -> ``S_t
    C_t`` [batch, S, heads, P]. The state is kept a group: [batch, groups,
    heads a group, P, N]."""
    batch, _, heads, P = x.shape
    groups, N = B.shape[-2:]
    per = heads // groups
    x = x.reshape(x.shape[:2] + (groups, per, P))
    dt = dt.reshape(dt.shape[:2] + (groups, per))
    A = A.reshape(groups, per)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, :, None, None, :])
        return state, jnp.einsum("bgjpn,bgn->bgjp", state, c_t)

    zero = jnp.zeros((batch, groups, per, P, N), x.dtype)
    _, y = jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1).reshape(batch, -1, heads, P)


def mamba2(u, w: Weights, p: str, cfg: Mapping):
    """The Mamba-2 mixer of one block; ``u`` [batch, S, hidden]."""
    batch, S, _ = u.shape
    heads, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    N, G, taps = cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"]
    inner = heads * P
    z, xbc, dt = jnp.split(u @ w[p + "in_proj.weight"].T,
                           [inner, 2 * inner + 2 * G * N], axis=-1)
    kernel = w[p + "conv1d.weight"][:, 0, :]          # [channels, taps]
    c = jnp.zeros_like(xbc)
    for j in range(taps):
        back = taps - 1 - j                           # tap j meets u[t - back]
        c = c + kernel[:, j] * jnp.pad(
            xbc, ((0, 0), (back, 0), (0, 0)))[:, :S]
    if cfg["use_conv_bias"]:
        c = c + w[p + "conv1d.bias"]
    x, B, C = jnp.split(jax.nn.silu(c), [inner, inner + G * N], axis=-1)
    x = x.reshape(batch, S, heads, P)
    dt = jax.nn.softplus(dt + w[p + "dt_bias"])
    y = selective_scan(x, dt, -jnp.exp(w[p + "A_log"]),
                       B.reshape(batch, S, G, N), C.reshape(batch, S, G, N))
    y = (y + w[p + "D"][:, None] * x).reshape(batch, S, inner)
    # the gate before the norm; the mean square a group of channels
    y = (y * jax.nn.silu(z)).reshape(batch, S, G, inner // G)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                     + cfg["layer_norm_epsilon"])
    y = y.reshape(batch, S, inner) * w[p + "norm.weight"]
    return y @ w[p + "out_proj.weight"].T


def mlp(u, w: Weights, p: str):
    return relu2(u @ w[p + "up_proj.weight"].T) @ w[p + "down_proj.weight"].T


def held_experts(cfg: Mapping) -> range:
    first = cfg.get("first_expert_held", 0)
    return range(first, first + cfg["n_routed_experts"])


def routed_weights(x, w: Weights, p: str, cfg: Mapping):
    """``x`` [tokens, hidden] -> [tokens, routed experts]: a token's weight
    for each of its chosen experts, zero elsewhere."""
    E = cfg.get("num_routed_experts", cfg["n_routed_experts"])
    s = jax.nn.sigmoid((x @ w[p + "gate.weight"].T).astype(jnp.float32))
    _, top_i = jax.lax.top_k(
        s + w[p + "gate.e_score_correction_bias"].astype(jnp.float32),
        cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + ROUTER_EPS)
    top_s = top_s * cfg["routed_scaling_factor"]
    return jnp.einsum("tk,tke->te", top_s,
                      jax.nn.one_hot(top_i, E, dtype=top_s.dtype))


def routed_experts(x, w: Weights, p: str, cfg: Mapping, experts=None):
    """What the experts ``experts`` (default: the held ones) add to ``x``
    [tokens, hidden]."""
    combine = routed_weights(x, w, p, cfg)
    out = jnp.zeros_like(x)
    for e in held_experts(cfg) if experts is None else experts:
        out = out + combine[:, e:e + 1].astype(x.dtype) * mlp(
            x, w, p + f"experts.{e}.")
    return out


def experts_block(x, w: Weights, p: str, cfg: Mapping):
    """The routed share and, whole, the shared expert."""
    y = routed_experts(x, w, p, cfg)
    if cfg["n_shared_experts"]:
        y = y + mlp(x, w, p + "shared_experts.")
    return y


def hidden_states(w: Weights, cfg: Mapping, tokens, *,
                  layers: Optional[int] = None):
    """The hidden states [batch, S, hidden] after ``norm_f``."""
    eps = cfg["layer_norm_epsilon"]
    h = w["backbone.embeddings.weight"][tokens]
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(f"hybrid_override_pattern {pattern!r} names "
                         f"{len(pattern)} blocks, num_hidden_layers is "
                         f"{cfg['num_hidden_layers']}")
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        p = f"backbone.layers.{i}."
        u = rms_norm(h, w[p + "norm.weight"], eps)
        p += "mixer."
        if pattern[i] == MAMBA:
            h = h + mamba2(u, w, p, cfg)
        elif pattern[i] == ATTENTION:
            h = h + attention(u, w, p, cfg)
        elif pattern[i] == MLP:
            h = h + mlp(u, w, p)
        elif pattern[i] == EXPERTS:
            h = h + experts_block(u.reshape(-1, u.shape[-1]), w, p,
                                  cfg).reshape(u.shape)
        else:
            raise ValueError(f"hybrid_override_pattern[{i}] = {pattern[i]!r}")
    return rms_norm(h, w["backbone.norm_f.weight"], eps)


def logits(w: Weights, cfg: Mapping, tokens, *, layers: Optional[int] = None):
    return hidden_states(w, cfg, tokens, layers=layers) \
        @ w["lm_head.weight"].T


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    """Sum of token negative log-likelihoods (the head is untied)."""
    return token_nll_sum(logits(w, cfg, tokens, layers=layers), labels)


def attention_blocks(config: Mapping) -> List[Dict[str, int]]:
    """One empty entry (the model's own heads and ``head_dim``, the whole
    causal span) for each ``*`` block of the pattern as run; no other block
    attends."""
    return [{} for letter in config["hybrid_override_pattern"]
            if letter == ATTENTION]


def mamba_flops_per_token(config: Mapping) -> float:
    """``in_proj`` (hidden x (2 inner + 2 groups x state + heads)),
    ``out_proj`` (inner x hidden) and the recurrence as the recurrence (the
    state update and the read-out, ``4 x head_dim x state`` a head) of one
    ``M`` block; the depthwise taps are no matmul."""
    H, heads = config["hidden_size"], config["mamba_num_heads"]
    inner = heads * config["mamba_head_dim"]
    wide = (2 * inner + 2 * config["n_groups"] * config["ssm_state_size"]
            + heads)
    return (2 * H * wide + 2 * inner * H
            + 4 * config["mamba_head_dim"] * config["ssm_state_size"] * heads)


def experts_flops_per_token(config: Mapping) -> float:
    """One ``E`` block: the router over all routed experts, the shared
    expert, and the held experts at their expected share of the routes; two
    matrices an expert."""
    H = config["hidden_size"]
    routed = config.get("num_routed_experts", config["n_routed_experts"])
    routes = (config["num_experts_per_tok"] * config["n_routed_experts"]
              / routed)
    shared = (config["n_shared_experts"]
              * config["moe_shared_expert_intermediate_size"])
    return (2 * H * routed + 2 * 2 * H * shared
            + routes * 2 * 2 * H * config["moe_intermediate_size"])


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """Blocks added up by letter: ``*`` through
    ``flops.attention_flops_per_token``, ``M`` and ``E`` as above, ``-`` two
    matrices of ``intermediate_size``; the untied head over the sliced
    vocabulary."""
    pattern = config["hybrid_override_pattern"]
    count = lambda letter: sum(c == letter for c in pattern)  # noqa: E731
    return (sum(flops.attention_flops_per_token(sizes, entry)
                for entry in sizes.attention_blocks())
            + count(MAMBA) * mamba_flops_per_token(config)
            + count(EXPERTS) * experts_flops_per_token(config)
            + count(MLP) * 2 * 2 * sizes.hidden * config["intermediate_size"]
            + flops.head_flops_per_token(sizes))
