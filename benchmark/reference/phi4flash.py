"""Reference family ``phi4flash``: Phi-4-mini-flash-reasoning's block stack
(``model_type`` ``phi4flash``), written from the published ``config.json``
(microsoft/Phi-4-mini-flash-reasoning) and the papers it is built from:
arXiv:2507.06607 (SambaY: the decoder-hybrid-decoder stack and the gated
memory unit), arXiv:2312.00752 (Mamba-1), arXiv:2406.07522 (Samba),
arXiv:2405.05254 (YOCO: cross-attention over one block's keys and values)
and arXiv:2410.05258 (differential attention); fed ``model.*`` tensors under
the names of ``modeling_phi4flash.py``. ``config.json`` gives the sizes and
nothing on the state-space block, on which block is of which kind or on the
attention's form: each such item is listed under ``assumed`` in the
configuration's file, with its source, and this file follows them.

* model: ``h = E[tokens]``; the blocks; ``LayerNorm(h)``; ``logits = h
  E^T`` (tied, no bias, no multiplier); no position encoding anywhere;
* block, every kind: ``h <- h + Op(LN(h; input_layernorm))``, then ``h <-
  h + W_down(silu(g) * u)`` with ``[g | u] = W_gate_up LN(h;
  post_attention_layernorm)``; ``LN`` a LayerNorm with weight and bias;
* ``Op`` of a ``mamba1`` block: ``[u | z] = W_in a``; ``u =
  silu(conv1d_causal(u) + b)``, depthwise, zero history before the
  sequence; ``[d | B | C] = W_x u``; ``dt = softplus(W_dt d + b_dt)``; ``A =
  -exp(A_log)`` [channels, state]; a channel ``c`` carries ``state`` values::

      s_t[c] = exp(dt_t[c] A[c]) * s_(t-1)[c] + dt_t[c] u_t[c] B_t
      y_t[c] = s_t[c] . C_t + D[c] u_t[c]

  computed AS THAT RECURRENCE, one position at a time (a ``lax.scan`` over
  positions, no chunks), so that it shares nothing with the chunked form of
  the program under test; ``Op = W_out (y * silu(z))``. The block's ``y``
  (after the ``D`` skip, before the gate) is the memory ``M`` where a later
  block reads it;
* ``Op`` of a ``gmu`` block (a gated memory unit): ``W_out (M * silu(W_in
  a))``, ``M`` the memory of the last ``mamba1`` block before the first
  ``gmu`` block; no norm on ``M`` or on the product;
* ``Op`` of a block that attends, differential: ``[q | k | v] = W_qkv a +
  b``; query heads ``2j`` and ``2j + 1`` are pair ``j``'s two maps, key and
  value heads ``2g`` and ``2g + 1`` pair ``g = j // (query pairs a
  key-value pair)``; ``P_c = softmax(mask(q_c k_c^T / sqrt(head_dim)))``,
  ``V = [v_1 | v_2]``; ``a_j = P_1 V - lambda P_2 V`` with ``lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 i)`` at block index ``i`` AS RUN; ``(1 - lambda_init)
  RMSNorm(a_j) w`` over the pair's ``2 head_dim`` values; the pairs side by
  side through ``out_proj`` (with bias). The two maps are computed
  SEPARATELY with ``plain.causal_attention`` and subtracted; the program
  makes one core call over all score heads. ``sliding_attention``: a query
  meets the ``sliding_window`` newest keys, its own included;
  ``full_attention``: the whole causal span; ``cross_attention``: ``W_qkv``
  holds the queries alone, and k and v are those of the last
  ``full_attention`` block before the first ``cross_attention`` block, as
  that block made them.

DEPARTURES from the published model, each because the configuration's file
states it and the program under test runs the same:

* the sliced vocabulary: ``vocab_size`` rows of the published 200,064; ids,
  logits and the loss are over the slice, and over the padding rows the
  program adds to reach a multiple of 128 (``extra_vocab_rows``: 25,008 to
  25,088; random tokens are drawn over all of them);
* depth: ``layer_types`` names the blocks as run (published blocks 14 to
  19), and ``lambda_init`` is taken at the index as run;
* ``layers=`` drops the LAST blocks (``reference_variants.py``'s one block
  fewer is the stack without its cross-attention block);
* the FLOP count takes the matmuls alone: the recurrence (``2 x channels x
  state`` multiply-adds a position twice, on the vector unit) counts
  nothing, so ``mfu_pct`` does not move with how the scan is run.
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
    layer_norm,
    merge_heads,
    rms_norm,
    split_heads,
    token_nll_sum,
)

MAMBA, WINDOW, FULL, GMU, CROSS = (
    "mamba1", "sliding_attention", "full_attention", "gmu", "cross_attention")
ATTENDS = (WINDOW, FULL, CROSS)


def d_inner(cfg: Mapping) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def selective_scan(u, dt, A, B, C):
    """The recurrence, one position at a time. ``u``, ``dt`` [batch, S,
    channels], ``A`` [channels, state], ``B``, ``C`` [batch, S, state] ->
    ``s_t . C_t`` [batch, S, channels]."""
    def step(state, at):
        u_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t[..., None] * A) * state
                 + (dt_t * u_t)[..., None] * b_t[:, None, :])
        return state, jnp.einsum("bcn,bn->bc", state, c_t)

    zero = jnp.zeros(u.shape[:1] + A.shape, u.dtype)
    _, y = jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (u, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def mamba1(a, w: Weights, p: str, cfg: Mapping):
    """(the mixer's output, its scan output ``y``); ``a`` [batch, S,
    hidden]."""
    S, N, taps = a.shape[1], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    R = cfg["mamba_dt_rank"]
    u, z = jnp.split(a @ w[p + "in_proj.weight"].T, 2, axis=-1)
    kernel = w[p + "conv1d.weight"][:, 0, :]          # [channels, taps]
    c = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j                           # tap j meets u[t - back]
        c = c + kernel[:, j] * jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :S]
    u = jax.nn.silu(c + w[p + "conv1d.bias"])
    d, B, C = jnp.split(u @ w[p + "x_proj.weight"].T, [R, R + N], axis=-1)
    dt = jax.nn.softplus(d @ w[p + "dt_proj.weight"].T
                         + w[p + "dt_proj.bias"])
    y = selective_scan(u, dt, -jnp.exp(w[p + "A_log"]), B, C) + w[p + "D"] * u
    return (y * jax.nn.silu(z)) @ w[p + "out_proj.weight"].T, y


def gmu(a, memory, w: Weights, p: str):
    return ((memory * jax.nn.silu(a @ w[p + "in_proj.weight"].T))
            @ w[p + "out_proj.weight"].T)


def differential(q, k, v, w: Weights, p: str, cfg: Mapping, i: int,
                 window: Optional[int]):
    """``q`` [batch, S, heads x D], ``k``, ``v`` [batch, S, kv heads x D]
    -> the block's output before ``out_proj`` [batch, S, heads x D]."""
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q, k, v = split_heads(q, nh), split_heads(k, nkv), split_heads(v, nkv)
    D = q.shape[-1]
    rep = (nh // 2) // (nkv // 2)     # query pairs a key-value pair
    # the pair's value, two heads wide
    V = jnp.repeat(jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1),
                   rep, axis=1)
    maps = [causal_attention(q[:, c::2], jnp.repeat(k[:, c::2], rep, axis=1),
                             V, window=window) for c in (0, 1)]
    lam0 = lambda_init(i)
    lam = (jnp.exp(jnp.sum(w[p + "lambda_q1"] * w[p + "lambda_k1"]))
           - jnp.exp(jnp.sum(w[p + "lambda_q2"] * w[p + "lambda_k2"])) + lam0)
    a = rms_norm(maps[0] - lam * maps[1], w[p + "subln.weight"],
                 cfg["layer_norm_eps"]) * (1.0 - lam0)
    assert a.shape[-1] == 2 * D
    return merge_heads(a)


def attention(a, w: Weights, p: str, cfg: Mapping, i: int, kind: str,
              kv=None):
    """(the mixer's output, its (k, v)); a ``cross_attention`` block reads
    ``kv``, every other makes its own."""
    D = cfg["hidden_size"] // cfg["num_attention_heads"]
    nq, nk = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    qkv = a @ w[p + "Wqkv.weight"].T + w[p + "Wqkv.bias"]
    if kind == CROSS:
        q, (k, v) = qkv, kv
    else:
        q, k, v = jnp.split(qkv, [nq, nq + nk], axis=-1)
    out = differential(q, k, v, w, p, cfg, i,
                       cfg["sliding_window"] if kind == WINDOW else None)
    return (out @ w[p + "out_proj.weight"].T + w[p + "out_proj.bias"],
            (k, v))


def mlp(m, w: Weights, p: str):
    g, u = jnp.split(m @ w[p + "gate_up_proj.weight"].T, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w[p + "down_proj.weight"].T


def makers(kinds) -> Dict[str, int]:
    """The block whose memory the ``gmu`` blocks read and the block whose
    keys and values the ``cross_attention`` blocks read: the last of the
    making kind before the first reader."""
    found = {}
    for reader, maker in ((GMU, MAMBA), (CROSS, FULL)):
        if reader in kinds:
            before = [i for i in range(kinds.index(reader))
                      if kinds[i] == maker]
            found[reader] = before[-1]
    return found


def hidden_states(w: Weights, cfg: Mapping, tokens, *,
                  layers: Optional[int] = None):
    """The hidden states [batch, S, hidden] after the final LayerNorm."""
    eps, kinds = cfg["layer_norm_eps"], cfg["layer_types"]
    made_by = makers(kinds)
    memory = kv = None
    h = embedding(w)[tokens]
    for i in range(len(kinds) if layers is None else layers):
        p = f"model.layers.{i}."
        a = layer_norm(h, w[p + "input_layernorm.weight"],
                       w[p + "input_layernorm.bias"], eps)
        if kinds[i] == MAMBA:
            out, y = mamba1(a, w, p + "attn.", cfg)
            if made_by.get(GMU) == i:
                memory = y
        elif kinds[i] == GMU:
            out = gmu(a, memory, w, p + "attn.")
        elif kinds[i] in ATTENDS:
            out, made = attention(a, w, p + "attn.", cfg, i, kinds[i], kv)
            if made_by.get(CROSS) == i:
                kv = made
        else:
            raise ValueError(f"layer_types[{i}] = {kinds[i]!r}")
        h = h + out
        m = layer_norm(h, w[p + "post_attention_layernorm.weight"],
                       w[p + "post_attention_layernorm.bias"], eps)
        h = h + mlp(m, w, p + "mlp.")
    return layer_norm(h, w["model.final_layernorm.weight"],
                      w["model.final_layernorm.bias"], eps)


def embedding(w: Weights):
    """The tied table, with the program's padding rows where it has any
    (``extra_vocab_rows``: the program pads the vocabulary to a multiple of
    128 and its random tokens and softmax include those rows)."""
    table = w["model.embed_tokens.weight"]
    if "extra_vocab_rows" in w:
        table = jnp.concatenate([table, w["extra_vocab_rows"]], axis=0)
    return table


def logits(w: Weights, cfg: Mapping, tokens, *, layers: Optional[int] = None):
    return hidden_states(w, cfg, tokens, layers=layers) @ embedding(w).T


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    """Sum of token negative log-likelihoods (the embedding is tied)."""
    return token_nll_sum(logits(w, cfg, tokens, layers=layers), labels)


def attention_blocks(config: Mapping) -> List[Dict[str, int]]:
    """One entry a block of ``layer_types`` that attends: the score heads
    the core runs (every query head, q/k ``head_dim`` wide, over the pair's
    value ``2 head_dim`` wide), a window where the block has one."""
    D = config["hidden_size"] // config["num_attention_heads"]
    core = {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "qk_head_dim": D, "v_head_dim": 2 * D}
    return [dict(core, window=config["sliding_window"]) if kind == WINDOW
            else dict(core)
            for kind in config["layer_types"] if kind in ATTENDS]


def core_flops_per_token(sizes: flops.Sizes, entry: flops.Attention) -> float:
    """The score and value matmuls of one block's core alone: what
    ``flops.attention_flops_per_token`` counts beside the projections."""
    _, pairs = flops._span(sizes, entry)
    heads, _, qk, v = flops._block(sizes, entry)
    return 2 * heads * (qk + v) * (pairs / sizes.seq)


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """Blocks added up by kind, matmuls only. A block that attends: its
    core by ``flops.attention_flops_per_token``'s rule (causal pairs, q/k
    ``head_dim`` and v ``2 head_dim``) and its OWN maps: q, k, v and out at
    the widths they have (that function would count v and out at the
    core's doubled value width, and k and v maps for a cross block that has
    none). A mamba1 block: its four projections; a gmu block: its two;
    every block's gated MLP; the tied head over the sliced vocabulary."""
    H, kinds = sizes.hidden, config["layer_types"]
    D = H // config["num_attention_heads"]
    nq, nk = config["num_attention_heads"] * D, config["num_key_value_heads"] * D
    di, N, R = d_inner(config), config["mamba_d_state"], config["mamba_dt_rank"]
    maps = {MAMBA: 2 * H * 2 * di + 2 * di * (R + 2 * N) + 2 * R * di
            + 2 * di * H,
            GMU: 2 * H * di + 2 * di * H,
            WINDOW: 2 * H * (nq + 2 * nk) + 2 * nq * H,
            FULL: 2 * H * (nq + 2 * nk) + 2 * nq * H,
            CROSS: 2 * H * nq + 2 * nq * H}
    mlp_flops = 2 * 3 * H * config["intermediate_size"]
    cores = sum(core_flops_per_token(sizes, entry)
                for entry in sizes.attention_blocks())
    return (sum(maps[kind] for kind in kinds) + cores
            + len(kinds) * mlp_flops + flops.head_flops_per_token(sizes))
