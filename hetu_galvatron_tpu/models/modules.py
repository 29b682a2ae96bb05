"""Transformer building blocks as pure functions over explicit param pytrees.

Capability parity with the reference's module zoo (runtime/models/modules.py,
runtime/transformer/attention.py:111-720, mlp.py, norm.py:6,
rotary_pos_embedding.py): embedding, decoder layer (attention + MLP with
RMS/LayerNorm, RoPE or learned positions, GQA, SwiGLU/GeGLU/GeLU), final norm,
and LM head with a numerically-stable cross-entropy.

TPU-first design, deliberately unlike the torch reference:

* **Pure functions + pytrees.** Each module is an ``init_*`` returning
  ``(params, logical_axes)`` and an ``apply_*``; no module objects, no hidden
  state. The whole model is a nested dict that `jax.jit`/`pjit` shard by a
  matching tree of :data:`PartitionSpec`s.
* **Logical axis names.** ``init_*`` returns, alongside every param, a tuple of
  logical axis names (``("embed", "qkv")`` etc). The mesh layer
  (``runtime/mesh.py``) maps logical names -> mesh axes *per layer*, which is
  how the reference's per-layer strategy vectors (tp/sp/cp/dp-type) become
  GSPMD shardings instead of Megatron process groups.
* **MXU-friendly shapes.** QKV is one fused matmul ((nq+2*nkv)*head_dim wide),
  SwiGLU gate+up is one fused matmul; weights live in fp32, compute runs in
  bf16 with fp32 accumulation (``preferred_element_type``).
* **One record of what a plan swaps in a block** (:class:`LayerOps`: the
  attention core, which by the layer's strategy is XLA attention, a Pallas
  flash kernel, Ulysses all-to-all or ring attention, reference dispatch
  attention.py:664-720; the projection matmuls; the kernels of a scan or a
  convolution). ``parallel/spmd.py`` fills one a layer from the plan; a
  block body (:func:`apply_decoder_layer`, models/encdec.py) takes it as
  ``ops`` and hands it on, and only :func:`apply_mixer` and the block's
  feed-forward branch take it apart, into the keywords of the functions
  that call an operator (``apply_attention(sdpa_fn=, matmul_fns=,
  shard_fn=)``, ``apply_mamba2(ssd_fn=, conv_fn=, norm_fn=)``, ...).
* **One table of mixer kinds.** :data:`MIXERS` has a row a kind of block
  operator: its parameter key, ``init``, ``apply``, whether it attends and
  which fields of the record it reads.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import SHARED_VALUES, ModelArgs

Params = Dict[str, Any]
Axes = Dict[str, Any]


@dataclass(frozen=True)
class LayerOps:
    """What a plan can swap in one block; an unset field is the
    ``jax.numpy`` / XLA form. ``sdpa(q, k, v, causal=...)`` is the attention
    core and ``cross_sdpa`` a t5 decoder block's cross-attention core (unset
    = ``sdpa``); ``matmuls`` ({"qkv", "out", "fc1", "fc2"}) the projection
    matmuls (ops/overlap.py's ring all-gather / reduce-scatter ones, each
    mapping (x, w) to the fp32 product the default einsum would produce);
    ``shard(a, axis)`` pins an interior activation
    of a tp > 1 layer (parallel/spmd.py::interior_sharding); ``ssd``,
    ``kda``, ``gdn``, ``selective``, ``conv`` and ``gated_norm`` are the
    kernels of a mamba block's chunked scan, a kda block's chunked delta
    rule, a linear_attention block's (a decay a head), a mamba1 block's
    selective scan, the causal depthwise convolution and a mamba block's
    skip and gated norm (ops/pallas/); ``exchange`` runs an expert block's
    sorted dispatcher across the chips of its ``ep``
    group (models/moe.py::make_expert_exchange); ``grouped(mode, a, b,
    group_sizes, out_dtype)`` is the kernels of an expert block's grouped
    matmuls, forward and both gradients (ops/pallas/grouped_matmul.py), None
    for shapes that fit them no tile. Which kinds of block read which field:
    :data:`MIXERS`; ``exchange`` and ``grouped`` are the expert
    feed-forward's."""

    sdpa: Optional[Callable[..., jax.Array]] = None
    cross_sdpa: Optional[Callable[..., jax.Array]] = None
    matmuls: Optional[Dict[str, Callable]] = None
    shard: Optional[Callable[[jax.Array, int], jax.Array]] = None
    ssd: Optional[Callable[..., jax.Array]] = None
    kda: Optional[Callable[..., jax.Array]] = None
    gdn: Optional[Callable[..., jax.Array]] = None
    selective: Optional[Callable[..., Optional[jax.Array]]] = None
    conv: Optional[Callable[..., Optional[jax.Array]]] = None
    gated_norm: Optional[Callable[..., Optional[jax.Array]]] = None
    exchange: Optional[Callable[..., Any]] = None
    grouped: Optional[Callable[..., Optional[jax.Array]]] = None

    def given(self) -> Dict[str, Any]:
        """The fields that are set, by name."""
        return {k: v for k, v in vars(self).items() if v is not None}


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def _normal(key, shape, std, dtype=jnp.float32):
    return std * jax.random.normal(key, shape, dtype)


def param_dtype_of(cfg: ModelArgs) -> jnp.dtype:
    return jnp.float32  # master weights are always fp32; compute casts down


def compute_dtype_of(mixed_precision: str) -> jnp.dtype:
    return {"bf16": jnp.bfloat16, "fp16": jnp.float16, "fp32": jnp.float32}[
        mixed_precision
    ]


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelArgs) -> Tuple[Params, Axes]:
    # zero-centered (gemma) weights store the offset from 1, so init is 0
    init = 0.0 if cfg.norm_zero_centered else 1.0
    p: Params = {"scale": jnp.full((cfg.hidden_size,), init, jnp.float32)}
    a: Axes = {"scale": ("embed",)}
    if cfg.normalization == "layernorm":
        p["bias"] = jnp.zeros((cfg.hidden_size,), jnp.float32)
        a["bias"] = ("embed",)
    return p, a


def apply_norm(p: Params, x: jax.Array, cfg: ModelArgs) -> jax.Array:
    """RMSNorm or LayerNorm, computed in fp32 regardless of activation dtype
    (matches the reference's fp32 norm path, norm.py:6). Empty params =
    identity (post-norm families have no final pre-head norm)."""
    if not p:
        return x
    dtype = x.dtype
    x = x.astype(jnp.float32)
    scale = p["scale"]
    if cfg.norm_zero_centered:
        scale = 1.0 + scale  # gemma RMSNorm: x * (1 + weight)
    if cfg.normalization == "rmsnorm":
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + cfg.layernorm_epsilon) * scale
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + cfg.layernorm_epsilon)
        y = y * scale + p["bias"]
    return y.astype(dtype)


def block_norm(p: Params, x: jax.Array, cfg: ModelArgs) -> jax.Array:
    """:func:`apply_norm` where it is a block's own norm (``ln1``, ``ln2``,
    the one before the head), under the named scope ``norm``; the q/k norm
    and a mamba block's gated norm have scopes of their own."""
    with jax.named_scope("norm"):
        return apply_norm(p, x, cfg)


def weight_view(w: jax.Array, dtype) -> jax.Array:
    """A stored weight in the compute dtype, under the named scope
    ``param_view``: the cast is no part of the projection it feeds (XLA
    hoists it out of the microbatch loop, and its transpose is the cast of
    the weight's gradient back)."""
    with jax.named_scope("param_view"):
        return w.astype(dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------


def _scale_inv_freq(inv_freq: jax.Array, scaling: Optional[dict],
                    theta: float = 10000.0) -> jax.Array:
    """HF-style ``rope_scaling``: "linear" divides frequencies by ``factor``;
    "llama3" keeps high-frequency bands, divides low-frequency bands by
    ``factor``, and smoothly interpolates between the two wavelength
    thresholds (the public llama-3.1 rope recipe; parity-tested against
    transformers' _compute_llama3_parameters); "yarn" keeps the bands that
    turn more than ``beta_fast`` times within the original context, divides
    by ``factor`` those that turn fewer than ``beta_slow`` times, and ramps
    linearly between the two band indices (transformers'
    _compute_yarn_parameters, truncated bounds)."""
    if not scaling:
        return inv_freq
    rope_type = scaling.get("rope_type", scaling.get("type", "linear"))
    factor = float(scaling.get("factor", 1.0))
    if rope_type == "linear":
        return inv_freq / factor
    if rope_type == "llama3":
        low = float(scaling["low_freq_factor"])
        high = float(scaling["high_freq_factor"])
        orig = float(scaling["original_max_position_embeddings"])
        wavelen = 2.0 * jnp.pi / inv_freq
        smooth = (orig / wavelen - low) / (high - low)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        return (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    if rope_type == "yarn":
        half = inv_freq.shape[0]
        dim = 2 * half
        orig = float(scaling["original_max_position_embeddings"])
        log_base = math.log(theta)

        def band(turns: float) -> float:
            # the band index that turns ``turns`` times within ``orig``
            return dim * math.log(orig / (turns * 2.0 * math.pi)) / (
                2.0 * log_base)

        low = max(math.floor(band(float(scaling.get("beta_fast", 32)))), 0)
        high = min(math.ceil(band(float(scaling.get("beta_slow", 1)))),
                   dim - 1)
        if low == high:
            high += 0.001  # transformers' guard against a zero-wide ramp
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
    raise ValueError(f"unsupported rope_scaling type {rope_type!r} "
                     "(supported: linear, llama3, yarn)")


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1`` (1 for
    a factor of at most 1)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def rope_attention_factor(scaling: Optional[dict]) -> float:
    """What "yarn" multiplies cos and sin by: the ``attention_factor`` a
    configuration states, else ``mscale`` over ``mscale_all_dim``
    (DeepSeek's form; 1 where they are equal); 1 for every other scaling."""
    if not scaling or scaling.get(
            "rope_type", scaling.get("type")) != "yarn":
        return 1.0
    if scaling.get("attention_factor") is not None:
        # stated by the configuration (transformers' yarn takes it before
        # anything it would compute)
        return float(scaling["attention_factor"])
    m, m_all = scaling.get("mscale"), scaling.get("mscale_all_dim")
    if not m or not m_all:
        raise ValueError(
            "rope_scaling type yarn: attention_factor, or mscale and "
            f"mscale_all_dim both, are required (got {scaling!r})")
    factor = float(scaling.get("factor", 1.0))
    return yarn_mscale(factor, float(m)) / yarn_mscale(factor, float(m_all))


def rope_cos_sin(
    seq_len: int, head_dim: int, theta: float, dtype=jnp.float32,
    scaling: Optional[dict] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Precompute RoPE tables [seq, head_dim//2] (reference
    rotary_pos_embedding.py builds the same inv-freq table)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    inv_freq = _scale_inv_freq(inv_freq, scaling, theta)
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [S, D/2]
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    factor = rope_attention_factor(scaling)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return cos.astype(dtype), sin.astype(dtype)


def mrope_cos_sin(
    position_ids: jax.Array,  # [3, B, S] (temporal, height, width)
    head_dim: int, theta: float, sections, dtype=jnp.float32,
    scaling: Optional[dict] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Multimodal rotary tables (reference
    rotary_pos_embedding.py MultimodalRotaryEmbedding / HF Qwen2-VL mrope):
    the D/2 frequency dims split into ``sections`` (sum = D/2); section j's
    rotations use the j-th position row, so temporal/height/width positions
    each drive their own frequency band. With the three rows identical this
    reduces EXACTLY to :func:`rope_cos_sin` over those positions (the
    text-only case — parity-tested). Returns cos/sin [B, S, D/2], the
    gathered-per-token layout :func:`apply_rope` accepts."""
    sections = tuple(int(s) for s in sections)
    if sum(sections) != head_dim // 2:
        raise ValueError(
            f"mrope sections {sections} must sum to head_dim//2 "
            f"= {head_dim // 2}")
    if position_ids.ndim != 3 or position_ids.shape[0] != len(sections):
        raise ValueError(
            f"mrope position_ids must be [{len(sections)}, B, S], got "
            f"{position_ids.shape}")
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    inv_freq = _scale_inv_freq(inv_freq, scaling, theta)
    # [3, B, S, D/2]; frequency dim d draws from position row row[d]
    freqs = position_ids.astype(jnp.float32)[..., None] * inv_freq
    row = jnp.concatenate([
        jnp.full((s,), j, jnp.int32) for j, s in enumerate(sections)])
    sel = jnp.einsum("rbsd,dr->bsd", freqs,
                     jax.nn.one_hot(row, len(sections), dtype=jnp.float32))
    return jnp.cos(sel).astype(dtype), jnp.sin(sel).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, N, D]; rotate-half convention (llama-style). cos/sin are
    [S, D/2] (positions in order) or [B, S, D/2] (gathered per-token
    position ids — packed samples with reset_position_ids). Tables
    narrower than D/2 rotate the leading ``2 * width`` values of a head and
    pass the rest through (a partial rotary factor)."""
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Axes]:
    h, hd = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.kv_heads
    k1, k2 = jax.random.split(key)
    std = 0.02
    # fused qkv: one MXU matmul; layout [q | k | v] along the wide axis
    # (a tp > 1 layer computes on qkv_group_major's view of it)
    p: Params = {
        "wqkv": _normal(k1, (h, (nq + 2 * nkv) * hd), std),
        "wo": _normal(k2, (nq * hd, h), std / math.sqrt(2 * cfg.num_hidden_layers)),
    }
    a: Axes = {"wqkv": ("embed", "qkv"), "wo": ("heads", "embed")}
    if cfg.add_qkv_bias:
        p["bqkv"] = jnp.zeros(((nq + 2 * nkv) * hd,), jnp.float32)
        a["bqkv"] = ("qkv",)
    if cfg.add_bias_linear or cfg.add_attn_out_bias:
        p["bo"] = jnp.zeros((h,), jnp.float32)
        a["bo"] = ("embed",)
    if cfg.differential_attention:
        dp, da = init_differential(jax.random.fold_in(key, 3), cfg)
        p.update(dp)
        a.update(da)
    if cfg.gating:
        # a logit a query head from the block's normed input; a leaf of its
        # own, replicated (a model with a gate runs with tp = 1,
        # eligibility.window_plan_reason): inside ``wqkv`` its nq columns
        # would end the fused product off a lane tile
        p["wg"] = _normal(jax.random.fold_in(key, 2), (h, nq), std)
        a["wg"] = ("embed", "attn_gate")
    if cfg.qk_norm:
        if cfg.normalization != "rmsnorm" or cfg.norm_zero_centered:
            raise ValueError("model.qk_norm is an RMSNorm with plain scales "
                             "(normalization=rmsnorm, norm_zero_centered "
                             "off), applied by apply_norm")
        # one scale over the whole projected width each; replicated (the
        # axis name is none of mesh.py's sharded ones), so under tp the
        # mean over the sharded width is GSPMD's all-reduce. Per head
        # (cfg.qk_norm_per_head) the scale is head_dim wide and the mean is
        # over one head's values, local to whichever shard holds the head
        widths = ((hd, hd) if cfg.qk_norm_per_head
                  else (nq * hd, nkv * hd))
        for name, width in zip(("q_norm", "k_norm"), widths):
            p[name] = {"scale": jnp.ones((width,), jnp.float32)}
            a[name] = {"scale": ("qk_norm",)}
    return p, a


# ---------------------------------------------------------------------------
# differential attention (two softmax maps a head pair, subtracted)
# ---------------------------------------------------------------------------


def init_differential(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Axes]:
    """What differential attention (arXiv:2410.05258) adds to a block that
    attends: ``lambdas`` [4, head_dim], the rows ``lambda_q1``,
    ``lambda_k1``, ``lambda_q2``, ``lambda_k2`` (N(0, 0.1)), and ``subln``,
    the scale of the RMSNorm over a pair's ``2 head_dim`` values.
    Replicated: a model with it runs with tp = 1
    (eligibility.window_plan_reason)."""
    if cfg.num_attention_heads % 2 or cfg.kv_heads % 2:
        raise ValueError(
            "model.differential_attention pairs heads: "
            f"{cfg.num_attention_heads} query and {cfg.kv_heads} key-value "
            "heads are not both even")
    return ({"lambdas": _normal(key, (4, cfg.head_dim), 0.1),
             "subln": {"scale": jnp.ones((2 * cfg.head_dim,), jnp.float32)}},
            {"lambdas": ("diff_lambda", "diff_width"),
             "subln": {"scale": ("diff_width",)}})


def pair_values(v: jax.Array) -> jax.Array:
    """[B, S, K, D] -> [B, S, K, 2 D]: key-value heads ``2g`` and ``2g + 1``
    are one pair, and both of its key heads are handed the pair's value
    ``[v_2g | v_2g+1]``, so that ONE core call over all score heads gives
    each softmax map of a pair over the value two heads wide."""
    B, S, K, D = v.shape
    return jnp.broadcast_to(v.reshape(B, S, K // 2, 1, 2 * D),
                            (B, S, K // 2, 2, 2 * D)).reshape(B, S, K, 2 * D)


def diff_lambda_init(i: int) -> float:
    """Block ``i``'s constant of differential attention."""
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def differential(p: Params, out: jax.Array, cfg: ModelArgs,
                 lam0: Optional[float]) -> jax.Array:
    """``a_j = P_1 V - lambda P_2 V`` a query pair, ``lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, then ``(1 -
    lambda_init) RMSNorm(a_j) w``: float32, under ``attn/diff``. ``out`` [B,
    S, N, 2 D] is the core's, its heads in the order the block's query
    projection keeps them (:func:`diff_core_order`): ``(key-value pair,
    map, query pair of it)``; returns [B, S, N / 2, 2 D], the query pairs in
    their published order. ``lam0``: the block's ``lambda_init``
    (:func:`diff_lambda_init` of its index, handed down by the walk)."""
    B, S, N, W = out.shape
    pairs = cfg.kv_heads // 2
    if lam0 is None:
        raise ValueError(
            "differential attention reads its block's lambda_init: hand the "
            "block lambda_init=diff_lambda_init(i)")
    f32 = jnp.float32
    with jax.named_scope("attn/diff"):
        lq1, lk1, lq2, lk2 = p["lambdas"].astype(f32)
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
        maps = out.astype(f32).reshape(B, S, pairs, 2, N // (2 * pairs), W)
        a = (maps[:, :, :, 0] - lam * maps[:, :, :, 1]).reshape(
            B, S, N // 2, W)
        var = jnp.mean(jnp.square(a), axis=-1, keepdims=True)
        a = (a * jax.lax.rsqrt(var + cfg.layernorm_epsilon)
             * p["subln"]["scale"] * (1.0 - lam0))
        return a.astype(out.dtype)


def diff_core_order(nq: int, nkv: int) -> Tuple[int, ...]:
    """The published index of the query head the core sees at each place.
    Published, query heads ``2j`` and ``2j + 1`` are the two maps of pair
    ``j``, and pair ``j`` reads key-value pair ``j // G`` (``G = nq /
    nkv``). The core's grouped heads want the queries of one key head side
    by side, so a block keeps its query projection's heads as ``(key-value
    pair g, map c, query pair t of it)``: place ``(2g + c) G + t`` holds
    published head ``2 (g G + t) + c``. The exporter permutes
    (runtime/checkpoint.py)."""
    G = nq // nkv
    return tuple(2 * (g * G + t) + c for g in range(nkv // 2)
                 for c in range(2) for t in range(G))


def remat(fn, cfg: ModelArgs):
    """Per-layer activation checkpointing with the configured policy
    (reference parallel.py:213-243 wraps with torch checkpoint_wrapper; the
    TPU lever is WHICH values the backward may keep — saving MXU outputs
    ("dots") trades a little memory for skipping matmul recompute).

    A plan's ``checkpoint`` bit means a block MAY be recomputed; it is,
    under this wrapper, where its values do not fit: the step program
    (parallel/spmd.py) counts what each such block would hold and leaves
    as many of them unwrapped as the device's memory takes
    (parallel/kept.py). The pipeline engines wrap every block whose bit is
    set.

    Under every policy the results a forward kernel's differentiated rule
    names are kept (each kernel file's ``KEPT``): a flash attention core's
    output and row statistics, a delta-rule, Mamba-2 or selective scan's
    output and the states that entered its chunks. They are what the rest of the step reads
    of the kernel, and producing them again is the kernel's whole run. So
    ``full`` keeps the block's input and what its forward kernels named; the
    recomputed forward then holds none of them. A block that ran no such
    kernel (the XLA core, a mixer in its ``jax.numpy`` form, an MLP alone)
    traces no name and is recomputed whole; the convolution's kernels name
    nothing either."""
    from hetu_galvatron_tpu.ops.pallas import (
        flash_attention,
        gdn,
        kda,
        selective_scan,
        ssd,
    )

    policies = jax.checkpoint_policies
    base = {"full": None, "dots": policies.checkpoint_dots,
            "dots_no_batch": policies.checkpoint_dots_with_no_batch_dims}
    if cfg.remat_policy not in base:
        # model_copy(update=...) skips pydantic validation, so a typo'd
        # policy would otherwise silently run full recompute
        raise ValueError(f"unknown remat_policy {cfg.remat_policy!r} "
                         "(full | dots | dots_no_batch)")
    policy = policies.save_only_these_names(
        *flash_attention.KEPT, *kda.KEPT, *gdn.KEPT, *ssd.KEPT,
        *selective_scan.KEPT)
    if base[cfg.remat_policy] is not None:
        policy = policies.save_from_both_policies(base[cfg.remat_policy],
                                                  policy)
    return jax.checkpoint(fn, policy=policy)


def recomputed(fn, cfg: ModelArgs, flag):
    """``fn`` as a block whose remat flag is ``flag`` runs it: as it stands
    (clear), under :func:`remat` (set), or as ``flag(fn, cfg)`` where the
    flag is the step program's probe, which counts what the block would hold
    (parallel/kept.py::Probe). The one place a flag of the stacks' lists
    (``forward_causal_lm``, ``apply_tower``, ``forward_encdec``) is read:
    the probe rides in the lists the plan's bits ride in, so that it meets
    each block where the model builds it, with the arguments it builds it
    with, and no stack's walk knows of the count."""
    if callable(flag):
        return flag(fn, cfg)
    return remat(fn, cfg) if flag else fn


# fold_in stream bases partitioning one per-step dropout key into disjoint
# substreams: decoder layers use their index i directly; these bases keep
# embeddings / encoder layers clear of that range
DROPOUT_STREAM_EMBED = 1 << 20        # (decoder-side) embedding
DROPOUT_STREAM_EMBED_ENC = (1 << 20) + 1  # encoder-side embedding (t5)
DROPOUT_STREAM_ENC = 1 << 21          # + j for encoder layer j


def fold_dropout_rng(rng: Optional[jax.Array], cfg: ModelArgs,
                     idx: int) -> Optional[jax.Array]:
    """None-propagating fold_in, also None when both dropout rates are 0 —
    the single place the per-step key is partitioned (builder, encdec, and
    the pipeline stage programs all route through here)."""
    if rng is None or (cfg.hidden_dropout <= 0.0
                       and cfg.attention_dropout <= 0.0):
        return None
    return jax.random.fold_in(rng, idx)


def dropout(x: jax.Array, rate: float, rng: Optional[jax.Array]) -> jax.Array:
    """Inverted dropout; identity when ``rng is None`` (eval) or rate 0.
    The reference inherits torch's nn.Dropout semantics; here the rng is
    threaded explicitly so training steps stay pure functions."""
    if rng is None or rate <= 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))


def xla_sdpa(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    dropout_rate: float = 0.0, dropout_rng: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Reference attention core on XLA: [B,S,N,D] x [B,T,K,D] -> [B,S,N,D]
    (v may have a width of its own: [B,T,K,Dv] -> [B,S,N,Dv]).

    GQA handled by reshaping q into [B,S,K,G,D] groups. Softmax in fp32.
    Swapped out for the Pallas flash kernel / ring attention by the strategy
    dispatch (reference attention.py:664-720 has the same three-way switch).
    ``dropout_rate`` applies attention-probability dropout (reference
    attention.py passes attention_dropout into its cores).
    ``segment_ids`` [B, S] (self-attention only, S == T) block-diagonalizes
    the mask so packed documents cannot attend across boundaries (the
    reference's reset_attention_mask, Megatron
    get_ltor_masks_and_position_ids).
    ``scale``: softmax(scale * q k^T) where the model states its own
    (``ModelArgs.attention_multiplier``); ``None`` divides by sqrt(D).
    ``window``: a query meets the ``window`` newest keys of its causal span,
    its own included (a block of sliding-window attention).
    """
    if window is not None and not causal:
        raise ValueError("a window is a part of the causal span")
    B, S, N, D = q.shape
    K = k.shape[2]
    G = N // K
    qg = q.reshape(B, S, K, G, D)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores / math.sqrt(D) if scale is None else scores * scale
    if causal:
        # queries own absolute positions [T-S, T): supports S<T (inference)
        qpos = jnp.arange(S)[:, None] + (k.shape[1] - S)
        kpos = jnp.arange(k.shape[1])[None, :]
        seen = qpos >= kpos
        if window is not None:
            seen &= qpos - kpos < window
        scores = jnp.where(seen, scores, jnp.finfo(jnp.float32).min)
    if segment_ids is not None:
        if k.shape[1] != S:
            raise ValueError("segment_ids require self-attention (S == T)")
        same = segment_ids[:, None, None, :, None] == \
            segment_ids[:, None, None, None, :]
        scores = jnp.where(same, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    probs = dropout(probs, dropout_rate, dropout_rng)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, N, v.shape[-1]).astype(q.dtype)


def qkv_group_major(w: jax.Array, cfg: ModelArgs) -> jax.Array:
    """The fused projection ``[..., q | k | v]`` (weight or bias) as
    ``[..., nkv, (nq/nkv + 2) * hd]``: each key-value head with the q heads
    that attend to it (Megatron's group-major order). Sharded over the
    group axis, the split into q, k and v is local to a tensor-parallel
    shard; the stored ``[q | k | v]`` order, cut in contiguous halves, puts
    q's first heads on one chip and k and v on another."""
    hd, nq, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.kv_heads
    q, k, v = jnp.split(w, [nq * hd, (nq + nkv) * hd], axis=-1)
    lead = w.shape[:-1]
    return jnp.concatenate(
        [q.reshape(lead + (nkv, nq // nkv * hd)),
         k.reshape(lead + (nkv, hd)), v.reshape(lead + (nkv, hd))], axis=-1)


def apply_attention(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    rope: Optional[Tuple[jax.Array, jax.Array]] = None,
    sdpa_fn: Callable[..., jax.Array] = xla_sdpa,
    compute_dtype=jnp.bfloat16,
    causal: bool = True,
    dropout_rng: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    matmul_fns: Optional[Dict[str, Callable]] = None,
    shard_fn: Optional[Callable[[jax.Array, int], jax.Array]] = None,
    windowed: bool = False,
    made: Optional[Dict[str, jax.Array]] = None,
    lambda_init: Optional[float] = None,
) -> jax.Array:
    """``cfg`` is the block's (``ModelArgs.for_block``: its own query
    heads). ``windowed`` (a "sliding_attention" block): the core attends
    over the ``cfg.sliding_window`` newest keys of the causal span, under
    the named scope ``attn/window_core``. A model with ``cfg.gating``
    multiplies each head's output by the sigmoid of the block's ``wg``
    logit for it, under ``attn/gate``. Under
    ``cfg.differential_attention`` the core is called once over all score
    heads with the pair's value (:func:`pair_values`) and
    :func:`differential` joins the pairs under ``lambda_init``. ``made``
    (the block that leaves its keys and values for later blocks,
    ``ModelArgs.block_shares``) is written ``keys`` and ``values`` [B, S,
    kv heads x head_dim] as the core read them.

    ``shard_fn(a, axis)`` (a layer whose plan has tp > 1,
    parallel/spmd.py::interior_sharding) pins an interior activation to
    the layer's own shards, dimension ``axis`` on its tp axes. The layer it
    is given to is given ``wqkv`` / ``bqkv`` as :func:`qkv_group_major`'s
    view with it, so that nothing between the two projections leaves its
    shard; without it the leaves are the stored ``[q | k | v]``."""
    B, S, H = x.shape
    hd = cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.kv_heads
    mm = matmul_fns or {}
    w = weight_view(p["wqkv"], compute_dtype)
    group_major = shard_fn is not None
    with jax.named_scope("attn/qkv_proj"):
        if "qkv" in mm:
            qkv = mm["qkv"](x.astype(compute_dtype), w)
        else:
            qkv = jnp.einsum(
                "bsh,hkf->bskf" if group_major else "bsh,hf->bsf",
                x.astype(compute_dtype), w,
                preferred_element_type=jnp.float32)
        if "bqkv" in p:
            qkv = qkv + p["bqkv"]
        qkv = qkv.astype(compute_dtype)
        if group_major:
            qkv = shard_fn(qkv, 2)
            g = nq // nkv
            q, k, v = (a.reshape(B, S, -1) for a in jnp.split(
                qkv, [g * hd, (g + 1) * hd], axis=-1))
        else:
            q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
    per_head = cfg.qk_norm_per_head
    if "q_norm" in p and not per_head:
        with jax.named_scope("attn/qk_norm"):
            q = apply_norm(p["q_norm"], q, cfg)
            k = apply_norm(p["k_norm"], k, cfg)
    with jax.named_scope("attn/qkv_proj"):
        q = q.reshape(B, S, nq, hd)
        k = k.reshape(B, S, nkv, hd)
        v = v.reshape(B, S, nkv, hd)
    if "q_norm" in p and per_head:
        with jax.named_scope("attn/qk_norm"):
            q = apply_norm(p["q_norm"], q, cfg)
            k = apply_norm(p["k_norm"], k, cfg)
    if group_major:
        q, k, v = (shard_fn(a, 2) for a in (q, k, v))
    if rope is not None:
        cos, sin = rope
        with jax.named_scope("attn/rope"):
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    core_kwargs: Dict[str, Any] = {}
    if made is not None:
        made.update(keys=k.reshape(B, S, nkv * hd),
                    values=v.reshape(B, S, nkv * hd))
    if cfg.differential_attention:
        v = pair_values(v)

    def core(*a, **kw):
        if windowed:
            with jax.named_scope("attn/window_core"):
                return sdpa_fn(*a, **kw)
        with jax.named_scope("attn/core"):
            return sdpa_fn(*a, **kw)

    if (windowed or "wg" in p or cfg.differential_attention
            or cfg.num_attention_heads_per_layer is not None):
        from hetu_galvatron_tpu.analysis.eligibility import WINDOW_REASON

        if group_major or mm:
            raise NotImplementedError(WINDOW_REASON)
    if windowed:
        # the window is an argument of the core; a core without it would
        # attend over the whole causal span in silence
        if not (sdpa_fn is xla_sdpa
                or getattr(sdpa_fn, "supports_window", False)):
            raise NotImplementedError(WINDOW_REASON)
        if not causal:
            raise NotImplementedError(
                "a sliding_attention block attends over a part of the "
                "causal span; an encoder stack has none")
        core_kwargs["window"] = int(cfg.sliding_window)
    if cfg.attention_multiplier is not None:
        # the model's own softmax scale is an argument of the core; a core
        # without the argument would attend at 1/sqrt(D) in silence
        if not (sdpa_fn is xla_sdpa
                or getattr(sdpa_fn, "supports_scale", False)):
            raise NotImplementedError(
                "model.attention_multiplier (a softmax scale other than "
                "1/sqrt(head_dim)) is an argument of the XLA attention core "
                "and of the Pallas flash kernels; the installed ring/Ulysses "
                "core does not take it. Avoid cp/ulysses layers for this "
                "model")
        core_kwargs["scale"] = float(cfg.attention_multiplier)
    use_dropout = dropout_rng is not None and cfg.attention_dropout > 0.0
    if use_dropout:
        # probability dropout lives inside the attention core: the XLA core
        # and the Pallas flash kernel implement it (flash regenerates a
        # counter-based mask per tile in fwd+bwd — the reference's CUDA
        # flash-attn dropout variant). Silently swapping a ring/Ulysses
        # kernel for the score-materializing XLA core would be an OOM/perf
        # cliff on the long-context plans those kernels exist for — refuse.
        if sdpa_fn is xla_sdpa or getattr(sdpa_fn, "supports_dropout",
                                          False):
            out = core(q, k, v, causal=causal,
                       dropout_rate=cfg.attention_dropout,
                       dropout_rng=dropout_rng, segment_ids=segment_ids,
                       **core_kwargs)
        else:
            raise NotImplementedError(
                "attention_dropout > 0 is only supported with the XLA "
                "attention core and the Pallas flash kernel; the installed "
                "ring/Ulysses kernel has no dropout variant. Avoid "
                "cp/ulysses layers or set model.attention_dropout=0; "
                "hidden_dropout works with every kernel")
    elif segment_ids is not None:
        # packed-document masking: the XLA core, the Pallas flash kernel
        # (per-tile in-kernel) and ring attention (k-side segments rotate
        # with their block) implement it; Ulysses does not
        if sdpa_fn is xla_sdpa or getattr(sdpa_fn, "supports_segments",
                                          False):
            out = core(q, k, v, causal=causal, segment_ids=segment_ids,
                       **core_kwargs)
        else:
            raise NotImplementedError(
                "reset_attention_mask is not supported by the installed "
                "Ulysses attention kernel; use flash, ring, or the XLA "
                "core for packed-document layers, or set "
                "data.reset_attention_mask=false")
    else:
        out = core(q, k, v, causal=causal, **core_kwargs)
    if "wg" in p:
        with jax.named_scope("attn/gate"):
            gate = jnp.einsum("bsh,hn->bsn", x.astype(compute_dtype),
                              weight_view(p["wg"], compute_dtype),
                              preferred_element_type=jnp.float32)
            out = out * jax.nn.sigmoid(gate).astype(compute_dtype)[..., None]
    if cfg.differential_attention:
        out = differential(p, out, cfg, lambda_init)
    with jax.named_scope("attn/out_proj"):
        out = out.reshape(B, S, nq * hd)
        if group_major:
            out = shard_fn(out, 2)
        wo = weight_view(p["wo"], compute_dtype)
        if "out" in mm:
            y = mm["out"](out, wo)
        else:
            y = jnp.einsum("bsf,fh->bsh", out, wo,
                           preferred_element_type=jnp.float32)
        if "bo" in p:
            y = y + p["bo"]
        return y.astype(compute_dtype)


# ---------------------------------------------------------------------------
# latent attention (low-rank q and kv projections, a rotary key for all heads)
# ---------------------------------------------------------------------------


def init_latent_attention(key: jax.Array,
                          cfg: ModelArgs) -> Tuple[Params, Axes]:
    """HF ``DeepseekV3Attention``: ``wq_a`` / ``q_norm`` / ``wq_b`` are
    ``q_a_proj``, ``q_a_layernorm`` and ``q_b_proj`` (a head's columns
    ``[nope | rope]``), ``wkv_a`` is ``kv_a_proj_with_mqa`` (columns
    ``[latent | rope key]``), ``kv_norm`` / ``wkv_b`` are ``kv_a_layernorm``
    and ``kv_b_proj`` (a head's columns ``[nope key | value]``), ``wo`` is
    ``o_proj``. The rotated columns are kept in the half layout
    :func:`apply_rope` turns (first halves, then second halves); the public
    checkpoint interleaves them, and ``params_to_hf`` / ``hf_to_params``
    permute the columns, so nothing is permuted at run time (q.k is the same
    under one permutation of both). No leaf carries an axis name that tensor
    parallelism shards: a plan with tp > 1 over such a block is refused by
    name (``eligibility.latent_plan_reason``)."""
    if cfg.normalization != "rmsnorm" or cfg.norm_zero_centered:
        raise ValueError("a latent-attention block's q and kv norm is an "
                         "RMSNorm with plain scales (normalization=rmsnorm, "
                         "norm_zero_centered off), applied by apply_norm")
    if cfg.add_qkv_bias or cfg.add_bias_linear:
        raise NotImplementedError(
            "latent attention is written without biases (DeepSeek-V3 and "
            "its descendants publish attention_bias false)")
    if not (cfg.kv_lora_rank and cfg.qk_nope_head_dim
            and cfg.qk_rope_head_dim and cfg.v_head_dim):
        raise ValueError(
            "a latent_attention block needs model.kv_lora_rank, "
            "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
    h, nq = cfg.hidden_size, cfg.num_attention_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    std = 0.02
    if rq:
        p: Params = {
            "wq_a": _normal(k1, (h, rq), std),
            "q_norm": {"scale": jnp.ones((rq,), jnp.float32)},
            "wq_b": _normal(k2, (rq, nq * (dn + dr)), std)}
        a: Axes = {"wq_a": ("embed", "latent_q"),
                   "q_norm": {"scale": ("latent_q",)},
                   "wq_b": ("latent_q", "latent_heads")}
    else:
        p = {"wq": _normal(k1, (h, nq * (dn + dr)), std)}
        a = {"wq": ("embed", "latent_heads")}
    p.update({
        "wkv_a": _normal(k3, (h, rkv + dr), std),
        "kv_norm": {"scale": jnp.ones((rkv,), jnp.float32)},
        "wkv_b": _normal(k4, (rkv, nq * (dn + dv)), std),
        "wo": _normal(k5, (nq * dv, h),
                      std / math.sqrt(2 * cfg.num_hidden_layers)),
    })
    a.update({
        "wkv_a": ("embed", "latent_kv"),
        "kv_norm": {"scale": ("latent_kv",)},
        "wkv_b": ("latent_kv", "latent_heads"),
        "wo": ("latent_heads", "embed"),
    })
    return p, a


def latent_softmax_scale(cfg: ModelArgs) -> float:
    """``qk_head_dim ** -0.5``, times YaRN's ``mscale_all_dim`` correction
    squared where the model scales its rotary bands (HF
    ``DeepseekV3Attention.scaling``)."""
    scale = cfg.qk_head_dim ** -0.5
    sc = cfg.rope_scaling or {}
    if sc.get("rope_type", sc.get("type")) == "yarn" and sc.get(
            "mscale_all_dim"):
        m = yarn_mscale(float(sc.get("factor", 1.0)),
                        float(sc["mscale_all_dim"]))
        scale *= m * m
    return scale


def apply_latent_attention(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    rope: Optional[Tuple[jax.Array, jax.Array]] = None,
    sdpa_fn: Callable[..., jax.Array] = xla_sdpa,
    compute_dtype=jnp.bfloat16,
    causal: bool = True,
    dropout_rng: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """``c_q = RMSNorm(x W_qa)``, ``[q_nope | q_rope] = c_q W_qb`` a head
    (``= x W_q`` where the block holds ``wq``: no low-rank step, no norm);
    ``[c_kv | k_rope] = x W_kva`` (``k_rope`` one for all heads), ``[k_nope
    | v] = RMSNorm(c_kv) W_kvb`` a head; RoPE on ``q_rope`` and ``k_rope``
    (none where ``rope`` is None: a model without positions);
    ``softmax([q_nope | q_rope] [k_nope | k_rope]^T * scale) v`` at
    :func:`latent_softmax_scale`; ``W_o``. The core is given q and k of
    ``qk_head_dim`` and v of ``v_head_dim``: no operand is padded to the
    other's width (the XLA core and the flash kernels take v's own)."""
    B, S, _ = x.shape
    nq, rkv = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    f32 = jnp.float32
    if not (sdpa_fn is xla_sdpa or getattr(sdpa_fn, "supports_scale", False)):
        raise NotImplementedError(
            "latent attention states its own softmax scale and a value "
            "width of its own, which the XLA attention core and the Pallas "
            "flash kernels take; the installed ring/Ulysses core does not. "
            "Avoid cp/ulysses layers for this model")

    def proj(a, w):
        return jnp.einsum("bsh,hf->bsf", a, weight_view(w, compute_dtype),
                          preferred_element_type=f32).astype(compute_dtype)

    with jax.named_scope("attn/latent_proj"):
        xc = x.astype(compute_dtype)
        q = (proj(xc, p["wq"]) if "wq" in p else
             proj(apply_norm(p["q_norm"], proj(xc, p["wq_a"]), cfg),
                  p["wq_b"])).reshape(B, S, nq, dn + dr)
        ckv, k_rope = jnp.split(proj(xc, p["wkv_a"]), [rkv], axis=-1)
        kv = proj(apply_norm(p["kv_norm"], ckv, cfg),
                  p["wkv_b"]).reshape(B, S, nq, dn + dv)
        q_nope, q_rope = jnp.split(q, [dn], axis=-1)
        k_nope, v = jnp.split(kv, [dn], axis=-1)
        k_rope = k_rope[:, :, None, :]
    if rope is not None:
        cos, sin = rope
        with jax.named_scope("attn/rope"):
            q_rope = apply_rope(q_rope, cos, sin)
            k_rope = apply_rope(k_rope, cos, sin)
    with jax.named_scope("attn/latent_proj"):
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, S, nq, dr))], axis=-1)
    kwargs: Dict[str, Any] = {"scale": latent_softmax_scale(cfg)}
    if dropout_rng is not None and cfg.attention_dropout > 0.0:
        kwargs.update(dropout_rate=cfg.attention_dropout,
                      dropout_rng=dropout_rng)
    if segment_ids is not None:
        kwargs["segment_ids"] = segment_ids
    with jax.named_scope("attn/core"):
        out = sdpa_fn(q, k, v, causal=causal, **kwargs)
    with jax.named_scope("attn/out_proj"):
        y = jnp.einsum("bsf,fh->bsh", out.reshape(B, S, nq * dv),
                       weight_view(p["wo"], compute_dtype),
                       preferred_element_type=f32)
        return y.astype(compute_dtype)


# ---------------------------------------------------------------------------
# the residual as several streams (manifold-constrained hyper-connections)
# ---------------------------------------------------------------------------


def init_hyper_maps(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Axes]:
    """One sub-layer's maps over ``n = hc_mult`` streams: ``phi`` [n H, n +
    n + n n], its columns ``[pre | post | res]`` (``res`` row-major: entry
    ``i n + j`` feeds stream ``j`` into stream ``i``), ``alpha`` [3] the
    three gates of the token-dependent term and ``bias`` the static term.
    At the start the token-dependent term is small (``alpha`` 0.01), the
    sub-layer reads the mean of the streams (``sigmoid(b_pre) = 1 / n``),
    writes to each with weight one and leaves the streams almost to
    themselves (``b_res`` 0 on the diagonal, -4 off it)."""
    n, h = cfg.hc_mult, cfg.hidden_size
    eye = jnp.eye(n, dtype=jnp.float32)
    bias = jnp.concatenate([
        jnp.full((n,), -math.log(n - 1.0), jnp.float32),
        jnp.zeros((n,), jnp.float32),
        ((1.0 - eye) * -4.0).reshape(n * n)])
    p: Params = {"phi": _normal(key, (n * h, 2 * n + n * n), 0.02),
                 "alpha": jnp.full((3,), 0.01, jnp.float32),
                 "bias": bias}
    a: Axes = {"phi": ("hc_in", "hc_out"), "alpha": ("hc_gate",),
               "bias": ("hc_out",)}
    return p, a


def hyper_maps(p: Params, x: jax.Array, cfg: ModelArgs,
               compute_dtype=jnp.bfloat16
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The three maps of one sub-layer from the streams ``x`` [B, S, n, H]:
    with ``u = RMSNorm(vec(x))`` over all ``n H`` values of a token (no
    learned scale: ``phi`` follows at once), ``pre = sigmoid(a_pre u phi_pre
    + b_pre)`` [n, B, S], ``post = 2 sigmoid(a_post u phi_post + b_post)``
    [n, B, S] and ``res`` [n, n, B, S] = Sinkhorn-Knopp of
    ``exp(clip(a_res mat(u phi_res) + b_res))``: rows, then columns,
    ``hc_sinkhorn_iters`` times, ``hc_eps`` in every divisor. The norm is a
    scalar a token, so it is applied AFTER the product: ``x phi`` runs on the
    streams as they are, in ``compute_dtype`` with float32 accumulation like
    every projection, and everything from there on is float32 with the
    tokens along lanes (a [.., n, n] tail would use 4 lanes of 128)."""
    B, S, n, H = x.shape
    f32 = jnp.float32
    with jax.named_scope("hc/maps"):
        flat = x.reshape(B, S, n * H)
        t = jnp.moveaxis(jnp.einsum(
            "bsk,km->bsm", flat.astype(compute_dtype),
            weight_view(p["phi"], compute_dtype),
            preferred_element_type=f32), -1, 0)
        mean_sq = jnp.mean(jnp.square(flat.astype(f32)), axis=-1)
        t = t * jax.lax.rsqrt(mean_sq + cfg.layernorm_epsilon)
        alpha, bias = p["alpha"], p["bias"][:, None, None]
        pre = jax.nn.sigmoid(alpha[0] * t[:n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * t[n:2 * n] + bias[n:2 * n])
        res = (alpha[2] * t[2 * n:] + bias[2 * n:]).reshape(n, n, B, S)
        res = jnp.exp(jnp.clip(res, cfg.hc_res_clamp_min,
                               cfg.hc_res_clamp_max))
        for _ in range(cfg.hc_sinkhorn_iters):
            res = res / (jnp.sum(res, axis=1, keepdims=True) + cfg.hc_eps)
            res = res / (jnp.sum(res, axis=0, keepdims=True) + cfg.hc_eps)
    return pre, post, res


def _streams_f32(x: jax.Array):
    return [x[:, :, j].astype(jnp.float32) for j in range(x.shape[2])]


@jax.custom_vjp
def _mix(res: jax.Array, post: jax.Array, x: jax.Array,
         y: jax.Array) -> jax.Array:
    xs, yf = _streams_f32(x), y.astype(jnp.float32)
    return jnp.stack(
        [sum(res[i, j][..., None] * xj for j, xj in enumerate(xs))
         + post[i][..., None] * yf for i in range(len(xs))],
        axis=2).astype(x.dtype)


def _mix_bwd(saved, g):
    res, post, x, y = saved
    gs, xs, yf = _streams_f32(g), _streams_f32(x), y.astype(jnp.float32)
    d_res = jnp.stack([jnp.stack([jnp.sum(gi * xj, axis=-1) for xj in xs])
                       for gi in gs])
    d_post = jnp.stack([jnp.sum(gi * yf, axis=-1) for gi in gs])
    d_x = jnp.stack([sum(res[i, j][..., None] * gi
                         for i, gi in enumerate(gs))
                     for j in range(len(xs))], axis=2)
    d_y = sum(post[i][..., None] * gi for i, gi in enumerate(gs))
    return d_res, d_post, d_x.astype(x.dtype), d_y.astype(y.dtype)


_mix.defvjp(lambda res, post, x, y: (_mix(res, post, x, y),
                                     (res, post, x, y)), _mix_bwd)


def hyper_collect(pre: jax.Array, x: jax.Array) -> jax.Array:
    """What the sub-layer reads: ``sum_j pre_j x_j`` [B, S, H], float32
    arithmetic on the streams as they are read, the result in their dtype.
    Plain reverse mode: a backward pass written out for it moved time from
    the backward to the recomputed pass and none off the step (PERF.md
    section 6, PR 40)."""
    with jax.named_scope("hc/mix"):
        return sum(p[..., None] * xj for p, xj in zip(pre, _streams_f32(x))
                   ).astype(x.dtype)


def hyper_mix(res: jax.Array, post: jax.Array, x: jax.Array,
              y: jax.Array) -> jax.Array:
    """``x'_i = sum_j res_ij x_j + post_i y``: the streams mixed among
    themselves, plus the sub-layer's output written to each. Float32
    arithmetic on the streams as they are read, the result in their dtype;
    the backward pass written out likewise (``_mix_bwd``)."""
    with jax.named_scope("hc/mix"):
        return _mix(res, post, x, y)


def residual(hc: Optional[Params], x: jax.Array, cfg: ModelArgs,
             branch: Callable[[jax.Array], jax.Array],
             compute_dtype=jnp.bfloat16) -> jax.Array:
    """One sub-layer around the residual. ``hc`` None (one stream, ``x``
    [B, S, H]): ``x + branch(x)``. With a sub-layer's maps (``x`` [B, S, n,
    H]): ``res x + post (x) branch(pre x)``. ``branch`` holds the
    sub-layer's own input norm."""
    if hc is None:
        return x + branch(x)
    pre, post, res = hyper_maps(hc, x, cfg, compute_dtype)
    return hyper_mix(res, post, x, branch(hyper_collect(pre, x)))


def streams_in(x: jax.Array, cfg: ModelArgs) -> jax.Array:
    """[B, S, H] copied to ``hc_mult`` streams [B, S, n, H] where the model
    has several; as it is otherwise."""
    if cfg.hc_mult <= 1:
        return x
    B, S, H = x.shape
    return jnp.broadcast_to(x[:, :, None, :], (B, S, cfg.hc_mult, H))


def streams_out(x: jax.Array, cfg: ModelArgs) -> jax.Array:
    """The streams summed back to [B, S, H] (arXiv:2409.19606 leaves the
    stack so)."""
    if cfg.hc_mult <= 1:
        return x
    return jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)


def init_block_maps(key: jax.Array, cfg: ModelArgs
                    ) -> Tuple[Params, Axes]:
    """A block's two sets of maps (``hc1`` around the mixer, ``hc2`` around
    the feed-forward; ``hc1`` alone for a block of one branch), drawn from
    the block's key folded once more so that the block's other leaves are
    what they are without them; empty for a model of one stream."""
    if cfg.hc_mult <= 1:
        return {}, {}
    k1, k2 = jax.random.split(jax.random.fold_in(key, 2))
    p1, a1 = init_hyper_maps(k1, cfg)
    if cfg.one_branch_blocks:
        return {"hc1": p1}, {"hc1": a1}
    p2, a2 = init_hyper_maps(k2, cfg)
    return {"hc1": p1, "hc2": p2}, {"hc1": a1, "hc2": a2}


# ---------------------------------------------------------------------------
# gated short convolution (a mixer that is no attention)
# ---------------------------------------------------------------------------


def init_short_conv(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Axes]:
    """LFM2's conv operator (HF ``Lfm2ShortConv``): ``win`` holds the three
    thirds of ``in_proj`` (B, C, X) as ``[3, H, C]``, ``taps`` the depthwise
    kernel ``[C, L]`` (``conv.conv.weight[:, 0, :]``), ``wout`` is
    ``out_proj``. A depthwise convolution is per channel, so the channel
    axis of every third, of the taps and of ``wout``'s rows shards over tp
    together and nothing between the two projections leaves its shard."""
    if cfg.conv_bias:
        raise NotImplementedError(
            "model.conv_bias: the conv operator is written without biases "
            "(LFM2 publishes conv_bias false)")
    h, L = cfg.hidden_size, cfg.conv_L_cache
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    p: Params = {
        "win": _normal(k1, (3, h, h), std),
        # the variance of torch's Conv1d default, U(+-1/sqrt(L)): at 0.02
        # the operator's output would vanish beside the residual
        "taps": _normal(k2, (h, L), 1.0 / math.sqrt(3 * L)),
        "wout": _normal(k3, (h, h), std / math.sqrt(2 * cfg.num_hidden_layers)),
    }
    a: Axes = {"win": ("conv_gate", "embed", "mlp"),
               "taps": ("mlp", "conv_tap"),
               "wout": ("mlp", "embed")}
    return p, a


def causal_depthwise_conv(
    u: jax.Array,
    taps: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    pre: Optional[jax.Array] = None,
    post: Optional[jax.Array] = None,
    silu: bool = False,
    head_norm: Optional[Tuple[int, float, Tuple[Optional[float], ...]]] = None,
    out_dtype=jnp.float32,
    conv_fn: Optional[Callable[..., Optional[jax.Array]]] = None,
    scope: str = "",
) -> jax.Array:
    """``c[t] = sum_j taps[:, j] * x[t - (L - 1 - j)]`` a channel, causal,
    zeros before the sequence, with what its callers do on both sides of it,
    all in float32: ``x = pre * u`` (``u``, ``pre``, ``post`` [B, S, C] in
    any dtype, ``taps`` [C, L]), ``c + bias``, SiLU, ``head_norm`` = (lanes
    a head, epsilon, a scale for each equal part of the channels or None):
    a head of a part with a scale is divided by its L2 norm and multiplied
    by the scale; then ``post *``, and the cast to ``out_dtype``.

    ``conv_fn`` (the Pallas kernels of ``ops/pallas/conv.py``, handed down
    by who knows the devices, ``parallel/spmd.attention_overrides``) runs
    the whole of it as one pass over HBM a direction where the shapes fit
    its tiles (it answers None where they do not); ``scope`` is the
    ``jax.named_scope`` path of the caller, for its backward. Otherwise, and
    on a CPU, the ``jax.numpy`` form: ``L`` padded, shifted products."""
    if conv_fn is not None:
        out = conv_fn(u, taps, bias, pre=pre, post=post, silu=silu,
                      head_norm=head_norm, out_dtype=out_dtype, scope=scope)
        if out is not None:
            return out
    f32 = jnp.float32
    S, L = u.shape[1], taps.shape[1]
    x = u.astype(f32) if pre is None else pre.astype(f32) * u.astype(f32)
    taps = taps.astype(f32)
    c = x * taps[:, L - 1]
    for back in range(1, L):
        # x[t - back]: zeros before the sequence
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :S]
        c = c + shifted * taps[:, L - 1 - back]
    if bias is not None:
        c = c + bias
    if silu:
        c = jax.nn.silu(c)
    if head_norm is not None:
        head, eps, scales = head_norm
        heads = c.reshape(c.shape[:2] + (len(scales), -1, head))
        r = jax.lax.rsqrt(
            jnp.sum(jnp.square(heads), axis=-1, keepdims=True) + eps)
        c = jnp.stack(
            [heads[:, :, i] if scale is None
             else heads[:, :, i] * (r[:, :, i] * scale)
             for i, scale in enumerate(scales)], axis=2).reshape(c.shape)
    if post is not None:
        c = post.astype(f32) * c
    return c.astype(out_dtype)


def apply_short_conv(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    compute_dtype=jnp.bfloat16,
    shard_fn: Optional[Callable[[jax.Array, int], jax.Array]] = None,
    conv_fn: Optional[Callable[..., Optional[jax.Array]]] = None,
) -> jax.Array:
    """``[B, C, X] = split3(x W_in)``; ``u = B * X``; ``c[t] = sum_j
    taps[:, j] * u[t - (L - 1 - j)]``, causal, zero history before the
    sequence; ``(C * c) W_out``. No softmax, no positions. The two
    projections run in ``compute_dtype`` with float32 accumulation; the
    gates and the taps between them are one elementwise pass in float32
    (:func:`causal_depthwise_conv`, which takes ``conv_fn``)."""
    with jax.named_scope("mixer/short_conv"):
        with jax.named_scope("in_proj"):
            bcx = jnp.einsum("bsh,ghc->gbsc", x.astype(compute_dtype),
                             p["win"].astype(compute_dtype),
                             preferred_element_type=jnp.float32
                             ).astype(compute_dtype)
            thirds = [bcx[g] for g in range(3)]
            if shard_fn is not None:
                thirds = [shard_fn(t, 2) for t in thirds]
        with jax.named_scope("gate_conv"):
            gate_b, gate_c, xs = thirds
            y = causal_depthwise_conv(
                xs, p["taps"], pre=gate_b, post=gate_c,
                out_dtype=compute_dtype, conv_fn=conv_fn,
                scope="mixer/short_conv/gate_conv")
            if shard_fn is not None:
                y = shard_fn(y, 2)
        with jax.named_scope("out_proj"):
            out = jnp.einsum("bsc,ch->bsh", y,
                             p["wout"].astype(compute_dtype),
                             preferred_element_type=jnp.float32)
    return out.astype(compute_dtype)


# ---------------------------------------------------------------------------
# Mamba-2 state-space block (a mixer that carries a state)
# ---------------------------------------------------------------------------

# float32 bytes of the decay matrix (chunks x heads x chunk x chunk) that
# one call of the intra-chunk part may hold: the chunks of a sequence are
# taken in groups of at most this much, one group at a time, and the
# backward pass makes each group's matrix again. Whole, one 8192-token
# sequence's is 64 heads x 32 chunks x 256 x 256 x 4 bytes = 512 MiB, and
# the backward holds several tensors of that shape at once
SSD_DECAY_BYTES = 64 * 2 ** 20


def init_mamba2(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Axes]:
    """HF ``GraniteMoeHybridMambaLayer`` (Bamba's Mamba-2 mixer): ``win`` is
    ``in_proj`` with its columns ``[z | x | B | C | dt]``, ``taps`` and
    ``conv_bias`` the depthwise kernel ``[x | B | C channels, taps]``
    (``conv1d.weight[:, 0, :]``) and its bias, ``dt_bias``, ``A_log`` and
    ``D`` one value a head, ``norm`` the gated RMSNorm's scale over all
    ``mamba_d_inner`` channels, ``wout`` is ``out_proj``. ``B`` and ``C``
    are ``mamba_n_groups`` groups of ``mamba_d_state`` columns each, group
    by group (Granite-4.0-H publishes one group, Nemotron-H eight); head
    ``j`` reads group ``j // (heads / groups)``. No leaf carries an
    axis name that tensor parallelism shards: a plan with tp > 1 over a
    mamba block is refused by name (``eligibility.mamba_plan_reason``).

    ``dt_bias``, ``A_log`` and ``D`` start as Mamba-2 starts them
    (state-spaces/mamba ``Mamba2.__init__``): ``dt`` log-uniform in [1e-3,
    1e-1] and ``dt_bias`` its inverse softplus, ``A`` uniform in [1, 16),
    ``D`` one. (A fresh HF module holds ``dt_bias`` 1 and ``A`` = 1..heads,
    under which a head forgets within a token or two.)"""
    if cfg.mamba_n_heads <= 0:
        raise ValueError("a mamba block needs model.mamba_n_heads > 0")
    if cfg.mamba_n_groups < 1 or cfg.mamba_n_heads % cfg.mamba_n_groups:
        raise ValueError(
            f"model.mamba_n_groups={cfg.mamba_n_groups} does not divide "
            f"the {cfg.mamba_n_heads} heads of a mamba block")
    if cfg.mamba_proj_bias:
        raise NotImplementedError(
            "model.mamba_proj_bias: the mamba block's projections are "
            "written without biases (Granite-4.0-H and Nemotron-H publish "
            "false)")
    h, nh, L = cfg.hidden_size, cfg.mamba_n_heads, cfg.mamba_d_conv
    di, cd = cfg.mamba_d_inner, cfg.mamba_conv_dim
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    std = 0.02
    dt = jnp.exp(jax.random.uniform(k4, (nh,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    p: Params = {
        "win": _normal(k1, (h, di + cd + nh), std),
        # the variance of torch's Conv1d default, as the conv block's taps
        "taps": _normal(k2, (cd, L), 1.0 / math.sqrt(3 * L)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k5, (nh,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((nh,), jnp.float32),
        "norm": {"scale": jnp.ones((di,), jnp.float32)},
        "wout": _normal(k3, (di, h), std / math.sqrt(2 * cfg.num_hidden_layers)),
    }
    a: Axes = {"win": ("embed", "mamba_proj"),
               "taps": ("mamba_conv", "conv_tap"),
               "dt_bias": ("mamba_head",), "A_log": ("mamba_head",),
               "D": ("mamba_head",),
               "norm": {"scale": ("mamba_inner",)},
               "wout": ("mamba_inner", "embed")}
    if cfg.mamba_conv_bias:
        p["conv_bias"] = jnp.zeros((cd,), jnp.float32)
        a["conv_bias"] = ("mamba_conv",)
    return p, a


def most_chunks_that_fit(chunks: int, fit: int) -> int:
    """The largest divisor of ``chunks`` that is at most ``fit`` (at least
    one): how many chunks of a sequence a scan's intra-chunk part takes at
    once."""
    return max(d for d in range(1, chunks + 1)
               if chunks % d == 0 and d <= max(1, fit))


def ssd_chunks_a_group(batch: int, chunks: int, heads: int,
                       chunk: int) -> int:
    """How many chunks the intra-chunk part takes at once: the most that
    divide ``chunks`` and whose decay matrices fit ``SSD_DECAY_BYTES``. A
    function of shapes alone."""
    return most_chunks_that_fit(
        chunks, SSD_DECAY_BYTES // (batch * heads * chunk * chunk * 4))


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, chunk: int,
                compute_dtype=jnp.bfloat16,
                scan_fn: Optional[Callable[..., jax.Array]] = None,
                groups: int = 1) -> jax.Array:
    """The selective state-space recurrence of Mamba-2 in its chunked,
    matmul form (Dao & Gu 2024, "SSD"). Per head, with state ``S`` [P, N],
    zero before the sequence::

        S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T ;   y_t = S_t C_t

    ``x`` [B, S, H, P]; ``dt`` [B, S, H] float32, after softplus; ``A`` [H]
    float32, negative; ``Bm``, ``Cm`` [B, S, groups * N], group by group:
    head ``j`` reads group ``j // (H / groups)`` (one group: shared by the
    heads). Returns ``y`` [B, S, H, P] float32 (without the ``D x`` skip).

    With ``cs`` the running sum of ``dt A`` inside a chunk of ``chunk``
    positions: inside a chunk ``y_i += sum_(j<=i) (C_i . B_j) exp(cs_i -
    cs_j) dt_j x_j`` (one matmul ``C B^T``, one decay matrix ``L``, one
    matmul ``(C B^T * L) X`` a head); a chunk leaves the state ``sum_j
    exp(cs_last - cs_j) dt_j x_j B_j^T``; the states are carried from chunk
    to chunk by a scan (``S <- exp(cs_last) S + the chunk's``); and the
    state ENTERING a chunk adds ``exp(cs_i) C_i . S``. Sums, decays and the
    carried state are float32; the matmul operands are ``compute_dtype``
    with float32 accumulation. A sequence that ``chunk`` does not divide is
    padded with ``dt = 0`` (no decay, no input), and the padding cut off.

    One algorithm run one of two ways, by what the caller hands in and the
    shapes alone. ``scan_fn`` (the Pallas kernels of ``ops/pallas/ssd.py``,
    which whoever knows the devices hands down:
    ``parallel/spmd.attention_overrides``) runs where the shapes fit its
    tiles (``ssd.tile_plan``): the decay matrix, ``C B^T * L`` and the
    carried state then live in VMEM. Otherwise it is ``jax.numpy``, the
    chunks in groups whose decay matrices fit ``SSD_DECAY_BYTES``, each
    group's made again in the backward pass. The heads of different groups
    of B and C share nothing, so the ``jax.numpy`` form takes several
    groups as further batch rows of one group each (B and C are never
    copied a head); the kernels index a head's group themselves."""
    from hetu_galvatron_tpu.ops.pallas.ssd import tile_plan

    f32 = jnp.float32
    B_, S, H, P = x.shape
    pad = -S % chunk
    if pad:
        x, dt, Bm, Cm = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, Bm, Cm))
    Q, nC = chunk, (S + pad) // chunk
    if scan_fn is not None and tile_plan(
            Q, H, P, Bm.shape[-1] // groups, groups) is not None:
        return scan_fn(x.astype(compute_dtype), dt, A,
                       Bm.astype(compute_dtype), Cm.astype(compute_dtype),
                       Q, groups=groups)[:, :S]
    if groups > 1:
        def fold(t):    # [B, S, G * k, ...] -> [B * G, S, k, ...]
            t = t.reshape((B_, t.shape[1], groups, -1) + t.shape[3:])
            t = jnp.moveaxis(t, 2, 1)
            return t.reshape((B_ * groups,) + t.shape[2:])

        y = ssd_chunked(
            fold(x), fold(dt),
            jnp.tile(A.reshape(groups, 1, 1, -1), (B_, 1, 1, 1)),
            fold(Bm), fold(Cm), chunk, compute_dtype)
        y = jnp.moveaxis(y.reshape((B_, groups) + y.shape[1:]), 1, 2)
        return y.reshape(B_, nC * Q, H, P)[:, :S]
    size = ssd_chunks_a_group(B_, nC, H, Q)
    parts = nC // size

    def grouped(t):   # [B, S, ...] -> [parts, B, size, Q, ...]
        return jnp.moveaxis(
            t.reshape((B_, parts, size, Q) + t.shape[2:]), 1, 0)

    def whole(t):     # [parts, B, size, ...] -> [B, nC, ...]
        t = jnp.moveaxis(t, 0, 1)
        return t.reshape((B_, nC) + t.shape[3:])

    tril = jnp.tril(jnp.ones((Q, Q), bool))

    def intra(args):
        xg, dtg, bg, cg = args   # [B, size, Q, H, P] [.., H] [.., N] [.., N]
        cs = jnp.cumsum(jnp.swapaxes(dtg * A, 2, 3), axis=-1)  # [B,c,H,Q]
        # L[i, j] = exp(cs_i - cs_j) for j <= i: masked BEFORE the exp,
        # above the diagonal cs_i - cs_j is positive and may overflow
        L = jnp.exp(jnp.where(tril, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))
        G = jnp.einsum("bcin,bcjn->bcij", cg, bg, preferred_element_type=f32)
        M = (G[:, :, None] * L).astype(compute_dtype)        # [B,c,H,Q,Q]
        xf = xg.astype(f32)
        xdt = (xf * dtg[..., None]).astype(compute_dtype)
        y = jnp.einsum("bchij,bcjhp->bcihp", M, xdt,
                       preferred_element_type=f32)
        to_end = jnp.swapaxes(jnp.exp(cs[..., -1:] - cs), 2, 3)  # [B,c,Q,H]
        xw = (xf * (dtg * to_end)[..., None]).astype(compute_dtype)
        st = jnp.einsum("bcjhp,bcjn->bchpn", xw, bg,
                        preferred_element_type=f32)
        return y, st, cs

    y, states, cs = map(whole, jax.lax.map(
        jax.checkpoint(intra), tuple(grouped(t) for t in (x, dt, Bm, Cm))))

    def carry(state, chunk_of):
        st, decay = chunk_of
        return decay[..., None, None] * state + st, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros(states.shape[:1] + states.shape[2:], f32),
        (jnp.moveaxis(states, 1, 0),
         jnp.moveaxis(jnp.exp(cs[..., -1]), 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)             # [B, nC, H, P, N]
    y_off = jnp.einsum("bcin,bchpn->bcihp",
                       Cm.reshape(B_, nC, Q, -1).astype(compute_dtype),
                       entering.astype(compute_dtype),
                       preferred_element_type=f32)
    y = y + y_off * jnp.swapaxes(jnp.exp(cs), 2, 3)[..., None]
    return y.reshape(B_, nC * Q, H, P)[:, :S]


def mamba_gated_norm(y: jax.Array, x: jax.Array, z: jax.Array, D: jax.Array,
                     scale: jax.Array, groups: int, eps: float,
                     out_dtype=jnp.bfloat16,
                     norm_fn: Optional[Callable[..., Optional[jax.Array]]]
                     = None) -> jax.Array:
    """What lies between a Mamba-2 block's scan and its ``out_proj``: the
    skip ``u = y + D x`` (``D`` one number a head), the gate ``a = u *
    silu(z)`` and ``RMSNorm(a) * scale``, the mean square over each of the
    ``groups`` groups of channels; ``y`` [B, S, C] float32, ``x`` and ``z``
    [B, S, C], all float32 inside, ``out_dtype`` out.

    One algorithm run one of two ways, by what the caller hands in and the
    shapes alone. ``norm_fn`` (the Pallas kernels of
    ``ops/pallas/gated_norm.py``, which whoever knows the devices hands
    down: ``parallel/spmd.attention_overrides``) is ONE pass over the rows
    as they lie, a group a range of whole lane tiles, with a backward of
    its own that keeps no float32 value: one path for every group count
    (PERF.md section 6, PR 75: at eight groups ``jax.numpy`` took twice the
    time and 20 ms a step more in relayouts; at one group, which XLA had
    fused into its neighbours, the step is level). Where it
    answers None (a group that is no whole number of lane tiles) or none is
    handed in, it is ``jax.numpy``, a group a minor dimension of its own:
    on a TPU that view and the view back are a relayout each."""
    if norm_fn is not None:
        out = norm_fn(y, x, z, D, scale, groups=groups, eps=eps,
                      out_dtype=out_dtype, scope="mixer/mamba/gated_norm")
        if out is not None:
            return out
    B, S, C = y.shape
    f32 = jnp.float32
    y = y + jnp.repeat(D, C // D.shape[0]) * x.astype(f32)
    y = y * jax.nn.silu(z.astype(f32))
    if groups > 1:   # the mean square a group of channels
        y = y.reshape(B, S, groups, C // groups)
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + eps)
    if groups > 1:
        y = y.reshape(B, S, C)
    return (y * scale).astype(out_dtype)


def apply_mamba2(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    compute_dtype=jnp.bfloat16,
    ssd_fn: Optional[Callable[..., jax.Array]] = None,
    conv_fn: Optional[Callable[..., Optional[jax.Array]]] = None,
    norm_fn: Optional[Callable[..., Optional[jax.Array]]] = None,
) -> jax.Array:
    """``[z | xBC | dt] = x W_in``; ``xBC = silu(conv1d_causal(xBC) + b)``
    (depthwise, ``mamba_d_conv`` taps, zero history before the sequence);
    ``[x | B | C] = xBC``, B and C ``mamba_n_groups`` groups of
    ``mamba_d_state``; ``dt = softplus(dt + dt_bias)``, ``A =
    -exp(A_log)``; ``y = SSD(x, dt, A, B, C) + D x``
    (:func:`ssd_chunked`); ``y = RMSNorm(y * silu(z)) * w``, the mean
    square over each group's ``mamba_d_inner / mamba_n_groups`` channels
    (one group: over all channels; :func:`mamba_gated_norm`, which adds the
    skip too); ``y W_out``. No softmax, no positions. The two projections
    and the recurrence's matmuls run in ``compute_dtype`` with float32
    accumulation; ``dt``, the decays, the state, the convolution and the
    gated norm are float32. ``ssd_fn``: the kernels for the
    recurrence, where the caller's devices run them
    (:func:`ssd_chunked`'s ``scan_fn``), ``conv_fn`` those for the
    convolution, its bias and SiLU (:func:`causal_depthwise_conv`) and
    ``norm_fn`` those for the skip and the gated norm."""
    B, S, _ = x.shape
    nh, hp = cfg.mamba_n_heads, cfg.mamba_d_head
    G, GN = cfg.mamba_n_groups, cfg.mamba_n_groups * cfg.mamba_d_state
    di, f32 = cfg.mamba_d_inner, jnp.float32
    with jax.named_scope("mixer/mamba"):
        with jax.named_scope("in_proj"):
            proj = jnp.einsum("bsh,hc->bsc", x.astype(compute_dtype),
                              p["win"].astype(compute_dtype),
                              preferred_element_type=f32)
            z, xbc, dt = jnp.split(proj, [di, di + cfg.mamba_conv_dim],
                                   axis=-1)
            z, xbc = z.astype(compute_dtype), xbc.astype(compute_dtype)
        with jax.named_scope("conv"):
            xs, Bm, Cm = jnp.split(causal_depthwise_conv(
                xbc, p["taps"], p.get("conv_bias"), silu=True,
                out_dtype=compute_dtype, conv_fn=conv_fn,
                scope="mixer/mamba/conv"), [di, di + GN], axis=-1)
        with jax.named_scope("ssd"):
            dt = jax.nn.softplus(dt + p["dt_bias"])
            y = ssd_chunked(xs.reshape(B, S, nh, hp), dt,
                            -jnp.exp(p["A_log"].astype(f32)), Bm, Cm,
                            cfg.mamba_chunk_size, compute_dtype,
                            scan_fn=ssd_fn, groups=G)
        with jax.named_scope("gated_norm"):
            # a head is hp of a row's lanes, here as in the kernels: a
            # [.., heads, hp] view of a row is no bitcast on a TPU
            y = mamba_gated_norm(
                y.reshape(B, S, di), xs, z, p["D"], p["norm"]["scale"], G,
                cfg.layernorm_epsilon, compute_dtype, norm_fn)
        with jax.named_scope("out_proj"):
            out = jnp.einsum("bsc,ch->bsh", y,
                             p["wout"].astype(compute_dtype),
                             preferred_element_type=f32)
    return out.astype(compute_dtype)


# ---------------------------------------------------------------------------
# Mamba-1 selective scan (a state a channel, decayed a channel and state
# index), and the gated memory unit that reads one block's scan output
# ---------------------------------------------------------------------------


def init_mamba1(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Axes]:
    """HF ``MambaMixer`` (state-spaces/mamba ``Mamba``): ``win`` is
    ``in_proj`` with its columns ``[u | z]``, ``taps`` / ``conv_bias`` the
    depthwise kernel (``conv1d.weight[:, 0, :]``) and its bias, ``wx`` is
    ``x_proj`` (columns ``[dt bottleneck | B | C]``), ``wdt`` / ``dt_bias``
    are ``dt_proj``, ``A_log`` [channels, state] and ``D`` [channels],
    ``wout`` is ``out_proj``; no bias on ``in_proj``, ``x_proj`` and
    ``out_proj``. No leaf carries an axis name that tensor parallelism
    shards: a plan with tp > 1 over such a block is refused by name
    (``eligibility.mamba1_plan_reason``).

    ``dt_bias``, ``A_log``, ``D`` and ``wdt`` start as state-spaces/mamba
    starts them: ``dt`` log-uniform in [1e-3, 1e-1] and ``dt_bias`` its
    inverse softplus, ``A = 1..d_state`` in every channel, ``D`` one,
    ``wdt`` uniform in +-rank^-0.5."""
    h, di, N = cfg.hidden_size, cfg.mamba1_d_inner, cfg.mamba1_d_state
    R, L = cfg.mamba1_rank, cfg.mamba1_d_conv
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    std = 0.02
    dt = jnp.exp(jax.random.uniform(k5, (di,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    p: Params = {
        "win": _normal(k1, (h, 2 * di), std),
        # the variance of torch's Conv1d default, as the conv block's taps
        "taps": _normal(k2, (di, L), 1.0 / math.sqrt(3 * L)),
        "conv_bias": jnp.zeros((di,), jnp.float32),
        "wx": _normal(k3, (di, R + 2 * N), std),
        "wdt": jax.random.uniform(k4, (R, di), jnp.float32,
                                  -R ** -0.5, R ** -0.5),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, N + 1, dtype=jnp.float32), (di, N))),
        "D": jnp.ones((di,), jnp.float32),
        "wout": _normal(k6, (di, h), std / math.sqrt(2 * cfg.num_hidden_layers)),
    }
    a: Axes = {"win": ("embed", "mamba1_proj"),
               "taps": ("mamba1_inner", "conv_tap"),
               "conv_bias": ("mamba1_inner",),
               "wx": ("mamba1_inner", "mamba1_low"),
               "wdt": ("mamba1_low", "mamba1_inner"),
               "dt_bias": ("mamba1_inner",),
               "A_log": ("mamba1_inner", "mamba1_state"),
               "D": ("mamba1_inner",),
               "wout": ("mamba1_inner", "embed")}
    return p, a


def _selective_chunk(state, u, dt, Bm, Cm, At):
    """One chunk of :func:`selective_scan`, a position at a time with the
    loop unrolled whole: ``state`` [B, N, C], ``u`` and ``dt`` [B, Q, C],
    ``Bm`` and ``Cm`` [B, Q, N], ``At`` [N, C] -> (the state after the
    chunk, ``y`` [B, Q, C])."""
    def step(s, at):
        u_t, dt_t, b_t, c_t = at
        s = (jnp.exp(dt_t[:, None, :] * At) * s
             + (dt_t * u_t)[:, None, :] * b_t[..., None])
        return s, jnp.sum(s * c_t[..., None], axis=1)

    state, y = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(t, 1, 0) for t in (u, dt, Bm, Cm)),
        unroll=u.shape[1])
    return state, jnp.moveaxis(y, 0, 1)


# positions of a chunk of :func:`selective_scan`, all of them unrolled: the
# fastest of the ``jax.numpy`` forms probed on a v5e (PR 61; the numbers are
# below), and what bounds the backward pass's memory. A TPU runs the
# kernels of ``ops/pallas/selective_scan.py`` (PR 64), whose chunk is their
# own; this is the chunk of the oracle, of the CPU and of shapes no tile fits
SELECTIVE_CHUNK = 16


def selective_scan(u: jax.Array, dt: jax.Array, A: jax.Array,
                   Bm: jax.Array, Cm: jax.Array,
                   chunk: int = SELECTIVE_CHUNK) -> jax.Array:
    """Mamba-1's recurrence (Gu & Dao 2023). A channel ``c`` carries a state
    ``s`` of ``N`` values, zero before the sequence::

        s_t[c] = exp(dt_t[c] A[c]) * s_(t-1)[c] + dt_t[c] u_t[c] B_t
        y_t[c] = s_t[c] . C_t

    ``u`` [B, S, C]; ``dt`` [B, S, C], after softplus; ``A`` [C, N],
    negative; ``Bm``, ``Cm`` [B, S, N], shared by the channels. Returns
    ``y`` [B, S, C] float32 (without the ``D u`` skip). Everything float32.

    This is the oracle of the recurrence, the form the CPU runs and the form
    of shapes that fit no tile of the kernels; on a TPU a ``mamba1`` block
    runs ``ops/pallas/selective_scan.py`` (``apply_mamba1``'s ``scan_fn``),
    which keeps the state in VMEM and is held to this function by
    ``tests/kernels/test_selective_scan_kernel.py``.

    The decay is a number a channel AND state index, so no chunked matmul
    form exists (Mamba-2's is one scalar a head): this is 2 ``C N``
    multiply-adds a position on the vector unit. A ``lax.scan`` over chunks
    of ``chunk`` positions carries the state [B, N, C] (the channels along
    lanes); inside a chunk the positions run one after another, unrolled, so
    that a chunk is one fused loop body whose operands are the chunk's rows
    of ``u``, ``dt``, ``B`` and ``C`` and never ``[S, C, N]``; the chunk's
    body is rematerialized, so the backward pass holds the states that
    entered the chunks (``S / chunk`` of them) and one chunk's
    intermediates. (On a v5e at [1, 8192, 5120] x 16, forward and backward:
    41 ms so at 16 positions a chunk (6.4 ms forward alone), 78 ms at 64 not
    unrolled, 56 ms and 182 ms with ``lax.associative_scan`` inside chunks
    of 16 and 64; PERF.md section 6, PR 61: the baseline the kernels of
    PR 64 are measured against.) A sequence that ``chunk`` does not divide is
    padded with ``dt = 0`` (no decay, no input) and the padding cut off."""
    f32 = jnp.float32
    B_, S, C = u.shape
    pad = -S % chunk
    u, dt, Bm, Cm = (jnp.pad(t.astype(f32), ((0, 0), (0, pad), (0, 0)))
                     for t in (u, dt, Bm, Cm))
    nC = (S + pad) // chunk

    def chunks(t):    # [B, S, W] -> [nC, B, chunk, W]
        return jnp.moveaxis(t.reshape(B_, nC, chunk, t.shape[-1]), 1, 0)

    At = A.astype(f32).T
    body = jax.checkpoint(_selective_chunk)
    _, y = jax.lax.scan(
        lambda s, at: body(s, *at, At),
        jnp.zeros((B_, A.shape[1], C), f32),
        tuple(chunks(t) for t in (u, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1).reshape(B_, nC * chunk, C)[:, :S]


def apply_mamba1(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    compute_dtype=jnp.bfloat16,
    scan_fn: Optional[Callable[..., Optional[jax.Array]]] = None,
    conv_fn: Optional[Callable[..., Optional[jax.Array]]] = None,
    made: Optional[Dict[str, jax.Array]] = None,
) -> jax.Array:
    """``[u | z] = x W_in``; ``u = silu(conv1d_causal(u) + b)`` (depthwise,
    ``mamba1_d_conv`` taps, zero history before the sequence); ``[d | B | C]
    = u W_x``; ``dt = softplus(d W_dt + b_dt)``, ``A = -exp(A_log)``; ``y =
    scan(u, dt, A, B, C) + D u`` (:func:`selective_scan`); ``(y * silu(z))
    W_out``. No softmax, no positions, no norm. The four projections run in
    ``compute_dtype`` with float32 accumulation; ``dt``, the decays, the
    state, the convolution and the gate are float32. ``scan_fn``: the
    kernels for the recurrence, where the caller's devices run them
    (``ops/pallas/selective_scan.py``, handed down by
    ``parallel/spmd.attention_overrides``; it answers None for shapes that
    fit no tile, and :func:`selective_scan` runs), and ``conv_fn`` those for
    the convolution, its bias and SiLU
    (:func:`causal_depthwise_conv`). ``made`` (the block whose scan output
    later blocks read, ``ModelArgs.block_shares``) is written ``memory`` =
    ``y`` [B, S, channels], after the ``D`` skip and before the gate."""
    N, R = cfg.mamba1_d_state, cfg.mamba1_rank
    f32 = jnp.float32

    def proj(a, w):
        return jnp.einsum("bsh,hc->bsc", a.astype(compute_dtype),
                          weight_view(w, compute_dtype),
                          preferred_element_type=f32)

    with jax.named_scope("mixer/mamba1"):
        with jax.named_scope("in_proj"):
            u, z = jnp.split(proj(x, p["win"]).astype(compute_dtype), 2,
                             axis=-1)
        with jax.named_scope("conv"):
            u = causal_depthwise_conv(
                u, p["taps"], p["conv_bias"], silu=True,
                out_dtype=compute_dtype, conv_fn=conv_fn,
                scope="mixer/mamba1/conv")
        with jax.named_scope("x_proj"):
            d, Bm, Cm = jnp.split(proj(u, p["wx"]), [R, R + N], axis=-1)
            dt = jax.nn.softplus(proj(d, p["wdt"]) + p["dt_bias"])
        with jax.named_scope("scan"):
            A = -jnp.exp(p["A_log"].astype(f32))
            y = scan_fn(u, dt, A, Bm, Cm) if scan_fn is not None else None
            if y is None:
                y = selective_scan(u, dt, A, Bm, Cm)
            y = y + p["D"] * u.astype(f32)
        if made is not None:
            made["memory"] = y.astype(compute_dtype)
        with jax.named_scope("gate"):
            y = (y * jax.nn.silu(z.astype(f32))).astype(compute_dtype)
        with jax.named_scope("out_proj"):
            out = proj(y, p["wout"])
    return out.astype(compute_dtype)


def init_gmu(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Axes]:
    """A gated memory unit (arXiv:2507.06607, section 2): ``win`` [hidden,
    memory width] and ``wout`` back, no bias; the memory is a "mamba1"
    block's scan output, ``mamba1_d_inner`` wide."""
    h, di = cfg.hidden_size, cfg.mamba1_d_inner
    k1, k2 = jax.random.split(key)
    std = 0.02
    return ({"win": _normal(k1, (h, di), std),
             "wout": _normal(k2, (di, h),
                             std / math.sqrt(2 * cfg.num_hidden_layers))},
            {"win": ("embed", "mamba1_inner"),
             "wout": ("mamba1_inner", "embed")})


def apply_gmu(p: Params, x: jax.Array, cfg: ModelArgs,
              compute_dtype=jnp.bfloat16, *,
              shared: Dict[str, jax.Array]) -> jax.Array:
    """``(M * silu(x W_in)) W_out`` with ``M = shared["memory"]`` [B, S,
    memory width], an earlier block's scan output: no convolution, no scan,
    no norm on ``M`` or on the product. The gate is float32."""
    f32 = jnp.float32
    with jax.named_scope("mixer/gmu"):
        with jax.named_scope("in_proj"):
            g = jnp.einsum("bsh,hc->bsc", x.astype(compute_dtype),
                           weight_view(p["win"], compute_dtype),
                           preferred_element_type=f32)
        with jax.named_scope("gate"):
            y = (shared["memory"].astype(f32) * jax.nn.silu(g)
                 ).astype(compute_dtype)
        with jax.named_scope("out_proj"):
            out = jnp.einsum("bsc,ch->bsh", y,
                             weight_view(p["wout"], compute_dtype),
                             preferred_element_type=f32)
    return out.astype(compute_dtype)


# ---------------------------------------------------------------------------
# cross-attention over an earlier block's keys and values
# ---------------------------------------------------------------------------


def init_cross_attention(key: jax.Array,
                         cfg: ModelArgs) -> Tuple[Params, Axes]:
    """A block of the second half of a decoder-hybrid-decoder stack
    (arXiv:2405.05254, arXiv:2507.06607): ``wq`` and ``wo`` (biases as
    :func:`init_attention` decides them) and, under differential attention,
    its own ``lambdas`` and ``subln``; it owns no key and no value."""
    h, hd, nq = cfg.hidden_size, cfg.head_dim, cfg.num_attention_heads
    k1, k2 = jax.random.split(key)
    std = 0.02
    p: Params = {
        "wq": _normal(k1, (h, nq * hd), std),
        "wo": _normal(k2, (nq * hd, h),
                      std / math.sqrt(2 * cfg.num_hidden_layers))}
    a: Axes = {"wq": ("embed", "cross_q"), "wo": ("cross_q", "embed")}
    if cfg.add_qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), jnp.float32)
        a["bq"] = ("cross_q",)
    if cfg.add_bias_linear or cfg.add_attn_out_bias:
        p["bo"] = jnp.zeros((h,), jnp.float32)
        a["bo"] = ("embed",)
    if cfg.differential_attention:
        dp, da = init_differential(jax.random.fold_in(key, 3), cfg)
        p.update(dp)
        a.update(da)
    return p, a


def apply_cross_attention(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    rope: Optional[Tuple[jax.Array, jax.Array]] = None,
    sdpa_fn: Callable[..., jax.Array] = xla_sdpa,
    compute_dtype=jnp.bfloat16,
    causal: bool = True,
    dropout_rng: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    *,
    shared: Dict[str, jax.Array],
    lambda_init: Optional[float] = None,
) -> jax.Array:
    """The block's own queries over ``shared["keys"]`` and
    ``shared["values"]`` [B, S, kv heads x head_dim] as an earlier block's
    core read them, causal over the whole span, the core under
    ``attn/cross_core``; differential as :func:`apply_attention`. Of what
    a kind that attends is handed it takes the causal flag alone."""
    if rope is not None or segment_ids is not None or (
            dropout_rng is not None and cfg.attention_dropout > 0.0):
        raise NotImplementedError(
            "a cross_attention block takes no rotation, no packed "
            "documents (segment_ids) and no dropout of probabilities: set "
            "model.position_embedding_type=nope, "
            "data.reset_attention_mask=false, model.attention_dropout=0")
    B, S, _ = x.shape
    hd, nq, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.kv_heads
    f32 = jnp.float32
    with jax.named_scope("attn/qkv_proj"):
        q = jnp.einsum("bsh,hf->bsf", x.astype(compute_dtype),
                       weight_view(p["wq"], compute_dtype),
                       preferred_element_type=f32)
        if "bq" in p:
            q = q + p["bq"]
        q = q.astype(compute_dtype).reshape(B, S, nq, hd)
        k = shared["keys"].reshape(B, S, nkv, hd)
        v = shared["values"].reshape(B, S, nkv, hd)
    if cfg.differential_attention:
        v = pair_values(v)
    with jax.named_scope("attn/cross_core"):
        out = sdpa_fn(q, k, v, causal=causal)
    if cfg.differential_attention:
        out = differential(p, out, cfg, lambda_init)
    with jax.named_scope("attn/out_proj"):
        y = jnp.einsum("bsf,fh->bsh", out.reshape(B, S, nq * hd),
                       weight_view(p["wo"], compute_dtype),
                       preferred_element_type=f32)
        if "bo" in p:
            y = y + p["bo"]
        return y.astype(compute_dtype)


# ---------------------------------------------------------------------------
# Kimi Delta Attention (a mixer that carries a matrix-valued state, decayed a
# channel and updated by the delta rule)
# ---------------------------------------------------------------------------

# positions of a sub-block of a chunk: inside one, the decay between two
# positions is taken element by element; between two, through a reference
# point (:func:`kda_pairs`)
KDA_SUB = 16
# what the L2 norm of a head's q and k adds under its root (fla's l2norm)
KDA_L2_EPS = 1e-6
# float32 bytes of the element-by-element decays (chunks x heads x sub-blocks
# x sub x sub x width) that one call of the intra-chunk part may hold: the
# chunks of a sequence are taken in groups of at most this much, one group
# at a time, and the backward pass makes each group's again. Whole, one
# 8192-token sequence's are 128 chunks x 32 heads x 4 x 16 x 16 x 128 x 4
# bytes = 2 GiB
KDA_PAIR_BYTES = 128 * 2 ** 20
# the float32 products of the triangular inverse: a TPU's default precision
# would round their operands to bfloat16
_HIGHEST = jax.lax.Precision.HIGHEST


def init_kda(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Axes]:
    """The released ``KimiDeltaAttention`` (``modeling_kimi.py``; fla's
    ``layers/kda.py``): ``wqkv`` is ``q_proj | k_proj | v_proj`` side by
    side, ``taps`` the three depthwise kernels ``[q | k | v channels, taps]``
    (``{q,k,v}_conv1d.weight[:, 0, :]``, no bias), ``wlow`` the three narrow
    projections of the input side by side, ``f_a_proj | g_a_proj | b_proj``
    (the decay's and the output gate's bottlenecks of ``kda_head_dim`` and
    ``beta``'s one value a head), ``wf_b`` / ``wg_b`` are ``f_b_proj`` /
    ``g_b_proj`` (no bias, as ``modeling_kimi.py`` has them), ``dt_bias`` one
    value a channel and ``A_log`` one a head, ``norm`` the output norm's
    scale of ``kda_head_dim`` shared by the heads, ``wout`` is ``o_proj``.
    No leaf carries an axis name that tensor parallelism shards: a plan
    with tp > 1 over a kda block is refused by name
    (``eligibility.kda_plan_reason``).

    ``A_log`` and ``dt_bias`` start as the released layer starts them (and
    as Mamba-2 does): ``A`` uniform in [1, 16), ``dt`` log-uniform in [1e-3,
    1e-1] and ``dt_bias`` its inverse softplus, so the strongest decay a
    fresh block draws is ``exp(-16 x 0.1)`` a token and channel."""
    if cfg.kda_num_heads <= 0:
        raise ValueError("a kda block needs model.kda_num_heads > 0")
    if cfg.normalization != "rmsnorm":
        raise ValueError("a kda block's output norm is an RMSNorm "
                         "(normalization=rmsnorm)")
    kda_sub_blocks(cfg.kda_chunk_size)   # raises for a chunk it cannot cut
    h, nh, d = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim
    inner, L = cfg.kda_inner, cfg.kda_conv_kernel
    k1, k2, k3, k4, k5, k6, k7, k8 = jax.random.split(key, 8)
    std = 0.02
    dt = jnp.exp(jax.random.uniform(k7, (inner,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    p: Params = {
        "wqkv": _normal(k1, (h, 3 * inner), std),
        # the variance of torch's Conv1d default, as the conv block's taps
        "taps": _normal(k2, (3 * inner, L), 1.0 / math.sqrt(3 * L)),
        "wlow": _normal(k3, (h, 2 * d + nh), std),
        "wf_b": _normal(k4, (d, inner), std),
        "wg_b": _normal(k5, (d, inner), std),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k8, (nh,), jnp.float32,
                                            1.0, 16.0)),
        "norm": {"scale": jnp.ones((d,), jnp.float32)},
        "wout": _normal(k6, (inner, h),
                        std / math.sqrt(2 * cfg.num_hidden_layers)),
    }
    a: Axes = {"wqkv": ("embed", "kda_proj"),
               "taps": ("kda_proj", "conv_tap"),
               "wlow": ("embed", "kda_low"),
               "wf_b": ("kda_low", "kda_inner"),
               "wg_b": ("kda_low", "kda_inner"),
               "dt_bias": ("kda_inner",), "A_log": ("kda_head",),
               "norm": {"scale": ("kda_width",)},
               "wout": ("kda_inner", "embed")}
    return p, a


def kda_sub_blocks(chunk: int) -> Tuple[int, int]:
    """(positions of a sub-block, sub-blocks a chunk): ``KDA_SUB`` positions
    each, a power of two of them (the triangular inverse joins them in
    pairs); a chunk of at most ``KDA_SUB`` is one."""
    sub = min(KDA_SUB, chunk)
    n = chunk // sub
    if chunk < 1 or chunk % sub or n & (n - 1):
        raise ValueError(
            f"model.kda_chunk_size={chunk}: a chunk is at most {KDA_SUB} "
            f"positions or {KDA_SUB} times a power of two")
    return sub, n


def kda_chunks_a_group(batch: int, chunks: int, heads: int, chunk: int,
                       width: int) -> int:
    """How many chunks the intra-chunk part takes at once: the most that
    divide ``chunks`` and whose element-by-element decays fit
    ``KDA_PAIR_BYTES``. A function of shapes alone."""
    sub, n = kda_sub_blocks(chunk)
    return most_chunks_that_fit(
        chunks, KDA_PAIR_BYTES // (batch * heads * n * sub * sub * width * 4))


def _unit_lower_inverse(N: jax.Array, sub: int) -> jax.Array:
    n = N.shape[-1]
    nb = n // sub
    lead = N.shape[:-2]
    blocks = N.reshape(lead + (nb, sub, nb, sub))
    # the diagonal sub-blocks, all at once, by forward substitution: row i
    # of (I + D)^-1 is e_i - D[i, :i] (I + D)^-1[:i]
    D = jnp.stack([blocks[..., b, :, b, :] for b in range(nb)], axis=-3)
    eye = jnp.eye(sub, dtype=N.dtype)
    X = jnp.broadcast_to(eye, D.shape)
    for i in range(1, sub):
        row = eye[i] - jnp.einsum("...j,...jk->...k", D[..., i, :i],
                                  X[..., :i, :], precision=_HIGHEST)
        X = X.at[..., i, :].set(row)
    # joined in pairs: [[X1, 0], [-X2 N21 X1, X2]]
    m = sub
    while nb > 1:
        blocks = N.reshape(lead + (nb // 2, 2, m, nb // 2, 2, m))
        N21 = jnp.stack([blocks[..., b, 1, :, b, 0, :]
                         for b in range(nb // 2)], axis=-3)
        X1, X2 = X[..., 0::2, :, :], X[..., 1::2, :, :]
        X21 = -jnp.einsum("...ij,...jk,...kl->...il", X2, N21, X1,
                          precision=_HIGHEST)
        X = jnp.concatenate([
            jnp.concatenate([X1, jnp.zeros_like(X1)], axis=-1),
            jnp.concatenate([X21, X2], axis=-1)], axis=-2)
        nb, m = nb // 2, 2 * m
    return X[..., 0, :, :]


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(N: jax.Array, sub: int) -> jax.Array:
    """``(I + N)^-1`` of ``N`` [..., C, C] float32, of which the strictly
    lower triangle is read: the ``sub`` x ``sub`` diagonal blocks by forward
    substitution (as stable as the recurrence it stands for: a Neumann
    product ``(I - N)(I + N^2)(I + N^4)..`` passes through powers whose
    entries cancel), then joined in pairs by block products, ``C / sub`` a
    power of two. Its cotangent is ``-X^T g X^T`` on that triangle."""
    strict = jnp.tril(jnp.ones(N.shape[-2:], bool), -1)
    return _unit_lower_inverse(jnp.where(strict, N, 0.0), sub)


def _unit_lower_inverse_fwd(N, sub):
    X = unit_lower_inverse(N, sub)
    return X, X


def _unit_lower_inverse_bwd(sub, X, g):
    Xt = jnp.swapaxes(X, -1, -2)
    bar = -jnp.einsum("...ij,...jk,...kl->...il", Xt, g, Xt,
                      precision=_HIGHEST)
    strict = jnp.tril(jnp.ones(X.shape[-2:], bool), -1)
    return (jnp.where(strict, bar, 0.0),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def kda_pairs(q: jax.Array, k: jax.Array, G: jax.Array, sub: int,
              compute_dtype=jnp.bfloat16) -> Tuple[jax.Array, jax.Array]:
    """``sum_c a_ic k_jc exp(G_ic - G_jc)`` for ``j <= i`` inside a chunk (0
    above the diagonal), for ``a = q`` and for ``a = k``: ``q``, ``k`` [...,
    C, d], ``G`` [..., C, d] float32, the running sum of the log decay
    inside the chunk (not rising along C). Returns two [..., C, C] float32.

    ``exp(G_i - G_j)`` is never split over a whole chunk: ``-G_j`` reaches
    hundreds and ``exp`` of it overflows float32. A chunk is cut into
    sub-blocks of ``sub`` positions. Inside one, the decay is taken element
    by element, masked BEFORE the exp. For row sub-block I and an earlier
    position j, with ``R_I`` the sum before I's first position, ``exp(G_i -
    G_j) = exp(G_i - R_I) exp(R_I - G_j)`` with both exponents <= 0: the
    rows of I times the first and every key times the second, against I's
    own reference, are matmul operands in ``compute_dtype`` (float32
    accumulation), one product ``[sub, d] x [d, C]`` a row sub-block."""
    f32 = jnp.float32
    C, d = k.shape[-2:]
    nb = C // sub
    lead = k.shape[:-2]
    cut = lambda t: t.reshape(lead + (nb, sub, d))
    q5, k5, G5 = cut(q.astype(f32)), cut(k.astype(f32)), cut(G)
    tri = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    decay = jnp.exp(jnp.where(
        tri, G5[..., :, None, :] - G5[..., None, :, :], -jnp.inf))
    kd = k5[..., None, :, :] * decay                    # [.., nb, i, j, d]
    diag = [jnp.sum(a5[..., :, None, :] * kd, axis=-1) for a5 in (q5, k5)]
    eye = jnp.eye(nb, dtype=f32)
    whole = [jnp.einsum("...Iij,IJ->...IiJj", t, eye).reshape(lead + (C, C))
             for t in diag]
    if nb == 1:
        return tuple(whole)
    ref = G5[..., :-1, -1, :]                # R_I of I = 1 .. nb - 1
    rows = jnp.exp(G5[..., 1:, :, :] - ref[..., None, :])
    # every key against I's reference; a key at or after I's first position
    # (exponent > 0) belongs to no product of I and is masked below
    keys = (k.astype(f32)[..., None, :, :] * jnp.exp(jnp.minimum(
        ref[..., None, :] - G[..., None, :, :], 0.0))).astype(compute_dtype)
    before = (jnp.arange(C)[None, :]
              < (jnp.arange(1, nb) * sub)[:, None])[:, None, :]
    out = []
    for a5, on_diag in zip((q5, k5), whole):
        off = jnp.einsum(
            "...Iid,...Ijd->...Iij",
            (a5[..., 1:, :, :] * rows).astype(compute_dtype), keys,
            preferred_element_type=f32)                 # [.., nb-1, sub, C]
        off = jnp.where(before, off, 0.0).reshape(lead + (C - sub, C))
        out.append(on_diag + jnp.pad(
            off, ((0, 0),) * len(lead) + ((sub, 0), (0, 0))))
    return tuple(out)


def delta_carry(state: jax.Array, chunk_of, compute_dtype=jnp.bfloat16):
    """One chunk of a delta rule's scan over chunks, what :func:`kda_chunked`
    and :func:`gated_delta_chunked` carry alike: the state ``S`` [B, H, d,
    dv] float32 enters, ``V' = U - W S``, ``o = (Q * exp(G)) S + A_qk V'``,
    and ``S' = decay * S + (K * exp(G_last - G))^T V'`` leaves. ``chunk_of``
    = (``w``, ``u``, ``a_qk``, ``q_in``, ``k_out``, ``decay``) of the chunk,
    what does not depend on ``S``; ``decay`` [B, H, d] a channel, or [B, H,
    1] where a head decays as one. Matmul operands ``compute_dtype``,
    accumulation and the state float32."""
    f32 = jnp.float32
    w, u, a_qk, q_in, k_out, decay = chunk_of
    s = state.astype(compute_dtype)
    v_new = u - jnp.einsum("bhcd,bhde->bhce", w, s,
                           preferred_element_type=f32)
    vc = v_new.astype(compute_dtype)
    o = (jnp.einsum("bhcd,bhde->bhce", q_in, s,
                    preferred_element_type=f32)
         + jnp.einsum("bhij,bhje->bhie", a_qk, vc,
                      preferred_element_type=f32))
    state = decay[..., None] * state + jnp.einsum(
        "bhcd,bhce->bhde", k_out, vc, preferred_element_type=f32)
    return state, o


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, chunk: int,
                compute_dtype=jnp.bfloat16,
                scan_fn: Optional[Callable[..., jax.Array]] = None
                ) -> jax.Array:
    """The gated delta rule with a decay a channel (Kimi Delta Attention,
    arXiv:2510.26692) in its chunked form. Per head, with the state ``S``
    [d, dv] (keys x values), zero before the sequence::

        S~  = Diag(exp(g_t)) S_(t-1)
        S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T ;   o_t = S_t^T q_t

    ``q``, ``k`` [B, S, H, d] (``k`` of unit length, ``q`` with its scale);
    ``v`` [B, S, H, dv]; ``g`` [B, S, H, d] float32, the log decay, <= 0;
    ``beta`` [B, S, H] float32. Returns ``o`` [B, S, H, dv] float32.

    With ``G`` the running sum of ``g`` inside a chunk of ``chunk``
    positions, ``A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` (j < i),
    ``T = (I + A)^-1 Diag(beta)`` (:func:`unit_lower_inverse`), ``W = T (K *
    exp(G))`` and ``U = T V``, a chunk that the state ``S`` enters has ``V' =
    U - W S``, ``o_i = (q_i * exp(G_i))^T S + sum_(j<=i) (sum_c q_ic k_jc
    exp(G_ic - G_jc)) v'_j`` and leaves ``Diag(exp(G_last)) S + sum_j (k_j *
    exp(G_last - G_j)) v'_j^T``. The chunks of a sequence are taken in
    groups by ``KDA_PAIR_BYTES``, a scan over the groups that carries ``S``:
    a group makes what does not depend on ``S`` for all its chunks at once
    (the two pair matrices of :func:`kda_pairs`, the inverse, ``W``, ``U``,
    the decayed ``q`` and ``k``), then scans over them; the backward pass
    makes a group again from the state that entered it, so that one
    group's intermediates exist at a time. Sums of ``g``, the inverse and the
    carried state are float32; the matmul operands are ``compute_dtype``
    with float32 accumulation. A sequence that ``chunk`` does not divide is
    padded with ``g = 0`` and ``beta = 0`` (no decay, no update), and the
    padding cut off.

    One algorithm run one of two ways, by what the caller hands in and the
    shapes alone, as :func:`ssd_chunked`. ``scan_fn`` (the Pallas kernels of
    ``ops/pallas/kda.py``, which whoever knows the devices hands down:
    ``parallel/spmd.attention_overrides``) runs where the shapes fit its
    tiles (``kda.tile_plan``): a chunk's pair matrices, the inverse, ``W``,
    ``U`` and the carried state then live in VMEM, the backward pass makes
    a chunk again from the state that entered it, and nothing of group size
    exists. Otherwise it is the ``jax.numpy`` below."""
    from hetu_galvatron_tpu.ops.pallas.kda import tile_plan

    f32 = jnp.float32
    B_, S, H, d = q.shape
    pad = -S % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    C, nC = chunk, (S + pad) // chunk
    if scan_fn is not None and tile_plan(C, H, d, v.shape[-1]) is not None:
        return scan_fn(q.astype(compute_dtype), k.astype(compute_dtype),
                       v.astype(compute_dtype), g, beta, C)[:, :S]
    sub, _ = kda_sub_blocks(C)
    size = kda_chunks_a_group(B_, nC, H, C, d)

    def grouped(t):   # [B, S, H, ...] -> [groups, B, size, H, C, ...]
        t = jnp.swapaxes(t.reshape((B_, nC // size, size, C) + t.shape[2:]),
                         3, 4)
        return jnp.moveaxis(t, 1, 0)

    strict = jnp.tril(jnp.ones((C, C), bool), -1)

    def intra(args):
        qg, kg, vg, gg, bg = args    # [B, size, H, C, d] .. [B, size, H, C]
        G = jnp.cumsum(gg, axis=-2)
        a_qk, a_kk = kda_pairs(qg, kg, G, sub, compute_dtype)
        T = (unit_lower_inverse(
            jnp.where(strict, a_kk, 0.0) * bg[..., None], sub)
             * bg[..., None, :]).astype(compute_dtype)
        kf, last = kg.astype(f32), G[..., -1:, :]
        w = jnp.einsum("...ij,...jd->...id", T,
                       (kf * jnp.exp(G)).astype(compute_dtype),
                       preferred_element_type=f32).astype(compute_dtype)
        u = jnp.einsum("...ij,...je->...ie", T, vg.astype(compute_dtype),
                       preferred_element_type=f32)
        return (w, u, a_qk.astype(compute_dtype),
                (qg.astype(f32) * jnp.exp(G)).astype(compute_dtype),
                (kf * jnp.exp(last - G)).astype(compute_dtype),
                jnp.exp(last[..., 0, :]))

    carry = partial(delta_carry, compute_dtype=compute_dtype)

    def group(state, args):
        return jax.lax.scan(carry, state, tuple(
            jnp.moveaxis(t, 1, 0) for t in intra(args)))

    _, o = jax.lax.scan(jax.checkpoint(group),
                        jnp.zeros((B_, H, d, v.shape[-1]), f32),
                        tuple(grouped(t) for t in (q, k, v, g, beta)))
    # [groups, size, B, H, C, dv] -> [B, nC, C, H, dv]
    o = jnp.swapaxes(jnp.moveaxis(o.reshape((nC,) + o.shape[2:]), 0, 1), 2, 3)
    return o.reshape(B_, nC * C, H, -1)[:, :S]


def apply_kda(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    compute_dtype=jnp.bfloat16,
    kda_fn: Optional[Callable[..., jax.Array]] = None,
    conv_fn: Optional[Callable[..., Optional[jax.Array]]] = None,
) -> jax.Array:
    """``[q~ | k~ | v] = silu(conv1d_causal(x W_qkv))`` (depthwise,
    ``kda_conv_kernel`` taps, zero history before the sequence, no bias); a
    head's ``q = q~ / |q~| * d^-0.5`` and ``k = k~ / |k~|``; the log decay a
    channel ``g = -exp(A_log) softplus(x W_fa W_fb + dt_bias)``; ``beta =
    sigmoid(x W_b)`` a head; ``o`` by the gated delta rule
    (:func:`kda_chunked`); ``y = RMSNorm(o) * w * sigmoid(x W_ga W_gb)`` a
    head; ``y W_out``. No softmax, no positions. The projections and the
    recurrence's matmuls run in ``compute_dtype`` with float32
    accumulation; the convolution, the L2 norms, the decay, ``beta``, the
    state and the gated norm are float32. ``kda_fn``: the kernels for the
    recurrence (:func:`kda_chunked`'s ``scan_fn``); ``conv_fn``: those for
    the convolution of the three at once, SiLU and the L2 norms
    (:func:`causal_depthwise_conv`)."""
    B, S, _ = x.shape
    nh, d, inner = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_inner
    f32 = jnp.float32

    def proj(a, w):
        return jnp.einsum("bsh,hc->bsc", a.astype(compute_dtype),
                          weight_view(w, compute_dtype),
                          preferred_element_type=f32)

    # Made again in the backward pass from the projection's output: its
    # float32 passes would otherwise be kept while the scan's backward
    # runs. It opens the mixer's scope itself and is called outside it, so
    # that an instruction's name reads ``checkpoint/mixer/kda/gates`` and
    # not ``mixer/kda/checkpoint/gates``, which no scope of the vocabulary
    # ends. (The convolution needs none: its kernels keep the projection's
    # output alone, and the ``jax.numpy`` form runs where memory is no
    # matter.)
    def log_decay(f, dt_bias, A_log):
        with jax.named_scope("mixer/kda"):
            with jax.named_scope("gates"):
                return (-jnp.repeat(jnp.exp(A_log.astype(f32)), d)
                        * jax.nn.softplus(f + dt_bias)).reshape(B, S, nh, d)

    with jax.named_scope("mixer/kda"):
        with jax.named_scope("in_proj"):
            qkv = proj(x, p["wqkv"]).astype(compute_dtype)
            f_a, g_a, b = jnp.split(proj(x, p["wlow"]), [d, 2 * d], axis=-1)
            f = proj(f_a, p["wf_b"])
            z = proj(g_a, p["wg_b"]).astype(compute_dtype)
        with jax.named_scope("conv"):
            # q, k and v in one pass; a head of q and of k L2-normed
            q, k, v = (t.reshape(B, S, nh, d) for t in jnp.split(
                causal_depthwise_conv(
                    qkv, p["taps"], silu=True,
                    head_norm=(d, KDA_L2_EPS, (d ** -0.5, 1.0, None)),
                    out_dtype=compute_dtype, conv_fn=conv_fn,
                    scope="mixer/kda/conv"), 3, axis=-1))
    g = jax.checkpoint(log_decay)(f, p["dt_bias"], p["A_log"])
    with jax.named_scope("mixer/kda"):
        with jax.named_scope("gates"):
            beta = jax.nn.sigmoid(b)
        with jax.named_scope("scan"):
            o = kda_chunked(q, k, v, g, beta, cfg.kda_chunk_size,
                            compute_dtype, scan_fn=kda_fn)
        with jax.named_scope("gated_norm"):
            var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            y = (o * jax.lax.rsqrt(var + cfg.layernorm_epsilon)
                 * p["norm"]["scale"]).reshape(B, S, inner)
            y = (y * jax.nn.sigmoid(z.astype(f32))).astype(compute_dtype)
        with jax.named_scope("out_proj"):
            out = jnp.einsum("bsc,ch->bsh", y,
                             weight_view(p["wout"], compute_dtype),
                             preferred_element_type=f32)
    return out.astype(compute_dtype)


# ---------------------------------------------------------------------------
# Gated DeltaNet (the published ``linear_attention``: a delta rule whose state
# decays by one number a head)
# ---------------------------------------------------------------------------


def init_gated_delta(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Axes]:
    """HF ``Qwen3NextGatedDeltaNet``'s parameters under Olmo Hybrid's
    published keys (fla's ``GatedDeltaNet``, arXiv:2412.06464): ``wqkv`` is
    ``q_proj | k_proj | v_proj`` side by side, ``taps`` the three depthwise
    kernels ``[q | k | v channels, taps]`` (``{q,k,v}_conv1d.weight[:, 0,
    :]``, no bias), ``wab`` is ``a_proj | b_proj`` (the decay's and
    ``beta``'s one value a head), ``wg`` the output gate's full-rank
    ``g_proj``, ``dt_bias`` and ``A_log`` one value a head, ``norm`` the
    output norm's scale of ``linear_value_head_dim`` shared by the heads,
    ``wout`` is ``o_proj``. No leaf carries an axis name that tensor
    parallelism shards (``eligibility.gdn_plan_reason``).

    ``A_log`` and ``dt_bias`` start as fla's layer starts them: ``A``
    uniform in (0, 16], ``dt`` log-uniform in [1e-3, 1e-1] and ``dt_bias``
    its inverse softplus."""
    if cfg.normalization != "rmsnorm":
        raise ValueError("a linear_attention block's output norm is an "
                         "RMSNorm (normalization=rmsnorm)")
    kda_sub_blocks(cfg.linear_chunk_size)   # raises for a chunk it cannot cut
    h, nh = cfg.hidden_size, cfg.linear_num_value_heads
    kd, vd = cfg.linear_key_dim, cfg.linear_value_dim
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    std = 0.02
    dt = jnp.exp(jax.random.uniform(k6, (nh,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    p: Params = {
        "wqkv": _normal(k1, (h, 2 * kd + vd), std),
        # the variance of torch's Conv1d default, as the conv block's taps
        "taps": _normal(k2, (2 * kd + vd, cfg.linear_conv_kernel_dim),
                        1.0 / math.sqrt(3 * cfg.linear_conv_kernel_dim)),
        "wab": _normal(k3, (h, 2 * nh), std),
        "wg": _normal(k4, (h, vd), std),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(16.0 * (1.0 - jax.random.uniform(
            k7, (nh,), jnp.float32))),
        "norm": {"scale": jnp.ones((cfg.linear_value_head_dim,),
                                   jnp.float32)},
        "wout": _normal(k5, (vd, h),
                        std / math.sqrt(2 * cfg.num_hidden_layers)),
    }
    a: Axes = {"wqkv": ("embed", "gdn_proj"),
               "taps": ("gdn_proj", "conv_tap"),
               "wab": ("embed", "gdn_low"), "wg": ("embed", "gdn_inner"),
               "dt_bias": ("gdn_head",), "A_log": ("gdn_head",),
               "norm": {"scale": ("gdn_width",)},
               "wout": ("gdn_inner", "embed")}
    return p, a


def gated_delta_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                        g: jax.Array, beta: jax.Array, chunk: int,
                        compute_dtype=jnp.bfloat16) -> jax.Array:
    """The gated delta rule with a decay a HEAD (Gated DeltaNet,
    arXiv:2412.06464) in its chunked form. Per head, with the state ``S``
    [d, dv] (keys x values), zero before the sequence::

        S~  = exp(g_t) S_(t-1)
        S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T ;   o_t = S_t^T q_t

    ``q``, ``k`` [B, S, H, d] (``k`` of unit length, ``q`` with its scale);
    ``v`` [B, S, H, dv]; ``g`` [B, S, H] float32, the log decay, <= 0;
    ``beta`` [B, S, H] float32, in (0, 2). Returns ``o`` [B, S, H, dv]
    float32.

    :func:`kda_chunked`'s lines with ``G``, the running sum of ``g`` inside
    a chunk, one number for all of a head's channels: the decay between two
    positions then leaves the contraction, and a chunk's pair matrices are
    ONE matmul each times the ``[C, C]`` matrix ``exp(G_i - G_j)`` (masked
    BEFORE the exp: above the diagonal the exponent is positive), with none
    of :func:`kda_pairs`' sub-blocks. ``A_ij = beta_i (k_i . k_j) exp(G_i -
    G_j)`` (j < i), ``T = (I + A)^-1 Diag(beta)``
    (:func:`unit_lower_inverse`), ``W = T (K * exp(G))``, ``U = T V``; then
    the scan over the chunks that carries ``S`` (:func:`delta_carry`). What
    does not depend on ``S`` is made for all chunks at once: at one number
    a head it is ``[B, chunks, H, C, C]`` float32 and a few arrays of the
    inputs' size, 31 MB a sequence of 4096 at 30 heads. Sums of ``g``,
    every exp, the inverse and the carried state are float32; the matmul
    operands are ``compute_dtype`` with float32 accumulation. A sequence
    that ``chunk`` does not divide is padded with ``g = 0`` and ``beta = 0``
    (no decay, no update), and the padding cut off."""
    f32 = jnp.float32
    B_, S, H, _ = q.shape
    pad = -S % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    C, nC = chunk, (S + pad) // chunk
    sub, _ = kda_sub_blocks(C)

    def chunks(t):   # [B, S, H, ...] -> [chunks, B, H, C, ...]
        t = jnp.swapaxes(t.reshape((B_, nC, C) + t.shape[2:]), 2, 3)
        return jnp.moveaxis(t, 1, 0)

    qc, kc, vc = (chunks(t.astype(compute_dtype)) for t in (q, k, v))
    G = jnp.cumsum(chunks(g), axis=-1)                       # [.., C]
    bc = chunks(beta)
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((C, C), bool)),
                              G[..., :, None] - G[..., None, :], -jnp.inf))
    pairs = lambda a: jnp.einsum("...id,...jd->...ij", a, kc,
                                 preferred_element_type=f32) * decay
    # the inverse reads the strictly lower triangle
    T = (unit_lower_inverse(pairs(kc) * bc[..., None], sub)
         * bc[..., None, :]).astype(compute_dtype)
    kf, last = kc.astype(f32), G[..., -1:]
    grown = jnp.exp(G)[..., None]
    w = jnp.einsum("...ij,...jd->...id", T,
                   (kf * grown).astype(compute_dtype),
                   preferred_element_type=f32).astype(compute_dtype)
    u = jnp.einsum("...ij,...je->...ie", T, vc, preferred_element_type=f32)
    _, o = jax.lax.scan(
        partial(delta_carry, compute_dtype=compute_dtype),
        jnp.zeros((B_, H, q.shape[-1], v.shape[-1]), f32),
        (w, u, pairs(qc).astype(compute_dtype),
         (qc.astype(f32) * grown).astype(compute_dtype),
         (kf * jnp.exp(last - G)[..., None]).astype(compute_dtype),
         jnp.exp(last)))
    # [chunks, B, H, C, dv] -> [B, chunks, C, H, dv]
    o = jnp.swapaxes(jnp.moveaxis(o, 0, 1), 2, 3)
    return o.reshape(B_, nC * C, H, -1)[:, :S]


def apply_gated_delta(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    compute_dtype=jnp.bfloat16,
    conv_fn: Optional[Callable[..., Optional[jax.Array]]] = None,
    stats: Optional[Dict[str, jax.Array]] = None,
    gdn_fn: Optional[Callable[..., jax.Array]] = None,
) -> jax.Array:
    """``[q~ | k~ | v] = silu(conv1d_causal(x W_qkv))`` (depthwise,
    ``linear_conv_kernel_dim`` taps, zero history before the sequence, no
    bias); a head's ``q = q~ / sqrt(|q~|^2 + 1e-6) * d^-0.5`` and ``k = k~ /
    sqrt(|k~|^2 + 1e-6)``; the log decay a head ``g = -exp(A_log) softplus(x
    W_a + dt_bias)``; ``beta = sigmoid(x W_b)`` a head, times 2 under
    ``linear_allow_neg_eigval``; ``o`` by the gated delta rule
    (:func:`gated_delta_chunked`); ``y = RMSNorm(o) * w * silu(x W_g)`` a
    head, the norm BEFORE the gate (HF ``Qwen3NextRMSNormGated``); ``y
    W_out``. No softmax, no positions. The projections and the recurrence's
    matmuls run in ``compute_dtype`` with float32 accumulation; the
    convolution, the L2 norms, the decay, ``beta``, the state and the gated
    norm are float32. ``conv_fn``: the kernels for the convolution of the
    three at once and its SiLU (:func:`causal_depthwise_conv`); a head of
    96 is no lane tile, so the L2 norms do not ride in its pass. ``gdn_fn``:
    the kernels for the recurrence (``ops/pallas/gdn.py``, which whoever
    knows the devices hands down: ``parallel/spmd.attention_overrides``);
    they run where the shapes fit their tiles (``gdn.tile_plan``) and the
    chunk divides the sequence: a chunk's pair matrices, the inverse, ``W``,
    ``U`` and the carried state then live in VMEM. Otherwise (the CPU, a
    chunk or widths that fit no tile, a ragged length) it is
    :func:`gated_delta_chunked`. ``stats`` takes ``beta_over_one``, the
    share of (position, head) whose delta step overshoots (``beta > 1``)."""
    from hetu_galvatron_tpu.ops.pallas.gdn import tile_plan

    B, S, _ = x.shape
    nh, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                  cfg.linear_value_head_dim)
    kd = cfg.linear_key_dim
    f32 = jnp.float32

    def proj(a, w):
        return jnp.einsum("bsh,hc->bsc", a.astype(compute_dtype),
                          weight_view(w, compute_dtype),
                          preferred_element_type=f32)

    def unit(t):    # a head of unit length
        t = t.astype(f32).reshape(B, S, nh, dk)
        return t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + KDA_L2_EPS)

    with jax.named_scope("mixer/gdn"):
        with jax.named_scope("in_proj"):
            qkv = proj(x, p["wqkv"]).astype(compute_dtype)
            a, b = jnp.split(proj(x, p["wab"]), 2, axis=-1)
            z = proj(x, p["wg"]).astype(compute_dtype)
        with jax.named_scope("conv"):
            q, k, v = jnp.split(
                causal_depthwise_conv(
                    qkv, p["taps"], silu=True, out_dtype=compute_dtype,
                    conv_fn=conv_fn, scope="mixer/gdn/conv"),
                [kd, 2 * kd], axis=-1)
        with jax.named_scope("gates"):
            q = (unit(q) * dk ** -0.5).astype(compute_dtype)
            k = unit(k).astype(compute_dtype)
            g = (-jnp.exp(p["A_log"].astype(f32))
                 * jax.nn.softplus(a + p["dt_bias"]))
            beta = jax.nn.sigmoid(b)
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
            if stats is not None:
                stats["beta_over_one"] = jnp.mean((beta > 1.0).astype(f32))
        with jax.named_scope("scan"):
            C = cfg.linear_chunk_size
            scan = partial(gated_delta_chunked, compute_dtype=compute_dtype)
            if (gdn_fn is not None and S % C == 0
                    and tile_plan(C, nh, dk, dv) is not None):
                scan = gdn_fn
            o = scan(q, k, v.reshape(B, S, nh, dv), g, beta, C)
        with jax.named_scope("gated_norm"):
            var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            y = (o * jax.lax.rsqrt(var + cfg.layernorm_epsilon)
                 * p["norm"]["scale"]).reshape(B, S, nh * dv)
            y = (y * jax.nn.silu(z.astype(f32))).astype(compute_dtype)
        with jax.named_scope("out_proj"):
            out = jnp.einsum("bsc,ch->bsh", y,
                             weight_view(p["wout"], compute_dtype),
                             preferred_element_type=f32)
    return out.astype(compute_dtype)


class Mixer(NamedTuple):
    """One kind of block operator: the block's key for its parameters,
    ``init(key, cfg) -> (params, axes)``, ``apply(params, h, cfg, ...)``,
    whether it attends (takes positions, a causal flag, packed documents'
    ``segment_ids`` and a dropout of probabilities), the fields of
    :class:`LayerOps` it reads by the keyword ``apply`` takes each as, what
    the launcher logs for a block of it (None: its attention core), for
    a kind whose projections no plan may cut over tp the name of the reason
    in analysis/eligibility.py, for a kind that does not attend what of it
    would cross the boundaries of packed documents, and the values a block
    of it may leave for later blocks (``leaves``; ``apply`` then takes
    ``made=``) or takes of an earlier one (``takes``; ``apply`` takes
    ``shared=``):
    ``args_schema.SHARED_VALUES`` by kind, ``ModelArgs.block_shares`` by
    block; and the counts a block of it writes for the log line of a logged
    step (``counts``; ``apply`` then takes ``stats=``, a dict, and they ride
    out of the step where an expert layer's do)."""

    key: str
    init: Callable
    apply: Callable
    attends: bool
    ops: Dict[str, str]
    logged: Optional[str] = None
    uncut_reason: Optional[str] = None
    crosses_documents: Optional[str] = None
    leaves: Tuple[str, ...] = ()
    takes: Tuple[str, ...] = ()
    counts: Tuple[str, ...] = ()

    def reads(self, field: str) -> bool:
        return field in self.ops.values()


_CARRIED = "the convolution's history and the carried state"
# a row a mixer kind (``ModelArgs.layer_types``); a new kind is its ``init``
# and ``apply`` and a row here
MIXERS: Dict[str, Mixer] = {
    "full_attention": Mixer(
        "attn", init_attention, apply_attention, True,
        {"sdpa_fn": "sdpa", "matmul_fns": "matmuls", "shard_fn": "shard"},
        leaves=SHARED_VALUES["cross_attention"][0]),
    "conv": Mixer(
        "conv", init_short_conv, apply_short_conv, False,
        {"shard_fn": "shard", "conv_fn": "conv"}, "short_conv",
        crosses_documents="the convolution's two tokens of history"),
    "mamba": Mixer(
        "mamba", init_mamba2, apply_mamba2, False,
        {"ssd_fn": "ssd", "conv_fn": "conv", "norm_fn": "gated_norm"},
        "mamba2",
        crosses_documents=_CARRIED),
    "latent_attention": Mixer(
        "attn", init_latent_attention, apply_latent_attention, True,
        {"sdpa_fn": "sdpa"}, uncut_reason="latent_plan_reason"),
    "kda": Mixer(
        "kda", init_kda, apply_kda, False,
        {"kda_fn": "kda", "conv_fn": "conv"}, "kda",
        uncut_reason="kda_plan_reason", crosses_documents=_CARRIED),
    "linear_attention": Mixer(
        "gdn", init_gated_delta, apply_gated_delta, False,
        {"gdn_fn": "gdn", "conv_fn": "conv"}, "gdn",
        uncut_reason="gdn_plan_reason", crosses_documents=_CARRIED,
        counts=("beta_over_one",)),
    "sliding_attention": Mixer(
        "attn", init_attention, partial(apply_attention, windowed=True),
        True, {"sdpa_fn": "sdpa"}, uncut_reason="window_plan_reason"),
    "mamba1": Mixer(
        "mamba1", init_mamba1, apply_mamba1, False,
        {"scan_fn": "selective", "conv_fn": "conv"}, "mamba1", uncut_reason="mamba1_plan_reason",
        crosses_documents=_CARRIED,
        leaves=SHARED_VALUES["gmu"][0]),
    "gmu": Mixer(
        "gmu", init_gmu, apply_gmu, False, {}, "gmu",
        uncut_reason="shared_plan_reason",
        crosses_documents="the memory it reads, an earlier block's carried "
        "state,", takes=SHARED_VALUES["gmu"][0]),
    "cross_attention": Mixer(
        "attn", init_cross_attention, apply_cross_attention, True,
        {"sdpa_fn": "sdpa"}, uncut_reason="shared_plan_reason",
        takes=SHARED_VALUES["cross_attention"][0]),
}


def writes_counts(cfg: ModelArgs) -> bool:
    """Whether a block of the stack writes counts (:class:`Mixer`)."""
    return any(m is not None and MIXERS[m].counts
               for m, _ in cfg.block_kinds())


def mixer_of(mixer: str) -> Mixer:
    if mixer not in MIXERS:
        raise ValueError(f"unknown mixer kind {mixer!r} "
                         f"({' | '.join(MIXERS)})")
    return MIXERS[mixer]


def apply_mixer(
    p: Params,
    h: jax.Array,
    cfg: ModelArgs,
    mixer: str = "full_attention",
    *,
    ops: LayerOps = LayerOps(),
    compute_dtype=jnp.bfloat16,
    rope: Optional[Tuple[jax.Array, jax.Array]] = None,
    causal: bool = True,
    dropout_rng: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    shared: Optional[Dict[str, jax.Array]] = None,
    made: Optional[Dict[str, jax.Array]] = None,
    lambda_init: Optional[float] = None,
    stats: Optional[Dict[str, jax.Array]] = None,
) -> jax.Array:
    """A block's operator on its normed input, by the block's mixer kind
    (``ModelArgs.block_kinds``, a row of :data:`MIXERS`), from the block's
    parameters under the row's key. Here ``ops`` is taken apart: each kind
    is handed the fields its row names, as the keywords its ``apply``
    takes, and no other. A kind that does not attend takes no rope, no
    attention core and no dropout of probabilities. ``shared`` holds what
    earlier blocks left (a kind that reads is handed the values its row
    names) and ``made``, where this block leaves something, is the dict
    its ``apply`` writes it into. ``lambda_init``: the block's constant
    under ``cfg.differential_attention`` (:func:`diff_lambda_init`).
    ``stats``: the dict a kind that writes counts writes them into."""
    row = mixer_of(mixer)
    if not row.attends and segment_ids is not None:
        raise NotImplementedError(
            f"packed documents (segment_ids) through a {mixer} block: "
            f"{row.crosses_documents} would cross document boundaries; set "
            "data.reset_attention_mask=false")
    if row.uncut_reason and (ops.shard is not None or ops.matmuls):
        raise NotImplementedError(
            f"a {mixer} block's projections are not cut over the tp axis "
            f"(eligibility.{row.uncut_reason})")
    given = ops.given()
    kwargs = {arg: given[field] for arg, field in row.ops.items()
              if field in given}
    if row.attends:
        kwargs.update(rope=rope, causal=causal, dropout_rng=dropout_rng,
                      segment_ids=segment_ids)
        if cfg.differential_attention:
            kwargs["lambda_init"] = lambda_init
    if row.takes:
        missing = [name for name in row.takes if name not in (shared or {})]
        if missing:
            raise ValueError(
                f"a {mixer} block reads the {' and '.join(missing)} an "
                "earlier block left, and none is handed to it (the walk of "
                "builder.forward_causal_lm carries them)")
        kwargs["shared"] = {name: shared[name] for name in row.takes}
    if made is not None:
        if not row.leaves:
            raise ValueError(f"a {mixer} block leaves nothing for later "
                             "blocks")
        kwargs["made"] = made
    if stats is not None and row.counts:
        kwargs["stats"] = stats
    return row.apply(p[row.key], h, cfg, compute_dtype=compute_dtype,
                     **kwargs)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def _is_gated(act: str) -> bool:
    return act in ("swiglu", "geglu")


def init_mlp(key: jax.Array, cfg: ModelArgs,
             ffn_dim: Optional[int] = None) -> Tuple[Params, Axes]:
    h = cfg.hidden_size
    f = ffn_dim or cfg.ffn_dim
    k1, k2 = jax.random.split(key)
    std = 0.02
    gated = _is_gated(cfg.hidden_act)
    # gated acts fuse gate+up into one [H, 2F] matmul (one MXU pass)
    p: Params = {
        "win": _normal(k1, (h, 2 * f if gated else f), std),
        "wout": _normal(k2, (f, h), std / math.sqrt(2 * cfg.num_hidden_layers)),
    }
    a: Axes = {"win": ("embed", "mlp"), "wout": ("mlp", "embed")}
    if cfg.add_bias_linear:
        p["bin"] = jnp.zeros((2 * f if gated else f,), jnp.float32)
        p["bout"] = jnp.zeros((h,), jnp.float32)
        a["bin"] = ("mlp",)
        a["bout"] = ("embed",)
    return p, a


_ACTS = {
    "gelu": partial(jax.nn.gelu, approximate=True),
    "gelu_exact": partial(jax.nn.gelu, approximate=False),  # HF BERT erf gelu
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),  # ungated (Nemotron-H)
    "silu": jax.nn.silu,
    "swiglu": jax.nn.silu,  # gate activation
    "geglu": partial(jax.nn.gelu, approximate=True),
}


def gate_up_pairs(w: jax.Array) -> jax.Array:
    """A gated MLP's fused ``[..., gate | up]`` (weight or bias) as
    ``[..., 2, F]``: with F sharded, a tensor-parallel shard holds its part
    of gate AND of up (the stored order, cut in contiguous halves, puts all
    of gate on one chip and all of up on the other)."""
    return w.reshape(w.shape[:-1] + (2, w.shape[-1] // 2))


def apply_mlp(p: Params, x: jax.Array, cfg: ModelArgs,
              compute_dtype=jnp.bfloat16,
              matmul_fns: Optional[Dict[str, Callable]] = None,
              shard_fn: Optional[Callable[[jax.Array, int], jax.Array]] = None
              ) -> jax.Array:
    """``shard_fn``: as in :func:`apply_attention`; the layer it is given
    to also gets a gated ``win`` / ``bin`` as :func:`gate_up_pairs`' view,
    computes gate and up as ``[B, S, 2, F]`` with F on tp and takes them
    apart by indexing."""
    act = _ACTS[cfg.hidden_act]
    mm = matmul_fns or {}
    gated = _is_gated(cfg.hidden_act)
    win = weight_view(p["win"], compute_dtype)
    with jax.named_scope("mlp"):
        if gated and "fc1_pair" in mm:
            # overlapped gated fc1: one ring over both weight halves keeps
            # the gate/up PRODUCT shard-aligned — splitting the fused
            # [B, S, 2F] output globally resharded activations per token;
            # the pair form pays only a weight-half reshard instead
            # (ops/overlap.make_ag_matmul_pair)
            F = p["wout"].shape[0]
            gate, up = mm["fc1_pair"](x.astype(compute_dtype),
                                      win[:, :F], win[:, F:])
            if "bin" in p:
                gate = gate + p["bin"][:F]
                up = up + p["bin"][F:]
            hproj = (act(gate.astype(compute_dtype))
                     * up.astype(compute_dtype))
        else:
            pairs = gated and shard_fn is not None
            if "fc1" in mm:
                hproj = mm["fc1"](x.astype(compute_dtype), win)
            else:
                hproj = jnp.einsum(
                    "bsh,hgf->bsgf" if pairs else "bsh,hf->bsf",
                    x.astype(compute_dtype), win,
                    preferred_element_type=jnp.float32)
            if "bin" in p:
                hproj = hproj + p["bin"]
            hproj = hproj.astype(compute_dtype)
            if pairs:
                hproj = shard_fn(hproj, 3)
                hproj = act(hproj[:, :, 0]) * hproj[:, :, 1]
            elif gated:
                gate, up = jnp.split(hproj, 2, axis=-1)
                hproj = act(gate) * up
            else:
                hproj = act(hproj)
            if shard_fn is not None:
                hproj = shard_fn(hproj, 2)
        wout = weight_view(p["wout"], compute_dtype)
        if "fc2" in mm:
            y = mm["fc2"](hproj, wout)
        else:
            y = jnp.einsum("bsf,fh->bsh", hproj, wout,
                           preferred_element_type=jnp.float32)
        if "bout" in p:
            y = y + p["bout"]
        return y.astype(compute_dtype)


# ---------------------------------------------------------------------------
# decoder layer
# ---------------------------------------------------------------------------


def init_decoder_layer(key: jax.Array, cfg: ModelArgs,
                       mixer: Optional[str] = "full_attention",
                       ff: Optional[Tuple[str, Callable]] = ("mlp", init_mlp)
                       ) -> Tuple[Params, Axes]:
    """A block of one mixer kind and one feed-forward: ``ff`` is (the
    block's key for it, ``init(key, cfg)``), a dense MLP unless the caller
    says otherwise (models/moe.py::init_moe_decoder_layer). A block of one
    branch (``ModelArgs.block_kinds`` of a stack whose ``layer_types`` name
    feed-forward blocks) has ``mixer`` None or ``ff`` None, one norm
    (``ln1``) and, over several streams, one set of maps (``hc1``)."""
    k1, k2 = jax.random.split(key)
    p, a = {}, {}
    both = mixer is not None and ff is not None
    for name in ("ln1", "ln2") if both else ("ln1",):
        p[name], a[name] = init_norm(cfg)
    if mixer is not None:
        row = mixer_of(mixer)
        p[row.key], a[row.key] = row.init(k1, cfg)
    if ff is not None:
        p[ff[0]], a[ff[0]] = ff[1](k2, cfg)
    hc_p, hc_a = init_block_maps(key, cfg)
    return {**p, **hc_p}, {**a, **hc_a}


def residual_branch(y: jax.Array, cfg: ModelArgs) -> jax.Array:
    """A pre-norm block's branch as it is added to the stream: times
    ``cfg.residual_multiplier`` where the model states one (Granite), else
    as it is."""
    if cfg.residual_multiplier == 1.0:
        return y
    return y * jnp.asarray(cfg.residual_multiplier, y.dtype)


def apply_decoder_layer(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    rope: Optional[Tuple[jax.Array, jax.Array]] = None,
    ops: LayerOps = LayerOps(),
    compute_dtype=jnp.bfloat16,
    causal: Optional[bool] = None,
    dropout_rng: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    mixer: Optional[str] = "full_attention",
    feed_forward: Optional[Callable[[jax.Array], jax.Array]] = None,
    shared: Optional[Dict[str, jax.Array]] = None,
    made: Optional[Dict[str, jax.Array]] = None,
    lambda_init: Optional[float] = None,
    stats: Optional[Dict[str, jax.Array]] = None,
) -> jax.Array:
    """Pre-norm residual block (reference GalvatronDecoderLayer,
    modules.py:233). Encoder families (bert, t5 encoder stack) run the same
    block with bidirectional attention; ``causal=None`` derives from the
    model family. ``dropout_rng`` enables attention/hidden dropout
    (HF semantics: sublayer output dropped before the residual add).
    ``ops`` is what the layer's plan swaps in (:class:`LayerOps`): handed on
    to :func:`apply_mixer`, the block's operator of kind ``mixer``, and its
    ``matmuls`` / ``shard`` to the MLP. ``feed_forward(h)`` is the second
    branch on its normed input where the block's is not the dense MLP of
    ``p["mlp"]`` (models/moe.py::apply_moe_decoder_layer). A model of several
    residual streams (``cfg.hc_mult``) hands ``x`` [B, S, n, H] and the
    block's maps ``hc1`` / ``hc2`` (:func:`residual`). ``shared`` / ``made``
    / ``lambda_init`` / ``stats``: what the block's operator reads of earlier
    blocks, the dict it writes what it leaves into, its constant of
    differential attention and the dict it writes its counts into
    (:func:`apply_mixer`). A block of a stack of one-branch
    blocks (``cfg.one_branch_blocks``) is ``x + F(ln1(x))``, one norm and
    one add: ``F`` the mixer, or the feed-forward where ``mixer`` is
    None. A block whose ``cfg.norm_position`` is "branch" (``cfg`` is the
    block's, ``ModelArgs.for_block``) is ``h = x + ln1(F(x))``, ``h +
    ln2(FF(h))``: the same two norms on the branches' outputs."""
    if causal is None:
        causal = cfg.model_type != "bert"
    r_attn = r_res1 = r_res2 = None
    if dropout_rng is not None:
        r_attn, r_res1, r_res2 = jax.random.split(dropout_rng, 3)

    def drop_h(y, rng):
        return dropout(y, cfg.hidden_dropout, rng)

    # where the block's two norms sit (``ModelArgs.for_block``: the block's
    # kind's): on a branch's input, or (Olmo 2 and 3) on its output
    on_output = cfg.branch_norm

    def norm(name, a, here):
        return block_norm(p[name], a, cfg) if here else a

    def mixed(h):
        return drop_h(norm("ln1", apply_mixer(
            p, h, cfg, mixer, ops=ops, rope=rope,
            compute_dtype=compute_dtype, causal=causal, dropout_rng=r_attn,
            segment_ids=segment_ids, shared=shared, made=made,
            lambda_init=lambda_init, stats=stats), on_output), r_res1)

    def fed(h):
        return drop_h(norm("ln2", (
            feed_forward(h) if feed_forward is not None else apply_mlp(
                p["mlp"], h, cfg, compute_dtype=compute_dtype,
                matmul_fns=ops.matmuls, shard_fn=ops.shard)), on_output),
            r_res2)

    if cfg.post_norm:
        if mixer != "full_attention" or cfg.one_branch_blocks:
            raise NotImplementedError(
                f"a post-norm block with a {mixer!r} mixer: post-norm "
                "families (bert) attend in every block")
        # HF BertLayer: residual-then-norm (attention.output.LayerNorm,
        # output.LayerNorm)
        x = block_norm(p["ln1"], x + mixed(x), cfg)
        return block_norm(p["ln2"], x + fed(x), cfg)

    if cfg.one_branch_blocks:
        branch = fed if mixer is None else mixed
        return residual(
            p.get("hc1"), x, cfg, lambda a: residual_branch(
                branch(block_norm(p["ln1"], a, cfg)), cfg), compute_dtype)

    def mixer_branch(a):
        return residual_branch(mixed(norm("ln1", a, not on_output)), cfg)

    def ff_branch(a):
        return residual_branch(fed(norm("ln2", a, not on_output)), cfg)

    x = residual(p.get("hc1"), x, cfg, mixer_branch, compute_dtype)
    return residual(p.get("hc2"), x, cfg, ff_branch, compute_dtype)


# ---------------------------------------------------------------------------
# embedding / lm head / loss
# ---------------------------------------------------------------------------


def init_embedding(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Axes]:
    k1, k2 = jax.random.split(key)
    p: Params = {"wte": _normal(k1, (cfg.padded_vocab_size, cfg.hidden_size), 0.02)}
    a: Axes = {"wte": ("vocab", "embed")}
    if cfg.position_embedding_type == "learned":
        p["wpe"] = _normal(k2, (cfg.max_position_embeddings, cfg.hidden_size), 0.02)
        a["wpe"] = ("pos", "embed")
    if cfg.post_norm:
        # HF BertEmbeddings applies LayerNorm after summing the tables;
        # token-type embeddings (single-segment type 0) are folded into wpe
        # by the HF converter (runtime/checkpoint.py)
        ln_p, ln_a = init_norm(cfg)
        p["ln"] = ln_p
        a["ln"] = ln_a
    return p, a


def apply_embedding(p: Params, tokens: jax.Array, cfg: ModelArgs,
                    compute_dtype=jnp.bfloat16,
                    dropout_rng: Optional[jax.Array] = None,
                    position_ids: Optional[jax.Array] = None) -> jax.Array:
    with jax.named_scope("embed"):
        x = jnp.take(p["wte"], tokens, axis=0)
        if "wpe" in p:
            if position_ids is not None:  # packed samples: per-token positions
                x = x + jnp.take(p["wpe"], position_ids, axis=0)
            else:
                S = tokens.shape[1]
                x = x + p["wpe"][:S][None, :, :]
        if "ln" in p:
            x = apply_norm(p["ln"], x, cfg)
        if cfg.scale_embeddings:
            # gemma: hidden states enter the stack scaled by sqrt(hidden)
            x = x * jnp.sqrt(jnp.float32(cfg.hidden_size)).astype(x.dtype)
        if cfg.embedding_multiplier != 1.0:
            # granite: the rows enter the stack times a stated constant
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        # HF GPT2Model.drop / BertEmbeddings.dropout: after sum (+LN for
        # bert)
        x = dropout(x, cfg.hidden_dropout, dropout_rng)
        return x.astype(compute_dtype)


def init_lm_head(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Axes]:
    if cfg.model_type == "bert":
        # HF BertLMPredictionHead: dense -> act -> LayerNorm -> (tied)
        # decoder + vocab bias (cls.predictions.*)
        k1, k2 = jax.random.split(key)
        ln_p, ln_a = init_norm(cfg)
        p: Params = {"wt": _normal(k1, (cfg.hidden_size, cfg.hidden_size), 0.02),
                     "bt": jnp.zeros((cfg.hidden_size,), jnp.float32),
                     "ln": ln_p,
                     "bias": jnp.zeros((cfg.padded_vocab_size,), jnp.float32)}
        # wt stays un-TP-sharded ("pos" = neutral axis): the transform is one
        # [H,H] matmul whose output feeds a full-width LayerNorm — TP-sharding
        # it would force an all-gather straight after
        a: Axes = {"wt": ("pos", "embed"), "bt": ("embed",),
                   "ln": ln_a, "bias": ("vocab",)}
        if not cfg.tie_word_embeddings:
            p["whead"] = _normal(k2, (cfg.hidden_size, cfg.padded_vocab_size),
                                 0.02)
            a["whead"] = ("embed", "vocab")
        return p, a
    if cfg.tie_word_embeddings:
        return {}, {}
    return (
        {"whead": _normal(key, (cfg.hidden_size, cfg.padded_vocab_size), 0.02)},
        {"whead": ("embed", "vocab")},
    )


def apply_lm_head(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    wte: Optional[jax.Array] = None,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """Returns fp32 logits [B, S, V]; tied weights reuse the embedding table
    (reference GalvatronCausalLMHead, modules.py:316-339). The bert path
    runs the HF MLM transform (dense -> act -> LN) and adds the vocab bias.
    A params tree that carries ``whead`` uses it even when the config says
    tied — the pipeline engine's last stage holds the transposed tied copy
    instead of a wte reference (runtime/pipeline.py split_params)."""
    with jax.named_scope("head"):
        if "wt" in p:
            x = jnp.einsum("bsh,hk->bsk", x.astype(compute_dtype),
                           p["wt"].astype(compute_dtype),
                           preferred_element_type=jnp.float32) + p["bt"]
            x = apply_norm(p["ln"], _ACTS[cfg.hidden_act](x), cfg)
            x = x.astype(compute_dtype)
        w = p["whead"] if "whead" in p else wte.T
        logits = jnp.einsum("bsh,hv->bsv", x.astype(compute_dtype),
                            weight_view(w, compute_dtype),
                            preferred_element_type=jnp.float32)
        if "bias" in p:
            logits = logits + p["bias"]
        if cfg.logits_scaling != 1.0:
            # granite: the logits divided by a stated constant before the
            # loss
            logits = logits / cfg.logits_scaling
        return logits


def _at_label(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Where a logit is its row's label's: a vocabulary iota against the
    label, in the logits' own layout."""
    columns = jax.lax.broadcasted_iota(labels.dtype, logits.shape,
                                       logits.ndim - 1)
    return columns == labels[..., None]


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _token_nll(logits: jax.Array, labels: jax.Array,
               z_loss: float) -> jax.Array:
    """``logsumexp - the label's logit (+ z_loss * logsumexp^2)`` a token,
    of float32 logits: the XLA path of :func:`cross_entropy_loss`.

    The label's logit is taken by compare-and-sum and the backward is
    written out, ``exp(logits - lse) * d_lse - onehot * d_gold``: an
    elementwise pass over the logits that XLA fuses into the operand side
    of the head's two backward matmuls, so the logits' gradient is never
    written. A gather's transpose is a scatter-add, which XLA:TPU ran on a
    FLATTENED copy of a one-sequence microbatch's f32 gradient (two
    relayouts of 824 MB each way to add 4096 numbers: 18 ms a step of
    ``olmoe_c1_s4k``, PR 68); and autodiff through this same forward
    compiles to an operand side that costs the weights' matmul 6 ms a step
    more there than this one ``exp`` does (PERF.md section 6, PR 68)."""
    return _token_nll_fwd(logits, labels, z_loss)[0]


def _token_nll_fwd(logits, labels, z_loss):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.sum(jnp.where(_at_label(logits, labels), logits, 0.0),
                   axis=-1)
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    return nll, (logits, labels, lse)


def _token_nll_bwd(z_loss, kept, g):
    logits, labels, lse = kept
    d_lse = g * (1.0 + 2.0 * z_loss * lse) if z_loss else g
    return (jnp.exp(logits - lse[..., None]) * d_lse[..., None]
            - jnp.where(_at_label(logits, labels), g[..., None], 0.0)), None


_token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


def cross_entropy_loss(
    logits: jax.Array,
    labels: jax.Array,
    loss_mask: Optional[jax.Array] = None,
    z_loss: float = 0.0,
    fused=False,
) -> jax.Array:
    """Stable mean CE over masked tokens; fp32 throughout.

    Vocab-parallel ready: under GSPMD a vocab-sharded logits array flows
    through logsumexp and the label's compare-and-sum (:func:`_token_nll`,
    an elementwise pass and two reduces over the sharded axis) with
    XLA-inserted collectives, replacing the reference's hand-written
    fused_vocab_parallel_cross_entropy
    (tensor_parallel/triton_cross_entropy.py:219-270). That path is a
    ``custom_vjp``: reverse mode only (``jax.grad``/``vjp``; no ``jvp``,
    ``jacfwd`` or ``linearize`` through the loss).

    ``fused=True`` routes the per-token NLL through the Pallas online
    logsumexp+gather kernel (ops/pallas/cross_entropy.py) on one device;
    distributed callers pass a callable instead (a shard_map nll_fn from
    ``make_vocab_parallel_ce``, matched to the head's sharding). Untileable
    shapes silently use the XLA path (both forms return None for them).
    """
    with jax.named_scope("head"):
        nll = None
        if callable(fused):
            nll = fused(logits, labels, z_loss=z_loss)
        elif fused:
            from hetu_galvatron_tpu.ops.pallas.cross_entropy import (
                fused_ce_nll,
            )

            nll = fused_ce_nll(logits, labels, z_loss=z_loss)
        if nll is None:
            nll = _token_nll(logits.astype(jnp.float32), labels, z_loss)
        if loss_mask is None:
            return jnp.mean(nll)
        loss_mask = loss_mask.astype(jnp.float32)
        return (jnp.sum(nll * loss_mask)
                / jnp.maximum(jnp.sum(loss_mask), 1.0))
