"""Generic causal-LM assembly: arch list -> params/axes pytrees -> forward.

Capability parity with the reference's model builder
(runtime/models/builder.py:42-121 ``build_causal_lm_arch`` /
``build_sequential_from_arch`` + MODULE_REGISTRY, modules.py): every supported
model family (gpt2/llama/qwen/mistral/mixtral) is one generic decoder stack
parameterized by :class:`ModelArgs`.

TPU design: the "model" is data, not objects — ``init_causal_lm`` returns a
nested params dict plus a parallel tree of logical-axis names; ``forward``
is a pure function. Per-layer heterogeneity (different sharding, remat flag,
attention impl per layer) enters through ``layer_overrides`` rather than
module wrappers, so one traced program covers any searched strategy.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.models import modules as M

Params = Dict[str, Any]

# Registry of arch-entry -> (init, apply); mirrors the reference
# MODULE_REGISTRY (builder.py:41) keyed by the same role names.
MODULE_REGISTRY: Dict[str, Tuple[Callable, Callable]] = {
    "embed": (M.init_embedding, M.apply_embedding),
    "decoder": (M.init_decoder_layer, M.apply_decoder_layer),
    "prenorm": (M.init_norm, M.apply_norm),
    "head": (M.init_lm_head, M.apply_lm_head),
}


def build_causal_lm_arch(cfg: ModelArgs) -> List[str]:
    """Arch role list (reference build_causal_lm_arch builder.py:111-121)."""
    return ["embed"] + ["decoder"] * cfg.num_hidden_layers + ["prenorm", "head"]


def init_causal_lm(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Params]:
    """Returns (params, logical_axes) with layers as a per-layer tuple so the
    axes tree mirrors params exactly (required for tree-mapped shardings).
    Each block's mixer and feed-forward kind come from the per-layer
    description (``cfg.block_kinds()``); t5 builds the encoder-decoder pair
    (models/encdec.py)."""
    from hetu_galvatron_tpu.models.moe import init_moe_decoder_layer

    if cfg.model_type == "t5":
        from hetu_galvatron_tpu.models.encdec import init_encdec

        return init_encdec(key, cfg)

    n = cfg.num_hidden_layers
    keys = jax.random.split(key, n + 2)
    embed_p, embed_a = M.init_embedding(keys[0], cfg)
    layers = [
        (init_moe_decoder_layer if ff == "experts"
         else M.init_decoder_layer)(keys[1 + i], cfg, mixer)
        for i, (mixer, ff) in enumerate(cfg.block_kinds())
    ]
    if cfg.post_norm:
        # post-norm families (bert) end each block already normalized; the
        # MLM head's transform LayerNorm is the final norm (HF BertLayer +
        # BertLMPredictionHead layout) — apply_norm({}) is the identity
        prenorm_p, prenorm_a = {}, {}
    else:
        prenorm_p, prenorm_a = M.init_norm(cfg)
    head_p, head_a = M.init_lm_head(keys[n + 1], cfg)
    params = {
        "embed": embed_p,
        "layers": tuple(lp for lp, _ in layers),
        "prenorm": prenorm_p,
        "head": head_p,
    }
    axes = {
        "embed": embed_a,
        "layers": tuple(la for _, la in layers),
        "prenorm": prenorm_a,
        "head": head_a,
    }
    return params, axes


def forward_causal_lm(
    params: Params,
    tokens: jax.Array,
    cfg: ModelArgs,
    *,
    compute_dtype=jnp.bfloat16,
    remat_flags: Optional[Sequence[bool]] = None,
    layer_overrides: Optional[Dict[int, Dict[str, Any]]] = None,
    boundary_fn: Optional[Callable[[int, jax.Array], jax.Array]] = None,
    logits_fp32: bool = True,
    with_aux: bool = False,
    dropout_rng: Optional[jax.Array] = None,
    position_ids: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    mrope_position_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V].

    ``dropout_rng`` (training only) enables cfg.attention_dropout /
    cfg.hidden_dropout; ``None`` (the default) is eval semantics — dropout
    layers are the identity, so existing callers are unchanged.

    ``position_ids`` / ``segment_ids`` [B, S] implement the reference's
    reset_position_ids / reset_attention_mask for packed multi-document
    samples: positions restart at 0 after each eod and attention is
    block-diagonalized per document (dataloader.packed_doc_fields).

    ``remat_flags[i]`` turns on `jax.checkpoint` for layer i (the reference's
    per-layer checkpoint_flags_enc, parallel.py:213-243). ``layer_overrides``
    maps layer index -> kwargs for :func:`modules.apply_decoder_layer`
    (e.g. a different ``sdpa_fn`` for Ulysses/ring layers). ``boundary_fn(i,
    x)`` is applied to the hidden state before layer i and once after the last
    layer (i == num layers) — the SPMD layer uses it to place
    `with_sharding_constraint` resharding at layer boundaries, replacing the
    reference's relocation wrappers (runtime/parallel.py:272-304).
    """
    from hetu_galvatron_tpu.models.moe import apply_moe_decoder_layer

    S = tokens.shape[1]
    rope = None
    if cfg.position_embedding_type == "rope" and cfg.mrope_section:
        # multimodal rope: per-axis positions [3, B, S]; text-only callers
        # (no mrope_position_ids) broadcast their 1-D positions, which is
        # exactly standard rope (modules.mrope_cos_sin docstring)
        mpos = mrope_position_ids
        if mpos is None:
            base = (position_ids if position_ids is not None
                    else jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                          tokens.shape))
            mpos = jnp.broadcast_to(base[None],
                                    (len(cfg.mrope_section),) + base.shape)
        rope = M.mrope_cos_sin(mpos, cfg.head_dim, cfg.rope_theta,
                               sections=cfg.mrope_section,
                               scaling=cfg.rope_scaling)
    elif cfg.position_embedding_type == "rope":
        with jax.named_scope("attn/rope"):
            cos, sin = M.rope_cos_sin(S, cfg.head_dim, cfg.rope_theta,
                                      scaling=cfg.rope_scaling)
        if position_ids is not None:
            # packed samples: gather per-token rows -> [B, S, D/2]
            cos, sin = cos[position_ids], sin[position_ids]
        rope = (cos, sin)
    x = M.apply_embedding(
        params["embed"], tokens, cfg, compute_dtype=compute_dtype,
        dropout_rng=M.fold_dropout_rng(dropout_rng, cfg,
                                       M.DROPOUT_STREAM_EMBED),
        position_ids=position_ids)
    aux_total = jnp.zeros((), jnp.float32)
    moe_stats: Dict[str, Dict[str, jax.Array]] = {}
    kinds = cfg.block_kinds()
    if len(kinds) != len(params["layers"]):
        raise ValueError(
            f"the parameters hold {len(params['layers'])} blocks and the "
            f"configuration describes {len(kinds)}")
    for i, lp in enumerate(params["layers"]):
        if boundary_fn is not None:
            x = boundary_fn(i, x)
        mixer, ff = kinds[i]
        kwargs: Dict[str, Any] = dict(rope=rope, compute_dtype=compute_dtype,
                                      mixer=mixer)
        if segment_ids is not None:
            kwargs["segment_ids"] = segment_ids
        if dropout_rng is not None:
            kwargs["dropout_rng"] = M.fold_dropout_rng(dropout_rng, cfg, i)
        if layer_overrides and i in layer_overrides:
            kwargs.update(layer_overrides[i])
        if ff == "experts":
            fn = lambda p, h, kw=kwargs: apply_moe_decoder_layer(
                p, h, cfg, **kw)
        else:
            fn = lambda p, h, kw=kwargs: (
                M.apply_decoder_layer(p, h, cfg, **kw),
                jnp.zeros((), jnp.float32), {})
        if remat_flags is not None and remat_flags[i]:
            fn = M.remat(fn, cfg)
        x, aux, stats = fn(lp, x)
        aux_total = aux_total + aux
        if stats:
            # per-layer balance tracker (reference moe_utils.py:547-644)
            moe_stats[f"layer{i}"] = stats
    if boundary_fn is not None:
        x = boundary_fn(len(params["layers"]), x)
    x = M.block_norm(params["prenorm"], x, cfg)
    logits = M.apply_lm_head(
        params["head"], x, cfg,
        wte=params["embed"]["wte"], compute_dtype=compute_dtype,
    )
    logits = logits if logits_fp32 else logits.astype(compute_dtype)
    return (logits, aux_total, moe_stats) if with_aux else logits


def causal_lm_loss(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelArgs,
    *,
    compute_dtype=jnp.bfloat16,
    remat_flags: Optional[Sequence[bool]] = None,
    layer_overrides: Optional[Dict[int, Dict[str, Any]]] = None,
    boundary_fn: Optional[Callable[[int, jax.Array], jax.Array]] = None,
    enc_remat_flags: Optional[Sequence[bool]] = None,
    enc_layer_overrides: Optional[Dict[int, Dict[str, Any]]] = None,
    enc_boundary_fn: Optional[Callable[[int, jax.Array], jax.Array]] = None,
    fused_ce: Union[None, bool, Callable] = None,
    with_moe_stats: bool = False,
) -> jax.Array:
    """batch: tokens [B,S], labels [B,S], optional loss_mask [B,S] -> scalar
    (or (scalar, per-layer MoE stats dict) with ``with_moe_stats=True`` —
    the reference's aux-losses tracker, moe_utils.py:547-644).

    Equivalent role to the reference's loss closure from the dataloader
    (dataloader.py:558 _loss_func + train_dist.py forward_backward wiring).
    t5 batches route to the encoder-decoder loss; the ``enc_*`` knobs index
    the encoder stack and are only meaningful there.

    ``fused_ce`` overrides ``cfg.use_fused_ce``: True runs the Pallas CE
    kernel directly (single device); on multi-device meshes the distributed
    builder passes a shard_map nll callable from ``make_vocab_parallel_ce``
    instead (a bare Pallas call is a custom call GSPMD cannot partition).
    """
    fused = cfg.use_fused_ce if fused_ce is None else fused_ce
    if cfg.model_type == "t5":
        from hetu_galvatron_tpu.models.encdec import encdec_loss

        loss = encdec_loss(params, batch, cfg, compute_dtype=compute_dtype,
                           remat_flags=remat_flags,
                           enc_remat_flags=enc_remat_flags,
                           boundary_fn=boundary_fn,
                           enc_boundary_fn=enc_boundary_fn,
                           layer_overrides=layer_overrides,
                           enc_layer_overrides=enc_layer_overrides,
                           fused_ce=fused)
        return (loss, {}) if with_moe_stats else loss
    logits, aux, moe_stats = forward_causal_lm(
        params, batch["tokens"], cfg,
        compute_dtype=compute_dtype, remat_flags=remat_flags,
        layer_overrides=layer_overrides, boundary_fn=boundary_fn,
        with_aux=True, dropout_rng=batch.get("dropout_rng"),
        position_ids=batch.get("position_ids"),
        segment_ids=batch.get("segment_ids"),
        mrope_position_ids=batch.get("mrope_position_ids"),
    )
    ce = M.cross_entropy_loss(logits, batch["labels"], batch.get("loss_mask"),
                              fused=fused)
    loss = ce + aux
    return (loss, moe_stats) if with_moe_stats else loss


def param_count(params: Params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


def model_flops_per_token(cfg: ModelArgs, seq_len: Optional[int] = None) -> float:
    """Approximate training FLOPs per token (6*N params + attention term),
    used by the MFU computation in bench/profilers."""
    s = seq_len or cfg.seq_length
    h, f, v = cfg.hidden_size, cfg.ffn_dim, cfg.padded_vocab_size
    nq, nkv, hd = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    per_layer = 2 * h * (nq + 2 * nkv) * hd  # qkv
    per_layer += 2 * nq * hd * h  # proj
    per_layer += 2 * h * f * (3 if M._is_gated(cfg.hidden_act) else 2)  # mlp
    attn = 2 * 2 * s * nq * hd  # qk^T + pv per token
    dense = cfg.num_hidden_layers * (per_layer + attn) + 2 * h * v
    return 3.0 * dense  # fwd + bwd(2x)
