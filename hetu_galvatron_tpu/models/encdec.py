"""Encoder-decoder (T5-family) stacks: cross-attention + seq2seq assembly.

Completes the BASELINE milestone-4 family (T5-style encoder-decoder with
asymmetric stacks). The reference snapshot ships no T5 runtime — this is
built on the same functional-module vocabulary as the decoder
(models/modules.py): an encoder of bidirectional blocks, a decoder whose
blocks add cross-attention over the encoder output, and a shared token
embedding. Positions use the configured scheme (RoPE/learned) in both stacks
rather than T5's relative bias — the parallelism machinery (this framework's
subject) is position-scheme agnostic.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.models import modules as M

Params = Dict[str, Any]


def encoder_layers(cfg: ModelArgs) -> int:
    return (cfg.num_encoder_layers if cfg.num_encoder_layers is not None
            else cfg.num_hidden_layers)


def init_cross_attention(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Params]:
    """Q from the decoder stream, fused KV from the encoder output."""
    h, hd = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.kv_heads
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    p: Params = {
        "wq": M._normal(k1, (h, nq * hd), std),
        "wkv": M._normal(k2, (h, 2 * nkv * hd), std),
        "wo": M._normal(k3, (nq * hd, h),
                        std / math.sqrt(2 * cfg.num_hidden_layers)),
    }
    a: Params = {"wq": ("embed", "qkv"), "wkv": ("embed", "qkv"),
                 "wo": ("heads", "embed")}
    if cfg.add_qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), jnp.float32)
        p["bkv"] = jnp.zeros((2 * nkv * hd,), jnp.float32)
        a["bq"] = ("qkv",)
        a["bkv"] = ("qkv",)
    if cfg.add_bias_linear:
        p["bo"] = jnp.zeros((h,), jnp.float32)
        a["bo"] = ("embed",)
    return p, a


def cross_kv(p: Params, memory: jax.Array, cfg: ModelArgs,
             compute_dtype=jnp.bfloat16) -> Tuple[jax.Array, jax.Array]:
    """Project the encoder memory to cross-attention (k, v) [B, S, Nkv, D].
    Decode caches this once per layer (the memory never changes during
    generation) instead of re-projecting every step."""
    nkv, hd = cfg.kv_heads, cfg.head_dim
    kv = jnp.einsum("bsh,hf->bsf", memory.astype(compute_dtype),
                    p["wkv"].astype(compute_dtype),
                    preferred_element_type=jnp.float32)
    if "bkv" in p:
        kv = kv + p["bkv"]
    k, v = jnp.split(kv.astype(compute_dtype), 2, axis=-1)
    S = memory.shape[1]
    return k.reshape(-1, S, nkv, hd), v.reshape(-1, S, nkv, hd)


def apply_cross_attention(
    p: Params,
    x: jax.Array,       # decoder stream [B, T, H]
    memory: jax.Array,  # encoder output [B, S, H]
    cfg: ModelArgs,
    sdpa_fn: Callable[..., jax.Array] = M.xla_sdpa,
    compute_dtype=jnp.bfloat16,
    dropout_rng=None,
    cached_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> jax.Array:
    B, T, H = x.shape
    hd = cfg.head_dim
    nq = cfg.num_attention_heads
    q = jnp.einsum("bth,hf->btf", x.astype(compute_dtype),
                   p["wq"].astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    if "bq" in p:
        q = q + p["bq"]
    q = q.astype(compute_dtype).reshape(B, T, nq, hd)
    k, v = (cached_kv if cached_kv is not None
            else cross_kv(p, memory, cfg, compute_dtype))
    # decoder sees the whole source; probability dropout mirrors
    # modules.apply_attention (HF T5Attention drops attention weights in
    # BOTH self- and cross-attention): the XLA core and dropout-capable
    # kernels (flash) implement it in-place; others refuse loudly
    if dropout_rng is not None and cfg.attention_dropout > 0.0:
        if sdpa_fn is M.xla_sdpa or getattr(sdpa_fn, "supports_dropout",
                                            False):
            out = sdpa_fn(q, k, v, causal=False,
                          dropout_rate=cfg.attention_dropout,
                          dropout_rng=dropout_rng)
        else:
            raise NotImplementedError(
                "attention_dropout > 0 needs the XLA attention core or a "
                "dropout-capable kernel (flash) for cross-attention "
                "(see modules.apply_attention)")
    else:
        out = sdpa_fn(q, k, v, causal=False)
    y = jnp.einsum("btf,fh->bth", out.reshape(B, T, nq * hd),
                   p["wo"].astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    if "bo" in p:
        y = y + p["bo"]
    return y.astype(compute_dtype)


def init_cross_decoder_layer(key: jax.Array, cfg: ModelArgs
                             ) -> Tuple[Params, Params]:
    k1, k2, k3 = jax.random.split(key, 3)
    self_p, self_a = M.init_attention(k1, cfg)
    cross_p, cross_a = init_cross_attention(k2, cfg)
    mlp_p, mlp_a = M.init_mlp(k3, cfg)
    ln1_p, ln1_a = M.init_norm(cfg)
    lnx_p, lnx_a = M.init_norm(cfg)
    ln2_p, ln2_a = M.init_norm(cfg)
    return (
        {"ln1": ln1_p, "attn": self_p, "lnx": lnx_p, "cross": cross_p,
         "ln2": ln2_p, "mlp": mlp_p},
        {"ln1": ln1_a, "attn": self_a, "lnx": lnx_a, "cross": cross_a,
         "ln2": ln2_a, "mlp": mlp_a},
    )


def apply_cross_decoder_layer(
    p: Params,
    x: jax.Array,
    memory: jax.Array,
    cfg: ModelArgs,
    rope=None,
    ops: M.LayerOps = M.LayerOps(),
    compute_dtype=jnp.bfloat16,
    dropout_rng=None,
    cached_cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> jax.Array:
    """Pre-norm: causal self-attention -> cross-attention -> MLP.

    ``ops.sdpa`` drives the (causal) self-attention; cross-attention uses
    ``ops.cross_sdpa`` when set, else ``ops.sdpa`` — the dispatch layer
    (parallel/spmd.py attention_overrides) passes a non-causal-capable kernel
    here (flash handles causal=False; ring layers fall back to the XLA core
    because the decoder/encoder sequence lengths differ). A t5 layer's plan
    swaps nothing else."""
    r_attn = r_xattn = r1 = r2 = r3 = None
    if dropout_rng is not None:
        r_attn, r_xattn, r1, r2, r3 = jax.random.split(dropout_rng, 5)

    def drop_h(y, rng):
        return M.dropout(y, cfg.hidden_dropout, rng)

    h = M.apply_norm(p["ln1"], x, cfg)
    x = x + drop_h(M.apply_mixer(p, h, cfg, ops=ops, rope=rope,
                                 compute_dtype=compute_dtype, causal=True,
                                 dropout_rng=r_attn), r1)
    h = M.apply_norm(p["lnx"], x, cfg)
    x = x + drop_h(apply_cross_attention(
        p["cross"], h, memory, cfg,
        sdpa_fn=ops.cross_sdpa or ops.sdpa or M.xla_sdpa,
        compute_dtype=compute_dtype, dropout_rng=r_xattn,
        cached_kv=cached_cross_kv), r2)
    h = M.apply_norm(p["ln2"], x, cfg)
    x = x + drop_h(M.apply_mlp(p["mlp"], h, cfg,
                               compute_dtype=compute_dtype), r3)
    return x


def init_encdec(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Params]:
    """Full T5-style model: shared embedding, encoder stack, decoder stack
    with cross-attention, final norm, (un)tied head."""
    n_enc = encoder_layers(cfg)
    n_dec = cfg.num_hidden_layers
    keys = jax.random.split(key, n_enc + n_dec + 3)
    embed_p, embed_a = M.init_embedding(keys[0], cfg)
    enc = [M.init_decoder_layer(keys[1 + i], cfg) for i in range(n_enc)]
    dec = [init_cross_decoder_layer(keys[1 + n_enc + i], cfg)
           for i in range(n_dec)]
    enc_norm_p, enc_norm_a = M.init_norm(cfg)
    prenorm_p, prenorm_a = M.init_norm(cfg)
    head_p, head_a = M.init_lm_head(keys[-1], cfg)
    params = {
        "embed": embed_p,
        "enc_layers": tuple(p for p, _ in enc),
        "enc_norm": enc_norm_p,
        "layers": tuple(p for p, _ in dec),
        "prenorm": prenorm_p,
        "head": head_p,
    }
    axes = {
        "embed": embed_a,
        "enc_layers": tuple(a for _, a in enc),
        "enc_norm": enc_norm_a,
        "layers": tuple(a for _, a in dec),
        "prenorm": prenorm_a,
        "head": head_a,
    }
    return params, axes


def encode(params: Params, enc_tokens: jax.Array, cfg: ModelArgs, *,
           compute_dtype=jnp.bfloat16) -> jax.Array:
    """Encoder-only forward -> memory [B, S, H] (the encoder runs ONCE per
    generation; decode steps reuse the memory via cached cross k/v)."""
    rope_enc = None
    if cfg.position_embedding_type == "rope":
        rope_enc = M.rope_cos_sin(enc_tokens.shape[1], cfg.head_dim,
                                  cfg.rope_theta, scaling=cfg.rope_scaling)
    mem = M.apply_embedding(params["embed"], enc_tokens, cfg,
                            compute_dtype=compute_dtype)
    for lp in params["enc_layers"]:
        mem = M.apply_decoder_layer(lp, mem, cfg, rope=rope_enc,
                                    compute_dtype=compute_dtype,
                                    causal=False)
    return M.apply_norm(params["enc_norm"], mem, cfg)


def forward_encdec(
    params: Params,
    enc_tokens: jax.Array,
    dec_tokens: jax.Array,
    cfg: ModelArgs,
    *,
    compute_dtype=jnp.bfloat16,
    remat_flags=None,
    enc_remat_flags=None,
    boundary_fn=None,
    enc_boundary_fn=None,
    layer_overrides=None,
    enc_layer_overrides=None,
    logits_fp32: bool = True,
    dropout_rng=None,
) -> jax.Array:
    """(enc_tokens [B,S], dec_tokens [B,T]) -> logits [B,T,V].

    Per-layer knobs mirror the decoder-only builder (models/builder.py):
    ``remat_flags`` / ``boundary_fn`` / ``layer_overrides`` index DECODER
    layers; the ``enc_*`` triplet indexes ENCODER layers (heterogeneous
    per-layer encoder plans — the combined-stack strategy list of
    runtime/hybrid_config.py). When ``enc_remat_flags`` is None the encoder
    falls back to ``remat_flags[0]`` uniformly (legacy behavior). A flag of
    either list is a bool, or the step program's probe
    (:func:`modules.recomputed`)."""
    rope_enc = rope_dec = None
    if cfg.position_embedding_type == "rope":
        rope_enc = M.rope_cos_sin(enc_tokens.shape[1], cfg.head_dim,
                                  cfg.rope_theta, scaling=cfg.rope_scaling)
        rope_dec = M.rope_cos_sin(dec_tokens.shape[1], cfg.head_dim,
                                  cfg.rope_theta, scaling=cfg.rope_scaling)

    if enc_remat_flags is None and remat_flags:
        enc_remat_flags = [bool(remat_flags[0])] * len(params["enc_layers"])
    # disjoint fold_in streams: encoder layers, decoder layers, embeddings
    r_embed_e = M.fold_dropout_rng(dropout_rng, cfg,
                                   M.DROPOUT_STREAM_EMBED_ENC)
    r_embed_d = M.fold_dropout_rng(dropout_rng, cfg, M.DROPOUT_STREAM_EMBED)
    mem = M.apply_embedding(params["embed"], enc_tokens, cfg,
                            compute_dtype=compute_dtype,
                            dropout_rng=r_embed_e)
    for i, lp in enumerate(params["enc_layers"]):
        if enc_boundary_fn is not None:
            mem = enc_boundary_fn(i, mem)
        kwargs: Dict[str, Any] = dict(
            rope=rope_enc, compute_dtype=compute_dtype, causal=False,
            ops=(enc_layer_overrides or {}).get(i, M.LayerOps()))
        if dropout_rng is not None:
            kwargs["dropout_rng"] = M.fold_dropout_rng(
                dropout_rng, cfg, M.DROPOUT_STREAM_ENC + i)
        fn = lambda p, h, kw=kwargs: M.apply_decoder_layer(p, h, cfg, **kw)
        mem = M.recomputed(
            fn, cfg, enc_remat_flags is not None and enc_remat_flags[i])(
                lp, mem)
    if enc_boundary_fn is not None:
        mem = enc_boundary_fn(len(params["enc_layers"]), mem)
    mem = M.apply_norm(params["enc_norm"], mem, cfg)

    x = M.apply_embedding(params["embed"], dec_tokens, cfg,
                          compute_dtype=compute_dtype,
                          dropout_rng=r_embed_d)
    for i, lp in enumerate(params["layers"]):
        if boundary_fn is not None:
            x = boundary_fn(i, x)
        kwargs = dict(rope=rope_dec, compute_dtype=compute_dtype,
                      ops=(layer_overrides or {}).get(i, M.LayerOps()))
        if dropout_rng is not None:
            kwargs["dropout_rng"] = M.fold_dropout_rng(dropout_rng, cfg, i)
        fn = lambda p, h, m, kw=kwargs: apply_cross_decoder_layer(
            p, h, m, cfg, **kw)
        x = M.recomputed(
            fn, cfg, remat_flags is not None and remat_flags[i])(lp, x, mem)
    if boundary_fn is not None:
        x = boundary_fn(len(params["layers"]), x)
    x = M.apply_norm(params["prenorm"], x, cfg)
    logits = M.apply_lm_head(params["head"], x, cfg,
                             wte=params["embed"]["wte"],
                             compute_dtype=compute_dtype)
    return logits if logits_fp32 else logits.astype(compute_dtype)


def encdec_loss(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelArgs,
    *,
    compute_dtype=jnp.bfloat16,
    remat_flags=None,
    enc_remat_flags=None,
    boundary_fn=None,
    enc_boundary_fn=None,
    layer_overrides=None,
    enc_layer_overrides=None,
    fused_ce=False,  # bool, or a shard_map nll callable (see builder)
) -> jax.Array:
    """batch: enc_tokens [B,S], tokens (decoder input) [B,T], labels [B,T],
    optional loss_mask."""
    logits = forward_encdec(params, batch["enc_tokens"], batch["tokens"],
                            cfg, compute_dtype=compute_dtype,
                            remat_flags=remat_flags,
                            enc_remat_flags=enc_remat_flags,
                            boundary_fn=boundary_fn,
                            enc_boundary_fn=enc_boundary_fn,
                            layer_overrides=layer_overrides,
                            enc_layer_overrides=enc_layer_overrides,
                            dropout_rng=batch.get("dropout_rng"))
    return M.cross_entropy_loss(logits, batch["labels"],
                                batch.get("loss_mask"), fused=fused_ce)
