"""Autoregressive generation with a KV cache (single device).

The reference ships only inference-context stubs in its attention layer
(transformer/attention.py inference params); this module provides a working
TPU-native decode path: static-shape KV cache buffers, a `lax.scan` decode
loop (one compiled step reused for every position), greedy or
temperature/top-k sampling, and EOS masking — no data-dependent Python
control flow, so the whole generate() jits.

The transformer math is NOT re-implemented here: both prefill and the
decode step run `modules.apply_decoder_layer` with an attention-core closure
that captures (and, when decoding, updates) the rope-applied k/v — the
same hook the distributed layer uses for flash/ring/Ulysses attention, so
any change to the block stays in one place.

Scope: dense causal decoder families (gpt/llama/qwen/mistral: pre-norm,
learned or rope positions, GQA, biases) via generate(), plus t5-style
encoder-decoder decode via generate_encdec() (encoder once, cached cross
k/v). MoE decode is out of scope here.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.models import modules as M

Params = Dict[str, Any]


def _check_supported(cfg: ModelArgs, params: Params) -> None:
    from hetu_galvatron_tpu.analysis.eligibility import tower_reason

    if cfg.post_norm or cfg.model_type == "bert":
        raise NotImplementedError("generate(): causal decoder families only")
    if cfg.model_type == "t5":
        raise NotImplementedError(
            "generate() is the causal-decoder path; use generate_encdec() "
            "for t5 (encoder once + cached cross-attention decode)")
    reason = tower_reason(cfg, "generate()")
    if reason is not None:
        raise NotImplementedError(reason)
    if any("moe" in lp for lp in params["layers"]):
        raise NotImplementedError("generate(): dense layers only")
    from hetu_galvatron_tpu.analysis.eligibility import (
        mixed_stack_reason,
        own_multipliers_reason,
        residual_streams_reason,
    )

    reason = mixed_stack_reason(
        cfg, "generate() (a key-value cache a block, no convolution state "
        "and no state-space state)") or own_multipliers_reason(
        cfg, "generate() (its cached attention core)"
    ) or residual_streams_reason(cfg, "generate()")
    if reason is not None:
        raise NotImplementedError(reason)


def _cached_sdpa(q, ck, cv, pos, shift=None):
    """q [B,W,Nq,D] — a window of W consecutive query positions per row
    (W=1 is the plain decode step) — against the full cache [B,T,Nkv,D];
    window row j sits at absolute position pos(+j), and key positions
    beyond it are masked (static T => one compiled shape for the whole
    decode scan). ``pos`` is a scalar (one shared position, the offline
    scan) or [B] (per-row positions — the serving engine's paged decode
    delegates here, as do its W-wide speculative-verify and
    prefix-suffix-prefill programs via ``kv_cache.paged_sdpa_window``:
    ONE implementation keeps the multi-row passes bit-identical to W
    sequential decode steps by construction, not by parallel
    maintenance). ``shift`` [B] (left-padded ragged prompts) additionally
    masks the leading pad positions < shift[b]."""
    B, W, nq, D = q.shape
    T, nkv = ck.shape[1], ck.shape[2]
    G = nq // nkv
    qg = q.reshape(B, W, nkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bwkgd,btkd->bwkgt", qg, ck.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.float32(D))
    t = jnp.arange(T)[None, None, None, None, :]
    pos = jnp.asarray(pos)
    base = pos[:, None, None, None, None] if pos.ndim else pos
    row = jnp.arange(W)[None, :, None, None, None]
    mask = t <= (base + row)
    if shift is not None:
        mask = mask & (t >= shift[:, None, None, None, None])
    s = jnp.where(mask, s, jnp.float32(jnp.finfo(jnp.float32).min))
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bwkgt,btkd->bwkgd", w, cv.astype(jnp.float32))
    return out.reshape(B, W, nq, D).astype(q.dtype)


def _embed_at(p: Params, tokens: jax.Array, pos, cfg: ModelArgs,
              compute_dtype, shift=None):
    """Token embedding for one decode step at absolute position ``pos``
    (per-row LOGICAL position ``pos - shift[b]`` for left-padded rows).
    Mirrors ``modules.apply_embedding`` — including the embedding LayerNorm
    and the gemma sqrt(hidden) scaling — so decode steps see the same
    hidden-state distribution prefill produced."""
    x = jnp.take(p["wte"], tokens[:, None], axis=0)  # [B,1,H]
    if "wpe" in p:
        if shift is not None:
            x = x + jnp.take(p["wpe"], pos - shift, axis=0)[:, None]
        else:
            x = x + jax.lax.dynamic_slice_in_dim(p["wpe"], pos, 1)[None]
    if "ln" in p:
        x = M.apply_norm(p["ln"], x, cfg)
    if cfg.scale_embeddings:
        x = x * jnp.sqrt(jnp.float32(cfg.hidden_size)).astype(x.dtype)
    return x.astype(compute_dtype)


def init_kv_cache(cfg: ModelArgs, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    n = cfg.num_hidden_layers
    shape = (batch, max_len, cfg.kv_heads, cfg.head_dim)
    return [{"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
            for _ in range(n)]


def prefill(params: Params, tokens: jax.Array, cfg: ModelArgs, max_len: int,
            *, compute_dtype=jnp.bfloat16, prompt_lens=None):
    """Run the prompt through the stack, filling the cache; returns
    (cache, logits_last [B, V]).

    ``prompt_lens`` [B] supports ragged batched prompts, LEFT-padded to the
    common width S0 (row b's real tokens occupy columns [S0 - len_b, S0)):
    positions restart at 0 on the first real token and the pad prefix is
    masked out of attention, so every row reproduces its unpadded
    single-row prefill exactly."""
    B, S0 = tokens.shape
    shift = position_ids = segment_ids = None
    if prompt_lens is not None:
        shift = jnp.asarray(S0, jnp.int32) - prompt_lens.astype(jnp.int32)
        idx = jnp.arange(S0, dtype=jnp.int32)[None]
        position_ids = jnp.maximum(idx - shift[:, None], 0)
        segment_ids = (idx >= shift[:, None]).astype(jnp.int32)
    rope = None
    if cfg.position_embedding_type == "rope":
        rope = M.rope_cos_sin(S0, cfg.head_dim, cfg.rope_theta,
                              scaling=cfg.rope_scaling)
        if position_ids is not None:
            rope = (rope[0][position_ids], rope[1][position_ids])
    cache = init_kv_cache(cfg, B, max_len, compute_dtype)
    x = M.apply_embedding(params["embed"], tokens, cfg,
                          compute_dtype=compute_dtype,
                          position_ids=position_ids)
    for i, lp in enumerate(params["layers"]):
        cell = {}

        def sdpa(q, k, v, *, causal=True, segment_ids=None, cell=cell):
            cell["k"], cell["v"] = k, v  # rope-applied, pre-attention
            return M.xla_sdpa(q, k, v, causal=causal,
                              segment_ids=segment_ids)

        sdpa.supports_segments = True
        x = M.apply_decoder_layer(lp, x, cfg, rope=rope,
                                  ops=M.LayerOps(sdpa=sdpa),
                                  compute_dtype=compute_dtype,
                                  segment_ids=segment_ids)
        cache[i] = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache[i]["k"], cell["k"].astype(cache[i]["k"].dtype), 0,
                axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache[i]["v"], cell["v"].astype(cache[i]["v"].dtype), 0,
                axis=1),
        }
    x = M.apply_norm(params["prenorm"], x, cfg)
    logits = M.apply_lm_head(params["head"], x[:, -1:], cfg,
                             wte=params["embed"]["wte"],
                             compute_dtype=compute_dtype)
    return cache, logits[:, 0]


def decode_step(params: Params, cache, tokens: jax.Array, pos, cfg: ModelArgs,
                *, rope_full=None, compute_dtype=jnp.bfloat16, shift=None):
    """One token per sequence at absolute position ``pos`` (a traced
    scalar); returns (cache, logits [B, V]). ``shift`` [B] carries the
    left-pad offsets of a ragged prefill: rope/learned positions use the
    logical ``pos - shift[b]`` and the pad prefix stays masked."""
    x = _embed_at(params["embed"], tokens, pos, cfg, compute_dtype,
                  shift=shift)
    step_rope = None
    if rope_full is not None:
        cos, sin = rope_full
        if shift is not None:
            step_rope = (cos[pos - shift][:, None], sin[pos - shift][:, None])
        else:
            step_rope = (jax.lax.dynamic_slice_in_dim(cos, pos, 1),
                         jax.lax.dynamic_slice_in_dim(sin, pos, 1))
    for i, lp in enumerate(params["layers"]):
        cell = {}

        def sdpa(q, k, v, *, causal=True, i=i, cell=cell):
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache[i]["k"], k.astype(cache[i]["k"].dtype), pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache[i]["v"], v.astype(cache[i]["v"].dtype), pos, axis=1)
            cell["k"], cell["v"] = ck, cv
            return _cached_sdpa(q, ck, cv, pos, shift=shift)

        x = M.apply_decoder_layer(lp, x, cfg, rope=step_rope,
                                  ops=M.LayerOps(sdpa=sdpa),
                                  compute_dtype=compute_dtype)
        cache[i] = {"k": cell["k"], "v": cell["v"]}
    x = M.apply_norm(params["prenorm"], x, cfg)
    logits = M.apply_lm_head(params["head"], x, cfg,
                             wte=params["embed"]["wte"],
                             compute_dtype=compute_dtype)
    return cache, logits[:, 0]


def generate(
    params: Params,
    tokens: jax.Array,  # [B, S0] prompt
    cfg: ModelArgs,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,  # 0 => greedy
    top_k: Optional[int] = None,
    eos_id: Optional[int] = None,
    pad_id: Optional[int] = None,
    prompt_lens: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """Returns [B, S0 + max_new_tokens]. Fully jittable (static shapes;
    scan over positions).

    Retirement contract: once a row has emitted ``eos_id`` it is retired —
    every later position carries ``pad_id`` (``eos_id`` when pad_id is
    None, the legacy layout), NOT live samples. With greedy decoding
    (temperature 0) this makes a row's whole output independent of which
    neighbors share the batch; with temperature > 0 the live tokens still
    draw from ONE shared key over the [B, V] batch (a row's samples depend
    on batch size/row index — the serving engine uses per-request keys
    instead), but the retired tail is masked either way. The serving
    engine's per-request streams are checked against exactly this contract
    (rows trimmed at their first eos).

    ``prompt_lens`` [B] enables ragged batched prompts, LEFT-padded to
    width S0: each row decodes as if it were the only (unpadded) sequence
    — pad prefix masked from attention, positions starting at 0 on the
    first real token.
    """
    _check_supported(cfg, params)
    B, S0 = tokens.shape
    total = S0 + max_new_tokens
    if total > cfg.max_position_embeddings and "wpe" in params["embed"]:
        raise ValueError(f"{total} exceeds max_position_embeddings")
    rope_full = None
    if cfg.position_embedding_type == "rope":
        rope_full = M.rope_cos_sin(total, cfg.head_dim, cfg.rope_theta,
                                   scaling=cfg.rope_scaling)
    if key is None:
        key = jax.random.key(0)
    shift = None
    if prompt_lens is not None:
        shift = jnp.asarray(S0, jnp.int32) - prompt_lens.astype(jnp.int32)

    cache, logits = prefill(params, tokens, cfg, total,
                            compute_dtype=compute_dtype,
                            prompt_lens=prompt_lens)
    pick = _sample_pick(cfg, tokens.dtype, temperature, top_k)
    fill = eos_id if pad_id is None else pad_id

    def body(carry, _):
        cache, logits, pos, done, k = carry
        k, sub = jax.random.split(k)
        nxt = pick(logits, sub)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.asarray(fill, nxt.dtype), nxt)
            done = done | (nxt == eos_id)
        cache, logits = decode_step(params, cache, nxt, pos, cfg,
                                    rope_full=rope_full,
                                    compute_dtype=compute_dtype,
                                    shift=shift)
        return (cache, logits, pos + 1, done, k), nxt

    done0 = jnp.zeros((B,), bool)
    (_, logits, _, done, _), toks = jax.lax.scan(
        body, (cache, logits, jnp.int32(S0), done0, key), None,
        length=max_new_tokens)
    return jnp.concatenate([tokens, toks.T], axis=1)


# ---------------------------------------------------------------------------
# encoder-decoder (t5) decode: encoder once + cached cross-attention k/v +
# cached causal self-attention (reference ships only inference-context stubs,
# transformer/attention.py inference params). NOTE: this runtime is
# position-scheme agnostic (no T5 relative bias — models/encdec.py docstring
# + the HF converter note, runtime/checkpoint.py _t5_hf_to_params), so
# imported HF T5 weights fine-tune rather than bit-match HF generation; the
# decode contract tested instead is incremental == full teacher-forced
# forward (tests/models/test_t5.py).
# ---------------------------------------------------------------------------


def _sample_pick(cfg, tokens_dtype, temperature, top_k):
    """Per-step token selection shared by the causal and encoder-decoder
    decode loops: greedy / temperature / top-k, with the vocab-padding
    columns (untrained head rows) never sampled."""
    valid = jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size

    def pick(logits, k):
        logits = jnp.where(valid, logits, jnp.finfo(logits.dtype).min)
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(tokens_dtype)
        logits = logits / temperature
        if top_k:
            kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
            logits = jnp.where(logits < kth,
                               jnp.finfo(logits.dtype).min, logits)
        return jax.random.categorical(k, logits, axis=-1).astype(tokens_dtype)

    return pick


def prefill_encdec(params: Params, mem: jax.Array, dec_tokens: jax.Array,
                   cfg: ModelArgs, max_len: int, *,
                   compute_dtype=jnp.bfloat16):
    """Decoder prefill over the start tokens against encoder memory ``mem``:
    fills the self-attention cache, projects + caches the cross k/v once
    per layer. Returns (cache, cross_cache, logits_last [B, V])."""
    from hetu_galvatron_tpu.models.encdec import (
        apply_cross_decoder_layer,
        cross_kv,
    )

    B, T0 = dec_tokens.shape
    rope = None
    if cfg.position_embedding_type == "rope":
        rope = M.rope_cos_sin(T0, cfg.head_dim, cfg.rope_theta,
                              scaling=cfg.rope_scaling)
    cache = init_kv_cache(cfg, B, max_len, compute_dtype)
    cross = [cross_kv(lp["cross"], mem, cfg, compute_dtype)
             for lp in params["layers"]]
    x = M.apply_embedding(params["embed"], dec_tokens, cfg,
                          compute_dtype=compute_dtype)
    for i, lp in enumerate(params["layers"]):
        cell = {}

        def sdpa(q, k, v, *, causal=True, cell=cell):
            cell["k"], cell["v"] = k, v  # rope-applied, pre-attention
            return M.xla_sdpa(q, k, v, causal=causal)

        x = apply_cross_decoder_layer(lp, x, mem, cfg, rope=rope,
                                      ops=M.LayerOps(
                                          sdpa=sdpa, cross_sdpa=M.xla_sdpa),
                                      compute_dtype=compute_dtype,
                                      cached_cross_kv=cross[i])
        cache[i] = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache[i]["k"], cell["k"].astype(cache[i]["k"].dtype), 0,
                axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache[i]["v"], cell["v"].astype(cache[i]["v"].dtype), 0,
                axis=1),
        }
    x = M.apply_norm(params["prenorm"], x, cfg)
    logits = M.apply_lm_head(params["head"], x[:, -1:], cfg,
                             wte=params["embed"]["wte"],
                             compute_dtype=compute_dtype)
    return cache, cross, logits[:, 0]


def decode_step_encdec(params: Params, cache, cross, mem, tokens: jax.Array,
                       pos, cfg: ModelArgs, *, rope_full=None,
                       compute_dtype=jnp.bfloat16):
    """One decoder token at absolute position ``pos``: cached causal
    self-attention + cached cross k/v. Returns (cache, logits [B, V])."""
    from hetu_galvatron_tpu.models.encdec import apply_cross_decoder_layer

    x = _embed_at(params["embed"], tokens, pos, cfg, compute_dtype)
    step_rope = None
    if rope_full is not None:
        cos, sin = rope_full
        step_rope = (jax.lax.dynamic_slice_in_dim(cos, pos, 1),
                     jax.lax.dynamic_slice_in_dim(sin, pos, 1))
    for i, lp in enumerate(params["layers"]):
        cell = {}

        def sdpa(q, k, v, *, causal=True, i=i, cell=cell):
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache[i]["k"], k.astype(cache[i]["k"].dtype), pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache[i]["v"], v.astype(cache[i]["v"].dtype), pos, axis=1)
            cell["k"], cell["v"] = ck, cv
            return _cached_sdpa(q, ck, cv, pos)

        x = apply_cross_decoder_layer(lp, x, mem, cfg, rope=step_rope,
                                      ops=M.LayerOps(
                                          sdpa=sdpa, cross_sdpa=M.xla_sdpa),
                                      compute_dtype=compute_dtype,
                                      cached_cross_kv=cross[i])
        cache[i] = {"k": cell["k"], "v": cell["v"]}
    x = M.apply_norm(params["prenorm"], x, cfg)
    logits = M.apply_lm_head(params["head"], x, cfg,
                             wte=params["embed"]["wte"],
                             compute_dtype=compute_dtype)
    return cache, logits[:, 0]


def generate_encdec(
    params: Params,
    enc_tokens: jax.Array,  # [B, S] source sequence
    cfg: ModelArgs,
    max_new_tokens: int,
    *,
    decoder_start_token_id: int = 0,
    temperature: float = 0.0,  # 0 => greedy
    top_k: Optional[int] = None,
    eos_id: Optional[int] = None,
    key: Optional[jax.Array] = None,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """Seq2seq generation: encoder ONCE, then a `lax.scan` decode loop with
    cached self-attention k/v and per-layer cached cross k/v. Returns the
    decoder tokens [B, 1 + max_new_tokens] (start token included). Fully
    jittable (static shapes)."""
    from hetu_galvatron_tpu.models.encdec import encode

    if cfg.model_type != "t5":
        raise ValueError("generate_encdec() is the t5/encoder-decoder path")
    B = enc_tokens.shape[0]
    total = 1 + max_new_tokens
    if total > cfg.max_position_embeddings and "wpe" in params["embed"]:
        raise ValueError(f"{total} exceeds max_position_embeddings")
    rope_full = None
    if cfg.position_embedding_type == "rope":
        rope_full = M.rope_cos_sin(total, cfg.head_dim, cfg.rope_theta,
                                   scaling=cfg.rope_scaling)
    if key is None:
        key = jax.random.key(0)

    mem = encode(params, enc_tokens, cfg, compute_dtype=compute_dtype)
    start = jnp.full((B, 1), decoder_start_token_id, jnp.int32)
    cache, cross, logits = prefill_encdec(params, mem, start, cfg, total,
                                          compute_dtype=compute_dtype)
    pick = _sample_pick(cfg, start.dtype, temperature, top_k)

    def body(carry, _):
        cache, logits, pos, done, k = carry
        k, sub = jax.random.split(k)
        nxt = pick(logits, sub)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.asarray(eos_id, nxt.dtype), nxt)
            done = done | (nxt == eos_id)
        cache, logits = decode_step_encdec(
            params, cache, cross, mem, nxt, pos, cfg,
            rope_full=rope_full, compute_dtype=compute_dtype)
        return (cache, logits, pos + 1, done, k), nxt

    done0 = jnp.zeros((B,), bool)
    (_, _, _, _, _), toks = jax.lax.scan(
        body, (cache, logits, jnp.int32(1), done0, key), None,
        length=max_new_tokens)
    return jnp.concatenate([start, toks.T], axis=1)
