"""Generic causal-LM assembly: arch list -> params/axes pytrees -> forward.

Capability parity with the reference's model builder
(runtime/models/builder.py:42-121 ``build_causal_lm_arch`` /
``build_sequential_from_arch`` + MODULE_REGISTRY, modules.py): every supported
model family (gpt2/llama/qwen/mistral/mixtral) is one generic decoder stack
parameterized by :class:`ModelArgs`.

TPU design: the "model" is data, not objects — ``init_causal_lm`` returns a
nested params dict plus a parallel tree of logical-axis names; ``forward``
is a pure function. Per-layer heterogeneity (different sharding, remat flag,
operators per layer) enters through ``layer_overrides``, a
:class:`modules.LayerOps` a layer, rather than module wrappers, so one traced
program covers any searched strategy.
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


def init_block(key: jax.Array, cfg: ModelArgs,
               kind: Tuple[Optional[str], Optional[str]]
               ) -> Tuple[Params, Params]:
    """(params, axes) of one block of ``kind``, (mixer, feed-forward); one
    of the two is None in a block of one branch."""
    from hetu_galvatron_tpu.models.moe import init_moe_decoder_layer

    mixer, ff = kind
    if ff == "experts":
        return init_moe_decoder_layer(key, cfg, mixer)
    return M.init_decoder_layer(
        key, cfg, mixer, ("mlp", M.init_mlp) if ff else None)


def build_causal_lm_arch(cfg: ModelArgs) -> List[str]:
    """Arch role list (reference build_causal_lm_arch builder.py:111-121)."""
    return ["embed"] + ["decoder"] * cfg.num_hidden_layers + ["prenorm", "head"]


def init_causal_lm(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Params]:
    """Returns (params, logical_axes) with layers as a per-layer tuple so the
    axes tree mirrors params exactly (required for tree-mapped shardings).
    Each block's mixer and feed-forward kind come from the per-layer
    description (``cfg.block_kinds()``); t5 builds the encoder-decoder pair
    (models/encdec.py)."""
    if cfg.model_type == "t5":
        from hetu_galvatron_tpu.models.encdec import init_encdec

        return init_encdec(key, cfg)

    n = cfg.num_hidden_layers
    cfg.block_shares()   # a reader with no maker before it: a ValueError
    keys = jax.random.split(key, n + 2)
    embed_p, embed_a = M.init_embedding(keys[0], cfg)
    layers = [init_block(keys[1 + i], cfg.for_block(i), kind)
              for i, kind in enumerate(cfg.block_kinds())]
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
    if cfg.num_nextn_predict_layers:
        # drawn from a key of its own, so the stack's leaves are what they
        # are without it
        params["mtp"], axes["mtp"] = init_mtp(
            jax.random.fold_in(key, n + 2), cfg)
    if cfg.tower_layers:
        # the tower of image patches and its projector (models/tower.py),
        # from a key of its own too
        from hetu_galvatron_tpu.models.tower import init_tower

        params["tower"], axes["tower"] = init_tower(
            jax.random.fold_in(key, n + 3), cfg)
    return params, axes


def mtp_block_kind(cfg: ModelArgs) -> Tuple[str, str]:
    """The (mixer, feed-forward) kind of the multi-token-prediction block:
    the stack's last block's (DeepSeek-V3 2.2: one more block of the
    model's own kind)."""
    return cfg.block_kinds()[-1]


def init_mtp(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Params]:
    """One further prediction depth (DeepSeek-V3 2.2): ``enorm`` / ``hnorm``
    over the next token's embedding and the stack's output, ``eh_proj`` [2H,
    H] over the two side by side (embedding first), one more block
    (``layer``) and its ``norm``; embedding and head are the model's."""
    if cfg.num_nextn_predict_layers != 1:
        raise NotImplementedError(
            f"model.num_nextn_predict_layers={cfg.num_nextn_predict_layers}:"
            " one further prediction depth is written")
    if cfg.post_norm or cfg.model_type in ("t5", "bert"):
        raise NotImplementedError(
            "multi-token prediction is written for pre-norm causal stacks")
    k1, k2 = jax.random.split(key)
    lp, la = init_block(k2, cfg.for_block(cfg.num_hidden_layers - 1),
                        mtp_block_kind(cfg))
    norms = [M.init_norm(cfg) for _ in range(3)]
    h = cfg.hidden_size
    return (
        {"enorm": norms[0][0], "hnorm": norms[1][0],
         "eh_proj": M._normal(k1, (2 * h, h), 0.02), "layer": lp,
         "norm": norms[2][0]},
        {"enorm": norms[0][1], "hnorm": norms[1][1],
         "eh_proj": ("mtp_in", "embed"), "layer": la, "norm": norms[2][1]},
    )


def rope_table(cfg: ModelArgs, seq: int, kind: Optional[str],
               position_ids: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """cos and sin of the blocks of mixer ``kind`` (``None``: the model's
    one rotation), ``[seq, width / 2]`` or, gathered by a packed sample's
    ``position_ids``, ``[B, seq, width / 2]``; ``width`` is the share of a
    head the kind rotates (``ModelArgs.rope_of``)."""
    theta, scaling, width = cfg.rope_of(kind)
    with jax.named_scope("attn/rope"):
        cos, sin = M.rope_cos_sin(seq, width, theta, scaling=scaling)
    if position_ids is not None:
        # packed samples: gather per-token rows -> [B, S, D/2]
        cos, sin = cos[position_ids], sin[position_ids]
    return cos, sin


def make_block(cfg: ModelArgs, kind: Tuple[str, str],
               kwargs: Dict[str, Any], remat, leaves: bool = False):
    """``fn(block params, x, shared) -> (x, aux loss, the block's stats, made)``
    of one block of ``kind`` with its keyword arguments, rematerialized
    where asked (``remat``: the block's flag, :func:`modules.recomputed`).
    ``shared`` holds what the block reads of earlier blocks
    (an argument of the rematerialized function, so its cotangent flows
    back to the block that made it) and ``made`` what it ``leaves`` for
    later ones (an output of it); both empty for most blocks."""
    from hetu_galvatron_tpu.models.moe import apply_moe_decoder_layer

    def fn(p, h, shared):
        made: Dict[str, jax.Array] = {}
        handed = dict(kwargs, shared=shared) if shared else kwargs
        if leaves:
            handed = dict(handed, made=made)
        if kind[1] == "experts":
            return apply_moe_decoder_layer(p, h, cfg, **handed) + (made,)
        # the counts a mixer kind writes for a logged step's line ride
        # where an expert layer's do
        stats: Dict[str, jax.Array] = {}
        if kind[0] is not None and M.mixer_of(kind[0]).counts:
            handed = dict(handed, stats=stats)
        return (M.apply_decoder_layer(p, h, cfg, **handed),
                jnp.zeros((), jnp.float32), stats, made)

    return M.recomputed(fn, cfg, remat)


def forward_causal_lm(
    params: Params,
    tokens: jax.Array,
    cfg: ModelArgs,
    *,
    compute_dtype=jnp.bfloat16,
    remat_flags: Optional[Sequence[bool]] = None,
    layer_overrides: Optional[Dict[int, M.LayerOps]] = None,
    boundary_fn: Optional[Callable[[int, jax.Array], jax.Array]] = None,
    logits_fp32: bool = True,
    with_aux: bool = False,
    dropout_rng: Optional[jax.Array] = None,
    position_ids: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    mrope_position_ids: Optional[jax.Array] = None,
    mtp_labels: Optional[jax.Array] = None,
    patches: Optional[jax.Array] = None,
    tower_remat_flags: Optional[Sequence[bool]] = None,
    tower_ops: Optional[M.LayerOps] = None,
) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V].

    ``patches`` [B, P, patch_dim] (a model with ``params["tower"]``; the
    images of ``cfg.image_grids`` packed in order): the tower and the
    projector run first (models/tower.py, its blocks rematerialized by
    ``tower_remat_flags`` and its attention core ``tower_ops.sdpa``), and
    their rows take the embedding's place where a token is
    ``cfg.image_token_id``. Without ``patches`` the sequence is text.

    ``mtp_labels`` [B, S] (the token after each position; a model with
    ``params["mtp"]``): also run the further prediction depth
    (:func:`forward_mtp`) and return (logits, aux, moe stats, mtp logits).

    ``dropout_rng`` (training only) enables cfg.attention_dropout /
    cfg.hidden_dropout; ``None`` (the default) is eval semantics — dropout
    layers are the identity, so existing callers are unchanged.

    ``position_ids`` / ``segment_ids`` [B, S] implement the reference's
    reset_position_ids / reset_attention_mask for packed multi-document
    samples: positions restart at 0 after each eod and attention is
    block-diagonalized per document (dataloader.packed_doc_fields).

    ``remat_flags[i]`` turns on `jax.checkpoint` for layer i (the reference's
    per-layer checkpoint_flags_enc, parallel.py:213-243). A flag, here and
    in ``tower_remat_flags``, is a bool or, while the step program counts
    what a block would hold, a callable ``flag(fn, cfg) -> fn`` that stands
    in for the block (:func:`modules.recomputed`, its one reader;
    ``parallel/kept.py::Probe``, its one writer). ``layer_overrides``
    maps layer index -> what the layer's plan swaps in the block
    (:class:`modules.LayerOps`, e.g. the attention core of a Ulysses or ring
    layer; a layer without an entry runs the ``jax.numpy`` / XLA forms).
    ``boundary_fn(i, x)`` is applied to the hidden state before layer i and
    once after the last layer (i == num layers) — the SPMD layer uses it to place
    `with_sharding_constraint` resharding at layer boundaries, replacing the
    reference's relocation wrappers (runtime/parallel.py:272-304).
    """
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
        rope = M.mrope_cos_sin(mpos, cfg.rope_dim, cfg.rope_theta,
                               sections=cfg.mrope_section,
                               scaling=cfg.rope_scaling)
    elif cfg.position_embedding_type == "rope":
        rope = rope_table(cfg, S, None, position_ids)
    x = M.apply_embedding(
        params["embed"], tokens, cfg, compute_dtype=compute_dtype,
        dropout_rng=M.fold_dropout_rng(dropout_rng, cfg,
                                       M.DROPOUT_STREAM_EMBED),
        position_ids=position_ids)
    if patches is not None:
        from hetu_galvatron_tpu.models.tower import apply_tower, place_images

        x = place_images(x, tokens, apply_tower(
            params["tower"], patches, cfg, compute_dtype=compute_dtype,
            remat_flags=tower_remat_flags, ops=tower_ops), cfg)
    x = M.streams_in(x, cfg)
    aux_total = jnp.zeros((), jnp.float32)
    moe_stats: Dict[str, Dict[str, jax.Array]] = {}
    kinds = cfg.block_kinds()
    # a table a mixer kind where the model states a rotation a kind (in one
    # order in every process: a set's order is the hash seed's, and the
    # tables' order in the program is part of the compile cache's key)
    ropes = {m: rope_table(cfg, S, m, position_ids)
             for m in sorted({m for m, _ in kinds}
                             & set(cfg.rope_parameters or {}))}
    if ropes and cfg.mrope_section:
        raise NotImplementedError(
            "model.rope_parameters (a rotation a mixer kind) with "
            "model.mrope_section: multimodal tables are one a model")
    if len(kinds) != len(params["layers"]):
        raise ValueError(
            f"the parameters hold {len(params['layers'])} blocks and the "
            f"configuration describes {len(kinds)}")
    # what a block left for later blocks (a mamba1 block's scan output, a
    # full_attention block's keys and values), by name; which block leaves
    # and which takes is the per-layer description's
    shares = cfg.block_shares()
    shared: Dict[str, jax.Array] = {}
    for i, lp in enumerate(params["layers"]):
        if boundary_fn is not None:
            x = boundary_fn(i, x)
        kwargs: Dict[str, Any] = dict(
            rope=ropes.get(kinds[i][0], rope), compute_dtype=compute_dtype,
            mixer=kinds[i][0],
            ops=(layer_overrides or {}).get(i, M.LayerOps()))
        if segment_ids is not None:
            kwargs["segment_ids"] = segment_ids
        if dropout_rng is not None:
            kwargs["dropout_rng"] = M.fold_dropout_rng(dropout_rng, cfg, i)
        if cfg.differential_attention:
            kwargs["lambda_init"] = M.diff_lambda_init(i)
        leaves, takes = shares[i]
        x, aux, stats, made = make_block(
            cfg.for_block(i), kinds[i], kwargs,
            remat_flags is not None and remat_flags[i],
            leaves=bool(leaves))(lp, x, {k: shared[k] for k in takes})
        # sharded as the stream is
        shared.update(made if boundary_fn is None else {
            k: boundary_fn(i, v) for k, v in made.items()})
        aux_total = aux_total + aux
        if stats:
            # per-layer balance tracker (reference moe_utils.py:547-644)
            moe_stats[f"layer{i}"] = stats
    if boundary_fn is not None:
        x = boundary_fn(len(params["layers"]), x)
    x = M.streams_out(x, cfg)
    if mtp_labels is not None:
        # the further prediction depth reads the stack's output before the
        # final norm; its block is the last block's kind, with the last
        # block's keyword arguments and remat flag
        if dropout_rng is not None:
            kwargs["dropout_rng"] = M.fold_dropout_rng(
                dropout_rng, cfg, len(params["layers"]))
        mtp_logits, aux, stats = forward_mtp(
            params, x, mtp_labels, cfg, block_fn=make_block(
                cfg.for_block(len(kinds) - 1), mtp_block_kind(cfg), kwargs,
                remat_flags is not None and remat_flags[-1]),
            compute_dtype=compute_dtype)
        aux_total = aux_total + aux
        if stats:
            moe_stats["mtp"] = stats
    x = M.block_norm(params["prenorm"], x, cfg)
    logits = M.apply_lm_head(
        params["head"], x, cfg,
        wte=params["embed"]["wte"], compute_dtype=compute_dtype,
    )
    logits = logits if logits_fp32 else logits.astype(compute_dtype)
    if mtp_labels is not None:
        return logits, aux_total, moe_stats, mtp_logits
    return (logits, aux_total, moe_stats) if with_aux else logits


def forward_mtp(params: Params, h: jax.Array, next_tokens: jax.Array,
                cfg: ModelArgs, *, block_fn: Callable,
                compute_dtype=jnp.bfloat16
                ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """The further prediction depth's logits [B, S, V] (DeepSeek-V3 2.2):
    ``h' = W_eh [RMSNorm(Emb(t_(i+1))) ; RMSNorm(h_i)]``, one more block, a
    norm, the model's own head. ``h`` [B, S, H] is the stack's output before
    the final norm and ``next_tokens`` [B, S] the token after each position
    (a batch's ``labels``). Position ``i`` predicts token ``i + 2``; the
    last position has no such token and the loss leaves it out, and under
    the causal mask it reaches no other. Returns (logits, the block's aux
    loss, its router stats)."""
    mp = params["mtp"]
    with jax.named_scope("mtp/embed_proj"):
        e = M.block_norm(mp["enorm"], M.apply_embedding(
            params["embed"], next_tokens, cfg, compute_dtype=compute_dtype),
            cfg)
        both = jnp.concatenate([e, M.block_norm(mp["hnorm"], h, cfg)],
                               axis=-1)
        x = jnp.einsum("bsk,kh->bsh", both.astype(compute_dtype),
                       M.weight_view(mp["eh_proj"], compute_dtype),
                       preferred_element_type=jnp.float32
                       ).astype(compute_dtype)
    with jax.named_scope("mtp/block"):
        x, aux, stats, _ = block_fn(mp["layer"], M.streams_in(x, cfg), {})
        x = M.streams_out(x, cfg)
    with jax.named_scope("mtp/head"):
        logits = M.apply_lm_head(
            params["head"], M.block_norm(mp["norm"], x, cfg), cfg,
            wte=params["embed"]["wte"], compute_dtype=compute_dtype)
    return logits, aux, stats


def causal_lm_loss(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelArgs,
    *,
    compute_dtype=jnp.bfloat16,
    remat_flags: Optional[Sequence[bool]] = None,
    layer_overrides: Optional[Dict[int, M.LayerOps]] = None,
    boundary_fn: Optional[Callable[[int, jax.Array], jax.Array]] = None,
    enc_remat_flags: Optional[Sequence[bool]] = None,
    enc_layer_overrides: Optional[Dict[int, M.LayerOps]] = None,
    enc_boundary_fn: Optional[Callable[[int, jax.Array], jax.Array]] = None,
    fused_ce: Union[None, bool, Callable] = None,
    with_moe_stats: bool = False,
    tower_remat_flags: Optional[Sequence[bool]] = None,
    tower_ops: Optional[M.LayerOps] = None,
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
    mtp = "mtp" in params
    logits, aux, moe_stats, *mtp_logits = forward_causal_lm(
        params, batch["tokens"], cfg,
        compute_dtype=compute_dtype, remat_flags=remat_flags,
        layer_overrides=layer_overrides, boundary_fn=boundary_fn,
        with_aux=True, dropout_rng=batch.get("dropout_rng"),
        position_ids=batch.get("position_ids"),
        segment_ids=batch.get("segment_ids"),
        mrope_position_ids=batch.get("mrope_position_ids"),
        mtp_labels=batch["labels"] if mtp else None,
        patches=batch.get("patches"), tower_remat_flags=tower_remat_flags,
        tower_ops=tower_ops,
    )
    ce = M.cross_entropy_loss(logits, batch["labels"], batch.get("loss_mask"),
                              fused=fused)
    loss = ce + aux
    if mtp:
        # position i of the further depth predicts token i + 2, which is
        # labels[i + 1]; the last position has none
        mask = batch.get("loss_mask")
        mask = (jnp.ones(batch["labels"].shape, jnp.float32) if mask is None
                else mask.astype(jnp.float32))
        with jax.named_scope("mtp/head"):
            loss = loss + cfg.mtp_loss_coeff * M.cross_entropy_loss(
                mtp_logits[0], jnp.roll(batch["labels"], -1, axis=1),
                mask.at[:, -1].set(0.0) * jnp.roll(mask, -1, axis=1),
                fused=fused)
    return (loss, moe_stats) if with_moe_stats else loss


def param_count(params: Params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))
