"""HuggingFace config → ModelArgs adapter.

Capability parity with the reference's hf_config_adapter
(utils/hf_config_adapter.py:196-393): populate our :class:`ModelArgs` from a HF
`AutoConfig` (or a plain dict of HF-style keys), auto-detecting norm type,
activation, rope, and GQA for llama/gpt2/qwen2/mistral/mixtral/olmoe families, and
expose `model_layer_configs`/`model_name` helpers for the profiler and search
engine.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs

# HF key → ModelArgs key, tried in order per field.
_FIELD_MAP = {
    "hidden_size": ["hidden_size", "n_embd", "d_model"],
    "num_hidden_layers": ["num_hidden_layers", "n_layer", "num_layers"],
    "num_attention_heads": ["num_attention_heads", "n_head", "num_heads"],
    "num_key_value_heads": ["num_key_value_heads", "num_kv_heads"],
    "ffn_hidden_size": ["intermediate_size", "n_inner", "ffn_dim", "d_ff"],
    "vocab_size": ["vocab_size"],
    "max_position_embeddings": ["max_position_embeddings", "n_positions", "n_ctx"],
    "layernorm_epsilon": ["rms_norm_eps", "layer_norm_epsilon", "layer_norm_eps"],
    "rope_theta": ["rope_theta"],
    "rope_scaling": ["rope_scaling"],
    # decoupled head dim (gemma-7b, mistral-nemo, ...); None skipped
    "head_dim_override": ["head_dim"],
    "tie_word_embeddings": ["tie_word_embeddings"],
    "num_experts": ["num_local_experts", "num_experts"],
    "moe_topk": ["num_experts_per_tok"],
}

_GEMMA_FAMILIES = {"gemma"}
_LFM2_FAMILIES = {"lfm2", "lfm2_moe"}
_GRANITE_HYBRID = "granitemoehybrid"
# DeepSeek-V3's keys (latent attention, shared beside sigmoid-routed experts,
# multi-token prediction) with the residual as several streams
_XING = "xing4_0"
# Kimi Linear (moonshotai; ``modeling_kimi.py``): Kimi Delta Attention and
# latent-attention blocks by number, DeepSeek-V3's expert layer
_KIMI_LINEAR = "kimi_linear"
# Kimi-VL (moonshotai; ``KimiVLConfig``): ``text_config`` holds
# DeepseekV3Config's keys (latent attention without a low-rank query, shared
# beside sigmoid-routed experts), ``vision_config`` MoonViTConfig's (the
# tower of image patches in front of it, models/tower.py)
_KIMI_VL = "kimi_vl"
# Laguna (poolside; ``LagunaConfig``): window and full attention blocks by
# ``layer_types`` with query heads, a rotation and a gate a head of their
# own, a dense block then softmax-free top-k experts beside a shared one
_LAGUNA = "laguna"
# Mellum 2 (JetBrains; Qwen3-MoE's keys with ``layer_types``,
# ``mlp_layer_types`` and ``rope_parameters`` by kind): window and full
# attention blocks with a q/k norm a head, softmax top-k experts in every
# block, no shared expert
_MELLUM = "mellum"
# Phi-4-mini-flash-reasoning (microsoft; ``modeling_phi4flash.py``, SambaY,
# arXiv:2507.06607): Mamba-1 and window-attention blocks in turn, then one
# Mamba-1 and one full-attention block whose scan output and keys and values
# the second half's gated memory units and cross-attention blocks read;
# differential attention; LayerNorms; no positions
_PHI4FLASH = "phi4flash"
# NemotronH (nvidia; ``model_type`` ``nemotron_h``): blocks of ONE branch by
# ``hybrid_override_pattern`` (M a Mamba-2 mixer with groups of B and C, *
# attention without positions, - an ungated squared-ReLU MLP, E
# sigmoid-routed experts of the same beside a shared one)
_NEMOTRON_H = "nemotron_h"
# Olmo Hybrid (allenai; ``model_type`` ``olmo_hybrid``): Gated DeltaNet
# blocks (``linear_attention``, HF ``Qwen3NextGatedDeltaNet``'s ``linear_*``
# keys) three to one with Olmo 3's attention block (an RMSNorm over the whole
# q and k widths, the block's norms on its branches' outputs), dense SwiGLU
_OLMO_HYBRID = "olmo_hybrid"
# families that state for themselves whether they have positions
_OWN_POSITIONS = {_GRANITE_HYBRID, _KIMI_LINEAR, _PHI4FLASH, _NEMOTRON_H,
                  _OLMO_HYBRID}
_ROPE_FAMILIES = {"llama", "qwen2", "mistral", "mixtral", "olmoe",
                  "qwen", _XING, _LAGUNA, _MELLUM, _KIMI_VL} \
    | _GEMMA_FAMILIES \
    | _LFM2_FAMILIES
_RMS_FAMILIES = _ROPE_FAMILIES | {"t5", _GRANITE_HYBRID, _KIMI_LINEAR,
                                  _NEMOTRON_H, _OLMO_HYBRID}
_SWIGLU_FAMILIES = {"llama", "qwen2", "mistral", "mixtral", "olmoe",
                    "qwen", _GRANITE_HYBRID, _XING, _KIMI_LINEAR,
                    _LAGUNA, _MELLUM, _KIMI_VL, _PHI4FLASH,
                    _OLMO_HYBRID} | _LFM2_FAMILIES
# gemma-2/3 add sandwich norms (a norm after each sub-layer as well as
# before it), logit softcapping (attention and final) and a softmax scale
# from query_pre_attn_scalar; v3 also a q/k norm a head with zero-centred
# scales. Their alternating sliding windows and v3's two rotations ARE run
# (``layer_types`` with "sliding_attention", ``rope_parameters``: the
# ``laguna`` family); the rest is not, and mapping them through gemma-1
# numerics would silently produce wrong logits, so they are refused by name
_UNSUPPORTED_FAMILIES = {"gemma2", "gemma3", "gemma3_text"}


def _cfg_to_dict(config: Any) -> Dict[str, Any]:
    if isinstance(config, dict):
        return config
    if hasattr(config, "to_dict"):
        return config.to_dict()
    return vars(config)


def populate_model_args_from_hf(
    config: Any, base: Optional[ModelArgs] = None
) -> ModelArgs:
    """Build ModelArgs from a HF config object/dict, auto-detecting family."""
    d = _cfg_to_dict(config)
    family = str(d.get("model_type", "gpt2")).lower()
    if family in _UNSUPPORTED_FAMILIES:
        raise NotImplementedError(
            f"model family {family!r} has architecture features this stack "
            "does not implement (sandwich norms around each sub-layer, "
            "attention and final logit softcapping, a softmax scale from "
            "query_pre_attn_scalar; its sliding windows are not the "
            "obstacle: layer_types with sliding_attention blocks are run); "
            "refusing rather than producing silently-wrong numerics")
    if family == _KIMI_VL:
        # the decoder's keys are a group of their own; read them where the
        # other families' lie, the tower's group beside them
        d = {**d, **_cfg_to_dict(d["text_config"]), "model_type": family}
    values: Dict[str, Any] = dict(base.model_dump() if base else {})
    for ours, theirs in _FIELD_MAP.items():
        for key in theirs:
            if key in d and d[key] is not None:
                values[ours] = d[key]
                break
    values["model_name"] = d.get("_name_or_path", family) or family
    if family == "bert":
        values["model_type"] = "bert"
    elif family == "t5":
        values["model_type"] = "t5"
    else:
        values["model_type"] = "moe" if values.get("num_experts", 0) else (
            "llama" if family in _ROPE_FAMILIES else "gpt"
        )
    values["normalization"] = "rmsnorm" if family in _RMS_FAMILIES else "layernorm"
    values["hidden_act"] = "swiglu" if family in _SWIGLU_FAMILIES else "gelu"
    if family in _GEMMA_FAMILIES:
        # gemma numerics: gated-gelu MLP, RMSNorm x*(1+w), sqrt(H)-scaled
        # embeddings (head_dim comes via the shared field map)
        values["hidden_act"] = "geglu"
        values["norm_zero_centered"] = True
        values["scale_embeddings"] = True
    if family == "olmoe":
        # OLMoE (Muennighoff et al., arXiv:2409.02060; HF modeling_olmoe):
        # ``intermediate_size`` is the width of ONE expert (the shared field
        # map reads it as ffn_hidden_size, which an expert layer uses when
        # moe_ffn_hidden_size is unset); RMSNorm over the whole q and k
        # widths; top-k router weights renormalised only if the config says
        # so; its own expert names in a checkpoint
        if d.get("clip_qkv") is not None:
            raise NotImplementedError(
                f"olmoe clip_qkv={d['clip_qkv']!r}: clamping the q/k/v "
                "projections is not implemented; refusing rather than "
                "producing silently-wrong numerics")
        values["qk_norm"] = True
        values["moe_hf_layout"] = "olmoe"
        values["moe_norm_topk_prob"] = bool(d.get("norm_topk_prob", False))
        if d.get("router_aux_loss_coef") is not None:
            values["moe_aux_loss_coeff"] = float(d["router_aux_loss_coef"])
    if family in _LFM2_FAMILIES:
        # LFM2 (LiquidAI; HF modeling_lfm2): conv and attention blocks by
        # ``layer_types``, the q/k RMSNorm per head, its own names for the
        # norms, the mixers and the MLP; ``lfm2_moe`` adds leading dense
        # blocks, then sigmoid-routed experts with a selection bias
        if d.get("layer_types") is None:
            raise NotImplementedError(
                f"{family}: config.json names no layer_types (which blocks "
                "are conv and which attend)")
        values.update(
            hf_layout="lfm2", moe_hf_layout="lfm2", qk_norm=True,
            qk_norm_per_head=True, layer_types=list(d["layer_types"]),
            layernorm_epsilon=float(d.get("norm_eps", 1e-5)),
            conv_L_cache=int(d.get("conv_L_cache", 3)),
            conv_bias=bool(d.get("conv_bias", False)),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", True)))
        theta = (d.get("rope_parameters") or {}).get("rope_theta")
        if theta is not None:
            values["rope_theta"] = float(theta)
        if family == "lfm2" and d.get("block_auto_adjust_ff_dim", True):
            # HF Lfm2MLP: two thirds of intermediate_size, times the
            # multiplier, rounded up to block_multiple_of
            ffn = int(2 * values["ffn_hidden_size"] / 3)
            if d.get("block_ffn_dim_multiplier") is not None:
                ffn = int(d["block_ffn_dim_multiplier"] * ffn)
                m = int(d.get("block_multiple_of", 256))
                ffn = m * ((ffn + m - 1) // m)
            values["ffn_hidden_size"] = ffn
        if family == "lfm2_moe":
            values.update(
                num_dense_layers=int(d.get("num_dense_layers", 0)),
                moe_ffn_hidden_size=d.get("moe_intermediate_size"),
                moe_score_function="sigmoid", moe_dispatcher="dropless",
                moe_norm_topk_prob=bool(d.get("norm_topk_prob", True)),
                moe_routed_scaling_factor=float(
                    d.get("routed_scaling_factor", 1.0)),
                moe_router_enable_expert_bias=bool(
                    d.get("use_expert_bias", True)),
                # trained with cross-entropy alone: no balancing-loss key
                moe_aux_loss_coeff=0.0)
    if family == _GRANITE_HYBRID:
        values.update(_granite_hybrid_values(d))
    if family == _XING:
        values.update(_xing_values(d))
    if family == _KIMI_LINEAR:
        values.update(_kimi_linear_values(d))
    if family == _KIMI_VL:
        values.update(_kimi_vl_values(d))
    if family == _LAGUNA:
        values.update(_laguna_values(d))
    if family == _MELLUM:
        values.update(_mellum_values(d))
    if family == _PHI4FLASH:
        values.update(_phi4flash_values(d))
    if family == _NEMOTRON_H:
        values.update(_nemotron_h_values(d))
    if family == _OLMO_HYBRID:
        values.update(_olmo_hybrid_values(d))
    if family == "bert":
        # HF bert uses erf gelu everywhere (BertIntermediate + the MLM
        # transform); our "gelu" is the tanh approximation (gpt2's gelu_new)
        values["hidden_act"] = "gelu_exact"
    if family == "t5":
        # HF t5: num_layers = ENCODER depth, num_decoder_layers = decoder;
        # act is relu (v1.0) or gated-gelu (v1.1)
        if d.get("num_layers") is not None:
            values["num_encoder_layers"] = d["num_layers"]
            values["num_hidden_layers"] = d.get("num_decoder_layers",
                                                d["num_layers"])
        ff = str(d.get("feed_forward_proj", "relu"))
        values["hidden_act"] = "geglu" if "gated" in ff else "relu"
        values["tie_word_embeddings"] = bool(d.get("tie_word_embeddings",
                                                   True))
    if family not in _OWN_POSITIONS:
        values["position_embedding_type"] = (
            "rope" if family in _ROPE_FAMILIES else "learned"
        )
    scaling = values.get("rope_scaling")
    if isinstance(scaling, dict) and "mrope_section" in scaling:
        # qwen2-vl style multimodal rope: rope_scaling carries the section
        # split (type "mrope"/"default"), not a frequency-scaling recipe —
        # route it to mrope_section so _scale_inv_freq never sees it
        values["mrope_section"] = list(scaling["mrope_section"])
        rest = {k: v for k, v in scaling.items()
                if k not in ("mrope_section", "type", "rope_type")}
        values["rope_scaling"] = rest or None
    # bias detection (reference hf_config_adapter.py:196-290 reads
    # attention_bias / mlp_bias / family defaults)
    # llama-likes and t5 default to no biases
    bias_free = _ROPE_FAMILIES | {"t5", _GRANITE_HYBRID, _KIMI_LINEAR,
                                  _NEMOTRON_H, _OLMO_HYBRID}
    # (_LAGUNA is one of _ROPE_FAMILIES)
    if "attention_bias" in d:
        values["add_qkv_bias"] = bool(d["attention_bias"])
    elif family in {"qwen", "qwen2"}:
        values["add_qkv_bias"] = True  # qwen2 has qkv bias, no mlp bias
    else:
        values["add_qkv_bias"] = family not in bias_free
    if "mlp_bias" in d:
        values["add_bias_linear"] = bool(d["mlp_bias"])
    else:
        values["add_bias_linear"] = family not in bias_free
    return ModelArgs.model_validate(values)


def _olmo_hybrid_values(d: Dict[str, Any]) -> Dict[str, Any]:
    """Olmo Hybrid: ``layer_types`` names each block ``linear_attention`` (a
    Gated DeltaNet block, the ``linear_*`` keys) or ``full_attention``
    (Olmo 3's block). ``rope_parameters.rope_theta`` null is no rotation
    anywhere; a number there is a rotation of the attending blocks. The
    norms' placement has no key: the attention block's on its branches'
    outputs is the family's (HF ``Olmo3DecoderLayer``), the linear block's
    on the inputs the released block's."""
    family = _OLMO_HYBRID
    types = d.get("layer_types")
    unknown = sorted(set(types or ()) - {"linear_attention",
                                         "full_attention"})
    if types is None or unknown:
        raise NotImplementedError(
            f"{family} layer_types={'none' if types is None else unknown}: "
            "linear_attention and full_attention are implemented")
    if d.get("hidden_act", "silu") != "silu":
        raise NotImplementedError(
            f"{family} hidden_act={d['hidden_act']!r}: silu (SwiGLU) is "
            "implemented")
    rope = d.get("rope_parameters") or {}
    if (d.get("sliding_window") or d.get("rope_scaling")
            or rope.get("rope_type", "default") != "default"):
        raise NotImplementedError(
            f"{family}: a sliding window or a scaled rotation is not "
            "written for this family (it publishes neither)")
    theta = rope.get("rope_theta", d.get("rope_theta"))
    out: Dict[str, Any] = dict(
        model_type="llama", hf_layout="olmo_hybrid", num_experts=0,
        layer_types=list(types), qk_norm=True,
        norm_positions={"linear_attention": "pre",
                        "full_attention": "branch"},
        position_embedding_type="nope" if theta is None else "rope",
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        linear_num_key_heads=int(d["linear_num_key_heads"]),
        linear_num_value_heads=int(d["linear_num_value_heads"]),
        linear_key_head_dim=int(d["linear_key_head_dim"]),
        linear_value_head_dim=int(d["linear_value_head_dim"]),
        linear_conv_kernel_dim=int(d.get("linear_conv_kernel_dim", 4)),
        linear_allow_neg_eigval=bool(d.get("linear_allow_neg_eigval",
                                           False)))
    if theta is not None:
        out["rope_theta"] = float(theta)
    return out


def _xing_values(d: Dict[str, Any]) -> Dict[str, Any]:
    """Xing4.0 (XingChen-AGI; ``DeepseekV3Config``'s keys and the ``hc_*`` /
    ``mhc_*`` ones): latent attention in every block, ``first_k_dense_replace``
    leading dense blocks, then ``n_routed_experts`` sigmoid-routed experts
    beside ``n_shared_experts`` shared ones, the residual as ``hc_mult``
    streams, ``num_nextn_predict_layers`` further prediction depths."""
    if int(d.get("n_group") or 1) != 1 or int(d.get("topk_group") or 1) != 1:
        raise NotImplementedError(
            f"{_XING} n_group={d.get('n_group')} topk_group="
            f"{d.get('topk_group')}: the group-limited choice of experts is "
            "not implemented (one group is)")
    if d.get("scoring_func", "sigmoid") != "sigmoid" or d.get(
            "topk_method", "noaux_tc") != "noaux_tc":
        raise NotImplementedError(
            f"{_XING} scoring_func={d.get('scoring_func')!r} topk_method="
            f"{d.get('topk_method')!r}: sigmoid scores with the selection "
            "bias (noaux_tc) are implemented")
    n = int(d["num_hidden_layers"])
    return dict(
        model_type="moe", hf_layout="llama", moe_hf_layout="deepseek",
        layer_types=["latent_attention"] * n,
        num_dense_layers=int(d.get("first_k_dense_replace", 0)),
        q_lora_rank=(None if d.get("q_lora_rank") is None
                     else int(d["q_lora_rank"])),
        kv_lora_rank=int(d["kv_lora_rank"]),
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        moe_ffn_hidden_size=int(d["moe_intermediate_size"]),
        num_experts=int(d["n_routed_experts"]),
        num_shared_experts=int(d.get("n_shared_experts") or 0),
        moe_layer_freq=int(d.get("moe_layer_freq", 1)),
        moe_score_function="sigmoid", moe_dispatcher="dropless",
        moe_norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        moe_norm_topk_eps=1e-20,
        moe_routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        moe_router_enable_expert_bias=True, moe_aux_loss_coeff=0.0,
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        hc_mult=int(d.get("hc_mult", 1)),
        hc_sinkhorn_iters=int(d.get("hc_sinkhorn_iters", 20)),
        hc_eps=float(d.get("hc_eps", 1e-6)),
        hc_res_clamp_min=float(d.get("mhc_h_res_clamp_min", -30.0)),
        hc_res_clamp_max=float(d.get("mhc_h_res_clamp_max", 30.0)),
        num_nextn_predict_layers=int(d.get("num_nextn_predict_layers", 0)))


def _kimi_vl_values(d: Dict[str, Any]) -> Dict[str, Any]:
    """Kimi-VL (``KimiVLConfig``): the decoder is DeepSeek-V3's
    (:func:`_xing_values` with one residual stream and no further depth),
    the tower ``vision_config``'s (``MoonViTConfig``: ``num_hidden_layers``
    blocks of ``hidden_size``, a ``patch_size`` square of 3 channels a
    patch, a position table of ``init_pos_emb_height x
    init_pos_emb_width``, ``merge_kernel_size``), and
    ``media_placeholder_token_id`` marks an image position. A key the
    file does not hold raises: no size of the tower is filled in here."""
    v = _cfg_to_dict(d["vision_config"])
    return dict(
        _xing_values(d),
        tower_layers=int(v["num_hidden_layers"]),
        tower_hidden_size=int(v["hidden_size"]),
        tower_num_heads=int(v["num_attention_heads"]),
        tower_ffn_hidden_size=int(v["intermediate_size"]),
        tower_patch_size=int(v["patch_size"]),
        tower_pos_emb_height=int(v["init_pos_emb_height"]),
        tower_pos_emb_width=int(v["init_pos_emb_width"]),
        tower_merge_kernel=list(v["merge_kernel_size"]),
        image_token_id=int(d["media_placeholder_token_id"]))


def _laguna_values(d: Dict[str, Any]) -> Dict[str, Any]:
    """Laguna (``LagunaConfig``): ``layer_types`` names each block
    "full_attention" or "sliding_attention" (the ``sliding_window`` newest
    keys), ``num_attention_heads_per_layer`` its query heads,
    ``rope_parameters`` a rotation a kind, ``gating`` the sigmoid gate a
    head; ``mlp_only_layers`` (leading) dense blocks, then ``num_experts``
    experts of ``moe_intermediate_size`` at top ``num_experts_per_tok``,
    weights renormalised and times ``moe_routed_scaling_factor``, beside a
    shared expert of ``shared_expert_intermediate_size``."""
    family = _LAGUNA
    n = int(d["num_hidden_layers"])
    types = d.get("layer_types")
    if types is None or set(types) - {"full_attention", "sliding_attention"}:
        raise NotImplementedError(
            f"{family} layer_types={types!r}: full_attention and "
            "sliding_attention blocks, named one by one, are implemented")
    dense = sorted(d.get("mlp_only_layers") or ())
    if dense != list(range(len(dense))) or int(
            d.get("decoder_sparse_step", 1)) != 1:
        raise NotImplementedError(
            f"{family} mlp_only_layers={dense} decoder_sparse_step="
            f"{d.get('decoder_sparse_step')}: leading dense blocks, then "
            "experts in every block, are implemented")
    gating = d.get("gating")
    if gating not in (None, False, True, "per-head", "per_head"):
        raise NotImplementedError(
            f"{family} gating={gating!r}: a sigmoid gate a head (per-head) "
            "is implemented")
    if set(d.get("gating_types") or ()) - {"per_head"}:
        raise NotImplementedError(
            f"{family} gating_types={sorted(set(d['gating_types']))}: every "
            "block's gate a head (per_head) is implemented")
    if float(d.get("moe_router_logit_softcapping") or 0.0):
        raise NotImplementedError(
            f"{family} moe_router_logit_softcapping="
            f"{d['moe_router_logit_softcapping']}: the router's logits "
            "uncapped (0) are implemented")
    if d.get("moe_apply_router_weight_on_input", False):
        raise NotImplementedError(
            f"{family} moe_apply_router_weight_on_input: the router's weight "
            "on an expert's OUTPUT is implemented")
    moe_ffn = int(d["moe_intermediate_size"])
    shared = int(d.get("shared_expert_intermediate_size") or 0)
    if shared % moe_ffn:
        raise NotImplementedError(
            f"{family} shared_expert_intermediate_size={shared}: a shared "
            f"expert of whole experts' width ({moe_ffn}) is implemented")
    out: Dict[str, Any] = dict(
        model_type="moe", hf_layout="llama", moe_hf_layout="laguna",
        layer_types=list(types), num_dense_layers=len(dense),
        moe_ffn_hidden_size=moe_ffn, num_shared_experts=shared // moe_ffn,
        moe_layer_freq=1, moe_score_function="sigmoid",
        moe_dispatcher="dropless",
        moe_norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        moe_norm_topk_eps=1e-20,
        moe_routed_scaling_factor=float(
            d.get("moe_routed_scaling_factor", 1.0)),
        moe_router_enable_expert_bias=False, moe_aux_loss_coeff=0.0,
        gating="per-head" if gating else None,
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)))
    if "sliding_attention" in types:
        out["sliding_window"] = int(d["sliding_window"])
    if d.get("num_attention_heads_per_layer") is not None:
        out["num_attention_heads_per_layer"] = [
            int(h) for h in d["num_attention_heads_per_layer"]]
    if len(types) != n:
        raise ValueError(f"{family}: layer_types names {len(types)} blocks "
                         f"and num_hidden_layers is {n}")
    rope = d.get("rope_parameters") or {}
    by_kind = {k: dict(v) for k, v in rope.items()
               if k in ("full_attention", "sliding_attention")}
    if by_kind:
        out["rope_parameters"] = by_kind
    return out


def _mellum_values(d: Dict[str, Any]) -> Dict[str, Any]:
    """Mellum 2 (``model_type: mellum``): ``layer_types`` names each block
    "full_attention" or "sliding_attention" (the ``sliding_window`` newest
    keys) and rules where ``max_window_layers`` / ``use_sliding_window``
    would say something else, ``rope_parameters`` a rotation a kind;
    ``mlp_layer_types`` "sparse" in every block: ``num_experts`` experts of
    ``moe_intermediate_size`` at top ``num_experts_per_tok``, softmax
    weights renormalised where ``norm_topk_prob``; llama's attention names
    with a q/k RMSNorm a head (Qwen3's ``q_norm`` / ``k_norm``, assumed: no
    key switches it), OLMoE's expert names."""
    family = _MELLUM
    n = int(d["num_hidden_layers"])
    types = d.get("layer_types")
    if types is None or set(types) - {"full_attention", "sliding_attention"}:
        raise NotImplementedError(
            f"{family} layer_types={types!r}: full_attention and "
            "sliding_attention blocks, named one by one, are implemented")
    if len(types) != n:
        raise ValueError(f"{family}: layer_types names {len(types)} blocks "
                         f"and num_hidden_layers is {n}")
    feeds = d.get("mlp_layer_types") or ["sparse"] * n
    if set(feeds) - {"sparse"} or len(feeds) != n:
        raise NotImplementedError(
            f"{family} mlp_layer_types={sorted(set(feeds))} over "
            f"{len(feeds)} of {n} blocks: experts (sparse) in every block "
            "are implemented; the config gives no rule for which width "
            "(intermediate_size) a dense block would take")
    out: Dict[str, Any] = dict(
        model_type="moe", hf_layout="llama", moe_hf_layout="olmoe",
        layer_types=list(types), num_dense_layers=0, moe_layer_freq=1,
        moe_ffn_hidden_size=int(d["moe_intermediate_size"]),
        qk_norm=True, qk_norm_per_head=True,
        moe_score_function="softmax", moe_dispatcher="dropless",
        moe_norm_topk_prob=bool(d.get("norm_topk_prob", False)),
        # trained with cross-entropy alone where the config has no key
        moe_aux_loss_coeff=float(d.get("router_aux_loss_coef") or 0.0),
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)))
    if "sliding_attention" in types:
        out["sliding_window"] = int(d["sliding_window"])
    rope = d.get("rope_parameters") or {}
    by_kind = {k: dict(v) for k, v in rope.items()
               if k in ("full_attention", "sliding_attention")}
    if by_kind:
        out["rope_parameters"] = by_kind
    return out


def phi4flash_layer_types(n: int, mb_per_layer: int = 2) -> List[str]:
    """The kinds of a ``phi4flash`` stack of ``n`` blocks
    (``configuration_phi4flash.py``; SambaY, arXiv:2507.06607): a Mamba-1
    block at every ``mb_per_layer``-th index; in the first half window
    attention between them; block ``n / 2`` is the Mamba-1 block whose scan
    output is kept and ``n / 2 + 1`` the full attention whose keys and
    values are kept; after them gated memory units at the Mamba indices and
    cross-attention between."""
    if mb_per_layer != 2 or n % 2 or n < 4:
        raise NotImplementedError(
            f"{_PHI4FLASH} mb_per_layer={mb_per_layer} over {n} blocks: a "
            "Mamba block at every other index of an even stack of at least "
            "four is implemented")
    half = n // 2
    return [("mamba1" if i <= half else "gmu") if i % 2 == 0
            else "sliding_attention" if i < half
            else "full_attention" if i == half + 1 else "cross_attention"
            for i in range(n)]


def _phi4flash_values(d: Dict[str, Any]) -> Dict[str, Any]:
    """``config.json`` gives the sizes; the state-space sizes are the
    family's defaults (``configuration_phi4flash.py``: ``mamba_d_state`` 16,
    ``mamba_d_conv`` 4, ``mamba_expand`` 2, ``mamba_dt_rank`` "auto" =
    ceil(hidden_size / 16))."""
    rank = d.get("mamba_dt_rank", "auto")
    return dict(
        model_type="llama", hf_layout="phi4flash", num_experts=0, moe_topk=2,
        position_embedding_type="nope", normalization="layernorm",
        layer_types=phi4flash_layer_types(int(d["num_hidden_layers"]),
                                          int(d.get("mb_per_layer", 2))),
        sliding_window=int(d["sliding_window"]),
        differential_attention=True, add_attn_out_bias=True,
        mamba1_d_state=int(d.get("mamba_d_state", 16)),
        mamba1_d_conv=int(d.get("mamba_d_conv", 4)),
        mamba1_expand=int(d.get("mamba_expand", 2)),
        mamba1_dt_rank=None if rank in (None, "auto") else int(rank),
        tie_word_embeddings=bool(d.get("tie_word_embeddings", True)))


# ``hybrid_override_pattern``'s letters -> the words of ``layer_types``
NEMOTRON_H_PATTERN = {"M": "mamba", "*": "full_attention", "-": "dense",
                      "E": "experts"}


def nemotron_h_layer_types(pattern: str) -> List[str]:
    """``hybrid_override_pattern`` a letter a block -> ``layer_types``: a
    stack of one-branch blocks (``ModelArgs.block_kinds``)."""
    unknown = sorted(set(pattern) - set(NEMOTRON_H_PATTERN))
    if unknown:
        raise NotImplementedError(
            f"nemotron_h hybrid_override_pattern holds {unknown}: "
            f"{' '.join(NEMOTRON_H_PATTERN)} are implemented")
    return [NEMOTRON_H_PATTERN[c] for c in pattern]


def _nemotron_h_values(d: Dict[str, Any]) -> Dict[str, Any]:
    """NemotronH's keys. A shared expert is stated as a multiple of the
    routed width (``moe_shared_expert_intermediate_size`` over
    ``moe_intermediate_size``, 3712 / 1856 = 2 as published); attention has
    no positions (arXiv:2504.03624 2.1: ``rope_theta`` is read by no
    layer); ``mamba_proj_bias`` / ``use_bias`` true is refused where the
    block is built."""
    family = _NEMOTRON_H
    pattern = d.get("hybrid_override_pattern")
    if not pattern:
        raise NotImplementedError(
            f"{family}: config.json names no hybrid_override_pattern")
    types = nemotron_h_layer_types(pattern)
    if len(types) != int(d["num_hidden_layers"]):
        raise ValueError(
            f"{family}: hybrid_override_pattern names {len(types)} blocks "
            f"and num_hidden_layers is {d['num_hidden_layers']}")
    act = d.get("mlp_hidden_act", "relu2")
    if act != "relu2" or d.get("mamba_hidden_act", "silu") != "silu":
        raise NotImplementedError(
            f"{family} mlp_hidden_act={act!r}, mamba_hidden_act="
            f"{d.get('mamba_hidden_act')!r}: relu2 and silu are implemented")
    if d.get("n_group", 1) != 1 or d.get("topk_group", 1) != 1:
        raise NotImplementedError(
            f"{family} n_group={d.get('n_group')}, topk_group="
            f"{d.get('topk_group')}: routing over groups of experts is not "
            "implemented (the published model has one group)")
    out: Dict[str, Any] = dict(
        model_type="moe" if "experts" in types else "llama",
        hf_layout="nemotron_h", hidden_act="relu2",
        position_embedding_type="nope", layer_types=types,
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        mamba_n_heads=int(d["mamba_num_heads"]),
        mamba_d_head=int(d["mamba_head_dim"]),
        mamba_d_state=int(d.get("ssm_state_size", 128)),
        mamba_n_groups=int(d.get("n_groups", 8)),
        mamba_d_conv=int(d.get("conv_kernel", 4)),
        mamba_chunk_size=int(d.get("chunk_size", 128)),
        mamba_conv_bias=bool(d.get("use_conv_bias", True)),
        mamba_proj_bias=bool(d.get("mamba_proj_bias",
                                   d.get("use_bias", False))))
    if "experts" in types:
        width = int(d["moe_intermediate_size"])
        shared = int(d.get("n_shared_experts", 0)) * int(
            d.get("moe_shared_expert_intermediate_size", width))
        if shared % width:
            raise NotImplementedError(
                f"{family}: a shared expert of {shared} is no multiple of "
                f"the routed width {width} (model.num_shared_experts counts "
                "routed widths)")
        out.update(
            num_experts=int(d["n_routed_experts"]),
            moe_ffn_hidden_size=width, num_shared_experts=shared // width,
            moe_score_function="sigmoid", moe_dispatcher="dropless",
            moe_norm_topk_prob=bool(d.get("norm_topk_prob", True)),
            moe_norm_topk_eps=1e-20,
            moe_routed_scaling_factor=float(
                d.get("routed_scaling_factor", 1.0)),
            moe_router_enable_expert_bias=True,
            # trained with cross-entropy alone: no balancing-loss key
            moe_aux_loss_coeff=0.0)
    return out


def _kimi_linear_values(d: Dict[str, Any]) -> Dict[str, Any]:
    """Kimi Linear (``modeling_kimi.py``): ``linear_attn_config`` numbers
    the blocks from 1, ``kda_layers`` those that run Kimi Delta Attention
    and ``full_attn_layers`` those that run latent attention (no low-rank
    query where ``q_lora_rank`` is null; no rotation where ``mla_use_nope``);
    ``first_k_dense_replace`` leading dense blocks, then ``num_experts``
    sigmoid-routed experts beside ``num_shared_experts`` shared ones."""
    family = _KIMI_LINEAR
    if (int(d.get("num_expert_group") or 1) != 1
            or int(d.get("topk_group") or 1) != 1):
        raise NotImplementedError(
            f"{family} num_expert_group={d.get('num_expert_group')} "
            f"topk_group={d.get('topk_group')}: the group-limited choice of "
            "experts is not implemented (one group is)")
    if d.get("moe_router_activation_func", "sigmoid") != "sigmoid":
        raise NotImplementedError(
            f"{family} moe_router_activation_func="
            f"{d.get('moe_router_activation_func')!r}: sigmoid scores with "
            "the selection bias are implemented")
    if not d.get("mla_use_nope", False):
        raise NotImplementedError(
            f"{family} mla_use_nope false: the latent blocks of this family "
            "are written without positions, as it publishes them")
    if int(d.get("num_nextn_predict_layers") or 0):
        raise NotImplementedError(
            f"{family} num_nextn_predict_layers="
            f"{d['num_nextn_predict_layers']}: the family publishes 0")
    lin = d.get("linear_attn_config") or {}
    n = int(d["num_hidden_layers"])
    kda = set(lin.get("kda_layers") or ())
    full = set(lin.get("full_attn_layers") or ())
    if kda & full or kda | full != set(range(1, n + 1)):
        raise ValueError(
            f"{family}: linear_attn_config.kda_layers and full_attn_layers "
            f"have to number each of the {n} blocks, from 1, exactly once "
            f"(got {sorted(kda)} and {sorted(full)})")
    return dict(
        model_type="moe", hf_layout="llama", moe_hf_layout="kimi",
        position_embedding_type="nope",
        layer_types=["kda" if i + 1 in kda else "latent_attention"
                     for i in range(n)],
        kda_num_heads=int(lin["num_heads"]),
        kda_head_dim=int(lin["head_dim"]),
        kda_conv_kernel=int(lin.get("short_conv_kernel_size", 4)),
        num_dense_layers=int(d.get("first_k_dense_replace", 0)),
        q_lora_rank=d.get("q_lora_rank"),
        kv_lora_rank=int(d["kv_lora_rank"]),
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]),
        moe_ffn_hidden_size=int(d["moe_intermediate_size"]),
        num_experts=int(d["num_experts"]),
        moe_topk=int(d["num_experts_per_token"]),
        num_shared_experts=int(d.get("num_shared_experts") or 0),
        moe_layer_freq=int(d.get("moe_layer_freq", 1)),
        moe_score_function="sigmoid", moe_dispatcher="dropless",
        moe_norm_topk_prob=bool(d.get("moe_renormalize", True)),
        moe_norm_topk_eps=1e-20,
        moe_routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        moe_router_enable_expert_bias=True, moe_aux_loss_coeff=0.0,
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        **({"max_position_embeddings": int(d["model_max_length"])}
           if d.get("model_max_length") else {}))


def _granite_hybrid_values(d: Dict[str, Any]) -> Dict[str, Any]:
    """Granite-4.0-H (IBM; HF modeling_granitemoehybrid): Mamba-2 blocks
    (Bamba's mixer) and attention blocks by ``layer_types``, the shared
    SwiGLU MLP in every block, positions from nowhere (``nope``) or from
    RoPE, and four stated multipliers. The published words ``mamba`` and
    ``attention`` become the program's ``mamba`` and ``full_attention``."""
    family = _GRANITE_HYBRID
    if int(d.get("num_local_experts") or 0) > 0:
        raise NotImplementedError(
            f"{family} num_local_experts={d['num_local_experts']}: a block "
            "whose feed-forward adds routed experts to the shared MLP is not "
            "implemented (shared experts beside routed ones)")
    # HF builds a rotary table only for "rope"; an absent key is no positions
    pos = d.get("position_embedding_type") or "nope"
    if pos not in ("nope", "rope"):
        raise NotImplementedError(
            f"{family} position_embedding_type={pos!r}: nope (no positions) "
            "and rope are implemented")
    types = d.get("layer_types") or d.get("layers_block_type")
    if types is None:
        raise NotImplementedError(
            f"{family}: config.json names no layer_types (which blocks are "
            "mamba and which attend)")
    words = {"mamba": "mamba", "attention": "full_attention"}
    unknown = sorted(set(types) - set(words))
    if unknown:
        raise NotImplementedError(
            f"{family} layer_types holds {unknown}: mamba and attention "
            "are implemented")
    heads, d_head = int(d["mamba_n_heads"]), d.get("mamba_d_head", "auto")
    inner = int(d.get("mamba_expand", 2)) * int(d["hidden_size"])
    if d_head in (None, "auto"):
        d_head = inner // heads
    if heads * int(d_head) != inner:
        raise ValueError(
            f"{family}: mamba_n_heads {heads} x mamba_d_head {d_head} is "
            f"not mamba_expand x hidden_size = {inner}")
    out: Dict[str, Any] = dict(
        model_type="llama", hf_layout="granite", num_experts=0, moe_topk=2,
        position_embedding_type=pos,
        layer_types=[words[t] for t in types],
        ffn_hidden_size=int(d.get("shared_intermediate_size")
                            or d["intermediate_size"]),
        tie_word_embeddings=bool(d.get("tie_word_embeddings", True)),
        mamba_n_heads=heads, mamba_d_head=int(d_head),
        mamba_d_state=int(d.get("mamba_d_state", 256)),
        mamba_n_groups=int(d.get("mamba_n_groups", 1)),
        mamba_d_conv=int(d.get("mamba_d_conv", 4)),
        mamba_chunk_size=int(d.get("mamba_chunk_size", 256)),
        mamba_conv_bias=bool(d.get("mamba_conv_bias", True)),
        mamba_proj_bias=bool(d.get("mamba_proj_bias", False)),
        embedding_multiplier=float(d.get("embedding_multiplier", 1.0)),
        residual_multiplier=float(d.get("residual_multiplier", 1.0)),
        logits_scaling=float(d.get("logits_scaling", 1.0)))
    if d.get("attention_multiplier") is not None:
        out["attention_multiplier"] = float(d["attention_multiplier"])
    return out


def resolve_model_config(args: CoreArgs, hf_path: Optional[str] = None) -> CoreArgs:
    """Resolve final ModelArgs: YAML-provided fields win; if ``hf_path`` (or
    args.extra['hf_model_path']) is set, pull architecture from HF AutoConfig.
    Mirrors reference resolve_model_config (hf_config_adapter.py:285)."""
    path = hf_path or args.extra.get("hf_model_path")
    if path:
        from transformers import AutoConfig

        hf_cfg = AutoConfig.from_pretrained(path)
        args = args.model_copy(
            update={"model": populate_model_args_from_hf(hf_cfg, base=args.model)}
        )
    if args.model.seq_length > args.model.max_position_embeddings:
        args.model.max_position_embeddings = args.model.seq_length
    return args


def model_layer_configs(model_args: ModelArgs) -> List[Dict[str, Any]]:
    """Per-layertype dicts consumed by profiler + search engine
    (reference hf_config_adapter.py:384). Dense models have one layertype; MoE
    models alternate dense/MoE according to moe_layer_freq."""
    base = {
        "hidden_size": model_args.hidden_size,
        "seq_len": model_args.seq_length,
        "num_attention_heads": model_args.num_attention_heads,
        "num_key_value_heads": model_args.kv_heads,
        "ffn_hidden_size": model_args.ffn_dim,
        "vocab_size": model_args.padded_vocab_size,
        "layer_num": model_args.num_hidden_layers,
    }
    if model_args.model_type == "t5":
        # layertype 0 = encoder, 1 = decoder (runtime/dataloader.py
        # seq2seq_batches splits each sample in half: source | target)
        n_enc = (model_args.num_encoder_layers
                 if model_args.num_encoder_layers is not None
                 else model_args.num_hidden_layers)
        half = model_args.seq_length // 2
        enc = dict(base, seq_len=half, layer_num=n_enc)
        dec = dict(base, seq_len=model_args.seq_length - half,
                   layer_num=model_args.num_hidden_layers)
        return ([enc] if n_enc else []) + [dec]
    from hetu_galvatron_tpu.analysis.eligibility import mixed_stack_reason

    reason = mixed_stack_reason(
        model_args, "the model profiler and the search (one layer type "
        "priced for a dense stack, two for dense and expert blocks)",
        feed_forward_may_differ=True)
    if reason is not None:
        raise NotImplementedError(reason)
    if not model_args.num_experts:
        return [base]
    # dense and expert blocks, counted from the per-layer description
    # (leading dense blocks, then every moe_layer_freq-th): layer_num is
    # split between the two layertypes (never double-counted).
    n = model_args.num_hidden_layers
    n_moe = sum(ff == "experts" for _, ff in model_args.block_kinds())
    if n_moe == 0:
        return [base]
    moe = dict(base)
    moe.update(
        layer_num=n_moe,
        num_experts=model_args.num_experts,
        moe_topk=model_args.moe_topk,
        moe_ffn_hidden_size=model_args.moe_ffn_hidden_size or model_args.ffn_dim,
    )
    if n - n_moe == 0:
        return [moe]
    base["layer_num"] = n - n_moe
    return [base, moe]


def model_name(model_args: ModelArgs) -> str:
    return model_args.model_name.replace("/", "_")
