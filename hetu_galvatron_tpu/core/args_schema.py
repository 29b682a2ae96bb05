"""Pydantic argument schemas.

Capability parity with the reference's Hydra+Pydantic config stack
(core/args_schema.py:46-52, runtime/args_schema.py:344-386,
profiler/args_schema.py, search_engine/args_schema.py:65-75): a validated
`CoreArgs` tree with per-domain submodels, YAML-loadable with dotted overrides
(loader in ``core/arguments.py``). Hydra itself is not a dependency; the loader
implements the subset Galvatron uses (compose a YAML + ``key=value`` /
``++key=value`` overrides).

TPU notes: `mixed_precision` defaults to bf16 (TPU-native), there is no NCCL
backend/timeout knob — the distributed "backend" is the XLA runtime — and
device-count fields describe chips in a `jax.sharding.Mesh`.
"""

from __future__ import annotations

from typing import Any, Dict, List, Literal, Optional, Tuple

from pydantic import BaseModel, Field, field_validator, model_validator

# what a block may leave for later blocks, by the kind that reads it: (the
# names of the values, the kind that makes them). ``ModelArgs.block_shares``
# says which block of a stack makes and which reads
# the entries of ``layer_types`` that are a feed-forward and no mixer: a block
# of a stack whose blocks have one branch each
FEED_FORWARD_KINDS = ("experts", "dense")

SHARED_VALUES: Dict[str, Tuple[Tuple[str, ...], str]] = {
    "gmu": (("memory",), "mamba1"),
    "cross_attention": (("keys", "values"), "full_attention"),
}


class ModelArgs(BaseModel):
    """Architecture hyperparameters for the generic causal-LM decoder stack
    (reference models share one decoder arch parameterized by YAML —
    models/model_configs/*.yaml, runtime/models/builder.py:111-121)."""

    model_name: str = "gpt2-small"
    model_type: Literal["gpt", "llama", "bert", "t5", "moe"] = "gpt"
    hidden_size: int = 768
    num_hidden_layers: int = 12  # decoder layers (t5: decoder stack depth)
    num_encoder_layers: Optional[int] = None  # t5 only; None => same as dec
    num_attention_heads: int = 12
    num_key_value_heads: Optional[int] = None  # None => MHA
    ffn_hidden_size: Optional[int] = None  # None => 4*hidden (or 8/3 for swiglu)
    vocab_size: int = 50257
    max_position_embeddings: int = 1024
    seq_length: int = 1024
    # "relu2": relu(x)^2, ungated (Nemotron-H's MLPs and experts)
    hidden_act: Literal["gelu", "gelu_exact", "swiglu", "geglu", "relu",
                        "silu", "relu2"] = "gelu"
    normalization: Literal["layernorm", "rmsnorm"] = "layernorm"
    # None derives from the family: "post" for bert (HF BertLayer applies
    # LN after each residual; embeddings get their own LN and the final
    # norm lives in the MLM transform head), "pre" for everything else
    # "branch" = the norm on a branch's OUTPUT, ``h + Norm(F(h))`` (OLMo 2
    # and 3's blocks, HF ``Olmo3DecoderLayer``); the stack keeps its final
    # norm. ``norm_positions``: a placement a mixer kind where the blocks of
    # one stack differ, {"linear_attention": "pre", "full_attention":
    # "branch"} (Olmo Hybrid); a kind without an entry takes
    # ``norm_position``. A block reads its own through :meth:`for_block`
    norm_position: Optional[Literal["pre", "post", "branch"]] = None
    norm_positions: Optional[Dict[str, Literal["pre", "branch"]]] = None
    layernorm_epsilon: float = 1e-5
    # "nope" = no positions at all: no table, no rotation (a stack whose
    # state-space blocks carry the order, Granite-4.0-H)
    position_embedding_type: Literal["learned", "rope", "nope"] = "learned"
    rope_theta: float = 10000.0
    # HF-style rope_scaling dict: {"rope_type": "linear"|"llama3"|"yarn",
    # "factor": ..., and for llama3 "low_freq_factor"/"high_freq_factor"/
    # "original_max_position_embeddings"} — llama-3.1+ checkpoints need it
    # for >8k contexts (BASELINE milestone 5). "yarn" (Peng et al.,
    # arXiv:2309.00071, as DeepSeek-V2/V3 publish it): "beta_fast",
    # "beta_slow", "original_max_position_embeddings", and "mscale" /
    # "mscale_all_dim", whose ratio scales cos and sin and whose second
    # enters a latent-attention block's softmax scale squared
    rope_scaling: Optional[Dict[str, Any]] = None
    # multimodal rope (qwen2-vl style; reference rotary_pos_embedding.py):
    # the head_dim//2 frequency dims split into per-axis sections
    # (temporal, height, width); batches supply "mrope_position_ids"
    # [3, B, S]. Text-only inputs reduce exactly to standard rope.
    mrope_section: Optional[List[int]] = None
    tie_word_embeddings: bool = True
    use_flash_attn: bool = True
    # Pallas fused CE kernel for the single-device loss path (distributed
    # runs keep the GSPMD vocab-parallel CE; see modules.cross_entropy_loss)
    use_fused_ce: bool = False
    # rematerialization policy for per-layer activation checkpointing:
    # "full" keeps the block's input and, of a block that attends through
    # the flash kernels, the attention core's output with its row statistics
    # (as large as the input; the layer's S x S work then runs once), of a
    # block whose recurrence runs in the scan kernels their output and the
    # states that entered the chunks (modules.remat), and
    # recomputes everything else (min memory); "dots" also saves matmul
    # outputs so the backward recomputes only cheap elementwise ops (MXU
    # FLOPs are the expensive part on TPU); "dots_no_batch" saves only
    # non-batch dots (XLA's offloading-friendly middle ground)
    remat_policy: Literal["full", "dots", "dots_no_batch"] = "full"
    attention_dropout: float = 0.0
    hidden_dropout: float = 0.0
    # gemma-family numerics: RMSNorm computes x * (1 + scale) (zero-centered
    # weights), embeddings are scaled by sqrt(hidden_size), and head_dim may
    # differ from hidden/heads
    norm_zero_centered: bool = False
    scale_embeddings: bool = False
    head_dim_override: Optional[int] = None
    make_vocab_size_divisible_by: int = 128
    untie_streams: bool = False
    # MoE
    num_experts: int = 0  # 0 => dense model
    moe_topk: int = 2
    moe_ffn_hidden_size: Optional[int] = None
    num_shared_experts: int = 0
    moe_aux_loss_coeff: float = 1e-2
    moe_z_loss_coeff: float = 0.0
    moe_router_dtype: Literal["float32", "bfloat16"] = "float32"
    moe_layer_freq: int = 1  # every k-th layer is MoE
    # dispatch: "capacity" = GShard one-hot einsums (drop over-capacity
    # tokens; across chips GSPMD shards their expert axis over ep and inserts
    # the all-to-alls), "dropless" = sorted ragged grouped matmuls (exact
    # numerics, reference alltoall dropless dispatcher; across chips this
    # and the held share run inside the expert exchange, models/moe.py::
    # make_expert_exchange: tokens all-gathered over ep, partial results
    # reduce-scattered back, at tp = cp = etp = 1 on the pp = 1 path)
    moe_dispatcher: Literal["capacity", "dropless"] = "capacity"
    # rows provisioned over the expected share of the routes: an expert's
    # buffer under the "capacity" dispatcher (what is over is dropped), and
    # the first chunk of a layer that holds a share of its experts under
    # the sorted one (moe.short_rows: nothing is dropped, what is over takes
    # counted passes behind it, and a step's time follows their count)
    moe_capacity_factor: float = 1.25
    # router: softmax topk (optionally expert-bias-corrected selection) or
    # sinkhorn load balancing (reference router.py:98)
    moe_router_type: Literal["topk", "sinkhorn"] = "topk"
    moe_router_enable_expert_bias: bool = False
    moe_expert_bias_update_rate: float = 1e-3
    # HF ``norm_topk_prob``: True divides the k chosen router probabilities
    # by their sum (Mixtral); False combines with the raw softmax values,
    # which sum to less than one (OLMoE)
    moe_norm_topk_prob: bool = True
    # which public names an expert layer is exported / imported under:
    # "mixtral" = block_sparse_moe.gate / experts.{e}.w1,w3,w2; "olmoe" =
    # mlp.gate / mlp.experts.{e}.{gate,up,down}_proj; "lfm2" =
    # feed_forward.gate / feed_forward.experts.{e}.w1,w3,w2 and
    # feed_forward.expert_bias
    # "deepseek" = olmoe's names, mlp.gate.e_score_correction_bias for the
    # selection bias and mlp.shared_experts.{gate,up,down}_proj for the
    # shared expert; "kimi" = mixtral's names with
    # block_sparse_moe.gate.e_score_correction_bias and
    # block_sparse_moe.shared_experts.{gate,up,down}_proj: the two layouts
    # with a slot for a shared expert
    # with a slot for a shared expert; "laguna" = olmoe's names and
    # mlp.shared_expert.{gate,up,down}_proj (one shared expert, singular)
    moe_hf_layout: Literal["mixtral", "olmoe", "lfm2", "deepseek",
                           "kimi", "laguna"] = "mixtral"
    # RMSNorm over the WHOLE projected q and k widths (all heads together,
    # one learned scale each), after the qkv product and before the split
    # into heads and RoPE (OLMoE; HF ``self_attn.{q,k}_norm``)
    qk_norm: bool = False
    # the q/k RMSNorm per HEAD instead: one learned scale of head_dim a
    # projection, applied to every head separately after the split into
    # heads and before RoPE (LFM2; HF ``self_attn.{q,k}_layernorm``)
    qk_norm_per_head: bool = False
    # THE per-layer description (:meth:`block_kinds`) comes from these two
    # published keys and ``moe_layer_freq``. ``layer_types``: each block's
    # mixer, "full_attention", "conv" (a gated short convolution,
    # modules.apply_short_conv), "mamba" (a Mamba-2 state-space block,
    # modules.apply_mamba2), "latent_attention" (DeepSeek-V2/V3's
    # low-rank q and kv projections, modules.apply_latent_attention) or
    # "kda" (Kimi Delta Attention: a gated delta rule with a decay a
    # channel, modules.apply_kda) or "sliding_attention" (attention over the
    # ``sliding_window`` newest keys of the causal span), "mamba1" (a Mamba-1
    # selective-scan block, modules.apply_mamba1), "gmu" (a gated memory
    # unit: it reads the scan output an earlier "mamba1" block left,
    # modules.apply_gmu) or "cross_attention" (its own queries over the keys
    # and values an earlier "full_attention" block left; which block leaves
    # what: :meth:`block_shares`) or "linear_attention" (the published word
    # of a Gated DeltaNet block: a gated delta rule with a decay a head,
    # modules.apply_gated_delta); None = every block attends.
    # A stack of blocks of ONE branch (Nemotron-H's ``hybrid_override_
    # pattern``: one norm and one residual add a block, around a mixer OR a
    # feed-forward) states its feed-forward blocks in the same list:
    # "experts" (the routed experts of models/moe.py) or "dense" (the MLP of
    # ``ffn_hidden_size``) is a block that is that feed-forward and no
    # mixer, and a mixer entry of such a stack is a block without a
    # feed-forward. A stack without such entries is a mixer AND a
    # feed-forward a block, the feed-forward's kind derived as below.
    # ``num_dense_layers``: so many leading blocks of an expert model keep
    # a dense MLP of ``ffn_hidden_size``
    layer_types: Optional[
        List[Literal["full_attention", "conv", "mamba",
                     "latent_attention", "kda", "sliding_attention",
                     "mamba1", "gmu", "cross_attention",
                     "linear_attention", "experts", "dense"]]] = None
    num_dense_layers: int = 0
    # what a stack of window and full attention blocks publishes beside
    # ``layer_types`` (HF ``LagunaConfig``). ``sliding_window``: the keys a
    # query of a "sliding_attention" block meets at most, its own included.
    # ``num_attention_heads_per_layer``: each block's query heads over the
    # model's ``num_key_value_heads`` (None = ``num_attention_heads`` in
    # every block; it needs ``head_dim_override``, a head's width being no
    # quotient of one count). ``rope_parameters``: a rotation a mixer kind,
    # {"full_attention": {...}, "sliding_attention": {...}}, each with its
    # ``rope_theta``, a ``rope_scaling``'s keys (``rope_type`` "default" =
    # none; "yarn" with the ``attention_factor`` that scales cos and sin
    # stated) and ``partial_rotary_factor``, the leading share of a head
    # that is rotated (the rest passes through); a kind without an entry
    # takes ``rope_theta`` / ``rope_scaling`` over the whole head.
    # ``gating`` "per-head": a sigmoid gate a query head on the attention
    # core's output, from the block's normed input (arXiv:2505.06708's
    # head-wise form)
    sliding_window: Optional[int] = None
    num_attention_heads_per_layer: Optional[List[int]] = None
    rope_parameters: Optional[Dict[str, Dict[str, Any]]] = None
    gating: Optional[Literal["per-head"]] = None
    conv_L_cache: int = 3   # taps of a conv block's depthwise convolution
    conv_bias: bool = False
    # router scores: softmax over the experts (Mixtral, OLMoE), or a sigmoid
    # each (LFM2, DeepSeek-V3): route_tokens says what each does with the
    # bias, the renormalisation and its epsilon
    moe_score_function: Literal["softmax", "sigmoid"] = "softmax"
    moe_routed_scaling_factor: float = 1.0
    # what a sigmoid router adds to the sum of the chosen scores before it
    # divides by it: LFM2's 1e-6; DeepSeek-V3's 1e-20
    moe_norm_topk_eps: float = 1e-6
    # an expert layer that is told which experts it holds: the router keeps
    # its ``num_experts`` outputs and its ``moe_topk`` a token, the layer's
    # weights are ``[moe_held_experts, ...]`` and it computes exactly the
    # routes that fall on [moe_first_held_expert, + moe_held_experts); what
    # the absent experts would have added is left out. 0 = all of them
    moe_held_experts: int = 0
    moe_first_held_expert: int = 0
    # which public names a block's norms, mixer and dense MLP carry:
    # "llama" = input_layernorm / self_attn.o_proj / mlp.{gate,up,down}_proj;
    # "lfm2" = operator_norm / ffn_norm / conv.* / self_attn.out_proj /
    # feed_forward.w1,w3,w2 / model.embedding_norm
    # "granite" = llama's norms and attention, shared_mlp.input_linear
    # (gate | up in one matrix) / shared_mlp.output_linear, mamba.*
    # "phi4flash" = input_layernorm / post_attention_layernorm /
    # attn.{Wqkv,out_proj,lambda_*,subln} or attn.{in_proj,conv1d,x_proj,
    # dt_proj,out_proj,A_log,D} / mlp.{gate_up_proj,down_proj} /
    # model.final_layernorm
    # "nemotron_h" = backbone.embeddings / backbone.layers.{i}.norm and
    # .mixer.* (a Mamba-2 mixer, q,k,v,o_proj, up,down_proj, or gate with
    # e_score_correction_bias / experts.{e} / shared_experts: blocks of one
    # branch) / backbone.norm_f / lm_head
    # "olmo_hybrid" = llama's names with a linear_attention block's
    # linear_attn.{q,k,v,a,b,g,o}_proj / {q,k,v}_conv1d / A_log / dt_bias /
    # o_norm under attention_layer_norm / feedforward_layer_norm, and an
    # attention block's two norms as post_attention_layernorm /
    # post_feedforward_layernorm (Olmo 3's)
    hf_layout: Literal["llama", "lfm2", "granite", "phi4flash",
                       "nemotron_h", "olmo_hybrid"] = "llama"
    # a "mamba" block (Mamba-2 / SSD; HF ``GraniteMoeHybridMambaLayer``):
    # ``mamba_n_heads`` heads of ``mamba_d_head`` channels each carry a
    # state of ``mamba_d_head x mamba_d_state`` over the sequence; B and C
    # come in ``mamba_n_groups`` groups of ``mamba_d_state``, head ``j``
    # reading group ``j // (heads / groups)`` (Granite-4.0-H publishes one
    # group, Nemotron-H eight), and the gated norm's mean square is taken
    # over each group's channels; a depthwise causal convolution of
    # ``mamba_d_conv`` taps runs over x, B and C before the recurrence,
    # which is computed ``mamba_chunk_size`` positions at a time
    mamba_n_heads: int = 0
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # the four Granite multipliers, each None / 1 = what every other model
    # does: softmax(attention_multiplier * q k^T) in place of 1/sqrt(D);
    # the embedding's rows times ``embedding_multiplier``; each of a
    # block's two residual branches times ``residual_multiplier``; the
    # logits divided by ``logits_scaling`` before the loss
    attention_multiplier: Optional[float] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # a "latent_attention" block (HF ``DeepseekV3Attention``): q through a
    # ``q_lora_rank`` bottleneck with its RMSNorm (None, as a config.json
    # writes null, or 0: one full-rank projection and no norm), k and v
    # through one of ``kv_lora_rank``; a head's query and key are
    # ``qk_nope_head_dim`` values without positions beside
    # ``qk_rope_head_dim`` further ones (rotated where the model has
    # positions; the key's one for all heads), its value ``v_head_dim`` wide
    q_lora_rank: Optional[int] = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # a "kda" block (Kimi Delta Attention, arXiv:2510.26692; the released
    # ``KimiDeltaAttention``): ``kda_num_heads`` heads each carry a state of
    # ``kda_head_dim x kda_head_dim`` (keys x values) over the sequence,
    # decayed a channel and updated by the delta rule; q, k and v each pass
    # a depthwise causal convolution of ``kda_conv_kernel`` taps; the
    # recurrence is computed ``kda_chunk_size`` positions at a time
    kda_num_heads: int = 0
    kda_head_dim: int = 128
    kda_conv_kernel: int = 4
    kda_chunk_size: int = 64
    # a "linear_attention" block (Gated DeltaNet, arXiv:2412.06464; HF
    # ``Qwen3NextGatedDeltaNet`` under the same published keys):
    # ``linear_num_value_heads`` heads each carry a state of
    # ``linear_key_head_dim x linear_value_head_dim`` (keys x values) over
    # the sequence, decayed by ONE number a head and token and updated by
    # the delta rule with ``beta = sigmoid`` (times 2 where
    # ``linear_allow_neg_eigval``: ``I - beta k k^T`` then has its
    # eigenvalue along ``k`` in (-1, 1), arXiv:2411.12537); q, k and v each
    # pass a depthwise causal convolution of ``linear_conv_kernel_dim``
    # taps; the recurrence is computed ``linear_chunk_size`` positions at a
    # time (fla's 64; no published key)
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False
    linear_chunk_size: int = 64
    # a "mamba1" block (Mamba-1's selective scan, arXiv:2312.00752; HF
    # ``MambaMixer``): ``mamba1_expand x hidden_size`` channels each carry a
    # state of ``mamba1_d_state`` values over the sequence, decayed by
    # ``exp(dt[t, channel] * A[channel, state])``; dt comes through a
    # bottleneck of ``mamba1_dt_rank`` (None: ceil(hidden_size / 16)); a
    # depthwise causal convolution of ``mamba1_d_conv`` taps runs over the
    # channels before the recurrence (modules.selective_scan: no matmul
    # form exists)
    mamba1_d_state: int = 16
    mamba1_d_conv: int = 4
    mamba1_expand: int = 2
    mamba1_dt_rank: Optional[int] = None
    # differential attention (arXiv:2410.05258) in every block that attends:
    # query heads 2j and 2j + 1 are one pair, two softmax maps over a value
    # two heads wide, subtracted under a learned scalar, then an RMSNorm a
    # pair and the constant ``1 - lambda_init``, the block's own
    # (modules.diff_lambda_init)
    differential_attention: bool = False
    # the residual as ``hc_mult`` streams [B, S, hc_mult, H], mixed around
    # every sub-layer by maps that depend on the token (manifold-constrained
    # hyper-connections, arXiv:2512.24880; modules.residual): the
    # stream-to-stream map is made doubly stochastic by ``hc_sinkhorn_iters``
    # row-then-column normalisations with ``hc_eps`` in the divisors, from
    # values clipped to [hc_res_clamp_min, hc_res_clamp_max]. 1 = x + f(x)
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp_min: float = -30.0
    hc_res_clamp_max: float = 30.0
    # multi-token prediction (DeepSeek-V3 2.2): so many further prediction
    # depths, each one more block fed the previous depth's hidden states and
    # the next token's embedding, sharing embedding and head; the loss adds
    # ``mtp_loss_coeff`` times the mean of their cross-entropies. 0 or 1
    num_nextn_predict_layers: int = 0
    mtp_loss_coeff: float = 0.3
    # a tower of image patches in front of the decoder (Kimi-VL's MoonViT,
    # arXiv:2504.07491 2.1; models/tower.py): ``tower_layers`` pre-norm
    # blocks of ``tower_hidden_size`` whose ``tower_num_heads`` heads attend
    # both ways inside an image, rotated on two axes; a patch is
    # ``tower_in_channels x tower_patch_size^2`` pixel values, its position a
    # row of a learned ``tower_pos_emb_height x tower_pos_emb_width`` table
    # interpolated to the image's grid; ``tower_merge_kernel`` patches side
    # by side go through the projector to one row of ``hidden_size``, which
    # takes the embedding's place where a token is ``image_token_id``.
    # ``image_grids``: the (rows, columns) in patches of every image of ONE
    # sequence, in order: the traffic's, static, so the step's tables are
    # constants. 0 layers = no tower, the program every other model has
    tower_layers: int = 0
    tower_hidden_size: int = 1152
    tower_num_heads: int = 16
    tower_ffn_hidden_size: int = 4304
    tower_patch_size: int = 14
    tower_in_channels: int = 3
    tower_pos_emb_height: int = 64
    tower_pos_emb_width: int = 64
    tower_merge_kernel: List[int] = Field(default_factory=lambda: [2, 2])
    tower_layernorm_epsilon: float = 1e-5
    tower_rope_theta: float = 10000.0
    image_token_id: Optional[int] = None
    image_grids: Optional[List[List[int]]] = None
    # initial values a configuration states beside the tower's plain draw
    # (``tower.init_tower``: every matrix and the table N(0, 0.02)): the
    # fused q | k | v maps' and the position table's standard deviation,
    # and the maps that read a GELU centred over their inputs
    tower_qkv_init_std: Optional[float] = None
    tower_pos_emb_init_std: Optional[float] = None
    tower_centred_init: bool = False

    @model_validator(mode="after")
    def _check_tower(self):
        if not self.tower_layers:
            if self.image_grids:
                raise ValueError(
                    "model.image_grids names images and the model has no "
                    "tower (model.tower_layers is 0)")
            return self
        head = self.tower_hidden_size // self.tower_num_heads
        if self.tower_hidden_size % self.tower_num_heads or head % 4:
            raise ValueError(
                f"model.tower_hidden_size {self.tower_hidden_size} over "
                f"{self.tower_num_heads} heads: a head rotated on two axes "
                "is a whole number of pairs of pairs")
        if self.image_token_id is None or not (
                0 <= self.image_token_id < self.vocab_size):
            raise ValueError(
                f"model.image_token_id {self.image_token_id!r}: a model "
                "with a tower names the id that marks an image position, "
                f"one of its {self.vocab_size} rows")
        mh, mw = self.tower_merge_kernel
        for h, w in self.image_grids or ():
            if h < mh or w < mw or h % mh or w % mw:
                raise ValueError(
                    f"model.image_grids: an image of {h} x {w} patches is "
                    f"no whole number of {mh} x {mw} merges")
        if self.image_positions > self.seq_length:
            raise ValueError(
                f"model.image_grids: {self.image_positions} image positions "
                f"in a sequence of {self.seq_length}")
        return self

    @property
    def tower_head_dim(self) -> int:
        return self.tower_hidden_size // self.tower_num_heads

    @property
    def tower_patch_dim(self) -> int:
        """Pixel values of one patch, channel-major."""
        return self.tower_in_channels * self.tower_patch_size ** 2

    @property
    def image_patches(self) -> List[int]:
        """Patches of each image of one sequence."""
        return [h * w for h, w in self.image_grids or ()]

    @property
    def image_positions(self) -> int:
        """Positions of one sequence that hold an image's merged patches."""
        mh, mw = self.tower_merge_kernel
        return sum(self.image_patches) // (mh * mw)

    @property
    def vision_config(self) -> Dict[str, Any]:
        """The tower's widths under the published group's keys
        (``MoonViTConfig``), the depth apart (``tower_layers``)."""
        return {"hidden_size": self.tower_hidden_size,
                "num_attention_heads": self.tower_num_heads,
                "intermediate_size": self.tower_ffn_hidden_size,
                "patch_size": self.tower_patch_size,
                "num_channels": self.tower_in_channels,
                "init_pos_emb_height": self.tower_pos_emb_height,
                "init_pos_emb_width": self.tower_pos_emb_width,
                "merge_kernel_size": list(self.tower_merge_kernel),
                "layer_norm_eps": self.tower_layernorm_epsilon,
                "rope_theta": self.tower_rope_theta}

    @model_validator(mode="after")
    def _check_attention_kinds(self):
        kinds = self.layer_types or []
        if "experts" in kinds and not self.num_experts:
            raise ValueError(
                "model.layer_types names experts blocks and "
                "model.num_experts is 0")
        if "mamba" in kinds and (self.mamba_n_groups < 1 or
                                 self.mamba_n_heads % self.mamba_n_groups):
            raise ValueError(
                f"model.mamba_n_groups={self.mamba_n_groups}: the groups of "
                f"B and C must divide the {self.mamba_n_heads} heads "
                "(model.mamba_n_heads), head j reading group "
                "j // (heads / groups)")
        if "sliding_attention" in kinds and not (
                self.sliding_window and self.sliding_window > 0):
            raise ValueError(
                "model.layer_types names sliding_attention blocks: "
                "model.sliding_window (the keys a query meets, its own "
                f"included) must be positive, got {self.sliding_window!r}")
        if "linear_attention" in kinds:
            nk, nv = self.linear_num_key_heads, self.linear_num_value_heads
            if nk < 1 or nv % nk:
                raise ValueError(
                    f"model.linear_num_value_heads={nv}: a multiple of the "
                    f"{nk} key heads (model.linear_num_key_heads)")
            if nv != nk:
                raise ValueError(
                    f"model.linear_num_value_heads={nv} over "
                    f"model.linear_num_key_heads={nk}: a key head repeated "
                    "for several value heads is not written (Olmo Hybrid "
                    "publishes equal counts)")
        for kind, where in (self.norm_positions or {}).items():
            if kind not in kinds or self.one_branch_blocks:
                raise ValueError(
                    f"model.norm_positions[{kind!r}]={where!r}: a placement "
                    "a mixer kind names a kind of model.layer_types, of a "
                    "stack whose blocks hold a mixer and a feed-forward")
        heads = self.num_attention_heads_per_layer
        if heads is not None:
            if len(heads) != self.num_hidden_layers:
                raise ValueError(
                    f"model.num_attention_heads_per_layer names {len(heads)} "
                    f"blocks and the stack has {self.num_hidden_layers}")
            if self.head_dim_override is None:
                raise ValueError(
                    "model.num_attention_heads_per_layer needs "
                    "model.head_dim_override: a head's width is no quotient "
                    "of a head count that differs by block")
            bad = [n for n in heads if n < 1 or n % self.kv_heads]
            if bad:
                raise ValueError(
                    f"model.num_attention_heads_per_layer: {bad} are no "
                    f"multiples of the {self.kv_heads} key-value heads")
        for kind, entry in (self.rope_parameters or {}).items():
            if kind not in ("full_attention", "sliding_attention"):
                raise ValueError(
                    f"model.rope_parameters[{kind!r}]: a rotation of its "
                    "own is written for full_attention and "
                    "sliding_attention blocks")
            share = float(entry.get("partial_rotary_factor", 1.0))
            if not 0.0 < share <= 1.0 or (self.head_dim * share) % 2:
                raise ValueError(
                    f"model.rope_parameters[{kind!r}].partial_rotary_factor "
                    f"{share}: an even share of a head of {self.head_dim}")
        return self

    def block_heads(self, i: int) -> int:
        """Query heads of block ``i``."""
        heads = self.num_attention_heads_per_layer
        return self.num_attention_heads if heads is None else heads[i]

    def for_block(self, i: int) -> "ModelArgs":
        """The model's arguments as block ``i`` reads them: its own query
        heads as ``num_attention_heads`` and its mixer kind's norm
        placement as ``norm_position``. The model's own where no block
        differs."""
        update: Dict[str, Any] = {}
        if self.num_attention_heads_per_layer is not None:
            update["num_attention_heads"] = self.block_heads(i)
        if self.norm_positions:
            where = self.norm_positions.get(self.block_kinds()[i][0])
            if where is not None:
                update["norm_position"] = where
        return self.model_copy(update=update) if update else self

    def rope_of(self, kind: Optional[str]
                ) -> Tuple[float, Optional[Dict[str, Any]], int]:
        """(theta, rope_scaling, rotated width) of the blocks of mixer
        ``kind``: ``rope_parameters[kind]`` where the model states one,
        else (and for ``None``) the model's ``rope_theta`` and
        ``rope_scaling`` over ``rope_dim``."""
        entry = (self.rope_parameters or {}).get(kind)
        if entry is None:
            return self.rope_theta, self.rope_scaling, self.rope_dim
        scaling = {k: v for k, v in entry.items()
                   if k not in ("rope_theta", "partial_rotary_factor")}
        if scaling.get("rope_type", "default") == "default":
            scaling = None
        return (float(entry.get("rope_theta", self.rope_theta)), scaling,
                int(self.head_dim
                    * float(entry.get("partial_rotary_factor", 1.0))))

    def block_kinds(self, n: Optional[int] = None
                    ) -> Tuple[Tuple[Optional[str], Optional[str]], ...]:
        """The one per-layer description of a decoder stack: for each block
        its mixer kind ("full_attention", "conv", "mamba",
        "latent_attention", "kda", "sliding_attention", "mamba1", "gmu",
        "cross_attention", "linear_attention") and its
        feed-forward kind ("dense", "experts"). The builder, the exporter, the launcher's
        report and every engine's refusal read this and nothing else.
        A stack of one-branch blocks (``layer_types`` names "experts" or
        "dense" blocks) gives each block one of the two and None for the
        other: (mixer, None) or (None, feed-forward).
        ``n``: the blocks a plan lists where that is not
        ``num_hidden_layers`` (t5's two stacks, a pipeline stage's slice),
        which only a model without ``layer_types`` can have."""
        n = self.num_hidden_layers if n is None else n
        mixers = self.layer_types or ["full_attention"] * n
        if len(mixers) != n:
            raise ValueError(
                f"model.layer_types names {len(mixers)} blocks and "
                f"the stack has {n} (model.num_hidden_layers is "
                f"{self.num_hidden_layers})")
        if self.one_branch_blocks:
            return tuple((None, m) if m in FEED_FORWARD_KINDS else (m, None)
                         for m in mixers)
        freq = max(self.moe_layer_freq, 1)
        return tuple(
            (m, "experts" if self.num_experts and i >= self.num_dense_layers
             and (i + 1) % freq == 0 else "dense")
            for i, m in enumerate(mixers))

    @property
    def one_branch_blocks(self) -> bool:
        """Whether the stack's blocks have one branch each: ``layer_types``
        names a feed-forward kind as a block of its own."""
        return any(m in FEED_FORWARD_KINDS for m in self.layer_types or ())

    def block_shares(self, n: Optional[int] = None
                     ) -> Tuple[Tuple[Tuple[str, ...], Tuple[str, ...]], ...]:
        """For each block (what it leaves for later blocks, what it reads
        of an earlier one), by the names of :data:`SHARED_VALUES`: a block
        of a reading kind reads what the LAST block of the making kind
        before the first reader left (the second half of a
        decoder-hybrid-decoder stack reads the first half's last scan and
        its one full attention, arXiv:2507.06607). A reader with no maker
        before it is a ``ValueError``."""
        mixers = [m for m, _ in self.block_kinds(n)]
        makes: List[Tuple[str, ...]] = [()] * len(mixers)
        for reader, (names, maker) in SHARED_VALUES.items():
            if reader not in mixers:
                continue
            first = mixers.index(reader)
            before = [i for i in range(first) if mixers[i] == maker]
            if not before:
                raise ValueError(
                    f"model.layer_types: block {first} is a {reader} block "
                    f"and reads the {' and '.join(names)} of an earlier "
                    f"{maker} block; there is none before it")
            makes[before[-1]] = makes[before[-1]] + names
        return tuple(
            (made, SHARED_VALUES[m][0] if m in SHARED_VALUES else ())
            for made, m in zip(makes, mixers))

    @property
    def mamba1_d_inner(self) -> int:
        """Channels of a mamba1 block's scan, gate and memory."""
        return self.mamba1_expand * self.hidden_size

    @property
    def mamba1_rank(self) -> int:
        """The width of a mamba1 block's dt bottleneck."""
        return self.mamba1_dt_rank or -(-self.hidden_size // 16)

    @property
    def mamba_d_inner(self) -> int:
        """Channels of a mamba block's x, z and gated norm."""
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the mamba block's convolution runs over: x | B | C."""
        return (self.mamba_d_inner
                + 2 * self.mamba_n_groups * self.mamba_d_state)

    @property
    def kda_inner(self) -> int:
        """Channels of a kda block's q, k, v, decay and output gate."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def linear_key_dim(self) -> int:
        """Channels of a linear_attention block's q and of its k."""
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        """Channels of a linear_attention block's v, gate and output."""
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def held_experts(self) -> int:
        return self.moe_held_experts or self.num_experts

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def ffn_dim(self) -> int:
        if self.ffn_hidden_size is not None:
            return self.ffn_hidden_size
        return 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        # decoupled head dim (gemma-7b: 16 heads x 256 over hidden 3072);
        # None derives the usual hidden/heads
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.hidden_size // self.num_attention_heads

    @property
    def qk_head_dim(self) -> int:
        """A latent-attention head's query / key width."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rope_dim(self) -> int:
        """The width RoPE rotates: a latent-attention model's
        ``qk_rope_head_dim``, else the whole head."""
        return self.qk_rope_head_dim or self.head_dim

    @property
    def padded_vocab_size(self) -> int:
        m = self.make_vocab_size_divisible_by
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def post_norm(self) -> bool:
        """True = residual-then-norm blocks (HF BERT layout)."""
        pos = self.norm_position or (
            "post" if self.model_type == "bert" else "pre")
        return pos == "post"

    @property
    def branch_norm(self) -> bool:
        """True = the norm on each branch's output, ``h + Norm(F(h))``."""
        return self.norm_position == "branch"

    # bias flags (HF adapter detects these per family, e.g. qwen2 qkv bias)
    add_bias_linear: bool = True
    add_qkv_bias: bool = False
    # a bias on attention's output projection where ``add_bias_linear`` (it
    # and the MLP's two) is off
    add_attn_out_bias: bool = False


class ParallelArgs(BaseModel):
    """GLOBAL-mode uniform strategy knobs + JSON-mode pointer, mirroring
    hybrid_parallel_config.py:18-130's two config modes."""

    # strategy source: 'global' (uniform knobs below) or 'json' (searched plan)
    config_mode: Literal["global", "json"] = "global"
    galvatron_config_path: Optional[str] = None
    # GLOBAL mode knobs
    pp_deg: int = 1
    global_tp_deg: int = 1
    global_tp_consec: int = 1
    global_cp_deg: int = 1
    # zigzag-balanced cp with the layout applied in the DATALOADER
    # (reference get_batch zigzag slice, utils.py:295): sequences arrive
    # pre-permuted, position ids ride the batch, and ring layers skip the
    # per-call layout reshard — the long-sequence deployment mode. Needs a
    # uniform cp degree across all layers (causal families only).
    cp_zigzag: bool = False
    global_ep_deg: int = 1  # expert parallel (MoE), carved from dp
    global_etp_deg: int = 1  # tp inside each expert
    sdp: int = 0  # 1 => force zero3 on all layers
    default_dp_type: Literal["ddp", "zero2", "zero3"] = "ddp"
    global_checkpoint: int = 0
    use_ulysses: bool = False
    vocab_tp: int = 1
    vocab_sp: int = 0
    vocab_cp: int = 1
    embed_sdp: int = 0
    # schedule
    pipeline_type: Literal["gpipe", "pipedream_flush"] = "gpipe"
    chunks: int = -1  # -1 => auto from global bsz (hybrid_parallel_config.py:359)
    # interleaved virtual stages (Megatron-style; BEYOND the reference, which
    # has no interleaved schedule): each physical stage hosts vpp
    # non-contiguous layer chunks, cutting the warmup/cooldown bubble by ~vpp
    virtual_pp_deg: int = 1
    # data
    global_train_batch_size: int = 8
    # precision
    mixed_precision: Literal["fp32", "bf16", "fp16"] = "bf16"
    # world
    num_devices: int = 0  # 0 => use every visible chip
    dp_axis_on_dcn: bool = True  # outermost dp/pp on DCN for multi-host pods
    # multi-host runtime init (reference _initialize_distributed,
    # runtime/initialize.py:114-160, reads torchrun's RANK/WORLD_SIZE; the
    # TPU equivalent is jax.distributed.initialize, auto-detecting on pods).
    # 0 processes => single-process; unset fields fall back to the
    # COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID env (launcher-set) or,
    # on Cloud TPU pods, to the metadata service autodetection.
    num_processes: int = 0
    coordinator_address: Optional[str] = None
    process_id: Optional[int] = None
    # DCN topology: number of ICI slices (pods) the job spans; >1 arranges
    # the mesh so pp + outer dp axes cross DCN and tp/cp stay ICI-local
    dcn_slices: int = 1

    @model_validator(mode="after")
    def _check(self):
        if self.config_mode == "json" and not self.galvatron_config_path:
            raise ValueError("config_mode=json requires galvatron_config_path")
        return self


class PipelineArgs(BaseModel):
    """Pipeline-schedule execution knobs (pp_deg > 1 only).

    ``schedule_impl`` selects how the 1F1B schedule executes:

    * ``host`` — the general engine (runtime/pipeline.py): one jitted GSPMD
      program per stage on its own submesh, the host sequences the schedule
      and relies on JAX async dispatch for overlap. Supports every plan
      shape (vpp interleaving, uneven pp_division, t5, MoE, ring/flash
      kernels, packed documents).
    * ``compiled`` — the single-program schedule
      (runtime/compiled_pipeline.py): the ENTIRE 1F1B step (all stages, all
      microbatches, grad accumulation, tied-embedding exchange, clip,
      optimizer update) is one donated jit over a mesh with a real ``pp``
      axis; inter-stage transfers are `lax.ppermute` collective-permutes
      XLA overlaps with compute. Plans the compiled path cannot express
      fall back to ``host`` with a logged reason.
    """

    schedule_impl: Literal["host", "compiled"] = "host"


class TpOverlapArgs(BaseModel):
    """Overlapped tensor-parallel collective knobs (``ops/overlap.py``).

    ``enable`` swaps every eligible Megatron-TP layer's four projection
    matmuls (attention qkv/out, MLP fc1/fc2) for decomposed ring
    all-gather/reduce-scatter matmuls under full-manual ``shard_map``: the
    sequence chunks `lax.ppermute` around the tp ring while each rank
    multiplies the chunk it already holds, so the transfer hides behind
    dependent compute instead of serializing against it (GSPMD's
    auto-partitioned all-gather -> matmul). Layers the path cannot express
    fall back to GSPMD with a logged ``unsupported_reason``: tp == 1,
    Ulysses (tp axes carry sequence), cp layers, tp not dividing the
    sequence/projection widths, MoE/t5 layers. The rings run under BOTH
    pipeline schedule impls — per stage submesh on the host engine, and
    as stage-stacked full-manual shard_maps (``stage_axis="pp"``) inside
    the compiled engine's fused single program (round 12's de-vmapped
    stage axis)."""

    enable: bool = False


class TrainArgs(BaseModel):
    lr: float = 1e-4
    min_lr: float = 1e-5
    weight_decay: float = 0.01
    adam_beta1: float = 0.9
    adam_beta2: float = 0.95
    adam_eps: float = 1e-8
    clip_grad: float = 1.0
    train_iters: int = 20
    lr_decay_style: Literal["constant", "linear", "cosine", "inverse-square-root", "WSD"] = (
        "cosine"
    )
    lr_warmup_iters: int = 0
    lr_decay_iters: Optional[int] = None
    lr_wsd_decay_iters: int = 0
    seed: int = 1234
    eval_interval: int = 0
    eval_iters: int = 0
    check_loss: bool = False
    deterministic_mode: bool = False
    # batch-size ramp [start, increment, ramp_samples] (reference
    # --rampup-batch-size, num_microbatches_calculator.py:193-258);
    # None = constant global batch size
    rampup_batch_size: Optional[List[int]] = None
    decrease_batch_size_if_needed: bool = False


class CheckpointArgs(BaseModel):
    save: Optional[str] = None
    load: Optional[str] = None
    save_interval: int = 0
    load_format: Literal["galvatron", "hf"] = "galvatron"
    async_save: bool = False
    distributed_checkpoint: bool = True
    # retention: keep only the newest N committed step dirs (0 = keep all);
    # partial dirs from crashed saves are garbage-collected either way
    keep_last: int = 0
    # time-based cadence alongside save_interval (seconds; 0 = step
    # cadence only): a save triggers when EITHER is due, so elastic RPO
    # is bounded in wall-clock even when steps slow down
    interval_s: float = 0.0
    # split each save into an on-step jitted device snapshot (bounded
    # stall, measured as checkpoint/snapshot_stall_ms) + a background
    # host-gather/write/commit thread (runtime/checkpoint.AsyncCheckpointer;
    # single-controller only — multi-process pods fall back to the
    # orbax async path with a logged reason)
    snapshot_async: bool = False
    # watchdog deadline for one background write: an in-flight save older
    # than this is declared hung (checkpoint/hung_saves) and the exit
    # drain stops waiting on it instead of blocking shutdown forever
    save_timeout_s: float = 120.0


class DataArgs(BaseModel):
    dataset: Literal["random", "indexed"] = "random"
    data_path: List[str] = Field(default_factory=list)
    split: str = "969,30,1"
    tokenizer_type: str = "none"
    tokenizer_path: Optional[str] = None
    num_workers: int = 0
    reset_position_ids: bool = False
    reset_attention_mask: bool = False
    eod_mask_loss: bool = False
    # a model with a tower (model.tower_layers, model.image_grids): the
    # text positions before the first image, between the images and after
    # the last, one more than the images; with the images' own positions
    # they are the sequence. None = the text in equal parts
    image_text_spans: Optional[List[int]] = None


class ProfileArgs(BaseModel):
    """Runtime-profiler switches (reference profile flags on the train run)."""

    profile: int = 0
    profile_type: Literal["memory", "computation"] = "computation"
    profile_forward: int = 0
    save_profiled_memory: int = 0
    profiler_dir: str = "configs"
    profile_iters: int = 5
    profile_warmup: int = 2
    # non-empty => capture an XLA/jax.profiler trace of iterations
    # [profile_warmup, profile_warmup + trace_iters) into this directory
    # (view with tensorboard / xprof — the TPU counterpart of the
    # reference's torch.profiler traces, profile_overlap.py:10-60)
    trace_dir: str = ""
    trace_iters: int = 3


class LoggingArgs(BaseModel):
    log_interval: int = 1
    tensorboard_dir: Optional[str] = None
    wandb_project: Optional[str] = None
    log_level: str = "info"


class ObservabilityArgs(BaseModel):
    """Unified telemetry layer knobs (``observability/``): metrics registry
    sinks, derived training stats, and the flush cadence."""

    enabled: bool = False
    # JSONL metrics file; None derives <logging.tensorboard_dir or .>/
    # metrics.jsonl at train time
    metrics_path: Optional[str] = None
    # mirror metrics into TensorBoard event files (needs tensorboardX /
    # torch; silently skipped when absent — the path CI exercises)
    tensorboard: bool = False
    flush_interval: int = 16  # steps between registry flushes
    # per-chip peak TFLOP/s override for MFU when the device_kind table
    # (observability/telemetry.py) does not know the hardware (CPU smoke
    # runs, new TPU generations); 0 = autodetect-or-skip
    peak_tflops: float = 0.0
    # predicted-vs-actual plan audit (observability/trace_analysis.py):
    # when a trace window was captured (profile.trace_dir), attribute the
    # device time and diff it against the plan's cost-model predictions at
    # loop exit, emitting audit/* gauges + the plan_audit event
    audit: bool = True
    # allreduce-bandwidth JSON (hardware_profiler output) whose fitted α-β
    # pairs price the audit's predicted collective times; None = volume-
    # only audit (no fitted hardware profile at hand)
    audit_hardware_config: Optional[str] = None
    # crash-forensics flight recorder (observability/recorder.py):
    # directory for flight_<ts>.json dumps on crash / trapped signal /
    # rerun-machine halt. None derives the metrics stream's directory
    # when observability is enabled; setting it explicitly enables the
    # recorder even with enabled=false
    flight_dir: Optional[str] = None
    flight_events: int = 256
    # self-calibrating cost model (observability/calibration.py): a
    # directory enables the loop-exit calibration pass — every plan audit
    # appends its per-curve residual points to
    # <calibration_dir>/residuals.jsonl (fingerprint-keyed, accumulated
    # across runs) and re-fits α-β curves over the accumulated points,
    # writing <calibration_dir>/calibrated_profile.json in the same key
    # namespace audit_hardware_config uses, provenance-tagged under
    # "calibration_meta" ({"source": "runtime-calibrated", per-curve
    # point counts + fit method, fit window, fingerprint}) — point
    # audit_hardware_config (or the search engine's
    # allreduce_bandwidth_config_path) at it to consume the posterior.
    # None = calibration off (audit-only, the pre-calibration behaviour)
    calibration_dir: Optional[str] = None
    # minimum accumulated points per curve before the re-fitter trusts a
    # full regression; below it a prior-anchored scale calibration (or
    # nothing, with no prior) is used instead
    calibration_min_points: int = 4
    # residual-store decay: drop accumulated points older than this many
    # days at load time (hardware changes age out of the posterior
    # instead of anchoring it forever). 0 = keep everything
    calibration_window_days: float = 0.0
    # residual-store windowing: keep at most this many NEWEST points per
    # curve key (bounds residuals.jsonl growth across long fleets).
    # 0 = unlimited
    calibration_max_points: int = 0
    # plan-regret sentinel alarm threshold, as a fraction of the
    # incumbent's adjusted step time: a plan_regret event fires when a
    # stored runner-up, re-priced under the calibrated curves, beats the
    # incumbent by more than this (the calibration/plan_regret_ms gauge
    # publishes the margin regardless)
    regret_threshold: float = 0.05


class ServingArgs(BaseModel):
    """Inference-serving engine knobs (``serving/``): continuous batching,
    paged KV cache, admission control, streaming."""

    # decode lanes: sequences decoded together at one jitted batch shape
    max_batch_size: int = 8
    # paged KV cache geometry; block 0 is reserved scratch. num_kv_blocks=0
    # derives a pool that holds max_batch_size full-length sequences
    kv_block_size: int = 16
    num_kv_blocks: int = 0
    # per-sequence cap (prompt + generation); 0 = model max positions
    max_seq_len: int = 0
    # default per-request generation budget (requests may override)
    max_new_tokens: int = 64
    # admission control: per-engine-step prefill budget, either as GFLOPs
    # (converted via the cost model's forward FLOPs/token) or a direct
    # token cap; 0 = that bound unlimited. The tighter one wins.
    prefill_flops_budget_g: float = 0.0
    max_prefill_tokens: int = 0
    # shared-prefix radix cache (serving/prefix_cache.py): cached
    # block-aligned prompt prefixes skip their prefill entirely (block
    # tables point at refcount-shared pool blocks copy-free); eviction is
    # LRU over unpinned radix nodes. prefix_cache_max_blocks caps how many
    # blocks the tree may hold (0 = bounded only by the pool)
    prefix_cache: bool = False
    prefix_cache_max_blocks: int = 0
    # lossless speculative decoding (serving/spec_decode.py): draft
    # spec_k tokens per lane per step and verify them in one batched
    # [max_batch_size, spec_k+1] pass — greedy streams stay bit-identical
    # to plain decode. spec_draft picks the draft provider: "ngram"
    # (prompt-lookup, free) or "model" (a small draft checkpoint passed
    # to ServingEngine via draft_params/draft_cfg)
    spec_decode: bool = False
    spec_k: int = 4
    spec_draft: Literal["ngram", "model"] = "ngram"
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1
    # sampling defaults (per-request temperature/eos override these);
    # top_k is engine-static (shapes the jitted sampler)
    temperature: float = 0.0
    top_k: Optional[int] = None
    eos_id: Optional[int] = None
    # retire requests older than this many seconds (0 = no deadline)
    request_timeout_s: float = 0.0
    # registry flush cadence, in engine steps
    flush_interval: int = 32
    # JSONL metrics file for cli/serve.py; None derives ./serve_metrics.jsonl
    metrics_path: Optional[str] = None
    # Prometheus text endpoint (observability/prometheus.py) exposing the
    # serve/* registry metrics over stdlib HTTP: None = off (default),
    # 0 = bind an ephemeral port (tests; the engine records the bound
    # port), N = bind that port
    metrics_port: Optional[int] = None
    # bind address for the endpoint; loopback by default — the endpoint
    # is unauthenticated, so exposing it (0.0.0.0) is an explicit choice
    metrics_host: str = "127.0.0.1"
    # per-request lifecycle tracing (observability/events.py): structured
    # submit/admit/prefill/decode/retire events with a stable request id,
    # written through the metrics sinks; cli/summarize.py rebuilds
    # timelines and the TTFT component breakdown. Off by default — the
    # JSONL stream grows per token when on
    trace_requests: bool = False
    # SLO targets in milliseconds (0 = none): when set, the engine
    # exports serve/slo_ttft_attainment / serve/slo_itl_attainment
    # gauges (share of observations inside the target)
    slo_ttft_ms: float = 0.0
    slo_itl_ms: float = 0.0
    # crash-forensics flight recorder (observability/recorder.py):
    # directory for flight_<ts>.json dumps on a fatal engine error; None
    # keeps the in-memory ring only (no artifact)
    flight_dir: Optional[str] = None
    flight_events: int = 256


class RerunArgs(BaseModel):
    """Fault-detection state machine knobs (reference rerun_state_machine.py)."""

    enable: bool = False
    mode: Literal[
        "disabled", "validate_results", "report_stats"
    ] = "disabled"
    error_injection_rate: float = 0.0
    error_injection_type: Literal[
        "transient_error", "persistent_error", "correct_result"
    ] = "transient_error"
    check_for_nan: bool = True
    check_for_spike: bool = True
    spike_factor: float = 10.0
    # deterministic at-step-k fault drills (runtime/rerun_machine.FaultDrill):
    # corrupt ("nan"/"spike"), crash ("crash" raises InjectedCrash), or
    # preempt ("preempt" delivers a real SIGTERM) exactly once, at
    # inject_at_iter, on fresh (non-resumed) runs
    inject_kind: Literal["none", "nan", "spike", "crash", "preempt"] = "none"
    inject_at_iter: int = -1
    inject_spike_scale: float = 100.0

    @field_validator("inject_kind", mode="before")
    @classmethod
    def _nan_is_a_name_here(cls, v):
        # the YAML override parser reads a bare `inject_kind=nan` as float
        # NaN; in this field it names the drill kind
        import math

        if isinstance(v, float) and math.isnan(v):
            return "nan"
        return v


class ChaosArgs(BaseModel):
    """Seeded fault-injection harness knobs (runtime/chaos.py) — the
    generalization of ``rerun.inject_kind`` from one at-step drill to a
    fault PLAN driven through the real process supervisor."""

    enable: bool = False
    # JSON fault-plan file ({"seed": n, "faults": [{"kind", "at_iter",
    # ...}, ...]}); wins over the inline kind/at_iter pair below
    plan: Optional[str] = None
    # inline single-fault plan (the chaos matrix cases):
    #   crash         — raise InjectedCrash at the step boundary
    #   sigterm       — deliver a real SIGTERM mid-step (preempt path)
    #   sigkill       — SIGKILL the process mid-step (no cleanup at all)
    #   kill_mid_save — SIGKILL from inside the save's pre-commit hook
    #                   (torn staging dir, no COMMITTED marker)
    #   hung_save     — stall the pre-commit hook past the watchdog
    #   corrupt_meta  — overwrite the newest commit's meta.json with junk
    #   truncate_meta — truncate the newest commit's meta.json mid-record
    #   io_error      — transient OSErrors through utils/retrying.py
    kind: Literal["none", "crash", "sigterm", "sigkill", "kill_mid_save",
                  "hung_save", "corrupt_meta", "truncate_meta",
                  "io_error"] = "none"
    at_iter: int = -1
    seed: int = 0
    # io_error: how many injected failures before the op succeeds (must
    # stay under the retry attempt budget to model a TRANSIENT fault)
    io_error_count: int = 2
    # io_error: only retry ops whose label contains this substring are
    # targeted ("" = every op)
    io_error_op: str = "checkpoint"
    # hung_save: how long the pre-commit hook stalls
    hang_s: float = 5.0
    # cross-process one-shot markers (CHAOS_FIRED_<i>) live here so a
    # fault does not re-fire on the relaunched attempt; None derives
    # ckpt.save (the dir that already survives the process boundary)
    state_dir: Optional[str] = None


class SupervisorArgs(BaseModel):
    """Preemption/restart supervisor knobs (runtime/supervisor.py)."""

    # trap SIGTERM/SIGINT and checkpoint-and-exit at the next step boundary
    graceful_signals: bool = True
    # wrap the training attempt in run_with_restarts: restartable exit
    # codes (16 resume-to-disambiguate, 18 preempted) and crashes resume
    # from the last committed checkpoint; code 17 surfaces immediately
    auto_restart: bool = False
    max_restarts: int = 3
    backoff_base_s: float = 1.0
    backoff_max_s: float = 60.0
    restart_on_error: bool = True
    # how the restart loop runs (only with auto_restart):
    #   inprocess — run_with_restarts re-invokes train() in THIS process
    #               (drills; world/device list frozen at backend init)
    #   process   — cli/supervise.py relaunches train_dist as a child
    #               process per attempt (production: exit codes, restart
    #               budget, RESUME_PIN and world changes are real across
    #               the process boundary)
    mode: Literal["inprocess", "process"] = "inprocess"
    # process mode: SIGTERM forwarded to the child escalates to SIGKILL
    # after this grace window (Cloud TPU preemption grants ~30s total;
    # the supervisor must leave headroom for its own shutdown)
    term_grace_s: float = 15.0
    # process mode: tmp+rename-atomic supervisor state file (attempt
    # count, restart budget, world-change budget, last-commit receipt);
    # None derives <ckpt.save>/SUPERVISOR_STATE.json
    state_file: Optional[str] = None
    # process mode: how many observed topology changes may reset the
    # restart budget before a flapping fleet stops counting as progress
    max_world_changes: int = 8
    # process mode: serve supervisor liveness on /healthz (+/metrics);
    # -1 = off, 0 = ephemeral port (logged), >0 = fixed port
    metrics_port: int = -1
    # process mode: child poll + commit-receipt refresh cadence
    poll_interval_s: float = 0.5


class SearchArgs(BaseModel):
    """Search-engine knobs (reference search_engine/args_schema.py:65-75)."""

    num_nodes: int = 1
    num_devices_per_node: int = 8
    memory_constraint: float = 16.0  # GB of HBM budget per chip
    min_bsz: int = 8
    max_bsz: int = 64
    bsz_scale: int = 8
    settle_bsz: int = -1  # >0 => search exactly this global bsz
    settle_chunks: int = -1
    search_space: Literal["full", "dp+tp", "dp+pp", "3d", "dp", "tp", "pp", "sdp"] = "full"
    disable_dp: int = 0
    disable_tp: int = 0
    disable_pp: int = 0
    disable_sdp: int = 0  # alias: disable_fsdp (zero3)
    disable_ckpt: int = 0
    disable_tp_consec: int = 1  # non-consecutive tp rarely wins on ICI
    disable_cp: int = 1
    disable_ulysses: int = 0  # alias: disable_sp
    disable_vtp: int = 0
    disable_vsp: int = 0
    max_tp_deg: int = 8
    max_pp_deg: int = 8
    max_sp_deg: int = 8
    max_cp_deg: int = 8
    sequence_parallel: bool = True  # Megatron-SP assumed on with TP
    global_memory_buffer: bool = True
    async_grad_reduce: bool = True
    time_profile_mode: Literal["static", "batch", "sequence"] = "static"
    memory_profile_mode: Literal["static", "batch", "sequence"] = "static"
    default_dp_type: Literal["ddp", "zero2", "zero3"] = "ddp"
    fine_grained_mode: int = 1
    sequence_parallel_mode: Literal["megatron", "ulysses"] = "megatron"
    pipeline_type: Literal["gpipe", "pipedream_flush"] = "pipedream_flush"
    mixed_precision: Literal["bf16", "fp32"] = "bf16"
    use_cpp_core: bool = True
    parallel_search: bool = False
    log_dir: str = "logs"
    # non-empty => append one JSONL record per explored (bsz, chunks, pp,
    # mode, tp-cap) task + the winning plan, so search decisions are
    # auditable after the fact (observability/sinks.py schema)
    search_trace_path: Optional[str] = None
    output_config_path: Optional[str] = None
    # profiled-data locations
    time_profiling_path: Optional[str] = None
    memory_profiling_path: Optional[str] = None
    allreduce_bandwidth_config_path: Optional[str] = None
    # auto-feed the calibration loop's posterior
    # (observability.calibration_dir/calibrated_profile.json) into the
    # search: when the calibrated profile exists and its fingerprint
    # matches this search's hardware/model key, it is preferred over
    # allreduce_bandwidth_config_path with a logged provenance line.
    # 0 opts out (profiled-priors-only, the pre-PR-16 behaviour)
    use_calibrated: int = 1
    p2p_bandwidth_config_path: Optional[str] = None
    overlap_coe_path: Optional[str] = None
    sp_time_path: Optional[str] = None
    sequence_length: Optional[int] = None
    costmodel_coe: float = 1.0
    # Host-dispatch overhead pricing (not measured on the chip):
    # one already-compiled stage-jit call costs ~dispatch_us of host wall
    # time, and the host-sequenced schedule pays 2 (fwd+bwd) * pp * chunks
    # of them per step. The compiled schedule (pipeline.schedule_impl=
    # compiled) pays none, so the search prices pp differently per impl —
    # cranking dispatch_us pushes the host-impl search away from deep pp.
    dispatch_us: float = 0.0
    pipeline_schedule_impl: Literal["host", "compiled"] = "host"
    # Static HBM gate (analysis/memory_doctor.py): > 0 prunes candidate
    # plans whose statically-accounted per-device peak exceeds this many
    # GB — the EXACT predicate `cli/check.py --memory --hbm-gb` applies
    # to plan JSONs (search == check parity), evaluated on the analytic
    # model shapes rather than the profiled memory the DP knapsack uses.
    # 0 (default) keeps the search's profiled-memory-only behavior.
    # Needs the searcher to know the model config (SearchEngine
    # model_cfg; cli/search_dist.py passes it).
    hbm_budget_gb: float = 0.0
    # Overlapped-TP pricing (ops/overlap.py + the α-β collective model):
    # 1 prices eligible Megatron-TP layers with the max(comm, compute)-style
    # overlap discount (cost_model/cost.py layer_time_cost), mirroring a
    # runtime that sets tp_overlap.enable. The α (latency) term itself is
    # independent: it activates whenever the allreduce-bandwidth JSON
    # carries fitted alpha/beta keys (hardware_profiler.profile_alpha_beta)
    # and falls back to the legacy latency tables otherwise, so legacy
    # profiles reproduce golden costs exactly.
    tp_overlap: int = 0
    # Plan-regret sentinel support (observability/calibration.py): embed
    # this many runner-up candidates — the feasible plans the search
    # almost picked, deduped + throughput-ordered, each with its priced
    # time_cost_ms and per-layer degrees — in the winning plan JSON as
    # "runner_ups" (plus the winner's own "predicted_time_cost_ms").
    # config2strategy ignores the extra keys; 0 disables the embedding.
    runner_up_k: int = 3


class ModelProfileArgs(BaseModel):
    """Model-profiler sweep description (reference profiler/args_schema.py)."""

    profile_type: Literal["computation", "memory"] = "computation"
    profile_mode: Literal["static", "batch", "sequence"] = "static"
    profile_batch_size: int = 1
    profile_min_batch_size: int = 1
    profile_max_batch_size: int = 8
    profile_batch_size_step: int = 1
    profile_seq_length_list: List[int] = Field(default_factory=lambda: [1024])
    profile_min_seq_length: int = 1024
    profile_max_seq_length: int = 8192
    profile_seq_length_step: int = 1024
    layernum_min: int = 2
    layernum_max: int = 4
    max_tp_deg: int = 8
    profile_dp_type: Literal["ddp", "zero2", "zero3"] = "ddp"
    mixed_precision: Literal["bf16", "fp32"] = "bf16"
    use_flash_attn: bool = True
    output_dir: str = "configs"
    extra_args_str: str = ""


class HardwareProfileArgs(BaseModel):
    """Hardware-profiler knobs: ICI/DCN collective microbenchmarks replacing the
    reference's NCCL benchmarks (profile_hardware/*, hardware_profiler.py)."""

    num_nodes: int = 1
    num_devices_per_node: int = 8
    max_pp_deg: int = 8
    max_tp_deg: int = 8
    start_mb: int = 1
    end_mb: int = 512
    scale: int = 2
    # smallest sub-MB all-reduce point (KB) for the α-β latency fit
    # (profile_sp_time 'sub_' keys + profile_alpha_beta); layer-wise TP
    # messages live in this regime, where the α term dominates
    sub_mb_floor_kb: int = 64
    # per-algorithm / per-level fits (profile_alpha_beta_algos): benchmark
    # ring vs recursive halving-doubling shaped schedules over ICI and
    # DCN-proxy groups and fit distinct (α, β) pairs per
    # (size, algorithm, level) — the cost model then prices each
    # collective as the min over available curves. 0 skips the sweep
    # (legacy-sized profiling runs)
    profile_algos: int = 1
    warmup_iters: int = 5
    profile_iters: int = 20
    avg_or_min_or_first: Literal["avg", "min", "first"] = "avg"
    output_dir: str = "hardware_configs"
    backend: Literal["auto", "tpu", "cpu"] = "auto"


class CoreArgs(BaseModel):
    """Top-level validated argument tree (reference core/args_schema.py:46)."""

    mode: Literal["train_dist", "search", "model_profiler", "profile_hardware"] = (
        "train_dist"
    )
    model: ModelArgs = Field(default_factory=ModelArgs)
    parallel: ParallelArgs = Field(default_factory=ParallelArgs)
    pipeline: PipelineArgs = Field(default_factory=PipelineArgs)
    tp_overlap: TpOverlapArgs = Field(default_factory=TpOverlapArgs)
    train: TrainArgs = Field(default_factory=TrainArgs)
    ckpt: CheckpointArgs = Field(default_factory=CheckpointArgs)
    data: DataArgs = Field(default_factory=DataArgs)
    profile: ProfileArgs = Field(default_factory=ProfileArgs)
    logging: LoggingArgs = Field(default_factory=LoggingArgs)
    observability: ObservabilityArgs = Field(default_factory=ObservabilityArgs)
    serving: ServingArgs = Field(default_factory=ServingArgs)
    rerun: RerunArgs = Field(default_factory=RerunArgs)
    chaos: ChaosArgs = Field(default_factory=ChaosArgs)
    supervisor: SupervisorArgs = Field(default_factory=SupervisorArgs)
    search: SearchArgs = Field(default_factory=SearchArgs)
    model_profiler: ModelProfileArgs = Field(default_factory=ModelProfileArgs)
    hardware_profiler: HardwareProfileArgs = Field(default_factory=HardwareProfileArgs)
    extra: Dict[str, Any] = Field(default_factory=dict)
