"""Hybrid-parallel configuration: GLOBAL/JSON modes -> per-layer strategies.

Capability parity with the reference's config expansion
(runtime/hybrid_parallel_config.py:18-130 ``get_hybrid_parallel_configs_api``,
:229-369 ``hp_config_whole_model`` + ``get_chunks``): GLOBAL mode replicates
the uniform CLI knobs across all layers; JSON mode loads a searched
``galvatron_config_*.json`` plan and overrides global_bsz / chunks / pp_deg /
vocab degrees from it; the whole-model expansion attaches vocab-strategy rows
for the embedding / final-norm / LM-head; ``chunks == -1`` auto-computes the
microbatch count.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import math

from hetu_galvatron_tpu.analysis import eligibility
from hetu_galvatron_tpu.core.args_schema import CoreArgs
from hetu_galvatron_tpu.utils.strategy import (
    DPType,
    EmbeddingLMHeadStrategy,
    LayerStrategy,
    config2strategy,
    default_pp_division,
    load_strategy_config,
)


@dataclass
class HybridParallelConfig:
    """Resolved plan for the whole model (the reference's
    hybrid_parallel_configs dict, hybrid_parallel_config.py:120-139)."""

    layers: List[LayerStrategy]  # one per transformer layer (see note below)
    vocab: EmbeddingLMHeadStrategy
    pp_deg: int
    pp_division: List[int]  # layers per stage, sums to len(layers)
    chunks: int
    global_bsz: int
    pipeline_type: str
    default_dp_type: DPType
    world_size: int
    # Encoder-decoder models (t5): ``layers`` spans the COMBINED stack —
    # encoder layers first, then decoder layers — and this records the split
    # point. 0 for decoder-only models. ``pp_division`` likewise divides the
    # combined stack, so a stage may hold encoder layers, decoder layers, or
    # the enc->dec boundary.
    num_encoder_layers: int = 0
    # Dataloader-side zigzag cp layout (reference get_batch zigzag slice,
    # utils.py:295): sequences arrive pre-permuted; ring layers skip the
    # in-layer layout reshard. Only set with a uniform cp > 1.
    cp_zigzag: bool = False
    # Interleaved virtual stages (beyond the reference): pp_division has
    # pp_deg * vpp_deg entries; chunk c runs on physical group c % pp_deg.
    vpp_deg: int = 1
    # Searched plans carry the cost model's per-layer compute prediction
    # (fct+bct, ms) so the plan audit can diff the exact model that picked
    # the plan; None for GLOBAL-mode or pre-audit plan files.
    predicted_layer_compute_ms: Optional[List[float]] = None
    # Keys of the removed hierarchical dp reduction that the plan file still
    # carried (strategy.IGNORED_PLAN_KEYS); the launcher names them once.
    ignored_plan_keys: Tuple[str, ...] = ()

    @property
    def enc_strategies(self) -> List[LayerStrategy]:
        return self.layers[:self.num_encoder_layers]

    @property
    def dec_strategies(self) -> List[LayerStrategy]:
        return self.layers[self.num_encoder_layers:]

    @property
    def pp_stage_of_layer(self) -> List[int]:
        """Layer index -> pipeline stage (reference pp_ranks_enc)."""
        out = []
        for stage, n in enumerate(self.pp_division):
            out.extend([stage] * n)
        return out

    def describe(self) -> str:
        from hetu_galvatron_tpu.utils.strategy import print_strategies

        return (f"pp{self.pp_deg} chunks{self.chunks} bsz{self.global_bsz} "
                f"[{print_strategies(self.layers)}] vocab(vtp{self.vocab.vtp}"
                f"{' vsp' if self.vocab.vsp else ''})")


def resolve_chunks(chunks: int, pp_deg: int, global_bsz: int,
                   world_size: int) -> int:
    """Shared chunks resolution for GLOBAL and JSON paths (reference
    get_chunks, hybrid_parallel_config.py:359-368): only -1 auto-computes
    (aiming for microbatches of ~4 samples per max-dp rank); 0 clamps to 1."""
    if chunks != -1:
        return max(chunks, 1)
    if pp_deg <= 1:
        return 1
    max_dp = world_size // pp_deg
    local_bsz = global_bsz / max(max_dp, 1)
    return max(int(math.ceil(local_bsz / 4)), 1)


def get_chunks(args: CoreArgs, world_size: int) -> int:
    return resolve_chunks(args.parallel.chunks, args.parallel.pp_deg,
                          args.parallel.global_train_batch_size, world_size)


def get_hybrid_parallel_config(
    args: CoreArgs, world_size: int
) -> HybridParallelConfig:
    """GLOBAL or JSON mode -> HybridParallelConfig (reference
    get_hybrid_parallel_configs_api, hybrid_parallel_config.py:18-130)."""
    par = args.parallel
    n_enc = 0
    if args.model.model_type == "t5":
        n_enc = (args.model.num_encoder_layers
                 if args.model.num_encoder_layers is not None
                 else args.model.num_hidden_layers)
    n_layers = args.model.num_hidden_layers + n_enc
    use_json = par.config_mode == "json" or (
        par.galvatron_config_path not in (None, "", "None"))

    if use_json:
        cfg = load_strategy_config(par.galvatron_config_path)
        layers, vocab, extras = config2strategy(cfg, world_size=world_size)
        if len(layers) != n_layers:
            raise ValueError(
                f"plan has {len(layers)} layers, model has {n_layers} "
                f"(encoder {n_enc} + decoder "
                f"{args.model.num_hidden_layers})")
        if extras["num_encoder_layers"] not in (None, n_enc):
            raise ValueError(
                f"plan was searched for {extras['num_encoder_layers']} "
                f"encoder layers, model has {n_enc}")
        pp_deg = layers[0].pp_deg
        global_bsz = extras["global_bsz"] or par.global_train_batch_size
        chunks = resolve_chunks(extras["chunks"], pp_deg, global_bsz,
                                world_size)
        pipeline_type = extras["pipeline_type"]
        default_dp = DPType.from_name(extras["default_dp_type"])
        vpp = max(extras.get("vpp_deg", 1), 1)
        pp_division = extras["pp_division"] or default_pp_division(
            n_layers, pp_deg * vpp)
        pred_layer_ms = extras.get("predicted_layer_compute_ms")
        ignored_keys = extras["ignored_keys"]
    else:
        pp_deg = par.pp_deg
        r = eligibility.pp_world_reason(world_size, pp_deg)
        if r:
            raise ValueError(r)
        stage = world_size // pp_deg
        tp = max(par.global_tp_deg, 1)
        cp = max(par.global_cp_deg, 1)
        r = eligibility.stage_degree_reason(world_size, pp_deg, tp, cp)
        if r:
            raise ValueError(r)
        default_dp = DPType.from_name(par.default_dp_type)
        dp_type = DPType.ZERO3 if par.sdp else default_dp
        # checkpoint: a block MAY be recomputed; it is, where its values do
        # not fit (the pp=1 step keeps as many blocks whole as the device's
        # memory leaves, parallel/spmd.py::KeptStep; the pipeline engines
        # recompute every block whose bit is set)
        base = LayerStrategy(
            pp_deg=pp_deg, tp_size=tp, cp_size=cp, dp_size=stage // (tp * cp),
            sp=par.use_ulysses, tp_consecutive=bool(par.global_tp_consec),
            dp_type=dp_type, checkpoint=bool(par.global_checkpoint),
            ep_size=max(par.global_ep_deg, 1),
            etp_size=max(par.global_etp_deg, 1),
        )
        layers = [base] * n_layers
        vocab = EmbeddingLMHeadStrategy(
            vtp=par.vocab_tp,
            vsp=bool(par.vocab_sp) or par.use_ulysses,  # ulysses forces vsp
            vcp=par.vocab_cp,
            embed_sdp=bool(par.embed_sdp),
        )
        global_bsz = par.global_train_batch_size
        pipeline_type = par.pipeline_type
        vpp = max(par.virtual_pp_deg, 1)
        pp_division = default_pp_division(n_layers, pp_deg * vpp)
        chunks = get_chunks(args, world_size)
        pred_layer_ms = None
        ignored_keys = ()

    # guard both branches (a JSON plan with pp*vpp > layers would otherwise
    # slip through as zero-layer chunks from default_pp_division): the
    # structural predicates are shared with the plan doctor, which reports
    # ALL of them instead of raising on the first
    for reason in (
            eligibility.vpp_layers_reason(pp_deg, vpp, n_layers),
            eligibility.pp_division_sum_reason(pp_division, n_layers),
            eligibility.pp_division_len_reason(pp_division, pp_deg, vpp),
            eligibility.batch_grain_reason(global_bsz, world_size, pp_deg,
                                           layers, vocab),
            eligibility.mamba_plan_reason(args.model, layers),
            eligibility.latent_plan_reason(args.model, layers),
            eligibility.kda_plan_reason(args.model, layers),
            eligibility.gdn_plan_reason(args.model, layers),
            eligibility.mamba1_plan_reason(args.model, layers),
            eligibility.shared_plan_reason(args.model, layers, pp_deg),
            eligibility.window_plan_reason(args.model, layers),
            eligibility.tower_plan_reason(args.model, layers, pp_deg),
            eligibility.ep_divides_reason(args.model, layers),
            (eligibility.residual_streams_reason(
                args.model, f"a pipelined plan (pp={pp_deg})")
             if pp_deg > 1 else None),
            eligibility.capacity_dispatch_reason(
                dispatcher=args.model.moe_dispatcher,
                tokens=global_bsz // chunks * args.model.seq_length,
                topk=args.model.moe_topk, experts=args.model.num_experts,
                capacity_factor=args.model.moe_capacity_factor,
                devices=world_size // pp_deg,
                hbm_gb=args.search.memory_constraint)):
        if reason is not None:
            raise ValueError(reason)
    cp_zigzag = bool(getattr(args.parallel, "cp_zigzag", False))
    if cp_zigzag:
        cps = {s.cp_size for s in layers}
        if len(cps) != 1:
            # a non-ring layer would causally mask PERMUTED data by its
            # array order — silently wrong; demand an all-ring stack
            raise ValueError(
                "parallel.cp_zigzag needs a UNIFORM cp degree across all "
                f"layers (plan has {sorted(cps)}): pre-permuted sequences "
                "are only correct when every attention layer is zigzag "
                "ring")
        if cps == {1}:
            cp_zigzag = False  # no cp: the flag is a no-op
        elif args.model.model_type in ("bert", "t5"):
            raise ValueError(
                "parallel.cp_zigzag is a causal-LM data layout "
                "(bert/t5 batches are not zigzag-slicable)")
    return HybridParallelConfig(
        layers=list(layers), vocab=vocab, pp_deg=pp_deg,
        pp_division=list(pp_division), chunks=chunks, global_bsz=global_bsz,
        pipeline_type=pipeline_type, default_dp_type=default_dp,
        world_size=world_size, num_encoder_layers=n_enc, vpp_deg=vpp,
        cp_zigzag=cp_zigzag, predicted_layer_compute_ms=pred_layer_ms,
        ignored_plan_keys=ignored_keys,
    )
