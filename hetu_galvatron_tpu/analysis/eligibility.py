"""Every plan-eligibility predicate, in one pure module.

Before this module existed, the predicates deciding which engine/kernel a
plan gets were duplicated across six files — ``runtime/compiled_pipeline.py``
(``unsupported_reason``), ``cli/train_dist.py`` (fallback logging),
``parallel/spmd.py`` (``tp_overlap_overrides``), ``ops/overlap.py``
(``layer_overlap_reason``), ``core/cost_model/cost.py``
(``compiled_expressible`` / ``tp_overlap_expressible``) and the structural
checks in ``runtime/hybrid_config.py`` — with nothing stopping the cost
model's gates from silently drifting away from what the runtime actually
accepts (the drift class PR 7's plan-flip tests could only spot-check).
All of those now CALL the functions here; the parity test
(``tests/analysis/test_eligibility_parity.py``) sweeps generated plans
through both sides to pin the contract.

Discipline: everything here is pure python over plain values (no jax, no
mesh, no devices) so the plan doctor (``analysis/plan_doctor.py``) can
evaluate a plan on a machine with no accelerator at all. Reason strings are
part of the contract — the launcher logs them and the plan doctor prints
them — so adapters must not rephrase.
"""

from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence

# ---------------------------------------------------------------------------
# compiled single-program 1F1B schedule expressibility
# ---------------------------------------------------------------------------


def compiled_schedule_unsupported_reason(
    *,
    pp_deg: int,
    pipeline_type: str,
    vpp_deg: int = 1,
    model_type: str = "gpt",
    num_experts: int = 0,
    pp_division: Sequence[int] = (),
    uniform_strategies: bool = True,
    packed_docs: bool = False,
    qk_norm: bool = False,
    mixed_stack: Optional[str] = None,
) -> Optional[str]:
    """None when the compiled 1F1B schedule can express a plan with these
    properties; otherwise the human-readable reason every caller logs.

    This is the CANONICAL predicate: the runtime engine
    (``CompiledPipelineEngine.unsupported_reason``), the launcher's
    fallback log, and the cost model's dispatch-waiver gate
    (:func:`search_compiled_expressible`) all evaluate it — the search must
    never price the compiled schedule into a plan the runtime will then
    reject at startup (or vice versa).
    """
    if pp_deg < 2:
        return "pp_deg < 2 routes through the SPMD path"
    if pipeline_type != "pipedream_flush":
        return "compiled schedule implements 1F1B (pipedream_flush) only"
    if vpp_deg > 1:
        return "interleaved virtual stages (vpp > 1)"
    if model_type == "t5":
        return "encoder-decoder (a, b) pair carry"
    if mixed_stack:
        return mixed_stack
    if num_experts:
        return "MoE layers alternate tree structures across the stack"
    if len(set(pp_division)) > 1:
        return (f"heterogeneous per-stage layer counts "
                f"{list(pp_division)} (stage stacking needs uniformity)")
    if not uniform_strategies:
        return "heterogeneous per-layer strategies"
    if packed_docs:
        return "packed-document position/segment fields"
    if qk_norm:
        return ("q/k norm (model.qk_norm): the stage-stacked attention "
                "has no norm between the qkv product and RoPE")
    return None


def compiled_unsupported_reason(cfg: Any, hpc: Any,
                                data: Any = None) -> Optional[str]:
    """Runtime adapter: (ModelArgs, HybridParallelConfig, DataArgs) ->
    reason. cp / zigzag-cp plans are expressible since the engine
    de-vmapped its stage axis (the ring kernel runs inside the fused
    program as a stage-stacked full-manual shard_map)."""
    return compiled_schedule_unsupported_reason(
        pp_deg=hpc.pp_deg,
        pipeline_type=hpc.pipeline_type,
        vpp_deg=getattr(hpc, "vpp_deg", 1),
        model_type=cfg.model_type,
        num_experts=cfg.num_experts,
        pp_division=hpc.pp_division,
        uniform_strategies=all(s == hpc.layers[0] for s in hpc.layers),
        packed_docs=data is not None and (
            getattr(data, "reset_position_ids", False)
            or getattr(data, "reset_attention_mask", False)),
        qk_norm=bool(getattr(cfg, "qk_norm", False)),
        mixed_stack=mixed_stack_reason(
            cfg, "the compiled pipeline engine (one stage-stacked tree "
            "shape)"),
    )


def search_compiled_expressible(
    schedule_impl: str,
    pipeline_type: str,
    partition: Sequence[int],
    strategy_list: Sequence[Any],
) -> bool:
    """Cost-model adapter: can the dispatch-overhead waiver apply to this
    candidate (``cost_model.cost.pipeline_time_cost``)? The search works in
    degrees (SearchStrategy), not model configs, so the model-level gates
    (t5 / MoE / packed docs) are resolved by the caller's layertype setup;
    here the structural gates must agree with the runtime exactly."""
    if schedule_impl != "compiled":
        return False
    return compiled_schedule_unsupported_reason(
        pp_deg=max(len(partition), 2),  # pp>1 is the caller's precondition
        pipeline_type=pipeline_type,
        pp_division=partition,
        uniform_strategies=all(s == strategy_list[0] for s in strategy_list),
    ) is None


# ---------------------------------------------------------------------------
# overlapped-TP (ring ag/rs matmul) per-layer eligibility
# ---------------------------------------------------------------------------

# shared fallback-reason strings: the launcher's plan-level logging, the
# actual dispatch (parallel/spmd.py tp_overlap_overrides) and the plan
# doctor must all report the SAME reasons
T5_REASON = "t5 encoder-decoder layers keep the GSPMD projection path"
MOE_REASON = ("MoE layer: expert matmuls route through the ep/etp "
              "dispatcher, not the dense projections")


CONV_REASON = ("conv block: the gated short convolution's projections are "
               "not cut for the ring all-gather / reduce-scatter matmuls "
               "(ops/overlap.py takes attention's qkv/out and the MLP's "
               "fc1/fc2)")


MAMBA_REASON = ("mamba block: the state-space block's projections are not "
                "cut for the ring all-gather / reduce-scatter matmuls, and "
                "its recurrence runs over the whole sequence on one shard")

LATENT_REASON = ("latent_attention block: the low-rank q and kv projections "
                 "and their norms are not cut for the ring all-gather / "
                 "reduce-scatter matmuls (ops/overlap.py takes one fused qkv "
                 "product)")

KDA_REASON = ("kda block: the delta-rule block's projections are not cut "
              "for the ring all-gather / reduce-scatter matmuls, and its "
              "recurrence runs over the whole sequence on one shard")

GDN_REASON = ("linear_attention block: the Gated DeltaNet block's "
              "projections are not cut for the ring all-gather / "
              "reduce-scatter matmuls, and its recurrence runs over the "
              "whole sequence on one shard")

MAMBA1_REASON = ("mamba1 block: the selective-scan block's projections are "
                 "not cut for the ring all-gather / reduce-scatter matmuls, "
                 "and its recurrence runs over the whole sequence on one "
                 "shard")

SHARED_REASON = ("a block that reads the scan output or the keys and values "
                 "an earlier block left (a gmu or cross_attention block) "
                 "runs at tp=1, cp=1 and pp=1, data parallel: the walk of "
                 "builder.forward_causal_lm carries those values from block "
                 "to block sharded as the residual stream is, no pipeline "
                 "stage hands them on, no cache holds one set of keys and "
                 "values for many blocks, and neither is cut over heads or "
                 "sequence (eligibility.shared_plan_reason)")

WINDOW_REASON = ("a block with a window, query heads of its own, a gate a "
                 "head or differential attention "
                 "attends through the XLA core or the Pallas flash "
                 "kernels with its projections whole on a device: the ring "
                 "and Ulysses cores take no window (a band over ring "
                 "attention's block schedule is not written), and the tp "
                 "interior, the ring all-gather / reduce-scatter matmuls and "
                 "their group-major view of the fused qkv read one "
                 "model-wide head count and no gate "
                 "(eligibility.window_plan_reason)")

ONE_BRANCH_REASON = ("a block of one branch (a stack whose layer_types name "
                     "feed-forward blocks: a mixer OR a feed-forward a "
                     "block, one norm, one add): the ring all-gather / "
                     "reduce-scatter matmuls are wired for a block that "
                     "holds both an attention and an MLP")

# why a block whose mixer is not plain attention keeps its matmuls on GSPMD,
# by mixer kind
MIXER_OVERLAP_REASON = {"conv": CONV_REASON, "mamba": MAMBA_REASON,
                        "latent_attention": LATENT_REASON,
                        "kda": KDA_REASON,
                        "linear_attention": GDN_REASON,
                        "sliding_attention": WINDOW_REASON,
                        "mamba1": MAMBA1_REASON, "gmu": SHARED_REASON,
                        "cross_attention": SHARED_REASON}

# the fields of ``ModelArgs`` by which a block's attention differs from the
# model-wide description: its window, its own query heads, its own rotation,
# its gate; and where its two norms sit, where that is not on the branches'
# inputs or is a kind's own (every engine but the pp=1 training path builds
# a pre-norm block, or BERT's)
BLOCK_ATTENTION_FIELDS = ("sliding_window", "num_attention_heads_per_layer",
                          "rope_parameters", "gating",
                          "differential_attention", "norm_positions")


def block_attention_stated(cfg: Any) -> List[str]:
    """``field=value`` of each of :data:`BLOCK_ATTENTION_FIELDS` the model
    states (a window only where a block has one), and of a model-wide norm
    on the branches' outputs."""
    windowed = "sliding_attention" in (getattr(cfg, "layer_types", None)
                                       or ())
    return [f"{k}={getattr(cfg, k)}" for k in BLOCK_ATTENTION_FIELDS
            if getattr(cfg, k, None) not in (None, False)
            and (k != "sliding_window" or windowed)] + (
        ["norm_position=branch"]
        if getattr(cfg, "norm_position", None) == "branch" else [])


def _cut_said(s: Any) -> str:
    """``tp=2, cp=2 (Ulysses)``: the degrees above 1 by which a layer's
    plan cuts heads or sequence; empty where it cuts neither."""
    cut = [f"{axis}={deg}" for axis, deg in (
        ("tp", s.tp_size), ("cp", s.cp_size)) if deg > 1]
    return ", ".join(cut) + (
        " (Ulysses)" if cut and s.sp and s.tp_size > 1 else "")


def _uncut_mixer_reason(cfg: Any, layers: Any, mixer: str, name: str,
                        why: str) -> Optional[str]:
    """The first block of kind ``mixer`` whose plan cuts heads (tp) or
    sequence (cp, Ulysses), said with ``why`` it runs uncut; None when there
    is none."""
    kinds = cfg.block_kinds(len(layers))
    for i, (s, (kind, _)) in enumerate(zip(layers, kinds)):
        if kind != mixer:
            continue
        cut = _cut_said(s)
        if cut:
            return (f"block {i} is a {mixer} block and its plan has {cut}"
                    f": {name} runs with tp=1 and cp=1 ({why})")
    return None


def mamba_plan_reason(cfg: Any, layers: Any) -> Optional[str]:
    """Why a plan cannot run this model's mamba blocks; None when it can
    (or the model has none). The Mamba-2 block's parameters carry no axis
    that tensor parallelism shards (heads on the tp axis with B and C
    replicated is not written), and its convolution and recurrence run over
    the whole sequence, so a block whose plan cuts the sequence (cp,
    Ulysses) would carry a state across shards it cannot see."""
    return _uncut_mixer_reason(
        cfg, layers, "mamba", "the state-space block",
        "its heads are not cut over the tp axis and its recurrence needs "
        "the whole sequence on one shard); use dp / ZeRO for this model")


def latent_plan_reason(cfg: Any, layers: Any) -> Optional[str]:
    """Why a plan cannot run this model's latent-attention blocks; None when
    it can (or the model has none). The block's projections carry no axis
    that tensor parallelism shards (heads on the tp axis behind a replicated
    latent is not written), and the ring and Ulysses cores take neither a
    softmax scale nor a value width of their own, so a block whose plan cuts
    heads or sequence is refused here, by name, and not inside a trace."""
    return _uncut_mixer_reason(
        cfg, layers, "latent_attention", "latent attention",
        "its low-rank projections are not cut over the tp axis, and the "
        "ring / Ulysses cores take no softmax scale and no value width of "
        "their own); use dp / ZeRO and ep for this model")


def kda_plan_reason(cfg: Any, layers: Any) -> Optional[str]:
    """Why a plan cannot run this model's kda blocks; None when it can (or
    the model has none). The block's parameters carry no axis that tensor
    parallelism shards (heads on the tp axis is not written), and its
    convolutions and its matrix-valued state run over the whole sequence,
    so a block whose plan cuts the sequence (cp, Ulysses) would carry a
    state across shards it cannot see."""
    return _uncut_mixer_reason(
        cfg, layers, "kda", "Kimi Delta Attention",
        "its heads are not cut over the tp axis and its recurrence needs "
        "the whole sequence on one shard); use dp / ZeRO and ep for this "
        "model")


def gdn_plan_reason(cfg: Any, layers: Any) -> Optional[str]:
    """Why a plan cannot run this model's linear_attention blocks; None
    when it can (or the model has none). As a kda block's: no leaf of the
    Gated DeltaNet block carries an axis that tensor parallelism shards
    (heads on the tp axis is not written), and its convolutions and its
    matrix-valued state run over the whole sequence, so a plan that cuts
    the sequence (cp, Ulysses) would carry a state across shards it cannot
    see. A pipelined plan, ``generate()`` and the serving engine refuse the
    mixed stack by name (:func:`mixed_stack_reason`), packed documents the
    block itself (``modules.apply_mixer``)."""
    return _uncut_mixer_reason(
        cfg, layers, "linear_attention", "the Gated DeltaNet block",
        "its heads are not cut over the tp axis and its recurrence needs "
        "the whole sequence on one shard); use dp / ZeRO for this model")


def mamba1_plan_reason(cfg: Any, layers: Any) -> Optional[str]:
    """Why a plan cannot run this model's mamba1 blocks; None when it can
    (or the model has none). The Mamba-1 block's parameters carry no axis
    that tensor parallelism shards (channels on the tp axis with B and C
    all-reduced is not written), and its convolution and recurrence run
    over the whole sequence, so a block whose plan cuts the sequence (cp,
    Ulysses) would carry a state across shards it cannot see."""
    return _uncut_mixer_reason(
        cfg, layers, "mamba1", "the selective-scan block",
        "its channels are not cut over the tp axis and its recurrence needs "
        "the whole sequence on one shard); use dp / ZeRO for this model")


def shared_plan_reason(cfg: Any, layers: Any, pp_deg: int = 1
                       ) -> Optional[str]:
    """Why a training plan cannot run this model's gmu and cross_attention
    blocks; None when it can (or the model has none): the plan's pp, and
    the tp and cp of every block from the first that leaves a value to the
    last that reads one, have to be 1 (:data:`SHARED_REASON`)."""
    shares = cfg.block_shares(len(layers))
    held = [i for i, (leaves, takes) in enumerate(shares) if leaves or takes]
    if not held:
        return None
    if pp_deg > 1:
        return (f"the plan has pp={pp_deg} and blocks {held[0]} to "
                f"{held[-1]} hand values from block to block: "
                + SHARED_REASON)
    for i in held:
        cut = _cut_said(layers[i])
        if cut:
            return (f"block {i} ({cfg.block_kinds(len(layers))[i][0]}) has "
                    f"{cut} in its plan: " + SHARED_REASON)
    return None


def window_plan_reason(cfg: Any, layers: Any) -> Optional[str]:
    """Why a plan cannot run this model's attention blocks; None when it
    can, or the model states no window, no query heads of a block's own, no
    gate and no differential attention. Such a block runs with tp = 1 and
    cp = 1: see :data:`WINDOW_REASON`."""
    stated = block_attention_stated(cfg)
    if not stated:
        return None
    kinds = cfg.block_kinds(len(layers))
    for i, (s, (kind, _)) in enumerate(zip(layers, kinds)):
        if kind not in ("full_attention", "sliding_attention",
                        "cross_attention"):
            continue
        cut = _cut_said(s)
        if cut:
            return (f"block {i} ({kind}) of a model that states "
                    f"{', '.join(stated)} has {cut} in its plan: "
                    f"{WINDOW_REASON}; use dp / ZeRO and ep for this model")
    return None


def ep_exchange_reason(cfg: Any, s: Any, pp_deg: int = 1) -> Optional[str]:
    """Why an expert block whose plan ``s`` has ``ep > 1`` does NOT run its
    dispatcher inside the expert exchange (models/moe.py::
    make_expert_exchange); None where it does, and where it needs none: ep =
    1, or the ``capacity`` dispatcher, whose einsums GSPMD shards over ``ep``
    by design. The exchange serves the sorted dispatchers (``dropless`` and
    the held share) of a block at tp = 1, cp = 1, etp = 1 on the pp = 1 SPMD
    path; a sorted dispatcher outside that keeps the program it had
    (``lax.ragged_dot`` left to GSPMD with its group dimension sharded,
    which is free to gather the experts' weights), and is named here so that
    no plan does so in silence."""
    if s.ep_size <= 1 or cfg.moe_dispatcher != "dropless":
        return None
    outside = [f"{axis}={deg}" for axis, deg in (
        ("pp", pp_deg), ("tp", s.tp_size), ("cp", s.cp_size),
        ("etp", s.etp_size)) if deg > 1]
    if not outside:
        return None
    return (f"ep={s.ep_size} beside {', '.join(outside)}: the expert "
            "exchange (tokens all-gathered and partial results "
            "reduce-scattered over ep) serves the sorted dispatchers at "
            "tp=1, cp=1, etp=1 on the pp=1 SPMD path; this block's grouped "
            "matmuls are left to GSPMD, which may gather expert weights")


def takes_exchange(cfg: Any, s: Any, pp_deg: int = 1) -> bool:
    """Whether an expert block of plan ``s`` runs inside the exchange."""
    return (s.ep_size > 1 and cfg.moe_dispatcher == "dropless"
            and ep_exchange_reason(cfg, s, pp_deg) is None)


def ep_plan_reason(cfg: Any, layers: Any, pp_deg: int = 1) -> Optional[str]:
    """The first expert block of the plan that :func:`ep_exchange_reason`
    names, said with its index; None when every expert block under ``ep >
    1`` is exchanged or needs no exchange (or the model has no experts)."""
    if not getattr(cfg, "num_experts", 0):
        return None
    kinds = cfg.block_kinds(len(layers))
    for i, (s, (_, ff)) in enumerate(zip(layers, kinds)):
        reason = ep_exchange_reason(cfg, s, pp_deg) if ff == "experts" \
            else None
        if reason:
            return f"block {i}: {reason}"
    return None


def ep_divides_reason(cfg: Any, layers: Any) -> Optional[str]:
    """Why a plan's ``ep`` cannot hold this model's experts: an ``ep`` that
    does not divide the experts a layer holds leaves a chip a ragged share,
    which neither the exchange nor a sharded expert axis has. None when it
    divides them (or the model has no experts)."""
    held = getattr(cfg, "held_experts", 0)
    if not held:
        return None
    for i, s in enumerate(layers):
        if held % s.ep_size:
            return (f"layer {i}: ep={s.ep_size} does not divide the "
                    f"{held} experts a layer holds (model.num_experts / "
                    "model.moe_held_experts); parallel.global_ep_deg must "
                    "divide them")
    return None


def residual_streams_reason(cfg: Any, what: str) -> Optional[str]:
    """Why ``what`` (an engine that carries ONE [B, S, H] stream between
    blocks or stages, or a loss with one prediction depth) cannot take a
    model whose residual is several streams or whose loss has a further
    prediction depth; None for every other model."""
    stated = [f"{k}={getattr(cfg, k)}" for k, plain in (
        ("hc_mult", 1), ("num_nextn_predict_layers", 0))
        if getattr(cfg, k, plain) != plain]
    if not stated:
        return None
    return (f"{what} hands one [B, S, H] residual stream from block to "
            f"block and predicts one token a position; this model states "
            f"{', '.join(stated)}, which only the pp=1 training path "
            "(builder.forward_causal_lm / causal_lm_loss) runs")


TOWER_REASON = ("a model with a tower of image patches in front of its "
                "decoder (model.tower_layers) runs the tower whole on every "
                "device, data parallel, at tp=1, cp=1 and pp=1: its "
                "parameters carry no axis tensor parallelism cuts, its "
                "patches are no positions of the sequence a cp plan cuts "
                "(the ring and Ulysses cores take a causal span, not an "
                "image's square), and no pipeline stage, cache or decoding "
                "path hands patches to it (eligibility.tower_plan_reason)")


def tower_reason(cfg: Any, what: str) -> Optional[str]:
    """Why ``what`` (a pipeline engine, ``generate()``, the serving engine,
    a profiler: anything that embeds ids alone) cannot take a model with a
    tower; None for a model without one."""
    if not getattr(cfg, "tower_layers", 0):
        return None
    return (f"{what} embeds token ids and nothing else; this model states "
            f"tower_layers={cfg.tower_layers}: {TOWER_REASON}")


def tower_plan_reason(cfg: Any, layers: Any, pp_deg: int = 1
                      ) -> Optional[str]:
    """Why a training plan cannot run this model's tower; None when it can
    (or the model has none). The tower takes the first decoder block's plan:
    that block's tp and cp, and the plan's pp, have to be 1."""
    if not getattr(cfg, "tower_layers", 0):
        return None
    if pp_deg > 1:
        return (f"the plan has pp={pp_deg} and the model a tower: "
                + TOWER_REASON)
    for i, s in enumerate(layers):
        cut = _cut_said(s)
        if cut:
            return (f"block {i}'s plan has {cut} and the model a tower: "
                    + TOWER_REASON)
    return None


def own_multipliers_reason(cfg: Any, what: str) -> Optional[str]:
    """Why ``what`` (a decoding path with its own attention core and its
    own embedding and residual adds) cannot take a model that states a
    softmax scale or a Granite multiplier; None for every other model."""
    stated = [f"{k}={getattr(cfg, k)}" for k, plain in (
        ("attention_multiplier", None), ("embedding_multiplier", 1.0),
        ("residual_multiplier", 1.0), ("logits_scaling", 1.0))
        if getattr(cfg, k, plain) != plain]
    if not stated:
        return None
    return (f"{what} attends at 1/sqrt(head_dim) and adds its branches "
            f"unscaled; this model states {', '.join(stated)}, which only "
            "the training path (builder.forward_causal_lm) applies")


def mixed_stack_reason(cfg: Any, what: str, *,
                       feed_forward_may_differ: bool = False
                       ) -> Optional[str]:
    """Why ``what`` (an engine, a profiler, the search) cannot take this
    model: it names the block kinds of the per-layer description
    (``ModelArgs.block_kinds``). None for a stack of attention blocks of one
    kind (``feed_forward_may_differ``: or of dense and expert blocks, which
    ``what`` tells apart itself). ``what`` prices, stacks or caches ONE
    block shape and its mixer is attention, so another stack would be
    mis-priced or crash; none may treat a conv block as attention in
    silence."""
    kinds = cfg.block_kinds()
    shapes = {m if feed_forward_may_differ else (m, ff) for m, ff in kinds}
    if len(shapes) <= 1 and all(m == "full_attention" for m, _ in kinds):
        stated = block_attention_stated(cfg)
        if not stated:
            return None
        return (f"{what} builds every block from the model-wide head count "
                "and rotation, with no window and no gate; this model "
                f"states {', '.join(stated)}, which only the pp=1 training "
                "path (builder.forward_causal_lm) gives each block")
    from collections import Counter

    said = ", ".join(f"{n} x {m or '-'}/{ff or '-'}"
                     for (m, ff), n in Counter(kinds).items())
    return (f"{what} takes a stack of one kind of block, with attention as "
            f"its mixer; this model's per-layer description holds {said} "
            "(mixer/feed-forward; '-' = a block of one branch has none)")


def overlap_unsupported_reason(
    cfg: Any,
    *,
    ulysses: bool,
    has_cp: bool,
    tp: int,
    seq_len: Optional[int] = None,
) -> Optional[str]:
    """Why one layer cannot run the decomposed ring-overlap matmuls
    (None = eligible). ``cfg`` supplies the concrete widths (seq_length,
    head_dim, heads, ffn_dim, hidden_act); the parallel degrees come in as
    plain values so both the mesh-lowered runtime and the degree-only
    search/doctor views evaluate the same predicate."""
    if ulysses:
        return ("ulysses layer: the tp axes carry sequence (all-to-all "
                "attention), not weight shards")
    if tp <= 1:
        return "tp == 1 (no tensor-parallel collectives to overlap)"
    if has_cp:
        return ("cp layer: the boundary activation is sequence-sharded "
                "over cp, not tp (ring attention owns the sequence axis)")
    seq = seq_len if seq_len is not None else cfg.seq_length
    if seq % tp:
        return (f"tp {tp} does not divide the sequence length {seq} into "
                "ring chunks")
    hd, nq, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.kv_heads
    if ((nq + 2 * nkv) * hd) % tp or (nq * hd) % tp:
        return f"tp {tp} does not divide the qkv/out projection widths"
    f = cfg.ffn_dim
    gated = cfg.hidden_act in ("swiglu", "geglu")
    if f % tp or (gated and (2 * f) % tp):
        return f"tp {tp} does not divide the MLP width {f}"
    return None


def layer_overlap_reason(cfg: Any, sharding: Any, tp: int,
                         seq_len: Optional[int] = None) -> Optional[str]:
    """Mesh-lowered adapter (the historical ``ops/overlap.py`` entry
    point): reads ulysses/cp off a :class:`~hetu_galvatron_tpu.runtime.
    mesh.LayerSharding`-shaped object."""
    return overlap_unsupported_reason(
        cfg,
        ulysses=bool(getattr(sharding, "ulysses", False)),
        has_cp=bool(getattr(sharding, "cp_axes", ())),
        tp=tp,
        seq_len=seq_len,
    )


def plan_overlap_reasons(cfg: Any, hpc: Any) -> List:
    """Per-layer eligibility from the PLAN alone (``hpc.layers``
    LayerStrategy rows; no mesh needed) — what
    ``parallel.spmd.tp_overlap_overrides`` will dispatch. Returns
    [(layer index, reason-or-None)]; reason None = the layer runs
    overlapped."""
    from hetu_galvatron_tpu.models.moe import is_moe_layer

    out = []
    for i, s in enumerate(hpc.layers):
        if cfg.model_type == "t5":
            out.append((i, T5_REASON))
            continue
        if is_moe_layer(cfg, i):
            out.append((i, MOE_REASON))
            continue
        if getattr(cfg, "one_branch_blocks", False):
            out.append((i, ONE_BRANCH_REASON))
            continue
        mixer = cfg.block_kinds(len(hpc.layers))[i][0]
        if mixer != "full_attention":
            out.append((i, MIXER_OVERLAP_REASON[mixer]))
            continue
        out.append((i, overlap_unsupported_reason(
            cfg, ulysses=s.sp, has_cp=s.cp_size > 1, tp=s.tp_size)))
    return out


def search_tp_overlap_expressible(tp: int, cp: int, enabled: bool) -> bool:
    """Cost-model adapter (``cost_model.cost.tp_overlap_expressible``):
    can this candidate layer earn the ring-overlap discount? Megatron TP
    only (Ulysses has tp == 1 here) and no cp — the degree-level half of
    :func:`overlap_unsupported_reason` (the search works in degrees, not
    concrete widths, so the divisibility checks are resolved at plan-doctor
    / runtime time)."""
    return enabled and tp > 1 and cp == 1


# ---------------------------------------------------------------------------
# plan structure (divisibility / stage sums / axis products)
# ---------------------------------------------------------------------------


def pp_world_reason(world_size: int, pp_deg: int) -> Optional[str]:
    if pp_deg >= 1 and world_size % pp_deg:
        return f"world {world_size} % pp {pp_deg} != 0"
    return None


def stage_degree_reason(world_size: int, pp_deg: int, tp: int,
                        cp: int) -> Optional[str]:
    stage = world_size // max(pp_deg, 1)
    if stage % (tp * cp):
        return f"stage world {stage} not divisible by tp{tp}*cp{cp}"
    return None


def vpp_layers_reason(pp_deg: int, vpp_deg: int,
                      n_layers: int) -> Optional[str]:
    if pp_deg * vpp_deg > n_layers:
        return (f"pp_deg {pp_deg} * virtual_pp_deg {vpp_deg} exceeds the "
                f"layer count {n_layers}")
    return None


def pp_division_sum_reason(pp_division: Sequence[int],
                           n_layers: int) -> Optional[str]:
    if sum(pp_division) != n_layers:
        return f"pp_division {list(pp_division)} != layer count {n_layers}"
    return None


def pp_division_len_reason(pp_division: Sequence[int], pp_deg: int,
                           vpp_deg: int) -> Optional[str]:
    if len(pp_division) != pp_deg * vpp_deg:
        return (f"pp_division has {len(pp_division)} entries, expected "
                f"pp_deg {pp_deg} * vpp_deg {vpp_deg} = {pp_deg * vpp_deg}")
    return None


def batch_grain_reason(global_bsz: int, world_size: int, pp_deg: int,
                       layers: Sequence[Any], vocab: Any) -> Optional[str]:
    """The batch must divide by the largest dp group any layer carves out
    (world // pp // min_tp // min_cp)."""
    min_tp = min(min(s.tp_size for s in layers), vocab.vtp)
    min_cp = min(min(s.cp_size for s in layers), vocab.vcp)
    grain = world_size // max(pp_deg, 1) // min_tp // min_cp
    if global_bsz % max(grain, 1):
        return (f"global_bsz {global_bsz} must be a multiple of "
                f"world//pp//min_tp//min_cp = {grain}")
    return None


def plan_structure_reasons(
    *,
    layers: Sequence[Any],
    vocab: Any,
    pp_deg: int,
    vpp_deg: int,
    pp_division: Sequence[int],
    n_layers: int,
    world_size: int,
    global_bsz: int,
) -> List[str]:
    """Every structural problem with a resolved plan, in the order
    ``runtime/hybrid_config.py`` raises them (it raises on the FIRST;
    the plan doctor reports them all)."""
    out: List[str] = []
    for r in (
        pp_world_reason(world_size, pp_deg),
        vpp_layers_reason(pp_deg, vpp_deg, n_layers),
        pp_division_sum_reason(pp_division, n_layers),
        pp_division_len_reason(pp_division, pp_deg, vpp_deg),
        batch_grain_reason(global_bsz, world_size, pp_deg, layers, vocab),
    ):
        if r is not None:
            out.append(r)
    return out


# ---------------------------------------------------------------------------
# MoE capacity dispatch: do its one-hot tensors fit a chip at all
# ---------------------------------------------------------------------------


def moe_capacity_of(tokens: int, topk: int, experts: int,
                    capacity_factor: float) -> int:
    """Rows an expert's capacity buffer holds for ``tokens`` routed tokens
    (``models/moe.py::moe_capacity``)."""
    return max(int(math.ceil(tokens * topk / experts * capacity_factor)),
               topk)


def capacity_dispatch_reason(
    *,
    dispatcher: str,
    tokens: int,
    topk: int,
    experts: int,
    capacity_factor: float,
    devices: int,
    hbm_gb: float,
) -> Optional[str]:
    """None when the GShard ``capacity`` dispatcher can hold its position
    one-hot ``[T*K, E, C]`` float32 (``models/moe.py::_capacity_dispatch``)
    for a microbatch of ``tokens`` tokens, even spread evenly over the
    ``devices`` chips of a stage, within ``hbm_gb`` GB a chip; otherwise the
    reason, which names the dispatcher that has no such tensor. It grows
    with T squared: 5.4 GB at 4096 tokens, 64 experts, top-8."""
    if dispatcher != "capacity" or not experts:
        return None
    cap = moe_capacity_of(tokens, topk, experts, capacity_factor)
    total_gb = tokens * topk * experts * cap * 4 / 1e9
    chips = max(devices, 1)
    if total_gb / chips <= hbm_gb:
        return None
    return (f"moe_dispatcher=capacity builds a [T*K, E, C] float32 one-hot "
            f"of {tokens} x {topk} x {experts} x {cap} x 4 B = "
            f"{total_gb:.1f} GB a microbatch ({total_gb / chips:.1f} GB a "
            f"chip over {chips}), over the {hbm_gb:g} GB a chip holds: set "
            "model.moe_dispatcher=dropless (sorted grouped matmuls, no "
            "capacity buffer), or lower the tokens a microbatch "
            "(parallel.chunks)")
