"""SPMD model assembly: strategies + mesh -> sharded params, train step.

Capability parity with the reference's hybrid-parallel model construction
(runtime/hybrid_parallel_model.py:107 ``construct_hybrid_parallel_model_api``
+ runtime/parallel.py:307-387 per-layer FSDP wrapping): the per-layer strategy
vectors become per-param `PartitionSpec`s (TP via logical weight axes, ZeRO-3
via dp-sharded params, ZeRO-2 via dp-sharded optimizer moments) and
layer-boundary `with_sharding_constraint`s (the reference's relocation,
parallel.py:272-304). One `jax.jit` with in/out shardings replaces the whole
wrapper stack; XLA emits the all-gathers/reduce-scatters the reference issues
through NCCL.
"""

from __future__ import annotations

import logging
import time
from dataclasses import replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.models.builder import causal_lm_loss
from hetu_galvatron_tpu.models.modules import LayerOps
from hetu_galvatron_tpu.parallel import kept
from hetu_galvatron_tpu.runtime.hybrid_config import HybridParallelConfig
from hetu_galvatron_tpu.runtime.mesh import (
    LayerSharding,
    attention_core,
    axes_size,
    flash_kernel_runs,
    lower_strategy,
    lower_vocab_strategy,
    spec_tree,
)
from hetu_galvatron_tpu.runtime.trainer import make_train_step

Params = Dict[str, Any]


def layer_shardings(
    hpc: HybridParallelConfig, mesh: Mesh
) -> Tuple[List[LayerSharding], LayerSharding]:
    """Lower every decoder layer + the vocab strategy onto the mesh
    (reference gen_comm_groups + hp_config_whole_model in one step)."""
    per_layer = [lower_strategy(s, mesh) for s in hpc.layers]
    vocab = lower_vocab_strategy(hpc.vocab, mesh, hpc.default_dp_type)
    return per_layer, vocab


# shared logical-axes -> PartitionSpec lowering (runtime/mesh.py)
_spec_tree = spec_tree


def param_specs(
    axes_tree: Params,
    per_layer: List[LayerSharding],
    vocab: LayerSharding,
    *,
    opt: bool = False,
    enc_per_layer: Optional[List[LayerSharding]] = None,
) -> Params:
    """PartitionSpec pytree mirroring the params tree: decoder layers use
    their own sharding, embed/prenorm/head use the vocab sharding (reference
    whole-model rows, hybrid_parallel_config.py:276-293). Encoder-decoder
    models (t5) shard each encoder layer with its own strategy from the
    combined-stack plan (``enc_per_layer``); legacy callers that pass only
    decoder shardings fall back to cloning the first decoder strategy."""
    out = {
        "embed": _spec_tree(axes_tree["embed"], vocab, opt),
        "layers": tuple(
            _spec_tree(a, sh, opt)
            for a, sh in zip(axes_tree["layers"], per_layer)),
        "prenorm": _spec_tree(axes_tree["prenorm"], vocab, opt),
        "head": _spec_tree(axes_tree["head"], vocab, opt),
    }
    if "mtp" in axes_tree:
        # the further prediction depth's block is sharded as the last block
        out["mtp"] = _spec_tree(axes_tree["mtp"], per_layer[-1], opt)
    if "tower" in axes_tree:
        # the tower in front of the decoder takes the first block's plan
        # (whole on every device: eligibility.tower_plan_reason)
        out["tower"] = _spec_tree(axes_tree["tower"], per_layer[0], opt)
    if "enc_layers" in axes_tree:
        enc = (enc_per_layer if enc_per_layer is not None
               else [per_layer[0]] * len(axes_tree["enc_layers"]))
        out["enc_layers"] = tuple(
            _spec_tree(a, sh, opt)
            for a, sh in zip(axes_tree["enc_layers"], enc))
        out["enc_norm"] = _spec_tree(axes_tree["enc_norm"], vocab, opt)
    return out


def opt_state_specs(
    tx: optax.GradientTransformation,
    params: Params,
    opt_param_specs: Params,
) -> Any:
    """Specs for the optimizer state: leaves whose tree path ends with a
    param's path (adam mu/nu mirror the params tree) get that param's
    opt-spec; everything else (step counts) is replicated."""
    state_shape = jax.eval_shape(tx.init, params)
    flat_specs = {
        tuple(str(k) for k in path): spec
        for path, spec in jax.tree_util.tree_flatten_with_path(
            opt_param_specs,
            is_leaf=lambda x: isinstance(x, P))[0]
    }
    param_paths = list(flat_specs)

    def for_leaf(path, leaf):
        key = tuple(str(k) for k in path)
        for ppath in param_paths:
            if len(key) >= len(ppath) and key[-len(ppath):] == ppath:
                # moments mirror the param exactly; anything else that
                # happens to share the path suffix (unlikely) differs in rank
                if len(flat_specs[ppath]) == leaf.ndim:
                    return flat_specs[ppath]
        return P()

    return jax.tree_util.tree_map_with_path(for_leaf, state_shape)


def merge_ops(under: Dict[int, LayerOps],
              over: Optional[Dict[int, LayerOps]]) -> Dict[int, LayerOps]:
    """The one rule by which two sources of a layer's operators meet: field
    by field ``over``'s beats ``under``'s, and a field ``over`` leaves unset
    keeps ``under``'s (a caller's record on a cp layer does not drop the
    plan's ring core unless it sets a core itself)."""
    out = dict(under)
    for i, ops in (over or {}).items():
        out[i] = replace(out.get(i, LayerOps()), **ops.given())
    return out


def block_kernels():
    """The Pallas kernels a plan hands a block beside its attention core, a
    row a field of ``LayerOps``: (the field, its ``make_*(mesh, dp_axes=,
    interpret=)``, whether the layer's sequence has to be whole on a device,
    the layer's axes its operands may be cut over beside dp, as ``tp_axes=``).
    The kinds of block that take a field are the rows of ``modules.MIXERS``
    that name it; whether the shapes fit a kernel's tiles is the kernel's
    caller's to see (``modules.ssd_chunked``, ``kda_chunked``,
    ``apply_gated_delta``, ``apply_mamba1``, ``causal_depthwise_conv``,
    ``mamba_gated_norm``),
    which keeps its ``jax.numpy`` form where they do not (for ``gdn`` also
    where the chunk does not divide the sequence). A mamba, mamba1, kda or
    linear_attention block cut any other way than over dp is refused by
    name (analysis/eligibility.py); a depthwise convolution is local to a
    channel shard."""
    from hetu_galvatron_tpu.ops.pallas.conv import make_causal_conv
    from hetu_galvatron_tpu.ops.pallas.gated_norm import make_gated_norm
    from hetu_galvatron_tpu.ops.pallas.gdn import make_gdn_scan
    from hetu_galvatron_tpu.ops.pallas.kda import make_kda_scan
    from hetu_galvatron_tpu.ops.pallas.selective_scan import (
        make_selective_scan,
    )
    from hetu_galvatron_tpu.ops.pallas.ssd import make_ssd_scan

    return (("ssd", make_ssd_scan, False, None),
            ("kda", make_kda_scan, False, None),
            ("gdn", make_gdn_scan, False, None),
            ("selective", make_selective_scan, False, None),
            ("conv", make_causal_conv, True, "weight_tp_axes"),
            ("gated_norm", make_gated_norm, False, None))


def attention_overrides(
    per_layer: List[LayerSharding],
    mesh: Mesh,
    *,
    use_flash: Optional[bool] = None,
    with_cross: bool = False,
    cp_zigzag: bool = False,
    flash_interpret: bool = False,
    mixers: Optional[Sequence[str]] = None,
    kernels: Optional[bool] = None,
) -> Dict[int, LayerOps]:
    """Per-layer attention-impl dispatch (reference attention.py:664-720),
    branching on :func:`~hetu_galvatron_tpu.runtime.mesh.attention_core`:
    cp > 1 layers swap in the ring-attention kernel over their cp axes;
    other layers get the Pallas flash kernel when ``use_flash`` (None = the
    shared rule :func:`~hetu_galvatron_tpu.runtime.mesh.flash_kernel_runs`:
    every mesh device is a TPU); everything else keeps the XLA core (GSPMD
    inserts the collectives).

    Ulysses layers get the explicit head-scatter all-to-all attention
    (ops/ulysses.py, reference _SeqAllToAll) instead of leaving GSPMD to
    infer collectives for a sequence-sharded softmax; on TPU the local core
    inside the a2a sandwich is the flash kernel.

    ``with_cross=True`` (t5 decoder layers) also sets ``cross_sdpa``:
    ring and ulysses layers pin cross-attention to the XLA core (the ring
    kernel needs equal q/kv sequence lengths and the a2a sandwich assumes
    self-attention geometry; GSPMD inserts the collectives instead), while
    flash layers reuse the flash kernel, which handles causal=False and
    unequal q/kv lengths.

    ``flash_interpret=True`` runs the Pallas kernels in interpret mode —
    CPU parity drills forcing ``use_flash=True`` on the virtual mesh (the
    compiled-vs-host kernel drills run the SAME kernel on both sides).

    ``mixers`` (the layers' mixer kinds, ``ModelArgs.block_kinds``; None =
    every layer attends): a layer whose kind does not attend, or that has no
    mixer (a feed-forward block of a one-branch stack), gets no core, and
    a layer whose kind reads a field of :func:`block_kernels` (a ``mamba``
    layer ``ssd``, ``conv`` and ``gated_norm``, a ``kda`` layer ``kda`` and
    ``conv``, a
    ``mamba1`` layer ``selective`` and ``conv``, a ``linear_attention``
    layer ``gdn`` and ``conv``, a ``conv`` layer ``conv``)
    gets that kernel when ``kernels`` (None = the same rule: every mesh
    device is a TPU)."""
    from functools import partial as _partial

    from hetu_galvatron_tpu.models.modules import MIXERS, xla_sdpa
    from hetu_galvatron_tpu.ops.ring_attention import make_ring_sdpa
    from hetu_galvatron_tpu.ops.ulysses import make_ulysses_sdpa

    if use_flash is None or kernels is None:
        on_tpu = flash_kernel_runs(True, mesh.devices.flat)
        use_flash = on_tpu if use_flash is None else use_flash
        kernels = on_tpu if kernels is None else kernels
    out: Dict[int, LayerOps] = {}
    for i, sh in enumerate(per_layer):
        if mixers is not None and (mixers[i] is None
                                   or not MIXERS[mixers[i]].attends):
            continue
        core = attention_core(bool(sh.cp_axes),
                              bool(sh.ulysses and sh.tp_axes), use_flash)
        cross = xla_sdpa if with_cross else None
        if core.startswith("ring"):
            out[i] = LayerOps(sdpa=make_ring_sdpa(
                mesh, sh.cp_axes, dp_axes=sh.dp_axes, tp_axes=sh.tp_axes,
                use_flash=use_flash, zigzag=cp_zigzag,
                data_zigzagged=cp_zigzag, interpret=flash_interpret),
                cross_sdpa=cross)
        elif core.startswith("ulysses"):
            local = None
            if use_flash:
                from hetu_galvatron_tpu.ops.pallas.flash_attention import (
                    flash_sdpa,
                )

                local = (_partial(flash_sdpa, interpret=True)
                         if flash_interpret else flash_sdpa)
            out[i] = LayerOps(sdpa=make_ulysses_sdpa(
                mesh, sh.tp_axes, dp_axes=sh.dp_axes, local_sdpa=local),
                cross_sdpa=cross)
        elif core == "flash":
            from hetu_galvatron_tpu.ops.pallas.flash_attention import (
                make_flash_sdpa,
            )

            out[i] = LayerOps(sdpa=make_flash_sdpa(
                mesh, dp_axes=sh.dp_axes, tp_axes=sh.tp_axes,
                interpret=flash_interpret))
    for field, make, whole, cut in block_kernels() if kernels else ():
        for i, mixer in enumerate(mixers or ()):
            sh = per_layer[i]
            if mixer is None:   # a feed-forward block: no mixer's kernel
                continue
            if MIXERS[mixer].reads(field) and not (whole and sh.cp_axes):
                out[i] = replace(out.get(i, LayerOps()), **{field: make(
                    mesh, dp_axes=sh.dp_axes, interpret=flash_interpret,
                    **({"tp_axes": getattr(sh, cut)} if cut else {}))})
    return out


def expert_exchange_overrides(
    per_layer: List[LayerSharding],
    mesh: Mesh,
    cfg: ModelArgs,
    hpc: HybridParallelConfig,
) -> Dict[int, LayerOps]:
    """``LayerOps.exchange`` for every expert block whose plan carves ``ep``
    axes from dp and whose sorted dispatcher the exchange serves
    (analysis/eligibility.py::takes_exchange: ``dropless`` or a held share,
    tp = cp = etp = 1, the pp = 1 path): models/moe.py::
    make_expert_exchange over the layer's dp and ep axes. A plan without
    ``ep`` axes gets an empty dict and its blocks the program they had."""
    from hetu_galvatron_tpu.analysis.eligibility import takes_exchange
    from hetu_galvatron_tpu.models.moe import make_expert_exchange

    strategies = hpc.layers[hpc.num_encoder_layers:]
    kinds = cfg.block_kinds(len(per_layer))
    cache: Dict[Tuple, LayerOps] = {}
    out: Dict[int, LayerOps] = {}
    for i, (sh, s) in enumerate(zip(per_layer, strategies)):
        if kinds[i][1] != "experts" or not takes_exchange(
                cfg, s, hpc.pp_deg):
            continue
        key = (sh.dp_axes, sh.ep_axes)
        if key not in cache:
            cache[key] = LayerOps(exchange=make_expert_exchange(mesh, *key))
        out[i] = cache[key]
    return out


def expert_kernel_overrides(
    per_layer: List[LayerSharding],
    mesh: Mesh,
    cfg: ModelArgs,
    hpc: HybridParallelConfig,
    *,
    kernels: Optional[bool] = None,
    interpret: bool = False,
) -> Dict[int, LayerOps]:
    """``LayerOps.grouped``, the Pallas kernels of the sorted dispatcher's
    grouped matmuls (ops/pallas/grouped_matmul.py), for every expert block
    whose rows and expert weights are whole on the device that runs the
    block: a mesh of one device, or a block inside the expert exchange
    (``eligibility.takes_exchange``: a chip on its own experts, inside a
    ``shard_map``). A Pallas call is a custom call GSPMD cannot partition,
    so a block whose experts or rows a mesh of several devices cuts any
    other way (``ep`` without the exchange, ``etp > 1``, plain dp) keeps
    ``lax.ragged_dot``, as does the ``capacity`` dispatcher, which has no
    grouped matmul. ``kernels`` None = the shared rule of
    :func:`attention_overrides`: every mesh device is a TPU."""
    from hetu_galvatron_tpu.analysis.eligibility import takes_exchange
    from hetu_galvatron_tpu.ops.pallas.grouped_matmul import (
        make_grouped_matmul,
    )

    if kernels is None:
        kernels = flash_kernel_runs(True, mesh.devices.flat)
    sorted_rows = (cfg.moe_dispatcher == "dropless"
                   or cfg.held_experts < cfg.num_experts)
    if not (kernels and sorted_rows):
        return {}
    ops = LayerOps(grouped=make_grouped_matmul(mesh, interpret=interpret))
    strategies = hpc.layers[hpc.num_encoder_layers:]
    kinds = cfg.block_kinds(len(per_layer))
    return {i: ops for i, (_, s) in enumerate(zip(per_layer, strategies))
            if kinds[i][1] == "experts"
            and (mesh.size == 1 or takes_exchange(cfg, s, hpc.pp_deg))}


def tp_overlap_overrides(
    per_layer: List[LayerSharding],
    mesh: Mesh,
    cfg: ModelArgs,
    *,
    is_moe_layer_fn: Optional[Any] = None,
) -> Tuple[Dict[int, LayerOps], List[Tuple[int, str]]]:
    """Per-layer overlapped-TP matmul dispatch (the ``matmuls`` analogue
    of :func:`attention_overrides`): eligible Megatron-TP layers get the
    decomposed ring all-gather/reduce-scatter matmuls (ops/overlap.py);
    everything else stays on GSPMD. Returns (overrides, fallbacks) where
    ``fallbacks`` lists (layer index, unsupported_reason) for layers the
    caller asked to overlap but could not — the launcher logs them."""
    from hetu_galvatron_tpu.analysis.eligibility import (
        MIXER_OVERLAP_REASON,
        MOE_REASON,
        ONE_BRANCH_REASON,
        T5_REASON,
        layer_overlap_reason,
    )
    from hetu_galvatron_tpu.models.moe import is_moe_layer
    from hetu_galvatron_tpu.ops.overlap import make_layer_matmuls
    from hetu_galvatron_tpu.runtime.mesh import axes_size

    moe_of = is_moe_layer_fn or is_moe_layer
    kinds = cfg.block_kinds(len(per_layer))
    out: Dict[int, LayerOps] = {}
    fallbacks: List[Tuple[int, str]] = []
    cache: Dict[Tuple, LayerOps] = {}
    for i, sh in enumerate(per_layer):
        if cfg.model_type == "t5":
            fallbacks.append((i, T5_REASON))
            continue
        if moe_of(cfg, i):
            fallbacks.append((i, MOE_REASON))
            continue
        if cfg.one_branch_blocks:
            fallbacks.append((i, ONE_BRANCH_REASON))
            continue
        if kinds[i][0] != "full_attention":
            fallbacks.append((i, MIXER_OVERLAP_REASON[kinds[i][0]]))
            continue
        tp_axes = sh.weight_tp_axes
        reason = layer_overlap_reason(cfg, sh, axes_size(mesh, tp_axes))
        if reason is not None:
            fallbacks.append((i, reason))
            continue
        key = (sh.dp_axes, tp_axes)
        if key not in cache:
            cache[key] = LayerOps(matmuls=make_layer_matmuls(
                mesh, sh.dp_axes, tp_axes))
        out[i] = cache[key]
    return out, fallbacks


def interior_sharding(
    per_layer: List[LayerSharding],
    mesh: Mesh,
    cfg: ModelArgs,
    layer_overrides: Dict[int, LayerOps],
) -> Tuple[Dict[int, LayerOps], Optional[Callable[[Params], Params]]]:
    """Keep a tensor-parallel layer's interior on its own shards, from the
    first projection to the second. The boundary constraint leaves a layer's
    hidden state sequence-sharded over tp (Megatron-SP); with nothing said
    inside, GSPMD keeps the whole interior that way too, because the stored
    ``[q | k | v]`` cannot be split on a shard, and pays an all-to-all on
    every activation to get heads for the attention core and back.

    Returns (overrides, param_view). ``overrides[i].shard`` pins an
    activation of layer i: batch on its dp axes, sequence on its cp axes,
    the named dimension on its tp axes (modules.apply_attention /
    apply_mlp call it). ``param_view`` re-lays those layers' fused
    projections so that a shard computes what it needs from what it holds:
    qkv group-major with the group axis on tp (modules.qkv_group_major), a
    gated MLP's gate | up as ``[H, 2, F]`` with F on tp
    (modules.gate_up_pairs), the biases likewise. The step applies it once,
    outside the microbatch scan (trainer.make_train_step; the eval step's
    loss applies it itself); what is stored stays ``[q | k | v]`` and
    ``[gate | up]``, which on this TPU is the layout a tp = 1 layer's one
    fused matmul wants (a stored ``[H, 2, F]`` is no bitcast of
    ``[H, 2F]`` under (8, 128) tiling: it cost
    ``mistral7b_c1_s4k`` 14 % more estimated cycles, AOT, PR 28). Only
    what the plan says decides: layers with no weight-tp axes (tp = 1,
    Ulysses), MoE and t5 layers are left as they are, and so are layers
    whose matmuls the caller replaced (``matmuls``): tp_overlap's
    shard_map kernels are cut for the stored two-axis weights, and the host
    pipeline engine hands the same kernels to the same layer body with no
    view (ROADMAP.md speed item 1(h): the kernels take the views, then
    ``fc1_pair`` goes). With none left ``param_view`` is None."""
    from hetu_galvatron_tpu.models.modules import (
        _is_gated,
        gate_up_pairs,
        qkv_group_major,
    )
    from hetu_galvatron_tpu.models.moe import is_moe_layer

    local = {
        i: sh for i, sh in enumerate(per_layer)
        if sh.weight_tp_axes and cfg.model_type != "t5"
        and not is_moe_layer(cfg, i)
        and layer_overrides.get(i, LayerOps()).matmuls is None}
    if not local:
        return {}, None

    def make_shard(sh: LayerSharding):
        def shard(a: jax.Array, axis: int) -> jax.Array:
            dims = [sh.dp_axes or None, sh.cp_axes or None]
            dims += [None] * (a.ndim - 2)
            dims[axis] = sh.tp_axes
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P(*dims)))
        return shard

    gated = _is_gated(cfg.hidden_act)

    def pin(a, sharding, axes):
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, sharding.param_spec(axes)))

    def relaid(sh, leaf, relay, lead, cut, new):
        """``relay(leaf)`` (the stored ``lead + cut`` axes become ``lead +
        new``) cut to the tp shard: gathered over tp, re-laid where it
        stands, then sliced. All-gathers forward and backward, once a step;
        left to itself GSPMD moves the columns by all-to-all."""
        whole = replace(sh, tp_axes=())   # the same plan, tp left out
        a = relay(pin(leaf, whole, lead + cut))
        return pin(pin(a, whole, lead + new), sh, lead + new)

    def param_view(params: Params) -> Params:
        layers = list(params["layers"])
        for i, sh in local.items():
            # a conv block has no fused q | k | v to re-lay: its thirds are
            # stored apart and shard by channel as they stand
            attn = dict(layers[i].get("attn", {}))
            mlp = dict(layers[i]["mlp"])
            for name, lead in (("wqkv", ("embed",)), ("bqkv", ())):
                if name in attn:
                    attn[name] = relaid(
                        sh, attn[name], lambda w: qkv_group_major(w, cfg),
                        lead, ("qkv",), ("qkv", "width"))
            for name, lead in (("win", ("embed",)), ("bin", ())):
                if gated and name in mlp:
                    mlp[name] = relaid(sh, mlp[name], gate_up_pairs, lead,
                                       ("mlp",), ("pair", "mlp"))
            layers[i] = {**layers[i], "mlp": mlp,
                         **({"attn": attn} if attn else {})}
        return {**params, "layers": tuple(layers)}

    return ({i: LayerOps(shard=make_shard(sh)) for i, sh in local.items()},
            param_view)


def make_boundary_fn(
    per_layer: List[LayerSharding],
    vocab: LayerSharding,
    mesh: Mesh,
) -> Callable[[int, jax.Array], jax.Array]:
    """Resharding constraints at layer boundaries — GSPMD's version of the
    reference's Module_with_relocation split/all-gather (parallel.py:272-304,
    redistribute.py:345-415). Boundary i < n constrains the input of layer i;
    boundary n (after the last layer) re-constrains for prenorm/head."""
    n = len(per_layer)

    def boundary(i: int, x: jax.Array) -> jax.Array:
        sh = per_layer[i] if i < n else vocab
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, sh.act_spec()))

    return boundary


def make_embed_use_constraint(
    embed_axes: Params, vocab: LayerSharding, mesh: Mesh
) -> Callable[[Params], Params]:
    """ZeRO-3 shards the embedding table's hidden dim across dp; the table
    must be (all-)gathered before the token lookup. State that explicitly
    with a use-site `with_sharding_constraint` (hidden dim unsharded, vocab
    dim still vtp-sharded) so the partitioner doesn't solve the gather with
    a hidden-sharded output and then full-rematerialize it to the batch/seq
    activation layout — the `spmd_partitioner.cc` "Involuntary full
    rematerialization" warning. Backward gets the transpose for free: the
    wte grad is formed in the gathered layout and reduce-scattered back to
    the ZeRO-3 spec by the constraint's adjoint. This is the relocation the
    reference does by hand (runtime/redistribute.py:345-415)."""
    is_axes = lambda x: (isinstance(x, tuple)
                         and all(isinstance(s, str) for s in x))
    specs = jax.tree.map(
        lambda la: vocab.param_spec(la, zero3_override=False),
        embed_axes, is_leaf=is_axes)

    def constrain(embed_params: Params) -> Params:
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)),
            embed_params, specs)

    return constrain


def shard_params(params: Params, specs: Params, mesh: Mesh) -> Params:
    """Place an (unsharded, host/single-device) params tree onto the mesh."""
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs)


def batch_sharding(
    per_layer: List[LayerSharding], mesh: Mesh
) -> NamedSharding:
    """Input batch layout: shard over the first decoder layer's dp axes (and
    cp axes along sequence); interior constraints reshard per layer."""
    return NamedSharding(mesh, per_layer[0].batch_spec())


def _lower_specs(hpc: HybridParallelConfig, mesh: Mesh, axes_tree: Params):
    """Shared lowering preamble: strategies -> (per-layer shardings, vocab
    sharding, param PartitionSpec tree) with the t5 combined-stack split."""
    per_layer_all, vocab = layer_shardings(hpc, mesh)
    n_enc = hpc.num_encoder_layers
    enc_per, per_layer = per_layer_all[:n_enc], per_layer_all[n_enc:]
    pspecs = param_specs(axes_tree, per_layer, vocab,
                         enc_per_layer=enc_per or None)
    return enc_per, per_layer, vocab, pspecs


def plan_remat_flags(cfg: ModelArgs, per_layer: Sequence[Any],
                     enc_per: Sequence[Any]) -> Dict[str, List[bool]]:
    """The plan's ``checkpoint`` bits as the model's stacks read them: the
    decoder's blocks, the tower's (all under the first decoder block's plan)
    and a t5's encoder's (``per_layer``, ``enc_per``: the layers' lowered
    shardings, or their strategies). A set bit says the block MAY be
    recomputed."""
    return {"decoder": [bool(sh.checkpoint) for sh in per_layer],
            "tower": [bool(per_layer[0].checkpoint)] * cfg.tower_layers,
            "encoder": [bool(sh.checkpoint) for sh in enc_per]}


def build_spmd_loss_fn(
    cfg: ModelArgs,
    hpc: HybridParallelConfig,
    mesh: Mesh,
    axes_tree: Params,
    *,
    compute_dtype=jnp.bfloat16,
    layer_overrides: Optional[Dict[int, LayerOps]] = None,
    with_moe_stats: bool = False,
    tp_overlap: bool = False,
    kernel_interpret: bool = False,
    hoist_view: bool = False,
    remat_flags: Optional[Dict[str, Sequence[Any]]] = None,
):
    """The plan-lowered loss closure shared by the train and eval steps:
    per-layer shardings, boundary constraints, attention-impl dispatch,
    remat flags, fused CE, and the ZeRO-3 embed use-site constraint.
    Returns (loss_fn, pspecs, batch_shd, per_layer, vocab, enc_per,
    param_view). ``loss_fn`` takes the stored parameters and re-lays a
    tp > 1 layer's fused projections itself (:func:`interior_sharding`);
    with ``hoist_view`` it takes ``param_view(params)`` instead, where
    ``param_view`` is not None, so that the caller can re-lay them once
    outside its microbatch scan.
    ``tp_overlap`` swaps eligible Megatron-TP layers' projection matmuls
    for the decomposed ring collectives (:func:`tp_overlap_overrides`);
    ineligible layers silently keep GSPMD — the launcher logs the reasons.
    ``kernel_interpret`` runs the Pallas kernels (flash, fused CE) in
    interpret mode: CPU tests pass it, nothing infers it.
    ``remat_flags`` (:func:`plan_remat_flags`'s lists, by stack) says which
    blocks are rematerialized; None = every block whose plan bit is set
    (the train step hands the lists it chose, :class:`KeptStep`)."""
    enc_per, per_layer, vocab, pspecs = _lower_specs(hpc, mesh, axes_tree)
    boundary = make_boundary_fn(per_layer, vocab, mesh)
    enc_boundary = (make_boundary_fn(enc_per, vocab, mesh)
                    if enc_per else None)
    use_flash = None if cfg.use_flash_attn else False
    ring = attention_overrides(
        per_layer, mesh, use_flash=use_flash,
        with_cross=cfg.model_type == "t5",
        cp_zigzag=getattr(hpc, "cp_zigzag", False),
        flash_interpret=kernel_interpret,
        mixers=[m for m, _ in cfg.block_kinds(len(per_layer))])
    enc_overrides = (attention_overrides(
        enc_per, mesh, use_flash=use_flash,
        flash_interpret=kernel_interpret) if enc_per else None)
    if tp_overlap:
        # under the plan's kernels, and both under the caller's
        ring = merge_ops(tp_overlap_overrides(per_layer, mesh, cfg)[0], ring)
    if cfg.num_experts and cfg.model_type != "t5":
        ring = merge_ops(ring, expert_exchange_overrides(
            per_layer, mesh, cfg, hpc))
        ring = merge_ops(ring, expert_kernel_overrides(
            per_layer, mesh, cfg, hpc, interpret=kernel_interpret))
    layer_overrides = merge_ops(ring, layer_overrides)
    interior, param_view = interior_sharding(per_layer, mesh, cfg,
                                             layer_overrides)
    layer_overrides = merge_ops(interior, layer_overrides)
    flags = remat_flags or plan_remat_flags(cfg, per_layer, enc_per)
    remat, enc_remat = flags["decoder"], flags["encoder"]
    batch_shd = batch_sharding(per_layer, mesh)

    enc_kwargs = {}
    if cfg.model_type == "t5":
        # always pass the explicit per-layer list: None would trigger the
        # legacy clone-remat_flags[0] fallback in forward_encdec
        enc_kwargs = dict(
            enc_remat_flags=enc_remat,
            enc_layer_overrides=enc_overrides,
            enc_boundary_fn=enc_boundary)

    # Fused CE on a mesh: a bare Pallas call is a custom call GSPMD cannot
    # partition, so distributed runs get the shard_map vocab-parallel
    # wrapper matched to the head's sharding (pmax/psum logsumexp merge
    # across vocab shards — the reference's Triton vocab-parallel CE
    # semantics); single-device runs use the kernel directly.
    fused_ce = cfg.use_fused_ce
    if fused_ce:
        from functools import partial

        from hetu_galvatron_tpu.ops.pallas.cross_entropy import (
            fused_ce_nll,
            make_vocab_parallel_ce,
        )

        fused_ce = (make_vocab_parallel_ce(mesh, vocab,
                                           interpret=kernel_interpret)
                    if mesh.size > 1
                    else partial(fused_ce_nll, interpret=kernel_interpret))

    constrain_embed = make_embed_use_constraint(
        axes_tree["embed"], vocab, mesh)
    view_here = None if hoist_view else param_view
    tower_kwargs = {}
    if cfg.tower_layers:
        # the tower's blocks run under the first decoder block's plan: its
        # remat flag (plan_remat_flags), and the attention core that plan
        # gives a block that attends (the flash kernels on a TPU)
        tower_kwargs = dict(
            tower_remat_flags=flags["tower"],
            tower_ops=attention_overrides(
                per_layer[:1], mesh, use_flash=use_flash,
                flash_interpret=kernel_interpret).get(0))

    def loss_fn(p, batch):
        if view_here is not None:
            p = view_here(p)
        p = {**p, "embed": constrain_embed(p["embed"])}
        return causal_lm_loss(
            p, batch, cfg, compute_dtype=compute_dtype,
            remat_flags=remat if any(remat) else None,
            layer_overrides=layer_overrides, boundary_fn=boundary,
            fused_ce=fused_ce, with_moe_stats=with_moe_stats, **enc_kwargs,
            **tower_kwargs)

    return (loss_fn, pspecs, batch_shd, per_layer, vocab, enc_per,
            param_view if hoist_view else None)


def make_spmd_eval_step(
    cfg: ModelArgs,
    hpc: HybridParallelConfig,
    mesh: Mesh,
    axes_tree: Params,
    *,
    compute_dtype=jnp.bfloat16,
    layer_overrides: Optional[Dict[int, LayerOps]] = None,
    tp_overlap: bool = False,
):
    """Jitted held-out loss under the SAME plan shardings as training
    (reference evaluate(), training.py side of dataloader.py:462): no
    optimizer, no dropout (eval semantics are the loss_fn default when the
    batch carries no 'dropout_rng'). Returns (eval_fn(params, batch) ->
    loss, batch_shd)."""
    if hpc.pp_deg != 1:
        raise ValueError("make_spmd_eval_step is the pp=1 path; use "
                         "PipelineEngine.eval_step for pp>1")
    loss_fn, pspecs, batch_shd, _, _, _, _ = build_spmd_loss_fn(
        cfg, hpc, mesh, axes_tree, compute_dtype=compute_dtype,
        layer_overrides=layer_overrides, tp_overlap=tp_overlap)
    nshd = jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    return jax.jit(loss_fn, in_shardings=(nshd, batch_shd)), batch_shd


def _plan_report(plan: Dict[str, List[bool]]) -> Dict[str, Any]:
    """What :class:`KeptStep` reports where every block whose plan bit is
    set is recomputed."""
    return {"blocks_kept": {stack: 0 for stack in plan},
            "blocks_recomputed": {stack: sum(f) for stack, f in plan.items()},
            "kept_bytes": 0, "budget_bytes": 0, "fallback": 0,
            "count_s": 0.0, "check_s": 0.0}


class KeptStep:
    """The train step of a plan whose blocks may be recomputed, on devices
    that say how much they hold: built at its first call (or ``lower``),
    when the state and the batch it is handed say what the step's arguments
    take. Then the loss is traced once with a :class:`kept.Probe` for each
    block whose bit is set (two traces a kind of block, the rest stubs),
    the step's static bytes are estimated with every flag as the plan gave
    it, and as many blocks as ``kept.FILL`` of the devices' ``bytes_limit``
    leaves room for hold their values (:func:`kept.choose`); a block whose
    bit is clear is never touched. ``report`` says what was chosen.

    A job that ran before still runs: the chosen step is compiled before
    its first call, and where the compiler answers ``RESOURCE_EXHAUSTED``,
    or XLA's own count of the executable passes ``kept.FILL`` of the limit,
    the step is built again with the plan's flags, one warning names the
    byte counts and ``report["fallback"]`` reads 1. Every run of a job
    counts, chooses and looks afresh: the choice is a function of the job
    and the devices' limit alone, never of what an earlier run left."""

    # where each stack's blocks keep their parameters
    _params_of = {"decoder": lambda p, i: p["layers"][i],
                  "tower": lambda p, i: p["tower"]["blocks"][i],
                  "encoder": lambda p, i: p["enc_layers"][i]}

    def __init__(self, make_step, count, plan, groups, shards, *, limit,
                 accumulates):
        self._make, self._count = make_step, count
        self._plan, self._groups, self._shards = plan, groups, shards
        self._limit, self._accumulates = limit, accumulates
        self._step = None
        self._settled = False
        self.report: Dict[str, Any] = {}

    def _choose(self, params, opt_state, batch) -> Dict[str, List[bool]]:
        t0 = time.perf_counter()
        plan = self._plan
        f32 = lambda tree: kept.device_bytes(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, jnp.float32, sharding=getattr(a, "sharding", None)),
            tree))
        args, grads = kept.device_bytes((params, opt_state, batch)), f32(params)
        accumulator = 2 * grads if self._accumulates else 0
        room = int(self._limit * kept.FILL)
        self.report = {**_plan_report(plan), "limit_bytes": self._limit,
                       "estimate_bytes": args + accumulator + grads}
        if args + accumulator + grads >= room:
            # the arguments and the gradients alone leave nothing: no trace
            return plan
        cache: Dict[Any, Any] = {}
        probes = {stack: [kept.Probe(g, n, cache) if bit else False
                          for bit, g, n in zip(plan[stack],
                                               self._groups[stack],
                                               self._shards[stack])]
                  for stack in plan}
        jaxpr, n_out, n_params = self._count(probes, params, opt_state, batch)
        # in the order the forward runs them: of two blocks of equal worth
        # the later one's values are held the shorter
        order = [(stack, i) for stack in ("tower", "encoder", "decoder")
                 for i, pr in enumerate(probes[stack]) if pr and pr.counts]
        counts = [probes[stack][i].counts for stack, i in order]
        outer = kept.residual_bytes(jaxpr, n_out, n_params) // max(
            self._shards["decoder"][0], 1)
        made, stored, carries = kept.made_bytes(jaxpr, n_params)
        estimate = kept.plan_peak(args, accumulator, grads, [
            kept.BlockTerms(
                sum(c.input_bytes for c in cs),
                f32(self._params_of[stack](params, i)),
                max(c.whole_bytes for c in cs), cs[0].grad_share,
                any(c.carries for c in cs))
            for (stack, i), cs in zip(order, counts)], outer,
            (made / stored if stored else 1.0, carries))
        budget = max(room - estimate, 0)
        blocks = [(sum(c.held_bytes for c in cs),
                   sum(c.forward_flops for c in cs)) for cs in counts]
        keep = kept.choose(blocks, budget)
        flags = {stack: list(f) for stack, f in plan.items()}
        for (stack, i), k in zip(order, keep):
            flags[stack][i] = not k
        self.report.update(
            blocks_kept={k: sum(plan[k]) - sum(flags[k]) for k in plan},
            blocks_recomputed={k: sum(flags[k]) for k in plan},
            kept_bytes=sum(b[0] for b, k in zip(blocks, keep) if k),
            budget_bytes=budget, estimate_bytes=estimate,
            count_s=time.perf_counter() - t0,
            counted={"args": args, "grads": grads, "outer": outer,
                     "blocks": [(stack, i, b[0], cs[0].input_bytes,
                                 cs[0].whole_bytes)
                                for (stack, i), b, cs
                                in zip(order, blocks, counts)]})
        return flags

    def _built(self, params, opt_state, batch):
        if self._step is None:
            self._flags = self._choose(params, opt_state, batch)
            self._step = self._make(
                None if self._flags == self._plan else self._flags)
        return self._step

    def _checked(self, params, opt_state, batch):
        """The chosen step, compiled and held to ``kept.FILL`` of the
        devices' memory by XLA's own count (the call that follows finds the
        executable cached); the plan's step where it does not compile for
        memory or compiles to more."""
        step = self._built(params, opt_state, batch)
        if (self._settled or self._flags == self._plan
                # (under a trace there is nothing to compile)
                or any(isinstance(a, jax.core.Tracer)
                       for a in jax.tree.leaves((params, opt_state)))):
            return step
        from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
            compiled_memory_bytes,
        )

        r, t0 = self.report, time.perf_counter()
        try:
            peak = compiled_memory_bytes(step.lower(
                params, opt_state, batch).compile()).get("live_peak", 0)
            r["check_s"] = time.perf_counter() - t0
            if peak <= self._limit * kept.FILL:
                return step
            why = f"compiled to {peak} bytes"
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            why = "did not compile for memory"

        logging.getLogger(__name__).warning(
            "the step with %d blocks kept whole (%d bytes counted beside an "
            "estimate of %d, of the devices' %d) %s; building it with the "
            "plan's flags", sum(r["blocks_kept"].values()), r["kept_bytes"],
            r["estimate_bytes"], r["limit_bytes"], why)
        r.update(blocks_recomputed={
            k: r["blocks_recomputed"][k] + r["blocks_kept"][k]
            for k in self._plan}, blocks_kept={k: 0 for k in self._plan},
            kept_bytes=0, fallback=1)
        self._flags = self._plan
        self._step = self._make(None)
        return self._step

    def __call__(self, params, opt_state, batch):
        out = self._checked(params, opt_state, batch)(
            params, opt_state, batch)
        self._settled = True
        return out

    def lower(self, params, opt_state, batch):
        return self._built(params, opt_state, batch).lower(
            params, opt_state, batch)


def kept_report(step, cfg: ModelArgs, hpc: HybridParallelConfig
                ) -> Dict[str, Any]:
    """What a step of :func:`make_spmd_train_step` chose
    (:attr:`KeptStep.report`, once it ran), or, for the plain jitted step
    of a device that reports no limit, that every block whose plan bit is
    set is recomputed."""
    n_enc = hpc.num_encoder_layers
    return getattr(step, "report", None) or _plan_report(plan_remat_flags(
        cfg, hpc.layers[n_enc:], hpc.layers[:n_enc]))


def make_spmd_train_step(
    cfg: ModelArgs,
    hpc: HybridParallelConfig,
    mesh: Mesh,
    axes_tree: Params,
    tx: optax.GradientTransformation,
    params: Params,
    *,
    compute_dtype=jnp.bfloat16,
    layer_overrides: Optional[Dict[int, LayerOps]] = None,
    donate: bool = True,
    chunks: Optional[int] = None,
    tp_overlap: bool = False,
    kernel_interpret: bool = False,
    keep_blocks: bool = True,
):
    """Build the jitted hybrid-parallel train step (no pipeline; pp=1).

    Returns (train_step, pspecs, opt_specs, batch_shd). The caller places
    params/opt_state with :func:`shard_params` and feeds batches laid out by
    ``batch_shd``. The pipeline engine (pp>1) wraps this per-stage.
    ``chunks`` overrides the plan's microbatch count (batch-size ramp:
    the launcher rebuilds the step per chunk count at a fixed micro size).
    ``tp_overlap`` runs eligible TP layers' projections as decomposed
    ring-collective matmuls (ops/overlap.py). Gradients are reduced over
    dp by XLA's partitioner, once a microbatch.
    ``kernel_interpret`` (CPU tests) runs the Pallas kernels in interpret
    mode. On devices that state how much they hold, a block whose plan bit
    is set is rematerialized only where its values do not fit
    (:class:`KeptStep`, built at the step's first call); ``keep_blocks=
    False`` is for the caller that measures what the plan's bit costs (the
    model profiler's memory tables): every such block is rematerialized.
    """
    if hpc.pp_deg != 1:
        raise ValueError("make_spmd_train_step is the pp=1 path; use the "
                         "pipeline engine for pp>1")
    from hetu_galvatron_tpu.models.modules import writes_counts

    # an expert layer's routing counts, or a mixer kind's own
    moe_stats = bool(cfg.num_experts) or (
        cfg.model_type != "t5" and writes_counts(cfg))

    def lowered(flags):
        return build_spmd_loss_fn(
            cfg, hpc, mesh, axes_tree, compute_dtype=compute_dtype,
            layer_overrides=layer_overrides, with_moe_stats=moe_stats,
            tp_overlap=tp_overlap, kernel_interpret=kernel_interpret,
            hoist_view=True, remat_flags=flags)

    loss_fn, pspecs, batch_shd, per_layer, vocab, enc_per, param_view = (
        lowered(None))
    opt_pspecs = param_specs(axes_tree, per_layer, vocab, opt=True,
                             enc_per_layer=enc_per or None)
    opt_specs = opt_state_specs(tx, params, opt_pspecs)
    chunks = max(chunks if chunks is not None else hpc.chunks, 1)
    constrain_mbs = None
    if chunks > 1:
        # microbatch pin (ROADMAP embed-ZeRO-3 BUG, fixed): the
        # [B] -> [chunks, B/chunks] reshape naturally absorbs the OUTER dp
        # mesh axis into the chunk dim, so every scanned microbatch arrives
        # batch-sharded over only the inner dp axes — a layout whose
        # ZeRO-3 gradient program the partitioner gets numerically WRONG
        # (wte rows at grad magnitude under vtp>1; every dp-sharded leaf
        # drifts). Pin the chunk axis replicated and the sample axis to
        # the plan's own batch sharding: each microbatch's embed-grad
        # reduce-scatter then materializes per microbatch in the correct
        # layout.
        mb_spec = NamedSharding(mesh, P(None, *per_layer[0].batch_spec()))

        def constrain_mbs(mbs):
            return jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(x, mb_spec), mbs)

    nshd = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    use_dropout = cfg.hidden_dropout > 0.0 or cfg.attention_dropout > 0.0

    def jitted_step(loss_fn):
        step = make_train_step(
            loss_fn, tx, chunks=chunks, aux_stats=moe_stats,
            constrain_microbatches=constrain_mbs, param_view=param_view)
        if not use_dropout:
            return jax.jit(
                step,
                in_shardings=(nshd(pspecs), nshd(opt_specs), batch_shd),
                out_shardings=(nshd(pspecs), nshd(opt_specs), None),
                donate_argnums=(0, 1) if donate else (),
            )
        # the rng key can't ride inside the batch at the jit boundary: the
        # batch in-sharding is ONE NamedSharding broadcast over every leaf,
        # and a scalar key has no batch axes. Jit a 4-arg step (key
        # replicated) and keep the public 3-arg contract with a wrapper that
        # pops the "dropout_rng" the trainer put in the batch dict.
        jitted = jax.jit(
            lambda p, o, b, rng: step(p, o, {**b, "dropout_rng": rng}),
            in_shardings=(nshd(pspecs), nshd(opt_specs), batch_shd,
                          NamedSharding(mesh, P())),
            out_shardings=(nshd(pspecs), nshd(opt_specs), None),
            donate_argnums=(0, 1) if donate else (),
        )

        def train_step(params, opt_state, batch):
            batch = dict(batch)
            rng = batch.pop("dropout_rng", None)
            if rng is None:
                raise ValueError(
                    "cfg enables dropout but the batch has no 'dropout_rng' "
                    "key; cli/train_dist.py adds it automatically — manual "
                    "callers must pass one per step")
            return jitted(params, opt_state, batch, rng)

        def lower(params, opt_state, batch):
            batch = dict(batch)
            rng = batch.pop("dropout_rng")
            return jitted.lower(params, opt_state, batch, rng)

        train_step.lower = lower  # same inspection surface as a bare jit
        return train_step

    plan = plan_remat_flags(cfg, per_layer, enc_per)
    limit = keep_blocks and kept.bytes_limit(list(mesh.devices.flat))
    if not limit or not any(map(any, plan.values())):
        # nothing to spend (the CPU reports no limit) or nothing to choose:
        # the flags are the plan's, the step the one it always was
        return jitted_step(loss_fn), pspecs, opt_specs, batch_shd

    def count(flags, p, o, batch):
        """The loss's trace with ``flags`` (probes) for one microbatch of
        ``batch``: its jaxpr, how many outputs are the primal's, how many
        inputs the parameters."""
        mb = {k: (v if k == "dropout_rng" else jax.ShapeDtypeStruct(
            (v.shape[0] // chunks,) + v.shape[1:], v.dtype))
            for k, v in batch.items()}
        probe_loss, *_, view = lowered(flags)

        def outer(p, mb):
            q = p if view is None else view(p)
            if moe_stats:
                out, pull, stats = jax.vjp(
                    lambda q: probe_loss(q, mb), q, has_aux=True)
                return (out, stats), pull
            return jax.vjp(lambda q: probe_loss(q, mb), q)

        closed, shape = jax.make_jaxpr(outer, return_shape=True)(p, mb)
        return (closed.jaxpr, len(jax.tree.leaves(shape[0])),
                len(jax.tree.leaves(p)))

    def shards(sh):
        return axes_size(mesh, sh.dp_axes + sh.cp_axes)

    kinds, shares = cfg.block_kinds(len(per_layer)), None
    if cfg.model_type != "t5":
        shares = cfg.block_shares(len(per_layer))
    # blocks the same trace serves: of one kind, one plan, one width
    groups = {
        "decoder": [(kinds[i], cfg.block_heads(i), shares and shares[i], sh)
                    for i, sh in enumerate(per_layer)],
        "tower": [("tower", per_layer[0])] * cfg.tower_layers,
        "encoder": [("encoder", sh) for sh in enc_per]}
    return (KeptStep(
        lambda flags: jitted_step(lowered(flags)[0]), count, plan, groups,
        {stack: [shards(g[-1]) for g in gs] for stack, gs in groups.items()},
        limit=limit, accumulates=chunks > 1), pspecs, opt_specs, batch_shd)


def make_spmd_generate(
    cfg: ModelArgs,
    hpc: HybridParallelConfig,
    mesh: Mesh,
    axes_tree: Params,
    max_new_tokens: int,
    **gen_kwargs,
):
    """Distributed autoregressive generation (pp=1): jit models/generate.py's
    fully-jittable generate() under the plan's GSPMD shardings and let
    propagation shard the KV cache off the (tp-sharded) k/v projections —
    batch rides the dp axes, kv heads the tp axes, with zero changes to the
    decode loop. The reference ships only inference-context stubs
    (transformer/attention.py inference params); this is a working
    tensor/data-parallel decode path.

    Returns (generate_fn(params, tokens, key) -> tokens, pspecs, batch_shd).
    Params must be placed with :func:`shard_params` first.
    """
    from hetu_galvatron_tpu.models.generate import generate, generate_encdec

    if hpc.pp_deg != 1:
        raise ValueError("make_spmd_generate is the pp=1 path")
    _, per_layer, vocab, pspecs = _lower_specs(hpc, mesh, axes_tree)
    # tokens: batch over the first layer's dp axes only (sequence stays
    # local — the decode step is one position wide)
    tok_spec = P(per_layer[0].batch_spec()[0])
    batch_shd = NamedSharding(mesh, tok_spec)
    nshd = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))

    if cfg.model_type == "t5":
        # seq2seq: tokens are the ENCODER source; the decoder stream and
        # both caches take their shardings from propagation exactly like
        # the causal path (cross k/v shard off the tp-sharded wkv)
        decode = lambda p, tokens, key: generate_encdec(
            p, tokens, cfg, max_new_tokens, key=key, **gen_kwargs)
    else:
        decode = lambda p, tokens, key: generate(
            p, tokens, cfg, max_new_tokens, key=key, **gen_kwargs)
    fn = jax.jit(
        decode,
        in_shardings=(nshd(pspecs), batch_shd, NamedSharding(mesh, P())),
        out_shardings=batch_shd,
    )
    return fn, pspecs, batch_shd
