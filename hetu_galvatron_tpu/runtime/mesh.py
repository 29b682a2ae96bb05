"""Device mesh construction + per-layer strategy -> GSPMD sharding lowering.

Capability parity with the reference's comm-group machinery
(runtime/comm_groups.py:266-442 ``gen_comm_groups`` and
runtime/parallel_state.py): where the reference builds NCCL process groups per
layer from the strategy vectors, we lower each :class:`LayerStrategy` to
`PartitionSpec`s over ONE global mesh — XLA materializes the collectives.

TPU-first design — the **binary-factorized mesh**: the per-stage world of
``W = 2^k`` chips becomes ``k`` binary mesh axes ``d0..d{k-1}`` (plus a ``pp``
axis when pp_deg > 1). A layer with (tp=4, dp=2) on W=8 shards its weights
over the two innermost axes ``(d1, d2)`` and its batch over ``d0``; the next
layer with (tp=2, dp=4) uses ``(d2,)`` and ``(d0, d1)``. Because both shardings
live on the same mesh, GSPMD inserts exactly the boundary reshard the
reference implements by hand (split/all-gather "relocation",
runtime/parallel.py:272-304) — heterogeneous per-layer parallelism becomes a
sharding annotation problem instead of a process-group bookkeeping problem.

Axis order follows the reference's rank-coordinate order 'pp-dp-cp-tp'
(comm_groups.py:39-116): tp innermost = adjacent chips = ICI-local, dp
outermost = ready to ride DCN on multi-pod (SURVEY §2.2).

Logical param axes (see models/modules.py init_*) map per layer:
  "qkv"/"mlp"/"heads"  -> the layer's tp axes  (Megatron TP; () under Ulysses)
  "vocab"              -> the vocab layer's vtp axes
  "embed" (2D+ params) -> dp axes under ZeRO-3, else replicated
  anything else        -> replicated
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, PartitionSpec as P

from hetu_galvatron_tpu.utils.strategy import (
    DPType,
    EmbeddingLMHeadStrategy,
    LayerStrategy,
)

# logical param-axis names sharded by tensor parallelism
_TP_LOGICAL = ("qkv", "mlp", "heads", "vocab")

def flash_kernel_runs(use_flash_attn: bool, devices: Sequence[Any]) -> bool:
    """THE attention-kernel rule, shared by the SPMD path, both pipeline
    engines and the launcher's eligibility checks: the Pallas flash kernel
    runs iff the config asks for it (``model.use_flash_attn``) and EVERY
    device the program spans is a TPU — Mosaic compiles for nothing else.
    A device set that mixes platforms has no single answer and raises."""
    platforms = {d.platform for d in devices}
    if len(platforms) != 1:
        raise ValueError(
            f"devices span platforms {sorted(platforms)}; the attention "
            "kernel choice needs exactly one")
    return bool(use_flash_attn) and platforms == {"tpu"}


def attention_core(cp: bool, ulysses: bool, flash: bool) -> str:
    """Name of the attention core one layer runs: ``ring`` (cp > 1) or
    ``ulysses`` (sequence-parallel a2a) around a local core, else the local
    core alone — ``flash`` (Mosaic-compiled Pallas kernel) or ``xla``.
    ``parallel/spmd.attention_overrides`` dispatches on this name and the
    launcher logs it per layer, so the log cannot drift from the dispatch."""
    outer = "ring" if cp else "ulysses" if ulysses else None
    if outer is None:
        return "flash" if flash else "xla"
    return f"{outer}+flash" if flash else outer


def _log2(n: int) -> int:
    k = n.bit_length() - 1
    if n <= 0 or (1 << k) != n:
        raise ValueError(f"{n} is not a positive power of two")
    return k


def dcn_factor_shape(global_shape: Tuple[int, ...], dcn_slices: int
                     ) -> Tuple[int, ...]:
    """Factor ``dcn_slices`` over the LEADING mesh axes (pp first, then the
    outer binary d-axes): pipeline stages and outer-dp replicas cross DCN
    while tp/cp stay on the inner, ICI-local axes — the reference's
    'consecutive ranks on NVLink' locality (comm_groups.py:96-100) lifted to
    the pod level. Returns the per-axis DCN factors; raises when the slices
    cannot divide the leading axes."""
    left = dcn_slices
    out = []
    for dim in global_shape:
        f = math.gcd(left, dim)
        out.append(f)
        left //= f
    if left != 1:
        raise ValueError(
            f"dcn_slices {dcn_slices} does not factor over the leading mesh "
            f"axes {global_shape} (pp * outer-dp must absorb the slices)")
    return tuple(out)


def device_array(
    world_size: int,
    pp_deg: int = 1,
    devices: Optional[Sequence] = None,
    dcn_slices: int = 1,
) -> np.ndarray:
    """Device ndarray of shape ``(pp, 2, ..., 2)`` behind :func:`build_mesh`
    — also used by the pipeline engine to carve DCN-aligned stage groups.

    Order: pp outermost (stage boundaries cross the slowest links), then
    d0..dk with dk fastest-varying (tp-adjacent chips are ICI neighbours,
    the reference's "consecutive" locality, comm_groups.py:96-100).

    ``dcn_slices > 1`` (multi-pod): devices are arranged with
    ``mesh_utils.create_hybrid_device_mesh`` so slice boundaries land on the
    leading axes (pp, then outer d) and every inner axis stays within one
    ICI domain (TPU pods granule by ``slice_index``; multi-process hosts
    without it granule by process). Falls back to the plain enumeration
    order when the devices carry no multi-process topology (tests /
    virtual platforms).
    """
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < world_size:
        raise ValueError(f"need {world_size} devices, have {len(devices)}")
    devices = devices[:world_size]
    if world_size % pp_deg:
        raise ValueError(f"world {world_size} not divisible by pp {pp_deg}")
    # only the per-stage world must be 2^k (it becomes the binary d-axes);
    # pp is a plain leading axis and may be any size (pp=3 on 24 chips is fine)
    stage = world_size // pp_deg
    k = _log2(stage)
    shape = (pp_deg,) + (2,) * k
    if dcn_slices > 1:
        n_proc = len({getattr(d, "process_index", 0) for d in devices})
        if n_proc > 1:
            from jax.experimental import mesh_utils

            dcn_shape = dcn_factor_shape(shape, dcn_slices)
            ici_shape = tuple(g // f for g, f in zip(shape, dcn_shape))
            # TPU pods carry slice_index; other multi-process platforms
            # (multi-host CPU/GPU) granule by process instead
            by_slice = all(hasattr(d, "slice_index") for d in devices)
            return mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=devices,
                process_is_granule=not by_slice)
        # single-process (virtual CPU tests): topology is synthetic anyway;
        # plain enumeration already puts the leading axes outermost
    return np.asarray(devices).reshape(shape)


def build_mesh(
    world_size: int,
    pp_deg: int = 1,
    devices: Optional[Sequence] = None,
    dcn_slices: int = 1,
) -> Mesh:
    """One global mesh: ('pp', 'd0', ..., 'd{k-1}') with binary d-axes over
    the :func:`device_array` arrangement (see there for ordering/DCN)."""
    arr = device_array(world_size, pp_deg, devices, dcn_slices)
    names = ("pp",) + tuple(f"d{i}" for i in range(arr.ndim - 1))
    return Mesh(arr, names)


def stage_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The binary intra-stage axes, outermost first."""
    return tuple(n for n in mesh.axis_names if n != "pp")


def axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    """Product of the named mesh axes' sizes (1 for the empty tuple) — the
    degree a (dp/cp/tp) axis-tuple assignment actually carries. Shared by
    the SPMD lowering and the overlapped-TP dispatch (ops/overlap.py)."""
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def spec_tree(axes: Any, sh: "LayerSharding", opt: bool = False) -> Any:
    """Map a logical-axis pytree (tuples of axis-name strings at the leaves,
    models/modules.py init_*) to PartitionSpecs under one layer's sharding.
    Shared by the SPMD lowering, the host pipeline engine and the compiled
    pipeline engine."""
    fn = sh.opt_spec if opt else sh.param_spec
    return jax.tree.map(
        fn, axes, is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(s, str) for s in x))


def stacked_spec(spec: P) -> P:
    """Spec for a per-stage value stacked along a leading ``[pp, ...]`` axis
    (the compiled pipeline's parameter/activation layout): the stage axis
    rides the mesh's ``pp`` axis, the remaining dims keep their intra-stage
    assignment."""
    return P("pp", *spec)


def make_pp_rotation(mesh: Mesh, spec: P, shift: int):
    """Stage-transfer collective for the compiled pipeline schedule: rotate a
    ``[pp, ...]``-stacked array (sharded :func:`stacked_spec`-style, one
    stage per ``pp`` mesh row) by ``shift`` stages as a `lax.ppermute` over
    the ``pp`` axis — the XLA collective-permute the latency-hiding
    scheduler overlaps with compute, replacing the host engine's
    ``jax.device_put`` submesh transfers. ``spec`` is the FULL stacked spec
    (leading ``pp`` entry included); axes it does not mention are treated as
    replicated (``check_rep=False`` — the rotation is an identity on them).

    ``shift=+1`` sends stage s's slice to stage s+1 (forward activations);
    ``shift=-1`` sends it to stage s-1 (backward cotangents). The wrap-around
    edge carries don't-care data by construction of the 1F1B schedule (lane 0
    embeds fresh tokens; the last lane seeds its cotangent from the loss)."""
    from hetu_galvatron_tpu.ops.pallas.common import on_shards

    pp = mesh.shape["pp"]
    perm = [(i, (i + shift) % pp) for i in range(pp)]

    def body(blk):
        # named_scope lands in the HLO metadata so trace attribution can
        # tell stage-rotation permutes from tp-ring / cp-ring permutes when
        # all three coexist in one compiled program
        # (observability/trace_analysis.py)
        with jax.named_scope("pp_rotate"):
            return jax.lax.ppermute(blk, "pp", perm)

    return on_shards(body, mesh, spec, spec)


@dataclass(frozen=True)
class LayerSharding:
    """A layer's strategy lowered onto the mesh: which binary axes carry
    dp / cp / tp, plus the dp flavour and remat flag.

    Replaces the reference's per-layer group tuple (tp_group, dp_group,
    cp_group, ... from gen_comm_groups) with named-axis assignments.
    """

    dp_axes: Tuple[str, ...]
    cp_axes: Tuple[str, ...]
    tp_axes: Tuple[str, ...]
    ulysses: bool = False  # tp axes carry sequence (a2a attention), not weights
    dp_type: DPType = DPType.DDP
    checkpoint: bool = False
    # MoE: experts over ep axes (carved from dp), expert weights' mlp axis
    # over etp axes (the reference's pp-ep-edp-etp grid, comm_groups.py:322-345)
    ep_axes: Tuple[str, ...] = ()
    etp_axes: Tuple[str, ...] = ()

    @property
    def edp_axes(self) -> Tuple[str, ...]:
        """Expert-dp: the dp axes not consumed by ep."""
        return self.dp_axes[len(self.ep_axes):]

    # -- param / optimizer-state specs ------------------------------------

    def _weight_axes(self) -> Tuple[str, ...]:
        return () if self.ulysses else self.tp_axes

    @property
    def weight_tp_axes(self) -> Tuple[str, ...]:
        """The mesh axes actually sharding this layer's WEIGHTS — () under
        Ulysses, where the tp axes carry sequence instead. The overlapped-TP
        dispatch keys off this (a layer with no weight-tp axes has no
        collective-vs-matmul pair to decompose)."""
        return self._weight_axes()

    def param_spec(self, logical_axes: Tuple[str, ...],
                   zero3_override: Optional[bool] = None) -> P:
        """PartitionSpec for a param with the given logical axis names.
        Expert params (an "expert" axis present) shard their weight dims over
        etp and their ZeRO-3 embed dim over edp instead of tp/dp."""
        zero3 = (self.dp_type == DPType.ZERO3
                 if zero3_override is None else zero3_override)
        shard_embed = zero3 and len(logical_axes) >= 2
        is_expert = "expert" in logical_axes
        weight_axes = self.etp_axes if is_expert else self._weight_axes()
        embed_axes = self.edp_axes if is_expert else self.dp_axes
        dims = []
        for name in logical_axes:
            if name == "expert":
                dims.append(self.ep_axes or None)
            elif name in _TP_LOGICAL:
                dims.append(weight_axes or None)
            elif name == "embed" and shard_embed:
                dims.append(embed_axes or None)
            else:
                dims.append(None)
        return P(*dims)

    def opt_spec(self, logical_axes: Tuple[str, ...]) -> P:
        """Optimizer-moment spec: ZeRO-2 shards moments over dp even when
        params are replicated (reference SHARD_GRAD_OP, parallel.py:121)."""
        zero3 = self.dp_type in (DPType.ZERO2, DPType.ZERO3)
        return self.param_spec(logical_axes, zero3_override=zero3)

    # -- activation specs --------------------------------------------------

    def act_spec(self) -> P:
        """[B, S, H] hidden-state spec at this layer's boundary:
        batch over dp, sequence over cp (ring) or tp (Megatron-SP/Ulysses),
        hidden replicated."""
        seq = self.cp_axes if self.cp_axes else (self.tp_axes or None)
        return P(self.dp_axes or None, seq or None, None)

    def batch_spec(self) -> P:
        """[B, S] token/label spec."""
        seq = self.cp_axes or None
        return P(self.dp_axes or None, seq)



def lower_strategy(s: LayerStrategy, mesh: Mesh) -> LayerSharding:
    """Assign the mesh's binary axes to (dp, cp, tp) for one layer.

    Consecutive tp (the default) takes the innermost axes; non-consecutive
    tp takes the outermost (the reference's strided groups,
    comm_groups.py:119-203).
    """
    axes = stage_axes(mesh)
    stage = 1 << len(axes)
    need = s.tp_size * s.cp_size * s.dp_size
    if need != stage:
        raise ValueError(
            f"strategy tp{s.tp_size}*cp{s.cp_size}*dp{s.dp_size} = {need} "
            f"!= stage world {stage}")
    ktp, kcp = _log2(s.tp_size), _log2(s.cp_size)
    kdp = _log2(s.dp_size)
    if s.tp_consecutive:
        dp_axes = axes[:kdp]
        cp_axes = axes[kdp:kdp + kcp]
        tp_axes = axes[kdp + kcp:]
    else:
        tp_axes = axes[:ktp]
        cp_axes = axes[ktp:ktp + kcp]
        dp_axes = axes[ktp + kcp:]
    kep, ketp = _log2(s.ep_size), _log2(s.etp_size)
    if kep > len(dp_axes):
        raise ValueError(
            f"ep {s.ep_size} exceeds the dp degree {s.dp_size} it is carved "
            "from (reference grid pp-ep-edp-etp)")
    if ketp > len(tp_axes):
        raise ValueError(f"etp {s.etp_size} exceeds tp {s.tp_size}")
    return LayerSharding(
        dp_axes=dp_axes, cp_axes=cp_axes, tp_axes=tp_axes,
        ulysses=s.sp, dp_type=s.dp_type, checkpoint=s.checkpoint,
        ep_axes=dp_axes[:kep],
        etp_axes=tp_axes[len(tp_axes) - ketp:] if ketp else (),
    )


def devices_along(mesh: Mesh, axes: Tuple[str, ...]) -> List[List[int]]:
    """The ids of the devices at each index along ``axes`` (row-major over
    them, as ``jax.lax.axis_index(axes)`` counts inside a ``shard_map``):
    ``[index][...]``. A device trace names its planes by device id, and a
    mesh need not hold its devices in the order of their ids."""
    names = list(mesh.axis_names)
    lead = [names.index(a) for a in axes]
    ids = np.vectorize(lambda d: d.id, otypes=[int])(mesh.devices)
    ids = ids.transpose(lead + [i for i in range(ids.ndim) if i not in lead])
    return ids.reshape(math.prod(ids.shape[:len(lead)]), -1).tolist()


def lower_vocab_strategy(
    v: EmbeddingLMHeadStrategy, mesh: Mesh, default_dp_type: DPType
) -> LayerSharding:
    """Embedding/LM-head sharding from the vocab strategy (reference
    hp_config_whole_model embedding rows, hybrid_parallel_config.py:276-293):
    tp=vtp (or sequence if vsp), cp=vcp, dp the rest; embed_sdp forces
    ZeRO-3."""
    stage = 1 << len(stage_axes(mesh))
    dp = stage // (v.vtp * v.vcp)
    s = LayerStrategy(
        pp_deg=mesh.shape.get("pp", 1),
        tp_size=v.vtp,
        cp_size=v.vcp,
        dp_size=dp,
        sp=v.vsp,
        dp_type=DPType.ZERO3 if v.embed_sdp else default_dp_type,
    )
    return lower_strategy(s, mesh)
