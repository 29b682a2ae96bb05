"""Hierarchical dp/sdp gradient reduction: explicit two-level collectives.

Why: under GSPMD the dp gradient all-reduce is invisible — the partitioner
inserts ONE flat ring over the whole dp group at partition time, every
microbatch, with no way to steer the algorithm or the topology level
("Demystifying NCCL" / "Revisiting the Time Cost Model of AllReduce",
PAPERS.md: flat rings price the slowest link into every hop). On a
multi-slice mesh the dp group spans both the ICI domain and the DCN
seam (``runtime/mesh.py::dcn_factor_shape`` puts pp + outer dp on DCN),
so the right schedule is hierarchical: reduce-scatter INTRA-host at full
volume over the fast links, all-reduce ACROSS slices on the 1/k shard
(the only traffic that touches DCN), and all-gather the result back
intra-host. This module makes that schedule an EXPLICIT part of the
program so the static census can count it, the flow pass can weigh it,
and the cost model can price it per level.

Mechanics (two halves):

* **Per-lane gradients** — the flat path's partial sums exist only inside
  the partitioner, so the cross-dp sum is made explicit by computing
  per-dp-lane gradients: the batch's leading dim reshapes to
  ``[lanes, B/lanes, ...]`` with the lane axis sharded over the plan's dp
  mesh axes, and ``jax.vmap(grad_fn, in_axes=(None, 0))`` produces
  lane-stacked grads with ZERO cross-dp communication (each lane's
  devices already hold its samples; the per-device contraction is
  identical to the flat path's local work — only the cross-lane
  summation ORDER changes, a reduction reassociation within float
  tolerance). Gradient accumulation across microbatches stays lane-local,
  so a ``chunks``-microbatch step pays the dp reduction ONCE instead of
  the flat path's once-per-microbatch in-scan all-reduce.
* **The reduction** — ONE full-manual ``shard_map`` over
  :func:`~hetu_galvatron_tpu.runtime.mesh.hier_submesh` (the global mesh
  with the dp axes regrouped into the canonical
  :data:`~hetu_galvatron_tpu.runtime.mesh.HIER_SLICE_AXIS` /
  :data:`~hetu_galvatron_tpu.runtime.mesh.HIER_HOST_AXIS` sub-axes).
  Every grad leaf flattens and concatenates into ONE per-device payload
  vector (zero-padded to the intra-host degree), so the whole tree costs
  exactly three collective eqns per step — ``psum_scatter`` over the host
  axis at full volume, ``psum`` over the slice axis on the 1/intra shard,
  ``all_gather`` back — each under its ``jax.named_scope`` marker
  (:data:`HIER_DP_RS_SCOPE` etc.) so trace attribution and the census can
  bill them. ``telemetry.plan_collective_counts/bytes`` predict these
  counts and padded payload bytes EXACTLY from the same spec arithmetic
  (:func:`hier_payload_elems` / :func:`hier_bucket_layout`).

**Bucketed software pipelining** (``parallel.hier_bucket_mb > 0``): the
concatenated payload splits into fixed-capacity buckets and the
three-stage schedule is emitted in WAVEFRONT order across them — while
bucket *i* runs its cross-slice all-reduce on the DCN links, bucket
*i+1* runs its reduce-scatter and bucket *i−1* its all-gather on ICI.
The per-bucket chains are data-independent and the two link classes are
disjoint, so XLA's latency-hiding scheduler can overlap them: steady
state approaches ``max(Σ T_ici, T_dcn) + ramp`` instead of the
monolithic ``T_rs + T_ar + T_ag``. Each element still rides exactly the
same rs→ar→ag association as the monolithic path (a bucket is a
contiguous slice of the same payload), so results are bit-identical;
the program contains ``3 × buckets`` collectives, each under a
per-bucket-stage scope (``hier_dp_rs_b0`` …) that keeps trace
attribution, the census exemptions, and the plan-audit rows honest.
``hier_bucket_mb = 0`` (the default) is byte-for-byte today's single
bucket. :func:`hier_bucket_layout` is the ONE source for the per-bucket
(elems, padded) arithmetic — the runtime slicing and the census/flow
predictions both call it, so they cannot drift.

Eligibility lives in ``analysis/eligibility.py``
(``hier_dp_unsupported_reason``): uniform plans — cp/Ulysses layers ARE
eligible (the lane vmap covers the dp axes; each lane's leftover
cp/sequence-parallel partial sums stay an in-lane GSPMD reduction, and
the runtime swaps their shard_map attention kernels for the GSPMD core),
but not zigzag-cp (its pre-permuted data layout needs the ring kernel),
no dropout (lane mask streams would diverge from the flat path's), no
shard_map kernels under the lane vmap (tp_overlap rings / flash cannot
nest — and the pp engines keep their stage-stacked cp/ulysses kernels,
so pp>1 cp/sp plans stay flat), and the vocab tp axes must stay off the
dp lane axes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_galvatron_tpu.ops.pallas.common import on_shards
from hetu_galvatron_tpu.runtime.mesh import (
    HIER_HOST_AXIS,
    HIER_SLICE_AXIS,
    LayerSharding,
    axes_size,
    hier_submesh,
)

# HLO-metadata markers (jax.named_scope) for the three hierarchical
# collectives — trace attribution (observability/trace_analysis.py) bills
# them to the dp component, and the sharding-flow reshard lint exempts the
# deliberate hier_dp_ag re-materialization. Bucketed schedules suffix a
# per-bucket stage id (hier_stage_scope: "hier_dp_rs_b3"); every consumer
# matches by SUBSTRING of the base scope, so the suffix only ADDS detail.
HIER_DP_RS_SCOPE = "hier_dp_rs"
HIER_DP_AR_SCOPE = "hier_dp_ar"
HIER_DP_AG_SCOPE = "hier_dp_ag"
HIER_DP_SCOPES = (HIER_DP_RS_SCOPE, HIER_DP_AR_SCOPE, HIER_DP_AG_SCOPE)

MB = 1024 * 1024


def hier_stage_scope(base: str, bucket: int, n_buckets: int) -> str:
    """named_scope for one bucket's stage: the bare base scope for the
    monolithic (single-bucket) schedule — byte-compatible with pre-bucket
    traces — else ``{base}_b{i}``. The base stays a prefix, so substring
    consumers (trace attribution ``_HIER_MARKERS``, the flow pass's
    ``hier_dp_ag`` gather exemption) see bucketed programs unchanged."""
    return base if n_buckets <= 1 else f"{base}_b{bucket}"


def hier_bucket_layout(local: int, intra: int,
                       bucket_mb: float) -> List[Tuple[int, int]]:
    """Per-bucket ``(elems, padded)`` split of the ``local`` per-device
    payload elements: contiguous f32 slices of at most ``bucket_mb``
    megabytes (rounded up to the intra-host degree so every full bucket
    scatters evenly), each independently zero-padded to a multiple of
    ``intra``. ``bucket_mb <= 0`` returns the single monolithic bucket —
    identical to :func:`hier_payload_elems`'s (local, padded) pair.

    This is THE bucket arithmetic: the runtime reducer slices its payload
    with it and ``telemetry.plan_collective_counts/bytes`` predict
    ``3 x len(layout)`` collectives with exactly these padded sizes —
    one function, two callers, no drift."""
    intra = max(intra, 1)
    pad = lambda n: -(-n // intra) * intra
    local = max(int(local), 0)
    if bucket_mb <= 0 or local == 0:
        return [(local, pad(local))]
    # capacity: bucket_mb of f32 elems, floored to a multiple of intra
    # (full buckets then scatter with zero padding), at least one tile
    cap = max((int(bucket_mb * MB) // 4) // intra * intra, intra)
    out: List[Tuple[int, int]] = []
    off = 0
    while off < local:
        n = min(cap, local - off)
        out.append((n, pad(n)))
        off += n
    return out


def _is_axes(x: Any) -> bool:
    return isinstance(x, tuple) and all(isinstance(s, str) for s in x)


def grad_reduce_specs(axes_tree: Any, per_layer: List[LayerSharding],
                      vocab: LayerSharding) -> Any:
    """PartitionSpec tree for the LANE-STACKED gradients' non-lane dims:
    the params' specs with ZeRO-3 dp-sharding overridden OFF (the lane
    axis owns the dp mesh axes; a leaf spec may not mention them twice).
    Mirrors ``parallel.spmd.param_specs``' row assignment — decoder layers
    use their own sharding, embed/prenorm/head the vocab sharding."""
    sp = lambda sh: (lambda la: sh.param_spec(la, zero3_override=False))
    tree = lambda axes, sh: jax.tree.map(sp(sh), axes, is_leaf=_is_axes)
    out = {
        "embed": tree(axes_tree["embed"], vocab),
        "layers": tuple(tree(a, sh)
                        for a, sh in zip(axes_tree["layers"], per_layer)),
        "prenorm": tree(axes_tree["prenorm"], vocab),
        "head": tree(axes_tree["head"], vocab),
    }
    if "enc_layers" in axes_tree:
        out["enc_layers"] = tuple(
            tree(a, per_layer[0]) for a in axes_tree["enc_layers"])
        out["enc_norm"] = tree(axes_tree["enc_norm"], vocab)
    return out


def hier_payload_elems(shapes: Sequence[Tuple[int, ...]],
                       specs: Sequence[P], mesh: Any,
                       intra: int) -> Tuple[int, int]:
    """(local, padded) per-device element counts of the concatenated
    reduction payload: each leaf contributes its GLOBAL size divided by
    the product of the mesh axes its spec shards it over, and the concat
    zero-pads up to the intra-host degree for the tiled scatter. This is
    the arithmetic ``plan_collective_bytes`` uses to predict the traced
    payload EXACTLY — one function, two callers, no drift. ``mesh`` only
    needs axis SIZES (``.shape``), so a shape-only stand-in works on a
    host with no devices (telemetry's plan prediction)."""
    local = 0
    for shape, spec in zip(shapes, specs):
        n = 1
        for d in shape:
            n *= int(d)
        div = 1
        for entry in spec:
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            div *= axes_size(mesh, names)
        local += n // div
    padded = -(-local // max(intra, 1)) * max(intra, 1)
    return local, padded


def _check_specs_off_lane_axes(specs: List[P],
                               dp_axes: Tuple[str, ...]) -> None:
    banned = set(dp_axes)
    for spec in specs:
        for entry in tuple(spec):
            names = (entry if isinstance(entry, tuple)
                     else (entry,) if entry else ())
            if banned & set(names):
                raise ValueError(
                    f"grad leaf spec {spec} shards a non-lane dim over the "
                    f"dp lane axes {dp_axes}; build the grad specs with "
                    "zero3_override=False (grad_reduce_specs)")


@dataclass
class HierDpReducer:
    """One plan's hierarchical dp gradient reducer, bound to a mesh.

    ``lanes`` is the plan's dp degree (the lane-vmap width);
    ``cross``/``intra`` the slice/host split of it. :meth:`reduce` takes a
    lane-stacked grad tree (leading ``[lanes]`` dim sharded over the dp
    axes, every other dim laid out per ``specs``) and returns the summed
    tree with the lane dim gone — three explicit collectives per bucket
    (one bucket at ``bucket_mb = 0``), software-pipelined across buckets.
    """

    mesh: Mesh
    dp_axes: Tuple[str, ...]
    cross: int
    intra: int
    # PartitionSpec tree matching the (unstacked) grad leaves; leaves that
    # carry extra stacked dims (the compiled engine's leading "pp") include
    # them in their own spec — the lane dim is prepended here
    specs: Any
    # the flat batch's [B, ...] spec (per_layer[0].batch_spec()); the lane
    # split re-pins dims past the lane one to it
    batch_spec: Optional[P] = None
    # bucketed software pipelining (module docstring): the payload splits
    # into ≤bucket_mb-MB buckets whose rs/ar/ag chains interleave so the
    # DCN stage of bucket i overlaps the ICI stages of its neighbours.
    # 0 = one monolithic bucket (byte-identical to the pre-bucket program)
    bucket_mb: float = 0.0
    # collective-compiler backend (collectives/): a schedule family name
    # ("ring" | "tree_hd" | "tree_bcast" | "torus2d" | "hier_rings")
    # synthesized for the dp group, statically verified, and emitted as
    # the reduction program in place of the hand-implemented
    # psum_scatter/psum/all_gather — or a hand-built reference body
    # ("ring_handbuilt" | "tree_handbuilt", collectives/reference.py)
    # for the bit-parity drills. None = the hand-implemented schedule.
    schedule: Optional[str] = None

    def __post_init__(self):
        self.lanes = axes_size(self.mesh, self.dp_axes)
        if self.lanes != self.cross * self.intra:
            raise ValueError(
                f"cross {self.cross} x intra {self.intra} != dp degree "
                f"{self.lanes}")
        self.hmesh = hier_submesh(self.mesh, self.dp_axes, self.cross)
        self._sched_body = None
        self._sched = None
        if self.schedule:
            from hetu_galvatron_tpu.analysis.eligibility import (
                dp_schedule_unsupported_reason,
            )

            reason = dp_schedule_unsupported_reason(
                self.schedule, self.lanes, self.cross, self.bucket_mb)
            if reason:
                raise ValueError(f"dp schedule unsupported: {reason}")
            axis = (HIER_SLICE_AXIS, HIER_HOST_AXIS)
            if self.schedule.endswith("_handbuilt"):
                from hetu_galvatron_tpu.collectives.reference import (
                    handbuilt_allreduce_body,
                )

                alg = self.schedule.split("_")[0]
                inner = handbuilt_allreduce_body(alg, self.lanes, axis)
                scope = f"dp_sched_handbuilt_{alg}"

                def body(v, _inner=inner, _scope=scope):
                    with jax.named_scope(_scope):
                        return _inner(v)

                self._sched_body = body
                self._sched_chunks = self.lanes
            else:
                from hetu_galvatron_tpu.collectives.emit import (
                    emit_allreduce_body,
                )
                from hetu_galvatron_tpu.collectives.synthesize import (
                    synthesize_dp_schedule,
                )
                from hetu_galvatron_tpu.collectives.verify import verify

                self._sched = verify(synthesize_dp_schedule(
                    self.schedule, self.lanes, self.cross))
                self._sched_body = emit_allreduce_body(
                    self._sched, axis, verify_first=False)
                self._sched_chunks = self._sched.n_chunks
        leaves, self._treedef = jax.tree_util.tree_flatten(
            self.specs, is_leaf=lambda x: isinstance(x, P))
        _check_specs_off_lane_axes(leaves, self.dp_axes)
        self._in_specs = tuple(
            P((HIER_SLICE_AXIS, HIER_HOST_AXIS), *s) for s in leaves)
        self._out_specs = tuple(leaves)
        self._leaf_specs = leaves
        self._lane_dim = tuple(self.dp_axes)
        self._fn = on_shards(self._body, self.hmesh, self._in_specs,
                             self._out_specs)

    # -- lane helpers -------------------------------------------------------

    def lane_batch(self, batch: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """Reshape a batch tree's leading [B, ...] dim to [lanes, B/lanes,
        ...] with the lane axis pinned to the dp mesh axes (the flat
        batch's own dp sharding — the reshape moves no data)."""
        L = self.lanes
        batch_spec = (self.batch_spec if self.batch_spec is not None
                      else P(self._lane_dim))

        def split(x):
            if x.shape[0] % L:
                raise ValueError(
                    f"batch dim {x.shape[0]} not divisible by the dp lane "
                    f"count {L}")
            y = x.reshape((L, x.shape[0] // L) + x.shape[1:])
            rest = tuple(batch_spec)[1:]
            return jax.lax.with_sharding_constraint(
                y, NamedSharding(self.mesh,
                                 P(self._lane_dim, None, *rest)))

        return jax.tree.map(split, batch)

    def constrain_stacked(self, grads: Any) -> Any:
        """Pin a lane-stacked grad tree's layout (lane over dp axes, the
        rest per the leaf specs) — used on the scan carry so the
        accumulator never silently re-shards."""
        specs = jax.tree_util.tree_unflatten(
            self._treedef,
            [P(self._lane_dim, *s) for s in self._leaf_specs])
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(self.mesh, s)),
            grads, specs, is_leaf=lambda x: isinstance(x, P))

    # -- the reduction ------------------------------------------------------

    @staticmethod
    def _bucket_segments(sizes: Sequence[int],
                         layout: Sequence[Tuple[int, int]]
                         ) -> List[List[Tuple[int, int, int]]]:
        """Per-bucket ``(leaf index, lo, hi)`` segment lists covering the
        flattened leaves in order — the bucket boundaries fall wherever
        ``hier_bucket_layout`` put them, splitting a leaf mid-way when
        needed. Each element is copied exactly once INTO its bucket and
        once OUT (the same copy volume the monolithic concat/split pays),
        so bucketing adds no extra payload traffic."""
        segs: List[List[Tuple[int, int, int]]] = []
        li, lo = 0, 0
        for n, _padded in layout:
            bucket: List[Tuple[int, int, int]] = []
            need = n
            while need > 0:
                take = min(need, sizes[li] - lo)
                bucket.append((li, lo, lo + take))
                lo += take
                need -= take
                if lo == sizes[li]:
                    li += 1
                    lo = 0
            segs.append(bucket)
        return segs

    def _body(self, *blocks):
        """Local shard_map body: each block arrives ``[1, ...]`` (one lane
        per device along the regrouped dp sub-axes); flatten the leaves
        into per-bucket payload vectors (hier_bucket_layout — ONE bucket
        covering everything at bucket_mb = 0), run each bucket's
        three-level schedule with the stage emissions interleaved in
        wavefront order, and reassemble the leaves from the gathered
        buckets."""
        intra = self.intra
        flats = [b[0].reshape(-1).astype(jnp.float32) for b in blocks]
        sizes = [f.size for f in flats]
        if self._sched_body is not None:
            # collective-compiler path: ONE payload padded to a whole
            # number of schedule chunks, reduced by the emitted (or
            # hand-built reference) all-reduce program
            v = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
            local = v.shape[0]
            C = self._sched_chunks
            padded = -(-local // C) * C
            if padded != local:
                v = jnp.pad(v, (0, padded - local))
            g = self._sched_body(v)[:local]
            outs = []
            off = 0
            for b, n in zip(blocks, sizes):
                outs.append(g[off:off + n].reshape(b.shape[1:])
                            .astype(b.dtype))
                off += n
            return tuple(outs)
        layout = hier_bucket_layout(sum(sizes), intra, self.bucket_mb)
        segs = self._bucket_segments(sizes, layout)
        B = len(layout)
        bufs = []
        for bucket, (n, padded) in zip(segs, layout):
            parts = [flats[li][lo:hi] if (lo, hi) != (0, sizes[li])
                     else flats[li] for li, lo, hi in bucket]
            v = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            if padded != n:
                v = jnp.pad(v, (0, padded - n))
            bufs.append(v)
        # wavefront emission: at step t, bucket t enters rs-intra (ICI)
        # while bucket t-1 runs ar-cross (DCN) and bucket t-2 ag-intra
        # (ICI). The chains share no data, so the emission order is the
        # overlap HINT the latency-hiding scheduler needs — with B = 1
        # this degenerates to exactly the monolithic three-collective
        # program (same scopes, same payload, same bytes).
        rs_out: List[Any] = [None] * B
        ar_out: List[Any] = [None] * B
        ag_out: List[Any] = [None] * B
        for t in range(B + 2):
            if t < B:
                with jax.named_scope(
                        hier_stage_scope(HIER_DP_RS_SCOPE, t, B)):
                    rs_out[t] = jax.lax.psum_scatter(
                        bufs[t], HIER_HOST_AXIS, scatter_dimension=0,
                        tiled=True)
            j = t - 1
            if 0 <= j < B:
                with jax.named_scope(
                        hier_stage_scope(HIER_DP_AR_SCOPE, j, B)):
                    ar_out[j] = jax.lax.psum(rs_out[j], HIER_SLICE_AXIS)
            k = t - 2
            if 0 <= k < B:
                with jax.named_scope(
                        hier_stage_scope(HIER_DP_AG_SCOPE, k, B)):
                    ag_out[k] = jax.lax.all_gather(
                        ar_out[k], HIER_HOST_AXIS, tiled=True)
        # reassemble each leaf from its (in-order) bucket segments
        pieces: List[List[Any]] = [[] for _ in flats]
        for bucket, (n, padded), g in zip(segs, layout, ag_out):
            off = 0
            for li, lo, hi in bucket:
                pieces[li].append(g[off:off + (hi - lo)])
                off += hi - lo
        outs = []
        for b, n, ps in zip(blocks, sizes, pieces):
            leaf = jnp.concatenate(ps) if len(ps) > 1 else ps[0]
            outs.append(leaf.reshape(b.shape[1:]).astype(b.dtype))
        return tuple(outs)

    def reduce(self, stacked: Any) -> Any:
        """Lane-stacked grads ``[lanes, ...]`` -> summed grads (lane dim
        dropped), via the one three-collective program."""
        leaves = jax.tree_util.tree_leaves(stacked)
        if len(leaves) != len(self._leaf_specs):
            raise ValueError(
                f"grad tree has {len(leaves)} leaves, reducer was built "
                f"for {len(self._leaf_specs)}")
        outs = self._fn(*leaves)
        return jax.tree_util.tree_unflatten(self._treedef, list(outs))

    def payload_elems(self, stacked_or_shapes: Any) -> Tuple[int, int]:
        """(local, padded) payload element counts — the traced-byte
        prediction's anchor. Accepts either a LANE-STACKED grad tree
        (leaf lane dims stripped) or a flat list of UNSTACKED global leaf
        shape tuples in spec order."""
        if isinstance(stacked_or_shapes, (list, tuple)) and all(
                isinstance(s, tuple) for s in stacked_or_shapes):
            shapes = [tuple(s) for s in stacked_or_shapes]
        else:
            shapes = [tuple(l.shape[1:]) for l in
                      jax.tree_util.tree_leaves(stacked_or_shapes)]
        return hier_payload_elems(shapes, self._leaf_specs, self.hmesh,
                                  self.intra)

    def bucket_layout(self, stacked_or_shapes: Any) -> List[Tuple[int, int]]:
        """Per-bucket (elems, padded) split of this reducer's payload —
        the exact slices :meth:`reduce` emits (``hier_bucket_layout`` over
        :meth:`payload_elems`'s local count). One entry at
        ``bucket_mb = 0``."""
        local, _ = self.payload_elems(stacked_or_shapes)
        return hier_bucket_layout(local, self.intra, self.bucket_mb)


def make_hier_reducer(
    mesh: Mesh,
    per_layer: List[LayerSharding],
    vocab: LayerSharding,
    axes_tree: Any,
    *,
    dcn_slices: int = 1,
    cross: Optional[int] = None,
    specs: Any = None,
    bucket_mb: float = 0.0,
    schedule: Optional[str] = None,
) -> HierDpReducer:
    """Build the reducer for a lowered plan: dp lane axes from the (uniform)
    first decoder layer, the slice/host split from ``dcn_slices`` (pp-first
    absorption, ``mesh.hier_cross_degree``) unless ``cross`` pins it, grad
    specs from :func:`grad_reduce_specs` unless given, and the bucketed
    pipelining granularity from ``bucket_mb`` (``parallel.hier_bucket_mb``;
    0 = one monolithic bucket)."""
    from hetu_galvatron_tpu.runtime.mesh import hier_cross_degree

    sh = per_layer[0]
    dp_axes = sh.dp_axes
    dp_deg = axes_size(mesh, dp_axes)
    if cross is None:
        cross = hier_cross_degree(mesh.shape.get("pp", 1), dp_deg,
                                  dcn_slices)
    if specs is None:
        specs = grad_reduce_specs(axes_tree, per_layer, vocab)
    return HierDpReducer(mesh=mesh, dp_axes=dp_axes, cross=cross,
                         intra=dp_deg // cross, specs=specs,
                         batch_spec=sh.batch_spec(), bucket_mb=bucket_mb,
                         schedule=schedule)


# NOTE: per-lane grad computation is NOT wrapped here on purpose — every
# caller (trainer / both pipeline engines) must build its own
# ``jax.vmap(grad_fn, in_axes=(None, 0), spmd_axis_name=dp_axes)`` with
# lane-aware (dp-free) interior shardings; a generic helper without the
# axis pinning would silently reintroduce the per-layer lane reshard this
# module's docstring warns about.
