"""Decomposed tensor-parallel collective matmuls: ring all-gather/
reduce-scatter fused with the projection they feed, so the transfer hides
behind dependent compute.

Why: under GSPMD auto-partitioning every Megatron-SP layer runs
<all-gather over sequence> -> <matmul> (column-parallel) and
<matmul> -> <reduce-scatter over sequence> (row-parallel) as two
dependent ops — the collective sits on the critical path. The decomposed
form splits the sequence into one chunk per tp rank and `lax.ppermute`s
chunks around the ring while each rank multiplies the chunk it already
holds; XLA's latency-hiding scheduler overlaps the permute DMA with the
chunk matmul, so only the first hop is exposed ("The Big Send-off",
PAPERS.md; TransformerEngine's ring-exchange ag/rs overlap is the GPU
analogue). The α-β cost model (cost_model/cost.py) prices this as the
``tp_overlap`` discount.

Discipline: full-manual ``shard_map`` over the layer's (dp, tp) mesh axes —
the same shard_map style ``runtime/compiled_pipeline.py`` and the flash
kernel wrapper use — with custom VJPs so the backward runs the transposed
collectives ring-overlapped too:

* :func:`make_ag_matmul` (column-parallel, e.g. qkv / MLP fc1):
  x [B, S/tp, H] (sequence-sharded) x w [H, F/tp] -> y [B, S, F/tp];
  bwd: dx = ring-reduce-scatter(dy @ w^T), dw = ring-ag(x)^T @ dy.
* :func:`make_matmul_rs` (row-parallel, e.g. attn out / MLP fc2):
  h [B, S, F/tp] x w [F/tp, H] -> y [B, S/tp, H] (partial products ring
  reduce-scattered as they finish); bwd mirrors with the ag ring.

Both are tolerance-identical to the GSPMD reference (the einsum paths in
``models/modules.py``): fp32 accumulation, per-chunk matmuls, only the
reduction ORDER across tp ranks differs (tests/kernels/test_tp_overlap.py
pins fwd+bwd parity at tp∈{2,4} in bf16 and f32).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from hetu_galvatron_tpu.ops.pallas.common import on_shards
from hetu_galvatron_tpu.runtime.mesh import axes_size as _axis_prod

# HLO-metadata marker for the ring ppermutes (jax.named_scope): trace
# attribution (observability/trace_analysis.py) uses it to bill tp-ring
# collective-permute time to the tp component instead of pp/cp when the
# rings run inside the compiled pipeline's single program
TP_RING_SCOPE = "tp_ring"


def _ring_perm(tp: int):
    return [(i, (i + 1) % tp) for i in range(tp)]


def _with_stage(spec: P, stage_axis: Optional[str]) -> P:
    """Prepend the compiled pipeline's stage axis to a kernel spec: the
    caller's operands carry a leading ``[pp, ...]`` stage dim (one stage per
    ``pp`` mesh row), which the kernel treats as a local size-1 lane."""
    return P(stage_axis, *spec) if stage_axis else spec


def staged_lane(fn: Callable, stage: bool) -> Callable:
    """Adapt a local shard_map body to the optional leading stage lane of
    the compiled 1F1B engine: inside the (full-manual) shard_map each
    operand arrives as ``[1, ...]`` — one stage's slice — so the body runs
    on the squeezed view and the lane dim is restored on the way out. The
    squeeze/expand pair is linear, so the custom VJPs underneath transpose
    through it unchanged. Shared by every stage-capable kernel factory
    (ring matmuls here, ring attention, Ulysses, flash)."""
    if not stage:
        return fn

    def wrapped(*args):
        out = fn(*(a[0] for a in args))
        if isinstance(out, tuple):
            return tuple(o[None] for o in out)
        return out[None]

    return wrapped


_staged = staged_lane  # module-internal alias used by the builders below


# ---------------------------------------------------------------------------
# per-shard ring kernels (run inside shard_map; axes/tp are static)
# ---------------------------------------------------------------------------


def _ring_ag_matmul(x, w, axes, tp, with_gathered=False):
    """Local: x [B, C, H] (this rank's sequence chunk), w [H, Fl] ->
    y [B, tp*C, Fl] fp32 (plus the assembled [B, tp*C, H] gather when
    ``with_gathered`` — the chunks pass through anyway, and saving them
    lets the backward form dw with ZERO extra collectives, exactly like
    GSPMD saving the gathered activation). Step t multiplies the chunk
    currently held (origin rank (r - t) % tp) while the ppermute ships it
    onward — the rotation is independent of the matmul, so the scheduler
    overlaps them."""
    r = jax.lax.axis_index(axes)
    B, C, _ = x.shape
    out = jnp.zeros((B, tp * C, w.shape[1]), jnp.float32)
    gathered = jnp.zeros((B, tp * C, x.shape[2]), x.dtype) \
        if with_gathered else None
    perm = _ring_perm(tp)
    cur = x
    for t in range(tp):
        c = (r - t) % tp  # origin chunk id of the block currently held
        part = jnp.einsum("bch,hf->bcf", cur, w,
                          preferred_element_type=jnp.float32)
        out = jax.lax.dynamic_update_slice(out, part, (0, c * C, 0))
        if with_gathered:
            gathered = jax.lax.dynamic_update_slice(
                gathered, cur, (0, c * C, 0))
        if t < tp - 1:
            cur = jax.lax.ppermute(cur, axes, perm)
    return (out, gathered) if with_gathered else out


def _ring_matmul_rs(h, w, axes, tp):
    """Local: h [B, S, Fl], w [Fl, Hd] -> this rank's sequence chunk of
    sum_over_ranks(h @ w): [B, S/tp, Hd] fp32. The partial-sum accumulator
    for chunk c starts at rank (c+1) % tp and rides the ring, each rank
    adding its partial product for that chunk as it passes through; the
    add and the next hop overlap with the following chunk's matmul."""
    r = jax.lax.axis_index(axes)
    B, S, _ = h.shape
    C = S // tp
    perm = _ring_perm(tp)
    acc = None
    for t in range(tp):
        c = (r - 1 - t) % tp  # chunk whose accumulator this rank holds now
        blk = jax.lax.dynamic_slice(h, (0, c * C, 0), (B, C, h.shape[2]))
        part = jnp.einsum("bcf,fh->bch", blk, w,
                          preferred_element_type=jnp.float32)
        acc = part if acc is None else (
            jax.lax.ppermute(acc, axes, perm) + part)
    return acc  # after tp-1 hops the chunk lands on its home rank r


def _ring_ag_grads(dy, w, h, axes, tp):
    """Fused backward ring for matmul_rs: ONE rotation of the cotangent
    chunk dy [B, C, Hd] serves both outputs —
    dh [B, tp*C, Fl] = all-gather(dy) @ w^T placed chunk-wise, and
    dw [Fl, Hd] = h^T @ all-gather(dy) accumulated chunk-wise."""
    r = jax.lax.axis_index(axes)
    B, C, _ = dy.shape
    Fl = w.shape[0]
    dh = jnp.zeros((B, tp * C, Fl), jnp.float32)
    dw = jnp.zeros((Fl, dy.shape[2]), jnp.float32)
    perm = _ring_perm(tp)
    cur = dy
    for t in range(tp):
        c = (r - t) % tp
        part = jnp.einsum("bch,fh->bcf", cur, w,
                          preferred_element_type=jnp.float32)
        dh = jax.lax.dynamic_update_slice(dh, part, (0, c * C, 0))
        h_c = jax.lax.dynamic_slice(h, (0, c * C, 0), (B, C, Fl))
        dw = dw + jnp.einsum("bcf,bch->fh", h_c, cur,
                             preferred_element_type=jnp.float32)
        if t < tp - 1:
            cur = jax.lax.ppermute(cur, axes, perm)
    return dh, dw


# ---------------------------------------------------------------------------
# public builders
# ---------------------------------------------------------------------------


def make_ag_matmul(mesh: Mesh, dp_axes: Tuple[str, ...],
                   tp_axes: Tuple[str, ...],
                   stage_axis: Optional[str] = None) -> Callable:
    """Column-parallel overlapped matmul: callable(x, w) with GLOBAL arrays
    x [B, S, H] (batch over dp, sequence over tp) and w [H, F] (columns over
    tp), returning fp32 [B, S, F] (features over tp) — the drop-in
    replacement for ``all-gather(seq) -> einsum`` in apply_attention /
    apply_mlp.

    ``stage_axis`` (the compiled 1F1B engine): operands and result carry a
    leading ``[pp, ...]`` stacked stage dim sharded on that mesh axis —
    x [pp, B, S, H], w [pp, H, F] — and each pp mesh row rings only its own
    stage's slice. This is how the kernels run INSIDE the fused pipeline
    program: one full-manual shard_map spanning the whole mesh, no nesting."""
    tp = _axis_prod(mesh, tp_axes)
    axes = tuple(tp_axes)

    @jax.custom_vjp
    def local(x, w):
        with jax.named_scope(TP_RING_SCOPE):
            return _ring_ag_matmul(x, w, axes, tp)

    def fwd(x, w):
        # save the ring-gathered activation (it passes through anyway):
        # dw then needs no collectives at all, matching GSPMD's
        # save-the-gather backward
        with jax.named_scope(TP_RING_SCOPE):
            y, x_full = _ring_ag_matmul(x, w, axes, tp, with_gathered=True)
        return y, (x_full, w)

    def bwd(res, dy):
        x_full, w = res
        # dx = reduce-scatter(dy @ w^T) over sequence — the rs ring with
        # the transposed weight; dw is collective-free off the saved gather
        # (the gather keeps x's dtype, so the casts below stay primal-exact)
        with jax.named_scope(TP_RING_SCOPE):
            dx = _ring_matmul_rs(dy, w.T, axes, tp).astype(x_full.dtype)
        dw = jnp.einsum("bsh,bsf->hf", x_full, dy,
                        preferred_element_type=jnp.float32).astype(w.dtype)
        return dx, dw

    local.defvjp(fwd, bwd)
    x_spec = _with_stage(P(dp_axes or None, axes, None), stage_axis)
    w_spec = _with_stage(P(None, axes), stage_axis)
    y_spec = _with_stage(P(dp_axes or None, None, axes), stage_axis)
    return on_shards(_staged(local, stage_axis is not None), mesh,
                     (x_spec, w_spec), y_spec)


def make_ag_matmul_pair(mesh: Mesh, dp_axes: Tuple[str, ...],
                        tp_axes: Tuple[str, ...],
                        stage_axis: Optional[str] = None) -> Callable:
    """Gated-MLP fc1: callable(x, w_gate, w_up) -> (gate, up), both fp32
    [B, S, F] with features over tp, from ONE ring rotation (each held
    chunk multiplies both weight halves). Splitting the FUSED [H, 2F]
    product globally instead would reshard the ACTIVATION: a tp shard of
    the fused layout holds contiguous columns of [gate | up], so the
    global split crosses shard boundaries and GSPMD pays a per-token
    collective to realign. The pair form moves that realignment to the
    weight halves instead (slicing the fused param re-shards each [H, F]
    half over tp) — weights are a per-step constant-size transfer, far
    smaller than the [B, S, F] activations, and the bench showed the swap
    is worth 30-50%% of step time at tp4/swiglu."""
    tp = _axis_prod(mesh, tp_axes)
    axes = tuple(tp_axes)

    def _pair_body(x, wg, wu, with_gathered=False):
        r = jax.lax.axis_index(axes)
        B, C, _ = x.shape
        g = jnp.zeros((B, tp * C, wg.shape[1]), jnp.float32)
        u = jnp.zeros((B, tp * C, wu.shape[1]), jnp.float32)
        gathered = jnp.zeros((B, tp * C, x.shape[2]), x.dtype) \
            if with_gathered else None
        perm = _ring_perm(tp)
        cur = x
        for t in range(tp):
            c = (r - t) % tp
            g = jax.lax.dynamic_update_slice(
                g, jnp.einsum("bch,hf->bcf", cur, wg,
                              preferred_element_type=jnp.float32),
                (0, c * C, 0))
            u = jax.lax.dynamic_update_slice(
                u, jnp.einsum("bch,hf->bcf", cur, wu,
                              preferred_element_type=jnp.float32),
                (0, c * C, 0))
            if with_gathered:
                gathered = jax.lax.dynamic_update_slice(
                    gathered, cur, (0, c * C, 0))
            if t < tp - 1:
                cur = jax.lax.ppermute(cur, axes, perm)
        return g, u, gathered

    @jax.custom_vjp
    def local(x, wg, wu):
        with jax.named_scope(TP_RING_SCOPE):
            g, u, _ = _pair_body(x, wg, wu)
        return g, u

    def fwd(x, wg, wu):
        with jax.named_scope(TP_RING_SCOPE):
            g, u, x_full = _pair_body(x, wg, wu, with_gathered=True)
        return (g, u), (x_full, wg, wu)

    def bwd(res, dys):
        x_full, wg, wu = res
        dg, du = dys
        # dx: ONE rs ring whose per-chunk partial sums both halves'
        # products; dw halves are collective-free off the saved gather
        with jax.named_scope(TP_RING_SCOPE):
            r = jax.lax.axis_index(axes)
            B, S, _ = dg.shape
            C = S // tp
            perm = _ring_perm(tp)
            acc = None
            for t in range(tp):
                c = (r - 1 - t) % tp
                g_c = jax.lax.dynamic_slice(dg, (0, c * C, 0),
                                            (B, C, dg.shape[2]))
                u_c = jax.lax.dynamic_slice(du, (0, c * C, 0),
                                            (B, C, du.shape[2]))
                part = (jnp.einsum("bcf,hf->bch", g_c, wg,
                                   preferred_element_type=jnp.float32)
                        + jnp.einsum("bcf,hf->bch", u_c, wu,
                                     preferred_element_type=jnp.float32))
                acc = part if acc is None else (
                    jax.lax.ppermute(acc, axes, perm) + part)
        dx = acc.astype(x_full.dtype)
        dwg = jnp.einsum("bsh,bsf->hf", x_full, dg,
                         preferred_element_type=jnp.float32).astype(wg.dtype)
        dwu = jnp.einsum("bsh,bsf->hf", x_full, du,
                         preferred_element_type=jnp.float32).astype(wu.dtype)
        return dx, dwg, dwu

    local.defvjp(fwd, bwd)
    x_spec = _with_stage(P(dp_axes or None, axes, None), stage_axis)
    w_spec = _with_stage(P(None, axes), stage_axis)
    y_spec = _with_stage(P(dp_axes or None, None, axes), stage_axis)
    return on_shards(_staged(local, stage_axis is not None), mesh,
                     (x_spec, w_spec, w_spec), (y_spec, y_spec))


def make_matmul_rs(mesh: Mesh, dp_axes: Tuple[str, ...],
                   tp_axes: Tuple[str, ...],
                   stage_axis: Optional[str] = None) -> Callable:
    """Row-parallel overlapped matmul: callable(h, w) with GLOBAL arrays
    h [B, S, F] (features over tp) and w [F, H] (rows over tp), returning
    fp32 [B, S, H] (sequence over tp) — replacing
    ``einsum -> reduce-scatter(seq)``. ``stage_axis``: see
    :func:`make_ag_matmul`."""
    tp = _axis_prod(mesh, tp_axes)
    axes = tuple(tp_axes)

    @jax.custom_vjp
    def local(h, w):
        with jax.named_scope(TP_RING_SCOPE):
            return _ring_matmul_rs(h, w, axes, tp)

    def fwd(h, w):
        with jax.named_scope(TP_RING_SCOPE):
            return _ring_matmul_rs(h, w, axes, tp), (h, w)

    def bwd(res, dy):
        h, w = res
        # one fused ring rotation of dy yields both dh = all-gather(dy) @
        # w^T and dw = h^T @ all-gather(dy)
        with jax.named_scope(TP_RING_SCOPE):
            dh, dw = _ring_ag_grads(dy, w, h, axes, tp)
        return dh.astype(h.dtype), dw.astype(w.dtype)

    local.defvjp(fwd, bwd)
    h_spec = _with_stage(P(dp_axes or None, None, axes), stage_axis)
    w_spec = _with_stage(P(axes, None), stage_axis)
    y_spec = _with_stage(P(dp_axes or None, axes, None), stage_axis)
    return on_shards(_staged(local, stage_axis is not None), mesh,
                     (h_spec, w_spec), y_spec)


# ---------------------------------------------------------------------------
# per-layer eligibility + dispatch
# ---------------------------------------------------------------------------

# The eligibility predicates and fallback-reason strings live in
# analysis/eligibility.py (shared with the launcher's logging, the cost
# model's discount gate and the plan doctor); re-exported here because this
# module is their historical home and the kernel dispatch reads them.
from hetu_galvatron_tpu.analysis.eligibility import (  # noqa: E402,F401
    MOE_REASON,
    T5_REASON,
    layer_overlap_reason,
    plan_overlap_reasons,
)


def make_layer_matmuls(mesh: Mesh, dp_axes: Tuple[str, ...],
                       tp_axes: Tuple[str, ...],
                       stage_axis: Optional[str] = None
                       ) -> Dict[str, Callable]:
    """The projection matmuls of one decoder layer as overlapped
    ring-decomposed fns (``matmul_fns`` for modules.apply_decoder_layer):
    column-parallel qkv/fc1 share one ag_matmul, row-parallel out/fc2 share
    one matmul_rs (the builders are shape-polymorphic), and gated MLPs use
    the shard-aligned ``fc1_pair`` instead of splitting the fused product
    globally. ``stage_axis`` builds the pp-stacked variants the compiled
    pipeline engine calls on ``[pp, ...]`` operands."""
    ag = make_ag_matmul(mesh, dp_axes, tp_axes, stage_axis)
    rs = make_matmul_rs(mesh, dp_axes, tp_axes, stage_axis)
    pair = make_ag_matmul_pair(mesh, dp_axes, tp_axes, stage_axis)
    return {"qkv": ag, "out": rs, "fc1": ag, "fc2": rs, "fc1_pair": pair}
