"""Ring attention: context-parallel causal attention over the cp mesh axes.

Capability parity with the reference's zigzag ring flash attention
(runtime/transformer/attention_impl.py:481-905 ``ZigzagRingFlashAttention`` +
``RingComm`` batched isend/irecv): each cp rank holds a contiguous sequence
block of q/k/v; k/v blocks rotate around the ring while a streaming (online
softmax) accumulator folds each block's contribution — memory per chip stays
O(S/cp) and the ring transfers ride ICI via `lax.ppermute` instead of NCCL
p2p.

Two sequence layouts are supported: contiguous blocks (trivial GSPMD
boundaries; block-causal masking; per-rank compute imbalance bounded by cp)
and the reference's zigzag layout (``zigzag=True``: each rank holds global
half-blocks r and 2cp-1-r, equalizing unmasked work across the ring).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _block_scores(q, k, scale):
    """[B,Sq,K,G,D] x [B,Sk,K,D] -> [B,K,G,Sq,Sk] fp32."""
    return jnp.einsum("bskgd,btkd->bkgst", q, k,
                      preferred_element_type=jnp.float32) * scale


def _positions(rank, length, cp, zigzag):
    """Global sequence positions of a rank's local block. Contiguous layout:
    [rank*L, rank*L + L). Zigzag layout (reference redistribute.py:5-41):
    the local block is the concatenation of global half-blocks rank and
    2cp-1-rank, balancing causal work across the ring."""
    i = jnp.arange(length)
    if not zigzag:
        return rank * length + i
    h = length // 2
    return jnp.where(i < h,
                     rank * h + i,
                     (2 * cp - 1 - rank) * h + (i - h))


def _fold_block(step, acc, *, q, k, v, my_idx, cp, causal, zigzag,
                qseg=None, kseg=None):
    """Fold the key/value block currently held (from rank
    (my_idx - step) mod cp) into the streaming softmax accumulator.
    ``qseg`` [B, Sq] / ``kseg`` [B, Sk] block-diagonalize packed documents
    (reference reset_attention_mask); kseg rotates with its k/v block."""
    o, m, l = acc
    B, Sq, K, G, D = q.shape
    src_block = (my_idx - step) % cp
    scores = _block_scores(q, k, 1.0 / math.sqrt(D))  # [B,K,G,Sq,Sk]
    if causal:
        qpos = _positions(my_idx, Sq, cp, zigzag)[:, None]
        kpos = _positions(src_block, k.shape[1], cp, zigzag)[None, :]
        scores = jnp.where(qpos >= kpos, scores, NEG_INF)
    if qseg is not None:
        same = (qseg[:, None, None, :, None]
                == kseg[:, None, None, None, :])  # [B,1,1,Sq,Sk]
        scores = jnp.where(same, scores, NEG_INF)
    block_max = jnp.max(scores, axis=-1)  # [B,K,G,Sq]
    new_m = jnp.maximum(m, block_max)
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
    correction = jnp.exp(jnp.where(m == NEG_INF, NEG_INF, m - new_m))
    p = jnp.exp(scores - new_m[..., None])
    p = jnp.where(scores == NEG_INF, 0.0, p)
    new_l = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    new_o = o * correction[..., None] + pv
    return new_o, new_m, new_l


def _ring_body(step, carry, *, q, qseg, my_idx, cp, causal, zigzag, axis):
    """One ring step: fold the current block, then rotate k/v (and the
    k-side segment ids) onward."""
    o, m, l, k, v, kseg = carry
    o, m, l = _fold_block(step, (o, m, l), q=q, k=k, v=v, my_idx=my_idx,
                          cp=cp, causal=causal, zigzag=zigzag,
                          qseg=qseg, kseg=kseg)
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    k = jax.lax.ppermute(k, axis, perm)
    v = jax.lax.ppermute(v, axis, perm)
    if kseg is not None:
        kseg = jax.lax.ppermute(kseg, axis, perm)
    return o, m, l, k, v, kseg


def _ring_attention_local(q, k, v, seg=None, *, axis, cp, causal,
                          zigzag=False):
    """Per-shard kernel under shard_map: q/k/v are the local sequence blocks
    [B, S/cp, N|K, D]; ``seg`` [B, S/cp] packed-document segment ids.
    ``cp`` is the static ring size (the caller knows it from the mesh)."""
    my_idx = jax.lax.axis_index(axis)
    B, Sq, N, D = q.shape
    K = k.shape[2]
    G = N // K
    qg = q.reshape(B, Sq, K, G, D)
    o = jnp.zeros((B, K, G, Sq, D), jnp.float32)
    m = jnp.full((B, K, G, Sq), NEG_INF, jnp.float32)
    l = jnp.zeros((B, K, G, Sq), jnp.float32)
    body = partial(_ring_body, q=qg, qseg=seg, my_idx=my_idx, cp=cp,
                   causal=causal, zigzag=zigzag, axis=axis)
    # cp-1 fold+rotate steps, then the final fold without the wasted rotate
    # (seg=None is a structure-only pytree leaf: one loop serves both cases)
    o, m, l, k, v, kseg = jax.lax.fori_loop(
        0, cp - 1, body, (o, m, l, k, v, seg))
    o, m, l = _fold_block(cp - 1, (o, m, l), q=qg, k=k, v=v, my_idx=my_idx,
                          cp=cp, causal=causal, zigzag=zigzag,
                          qseg=seg, kseg=kseg)
    o = o / jnp.maximum(l, 1e-20)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, N, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# flash-inside-the-ring: each ring step runs the Pallas flash kernel on the
# currently-held k/v block; per-block (o, lse) pairs merge in log space.
# Mirrors the reference's zigzag ring flash (attention_impl.py:564-905), where
# each step issues a flash_attn call on a full or half block:
#   * diagonal step (src == my): plain causal flash on the local layout
#     (for zigzag the local [half r | half 2cp-1-r] order IS causal order);
#   * src < my: every q row attends the earlier block — non-causal flash on
#     the full k (contiguous) or its first half (zigzag: the second half of
#     an earlier rank's block is LATER than all local rows... see _positions);
#   * src > my: contiguous ranks skip entirely; zigzag ranks attend with the
#     local second half only (global half-block 2cp-1-my is after everything
#     rank src holds).
# The backward replays the ring with the final (o, lse): the flash backward
# recomputes p per tile from the global logsumexp, so per-step dk/dv are
# exact partial sums; they accumulate in buffers that rotate in lockstep
# with k/v and arrive home after cp rotations (the reference's reverse-ring
# send of dk/dv).
# ---------------------------------------------------------------------------


def _blocks_or_die(q, k, floor: int) -> Tuple[int, int]:
    """The flash blocks of one ring step on heads-major q / k blocks."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import choose_blocks

    if not ring_flash_blocks_fit(q.shape[2], False, floor) \
            or not ring_flash_blocks_fit(k.shape[2], False, floor):
        raise ValueError(f"no flash block >= {floor} divides seq "
                         f"{q.shape[2]} / {k.shape[2]}")
    return choose_blocks(q.shape[3], q.shape[2], k.shape[2], floor)


def ring_flash_blocks_fit(s_local: int, zigzag: bool, floor: int) -> bool:
    """Whether the flash-in-ring path can tile this local sequence length
    (callers fall back to the dense XLA ring otherwise)."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        DEFAULT_BLOCK_K,
        DEFAULT_BLOCK_Q,
        fit_block,
    )

    seqs = [s_local] + ([s_local // 2] if zigzag else [])
    return all(s > 0
               and fit_block(DEFAULT_BLOCK_Q, s, floor)
               and fit_block(DEFAULT_BLOCK_K, s, floor) for s in seqs)


def _fa_block(q, k, v, causal, interpret, floor):
    """Forward flash on one (q, k/v) block pair; heads-major [B,N,S,D]."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        flash_attention_hmajor,
    )

    bq, bk = _blocks_or_die(q, k, floor)
    o, lse = flash_attention_hmajor(q, k, v, None, causal=causal,
                                    block_q=bq, block_k=bk,
                                    interpret=interpret)
    return o.astype(jnp.float32), lse


def _fa_block_bwd(q, k, v, o, lse, do, causal, interpret, floor):
    """Backward flash on one block pair -> (dq, dk, dv) fp32."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd_hmajor,
    )

    bq, bk = _blocks_or_die(q, k, floor)
    dq, dk, dv = flash_attention_bwd_hmajor(
        q, k, v, o, lse, do, None, causal=causal,
        block_q=bq, block_k=bk, interpret=interpret)
    return (dq.astype(jnp.float32), dk.astype(jnp.float32),
            dv.astype(jnp.float32))


def _combine_blocks(o, lse, oi, lsei):
    """Merge two normalized flash outputs (o fp32 [B,N,S,D], lse
    [B,N,S,1]): o = o*exp(lse-m)/denom + oi*exp(lsei-m)/denom."""
    m = jnp.maximum(lse, lsei)
    m_safe = jnp.where(m == NEG_INF, 0.0, m)
    a = jnp.where(lse == NEG_INF, 0.0, jnp.exp(lse - m_safe))
    ai = jnp.where(lsei == NEG_INF, 0.0, jnp.exp(lsei - m_safe))
    denom = jnp.maximum(a + ai, 1e-38)
    new_lse = jnp.where(a + ai > 0.0, m_safe + jnp.log(denom), NEG_INF)
    return o * (a / denom) + oi * (ai / denom), new_lse


def _rotate(ts, axis, cp):
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    return tuple(jax.lax.ppermute(t, axis, perm) for t in ts)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash_local(q, k, v, axis, cp, causal, zigzag, interpret, floor):
    out, _ = _ring_flash_fwd(q, k, v, axis, cp, causal, zigzag, interpret,
                             floor)
    return out


def _ring_flash_fwd(q, k, v, axis, cp, causal, zigzag, interpret, floor):
    """q [B,N,S,D], k/v [B,K,S,D] heads-major local blocks under shard_map."""
    my = jax.lax.axis_index(axis)
    B, N, S, D = q.shape
    K = k.shape[1]
    half = S // 2
    kt, vt = k, v
    o, lse = _fa_block(q, kt, vt, causal, interpret, floor)  # diagonal step
    for t in range(1, cp):
        kt, vt = _rotate((kt, vt), axis, cp)
        src = (my - t) % cp
        if not causal:
            oi, lsei = _fa_block(q, kt, vt, False, interpret, floor)
        elif zigzag:
            def _earlier(kb, vb):
                # src holds global half-blocks (src, 2cp-1-src); only the
                # FIRST half (src < my) is in the local rows' past
                return _fa_block(q, kb[:, :, :half], vb[:, :, :half],
                                 False, interpret, floor)

            def _later(kb, vb):
                # src > my: only local half 2cp-1-my (rows half:) is after
                # everything rank src holds
                ob, lb = _fa_block(q[:, :, half:], kb, vb,
                                   False, interpret, floor)
                return (
                    jnp.concatenate(
                        [jnp.zeros((B, N, half, D), jnp.float32), ob], 2),
                    jnp.concatenate(
                        [jnp.full((B, N, half, 1), NEG_INF, jnp.float32),
                         lb], 2),
                )

            oi, lsei = jax.lax.cond(src < my, _earlier, _later, kt, vt)
        else:
            def _earlier(kb, vb):
                return _fa_block(q, kb, vb, False, interpret, floor)

            def _later(kb, vb):
                return (jnp.zeros((B, N, S, D), jnp.float32),
                        jnp.full((B, N, S, 1), NEG_INF, jnp.float32))

            oi, lsei = jax.lax.cond(src < my, _earlier, _later, kt, vt)
        o, lse = _combine_blocks(o, lse, oi, lsei)
    out = o.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis, cp, causal, zigzag, interpret, floor, res, do):
    """Ring replay: per-step flash backward against the final (o, lse);
    dk/dv partial sums rotate with k/v and arrive home after cp steps."""
    q, k, v, o, lse = res
    my = jax.lax.axis_index(axis)
    B, N, S, D = q.shape
    K = k.shape[1]
    half = S // 2
    dq = jnp.zeros((B, N, S, D), jnp.float32)
    dk_acc = jnp.zeros((B, K, S, D), jnp.float32)
    dv_acc = jnp.zeros((B, K, S, D), jnp.float32)
    kt, vt = k, v
    for t in range(cp):
        src = (my - t) % cp
        if t == 0:
            dq_c, dk_c, dv_c = _fa_block_bwd(q, kt, vt, o, lse, do, causal,
                                             interpret, floor)
        elif not causal:
            dq_c, dk_c, dv_c = _fa_block_bwd(q, kt, vt, o, lse, do, False,
                                             interpret, floor)
        elif zigzag:
            def _earlier(kb, vb):
                dqb, dkb, dvb = _fa_block_bwd(
                    q, kb[:, :, :half], vb[:, :, :half], o, lse, do,
                    False, interpret, floor)
                pad = jnp.zeros((B, K, half, D), jnp.float32)
                return (dqb, jnp.concatenate([dkb, pad], 2),
                        jnp.concatenate([dvb, pad], 2))

            def _later(kb, vb):
                dqb, dkb, dvb = _fa_block_bwd(
                    q[:, :, half:], kb, vb, o[:, :, half:],
                    lse[:, :, half:], do[:, :, half:],
                    False, interpret, floor)
                pad = jnp.zeros((B, N, half, D), jnp.float32)
                return jnp.concatenate([pad, dqb], 2), dkb, dvb

            dq_c, dk_c, dv_c = jax.lax.cond(src < my, _earlier, _later,
                                            kt, vt)
        else:
            def _earlier(kb, vb):
                return _fa_block_bwd(q, kb, vb, o, lse, do, False,
                                     interpret, floor)

            def _later(kb, vb):
                return (jnp.zeros((B, N, S, D), jnp.float32),
                        jnp.zeros((B, K, S, D), jnp.float32),
                        jnp.zeros((B, K, S, D), jnp.float32))

            dq_c, dk_c, dv_c = jax.lax.cond(src < my, _earlier, _later,
                                            kt, vt)
        dq = dq + dq_c
        dk_acc = dk_acc + dk_c
        dv_acc = dv_acc + dv_c
        # rotate every step (cp total): a contribution for block b added at
        # step t undergoes cp - t further rotations -> lands on rank b
        kt, vt, dk_acc, dv_acc = _rotate((kt, vt, dk_acc, dv_acc), axis, cp)
    return dq.astype(q.dtype), dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)


_ring_flash_local.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def _ring_flash_sdpa_local(q, k, v, *, axis, cp, causal, zigzag, interpret,
                           floor):
    """shard_map body: [B, S/cp, N|K, D] in/out (matches
    :func:`_ring_attention_local`); flash kernels want heads-major."""
    qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    out = _ring_flash_local(qh, kh, vh, axis, cp, causal, zigzag, interpret,
                            floor)
    return out.transpose(0, 2, 1, 3)


def make_ring_sdpa(
    mesh: Mesh,
    cp_axes: Tuple[str, ...],
    dp_axes: Tuple[str, ...] = (),
    tp_axes: Tuple[str, ...] = (),
    zigzag: bool = False,
    use_flash: bool = False,
    interpret: bool = False,
    data_zigzagged: bool = False,
    stage_axis: Optional[str] = None,
):
    """sdpa_fn for modules.apply_attention: reshards q/k/v so the sequence
    lives on the cp axes, runs the ring kernel under shard_map, and hands the
    seq-sharded output back to GSPMD (the reference reaches its ring kernel
    through the per-layer dispatch at attention.py:664-720).

    ``zigzag=True`` re-lays the sequence into the reference's balanced
    causal order around the kernel (RoPE is applied upstream, so permuting
    post-RoPE q/k/v is position-safe). Balancing costs one all-to-all-ish
    reshard at entry/exit; pushing the zigzag layout out to the dataloader
    (get_batch zigzag slice, reference utils.py:295) removes that cost and
    is the long-sequence deployment mode.

    ``use_flash=True`` runs the Pallas flash kernel inside each ring step
    (the reference's flash-in-ring, attention_impl.py:564-905) instead of
    the dense per-block XLA fold — O(block) memory per step at MXU speed.
    Falls back to the dense fold per call when no lane-aligned flash block
    tiles the local sequence. ``interpret=True`` is for CPU tests.

    ``data_zigzagged=True`` (with ``zigzag=True``) declares the inputs
    ALREADY in zigzag order — the dataloader applied the layout
    (runtime/dataloader.py zigzag_cp_batches) — so the entry/exit
    permutes are skipped entirely: zero reshard cost per call.

    ``stage_axis`` (the compiled 1F1B engine): q/k/v carry a leading
    ``[pp, ...]`` stacked stage dim sharded on that mesh axis — the
    shard_map spans the whole mesh (full-manual, pp included) and each pp
    row rings only its own stage's blocks over the cp axes."""
    if not cp_axes:
        raise ValueError("ring attention needs at least one cp axis")
    if data_zigzagged and not zigzag:
        raise ValueError("data_zigzagged requires zigzag=True (the kernel "
                         "must mask by zigzag global positions)")
    axis = cp_axes if len(cp_axes) > 1 else cp_axes[0]
    spec = P(dp_axes or None, cp_axes, tp_axes or None, None)
    s_dim = 1
    if stage_axis is not None:
        spec = P(stage_axis, *spec)
        s_dim = 2
    cp = 1
    for a in cp_axes:
        cp *= mesh.shape[a]

    def sdpa(q, k, v, *, causal=True, segment_ids=None):
        S = q.shape[s_dim]
        if S % cp:
            raise ValueError(f"sequence {S} not divisible by cp {cp}")
        if zigzag and S % (2 * cp):
            raise ValueError(
                f"zigzag layout needs sequence {S} divisible by 2*cp "
                f"= {2 * cp} (two half-blocks per rank)")
        floor = 8 if interpret else 128
        has_seg = segment_ids is not None
        if (use_flash and not has_seg
                and ring_flash_blocks_fit(S // cp, zigzag, floor)):
            local = partial(_ring_flash_sdpa_local, axis=axis, cp=cp,
                            causal=causal, zigzag=zigzag,
                            interpret=interpret, floor=floor)
        else:
            # packed documents ride the dense fold: k-side segment ids
            # rotate with their k/v block; the flash-in-ring kernels would
            # need unequal-length q/k segment operands (future work)
            local = partial(_ring_attention_local, axis=axis, cp=cp,
                            causal=causal, zigzag=zigzag)
        from hetu_galvatron_tpu.ops.overlap import staged_lane

        # each pp row holds its stage's [1, ...] lane: squeeze it, run the
        # ring, restore it on the way out (the shared compiled-engine
        # adapter)
        inner = staged_lane(local, stage_axis is not None)
        local_scoped = lambda *a, _f=inner: _cp_scoped(_f, *a)
        seg_spec = P(spec[0], cp_axes) if stage_axis is None \
            else P(stage_axis, spec[1], cp_axes)
        in_specs = (spec, spec, spec) + ((seg_spec,) if has_seg else ())
        from hetu_galvatron_tpu.ops.pallas.common import on_shards

        fn = on_shards(local_scoped, mesh, in_specs, spec)
        relayout = zigzag and not data_zigzagged
        if relayout:
            q, k, v = (zigzag_layout(t, cp, axis=s_dim) for t in (q, k, v))
            if has_seg:
                segment_ids = zigzag_layout(segment_ids, cp, axis=s_dim)
        out = fn(q, k, v, *((segment_ids,) if has_seg else ()))
        return zigzag_unlayout(out, cp, axis=s_dim) if relayout else out

    sdpa.supports_segments = True
    return sdpa


def _cp_scoped(fn, *args):
    """Run a ring body under the ``cp_ring`` HLO-metadata scope so trace
    attribution can bill its collective-permutes to the cp component even
    when they share one program with pp stage rotations
    (observability/trace_analysis.py)."""
    with jax.named_scope("cp_ring"):
        return fn(*args)


def zigzag_layout(x: jax.Array, cp: int, axis: int = 1) -> jax.Array:
    """Re-layout a sequence into zigzag block order (block i and 2cp-1-i per
    rank) — the reference's balanced causal layout (redistribute.py:5-41).
    Provided for interchange with zigzag-trained checkpoints/plans."""
    blocks = jnp.split(x, 2 * cp, axis=axis)
    out = []
    for r in range(cp):
        out.append(blocks[r])
        out.append(blocks[2 * cp - 1 - r])
    return jnp.concatenate(out, axis=axis)


def zigzag_unlayout(x: jax.Array, cp: int, axis: int = 1) -> jax.Array:
    """Inverse of :func:`zigzag_layout`."""
    blocks = jnp.split(x, 2 * cp, axis=axis)
    out = [None] * (2 * cp)
    for r in range(cp):
        out[r] = blocks[2 * r]
        out[2 * cp - 1 - r] = blocks[2 * r + 1]
    return jnp.concatenate(out, axis=axis)
