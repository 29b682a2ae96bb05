"""Pallas flash attention for TPU: causal, GQA-aware, online-softmax.

Replaces the reference's external flash-attn CUDA ops (SURVEY §2 native-code
checklist item 4; installed by galvatron/scripts/flash_attn_ops_install.sh)
with a TPU kernel: the grid runs (batch, q-head, q-block, k-block) with the
k-block axis innermost, so each k/v tile is DMA'd into VMEM on demand while
running-max/normalizer/accumulator scratch persists across k-steps — the
[S, S] score matrix never exists and VMEM holds only O(block) tiles, so
sequence length is bounded by HBM, not VMEM.

Layout: q [B, N, S, D], k/v [B, K, S, D] (heads-major so a grid cell's tiles
are contiguous); GQA maps q-head n to kv-head n // (N // K) in the index map.
The backward is fused too (dq and dk/dv kernels recompute p per tile from the
saved logsumexp), so neither direction materializes [S, S].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)


def keep_mask(seed, bn, qpos, kpos, rate: float):
    """Deterministic counter-based dropout keep-mask (splitmix32 finalizer
    chain over global coordinates). Depends only on GLOBAL coordinates
    (seed, batch*heads index, q position, k position), so forward/backward
    kernels regenerate identical masks regardless of tile sizes — the same
    property the reference gets from flash-attn's saved philox state. Plain
    integer ops only: lowers under Mosaic AND interpret mode (pltpu.prng_*
    has no CPU lowering), and a pure-JAX caller over full index grids is
    the test reference. qpos/kpos are int32 arrays broadcastable to the
    mask shape; returns bool (True = keep).

    There is no sequence-length bound: qpos and kpos are mixed through
    SEPARATE finalizer rounds rather than a linear ``qpos * S + kpos``
    counter (which wrapped uint32 once S exceeded 2**16 and aliased masks
    between distant (qpos, kpos) pairs within one head — the PR 1 fix), so
    distinct coordinate pairs collide only by hash accident, like head
    streams. The old ``s_total`` parameter that rode along for call-site
    compatibility is gone."""
    import numpy as np

    # numpy scalar literals (NOT jnp arrays): closed-over jnp constants are
    # rejected by the pallas_call lowering
    u32 = jnp.uint32
    c = np.uint32

    def fin(x):  # splitmix32 finalizer (full avalanche)
        x = x ^ (x >> c(16))
        x = x * c(0x85EBCA6B)
        x = x ^ (x >> c(13))
        x = x * c(0xC2B2AE35)
        return x ^ (x >> c(16))

    # hash (seed, bn) into a per-head key FIRST: a linear bn*S^2 counter
    # would wrap every 2^32/S^2 heads and hand distant heads bit-identical
    # masks; after avalanche, head streams collide only by hash accident
    key = fin(seed.astype(u32) * c(0x9E3779B9) + bn.astype(u32))
    x = fin(fin(qpos.astype(u32) ^ key) ^ kpos.astype(u32))
    keep_prob = 1.0 - rate
    threshold = c(min(int(keep_prob * 2.0 ** 32), 2 ** 32 - 1))
    return x < threshold


def _tile_keep(seed_ref, bn, qi, ki, block_q: int, block_k: int,
               rate: float):
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return keep_mask(seed_ref[0], bn, qpos, kpos, rate)


def _flash_kernel(q_ref, k_ref, v_ref, *rest,
                  block_q: int, block_k: int, num_k: int, causal: bool,
                  scale: float, has_seg: bool = False,
                  dropout_rate: float = 0.0):
    if dropout_rate > 0.0:
        seed_ref, rest = rest[0], rest[1:]
    else:
        seed_ref = None
    if has_seg:
        qseg_ref, kseg_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        qseg_ref = kseg_ref = None
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    # flat batch*heads index for the dropout mask; program_id must be read
    # at kernel top level (the interpret-mode executor does not rewrite it
    # inside pl.when bodies)
    bn = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # blocks entirely past the causal diagonal contribute nothing
    diag_last = (qi * block_q + block_q - 1) // block_k if causal else num_k

    @pl.when(ki <= diag_last)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        if qseg_ref is not None:
            # packed documents: mask cross-segment pairs (reference
            # reset_attention_mask; same trailing-singleton layout as lse)
            s = jnp.where(qseg_ref[0, :, 0][:, None]
                          == kseg_ref[0, :, 0][None, :], s, NEG_INF)
        m = m_ref[...]
        block_max = jnp.max(s, axis=1)
        new_m = jnp.maximum(m, block_max)
        corr = jnp.exp(jnp.where(m == NEG_INF, NEG_INF, m - new_m))
        p = jnp.exp(s - new_m[:, None])
        p = jnp.where(s == NEG_INF, 0.0, p)
        m_ref[...] = new_m
        # the normalizer uses the UNdropped p: out = dropout(softmax(s)) @ v
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        if dropout_rate > 0.0:
            keep = _tile_keep(seed_ref, bn, qi, ki, block_q, block_k,
                              dropout_rate)
            p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        # logsumexp per row, consumed by the backward kernels; stored with a
        # trailing singleton lane dim — Mosaic requires the last two block
        # dims to be (mult-of-8, mult-of-128) or equal to the array dims, so
        # a rank-3 (1, 1, block_q) lse block cannot lower on hardware
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l))[:, None]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "dropout_rate"))
def flash_attention_hmajor(
    q: jax.Array,  # [B, N, S, D]
    k: jax.Array,  # [B, K, S, D]
    v: jax.Array,
    segments: "jax.Array | None" = None,  # [B, S] int32 (packed docs)
    dropout_seed: "jax.Array | None" = None,  # [1] int32 (attention dropout)
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    dropout_rate: float = 0.0,
) -> jax.Array:
    B, N, S, D = q.shape
    K = k.shape[1]
    Sk = k.shape[2]  # may differ from S (ring off-diagonal blocks)
    G = N // K
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    if S % block_q or Sk % block_k:
        raise ValueError(
            f"seq {S}/{Sk} must divide by blocks {block_q}/{block_k}")
    if causal and Sk != S:
        raise ValueError("causal flash needs equal q/k lengths")
    if segments is not None and Sk != S:
        raise ValueError("segment masking needs equal q/k lengths")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 needs a dropout_seed")
    num_k = Sk // block_k
    grid = (B, N, S // block_q, num_k)  # k-block axis innermost
    has_seg = segments is not None
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, num_k=num_k,
        causal=causal, scale=1.0 / math.sqrt(D), has_seg=has_seg,
        dropout_rate=dropout_rate)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, D),
                     lambda b, n, qi, ki: (b, n, qi, 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, n, qi, ki: (b, n // G, ki, 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, n, qi, ki: (b, n // G, ki, 0)),
    ]
    operands = [q, k, v]
    if dropout_rate > 0.0:
        # kernel unpacks the seed ref FIRST from *rest (after q/k/v)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(dropout_seed.astype(jnp.int32).reshape(1))
    if has_seg:
        # [B, S, 1]: trailing singleton keeps Mosaic's (8, 128)-or-equal
        # tiling rule satisfied (same layout trick as lse)
        seg3 = segments.astype(jnp.int32)[:, :, None]
        in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, n, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, 1), lambda b, n, qi, ki: (b, ki, 0)),
        ]
        operands += [seg3, seg3]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, n, qi, ki: (b, n, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, n, qi, ki: (b, n, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, N, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, N, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        # only the k-block axis carries loop state (the online softmax);
        # everything else may be reordered/partitioned by Mosaic
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        # the kernel's instruction name on a trace's ``XLA Ops`` line; the
        # three kernels differ after ``flash_attention`` so that a trace
        # tells them apart and a ``^flash_attention`` pattern finds all
        name="flash_attention_fwd",
    )(*operands)


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           *rest, block_q: int, block_k: int, num_q: int,
                           G: int, causal: bool, scale: float,
                           has_seg: bool = False,
                           dropout_rate: float = 0.0):
    """Grid (B, KV, kb, G, qb): accumulate dk/dv for one k/v tile across the
    G query heads of this kv head and all q blocks."""
    if dropout_rate > 0.0:
        seed_ref, rest = rest[0], rest[1:]
    else:
        seed_ref = None
    if has_seg:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        qseg_ref = kseg_ref = None
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    kb = pl.program_id(2)
    g = pl.program_id(3)
    qb = pl.program_id(4)
    # flat head index n = kh*G + g (N = KV*G heads); top-level program_id
    bn = pl.program_id(0) * (pl.num_programs(1) * G) + pl.program_id(1) * G + g

    @pl.when((g == 0) & (qb == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # q blocks entirely above the causal diagonal contribute nothing
    first_q = (kb * block_k) // block_q if causal else 0

    @pl.when(qb >= first_q)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # (block_q, 1): broadcasts over block_k
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        if qseg_ref is not None:
            s = jnp.where(qseg_ref[0, :, 0][:, None]
                          == kseg_ref[0, :, 0][None, :], s, NEG_INF)
        p = jnp.exp(s - lse)
        p = jnp.where(s == NEG_INF, 0.0, p)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        pd = p
        if dropout_rate > 0.0:
            # mask is (qpos, kpos)-indexed; this kernel's tile is q=qb, k=kb
            keep = _tile_keep(seed_ref, bn, qb, kb, block_q, block_k,
                              dropout_rate)
            pd = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        dv_acc[...] += jax.lax.dot_general(
            pd, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # delta = rowsum(dropout(P) . dP') = dO . O, so the flash delta
        # trick survives dropout unchanged
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((g == G - 1) & (qb == num_q - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *rest, block_q: int, block_k: int,
                         num_k: int, causal: bool, scale: float,
                         has_seg: bool = False,
                         dropout_rate: float = 0.0):
    """Grid (B, N, qb, kb): accumulate dq for one q tile across k blocks."""
    if dropout_rate > 0.0:
        seed_ref, rest = rest[0], rest[1:]
    else:
        seed_ref = None
    if has_seg:
        qseg_ref, kseg_ref, dq_ref, dq_acc = rest
    else:
        qseg_ref = kseg_ref = None
        dq_ref, dq_acc = rest
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    bn = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    diag_last = (qb * block_q + block_q - 1) // block_k if causal else num_k

    @pl.when(kb <= diag_last)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # (block_q, 1): broadcasts over block_k
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        if qseg_ref is not None:
            s = jnp.where(qseg_ref[0, :, 0][:, None]
                          == kseg_ref[0, :, 0][None, :], s, NEG_INF)
        p = jnp.exp(s - lse)
        p = jnp.where(s == NEG_INF, 0.0, p)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _tile_keep(seed_ref, bn, qb, kb,
                              block_q, block_k, dropout_rate)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == num_k - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "dropout_rate"))
def flash_attention_bwd_hmajor(
    q, k, v, o, lse, do, segments=None, dropout_seed=None, *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    dropout_rate: float = 0.0,
):
    """Fused flash backward (heads-major layouts): recomputes p from lse per
    tile, so nothing O(S^2) ever hits HBM. Returns (dq, dk, dv)."""
    B, N, S, D = q.shape
    KV = k.shape[1]
    Sk = k.shape[2]  # may differ from S (ring off-diagonal blocks)
    G = N // KV
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    num_q = S // block_q
    num_k = Sk // block_k
    scale = 1.0 / math.sqrt(D)
    if causal and Sk != S:
        raise ValueError("causal flash needs equal q/k lengths")
    has_seg = segments is not None
    if has_seg and Sk != S:
        raise ValueError("segment masking needs equal q/k lengths")
    # (B, N, S, 1): same trailing-singleton layout as lse (Mosaic tiling)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)

    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 needs a dropout_seed")
    seed_arr = (dropout_seed.astype(jnp.int32).reshape(1)
                if dropout_rate > 0.0 else None)

    dkdv_in_specs = [
        pl.BlockSpec((1, 1, block_q, D),
                     lambda b, kh, kb, g, qb: (b, kh * G + g, qb, 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, kh, kb, g, qb: (b, kh, kb, 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, kh, kb, g, qb: (b, kh, kb, 0)),
        pl.BlockSpec((1, 1, block_q, D),
                     lambda b, kh, kb, g, qb: (b, kh * G + g, qb, 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda b, kh, kb, g, qb: (b, kh * G + g, qb, 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda b, kh, kb, g, qb: (b, kh * G + g, qb, 0)),
    ]
    dkdv_operands = [q, k, v, do, lse, delta]
    if dropout_rate > 0.0:
        dkdv_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dkdv_operands.append(seed_arr)
    if has_seg:
        seg3 = segments.astype(jnp.int32)[:, :, None]
        dkdv_in_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda b, kh, kb, g, qb: (b, qb, 0)),
            pl.BlockSpec((1, block_k, 1),
                         lambda b, kh, kb, g, qb: (b, kb, 0)),
        ]
        dkdv_operands += [seg3, seg3]

    dkdv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, block_q=block_q,
                          block_k=block_k, num_q=num_q, G=G, causal=causal,
                          scale=scale, has_seg=has_seg,
                          dropout_rate=dropout_rate),
        grid=(B, KV, num_k, G, num_q),
        in_specs=dkdv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, kh, kb, g, qb: (b, kh, kb, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, kh, kb, g, qb: (b, kh, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B, KV, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        # dk/dv accumulate across the (g, qb) axes; kb tiles are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(*dkdv_operands)

    dq_in_specs = [
        pl.BlockSpec((1, 1, block_q, D),
                     lambda b, n, qb, kb: (b, n, qb, 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, n, qb, kb: (b, n // G, kb, 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, n, qb, kb: (b, n // G, kb, 0)),
        pl.BlockSpec((1, 1, block_q, D),
                     lambda b, n, qb, kb: (b, n, qb, 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda b, n, qb, kb: (b, n, qb, 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda b, n, qb, kb: (b, n, qb, 0)),
    ]
    dq_operands = [q, k, v, do, lse, delta]
    if dropout_rate > 0.0:
        dq_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dq_operands.append(seed_arr)
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, n, qb, kb: (b, qb, 0)),
            pl.BlockSpec((1, block_k, 1), lambda b, n, qb, kb: (b, kb, 0)),
        ]
        dq_operands += [seg3, seg3]
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, num_k=num_k, causal=causal,
                          scale=scale, has_seg=has_seg,
                          dropout_rate=dropout_rate),
        grid=(B, N, num_q, num_k),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, n, qb, kb: (b, n, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        # dq accumulates across k blocks only
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*dq_operands)
    return dq, dkdv[0], dkdv[1]


# default tile sizes, overridable per call
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512


def fit_block(default: int, seq: int, floor: int = 128) -> int:
    """Largest block <= default that divides seq (halving from default, so
    the result keeps the mult-of-128 lane alignment Mosaic wants). Returns 0
    if nothing >= floor divides seq — callers then run one whole-length
    block."""
    b = min(default, seq)
    while b >= floor:
        if seq % b == 0:
            return b
        b //= 2
    return 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_with_vjp(q, k, v, segments, dropout_seed, causal, interpret,
                    block_q, block_k, dropout_rate):
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out, _ = flash_attention_hmajor(qh, kh, vh, segments, dropout_seed,
                                    causal=causal, interpret=interpret,
                                    block_q=block_q, block_k=block_k,
                                    dropout_rate=dropout_rate)
    return out.transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, segments, dropout_seed, causal, interpret, block_q,
               block_k, dropout_rate):
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out, lse = flash_attention_hmajor(qh, kh, vh, segments, dropout_seed,
                                      causal=causal, interpret=interpret,
                                      block_q=block_q, block_k=block_k,
                                      dropout_rate=dropout_rate)
    return (out.transpose(0, 2, 1, 3),
            (qh, kh, vh, out, lse, segments, dropout_seed))


def _flash_bwd(causal, interpret, block_q, block_k, dropout_rate, res, g):
    qh, kh, vh, out, lse, segments, dropout_seed = res
    dq, dk, dv = flash_attention_bwd_hmajor(
        qh, kh, vh, out, lse, g.transpose(0, 2, 1, 3), segments,
        dropout_seed, causal=causal, interpret=interpret,
        block_q=block_q, block_k=block_k, dropout_rate=dropout_rate)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3), None, None)  # int operands: no cotan


_flash_with_vjp.defvjp(_flash_fwd, _flash_bwd)


def seed_from_key(rng: jax.Array) -> jax.Array:
    """Fold a jax PRNG key into the [1] int32 seed the kernel's
    counter-based mask consumes."""
    return jax.random.randint(rng, (1,), 0, jnp.iinfo(jnp.int32).max,
                              dtype=jnp.int32)


def flash_sdpa(q, k, v, *, causal: bool = True, interpret: bool = False,
               block_q: int | None = None, block_k: int | None = None,
               segment_ids=None, dropout_rate: float = 0.0,
               dropout_rng=None):
    """Drop-in sdpa_fn for modules.apply_attention: [B, S, N, D] layout in
    and out; fully differentiable — forward and backward both run as fused
    Pallas kernels (backward recomputes p per tile from the saved
    logsumexp), so neither direction materializes [S, S].

    ``segment_ids`` [B, S] masks cross-document attention for packed
    samples (reference reset_attention_mask) inside the kernel — packed
    pretraining keeps flash speed instead of falling back to the dense core.

    ``dropout_rate`` > 0 (+ ``dropout_rng``) applies attention-probability
    dropout in-kernel via a counter-based mask over global (head, qpos,
    kpos) — the reference's flash-attn dropout variant. The mask derives
    from the key, not from jax.random's threefry, so flash-dropout
    trajectories are deterministic per seed but not bit-equal to the XLA
    core's (the reference's CUDA kernel has the same property vs torch).

    Block defaults are clamped to divisors of the q / kv lengths (e.g.
    S=768 runs 256-wide k blocks even though the tuned default is 512)."""
    S, Sk = q.shape[1], k.shape[1]
    seed = None
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("flash dropout_rate > 0 needs dropout_rng")
        seed = seed_from_key(dropout_rng)
    return _flash_with_vjp(q, k, v, segment_ids, seed, causal, interpret,
                           block_q or fit_block(DEFAULT_BLOCK_Q, S) or S,
                           block_k or fit_block(DEFAULT_BLOCK_K, Sk) or Sk,
                           dropout_rate)


# the fwd + both bwd kernels mask cross-document tiles in-kernel
flash_sdpa.supports_segments = True
# in-kernel counter-based attention dropout (fwd + bwd regenerate the mask)
flash_sdpa.supports_dropout = True


def make_flash_sdpa(mesh, dp_axes=(), tp_axes=(), *, interpret: bool = False,
                    stage_axis=None):
    """Distributed flash attention: the kernel is a custom call XLA cannot
    auto-partition, so it runs under shard_map — batch sharded over dp,
    heads over tp, sequence local (attention needs the full sequence; cp
    layers use ring attention instead). Grad flows through the fused VJP
    inside the shard_map. ``segment_ids`` [B, S] ride as an extra batch-
    sharded operand so packed documents keep flash speed under SPMD.
    ``dropout_rate`` > 0 runs the in-kernel counter-based dropout; each
    shard folds its (dp, tp) mesh coordinates into the seed so masks
    decorrelate across the sharded batch/head dims.

    Block sizes follow ``flash_sdpa``: the tuned defaults clamped to
    divisors of the q / kv lengths, else one whole-length block. There is
    no fallback to the XLA core — a shape Mosaic refuses raises at compile
    time. ``interpret`` comes only from the caller (CPU tests pass True).

    ``stage_axis`` (the compiled 1F1B engine): q/k/v carry a leading
    ``[pp, ...]`` stacked stage dim sharded on that mesh axis; the
    shard_map spans the WHOLE mesh (pp included, full-manual) and each pp
    row runs its own stage's attention — this is how the Pallas kernel
    nests inside the fused single-program pipeline. ``dropout_rng`` is
    then a ``[pp]`` key array (one per stage lane, matching the host
    engine's per-(microbatch, stage) keys)."""
    from jax.sharding import PartitionSpec as P

    import jax

    spec = P(dp_axes or None, None, tp_axes or None, None)
    seg_spec = P(dp_axes or None, None)
    seed_spec = P()
    s_dim = 1
    if stage_axis is not None:
        spec = P(stage_axis, *spec)
        seg_spec = P(stage_axis, *seg_spec)
        seed_spec = P(stage_axis, None)
        s_dim = 2

    def _shard_seed(seed):
        idx = jnp.int32(0)
        for ax in tuple(dp_axes) + tuple(tp_axes):
            idx = idx * jnp.int32(mesh.shape[ax]) + jax.lax.axis_index(ax)
        return seed + idx * jnp.int32(-1640531527)  # 2654435761 as int32

    def sdpa(q, k, v, *, causal=True, segment_ids=None,
             dropout_rate: float = 0.0, dropout_rng=None):
        # a length no lane-aligned block divides runs as ONE whole-length
        # block (a block equal to the array dim satisfies Mosaic's tiling
        # rule); what then overflows VMEM fails at compile time — there is
        # no XLA core behind the kernel to hide it
        S, Sk = q.shape[s_dim], k.shape[s_dim]
        bq = fit_block(DEFAULT_BLOCK_Q, S) or S
        bk = fit_block(DEFAULT_BLOCK_K, Sk) or Sk
        seed = None
        if dropout_rate > 0.0:
            if dropout_rng is None:
                raise ValueError("flash dropout_rate > 0 needs dropout_rng")
            if stage_axis is not None:
                # one independent counter stream per stage lane
                seed = jax.vmap(seed_from_key)(dropout_rng)
            else:
                seed = seed_from_key(dropout_rng)

        # one shard_map over a dynamic operand list; the optional operands
        # are rebuilt into keywords inside (custom_vjp args stay positional)
        has_seg, has_seed = segment_ids is not None, seed is not None
        in_specs = [spec, spec, spec]
        operands = [q, k, v]
        if has_seg:
            in_specs.append(seg_spec)
            operands.append(segment_ids)
        if has_seed:
            in_specs.append(seed_spec)
            operands.append(seed)

        def local(a, b, c, *rest):
            s = rest[0] if has_seg else None
            sd = _shard_seed(rest[-1]) if has_seed else None
            return _flash_with_vjp(a, b, c, s, sd, causal, interpret,
                                   bq, bk, dropout_rate)

        from jax.experimental.shard_map import shard_map

        from hetu_galvatron_tpu.ops.overlap import staged_lane

        # each pp row holds its stage's [1, ...] lane (the shared
        # compiled-engine adapter squeezes it around the kernel)
        local = staged_lane(local, stage_axis is not None)

        fn = shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=spec, check_rep=False)
        return fn(*operands)

    sdpa.supports_segments = True
    sdpa.supports_dropout = True
    return sdpa
