"""Pallas kernels for the chunked gated delta rule with a decay a channel
(Kimi Delta Attention; ``modules.kda_chunked`` is the same mathematics in
``jax.numpy``, its docstring the equations).

Per batch row and head, in chunks of ``C`` positions, with ``G`` the running
sum of the log decay inside a chunk and ``S`` [d, dv] the state entering it::

    Aqk_ij = sum_c q_ic k_jc exp(G_ic - G_jc)  (j <= i),  Akk likewise
    T  = (I + strict(Akk) * beta_i)^-1 Diag(beta)
    W  = T (K * exp(G)),  U = T V,  V' = U - W S
    o  = (Q * exp(G)) S + Aqk V'
    S' = Diag(exp(G_last)) S + (K * exp(G_last - G))^T V'

In ``jax.numpy`` the element-by-element decays of the diagonal sub-blocks
are ``[chunks, heads, 4, 16, 16, 128]`` float32 arrays in HBM, the inverse
some thousand small instructions, and ``W``, ``U``, the pair matrix, the
decayed ``q`` and ``k`` and the state go through HBM at each of a scan's
steps. Here the chunks of a sequence are the innermost, sequential grid
axis and the state of every held head stays in VMEM scratch from one to the
next, held transposed (``[dv, d]``: a decay a channel is then a row over
lanes, and its cotangent a sum over sublanes). Nothing of a chunk's
intermediates reaches HBM.

Layout. The operands come as the model has them, ``[B, S, H, d]`` (a head
is a sublane of an ``[H, d]`` tile; merging ``H`` into the lanes outside
would be a pass over HBM for every operand and result). A grid step holds
one chunk of ``hb`` heads: it first copies each head's ``[C, d]`` rows out
of the block (a strided read a head), then loops over PACKS of ``P = 128 /
C`` heads. Whatever is ``C x C`` a head (the pair matrices, the inverse,
``T`` and their cotangents) is held for a pack side by side along the
lanes, ``[C, P * C]`` = ``[C, 128]``: whole vector registers where one
head would fill half of each at ``C`` 64, the forward substitution's steps
taken once for every sub-block of every head of the pack, and the
inverse's block products and their cotangents' one full-width product for
the pack (against the block-diagonal ``[128, 128]`` of the other factor)
where a head alone would leave three quarters of the MXU idle. A head's
product with its ``[C, d]`` operands takes the pack's matrix with the other
heads' lanes zeroed against the heads' operands stacked along the rows.

The pair matrices are ``kda_pairs``'s scheme exactly: a chunk is cut into
sub-blocks of ``SUB`` positions; inside one the decay is taken element by
element, one key position at a time over the sub-block's rows, masked
BEFORE the exp; a row sub-block against earlier keys goes through its
reference point ``R_I`` (the sum before its first position), both exponents
<= 0, matmul operands in the compute dtype. No ``exp(-G)`` over a chunk.
The inverse is forward substitution on the diagonal sub-blocks (rank-one
updates on the VPU, a sub-block's column brought to the lanes that need it
by rotations every column shares), then joined in pairs by block products
on the MXU with float32 operands at full precision, as
``modules.unit_lower_inverse``.

``G`` is made outside the kernels by one ``jnp.cumsum`` a chunk (float32)
and differentiated by JAX; ``beta`` comes twice, as columns ``[B, chunks, H
/ P, C, P]`` (``beta_i`` along sublanes) and as a pack's row ``[B, chunks,
H / P, 1, P * C]`` (``beta_j`` along lanes), and the kernels return a
cotangent for each.

The forward that is differentiated also writes the state that entered each
chunk (float32, ``[B, chunks, H, dv, d]``), and names it and the output
(``KEPT``): ``modules.remat`` keeps what carries those names, so a block's
recomputed forward runs no scan kernel. The backward runs the chunks in
reverse with the state's cotangent in VMEM, reads those states, and makes
the pair matrices, the inverse, ``W``, ``U`` and ``V'`` again from the
inputs, as the flash backward makes its scores again. The inverse's
cotangent is ``-X^T g X^T`` on the strict triangle. The decay's gradient
through the pair matrices needs no reduction of its own: ``dG = q * dq + k
* dk_as_row - k * dk_as_key`` element by element, and a reference point's
cancels.

Arithmetic is ``kda_chunked``'s: ``G``, every exp, the diagonal
sub-blocks, the inverse, the carried state and every accumulator float32;
the matmul operands (``T``, ``W``, the pair matrix, the decayed ``q`` and
``k``, ``V'``, the state where it is read, the cotangents) in the inputs'
dtype with float32 accumulation.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_galvatron_tpu.ops.pallas.common import LANES, batch_spec, on_shards
from hetu_galvatron_tpu.ops.pallas.flash_attention import _NN, _NT, _dot
from hetu_galvatron_tpu.ops.pallas.ssd import _TN

# positions of a sub-block (``modules.KDA_SUB``) and of a float32 sublane
# tile: a key position in a sub-block's second tile leaves the first masked
SUB = 16
_TILE = 8
# heads a grid step holds where a model has more: a whole tile of a
# two-byte ``[H, d]`` operand's sublanes (fewer heads are held all)
HEADS_A_STEP = 16
# bytes of VMEM the kernels are given, and the most a call's blocks
# (double-buffered), its copies a head and its state may take of them; what
# the compiler keeps of a pack's intermediates comes on top
VMEM_LIMIT = 64 * 2 ** 20
VMEM_BYTES = 32 * 2 ** 20
# the scope every call of this file is traced under, forward and backward:
# a backward rule does not inherit the scope its forward was called in
# (``observability/trace_analysis.KDA_SCAN_SCOPE`` is the same words)
SCOPE = "mixer/kda/scan"
# ``checkpoint_name``s of the differentiated forward's two results, the
# output the block goes on with and the states the backward kernel reads;
# ``modules.remat`` keeps the values under the names of ``KEPT``
KEPT_OUT = "kda_scan_out"
KEPT_STATES = "kda_scan_states"
KEPT = (KEPT_OUT, KEPT_STATES)
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def tile_plan(chunk: int, heads: int, d: int, dv: int
              ) -> Optional[Tuple[int, int]]:
    """(heads a grid step holds, heads a pack) where the kernels' tiles fit
    these shapes, else None (the caller keeps the ``jax.numpy`` form): the
    chunk a power of two of ``SUB``-row sub-blocks within one lane tile,
    whose packs fill it; ``d`` and ``dv`` whole lane tiles; the heads a
    whole number of packs and of steps, a step's blocks, copies and state
    inside ``VMEM_BYTES``."""
    nb = chunk // SUB
    if chunk % SUB or chunk > LANES or nb & (nb - 1):
        return None
    if d < LANES or dv < LANES or d % LANES or dv % LANES:
        return None
    pack = LANES // chunk
    hb = min(heads, HEADS_A_STEP)
    if heads % hb or hb % pack:
        return None
    # the backward's: q, k, dq, dk in two bytes at least, v, dv, G, dG and
    # do in four, the entering state, twice each; float32 copies of q, k, G,
    # dq, dk, dG, v, dv, do; the carried cotangent
    blocks = chunk * hb * (4 * 2 * d + 2 * 2 * dv + 2 * 4 * d + 4 * dv)
    copies = chunk * hb * 4 * (6 * d + 3 * dv)
    state = 4 * hb * d * dv
    if 2 * (blocks + state) + copies + state > VMEM_BYTES:
        return None
    return hb, pack


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _dot32(a, b, dims):
    """A product of float32 operands at full precision (the MXU's default
    would round them to bfloat16)."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=_F32)


def _rows(parts):
    """Heads' operands stacked along the rows."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


class _Pack:
    """The lanes of a pack's ``[.., P * C]`` matrices: which head a lane
    is, its position in the head's chunk and that position's sub-block."""

    def __init__(self, C: int, P: int):
        self.C, self.P, self.W = C, P, P * C
        self.shift = C.bit_length() - 1     # (Mosaic has no vector division)

    def lane(self, rows: int):
        return _iota((rows, self.W), 1)

    def head(self, rows: int, s: int):
        """The lanes of head ``s``."""
        return (self.lane(rows) >> self.shift) == s

    def at(self, rows: int):
        """A lane's position in its head's chunk."""
        return self.lane(rows) & (self.C - 1)

    def sub_block(self, rows: int):
        """The sub-block of a lane's position."""
        return self.at(rows) >> (SUB.bit_length() - 1)

    def head_rows(self, M, s: int):
        """Head ``s``'s rows of ``M`` [W, ..], the heads stacked."""
        return M[s * self.C:(s + 1) * self.C]

    def own_lanes(self, parts):
        """Head ``s``'s lanes of ``parts[s]`` [rows, W], for every head."""
        out = parts[0]
        for s in range(1, self.P):
            out = jnp.where(self.head(out.shape[0], s), parts[s], out)
        return out

    def heads_own(self, M):
        """``M`` [C, W] as the block-diagonal [W, W]: head ``s``'s lanes in
        head ``s``'s rows, zero elsewhere."""
        return _rows([jnp.where(self.head(M.shape[0], s), M,
                                jnp.zeros_like(M)) for s in range(self.P)])

    def diagonal_blocks(self, M):
        """The diagonal sub-blocks of ``M`` [C, W], every head's: [SUB, W],
        row ``i`` and lane ``(s, I, j)`` from ``M[(I, i), (s, I, j)]``."""
        block = self.sub_block(SUB)
        out = jnp.zeros((SUB, self.W), M.dtype)
        for I in range(self.C // SUB):
            out = jnp.where(block == I, M[I * SUB:(I + 1) * SUB], out)
        return out

    def from_diagonal_blocks(self, D, below=None):
        """``diagonal_blocks`` undone: [C, W] with ``D``'s sub-blocks on
        the diagonal, ``below[I]`` [SUB, W] to their left in row sub-block
        ``I`` and zero to their right."""
        block = self.sub_block(SUB)
        out = []
        for I in range(self.C // SUB):
            rest = jnp.zeros_like(D)
            if below is not None and I:
                rest = jnp.where(block < I, below[I], rest)
            out.append(jnp.where(block == I, D, rest))
        return _rows(out)


def _sub_block_decay(GI, j: int):
    """``exp(G_i - G_j)`` for key ``j`` of a sub-block over the rows ``i >=
    j`` of its sublane tiles that hold any (masked BEFORE the exp: above
    the diagonal the exponent is positive and may overflow); and the first
    of those rows."""
    lo = (j // _TILE) * _TILE
    rows = _iota((SUB - lo, GI.shape[1]), 0) + lo
    return jnp.exp(jnp.where(rows >= j, GI[lo:] - GI[j:j + 1],
                             -jnp.inf)), lo


def _below(t, lo: int):
    """Rows from ``lo`` on of a sub-block, zero above."""
    if not lo:
        return t
    return jnp.concatenate([jnp.zeros((lo, t.shape[1]), t.dtype), t], axis=0)


def _reference(G, r0: int):
    """Row sub-block ``r0 // SUB``'s factors through its reference point:
    its rows' ``exp(G_i - R)`` and every key's ``exp(min(R - G_j, 0))`` (a
    key at or after the sub-block's first position belongs to none of its
    products and is masked by the caller)."""
    R = G[r0 - 1:r0]
    return jnp.exp(G[r0:r0 + SUB] - R), jnp.exp(jnp.minimum(R - G, 0.0))


def _through_references(pk: _Pack, q, k, G, r0: int, cd):
    """Row sub-block ``r0 // SUB`` of every head against every key through
    the head's reference point: the rows' factors a head, the products'
    left operand (rows ``(s, q | k, i)``) and right operand (rows ``(s,
    j)``) in the compute dtype, and the keys' factors stacked."""
    rows, lhs, keys, to_keys = [], [], [], []
    for s in range(pk.P):
        r, t = _reference(G[s], r0)
        rows.append(r)
        to_keys.append(t)
        lhs += [q[s][r0:r0 + SUB] * r, k[s][r0:r0 + SUB] * r]
        keys.append(k[s] * t)
    return (rows, _rows(lhs).astype(cd), _rows(keys).astype(cd),
            _rows(to_keys))


def _pairs(pk: _Pack, q, k, G, cd):
    """The two pair matrices of a pack ``[C, W]`` float32, zero above the
    diagonal (``modules.kda_pairs``)."""
    C = pk.C
    lane = pk.lane(SUB)
    dq = dk = jnp.zeros((SUB, pk.W), _F32)
    for s in range(pk.P):
        for r0 in range(0, C, SUB):
            GI, qI, kI = (t[s][r0:r0 + SUB] for t in (G, q, k))
            for j in range(SUB):
                dec, lo = _sub_block_decay(GI, j)
                kd = kI[j:j + 1] * dec
                at = lane == s * C + r0 + j
                dq = jnp.where(at, _below(jnp.sum(
                    qI[lo:] * kd, axis=1, keepdims=True), lo), dq)
                dk = jnp.where(at, _below(jnp.sum(
                    kI[lo:] * kd, axis=1, keepdims=True), lo), dk)
    below_q, below_k = [None], [None]
    for r0 in range(SUB, C, SUB):
        _, lhs, keys, _ = _through_references(pk, q, k, G, r0, cd)
        off = _dot(lhs, keys, _NT)                      # [P 2 SUB, W]
        for part, below in enumerate((below_q, below_k)):
            below.append(pk.own_lanes(
                [off[(2 * s + part) * SUB:(2 * s + part + 1) * SUB]
                 for s in range(pk.P)]))
    return (pk.from_diagonal_blocks(dq, below_q),
            pk.from_diagonal_blocks(dk, below_k))


def _pairs_bwd(pk: _Pack, q, k, G, dAq, dAk, cd):
    """The pair matrices' cotangents (``dAq`` zero above the diagonal,
    ``dAk`` on and above it) to ``q``, to ``k`` as a row of ``Akk`` and to
    ``k`` as a key of both: three ``[C, d]`` float32 a head. ``G``'s follows
    from them (the module's docstring)."""
    C, P = pk.C, pk.P
    d = G[0].shape[1]
    row = _iota((SUB, d), 0)
    block = pk.sub_block(SUB)
    dq = [[] for _ in range(P)]
    dk_row = [[] for _ in range(P)]
    diag = [[] for _ in range(P)]
    dk_key = jnp.zeros((pk.W, d), _F32)
    for r0 in range(0, C, SUB):
        gq, gk = dAq[r0:r0 + SUB], dAk[r0:r0 + SUB]
        if r0:
            rows, lhs, keys, to_keys = _through_references(pk, q, k, G, r0,
                                                           cd)
            g = []
            for s in range(P):
                earlier = pk.head(SUB, s) & (block < r0 // SUB)
                g += [jnp.where(earlier, gq, 0.0),
                      jnp.where(earlier, gk, 0.0)]
            g = _rows(g).astype(cd)                         # [P 2 SUB, W]
            dlhs = _dot(g, keys, _NN)                       # [P 2 SUB, d]
            dk_key = dk_key + _dot(g, lhs, _TN) * to_keys    # [W, d]
        for s in range(P):
            GI, qI, kI = (t[s][r0:r0 + SUB] for t in (G, q, k))
            dqI = dkI = dkeyI = jnp.zeros(GI.shape, _F32)
            for j in range(SUB):
                dec, lo = _sub_block_decay(GI, j)
                at = s * C + r0 + j
                cq, ck = gq[lo:, at:at + 1], gk[lo:, at:at + 1]
                kd = kI[j:j + 1] * dec
                dqI = dqI + _below(cq * kd, lo)
                dkI = dkI + _below(ck * kd, lo)
                dkeyI = jnp.where(row == j, jnp.sum(
                    (cq * qI[lo:] + ck * kI[lo:]) * dec, axis=0,
                    keepdims=True), dkeyI)
            if r0:
                dqI = dqI + dlhs[2 * s * SUB:(2 * s + 1) * SUB] * rows[s]
                dkI = dkI + dlhs[(2 * s + 1) * SUB:(2 * s + 2) * SUB] * rows[s]
            dq[s].append(dqI)
            dk_row[s].append(dkI)
            diag[s].append(dkeyI)
    return ([_rows(t) for t in dq], [_rows(t) for t in dk_row],
            [pk.head_rows(dk_key, s) + _rows(diag[s]) for s in range(P)])


def _columns_over_their_sub_blocks(D):
    """``D`` [SUB, W], strictly lower in every group of ``SUB`` lanes ->
    for each ``r`` under ``SUB - 1``, lane ``r`` of every group at the
    group's lanes ``t <= r`` (the rest of a group's lanes hold other
    entries of ``D``: the substitution multiplies them by zeros). A lane at
    ``t`` takes the lane ``r - t`` to its right: ``SUB`` rotations of ``D``
    shared by every ``r``, and a select a pair ``(r, t)``."""
    width = D.shape[1]
    t = _iota(D.shape, 1) & (SUB - 1)
    left = [D] + [pltpu.roll(D, width - by, 1) for by in range(1, SUB)]
    out = []
    for r in range(SUB - 1):
        col = left[r]                                   # for t = 0
        for at in range(1, r + 1):
            col = jnp.where(t == at, left[r - at], col)
        out.append(col)
    return out


def _inverse(pk: _Pack, N):
    """``(I + N_s)^-1`` for every head ``s`` of a pack, ``N`` [C, W]
    float32 and strictly lower a head (``modules.unit_lower_inverse``): the
    diagonal sub-blocks of every head at once by forward substitution, each
    finished row taken off the rows below it; then joined in pairs, ``[[X1,
    0], [-X2 N21 X1, X2]]``, all pairs of a level and all heads in two
    products of the pack's matrices."""
    C = pk.C
    D = pk.diagonal_blocks(N)
    X = ((pk.lane(SUB) & (SUB - 1)) == _iota((SUB, pk.W), 0)).astype(_F32)
    for r, col in enumerate(_columns_over_their_sub_blocks(D)):
        X = X - col * X[r:r + 1]
    X = pk.from_diagonal_blocks(X)
    m = SUB     # positions a joined block holds, a power of two
    while m < C:
        # the lower-left block of each pair of blocks (shifts: Mosaic has
        # no vector division)
        pair_row = _iota((C, pk.W), 0) >> (m.bit_length() - 1)
        pair_col = pk.at(C) >> (m.bit_length() - 1)
        lower_left = ((pair_row & 1) == 1) & (pair_col == pair_row - 1)
        XN = _dot32(X, pk.heads_own(jnp.where(lower_left, N, 0.0)), _NN)
        X = X - _dot32(XN, pk.heads_own(X), _NN)
        m *= 2
    return X


def _chunk(pk: _Pack, q, k, v, G, bcol, brow, St):
    """What a chunk's forward makes of a pack's inputs (lists a head: ``q``,
    ``k``, ``G`` [C, d] float32, ``v`` [C, dv], ``bcol`` [C, 1], ``St`` [dv,
    d] float32 the state entering; ``brow`` [1, W])."""
    cd = v[0].dtype
    C, P = pk.C, pk.P
    Aqk, Akk = _pairs(pk, q, k, G, cd)
    strict = pk.at(C) < _iota((C, pk.W), 0)
    beta_i = pk.own_lanes([jnp.broadcast_to(b, (C, pk.W)) for b in bcol])
    X = _inverse(pk, jnp.where(strict, Akk, 0.0) * beta_i)
    T = pk.heads_own((X * brow).astype(cd))                     # [W, W]
    A = pk.heads_own(Aqk.astype(cd))
    eG = [jnp.exp(g) for g in G]
    last = [g[C - 1:C] for g in G]
    to_end = [jnp.exp(e - g) for e, g in zip(last, G)]
    kg = _rows([(a * e).astype(cd) for a, e in zip(k, eG)])     # [W, d]
    vs = _rows(v)
    sb = [s.astype(cd) for s in St]
    Ts = [pk.head_rows(T, s) for s in range(P)]
    W = [_dot(t, kg, _NN).astype(cd) for t in Ts]
    vc = [(_dot(t, vs, _NN) - _dot(w, s, _NT)).astype(cd)       # V'
          for t, w, s in zip(Ts, W, sb)]
    return dict(
        Akk=Akk, X=X, T=T, A=A, eG=eG, to_end=to_end, kg=kg, vs=vs,
        W=W, sb=sb, vc=vc, beta_i=beta_i, strict=strict,
        decay=[jnp.exp(e) for e in last],
        q_in=[(a * e).astype(cd) for a, e in zip(q, eG)],
        k_out=[(a * e).astype(cd) for a, e in zip(k, to_end)])


def _copy_heads_in(hb: int, pairs):
    """Each head's ``[C, d]`` rows of a step's blocks ``[1, C, hb, d]``
    into scratch ``[hb, C, d]``, where a pack is found by its index."""
    for ref, scratch in pairs:
        for h in range(hb):
            scratch[h] = ref[0, :, h, :].astype(scratch.dtype)


def _copy_heads_out(hb: int, pairs):
    for scratch, ref in pairs:
        for h in range(hb):
            ref[0, :, h, :] = scratch[h].astype(ref.dtype)


def _pack_inputs(pk: _Pack, p, qs, ks, vs, gs, cols_ref, rows_ref):
    heads = [p * pk.P + s for s in range(pk.P)]
    cols = cols_ref[0, 0, p]                                   # [C, P]
    return (heads, [qs[h] for h in heads], [ks[h] for h in heads],
            [vs[h] for h in heads], [gs[h] for h in heads],
            [cols[:, s:s + 1] for s in range(pk.P)], rows_ref[0, 0, p])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, cols_ref, rows_ref, o_ref, *rest,
                hb: int, P: int, keep_states: bool):
    enter_ref, qs, ks, vs, gs, os, s_ref = (
        rest if keep_states else (None,) + rest)
    pk = _Pack(g_ref.shape[1], P)

    @pl.when(pl.program_id(2) == 0)
    def _init():    # zero before the sequence
        s_ref[...] = jnp.zeros_like(s_ref)

    _copy_heads_in(hb, ((q_ref, qs), (k_ref, ks), (v_ref, vs), (g_ref, gs)))

    def pack(p, carry):
        heads, q, k, v, G, bcol, brow = _pack_inputs(
            pk, p, qs, ks, vs, gs, cols_ref, rows_ref)
        St = [s_ref[h] for h in heads]
        if keep_states:
            for h, s in zip(heads, St):
                enter_ref[0, 0, h] = s
        c = _chunk(pk, q, k, v, G, bcol, brow, St)
        for s, h in enumerate(heads):
            os[h] = (_dot(c["q_in"][s], c["sb"][s], _NT)
                     + _dot(pk.head_rows(c["A"], s), _rows(c["vc"]), _NN))
            s_ref[h] = c["decay"][s] * St[s] + _dot(c["vc"][s],
                                                    c["k_out"][s], _TN)
        return carry

    jax.lax.fori_loop(0, hb // P, pack, 0)
    _copy_heads_out(hb, ((os, o_ref),))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, cols_ref, rows_ref, enter_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dcols_ref, drows_ref,
                qs, ks, vs, gs, dos, dqs, dks, dvs, dgs, ds_ref,
                *, hb: int, P: int):
    C, d = g_ref.shape[1], g_ref.shape[3]
    pk = _Pack(C, P)

    @pl.when(pl.program_id(2) == 0)
    def _init():    # nothing reads the state the last chunk leaves
        ds_ref[...] = jnp.zeros_like(ds_ref)

    _copy_heads_in(hb, ((q_ref, qs), (k_ref, ks), (v_ref, vs), (g_ref, gs),
                        (do_ref, dos)))
    on_or_below = pk.at(C) <= _iota((C, pk.W), 0)
    last_row = _iota((C, d), 0) == C - 1
    same_head = (_iota((pk.W, pk.W), 0) >> pk.shift
                 == pk.lane(pk.W) >> pk.shift)

    def pack(p, carry):
        heads, q, k, v, G, bcol, brow = _pack_inputs(
            pk, p, qs, ks, vs, gs, cols_ref, rows_ref)
        cd = v[0].dtype
        St = [enter_ref[0, 0, h] for h in heads]
        c = _chunk(pk, q, k, v, G, bcol, brow, St)
        dob = [dos[h] for h in heads]
        dSt = [ds_ref[h] for h in heads]
        dSb = [t.astype(cd) for t in dSt]
        vc_all = _rows(c["vc"])
        # o = q_in S + Aqk V';  S' = decay S + k_out^T V'
        dq_in = [_dot(a, s, _NN) for a, s in zip(dob, c["sb"])]     # [C, d]
        dAqk = jnp.where(on_or_below, pk.own_lanes(
            [_dot(a, vc_all, _NT) for a in dob]), 0.0)
        dvc = (_dot(c["A"], _rows(dob), _TN) + _rows(
            [_dot(a, s, _NT) for a, s in zip(c["k_out"], dSb)])).astype(cd)
        dvc = [pk.head_rows(dvc, s) for s in range(P)]             # [C, dv]
        dk_out = [_dot(a, s, _NN) for a, s in zip(c["vc"], dSb)]    # [C, d]
        ddecay = [jnp.sum(a * s, axis=0, keepdims=True)             # [1, d]
                  for a, s in zip(dSt, St)]
        # V' = U - W S;  W = T (K exp G);  U = T V
        dW = [(-_dot(a, s, _NN)).astype(cd)                         # [C, d]
              for a, s in zip(dvc, c["sb"])]
        for s, h in enumerate(heads):
            ds_ref[h] = (c["decay"][s] * dSt[s]
                         + _dot(dob[s], c["q_in"][s], _TN)
                         - _dot(dvc[s], c["W"][s], _TN))
        dT = pk.own_lanes([_dot(a, c["kg"], _NT) + _dot(b, c["vs"], _NT)
                           for a, b in zip(dW, dvc)])               # [C, W]
        dkg = _dot(c["T"], _rows(dW), _TN)                          # [W, d]
        dv_all = _dot(c["T"], _rows(dvc), _TN)                      # [W, dv]
        # T = X Diag(beta);  X = (I + strict(Akk) * beta_i)^-1
        X = c["X"]
        drows_ref[0, 0, p] = jnp.sum(dT * X, axis=0, keepdims=True)
        bar = jnp.where(same_head, _dot32(X, dT * brow, _TN), 0.0)  # [W, W]
        bar = _dot32(bar, pk.heads_own(X), _NT)
        dN = jnp.where(c["strict"],
                       -sum(pk.head_rows(bar, s) for s in range(P)), 0.0)
        on_pairs = dN * c["Akk"]
        for s in range(P):
            dcols_ref[0, 0, p, :, s:s + 1] = jnp.sum(
                jnp.where(pk.head(C, s), on_pairs, 0.0), axis=1,
                keepdims=True)
        dq_pair, dk_row, dk_key = _pairs_bwd(pk, q, k, G, dAqk,
                                             dN * c["beta_i"], cd)
        for s, h in enumerate(heads):
            eG, to_end = c["eG"][s], c["to_end"][s]
            dq = dq_in[s] * eG + dq_pair[s]
            # with G_i, and against it, through k
            rises = pk.head_rows(dkg, s) * eG + dk_row[s]
            falls = dk_out[s] * to_end + dk_key[s]
            dqs[h] = dq
            dks[h] = rises + falls
            dvs[h] = pk.head_rows(dv_all, s)
            # the last position's sum is in every key's ``exp(G_last -
            # G_j)`` and in the state's decay
            to_last = (jnp.sum(k[s] * dk_out[s] * to_end, axis=0,
                               keepdims=True)
                       + ddecay[s] * c["decay"][s])
            dgs[h] = (q[s] * dq + k[s] * (rises - falls)
                      + jnp.where(last_row, to_last, 0.0))
        return carry

    jax.lax.fori_loop(0, hb // P, pack, 0)
    _copy_heads_out(hb, ((dqs, dq_ref), (dks, dk_ref), (dvs, dv_ref),
                         (dgs, dg_ref)))


def _specs(nC: int, C: int, d: int, dv: int, hb: int, P: int,
           reverse: bool):
    at = (lambda c: nC - 1 - c) if reverse else (lambda c: c)
    wide = pl.BlockSpec((1, C, hb, d), lambda b, g, c: (b, at(c), g, 0))
    wide_v = pl.BlockSpec((1, C, hb, dv), lambda b, g, c: (b, at(c), g, 0))
    cols = pl.BlockSpec((1, 1, hb // P, C, P),
                        lambda b, g, c: (b, at(c), g, 0, 0))
    rows = pl.BlockSpec((1, 1, hb // P, 1, P * C),
                        lambda b, g, c: (b, at(c), g, 0, 0))
    states = pl.BlockSpec((1, 1, hb, dv, d),
                          lambda b, g, c: (b, at(c), g, 0, 0))
    return wide, wide_v, cols, rows, states


# the chunk axis is innermost and sequential: it carries the state
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)


def _shapes(q, v, cols):
    B, S, H, d = q.shape
    nC, _, C, P = cols.shape[1:]
    hb, _ = tile_plan(C, H, d, v.shape[-1])
    return B, S, H, nC, C, hb, P, d, v.shape[-1]


# (jitted, as the flash kernels' calls are: blocks of one shape share one
# trace of the call, the kernel's body included, which is most of what
# tracing a KDA block costs)
@functools.partial(jax.jit, static_argnames=("interpret", "keep_states"))
def _scan_call(q, k, v, G, cols, rows, interpret: bool, keep_states: bool):
    B, S, H, nC, C, hb, P, d, dv = _shapes(q, v, cols)
    wide, wide_v, cols_at, rows_at, states = _specs(nC, C, d, dv, hb, P,
                                                    reverse=False)
    o_shape = jax.ShapeDtypeStruct((B, S, H, dv), _F32)
    kept = jax.ShapeDtypeStruct((B, nC, H, dv, d), _F32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, P=P, keep_states=keep_states),
        grid=(B, H // hb, nC),
        in_specs=[wide, wide, wide_v, wide, cols_at, rows_at],
        out_specs=[wide_v, states] if keep_states else wide_v,
        out_shape=[o_shape, kept] if keep_states else o_shape,
        scratch_shapes=[pltpu.VMEM((hb, C, d), _F32),          # q, k
                        pltpu.VMEM((hb, C, d), _F32),
                        pltpu.VMEM((hb, C, dv), v.dtype),
                        pltpu.VMEM((hb, C, d), _F32),          # G
                        pltpu.VMEM((hb, C, dv), _F32),         # o
                        pltpu.VMEM((hb, dv, d), _F32)],        # the state
        compiler_params=_PARAMS,
        interpret=interpret,
        # the kernels' instruction names on a trace's ``XLA Ops`` line
        name="kda_scan_fwd",
    )(q, k, v, G, cols, rows)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_bwd_call(q, k, v, G, cols, rows, entering, do, interpret: bool):
    B, S, H, nC, C, hb, P, d, dv = _shapes(q, v, cols)
    wide, wide_v, cols_at, rows_at, states = _specs(nC, C, d, dv, hb, P,
                                                    reverse=True)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    wide_f32 = lambda n: pltpu.VMEM((hb, C, n), _F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, P=P),
        grid=(B, H // hb, nC),
        in_specs=[wide, wide, wide_v, wide, cols_at, rows_at, states,
                  wide_v],
        out_specs=[wide, wide, wide_v, wide, cols_at, rows_at],
        out_shape=[like(q), like(k), like(v), like(G), like(cols),
                   like(rows)],
        scratch_shapes=[wide_f32(d), wide_f32(d),               # q, k
                        pltpu.VMEM((hb, C, dv), v.dtype),
                        wide_f32(d),                            # G
                        pltpu.VMEM((hb, C, dv), v.dtype),       # do
                        wide_f32(d), wide_f32(d), wide_f32(dv),
                        wide_f32(d),                            # dq .. dG
                        pltpu.VMEM((hb, dv, d), _F32)],         # dS
        compiler_params=_PARAMS,
        interpret=interpret,
        name="kda_scan_bwd",
    )(q, k, v, G, cols, rows, entering, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(q, k, v, G, cols, rows, interpret):
    return _scan_call(q, k, v, G, cols, rows, interpret, keep_states=False)


def _scan_fwd(q, k, v, G, cols, rows, interpret):
    o, entering = _scan_call(q, k, v, G, cols, rows, interpret,
                             keep_states=True)
    # the pair per-layer remat keeps (``modules.remat``), as the kernel
    # wrote them: a block's recomputed forward then holds no scan kernel
    o = checkpoint_name(o, KEPT_OUT)
    entering = checkpoint_name(entering, KEPT_STATES)
    return o, (q, k, v, G, cols, rows, entering)


def _scan_bwd(interpret, res, do):
    with jax.named_scope(SCOPE):
        return tuple(_scan_bwd_call(*res, do, interpret))


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, chunk: int, *,
             interpret: bool = False) -> jax.Array:
    """``modules.kda_chunked`` for shapes that fit :func:`tile_plan`: ``q``,
    ``k`` [B, S, H, d] and ``v`` [B, S, H, dv] in the compute dtype, ``g``
    [B, S, H, d] and ``beta`` [B, S, H] float32, ``S`` a multiple of
    ``chunk`` -> ``o`` [B, S, H, dv] float32, differentiable in all five.
    ``interpret`` comes only from the caller."""
    B, S, H, d = q.shape
    C, nC = chunk, S // chunk
    plan = tile_plan(C, H, d, v.shape[-1])
    if plan is None or S % C:
        raise ValueError(
            f"{S} positions in chunks of {C}, {H} heads of {d} keys and "
            f"{v.shape[-1]} values fit no tile of the kda kernels")
    P = plan[1]
    G = jnp.cumsum(g.astype(_F32).reshape(B, nC, C, H, d), axis=2)
    packs = beta.astype(_F32).reshape(B, nC, C, H // P, P)
    return _scan(q, k.astype(q.dtype), v.astype(q.dtype),
                 G.reshape(B, S, H, d),
                 jnp.swapaxes(packs, 2, 3),             # [B, nC, H / P, C, P]
                 jnp.transpose(packs, (0, 1, 3, 4, 2)).reshape(
                     B, nC, H // P, 1, P * C), interpret)


def make_kda_scan(mesh, dp_axes=(), *, interpret: bool = False):
    """The kernels on a mesh (``common.on_shards``): the batch sharded over
    dp, everything else local (a plan that cuts a kda block any other way is
    refused by name, ``eligibility.kda_plan_reason``)."""
    wide = batch_spec(4, dp_axes)

    def scan(q, k, v, g, beta, chunk):
        return on_shards(
            lambda *a: kda_scan(*a, chunk, interpret=interpret), mesh,
            (wide, wide, wide, wide, batch_spec(3, dp_axes)), wide)(
                q, k, v, g, beta)
    return scan
