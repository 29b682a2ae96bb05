"""Pallas kernels for the chunked gated delta rule with a decay a HEAD
(Gated DeltaNet; ``modules.gated_delta_chunked`` is the same mathematics in
``jax.numpy``, its docstring the equations).

Per batch row and head, in chunks of ``C`` positions, with ``G`` the running
sum of the log decay inside a chunk (one number a position) and ``S`` [dk,
dv] the state entering it::

    D_ij   = exp(G_i - G_j)  (j <= i, masked BEFORE the exp)
    Aqk_ij = (q_i . k_j) D_ij,  Akk likewise
    T  = (I + strict(Akk) * beta_i)^-1 Diag(beta)
    W  = T (K * exp(G)),  U = T V,  V' = U - W S
    o  = (Q * exp(G)) S + Aqk V'
    S' = exp(G_last) S + (K * exp(G_last - G))^T V'

In ``jax.numpy`` the inverse is some thousand small instructions, and ``W``,
``U``, the pair matrix, the decayed ``q`` and ``k`` and the state go through
HBM at each of a scan's 64 steps. Here the chunks of a sequence are the
innermost, sequential grid axis and the state of every held head stays in
VMEM scratch from one to the next. Nothing of a chunk's intermediates
reaches HBM. With one ``G`` a head and position the pair matrices are ONE
product each times the masked ``[C, C]`` decay: none of ``kda.py``'s
sub-blocks and reference points, which a decay a channel needs, and no lane
tile is asked of the widths (keys of 96 under values of 192 run as they
are: the MXU's passes are 128 deep and wide either way).

Layout. The kernels read and write head-major operands, ``[B, H, S, d]``: a
head's ``[C, d]`` rows of a chunk are then whole tiles of a block, where a
head of the model's ``[B, S, H, d]`` would be one sublane in every
position's ``[H, d]`` tile and a strided read a head and operand. The
differentiated function takes and gives the model's layout; the transposes
are XLA's, beside the producers and consumers it fuses them with, and under
the scan's scope. A grid step holds one chunk of ``hb`` heads and loops over
PACKS of ``P = 128 / C`` heads exactly as ``kda.py`` does (its ``_Pack``
and ``_inverse`` are imported, not copied): whatever is ``C x C`` a head
(the decay, the pair matrices, the inverse, ``T`` and their cotangents) is
held for a pack side by side along the lanes, ``[C, 128]``, and a head's
product with ``[C, d]`` operands takes the pack's block-diagonal ``[128,
128]`` against the heads' operands stacked along the rows.

``G`` is made outside the kernels by one ``jnp.cumsum`` a chunk (float32)
and differentiated by JAX. ``G`` and ``beta`` come twice, as columns ``[B,
chunks, H / P, C, P]`` (a position along sublanes) and as a pack's row
``[B, chunks, H / P, 1, P * C]`` (a position along lanes); the backward
returns ``G``'s cotangent whole as columns (``dG_i = q_i . dq_i + k_i .
(dk_i as a row and through exp(G_i) - dk_i as a key and through exp(G_last
- G_i))``, the last position taking what ``exp(G_last)`` collects) and
``beta``'s as both.

The forward that is differentiated also writes the state that entered each
chunk (float32, ``[B, chunks, H, dk, dv]``), and names it and the output
(``KEPT``): ``modules.remat`` keeps what carries those names, so a block's
recomputed forward runs no scan kernel. The backward runs the chunks in
reverse with the state's cotangent in VMEM, reads those states, and makes
the pair matrices, the inverse, ``W``, ``U`` and ``V'`` again from the
inputs. The inverse's cotangent is ``-X^T g X^T`` on the strict triangle.

Arithmetic is ``gated_delta_chunked``'s: ``G``, every exp, the inverse
(forward substitution on 16-row sub-blocks, joined by block products with
float32 operands at full precision), the carried state and every
accumulator float32; the matmul operands (``q``, ``k``, ``T``, ``W``, the
pair matrix, the decayed ``q`` and ``k``, ``V'``, the state where it is
read, the cotangents) in the inputs' dtype with float32 accumulation; ``o``
float32.
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
from hetu_galvatron_tpu.ops.pallas.kda import (
    SUB,
    VMEM_BYTES,
    VMEM_LIMIT,
    _F32,
    _Pack,
    _dot32,
    _inverse,
    _iota,
    _rows,
)
from hetu_galvatron_tpu.ops.pallas.ssd import _TN

# rows of a two-byte operand's sublane tile: the state's ``dk`` rows are a
# matmul operand in the compute dtype
_ROWS = 16
# the scope every call of this file is traced under, forward and backward:
# a backward rule does not inherit the scope its forward was called in
# (``observability/trace_analysis.GDN_SCAN_SCOPE`` is the same words)
SCOPE = "mixer/gdn/scan"
# ``checkpoint_name``s of the differentiated forward's two results, the
# output the block goes on with and the states the backward kernel reads;
# ``modules.remat`` keeps the values under the names of ``KEPT``
KEPT_OUT = "gdn_scan_out"
KEPT_STATES = "gdn_scan_states"
KEPT = (KEPT_OUT, KEPT_STATES)


def _lanes(n: int) -> int:
    """``n`` values along the lanes, as whole lane tiles."""
    return -(-n // LANES) * LANES


def tile_plan(chunk: int, heads: int, dk: int, dv: int
              ) -> Optional[Tuple[int, int]]:
    """(heads a grid step holds, heads a pack) where the kernels' tiles fit
    these shapes, else None (the caller keeps the ``jax.numpy`` form): the
    chunk a power of two of ``SUB``-row sub-blocks within one lane tile,
    whose packs fill it; the keys a head whole sublane tiles of a two-byte
    operand (the state's rows) and the values whole sublane tiles; the
    heads a whole number of packs. A step holds the most heads that divide
    them into whole steps of whole packs with the backward's blocks and
    state inside ``VMEM_BYTES``."""
    nb = chunk // SUB
    if chunk % SUB or chunk > LANES or nb & (nb - 1):
        return None
    if dk % _ROWS or dv % _ROWS:
        return None
    pack = LANES // chunk
    if heads % pack:
        return None
    # the backward's, a head: q, k, dq, dk and v, do, dv (four bytes at
    # most) and the entering state, twice each; the carried cotangent
    blocks = 4 * chunk * (4 * _lanes(dk) + 3 * _lanes(dv))
    state = 4 * dk * _lanes(dv)
    most = VMEM_BYTES // (2 * (blocks + state) + state)
    fits = [hb for hb in range(pack, heads + 1, pack)
            if heads % hb == 0 and hb <= most]
    return (fits[-1], pack) if fits else None


def _own(pk: _Pack, cols):
    """A column a head ``[C, 1]`` -> ``[C, W]``, each over its head's
    lanes."""
    return pk.own_lanes([jnp.broadcast_to(c, (pk.C, pk.W)) for c in cols])


def _chunk(pk: _Pack, q, k, v, gcol, grow, bcol, brow, S):
    """What a chunk's forward makes of a pack's inputs (lists a head: ``q``,
    ``k`` [C, dk] and ``v`` [C, dv] in the compute dtype, ``gcol``, ``bcol``
    [C, 1] float32, ``S`` [dk, dv] float32 the state entering; ``grow``,
    ``brow`` [1, W] the pack's)."""
    cd = v[0].dtype
    C, P = pk.C, pk.P
    row = _iota((C, pk.W), 0)
    strict = pk.at(C) < row
    # above the diagonal the exponent is positive: masked BEFORE the exp
    D = jnp.exp(jnp.where(pk.at(C) <= row, _own(pk, gcol) - grow, -jnp.inf))
    keys = _rows(k)                                             # [W, dk]
    Aqk = pk.own_lanes([_dot(t, keys, _NT) for t in q]) * D
    Akk = pk.own_lanes([_dot(t, keys, _NT) for t in k]) * D
    beta_i = _own(pk, bcol)
    X = _inverse(pk, jnp.where(strict, Akk, 0.0) * beta_i)
    T = pk.heads_own((X * brow).astype(cd))                     # [W, W]
    A = pk.heads_own(Aqk.astype(cd))
    eG = [jnp.exp(g) for g in gcol]
    last = [g[C - 1:C] for g in gcol]
    to_end = [jnp.exp(e - g) for e, g in zip(last, gcol)]
    qf, kf = ([t.astype(_F32) for t in a] for a in (q, k))
    kg = _rows([(a * e).astype(cd) for a, e in zip(kf, eG)])    # [W, dk]
    vs = _rows(v)
    sb = [s.astype(cd) for s in S]
    Ts = [pk.head_rows(T, s) for s in range(P)]
    W = [_dot(t, kg, _NN).astype(cd) for t in Ts]
    vc = [(_dot(t, vs, _NN) - _dot(w, s, _NN)).astype(cd)       # V'
          for t, w, s in zip(Ts, W, sb)]
    return dict(
        D=D, Akk=Akk, X=X, T=T, A=A, eG=eG, to_end=to_end, keys=keys,
        qf=qf, kf=kf, kg=kg, vs=vs, W=W, sb=sb, vc=vc, beta_i=beta_i,
        strict=strict,
        # (over the lanes first: Mosaic broadcasts one way at a time)
        decay=[jnp.exp(jnp.broadcast_to(e, (1, S[0].shape[1])))
               for e in last],
        q_in=[(a * e).astype(cd) for a, e in zip(qf, eG)],
        k_out=[(a * e).astype(cd) for a, e in zip(kf, to_end)])


def _pack_inputs(pk: _Pack, p, q_ref, k_ref, v_ref, gcol_ref, grow_ref,
                 bcol_ref, brow_ref):
    heads = [p * pk.P + s for s in range(pk.P)]
    gcols, bcols = gcol_ref[0, 0, p], bcol_ref[0, 0, p]         # [C, P]
    cols = lambda t: [t[:, s:s + 1] for s in range(pk.P)]
    return (heads, [q_ref[0, h] for h in heads], [k_ref[0, h] for h in heads],
            [v_ref[0, h] for h in heads], cols(gcols), grow_ref[0, 0, p],
            cols(bcols), brow_ref[0, 0, p])


def _fwd_kernel(q_ref, k_ref, v_ref, gcol_ref, grow_ref, bcol_ref, brow_ref,
                o_ref, *rest, hb: int, P: int, keep_states: bool):
    enter_ref, s_ref = rest if keep_states else (None,) + rest
    pk = _Pack(q_ref.shape[2], P)

    @pl.when(pl.program_id(2) == 0)
    def _init():    # zero before the sequence
        s_ref[...] = jnp.zeros_like(s_ref)

    def pack(p, carry):
        heads, *inputs = _pack_inputs(pk, p, q_ref, k_ref, v_ref, gcol_ref,
                                      grow_ref, bcol_ref, brow_ref)
        S = [s_ref[h] for h in heads]
        if keep_states:
            for h, s in zip(heads, S):
                enter_ref[0, 0, h] = s
        c = _chunk(pk, *inputs, S)
        vc_all = _rows(c["vc"])
        for s, h in enumerate(heads):
            o_ref[0, h] = (_dot(c["q_in"][s], c["sb"][s], _NN)
                           + _dot(pk.head_rows(c["A"], s), vc_all, _NN))
            s_ref[h] = c["decay"][s] * S[s] + _dot(c["k_out"][s],
                                                   c["vc"][s], _TN)
        return carry

    jax.lax.fori_loop(0, hb // P, pack, 0)


def _inverse_bwd(pk: _Pack, X, dX, same_head, strict):
    """The cotangent of ``N`` through ``X = (I + N)^-1``, a pack's ``[C,
    W]``: ``-X^T dX X^T`` a head, on the strict triangle."""
    bar = jnp.where(same_head, _dot32(X, dX, _TN), 0.0)         # [W, W]
    bar = _dot32(bar, pk.heads_own(X), _NT)
    return jnp.where(strict,
                     -sum(pk.head_rows(bar, s) for s in range(pk.P)), 0.0)


def _bwd_kernel(q_ref, k_ref, v_ref, gcol_ref, grow_ref, bcol_ref, brow_ref,
                enter_ref, do_ref, dq_ref, dk_ref, dv_ref, dgcol_ref,
                dbcol_ref, dbrow_ref, ds_ref, *, hb: int, P: int):
    C = q_ref.shape[2]
    pk = _Pack(C, P)

    @pl.when(pl.program_id(2) == 0)
    def _init():    # nothing reads the state the last chunk leaves
        ds_ref[...] = jnp.zeros_like(ds_ref)

    on_or_below = pk.at(C) <= _iota((C, pk.W), 0)
    last_row = _iota((C, 1), 0) == C - 1
    same_head = (_iota((pk.W, pk.W), 0) >> pk.shift
                 == pk.lane(pk.W) >> pk.shift)

    def pack(p, carry):
        heads, q, k, v, gcol, grow, bcol, brow = _pack_inputs(
            pk, p, q_ref, k_ref, v_ref, gcol_ref, grow_ref, bcol_ref,
            brow_ref)
        cd = v[0].dtype
        S = [enter_ref[0, 0, h] for h in heads]
        c = _chunk(pk, q, k, v, gcol, grow, bcol, brow, S)
        dob = [do_ref[0, h] for h in heads]
        dS = [ds_ref[h] for h in heads]
        dSb = [t.astype(cd) for t in dS]
        vc_all = _rows(c["vc"])
        # o = q_in S + Aqk V';  S' = decay S + k_out^T V'
        dq_in = [_dot(a, s, _NT) for a, s in zip(dob, c["sb"])]     # [C, dk]
        dAqk = jnp.where(on_or_below, pk.own_lanes(
            [_dot(a, vc_all, _NT) for a in dob]), 0.0)
        dvc = (_dot(c["A"], _rows(dob), _TN) + _rows(
            [_dot(a, s, _NN) for a, s in zip(c["k_out"], dSb)])).astype(cd)
        dvc = [pk.head_rows(dvc, s) for s in range(P)]             # [C, dv]
        dk_out = [_dot(a, s, _NT) for a, s in zip(c["vc"], dSb)]    # [C, dk]
        ddecay = [jnp.sum(jnp.sum(a * s, axis=1, keepdims=True), axis=0,
                          keepdims=True) for a, s in zip(dS, S)]    # [1, 1]
        # V' = U - W S;  W = T (K exp G);  U = T V
        dW = [(-_dot(a, s, _NT)).astype(cd)                         # [C, dk]
              for a, s in zip(dvc, c["sb"])]
        for s, h in enumerate(heads):
            ds_ref[h] = (c["decay"][s] * dS[s]
                         + _dot(c["q_in"][s], dob[s], _TN)
                         - _dot(c["W"][s], dvc[s], _TN))
        dT = pk.own_lanes([_dot(a, c["kg"], _NT) + _dot(b, c["vs"], _NT)
                           for a, b in zip(dW, dvc)])               # [C, W]
        dkg = _dot(c["T"], _rows(dW), _TN)                          # [W, dk]
        dv_all = _dot(c["T"], _rows(dvc), _TN)                      # [W, dv]
        # T = X Diag(beta);  X = (I + strict(Akk) * beta_i)^-1
        dbrow_ref[0, 0, p] = jnp.sum(dT * c["X"], axis=0, keepdims=True)
        dN = _inverse_bwd(pk, c["X"], dT * brow, same_head, c["strict"])
        on_pairs = dN * c["Akk"]
        for s in range(P):
            dbcol_ref[0, 0, p, :, s:s + 1] = jnp.sum(
                jnp.where(pk.head(C, s), on_pairs, 0.0), axis=1,
                keepdims=True)
        # Aqk = (q k^T) D;  Akk = (k k^T) D: the cotangents times D against
        # the other factor, both heads' in one product
        Pq = pk.heads_own((dAqk * c["D"]).astype(cd))               # [W, W]
        Pk = pk.heads_own((dN * c["beta_i"] * c["D"]).astype(cd))
        dq_pair = _dot(Pq, c["keys"], _NN)                          # [W, dk]
        dk_row = _dot(Pk, c["keys"], _NN)
        dk_key = _dot(Pq, _rows(q), _TN) + _dot(Pk, c["keys"], _TN)
        for s, h in enumerate(heads):
            eG, to_end = c["eG"][s], c["to_end"][s]
            qf, kf = c["qf"][s], c["kf"][s]
            dq = dq_in[s] * eG + pk.head_rows(dq_pair, s)
            # with G_i, and against it, through k
            rises = pk.head_rows(dkg, s) * eG + pk.head_rows(dk_row, s)
            falls = dk_out[s] * to_end + pk.head_rows(dk_key, s)
            dq_ref[0, h] = dq.astype(dq_ref.dtype)
            dk_ref[0, h] = (rises + falls).astype(dk_ref.dtype)
            dv_ref[0, h] = pk.head_rows(dv_all, s).astype(dv_ref.dtype)
            # the last position's sum is in every key's ``exp(G_last -
            # G_j)`` and in the state's decay
            to_last = (jnp.sum(jnp.sum(kf * dk_out[s], axis=1, keepdims=True)
                               * to_end, axis=0, keepdims=True)
                       + ddecay[s] * c["decay"][s][:, :1])
            dgcol_ref[0, 0, p, :, s:s + 1] = (
                jnp.sum(qf * dq + kf * (rises - falls), axis=1, keepdims=True)
                + jnp.where(last_row, to_last, 0.0))
        return carry

    jax.lax.fori_loop(0, hb // P, pack, 0)


def _specs(nC: int, C: int, dk: int, dv: int, hb: int, P: int,
           reverse: bool):
    at = (lambda c: nC - 1 - c) if reverse else (lambda c: c)
    wide = pl.BlockSpec((1, hb, C, dk), lambda b, g, c: (b, g, at(c), 0))
    wide_v = pl.BlockSpec((1, hb, C, dv), lambda b, g, c: (b, g, at(c), 0))
    cols = pl.BlockSpec((1, 1, hb // P, C, P),
                        lambda b, g, c: (b, at(c), g, 0, 0))
    rows = pl.BlockSpec((1, 1, hb // P, 1, P * C),
                        lambda b, g, c: (b, at(c), g, 0, 0))
    states = pl.BlockSpec((1, 1, hb, dk, dv),
                          lambda b, g, c: (b, at(c), g, 0, 0))
    return wide, wide_v, cols, rows, states


# the chunk axis is innermost and sequential: it carries the state
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)


def _shapes(q, v, cols):
    B, H, S, dk = q.shape
    nC, _, C, P = cols.shape[1:]
    hb, _ = tile_plan(C, H, dk, v.shape[-1])
    return B, S, H, nC, C, hb, P, dk, v.shape[-1]


# (jitted, as ``kda.py``'s calls are: blocks of one shape share one trace of
# the call, the kernel's body included)
@functools.partial(jax.jit, static_argnames=("interpret", "keep_states"))
def _scan_call(q, k, v, gcols, grows, bcols, brows, interpret: bool,
               keep_states: bool):
    B, S, H, nC, C, hb, P, dk, dv = _shapes(q, v, gcols)
    wide, wide_v, cols_at, rows_at, states = _specs(nC, C, dk, dv, hb, P,
                                                    reverse=False)
    o_shape = jax.ShapeDtypeStruct((B, H, S, dv), _F32)
    kept = jax.ShapeDtypeStruct((B, nC, H, dk, dv), _F32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, P=P, keep_states=keep_states),
        grid=(B, H // hb, nC),
        in_specs=[wide, wide, wide_v, cols_at, rows_at, cols_at, rows_at],
        out_specs=[wide_v, states] if keep_states else wide_v,
        out_shape=[o_shape, kept] if keep_states else o_shape,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],       # the state
        compiler_params=_PARAMS,
        interpret=interpret,
        # the kernels' instruction names on a trace's ``XLA Ops`` line
        name="gdn_scan_fwd",
    )(q, k, v, gcols, grows, bcols, brows)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_bwd_call(q, k, v, gcols, grows, bcols, brows, entering, do,
                   interpret: bool):
    B, S, H, nC, C, hb, P, dk, dv = _shapes(q, v, gcols)
    wide, wide_v, cols_at, rows_at, states = _specs(nC, C, dk, dv, hb, P,
                                                    reverse=True)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, P=P),
        grid=(B, H // hb, nC),
        in_specs=[wide, wide, wide_v, cols_at, rows_at, cols_at, rows_at,
                  states, wide_v],
        out_specs=[wide, wide, wide_v, cols_at, cols_at, rows_at],
        out_shape=[like(q), like(k), like(v), like(gcols), like(bcols),
                   like(brows)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],       # dS
        compiler_params=_PARAMS,
        interpret=interpret,
        name="gdn_scan_bwd",
    )(q, k, v, gcols, grows, bcols, brows, entering, do)


def _laid_out(q, k, v, G, beta, C: int):
    """The kernels' operands of the model's: ``q``, ``k``, ``v`` head-major,
    ``G`` and ``beta`` [B, S, H] as a pack's columns and as its row."""
    B, S, H = q.shape[:3]
    P = LANES // C
    out = [jnp.swapaxes(t, 1, 2) for t in (q, k, v)]
    for t in (G, beta):
        packs = t.reshape(B, S // C, C, H // P, P)
        out += [jnp.swapaxes(packs, 2, 3),             # [B, nC, H / P, C, P]
                jnp.transpose(packs, (0, 1, 3, 4, 2)).reshape(
                    B, S // C, H // P, 1, P * C)]
    return tuple(out)


def _of_columns(cols):
    """``[B, chunks, H / P, C, P]`` -> ``[B, S, H]``."""
    B, nC, packs, C, P = cols.shape
    return jnp.swapaxes(cols, 2, 3).reshape(B, nC * C, packs * P)


def _of_rows(rows, C: int):
    """``[B, chunks, H / P, 1, P * C]`` -> ``[B, S, H]``."""
    B, nC, packs = rows.shape[:3]
    return jnp.transpose(rows.reshape(B, nC, packs, -1, C),
                         (0, 1, 4, 2, 3)).reshape(B, nC * C, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(q, k, v, G, beta, chunk, interpret):
    return jnp.swapaxes(_scan_call(*_laid_out(q, k, v, G, beta, chunk),
                                   interpret, keep_states=False), 1, 2)


def _scan_fwd(q, k, v, G, beta, chunk, interpret):
    operands = _laid_out(q, k, v, G, beta, chunk)
    o, entering = _scan_call(*operands, interpret, keep_states=True)
    # the pair per-layer remat keeps (``modules.remat``), as the kernel
    # wrote them: a block's recomputed forward then holds no scan kernel
    o = checkpoint_name(o, KEPT_OUT)
    entering = checkpoint_name(entering, KEPT_STATES)
    return jnp.swapaxes(o, 1, 2), operands + (entering,)


def _scan_bwd(chunk, interpret, res, do):
    with jax.named_scope(SCOPE):
        dq, dk, dv, dgcols, dbcols, dbrows = _scan_bwd_call(
            *res, jnp.swapaxes(do, 1, 2).astype(res[0].dtype), interpret)
        return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
                jnp.swapaxes(dv, 1, 2), _of_columns(dgcols),
                _of_columns(dbcols) + _of_rows(dbrows, chunk))


_scan.defvjp(_scan_fwd, _scan_bwd)


def gdn_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, chunk: int, *,
             interpret: bool = False) -> jax.Array:
    """``modules.gated_delta_chunked`` for shapes that fit
    :func:`tile_plan`: ``q``, ``k`` [B, S, H, dk] and ``v`` [B, S, H, dv] in
    the compute dtype, ``g`` and ``beta`` [B, S, H] float32, ``S`` a
    multiple of ``chunk`` -> ``o`` [B, S, H, dv] float32, differentiable in
    all five. ``interpret`` comes only from the caller."""
    B, S, H, dk = q.shape
    plan = tile_plan(chunk, H, dk, v.shape[-1])
    if plan is None or S % chunk:
        raise ValueError(
            f"{S} positions in chunks of {chunk}, {H} heads of {dk} keys "
            f"and {v.shape[-1]} values fit no tile of the gdn kernels")
    # the running sums with a chunk's positions along the lanes, as the
    # packs' rows want them: summed beside 30 heads, XLA lays the window
    # reduction out with a pack's two heads in the lanes (0.51 ms a call on
    # the chip, 0.03 this way; the same sums to the bit)
    G = jnp.swapaxes(jnp.cumsum(jnp.swapaxes(
        g.astype(_F32).reshape(B, S // chunk, chunk, H), 2, 3), axis=3), 2, 3)
    return _scan(q, k.astype(q.dtype), v.astype(q.dtype),
                 G.reshape(B, S, H), beta.astype(_F32), chunk, interpret)


def make_gdn_scan(mesh, dp_axes=(), *, interpret: bool = False):
    """The kernels on a mesh (``common.on_shards``): the batch sharded over
    dp, everything else local (a plan that cuts a linear_attention block
    any other way is refused by name, ``eligibility.gdn_plan_reason``)."""
    wide, thin = batch_spec(4, dp_axes), batch_spec(3, dp_axes)

    def scan(q, k, v, g, beta, chunk):
        return on_shards(
            lambda *a: gdn_scan(*a, chunk, interpret=interpret), mesh,
            (wide, wide, wide, thin, thin), wide)(q, k, v, g, beta)
    return scan
