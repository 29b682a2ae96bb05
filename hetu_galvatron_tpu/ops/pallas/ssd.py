"""Pallas kernels for the chunked Mamba-2 scan (``modules.ssd_chunked``).

Per batch row and head, in chunks of ``Q`` positions, with ``cs`` the
running sum of ``dt A`` inside a chunk and ``S`` the state entering it::

    y_i  = sum_(j<=i) (C_i . B_j) exp(cs_i - cs_j) dt_j x_j  +  exp(cs_i) C_i . S
    S'   = exp(cs_last) S + sum_j exp(cs_last - cs_j) dt_j x_j B_j^T

In ``jax.numpy`` the decay matrix ``L = exp(cs_i - cs_j)``, its mask and
``M = (C B^T) * L`` are ``[.., heads, Q, Q]`` float32 arrays in HBM, every
chunk's state and every entering state a ``[.., heads, P, N]`` one, and the
arrays between them change layout several times (a head is 64 of a row's
4096 lanes in one place and a minor dimension of its own in the next). Here
one grid step holds one chunk and ``hb`` heads; the chunks of a sequence
are the innermost, sequential grid axis and the state stays in VMEM from
one to the next. ``G = C B^T`` is made once a step; ``L``, the mask and
``M`` exist for one head at a time.

Layout. ``x``, ``y`` and their cotangents are ``[B, S, H * P]``: a head is
``P`` lanes of a row, and the state is held transposed, ``[N, hb * P]``, so
that it is lane-dense the same way. Where ``P`` is under a lane tile (``P``
64: two heads in 128 lanes) the heads of one tile share their products:
``M_a @ (x dt)[:, tile]`` is right in head a's lanes and wrong in the
others', which a select on the lane index throws away, so no slice is ever
off the lane tiling and the MXU, 128 columns wide, does what it would have
done for 64. What is one number a position and head comes twice, made
outside the kernels from one cumulative sum: ``cs`` as rows ``[B, chunks,
H, Q]`` (a head's ``cs_j`` along lanes) and, as columns ``[B, chunks, H /
hb, Q, 4 hb]`` (along sublanes), ``dt``, ``cs``, ``w = dt exp(cs_last -
cs)`` and ``grow = exp(cs)``; ``a = exp(cs_last)`` comes spread over its
head's lanes. The exps outside are differentiated by JAX; the kernels
return the cotangents of what they were given.

The forward that is differentiated names its output and the entering states
it writes beside it (``KEPT``): ``modules.remat`` keeps what carries those
names, so a block's recomputed forward runs no scan kernel. The backward
runs the chunks in reverse with the state's cotangent in VMEM, reads the
entering states the forward kept, and makes ``G``, ``L`` and ``M``
again from the inputs, as the flash backward makes its scores again, in
TRANSPOSED tiles (sources ``j`` along sublanes, targets ``i`` along lanes)
so that ``M^T dy`` and ``(x dt) dy^T`` are plain products. The decay's
gradient needs no ``Q x Q`` reduction: with ``D = dM * M``, ``dcs_i = sum_j
D[i, j] - sum_j D[j, i]``, the first is ``sum_p dy[i, p] y_intra[i, p]`` and
the second ``sum_p (x dt)[i, p] (M^T dy)[i, p]``: row sums over a head's
``P`` lanes. ``dB`` and ``dC`` leave a step summed over its ``hb`` heads.

Groups. ``B`` and ``C`` may come in ``groups`` groups of ``N`` columns,
``[B, S, groups * N]``, head ``j`` reading group ``j // (H / groups)``
(Nemotron-H: eight; Granite-4.0-H: one, shared by all heads). A grid step
then holds heads of ONE group (``hb`` divides ``H / groups``) and its block
of ``B`` and ``C`` is that group's ``N`` columns, picked by the block index:
nothing is copied a head in HBM. ``dB`` and ``dC`` leave a step in their
group's columns and are summed over the steps of a group outside.

Arithmetic is ``ssd_chunked``'s: ``cs``, ``L``, every exp, the carried
state and every accumulator float32; the matmul operands (``M`` after ``G *
L``, ``x dt`` and ``x w`` after the float32 product, the state where it is
read out, the cotangents) in the inputs' dtype with float32 accumulation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_galvatron_tpu.ops.pallas.common import LANES, batch_spec, on_shards
from hetu_galvatron_tpu.ops.pallas.flash_attention import _NN, _NT, _dot

# heads a grid step may hold, the most first: multiples of the sublane tile
# (a step's rows of ``cs`` are ``hb`` sublanes); and the lanes ``hb * P`` its
# tiles may span, so that x, y, dy, the state and their double buffers stay
# inside the 16 MiB of VMEM a kernel is given (2048 lanes need 17.9)
HEADS_A_STEP = (16, 8)
STEP_LANES = 1024
# the scope every call of this file is traced under, forward and backward:
# a backward rule does not inherit the scope its forward was called in
# (``observability/trace_analysis.SSD_SCOPE`` is the same words)
SCOPE = "mixer/mamba/ssd"
# ``checkpoint_name``s of the differentiated forward's two results, both of
# which the backward kernel reads: the output and the states that entered
# the chunks; ``modules.remat`` keeps the values under the names of ``KEPT``
KEPT_OUT = "ssd_scan_out"
KEPT_STATES = "ssd_scan_states"
KEPT = (KEPT_OUT, KEPT_STATES)

_TN = (((0,), (0,)), ((), ()))   # a.T @ b, beside the flash kernels' two
# the columns' order in their packed operand, and of their cotangents
_DT, _CS, _W, _GROW = range(4)


def tile_plan(chunk: int, heads: int, head_dim: int,
              state: int, groups: int = 1) -> Optional[int]:
    """The heads a grid step holds where the kernels' tiles fit these
    shapes, else None (the caller keeps the ``jax.numpy`` form): the chunk
    and the state whole lane tiles, a head a whole number of lane tiles or
    a whole fraction of one, the heads OF A GROUP of B and C a whole number
    of steps of at most ``STEP_LANES`` lanes."""
    if chunk % LANES or state % LANES or head_dim < 16 or heads % groups:
        return None
    if head_dim % LANES and LANES % head_dim:
        return None
    pack = max(1, LANES // head_dim)    # heads a lane tile
    for hb in HEADS_A_STEP:
        if not (heads // groups % hb or hb % pack
                or hb * head_dim > STEP_LANES):
            return hb
    return None


def _tiles(hb: int, P: int):
    """The lane tiles of a step's ``hb * P`` lanes: (lane slice, its heads)."""
    width = max(P, LANES)
    pack = width // P
    return width, [(slice(t * width, (t + 1) * width),
                    range(t * pack, (t + 1) * pack))
                   for t in range(hb // pack)]


def _mine(lane, s: int, P: int):
    return (lane >= s * P) & (lane < (s + 1) * P)


def _over_heads(cols, heads, lane, P: int, shape):
    """``shape`` = [Q, width]: each head's column ``cols[:, h]`` over that
    head's ``P`` lanes."""
    out = None
    for s, h in enumerate(heads):
        col = cols[:, h:h + 1]
        out = col if out is None else jnp.where(lane >= s * P, col, out)
    return jnp.broadcast_to(out, shape)


def _head_sums(v, heads, lane, P: int):
    """Each head's row sums ``[Q, 1]`` of ``v`` [Q, width] over its lanes."""
    if len(heads) == 1:
        return [jnp.sum(v, axis=1, keepdims=True)]
    return [jnp.sum(jnp.where(_mine(lane, s, P), v, 0.0), axis=1,
                    keepdims=True) for s in range(len(heads))]


def _fwd_kernel(x_ref, col_ref, csr_ref, a_ref, b_ref, c_ref, y_ref,
                *rest, hb: int, P: int, keep_states: bool):
    enter_ref, s_ref = rest if keep_states else (None,) + rest
    Q = x_ref.shape[1]
    cd = x_ref.dtype
    width, tiles = _tiles(hb, P)

    @pl.when(pl.program_id(2) == 0)
    def _init():    # zero before the sequence
        s_ref[...] = jnp.zeros_like(s_ref)

    Bc, Cc = b_ref[0], c_ref[0]
    G = _dot(Cc, Bc, _NT)                                   # [i, j]
    at_or_below = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
                   <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    cols, csr = col_ref[0, 0, 0], csr_ref[0, 0]
    col = lambda k: cols[:, k * hb:(k + 1) * hb]
    for lanes, heads in tiles:
        spread = lambda k: _over_heads(col(k), heads, lane, P, (Q, width))
        xf = x_ref[0, :, lanes].astype(jnp.float32)
        xdt = (xf * spread(_DT)).astype(cd)
        y = None
        for s, h in enumerate(heads):
            # masked BEFORE the exp: above the diagonal cs_i - cs_j is
            # positive and may overflow
            L = jnp.exp(jnp.where(
                at_or_below,
                col(_CS)[:, h:h + 1] - csr[h:h + 1, :], -jnp.inf))
            yh = _dot((G * L).astype(cd), xdt, _NN)
            y = yh if y is None else jnp.where(lane >= s * P, yh, y)
        entering = s_ref[:, lanes]                          # [N, width]
        if keep_states:
            enter_ref[0, 0, :, lanes] = entering
        y_ref[0, :, lanes] = y + spread(_GROW) * _dot(
            Cc, entering.astype(cd), _NN)
        s_ref[:, lanes] = (a_ref[0, 0, :, lanes] * entering + _dot(
            Bc, (xf * spread(_W)).astype(cd), _TN))


def _bwd_kernel(x_ref, col_ref, csr_ref, a_ref, b_ref, c_ref, y_ref,
                enter_ref, dy_ref, dx_ref, dcol_ref, da_ref, db_ref, dc_ref,
                ds_ref, *, hb: int, P: int):
    Q = x_ref.shape[1]
    cd = x_ref.dtype
    width, tiles = _tiles(hb, P)

    @pl.when(pl.program_id(2) == 0)
    def _init():    # nothing reads the state the last chunk leaves
        ds_ref[...] = jnp.zeros_like(ds_ref)

    Bc, Cc = b_ref[0], c_ref[0]
    GT = _dot(Bc, Cc, _NT)                                  # [j, i]
    at_or_above = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
                   <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    cols, csr = col_ref[0, 0, 0], csr_ref[0, 0]
    col = lambda k: cols[:, k * hb:(k + 1) * hb]
    dgt = jnp.zeros((Q, Q), jnp.float32)                    # dG^T [j, i]
    dB = jnp.zeros(Bc.shape, jnp.float32)
    dC = jnp.zeros(Cc.shape, jnp.float32)
    for lanes, heads in tiles:
        spread = lambda k: _over_heads(col(k), heads, lane, P, (Q, width))
        dtp, wp, growp = spread(_DT), spread(_W), spread(_GROW)
        xf = x_ref[0, :, lanes].astype(jnp.float32)
        xdt = xf * dtp
        dyf = dy_ref[0, :, lanes]
        dyc = dyf.astype(cd)
        # the chunk's own positions, tiles transposed
        dxdt = None
        for s, h in enumerate(heads):
            LT = jnp.exp(jnp.where(
                at_or_above,
                csr[h:h + 1, :] - col(_CS)[:, h:h + 1], -jnp.inf))
            dX = _dot((GT * LT).astype(cd), dyc, _NN)       # M^T dy
            dxdt = dX if dxdt is None else jnp.where(lane >= s * P, dX, dxdt)
            mine = xdt if len(heads) == 1 else jnp.where(
                _mine(lane, s, P), xdt, 0.0)
            dgt += _dot(mine.astype(cd), dyc, _NT) * LT     # dM^T * L^T
        # the entering state's read-out, y += grow * (C . S)
        entering = enter_ref[0, 0, :, lanes]                # [N, width]
        entered = entering.astype(cd)
        read = _dot(Cc, entered, _NN)
        dyg = (dyf * growp).astype(cd)
        dC += _dot(dyg, entered, _NT)
        # the state the chunk leaves, S' = a S + B^T (x w)
        dS = ds_ref[:, lanes]
        dSc = dS.astype(cd)
        dxw = _dot(Bc, dSc, _NN)
        dB += _dot((xf * wp).astype(cd), dSc, _NT)
        da_ref[0, 0, :, lanes] = jnp.sum(dS * entering, axis=0,
                                         keepdims=True)
        ds_ref[:, lanes] = a_ref[0, 0, :, lanes] * dS + _dot(Cc, dyg, _TN)
        dx_ref[0, :, lanes] = (dxdt * dtp + dxw * wp).astype(dx_ref.dtype)
        # one number a position and head, as row sums over a head's lanes:
        # dt's own sum_p dxdt x; w's sum_p dxw x; grow's sum_p dy read; and
        # cs's (in L alone) sum_p dy y_intra - sum_p (x dt) dxdt
        to_dt, to_grow = dxdt * xf, dyf * read
        to_cs = dyf * y_ref[0, :, lanes] - growp * to_grow - dtp * to_dt
        for k, v in ((_DT, to_dt), (_CS, to_cs), (_W, dxw * xf),
                     (_GROW, to_grow)):
            for h, total in zip(heads, _head_sums(v, heads, lane, P)):
                dcol_ref[0, 0, 0, :, k * hb + h:k * hb + h + 1] = total
    dg = dgt.astype(cd)
    db_ref[0, 0] = dB + _dot(dg, Cc, _NN)
    dc_ref[0, 0] = dC + _dot(dg, Bc, _TN)


def _specs(nC: int, Q: int, P: int, N: int, hb: int, reverse: bool,
           steps: int):
    """``steps``: the grid steps a group of B and C spans along the head
    axis (all of them where there is one group). Step ``g`` reads columns
    ``g // steps`` of B and C and writes its part of their cotangents as
    part ``g % steps`` of those columns."""
    at = (lambda c: nC - 1 - c) if reverse else (lambda c: c)
    wide = pl.BlockSpec((1, Q, hb * P), lambda b, g, c: (b, at(c), g))
    cols = pl.BlockSpec((1, 1, 1, Q, 4 * hb),
                        lambda b, g, c: (b, at(c), g, 0, 0))
    rows = pl.BlockSpec((1, 1, hb, Q), lambda b, g, c: (b, at(c), g, 0))
    a = pl.BlockSpec((1, 1, 1, hb * P), lambda b, g, c: (b, at(c), 0, g))
    states = pl.BlockSpec((1, 1, N, hb * P),
                          lambda b, g, c: (b, at(c), 0, g))
    if steps is None:   # one group: the index maps they always were
        shared = pl.BlockSpec((1, Q, N), lambda b, g, c: (b, at(c), 0))
        part = pl.BlockSpec((1, 1, Q, N), lambda b, g, c: (b, g, at(c), 0))
    else:
        shared = pl.BlockSpec((1, Q, N),
                              lambda b, g, c: (b, at(c), g // steps))
        part = pl.BlockSpec((1, 1, Q, N),
                            lambda b, g, c: (b, g % steps, at(c), g // steps))
    return wide, cols, rows, a, shared, states, part


# the chunk axis is innermost and sequential: it carries the state
_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _group_steps(H: int, hb: int, groups: int) -> Optional[int]:
    """``_specs``' ``steps``: None for one group."""
    return None if groups == 1 else H // groups // hb


def _scan_call(x, cols, csr, a, Bm, Cm, interpret: bool, keep_states: bool,
               groups: int = 1):
    B, S, HP = x.shape
    nC, H, Q = csr.shape[1:]
    P, N, hb = HP // H, Bm.shape[-1] // groups, cols.shape[-1] // 4
    wide, col, rows, a_spec, shared, states, _ = _specs(
        nC, Q, P, N, hb, False, _group_steps(H, hb, groups))
    y_shape = jax.ShapeDtypeStruct((B, S, HP), jnp.float32)
    kept = jax.ShapeDtypeStruct((B, nC, N, HP), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, P=P, keep_states=keep_states),
        grid=(B, H // hb, nC),
        in_specs=[wide, col, rows, a_spec, shared, shared],
        out_specs=[wide, states] if keep_states else wide,
        out_shape=[y_shape, kept] if keep_states else y_shape,
        scratch_shapes=[pltpu.VMEM((N, hb * P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
        # the kernels' instruction names on a trace's ``XLA Ops`` line
        name="ssd_scan_fwd",
    )(x, cols, csr, a, Bm, Cm)


def _scan_bwd_call(x, cols, csr, a, Bm, Cm, y, entering, dy,
                   interpret: bool, groups: int = 1):
    B, S, HP = x.shape
    nC, H, Q = csr.shape[1:]
    P, N, hb = HP // H, Bm.shape[-1] // groups, cols.shape[-1] // 4
    wide, col, rows, a_spec, shared, states, part = _specs(
        nC, Q, P, N, hb, True, _group_steps(H, hb, groups))
    # a group's steps side by side, each over its group's columns
    parts = jax.ShapeDtypeStruct((B, H // groups // hb, S, groups * N),
                                 jnp.float32)
    dx, dcols, da, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, P=P),
        grid=(B, H // hb, nC),
        in_specs=[wide, col, rows, a_spec, shared, shared, wide, states,
                  wide],
        out_specs=[wide, col, a_spec, part, part],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(cols.shape, jnp.float32),
                   jax.ShapeDtypeStruct(a.shape, jnp.float32), parts, parts],
        scratch_shapes=[pltpu.VMEM((N, hb * P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="ssd_scan_bwd",
    )(x, cols, csr, a, Bm, Cm, y, entering, dy.astype(jnp.float32))
    return (dx, dcols, jnp.zeros_like(csr), da,
            jnp.sum(dB, axis=1).astype(Bm.dtype),
            jnp.sum(dC, axis=1).astype(Cm.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, cols, csr, a, Bm, Cm, interpret, groups):
    return _scan_call(x, cols, csr, a, Bm, Cm, interpret, keep_states=False,
                      groups=groups)


def _scan_fwd(x, cols, csr, a, Bm, Cm, interpret, groups):
    y, entering = _scan_call(x, cols, csr, a, Bm, Cm, interpret,
                             keep_states=True, groups=groups)
    # the pair per-layer remat keeps (``modules.remat``), as the kernel
    # wrote them: a block's recomputed forward then holds no scan kernel
    y = checkpoint_name(y, KEPT_OUT)
    entering = checkpoint_name(entering, KEPT_STATES)
    return y, (x, cols, csr, a, Bm, Cm, y, entering)


def _scan_bwd(interpret, groups, res, dy):
    with jax.named_scope(SCOPE):
        return _scan_bwd_call(*res, dy, interpret, groups)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, chunk: int, *, groups: int = 1,
             interpret: bool = False) -> jax.Array:
    """``modules.ssd_chunked`` for shapes that fit :func:`tile_plan`: ``x``
    [B, S, H, P] and ``Bm``, ``Cm`` [B, S, groups * N] in the compute dtype,
    ``dt`` [B, S, H] and ``A`` [H] float32, ``S`` a multiple of ``chunk`` ->
    ``y`` [B, S, H, P] float32, differentiable in all five. ``interpret``
    comes only from the caller."""
    B, S, H, P = x.shape
    Q, nC = chunk, S // chunk
    hb = tile_plan(Q, H, P, Bm.shape[-1] // groups, groups)
    if hb is None or S % Q:
        raise ValueError(
            f"{S} positions in chunks of {Q}, {H} heads of {P} in {groups} "
            f"groups and a state of {Bm.shape[-1] // groups} fit no tile of "
            "the ssd kernels")
    rows = jnp.swapaxes(dt.reshape(B, nC, Q, H), 2, 3)      # [B, nC, H, Q]
    cs = jnp.cumsum(rows * A[:, None], axis=-1)
    last = cs[..., -1:]
    # [B, nC, H, 4, Q] -> [B, nC, H / hb, Q, 4 hb]: dt | cs | w | grow
    cols = jnp.stack([rows, cs, rows * jnp.exp(last - cs), jnp.exp(cs)],
                     axis=3).reshape(B, nC, H // hb, hb, 4, Q)
    cols = cols.transpose(0, 1, 2, 5, 4, 3).reshape(B, nC, H // hb, Q,
                                                    4 * hb)
    a = jnp.repeat(jnp.exp(last[..., 0]), P, axis=-1)[:, :, None, :]
    y = _scan(x.reshape(B, S, H * P), cols, cs, a, Bm.astype(x.dtype),
              Cm.astype(x.dtype), interpret, groups)
    return y.reshape(B, S, H, P)


def make_ssd_scan(mesh, dp_axes=(), *, interpret: bool = False):
    """The kernels on a mesh (``common.on_shards``): the batch sharded over
    dp, everything else local (a plan that cuts a mamba block any other way
    is refused by name, ``eligibility.mamba_plan_reason``)."""
    from jax.sharding import PartitionSpec

    wide, flat = batch_spec(4, dp_axes), batch_spec(3, dp_axes)

    def scan(x, dt, A, Bm, Cm, chunk, groups=1):
        return on_shards(
            lambda *a: ssd_scan(*a, chunk, groups=groups,
                                interpret=interpret), mesh,
            (wide, flat, PartitionSpec(), flat, flat), wide)(x, dt, A, Bm, Cm)
    return scan
