"""Pallas kernels for the experts' grouped matmuls (``models/moe.py::
_grouped_matmul``): sorted rows through the weights of the group each row
belongs to, in the three modes the layer and its backward pass ask for:

1. ``fwd``       rows ``[M, K]`` x weights ``[G, K, N]`` -> ``[M, N]``;
2. ``drows``     cotangent ``[M, N]`` x weights ``[G, K, N]`` contracted over
   ``N`` -> ``[M, K]`` (the weight tile is read transposed by the MXU: no
   transposed copy of the weights exists in HBM);
3. ``dweights``  rows ``[M, K]`` and cotangent ``[M, N]`` contracted over
   each group's rows -> ``[G, K, N]``.

Group ``g`` owns the rows ``[ends[g-1], ends[g])`` of the sorted buffer,
``ends = cumsum(group_sizes)``; the rows from ``ends[-1]`` on belong to no
group. Operands in the compute dtype, float32 accumulation, one rounding to
``out_dtype``: what ``lax.ragged_dot`` computes, which stays the XLA form
of the layer wherever :func:`tile_plan` fits no tile.

What makes the kernels fast is what they do NOT fetch twice. A grid step
holds a tile of ``tm`` rows and a tile of one expert's weights that spans
the WHOLE contracted width; the grid walks the weights' other width
outermost and the row tiles innermost, so over the consecutive row tiles
of one group the weights' block index does not move and the pipeline skips
the copy: an expert's weight tile comes from HBM once for all of its rows
(a tile re-fetched for every 128 rows is bound by HBM at half the MXU's
speed on a v5e: 240 FLOPs a byte). In mode 3 the float32 accumulator of one
expert's ``[tk, tn]`` stays in VMEM over the group's row tiles and is
written once.

Group ends fall anywhere. Which row tile and which group a grid step works
on is a schedule made from ``group_sizes`` outside the kernels
(:func:`schedule`) and prefetched as scalars: a row tile that holds rows of
several groups is visited once for each, consecutively, so its output block
stays in VMEM between the visits. A visit multiplies only the ``sub``-row
pieces of its tile that hold rows of its group, masks the one or two pieces
the group's ends cut, and leaves the rest alone; a group of no rows costs
modes 1 and 2 nothing and mode 3 one visit that writes a zero ``[K, N]``.
The grid is static, ``cdiv(M, tm) + G - 1`` visits, the most a schedule can
need; the steps a step's groups do not need repeat the last block indices
(no copy) and do nothing.

Rows of no group are never read into a product that reaches a real row, and
what modes 1 and 2 write there is zeros: a row tile's first visit zeroes
what its group does not own, and the tiles past the last group's end are
visited only to be zeroed (their rows are not fetched).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_galvatron_tpu.ops.pallas.common import LANES
from hetu_galvatron_tpu.ops.pallas.flash_attention import _NN, _NT, _dot

_F32 = jnp.float32
_TN = (((0,), (0,)), ((), ()))   # a.T @ b
# the scope every call of this file is traced under, forward and backward (a
# backward rule does not inherit the scope its forward was called in): the
# expert layer's own, which the step report counts these kernels under
# (``experts/mosaic_calls``)
SCOPE = "moe/experts"
# the kernels' instruction names in a compiled step and on a trace
FWD_CALL = "grouped_matmul_fwd"
DROWS_CALL = "grouped_matmul_drows"
DWEIGHTS_CALL = "grouped_matmul_dweights"
CALLS = (FWD_CALL, DROWS_CALL, DWEIGHTS_CALL)
MODES = ("fwd", "drows", "dweights")
# a weight tile of modes 1 and 2 (the whole contracted width by ``tn``
# columns, twice: the pipeline's two buffers) and mode 3's accumulator stay
# under these, so that a step's blocks fit the VMEM the kernels ask for
WEIGHT_TILE_BYTES = 8 * 2 ** 20
ACC_BYTES = 8 * 2 ** 20
VMEM_LIMIT = 96 * 2 ** 20
# rows of a grid step and of one product inside it
ROW_TILE, ROW_PIECE = 512, 128
# the fewest rows of a product a plan hands the kernels
# (:func:`make_grouped_matmul`): under it lie the counted passes of the cells
# that hold a share of their experts (512 to 768 rows, taken in a few steps
# of a hundred), where a call gains 0.1 to 0.2 ms over ``lax.ragged_dot``
# (0.24 / 0.32 / 0.34 against 0.43 / 0.56 / 0.49 ms at 512 rows) and six more
# kernels to trace and lower cost every run's set-up 2 s of 45
# (`kimilin_c1_b1_s8k`, warm: 44.9 -> 48.8 s with them; my chip runs, PR 67)
PLAN_ROWS = 1024


class Plan(NamedTuple):
    """Static tiles of the three kernels: ``rows`` = (tm, sub, tn) of modes
    1 and 2 (``tn`` counts the weights' uncontracted width), ``dweights`` =
    (tm, sub, tk, tn) of mode 3."""
    fwd: Tuple[int, int, int]
    drows: Tuple[int, int, int]
    dweights: Tuple[int, int, int, int]


def _lane_divisors(n: int):
    """The divisors of ``n`` (a multiple of a lane tile) that are whole lane
    tiles, the widest first."""
    tiles = n // LANES
    return [d * LANES for d in range(tiles, 0, -1) if tiles % d == 0]


def _widest(n: int, most: int) -> int:
    """The widest whole-lane-tile divisor of ``n`` that is at most ``most``
    (a lane tile at least)."""
    return next(d for d in _lane_divisors(n) if d <= max(most, LANES))


def tile_plan(rows: int, groups: int, k: int, n: int, dtype
              ) -> Optional[Plan]:
    """The three kernels' tiles for rows ``[rows, k]`` through weights
    ``[groups, k, n]`` in ``dtype``, or None where they fit none (the caller
    keeps ``lax.ragged_dot``): both widths whole lane tiles, a piece of rows
    at least. Row tiles of ``ROW_TILE`` in pieces of ``ROW_PIECE`` (a
    group's end costs a piece, not a tile); the weights' tile as wide as
    ``WEIGHT_TILE_BYTES`` allows beside the whole contracted width; mode
    3's accumulator ``[tk, tn]`` the divisors of the two widths that read
    its operands least often inside ``ACC_BYTES`` (the most FLOPs a byte,
    ``tk tn / (tk + tn)``; the wider ``tn`` of two that tie, which
    transposes a row tile for more columns)."""
    del groups
    size = jnp.dtype(dtype).itemsize
    if k % LANES or n % LANES or rows < ROW_PIECE or size not in (2, 4):
        return None
    tm, sub = min(ROW_TILE, rows // ROW_PIECE * ROW_PIECE), ROW_PIECE
    tn = _widest(n, WEIGHT_TILE_BYTES // (k * size))
    tk = _widest(k, WEIGHT_TILE_BYTES // (n * size))
    dk, dn = max(((a, b) for b in _lane_divisors(n)
                  for a in _lane_divisors(k) if a * b * 4 <= ACC_BYTES),
                 key=lambda t: (t[0] * t[1] / (t[0] + t[1]), t[1]))
    return Plan((tm, sub, tn), (tm, sub, tk), (tm, sub, dk, dn))


def schedule(group_sizes: jax.Array, rows: int, tm: int, empty_visits: bool):
    """The grid steps of a kernel over ``rows`` rows in tiles of ``tm``:
    int32 arrays of ``cdiv(rows, tm) + G - 1`` steps each, ``(group, tile,
    out_tile, lo, hi)``: the group a step works for, the row tile it reads,
    the row tile it writes (modes 1 and 2), and the rows ``[lo, hi)`` of the
    tile, counted from the tile's first, that are the group's.

    A group's steps are consecutive and so are a tile's. A group of no rows
    takes no step, or one of no rows (``empty_visits``: mode 3 has to write
    its zeros). Behind the groups' steps come, for modes 1 and 2, one a tile
    that lies wholly past the last group's end (``lo = hi = 0``: nothing to
    multiply, the tile is zeroed; ``tile`` stays where it was, so nothing is
    fetched), and then steps that repeat the last one's blocks and do
    nothing (``lo = hi = -1``)."""
    G = group_sizes.shape[0]
    tiles = pl.cdiv(rows, tm)
    steps = tiles + G - 1
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(jnp.int32)), rows)
    starts = jnp.concatenate([jnp.zeros_like(ends[:1]), ends[:-1]])
    first = jnp.minimum(starts // tm, tiles - 1)
    visits = jnp.where(ends > starts,
                       (ends - 1) // tm - first + 1, int(empty_visits))
    upto = jnp.cumsum(visits)
    active = upto[-1]
    v = jnp.arange(steps, dtype=jnp.int32)
    # a step behind the groups' repeats the last of them
    at = jnp.minimum(v, jnp.maximum(active - 1, 0))
    group = jnp.minimum(
        jnp.sum(upto[None, :] <= at[:, None], axis=1, dtype=jnp.int32),
        G - 1)
    tile = jnp.minimum(first[group] + at - (upto - visits)[group], tiles - 1)
    live = v < active
    lo = jnp.where(live, jnp.clip(starts[group] - tile * tm, 0, tm), 0)
    hi = jnp.where(live, jnp.clip(ends[group] - tile * tm, 0, tm), 0)
    # the tile a step behind the groups' zeroes
    behind = pl.cdiv(ends[-1], tm) + v - active
    idle = ~live & (behind >= tiles)
    out_tile = jnp.where(live, tile, jnp.minimum(behind, tiles - 1))
    return (group, tile, out_tile, jnp.where(idle, -1, lo),
            jnp.where(idle, -1, hi))


def _pieces(lo, hi, tm: int, sub: int, whole, cut, untouched=None):
    """The rows ``[lo, hi)`` of a tile of ``tm`` rows over its pieces of
    ``sub`` (one loop, one body however many pieces): a piece they cover
    runs ``whole(rows)``, ``rows`` the piece's ``pl.ds``; one they cut runs
    ``cut(rows, mask)``, the mask [sub, 1] of its rows they hold; one they
    do not touch runs ``untouched(rows)`` where that is given."""
    def piece(i, carry):
        start = pl.multiple_of(i * sub, sub)
        at = pl.ds(start, sub)
        covered = (lo <= start) & (hi >= start + sub)
        touched = (hi > lo) & (hi > start) & (lo < start + sub)
        pl.when(covered)(lambda: whole(at))

        @pl.when(touched & ~covered)
        def _cut():
            r = start + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
            cut(at, (r >= lo) & (r < hi))

        if untouched is not None:
            pl.when(~touched)(lambda: untouched(at))
        return carry
    jax.lax.fori_loop(0, tm // sub, piece, 0)


def _rows_kernel(group, tile, out_tile, lo, hi, a_ref, w_ref, o_ref, *,
                 sub: int, dims):
    """Modes 1 and 2: one visit of a row tile for one group. ``a_ref`` [tm,
    contracted], ``w_ref`` the group's weight tile, ``o_ref`` [tm, tn]."""
    del group, tile
    v = pl.program_id(1)
    lo, hi = lo[v], hi[v]
    # the tile's first visit: what is in ``o_ref`` is nobody's yet
    fresh = (v == 0) | (out_tile[jnp.maximum(v - 1, 0)] != out_tile[v])
    product = lambda at: _dot(a_ref[at, :], w_ref[...], dims)  # noqa: E731

    def whole(at):
        o_ref[at, :] = product(at).astype(o_ref.dtype)

    def cut(at, mine):
        kept = jnp.where(fresh, 0.0, o_ref[at, :].astype(_F32))
        o_ref[at, :] = jnp.where(mine, product(at), kept).astype(o_ref.dtype)

    def untouched(at):
        @pl.when(fresh & (lo >= 0))
        def _zeros():
            o_ref[at, :] = jnp.zeros((at.size, o_ref.shape[1]), o_ref.dtype)

    _pieces(lo, hi, a_ref.shape[0], sub, whole, cut, untouched)


def _dweights_kernel(group, tile, out_tile, lo, hi, a_ref, g_ref, o_ref,
                     acc_ref, *, sub: int):
    """Mode 3: one visit of a row tile for one group. ``a_ref`` [tm, tk],
    ``g_ref`` [tm, tn], ``o_ref`` and ``acc_ref`` the group's [tk, tn]."""
    del tile, out_tile
    v, end = pl.program_id(2), pl.num_programs(2) - 1
    mine_is = group[v]

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != mine_is))
    def _first():
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def whole(at):
        acc_ref[...] += _dot(a_ref[at, :], g_ref[at, :], _TN)

    def cut(at, mine):
        # both sides: a row of another group, or of none, may hold
        # anything, and 0 x inf is no zero
        a = jnp.where(mine, a_ref[at, :], 0).astype(a_ref.dtype)
        g = jnp.where(mine, g_ref[at, :], 0).astype(g_ref.dtype)
        acc_ref[...] += _dot(a, g, _TN)

    _pieces(lo[v], hi[v], a_ref.shape[0], sub, whole, cut)

    @pl.when((v == end) | (group[jnp.minimum(v + 1, end)] != mine_is))
    def _last():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _params(axes: int):
    # every axis in order: the row tiles carry an output block from one
    # visit to the next, and the axes outside them the weights' block
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * axes,
        vmem_limit_bytes=VMEM_LIMIT)


# (jitted, as the other kernels' calls are: the expert blocks of one shape,
# and the bodies of one block, share one trace of a call)
@functools.partial(jax.jit, static_argnames=(
    "out_dtype", "tiles", "transposed", "interpret"))
def rows_call(a, weights, group_sizes, *, out_dtype, tiles,
              transposed: bool, interpret: bool = False):
    """Mode 1 (``transposed`` false: ``a`` [M, K] -> [M, N]) or mode 2
    (``a`` [M, N] -> [M, K], the weights read transposed) at ``tiles`` =
    (tm, sub, tn)."""
    tm, sub, tn = tiles
    M, C = a.shape
    # (the weights' width that is not contracted)
    W = weights.shape[1 if transposed else 2]
    steps = schedule(group_sizes, M, tm, empty_visits=False)
    if transposed:
        w_spec = pl.BlockSpec((None, tn, C),
                              lambda n, v, g, *_: (g[v], n, 0))
    else:
        w_spec = pl.BlockSpec((None, C, tn),
                              lambda n, v, g, *_: (g[v], 0, n))
    return pl.pallas_call(
        functools.partial(_rows_kernel, sub=sub,
                          dims=_NT if transposed else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(pl.cdiv(W, tn), len(steps[0])),
            in_specs=[pl.BlockSpec((tm, C), lambda n, v, g, t, *_: (t[v], 0)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, v, g, t, o, *_: (o[v], n))),
        out_shape=jax.ShapeDtypeStruct((M, W), out_dtype),
        compiler_params=_params(2),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * C * W, transcendentals=0,
            bytes_accessed=(a.size * a.dtype.itemsize * pl.cdiv(W, tn)
                            + weights.size * weights.dtype.itemsize
                            + M * W * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret,
        name=DROWS_CALL if transposed else FWD_CALL,
    )(*steps, a, weights)


@functools.partial(jax.jit, static_argnames=(
    "out_dtype", "tiles", "interpret"))
def dweights_call(rows, g, group_sizes, *, out_dtype, tiles,
                  interpret: bool = False):
    """Mode 3: ``rows`` [M, K], ``g`` [M, N] -> [G, K, N] at ``tiles`` =
    (tm, sub, tk, tn)."""
    tm, sub, tk, tn = tiles
    (M, K), N, G = rows.shape, g.shape[1], group_sizes.shape[0]
    steps = schedule(group_sizes, M, tm, empty_visits=True)
    return pl.pallas_call(
        functools.partial(_dweights_kernel, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(pl.cdiv(K, tk), pl.cdiv(N, tn), len(steps[0])),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda k, n, v, g, t, *_: (t[v], k)),
                pl.BlockSpec((tm, tn), lambda k, n, v, g, t, *_: (t[v], n))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda k, n, v, g, *_: (g[v], k, n)),
            scratch_shapes=[pltpu.VMEM((tk, tn), _F32)]),
        out_shape=jax.ShapeDtypeStruct((G, K, N), out_dtype),
        compiler_params=_params(3),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N, transcendentals=0,
            bytes_accessed=(rows.size * rows.dtype.itemsize * pl.cdiv(N, tn)
                            + g.size * g.dtype.itemsize * pl.cdiv(K, tk)
                            + G * K * N * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret,
        name=DWEIGHTS_CALL,
    )(*steps, rows, g)


def grouped_matmul(mode: str, a: jax.Array, b: jax.Array,
                   group_sizes: jax.Array, out_dtype, *,
                   interpret: bool = False) -> Optional[jax.Array]:
    """One of the three products, or None where :func:`tile_plan` fits the
    shapes no tile (the caller keeps ``lax.ragged_dot``): ``fwd`` (rows
    ``a`` [M, K], weights ``b`` [G, K, N]) -> [M, N]; ``drows`` (cotangent
    ``a`` [M, N], weights ``b`` [G, K, N]) -> [M, K]; ``dweights`` (rows
    ``a`` [M, K], cotangent ``b`` [M, N]) -> [G, K, N]. Operands of one
    dtype, float32 accumulation, one rounding to ``out_dtype``; rows of no
    group come out of ``fwd`` and ``drows`` as zeros."""
    if a.dtype != b.dtype:
        return None
    if mode == "dweights":
        k, n = a.shape[1], b.shape[1]
    else:
        k, n = b.shape[1:]
    plan = tile_plan(a.shape[0], group_sizes.shape[0], k, n, a.dtype)
    tiles = plan and getattr(plan, mode)
    if tiles is None:
        return None
    with jax.named_scope(SCOPE):
        if mode == "dweights":
            return dweights_call(a, b, group_sizes, out_dtype=out_dtype,
                                 tiles=tiles, interpret=interpret)
        return rows_call(a, b, group_sizes, out_dtype=out_dtype, tiles=tiles,
                         transposed=mode == "drows", interpret=interpret)


def make_grouped_matmul(mesh, dp_axes=(), *, interpret: bool = False):
    """``LayerOps.grouped`` for an expert block whose rows and weights are
    whole on the device that runs it (``parallel/spmd.py`` hands it to a
    block on a mesh of one device, and to one whose sorted dispatcher runs
    inside ``moe.make_expert_exchange``'s ``shard_map``, a chip on its own
    experts): the kernels are called where they stand, under no
    ``shard_map`` of their own. A product of fewer than ``PLAN_ROWS`` rows
    is left to ``lax.ragged_dot`` (None)."""
    del mesh, dp_axes

    def grouped(mode, a, b, group_sizes, out_dtype):
        if a.shape[0] < PLAN_ROWS:
            return None
        return grouped_matmul(mode, a, b, group_sizes, out_dtype,
                              interpret=interpret)
    return grouped
