"""Pallas kernels for Mamba-1's selective scan (``modules.selective_scan``).

A channel ``c`` carries a state of ``N`` values, zero before the sequence::

    s_t[c] = exp(dt_t[c] A[c]) s_(t-1)[c] + dt_t[c] u_t[c] B_t
    y_t[c] = s_t[c] . C_t

The decay is a number a channel AND a state index, so there is no matmul
form: the work is the vector unit's, some six multiply-adds and one ``exp``
a state element and position. In ``jax.numpy`` the ``[N, channels]`` state
goes through HBM between the positions' fusions; here it stays on the chip.

Layout. The state of a channel tile is ``[N, W]`` float32, the state index
along sublanes and ``W`` channels along lanes, so a row of ``u``, ``dt`` or
``y`` ([B, S, channels], as the block has them) meets it by a sublane
broadcast and the sum over ``N`` is a sublane reduction. ``B_t`` and ``C_t``
are ``N`` numbers a position that have to lie ALONG SUBLANES and be the
same in every lane: they come transposed, ``[B, N, S]`` (made outside, half
a megabyte), and a grid step spreads its chunk's columns over the lanes
once, into a ``[Q, N, 128]`` VMEM scratch that every channel tile of the
chunk then reads (no lane-replicated copy ever exists in HBM).

Grid: (batch row, chunk of ``Q`` positions, channel tile), the channel tile
innermost. The carried state of EVERY channel tile lives in one VMEM scratch
``[tiles, N, W]`` (320 KB at 5120 channels of 16) from chunk to chunk, so
the chunk axis is sequential and the spread columns are made once a chunk.
The forward writes its rows of ``y`` and the state that ENTERED the chunk.
Inside a step the positions run one after another with the tile's state in
registers, eight positions a loop trip: a trip's eight rows of ``y`` leave
as one aligned store.

The backward runs the chunks in reverse with the state's cotangent in the
same kind of scratch. A step makes its chunk's states again from the kept
entering state (one forward sweep into VMEM, the decays beside them, so
``exp(dt A)`` is made once in this kernel), then sweeps in reverse: ``du``,
``ddt`` a row; ``dA`` summed over the positions in a scratch and written
with the last chunk; ``dB_t`` and ``dC_t`` are sums over CHANNELS, kept as
``[Q, N, 128]`` lane-wise partial sums over the chunk's channel tiles and
folded to ``[N, Q]`` columns once a chunk.

Arithmetic is ``selective_scan``'s: everything float32 (``u`` may arrive in
the compute dtype and is widened here); no sum of decays is exponentiated.

The forward that is differentiated names its output and the entering states
(``KEPT``): ``modules.remat`` keeps what carries those names, so a block's
recomputed forward runs no scan kernel.
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

_SUB = 8                  # sublanes of a float32 tile
_F32 = jnp.float32
# positions of a chunk and the lanes of a channel tile, the widest first
# (chosen by a probe of the kernels alone on a v5e at [1, 8192, 5120] x 16;
# PERF.md section 6, PR 64). A tile's chunk of states and decays, [Q, N, W]
# float32 each, is what the backward holds in VMEM: ``STATE_LANES`` bounds
# ``N * W``
CHUNK = 128
CHANNEL_TILES = (512, 256, 128)
# lane tiles whose states (and, backward, cotangents and ``dA`` sums) one
# sweep over a chunk's positions carries in registers
FWD_TILES = 4
BWD_TILES = 4
# positions a loop trip of the column spread and of the fold, unrolled (the
# probe: 8 / 16 / 32 / 128 read 7.97 / 7.40 / 7.29 / 7.02 ms forward and
# backward, and 128 costs every run's set-up two seconds of tracing and
# lowering where 32 costs a tenth of one)
GROUP = 32
STATE_LANES = 16 * 512
VMEM_LIMIT = 48 * 1024 * 1024
# the scope every call of this file is traced under, forward and backward:
# a backward rule does not inherit the scope its forward was called in
# (``observability/trace_analysis.SELECTIVE_SCOPE`` is the same words)
SCOPE = "mixer/mamba1/scan"
# ``checkpoint_name``s of the differentiated forward's two results, both of
# which the backward kernel reads: the output and the states that entered
# the chunks; ``modules.remat`` keeps the values under the names of ``KEPT``
KEPT_OUT = "selective_scan_out"
KEPT_STATES = "selective_scan_states"
KEPT = (KEPT_OUT, KEPT_STATES)


def tile_plan(channels: int, state: int, seq: int) -> Optional[int]:
    """The lanes of a channel tile where the kernels' tiles fit these
    shapes, else None (the caller keeps the ``jax.numpy`` form): the state a
    whole number of sublane tiles, the channels a whole number of tiles of
    at most ``STATE_LANES / state`` lanes, a sequence of a sublane tile at
    least."""
    if state % _SUB or seq < _SUB:
        return None
    for w in CHANNEL_TILES:
        if channels % w == 0 and state * w <= STATE_LANES:
            return w
    return None


def _spread(t_ref, out_ref):
    """``t_ref`` [1, N, Q], a chunk's ``B`` or ``C`` with the positions
    along lanes -> ``out_ref`` [Q, N, 128]: position ``t``'s column in
    every lane. ``GROUP`` positions a loop trip: a rotation by the trip's
    first position brings their columns to the first lanes, where the
    slices are static (a masked sum over the lanes a position, which needs
    no static index, costs a millisecond a call)."""
    cols = t_ref[0]
    N, Q = cols.shape

    def group(i, _):
        t0 = pl.multiple_of(i * GROUP, GROUP)
        first = pltpu.roll(cols, Q - t0, 1)
        for r in range(GROUP):
            out_ref[t0 + r] = jnp.broadcast_to(first[:, r:r + 1], (N, LANES))
        return _

    jax.lax.fori_loop(0, Q // GROUP, group, None)


def _fold(acc_ref, out_ref):
    """``acc_ref`` [Q, N, 128], lane-wise partial sums a position ->
    ``out_ref`` [1, N, Q]: their sums over the lanes, position ``t``'s in
    column ``t``; ``GROUP`` positions a loop trip, as :func:`_spread`."""
    Q, N, _ = acc_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (N, Q), 1)

    def group(i, out):
        t0 = pl.multiple_of(i * GROUP, GROUP)
        for r in range(GROUP):
            out = jnp.where(
                lane == t0 + r,
                jnp.sum(acc_ref[t0 + r], axis=1, keepdims=True), out)
        return out

    out_ref[0] = jax.lax.fori_loop(0, Q // GROUP, group,
                                   jnp.zeros((N, Q), _F32))


def _over_states(p):
    """``p`` [N, 128] -> [8, 128], the sum over ``N`` in every sublane:
    the sublane tiles added, then three rotate-and-adds."""
    r = p[:_SUB]
    for k in range(1, p.shape[0] // _SUB):
        r = r + p[k * _SUB:(k + 1) * _SUB]
    for shift in (4, 2, 1):
        r = r + pltpu.roll(r, shift, 0)
    return r


def _lane_tiles(W: int, together: int):
    """The lane tiles of ``W`` lanes, in the groups of at most ``together``
    whose states a sweep holds in registers at once."""
    tiles = [slice(k * LANES, (k + 1) * LANES) for k in range(W // LANES)]
    return [tiles[i:i + together] for i in range(0, len(tiles), together)]


def _rows(ref, t0, tiles):
    """Eight rows of ``ref`` [Q, W] from the aligned ``t0``, a lane tile
    each: a position's row is a static slice of them (a dynamic load of one
    row at an unaligned index is not Mosaic's to make)."""
    return [ref[pl.ds(t0, _SUB), l] for l in tiles]


def _fwd_kernel(u_ref, dt_ref, at_ref, bt_ref, ct_ref, y_ref, *rest,
                keep_states: bool):
    enter_ref, s_ref, x_ref, bb_ref, cb_ref = (
        rest if keep_states else (None,) + rest)
    Q, W = u_ref.shape[1:]
    g = pl.program_id(2)

    @pl.when((pl.program_id(1) == 0) & (g == 0))
    def _init():    # zero before the sequence
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(g == 0)
    def _columns():     # once a chunk, for all its channel tiles
        _spread(bt_ref, bb_ref)
        _spread(ct_ref, cb_ref)

    if keep_states:
        enter_ref[0, 0] = s_ref[g]
    x_ref[...] = dt_ref[0] * u_ref[0].astype(_F32)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, LANES), 0)

    def sweep(tiles):
        A = [at_ref[:, l] for l in tiles]

        def eight(i, states):
            t0 = pl.multiple_of(i * _SUB, _SUB)
            ys = [jnp.zeros((_SUB, LANES), _F32) for _ in tiles]
            dts, xs = _rows(dt_ref.at[0], t0, tiles), _rows(x_ref, t0, tiles)
            for r in range(_SUB):
                b, c = bb_ref[t0 + r], cb_ref[t0 + r]
                new = []
                for k, l in enumerate(tiles):
                    dt, x = dts[k][r:r + 1], xs[k][r:r + 1]
                    s = jnp.exp(dt * A[k]) * states[k] + x * b
                    ys[k] = jnp.where(sub == r, _over_states(s * c), ys[k])
                    new.append(s)
                states = tuple(new)
            for k, l in enumerate(tiles):
                y_ref[0, pl.ds(t0, _SUB), l] = ys[k]
            return states

        left = jax.lax.fori_loop(0, Q // _SUB, eight,
                                 tuple(s_ref[g, :, l] for l in tiles))
        for k, l in enumerate(tiles):
            s_ref[g, :, l] = left[k]

    for tiles in _lane_tiles(W, FWD_TILES):
        sweep(tiles)


def _bwd_kernel(u_ref, dt_ref, at_ref, bt_ref, ct_ref, enter_ref, dy_ref,
                du_ref, ddt_ref, da_ref, db_ref, dc_ref,
                ds_ref, dacc_ref, st_ref, a_ref, u32_ref, x_ref, du32_ref,
                bb_ref, cb_ref, dbacc_ref, dcacc_ref):
    Q, W = u_ref.shape[1:]
    g = pl.program_id(2)

    # (the chunks run reversed: the first to run is the sequence's last)
    @pl.when((pl.program_id(1) == 0) & (g == 0))
    def _init():    # nothing reads the state the last chunk leaves
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dacc_ref[...] = jnp.zeros_like(dacc_ref)

    @pl.when(g == 0)
    def _columns():     # once a chunk, for all its channel tiles
        _spread(bt_ref, bb_ref)
        _spread(ct_ref, cb_ref)
        dbacc_ref[...] = jnp.zeros_like(dbacc_ref)
        dcacc_ref[...] = jnp.zeros_like(dcacc_ref)

    u32_ref[...] = u_ref[0].astype(_F32)
    x_ref[...] = dt_ref[0] * u32_ref[...]
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, LANES), 0)

    def sweep(tiles):
        A = [at_ref[:, l] for l in tiles]

        # the chunk's states and decays again: st[t] entered position t
        def forward(i, states):
            t0 = pl.multiple_of(i * _SUB, _SUB)
            dts, xs = _rows(dt_ref.at[0], t0, tiles), _rows(x_ref, t0, tiles)
            for r in range(_SUB):
                t = t0 + r
                b = bb_ref[t]
                new = []
                for k, l in enumerate(tiles):
                    a = jnp.exp(dts[k][r:r + 1] * A[k])
                    a_ref[t, :, l] = a
                    st_ref[t, :, l] = states[k]
                    new.append(a * states[k] + xs[k][r:r + 1] * b)
                states = tuple(new)
            return states

        left = jax.lax.fori_loop(
            0, Q // _SUB, forward,
            tuple(enter_ref[0, 0, :, l] for l in tiles))
        for k, l in enumerate(tiles):
            st_ref[Q, :, l] = left[k]

        def eight(i, carry):
            t0 = pl.multiple_of((Q // _SUB - 1 - i) * _SUB, _SUB)
            ds, dA = carry
            dus = [jnp.zeros((_SUB, LANES), _F32) for _ in tiles]
            ddts = [jnp.zeros((_SUB, LANES), _F32) for _ in tiles]
            dys = _rows(dy_ref.at[0], t0, tiles)
            dts, xs = _rows(dt_ref.at[0], t0, tiles), _rows(x_ref, t0, tiles)
            us = _rows(u32_ref, t0, tiles)
            for r in reversed(range(_SUB)):
                t = t0 + r
                b, c = bb_ref[t], cb_ref[t]
                to_b, to_c = dbacc_ref[t], dcacc_ref[t]
                new_ds, new_dA = [], []
                for k, l in enumerate(tiles):
                    dy, dt = dys[k][r:r + 1], dts[k][r:r + 1]
                    a = a_ref[t, :, l]
                    gs = ds[k] + dy * c                 # d s_t, whole
                    to_c = to_c + dy * st_ref[t + 1, :, l]
                    to_b = to_b + gs * xs[k][r:r + 1]
                    dx = _over_states(gs * b)           # d (dt u)
                    e = gs * st_ref[t, :, l] * a        # d (dt A)
                    dus[k] = jnp.where(sub == r, dx * dt, dus[k])
                    ddts[k] = jnp.where(
                        sub == r,
                        dx * us[k][r:r + 1] + _over_states(e * A[k]),
                        ddts[k])
                    new_dA.append(dA[k] + e * dt)
                    new_ds.append(gs * a)
                dbacc_ref[t] = to_b
                dcacc_ref[t] = to_c
                ds, dA = tuple(new_ds), tuple(new_dA)
            for k, l in enumerate(tiles):
                du32_ref[pl.ds(t0, _SUB), l] = dus[k]
                ddt_ref[0, pl.ds(t0, _SUB), l] = ddts[k]
            return ds, dA

        ds, dA = jax.lax.fori_loop(
            0, Q // _SUB, eight,
            (tuple(ds_ref[g, :, l] for l in tiles),
             tuple(dacc_ref[g, :, l] for l in tiles)))
        for k, l in enumerate(tiles):
            ds_ref[g, :, l] = ds[k]
            dacc_ref[g, :, l] = dA[k]

    for tiles in _lane_tiles(W, BWD_TILES):
        sweep(tiles)
    du_ref[0] = du32_ref[...].astype(du_ref.dtype)
    # the sum so far: the last chunk to run, the sequence's first, writes
    # the whole
    da_ref[0] = dacc_ref[g]

    @pl.when(g == pl.num_programs(2) - 1)
    def _columns_out():
        _fold(dbacc_ref, db_ref)
        _fold(dcacc_ref, dc_ref)


def _specs(nC: int, Q: int, N: int, W: int, reverse: bool):
    at = (lambda c: nC - 1 - c) if reverse else (lambda c: c)
    wide = pl.BlockSpec((1, Q, W), lambda b, c, g: (b, at(c), g))
    decay = pl.BlockSpec((N, W), lambda b, c, g: (0, g))
    cols = pl.BlockSpec((1, N, Q), lambda b, c, g: (b, 0, at(c)))
    states = pl.BlockSpec((1, 1, N, W), lambda b, c, g: (b, at(c), 0, g))
    summed = pl.BlockSpec((1, N, W), lambda b, c, g: (b, 0, g))
    return wide, decay, cols, states, summed


# the chunk axis carries the state and the channel tiles of a chunk share
# its spread columns: both sequential
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)


def _shapes(u, At):
    B, S, C = u.shape
    N = At.shape[0]
    W = tile_plan(C, N, S)
    return B, S, C, N, W, CHUNK, S // CHUNK


# (jitted, as the flash and kda kernels' calls are: blocks of one shape, the
# step program's count of them and the step's own, share one trace of the
# call, the kernel's body included)
@functools.partial(jax.jit, static_argnames=("interpret", "keep_states"))
def _scan_call(u, dt, At, Bt, Ct, interpret: bool, keep_states: bool):
    B, S, C, N, W, Q, nC = _shapes(u, At)
    wide, decay, cols, states, _ = _specs(nC, Q, N, W, reverse=False)
    y_shape = jax.ShapeDtypeStruct((B, S, C), _F32)
    kept = jax.ShapeDtypeStruct((B, nC, N, C), _F32)
    spread = pltpu.VMEM((Q, N, LANES), _F32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, keep_states=keep_states),
        grid=(B, nC, C // W),
        in_specs=[wide, wide, decay, cols, cols],
        out_specs=[wide, states] if keep_states else wide,
        out_shape=[y_shape, kept] if keep_states else y_shape,
        scratch_shapes=[pltpu.VMEM((C // W, N, W), _F32),      # the state
                        pltpu.VMEM((Q, W), _F32),              # dt u
                        spread, spread],
        compiler_params=_PARAMS,
        interpret=interpret,
        # the kernels' instruction names on a trace's ``XLA Ops`` line
        name="selective_scan_fwd",
    )(u, dt, At, Bt, Ct)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_bwd_call(u, dt, At, Bt, Ct, entering, dy, interpret: bool):
    B, S, C, N, W, Q, nC = _shapes(u, At)
    wide, decay, cols, states, summed = _specs(nC, Q, N, W, reverse=True)
    spread = pltpu.VMEM((Q, N, LANES), _F32)
    carried = pltpu.VMEM((C // W, N, W), _F32)
    wide32 = pltpu.VMEM((Q, W), _F32)
    return pl.pallas_call(
        _bwd_kernel,
        grid=(B, nC, C // W),
        in_specs=[wide, wide, decay, cols, cols, states, wide],
        out_specs=[wide, wide, summed, cols, cols],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct((B, N, C), _F32),
                   jax.ShapeDtypeStruct(Bt.shape, _F32),
                   jax.ShapeDtypeStruct(Ct.shape, _F32)],
        scratch_shapes=[carried, carried,                      # ds, dA
                        pltpu.VMEM((Q + 1, N, W), _F32),       # states
                        pltpu.VMEM((Q, N, W), _F32),           # decays
                        wide32, wide32, wide32,             # u, dt u, du
                        spread, spread, spread, spread],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="selective_scan_bwd",
    )(u, dt, At, Bt, Ct, entering, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(u, dt, At, Bt, Ct, interpret):
    return _scan_call(u, dt, At, Bt, Ct, interpret, keep_states=False)


def _scan_fwd(u, dt, At, Bt, Ct, interpret):
    y, entering = _scan_call(u, dt, At, Bt, Ct, interpret, keep_states=True)
    # the pair per-layer remat keeps (``modules.remat``), as the kernel
    # wrote them: a block's recomputed forward then holds no scan kernel
    y = checkpoint_name(y, KEPT_OUT)
    entering = checkpoint_name(entering, KEPT_STATES)
    return y, (u, dt, At, Bt, Ct, entering)


def _scan_bwd(interpret, res, dy):
    with jax.named_scope(SCOPE):
        du, ddt, dAt, dBt, dCt = _scan_bwd_call(
            *res, dy.astype(_F32), interpret)
        return du, ddt, jnp.sum(dAt, axis=0), dBt, dCt


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                   Cm: jax.Array, *,
                   interpret: bool = False) -> Optional[jax.Array]:
    """``modules.selective_scan`` for shapes that fit :func:`tile_plan`,
    else None: ``u`` [B, S, C] in the compute dtype or float32, ``dt`` [B,
    S, C] after softplus, ``A`` [C, N] negative, ``Bm``, ``Cm`` [B, S, N]
    -> ``y`` [B, S, C] float32, differentiable in all five. A sequence that
    ``CHUNK`` does not divide is padded with ``dt = 0`` (no decay, no input)
    and the padding cut off. ``interpret`` comes only from the caller."""
    B, S, C = u.shape
    if tile_plan(C, A.shape[1], S) is None:
        return None
    pad = -S % CHUNK
    dt, Bm, Cm = (t.astype(_F32) for t in (dt, Bm, Cm))
    if pad:
        u, dt, Bm, Cm = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                         for t in (u, dt, Bm, Cm))
    y = _scan(u, dt, A.astype(_F32).T, jnp.swapaxes(Bm, 1, 2),
              jnp.swapaxes(Cm, 1, 2), interpret)
    return y[:, :S] if pad else y


def make_selective_scan(mesh, dp_axes=(), *, interpret: bool = False):
    """The kernels on a mesh (``common.on_shards``): the batch sharded over
    dp, everything else local (a plan that cuts a mamba1 block any other way
    is refused by name, ``eligibility.mamba1_plan_reason``). None where the
    shapes fit no tile."""
    from jax.sharding import PartitionSpec

    wide = batch_spec(3, dp_axes)

    def scan(u, dt, A, Bm, Cm):
        if tile_plan(u.shape[2], A.shape[1], u.shape[1]) is None:
            return None
        return on_shards(
            lambda *a: selective_scan(*a, interpret=interpret), mesh,
            (wide, wide, PartitionSpec(), wide, wide), wide)(
                u, dt, A, Bm, Cm)
    return scan
