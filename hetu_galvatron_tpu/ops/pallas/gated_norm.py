"""Pallas kernels for Mamba-2's gated norm and the skip in front of it
(``modules.mamba_gated_norm``): everything between the scan's result and
``out_proj``'s operand, a row at a time::

    u = y + D x                      (the skip; D one number a head)
    a = u * silu(z)                  (the gate)
    o = a * rsqrt(mean_g(a^2) + eps) * scale

``mean_g`` over the ``C / groups`` channels of a group (Nemotron-H: eight
groups of 512; Granite-4.0-H: one of 4096). In ``jax.numpy`` a group is a
minor dimension of its own, ``[B, S, G, C / G]``: on a TPU the second-minor
dimension then changes from positions to groups, so the view and the view
back are two relayouts of a float32 array a pass, and XLA fuses nothing
across them. Here a grid step holds ``rows`` whole rows of every operand as
they lie, ``[B, S, C]`` with the channels along the lanes; a group is a
static range of whole lane tiles and its mean square a lane reduction of
that slice. A loop inside the step takes ``_ROWS`` rows at a time through
the whole chain in float32 and writes ``o`` once, in the dtype the caller
casts to.

The backward keeps nothing float32: its residuals are the operands (the
scan's ``y``, which per-layer remat keeps, ``x``, ``z``, ``D``, the scale).
It makes ``u``, ``a`` and the group's statistic again, and writes ``dy``,
``dx`` (the skip's share), ``dz`` in one pass; the gradients of the scale
and of ``D`` (a channel's, summed to its head outside) are summed over the
rows in a float32 block that stays in VMEM over the sequence axis.

Arithmetic is the ``jax.numpy`` form's: every product, sum, ``exp`` and
``rsqrt`` float32, in its order; two bytes only where an operand or the
result has two already.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from hetu_galvatron_tpu.ops.pallas.common import LANES, batch_spec, on_shards
# ``_SUB``: float32 sublanes, the rows ``_group_sums`` keeps a channel's
# sums in
from hetu_galvatron_tpu.ops.pallas.conv import _SUB, _group_sums

# rows the loop inside a grid step takes at a time: a two-byte tile's
# sublanes
_ROWS = 16
# bytes of one grid step's operands and results, (forward, backward); the
# pipeline holds them twice inside the 16 MiB a kernel is given
TILE_BYTES = (5 * 1024 * 1024, 5 * 1024 * 1024)
_F32 = jnp.float32


def tile_plan(seq: int, channels: int, groups: int, itemsize: int = 2,
              backward: bool = False) -> Optional[int]:
    """The rows of a grid step's tile where the kernels fit these shapes,
    else None (the caller keeps the ``jax.numpy`` form): a group whole lane
    tiles, the sequence ``_ROWS`` rows at least (its last tile may be
    ragged). ``itemsize``: of ``x``, ``z`` and the result; ``y`` and its
    cotangent are float32."""
    if channels % groups or (channels // groups) % LANES or seq < _ROWS:
        return None
    a_row = channels * ((4 + 3 * itemsize) + backward * (4 + 2 * itemsize))
    rows = max(TILE_BYTES[backward] // a_row, _ROWS)
    return min(rows, seq) // _ROWS * _ROWS


def _gated(y_ref, x_ref, z_ref, d_ref, at, lanes):
    """``(x, u, z, sigmoid(z), a)`` of the rows ``at`` and the lanes
    ``lanes``, float32."""
    x = x_ref[0, at, lanes].astype(_F32)
    u = y_ref[0, at, lanes].astype(_F32) + d_ref[:, lanes] * x
    z = z_ref[0, at, lanes].astype(_F32)
    s = jax.nn.sigmoid(z)
    return x, u, z, s, u * (z * s)


def _over_rows(rows: int, groups: int, width: int, body):
    """``body(r0, lanes)`` for every ``_ROWS`` rows of a tile of ``rows``,
    from row ``r0`` on, and every group's ``width`` lanes."""
    def some(i, _):
        r0 = pl.multiple_of(i * _ROWS, _ROWS)
        for g in range(groups):
            body(r0, slice(g * width, (g + 1) * width))
        return 0

    jax.lax.fori_loop(0, rows // _ROWS, some, 0)


def _fwd_kernel(y_ref, x_ref, z_ref, d_ref, w_ref, o_ref, *, groups: int,
                eps: float):
    tS, C = o_ref.shape[1:]

    def body(r0, lanes):
        at = pl.ds(r0, _ROWS)
        a = _gated(y_ref, x_ref, z_ref, d_ref, at, lanes)[-1]
        r = jax.lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True)
                          + eps)
        o_ref[0, at, lanes] = (a * r * w_ref[:, lanes]).astype(o_ref.dtype)

    _over_rows(tS, groups, C // groups, body)


def _bwd_kernel(y_ref, x_ref, z_ref, d_ref, w_ref, do_ref, dy_ref, dx_ref,
                dz_ref, acc_ref, *, groups: int, eps: float, seq: int):
    tS, C = dy_ref.shape[1:]
    ragged = seq % tS != 0
    # read out here: interpret mode knows no program_id inside a loop
    row0 = pl.program_id(1) * tS

    @pl.when(pl.program_id(1) == 0)
    def _init():    # nothing summed yet
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(r0, lanes):
        at = pl.ds(r0, _ROWS)
        x, u, z, s, a = _gated(y_ref, x_ref, z_ref, d_ref, at, lanes)
        # o = n scale, n = a r, r = (mean a^2 + eps)^-1/2
        r = jax.lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True)
                          + eps)
        n = a * r
        do = do_ref[0, at, lanes].astype(_F32)
        dn = do * w_ref[:, lanes]
        da = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
        du = da * (z * s)
        dz = da * u * (s * (1.0 + z * (1.0 - s)))
        dy_ref[0, at, lanes] = du.astype(dy_ref.dtype)
        dx_ref[0, at, lanes] = (du * d_ref[:, lanes]).astype(dx_ref.dtype)
        dz_ref[0, at, lanes] = dz.astype(dz_ref.dtype)
        dw, dd = do * n, du * x
        if ragged:   # rows past the sequence hold anything
            valid = (row0 + r0 + jax.lax.broadcasted_iota(
                jnp.int32, (_ROWS, 1), 0)) < seq
            dw, dd = jnp.where(valid, dw, 0.0), jnp.where(valid, dd, 0.0)
        # rows of ``acc``: the scale's sums, then D's by channel
        acc_ref[0, :_SUB, lanes] += _group_sums(dw)
        acc_ref[0, _SUB:, lanes] += _group_sums(dd)

    _over_rows(tS, groups, C // groups, body)


def _specs(y, x, groups: int, backward: bool):
    """(the grid, a row tile's spec, a ``[1, C]`` row's spec) of a call."""
    B, S, C = y.shape
    tS = tile_plan(S, C, groups, x.dtype.itemsize, backward)
    return ((B, pl.cdiv(S, tS)),
            pl.BlockSpec((1, tS, C), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, C), lambda b, s: (0, 0)))


def _fwd_call(y, x, z, d, w, groups, eps, out_dtype, interpret):
    grid, tile, row = _specs(y, x, groups, backward=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=groups, eps=eps),
        grid=grid, in_specs=[tile, tile, tile, row, row], out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(y.shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        # the kernels' instruction names on a trace's ``XLA Ops`` line
        name="gated_norm_fwd",
    )(y, x, z, d, w)


def _bwd_call(y, x, z, d, w, do, groups, eps, interpret):
    B, S, C = y.shape
    grid, tile, row = _specs(y, x, groups, backward=True)
    dy, dx, dz, acc = pl.pallas_call(
        functools.partial(_bwd_kernel, groups=groups, eps=eps, seq=S),
        grid=grid, in_specs=[tile, tile, tile, row, row, tile],
        out_specs=[tile, tile, tile,
                   pl.BlockSpec((1, 2 * _SUB, C), lambda b, s: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((B, 2 * _SUB, C), _F32)],
        # the sequence axis is innermost and sequential: it carries the sums
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gated_norm_bwd",
    )(y, x, z, d, w, do)
    dw, dd = jnp.sum(acc.reshape(B, 2, _SUB, C), axis=(0, 2))[:, None]
    return dy, dx, dz, dd, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _norm_op(y, x, z, d, w, groups, eps, out_dtype, scope, interpret):
    return _fwd_call(y, x, z, d, w, groups, eps, out_dtype, interpret)


def _norm_op_fwd(y, x, z, d, w, groups, eps, out_dtype, scope, interpret):
    return (_fwd_call(y, x, z, d, w, groups, eps, out_dtype, interpret),
            (y, x, z, d, w))


def _norm_op_bwd(groups, eps, out_dtype, scope, interpret, res, do):
    # a backward rule does not inherit the scope its forward was called in
    with jax.named_scope(scope):
        return _bwd_call(*res, do, groups, eps, interpret)


_norm_op.defvjp(_norm_op_fwd, _norm_op_bwd)


def gated_norm(y: jax.Array, x: jax.Array, z: jax.Array, D: jax.Array,
               scale: jax.Array, *, groups: int, eps: float, out_dtype,
               scope: str, interpret: bool = False) -> Optional[jax.Array]:
    """``modules.mamba_gated_norm`` for shapes that fit :func:`tile_plan`,
    else None: ``y`` [B, S, C] float32 (the scan's result), ``x`` and ``z``
    [B, S, C] in the compute dtype, ``D`` [heads] (a head ``C / heads``
    channels), ``scale`` [C] -> [B, S, C] in ``out_dtype``, differentiable
    in all five.
    ``scope``: the ``jax.named_scope`` path the caller is under, which the
    backward opens again. ``interpret`` comes only from the caller."""
    _, S, C = y.shape
    if tile_plan(S, C, groups, x.dtype.itemsize) is None:
        return None
    d = jnp.repeat(D.astype(_F32), C // D.shape[0])[None]
    return _norm_op(y, x, z, d, scale.astype(_F32)[None], groups, eps,
                    jnp.dtype(out_dtype), scope, interpret)


def make_gated_norm(mesh, dp_axes=(), *, interpret: bool = False):
    """The kernels on a mesh (``common.on_shards``): the batch sharded over
    dp, everything else local (a plan that cuts a mamba block any other way
    is refused by name, ``eligibility.mamba_plan_reason``). None where the
    shapes fit no tile."""
    wide = batch_spec(3, dp_axes)

    def norm(y, x, z, D, scale, *, groups, **static):
        if tile_plan(y.shape[1], y.shape[2], groups,
                     x.dtype.itemsize) is None:
            return None
        return on_shards(
            lambda *a: gated_norm(*a, groups=groups, interpret=interpret,
                                  **static), mesh,
            (wide, wide, wide, P(), P()), wide)(y, x, z, D, scale)
    return norm
