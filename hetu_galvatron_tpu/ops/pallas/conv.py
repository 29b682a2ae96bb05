"""Pallas kernels for the causal depthwise convolution
(``modules.causal_depthwise_conv``) and what its callers do around it.

A channel's ``c[t] = sum_j taps[j] * u[t - (L - 1 - j)]``, zeros before the
sequence, with the callers' elementwise work on both sides of it::

    x = pre * u                      (the short-conv block's ``B * X``)
    c = conv(x) + bias               (Mamba-2's bias)
    a = silu(c)                      (Mamba-2, KDA)
    y = a / |a|_head * scale         (KDA's q and k: a head of whole lane tiles)
    o = post * y                     (the short-conv block's ``C *``)

In ``jax.numpy`` the ``L`` shifted products are ``L`` padded float32 passes
over HBM a direction, each kept for the backward, and the taps' gradients
``L`` reductions over the sequence. Here a grid step holds a ``[tS, tC]``
tile of ``u`` in the dtype the caller has, a loop inside it takes ``R`` rows
at a time through the whole chain in float32 registers and writes ``o``
once, in the dtype the caller casts to. The rows before a tile are a second
view of the same operand (``_HIST`` rows; zeros before the sequence), and a
shift by ``b`` rows is a sublane roll of ``[8 rows before | R rows]``.

The backward keeps nothing float32: its residuals are the operands. It
runs the sequence in reverse, makes ``c`` again, takes the cotangent back
through the epilogue to ``dc``, and with ``dc[t + b]`` (a roll the other
way; the ``8`` rows after a sub-block are carried, across tiles in VMEM
scratch) forms both ``dx[t] = sum_b taps[L - 1 - b] dc[t + b]`` and the
taps' gradient ``sum_t x[t] dc[t + b]``, which with the bias's is summed in
a float32 block that stays in VMEM over the sequence axis and is written
once a channel tile.

Arithmetic is the ``jax.numpy`` form's: every product, sum, ``exp`` and
``rsqrt`` float32, the additions of the taps in its order; two bytes only
where the operand and the result have two already.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from hetu_galvatron_tpu.ops.pallas.common import LANES, batch_spec, on_shards

# float32 sublanes: the rows before (after) a sub-block that a shift reads
_SUB = 8
# rows of the view that precedes a tile: a two-byte tile's sublanes
_HIST = 16
# a channel tile's lanes, the most first, and the float32 elements of a
# sub-block of rows, (forward, backward): the backward holds about twice
# the arrays a row. Swept on the chip at the three cells' shapes (PERF.md
# section 6, PR 45): a pass is 2.4x faster at 32 Ki elements than at 4 Ki
# (the loop's chain has more rows to fill the vector unit's slots with),
# the backward with a bias slower again past 16 Ki
TILE_LANES = (512, 256, 128)
SUB_BLOCK = (32 * 1024, 16 * 1024)
# bytes of a grid step's sequence tile of one operand (the backward of the
# gated form holds seven, twice each, inside the 16 MiB a kernel is given)
TILE_BYTES = 1024 * 1024
_F32 = jnp.float32
# a head's L2 norm: (lanes a head, epsilon, a scale for each equal part of
# the channels, None = that part is left as it is)
HeadNorm = Tuple[int, float, Tuple[Optional[float], ...]]


def tile_plan(seq: int, channels: int, itemsize: int = 2,
              head_norm: Optional[HeadNorm] = None
              ) -> Optional[Tuple[int, int]]:
    """(rows, lanes) of a grid step's tile where the kernels fit these
    shapes, else None (the caller keeps the ``jax.numpy`` form): the
    channels whole lane tiles, every part of ``head_norm`` whole channel
    tiles and a head whole lane tiles within one, the sequence one tile at
    least (its last tile may be ragged)."""
    if channels % LANES:
        return None
    head, parts = (head_norm[0], len(head_norm[2])) if head_norm else (0, 1)
    if channels % parts or (head and head % LANES):
        return None
    for lanes in TILE_LANES:
        if (channels // parts) % lanes or (head and lanes % head):
            continue
        rows = TILE_BYTES // (lanes * itemsize)
        while rows > seq and rows > LANES:
            rows //= 2
        return (rows, lanes) if rows <= seq else None
    return None


def _x(refs, rows):
    """``x`` of ``rows``, float32: ``u``, or ``pre * u`` where gated
    (``refs``: ``u``'s alone, or ``pre``'s and ``u``'s)."""
    x = refs[-1][0, rows, :].astype(_F32)
    return x if len(refs) == 1 else refs[0][0, rows, :].astype(_F32) * x


def _rows_before(refs, hist, i, r0):
    """The ``_SUB`` rows of ``x`` before row ``r0`` of a tile: of the tile
    itself, or (sub-block 0) of ``hist``, the view that precedes it."""
    at = pl.multiple_of(jnp.maximum(r0 - _HIST, 0), _HIST)
    return jnp.where(i == 0, hist, _x(refs, pl.ds(at, _HIST)))[_HIST - _SUB:]


def _conv(before, x, taps):
    """``c`` of ``x``'s rows, ``before`` the ``_SUB`` rows that precede
    them, in the order of additions of the ``jax.numpy`` form."""
    L = len(taps)
    ext = jnp.concatenate([before, x], axis=0)
    c = x * taps[L - 1]
    for back in range(1, L):
        # ext[t - back]: rolled rows wrap into the first _SUB, cut off
        c = c + pltpu.roll(ext, back, 0)[_SUB:] * taps[L - 1 - back]
    return c


def _heads(width: int, head: int):
    return [slice(h * head, (h + 1) * head) for h in range(width // head)]


def _over_parts(scales: Sequence[Optional[float]], tiles_a_part: int, run):
    """``run(scale)`` for the part the grid step's channel tile lies in."""
    if len(set(scales)) == 1:
        run(scales[0])
        return
    part = pl.program_id(1) // tiles_a_part
    for p, scale in enumerate(scales):
        pl.when(part == p)(functools.partial(run, scale))


def _operands(refs, has_bias: bool, gated: bool):
    """(what ``x`` is made of, the same of the view before the tile, taps,
    bias, post, the rest): see :func:`_call`."""
    u, uh, taps, *rest = refs
    bias = rest.pop(0) if has_bias else None
    if not gated:
        return (u,), (uh,), taps, bias, None, rest
    pre, ph, post, *rest = rest
    return (pre, u), (ph, uh), taps, bias, post, rest


def _fwd_kernel(*refs, L: int, R: int, silu: bool, has_bias: bool,
                gated: bool, norm: Optional[HeadNorm], tiles_a_part: int):
    x_refs, hist_refs, taps_ref, bias_ref, post_ref, (o_ref,) = _operands(
        list(refs), has_bias, gated)
    tS, tC = o_ref.shape[1:]
    # zeros before the sequence (read out here: interpret mode knows no
    # program_id inside a branch)
    first = pl.program_id(2) == 0

    def run(scale):
        taps = [taps_ref[j:j + 1, :] for j in range(L)]
        hist = jnp.where(first, 0.0, _x(hist_refs, slice(None)))

        def rows(i, _):
            r0 = pl.multiple_of(i * R, R)
            at = pl.ds(r0, R)
            x = _x(x_refs, at)
            c = _conv(_rows_before(x_refs, hist, i, r0), x, taps)
            if has_bias:
                c = c + bias_ref[...]
            if silu:
                c = c * jax.nn.sigmoid(c)
            for lanes in _heads(tC, norm[0] if scale is not None else tC):
                y = c[:, lanes]
                if scale is not None:
                    y = y * (jax.lax.rsqrt(jnp.sum(
                        jnp.square(y), axis=-1, keepdims=True) + norm[1])
                             * scale)
                if gated:
                    y = post_ref[0, at, lanes].astype(_F32) * y
                o_ref[0, at, lanes] = y.astype(o_ref.dtype)
            return 0

        jax.lax.fori_loop(0, tS // R, rows, 0)

    _over_parts(norm[2] if norm else (None,), tiles_a_part, run)


def _group_sums(v):
    """``[R, tC]`` summed to its ``_SUB`` sublanes: register adds."""
    out = v[:_SUB]
    for k in range(1, v.shape[0] // _SUB):
        out = out + v[k * _SUB:(k + 1) * _SUB]
    return out


def _bwd_kernel(*refs, L: int, R: int, seq: int, silu: bool, has_bias: bool,
                gated: bool, norm: Optional[HeadNorm], tiles_a_part: int):
    x_refs, hist_refs, taps_ref, bias_ref, post_ref, rest = _operands(
        list(refs), has_bias, gated)
    dy_ref, du_ref, acc_ref, *rest = rest
    dpre_ref, dpost_ref = (rest.pop(0), rest.pop(0)) if gated else (None,
                                                                    None)
    after_ref, = rest
    tS, tC = du_ref.shape[1:]
    n = tS // R
    # the sequence in reverse: grid step 0 holds its last tile
    tile = pl.num_programs(2) - 1 - pl.program_id(2)
    ragged = seq % tS != 0

    @pl.when(pl.program_id(2) == 0)
    def _init():    # nothing after the sequence, nothing summed yet
        after_ref[...] = jnp.zeros_like(after_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def run(scale):
        taps = [taps_ref[j:j + 1, :] for j in range(L)]
        hist = jnp.where(tile == 0, 0.0, _x(hist_refs, slice(None)))

        def rows(k, after):
            i = n - 1 - k
            r0 = pl.multiple_of(i * R, R)
            at = pl.ds(r0, R)
            x = _x(x_refs, at)
            c = _conv(_rows_before(x_refs, hist, i, r0), x, taps)
            if has_bias:
                c = c + bias_ref[...]
            s = jax.nn.sigmoid(c) if silu else None
            a = c * s if silu else c
            dy = dy_ref[0, at, :].astype(_F32)
            da = []
            for lanes in _heads(tC, norm[0] if scale is not None else tC):
                y, g = a[:, lanes], dy[:, lanes]
                if gated:
                    post = post_ref[0, at, lanes].astype(_F32)
                    g, g_out = post * g, g
                if scale is not None:
                    # y = a r scale, r = (sum a^2 + eps)^-1/2
                    r = jax.lax.rsqrt(jnp.sum(
                        jnp.square(y), axis=-1, keepdims=True) + norm[1])
                    along = jnp.sum(g * y, axis=-1, keepdims=True)
                    g = (g - y * (jnp.square(r) * along)) * (r * scale)
                    y = y * (r * scale)
                if gated:
                    dpost_ref[0, at, lanes] = (g_out * y).astype(
                        dpost_ref.dtype)
                da.append(g)
            dc = da[0] if len(da) == 1 else jnp.concatenate(da, axis=1)
            if silu:
                dc = dc * (s * (1.0 + c * (1.0 - s)))
            if ragged:   # rows past the sequence hold anything
                valid = (tile * tS + r0 + jax.lax.broadcasted_iota(
                    jnp.int32, (R, 1), 0)) < seq
                dc, x = jnp.where(valid, dc, 0.0), jnp.where(valid, x, 0.0)
            ext = jnp.concatenate([dc, after], axis=0)
            dx = dc * taps[L - 1]
            sums = [dc, x * dc]
            for b in range(1, L):
                ahead = pltpu.roll(ext, R + _SUB - b, 0)[:R]    # dc[t + b]
                dx = dx + ahead * taps[L - 1 - b]
                sums.append(x * ahead)
            # rows of ``acc``: the bias's, then tap L-1, L-2, ..., 0
            for j, v in enumerate(sums[0 if has_bias else 1:]):
                acc_ref[0, j * _SUB:(j + 1) * _SUB, :] += _group_sums(v)
            if gated:
                pre, u = (_x((ref,), at) for ref in x_refs)
                dpre_ref[0, at, :] = (dx * u).astype(dpre_ref.dtype)
                dx = dx * pre
            du_ref[0, at, :] = dx.astype(du_ref.dtype)
            return dc[:_SUB]

        after_ref[...] = jax.lax.fori_loop(0, n, rows, after_ref[...])

    _over_parts(norm[2] if norm else (None,), tiles_a_part, run)


def _call(kernel, u, taps, bias, pre, post, silu, norm, backward: bool):
    """What both ``pallas_call``s share: (the kernel with its statics, the
    grid, the operands both read, their specs, a sequence tile's spec).
    ``taps`` go in as rows ``[_SUB, C]`` (tap j along lanes in row j),
    ``bias`` as ``[1, C]``; the backward takes the sequence's tiles in
    reverse."""
    B, S, C = u.shape
    L = taps.shape[1]
    tS, tC = tile_plan(S, C, u.dtype.itemsize, norm)
    nS = pl.cdiv(S, tS)
    at = (lambda s: nS - 1 - s) if backward else (lambda s: s)
    tile = pl.BlockSpec((1, tS, tC), lambda b, j, s: (b, at(s), j))
    hist = pl.BlockSpec(
        (1, _HIST, tC),
        lambda b, j, s: (b, jnp.maximum(at(s) * (tS // _HIST) - 1, 0), j))
    row = lambda n: pl.BlockSpec((n, tC), lambda b, j, s: (0, j))
    args = [u, u, jnp.pad(taps.astype(_F32).T, ((0, _SUB - L), (0, 0)))]
    specs = [tile, hist, row(_SUB)]
    if bias is not None:
        args.append(bias.astype(_F32)[None])
        specs.append(row(1))
    if pre is not None:
        args += [pre, pre, post]
        specs += [tile, hist, tile]
    kernel = functools.partial(
        kernel, L=L, R=min(SUB_BLOCK[backward] // tC, tS), silu=silu,
        has_bias=bias is not None, gated=pre is not None, norm=norm,
        tiles_a_part=C // (len(norm[2]) if norm else 1) // tC)
    return kernel, (B, C // tC, nS), args, specs, tile


def _fwd_call(u, taps, bias, pre, post, silu, norm, out_dtype, interpret):
    kernel, grid, args, specs, tile = _call(
        _fwd_kernel, u, taps, bias, pre, post, silu, norm, backward=False)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=specs, out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(u.shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        # the kernels' instruction names on a trace's ``XLA Ops`` line
        name="causal_conv_fwd",
    )(*args)


def _bwd_call(u, taps, bias, pre, post, dy, silu, norm, interpret):
    B, S, C = u.shape
    kernel, grid, args, specs, tile = _call(
        functools.partial(_bwd_kernel, seq=S), u, taps, bias, pre, post,
        silu, norm, backward=True)
    sums = taps.shape[1] + (bias is not None)
    tC = tile.block_shape[2]
    out_specs = [tile, pl.BlockSpec((1, sums * _SUB, tC),
                                    lambda b, j, s: (b, 0, j))]
    out_shape = [jax.ShapeDtypeStruct(u.shape, u.dtype),
                 jax.ShapeDtypeStruct((B, sums * _SUB, C), _F32)]
    if pre is not None:
        out_specs += [tile, tile]
        out_shape += [jax.ShapeDtypeStruct(pre.shape, pre.dtype),
                      jax.ShapeDtypeStruct(post.shape, post.dtype)]
    du, acc, *gates = pl.pallas_call(
        kernel, grid=grid, in_specs=specs + [tile], out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=[pltpu.VMEM((_SUB, tC), _F32)],
        # the sequence axis is innermost and sequential: it carries the
        # rows after a tile and the sums
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="causal_conv_bwd",
    )(*args, dy)
    acc = jnp.sum(acc.reshape(B, sums, _SUB, C), axis=(0, 2))   # [sums, C]
    dbias = None
    if bias is not None:
        dbias, acc = acc[0].astype(bias.dtype), acc[1:]
    dtaps = acc[::-1].T.astype(taps.dtype)
    return (du, dtaps, dbias) + (tuple(gates) if gates else (None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _conv_op(u, taps, bias, pre, post, silu, norm, out_dtype, scope,
             interpret):
    return _fwd_call(u, taps, bias, pre, post, silu, norm, out_dtype,
                     interpret)


def _conv_op_fwd(u, taps, bias, pre, post, silu, norm, out_dtype, scope,
                 interpret):
    return (_fwd_call(u, taps, bias, pre, post, silu, norm, out_dtype,
                      interpret), (u, taps, bias, pre, post))


def _conv_op_bwd(silu, norm, out_dtype, scope, interpret, res, dy):
    # a backward rule does not inherit the scope its forward was called in
    with jax.named_scope(scope):
        return _bwd_call(*res, dy, silu, norm, interpret)


_conv_op.defvjp(_conv_op_fwd, _conv_op_bwd)


def causal_conv(u: jax.Array, taps: jax.Array,
                bias: Optional[jax.Array] = None, *,
                pre: Optional[jax.Array] = None,
                post: Optional[jax.Array] = None, silu: bool = False,
                head_norm: Optional[HeadNorm] = None, out_dtype=None,
                scope: str, interpret: bool = False) -> Optional[jax.Array]:
    """``modules.causal_depthwise_conv`` with its epilogue, for shapes that
    fit :func:`tile_plan`, else None: ``u`` (and the gates ``pre``,
    ``post``, both or neither) [B, S, C], ``taps`` [C, L] with ``L`` at
    most ``_SUB``, ``bias`` [C] -> [B, S, C] in ``out_dtype``,
    differentiable in all five. ``scope``: the ``jax.named_scope`` path the
    caller is under, which the backward opens again. ``interpret`` comes
    only from the caller."""
    if (tile_plan(u.shape[1], u.shape[2], u.dtype.itemsize, head_norm) is None
            or not 1 <= taps.shape[1] <= _SUB):
        return None
    if (pre is None) != (post is None):
        raise ValueError("the gates come as a pair: pre and post")
    if pre is not None:
        pre, post = pre.astype(u.dtype), post.astype(u.dtype)
    return _conv_op(u, taps, bias, pre, post, silu, head_norm,
                    jnp.dtype(out_dtype or u.dtype), scope, interpret)


def make_causal_conv(mesh, dp_axes=(), tp_axes=(), *,
                     interpret: bool = False):
    """The kernels on a mesh (``common.on_shards``): the batch sharded over
    dp and the channels over tp where the layer cuts them (a depthwise
    convolution is local to a channel shard; the parts of a ``head_norm``
    are not, and the blocks that norm are never cut). None where a device's
    shapes fit no tile."""
    from hetu_galvatron_tpu.runtime.mesh import axes_size

    chan = tp_axes or None
    wide = batch_spec(3, dp_axes, (2, tp_axes))

    def conv(u, taps, bias=None, *, pre=None, post=None, head_norm=None,
             **static):
        local = u.shape[2] // axes_size(mesh, tp_axes)
        if (head_norm is not None and tp_axes) or tile_plan(
                u.shape[1], local, u.dtype.itemsize, head_norm) is None:
            return None
        args = [(u, wide), (taps, P(chan, None))]
        names = []
        for name, a, spec in (("bias", bias, P(chan)), ("pre", pre, wide),
                              ("post", post, wide)):
            if a is not None:
                names.append(name)
                args.append((a, spec))

        def local_conv(u, taps, *rest):
            return causal_conv(u, taps, head_norm=head_norm,
                               interpret=interpret,
                               **dict(zip(names, rest)), **static)
        return on_shards(local_conv, mesh, tuple(s for _, s in args), wide)(
            *(a for a, _ in args))
    return conv
