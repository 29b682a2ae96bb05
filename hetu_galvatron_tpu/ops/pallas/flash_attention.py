"""Pallas flash attention for TPU: causal, GQA-aware, online-softmax.

Replaces the reference's external flash-attn CUDA ops (SURVEY §2 native-code
checklist item 4; installed by galvatron/scripts/flash_attn_ops_install.sh)
with three TPU kernels. The [S, S] score matrix never exists: every kernel
works on one ``block_q x block_k`` score tile at a time.

Layout. A call hands the kernels what the projections wrote: q
[B, S, N, D] is the bytes of [B, S, N * D] rows, and a (1, block_q, 128)
block at column block ``c`` of that array is a legal, unpadded Mosaic tile.
At head widths of whole lane tiles (128, 256) column block ``n`` IS head
``n`` and the kernel bodies see the [block_q, D] tiles they always saw;
only the ``BlockSpec``s index otherwise. At width 64 a column block holds
heads ``2p`` and ``2p + 1`` side by side and one grid step serves the pair:
head ``h``'s scores are ``where(lane is h's, q2, 0) . k2^T`` over the whole
128-deep contraction (the other head's lanes add exact zeros, and the
MXU's depth, half empty at width 64, costs the passes it did), each head
has statistics and an accumulator of its own, and its half of the result
is selected at the end. Under GQA a pair's key/value heads are one head or
the two halves of ONE key/value column block; a query head whose half is
not its key/value head's is moved there once a q tile (``_half_view``).
Selects, never products: the last column block of an odd count of heads
(GPT-2 XL's 25) is half outside the array and holds anything there. GQA
maps q-head n to kv-head n // (N // K) in the index map. Outputs, dq, dk
and dv come out as rows too, so nothing is transposed around a call
(``row_layout`` decides from the widths alone; twelve transposes a layer
under per-layer remat until PR 50, each a copy XLA could fuse into
nothing, of arrays that head-major at width 64 are padded to twice their
numbers). Every other width (192 of the latent cores) and the ring
(``ops/ring_attention.py``, on head-major blocks of its own) run the same
kernel bodies on head-major [B, N, S, D] operands between transposes
(``flash_attention_hmajor`` / ``flash_attention_bwd_hmajor``), the program
they always were. v has a width of its own (latent attention: q/k 192, v
128): the output, dO, dv and their accumulators are ``Dv`` wide, q, k, dq
and dk ``D`` wide, and no operand is padded to the other's width.

The tile loop. A grid step of the forward and of the dq kernel holds one q
tile and a MAJOR block of K and V (the whole length where it fits
``_RESIDENT_BYTES``, so the next step of the same head fetches nothing) and
loops inside the kernel over its ``block_k``-row chunks, up to the diagonal
when causal: first the chunks wholly at or below it, with no mask built at
all, then the one or two that cross it, with the mask. Chunks above the
diagonal are never visited and major blocks above it never fetched (their
block index repeats the last needed one). The dk/dv kernel is the mirror
image: a k/v tile stays, q / dO / lse / delta come as a major block and the
loop runs over q chunks from the diagonal down; its score tiles are [k, q]
(keys along sublanes), so that p^T.dO and ds^T.q are plain products and lse
and delta broadcast along sublanes from (1, block_q) rows. Segment and
dropout masks are applied on every tile they are given for, and segment
ids bound the loops as the diagonal and a window do: from the ids' least
and greatest value a tile and a chunk (``segment_chunk_ranges``, a few
reductions outside the kernels) every q tile has a range of k chunks and
every k tile one of q chunks outside which no query shares an id with a
key; the ranges ride in as prefetched scalars, the loops run over their
intersection with the causal or banded range, and the index maps clamp the
major block to it, so that a tile pair the segments empty (the images of a
tower's packed patches, packed documents) is neither copied nor computed.
A call without ids is the ``pallas_call`` it always was.

Arithmetic: matmul operands stay in the inputs' dtype with f32 accumulation
(``preferred_element_type``); scores, the softmax statistics, exp, lse,
delta and all accumulators are f32; p and ds are cast to the inputs' dtype
for their products, as ``modules.xla_sdpa`` casts its probabilities. The
forward's running max lives replicated along 128 lanes and its normalizer
as 128 partial sums a row (one cross-lane reduction a chunk, one more at the
end). The backward recomputes p per tile from the saved logsumexp, so
neither direction materializes [S, S]. Block sizes come from the call's
shapes (``choose_blocks``).

What a rematted block keeps. The differentiated forward (``_flash_fwd``)
names the two results the backward kernels read, ``KEPT_OUT`` (the output)
and ``KEPT_LSE`` (the row statistics), and ``modules.remat`` saves what
carries those names, so the recomputed forward of a block has no forward
kernel left in it: the S x S work of a layer runs once. Both are kept in
the layout HBM does not pad. The output as [B, S, N * Dv] rows, the form
the out-projection reads and exactly the bytes of the block's input, which
remat keeps anyway: on rows it is the forward kernel's own result and the
backward kernels read it as it lies; on the head-major path it is the
kernel's output turned once, and the backward turns it back (head-major
[B, N, S, Dv] at a head width of 64 is padded to 128 lanes, twice its
numbers: 0.83 GiB against 0.41 over GPT-2 XL's 16 layers at batch 8; AOT,
PR 41). lse as [B, N, S] rows, not as the [B, N, S, 1] column the kernel
writes: a trailing singleton is padded 128 times (64 to 105 MiB a call at
the benchmark's shapes, more than the output); the dk/dv kernel reads lse
as rows anyway and the dq kernel gets its column by ``[..., None]`` (two
relayouts of a padded array a layer, which a forward that wrote the rows
itself would save: PERF.md section 7). An odd count of paired heads keeps
the statistics of one head more. delta = rowsum(dO . O) is dk/dv's other
row statistic: on the head-major path XLA sums it; on rows the dq kernel,
which forms it for its own tile anyway, writes it as the rows the dk/dv
kernel reads (XLA would first turn the whole f32 product to put the
positions along lanes). q, k and v are not named: the projection
recomputes them as before.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_galvatron_tpu.ops.pallas.common import LANES, on_shards

NEG_INF = float(jnp.finfo(jnp.float32).min)

# ``checkpoint_name``s of the forward's two results that the backward kernels
# read; ``modules.remat`` keeps the values under the names of ``KEPT``
KEPT_OUT = "flash_attention_out"
KEPT_LSE = "flash_attention_lse"
KEPT = (KEPT_OUT, KEPT_LSE)


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


def _tile_pos(q0, k0, shape, q_axis: int = 0):
    """Global (q position, k position) of every element of a score tile
    that starts at q position q0 and k position k0; q runs along
    ``q_axis`` of ``shape``, k along the other."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return qpos, kpos


# a @ b.T and a @ b: operands in their own dtype, f32 accumulation
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _scores(q, k, q0, k0, qseg, kseg, *, masked: bool, scale: float,
            k_rows: bool = False, window: "int | None" = None):
    """f32 score tile q.k^T * scale (k.q^T, keys along rows, with
    ``k_rows``) with the causal mask (only where the caller says the tile
    crosses the diagonal) and the segment mask (on every tile it is given
    for; qseg and kseg broadcast against each other to the tile). A masked
    score is NEG_INF: once a query has seen one finite score its masked
    entries give exp(NEG_INF - m) = 0, and whatever it gathered while all
    it had seen was masked is wiped by the rescale exp(NEG_INF - m) = 0 at
    its first finite score; causal, band and segment masks all leave every
    query its own position. With ``window`` a masked tile also loses the
    keys at or beyond ``window`` positions before the query."""
    s = (_dot(k, q, _NT) if k_rows else _dot(q, k, _NT)) * scale
    if masked:
        qpos, kpos = _tile_pos(q0, k0, s.shape, int(k_rows))
        seen = qpos >= kpos
        if window is not None:
            seen &= qpos - kpos < window
        s = jnp.where(seen, s, NEG_INF)
    if qseg is not None:
        s = jnp.where(qseg == kseg, s, NEG_INF)
    return s


def _across(x, width: int):
    """A lane-replicated (rows, LANES) array as (rows, width): the same
    registers again where ``width`` is whole lane tiles, no relayout."""
    if width % LANES == 0:
        return jnp.concatenate([x] * (width // LANES), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _lane_sums(p):
    """Row sums of p left as LANES partial sums a row (register adds of
    p's lane tiles; a ragged width is summed across lanes into lane 0)."""
    rows, width = p.shape
    if width % LANES == 0:
        return sum(p[:, j:j + LANES] for j in range(0, width, LANES))
    lane0 = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1) == 0
    return jnp.where(lane0, jnp.sum(p, axis=1, keepdims=True), 0.0)


def _for(lo, hi, body):
    """``body(c)`` for c in [lo, hi); the bounds may be traced (an empty
    range costs a compare)."""
    jax.lax.fori_loop(lo, hi, lambda c, _: body(c), None)


def _for_banded(chunk, lo, chunks: int, first, clear_from, clear_to, end):
    """``chunk(c, masked)`` over the chunks [first, end) of a band, global
    indices, of which those in [clear_from, clear_to) no edge of the band
    cuts: the cut ones before them with the mask, they without, the cut
    ones after them with it. ``lo`` is the global index of the grid step's
    first chunk and ``chunks`` how many it holds; an empty middle leaves
    every chunk masked."""
    a = jnp.clip(first - lo, 0, chunks)
    d = jnp.clip(end - lo, 0, chunks)
    b = jnp.clip(clear_from - lo, a, d)
    c = jnp.clip(clear_to - lo, b, d)
    _for(a, b, lambda i: chunk(i, True))
    _for(b, c, lambda i: chunk(i, False))
    _for(c, d, lambda i: chunk(i, True))


def segment_chunk_ranges(segments, rows_block: int, cols_block: int):
    """``(first, end)``, int32 ``[..., S // rows_block]``: the chunks of
    ``cols_block`` positions that each tile of ``rows_block`` positions of
    the segment ids ``[..., S]`` can meet, as the range ``[first, end)``
    that a kernel loop runs over. A chunk is needed by a tile iff the closed
    intervals of their least and greatest ids overlap, and the range spans
    the first needed chunk to the last. An id that a query of the tile
    shares with a key of the chunk lies in both intervals, so for ANY ids
    the range holds every chunk in which ``_scores``' segment mask leaves a
    pair (a chunk outside it would be masked whole); for ids that do not
    decrease along the sequence (images one after the other, packed
    documents) it is exactly those. A tile's own positions lie in chunks it
    needs, so no range is empty. Array methods only: a NumPy row (a
    launcher's count, ``two_way_tiles``) and a traced array (the kernels'
    bounds) go the same way."""
    tiles = segments.reshape(*segments.shape[:-1], -1, rows_block)
    chunks = segments.reshape(*segments.shape[:-1], -1, cols_block)
    tile_lo, tile_hi = tiles.min(-1)[..., None], tiles.max(-1)[..., None]
    chunk_lo = chunks.min(-1)[..., None, :]
    chunk_hi = chunks.max(-1)[..., None, :]
    needed = (tile_lo <= chunk_hi) & (chunk_lo <= tile_hi)
    first = needed.argmax(-1)
    end = needed.shape[-1] - needed[..., ::-1].argmax(-1)
    return first.astype("int32"), end.astype("int32")


def _for_two_way(chunk, lo, chunks: int, seg):
    """``chunk(c, False)`` over the ``chunks`` chunks from global chunk
    ``lo`` on of a call that is not causal: all of them, or those of them
    in ``seg``'s ``[first, end)``."""
    if seg is None:
        _for(0, chunks, lambda c: chunk(c, False))
    else:
        _for(jnp.clip(seg[0] - lo, 0, chunks),
             jnp.clip(seg[1] - lo, 0, chunks), lambda c: chunk(c, False))


def _for_k_chunks(chunk, q0, block_q: int, block_k: int, lo, chunks: int,
                  causal: bool, window: "int | None" = None, seg=None):
    """``chunk(c, masked)`` over those of the ``chunks`` k chunks from global
    chunk ``lo`` on that the q rows [q0, q0 + block_q) see: first the ones
    wholly at or below the diagonal, without the mask, then the ones that
    cross it, with it; all of them, unmasked, when not causal. With a
    ``window`` the range starts at the chunk that holds the oldest key the
    tile's first query meets, and the chunks the band's lower edge cuts are
    masked as well. ``seg``, the tile's ``(first, end)`` of
    ``segment_chunk_ranges``, bounds every one of these ranges: a chunk
    whose ids none of the tile's queries can share is not visited (the
    chunks of the tile's own positions always are, so the diagonal's end
    stands)."""
    if not causal:
        _for_two_way(chunk, lo, chunks, seg)
        return
    if window is not None:
        first = jnp.maximum(q0 - window + 1, 0) // block_k
        # the first chunk the tile's LAST query still holds whole
        clear_from = ((jnp.maximum(q0 + block_q - window, 0) + block_k - 1)
                      // block_k)
        clear_to = (q0 + 1) // block_k
        end = (q0 + block_q - 1) // block_k + 1
        if seg is not None:
            first, end = jnp.maximum(first, seg[0]), jnp.minimum(end, seg[1])
        _for_banded(chunk, lo, chunks, first, clear_from, clear_to, end)
        return
    full = jnp.clip((q0 + 1) // block_k - lo, 0, chunks)
    some = jnp.clip((q0 + block_q - 1) // block_k + 1 - lo, 0, chunks)
    first = 0
    if seg is not None:
        first = jnp.clip(seg[0] - lo, 0, chunks)
        full = jnp.maximum(full, first)
    _for(first, full, lambda c: chunk(c, False))
    _for(full, some, lambda c: chunk(c, True))


def _held_to(j, lo, hi, seg):
    """Major block index ``j`` held to ``[lo, hi]`` (None: no bound on that
    side) and to ``seg``, a tile's ``(first, end)`` of
    ``segment_chunk_ranges`` in major blocks (None: no segments): a block
    outside repeats the nearest inside, and an unchanged block index elides
    the copy."""
    if seg is not None:
        lo = seg[0] if lo is None else jnp.maximum(lo, seg[0])
        hi = seg[1] - 1 if hi is None else jnp.minimum(hi, seg[1] - 1)
    if lo is None:
        return j if hi is None else jnp.minimum(j, hi)
    return jnp.maximum(j, lo) if hi is None else jnp.clip(j, lo, hi)


def _needed_k_major(qi, kj, block_q: int, major: int, causal: bool,
                    window: "int | None" = None, seg=None):
    """Index map of a k major block: one wholly past the diagonal repeats
    the last that is needed, and an unchanged block index elides the copy;
    with a ``window`` one wholly before the band repeats the first. ``seg``,
    the q tile's range in major blocks, clamps the same way: a major block
    none of whose chunks the segments leave the tile is not copied."""
    lo = hi = None
    if causal and window is not None:
        lo = jnp.maximum(qi * block_q - window + 1, 0) // major
    if causal:
        hi = (qi * block_q + block_q - 1) // major
    return _held_to(kj, lo, hi, seg)


def _tile(ref, rows=slice(None)):
    """The [rows, width] tile of a block whose leading dims are all 1: one
    head's of a head-major [B, N, S, width] array, or a column block's of
    the projections' rows [B, S, N * width]."""
    return ref[(0,) * (len(ref.shape) - 2) + (rows, slice(None))]


def _put(ref, value):
    """``value`` as the [rows, width] tile of a block (``_tile``)."""
    ref[(0,) * (len(ref.shape) - 2) + (slice(None), slice(None))] = value


# two heads of HALF lanes lie side by side in one 128-lane column block of
# the projections' rows where a head is 64 wide
HALF = LANES // 2


def _half_view(x, own, at, there=True):
    """The 64-lane half ``own`` of a [rows, 128] tile ``x`` at half ``at``,
    zeros in the other half (and everywhere for a head that is not
    ``there``). Selects, not products: a lane past the array's last head
    holds anything. ``own``, ``at`` and ``there`` may be traced scalars."""
    same = at == own
    if same is True:
        moved = x
    else:
        # halves exchanged (a lane rotation by half a tile, written as two
        # slices: Mosaic rotates 32-bit lanes only)
        moved = jnp.concatenate([x[:, HALF:], x[:, :HALF]], axis=1)
        if same is not False:
            moved = jnp.where(same, x, moved)
    keep = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // HALF == at
    if there is not True:
        keep &= there
    return jnp.where(keep, moved, jnp.zeros_like(moved))


class _Pairs(NamedTuple):
    """A call on column blocks of two 64-wide heads: query column block
    ``p`` holds heads ``2p`` and ``2p + 1``, key/value column block ``c``
    key/value heads ``2c`` and ``2c + 1``. A query pair's two key/value
    heads are one head or the two halves of one column block
    (``row_layout`` admits an odd count of key/value heads only without
    groups), so a grid step reads one k/v block for both."""

    N: int  # query heads
    K: int  # key/value heads

    @property
    def G(self):
        return self.N // self.K

    def kv_block(self, p):
        """Column block of the key/value heads of query pair ``p``."""
        return (2 * p // self.G) // 2

    def heads(self, b, p):
        """The two heads of query pair ``p`` of batch row ``b``: (flat
        batch * heads index for dropout's counter, half of the query
        block, half of the key/value block, whether the head exists)."""
        found = []
        for h in range(2):
            n = 2 * p + h
            found.append(_Head(
                b * self.N + n, h,
                h if self.G == 1 else (n // self.G) % 2,
                True if h == 0 or self.N % 2 == 0 else n < self.N))
        return found

    def kv_clean(self, x, c):
        """A k/v tile of column block ``c`` with the lanes past the last
        key/value head (an odd count's last block) as zeros: the tile goes
        whole into contractions against a view's zeros."""
        if self.K % 2 == 0:
            return x
        # (a select on every tile: a branch taken for the last block alone
        # measured slower on the chip, PR 50)
        lower = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < HALF
        return jnp.where(lower | (2 * c + 1 < self.K), x, jnp.zeros_like(x))


class _Head(NamedTuple):
    bn: "jax.Array"          # batch * heads index, dropout's counter
    own: "int | None" = None  # its half of a query column block
    at: "int | jax.Array | None" = None  # its key/value head's half
    there: "bool | jax.Array" = True

    def view(self, x):
        """The head's numbers of a query-side tile where its key/value
        head's lie (the tile itself where a block is one head)."""
        return x if self.own is None else _half_view(
            x, self.own, self.at, self.there)

    def back(self, x):
        """A result computed against the key/value block, in the head's own
        half of the query block."""
        return x if self.own is None else _half_view(x, self.at, self.own)


def _sum_of(parts):
    return functools.reduce(operator.add, parts)


def _of(ref, i: int, pair):
    """Head ``i``'s part of a scratch buffer: the buffer where a block is
    one head, else its ``i``-th leading slice."""
    return ref if pair is None else ref.at[i]


def _seg_range(refs, has_seg: bool):
    """``((first, end), the other refs)`` of a kernel with segments: its
    two leading refs are the prefetched scalars, ``segment_chunk_ranges`` of
    every (batch row, tile) flat, and the grid step's tile is the grid's
    third axis in all three kernels. ``(None, refs)`` without segments,
    whose call has no such operand. ``program_id``: call at kernel top
    level."""
    if not has_seg:
        return None, refs
    first_ref, end_ref, *refs = refs
    at = pl.program_id(0) * pl.num_programs(2) + pl.program_id(2)
    return (first_ref[at], end_ref[at]), refs


def _major_range(bounds, at, chunks: int):
    """An index map's ``(first, end)`` in major blocks of ``chunks`` chunks
    for tile ``at`` (flat over batch rows) from the prefetched scalar refs
    it is handed after the grid's indices; None where there are none."""
    if not bounds:
        return None
    first_ref, end_ref = bounds
    return first_ref[at] // chunks, (end_ref[at] - 1) // chunks + 1


def _grid(bounds, **spec):
    """``pallas_call``'s grid arguments: ``spec`` as it is for a call
    without segments; with them a scalar-prefetch grid whose scalar
    operands, first in the call, are the ``bounds``."""
    if bounds is None:
        return spec
    return {"grid_spec": pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(bounds), **spec)}


def _flash_kernel(*refs, block_q: int, block_k: int, chunks: int,
                  num_major: int,
                  causal: bool, scale: float, has_seg: bool = False,
                  dropout_rate: float = 0.0, window: "int | None" = None,
                  pair: "_Pairs | None" = None):
    """Grid (B, N, q block, k major block). One step holds a q tile and
    ``chunks`` k/v chunks of ``block_k`` rows and loops over the chunks the
    causal mask leaves, so a step past the diagonal neither fetches nor
    computes and only the chunks that cross the diagonal build a mask.
    With ``pair`` the second grid axis runs over pairs of 64-wide heads
    and a step serves both: each head's query view (zeros in the other
    head's lanes, so the 128-deep contraction adds exact zeros) is made
    once a q tile, and each head has statistics and an accumulator of its
    own, whose half under its key/value head is the result. With segments
    the loop's range is cut to the chunks the q tile's ids can meet
    (``_seg_range``)."""
    seg, (q_ref, k_ref, v_ref, *rest) = _seg_range(refs, has_seg)
    if dropout_rate > 0.0:
        seed_ref, rest = rest[0], rest[1:]
    else:
        seed_ref = None
    if has_seg:
        qseg_ref, kseg_ref, *rest = rest
    else:
        qseg_ref = kseg_ref = None
    o_ref, lse_ref, m_ref, l_ref, acc_ref, *views = rest
    kj = pl.program_id(3)
    q0 = pl.program_id(2) * block_q
    lo = kj * chunks
    # flat batch*heads index for the dropout mask; program_id must be read
    # at kernel top level (the interpret-mode executor does not rewrite it
    # inside pl.when bodies)
    if pair is None:
        heads = [_Head(pl.program_id(0) * pl.num_programs(1)
                       + pl.program_id(1))]
    else:
        heads = pair.heads(pl.program_id(0), pl.program_id(1))
        kv_block = pair.kv_block(pl.program_id(1))

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if pair is not None:
            for i, head in enumerate(heads):
                views[0][i] = head.view(_tile(q_ref))

    def chunk(c, masked):
        rows = pl.ds(pl.multiple_of(c * block_k, block_k), block_k)
        k0 = (lo + c) * block_k
        v = _tile(v_ref, rows)
        if pair is None:
            q, k = _tile(q_ref), _tile(k_ref, rows)
        else:
            k = pair.kv_clean(_tile(k_ref, rows), kv_block)
            v = pair.kv_clean(v, kv_block)
        for i, head in enumerate(heads):
            m_i, l_i, acc_i = (_of(r, i, pair)
                               for r in (m_ref, l_ref, acc_ref))
            s = _scores(q if pair is None else views[0][i], k, q0, k0,
                        qseg_ref[0] if has_seg else None,
                        kseg_ref[0, c] if has_seg else None,
                        masked=masked, scale=scale, window=window)
            # the running max is kept replicated along LANES lanes, so it
            # meets the score tile and the accumulator without a relayout;
            # the cross-lane max is the one reduction a chunk pays
            m = m_i[...]
            new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m - new_m)
            p = jnp.exp(s - _across(new_m, block_k))
            m_i[...] = new_m
            # the normalizer (of the UNdropped p: out = dropout(softmax(s))
            # @ v) is kept as LANES partial sums a row, added up once at
            # the end
            l_i[...] = l_i[...] * corr + _lane_sums(p)
            if dropout_rate > 0.0:
                keep = keep_mask(seed_ref[0], head.bn,
                                 *_tile_pos(q0, k0, s.shape), dropout_rate)
                p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            acc_i[...] = (acc_i[...] * _across(corr, acc_i.shape[1])
                          + _dot(p.astype(v.dtype), v, _NN))

    _for_k_chunks(chunk, q0, block_q, block_k, lo, chunks, causal, window,
                  seg)

    @pl.when(kj == num_major - 1)
    def _finalize():
        ls = [jnp.maximum(jnp.sum(_of(l_ref, i, pair)[...], axis=1,
                                  keepdims=True), 1e-20)
              for i in range(len(heads))]
        _put(o_ref, _sum_of(
            head.back(_of(acc_ref, i, pair)[...] / ls[i])
            for i, head in enumerate(heads)).astype(o_ref.dtype))
        # logsumexp per row, consumed by the backward kernels; stored with a
        # trailing singleton lane dim — Mosaic requires the last two block
        # dims to be (mult-of-8, mult-of-128) or equal to the array dims, so
        # a rank-3 (1, 1, block_q) lse block cannot lower on hardware
        for i, l in enumerate(ls):
            lse_ref[0, i] = _of(m_ref, i, pair)[:, :1] + jnp.log(l)


# what one grid step may keep resident of each streamed operand (bytes): K
# and V (forward, dq) or q and dO (dk/dv), each double-buffered by the
# pipeline, beside the tiles and the f32 score temporaries
_RESIDENT_BYTES = 1 << 20


def _major_chunks(seq: int, block: int, row_bytes: int) -> int:
    """How many ``block``-row chunks of a ``seq``-row operand one grid step
    holds: the largest divisor of ``seq // block`` that keeps ``row_bytes``
    a row within _RESIDENT_BYTES (at least one chunk)."""
    n = seq // block
    cap = max(1, _RESIDENT_BYTES // (block * row_bytes))
    return max(c for c in range(1, n + 1) if n % c == 0 and c <= cap)


def _check_call(S, Sk, block_q, block_k, causal, segments, dropout_rate,
                dropout_seed, window=None):
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"a window ({window}) is so many keys of the causal span, the "
            "query's own included: it needs causal=True and at least 1")
    if S % block_q or Sk % block_k:
        raise ValueError(
            f"seq {S}/{Sk} must divide by blocks {block_q}/{block_k}")
    if causal and Sk != S:
        raise ValueError("causal flash needs equal q/k lengths")
    if segments is not None and Sk != S:
        raise ValueError("segment masking needs equal q/k lengths")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 needs a dropout_seed")


def _chunk_rows(x, block: int):
    """[..., S] as [..., S // block, 1, block]: one chunk along lanes,
    picked by a leading index (a (1, block) block keeps Mosaic's (8, 128)-
    or-equal tiling rule, and a kernel cannot slice lanes at a traced
    offset)."""
    return x.reshape(*x.shape[:-1], x.shape[-1] // block, 1, block)


def _segment_operands(segments, block: int):
    """Segment ids as the kernels read them: along sublanes as a [B, S, 1]
    column (the trailing singleton for the tiling rule, as for lse), and
    along lanes in chunks of ``block``."""
    seg = segments.astype(jnp.int32)
    return seg[:, :, None], _chunk_rows(seg, block)


def _segment_bounds(segments, rows_block: int, cols_block: int):
    """``segment_chunk_ranges`` as the scalar operands a call with segments
    prefetches: (first, end), each flat over (batch row, tile); None
    without segments."""
    if segments is None:
        return None
    return tuple(x.reshape(-1) for x in segment_chunk_ranges(
        segments.astype(jnp.int32), rows_block, cols_block))


def row_layout(N: int, K: int, D: int, Dv: int) -> "int | None":
    """Heads a column block holds where the kernels can index the
    projections' rows ``[B, S, N * D]`` as they are: 1 at widths of whole
    lane tiles, 2 at width 64 (a pair reads ONE key/value column block,
    which an odd count of key/value heads allows only without groups), None
    where the call keeps the head-major path. From the shapes alone."""
    if D % LANES == 0 and Dv % LANES == 0:
        return 1
    if D == Dv == HALF and (K % 2 == 0 or N == K):
        return 2
    return None


def _block(rows_layout: bool, rows: int, width: int, index):
    """BlockSpec of a [rows, width] tile at the (batch, head or column
    block, row block) that ``index`` makes of the grid's indices: a block
    of a head-major [B, N, S, width] array, or of the projections' rows
    [B, S, N * width], where a head (a pair) is a column block."""
    if rows_layout:
        def index_map(*grid):
            b, col, row = index(*grid)
            return b, row, col

        return pl.BlockSpec((1, rows, width), index_map)
    return pl.BlockSpec((1, 1, rows, width), lambda *grid: (*index(*grid), 0))


def _kv_col(n, G: int, pair):
    """The key/value head of query head ``n``, or the key/value column
    block of query pair ``n``."""
    return n // G if pair is None else pair.kv_block(n)


def _call_shapes(q, k, v, heads):
    """(B, N, K, S, Sk, D, Dv, heads a block, ``_Pairs`` or None) of a
    call: head-major operands (``heads`` None), or rows with their
    (query, key/value) head counts."""
    if heads is None:
        (B, N, S, D), (_, K, Sk, _), Dv = q.shape, k.shape, v.shape[3]
        return B, N, K, S, Sk, D, Dv, 1, None
    (B, S, _), Sk, (N, K) = q.shape, k.shape[1], heads
    D, Dv = q.shape[2] // N, v.shape[2] // K
    per = row_layout(N, K, D, Dv)
    return B, N, K, S, Sk, D, Dv, per, _Pairs(N, K) if per == 2 else None


def _forward_call(q, k, v, segments, dropout_seed, *, heads, causal, block_q,
                  block_k, interpret, dropout_rate, scale, window):
    B, N, K, S, Sk, D, Dv, per, pair = _call_shapes(q, k, v, heads)
    G = N // K
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    _check_call(S, Sk, block_q, block_k, causal, segments, dropout_rate,
                dropout_seed, window)
    chunks = _major_chunks(Sk, block_k, max(D, Dv) * k.dtype.itemsize)
    major = chunks * block_k
    num_major = Sk // major
    cols = -(-N // per)  # heads, or pairs of them
    grid = (B, cols, S // block_q, num_major)  # k major axis innermost
    has_seg = segments is not None
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, chunks=chunks,
        num_major=num_major, causal=causal,
        scale=1.0 / math.sqrt(D) if scale is None else scale,
        has_seg=has_seg, dropout_rate=dropout_rate, window=window, pair=pair)
    block = functools.partial(_block, heads is not None)
    # with segments: each q tile's range of k chunks, prefetched scalars
    # that every index map is handed after the grid's indices
    bounds = _segment_bounds(segments, block_q, block_k)

    def kj_of(b, qi, kj, bounds):
        return _needed_k_major(
            qi, kj, block_q, major, causal, window,
            _major_range(bounds, b * (S // block_q) + qi, chunks))

    def q_tile(b, n, qi, kj, *bounds):
        return b, n, qi

    def kv_rows(b, n, qi, kj, *bounds):
        return b, _kv_col(n, G, pair), kj_of(b, qi, kj, bounds)

    in_specs = [block(block_q, D * per, q_tile),
                block(major, D * per, kv_rows),
                block(major, Dv * per, kv_rows)]
    operands = [q, k, v]
    if dropout_rate > 0.0:
        # kernel unpacks the seed ref FIRST from *rest (after q/k/v)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(dropout_seed.astype(jnp.int32).reshape(1))
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda b, n, qi, kj, *bounds: (b, qi, 0)),
            pl.BlockSpec((1, chunks, 1, block_k),
                         lambda b, n, qi, kj, *bounds: (
                             b, kj_of(b, qi, kj, bounds), 0, 0)),
        ]
        operands = [*bounds, *operands, *_segment_operands(segments, block_k)]
    # statistics and accumulator of each head of a block, and of a pair the
    # two query views
    of_head = () if pair is None else (per,)
    return pl.pallas_call(
        kernel,
        **_grid(
            bounds,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                block(block_q, Dv * per, q_tile),
                pl.BlockSpec((1, per, block_q, 1),
                             lambda b, n, qi, kj, *bounds: (b, n, qi, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((*of_head, block_q, LANES), jnp.float32),
                pltpu.VMEM((*of_head, block_q, LANES), jnp.float32),
                pltpu.VMEM((*of_head, block_q, Dv * per), jnp.float32),
            ] + ([] if pair is None else [
                pltpu.VMEM((per, block_q, LANES), q.dtype)])),
        out_shape=[
            jax.ShapeDtypeStruct(
                (B, N, S, Dv) if heads is None else (B, S, N * Dv), q.dtype),
            # (an odd count of paired heads has the statistics of one more)
            jax.ShapeDtypeStruct((B, cols * per, S, 1), jnp.float32),
        ],
        # only the k axis carries loop state (the online softmax);
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


_CALL_STATICS = ("causal", "block_q", "block_k", "interpret", "dropout_rate",
                 "scale", "window")


@functools.partial(jax.jit, static_argnames=_CALL_STATICS)
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
    scale: "float | None" = None,  # softmax(scale * q.k^T); None = D ** -0.5
    window: "int | None" = None,  # keys a query meets at most, itself included
) -> jax.Array:
    """(out [B, N, S, Dv], lse [B, N, S, 1]) on head-major operands; Sk may
    differ from S (ring off-diagonal blocks)."""
    return _forward_call(
        q, k, v, segments, dropout_seed, heads=None, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        dropout_rate=dropout_rate, scale=scale, window=window)


@functools.partial(jax.jit, static_argnames=_CALL_STATICS + ("heads",))
def flash_attention_rows(q, k, v, segments=None, dropout_seed=None, *,
                         heads: "tuple[int, int]", causal: bool = True,
                         block_q: int = 256, block_k: int = 256,
                         interpret: bool = False, dropout_rate: float = 0.0,
                         scale: "float | None" = None,
                         window: "int | None" = None):
    """The same kernel on the projections' own rows: q [B, S, N * D], k
    [B, Sk, K * D], v [B, Sk, K * Dv] with ``heads`` = (N, K), at the
    widths ``row_layout`` admits. (out [B, S, N * Dv], lse [B, N', S, 1]
    with N' = N, or N + 1 for an odd count of 64-wide heads.)"""
    return _forward_call(
        q, k, v, segments, dropout_seed, heads=heads, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        dropout_rate=dropout_rate, scale=scale, window=window)


def _p_and_ds(q, k, v, do, lse, delta, q0, k0, qseg, kseg, seed_ref, bn, *,
              masked: bool, scale: float, dropout_rate: float,
              k_rows: bool = False, window: "int | None" = None):
    """What both backward kernels recompute for one score tile from the
    saved logsumexp: p (dropped and rescaled where dropout is on, as the
    forward fed it to p.v) and ds = p * (dp - delta) * scale, both f32.
    With ``k_rows`` the tile is [k, q] and lse / delta / qseg are rows
    (1, block_q), else [q, k] and they are columns (block_q, 1)."""
    s = _scores(q, k, q0, k0, qseg, kseg, masked=masked, scale=scale,
                k_rows=k_rows, window=window)
    p = jnp.exp(s - lse)
    dp = _dot(v, do, _NT) if k_rows else _dot(do, v, _NT)
    pd = p
    if dropout_rate > 0.0:
        keep = keep_mask(seed_ref[0], bn,
                         *_tile_pos(q0, k0, s.shape, int(k_rows)),
                         dropout_rate)
        pd = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
    # delta = rowsum(dropout(P) . dP') = dO . O, so the flash delta trick
    # survives dropout unchanged
    return pd, p * (dp - delta) * scale


def _flash_bwd_dkdv_kernel(*refs, block_q: int, block_k: int, chunks: int,
                           num_major: int, G: int, causal: bool,
                           scale: float, has_seg: bool = False,
                           dropout_rate: float = 0.0,
                           window: "int | None" = None,
                           pair: "_Pairs | None" = None):
    """Grid (B, KV, k block, G, q major block): accumulate dk/dv for one k/v
    tile across the G query heads of this kv head and all q rows; one step
    holds ``chunks`` q chunks and loops over those at or below the
    diagonal, masking only the ones that cross it. With a ``window`` the
    loop ends at the last q chunk that still meets the tile's last key, and
    the chunks the band's lower edge cuts are masked as well. With ``pair``
    the second axis runs over pairs of key/value heads and the fourth over
    the G query pairs of theirs: each query head's q and dO chunk is viewed
    at its key/value head's half, so ``p^T.dO`` and ``ds^T.q`` land there
    in the one accumulator and add zeros to the other half. With segments
    the loop's range is cut to the q chunks the k tile's ids can meet."""
    seg, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest) = (
        _seg_range(refs, has_seg))
    if dropout_rate > 0.0:
        seed_ref, rest = rest[0], rest[1:]
    else:
        seed_ref = None
    if has_seg:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        qseg_ref = kseg_ref = None
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    k0 = pl.program_id(2) * block_k
    g = pl.program_id(3)
    qj = pl.program_id(4)
    lo = qj * chunks
    # flat head index n = kh*G + g (N = KV*G heads); top-level program_id
    if pair is None:
        heads = [_Head(pl.program_id(0) * (pl.num_programs(1) * G)
                       + pl.program_id(1) * G + g)]
    else:
        heads = pair.heads(pl.program_id(0), pl.program_id(1) * G + g)
        kv_block = pl.program_id(1)

    @pl.when((g == 0) & (qj == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def chunk(c, masked):
        rows = pl.ds(pl.multiple_of(c * block_q, block_q), block_q)
        q_rows = _tile(q_ref, rows)
        do_rows = _tile(do_ref, rows)
        if pair is None:
            k, v = _tile(k_ref), _tile(v_ref)
        else:
            k = pair.kv_clean(_tile(k_ref), kv_block)
            v = pair.kv_clean(_tile(v_ref), kv_block)
        for i, head in enumerate(heads):
            q, do = head.view(q_rows), head.view(do_rows)
            # the tile is [k, q]: p^T.dO and ds^T.q are then plain products,
            # and lse / delta broadcast along sublanes from (1, block_q)
            # rows
            pd, ds = _p_and_ds(
                q, k, v, do, lse_ref[0, i, c], delta_ref[0, i, c],
                (lo + c) * block_q, k0,
                qseg_ref[0, c] if has_seg else None,
                kseg_ref[0] if has_seg else None, seed_ref, head.bn,
                masked=masked, scale=scale, dropout_rate=dropout_rate,
                k_rows=True, window=window)
            dv_acc[...] += _dot(pd.astype(do.dtype), do, _NN)
            dk_acc[...] += _dot(ds.astype(q.dtype), q, _NN)

    if causal and window is not None:
        # from the first q chunk with a visible row to the last that meets
        # the tile's last key; clear of the diagonal from the chunk below
        # it, and of the band's edge while the chunk's last query still
        # meets the tile's first key
        first = k0 // block_q
        clear_from = (k0 + block_k + block_q - 2) // block_q
        clear_to = (k0 + window) // block_q
        end = (k0 + block_k + window - 2) // block_q + 1
        if seg is not None:
            first, end = jnp.maximum(first, seg[0]), jnp.minimum(end, seg[1])
        _for_banded(chunk, lo, chunks, first, clear_from, clear_to, end)
    elif causal:
        # q chunks from the first with a visible row (the tile's own
        # positions': no segment moves it); those from ``clear`` on lie
        # wholly at or below the diagonal, up to the last the segments
        # leave
        first = jnp.clip(k0 // block_q - lo, 0, chunks)
        clear = jnp.clip((k0 + block_k + block_q - 2) // block_q - lo,
                         0, chunks)
        end = chunks
        if seg is not None:
            end = jnp.clip(seg[1] - lo, 0, chunks)
            clear = jnp.minimum(clear, end)
        _for(first, clear, lambda c: chunk(c, True))
        _for(clear, end, lambda c: chunk(c, False))
    else:
        _for_two_way(chunk, lo, chunks, seg)

    @pl.when((g == G - 1) & (qj == num_major - 1))
    def _finalize():
        _put(dk_ref, dk_acc[...].astype(dk_ref.dtype))
        _put(dv_ref, dv_acc[...].astype(dv_ref.dtype))


def _flash_bwd_dq_kernel(*refs, block_q: int, block_k: int, chunks: int,
                         num_major: int, causal: bool, scale: float,
                         has_seg: bool = False, dropout_rate: float = 0.0,
                         window: "int | None" = None,
                         pair: "_Pairs | None" = None,
                         delta_out: bool = False):
    """Grid (B, N, q block, k major block): accumulate dq for one q tile
    over the k chunks the causal mask leaves (the forward's loop); ``o_ref``
    is the forward's output tile, for delta. With ``pair`` a step serves a
    pair of 64-wide heads as the forward does: views of q and dO made once
    a q tile, an accumulator a head. With ``delta_out`` the q tile's delta
    is a second result, as the (1, block_q) row the dk/dv kernel reads: on
    the projections' rows XLA would relayout dO . O whole to sum it by
    head with the positions along lanes."""
    seg, (q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, *rest) = _seg_range(
        refs, has_seg)
    if dropout_rate > 0.0:
        seed_ref, rest = rest[0], rest[1:]
    else:
        seed_ref = None
    if has_seg:
        qseg_ref, kseg_ref, *rest = rest
    else:
        qseg_ref = kseg_ref = None
    if delta_out:
        dq_ref, delta_ref, dq_acc, *views = rest
    else:
        dq_ref, dq_acc, *views = rest
    kj = pl.program_id(3)
    q0 = pl.program_id(2) * block_q
    lo = kj * chunks
    if pair is None:
        heads = [_Head(pl.program_id(0) * pl.num_programs(1)
                       + pl.program_id(1))]
    else:
        heads = pair.heads(pl.program_id(0), pl.program_id(1))
        kv_block = pair.kv_block(pl.program_id(1))

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if pair is not None:
            for i, head in enumerate(heads):
                views[0][i] = head.view(_tile(q_ref))
                views[1][i] = head.view(_tile(do_ref))

    # delta = rowsum(dO . O) of this q tile, once a grid step from the tiles
    # themselves: as a [B, N, S, 1] column in HBM it would cost a 128-lane
    # row an element (the dk/dv kernel reads it as rows); of a pair, each
    # head's over its own half of the lanes
    do_o = (_tile(do_ref).astype(jnp.float32)
            * _tile(o_ref).astype(jnp.float32))
    deltas = [jnp.sum(do_o if head.own is None
                      else _half_view(do_o, head.own, head.own, head.there),
                      axis=1, keepdims=True) for head in heads]

    def chunk(c, masked):
        rows = pl.ds(pl.multiple_of(c * block_k, block_k), block_k)
        k = _tile(k_ref, rows)
        if pair is None:
            q, v, do = _tile(q_ref), _tile(v_ref, rows), _tile(do_ref)
        else:
            k = pair.kv_clean(k, kv_block)
            v = pair.kv_clean(_tile(v_ref, rows), kv_block)
        for i, head in enumerate(heads):
            _, ds = _p_and_ds(
                q if pair is None else views[0][i], k, v,
                do if pair is None else views[1][i], lse_ref[0, i],
                deltas[i], q0, (lo + c) * block_k,
                qseg_ref[0] if has_seg else None,
                kseg_ref[0, c] if has_seg else None, seed_ref, head.bn,
                masked=masked, scale=scale, dropout_rate=dropout_rate,
                window=window)
            _of(dq_acc, i, pair)[...] += _dot(ds.astype(k.dtype), k, _NN)

    _for_k_chunks(chunk, q0, block_q, block_k, lo, chunks, causal, window,
                  seg)

    @pl.when(kj == num_major - 1)
    def _finalize():
        _put(dq_ref, _sum_of(
            head.back(_of(dq_acc, i, pair)[...])
            for i, head in enumerate(heads)).astype(dq_ref.dtype))
        if delta_out:
            # the columns along lanes: one transpose of whole (8, 128) tiles
            # with a head's column in each of its lanes (on the chip no
            # slower than ones . (dO . O)^T on the idle MXU)
            across = jnp.broadcast_to(deltas[0], (block_q, LANES))
            if pair is not None:
                across = jnp.where(jax.lax.broadcasted_iota(
                    jnp.int32, across.shape, 1) < HALF, across, deltas[1])
            rows = across.T
            for i in range(len(heads)):
                delta_ref[0, i, 0] = rows[i * HALF:i * HALF + 1]


def _backward_call(q, k, v, o, lse, do, segments, dropout_seed, *, heads,
                   causal, block_q, block_k, interpret, dropout_rate, scale,
                   window):
    B, N, KV, S, Sk, D, Dv, per, pair = _call_shapes(q, k, v, heads)
    G = N // KV
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    _check_call(S, Sk, block_q, block_k, causal, segments, dropout_rate,
                dropout_seed, window)
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    has_seg = segments is not None
    if heads is None:
        # delta for dk/dv, as [B, N, S] rows in chunks; the dq kernel takes
        # its own from the o / dO tiles, and on the projections' rows it
        # writes these rows too: there XLA would relayout dO . O whole to
        # sum it by head with the positions along lanes
        delta = _chunk_rows(
            jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1),
            block_q)
    seed_arr = (dropout_seed.astype(jnp.int32).reshape(1)
                if dropout_rate > 0.0 else None)
    if has_seg:
        seg_col, kseg_rows = _segment_operands(segments, block_k)
        _, qseg_rows = _segment_operands(segments, block_q)
    block = functools.partial(_block, heads is not None)

    # dk/dv: a k/v tile stays, q / dO / lse / delta stream by major blocks;
    # its score tiles are [k, q], so lse and delta come as rows
    q_chunks = _major_chunks(S, block_q, max(D, Dv) * q.dtype.itemsize)
    q_major = q_chunks * block_q

    # with segments: each k tile's range of q chunks, prefetched scalars
    dkdv_bounds = _segment_bounds(segments, block_k, block_q)

    def qj_of(b, kb, qj, bounds):
        # a q major block wholly above the diagonal repeats the first one
        # that is needed (no copy for an unchanged block index), and one
        # wholly past the band the last; so does one none of whose chunks
        # the segments leave the k tile
        lo = hi = None
        if causal:
            lo = (kb * block_k) // q_major
        if causal and window is not None:
            hi = (kb * block_k + block_k + window - 2) // q_major
        return _held_to(qj, lo, hi, _major_range(
            bounds, b * (Sk // block_k) + kb, q_chunks))

    # (the query heads of key/value head kh are kh * G + g, and so are the
    # query pairs of a key/value pair)
    def q_rows(b, kh, kb, g, qj, *bounds):
        return b, kh * G + g, qj_of(b, kb, qj, bounds)

    q_stat = pl.BlockSpec(
        (1, per, q_chunks, 1, block_q),
        lambda b, kh, kb, g, qj, *bounds: (
            b, kh * G + g, qj_of(b, kb, qj, bounds), 0, 0))

    def k_tile(b, kh, kb, g, qj, *bounds):
        return b, kh, kb

    dkdv_in_specs = [block(q_major, D * per, q_rows),
                     block(block_k, D * per, k_tile),
                     block(block_k, Dv * per, k_tile),
                     block(q_major, Dv * per, q_rows), q_stat, q_stat]
    dkdv_operands = [q, k, v, do, _chunk_rows(lse[..., 0], block_q)]
    if dropout_rate > 0.0:
        dkdv_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dkdv_operands.append(seed_arr)
    if has_seg:
        dkdv_in_specs += [
            pl.BlockSpec((1, q_chunks, 1, block_q),
                         lambda b, kh, kb, g, qj, *bounds: (
                             b, qj_of(b, kb, qj, bounds), 0, 0)),
            pl.BlockSpec((1, block_k, 1),
                         lambda b, kh, kb, g, qj, *bounds: (b, kb, 0)),
        ]
        dkdv_operands += [qseg_rows, seg_col]

    dkdv = lambda delta: pl.pallas_call(  # noqa: E731
        functools.partial(_flash_bwd_dkdv_kernel, block_q=block_q,
                          block_k=block_k, chunks=q_chunks,
                          num_major=S // q_major, G=G, causal=causal,
                          scale=scale, has_seg=has_seg,
                          dropout_rate=dropout_rate, window=window,
                          pair=pair),
        **_grid(
            dkdv_bounds,
            grid=(B, -(-KV // per), Sk // block_k, G, S // q_major),
            in_specs=dkdv_in_specs,
            out_specs=[block(block_k, D * per, k_tile),
                       block(block_k, Dv * per, k_tile)],
            scratch_shapes=[
                pltpu.VMEM((block_k, D * per), jnp.float32),
                pltpu.VMEM((block_k, Dv * per), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        # dk/dv accumulate across the (g, q) axes; k tiles are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(*(dkdv_bounds or ()), *dkdv_operands[:5], delta, *dkdv_operands[5:])
    if heads is None:
        dk, dv = dkdv(delta)

    # dq: a q tile stays, k / v stream by major blocks (the forward's grid)
    k_chunks = _major_chunks(Sk, block_k, max(D, Dv) * k.dtype.itemsize)
    k_major = k_chunks * block_k

    dq_bounds = _segment_bounds(segments, block_q, block_k)

    def kj_of(b, qi, kj, bounds):
        return _needed_k_major(
            qi, kj, block_q, k_major, causal, window,
            _major_range(bounds, b * (S // block_q) + qi, k_chunks))

    def q_tile(b, n, qi, kj, *bounds):
        return b, n, qi

    def kv_rows(b, n, qi, kj, *bounds):
        return b, _kv_col(n, G, pair), kj_of(b, qi, kj, bounds)

    dq_in_specs = [block(block_q, D * per, q_tile),
                   block(k_major, D * per, kv_rows),
                   block(k_major, Dv * per, kv_rows),
                   block(block_q, Dv * per, q_tile),
                   pl.BlockSpec((1, per, block_q, 1),
                                lambda b, n, qi, kj, *bounds: (b, n, qi, 0)),
                   block(block_q, Dv * per, q_tile)]
    dq_operands = [q, k, v, do, lse, o]
    if dropout_rate > 0.0:
        dq_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dq_operands.append(seed_arr)
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda b, n, qi, kj, *bounds: (b, qi, 0)),
            pl.BlockSpec((1, k_chunks, 1, block_k),
                         lambda b, n, qi, kj, *bounds: (
                             b, kj_of(b, qi, kj, bounds), 0, 0)),
        ]
        dq_operands = [*dq_bounds, *dq_operands, seg_col, kseg_rows]
    dq_spec = block(block_q, D * per, q_tile)
    dq_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if heads is not None:
        # on rows the dq kernel writes delta's rows as well, in the chunks
        # ``q_stat`` reads (what ``_chunk_rows`` makes of [B, N, S])
        dq_spec = [dq_spec, pl.BlockSpec(
            (1, per, 1, 1, block_q),
            lambda b, n, qi, kj, *bounds: (b, n, qi, 0, 0))]
        dq_shape = [dq_shape, jax.ShapeDtypeStruct(
            (B, lse.shape[1], S // block_q, 1, block_q), jnp.float32)]
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, chunks=k_chunks,
                          num_major=Sk // k_major, causal=causal,
                          scale=scale, has_seg=has_seg,
                          dropout_rate=dropout_rate, window=window,
                          pair=pair, delta_out=heads is not None),
        **_grid(
            dq_bounds,
            grid=(B, -(-N // per), S // block_q, Sk // k_major),
            in_specs=dq_in_specs,
            out_specs=dq_spec,
            # the accumulator; of a pair one a head, and the views of q and
            # dO
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)]
            if pair is None else [
                pltpu.VMEM((per, block_q, LANES), jnp.float32),
                pltpu.VMEM((per, block_q, LANES), q.dtype),
                pltpu.VMEM((per, block_q, LANES), do.dtype)]),
        out_shape=dq_shape,
        # dq accumulates across k only
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*dq_operands)
    if heads is not None:
        dq, delta = dq
        dk, dv = dkdv(delta)
    return dq, dk, dv


@functools.partial(jax.jit, static_argnames=_CALL_STATICS)
def flash_attention_bwd_hmajor(
    q, k, v, o, lse, do, segments=None, dropout_seed=None, *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    dropout_rate: float = 0.0,
    scale: "float | None" = None,
    window: "int | None" = None,
):
    """Fused flash backward (heads-major layouts): recomputes p from lse per
    tile, so nothing O(S^2) ever hits HBM. Returns (dq, dk, dv). ``scale``
    and ``window``: the forward's (``None`` = ``D ** -0.5``, the whole
    causal span)."""
    return _backward_call(
        q, k, v, o, lse, do, segments, dropout_seed, heads=None,
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
        dropout_rate=dropout_rate, scale=scale, window=window)


@functools.partial(jax.jit, static_argnames=_CALL_STATICS + ("heads",))
def flash_attention_bwd_rows(q, k, v, o, lse, do, segments=None,
                             dropout_seed=None, *, heads: "tuple[int, int]",
                             causal: bool = True, block_q: int = 256,
                             block_k: int = 256, interpret: bool = False,
                             dropout_rate: float = 0.0,
                             scale: "float | None" = None,
                             window: "int | None" = None):
    """The same backward on the projections' own rows (the operands of
    ``flash_attention_rows``, its two results, and dO [B, S, N * Dv]):
    (dq, dk, dv) as rows."""
    return _backward_call(
        q, k, v, o, lse, do, segments, dropout_seed, heads=heads,
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
        dropout_rate=dropout_rate, scale=scale, window=window)


# the largest score tile a call takes; ``choose_blocks`` fits it to the call
DEFAULT_BLOCK_Q = 512
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


def effective_window(window: "int | None", S: int) -> "int | None":
    """A window no shorter than the sequence is no window: the call is
    the unwindowed program."""
    return None if window is None or window >= S else int(window)


def band_tiles(S: int, block_q: int, block_k: int,
               window: "int | None") -> "tuple[int, int]":
    """(score tiles the forward and dq kernels visit, score tiles of the
    causal triangle) of one head of a causal call at these tiles: the k
    chunks a q tile's loop runs over, by the same bounds as
    ``_for_k_chunks``."""
    visited = triangle = 0
    for q0 in range(0, S, block_q):
        end = (q0 + block_q - 1) // block_k + 1
        first = 0 if window is None else max(q0 - window + 1, 0) // block_k
        visited += end - first
        triangle += end
    return visited, triangle


# every call with a window that ``flash_sdpa`` / ``make_flash_sdpa`` built in
# this process, as the kernels were handed it: (q length, query heads,
# block_q, block_k, window). A set, so tracing a call again adds nothing. A
# launcher empties it before it builds its step and reads it once the step
# is compiled (``cli/train_dist.py``, the gauge ``flash/band_tiles_pct``)
WINDOWED_CALLS: "set[tuple[int, int, int, int, int]]" = set()


def two_way_tiles(S: int, Sk: int, block_q: int, block_k: int,
                  segments=None) -> int:
    """Score tiles the three kernels visit of one head of a call that is
    not causal, by the bounds of their own loops: every k chunk for every q
    tile without segments (``_for_two_way``; ``_needed_k_major`` keeps every
    major block); with the call's segment ids (a NumPy row ``[S]``, or
    ``[B, S]`` for the rows' sum) the chunks of each q tile's range,
    ``segment_chunk_ranges``, which is where the kernels' prefetched bounds
    come from: a tile in which no query's ids can meet a key's is not
    visited."""
    if segments is None:
        return (S // block_q) * (Sk // block_k)
    first, end = segment_chunk_ranges(segments, block_q, block_k)
    return int((end - first).sum())


# every call that is not causal, as the kernels were handed it: (q length,
# k length, block_q, block_k). A launcher reads the pairs its tiles cover
# (``cli/train_dist.py``, the gauge ``tower/pairs_tiled``)
TWO_WAY_CALLS: "set[tuple[int, int, int, int]]" = set()

# and every call, as the kernels were handed it (on a shard of a mesh: its
# shard's): ("rows" | "transposed", q length, k length, query heads, key/value
# heads, q/k width, v width, window). Which layout the kernels indexed is
# ``row_layout``'s reading of the widths; a launcher counts the set by its
# first entry (gauges ``flash/row_layout_calls``, ``flash/transposed_calls``)
LAYOUT_CALLS: "set[tuple]" = set()


def choose_blocks(D: int, S: int, Sk: int, floor: int = 128):
    """(block_q, block_k) of a call from its shapes alone: head width D, q
    length S, k/v length Sk. A 512 x 512 score tile where the lengths allow
    it, else the largest halving of 512 (down to ``floor``) that divides the
    length, else the whole length as one block (a block equal to the array
    dim keeps Mosaic's tiling rule; what then overflows VMEM fails at
    compile time). Measured on the v5e at D=64 / S=1024 and D=128 / S=4096
    (PERF.md, PR 25): a chunk pays one cross-lane max a row and a pass over
    the accumulator whatever its width, so 512 x 512 beats every smaller
    tile in all three kernels at both widths although at S=1024 it computes
    3/4 of the square where 256 x 256 computes 5/8; 1024-wide tiles lose
    more to the diagonal than they save. D does not move the choice at the
    widths measured: K and V are held by the major block, which the
    kernels size from D themselves (``_major_chunks``), and a pair of
    64-wide heads a step runs the two heads' tiles one after the other, at
    the tile one head had (a column block of two heads has the VMEM
    footprint the padded head-major block of one had). Nor does a window:
    at D=128 / S=8192 / window 512 the same tile won (PERF.md, PR 48)."""
    del D
    return (fit_block(DEFAULT_BLOCK_Q, S, floor) or S,
            fit_block(DEFAULT_BLOCK_K, Sk, floor) or Sk)


def _forward(q, k, v, segments, dropout_seed, **call):
    """The forward kernel on [B, S, N, D] operands: the output as
    [B, S, N * Dv] rows, lse [B, N', S, 1] and the operands as the kernels
    read them, which are the projections' own rows where ``row_layout``
    admits the widths (reshapes that move nothing) and head-major copies
    where it does not."""
    (B, S, N, D), K, Dv = q.shape, k.shape[2], v.shape[3]
    rows = row_layout(N, K, D, Dv) is not None
    LAYOUT_CALLS.add(("rows" if rows else "transposed", S, k.shape[1], N, K,
                      D, Dv, call["window"]))
    if rows:
        ops = tuple(x.reshape(*x.shape[:2], -1) for x in (q, k, v))
        out, lse = flash_attention_rows(*ops, segments, dropout_seed,
                                        heads=(N, K), **call)
        return out, lse, ops
    ops = tuple(x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out, lse = flash_attention_hmajor(*ops, segments, dropout_seed, **call)
    return out.transpose(0, 2, 1, 3).reshape(B, S, N * Dv), lse, ops


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_with_vjp(q, k, v, segments, dropout_seed, causal, interpret,
                    block_q, block_k, dropout_rate, scale, window=None):
    out, _, _ = _forward(q, k, v, segments, dropout_seed, causal=causal,
                         interpret=interpret, block_q=block_q,
                         block_k=block_k, dropout_rate=dropout_rate,
                         scale=scale, window=window)
    return out.reshape(*q.shape[:3], v.shape[3])


def _flash_fwd(q, k, v, segments, dropout_seed, causal, interpret, block_q,
               block_k, dropout_rate, scale, window=None):
    out, lse, ops = _forward(q, k, v, segments, dropout_seed, causal=causal,
                             interpret=interpret, block_q=block_q,
                             block_k=block_k, dropout_rate=dropout_rate,
                             scale=scale, window=window)
    # the pair per-layer remat keeps (``modules.remat``), each in the layout
    # HBM does not pad (module docstring); what the block goes on with is
    # derived from the kept output, so the recomputed forward needs no kernel
    out_rows = checkpoint_name(out, KEPT_OUT)
    lse_rows = checkpoint_name(lse[..., 0], KEPT_LSE)
    return (out_rows.reshape(*q.shape[:3], v.shape[3]),
            (*ops, out_rows, lse_rows, segments, dropout_seed))


def _flash_bwd(causal, interpret, block_q, block_k, dropout_rate, scale,
               window, res, g):
    q, k, v, out_rows, lse_rows, segments, dropout_seed = res
    call = dict(causal=causal, interpret=interpret, block_q=block_q,
                block_k=block_k, dropout_rate=dropout_rate, scale=scale,
                window=window)
    B, S, N, Dv = g.shape
    if q.ndim == 3:  # the projections' rows in, their cotangents' rows out
        Sk, K = k.shape[1], v.shape[2] // Dv
        dq, dk, dv = flash_attention_bwd_rows(
            q, k, v, out_rows, lse_rows[..., None], g.reshape(B, S, N * Dv),
            segments, dropout_seed, heads=(N, K), **call)
        return (dq.reshape(B, S, N, -1), dk.reshape(B, Sk, K, -1),
                dv.reshape(B, Sk, K, Dv), None, None)
    out = out_rows.reshape(g.shape).transpose(0, 2, 1, 3)
    dq, dk, dv = flash_attention_bwd_hmajor(
        q, k, v, out, lse_rows[..., None], g.transpose(0, 2, 1, 3),
        segments, dropout_seed, **call)
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
               dropout_rng=None, scale: float | None = None,
               window: int | None = None):
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

    ``scale``: softmax(scale * q.k^T) where a model states its own
    (``ModelArgs.attention_multiplier``); ``None`` is ``D ** -0.5``, the
    float every kernel was given before the argument was there.

    ``window``: a query meets the ``window`` newest keys of its causal
    span, its own included (a block of sliding-window attention); all
    three kernels run over the band's tiles alone. ``None``, or a window no
    shorter than the sequence, is the unwindowed program.

    Blocks not given come from ``choose_blocks``: a function of the head
    width and the q / kv lengths alone."""
    S, Sk = q.shape[1], k.shape[1]
    window = effective_window(window, S)
    bq, bk = choose_blocks(q.shape[-1], S, Sk)
    if window is not None:
        WINDOWED_CALLS.add((S, q.shape[2], block_q or bq, block_k or bk,
                            window))
    if not causal:
        TWO_WAY_CALLS.add((S, Sk, min(block_q or bq, S),
                           min(block_k or bk, Sk)))
    seed = None
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("flash dropout_rate > 0 needs dropout_rng")
        seed = seed_from_key(dropout_rng)
    return _flash_with_vjp(q, k, v, segment_ids, seed, causal, interpret,
                           block_q or bq, block_k or bk, dropout_rate, scale,
                           window)


# the fwd + both bwd kernels mask cross-document tiles in-kernel
flash_sdpa.supports_segments = True
# in-kernel counter-based attention dropout (fwd + bwd regenerate the mask)
flash_sdpa.supports_dropout = True
# the softmax scale is an argument of all three kernels
flash_sdpa.supports_scale = True
# and so is a window: the kernels visit the band's tiles alone
flash_sdpa.supports_window = True


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

    Block sizes follow ``flash_sdpa`` (``choose_blocks``). There is no
    fallback to the XLA core — a shape Mosaic refuses raises at compile
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
             dropout_rate: float = 0.0, dropout_rng=None, scale=None,
             window=None):
        # a length no lane-aligned block divides runs as ONE whole-length
        # block; what then overflows VMEM fails at compile time — there is
        # no XLA core behind the kernel to hide it
        window = effective_window(window, q.shape[s_dim])
        bq, bk = choose_blocks(q.shape[-1], q.shape[s_dim], k.shape[s_dim])
        if window is not None:
            WINDOWED_CALLS.add((q.shape[s_dim], q.shape[s_dim + 1], bq, bk,
                                window))
        if not causal:
            TWO_WAY_CALLS.add((q.shape[s_dim], k.shape[s_dim], bq, bk))
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
                                   bq, bk, dropout_rate, scale, window)

        from hetu_galvatron_tpu.ops.overlap import staged_lane

        # each pp row holds its stage's [1, ...] lane (the shared
        # compiled-engine adapter squeezes it around the kernel)
        local = staged_lane(local, stage_axis is not None)

        return on_shards(local, mesh, tuple(in_specs), spec)(*operands)

    sdpa.supports_segments = True
    sdpa.supports_dropout = True
    sdpa.supports_scale = True
    sdpa.supports_window = True
    return sdpa
