"""Segment ids bound the flash kernels' loops: the chunk ranges, and the
kernels through them against the dense core (interpret mode on the CPU). A
file of its own so that no file of the suite runs longer than a worker's
fair share (``tests/conftest.py``: a module's cases stay on one worker)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.models.modules import xla_sdpa
from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa

from test_flash_attention import _assert_f32_parity, _fwd_and_grads, _qkv

pytestmark = pytest.mark.kernels


# ---------------------------------------------------------------------------
# segments bound the loops: a chunk range a tile from the ids' bounds
# ---------------------------------------------------------------------------


def _ids(kind: str, S: int = 256, seed: int = 0) -> np.ndarray:
    """Segment ids [B, S] of one kind (document lengths from ``seed``)."""
    rng = np.random.default_rng(seed)

    def docs(n, total):
        cuts = np.sort(rng.choice(np.arange(1, total), n - 1, replace=False))
        return np.diff(np.concatenate([[0], cuts, [total]]))

    if kind == "sorted":
        return np.repeat(np.arange(5), docs(5, S))[None].astype(np.int32)
    if kind == "unsorted":
        return np.repeat(rng.permutation(7), docs(7, S))[None].astype(
            np.int32)
    if kind == "padding_tail":
        # documents 1.., then the padding's id 0: sorted no longer
        body = np.repeat(1 + np.arange(3), docs(3, S - 37))
        return np.concatenate([body, np.zeros(37, body.dtype)])[None].astype(
            np.int32)
    if kind == "two_rows":
        return np.stack([np.repeat(np.arange(4), docs(4, S)),
                         np.repeat(np.arange(2), docs(2, S))]).astype(
                             np.int32)
    if kind == "every_position_its_own":
        return np.arange(S, dtype=np.int32)[None]
    raise KeyError(kind)


@pytest.mark.parametrize("blocks", [(32, 32), (64, 16), (16, 64)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("kind", ["sorted", "unsorted", "padding_tail",
                                  "two_rows", "every_position_its_own"])
def test_segment_chunk_ranges_hold_every_pair_the_mask_leaves(kind, blocks):
    """Against brute force: every (tile, chunk) in which some query's id
    equals some key's lies inside the tile's range whatever the ids, and
    for ids that do not decrease the range is exactly those; a traced
    array and a NumPy row go the same way."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        segment_chunk_ranges)

    rows, cols = blocks
    for seed in range(4):
        ids = _ids(kind, seed=seed)
        first, end = segment_chunk_ranges(ids, rows, cols)
        traced = jax.jit(functools.partial(
            segment_chunk_ranges, rows_block=rows, cols_block=cols))(ids)
        np.testing.assert_array_equal(first, traced[0])
        np.testing.assert_array_equal(end, traced[1])
        assert first.dtype == end.dtype == np.int32
        assert first.shape == (ids.shape[0], ids.shape[1] // rows)
        same = ids[:, :, None] == ids[:, None, :]
        meets = same.reshape(ids.shape[0], ids.shape[1] // rows, rows,
                             ids.shape[1] // cols, cols).any((2, 4))
        chunk = np.arange(meets.shape[-1])
        inside = ((first[..., None] <= chunk) & (chunk < end[..., None]))
        assert np.all(inside | ~meets)
        assert np.all(end > first)
        if np.all(np.diff(ids, axis=1) >= 0):
            np.testing.assert_array_equal(inside, meets)
    assert kind not in ("sorted", "two_rows") or np.all(
        np.diff(ids, axis=1) >= 0)


def test_the_cells_images_leave_108_of_256_tiles():
    """kimivl_c1_b1_s4k's tower call: three images one after the other at
    512 x 512 tiles, 8 x 8 + 6 x 6 + 3 x 3 less the tile the second
    boundary (patch 6,976, inside tile 13) puts in two squares."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        segment_chunk_ranges, two_way_tiles)

    ids = np.repeat(np.arange(3), [64 * 64, 36 * 80, 32 * 38])
    first, end = segment_chunk_ranges(ids, 512, 512)
    assert first.tolist() == [0] * 8 + [8] * 6 + [13] * 2
    assert end.tolist() == [8] * 8 + [14] * 5 + [16] * 3
    assert two_way_tiles(8192, 8192, 512, 512, ids) == 108
    assert two_way_tiles(8192, 8192, 512, 512) == 256
    # the dk/dv kernel's ranges (a k tile's q chunks) are the same count
    assert two_way_tiles(8192, 8192, 256, 512, ids) == int(np.sum(np.subtract(
        *segment_chunk_ranges(ids, 512, 256)[::-1])))


# ids of two batch rows, segmented differently, by what the boundaries do
# at q tiles of 32 and k chunks of 16 (S = 128; 160 where the case shrinks
# the residency budget to two chunks a major block)
_SEGMENTATIONS = {
    "on_tile_edges": ([32, 64, 32], [64, 64]),
    "inside_tiles": ([40, 35, 53], [10, 90, 20, 8]),
    "across_major_blocks": ([50, 60, 50], [90, 70]),
    "unsorted_ids": ([(2, 30), (0, 50), (2, 20), (1, 28)],
                     [(1, 64), (0, 64)]),
    "a_document_under_a_tile": ([60, 5, 63], [3, 125]),
}
_LAYOUTS = {"head_major_72": 72, "rows_128": 128, "pairs_64": 64}
_MASKS = {"two_way": (False, None), "causal": (True, None),
          "window_24": (True, 24)}


@pytest.mark.parametrize("segmentation", sorted(_SEGMENTATIONS))
@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("mask", sorted(_MASKS))
def test_flash_with_segments_visits_what_the_dense_mask_leaves(
        monkeypatch, mask, layout, segmentation):
    """out, dq, dk and dv against the dense core for ids of every kind: the
    kernels' loops and index maps run over each tile's prefetched chunk
    range alone, and what they skip the mask would have emptied."""
    from hetu_galvatron_tpu.ops.pallas import flash_attention as fa

    causal, window = _MASKS[mask]
    D = _LAYOUTS[layout]
    assert fa.row_layout(4, 2, D, D) == {72: None, 128: 1, 64: 2}[D]
    rows = []
    for row in _SEGMENTATIONS[segmentation]:
        docs = [d if isinstance(d, tuple) else (i, d)
                for i, d in enumerate(row)]
        rows.append(np.repeat([i for i, _ in docs], [n for _, n in docs]))
    seg = jnp.asarray(np.stack(rows), jnp.int32)
    S = seg.shape[1]
    if segmentation == "across_major_blocks":
        # two k chunks a major block, one q chunk: 5 major blocks each way
        # (a length no other case traces, so no trace with the real budget
        # is reused)
        monkeypatch.setattr(fa, "_RESIDENT_BYTES", 2 * 16 * 128 * 4)
        assert S == 160 and fa._major_chunks(S, 16, D * 4) == 2
    q, k, v = _qkv(B=2, S=S, N=4, K=2, D=D, seed=21)
    do = jax.random.normal(jax.random.key(22), q.shape, q.dtype)
    ref = _fwd_and_grads(
        lambda a, b, c: xla_sdpa(a, b, c, causal=causal, window=window,
                                 segment_ids=seg), q, k, v, do)
    got = _fwd_and_grads(
        lambda a, b, c: flash_sdpa(a, b, c, causal=causal, window=window,
                                   segment_ids=seg, interpret=True,
                                   block_q=32, block_k=16), q, k, v, do)
    _assert_f32_parity(got, ref)


@pytest.mark.parametrize("causal", [False, True])
def test_a_chunk_outside_a_tiles_range_is_never_read(causal):
    """The second document's q, k, v and dO are NaN. A kernel that visited
    its chunks for the first document's tiles would spread them (a masked
    probability is 0, and 0 . NaN is NaN, in p . v, ds . k, p^T . dO and
    ds^T . q alike); the first document's rows come out as those of the
    document alone."""
    q, k, v = _qkv(B=1, S=128, N=2, K=2, D=32, seed=31)
    do = jax.random.normal(jax.random.key(32), q.shape, q.dtype)
    seg = jnp.asarray(np.repeat([0, 1], 64)[None], jnp.int32)
    alone = _fwd_and_grads(
        lambda a, b, c: xla_sdpa(a, b, c, causal=causal),
        *(x[:, :64] for x in (q, k, v, do)))
    nan = lambda x: x.at[:, 64:].set(jnp.nan)  # noqa: E731
    got = _fwd_and_grads(
        lambda a, b, c: flash_sdpa(a, b, c, causal=causal, segment_ids=seg,
                                   interpret=True, block_q=32, block_k=16),
        nan(q), nan(k), nan(v), nan(do))
    _assert_f32_parity([g[:, :64] for g in got], alone)
