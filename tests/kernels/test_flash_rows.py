"""The flash kernels on the projections' own rows against the same kernels on
head-major copies (interpret mode on the CPU). A file of its own so that no
file of the suite runs longer than a worker's fair share
(``tests/conftest.py``: a module's cases stay on one worker)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.kernels


# ---------------------------------------------------------------------------
# the projections' own rows: at head widths of whole lane tiles and at width
# 64 the kernels index [B, S, N * D] as it is (one head a 128-lane column
# block, or two), and nothing is transposed around a call
# ---------------------------------------------------------------------------


# B, S, heads, kv heads, q/k width, v width, dtype, blocks, window,
# segments, dropout
_ROWS = {
    "w128_mha": (2, 256, 2, 2, 128, 128, jnp.float32, None, None, False, 0.0),
    "w128_g4": (1, 256, 8, 2, 128, 128, jnp.float32, (128, 128), None, False,
                0.0),
    "w128_g4_bf16": (1, 256, 4, 1, 128, 128, jnp.bfloat16, (128, 128), None,
                     False, 0.0),
    "w256_v128": (1, 128, 2, 2, 256, 128, jnp.float32, None, None, False,
                  0.0),
    "w128_v256_g2": (1, 128, 4, 2, 128, 256, jnp.float32, (64, 64), None,
                     False, 0.0),
    "w128_window": (1, 256, 4, 2, 128, 128, jnp.float32, (64, 64), 100,
                    False, 0.0),
    "w128_segments_dropout": (2, 128, 2, 1, 128, 128, jnp.float32, (64, 32),
                              None, True, 0.2),
    "w64_even_heads": (2, 256, 4, 4, 64, 64, jnp.float32, (128, 128), None,
                       False, 0.0),
    "w64_even_heads_bf16": (1, 256, 2, 2, 64, 64, jnp.bfloat16, None, None,
                            False, 0.0),
    "w64_5_heads": (2, 128, 5, 5, 64, 64, jnp.float32, (64, 64), None, False,
                    0.0),
    "w64_5_heads_bf16": (1, 256, 5, 5, 64, 64, jnp.bfloat16, (128, 128),
                         None, False, 0.0),
    "w64_one_head": (1, 128, 1, 1, 64, 64, jnp.float32, None, None, False,
                     0.0),
    "w64_g2": (1, 128, 4, 2, 64, 64, jnp.float32, (64, 64), None, False,
               0.0),
    "w64_g4": (1, 256, 8, 2, 64, 64, jnp.float32, (128, 128), None, False,
               0.0),
    "w64_g4_bf16": (1, 128, 8, 2, 64, 64, jnp.bfloat16, None, None, False,
                    0.0),
    "w64_g5": (1, 128, 10, 2, 64, 64, jnp.float32, (64, 64), None, False,
               0.0),
    "w64_g3_four_kv": (1, 128, 12, 4, 64, 64, jnp.float32, (64, 64), None,
                       False, 0.0),
    "w64_window": (1, 256, 4, 4, 64, 64, jnp.float32, (64, 64), 70, False,
                   0.0),
    "w64_5_heads_window_segments": (2, 128, 5, 5, 64, 64, jnp.float32,
                                    (32, 64), 50, True, 0.0),
    "w64_segments": (2, 128, 4, 2, 64, 64, jnp.float32, (64, 32), None, True,
                     0.0),
    "w64_dropout": (2, 128, 4, 4, 64, 64, jnp.float32, (64, 64), None, False,
                    0.25),
    "w64_5_heads_dropout": (1, 128, 5, 5, 64, 64, jnp.float32, (32, 32),
                            None, False, 0.25),
    "w64_g4_segments_dropout": (2, 128, 8, 2, 64, 64, jnp.float32, (64, 64),
                                None, True, 0.1),
    "w64_noncausal_unequal_lengths": (1, 128, 4, 2, 64, 64, jnp.float32,
                                      (64, 64), None, False, 0.0),
}


def _rows_case(case):
    """Operands of a ``_ROWS`` case and its call's keywords (``Sk`` twice
    ``S`` and no causal mask where the case's name says so)."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        choose_blocks, seed_from_key)

    B, S, N, K, D, Dv, dtype, blocks, window, seg, rate = _ROWS[case]
    Sk = 2 * S if "unequal" in case else S
    ks = jax.random.split(jax.random.key(13), 4)
    q = jax.random.normal(ks[0], (B, S, N, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, K, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, K, Dv), dtype)
    do = jax.random.normal(ks[3], (B, S, N, Dv), dtype)
    segs = ((jnp.arange(S)[None, :] >= jnp.array([[S // 3], [S // 2]])[:B]
             ).astype(jnp.int32) if seg else None)
    bq, bk = blocks or choose_blocks(D, S, Sk)
    call = dict(causal="noncausal" not in case, block_q=bq, block_k=bk,
                interpret=True, dropout_rate=rate, window=window)
    seed = seed_from_key(jax.random.key(5)) if rate else None
    return (q, k, v, do), segs, seed, call


@pytest.mark.parametrize("case", sorted(_ROWS))
def test_flash_on_rows_is_the_head_major_call(case):
    """Output, statistics and the three gradients of the kernels on the
    projections' rows against the same kernels on head-major copies
    (``flash_attention_hmajor`` between transposes, the only path there
    was): float32 to the accumulation order of a 128-deep contraction that
    adds exact zeros, bf16 to a rounding of the result."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd_hmajor, flash_attention_bwd_rows,
        flash_attention_hmajor, flash_attention_rows, row_layout)

    (q, k, v, do), segs, seed, call = _rows_case(case)
    (B, S, N, D), K, Dv = q.shape, k.shape[2], v.shape[3]
    assert row_layout(N, K, D, Dv) == (2 if D == 64 else 1)
    t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
    o_h, lse_h = flash_attention_hmajor(t(q), t(k), t(v), segs, seed, **call)
    want = (t(o_h),) + tuple(t(g) for g in flash_attention_bwd_hmajor(
        t(q), t(k), t(v), o_h, lse_h, t(do), segs, seed, **call))
    flat = lambda a: a.reshape(*a.shape[:2], -1)  # noqa: E731
    o_r, lse_r = flash_attention_rows(flat(q), flat(k), flat(v), segs, seed,
                                      heads=(N, K), **call)
    assert o_r.shape == (B, S, N * Dv)
    # (an odd count of paired heads carries the statistics of one more)
    assert lse_r.shape == (B, N + (N % 2 if D == 64 else 0), S, 1)
    got = (o_r,) + flash_attention_bwd_rows(
        flat(q), flat(k), flat(v), o_r, lse_r, flat(do), segs, seed,
        heads=(N, K), **call)
    tol = (dict(rtol=2e-2, atol=2e-2) if q.dtype == jnp.bfloat16
           else dict(rtol=1e-5, atol=1e-5))
    np.testing.assert_allclose(np.asarray(lse_r[:, :N]), np.asarray(lse_h),
                               rtol=1e-5, atol=1e-5)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        g = np.asarray(g.reshape(w.shape).astype(jnp.float32))
        assert np.all(np.isfinite(g)), name
        np.testing.assert_allclose(g, np.asarray(w.astype(jnp.float32)),
                                   err_msg=name, **tol)
