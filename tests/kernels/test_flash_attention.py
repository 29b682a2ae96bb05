"""Pallas flash attention vs the dense XLA core (interpret mode on CPU; the
kernel itself compiles with Mosaic on TPU)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.models.modules import xla_sdpa
from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa

pytestmark = pytest.mark.kernels


def _qkv(B=2, S=128, N=4, K=4, D=32, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, S, N, D), dtype)
    k = jax.random.normal(ks[1], (B, S, K, D), dtype)
    v = jax.random.normal(ks[2], (B, S, K, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    ref = xla_sdpa(q, k, v, causal=causal)
    out = flash_sdpa(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_gqa():
    q, k, v = _qkv(N=8, K=2)
    ref = xla_sdpa(q, k, v, causal=True)
    out = flash_sdpa(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_multiple_q_blocks():
    # S=512 with block 256 -> 2 q blocks, causal skips the upper k block
    q, k, v = _qkv(B=1, S=512, N=2, K=2)
    ref = xla_sdpa(q, k, v, causal=True)
    out = flash_sdpa(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_fits_blocks_to_seq():
    # 384 is not a multiple of the 256/512 defaults: the wrapper clamps to
    # the largest lane-aligned divisor (128) instead of raising
    q, k, v = _qkv(B=1, S=384, N=2, K=2)
    out = flash_sdpa(q, k, v, causal=True, interpret=True)
    ref = xla_sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # an explicitly requested non-divisor block still raises
    with pytest.raises(ValueError, match="must divide"):
        flash_sdpa(q, k, v, interpret=True, block_q=256)


def test_flash_gradients_match():
    """jax.grad must flow through the flash kernel (custom VJP via dense
    recompute) and match the dense-core gradients."""
    q, k, v = _qkv(B=1, S=128, N=2, K=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_sdpa(q, k, v, causal=True, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_sdpa(q, k, v, causal=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-5)


def test_flash_gradients_gqa_groups():
    """Fused backward with G=4 query heads per kv head (the grouped dk/dv
    accumulation path)."""
    q, k, v = _qkv(B=1, S=128, N=8, K=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_sdpa(q, k, v, causal=True, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_sdpa(q, k, v, causal=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-5)


def test_flash_gradients_noncausal():
    q, k, v = _qkv(B=1, S=64, N=2, K=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_sdpa(q, k, v, causal=False, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_sdpa(q, k, v, causal=False) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-5)


def test_flash_segment_ids_match_dense():
    """Packed-document masking inside the kernel (fwd + grads) == dense core
    with the block-diagonal mask."""
    q, k, v = _qkv(B=2, S=128, N=4, K=4)
    seg = jnp.asarray(
        np.concatenate([np.zeros((2, 40), np.int32),
                        np.ones((2, 50), np.int32),
                        np.full((2, 38), 2, np.int32)], axis=1))
    ref = xla_sdpa(q, k, v, causal=True, segment_ids=seg)
    out = flash_sdpa(q, k, v, causal=True, interpret=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g_ref = jax.grad(lambda a, b, c: jnp.sum(
        xla_sdpa(a, b, c, causal=True, segment_ids=seg) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(lambda a, b, c: jnp.sum(
        flash_sdpa(a, b, c, causal=True, interpret=True,
                   segment_ids=seg) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-5)


def test_flash_supports_segments_attrs(cpu_devices):
    """apply_attention routes packed docs by this attribute; both the plain
    kernel and the shard_map wrapper must advertise it (ADVICE r3)."""
    from jax.sharding import Mesh
    from hetu_galvatron_tpu.ops.pallas.flash_attention import make_flash_sdpa

    assert getattr(flash_sdpa, "supports_segments", False)
    mesh = Mesh(np.array(cpu_devices[:4]).reshape(2, 2), ("dp", "tp"))
    sdpa = make_flash_sdpa(mesh, dp_axes=("dp",), tp_axes=("tp",),
                           interpret=True)
    assert getattr(sdpa, "supports_segments", False)


def test_distributed_flash_segment_ids(cpu_devices):
    """segment_ids through the shard_map wrapper (dp-sharded operand)."""
    from jax.sharding import Mesh
    from hetu_galvatron_tpu.ops.pallas.flash_attention import make_flash_sdpa

    mesh = Mesh(np.array(cpu_devices[:4]).reshape(2, 2), ("dp", "tp"))
    q, k, v = _qkv(B=2, S=128, N=4, K=4)
    seg = jnp.asarray(
        np.concatenate([np.zeros((2, 64), np.int32),
                        np.ones((2, 64), np.int32)], axis=1))
    flash = make_flash_sdpa(mesh, dp_axes=("dp",), tp_axes=("tp",),
                            interpret=True)
    ref = xla_sdpa(q, k, v, causal=True, segment_ids=seg)
    out = jax.jit(lambda a, b, c: flash(a, b, c, causal=True,
                                        segment_ids=seg))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_distributed_flash_matches_dense(cpu_devices):
    """shard_map-wrapped flash (batch over dp, heads over tp) == dense, with
    gradients, on a dp2 x tp2 mesh (interpret mode)."""
    from jax.sharding import Mesh
    from hetu_galvatron_tpu.ops.pallas.flash_attention import make_flash_sdpa

    mesh = Mesh(np.array(cpu_devices[:4]).reshape(2, 2), ("dp", "tp"))
    q, k, v = _qkv(B=2, S=64, N=4, K=4)
    flash = make_flash_sdpa(mesh, dp_axes=("dp",), tp_axes=("tp",),
                            interpret=True)
    ref = xla_sdpa(q, k, v, causal=True)
    out = jax.jit(lambda a, b, c: flash(a, b, c, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g_ref = jax.grad(lambda a, b, c: jnp.sum(
        xla_sdpa(a, b, c, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    g_out = jax.jit(jax.grad(lambda a, b, c: jnp.sum(
        flash(a, b, c, causal=True) ** 2), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-5)


# ---------------------------------------------------------------------------
# in-kernel attention dropout (counter-based mask over global coordinates;
# the reference's flash-attn dropout variant). keep_mask is pure jnp, so a
# dense reference applying the EXACT same mask verifies fwd + bwd bitwise
# (up to fp tolerance) — stronger than a statistical check.
# ---------------------------------------------------------------------------


def _ref_dropout_attn(q, k, v, seed, rate, causal=True):
    """Dense attention with the kernel's exact dropout mask."""
    import math

    from hetu_galvatron_tpu.ops.pallas.flash_attention import keep_mask

    B, S, N, D = q.shape
    K = k.shape[2]
    G = N // K
    qg = q.reshape(B, S, K, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    if causal:
        s = jnp.where(jnp.arange(S)[:, None] >= jnp.arange(S)[None, :], s,
                      jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    bn = (jnp.arange(B)[:, None] * N
          + jnp.arange(N)[None, :])  # flat head index n = kh*G + g
    keep = keep_mask(seed[0], bn[:, :, None, None],
                     jnp.arange(S)[None, None, :, None],
                     jnp.arange(S)[None, None, None, :], rate)
    keep = keep.reshape(B, K, G, S, S)
    p = jnp.where(keep, p / (1.0 - rate), 0.0)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, N, D).astype(q.dtype)


def test_flash_dropout_matches_masked_dense():
    from hetu_galvatron_tpu.ops.pallas.flash_attention import seed_from_key

    q, k, v = _qkv(S=64, D=16)
    rng = jax.random.key(5)
    seed = seed_from_key(rng)
    ref = _ref_dropout_attn(q, k, v, seed, 0.2)
    out = flash_sdpa(q, k, v, causal=True, interpret=True,
                     dropout_rate=0.2, dropout_rng=rng)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_dropout_gradients_match_masked_dense():
    from hetu_galvatron_tpu.ops.pallas.flash_attention import seed_from_key

    q, k, v = _qkv(S=32, N=4, K=2, D=16)  # GQA
    rng = jax.random.key(11)
    seed = seed_from_key(rng)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_dropout_attn(q, k, v, seed, 0.3) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_sdpa(q, k, v, causal=True, interpret=True,
                                  dropout_rate=0.3, dropout_rng=rng) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-5)


def test_flash_dropout_block_size_invariant():
    """The mask hashes GLOBAL coordinates, so different tilings drop the
    same entries."""
    q, k, v = _qkv(S=64, D=16)
    rng = jax.random.key(3)
    a = flash_sdpa(q, k, v, interpret=True, dropout_rate=0.25,
                   dropout_rng=rng, block_q=16, block_k=32)
    b = flash_sdpa(q, k, v, interpret=True, dropout_rate=0.25,
                   dropout_rng=rng, block_q=32, block_k=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


def test_flash_dropout_statistics_and_zero_rate():
    from hetu_galvatron_tpu.ops.pallas.flash_attention import keep_mask

    # empirical keep fraction over a large grid ~ 1 - rate
    bn = jnp.zeros((1,), jnp.int32)
    m = keep_mask(jnp.int32(123), bn, jnp.arange(512)[:, None],
                  jnp.arange(512)[None, :], 0.3)
    frac = float(jnp.mean(m.astype(jnp.float32)))
    assert abs(frac - 0.7) < 0.01, frac
    # rate 0 == no dropout path
    q, k, v = _qkv(S=32, D=16)
    a = flash_sdpa(q, k, v, interpret=True)
    b = flash_sdpa(q, k, v, interpret=True, dropout_rate=0.0,
                   dropout_rng=jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_apply_attention_flash_dropout_dispatch(cpu_devices):
    """modules.apply_attention routes attention_dropout through a
    dropout-capable kernel instead of refusing (ring still refuses)."""
    from jax.sharding import Mesh

    from hetu_galvatron_tpu.core.args_schema import ModelArgs
    from hetu_galvatron_tpu.models import modules as M
    from hetu_galvatron_tpu.ops.ring_attention import make_ring_sdpa

    cfg = ModelArgs(
        hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
        vocab_size=64, max_position_embeddings=64, seq_length=32,
        attention_dropout=0.2, hidden_act="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", add_bias_linear=False,
        add_qkv_bias=False, make_vocab_size_divisible_by=1)
    p, _ = M.init_attention(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32), jnp.float32)

    def flash_interp(qq, kk, vv, **kw):
        return flash_sdpa(qq, kk, vv, interpret=True, **kw)

    flash_interp.supports_dropout = True
    out = M.apply_attention(p, x, cfg, sdpa_fn=flash_interp,
                            compute_dtype=jnp.float32,
                            dropout_rng=jax.random.key(2))
    assert np.all(np.isfinite(np.asarray(out)))
    ring = make_ring_sdpa(Mesh(np.array(cpu_devices[:2]), ("c",)), ("c",))
    with pytest.raises(NotImplementedError, match="ring"):
        M.apply_attention(p, x, cfg, sdpa_fn=ring,
                          compute_dtype=jnp.float32,
                          dropout_rng=jax.random.key(2))


def test_distributed_flash_dropout(cpu_devices):
    """make_flash_sdpa dropout under shard_map: runs, differs from the
    no-dropout output, is deterministic per key, and decorrelates masks
    across dp shards (each shard folds its mesh coordinates into the
    seed)."""
    from jax.sharding import Mesh

    from hetu_galvatron_tpu.ops.pallas.flash_attention import make_flash_sdpa

    mesh = Mesh(np.array(cpu_devices[:2]).reshape(2), ("dp",))
    sdpa = make_flash_sdpa(mesh, dp_axes=("dp",), interpret=True)
    assert sdpa.supports_dropout
    q, k, v = _qkv(B=4, S=64, D=16)
    rng = jax.random.key(9)
    base = sdpa(q, k, v, causal=True)
    a = sdpa(q, k, v, causal=True, dropout_rate=0.3, dropout_rng=rng)
    b = sdpa(q, k, v, causal=True, dropout_rate=0.3, dropout_rng=rng)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)
    assert np.abs(np.asarray(a - base)).max() > 1e-3
    # shard decorrelation: rows 0-1 (shard 0) and rows 2-3 (shard 1) see
    # different masks even for identical inputs
    q2 = jnp.concatenate([q[:2], q[:2]], axis=0)
    k2 = jnp.concatenate([k[:2], k[:2]], axis=0)
    v2 = jnp.concatenate([v[:2], v[:2]], axis=0)
    out = sdpa(q2, k2, v2, causal=True, dropout_rate=0.3, dropout_rng=rng)
    assert np.abs(np.asarray(out[:2] - out[2:])).max() > 1e-3
    # and grads flow
    g = jax.grad(lambda qq: jnp.sum(sdpa(qq, k, v, causal=True,
                                         dropout_rate=0.3,
                                         dropout_rng=rng) ** 2))(q)
    assert np.all(np.isfinite(np.asarray(g)))


def test_keep_mask_no_long_sequence_aliasing():
    """ADVICE r5: the old per-element counter qpos*s_total+kpos wrapped
    uint32 once s_total exceeded 2**16, handing distant (qpos, kpos) pairs
    within one head bit-identical dropout masks. The chained finalizer mix
    has no sequence-length bound (and no s_total parameter any more): rows
    that PROVABLY aliased under the old scheme at s_total = 2**17
    (qpos * s_total === 0 mod 2**32) must now differ."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import keep_mask

    bn = jnp.zeros((1,), jnp.int32)
    kpos = jnp.arange(4096)[None, :]
    rows = []
    # old counters at s_total=2**17: 0*s+k, (2**15)*s+k = 2**32+k = k,
    # (2**16)*s+k = k — all three rows were identical
    for q in (0, 2 ** 15, 2 ** 16):
        rows.append(np.asarray(keep_mask(
            jnp.int32(7), bn, jnp.full((1, 1), q, jnp.int32), kpos, 0.5)))
    assert not np.array_equal(rows[0], rows[1])
    assert not np.array_equal(rows[0], rows[2])
    assert not np.array_equal(rows[1], rows[2])
    # keep fraction stays calibrated at extreme lengths
    for r in rows:
        assert abs(float(r.mean()) - 0.5) < 0.05


def test_keep_mask_tile_invariance_property():
    """The mask depends only on global coordinates: slicing the full-grid
    mask equals computing the mask on the slice's coordinates (the
    property that keeps fwd/bwd kernels tile-size independent)."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import keep_mask

    S = 128
    bn = jnp.zeros((1,), jnp.int32)
    full = np.asarray(keep_mask(jnp.int32(3), bn,
                                jnp.arange(S)[:, None],
                                jnp.arange(S)[None, :], 0.3))
    tile = np.asarray(keep_mask(jnp.int32(3), bn,
                                (32 + jnp.arange(16))[:, None],
                                (64 + jnp.arange(16))[None, :], 0.3))
    np.testing.assert_array_equal(full[32:48, 64:80], tile)


# ---------------------------------------------------------------------------
# PR 25: the tile loop (in-kernel k / q chunk loops, mask only on the
# diagonal, operands in their own dtype) and the block chooser
# ---------------------------------------------------------------------------


def _fwd_and_grads(fn, q, k, v, do):
    """``fn``'s output and its three gradients as ONE program (op by op the
    dense side is some fifty compiles a case)."""
    def side(q, k, v, do):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + tuple(vjp(do))
    return jax.jit(side)(q, k, v, do)


def _assert_f32_parity(got, ref):
    """f32 inputs keep the tolerances the older cases set."""
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(ref[1:], got[1:]):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-5)


def _assert_close_to_f32_reference(got, ref, what):
    """``chip_smoke.py``'s rule for a bf16 kernel against the f32 reference:
    within two bf16 roundings of the reference's largest magnitude."""
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
        b = np.asarray(b, np.float32)
        tol = 2 * 2.0 ** -7 * np.abs(b).max()
        err = np.abs(np.asarray(a.astype(jnp.float32)) - b).max()
        assert err <= tol, f"{what} {name}: max abs err {err} > {tol}"


@pytest.mark.parametrize("shape", [
    dict(B=1, S=512, N=2, K=2, D=64),    # MHA, head 64 (GPT-2 XL's)
    dict(B=1, S=512, N=4, K=1, D=128),   # GQA 4:1, head 128 (Mistral's)
], ids=["mha_h64", "gqa4_h128"])
def test_flash_bf16_matches_f32_reference(shape):
    """bf16 operands go into the matmuls as they are (f32 accumulation, f32
    softmax statistics, p and ds cast like ``xla_sdpa`` casts its probs):
    forward and all three gradients stay within the bf16 tolerance of the
    dense core run on f32 copies."""
    q, k, v = _qkv(**shape, dtype=jnp.bfloat16)
    do = jax.random.normal(jax.random.key(7), q.shape, jnp.bfloat16)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    ref = _fwd_and_grads(lambda a, b, c: xla_sdpa(a, b, c, causal=True),
                         f32(q), f32(k), f32(v), f32(do))
    got = _fwd_and_grads(
        lambda a, b, c: flash_sdpa(a, b, c, causal=True, interpret=True,
                                   block_q=128, block_k=256), q, k, v, do)
    assert got[0].dtype == jnp.bfloat16
    _assert_close_to_f32_reference(got, ref, str(shape))


@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128)])
def test_flash_causal_interior_diagonal_and_skipped_tiles(block_q, block_k):
    """S=512 with unequal blocks: each of the three kernels meets a chunk
    wholly below the diagonal (no mask built), chunks that cross it (masked)
    and chunks wholly above it (never visited)."""
    q, k, v = _qkv(B=1, S=512, N=4, K=2)
    do = jax.random.normal(jax.random.key(3), q.shape, q.dtype)
    ref = _fwd_and_grads(lambda a, b, c: xla_sdpa(a, b, c, causal=True),
                         q, k, v, do)
    got = _fwd_and_grads(
        lambda a, b, c: flash_sdpa(a, b, c, causal=True, interpret=True,
                                   block_q=block_q, block_k=block_k),
        q, k, v, do)
    _assert_f32_parity(got, ref)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_several_major_blocks(monkeypatch, causal):
    """A sequence longer than one grid step may hold: the chunk loops run
    per major block, the running statistics carry across them and, causal,
    a major block past the diagonal is neither fetched nor computed. The
    residency budget is shrunk so that 768 rows already need 3 k and 6 q
    major blocks (shapes no other test traces, so no cached trace with the
    real budget is reused)."""
    from hetu_galvatron_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 2 * 128 * 24 * 4)
    assert fa._major_chunks(768, 128, 24 * 4) == 2
    q, k, v = _qkv(B=1, S=768, N=2, K=1, D=24)
    do = jax.random.normal(jax.random.key(5), q.shape, q.dtype)
    ref = _fwd_and_grads(lambda a, b, c: xla_sdpa(a, b, c, causal=causal),
                         q, k, v, do)
    got = _fwd_and_grads(
        lambda a, b, c: flash_sdpa(a, b, c, causal=causal, interpret=True,
                                   block_q=128, block_k=128), q, k, v, do)
    _assert_f32_parity(got, ref)


def test_flash_unequal_lengths_as_the_ring_calls_it():
    """Non-causal, Sk != S, heads-major, (o, lse) out and the backward fed
    the same lse: a ring step on an off-diagonal block."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd_hmajor,
        flash_attention_hmajor,
    )

    ks = jax.random.split(jax.random.key(11), 4)
    B, N, K, S, Sk, D = 1, 4, 2, 256, 512, 32
    q = jax.random.normal(ks[0], (B, N, S, D))
    k = jax.random.normal(ks[1], (B, K, Sk, D))
    v = jax.random.normal(ks[2], (B, K, Sk, D))
    do = jax.random.normal(ks[3], (B, N, S, D))

    def dense(q, k, v):
        kk, vv = (jnp.repeat(a, N // K, axis=1) for a in (k, v))
        s = jnp.einsum("bnsd,bntd->bnst", q, kk) / np.sqrt(D)
        return (jnp.einsum("bnst,bntd->bnsd", jax.nn.softmax(s, -1), vv),
                jax.nn.logsumexp(s, axis=-1)[..., None])

    (o_ref, lse_ref), vjp = jax.vjp(dense, q, k, v)
    g_ref = vjp((do, jnp.zeros_like(lse_ref)))
    o, lse = flash_attention_hmajor(q, k, v, None, causal=False,
                                    block_q=128, block_k=256, interpret=True)
    assert lse.shape == (B, N, S, 1) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=2e-5, atol=2e-5)
    g = flash_attention_bwd_hmajor(q, k, v, o, lse, do, None, causal=False,
                                   block_q=128, block_k=256, interpret=True)
    for a, b in zip(g_ref, g):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_segment_boundary_inside_interior_tile(causal):
    """Segment boundaries at 60 and 300 with 128-blocks over S=512: the
    first lies inside k chunk 0, which is wholly below the diagonal for
    every later q block, so the segment mask must hold on tiles that build
    no causal mask (and, for the rows of later segments, on chunks where a
    row sees nothing at all before its own segment begins)."""
    q, k, v = _qkv(B=2, S=512, N=2, K=2)
    do = jax.random.normal(jax.random.key(9), q.shape, q.dtype)
    seg = jnp.asarray(np.repeat(
        np.concatenate([np.zeros(60, np.int32), np.ones(240, np.int32),
                        np.full(212, 2, np.int32)])[None], 2, axis=0))
    ref = _fwd_and_grads(
        lambda a, b, c: xla_sdpa(a, b, c, causal=causal, segment_ids=seg),
        q, k, v, do)
    got = _fwd_and_grads(
        lambda a, b, c: flash_sdpa(a, b, c, causal=causal, interpret=True,
                                   segment_ids=seg, block_q=128,
                                   block_k=128), q, k, v, do)
    _assert_f32_parity(got, ref)


def test_flash_dropout_gradients_with_unequal_blocks():
    """The dk/dv kernel walks [k, q] tiles: its regenerated dropout mask
    must be the forward's at every global (q, k) position."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        keep_mask,
        seed_from_key,
    )

    q, k, v = _qkv(B=1, S=256, N=2, K=1)
    rate, rng = 0.25, jax.random.key(21)
    seed = seed_from_key(rng)
    pos = jnp.arange(256, dtype=jnp.int32)
    keep = jnp.stack([keep_mask(seed[0], jnp.int32(n), pos[:, None],
                                pos[None, :], rate) for n in range(2)])

    def dense(q, k, v):
        kk, vv = (jnp.repeat(a, 2, axis=2) for a in (k, v))
        s = jnp.einsum("bsnd,btnd->bnst", q, kk) / np.sqrt(q.shape[-1])
        s = jnp.where(pos[:, None] >= pos[None, :], s, -1e30)
        p = jnp.where(keep[None], jax.nn.softmax(s, -1) / (1 - rate), 0.0)
        return jnp.einsum("bnst,btnd->bsnd", p, vv)

    do = jax.random.normal(jax.random.key(4), q.shape, q.dtype)
    ref = _fwd_and_grads(dense, q, k, v, do)
    got = _fwd_and_grads(
        lambda a, b, c: flash_sdpa(a, b, c, causal=True, interpret=True,
                                   dropout_rate=rate, dropout_rng=rng,
                                   block_q=128, block_k=64), q, k, v, do)
    _assert_f32_parity(got, ref)


# (D, S, Sk): the four benchmark cells (GPT-2 XL at S=1024, Mistral at
# S=4096, one and four chips share the per-chip attention shape), the ring
# tests' local blocks (floor 8 in interpret mode, full and zigzag halves,
# off-diagonal Sk != S), T5's encoder / decoder / cross attention, and
# lengths no 128-multiple divides
_CHOOSER_SHAPES = [
    (64, 1024, 1024, 128), (128, 4096, 4096, 128),
    (8, 32, 32, 8), (8, 16, 16, 8), (8, 8, 8, 8), (8, 16, 8, 8),
    (8, 8, 16, 8), (16, 16, 16, 128), (32, 384, 384, 128),
    (32, 768, 768, 128), (64, 2048, 1024, 128), (128, 32768, 32768, 128),
    (32, 100, 100, 128),
]


@pytest.mark.parametrize("D,S,Sk,floor", _CHOOSER_SHAPES)
def test_choose_blocks_divide_and_keep_the_tiling_rule(D, S, Sk, floor):
    from hetu_galvatron_tpu.ops.pallas.flash_attention import choose_blocks

    bq, bk = choose_blocks(D, S, Sk, floor)
    assert S % bq == 0 and Sk % bk == 0
    for block, seq in ((bq, S), (bk, Sk)):
        # Mosaic's rule for the second-minor dim of a [block, D] tile and
        # the minor dim of a (1, block) row: a multiple of (8, 128) or the
        # whole array dim; interpret-mode floors only ask for the divisor
        assert block == seq or block % max(floor, 8) == 0
        if floor >= 128:
            assert block == seq or block % 128 == 0
    # a function of (D, S, Sk) alone: same answer again, nothing else read
    assert choose_blocks(D, S, Sk, floor) == (bq, bk)


def test_choose_blocks_for_the_benchmark_cells():
    from hetu_galvatron_tpu.ops.pallas.flash_attention import choose_blocks

    assert choose_blocks(64, 1024, 1024) == (512, 512)     # gpt2xl_c1_*
    assert choose_blocks(128, 4096, 4096) == (512, 512)    # mistral7b_*
    assert choose_blocks(32, 768, 768) == (256, 256)       # halved to fit
    assert choose_blocks(32, 384, 384) == (384, 384)       # under one tile
    assert choose_blocks(32, 100, 100) == (100, 100)       # one whole block


# ---------------------------------------------------------------------------
# a value width of its own (latent attention: q/k 192, v 128)
# ---------------------------------------------------------------------------


def _qkv_wide(B, S, N, K, D, Dv, dtype=jnp.float32, seed=5):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (B, S, N, D), dtype),
            jax.random.normal(ks[1], (B, S, K, D), dtype),
            jax.random.normal(ks[2], (B, S, K, Dv), dtype),
            jax.random.normal(ks[3], (B, S, N, Dv), dtype))


# B, S, heads, kv heads, q/k width, v width, causal, segments, scale
_OWN_V_WIDTH = {
    "latent_ratio": (1, 256, 4, 4, 48, 32, True, False, 0.17),
    "v_wider_than_qk": (2, 128, 2, 2, 16, 40, True, False, None),
    "gqa_noncausal": (1, 256, 8, 2, 24, 16, False, False, None),
    "segments_two_q_blocks": (1, 512, 2, 2, 48, 32, True, True, 0.2),
}


@pytest.mark.parametrize("case", sorted(_OWN_V_WIDTH))
def test_flash_with_a_value_width_of_its_own(case):
    """Forward and the three gradients at ``Dv != D`` against the XLA core:
    the output, dO and dv are ``Dv`` wide, q, k, dq and dk ``D`` wide, and
    nothing is padded to the other's width."""
    B, S, N, K, D, Dv, causal, seg, scale = _OWN_V_WIDTH[case]
    q, k, v, do = _qkv_wide(B, S, N, K, D, Dv)
    segs = ((jnp.arange(S)[None, :] // 200).astype(jnp.int32).repeat(B, 0)
            if seg else None)
    kw = dict(causal=causal, segment_ids=segs, scale=scale)
    want, *want_grads = _fwd_and_grads(
        lambda a, b, c: xla_sdpa(a, b, c, **kw), q, k, v, do)
    got, *got_grads = _fwd_and_grads(
        lambda a, b, c: flash_sdpa(a, b, c, interpret=True, **kw), q, k, v,
        do)
    assert got.shape == (B, S, N, Dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for name, g, w in zip(("dq", "dk", "dv"), got_grads, want_grads):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


# sha256 (first 16 hex) of out | dq | dk | dv in interpret mode on seeded
# inputs, recorded from the kernels as they were before v had a width of its
# own (commit 98a0ba2): with ``Dv == D`` every block, every scratch and every
# instruction is what it was. The bf16 case has no hash: the bits of a bf16
# result in interpret mode follow the machine that runs them (one tree gave
# both answers, PERF_LEDGER.jsonl ``tests.rcs`` of PR 41 to 45), so it is
# held, inside the one process, to the XLA core in float32 on the same
# operands, forward and the three gradients, at what bf16 resolves
_TODAYS = {
    "mha_f32": ((2, 256, 4, 4, 32, jnp.float32, False), "bbb22d8f65cd9718"),
    "gqa_bf16_segments": ((1, 384, 8, 2, 64, jnp.bfloat16, True), None),
}


@pytest.mark.parametrize("case", sorted(_TODAYS))
def test_flash_at_equal_widths_is_bit_equal_to_the_kernels_before(case):
    import hashlib

    (B, S, N, K, D, dtype, seg), want = _TODAYS[case]
    ks = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(ks[0], (B, S, N, D), dtype)
    k = jax.random.normal(ks[1], (B, S, K, D), dtype)
    v = jax.random.normal(ks[2], (B, S, K, D), dtype)
    do = jax.random.normal(ks[3], (B, S, N, D), dtype)
    segs = ((jnp.arange(S)[None, :] // 96).astype(jnp.int32).repeat(B, 0)
            if seg else None)
    kw = dict(causal=True, segment_ids=segs, scale=0.11 if seg else None)
    out, vjp = jax.vjp(lambda a, b, c: flash_sdpa(
        a, b, c, interpret=True, **kw), q, k, v)
    got = [np.asarray(t.astype(jnp.float32)) for t in (out,) + vjp(do)]
    if want is not None:
        h = hashlib.sha256()
        for t in got:
            h.update(t.tobytes())
        assert h.hexdigest()[:16] == want
        return
    f32 = lambda t: t.astype(jnp.float32)
    ref, ref_vjp = jax.vjp(lambda a, b, c: xla_sdpa(a, b, c, **kw),
                           f32(q), f32(k), f32(v))
    for name, g, w in zip(("out", "dq", "dk", "dv"), got,
                          (ref,) + ref_vjp(f32(do))):
        w = np.asarray(w)
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, rtol=2e-2, atol=2e-2, err_msg=name)
        # (measured 1.9e-3 to 2.6e-3: a bf16 result's own rounding)
        assert np.sqrt(np.mean((g - w) ** 2) / np.mean(w ** 2)) < 5e-3, name


# a window: S, block_q, block_k, window, segments, dropout. The window is
# smaller than a tile, a tile, straddles tiles of unequal sizes, is one key,
# is the sequence less one; the sequence is whole tiles, ragged halvings
# (48 = 3 x 16) and one whole-length block (40)
_WINDOWS = {
    "under_a_tile": (64, 16, 16, 5, False, 0.0),
    "a_tile": (64, 16, 16, 16, False, 0.0),
    "a_tile_and_a_half": (64, 16, 16, 24, False, 0.0),
    "wide_k_tiles": (64, 16, 32, 17, False, 0.0),
    "wide_q_tiles": (64, 32, 16, 33, False, 0.0),
    "itself_alone": (64, 16, 16, 1, False, 0.0),
    "all_but_one": (64, 16, 16, 63, False, 0.0),
    "three_tiles": (48, 16, 16, 20, False, 0.0),
    "one_block": (40, None, None, 7, False, 0.0),
    "segments": (64, 16, 16, 24, True, 0.0),
    "segments_wide_q": (64, 32, 16, 10, True, 0.0),
    "dropout": (64, 16, 32, 24, False, 0.25),
    "segments_and_dropout": (64, 16, 16, 12, True, 0.25),
}


def _ref_window_attn(q, k, v, window, segs, seed, rate):
    """The dense band: softmax over the window's keys, then the kernels'
    own counter-based dropout mask over (head, q position, k position)."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import keep_mask

    B, S, N, D = q.shape
    G = N // k.shape[2]
    kf, vf = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bsnd,btnd->bnst", q, kf) / np.sqrt(D)
    ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    seen = (ahead >= 0) & (ahead < window)
    if segs is not None:
        seen = seen & (segs[:, None, :, None] == segs[:, None, None, :])
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    if rate:
        bn = (jnp.arange(B)[:, None] * N + jnp.arange(N)[None, :])
        keep = keep_mask(seed[0], bn[:, :, None, None],
                         jnp.arange(S)[None, None, :, None],
                         jnp.arange(S)[None, None, None, :], rate)
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bnst,btnd->bsnd", p, vf)


@pytest.mark.parametrize("case", sorted(_WINDOWS))
def test_flash_window_matches_the_dense_band(case):
    """out, dq, dk and dv of the three kernels with a window against the
    dense band, and the band against ``xla_sdpa``'s own window."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import seed_from_key

    S, bq, bk, window, seg, rate = _WINDOWS[case]
    q, k, v = _qkv(S=S, N=4, K=2, D=32, seed=3)
    do = jax.random.normal(jax.random.key(7), q.shape, q.dtype)
    segs = ((jnp.arange(S)[None, :] >= jnp.array([[S // 3], [S // 2]])
             ).astype(jnp.int32) if seg else None)
    rng = jax.random.key(5)
    seed = seed_from_key(rng) if rate else None
    ref = _fwd_and_grads(
        lambda a, b, c: _ref_window_attn(a, b, c, window, segs, seed, rate),
        q, k, v, do)
    got = _fwd_and_grads(
        lambda a, b, c: flash_sdpa(
            a, b, c, interpret=True, block_q=bq, block_k=bk, window=window,
            segment_ids=segs, dropout_rate=rate,
            dropout_rng=rng if rate else None), q, k, v, do)
    _assert_f32_parity(got, ref)
    if not rate:
        _assert_f32_parity(_fwd_and_grads(
            lambda a, b, c: xla_sdpa(a, b, c, window=window,
                                     segment_ids=segs), q, k, v, do), ref)


def test_flash_window_across_major_blocks(monkeypatch):
    """The band over several major blocks: a k major block wholly before
    the band is neither fetched nor computed (forward, dq), nor a q major
    block wholly past it (dk/dv)."""
    from hetu_galvatron_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 2 * 128 * 24 * 4)
    q, k, v = _qkv(B=1, S=768, N=2, K=1, D=24, seed=9)
    do = jax.random.normal(jax.random.key(5), q.shape, q.dtype)
    for window in (100, 300):
        ref = _fwd_and_grads(
            lambda a, b, c: xla_sdpa(a, b, c, window=window), q, k, v, do)
        got = _fwd_and_grads(
            lambda a, b, c: flash_sdpa(a, b, c, interpret=True, block_q=128,
                                       block_k=128, window=window),
            q, k, v, do)
        _assert_f32_parity(got, ref)


def test_a_window_no_shorter_than_the_sequence_is_no_window():
    """``window >= S`` is the unwindowed call, jaxpr for jaxpr; a window
    needs the causal span and at least the query's own key."""
    q, k, v = _qkv(S=64, N=2, K=2, D=16)

    def text(**kw):
        return str(jax.make_jaxpr(lambda a, b, c: flash_sdpa(
            a, b, c, interpret=True, **kw))(q, k, v))

    assert text(window=64) == text() == text(window=1000)
    assert text(window=63) != text()
    with pytest.raises(ValueError, match="causal"):
        flash_sdpa(q, k, v, interpret=True, causal=False, window=8)
    with pytest.raises(ValueError, match="at least 1"):
        flash_sdpa(q, k, v, interpret=True, window=0)
    with pytest.raises(ValueError, match="causal span"):
        xla_sdpa(q, k, v, causal=False, window=8)


def test_band_tiles_count_the_loops_of_the_kernels():
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        WINDOWED_CALLS, band_tiles)

    # the cell's window blocks: 16 q tiles, two k tiles each but the first
    assert band_tiles(8192, 512, 512, 512) == (31, 136)
    assert band_tiles(8192, 512, 512, None) == (136, 136)
    assert band_tiles(8192, 256, 256, 512) == (3 * 32 - 3, 32 * 33 // 2)
    assert band_tiles(64, 16, 16, 1) == (4, 10)
    # a call is recorded with the window and tiles the kernels are handed,
    # once however often it is traced; one without a window is not
    q = jax.ShapeDtypeStruct((1, 8192, 72, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    WINDOWED_CALLS.clear()
    for window in (None, 8192, 512, 512):
        jax.eval_shape(functools.partial(flash_sdpa, window=window), q, k, k)
    assert WINDOWED_CALLS == {(8192, 72, 512, 512, 512)}
    WINDOWED_CALLS.clear()


# sha256 of the jaxpr of loss and gradients through ``flash_sdpa`` WITHOUT a
# window, with `` at <file>:<line>`` taken out (a kernel's source position
# rides in its ``name_and_src_info``). The two cases at 192 keep the
# head-major kernels between transposes and were recorded from the parent of
# the commit that gave the kernels the projections' rows (3c3b657): such a
# call traces to the program it was, equation for equation, as the
# unwindowed call did across the commit that gave the kernels a window
# (f5bc594: the digests of the two cases at 128 and 64 until their calls
# moved to the rows, re-recorded from this commit). The two cases with
# segments were re-recorded where their calls took the segments' chunk
# ranges as prefetched scalars; the two without are the programs they were
# across that commit too
_UNWINDOWED = {
    "gqa_128": ((1024, 4, 2, 128, 128, False, False),
                "cd637775ca9bb06472f0a17f0d7b1fbd6b8855ead467bdba9de82b28"
                "4c6ac338"),
    "segments_dropout_64": ((512, 2, 2, 64, 64, True, True),
                            "bd0d2016624bae59788710cdce31800565f44ecd3a87e351"
                            "cb01fc83a22354ac"),
    "latent_192_128": ((1024, 4, 4, 192, 128, False, False),
                       "45d7f6bd14a7f32d4728e708b9f107c8509890eacd41b07a02a9"
                       "0040ae49a68b"),
    "gqa_192_segments_dropout": ((512, 4, 2, 192, 192, True, True),
                                 "0265e3d1b1e82a36dd11e4decd0deb55885f0d20481"
                                 "64300bbba7eb9ee725012"),
}


def _loss_and_gradients_jaxpr(S, N, K, D, Dv, seg, drop, **kw):
    q = jax.ShapeDtypeStruct((2, S, N, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, S, K, D), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, S, K, Dv), jnp.bfloat16)
    segs = jnp.zeros((2, S), jnp.int32) if seg else None

    def loss(q, k, v):
        return jnp.sum(flash_sdpa(
            q, k, v, segment_ids=segs, dropout_rate=0.1 if drop else 0.0,
            dropout_rng=jax.random.key(0) if drop else None, **kw
        ).astype(jnp.float32))

    return jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("case", sorted(_UNWINDOWED))
def test_an_unwindowed_call_is_the_jaxpr_it_was(case):
    import hashlib
    import re

    sizes, want = _UNWINDOWED[case]
    text = re.sub(r" at [^\s:]+:\d+", "",
                  str(_loss_and_gradients_jaxpr(*sizes)))
    assert hashlib.sha256(text.encode()).hexdigest() == want


def _equations(jaxpr, found=None):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold,
    a kernel's own body left out."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn)
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _equations(sub, found)
    return found


# S, heads, kv heads, q/k width, v width, segments, dropout, window; how
# many transposes the loss and its gradients hold
_RELAYOUTS = {
    "w128_gqa": ((1024, 4, 2, 128, 128, False, False, None), 0),
    "w64_25_heads": ((1024, 25, 25, 64, 64, False, False, None), 0),
    "w64_g4_segments_dropout": ((512, 8, 2, 64, 64, True, True, None), 0),
    "w128_window": ((1024, 8, 2, 128, 128, False, False, 300), 0),
    "w256_v128": ((512, 2, 2, 256, 128, False, False, None), 0),
    # q, k, v in, the output back; the kept rows and the cotangent in, dq,
    # dk and dv back
    "latent_192_128": ((512, 4, 4, 192, 128, False, False, None), 9),
    "w64_three_kv_heads_g2": ((512, 6, 3, 64, 64, False, False, None), 9),
    "w32": ((512, 4, 4, 32, 32, False, False, None), 9),
}


@pytest.mark.parametrize("case", sorted(_RELAYOUTS))
def test_a_call_on_rows_holds_no_transpose_around_its_kernels(case):
    """Loss and gradients through ``flash_sdpa`` at a width the kernels
    index as rows: three ``pallas_call``s between reshapes and no
    ``transpose`` at all, of an operand, a result, a cotangent or a
    statistic (dk/dv's delta comes from the dq kernel in the rows it is
    read in). At any other width (and for an odd count of key/value heads
    under groups, whose pairs would straddle column blocks) the nine
    transposes of the head-major path."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import LAYOUT_CALLS

    (*sizes, window), relayouts = _RELAYOUTS[case]
    LAYOUT_CALLS.clear()
    eqns = _equations(
        _loss_and_gradients_jaxpr(*sizes, window=window).jaxpr)
    assert sum(e.primitive.name == "pallas_call" for e in eqns) == 3
    moved = [e.invars[0].aval.shape for e in eqns
             if e.primitive.name == "transpose"]
    assert all(len(shape) == 4 for shape in moved)
    S, N, K, D, Dv = sizes[:5]
    assert LAYOUT_CALLS == {
        ("transposed" if relayouts else "rows", S, S, N, K, D, Dv, window)}
    assert len(moved) == relayouts
    LAYOUT_CALLS.clear()
