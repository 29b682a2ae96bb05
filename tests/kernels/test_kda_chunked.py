"""The chunked form of Kimi Delta Attention's recurrence
(``modules.kda_chunked``: the two pair matrices under their decays, the
triangular inverse, the scan over the chunks) against the recurrence one
position at a time, as the benchmark's plain reference runs it
(``benchmark/reference/kimi_linear.py::delta_rule``): values and the
gradients to all five inputs, at a sequence the chunk does not divide, at
two chunk lengths, from a mild decay to the strongest the initialisation
draws and well past it. CPU, float32 at ``highest``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from hetu_galvatron_tpu.models import modules as M

pytestmark = pytest.mark.kernels

BATCH, SEQ, HEADS, WIDTH = 2, 150, 3, 16
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
# the log decay a token and channel: the mildest and the strongest a fresh
# block draws (A in [1, 16) times softplus in [1e-3, 1e-1]), and five times
# past the strongest, where exp(-G_j) over a chunk of 64 is exp(512)
DECAYS = {"mildest_init": 1e-3, "strongest_init": 1.6, "past_init": 8.0}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _recurrence():
    return reference.load_family("kimi_linear").delta_rule


def _inputs(strength, seed=0, seq=SEQ):
    ks = jax.random.split(jax.random.key(seed), 5)
    shape = (BATCH, seq, HEADS, WIDTH)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], shape)) * WIDTH ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    # every channel decays at its own rate, up to ``strength`` a token
    g = -strength * jax.random.uniform(ks[3], shape, minval=0.05, maxval=1.0)
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], shape[:3]))
    return q, k, v, g, beta


def _values_and_gradients(fn):
    """The program of ``fn``'s value and five gradients (one a side: op by
    op a form is hundreds of compiles)."""
    def both(*a):
        weight = jax.random.normal(jax.random.key(9), a[2].shape)
        return (fn(*a),) + jax.grad(lambda *b: jnp.sum(fn(*b) * weight),
                                    argnums=(0, 1, 2, 3, 4))(*a)
    return jax.jit(both)


@functools.lru_cache(maxsize=None)
def _side(chunk):
    """The chunked form's program, the recurrence's where ``chunk`` is
    None: the decays differ in their data alone and are one compile."""
    return _values_and_gradients(
        _recurrence() if chunk is None
        else lambda *a: M.kda_chunked(*a, chunk, jnp.float32))


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("decay", list(DECAYS))
def test_chunked_form_is_the_recurrence(decay, chunk):
    args = _inputs(DECAYS[decay])
    got, want = _side(chunk)(*args), _side(None)(*args)
    for name, a, b in zip(NAMES, got, want):
        assert bool(jnp.all(jnp.isfinite(a))), (name, decay, chunk)
        # tolerance: float32 on both sides, sums in another order; relative
        # to the largest entry (a gradient's entries span many magnitudes
        # where the decay is strong)
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=0, atol=3e-5 * scale,
                                   err_msg=f"{name} {decay} {chunk}")


def test_a_split_decay_over_a_whole_chunk_overflows_and_this_does_not():
    """What the sub-blocks are for: at the strongest initial decay ``exp(-G)``
    over a chunk of 64 is ``exp(102)``, past float32; the form that splits
    ``exp(G_i - G_j)`` into two factors over the whole chunk is not finite,
    the program's is."""
    q, k, v, g, beta = _inputs(DECAYS["strongest_init"], seq=64)
    G = jnp.cumsum(jnp.full_like(g, -1.6), axis=1)
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-G))))
    out = M.kda_chunked(q, k, v, jnp.full_like(g, -1.6), beta, 64,
                        jnp.float32)
    assert bool(jnp.all(jnp.isfinite(out)))
    want = _recurrence()(q, k, v, jnp.full_like(g, -1.6), beta)
    np.testing.assert_allclose(out, want, rtol=0,
                               atol=3e-5 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("leave_out", ["beta", "decay", "delta_term"])
def test_the_recurrence_without_a_part_is_another_function(leave_out):
    """The comparison above can tell: with ``beta`` at one, the decay at
    none, or the delta term ``S~^T k`` dropped, the recurrence moves by far
    more than the tolerance."""
    q, k, v, g, beta = _inputs(DECAYS["strongest_init"] / 8)
    got = M.kda_chunked(q, k, v, g, beta, 32, jnp.float32)
    if leave_out == "beta":
        want = _recurrence()(q, k, v, g, jnp.ones_like(beta))
    elif leave_out == "decay":
        want = _recurrence()(q, k, v, jnp.zeros_like(g), beta)
    else:
        # without S~^T k the update is plain linear attention's
        # beta_t k_t v_t^T under the same decay
        def step(state, at):
            q_t, k_t, v_t, g_t, b_t = at
            state = jnp.exp(g_t)[..., None] * state + (
                b_t[..., None] * k_t)[..., None] * v_t[..., None, :]
            return state, jnp.einsum("bnkv,bnk->bnv", state, q_t)
        zero = jnp.zeros((BATCH, HEADS, WIDTH, WIDTH))
        want = jnp.moveaxis(jax.lax.scan(step, zero, tuple(
            jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))[1], 0, 1)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2 * scale


@pytest.mark.parametrize("size,sub", [(16, 16), (32, 16), (64, 16), (8, 8)])
def test_the_triangular_inverse_and_its_cotangent(size, sub):
    """``unit_lower_inverse`` reads the strictly lower triangle alone and
    inverts ``I + N``; its written-out cotangent is ``-X^T g X^T`` on that
    triangle. Both against numpy in float64, at entries of order a half
    (the inverse's reach the hundreds at 64)."""
    N = 0.5 * jax.random.normal(jax.random.key(size), (3, 2, size, size))
    strict = np.tril(np.ones((size, size), bool), -1)
    exact = np.linalg.inv(np.eye(size) + np.where(
        strict, np.asarray(N, np.float64), 0))
    # (under ``jit``: op by op the inverse's rows compile one by one)
    X = jax.jit(lambda n: M.unit_lower_inverse(n, sub))(N)
    assert float(jnp.max(jnp.abs(jnp.triu(X, 1)))) == 0.0
    np.testing.assert_allclose(X, exact, rtol=0,
                               atol=1e-5 * np.abs(exact).max())
    w = jax.random.normal(jax.random.key(1), X.shape)
    got = jax.jit(jax.grad(
        lambda n: jnp.sum(M.unit_lower_inverse(n, sub) * w)))(N)
    Xt = np.swapaxes(exact, -1, -2)
    want = np.where(strict, -Xt @ np.asarray(w, np.float64) @ Xt, 0)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.abs(want).max())


def test_the_pair_matrices_element_by_element():
    """``kda_pairs`` against the sum written out for every pair of positions,
    where the decays are strong enough that a whole-chunk split would
    overflow: sub-blocks on the diagonal and reference points off it give
    the same numbers, and nothing above the diagonal."""
    q, k, _, g, _ = _inputs(4.0, seq=64)
    q, k, g = (jnp.swapaxes(t, 1, 2) for t in (q, k, g))   # [B, H, C, d]
    G = jnp.cumsum(g, axis=-2)
    a_qk, a_kk = M.kda_pairs(q, k, G, 16, jnp.float32)
    lower = jnp.tril(jnp.ones((64, 64), bool))
    diff = jnp.where(lower[..., None],
                     G[..., :, None, :] - G[..., None, :, :], -jnp.inf)
    for got, a in ((a_qk, q), (a_kk, k)):
        want = jnp.sum(a[..., :, None, :] * k[..., None, :, :]
                       * jnp.exp(diff), axis=-1)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_which_chunks_and_groups_follow_from_shapes_alone():
    assert M.kda_sub_blocks(64) == (16, 4)
    assert M.kda_sub_blocks(8) == (8, 1)
    with pytest.raises(ValueError, match="kda_chunk_size=48"):
        M.kda_sub_blocks(48)
    with pytest.raises(ValueError, match="kda_chunk_size=24"):
        M.kda_sub_blocks(24)
    # the cell's shapes: one 8192-token sequence, 32 heads of 128 at chunk
    # 64 is 16 MiB of element-by-element decays a chunk
    per_chunk = 32 * 4 * 16 * 16 * 128 * 4
    size = M.kda_chunks_a_group(1, 128, 32, 64, 128)
    assert 128 % size == 0 and size * per_chunk <= M.KDA_PAIR_BYTES
    assert (size * 2) * per_chunk > M.KDA_PAIR_BYTES or size == 128
    assert M.kda_chunks_a_group(1, 3, 2, 16, 8) == 3
