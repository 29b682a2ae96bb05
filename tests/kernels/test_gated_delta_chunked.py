"""The chunked form of the gated delta rule with a decay a head
(``modules.gated_delta_chunked``: one matmul a pair matrix times ``exp(G_i -
G_j)``, the triangular inverse, the scan over the chunks) against the
recurrence one position at a time, as the benchmark's plain reference runs
it (``benchmark/reference/olmo_hybrid.py::delta_rule``): values and the
gradients to all five inputs, keys narrower than values, at a sequence the
chunk does not divide and over several chunks, ``beta`` from the middle of
its range to hard against 2, decays from next to none to a state gone within
a token. CPU, float32 at ``highest``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from hetu_galvatron_tpu.models import modules as M

pytestmark = pytest.mark.kernels

BATCH, SEQ, HEADS, KEYS, VALUES = 2, 150, 3, 24, 48
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
# the log decay a token and head: exp(-1e-4) keeps the state over the whole
# sequence (a decay near 1), 1.6 is the strongest a fresh block draws (A 16
# times softplus 0.1), 20 leaves exp(-20) of a state a token (near 0)
DECAYS = {"near_one": 1e-4, "strongest_init": 1.6, "near_zero": 20.0}
# beta = 2 sigmoid(b): b drawn around 0, and pushed to where beta > 1.99
BETAS = {"mid_range": 0.0, "near_two": 6.0}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _recurrence():
    return jax.jit(reference.load_family("olmo_hybrid").delta_rule)


def _chunked(chunk, dtype=jnp.float32):
    """(under ``jit``: op by op the inverse's rows compile one by one)"""
    return jax.jit(lambda *a: M.gated_delta_chunked(*a, chunk, dtype))


def _inputs(strength, push, seed=0, seq=SEQ):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (BATCH, seq, HEADS, KEYS))) \
        * KEYS ** -0.5
    k = unit(jax.random.normal(ks[1], (BATCH, seq, HEADS, KEYS)))
    v = jax.random.normal(ks[2], (BATCH, seq, HEADS, VALUES))
    g = -strength * jax.random.uniform(ks[3], (BATCH, seq, HEADS),
                                       minval=0.05, maxval=1.0)
    beta = 2.0 * jax.nn.sigmoid(
        push + jax.random.normal(ks[4], (BATCH, seq, HEADS)))
    return q, k, v, g, beta


@functools.lru_cache(maxsize=None)
def _values_and_gradients(chunk):
    """The program of a side's value and five gradients, the recurrence's
    where ``chunk`` is None: the cases that differ in their data alone (the
    decays, the betas) are one compile of it."""
    fn = _recurrence() if chunk is None else _chunked(chunk)

    def side(*args):
        weight = jax.random.normal(jax.random.key(9), args[2].shape)
        return (fn(*args),) + jax.grad(
            lambda *a: jnp.sum(fn(*a) * weight),
            argnums=(0, 1, 2, 3, 4))(*args)
    return jax.jit(side)


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("beta", list(BETAS))
@pytest.mark.parametrize("decay", list(DECAYS))
def test_chunked_is_the_recurrence(decay, beta, chunk):
    """150 positions are 4.7 chunks of 32 and 2.3 of 64: the carried state,
    the padding and both triangles are exercised. Both sides are float32
    and differ in operation order only; with ``beta`` near 2 and next to no
    decay the delta rule reflects the state along each key and rounding
    errors are carried rather than damped, so the band is a few 1e-4 of
    each array's largest entry."""
    args = _inputs(DECAYS[decay], BETAS[beta])
    if beta == "near_two":
        assert float(jnp.mean(args[4] > 1.99)) > 0.3
    want = _values_and_gradients(None)(*args)
    got = _values_and_gradients(chunk)(*args)
    for name, a, b in zip(NAMES, got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=3e-4 * scale + 1e-9,
                                   err_msg=name)


def test_a_chunk_that_divides_nothing_and_one_that_holds_all():
    """A sequence shorter than its chunk (all padding behind it) and a
    chunk of one sub-block."""
    args = _inputs(1.6, 0.0, seq=23)
    want = _recurrence()(*args)
    for chunk in (64, 16, 8):
        np.testing.assert_allclose(_chunked(chunk)(*args), want,
                                   rtol=1e-4, atol=1e-6, err_msg=str(chunk))


def test_bf16_operands_stay_near_the_recurrence():
    """What the cell runs: bfloat16 matmul operands, float32 sums of the
    decay, inverse and state. Within bf16's eight bits of the largest
    output."""
    args = _inputs(1.6, 0.0)
    want = _recurrence()(*args)
    got = _chunked(64, jnp.bfloat16)(*args)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 3e-2 * float(
        jnp.max(jnp.abs(want)))


def test_the_carry_is_the_one_kda_runs():
    """``kda_chunked`` with every channel of a head decaying alike is the
    scalar form: the two share ``unit_lower_inverse`` and ``delta_carry``
    and differ in how the pair matrices are made."""
    q, k, v, g, beta = _inputs(1.6, 0.0)
    v, beta = v[..., :KEYS], beta / 2.0      # kda: a square state, beta < 1
    wide = jnp.broadcast_to(g[..., None], q.shape)
    np.testing.assert_allclose(
        _chunked(32)(q, k, v, g, beta),
        jax.jit(lambda *a: M.kda_chunked(*a, 32, jnp.float32))(
            q, k, v, wide, beta),
        rtol=1e-4, atol=1e-6)
