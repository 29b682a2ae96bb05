"""The Pallas kernels of the chunked gated delta rule (``ops/pallas/kda.py``)
in interpret mode on the CPU, at the widths the benchmark's cell runs (heads
of 128 keys and 128 values, chunk 64) with one row and a few heads (and
once with the cell's 32): against
``modules.kda_chunked``'s ``jax.numpy`` form AND against the plain
reference's recurrence one position at a time, values and the gradients to
all five inputs, where the chunk divides the sequence and where the last
chunk is padded, one and several packs of heads a step and one and two
steps of heads, two and four heads a pack, decays from the mildest
the initialisation draws to five times past the strongest, with float32
operands (tight) and bfloat16 operands (the program's). Then the controls
that tell a state carried in bfloat16 and an inverse of bfloat16 operands
from float32, pointed at the kernels. That which path runs follows from
shapes and devices alone is in ``test_kda_kernel_wiring.py``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.ops.pallas import kda

pytestmark = pytest.mark.kernels

WIDTH, CHUNK = 128, 64
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
# the log decay a token and channel, as ``test_kda_chunked``: the mildest and
# the strongest a fresh block draws, and five times past the strongest
DECAYS = {"mildest_init": 1e-3, "strongest_init": 1.6, "past_init": 8.0}
# (positions, heads, operands' dtype, decay, chunk): three chunks with the
# last one padded and one pack of two heads, at every decay; two packs a
# step; the cell's heads, two steps of eight packs; four heads a pack; the
# program's dtype
CASES = {
    "ragged_mild": (150, 2, "float32", "mildest_init", 64),
    "ragged_strong": (150, 2, "float32", "strongest_init", 64),
    "ragged_past": (150, 2, "float32", "past_init", 64),
    "two_packs_a_step": (150, 4, "float32", "strongest_init", 64),
    "four_heads_a_pack": (80, 4, "float32", "strongest_init", 32),
    "two_steps_of_sixteen": (128, 32, "bfloat16", "strongest_init", 64),
    "ragged_bf16": (150, 2, "bfloat16", "mildest_init", 64),
}
# distance allowed as a share of the other side's largest entry, (values,
# gradients): float32 sides differ in operation order alone; with bfloat16
# operands the kernels and the jax.numpy form round the same operands (made
# by sums in another order, so a rounding apart here and there), and both
# stand a few thousandths from the float32 recurrence
LIMITS = {("float32", "chunked"): (1e-5, 3e-5),
          ("float32", "sequential"): (3e-5, 3e-5),
          ("bfloat16", "chunked"): (1e-2, 2e-2),
          ("bfloat16", "sequential"): (2e-2, 5e-2)}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _recurrence():
    return reference.load_family("kimi_linear").delta_rule


def _inputs(seq, heads, strength, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    shape = (1, seq, heads, WIDTH)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], shape)) * WIDTH ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    # every channel decays at its own rate, up to ``strength`` a token
    g = -strength * jax.random.uniform(ks[3], shape, minval=0.05, maxval=1.0)
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], shape[:3]))
    return q, k, v, g, beta


def _values_and_gradients(fn):
    """``fn``'s value and its gradients to its arguments as ONE program (a
    compile a side, not one an operation), each in float32."""
    def side(*a):
        out, vjp = jax.vjp(fn, *a)
        return (out,) + vjp(jax.random.normal(jax.random.key(9), out.shape,
                                              out.dtype))
    program = jax.jit(side)
    return lambda *a: tuple(np.asarray(t, np.float32) for t in program(*a))


@functools.lru_cache(maxsize=None)
def _side(name, dtype, chunk):
    """A side's program: the cases that differ in their data alone (the
    three decays) are one compile of it."""
    dtype = jnp.dtype(dtype)
    return _values_and_gradients({
        "kernel": lambda *a: M.kda_chunked(
            *a, chunk, dtype, scan_fn=functools.partial(kda.kda_scan,
                                                        interpret=True)),
        "chunked": lambda *a: M.kda_chunked(*a, chunk, dtype),
        "sequential": _recurrence()}[name])


@functools.lru_cache(maxsize=None)
def _sides(case):
    """(kernels, chunked in jax.numpy, sequential in float32) on one set of
    inputs, each as (o, dq, dk, dv, dg, dbeta) in float32."""
    seq, heads, dtype, decay, chunk = CASES[case]
    args = _inputs(seq, heads, DECAYS[decay])
    # (shapes that fit no tile would fall back, and compare nothing)
    assert kda.tile_plan(chunk, heads, WIDTH, WIDTH) is not None
    with jax.default_matmul_precision("highest"):
        return {name: _side(name, dtype, chunk)(*args)
                for name in ("kernel", "chunked", "sequential")}


def _apart(a, b):
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.mark.parametrize("quantity", NAMES)
@pytest.mark.parametrize("against", ["chunked", "sequential"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_scan_is_the_chunked_and_the_sequential_one(
        case, against, quantity):
    sides = _sides(case)
    at = NAMES.index(quantity)
    got, want = sides["kernel"][at], sides[against][at]
    assert got.shape == want.shape and np.isfinite(got).all()
    limit = LIMITS[CASES[case][2], against][min(at, 1)]
    assert _apart(got, want) < limit, (_apart(got, want), limit)


def _recurrence_with_a_bf16_state(q, k, v, g, beta):
    """The recurrence one position at a time with the carried state rounded
    to bfloat16 at every position: what kernels that kept it so compute."""
    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None] * state
        v_new = v_t - jnp.einsum("bnkv,bnk->bnv", state, k_t)
        state = state + (b_t[..., None] * k_t)[..., None] * v_new[
            ..., None, :]
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("bnkv,bnk->bnv", state, q_t)
    zero = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:])
    return jnp.moveaxis(jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))[1], 0, 1)


def _inverse_of_bf16_operands(N, sub):
    """``modules.unit_lower_inverse``'s algorithm with every product's
    operands rounded to bfloat16 (float32 accumulation): what the MXU's
    default precision makes of float32 operands."""
    strict = jnp.tril(jnp.ones(N.shape[-2:], bool), -1)
    r = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    N = jnp.where(strict, N, 0.0)
    eye = jnp.eye(N.shape[-1], dtype=N.dtype)

    def row(X, i):  # the rows from ``i`` on are still zero, as ``N[i, i:]``
        return X.at[..., i, :].set(eye[i] - jnp.einsum(
            "...j,...jk->...k", r(N[..., i, :]), r(X))), None

    return jax.lax.scan(row, jnp.zeros_like(N),
                        jnp.arange(N.shape[-1]))[0]


@pytest.mark.parametrize("case", ["as_published", "state_carried_in_bf16",
                                  "inverse_of_bf16_operands"])
def test_a_bf16_state_or_inverse_is_told_from_the_kernels(case, monkeypatch):
    """The kernels keep the carried state float32 and give the inverse
    float32 operands at full precision. The recurrence with its state
    rounded to bfloat16 at every position, and the chunked form with an
    inverse whose products round their operands, are what kernels that did
    either would compute: each lies many times farther from the kernels
    than the float32 recurrence does, by values or by gradients: a hundred
    times and more."""
    # (the inverse's entries are largest where the decay is mildest)
    on = "ragged_mild" if case == "inverse_of_bf16_operands" else (
        "ragged_strong")
    kernel, want = (_sides(on)[s] for s in ("kernel", "sequential"))
    near = max(_apart(g, w) for g, w in zip(kernel, want))
    assert near < 3e-5
    if case == "as_published":
        return
    seq, heads, _, decay, _ = CASES[on]
    args = _inputs(seq, heads, DECAYS[decay])
    if case == "state_carried_in_bf16":
        rounded = _values_and_gradients(_recurrence_with_a_bf16_state)(*args)
    else:
        monkeypatch.setattr(M, "unit_lower_inverse",
                            _inverse_of_bf16_operands)
        rounded = _values_and_gradients(
            lambda *a: M.kda_chunked(*a, CHUNK, jnp.float32))(*args)
    far = max(_apart(g, r) for g, r in zip(kernel, rounded))
    assert far > 100 * near and far > 3e-4, (case, near, far)


@pytest.mark.parametrize("chunk,heads,d,dv,plan", [
    (64, 32, 128, 128, (16, 2)),    # the cell: two steps of eight packs
    (64, 2, 128, 128, (2, 2)),      # fewer heads than a step: all of them
    (64, 4, 256, 256, (4, 2)),
    (32, 4, 128, 256, (4, 4)),      # four heads fill the lanes
    (128, 3, 128, 128, (3, 1)),     # a chunk of a whole lane tile
    (16, 8, 128, 128, (8, 8)),      # one sub-block a chunk
    (64, 3, 128, 128, None),        # heads that fill no pack
    (16, 2, 128, 128, None),
    (64, 24, 128, 128, None),       # heads that fill no step
    (8, 2, 8, 8, None),             # the tests' tiny model
    (64, 2, 64, 128, None),         # keys under a lane tile
    (64, 2, 128, 192, None),        # values off the lane tiling
    (48, 2, 128, 128, None),        # three sub-blocks: no power of two
    (24, 2, 128, 128, None),        # a chunk off the sub-blocks
    (256, 2, 128, 128, None),       # a chunk past one lane tile
    (64, 16, 512, 512, None),       # a state past the VMEM set
])
def test_the_tile_plan_is_a_function_of_shapes(chunk, heads, d, dv, plan):
    assert kda.tile_plan(chunk, heads, d, dv) == plan
