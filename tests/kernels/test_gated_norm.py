"""Mamba-2's skip and gated norm as one pass (``modules.mamba_gated_norm``,
``ops/pallas/gated_norm.py``): the kernels in interpret mode on the CPU,
and the ``jax.numpy`` fallback, against the float32 ``jax.numpy`` form of
``apply_mamba2`` as it stood before the kernels (written out below, a
group a minor dimension of its own): the value and the gradients to the
scan's result, the gate, the convolution's output, ``D`` and the scale; at
one, two and eight groups of 128, 512 and 4096 channels, a row count no
tile divides, float32 operands (tight) and bfloat16 operands (the
program's). Then which path runs, from shapes and devices alone, and the
scope its backward is traced under."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.ops.pallas import gated_norm

pytestmark = pytest.mark.kernels

BATCH, HEAD, EPS = 2, 64, 1e-5
SCOPE = "mixer/mamba/gated_norm"
# a tile of 32 rows whatever the lanes: 72 rows are two tiles and a ragged
# third of 8, 64 rows two whole ones
TILE_ROWS = 32
# groups, channels a group, rows; the kernels run in interpret mode where
# a code path differs: one group and several, a ragged tile and none, the
# widest group; the other widths go through the fallback's comparison
KERNEL_SHAPES = {"g1_w128": (1, 128, 72), "g1_w4096": (1, 4096, 40),
                 "g2_w512": (2, 512, 64), "g8_w512": (8, 512, 72)}
FALLBACK_SHAPES = {**KERNEL_SHAPES, "g2_w4096": (2, 4096, 24),
                   "g8_w4096": (8, 4096, 24), "g2_w128": (2, 128, 72),
                   "g8_w128": (8, 128, 64), "g1_w512": (1, 512, 72),
                   "g4_w96": (4, 96, 72)}
NAMES = ("out", "dy", "dx", "dz", "dD", "dscale")
# relative RMS distance allowed: float32 sides differ in the order of a
# group's sum alone; with bfloat16 operands both sides round the result,
# and two cotangents, to eight bits once
LIMITS = {"float32": 2e-6, "bfloat16": 1e-2}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """``TILE_ROWS`` rows a tile, forward and backward, of whatever width
    (4096 channels of float32 operands are 64 KiB a row forward)."""
    monkeypatch.setattr(gated_norm, "tile_plan", functools.partial(
        _plan, gated_norm.tile_plan))


def _plan(real, seq, channels, groups, itemsize=2, backward=False):
    return real(seq, channels, groups, itemsize, backward) and min(
        TILE_ROWS, seq // 16 * 16)


def as_the_parent(y, x, z, D, scale, groups, out_dtype):
    """``apply_mamba2`` between its scan and ``out_proj`` at the parent of
    the PR that brought the kernels, line for line."""
    f32 = jnp.float32
    B, S, di = y.shape
    y = y + jnp.repeat(D, di // D.shape[0]) * x.astype(f32)
    y = y * jax.nn.silu(z.astype(f32))
    if groups > 1:
        y = y.reshape(B, S, groups, di // groups)
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + EPS)
    if groups > 1:
        y = y.reshape(B, S, di)
    return (y * scale).astype(out_dtype)


def _inputs(shape, dtype):
    G, W, S = FALLBACK_SHAPES[shape]
    C = G * W
    k = jax.random.split(jax.random.key(G * W + S), 6)
    wide = lambda key: jax.random.normal(key, (BATCH, S, C))  # noqa: E731
    return ((wide(k[0]), wide(k[1]).astype(dtype), wide(k[2]).astype(dtype),
             jax.random.normal(k[3], (max(C // HEAD, 1),)),
             1.0 + 0.3 * jax.random.normal(k[4], (C,))),
            wide(k[5]).astype(dtype))


def _value_and_grads(fns, args, ct):
    """``NAMES``' quantities of each ``fn(y, x, z, D, scale)`` of ``fns``,
    values and gradients through ONE ``jax.jit`` (a compile, not one an
    op)."""
    def one(fn, *a):
        def loss(*a):
            out = fn(*a)
            return jnp.sum((out * ct).astype(jnp.float32)), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*a)
        return dict(zip(NAMES, (out,) + grads))

    return jax.jit(lambda *a: tuple(one(fn, *a) for fn in fns))(*args)


@functools.lru_cache(maxsize=None)
def _sides(shape, dtype, path):
    G = FALLBACK_SHAPES[shape][0]
    dt = jnp.dtype(dtype)
    args, ct = _inputs(shape, dt)
    kernels = functools.partial(gated_norm.gated_norm, interpret=True)
    sides = (lambda *a: M.mamba_gated_norm(
        *a, G, EPS, dt, kernels if path == "kernels" else None),
        lambda *a: as_the_parent(*a, G, dt))
    if path == "kernels":
        return _value_and_grads(sides, args, ct)
    # the fallback IS the parent's lines: in one program XLA would fold the
    # two sides into one and compare it with itself
    return tuple(_value_and_grads((side,), args, ct)[0] for side in sides)


def _distance(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("quantity", NAMES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(KERNEL_SHAPES))
def test_the_kernels_are_the_parents_jax_numpy_form(shape, dtype, quantity):
    got, want = _sides(shape, dtype, "kernels")
    assert got[quantity].dtype == want[quantity].dtype
    assert got[quantity].shape == want[quantity].shape
    assert _distance(got[quantity], want[quantity]) < LIMITS[dtype], (
        shape, dtype, quantity)


@pytest.mark.parametrize("quantity", NAMES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [s for s in FALLBACK_SHAPES
                                   if s not in KERNEL_SHAPES])
def test_the_fallback_is_the_parents_jax_numpy_form_to_the_bit(
        shape, dtype, quantity):
    got, want = _sides(shape, dtype, "fallback")
    np.testing.assert_array_equal(np.asarray(got[quantity], np.float32),
                                  np.asarray(want[quantity], np.float32))


def test_a_control_tells_one_group_from_several():
    """The comparison sees which channels a mean square runs over."""
    args, _ = _inputs("g8_w128", jnp.float32)
    kernels = functools.partial(gated_norm.gated_norm, interpret=True)
    one = M.mamba_gated_norm(*args, 1, EPS, jnp.float32, kernels)
    assert _distance(one, as_the_parent(*args, 8, jnp.float32)) > 0.05


@pytest.mark.parametrize("seq,channels,groups,itemsize,plans", [
    # the two cells: Nemotron-H's eight groups, Granite's one
    (8192, 4096, 8, 2, (128, 64)),
    (8192, 4096, 1, 2, (128, 64)),
    (8192, 4096, 8, 4, (80, 32)),     # float32 operands: fewer rows
    (100, 1024, 2, 2, (96, 96)),      # a ragged second tile
    (16, 128, 1, 2, (16, 16)),
    (8, 128, 1, 2, (None, None)),     # a sequence under a sub-block
    (8192, 4096, 64, 2, (None, None)),   # a group half a lane tile
    (8192, 768, 4, 2, (None, None)),     # 192 channels a group
    (8192, 1000, 3, 2, (None, None)),    # groups that do not divide
])
def test_the_tile_plan_is_a_function_of_shapes(monkeypatch, seq, channels,
                                               groups, itemsize, plans):
    monkeypatch.undo()      # the module's own sizes
    assert tuple(gated_norm.tile_plan(seq, channels, groups, itemsize, b)
                 for b in (False, True)) == plans


def test_groups_of_no_lane_tile_take_the_jax_numpy_form():
    """Four groups of 96 channels with the kernels handed in: they answer
    None, nothing is raised, and the result is the plain one's bits."""
    args, _ = _inputs("g4_w96", jnp.float32)
    asked = []

    def kernels(*a, **kw):
        asked.append(gated_norm.gated_norm(*a, **kw, interpret=True))
        return asked[-1]
    np.testing.assert_array_equal(
        np.asarray(M.mamba_gated_norm(*args, 4, EPS, jnp.float32, kernels)),
        np.asarray(as_the_parent(*args, 4, jnp.float32)))
    assert asked == [None]


@pytest.mark.parametrize("forced", [None, True, False])
def test_who_knows_the_devices_hands_the_kernels_down(forced):
    """``attention_overrides`` gives a mamba layer its ``gated_norm``
    kernels where every device of the mesh is a TPU (here: never, unless a
    test says so), a layer whose sequence is cut too (the norm is a
    row's), and no other kind of layer ever."""
    from hetu_galvatron_tpu.parallel import spmd
    from hetu_galvatron_tpu.runtime.mesh import LayerSharding, build_mesh

    mesh = build_mesh(2, 1, devices=jax.devices()[:2])
    whole = LayerSharding(dp_axes=("d0",), cp_axes=(), tp_axes=())
    mixers = ["mamba", "full_attention", "kda", "conv", "mamba1"]
    got = spmd.attention_overrides(
        [whole] * 5, mesh, use_flash=False, flash_interpret=True,
        mixers=mixers, kernels=forced)
    assert [i for i, ops in got.items() if ops.gated_norm is not None] == (
        [0] if forced else [])
    if forced:
        # and what it hands down is the norm, under shard_map over dp
        args, ct = _inputs("g2_w512", jnp.float32)
        fn = lambda *a: M.mamba_gated_norm(  # noqa: E731
            *a, 2, EPS, jnp.float32, got[0].gated_norm)
        sides = _value_and_grads(
            (fn, lambda *a: as_the_parent(*a, 2, jnp.float32)), args, ct)
        for name in NAMES:
            assert _distance(sides[0][name], sides[1][name]) < 2e-6, name
        args, _ = _inputs("g4_w96", jnp.float32)
        assert got[0].gated_norm(*args, groups=4, eps=EPS,
                                 out_dtype=jnp.float32, scope="x") is None


def test_forward_and_backward_are_traced_under_the_callers_scope():
    """What lays device time over the norm is the ``op_name`` of a compiled
    instruction. The forward is called under the block's scope; the
    backward rule of a ``custom_vjp`` is traced when the gradient is taken,
    outside every scope of the model, and opens the scope it was told
    itself."""
    from hetu_galvatron_tpu.observability import trace_analysis

    assert trace_analysis.GATED_NORM_SCOPE == SCOPE
    assert SCOPE in trace_analysis.SCOPES

    def block(*a):
        with jax.named_scope("mixer/mamba"):
            with jax.named_scope("gated_norm"):
                return M.mamba_gated_norm(
                    *a, 2, EPS, jnp.float32, functools.partial(
                        gated_norm.gated_norm, interpret=True))

    args, _ = _inputs("g2_w512", jnp.float32)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(block(*a))),
                            argnums=(0, 1, 2, 3, 4))).lower(
                                *args).compile().as_text()
    found = trace_analysis.scope_instructions(text, (SCOPE,))
    listed = set(found["scopes"][SCOPE])
    calls = {"gated_norm_fwd": [0, 0], "gated_norm_bwd": [0, 0]}
    for line in text.splitlines():
        inst = trace_analysis._INSTRUCTION.match(line)
        op = trace_analysis._OP_NAME.search(line)
        if not inst or not op or inst.group(1) not in found["instructions"]:
            continue
        for call, (inside, outside) in calls.items():
            if f"/{call}/" in op.group(1):
                calls[call] = [inside + (inst.group(1) in listed),
                               outside + (inst.group(1) not in listed)]
    # (interpret mode: a call is the instructions it was unrolled into)
    for call, (inside, outside) in calls.items():
        assert inside > 0 and outside == 0, (call, inside, outside)
    assert found["mosaic_calls"] == frozenset()   # none on a CPU
