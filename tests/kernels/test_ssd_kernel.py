"""The Pallas kernels of the chunked Mamba-2 scan (``ops/pallas/ssd.py``) in
interpret mode on the CPU, at the widths the benchmark's cell runs (head 64,
state 128, chunk 256) with a small batch and head count: against
``modules.ssd_chunked``'s ``jax.numpy`` form AND against the plain
reference's recurrence one position at a time, values and the gradients to
all five inputs, where the chunk divides the sequence and where the last
chunk is padded, with float32 operands (tight) and bfloat16 operands (the
program's). Then the controls that tell a state or a decay kept in bfloat16
from float32, pointed at the kernels; and that which path runs follows from
shapes and devices alone."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.ops.pallas import ssd
from tools.granite_forward_check import rounding_scan

pytestmark = pytest.mark.kernels

BATCH, HEAD_DIM, STATE, CHUNK = 2, 64, 128, 256
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC")
# relative RMS distance allowed, (values, gradients): float32 sides differ
# in operation order alone; with bfloat16 operands each side rounds M, x dt
# and the state where the other does, but the kernels also round the
# cotangent dy for the MXU, which XLA on the CPU does not (the chip's default
# precision does), and both stand 0.014 to 0.023 from the float32 recurrence
LIMITS = {("float32", "chunked"): (1e-5, 1e-4),
          ("float32", "sequential"): (1e-5, 1e-4),
          ("bfloat16", "chunked"): (2e-3, 3e-2),
          ("bfloat16", "sequential"): (2e-2, 5e-2)}


def _family():
    return reference.load_family("granite_hybrid")


def _inputs(seq, dtype, heads=8):
    k = jax.random.split(jax.random.key(0), 5)
    return (jax.random.normal(k[0], (BATCH, seq, heads, HEAD_DIM)
                              ).astype(dtype),
            # dt as the model starts it: softplus of a bias near -3
            jax.nn.softplus(jax.random.normal(k[1], (BATCH, seq, heads)) - 3),
            -jnp.exp(jax.random.normal(k[2], (heads,))),
            jax.random.normal(k[3], (BATCH, seq, STATE)).astype(dtype),
            jax.random.normal(k[4], (BATCH, seq, STATE)).astype(dtype))


def _value_and_grads(scan, args):
    y, vjp = jax.vjp(scan, *args)
    return (y,) + vjp(jnp.cos(y))


def _a_side(scan, args):
    """``_value_and_grads`` as ONE program (a compile a side, not one an
    operation), in float32."""
    side = jax.jit(lambda *a: _value_and_grads(scan, a))(*args)
    return tuple(np.asarray(t, np.float32) for t in side)


@functools.lru_cache(maxsize=None)
def _sides(seq, dtype_name, heads=8):
    """(kernels, chunked in jax.numpy, sequential in float32) on one set of
    inputs, each as (y, dx, ddt, dA, dB, dC) in float32."""
    dtype = jnp.dtype(dtype_name)
    args = _inputs(seq, dtype, heads)
    kernel = lambda *a: M.ssd_chunked(
        *a, CHUNK, dtype, scan_fn=functools.partial(ssd.ssd_scan,
                                                    interpret=True))
    chunked = lambda *a: M.ssd_chunked(*a, CHUNK, dtype)
    return {"kernel": _a_side(kernel, args),
            "chunked": _a_side(chunked, args),
            "sequential": _a_side(
                _family().selective_scan,
                tuple(t.astype(jnp.float32) for t in args))}


def _apart(a, b):
    return float(np.sqrt(np.mean(np.square(a - b)))
                 / np.sqrt(np.mean(np.square(b))))


@pytest.mark.parametrize("quantity", NAMES)
@pytest.mark.parametrize("against", ["chunked", "sequential"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
# two chunks, one step of eight heads; three chunks, the last one padded,
# two steps of sixteen heads and of eight (the plans of ``tile_plan``)
@pytest.mark.parametrize("seq,heads", [(512, 8), (600, 32), (600, 24)])
def test_kernel_scan_is_the_chunked_and_the_sequential_one(
        seq, heads, dtype, against, quantity):
    sides = _sides(seq, dtype, heads)
    at = NAMES.index(quantity)
    got, want = sides["kernel"][at], sides[against][at]
    assert got.shape == want.shape and np.isfinite(got).all()
    limit = LIMITS[dtype, against][min(at, 1)]
    assert _apart(got, want) < limit, (_apart(got, want), limit)


@pytest.mark.parametrize("case", ["as_published", "state_carried_in_bf16",
                                  "decay_in_bf16"])
def test_a_bf16_state_or_decay_is_told_from_the_kernels(case):
    """The tier-1 controls of ``test_granite_hybrid`` pointed at the kernel
    path: the reference's recurrence with its carried state or its decay
    rounded to bfloat16 at every position is what kernels that kept either
    in bfloat16 would compute, and it lies many times farther from the
    kernels than the float32 recurrence does, by values or by gradients."""
    kernel = _sides(600, "float32")["kernel"]
    want = _sides(600, "float32")["sequential"]
    near = max(_apart(g, w) for g, w in zip(kernel, want))
    assert near < 1e-4
    if case == "as_published":
        return
    args = tuple(t.astype(jnp.float32) for t in _inputs(600, jnp.float32))
    rounded = _value_and_grads(rounding_scan(
        round_state=case == "state_carried_in_bf16",
        round_decay=case == "decay_in_bf16"), args)
    far = max(_apart(g, np.asarray(r)) for g, r in zip(kernel, rounded))
    assert far > 10 * near and far > 1e-3, (case, near, far)


@pytest.mark.parametrize("chunk,heads,head_dim,state,plan", [
    (256, 64, 64, 128, 16),    # the cell: two heads a lane tile
    (256, 8, 64, 128, 8),      # fewer heads than the widest step
    (256, 8, 128, 128, 8),     # a head a whole lane tile
    (128, 16, 32, 256, 16),
    (256, 16, 256, 128, None),      # eight heads span too many lanes
    (8, 8, 8, 16, None),            # the tests' tiny model
    (256, 64, 64, 96, None),        # a state off the lane tiling
    (192, 64, 64, 128, None),       # a chunk off the lane tiling
    (256, 6, 64, 128, None),        # heads that fill no grid step
    (256, 64, 48, 128, None),       # a head no fraction of a lane tile
])
def test_the_tile_plan_is_a_function_of_shapes(chunk, heads, head_dim, state,
                                               plan):
    assert ssd.tile_plan(chunk, heads, head_dim, state) == plan


# ---------------------------------------------------------------------------
# B and C in several groups (Nemotron-H: head j reads group j // (H / G))
# ---------------------------------------------------------------------------

GROUPED_CHUNK = 128     # Nemotron-H's
# as LIMITS; with bfloat16 operands the gradient to A, one number a head
# summed over every position, stands 0.053 from the float32 recurrence's at
# forty-eight heads (the chunked jax.numpy form stands as far)
GROUPED_LIMITS = {**LIMITS, ("bfloat16", "sequential"): (2e-2, 7e-2)}


def _grouped_inputs(seq, dtype, heads, groups):
    k = jax.random.split(jax.random.key(3), 5)
    return (jax.random.normal(k[0], (BATCH, seq, heads, HEAD_DIM)
                              ).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (BATCH, seq, heads)) - 3),
            -jnp.exp(jax.random.normal(k[2], (heads,))),
            jax.random.normal(k[3], (BATCH, seq, groups * STATE)
                              ).astype(dtype),
            jax.random.normal(k[4], (BATCH, seq, groups * STATE)
                              ).astype(dtype))


@functools.lru_cache(maxsize=None)
def _grouped_sides(seq, dtype_name, heads, groups):
    """``_sides`` with ``groups`` groups of B and C: the kernels (a grid
    step's heads of one group, its B and C picked by the block index), the
    ``jax.numpy`` form (groups as further batch rows) and the recurrence of
    ``benchmark/reference/nemotron_h.py``, one position at a time."""
    dtype = jnp.dtype(dtype_name)
    args = _grouped_inputs(seq, dtype, heads, groups)
    kernel = lambda *a: M.ssd_chunked(
        *a, GROUPED_CHUNK, dtype, groups=groups,
        scan_fn=functools.partial(ssd.ssd_scan, interpret=True))
    chunked = lambda *a: M.ssd_chunked(*a, GROUPED_CHUNK, dtype,
                                       groups=groups)
    a_group = lambda t: t.reshape(t.shape[:2] + (groups, STATE))
    sequential = lambda x, dt, A, Bm, Cm: reference.load_family(
        "nemotron_h").selective_scan(x, dt, A, a_group(Bm), a_group(Cm))
    return {"kernel": _a_side(kernel, args),
            "chunked": _a_side(chunked, args),
            "sequential": _a_side(
                sequential, tuple(t.astype(jnp.float32) for t in args))}


@pytest.mark.parametrize("quantity", NAMES)
@pytest.mark.parametrize("against", ["chunked", "sequential"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
# two groups of eight heads (a grid step a group, as the cell's eight
# groups of eight); two groups of twenty-four (three steps of eight a
# group: their parts of dB and dC are summed outside), the last chunk padded
@pytest.mark.parametrize("seq,heads,groups", [(256, 16, 2), (300, 48, 2)])
def test_grouped_kernel_scan_is_the_chunked_and_the_sequential_one(
        seq, heads, groups, dtype, against, quantity):
    sides = _grouped_sides(seq, dtype, heads, groups)
    at = NAMES.index(quantity)
    got, want = sides["kernel"][at], sides[against][at]
    assert got.shape == want.shape and np.isfinite(got).all()
    limit = GROUPED_LIMITS[dtype, against][min(at, 1)]
    assert _apart(got, want) < limit, (_apart(got, want), limit)


@pytest.mark.parametrize("form", ["kernel", "chunked"])
def test_a_group_is_the_one_group_scan_of_its_heads(form):
    """Several groups are the one-group scan of each group's heads and
    columns, bit for bit, values and gradients: the code one group runs is
    the code every group runs. (The ``jax.numpy`` form takes the groups as
    further batch rows, so its gradient to ``A``, one number a head summed
    over the rows, adds the same terms up in another order.)"""
    heads, groups = 16, 2
    args = _grouped_inputs(256, jnp.float32, heads, groups)
    scan_fn = (functools.partial(ssd.ssd_scan, interpret=True)
               if form == "kernel" else None)
    scan = lambda *a, **kw: M.ssd_chunked(  # noqa: E731
        *a, GROUPED_CHUNK, jnp.float32, scan_fn=scan_fn, **kw)
    whole = _value_and_grads(lambda *a: scan(*a, groups=groups), args)
    per = heads // groups
    for g in range(groups):
        hs, cols = slice(g * per, (g + 1) * per), slice(g * STATE,
                                                        (g + 1) * STATE)
        x, dt, A, Bm, Cm = args
        mine = (x[:, :, hs], dt[:, :, hs], A[hs], Bm[..., cols],
                Cm[..., cols])
        y, vjp = jax.vjp(scan, *mine)
        alone = (y,) + vjp(jnp.cos(whole[0])[:, :, hs])
        for name, got, want in zip(
                NAMES, alone, (whole[0][:, :, hs], whole[1][:, :, hs],
                               whole[2][:, :, hs], whole[3][hs],
                               whole[4][..., cols], whole[5][..., cols])):
            if (form, name) == ("chunked", "dA"):
                np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                           rtol=1e-5)
                continue
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=name)


@pytest.mark.parametrize("chunk,heads,head_dim,state,groups,plan", [
    (128, 64, 64, 128, 8, 8),     # the cell: a grid step a group
    (128, 64, 64, 128, 4, 16),    # sixteen heads a group: the widest step
    (128, 48, 64, 128, 2, 8),     # twenty-four a group: three steps of 8
    (128, 64, 64, 128, 16, None),   # four heads a group fill no step
    (128, 64, 64, 128, 3, None),    # groups that do not divide the heads
])
def test_the_tile_plan_keeps_a_step_inside_a_group(chunk, heads, head_dim,
                                                   state, groups, plan):
    assert ssd.tile_plan(chunk, heads, head_dim, state, groups) == plan


def test_shapes_that_fit_no_tile_take_the_jax_numpy_form():
    """Chunk 8 with the kernels handed in: they are not called, nothing is
    raised, and the result is the plain one's bits."""
    def never(*a, **kw):
        raise AssertionError("the kernels were called")
    k = jax.random.split(jax.random.key(1), 5)
    args = (jax.random.normal(k[0], (2, 21, 4, 8)),
            jax.nn.softplus(jax.random.normal(k[1], (2, 21, 4))),
            -jnp.exp(jax.random.normal(k[2], (4,))),
            jax.random.normal(k[3], (2, 21, 16)),
            jax.random.normal(k[4], (2, 21, 16)))
    # (one program a side: they trace to one jaxpr)
    got, want = (jax.jit(lambda *a, fn=fn: M.ssd_chunked(
        *a, 8, jnp.float32, scan_fn=fn))(*args) for fn in (never, None))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="fit no tile"):
        ssd.ssd_scan(*args, 8, interpret=True)


@pytest.mark.parametrize("forced", [None, True, False])
def test_who_knows_the_devices_hands_the_kernels_down(forced):
    """``attention_overrides`` gives a mamba layer its ``ssd`` kernels where
    every device of the mesh is a TPU (here: never, unless a test says so),
    and no other layer ever."""
    from hetu_galvatron_tpu.parallel.spmd import attention_overrides
    from hetu_galvatron_tpu.runtime.mesh import LayerSharding, build_mesh

    mesh = build_mesh(2, 1, devices=jax.devices()[:2])
    per_layer = [LayerSharding(dp_axes=("d0",), cp_axes=(), tp_axes=())] * 3
    got = attention_overrides(
        per_layer, mesh, use_flash=False, flash_interpret=True,
        mixers=["mamba", "full_attention", "conv"], kernels=forced)
    assert {i: list(ops.given()) for i, ops in got.items()} == (
        {0: ["ssd", "conv", "gated_norm"], 2: ["conv"]} if forced else {})
    if forced:
        # and what it hands down is the scan, under shard_map
        args = _inputs(256, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(got[0].ssd(*args, CHUNK)),
            np.asarray(M.ssd_chunked(*args, CHUNK, jnp.float32)),
            rtol=1e-4, atol=1e-4)


def test_forward_and_backward_are_traced_under_the_scans_scope():
    """What lays device time over ``mixer/mamba/ssd`` is the ``op_name`` of
    a compiled instruction (``trace_analysis.scope_instructions``). The
    forward is called under the block's scope; the backward rule of a
    ``custom_vjp`` is traced when the gradient is taken, outside every
    scope of the model, and opens the scope itself (this JAX also carries
    the forward's name stack into the rule; the kernels do not lean on
    it). Here as the step does it: the scope around the forward only,
    ``jax.grad`` around the whole."""
    from hetu_galvatron_tpu.observability import trace_analysis

    assert ssd.SCOPE == trace_analysis.SSD_SCOPE
    assert ssd.SCOPE in trace_analysis.MIXER_SCOPES["mamba"]

    def block(*a):
        with jax.named_scope("mixer/mamba"):
            with jax.named_scope("ssd"):
                return ssd.ssd_scan(*a, CHUNK, interpret=True)

    args = _inputs(CHUNK, jnp.float32)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(block(*a))),
                            argnums=(0, 1, 2, 3, 4))).lower(
                                *args).compile().as_text()
    found = trace_analysis.scope_instructions(text, (ssd.SCOPE,))
    listed = set(found["scopes"][ssd.SCOPE])
    calls = {"ssd_scan_fwd": [0, 0], "ssd_scan_bwd": [0, 0]}
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
