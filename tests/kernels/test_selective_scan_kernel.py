"""The Pallas kernels of Mamba-1's selective scan
(``ops/pallas/selective_scan.py``) in interpret mode on the CPU, at the
state the benchmark's cell runs (16) with few channels: against
``modules.selective_scan``'s ``jax.numpy`` form AND against the plain
reference's recurrence one position at a time, values and the gradients to
all five inputs under a random cotangent, over a sequence the chunk does not
divide, more than one channel tile, more than one batch row and ``u`` in
bfloat16. Then the controls that tell a state or a decay kept in bfloat16
from float32, pointed at the kernels; and that which path runs follows from
shapes and devices alone."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.ops.pallas import selective_scan as ss

pytestmark = pytest.mark.kernels

NAMES = ("y", "du", "ddt", "dA", "dB", "dC")
# batch rows, positions, channels, state, u's dtype
CASES = {
    # three chunks, the last one padded; three channel tiles of one lane
    # tile; two batch rows
    "ragged_three_tiles_two_rows": (2, 300, 384, 16, "float32"),
    # the cell's channel tile (four lane tiles, swept in the kernels'
    # groups) and its state, two chunks, u as the block hands it
    "cell_tile_u_bfloat16": (1, 256, 512, 16, "bfloat16"),
    # a sequence shorter than a chunk and padded to it, a state of one
    # sublane tile, two channel tiles of two lane tiles
    "short_state_of_eight": (2, 37, 512, 8, "float32"),
}
# relative RMS distance allowed: the sides are float32 throughout and differ
# in the order of their operations alone; ``du`` of a bfloat16 ``u`` is
# rounded to bfloat16 on every side (2 ** -9 at most a value)
LIMIT, LIMIT_ROUNDED = 2e-5, 3e-3


def _family():
    return reference.load_family("phi4flash")


def _inputs(case):
    B, S, C, N, dtype = CASES[case]
    k = jax.random.split(jax.random.key(len(case)), 6)
    return (jax.random.normal(k[0], (B, S, C)).astype(dtype),
            # dt as the model starts it: softplus of a bias near -3
            jax.nn.softplus(jax.random.normal(k[1], (B, S, C)) - 3),
            # A as the model starts it, 1 .. N a channel, a channel's own
            # scale beside
            -jnp.broadcast_to(jnp.arange(1.0, N + 1), (C, N))
            * jax.random.uniform(k[2], (C, 1), minval=0.5, maxval=1.5),
            jax.random.normal(k[3], (B, S, N)),
            jax.random.normal(k[4], (B, S, N)),
            jax.random.normal(k[5], (B, S, C)))


def _value_and_grads(scan, args, cotangent):
    y, vjp = jax.vjp(scan, *args)
    return (y,) + vjp(cotangent)


@functools.lru_cache(maxsize=None)
def _sides(case):
    """(kernels, chunked in jax.numpy, sequential) on one set of inputs,
    each as (y, du, ddt, dA, dB, dC) in float32."""
    *args, cotangent = _inputs(case)
    args = tuple(args)
    kernel = functools.partial(ss.selective_scan, interpret=True)
    as_f32 = lambda side: tuple(np.asarray(t, np.float32) for t in side)
    run = lambda scan: as_f32(jax.jit(
        lambda a, ct: _value_and_grads(scan, a, ct))(args, cotangent))
    return {"kernel": run(kernel), "chunked": run(M.selective_scan),
            "sequential": run(lambda u, *rest: _family().selective_scan(
                u.astype(jnp.float32), *rest))}


def _apart(a, b):
    return float(np.sqrt(np.mean(np.square(a - b)))
                 / np.sqrt(np.mean(np.square(b))))


@pytest.mark.parametrize("quantity", NAMES)
@pytest.mark.parametrize("against", ["chunked", "sequential"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_scan_is_the_chunked_and_the_sequential_one(case, against,
                                                           quantity):
    sides = _sides(case)
    at = NAMES.index(quantity)
    got, want = sides["kernel"][at], sides[against][at]
    assert got.shape == want.shape and np.isfinite(got).all()
    assert np.abs(want).max() > 0
    rounded = quantity == "du" and CASES[case][4] == "bfloat16"
    limit = LIMIT_ROUNDED if rounded else LIMIT
    assert _apart(got, want) < limit, (_apart(got, want), limit)


def _rounding_scan(round_state=False, round_decay=False):
    """The reference's recurrence with its carried state or its decay
    rounded to bfloat16 at every position: what kernels that kept either in
    bfloat16 would compute."""
    bf16 = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)

    def scan(u, dt, A, Bm, Cm):
        def step(state, at):
            u_t, dt_t, b_t, c_t = at
            decay = jnp.exp(dt_t[..., None] * A)
            state = ((bf16(decay) if round_decay else decay) * state
                     + (dt_t * u_t)[..., None] * b_t[:, None, :])
            if round_state:
                state = bf16(state)
            return state, jnp.einsum("bcn,bn->bc", state, c_t)

        _, y = jax.lax.scan(step, jnp.zeros(u.shape[:1] + A.shape, u.dtype),
                            tuple(jnp.moveaxis(t, 1, 0)
                                  for t in (u, dt, Bm, Cm)))
        return jnp.moveaxis(y, 0, 1)
    return scan


@pytest.mark.parametrize("case", ["as_published", "state_carried_in_bf16",
                                  "decay_in_bf16"])
def test_a_bf16_state_or_decay_is_told_from_the_kernels(case):
    """The recurrence with its state or its decay in bfloat16 lies many
    times farther from the kernels than the float32 recurrence does, by
    values or by gradients: the test's tolerance tells them apart."""
    shapes = "ragged_three_tiles_two_rows"
    kernel, want = _sides(shapes)["kernel"], _sides(shapes)["sequential"]
    near = max(_apart(g, w) for g, w in zip(kernel, want))
    assert near < LIMIT
    if case == "as_published":
        return
    *args, cotangent = _inputs(shapes)
    rounded = jax.jit(lambda a, ct: _value_and_grads(_rounding_scan(
        round_state=case == "state_carried_in_bf16",
        round_decay=case == "decay_in_bf16"), a, ct))(tuple(args), cotangent)
    far = max(_apart(g, np.asarray(r)) for g, r in zip(kernel, rounded))
    assert far > 10 * LIMIT and far > 10 * near, (case, near, far)


@pytest.mark.parametrize("channels,state,seq,plan", [
    (5120, 16, 8192, 512),      # the cell
    (384, 16, 300, 128),        # three lane tiles
    (768, 16, 64, 256),
    (512, 32, 64, 256),         # a wider state: a narrower tile
    (512, 64, 64, 128),
    (512, 128, 64, None),       # a state whose chunk fits no VMEM
    (128, 8, 8, 128),
    (64, 16, 21, None),         # the tests' tiny model: under a lane tile
    (5120, 12, 8192, None),     # a state off the sublane tiling
    (200, 16, 8192, None),      # channels off the lane tiling
    (512, 16, 5, None),         # a sequence under a sublane tile
])
def test_the_tile_plan_is_a_function_of_shapes(channels, state, seq, plan):
    assert ss.tile_plan(channels, state, seq) == plan


def test_shapes_that_fit_no_tile_take_the_jax_numpy_form(cpu_devices):
    """64 channels with the kernels handed in: they answer None, alone and
    on a mesh, and the block computes the plain form's bits."""
    from jax.sharding import Mesh

    from hetu_galvatron_tpu.core.args_schema import ModelArgs

    k = jax.random.split(jax.random.key(1), 5)
    args = (jax.random.normal(k[0], (2, 21, 64)),
            jax.nn.softplus(jax.random.normal(k[1], (2, 21, 64))),
            -jnp.exp(jax.random.normal(k[2], (64, 16))),
            jax.random.normal(k[3], (2, 21, 16)),
            jax.random.normal(k[4], (2, 21, 16)))
    assert ss.selective_scan(*args, interpret=True) is None
    on_mesh = ss.make_selective_scan(
        Mesh(np.array(cpu_devices[:2]), ("dp",)), dp_axes=("dp",),
        interpret=True)
    assert on_mesh(*args) is None
    cfg = ModelArgs(hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2, vocab_size=64, seq_length=21,
                    max_position_embeddings=64,
                    make_vocab_size_divisible_by=1)
    assert cfg.mamba1_d_inner == 64
    params, _ = M.init_mamba1(jax.random.key(2), cfg)
    x = jax.random.normal(jax.random.key(3), (2, 21, 32))
    calls = []

    def answers_none(*a):
        calls.append(a)
        return on_mesh(*a)

    # (one program a side: they trace to one jaxpr)
    got, want = (jax.jit(lambda p, x, fn=fn: M.apply_mamba1(
        p, x, cfg, jnp.float32, scan_fn=fn))(params, x)
        for fn in (answers_none, None))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert len(calls) == 1


@pytest.mark.parametrize("forced", [None, True, False])
def test_who_knows_the_devices_hands_the_kernels_down(forced):
    """``attention_overrides`` gives a mamba1 layer its ``selective``
    kernels where every device of the mesh is a TPU (here: never, unless a
    test says so), and no other layer ever."""
    from hetu_galvatron_tpu.parallel.spmd import attention_overrides
    from hetu_galvatron_tpu.runtime.mesh import LayerSharding, build_mesh

    mesh = build_mesh(2, 1, devices=jax.devices()[:2])
    per_layer = [LayerSharding(dp_axes=("d0",), cp_axes=(), tp_axes=())] * 4
    got = attention_overrides(
        per_layer, mesh, use_flash=False, flash_interpret=True,
        mixers=["mamba1", "full_attention", "mamba", "gmu"], kernels=forced)
    assert {i: list(ops.given()) for i, ops in got.items()} == (
        {0: ["selective", "conv"], 2: ["ssd", "conv", "gated_norm"]}
        if forced else {})
    if forced:
        # and what it hands down is the scan, under shard_map
        *args, _ = _inputs("short_state_of_eight")
        np.testing.assert_allclose(
            np.asarray(got[0].selective(*args)),
            _sides("short_state_of_eight")["chunked"][0],
            rtol=1e-4, atol=1e-4)


def test_forward_and_backward_are_traced_under_the_scans_scope():
    """What lays device time over ``mixer/mamba1/scan`` is the ``op_name``
    of a compiled instruction (``trace_analysis.scope_instructions``). The
    forward is called under the block's scope; the backward rule of a
    ``custom_vjp`` is traced when the gradient is taken, outside every
    scope of the model, and opens the scope itself. Here as the step does
    it: the scope around the forward only, ``jax.grad`` around the whole."""
    from hetu_galvatron_tpu.observability import trace_analysis

    assert ss.SCOPE == trace_analysis.SELECTIVE_SCOPE
    assert ss.SCOPE in trace_analysis.MIXER_SCOPES["mamba1"]
    assert "selective_scan_fwd" in trace_analysis.SCAN_FWD_CALLS

    def block(*a):
        with jax.named_scope("mixer/mamba1"):
            with jax.named_scope("scan"):
                return ss.selective_scan(*a, interpret=True)

    *args, _ = _inputs("short_state_of_eight")
    text = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(block(*a))),
                            argnums=(0, 1, 2, 3, 4))).lower(
                                *args).compile().as_text()
    found = trace_analysis.scope_instructions(text, (ss.SCOPE,))
    listed = set(found["scopes"][ss.SCOPE])
    calls = {"selective_scan_fwd": [0, 0], "selective_scan_bwd": [0, 0]}
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
