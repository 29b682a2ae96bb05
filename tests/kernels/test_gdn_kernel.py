"""The Pallas kernels of the chunked gated delta rule with a decay a head
(``ops/pallas/gdn.py``) in interpret mode on the CPU, at the widths the
benchmark's cell runs (keys of 96 under values of 192, ``beta`` up to 2,
chunk 64) with two chunks and a few heads: against
``modules.gated_delta_chunked``'s ``jax.numpy`` form AND against the plain
reference's recurrence one position at a time, values and the gradients to
all five inputs, one and three packs of heads (a head count that is no
multiple of 16), one and two rows, four heads a pack, decays from the mildest the
initialisation draws to five times past the strongest, with float32
operands (tight) and bfloat16 operands (the program's). Then the controls
that tell a state carried in bfloat16 and an inverse of bfloat16 operands
from float32, pointed at the kernels; and that which path runs follows from
shapes and devices alone."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.ops.pallas import gdn

pytestmark = pytest.mark.kernels

DK, DV, CHUNK = 96, 192, 64
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
# the log decay a token and head, as ``test_kda_kernel``: the mildest and
# the strongest a fresh block draws, and five times past the strongest
DECAYS = {"mildest_init": 1e-3, "strongest_init": 1.6, "past_init": 8.0}
# (rows, positions, heads, operands' dtype, decay, chunk): two chunks and
# one pack of two heads, at every decay (two rows at the mildest); three
# packs a step; four heads a pack; the program's dtype
CASES = {
    "mild": (2, 128, 2, "float32", "mildest_init", 64),
    "strong": (1, 128, 2, "float32", "strongest_init", 64),
    "past": (1, 128, 2, "float32", "past_init", 64),
    "three_packs_a_step": (1, 128, 6, "float32", "strongest_init", 64),
    "four_heads_a_pack": (1, 96, 4, "float32", "strongest_init", 32),
    "bf16": (1, 128, 2, "bfloat16", "mildest_init", 64),
    "bf16_three_packs": (1, 128, 6, "bfloat16", "strongest_init", 64),
}
# distance allowed as a share of the other side's largest entry, (values,
# gradients): float32 sides differ in operation order alone; with bfloat16
# operands the kernels and the jax.numpy form round the same operands, and
# both stand a few thousandths from the float32 recurrence
LIMITS = {("float32", "chunked"): (1e-5, 3e-5),
          ("float32", "sequential"): (3e-5, 3e-5),
          ("bfloat16", "chunked"): (1e-2, 2e-2),
          ("bfloat16", "sequential"): (2e-2, 5e-2)}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _recurrence():
    return reference.load_family("olmo_hybrid").delta_rule


def _inputs(rows, seq, heads, strength, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (rows, seq, heads, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (rows, seq, heads, DK)))
    v = jax.random.normal(ks[2], (rows, seq, heads, DV))
    # every head decays at its own rate, up to ``strength`` a token
    g = -strength * jax.random.uniform(ks[3], (rows, seq, heads),
                                       minval=0.05, maxval=1.0)
    # (0, 2): a delta step that overshoots in half of the positions
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], g.shape))
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


def _kernels(dtype, chunk=CHUNK):
    return lambda q, k, v, g, beta: gdn.gdn_scan(
        q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta, chunk,
        interpret=True)


@functools.lru_cache(maxsize=None)
def _side(name, dtype, chunk):
    """A side's program: the cases that differ in their data alone (the
    decays) are one compile of it."""
    dtype = jnp.dtype(dtype)
    return _values_and_gradients({
        "kernel": _kernels(dtype, chunk),
        "chunked": lambda *a: M.gated_delta_chunked(*a, chunk, dtype),
        "sequential": _recurrence()}[name])


@functools.lru_cache(maxsize=None)
def _sides(case):
    """(kernels, chunked in jax.numpy, sequential in float32) on one set of
    inputs, each as (o, dq, dk, dv, dg, dbeta) in float32."""
    rows, seq, heads, dtype, decay, chunk = CASES[case]
    args = _inputs(rows, seq, heads, DECAYS[decay])
    assert gdn.tile_plan(chunk, heads, DK, DV) is not None
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
    limit = LIMITS[CASES[case][3], against][min(at, 1)]
    assert _apart(got, want) < limit, (_apart(got, want), limit)


def _recurrence_with_a_bf16_state(q, k, v, g, beta):
    """The recurrence one position at a time with the carried state rounded
    to bfloat16 at every position: what kernels that kept it so compute."""
    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None, None] * state
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
    than the float32 recurrence does, by values or by gradients."""
    # (the inverse's entries are largest where the decay is mildest)
    on = "mild" if case == "inverse_of_bf16_operands" else "strong"
    kernel, want = (_sides(on)[s] for s in ("kernel", "sequential"))
    near = max(_apart(g, w) for g, w in zip(kernel, want))
    assert near < 3e-5
    if case == "as_published":
        return
    rows, seq, heads, _, decay, _ = CASES[on]
    args = _inputs(rows, seq, heads, DECAYS[decay])
    if case == "state_carried_in_bf16":
        rounded = _values_and_gradients(_recurrence_with_a_bf16_state)(*args)
    else:
        monkeypatch.setattr(M, "unit_lower_inverse",
                            _inverse_of_bf16_operands)
        rounded = _values_and_gradients(
            lambda *a: M.gated_delta_chunked(*a, CHUNK, jnp.float32))(*args)
    far = max(_apart(g, r) for g, r in zip(kernel, rounded))
    assert far > 100 * near and far > 3e-4, (case, near, far)


@pytest.mark.parametrize("chunk,heads,dk,dv,plan", [
    (64, 30, 96, 192, (30, 2)),     # the cell: every head a step, 15 packs
    (64, 2, 96, 192, (2, 2)),
    (64, 6, 96, 192, (6, 2)),       # no multiple of 16
    (64, 4, 128, 128, (4, 2)),      # whole lane tiles, as kda's
    (32, 4, 32, 48, (4, 4)),        # four heads fill the lanes
    (128, 3, 128, 256, (3, 1)),     # a chunk of a whole lane tile
    (16, 8, 64, 64, (8, 8)),        # one sub-block a chunk
    (64, 64, 128, 256, (32, 2)),    # two steps of heads
    (64, 3, 96, 192, None),         # heads that fill no pack
    (32, 3, 24, 48, None),          # the tests' tiny model
    (64, 2, 24, 48, None),          # keys off a two-byte sublane tile
    (64, 2, 96, 40, None),          # values off it
    (48, 2, 96, 192, None),         # three sub-blocks: no power of two
    (24, 2, 96, 192, None),         # a chunk off the sub-blocks
    (256, 2, 96, 192, None),        # a chunk past one lane tile
    (64, 2, 1024, 2048, None),      # a pack's states past the VMEM set
])
def test_the_tile_plan_is_a_function_of_shapes(chunk, heads, dk, dv, plan):
    assert gdn.tile_plan(chunk, heads, dk, dv) == plan


def _block(heads, dk, dv, chunk, seq):
    """A linear_attention mixer's configuration and parameters."""
    cfg = ModelArgs(
        hidden_size=64, num_hidden_layers=1, num_attention_heads=2,
        vocab_size=64, max_position_embeddings=seq, seq_length=seq,
        hidden_act="swiglu", normalization="rmsnorm",
        add_bias_linear=False, make_vocab_size_divisible_by=1,
        linear_num_key_heads=heads, linear_num_value_heads=heads,
        linear_key_head_dim=dk, linear_value_head_dim=dv,
        linear_chunk_size=chunk, linear_allow_neg_eigval=True)
    return cfg, M.init_gated_delta(jax.random.key(2), cfg)[0]


@pytest.mark.parametrize("heads,dk,dv,chunk,seq,taken", [
    (2, 32, 48, 64, 128, True),
    (3, 24, 48, 32, 64, False),     # the tests' tiny model: no tile
    (2, 32, 48, 64, 100, False),    # a length the chunk does not divide
])
def test_shapes_that_fit_no_tile_take_the_jax_numpy_form(
        heads, dk, dv, chunk, seq, taken):
    """A block handed the kernels calls them where ``tile_plan`` fits and
    the chunk divides the sequence; anywhere else they are not called,
    nothing is raised, and the result is the plain one's bits."""
    cfg, p = _block(heads, dk, dv, chunk, seq)
    x = jax.random.normal(jax.random.key(1), (2, seq, cfg.hidden_size))
    called = []

    def kernels(*a):
        called.append(a[0].shape)
        return gdn.gdn_scan(*a, interpret=True)

    # (one program a side; where the kernels are not called the two sides
    # trace to one jaxpr)
    got, want = (jax.jit(lambda p, x, fn=fn: M.apply_gated_delta(
        p, x, cfg, jnp.float32, gdn_fn=fn))(p, x) for fn in (kernels, None))
    assert bool(called) == taken
    if taken:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        q, k, _, g, beta = _inputs(1, seq, heads, 1.0)
        with pytest.raises(ValueError, match="fit no tile"):
            gdn.gdn_scan(q[..., :dk], k[..., :dk],
                         jnp.zeros((1, seq, heads, dv)), g, beta, chunk,
                         interpret=True)


@pytest.mark.parametrize("forced", [None, True, False])
def test_who_knows_the_devices_hands_the_kernels_down(forced):
    """``attention_overrides`` gives a linear_attention layer its ``gdn``
    kernels where every device of the mesh is a TPU (here: never, unless a
    test says so), and no other layer ever."""
    from hetu_galvatron_tpu.parallel.spmd import attention_overrides
    from hetu_galvatron_tpu.runtime.mesh import LayerSharding, build_mesh

    mesh = build_mesh(2, 1, devices=jax.devices()[:2])
    per_layer = [LayerSharding(dp_axes=("d0",), cp_axes=(), tp_axes=())] * 4
    got = attention_overrides(
        per_layer, mesh, use_flash=False, flash_interpret=True,
        mixers=["full_attention", "linear_attention", "kda",
                "linear_attention"],
        kernels=forced)
    assert {i: list(ops.given()) for i, ops in got.items()} == (
        {1: ["gdn", "conv"], 2: ["kda", "conv"], 3: ["gdn", "conv"]}
        if forced else {})
    if forced:
        # and what it hands down is the scan, under shard_map over dp
        args = _inputs(2, CHUNK, 2, DECAYS["strongest_init"])
        np.testing.assert_allclose(
            np.asarray(got[1].gdn(*args, CHUNK)),
            np.asarray(M.gated_delta_chunked(*args, CHUNK, jnp.float32)),
            rtol=1e-4, atol=1e-5)


def test_forward_and_backward_are_traced_under_the_scans_scope():
    """What lays device time over ``mixer/gdn/scan`` is the ``op_name`` of a
    compiled instruction (``trace_analysis.scope_instructions``). The
    forward is called under the block's scope; the backward rule of a
    ``custom_vjp`` is traced when the gradient is taken, outside every
    scope of the model, and opens the scope itself. Here as the step does
    it: the scope around the forward only, ``jax.grad`` around the whole."""
    from hetu_galvatron_tpu.observability import trace_analysis

    assert gdn.SCOPE == trace_analysis.GDN_SCAN_SCOPE
    assert gdn.SCOPE in trace_analysis.MIXER_SCOPES["gdn"]
    assert "gdn_scan_fwd" in trace_analysis.SCAN_FWD_CALLS

    def block(*a):
        with jax.named_scope("mixer/gdn"):
            with jax.named_scope("scan"):
                return gdn.gdn_scan(*a, CHUNK, interpret=True)

    args = _inputs(1, CHUNK, 2, DECAYS["strongest_init"])
    text = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(block(*a))),
                            argnums=(0, 1, 2, 3, 4))).lower(
                                *args).compile().as_text()
    found = trace_analysis.scope_instructions(text, (gdn.SCOPE,))
    listed = set(found["scopes"][gdn.SCOPE])
    calls = {"gdn_scan_fwd": [0, 0], "gdn_scan_bwd": [0, 0]}
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


def test_blocks_of_one_shape_share_one_trace_of_the_kernels(monkeypatch):
    """The step program traces every kind of block once more to count what
    it holds (``parallel/kept.py``), and the cell has three such blocks: a
    second scan of the same shapes, in another trace of the same kind, runs
    no kernel's Python again, forward or backward."""
    traced = {"fwd": 0, "bwd": 0}

    def counting(name, kernel):
        def body(*refs, **statics):
            traced[name] += 1
            return kernel(*refs, **statics)
        return body

    monkeypatch.setattr(gdn, "_fwd_kernel", counting("fwd", gdn._fwd_kernel))
    monkeypatch.setattr(gdn, "_bwd_kernel", counting("bwd", gdn._bwd_kernel))
    gdn._scan_call.clear_cache()
    gdn._scan_bwd_call.clear_cache()
    args = _inputs(1, CHUNK, 2, DECAYS["strongest_init"], seed=3)
    scan = lambda *a: gdn.gdn_scan(*a, CHUNK, interpret=True)
    trace = lambda block, scale: jax.make_jaxpr(jax.grad(
        lambda *a: scale * jnp.sum(block(*a)), argnums=(0, 1, 2, 3, 4)))(*args)
    try:
        trace(scan, 1.0)
        # (the forward that keeps its states, and the backward)
        assert traced == {"fwd": 1, "bwd": 1}
        trace(scan, 2.0)
        assert traced == {"fwd": 1, "bwd": 1}
        # a recomputed block's: the primal call, which keeps no states, and
        # the keeping forward as ``jax.checkpoint`` traces it, once each
        trace(jax.checkpoint(scan), 1.0)
        assert traced == {"fwd": 3, "bwd": 1}
        trace(jax.checkpoint(scan), 2.0)
        trace(scan, 3.0)
        assert traced == {"fwd": 3, "bwd": 1}
    finally:
        # (what was traced through the counting bodies is not left behind)
        gdn._scan_call.clear_cache()
        gdn._scan_bwd_call.clear_cache()
