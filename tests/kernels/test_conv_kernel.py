"""The Pallas kernels of the causal depthwise convolution
(``ops/pallas/conv.py``) in interpret mode on the CPU, as the three blocks
call them (Kimi Delta Attention: 4 taps, SiLU, a head's L2 norm for two
thirds of the channels; Mamba-2: 4 taps, a bias, SiLU; the gated short
convolution: 3 taps between two gates) and bare: against
``modules.causal_depthwise_conv``'s ``jax.numpy`` form AND against the plain
references' convolutions, values and every gradient, over several tiles of
the sequence where the tile divides it and where the last one is ragged,
with float32 operands (tight) and bfloat16 operands (the program's). Then
that a sequence starts from zeros and a tile from the rows before it; the
controls that tell a sum of taps or an L2 norm kept in bfloat16 from
float32, pointed at the kernels; and that which path runs follows from
shapes and devices alone."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.ops.pallas import conv

pytestmark = pytest.mark.kernels

BATCH, HEAD = 2, 128
EPS = M.KDA_L2_EPS
# a tile of 128 rows whatever the lanes, so that 384 positions are three
# tiles and 300 two and a ragged third; a sub-block of rows is half a tile
TILE_ROWS = 128
CASES = {
    # channels, taps, bias, gates, silu, head_norm
    "kda": (768, 4, False, False, True, (HEAD, EPS, (HEAD ** -0.5, 1.0,
                                                      None))),
    "mamba": (384, 4, True, False, True, None),
    "short_conv": (256, 3, False, True, False, None),
    "bare": (128, 3, False, False, False, None),
}
NAMES = ("y", "du", "dtaps", "dbias", "dpre", "dpost")
# relative RMS distance allowed: float32 sides differ in the order of a
# head's sum alone; with bfloat16 operands both sides round the result, and
# the cotangents, to eight bits once
LIMITS = {"float32": 2e-6, "bfloat16": 1e-2}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(conv, "TILE_LANES", (256, 128))
    monkeypatch.setattr(conv, "TILE_BYTES", TILE_ROWS * 256 * 2)
    monkeypatch.setattr(conv, "SUB_BLOCK", (64 * 256, 64 * 256))


def _inputs(case, seq, dtype):
    C, L, bias, gated = CASES[case][:4]
    k = jax.random.split(jax.random.key(0), 5)
    wide = lambda key: jax.random.normal(key, (BATCH, seq, C)).astype(dtype)
    return (wide(k[0]), jax.random.normal(k[1], (C, L)) / L ** 0.5,
            jax.random.normal(k[2], (C,)) if bias else None,
            wide(k[3]) if gated else None, wide(k[4]) if gated else None)


def _kernel(case, out_dtype):
    silu, head_norm = CASES[case][4:]
    return lambda u, taps, bias, pre, post: conv.causal_conv(
        u, taps, bias, pre=pre, post=post, silu=silu, head_norm=head_norm,
        out_dtype=out_dtype, scope="mixer/kda/conv", interpret=True)


def _plain(case, out_dtype):
    silu, head_norm = CASES[case][4:]
    return lambda u, taps, bias, pre, post: M.causal_depthwise_conv(
        u, taps, bias, pre=pre, post=post, silu=silu, head_norm=head_norm,
        out_dtype=out_dtype)


def _reference(case):
    """The plain references' own convolutions with the callers' epilogues
    as the references write them, in float32."""
    C, L, _, gated, silu, head_norm = CASES[case]
    kimi = reference.load_family("kimi_linear")
    lfm2 = reference.load_family("lfm2_moe")

    def fn(u, taps, bias, pre, post):
        f32 = jnp.float32
        if gated:
            # the block whole, its two projections the identity
            w = {"in_proj.weight": jnp.eye(3 * C), "conv.weight":
                 taps[:, None, :], "out_proj.weight": jnp.eye(C)}
            return lfm2.short_conv(jnp.concatenate(
                [pre, post, u], axis=-1).astype(f32), w, "", L)
        c = kimi.causal_conv(u.astype(f32), taps[:, None, :])
        if bias is not None:
            c = c + bias
        if silu:
            c = jax.nn.silu(c)
        if head_norm is not None:
            parts = [t.reshape(t.shape[:2] + (-1, HEAD))
                     for t in jnp.split(c, 3, axis=-1)]
            c = jnp.concatenate(
                [(kimi.unit(parts[0]) * HEAD ** -0.5), kimi.unit(parts[1]),
                 parts[2]], axis=2).reshape(c.shape)
        return c
    return fn


def _value_and_grads(fn, args):
    y, vjp = jax.vjp(fn, *args)
    return (y,) + vjp(jnp.cos(y.astype(jnp.float32)).astype(y.dtype))


@functools.lru_cache(maxsize=None)
def _sides(case, seq, dtype_name):
    dtype = jnp.dtype(dtype_name)
    args = _inputs(case, seq, dtype)
    as_f32 = lambda side: tuple(
        None if t is None else np.asarray(t, np.float32) for t in side)
    with jax.default_matmul_precision("highest"):
        return {"kernel": as_f32(_value_and_grads(_kernel(case, dtype),
                                                  args)),
                "jax_numpy": as_f32(_value_and_grads(_plain(case, dtype),
                                                     args)),
                "reference": as_f32(_value_and_grads(
                    _reference(case), tuple(
                        None if t is None else t.astype(jnp.float32)
                        for t in args)))}


def _apart(a, b):
    return float(np.sqrt(np.mean(np.square(a - b)))
                 / np.sqrt(np.mean(np.square(b))))


@pytest.mark.parametrize("quantity", NAMES)
@pytest.mark.parametrize("against", ["jax_numpy", "reference"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
# three tiles; two and a ragged third
@pytest.mark.parametrize("seq", [384, 300])
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_are_the_jax_numpy_form_and_the_references(
        case, seq, dtype, against, quantity):
    sides = _sides(case, seq, dtype)
    at = NAMES.index(quantity)
    got, want = sides["kernel"][at], sides[against][at]
    if want is None:    # this caller hands no such operand
        assert got is None
        return
    assert got.shape == want.shape and np.isfinite(got).all()
    # (the reference side of a bfloat16 case computed in float32 all along)
    limit = LIMITS[dtype] * (3 if against == "reference" else 1)
    assert _apart(got, want) < limit, (_apart(got, want), limit)


@pytest.mark.parametrize("case", list(CASES))
def test_a_sequence_starts_from_zeros_and_a_tile_from_the_rows_before_it(
        case):
    """The first ``L - 1`` rows of every sequence of a batch see zeros, not
    the sequence before them in memory; the first rows of a later tile see
    the tile before it, not zeros."""
    L = CASES[case][1]
    u, taps, bias, pre, post = _inputs(case, 384, jnp.float32)
    u = u.at[0].multiply(1e3)        # a loud first sequence
    y = _kernel(case, jnp.float32)(u, taps, bias, pre, post)
    alone = lambda t: None if t is None else t[1:]
    second = _kernel(case, jnp.float32)(u[1:], taps, bias, alone(pre),
                                        alone(post))
    np.testing.assert_array_equal(np.asarray(y[1, :L - 1]),
                                  np.asarray(second[0, :L - 1]))
    want = _plain(case, jnp.float32)(u, taps, bias, pre, post)
    for start in (0, TILE_ROWS, 2 * TILE_ROWS):
        np.testing.assert_allclose(
            np.asarray(y[:, start:start + L - 1]),
            np.asarray(want[:, start:start + L - 1]), rtol=2e-5, atol=1e-5)
    # a tile cut off from the rows before it reads otherwise
    rows = slice(TILE_ROWS, 2 * TILE_ROWS)
    cut = _plain(case, jnp.float32)(u[:, rows], taps, bias,
                                    None if pre is None else pre[:, rows],
                                    None if post is None else post[:, rows])
    assert _apart(np.asarray(cut[1, :L - 1]),
                  np.asarray(y[1, TILE_ROWS:TILE_ROWS + L - 1])) > 0.1


def _rounding(case, what):
    """The ``jax.numpy`` form with one float32 quantity kept in bfloat16:
    the running sum of the taps' products, or a head's sum of squares."""
    silu, head_norm = CASES[case][4:]
    bf16, f32 = jnp.bfloat16, jnp.float32
    rnd = lambda t: t.astype(bf16).astype(f32)

    def fn(u, taps, bias, pre, post):
        S, L = u.shape[1], taps.shape[1]
        x = u.astype(f32)
        c = x * taps[:, L - 1]
        for back in range(1, L):
            shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :S]
            c = c + shifted * taps[:, L - 1 - back]
            if what == "taps_summed_in_bf16":
                c = rnd(c)
        if bias is not None:
            c = c + bias
        c = jax.nn.silu(c) if silu else c
        heads = c.reshape(c.shape[:2] + (3, -1, HEAD))
        ss = jnp.sum(jnp.square(heads), axis=-1, keepdims=True)
        if what == "norm_in_bf16":
            ss = jnp.sum(rnd(jnp.square(heads)), axis=-1, keepdims=True,
                         dtype=bf16).astype(f32)
        r = jax.lax.rsqrt(ss + EPS)
        return jnp.stack([heads[:, :, i] if s is None
                          else heads[:, :, i] * (r[:, :, i] * s)
                          for i, s in enumerate(head_norm[2])],
                         axis=2).reshape(c.shape)
    return fn


@pytest.mark.parametrize("what", ["as_written", "taps_summed_in_bf16",
                                  "norm_in_bf16"])
def test_a_bf16_sum_or_norm_is_told_from_the_kernels(what):
    """The program's operands (bfloat16) and a float32 result, so that only
    what happens between them shows: the kernels lie at float32 rounding
    from the ``jax.numpy`` form, and many times farther from a form that
    keeps the taps' running sum or a head's sum of squares in bfloat16, by
    values or by gradients."""
    args = _inputs("kda", 300, jnp.bfloat16)
    kernel = _value_and_grads(_kernel("kda", jnp.float32), args)
    plain = _value_and_grads(_plain("kda", jnp.float32), args)
    f = lambda side: [np.asarray(t, np.float32) for t in side
                      if t is not None]
    near = max(_apart(g, w) for g, w in zip(f(kernel), f(plain)))
    # (du leaves both sides through one rounding to bfloat16)
    assert near < 4e-3
    near_y = _apart(f(kernel)[0], f(plain)[0])
    assert near_y < 2e-6
    if what == "as_written":
        return
    rounded = _value_and_grads(_rounding("kda", what), args)
    far_y = _apart(f(kernel)[0], f(rounded)[0])
    far_taps = _apart(f(kernel)[2], f(rounded)[2])
    # (read: values 2.8e-3 and 3.8e-4, the taps' gradient 1.1e-3 and 7e-4)
    assert far_y > 1000 * near_y and far_y > 2e-4, (what, near_y, far_y)
    assert far_taps > 50 * near and far_taps > 2e-4, (what, near, far_taps)


@pytest.mark.parametrize("seq,channels,itemsize,head_norm,plan", [
    # the three cells, at the sizes this file's fixture leaves aside
    (8192, 12288, 2, (128, 1e-6, (0.1, 1.0, None)), (1024, 512)),
    (8192, 4352, 2, None, (2048, 256)),
    (8192, 2048, 2, None, (1024, 512)),
    (8192, 2048, 4, None, (512, 512)),       # float32 operands: half the rows
    (300, 256, 2, None, (256, 256)),         # a ragged second tile
    (128, 128, 2, None, (128, 128)),
    (100, 256, 2, None, None),               # a sequence under one tile
    (8192, 200, 2, None, None),              # channels off the lane tiling
    (8192, 768, 2, (64, 1e-6, (1.0, 1.0, None)), None),   # a head half a tile
    (8192, 768, 2, (128, 1e-6, (1.0, None)), (4096, 128)),  # parts of 384
    (8192, 512, 2, (128, 1e-6, (1.0, 1.0, None)), None),  # parts of no tile
])
def test_the_tile_plan_is_a_function_of_shapes(monkeypatch, seq, channels,
                                               itemsize, head_norm, plan):
    monkeypatch.undo()      # the module's own sizes
    assert conv.tile_plan(seq, channels, itemsize, head_norm) == plan


def test_shapes_that_fit_no_tile_take_the_jax_numpy_form():
    """200 channels with the kernels handed in: they answer None, nothing
    is raised, and the result is the plain one's bits."""
    k = jax.random.split(jax.random.key(1), 3)
    u = jax.random.normal(k[0], (2, 300, 200))
    taps, bias = jax.random.normal(k[1], (200, 4)), jax.random.normal(
        k[2], (200,))
    asked = []

    def kernels(*a, **kw):
        asked.append(conv.causal_conv(*a, **kw, interpret=True))
        return asked[-1]
    np.testing.assert_array_equal(
        np.asarray(M.causal_depthwise_conv(u, taps, bias, silu=True,
                                           conv_fn=kernels, scope="x")),
        np.asarray(M.causal_depthwise_conv(u, taps, bias, silu=True)))
    assert asked == [None]
    with pytest.raises(ValueError, match="come as a pair"):
        conv.causal_conv(jnp.zeros((1, 128, 128)), taps[:128],
                         pre=jnp.zeros((1, 128, 128)), scope="x",
                         interpret=True)


@pytest.mark.parametrize("forced", [None, True, False])
def test_who_knows_the_devices_hands_the_kernels_down(forced):
    """``attention_overrides`` gives a layer that convolves its ``conv``
    kernels where every device of the mesh is a TPU (here: never, unless a test
    says so) and the layer's sequence is whole on a device, and no other
    layer ever."""
    from hetu_galvatron_tpu.parallel import spmd
    from hetu_galvatron_tpu.runtime.mesh import LayerSharding, build_mesh

    mesh = build_mesh(2, 1, devices=jax.devices()[:2])
    whole = LayerSharding(dp_axes=("d0",), cp_axes=(), tp_axes=())
    cut = LayerSharding(dp_axes=(), cp_axes=("d0",), tp_axes=())
    mixers = ["mamba", "full_attention", "kda", "conv", "latent_attention",
              "mamba"]
    got = spmd.attention_overrides(
        [whole] * 5 + [cut], mesh, use_flash=False, flash_interpret=True,
        mixers=mixers, kernels=forced)
    takers = [i for i, m in enumerate(mixers[:5])
              if M.MIXERS[m].reads("conv")]
    # (a mamba layer whose sequence is cut gets no convolution: where the
    # kernels run, its scan alone)
    assert getattr(got.pop(5, None), "conv", None) is None
    assert sorted(got) == (takers if forced else [])
    assert all(ops.conv is not None and ops.sdpa is None
               for ops in got.values())
    if forced:
        # and what it hands down is the convolution, under shard_map over dp
        args = _inputs("mamba", 300, jnp.float32)
        fn = got[0].conv
        np.testing.assert_allclose(
            np.asarray(M.causal_depthwise_conv(
                *args[:3], silu=True, conv_fn=fn, scope="mixer/mamba/conv")),
            np.asarray(_plain("mamba", jnp.float32)(*args)),
            rtol=1e-5, atol=1e-5)
        assert fn(jnp.zeros((2, 300, 200)), jnp.zeros((200, 4)),
                  scope="x") is None


def test_the_channels_of_a_layer_cut_over_tp_stay_on_their_shards():
    """A depthwise convolution is local to a channel shard: under a mesh
    that cuts the channels in two, each device's kernel takes its half."""
    from hetu_galvatron_tpu.runtime.mesh import build_mesh

    mesh = build_mesh(2, 1, devices=jax.devices()[:2])
    fn = conv.make_causal_conv(mesh, tp_axes=("d0",), interpret=True)
    args = _inputs("short_conv", 300, jnp.float32)
    u, taps, _, pre, post = args
    got = fn(u, taps, pre=pre, post=post, out_dtype=jnp.float32,
             scope="mixer/short_conv/gate_conv")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_plain("short_conv", jnp.float32)(*args)),
        rtol=1e-5, atol=1e-5)
    # 128 channels a device fit a tile; a head's norm over cut parts none
    assert fn(u[..., :128], taps[:128], scope="x") is None
    assert fn(u, taps, head_norm=(128, EPS, (1.0, None)), scope="x") is None


@pytest.mark.parametrize("scope", [
    "mixer/kda/conv", "mixer/mamba/conv", "mixer/short_conv/gate_conv"])
def test_forward_and_backward_are_traced_under_the_callers_scope(scope):
    """What lays device time over a block's convolution is the ``op_name``
    of a compiled instruction. The forward is called under the block's
    scope; the backward rule of a ``custom_vjp`` is traced when the
    gradient is taken, outside every scope of the model, and opens the
    scope it was told itself. Here as the step does it: the scope around
    the forward only, ``jax.grad`` around the whole."""
    from hetu_galvatron_tpu.observability import trace_analysis

    assert scope in trace_analysis.CONV_SCOPES
    assert scope in trace_analysis.SCOPES
    outer, inner = scope.rsplit("/", 1)

    def block(u, taps):
        with jax.named_scope(outer):
            with jax.named_scope(inner):
                return conv.causal_conv(u, taps, silu=True, scope=scope,
                                        interpret=True)

    u, taps = _inputs("bare", 256, jnp.float32)[:2]
    text = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(block(*a))),
                            argnums=(0, 1))).lower(
                                u, taps).compile().as_text()
    found = trace_analysis.scope_instructions(text, (scope,))
    listed = set(found["scopes"][scope])
    calls = {name: [0, 0] for name in trace_analysis.CONV_CALLS}
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
    # so the step report's reader counts no kernel
    assert trace_analysis.conv_kernel_calls(
        trace_analysis.step_hlo(text)) == {
            "forward": 0, "recompute": 0, "backward": 0}


def test_the_step_report_counts_the_kernels_by_phase():
    """A step's lines in the cells' own form, shortened: a block whose
    convolution runs in the kernels is one call in each of the forward
    pass, the forward made again under per-layer remat and the backward
    pass; a kernel of another scope, and a call of that name under no
    convolution's scope, are no part of the count."""
    from hetu_galvatron_tpu.observability import trace_analysis

    call = 'custom-call(%a), custom_call_target="tpu_custom_call"'
    remat = "jit(step)/transpose(jvp())/checkpoint"
    hlo = f"""ENTRY %main (a: f32[8]) -> f32[8] {{
  %causal_conv_fwd.1 = bf16[8]{{0}} {call}, metadata={{op_name="jit(step)/jvp(mixer/kda)/conv/causal_conv_fwd/pallas_call"}}
  %causal_conv_fwd.2 = bf16[8]{{0}} {call}, metadata={{op_name="jit(step)/jvp(mixer/mamba)/conv/causal_conv_fwd/pallas_call"}}
  %kda_scan_fwd.8 = f32[8]{{0}} {call}, metadata={{op_name="jit(step)/jvp(mixer/kda)/scan/kda_scan_fwd/pallas_call"}}
  %causal_conv_fwd.3 = bf16[8]{{0}} {call}, metadata={{op_name="{remat}/rematted_computation/mixer/kda/conv/causal_conv_fwd/pallas_call"}}
  %causal_conv_bwd.1 = bf16[8]{{0}} {call}, metadata={{op_name="{remat}/mixer/kda/conv/mixer/kda/conv/causal_conv_bwd/pallas_call"}}
  %causal_conv_bwd.2 = bf16[8]{{0}} {call}, metadata={{op_name="{remat}/mixer/short_conv/gate_conv/causal_conv_bwd/pallas_call"}}
  %causal_conv_fwd.4 = bf16[8]{{0}} {call}, metadata={{op_name="jit(step)/jvp(mlp)/causal_conv_fwd/pallas_call"}}
}}
"""
    assert trace_analysis.conv_kernel_calls(trace_analysis.step_hlo(hlo)) == {
        "forward": 2, "recompute": 1, "backward": 2}
    assert trace_analysis.conv_kernel_calls(trace_analysis.step_hlo("")) == {
        "forward": 0, "recompute": 0, "backward": 0}
