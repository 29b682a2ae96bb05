"""The flash kernels, the kernels of the Mamba-2 scan and of its gated
norm, those of the
chunked delta rule (a decay a channel, and a decay a head), those of the
causal depthwise convolution and those of
the experts' grouped matmuls, compiled by the real Mosaic / XLA:TPU compilers for a
described (not attached) TPU v5e, at
the widths the benchmark's cells run and with every optional operand: what interpret mode cannot refuse (a slice off
the tiling, a relayout Mosaic has no rule for, too much VMEM) fails here, on
the CPU, in seconds. Nothing runs, so nothing here is a result or a time.

All such compiles live in THIS file and describe the topology inside a
fixture: one process may hold libtpu at a time, and a module that touched it
while being imported would do so in every xdist worker."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from hetu_galvatron_tpu.ops.pallas import (
    conv,
    gated_norm,
    gdn,
    kda,
    selective_scan,
    ssd,
)
from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa

pytestmark = pytest.mark.kernels


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# B, S, Sk, heads, kv heads, head dim (q/k, or (q/k, v)), dtype, causal,
# segments, dropout[, window]
_CASES = {
    "window_cell_72_heads": (1, 8192, 8192, 72, 8, 128, jnp.bfloat16, True,
                             False, 0.0, 512),
    "window_f32_segments_dropout": (1, 2048, 2048, 6, 2, 128, jnp.float32,
                                    True, True, 0.1, 300),
    "latent_cell_192_128": (1, 4096, 4096, 32, 32, (192, 128), jnp.bfloat16,
                            True, False, 0.0),
    "latent_f32_segments": (1, 1024, 1024, 4, 4, (192, 128), jnp.float32,
                            True, True, 0.0),
    "gpt2xl_cell": (8, 1024, 1024, 25, 25, 64, jnp.bfloat16, True, False, 0.0),
    "mistral_cell": (1, 4096, 4096, 32, 8, 128, jnp.bfloat16, True, False,
                     0.0),
    "two_major_blocks_f32": (1, 4096, 4096, 8, 2, 128, jnp.float32, True,
                             False, 0.0),
    "ring_off_diagonal": (2, 2048, 1024, 4, 4, 64, jnp.bfloat16, False,
                          False, 0.0),
    "segments_and_dropout": (1, 4096, 4096, 8, 2, 128, jnp.bfloat16, True,
                             True, 0.1),
    "whole_length_block": (2, 384, 384, 4, 4, 64, jnp.bfloat16, True, False,
                           0.0),
    # the kernels on the projections' own rows, at the cells' shapes: one
    # head a 128-lane column block (mistral_cell, window_cell_72_heads and
    # the others at width 128 above) or two (gpt2xl_cell above: 25 heads,
    # the last pair half outside the array)
    "lfm2_cell_pairs_g4": (1, 8192, 8192, 32, 8, 64, jnp.bfloat16, True,
                           False, 0.0),
    "granite_cell_pairs_g4": (1, 8192, 8192, 32, 8, 64, jnp.bfloat16, True,
                              False, 0.0, None, 0.0078125),
    "olmoe_cell_mha_128": (1, 4096, 4096, 16, 16, 128, jnp.bfloat16, True,
                           False, 0.0),
    "laguna_cell_full_48_heads": (1, 8192, 8192, 48, 8, 128, jnp.bfloat16,
                                  True, False, 0.0),
    # mellum2_c4_ep4: a chip's one sequence, 32 query heads over 4 key-value
    # heads of 128 (groups of 8), the window blocks' 1024 and the full
    # block; at 8192 and at the cell's 4096
    "mellum_cell_window_1024": (1, 8192, 8192, 32, 4, 128, jnp.bfloat16,
                                True, False, 0.0, 1024),
    "mellum_cell_full_g8": (1, 8192, 8192, 32, 4, 128, jnp.bfloat16, True,
                            False, 0.0),
    "mellum_cell_window_1024_at_4096": (1, 4096, 4096, 32, 4, 128,
                                        jnp.bfloat16, True, False, 0.0,
                                        1024),
    "mistral_four_chip_shard": (2, 4096, 4096, 16, 4, 128, jnp.bfloat16,
                                True, False, 0.0),
    "pairs_f32_segments_dropout_window": (2, 1024, 1024, 5, 5, 64,
                                          jnp.float32, True, True, 0.1, 300),
    "pairs_g5_segments_dropout": (1, 1024, 1024, 10, 2, 64, jnp.bfloat16,
                                  True, True, 0.1),
    "pairs_off_diagonal_g2": (1, 1024, 2048, 4, 2, 64, jnp.bfloat16, False,
                              False, 0.0),
    "rows_256_v128": (1, 1024, 1024, 4, 2, (256, 128), jnp.bfloat16, True,
                      False, 0.0),
    # kimivl_c1_b1_s4k's tower: not causal, the images as segments, whose
    # chunk ranges the three kernels prefetch as scalars (every case with
    # segments above compiles that path too: causal, window, dropout, pairs)
    "tower_cell_72_segments": (1, 8192, 8192, 16, 16, 72, jnp.bfloat16,
                               False, True, 0.0),
    "two_way_f32_segments_two_major_blocks": (2, 4096, 4096, 4, 2, 128,
                                              jnp.float32, False, True, 0.0),
    # phi4flash_c1_b1: differential attention's one core call a block, 40
    # score heads at q/k 64 over the pair's value of 128 handed to both of
    # its 20 key heads; whole and under the window of 512. 64 / 128 is
    # outside the rows form (``row_layout``: whole lane tiles, or 64 / 64)
    "phi4flash_cell_64_128": (1, 8192, 8192, 40, 20, (64, 128), jnp.bfloat16,
                              True, False, 0.0),
    "phi4flash_cell_64_128_window_512": (1, 8192, 8192, 40, 20, (64, 128),
                                         jnp.bfloat16, True, False, 0.0, 512),
}
# the cases whose call keeps the head-major kernels between transposes
_TRANSPOSED = {"latent_cell_192_128", "latent_f32_segments",
               "tower_cell_72_segments", "phi4flash_cell_64_128",
               "phi4flash_cell_64_128_window_512"}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_flash_forward_and_backward_compile_for_v5e(one_chip, case):
    from hetu_galvatron_tpu.ops.pallas.flash_attention import row_layout

    B, S, Sk, N, K, D, dtype, causal, seg, drop, *rest = _CASES[case]
    D, Dv = D if isinstance(D, tuple) else (D, D)
    window, scale = (rest + [None, None])[:2]
    assert (row_layout(N, K, D, Dv) is None) == (case in _TRANSPOSED)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fwd_bwd(q, k, v, do, segments, key):
        out, vjp = jax.vjp(
            lambda a, b, c: flash_sdpa(
                a, b, c, causal=causal,
                segment_ids=segments if seg else None, dropout_rate=drop,
                dropout_rng=key if drop else None, window=window,
                scale=scale), q, k, v)
        return (out,) + vjp(do)

    compiled = jax.jit(fwd_bwd).lower(
        spec((B, S, N, D), dtype), spec((B, Sk, K, D), dtype),
        spec((B, Sk, K, Dv), dtype), spec((B, S, N, Dv), dtype),
        spec((B, S), jnp.int32),
        spec((), jax.random.key(0).dtype)).compile()
    hlo = compiled.as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"):
        assert kernel in hlo, f"{kernel} is not in the compiled program"
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3


# B, S, heads, head dim, state, chunk, dtype[, groups of B and C]
_SSD_CASES = {
    "granite_cell": (1, 8192, 64, 64, 128, 256, jnp.bfloat16),
    "two_rows_f32": (2, 1024, 16, 64, 128, 256, jnp.float32),
    "a_head_a_lane_tile": (1, 1024, 8, 128, 128, 128, jnp.bfloat16),
    "four_heads_a_lane_tile": (1, 512, 16, 32, 256, 256, jnp.bfloat16),
    "nemotronh_cell_eight_groups": (2, 8192, 64, 64, 128, 128, jnp.bfloat16,
                                    8),
    "three_steps_a_group_f32": (1, 512, 48, 64, 128, 128, jnp.float32, 2),
}


@pytest.mark.parametrize("case", sorted(_SSD_CASES))
def test_ssd_scan_forward_and_backward_compile_for_v5e(one_chip, case):
    """Both kernels compile at the cell's shapes, and each is one
    instruction under ``mixer/mamba/ssd`` in the map the ``granite_*``
    readers lay a trace over: the forward by the scope it was called in,
    the backward by the scope its rule opens."""
    from hetu_galvatron_tpu.observability.trace_analysis import (
        scope_instructions,
    )

    B, S, H, P, N, Q, dtype, *groups = _SSD_CASES[case]
    G = groups[0] if groups else 1

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def block(*a):
        with jax.named_scope("mixer/mamba"):
            with jax.named_scope("ssd"):
                return ssd.ssd_scan(*a, Q, groups=G)

    compiled = jax.jit(jax.grad(
        lambda *a: jnp.sum(block(*a)), argnums=(0, 1, 2, 3, 4))).lower(
            spec((B, S, H, P), dtype), spec((B, S, H), jnp.float32),
            spec((H,), jnp.float32), spec((B, S, G * N), dtype),
            spec((B, S, G * N), dtype)).compile()
    found = scope_instructions(compiled.as_text(), (ssd.SCOPE,))
    calls = sorted(found["mosaic_calls"])
    assert len(calls) == 2, calls
    assert "ssd_scan_bwd" in calls[0] and "ssd_scan_fwd" in calls[1], calls
    assert set(calls) <= set(found["scopes"][ssd.SCOPE])


# B, S, heads, keys and values a head, chunk, dtype (``G`` and ``beta`` are
# float32 whatever the operands are)
_KDA_CASES = {
    "kimi_cell": (1, 8192, 32, 128, 64, jnp.bfloat16),
    "two_rows_f32_all_heads_a_step": (2, 512, 4, 128, 64, jnp.float32),
    "four_heads_a_pack": (1, 256, 8, 128, 32, jnp.bfloat16),
    "a_head_a_pack": (1, 512, 2, 128, 128, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(_KDA_CASES))
def test_kda_scan_forward_and_backward_compile_for_v5e(one_chip, case):
    """The kernels compile at the cell's shapes, and under per-layer remat
    a block is three instructions under ``mixer/kda/scan`` in the map the
    ``kimi_*`` readers lay a trace over (the forward, the forward made
    again with the entering states kept, the backward by the scope its
    rule opens); the step report reads its blocks and chunk from the calls
    themselves, the compiled step having no loop to read them from."""
    from hetu_galvatron_tpu.observability.trace_analysis import (
        kda_kernel_calls,
        kda_loops,
        scope_instructions,
    )

    B, S, H, d, C, dtype = _KDA_CASES[case]

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    @jax.checkpoint
    def block(*a):
        with jax.named_scope("mixer/kda"):
            with jax.named_scope("scan"):
                return kda.kda_scan(*a, C)

    compiled = jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.square(block(*a))),
        argnums=(0, 1, 2, 3, 4))).lower(
            spec((B, S, H, d), dtype), spec((B, S, H, d), dtype),
            spec((B, S, H, d), dtype), spec((B, S, H, d), jnp.float32),
            spec((B, S, H), jnp.float32)).compile()
    text = compiled.as_text()
    found = scope_instructions(text, (kda.SCOPE,))
    calls = sorted(found["mosaic_calls"])
    assert len(calls) == 3, calls
    assert "kda_scan_bwd" in calls[0], calls
    assert all("kda_scan_fwd" in c for c in calls[1:]), calls
    assert set(calls) <= set(found["scopes"][kda.SCOPE])
    assert kda_kernel_calls(text) == {"mosaic_calls": 3, "blocks": 1,
                                      "chunk": C}
    assert kda_loops(text) == {"blocks": 0, "chunks": 0}


# B, S, heads, keys and values a head, chunk, dtype (``g`` and ``beta``, one
# number a head and position, are float32 whatever the operands are)
_GDN_CASES = {
    "olmohybrid_cell": (1, 4096, 30, 96, 192, 64, jnp.bfloat16),
    "two_rows_f32": (2, 512, 6, 96, 192, 64, jnp.float32),
    "four_heads_a_pack": (1, 256, 8, 32, 48, 32, jnp.bfloat16),
    "a_head_a_pack_two_steps": (1, 512, 64, 128, 256, 128, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(_GDN_CASES))
def test_gdn_scan_forward_and_backward_compile_for_v5e(one_chip, case):
    """The kernels compile at the cell's shapes (30 heads of 96 keys under
    192 values: no lane tile asked of either), and under plain
    ``jax.checkpoint`` a block is three Mosaic calls under
    ``mixer/gdn/scan`` (the forward, the forward made again with the
    entering states kept, the backward by the scope its rule opens), which
    is what the step report's ``gdn/mosaic_calls`` counts."""
    from hetu_galvatron_tpu.observability.trace_analysis import (
        scope_instructions,
    )

    B, S, H, dk, dv, C, dtype = _GDN_CASES[case]
    assert gdn.tile_plan(C, H, dk, dv) is not None

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    @jax.checkpoint
    def block(*a):
        with jax.named_scope("mixer/gdn"):
            with jax.named_scope("scan"):
                return gdn.gdn_scan(*a, C)

    compiled = jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.square(block(*a))),
        argnums=(0, 1, 2, 3, 4))).lower(
            spec((B, S, H, dk), dtype), spec((B, S, H, dk), dtype),
            spec((B, S, H, dv), dtype), spec((B, S, H), jnp.float32),
            spec((B, S, H), jnp.float32)).compile()
    found = scope_instructions(compiled.as_text(), (gdn.SCOPE,))
    calls = sorted(found["mosaic_calls"])
    assert len(calls) == 3, calls
    assert "gdn_scan_bwd" in calls[0], calls
    assert all("gdn_scan_fwd" in c for c in calls[1:]), calls
    assert set(calls) <= set(found["scopes"][gdn.SCOPE])


# B, S, channels, state, u's dtype
_SELECTIVE_CASES = {
    "phi4flash_cell": (1, 8192, 5120, 16, jnp.bfloat16),
    "two_rows_f32_a_ragged_chunk": (2, 300, 384, 16, jnp.float32),
    "a_wider_state": (1, 1024, 512, 64, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(_SELECTIVE_CASES))
def test_selective_scan_forward_and_backward_compile_for_v5e(one_chip, case):
    """Both kernels compile at the cell's shapes, and under per-layer remat
    a block is two instructions under ``mixer/mamba1/scan`` in the map a
    trace is laid over (the forward by the scope it was called in, the
    backward by the scope its rule opens; ``modules.remat`` keeps what the
    forward named, so none is made again)."""
    from hetu_galvatron_tpu.core.args_schema import ModelArgs
    from hetu_galvatron_tpu.models import modules
    from hetu_galvatron_tpu.observability.trace_analysis import (
        scans_recomputed,
        step_hlo,
    )

    B, S, C, N, dtype = _SELECTIVE_CASES[case]

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def block(*a):
        with jax.named_scope("mixer/mamba1"):
            with jax.named_scope("scan"):
                return selective_scan.selective_scan(*a)

    block = modules.remat(block, ModelArgs(
        hidden_size=32, num_hidden_layers=1, num_attention_heads=2,
        vocab_size=64))
    text = jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.square(block(*a))),
        argnums=(0, 1, 2, 3, 4))).lower(
            spec((B, S, C), dtype), spec((B, S, C), jnp.float32),
            spec((C, N), jnp.float32), spec((B, S, N), jnp.float32),
            spec((B, S, N), jnp.float32)).compile().as_text()
    found = step_hlo(text, (selective_scan.SCOPE,))
    calls = sorted(found["mosaic_calls"])
    assert len(calls) == 2, calls
    assert "selective_scan_bwd" in calls[0], calls
    assert "selective_scan_fwd" in calls[1], calls
    assert set(calls) <= set(found["scopes"][selective_scan.SCOPE])
    assert scans_recomputed(found) == 0


# B, S, channels, taps, bias, gates, head_norm, dtype, the caller's scope
_CONV_CASES = {
    "kimi_cell_three_at_once": (
        1, 8192, 12288, 4, False, False, (128, 1e-6, (128 ** -0.5, 1.0, None)),
        jnp.bfloat16, "mixer/kda/conv"),
    "granite_cell": (1, 8192, 4352, 4, True, False, None, jnp.bfloat16,
                     "mixer/mamba/conv"),
    "lfm2_cell_between_its_gates": (
        1, 8192, 2048, 3, False, True, None, jnp.bfloat16,
        "mixer/short_conv/gate_conv"),
    "two_rows_f32_a_ragged_tile": (
        2, 1100, 768, 4, True, True, (128, 1e-6, (1.0, None, 0.5)),
        jnp.float32, "mixer/kda/conv"),
    "phi4flash_cell": (1, 8192, 5120, 4, True, False, None, jnp.bfloat16,
                       "mixer/mamba1/conv"),
    # q | k | v of 2880 | 2880 | 5760: 90 lane tiles side by side, no L2
    # norm in the pass (a head of 96 is no lane tile)
    "olmohybrid_cell_three_at_once": (
        1, 4096, 11520, 4, False, False, None, jnp.bfloat16,
        "mixer/gdn/conv"),
}


@pytest.mark.parametrize("case", sorted(_CONV_CASES))
def test_causal_conv_forward_and_backward_compile_for_v5e(one_chip, case):
    """Both kernels compile at the three cells' shapes, and each is one
    instruction under its caller's scope in the map a trace is laid over
    (the forward by the scope it was called in, the backward by the scope
    its rule is told), which the step report counts by phase. (Under a
    bare ``jax.checkpoint`` XLA makes one call of the forward and the
    forward made again, which here nothing separates; a step's layers are
    apart, and its report reads one a phase: PERF.md section 6, PR 45.)"""
    from hetu_galvatron_tpu.observability.trace_analysis import (
        CONV_CALLS,
        conv_kernel_calls,
        step_hlo,
    )

    B, S, C, L, bias, gated, head_norm, dtype, scope = _CONV_CASES[case]
    outer, inner = scope.rsplit("/", 1)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    wide = spec((B, S, C), dtype)
    args = {"u": wide, "taps": spec((C, L), jnp.float32)}
    if bias:
        args["bias"] = spec((C,), jnp.float32)
    if gated:
        args.update(pre=wide, post=wide)

    def block(a):
        with jax.named_scope(outer):
            with jax.named_scope(inner):
                return conv.causal_conv(
                    a["u"], a["taps"], a.get("bias"), pre=a.get("pre"),
                    post=a.get("post"), silu=not gated, head_norm=head_norm,
                    out_dtype=dtype, scope=scope)

    text = jax.jit(jax.grad(lambda a: jnp.sum(jnp.square(
        block(a).astype(jnp.float32))))).lower(args).compile().as_text()
    found = step_hlo(text, (scope,))
    calls = sorted(found["mosaic_calls"])
    assert len(calls) == 2, calls
    assert calls[0].startswith(CONV_CALLS[1]), calls
    assert calls[1].startswith(CONV_CALLS[0]), calls
    assert set(calls) <= set(found["scopes"][scope])
    assert conv_kernel_calls(found) == {"forward": 1, "recompute": 0,
                                        "backward": 1}


# rows, groups, contracted width, columns, dtype: the product forward
# (float32 out, as the second product of the layer is) and both gradients
_GROUPED_CASES = {
    # OLMoE's first product: the whole [2048, 2048] of an expert in VMEM
    "olmoe_win": (32768, 64, 2048, 2048, jnp.bfloat16),
    # Mellum2's second: 7 and 18 lane tiles; Laguna's first chunk of 3,200
    # rows, whose last row tile is a quarter inside the array
    "odd_widths_ragged_rows": (3200, 16, 896, 2304, jnp.bfloat16),
    "f32": (640, 5, 256, 384, jnp.float32),
}


@pytest.mark.parametrize("case", sorted(_GROUPED_CASES))
def test_grouped_matmuls_three_kernels_compile_for_v5e(one_chip, case):
    """``moe._grouped_matmul`` handed the kernels, value and both gradients
    in one program: the three Mosaic calls, each under ``moe/experts`` by
    its name stack (the backward rule opens the scope itself), and no
    ``ragged-dot`` call of libtpu's."""
    from hetu_galvatron_tpu.models import moe
    from hetu_galvatron_tpu.observability.trace_analysis import (
        EXPERTS_CALLS,
        experts_kernel_calls,
        step_hlo,
    )
    from hetu_galvatron_tpu.ops.pallas import grouped_matmul as gm

    M, G, K, N, dtype = _GROUPED_CASES[case]
    assert gm.tile_plan(M, G, K, N, dtype) is not None
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)

    def loss(rows, weights, sizes):
        with jax.named_scope("moe/experts"):
            y = moe._grouped_matmul(rows, weights, sizes, jnp.float32,
                                    gm.grouped_matmul)
        return jnp.sum(jnp.square(y))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        spec((M, K), dtype), spec((G, K, N), dtype),
        spec((G,), jnp.int32)).compile().as_text()
    found = step_hlo(text)
    calls = sorted(found["mosaic_calls"])
    assert [c.split(".")[0] for c in calls] == sorted(EXPERTS_CALLS), calls
    assert experts_kernel_calls(found) == 3
    assert "ragged-dot" not in text
    placed = found["map"]["instructions"]
    assert {placed[c][:2] for c in calls} == {
        ("moe/experts", "forward"), ("moe/experts", "backward")}


@pytest.mark.parametrize("wrapper,forwards,recomputed", [
    ("remat", 1, 0), ("plain_checkpoint", 2, 1)])
def test_a_rematted_block_holds_one_forward_kernel(one_chip, wrapper,
                                                   forwards, recomputed):
    """The real kernels under per-layer remat, at GPT-2 XL's widths: the
    gradient of a block wrapped by ``modules.remat`` compiles to one
    ``flash_attention_fwd`` (three Mosaic calls: what
    ``benchmark/check.py`` asks of a layer), none of it in the recompute
    phase; under plain ``jax.checkpoint`` there are two, and
    ``trace_analysis.cores_recomputed`` counts the second (the control,
    at two heads: the count is the wrapper's, not the widths')."""
    from hetu_galvatron_tpu.core.args_schema import ModelArgs
    from hetu_galvatron_tpu.observability.trace_analysis import (
        FLASH_FWD_CALL,
        cores_recomputed,
    )

    B, S, H, N = (2, 1024, 1600, 25) if wrapper == "remat" else (
        1, 1024, 128, 2)
    cfg = ModelArgs(hidden_size=H, num_hidden_layers=1,
                    num_attention_heads=N, vocab_size=128,
                    max_position_embeddings=S, seq_length=S)
    _, found = _block_step(one_chip, cfg, "full_attention", wrapper, B,
                           sdpa=flash_sdpa)
    fwd = [n for n in found["mosaic_calls"] if n.startswith(FLASH_FWD_CALL)]
    assert len(fwd) == forwards, sorted(found["mosaic_calls"])
    assert found["mosaic_custom_calls"] == forwards + 2
    assert cores_recomputed(found) == recomputed


def _block_step(one_chip, cfg, kind, wrapper, B, dtype=jnp.bfloat16, **ops):
    """(optimized HLO, ``step_hlo`` of it) of the gradient, to the
    parameters and to the input, of one block of ``cfg`` handed the kernels
    ``ops`` under ``modules.remat`` or plain ``jax.checkpoint``."""
    from hetu_galvatron_tpu.models import modules as M
    from hetu_galvatron_tpu.observability.trace_analysis import step_hlo

    shapes = jax.eval_shape(
        lambda k: M.init_decoder_layer(k, cfg, mixer=kind)[0],
        jax.random.key(0))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    x = jax.ShapeDtypeStruct((B, cfg.seq_length, cfg.hidden_size), dtype,
                             sharding=one_chip)

    def block(p, h):
        return M.apply_decoder_layer(p, h, cfg, ops=M.LayerOps(**ops),
                                     mixer=kind, compute_dtype=dtype)

    wrapped = M.remat(block, cfg) if wrapper == "remat" else jax.checkpoint(
        block)
    # (a loss whose gradient needs the block's output: under a plain sum
    # the first forward pass is dead code and only the recomputed one stays)
    text = jax.jit(jax.grad(
        lambda p, h: jnp.sum(wrapped(p, h).astype(jnp.float32) ** 2),
        argnums=(0, 1))).lower(params, x).compile().as_text()
    return text, step_hlo(text)


# a recurrent block at its cell's widths: the field of ``LayerOps`` its scan
# goes in, the scan, its forward kernel, the model's sizes
# (``kimilin_c1_b1_s8k``, ``olmohybrid_c1_b1``, ``granite4h_c1_b1``; one
# sequence of 8192), and the control's: the kinds' own head widths and chunks
# at two heads (eight of mamba's: a grid step) over 1024 positions
_SCAN_BLOCKS = {
    "kda": ("kda", kda.kda_scan, "kda_scan_fwd", dict(
        hidden_size=2304, ffn_hidden_size=9216, kda_num_heads=32,
        kda_head_dim=128, kda_chunk_size=64), dict(
        hidden_size=256, ffn_hidden_size=512, kda_num_heads=2)),
    "linear_attention": ("gdn", gdn.gdn_scan, "gdn_scan_fwd", dict(
        hidden_size=3840, ffn_hidden_size=11008, linear_num_key_heads=30,
        linear_num_value_heads=30, linear_key_head_dim=96,
        linear_value_head_dim=192, linear_chunk_size=64,
        linear_allow_neg_eigval=True), dict(
        hidden_size=384, ffn_hidden_size=512, linear_num_key_heads=2,
        linear_num_value_heads=2)),
    "mamba": ("ssd", ssd.ssd_scan, "ssd_scan_fwd", dict(
        hidden_size=2048, ffn_hidden_size=8192, mamba_n_heads=64,
        mamba_d_head=64, mamba_d_state=128, mamba_chunk_size=256), dict(
        hidden_size=256, ffn_hidden_size=512, mamba_n_heads=8)),
}


@functools.lru_cache(maxsize=None)
def _scan_block_step(one_chip, kind, wrapper, B, S, dtype, groups,
                     norm_kernels, control):
    """``_block_step`` of a recurrent block at its cell's widths (the
    control's where ``control``) handed its scan's and the convolution's
    kernels and, where ``norm_kernels``, the gated norm's; what two cases
    both read (a mamba block of one group under ``modules.remat``) is
    compiled once."""
    from hetu_galvatron_tpu.core.args_schema import ModelArgs

    field, scan, _, sizes, small = _SCAN_BLOCKS[kind]
    cfg = ModelArgs(num_hidden_layers=1, num_attention_heads=32,
                    vocab_size=128, max_position_embeddings=S, seq_length=S,
                    hidden_act="swiglu", normalization="rmsnorm",
                    add_bias_linear=False, mamba_n_groups=groups,
                    **{**sizes, **(small if control else {})})
    return _block_step(
        one_chip, cfg, kind, wrapper, B, dtype, conv=conv.causal_conv,
        gated_norm=gated_norm.gated_norm if norm_kernels else None,
        **{field: scan})


@pytest.mark.parametrize("wrapper,forwards,recomputed", [
    ("remat", 1, 0), ("plain_checkpoint", 2, 1)])
@pytest.mark.parametrize("kind", sorted(_SCAN_BLOCKS))
def test_a_rematted_recurrent_block_holds_one_scan_forward(
        one_chip, kind, wrapper, forwards, recomputed):
    """The real scan and convolution kernels under per-layer remat, at the
    cells' widths: the gradient of a block wrapped by ``modules.remat``
    compiles to one ``kda_scan_fwd`` / ``gdn_scan_fwd`` / ``ssd_scan_fwd``,
    none of it in the
    recompute phase (the convolution's forward names nothing and is there
    twice); under plain ``jax.checkpoint`` there are two, and
    ``trace_analysis.scans_recomputed`` counts the second (the control, a
    few heads over 1024 positions: the count is the wrapper's)."""
    from hetu_galvatron_tpu.observability.trace_analysis import (
        conv_kernel_calls,
        cores_recomputed,
        scans_recomputed,
    )

    forward = _SCAN_BLOCKS[kind][2]
    control = wrapper != "remat"
    _, found = _scan_block_step(one_chip, kind, wrapper, 1,
                                1024 if control else 8192, jnp.bfloat16, 1,
                                False, control)
    fwd = [n for n in found["mosaic_calls"] if n.startswith(forward)]
    assert len(fwd) == forwards, sorted(found["mosaic_calls"])
    # the scan's backward, and the convolution's three either way
    assert found["mosaic_custom_calls"] == forwards + 1 + 3
    assert conv_kernel_calls(found) == {"forward": 1, "recompute": 1,
                                        "backward": 1}
    assert scans_recomputed(found) == recomputed
    assert cores_recomputed(found) == 0


# groups of B, C and the gated norm's channels, the rows a step, the dtype:
# Nemotron-H's mixer (two sequences in one microbatch), Granite's, and
# float32 operands over a ragged last tile (rows of 80 forward)
_GATED_NORM_BLOCKS = {"nemotronh_cell_8_groups": (8, 2, 8192, jnp.bfloat16),
                      "granite_cell_1_group": (1, 1, 8192, jnp.bfloat16),
                      "f32_a_ragged_tile": (2, 1, 1792, jnp.float32)}


@pytest.mark.parametrize("norm", ["kernels", "jax_numpy"])
@pytest.mark.parametrize("case", sorted(_GATED_NORM_BLOCKS))
def test_a_mamba_blocks_gated_norm_is_one_pass_a_phase(one_chip, case, norm):
    """A mamba block at the cells' widths under per-layer remat, handed the
    scan's, the convolution's and the gated norm's kernels: the norm is ONE
    Mosaic call in each of the forward pass, the forward made again and the
    backward pass, each under ``mixer/mamba/gated_norm`` (the backward by
    the scope its rule is told), which ``gated_norm/mosaic_calls`` counts,
    while the scan's two stay what ``ssd/mosaic_calls`` counts; and no
    ``reshape`` or ``copy`` of the rows outside a fusion is that scope's,
    by its own ``op_name`` or by the map's ``owners``. The control: in
    ``jax.numpy`` several groups are a minor dimension of their own, and the compiled
    block holds such relayouts (one group holds none either way)."""
    from hetu_galvatron_tpu.observability.trace_analysis import (
        GATED_NORM_SCOPE,
        SSD_SCOPE,
    )

    G, B, S, dtype = _GATED_NORM_BLOCKS[case]
    text, found = _scan_block_step(one_chip, "mamba", "remat", B, S, dtype,
                                   G, norm == "kernels", False)
    placed, owners = found["map"]["instructions"], found["map"]["owners"]
    calls = [n for n in found["scopes"][GATED_NORM_SCOPE]
             if n in found["mosaic_calls"]]
    # the scope's relayouts of an array of the rows' size (``D`` spread
    # over its head's lanes is a reshape of 16 KB either way)
    relayouts = [n for n in found["map"]["relayouts"]
                 if GATED_NORM_SCOPE in (placed[n][0],
                                         owners.get(n, (None,))[0])
                 and re.search(rf"%{re.escape(n)} = \w+\[{B},{S},", text)]
    phases = {phase for n, (scope, phase, _) in placed.items()
              if scope == GATED_NORM_SCOPE}
    assert {"forward", "recompute", "backward"} <= phases, phases
    assert sum(n in found["mosaic_calls"]
               for n in found["scopes"][SSD_SCOPE]) == 2
    if norm == "kernels":
        assert sorted(placed[n][1] for n in calls) == [
            "backward", "forward", "recompute"], calls
        assert sorted(n.split(".")[0] for n in calls) == [
            "gated_norm_bwd", "gated_norm_fwd", "gated_norm_fwd"]
        assert relayouts == []
    else:
        assert calls == []
        assert bool(relayouts) == (G > 1), relayouts
