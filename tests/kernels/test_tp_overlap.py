"""Decomposed ring TP collective matmuls (ops/overlap.py): the overlapped
ag_matmul / matmul_rs / gated pair must match the GSPMD-reference einsum
arithmetic to dtype tolerance, forward AND backward, at tp in {2, 4} on the
8-device virtual mesh, in bf16 and f32 — and per-layer dispatch must fall
back (with a reason) exactly where the path cannot express the plan."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.ops.overlap import (
    layer_overlap_reason,
    make_ag_matmul,
    make_ag_matmul_pair,
    make_layer_matmuls,
    make_matmul_rs,
    plan_overlap_reasons,
)

pytestmark = [pytest.mark.kernels, pytest.mark.tp_overlap,
              pytest.mark.distributed]

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _mesh(cpu_devices, tp):
    arr = np.array(cpu_devices).reshape(8 // tp, tp)
    return Mesh(arr, ("dp", "tp")), ("dp",), ("tp",)


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32
                             ).astype(dtype)


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ag_matmul_fwd_bwd_parity(tp, dtype, cpu_devices):
    mesh, dp, tpa = _mesh(cpu_devices, tp)
    B, S, H, F = 4, 16, 8, 16
    x = _rand(1, (B, S, H), dtype)
    w = _rand(2, (H, F), dtype)
    ag = make_ag_matmul(mesh, dp, tpa)

    ref = lambda x, w: jnp.einsum("bsh,hf->bsf", x, w,
                                  preferred_element_type=jnp.float32)
    with mesh:
        y = jax.jit(ag)(x, w)
    assert y.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, w)),
                               **TOL[dtype])

    loss = lambda f: lambda x, w: jnp.sum(jnp.sin(f(x, w)))
    with mesh:
        gx, gw = jax.jit(jax.grad(loss(ag), argnums=(0, 1)))(x, w)
    rx, rw = jax.grad(loss(ref), argnums=(0, 1))(x, w)
    assert gx.dtype == dtype and gw.dtype == dtype
    np.testing.assert_allclose(np.asarray(gx, np.float32),
                               np.asarray(rx, np.float32), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(gw, np.float32),
                               np.asarray(rw, np.float32), **TOL[dtype])


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_rs_fwd_bwd_parity(tp, dtype, cpu_devices):
    mesh, dp, tpa = _mesh(cpu_devices, tp)
    B, S, F, H = 4, 16, 16, 8
    h = _rand(3, (B, S, F), dtype)
    w = _rand(4, (F, H), dtype)
    rs = make_matmul_rs(mesh, dp, tpa)

    ref = lambda h, w: jnp.einsum("bsf,fh->bsh", h, w,
                                  preferred_element_type=jnp.float32)
    with mesh:
        y = jax.jit(rs)(h, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref(h, w)),
                               **TOL[dtype])

    loss = lambda f: lambda h, w: jnp.sum(jnp.sin(f(h, w)))
    with mesh:
        gh, gw = jax.jit(jax.grad(loss(rs), argnums=(0, 1)))(h, w)
    rh, rw = jax.grad(loss(ref), argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(gh, np.float32),
                               np.asarray(rh, np.float32), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(gw, np.float32),
                               np.asarray(rw, np.float32), **TOL[dtype])


@pytest.mark.parametrize("tp", [2, 4])
def test_gated_pair_matches_fused_split(tp, cpu_devices):
    """fc1_pair(x, wg, wu) == split(fused fc1) halves, fwd + bwd."""
    mesh, dp, tpa = _mesh(cpu_devices, tp)
    B, S, H, F = 4, 16, 8, 16
    dtype = jnp.float32
    x = _rand(5, (B, S, H), dtype)
    w = _rand(6, (H, 2 * F), dtype)
    pair = make_ag_matmul_pair(mesh, dp, tpa)

    def ref(x, w):
        h = jnp.einsum("bsh,hf->bsf", x, w,
                       preferred_element_type=jnp.float32)
        return h[..., :F], h[..., F:]

    with mesh:
        g, u = jax.jit(lambda x, w: pair(x, w[:, :F], w[:, F:]))(x, w)
    rg, ru = ref(x, w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(rg), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(u), np.asarray(ru), **TOL[dtype])

    def loss(f):
        def inner(x, w):
            a, b = f(x, w)
            return jnp.sum(jnp.sin(a) * jnp.cos(b))
        return inner

    with mesh:
        gx, gw = jax.jit(jax.grad(
            loss(lambda x, w: pair(x, w[:, :F], w[:, F:])),
            argnums=(0, 1)))(x, w)
    rx, rw = jax.grad(loss(ref), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), **TOL[dtype])


def test_multi_axis_tp_ring(cpu_devices):
    """tp spread over TWO binary mesh axes (the mesh layer's tp4 = (d1, d2)
    assignment) rings over the flattened axis tuple."""
    arr = np.array(cpu_devices).reshape(2, 2, 2)
    mesh = Mesh(arr, ("d0", "d1", "d2"))
    B, S, H, F = 2, 8, 8, 16
    x = _rand(7, (B, S, H), jnp.float32)
    w = _rand(8, (H, F), jnp.float32)
    ag = make_ag_matmul(mesh, ("d0",), ("d1", "d2"))
    with mesh:
        y = jax.jit(ag)(x, w)
    ref = jnp.einsum("bsh,hf->bsf", x, w,
                     preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_layer_matmuls_keys(cpu_devices):
    mesh, dp, tpa = _mesh(cpu_devices, 2)
    mm = make_layer_matmuls(mesh, dp, tpa)
    assert set(mm) == {"qkv", "out", "fc1", "fc2", "fc1_pair"}
    assert mm["qkv"] is mm["fc1"]
    assert mm["out"] is mm["fc2"]


# ---------------------------------------------------------------------------
# fallback reasons
# ---------------------------------------------------------------------------


def _cfg(**kw):
    base = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                vocab_size=128, seq_length=16, max_position_embeddings=64,
                hidden_act="swiglu", normalization="rmsnorm",
                position_embedding_type="rope", tie_word_embeddings=False,
                add_bias_linear=False, make_vocab_size_divisible_by=1,
                ffn_hidden_size=128)
    base.update(kw)
    return ModelArgs(**base)


class _Shim:
    def __init__(self, ulysses=False, cp_axes=()):
        self.ulysses = ulysses
        self.cp_axes = cp_axes


def test_layer_overlap_reasons():
    cfg = _cfg()
    assert layer_overlap_reason(cfg, _Shim(), 2) is None
    assert "tp == 1" in layer_overlap_reason(cfg, _Shim(), 1)
    assert "ulysses" in layer_overlap_reason(cfg, _Shim(ulysses=True), 2)
    assert "cp layer" in layer_overlap_reason(
        cfg, _Shim(cp_axes=("d1",)), 2)
    # tp not dividing the sequence into ring chunks
    assert "divide the sequence" in layer_overlap_reason(
        _cfg(seq_length=6), _Shim(), 4)


def test_plan_overlap_reasons_from_hpc():
    from hetu_galvatron_tpu.core.args_schema import CoreArgs
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    cfg = _cfg()
    a = CoreArgs(model=cfg.model_dump())
    a.parallel.global_tp_deg = 2
    a.parallel.global_train_batch_size = 8
    hpc = get_hybrid_parallel_config(a, 8)
    rs = plan_overlap_reasons(cfg, hpc)
    assert [r for _, r in rs] == [None, None]

    a.parallel.global_tp_deg = 4
    a.parallel.use_ulysses = True
    hpc = get_hybrid_parallel_config(a, 8)
    rs = plan_overlap_reasons(cfg, hpc)
    assert all("ulysses" in r for _, r in rs)


def test_spmd_overrides_dispatch_and_fallback(cpu_devices):
    """tp_overlap_overrides: eligible layers get ``matmuls``; a non-dividing
    tp reports the reason instead."""
    from hetu_galvatron_tpu.core.args_schema import CoreArgs
    from hetu_galvatron_tpu.parallel.spmd import (
        layer_shardings,
        tp_overlap_overrides,
    )
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.runtime.mesh import build_mesh

    cfg = _cfg()
    a = CoreArgs(model=cfg.model_dump())
    a.parallel.global_tp_deg = 2
    a.parallel.global_train_batch_size = 8
    hpc = get_hybrid_parallel_config(a, 8)
    mesh = build_mesh(8, 1, devices=cpu_devices)
    per_layer, _ = layer_shardings(hpc, mesh)
    ov, fb = tp_overlap_overrides(per_layer, mesh, cfg)
    assert sorted(ov) == [0, 1] and not fb
    assert set(ov[0].matmuls) == {"qkv", "out", "fc1", "fc2", "fc1_pair"}

    bad = _cfg(seq_length=7, max_position_embeddings=8)
    ov, fb = tp_overlap_overrides(per_layer, mesh, bad)
    assert not ov and len(fb) == 2
    assert all("divide the sequence" in r for _, r in fb)
