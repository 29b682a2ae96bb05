"""A tensor-parallel layer's interior stays on its own shards
(parallel/spmd.py::interior_sharding): the tp2 x dp2 ZeRO-3 step computes
what the tp = 1 step computes, its compiled HLO moves no activation between
the two projections and reduces no full-size weight gradient over all four
devices, and a tp = 1 layer traces to the program it always traced to."""

import hashlib
import re

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.models.builder import init_causal_lm
from hetu_galvatron_tpu.parallel.spmd import (
    make_spmd_train_step,
    shard_params,
)
from hetu_galvatron_tpu.runtime.dataloader import make_batch
from hetu_galvatron_tpu.runtime.hybrid_config import get_hybrid_parallel_config
from hetu_galvatron_tpu.runtime.mesh import build_mesh
from tools.aot_hlo_report import is_collective, parse_hlo

pytestmark = [pytest.mark.parallel, pytest.mark.distributed]

# (vocabulary and positions sized so that no table has a layer matrix's shape)
_COMMON = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
               vocab_size=224, max_position_embeddings=32, seq_length=16,
               make_vocab_size_divisible_by=1, ffn_hidden_size=160)
CFGS = {
    "gqa_swiglu_rope": ModelArgs(
        num_key_value_heads=2, hidden_act="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", tie_word_embeddings=False,
        add_bias_linear=False, add_qkv_bias=False, **_COMMON),
    "mha_gelu_biases": ModelArgs(
        hidden_act="gelu", normalization="layernorm",
        position_embedding_type="learned", tie_word_embeddings=True,
        add_bias_linear=True, add_qkv_bias=True, **_COMMON),
    # a gated MLP WITH biases: the [2, F] view of bin
    "mha_geglu_biases": ModelArgs(
        hidden_act="geglu", normalization="layernorm",
        position_embedding_type="learned", tie_word_embeddings=True,
        add_bias_linear=True, add_qkv_bias=True, **_COMMON),
}
WORLD = 4
# vocab_tp=2: the embedding and the head leave the hidden state as the layers
# take it, so no all-to-all is owed at a boundary either (the four-chip cell's
# vtp1 plan owes three a microbatch)
TP2 = dict(global_tp_deg=2, vocab_tp=2, default_dp_type="zero3", chunks=2,
           global_checkpoint=1, global_train_batch_size=8)
TP1 = dict(default_dp_type="zero3", chunks=2, global_checkpoint=1,
           global_train_batch_size=8)


def _build(cfg, parallel, devices):
    args = CoreArgs(model=cfg.model_dump())
    for k, v in parallel.items():
        setattr(args.parallel, k, v)
    hpc = get_hybrid_parallel_config(args, WORLD)
    mesh = build_mesh(WORLD, 1, devices=devices[:WORLD])
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    # biases start at zero; give them values so that their layout matters
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.02 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    # plain SGD at rate 1: the step's update IS the gradient, leaf by leaf
    tx = optax.sgd(1.0)
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        cfg, hpc, mesh, axes, tx, params, compute_dtype=jnp.float32,
        donate=False)
    sp = shard_params(params, pspecs, mesh)
    data = np.random.RandomState(0).randint(0, 224, (8, cfg.seq_length + 1))
    batch = jax.device_put(jax.tree.map(jnp.asarray, make_batch(data)),
                           batch_shd)
    return step, sp, tx.init(sp), batch


@pytest.fixture(scope="module", params=list(CFGS))
def steps(request, cpu_devices):
    cfg = CFGS[request.param]
    out = {}
    for name, parallel in (("tp2", TP2), ("tp1", TP1)):
        step, sp, opt, batch = _build(cfg, parallel, cpu_devices)
        new_p, _, metrics = step(sp, opt, batch)
        out[name] = {
            "loss": float(metrics["loss"]),
            "grads": jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                                  sp, new_p),
            "hlo": step.lower(sp, opt, batch).compile().as_text()}
    return cfg, out


def test_tp2_step_equals_tp1_step(steps):
    """Loss and every gradient leaf, to the SPMD parity tests' tolerance
    (tests/core/test_parallel_spmd.py)."""
    _, out = steps
    assert abs(out["tp2"]["loss"] - out["tp1"]["loss"]) < 2e-5
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(out["tp1"]["grads"]),
            jax.tree.leaves(out["tp2"]["grads"])):
        assert np.abs(a).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=3e-4,
            err_msg=f"gradient of {jax.tree_util.keystr(path)}")


def _collectives(hlo):
    return [(comp, ins) for comp, instrs in parse_hlo(hlo)
            for ins in instrs if is_collective(ins["opcode"])]


def test_no_all_to_all_in_the_microbatch_loop(steps):
    """Before PR 28 the tp2 step held ten a microbatch, at ``split``,
    ``mul`` (RoPE's backward) and the gated product, forward, recomputed
    and backward. What is left is the batch's own relayout once a step
    (``jit(step)/reshape``, outside the loop)."""
    _, out = steps
    for name in ("tp2", "tp1"):
        assert [ins["op_name"] for comp, ins in _collectives(out[name]["hlo"])
                if ins["opcode"].startswith("all-to-all")
                and "/while/" in ins["op_name"]] == [], name


def test_no_full_size_weight_gradient_all_reduce(steps):
    """A layer's matrices are sharded four ways (tp x dp, ZeRO-3) and their
    gradients are reduced over dp as the tp shard: no all-reduce, over
    whichever devices, carries one at its full size. (Before PR 28 the
    interior was sequence-sharded, so every weight gradient came out full
    size on every device and was all-reduced so.)"""
    cfg, out = steps
    h, f = cfg.hidden_size, cfg.ffn_dim
    qkv = (cfg.num_attention_heads + 2 * cfg.kv_heads) * cfg.head_dim
    wide = 2 * f if M._is_gated(cfg.hidden_act) else f
    full = {f"[{a},{b}]" for a, b in ((h, qkv), (h, wide), (f, h), (h, h))}
    full |= {f"[{b},{a}]" for a, b in ((h, qkv), (h, wide), (f, h))}
    reduced = [ins for _, ins in _collectives(out["tp2"]["hlo"])
               if ins["opcode"].startswith("all-reduce")]
    assert reduced
    for ins in reduced:
        assert not full & set(re.findall(r"\[[\d,]+\]", ins["shape"])), ins


def test_view_layouts():
    """qkv_group_major and gate_up_pairs put beside each other what one
    shard needs: each key-value head with its q heads; gate with up."""
    cfg = CFGS["gqa_swiglu_rope"]
    hd, nq, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.kv_heads
    cols = jnp.arange((nq + 2 * nkv) * hd)
    gm = np.asarray(M.qkv_group_major(cols, cfg))
    g = nq // nkv
    assert gm.shape == (nkv, (g + 2) * hd)
    for i in range(nkv):
        np.testing.assert_array_equal(
            gm[i, :g * hd], np.arange(i * g * hd, (i + 1) * g * hd))
        np.testing.assert_array_equal(
            gm[i, g * hd:(g + 1) * hd], nq * hd + np.arange(i * hd, (i + 1) * hd))
        np.testing.assert_array_equal(
            gm[i, (g + 1) * hd:],
            (nq + nkv) * hd + np.arange(i * hd, (i + 1) * hd))
    pairs = np.asarray(M.gate_up_pairs(jnp.arange(2 * cfg.ffn_dim)))
    np.testing.assert_array_equal(pairs[0], np.arange(cfg.ffn_dim))
    np.testing.assert_array_equal(pairs[1], cfg.ffn_dim + np.arange(cfg.ffn_dim))


@pytest.mark.parametrize("name", list(CFGS))
def test_layer_on_the_views_equals_layer_on_the_stored(name):
    """One device, no mesh: a layer given a ``shard`` and the views
    computes what the layer given the stored leaves computes."""
    cfg = CFGS[name]
    p, _ = M.init_decoder_layer(jax.random.key(0), cfg)
    p = jax.tree.map(lambda x: x + 0.02 * jax.random.normal(
        jax.random.key(x.size), x.shape), p)
    x = jax.random.normal(jax.random.key(2), (2, 16, cfg.hidden_size))
    rope = (M.rope_cos_sin(16, cfg.head_dim, cfg.rope_theta)
            if cfg.position_embedding_type == "rope" else None)
    view = {**p, "attn": dict(p["attn"]), "mlp": dict(p["mlp"])}
    for k in ("wqkv", "bqkv"):
        if k in p["attn"]:
            view["attn"][k] = M.qkv_group_major(p["attn"][k], cfg)
    if M._is_gated(cfg.hidden_act):
        for k in ("win", "bin"):
            if k in p["mlp"]:
                view["mlp"][k] = M.gate_up_pairs(p["mlp"][k])
    kw = dict(rope=rope, compute_dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(M.apply_decoder_layer(
            view, x, cfg, ops=M.LayerOps(shard=lambda a, axis: a), **kw)),
        np.asarray(M.apply_decoder_layer(p, x, cfg, **kw)),
        rtol=1e-5, atol=1e-5)


# sha256 of the tp = 1 layer's jaxpr at commit 2fb9159 (PR 27), bf16
# compute, x [2, 16, 64], the configurations above: a layer with no
# ``shard`` and the stored leaves traces to that program, to the character
_TP1_JAXPR = {
    "gqa_swiglu_rope":
        "ff2cb4d88a1e03ed94975b317ed268baad5e1b7f5e0d16157fc6197d94d349dd",
    "mha_gelu_biases":
        "48c55864baa4274ad09e552a7db09a439b1976fb16696deebdddf1e3fda928b4",
    "mha_geglu_biases":
        "570dbc98b5c704956992e8d003c8f7b018f2eb991023926e20c7a7ad4923e8ac",
}


@pytest.mark.parametrize("name", list(CFGS))
def test_tp1_layer_traces_to_the_program_it_always_did(name):
    cfg = CFGS[name]
    p, _ = M.init_decoder_layer(jax.random.key(0), cfg)
    x = jnp.zeros((2, 16, cfg.hidden_size), jnp.bfloat16)
    rope = (M.rope_cos_sin(16, cfg.head_dim, cfg.rope_theta)
            if cfg.position_embedding_type == "rope" else None)
    jaxpr = str(jax.make_jaxpr(
        lambda p, x: M.apply_decoder_layer(p, x, cfg, rope=rope))(p, x))
    assert hashlib.sha256(jaxpr.encode()).hexdigest() == _TP1_JAXPR[name], \
        jaxpr
