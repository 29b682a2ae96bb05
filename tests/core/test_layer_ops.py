"""The seam between a plan and a block: ``modules.LayerOps`` (what a plan
swaps in a block), ``modules.MIXERS`` (which kind of block reads which of its
fields), the one block body, and ``parallel/spmd.py``, which fills a record
a layer (``attention_overrides``, ``tp_overlap_overrides``,
``interior_sharding``) and says how two records meet (``merge_ops``)."""

import json
import os
from dataclasses import fields

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.models import moe
from hetu_galvatron_tpu.parallel import spmd
from hetu_galvatron_tpu.runtime.mesh import LayerSharding, build_mesh

pytestmark = [pytest.mark.core]

FIELDS = [f.name for f in fields(M.LayerOps)]


def set_fields(ops):
    return list(ops.given())


# ---------------------------------------------------------------------------
# who reads what
# ---------------------------------------------------------------------------


def test_the_table_names_fields_the_record_has_and_keywords_the_leaves_take():
    import inspect

    for kind, row in M.MIXERS.items():
        assert set(row.ops.values()) <= set(FIELDS), kind
        takes = set(inspect.signature(row.apply).parameters)
        assert set(row.ops) <= takes, kind
        assert row.attends == ({"rope", "causal", "dropout_rng",
                                "segment_ids"} <= takes), kind
    assert [k for k, row in M.MIXERS.items() if row.attends] == [
        "full_attention", "latent_attention", "sliding_attention",
        "cross_attention"]
    # a kind that leaves or takes values of other blocks takes the keyword
    for kind, row in M.MIXERS.items():
        takes = set(inspect.signature(row.apply).parameters)
        assert "made" in takes or not row.leaves, kind
        assert ("shared" in takes) == bool(row.takes), kind


@pytest.mark.parametrize("kernels", [True, None])
@pytest.mark.parametrize("kind", list(M.MIXERS))
def test_a_layers_record_holds_what_its_kind_reads_and_no_more(kind, kernels):
    """``attention_overrides`` on the CPU mesh: where the kernels run (here:
    where a test says so) a layer gets the attention core if its kind
    attends and each kernel its row names; where they do not, nothing."""
    mesh = build_mesh(2, 1, devices=jax.devices()[:2])
    per_layer = [LayerSharding(dp_axes=("d0",), cp_axes=(), tp_axes=())] * 2
    got = spmd.attention_overrides(
        per_layer, mesh, use_flash=kernels, flash_interpret=True,
        mixers=[kind, "full_attention"], kernels=kernels)
    # what a plan alone never fills here: the projections' replacements
    want = [f for f in FIELDS if M.MIXERS[kind].reads(f)
            and f not in ("matmuls", "shard")] if kernels else []
    assert set_fields(got.get(0, M.LayerOps())) == want
    assert set_fields(got.get(1, M.LayerOps())) == (
        ["sdpa"] if kernels else [])


@pytest.mark.parametrize("kind", list(M.MIXERS))
def test_a_kind_is_handed_its_rows_fields_under_its_own_keywords(
        kind, monkeypatch):
    """``apply_mixer`` takes the record apart: the leaf sees the fields its
    row names, as the keywords it takes, and none of the others."""
    row, seen = M.MIXERS[kind], {}

    def leaf(p, h, cfg, **kwargs):
        seen.update(kwargs, p=p)
        return h

    monkeypatch.setitem(M.MIXERS, kind, row._replace(apply=leaf))
    full = M.LayerOps(**{f: f for f in FIELDS if f not in (
        ("matmuls", "shard") if row.uncut_reason else ())})
    # what earlier blocks left: a kind is handed the values its row names
    left = {name: name for name in ("memory", "keys", "values")}
    M.apply_mixer({row.key: "mine"}, jnp.zeros((1, 2, 4)), ModelArgs(), kind,
                  ops=full, compute_dtype=jnp.float32, shared=left)
    assert seen.pop("p") == "mine" and seen.pop(
        "compute_dtype") == jnp.float32
    assert seen.pop("shared", {}) == {name: name for name in row.takes}
    if row.attends:
        assert (seen.pop("rope"), seen.pop("causal"), seen.pop("dropout_rng"),
                seen.pop("segment_ids")) == (None, True, None, None)
    assert seen == {arg: field for arg, field in row.ops.items()
                    if getattr(full, field) is not None}
    # and an empty record leaves every leaf its own defaults
    seen.clear()
    M.apply_mixer({row.key: "mine"}, jnp.zeros((1, 2, 4)), ModelArgs(), kind,
                  shared=left)
    assert not set(seen) & set(row.ops)


# ---------------------------------------------------------------------------
# how two records meet
# ---------------------------------------------------------------------------


def _tp_layers(cpu_devices, n=2):
    mesh = build_mesh(4, 1, devices=cpu_devices[:4])
    sh = LayerSharding(dp_axes=("d0",), cp_axes=(), tp_axes=("d1",))
    return mesh, [sh] * n


def rule_field_by_field_the_callers_beats_the_plans(cpu_devices):
    got = spmd.merge_ops({0: M.LayerOps(sdpa="ring", conv="kernel")},
                         {0: M.LayerOps(sdpa="mine")})
    assert got == {0: M.LayerOps(sdpa="mine", conv="kernel")}


def rule_an_unset_field_of_the_callers_keeps_the_plans_core(cpu_devices):
    got = spmd.merge_ops({0: M.LayerOps(sdpa="ring", cross_sdpa="xla"),
                          1: M.LayerOps(sdpa="ulysses")},
                         {0: M.LayerOps(matmuls={"qkv": "mm"}),
                          2: M.LayerOps(shard="pin")})
    assert got == {0: M.LayerOps(sdpa="ring", cross_sdpa="xla",
                                 matmuls={"qkv": "mm"}),
                   1: M.LayerOps(sdpa="ulysses"), 2: M.LayerOps(shard="pin")}
    assert spmd.merge_ops({3: M.LayerOps(ssd="scan")}, None) == {
        3: M.LayerOps(ssd="scan")}


def rule_interior_sharding_skips_a_layer_whose_matmuls_were_replaced(
        cpu_devices):
    mesh, per_layer = _tp_layers(cpu_devices)
    cfg = ModelArgs(hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, vocab_size=64, seq_length=8)
    interior, view = spmd.interior_sharding(
        per_layer, mesh, cfg, {0: M.LayerOps(matmuls={"qkv": "mm"}),
                               1: M.LayerOps(sdpa="flash")})
    assert list(interior) == [1] and set_fields(interior[1]) == ["shard"]
    assert view is not None
    # met with what was there, the layer keeps its core beside the pin
    assert set_fields(spmd.merge_ops(interior, {1: M.LayerOps(
        sdpa="flash")})[1]) == ["sdpa", "shard"]
    # with every layer's matmuls replaced there is nothing to re-lay
    assert spmd.interior_sharding(
        per_layer, mesh, cfg,
        {i: M.LayerOps(matmuls={"qkv": "mm"}) for i in range(2)}) == (
            {}, None)


def rule_overlap_goes_under_the_plans_kernels_and_both_under_the_callers(
        cpu_devices):
    """``build_spmd_loss_fn``'s order: tp_overlap's matmuls, the plan's
    cores and kernels over them, the caller's over both, then the interior
    under all of it."""
    mesh, per_layer = _tp_layers(cpu_devices)
    cfg = ModelArgs(hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, vocab_size=64, seq_length=8,
                    max_position_embeddings=8)
    overlap, fallbacks = spmd.tp_overlap_overrides(per_layer, mesh, cfg)
    assert not fallbacks and [set_fields(o) for o in overlap.values()] == [
        ["matmuls"]] * 2
    plan = spmd.merge_ops(overlap, spmd.attention_overrides(
        per_layer, mesh, use_flash=True, flash_interpret=True))
    assert [set_fields(o) for o in plan.values()] == [["sdpa", "matmuls"]] * 2
    mine = spmd.merge_ops(plan, {0: M.LayerOps(sdpa="mine")})
    assert mine[0].sdpa == "mine" and mine[0].matmuls is overlap[0].matmuls
    assert mine[1] == plan[1]
    assert spmd.interior_sharding(per_layer, mesh, cfg, mine) == ({}, None)


@pytest.mark.parametrize("rule", [
    rule_field_by_field_the_callers_beats_the_plans,
    rule_an_unset_field_of_the_callers_keeps_the_plans_core,
    rule_interior_sharding_skips_a_layer_whose_matmuls_were_replaced,
    rule_overlap_goes_under_the_plans_kernels_and_both_under_the_callers,
], ids=lambda f: f.__name__[5:])
def test_precedence(rule, cpu_devices):
    rule(cpu_devices)


# ---------------------------------------------------------------------------
# the one block body computes what the two bodies computed
# ---------------------------------------------------------------------------

# "<mixer>/<ff>": the block's keys, leaves, parameters and their absolute
# sum at key 3, and of its output on x ~ N(0, 1) [2, 40, 32] at key 7 under
# hidden dropout at key 11 the sum, the absolute sum, four values and the
# auxiliary loss: computed at the parent commit 0fa5720 by the lines below,
# through modules.apply_decoder_layer (dense) and the second body that
# moe.apply_moe_decoder_layer then was (experts), f32
PARENTS = json.load(open(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "parents_blocks.json")))

BLOCK = dict(
    model_type="moe", hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, ffn_hidden_size=48,
    moe_ffn_hidden_size=16, vocab_size=64, max_position_embeddings=64,
    seq_length=40, hidden_act="swiglu", normalization="rmsnorm",
    layernorm_epsilon=1e-5, position_embedding_type="nope",
    add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, use_flash_attn=False,
    hidden_dropout=0.1, residual_multiplier=0.22,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_chunk_size=8,
    kda_num_heads=2, kda_head_dim=8, kda_conv_kernel=4, kda_chunk_size=32,
    q_lora_rank=None, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8,
    num_experts=8, num_shared_experts=1, moe_topk=2,
    moe_norm_topk_prob=True, moe_dispatcher="dropless",
    moe_aux_loss_coeff=0.01)


@pytest.mark.parametrize("kind", sorted(PARENTS))
def test_a_block_of_every_kind_is_the_parents_block(kind):
    mixer, ff = kind.split("/")
    assert mixer in M.MIXERS
    cfg = ModelArgs(**BLOCK)
    x = jax.random.normal(jax.random.key(7), (2, 40, 32), jnp.float32)
    init, apply = ((moe.init_moe_decoder_layer, moe.apply_moe_decoder_layer)
                   if ff == "experts" else
                   (M.init_decoder_layer, M.apply_decoder_layer))
    p, axes = init(jax.random.key(3), cfg, mixer)
    assert jax.tree.structure(p) == jax.tree.structure(
        axes, is_leaf=lambda a: isinstance(a, tuple))
    # (one program: op by op a block is hundreds of compiles)
    got = jax.jit(lambda p, x, key: apply(
        p, x, cfg, rope=None, compute_dtype=jnp.float32, dropout_rng=key,
        mixer=mixer))(p, x, jax.random.key(11))
    y, aux = (got[0], float(got[1])) if ff == "experts" else (got, 0.0)
    want = PARENTS[kind]
    leaves = jax.tree.leaves(p)
    assert (sorted(p), len(leaves), sum(a.size for a in leaves)) == (
        want["keys"], want["leaves"], want["parameters"])
    close = lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-6,
                                                    atol=2e-6)
    close(float(sum(jnp.sum(jnp.abs(a)) for a in leaves)),
          want["param_sum"])
    close(np.asarray(y)[0, -1, :4], want["first"])
    close(float(jnp.sum(jnp.abs(y))) / y.size, want["abs_sum"] / y.size)
    close(float(jnp.sum(y)) / y.size, want["sum"] / y.size)
    close(aux, want["aux"])
    assert ff == "dense" or got[2]     # the router's stats come back
