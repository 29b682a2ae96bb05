"""NemotronH (the causal tower of nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base,
``model_type`` ``nemotron_h``): blocks of ONE branch from one per-layer
description, Mamba-2 with several groups of B and C, attention without
positions, ungated squared-ReLU experts beside a shared one on a held share;
its checkpoint names and the adapter's reading of
``hybrid_override_pattern``; and the program against the benchmark's plain
reference (``benchmark/reference/nemotron_h.py``: the recurrence one
position at a time, every held expert on every token). CPU, fp32, tiny
widths."""

import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.analysis import eligibility
from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.core.arguments import load_config
from hetu_galvatron_tpu.core.cost_model.cost import model_flops_per_token
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.models import moe
from hetu_galvatron_tpu.models.builder import (
    causal_lm_loss,
    forward_causal_lm,
    init_causal_lm,
)
from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params, params_to_hf
from hetu_galvatron_tpu.runtime.dataloader import make_batch
from hetu_galvatron_tpu.utils.hf_config_adapter import (
    nemotron_h_layer_types,
    populate_model_args_from_hf,
)

pytestmark = [pytest.mark.model]

ZOO = os.path.join(os.path.dirname(M.__file__), "configs")
PATTERN = "MEM*E"
TYPES = ["mamba", "experts", "mamba", "full_attention", "experts"]
# a sequence of 21 and a chunk of 8: the chunk does not divide the sequence;
# 4 heads in 2 groups of B and C; 8 experts at top-2, all held
TINY = dict(
    model_type="moe", hf_layout="nemotron_h", hidden_size=32,
    num_hidden_layers=5, layer_types=TYPES, num_attention_heads=4,
    num_key_value_heads=2, head_dim_override=8, ffn_hidden_size=24,
    moe_ffn_hidden_size=24, vocab_size=64, max_position_embeddings=64,
    seq_length=21, hidden_act="relu2", normalization="rmsnorm",
    layernorm_epsilon=1e-5, position_embedding_type="nope",
    tie_word_embeddings=False, add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, use_flash_attn=False,
    mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2,
    mamba_d_conv=4, mamba_chunk_size=8, num_experts=8, num_shared_experts=2,
    moe_topk=2, moe_score_function="sigmoid", moe_norm_topk_prob=True,
    moe_norm_topk_eps=1e-20, moe_routed_scaling_factor=2.5,
    moe_router_enable_expert_bias=True, moe_dispatcher="dropless",
    moe_aux_loss_coeff=0.0)

# the configuration's file as benchmark/reference/nemotron_h.py reads it
REF_CFG = {
    "hidden_size": 32, "num_hidden_layers": 5,
    "hybrid_override_pattern": PATTERN, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 24,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "n_routed_experts": 8, "num_routed_experts": 8,
    "first_expert_held": 0, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "mamba_num_heads": 4, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "use_conv_bias": True, "vocab_size": 64}


def _family():
    from benchmark import reference

    return reference.load_family("nemotron_h")


def _seeded(cfg, key=7):
    """Seeded random weights with norm scales that are not all ones, a conv
    bias and a selection bias that are not zero, a ``D`` that is not one, q
    and k of order one and a state that outweighs the skip, so that a norm,
    a bias, the skip, a group or a rotation shows."""
    params, _ = init_causal_lm(jax.random.key(key), cfg)

    def shake(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.key(len(name) + 13 * sum(map(ord, name)))
        if "norm" in name or "ln" in name or name.endswith("['D']"):
            return x + 0.3 * jax.random.normal(k, x.shape)
        if "conv_bias" in name:
            return 0.2 * jax.random.normal(k, x.shape)
        if "expert_bias" in name:
            return 0.3 * jax.random.normal(k, x.shape)
        if "wqkv" in name:
            return 25.0 * x
        if "router" in name:
            return 20.0 * x
        if "mamba']['win" in name:
            return 8.0 * x
        if "['moe']" in name or "['mlp']" in name:
            return 6.0 * x
        if "dt_bias" in name:
            return 0.5 + 0.3 * jax.random.normal(k, x.shape)
        if "A_log" in name:
            return jnp.log(jax.random.uniform(k, x.shape, minval=0.05,
                                              maxval=0.3))
        return x
    return jax.tree_util.tree_map_with_path(shake, params)


def _batch(rows=2, seq=21, seed=3):
    return jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(seed).randint(0, 64, (rows, seq + 1))))


# ---------------------------------------------------------------------------
# (a) the per-layer description of a stack of one-branch blocks
# ---------------------------------------------------------------------------


def test_block_kinds_of_a_one_branch_stack():
    cfg = ModelArgs(**TINY)
    assert cfg.one_branch_blocks
    assert cfg.block_kinds() == (
        ("mamba", None), (None, "experts"), ("mamba", None),
        ("full_attention", None), (None, "experts"))
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    assert [sorted(lp) for lp in params["layers"]] == [
        ["ln1", "mamba"], ["ln1", "moe"], ["ln1", "mamba"],
        ["attn", "ln1"], ["ln1", "moe"]]
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))
    # the shared expert is num_shared_experts routed widths wide
    assert params["layers"][1]["moe"]["shared"]["win"].shape == (32, 48)
    assert params["layers"][1]["moe"]["win"].shape == (8, 32, 24)


def test_a_stack_without_feed_forward_entries_resolves_as_before():
    plain = ModelArgs(**{**TINY, "layer_types": [
        "mamba", "mamba", "full_attention", "mamba", "mamba"],
        "num_dense_layers": 1})
    assert not plain.one_branch_blocks
    assert plain.block_kinds() == (
        ("mamba", "dense"), ("mamba", "experts"),
        ("full_attention", "experts"), ("mamba", "experts"),
        ("mamba", "experts"))
    assert ModelArgs().block_kinds(2) == (("full_attention", "dense"),) * 2


def test_a_dense_block_of_its_own_and_several_streams():
    """``dense`` as a block: the MLP alone; over ``hc_mult`` streams a
    one-branch block holds one set of maps and runs through ``residual``."""
    cfg = ModelArgs(**{**TINY, "layer_types": [
        "mamba", "dense", "full_attention", "dense", "experts"],
        "hc_mult": 2, "hc_sinkhorn_iters": 2})
    params, _ = init_causal_lm(jax.random.key(1), cfg)
    assert [sorted(lp) for lp in params["layers"]][:2] == [
        ["hc1", "ln1", "mamba"], ["hc1", "ln1", "mlp"]]
    batch = _batch()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32)))(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["layers"][1]["mlp"]["wout"]).max()) > 0


@pytest.mark.parametrize("update,said", [
    (dict(num_experts=0), "model.num_experts is 0"),
    (dict(mamba_n_groups=3), "must divide the 4 heads"),
])
def test_the_description_is_refused_by_name(update, said):
    with pytest.raises(ValueError, match=said):
        ModelArgs(**{**TINY, **update})


def test_the_engines_that_take_one_kind_of_block_refuse_by_name():
    cfg = ModelArgs(**TINY)
    reason = eligibility.mixed_stack_reason(cfg, "the pipeline engine")
    assert "2 x mamba/-" in reason and "2 x -/experts" in reason
    assert "one branch" in reason
    # a one-branch stack of one kind of block is no attention-and-MLP stack
    alone = ModelArgs(**{**TINY, "layer_types": ["dense"] * 5})
    assert "5 x -/dense" in eligibility.mixed_stack_reason(alone, "x")
    from hetu_galvatron_tpu.core.args_schema import CoreArgs
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    # (a mamba block's plan takes no tp at all: mamba_plan_reason)
    cut = ModelArgs(**{**TINY, "layer_types": [
        "full_attention", "dense", "experts", "full_attention", "dense"]})
    args = CoreArgs(model=cut.model_dump())
    args.parallel.global_tp_deg = 2
    args.parallel.global_train_batch_size = 2
    hpc = get_hybrid_parallel_config(args, 2)
    reasons = dict(eligibility.plan_overlap_reasons(cut, hpc))
    assert reasons[0] == reasons[1] == eligibility.ONE_BRANCH_REASON
    assert reasons[2] == eligibility.MOE_REASON


def test_the_flop_count_adds_one_branch_a_block():
    cfg = ModelArgs(**TINY)
    h, s = 32, 21
    mamba = 2 * h * (32 + (32 + 2 * 2 * 16) + 4) + 2 * 32 * h + 4 * 32 * 16
    attn = (2 * h * 32 + 2 * 2 * h * 16 + 2 * 32 * h + 2 * 2 * s * 32)
    experts = 2 * h * 8 + (2 + 2) * 2 * 2 * h * 24
    head = 2 * h * 64
    assert model_flops_per_token(cfg) == 3.0 * (
        2 * mamba + attn + 2 * experts + head)


# ---------------------------------------------------------------------------
# (b) hybrid_override_pattern -> kinds -> exported names, and back
# ---------------------------------------------------------------------------

PUBLISHED = {
    "model_type": "nemotron_h", "hidden_size": 32, "num_hidden_layers": 5,
    "hybrid_override_pattern": PATTERN, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 24,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_group": 1,
    "topk_group": 1, "layer_norm_epsilon": 1e-5, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 8, "use_conv_bias": True,
    "mamba_proj_bias": False, "mlp_hidden_act": "relu2",
    "mamba_hidden_act": "silu", "attention_bias": False, "mlp_bias": False,
    "tie_word_embeddings": False, "vocab_size": 64,
    "max_position_embeddings": 64, "rope_theta": 10000}


def test_pattern_to_kinds_to_names_round_trip():
    assert nemotron_h_layer_types("M*-E") == [
        "mamba", "full_attention", "dense", "experts"]
    cfg = populate_model_args_from_hf(PUBLISHED).model_copy(update=dict(
        make_vocab_size_divisible_by=1, use_flash_attn=False,
        seq_length=21))
    want = ModelArgs(**TINY)
    assert cfg.model_dump(exclude={"model_name"}) == want.model_dump(
        exclude={"model_name"})
    params = _seeded(cfg)
    sd = params_to_hf(params, cfg)
    assert sd["backbone.layers.0.mixer.in_proj.weight"].shape == (
        2 * 32 + 2 * 2 * 16 + 4, 32)
    assert sd["backbone.layers.0.mixer.conv1d.weight"].shape == (96, 1, 4)
    assert sd["backbone.layers.3.mixer.k_proj.weight"].shape == (16, 32)
    assert sd["backbone.layers.1.mixer.shared_experts.up_proj.weight"
              ].shape == (48, 32)
    assert sorted(k.split("mixer.")[1] for k in sd
                  if k.startswith("backbone.layers.4.mixer.")
                  and "experts." not in k) == [
        "gate.e_score_correction_bias", "gate.weight"]
    assert {k for k in sd if "layers" not in k} == {
        "backbone.embeddings.weight", "backbone.norm_f.weight",
        "lm_head.weight"}
    back = hf_to_params(sd, cfg)
    flat, tree = jax.tree.flatten(params)
    flat_back, tree_back = jax.tree.flatten(back)
    assert tree == tree_back
    for a, b in zip(flat, flat_back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("key,value,said", [
    ("hybrid_override_pattern", "MXM*E", "['X']"),
    ("hybrid_override_pattern", None, "names no hybrid_override_pattern"),
    ("mlp_hidden_act", "silu", "mlp_hidden_act='silu'"),
    ("n_group", 2, "n_group=2"),
    ("moe_shared_expert_intermediate_size", 40, "no multiple"),
])
def test_the_adapter_refuses_by_name(key, value, said):
    with pytest.raises(NotImplementedError) as err:
        populate_model_args_from_hf({**PUBLISHED, key: value})
    assert said in str(err.value)


def test_the_other_layouts_refuse_a_one_branch_stack():
    cfg = ModelArgs(**{**TINY, "hf_layout": "llama"})
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    with pytest.raises(NotImplementedError, match="hf_layout=nemotron_h"):
        params_to_hf(params, cfg)


def test_mamba_proj_bias_stays_refused_by_name():
    cfg = ModelArgs(**{**TINY, "mamba_proj_bias": True})
    with pytest.raises(NotImplementedError, match="mamba_proj_bias"):
        init_causal_lm(jax.random.key(0), cfg)


def test_the_published_yaml_is_the_published_model():
    """The adapter reads the YAML's model out of the catalog's
    ``config.json`` (the benchmark's configuration with its cut taken
    back), and the cut's parameter count is the issue's arithmetic."""
    from benchmark import manifest

    cfg = load_config(os.path.join(
        ZOO, "nemotron-twotower-30b-a3b.yaml")).model
    body = manifest.read_json(os.path.join(
        manifest.ROOT, "benchmark", "configs",
        "nemotron-twotower-30b-a3b-ep16.json"))
    published = {**{k: v for k, v in body.items()
                    if not isinstance(v, (dict, list))
                    and k not in ("position_embedding_type", "source",
                                  "deployment")},
                 **body["reduced_from"]}
    read = populate_model_args_from_hf(published).model_copy(update=dict(
        model_name=cfg.model_name, seq_length=cfg.seq_length))
    assert read.model_dump() == cfg.model_dump()
    kinds = cfg.block_kinds()
    count = lambda kind: sum(kind in k for k in kinds)   # noqa: E731
    assert (count("mamba"), count("experts"), count("full_attention")) == (
        23, 23, 6)
    assert body["layer_types_as_run"] == cfg.layer_types[:9]
    cut = cfg.model_copy(update=dict(
        num_hidden_layers=9, layer_types=cfg.layer_types[:9],
        moe_held_experts=8, vocab_size=16384))
    tree = jax.eval_shape(lambda k: init_causal_lm(k, cut)[0],
                          jax.random.key(0))
    sizes = [sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
             for t in (tree["layers"][0], tree["layers"][1],
                       tree["layers"][5], tree)]
    assert sizes == [38_744_896, 100_125_440, 23_399_040, 666_963_456]


# ---------------------------------------------------------------------------
# (c) the program against the benchmark's plain reference, and the controls
# ---------------------------------------------------------------------------

BF16_LOSS = 3e-2
CONTROLS = ["as_published", "as_published_bf16", "relu_not_squared",
            "scaling_factor_left_out", "selection_bias_left_out",
            "shared_expert_left_out", "d_skip_left_out",
            "conv_bias_left_out", "groups_swapped", "rope_left_on",
            "one_block_fewer"]


def _with_leaf(params, block_key, path, fn):
    def edit(lp):
        if block_key not in lp:
            return lp
        node = dict(lp[block_key])
        if len(path) == 2:
            node[path[0]] = {**node[path[0]],
                             path[1]: fn(node[path[0]][path[1]])}
        else:
            node[path[0]] = fn(node[path[0]])
        return {**lp, block_key: node}
    return {**params, "layers": tuple(edit(lp) for lp in params["layers"])}


@pytest.mark.parametrize("case", CONTROLS)
def test_program_matches_plain_reference(case, monkeypatch):
    """Loss and gradients of the program (one-branch blocks, the chunked
    recurrence with groups folded into rows, sorted grouped matmuls) against
    ``benchmark/reference/nemotron_h.py`` (the recurrence a position at a
    time, every expert on every token) on seeded random weights through the
    exporter; the program's gradient tree goes through the same exporter
    and meets ``jax.grad`` of the reference's ``nll_sum``. Each control
    breaks one equation on one side and FAILS the comparison."""
    ref = _family()
    cfg = ModelArgs(**TINY)
    params = _seeded(cfg)
    batch = _batch()
    weights = {k: jnp.asarray(v)
               for k, v in params_to_hf(params, cfg).items()}
    run_cfg, run_params, ref_kw = cfg, params, {}
    zero = jnp.zeros_like
    if case == "relu_not_squared":
        run_cfg = cfg.model_copy(update=dict(hidden_act="relu"))
    if case == "scaling_factor_left_out":
        run_cfg = cfg.model_copy(update=dict(moe_routed_scaling_factor=1.0))
    if case == "selection_bias_left_out":
        run_params = _with_leaf(params, "moe", ("expert_bias",), zero)
    if case == "shared_expert_left_out":
        run_params = _with_leaf(params, "moe", ("shared", "wout"), zero)
    if case == "d_skip_left_out":
        run_params = _with_leaf(params, "mamba", ("D",), zero)
    if case == "conv_bias_left_out":
        run_params = _with_leaf(params, "mamba", ("conv_bias",), zero)
    if case == "groups_swapped":
        scan = ref.selective_scan
        monkeypatch.setattr(ref, "selective_scan", lambda x, dt, A, B, C:
                            scan(x, dt, A, B[:, :, ::-1], C[:, :, ::-1]))
    if case == "rope_left_on":
        run_cfg = cfg.model_copy(update=dict(position_embedding_type="rope"))
    if case == "one_block_fewer":
        ref_kw = {"layers": 4}

    def ref_loss(w):
        return ref.nll_sum(w, REF_CFG, batch["tokens"], batch["labels"],
                           **ref_kw) / batch["labels"].size
    # (one program a side: op by op they are some thousands of compiles)
    if case == "as_published_bf16":
        got = jax.jit(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.bfloat16))(params)
        assert abs(float(got) - float(jax.jit(ref_loss)(weights))) \
            < BF16_LOSS
        return
    def run_loss(p):
        return causal_lm_loss(p, batch, run_cfg, compute_dtype=jnp.float32)
    loss_close = abs(float(jax.jit(run_loss)(run_params))
                     - float(jax.jit(ref_loss)(weights))) < 2e-5
    if case != "as_published" and not loss_close:
        return   # told by the loss: the gradients' programs are not built
    want, want_grads = jax.jit(jax.value_and_grad(ref_loss))(weights)
    got, got_grads = jax.jit(jax.value_and_grad(run_loss))(run_params)
    got_grads = params_to_hf(got_grads, cfg)
    assert sorted(got_grads) == sorted(want_grads)
    # the selection bias is kept outside the gradient on the reference's
    # side; the program's carries its maintenance, -update
    apart = [k for k in want_grads if "e_score_correction_bias" not in k
             and not np.allclose(got_grads[k], want_grads[k],
                                 rtol=3e-4, atol=3e-6)]
    if case != "as_published":
        assert not loss_close or apart, (case, float(got), float(want))
        return
    assert loss_close, (float(got), float(want))
    assert not apart, apart


def test_logits_match_the_reference_position_by_position():
    ref = _family()
    cfg = ModelArgs(**TINY)
    params = _seeded(cfg)
    tokens = _batch()["tokens"]
    weights = {k: jnp.asarray(v)
               for k, v in params_to_hf(params, cfg).items()}
    got = jax.jit(lambda p: forward_causal_lm(
        p, tokens, cfg, compute_dtype=jnp.float32))(params)
    want = jax.jit(lambda w: ref.logits(w, REF_CFG, tokens))(weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """Sixteen chips' shares of an expert block (one of 16 routed experts
    each, the router and its top-2 whole) plus the shared expert ONCE are
    the uncut reference's block."""
    ref = _family()
    cfg = ModelArgs(**{**TINY, "num_experts": 16})
    key = jax.random.key(5)
    p, _ = moe.init_moe_mlp(key, cfg)
    p = {**p, "router": 20.0 * p["router"], "win": 6.0 * p["win"],
         "wout": 6.0 * p["wout"],
         "expert_bias": 0.3 * jax.random.normal(key, (16,)),
         "shared": jax.tree.map(lambda x: 6.0 * x, p["shared"])}
    x = jax.random.normal(jax.random.key(6), (2, 21, 32))
    total = jnp.zeros_like(x)
    for j in range(16):
        share = cfg.model_copy(update=dict(moe_held_experts=1,
                                           moe_first_held_expert=j))
        mine = {k: v for k, v in p.items() if k != "shared"}
        mine.update(win=p["win"][j:j + 1], wout=p["wout"][j:j + 1])
        y, _, stats = moe.apply_moe_mlp(mine, x, share,
                                        compute_dtype=jnp.float32)
        assert stats["held_tokens_per_expert"].shape == (1,)
        total = total + y
    total = total + M.apply_mlp(p["shared"], x, cfg,
                                compute_dtype=jnp.float32)
    block = {"ln1": {"scale": jnp.ones((32,))}, "moe": p}
    full = ModelArgs(**{**TINY, "num_experts": 16, "num_hidden_layers": 1,
                        "layer_types": ["experts"]})
    sd = params_to_hf({"embed": {"wte": jnp.zeros((64, 32))},
                       "layers": (block,),
                       "prenorm": {"scale": jnp.ones((32,))},
                       "head": {"whead": jnp.zeros((32, 64))}}, full)
    weights = {k: jnp.asarray(v) for k, v in sd.items()}
    want = ref.experts_block(
        x.reshape(-1, 32), weights, "backbone.layers.0.mixer.",
        {**REF_CFG, "n_routed_experts": 16, "num_routed_experts": 16})
    np.testing.assert_allclose(np.asarray(total).reshape(-1, 32),
                               np.asarray(want), rtol=2e-4, atol=2e-5)
    # and a share is what the reference's share is
    one = ref.routed_experts(
        x.reshape(-1, 32), weights, "backbone.layers.0.mixer.",
        {**REF_CFG, "n_routed_experts": 1, "num_routed_experts": 16,
         "first_expert_held": 15})
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 32),
                               np.asarray(one), rtol=2e-4, atol=2e-5)


def test_the_capacity_factor_sizes_the_first_chunk_and_drops_nothing():
    """``moe_capacity_factor`` is the first chunk of a held share over its
    expected routes (the cell's 2.0: 12,288 rows of 98,304 where the
    default's 1.25 provisions 7,680); what is over takes counted passes, so
    the result is the same at any factor."""
    assert moe.short_rows(98304, 8, 128) == 7680
    assert moe.short_rows(98304, 8, 128, 2.0) == 12288
    assert moe.layer_body(98304, 8, 128, 2.0) == "counted 12288/98304 +1536"
    cfg = ModelArgs(**{**TINY, "num_experts": 16, "moe_held_experts": 2})
    p, _ = moe.init_moe_mlp(jax.random.key(5), cfg)
    p = {**p, "router": 20.0 * p["router"]}
    x = jax.random.normal(jax.random.key(6), (2, 21, 32))
    got = {}
    for factor in (1.25, 2.0):
        y, _, stats = moe.apply_moe_mlp(
            p, x, cfg.model_copy(update=dict(moe_capacity_factor=factor)),
            compute_dtype=jnp.float32)
        got[factor] = (np.asarray(y), float(stats["rows_computed"])
                       - float(stats["overflow_chunks"])
                       * moe.overflow_rows(84, 2, 16))
    assert (got[1.25][1], got[2.0][1]) == (16, 24)
    np.testing.assert_allclose(got[1.25][0], got[2.0][0], rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("act", ["relu2", "swiglu"])
def test_widths_off_the_grouped_matmuls_tiles_are_padded_with_zeros(
        act, monkeypatch):
    """Experts of 136 columns over a hidden width of 160 run as 256 over 512
    (``moe._whole_tiles``: libtpu's grouped matmul is 2.4 times faster at
    2048 columns than at the model's 1856, and a third faster again over
    3072 than over its 2688) and give the same result and the same
    gradients: a zero column is a zero after every activation."""
    cfg = ModelArgs(**{**TINY, "hidden_size": 160, "hidden_act": act,
                       "moe_ffn_hidden_size": 136, "num_shared_experts": 0})
    p, _ = moe.init_moe_mlp(jax.random.key(0), cfg)
    p = {**p, "win": 5 * p["win"], "wout": 5 * p["wout"]}
    x = jax.random.normal(jax.random.key(1), (2, 16, 160))
    seen = []
    padded = moe._whole_tiles

    def watched(*operands):
        out = padded(*operands)
        seen.append(tuple(t.shape[1:] for t in out))
        return out

    def run():
        return jax.value_and_grad(lambda p, x: jnp.sum(jnp.tanh(
            moe.apply_moe_mlp(p, x, cfg, compute_dtype=jnp.float32)[0])),
            argnums=(0, 1))(p, x)

    monkeypatch.setattr(moe, "_whole_tiles", watched)
    got = run()
    assert set(seen) == {((512,), (512, 512 if act == "swiglu" else 256),
                          (256, 512))}
    monkeypatch.setattr(moe, "_whole_tiles", lambda *operands: operands)
    want = run()
    # (float32 sums over padded rows and columns, in another order)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)
    # the widths the benchmark's other cells run, and a test's under a lane
    # tile, are left alone
    for hidden, width in ((32, 24), (2048, 1408), (2304, 896), (3584, 1024)):
        w = (jnp.zeros((8, hidden)), jnp.zeros((2, hidden, width)),
             jnp.zeros((2, width, hidden)))
        assert all(a is b for a, b in zip(padded(*w), w)), (hidden, width)
    assert [t.shape for t in padded(
        jnp.zeros((8, 2688)), jnp.zeros((2, 2688, 1856)),
        jnp.zeros((2, 1856, 2688)))] == [(8, 3072), (2, 3072, 2048),
                                         (2, 2048, 3072)]


# ---------------------------------------------------------------------------
# (d) the grouped mixer against transformers' torch path
# ---------------------------------------------------------------------------


def test_grouped_mixer_is_transformers_mamba2_mixer():
    """``apply_mamba2`` with two groups of B and C against ``transformers``'
    ``Mamba2Mixer.torch_forward`` at ``n_groups`` 2, three chunks with the
    last one padded and a state that carries. ``Mamba2Mixer``'s own gated
    norm never groups, so what it hands its norm (the scan's output with
    the skip, and the gate) goes through ``Zamba2RMSNormGated`` at the
    group's size and the mixer's ``out_proj``: NemotronH's mixer, every
    piece ``transformers``' own. (``Zamba2MambaMixer.torch_forward`` itself
    is not the recurrence: its result moves with ``chunk_size``, 0.07 at
    these sizes between chunks of 8 and of 32, where ``Mamba2Mixer``'s
    moves by 2e-7.)"""
    torch = pytest.importorskip("torch")
    from transformers import Mamba2Config
    from transformers.models.mamba2.modeling_mamba2 import Mamba2Mixer
    from transformers.models.zamba2.modeling_zamba2 import (
        Zamba2RMSNormGated,
    )

    hf_cfg = Mamba2Config(
        hidden_size=32, num_heads=4, head_dim=8, state_size=16, n_groups=2,
        conv_kernel=4, expand=1, chunk_size=8, use_conv_bias=True,
        use_bias=False, layer_norm_epsilon=1e-5, num_hidden_layers=1,
        vocab_size=64)
    torch.manual_seed(0)
    mixer = Mamba2Mixer(hf_cfg, layer_idx=0).eval()
    with torch.no_grad():
        mixer.D.add_(0.3 * torch.randn_like(mixer.D))
        mixer.norm.weight.add_(0.3 * torch.randn_like(mixer.norm.weight))
        mixer.conv1d.bias.copy_(0.2 * torch.randn_like(mixer.conv1d.bias))
        mixer.dt_bias.copy_(-1.0 + torch.randn_like(mixer.dt_bias))
        mixer.A_log.copy_(torch.log(0.5 + 2.0 * torch.rand_like(mixer.A_log)))
        mixer.in_proj.weight.mul_(4.0)
    sd = {k: v.detach().numpy() for k, v in mixer.state_dict().items()}
    cfg = ModelArgs(**TINY)
    p = {"win": jnp.asarray(sd["in_proj.weight"].T),
         "taps": jnp.asarray(sd["conv1d.weight"][:, 0, :]),
         "conv_bias": jnp.asarray(sd["conv1d.bias"]),
         "dt_bias": jnp.asarray(sd["dt_bias"]),
         "A_log": jnp.asarray(sd["A_log"]), "D": jnp.asarray(sd["D"]),
         "norm": {"scale": jnp.asarray(sd["norm.weight"])},
         "wout": jnp.asarray(sd["out_proj.weight"].T)}
    x = np.random.RandomState(2).randn(2, 21, 32).astype(np.float32)
    handed = {}
    mixer.norm.register_forward_pre_hook(
        lambda _, args, kwargs: handed.update(args=args, kwargs=kwargs),
        with_kwargs=True)
    grouped = Zamba2RMSNormGated(32, group_size=16, eps=1e-5)
    with torch.no_grad():
        mixer.torch_forward(torch.tensor(x))
        grouped.weight.copy_(mixer.norm.weight)
        want = mixer.out_proj(grouped(*handed["args"], **handed["kwargs"])
                              ).numpy()
    got = M.apply_mamba2(p, jnp.asarray(x), cfg, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-4, atol=3e-5)
    # and the groups are read: B and C of the two groups exchanged is
    # another function
    win = p["win"]
    swapped = {**p, "win": jnp.concatenate(
        [win[:, :64], win[:, 80:96], win[:, 64:80], win[:, 112:128],
         win[:, 96:112], win[:, 128:]], axis=1)}
    off = M.apply_mamba2(swapped, jnp.asarray(x), cfg,
                         compute_dtype=jnp.float32)
    assert not np.allclose(np.asarray(off), want, rtol=3e-2, atol=3e-3)


def test_grouped_gated_norm_is_zamba2s():
    """The gated norm of ``apply_mamba2`` (the gate before the norm, the
    mean square a group of channels) and the reference's against
    ``Zamba2RMSNormGated`` at a group size of its own."""
    torch = pytest.importorskip("torch")
    from transformers.models.zamba2.modeling_zamba2 import (
        Zamba2RMSNormGated,
    )

    norm = Zamba2RMSNormGated(32, group_size=16, eps=1e-5)
    with torch.no_grad():
        norm.weight.add_(0.3 * torch.randn_like(norm.weight))
    y = np.random.RandomState(0).randn(2, 5, 32).astype(np.float32)
    z = np.random.RandomState(1).randn(2, 5, 32).astype(np.float32)
    with torch.no_grad():
        want = norm(torch.tensor(y), torch.tensor(z)).numpy()
    cfg = ModelArgs(**TINY)
    got = _gated_norm(jnp.asarray(y), jnp.asarray(z),
                      jnp.asarray(norm.weight.detach().numpy()), cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    one = _gated_norm(jnp.asarray(y), jnp.asarray(z),
                      jnp.asarray(norm.weight.detach().numpy()),
                      cfg.model_copy(update=dict(mamba_n_groups=1)))
    assert not np.allclose(np.asarray(one), want, rtol=1e-2, atol=1e-3)


def _gated_norm(y, z, scale, cfg):
    """The gated norm as ``apply_mamba2`` computes it, alone: a block whose
    ``win`` hands its input on as the gate ``z`` (the convolution's channels
    and dt see zeros), whose scan is made to answer ``y``, with no skip and
    the identity as ``wout``."""
    import unittest.mock as mock

    B, S, di = y.shape
    heads, cd = cfg.mamba_n_heads, cfg.mamba_conv_dim
    p = {"win": jnp.concatenate(
            [jnp.eye(di), jnp.zeros((di, cd + heads))], axis=1),
         "taps": jnp.zeros((cd, 4)), "dt_bias": jnp.zeros((heads,)),
         "A_log": jnp.zeros((heads,)), "D": jnp.zeros((heads,)),
         "norm": {"scale": scale}, "wout": jnp.eye(di)}
    with mock.patch.object(M, "ssd_chunked", lambda *a, **kw: y.reshape(
            B, S, heads, di // heads)):
        return M.apply_mamba2(p, z, cfg, compute_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# (e) one group stays the code that was
# ---------------------------------------------------------------------------

# sha256 of the jaxpr of ``apply_mamba2`` and of its gradient at one group,
# recorded from the parent of the PR that brought the groups (bdd9c10): one
# group is the program it was, so granite's numbers are what they were
ONE_GROUP_DIGESTS = {
    "forward":
    "521c3b125ac2eb32cd1e65f6b1174387e742318de5168943d2fcc12644071aff",
    "gradient":
    "80b3d08cdcaa30cd6dfca2264033366bec8e101af6405436985e31c4be247844"}


def _one_group_jaxprs():
    cfg = ModelArgs(hidden_size=32, num_hidden_layers=5, mamba_n_heads=4,
                    mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=8)
    p, _ = M.init_mamba2(jax.random.key(0), cfg)
    x = jnp.zeros((2, 21, 32))
    fwd = lambda p, x: M.apply_mamba2(  # noqa: E731
        p, x, cfg, compute_dtype=jnp.bfloat16)
    return {"forward": jax.make_jaxpr(fwd)(p, x),
            "gradient": jax.make_jaxpr(jax.grad(
                lambda p, x: jnp.sum(fwd(p, x).astype(jnp.float32)),
                argnums=(0, 1)))(p, x)}


@pytest.mark.parametrize("which", sorted(ONE_GROUP_DIGESTS))
def test_one_group_is_the_jaxpr_it_was(which):
    text = str(_one_group_jaxprs()[which])
    assert hashlib.sha256(text.encode()).hexdigest() == \
        ONE_GROUP_DIGESTS[which]
