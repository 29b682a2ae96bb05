"""Laguna-S-2.1 (``laguna``): window and full attention blocks three to one
with query heads, a rotation and a gate a head of their own, a leading dense
block, then sigmoid-scored top-k experts beside a shared one on a held
share: the program against the benchmark's plain reference, the checkpoint
names, the published preset, the step's names and counters, and the
refusals. CPU, fp32 at ``highest``, tiny widths that keep the shape of the
thing."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs
from hetu_galvatron_tpu.core.arguments import load_config
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.models.builder import (
    causal_lm_loss,
    forward_causal_lm,
    init_causal_lm,
)
from hetu_galvatron_tpu.models.moe import apply_moe_mlp, init_moe_mlp
from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params, params_to_hf
from hetu_galvatron_tpu.runtime.dataloader import make_batch

pytestmark = [pytest.mark.model]

ZOO = os.path.join(os.path.dirname(M.__file__), "configs")
KINDS = ["full_attention", "sliding_attention", "sliding_attention",
         "sliding_attention", "full_attention"]
HEADS = [4, 6, 6, 6, 4]
ROPE = {
    "full_attention": {
        "rope_theta": 100.0, "rope_type": "yarn", "factor": 8,
        "original_max_position_embeddings": 8, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.2,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10.0,
                          "partial_rotary_factor": 1}}
# the cell's pattern at tiny widths: 4 and 6 query heads of 8 over 2
# key-value heads, a window of 4 in 16 positions, three window blocks to one
# full, a dense block 0, 8 experts at top-3 with a shared one
TINY = dict(
    model_type="moe", hidden_size=32, num_hidden_layers=5, layer_types=KINDS,
    num_dense_layers=1, num_attention_heads=4,
    num_attention_heads_per_layer=HEADS, num_key_value_heads=2,
    head_dim_override=8, sliding_window=4, gating="per-head",
    rope_parameters=ROPE, ffn_hidden_size=48, moe_ffn_hidden_size=16,
    vocab_size=64, max_position_embeddings=64, seq_length=16,
    hidden_act="swiglu", normalization="rmsnorm", layernorm_epsilon=1e-6,
    position_embedding_type="rope", tie_word_embeddings=False,
    add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, num_experts=8, num_shared_experts=1,
    moe_topk=3, moe_score_function="sigmoid", moe_norm_topk_prob=True,
    moe_norm_topk_eps=1e-20, moe_routed_scaling_factor=2.5,
    moe_hf_layout="laguna", moe_dispatcher="dropless",
    moe_aux_loss_coeff=0.0, use_flash_attn=False)

# the configuration's file as benchmark/reference/laguna.py reads it
REF_CFG = {
    "hidden_size": 32, "num_hidden_layers": 5, "layer_types": KINDS,
    "num_attention_heads_per_layer": HEADS, "num_key_value_heads": 2,
    "head_dim": 8, "sliding_window": 4, "gating": "per-head",
    "rope_parameters": ROPE, "intermediate_size": 48,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "rms_norm_eps": 1e-6, "mlp_only_layers": [0], "num_experts": 8,
    "num_routed_experts": 8, "first_expert_held": 0,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _family():
    from benchmark import reference

    return reference.load_family("laguna")


def _seeded(cfg, key=7):
    """Seeded random weights drawn so that each new equation matters: norm
    scales that are not all ones, q, k and v large enough that the scores
    are of order one (so that a window, a rotation and the key-value head a
    query reads all move the softmax), gate logits that range over (0, 1)."""
    params, _ = init_causal_lm(jax.random.key(key), cfg)

    def shake(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.key(len(name) + 13 * sum(map(ord, name)))
        if "norm" in name or "ln" in name:
            return x + 0.3 * jax.random.normal(k, x.shape)
        if "wqkv" in name:
            return 25.0 * x
        if "wg" in name:
            return 40.0 * x
        return x
    return jax.tree_util.tree_map_with_path(shake, params)


def _batch(seed=3, rows=2, seq=16):
    return jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(seed).randint(0, 64, (rows, seq + 1))))


# ---------------------------------------------------------------------------
# (a) the program against the plain reference, and the controls
# ---------------------------------------------------------------------------

# the program in bfloat16 (what the cell computes in) against the float32
# reference: the loss alone, of order 4.2, within bf16's eight bits
BF16_LOSS = 2e-2
CONTROLS = ["as_published", "as_published_bf16", "no_window", "no_gate", "head_counts_swapped",
            "one_rotation_for_both_kinds", "whole_head_rotated_in_a_full_block",
            "yarn_dropped", "shared_expert_left_out",
            "scaling_factor_left_out"]


@pytest.fixture(scope="module")
def program():
    """The program's side, made once: seeded weights, a batch, the weights
    under their public names, and its loss, gradients and logits."""
    with jax.default_matmul_precision("highest"):
        cfg = ModelArgs(**TINY)
        params, batch = _seeded(cfg), _batch()
        loss, grads = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.float32)))(params)
        logits = jax.jit(lambda p: forward_causal_lm(
            p, batch["tokens"], cfg, compute_dtype=jnp.float32))(params)
        weights = {k: jnp.asarray(v)
                   for k, v in params_to_hf(params, cfg).items()}
        loss_bf16 = jax.jit(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.bfloat16))(params)
        return dict(cfg=cfg, batch=batch, weights=weights, loss=float(loss),
                    loss_bf16=float(loss_bf16),
                    grads=params_to_hf(grads, cfg), logits=logits)


@pytest.mark.parametrize("case", CONTROLS)
def test_program_matches_plain_reference(case, program, monkeypatch):
    """Logits, loss and every gradient leaf of the program against
    ``benchmark/reference/laguna.py`` on seeded random weights through the
    exporter; the program's gradient tree goes through the same exporter
    and meets ``jax.grad`` of the reference's ``nll_sum``. Each control
    breaks one equation of the reference and FAILS the loss's tolerance."""
    ref = _family()
    batch, weights = program["batch"], program["weights"]
    ref_cfg = dict(REF_CFG)
    full, window = ROPE["full_attention"], ROPE["sliding_attention"]
    if case == "no_window":
        ref_cfg["sliding_window"] = None
    if case == "no_gate":
        ref_cfg["gating"] = None
    if case == "head_counts_swapped":
        # the key-value head a query head reads, by the OTHER kind's group
        # size (the projections' shapes leave the counts no other freedom)
        def other_groups(t, heads):
            group = {4: 3, 6: 2}[heads]
            return t[:, jnp.minimum(jnp.arange(heads) // group,
                                    t.shape[1] - 1)]
        monkeypatch.setattr(ref, "expand_kv", other_groups)
    if case == "one_rotation_for_both_kinds":
        ref_cfg["rope_parameters"] = {"full_attention": full,
                                      "sliding_attention": full}
    if case == "whole_head_rotated_in_a_full_block":
        ref_cfg["rope_parameters"] = {
            "full_attention": {**full, "partial_rotary_factor": 1},
            "sliding_attention": window}
    if case == "yarn_dropped":
        ref_cfg["rope_parameters"] = {
            "full_attention": {"rope_type": "default",
                               "rope_theta": full["rope_theta"],
                               "partial_rotary_factor": 0.5},
            "sliding_attention": window}
    if case == "shared_expert_left_out":
        experts = ref.experts
        monkeypatch.setattr(ref, "experts", lambda x, w, p, c: experts(
            x, w, p, c, shared=False))
    if case == "scaling_factor_left_out":
        ref_cfg["moe_routed_scaling_factor"] = 1.0

    def ref_loss(w):
        return ref.nll_sum(w, ref_cfg, batch["tokens"],
                           batch["labels"]) / batch["labels"].size
    # tolerance: both sides are fp32 at highest on the CPU and differ in
    # operation order only (one fused qkv product against three, grouped
    # against all-experts matmuls). The loss is of order 4.2
    if case == "as_published_bf16":
        want = float(jax.jit(ref_loss)(weights))
        assert abs(program["loss_bf16"] - want) < BF16_LOSS, (
            program["loss_bf16"], want)
        return
    if case != "as_published":
        want = float(jax.jit(ref_loss)(weights))
        assert abs(program["loss"] - want) > 2e-5, (case, want)
        return
    want, want_grads = jax.jit(jax.value_and_grad(ref_loss))(weights)
    assert abs(program["loss"] - float(want)) < 2e-5, float(want)
    np.testing.assert_allclose(
        program["logits"],
        jax.jit(lambda w: ref.logits(w, ref_cfg, batch["tokens"]))(weights),
        rtol=1e-4, atol=5e-6)
    got_grads = program["grads"]
    assert sorted(got_grads) == sorted(want_grads)
    for k in want_grads:
        scale = float(jnp.max(jnp.abs(want_grads[k])))
        np.testing.assert_allclose(
            got_grads[k], want_grads[k], rtol=5e-4,
            atol=1e-4 * scale + 1e-9, err_msg=k)


@pytest.mark.parametrize("shift", [0, 1])
def test_program_matches_reference_on_a_share(shift):
    """The same comparison where every expert layer holds experts [2, 6) of
    8 and the shared expert whole; told a range one expert further along,
    on the same weights, it fails."""
    ref = _family()
    cfg = ModelArgs(**{**TINY, "moe_held_experts": 4,
                       "moe_first_held_expert": 2})
    ref_cfg = {**REF_CFG, "num_experts": 4, "first_expert_held": 2}
    params, batch = _seeded(cfg), _batch(4, rows=1)
    weights = {k: jnp.asarray(v) for k, v in params_to_hf(params, cfg).items()}
    assert "model.layers.1.mlp.experts.2.gate_proj.weight" in weights
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in weights
    want = jax.jit(lambda w: ref.nll_sum(
        w, ref_cfg, batch["tokens"], batch["labels"]))(
            weights) / batch["labels"].size
    run_cfg = cfg.model_copy(update=dict(moe_first_held_expert=2 + shift))
    got = jax.jit(lambda p: causal_lm_loss(
        p, batch, run_cfg, compute_dtype=jnp.float32))(params)
    assert (abs(float(got) - float(want)) < 2e-5) == (shift == 0)


def test_bf16_compute_stays_near_the_fp32_reference():
    """The timed path's dtype: bf16 operands with fp32 accumulation, norms,
    softmax and gate logits, under per-layer remat as the cell runs it."""
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    exact = jax.jit(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32))(params)
    with jax.default_matmul_precision("default"):
        got, grads = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.bfloat16,
            remat_flags=[True] * 5)))(params)
    assert abs(float(got) - float(exact)) < 3e-2
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))


def test_the_flash_kernels_run_the_blocks_as_the_xla_core_does():
    """The stack with every attending block on the Pallas kernels
    (interpret mode; the window blocks through the banded loops) against
    the XLA core: loss and gradients."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa
    from functools import partial

    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    flash = partial(flash_sdpa, interpret=True, block_q=8, block_k=8)
    flash.supports_window = True
    ops = {i: M.LayerOps(sdpa=flash) for i in range(5)}
    want, want_g = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32)))(params)
    got, got_g = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32, layer_overrides=ops,
        remat_flags=[True] * 5)))(params)
    assert abs(float(got) - float(want)) < 2e-5
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        # (fp32, an online softmax against a whole one: 1.4e-5 at most)
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5)


# ---------------------------------------------------------------------------
# (b) the share ties to the model
# ---------------------------------------------------------------------------


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The tiny model's 8 experts held 2 at a time, as ep32 cuts the
    published 256 in shares of 8: the four shares' layer outputs, with the
    shared expert (which every chip computes alike) counted once, add up to
    what the uncut reference gives for the whole layer, and their routes to
    all T*K."""
    ref = _family()
    layer = ModelArgs(**TINY)
    p, _ = init_moe_mlp(jax.random.key(5), layer)
    x = jax.random.normal(jax.random.key(8), (2, 16, 32), jnp.float32)
    w = {"gate.weight": p["router"].T}
    gate, up = jnp.split(p["shared"]["win"], 2, axis=1)
    w.update({"shared_expert.gate_proj.weight": gate.T,
              "shared_expert.up_proj.weight": up.T,
              "shared_expert.down_proj.weight": p["shared"]["wout"].T})
    for e in range(8):
        gate, up = jnp.split(p["win"][e], 2, axis=1)
        w.update({f"experts.{e}.gate_proj.weight": gate.T,
                  f"experts.{e}.up_proj.weight": up.T,
                  f"experts.{e}.down_proj.weight": p["wout"][e].T})
    flat = x.reshape(-1, 32)
    whole = ref.experts(flat, w, "", REF_CFG).reshape(x.shape)
    shared = ref.experts(flat, w, "", REF_CFG, held=()).reshape(x.shape)
    total, rows = 0.0, 0.0
    for first in range(0, 8, 2):
        cfg = layer.model_copy(update=dict(moe_held_experts=2,
                                           moe_first_held_expert=first))
        share = {**p, "win": p["win"][first:first + 2],
                 "wout": p["wout"][first:first + 2]}
        y, _, stats = apply_moe_mlp(share, x, cfg, compute_dtype=jnp.float32)
        total, rows = total + y, rows + float(stats["rows_held"])
    assert rows == 2 * 16 * 3
    # tolerance: fp32, sums in another order
    np.testing.assert_allclose(total - 3 * shared, whole, rtol=1e-5,
                               atol=2e-6)


# ---------------------------------------------------------------------------
# (c) the checkpoint layout, the preset, the adapter
# ---------------------------------------------------------------------------


def test_round_trip_through_the_public_names():
    cfg = ModelArgs(**{**TINY, "moe_held_experts": 4,
                       "moe_first_held_expert": 4})
    params = _seeded(cfg)
    sd = params_to_hf(params, cfg)
    for name, shape in {
            "model.layers.0.self_attn.q_proj.weight": (4 * 8, 32),
            "model.layers.0.self_attn.g_proj.weight": (4, 32),
            "model.layers.0.self_attn.o_proj.weight": (32, 4 * 8),
            "model.layers.0.mlp.gate_proj.weight": (48, 32),
            "model.layers.1.self_attn.q_proj.weight": (6 * 8, 32),
            "model.layers.1.self_attn.k_proj.weight": (2 * 8, 32),
            "model.layers.1.self_attn.g_proj.weight": (6, 32),
            "model.layers.1.self_attn.o_proj.weight": (32, 6 * 8),
            "model.layers.1.mlp.gate.weight": (8, 32),
            "model.layers.1.mlp.shared_expert.down_proj.weight": (32, 16),
            "model.layers.1.mlp.experts.4.up_proj.weight": (16, 32),
            "model.layers.4.mlp.experts.7.down_proj.weight": (32, 16),
            "lm_head.weight": (64, 32)}.items():
        assert sd[name].shape == shape, name
    assert not any("shared_experts" in k or "expert_bias" in k for k in sd)
    back = hf_to_params(sd, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


def test_the_published_yaml_is_the_published_model():
    from benchmark import manifest
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    cfg = load_config(os.path.join(ZOO, "laguna-s-2.1.yaml")).model
    # the catalog's config.json, as the benchmark's configuration keeps it,
    # with the cuts taken back: the adapter reads the YAML's model out of it
    body = manifest.read_json(os.path.join(
        manifest.ROOT, "benchmark", "configs", "laguna-s-2.1-ep32.json"))
    published = {**{k: v for k, v in body.items()
                    if k not in ("reduced_from", "assumed", "deployment",
                                 "program", "reference", "source")},
                 **body["reduced_from"]}
    read = populate_model_args_from_hf(published).model_copy(update=dict(
        model_name=cfg.model_name, seq_length=cfg.seq_length))
    assert read.model_dump() == cfg.model_dump()
    kinds = cfg.block_kinds()
    assert len(kinds) == 48
    assert [i for i, (m, _) in enumerate(kinds)
            if m == "full_attention"] == list(range(0, 48, 4))
    assert {m for m, _ in kinds} == {"full_attention", "sliding_attention"}
    assert [ff for _, ff in kinds] == ["dense"] + ["experts"] * 47
    assert [cfg.block_heads(i) for i in range(5)] == [48, 72, 72, 72, 48]
    assert cfg.rope_of("full_attention") == (500000.0, {
        "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618}, 64)
    assert cfg.rope_of("sliding_attention") == (10000.0, None, 128)
    # the cell's share, as shapes alone: the count the file states, and
    # the published model's
    cut = cfg.model_copy(update=dict(
        num_hidden_layers=5, layer_types=KINDS,
        num_attention_heads_per_layer=[48, 72, 72, 72, 48],
        moe_held_experts=8, vocab_size=12544))

    def count(c, part=lambda t: t):
        shapes = jax.eval_shape(lambda k: init_causal_lm(k, c)[0],
                                jax.random.key(0))
        return sum(int(np.prod(a.shape))
                   for a in jax.tree.leaves(part(shapes)))

    assert count(cut) == 811_017_216
    assert f"{count(cut):,} parameters" in body["deployment"]
    assert count(cut, lambda t: t["layers"][0]["attn"]) == 44_187_648
    assert count(cut, lambda t: t["layers"][1]["attn"]) == 63_135_744
    assert count(cut, lambda t: t["layers"][1]["moe"]) == 85_721_088
    assert round(count(cfg) / 1e9, 2) == 117.56


def test_the_adapter_reads_a_laguna_config_and_refuses_what_is_not_written():
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    d = {"model_type": "laguna", "hidden_size": 32, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
         "intermediate_size": 48, "moe_intermediate_size": 16,
         "shared_expert_intermediate_size": 16, "vocab_size": 64,
         "num_experts": 8, "num_experts_per_tok": 3, "mlp_only_layers": [0],
         "layer_types": ["full_attention", "sliding_attention"],
         "num_attention_heads_per_layer": [4, 6], "sliding_window": 4,
         "gating": "per-head", "rope_parameters": ROPE,
         "moe_routed_scaling_factor": 2.5, "norm_topk_prob": True}
    cfg = populate_model_args_from_hf(d)
    assert cfg.layer_types == ["full_attention", "sliding_attention"]
    assert (cfg.sliding_window, cfg.gating, cfg.moe_hf_layout, cfg.moe_topk,
            cfg.num_shared_experts, cfg.num_dense_layers) == (
                4, "per-head", "laguna", 3, 1, 1)
    assert cfg.rope_parameters == ROPE
    with pytest.raises(NotImplementedError, match="softcapping"):
        populate_model_args_from_hf({**d,
                                     "moe_router_logit_softcapping": 30.0})
    with pytest.raises(NotImplementedError, match="leading dense blocks"):
        populate_model_args_from_hf({**d, "mlp_only_layers": [1]})
    with pytest.raises(NotImplementedError, match="gating"):
        populate_model_args_from_hf({**d, "gating": "per-channel"})
    with pytest.raises(NotImplementedError, match="layer_types"):
        populate_model_args_from_hf({**d, "layer_types": [
            "full_attention", "chunked_attention"]})
    # gemma-2/3 stay refused, for what is still not run and not for their
    # windows
    with pytest.raises(NotImplementedError,
                       match="sliding windows are not the obstacle"):
        populate_model_args_from_hf({"model_type": "gemma2"})


def test_the_schema_holds_the_published_keys_to_the_stack():
    with pytest.raises(ValueError, match="sliding_window"):
        ModelArgs(**{**TINY, "sliding_window": None})
    with pytest.raises(ValueError, match="names 4 blocks"):
        ModelArgs(**{**TINY, "num_attention_heads_per_layer": [4, 6, 6, 4]})
    with pytest.raises(ValueError, match="head_dim_override"):
        ModelArgs(**{**TINY, "head_dim_override": None})
    with pytest.raises(ValueError, match="no multiples"):
        ModelArgs(**{**TINY, "num_attention_heads_per_layer":
                     [4, 5, 6, 6, 4]})
    with pytest.raises(ValueError, match="rotation of its own"):
        ModelArgs(**{**TINY, "rope_parameters": {"kda": {}}})
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        ModelArgs(**{**TINY, "rope_parameters": {
            "full_attention": {"partial_rotary_factor": 0.3}}})
    # a model that states none of them is the model it was
    plain = ModelArgs(hidden_size=32, num_attention_heads=4)
    assert plain.for_block(0) is plain
    assert plain.rope_of("full_attention") == (10000.0, None, 8)


def test_todays_trees_are_untouched_by_the_new_keys():
    """An attention block without a gate draws the leaves it drew."""
    cfg = ModelArgs(**{**TINY, "gating": None})
    plain, _ = M.init_attention(jax.random.key(3), cfg)
    gated, _ = M.init_attention(jax.random.key(3), ModelArgs(**TINY))
    assert list(plain) == ["wqkv", "wo"]
    assert list(gated) == ["wqkv", "wo", "wg"]
    for leaf in plain:
        np.testing.assert_array_equal(np.asarray(plain[leaf]),
                                      np.asarray(gated[leaf]))


# ---------------------------------------------------------------------------
# (d) the step's names and counters
# ---------------------------------------------------------------------------


def test_the_step_names_the_new_parts():
    from hetu_galvatron_tpu.observability import trace_analysis

    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    hlo = jax.jit(jax.grad(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32,
        remat_flags=[True] * 5))).lower(params).compile().as_text()
    found = trace_analysis.step_hlo(hlo)
    scopes = {c[0] for c in found["map"]["instructions"].values()}
    assert {"attn/window_core", "attn/core", "attn/gate", "attn/rope",
            "attn/qkv_proj", "attn/out_proj", "mlp", "moe/experts",
            "head"} <= scopes
    assert {"attn/window_core", "attn/gate"} <= set(trace_analysis.SCOPES)


def test_the_rotations_tables_are_traced_in_one_order(monkeypatch):
    """The order a set of kinds iterates in is the process's hash seed's;
    the tables' order is the order of the program's constants, which the
    persistent compile cache keys on (the cell's second run compiled again
    every other time: chip runs, PR 72)."""
    from hetu_galvatron_tpu.models import builder

    seen = []
    table = builder.rope_table
    monkeypatch.setattr(builder, "rope_table", lambda cfg, S, m, *ids: (
        seen.append(m), table(cfg, S, m, *ids))[1])
    cfg = ModelArgs(**TINY)
    params = jax.eval_shape(
        lambda k: init_causal_lm(k, cfg)[0], jax.random.key(0))
    jax.eval_shape(lambda p: forward_causal_lm(
        p, _batch()["tokens"], cfg, compute_dtype=jnp.float32), params)
    assert [m for m in seen if m] == sorted(ROPE)


def test_the_flop_counts_take_a_band_and_a_blocks_own_heads():
    from hetu_galvatron_tpu.core.cost_model.cost import model_flops_per_token

    cfg = ModelArgs(**TINY)
    no_window = cfg.model_copy(update=dict(sliding_window=16))
    # three window blocks of 6 heads of 8: 4 of 16 keys a query
    saved = 3 * 3 * (2 * 2 * (16 - 4) * 6 * 8)
    assert model_flops_per_token(no_window) - model_flops_per_token(
        cfg) == saved
    even = cfg.model_copy(update=dict(num_attention_heads_per_layer=None))
    assert model_flops_per_token(cfg) > model_flops_per_token(even)


# ---------------------------------------------------------------------------
# (e) what cannot run it says why
# ---------------------------------------------------------------------------


def _plan(model=None, **parallel):
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    args = CoreArgs(model=ModelArgs(**{**TINY, **(model or {})}).model_dump())
    for k, v in parallel.items():
        setattr(args.parallel, k, v)
    args.parallel.global_train_batch_size = 8
    return get_hybrid_parallel_config(args, 4)


# a stack of full_attention blocks alone that states a gate, or heads of a
# block's own: no mixer kind tells it from llama
_GATED = dict(layer_types=None, sliding_window=None, rope_parameters=None,
              num_attention_heads_per_layer=None)
_OWN_HEADS = dict(layer_types=None, sliding_window=None, rope_parameters=None,
                  gating=None, num_attention_heads_per_layer=[4, 6, 6, 6, 4])


@pytest.mark.parametrize("model,parallel,said", [
    (None, dict(global_tp_deg=2), r"block 0 \(full_attention\).*tp=2"),
    (None, dict(global_cp_deg=2), r"block 0 \(full_attention\).*cp=2"),
    (None, dict(global_tp_deg=2, use_ulysses=True), "Ulysses"),
    (_GATED, dict(global_tp_deg=2), "gating=per-head.*tp=2"),
    (_OWN_HEADS, dict(global_cp_deg=2),
     "num_attention_heads_per_layer=.*cp=2"),
], ids=["tp2", "cp2", "ulysses", "gate_alone_tp2", "own_heads_cp2"])
def test_a_plan_that_cuts_heads_or_sequence_is_refused(model, parallel, said):
    with pytest.raises(ValueError, match=said):
        _plan(model, **parallel)
    assert _plan(model) is not None    # dp alone runs


def test_a_core_without_a_window_and_a_cut_projection_are_refused():
    from hetu_galvatron_tpu.analysis.eligibility import WINDOW_REASON

    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    x = jnp.zeros((1, 16, 32))

    def ring_like(q, k, v, *, causal=True):
        return q

    # a ring or Ulysses core takes no window
    with pytest.raises(NotImplementedError, match="ring and Ulysses"):
        M.apply_mixer(params["layers"][1], x, cfg.for_block(1),
                      "sliding_attention", ops=M.LayerOps(sdpa=ring_like))
    # the tp interior and the ring matmuls, of a window block and of a
    # gated full block
    with pytest.raises(NotImplementedError, match="window_plan_reason"):
        M.apply_mixer(params["layers"][1], x, cfg.for_block(1),
                      "sliding_attention",
                      ops=M.LayerOps(shard=lambda a, axis: a))
    with pytest.raises(NotImplementedError, match="window_plan_reason"):
        M.apply_mixer(params["layers"][0], x, cfg.for_block(0),
                      "full_attention",
                      ops=M.LayerOps(matmuls={"qkv": lambda a, w: a @ w}))
    assert "window_plan_reason" in WINDOW_REASON
    # an encoder stack has no causal span to take a window of
    with pytest.raises(NotImplementedError, match="causal span"):
        M.apply_mixer(params["layers"][1], x, cfg.for_block(1),
                      "sliding_attention", causal=False)


def test_other_engines_refuse_the_model_by_a_reason():
    from hetu_galvatron_tpu.analysis.eligibility import (
        MIXER_OVERLAP_REASON,
        WINDOW_REASON,
        compiled_unsupported_reason,
        mixed_stack_reason,
        plan_overlap_reasons,
    )
    from hetu_galvatron_tpu.models.generate import generate
    from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine
    from hetu_galvatron_tpu.serving.engine import ServingEngine
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        model_layer_configs,
    )

    cfg = ModelArgs(**TINY)
    said = mixed_stack_reason(cfg, "generate()")
    assert "3 x sliding_attention/experts" in said
    assert MIXER_OVERLAP_REASON["sliding_attention"] is WINDOW_REASON
    # tp_overlap: a window block keeps GSPMD's matmuls, by the reason
    reasons = dict(plan_overlap_reasons(
        cfg.model_copy(update=dict(num_experts=0)), _plan()))
    assert reasons[1] is WINDOW_REASON
    # the two pp > 1 engines
    with pytest.raises(NotImplementedError, match="run it at pp_deg=1"):
        PipelineEngine(cfg, None, None, None)
    assert "sliding_attention" in compiled_unsupported_reason(
        cfg, _plan(pp_deg=2, pipeline_type="pipedream_flush"))
    # the profiler and the search price one block shape
    with pytest.raises(NotImplementedError, match="sliding_attention"):
        model_layer_configs(cfg)
    # generate() and serving: a dense stack with a window, and a stack of
    # full blocks alone that states a gate or heads of its own
    dense = dict(model_type="llama", num_experts=0, num_shared_experts=0,
                 num_dense_layers=0)
    for model, match in (
            (dense, "3 x sliding_attention/dense"),
            ({**dense, **_GATED}, "gating=per-head"),
            ({**dense, **_OWN_HEADS}, "num_attention_heads_per_layer=")):
        c = ModelArgs(**{**TINY, **model})
        params, _ = init_causal_lm(jax.random.key(0), c)
        with pytest.raises(NotImplementedError, match=match):
            generate(params, jnp.zeros((1, 4), jnp.int32), c,
                     max_new_tokens=1)
        with pytest.raises(NotImplementedError, match=match):
            ServingEngine(params, c)
