"""Mellum2-12B-A2.5B (``mellum``): window and full attention blocks three to
one, the full block last of a period, a q/k norm a head, YaRN on the full
blocks alone, softmax top-k experts renormalised in every block: the program
against the benchmark's plain reference, the checkpoint names, the published
preset and the adapter's refusals. CPU, fp32 at ``highest``, tiny widths that
keep the shape of the thing."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.core.arguments import load_config
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.models.builder import (
    causal_lm_loss,
    forward_causal_lm,
    init_causal_lm,
)
from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params, params_to_hf
from hetu_galvatron_tpu.runtime.dataloader import make_batch
from hetu_galvatron_tpu.utils.hf_config_adapter import (
    populate_model_args_from_hf,
)

pytestmark = [pytest.mark.model]

ZOO = os.path.join(os.path.dirname(M.__file__), "configs")
KINDS = ["sliding_attention", "sliding_attention", "sliding_attention",
         "full_attention"]
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 100.0, "factor": 8,
        "original_max_position_embeddings": 8, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2},
    "sliding_attention": {"rope_type": "default", "rope_theta": 100.0}}
# the cell's pattern at tiny widths: a period of four blocks, 4 query heads
# of 8 over 2 key-value heads, a window of 8 in 32 positions, 8 experts at
# top-2
TINY = dict(
    model_type="moe", hf_layout="llama", hidden_size=32, num_hidden_layers=4,
    layer_types=KINDS, num_attention_heads=4, num_key_value_heads=2,
    head_dim_override=8, sliding_window=8, qk_norm=True,
    qk_norm_per_head=True, rope_parameters=ROPE, ffn_hidden_size=48,
    moe_ffn_hidden_size=16, vocab_size=64, max_position_embeddings=64,
    seq_length=32, hidden_act="swiglu", normalization="rmsnorm",
    layernorm_epsilon=1e-6, position_embedding_type="rope",
    tie_word_embeddings=False, add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, num_experts=8, moe_topk=2,
    moe_score_function="softmax", moe_norm_topk_prob=True,
    moe_hf_layout="olmoe", moe_dispatcher="dropless",
    moe_aux_loss_coeff=0.0, use_flash_attn=False)

# the configuration's file as benchmark/reference/mellum.py reads it
REF_CFG = {
    "hidden_size": 32, "num_hidden_layers": 4, "layer_types": KINDS,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "sliding_window": 8, "rope_parameters": ROPE, "intermediate_size": 48,
    "moe_intermediate_size": 16, "rms_norm_eps": 1e-6, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True}

# the published config.json, as the catalog has it
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": KINDS * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _family():
    from benchmark import reference

    return reference.load_family("mellum")


def _seeded(cfg, key=7):
    """Seeded random weights drawn so that each equation matters: norm
    scales (the q/k norms' too) that are not all ones, q, k and v large
    enough that the scores are of order one, router logits far enough apart
    that the renormalised weights differ from the plain ones, experts large
    enough that what they add moves the loss."""
    params, _ = init_causal_lm(jax.random.key(key), cfg)

    def shake(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.key(len(name) + 13 * sum(map(ord, name)))
        if "norm" in name or "ln" in name:
            return x + 0.3 * jax.random.normal(k, x.shape)
        if "wqkv" in name:
            return 25.0 * x
        if "router" in name:
            return 60.0 * x
        if "moe" in name:
            return 8.0 * x
        return x
    return jax.tree_util.tree_map_with_path(shake, params)


def _batch(seed=3, rows=2, seq=32):
    return jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(seed).randint(0, 64, (rows, seq + 1))))


# ---------------------------------------------------------------------------
# (a) the program against the plain reference, and the controls
# ---------------------------------------------------------------------------

# the program in bfloat16 (what the cell computes in) against the float32
# reference: the loss alone, of order 4.2, within bf16's eight bits
BF16_LOSS = 2e-2
CONTROLS = ["as_published", "as_published_bf16", "no_qk_norm", "no_renormalisation",
            "window_on_the_full_block", "no_window",
            "yarn_factor_on_the_window_blocks", "yarn_dropped",
            "one_expert_left_out"]


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
    ``benchmark/reference/mellum.py`` on seeded random weights through the
    exporter; the program's gradient tree goes through the same exporter
    and meets ``jax.grad`` of the reference's ``nll_sum``. Each control
    breaks one equation of the reference and FAILS the loss's tolerance."""
    ref = _family()
    batch, weights = program["batch"], program["weights"]
    ref_cfg = dict(REF_CFG)
    full, window = ROPE["full_attention"], ROPE["sliding_attention"]
    if case == "no_qk_norm":
        # a head's 8 values pass as they are; the blocks' norms stay
        rms = ref.rms_norm
        monkeypatch.setattr(ref, "rms_norm", lambda x, w, eps: (
            x if x.ndim == 4 else rms(x, w, eps)))
    if case == "no_renormalisation":
        ref_cfg["norm_topk_prob"] = False
    if case == "window_on_the_full_block":
        # (the full block keeps its own rotation: only its span changes)
        ref_cfg["layer_types"] = ["sliding_attention"] * 4
        monkeypatch.setattr(ref, "attention", _attention_with_kinds(
            ref, KINDS, ROPE))
    if case == "no_window":
        ref_cfg["sliding_window"] = None
    if case == "yarn_factor_on_the_window_blocks":
        ref_cfg["rope_parameters"] = {"full_attention": full,
                                      "sliding_attention": full}
    if case == "yarn_dropped":
        ref_cfg["rope_parameters"] = {"full_attention": window,
                                      "sliding_attention": window}
    if case == "one_expert_left_out":
        ref_cfg["experts_left_out"] = (5,)

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


def _attention_with_kinds(ref, kinds, rope):
    """The reference's attention with every block's SPAN the configuration's
    (so that a control can window the full block) and its rotation the
    published kind's."""
    attention = ref.attention

    def with_kinds(u, w, p, cfg, i):
        return attention(u, w, p, {**cfg, "rope_parameters": {
            cfg["layer_types"][i]: rope[kinds[i]]}}, i)
    return with_kinds


def test_bf16_compute_stays_near_the_fp32_reference():
    """The timed path's dtype: bf16 operands with fp32 accumulation, norms,
    softmax and router, under per-layer remat as the cell runs it."""
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    exact = jax.jit(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32))(params)
    with jax.default_matmul_precision("default"):
        got, grads = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.bfloat16,
            remat_flags=[True] * 4)))(params)
    assert abs(float(got) - float(exact)) < 3e-2
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))


def test_the_flash_kernels_run_the_blocks_as_the_xla_core_does():
    """The stack with every block on the Pallas kernels (interpret mode; the
    window blocks through the banded loops) against the XLA core."""
    from functools import partial

    from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa

    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    flash = partial(flash_sdpa, interpret=True, block_q=8, block_k=8)
    flash.supports_window = True
    ops = {i: M.LayerOps(sdpa=flash) for i in range(4)}
    want, want_g = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32)))(params)
    got, got_g = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32, layer_overrides=ops,
        remat_flags=[True] * 4)))(params)
    assert abs(float(got) - float(want)) < 2e-5
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5)


# ---------------------------------------------------------------------------
# (b) the checkpoint layout, the preset, the adapter
# ---------------------------------------------------------------------------


def test_round_trip_through_the_public_names():
    cfg = ModelArgs(**TINY)
    params = _seeded(cfg)
    sd = params_to_hf(params, cfg)
    for name, shape in {
            "model.layers.0.self_attn.q_proj.weight": (4 * 8, 32),
            "model.layers.0.self_attn.k_proj.weight": (2 * 8, 32),
            "model.layers.0.self_attn.q_norm.weight": (8,),
            "model.layers.0.self_attn.k_norm.weight": (8,),
            "model.layers.0.self_attn.o_proj.weight": (32, 4 * 8),
            "model.layers.3.mlp.gate.weight": (8, 32),
            "model.layers.3.mlp.experts.7.up_proj.weight": (16, 32),
            "model.layers.3.mlp.experts.7.down_proj.weight": (32, 16),
            "model.norm.weight": (32,),
            "lm_head.weight": (64, 32)}.items():
        assert sd[name].shape == shape, name
    assert not any(".mlp.gate_proj." in k or "shared" in k for k in sd)
    back = hf_to_params(sd, cfg)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves_with_path(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(pa))


def test_the_preset_is_the_published_config():
    """``mellum2-12b-a2.5b.yaml`` and the adapter's reading of the published
    ``config.json`` are the same model, and it counts what the card says."""
    preset = load_config(os.path.join(ZOO, "mellum2-12b-a2.5b.yaml"),
                         mode="train_dist").model
    adapted = populate_model_args_from_hf(PUBLISHED)
    a, b = preset.model_dump(), adapted.model_dump()
    differ = {k for k in a if a[k] != b[k]}
    assert differ == {"model_name", "seq_length"}, differ
    kinds = preset.block_kinds()
    assert [m for m, _ in kinds] == KINDS * 7
    assert {ff for _, ff in kinds} == {"experts"}
    # the full block is the last of a period, with YaRN; the window blocks
    # rotate plainly at the same base
    assert preset.rope_of("full_attention")[1]["factor"] == 16
    assert preset.rope_of("sliding_attention") == (500000.0, None, 128)
    shapes = jax.eval_shape(
        lambda k: init_causal_lm(k, preset)[0], jax.random.key(0))
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == 12_149_923_072
    block = sum(int(np.prod(x.shape))
                for x in jax.tree.leaves(shapes["layers"][0]))
    assert block == 21_233_664 + 4_608 + 256 + 147_456 + 64 * 6_193_152


@pytest.mark.parametrize("change, named", [
    ({"mlp_layer_types": ["dense"] + ["sparse"] * 27}, "mlp_layer_types"),
    ({"layer_types": ["linear_attention"] + (KINDS * 7)[1:]}, "layer_types"),
    ({"layer_types": KINDS * 6}, "layer_types"),
])
def test_the_adapter_refuses_by_name_what_it_does_not_read(change, named):
    with pytest.raises((NotImplementedError, ValueError), match=named):
        populate_model_args_from_hf({**PUBLISHED, **change})


def test_the_cost_models_count_of_the_cells_period():
    """``core/cost_model``'s FLOPs a token for the four blocks the cell runs
    (one period) at 8192: projections, a window block over its band of 1024
    and the full one over the sequence (the cost model's convention: every
    key of the span, not the causal half), the router, eight experts of
    three matrices, the head; times three for training."""
    from hetu_galvatron_tpu.core.cost_model.cost import model_flops_per_token

    preset = load_config(os.path.join(ZOO, "mellum2-12b-a2.5b.yaml"),
                         mode="train_dist").model
    cut = preset.model_copy(update=dict(
        num_hidden_layers=4, layer_types=preset.layer_types[:4]))
    h, nd, kd, S = 2304, 32 * 128, 4 * 128, 8192
    projections = 2 * h * nd + 2 * 2 * h * kd + 2 * nd * h
    cores = 3 * (2 * 2 * 1024 * nd) + 2 * 2 * S * nd
    experts = 2 * h * 64 + 8 * 3 * 2 * h * 896
    forward = 4 * projections + cores + 4 * experts + 2 * h * 98304
    assert model_flops_per_token(cut, S) == 3 * forward
