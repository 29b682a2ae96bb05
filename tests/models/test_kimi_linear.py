"""Kimi-Linear-48B-A3B (``kimi_linear``): Kimi Delta Attention blocks (a
gated delta rule with a decay a channel, chunked in the program) three to
one with latent attention without positions and without a low-rank query,
a leading dense block, then sigmoid-routed experts beside a shared one on a
held share: the program against the benchmark's plain reference, which runs
the recurrence one position at a time, the checkpoint names, the published
preset, the step's names and counters, and the refusals. CPU, fp32 at
``highest``, tiny widths."""

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
KINDS = ["kda", "kda", "kda", "latent_attention", "kda"]
# the cell's pattern at tiny widths: a dense KDA block, two KDA expert
# blocks, a latent block, a KDA block; 8 routed experts (all held) and a
# shared one; a sequence of 40 in chunks of 32 (two sub-blocks of 16 a
# chunk, the last chunk padded)
TINY = dict(
    model_type="moe", hidden_size=32, num_hidden_layers=5, layer_types=KINDS,
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=4,
    kda_num_heads=2, kda_head_dim=8, kda_conv_kernel=4, kda_chunk_size=32,
    q_lora_rank=None, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, ffn_hidden_size=48, moe_ffn_hidden_size=16, vocab_size=64,
    max_position_embeddings=64, seq_length=40, hidden_act="swiglu",
    normalization="rmsnorm", layernorm_epsilon=1e-5,
    position_embedding_type="nope", tie_word_embeddings=False,
    add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, num_experts=8, num_shared_experts=1,
    moe_topk=2, moe_score_function="sigmoid", moe_norm_topk_prob=True,
    moe_norm_topk_eps=1e-20, moe_routed_scaling_factor=2.446,
    moe_router_enable_expert_bias=True, moe_hf_layout="kimi",
    moe_dispatcher="dropless", moe_aux_loss_coeff=0.0, use_flash_attn=False)

# the configuration's file as benchmark/reference/kimi_linear.py reads it
REF_CFG = {
    "hidden_size": 32, "num_hidden_layers": 5, "num_attention_heads": 4,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5],
                           "full_attn_layers": [4], "num_heads": 2,
                           "head_dim": 8, "short_conv_kernel_size": 4},
    "q_lora_rank": None, "mla_use_nope": True, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rms_norm_eps": 1e-5, "first_k_dense_replace": 1, "num_experts": 8,
    "num_routed_experts": 8, "first_expert_held": 0,
    "num_experts_per_token": 2, "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "num_shared_experts": 1}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _family():
    from benchmark import reference

    return reference.load_family("kimi_linear")


def _seeded(cfg, key=7):
    """Seeded random weights drawn so that each new equation matters: norm
    scales that are not all ones, a nonzero selection bias; in a KDA block
    ``A_log`` and ``dt_bias`` at the strongest decay the initialisation
    draws in half the channels, ``beta``'s and both gates' projections
    large enough that ``beta``, the decay and the output gate range over
    (0, 1), q, k and v large enough that the state and the delta term
    ``S~^T k`` are of order one; the latent block's up-projections large
    enough that its scores are."""
    params, _ = init_causal_lm(jax.random.key(key), cfg)

    def shake(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.key(len(name) + 13 * sum(map(ord, name)))
        if "expert_bias" in name:
            return 0.2 * jax.random.normal(k, x.shape)
        if "norm" in name or "ln" in name:
            return x + 0.3 * jax.random.normal(k, x.shape)
        if "A_log" in name:
            return jnp.full_like(x, np.log(16.0)).at[0].set(0.0)
        if "dt_bias" in name:
            # softplus^-1(0.1), the top of the initial range, and its foot
            strong = jnp.log(jnp.expm1(0.1))
            return jnp.where(jnp.arange(x.size) % 2 == 0, strong,
                             jnp.log(jnp.expm1(1e-3)))
        if "wf_b" in name or "wg_b" in name:
            return 10.0 * x
        if ("wlow" in name or "wqkv" in name or "wq'" in name
                or "wkv_b" in name):
            return 15.0 * x
        return x
    return jax.tree_util.tree_map_with_path(shake, params)


def _batch(seed=3, rows=2, seq=40):
    return jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(seed).randint(0, 64, (rows, seq + 1))))


# ---------------------------------------------------------------------------
# (a) the program against the plain reference, and the controls
# ---------------------------------------------------------------------------

# the program in bfloat16 (what the cell computes in) against the float32
# reference: the loss alone, of order 4.2, within bf16's eight bits
BF16_LOSS = 2e-2
CONTROLS = ["as_published", "as_published_bf16", "beta_left_out", "decay_left_out",
            "l2_norm_left_out", "convolution_left_out",
            "output_gate_left_out", "delta_term_left_out",
            "shared_expert_left_out", "latent_scale_of_another_width"]


def _delta_rule_without_the_delta_term(q, k, v, g, beta):
    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None] * state + (
            b_t[..., None] * k_t)[..., None] * v_t[..., None, :]
        return state, jnp.einsum("bnkv,bnk->bnv", state, q_t)

    zero = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], q.dtype)
    _, o = jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


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
    """Logits, loss and gradients of the program against
    ``benchmark/reference/kimi_linear.py`` (the recurrence one position at
    a time) on seeded random weights through the exporter, every new part
    on; the program's gradient tree goes through the same exporter and meets
    ``jax.grad`` of the reference's ``nll_sum``. Each control breaks one
    equation of the reference and FAILS the loss's tolerance."""
    ref = _family()
    batch, weights = program["batch"], program["weights"]
    rule = ref.delta_rule
    if case == "beta_left_out":
        monkeypatch.setattr(ref, "delta_rule", lambda q, k, v, g, b: rule(
            q, k, v, g, jnp.ones_like(b)))
    if case == "decay_left_out":
        monkeypatch.setattr(ref, "delta_rule", lambda q, k, v, g, b: rule(
            q, k, v, jnp.zeros_like(g), b))
    if case == "l2_norm_left_out":
        monkeypatch.setattr(ref, "unit", lambda x: x)
    if case == "convolution_left_out":
        monkeypatch.setattr(ref, "causal_conv", lambda u, kernel: u)
    if case == "output_gate_left_out":
        # sigmoid(0) = 1/2 in every channel: a constant gate
        weights = {k: 0 * v if k.endswith("g_b_proj.weight") else v
                   for k, v in weights.items()}
    if case == "delta_term_left_out":
        monkeypatch.setattr(ref, "delta_rule",
                            _delta_rule_without_the_delta_term)
    if case == "shared_expert_left_out":
        experts = ref.experts
        monkeypatch.setattr(ref, "experts", lambda x, w, p, c: experts(
            x, w, p, c, shared=False))
    if case == "latent_scale_of_another_width":
        # 1 / sqrt(qk_nope_head_dim) in place of 1 / sqrt(the whole head)
        attend = ref.causal_attention
        monkeypatch.setattr(ref, "causal_attention", lambda q, k, v: attend(
            q * (12 / 8) ** 0.5, k, v))

    def ref_loss(w):
        return ref.nll_sum(w, REF_CFG, batch["tokens"],
                           batch["labels"]) / batch["labels"].size
    # tolerance: both sides are fp32 at highest on the CPU and differ in
    # operation order only (chunks, sub-blocks and a triangular inverse
    # against one position at a time; grouped against all-experts matmuls).
    # The loss is of order 4.2, gradients up to 0.1
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
        jax.jit(lambda w: ref.logits(w, REF_CFG, batch["tokens"]))(weights),
        rtol=1e-4, atol=5e-6)
    got_grads = program["grads"]
    assert sorted(got_grads) == sorted(want_grads)
    for k in want_grads:
        if k.endswith("e_score_correction_bias"):
            # the bias takes no gradient of the loss; what the program's
            # tree carries on its path is the balance update (moe.py)
            assert float(jnp.max(jnp.abs(want_grads[k]))) == 0.0
            continue
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
    assert "model.layers.1.block_sparse_moe.experts.2.w1.weight" in weights
    assert "model.layers.1.block_sparse_moe.experts.0.w1.weight" \
        not in weights
    want = jax.jit(lambda w: ref.nll_sum(
        w, ref_cfg, batch["tokens"], batch["labels"]))(
            weights) / batch["labels"].size
    run_cfg = cfg.model_copy(update=dict(moe_first_held_expert=2 + shift))
    got = jax.jit(lambda p: causal_lm_loss(
        p, batch, run_cfg, compute_dtype=jnp.float32))(params)
    assert (abs(float(got) - float(want)) < 2e-5) == (shift == 0)


def test_bf16_compute_stays_near_the_fp32_reference():
    """The timed path's dtype: bf16 operands with fp32 accumulation, norms,
    decays, the inverse and the state. At this size the loss moves by
    rounding alone, under per-layer remat as the cell runs it."""
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    exact = jax.jit(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32))(params)
    with jax.default_matmul_precision("default"):
        got, grads = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.bfloat16,
            remat_flags=[True] * 5)))(params)
    assert abs(float(got) - float(exact)) < 2e-2
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))


# ---------------------------------------------------------------------------
# (b) the share ties to the model
# ---------------------------------------------------------------------------

LAYER = ModelArgs(**{**TINY, "num_experts": 64, "moe_topk": 8})


def test_the_32_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """64 tiny experts in 32 shares of 2, as ep32 cuts the published 256 in
    shares of 8: the 32 shares' layer outputs, with the shared expert
    (which every chip computes alike) counted once, add up to what the
    uncut reference gives for the whole layer, and their routes to all
    T*K."""
    ref = _family()
    p, _ = init_moe_mlp(jax.random.key(5), LAYER)
    p["expert_bias"] = 0.2 * jax.random.normal(jax.random.key(6), (64,))
    x = jax.random.normal(jax.random.key(8), (2, 16, 32), jnp.float32)
    w = {"gate.weight": p["router"].T,
         "gate.e_score_correction_bias": p["expert_bias"]}
    gate, up = jnp.split(p["shared"]["win"], 2, axis=1)
    w.update({"shared_experts.gate_proj.weight": gate.T,
              "shared_experts.up_proj.weight": up.T,
              "shared_experts.down_proj.weight": p["shared"]["wout"].T})
    for e in range(64):
        gate, up = jnp.split(p["win"][e], 2, axis=1)
        w.update({f"experts.{e}.w1.weight": gate.T,
                  f"experts.{e}.w3.weight": up.T,
                  f"experts.{e}.w2.weight": p["wout"][e].T})
    ref_cfg = {**REF_CFG, "num_routed_experts": 64, "num_experts": 64,
               "num_experts_per_token": 8}
    flat = x.reshape(-1, 32)
    whole = ref.experts(flat, w, "", ref_cfg).reshape(x.shape)
    shared = ref.experts(flat, w, "", ref_cfg, held=()).reshape(x.shape)
    total, rows = 0.0, 0.0
    for first in range(0, 64, 2):
        cfg = LAYER.model_copy(update=dict(moe_held_experts=2,
                                           moe_first_held_expert=first))
        share = {**p, "win": p["win"][first:first + 2],
                 "wout": p["wout"][first:first + 2]}
        y, _, stats = apply_moe_mlp(share, x, cfg, compute_dtype=jnp.float32)
        total, rows = total + y, rows + float(stats["rows_held"])
    assert rows == 2 * 16 * 8
    # tolerance: fp32, sums in another order
    np.testing.assert_allclose(total - 31 * shared, whole, rtol=1e-5,
                               atol=2e-6)


# ---------------------------------------------------------------------------
# (c) the checkpoint layout
# ---------------------------------------------------------------------------


def test_round_trip_through_the_public_names():
    cfg = ModelArgs(**{**TINY, "moe_held_experts": 4,
                       "moe_first_held_expert": 4})
    params = _seeded(cfg)
    sd = params_to_hf(params, cfg)
    for name, shape in {
            "model.layers.0.self_attn.q_proj.weight": (16, 32),
            "model.layers.0.self_attn.v_conv1d.weight": (16, 1, 4),
            "model.layers.0.self_attn.A_log": (1, 1, 2, 1),
            "model.layers.0.self_attn.dt_bias": (16,),
            "model.layers.0.self_attn.f_a_proj.weight": (8, 32),
            "model.layers.0.self_attn.f_b_proj.weight": (16, 8),
            "model.layers.0.self_attn.b_proj.weight": (2, 32),
            "model.layers.0.self_attn.g_a_proj.weight": (8, 32),
            "model.layers.0.self_attn.g_b_proj.weight": (16, 8),
            "model.layers.0.self_attn.o_norm.weight": (8,),
            "model.layers.0.self_attn.o_proj.weight": (32, 16),
            "model.layers.0.mlp.gate_proj.weight": (48, 32),
            # the latent block: one full-rank q_proj, no q_a / q_b
            "model.layers.3.self_attn.q_proj.weight": (4 * 12, 32),
            "model.layers.3.self_attn.kv_a_proj_with_mqa.weight": (8 + 4, 32),
            "model.layers.3.self_attn.kv_a_layernorm.weight": (8,),
            "model.layers.3.self_attn.kv_b_proj.weight": (4 * 16, 8),
            "model.layers.3.self_attn.o_proj.weight": (32, 4 * 8),
            "model.layers.1.block_sparse_moe.gate.weight": (8, 32),
            "model.layers.1.block_sparse_moe.gate.e_score_correction_bias":
                (8,),
            "model.layers.1.block_sparse_moe.shared_experts.down_proj.weight":
                (32, 16),
            "model.layers.1.block_sparse_moe.experts.4.w3.weight": (16, 32),
            "model.layers.4.block_sparse_moe.experts.7.w2.weight": (32, 16),
            "lm_head.weight": (64, 32)}.items():
        assert sd[name].shape == shape, name
    assert not any("q_a_proj" in k or "q_a_layernorm" in k for k in sd)
    back = hf_to_params(sd, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
    # a model without positions stores the latent block's columns as they
    # are; q, k and v lie side by side in wqkv and in the taps
    wq = np.asarray(params["layers"][3]["attn"]["wq"])
    np.testing.assert_array_equal(
        sd["model.layers.3.self_attn.q_proj.weight"].T, wq)
    kp = params["layers"][0]["kda"]
    np.testing.assert_array_equal(
        sd["model.layers.0.self_attn.k_proj.weight"].T,
        np.asarray(kp["wqkv"])[:, 16:32])
    np.testing.assert_array_equal(
        sd["model.layers.0.self_attn.k_conv1d.weight"][:, 0, :],
        np.asarray(kp["taps"])[16:32])
    np.testing.assert_array_equal(
        sd["model.layers.0.self_attn.b_proj.weight"].T,
        np.asarray(kp["wlow"])[:, 16:])


def test_the_published_yaml_is_the_published_model():
    from benchmark import manifest
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    cfg = load_config(os.path.join(ZOO, "kimi-linear-48b-a3b.yaml")).model
    # the catalog's config.json, as the benchmark's configuration keeps it,
    # with the cuts taken back: the adapter reads the YAML's model out of it
    body = manifest.read_json(os.path.join(
        manifest.ROOT, "benchmark", "configs",
        "kimi-linear-48b-a3b-ep32.json"))
    published = {**{k: v for k, v in body.items()
                    if not isinstance(v, (dict, list))},
                 **body["reduced_from"]}
    read = populate_model_args_from_hf(published).model_copy(update=dict(
        model_name=cfg.model_name, seq_length=cfg.seq_length))
    assert read.model_dump() == cfg.model_dump()
    kinds = cfg.block_kinds()
    assert len(kinds) == 27
    assert [i + 1 for i, (m, _) in enumerate(kinds)
            if m == "latent_attention"] == [4, 8, 12, 16, 20, 24, 27]
    assert {m for m, _ in kinds} == {"kda", "latent_attention"}
    assert [ff for _, ff in kinds] == ["dense"] + ["experts"] * 26
    assert (cfg.qk_head_dim, cfg.v_head_dim, cfg.kda_inner, cfg.q_lora_rank,
            cfg.position_embedding_type) == (192, 128, 4096, None, "nope")
    assert M.latent_softmax_scale(cfg) == pytest.approx(192 ** -0.5)
    # the cell's share, as shapes alone: the count the file states
    cut = cfg.model_copy(update=dict(
        num_hidden_layers=5, layer_types=KINDS, moe_held_experts=8,
        vocab_size=20480))
    shapes = jax.eval_shape(lambda k: init_causal_lm(k, cut)[0],
                            jax.random.key(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 602_434_432
    assert f"{count:,} parameters" in body["deployment"]
    kda = sum(int(np.prod(a.shape))
              for a in jax.tree.leaves(shapes["layers"][0]["kda"]))
    latent = sum(int(np.prod(a.shape))
                 for a in jax.tree.leaves(shapes["layers"][3]["attn"]))
    assert (kda, latent) == (39_514_272, 29_114_880)


def test_the_adapter_refuses_what_is_not_written():
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    d = {"model_type": "kimi_linear", "hidden_size": 32,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "intermediate_size": 48, "moe_intermediate_size": 16,
         "vocab_size": 64, "kv_lora_rank": 8, "q_lora_rank": None,
         "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
         "mla_use_nope": True, "num_experts": 8, "num_experts_per_token": 2,
         "linear_attn_config": {"kda_layers": [1], "full_attn_layers": [2],
                                "num_heads": 2, "head_dim": 8,
                                "short_conv_kernel_size": 4}}
    cfg = populate_model_args_from_hf(d)
    assert cfg.layer_types == ["kda", "latent_attention"]
    assert (cfg.moe_topk, cfg.moe_hf_layout) == (2, "kimi")
    with pytest.raises(NotImplementedError, match="group-limited"):
        populate_model_args_from_hf({**d, "num_expert_group": 8,
                                     "topk_group": 4})
    with pytest.raises(NotImplementedError, match="mla_use_nope"):
        populate_model_args_from_hf({**d, "mla_use_nope": False})
    with pytest.raises(ValueError, match="exactly once"):
        populate_model_args_from_hf({**d, "linear_attn_config": {
            **d["linear_attn_config"], "full_attn_layers": [1, 2]}})


def test_todays_trees_are_untouched_by_the_new_keys():
    """A latent block with ``q_lora_rank`` set draws the leaves it drew, and
    without it the others are the same draws beside ``wq``."""
    cfg = ModelArgs(**{**TINY, "q_lora_rank": 12})
    low, _ = M.init_latent_attention(jax.random.key(3), cfg)
    full, _ = M.init_latent_attention(jax.random.key(3), ModelArgs(**TINY))
    assert list(low) == ["wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                         "wkv_b", "wo"]
    assert list(full) == ["wq", "wkv_a", "kv_norm", "wkv_b", "wo"]
    for leaf in ("wkv_a", "wkv_b", "wo"):
        np.testing.assert_array_equal(np.asarray(low[leaf]),
                                      np.asarray(full[leaf]))
    # ... and the first draw is what q_a was drawn from
    k1 = jax.random.split(jax.random.key(3), 5)[0]
    np.testing.assert_array_equal(
        np.asarray(low["wq_a"]), np.asarray(M._normal(k1, (32, 12), 0.02)))


# ---------------------------------------------------------------------------
# (d) the step's names and counters
# ---------------------------------------------------------------------------


def test_the_step_names_the_new_parts_and_counts_its_loops():
    """The compiled step's HLO carries the six ``mixer/kda/*`` scopes and
    the latent block's three, and the loops of the forward pass under
    ``mixer/kda/scan`` say how many blocks run the mixer and over how many
    chunks."""
    from hetu_galvatron_tpu.observability import trace_analysis

    cfg = ModelArgs(**{**TINY, "kda_chunk_size": 8})
    params, batch = _seeded(cfg), _batch()
    hlo = jax.jit(jax.grad(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32,
        remat_flags=[True] * 5))).lower(params).compile().as_text()
    found = trace_analysis.step_hlo(hlo)
    scopes = {c[0] for c in found["map"]["instructions"].values()}
    assert set(trace_analysis.MIXER_SCOPES["kda"]) <= scopes
    assert {"attn/latent_proj", "attn/core", "attn/out_proj", "mlp",
            "moe/experts", "head"} <= scopes
    assert "attn/rope" not in scopes     # a model without positions
    assert all(found["scopes"][s] for s in trace_analysis.MIXER_SCOPES["kda"])
    # 40 positions in chunks of 8
    assert trace_analysis.kda_loops(hlo) == {"blocks": 4, "chunks": 5}
    assert trace_analysis.kda_loops("") == {"blocks": 0, "chunks": 0}
    # and no kernel: what the step report's ``kda/mosaic_calls`` says here
    assert trace_analysis.kda_kernel_calls(hlo) == {
        "mosaic_calls": 0, "blocks": 0, "chunk": 0}


def test_the_loops_are_counted_through_their_groups(monkeypatch):
    """Where a sequence's chunks are taken in several groups the recurrence
    is a loop in a loop, and the count is their product."""
    from hetu_galvatron_tpu.observability import trace_analysis

    # two chunks of 8 positions, 2 heads of 8 a group
    monkeypatch.setattr(M, "KDA_PAIR_BYTES", 2 * 2 * 2 * 8 * 8 * 8 * 4)
    cfg = ModelArgs(**{**TINY, "kda_chunk_size": 8, "seq_length": 48})
    params, batch = _seeded(cfg), _batch(seq=48)
    assert M.kda_chunks_a_group(2, 6, 2, 8, 8) == 2
    hlo = jax.jit(jax.grad(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32,
        remat_flags=[True] * 5))).lower(params).compile().as_text()
    assert trace_analysis.kda_loops(hlo) == {"blocks": 4, "chunks": 6}


def test_a_loop_without_a_stated_count_is_read_by_its_stacked_operands():
    """XLA:TPU's text gives a ``while`` no ``known_trip_count``: the loop's
    length is the leading dimension its stacked operands share (the lines
    are the cell's own, shortened: 16 groups of 8 chunks, a carried state
    of one array)."""
    from hetu_galvatron_tpu.observability import trace_analysis

    name = "jit(step)/jvp(mixer/kda)/scan/closed_call/while"
    hlo = f"""%groups_body.1 (p: (s32[], f32[1,32,128,128])) -> (s32[]) {{
  %while.2 = (s32[]{{:T(128)}}, f32[1,32,128,128]{{3,2,1,0}}, f32[8,1,32,64,128]{{4,3,2,1,0}}, bf16[8,1,32,64,64]{{4,3,2,1,0}}, bf16[8,1,32,64,128]{{4,2,3,0,1}}) while(%tuple.2), condition=%cond.2, body=%chunks_body.2, metadata={{op_name="{name}/body/closed_call/checkpoint/closed_call/while"}}
}}
%step.3 (p: (s32[])) -> (s32[]) {{
  %while.1 = (s32[]{{:T(128)}}, f32[1,32,128,128]{{3,2,1,0}}, f32[16,8,1,32,64,128]{{5,4,3,2,1,0}}, bf16[16,1,8,32,64,128]{{5,4,3,2,1,0}}) while(%tuple.1), condition=%cond.1, body=%groups_body.1, metadata={{op_name="{name}"}}
  %while.9 = (s32[]{{:T(128)}}, f32[16,1,8,32,64,128]{{5,4,3,2,1,0}}) while(%tuple.9), condition=%cond.9, body=%other.9, metadata={{op_name="jit(step)/transpose(jvp(mixer/kda))/scan/while"}}
}}
"""
    assert trace_analysis.kda_loops(hlo) == {"blocks": 1, "chunks": 128}


def test_the_kernel_form_is_read_from_its_calls():
    """Where the recurrence runs in the kernels of ``ops/pallas/kda.py`` the
    compiled step has no loop under ``mixer/kda/scan``: the blocks are the
    forward kernels of the forward pass, the chunk the rows of such a
    call's fifth operand (``beta`` as columns), and ``mosaic_calls``
    every Mosaic call under the scope: three a block under per-layer remat
    (the lines are the cell's own, shortened; a flash call beside them is
    no part of the count)."""
    from hetu_galvatron_tpu.observability import trace_analysis

    shapes = ("operand_layout_constraints={bf16[1,8192,32,128]{3,2,1,0}, "
              "bf16[1,8192,32,128]{3,2,1,0}, bf16[1,8192,32,128]{3,2,1,0}, "
              "f32[1,8192,32,128]{3,2,1,0}, f32[1,128,16,64,2]{4,3,2,1,0}, "
              "f32[1,128,16,1,128]{4,3,2,1,0}")
    call = ('custom-call(%a), custom_call_target="tpu_custom_call", '
            + shapes)
    hlo = f"""ENTRY %main (a: f32[8]) -> f32[8] {{
  %kda_scan_fwd.8 = (f32[1,8192,32,128]{{3,2,1,0:T(8,128)}}, f32[1,128,32,128,128]{{4,3,2,1,0:T(8,128)}}) {call}}}, metadata={{op_name="jit(step)/jvp(mixer/kda)/scan/kda_scan_fwd/pallas_call"}}
  %kda_scan_fwd.9 = f32[1,8192,32,128]{{3,2,1,0:T(8,128)}} {call}}}, metadata={{op_name="jit(step)/jvp(mixer/kda)/scan/kda_scan_fwd/pallas_call"}}
  %flash_attention_fwd.1 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="jit(step)/jvp()/attn/core/flash_attention_fwd/pallas_call"}}
  %kda_scan_fwd.12 = f32[1,8192,32,128]{{3,2,1,0:T(8,128)}} {call}}}, metadata={{op_name="jit(step)/transpose(jvp())/checkpoint/rematted_computation/mixer/kda/scan/kda_scan_fwd/pallas_call"}}
  %kda_scan_bwd.3 = bf16[1,8192,32,128]{{3,2,1,0:T(8,128)(2,1)}} {call}, f32[1,128,32,128,128]{{4,3,2,1,0}}, f32[1,8192,32,128]{{3,2,1,0}}}}, metadata={{op_name="jit(step)/transpose(jvp())/checkpoint/mixer/kda/scan/mixer/kda/scan/kda_scan_bwd/pallas_call"}}
  %kda_scan_fwd.13 = f32[1,8192,32,128]{{3,2,1,0:T(8,128)}} {call}}}, metadata={{op_name="jit(step)/transpose(jvp())/checkpoint/rematted_computation/mixer/kda/scan/kda_scan_fwd/pallas_call"}}
  %kda_scan_bwd.4 = bf16[1,8192,32,128]{{3,2,1,0:T(8,128)(2,1)}} {call}, f32[1,128,32,128,128]{{4,3,2,1,0}}, f32[1,8192,32,128]{{3,2,1,0}}}}, metadata={{op_name="jit(step)/transpose(jvp())/checkpoint/mixer/kda/scan/mixer/kda/scan/kda_scan_bwd/pallas_call"}}
}}
"""
    assert trace_analysis.kda_kernel_calls(hlo) == {
        "mosaic_calls": 6, "blocks": 2, "chunk": 64}
    assert trace_analysis.kda_loops(hlo) == {"blocks": 0, "chunks": 0}
    assert trace_analysis.kda_kernel_calls("") == {
        "mosaic_calls": 0, "blocks": 0, "chunk": 0}


# ---------------------------------------------------------------------------
# (e) what cannot run it says why
# ---------------------------------------------------------------------------


def _plan(**parallel):
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    # (the latent block of the cell's stack would be refused first)
    args = CoreArgs(model=ModelArgs(
        **{**TINY, "layer_types": ["kda"] * 5}).model_dump())
    for k, v in parallel.items():
        setattr(args.parallel, k, v)
    args.parallel.global_train_batch_size = 8
    return get_hybrid_parallel_config(args, 4)


@pytest.mark.parametrize("parallel,said", [
    (dict(global_tp_deg=2), "kda block and its plan has tp=2"),
    (dict(global_cp_deg=2), "kda block and its plan has cp=2"),
], ids=["tp2", "cp2"])
def test_a_plan_that_cuts_heads_or_sequence_is_refused(parallel, said):
    with pytest.raises(ValueError, match=said):
        _plan(**parallel)
    assert _plan() is not None    # dp alone runs


def test_other_engines_refuse_the_mixer_by_a_reason():
    from hetu_galvatron_tpu.analysis.eligibility import (
        KDA_REASON,
        MIXER_OVERLAP_REASON,
        mixed_stack_reason,
    )
    from hetu_galvatron_tpu.models.generate import generate

    cfg = ModelArgs(**TINY)
    said = mixed_stack_reason(cfg, "generate()")
    assert "kda/dense" in said and "latent_attention/experts" in said
    assert MIXER_OVERLAP_REASON["kda"] is KDA_REASON
    # (a decoding path says "dense layers only" to any expert model first)
    dense = ModelArgs(**{**TINY, "model_type": "llama", "num_experts": 0,
                         "num_shared_experts": 0, "num_dense_layers": 0})
    params, _ = init_causal_lm(jax.random.key(0), dense)
    with pytest.raises(NotImplementedError, match="4 x kda/dense"):
        generate(params, jnp.zeros((1, 4), jnp.int32), dense,
                 max_new_tokens=1)
    from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine

    with pytest.raises(NotImplementedError, match="run it at pp_deg=1"):
        PipelineEngine(cfg, None, None, None)


def test_kda_refuses_what_it_is_not_written_for():
    cfg = ModelArgs(**TINY)
    with pytest.raises(ValueError, match="kda_num_heads"):
        M.init_kda(jax.random.key(0), cfg.model_copy(
            update=dict(kda_num_heads=0)))
    with pytest.raises(ValueError, match="kda_chunk_size=48"):
        M.init_kda(jax.random.key(0), cfg.model_copy(
            update=dict(kda_chunk_size=48)))
    with pytest.raises(ValueError, match="kv_lora_rank"):
        M.init_latent_attention(jax.random.key(0), cfg.model_copy(
            update=dict(kv_lora_rank=0)))
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    batch = _batch()
    with pytest.raises(NotImplementedError, match="carried state"):
        forward_causal_lm(params, batch["tokens"], cfg,
                          compute_dtype=jnp.float32,
                          segment_ids=jnp.zeros_like(batch["tokens"]))
    with pytest.raises(NotImplementedError, match="kda_plan_reason"):
        M.apply_mixer(params["layers"][0], jnp.zeros((1, 40, 32)), cfg, "kda",
                      ops=M.LayerOps(shard=lambda a, axis: a))


@pytest.mark.parametrize("dtype,loss_band,grad_band", [
    ("float32", 2e-5, 1e-4), ("bfloat16", 2e-2, 5e-2)])
def test_a_block_through_the_convolutions_kernels_is_the_block(
        conv_kernels_are_the_block, dtype, loss_band, grad_band):
    """A KDA block whose q, k and v are whole lane tiles (two heads of 128)
    over 300 positions: its convolution, SiLU and L2 norms as one call of
    the kernels of ``ops/pallas/conv.py`` over the three at once
    (interpret mode) against the ``jax.numpy`` form, the recurrence in its
    ``jax.numpy`` form on both sides."""
    cfg = ModelArgs(**{**TINY, "hidden_size": 64, "kda_head_dim": 128,
                       "seq_length": 300, "max_position_embeddings": 512})
    params, _ = M.init_kda(jax.random.key(5), cfg)
    x = jax.random.normal(jax.random.key(6), (2, 300, 64))
    conv_kernels_are_the_block(
        lambda p, a, conv_fn: M.apply_kda(
            p, a, cfg, compute_dtype=jnp.dtype(dtype), conv_fn=conv_fn),
        params, x, dtype, loss_band, grad_band)
