"""Xing4.0-29B-A4B (``xing4_0``): latent attention with YaRN at a q/k and a
v width of their own, the residual as four Sinkhorn-mixed streams, shared
beside routed experts on a held share, and a multi-token-prediction block in
the loss: the program against the benchmark's plain reference and against
transformers' DeepSeek-V3 for what that has, the checkpoint names, and the
refusals. CPU, fp32 at ``highest``, tiny widths."""

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
YARN = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8}
# one dense block, then two with 8 routed experts (all held) and a shared
# one; every new part on
TINY = dict(
    model_type="moe", hidden_size=32, num_hidden_layers=3,
    layer_types=["latent_attention"] * 3, num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=12,
    kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    ffn_hidden_size=48, moe_ffn_hidden_size=16, vocab_size=64,
    max_position_embeddings=64, seq_length=16, hidden_act="swiglu",
    normalization="rmsnorm", layernorm_epsilon=1e-6,
    position_embedding_type="rope", rope_theta=10000.0, rope_scaling=YARN,
    tie_word_embeddings=False, add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, hc_mult=4, num_nextn_predict_layers=1,
    num_experts=8, num_shared_experts=1, moe_topk=2,
    moe_score_function="sigmoid", moe_norm_topk_prob=True,
    moe_norm_topk_eps=1e-20, moe_routed_scaling_factor=2.0,
    moe_router_enable_expert_bias=True, moe_hf_layout="deepseek",
    moe_dispatcher="dropless", moe_aux_loss_coeff=0.0, use_flash_attn=False)

# the configuration's file as benchmark/reference/xing4_0.py reads it
REF_CFG = {
    "hidden_size": 32, "num_hidden_layers": 3, "num_attention_heads": 4,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "q_lora_rank": 12, "kv_lora_rank": 8, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "rope_scaling": YARN, "first_k_dense_replace": 1,
    "n_routed_experts": 8, "num_routed_experts": 8, "first_expert_held": 0,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2, "n_shared_experts": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "num_nextn_predict_layers": 1,
    "mtp_loss_lambda": 0.3}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _family():
    from benchmark import reference

    return reference.load_family("xing4_0")


def _seeded(cfg, key=7):
    """Seeded random weights drawn so that each new equation matters: norm
    scales that are not all ones, a nonzero selection bias, and residual
    maps whose token-dependent term is large (``alpha`` near 0.5, ``phi``
    five times its start) and whose static term is far from a doubly
    stochastic matrix, so that leaving out the Sinkhorn normalisation or the
    token-dependent term moves the loss; q and kv up-projections fifteen
    times their start, so that the scores are of order one and the softmax
    scale's ``mscale`` moves it too."""
    params, _ = init_causal_lm(jax.random.key(key), cfg)

    def shake(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.key(len(name) + 13 * sum(map(ord, name)))
        if "hc" in name:
            if "alpha" in name:
                return x + 0.5 + 0.2 * jax.random.normal(k, x.shape)
            if "bias" in name:
                return x + 0.5 * jax.random.normal(k, x.shape)
            return 5.0 * x
        if "norm" in name or "ln" in name:
            return x + 0.3 * jax.random.normal(k, x.shape)
        if "wq_b" in name or "wkv_b" in name:
            return 15.0 * x    # scores of order one: the softmax scale shows
        if "expert_bias" in name:
            return 0.2 * jax.random.normal(k, x.shape)
        return x
    return jax.tree_util.tree_map_with_path(shake, params)


def _batch(seed=3, rows=2):
    return jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(seed).randint(0, 64, (rows, 17))))


# ---------------------------------------------------------------------------
# (a) transformers' DeepSeek-V3 for what it has: latent attention with YaRN,
# leading dense blocks, sigmoid routing with the bias, the shared expert
# ---------------------------------------------------------------------------


def test_one_stream_and_no_further_depth_is_hf_deepseek_v3():
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV3Config, DeepseekV3ForCausalLM

    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    d = dict(vocab_size=64, hidden_size=32, intermediate_size=48,
             moe_intermediate_size=16, num_hidden_layers=3,
             num_attention_heads=4, num_key_value_heads=4,
             n_shared_experts=1, n_routed_experts=8,
             routed_scaling_factor=2.0, kv_lora_rank=8, q_lora_rank=12,
             qk_rope_head_dim=4, v_head_dim=8, qk_nope_head_dim=8, n_group=1,
             topk_group=1, num_experts_per_tok=2, first_k_dense_replace=1,
             norm_topk_prob=True, max_position_embeddings=64,
             rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=dict(YARN),
             attention_bias=False, tie_word_embeddings=False,
             rope_interleave=True)
    torch.manual_seed(0)
    hf = DeepseekV3ForCausalLM(DeepseekV3Config(**d)).eval()
    with torch.no_grad():
        for name, t in list(hf.named_parameters()) + list(
                hf.named_buffers()):
            if "norm" in name:   # a fresh model's scales are all ones
                t.add_(0.3 * torch.randn_like(t))
            if "e_score_correction_bias" in name:
                t.copy_(0.2 * torch.randn_like(t))
    cfg = populate_model_args_from_hf(
        {**d, "model_type": "xing4_0", "hc_mult": 1,
         "num_nextn_predict_layers": 0}).model_copy(update=dict(
             make_vocab_size_divisible_by=1, seq_length=16,
             use_flash_attn=False))
    assert cfg.block_kinds() == (("latent_attention", "dense"),) + (
        ("latent_attention", "experts"),) * 2
    params = jax.tree.map(jnp.asarray, hf_to_params(hf.state_dict(), cfg))
    tok = np.random.RandomState(0).randint(0, 64, (2, 16))
    with torch.no_grad():
        want = hf(torch.tensor(tok)).logits.numpy()
    got = forward_causal_lm(params, jnp.asarray(tok), cfg,
                            compute_dtype=jnp.float32)
    # both fp32; logits of order 0.5
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=2e-6)


def test_yarn_bands_are_transformers():
    pytest.importorskip("torch")
    from transformers import DeepseekV3Config
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    sc = {**YARN, "original_max_position_embeddings": 4096}
    hf_cfg = DeepseekV3Config(
        hidden_size=32, num_attention_heads=4, qk_rope_head_dim=64,
        rope_theta=10000.0, rope_scaling=dict(sc),
        max_position_embeddings=262144)
    want, factor = ROPE_INIT_FUNCTIONS["yarn"](hf_cfg, "cpu")
    base = 1.0 / 10000.0 ** (jnp.arange(0, 64, 2, dtype=jnp.float32) / 64)
    got = M._scale_inv_freq(base, sc, 10000.0)
    np.testing.assert_allclose(np.asarray(got), want.numpy(), rtol=1e-6)
    assert M.rope_attention_factor(sc) == pytest.approx(factor) == 1.0
    # the one form a configuration here states; any other is refused
    with pytest.raises(ValueError, match="mscale and mscale_all_dim"):
        M.rope_attention_factor({"type": "yarn", "factor": 64})
    assert M.latent_softmax_scale(ModelArgs(**TINY)) == pytest.approx(
        12 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)


# ---------------------------------------------------------------------------
# (b) the program against the benchmark's plain reference, and the controls
# ---------------------------------------------------------------------------

# the program in bfloat16 (what the cell computes in) against the float32
# reference: the loss alone, of order 5.4, within bf16's eight bits
BF16_LOSS = 2e-2
CONTROLS = ["as_published", "as_published_bf16", "sinkhorn_left_out",
            "dynamic_term_left_out",
            "mscale_left_out", "multi_token_term_left_out",
            "shared_expert_left_out", "rope_columns_not_interleaved"]


@pytest.mark.parametrize("case", CONTROLS)
def test_program_matches_plain_reference(case, monkeypatch):
    """Logits, loss and gradients of the program against
    ``benchmark/reference/xing4_0.py`` on seeded random weights through the
    exporter, every new part on; the program's gradient tree goes through
    the same exporter and meets ``jax.grad`` of the reference's ``nll_sum``.
    Each control breaks one equation on one side and FAILS the loss's
    tolerance."""
    ref = _family()
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    weights = {k: jnp.asarray(v) for k, v in params_to_hf(params, cfg).items()}
    run_cfg, run_params, ref_cfg = cfg, params, dict(REF_CFG)
    if case == "sinkhorn_left_out":
        run_cfg = cfg.model_copy(update=dict(hc_sinkhorn_iters=0))
    if case == "dynamic_term_left_out":
        zero = lambda lp: {**lp, **{k: {**lp[k], "alpha": 0 * lp[k]["alpha"]}
                                    for k in ("hc1", "hc2")}}
        run_params = {**params, "layers": tuple(map(zero, params["layers"])),
                      "mtp": {**params["mtp"],
                              "layer": zero(params["mtp"]["layer"])}}
    if case == "mscale_left_out":
        ref_cfg["rope_scaling"] = {**YARN, "mscale": 0, "mscale_all_dim": 0}
    if case == "multi_token_term_left_out":
        run_cfg = cfg.model_copy(update=dict(mtp_loss_coeff=0.0))
    if case == "shared_expert_left_out":
        monkeypatch.setattr(
            ref, "experts",
            lambda x, w, p, c: _experts_without_shared(ref, x, w, p, c))
    if case == "rope_columns_not_interleaved":
        monkeypatch.setattr(
            ref, "rope_interleaved",
            lambda x, cos, sin: x * cos + ref.rotate_half(x) * sin)

    def ref_loss(w):
        return ref.nll_sum(w, ref_cfg, batch["tokens"],
                           batch["labels"]) / batch["labels"].size
    if case == "as_published_bf16":
        got = causal_lm_loss(params, batch, cfg, compute_dtype=jnp.bfloat16)
        assert abs(float(got) - float(ref_loss(weights))) < BF16_LOSS, \
            float(got)
        return
    def run_loss(p):
        return causal_lm_loss(p, batch, run_cfg, compute_dtype=jnp.float32)
    if case != "as_published":
        # a control is told by the loss alone
        assert abs(float(run_loss(run_params))
                   - float(ref_loss(weights))) >= 2e-5, case
        return
    # (one program a side; op by op the backward passes are some four
    # thousand compiles more than the forward ones the other cases share)
    want, want_grads = jax.jit(jax.value_and_grad(ref_loss))(weights)
    got, got_grads = jax.jit(jax.value_and_grad(run_loss))(run_params)
    # tolerance: both sides are fp32 at highest on the CPU and differ in
    # operation order only (the norm of the maps applied after the phi
    # product, tokens along lanes in the Sinkhorn passes, grouped against
    # all-experts matmuls, S against S - 1 positions in the further depth).
    # The loss is of order 5.4, gradients up to 0.1
    assert abs(float(got) - float(want)) < 2e-5, (float(got), float(want))
    np.testing.assert_allclose(
        forward_causal_lm(params, batch["tokens"], cfg,
                          compute_dtype=jnp.float32),
        ref.logits(weights, REF_CFG, batch["tokens"]), rtol=1e-4, atol=2e-6)
    got_grads = params_to_hf(got_grads, cfg)
    assert sorted(got_grads) == sorted(want_grads)
    for k in want_grads:
        if k.endswith("e_score_correction_bias"):
            # the bias takes no gradient of the loss; what the program's
            # tree carries on its path is the balance update (moe.py)
            assert float(jnp.max(jnp.abs(want_grads[k]))) == 0.0
            continue
        np.testing.assert_allclose(got_grads[k], want_grads[k], rtol=5e-4,
                                   atol=3e-6, err_msg=k)


def _experts_without_shared(ref, x, w, p, cfg):
    combine = ref.routed_weights(x, w, p, cfg)
    return sum(combine[:, e:e + 1].astype(x.dtype)
               * ref.swiglu(x, w, p + f"experts.{e}.")
               for e in ref.held_experts(cfg))


@pytest.mark.parametrize("shift", [0, 1])
def test_program_matches_reference_on_a_share(shift):
    """The same comparison where every expert layer (the further depth's
    too) holds experts [2, 6) of 8 and the shared expert whole; told a range
    one expert further along, on the same weights, it fails."""
    ref = _family()
    cfg = ModelArgs(**{**TINY, "moe_held_experts": 4,
                       "moe_first_held_expert": 2})
    ref_cfg = {**REF_CFG, "n_routed_experts": 4, "first_expert_held": 2}
    params, batch = _seeded(cfg), _batch(4, rows=1)
    weights = {k: jnp.asarray(v) for k, v in params_to_hf(params, cfg).items()}
    assert "model.layers.1.mlp.experts.2.gate_proj.weight" in weights
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in weights
    want = ref.nll_sum(weights, ref_cfg, batch["tokens"],
                       batch["labels"]) / batch["labels"].size
    run_cfg = cfg.model_copy(update=dict(moe_first_held_expert=2 + shift))
    got = causal_lm_loss(params, batch, run_cfg, compute_dtype=jnp.float32)
    assert (abs(float(got) - float(want)) < 2e-5) == (shift == 0)


def test_bf16_compute_stays_near_the_fp32_reference():
    """The timed path's dtype: bf16 operands with fp32 accumulation, norms,
    maps and softmax. At this size the loss moves by rounding alone."""
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    exact = causal_lm_loss(params, batch, cfg, compute_dtype=jnp.float32)
    with jax.default_matmul_precision("default"):
        got = jax.jit(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.bfloat16,
            remat_flags=[True] * 3))(params)
    assert abs(float(got) - float(exact)) < 2e-2


# ---------------------------------------------------------------------------
# (c) the share ties to the model
# ---------------------------------------------------------------------------

LAYER = ModelArgs(**{**TINY, "num_experts": 16})


def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """16 tiny experts in eight shares of 2: the eight shares' layer outputs,
    with the shared expert (which every chip computes alike) counted once,
    add up to what the uncut reference gives for the whole layer, and their
    routes to all T*K."""
    ref = _family()
    p, _ = init_moe_mlp(jax.random.key(5), LAYER)
    p["expert_bias"] = 0.2 * jax.random.normal(jax.random.key(6), (16,))
    x = jax.random.normal(jax.random.key(8), (2, 16, 32), jnp.float32)
    w = {"gate.weight": p["router"].T,
         "gate.e_score_correction_bias": p["expert_bias"]}
    gate, up = jnp.split(p["shared"]["win"], 2, axis=1)
    w.update({"shared_experts.gate_proj.weight": gate.T,
              "shared_experts.up_proj.weight": up.T,
              "shared_experts.down_proj.weight": p["shared"]["wout"].T})
    for e in range(16):
        gate, up = jnp.split(p["win"][e], 2, axis=1)
        w.update({f"experts.{e}.gate_proj.weight": gate.T,
                  f"experts.{e}.up_proj.weight": up.T,
                  f"experts.{e}.down_proj.weight": p["wout"][e].T})
    ref_cfg = {**REF_CFG, "num_routed_experts": 16, "n_routed_experts": 16}
    flat = x.reshape(-1, 32)
    whole = ref.experts(flat, w, "", ref_cfg).reshape(x.shape)
    shared = ref.swiglu(flat, w, "shared_experts.").reshape(x.shape)
    total, rows = 0.0, 0.0
    for first in range(0, 16, 2):
        cfg = LAYER.model_copy(update=dict(moe_held_experts=2,
                                           moe_first_held_expert=first))
        share = {**p, "win": p["win"][first:first + 2],
                 "wout": p["wout"][first:first + 2]}
        y, _, stats = apply_moe_mlp(share, x, cfg, compute_dtype=jnp.float32)
        total, rows = total + y, rows + float(stats["rows_held"])
    assert rows == 2 * 16 * 2
    # tolerance: fp32, sums in another order
    np.testing.assert_allclose(total - 7 * shared, whole, rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# (d) the checkpoint layout
# ---------------------------------------------------------------------------


def test_round_trip_through_the_public_names():
    cfg = ModelArgs(**{**TINY, "moe_held_experts": 4,
                       "moe_first_held_expert": 4})
    params = _seeded(cfg)
    sd = params_to_hf(params, cfg)
    for name, shape in {
            "model.layers.0.self_attn.q_a_proj.weight": (12, 32),
            "model.layers.0.self_attn.q_b_proj.weight": (4 * 12, 12),
            "model.layers.0.self_attn.kv_a_proj_with_mqa.weight": (8 + 4, 32),
            "model.layers.0.self_attn.kv_b_proj.weight": (4 * 16, 8),
            "model.layers.0.self_attn.o_proj.weight": (32, 4 * 8),
            "model.layers.0.attn_hc.phi.weight": (24, 4 * 32),
            "model.layers.0.mlp_hc.alpha": (3,),
            "model.layers.1.mlp.gate.e_score_correction_bias": (8,),
            "model.layers.1.mlp.shared_experts.down_proj.weight": (32, 16),
            "model.layers.1.mlp.experts.4.up_proj.weight": (16, 32),
            # the further depth: DeepSeek-V3's released layout, a block at
            # index num_hidden_layers
            "model.layers.3.eh_proj.weight": (32, 64),
            "model.layers.3.enorm.weight": (32,),
            "model.layers.3.shared_head.norm.weight": (32,),
            "model.layers.3.self_attn.kv_a_layernorm.weight": (8,),
            "model.layers.3.mlp.experts.7.down_proj.weight": (32, 16),
            "lm_head.weight": (64, 32)}.items():
        assert sd[name].shape == shape, name
    assert "model.layers.3.embed_tokens.weight" not in sd   # the model's
    back = hf_to_params(sd, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
    # the rotated columns go out interleaved: public column 2 i is the
    # program's i, 2 i + 1 its i + d / 2
    wq_b = np.asarray(params["layers"][0]["attn"]["wq_b"])
    hf_q = sd["model.layers.0.self_attn.q_b_proj.weight"].T
    np.testing.assert_array_equal(hf_q[:, 8:12], wq_b[:, [8, 10, 9, 11]])
    np.testing.assert_array_equal(hf_q[:, :8], wq_b[:, :8])


def test_another_layout_has_no_slot_for_the_shared_expert():
    cfg = ModelArgs(**{**TINY, "moe_hf_layout": "olmoe", "hc_mult": 1,
                       "num_nextn_predict_layers": 0})
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    with pytest.raises(NotImplementedError, match="no shared-expert slot"):
        params_to_hf(params, cfg)


def test_the_published_yaml_is_the_published_model():
    from benchmark import manifest
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    cfg = load_config(os.path.join(ZOO, "xing4.0-29b-a4b.yaml")).model
    # the catalog's config.json, as the benchmark's configuration keeps it,
    # with the cuts taken back: the adapter reads the YAML's model out of it
    body = manifest.read_json(os.path.join(
        manifest.ROOT, "benchmark", "configs", "xing4.0-29b-a4b-ep8.json"))
    published = {**{k: v for k, v in body.items()
                    if not isinstance(v, (dict, list))
                    or k == "rope_scaling"}, **body["reduced_from"]}
    read = populate_model_args_from_hf(published).model_copy(update=dict(
        model_name=cfg.model_name, seq_length=cfg.seq_length))
    assert read.model_dump() == cfg.model_dump()
    kinds = cfg.block_kinds()
    assert len(kinds) == 40
    assert {m for m, _ in kinds} == {"latent_attention"}
    assert [ff for _, ff in kinds] == ["dense"] * 2 + ["experts"] * 38
    assert (cfg.qk_head_dim, cfg.v_head_dim, cfg.rope_dim, cfg.hc_mult,
            cfg.num_nextn_predict_layers) == (192, 128, 64, 4, 1)
    # the cell's share, as shapes alone: the count the file states
    cut = cfg.model_copy(update=dict(
        num_hidden_layers=5, layer_types=["latent_attention"] * 5,
        num_dense_layers=1, moe_held_experts=8, vocab_size=16384))
    shapes = jax.eval_shape(lambda k: init_causal_lm(k, cut)[0],
                            jax.random.key(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 913_473_668
    assert f"{count:,} parameters" in body["deployment"]


def test_todays_trees_are_untouched_by_the_new_keys():
    """A model of one stream and no further depth draws the leaves it drew:
    the residual maps and the further depth take keys folded from the
    block's and the model's, not a share of their splits."""
    base = dict(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                vocab_size=64, make_vocab_size_divisible_by=1,
                max_position_embeddings=32, seq_length=16)
    plain, _ = init_causal_lm(jax.random.key(3), ModelArgs(**base))
    streams, _ = init_causal_lm(jax.random.key(3), ModelArgs(
        **base, hc_mult=4, num_nextn_predict_layers=1))
    assert set(streams) - set(plain) == {"mtp"}
    for lp, ls in zip(plain["layers"], streams["layers"]):
        assert set(ls) - set(lp) == {"hc1", "hc2"}
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(lp),
                                jax.tree.leaves({k: ls[k] for k in lp})):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# (e) the maps themselves
# ---------------------------------------------------------------------------


def test_the_stream_map_is_doubly_stochastic_and_starts_near_identity():
    cfg = ModelArgs(**TINY)
    p, _ = M.init_hyper_maps(jax.random.key(1), cfg)
    x = jax.random.normal(jax.random.key(2), (2, 16, 4, 32), jnp.float32)
    pre, post, res = M.hyper_maps(p, x, cfg, jnp.float32)
    assert (pre.shape, post.shape, res.shape) == (
        (4, 2, 16), (4, 2, 16), (4, 4, 2, 16))
    # (hc_eps in every divisor leaves the sums a few 1e-5 off one)
    np.testing.assert_allclose(res.sum(0), 1.0, atol=1e-4)
    np.testing.assert_allclose(res.sum(1), 1.0, atol=1e-4)
    # the start: the sub-layer reads the streams' mean, writes to each with
    # weight one, and the streams keep 0.95 of themselves
    np.testing.assert_allclose(pre.sum(0), 1.0, atol=0.05)
    np.testing.assert_allclose(post, 1.0, atol=0.05)
    assert float(jnp.min(jnp.diagonal(res))) > 0.9
    # a shaken map is made doubly stochastic just the same
    shaken = {"phi": 5 * p["phi"], "alpha": p["alpha"] + 0.5,
              "bias": p["bias"] + jax.random.normal(jax.random.key(3),
                                                    p["bias"].shape)}
    _, _, res = M.hyper_maps(shaken, x, cfg, jnp.float32)
    # the columns were normalised last; the rows are as near as 20 passes
    # bring them
    np.testing.assert_allclose(res.sum(0), 1.0, atol=1e-4)
    np.testing.assert_allclose(res.sum(1), 1.0, atol=0.05)
    assert float(jnp.min(res)) < 0.01 < 0.9 < float(jnp.max(res)) < 1.0


def test_the_step_names_the_new_parts():
    """The compiled step's HLO carries the new named scopes, and the
    further depth's instructions are listed under its three own scopes
    whatever deeper scope they lie under."""
    from hetu_galvatron_tpu.observability import trace_analysis

    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    hlo = jax.jit(jax.grad(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32,
        remat_flags=[True] * 3))).lower(params).compile().as_text()
    found = trace_analysis.step_hlo(hlo)
    scopes = {c[0] for c in found["map"]["instructions"].values()}
    assert {"attn/latent_proj", "attn/rope", "attn/core", "attn/out_proj",
            "hc/maps", "hc/mix", "mtp/embed_proj", "moe/experts",
            "head"} <= scopes
    assert all(found["scopes"][s] for s in trace_analysis.MTP_SCOPES)
    under_block = set(found["scopes"]["mtp/block"])
    # (a list may name an instruction of a reduction's body, which the map
    # of traced events leaves out)
    assert any(found["map"]["instructions"].get(n, (None,))[0] == "hc/maps"
               for n in under_block)


# ---------------------------------------------------------------------------
# (f) what cannot run it says why
# ---------------------------------------------------------------------------


def _plan(**parallel):
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    args = CoreArgs(model=ModelArgs(**TINY).model_dump())
    for k, v in parallel.items():
        setattr(args.parallel, k, v)
    args.parallel.global_train_batch_size = 8
    return get_hybrid_parallel_config(args, 4)


@pytest.mark.parametrize("parallel,said", [
    (dict(global_tp_deg=2), "latent_attention block and its plan has tp=2"),
    (dict(global_cp_deg=2), "latent_attention block and its plan has cp=2"),
    (dict(pp_deg=2), r"pipelined plan \(pp=2\).*hc_mult=4"),
], ids=["tp2", "cp2", "pp2"])
def test_a_plan_that_cuts_heads_sequence_or_depth_is_refused(parallel, said):
    with pytest.raises(ValueError, match=said):
        _plan(**parallel)
    assert _plan() is not None    # dp alone runs


def test_decoding_paths_refuse_the_mixer_and_the_streams():
    from hetu_galvatron_tpu.analysis.eligibility import (
        LATENT_REASON,
        mixed_stack_reason,
        residual_streams_reason,
    )

    cfg = ModelArgs(**TINY)
    assert "latent_attention/experts" in mixed_stack_reason(cfg, "generate()")
    assert "hc_mult=4, num_nextn_predict_layers=1" in residual_streams_reason(
        cfg, "generate()")
    plain = ModelArgs(hidden_size=32, num_hidden_layers=2,
                      num_attention_heads=2, vocab_size=64)
    assert residual_streams_reason(plain, "x") is None
    assert "low-rank" in LATENT_REASON
    # a stack of full attention over several streams is refused too
    dense = ModelArgs(hidden_size=32, num_hidden_layers=2,
                      num_attention_heads=2, vocab_size=64, hc_mult=2,
                      make_vocab_size_divisible_by=1)
    params, _ = init_causal_lm(jax.random.key(0), dense)
    from hetu_galvatron_tpu.models.generate import generate

    with pytest.raises(NotImplementedError, match="hc_mult=2"):
        generate(params, jnp.zeros((1, 4), jnp.int32), dense,
                 max_new_tokens=1)


def test_latent_attention_refuses_what_it_is_not_written_for():
    cfg = ModelArgs(**TINY)
    with pytest.raises(NotImplementedError, match="without biases"):
        M.init_latent_attention(jax.random.key(0), cfg.model_copy(
            update=dict(add_qkv_bias=True)))
    with pytest.raises(ValueError, match="kv_lora_rank"):
        M.init_latent_attention(jax.random.key(0), cfg.model_copy(
            update=dict(kv_lora_rank=0)))
    p, _ = M.init_latent_attention(jax.random.key(0), cfg)
    x = jnp.zeros((1, 16, 32), jnp.float32)
    with pytest.raises(NotImplementedError, match="ring/Ulysses"):
        M.apply_latent_attention(p, x, cfg, sdpa_fn=lambda *a, **k: None)
    with pytest.raises(NotImplementedError, match="one further prediction"):
        init_causal_lm(jax.random.key(0), cfg.model_copy(
            update=dict(num_nextn_predict_layers=2)))
    with pytest.raises(ValueError, match="yarn"):
        M._scale_inv_freq(jnp.ones((4,)), {"type": "ntk"})
