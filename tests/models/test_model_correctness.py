"""Model-core correctness: shapes/grads + logit parity vs HuggingFace torch
baselines (the reference's tier-2 strategy, tests/models/test_model_correctness.py:
loss trajectories vs GPT2LMHeadModel / LlamaForCausalLM — here we compare
logits directly, which is stronger)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.models.builder import (
    build_causal_lm_arch,
    causal_lm_loss,
    forward_causal_lm,
    init_causal_lm,
    param_count,
)

pytestmark = pytest.mark.model

TINY_GPT = ModelArgs(
    model_type="gpt", hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, vocab_size=128, max_position_embeddings=32,
    seq_length=16, hidden_act="gelu", normalization="layernorm",
    position_embedding_type="learned", make_vocab_size_divisible_by=1,
)

TINY_LLAMA = ModelArgs(
    model_type="llama", hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, ffn_hidden_size=176,
    vocab_size=128, max_position_embeddings=32, seq_length=16,
    hidden_act="swiglu", normalization="rmsnorm",
    position_embedding_type="rope", tie_word_embeddings=False,
    add_bias_linear=False, add_qkv_bias=False, make_vocab_size_divisible_by=1,
)


def test_arch_list():
    arch = build_causal_lm_arch(TINY_GPT)
    assert arch[0] == "embed" and arch[-2:] == ["prenorm", "head"]
    assert arch.count("decoder") == 2


def test_forward_shapes_and_loss():
    params, axes = init_causal_lm(jax.random.key(0), TINY_GPT)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(s, str) for s in x))
    tokens = jnp.zeros((2, 16), jnp.int32)
    # (one program each: op by op a model is hundreds of compiles)
    logits = jax.jit(lambda p, t: forward_causal_lm(p, t, TINY_GPT))(
        params, tokens)
    assert logits.shape == (2, 16, 128)
    assert logits.dtype == jnp.float32
    batch = {"tokens": tokens, "labels": tokens}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: causal_lm_loss(p, batch, TINY_GPT)))(params)
    assert np.isfinite(float(loss))
    leaves = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(g)) for g in leaves)
    # loss at init is ~ log(V)
    assert abs(float(loss) - np.log(128)) < 1.0


def test_remat_same_loss():
    params, _ = init_causal_lm(jax.random.key(0), TINY_LLAMA)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    batch = {"tokens": tokens, "labels": tokens}
    # (one program a side)
    (l0, g0), (l1, g1) = (jax.jit(jax.value_and_grad(
        lambda p, flags=flags: causal_lm_loss(
            p, batch, TINY_LLAMA, compute_dtype=jnp.float32,
            remat_flags=flags)))(params) for flags in (None, [True, True]))
    assert abs(float(l0) - float(l1)) < 1e-6
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_causal_masking():
    """Changing a future token must not change past logits."""
    params, _ = init_causal_lm(jax.random.key(0), TINY_LLAMA)
    t1 = jax.random.randint(jax.random.key(1), (1, 16), 0, 128)
    t2 = t1.at[0, -1].set((t1[0, -1] + 1) % 128)
    forward = jax.jit(lambda p, t: forward_causal_lm(
        p, t, TINY_LLAMA, compute_dtype=jnp.float32))
    l1, l2 = forward(params, t1), forward(params, t2)
    np.testing.assert_allclose(l1[:, :-1], l2[:, :-1], atol=1e-6)
    assert not np.allclose(l1[:, -1], l2[:, -1])


# ---------------------------------------------------------------------------
# HF parity
# ---------------------------------------------------------------------------


def _t2j(t):
    return jnp.asarray(t.detach().numpy())


def test_gpt2_logit_parity_vs_hf():
    torch = pytest.importorskip("torch")
    from transformers import GPT2Config, GPT2LMHeadModel

    hf_cfg = GPT2Config(
        vocab_size=128, n_positions=32, n_embd=64, n_layer=2, n_head=4,
        activation_function="gelu_new", resid_pdrop=0.0, embd_pdrop=0.0,
        attn_pdrop=0.0,
    )
    torch.manual_seed(0)
    hf = GPT2LMHeadModel(hf_cfg).eval()

    params, _ = init_causal_lm(jax.random.key(0), TINY_GPT)
    sd = hf.state_dict()
    layers = []
    for i in range(2):
        pre = f"transformer.h.{i}."
        layers.append({
            "ln1": {"scale": _t2j(sd[pre + "ln_1.weight"]),
                    "bias": _t2j(sd[pre + "ln_1.bias"])},
            "attn": {"wqkv": _t2j(sd[pre + "attn.c_attn.weight"]),
                     "bqkv": _t2j(sd[pre + "attn.c_attn.bias"]),
                     "wo": _t2j(sd[pre + "attn.c_proj.weight"]),
                     "bo": _t2j(sd[pre + "attn.c_proj.bias"])},
            "ln2": {"scale": _t2j(sd[pre + "ln_2.weight"]),
                    "bias": _t2j(sd[pre + "ln_2.bias"])},
            "mlp": {"win": _t2j(sd[pre + "mlp.c_fc.weight"]),
                    "bin": _t2j(sd[pre + "mlp.c_fc.bias"]),
                    "wout": _t2j(sd[pre + "mlp.c_proj.weight"]),
                    "bout": _t2j(sd[pre + "mlp.c_proj.bias"])},
        })
    params = {
        "embed": {"wte": _t2j(sd["transformer.wte.weight"]),
                  "wpe": _t2j(sd["transformer.wpe.weight"])},
        "layers": tuple(layers),
        "prenorm": {"scale": _t2j(sd["transformer.ln_f.weight"]),
                    "bias": _t2j(sd["transformer.ln_f.bias"])},
        "head": {},
    }
    tokens_np = np.random.RandomState(0).randint(0, 128, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens_np)).logits.numpy()
    ours = forward_causal_lm(params, jnp.asarray(tokens_np), TINY_GPT,
                             compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-4, atol=2e-4)


def test_llama_logit_parity_vs_hf():
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=176,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=32, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
    )
    torch.manual_seed(0)
    hf = LlamaForCausalLM(hf_cfg).eval()
    sd = hf.state_dict()

    def lin(name):  # torch Linear stores [out, in]
        return _t2j(sd[name]).T

    layers = []
    for i in range(2):
        pre = f"model.layers.{i}."
        wqkv = jnp.concatenate(
            [lin(pre + "self_attn.q_proj.weight"),
             lin(pre + "self_attn.k_proj.weight"),
             lin(pre + "self_attn.v_proj.weight")], axis=1)
        win = jnp.concatenate(
            [lin(pre + "mlp.gate_proj.weight"),
             lin(pre + "mlp.up_proj.weight")], axis=1)
        layers.append({
            "ln1": {"scale": _t2j(sd[pre + "input_layernorm.weight"])},
            "attn": {"wqkv": wqkv, "wo": lin(pre + "self_attn.o_proj.weight")},
            "ln2": {"scale": _t2j(sd[pre + "post_attention_layernorm.weight"])},
            "mlp": {"win": win, "wout": lin(pre + "mlp.down_proj.weight")},
        })
    params = {
        "embed": {"wte": _t2j(sd["model.embed_tokens.weight"])},
        "layers": tuple(layers),
        "prenorm": {"scale": _t2j(sd["model.norm.weight"])},
        "head": {"whead": lin("lm_head.weight")},
    }
    tokens_np = np.random.RandomState(0).randint(0, 128, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens_np)).logits.numpy()
    ours = forward_causal_lm(params, jnp.asarray(tokens_np), TINY_LLAMA,
                             compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-4, atol=2e-4)


def test_param_count_gpt2_small():
    cfg = ModelArgs(model_name="gpt2-small")  # defaults are gpt2-small
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    n = param_count(params)
    # 124M-class (padded vocab 50304)
    assert 1.2e8 < n < 1.3e8


def test_llama3_rope_scaling_parity_vs_hf():
    """llama-3.1-style rope_scaling (llama3 recipe) + linear scaling: the
    scaled inv-freq table matches transformers' ROPE_INIT_FUNCTIONS and the
    full model matches HF logits (BASELINE milestone 5 model family)."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    from hetu_galvatron_tpu.models.modules import _scale_inv_freq
    from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params

    sc = {"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
          "high_freq_factor": 4.0, "original_max_position_embeddings": 8192}
    hf_cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=176,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=16384, rms_norm_eps=1e-5,
        rope_theta=500000.0, rope_scaling=dict(sc),
        tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
    )
    inv_ref, _ = ROPE_INIT_FUNCTIONS["llama3"](hf_cfg, "cpu")
    base = 1.0 / (500000.0 ** (np.arange(0, 16, 2, dtype=np.float64) / 16.0))
    ours = _scale_inv_freq(jnp.asarray(base, jnp.float32), sc)
    np.testing.assert_allclose(np.asarray(ours), inv_ref.numpy(), rtol=1e-6)
    lin = _scale_inv_freq(jnp.asarray(base, jnp.float32),
                          {"rope_type": "linear", "factor": 4.0})
    np.testing.assert_allclose(np.asarray(lin), base / 4.0, rtol=1e-6)

    cfg = TINY_LLAMA.model_copy(update={
        "rope_theta": 500000.0, "rope_scaling": sc,
        "max_position_embeddings": 16384})
    torch.manual_seed(0)
    hf = LlamaForCausalLM(hf_cfg).eval()
    params = hf_to_params(hf.state_dict(), cfg)
    tokens_np = np.random.RandomState(0).randint(0, 128, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens_np)).logits.numpy()
    ours_logits = forward_causal_lm(params, jnp.asarray(tokens_np), cfg,
                                    compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ours_logits), ref,
                               rtol=2e-4, atol=2e-4)


def test_gemma_logit_parity_vs_hf():
    """Gemma family numerics: zero-centered RMSNorm (x * (1+w)), sqrt(H)
    embedding scaling, gated-gelu MLP, decoupled head_dim — logits must
    match HF GemmaForCausalLM through the config adapter + converter."""
    torch = pytest.importorskip("torch")
    from transformers import GemmaConfig, GemmaForCausalLM

    from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    hf_cfg = GemmaConfig(
        vocab_size=128, hidden_size=48, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=32, rms_norm_eps=1e-6,
        rope_theta=10000.0, attention_dropout=0.0,
        hidden_act="gelu_pytorch_tanh", hidden_activation="gelu_pytorch_tanh",
    )
    cfg = populate_model_args_from_hf(hf_cfg)
    cfg = cfg.model_copy(update={"seq_length": 16,
                                 "make_vocab_size_divisible_by": 1})
    assert cfg.norm_zero_centered and cfg.scale_embeddings
    assert cfg.head_dim == 16 and cfg.hidden_act == "geglu"
    assert cfg.tie_word_embeddings

    torch.manual_seed(0)
    hf = GemmaForCausalLM(hf_cfg).eval()
    params = hf_to_params(hf.state_dict(), cfg)
    tokens_np = np.random.RandomState(0).randint(0, 128, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens_np)).logits.numpy()
    ours = forward_causal_lm(params, jnp.asarray(tokens_np), cfg,
                             compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=5e-4, atol=5e-4)


def test_remat_policy_parity():
    """remat policies change memory/recompute, never numerics: loss and
    grads identical across full / dots / dots_no_batch and no-remat."""
    from hetu_galvatron_tpu.models.builder import causal_lm_loss

    base = TINY_LLAMA
    params, _ = init_causal_lm(jax.random.key(0), base)
    tokens = np.random.RandomState(0).randint(0, 128, (2, 17))
    batch = {"tokens": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    flags = [True] * base.num_hidden_layers

    def loss_grads(cfg, remat_flags):
        l, g = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.float32,
            remat_flags=remat_flags)))(params)
        return float(l), g

    l_ref, g_ref = loss_grads(base, None)
    for policy in ("full", "dots", "dots_no_batch"):
        cfg = base.model_copy(update={"remat_policy": policy})
        l, g = loss_grads(cfg, flags)
        assert l == pytest.approx(l_ref, rel=1e-6), policy
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6, err_msg=policy)


@pytest.mark.parametrize("family", ["qwen2", "mistral"])
def test_qwen2_mistral_logit_parity_vs_hf(family):
    """Qwen2 (qkv bias, no mlp bias) and Mistral (bias-free GQA) through the
    config adapter + converter match HF logits."""
    torch = pytest.importorskip("torch")

    from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    if family == "qwen2":
        from transformers import Qwen2Config as Cfg, Qwen2ForCausalLM as LM
    else:
        from transformers import MistralConfig as Cfg, MistralForCausalLM as LM

    hf_cfg = Cfg(
        vocab_size=128, hidden_size=64, intermediate_size=176,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-6, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    cfg = populate_model_args_from_hf(hf_cfg)
    cfg = cfg.model_copy(update={"seq_length": 16,
                                 "make_vocab_size_divisible_by": 1})
    assert cfg.add_qkv_bias == (family == "qwen2")
    assert not cfg.add_bias_linear

    torch.manual_seed(0)
    hf = LM(hf_cfg).eval()
    params = hf_to_params(hf.state_dict(), cfg)
    tokens_np = np.random.RandomState(0).randint(0, 128, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens_np)).logits.numpy()
    ours = forward_causal_lm(params, jnp.asarray(tokens_np), cfg,
                             compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# multimodal rope (qwen2-vl mrope; reference rotary_pos_embedding.py)
# ---------------------------------------------------------------------------


def test_mrope_identical_rows_equal_standard_rope():
    """With temporal == height == width positions (text-only), mrope IS
    standard rope — exact equality of the tables and of forward logits."""
    from hetu_galvatron_tpu.models import modules as M

    S, D = 16, 16
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (3, 2, S))
    cos_m, sin_m = M.mrope_cos_sin(pos, D, 10000.0, sections=(2, 3, 3))
    cos, sin = M.rope_cos_sin(S, D, 10000.0)
    np.testing.assert_allclose(np.asarray(cos_m[0]), np.asarray(cos),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin_m[1]), np.asarray(sin),
                               atol=1e-6)

    cfg = ModelArgs(
        hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
        vocab_size=64, max_position_embeddings=32, seq_length=S,
        hidden_act="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", tie_word_embeddings=False,
        add_bias_linear=False, add_qkv_bias=False,
        make_vocab_size_divisible_by=1)
    mcfg = cfg.model_copy(update={"mrope_section": [2, 3, 3]})
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, S)))
    base = forward_causal_lm(params, toks, cfg, compute_dtype=jnp.float32)
    out = forward_causal_lm(params, toks, mcfg, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=1e-5, atol=1e-5)


def test_mrope_sections_draw_from_their_axis():
    """Frequency section j rotates by position row j: changing only the
    height row changes only its section's columns."""
    from hetu_galvatron_tpu.models import modules as M

    S, D = 8, 16
    base = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (3, 1, S))
    shifted = base.at[1].add(5)  # move the height positions only
    c0, s0 = M.mrope_cos_sin(base, D, 10000.0, sections=(2, 3, 3))
    c1, s1 = M.mrope_cos_sin(shifted, D, 10000.0, sections=(2, 3, 3))
    diff = np.abs(np.asarray(c1 - c0)).max(axis=(0, 1))  # per freq dim
    assert np.all(diff[2:5] > 1e-3), diff  # height section moved
    assert np.allclose(diff[:2], 0) and np.allclose(diff[5:], 0), diff


def test_mrope_batch_position_ids_and_validation():
    from hetu_galvatron_tpu.models import modules as M

    with pytest.raises(ValueError, match="sum"):
        M.mrope_cos_sin(jnp.zeros((3, 1, 4), jnp.int32), 16, 1e4,
                        sections=(2, 2, 2))
    with pytest.raises(ValueError, match="3, B, S"):
        M.mrope_cos_sin(jnp.zeros((1, 4), jnp.int32), 16, 1e4,
                        sections=(2, 3, 3))
    # explicit [3,B,S] ids through the forward (multimodal-shaped batch)
    cfg = ModelArgs(
        hidden_size=32, num_hidden_layers=1, num_attention_heads=2,
        vocab_size=64, max_position_embeddings=32, seq_length=8,
        hidden_act="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", tie_word_embeddings=False,
        add_bias_linear=False, add_qkv_bias=False,
        make_vocab_size_divisible_by=1, mrope_section=[2, 3, 3])
    params, _ = init_causal_lm(jax.random.key(1), cfg)
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 64, (1, 8)))
    # non-uniform per-axis positions: rope attention is shift-invariant,
    # so constant offsets would leave logits unchanged — stretch the
    # height/width grids instead (vision-patch geometry)
    mpos = jnp.stack([jnp.arange(8), jnp.arange(8) * 2,
                      jnp.arange(8) * 3]).astype(jnp.int32)[:, None, :]
    out = forward_causal_lm(params, toks, cfg, compute_dtype=jnp.float32,
                            mrope_position_ids=mpos)
    plain = forward_causal_lm(params, toks, cfg, compute_dtype=jnp.float32)
    assert np.all(np.isfinite(np.asarray(out)))
    assert np.abs(np.asarray(out - plain)).max() > 1e-5


def test_hf_adapter_detects_mrope():
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    cfg = populate_model_args_from_hf({
        "model_type": "qwen2", "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "vocab_size": 128,
        "max_position_embeddings": 64,
        "rope_scaling": {"type": "mrope", "mrope_section": [2, 3, 3]},
    })
    assert cfg.mrope_section == [2, 3, 3]
    assert cfg.rope_scaling is None  # "mrope" is not a frequency scaling
