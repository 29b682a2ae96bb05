"""Phi-4-mini-flash-reasoning (microsoft, ``phi4flash``; SambaY): Mamba-1
selective-scan blocks, differential attention under a window and whole, and
a second half whose blocks read one earlier block's scan output (gated
memory units) and one earlier block's keys and values (cross-attention),
from one per-layer description; its checkpoint names; and the program (the
scan in chunks, one core call over all score heads) against the benchmark's
plain reference (the recurrence one position at a time, the two softmax maps
apart). CPU, fp32, tiny widths."""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.analysis import eligibility
from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.core.arguments import args_from_cli, load_config
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.models.builder import (
    causal_lm_loss,
    forward_causal_lm,
    init_causal_lm,
    param_count,
)
from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params, params_to_hf
from hetu_galvatron_tpu.runtime.dataloader import make_batch
from hetu_galvatron_tpu.utils.hf_config_adapter import (
    phi4flash_layer_types,
    populate_model_args_from_hf,
)

pytestmark = [pytest.mark.model]

ZOO = os.path.join(os.path.dirname(M.__file__), "configs")
TYPES = ["mamba1", "sliding_attention", "mamba1", "full_attention", "gmu",
         "cross_attention"]
# a sequence of 21: the scan's chunk of 16 does not divide it;
# a window of 6: it bites
TINY = dict(
    model_type="llama", hf_layout="phi4flash", hidden_size=32,
    num_hidden_layers=6, layer_types=TYPES, num_attention_heads=8,
    num_key_value_heads=4, ffn_hidden_size=48, vocab_size=64,
    max_position_embeddings=64, seq_length=21, hidden_act="swiglu",
    normalization="layernorm", layernorm_epsilon=1e-5,
    position_embedding_type="nope", tie_word_embeddings=True,
    add_bias_linear=False, add_qkv_bias=True, add_attn_out_bias=True,
    make_vocab_size_divisible_by=1, use_flash_attn=False,
    sliding_window=6, differential_attention=True)
# the configuration's file as benchmark/reference/phi4flash.py reads it
REF_CFG = {
    "hidden_size": 32, "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 48, "layer_norm_eps": 1e-5, "sliding_window": 6,
    "layer_types": TYPES, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 2, "vocab_size": 64}
# the published config.json (the catalog's keys)
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
# |program - reference| over the largest value, logits and every gradient
# leaf: float32 at these sizes reads 3e-4 at most (a lambda's gradient of
# 1e-6); a bfloat16 computation reads over 1e-2
# (test_a_bfloat16_computation_fails_the_tolerance)
RTOL = 1e-3


def _family():
    from benchmark import reference

    return reference.load_family("phi4flash")


def _seeded(cfg, key=7):
    """Seeded random weights with biases and norm offsets that are not zero,
    a ``D`` that is not one, lambdas of order one and q and k of order one,
    so that a bias, the skip, the subtraction or the window shows."""
    params, _ = init_causal_lm(jax.random.key(key), cfg)

    def shake(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.key(len(name) + 13 * sum(map(ord, name)))
        if x.ndim == 1 and "dt_bias" not in name:
            return x + 0.3 * jax.random.normal(k, x.shape)
        if "wqkv" in name or "wq'" in name:
            return 25.0 * x
        if "lambdas" in name:
            return 4.0 * x
        if "wo'" in name or "wout" in name:
            # at std / sqrt(2 x blocks) a later block hardly moves the logits
            return 6.0 * x
        if "mamba1']['win" in name or "wx" in name:
            return 8.0 * x
        return x
    return jax.tree_util.tree_map_with_path(shake, params)


def _batch(rows=2, seq=21, seed=3):
    return jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(seed).randint(0, 64, (rows, seq + 1))))


def _reference_weights(params, cfg):
    return {k: jnp.asarray(v) for k, v in params_to_hf(params, cfg).items()}


# ---------------------------------------------------------------------------
# (a) the program against the plain reference
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _reference_logits():
    """The reference's logits of the seeded stack on the batch (under
    ``jit``: op by op the position-by-position scan compiles for seconds)."""
    cfg = ModelArgs(**TINY)
    return jax.jit(lambda w, tokens: _family().logits(w, REF_CFG, tokens))(
        _reference_weights(_seeded(cfg), cfg), _batch()["tokens"])


def _logits(params, cfg, tokens, dtype=jnp.float32):
    return jax.jit(lambda p, t: forward_causal_lm(
        p, t, cfg, compute_dtype=dtype))(params, tokens)


def test_logits_match_the_plain_reference():
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    ours = _logits(params, cfg, batch["tokens"])
    theirs = _reference_logits()
    scale = float(jnp.max(jnp.abs(theirs)))
    assert scale > 0.1
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               atol=RTOL * scale, rtol=0)


def _leafwise(a, b):
    flat_a = jax.tree_util.tree_leaves_with_path(a)
    flat_b = jax.tree.leaves(b)
    assert len(flat_a) == len(flat_b)
    return [(jax.tree_util.keystr(p), np.asarray(x), np.asarray(y))
            for (p, x), y in zip(flat_a, flat_b)]


def test_every_gradient_leaf_matches_the_plain_reference():
    """The reference's gradient, taken through the public names and carried
    back by the importer (a permutation), against the program's, leaf by
    leaf: every leaf has a gradient that is not zero, and they agree."""
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    family = _family()
    loss, ours = _loss_and_grads()(params, batch)
    w = _reference_weights(params, cfg)
    ref_loss, g = jax.jit(jax.value_and_grad(lambda w: family.nll_sum(
        w, REF_CFG, batch["tokens"], batch["labels"])
        / batch["labels"].size))(w)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    theirs = jax.tree.map(jnp.asarray, hf_to_params(
        {k: np.asarray(v) for k, v in g.items()}, cfg))
    for name, x, y in _leafwise(ours, theirs):
        assert np.abs(y).max() > 0, name
        np.testing.assert_allclose(x, y, atol=RTOL * np.abs(y).max(),
                                   rtol=0, err_msg=name)


def test_the_loss_in_bfloat16_matches_the_plain_reference():
    """The program in bfloat16 (what the cell computes in) against the
    float32 reference: the loss alone, of order 4.2, within bf16's eight
    bits (its logits fail the float32 tolerance, next test)."""
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    family = _family()
    want = jax.jit(lambda w: family.nll_sum(
        w, REF_CFG, batch["tokens"], batch["labels"])
        / batch["labels"].size)(_reference_weights(params, cfg))
    got = jax.jit(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.bfloat16))(params)
    assert abs(float(got) - float(want)) < 2e-2, (float(got), float(want))


def test_a_bfloat16_computation_fails_the_tolerance():
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    theirs = _reference_logits()
    rounded = _logits(params, cfg, batch["tokens"], jnp.bfloat16)
    scale = float(jnp.max(jnp.abs(theirs)))
    assert float(jnp.max(jnp.abs(rounded - theirs))) > 10 * RTOL * scale


@pytest.mark.parametrize("broken,said", [
    ("no_window", "the window"), ("no_subtraction", "the second map"),
    ("no_memory", "the memory"), ("own_keys", "the shared keys"),
])
def test_what_the_reference_would_catch(broken, said, monkeypatch):
    """Controls: the program with one mechanism left out is far from the
    reference, so agreement above says the mechanism is there."""
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    if broken == "no_window":
        cfg = cfg.model_copy(update={"sliding_window": 64})
    elif broken == "no_subtraction":
        monkeypatch.setattr(
            M, "differential", lambda p, out, cfg, lam0: out.reshape(
                out.shape[0], out.shape[1], cfg.kv_heads // 2, 2, -1,
                out.shape[-1])[:, :, :, 0].reshape(
                out.shape[0], out.shape[1], -1, out.shape[-1]))
    elif broken == "no_memory":
        row = M.MIXERS["gmu"]
        monkeypatch.setitem(M.MIXERS, "gmu", row._replace(
            apply=lambda p, x, cfg, shared, **kw: row.apply(
                p, x, cfg, shared={"memory": jnp.ones_like(
                    shared["memory"])}, **kw)))
    else:
        row = M.MIXERS["cross_attention"]
        monkeypatch.setitem(M.MIXERS, "cross_attention", row._replace(
            apply=lambda p, x, cfg, shared, **kw: row.apply(
                p, x, cfg, shared={k: jnp.roll(v, 1, axis=1)
                                   for k, v in shared.items()}, **kw)))
    ours = _logits(params, cfg, batch["tokens"])
    theirs = _reference_logits()
    scale = float(jnp.max(jnp.abs(theirs)))
    assert float(jnp.max(jnp.abs(ours - theirs))) > 10 * RTOL * scale, said


def test_one_block_fewer_is_the_stack_without_its_cross_attention():
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    family = _family()
    w = _reference_weights(params, cfg)
    nll = jax.jit(lambda w, layers: family.nll_sum(
        w, REF_CFG, batch["tokens"], batch["labels"], layers=layers),
        static_argnums=1)
    whole, fewer = nll(w, None), nll(w, 5)
    short = ModelArgs(**{**TINY, "num_hidden_layers": 5,
                         "layer_types": TYPES[:5]})
    cut = {**params, "layers": params["layers"][:5]}
    ours = jax.jit(lambda p: causal_lm_loss(
        p, batch, short, compute_dtype=jnp.float32))(cut)
    assert abs(float(fewer) / batch["labels"].size - float(ours)) < 1e-5
    assert abs(float(whole) - float(fewer)) > 1e-3


# ---------------------------------------------------------------------------
# (b) Hugging Face parity of the two new operators
# ---------------------------------------------------------------------------


def test_the_mamba1_mixer_matches_transformers_slow_path():
    torch = pytest.importorskip("torch")
    try:
        from transformers import MambaConfig
        from transformers.models.mamba.modeling_mamba import MambaMixer
    except ImportError as e:   # pragma: no cover
        pytest.skip(f"transformers has no MambaMixer: {e}")
    cfg = ModelArgs(**TINY)
    hf_cfg = MambaConfig(
        hidden_size=32, state_size=16, conv_kernel=4, expand=2,
        time_step_rank=2, use_bias=False, use_conv_bias=True,
        hidden_act="silu", num_hidden_layers=1, vocab_size=64)
    torch.manual_seed(0)
    mixer = MambaMixer(hf_cfg, layer_idx=0).eval()
    with torch.no_grad():
        mixer.conv1d.bias.add_(0.2 * torch.randn_like(mixer.conv1d.bias))
        mixer.D.add_(0.3 * torch.randn_like(mixer.D))
        mixer.in_proj.weight.mul_(4.0)
    sd = {f"model.layers.0.attn.{k}": v.detach().numpy()
          for k, v in mixer.state_dict().items()}
    from hetu_galvatron_tpu.runtime.checkpoint import _PHI4FLASH_MIXER_NAMES

    p = {leaf: (w.T if leaf.startswith("w") else w[:, 0, :]
                if leaf == "taps" else w)
         for leaf, w in ((leaf, sd["model.layers.0.attn." + name])
                         for leaf, name in
                         _PHI4FLASH_MIXER_NAMES["mamba1"].items())}
    x = np.random.RandomState(1).randn(2, 21, 32).astype(np.float32)
    with torch.no_grad():
        theirs = mixer.slow_forward(torch.from_numpy(x)).numpy()
    made = {}
    ours = M.apply_mamba1(jax.tree.map(jnp.asarray, p), jnp.asarray(x), cfg,
                          compute_dtype=jnp.float32, made=made)
    assert np.abs(theirs).max() > 1e-2
    np.testing.assert_allclose(np.asarray(ours), theirs,
                               atol=1e-5 * np.abs(theirs).max() + 1e-7)
    assert made["memory"].shape == (2, 21, 64)


def test_the_differential_core_matches_transformers_diffllama():
    """``DiffLlamaAttention`` pairs head ``j`` with head ``j + N / 2`` (and
    key-value head ``g`` with ``g + K / 2``); the published layout here
    pairs ``2j`` with ``2j + 1``. Under that permutation of the q, k and v
    projections' heads the two are one function (its norm has no learned
    weight: ``subln`` stays at one)."""
    torch = pytest.importorskip("torch")
    try:
        from transformers import DiffLlamaConfig
        from transformers.models.diffllama.modeling_diffllama import (
            DiffLlamaAttention,
        )
    except ImportError as e:   # pragma: no cover
        pytest.skip(f"transformers has no DiffLlamaAttention: {e}")
    from hetu_galvatron_tpu.runtime.checkpoint import _diff_query_columns

    cfg = ModelArgs(**{**TINY, "add_qkv_bias": False,
                       "add_attn_out_bias": False})
    hf = DiffLlamaAttention(DiffLlamaConfig(
        hidden_size=32, num_attention_heads=8, num_key_value_heads=4,
        num_hidden_layers=6, intermediate_size=48, vocab_size=64,
        attention_bias=False, lambda_std_dev=0.1, rms_norm_eps=1e-5,
        attn_implementation="eager"), layer_idx=2).eval()
    assert hf.lambda_init == pytest.approx(M.diff_lambda_init(2))
    p, _ = M.init_attention(jax.random.key(5), cfg)
    p = {**p, "wqkv": 25.0 * p["wqkv"], "lambdas": 4.0 * p["lambdas"]}
    hd, nq, nkv = 4, 8, 4
    q, k, v = np.split(np.asarray(p["wqkv"]), [nq * hd, (nq + nkv) * hd], 1)
    q = q[:, _diff_query_columns(cfg, 2, to_hf=True)]   # published order

    def halves(w, n):   # published heads (2j, 2j + 1) -> (j, j + n / 2)
        heads = w.reshape(w.shape[0], n, hd)
        return np.concatenate([heads[:, 0::2], heads[:, 1::2]],
                              axis=1).reshape(w.shape[0], n * hd)

    with torch.no_grad():
        hf.q_proj.weight.copy_(torch.from_numpy(halves(q, nq).T.copy()))
        hf.k_proj.weight.copy_(torch.from_numpy(halves(k, nkv).T.copy()))
        hf.v_proj.weight.copy_(torch.from_numpy(halves(v, nkv).T.copy()))
        hf.o_proj.weight.copy_(torch.from_numpy(np.asarray(p["wo"]).T.copy()))
        for name, row in zip(("lambda_q1", "lambda_k1", "lambda_q2",
                              "lambda_k2"), np.asarray(p["lambdas"])):
            getattr(hf, name).copy_(torch.from_numpy(row.copy()))
    x = np.random.RandomState(2).randn(2, 21, 32).astype(np.float32)
    mask = torch.full((21, 21), float("-inf")).triu(1)[None, None]
    ones = torch.ones(2, 21, hd), torch.zeros(2, 21, hd)   # no rotation
    with torch.no_grad():
        theirs = hf(torch.from_numpy(x), ones, attention_mask=mask)[0].numpy()
    ours = M.apply_attention(p, jnp.asarray(x), cfg,
                             compute_dtype=jnp.float32,
                             lambda_init=M.diff_lambda_init(2))
    assert np.abs(theirs).max() > 1e-2
    np.testing.assert_allclose(np.asarray(ours), theirs,
                               atol=2e-5 * np.abs(theirs).max())


# ---------------------------------------------------------------------------
# (c) the values that cross blocks
# ---------------------------------------------------------------------------


def test_which_block_leaves_and_which_takes_is_the_descriptions():
    cfg = ModelArgs(**TINY)
    assert cfg.block_shares() == (
        ((), ()), ((), ()), (("memory",), ()), (("keys", "values"), ()),
        ((), ("memory",)), ((), ("keys", "values")))
    whole = populate_model_args_from_hf(PUBLISHED)
    shares = whole.block_shares()
    assert [i for i, (made, _) in enumerate(shares) if made] == [16, 17]
    assert [i for i, (_, takes) in enumerate(shares) if takes] == list(
        range(18, 32))
    # a stack without readers hands nothing on
    assert ModelArgs(**{**TINY, "num_hidden_layers": 2,
                        "layer_types": TYPES[:2]}).block_shares() == (
        ((), ()), ((), ()))


@pytest.mark.parametrize("types,said", [
    (["gmu", "mamba1"], "block 0 is a gmu block and reads the memory"),
    (["mamba1", "cross_attention", "full_attention"],
     "block 1 is a cross_attention block and reads the keys and values"),
    (["sliding_attention", "cross_attention"],
     "an earlier full_attention block; there is none"),
])
def test_a_reader_before_its_maker_is_refused_at_init(types, said):
    cfg = ModelArgs(**{**TINY, "num_hidden_layers": len(types),
                       "layer_types": types})
    with pytest.raises(ValueError) as err:
        init_causal_lm(jax.random.key(0), cfg)
    assert said in str(err.value)


def test_a_block_handed_nothing_says_what_it_reads():
    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="reads the memory"):
        M.apply_mixer(params["layers"][4], jnp.zeros((1, 4, 32)), cfg, "gmu")
    with pytest.raises(ValueError, match="leaves nothing"):
        M.apply_mixer(params["layers"][4], jnp.zeros((1, 4, 32)), cfg, "gmu",
                      shared={"memory": jnp.zeros((1, 4, 64))}, made={})


@functools.lru_cache(maxsize=None)
def _loss_and_grads(policy=None):
    """``(params, batch) -> (loss, gradients)`` of the tiny stack, compiled
    once a remat policy (None: no block rematerialized)."""
    cfg = ModelArgs(**TINY) if policy is None else ModelArgs(
        **{**TINY, "remat_policy": policy})
    kw = {} if policy is None else {"remat_flags": [True] * 6}
    return jax.jit(jax.value_and_grad(lambda p, batch: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32, **kw)))


def test_the_cotangents_reach_the_blocks_that_made_the_values():
    """With block 2's and block 3's own output projections at zero their
    operators reach the loss ONLY through what they left for blocks 4 and
    5: a gradient of zero at their input projections would say that the
    memory's or the keys' and values' cotangent never flowed back."""
    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), _batch()
    layers = list(params["layers"])
    layers[2] = {**layers[2], "mamba1": {
        **layers[2]["mamba1"],
        "wout": jnp.zeros_like(layers[2]["mamba1"]["wout"])}}
    layers[3] = {**layers[3], "attn": {
        **layers[3]["attn"], "wo": jnp.zeros_like(layers[3]["attn"]["wo"])}}
    _, g = _loss_and_grads()({**params, "layers": tuple(layers)}, batch)
    for leaf in ("win", "taps", "wx", "wdt", "A_log", "D"):
        assert float(jnp.abs(g["layers"][2]["mamba1"][leaf]).max()) > 1e-7, leaf
    kv = np.asarray(g["layers"][3]["attn"]["wqkv"])[:, 8 * 4:]
    assert np.abs(kv[:, :16]).max() > 1e-7 and np.abs(kv[:, 16:]).max() > 1e-7
    # ... and the queries of block 3, which nothing reads, get none
    assert np.abs(np.asarray(g["layers"][3]["attn"]["wqkv"])[:, :32]).max() == 0


def test_remat_on_and_off_give_the_same_gradients(policy="full"):
    params, batch = _seeded(ModelArgs(**TINY)), _batch()
    _, plain = _loss_and_grads()(params, batch)
    _, again = _loss_and_grads(policy)(params, batch)
    for name, x, y in _leafwise(plain, again):
        np.testing.assert_allclose(x, y, atol=1e-6 * max(np.abs(x).max(), 1e-6),
                                   rtol=1e-5, err_msg=name)


def test_the_stack_through_the_scans_kernels_is_the_stack():
    """The tiny stack at a width whose scan fits the kernels' tiles (128
    channels, a state of 16, 21 positions padded to one chunk) with
    ``ops/pallas/selective_scan.py`` handed to its two mamba1 blocks
    (interpret mode) against the stack in its ``jax.numpy`` form, under
    per-layer remat as the cell runs it: the logits, the loss and every
    gradient leaf, none of them zero."""
    from hetu_galvatron_tpu.ops.pallas import selective_scan

    cfg = ModelArgs(**{**TINY, "hidden_size": 64, "remat_policy": "full"})
    assert selective_scan.tile_plan(
        cfg.mamba1_d_inner, cfg.mamba1_d_state, cfg.seq_length) == 128
    params, batch = _seeded(cfg), _batch()
    calls = []

    def scan(*a):
        calls.append(a)
        return selective_scan.selective_scan(*a, interpret=True)

    with_kernels = {i: M.LayerOps(selective=scan)
                    for i, kind in enumerate(TYPES) if kind == "mamba1"}
    sides = {}
    for name, overrides in (("kernels", with_kernels), ("numpy", None)):
        logits = jax.jit(lambda p, t: forward_causal_lm(
            p, t, cfg, compute_dtype=jnp.float32,
            layer_overrides=overrides))(params, batch["tokens"])
        loss, grads = jax.jit(jax.value_and_grad(lambda p, b: causal_lm_loss(
            p, b, cfg, compute_dtype=jnp.float32, remat_flags=[True] * 6,
            layer_overrides=overrides)))(params, batch)
        sides[name] = (logits, loss, grads)
    # (two blocks, traced for the logits and for the loss; the recomputed
    # forward of a rematted block is the trace of its first)
    assert len(calls) == 4
    scale = float(jnp.abs(sides["numpy"][0]).max())
    assert scale > 0.1
    np.testing.assert_allclose(np.asarray(sides["kernels"][0]),
                               np.asarray(sides["numpy"][0]),
                               atol=1e-5 * scale, rtol=0)
    assert abs(float(sides["kernels"][1]) - float(sides["numpy"][1])) < 1e-5
    for name, x, y in _leafwise(sides["kernels"][2], sides["numpy"][2]):
        assert np.abs(y).max() > 0, name
        np.testing.assert_allclose(x, y, atol=1e-4 * np.abs(y).max(), rtol=0,
                                   err_msg=name)


# ---------------------------------------------------------------------------
# (d) names, configuration, counts
# ---------------------------------------------------------------------------


def test_the_exported_names_and_the_round_trip():
    cfg = ModelArgs(**TINY)
    params = _seeded(cfg)
    sd = params_to_hf(params, cfg)
    pre = "model.layers.{}.attn."
    assert {"model.embed_tokens.weight", "model.final_layernorm.weight",
            "model.final_layernorm.bias",
            "model.layers.0.input_layernorm.bias",
            "model.layers.0.mlp.gate_up_proj.weight",
            "model.layers.0.mlp.down_proj.weight"} <= set(sd)
    assert {pre.format(0) + n for n in (
        "in_proj.weight", "conv1d.weight", "conv1d.bias", "x_proj.weight",
        "dt_proj.weight", "dt_proj.bias", "A_log", "D", "out_proj.weight")
        } == {k for k in sd if k.startswith(pre.format(0))}
    assert {pre.format(1) + n for n in (
        "Wqkv.weight", "Wqkv.bias", "out_proj.weight", "out_proj.bias",
        "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln.weight")
        } == {k for k in sd if k.startswith(pre.format(1))}
    assert {k for k in sd if k.startswith(pre.format(4))} == {
        pre.format(4) + "in_proj.weight", pre.format(4) + "out_proj.weight"}
    assert sd[pre.format(0) + "conv1d.weight"].shape == (64, 1, 4)
    assert sd[pre.format(0) + "x_proj.weight"].shape == (2 + 32, 64)
    assert sd[pre.format(1) + "Wqkv.weight"].shape == (64, 32)
    assert sd[pre.format(5) + "Wqkv.weight"].shape == (32, 32)   # q alone
    back = hf_to_params(sd, cfg)
    for name, x, y in _leafwise(params, back):
        np.testing.assert_array_equal(x, y, err_msg=name)


def test_the_exporter_permutes_the_query_heads_to_the_published_pairs():
    """Inside, a block keeps its query heads as the core reads them
    (key-value pair, map, query pair); outside, heads 2j and 2j + 1 are
    pair j."""
    assert M.diff_core_order(8, 4) == (0, 2, 1, 3, 4, 6, 5, 7)
    assert M.diff_core_order(40, 20)[:8] == (0, 2, 1, 3, 4, 6, 5, 7)
    assert M.diff_core_order(8, 2) == (0, 2, 4, 6, 1, 3, 5, 7)
    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    inside = np.asarray(params["layers"][1]["attn"]["wqkv"])[:, :32]
    outside = params_to_hf(params, cfg)["model.layers.1.attn.Wqkv.weight"].T
    heads_in = inside.reshape(32, 8, 4)
    heads_out = outside[:, :32].reshape(32, 8, 4)
    for place, published in enumerate(M.diff_core_order(8, 4)):
        np.testing.assert_array_equal(heads_in[:, place],
                                      heads_out[:, published])
    np.testing.assert_array_equal(
        np.asarray(params["layers"][1]["attn"]["wqkv"])[:, 32:],
        outside[:, 32:])


def test_the_adapter_reads_the_published_config():
    cfg = populate_model_args_from_hf(PUBLISHED)
    from collections import Counter

    assert Counter(cfg.layer_types) == {
        "mamba1": 9, "sliding_attention": 8, "full_attention": 1, "gmu": 7,
        "cross_attention": 7}
    assert cfg.layer_types[14:20] == TYPES
    assert (cfg.hidden_size, cfg.head_dim, cfg.kv_heads, cfg.ffn_dim) == (
        2560, 64, 20, 10240)
    assert (cfg.mamba1_d_inner, cfg.mamba1_rank, cfg.mamba1_d_state) == (
        5120, 160, 16)
    assert cfg.differential_attention and cfg.sliding_window == 512
    assert cfg.add_qkv_bias and cfg.add_attn_out_bias
    assert not cfg.add_bias_linear
    assert (cfg.normalization, cfg.position_embedding_type,
            cfg.layernorm_epsilon) == ("layernorm", "nope", 1e-5)
    with pytest.raises(NotImplementedError, match="mb_per_layer=3"):
        populate_model_args_from_hf({**PUBLISHED, "mb_per_layer": 3})
    assert phi4flash_layer_types(4) == [
        "mamba1", "sliding_attention", "mamba1", "full_attention"]


def test_the_zoo_yaml_is_the_adapters_model():
    yaml = load_config(os.path.join(ZOO, "phi-4-mini-flash.yaml"),
                       mode="train_dist").model
    adapted = populate_model_args_from_hf(PUBLISHED)
    for key in ("hidden_size", "num_hidden_layers", "layer_types",
                "num_attention_heads", "num_key_value_heads", "vocab_size",
                "sliding_window", "differential_attention",
                "mamba1_d_state", "mamba1_d_conv", "mamba1_expand",
                "normalization", "layernorm_epsilon", "hidden_act",
                "position_embedding_type", "tie_word_embeddings",
                "add_bias_linear", "add_qkv_bias", "add_attn_out_bias",
                "hf_layout", "max_position_embeddings"):
        assert getattr(yaml, key) == getattr(adapted, key), key
    assert yaml.ffn_dim == adapted.ffn_dim
    assert yaml.mamba1_rank == adapted.mamba1_rank == 160


def test_the_cut_cell_counts_the_issues_parameters():
    """Published blocks 14 to 19 over an eighth of the vocabulary, by
    ``jax.eval_shape`` (nothing is allocated): the arithmetic of PERF.md
    section 4, block by block."""
    cfg = load_config(os.path.join(ZOO, "phi-4-mini-flash.yaml"),
                      mode="train_dist").model.model_copy(update={
        "num_hidden_layers": 6, "layer_types": TYPES, "vocab_size": 25008,
        "make_vocab_size_divisible_by": 1})
    shapes = jax.eval_shape(lambda k: init_causal_lm(k, cfg)[0],
                            jax.random.key(0))
    blocks = [param_count(lp) for lp in shapes["layers"]]
    assert blocks == [119_895_040, 98_322_304, 119_895_040, 98_322_304,
                      104_867_840, 91_766_144]
    assert param_count(shapes) == 697_094_272


def test_the_telemetry_count_by_hand():
    from hetu_galvatron_tpu.core.cost_model.cost import model_flops_per_token

    cfg = ModelArgs(**TINY)
    h, s, nd, kd, di, f = 32, 21, 32, 16, 64, 48
    maps = {"mamba1": 2 * (h * 2 * di + di * (2 + 32) + 2 * di + di * h),
            "gmu": 2 * 2 * h * di,
            "attn": 2 * h * nd + 2 * 2 * h * kd + 2 * nd * h,
            "cross": 2 * h * nd + 2 * nd * h}
    core = lambda span: 2 * 3 * span * nd   # q k^T, and P V two heads wide
    forward = (2 * maps["mamba1"] + maps["gmu"]
               + maps["attn"] + core(6) + maps["attn"] + core(s)
               + maps["cross"] + core(s)
               + 6 * 3 * 2 * h * f + 2 * h * 64)
    assert model_flops_per_token(cfg) == 3.0 * forward


# ---------------------------------------------------------------------------
# (e) what is not built is refused by name
# ---------------------------------------------------------------------------


def _args(*overrides):
    return args_from_cli(
        [os.path.join(ZOO, "phi-4-mini-flash.yaml"),
         "model.hidden_size=32", "model.num_hidden_layers=6",
         "model.layer_types=[" + ",".join(TYPES) + "]",
         "model.num_attention_heads=8", "model.num_key_value_heads=4",
         "model.ffn_hidden_size=48", "model.vocab_size=64",
         "model.seq_length=16", "model.max_position_embeddings=64",
         "model.make_vocab_size_divisible_by=1", "model.sliding_window=6",
         "model.mamba1_dt_rank=2", "parallel.mixed_precision=fp32",
         "parallel.global_train_batch_size=8", *overrides],
        mode="train_dist")


@pytest.mark.parametrize("override,said", [
    ("parallel.global_tp_deg=2", "tp=2"),
    ("parallel.global_cp_deg=2", "cp=2"),
])
def test_a_plan_that_cuts_the_blocks_is_refused_by_name(override, said):
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    with pytest.raises(ValueError) as err:
        get_hybrid_parallel_config(_args(override), 8)
    assert "block 0 is a mamba1 block" in str(err.value)
    assert said in str(err.value)
    # dp and ZeRO-3 are what it runs under
    hpc = get_hybrid_parallel_config(_args("parallel.sdp=1"), 8)
    assert hpc.layers[0].dp_size == 8


def test_the_reasons_name_each_kind_of_cut():
    cfg = _args().model

    class S:
        sp, cp_size, tp_size = False, 1, 1

    class Cut(S):
        tp_size = 2

    whole = [S] * 6
    assert eligibility.shared_plan_reason(cfg, whole) is None
    assert eligibility.mamba1_plan_reason(cfg, whole) is None
    said = eligibility.shared_plan_reason(cfg, whole, pp_deg=2)
    assert "pp=2" in said and "blocks 2 to 5" in said
    said = eligibility.shared_plan_reason(cfg, whole[:4] + [Cut, S])
    assert "block 4 (gmu) has tp=2" in said
    said = eligibility.window_plan_reason(cfg, whole[:5] + [Cut])
    assert "block 5 (cross_attention)" in said
    assert "differential_attention=True" in said
    # a model without such blocks is nobody's business here
    plain = ModelArgs(hidden_size=32, num_hidden_layers=2,
                      num_attention_heads=4)
    assert eligibility.shared_plan_reason(plain, [Cut, Cut], 2) is None
    assert eligibility.block_attention_stated(plain) == []
    for kind in ("mamba1", "gmu", "cross_attention"):
        assert kind in eligibility.MIXER_OVERLAP_REASON
        assert hasattr(eligibility, M.MIXERS[kind].uncut_reason)


def _refuse_pp():
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    try:
        get_hybrid_parallel_config(_args(
            "parallel.pp_deg=2", "parallel.chunks=2",
            "parallel.pipeline_type=pipedream_flush"), 2)
    except ValueError as e:
        raise NotImplementedError(str(e))


def _refuse_host_pipeline():
    from hetu_galvatron_tpu.analysis.eligibility import mixed_stack_reason

    raise NotImplementedError(mixed_stack_reason(
        _args().model, "the host pipeline engine"))


def _refuse_generate():
    from hetu_galvatron_tpu.models.generate import generate

    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    generate(params, jnp.zeros((1, 4), jnp.int32), cfg, max_new_tokens=2)


def _refuse_serving():
    from hetu_galvatron_tpu.serving import engine

    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    engine._check_supported(cfg, params)


@pytest.mark.parametrize("engine,said", [
    (_refuse_pp, "pp=2"),
    (_refuse_host_pipeline, "the host pipeline engine"),
    (_refuse_generate, "generate()"),
    (_refuse_serving, "ServingEngine"),
])
def test_what_cannot_take_the_stack_says_so(engine, said):
    with pytest.raises(NotImplementedError) as err:
        engine()
    assert said in str(err.value)
    if said != "pp=2":
        assert "2 x mamba1/dense" in str(err.value)
        assert "1 x gmu/dense, 1 x cross_attention/dense" in str(err.value)


def test_a_uniform_differential_stack_is_no_plain_attention_stack():
    """Every block ``full_attention``, so the kinds alone would pass for a
    stack ``generate()`` can run: the pairing of heads is stated."""
    from hetu_galvatron_tpu.models.generate import generate

    cfg = ModelArgs(**{**TINY, "num_hidden_layers": 2, "layer_types": None})
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    with pytest.raises(NotImplementedError,
                       match="differential_attention=True"):
        generate(params, jnp.zeros((1, 4), jnp.int32), cfg, max_new_tokens=2)


@pytest.mark.parametrize("types,said", [
    (TYPES, "through a mamba1 block: the convolution's history and the "
            "carried state would cross"),
    (["full_attention", "cross_attention"],
     "a cross_attention block takes no rotation, no packed documents"),
    (["full_attention", "sliding_attention"], None),
])
def test_packed_documents_through_the_stack(types, said):
    cfg = ModelArgs(**{**TINY, "num_hidden_layers": len(types),
                       "layer_types": types})
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    batch = {**_batch(), "segment_ids": jnp.zeros((2, 21), jnp.int32)}
    if said is None:   # the attending kinds take them
        assert np.isfinite(float(causal_lm_loss(
            params, batch, cfg, compute_dtype=jnp.float32)))
        return
    with pytest.raises(NotImplementedError) as err:
        causal_lm_loss(params, batch, cfg, compute_dtype=jnp.float32)
    assert said in str(err.value)


@pytest.mark.parametrize("kind", sorted(M.MIXERS))
def test_every_kind_that_does_not_attend_says_what_crosses_documents(kind):
    row = M.MIXERS[kind]
    assert row.attends == (row.crosses_documents is None)
    # what a kind leaves or takes is the schema's table, by kind
    from hetu_galvatron_tpu.core.args_schema import SHARED_VALUES

    assert row.takes == SHARED_VALUES.get(kind, ((),))[0]
    makers = {maker: names for names, maker in SHARED_VALUES.values()}
    assert row.leaves == makers.get(kind, ())
