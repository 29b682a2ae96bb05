"""LFM2 (LiquidAI/LFM2-24B-A2B, ``lfm2_moe``): gated short-convolution
blocks among GQA blocks from one per-layer description, the q/k norm per
head, sigmoid routing with a selection bias, an expert layer that is told
which experts it holds, its checkpoint names, and the program against the
benchmark's plain reference. CPU, fp32, tiny widths."""

import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs, TrainArgs
from hetu_galvatron_tpu.core.arguments import load_config
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.models.builder import (
    causal_lm_loss,
    forward_causal_lm,
    init_causal_lm,
)
from hetu_galvatron_tpu.models.moe import init_moe_decoder_layer
from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params, params_to_hf
from hetu_galvatron_tpu.runtime.dataloader import make_batch

pytestmark = [pytest.mark.model]

ZOO = os.path.join(os.path.dirname(M.__file__), "configs")
TYPES = ["conv", "full_attention", "conv", "conv", "conv"]
# one dense block, then four with experts; every expert held
TINY = dict(
    model_type="moe", hf_layout="lfm2", hidden_size=32, num_hidden_layers=5,
    layer_types=TYPES, num_dense_layers=1, conv_L_cache=3,
    num_attention_heads=4, num_key_value_heads=2, ffn_hidden_size=48,
    moe_ffn_hidden_size=24, vocab_size=64, max_position_embeddings=32,
    seq_length=16, hidden_act="swiglu", normalization="rmsnorm",
    layernorm_epsilon=1e-5, position_embedding_type="rope",
    rope_theta=1e6, tie_word_embeddings=True, add_bias_linear=False,
    add_qkv_bias=False, make_vocab_size_divisible_by=1, qk_norm=True,
    qk_norm_per_head=True, num_experts=8, moe_topk=4,
    moe_score_function="sigmoid", moe_norm_topk_prob=True,
    moe_router_enable_expert_bias=True, moe_hf_layout="lfm2",
    moe_dispatcher="dropless", moe_aux_loss_coeff=0.0, use_flash_attn=False)

# the configuration's file as benchmark/reference/lfm2_moe.py reads it
REF_CFG = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 24,
    "layer_types": TYPES, "num_hidden_layers": 5, "num_dense_layers": 1,
    "conv_L_cache": 3, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_experts": 8, "num_routed_experts": 8, "first_expert_held": 0,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"}}


def _family():
    from benchmark import reference

    return reference.load_family("lfm2_moe")


def _seeded(cfg, key=7):
    """Seeded random weights with norm scales that are not all ones and a
    nonzero expert bias, so that a norm or a bias left out shows."""
    params, _ = init_causal_lm(jax.random.key(key), cfg)

    def shake(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.key(len(name) + 13 * sum(map(ord, name)))
        if "norm" in name or "ln" in name:
            return x + 0.3 * jax.random.normal(k, x.shape)
        if "expert_bias" in name:
            return 0.2 * jax.random.normal(k, x.shape)
        return x
    return jax.tree_util.tree_map_with_path(shake, params)


# ---------------------------------------------------------------------------
# (a) Hugging Face parity for what transformers has: conv and attention
# blocks with dense MLPs
# ---------------------------------------------------------------------------

HF_TYPES = ["conv", "conv", "full_attention", "conv"]
HF_TINY = {k: v for k, v in TINY.items() if not k.startswith("moe_")
           and k not in ("num_experts", "num_dense_layers")}
HF_TINY.update(model_type="llama", num_hidden_layers=4, layer_types=HF_TYPES,
               moe_hf_layout="lfm2")


def _hf_lfm2():
    torch = pytest.importorskip("torch")
    from transformers import Lfm2Config, Lfm2ForCausalLM

    hf_cfg = Lfm2Config(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=32, norm_eps=1e-5, rope_theta=1e6,
        conv_bias=False, conv_L_cache=3, block_auto_adjust_ff_dim=False,
        layer_types=HF_TYPES, tie_word_embeddings=True)
    torch.manual_seed(0)
    hf = Lfm2ForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for name, t in hf.named_parameters():
            if "norm" in name:   # a fresh model's scales are all ones
                t.add_(0.3 * torch.randn_like(t))
            if name.endswith("conv.conv.weight"):
                t.copy_(0.4 * torch.randn_like(t))
    return torch, hf


def test_lfm2_hf_logit_parity():
    """A random tiny ``Lfm2ForCausalLM`` (conv and attention blocks, dense
    MLPs) through ``hf_to_params`` gives HF's logits; with the taps
    reversed it does not."""
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    torch, hf = _hf_lfm2()
    cfg = populate_model_args_from_hf(hf.config).model_copy(update=dict(
        seq_length=16, make_vocab_size_divisible_by=1, use_flash_attn=False))
    assert cfg.model_dump() == ModelArgs(**{
        **HF_TINY, "model_name": cfg.model_name}).model_dump()
    params = hf_to_params(hf.state_dict(), cfg)
    assert [("conv" in lp, "attn" in lp) for lp in params["layers"]] == [
        (True, False), (True, False), (False, True), (True, False)]
    tokens_np = np.random.RandomState(0).randint(0, 64, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens_np)).logits.numpy()
    forward = jax.jit(lambda p, t: forward_causal_lm(
        p, t, cfg, compute_dtype=jnp.float32))
    ours = forward(params, jnp.asarray(tokens_np))
    # tolerance: fp32 torch against fp32 XLA through four blocks; the
    # logits are of order 0.5
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-4, atol=5e-5)
    reversed_taps = {**params, "layers": tuple(
        {**lp, "conv": {**lp["conv"], "taps": lp["conv"]["taps"][:, ::-1]}}
        if "conv" in lp else lp for lp in params["layers"])}
    wrong = forward(reversed_taps, jnp.asarray(tokens_np))
    assert np.abs(np.asarray(wrong) - ref).max() > 1e-3


def test_lfm2_hf_roundtrip():
    """``params_to_hf(hf_to_params(sd))`` is ``sd``: LFM2's names
    (``conv.in_proj``, ``conv.conv``, ``operator_norm``, ``ffn_norm``,
    ``self_attn.out_proj``, ``self_attn.{q,k}_layernorm``,
    ``feed_forward.w1|w3|w2``, ``model.embedding_norm``) and the same bits.
    HF lists the tied head as ``lm_head.weight`` too; the exporter writes a
    tied embedding once."""
    _, hf = _hf_lfm2()
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    np.testing.assert_array_equal(sd.pop("lm_head.weight"),
                                  sd["model.embed_tokens.weight"])
    cfg = ModelArgs(**HF_TINY)
    back = params_to_hf(hf_to_params(sd, cfg), cfg)
    assert sorted(back) == sorted(sd)
    for name in ("model.layers.0.conv.conv.weight",
                 "model.layers.2.self_attn.q_layernorm.weight",
                 "model.layers.3.feed_forward.w3.weight",
                 "model.embedding_norm.weight"):
        assert name in back
    for k in sd:
        np.testing.assert_array_equal(back[k], sd[k], err_msg=k)


def test_held_experts_are_exported_under_their_published_indices():
    cfg = ModelArgs(**{**TINY, "moe_held_experts": 2,
                       "moe_first_held_expert": 4})
    params = _seeded(cfg)
    sd = params_to_hf(params, cfg)
    pre = "model.layers.1.feed_forward."
    assert sorted(k for k in sd if k.startswith(pre + "experts.")
                  and k.endswith("w1.weight")) == [
        pre + "experts.4.w1.weight", pre + "experts.5.w1.weight"]
    assert sd[pre + "gate.weight"].shape == (8, 32)       # all 8 scored
    assert sd[pre + "expert_bias"].shape == (8,)
    back = hf_to_params(sd, cfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_exporter_names_the_kinds_it_knows():
    from hetu_galvatron_tpu.runtime import checkpoint

    err = checkpoint._unknown_mixer(3, "scan")
    for word in ("block 3", "'scan'", "full_attention", "conv", "dense",
                 "experts"):
        assert word in str(err)


# ---------------------------------------------------------------------------
# (b) the program against the benchmark's plain reference, and the controls
# ---------------------------------------------------------------------------

# the program in bfloat16 (what the cell computes in) against the float32
# reference: the loss alone, of order 4.2, within bf16's eight bits
BF16_LOSS = 2e-2
CONTROLS = ["as_published", "as_published_bf16", "taps_reversed",
            "gate_c_left_out",
            "qk_norm_over_the_whole_width", "softmax_for_sigmoid",
            "weights_not_renormalised", "top3_for_top4", "bias_left_out"]


def _reference_loss(ref, ref_cfg, batch):
    """``loss(weights)`` of the plain reference, one program a call (op by
    op a side is some thousands of compiles)."""
    return jax.jit(lambda w: ref.nll_sum(
        w, ref_cfg, batch["tokens"],
        batch["labels"]) / batch["labels"].size)


@pytest.mark.parametrize("case", CONTROLS)
def test_program_matches_plain_reference(case, monkeypatch):
    """Loss and gradients of the program against
    ``benchmark/reference/lfm2_moe.py`` on seeded random weights through the
    exporter (one dense and four sparse blocks, a nonzero expert bias, every
    expert held); the program's gradient tree goes through the same exporter
    and meets ``jax.grad`` of the reference's ``nll_sum``. Each control
    breaks one equation on one side and FAILS the comparison."""
    ref = _family()
    cfg = ModelArgs(**TINY)
    params = _seeded(cfg)
    batch = jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(3).randint(0, 64, (1, 17))))
    weights = {k: jnp.asarray(v) for k, v in params_to_hf(params, cfg).items()}

    run_cfg, run_params = cfg, params
    if case == "taps_reversed":
        run_params = {**params, "layers": tuple(
            {**lp, "conv": {**lp["conv"],
                            "taps": lp["conv"]["taps"][:, ::-1]}}
            if "conv" in lp else lp for lp in params["layers"])}
    if case == "gate_c_left_out":
        def no_gate_c(a, w, p, taps):
            S = a.shape[1]
            b, _, x = jnp.split(a @ w[p + "in_proj.weight"].T, 3, axis=-1)
            u, kernel = b * x, w[p + "conv.weight"][:, 0, :]
            c = sum(kernel[:, j] * jnp.pad(
                u, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :S]
                for j in range(taps))
            return c @ w[p + "out_proj.weight"].T
        monkeypatch.setattr(ref, "short_conv", no_gate_c)
    if case == "qk_norm_over_the_whole_width":
        run_cfg = cfg.model_copy(update=dict(qk_norm_per_head=False))
        run_params = {**params, "layers": tuple(
            {**lp, "attn": {
                **lp["attn"],
                "q_norm": {"scale": jnp.tile(lp["attn"]["q_norm"]["scale"],
                                             cfg.num_attention_heads)},
                "k_norm": {"scale": jnp.tile(lp["attn"]["k_norm"]["scale"],
                                             cfg.kv_heads)}}}
            if "attn" in lp else lp for lp in params["layers"])}
    if case == "softmax_for_sigmoid":
        run_cfg = cfg.model_copy(update=dict(moe_score_function="softmax"))
    if case == "weights_not_renormalised":
        run_cfg = cfg.model_copy(update=dict(moe_norm_topk_prob=False))
    if case == "top3_for_top4":
        run_cfg = cfg.model_copy(update=dict(moe_topk=3))
    if case == "bias_left_out":
        monkeypatch.setitem(REF_CFG, "use_expert_bias", False)

    ref_loss = _reference_loss(ref, REF_CFG, batch)

    def run_loss(dtype):
        return lambda p: causal_lm_loss(p, batch, run_cfg,
                                        compute_dtype=dtype)
    if case != "as_published":
        # bfloat16 and the controls are told by the loss alone
        bf16 = case == "as_published_bf16"
        got = jax.jit(run_loss(jnp.bfloat16 if bf16 else jnp.float32))(
            run_params)
        apart = abs(float(got) - float(ref_loss(weights)))
        assert (apart < BF16_LOSS if bf16 else apart >= 2e-5), (
            case, float(got), apart)
        return
    want, want_grads = jax.jit(jax.value_and_grad(ref_loss))(weights)
    got, got_grads = jax.jit(jax.value_and_grad(run_loss(jnp.float32)))(
        run_params)
    # tolerance: both sides are fp32 on the CPU and differ in operation
    # order only (fused qkv and gate|up products, grouped against
    # all-experts matmuls, three shifted products against the same three).
    # The loss is of order 4.2, gradients up to 0.1
    assert abs(float(got) - float(want)) < 2e-5, (float(got), float(want))
    got_grads = params_to_hf(got_grads, cfg)
    assert sorted(got_grads) == sorted(want_grads)
    for k in want_grads:
        if k.endswith("expert_bias"):
            # the bias takes no gradient of the loss; what the program's
            # tree carries on its path is the balance update (moe.py)
            assert float(jnp.max(jnp.abs(want_grads[k]))) == 0.0
            continue
        np.testing.assert_allclose(got_grads[k], want_grads[k], rtol=2e-4,
                                   atol=2e-6, err_msg=k)


@pytest.mark.parametrize("shift", [0, 1])
def test_program_matches_reference_on_a_share(shift):
    """The same comparison where the layer holds experts [2, 6) of 8; told
    a range one expert further along, on the same weights, it fails."""
    ref = _family()
    cfg = ModelArgs(**{**TINY, "moe_held_experts": 4,
                       "moe_first_held_expert": 2})
    ref_cfg = {**REF_CFG, "num_experts": 4, "first_expert_held": 2}
    params = _seeded(cfg)
    batch = jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(4).randint(0, 64, (1, 17))))
    weights = {k: jnp.asarray(v) for k, v in params_to_hf(params, cfg).items()}
    want = _reference_loss(ref, ref_cfg, batch)(weights)
    run_cfg = cfg.model_copy(update=dict(moe_first_held_expert=2 + shift))
    got = jax.jit(lambda p: causal_lm_loss(
        p, batch, run_cfg, compute_dtype=jnp.float32))(params)
    assert (abs(float(got) - float(want)) < 2e-5) == (shift == 0)


# ---------------------------------------------------------------------------
# (e) today's configurations resolve to the uniform description and build the
# trees they built
# ---------------------------------------------------------------------------

TODAY = sorted(os.path.basename(p) for p in glob.glob(
    os.path.join(ZOO, "*.yaml"))
    if not any(word in p for word in ("lfm2", "t5", "granite", "xing",
                                      "kimi", "laguna", "mellum", "phi-4",
                                      "nemotron", "olmo-hybrid")))


def _the_parents_tree(key, cfg):
    """``init_causal_lm`` as the commit before the per-layer description
    wrote it: experts every ``moe_layer_freq``-th block, attention in all."""
    n = cfg.num_hidden_layers
    keys = jax.random.split(key, n + 2)
    freq = max(cfg.moe_layer_freq, 1)
    layers = [
        (init_moe_decoder_layer(keys[1 + i], cfg)
         if cfg.num_experts and (i + 1) % freq == 0
         else M.init_decoder_layer(keys[1 + i], cfg))[0] for i in range(n)]
    return {"embed": M.init_embedding(keys[0], cfg)[0],
            "layers": tuple(layers),
            "prenorm": {} if cfg.post_norm else M.init_norm(cfg)[0],
            "head": M.init_lm_head(keys[n + 1], cfg)[0]}


@pytest.mark.parametrize("yaml", TODAY)
def test_todays_configurations_resolve_to_the_uniform_description(yaml):
    """At the published sizes (shapes only): one kind of block, attention
    in every one, and the tree the parent built, name for name and leaf for
    leaf."""
    cfg = load_config(os.path.join(ZOO, yaml)).model
    kinds = cfg.block_kinds()
    assert {m for m, _ in kinds} == {"full_attention"}
    assert len(set(kinds)) == 1 or cfg.moe_layer_freq > 1
    assert (cfg.layer_types, cfg.num_dense_layers, cfg.moe_held_experts,
            cfg.qk_norm_per_head, cfg.moe_score_function) == (
        None, 0, 0, False, "softmax")
    got = jax.eval_shape(lambda k: init_causal_lm(k, cfg)[0],
                         jax.random.key(0))
    want = jax.eval_shape(lambda k: _the_parents_tree(k, cfg),
                          jax.random.key(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype), \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("yaml", ["gpt2-xl.yaml", "mistral-7b.yaml",
                                  "olmoe-1b-7b.yaml", "mixtral-8x7b.yaml"])
def test_todays_trees_hold_the_same_values_at_a_tiny_size(yaml):
    base = load_config(os.path.join(ZOO, yaml)).model
    cfg = base.model_copy(update=dict(
        hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4 if base.num_key_value_heads else None,
        ffn_hidden_size=48, vocab_size=64, max_position_embeddings=32,
        seq_length=16, make_vocab_size_divisible_by=1,
        num_experts=4 if base.num_experts else 0))
    got, _ = init_causal_lm(jax.random.key(3), cfg)
    want = _the_parents_tree(jax.random.key(3), cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_published_yaml_is_the_published_model():
    import json

    from benchmark import manifest
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    cfg = load_config(os.path.join(ZOO, "lfm2-24b-a2b.yaml")).model
    # the catalog's config.json, as the benchmark's configuration keeps it,
    # with the cut taken back: the adapter reads the YAML's model out of it
    body = manifest.read_json(os.path.join(
        manifest.ROOT, "benchmark", "configs", "lfm2-24b-a2b-ep8.json"))
    published = {**{k: v for k, v in body.items()
                    if not isinstance(v, (dict, list)) or k == "rope_parameters"},
                 **{k: v for k, v in body["reduced_from"].items()
                    if k != "layer_types"},
                 "layer_types": (["conv", "conv", "full_attention", "conv"]
                                 * 10)}
    published.pop("head_dim")   # null in config.json; 64 is the file's note
    assert json.dumps(published["rope_parameters"])
    read = populate_model_args_from_hf(published).model_copy(update=dict(
        model_name=cfg.model_name, seq_length=cfg.seq_length))
    assert read.model_dump() == cfg.model_dump()
    kinds = cfg.block_kinds()
    assert len(kinds) == 40
    assert [m for m, _ in kinds[:4]] == ["conv", "conv", "full_attention",
                                         "conv"]
    assert sum(m == "full_attention" for m, _ in kinds) == 10
    assert [ff for _, ff in kinds] == ["dense"] * 2 + ["experts"] * 38
    assert (cfg.head_dim, cfg.held_experts, cfg.moe_topk) == (64, 64, 4)
    with pytest.raises(ValueError, match="names 40 blocks"):
        cfg.model_copy(update=dict(num_hidden_layers=5)).block_kinds()


# ---------------------------------------------------------------------------
# (f) the conv block and the per-head norm at tp2
# ---------------------------------------------------------------------------


def test_conv_block_and_per_head_norm_at_tp2_equal_one_device(cpu_devices):
    """A conv/dense block and an attention/dense block with the q/k norm
    per head, tp2 x dp2 on the CPU mesh against the single-device step:
    the channel axis of the convolution's thirds, taps and ``out_proj``
    rows shards over tp, and a head's norm is local to its shard."""
    import optax

    from hetu_galvatron_tpu.parallel.spmd import (
        make_spmd_train_step,
        shard_params,
    )
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.runtime.mesh import build_mesh
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer

    cfg = ModelArgs(**{**HF_TINY, "num_hidden_layers": 2,
                       "layer_types": ["conv", "full_attention"]})
    train = TrainArgs(lr=1e-2, clip_grad=1.0, weight_decay=0.0,
                      lr_decay_style="constant", lr_warmup_iters=0)
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    batch = jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(0).randint(0, 64, (4, 17))))
    tx = make_optimizer(train)

    # (one program: op by op the model and Adam are hundreds of compiles)
    @jax.jit
    def ref_step(params):
        loss, grads = jax.value_and_grad(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.float32))(params)
        upd, _ = tx.update(grads, tx.init(params), params)
        return loss, optax.apply_updates(params, upd)

    ref_loss, ref_params = ref_step(params)

    args = CoreArgs(model=cfg.model_dump(), train=train.model_dump())
    args.parallel.global_tp_deg = 2
    args.parallel.global_train_batch_size = 4
    args.parallel.chunks = 1
    hpc = get_hybrid_parallel_config(args, 4)
    mesh = build_mesh(4, 1, devices=cpu_devices[:4])
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        cfg, hpc, mesh, axes, tx, params, compute_dtype=jnp.float32,
        donate=False)
    conv = pspecs["layers"][0]["conv"]
    assert conv["win"][2] is not None and conv["taps"][0] is not None \
        and conv["wout"][0] is not None          # the channel axis, on tp
    sp = shard_params(params, pspecs, mesh)
    opt = jax.jit(tx.init, out_shardings=jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))(sp)
    new_p, _, metrics = step(sp, opt, jax.device_put(batch, batch_shd))
    # tolerance: fp32, the contractions over the sharded channel and head
    # axes summed across two shards
    assert abs(float(metrics["loss"]) - float(ref_loss)) < 2e-5
    for (pa, a), b in zip(jax.tree_util.tree_leaves_with_path(ref_params),
                          jax.tree.leaves(new_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                                   atol=3e-4, err_msg=jax.tree_util.keystr(pa))


# ---------------------------------------------------------------------------
# (g) what cannot take a mixed stack says so
# ---------------------------------------------------------------------------

MIXED = ModelArgs(**TINY)
DENSE_MIXED = ModelArgs(**{**HF_TINY, "num_hidden_layers": 2,
                           "layer_types": ["conv", "full_attention"]})
KINDS_SAID = "conv/dense.*full_attention/experts.*conv/experts"


def _pp2(cfg):
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    args = CoreArgs(model=cfg.model_dump())
    args.parallel.pp_deg = 2
    args.parallel.global_train_batch_size = 4
    args.parallel.chunks = 2
    args.parallel.pipeline_type = "pipedream_flush"
    return args, get_hybrid_parallel_config(args, 2)


def _refuse_compiled_pipeline():
    from hetu_galvatron_tpu.analysis.eligibility import (
        compiled_unsupported_reason,
    )

    _, hpc = _pp2(DENSE_MIXED)
    reason = compiled_unsupported_reason(DENSE_MIXED, hpc)
    assert reason is not None
    raise NotImplementedError(reason)


def _refuse_host_pipeline():
    from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine

    args, hpc = _pp2(MIXED)
    PipelineEngine(MIXED, hpc, args.train, devices=jax.devices()[:2])


def _refuse_generate():
    from hetu_galvatron_tpu.models.generate import _check_supported

    _check_supported(DENSE_MIXED, init_causal_lm(jax.random.key(0),
                                                 DENSE_MIXED)[0])


def _refuse_serving():
    from hetu_galvatron_tpu.serving.engine import _check_supported

    _check_supported(DENSE_MIXED, init_causal_lm(jax.random.key(0),
                                                 DENSE_MIXED)[0])


def _refuse_search():
    from hetu_galvatron_tpu.utils.hf_config_adapter import model_layer_configs

    model_layer_configs(MIXED)


def _refuse_model_profiler():
    from hetu_galvatron_tpu.core.profiler.model_profiler import ModelProfiler

    ModelProfiler(CoreArgs(model=MIXED.model_dump()),
                  devices=jax.devices()[:1])


@pytest.mark.parametrize("engine,said", [
    (_refuse_compiled_pipeline, "compiled pipeline engine.*conv/dense"),
    (_refuse_host_pipeline, "host pipeline engine.*" + KINDS_SAID),
    (_refuse_generate, r"generate\(\).*conv/dense.*full_attention/dense"),
    (_refuse_serving, "ServingEngine.*conv/dense"),
    (_refuse_search, "the search.*" + KINDS_SAID),
    (_refuse_model_profiler, "model profiler.*" + KINDS_SAID),
], ids=lambda v: getattr(v, "__name__", None))
def test_what_cannot_take_a_mixed_stack_names_the_block_kinds(engine, said):
    with pytest.raises(NotImplementedError, match=said):
        engine()


def test_tp_overlap_leaves_a_conv_block_to_gspmd_with_a_reason():
    from hetu_galvatron_tpu.analysis.eligibility import (
        CONV_REASON,
        MOE_REASON,
        plan_overlap_reasons,
    )
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    args = CoreArgs(model=MIXED.model_dump())
    args.parallel.global_tp_deg = 2
    reasons = dict(plan_overlap_reasons(
        MIXED, get_hybrid_parallel_config(args, 2)))
    assert reasons[0] == CONV_REASON and reasons[1] == MOE_REASON


def test_a_uniform_stack_is_refused_by_none_of_them():
    from hetu_galvatron_tpu.analysis.eligibility import mixed_stack_reason

    dense = ModelArgs(hidden_size=32, num_hidden_layers=2,
                      num_attention_heads=2, vocab_size=64)
    moe = ModelArgs(**{**TINY, "layer_types": None, "num_dense_layers": 0,
                       "moe_layer_freq": 2, "num_hidden_layers": 4})
    assert mixed_stack_reason(dense, "x") is None
    assert mixed_stack_reason(moe, "x") is not None
    assert mixed_stack_reason(moe, "x", feed_forward_may_differ=True) is None


def test_a_conv_block_refuses_packed_documents_and_sigmoid_an_aux_loss():
    cfg = ModelArgs(**TINY)
    params = _seeded(cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(NotImplementedError, match="conv block"):
        forward_causal_lm(params, tokens, cfg, compute_dtype=jnp.float32,
                          segment_ids=jnp.zeros((1, 16), jnp.int32))
    with pytest.raises(ValueError, match="sigmoid router scores"):
        forward_causal_lm(params, tokens, cfg.model_copy(update=dict(
            moe_aux_loss_coeff=0.01)), compute_dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="dropless dispatcher"):
        init_causal_lm(jax.random.key(0), cfg.model_copy(update=dict(
            moe_held_experts=2, moe_dispatcher="capacity")))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_held_shares_forward_and_loss_are_the_parents_formulation(
        dtype, forward_and_loss_as_before_pr38):
    """The stack with four expert layers that each hold 2 of 8 experts: the
    first chunk and the counted loop of ``_held_dispatch`` in the program,
    as many passes taken as the count asks for."""
    cfg = ModelArgs(**{**TINY, "moe_held_experts": 2,
                       "moe_first_held_expert": 2})
    forward_and_loss_as_before_pr38(cfg, _seeded(cfg), dtype)


@pytest.mark.parametrize("dtype,loss_band,grad_band", [
    ("float32", 2e-5, 1e-4), ("bfloat16", 2e-2, 5e-2)])
def test_a_block_through_the_convolutions_kernels_is_the_block(
        conv_kernels_are_the_block, dtype, loss_band, grad_band):
    """A conv block of 128 channels over 300 positions: its two gates and
    three taps in the kernels of ``ops/pallas/conv.py`` (interpret mode)
    against the ``jax.numpy`` form."""
    cfg = ModelArgs(**{**TINY, "hidden_size": 128, "seq_length": 300,
                       "max_position_embeddings": 512})
    params, _ = M.init_short_conv(jax.random.key(5), cfg)
    x = jax.random.normal(jax.random.key(6), (2, 300, 128))
    conv_kernels_are_the_block(
        lambda p, a, conv_fn: M.apply_short_conv(
            p, a, cfg, compute_dtype=jnp.dtype(dtype), conv_fn=conv_fn),
        params, x, dtype, loss_band, grad_band)
