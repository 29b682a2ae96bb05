"""OLMoE (allenai/OLMoE-1B-7B): the q/k norm over the whole projected width,
router weights that are not renormalised, its checkpoint names, and the
program against the benchmark's plain reference. CPU, fp32, tiny widths."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs
from hetu_galvatron_tpu.models.builder import (
    causal_lm_loss,
    forward_causal_lm,
    init_causal_lm,
)
from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params, params_to_hf
from hetu_galvatron_tpu.runtime.dataloader import make_batch

pytestmark = [pytest.mark.model]

TINY = dict(
    model_type="moe", hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=4, ffn_hidden_size=24,
    vocab_size=64, max_position_embeddings=32, seq_length=16,
    hidden_act="swiglu", normalization="rmsnorm", layernorm_epsilon=1e-5,
    position_embedding_type="rope", tie_word_embeddings=False,
    add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, num_experts=4, moe_topk=2,
    moe_dispatcher="dropless", moe_hf_layout="olmoe", qk_norm=True,
    moe_norm_topk_prob=False, moe_aux_loss_coeff=0.01,
    moe_z_loss_coeff=0.001)

# the configuration's file as benchmark/reference/olmoe.py reads it
REF_CFG = {
    "hidden_size": 32, "intermediate_size": 24, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "num_experts": 4,
    "num_experts_per_tok": 2, "norm_topk_prob": False, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "router_aux_loss_coef": 0.01,
    "router_z_loss_coef": 0.001}


def _hf_olmoe(norm_topk_prob: bool):
    torch = pytest.importorskip("torch")
    from transformers import OlmoeConfig, OlmoeForCausalLM

    hf_cfg = OlmoeConfig(
        vocab_size=64, hidden_size=32, intermediate_size=24,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        num_experts=4, num_experts_per_tok=2,
        norm_topk_prob=norm_topk_prob, max_position_embeddings=32,
        rms_norm_eps=1e-5, rope_theta=10000.0, tie_word_embeddings=False,
        attention_dropout=0.0, clip_qkv=None, router_aux_loss_coef=0.01)
    torch.manual_seed(0)
    hf = OlmoeForCausalLM(hf_cfg).eval()
    # a fresh model's norm scales are all ones: make them count
    with torch.no_grad():
        for name, t in hf.named_parameters():
            if "norm" in name:
                t.add_(0.3 * torch.randn_like(t))
    return torch, hf_cfg, hf


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_olmoe_hf_logit_parity(norm_topk_prob):
    """A random HF ``OlmoeForCausalLM`` through the adapter and
    ``hf_to_params`` gives HF's logits, with the router's weights
    renormalised or not as its config says; with the switch the other way
    round it does not."""
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    torch, hf_cfg, hf = _hf_olmoe(norm_topk_prob)
    cfg = populate_model_args_from_hf(hf_cfg).model_copy(update=dict(
        seq_length=16, make_vocab_size_divisible_by=1,
        moe_dispatcher="dropless"))
    assert cfg.qk_norm and cfg.moe_hf_layout == "olmoe"
    assert cfg.moe_norm_topk_prob is norm_topk_prob
    assert (cfg.num_experts, cfg.moe_topk, cfg.ffn_dim) == (4, 2, 24)
    assert cfg.moe_aux_loss_coeff == 0.01
    params = hf_to_params(hf.state_dict(), cfg)
    tokens_np = np.random.RandomState(0).randint(0, 64, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens_np)).logits.numpy()
    ours = jax.jit(lambda p, t: forward_causal_lm(
        p, t, cfg, compute_dtype=jnp.float32))(params, jnp.asarray(tokens_np))
    # tolerance: fp32 torch against fp32 XLA through two blocks (softmax,
    # three RMSNorms a block); the logits are of order 0.1
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-4, atol=2e-5)
    flipped = cfg.model_copy(update=dict(
        moe_norm_topk_prob=not norm_topk_prob))
    wrong = jax.jit(lambda p, t: forward_causal_lm(
        p, t, flipped, compute_dtype=jnp.float32))(
            params, jnp.asarray(tokens_np))
    assert np.abs(np.asarray(wrong) - ref).max() > 1e-3


def test_olmoe_hf_roundtrip():
    """``params_to_hf(hf_to_params(sd))`` is ``sd``: the same names (OLMoE's:
    ``mlp.gate``, ``mlp.experts.{e}.{gate,up,down}_proj``,
    ``self_attn.{q,k}_norm``) and the same bits."""
    _, _, hf = _hf_olmoe(False)
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    cfg = ModelArgs(**TINY)
    back = params_to_hf(hf_to_params(sd, cfg), cfg)
    assert sorted(back) == sorted(sd)
    assert "model.layers.1.mlp.experts.3.down_proj.weight" in back
    assert "model.layers.0.self_attn.k_norm.weight" in back
    for k in sd:
        np.testing.assert_array_equal(back[k], sd[k], err_msg=k)


def test_shared_expert_errors_name_the_layout():
    cfg = ModelArgs(**{**TINY, "num_shared_experts": 1})
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    with pytest.raises(NotImplementedError, match="olmoe HF layout"):
        params_to_hf(params, cfg)
    mix = cfg.model_copy(update=dict(moe_hf_layout="mixtral", qk_norm=False))
    params, _ = init_causal_lm(jax.random.key(0), mix)
    with pytest.raises(NotImplementedError, match="mixtral HF layout"):
        params_to_hf(params, mix)


def test_olmoe_adapter_refuses_clip_qkv():
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    with pytest.raises(NotImplementedError, match="clip_qkv"):
        populate_model_args_from_hf({"model_type": "olmoe", "clip_qkv": 8.0})


# ---------------------------------------------------------------------------
# the program against the benchmark's plain reference
# ---------------------------------------------------------------------------


def _without_qk_norm(params):
    """The program's parameters with the q/k norm left out of every block:
    what ``qk_norm: false`` computes on the same weights."""
    return {**params, "layers": tuple(
        {**lp, "attn": {k: v for k, v in lp["attn"].items()
                        if k not in ("q_norm", "k_norm")}}
        for lp in params["layers"])}


# the program in bfloat16 (what the cells compute in) against the float32
# reference: the loss alone, of order 4.2, within bf16's eight bits
BF16_LOSS = 2e-2


@pytest.mark.parametrize("case", ["as_published", "as_published_bf16",
                                  "topk_renormalised", "no_qk_norm"])
def test_program_matches_plain_reference(case):
    """Loss (cross-entropy and both router terms) and gradients of the
    program against ``benchmark/reference/olmoe.py`` on seeded random
    weights through the exporter; the program's gradient tree goes through
    the same exporter and meets ``jax.grad`` of the reference's ``nll_sum``.
    One sequence a call, as the reference's docstring says. With either new
    switch left off the comparison fails."""
    from benchmark import reference

    ref = reference.load_family("olmoe")
    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(7), cfg)
    # scales that are not all ones, so that a norm left out shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: (x + 0.3 * jax.random.normal(
            jax.random.key(len(str(path))), x.shape)
            if "norm" in str(path) else x), params)
    data = np.random.RandomState(3).randint(0, 64, (1, 17))
    batch = jax.tree.map(jnp.asarray, make_batch(data))
    weights = {k: jnp.asarray(v) for k, v in params_to_hf(params, cfg).items()}

    def ref_loss(w):
        return ref.nll_sum(w, REF_CFG, batch["tokens"],
                           batch["labels"]) / batch["labels"].size

    # (one program a side: op by op they are some thousands of compiles)
    want, want_grads = jax.jit(jax.value_and_grad(ref_loss))(weights)

    run_cfg, run_params = cfg, params
    if case == "topk_renormalised":
        run_cfg = cfg.model_copy(update=dict(moe_norm_topk_prob=True))
    if case == "no_qk_norm":
        run_params = _without_qk_norm(params)

    if case == "as_published_bf16":
        got = jax.jit(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.bfloat16))(params)
        assert abs(float(got) - float(want)) < BF16_LOSS, (
            float(got), float(want))
        return

    def prog_loss(p):
        return causal_lm_loss(p, batch, run_cfg, compute_dtype=jnp.float32)

    got, got_grads = jax.jit(jax.value_and_grad(prog_loss))(run_params)
    if case == "no_qk_norm":   # give the exporter the norms' (absent) slots
        got_grads = {**got_grads, "layers": tuple(
            {**lp, "attn": {**lp["attn"],
                            "q_norm": {"scale": jnp.zeros(32)},
                            "k_norm": {"scale": jnp.zeros(32)}}}
            for lp in got_grads["layers"])}
    got_grads = params_to_hf(got_grads, cfg)
    assert sorted(got_grads) == sorted(want_grads)
    # tolerance: both sides are fp32 on the CPU; they differ in operation
    # order only (fused qkv and gate|up products, grouped against
    # all-experts matmuls). The loss is of order 4.2, gradients up to 0.1
    loss_close = abs(float(got) - float(want)) < 2e-5
    worst = max(float(jnp.max(jnp.abs(got_grads[k] - want_grads[k])))
                for k in want_grads)
    if case == "as_published":
        assert loss_close, (float(got), float(want))
        assert worst < 2e-5, worst
    else:
        assert not loss_close, (float(got), float(want))
        assert worst > 1e-3, worst


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_forward_and_loss_are_the_parents_formulation(
        dtype, forward_and_loss_as_before_pr38):
    """The forward pass is not changed by PR 38
    (``tools/olmoe_forward_check.py`` reads what it read)."""
    cfg = ModelArgs(**TINY)
    forward_and_loss_as_before_pr38(
        cfg, init_causal_lm(jax.random.key(7), cfg)[0], dtype)


def test_router_terms_are_in_the_reference_loss():
    """The reference's loss is cross-entropy PLUS both router terms: with
    the coefficients at zero it is smaller by what the program's tracker
    reports for them."""
    from benchmark import reference

    ref = reference.load_family("olmoe")
    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(1), cfg)
    data = np.random.RandomState(5).randint(0, 64, (1, 17))
    batch = jax.tree.map(jnp.asarray, make_batch(data))
    w = {k: jnp.asarray(v) for k, v in params_to_hf(params, cfg).items()}
    full = ref.nll_sum(w, REF_CFG, batch["tokens"], batch["labels"])
    bare = ref.nll_sum(w, {**REF_CFG, "router_aux_loss_coef": 0.0,
                           "router_z_loss_coef": 0.0},
                       batch["tokens"], batch["labels"])
    _, stats = causal_lm_loss(params, batch, cfg, compute_dtype=jnp.float32,
                              with_moe_stats=True)
    tracked = sum(float(s["load_balance_loss"]) + float(s["z_loss"])
                  for s in stats.values())
    assert tracked > 0.03   # 0.01 x k = 0.02 a block when balanced, and z
    np.testing.assert_allclose(float(full - bare) / batch["labels"].size,
                               tracked, rtol=1e-4)


# ---------------------------------------------------------------------------
# the q/k norm under tensor parallelism
# ---------------------------------------------------------------------------


def test_qk_norm_tp2_matches_single_device(cpu_devices):
    """A norm over a width that tp shards reduces across the shards: the
    tp2 step's loss and gradient norm are the single-device step's."""
    from jax.sharding import NamedSharding, PartitionSpec

    from hetu_galvatron_tpu.parallel.spmd import (
        make_spmd_train_step,
        shard_params,
    )
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.runtime.mesh import build_mesh
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer

    model = {**TINY, "model_type": "llama", "num_experts": 0,
             "ffn_hidden_size": 48}
    got = {}
    for tp in (1, 2):
        args = CoreArgs.model_validate({
            "model": model,
            "parallel": {"global_tp_deg": tp, "global_train_batch_size": 4,
                         "chunks": 1}})
        mesh = build_mesh(tp, 1, devices=cpu_devices[:tp])
        hpc = get_hybrid_parallel_config(args, tp)
        params, axes = init_causal_lm(jax.random.key(0), args.model)
        assert "q_norm" in params["layers"][0]["attn"]
        # scales that differ along the width, so a shard-local mean shows
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: (x + jnp.linspace(-0.5, 0.5, x.shape[0])
                             if "q_norm" in str(path) or "k_norm" in str(path)
                             else x), params)
        tx = make_optimizer(args.train)
        step, pspecs, ospecs, batch_shd = make_spmd_train_step(
            args.model, hpc, mesh, axes, tx, params,
            compute_dtype=jnp.float32, donate=False)
        sp = shard_params(params, pspecs, mesh)
        opt = jax.jit(tx.init, out_shardings=jax.tree.map(
            lambda s: NamedSharding(mesh, s), ospecs,
            is_leaf=lambda x: isinstance(x, PartitionSpec)))(sp)
        data = np.random.RandomState(0).randint(0, 64, (4, 17))
        batch = jax.device_put(jax.tree.map(jnp.asarray, make_batch(data)),
                               batch_shd)
        _, _, metrics = step(sp, opt, batch)
        got[tp] = (float(metrics["loss"]), float(metrics["grad_norm"]))
    # tolerance: fp32, the same sums in another order across two shards
    np.testing.assert_allclose(got[2], got[1], rtol=2e-5)


def test_compiled_pipeline_refuses_qk_norm():
    from hetu_galvatron_tpu.analysis.eligibility import (
        compiled_schedule_unsupported_reason,
    )

    kw = dict(pp_deg=2, pipeline_type="pipedream_flush", pp_division=(1, 1))
    assert compiled_schedule_unsupported_reason(**kw) is None
    assert "q/k norm" in compiled_schedule_unsupported_reason(
        **kw, qk_norm=True)


# ---------------------------------------------------------------------------
# the capacity dispatcher at sizes where its one-hot tensors cannot exist
# ---------------------------------------------------------------------------


def test_capacity_dispatcher_refused_at_olmoe_sizes():
    """64 experts at top-8 over 4096 tokens a microbatch: the GShard
    one-hot is 5.4 GB and its capacity sum more; refused when the plan is
    resolved, naming the dispatcher that works, not inside the compiler."""
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    model = {**TINY, "num_experts": 64, "moe_topk": 8, "seq_length": 4096,
             "max_position_embeddings": 4096, "moe_dispatcher": "capacity"}
    par = {"global_train_batch_size": 4, "chunks": 1}
    args = CoreArgs.model_validate({"model": model, "parallel": par})
    with pytest.raises(ValueError, match="moe_dispatcher=dropless"):
        get_hybrid_parallel_config(args, 1)
    # one sequence a microbatch: 5.4 GB, which a 16 GB chip can hold
    args = CoreArgs.model_validate({"model": model,
                                    "parallel": {**par, "chunks": 4}})
    assert get_hybrid_parallel_config(args, 1).chunks == 4
    args = CoreArgs.model_validate({
        "model": {**model, "moe_dispatcher": "dropless"}, "parallel": par})
    assert get_hybrid_parallel_config(args, 1).chunks == 1


def test_olmoe_yaml_is_the_published_model():
    import os

    from hetu_galvatron_tpu.core.arguments import args_from_cli

    import hetu_galvatron_tpu

    yaml = os.path.join(os.path.dirname(hetu_galvatron_tpu.__file__),
                        "models", "configs", "olmoe-1b-7b.yaml")
    cfg = args_from_cli([yaml], mode="train_dist").model
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.kv_heads, cfg.head_dim) == (2048, 16, 16, 16, 128)
    assert (cfg.num_experts, cfg.moe_topk, cfg.ffn_dim) == (64, 8, 1024)
    assert cfg.qk_norm and not cfg.moe_norm_topk_prob
    assert cfg.moe_dispatcher == "dropless" and cfg.moe_hf_layout == "olmoe"
    assert (cfg.moe_aux_loss_coeff, cfg.moe_z_loss_coeff) == (0.01, 0.001)
    assert cfg.padded_vocab_size == cfg.vocab_size == 50304
    shapes = jax.eval_shape(lambda k: init_causal_lm(k, cfg)[0],
                            jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == 16 * 419_569_664 + 2 * 50304 * 2048 + 2048   # 6.92 B
