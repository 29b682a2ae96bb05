"""MoE correctness: routing math, aux losses, dense/MoE alternation, and
expert-parallel parity on the 8-CPU mesh (reference test_ep.py /
test_moe_correctness.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs, TrainArgs
from hetu_galvatron_tpu.models.builder import causal_lm_loss, init_causal_lm
from hetu_galvatron_tpu.models.moe import (
    apply_moe_mlp,
    is_moe_layer,
    moe_capacity,
)
from hetu_galvatron_tpu.runtime.dataloader import make_batch

pytestmark = [pytest.mark.model, pytest.mark.parallel]

MOE_CFG = ModelArgs(
    model_type="moe", hidden_size=32, num_hidden_layers=2,
    num_attention_heads=2, vocab_size=64, max_position_embeddings=32,
    seq_length=16, hidden_act="swiglu", normalization="rmsnorm",
    position_embedding_type="rope", tie_word_embeddings=False,
    add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, ffn_hidden_size=48,
    num_experts=4, moe_topk=2, moe_aux_loss_coeff=1e-2,
    moe_z_loss_coeff=1e-3)


def test_is_moe_layer_alternation():
    cfg = MOE_CFG.model_copy(update={"moe_layer_freq": 2,
                                     "num_hidden_layers": 4})
    assert [is_moe_layer(cfg, i) for i in range(4)] == [
        False, True, False, True]
    dense = ModelArgs(num_experts=0)
    assert not is_moe_layer(dense, 0)


def test_moe_mlp_routing_and_aux():
    from hetu_galvatron_tpu.models.moe import init_moe_mlp

    p, axes = init_moe_mlp(jax.random.key(0), MOE_CFG)
    assert axes["win"] == ("expert", "embed", "mlp")
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    y, aux, stats = apply_moe_mlp(p, x, MOE_CFG, compute_dtype=jnp.float32)
    assert y.shape == x.shape
    assert np.isfinite(float(aux)) and float(aux) > 0
    # perfectly balanced router would give aux = coeff * E * E * (1/E)^2
    assert float(aux) < 1.0


def test_moe_capacity():
    assert moe_capacity(MOE_CFG, tokens=32) == int(
        np.ceil(32 * 2 / 4 * 1.25))


def test_moe_model_trains():
    params, axes = init_causal_lm(jax.random.key(0), MOE_CFG)
    assert "moe" in params["layers"][0]  # freq=1: every layer MoE
    batch = jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(0).randint(0, 64, (4, 17))))
    loss_fn = lambda p: causal_lm_loss(p, batch, MOE_CFG,
                                       compute_dtype=jnp.float32)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss))
    # router + expert weights all get gradients
    g = grads["layers"][0]["moe"]
    assert float(jnp.abs(g["router"]).sum()) > 0
    assert float(jnp.abs(g["win"]).sum()) > 0


@pytest.mark.slow
def test_expert_parallel_matches_single_device(cpu_devices):
    """ep=2 x dp=4 sharded step == single-device step (the dispatch math is
    identical; ep only distributes experts)."""
    from hetu_galvatron_tpu.parallel.spmd import (
        make_spmd_train_step, shard_params)
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config)
    from hetu_galvatron_tpu.runtime.mesh import build_mesh
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer
    import optax

    train = TrainArgs(lr=1e-2, clip_grad=1.0, weight_decay=0.0,
                      lr_decay_style="constant", lr_warmup_iters=0)
    params, axes = init_causal_lm(jax.random.key(0), MOE_CFG)
    batch = jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(0).randint(0, 64, (8, 17))))

    tx = make_optimizer(train)
    loss_fn = lambda p: causal_lm_loss(p, batch, MOE_CFG,
                                       compute_dtype=jnp.float32)
    ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params)
    upd, _ = tx.update(ref_grads, tx.init(params), params)
    ref_params = optax.apply_updates(params, upd)

    args = CoreArgs(model=MOE_CFG.model_dump(), train=train.model_dump())
    args.parallel.global_ep_deg = 2
    args.parallel.global_train_batch_size = 8
    hpc = get_hybrid_parallel_config(args, 8)
    assert hpc.layers[0].ep_size == 2
    mesh = build_mesh(8, 1, devices=cpu_devices)
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        MOE_CFG, hpc, mesh, axes, tx, params,
        compute_dtype=jnp.float32, donate=False)
    # expert weights sharded over the ep axis
    assert pspecs["layers"][0]["moe"]["win"][0] in ("d0", ("d0",))
    sp = shard_params(params, pspecs, mesh)
    opt = jax.jit(tx.init, out_shardings=jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))(sp)
    b = jax.device_put(batch, batch_shd)
    new_p, _, metrics = step(sp, opt, b)
    assert abs(float(metrics["loss"]) - float(ref_loss)) < 2e-5
    for (pa, a), (_, b2) in zip(
            jax.tree_util.tree_leaves_with_path(ref_params),
            jax.tree_util.tree_leaves_with_path(new_p)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b2), rtol=5e-4, atol=3e-4,
            err_msg=jax.tree_util.keystr(pa))


@pytest.mark.slow
def test_moe_pipeline_matches_single_device(cpu_devices):
    """pp=2 x ep=2 MoE pipeline == single device (aux losses flow across
    stage boundaries with correct gradients)."""
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config)
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer
    from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine
    import optax

    train = TrainArgs(lr=1e-2, clip_grad=1.0, weight_decay=0.0,
                      lr_decay_style="constant", lr_warmup_iters=0)
    cfg = MOE_CFG.model_copy(update={"num_hidden_layers": 4})
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    raw = make_batch(np.random.RandomState(0).randint(0, 64, (8, 17)))
    batch = jax.tree.map(jnp.asarray, raw)

    # MoE aux losses and capacity are computed per microbatch, so the
    # single-device reference must microbatch identically (chunks=2)
    from hetu_galvatron_tpu.runtime.trainer import make_loss_fn, make_train_step

    tx = make_optimizer(train)
    ref_step = jax.jit(make_train_step(
        make_loss_fn(cfg, compute_dtype=jnp.float32), tx, chunks=2))
    ref_params, _, ref_metrics = ref_step(params, tx.init(params), batch)
    ref_loss = ref_metrics["loss"]

    args = CoreArgs(model=cfg.model_dump(), train=train.model_dump())
    args.parallel.pp_deg = 2
    args.parallel.chunks = 2
    args.parallel.global_ep_deg = 2
    args.parallel.global_train_batch_size = 8
    hpc = get_hybrid_parallel_config(args, 8)
    eng = PipelineEngine(cfg, hpc, train, devices=cpu_devices,
                         compute_dtype=jnp.float32)
    sp = eng.split_params(params, axes)
    so = eng.init_opt(sp, axes)
    new_sp, _, metrics = eng.train_step(sp, so, raw)
    assert abs(metrics["loss"] - float(ref_loss)) < 2e-5
    merged = eng.merge_params(new_sp)
    for (pa, a), (_, b2) in zip(
            jax.tree_util.tree_leaves_with_path(ref_params),
            jax.tree_util.tree_leaves_with_path(merged)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b2), rtol=5e-4, atol=3e-4,
            err_msg=jax.tree_util.keystr(pa))


# ---------------------------------------------------------------------------
# dispatchers + router variants (round 3)
# ---------------------------------------------------------------------------


def _moe_params(cfg, seed=0):
    from hetu_galvatron_tpu.models.moe import init_moe_mlp

    return init_moe_mlp(jax.random.key(seed), cfg)[0]


def test_dropless_matches_uncapped_capacity():
    """With capacity high enough that nothing drops, the GShard einsum path
    and the ragged-dot dropless path are the same function."""
    cfg = MOE_CFG
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    y_cap, aux_cap, _ = apply_moe_mlp(p, x, cfg, compute_dtype=jnp.float32,
                                   capacity_factor=100.0)
    y_dl, aux_dl, _ = apply_moe_mlp(
        p, x, cfg.model_copy(update={"moe_dispatcher": "dropless"}),
        compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_dl), np.asarray(y_cap),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux_dl), float(aux_cap), rtol=1e-6)


def test_capacity_overflow_drops_and_renormalizes():
    """Force overflow (tiny capacity): output stays finite, differs from the
    dropless result, and each surviving token keeps a unit combine weight
    (outputs bounded by the expert-output scale)."""
    cfg = MOE_CFG.model_copy(update={"moe_capacity_factor": 0.25})
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.key(2), (2, 16, 32))
    y_cap, _, _ = apply_moe_mlp(p, x, cfg, compute_dtype=jnp.float32)
    y_dl, _, _ = apply_moe_mlp(
        p, x, cfg.model_copy(update={"moe_dispatcher": "dropless"}),
        compute_dtype=jnp.float32)
    assert np.all(np.isfinite(np.asarray(y_cap)))
    assert not np.allclose(np.asarray(y_cap), np.asarray(y_dl))
    # dropped-token outputs are zero or renormalized, never amplified
    assert np.abs(np.asarray(y_cap)).max() <= \
        np.abs(np.asarray(y_dl)).max() * 4 + 1.0


def test_dropless_grads_flow():
    cfg = MOE_CFG.model_copy(update={"moe_dispatcher": "dropless"})
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.key(3), (2, 8, 32))

    def loss(p_):
        y, aux, _ = apply_moe_mlp(p_, x, cfg, compute_dtype=jnp.float32)
        return jnp.sum(jnp.square(y)) + aux

    g = jax.jit(jax.grad(loss))(p)
    for path, leaf in jax.tree_util.tree_leaves_with_path(g):
        assert np.all(np.isfinite(leaf)), path
    # router gets gradient through the combine weights
    assert np.abs(np.asarray(g["router"])).sum() > 0


def test_sinkhorn_router():
    from hetu_galvatron_tpu.models.moe import route_tokens, sinkhorn

    cfg = MOE_CFG.model_copy(update={"moe_router_type": "sinkhorn",
                                     "moe_aux_loss_coeff": 0.0,
                                     "moe_z_loss_coeff": 0.0})
    p = _moe_params(cfg)
    xt = jax.random.normal(jax.random.key(4), (64, 32))
    idx, w, aux, _ = route_tokens(p, xt, cfg, compute_dtype=jnp.float32)
    assert idx.shape == (64, 2) and w.shape == (64, 2)
    assert float(aux) == 0.0
    # sinkhorn normalization balances the assignment matrix
    norm = np.asarray(sinkhorn(jax.random.normal(jax.random.key(5),
                                                 (64, 4))))
    np.testing.assert_allclose(norm.sum(axis=1), 1.0 / 64, rtol=1e-3)
    np.testing.assert_allclose(norm.sum(axis=0), 1.0 / 4, rtol=1e-3)
    # aux loss is rejected (reference router.py:158)
    bad = cfg.model_copy(update={"moe_aux_loss_coeff": 1e-2})
    with pytest.raises(ValueError):
        route_tokens(p, xt, bad, compute_dtype=jnp.float32)
    # end-to-end through the layer
    y, _, _ = apply_moe_mlp(p, xt[None], cfg, compute_dtype=jnp.float32)
    assert np.all(np.isfinite(np.asarray(y)))


def test_expert_bias_steers_selection():
    from hetu_galvatron_tpu.models.moe import route_tokens, update_expert_bias

    cfg = MOE_CFG.model_copy(update={"moe_router_enable_expert_bias": True,
                                     "moe_topk": 1})
    p = _moe_params(cfg)
    assert "expert_bias" in p
    xt = jax.random.normal(jax.random.key(6), (128, 32))
    idx0, w0, _, _ = route_tokens(p, xt, cfg, compute_dtype=jnp.float32)
    # bias expert 3 way up: every token must now select it...
    p2 = dict(p, expert_bias=jnp.array([-10., -10., -10., 10.]))
    idx1, w1, _, _ = route_tokens(p2, xt, cfg, compute_dtype=jnp.float32)
    assert np.all(np.asarray(idx1) == 3)
    # ...but combine weights still come from the unbiased probs
    sel_same = np.asarray(idx0) == 3
    np.testing.assert_allclose(np.asarray(w1)[sel_same],
                               np.asarray(w0)[sel_same])
    # the maintenance step pushes the overloaded expert's bias down
    counts = jnp.array([0., 0., 0., 128.])
    b = update_expert_bias(p2["expert_bias"], counts, update_rate=0.1)
    assert float(b[3]) < float(p2["expert_bias"][3])
    assert float(b[0]) > float(p2["expert_bias"][0])


@pytest.mark.slow
def test_mixtral_hf_logit_parity():
    """Converted HF Mixtral checkpoint + dropless dispatch must reproduce HF
    logits (the round-2 verdict's missing Mixtral parity evidence)."""
    torch = pytest.importorskip("torch")
    from transformers import MixtralConfig, MixtralForCausalLM

    from hetu_galvatron_tpu.models.builder import forward_causal_lm
    from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params

    cfg = ModelArgs(
        model_type="moe", hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, num_key_value_heads=2, ffn_hidden_size=48,
        moe_ffn_hidden_size=48, vocab_size=64, max_position_embeddings=32,
        seq_length=16, hidden_act="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", tie_word_embeddings=False,
        add_bias_linear=False, add_qkv_bias=False,
        make_vocab_size_divisible_by=1,
        num_experts=4, moe_topk=2, moe_aux_loss_coeff=0.0,
        moe_dispatcher="dropless")
    hf_cfg = MixtralConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=32, tie_word_embeddings=False,
        attention_dropout=0.0, router_aux_loss_coef=0.0)
    torch.manual_seed(0)
    hf = MixtralForCausalLM(hf_cfg).eval()
    params = hf_to_params(hf.state_dict(), cfg)
    tokens_np = np.random.RandomState(0).randint(0, 64, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens_np)).logits.numpy()
    ours = forward_causal_lm(params, jnp.asarray(tokens_np), cfg,
                             compute_dtype=jnp.float32)
    # tolerance: a token sitting exactly on the top-k boundary can route
    # differently between torch and XLA fp32 softmax; everything else is
    # bit-close
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-3, atol=1e-3)


def test_expert_bias_updates_during_training():
    """The expert-bias flag must be live end to end: the router emits the
    maintenance signal through the gradient and the optimizer's SGD(1)
    partition applies it — bias moves after a step, model weights still
    train under Adam (round-3 review finding: the flag was a silent no-op)."""
    import optax

    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer

    cfg = MOE_CFG.model_copy(update={"moe_router_enable_expert_bias": True,
                                     "moe_aux_loss_coeff": 0.0,
                                     "moe_z_loss_coeff": 0.0})
    params, _ = init_causal_lm(jax.random.key(7), cfg)
    tx = make_optimizer(TrainArgs(lr=1e-3, clip_grad=0.0,
                                  lr_decay_style="constant"))
    tok = np.random.RandomState(7).randint(0, 64, (4, 17))
    batch = make_batch(tok)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    loss_fn = lambda p: causal_lm_loss(p, batch, cfg,
                                       compute_dtype=jnp.float32)
    loss, grads = jax.value_and_grad(loss_fn)(params)
    upd, _ = tx.update(grads, tx.init(params), params)
    new_params = optax.apply_updates(params, upd)

    for i, lp in enumerate(params["layers"]):
        b0 = np.asarray(lp["moe"]["expert_bias"])
        b1 = np.asarray(new_params["layers"][i]["moe"]["expert_bias"])
        assert not np.allclose(b0, b1), f"layer {i} expert_bias did not move"
        # the SGD(1) partition applies the raw ±update_rate signal
        deltas = np.abs(b1 - b0)
        rate = cfg.moe_expert_bias_update_rate
        assert np.all(np.isclose(deltas, 0.0, atol=1e-9)
                      | np.isclose(deltas, rate, rtol=1e-4))
        # and the bias-maintenance term added zero to the loss value
    w0 = np.asarray(params["layers"][0]["attn"]["wqkv"])
    w1 = np.asarray(new_params["layers"][0]["attn"]["wqkv"])
    assert not np.allclose(w0, w1), "model weights must still train"
    assert np.isfinite(float(loss))


def test_per_layer_aux_tracker_in_train_metrics(cpu_devices):
    """Per-layer aux/z-loss + tokens-per-expert ride the train-step metrics
    (reference aux-losses tracker, moe_utils.py:547-644), spmd path with
    microbatching."""
    from hetu_galvatron_tpu.core.args_schema import CoreArgs
    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.parallel.spmd import (
        make_spmd_train_step,
        shard_params,
    )
    from hetu_galvatron_tpu.runtime.dataloader import make_batch
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.runtime.mesh import build_mesh
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer
    from jax.sharding import NamedSharding, PartitionSpec

    args = CoreArgs.model_validate({
        "model": {
            "model_type": "moe", "hidden_size": 32, "num_hidden_layers": 4,
            "num_attention_heads": 2, "vocab_size": 64, "seq_length": 8,
            "max_position_embeddings": 16, "num_experts": 4,
            "moe_layer_freq": 2, "moe_aux_loss_coeff": 1e-2,
            "moe_z_loss_coeff": 1e-3, "hidden_act": "swiglu",
            "normalization": "rmsnorm", "position_embedding_type": "rope",
            "tie_word_embeddings": False, "add_bias_linear": False,
            "add_qkv_bias": False, "make_vocab_size_divisible_by": 1,
            "ffn_hidden_size": 64,
        },
        "parallel": {"global_tp_deg": 2, "default_dp_type": "zero3",
                     "vocab_tp": 1, "global_train_batch_size": 8,
                     "chunks": 2, "global_ep_deg": 2},
    })
    mesh = build_mesh(8, 1, devices=cpu_devices)
    hpc = get_hybrid_parallel_config(args, 8)
    params, axes = init_causal_lm(jax.random.key(0), args.model)
    tx = make_optimizer(args.train)
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        args.model, hpc, mesh, axes, tx, params,
        compute_dtype=jnp.float32, donate=False)
    sp = shard_params(params, pspecs, mesh)
    opt = jax.jit(tx.init, out_shardings=jax.tree.map(
        lambda s: NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, PartitionSpec)))(sp)
    data = np.random.RandomState(0).randint(
        0, args.model.padded_vocab_size, (8, 9))
    batch = jax.device_put(jax.tree.map(jnp.asarray, make_batch(data)),
                           batch_shd)
    _, _, metrics = step(sp, opt, batch)
    moe = metrics["moe"]
    # layers 1 and 3 are MoE (freq 2); 0 and 2 dense
    assert set(moe) == {"layer1", "layer3"}, set(moe)
    total_tokens = 8 * 8 * args.model.moe_topk
    for st in moe.values():
        assert float(st["load_balance_loss"]) > 0
        assert float(st["z_loss"]) > 0
        tpe = np.asarray(st["tokens_per_expert"])
        assert tpe.shape == (4,)
        assert int(tpe.sum()) == total_tokens, (tpe, total_tokens)
    # the iteration log renders the tracker
    from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
        RuntimeProfiler,
    )

    prof = RuntimeProfiler(args, world_size=8, rank=0)
    line = prof.iteration_log(0, metrics)
    assert "moe[layer1]" in line and "imb" in line


# the grouped matmuls' dtypes, forward and backward (PR 38)
# ---------------------------------------------------------------------------

# 8 experts, 2 a token, 64 slots; a layer that holds experts 2 and 3 has a
# first chunk of 24 rows. H = 32, F = 24, so that the three matrices a
# grouped matmul can read ([*, H, 2F], [*, F, H] and their transposes) and
# its three results' widths (2F, H, F) tell the six of a layer apart
GROUPED_CFG = ModelArgs(
    model_type="moe", hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, vocab_size=64, max_position_embeddings=32,
    seq_length=16, hidden_act="swiglu", normalization="rmsnorm",
    position_embedding_type="rope", add_bias_linear=False,
    add_qkv_bias=False, make_vocab_size_divisible_by=1, ffn_hidden_size=48,
    moe_ffn_hidden_size=24, num_experts=8, moe_topk=2,
    moe_score_function="sigmoid", moe_router_enable_expert_bias=True,
    moe_dispatcher="dropless", moe_aux_loss_coeff=0.0)
# mode -> (experts held, the selection bias on the held pair, the counted
# passes behind the first chunk: 24 of the 64 slots, then 8 a pass);
# ``dropless``: every expert held, the one body of all 64 slots
GROUPED_MODES = {"dropless": (0, 0.0, None),
                 "held_short_body": (2, 0.0, 0.0),
                 "held_full_body": (2, 10.0, 5.0)}


def _grouped_case(mode):
    """(cfg, dispatch(win, wout, xt, w, dtype) -> (y [T, H] f32, stats),
    operands) of one mode: the expert layer below the router, as a function
    of the four things it is differentiated by. Weights and tokens are
    bfloat16 values held in float32, so that a bfloat16 layer and a float32
    one read the same numbers."""
    from hetu_galvatron_tpu.models import moe

    held, bias, _ = GROUPED_MODES[mode]
    cfg = GROUPED_CFG.model_copy(update=dict(
        moe_held_experts=held, moe_first_held_expert=2 if held else 0))
    p = _moe_params(cfg, seed=5)
    p["expert_bias"] = jnp.zeros(8).at[2:4].set(bias)
    rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    xt = rounded(jax.random.normal(jax.random.key(8), (32, 32)))
    topk_idx, w, _, _ = moe.route_tokens(p, xt, cfg, jnp.float32)

    def dispatch(win, wout, xt, w, dtype):
        q = {**p, "win": win, "wout": wout}
        return moe._held_dispatch(q, xt.astype(dtype), topk_idx, w, cfg,
                                  dtype)
    return cfg, dispatch, (rounded(p["win"] * 8), rounded(p["wout"] * 8), xt,
                           w)


def _equations(jaxpr, found=None):
    """Every equation of ``jaxpr``, in order, the bodies of its calls,
    custom derivatives, conditionals and loops included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _equations(sub, found)
    return found


def _grouped_matmuls(jaxpr):
    """(lhs aval, rhs aval, result aval, dimension numbers) of every
    ``ragged_dot_general`` of a jaxpr, in order."""
    return [(*(v.aval for v in eqn.invars[:2]), eqn.outvars[0].aval,
             str(eqn.params["ragged_dot_dimension_numbers"]))
            for eqn in _equations(jaxpr)
            if eqn.primitive.name == "ragged_dot_general"]


def _sum_sq_and_grads(dispatch, dtype):
    def loss(*ops):
        y, stats = dispatch(*ops, dtype)
        return jnp.sum(jnp.square(y)), (y, stats)
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)


@pytest.mark.parametrize("mode", list(GROUPED_MODES))
def test_grouped_matmuls_read_and_write_the_compute_dtype(
        mode, grouped_matmul_as_before_pr38):
    """What stands in for a counter of engagement: the gradient of the
    expert layer at bfloat16 holds no grouped matmul with a float32 operand,
    and none with a float32 result but the forward ``wout`` product (rows
    [*, F] through [*, F, H], forward and recomputed; a layer that holds a
    share has it in its first chunk and in a counted pass). At float32 the
    grouped matmuls are the parent's, in the parent's order, and so are the
    loss and the four gradients, bit for bit, on the chunks the mode
    takes."""
    cfg, dispatch, operands = _grouped_case(mode)
    held, _, passes = GROUPED_MODES[mode]
    F, H = cfg.moe_ffn_hidden_size, cfg.hidden_size
    grad_of = lambda dt: _sum_sq_and_grads(dispatch, dt)  # noqa: E731

    calls = _grouped_matmuls(jax.make_jaxpr(grad_of(jnp.bfloat16))(
        *operands).jaxpr)
    # the one body of a layer that holds every expert: forward 2, and its
    # own backward recomputes the 2 and transposes each twice; a held share:
    # the first chunk and the loop's pass forward, and the two again
    # backward, each as the one body
    assert len(calls) == (16 if held else 8)
    forward_wout = 0
    for lhs, rhs, out, _ in calls:
        assert lhs.dtype == rhs.dtype == jnp.bfloat16, (lhs, rhs, out)
        is_forward_wout = lhs.shape[1] == F and rhs.shape[1:] == (F, H)
        forward_wout += is_forward_wout
        assert out.dtype == (jnp.float32 if is_forward_wout
                             else jnp.bfloat16), (lhs, rhs, out)
    assert forward_wout == (4 if held else 2)

    mine = jax.make_jaxpr(grad_of(jnp.float32))(*operands)
    (loss, (_, stats)), grads = jax.jit(grad_of(jnp.float32))(*operands)
    if held:
        assert float(stats["overflow_chunks"]) == passes
        assert float(stats["short_dispatch"]) == (passes == 0)
        assert float(stats["rows_computed"]) == 24 + 8 * passes
    grouped_matmul_as_before_pr38()
    parents = jax.make_jaxpr(grad_of(jnp.float32))(*operands)
    assert [tuple(map(str, c)) for c in _grouped_matmuls(mine.jaxpr)] == \
        [tuple(map(str, c)) for c in _grouped_matmuls(parents.jaxpr)]
    (ploss, _), pgrads = jax.jit(grad_of(jnp.float32))(*operands)
    assert np.array_equal(loss, ploss)
    for g, pg in zip(grads, pgrads):
        assert g.dtype == pg.dtype == jnp.float32
        assert np.array_equal(g, pg)


@pytest.mark.parametrize("mode", list(GROUPED_MODES))
def test_bf16_expert_layer_against_the_parents_and_the_f32_layer(
        mode, grouped_matmul_as_before_pr38):
    """At bfloat16 the layer's output is the parent's bit for bit (``hproj``
    rounded once, in the kernel's epilogue and not in a pass behind it; on
    the CPU both are a float32 product rounded), and the gradients to the
    tokens, ``win``, ``wout`` and the combine weights lie within the
    flash kernels' rule for a bfloat16 path against float32 (two roundings
    of the reference's largest magnitude) of the float32 layer's, as the
    parent's do: the one value that is newly rounded is the cotangent of
    ``ys``."""
    _, dispatch, operands = _grouped_case(mode)
    grad_of = lambda dt: jax.jit(_sum_sq_and_grads(dispatch, dt))  # noqa: E731
    (_, (y, _)), grads = grad_of(jnp.bfloat16)(*operands)
    (_, (y32, _)), grads32 = grad_of(jnp.float32)(*operands)
    grouped_matmul_as_before_pr38()
    (_, (py, _)), pgrads = grad_of(jnp.bfloat16)(*operands)
    assert np.array_equal(y, py) and not np.array_equal(y, y32)
    for name, g, pg, g32 in zip(("win", "wout", "xt", "w"), grads, pgrads,
                                grads32):
        assert g.dtype == jnp.float32
        tol = 2 * 2.0 ** -7 * np.abs(g32).max()
        assert np.abs(g - g32).max() <= tol, (name, np.abs(g - g32).max(), tol)
        assert np.abs(pg - g32).max() <= tol, name


# the layer that holds every expert: the held share's one body (PR 56)
# ---------------------------------------------------------------------------

def _parents_dropless_dispatch(p, xt, topk_idx, w, cfg, compute_dtype):
    """The dispatcher a layer that holds every expert ran until PR 56
    (``models/moe.py::_dropless_dispatch``, word for word): an ``argsort``,
    three gathers and a ``bincount`` a slot at a time, plain reverse mode, a
    row scatter-add for the combine. Kept here as the oracle."""
    from hetu_galvatron_tpu.models import modules as M
    from hetu_galvatron_tpu.models import moe

    T, H = xt.shape
    E, K = cfg.num_experts, cfg.moe_topk
    eid = topk_idx.reshape(T * K)
    order = jnp.argsort(eid, stable=True)
    tok = jnp.arange(T * K, dtype=jnp.int32) // K  # slot -> token
    tok_sorted = tok[order]
    xs = xt[tok_sorted].astype(compute_dtype)  # [T*K, H]
    group_sizes = jnp.bincount(eid, length=E).astype(jnp.int32)
    hproj = moe._grouped_matmul(xs, M.weight_view(p["win"], compute_dtype),
                                group_sizes, compute_dtype)
    hproj = moe._expert_act(hproj, cfg, compute_dtype)
    ys = moe._grouped_matmul(hproj, M.weight_view(p["wout"], compute_dtype),
                             group_sizes, jnp.float32)
    ws = w.reshape(T * K)[order]
    return jnp.zeros((T, H), jnp.float32).at[tok_sorted].add(
        ys * ws[:, None])


def _every_expert_of_every_token(win, wout, xt, w, topk_idx):
    """The plain ``jax.numpy`` layer: every token through every expert's
    SwiGLU in float32, and a token's result the weighted sum over the
    experts it chose."""
    gate, up = jnp.split(jnp.einsum("th,ehf->tef", xt, win), 2, axis=-1)
    ye = jnp.einsum("tef,efh->teh", jax.nn.silu(gate) * up, wout)
    chosen = (jax.nn.one_hot(topk_idx, win.shape[0]) * w[..., None]).sum(1)
    return jnp.einsum("te,teh->th", chosen, ye)


# the selection bias a case adds to the router's: expert 3 is never chosen,
# expert 5 by every token
FULL_HOLDER_ROUTES = {"the_routers_own": {}, "an_expert_without_a_route":
                      {3: -10.0}, "an_expert_with_every_route": {5: 10.0}}


@pytest.mark.parametrize("routes", sorted(FULL_HOLDER_ROUTES))
@pytest.mark.parametrize("norm_topk", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_layer_that_holds_every_expert_is_the_dropless_layer_it_was(
        dtype, norm_topk, routes):
    """Result and the four gradients (``win``, ``wout``, the tokens, the
    routes' weights) of ``_held_dispatch`` at a share of all: against the
    plain layer within the dtype's rounding, and at float32 against the
    parent's ``_dropless_dispatch`` to the last bit (two routes a token: the
    sum over them has one order)."""
    from hetu_galvatron_tpu.models import moe

    dtype = jnp.dtype(dtype)
    cfg = GROUPED_CFG.model_copy(update=dict(moe_norm_topk_prob=norm_topk))
    p = _moe_params(cfg, seed=5)
    bias = jnp.zeros(8)
    for expert, b in FULL_HOLDER_ROUTES[routes].items():
        bias = bias.at[expert].set(b)
    p["expert_bias"] = bias
    rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    xt = rounded(jax.random.normal(jax.random.key(8), (32, 32)))
    topk_idx, w, _, stats = moe.route_tokens(p, xt, cfg, jnp.float32)
    counts = np.asarray(stats["tokens_per_expert"])
    assert counts.sum() == 64 and (counts[3] == 0) == (
        routes == "an_expert_without_a_route") and (counts[5] == 32) == (
        routes == "an_expert_with_every_route")
    assert np.allclose(np.asarray(w).sum(-1), 1.0) == norm_topk
    operands = (rounded(p["win"] * 8), rounded(p["wout"] * 8), xt, w)

    def of(dispatch, jit=jax.jit):
        def loss(win, wout, xt, w):
            y = dispatch(win, wout, xt, w)
            return jnp.sum(jnp.square(y)), y
        return jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                      has_aux=True))(*operands)

    def held(win, wout, xt, w):
        y, share_stats = moe._held_dispatch(
            {"win": win, "wout": wout}, xt.astype(dtype), topk_idx, w, cfg,
            dtype)
        assert share_stats == {}
        return y

    (_, y), grads = of(held)
    (_, want_y), want = of(lambda win, wout, xt, w:
                           _every_expert_of_every_token(win, wout, xt, w,
                                                        topk_idx))
    # float32: sums in another order; bfloat16: the flash kernels' rule, two
    # roundings of the reference's largest magnitude
    eps = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -20
    for name, got, ref in zip(("y", "win", "wout", "xt", "w"), (y, *grads),
                              (want_y, *want)):
        assert got.dtype == jnp.float32 and got.shape == ref.shape
        tol = 2 * eps * np.abs(ref).max()
        assert np.abs(got - ref).max() <= tol, (name, np.abs(got - ref).max(),
                                                tol)
    if dtype == jnp.float32:
        # an operation at a time: inside one compiled program the CPU
        # contracts ``a * wa + b * wb`` of the gathered sum into a fused
        # multiply-add, which the scatter-add's rounded products are not
        eager = lambda f: f  # noqa: E731
        (_, y), grads = of(held, eager)
        (_, was_y), was = of(lambda win, wout, xt, w:
                             _parents_dropless_dispatch(
            {"win": win, "wout": wout}, xt, topk_idx, w, cfg, dtype), eager)
        for name, got, ref in zip(("y", "win", "wout", "xt", "w"),
                                  (y, *grads), (was_y, *was)):
            assert np.array_equal(got, ref), name


def test_a_layer_that_holds_every_expert_traces_one_body():
    """The differentiated layer under ``modules.remat``: no loop and no
    conditional; eight grouped matmuls (forward 2; the rule's own backward
    recomputes its one chunk, 2, and transposes each twice; the remat's
    recomputed forward is dead); no row is scatter-added into ``[T, H]``
    (a token's rows are gathered by the inverse permutation and summed); and
    nothing is gathered from a ``[T*K]`` vector a slot at a time (the sort
    carries its payloads, the weights' cotangent goes back by a sort)."""
    from hetu_galvatron_tpu.models import modules as M
    from hetu_galvatron_tpu.models.moe import init_moe_mlp

    cfg = GROUPED_CFG
    p = jax.eval_shape(lambda k: init_moe_mlp(k, cfg)[0], jax.random.key(0))
    x = jax.ShapeDtypeStruct((2, 16, 32), jnp.bfloat16)
    T, H, K = 32, 32, cfg.moe_topk

    def layer_sum(p, x):
        y, aux, stats = apply_moe_mlp(p, x, cfg)
        return jnp.sum(y.astype(jnp.float32)) + aux

    eqns = _equations(jax.make_jaxpr(jax.grad(
        M.remat(layer_sum, cfg), argnums=(0, 1)))(p, x).jaxpr)
    names = [e.primitive.name for e in eqns]
    assert "while" not in names and "cond" not in names
    assert names.count("ragged_dot_general") == 8
    # the sort and its inverse, forward and in the remat's recomputation
    # (one program to the compiler), and the weights' cotangent's way back
    assert names.count("sort") == 5
    for e in eqns:
        if e.primitive.name.startswith("scatter"):
            assert e.invars[0].aval.shape != (T, H), e
        if e.primitive.name == "gather":
            assert e.invars[0].aval.shape != (T * K,), e
    # the rows move by gathers: the tokens' rows to the sorted slots (and
    # the result's cotangent), the sorted rows back by the inverse
    moved = [e.invars[0].aval.shape for e in eqns
             if e.primitive.name == "gather"
             and e.invars[0].aval.shape in ((T, H), (T * K, H))]
    assert sorted(set(moved)) == [(T, H), (T * K, H)]


def test_a_layer_that_holds_every_expert_tells_no_share(capsys):
    """``apply_moe_mlp``'s stats are the router's alone, and the log line of
    a step with such a layer is the line it was: no ``local ... rows``
    group, no share gauge."""
    from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
        RuntimeProfiler,
    )
    from hetu_galvatron_tpu.observability.registry import MetricsRegistry

    cfg = GROUPED_CFG
    p = _moe_params(cfg, seed=5)
    x = jax.random.normal(jax.random.key(8), (2, 16, 32))
    _, _, stats = apply_moe_mlp(p, x, cfg, compute_dtype=jnp.float32)
    assert sorted(stats) == ["load_balance_loss", "tokens_per_expert",
                             "z_loss"]
    held = cfg.model_copy(update=dict(moe_held_experts=2))
    _, _, share = apply_moe_mlp(
        {**p, "win": p["win"][:2], "wout": p["wout"][:2]}, x, held,
        compute_dtype=jnp.float32)
    assert set(share) - set(stats) == {
        "rows_held", "rows_computed", "overflow_chunks", "short_dispatch",
        "held_tokens_per_expert"}

    reg = MetricsRegistry()
    prof = RuntimeProfiler(CoreArgs(model=cfg.model_dump()), registry=reg)
    line = prof.iteration_log(0, {"loss": jnp.float32(1.5),
                                  "moe": {"layer0": stats}})
    tpe = np.asarray(stats["tokens_per_expert"])
    assert line == capsys.readouterr().out.strip() == (
        "iter 0 | loss 1.5000 | moe[layer0] aux 0.000e+00 z 0.000e+00 "
        f"imb {tpe.max() / tpe.mean():.2f}")
    assert sorted({m.name for m in reg.metrics()}) == [
        "moe/aux_loss", "moe/imbalance", "moe/rows_per_expert", "moe/z_loss"]
