"""Dropout semantics: attention/hidden dropout with an explicitly-threaded
rng (reference C7 applies torch nn.Dropout inside attention/MLP/embeddings;
here the rng rides the batch dict so train steps stay pure functions).

Covers: eval identity (rng=None), train-mode stochasticity + rng
determinism, inverted-dropout scaling, the chunked-accumulation path, the
SPMD distributed path on the virtual mesh, and the encoder-decoder stack.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs, TrainArgs
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.models.builder import (
    causal_lm_loss,
    forward_causal_lm,
    init_causal_lm,
)
from hetu_galvatron_tpu.runtime.dataloader import make_batch
from hetu_galvatron_tpu.runtime.optimizer import make_optimizer
from hetu_galvatron_tpu.runtime.trainer import make_loss_fn, make_train_step

pytestmark = [pytest.mark.model]

CFG = ModelArgs(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    vocab_size=128, max_position_embeddings=64, seq_length=16,
    hidden_act="swiglu", normalization="rmsnorm",
    position_embedding_type="rope", tie_word_embeddings=False,
    add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, ffn_hidden_size=128,
    hidden_dropout=0.5, attention_dropout=0.25,
)
EVAL_CFG = CFG.model_copy(update={"hidden_dropout": 0.0,
                                  "attention_dropout": 0.0})


def _batch(bsz=4, seed=0):
    data = np.random.RandomState(seed).randint(
        0, 128, (bsz, CFG.seq_length + 1))
    return jax.tree.map(jnp.asarray, make_batch(data))


def test_dropout_unit_scaling_and_identity():
    x = jnp.ones((64, 64))
    assert M.dropout(x, 0.5, None) is x  # eval: identity, no copy
    rng = jax.random.key(0)
    y = np.asarray(M.dropout(x, 0.5, rng))
    kept = y != 0.0
    # inverted dropout: survivors scaled by 1/(1-rate)
    np.testing.assert_allclose(y[kept], 2.0)
    assert 0.3 < kept.mean() < 0.7


def test_forward_eval_identity_and_train_stochasticity():
    params, _ = init_causal_lm(jax.random.key(0), CFG)
    tokens = _batch()["tokens"]
    # rng=None on a dropout-enabled cfg == the dropout-free cfg exactly
    a = forward_causal_lm(params, tokens, CFG, compute_dtype=jnp.float32)
    b = forward_causal_lm(params, tokens, EVAL_CFG, compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # same rng => identical; different rng => different
    r1, r2 = jax.random.key(1), jax.random.key(2)
    o1 = forward_causal_lm(params, tokens, CFG, compute_dtype=jnp.float32,
                           dropout_rng=r1)
    o1b = forward_causal_lm(params, tokens, CFG, compute_dtype=jnp.float32,
                            dropout_rng=r1)
    o2 = forward_causal_lm(params, tokens, CFG, compute_dtype=jnp.float32,
                           dropout_rng=r2)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o1b))
    assert np.abs(np.asarray(o1) - np.asarray(o2)).max() > 1e-3
    assert np.abs(np.asarray(o1) - np.asarray(a)).max() > 1e-3


def test_train_step_rng_in_batch_and_chunks():
    tx = make_optimizer(TrainArgs(lr=1e-3, lr_decay_style="constant"))
    params, _ = init_causal_lm(jax.random.key(0), CFG)
    loss_fn = make_loss_fn(CFG, compute_dtype=jnp.float32)
    batch = _batch(bsz=4)
    for chunks in (1, 2):
        step = jax.jit(make_train_step(loss_fn, tx, chunks=chunks))
        opt = tx.init(params)
        b = dict(batch)
        b["dropout_rng"] = jax.random.key(7)
        p1, _, m1 = step(params, opt, b)
        p1b, _, m1b = step(params, opt, dict(b))
        b2 = dict(batch)
        b2["dropout_rng"] = jax.random.key(8)
        p2, _, m2 = step(params, opt, b2)
        assert float(m1["loss"]) == pytest.approx(float(m1b["loss"]))
        assert float(m1["loss"]) != pytest.approx(float(m2["loss"]),
                                                  abs=1e-6)
        # batch dict passed in is not mutated by the step
        assert "dropout_rng" in b


def test_dropout_grads_flow_and_masked_positions_get_zero_grad():
    """Gradient sanity: with dropout the grads still differentiate the same
    graph (no rng leakage into tangents), and eval-mode grads match the
    dropout-free config."""
    params, _ = init_causal_lm(jax.random.key(0), CFG)
    batch = _batch()
    g_eval = jax.grad(lambda p: causal_lm_loss(
        p, batch, CFG, compute_dtype=jnp.float32))(params)
    g_ref = jax.grad(lambda p: causal_lm_loss(
        p, batch, EVAL_CFG, compute_dtype=jnp.float32))(params)
    for a, b in zip(jax.tree.leaves(g_eval), jax.tree.leaves(g_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.distributed
def test_spmd_dropout_runs_and_is_rng_deterministic():
    from hetu_galvatron_tpu.parallel.spmd import (
        make_spmd_train_step,
        shard_params,
    )
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.runtime.mesh import build_mesh

    devices = jax.devices("cpu")[:4]
    args = CoreArgs(model=CFG.model_dump())
    args.parallel.global_tp_deg = 2
    args.parallel.global_train_batch_size = 4
    hpc = get_hybrid_parallel_config(args, 4)
    mesh = build_mesh(4, 1, devices=devices)
    params, axes = init_causal_lm(jax.random.key(0), CFG)
    tx = make_optimizer(TrainArgs(lr=1e-3, lr_decay_style="constant"))
    step, pspecs, opt_specs, batch_shd = make_spmd_train_step(
        CFG, hpc, mesh, axes, tx, params, compute_dtype=jnp.float32,
        donate=False)
    params = shard_params(params, pspecs, mesh)
    opt = jax.jit(tx.init)(params)
    batch = jax.device_put(_batch(bsz=4), batch_shd)

    with pytest.raises(ValueError, match="dropout_rng"):
        step(params, opt, dict(batch))

    b = dict(batch)
    b["dropout_rng"] = jax.random.key(3)
    _, _, m1 = step(params, opt, b)
    _, _, m1b = step(params, opt, dict(b))
    b2 = dict(batch)
    b2["dropout_rng"] = jax.random.key(4)
    _, _, m2 = step(params, opt, b2)
    assert float(m1["loss"]) == pytest.approx(float(m1b["loss"]), rel=1e-6)
    assert float(m1["loss"]) != pytest.approx(float(m2["loss"]), abs=1e-6)


def test_encdec_dropout_paths():
    t5 = CFG.model_copy(update={
        "model_type": "t5", "num_encoder_layers": 2, "hidden_act": "relu",
        "position_embedding_type": "rope"})
    from hetu_galvatron_tpu.models.encdec import init_encdec

    params, _ = init_encdec(jax.random.key(0), t5)
    rs = np.random.RandomState(0)
    batch = {
        "enc_tokens": jnp.asarray(rs.randint(0, 128, (2, 8))),
        "tokens": jnp.asarray(rs.randint(0, 128, (2, 8))),
        "labels": jnp.asarray(rs.randint(0, 128, (2, 8))),
    }
    l_eval = causal_lm_loss(params, batch, t5, compute_dtype=jnp.float32)
    b = dict(batch)
    b["dropout_rng"] = jax.random.key(5)
    l1 = causal_lm_loss(params, b, t5, compute_dtype=jnp.float32)
    l1b = causal_lm_loss(params, dict(b), t5, compute_dtype=jnp.float32)
    assert float(l1) == pytest.approx(float(l1b))
    assert float(l1) != pytest.approx(float(l_eval), abs=1e-6)


@pytest.mark.distributed
def test_pipeline_engine_dropout_rng_deterministic():
    """pp>1 dropout: the same per-step key gives the same loss (the
    backward's remat recomputation reuses the forward's masks), a different
    key gives a different loss, and a dropout-off cfg through the engine
    still matches the single-device loss."""
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine

    args = CoreArgs(model=CFG.model_dump())
    args.parallel.pp_deg = 2
    args.parallel.chunks = 2
    args.parallel.global_train_batch_size = 4
    hpc = get_hybrid_parallel_config(args, 4)
    tr = TrainArgs(lr=1e-3, lr_decay_style="constant")
    eng = PipelineEngine(CFG, hpc, tr, devices=jax.devices("cpu")[:4],
                         compute_dtype=jnp.float32)
    params, axes = init_causal_lm(jax.random.key(0), CFG)
    sp = eng.split_params(params, axes)
    so = eng.init_opt(sp, axes)
    raw = {k: np.asarray(v) for k, v in _batch(bsz=4).items()}

    b1 = dict(raw)
    b1["dropout_rng"] = jax.random.key(11)
    _, _, m1 = eng.train_step(sp, so, b1)
    _, _, m1b = eng.train_step(sp, so, dict(b1))
    b2 = dict(raw)
    b2["dropout_rng"] = jax.random.key(12)
    _, _, m2 = eng.train_step(sp, so, b2)
    assert m1["loss"] == pytest.approx(m1b["loss"], rel=1e-6)
    assert m1["loss"] != pytest.approx(m2["loss"], abs=1e-6)

    # dropout-off cfg through the engine matches the single-device loss
    eng0 = PipelineEngine(EVAL_CFG, hpc, tr, devices=jax.devices("cpu")[:4],
                          compute_dtype=jnp.float32)
    sp0 = eng0.split_params(params, axes)
    so0 = eng0.init_opt(sp0, axes)
    _, _, m0 = eng0.train_step(sp0, so0, dict(raw))
    ref = float(causal_lm_loss(params, _batch(bsz=4), EVAL_CFG,
                               compute_dtype=jnp.float32))
    assert m0["loss"] == pytest.approx(ref, rel=1e-4)


def test_attention_dropout_refuses_custom_kernels():
    """attention_dropout>0 with an installed flash/ring/Ulysses kernel must
    refuse loudly, not silently swap in the score-materializing XLA core."""
    params, _ = init_causal_lm(jax.random.key(0), CFG)
    tokens = _batch()["tokens"]
    fake_kernel = lambda q, k, v, causal=True: M.xla_sdpa(q, k, v,
                                                          causal=causal)
    with pytest.raises(NotImplementedError, match="attention_dropout"):
        forward_causal_lm(
            params, tokens, CFG, compute_dtype=jnp.float32,
            dropout_rng=jax.random.key(0),
            layer_overrides={i: M.LayerOps(sdpa=fake_kernel)
                             for i in range(CFG.num_hidden_layers)})
