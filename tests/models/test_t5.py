"""Encoder-decoder (T5 family): cross-attention semantics, seq2seq batches,
and distributed parity (BASELINE milestone 4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs, TrainArgs
from hetu_galvatron_tpu.models.builder import causal_lm_loss, init_causal_lm
from hetu_galvatron_tpu.models.encdec import encdec_loss, forward_encdec

pytestmark = [pytest.mark.model, pytest.mark.parallel]

T5 = ModelArgs(
    model_type="t5", hidden_size=32, num_hidden_layers=2,
    num_encoder_layers=3, num_attention_heads=2, vocab_size=64,
    max_position_embeddings=32, seq_length=16, hidden_act="gelu",
    normalization="rmsnorm", position_embedding_type="rope",
    tie_word_embeddings=True, add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1)


def _batch(bsz=4, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "enc_tokens": jnp.asarray(rng.randint(0, 64, (bsz, 8))),
        "tokens": jnp.asarray(rng.randint(0, 64, (bsz, 6))),
        "labels": jnp.asarray(rng.randint(0, 64, (bsz, 6))),
    }


def test_init_structure_and_loss():
    params, axes = init_causal_lm(jax.random.key(0), T5)
    assert len(params["enc_layers"]) == 3
    assert len(params["layers"]) == 2
    assert "cross" in params["layers"][0]
    assert axes["layers"][0]["cross"]["wq"] == ("embed", "qkv")
    loss = causal_lm_loss(params, _batch(), T5, compute_dtype=jnp.float32)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: encdec_loss(p, _batch(), T5,
                                           compute_dtype=jnp.float32))(params)
    assert all(np.all(np.isfinite(g)) for g in jax.tree.leaves(grads))


def test_decoder_causal_encoder_bidirectional():
    params, _ = init_causal_lm(jax.random.key(0), T5)
    b = _batch(bsz=1)
    base = forward_encdec(params, b["enc_tokens"], b["tokens"], T5,
                          compute_dtype=jnp.float32)
    # future decoder token must not change earlier decoder logits
    d2 = b["tokens"].at[0, -1].set((b["tokens"][0, -1] + 1) % 64)
    out2 = forward_encdec(params, b["enc_tokens"], d2, T5,
                          compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(base[:, :-1]),
                               np.asarray(out2[:, :-1]), atol=1e-6)
    # but any encoder token change reaches every decoder position
    e2 = b["enc_tokens"].at[0, -1].set((b["enc_tokens"][0, -1] + 1) % 64)
    out3 = forward_encdec(params, e2, b["tokens"], T5,
                          compute_dtype=jnp.float32)
    assert not np.allclose(np.asarray(base[:, 0]), np.asarray(out3[:, 0]))


def test_seq2seq_batches():
    from hetu_galvatron_tpu.runtime.dataloader import get_data_iterator

    args = CoreArgs(model=T5.model_dump())
    it = get_data_iterator(args, global_batch_size=4)
    b = next(it)
    assert set(b) == {"enc_tokens", "tokens", "labels", "loss_mask"}
    assert b["enc_tokens"].shape == (4, 8)
    assert b["tokens"].shape[1] == b["labels"].shape[1]


@pytest.mark.slow
def test_t5_tp2_matches_single_device(cpu_devices):
    from hetu_galvatron_tpu.parallel.spmd import (
        make_spmd_train_step, shard_params)
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config)
    from hetu_galvatron_tpu.runtime.mesh import build_mesh
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer
    import optax

    train = TrainArgs(lr=1e-2, clip_grad=1.0, weight_decay=0.01,
                      lr_decay_style="constant", lr_warmup_iters=0)
    params, axes = init_causal_lm(jax.random.key(0), T5)
    batch = _batch(bsz=8)

    tx = make_optimizer(train)
    loss_fn = lambda p: encdec_loss(p, batch, T5, compute_dtype=jnp.float32)
    ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params)
    upd, _ = tx.update(ref_grads, tx.init(params), params)
    ref_params = optax.apply_updates(params, upd)

    args = CoreArgs(model=T5.model_dump(), train=train.model_dump())
    args.parallel.global_tp_deg = 2
    args.parallel.vocab_tp = 2
    args.parallel.global_train_batch_size = 8
    hpc = get_hybrid_parallel_config(args, 8)
    mesh = build_mesh(8, 1, devices=cpu_devices)
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        T5, hpc, mesh, axes, tx, params, compute_dtype=jnp.float32,
        donate=False)
    assert "enc_layers" in pspecs
    sp = shard_params(params, pspecs, mesh)
    opt = jax.jit(tx.init, out_shardings=jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))(sp)
    new_p, _, metrics = step(sp, opt, jax.device_put(batch, batch_shd))
    assert abs(float(metrics["loss"]) - float(ref_loss)) < 2e-5
    for (pa, a), (_, b2) in zip(
            jax.tree_util.tree_leaves_with_path(ref_params),
            jax.tree_util.tree_leaves_with_path(new_p)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b2), rtol=5e-4, atol=3e-4,
            err_msg=jax.tree_util.keystr(pa))


def test_t5_train_dist_cli(capsys):
    import os
    from hetu_galvatron_tpu.cli.train_dist import main

    ZOO = os.path.join(os.path.dirname(__file__), "..", "..",
                       "hetu_galvatron_tpu", "models", "configs")
    rc = main([os.path.join(ZOO, "t5-3b.yaml"),
               "model.hidden_size=32", "model.num_hidden_layers=2",
               "model.num_encoder_layers=2", "model.num_attention_heads=2",
               "model.vocab_size=64", "model.seq_length=16",
               "model.max_position_embeddings=16",
               "model.make_vocab_size_divisible_by=1",
               "model.ffn_hidden_size=64",
               "train.train_iters=2", "parallel.mixed_precision=fp32",
               "parallel.global_train_batch_size=8",
               "parallel.global_tp_deg=2"])
    assert rc == 0
    assert "training done" in capsys.readouterr().out


def test_cross_attention_biases_honored():
    """add_qkv_bias/add_bias_linear apply to cross-attention too."""
    cfg = T5.model_copy(update={"add_qkv_bias": True,
                                "add_bias_linear": True})
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    cross = params["layers"][0]["cross"]
    assert "bq" in cross and "bkv" in cross and "bo" in cross
    loss = causal_lm_loss(params, _batch(), cfg, compute_dtype=jnp.float32)
    assert np.isfinite(float(loss))


def test_num_encoder_layers_zero_is_zero():
    cfg = T5.model_copy(update={"num_encoder_layers": 0})
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    assert len(params["enc_layers"]) == 0


# ---------------------------------------------------------------------------
# pipeline parallelism over the combined enc+dec stack (BASELINE milestone 4)
# ---------------------------------------------------------------------------

TRAIN = TrainArgs(lr=1e-2, clip_grad=1.0, weight_decay=0.01,
                  lr_decay_style="constant", lr_warmup_iters=0)


def _ref_step(cfg, params, batch):
    import optax

    from hetu_galvatron_tpu.models.encdec import encdec_loss
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer

    tx = make_optimizer(TRAIN)
    loss_fn = lambda p: encdec_loss(p, batch, cfg, compute_dtype=jnp.float32)
    loss, grads = jax.value_and_grad(loss_fn)(params)
    upd, _ = tx.update(grads, tx.init(params), params)
    return float(loss), optax.apply_updates(params, upd)


def _t5_pipeline_step(cfg, params, axes, batch, cpu_devices, **pkw):
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config)
    from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine

    args = CoreArgs(model=cfg.model_dump(), train=TRAIN.model_dump())
    for k, v in pkw.items():
        setattr(args.parallel, k, v)
    hpc = get_hybrid_parallel_config(args, 8)
    assert hpc.num_encoder_layers == 3
    assert sum(hpc.pp_division) == 5  # combined enc(3) + dec(2)
    eng = PipelineEngine(cfg, hpc, args.train, devices=cpu_devices,
                         compute_dtype=jnp.float32)
    sp = eng.split_params(params, axes)
    so = eng.init_opt(sp, axes)
    new_sp, _, metrics = eng.train_step(sp, so, batch)
    return metrics, eng.merge_params(new_sp)


T5_PP_CASES = [
    dict(pp_deg=2, pipeline_type="gpipe", chunks=2),
    dict(pp_deg=2, pipeline_type="pipedream_flush", chunks=4),
    # pp=4 over 5 combined layers -> [1,1,1,2]: encoder-only stages with the
    # decoder-stream passthrough, and the enc->dec boundary mid-pipeline
    dict(pp_deg=4, pipeline_type="pipedream_flush", chunks=4),
    dict(pp_deg=2, pipeline_type="gpipe", chunks=2, global_tp_deg=2),
]


@pytest.mark.distributed
@pytest.mark.parametrize(
    "pkw", T5_PP_CASES,
    ids=lambda d: ",".join(f"{k}={v}" for k, v in d.items()))
@pytest.mark.slow
def test_t5_pipeline_matches_single_device(pkw, cpu_devices):
    """pp>1 over the combined enc+dec stack must reproduce the single-device
    step (the reference pipelines any arch via PipeSequential,
    pipeline.py:1592; this engine stage-slices the (a, b) activation pair)."""
    params, axes = init_causal_lm(jax.random.key(0), T5)
    rng = np.random.RandomState(0)
    batch = {
        "enc_tokens": rng.randint(0, 64, (16, 8)),
        "tokens": rng.randint(0, 64, (16, 6)),
        "labels": rng.randint(0, 64, (16, 6)),
    }
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    ref_loss, ref_params = _ref_step(T5, params, jbatch)
    pkw = dict(pkw, global_train_batch_size=16)
    metrics, new_params = _t5_pipeline_step(T5, params, axes, batch,
                                            cpu_devices, **pkw)
    assert abs(metrics["loss"] - ref_loss) < 2e-5, \
        f"loss {metrics['loss']} != {ref_loss}"
    # tied embedding: enc-token AND dec-token wte grads + transposed head
    # copy must all have reconciled across stages
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_params),
            jax.tree_util.tree_leaves_with_path(new_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=3e-4,
            err_msg=f"param {jax.tree_util.keystr(pa)}")


@pytest.mark.distributed
@pytest.mark.slow
def test_t5_heterogeneous_combined_plan(cpu_devices, tmp_path):
    """A searched-style JSON plan over the COMBINED stack: per-layer encoder
    strategies differ from decoder strategies (tp2 encoder, dp zero3 decoder)
    and pp_division splits mid-encoder."""
    import json

    plan = {
        "pp_deg": 2,
        "tp_sizes_enc": "2,2,1,1,1",    # enc 3 layers then dec 2 layers
        "tp_consecutive_flags": "1,1,1,1,1",
        "dp_types_enc": "0,0,0,1,1",
        "use_sp": "0,0,0,0,0",
        "cp_sizes_enc": "1,1,1,1,1",
        "checkpoint": "0,1,0,0,1",
        "global_bsz": 8,
        "chunks": 2,
        "pp_division": "2,3",
        "pipeline_type": "pipedream_flush",
        "default_dp_type": "ddp",
        "vtp": 1, "vsp": 0, "vcp": 1, "embed_sdp": 0,
        "num_encoder_layers": 3,
    }
    path = tmp_path / "t5_plan.json"
    path.write_text(json.dumps(plan))
    params, axes = init_causal_lm(jax.random.key(1), T5)
    rng = np.random.RandomState(1)
    batch = {
        "enc_tokens": rng.randint(0, 64, (8, 8)),
        "tokens": rng.randint(0, 64, (8, 6)),
        "labels": rng.randint(0, 64, (8, 6)),
    }
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    ref_loss, ref_params = _ref_step(T5, params, jbatch)
    metrics, new_params = _t5_pipeline_step(
        T5, params, axes, batch, cpu_devices,
        galvatron_config_path=str(path))
    assert abs(metrics["loss"] - ref_loss) < 2e-5
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_params),
            jax.tree_util.tree_leaves_with_path(new_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=3e-4,
            err_msg=f"param {jax.tree_util.keystr(pa)}")


def test_t5_flash_attention_overrides():
    """Flash kernels (interpret mode) in BOTH t5 stacks: encoder non-causal,
    decoder causal self-attention + non-causal cross-attention — must match
    the XLA core. Equal enc/dec lengths so cross-attention tiles."""
    from functools import partial as fpartial

    from hetu_galvatron_tpu.models.modules import LayerOps
    from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa

    cfg = T5.model_copy(update={"num_encoder_layers": 2})
    params, _ = init_causal_lm(jax.random.key(2), cfg)
    rng = np.random.RandomState(2)
    batch = {
        "enc_tokens": jnp.asarray(rng.randint(0, 64, (2, 16))),
        "tokens": jnp.asarray(rng.randint(0, 64, (2, 16))),
        "labels": jnp.asarray(rng.randint(0, 64, (2, 16))),
    }
    base = causal_lm_loss(params, batch, cfg, compute_dtype=jnp.float32)
    flash = fpartial(flash_sdpa, interpret=True)
    over = {i: LayerOps(sdpa=flash) for i in range(2)}
    out = causal_lm_loss(params, batch, cfg, compute_dtype=jnp.float32,
                         layer_overrides=over, enc_layer_overrides=over)
    np.testing.assert_allclose(float(out), float(base), rtol=2e-5, atol=2e-5)


@pytest.mark.distributed
@pytest.mark.slow
def test_t5_ring_cp_matches_xla(cpu_devices):
    """cp=2 on every combined layer: encoder runs non-causal ring, decoder
    self-attention runs causal ring, cross-attention falls back to the XLA
    core (unequal q/kv lengths) — loss must match the single-device step."""
    from hetu_galvatron_tpu.parallel.spmd import (
        make_spmd_train_step, shard_params)
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config)
    from hetu_galvatron_tpu.runtime.mesh import build_mesh
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer

    from hetu_galvatron_tpu.models.encdec import encdec_loss

    cfg = T5.model_copy(update={"num_encoder_layers": 2})
    params, axes = init_causal_lm(jax.random.key(3), cfg)
    rng = np.random.RandomState(3)
    batch = {
        "enc_tokens": jnp.asarray(rng.randint(0, 64, (8, 8))),
        "tokens": jnp.asarray(rng.randint(0, 64, (8, 8))),
        "labels": jnp.asarray(rng.randint(0, 64, (8, 8))),
    }
    ref_loss = float(encdec_loss(params, batch, cfg,
                                 compute_dtype=jnp.float32))
    args = CoreArgs(model=cfg.model_dump(), train=TRAIN.model_dump())
    args.parallel.global_cp_deg = 2
    args.parallel.global_train_batch_size = 8
    hpc = get_hybrid_parallel_config(args, 8)
    assert hpc.num_encoder_layers == 2
    mesh = build_mesh(8, 1, devices=cpu_devices)
    tx = make_optimizer(TRAIN)
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        cfg, hpc, mesh, axes, tx, params, compute_dtype=jnp.float32,
        donate=False)
    sp = shard_params(params, pspecs, mesh)
    opt = jax.jit(tx.init, out_shardings=jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))(sp)
    _, _, metrics = step(sp, opt, jax.device_put(batch, batch_shd))
    assert abs(float(metrics["loss"]) - ref_loss) < 2e-5


@pytest.mark.distributed
@pytest.mark.slow
def test_t5_interleaved_virtual_stages(cpu_devices):
    """vpp=2 x pp=2 over the combined enc+dec stack: 4 chunks round-robin
    on 2 device groups, enc->dec boundary inside a chunk."""
    params, axes = init_causal_lm(jax.random.key(0), T5)
    rng = np.random.RandomState(5)
    batch = {
        "enc_tokens": rng.randint(0, 64, (16, 8)),
        "tokens": rng.randint(0, 64, (16, 6)),
        "labels": rng.randint(0, 64, (16, 6)),
    }
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    ref_loss, ref_params = _ref_step(T5, params, jbatch)
    metrics, new_params = _t5_pipeline_step(
        T5, params, axes, batch, cpu_devices,
        pp_deg=2, virtual_pp_deg=2, chunks=4,
        pipeline_type="pipedream_flush", global_train_batch_size=16)
    assert abs(metrics["loss"] - ref_loss) < 2e-5
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_params),
            jax.tree_util.tree_leaves_with_path(new_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=3e-4,
            err_msg=f"param {jax.tree_util.keystr(pa)}")


# ---------------------------------------------------------------------------
# encoder-decoder decode (encoder once + cached cross k/v + cached causal
# self-attention). This runtime is position-scheme agnostic (no T5 relative
# bias — encdec.py docstring), so the decode contract is incremental ==
# full teacher-forced forward, not HF bit-parity.
# ---------------------------------------------------------------------------


def test_t5_greedy_decode_matches_teacher_forced_forward():
    """Greedy generate_encdec token t+1 must equal the argmax of the full
    (uncached) forward_encdec over the prefix — the KV/cross caches change
    nothing."""
    from hetu_galvatron_tpu.models.generate import generate_encdec

    params, _ = init_causal_lm(jax.random.key(7), T5)
    rng = np.random.RandomState(1)
    enc = jnp.asarray(rng.randint(0, 64, (2, 8)))
    n_new = 6
    out = jax.jit(lambda p, t: generate_encdec(
        p, t, T5, n_new, compute_dtype=jnp.float32))(params, enc)
    assert out.shape == (2, 1 + n_new)
    assert np.all(np.asarray(out[:, 0]) == 0)  # decoder start token
    for t in range(n_new):
        logits = forward_encdec(params, enc, out[:, :t + 1], T5,
                                compute_dtype=jnp.float32)
        nxt = np.argmax(np.asarray(logits[:, -1]), axis=-1)
        np.testing.assert_array_equal(np.asarray(out[:, t + 1]), nxt,
                                      err_msg=f"step {t}")


def test_t5_decode_eos_masking_and_sampling_shapes():
    from hetu_galvatron_tpu.models.generate import generate_encdec

    params, _ = init_causal_lm(jax.random.key(3), T5)
    enc = jnp.asarray(np.random.RandomState(2).randint(0, 64, (3, 8)))
    out = generate_encdec(params, enc, T5, 5, temperature=0.7, top_k=10,
                          eos_id=9, key=jax.random.key(0),
                          compute_dtype=jnp.float32)
    assert out.shape == (3, 6)
    arr = np.asarray(out)
    # once eos appears, everything after stays eos
    for row in arr:
        hits = np.where(row[1:] == 9)[0]
        if len(hits):
            assert np.all(row[1 + hits[0]:] == 9)


def test_t5_generate_cli_smoke(capsys):
    """CLI routes t5 configs through generate_encdec (random weights)."""
    import os

    from hetu_galvatron_tpu.cli.generate import main as gen_main

    zoo = os.path.join(os.path.dirname(__file__), "..", "..",
                       "hetu_galvatron_tpu", "models", "configs")
    rc = gen_main([os.path.join(zoo, "t5-3b.yaml"),
                   "model.hidden_size=32", "model.num_hidden_layers=2",
                   "model.num_encoder_layers=2",
                   "model.num_attention_heads=2", "model.vocab_size=300",
                   "model.seq_length=16", "model.max_position_embeddings=32",
                   "model.make_vocab_size_divisible_by=1",
                   "prompt=translate this", "max_new_tokens=4"])
    assert rc == 0
    assert capsys.readouterr().out.strip() != ""


@pytest.mark.distributed
def test_t5_spmd_generate_matches_single_device(cpu_devices):
    """make_spmd_generate routes t5 configs through generate_encdec under
    the plan's GSPMD shardings; tp2 x dp2 greedy decode == single-device."""
    from hetu_galvatron_tpu.models.generate import generate_encdec
    from hetu_galvatron_tpu.parallel.spmd import (
        make_spmd_generate,
        shard_params,
    )
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.runtime.mesh import build_mesh

    params, axes = init_causal_lm(jax.random.key(2), T5)
    args = CoreArgs(model=T5.model_dump())
    args.parallel.global_tp_deg = 2
    args.parallel.vocab_tp = 2
    args.parallel.global_train_batch_size = 4
    mesh = build_mesh(4, 1, devices=cpu_devices[:4])
    hpc = get_hybrid_parallel_config(args, 4)
    enc = jnp.asarray(np.random.RandomState(8).randint(0, 64, (4, 8)))
    # fp32 on both sides: bf16 + resharded reduction order could flip an
    # argmax tie and cascade through the greedy decode (same convention as
    # the causal spmd-generate parity test)
    ref = generate_encdec(params, enc, T5, 5, compute_dtype=jnp.float32)
    fn, pspecs, batch_shd = make_spmd_generate(
        T5, hpc, mesh, axes, 5, compute_dtype=jnp.float32)
    sp = shard_params(params, pspecs, mesh)
    out = fn(sp, jax.device_put(enc, batch_shd), jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_t5_cross_attention_dropout_with_capable_kernel():
    """Cross-attention dropout routes through dropout-capable kernels
    (flash) instead of refusing; incapable kernels still refuse."""
    from hetu_galvatron_tpu.models.encdec import apply_cross_attention
    from hetu_galvatron_tpu.models.modules import xla_sdpa

    cfg = T5.model_copy(update={"attention_dropout": 0.2})
    from hetu_galvatron_tpu.models.encdec import init_cross_attention

    p, _ = init_cross_attention(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 6, 32), jnp.float32)
    mem = jax.random.normal(jax.random.key(2), (2, 8, 32), jnp.float32)

    def capable(q, k, v, **kw):
        kw.pop("dropout_rate", None)
        kw.pop("dropout_rng", None)
        return xla_sdpa(q, k, v, **kw)

    capable.supports_dropout = True
    out = apply_cross_attention(p, x, mem, cfg, sdpa_fn=capable,
                                compute_dtype=jnp.float32,
                                dropout_rng=jax.random.key(3))
    assert np.all(np.isfinite(np.asarray(out)))

    def incapable(q, k, v, **kw):
        return xla_sdpa(q, k, v, **kw)

    with pytest.raises(NotImplementedError, match="dropout-capable"):
        apply_cross_attention(p, x, mem, cfg, sdpa_fn=incapable,
                              compute_dtype=jnp.float32,
                              dropout_rng=jax.random.key(3))
