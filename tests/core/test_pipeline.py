"""Pipeline-engine correctness on the virtual 8-CPU mesh: pp=2/pp=4 with
GPipe and 1F1B must reproduce the single-device step (the reference's
test_pp.py compares loss trajectories vs HF for both schedules)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs, TrainArgs
from hetu_galvatron_tpu.models.builder import causal_lm_loss, init_causal_lm
from hetu_galvatron_tpu.runtime.dataloader import make_batch
from hetu_galvatron_tpu.runtime.hybrid_config import get_hybrid_parallel_config
from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine

pytestmark = [pytest.mark.parallel, pytest.mark.distributed]

CFG = ModelArgs(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
    vocab_size=128, max_position_embeddings=64, seq_length=16,
    hidden_act="swiglu", normalization="rmsnorm",
    position_embedding_type="rope", tie_word_embeddings=False,
    add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, ffn_hidden_size=128,
)

TRAIN = TrainArgs(lr=1e-2, clip_grad=1.0, weight_decay=0.01,
                  lr_decay_style="constant", lr_warmup_iters=0)


def _batch(bsz=16, seed=0):
    data = np.random.RandomState(seed).randint(
        0, 128, (bsz, CFG.seq_length + 1))
    return make_batch(data)


def _reference_step(params, batch, cfg=CFG, train=TRAIN):
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer
    import optax

    tx = make_optimizer(train)

    # (one program: op by op the model and Adam are some forty seconds of
    # compiles, which the file's first case paid)
    @jax.jit
    def step(params, jb):
        loss, grads = jax.value_and_grad(lambda p: causal_lm_loss(
            p, jb, cfg, compute_dtype=jnp.float32))(params)
        upd, _ = tx.update(grads, tx.init(params), params)
        return loss, optax.apply_updates(params, upd)

    loss, new_params = step(params, jax.tree.map(jnp.asarray, batch))
    return float(loss), new_params


def _pipeline_step(cfg, params, axes, batch, cpu_devices, **pkw):
    args = CoreArgs(model=cfg.model_dump(), train=TRAIN.model_dump())
    for k, v in pkw.items():
        setattr(args.parallel, k, v)
    hpc = get_hybrid_parallel_config(args, 8)
    eng = PipelineEngine(cfg, hpc, args.train, devices=cpu_devices,
                         compute_dtype=jnp.float32)
    sp = eng.split_params(params, axes)
    so = eng.init_opt(sp, axes)
    new_sp, _, metrics = eng.train_step(sp, so, batch)
    return metrics, eng.merge_params(new_sp)


# pp > 1 is on in no cell of the benchmark: one case of each schedule is
# tier-1's, the other plans the slow tier's
_slow = lambda **pkw: pytest.param(pkw, marks=pytest.mark.slow)  # noqa: E731
CASES = [
    dict(pp_deg=2, pipeline_type="gpipe", chunks=2),
    dict(pp_deg=2, pipeline_type="pipedream_flush", chunks=4),
    _slow(pp_deg=4, pipeline_type="gpipe", chunks=4),
    _slow(pp_deg=4, pipeline_type="pipedream_flush", chunks=2),
    _slow(pp_deg=2, pipeline_type="gpipe", chunks=2, global_tp_deg=2),
    _slow(pp_deg=2, pipeline_type="pipedream_flush", chunks=2, sdp=1),
]


@pytest.mark.parametrize(
    "pkw", CASES,
    ids=lambda d: ",".join(f"{k}={v}" for k, v in d.items()))
def test_pipeline_matches_single_device(pkw, cpu_devices):
    params, axes = init_causal_lm(jax.random.key(0), CFG)
    batch = _batch()
    ref_loss, ref_params = _reference_step(params, batch)
    pkw = dict(pkw, global_train_batch_size=16)
    metrics, new_params = _pipeline_step(CFG, params, axes, batch,
                                         cpu_devices, **pkw)
    assert abs(metrics["loss"] - ref_loss) < 2e-5, \
        f"loss {metrics['loss']} != {ref_loss}"
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_params),
            jax.tree_util.tree_leaves_with_path(new_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=3e-4,
            err_msg=f"param {jax.tree_util.keystr(pa)}")


def test_pipeline_tied_embeddings(cpu_devices):
    """GPT-2-style tied wte: grads must sum across first/last stages and the
    two copies must stay in sync after the update."""
    cfg = ModelArgs(
        hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=32, seq_length=16,
        tie_word_embeddings=True, make_vocab_size_divisible_by=1)
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    batch = _batch()
    ref_loss, ref_params = _reference_step(params, batch, cfg=cfg)
    args = CoreArgs(model=cfg.model_dump(), train=TRAIN.model_dump())
    args.parallel.pp_deg = 2
    args.parallel.chunks = 2
    args.parallel.global_train_batch_size = 8
    hpc = get_hybrid_parallel_config(args, 8)
    eng = PipelineEngine(cfg, hpc, args.train, devices=cpu_devices,
                         compute_dtype=jnp.float32)
    sp = eng.split_params(params, axes)
    so = eng.init_opt(sp, axes)
    new_sp, _, metrics = eng.train_step(sp, so, batch)
    assert abs(metrics["loss"] - ref_loss) < 2e-5
    # the two tied copies stay transposed-identical
    wte = np.asarray(jax.device_get(new_sp[0]["embed"]["wte"]))
    whead = np.asarray(jax.device_get(new_sp[-1]["head"]["whead"]))
    np.testing.assert_allclose(wte, whead.T, rtol=1e-6, atol=1e-7)
    merged = eng.merge_params(new_sp)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_params),
            jax.tree_util.tree_leaves_with_path(merged)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=3e-4,
            err_msg=f"param {jax.tree_util.keystr(pa)}")


def test_uneven_pp_division(cpu_devices):
    """5 layers over pp=2 -> [2, 3]; must still match single device."""
    cfg = CFG.model_copy(update={"num_hidden_layers": 5})
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    batch = _batch()
    ref_loss, ref_params = _reference_step(params, batch, cfg=cfg)
    metrics, new_params = _pipeline_step(
        cfg, params, axes, batch, cpu_devices,
        pp_deg=2, chunks=2, global_train_batch_size=8)
    assert abs(metrics["loss"] - ref_loss) < 2e-5
    for a, b in zip(jax.tree.leaves(ref_params),
                    jax.tree.leaves(new_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=3e-4)


def test_engine_builds_jits_lazily(cpu_devices):
    """The engine's stage/step jits are construct-on-first-use: building an
    engine creates none of them, eval-only use never builds backward/update
    programs, and an untied plan never builds the tied-grad transpose."""
    args = CoreArgs(model=CFG.model_dump(), train=TRAIN.model_dump())
    args.parallel.pp_deg = 2
    args.parallel.chunks = 2
    args.parallel.global_train_batch_size = 8
    hpc = get_hybrid_parallel_config(args, 8)
    eng = PipelineEngine(CFG, hpc, args.train, devices=cpu_devices,
                         compute_dtype=jnp.float32)
    assert eng._lazy_jits == {}, "construction built jits eagerly"
    params, axes = init_causal_lm(jax.random.key(0), CFG)
    sp = eng.split_params(params, axes)
    assert eng._lazy_jits == {}
    eng.eval_step(sp, _batch(bsz=8))
    # eval builds only the eval stage programs (and the fwd list they
    # share nothing with): no backward, update, clip or transpose jits
    assert "bwd" not in eng._lazy_jits
    assert "update" not in eng._lazy_jits
    assert "transpose" not in eng._lazy_jits
    so = eng.init_opt(sp, axes)
    eng.train_step(sp, so, _batch(bsz=8))
    # CFG is untied: a full train step still never builds the tied-grad
    # transpose program
    assert "transpose" not in eng._lazy_jits
    assert {"fwd", "bwd", "update", "gnorm", "clip"} <= set(eng._lazy_jits)


@pytest.mark.parametrize("pipeline_type", ["gpipe", "pipedream_flush"])
def test_interleaved_virtual_stages_match_single_device(pipeline_type,
                                                        cpu_devices):
    """vpp=2 over pp=2: 4 model chunks round-robin on 2 device groups
    (chunk c on group c % pp) must reproduce the single-device step —
    beyond the reference, which has no interleaved schedule."""
    cfg = CFG.model_copy(update={"num_hidden_layers": 5})
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    batch = _batch()
    ref_loss, ref_params = _reference_step(params, batch, cfg=cfg)
    metrics, new_params = _pipeline_step(
        cfg, params, axes, batch, cpu_devices,
        pp_deg=2, virtual_pp_deg=2, chunks=4, pipeline_type=pipeline_type,
        global_train_batch_size=16)
    assert abs(metrics["loss"] - ref_loss) < 2e-5
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_params),
            jax.tree_util.tree_leaves_with_path(new_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=3e-4,
            err_msg=f"param {jax.tree_util.keystr(pa)}")


def test_interleaved_tied_embeddings(cpu_devices):
    """Tied wte with vpp=2: embed chunk and head chunk live on DIFFERENT
    physical groups (chunk 0 -> group 0, chunk 3 -> group 1) and the grad
    reconciliation still keeps the copies in sync."""
    cfg = ModelArgs(
        hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=32, seq_length=16,
        tie_word_embeddings=True, make_vocab_size_divisible_by=1)
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    batch = _batch()
    ref_loss, ref_params = _reference_step(params, batch, cfg=cfg)
    args = CoreArgs(model=cfg.model_dump(), train=TRAIN.model_dump())
    args.parallel.pp_deg = 2
    args.parallel.virtual_pp_deg = 2
    args.parallel.chunks = 4
    args.parallel.global_train_batch_size = 16
    hpc = get_hybrid_parallel_config(args, 8)
    assert len(hpc.pp_division) == 4
    eng = PipelineEngine(cfg, hpc, args.train, devices=cpu_devices,
                         compute_dtype=jnp.float32)
    sp = eng.split_params(params, axes)
    so = eng.init_opt(sp, axes)
    new_sp, _, metrics = eng.train_step(sp, so, batch)
    assert abs(metrics["loss"] - ref_loss) < 2e-5
    wte = np.asarray(jax.device_get(new_sp[0]["embed"]["wte"]))
    whead = np.asarray(jax.device_get(new_sp[-1]["head"]["whead"]))
    np.testing.assert_allclose(wte, whead.T, rtol=1e-6, atol=1e-7)
    merged = eng.merge_params(new_sp)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_params),
            jax.tree_util.tree_leaves_with_path(merged)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=3e-4,
            err_msg=f"param {jax.tree_util.keystr(pa)}")
