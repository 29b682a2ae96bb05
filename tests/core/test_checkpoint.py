"""Checkpoint save/restore + HF interchange (reference
test_checkpoint_convert.py + distributed ckpt round-trips)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs, TrainArgs
from hetu_galvatron_tpu.models.builder import forward_causal_lm, init_causal_lm
from hetu_galvatron_tpu.runtime.checkpoint import (
    hf_to_params,
    latest_checkpoint,
    load_checkpoint,
    params_to_hf,
    save_checkpoint,
)
from hetu_galvatron_tpu.runtime.hybrid_config import get_hybrid_parallel_config
from hetu_galvatron_tpu.runtime.optimizer import make_optimizer

pytestmark = pytest.mark.model

TINY = ModelArgs(
    hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
    vocab_size=64, max_position_embeddings=16, seq_length=8,
    make_vocab_size_divisible_by=1)


def test_save_load_roundtrip(tmp_path):
    params, _ = init_causal_lm(jax.random.key(0), TINY)
    tx = make_optimizer(TrainArgs())
    opt = tx.init(params)
    d = save_checkpoint(str(tmp_path), 7, params, opt)
    assert latest_checkpoint(str(tmp_path)) == d
    target_p = jax.tree.map(lambda x: jnp.zeros_like(x), params)
    target_o = jax.tree.map(lambda x: jnp.zeros_like(x), opt)
    p2, o2, step = load_checkpoint(d, target_p, target_o)
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert o2 is not None
    for a, b in zip(jax.tree.leaves(opt), jax.tree.leaves(o2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_checkpoint_picks_max(tmp_path):
    params, _ = init_causal_lm(jax.random.key(0), TINY)
    save_checkpoint(str(tmp_path), 2, params)
    save_checkpoint(str(tmp_path), 10, params)
    assert latest_checkpoint(str(tmp_path)).endswith("step_10")
    assert latest_checkpoint(str(tmp_path / "nope")) is None


@pytest.mark.robustness
def test_latest_checkpoint_skips_stray_entries(tmp_path):
    """Non-integer step suffixes (orbax temp dirs, step_5.partial) and
    uncommitted dirs must be skipped, not crash resume with ValueError."""
    import os

    params, _ = init_causal_lm(jax.random.key(0), TINY)
    d = save_checkpoint(str(tmp_path), 3, params)
    (tmp_path / "step_x").mkdir()
    (tmp_path / "step_5.partial").mkdir()
    (tmp_path / "step_7.orbax-checkpoint-tmp-123").mkdir()
    (tmp_path / "step_9.tmp").mkdir()  # crashed mid-save staging dir
    # an uncommitted final-named dir (no marker, no meta.json)
    (tmp_path / "step_99").mkdir()
    (tmp_path / "step_4").write_text("a file, not a dir")
    assert latest_checkpoint(str(tmp_path)) == d
    # stray entries we did not create survive GC; our staging dir and the
    # uncommitted partial do not
    from hetu_galvatron_tpu.runtime.checkpoint import gc_checkpoints

    gc_checkpoints(str(tmp_path))
    assert os.path.isdir(tmp_path / "step_x")
    assert os.path.isdir(tmp_path / "step_5.partial")
    assert not os.path.isdir(tmp_path / "step_9.tmp")
    assert not os.path.isdir(tmp_path / "step_99")
    assert latest_checkpoint(str(tmp_path)) == d


@pytest.mark.robustness
def test_keep_last_retention(tmp_path):
    import os

    params, _ = init_causal_lm(jax.random.key(0), TINY)
    for s in (1, 2, 3):
        save_checkpoint(str(tmp_path), s, params)
    save_checkpoint(str(tmp_path), 4, params, keep_last=2)
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert kept == ["step_3", "step_4"]
    assert latest_checkpoint(str(tmp_path)).endswith("step_4")
    # the async commit path enforces the same bound (its own just-committed
    # dir must count toward keep_last, not read as in-flight)
    from hetu_galvatron_tpu.runtime.checkpoint import wait_for_checkpoints

    save_checkpoint(str(tmp_path), 5, params, async_save=True, keep_last=2)
    wait_for_checkpoints()
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert kept == ["step_4", "step_5"]


@pytest.mark.robustness
def test_async_save_commits_only_after_wait(tmp_path):
    """An async save is invisible to latest_checkpoint until
    wait_for_checkpoints commits it through the same marker/rename
    protocol."""
    import os

    from hetu_galvatron_tpu.runtime.checkpoint import wait_for_checkpoints

    params, _ = init_causal_lm(jax.random.key(0), TINY)
    d1 = save_checkpoint(str(tmp_path), 1, params)
    d2 = save_checkpoint(str(tmp_path), 2, params, async_save=True)
    # not committed yet: the staging dir exists, the final name does not
    assert os.path.isdir(d2 + ".tmp")
    assert not os.path.isdir(d2)
    assert latest_checkpoint(str(tmp_path)) == d1
    wait_for_checkpoints()
    assert latest_checkpoint(str(tmp_path)) == d2
    assert os.path.exists(os.path.join(d2, "meta.json"))
    # idempotent when drained
    wait_for_checkpoints()


@pytest.mark.robustness
def test_wait_for_checkpoints_drains_despite_failure(tmp_path):
    """A failing commit mid-drain must not abandon the remaining async
    saves unawaited: everything drains, the first error re-raises."""
    from hetu_galvatron_tpu.runtime import checkpoint as ck

    class FakeCkptr:
        def __init__(self, log, name, fail=False):
            self.log, self.name, self.fail = log, name, fail

        def wait_until_finished(self):
            self.log.append(self.name)
            if self.fail:
                raise IOError(f"flaky wait: {self.name}")

    log = []
    for i, fail in enumerate([False, True, False]):
        d = tmp_path / f"step_{i + 1}"
        d.mkdir()
        ck._PENDING.append(ck._PendingSave(
            [FakeCkptr(log, f"c{i}", fail)], str(d) + ".tmp", str(d),
            str(tmp_path)))
    # give the non-failing entries real staging dirs so their commit works
    (tmp_path / "step_1.tmp").mkdir()
    (tmp_path / "step_3.tmp").mkdir()
    with pytest.raises(IOError, match="flaky wait: c1"):
        ck.wait_for_checkpoints()
    assert log == ["c0", "c1", "c2"]  # every save awaited, none dropped
    assert not ck._PENDING


@pytest.mark.robustness
def test_train_state_rides_meta(tmp_path):
    from hetu_galvatron_tpu.runtime.checkpoint import read_checkpoint_meta

    params, _ = init_causal_lm(jax.random.key(0), TINY)
    ts = {"step": 4, "seed": 7, "batches_consumed": 4,
          "rerun": {"records": [], "ema": 2.5}}
    d = save_checkpoint(str(tmp_path), 4, params, train_state=ts)
    meta = read_checkpoint_meta(d)
    assert meta["step"] == 4
    assert meta["train_state"] == ts
    assert read_checkpoint_meta(str(tmp_path / "nowhere")) == {}


def test_plan_mismatch_raises(tmp_path):
    params, _ = init_causal_lm(jax.random.key(0), TINY)
    args = CoreArgs(model=TINY.model_dump())
    args.parallel.global_tp_deg = 2
    hpc = get_hybrid_parallel_config(args, 8)
    d = save_checkpoint(str(tmp_path), 1, params, hpc=hpc)
    args2 = CoreArgs(model=TINY.model_dump())
    args2.parallel.global_tp_deg = 1
    hpc2 = get_hybrid_parallel_config(args2, 8)
    with pytest.raises(ValueError, match="plan mismatch"):
        load_checkpoint(d, params, hpc=hpc2, strict_plan=True)
    # non-strict restore reshards instead
    p2, _, _ = load_checkpoint(d, params, hpc=hpc2)
    assert p2 is not None


def test_hf_gpt2_roundtrip_and_forward():
    torch = pytest.importorskip("torch")
    from transformers import GPT2Config, GPT2LMHeadModel

    hf_cfg = GPT2Config(vocab_size=64, n_positions=16, n_embd=32, n_layer=2,
                        n_head=2, activation_function="gelu_new",
                        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    torch.manual_seed(0)
    hf = GPT2LMHeadModel(hf_cfg).eval()
    params = hf_to_params(hf.state_dict(), TINY)
    tokens_np = np.random.RandomState(0).randint(0, 64, (2, 8))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens_np)).logits.numpy()
    ours = forward_causal_lm(params, jnp.asarray(tokens_np), TINY,
                             compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-4, atol=2e-4)
    # g2h inverse gives back identical tensors
    sd = params_to_hf(params, TINY)
    for k, v in sd.items():
        np.testing.assert_allclose(v, np.asarray(hf.state_dict()[k]),
                                   atol=1e-6, err_msg=k)


def test_hf_llama_roundtrip():
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    cfg = ModelArgs(
        model_type="llama", hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, num_key_value_heads=2, ffn_hidden_size=48,
        vocab_size=64, max_position_embeddings=16, seq_length=8,
        hidden_act="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", tie_word_embeddings=False,
        add_bias_linear=False, add_qkv_bias=False,
        make_vocab_size_divisible_by=1)
    hf_cfg = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=16, tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False)
    torch.manual_seed(0)
    hf = LlamaForCausalLM(hf_cfg).eval()
    params = hf_to_params(hf.state_dict(), cfg)
    sd = params_to_hf(params, cfg)
    ref_sd = hf.state_dict()
    for k, v in sd.items():
        np.testing.assert_allclose(v, np.asarray(ref_sd[k]), atol=1e-6,
                                   err_msg=k)


def test_resume_continues_training(tmp_path):
    """Save mid-run, restore, and verify the next step's loss matches an
    uninterrupted run exactly."""
    from hetu_galvatron_tpu.runtime.dataloader import make_batch
    from hetu_galvatron_tpu.runtime.trainer import make_loss_fn, make_train_step

    params, _ = init_causal_lm(jax.random.key(0), TINY)
    tx = make_optimizer(TrainArgs(lr=1e-2, lr_decay_style="constant"))
    step = jax.jit(make_train_step(make_loss_fn(TINY,
                                                compute_dtype=jnp.float32),
                                   tx))
    batch = jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(0).randint(0, 64, (4, 9))))
    opt = tx.init(params)
    p1, o1, _ = step(params, opt, batch)
    d = save_checkpoint(str(tmp_path), 1, p1, o1)
    p2, o2, _ = step(p1, o1, batch)  # uninterrupted second step

    rp, ro, _ = load_checkpoint(d, jax.tree.map(jnp.zeros_like, p1),
                                jax.tree.map(jnp.zeros_like, o1))
    rp2, ro2, m = step(rp, ro, batch)
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(rp2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_hf_mixtral_roundtrip():
    torch = pytest.importorskip("torch")
    from transformers import MixtralConfig, MixtralForCausalLM

    cfg = ModelArgs(
        model_type="moe", hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, num_key_value_heads=2, ffn_hidden_size=48,
        moe_ffn_hidden_size=48, vocab_size=64, max_position_embeddings=16,
        seq_length=8, hidden_act="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", tie_word_embeddings=False,
        add_bias_linear=False, add_qkv_bias=False,
        make_vocab_size_divisible_by=1, num_experts=4, moe_topk=2)
    hf_cfg = MixtralConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=16, tie_word_embeddings=False)
    torch.manual_seed(0)
    hf = MixtralForCausalLM(hf_cfg).eval()
    params = hf_to_params(hf.state_dict(), cfg)
    assert "moe" in params["layers"][0]
    assert params["layers"][0]["moe"]["win"].shape == (4, 32, 96)
    sd = params_to_hf(params, cfg)
    ref_sd = hf.state_dict()
    for k, v in sd.items():
        np.testing.assert_allclose(v, np.asarray(ref_sd[k]), atol=1e-6,
                                   err_msg=k)
    # imported params run a finite forward through our MoE stack
    import jax, jax.numpy as jnp
    from hetu_galvatron_tpu.models.builder import causal_lm_loss
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 8)))
    loss = causal_lm_loss(params, {"tokens": tokens, "labels": tokens}, cfg,
                          compute_dtype=jnp.float32)
    assert np.isfinite(float(loss))


def test_hf_bert_roundtrip_and_forward():
    """BERT h2g: HF BertForMaskedLM logits must match our post-norm encoder
    exactly (embeddings LN + post-LN blocks + MLM transform head); g2h is the
    tensor-exact inverse (token-type folded into wpe, exported as zeros)."""
    torch = pytest.importorskip("torch")
    from transformers import BertConfig, BertForMaskedLM

    cfg = ModelArgs(
        model_type="bert", hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, ffn_hidden_size=64, vocab_size=64,
        max_position_embeddings=16, seq_length=8, hidden_act="gelu_exact",
        tie_word_embeddings=True, make_vocab_size_divisible_by=1,
        layernorm_epsilon=1e-12)
    hf_cfg = BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16, hidden_act="gelu",
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    torch.manual_seed(0)
    hf = BertForMaskedLM(hf_cfg).eval()
    params = hf_to_params(hf.state_dict(), cfg)
    tokens_np = np.random.RandomState(0).randint(0, 64, (2, 8))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens_np)).logits.numpy()
    ours = forward_causal_lm(params, jnp.asarray(tokens_np), cfg,
                             compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-4, atol=2e-4)
    sd = params_to_hf(params, cfg)
    ref_sd = {k: np.asarray(v) for k, v in hf.state_dict().items()}
    for k, v in sd.items():
        if k == "bert.embeddings.position_embeddings.weight":
            # import folds token_type[0] into wpe; export keeps the fold
            np.testing.assert_allclose(
                v, ref_sd[k]
                + ref_sd["bert.embeddings.token_type_embeddings.weight"][0],
                atol=1e-6, err_msg=k)
        elif k == "bert.embeddings.token_type_embeddings.weight":
            np.testing.assert_allclose(v, 0.0)
        else:
            np.testing.assert_allclose(v, ref_sd[k], atol=1e-6, err_msg=k)
    # and re-importing the export reproduces the same forward
    params2 = hf_to_params(sd, cfg)
    ours2 = forward_causal_lm(params2, jnp.asarray(tokens_np), cfg,
                              compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ours2), np.asarray(ours), atol=1e-6)


@pytest.mark.robustness
@pytest.mark.elastic
def test_world_mismatch_raises_typed_error(tmp_path):
    """A topology-changed resume must fail AT LOAD with both worlds named
    (not as a shape error deep in device_put) — the exact condition the
    elastic resume path catches to trigger re-search + reshard."""
    from hetu_galvatron_tpu.runtime.checkpoint import WorldSizeMismatchError

    params, _ = init_causal_lm(jax.random.key(0), TINY)
    args = CoreArgs.model_validate({"model": TINY.model_dump()})
    hpc = get_hybrid_parallel_config(args, 2)
    save_checkpoint(str(tmp_path), 3, params, hpc=hpc)
    d = latest_checkpoint(str(tmp_path))

    # same world: loads fine with the check armed
    p2, _, step = load_checkpoint(d, params, expected_world=2)
    assert step == 3

    with pytest.raises(WorldSizeMismatchError) as ei:
        load_checkpoint(d, params, expected_world=1)
    err = ei.value
    assert err.stored_world == 2 and err.live_world == 1
    assert "2-device" in str(err) and "1 devices" in str(err)
    assert "reshard" in str(err)  # actionable: names the remedy

    # legacy checkpoints (no plan fingerprint) stay loadable
    save_checkpoint(str(tmp_path / "legacy"), 1, params)
    d2 = latest_checkpoint(str(tmp_path / "legacy"))
    load_checkpoint(d2, params, expected_world=1)


@pytest.mark.robustness
@pytest.mark.elastic
def test_gc_never_reaps_live_resume_selection(tmp_path):
    """keep_last pruning racing a concurrent resume must never delete the
    step latest_checkpoint() just selected — the selection is held out of
    the prune set until the next selection releases it."""
    import os

    from hetu_galvatron_tpu.runtime.checkpoint import gc_checkpoints

    params, _ = init_causal_lm(jax.random.key(0), TINY)
    for s in (1, 2, 3):
        save_checkpoint(str(tmp_path), s, params)
    sel = latest_checkpoint(str(tmp_path))
    assert sel.endswith("step_3")

    # a newer save commits and prunes aggressively while the resume is
    # between its latest_checkpoint() and the shard/meta reads
    save_checkpoint(str(tmp_path), 4, params, keep_last=1)
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert kept == ["step_3", "step_4"]  # selection survived; 1/2 pruned
    load_checkpoint(sel, params)  # the resume still completes

    # the NEXT selection releases the old protection
    assert latest_checkpoint(str(tmp_path)).endswith("step_4")
    gc_checkpoints(str(tmp_path), keep_last=1)
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert kept == ["step_4"]


def test_hf_t5_roundtrip():
    """T5 h2g/g2h: every projection/norm tensor round-trips exactly (position
    scheme intentionally differs — models/encdec.py is RoPE/learned by
    design, so no logit parity leg here)."""
    torch = pytest.importorskip("torch")
    from transformers import T5Config, T5ForConditionalGeneration

    cfg = ModelArgs(
        model_type="t5", hidden_size=32, num_hidden_layers=2,
        num_encoder_layers=3, num_attention_heads=2, ffn_hidden_size=48,
        vocab_size=64, max_position_embeddings=16, seq_length=8,
        hidden_act="geglu", normalization="rmsnorm",
        position_embedding_type="rope", tie_word_embeddings=False,
        add_bias_linear=False, add_qkv_bias=False,
        make_vocab_size_divisible_by=1)
    hf_cfg = T5Config(
        vocab_size=64, d_model=32, d_kv=16, d_ff=48, num_layers=3,
        num_decoder_layers=2, num_heads=2, feed_forward_proj="gated-gelu",
        tie_word_embeddings=False, dropout_rate=0.0)
    torch.manual_seed(0)
    hf = T5ForConditionalGeneration(hf_cfg).eval()
    params = hf_to_params(hf.state_dict(), cfg)
    assert len(params["enc_layers"]) == 3 and len(params["layers"]) == 2
    sd = params_to_hf(params, cfg)
    ref_sd = {k: np.asarray(v) for k, v in hf.state_dict().items()}
    assert len(sd) > 40
    for k, v in sd.items():
        np.testing.assert_allclose(v, ref_sd[k], atol=1e-6, err_msg=k)


_EXPORT = dict(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
               ffn_hidden_size=48, vocab_size=64, max_position_embeddings=16,
               seq_length=8, make_vocab_size_divisible_by=1)
_LLAMA = dict(model_type="llama", normalization="rmsnorm",
              position_embedding_type="rope", tie_word_embeddings=False,
              add_bias_linear=False, **_EXPORT)
EXPORTED = {
    "gpt2_mha_gelu_biases": ModelArgs(add_qkv_bias=True, **_EXPORT),
    "llama_mha_swiglu": ModelArgs(hidden_act="swiglu", add_qkv_bias=False,
                                  **_LLAMA),
    "llama_gqa_swiglu": ModelArgs(hidden_act="swiglu", add_qkv_bias=False,
                                  num_key_value_heads=2, **_LLAMA),
    "llama_gqa_swiglu_qkv_bias": ModelArgs(
        hidden_act="swiglu", add_qkv_bias=True, num_key_value_heads=2,
        **_LLAMA),
    "llama_mqa_geglu": ModelArgs(hidden_act="geglu", add_qkv_bias=False,
                                 num_key_value_heads=1, **_LLAMA),
    "bert_mha_gelu_biases": ModelArgs(
        model_type="bert", post_norm=True, hidden_act="gelu_exact",
        add_bias_linear=True, add_qkv_bias=True, **_EXPORT),
    "t5_gated": ModelArgs(
        model_type="t5", num_encoder_layers=2, hidden_act="geglu",
        normalization="rmsnorm", position_embedding_type="rope",
        tie_word_embeddings=False, add_bias_linear=False, add_qkv_bias=False,
        **_EXPORT),
    "t5_plain": ModelArgs(
        model_type="t5", num_encoder_layers=2, hidden_act="relu",
        normalization="rmsnorm", position_embedding_type="rope",
        tie_word_embeddings=False, add_bias_linear=False, add_qkv_bias=False,
        **_EXPORT),
}


@pytest.mark.parametrize("name", list(EXPORTED))
def test_export_then_import_is_bit_exact(name):
    """params_to_hf -> hf_to_params gives back every stored leaf, bit for
    bit, in the layout the layers compute on ([q | k | v], [gate | up]):
    MHA, GQA and MQA, gated and plain MLPs, with and without biases."""
    cfg = EXPORTED[name]
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    # zero-initialised biases would hide a misplaced one
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    back = hf_to_params(params_to_hf(params, cfg), cfg)
    flat = dict(jax.tree_util.tree_leaves_with_path(back))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        assert path in flat, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(flat[path]),
            err_msg=jax.tree_util.keystr(path))


def test_orbax_is_imported_without_the_distribution_scan():
    """``runtime/checkpoint.py`` answers ``packages_distributions`` with an
    empty map only while orbax loads: afterwards the real function is back,
    and it comes back when the import raises too."""
    import importlib.metadata as metadata

    from hetu_galvatron_tpu.runtime import checkpoint

    real = metadata.packages_distributions
    assert real is not dict and checkpoint.ocp.__name__ == "orbax.checkpoint"
    with checkpoint._no_distribution_scan():
        assert metadata.packages_distributions() == {}
    assert metadata.packages_distributions is real
    with pytest.raises(ImportError):
        with checkpoint._no_distribution_scan():
            raise ImportError("stands for an import that fails")
    assert metadata.packages_distributions is real
