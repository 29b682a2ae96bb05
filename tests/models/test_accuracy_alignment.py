"""Accuracy alignment: multi-step loss trajectories vs a HuggingFace torch
baseline trained from identical weights on identical batches (the reference's
tier-2 method, tests/models/test_model_correctness.py + the
scripts/accuracy_alignment harness)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import ModelArgs, TrainArgs
from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params
from hetu_galvatron_tpu.runtime.optimizer import make_optimizer
from hetu_galvatron_tpu.runtime.trainer import make_loss_fn, make_train_step

pytestmark = pytest.mark.model

CFG = ModelArgs(
    hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
    vocab_size=64, max_position_embeddings=16, seq_length=8,
    make_vocab_size_divisible_by=1)

STEPS = 5
LR = 1e-3


def test_gpt2_loss_trajectory_matches_hf():
    torch = pytest.importorskip("torch")
    from transformers import GPT2Config, GPT2LMHeadModel

    hf_cfg = GPT2Config(
        vocab_size=64, n_positions=16, n_embd=32, n_layer=2, n_head=2,
        activation_function="gelu_new", resid_pdrop=0.0, embd_pdrop=0.0,
        attn_pdrop=0.0)
    torch.manual_seed(0)
    hf = GPT2LMHeadModel(hf_cfg)
    params = hf_to_params(hf.state_dict(), CFG)

    train = TrainArgs(lr=LR, weight_decay=0.01, adam_beta1=0.9,
                      adam_beta2=0.95, adam_eps=1e-8, clip_grad=0.0,
                      lr_decay_style="constant", lr_warmup_iters=0)
    tx = make_optimizer(train)
    step = jax.jit(make_train_step(
        make_loss_fn(CFG, compute_dtype=jnp.float32), tx))

    # torch AdamW with decoupled weight decay on >=2D params only, matching
    # our optimizer's decay mask
    decay, no_decay = [], []
    for name, p in hf.named_parameters():
        (decay if p.ndim >= 2 else no_decay).append(p)
    opt = torch.optim.AdamW(
        [{"params": decay, "weight_decay": 0.01},
         {"params": no_decay, "weight_decay": 0.0}],
        lr=LR, betas=(0.9, 0.95), eps=1e-8)

    rng = np.random.RandomState(0)
    opt_state = tx.init(params)
    ours, theirs = [], []
    for it in range(STEPS):
        tokens = rng.randint(0, 64, (4, 9))
        batch = {"tokens": jnp.asarray(tokens[:, :-1]),
                 "labels": jnp.asarray(tokens[:, 1:])}
        params, opt_state, metrics = step(params, opt_state, batch)
        ours.append(float(metrics["loss"]))

        t = torch.tensor(tokens[:, :-1])
        lbl = torch.tensor(tokens[:, 1:])
        out = hf(t)
        loss = torch.nn.functional.cross_entropy(
            out.logits.reshape(-1, 64), lbl.reshape(-1))
        opt.zero_grad()
        loss.backward()
        opt.step()
        theirs.append(float(loss))

    np.testing.assert_allclose(ours, theirs, rtol=2e-3, atol=2e-3,
                               err_msg=f"ours={ours} hf={theirs}")


def test_bert_mlm_loss_trajectory_matches_hf():
    """Tier-2 alignment for the encoder family: 5 AdamW steps of MLM from an
    HF BertForMaskedLM init must track HF exactly (post-norm blocks,
    embedding LN, MLM transform head — runtime/checkpoint.py bert h2g)."""
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
    hf = BertForMaskedLM(hf_cfg)
    params = hf_to_params(hf.state_dict(), cfg)

    train = TrainArgs(lr=LR, weight_decay=0.01, adam_beta1=0.9,
                      adam_beta2=0.95, adam_eps=1e-8, clip_grad=0.0,
                      lr_decay_style="constant", lr_warmup_iters=0)
    tx = make_optimizer(train)
    step = jax.jit(make_train_step(
        make_loss_fn(cfg, compute_dtype=jnp.float32), tx))

    decay, no_decay = [], []
    for name, p in hf.named_parameters():
        (decay if p.ndim >= 2 else no_decay).append(p)
    opt = torch.optim.AdamW(
        [{"params": decay, "weight_decay": 0.01},
         {"params": no_decay, "weight_decay": 0.0}],
        lr=LR, betas=(0.9, 0.95), eps=1e-8)

    rng = np.random.RandomState(0)
    opt_state = tx.init(params)
    ours, theirs = [], []
    for it in range(STEPS):
        orig = rng.randint(0, 64, (4, 8))
        tokens = orig.copy()
        mask = rng.rand(4, 8) < 0.2
        tokens[mask] = 63  # mask token
        batch = {"tokens": jnp.asarray(tokens),
                 "labels": jnp.asarray(orig),
                 "loss_mask": jnp.asarray(mask.astype(np.float32))}
        params, opt_state, metrics = step(params, opt_state, batch)
        ours.append(float(metrics["loss"]))

        t = torch.tensor(tokens)
        lbl = torch.tensor(orig.copy())
        lbl[~torch.tensor(mask)] = -100  # HF ignores unmasked positions
        out = hf(t, labels=lbl)
        opt.zero_grad()
        out.loss.backward()
        opt.step()
        theirs.append(float(out.loss))

    np.testing.assert_allclose(ours, theirs, rtol=2e-3, atol=2e-3,
                               err_msg=f"ours={ours} hf={theirs}")
