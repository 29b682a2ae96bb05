"""ORACLE for ``test_the_moved_references_return_the_old_loss_bit_for_bit``:
``benchmark/reference/decoder_lm.py`` as it was before PR 26 split it into
``reference/plain.py``, ``gpt2.py``, ``mistral.py`` and a family-neutral
``mean_loss``. Nothing but that test imports it. Its own words follow.

Plain reference: forward pass and mean token cross-entropy of a GPT-2
block stack and of a Mistral block stack.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no remat, no
sharding, no cache, nothing imported from the program. Written from the
published descriptions (Radford et al. 2019 and the ``gpt2`` model card;
Jiang et al. 2023, arXiv:2310.06825, and the ``mistral`` model card), and
fed weights under their public Hugging Face names, so it does not know the
program's parameter tree.

Departures from the published models, each because the program under test
trains that way and the comparison is of the same mathematics:

* GPT-2: no dropout (published 0.1). The token table may carry extra
  padding rows beyond ``vocab_size`` (``extra_vocab_rows``): the program
  pads 50257 to 50304, draws random tokens over all of them and keeps them
  in its softmax, so the reference must see the same rows.
* Mistral: no sliding window (published 4096). With sequences of at most
  4096 tokens the window and the causal mask are the same mask.

TOLERANCE. The program computes in bfloat16 with float32 accumulation,
norms, softmax and loss; this file computes in float32 throughout. Each
configuration's file states ``reference.loss_tolerance``, the allowed
|program's step-0 loss - reference loss|, and benchmark/README.md says how
it was set from chip runs (the bf16 error of the mean loss over a batch of
thousands of tokens against what a dropped block or a wrong precision
moves it).
"""

from __future__ import annotations

import math
from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

Weights = Mapping[str, jax.Array]


def _layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * weight


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _causal_attention(q, k, v):
    """q, k, v: [B, heads, S, D] -> [B, heads, S, D]."""
    S, D = q.shape[-2], q.shape[-1]
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) / math.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(scores, axis=-1), v)


def _heads(x, n):
    B, S, F = x.shape
    return x.reshape(B, S, n, F // n).transpose(0, 2, 1, 3)


def _merge(x):
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def _token_nll_sum(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


# ---------------------------------------------------------------------------
# GPT-2
# ---------------------------------------------------------------------------


def gpt2_nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
                 layers: Optional[int] = None):
    """Sum of token negative log-likelihoods. ``w`` holds
    ``transformer.*`` tensors; ``extra_vocab_rows`` (optional) are padding
    rows appended to ``transformer.wte.weight``."""
    wte = w["transformer.wte.weight"]
    if "extra_vocab_rows" in w:
        wte = jnp.concatenate([wte, w["extra_vocab_rows"]], axis=0)
    S = tokens.shape[1]
    eps, nh = cfg["layer_norm_epsilon"], cfg["n_head"]
    h = wte[tokens] + w["transformer.wpe.weight"][:S]
    for i in range(cfg["n_layer"] if layers is None else layers):
        p = f"transformer.h.{i}."
        a = _layer_norm(h, w[p + "ln_1.weight"], w[p + "ln_1.bias"], eps)
        qkv = a @ w[p + "attn.c_attn.weight"] + w[p + "attn.c_attn.bias"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        a = _merge(_causal_attention(_heads(q, nh), _heads(k, nh),
                                     _heads(v, nh)))
        h = h + a @ w[p + "attn.c_proj.weight"] + w[p + "attn.c_proj.bias"]
        m = _layer_norm(h, w[p + "ln_2.weight"], w[p + "ln_2.bias"], eps)
        m = _gelu_new(m @ w[p + "mlp.c_fc.weight"] + w[p + "mlp.c_fc.bias"])
        h = h + m @ w[p + "mlp.c_proj.weight"] + w[p + "mlp.c_proj.bias"]
    h = _layer_norm(h, w["transformer.ln_f.weight"],
                    w["transformer.ln_f.bias"], eps)
    return _token_nll_sum(h @ wte.T, labels)   # tied output head


# ---------------------------------------------------------------------------
# Mistral
# ---------------------------------------------------------------------------


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope(x, theta):
    """x: [B, heads, S, D]; positions 0..S-1."""
    S, D = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def mistral_nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
                    layers: Optional[int] = None):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h = w["model.embed_tokens.weight"][tokens]
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        p = f"model.layers.{i}."
        a = _rms_norm(h, w[p + "input_layernorm.weight"], eps)
        q = _rope(_heads(a @ w[p + "self_attn.q_proj.weight"].T, nh), theta)
        k = _rope(_heads(a @ w[p + "self_attn.k_proj.weight"].T, nkv), theta)
        v = _heads(a @ w[p + "self_attn.v_proj.weight"].T, nkv)
        # grouped-query attention: each key-value head serves nh/nkv
        # consecutive query heads
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        a = _merge(_causal_attention(q, k, v))
        h = h + a @ w[p + "self_attn.o_proj.weight"].T
        m = _rms_norm(h, w[p + "post_attention_layernorm.weight"], eps)
        m = (jax.nn.silu(m @ w[p + "mlp.gate_proj.weight"].T)
             * (m @ w[p + "mlp.up_proj.weight"].T))
        h = h + m @ w[p + "mlp.down_proj.weight"].T
    h = _rms_norm(h, w["model.norm.weight"], eps)
    head = (w["model.embed_tokens.weight"] if cfg.get("tie_word_embeddings")
            else w["lm_head.weight"])
    return _token_nll_sum(h @ head.T, labels)


FAMILIES = {"gpt2": gpt2_nll_sum, "mistral": mistral_nll_sum}


def mean_loss(family: str, weights: Mapping[str, np.ndarray], cfg: Mapping,
              tokens: np.ndarray, labels: np.ndarray, *,
              rows_per_call: int = 1, dtype=jnp.float32,
              layers: Optional[int] = None) -> float:
    """Mean token cross-entropy of ``tokens`` -> ``labels`` ([B, S] each),
    computed ``rows_per_call`` sequences at a time so that the logits of a
    whole batch never have to exist. ``dtype`` and ``layers`` are there to
    show that the comparison fails when it should (a lower precision, a
    dropped block); a real check leaves them alone."""
    fn = FAMILIES[family]
    w: Dict[str, jax.Array] = {k: jnp.asarray(v, dtype)
                               for k, v in weights.items()}
    precision = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def nll_sum(w, t, l):
        with jax.default_matmul_precision(precision):
            return fn(w, cfg, t, l, layers=layers).astype(jnp.float32)

    total = 0.0
    for i in range(0, tokens.shape[0], rows_per_call):
        total += float(nll_sum(w, jnp.asarray(tokens[i:i + rows_per_call]),
                               jnp.asarray(labels[i:i + rows_per_call])))
    return total / labels.size
