"""The plain pieces every reference family is built from.

Straightforward ``jax.numpy``: no kernel, no remat, no sharding, no cache,
nothing imported from the program. A family's file
(``benchmark/reference/<family>.py``) imports what it needs from here and
writes only what is its own. Precision and dtype are set by the caller
(``benchmark/reference/__init__.py::mean_loss``), not here.
"""

from __future__ import annotations

import math
from typing import Mapping

import jax
import jax.numpy as jnp

Weights = Mapping[str, jax.Array]


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * weight


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def causal_attention(q, k, v):
    """q, k, v: [B, heads, S, D] -> [B, heads, S, D]."""
    S, D = q.shape[-2], q.shape[-1]
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) / math.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(scores, axis=-1), v)


def split_heads(x, n):
    B, S, F = x.shape
    return x.reshape(B, S, n, F // n).transpose(0, 2, 1, 3)


def merge_heads(x):
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def token_nll_sum(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope(x, theta):
    """x: [B, heads, S, D]; positions 0..S-1."""
    S, D = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)
