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


# Scores that may exist at once, in elements: what 32 heads over 4096
# positions take in one call (2 GiB in float32, the largest the cells'
# references have always made). A longer sequence is cut into blocks of
# queries so that a block's scores against every key are no more than this.
SCORE_ELEMENTS = 32 * 4096 * 4096


def query_block(batch: int, heads: int, seq: int) -> int:
    """Queries a call of ``causal_attention`` takes at once: all of them
    where their scores fit ``SCORE_ELEMENTS``, else the largest power of two
    that does."""
    if batch * heads * seq * seq <= SCORE_ELEMENTS:
        return seq
    return 1 << max(0, (SCORE_ELEMENTS // (batch * heads * seq)).bit_length()
                    - 1)


def _attend(q, k, v, mask):
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(scores, axis=-1), v)


def causal_attention(q, k, v, window=None, block=None):
    """q, k: [B, heads, S, D], v: [B, heads, S, Dv] -> [B, heads, S, Dv].
    ``window``: the keys a query meets at most, itself included (``None`` or
    0: the whole causal span). A sequence longer than ``block`` queries
    (default ``query_block``) is taken a block at a time against the keys
    that block may meet, so ``[B, heads, block, S]`` and never
    ``[B, heads, S, S]`` exists; the rows of a softmax do not depend on each
    other, so the blocks are the same mathematics as the one call."""
    B, H, S, _ = q.shape
    block = block or query_block(B, H, S)
    if not window and block >= S:
        return _attend(q, k, v, jnp.tril(jnp.ones((S, S), bool)))
    out = []
    for lo in range(0, S, block):
        hi = min(lo + block, S)
        first = max(0, lo - window + 1) if window else 0   # the oldest key
        # key t of query s: not after it, and within the window
        ahead = jnp.arange(first, hi)[None, :] - jnp.arange(lo, hi)[:, None]
        mask = (ahead <= 0) & (ahead > -window) if window else ahead <= 0
        out.append(_attend(q[:, :, lo:hi], k[:, :, first:hi],
                           v[:, :, first:hi], mask))
    return jnp.concatenate(out, axis=2)


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
