"""Reference family ``tiny_packed``: a family whose batches hold more than
ids, kept here as a test fixture (``tiny.add_packed_family`` copies it into
``<copy>/benchmark/reference/``): what a later PR adds for documents packed
into one sequence, and the form a family with a tower in front of its
decoder takes its patches in.

Mistral's block on the program's packed batches (``data.dataset=indexed``
with ``data.reset_position_ids``, ``data.reset_attention_mask`` and
``data.eod_mask_loss``, ``runtime/dataloader.py::packed_doc_fields``):
``nll_sum`` names the keyword ``batch`` and so is handed the first batch's
other fields, the same rows of each. A query meets the keys of its own
document alone (``segment_ids``), the rotation restarts where a document
does (``position_ids``), and the loss is summed over the positions
``loss_mask`` marks, which leaves out the position that holds an
end-of-document id; ``reference.mean_loss`` divides by the mask's sum.

``IGNORES`` is what the test's control sets to show that the comparison
fails when the family leaves a field unread.
"""

from __future__ import annotations

import math
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark.reference.plain import (
    Weights,
    merge_heads,
    rms_norm,
    rotate_half,
    split_heads,
)

IGNORES: tuple = ()   # fields of ``batch`` left unread: a test's control


def rope_at(x, positions, theta):
    """x: [B, heads, S, D]; positions: [B, S], a document's own."""
    D = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[:, None, :, None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def attention_within_documents(q, k, v, segments):
    """Causal, and a key of another document is not met: [B, S] segments."""
    S = q.shape[2]
    mask = (jnp.tril(jnp.ones((S, S), bool))[None]
            & (segments[:, :, None] == segments[:, None, :]))[:, None]
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(scores, axis=-1), v)


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None,
            batch: Optional[Mapping[str, Any]] = None):
    batch = {k: v for k, v in (batch or {}).items() if k not in IGNORES}
    B, S = tokens.shape
    positions = batch.get("position_ids",
                          jnp.broadcast_to(jnp.arange(S), (B, S)))
    segments = batch.get("segment_ids", jnp.zeros((B, S), jnp.int32))
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h = w["model.embed_tokens.weight"][tokens]
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        p = f"model.layers.{i}."
        a = rms_norm(h, w[p + "input_layernorm.weight"], eps)
        q = rope_at(split_heads(a @ w[p + "self_attn.q_proj.weight"].T, nh),
                    positions, theta)
        k = rope_at(split_heads(a @ w[p + "self_attn.k_proj.weight"].T, nkv),
                    positions, theta)
        v = split_heads(a @ w[p + "self_attn.v_proj.weight"].T, nkv)
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        h = h + merge_heads(attention_within_documents(q, k, v, segments)) \
            @ w[p + "self_attn.o_proj.weight"].T
        m = rms_norm(h, w[p + "post_attention_layernorm.weight"], eps)
        m = (jax.nn.silu(m @ w[p + "mlp.gate_proj.weight"].T)
             * (m @ w[p + "mlp.up_proj.weight"].T))
        h = h + m @ w[p + "mlp.down_proj.weight"].T
    h = rms_norm(h, w["model.norm.weight"], eps)
    logp = jax.nn.log_softmax(h @ w["lm_head.weight"].T, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * batch.get("loss_mask", jnp.ones((B, S))))


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """The dense count: the mask across documents is the traffic's, and a
    count from shapes does not know where the documents end."""
    return flops.forward_flops_per_token(sizes)
