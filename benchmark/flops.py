"""Operations and bytes, from shapes: the model step and the flash kernel.

These are the operations the ALGORITHM requires, which is what a
utilisation or a roofline share is measured against. Every count is held
to these rules, the one here and the one a reference family exports
(``benchmark/reference/<family>.py::forward_flops_per_token``):

* matmuls only (2 x m x n x k each); norms, activations, softmax and the
  optimizer update are not counted;
* forward + backward = 3 x forward (each forward matmul has two in the
  backward pass);
* attention is counted CAUSAL: a query at position i meets i + 1 keys, so
  (S + 1) / 2 on average and not S;
* recomputation (per-layer remat, the flash backward's recomputed scores)
  is not counted: it is work the implementation chose, not work the model
  needs.

The model's count comes from its family, because only the family knows its
layer: ``gpt2`` and ``mistral`` return the dense count below; a family with
experts adds its experts per token and its router, one with latent
attention its own projections, from ``attention_flops_per_token`` and
``head_flops_per_token`` and the keys of its configuration's file. The dense
count refuses a program that has experts, so that a configuration cannot
name a dense family and have one expert counted where a token uses several.

Where this differs from ``hetu_galvatron_tpu/observability/telemetry.py``
and ``models/builder.py::model_flops_per_token``: those count the S x S
attention matmuls dense (twice the causal work), so a causal model's MFU is
overstated there; and the program's peak lookup matches ``device_kind`` by
substring and returns ``None`` in silence for an unknown chip, where
``peaks.py`` raises.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict


@dataclass(frozen=True)
class Sizes:
    """What the arithmetic needs of a decoder-only model, as it is run."""

    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    ffn_matrices: int   # 3 for a gated MLP (gate, up, down), else 2
    vocab: int          # published rows; padding rows are not required work
    seq: int
    experts: int = 0    # routed experts a layer; 0 for a dense MLP

    @classmethod
    def of(cls, cfg: Any) -> "Sizes":
        """From the program's resolved ``ModelArgs`` (its public config)."""
        return cls(
            layers=cfg.num_hidden_layers, hidden=cfg.hidden_size,
            heads=cfg.num_attention_heads, kv_heads=cfg.kv_heads,
            head_dim=cfg.head_dim, ffn=cfg.ffn_dim,
            ffn_matrices=3 if cfg.hidden_act in ("swiglu", "geglu") else 2,
            vocab=cfg.vocab_size, seq=cfg.seq_length,
            experts=cfg.num_experts)


def causal_keys_per_query(seq: int) -> float:
    return (seq + 1) / 2.0


def attention_flops_per_token(s: Sizes) -> float:
    """q, k, v and output projections and causal attention, one block."""
    qkv = 2 * s.hidden * (s.heads + 2 * s.kv_heads) * s.head_dim
    out = 2 * s.heads * s.head_dim * s.hidden
    # q.k^T and p.v, each 2 x head_dim per (query, key) pair and head
    attn = 2 * 2 * s.heads * s.head_dim * causal_keys_per_query(s.seq)
    return qkv + out + attn


def head_flops_per_token(s: Sizes) -> float:
    return 2 * s.hidden * s.vocab


def forward_flops_per_token(s: Sizes) -> float:
    """The dense decoder: attention and one MLP of ``ffn x ffn_matrices`` a
    block, and the head."""
    if s.experts:
        raise ValueError(
            f"the program runs {s.experts} experts a layer and the "
            "configuration's reference family returns the dense FLOP count "
            "(one MLP a block, no router): the family's own "
            "forward_flops_per_token has to count its experts per token")
    mlp = 2 * s.hidden * s.ffn * s.ffn_matrices
    return (s.layers * (attention_flops_per_token(s) + mlp)
            + head_flops_per_token(s))


def train_from_forward(forward_flops: float) -> float:
    """Forward + backward: each forward matmul has two in the backward."""
    return 3.0 * forward_flops


def train_flops_per_token(s: Sizes) -> float:
    return train_from_forward(forward_flops_per_token(s))


def mfu_pct(tokens_per_s: float, train_flops_per_token: float, chips: int,
            peak_flops_per_s: float) -> float:
    """``train_flops_per_token``: three times the family's forward count."""
    return 100.0 * tokens_per_s * train_flops_per_token / (
        chips * peak_flops_per_s)


# ---------------------------------------------------------------------------
# the flash attention kernels (ops/pallas/flash_attention.py), per step
# ---------------------------------------------------------------------------


def flash_step_cost(s: Sizes, sequences: int, bytes_per_el: int = 2
                    ) -> Dict[str, float]:
    """Operations and HBM bytes that causal attention needs in one training
    step over ``sequences`` sequences, all layers, forward and backward.

    Forward: two matmuls (q.k^T, p.v). Backward: five (the scores again,
    dp = do.v^T, dv = p^T.do, dq = ds.k, dk = ds^T.q); the recomputed
    scores are part of the flash algorithm's minimum, since it never keeps
    them. What is NOT counted: the forward pass run a second time under
    per-layer remat, and the second recomputation that comes from splitting
    the backward into a dq kernel and a dk/dv kernel.

    Bytes: every operand read once and every result written once. Forward
    reads q, k, v and writes o and the row statistics; backward reads q, k,
    v, o, do and the statistics and writes dq, dk, dv.
    """
    pairs = sequences * s.seq * causal_keys_per_query(s.seq)  # (q, k) pairs
    matmul = 2 * s.heads * s.head_dim * pairs
    q_el = sequences * s.seq * s.heads * s.head_dim
    kv_el = sequences * s.seq * s.kv_heads * s.head_dim
    stats = sequences * s.seq * s.heads * 4          # float32 row statistics
    fwd_bytes = (2 * q_el + 2 * kv_el) * bytes_per_el + stats
    bwd_bytes = (4 * q_el + 4 * kv_el) * bytes_per_el + stats
    return {"flops": s.layers * 7 * matmul,
            "bytes": s.layers * (fwd_bytes + bwd_bytes)}


def roofline_least_s(cost: Dict[str, float], peaks: Dict[str, float],
                     chips: int = 1) -> Dict[str, Any]:
    """The least time the chips could take, and which bound holds."""
    by_flops = cost["flops"] / (chips * peaks["bf16_flops_per_s"])
    by_bytes = cost["bytes"] / (chips * peaks["hbm_bytes_per_s"])
    return {"least_s": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}
