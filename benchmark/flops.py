"""Operations and bytes, from shapes: the model step and the flash kernel.

These are the operations the ALGORITHM requires, which is what a
utilisation or a roofline share is measured against:

* matmuls only (2 x m x n x k each); norms, activations, softmax and the
  optimizer update are not counted;
* forward + backward = 3 x forward (each forward matmul has two in the
  backward pass);
* attention is counted CAUSAL: a query at position i meets i + 1 keys, so
  (S + 1) / 2 on average and not S;
* recomputation (per-layer remat, the flash backward's recomputed scores)
  is not counted: it is work the implementation chose, not work the model
  needs.

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

    @classmethod
    def of(cls, cfg: Any) -> "Sizes":
        """From the program's resolved ``ModelArgs`` (its public config)."""
        return cls(
            layers=cfg.num_hidden_layers, hidden=cfg.hidden_size,
            heads=cfg.num_attention_heads, kv_heads=cfg.kv_heads,
            head_dim=cfg.head_dim, ffn=cfg.ffn_dim,
            ffn_matrices=3 if cfg.hidden_act in ("swiglu", "geglu") else 2,
            vocab=cfg.vocab_size, seq=cfg.seq_length)


def causal_keys_per_query(seq: int) -> float:
    return (seq + 1) / 2.0


def forward_flops_per_token(s: Sizes) -> float:
    qkv = 2 * s.hidden * (s.heads + 2 * s.kv_heads) * s.head_dim
    out = 2 * s.heads * s.head_dim * s.hidden
    mlp = 2 * s.hidden * s.ffn * s.ffn_matrices
    # q.k^T and p.v, each 2 x head_dim per (query, key) pair and head
    attn = 2 * 2 * s.heads * s.head_dim * causal_keys_per_query(s.seq)
    head = 2 * s.hidden * s.vocab
    return s.layers * (qkv + out + mlp + attn) + head


def train_flops_per_token(s: Sizes) -> float:
    return 3.0 * forward_flops_per_token(s)


def mfu_pct(tokens_per_s: float, s: Sizes, chips: int,
            peak_flops_per_s: float) -> float:
    return 100.0 * tokens_per_s * train_flops_per_token(s) / (
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
