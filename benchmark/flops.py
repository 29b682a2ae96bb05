"""Operations and bytes, from shapes: the model step and the flash kernel.

These are the operations the ALGORITHM requires, which is what a
utilisation or a roofline share is measured against. Every count is held
to these rules, the one here and the one a reference family exports
(``benchmark/reference/<family>.py::forward_flops_per_token``):

* matmuls only (2 x m x n x k each); norms, activations, softmax and the
  optimizer update are not counted;
* forward + backward = 3 x forward (each forward matmul has two in the
  backward pass);
* attention is counted CAUSAL, and only where a stack has it: a query at
  position i meets min(i + 1, window) keys, so (S + 1) / 2 on average and
  not S where no window bites, in the blocks that attend and in no other
  (``Sizes.attention``, from the family's ``attention_blocks``). A block
  whose mask is not that (a stack beside the decoder's that attends both
  ways inside an image, over positions of its own) states its positions
  and the (query, key) pairs its mask leaves, and is counted as those;
* recomputation (per-layer remat, the flash backward's recomputed scores)
  is not counted: it is work the implementation chose, not work the model
  needs.

The model's count comes from its family, because only the family knows its
layer: ``gpt2`` and ``mistral`` return the dense count below; a family with
experts adds its experts per token and its router, one with latent
attention its own projections, one whose blocks differ (a convolution here,
a window there) one ``attention_flops_per_token(sizes, entry)`` a block that
attends, from ``attention_flops_per_token`` and ``head_flops_per_token`` and
the keys of its configuration's file. The dense count refuses a program that
has experts, so that a configuration cannot name a dense family and have one
expert counted where a token uses several.

Where this differs from ``hetu_galvatron_tpu/observability/telemetry.py``
and ``models/builder.py::model_flops_per_token``: those count the S x S
attention matmuls dense (twice the causal work), so a causal model's MFU is
overstated there; and the program's peak lookup matches ``device_kind`` by
substring and returns ``None`` in silence for an unknown chip, where
``peaks.py`` raises.
"""

from __future__ import annotations

from dataclasses import dataclass, fields, replace
from typing import Any, Dict, Iterable, Mapping, Optional, Tuple


@dataclass(frozen=True)
class Attention:
    """One block that attends, as a family's ``attention_blocks`` states it.
    0 = the model's own (``Sizes``): the whole causal span, its heads, its
    key-value heads, its head width, its positions, its hidden width."""

    window: int = 0        # keys a query meets at most, itself included
    heads: int = 0
    kv_heads: int = 0
    qk_head_dim: int = 0   # what q.k^T contracts
    v_head_dim: int = 0    # what p.v produces
    # a block of a stack beside the decoder's (a tower before it), from the
    # configuration's own keys and its traffic:
    positions: int = 0     # of ONE sequence as this block sees them
    pairs: int = 0         # (query, key) pairs of one sequence its mask
    #                        leaves; 0 = causal over ``positions``, ``window``
    hidden: int = 0        # the width its four projections read and write

    @classmethod
    def of(cls, entry: Mapping[str, int]) -> "Attention":
        known = {f.name for f in fields(cls)}
        bad = {k: v for k, v in entry.items() if k not in known
               or not isinstance(v, int) or isinstance(v, bool) or v < 0}
        if bad:
            raise ValueError(
                f"attention_blocks: an entry may hold {sorted(known)} as "
                f"whole numbers from 0 on, this one holds {bad}")
        return cls(**entry)


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
    # the blocks that attend, in order; None = every one of ``layers``, the
    # whole causal span, the sizes above
    attention: Optional[Tuple[Attention, ...]] = None

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

    def with_attention(self, entries: Iterable[Mapping[str, int]],
                       beside: int = 0) -> "Sizes":
        """With a family's ``attention_blocks(config)``: one entry a block
        that attends, of the stack as it is run and of a second stack of
        ``beside`` blocks that the program runs beside it (what the
        configuration's file states and ``program.equals`` ties to the
        program; ``manifest.second_stack_depth``)."""
        blocks = tuple(Attention.of(e) for e in entries)
        if len(blocks) > self.layers + beside:
            raise ValueError(
                f"attention_blocks describes {len(blocks)} blocks that "
                f"attend and the program runs {self.layers + beside} blocks "
                "in all")
        return replace(self, attention=blocks)

    def attention_blocks(self) -> Tuple[Attention, ...]:
        return ((Attention(),) * self.layers if self.attention is None
                else self.attention)


def causal_keys_per_query(seq: int) -> float:
    return (seq + 1) / 2.0


def causal_pairs(seq: int, window: int = 0) -> float:
    """(query, key) pairs of one sequence: the sum over positions i of
    min(i + 1, window); no window, or one of the whole sequence, leaves the
    causal triangle."""
    if not 0 < window < seq:
        return seq * causal_keys_per_query(seq)
    return float(window * (window + 1) // 2 + (seq - window) * window)


def _block(s: Sizes, a: Attention) -> Tuple[int, int, int, int]:
    """heads, key-value heads, q/k width and v width of one block."""
    return (a.heads or s.heads, a.kv_heads or s.kv_heads,
            a.qk_head_dim or s.head_dim, a.v_head_dim or s.head_dim)


def _span(s: Sizes, a: Attention) -> Tuple[int, float]:
    """Positions of one sequence as the block sees them, and the (query,
    key) pairs of one sequence that its mask leaves."""
    positions = a.positions or s.seq
    return positions, a.pairs or causal_pairs(positions, a.window)


def attention_flops_per_token(s: Sizes, entry: Attention = Attention()
                              ) -> float:
    """q, k, v and output projections and attention, one block, a token of
    the step (one of ``Sizes.seq`` positions): ``entry`` is the block's line
    of ``Sizes.attention_blocks()`` where it differs from the model's sizes
    (a window, other heads or widths; positions, pairs and a hidden width of
    its own, whose projections run ``positions / seq`` times a token)."""
    heads, kv_heads, qk, v = _block(s, entry)
    hidden = entry.hidden or s.hidden
    positions, pairs = _span(s, entry)
    qkv = 2 * hidden * (heads * qk + kv_heads * qk + kv_heads * v)
    out = 2 * heads * v * hidden
    # q.k^T contracts qk and p.v produces v, 2 each per (query, key) pair
    # and head
    attn = 2 * heads * (qk + v) * (pairs / s.seq)
    return (qkv + out) * (positions / s.seq) + attn


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
    """Operations and HBM bytes that attention needs in one training step
    over ``sequences`` sequences, every block that attends
    (``Sizes.attention_blocks()``: causal over the sequence, or over the
    positions and pairs an entry states), forward and backward.

    Forward: two matmuls (q.k^T, p.v). Backward: five (the scores again,
    dp = do.v^T, dv = p^T.do, dq = ds.k, dk = ds^T.q); the recomputed
    scores are part of the flash algorithm's minimum, since it never keeps
    them. Four of the seven (q.k^T, the scores again, dq, dk) contract or
    produce the q/k width, three (p.v, dp, dv) the v width. What is NOT
    counted: the forward pass run a second time under
    per-layer remat, and the second recomputation that comes from splitting
    the backward into a dq kernel and a dk/dv kernel.

    Bytes: every operand read once and every result written once, whatever
    the mask, over the block's own positions. Forward reads q, k, v and
    writes o and the row statistics; backward reads q, k, v, o, do and the
    statistics and writes dq, dk, dv.
    """
    cost = {"flops": 0.0, "bytes": 0}
    for a in s.attention_blocks():
        heads, kv_heads, qk, v = _block(s, a)
        positions, pairs = _span(s, a)
        tokens = sequences * positions
        pairs = sequences * pairs                    # (q, k) pairs
        # q and k are qk wide, v and o are v wide
        io_el = tokens * (heads * qk + kv_heads * qk + kv_heads * v
                          + heads * v)
        stats = tokens * heads * 4                   # float32 row statistics
        cost["flops"] += 2 * heads * (4 * qk + 3 * v) * pairs
        # forward: q, k, v, o once; backward: those, and do, dq, dk, dv
        cost["bytes"] += 3 * io_el * bytes_per_el + 2 * stats
    return cost


def roofline_least_s(cost: Dict[str, float], peaks: Dict[str, float],
                     chips: int = 1) -> Dict[str, Any]:
    """The least time the chips could take, and which bound holds."""
    by_flops = cost["flops"] / (chips * peaks["bf16_flops_per_s"])
    by_bytes = cost["bytes"] / (chips * peaks["hbm_bytes_per_s"])
    return {"least_s": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}
