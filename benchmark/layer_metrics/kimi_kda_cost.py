"""Operations and bytes of the delta-rule recurrence of
``kimi-linear-48b-a3b-ep32`` (the part of a Kimi Delta Attention block
between its projections: the three depthwise convolutions, the gates and the
chunked scan), for ``kimi_kda_roofline``.

The cost function gets ``flops.Sizes`` and the sequences a step; what
``Sizes`` does not hold (heads, head width, chunk, which blocks run the
mixer) is read from ``benchmark/configs/kimi-linear-48b-a3b-ep32.json``,
the one configuration whose cell the metric lists.
"""

import json
import os

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "kimi-linear-48b-a3b-ep32.json")


def kimi_kda_step_cost(sizes, sequences, bytes_per_el=2):
    """What one training step over ``sequences`` sequences needs of the
    recurrence, every KDA block.

    Operations: the CHUNKED form's matmuls, a token and head, width ``d``
    (keys and values alike), chunk ``C``: the two pair matrices ``K K^T``
    and ``Q K^T`` under their decays (``2 C d`` each: the whole ``C x C``
    tile, the masked half too, since the tile is what the MXU is given),
    ``W = T (K * exp(G))`` and ``U = T V`` (``2 C d`` each), ``A_qk V'``
    (``2 C d``), and the three products with the ``d x d`` state (``W S``,
    ``(Q * exp(G)) S`` and the chunk's update ``K^T V'``: ``2 d d`` each);
    forward, and twice that backward. The triangular inverse (float32, a
    few ``C^3`` a chunk) and the forward run a second time under per-layer
    remat are not counted. This is MORE than the recurrence itself needs
    (``6 d d`` a head and token, which is what ``mfu_pct`` counts): the
    share is of the form that is run.

    Bytes, a block and pass: q, k, v and o (``heads x d`` wide) in bf16 and
    the log decay ``g`` (``heads x d``) in float32, each once, and ``beta``
    (``heads``, float32); three passes (forward, and the backward's two
    products a matmul). The decays between positions, the inverse and the
    carried states are the implementation's: a kernel would keep them on
    the chip."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    lin = cfg["linear_attn_config"]
    heads, d, C = lin["num_heads"], lin["head_dim"], cfg["kda_chunk_size"]
    blocks = len(lin["kda_layers"])
    tokens = sequences * sizes.seq
    forward_flops = heads * (5 * 2 * C * d + 3 * 2 * d * d)
    one_pass = heads * d * (4 * bytes_per_el + 4) + 4 * heads
    return {"flops": blocks * tokens * 3 * forward_flops,
            "bytes": blocks * tokens * 3 * one_pass}
