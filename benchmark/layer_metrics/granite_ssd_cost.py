"""Operations and bytes of the state-space recurrence of
``granite-4.0-h-micro-p1`` (the part of a Mamba-2 block between its two
projections: the depthwise convolution and the chunked scan), for
``granite_ssd_roofline``.

The cost function gets ``flops.Sizes`` and the sequences a step; what
``Sizes`` does not hold (heads, head width, state, chunk, which blocks are
mamba blocks) is read from ``benchmark/configs/granite-4.0-h-micro-p1.json``,
the one configuration whose cell the metric lists.
"""

import json
import os

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "granite-4.0-h-micro-p1.json")


def granite_ssd_step_cost(sizes, sequences, bytes_per_el=2):
    """What one training step over ``sequences`` sequences needs of the
    recurrence, every mamba block.

    Operations: the CHUNKED form's matmuls as the program runs them, a
    token, head width P, state N, chunk Q, heads H: ``C B^T`` inside a
    chunk (2 Q N, shared by the heads), ``(C B^T * L) X`` (2 Q P a head:
    the whole Q x Q tile, the masked half too, since the tile is what the
    MXU is given), the chunk's state ``X^T B`` and the entering state's
    read-out ``C S`` (2 P N a head each); forward, and twice that backward.
    The forward run a second and a third time (per-layer remat, and the
    remat of a group of chunks inside it) is not counted. This is MORE
    than the recurrence itself needs (``4 P N`` a head and token, which is
    what ``mfu_pct`` counts): the share is of the form that is run.

    Bytes, a block and pass: x, z and y (``H P`` wide) and B and C (``N``
    wide) in bf16 and dt (``H`` wide) in float32, each once; three passes
    (forward, and the backward's two products a matmul). The decay
    matrices, the masks and the carried states are the implementation's:
    a kernel would keep them on the chip."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    N, Q = cfg["mamba_d_state"], cfg["mamba_chunk_size"]
    blocks = sum(kind == "mamba" for kind in cfg["layer_types"])
    tokens = sequences * sizes.seq
    forward_flops = 2 * Q * N + H * (2 * Q * P + 4 * P * N)
    one_pass = (3 * H * P + 2 * N) * bytes_per_el + 4 * H
    return {"flops": blocks * tokens * 3 * forward_flops,
            "bytes": blocks * tokens * 3 * one_pass}
