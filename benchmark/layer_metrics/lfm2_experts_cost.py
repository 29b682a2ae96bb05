"""Operations and bytes of the held experts' grouped matmuls of
``lfm2-24b-a2b-ep8``, for ``lfm2_experts_roofline``.

The roofline reader hands a cost function ``flops.Sizes`` and the sequences
a step; what ``Sizes`` does not hold (experts a token, the router's width,
the experts held, their width, which blocks have experts) is read from
``benchmark/configs/lfm2-24b-a2b-ep8.json``, the one configuration whose
cell the metric lists. That cell runs one sequence a microbatch, so the
microbatches a step are its sequences.
"""

import json
import os

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "lfm2-24b-a2b-ep8.json")


def lfm2_experts_step_cost(sizes, sequences, bytes_per_el=2):
    """What one training step over ``sequences`` sequences needs of the
    held experts' matmuls, every block that has experts.

    Rows: the EXPECTED share of the routes, ``S x num_experts_per_tok x
    held / routed`` a microbatch (what a balanced router sends the experts
    held here); the static buffer's further rows belong to no group and are
    the implementation's cost, not the model's.

    Operations: each row goes through three ``hidden x
    moe_intermediate_size`` matrices (w1, w3, w2), forward, and twice that
    backward (the gradient to the rows and the gradient to the weights).
    The forward run a second time under per-layer remat is not counted.

    Bytes, a microbatch and block, all in bf16: a pass reads every HELD
    expert matrix once and each grouped matmul's rows in and writes its rows
    out (w1 and w3 as one matmul of ``2 x width`` columns, then w2); three
    passes (forward, gradient to the rows, gradient to the weights)."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    held, routed = cfg["num_experts"], cfg["num_routed_experts"]
    width = cfg["moe_intermediate_size"]
    blocks = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    rows = sizes.seq * cfg["num_experts_per_tok"] * held / routed
    forward_flops = rows * 3 * 2 * sizes.hidden * width
    matrices = held * 3 * sizes.hidden * width * bytes_per_el
    row_bytes = rows * (sizes.hidden + 2 * width + width
                        + sizes.hidden) * bytes_per_el
    one_pass = matrices + row_bytes
    return {"flops": blocks * sequences * 3 * forward_flops,
            "bytes": blocks * sequences * 3 * one_pass}
