"""Operations and bytes of the held experts' grouped matmuls of
``xing4.0-29b-a4b-ep8``, for ``xing_experts_roofline``.

The roofline reader hands a cost function ``flops.Sizes`` and the sequences
a step; what ``Sizes`` does not hold (experts a token, the router's width,
the experts held, their width, which blocks have experts) is read from
``benchmark/configs/xing4.0-29b-a4b-ep8.json``, the one configuration whose
cell the metric lists. That cell runs one sequence a microbatch, so the
microbatches a step are its sequences.
"""

import json
import os

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "xing4.0-29b-a4b-ep8.json")


def xing_experts_step_cost(sizes, sequences, bytes_per_el=2):
    """What one training step over ``sequences`` sequences needs of the
    held experts' matmuls, every block that has experts: those of the stack
    past the leading dense ones and the further prediction depth's, which
    sees ``S - 1`` positions a sequence.

    Rows: the EXPECTED share of the routes, ``positions x
    num_experts_per_tok x held / routed`` a microbatch and block (what a
    balanced router sends the experts held here); the static buffer's
    further rows belong to no group and are the implementation's cost, not
    the model's. The shared expert is a dense SwiGLU, no grouped matmul,
    and is not counted.

    Operations: each row goes through three ``hidden x
    moe_intermediate_size`` matrices (gate, up, down), forward, and twice
    that backward (the gradient to the rows and the gradient to the
    weights). The forward run a second time under per-layer remat is not
    counted.

    Bytes, a microbatch and block, all in bf16: a pass reads every HELD
    expert matrix once and each grouped matmul's rows in and writes its rows
    out (gate and up as one matmul of ``2 x width`` columns, then down);
    three passes (forward, gradient to the rows, gradient to the weights)."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    held, routed = cfg["n_routed_experts"], cfg["num_routed_experts"]
    width = cfg["moe_intermediate_size"]
    share = cfg["num_experts_per_tok"] * held / routed
    positions = (sizes.seq * (cfg["num_hidden_layers"]
                              - cfg["first_k_dense_replace"])
                 + (sizes.seq - 1) * cfg["num_nextn_predict_layers"])
    rows = positions * share            # over every block that has experts
    blocks = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
              + cfg["num_nextn_predict_layers"])
    forward_flops = rows * 3 * 2 * sizes.hidden * width
    matrices = blocks * held * 3 * sizes.hidden * width * bytes_per_el
    row_bytes = rows * (sizes.hidden + 2 * width + width
                        + sizes.hidden) * bytes_per_el
    one_pass = matrices + row_bytes
    return {"flops": sequences * 3 * forward_flops,
            "bytes": sequences * 3 * one_pass}
