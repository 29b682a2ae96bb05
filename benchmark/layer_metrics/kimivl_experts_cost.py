"""Operations and bytes of the held experts' grouped matmuls of
``kimi-vl-a3b-ep8``, for ``kimivl_experts_roofline``.

The roofline reader hands a cost function ``flops.Sizes`` and the sequences
a step; what ``Sizes`` does not hold (experts a token, the router's width,
the experts held, their width, which blocks have experts) is read from
``benchmark/configs/kimi-vl-a3b-ep8.json``, the one configuration whose cell
the metric lists. That cell runs one sequence a microbatch, so the
microbatches a step are its sequences.
"""

import json
import os

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "kimi-vl-a3b-ep8.json")


def kimivl_experts_step_cost(sizes, sequences, bytes_per_el=2):
    """What one training step over ``sequences`` sequences needs of the
    held experts' matmuls, every block past the leading dense one, as
    ``kimi_experts_cost.py`` counts its own.

    Rows: the EXPECTED share of the routes, ``positions x
    num_experts_per_tok x held / routed`` a microbatch and block (4096 x 6 x
    8 / 64 = 3072; image positions are routed as text positions are); the
    static buffer's further rows belong to no group and are the
    implementation's cost, not the model's. The two shared experts are one
    dense SwiGLU, no grouped matmul, and are not counted.

    Operations: each row goes through three ``hidden x
    moe_intermediate_size`` matrices (gate, up, down), forward, and twice
    that backward. The forward run a second time under per-layer remat is
    not counted.

    Bytes, a microbatch and block, all in bf16: a pass reads every HELD
    expert matrix once and each grouped matmul's rows in and writes its rows
    out (gate and up as one matmul of ``2 x width`` columns, then down);
    three passes."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    held, routed = cfg["n_routed_experts"], cfg["num_routed_experts"]
    width = cfg["moe_intermediate_size"]
    blocks = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    rows = (sizes.seq * blocks * cfg["num_experts_per_tok"] * held
            / routed)                   # over every block that has experts
    forward_flops = rows * 3 * 2 * sizes.hidden * width
    matrices = blocks * held * 3 * sizes.hidden * width * bytes_per_el
    row_bytes = rows * (sizes.hidden + 2 * width + width
                        + sizes.hidden) * bytes_per_el
    return {"flops": sequences * 3 * forward_flops,
            "bytes": sequences * 3 * (matrices + row_bytes)}
