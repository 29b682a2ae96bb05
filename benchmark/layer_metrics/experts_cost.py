"""Operations and bytes of OLMoE's grouped expert matmuls, for
``experts_roofline``.

The roofline reader hands a cost function ``flops.Sizes`` and the sequences
a step, and ``Sizes`` has no experts-per-token, so this file reads it from
``benchmark/configs/olmoe-1b-7b-d1.json``: the one configuration whose cells
the metric lists. That configuration runs one sequence a microbatch (its
``assumed``), so the microbatches a step are its sequences.
"""

import json
import os

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "olmoe-1b-7b-d1.json")


def experts_step_cost(sizes, sequences, bytes_per_el=2):
    """What one training step over ``sequences`` sequences needs of the
    expert matmuls, all layers.

    Operations: every token's row goes through ``num_experts_per_tok``
    experts of three ``hidden x intermediate_size`` matrices (gate, up,
    down), forward, and twice that backward (the gradient to the rows and
    the gradient to the weights). The forward run a second time under
    per-layer remat is not counted, as for flash.

    Bytes, a microbatch and layer, all in bf16: forward reads every expert
    matrix once and each grouped matmul's rows in and writes its rows out
    (gate and up as one matmul of ``2 x intermediate_size`` columns, then
    down); the gradient to the rows does the same with the transposed
    matrices; the gradient to the weights reads both row sets of each
    matmul and writes every expert matrix once."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    per_token = cfg["num_experts_per_tok"]
    experts, width = cfg["num_experts"], cfg["intermediate_size"]
    rows = sizes.seq * per_token                   # a microbatch
    forward_flops = rows * 3 * 2 * sizes.hidden * width
    matrices = experts * 3 * sizes.hidden * width * bytes_per_el
    # rows in and out of the two grouped matmuls: H -> 2F, F -> H
    row_bytes = rows * (sizes.hidden + 2 * width + width
                        + sizes.hidden) * bytes_per_el
    one_pass = matrices + row_bytes
    return {"flops": sizes.layers * sequences * 3 * forward_flops,
            "bytes": sizes.layers * sequences * 3 * one_pass}
