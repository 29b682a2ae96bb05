"""Operations and bytes of the experts' grouped matmuls of
``mellum2-12b-a2.5b-p1`` over the four chips of its ``ep`` group, for
``mellum_experts_roofline``.

The roofline reader hands a cost function ``flops.Sizes`` and the sequences
a step, and divides the least time by the cell's chips; what ``Sizes`` does
not hold (experts a token, how many experts, their width, the blocks) is
read from ``benchmark/configs/mellum2-12b-a2.5b-p1.json``, the one
configuration whose cell the metric lists. That cell runs its sequences in
ONE microbatch, a sequence a chip.
"""

import json
import os

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "mellum2-12b-a2.5b-p1.json")


def mellum_experts_step_cost(sizes, sequences, bytes_per_el=2):
    """What one training step over ``sequences`` sequences needs of the
    experts' matmuls over all the chips of the group, every block.

    Rows: every route of the group's tokens, ``sequences x positions x
    num_experts_per_tok`` a block, which is the EXPECTED ``1 / ep`` of them
    on each chip's sixteen experts; the rows of a chip's static buffer that
    belong to no group (the short buffer is twice the expected share) are
    the implementation's cost, not the model's.

    Operations: each row goes through three ``hidden x
    moe_intermediate_size`` matrices (gate, up, down), forward, and twice
    that backward. The forward run a second time under per-layer remat is
    not counted.

    Bytes, a block, all in bf16: a pass reads every expert's matrices once
    (each chip its own, once a microbatch: the step has one) and each
    grouped matmul's rows in and writes its rows out (gate and up as one
    matmul of ``2 x width`` columns, then down); three passes."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    width, blocks = cfg["moe_intermediate_size"], cfg["num_hidden_layers"]
    rows = sequences * sizes.seq * blocks * cfg["num_experts_per_tok"]
    forward_flops = rows * 3 * 2 * sizes.hidden * width
    matrices = (blocks * cfg["num_experts"] * 3 * sizes.hidden * width
                * bytes_per_el)
    row_bytes = rows * (sizes.hidden + 2 * width + width
                        + sizes.hidden) * bytes_per_el
    return {"flops": 3 * forward_flops, "bytes": 3 * (matrices + row_bytes)}
