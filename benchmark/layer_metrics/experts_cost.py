"""Operations and bytes of the routed experts' grouped matmuls, for
``experts_roofline``: one count for every cell whose configuration has
routed experts.

The roofline reader hands a cost function ``flops.Sizes``, the sequences a
step and, as this one names them, the cell's configuration file as read and
the microbatches a step. What ``Sizes`` does not hold (the experts a
deployment holds of those the router sees, the experts a token, their
width, which blocks have experts) is in the configuration's file under the
published keys, which differ from one model to the next, so the file says
which key is which in ``reference.experts``::

    "experts": {"held": "num_experts", "routed": "num_routed_experts",
                "per_token": "num_experts_per_tok",
                "width": "moe_intermediate_size",
                "dense_blocks": "first_k_dense_replace",
                "further_depths": "num_nextn_predict_layers"}

``held``, ``per_token`` and ``width`` are required. ``routed`` absent: the
deployment holds every expert. ``dense_blocks`` (a number, or a list of the
blocks' indices) absent: every block of the stack has experts.
``further_depths`` names the count of prediction depths beside the stack,
each a block with experts that sees ``seq - 1`` positions. A configuration
without the group has no such layer, and nothing is counted.
"""


def experts_step_cost(sizes, sequences, config=None, microbatches=None,
                      bytes_per_el=2):
    """What one training step over ``sequences`` sequences in
    ``microbatches`` microbatches needs of the routed experts' matmuls, every
    block that has experts, over all the chips of the cell.

    Rows: the EXPECTED share of the routes, ``positions x per_token x held
    / routed`` a sequence and block (what a balanced router sends the
    experts held here; every route where all are held); the static buffer's
    further rows belong to no group and are the implementation's cost, not
    the model's. A shared expert is a dense SwiGLU, no grouped matmul, and is
    not counted.

    Operations: each row goes through three ``hidden x width`` matrices
    (gate, up, down), forward, and twice that backward (the gradient to the
    rows and the gradient to the weights). The forward run a second time
    under per-layer remat is not counted, as for flash.

    Bytes, all in bf16: a pass reads every HELD expert matrix once a
    microbatch and each grouped matmul's rows in and writes its rows out
    (gate and up as one matmul of ``2 x width`` columns, then down); three
    passes (forward, gradient to the rows, gradient to the weights)."""
    keys = ((config or {}).get("reference") or {}).get("experts")
    if not keys:
        return None
    stated = lambda k, default: config[keys[k]] if k in keys else default
    held, per_token, width = (config[keys[k]]
                              for k in ("held", "per_token", "width"))
    routed, further = stated("routed", held), stated("further_depths", 0)
    dense = stated("dense_blocks", 0)
    dense = len(dense) if isinstance(dense, list) else dense
    stack = sizes.layers - dense
    positions = sizes.seq * stack + (sizes.seq - 1) * further  # a sequence
    rows = sequences * positions * per_token * held / routed
    microbatches = sequences if microbatches is None else microbatches
    matrices = ((stack + further) * held * 3 * sizes.hidden * width
                * bytes_per_el)
    row_bytes = rows * (sizes.hidden + 2 * width + width
                        + sizes.hidden) * bytes_per_el
    return {"flops": 3 * rows * 3 * 2 * sizes.hidden * width,
            "bytes": 3 * (microbatches * matrices + row_bytes)}
