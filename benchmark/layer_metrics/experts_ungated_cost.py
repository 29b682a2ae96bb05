"""Operations and bytes of the routed experts' grouped matmuls where an
expert is UNGATED (two matrices, up and down: Nemotron-H's squared-ReLU
experts), for ``experts_ungated_roofline``. The rows, the passes and the
key map (``reference.experts`` of the configuration's file) are
``experts_cost.py``'s; a configuration states ``"matrices": 2`` in that group
to say its experts are of this kind, and any other is not counted here
(its count is ``experts_cost.py``'s three matrices).
"""


def experts_ungated_step_cost(sizes, sequences, config=None,
                              microbatches=None, bytes_per_el=2):
    """What one training step over ``sequences`` sequences in
    ``microbatches`` microbatches needs of the routed experts' matmuls, every
    block that has experts.

    Rows: the EXPECTED share of the routes, ``positions x per_token x held
    / routed`` a sequence and block. A shared expert is a dense MLP, no
    grouped matmul, and is not counted.

    Operations: each row goes through two ``hidden x width`` matrices (up,
    down), forward, and twice that backward. The forward run a second time
    under per-layer remat is not counted.

    Bytes, all in bf16: a pass reads every HELD expert matrix once a
    microbatch and each grouped matmul's rows in and writes its rows out
    (up: ``hidden`` in, ``width`` out; down: ``width`` in, ``hidden`` out);
    three passes (forward, gradient to the rows, gradient to the
    weights)."""
    keys = ((config or {}).get("reference") or {}).get("experts")
    if not keys or keys.get("matrices") != 2:
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
    matrices = ((stack + further) * held * 2 * sizes.hidden * width
                * bytes_per_el)
    row_bytes = rows * 2 * (sizes.hidden + width) * bytes_per_el
    return {"flops": 3 * rows * 2 * 2 * sizes.hidden * width,
            "bytes": 3 * (microbatches * matrices + row_bytes)}
