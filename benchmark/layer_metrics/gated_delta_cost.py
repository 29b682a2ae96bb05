"""Operations and bytes of the delta-rule recurrence of a Gated DeltaNet
block (the part between its projections: the three depthwise convolutions,
the gates and the chunked scan), for ``gated_delta_roofline``.

The cost function gets ``flops.Sizes``, the sequences a step and the cell's
configuration file as read (``readers.cost_of``): heads, the two head
widths, the chunk and which blocks run the mixer are its keys, so one
function serves every cell whose model has such blocks.
"""


def gated_delta_step_cost(sizes, sequences, config, bytes_per_el=2):
    """What one training step over ``sequences`` sequences needs of the
    recurrence, every ``linear_attention`` block; ``None`` for a
    configuration without one.

    Operations: the CHUNKED form's matmuls, a token and head, keys ``dk``
    wide under values of ``dv``, chunk ``C``: the two pair matrices ``K
    K^T`` and ``Q K^T`` (``2 C dk`` each: the whole ``C x C`` tile, the
    masked half too, since the tile is what the MXU is given), ``W = T (K *
    exp(G))`` (``2 C dk``), ``U = T V`` and ``A_qk V'`` (``2 C dv`` each),
    and the three products with the ``dk x dv`` state (``W S``, ``(Q *
    exp(G)) S`` and the chunk's update ``K^T V'``: ``2 dk dv`` each);
    forward, and twice that backward. The triangular inverse (float32, a
    few ``C^3`` a chunk) and the forward run a second time under per-layer
    remat are not counted. This is MORE than the recurrence itself needs
    (``6 dk dv`` a head and token, which is what ``mfu_pct`` counts): the
    share is of the form that is run.

    Bytes, a block and pass: q and k (``heads x dk`` wide), v and o
    (``heads x dv``) in bf16, the log decay ``g`` and ``beta`` (``heads``
    each) in float32, each once; three passes (forward, and the backward's
    two products a matmul). The decays between positions, the inverse and
    the carried states are the implementation's: a kernel would keep them
    on the chip."""
    blocks = sum(kind == "linear_attention"
                 for kind in config.get("layer_types") or ())
    if not blocks:
        return None
    heads, dk, dv = (config["linear_num_value_heads"],
                     config["linear_key_head_dim"],
                     config["linear_value_head_dim"])
    C = config["linear_chunk_size"]
    tokens = sequences * sizes.seq
    forward_flops = heads * (2 * C * (3 * dk + 2 * dv) + 3 * 2 * dk * dv)
    one_pass = heads * (2 * (dk + dv) * bytes_per_el + 2 * 4)
    return {"flops": blocks * tokens * 3 * forward_flops,
            "bytes": blocks * tokens * 3 * one_pass}
