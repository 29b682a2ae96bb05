"""Operations and bytes of the state-space recurrence of a Mamba-2 block
(the part between its two projections: the depthwise convolution and the
chunked scan), for ``ssd_roofline``: one count for every cell whose
configuration states which of its keys are the recurrence's sizes.

The roofline reader hands a cost function ``flops.Sizes``, the sequences a
step and, as this one names it, the cell's configuration file as read. What
``Sizes`` does not hold is in the configuration's file under the published
keys, which differ from one model to the next, so the file says which key
is which in ``reference.ssd``::

    "ssd": {"heads": "mamba_num_heads", "head_dim": "mamba_head_dim",
            "state": "ssm_state_size", "chunk": "chunk_size",
            "groups": "n_groups",
            "blocks": "hybrid_override_pattern", "block_word": "M"}

``blocks`` names the per-block description (a list of words or a string of
letters) and ``block_word`` the entry of it that is a Mamba-2 block.
``groups`` absent: B and C are shared by all heads. A configuration without
the group has no such layer, and nothing is counted.
"""


def ssd_step_cost(sizes, sequences, config=None, bytes_per_el=2):
    """What one training step over ``sequences`` sequences needs of the
    recurrence, every Mamba-2 block.

    Operations: the CHUNKED form's matmuls as the program runs them, a
    token, head width P, state N, chunk Q, heads H, groups G of B and C:
    ``C B^T`` inside a chunk (2 Q N, ONCE A GROUP: the heads of a group
    share it), ``(C B^T * L) X`` (2 Q P a head: the whole Q x Q tile, the
    masked half too, since the tile is what the MXU is given), the chunk's
    state ``X^T B`` and the entering state's read-out ``C S`` (2 P N a head
    each); forward, and twice that backward. A forward run again under
    remat is not counted. This is MORE than the recurrence itself needs
    (``4 P N`` a head and token, which is what ``mfu_pct`` counts): the
    share is of the form that is run.

    Bytes, a block and pass: x, z and y (``H P`` wide) and B and C (``G N``
    wide each) in bf16 and dt (``H`` wide) in float32, each once; three
    passes (forward, and the backward's two products a matmul). The decay
    matrices, the masks and the carried states are the implementation's:
    the kernels keep them on the chip."""
    keys = ((config or {}).get("reference") or {}).get("ssd")
    if not keys:
        return None
    H, P, N, Q = (config[keys[k]]
                  for k in ("heads", "head_dim", "state", "chunk"))
    G = config[keys["groups"]] if "groups" in keys else 1
    blocks = sum(entry == keys["block_word"]
                 for entry in config[keys["blocks"]])
    tokens = sequences * sizes.seq
    forward_flops = G * 2 * Q * N + H * (2 * Q * P + 4 * P * N)
    one_pass = (3 * H * P + 2 * G * N) * bytes_per_el + 4 * H
    return {"flops": blocks * tokens * 3 * forward_flops,
            "bytes": blocks * tokens * 3 * one_pass}
