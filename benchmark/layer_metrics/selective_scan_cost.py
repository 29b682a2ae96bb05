"""Bytes of a Mamba-1 block's causal convolution and selective scan, for
``selective_scan_roofline``.

The recurrence has no matmul form (the decay is a number a channel AND a
state index): its work is the vector unit's, and ``peaks.py`` has no vector
peak, so no operation is counted and the least time is the memory's. What
``flops.Sizes`` does not hold (which blocks scan, the channels, the state)
is read from the cell's configuration, which the reader hands this function
as it names it: ``layer_types`` (its ``mamba1`` entries), ``mamba_expand``
x ``hidden_size`` channels, ``mamba_d_state``.
"""

SCAN_KIND = "mamba1"


def selective_scan_step_cost(sizes, sequences, config=None):
    """What one training step needs of every scanning block's convolution
    and scan, each array once a pass, bytes a position with ``C`` channels
    and a state of ``N``:

    * the scan, forward: ``u`` (bf16) and ``dt`` (float32) read, ``B`` and
      ``C`` (float32, ``N`` each) read, ``y`` (float32) written: ``10 C +
      8 N``;
    * the scan, backward: ``u``, ``dt``, ``B``, ``C`` and ``dy`` (float32)
      read, ``du`` (bf16), ``ddt``, ``dB`` and ``dC`` written: ``16 C +
      16 N``;
    * the convolution with its bias and SiLU, all bf16: forward a read and
      a write, backward two reads and a write: ``10 C``.

    The forward is not run a second time (per-layer remat keeps the scan's
    output) and a recomputed convolution is not counted, as for flash. The
    states that enter the chunks of positions, which a backward pass reads
    back, are a checkpoint whose spacing is the implementation's: its cost,
    not the model's, and not counted. The parameters (``A``, ``D``, the
    taps) are kilobytes."""
    kinds = (config or {}).get("layer_types") or ()
    blocks = sum(k == SCAN_KIND for k in kinds)
    if not blocks:
        return None
    channels = config["mamba_expand"] * config["hidden_size"]
    state = config["mamba_d_state"]
    a_position = 36 * channels + 24 * state
    return {"flops": 0,
            "bytes": blocks * sequences * sizes.seq * a_position}
