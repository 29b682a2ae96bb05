"""Operations and bytes of the banded attention cores (a stack's
``sliding_attention`` blocks), for ``window_roofline``.

The cost function gets ``flops.Sizes`` and the sequences a step; the blocks
that hold a window, their window and their own heads are the entries of
``Sizes.attention`` (the family's ``attention_blocks``), so the count is
``flops.flash_step_cost`` over those entries alone: the band's (query, key)
pairs ``causal_pairs(seq, window)`` a head, seven matmuls over three passes,
and q, k, v, o once a pass, summed over the step's sequences (the roofline
divides by the cell's chips). A stack without a banded block counts nothing,
and the reader says nothing.
"""

from dataclasses import replace

from benchmark import flops


def window_step_cost(sizes, sequences, bytes_per_el=2):
    banded = tuple(a for a in sizes.attention_blocks() if a.window)
    return flops.flash_step_cost(replace(sizes, attention=banded), sequences,
                                 bytes_per_el)
