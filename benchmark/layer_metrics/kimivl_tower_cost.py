"""Operations and bytes of the attention cores of ``kimi-vl-a3b-ep8``'s
tower of image patches, for ``kimivl_tower_core_roofline``.

The cost function gets ``flops.Sizes`` and the sequences a step; the tower's
blocks are the entries of ``Sizes.attention`` that state positions of their
own (the family's ``attention_blocks``: 16 heads of 72, the patches of one
sequence, the pairs its mask leaves: a patch meets its own image both ways),
so the count is ``flops.flash_step_cost`` over those entries alone: seven
matmuls over three passes at the PUBLISHED head width over the pairs the
mask leaves, and q, k, v, o once a pass over the block's own positions.
What the implementation adds (lanes a head is padded to, tiles that span
two images) is its cost, not the model's, and is not counted.
"""

from dataclasses import replace

from benchmark import flops


def kimivl_tower_step_cost(sizes, sequences, bytes_per_el=2):
    tower = tuple(a for a in sizes.attention_blocks() if a.positions)
    return flops.flash_step_cost(replace(sizes, attention=tower), sequences,
                                 bytes_per_el)
