"""What the kernel files of this package share: the lane width of the chip's
tiles, and the one way a kernel is put on a mesh.

A Pallas kernel is a custom call that XLA cannot partition, so on a mesh it
runs under ``shard_map``: the batch sharded over the layer's dp axes, one
named dimension over the axes the layer cuts it by, everything else local.
``shard_map`` is imported here for the whole program (``jax.shard_map``
when the pinned spelling goes: this line, and ``check_rep`` below).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

# lanes of a vector register: the last dimension of a tile
LANES = 128


def batch_spec(rank: int, dp_axes: Sequence[str] = (),
               cut: Optional[Tuple[int, Sequence[str]]] = None) -> P:
    """The layout of an operand of ``rank`` dimensions under
    :func:`on_shards`: dimension 0, the batch, over ``dp_axes``, dimension
    ``cut[0]`` over the axes ``cut[1]``, everything else local."""
    dims = [dp_axes or None] + [None] * (rank - 1)
    if cut is not None:
        dims[cut[0]] = cut[1] or None
    return P(*dims)


def on_shards(fn: Callable, mesh, in_specs, out_specs) -> Callable:
    """``fn`` on every device's own shard of its operands; nothing is
    replicated for the check to find (``check_rep=False``: the kernels'
    custom calls have no replication rule)."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_rep=False)
