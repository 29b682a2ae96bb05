"""What ``modules.remat`` keeps of a forward kernel leaves a block's numbers
plain ``jax.checkpoint``'s to the last bit: loss and every gradient, flash
attention cores and the four scans (``test_remat_keeps_core.py`` has the
cases, counts the kernels and says what is kept). Interpret-mode kernels on
the CPU.

Bit-equality is asserted op by op (no outer ``jit``): each primitive then
runs as its own program on both sides, and what is compared is the
arithmetic, not which elementwise neighbours XLA:CPU chose to fuse into a
matmul in two differently shaped programs. A file of its own so that neither
runs longer than a worker's fair share (``tests/conftest.py``: a module's
cases stay on one worker)."""

import numpy as np
import pytest

import jax

from hetu_galvatron_tpu.models import modules as M

from test_remat_keeps_core import _CASES, BASE_POLICIES, SCANS, _stack

pytestmark = pytest.mark.kernels


# The numbers cost an interpret-mode compile a kernel call and side, so they
# are compared where the path differs: ``remat`` is one policy for every
# kind (its names beside the base policy), which the flash core takes
# through every base policy, segments, dropout, ``shard_map``, the rows and
# a stack of two; of each scan it is the block under ``full`` that shows
# that what its kernels named is what a second run of them gives.
_NUMBERS = ["block", "stack", "dots", "dots_no_batch", "segments", "dropout",
            "shard_map", "rows_pairs", "rows_pairs_dots_segments",
            "rows_w128_stack", *(f"{kind}_block" for kind in SCANS)]


@pytest.mark.parametrize("case", _NUMBERS)
def test_a_rematted_block_has_a_plain_ones_numbers(cpu_devices, case):
    blocks, policy, extras = _CASES[case]
    loss, params, x, cfg, _ = _stack(blocks, policy, extras, cpu_devices)
    kept = loss(lambda fn: M.remat(fn, cfg))
    plain = loss(lambda fn: jax.checkpoint(fn, policy=BASE_POLICIES[policy]))
    ours = jax.value_and_grad(kept, argnums=(0, 1))(params, x)
    theirs = jax.value_and_grad(plain, argnums=(0, 1))(params, x)
    assert np.isfinite(float(ours[0]))
    paths = jax.tree_util.tree_flatten_with_path(ours)[0]
    for (path, a), b in zip(paths, jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{case}: {jax.tree_util.keystr(path)}")
