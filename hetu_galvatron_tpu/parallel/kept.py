"""Which blocks hold their forward's values, and which make them again.

A layer's ``checkpoint`` bit says the block MAY be recomputed so that the
step fits. The step program (parallel/spmd.py) counts, before anything is
compiled, what each such block's backward would hold beyond its input and
its kernels' ``KEPT`` names if it were not recomputed, estimates the step's
static bytes with every flag as the plan gave it, and keeps as many blocks
whole as the device's memory leaves (:func:`choose`). Everything here reads
traces of abstract values: no device runs, nothing is compiled.

The count is JAX's, taken before XLA fuses: the residuals of ``jax.vjp`` of
the block. XLA holds fewer (a chain of elementwise values is held once and
made again inside the backward's fusions), so a value that cheap
elementwise operations derive from values already held is not counted
(:data:`DERIVED`); what that still overstates by is in PERF.md.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.models import modules as M

# the share of the device's ``bytes_limit`` the step's static bytes may
# reach once blocks are kept whole; the rest is the allocator's own, the
# batches held ahead and what the count cannot see
FILL = 0.92

# the compiled step's own code, which XLA counts among its static bytes
CODE = 256 * 2 ** 20

# operations XLA:TPU duplicates into the fusion that reads their result
# rather than hold it: a residual one of these derives from values that are
# held (or derived) anyway costs the backward no memory. Transcendentals
# and divisions are not here: XLA counts them expensive and keeps their
# result.
DERIVED = frozenset({
    "add", "sub", "mul", "neg", "max", "min", "abs", "sign", "square",
    "integer_pow", "convert_element_type", "select_n", "broadcast_in_dim",
    "reshape", "squeeze", "transpose", "copy", "reduce_precision", "name",
    "gt", "ge", "lt", "le", "eq", "ne", "and", "or", "not", "iota"})


@dataclass(frozen=True)
class BlockCount:
    """What one block's trace says, bytes a device.

    ``held_bytes``: what the backward holds if the block is NOT recomputed,
    less what it holds if it is. ``forward_flops``: the matmul operations
    of the pass a recomputed block runs again (orders blocks; no time).
    ``input_bytes``: what a recomputed block holds: its input and the
    ``KEPT`` names of its forward kernels. ``whole_bytes``: all of it, the
    working set of the one block whose backward is running."""

    held_bytes: int
    forward_flops: int
    input_bytes: int
    whole_bytes: int


# what the allocator of an attached chip of the kind reports as its
# ``bytes_limit`` (the kind's HBM less what the runtime holds back; the
# lesser of the readings of one chip and of a host of four, PR 62's chip
# runs). A device that is described and not attached (``jax.experimental.
# topologies``: the tools that compile a step ahead of time) states its kind
# and nothing of its memory, and the step compiled for it is to be the step
# an attached chip of the kind runs.
DESCRIBED_LIMIT = {"TPU v5 lite": 16909334528}


def bytes_limit(devices: Sequence[Any]) -> Optional[int]:
    """The least ``bytes_limit`` the devices' allocators report; for a
    device that is described and not attached, what its kind's does
    (:data:`DESCRIBED_LIMIT`); None where one reports none (the CPU)."""
    limits = []
    for d in devices:
        try:
            limit = (d.memory_stats() or {}).get("bytes_limit")
        except jax.errors.JaxRuntimeError:
            limit = DESCRIBED_LIMIT.get(d.device_kind)
        if not limit:
            return None
        limits.append(int(limit))
    return min(limits) if limits else None


def device_bytes(tree: Any) -> int:
    """Bytes one device holds of a tree of arrays (or of shapes that carry
    a sharding): each leaf's shard."""
    total = 0
    for a in jax.tree.leaves(tree):
        shape = a.shape
        sharding = getattr(a, "sharding", None)
        if sharding is not None:
            shape = sharding.shard_shape(shape)
        total += math.prod(shape) * jnp.dtype(a.dtype).itemsize
    return total


def _aval_bytes(v) -> int:
    return math.prod(v.aval.shape) * jnp.dtype(v.aval.dtype).itemsize


def _sub_jaxprs(params: Dict[str, Any]):
    for v in params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(j, "eqns"):
                yield j
            elif hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                yield j.jaxpr


def matmul_flops(jaxpr) -> int:
    """Operations of the ``dot_general`` / ``ragged_dot`` equations of a
    jaxpr and of what it calls (a scan's body times its length, a
    ``shard_map``'s times its mesh); a Pallas kernel's body is not read:
    the kernels' results are kept under remat."""
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2 * math.prod(eqn.outvars[0].aval.shape) * math.prod(
                lhs[d] for d in lc)
        elif name == "ragged_dot":
            m, k = eqn.invars[0].aval.shape[-2:]
            total += 2 * m * k * eqn.invars[1].aval.shape[-1]
        elif name == "pallas_call":
            continue
        else:
            times = 1
            if name == "scan":
                times = int(eqn.params.get("length", 1))
            elif name == "shard_map":
                times = int(eqn.params["mesh"].size)
            for sub in _sub_jaxprs(eqn.params):
                total += times * matmul_flops(sub)
    return total


# calls whose body the count reads through: the value a jitted
# ``jax.nn.silu`` returns is made by the operations inside it
_READ_THROUGH = frozenset({"jit", "pjit", "closed_call", "custom_jvp_call"})


def _producers(jaxpr, made: Dict[Any, Tuple[str, Tuple]], free: set) -> None:
    """``made[v] = (primitive, inputs)`` for every variable of ``jaxpr``,
    the bodies of :data:`_READ_THROUGH` calls read as if written in place
    (their inputs and outputs ``alias`` the caller's)."""
    free.update(jaxpr.constvars)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        inner = None
        if name in _READ_THROUGH:
            inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            inner = getattr(inner, "jaxpr", inner)
        if inner is None or len(inner.invars) != len(eqn.invars):
            for v in eqn.outvars:
                made[v] = (name, tuple(eqn.invars))
            continue
        for iv, ov in zip(inner.invars, eqn.invars):
            made[iv] = ("alias", (ov,))
        _producers(inner, made, free)
        for ov, iv in zip(eqn.outvars, inner.outvars):
            made[ov] = ("alias", (iv,))


def residual_bytes(jaxpr, n_out: int, n_params: int) -> int:
    """Bytes the residuals among a ``jax.vjp`` trace's outputs (those after
    the ``n_out`` primal ones) cost the backward: each variable once; the
    function's first ``n_params`` inputs (the block's own parameters,
    arguments of the step), closed-over constants and literals cost
    nothing; and a value that :data:`DERIVED` operations make of others
    costs what holding the cheapest cut of that chain costs (a float32
    copy of a bfloat16 value costs the bfloat16 value, once)."""
    free = set(jaxpr.invars[:n_params])
    made: Dict[Any, Tuple[str, Tuple]] = {}
    _producers(jaxpr, made, free)
    held: Dict[Any, int] = {}

    def cut(v, seen: Dict[Any, frozenset]) -> frozenset:
        """The cheapest set of values to hold so that ``v`` can be read."""
        if not hasattr(v, "count") or v in free or v in held:
            return frozenset()
        if v not in seen:
            name, inputs = made.get(v, (None, ()))
            if name != "alias" and name not in DERIVED:
                seen[v] = frozenset((v,))
            else:
                below = frozenset().union(*(cut(u, seen) for u in inputs))
                seen[v] = (below if name == "alias" or sum(
                    map(_aval_bytes, below)) <= _aval_bytes(v)
                    else frozenset((v,)))
        return seen[v]

    for v in jaxpr.outvars[n_out:]:
        for u in cut(v, {}):
            held[u] = _aval_bytes(u)
    return sum(held.values())


def trace_vjp(fn: Callable, args: Tuple) -> Tuple[Any, int, Any]:
    """(jaxpr of ``jax.vjp(fn, *args)``, how many of its outputs are the
    primal's, the primal output's shapes)."""
    closed, shape = jax.make_jaxpr(
        lambda *a: jax.vjp(fn, *a), return_shape=True)(*args)
    out = shape[0]
    return closed.jaxpr, len(jax.tree.leaves(out)), out


def count_block(fn: Callable, cfg, args: Tuple, shards: int = 1
                ) -> Tuple[BlockCount, Any]:
    """Trace ``fn(*args)`` (``args[0]`` the block's parameters) as it
    stands and under :func:`modules.remat` and read the two counts off the
    traces; ``shards``: the devices the stream is sharded over (the traces
    are of the global shapes). Returns (the count, the output's shapes)."""
    n_params = len(jax.tree.leaves(args[0]))
    # (jitted: the second trace reads the first one's jaxpr and does not
    # run the block's Python again, a kernel's tracing included)
    fn = jax.jit(fn)
    plain, n_out, out = trace_vjp(fn, args)
    again, _, _ = trace_vjp(M.remat(fn, cfg), args)
    whole = residual_bytes(plain, n_out, n_params)
    kept = residual_bytes(again, n_out, n_params)
    return BlockCount(
        held_bytes=max(whole - kept, 0) // shards,
        forward_flops=matmul_flops(plain),
        input_bytes=kept // shards, whole_bytes=whole // shards), out


def plan_peak(args: int, accumulator: int, grads: int,
              blocks: Sequence[Tuple[int, int, int]], outer: int) -> int:
    """The step's static bytes a device, estimated for the flags the
    blocks were counted under. ``args``: parameters, optimizer state and
    batch; ``accumulator``: the gradient's float32 accumulator of a step of
    several microbatches (0 for one); ``grads``: one gradient of every
    parameter; ``blocks``: in the order the forward runs them, ``(what the
    block holds until its backward ran, its parameters' gradient, its
    backward's working set)``; ``outer``: what the embedding, the head and
    the loss hold. The most of: the loss's backward (every block holds,
    the logits' cotangent is as large again as what the loss holds), each
    block's backward (the blocks before it hold, it works, the gradients
    of everything after it are made) and the update (every gradient).

    An estimate, where XLA's own count of the plan's step would be exact:
    that count exists once the plan's step is compiled, a step that a job
    which keeps blocks never runs (one to two minutes of a first run on the
    chip), and a budget read off what the compile cache happens to hold
    would give one job two programs, its first run's and its later ones'.
    The estimate reads from 12 % under to 9.5 % over XLA's count in the
    benchmark's cells (PERF.md section 6, PR 62): over keeps fewer blocks,
    which is the safe side, and under is caught, since XLA's count of the
    CHOSEN step is read before that step runs (``KeptStep._checked``)."""
    held = sum(b[0] for b in blocks)
    made = grads - sum(b[1] for b in blocks)   # the head's and the rest's
    peak = max(held + 2 * outer, grads)
    for res, grad, working in reversed(blocks):
        made += grad
        peak = max(peak, held + made + working)
        held -= res
    return CODE + args + accumulator + peak


def choose(blocks: Sequence[Tuple[int, int]], budget: int) -> List[bool]:
    """Which blocks hold their values: ``blocks[i]`` is ``(held_bytes,
    forward_flops)``; blocks are taken in order of ``forward_flops /
    held_bytes`` (what keeping saves over what it costs), of two equal the
    later block first (its values are held the shortest), each where it
    still fits: the kept blocks' ``held_bytes`` never pass ``budget``."""
    def worth(i):
        held, flops = blocks[i]
        return (flops / held if held else math.inf, i)

    keep = [False] * len(blocks)
    left = budget
    for i in sorted(range(len(blocks)), key=worth, reverse=True):
        if blocks[i][0] <= left:
            keep[i] = True
            left -= blocks[i][0]
    return keep


class Probe:
    """A block's remat flag while the step program counts (it is callable:
    :func:`modules.recomputed` hands it the block's function). The function
    it returns traces the block twice (:func:`count_block`) where no block
    of its ``group`` at the same shapes was traced before, notes the count
    in ``counts`` (a second call of one flag: the further prediction depth's
    block, of the last block's kind and under its flag) and answers zeros
    of the block's output shapes, so that the loss around it traces on
    without the block."""

    def __init__(self, group: Any, shards: int, cache: Dict[Any, Any]):
        self.group, self.shards, self.cache = group, shards, cache
        self.counts: List[BlockCount] = []

    def __call__(self, fn: Callable, cfg) -> Callable:
        def counted(*args):
            key = (self.group, jax.tree.structure(args),
                   tuple((a.shape, str(a.dtype))
                         for a in jax.tree.leaves(args)))
            if key not in self.cache:
                self.cache[key] = count_block(fn, cfg, args, self.shards)
            count, out = self.cache[key]
            self.counts.append(count)
            return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), out)

        return counted
