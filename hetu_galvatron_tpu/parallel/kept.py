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
from typing import (
    Any,
    Callable,
    Dict,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.models import modules as M

# the share of the device's ``bytes_limit`` the step's static bytes may
# reach once blocks are kept whole; the rest is the allocator's own, the
# batches held ahead and what the count cannot see
FILL = 0.92

# what XLA counts among a step's static bytes beside its arguments and the
# values its schedule shows live at the fullest moment: the step's own code
# (0.03 to 0.45 GB), its scratch in VMEM (0.13), the padding of its
# arguments and what the packing of the temporaries loses. 0.20 to 0.40 GB
# in all in the four cells whose step runs no loop over its gradients; the
# least that leaves none of them under XLA's count (PERF.md section 6, PR 72)
CODE = 320 * 2 ** 20

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
    working set of the one block whose backward is running. ``grad_share``:
    what the gradient of its parameters takes as the backward makes it,
    over the parameters as stored; ``carries``: a rule's backward carries
    part of it through a loop (:func:`made_bytes`)."""

    held_bytes: int
    forward_flops: int
    input_bytes: int
    whole_bytes: int
    grad_share: float = 1.0
    carries: bool = False


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


def _body(eqn, through=_READ_THROUGH):
    """The jaxpr a call of ``through`` runs, where its inputs are the
    equation's one for one; else None."""
    if eqn.primitive.name not in through:
        return None
    inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
    inner = getattr(inner, "jaxpr", inner)
    if inner is None or len(inner.invars) != len(eqn.invars):
        return None
    return inner


def _producers(jaxpr, made: Dict[Any, Tuple[str, Tuple]], free: set) -> None:
    """``made[v] = (primitive, inputs)`` for every variable of ``jaxpr``,
    the bodies of :data:`_READ_THROUGH` calls read as if written in place
    (their inputs and outputs ``alias`` the caller's)."""
    free.update(jaxpr.constvars)
    for eqn in jaxpr.eqns:
        inner = _body(eqn)
        if inner is None:
            for v in eqn.outvars:
                made[v] = (eqn.primitive.name, tuple(eqn.invars))
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


# who reads a parameter is asked of an exchange's body too (its shapes are a
# shard's, so the byte count does not read through it)
_READERS_THROUGH = _READ_THROUGH | {"shard_map"}


def _readers(jaxpr, root: Dict[Any, Any], readers: Dict[Any, List]) -> None:
    """``readers[v]``: the equations that read variable ``v`` of ``jaxpr``,
    the bodies of :data:`_READERS_THROUGH` calls read as if written in
    place (``root``: a body's input -> the caller's variable)."""
    for eqn in jaxpr.eqns:
        inner = _body(eqn, _READERS_THROUGH)
        if inner is None:
            for v in eqn.invars:
                if hasattr(v, "count"):
                    readers.setdefault(root.get(v, v), []).append(eqn)
            continue
        for iv, ov in zip(inner.invars, eqn.invars):
            if hasattr(ov, "count"):
                root[iv] = root.get(ov, ov)
        _readers(inner, root, readers)


# a rule with a backward of its own (``jax.custom_vjp``), by its equation
_RULES = ("custom_vjp_call", "custom_vjp_call_jaxpr")


def made_bytes(jaxpr, n_params: int) -> Tuple[int, int, bool]:
    """Of the function's first ``n_params`` inputs that the trace reads at
    all: (bytes of their gradients as its backward makes them, bytes of the
    inputs as stored, whether a rule with a backward of its own reads one).

    A parameter every reader of which is a cast to a narrower dtype
    (:func:`modules.weight_view`) has its gradient made in that dtype: the
    cast back is the transpose of the cast, and XLA fuses it into what
    reads the gradient (the norm, the update), so the step holds the narrow
    one. A parameter anything else reads counts as stored: a gather's
    scatter-add lands in the stored dtype, and a rule (the expert layer's,
    models/moe.py) hands back cotangents of its operands' dtype, which its
    backward carries through the passes of a loop."""
    readers: Dict[Any, List] = {}
    _readers(jaxpr, {}, readers)
    made = stored = 0
    carries = False
    for v in jaxpr.invars[:n_params]:
        eqns = readers.get(v)
        if not eqns:
            continue
        size = jnp.dtype(v.aval.dtype).itemsize
        as_made = size
        if all(e.primitive.name == "convert_element_type" for e in eqns):
            as_made = min(size, max(
                jnp.dtype(e.outvars[0].aval.dtype).itemsize for e in eqns))
        carries |= any(e.primitive.name in _RULES for e in eqns)
        made += math.prod(v.aval.shape) * as_made
        stored += math.prod(v.aval.shape) * size
    return made, stored, carries


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
    made, stored, carries = made_bytes(plain, n_params)
    return BlockCount(
        held_bytes=max(whole - kept, 0) // shards,
        forward_flops=matmul_flops(plain),
        input_bytes=kept // shards, whole_bytes=whole // shards,
        grad_share=made / stored if stored else 1.0, carries=carries), out


class BlockTerms(NamedTuple):
    """One block in :func:`plan_peak`: what it holds until its backward
    ran, one float32 gradient of its parameters, its backward's working
    set, and of its count ``grad_share`` and ``carries``
    (:class:`BlockCount`)."""

    held: int
    grad: int
    working: int
    grad_share: float = 1.0
    carries: bool = False


def plan_peak(args: int, accumulator: int, grads: int,
              blocks: Sequence[Tuple], outer: int,
              rest: Tuple[float, bool] = (1.0, False)) -> int:
    """The step's static bytes a device, estimated for the flags the
    blocks were counted under. ``args``: parameters, optimizer state and
    batch; ``accumulator``: what a step of several microbatches holds to
    accumulate its gradient (0 for one); ``grads``: one float32 gradient of
    every parameter; ``blocks``: in the order the forward runs them, each a
    :class:`BlockTerms`; ``outer``: what the embedding, the head and the
    loss hold; ``rest``: ``grad_share`` and ``carries`` of the parameters
    outside the blocks. The most of: the loss's backward (every block
    holds, the logits' cotangent is as large again as what the loss holds),
    each block's backward (the blocks before it hold; it works, on a
    working set that counts its own held values already; the gradients of
    everything after it are made) and the update (every gradient).

    What each term is, from the buffer assignment of the plan's step in the
    benchmark's cells (PERF.md section 6, PR 72). The gradient: a step that
    runs no loop over its gradients holds a weight's gradient in the dtype
    the backward makes it in (``grad_share``, :func:`made_bytes`: bfloat16
    under mixed precision, half of what was counted until PR 72). A loop
    that carries gradients, the scan over microbatches or the passes of the
    expert rule's backward (``carries``), carries float32, and XLA's count
    of such a step stands 0.3 to 2.4 GB over the bytes its schedule shows
    live: there every gradient is counted in float32 (and ``accumulator``
    at two gradients' worth, PR 62), which over-counts what is made outside
    the loop by about what the loop costs. The working set is the block's
    whole residual count: XLA fuses part of it away and holds cotangents
    and wide intermediate results of its own in its place, from 0.66 of the
    count (a Mamba-1 block) over 1.0 (Mamba-2 with its MLP) to 1.6 (a
    Mamba-2 mixer alone at two sequences), which no trace of the forward
    shows.

    An estimate, where XLA's own count of the plan's step would be exact:
    that count exists once the plan's step is compiled, a step that a job
    which keeps blocks never runs (one to two minutes of a first run on the
    chip), and a budget read off what the compile cache happens to hold
    would give one job two programs, its first run's and its later ones'.
    Against XLA's count of the plan's step the estimate reads 0.88 to 1.15
    in the benchmark's cells, and 1.00 to 1.05 in the four whose step runs
    no such loop (``tools/kept_report.py``; PERF.md section 6, PR 72): over
    keeps fewer blocks, which is the safe side, and under is caught, since
    XLA's count of the CHOSEN step is read before that step runs
    (``KeptStep._checked``)."""
    blocks = [BlockTerms(*b) for b in blocks]
    loops = bool(accumulator) or rest[1] or any(b.carries for b in blocks)
    as_held = [b.grad if loops else int(b.grad * b.grad_share)
               for b in blocks]
    made = grads - sum(b.grad for b in blocks)   # the head's and the rest's
    if not loops:
        made = int(made * rest[0])
    held = sum(b.held for b in blocks)
    peak = max(held + 2 * outer, made + sum(as_held))
    for b, grad in zip(reversed(blocks), reversed(as_held)):
        made += grad
        peak = max(peak, held - b.held + made + max(b.working, b.held))
        held -= b.held
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
