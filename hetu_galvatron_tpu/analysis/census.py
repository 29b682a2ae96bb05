"""Pass 2 — the jaxpr collective census.

The α-β cost model and ``plan_comm_volume`` predict what a plan SHOULD
communicate; PR 6's plan audit checks those predictions against a measured
device trace. This module closes the same loop from the STATIC side: trace
the hot-path programs with ``jax.make_jaxpr`` (no devices execute, no step
runs) and count the collectives the program actually contains, recursing
into jit/shard_map/scan/remat/custom-vjp subjaxprs with scan trip-count
multipliers — so a program that silently grew an extra ring hop, lost a
``jax.named_scope`` trace marker, or picked up a host callback in the step
path fails ``cli/check.py`` before any TPU time is burned.

What the census can and cannot see (documented, not hidden): jaxpr-level
collectives are the EXPLICIT ones — the shard_map kernels' ``ppermute``
rings (tp overlap, cp ring attention, pp stage rotation), Ulysses
``all_to_all``, fused-CE ``psum``. GSPMD-inserted collectives (ZeRO
gathers, dp grad all-reduce under ``jit``) materialize only at partition
time and are the measured audit's job. That split is exactly why the
predicted side (:func:`~hetu_galvatron_tpu.observability.telemetry.
plan_collective_counts`) predicts the explicit kernels' counts.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

# primitive name -> census category (explicit collectives only; GSPMD
# inserts the rest at partition time, invisible to a jaxpr)
COLLECTIVE_PRIMS: Dict[str, str] = {
    "ppermute": "ppermute",
    "pcollective_permute": "ppermute",
    "psum": "all_reduce",
    "all_gather": "all_gather",
    "psum_scatter": "reduce_scatter",
    "reduce_scatter": "reduce_scatter",
    "all_to_all": "all_to_all",
}

# host-callback primitives that must never ride a hot-path program (each
# one is a device->host sync per execution)
CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                  "outside_call", "host_callback")

# the named_scope markers the kernels stamp their permutes with so trace
# attribution (observability/trace_analysis.py _PERMUTE_MARKERS) can bill
# them to the right plan component; the census fails unmarked permutes so
# the attribution can never silently regress
PERMUTE_MARKERS: Tuple[str, ...] = ("tp_ring", "cp_ring", "pp_rotate")


@dataclass
class CensusResult:
    """Executed-collective counts for one traced program."""

    counts: Dict[str, int] = field(default_factory=dict)
    # ppermute counts split by named_scope marker; key "<unmarked>" holds
    # permutes carrying none of PERMUTE_MARKERS
    permutes_by_marker: Dict[str, int] = field(default_factory=dict)
    # name-stack strings of unmarked permute eqns (diagnostics)
    unmarked_permutes: List[str] = field(default_factory=list)
    callbacks: List[str] = field(default_factory=list)
    donated_args: int = 0  # donated invars of the outermost jit, if any
    notes: List[str] = field(default_factory=list)

    @property
    def total_collectives(self) -> int:
        return sum(self.counts.values())

    def merge_scaled(self, other: "CensusResult", mult: int) -> None:
        for k, v in other.counts.items():
            self.counts[k] = self.counts.get(k, 0) + v * mult
        for k, v in other.permutes_by_marker.items():
            self.permutes_by_marker[k] = \
                self.permutes_by_marker.get(k, 0) + v * mult
        self.unmarked_permutes.extend(other.unmarked_permutes)
        self.callbacks.extend(other.callbacks)
        for n in other.notes:
            if n not in self.notes:
                self.notes.append(n)


def _is_jaxpr(v: Any) -> bool:
    return hasattr(v, "eqns") and hasattr(v, "invars")


def _as_jaxpr(v: Any):
    """ClosedJaxpr -> Jaxpr; Jaxpr passes through; else None."""
    if _is_jaxpr(v):
        return v
    inner = getattr(v, "jaxpr", None)
    if inner is not None and _is_jaxpr(inner):
        return inner
    return None


def _sub_jaxprs(params: Dict[str, Any]):
    """(key, jaxpr) pairs for every subjaxpr value in an eqn's params —
    covers jit/shard_map/scan/remat ('jaxpr'), custom vjp/jvp
    ('call_jaxpr'/'fun_jaxpr'/'fwd_jaxpr_thunk' is a thunk and skipped),
    and tuple-valued params like cond 'branches'."""
    for key, v in params.items():
        j = _as_jaxpr(v)
        if j is not None:
            yield key, j
            continue
        if isinstance(v, (tuple, list)):
            for x in v:
                j = _as_jaxpr(x)
                if j is not None:
                    yield key, j


def census_jaxpr(jaxpr: Any) -> CensusResult:
    """Count collectives in a (Closed)Jaxpr, recursing into subjaxprs.

    Multipliers: a ``scan`` body is counted ``length`` times (the schedule
    tick loop); ``while`` bodies have no static trip count, so their
    collectives are counted ONCE and flagged in ``notes``; ``cond``
    branches are counted as the element-wise max across branches (the
    program executes one of them), flagged when branches disagree.
    """
    out = CensusResult()
    j = _as_jaxpr(jaxpr)
    if j is None:
        raise TypeError(f"not a jaxpr: {type(jaxpr).__name__}")
    for eqn in j.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            cat = COLLECTIVE_PRIMS[name]
            out.counts[cat] = out.counts.get(cat, 0) + 1
            if cat == "ppermute":
                stack = str(getattr(eqn.source_info, "name_stack", ""))
                for marker in PERMUTE_MARKERS:
                    if marker in stack:
                        out.permutes_by_marker[marker] = \
                            out.permutes_by_marker.get(marker, 0) + 1
                        break
                else:
                    out.permutes_by_marker["<unmarked>"] = \
                        out.permutes_by_marker.get("<unmarked>", 0) + 1
                    out.unmarked_permutes.append(stack or "<no name stack>")
            continue
        if name in CALLBACK_PRIMS:
            cb = str(eqn.params.get("callback", name))
            out.callbacks.append(f"{name}: {cb}")
            continue
        if name == "cond":
            branches = [census_jaxpr(b)
                        for b in eqn.params.get("branches", ())]
            if branches:
                merged = branches[0]
                for b in branches[1:]:
                    if b.counts != merged.counts:
                        merged.notes.append(
                            "cond branches contain differing collective "
                            "counts; census takes the element-wise max")
                    for k, v in b.counts.items():
                        merged.counts[k] = max(merged.counts.get(k, 0), v)
                    for k, v in b.permutes_by_marker.items():
                        merged.permutes_by_marker[k] = max(
                            merged.permutes_by_marker.get(k, 0), v)
                    merged.unmarked_permutes.extend(b.unmarked_permutes)
                    merged.callbacks.extend(b.callbacks)
                out.merge_scaled(merged, 1)
            continue
        mult = 1
        if name == "scan":
            mult = int(eqn.params.get("length", 1))
        elif name == "while":
            sub = None
            for _, sj in _sub_jaxprs(eqn.params):
                sub = census_jaxpr(sj)
                if sub.total_collectives:
                    out.notes.append(
                        "while-loop body contains collectives; trip count "
                        "is dynamic so they are counted once")
                out.merge_scaled(sub, 1)
            continue
        if name == "jit" and not out.counts and not out.donated_args:
            donated = eqn.params.get("donated_invars", ())
            out.donated_args = int(sum(bool(d) for d in donated))
        for _, sj in _sub_jaxprs(eqn.params):
            out.merge_scaled(census_jaxpr(sj), mult)
    return out


# ---------------------------------------------------------------------------
# tracing the hot-path programs (no devices execute)
# ---------------------------------------------------------------------------


def _tiny_batch(cfg: Any, global_bsz: int, seed: int = 0):
    import numpy as np

    rng = np.random.RandomState(seed)
    toks = rng.randint(0, cfg.padded_vocab_size,
                       (global_bsz, cfg.seq_length + 1))
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}


def trace_compiled_step(cfg: Any, hpc: Any, train: Any, *,
                        tp_overlap: bool = True,
                        num_microbatches: Optional[int] = None,
                        devices: Optional[list] = None,
                        donate: bool = True):
    """Build the compiled 1F1B engine on (virtual CPU) devices, split
    freshly initialized params, and return
    ``(step ClosedJaxpr, overlap-ineligibility note or None)`` via
    ``CompiledPipelineEngine.step_jaxpr`` — tracing only, nothing executes
    a training step. Shared by the collective census (Pass 2) and the
    sharding-flow byte census (Pass 5); ``donate=False`` exists for the
    undonated-buffer drill."""
    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.runtime.compiled_pipeline import (
        CompiledPipelineEngine,
    )

    import jax
    import jax.numpy as jnp

    eng = CompiledPipelineEngine(cfg, hpc, train, devices=devices,
                                 compute_dtype=jnp.float32,
                                 tp_overlap=tp_overlap, donate=donate)
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    sp = eng.split_params(params, axes)
    so = eng.init_opt(sp, axes)
    jaxpr = eng.step_jaxpr(sp, so, _tiny_batch(cfg, hpc.global_bsz),
                           num_microbatches)
    note = None
    if tp_overlap and not eng.tp_overlap:
        note = f"tp_overlap requested but ineligible: {eng.overlap_reason}"
    return jaxpr, note


def census_compiled_step(cfg: Any, hpc: Any, train: Any, *,
                         tp_overlap: bool = True,
                         num_microbatches: Optional[int] = None,
                         devices: Optional[list] = None) -> CensusResult:
    """Trace the compiled single-program 1F1B step for a plan and census
    it (:func:`trace_compiled_step` + :func:`census_jaxpr`)."""
    jaxpr, note = trace_compiled_step(
        cfg, hpc, train, tp_overlap=tp_overlap,
        num_microbatches=num_microbatches, devices=devices)
    out = census_jaxpr(jaxpr)
    if note is not None:
        out.notes.append(note)
    return out


def trace_spmd_step(cfg: Any, hpc: Any, train: Any, mesh: Any,
                    *, tp_overlap: bool = True):
    """ClosedJaxpr of the pp=1 SPMD train step (``parallel.spmd``) —
    tracing only, nothing executes. Shared by the count census and the
    sharding-flow byte census."""
    import jax
    import jax.numpy as jnp

    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.parallel.spmd import make_spmd_train_step
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer

    params, axes = init_causal_lm(jax.random.key(0), cfg)
    tx = make_optimizer(train)
    step, pspecs, ospecs, _ = make_spmd_train_step(
        cfg, hpc, mesh, axes, tx, params, compute_dtype=jnp.float32,
        donate=True, tp_overlap=tp_overlap)
    sp_shape = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)
    so_shape = jax.eval_shape(tx.init, sp_shape)
    batch = _tiny_batch(cfg, hpc.global_bsz)
    return jax.make_jaxpr(step)(sp_shape, so_shape, batch)


def census_spmd_step(cfg: Any, hpc: Any, train: Any, mesh: Any,
                     *, tp_overlap: bool = True) -> CensusResult:
    """Trace the pp=1 SPMD train step (``parallel.spmd``) and census it."""
    return census_jaxpr(trace_spmd_step(
        cfg, hpc, train, mesh, tp_overlap=tp_overlap))


def trace_serving_programs(cfg: Any, *, mesh: Any = None, hpc: Any = None,
                           bucket: Optional[int] = None,
                           serving: Any = None) -> Dict[str, Any]:
    """ClosedJaxprs of every serving program family
    (``ServingEngine.step_jaxprs``) on a throwaway engine — the shared
    trace entry for the count census and the sharding-flow byte census."""
    import jax

    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.serving.engine import ServingEngine

    params, axes = init_causal_lm(jax.random.key(0), cfg)
    kw = {}
    if mesh is not None:
        kw = {"mesh": mesh, "hpc": hpc, "axes_tree": axes}
    eng = ServingEngine(params, cfg, serving, **kw)
    try:
        return eng.step_jaxprs(bucket=bucket)
    finally:
        eng.close()


def census_serving_programs(cfg: Any, *, mesh: Any = None, hpc: Any = None,
                            bucket: Optional[int] = None,
                            serving: Any = None) -> Dict[str, CensusResult]:
    """Trace the serving prefill + decode programs (``serving/engine.py``)
    and census each — catches a host callback or an unmarked collective
    creeping into the token-latency path."""
    jaxprs = trace_serving_programs(cfg, mesh=mesh, hpc=hpc, bucket=bucket,
                                    serving=serving)
    return {name: census_jaxpr(j) for name, j in jaxprs.items()}


# ---------------------------------------------------------------------------
# census vs plan cross-check
# ---------------------------------------------------------------------------


def check_census(
    census: CensusResult,
    predicted: Optional[Dict[str, int]] = None,
    *,
    program: str = "step",
    allow_callbacks: bool = False,
) -> List[str]:
    """Problems (empty = clean): unmarked permutes, host callbacks in the
    hot path, and — when ``predicted`` counts are given
    (:func:`~hetu_galvatron_tpu.observability.telemetry.
    plan_collective_counts`) — any exact-count mismatch between what the
    plan arithmetic promises and what the traced program contains. The
    ppermute check is TOTAL-strict (per-marker counts AND the overall
    ppermute total must both match the prediction's sum, so a surplus
    permute in any category is caught); other explicit categories
    (psum from shard_map weight-cotangent transposes, all_to_all) are
    counted and reported but gated only when the prediction names them —
    their counts are partitioner-shaped, not plan arithmetic."""
    problems: List[str] = []
    n_unmarked = census.permutes_by_marker.get("<unmarked>", 0)
    if n_unmarked:
        where = "; ".join(sorted(set(census.unmarked_permutes))[:4])
        problems.append(
            f"{program}: {n_unmarked} collective-permute(s) carry no "
            f"tp_ring/cp_ring/pp_rotate named_scope marker "
            f"(trace attribution would mis-bill them) — name stacks: "
            f"{where}")
    if census.callbacks and not allow_callbacks:
        problems.append(
            f"{program}: host callback(s) in the hot path: "
            + "; ".join(sorted(set(census.callbacks))[:4]))
    if predicted is not None:
        marker_of = {"ppermute_tp": "tp_ring", "ppermute_cp": "cp_ring",
                     "ppermute_pp": "pp_rotate"}
        for key, want in sorted(predicted.items()):
            if key in marker_of:
                got = census.permutes_by_marker.get(marker_of[key], 0)
            else:
                got = census.counts.get(key, 0)
            if got != want:
                problems.append(
                    f"{program}: plan arithmetic predicts {want} x {key}, "
                    f"traced program contains {got}")
        # total-strict on permutes: a surplus ppermute under a marker the
        # prediction did not bill (or double-marked) must not pass just
        # because its own key was absent from `predicted`
        want_total = sum(v for k, v in predicted.items()
                         if k in marker_of)
        got_total = census.counts.get("ppermute", 0)
        if got_total != want_total:
            problems.append(
                f"{program}: plan arithmetic bills {want_total} "
                f"collective-permutes in total, traced program contains "
                f"{got_total}")
    return problems
