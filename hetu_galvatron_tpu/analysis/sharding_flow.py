"""Pass 5 — the sharding-flow analysis: from collective COUNTS to BYTES.

PR 8's census (``analysis/census.py``) proved the hot-path programs
contain exactly the collectives the plan arithmetic promises — but a
program can pass the count gate while moving the wrong AMOUNT: a ring
hop that silently grew a replicated dimension, an activation resharded
twice at a layer boundary, a donated buffer that quietly stopped being
donated (live memory doubles). This module walks the same traced
programs (``CompiledPipelineEngine.step_jaxpr`` /
``ServingEngine.step_jaxprs``) and accounts the BYTES:

* **byte census** — per-collective message megabytes summed per category
  and per ``named_scope`` marker, with the census's scan trip-count
  multipliers, cross-checked EXACTLY (no tolerance) against
  ``observability/telemetry.py::plan_collective_bytes`` — the byte-side
  companion of ``plan_collective_counts``, derived from
  ``plan_comm_volume``'s message arithmetic. A program that moves one
  byte the plan does not predict fails ``cli/check.py``.
* **reshard detection** — explicit all-gathers materializing arrays the
  plan keeps sharded (a weight-sized gather in the step path means GSPMD
  or a kernel is un-sharding what the plan paid to shard), and
  double-resharded values (back-to-back ``sharding_constraint`` eqns
  with differing shardings: the value moves across the mesh twice where
  once suffices). Each finding names the offending program, eqn, and
  shape.
* **donation audit** — the outermost jit's ``donated_invars`` weighed
  in megabytes: the train step must donate the majority of its input
  bytes (params + optimizer state; an undonated step double-buffers the
  model), and the largest undonated buffers are named.

What the jaxpr walk can and cannot see mirrors the census's documented
split: jaxpr-level bytes are the EXPLICIT collectives' (shard_map rings,
rotations, a2a); GSPMD-inserted collectives materialize at partition
time. For those, :func:`hlo_collectives` scans the PARTITIONED program's
compiled HLO text (counts + megabytes per collective category, plus
full-weight-sized all-gather detection) — compiling is expensive, so the
full-program HLO walk rides the slow tier
(``tests/analysis/test_sharding_flow.py``), not ``check --all``.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from hetu_galvatron_tpu.analysis.census import (
    COLLECTIVE_PRIMS,
    PERMUTE_MARKERS,
    _sub_jaxprs,
    _as_jaxpr,
)

MB = 1024 * 1024

# HLO dtype token -> bytes per element (the compiled-text walk)
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}


def _aval_mb(v: Any) -> float:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0.0
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n * aval.dtype.itemsize / MB


@dataclass
class FlowResult:
    """Executed-collective megabytes for one traced program."""

    mb_by_cat: Dict[str, float] = field(default_factory=dict)
    # ppermute megabytes split by named_scope marker ("<unmarked>" pools
    # the rest, same contract as the count census)
    permute_mb_by_marker: Dict[str, float] = field(default_factory=dict)
    notes: List[str] = field(default_factory=list)

    @property
    def total_mb(self) -> float:
        return sum(self.mb_by_cat.values())

    def merge_scaled(self, other: "FlowResult", mult: float) -> None:
        for k, v in other.mb_by_cat.items():
            self.mb_by_cat[k] = self.mb_by_cat.get(k, 0.0) + v * mult
        for k, v in other.permute_mb_by_marker.items():
            self.permute_mb_by_marker[k] = \
                self.permute_mb_by_marker.get(k, 0.0) + v * mult
        for n in other.notes:
            if n not in self.notes:
                self.notes.append(n)


def flow_jaxpr(jaxpr: Any) -> FlowResult:
    """Byte-account the collectives of a (Closed)Jaxpr, recursing into
    subjaxprs with the census's multipliers: scan bodies count ``length``
    times, while bodies once (flagged — dynamic trip count), cond takes
    the branch with the larger collective total (flagged when branches
    disagree). Bytes are the SUMMED operand megabytes of each collective
    eqn — per-device payloads, since shard_map bodies trace local
    shapes."""
    out = FlowResult()
    j = _as_jaxpr(jaxpr)
    if j is None:
        raise TypeError(f"not a jaxpr: {type(jaxpr).__name__}")
    for eqn in j.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            cat = COLLECTIVE_PRIMS[name]
            mb = sum(_aval_mb(v) for v in eqn.invars)
            out.mb_by_cat[cat] = out.mb_by_cat.get(cat, 0.0) + mb
            if cat == "ppermute":
                stack = str(getattr(eqn.source_info, "name_stack", ""))
                for marker in PERMUTE_MARKERS:
                    if marker in stack:
                        out.permute_mb_by_marker[marker] = \
                            out.permute_mb_by_marker.get(marker, 0.0) + mb
                        break
                else:
                    out.permute_mb_by_marker["<unmarked>"] = \
                        out.permute_mb_by_marker.get("<unmarked>", 0.0) + mb
            continue
        if name == "cond":
            branches = [flow_jaxpr(b)
                        for b in eqn.params.get("branches", ())]
            if branches:
                best = max(branches, key=lambda b: b.total_mb)
                if any(not math.isclose(b.total_mb, best.total_mb)
                       for b in branches):
                    best.notes.append(
                        "cond branches move differing collective bytes; "
                        "byte census takes the larger branch")
                out.merge_scaled(best, 1.0)
            continue
        mult = 1.0
        if name == "scan":
            mult = float(eqn.params.get("length", 1))
        elif name == "while":
            for _, sj in _sub_jaxprs(eqn.params):
                sub = flow_jaxpr(sj)
                if sub.total_mb:
                    out.notes.append(
                        "while-loop body moves collective bytes; trip "
                        "count is dynamic so they are counted once")
                out.merge_scaled(sub, 1.0)
            continue
        for _, sj in _sub_jaxprs(eqn.params):
            out.merge_scaled(flow_jaxpr(sj), mult)
    return out


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------


@dataclass
class DonationReport:
    """Megabyte-weighed view of the outermost jit's donated_invars."""

    donated_mb: float = 0.0
    undonated_mb: float = 0.0
    # (shape string, mb) of the largest undonated inputs, descending
    largest_undonated: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def majority_donated(self) -> bool:
        return self.donated_mb >= self.undonated_mb


def donation_report(jaxpr: Any, top: int = 4) -> DonationReport:
    """Weigh the outermost jit's donation decisions: which input bytes
    the program consumes in place vs double-buffers."""
    rep = DonationReport()
    j = _as_jaxpr(jaxpr)
    if j is None:
        return rep
    for eqn in j.eqns:
        if eqn.primitive.name != "jit":
            continue
        donated = eqn.params.get("donated_invars", ())
        undonated: List[Tuple[str, float]] = []
        for v, d in zip(eqn.invars, donated):
            mb = _aval_mb(v)
            if d:
                rep.donated_mb += mb
            else:
                rep.undonated_mb += mb
                aval = getattr(v, "aval", None)
                undonated.append((str(aval) if aval is not None
                                  else "<unknown>", mb))
        undonated.sort(key=lambda t: -t[1])
        rep.largest_undonated = undonated[:top]
        break
    return rep


def check_donation(rep: DonationReport, *, program: str) -> List[str]:
    """The train-step donation gate: the fused optimizer step must donate
    the MAJORITY of its input bytes (params + opt state dominate; an
    undonated step holds the old and new model states simultaneously —
    live memory doubles). Serving programs keep their params resident by
    design and must NOT run through this check."""
    if rep.majority_donated and rep.donated_mb > 0:
        return []
    worst = "; ".join(f"{shape} ({mb:.2f} MB)"
                      for shape, mb in rep.largest_undonated[:3])
    return [
        f"{program}: donated {rep.donated_mb:.2f} MB but left "
        f"{rep.undonated_mb:.2f} MB undonated — the step must donate "
        f"(params, opt) or live memory doubles; largest undonated "
        f"buffers: {worst or '<none>'}"]


# ---------------------------------------------------------------------------
# reshard detection
# ---------------------------------------------------------------------------


def reshard_findings(jaxpr: Any, *, program: str,
                     gather_mb: float = 1.0,
                     _path: str = "") -> List[str]:
    """Static reshard lint over one traced program:

    * an explicit ``all_gather`` whose OUTPUT is at least ``gather_mb``
      megabytes — an array the plan keeps sharded being materialized in
      full (a weight gather in the step path un-does the plan's sharding
      every step);
    * a ``sharding_constraint`` whose operand comes STRAIGHT from another
      ``sharding_constraint`` with a different sharding — the value is
      moved across the mesh twice where one placement suffices (double
      reshard); identical back-to-back constraints are reported as
      redundant notes-grade findings only if shardings differ.

    Findings name the program, the eqn path, and the offending shape
    (the plan-doctor contract: report everything, never raise).
    """
    problems: List[str] = []
    j = _as_jaxpr(jaxpr)
    if j is None:
        return problems
    constrained_by: Dict[Any, Any] = {}
    for i, eqn in enumerate(j.eqns):
        name = eqn.primitive.name
        where = f"{_path}eqn {i} ({name})"
        if name == "all_gather":
            out_mb = sum(_aval_mb(v) for v in eqn.outvars)
            if out_mb >= gather_mb:
                aval = getattr(eqn.outvars[0], "aval", None)
                problems.append(
                    f"{program}: {where} all-gathers "
                    f"{aval.str_short() if aval is not None else '?'} "
                    f"({out_mb:.2f} MB) — an array the plan shards is "
                    "materialized in full every execution")
        elif name == "sharding_constraint":
            sh = str(eqn.params.get("sharding"))
            src = eqn.invars[0]
            prev = constrained_by.get(src)
            if prev is not None and prev != sh:
                aval = getattr(src, "aval", None)
                problems.append(
                    f"{program}: {where} re-reshards "
                    f"{aval.str_short() if aval is not None else '?'} "
                    f"from {prev} to {sh} — the value crosses the mesh "
                    "twice (double reshard); constrain it once at the "
                    "final placement")
            for ov in eqn.outvars:
                constrained_by[ov] = sh
        for key, sj in _sub_jaxprs(eqn.params):
            problems.extend(reshard_findings(
                sj, program=program, gather_mb=gather_mb,
                _path=f"{_path}eqn {i} ({name}) > "))
    return problems


# ---------------------------------------------------------------------------
# byte census vs plan cross-check
# ---------------------------------------------------------------------------

_MARKER_OF = {"ppermute_tp": "tp_ring", "ppermute_cp": "cp_ring",
              "ppermute_pp": "pp_rotate"}


def check_flow(
    flow: FlowResult,
    predicted: Optional[Dict[str, float]] = None,
    *,
    program: str = "step",
) -> List[str]:
    """Problems (empty = clean): when ``predicted`` megabytes are given
    (:func:`~hetu_galvatron_tpu.observability.telemetry.
    plan_collective_bytes`), every predicted marker's traced megabytes
    must match EXACTLY (float-equal within 1e-9 relative — the numbers
    are integer byte counts divided by 2**20), and the total ppermute
    megabytes must equal the prediction's sum (total-strict, mirroring
    the count census: surplus bytes under an unbilled marker are still
    caught). Unpredicted categories (psum transposes, a2a) are reported
    by the caller, not gated — their sizes are partitioner-shaped."""
    problems: List[str] = []
    if predicted is None:
        return problems

    def close(a: float, b: float) -> bool:
        return math.isclose(a, b, rel_tol=1e-9, abs_tol=1e-12)

    for key, want in sorted(predicted.items()):
        marker = _MARKER_OF.get(key)
        got = (flow.permute_mb_by_marker.get(marker, 0.0)
               if marker else flow.mb_by_cat.get(key, 0.0))
        if not close(got, want):
            problems.append(
                f"{program}: plan arithmetic predicts {want:.6f} MB of "
                f"{key}, traced program moves {got:.6f} MB")
    want_total = sum(v for k, v in predicted.items() if k in _MARKER_OF)
    got_total = flow.mb_by_cat.get("ppermute", 0.0)
    if not close(got_total, want_total):
        problems.append(
            f"{program}: plan arithmetic bills {want_total:.6f} MB of "
            f"collective-permute traffic in total, traced program moves "
            f"{got_total:.6f} MB")
    return problems


# ---------------------------------------------------------------------------
# program-level entries (shared trace hooks with the count census)
# ---------------------------------------------------------------------------


@dataclass
class ProgramFlow:
    """One program's full sharding-flow verdict."""

    name: str
    flow: FlowResult
    donation: DonationReport
    reshard_problems: List[str] = field(default_factory=list)


def flow_compiled_step(cfg: Any, hpc: Any, train: Any, *,
                       tp_overlap: bool = True,
                       num_microbatches: Optional[int] = None,
                       devices: Optional[list] = None,
                       donate: bool = True,
                       gather_mb: float = 1.0) -> ProgramFlow:
    """Trace the compiled 1F1B step (``census.trace_compiled_step`` — the
    same hook the count census uses) and run the full byte-side analysis
    on it. ``donate=False`` exists for the undonated-buffer drill."""
    from hetu_galvatron_tpu.analysis.census import trace_compiled_step

    jaxpr, note = trace_compiled_step(
        cfg, hpc, train, tp_overlap=tp_overlap,
        num_microbatches=num_microbatches, devices=devices, donate=donate)
    flow = flow_jaxpr(jaxpr)
    if note is not None:
        flow.notes.append(note)
    return ProgramFlow(
        name="compiled_step", flow=flow,
        donation=donation_report(jaxpr),
        reshard_problems=reshard_findings(
            jaxpr, program="compiled_step", gather_mb=gather_mb))


def flow_spmd_step(cfg: Any, hpc: Any, train: Any, mesh: Any, *,
                   tp_overlap: bool = True,
                   gather_mb: float = 1.0) -> ProgramFlow:
    """Trace the pp=1 SPMD train step (``census.trace_spmd_step``) and run
    the full byte-side analysis."""
    from hetu_galvatron_tpu.analysis.census import trace_spmd_step

    jaxpr = trace_spmd_step(cfg, hpc, train, mesh, tp_overlap=tp_overlap)
    return ProgramFlow(
        name="spmd_step", flow=flow_jaxpr(jaxpr),
        donation=donation_report(jaxpr),
        reshard_problems=reshard_findings(
            jaxpr, program="spmd_step", gather_mb=gather_mb))


def flow_serving_programs(cfg: Any, *, mesh: Any = None, hpc: Any = None,
                          bucket: Optional[int] = None,
                          serving: Any = None,
                          gather_mb: float = 1.0) -> Dict[str, ProgramFlow]:
    """Byte-side analysis of every serving program family. The donation
    audit is informational here (params legitimately stay undonated —
    they persist across calls); the reshard lint gates."""
    from hetu_galvatron_tpu.analysis.census import trace_serving_programs

    jaxprs = trace_serving_programs(cfg, mesh=mesh, hpc=hpc, bucket=bucket,
                                    serving=serving)
    out = {}
    for name, j in jaxprs.items():
        out[name] = ProgramFlow(
            name=name, flow=flow_jaxpr(j), donation=donation_report(j),
            reshard_problems=reshard_findings(
                j, program=f"serving {name}", gather_mb=gather_mb))
    return out


# ---------------------------------------------------------------------------
# partition-time walk (compiled HLO text) — the slow tier
# ---------------------------------------------------------------------------

_HLO_COLLECTIVE_RE = re.compile(
    r"=\s*(\(?[^=]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
    r"(-start)?\(")
_HLO_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_mb(dtype: str, dims: str) -> Optional[float]:
    elem = _HLO_DTYPE_BYTES.get(dtype)
    if elem is None:
        return None
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * elem / MB


def hlo_collectives(hlo_text: str, *, weight_gather_mb: Optional[float]
                    = None) -> Tuple[Dict[str, Dict[str, float]], List[str]]:
    """Scan a PARTITIONED program's HLO text for the collectives GSPMD
    inserted (invisible to a jaxpr): returns
    ``({category: {count, mb}}, findings)``. With ``weight_gather_mb``
    set, any all-gather whose result is at least that many megabytes is a
    finding — a full weight being re-materialized at partition time means
    the lowered program un-shards what the plan shards (the implicit
    GSPMD weight gather this pass exists to catch).

    Async pairs: the ``-start`` op carries the payload and its tuple
    result lists (operand shard, gathered result) — the LARGEST shape in
    the result is taken, so an async full-weight gather is measured by
    its gathered size, not its pre-gather shard; ``-done`` halves carry
    no new bytes and are skipped."""
    cats: Dict[str, Dict[str, float]] = {}
    findings: List[str] = []
    for line_no, line in enumerate(hlo_text.splitlines(), 1):
        m = _HLO_COLLECTIVE_RE.search(line)
        if m is None:
            continue
        result_seg, op = m.group(1), m.group(2)
        shapes = [(_shape_mb(d, dims), d, dims)
                  for d, dims in _HLO_SHAPE_RE.findall(result_seg)]
        shapes = [s for s in shapes if s[0] is not None]
        if not shapes:
            continue
        mb, dtype, dims = max(shapes, key=lambda s: s[0])
        slot = cats.setdefault(op, {"count": 0, "mb": 0.0})
        slot["count"] += 1
        slot["mb"] += mb
        if (op == "all-gather" and weight_gather_mb is not None
                and mb >= weight_gather_mb):
            findings.append(
                f"partitioned HLO line {line_no}: all-gather materializes "
                f"{dtype}[{dims}] ({mb:.2f} MB) — a plan-sharded weight "
                "is re-gathered at partition time")
    return cats, findings
