"""Device time of a Mamba-2 block's parts, by named scope.

A TPU trace names its events by HLO instruction (``fusion.12``); which
``jax.named_scope`` an instruction came from is only in the optimized HLO's
``op_name`` metadata. The program, where it reads that HLO for its step
report (``cli/train_dist.py``, span ``setup/step_report``), keeps the
instruction names under each ``mixer/mamba/*`` scope
(``observability/trace_analysis.py::scope_instructions``, a fusion by its
root's scope), and this reader, which runs in the trainer's process, lays
the traced leaf operations of the cell's first device over them.

Nothing is published (``None``, the line leaves the metric out) where there
is no trace, where the program kept no such map (the parent commit has no
state-space block and no map), where a scope holds no instruction, or where
an operation traced inside a step is no instruction of the step's HLO: the
join would then be of two different programs.
"""

import importlib
import os

from benchmark import manifest, readers

SCOPES = tuple(f"mixer/mamba/{part}" for part in (
    "in_proj", "conv", "ssd", "gated_norm", "out_proj"))
# the part between the two projections: the convolution and the scan
SSD_SCOPES = ("mixer/mamba/conv", "mixer/mamba/ssd")
COST_FILE, COST = "granite_ssd_cost.py", "granite_ssd_step_cost"


def _step_scopes():
    try:
        mod = importlib.import_module(
            "hetu_galvatron_tpu.observability.trace_analysis")
    except ImportError:
        return None
    kept = getattr(mod, "step_scopes", None)
    return kept() if callable(kept) else None


def scope_ns(facts, scopes):
    """Summed leaf time (ns, over the steady window) of the instructions
    under ``scopes`` on the cell's first device, and that device's reduced
    trace; ``None`` where the join cannot be made."""
    trace, kept = facts.get("trace"), _step_scopes()
    if not trace or not kept or not kept.get("scopes"):
        return None
    by_scope, known = kept["scopes"], kept.get("instructions") or ()
    if any(not by_scope.get(s) for s in scopes):
        return None
    r = trace["reduced"][0]
    inside = lambda s, e: any(a <= s and e <= b for a, b in r.steps)
    if any(n not in known for n, s, e in r.leaves if inside(s, e)):
        return None
    wanted = {n for s in scopes for n in by_scope[s]}
    return sum(e - s for n, s, e in r.leaves if n in wanted), r


def _ms_per_step(facts, scopes):
    got = scope_ns(facts, scopes)
    return None if got is None else got[0] / got[1].periods / 1e6


def ssd_ms(facts):
    return _ms_per_step(facts, SSD_SCOPES)


def mamba_ms(facts):
    return _ms_per_step(facts, SCOPES)


def ssd_time_share_pct(facts):
    got = scope_ns(facts, SSD_SCOPES)
    return None if got is None else 100.0 * got[0] / (got[1].busy_s * 1e9)


def ssd_roofline(facts):
    """Least time by the roofline (``granite_ssd_cost.py``) over the
    measured time of the convolution and the scan, in percent."""
    got = scope_ns(facts, SSD_SCOPES)
    if got is None or got[0] <= 0:
        return None
    return readers.roofline_pct(
        facts, got[0] / got[1].periods / 1e9, COST,
        manifest.load_python(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), COST_FILE)))
