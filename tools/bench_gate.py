"""Perf regression sentinel over the bench history (BENCH_r*.json).

The driver runs ``bench.py`` every round and archives the result as
``BENCH_r<NN>.json`` (``{"n": ..., "cmd": ..., "rc": ..., "tail": ...,
"parsed": {...}}`` — ``parsed`` is bench.py's single JSON result line).
Nothing ever read those files back; a regression was only caught by a
human rereading them. This gate closes that loop:

* a **committed baseline** (``tools/bench_baseline.json``) records the
  accepted per-leg numbers and the device they were measured on,
* each run, the **newest** history entry is compared leg-by-leg against
  the baseline with a relative threshold (default 10%), honoring each
  leg's direction (``tokens_per_sec`` up is good; ``compiled_vs_host``
  down is good),
* a leg past the threshold fails the gate (rc 1) with a readable per-leg
  delta report; legs measured on a different device than the baseline are
  skipped with a warning (a CPU-fallback bench must not "regress" a TPU
  baseline, nor green-light it); a baseline leg the candidate lacks fails
  only when same-device history shows it was measured before (vanished),
  and renders as "pending" when it is simply newer than the history (a
  freshly committed entry),
* the history's per-leg min/max rides along as a noise-context column.

Wiring: nothing runs the gate on a chip today;
``__graft_entry__.dryrun_multichip`` runs ``--smoke`` (a synthetic
self-check: an unchanged run must pass, an artificially regressed leg
must fail) so the gate itself is exercised on every CI dryrun with no
bench data needed.

Usage:
  python tools/bench_gate.py                 # newest BENCH_r*.json vs baseline
  python tools/bench_gate.py --threshold 0.05
  python tools/bench_gate.py --candidate path.json
  python tools/bench_gate.py --update-baseline   # accept the candidate
  python tools/bench_gate.py --smoke             # self-check, no data needed
"""

from __future__ import annotations

import argparse
import glob
import io
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(ROOT, "tools", "bench_baseline.json")
DEFAULT_HISTORY = os.path.join(ROOT, "BENCH_r*.json")

# leg name -> (source key in the parsed bench result, higher_is_better)
LEGS: Tuple[Tuple[str, str, bool], ...] = (
    ("mfu_pct", "value", True),
    ("tokens_per_sec", "tokens_per_sec", True),
    ("flash_speedup", "flash_speedup", True),
    ("fused_ce_speedup", "fused_ce_speedup", True),
    ("tp_overlap_vs_gspmd", "tp_overlap_vs_gspmd", False),
    ("compiled_vs_host", "compiled_vs_host", False),
    # the unified path: compiled 1F1B with the shard_map kernels (ring tp
    # matmuls + flash) inside, vs the host engine with the same kernels
    # (tools/pipeline_dispatch_bench.py --kernels). A ratio, regresses UP.
    ("compiled_overlap", "compiled_overlap_vs_host", False),
    # serving legs (tools/serve_bench.py run_prefix / run_spec):
    # hit-vs-cold TTFT ratio under the radix prefix cache (below 1.0 =
    # cached prefill really skipped; regresses UP) and speculative-decode
    # vs plain tokens/sec (above 1.0 = accepted drafts beat the wider
    # verify program; regresses DOWN)
    ("serve_prefix", "serve_prefix_ttft_ratio", False),
    ("spec_decode", "spec_decode_tokens_ratio", True),
    # hierarchical-vs-flat dp gradient reduction (tools/hier_dp_bench.py):
    # lane-accumulated rs/ar/ag once per step vs the flat GSPMD in-scan
    # all-reduce. A ratio, regresses UP.
    ("hier_dp", "hier_dp_vs_flat", False),
    # bucketed software-pipelined hier schedule vs the monolithic
    # three-collective program (hier vs hier, same plan): on the CPU mesh
    # the ratio prices the bucketing overhead (<= ~1.0 — the pipelined
    # program must not cost more than it hides); regresses UP.
    ("hier_dp_bucketed", "hier_dp_bucketed_vs_mono", False),
    # synthesized-schedule emitter vs the hand-built reference bodies
    # (tools/synth_collectives_bench.py): emitted ring/halving-doubling
    # program wall-clock over the canonical bodies, bit-parity asserted
    # before timing. A ratio pricing the emitter's table-driven
    # bookkeeping; regresses UP.
    ("synth_collectives", "synth_collectives_vs_handbuilt", False),
)


def extract_legs(parsed: Optional[Dict[str, Any]]) -> Dict[str, float]:
    """Numeric per-leg values from one bench ``parsed`` dict."""
    out: Dict[str, float] = {}
    if not isinstance(parsed, dict):
        return out
    for leg, key, _ in LEGS:
        v = parsed.get(key)
        if isinstance(v, (int, float)) and v > 0:
            out[leg] = float(v)
    return out


def load_history(pattern: str = DEFAULT_HISTORY
                 ) -> List[Tuple[int, str, Dict[str, Any]]]:
    """(round, path, parsed) for every readable history file with a parsed
    result, ordered by round number."""
    out = []
    for path in glob.glob(pattern):
        m = re.search(r"r(\d+)\.json$", os.path.basename(path))
        if not m:
            continue
        try:
            with open(path) as f:
                obj = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        parsed = obj.get("parsed") if isinstance(obj, dict) else None
        if isinstance(parsed, dict):
            out.append((int(m.group(1)), path, parsed))
    return sorted(out)


def compare(baseline: Dict[str, Any], candidate: Dict[str, Any],
            *, threshold: float,
            history: Optional[List[Dict[str, Any]]] = None
            ) -> Tuple[List[Dict[str, Any]], bool]:
    """Per-leg delta rows + overall pass. ``baseline``/``candidate`` are
    {"device": ..., "legs": {...}} dicts; ``history`` is a list of older
    parsed bench results for the noise-context column."""
    base_dev = str(baseline.get("device", ""))
    cand_dev = str(candidate.get("device", ""))
    dev_ok = (not base_dev) or (base_dev == cand_dev)
    hist_legs: Dict[str, List[float]] = {}
    for parsed in history or []:
        if str(parsed.get("device", "")) != base_dev:
            continue
        for leg, v in extract_legs(parsed).items():
            hist_legs.setdefault(leg, []).append(v)

    rows: List[Dict[str, Any]] = []
    ok = True
    directions = {leg: hib for leg, _, hib in LEGS}
    for leg in [l for l, _, _ in LEGS]:
        b = baseline.get("legs", {}).get(leg)
        c = candidate.get("legs", {}).get(leg)
        if b is None and c is None:
            continue
        row: Dict[str, Any] = {"leg": leg, "baseline": b, "candidate": c}
        hist = hist_legs.get(leg)
        if hist:
            row["history"] = (min(hist), max(hist))
        if not dev_ok:
            row["status"] = (f"skipped (device mismatch: "
                             f"{cand_dev or '?'} vs baseline "
                             f"{base_dev or '?'})")
        elif b is None:
            row["status"] = "new (no baseline; run --update-baseline)"
        elif c is None:
            if hist:
                # a leg silently VANISHING is a regression signal: the
                # bench measured it before (same-device history) and
                # stopped — something the baseline promises went dark
                row["status"] = "MISSING from candidate"
                ok = False
            else:
                # a baseline leg NO same-device run ever produced is
                # merely newer than the history (a freshly committed
                # entry, e.g. compiled_overlap): render it pending, not
                # failed, or the gate is permanently red from the commit
                # that introduces a leg until the next bench round
                row["status"] = "pending (no measured history yet)"
        else:
            delta = (c - b) / b
            row["delta"] = delta
            worse = -delta if directions[leg] else delta
            if worse > threshold:
                row["status"] = f"REGRESSED (>{threshold:.0%})"
                ok = False
            elif worse < -threshold:
                row["status"] = "improved"
            else:
                row["status"] = "ok"
        rows.append(row)
    return rows, ok


def _fmt(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if abs(v) >= 1000:
        return f"{v:,.0f}"
    return f"{v:.3f}".rstrip("0").rstrip(".")


def render_report(rows: List[Dict[str, Any]], ok: bool, *,
                  candidate_name: str, baseline_name: str, out=None) -> None:
    out = out or sys.stdout
    w = lambda s="": print(s, file=out)
    w(f"== bench gate: {candidate_name} vs {baseline_name} ==")
    w(f"{'leg':<22}{'baseline':>10}{'candidate':>11}{'delta':>9}"
      f"{'history':>17}  status")
    for r in rows:
        hist = (f"[{_fmt(r['history'][0])}, {_fmt(r['history'][1])}]"
                if "history" in r else "-")
        delta = f"{r['delta']:+.1%}" if "delta" in r else "-"
        w(f"{r['leg']:<22}{_fmt(r['baseline']):>10}"
          f"{_fmt(r['candidate']):>11}{delta:>9}{hist:>17}  {r['status']}")
    n_bad = sum(1 for r in rows
                if r["status"].startswith(("REGRESSED", "MISSING")))
    if not ok:
        w(f"bench gate: FAIL ({n_bad} leg(s) regressed)")
    elif rows and all(r["status"].startswith("skipped") for r in rows):
        # every leg was device-skipped: nothing was actually gated, and
        # "PASS" would green-light an ungated run (e.g. a TPU candidate
        # against the committed CPU baseline)
        w("bench gate: NO VERDICT (every leg skipped — run "
          "--update-baseline on this device to start gating it)")
    else:
        w("bench gate: PASS")


def smoke() -> int:
    """Self-check with synthetic data: an unchanged run must pass and an
    artificially regressed leg must fail — exercising extract/compare/
    render end-to-end without any bench history."""
    base = {"device": "TPU v5 lite",
            "legs": {"mfu_pct": 40.0, "tokens_per_sec": 100000.0,
                     "compiled_vs_host": 0.7, "compiled_overlap": 0.75,
                     "serve_prefix": 0.3, "spec_decode": 1.4,
                     "hier_dp": 0.85, "hier_dp_bucketed": 0.95}}
    same = {"device": "TPU v5 lite",
            "legs": {"mfu_pct": 39.2, "tokens_per_sec": 98000.0,
                     "compiled_vs_host": 0.72, "compiled_overlap": 0.77,
                     "serve_prefix": 0.31, "spec_decode": 1.37,
                     # hier_dp_bucketed IMPROVING (dropping — the
                     # pipelined schedule hiding more) must pass too:
                     # both directions of the new leg ride the smoke
                     "hier_dp": 0.87, "hier_dp_bucketed": 0.82}}
    bad = {"device": "TPU v5 lite",
           "legs": {"mfu_pct": 40.1, "tokens_per_sec": 80000.0,
                    "compiled_vs_host": 0.95, "compiled_overlap": 1.2,
                    # serve_prefix regresses UP (hits stop skipping
                    # prefill), spec_decode DOWN (drafts stop paying)
                    "serve_prefix": 0.9, "spec_decode": 0.8,
                    # hier_dp regresses UP (the hierarchical schedule
                    # stops beating the flat all-reduce); the bucketed
                    # leg regresses UP too (bucketing overhead outgrew
                    # the overlap win)
                    "hier_dp": 1.3, "hier_dp_bucketed": 1.25}}
    other_dev = {"device": "cpu", "legs": {"mfu_pct": 5.0}}

    rows, ok_same = compare(base, same, threshold=0.10)
    render_report(rows, ok_same, candidate_name="<unchanged run>",
                  baseline_name="<synthetic baseline>")
    rows, ok_bad = compare(base, bad, threshold=0.10)
    render_report(rows, ok_bad, candidate_name="<regressed run>",
                  baseline_name="<synthetic baseline>")
    regressed = {r["leg"] for r in rows
                 if r["status"].startswith("REGRESSED")}
    rows, ok_dev = compare(base, other_dev, threshold=0.10)
    buf = io.StringIO()
    render_report(rows, ok_dev, candidate_name="<other device>",
                  baseline_name="<synthetic baseline>", out=buf)
    healthy = (ok_same and not ok_bad
               and regressed == {"tokens_per_sec", "compiled_vs_host",
                                 "compiled_overlap", "serve_prefix",
                                 "spec_decode", "hier_dp",
                                 "hier_dp_bucketed"}
               and ok_dev
               and all(r["status"].startswith("skipped") for r in rows)
               and "NO VERDICT" in buf.getvalue()
               and _regret_smoke())
    print(f"bench gate --smoke: "
          f"{'self-check OK' if healthy else 'SELF-CHECK FAILED'}")
    return 0 if healthy else 1


def _regret_smoke() -> bool:
    """Synthetic plan-regret detection case (observability/calibration):
    calibrated curves that halve the collective cost must flip a
    comm-heavy runner-up past the incumbent (triggered), while calibrated
    == prior must not. Keeps the regret sentinel's arithmetic under the
    same no-bench-data self-check the perf legs get."""
    try:
        try:
            from hetu_galvatron_tpu.observability.calibration import (
                evaluate_plan_regret,
            )
        except ImportError:
            # run as a bare script (python tools/bench_gate.py): the repo
            # root is not on sys.path — add it and retry
            sys.path.insert(0, os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))
            from hetu_galvatron_tpu.observability.calibration import (
                evaluate_plan_regret,
            )
    except ImportError as e:
        print(f"regret smoke: calibration module unavailable ({e})")
        return False
    prior = {"2_1": (0.1, 100.0), "4_1": (0.1, 100.0)}
    calib = {"2_1": (0.05, 200.0), "4_1": (0.05, 200.0)}
    incumbent = {"time_cost_ms": 100.0, "pp": 1, "bsz": 8, "chunks": 2,
                 "layers": [{"tp": 1, "dp": 2}] * 2}
    hungry = {"time_cost_ms": 101.0, "pp": 1, "bsz": 8, "chunks": 2,
              "layers": [{"tp": 4, "dp": 2}] * 2}
    kw = dict(seq_len=4096, hidden_size=4096, param_mb=8.0,
              mixed_precision=True, threshold=0.001)
    hit = evaluate_plan_regret(incumbent, [hungry], prior=(prior, None),
                               calibrated=(calib, None), **kw)
    quiet = evaluate_plan_regret(incumbent, [hungry], prior=(prior, None),
                                 calibrated=(prior, None), **kw)
    ok = (bool(hit["triggered"]) and hit["regret_ms"] > 0
          and not quiet["triggered"] and quiet["regret_ms"] == 0.0)
    print(f"regret smoke: {'ok' if ok else 'FAILED'} "
          f"(triggered {hit['regret_ms']:.3f} ms; quiet "
          f"{quiet['regret_ms']:.3f} ms)")
    return ok


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--history", default=DEFAULT_HISTORY,
                    help="glob of BENCH_r*.json files")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--candidate", default=None,
                    help="a bench result JSON (BENCH_r*.json shape or a "
                         "bare parsed dict); default: newest history entry")
    ap.add_argument("--threshold", type=float, default=None,
                    help="relative regression threshold (default: the "
                         "baseline's recorded threshold, else 0.10)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="accept the candidate as the new baseline")
    ap.add_argument("--smoke", action="store_true",
                    help="synthetic self-check (CI; needs no bench data)")
    args = ap.parse_args(argv)

    if args.smoke:
        return smoke()

    history = load_history(args.history)
    if args.candidate:
        try:
            with open(args.candidate) as f:
                obj = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"bench gate: cannot read candidate "
                  f"{args.candidate}: {e}", file=sys.stderr)
            return 2
        parsed = obj.get("parsed", obj) if isinstance(obj, dict) else None
        cand_name = args.candidate
        prior = [p for _, path, p in history
                 if os.path.abspath(path) != os.path.abspath(args.candidate)]
    elif history:
        _, cand_name, parsed = history[-1]
        prior = [p for _, _, p in history[:-1]]
    else:
        print(f"bench gate: no parseable history at {args.history} and no "
              "--candidate given", file=sys.stderr)
        return 2
    legs = extract_legs(parsed)
    if not legs:
        print(f"bench gate: candidate {cand_name} carries no per-leg "
              "numbers (bench never completed?); nothing to gate",
              file=sys.stderr)
        return 0
    candidate = {"device": (parsed or {}).get("device", ""), "legs": legs}

    if args.update_baseline:
        baseline = {"created_from": os.path.basename(str(cand_name)),
                    "device": candidate["device"],
                    "threshold": (args.threshold if args.threshold is not None
                                  else 0.10),
                    "legs": candidate["legs"]}
        with open(args.baseline, "w") as f:
            json.dump(baseline, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"bench gate: baseline updated from {cand_name} "
              f"({len(legs)} legs) -> {args.baseline}")
        return 0

    try:
        with open(args.baseline) as f:
            baseline = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench gate: no baseline at {args.baseline} ({e}); run "
              "with --update-baseline to create one", file=sys.stderr)
        return 2

    threshold = args.threshold
    if threshold is None:
        rec = baseline.get("threshold")
        threshold = float(rec) if isinstance(rec, (int, float)) else 0.10
    rows, ok = compare(baseline, candidate, threshold=threshold,
                       history=prior)
    render_report(rows, ok, candidate_name=str(cand_name),
                  baseline_name=args.baseline)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
