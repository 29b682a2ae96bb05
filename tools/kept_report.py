#!/usr/bin/env python3
"""The step program's byte estimate beside XLA's own count, a cell, with no
chip attached.

    JAX_PLATFORMS=cpu python3 tools/kept_report.py [--workload NAME ...] \
        [--no-compile] [--out FILE]

For each cell of ``BENCHMARK.json`` (``tools/aot_hlo_report.py::cell_step``:
the cell's own command line on a described ``v5e:2x2``) it prints what
``parallel/spmd.py::KeptStep`` counted before anything was compiled (the
estimate of the plan's step, ``parallel/kept.py::plan_peak``; the budget
``FILL x bytes_limit`` leaves; the blocks chosen), then compiles the PLAN's
step (``keep_blocks=False``) and the CHOSEN step and prints XLA's
``live_peak`` of each: the estimate over the first, the second against the
fill. ``fallback`` 1 means an attached chip would have built the step
twice (``step/kept_fallback``). ``--no-compile`` prints the count alone
(seconds a cell). Nothing runs; a rehearsal, never a measurement of time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

GB = 1e9


def cell_report(name: str, compile_steps: bool = True) -> dict:
    from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
        compiled_memory_bytes,
    )
    from hetu_galvatron_tpu.parallel import kept
    from tools.aot_hlo_report import cell_step

    step, shapes = cell_step(name)
    t0 = time.perf_counter()
    lowered = step.lower(*shapes)
    r = dict(getattr(step, "report", None) or {})
    limit = r.get("limit_bytes")
    row = {"cell": name, "fill_bytes": limit and int(limit * kept.FILL),
           **{k: r.get(k) for k in (
               "limit_bytes", "estimate_bytes", "budget_bytes", "kept_bytes",
               "blocks_kept", "blocks_recomputed", "count_s", "counted")},
           "lower_s": time.perf_counter() - t0}
    if not compile_steps:
        return row
    chosen = compiled_memory_bytes(lowered.compile())
    row["chosen"] = chosen
    if sum((r.get("blocks_kept") or {}).values()):
        plan_step, _ = cell_step(name, keep_blocks=False)
        row["plan"] = compiled_memory_bytes(
            plan_step.lower(*shapes).compile())
    else:
        row["plan"] = chosen   # no block is kept: the plan's step it is
    if row["estimate_bytes"]:
        row["estimate_over_xla"] = (
            row["estimate_bytes"] / row["plan"]["live_peak"])
    if limit:
        row["chosen_fill_pct"] = 100.0 * chosen["live_peak"] / limit
        # (a step that keeps no block is the plan's, whatever it compiles to)
        row["fallback"] = int(row["plan"] is not chosen
                              and chosen["live_peak"] > limit * kept.FILL)
    return row


def line(row: dict) -> str:
    gb = lambda b: "-" if b is None else f"{b / GB:.3f}"
    kept_n = sum((row.get("blocks_kept") or {}).values())
    of = kept_n + sum((row.get("blocks_recomputed") or {}).values())
    out = (f"{row['cell']}: estimate {gb(row['estimate_bytes'])} GB, "
           f"budget {gb(row['budget_bytes'])}, kept {kept_n} of {of} "
           f"({gb(row['kept_bytes'])} GB counted)")
    if "plan" in row:
        out += (f"; XLA plan {gb(row['plan']['live_peak'])} "
                f"(estimate x{row.get('estimate_over_xla', 0):.3f}), chosen "
                f"{gb(row['chosen']['live_peak'])} of fill "
                f"{gb(row['fill_bytes'])} "
                f"({row.get('chosen_fill_pct', 0):.1f} % of the limit), "
                f"fallback {row.get('fallback')}")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append",
                    help="a cell of BENCHMARK.json (default: every cell)")
    ap.add_argument("--no-compile", action="store_true",
                    help="the count alone: no step is compiled")
    ap.add_argument("--out", help="append each cell's row (JSON) to FILE")
    a = ap.parse_args()
    from benchmark import manifest as mf

    names = a.workload or [w["name"]
                           for w in mf.load_manifest()["workloads"]]
    bad = 0
    for name in names:
        row = cell_report(name, compile_steps=not a.no_compile)
        bad += row.get("fallback", 0)
        if a.out:
            with open(a.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        print(line(row), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
