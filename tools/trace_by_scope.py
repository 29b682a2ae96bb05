#!/usr/bin/env python3
"""A TPU trace of the trainer laid over the compiled step's own HLO: device
ms a step by named scope and phase, the collectives by class, what is still
under no scope, and what the idle gaps inside a step lie between.

    python3 tools/trace_by_scope.py TRACE_DIR [--device N] [--json FILE]

``TRACE_DIR`` is what ``profile.trace_dir`` was (for a benchmark run:
``benchmark/out/trace/<cell>.seed<n>``). When the trainer's profiler window
closes it writes ``step_map.json`` there, beside ``plugins/profile/<run>/
*.xplane.pb``: each instruction of the step's optimized HLO with its scope,
phase and collective class (``observability/trace_analysis.py::step_hlo``
has the rules). A trace names its events by instruction, so the join needs
nothing else: no process, no chip, no second compile. The trace is reduced
by ``benchmark/xplane.py`` exactly as the benchmark's readers reduce it
(leaf operations of the first device's ``XLA Ops`` line inside the traced
steps, over the whole periods of the steady window), so the sums here are
the per-layer metrics' (``benchmark/layer_metrics/step_map.py``).

It prints

* ms a step by scope (rows) and phase (columns), heaviest first, with the
  share of the summed leaf time;
* the collectives by class and phase, ms a step and events a step;
* the twenty heaviest instructions under no scope, with the tail of their
  ``op_name`` (their opcode where they have none) and, from the map's
  ``owners``, the scope and phase of the nearest scoped instruction that
  uses the result (``user``) or made an operand (``operand``) and how many
  instructions away it is (``hops``): whose code the copy belongs to;
* each idle gap inside a step longer than 0.2 ms, by the instruction that
  ends before it and the one that starts after it (scope, phase and
  collective class of both), with how often it occurs and its mean length;
  and, by ``benchmark/layer_metrics/step_flow.py``'s rules (the per-layer
  metrics ``idle_*_ms`` sum the same records), its class: ``hidden`` (no
  gap: the named operation ran, and an event of no length at its own start
  took it out of the trace's leaves), ``collective``, ``prefetch`` or
  ``unexplained``; the custom call that says so with its target
  (``ConcatBitcast``), and the transfers behind it (the prefetches whose
  results it concatenates, or the one in flight that feeds the next
  operation) with their bytes and the time from their start on the ``Async
  XLA Ops`` line to the gap's end: bytes over that time is the rate the
  wait implies;
* the traced names that are no instruction of the map (none, when trace
  and map are of one program);
* where the trace holds several chips, one row a chip: its compute, the
  collectives' transfer (the least duration of each occurrence over the
  chips) and its wait for the latest chip (its durations less that), the
  wait cut by starts beside it, and the rows and counted passes the expert
  exchange handed it in the last logged step (``chips`` of
  ``step_map.json``); under it how far apart one reduce-scatter's ends lie
  on the chips, which says whether the planes share a clock. The function
  is the benchmark's (``benchmark/layer_metrics/chip_skew.py``:
  ``collective_wait_ms``, ``collective_transfer_ms``, ``chip_skew_ms``).
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from hetu_galvatron_tpu.observability.trace_analysis import (  # noqa: E402
    PHASES,
    STEP_MAP_FILE,
)

GAP_MS, HEAVIEST = 0.2, 20
NO_SCOPE = "(no scope)"


def join(reduced, step_map):
    """The tables of one device's steady window (a ``benchmark.xplane.
    Reduced``) over a ``step_map.json``'s content, as a dict; times in ms a
    step."""
    classes = {n: tuple(c) for n, c in step_map["instructions"].items()}
    tails = step_map.get("tails", {})
    periods = reduced.periods
    per = lambda ns: ns / periods / 1e6
    step_of = lambda s, e: next(
        (i for i, (a, b) in enumerate(reduced.steps) if a <= s and e <= b),
        None)
    leaves = sorted((s, e, n) for n, s, e in reduced.leaves
                    if step_of(s, e) is not None)
    by_cell = collections.Counter()
    by_class = collections.Counter()
    events = collections.Counter()
    unnamed = collections.Counter()
    strangers = collections.Counter()
    for s, e, n in leaves:
        if n not in classes:
            strangers[n] += e - s
            continue
        scope, phase, cls = classes[n]
        by_cell[(scope or NO_SCOPE, phase)] += e - s
        if cls:
            by_class[(cls, phase)] += e - s
            events[(cls, phase)] += 1
        if scope is None:
            unnamed[n] += e - s
    total = sum(by_cell.values()) + sum(strangers.values())
    scopes = collections.Counter()
    for (scope, _), ns in by_cell.items():
        scopes[scope] += ns
    describe = lambda n: "/".join(
        str(x) for x in classes.get(n, ("?",)) if x) or NO_SCOPE
    owners = step_map.get("owners", {})
    # (a map written before PR 73 follows no data: every gap is then
    # hidden, a collective's by its class, or unexplained)
    followed = {"transfers": {}, "calls": {}, **step_map}
    flow = _reader("step_flow.py").laid(reduced, followed)
    gaps = collections.defaultdict(list)
    for g in flow["gaps"]:
        if g["end"] - g["start"] > GAP_MS * 1e6:
            gaps[(g["after"], g["before"], g["class"])].append(g)
    for n, s, e in flow["hidden"]:
        if e - s > GAP_MS * 1e6:
            gaps[(n, n, "hidden")].append(
                {"start": s, "end": e, "by": None, "waits_for": []})

    def gap_row(n0, n1, cls, found):
        by, waits = found[0]["by"], collections.defaultdict(list)
        for g in found:
            for n, size, ns in g["waits_for"]:
                waits[(n, size)].append(ns)
        return {
            "after": n0, "after_is": describe(n0), "before": n1,
            "before_is": describe(n1), "times": len(found),
            "mean_ms": sum(g["end"] - g["start"] for g in found)
            / len(found) / 1e6,
            "class": cls, "by": by,
            "target": followed["calls"].get(by, {}).get("target"),
            "waits_for": [
                {"transfer": n, "bytes": size,
                 "us_from_its_start": sum(ns) / len(ns) / 1e3}
                for (n, size), ns in waits.items()]}
    return {
        "periods": periods,
        "leaf_ms_a_step": per(total),
        "busy_ms_a_step": reduced.busy_s * 1e3 / periods,
        "by_scope_and_phase": [
            {"scope": scope, "ms": per(ns), "pct": 100.0 * ns / total,
             **{p: per(by_cell[(scope, p)]) for p in PHASES
                if by_cell[(scope, p)]}}
            for scope, ns in scopes.most_common()],
        "by_phase": {p: per(sum(ns for (_, q), ns in by_cell.items()
                                if q == p)) for p in PHASES},
        "collectives": [
            {"class": cls, "phase": phase, "ms": per(ns),
             "events_a_step": events[(cls, phase)] / periods}
            for (cls, phase), ns in sorted(by_class.items())],
        "unnamed": [
            {"instruction": n, "ms": per(ns), "phase": classes[n][1],
             "op_name_tail": tails.get(n, ""),
             **dict(zip(("owner", "owner_phase", "via", "hops"),
                        owners.get(n) or (None,) * 4))}
            for n, ns in unnamed.most_common(HEAVIEST)],
        "idle_inside_ms": {
            "all": per(flow["inside_ns"]), "hidden": per(flow["hidden_ns"]),
            **{cls: per(ns) for cls, ns in flow["by_class_ns"].items()}},
        "gaps": sorted((gap_row(*key, found) for key, found in gaps.items()),
                       key=lambda g: -g["mean_ms"] * g["times"]),
        "not_in_the_map": [[n, per(ns)] for n, ns in strangers.most_common()],
    }


def print_tables(t, file=None):
    say = lambda *a: print(*a, file=file)
    say(f"{t['periods']} periods; summed leaf time {t['leaf_ms_a_step']:.2f}"
        f" ms a step, busy {t['busy_ms_a_step']:.2f} ms a step")
    say(f"{'scope':28s} {'ms':>9s} {'%':>6s} "
        + " ".join(f"{p:>10s}" for p in PHASES))
    for row in t["by_scope_and_phase"]:
        say(f"{row['scope']:28s} {row['ms']:9.3f} {row['pct']:6.2f} "
            + " ".join(f"{row.get(p, 0.0):10.3f}" for p in PHASES))
    say(f"{'(every scope)':28s} {t['leaf_ms_a_step']:9.3f} {100.0:6.2f} "
        + " ".join(f"{t['by_phase'][p]:10.3f}" for p in PHASES))
    if t["collectives"]:
        say("collectives by class and phase:")
    for row in t["collectives"]:
        say(f"  {row['class']:24s} {row['phase']:10s} {row['ms']:9.3f} ms "
            f"{row['events_a_step']:7.1f} a step")
    say(f"the {len(t['unnamed'])} heaviest instructions under no scope:")
    for row in t["unnamed"]:
        owner = (f"  owner {row['owner']}/{row['owner_phase']} via "
                 f"{row['via']}, {row['hops']} hops" if row["owner"]
                 else "  no owner")
        say(f"  {row['ms']:9.3f} ms  {row['instruction']:32s} "
            f"{row['phase']:10s} {row['op_name_tail']}{owner}")
    idle = t["idle_inside_ms"]
    say(f"idle inside a step {idle['all']:.3f} ms: " + ", ".join(
        f"{k} {v:.3f}" for k, v in idle.items() if k != "all")
        + f"; the gaps over {GAP_MS} ms:" + ("" if t["gaps"] else " none"))
    for g in t["gaps"]:
        if g["class"] == "hidden":
            say(f"  {g['mean_ms']:7.3f} ms x {g['times']}  hidden    no gap: "
                f"{g['after']} [{g['after_is']}] ran, an empty event at its "
                "start took it out of the leaves")
            continue
        say(f"  {g['mean_ms']:7.3f} ms x {g['times']}  {g['class']:9s} after "
            f"{g['after']} [{g['after_is']}], before {g['before']} "
            f"[{g['before_is']}]"
            + (f"; by {g['by']}" + (f" ({g['target']})" if g["target"]
                                    else "") if g["by"] else "")
            + "".join(f"; {w['transfer']} {w['bytes'] / 1e6:.2f} MB "
                      f"{w['us_from_its_start']:.0f} us"
                      for w in g["waits_for"]))
    if t["not_in_the_map"]:
        say("traced inside a step and NO instruction of the map (trace and "
            "map are of two programs?):")
        for n, ms in t["not_in_the_map"]:
            say(f"  {ms:9.3f} ms  {n}")


def _reader(name):
    from benchmark import manifest

    return manifest.load_python(os.path.join(
        ROOT, "benchmark", "layer_metrics", name))


def by_chip(reduced, step_map):
    """The chips side by side (``chip_skew.py``'s table): ``None`` for one
    chip, a step map without collectives, or chips that hold different
    collective occurrences in every traced step."""
    skew = _reader("chip_skew.py")
    table = skew.side_by_side(reduced, step_map["instructions"])
    if table is None:
        return None
    apart = skew.clock_check(table)
    return {"steps": table["steps"],
            "chips": skew.by_chip_rows(table, step_map.get("chips")),
            "reduce_scatter_ends_apart_us": (
                None if apart is None else [ns / 1e3 for ns in apart])}


def print_by_chip(t, file=None):
    say = lambda *a: print(*a, file=file)
    say(f"the chips side by side, ms a step over {t['steps']} traced steps:")
    say(f"  {'chip':>4s} {'compute':>9s} {'transfer':>9s} {'wait':>9s} "
        f"{'by starts':>9s} {'rows':>9s} {'passes':>6s}")
    show = lambda v: "-" if v is None else f"{v:.0f}"
    for row in t["chips"]:
        say(f"  {row['chip']:4d} {row['compute_ms']:9.3f} "
            f"{row['transfer_ms']:9.3f} {row['wait_ms']:9.3f} "
            f"{row['wait_by_starts_ms']:9.3f} {show(row['rows']):>9s} "
            f"{show(row['passes']):>6s}")
    if t["reduce_scatter_ends_apart_us"]:
        say("  one reduce-scatter's ends on the chips lie {:.3f} us apart "
            "(median), {:.3f} at most".format(
                *t["reduce_scatter_ends_apart_us"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--device", type=int, default=0,
                    help="which traced device (default: the first)")
    ap.add_argument("--json", help="also write the tables to this file")
    a = ap.parse_args(argv)
    from benchmark import xplane

    map_path = os.path.join(a.trace_dir, STEP_MAP_FILE)
    if not os.path.isfile(map_path):
        print(f"no {STEP_MAP_FILE} in {a.trace_dir}: the trainer writes it "
              "when its profiler window closes", file=sys.stderr)
        return 2
    path = xplane.find_xplane(a.trace_dir)
    if path is None:
        print(f"no .xplane.pb under {a.trace_dir}", file=sys.stderr)
        return 2
    with open(map_path) as f:
        step_map = json.load(f)
    devices = [d for d in xplane.read_devices(path) if d.ops]
    reduced = [xplane.reduce_device(d) for d in devices]
    tables = join(reduced[a.device], step_map)
    tables["by_chip"] = by_chip(reduced, step_map)
    if a.json:
        with open(a.json, "w") as f:
            json.dump(tables, f, indent=1)
    print_tables(tables)
    if tables["by_chip"]:
        print_by_chip(tables["by_chip"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
