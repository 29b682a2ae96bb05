"""From the profiler's ``.xplane.pb`` to facts about the device.

Read with ``jax.profiler.ProfileData`` and nothing else. What a TPU trace
looks like (read off real traces of this program on a v5e by hand, see
benchmark/README.md): one plane ``/device:TPU:<n>`` per chip; on it a line
``XLA Modules`` with one event per executed program and a line ``XLA Ops``
with one event per executed HLO operation, control flow (``while``,
``conditional``) as a parent event around its body's operations; a line
``Async XLA Ops`` with one event per asynchronous operation from its
``-start`` to its ``-done`` (copies, slices, collectives in flight). An
event's name is the whole HLO instruction (``%fusion.12 = f32[...] ...``);
this module keeps the instruction's name without the ``%``. All lines of a
plane share one clock, in nanoseconds.

Definitions, the same for every cell and every later PR:

* a STEP is one event of the step program on ``XLA Modules``: the module
  name that holds most of the traced time. The steady window runs from the
  first traced step's start to the last traced step's start, so it holds
  N - 1 whole periods (step and the gap after it) and no ragged edge;
* BUSY is the union of the LEAF operation intervals (events that hold no
  other event), so a ``while`` around idle time does not count as work;
* an operation's own time (``self``) is its duration less its children's.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
# trailing ".12", ".clone", "-start"/"-done" numbering: what makes two
# executions of one kind of operation differ in name
_STEM = re.compile(r"(\.\d+|\.clone|\.remat\d*)+$")

Event = Tuple[str, float, float]   # name, start ns, end ns


@dataclass
class DeviceTrace:
    id: int
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    async_ops: List[Event] = field(default_factory=list)
    lines: List[str] = field(default_factory=list)


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_devices(path: str) -> List[DeviceTrace]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = DeviceTrace(id=int(m.group(1)))
        for line in plane.lines:
            dev.lines.append(line.name)
            if line.name in (OPS_LINE, MODULES_LINE, ASYNC_LINE):
                short = ((lambda n: n) if line.name == MODULES_LINE
                         else op_name)
                evs = sorted(((short(e.name), float(e.start_ns),
                               float(e.start_ns + e.duration_ns))
                              for e in line.events), key=lambda e: (e[1], -e[2]))
                setattr(dev, {OPS_LINE: "ops", MODULES_LINE: "modules",
                              ASYNC_LINE: "async_ops"}[line.name], evs)
        out.append(dev)
    return sorted(out, key=lambda d: d.id)


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def stem(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``all-gather-start.4.clone`` ->
    ``all-gather-start``."""
    return _STEM.sub("", op_name(name))


def self_times(ops: List[Event]) -> List[Tuple[str, float, float, float, bool]]:
    """(name, start, end, self ns, is leaf) per event of one line, where
    events may nest: an event is the child of the nearest earlier event
    that holds all of it (one that only overlaps it is a sibling). ``ops``
    sorted by (start, -end)."""
    out: List[List[Any]] = []
    stack: List[int] = []
    for name, s, e in ops:
        while stack and (out[stack[-1]][2] <= s or out[stack[-1]][2] < e):
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= e - s
            parent[4] = False
        out.append([name, s, e, e - s, True])
        stack.append(len(out) - 1)
    return [tuple(x) for x in out]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def step_module(dev: DeviceTrace) -> str:
    total: Dict[str, float] = {}
    for name, s, e in dev.modules:
        total[name] = total.get(name, 0.0) + (e - s)
    if not total:
        raise ValueError(
            f"device {dev.id}: no event on {MODULES_LINE!r} (lines: "
            f"{dev.lines})")
    return max(total, key=total.get)


@dataclass
class Reduced:
    """One device's steady window."""

    id: int
    steps: List[Tuple[float, float]]          # every traced step
    window: Tuple[float, float]
    leaves: List[Tuple[str, float, float]]    # leaf ops inside the window
    selfs: List[Tuple[str, float]]            # (name, self ns) inside it
    in_flight: List[Tuple[str, float, float]]  # async ops inside it

    @property
    def periods(self) -> int:
        return len(self.steps) - 1

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return union_ns((s, e) for _, s, e in self.leaves) / 1e9


def reduce_device(dev: DeviceTrace) -> Reduced:
    name = step_module(dev)
    steps = [(s, e) for n, s, e in dev.modules if n == name]
    if len(steps) < 2:
        raise ValueError(f"device {dev.id}: {len(steps)} traced step(s) of "
                         f"{name!r}; a steady window needs two")
    lo, hi = steps[0][0], steps[-1][0]
    leaves, selfs = [], []
    for n, s, e, self_ns, leaf in self_times(dev.ops):
        if e <= lo or s >= hi:
            continue
        frac = (min(e, hi) - max(s, lo)) / (e - s) if e > s else 0.0
        selfs.append((n, self_ns * frac))
        if leaf:
            leaves.append((n, max(s, lo), min(e, hi)))
    in_flight = [(n, max(s, lo), min(e, hi)) for n, s, e in dev.async_ops
                 if e > lo and s < hi]
    return Reduced(dev.id, steps, (lo, hi), leaves, selfs, in_flight)


# ---------------------------------------------------------------------------
# what the readers and the result line take
# ---------------------------------------------------------------------------


def _events(r: Reduced, line: str) -> List[Tuple[str, float, float]]:
    return {OPS_LINE: r.leaves, ASYNC_LINE: r.in_flight}[line]


def matching_ns(r: Reduced, pattern: str, line: str = OPS_LINE) -> float:
    """Summed time of the operations on ``line`` (leaf operations of ``XLA
    Ops``, or ``Async XLA Ops`` from start to done) whose name matches
    ``pattern``."""
    rx = re.compile(pattern)
    return sum(e - s for n, s, e in _events(r, line) if rx.search(n))


def exposed_ns(r: Reduced, pattern: str, line: str = OPS_LINE) -> float:
    """The part of the matching operations' time during which no other
    leaf operation of ``XLA Ops`` runs on that device."""
    rx = re.compile(pattern)
    mine = [(s, e) for n, s, e in _events(r, line) if rx.search(n)]
    others = [(s, e) for n, s, e in r.leaves if not rx.search(n)]
    return union_ns(mine) - (union_ns(mine) + union_ns(others)
                             - union_ns(mine + others))


def host_gaps_ns(r: Reduced) -> List[float]:
    """Gap between one step program's end and the next one's start."""
    return [b[0] - a[1] for a, b in zip(r.steps, r.steps[1:])]


def breakdown(r: Reduced, top: int = 10) -> Dict[str, List[List[Any]]]:
    by_stem: Dict[str, float] = {}
    for n, ns in r.selfs:
        by_stem[stem(n)] = by_stem.get(stem(n), 0.0) + ns
    ops = sorted(by_stem.items(), key=lambda kv: -kv[1])[:top]
    # idle gaps between consecutive leaf operations, classed by whether a
    # step program was running
    gaps: Dict[str, float] = {}
    longest: List[Tuple[float, str]] = []
    busy = sorted((s, e) for _, s, e in r.leaves)
    cur_e = r.window[0]
    for s, e in busy:
        if s > cur_e:
            inside = any(a <= cur_e and s <= b for a, b in r.steps)
            kind = "inside_step" if inside else "between_steps"
            gaps[kind] = gaps.get(kind, 0.0) + (s - cur_e)
            longest.append((s - cur_e, kind))
        cur_e = max(cur_e, e)
    if r.window[1] > cur_e:
        gaps["between_steps"] = gaps.get("between_steps", 0.0) + (
            r.window[1] - cur_e)
        longest.append((r.window[1] - cur_e, "between_steps"))
    idle = [[f"{k}_total", v / 1e9] for k, v in sorted(
        gaps.items(), key=lambda kv: -kv[1])]
    idle += [[f"{k}_longest", ns / 1e9] for ns, k in sorted(
        longest, reverse=True)[:top - len(idle)]]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": idle[:top]}


def facts_of(path: str, chips: int) -> Dict[str, Any]:
    """Everything the per-layer readers and the result line need, from one
    trace file, over the ``chips`` devices the cell used."""
    devs = [d for d in read_devices(path) if d.ops][:chips]
    if len(devs) < chips:
        raise ValueError(f"trace holds operations of {len(devs)} device(s), "
                         f"the cell used {chips}")
    red = [reduce_device(d) for d in devs]
    mean = lambda xs: sum(xs) / len(xs)
    r0 = red[0]
    return {
        "reduced": red,
        "busy_s": mean([r.busy_s for r in red]),
        "window_s": mean([r.window_s for r in red]),
        "periods": r0.periods,
        "idle_pct": 100.0 * (1.0 - mean([r.busy_s / r.window_s
                                         for r in red])),
        "step_device_ms": mean([r.busy_s / r.periods for r in red]) * 1e3,
        "host_gap_ms": statistics.median(host_gaps_ns(r0)) / 1e6,
        "step_module": step_module(devs[0]),
        "breakdown": breakdown(r0),
    }


def dump(path: str, limit: int = 40) -> str:
    """A trace by hand: planes, lines, and the heaviest names on each."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            tot: Dict[str, List[float]] = {}
            n = 0
            for e in line.events:
                n += 1
                t = tot.setdefault(e.name, [0, 0.0])
                t[0] += 1
                t[1] += e.duration_ns
            rows.append(f"  LINE {line.name!r}: {n} events, "
                        f"{len(tot)} names")
            for name, (cnt, ns) in sorted(
                    tot.items(), key=lambda kv: -kv[1][1])[:limit]:
                rows.append(f"    {ns / 1e6:12.3f} ms  x{cnt:<6d} {name[:140]}")
    return "\n".join(rows)
