"""What the host does while the device waits between two steps.

The trainer's loop (``cli/train_dist.py::run_loop``) enters flat sibling
spans ``train/data``, ``train/h2d``, ``train/dispatch``, ``train/sync``,
``train/lr``, ``train/log``, ``train/check`` (and ``train/telemetry``,
``train/eval``, ``train/save`` when that work is done) through
``observability/tracing.py::span``. Inside the profiler's window each is a
TraceMe on a line of ``/host:CPU`` with the iteration as its ``step``.

A gap runs from one step program's end on the first device to the next
one's start (``xplane.host_gaps_ns``; ``host_gap_ms`` is their median).
Each of the steady window's gaps is cut into the parts the spans cover; the
metrics are the mean of each part over the gaps, so the eight add up to the
mean gap whatever the clocks do.

**The two planes do not share a clock to the millisecond.** In every trace
read so far the device's plane runs early against the host's by a constant
of its own, 0.4 to 1.7 ms (PERF.md, PR 24): a step "starts" on the device
before the host has enqueued it. The constant is bounded from both sides by
what cannot happen: a program does not start before the host begins its
``DoEnqueueProgram`` (paired by ``run_id``), and the host's
``tpu::System::Execute=>Done`` for a core does not begin before that
program ended (paired in order). The device's steps are shifted by the
middle of that interval before they are laid over the spans (``place``).
The shift moves time only between the two parts at a gap's ends
(``gap_sync_ms``: the step's end to the host knowing it, and the wait for
the next step to start once ``train/dispatch`` has returned;
``gap_dispatch_ms``), by at most half the interval's width; the parts
inside a gap are host clock alone. So an interval wider than
``MAX_OFFSET_WIDTH_NS`` is still placed at its middle, with its width
printed, and so is one that the stamps' own jitter has turned inside out
by no more than ``STAMP_JITTER_NS``: an absent metric refuses a PR, and a
few tenths of a millisecond moved between two of eight parts do not.
Nothing is published where the interval cannot be made (no enqueue, or
completions that do not pair with the programs) or is inverted by more
than the stamps can jitter, which is no pairing either.

After the shift the clocks are checked: every traced step starts on the
device after its iteration's ``train/dispatch`` began and at most
``CLOCK_SLACK_NS`` after it ended, and ends before that iteration's
``train/sync`` does. A trace that fails, or one of a program without these
spans, gives ``None`` for every metric here.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

from benchmark import xplane

HOST_PLANE = "/host:CPU"
PREFIX = "train/"
ENQUEUE, DONE = "DoEnqueueProgram", "tpu::System::Execute=>Done"
# span -> the part of a gap it is booked to; any other train/* span is OTHER
PART_OF = {"train/sync": "sync", "train/lr": "lr", "train/log": "log",
           "train/data": "data", "train/h2d": "h2d",
           "train/dispatch": "dispatch"}
OTHER, UNNAMED = "other", "unnamed"
PARTS = tuple(PART_OF.values()) + (OTHER, UNNAMED)
CLOCK_SLACK_NS = 2e6
# an interval wider than this is placed like any other and said to be wide:
# five step programs bound the offset to 0.36 to 0.76 ms (26 traces, PR 31)
MAX_OFFSET_WIDTH_NS = 1e6
# How far one host stamp strays from another that bounds the same constant:
# in the recorded trace beside the tests the eight tightest lower bounds
# (of 55) lie within 46 us and so do the eight tightest upper ones, and the
# one empty interval in 28 traced runs (PR 30) was inverted by 15.5 us with
# every count paired. A mis-pairing is off by a program's length instead.
STAMP_JITTER_NS = 50e3

Span = Tuple[str, float, float, Optional[int]]   # name, start, end, step
Step = Tuple[float, float]


class Planes(NamedTuple):
    """What one trace says about the loop and about one device's clock."""

    spans: List[Span]                  # train/* TraceMes, sorted by start
    programs: List[Tuple[float, float, Any]]   # device: start, end, run_id
    enqueued: Dict[Any, float]         # run_id -> host begins the enqueue
    done: List[float]                  # host begins Execute=>Done, in order


def read_planes(path: str, device: int = 0) -> Planes:
    from jax.profiler import ProfileData

    spans: List[Span] = []
    programs, enqueued, done = [], {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == f"/device:TPU:{device}":
            for line in plane.lines:
                if line.name == xplane.MODULES_LINE:
                    programs = sorted(
                        (float(e.start_ns), float(e.start_ns + e.duration_ns),
                         dict(e.stats).get("run_id")) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                mine = []
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        mine.append((e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns),
                                     dict(e.stats).get("step")))
                    elif e.name == ENQUEUE:
                        stats = dict(e.stats)
                        if stats.get("device_ordinal") == device:
                            enqueued[stats.get("run_id")] = float(e.start_ns)
                    elif e.name == DONE:
                        if dict(e.stats).get("core_id") == device:
                            done.append(float(e.start_ns))
                # the thread that dispatches the step
                if any(s[0] == "train/dispatch" for s in mine):
                    spans += mine
    return Planes(sorted(spans, key=lambda s: (s[1], s[2])), programs,
                  enqueued, sorted(done))


def offset_bounds(planes: Planes) -> Optional[Tuple[float, float]]:
    """``(lower, upper)`` in ns of what has to be added to the device's
    clock to get the host's; ``None`` where a side cannot be bounded."""
    lower = [planes.enqueued[r] - s for s, _, r in planes.programs
             if r in planes.enqueued]
    if not lower or len(planes.done) != len(planes.programs):
        return None
    upper = [d - e for (_, e, _), d in zip(planes.programs, planes.done)]
    return max(lower), min(upper)


def clock_offsets(steps: Sequence[Step], spans: Sequence[Span]
                  ) -> Optional[List[Tuple[int, float, float, float]]]:
    """Per traced step, in ns: (iteration, the step's start on the device
    after its ``train/dispatch`` began, after that span ended (negative:
    inside it), and the end of its ``train/sync`` after the step's end on
    the device). ``None`` when steps and iterations cannot be paired."""
    by_iter: Dict[Any, Dict[str, Span]] = {}
    for s in spans:
        by_iter.setdefault(s[3], {}).setdefault(s[0], s)
    iters = sorted(k for k, v in by_iter.items() if k is not None
                   and "train/dispatch" in v and "train/sync" in v)
    if len(iters) != len(steps):
        return None
    return [(it, s - by_iter[it]["train/dispatch"][1],
             s - by_iter[it]["train/dispatch"][2],
             by_iter[it]["train/sync"][2] - e)
            for it, (s, e) in zip(iters, steps)]


def clock_problems(steps: Sequence[Step], spans: Sequence[Span]) -> List[str]:
    """Why the spans cannot be laid over the device's steps; empty when
    they can."""
    offsets = clock_offsets(steps, spans)
    if offsets is None:
        return [f"{len(steps)} traced steps on the device do not pair with "
                "the iterations that have train/dispatch and train/sync"]
    bad = []
    for it, after_start, after_end, sync_after in offsets:
        if after_start < 0 or after_end > CLOCK_SLACK_NS:
            bad.append(f"iteration {it}: the step starts "
                       f"{after_start / 1e6:.3f} ms after train/dispatch "
                       f"starts, {after_end / 1e6:.3f} ms after it ends")
        if sync_after < 0:
            bad.append(f"iteration {it}: the step ends "
                       f"{-sync_after / 1e6:.3f} ms after train/sync ends")
    return bad


def split_gap(lo: float, hi: float, spans: Sequence[Span]) -> Dict[str, float]:
    """``[lo, hi]`` cut into the parts the spans cover, in ns. A span is
    cut at the gap's ends; where two overlap (they should not: the loop's
    spans are siblings on one thread) the earlier keeps the overlap, so
    the parts always add up to ``hi - lo``."""
    parts = dict.fromkeys(PARTS, 0.0)
    cursor = lo
    for name, s, e, _ in spans:
        s, e = max(s, cursor), min(e, hi)
        if e <= s:
            continue
        parts[UNNAMED] += s - cursor
        parts[PART_OF.get(name, OTHER)] += e - s
        cursor = e
    parts[UNNAMED] += hi - cursor
    return parts


def gap_parts_ms(steps: Sequence[Step], spans: Sequence[Span],
                 offset_ns: float = 0.0) -> Optional[Dict[str, float]]:
    """Mean over the between-step gaps of each part, in ms, with the
    device's steps shifted by ``offset_ns`` onto the host's clock;
    ``None`` when there is no gap, no span, or the clock check fails."""
    if len(steps) < 2 or not spans:
        return None
    steps = [(s + offset_ns, e + offset_ns) for s, e in steps]
    problems = clock_problems(steps, spans)
    if problems:
        print("host_phases: no gap_* metric, the clock check failed: "
              + "; ".join(problems), flush=True)
        return None
    gaps = [split_gap(a[1], b[0], spans) for a, b in zip(steps, steps[1:])]
    return {p: sum(g[p] for g in gaps) / len(gaps) / 1e6 for p in PARTS}


def place(bounds: Optional[Tuple[float, float]]
          ) -> Tuple[Optional[float], str]:
    """``(offset, rule)``: the middle of ``offset_bounds``' interval in ns
    and which rule put it there (``bounded``, ``inverted``: inside out by
    no more than ``STAMP_JITTER_NS``, ``wide``: over
    ``MAX_OFFSET_WIDTH_NS``); ``(None, why)`` where there is no interval
    or the two pairings contradict each other by more than stamps jitter."""
    if bounds is None:
        return None, "not bounded from both sides"
    width = bounds[1] - bounds[0]
    if width < -STAMP_JITTER_NS:
        return None, (f"inverted by {-width / 1e3:.1f} us, more than the "
                      f"stamps' jitter of {STAMP_JITTER_NS / 1e3:g} us")
    if width < 0:
        rule = "inverted"
    elif width > MAX_OFFSET_WIDTH_NS:
        rule = "wide"
    else:
        rule = "bounded"
    return sum(bounds) / 2, rule


def parts_of_trace(path: str, steps: Sequence[Step],
                   device: int = 0) -> Optional[Dict[str, float]]:
    """The eight parts of the mean gap from one ``.xplane.pb`` and the
    device's traced steps (``xplane.Reduced.steps``)."""
    planes = read_planes(path, device)
    if not planes.spans:
        return None
    bounds = offset_bounds(planes)
    offset, rule = place(bounds)
    counts = (f"{len(planes.programs)} programs, {len(planes.enqueued)} "
              f"enqueues, {len(planes.done)} completions")
    if offset is None:
        print("host_phases: no gap_* metric, the device's clock cannot be "
              f"placed on the host's: {rule}: bounds {bounds} ns from "
              f"{counts}", flush=True)
        return None
    print(f"host_phases: the device's clock placed {offset / 1e6:+.6f} ms "
          f"onto the host's, rule {rule}: bounds "
          f"{(bounds[1] - bounds[0]) / 1e6:.6f} ms apart from {counts}",
          flush=True)
    return gap_parts_ms(steps, planes.spans, offset)


def _trace_dir(facts: Dict[str, Any]) -> Optional[str]:
    for word in facts.get("argv", ()):
        if word.startswith("profile.trace_dir="):
            return word.split("=", 1)[1]
    return None


def _parts(facts: Dict[str, Any]) -> Optional[Dict[str, float]]:
    if "host_phases" not in facts:   # one read of the trace for all eight
        trace, tdir = facts.get("trace"), _trace_dir(facts)
        path = xplane.find_xplane(tdir) if tdir else None
        facts["host_phases"] = parts_of_trace(
            path, trace["reduced"][0].steps, trace["reduced"][0].id) \
            if trace and path else None
    return facts["host_phases"]


def _part(facts: Dict[str, Any], name: str) -> Optional[float]:
    parts = _parts(facts)
    return None if parts is None else parts[name]


def gap_sync_ms(facts):
    return _part(facts, "sync")


def gap_lr_ms(facts):
    return _part(facts, "lr")


def gap_log_ms(facts):
    return _part(facts, "log")


def gap_data_ms(facts):
    return _part(facts, "data")


def gap_h2d_ms(facts):
    return _part(facts, "h2d")


def gap_dispatch_ms(facts):
    return _part(facts, "dispatch")


def gap_other_ms(facts):
    return _part(facts, OTHER)


def gap_unnamed_ms(facts):
    return _part(facts, UNNAMED)
