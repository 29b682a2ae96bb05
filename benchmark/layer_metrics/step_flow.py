"""The idle time inside a traced step put down to what the device was
waiting for, and the layout passes and unowned instructions of a step, by
following the step's data through the compiled step's map.

``step_map.py`` reads each traced instruction alone. Since PR 73 the map
(``observability/trace_analysis.py::step_hlo``) also follows a value from
the instruction that makes it to the one that uses it: ``transfers`` (every
asynchronous pair: a prefetch XLA made, or a collective in two halves, with
its bytes, memory space, producer and what it ``feeds``), ``calls`` (every
custom call's target and the transfers behind its operands), ``owners``
(for an instruction under no scope, the nearest scoped one that uses or
feeds it) and ``relayouts`` (the names of the reshapes, copies and
transposes left outside fusions). These readers lay the first device's
steady window over them.

**A gap** is what ``xplane.breakdown`` calls ``idle_gaps.inside_step``: the
time between the latest end so far and the next leaf operation's start,
inside one traced step program. ``idle_inside_ms`` is their sum a step,
walked here exactly as ``breakdown`` walks it. It has FOUR parts, which add
up to it:

* ``idle_hidden_ms``: no idle time at all. ``xplane.self_times`` classes an
  event as a leaf when no other event lies inside it, and XLA:TPU's empty
  ``custom-call.N`` (a ``ConcatBitcast`` of prefetched slices is an event of
  no length) that starts at an operation's own nanosecond sorts inside it:
  the operation is then no leaf, and its whole duration reads as a gap
  "after ``custom-call.N``". ``chip_skew.py::_swallowed`` takes such
  operations back; their time inside the gaps is this part. (Found on the
  recorded trace of ``mistral7b_c1_s4k``: its four longest inside-step gaps,
  1,198 / 77 / 46 / 46 us, are ``convert.149``, ``convert_element_type.1549``,
  ``fusion.575`` and ``fusion.593`` to the nanosecond.)

The rest, with the hidden operations put back among the leaves, is idle.
For each such gap take A, the operation that ended last before it, B, the
one that starts after it, the events of no length between them, and the
transfers of the ``Async XLA Ops`` line in flight over it:

* ``idle_collective_ms``: A, B or an event between them is a collective by
  the map's class (any but ``overlapped``), or B is the ``-done`` or the
  ``feeds`` of a collective transfer in flight over the gap;
* else ``idle_prefetch_ms``: B is a prefetch's ``-start`` or ``-done``; or
  A, B or an event between them is a custom call whose operands are
  prefetches' results; or B is the ``feeds`` of a prefetch in flight over
  the gap;
* else ``idle_unexplained_ms``.

``scope_unowned_pct`` stands beside ``scope_unnamed_pct`` (the same leaves,
the same total): the share in instructions with no scope AND no owner.
``relayout_ms`` is the time of the map's ``relayouts`` and of the
prefetches' ``-start`` / ``-done`` halves (what ROADMAP's speed item 8
summed by hand from ten stems), hidden operations included.

Everything is ``None`` (the line leaves the metric out) where there is no
trace, where the program kept no ``transfers`` (the parent commit's map), or
where the trace cannot be joined to the map (``step_map.joined``).
``tools/trace_by_scope.py`` prints :func:`laid`'s gaps one by one.
"""

import bisect
import collections
import heapq
import os

from benchmark import manifest

_HERE = os.path.dirname(os.path.abspath(__file__))
_step_map = manifest.load_python(os.path.join(_HERE, "step_map.py"))
_skew = manifest.load_python(os.path.join(_HERE, "chip_skew.py"))

CLASSES = ("collective", "prefetch", "unexplained")
PREFETCH, OVERLAPPED = "prefetch", "overlapped"


def _gaps(r, events):
    """``xplane.breakdown``'s walk over ``events`` (``(start, end, name)``,
    sorted): ``(gap start, gap end, the event that ended last, the event
    that starts)`` for each gap that lies inside one traced step."""
    out, cur_e, last = [], r.window[0], None
    for s, e, n in events:
        if s > cur_e and any(a <= cur_e and s <= b for a, b in r.steps):
            out.append((cur_e, s, last, n))
        if last is None or e > cur_e:
            cur_e, last = max(cur_e, e), n
    return out


def _rules(step_map):
    """``why(after, before, between, flying) -> (class, the custom call or
    transfer that says so)`` of one gap, by this file's rules."""
    placed, transfers = step_map["instructions"], step_map["transfers"]
    collective = {n for n, c in placed.items()
                  if c[2] and c[2] != OVERLAPPED}
    prefetches = {n for n, t in transfers.items() if t["kind"] == PREFETCH}
    halves = prefetches | {transfers[n]["done"] for n in prefetches}
    concats = {n for n, c in step_map.get("calls", {}).items()
               if any(s in prefetches for s in c["transfers"])}

    def why(after, before, between, flying):
        around = (after, before, *between)
        moving = [(n, transfers[n]) for n in flying if n in transfers]
        fed = lambda t: bool(t["feeds"]) and t["feeds"][0] == before
        for n in around:
            if n in collective:
                return "collective", n
        for n, t in moving:
            if n not in prefetches and (t["done"] == before or fed(t)):
                return "collective", n
        if before in halves:
            return PREFETCH, before
        for n in around:
            if n in concats:
                return PREFETCH, n
        for n, t in moving:
            if n in prefetches and fed(t):
                return PREFETCH, n
        return "unexplained", None
    return why


def laid(r, step_map):
    """One device's steady window (an ``xplane.Reduced``) over a step map
    that has ``transfers``: ``{"periods", "inside_ns" (the gaps inside a
    step as ``xplane.breakdown`` sums them), "hidden_ns", "by_class_ns":
    {class: ns}, "hidden": the operations an empty event hid, inside a
    step, "gaps": one record a truly idle gap}``. A record: ``start``,
    ``end``, ``after`` (A), ``before`` (B), ``between`` (the events of no
    length), ``class``, ``by`` (the call or transfer that says so) and
    ``waits_for``: for the transfers behind ``by`` (a custom call's
    operands, or the transfer itself), ``(name, bytes, ns from its start on
    the Async XLA Ops line to the gap's end)``."""
    transfers, calls = step_map["transfers"], step_map.get("calls", {})
    hidden = _skew._swallowed(r)
    as_read = sorted((s, e, n) for n, s, e in r.leaves)
    inside_ns = sum(b - a for a, b, _, _ in _gaps(r, as_read))
    timed = sorted((s, e, n) for n, s, e in list(r.leaves) + hidden
                   if e > s)
    empty = sorted((s, n) for n, s, e in r.leaves if s == e)
    starts = [s for s, _ in empty]
    # the Async XLA Ops line: by name for a transfer's start, and swept
    # beside the gaps (both run forward in time) for what is in flight
    begun = collections.defaultdict(list)
    flights = sorted((s, e, n) for n, s, e in r.in_flight)
    for s, _, n in flights:
        begun[n].append(s)
    why, nxt, flying = _rules(step_map), 0, []
    by_class, records = dict.fromkeys(CLASSES, 0.0), []
    for a, b, after, before in _gaps(r, timed):
        while nxt < len(flights) and flights[nxt][0] < b:
            heapq.heappush(flying, flights[nxt][1:])
            nxt += 1
        while flying and flying[0][0] <= a:
            heapq.heappop(flying)
        between = [n for _, n in empty[bisect.bisect_left(starts, a):
                                       bisect.bisect_right(starts, b)]]
        cls, by = why(after, before, between, [n for _, n in flying])
        behind = (calls.get(by, {}).get("transfers") or [by]) if by else []
        waits = []
        for n in behind:
            at = bisect.bisect_right(begun.get(n, ()), b)
            if n in transfers and at:
                waits.append((n, transfers[n]["bytes"], b - begun[n][at - 1]))
        by_class[cls] += b - a
        records.append({"start": a, "end": b, "after": after,
                        "before": before, "between": between,
                        "class": cls, "by": by, "waits_for": waits})
    return {"periods": r.periods, "inside_ns": inside_ns,
            "hidden_ns": inside_ns - sum(by_class.values()),
            "by_class_ns": by_class, "gaps": records,
            "hidden": [(n, s, e) for n, s, e in hidden
                       if any(a <= s and e <= b for a, b in r.steps)]}


# ---------------------------------------------------------------------------
# the per-layer metrics
# ---------------------------------------------------------------------------


def _kept():
    """The recorded step's map, where it follows the data."""
    import importlib

    try:
        mod = importlib.import_module(
            "hetu_galvatron_tpu.observability.trace_analysis")
    except ImportError:
        return None
    kept = getattr(mod, "step_scopes", None)
    found = kept().get("map") if callable(kept) else None
    return found if found and "transfers" in found else None


def _flow(facts):
    """(the first device's reduced trace, :func:`laid`'s answer, the map),
    made once a run and kept in ``facts``; ``None`` where it cannot be."""
    if "step_flow" not in facts:
        kept = _kept()
        got = _step_map.joined(facts) if kept else None
        facts["step_flow"] = got and (got[0], laid(got[0], kept), kept)
    return facts["step_flow"]


def _ms(facts, ns_of):
    got = _flow(facts)
    return None if got is None else ns_of(got[1]) / got[1]["periods"] / 1e6


def idle_inside_ms(facts):
    return _ms(facts, lambda t: t["inside_ns"])


def idle_hidden_ms(facts):
    return _ms(facts, lambda t: t["hidden_ns"])


def idle_prefetch_ms(facts):
    return _ms(facts, lambda t: t["by_class_ns"]["prefetch"])


def idle_collective_ms(facts):
    return _ms(facts, lambda t: t["by_class_ns"]["collective"])


def idle_unexplained_ms(facts):
    return _ms(facts, lambda t: t["by_class_ns"]["unexplained"])


def _inside(r, events):
    return [(n, s, e) for n, s, e in events
            if any(a <= s and e <= b for a, b in r.steps)]


def scope_unowned_pct(facts):
    got = _flow(facts)
    if got is None:
        return None
    r, _, kept = got
    placed, owners = kept["instructions"], kept["owners"]
    leaves = _inside(r, r.leaves)
    total = sum(e - s for _, s, e in leaves)
    if total <= 0:
        return None
    return 100.0 * sum(e - s for n, s, e in leaves
                       if placed[n][0] is None and n not in owners) / total


def relayout_ms(facts):
    got = _flow(facts)
    if got is None:
        return None
    r, table, kept = got
    mine = set(kept["relayouts"])
    for n, t in kept["transfers"].items():
        if t["kind"] == PREFETCH:
            mine.update((n, t["done"]))
    return sum(e - s for n, s, e in _inside(r, r.leaves) + table["hidden"]
               if n in mine) / r.periods / 1e6
