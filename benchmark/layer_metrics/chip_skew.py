"""The chips of a cell read side by side: a collective's time cut into
transfer and the wait for the latest chip, each chip's compute beside it,
and what the expert exchange counted chip by chip.

Every other reader of a four-chip cell reads the cell's FIRST device. A
synchronous collective ends on every chip when its latest arrival has been
served, so on a chip that arrived early its duration is the transfer AND
the wait for the others: what device 0 calls ``mellum_exchange_ms`` depends
on whether device 0 happens to be the fullest chip of the seed. Here each
collective instruction of the step map (every class but ``overlapped``) is
taken on EVERY chip, occurrence by occurrence (matched by instruction name
and order inside a traced step):

* TRANSFER of an occurrence = the least duration over the chips;
* WAIT on chip c = its duration less that least. This needs no clock shared
  between the planes. Where they do share one (they do on a v5e host: the
  ends of one occurrence on the four chips lie within a microsecond), the
  same cut by starts (``latest start - own start``) is kept beside it as the
  cross-check, with the distance between the ends of the reduce-scatters;
* a chip's COMPUTE a step = its busy time less its collectives' durations.

Over subgroups (tp2 x dp2) one instruction runs in two groups at once; the
least is still taken over all the chips, so a difference between the two
groups' transfers reads as wait.

``xplane.self_times`` classes an event as a leaf when no other event lies
inside it, and an event of no length that starts at an operation's own
nanosecond (``custom-call.N``; it happens to one operation in a hundred
on a v5e, on another chip each time) sorts inside it: the operation, a
collective among them, is then missing from that chip's ``leaves`` and its
time from that chip's busy time (1 to 2 % of a step, by chance more on one
chip than on another). :func:`_swallowed` takes such operations back from
``selfs`` (their own time is their duration, their start the empty
event's), so that the chips hold the same occurrences and a chip's compute
is not short of what the sort order hid.

A step in which they still do not publishes nothing, with the reason on
stderr; the metrics are means over the steps that do, and ``None`` (the line
leaves the metric out, never a 0) where none does, where there is no trace
or no map, where the map holds no collective (one chip), or where the
program wrote no such gauge or histogram (the parent commit).
"""

import collections
import os
import statistics
import sys

from benchmark import manifest, xplane

_HERE = os.path.dirname(os.path.abspath(__file__))
_step_map = manifest.load_python(os.path.join(_HERE, "step_map.py"))
_gauges = manifest.load_python(os.path.join(_HERE, "program_gauges.py"))

EXCHANGE_SCOPES = ("moe/exchange/gather", "moe/exchange/scatter")
STEP_PASSES_HISTOGRAM = "moe/step_passes"
FULLEST_CHIP_GAUGE = "moe/fullest_chip_pct"
# written beside them, chip by chip: what the operator's table prints
CHIP_GAUGES = ("moe/chip_rows", "moe/chip_passes")
OVERLAPPED = "overlapped"


def _swallowed(r):
    """The events that are in ``r.selfs`` and not in ``r.leaves`` because
    events of no length lie inside them, as ``(name, start, end)``: both
    lists are in the order of the trace's line, so an entry of ``selfs``
    that is not the next leaf is a parent and its first child is the next
    leaf; where every leaf that starts inside the parent's own time has no
    length (a ``while`` or a ``conditional`` holds one that has), that own
    time is its duration and the empty event's start its start."""
    selfs, leaves = getattr(r, "selfs", None) or (), r.leaves
    found, at = [], 0
    for name, self_ns in selfs:
        if at < len(leaves) and leaves[at][0] == name:
            at += 1
            continue
        if at == len(leaves) or leaves[at][1] != leaves[at][2]:
            continue
        start, nxt = leaves[at][1], at
        while nxt < len(leaves) and leaves[nxt][1] < start + self_ns \
                and leaves[nxt][1] == leaves[nxt][2]:
            nxt += 1
        if nxt == len(leaves) or leaves[nxt][1] >= start + self_ns:
            found.append((name, start, start + self_ns))
    return found


def _chip_steps(r, collective):
    """One chip's traced steps: ``(busy ns, {(name, nth inside the step):
    (start, end)})`` a whole period of the window, the collectives named in
    ``collective``."""
    events = sorted(list(r.leaves) + _swallowed(r), key=lambda ev: ev[1])
    out = []
    for a, b in r.steps[:r.periods]:
        inside = [(n, s, e) for n, s, e in events if a <= s and e <= b]
        seen, held = collections.Counter(), {}
        for n, s, e in inside:
            if n in collective:
                held[(n, seen[n])] = (s, e)
                seen[n] += 1
        out.append((xplane.union_ns((s, e) for _, s, e in inside), held))
    return out


def side_by_side(reduced, classes, err=sys.stderr):
    """The table of this file's docstring from every chip's reduced trace
    and the step map's ``instructions``; ``None`` where no traced step
    holds the same collective occurrences on every chip. Times in ns, summed
    over the ``steps`` that published: ``occurrences`` one record a
    collective occurrence ``(instruction, its (scope, phase, class), the
    durations by chip, the starts by chip, the ends by chip)``, ``busy`` and
    ``compute`` by chip."""
    collective = {n: c for n, c in classes.items()
                  if c[_step_map.COLLECTIVE]
                  and c[_step_map.COLLECTIVE] != OVERLAPPED}
    if len(reduced) < 2 or not collective:
        return None
    chips = [_chip_steps(r, collective) for r in reduced]
    occurrences, busy, steps = [], [0.0] * len(chips), 0
    for k, by_chip in enumerate(zip(*chips)):
        keys = [set(held) for _, held in by_chip]
        if not keys[0] or any(ks != keys[0] for ks in keys[1:]):
            odd = sorted(set.union(*keys) - set.intersection(*keys))
            print(f"chip_skew: traced step {k} publishes nothing: the chips "
                  f"hold {[len(ks) for ks in keys]} collective occurrences"
                  f"{', not on every chip: ' + str(odd[:4]) if odd else ''}",
                  file=err)
            continue
        steps += 1
        for c, (busy_ns, _) in enumerate(by_chip):
            busy[c] += busy_ns
        for key in sorted(keys[0], key=lambda key: by_chip[0][1][key][0]):
            spans = [held[key] for _, held in by_chip]
            occurrences.append((
                key[0], tuple(collective[key[0]]),
                [e - s for s, e in spans], [s for s, _ in spans],
                [e for _, e in spans]))
    if not steps:
        return None
    spent = [sum(o[2][c] for o in occurrences) for c in range(len(chips))]
    return {"steps": steps, "ids": [r.id for r in reduced],
            "occurrences": occurrences, "busy": busy,
            "compute": [b - t for b, t in zip(busy, spent)]}


def split(table, wanted=lambda scope_phase_class: True):
    """``(transfer, wait by chip, wait by chip cut by starts)`` in ns a
    step over the occurrences whose class ``wanted`` takes."""
    chips = range(len(table["ids"]))
    transfer, wait, by_starts = 0.0, [0.0 for _ in chips], [0.0 for _ in chips]
    for _, cls, durations, starts, _ in table["occurrences"]:
        if not wanted(cls):
            continue
        least, latest = min(durations), max(starts)
        transfer += least
        for c in chips:
            wait[c] += durations[c] - least
            by_starts[c] += latest - starts[c]
    per = lambda ns: ns / table["steps"]
    return per(transfer), [per(w) for w in wait], [per(w) for w in by_starts]


def clock_check(table):
    """``(median, largest)`` distance in ns between the earliest and the
    latest END of one reduce-scatter occurrence on the chips: near nothing
    where the planes share a clock; ``None`` without a reduce-scatter."""
    apart = [max(ends) - min(ends) for _, cls, _, _, ends
             in table["occurrences"]
             if "reduce-scatter" in cls[_step_map.COLLECTIVE]]
    return (statistics.median(apart), max(apart)) if apart else None


def by_chip_rows(table, counts=None):
    """The operator's table (``tools/trace_by_scope.py``), one row a chip in
    ms a step: compute, the collectives' transfer and wait (and the wait
    cut by starts), and from ``counts`` (``{"rows": {layer: [by chip]},
    "passes": {...}, "devices": {device id: chip}}``, what ``step_map.json``
    keeps of the last logged step's ``moe/chip_rows`` / ``moe/chip_passes``
    and of the mesh) the rows and passes it was handed, summed over the
    exchanged layers. A plane is a device id and a count's index a place
    in the ep group: without ``devices`` they are taken to be the same."""
    transfer, wait, by_starts = split(table)
    place = (counts or {}).get("devices") or {}

    def total(kind, device):
        at = place.get(str(device), device)
        if not counts or not counts.get(kind):
            return None
        return sum(by[at] for by in counts[kind].values())
    return [{"chip": chip, "compute_ms": table["compute"][c]
             / table["steps"] / 1e6,
             "transfer_ms": transfer / 1e6, "wait_ms": wait[c] / 1e6,
             "wait_by_starts_ms": by_starts[c] / 1e6,
             "rows": total("rows", chip), "passes": total("passes", chip)}
            for c, chip in enumerate(table["ids"])]


# ---------------------------------------------------------------------------
# the per-layer metrics
# ---------------------------------------------------------------------------


def _table(facts):
    """Made once a run and kept in ``facts``, as ``step_map.joined``."""
    if "chip_skew" not in facts:
        trace, classes = facts.get("trace"), _step_map._step_map()
        facts["chip_skew"] = (
            side_by_side(trace["reduced"], classes)
            if trace and classes else None)
    return facts["chip_skew"]


def _in_exchange(cls):
    return cls[_step_map.SCOPE] in EXCHANGE_SCOPES


TRANSFER, WAIT = 0, 1


def _split_ms(facts, part, wanted=lambda cls: True):
    """The transfer, or the mean over the chips of the wait, in ms a step
    over the occurrences ``wanted`` takes; nothing where there is none."""
    table = _table(facts)
    if table is None or not any(wanted(o[1]) for o in table["occurrences"]):
        return None
    transfer, wait, _ = split(table, wanted)
    return (transfer, statistics.fmean(wait))[part] / 1e6


def collective_transfer_ms(facts):
    return _split_ms(facts, TRANSFER)


def collective_wait_ms(facts):
    """The mean over the chips; with ``collective_transfer_ms`` it adds up
    to ``collective_all_ms`` taken as a mean over the chips."""
    return _split_ms(facts, WAIT)


def exchange_transfer_ms(facts):
    return _split_ms(facts, TRANSFER, _in_exchange)


def exchange_wait_ms(facts):
    return _split_ms(facts, WAIT, _in_exchange)


def chip_skew_ms(facts):
    """The busiest chip's compute a step less the idlest chip's."""
    table = _table(facts)
    if table is None:
        return None
    return (max(table["compute"]) - min(table["compute"])) \
        / table["steps"] / 1e6


def step_passes(facts):
    """The mean of histogram ``moe/step_passes`` over the run's logged
    steps: the counted passes a step waited for, every exchanged layer's
    fullest chip's added up."""
    h = _gauges.written(STEP_PASSES_HISTOGRAM)
    return None if h is None else h.total / h.count


def fullest_chip_pct(facts):
    """The largest ``moe/fullest_chip_pct{layer}`` over the exchanged
    layers at the last logged step: the fullest chip's routes over its
    first chunk's rows; past 100 it took a pass."""
    from hetu_galvatron_tpu.observability.registry import get_registry

    found = [m.value for m in get_registry().metrics()
             if m.name == FULLEST_CHIP_GAUGE]
    return max(found) if found else None
