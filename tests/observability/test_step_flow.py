"""``benchmark/layer_metrics/step_flow.py`` on a steady window made by hand:
one gap a class (a hidden operation, a collective, a prefetch by each of its
three rules, an unexplained one), an event of no length between the two
operations, the parts adding up to what ``xplane.breakdown`` calls the idle
inside a step, nothing to say without a trace or for a map that follows no
data, the operator's table of the same (``tools/trace_by_scope.py``), and
the recorded trace of ``mistral7b_c1_s4k``, whose four longest gaps are
operations that ran."""

import gzip
import json
import os
import shutil

import pytest

from benchmark import manifest, readers, xplane
from hetu_galvatron_tpu.observability import trace_analysis
from tools import trace_by_scope

pytestmark = pytest.mark.observability

METRICS = os.path.join(manifest.ROOT, "benchmark", "layer_metrics")
flow = manifest.load_python(os.path.join(METRICS, "step_flow.py"))

MS = 1e6
NAMES = ("idle_inside_ms", "idle_prefetch_ms", "idle_collective_ms",
         "idle_unexplained_ms", "scope_unowned_pct", "relayout_ms",
         "idle_hidden_ms")

_T = lambda done, kind, feeds, size=1 << 20: {
    "done": done, "kind": kind, "bytes": size, "space": "S(1)",
    "from": None, "feeds": feeds}
STEP_MAP = {
    "instructions": {
        "fusion.1": ("mlp", "forward", None),
        "copy-done.1": (None, "forward", None),
        "fusion.2": ("mlp", "forward", None),
        "custom-call.7": (None, "forward", None),
        "fusion.7": ("mlp", "backward", None),
        "all-gather.1": ("attn/qkv_proj", "backward", "all-gather"),
        "fusion.9": ("head", "forward", None),
        "custom-call.8": (None, "forward", None),
        "fusion.10": ("norm", "forward", None),
        "fusion.11": ("mlp", "forward", "overlapped"),
        "fusion.20": ("mlp", "forward", None),
        "copy.30": (None, "forward", None),
        "fusion.30": ("attn/out_proj", "forward", None),
        "convert.40": (None, "other", None)},
    "inferred": [], "tails": {},
    "transfers": {
        "copy-start.1": _T("copy-done.1", "prefetch",
                           ("fusion.2", "mlp", "forward")),
        "slice-start.2": _T("slice-done.2", "prefetch",
                            ("fusion.7", "mlp", "backward"), 3 << 20),
        "slice-start.3": _T("slice-done.3", "prefetch",
                            ("fusion.7", "mlp", "backward"), 3 << 20),
        "copy-start.9": _T("copy-done.9", "prefetch",
                           ("fusion.20", "mlp", "forward")),
        "async-collective-start.2": _T(
            "async-collective-done.2", "all-gather",
            ("fusion.30", "attn/out_proj", "forward"))},
    "calls": {"custom-call.7": {
        "target": "ConcatBitcast",
        "transfers": ["slice-start.2", "slice-start.3"]},
        "custom-call.8": {"target": "ConcatBitcast", "transfers": []}},
    "owners": {"copy-done.1": ("mlp", "forward", "user", 1),
               "copy.30": ("attn/out_proj", "forward", "user", 1),
               "custom-call.7": ("mlp", "backward", "user", 1)},
    "relayouts": ["copy.30"]}

# one step's leaves (ms after its start), the gaps between them named by
# what they are, and what the Async XLA Ops line holds
LEAVES = [
    ("fusion.1", 0.0, 1.0),
    # 0.5 ms: before a prefetch's -done
    ("copy-done.1", 1.5, 1.6), ("fusion.2", 1.6, 2.0),
    # 0.4 ms: an empty ConcatBitcast of prefetched slices between A and B
    ("custom-call.7", 2.2, 2.2), ("fusion.7", 2.4, 3.0),
    # 0.2 ms: nothing the map knows
    ("fusion.9", 3.2, 3.5),
    # 0.3 ms: before a collective
    ("all-gather.1", 3.8, 4.0),
    # 0.6 ms that is no gap: fusion.10 ran, custom-call.8 at its own start
    ("custom-call.8", 4.0, 4.0), ("fusion.11", 4.6, 5.0),
    # 0.25 ms: before what a prefetch in flight feeds
    ("fusion.20", 5.25, 5.5), ("copy.30", 5.5, 5.6),
    # 0.15 ms: before what a collective transfer in flight feeds
    ("fusion.30", 5.75, 6.0), ("convert.40", 6.0, 6.1)]
HIDDEN = ("fusion.10", 0.6)
IN_FLIGHT = [("slice-start.2", 0.2, 2.1), ("slice-start.3", 0.3, 2.3),
             ("copy-start.9", 4.9, 5.2),
             ("async-collective-start.2", 5.55, 5.7)]
GAPS = {"prefetch": 0.5 + 0.4 + 0.25, "collective": 0.3 + 0.15,
        "unexplained": 0.2, "hidden": 0.6}
PERIOD, STEP = 12.0, 10.0


def _reduced(periods=2):
    ns = lambda ms: float(round(ms * MS))   # (whole nanoseconds, as traced)
    leaves, selfs, in_flight = [], [], []
    for k in range(periods):
        at = k * PERIOD
        for n, s, e in LEAVES:
            if n == "custom-call.8":    # (the parent comes first on a line)
                selfs.append((HIDDEN[0], ns(HIDDEN[1])))
            leaves.append((n, ns(at + s), ns(at + e)))
            selfs.append((n, ns(at + e) - ns(at + s)))
        in_flight += [(n, ns(at + s), ns(at + e)) for n, s, e in IN_FLIGHT]
    steps = [(ns(k * PERIOD), ns(k * PERIOD + STEP))
             for k in range(periods + 1)]
    return xplane.Reduced(0, steps, (steps[0][0], steps[-1][0]), leaves,
                          selfs, in_flight)


def _facts(monkeypatch, step_map=STEP_MAP, reduced=None):
    monkeypatch.setattr(trace_analysis, "step_scopes",
                        lambda: {"map": step_map})
    return {"trace": {"reduced": [reduced or _reduced()]}}


@pytest.mark.parametrize("before,cls,by", [
    ("copy-done.1", "prefetch", "copy-done.1"),
    ("fusion.7", "prefetch", "custom-call.7"),
    ("fusion.20", "prefetch", "copy-start.9"),
    ("all-gather.1", "collective", "all-gather.1"),
    ("fusion.30", "collective", "async-collective-start.2"),
    ("fusion.9", "unexplained", None),
])
def test_a_gap_is_put_down_to_what_the_device_waited_for(before, cls, by):
    table = flow.laid(_reduced(), STEP_MAP)
    found = [g for g in table["gaps"] if g["before"] == before]
    assert len(found) == 2 and {(g["class"], g["by"]) for g in found} == {
        (cls, by)}
    # the operation hidden behind custom-call.8 is no gap and no B
    assert not any(g["before"] == "fusion.11" for g in table["gaps"])
    assert [h[0] for h in table["hidden"]] == ["fusion.10"] * 2


def test_an_event_of_no_length_lies_between_the_two_operations():
    """A is the operation that ended, not the empty event after it; the
    gap is one, not two; and what the call concatenates is what it waits
    for, from each transfer's start on the Async XLA Ops line."""
    (g, _) = [g for g in flow.laid(_reduced(), STEP_MAP)["gaps"]
              if g["before"] == "fusion.7"]
    assert (g["after"], g["between"]) == ("fusion.2", ["custom-call.7"])
    assert g["end"] - g["start"] == pytest.approx(0.4 * MS)
    assert g["waits_for"] == [
        ("slice-start.2", 3 << 20, pytest.approx(2.2 * MS)),
        ("slice-start.3", 3 << 20, pytest.approx(2.1 * MS))]


def test_the_parts_add_up_to_what_breakdown_calls_idle_inside_a_step(
        monkeypatch):
    facts = _facts(monkeypatch)
    r = facts["trace"]["reduced"][0]
    got = {n: getattr(flow, n)(facts) for n in NAMES}
    idle = dict(map(tuple, xplane.breakdown(r)["idle_gaps"]))
    assert got["idle_inside_ms"] == pytest.approx(
        idle["inside_step_total"] * 1e3 / r.periods)
    assert got["idle_inside_ms"] == pytest.approx(sum(GAPS.values()))
    for part in ("prefetch", "collective", "unexplained", "hidden"):
        assert got[f"idle_{part}_ms"] == pytest.approx(GAPS[part])
    assert got["idle_inside_ms"] == pytest.approx(
        sum(got[f"idle_{p}_ms"] for p in GAPS), abs=1e-9)
    # convert.40 alone has no scope and no owner; the total is the leaves'
    # as scope_unnamed_pct takes it
    leaf_ms = sum(e - s for _, s, e in LEAVES)
    assert got["scope_unowned_pct"] == pytest.approx(100 * 0.1 / leaf_ms)
    step_map = manifest.load_python(os.path.join(METRICS, "step_map.py"))
    assert got["scope_unowned_pct"] < step_map.scope_unnamed_pct(facts)
    # copy.30 (a relayout) and copy-done.1 (a prefetch's half)
    assert got["relayout_ms"] == pytest.approx(0.1 + 0.1)


def test_nothing_is_said_without_a_trace_or_a_map_that_follows_the_data(
        monkeypatch):
    before = {k: STEP_MAP[k] for k in ("instructions", "inferred", "tails")}
    stranger = dict(STEP_MAP, instructions={"fusion.1": (None, "other",
                                                         None)})
    for facts in ({}, {"trace": None}, _facts(monkeypatch, before),
                  _facts(monkeypatch, stranger)):
        assert [getattr(flow, n)(facts) for n in NAMES] == [None] * 7
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: {})
    assert flow.idle_inside_ms({"trace": {"reduced": [_reduced()]}}) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_metric_has_its_file_its_entry_and_its_reader(name, monkeypatch):
    with open(os.path.join(METRICS, name + ".json")) as f:
        assert json.load(f)["reader"] == {
            "kind": "python", "file": "step_flow.py", "function": name}
    (entry,) = [m for m in manifest.load_manifest()["per_layer"]
                if m["name"] == name]
    assert (entry["layer"], entry["moves"], entry["better"],
            entry["source"]) == ("step program", "tokens_per_s", "lower",
                                 "device_trace")
    assert entry.get("workloads") == (
        ["mistral7b_c4_tp2dp2z3", "mellum2_c4_ep4"]
        if name == "idle_collective_ms" else None)
    assert readers.read_metric(name, _facts(monkeypatch)) == pytest.approx(
        getattr(flow, name)(_facts(monkeypatch)))
    assert readers.read_metric(name, {}) is None


def test_the_tool_prints_the_owner_and_what_each_gap_waits_for(capsys):
    step_map = json.loads(json.dumps(STEP_MAP))
    t = trace_by_scope.join(_reduced(), step_map)
    assert t["idle_inside_ms"] == pytest.approx({
        "all": sum(GAPS.values()), **GAPS})
    by_name = {row["instruction"]: row for row in t["unnamed"]}
    assert (by_name["copy.30"]["owner"], by_name["copy.30"]["via"],
            by_name["copy.30"]["hops"]) == ("attn/out_proj", "user", 1)
    assert by_name["convert.40"]["owner"] is None
    gaps = {g["before"]: g for g in t["gaps"]}
    assert {b: g["class"] for b, g in gaps.items()} == {
        "copy-done.1": "prefetch", "fusion.7": "prefetch",
        "fusion.20": "prefetch", "all-gather.1": "collective",
        "fusion.10": "hidden"}      # (the two under 0.2 ms are not listed)
    assert gaps["fusion.7"]["target"] == "ConcatBitcast"
    assert gaps["fusion.7"]["waits_for"] == [
        {"transfer": "slice-start.2", "bytes": 3 << 20,
         "us_from_its_start": pytest.approx(2200.0)},
        {"transfer": "slice-start.3", "bytes": 3 << 20,
         "us_from_its_start": pytest.approx(2100.0)}]
    assert gaps["fusion.10"]["mean_ms"] == pytest.approx(0.6)
    trace_by_scope.print_tables(t)
    out = capsys.readouterr().out
    assert "owner attn/out_proj/forward via user, 1 hops" in out
    assert "convert.40" in out and "no owner" in out
    assert ("0.400 ms x 2  prefetch  after fusion.2 [mlp/forward], before "
            "fusion.7 [mlp/backward]; by custom-call.7 (ConcatBitcast); "
            "slice-start.2 3.15 MB 2200 us; slice-start.3 3.15 MB 2100 us"
            ) in out
    assert "hidden    no gap: fusion.10 [norm/forward] ran" in out
    # a map written before the data was followed still prints its gaps
    old = {k: step_map[k] for k in ("instructions", "inferred", "tails")}
    t = trace_by_scope.join(_reduced(), old)
    assert {g["class"] for g in t["gaps"]} == {
        "collective", "unexplained", "hidden"}
    assert all(row["owner"] is None for row in t["unnamed"])


def test_the_longest_gaps_of_the_recorded_trace_are_operations_that_ran(
        tmp_path):
    """``mistral7b_c1_s4k`` on a v5e (seed 1, PR 22; no map was kept then,
    so every true gap is unexplained): of the 1.517 ms that ``breakdown``
    calls idle inside the four steps, 1.423 are nineteen operations behind
    an empty ``custom-call.N``; the longest gap, 1,197.8 us "after
    custom-call.8", is ``convert.149``."""
    path = str(tmp_path / "t.xplane.pb")
    with gzip.open(os.path.join(
            manifest.ROOT, "benchmark", "tests",
            "mistral7b_c1_s4k.seed1.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    trace = xplane.facts_of(path, chips=1)
    table = flow.laid(trace["reduced"][0], {
        "instructions": {}, "transfers": {}, "calls": {}})
    idle = dict(map(tuple, trace["breakdown"]["idle_gaps"]))
    assert table["inside_ns"] == pytest.approx(
        idle["inside_step_total"] * 1e9)
    assert table["hidden_ns"] == pytest.approx(1422532.0)
    assert table["by_class_ns"] == pytest.approx({
        "collective": 0.0, "prefetch": 0.0, "unexplained": 94897.0})
    longest = max(table["hidden"], key=lambda h: h[2] - h[1])
    assert (longest[0], longest[2] - longest[1]) == ("convert.149",
                                                      1197798.0)
    assert max(g["end"] - g["start"] for g in table["gaps"]) < 8000.0
