"""``benchmark/layer_metrics/chip_skew.py`` on four device planes made by
hand, with known arrivals: a collective's time cut into transfer and wait to
the nanosecond, the chips' compute beside it, the steps that publish and
the ones that do not, what an empty event hides and the reader takes back,
and the operator's table of the same (``tools/trace_by_scope.py``).

Collected in tier-1 through ``tests/observability/test_chip_skew.py``."""

import io
import os

import pytest

from benchmark import manifest, readers, xplane

METRICS = os.path.join(manifest.ROOT, "benchmark", "layer_metrics")
skew = manifest.load_python(os.path.join(METRICS, "chip_skew.py"))

US = 1_000
STEP, PERIOD = 1_000 * US, 1_200 * US
CLASSES = {
    "all-gather.1": ("moe/exchange/gather", "forward", "all-gather"),
    "fusion.2": ("moe/exchange/scatter", "forward", "reduce-scatter.fused"),
    "all-reduce.3": ("optimizer/update", "update", "all-reduce"),
    "fusion.4": ("moe/experts", "forward", None),
    "fusion.5": ("mlp", "forward", "overlapped"),
    "custom-call.6": (None, "forward", None),
}
# when each chip arrives at the three collectives of a step, in us after
# the step's start; every chip leaves one together with the others
ARRIVALS = {"all-gather.1": (100, 100, 100, 100),
            "fusion.2": (400, 437, 391, 500),
            "all-reduce.3": (700, 700, 705, 700)}
ENDS = {"all-gather.1": 150, "fusion.2": 520, "all-reduce.3": 760}


def _chip(c, steps=3, arrivals=ARRIVALS, lacks=(), hidden=()):
    """Chip ``c``'s reduced trace over ``steps`` traced steps (``steps - 1``
    whole periods): before each collective it computes (``fusion.4``) from
    the last one's end until it arrives, so a chip that arrives late was
    busy longer. ``lacks``: (step, name) pairs this chip does not hold;
    ``hidden``: (step, name) pairs that an empty ``custom-call.6`` at their
    own start keeps out of the leaves (``xplane.self_times``'s rule)."""
    leaves, selfs, spans = [], [], []
    for k in range(steps):
        t0, at = k * PERIOD, 0
        spans.append((float(t0), float(t0 + STEP)))
        if k == steps - 1:
            break               # the window ends at the last step's start
        for name in ("all-gather.1", "fusion.2", "all-reduce.3"):
            if (k, name) in lacks:
                continue
            s, e = t0 + arrivals[name][c] * US, t0 + ENDS[name] * US
            work = ("fusion.4", float(t0 + at * US), float(s))
            leaves.append(work)
            selfs.append((work[0], work[2] - work[1]))
            selfs.append((name, float(e - s)))
            if (k, name) in hidden:
                leaves.append(("custom-call.6", float(s), float(s)))
                selfs.append(("custom-call.6", 0.0))
            else:
                leaves.append((name, float(s), float(e)))
            at = ENDS[name]
        # an all-gather riding a matmul is compute
        leaves.append(("fusion.5", float(t0 + at * US),
                       float(t0 + (at + 40) * US)))
        selfs.append(("fusion.5", 40.0 * US))
    return xplane.Reduced(c, spans, (spans[0][0], spans[-1][0]), leaves,
                          selfs, [])


def _four(**kw):
    return [_chip(c, **{k: v.get(c, ()) if isinstance(v, dict) else v
                        for k, v in kw.items()}) for c in range(4)]


def _facts(reduced, monkeypatch, classes=CLASSES):
    from hetu_galvatron_tpu.observability import trace_analysis

    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: {
        "map": {"instructions": classes, "inferred": [], "tails": {}}})
    return {"trace": {"reduced": reduced}}


@pytest.mark.parametrize("name, scope_only", [
    ("fusion.2", False), ("all-reduce.3", False), ("all-gather.1", False),
    ("fusion.2", True)])
def test_transfer_and_wait_of_known_arrivals_to_the_nanosecond(
        name, scope_only):
    """An occurrence's transfer is from its latest arrival to its end, a
    chip's wait from its own arrival to the latest; by durations and by
    starts alike, since the chips end together."""
    table = skew.side_by_side(_four(), CLASSES)
    assert table["steps"] == 2 and table["ids"] == [0, 1, 2, 3]
    wanted = (skew._in_exchange if scope_only
              else lambda cls, n=name: CLASSES[n] == cls)
    transfer, wait, by_starts = skew.split(table, wanted)
    names = (("all-gather.1", "fusion.2") if scope_only else (name,))
    assert transfer == sum(
        (ENDS[n] - max(ARRIVALS[n])) * US for n in names)
    want = [sum((max(ARRIVALS[n]) - ARRIVALS[n][c]) * US for n in names)
            for c in range(4)]
    assert wait == want and by_starts == want
    if name == "all-gather.1" and not scope_only:
        assert wait == [0, 0, 0, 0]         # all arrive together


def test_wait_and_transfer_add_up_to_the_collectives_time(monkeypatch):
    """The two metrics are a cut of ``collective_all_ms`` taken as a mean
    over the chips, and the exchange's two of its own scopes' time."""
    facts = _facts(_four(), monkeypatch)
    step_map = manifest.load_python(os.path.join(METRICS, "step_map.py"))
    by_device = []
    for r in facts["trace"]["reduced"]:
        one = {"trace": {"reduced": [r]}}
        by_device.append(step_map.collective_all_ms(one))
    wait, transfer = (skew.collective_wait_ms(facts),
                      skew.collective_transfer_ms(facts))
    assert wait + transfer == pytest.approx(sum(by_device) / 4, rel=1e-9)
    assert transfer == pytest.approx((50 + 20 + 55) / 1e3)
    assert wait == pytest.approx(
        ((100 + 63 + 109 + 0) / 4 + (5 + 5 + 0 + 5) / 4) / 1e3)
    assert skew.exchange_transfer_ms(facts) == pytest.approx(70 / 1e3)
    assert skew.exchange_wait_ms(facts) == pytest.approx(68 / 1e3)
    # the chip that arrived last at the exchange computed longest: chip 3
    # worked 109 us more than chip 2 before the scatter, 5 less before the
    # all-reduce
    assert skew.chip_skew_ms(facts) == pytest.approx(104 / 1e3)
    table = facts["chip_skew"]
    assert [c / 2 for c in table["compute"]] == pytest.approx([
        (100 + 250 + 180 + 40 + d) * US for d in (0, 37, -4, 100)])
    assert skew.clock_check(table) == (0, 0)


@pytest.mark.parametrize("lacking, steps", [
    ({1: ((0, "fusion.2"),)}, 1),
    ({1: ((0, "fusion.2"), (1, "fusion.2"))}, 0),
    ({0: ((1, "all-reduce.3"),), 3: ((1, "all-reduce.3"),)}, 1)])
def test_a_step_whose_chips_hold_other_occurrences_publishes_nothing(
        lacking, steps, monkeypatch):
    """The step is left out with the reason said, the others carry the
    mean; with no step left the metrics are None, never 0."""
    said = io.StringIO()
    reduced = _four(lacks=lacking)
    table = skew.side_by_side(reduced, CLASSES, err=said)
    assert "publishes nothing" in said.getvalue()
    facts = _facts(reduced, monkeypatch)
    if not steps:
        assert table is None
        for read in (skew.collective_wait_ms, skew.collective_transfer_ms,
                     skew.chip_skew_ms, skew.exchange_wait_ms,
                     skew.exchange_transfer_ms):
            assert read(facts) is None
        return
    assert table["steps"] == steps
    whole = skew.side_by_side(_four(), CLASSES)
    assert skew.split(table) == skew.split(whole)
    assert skew.collective_transfer_ms(facts) == pytest.approx(125 / 1e3)


def test_what_an_empty_event_hid_is_taken_back(monkeypatch):
    """``xplane.self_times`` makes a collective that an event of no length
    starts inside a parent: chip 2's scatter of step 0 and chip 0's
    all-reduce of step 1 are in ``selfs`` alone. The reader holds them all
    the same, to the nanosecond, and the chips' busy time with them."""
    hidden = {2: ((0, "fusion.2"),), 0: ((1, "all-reduce.3"),)}
    reduced = _four(hidden=hidden)
    assert [len(skew._swallowed(r)) for r in reduced] == [1, 0, 1, 0]
    assert skew._swallowed(reduced[2]) == [
        ("fusion.2", 391.0 * US, 520.0 * US)]
    table, whole = (skew.side_by_side(reduced, CLASSES),
                    skew.side_by_side(_four(), CLASSES))
    assert table["steps"] == 2
    assert skew.split(table) == skew.split(whole)
    assert table["compute"] == whole["compute"]
    # a parent that holds work (a loop) is no operation to take back
    loop = xplane.Reduced(
        0, [(0.0, 100.0), (200.0, 300.0)], (0.0, 200.0),
        [("custom-call.6", 10.0, 10.0), ("fusion.4", 12.0, 60.0)],
        [("while.7", 30.0), ("custom-call.6", 0.0), ("fusion.4", 48.0)], [])
    assert skew._swallowed(loop) == []


@pytest.mark.parametrize("metric, function", [
    ("collective_wait_ms", "collective_wait_ms"),
    ("collective_transfer_ms", "collective_transfer_ms"),
    ("chip_skew_ms", "chip_skew_ms"),
    ("mellum_exchange_wait_ms", "exchange_wait_ms"),
    ("mellum_exchange_transfer_ms", "exchange_transfer_ms"),
    ("mellum_step_passes", "step_passes"),
    ("mellum_fullest_chip_pct", "fullest_chip_pct")])
def test_a_metric_has_its_file_its_entry_and_nothing_to_say_without_a_trace(
        metric, function, monkeypatch):
    declared = manifest.read_json(manifest.layer_metric_path(
        manifest.ROOT, metric))
    assert declared["what"] and declared["reader"] == {
        "kind": "python", "file": "chip_skew.py", "function": function}
    (entry,) = [m for m in manifest.load_manifest()["per_layer"]
                if m["name"] == metric]
    cells = ["mistral7b_c4_tp2dp2z3", "mellum2_c4_ep4"]
    assert entry["workloads"] == (
        cells[1:] if metric.startswith("mellum_") else cells)
    assert (entry["better"], entry["moves"]) == ("lower", "tokens_per_s")
    from hetu_galvatron_tpu.observability import trace_analysis
    from hetu_galvatron_tpu.observability.registry import (
        MetricsRegistry,
        get_registry,
        set_registry,
    )

    before = get_registry()
    set_registry(MetricsRegistry())
    try:
        # no trace; a trace and no map (the parent of PR 37); one chip; a
        # map without a collective; a registry without the gauges
        assert readers.read_metric(metric, {}) is None
        monkeypatch.setattr(trace_analysis, "step_scopes", lambda: {})
        assert readers.read_metric(
            metric, {"trace": {"reduced": _four()}}) is None
        assert readers.read_metric(metric, _facts(
            [_chip(0)], monkeypatch)) is None
        assert readers.read_metric(metric, _facts(_four(), monkeypatch, {
            n: (s, p, None) for n, (s, p, _) in CLASSES.items()})) is None
        if entry["source"] == "program_counter":
            get_registry().histogram(skew.STEP_PASSES_HISTOGRAM).observe(1.0)
            get_registry().histogram(skew.STEP_PASSES_HISTOGRAM).observe(2.0)
            for layer, pct in (("layer0", 97.5), ("layer3", 104.25)):
                get_registry().gauge(skew.FULLEST_CHIP_GAUGE,
                                     layer=layer).set(pct)
            assert readers.read_metric(metric, {}) == {
                "step_passes": 1.5, "fullest_chip_pct": 104.25}[function]
        else:
            assert readers.read_metric(
                metric, _facts(_four(), monkeypatch)) >= 0
    finally:
        set_registry(before)


def test_the_operators_table_is_the_readers(capsys):
    """``tools/trace_by_scope.py`` prints the same function's numbers, one
    row a chip, with the rows and passes ``step_map.json`` kept."""
    from tools import trace_by_scope

    counts = {"rows": {"layer0": [30.0, 33.0, 31.0, 34.0],
                       "layer1": [32.0, 32.0, 32.0, 32.0]},
              "passes": {"layer0": [0.0, 0.0, 0.0, 1.0],
                         "layer1": [0.0, 0.0, 0.0, 0.0]}}
    got = trace_by_scope.by_chip(
        _four(), {"instructions": CLASSES, "chips": counts})
    assert got["steps"] == 2
    assert [(r["chip"], r["rows"], r["passes"]) for r in got["chips"]] == [
        (0, 62.0, 0.0), (1, 65.0, 0.0), (2, 63.0, 0.0), (3, 66.0, 1.0)]
    assert [r["wait_ms"] for r in got["chips"]] == pytest.approx(
        [0.105, 0.068, 0.109, 0.005])
    assert [r["transfer_ms"] for r in got["chips"]] == pytest.approx(
        [0.125] * 4)
    assert got["reduce_scatter_ends_apart_us"] == [0.0, 0.0]
    trace_by_scope.print_by_chip(got)
    out = capsys.readouterr().out
    assert "   3     0.670     0.125     0.005     0.005        66      1" \
        in out
    # a mesh that holds its devices in another order than their ids: the
    # plane of device 1 is the group's chip 3
    moved = trace_by_scope.by_chip(_four(), {
        "instructions": CLASSES,
        "chips": {**counts, "devices": {"0": 0, "1": 3, "2": 2, "3": 1}}})
    assert [(r["chip"], r["rows"], r["passes"]) for r in moved["chips"]] \
        == [(0, 62.0, 0.0), (1, 66.0, 1.0), (2, 63.0, 0.0), (3, 65.0, 0.0)]
    # without the counts (a dense cell, an older step_map.json): dashes
    bare = trace_by_scope.by_chip(_four(), {"instructions": CLASSES})
    assert {(r["rows"], r["passes"]) for r in bare["chips"]} == {(None, None)}
    assert trace_by_scope.by_chip([_chip(0)], {"instructions": CLASSES}) \
        is None
