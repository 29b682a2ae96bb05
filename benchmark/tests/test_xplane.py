"""The trace reducer on hand-made events; the recorded trace has a test of
its own (test_recorded_trace.py)."""

import pytest

from benchmark import xplane

US = 1000.0


def _dev():
    """Two steps of one program, 100 us each, 20 us apart; inside each a
    ``while`` around two kernels and a collective that half overlaps."""
    ops, modules = [], []
    for k in range(3):
        t = k * 120 * US
        modules.append(("jit_step(123)", t, t + 100 * US))
        ops += [
            ("while.1", t, t + 90 * US),
            ("fusion.1", t + 0 * US, t + 30 * US),
            ("flash_kernel.3", t + 30 * US, t + 50 * US),
            ("all-gather.7", t + 40 * US, t + 70 * US),
            ("fusion.2", t + 80 * US, t + 90 * US),
            ("copy.5", t + 95 * US, t + 100 * US),
        ]
    modules.append(("jit_other(9)", 400 * US, 401 * US))
    d = xplane.DeviceTrace(id=0)
    d.ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    d.modules = sorted(modules, key=lambda e: (e[1], -e[2]))
    return d


def test_union_and_self_times():
    assert xplane.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    st = xplane.self_times([("p", 0, 100), ("a", 0, 30), ("b", 50, 70),
                            ("q", 100, 110)])
    assert [(n, s, leaf) for n, _, _, s, leaf in st] == [
        ("p", 50, False), ("a", 30, True), ("b", 20, True), ("q", 10, True)]


def test_steady_window_steps_busy_and_gaps():
    r = xplane.reduce_device(_dev())
    assert xplane.step_module(_dev()) == "jit_step(123)"
    assert r.periods == 2 and r.window == (0.0, 240 * US)
    # leaves per step: 0-30, 30-50, 40-70, 80-90, 95-100 -> 85 us busy
    assert r.busy_s == pytest.approx(2 * 85e-6)
    assert r.window_s == pytest.approx(240e-6)
    assert xplane.host_gaps_ns(r) == [20 * US, 20 * US]
    assert xplane.matching_ns(r, r"^flash_kernel") == 2 * 20 * US
    # the all-gather runs 40-70; another op runs until 50: 20 us exposed
    assert xplane.exposed_ns(r, r"^all-gather") == pytest.approx(2 * 20 * US)
    b = xplane.breakdown(r)
    assert b["device_ops"][0][0] == "fusion"
    assert dict(map(tuple, b["device_ops"]))["all-gather"] == pytest.approx(60e-6)
    gaps = dict(map(tuple, b["idle_gaps"]))
    assert gaps["between_steps_total"] == pytest.approx(40e-6)
    assert gaps["inside_step_total"] == pytest.approx(2 * 15e-6)


def test_stem_drops_the_numbering():
    assert xplane.stem("fusion.123") == "fusion"
    assert xplane.stem("all-gather-start.4.clone") == "all-gather-start"
    assert xplane.stem("%while.2 = (s32[], f32[8]) while(...)") == "while"


def test_too_short_a_trace_is_an_error():
    d = _dev()
    d.modules = d.modules[:1]
    with pytest.raises(ValueError, match="steady window needs two"):
        xplane.reduce_device(d)
