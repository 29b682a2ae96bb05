"""The readers of what the program says about itself: the phase spans of
``run_loop`` laid over the device's steps (``layer_metrics/host_phases.py``)
and the registry's set-up spans and static-memory gauges
(``layer_metrics/program_gauges.py``). Synthetic steps and spans first,
then one recorded TPU run: the ``train/*``, ``DoEnqueueProgram`` and
``tpu::System::Execute=>Done`` events of ``/host:CPU`` and device 0's
``XLA Modules`` line of the ``--trace 1`` run of ``mistral7b_c1_s4k`` on a
v5e (seed 33, PR 24; every other line and plane taken out), with the
numbers that run printed pinned."""

import gzip
import importlib.util
import os
import shutil

import pytest

from benchmark import manifest, readers, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
LM = os.path.join(manifest.ROOT, "benchmark", "layer_metrics")
GAP_METRICS = ["gap_sync_ms", "gap_lr_ms", "gap_log_ms", "gap_data_ms",
               "gap_h2d_ms", "gap_dispatch_ms", "gap_other_ms",
               "gap_unnamed_ms"]
GAUGE_METRICS = ["setup_imports_s", "setup_init_s", "setup_first_dispatch_s",
                 "setup_step_report_s", "static_hbm_GiB",
                 "static_hbm_fill_pct"]
KERNEL_METRICS = ["flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms"]
RECORDED = os.path.join(HERE, "mistral7b_c1_s4k.seed33.phases.xplane.pb.gz")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(LM, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


hp = _load("host_phases")   # the gauge readers go through readers.read_metric

MS = 1e6   # ns


def _iteration(it, t0, step_ms=100.0):
    """One iteration's spans from ``t0`` on (ns), as run_loop lays them,
    and the device's step: it starts 0.2 ms into train/dispatch and ends
    1 ms before train/sync does."""
    spans, t = [], t0
    for name, ms in (("train/data", 0.2), ("train/h2d", 1.0),
                     ("train/dispatch", 0.5), ("train/sync", step_ms + 0.7),
                     ("train/lr", 2.0), ("train/log", 1.5),
                     ("train/check", 0.6)):
        spans.append((name, t, t + ms * MS, it))
        t += ms * MS
    dispatch, sync = spans[2], spans[3]
    step = (dispatch[1] + 0.2 * MS, sync[2] - 1.0 * MS)
    return spans, step, t


def _loop(n=5, between_ms=0.0, **kw):
    spans, steps, t = [], [], 0.0
    for it in range(3, 3 + n):
        s, step, t = _iteration(it, t + between_ms * MS, **kw)
        spans += s
        steps.append(step)
    return steps, spans


def test_parts_add_up_to_the_gap_and_follow_the_spans():
    steps, spans = _loop()
    parts = hp.gap_parts_ms(steps, spans)
    gaps = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
    assert sum(parts.values()) == pytest.approx(sum(gaps) / len(gaps) / MS)
    assert parts == pytest.approx({
        "sync": 1.0, "lr": 2.0, "log": 1.5, "other": 0.6, "data": 0.2,
        "h2d": 1.0, "dispatch": 0.2, "unnamed": 0.0}, abs=1e-9)


def test_an_uncovered_stretch_is_unnamed():
    steps, spans = _loop(between_ms=0.25)
    parts = hp.gap_parts_ms(steps, spans)
    assert parts["unnamed"] == pytest.approx(0.25)
    assert sum(parts.values()) == pytest.approx(6.75)


def test_a_span_straddling_a_step_s_start_or_end_is_cut_at_it():
    # train/dispatch runs 0.5 ms, the step starts 0.2 ms into it: only the
    # 0.2 ms before the start belong to the gap; train/sync began long
    # before the step's end: only its last millisecond does
    lo, hi = 100.0 * MS, 106.5 * MS
    parts = hp.split_gap(lo, hi, [
        ("train/sync", 0.0, 101.0 * MS, 3),
        ("train/eval", 101.0 * MS, 104.0 * MS, 3),
        ("train/dispatch", 106.3 * MS, 106.8 * MS, 4)])
    assert parts["sync"] == pytest.approx(1.0 * MS)
    assert parts["other"] == pytest.approx(3.0 * MS)
    assert parts["dispatch"] == pytest.approx(0.2 * MS)
    assert parts["unnamed"] == pytest.approx(2.3 * MS)
    assert sum(parts.values()) == pytest.approx(hi - lo)


def test_overlapping_spans_still_add_up_to_the_gap():
    parts = hp.split_gap(0.0, 10.0, [("train/lr", 1.0, 6.0, 3),
                                     ("train/log", 4.0, 9.0, 3)])
    assert parts["lr"] == 5.0 and parts["log"] == 3.0
    assert sum(parts.values()) == 10.0


@pytest.mark.parametrize("why,shift", [
    ("the step starts before its train/dispatch", ("start", -1.0)),
    ("the step starts 3 ms after train/dispatch ends", ("start", 3.5)),
    ("the step ends after train/sync does", ("end", 2.0)),
])
def test_clock_check_failing_publishes_nothing(capsys, why, shift):
    steps, spans = _loop()
    s, e = steps[2]
    steps[2] = ((s + shift[1] * MS, e) if shift[0] == "start"
                else (s, e + shift[1] * MS))
    assert hp.clock_problems(steps, spans), why
    assert hp.gap_parts_ms(steps, spans) is None
    assert "clock check failed" in capsys.readouterr().out


def test_clock_check_needs_a_dispatch_and_a_sync_for_every_step():
    steps, spans = _loop()
    assert hp.clock_problems(steps, spans) == []
    assert hp.clock_problems(steps[:-1], spans)
    no_sync = [s for s in spans if not (s[0] == "train/sync" and s[3] == 5)]
    assert hp.clock_problems(steps, no_sync)


def _planes(steps, spans, early_ms, enqueue_ms=0.05, done_ms=0.2):
    """The trace of ``_loop`` as a profiler would write it when the
    device's plane runs ``early_ms`` early: every program is enqueued
    ``enqueue_ms`` before it starts and reported done ``done_ms`` after it
    ended, on the host's clock."""
    programs = [(s - early_ms * MS, e - early_ms * MS, 100 + i)
                for i, (s, e) in enumerate(steps)]
    return hp.Planes(
        spans=spans, programs=programs,
        enqueued={100 + i: s - enqueue_ms * MS
                  for i, (s, _) in enumerate(steps)},
        done=[e + done_ms * MS for _, e in steps])


@pytest.mark.parametrize("early_ms", [0.0, 0.46, 1.67])
def test_the_device_clock_is_placed_between_enqueue_and_done(early_ms):
    steps, spans = _loop()
    planes = _planes(steps, spans, early_ms)
    lower, upper = hp.offset_bounds(planes)
    assert lower == pytest.approx((early_ms - 0.05) * MS)
    assert upper == pytest.approx((early_ms + 0.2) * MS)
    # unshifted, a plane that runs early fails the check; shifted by the
    # middle of the bounds it passes and moves time only between the ends
    device = [(s, e) for s, e, _ in planes.programs]
    if early_ms > 0.3:
        assert hp.clock_problems(device, spans)
    parts = hp.gap_parts_ms(device, spans, (lower + upper) / 2)
    truth = hp.gap_parts_ms(steps, spans)
    assert sum(parts.values()) == pytest.approx(sum(truth.values()))
    for name in ("lr", "log", "other", "data", "h2d", "unnamed"):
        assert parts[name] == pytest.approx(truth[name], abs=1e-9)
    assert parts["sync"] == pytest.approx(truth["sync"] - 0.075)
    assert parts["dispatch"] == pytest.approx(truth["dispatch"] + 0.075)


@pytest.mark.parametrize("enqueue_ms,done_ms,rule,moved_ms", [
    (0.05, 0.2, "bounded", 0.075),
    # the stamps' jitter turned the interval inside out: PR 30's refused run
    # read 15.5 us, with every count paired
    (-0.0155 / 2, -0.0155 / 2, "inverted", 0.0),
    (-0.03, -0.02, "inverted", 0.005),
    # wider than MAX_OFFSET_WIDTH_NS: placed all the same, and said
    (0.9, 0.9, "wide", 0.0),
    (0.3, 1.1, "wide", 0.4),
])
def test_an_inverted_or_a_wide_interval_is_placed_at_its_middle(
        monkeypatch, capsys, enqueue_ms, done_ms, rule, moved_ms):
    """All eight parts are published and add up to the mean gap; the shift
    moves time only between the parts at a gap's ends."""
    steps, spans = _loop()
    planes = _planes(steps, spans, 1.0, enqueue_ms, done_ms)
    lower, upper = bounds = hp.offset_bounds(planes)
    assert upper - lower == pytest.approx((enqueue_ms + done_ms) * MS)
    offset, said = hp.place(bounds)
    assert said == rule
    assert offset == pytest.approx((1.0 + moved_ms) * MS)
    monkeypatch.setattr(hp, "read_planes", lambda path, device=0: planes)
    parts = hp.parts_of_trace(
        "any.xplane.pb", [(s, e) for s, e, _ in planes.programs])
    truth = hp.gap_parts_ms(steps, spans)
    assert set(parts) == set(hp.PARTS) and len(parts) == 8
    assert sum(parts.values()) == pytest.approx(sum(truth.values()))
    for name in ("lr", "log", "other", "data", "h2d", "unnamed"):
        assert parts[name] == pytest.approx(truth[name], abs=1e-9)
    assert parts["sync"] + parts["dispatch"] == pytest.approx(
        truth["sync"] + truth["dispatch"])
    assert abs(parts["sync"] - truth["sync"]) <= abs(upper - lower) / 2 / MS \
        + 1e-9
    out = capsys.readouterr().out
    assert f"rule {rule}: bounds {(upper - lower) / MS:.6f} ms apart" in out
    assert out.count("\n") == 1


@pytest.mark.parametrize("bounds,why", [
    (None, "not bounded from both sides"),
    # twice the jitter: two pairings that contradict each other
    ((1.0 * MS, 0.9 * MS), "inverted by 100.0 us, more than the stamps'"),
])
def test_no_interval_or_one_inverted_beyond_the_jitter_publishes_nothing(
        monkeypatch, capsys, bounds, why):
    offset, said = hp.place(bounds)
    assert offset is None and why in said
    steps, spans = _loop()
    planes = _planes(steps, spans, 1.0)
    monkeypatch.setattr(hp, "read_planes", lambda path, device=0: planes)
    monkeypatch.setattr(hp, "offset_bounds", lambda planes: bounds)
    assert hp.parts_of_trace("any.xplane.pb", steps) is None
    assert "no gap_* metric" in capsys.readouterr().out


def test_the_one_empty_interval_of_pr_30_is_placed_now():
    """``bounds (684410.0, 668882.0) ns from 55 programs, 55 enqueues, 55
    completions`` (PERF.md section 7): the refused run's own line."""
    assert hp.place((684410.0, 668882.0)) == (676646.0, "inverted")
    assert 684410.0 - 668882.0 < hp.STAMP_JITTER_NS < hp.MAX_OFFSET_WIDTH_NS


def test_bounds_need_both_sides_and_matching_counts():
    steps, spans = _loop()
    planes = _planes(steps, spans, 1.0)
    assert hp.offset_bounds(planes._replace(enqueued={})) is None
    assert hp.offset_bounds(planes._replace(done=planes.done[1:])) is None
    # an enqueue the trace lost is passed over, the others still bound
    fewer = dict(list(planes.enqueued.items())[1:])
    assert hp.offset_bounds(planes._replace(enqueued=fewer)) is not None


# the python readers on the facts of a run -----------------------------------

@pytest.fixture(scope="module")
def pr22_trace(tmp_path_factory):
    """PR 22's recorded trace, of a program that has no span in its loop,
    where ``facts['argv']`` says a run's trace is."""
    tdir = tmp_path_factory.mktemp("pr22")
    run = tdir / "plugins" / "profile" / "2026_09_26"
    run.mkdir(parents=True)
    path = str(run / "t.xplane.pb")
    with gzip.open(os.path.join(
            HERE, "mistral7b_c1_s4k.seed1.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return {"argv": ["x.yaml", f"profile.trace_dir={tdir}"],
            "trace": xplane.facts_of(path, chips=1)}


@pytest.mark.parametrize("name", GAP_METRICS + KERNEL_METRICS)
def test_a_program_without_the_spans_or_names_gives_nothing(pr22_trace, name):
    """What the driver sees when it lays this PR's benchmark files over
    the parent: no value, no exception (the flash readers say 0.0: the
    parent's kernels run under other names)."""
    v = readers.read_metric(name, dict(pr22_trace))
    assert v is None if name in GAP_METRICS else v == 0.0


@pytest.mark.parametrize("facts", [{}, {"argv": ["profile.trace_dir=/none"]},
                                   {"argv": [], "trace": None}])
def test_gap_readers_without_a_trace_give_nothing(facts):
    assert [readers.read_metric(n, dict(facts)) for n in GAP_METRICS] \
        == [None] * len(GAP_METRICS)


@pytest.fixture
def registry():
    from hetu_galvatron_tpu.observability.registry import (
        MetricsRegistry,
        get_registry,
        set_registry,
    )

    before = get_registry()
    yield set_registry(MetricsRegistry())
    set_registry(before)


@pytest.mark.parametrize("name", GAUGE_METRICS)
def test_a_gauge_never_written_gives_nothing_and_is_not_created(
        registry, name):
    facts = {"memory": {"per_device": [{"bytes_limit": 2 ** 34}]}}
    assert readers.read_metric(name, facts) is None
    assert registry.metrics() == []


def test_gauge_readers_read_what_the_program_wrote(registry):
    for ms in (9000.0, 400.0, 380.0):
        registry.histogram("span_ms", path="train/dispatch").observe(ms)
    registry.histogram("span_ms", path="setup/imports").observe(12500.0)
    registry.histogram("span_ms", path="setup/init").observe(5100.0)
    registry.histogram("span_ms", path="setup/step_report").observe(7400.0)
    registry.gauge("step/static_bytes", part="live_peak").set(15 * 2 ** 30)
    registry.gauge("step/static_bytes", part="temporaries").set(7 * 2 ** 30)
    facts = {"memory": {"per_device": [{"bytes_limit": 16 * 2 ** 30}]}}
    got = {n: readers.read_metric(n, facts) for n in GAUGE_METRICS}
    assert got == pytest.approx({
        "setup_imports_s": 12.5, "setup_init_s": 5.1,
        "setup_first_dispatch_s": 9.0, "setup_step_report_s": 7.4,
        "static_hbm_GiB": 15.0, "static_hbm_fill_pct": 93.75})
    assert readers.read_metric("static_hbm_fill_pct", {}) is None


def test_read_planes_finds_the_trace_annotations_of_a_profile(tmp_path):
    """``tracing.span`` to ``read_planes`` through a real ``.xplane.pb``
    (made here on the CPU: the host plane is the same on a TPU)."""
    import jax

    from hetu_galvatron_tpu.observability.registry import MetricsRegistry
    from hetu_galvatron_tpu.observability.tracing import span

    reg = MetricsRegistry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for it in (7, 8):
            for name in ("train/dispatch", "train/sync", "other/ignored"):
                with span(name, registry=reg, step=it):
                    pass
    finally:
        jax.profiler.stop_trace()
    planes = hp.read_planes(xplane.find_xplane(str(tmp_path)))
    spans = planes.spans
    # a CPU has no device plane and no TPU runtime events: no bounds
    assert planes.programs == [] and hp.offset_bounds(planes) is None
    assert [(n, step) for n, _, _, step in spans] == [
        ("train/dispatch", 7), ("train/sync", 7),
        ("train/dispatch", 8), ("train/sync", 8)]
    assert all(s <= e for _, s, e, _ in spans)
    assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))


def test_manifest_holds_with_the_new_metrics():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in GAP_METRICS + GAUGE_METRICS + KERNEL_METRICS:
        assert "workloads" not in by_name[name]     # every cell
    assert {by_name[n]["source"] for n in GAP_METRICS} == {"program_span"}
    assert by_name["gap_data_ms"]["layer"] == "data"
    assert {by_name[n]["moves"] for n in GAUGE_METRICS[:4]} == {"setup_s"}
    # the three kernel patterns split what flash_time_share_pct reads
    share = manifest.read_json(manifest.layer_metric_path(
        manifest.ROOT, "flash_time_share_pct"))["reader"]["pattern"]
    for n in KERNEL_METRICS:
        pat = manifest.read_json(manifest.layer_metric_path(
            manifest.ROOT, n))["reader"]["pattern"]
        assert pat.startswith(share)


# one recorded run on the chip ------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("phases") / "t.xplane.pb")
    with gzip.open(RECORDED) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    (dev,) = xplane.read_devices(path)
    name = xplane.step_module(dev)
    steps = [(s, e) for n, s, e in dev.modules if n == name]
    return path, steps


def test_recorded_run_the_device_plane_runs_early_by_a_bounded_constant(
        recorded):
    path, steps = recorded
    planes = hp.read_planes(path)
    assert len(steps) == 5 and len(planes.programs) == 55
    assert len(planes.enqueued) == 55 and len(planes.done) == 55
    assert [s[3] for s in planes.spans if s[0] == "train/dispatch"] \
        == [3, 4, 5, 6, 7]
    lower, upper = hp.offset_bounds(planes)
    assert lower / MS == pytest.approx(1.810, abs=1e-3)
    assert upper / MS == pytest.approx(1.833, abs=1e-3)
    # as written, every step "starts" a millisecond before the host called
    # it; placed on the host's clock it starts 0.7 to 0.85 ms into the call
    assert len(hp.clock_problems(steps, planes.spans)) == 5
    off = (lower + upper) / 2
    shifted = hp.clock_offsets([(s + off, e + off) for s, e in steps],
                               planes.spans)
    assert all(0.6 * MS < after_start < 0.9 * MS
               and after_end < 0.3 * MS and 0.5 * MS < sync_after < 1.0 * MS
               for _, after_start, after_end, sync_after in shifted)


def test_recorded_run_the_parts_are_the_ones_the_run_printed(recorded,
                                                             capsys):
    path, steps = recorded
    parts = hp.parts_of_trace(path, steps)
    assert capsys.readouterr().out == (
        "host_phases: the device's clock placed +1.821138 ms onto the "
        "host's, rule bounded: bounds 0.023087 ms apart from 55 programs, "
        "55 enqueues, 55 completions\n")
    assert parts == pytest.approx({
        "sync": 0.769825625, "lr": 5.694835, "log": 1.0965425,
        "data": 0.1597475, "h2d": 1.0028375, "dispatch": 0.562925,
        "other": 0.04856, "unnamed": 0.213744125}, rel=1e-9)
    gaps = [(b[0] - a[1]) / MS for a, b in zip(steps, steps[1:])]
    assert sum(parts.values()) == pytest.approx(sum(gaps) / 4)
    # host_gap_ms of that run (their median) was 9.597665
    assert sorted(gaps)[1:3] == pytest.approx([9.565, 9.630], abs=0.04)
    assert parts["unnamed"] < 0.1 * sum(parts.values())
