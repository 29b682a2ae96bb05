"""Unit coverage for the self-calibrating cost model
(observability/calibration.py): the persistent residual store (atomic
O_APPEND batches, torn-line tolerance, fingerprint isolation), the α-β
re-fitter's degenerate inputs (single point, zero size variance, negative
slope) and robust regression, profile round-trips through the
read_alpha_beta parsers with calibration_meta provenance, the stored-plan
re-pricer's hand-checked arithmetic, and the plan-regret sentinel."""

import io
import json
import os
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import pytest

from hetu_galvatron_tpu.core.cost_model.cost import reprice_stored_plan_ms
from hetu_galvatron_tpu.core.search_engine.profiles import (
    merge_calibrated_profile,
    read_alpha_beta,
    read_alpha_beta_algos,
    read_profile_provenance,
)
from hetu_galvatron_tpu.observability.calibration import (
    META_KEY,
    ResidualStore,
    calibration_points,
    drift_score,
    evaluate_plan_regret,
    fingerprint_key,
    hardware_fingerprint,
    plan_spec_from_hpc,
    refit_profile,
    run_calibration,
    write_calibrated_profile,
)
from hetu_galvatron_tpu.observability.recorder import FlightRecorder
from hetu_galvatron_tpu.observability.registry import MetricsRegistry
from hetu_galvatron_tpu.observability.sinks import JsonlSink

pytestmark = pytest.mark.observability

FP_A = {"device": "cpu", "world": 8, "mesh": [2, 2, 2]}
FP_B = {"device": "TPU v4", "world": 8, "mesh": [2, 2, 2]}


def _pt(group="2_1", alg="flat", mb=4.0, ms=1.0, **kw):
    return {"collective": "allreduce", "group": group, "alg": alg,
            "mb": mb, "ms": ms, **kw}


# ---------------------------------------------------------------------------
# fingerprint
# ---------------------------------------------------------------------------


def test_hardware_fingerprint_and_key():
    fp = hardware_fingerprint(None, world=8, device_kind="TPU v4")
    assert fp == {"device": "TPU v4", "world": 8, "mesh": []}
    assert fingerprint_key(fp) == "TPU-v4_w8_nomesh"
    layers = [SimpleNamespace(tp_size=2, dp_size=2)]
    hpc = SimpleNamespace(layers=layers, pp_deg=2, world_size=8)
    fp = hardware_fingerprint(hpc, device_kind="cpu")
    assert fp == {"device": "cpu", "world": 8, "mesh": [2, 2, 2]}
    assert fingerprint_key(fp) == "cpu_w8_2x2x2"


# ---------------------------------------------------------------------------
# persistent residual store
# ---------------------------------------------------------------------------


def test_store_roundtrip_and_fingerprint_isolation(tmp_path):
    store = ResidualStore(str(tmp_path / "residuals.jsonl"))
    assert store.load() == []  # missing file is empty, not an error
    assert store.append([_pt(ms=1.0), _pt(ms=2.0)], fingerprint=FP_A,
                        run_id="r0") == 2
    assert store.append([_pt(ms=9.0)], fingerprint=FP_B) == 1
    everything = store.load()
    assert len(everything) == 3
    assert all("t" in p and "fp" in p for p in everything)
    assert everything[0]["run"] == "r0"
    # a v4 curve must never be refit from cpu residuals (and vice versa)
    mine = store.load(fingerprint=FP_A)
    assert [p["ms"] for p in mine] == [1.0, 2.0]
    assert store.skipped == 0
    assert [p["ms"] for p in store.load(fingerprint=FP_B)] == [9.0]


def test_store_skips_torn_and_corrupt_lines(tmp_path, capsys):
    path = tmp_path / "residuals.jsonl"
    store = ResidualStore(str(path))
    store.append([_pt(ms=1.0)], fingerprint=FP_A)
    with open(path, "a") as f:
        f.write("[1, 2, 3]\n")              # parseable but not a record
        f.write('{"collective": "allredu')  # torn mid-write crash line
    pts = store.load(fingerprint=FP_A)
    assert [p["ms"] for p in pts] == [1.0]
    assert store.skipped == 2
    assert "skipped 2" in capsys.readouterr().err
    # the next batch's leading newline terminates the torn tail, so only
    # the torn line itself stays lost — not the new batch's first record
    store.append([_pt(ms=3.0)], fingerprint=FP_A)
    assert [p["ms"] for p in store.load(fingerprint=FP_A)] == [1.0, 3.0]
    assert store.skipped == 2


def test_store_concurrent_appends_interleave_whole_lines(tmp_path):
    path = str(tmp_path / "residuals.jsonl")

    def worker(i):
        # each call opens its own O_APPEND descriptor, like concurrent
        # supervisor restarts sharing one store
        ResidualStore(path).append(
            [_pt(ms=float(i), run=i) for _ in range(5)], fingerprint=FP_A)

    with ThreadPoolExecutor(max_workers=8) as ex:
        list(ex.map(worker, range(40)))
    store = ResidualStore(path)
    pts = store.load(fingerprint=FP_A)
    assert store.skipped == 0  # no torn interior lines
    assert len(pts) == 200


def test_jsonl_sink_concurrent_flushes_stay_parseable(tmp_path):
    """The event-stream JSONL gets the same one-write O_APPEND discipline
    (a calibration sidecar and a training process may share a stream)."""
    path = str(tmp_path / "metrics.jsonl")

    def worker(i):
        sink = JsonlSink(path)
        for j in range(20):
            sink.write({"kind": "event", "name": "e", "data": {"i": i,
                                                               "j": j}})
            sink.flush()
        sink.close()

    with ThreadPoolExecutor(max_workers=8) as ex:
        list(ex.map(worker, range(8)))
    records = [json.loads(l) for l in open(path)]
    assert len(records) == 160
    assert all(r["kind"] == "event" for r in records)


def test_jsonl_sink_lazy_creation(tmp_path):
    path = tmp_path / "sub" / "metrics.jsonl"
    sink = JsonlSink(str(path))
    sink.flush()
    sink.close()
    assert not path.exists()  # nothing emitted -> no artifact


# ---------------------------------------------------------------------------
# residual extraction from an audit table
# ---------------------------------------------------------------------------


def _model():
    return SimpleNamespace(seq_length=8, hidden_size=32,
                           num_attention_heads=2, head_dim=16, kv_heads=2,
                           hidden_act="silu", ffn_dim=64)


def _hpc(pp=2, tp=2, dp=2, sp=False, ckpt=False, layers=2):
    mk = lambda: SimpleNamespace(  # noqa: E731 — local fixture factory
        tp_size=tp, dp_size=dp, cp_size=1, sp=sp, checkpoint=ckpt,
        tp_consecutive=True)
    return SimpleNamespace(layers=[mk() for _ in range(layers)],
                           pp_deg=pp, chunks=2, global_bsz=8, world_size=8)


def test_calibration_points_tp_dp_arithmetic():
    from hetu_galvatron_tpu.observability.telemetry import layer_param_mb

    table = {"rows": [
        {"component": "tp", "measured_ms": 6.0, "predicted_ms": 3.0},
        {"component": "tp[ring_ici]", "predicted_ms": 3.0, "chosen": True},
        {"component": "tp[flat]", "predicted_ms": 4.0},
        {"component": "dp", "measured_ms": 2.0, "predicted_ms": 1.0},
    ]}
    pts = calibration_points(table, _hpc(), _model(),
                             mixed_precision=False)
    by = {(p["group"], p["alg"]): p for p in pts}
    assert set(by) == {("2_1", "ring_ici"), ("2_0", "flat")}
    # tp: lbsz=8//2//2=2, act = 2*8*32*4B = 0.001953125 MB; per-layer
    # weight 6*chunks*0.5/pp = 3 messages, two identical layers -> one
    # group of weight 6, so per-message ms = 6.0/6
    tp = by[("2_1", "ring_ici")]
    assert tp["mb"] == pytest.approx(2 * 8 * 32 * 4 / 2**20)
    assert tp["w"] == pytest.approx(6.0)
    assert tp["ms"] == pytest.approx(1.0)
    # dp: sdp=2, consec=0 (tp>1), grad = param_mb/2 at fp32; weight
    # 1/pp per layer -> 1.0 total, per-ring ms = 2.0/1.0
    dp = by[("2_0", "flat")]
    assert dp["mb"] == pytest.approx(layer_param_mb(_model()) / 2)
    assert dp["w"] == pytest.approx(1.0)
    assert dp["ms"] == pytest.approx(2.0)


def test_drift_score_excludes_decomposition_rows():
    table = {"rows": [
        {"component": "tp", "measured_ms": 3.0, "predicted_ms": 2.0},
        {"component": "dp", "measured_ms": 1.0, "predicted_ms": 1.0},
        {"component": "tp[ring_ici]", "measured_ms": 99.0,
         "predicted_ms": 1.0},
        {"component": "bubble", "predicted_frac": 0.5},  # no time pred
    ]}
    assert drift_score(table) == pytest.approx(1.0 / 3.0)
    assert drift_score({"rows": []}) is None


# ---------------------------------------------------------------------------
# α-β re-fitter
# ---------------------------------------------------------------------------

PRIOR = {"allreduce_size_2_consec_1_alpha_ms": 1.0,
         "allreduce_size_2_consec_1_beta_mb_per_ms": 1.0,
         "allreduce_size_2_consec_1_alg_ring_lvl_ici_alpha_ms": 2.0,
         "allreduce_size_2_consec_1_alg_ring_lvl_ici_beta_mb_per_ms": 2.0}


def test_refit_single_point_scale_fallback():
    # one production size can't support a regression, but it CAN rescale
    # the prior: r = 1.0 / (1 + 4/1) = 0.2 -> α·r, β/r
    cfg, meta = refit_profile([_pt(mb=4.0, ms=1.0)], prior=PRIOR)
    assert cfg["allreduce_size_2_consec_1_alpha_ms"] == pytest.approx(0.2)
    assert cfg["allreduce_size_2_consec_1_beta_mb_per_ms"] == \
        pytest.approx(5.0)
    assert meta["curves"]["2_1/flat"] == {"points": 1, "method": "scale"}
    assert meta["source"] == "runtime-calibrated"


def test_refit_single_point_without_prior_skips():
    cfg, meta = refit_profile([_pt(mb=4.0, ms=1.0)], prior=None)
    assert cfg == {}
    assert meta["curves"] == {}


def test_refit_scale_ratio_is_clamped():
    # measured 1000x under the prior: the posterior moves hard toward the
    # measurement but a single window may not rescale beyond 20x
    cfg, _ = refit_profile([_pt(mb=4.0, ms=0.005)], prior=PRIOR)
    assert cfg["allreduce_size_2_consec_1_alpha_ms"] == pytest.approx(0.05)


def test_refit_zero_size_variance_falls_back_to_scale():
    # many points, one message size: no spread -> regression refused even
    # above min_points, scale fallback over all of them
    pts = [_pt(mb=4.0, ms=1.0 + 0.01 * i) for i in range(6)]
    cfg, meta = refit_profile(pts, prior=PRIOR)
    assert meta["curves"]["2_1/flat"]["method"] == "scale"
    assert meta["curves"]["2_1/flat"]["points"] == 6
    assert cfg["allreduce_size_2_consec_1_alpha_ms"] < 1.0


def test_refit_negative_slope_falls_back_to_scale():
    # ms DECREASING with size: fit_alpha_beta's degenerate-slope guard
    # (PR 13) rejects the regression; the prior-anchored scale posterior
    # still absorbs the level shift
    pts = [_pt(mb=m, ms=s) for m, s in
           [(1.0, 4.0), (2.0, 3.0), (4.0, 2.0), (8.0, 1.0)]]
    cfg, meta = refit_profile(pts, prior=PRIOR)
    assert meta["curves"]["2_1/flat"]["method"] == "scale"
    assert "allreduce_size_2_consec_1_alpha_ms" in cfg
    # ...and with no prior to rescale, the curve is skipped, not invented
    cfg2, meta2 = refit_profile(pts, prior=None)
    assert cfg2 == {}


def test_refit_regression_recovers_truth_and_drops_outlier():
    alpha, beta = 0.05, 250.0
    pts = [_pt(mb=m, ms=alpha + m / beta)
           for m in (1.0, 2.0, 4.0, 8.0, 16.0, 32.0)]
    pts.append(_pt(mb=32.0, ms=10.0))  # one wild straggler
    cfg, meta = refit_profile(pts, prior=None)
    assert cfg["allreduce_size_2_consec_1_alpha_ms"] == \
        pytest.approx(alpha, rel=1e-3)
    assert cfg["allreduce_size_2_consec_1_beta_mb_per_ms"] == \
        pytest.approx(beta, rel=1e-3)
    assert meta["curves"]["2_1/flat"]["method"] == "regression"
    # the MAD pass dropped the straggler (and at most one truth point the
    # outlier-biased first fit also pushed past the cut)
    assert 5 <= meta["curves"]["2_1/flat"]["points"] <= 6


def test_refit_per_algorithm_curve_lands_in_algos_namespace():
    pts = [_pt(alg="ring_ici", mb=4.0, ms=1.0)]
    cfg, meta = refit_profile(pts, prior=PRIOR)
    # prior ring_ici predicts 2 + 4/2 = 4 -> r = 0.25
    assert cfg == {
        "allreduce_size_2_consec_1_alg_ring_lvl_ici_alpha_ms":
            pytest.approx(0.5),
        "allreduce_size_2_consec_1_alg_ring_lvl_ici_beta_mb_per_ms":
            pytest.approx(8.0)}
    assert meta["curves"] == {"2_1/ring_ici": {"points": 1,
                                               "method": "scale"}}


def test_refit_ignores_garbage_records():
    pts = [_pt(group="abc"), _pt(group="2_1_3"), _pt(mb=-1.0),
           _pt(ms=0.0), "not a dict", {"group": "2_1"}]
    cfg, meta = refit_profile(pts, prior=PRIOR)
    assert cfg == {}
    assert meta["curves"] == {}


def test_profile_roundtrip_with_provenance(tmp_path):
    pts = [_pt(mb=m, ms=0.05 + m / 250.0, t=100.0 + m)
           for m in (1.0, 2.0, 4.0, 8.0)]
    pts += [_pt(alg="ring_ici", mb=4.0, ms=1.0)]
    prof, meta = refit_profile(pts, prior=PRIOR)
    calibrated = dict(prof)
    calibrated[META_KEY] = meta
    full = merge_calibrated_profile(PRIOR, calibrated)
    # calibrated keys override the prior's; untouched prior keys survive
    assert full["allreduce_size_2_consec_1_alpha_ms"] == \
        prof["allreduce_size_2_consec_1_alpha_ms"]
    path = str(tmp_path / "calibrated_profile.json")
    write_calibrated_profile(path, full)
    loaded = json.loads(open(path).read())
    # both parsers read THROUGH the meta key; provenance reads AROUND it
    flat = read_alpha_beta(loaded)
    algos = read_alpha_beta_algos(loaded)
    assert flat["2_1"] == pytest.approx((0.05, 250.0), rel=1e-3)
    assert "ring_ici" in algos["2_1"]
    prov = read_profile_provenance(loaded)
    assert prov["source"] == "runtime-calibrated"
    assert prov["window"] == [101.0, 108.0]
    assert prov["curves"]["2_1/flat"]["method"] == "regression"
    assert read_profile_provenance(PRIOR) == {}  # profiled files: none


# ---------------------------------------------------------------------------
# stored-plan re-pricing + regret sentinel
# ---------------------------------------------------------------------------

LAYERS = [{"tp": 2, "dp": 2, "cp": 1, "sp": 0, "ckpt": 0, "consec": 1}
          for _ in range(2)]
PLAN = {"layers": LAYERS, "pp": 2, "bsz": 8, "chunks": 2}
FLAT = {"2_1": (1.0, 2.0), "2_0": (0.5, 4.0)}
KW = dict(seq_len=8, hidden_size=32, param_mb=8.0, mixed_precision=False)


def test_reprice_stored_plan_hand_math():
    act = 2 * 8 * 32 * 4 / 2**20  # lbsz=2, fp32
    # tp: 6*chunks msgs * 0.5/pp = 3 per layer; dp: (α+4/β)/pp per layer
    want = 2 * 3 * (1.0 + act / 2.0) + 2 * (0.5 + (8.0 / 2) / 4.0) / 2
    got = reprice_stored_plan_ms(PLAN, alpha_beta=FLAT, **KW)
    assert got == pytest.approx(want)
    # a cheaper ici algorithm curve wins the tp min; dcn curves are not
    # candidates for the intra-slice tp collective
    algos = {"2_1": {"ring_ici": (0.25, 2.0), "ring_dcn": (0.0, 1e9)}}
    got2 = reprice_stored_plan_ms(PLAN, alpha_beta=FLAT,
                                  alpha_beta_algos=algos, **KW)
    assert got2 == pytest.approx(want - 2 * 3 * 0.75)


def test_reprice_sp_layer_prices_dp_only():
    plan = {"layers": [{"tp": 2, "dp": 2, "sp": 1, "consec": 1}],
            "pp": 1, "bsz": 8, "chunks": 2}
    # sp folds tp into the dp ring: sdp = 2*2 = 4, consec 1 (tp==1), full
    # param grad at fp32
    got = reprice_stored_plan_ms(plan, alpha_beta={"4_1": (0.5, 4.0)},
                                 **KW)
    assert got == pytest.approx(0.5 + 8.0 / 4.0)


def test_reprice_unpriceable_plan_returns_none():
    assert reprice_stored_plan_ms(PLAN, alpha_beta={}, **KW) is None
    assert reprice_stored_plan_ms(
        {"layers": [{"tp": 1, "dp": 1}], "pp": 1, "bsz": 8, "chunks": 1},
        alpha_beta=FLAT, **KW) is None  # nothing communicates


def test_plan_regret_triggered_and_quiet():
    cal = {"2_1": (0.5, 4.0), "2_0": (0.25, 8.0)}  # everything got faster
    incumbent = dict(PLAN, time_cost_ms=10.0)
    heavy = dict(PLAN, pp=1, time_cost_ms=10.01,
                 strategies=["pp1-tp2-dp2"])
    unpriceable = {"layers": [{"tp": 1, "dp": 1}], "pp": 1, "bsz": 8,
                   "chunks": 2, "time_cost_ms": 1.0}
    res = evaluate_plan_regret(
        incumbent, [unpriceable, heavy], prior=(FLAT, None),
        calibrated=(cal, None), threshold=0.05, **KW)
    # the pp1 runner-up carries 2x the incumbent's comm, so the
    # calibration windfall favors it 2:1 and it overtakes
    assert res["triggered"] is True
    assert res["best_runner_up"] == 1
    assert res["regret_ms"] > 0
    assert res["regret_frac"] > 0.05
    assert res["runner_ups"][0]["adjusted_ms"] is None  # skipped, not faked
    # calibration that matches the prior moves nothing: no regret
    quiet = evaluate_plan_regret(
        incumbent, [heavy], prior=(FLAT, None), calibrated=(FLAT, None),
        threshold=0.05, **KW)
    assert quiet["triggered"] is False
    assert quiet["regret_ms"] == 0.0
    assert quiet["incumbent_ms"] == pytest.approx(10.0)


def test_plan_spec_from_hpc():
    spec = plan_spec_from_hpc(_hpc())
    assert spec == {"layers": LAYERS, "pp": 2, "bsz": 8, "chunks": 2}


# ---------------------------------------------------------------------------
# the glue + the crash-forensics pin
# ---------------------------------------------------------------------------


def test_run_calibration_empty_table_is_harmless(tmp_path):
    reg = MetricsRegistry()
    out = run_calibration({}, None, _model(),
                          calibration_dir=str(tmp_path), registry=reg,
                          world=8, device_kind="cpu")
    assert "error" not in out
    assert out["points_appended"] == 0
    assert out["profile_path"] is None
    assert not os.path.exists(tmp_path / "calibrated_profile.json")


def test_run_calibration_end_to_end_with_recorder(tmp_path):
    reg = MetricsRegistry()
    rec = FlightRecorder(registry=reg, out_dir=str(tmp_path / "flight"))
    table = {"steps": 2, "step_device_ms": 5.0, "rows": [
        {"component": "tp", "measured_ms": 0.6, "predicted_ms": 12.0},
        {"component": "dp", "measured_ms": 0.2, "predicted_ms": 4.0},
    ]}
    prior = {"allreduce_size_2_consec_1_alpha_ms": 2.0,
             "allreduce_size_2_consec_1_beta_mb_per_ms": 50.0,
             "allreduce_size_2_consec_0_alpha_ms": 3.0,
             "allreduce_size_2_consec_0_beta_mb_per_ms": 40.0}
    plan = {"layers": LAYERS, "pp": 2, "bsz": 8, "chunks": 2,
            "predicted_time_cost_ms": 50.0,
            "runner_ups": [dict(PLAN, pp=1, time_cost_ms=50.01,
                                strategies=["pp1-tp2-dp2"])]}
    plan_path = tmp_path / "plan.json"
    plan_path.write_text(json.dumps(plan))
    out = run_calibration(
        table, _hpc(), _model(), calibration_dir=str(tmp_path),
        registry=reg, prior_config=prior, world=8, device_kind="cpu",
        regret_threshold=1e-9, plan_path=str(plan_path),
        mixed_precision=False, recorder=rec, run_id="t0")
    assert "error" not in out
    assert out["points_appended"] == 2  # one tp + one dp point
    assert out["curves_fitted"] == 2
    assert out["drift_score"] == pytest.approx(
        (11.4 + 3.8) / 16.0)
    assert out["regret"]["triggered"] is True
    # the crash dump carries the calibration picture at failure time
    events = [json.loads(l)
              for l in open(tmp_path / "residuals.jsonl") if l.strip()]
    assert len(events) == 2
    snap = rec.snapshot("test")
    assert snap["retained"]["plan_audit"]["data"]["drift_score"] == \
        out["drift_score"]
    assert snap["retained"]["plan_regret"]["data"]["regret_ms"] == \
        out["regret"]["regret_ms"]
    path = rec.dump("test")
    dumped = json.loads(open(path).read())
    assert "plan_regret" in dumped["retained"]


def test_recorder_retain_latest_wins_and_survives_ring_pressure():
    rec = FlightRecorder(capacity=4, registry=MetricsRegistry())
    rec.retain("plan_audit", {"drift_score": 0.5})
    rec.retain("plan_audit", {"drift_score": 0.7})
    for i in range(64):  # far past ring capacity
        rec.note("step", i=i)
    snap = rec.snapshot("test")
    assert len(snap["events"]) == 4
    assert snap["retained"]["plan_audit"]["data"]["drift_score"] == 0.7


def test_check_calibration_pass_is_green(capsys):
    from hetu_galvatron_tpu.cli.check import run_calibration as check_cal

    assert check_cal() == 0
    assert "FAIL" not in capsys.readouterr().out


def test_summarize_renders_calibrated_provenance(tmp_path):
    from hetu_galvatron_tpu.cli.summarize import summarize

    pts = [_pt(mb=m, ms=0.05 + m / 250.0) for m in (1.0, 2.0, 4.0, 8.0)]
    prof, meta = refit_profile(pts, prior=PRIOR)
    full = merge_calibrated_profile(PRIOR, prof)
    full[META_KEY] = meta
    path = str(tmp_path / "calibrated_profile.json")
    write_calibrated_profile(path, full)
    buf = io.StringIO()
    headline = summarize(path, out=buf)
    text = buf.getvalue()
    assert "runtime-calibrated" in text
    assert headline["calibrated_curves"] == 1
