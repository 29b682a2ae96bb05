"""Perf regression sentinel: an unchanged run must pass the gate, an
artificially regressed leg must fail it with a readable per-leg delta
report, device-mismatched candidates are skipped (not judged), and the
baseline/history plumbing round-trips through the CLI."""

import importlib.util
import io
import json
import os

import pytest

pytestmark = [pytest.mark.core, pytest.mark.observability]

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
GATE = os.path.abspath(os.path.join(ROOT, "tools", "bench_gate.py"))

spec = importlib.util.spec_from_file_location("bench_gate", GATE)
bench_gate = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench_gate)


PARSED = {"metric": "gpt2_125m_train_mfu", "value": 5.0, "unit": "% MFU",
          "tokens_per_sec": 100.0, "device": "cpu",
          "compiled_vs_host": 0.7, "tp_overlap_vs_gspmd": 0.9}


def _baseline(legs=None, device="cpu"):
    return {"device": device,
            "legs": legs or {"mfu_pct": 5.0, "tokens_per_sec": 100.0,
                             "compiled_vs_host": 0.7}}


def test_extract_legs_maps_and_filters():
    legs = bench_gate.extract_legs(PARSED)
    assert legs == {"mfu_pct": 5.0, "tokens_per_sec": 100.0,
                    "compiled_vs_host": 0.7, "tp_overlap_vs_gspmd": 0.9}
    assert bench_gate.extract_legs(None) == {}
    # non-numeric / non-positive values never become legs
    assert bench_gate.extract_legs({"value": 0, "tokens_per_sec": "n/a"}) \
        == {}


def test_unchanged_run_passes_within_threshold():
    cand = {"device": "cpu", "legs": {"mfu_pct": 4.8,
                                      "tokens_per_sec": 98.0,
                                      "compiled_vs_host": 0.73}}
    rows, ok = bench_gate.compare(_baseline(), cand, threshold=0.10)
    assert ok
    assert all(r["status"] in ("ok", "improved") for r in rows)


def test_regressed_leg_fails_direction_aware():
    # tokens_per_sec DOWN 20% is a regression; compiled_vs_host UP past
    # threshold is a regression (lower is better there)
    cand = {"device": "cpu", "legs": {"mfu_pct": 5.0,
                                      "tokens_per_sec": 80.0,
                                      "compiled_vs_host": 0.9}}
    rows, ok = bench_gate.compare(_baseline(), cand, threshold=0.10)
    assert not ok
    status = {r["leg"]: r["status"] for r in rows}
    assert status["tokens_per_sec"].startswith("REGRESSED")
    assert status["compiled_vs_host"].startswith("REGRESSED")
    assert status["mfu_pct"] == "ok"
    # the inverse moves are improvements, not regressions
    cand = {"device": "cpu", "legs": {"mfu_pct": 5.0,
                                      "tokens_per_sec": 130.0,
                                      "compiled_vs_host": 0.5}}
    rows, ok = bench_gate.compare(_baseline(), cand, threshold=0.10)
    assert ok
    assert {r["status"] for r in rows} == {"ok", "improved"}


def test_missing_leg_is_a_regression_and_new_leg_is_not():
    # VANISHED: the same-device history proves the leg used to be
    # measured — its absence from the candidate is a failure
    cand = {"device": "cpu", "legs": {"mfu_pct": 5.0,
                                      "tokens_per_sec": 100.0}}
    hist = [{"device": "cpu", "value": 5.0, "compiled_vs_host": 0.7}]
    rows, ok = bench_gate.compare(_baseline(), cand, threshold=0.10,
                                  history=hist)
    assert not ok
    assert any(r["status"].startswith("MISSING") for r in rows)
    # PENDING: a baseline leg no same-device run ever produced (a freshly
    # committed entry) must NOT fail the gate — it renders as pending
    # until the first bench round measures it
    rows, ok = bench_gate.compare(_baseline(), cand, threshold=0.10)
    assert ok
    status = {r["leg"]: r["status"] for r in rows}
    assert status["compiled_vs_host"].startswith("pending")
    # a leg only the candidate has is informational, not a failure
    base = _baseline(legs={"mfu_pct": 5.0})
    cand = {"device": "cpu", "legs": {"mfu_pct": 5.0, "flash_speedup": 2.0}}
    rows, ok = bench_gate.compare(base, cand, threshold=0.10)
    assert ok
    assert any(r["status"].startswith("new") for r in rows)


def test_device_mismatch_skips_not_judges():
    """A CPU-fallback bench must neither regress nor green-light a TPU
    baseline."""
    cand = {"device": "cpu", "legs": {"mfu_pct": 0.1,
                                      "tokens_per_sec": 1.0}}
    rows, ok = bench_gate.compare(_baseline(device="TPU v5 lite"), cand,
                                  threshold=0.10)
    assert ok  # skipped, not failed
    assert all(r["status"].startswith("skipped (device mismatch")
               for r in rows)
    # ...but an all-skipped comparison gated NOTHING: the report must say
    # NO VERDICT, not green-light the run as PASS
    buf = io.StringIO()
    bench_gate.render_report(rows, ok, candidate_name="c",
                             baseline_name="b", out=buf)
    text = buf.getvalue()
    assert "NO VERDICT" in text and "PASS" not in text


def test_history_noise_column_filters_by_device():
    hist = [dict(PARSED, tokens_per_sec=v) for v in (90.0, 110.0)]
    hist.append(dict(PARSED, device="tpu", tokens_per_sec=9999.0))
    cand = {"device": "cpu", "legs": {"tokens_per_sec": 100.0}}
    rows, ok = bench_gate.compare(_baseline(legs={"tokens_per_sec": 100.0}),
                                  cand, threshold=0.10, history=hist)
    row = next(r for r in rows if r["leg"] == "tokens_per_sec")
    assert row["history"] == (90.0, 110.0)  # other-device entry excluded


def test_render_report_per_leg_deltas(capsys):
    cand = {"device": "cpu", "legs": {"mfu_pct": 5.0,
                                      "tokens_per_sec": 80.0,
                                      "compiled_vs_host": 0.7}}
    rows, ok = bench_gate.compare(_baseline(), cand, threshold=0.10)
    buf = io.StringIO()
    bench_gate.render_report(rows, ok, candidate_name="BENCH_r06.json",
                             baseline_name="baseline", out=buf)
    text = buf.getvalue()
    assert "BENCH_r06.json vs baseline" in text
    assert "-20.0%" in text           # the per-leg delta
    assert "REGRESSED (>10%)" in text
    assert "FAIL (1 leg(s) regressed)" in text


def test_smoke_self_check():
    assert bench_gate.smoke() == 0


# ---------------------------------------------------------------------------
# CLI end-to-end over a synthetic history directory
# ---------------------------------------------------------------------------


def _hist(tmp_path, n, parsed):
    path = tmp_path / f"BENCH_r{n:02d}.json"
    path.write_text(json.dumps({"n": n, "cmd": "bench.py", "rc": 0,
                                "tail": "", "parsed": parsed}))
    return str(path)


def test_main_end_to_end_pass_fail_and_update(tmp_path, capsys):
    hist_glob = str(tmp_path / "BENCH_r*.json")
    baseline = str(tmp_path / "baseline.json")
    _hist(tmp_path, 1, dict(PARSED, tokens_per_sec=95.0))
    _hist(tmp_path, 2, PARSED)

    # no baseline yet -> rc 2 with a pointer at --update-baseline
    assert bench_gate.main(["--history", hist_glob,
                            "--baseline", baseline]) == 2
    assert "--update-baseline" in capsys.readouterr().err

    # accept the newest entry as the baseline
    assert bench_gate.main(["--history", hist_glob, "--baseline", baseline,
                            "--update-baseline"]) == 0
    saved = json.loads(open(baseline).read())
    assert saved["device"] == "cpu"
    assert saved["legs"]["tokens_per_sec"] == 100.0
    assert saved["created_from"] == "BENCH_r02.json"
    capsys.readouterr()

    # an unchanged newer round passes
    _hist(tmp_path, 3, dict(PARSED, tokens_per_sec=97.0))
    assert bench_gate.main(["--history", hist_glob,
                            "--baseline", baseline]) == 0
    assert "bench gate: PASS" in capsys.readouterr().out

    # an artificially regressed leg fails with the delta report
    _hist(tmp_path, 4, dict(PARSED, tokens_per_sec=60.0))
    assert bench_gate.main(["--history", hist_glob,
                            "--baseline", baseline]) == 1
    out = capsys.readouterr().out
    assert "tokens_per_sec" in out and "REGRESSED" in out
    assert "-40.0%" in out
    # prior rounds show up as the noise-context column
    assert "[95, 100]" in out.replace(",000", "")  # formatting-agnostic

    # explicit --candidate takes precedence over newest-history
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps({"parsed": PARSED}))
    assert bench_gate.main(["--history", hist_glob, "--baseline", baseline,
                            "--candidate", str(cand)]) == 0


def test_main_degrades_on_garbage(tmp_path, capsys):
    hist_glob = str(tmp_path / "BENCH_r*.json")
    baseline = str(tmp_path / "baseline.json")
    # unreadable history entries are skipped; with none left, rc 2
    (tmp_path / "BENCH_r01.json").write_text("{torn")
    assert bench_gate.main(["--history", hist_glob,
                            "--baseline", baseline]) == 2
    # a history entry whose bench never completed (no legs) gates nothing
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "parsed": {"error": "tpu_unavailable", "rc": 1}}))
    assert bench_gate.main(["--history", hist_glob,
                            "--baseline", baseline]) == 0
    assert "nothing to gate" in capsys.readouterr().err


def test_committed_baseline_matches_gate_schema():
    """The repo's committed baseline must stay loadable and on-schema."""
    with open(os.path.join(ROOT, "tools", "bench_baseline.json")) as f:
        base = json.load(f)
    assert isinstance(base.get("legs"), dict) and base["legs"]
    assert base.get("device")
    known = {leg for leg, _, _ in bench_gate.LEGS}
    assert set(base["legs"]) <= known
