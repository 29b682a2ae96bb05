"""tools/serve_bench.py smoke: the closed-loop load generator must run on
CPU (--smoke), complete its request budget, and report a parseable JSON
with zero steady-state recompiles — plus the shared-prefix trace mode
(hit/miss TTFT split) and the importable serve_prefix / spec_decode A/B
legs bench_gate.py consumes."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "tools", "serve_bench.py")


def test_serve_bench_smoke(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # single device is enough (and faster)
    out_json = tmp_path / "report.json"
    metrics = tmp_path / "m.jsonl"
    proc = subprocess.run(
        [sys.executable, BENCH, "--smoke", "--requests", "10",
         "--concurrency", "4", "--json", str(out_json),
         "--metrics", str(metrics)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(out_json.read_text())
    assert report["completed"] == 10
    assert report["tokens_out"] > 0 and report["tokens_per_sec"] > 0
    assert report["ttft_ms"]["p50"] > 0
    assert report["steady_state_recompiles"] == 0
    # the engine's own telemetry stream landed too
    names = {json.loads(line).get("name") for line in open(metrics)}
    assert "serve/ttft_ms" in names and "serve/tokens_per_sec" in names


def test_serve_bench_shared_prefix_trace(tmp_path):
    """--shared-prefixes + --prefix-cache + --spec-decode: the report
    splits TTFT by hit/miss, carries the hit and accept rates, and the
    trace really produces hits."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    out_json = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, BENCH, "--smoke", "--requests", "10",
         "--concurrency", "2", "--shared-prefixes", "2",
         "--prefix-len", "24", "--prefix-cache", "--spec-decode",
         "--spec-k", "2", "--json", str(out_json)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(out_json.read_text())
    assert report["completed"] == 10
    assert report["steady_state_recompiles"] == 0
    assert report["prefix_hit_rate"] > 0
    assert report["ttft_ms_hit"]["n"] + report["ttft_ms_miss"]["n"] == 10
    assert report["ttft_ms_hit"]["n"] >= 5  # 2 prefixes, 10 requests
    assert "spec_accept_rate" in report


def test_serve_bench_ab_legs_importable():
    """run_prefix / run_spec (the bench_gate legs): sane ratios, zero
    steady-state recompiles, lossless spec. Shrunk shapes — this is a
    wiring test, not a measurement."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve_bench

    out = serve_bench.run_prefix(reps=2)
    assert "skipped" not in out, out
    assert 0 < out["serve_prefix_ttft_ratio"] < 1.0
    assert out["serve_prefix_recompiles"] == 0
    assert out["prefix_hit_rate"] > 0
    out = serve_bench.run_spec(requests=2, iters=1)
    assert "skipped" not in out, out
    assert out["spec_decode_tokens_ratio"] > 0
    assert out["spec_decode_recompiles"] == 0
    assert out["spec_accept_rate"] > 0
