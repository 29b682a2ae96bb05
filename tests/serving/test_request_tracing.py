"""Request-lifecycle tracing through the serving engine: complete
timelines, additive TTFT component split, queue-wait histogram, SLO
attainment gauges, zero steady-state recompiles with tracing ON, the
serve/errors counter (exception class label) on an injected failing
step, and the flight dump on engine abort."""

import io
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_galvatron_tpu.cli.summarize import (
    request_timelines,
    summarize,
    timeline_complete,
    ttft_components,
)
from hetu_galvatron_tpu.core.args_schema import ModelArgs, ServingArgs
from hetu_galvatron_tpu.models.builder import init_causal_lm
from hetu_galvatron_tpu.observability.registry import MetricsRegistry
from hetu_galvatron_tpu.observability.sinks import JsonlSink
from hetu_galvatron_tpu.serving.engine import ServingEngine

pytestmark = pytest.mark.serving


def _cfg(**kw):
    base = dict(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=128, seq_length=32,
        hidden_act="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", tie_word_embeddings=False,
        add_bias_linear=False, add_qkv_bias=False,
        make_vocab_size_divisible_by=1, ffn_hidden_size=128)
    base.update(kw)
    return ModelArgs(**base)


def _traced_engine(tmp_path, params, cfg, **sv_kw):
    metrics_path = str(tmp_path / "serve_metrics.jsonl")
    reg = MetricsRegistry([JsonlSink(metrics_path)])
    sv = ServingArgs(max_batch_size=4, kv_block_size=8, max_seq_len=48,
                     max_new_tokens=8, trace_requests=True,
                     slo_ttft_ms=60_000.0, slo_itl_ms=60_000.0,
                     flush_interval=4, **sv_kw)
    eng = ServingEngine(params, cfg, sv, registry=reg,
                        compute_dtype=jnp.float32)
    return eng, reg, metrics_path


class _Ticks:
    """The ``time`` the scheduler and the engine read in a test that compares
    their readings: each reading of either clock is one tick after the last,
    whatever else the host was running."""
    TICK_MS = 0.1
    sleep = staticmethod(time.sleep)

    def __init__(self):
        self.now = 0.0

    def monotonic(self):
        self.now += self.TICK_MS / 1000.0
        return self.now

    perf_counter = monotonic


def test_complete_timelines_and_additive_ttft_split(tmp_path, monkeypatch):
    from hetu_galvatron_tpu.serving import engine, scheduler

    clock = _Ticks()
    monkeypatch.setattr(engine, "time", clock)
    monkeypatch.setattr(scheduler, "time", clock)
    cfg = _cfg()
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    eng, reg, metrics_path = _traced_engine(tmp_path, params, cfg)
    eng.warmup(buckets=[8, 16])
    warm = eng.compile_count()
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, 128, (n,)).tolist(), m)
            for n, m in [(3, 4), (9, 6), (13, 5), (1, 8), (7, 3)]]
    handles = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
    eng.run_until_idle()
    eng.close()
    reg.close()

    # tracing adds zero steady-state recompiles (host-side events only)
    assert eng.compile_count() == warm
    assert all(h.status == "done" for h in handles)

    records = [json.loads(line) for line in open(metrics_path)]
    timelines, bad = request_timelines(records)
    assert bad == 0
    rids = {h.request.rid for h in handles}
    assert set(timelines) == rids  # no orphaned or missing requests
    for rid, evs in timelines.items():
        assert timeline_complete(evs), (rid, [e["ev"] for e in evs])
        names = [e["ev"] for e in evs]
        assert names[0] == "submit" and names[-1] == "retire"
        assert "admit" in names and "first_token" in names
        # one decode/verify window event per generated token after the
        # first (prefill produced token 1), retire reason length-bound
        ret = evs[-1]
        n_windows = sum(1 for e in evs if e["ev"] in ("decode", "verify"))
        assert n_windows == ret["generated"] - 1

    # the component split sums to measured TTFT (additive by design)
    comp = ttft_components(timelines)
    assert len(comp["ttft"]) == len(rids)
    for q, p, d, t in zip(comp["queue"], comp["prefill"],
                          comp["first_decode"], comp["ttft"]):
        assert q + p + d == pytest.approx(t, abs=1e-6)
        assert p > 0  # cold requests really paid a prefill

    # ... and the handle-side TTFT is the event's, a reading or two later
    by_rid = {h.request.rid: h for h in handles}
    for rid, evs in timelines.items():
        ft = next(e for e in evs if e["ev"] == "first_token")
        assert ft["ttft_ms"] == pytest.approx(
            by_rid[rid].ttft_s() * 1000.0, abs=2.5 * _Ticks.TICK_MS)
        assert ft["ttft_ms"] > 10 * _Ticks.TICK_MS

    # queue-wait histogram (satellite): one observation per admission
    qw = [r for r in records if r.get("name") == "serve/queue_wait_ms"]
    assert qw and qw[-1]["count"] == len(rids)

    # SLO attainment gauges exported (generous targets -> 1.0)
    names = {(r.get("kind"), r.get("name")) for r in records}
    assert ("gauge", "serve/slo_ttft_attainment") in names
    assert ("gauge", "serve/slo_itl_attainment") in names
    slo = [r for r in records
           if r.get("name") == "serve/slo_ttft_attainment"]
    assert slo[-1]["value"] == 1.0

    # summarize renders the breakdown and timelines
    buf = io.StringIO()
    headline = summarize(metrics_path, out=buf, timeline="all")
    text = buf.getvalue()
    assert headline["requests_traced"] == len(rids)
    assert headline["timelines_complete"] == len(rids)
    assert "TTFT breakdown" in text and "first_decode" in text
    assert "SLO" in text and "request timelines" in text
    assert headline["ttft_queue_p50_ms"] >= 0


def test_rejected_request_has_complete_timeline(tmp_path):
    cfg = _cfg()
    params, _ = init_causal_lm(jax.random.key(1), cfg)
    eng, reg, metrics_path = _traced_engine(tmp_path, params, cfg)
    h = eng.submit([5] * 100, max_new_tokens=8)  # can never fit
    assert h.status == "rejected"
    eng.close()
    reg.close()
    records = [json.loads(line) for line in open(metrics_path)]
    timelines, _ = request_timelines(records)
    evs = timelines[h.request.rid]
    assert [e["ev"] for e in evs] == ["submit", "retire"]
    assert evs[-1]["status"] == "rejected"
    assert timeline_complete(evs)


def test_tight_slo_reports_partial_attainment(tmp_path):
    cfg = _cfg()
    params, _ = init_causal_lm(jax.random.key(2), cfg)
    eng, reg, metrics_path = _traced_engine(
        tmp_path, params, cfg)
    eng.serving = eng.serving.model_copy(update={"slo_ttft_ms": 1e-6})
    h = eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run_until_idle()
    eng.close()
    reg.close()
    assert h.status == "done"
    assert reg.gauge("serve/slo_ttft_attainment").value == 0.0
    assert reg.gauge("serve/slo_ttft_ms").value == pytest.approx(1e-6)


def test_engine_error_counter_labeled_and_flight_dump(tmp_path):
    """Satellite: an engine-error retirement must leave a labeled
    serve/errors counter, retire events, and a flight dump."""
    cfg = _cfg()
    params, _ = init_causal_lm(jax.random.key(3), cfg)
    eng, reg, metrics_path = _traced_engine(
        tmp_path, params, cfg, flight_dir=str(tmp_path / "flight"))

    def boom(slot, bucket):
        raise RuntimeError("injected step failure")

    eng._prefill_slot = boom
    eng.start()
    h = eng.submit([1, 2, 3])
    assert h.result(timeout=30) == []
    assert h.status == "error"
    eng.close()
    reg.close()

    # the counter carries the exception class as a label
    assert reg.counter("serve/errors", error="RuntimeError").value == 1
    assert reg.counter("serve/engine_errors").value == 1

    # flight dump: parseable, carries the traceback and the event ring
    dumps = os.listdir(tmp_path / "flight")
    assert len(dumps) == 1 and dumps[0].startswith("flight_")
    assert eng.recorder.dumped
    with open(tmp_path / "flight" / dumps[0]) as f:
        flight = json.load(f)
    assert flight["reason"] == "engine_error"
    assert flight["exception"]["type"] == "RuntimeError"
    assert "injected step failure" in flight["exception"]["traceback"]
    assert any(e["data"].get("ev") == "submit" for e in flight["events"])

    # the timeline in the metrics stream still terminates (retire/error)
    records = [json.loads(line) for line in open(metrics_path)]
    timelines, _ = request_timelines(records)
    evs = timelines[h.request.rid]
    assert evs[-1]["ev"] == "retire" and evs[-1]["status"] == "error"


def test_tracing_off_emits_no_request_events(tmp_path):
    cfg = _cfg()
    params, _ = init_causal_lm(jax.random.key(4), cfg)
    metrics_path = str(tmp_path / "m.jsonl")
    reg = MetricsRegistry([JsonlSink(metrics_path)])
    sv = ServingArgs(max_batch_size=2, kv_block_size=8, max_seq_len=32,
                     max_new_tokens=4)
    eng = ServingEngine(params, cfg, sv, registry=reg,
                        compute_dtype=jnp.float32)
    h = eng.submit([1, 2, 3])
    eng.run_until_idle()
    eng.close()
    reg.close()
    assert h.status == "done"
    records = [json.loads(line) for line in open(metrics_path)]
    timelines, _ = request_timelines(records)
    assert timelines == {}
    # with tracing off AND no flight_dir the recorder tap is not even
    # attached — the default serving path pays nothing per token
    assert eng.recorder.events() == []


def test_flight_dir_alone_keeps_ring_context(tmp_path):
    """flight_dir without trace_requests: no JSONL stream, but the
    recorder ring still captures the lifecycle for crash dumps."""
    cfg = _cfg()
    params, _ = init_causal_lm(jax.random.key(6), cfg)
    sv = ServingArgs(max_batch_size=2, kv_block_size=8, max_seq_len=32,
                     max_new_tokens=4, flight_dir=str(tmp_path / "fl"))
    eng = ServingEngine(params, cfg, sv, registry=MetricsRegistry(),
                        compute_dtype=jnp.float32)
    h = eng.submit([1, 2, 3])
    eng.run_until_idle()
    eng.close()
    assert h.status == "done"
    assert any(e["data"].get("ev") == "retire"
               for e in eng.recorder.events())
