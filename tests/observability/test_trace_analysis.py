"""Closed-loop trace analysis: Chrome-trace parsing, device-time
attribution (categories / modules / spans / bubbles), XLA program cost
accounting, the predicted-vs-actual plan audit, and the TraceCapture edge
cases (window never triggered, pre-existing trace dir, stop without start,
nested span names surviving into the parsed capture)."""

import glob
import gzip
import io
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
    set_registry,
)
from hetu_galvatron_tpu.observability.sinks import JsonlSink
from hetu_galvatron_tpu.observability.trace_analysis import (
    Attribution,
    analyze_and_audit,
    attribute,
    audit_plan,
    jit_cost_summary,
    latest_profile_dir,
    load_trace,
    maybe_record_jit_cost,
    measured_components,
    op_category,
    predicted_comm_per_step,
)
from hetu_galvatron_tpu.observability.tracing import TraceCapture, span
from hetu_galvatron_tpu.utils.strategy import LayerStrategy

pytestmark = pytest.mark.observability

MB = 1024 * 1024

CFG = ModelArgs(
    hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
    vocab_size=64, max_position_embeddings=16, seq_length=8,
    make_vocab_size_divisible_by=1, ffn_hidden_size=64)


# ---------------------------------------------------------------------------
# synthetic Chrome traces
# ---------------------------------------------------------------------------


def _ev(pid, tid, ts, dur, name, **args):
    e = {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
         "name": name}
    if args:
        e["args"] = args
    return e


def _write_trace(run_dir, events, procs=None, name="t.trace.json.gz"):
    os.makedirs(run_dir, exist_ok=True)
    meta = [{"ph": "M", "name": "process_name", "pid": pid,
             "args": {"name": pname}}
            for pid, pname in (procs or {}).items()]
    path = os.path.join(run_dir, name)
    data = json.dumps({"traceEvents": meta + events}).encode()
    if name.endswith(".gz"):
        with gzip.open(path, "wb") as f:
            f.write(data)
    else:
        with open(path, "wb") as f:
            f.write(data)
    return path


def test_op_category_stems():
    assert op_category("all-reduce.1") == "allreduce"
    assert op_category("all-reduce-start.3") == "allreduce"  # async pair
    assert op_category("all-gather.2") == "allgather"
    assert op_category("reduce-scatter.7") == "reducescatter"
    assert op_category("all-to-all") == "alltoall"
    assert op_category("collective-permute.1") == "permute"
    assert op_category("fusion.12") == "compute"
    assert op_category("dot_general") == "compute"


def test_load_trace_run_discovery_and_torn_files(tmp_path):
    root = str(tmp_path / "trace")
    run = os.path.join(root, "plugins", "profile", "2026_01_01_00_00_00")
    _write_trace(run, [_ev(1, 1, 0, 10, "fusion.1", hlo_op="fusion.1")])
    # a torn gz (crashed run) and a valid-JSON-but-not-a-trace file must
    # both be skipped, not fatal
    with open(os.path.join(run, "torn.trace.json.gz"), "wb") as f:
        f.write(b"\x1f\x8b\x08garbage")
    with open(os.path.join(run, "bare.trace.json"), "w") as f:
        f.write("[1, 2, 3]")
    for probe in (root, run):  # capture root and run dir both accepted
        td = load_trace(probe)
        assert len(td.events) == 1
        assert td.path == run
    with pytest.raises(FileNotFoundError):
        load_trace(str(tmp_path / "empty"))
    assert latest_profile_dir(str(tmp_path / "empty")) is None
    # newest run (lexicographic max) wins
    run2 = os.path.join(root, "plugins", "profile", "2026_02_02_00_00_00")
    _write_trace(run2, [_ev(1, 1, 0, 10, "fusion.9", hlo_op="fusion.9"),
                        _ev(1, 1, 20, 10, "fusion.9", hlo_op="fusion.9")])
    assert len(load_trace(root).events) == 2


def test_attribute_categories_bubble_and_modules():
    """Hand-computed two-track device trace: busy/idle split, per-device
    category averaging, and per-module attribution."""
    events = [
        # track (1,1): 0.4ms compute, 0.2 allreduce, 0.2 idle, 0.2 allgather
        _ev(1, 1, 0, 400, "fusion.1", hlo_op="fusion.1", hlo_module="jit_s"),
        _ev(1, 1, 400, 200, "all-reduce.1", hlo_op="all-reduce.1",
            hlo_module="jit_s"),
        _ev(1, 1, 800, 200, "all-gather.2", hlo_op="all-gather.2",
            hlo_module="jit_s"),
        # track (1,2): 0.6ms compute, 0.1 idle, 0.3 reduce-scatter
        _ev(1, 2, 0, 600, "fusion.2", hlo_op="fusion.2", hlo_module="jit_s"),
        _ev(1, 2, 700, 300, "reduce-scatter.1", hlo_op="reduce-scatter.1",
            hlo_module="jit_s"),
    ]
    attr = attribute(SimpleNamespace(events=events, process_names={},
                                     thread_names={}, path=""))
    assert attr.tracks == 2
    assert attr.wall_ms == pytest.approx(1.0)
    assert attr.device_busy_ms == pytest.approx(1.7)
    assert attr.per_device_busy_ms == pytest.approx(0.85)
    assert attr.bubble_ms == pytest.approx(0.15)
    assert attr.bubble_frac == pytest.approx(0.15)
    assert attr.categories_ms["compute"] == pytest.approx(0.5)
    assert attr.categories_ms["allreduce"] == pytest.approx(0.1)
    assert attr.categories_ms["allgather"] == pytest.approx(0.1)
    assert attr.categories_ms["reducescatter"] == pytest.approx(0.15)
    assert attr.collective_ms == pytest.approx(0.35)
    assert attr.compute_ms == pytest.approx(0.5)
    assert attr.per_module_ms["jit_s"] == pytest.approx(0.85)


def test_attribute_nested_spans_steps_and_layers():
    """Host annotations reconstruct nesting paths by containment, count
    optimizer steps via the step-span markers, and bucket layer spans."""
    events = [
        _ev(9, 1, 0, 1000, "train/step"),
        _ev(9, 1, 100, 200, "pp/fwd_s0"),
        _ev(9, 1, 400, 100, "layer0/fwd"),
        _ev(9, 1, 1000, 1000, "train/step"),
        _ev(9, 1, 1100, 100, "layer1/fwd"),
    ]
    attr = attribute(SimpleNamespace(events=events, process_names={},
                                     thread_names={}, path=""))
    assert attr.steps == 2
    assert attr.host_span_ms["train/step"] == pytest.approx(2.0)
    assert attr.host_span_ms["train/step/pp/fwd_s0"] == pytest.approx(0.2)
    assert attr.host_span_ms["train/step/layer0/fwd"] == pytest.approx(0.1)
    assert attr.per_layer_ms == {0: pytest.approx(0.1),
                                 1: pytest.approx(0.1)}
    assert attr.tracks == 0  # no device events in this trace


def test_attribute_steps_not_inflated_by_device_track_copies():
    """On TPU the step annotation propagates onto every device track;
    steps must be the per-track max, not the all-track sum."""
    events = []
    for pid in (9, 5, 6):  # host thread + two device tracks
        events += [_ev(pid, 1, 0, 900, "train/step"),
                   _ev(pid, 1, 1000, 900, "train/step")]
    attr = attribute(SimpleNamespace(
        events=events,
        process_names={5: "/device:TPU:0", 6: "/device:TPU:1"},
        thread_names={}, path=""))
    assert attr.steps == 2


def test_attribute_device_track_annotation_coverage():
    """On a TPU-style device track (``/device:*`` process), an annotation
    interval attributes the device-op time it covers — the propagated
    TraceAnnotation names."""
    events = [
        _ev(5, 1, 0, 300, "fusion.7"),
        _ev(5, 1, 300, 100, "all-reduce.3"),
        _ev(5, 1, 500, 100, "fusion.8"),
        _ev(5, 1, 0, 350, "train/step"),  # covers fusion.7 + half the AR
    ]
    attr = attribute(SimpleNamespace(
        events=events, process_names={5: "/device:TPU:0"},
        thread_names={}, path=""))
    assert attr.tracks == 1
    assert attr.categories_ms["compute"] == pytest.approx(0.4)
    assert attr.categories_ms["allreduce"] == pytest.approx(0.1)
    assert attr.device_annotation_ms["train/step"] == pytest.approx(0.35)


def test_attribute_two_source_permute_disambiguation():
    """One compiled program mixing tp-ring hops with pp stage rotations
    (the unified 1F1B engine): permutes stamped with named_scope metadata
    (``pp_rotate`` / ``tp_ring`` / ``cp_ring`` in the tf_op path) bill to
    their own sub-category, an unmarked permute covered by a
    device-propagated ``tp/overlap_step`` span rebills to tp, and only the
    remainder stays on the plan-level heuristic."""
    events = [
        # stage rotation: named_scope metadata rides in tf_op
        _ev(5, 1, 0, 100, "collective-permute.1",
            tf_op="pp_rotate/ppermute"),
        # tp ring hop, marker in long_name instead
        _ev(5, 1, 150, 100, "collective-permute.2",
            long_name="jit(step)/tp_ring/ppermute"),
        # cp ring hop
        _ev(5, 1, 300, 50, "collective-permute.5",
            tf_op="cp_ring/ppermute"),
        # unmarked permute fully inside a tp/overlap_step device window
        _ev(5, 1, 400, 100, "collective-permute.3"),
        _ev(5, 1, 380, 140, "tp/overlap_step"),
        # unmarked permute outside every window -> plan heuristic
        _ev(5, 1, 600, 100, "collective-permute.4"),
        _ev(5, 1, 750, 100, "fusion.1"),
    ]
    attr = attribute(SimpleNamespace(
        events=events, process_names={5: "/device:TPU:0"},
        thread_names={}, path=""))
    assert attr.categories_ms["permute_pp"] == pytest.approx(0.1)
    assert attr.categories_ms["permute_tp"] == pytest.approx(0.2)
    assert attr.categories_ms["permute_cp"] == pytest.approx(0.05)
    assert attr.categories_ms["permute"] == pytest.approx(0.1)
    # a pipelined tp plan: ring hops land on tp, rotations + the unmarked
    # remainder on pp — the mis-billing the round-11 heuristic had
    from hetu_galvatron_tpu.utils.strategy import LayerStrategy

    hpc = SimpleNamespace(layers=[LayerStrategy(pp_deg=2, tp_size=2,
                                                dp_size=2)], pp_deg=2)
    m = measured_components(attr, hpc)
    assert m["tp"] == pytest.approx(0.2)
    assert m["cp"] == pytest.approx(0.05)
    assert m["pp"] == pytest.approx(0.1 + 0.1)


def test_attribute_window_rebilling_disabled_under_compiled_pipeline():
    """The tp/overlap_step span wraps the whole train step, so when the
    COMPILED engine ran (its pp stage rotations are in-program ppermutes
    inside the same window) an unmarked permute must NOT be rebilled to tp
    by window coverage — the pp/compiled_step annotation is the evidence
    that disables the pass; only named_scope markers disambiguate there."""
    events = [
        # unmarked permute (a stage rotation whose HLO metadata was
        # stripped) fully inside a step-wide tp/overlap_step window
        _ev(5, 1, 400, 100, "collective-permute.3"),
        _ev(5, 1, 0, 1000, "tp/overlap_step"),
        _ev(5, 1, 0, 1000, "pp/compiled_step"),
        _ev(5, 1, 750, 100, "fusion.1"),
    ]
    attr = attribute(SimpleNamespace(
        events=events, process_names={5: "/device:TPU:0"},
        thread_names={}, path=""))
    # stays a bare permute -> the plan heuristic (pp when pipelined)
    assert attr.categories_ms.get("permute") == pytest.approx(0.1)
    assert "permute_tp" not in attr.categories_ms


# ---------------------------------------------------------------------------
# XLA program cost accounting
# ---------------------------------------------------------------------------


def test_jit_cost_summary_counts_flops():
    fn = jax.jit(lambda a, b: a @ b)
    sds = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    out = jit_cost_summary(fn, (sds, sds))
    # 64^3 multiply-adds = 2*64^3 flops; XLA counts at least the matmul
    assert out.get("flops", 0) >= 2 * 64 ** 3
    # never raises on garbage
    assert jit_cost_summary(object()) == {}


def test_maybe_record_jit_cost_once_per_registry_and_sink_gating(tmp_path):
    fn = jax.jit(lambda a: a * 2.0)
    args = (jnp.ones((8, 8)),)
    path = str(tmp_path / "m.jsonl")
    reg = MetricsRegistry([JsonlSink(path)])
    first = maybe_record_jit_cost("prog/a", fn, args, registry=reg)
    assert first and first["flops"] > 0
    # idempotent per (registry, program)
    assert maybe_record_jit_cost("prog/a", fn, args, registry=reg) is None
    # a different registry records independently
    reg2 = MetricsRegistry([JsonlSink(str(tmp_path / "m2.jsonl"))])
    assert maybe_record_jit_cost("prog/a", fn, args, registry=reg2)
    # gauges + one-shot event land in the stream
    assert reg.gauge("cost/flops", program="prog/a").value > 0
    reg.close()
    recs = [json.loads(l) for l in open(path)]
    ev = [r for r in recs if r.get("name") == "program_cost"]
    assert len(ev) == 1 and ev[0]["data"]["program"] == "prog/a"
    # default registry without sinks: pure no-op
    old = get_registry()
    try:
        set_registry(MetricsRegistry())
        assert maybe_record_jit_cost("prog/b", fn, args) is None
        assert not get_registry().metrics()
    finally:
        set_registry(old)


# ---------------------------------------------------------------------------
# predicted communication + component mapping
# ---------------------------------------------------------------------------


def _hpc(layers, *, chunks=1, global_bsz=8, pp_deg=1):
    return SimpleNamespace(layers=layers, chunks=chunks,
                           global_bsz=global_bsz, pp_deg=pp_deg)


def test_predicted_comm_per_step_alpha_beta_pricing():
    """The α-β time predictions follow the cost model's pricing exactly:
    one Megatron-SP message is 0.5*(α + size/β) × 6 msgs/layer/chunk, one
    dp all-reduce is α + grad_mb/β."""
    from hetu_galvatron_tpu.observability.telemetry import layer_param_mb

    ab = {"2_1": (0.05, 100.0), "2_0": (0.07, 80.0)}
    layers = [LayerStrategy(tp_size=2, dp_size=2)] * 2
    hpc = _hpc(layers, chunks=1, global_bsz=8)
    out = predicted_comm_per_step(hpc, CFG, alpha_beta=ab,
                                  mixed_precision=True)
    lbsz = 8 // 1 // 2
    act_mb = lbsz * CFG.seq_length * CFG.hidden_size * 2 / MB
    exp_tp = 2 * 6 * 0.5 * (0.05 + act_mb / 100.0)  # 2 layers, consec pair
    assert out["tp"]["predicted_ms"] == pytest.approx(exp_tp)
    grad_mb = layer_param_mb(CFG) / 2 * 0.5
    exp_dp = 2 * (0.07 + grad_mb / 80.0)  # tp>1 leaves dp strided -> "2_0"
    assert out["dp"]["predicted_ms"] == pytest.approx(exp_dp)
    assert out["tp"]["predicted_mb"] > 0 and out["dp"]["predicted_mb"] > 0
    # without fitted pairs: volumes only, no invented times
    vol_only = predicted_comm_per_step(hpc, CFG)
    assert "predicted_ms" not in vol_only["tp"]
    assert "predicted_ms" not in vol_only["dp"]


def test_predicted_comm_checkpoint_and_chunks_scaling():
    ab = {"2_1": (0.0, 100.0), "2_0": (0.0, 100.0)}
    base = predicted_comm_per_step(
        _hpc([LayerStrategy(tp_size=2, dp_size=2)]), CFG, alpha_beta=ab)
    ck = predicted_comm_per_step(
        _hpc([LayerStrategy(tp_size=2, dp_size=2, checkpoint=True)]),
        CFG, alpha_beta=ab)
    # remat replays the forward collectives: 1.5x messages
    assert ck["tp"]["predicted_ms"] == pytest.approx(
        1.5 * base["tp"]["predicted_ms"])


def test_predicted_comm_per_device_pp_normalization():
    """The measured side is a per-device-track average and each device runs
    one stage's layers, so the priced ms divide by pp_deg (volumes stay
    whole-plan)."""
    ab = {"2_1": (0.05, 100.0), "2_0": (0.07, 80.0)}
    flat = predicted_comm_per_step(
        _hpc([LayerStrategy(tp_size=2, dp_size=2)] * 2), CFG, alpha_beta=ab)
    piped = predicted_comm_per_step(
        _hpc([LayerStrategy(pp_deg=2, tp_size=2, dp_size=2)] * 2, pp_deg=2),
        CFG, alpha_beta=ab)
    for comp in ("tp", "dp"):
        assert piped[comp]["predicted_ms"] == pytest.approx(
            flat[comp]["predicted_ms"] / 2)
        assert piped[comp]["predicted_mb"] == pytest.approx(
            flat[comp]["predicted_mb"])


def test_measured_components_plan_disambiguation():
    attr = Attribution(categories_ms={
        "allreduce": 5.0, "allgather": 2.0, "reducescatter": 1.0,
        "alltoall": 3.0, "permute": 4.0})
    # pipelined plan with a dp group: permute->pp, allreduce->dp
    m = measured_components(attr, _hpc([LayerStrategy(
        pp_deg=2, tp_size=2, dp_size=2)], pp_deg=2))
    assert m == {"tp": 3.0, "sp": 3.0, "dp": 5.0, "pp": 4.0}
    # unpipelined cp plan: permute is the ring attention
    m = measured_components(attr, _hpc([LayerStrategy(
        tp_size=2, cp_size=2)]))
    assert m["cp"] == 4.0 and "pp" not in m
    # pure-TP single-replica plan: all-reduces are TP activations, and
    # with no pp/cp the permutes are the ring-overlap rotations
    m = measured_components(attr, _hpc([LayerStrategy(tp_size=8)]))
    assert m["tp"] == 3.0 + 5.0 + 4.0


# ---------------------------------------------------------------------------
# the plan audit
# ---------------------------------------------------------------------------


def _measured_attr(steps=2):
    return Attribution(
        steps=steps, tracks=8, wall_ms=20.0, device_busy_ms=128.0,
        per_device_busy_ms=16.0, bubble_ms=4.0, bubble_frac=0.2,
        categories_ms={"compute": 10.0, "allgather": 2.0,
                       "reducescatter": 1.0, "allreduce": 2.0,
                       "permute": 1.0})


def test_audit_plan_ratios_residuals_gauges_and_event(tmp_path):
    path = str(tmp_path / "m.jsonl")
    reg = MetricsRegistry([JsonlSink(path)])
    ab = {"2_1": (0.05, 100.0), "2_0": (0.07, 80.0)}
    hpc = _hpc([LayerStrategy(pp_deg=2, tp_size=2, dp_size=2)] * 2,
               chunks=2, pp_deg=2)
    table = audit_plan(_measured_attr(), hpc, CFG, registry=reg,
                       alpha_beta=ab, predicted_layer_s=[0.004, 0.004])
    rows = {r["component"]: r for r in table["rows"]}
    # tp: measured (ag+rs)/steps vs α-β prediction -> ratio + residual
    pred = predicted_comm_per_step(hpc, CFG, alpha_beta=ab)
    assert rows["tp"]["measured_ms"] == pytest.approx(1.5)
    assert rows["tp"]["predicted_ms"] == pytest.approx(
        pred["tp"]["predicted_ms"], abs=1e-4)
    assert rows["tp"]["ratio"] == pytest.approx(
        1.5 / pred["tp"]["predicted_ms"], rel=1e-3)
    assert rows["tp"]["residual_ms"] == pytest.approx(
        1.5 - pred["tp"]["predicted_ms"], abs=1e-3)
    assert rows["dp"]["measured_ms"] == pytest.approx(1.0)
    assert "ratio" in rows["dp"]
    # compute vs the cost model's per-layer per-microbatch seconds,
    # scaled x chunks/pp to the per-device per-step normalization
    # (here 2/2 = 1): 2 layers x 4ms = 8ms
    assert rows["compute"]["measured_ms"] == pytest.approx(5.0)
    assert rows["compute"]["predicted_ms"] == pytest.approx(8.0)
    assert rows["compute"]["ratio"] == pytest.approx(5.0 / 8.0)
    # gradient accumulation without pp: chunks=4 microbatches per step on
    # every device -> the same per-layer seconds predict 4x the ms
    acc = audit_plan(
        _measured_attr(),
        _hpc([LayerStrategy(tp_size=2, dp_size=2)] * 2,
             chunks=4, global_bsz=16),
        CFG, registry=MetricsRegistry(),
        predicted_layer_s=[0.004, 0.004])
    acc_rows = {r["component"]: r for r in acc["rows"]}
    assert acc_rows["compute"]["predicted_ms"] == pytest.approx(32.0)
    # pipeline bubble vs the 1F1B analytical fraction
    assert rows["bubble"]["measured_frac"] == pytest.approx(0.2)
    assert rows["bubble"]["predicted_frac"] == pytest.approx(
        2 * (2 - 1) / (2 + 2 * (2 - 1)))
    assert table["steps"] == 2
    assert table["step_device_ms"] == pytest.approx(8.0)
    # audit/* gauges (component-labelled) + the plan_audit event
    assert reg.gauge("audit/time_ratio", component="tp").value == \
        rows["tp"]["ratio"]
    assert reg.gauge("audit/measured_ms", component="dp").value == \
        rows["dp"]["measured_ms"]
    assert reg.gauge("audit/step_device_ms").value == pytest.approx(8.0)
    reg.close()
    evs = [json.loads(l) for l in open(path)
           if json.loads(l).get("name") == "plan_audit"]
    assert len(evs) == 1 and evs[0]["data"]["rows"] == table["rows"]


def test_audit_plan_volume_only_without_alpha_beta():
    reg = MetricsRegistry()
    hpc = _hpc([LayerStrategy(tp_size=2, dp_size=2)] * 2)
    table = audit_plan(_measured_attr(), hpc, CFG, registry=reg)
    rows = {r["component"]: r for r in table["rows"]}
    assert rows["tp"]["predicted_mb"] > 0
    assert "ratio" not in rows["tp"]  # no fitted pairs -> no invented time
    assert "predicted_frac" not in rows["bubble"]  # pp1 plan


def test_analyze_and_audit_never_raises(tmp_path):
    hpc = _hpc([LayerStrategy(tp_size=2, dp_size=2)])
    assert analyze_and_audit(str(tmp_path / "nope"), hpc, CFG) is None
    # a trace with no events -> None, not a crash
    run = str(tmp_path / "t" / "plugins" / "profile" / "r1")
    _write_trace(run, [])
    assert analyze_and_audit(str(tmp_path / "t"), hpc, CFG) is None
    # garbage hpc on a real trace -> swallowed (post-mortem helper)
    _write_trace(run, [_ev(9, 1, 0, 100, "train/step")])
    assert analyze_and_audit(str(tmp_path / "t"), object(), CFG) is None


# ---------------------------------------------------------------------------
# TraceCapture edge cases
# ---------------------------------------------------------------------------


def test_trace_capture_window_never_triggered(tmp_path):
    d = str(tmp_path / "trace")
    tc = TraceCapture(d, start_iter=100, num_iters=2)
    assert all(not tc.step(it) for it in range(5))
    tc.stop()  # idempotent no-op
    assert not tc.active
    assert latest_profile_dir(d) is None  # nothing was ever captured
    assert not os.path.exists(os.path.join(d, "plugins"))


def test_trace_capture_stop_without_start(tmp_path):
    tc = TraceCapture(str(tmp_path / "t"), start_iter=0, num_iters=1)
    tc.stop()  # never started: must not raise
    tc.stop()
    assert tc._captured == 0
    # disabled capture never starts either
    off = TraceCapture("", enabled=True)
    assert not off.enabled and not off.step(0)


def test_trace_capture_existing_dir_and_nested_spans_in_trace(tmp_path):
    """The full loop on a REAL capture: the trace dir already exists (a
    restarted run reuses it), two iterations are captured, and nested
    span() names survive into the parsed trace as containment paths."""
    d = str(tmp_path / "trace")
    os.makedirs(os.path.join(d, "plugins", "profile"))  # pre-existing
    fn = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((32, 32))
    fn(x).block_until_ready()  # compile outside the window
    tc = TraceCapture(d, start_iter=1, num_iters=2)
    assert not tc.step(0)  # before the window
    for it in (1, 2):
        assert tc.step(it)
        with span("train/step"):
            with span("pp/fwd_s0"):
                fn(x).block_until_ready()
    assert not tc.step(3)  # window closed itself after num_iters
    assert not tc.active
    tc.stop()

    attr = attribute(load_trace(d))
    assert attr.host_span_ms["train/step"] > 0
    assert attr.host_span_ms["train/step/pp/fwd_s0"] > 0  # nesting survived
    assert attr.steps == 2
    # the CPU thunk trace carries device ops (hlo_op args) -> compute time
    assert attr.tracks > 0
    assert attr.compute_ms > 0


def test_runtime_profiler_analyze_trace(tmp_path):
    """RuntimeProfiler.analyze_trace attributes its own flushed capture
    window, and degrades to None when no window was configured/flushed."""
    from hetu_galvatron_tpu.core.args_schema import CoreArgs
    from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
        RuntimeProfiler,
    )

    args = CoreArgs(model={"hidden_size": 32, "num_hidden_layers": 1,
                           "num_attention_heads": 2, "vocab_size": 64,
                           "seq_length": 8, "max_position_embeddings": 16})
    assert RuntimeProfiler(args).analyze_trace() is None  # no trace_dir
    args.profile.trace_dir = str(tmp_path / "t")
    args.profile.profile_warmup = 0
    args.profile.trace_iters = 1
    prof = RuntimeProfiler(args)
    assert prof.analyze_trace() is None  # configured but never flushed
    fn = jax.jit(lambda a: a * 2)
    prof.time_start(0)
    with span("train/step"):
        fn(jnp.ones((16, 16))).block_until_ready()
    prof.time_end(0)
    prof.stop_trace()
    attr = prof.analyze_trace()
    assert attr is not None and attr.host_span_ms["train/step"] > 0


# ---------------------------------------------------------------------------
# summarize hardening (torn JSONL) — the report-side satellite
# ---------------------------------------------------------------------------


def test_summarize_survives_torn_jsonl(tmp_path, capsys):
    from hetu_galvatron_tpu.cli.summarize import load_records, summarize

    path = str(tmp_path / "m.jsonl")
    reg = MetricsRegistry([JsonlSink(path)])
    reg.counter("train/steps").inc(3)
    reg.gauge("train/tokens_per_sec").set(11.0)
    reg.close()
    with open(path, "a") as f:
        f.write('42\n')                                  # valid JSON, not a record
        f.write('{"kind": "gauge", "name": "train/')     # torn mid-write
    recs = load_records(path)
    assert all(isinstance(r, dict) for r in recs)
    assert "skipped 2 unparseable line(s)" in capsys.readouterr().err
    buf = io.StringIO()
    headline = summarize(path, out=buf)
    assert headline["steps"] == 3
    assert "tokens/sec" in buf.getvalue()


def test_summarize_renders_calibration_table(tmp_path):
    """audit_plan -> JSONL -> summarize renders the plan-audit table and
    surfaces the per-component ratios in the headline dict."""
    from hetu_galvatron_tpu.cli.summarize import summarize

    path = str(tmp_path / "m.jsonl")
    reg = MetricsRegistry([JsonlSink(path)])
    hpc = _hpc([LayerStrategy(pp_deg=2, tp_size=2, dp_size=2)] * 2,
               chunks=2, pp_deg=2)
    audit_plan(_measured_attr(), hpc, CFG, registry=reg,
               alpha_beta={"2_1": (0.05, 100.0), "2_0": (0.07, 80.0)},
               predicted_layer_s=[0.004, 0.004])
    reg.close()
    buf = io.StringIO()
    headline = summarize(path, out=buf)
    text = buf.getvalue()
    assert "plan audit: predicted vs actual" in text
    for comp in ("tp", "dp", "compute", "bubble"):
        assert comp in text
    assert headline["audit_ratio_tp"] > 0
    assert headline["audit_ratio_compute"] == pytest.approx(5.0 / 8.0)
    assert headline["audit_step_device_ms"] == pytest.approx(8.0)


# ---------------------------------------------------------------------------
# per-algorithm audit rows
# ---------------------------------------------------------------------------


def test_predicted_comm_per_algorithm_min_choice():
    """With per-algorithm curves, each priced component carries every
    candidate's ms and predicted_ms = the min — the cost model's own
    choice (min over flat pair + ICI algo curves)."""
    ab = {"2_1": (0.05, 100.0), "2_0": (0.07, 80.0)}
    algos = {"2_1": {"tree_ici": (0.01, 100.0),
                     "ring_ici": (0.2, 400.0),
                     "ring_dcn": (9.9, 1.0)}}  # dcn curve must not price tp
    hpc = _hpc([LayerStrategy(tp_size=2, dp_size=2)])
    out = predicted_comm_per_step(hpc, CFG, alpha_beta=ab,
                                  alpha_beta_algos=algos)
    tp = out["tp"]
    assert set(tp["algorithms"]) == {"flat", "tree_ici", "ring_ici"}
    assert tp["algorithm"] == min(tp["algorithms"],
                                  key=tp["algorithms"].get)
    assert tp["predicted_ms"] == pytest.approx(
        min(tp["algorithms"].values()))
    # without algo data: behavior unchanged (no algorithms key)
    flat_only = predicted_comm_per_step(hpc, CFG, alpha_beta=ab)
    assert "algorithms" not in flat_only["tp"]


def test_audit_plan_emits_per_algorithm_rows(tmp_path):
    path = str(tmp_path / "m.jsonl")
    reg = MetricsRegistry([JsonlSink(path)])
    ab = {"2_1": (0.05, 100.0), "2_0": (0.07, 80.0)}
    algos = {"2_1": {"tree_ici": (0.01, 100.0),
                     "ring_ici": (0.2, 400.0)}}
    attr = _measured_attr()
    hpc = _hpc([LayerStrategy(tp_size=2, dp_size=2)] * 2)
    algos.update({"1_1": {}})
    table = audit_plan(attr, hpc, CFG, registry=reg, alpha_beta=ab,
                       alpha_beta_algos=algos)
    comps = {r["component"]: r for r in table["rows"]}
    # per-algorithm candidate rows ride along, exactly one chosen
    for name in ("tp[flat]", "tp[tree_ici]", "tp[ring_ici]"):
        assert name in comps and "predicted_ms" in comps[name]
    chosen = [r for c, r in comps.items()
              if c.startswith("tp[") and r.get("chosen")]
    assert len(chosen) == 1
    # dp is priced on the one fitted pair: no per-algorithm rows
    assert not [c for c in comps if c.startswith("dp[")]
    assert comps["dp"]["measured_ms"] == pytest.approx(2.0 / attr.steps)
    reg.flush()


def test_summarize_hardware_renders_algo_columns(tmp_path, capsys):
    from hetu_galvatron_tpu.cli.summarize import summarize_hardware

    cfg = {
        "allreduce_size_4_consec_1": 120.0,
        "allreduce_size_4_consec_1_alpha_ms": 0.2,
        "allreduce_size_4_consec_1_beta_mb_per_ms": 100.0,
        "allreduce_size_4_consec_1_alg_ring_lvl_ici_alpha_ms": 0.3,
        "allreduce_size_4_consec_1_alg_ring_lvl_ici_beta_mb_per_ms": 140.0,
        "allreduce_size_2_consec_0": 80.0,
        "allreduce_size_2_consec_0_alg_ring_lvl_dcn_alpha_ms": 0.9,
        "allreduce_size_2_consec_0_alg_ring_lvl_dcn_beta_mb_per_ms": 30.0,
    }
    import io

    buf = io.StringIO()
    head = summarize_hardware(cfg, "hw.json", out=buf)
    text = buf.getvalue()
    assert "ring_ici" in text and "ring_dcn" in text
    assert "—" in text  # unfitted cells render as em-dash
    assert head["algo_groups"] == 2
    # legacy JSON renders without the algo columns
    buf2 = io.StringIO()
    summarize_hardware({"allreduce_size_4_consec_1": 120.0}, "hw.json",
                       out=buf2)
    assert "ring_ici" not in buf2.getvalue()

