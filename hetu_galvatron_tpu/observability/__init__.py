"""Unified training telemetry: metrics registry, trace spans, derived stats.

The measurement substrate the ROADMAP's "measurably faster" contract needs:

* :mod:`registry` — process-wide counters/gauges/histograms with labels,
  flushed to pluggable sinks (JSONL always available; TensorBoard when
  ``tensorboardX``/``torch``/``tf`` is importable, else a no-op).
* :mod:`sinks` — the sink implementations and the JSONL record schema.
* :mod:`tracing` — host-side ``span("fwd")`` context managers that also
  emit ``jax.profiler.TraceAnnotation`` so the same names show up inside
  XLA device traces, plus the windowed ``jax.profiler.start_trace`` hook.
  The launcher's loop is tiled by ``train/data``, ``train/h2d``,
  ``train/dispatch``, ``train/sync``, ``train/lr``, ``train/log``,
  ``train/check`` (``span_ms{path=...}``, and TraceMes with the iteration
  as ``step``); its set-up by ``setup/imports|runtime|init|resume|
  step_report``; the compiled step's static memory is the gauges
  ``step/static_bytes{part=...}``, and which blocks hold their values
  ``step/blocks_kept{stack=...}``, ``step/blocks_recomputed{stack=...}``,
  ``step/kept_bytes``, ``step/kept_budget_bytes`` (listed in
  :mod:`tracing`'s docstring).
* :mod:`telemetry` — derived training stats: tokens/sec, step-time
  percentiles, model-FLOPs utilization (FLOPs accounting lives in
  ``core/cost_model/cost.py``), device memory gauges, and per-strategy
  predicted comm volume from the plan JSON.
* :mod:`events` — per-request lifecycle event stream for the serving
  stack (submit/admit/prefill/decode/retire with a stable request id),
  written through the same sinks so ``cli/summarize.py`` can rebuild a
  timeline and a TTFT component breakdown per request.
* :mod:`recorder` — crash-forensics flight recorder: bounded ring of
  recent events + metric snapshots, dumped atomically
  (``flight_<ts>.json``) on fault/signal/NaN-halt without ever masking
  the real traceback.
* :mod:`goodput` — wall-clock partitioned into productive-step /
  checkpoint-save / restart-lost / recompile / resume-replay time,
  persisted across restarts through the checkpoint ``train_state``
  payload.

Everything here is host-side and sync-free: nothing in the hot loop calls
``float()`` on a device value (see ``TrainingTelemetry``'s lagged drain),
so attaching telemetry never serializes XLA's async dispatch.
"""

from hetu_galvatron_tpu.observability.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    configure,
    get_registry,
    set_registry,
)
from hetu_galvatron_tpu.observability.sinks import (
    JsonlSink,
    NullSink,
    TensorBoardSink,
    make_tensorboard_sink,
)
from hetu_galvatron_tpu.observability.tracing import (
    TraceCapture,
    span,
)
from hetu_galvatron_tpu.observability.telemetry import (
    TrainingTelemetry,
    peak_device_tflops,
    plan_comm_volume,
)
from hetu_galvatron_tpu.observability.trace_analysis import (
    Attribution,
    analyze_and_audit,
    attribute,
    audit_plan,
    jit_cost_summary,
    load_trace,
    maybe_record_jit_cost,
)
from hetu_galvatron_tpu.observability.prometheus import (
    MetricsHTTPServer,
    prometheus_text,
)
from hetu_galvatron_tpu.observability.events import EventStream
from hetu_galvatron_tpu.observability.recorder import FlightRecorder
from hetu_galvatron_tpu.observability.goodput import GoodputTracker

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "configure",
    "get_registry",
    "set_registry",
    "JsonlSink",
    "NullSink",
    "TensorBoardSink",
    "make_tensorboard_sink",
    "TraceCapture",
    "span",
    "TrainingTelemetry",
    "peak_device_tflops",
    "plan_comm_volume",
    "Attribution",
    "analyze_and_audit",
    "attribute",
    "audit_plan",
    "jit_cost_summary",
    "load_trace",
    "maybe_record_jit_cost",
    "MetricsHTTPServer",
    "prometheus_text",
    "EventStream",
    "FlightRecorder",
    "GoodputTracker",
]
