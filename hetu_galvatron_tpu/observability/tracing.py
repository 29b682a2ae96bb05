"""Host-side trace spans + windowed XLA device-trace capture.

``span("fwd")`` measures host wall-clock for a code region AND enters a
``jax.profiler.TraceAnnotation``, so the same name shows up on the host
track of an XLA device trace (captured with :class:`TraceCapture` /
``jax.profiler.start_trace``, viewed in tensorboard/xprof). Under async
dispatch a host span around jitted calls measures DISPATCH time, not device
time — that is the point: a hot dispatch loop (e.g. the pipeline
controller) shows up here, while device time lives in the captured trace
under the same annotation names.

Span durations aggregate into the registry as ``span_ms`` histograms
labelled by the nesting path (``train/step``, ``pp/fwd_s0``, ...), so
per-iteration spans cost one histogram observe — no per-span records, no
unbounded JSONL growth.

The spans of the launcher (``cli/train_dist.py``), all flat siblings:

* per iteration of ``run_loop``, tiling the loop body, each carrying the
  iteration as ``step`` on its TraceAnnotation: ``train/data`` (fault
  plan, batch-size ramp, next batch, dropout key), ``train/h2d``
  (``device_put`` of the batch), ``train/dispatch`` (the call of the
  jitted step; the first one traces, lowers and compiles or loads it),
  ``train/sync`` (on a printing iteration the host copies of the log
  line's values are started behind the step; then ``profiler.time_end``:
  blocks on the loss when ``profile.profile=1``), ``train/lr`` (the log
  line's learning rate looked up on the host, on printing iterations;
  nothing runs on the accelerator), ``train/log`` (formats
  the copies that landed; with the profiler off, where the host first
  waits for the step), ``train/check`` (the loss already on the host,
  fault drill, rerun validation), and only when that work is done
  ``train/telemetry``, ``train/eval``, ``train/save``. The pp>1 engines
  keep their own ``pp/*`` spans in place of ``train/h2d`` and
  ``train/dispatch``;
* once per ``train()``, registry only: ``setup/imports``,
  ``setup/runtime`` (model config, TPU client, plan, data iterators,
  telemetry), ``setup/init`` (mesh, building the step, jitted parameter
  and optimizer init), ``setup/resume``, ``setup/step_report`` (the
  compiled step's HLO text and ``memory_analysis()`` after the first
  call, which also sets the gauges ``step/static_bytes{part=arguments|outputs|
  aliased|temporaries|generated_code|live_peak}`` and
  ``step/cores_recomputed`` and ``step/scans_recomputed`` (the flash
  forward kernels, and the recurrent mixers' scan forward kernels,
  per-layer remat runs a second time; 0 where ``modules.remat`` keeps every
  kernel's results) and ``step/blocks_kept{stack=decoder|tower|encoder}``,
  ``step/blocks_recomputed{stack=...}``, ``step/kept_bytes``,
  ``step/kept_budget_bytes`` and ``step/kept_fallback`` (of the blocks
  whose plan bit is set, how many hold their forward's values and how many
  make them again; the bytes the step program counted for the kept, of the
  budget the devices left; 1 where the chosen step did not fit and the
  plan's flags ran: ``parallel/spmd.py::KeptStep``) and
  ``step/relayout_bytes``, ``step/prefetches``, ``step/prefetch_bytes`` and
  ``step/unowned_instructions`` (the passes over an array left outside
  fusions; the asynchronous copies and slices XLA made, and their bytes;
  the instructions under no scope that no scoped one uses or feeds), and
  keeps every instruction's scope, phase and collective class for a reader
  of a trace, with every asynchronous transfer (``transfers``: kind, bytes,
  memory space, what made it and what it feeds), every custom call's target
  and the transfers behind its operands (``calls``), the owner of every
  instruction under no scope (``owners``) and the names of the layout
  passes (``relayouts``): ``trace_analysis.step_hlo``;
  :class:`TraceCapture` writes them beside the trace as ``step_map.json``
  when its window closes).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from hetu_galvatron_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
)

_tls = threading.local()


def current_span_path() -> str:
    """Slash-joined names of the open spans on this thread ('' outside)."""
    return "/".join(getattr(_tls, "stack", []))


class span:
    """Measure a region; nests ('train/step' inside 'train' -> path
    'train/train/step' is avoided by naming spans hierarchically at the
    call site). Re-entrant and thread-safe (per-thread stacks).

    ``attrs`` go onto the TraceAnnotation only (``span("train/lr",
    step=it)``): a trace reader groups the spans of one iteration by them;
    the registry path stays ``name``. The registry is looked up when the
    span ENDS, so a span that is open while the launcher configures its
    sinks still lands in the configured stream.

    A class and not a ``contextmanager`` generator: the TraceAnnotation is
    entered first and left last, so the span's own bookkeeping lies inside
    it and sibling spans that tile a loop leave next to nothing between
    them on the trace (under the profiler's Python tracer every Python
    call between two spans costs microseconds)."""

    __slots__ = ("name", "registry", "attrs", "_t0", "_path", "_ann")

    def __init__(self, name: str, registry: Optional[MetricsRegistry] = None,
                 **attrs):
        self.name, self.registry, self.attrs = name, registry, attrs

    def __enter__(self) -> "span":
        self._t0 = time.perf_counter()  # before the import: it may be timed
        import jax

        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self.name)
        self._path = "/".join(stack)
        return self

    def __exit__(self, *exc) -> bool:
        dur_ms = (time.perf_counter() - self._t0) * 1000.0
        _tls.stack.pop()
        (self.registry or get_registry()).histogram(
            "span_ms", path=self._path).observe(dur_ms)
        self._ann.__exit__(*exc)
        return False


class TraceCapture:
    """Opt-in windowed ``jax.profiler.start_trace`` capture.

    ``step(it)`` starts the trace when ``it`` ENTERS the window
    [start_iter, start_iter + num_iters) and stops it on leaving; the
    window test is ">= start" (not "=="), so a checkpoint-resumed run whose
    first iteration is already past ``start_iter`` still captures a full
    window. One capture per process lifetime; rank-gating is the caller's
    job (pass ``enabled=False`` on non-zero ranks).
    """

    def __init__(self, trace_dir: str, start_iter: int = 0,
                 num_iters: int = 3, enabled: bool = True):
        self.trace_dir = trace_dir
        self.start_iter = start_iter
        self.num_iters = num_iters
        self.enabled = bool(enabled and trace_dir)
        self.active = False
        self._captured = 0

    def step(self, it: int) -> bool:
        """Advance the window; returns True while this iteration is being
        traced (callers keep traced iterations out of timing stats — the
        instrumentation inflates step time)."""
        if not self.enabled:
            return False
        if self.active:
            self._captured += 1
            if self._captured >= self.num_iters:
                self.stop()
            return self.active
        if self._captured == 0 and it >= self.start_iter:
            import jax

            jax.profiler.start_trace(self.trace_dir)
            self.active = True
            return True
        return False

    def stop(self) -> None:
        """Idempotent; call at loop exit so short/crashing runs still flush
        the capture."""
        if self.active:
            import jax

            jax.profiler.stop_trace()
            self.active = False
            # the compiled step's instructions by scope, phase and
            # collective class, beside the trace whose events they name
            from hetu_galvatron_tpu.observability.trace_analysis import (
                write_step_map,
            )

            write_step_map(self.trace_dir)
