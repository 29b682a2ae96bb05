"""One cell through the users' entry point, inside a time-boxed window.

The run is ``hetu_galvatron_tpu.cli.train_dist.main(argv, result=out)`` in
this process: the same call ``chip_smoke.py`` makes. Two seams of the
program, both public, make a timed window possible without touching it:

* ``profile.profile=1 profile.profile_warmup=W`` makes the loop observe
  every synced step (host clock around ``block_until_ready`` on the loss)
  into the process-wide registry's histogram ``profiler/iter_time_ms``.
  The harness installs a registry (``set_registry``) whose histogram of
  that name also stamps each sample with the clock, so every measured step
  has a start and an end and the window needs no polling thread.
* ``train.train_iters`` is set far above what the window can hold. When a
  sample ends ``seconds`` or more after the first sample's start, the
  harness raises SIGTERM in its own process, from the main thread, inside
  that ``observe`` call. ``PreemptionGuard`` (``supervisor.graceful_signals``,
  on by default) stops the loop at that step's boundary with exit code 18
  and, with no ``ckpt.save``, writes nothing: a preempted job.

Warm-up is the compiling first step plus two more (``WARMUP_STEPS``).
Everything here is platform-neutral so that ``benchmark/tests`` can drive
it at a tiny size; ``run.py`` is what refuses anything but a TPU.
"""

from __future__ import annotations

import logging
import math
import signal
import statistics
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

WARMUP_STEPS = 3
TRACE_STEPS = 5
HISTOGRAM = "profiler/iter_time_ms"
EXIT_PREEMPTED = 18
FAR_AWAY_ITERS = 10_000_000


class CompileWatch:
    """Counts persistent-cache hits and writes and sums backend compile
    seconds, from JAX's own monitoring events, each with the clock, so that
    what happened inside the window can be told from set-up. (After
    ``chip_smoke.py``'s class of the same name.)"""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.hits: List[float] = []
        self.writes: List[float] = []
        self.compiles: List[tuple] = []   # (clock at end, seconds)
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def close(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_listener(self._event)
        mon.unregister_event_duration_listener(self._duration)

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits.append(time.perf_counter())
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes.append(time.perf_counter())  # when an entry is written

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), secs))

    def split(self, start: float, end: float) -> Dict[str, Any]:
        """Before the window (set-up) and inside it."""
        inside = lambda t: start <= t <= end
        return {
            "setup": {
                "cache_hits": sum(t < start for t in self.hits),
                "cache_writes": sum(t < start for t in self.writes),
                "backend_compile_s": sum(s for t, s in self.compiles
                                         if t < start)},
            "window": {
                "cache_writes": sum(inside(t) for t in self.writes),
                "backend_compiles": sum(inside(t) for t, _ in self.compiles)},
        }


class LogMarks(logging.Handler):
    """Stamps the program's own log lines (logger ``hetu_galvatron_tpu``)
    with the clock: a free timeline of set-up."""

    LOGGER = "hetu_galvatron_tpu"

    def __init__(self) -> None:
        super().__init__(level=logging.INFO)
        self.marks: List[tuple] = []
        logging.getLogger(self.LOGGER).addHandler(self)

    def emit(self, record: logging.LogRecord) -> None:
        self.marks.append((time.perf_counter(), record.getMessage()[:80]))

    def close(self) -> None:
        logging.getLogger(self.LOGGER).removeHandler(self)
        super().close()


def device_memory(devices: Sequence[Any]) -> List[Dict[str, Any]]:
    """Allocator statistics of this process on each device (the peak is the
    process's high-water mark)."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({"id": d.id,
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit")})
    return out


class Window:
    """Collects (start, end) of every measured step and ends the run."""

    def __init__(self, seconds: float,
                 stop: Optional[Callable[[], None]] = None) -> None:
        self.seconds = seconds
        self.steps: List[tuple] = []
        self.signalled = False
        self._stop = stop or (lambda: signal.raise_signal(signal.SIGTERM))

    def on_sample(self, now: float, ms: float) -> None:
        self.steps.append((now - ms / 1000.0, now))
        if not self.signalled and now - self.start >= self.seconds:
            self.signalled = True
            self._stop()

    @property
    def start(self) -> float:
        return self.steps[0][0]

    @property
    def end(self) -> float:
        return self.steps[-1][1]


def install_stamping_registry(window: Window):
    """Make the program's process-wide registry hand out a histogram for
    ``profiler/iter_time_ms`` that tells ``window`` about every sample."""
    from hetu_galvatron_tpu.observability.registry import (
        Histogram,
        MetricsRegistry,
        set_registry,
    )

    class StampingHistogram(Histogram):
        def observe(self, v: float) -> None:
            now = time.perf_counter()
            super().observe(v)
            window.on_sample(now, float(v))

    class StampingRegistry(MetricsRegistry):
        def histogram(self, name: str, **labels):
            if name == HISTOGRAM and not labels:
                return self._get(StampingHistogram, name, labels)
            return super().histogram(name, **labels)

    return set_registry(StampingRegistry())


def harness_overrides(trace_dir: Optional[str]) -> List[str]:
    over = [f"train.train_iters={FAR_AWAY_ITERS}", "profile.profile=1",
            f"profile.profile_warmup={WARMUP_STEPS}"]
    if trace_dir:
        over += [f"profile.trace_dir={trace_dir}",
                 f"profile.trace_iters={TRACE_STEPS}"]
    return over


def run_window(argv: List[str], *, seconds: float,
               trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """Run ``train_dist.main(argv + the harness's overrides)`` until the
    window is over; return the facts of the run (no judgement)."""
    import jax

    from hetu_galvatron_tpu.cli import train_dist

    window = Window(seconds)
    install_stamping_registry(window)
    watch = CompileWatch()
    marks = LogMarks()
    argv = list(argv) + harness_overrides(trace_dir)
    out: Dict[str, Any] = {}
    raised = None
    t0 = time.perf_counter()
    try:
        rc = train_dist.main(argv, result=out)
    except Exception as e:  # noqa: BLE001 — a step that raised is a fact
        import traceback

        traceback.print_exc()
        rc, raised = None, f"{type(e).__name__}: {e}"
    finally:
        watch.close()
        marks.close()
    t1 = time.perf_counter()
    devices = jax.devices()
    steps = window.steps
    losses = list(out.get("losses", []))
    # losses[i] is step i's; the measured steps are the last len(steps) of
    # those after warm-up (in a traced run the traced steps lie between)
    n = len(steps)
    facts: Dict[str, Any] = {
        "argv": argv, "rc": rc, "raised": raised,
        "signalled": window.signalled,
        "main_started": t0, "main_returned": t1,
        "steps": steps,
        "losses": losses,
        "window_losses": losses[len(losses) - n:] if n else [],
        "attention_cores": out.get("attention_cores"),
        "mosaic_custom_calls": out.get("mosaic_custom_calls"),
        "goodput": (out.get("goodput") or {}).get("totals", {}),
        "memory": device_memory(devices),
        "devices": devices,
        # what happened when, in seconds after main() was called: the
        # program's own log lines and JAX's compile events
        "timeline": sorted(
            [(t - t0, msg) for t, msg in marks.marks]
            + [(t - t0, "compile cache hit") for t in watch.hits]
            + [(t - t0, "compile cache write") for t in watch.writes]
            + [(t - t0, f"backend compile or cache load of {s:.2f} s ends")
               for t, s in watch.compiles]),
    }
    if n:
        facts["window"] = {"start": window.start, "end": window.end,
                           "wall_s": window.end - window.start,
                           "steps": n,
                           "step_sum_s": sum(e - s for s, e in steps)}
        facts["compile"] = watch.split(window.start, t1)
    return facts


def steady_rate(steps: Sequence[tuple]) -> Dict[str, float]:
    """The window's rate, and what of it is stalls.

    A period runs from one measured step's start to the next one's, so it
    holds the step and the rest of the loop body. The rate is taken from the
    MEAN period: every whole period of the window and all of their time, a
    stalled step's too. (Until PR 58 it was the median period, which left
    stalls out and, where a cell's steps stand on levels as
    ``mellum2_c4_ep4``'s do by their counted passes, fell on one level or
    the next by a step or two.) The median period stays beside it, and
    ``stall_pct`` is the share of the window beyond what the same number of
    median periods would have taken: what the mean holds and the median
    does not."""
    if len(steps) < 2:
        raise ValueError("a window of fewer than two steps has no period")
    starts = [s for s, _ in steps]
    periods = [b - a for a, b in zip(starts, starts[1:])]
    tails = [p - (e - s) for p, (s, e) in zip(periods, steps)]
    med = statistics.median(periods)
    return {
        "mean_period_s": (starts[-1] - starts[0]) / len(periods),
        "median_period_s": med,
        "loop_overhead_ms": 1e3 * statistics.median(tails),
        "stall_pct": 100.0 * (1.0 - med * len(periods)
                              / (starts[-1] - starts[0])),
    }


def attempted_failed(facts: Dict[str, Any]) -> tuple:
    """Steps started in the window, and those whose loss was not finite or
    that raised."""
    bad = sum(not math.isfinite(x) for x in facts["window_losses"])
    raised = 1 if facts["raised"] else 0
    return len(facts["steps"]) + raised, bad + raised
