"""Runtime profiler: per-iteration timing + device memory accounting.

Capability parity with the reference runtime profiler
(core/profiler/runtime_profiler.py:12-370): wall-clock per-iteration timing
with warmup and a 3-sigma outlier filter, device and compiled-program memory
figures, an iteration log line, and the computation/memory JSON writers the
model profiler post-processes.

TPU-native measurement: timing is host wall-clock around `block_until_ready`
(XLA has no CUDA events; dispatch is async so this measures true device
time once warm), memory uses `device.memory_stats()` when the backend
provides it (TPU does) and falls back to the jitted executable's
`memory_analysis()` — XLA's own static accounting — on backends without
allocator stats (CPU tests).

Everything measured here is also routed through the observability metrics
registry (``observability/registry.py``): iteration times land in the
``profiler/iter_time_ms`` histogram and the MoE balance tracker in
``moe/*`` gauges (the compiled step's static memory is the launcher's
``step/static_bytes`` gauges, from :func:`compiled_memory_bytes`), so a
configured JSONL/TensorBoard sink sees the profiler's view of the run
without any extra plumbing. The XLA trace window is delegated to
``observability.tracing.TraceCapture``.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

import jax

from hetu_galvatron_tpu.core.args_schema import CoreArgs
from hetu_galvatron_tpu.core.search_engine.profiles import write_json
from hetu_galvatron_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
)
from hetu_galvatron_tpu.observability.tracing import TraceCapture

MB = 1024 * 1024
# the entries of a step's metrics that the iteration log line formats
LOG_LINE_KEYS = ("loss", "grad_norm", "moe")


def device_memory_mb(device=None) -> Optional[Dict[str, float]]:
    """Current/peak bytes in use from the backend allocator, or None when
    unsupported (CPU)."""
    device = device or jax.devices()[0]
    stats = getattr(device, "memory_stats", lambda: None)()
    if not stats:
        return None
    return {
        "current": stats.get("bytes_in_use", 0) / MB,
        "peak": stats.get("peak_bytes_in_use", 0) / MB,
    }


def compiled_memory_mb(compiled) -> Dict[str, float]:
    """Static memory accounting from a lowered+compiled jit function
    (the TPU-native analogue of torch.cuda.max_memory_allocated for
    profiling: XLA reports argument/output/temp/generated sizes)."""
    m = compiled.memory_analysis()
    if m is None:
        return {}
    def g(name):
        return getattr(m, name, 0) or 0
    return {
        "arguments": g("argument_size_in_bytes") / MB,
        "outputs": g("output_size_in_bytes") / MB,
        "temps": g("temp_size_in_bytes") / MB,
        "total": (g("argument_size_in_bytes") + g("output_size_in_bytes")
                  + g("temp_size_in_bytes")) / MB,
    }


def compiled_memory_bytes(compiled) -> Dict[str, int]:
    """XLA's static accounting of one compiled program, per device, in
    bytes, with the peak a step needs while it runs: donated arguments are
    reused for the outputs (``aliased``), so ``live_peak = arguments +
    outputs - aliased + temporaries + generated_code``. The allocator's
    ``peak_bytes_in_use`` never sees a program's temporaries; this is the
    figure that says whether a step fits. Empty when the backend has no
    analysis."""
    m = compiled.memory_analysis()
    if m is None:
        return {}
    parts = {
        "arguments": int(m.argument_size_in_bytes),
        "outputs": int(m.output_size_in_bytes),
        "aliased": int(m.alias_size_in_bytes),
        "temporaries": int(m.temp_size_in_bytes),
        "generated_code": int(m.generated_code_size_in_bytes),
    }
    parts["live_peak"] = (parts["arguments"] + parts["outputs"]
                          - parts["aliased"] + parts["temporaries"]
                          + parts["generated_code"])
    return parts


class RuntimeProfiler:
    """Hooks into the train loop: time_start/time_end around the step
    (reference profile_time_start :218), the XLA trace window, the
    iteration log line."""

    def __init__(self, args: CoreArgs, world_size: int = 1, rank: int = 0,
                 registry: Optional[MetricsRegistry] = None,
                 pass_rows: Optional[int] = None):
        self.args = args
        self.world_size = world_size
        self.rank = rank
        # rows of one counted pass of a layer inside the expert exchange
        # (``moe.overflow_rows``, the launcher's ``ep`` report): what the
        # log line takes off ``rows_computed`` to find the first chunk's
        self.pass_rows = pass_rows
        # None = late-bind the process default at USE time, so a profiler
        # constructed before the train launcher configures sinks still
        # lands its metrics in the configured stream
        self._registry = registry
        self.time_samples: List[float] = []
        self._t0: Optional[float] = None
        self.enabled = bool(args.profile.profile)
        p = args.profile
        self._trace = TraceCapture(
            p.trace_dir, start_iter=p.profile_warmup,
            num_iters=p.trace_iters, enabled=bool(p.trace_dir and rank == 0))
        self._tracing_now = False

    @property
    def registry(self) -> MetricsRegistry:
        return self._registry if self._registry is not None else get_registry()

    # -- timing -------------------------------------------------------------

    def time_start(self, it: int) -> None:
        # XLA trace window [warmup, warmup + trace_iters): the TPU
        # counterpart of the reference's torch.profiler capture
        # (observability/tracing.py — window-based so checkpoint-resumed
        # runs whose first iteration is already past warmup still capture)
        self._tracing_now = self._trace.step(it)
        if not self.enabled or it < self.args.profile.profile_warmup:
            return
        if self._tracing_now:
            # trace instrumentation inflates step time; traced iterations
            # stay out of time_samples so filtered_time_ms (and the
            # computation profiles the search engine fits) stay clean
            return
        self._t0 = time.perf_counter()

    def stop_trace(self) -> None:
        """Idempotent; also called at loop exit so short runs still flush."""
        self._trace.stop()

    def analyze_trace(self):
        """Device-time attribution of the flushed capture window
        (``observability/trace_analysis.attribute``), or None when no
        window was configured or ever flushed."""
        if not self._trace.enabled:
            return None
        from hetu_galvatron_tpu.observability.trace_analysis import (
            attribute,
            load_trace,
        )

        try:
            return attribute(load_trace(self._trace.trace_dir))
        except FileNotFoundError:
            return None

    def time_end(self, it: int, sync: Any = None) -> None:
        if self._t0 is None:
            if self._tracing_now and self.enabled and sync is not None:
                # a traced iteration records no sample but blocks where a
                # measured one does, so the trace shows the loop the
                # window runs (not one that first blocks a statement later)
                jax.block_until_ready(sync)
            return
        if sync is not None:
            jax.block_until_ready(sync)
        ms = (time.perf_counter() - self._t0) * 1000.0
        self.time_samples.append(ms)
        self.registry.histogram("profiler/iter_time_ms").observe(ms)
        self._t0 = None

    def filtered_time_ms(self) -> float:
        """Mean after dropping >3-sigma outliers (reference
        _filtered_time_samples, runtime_profiler.py:312)."""
        if not self.time_samples:
            return 0.0
        arr = np.asarray(self.time_samples)
        mean, std = arr.mean(), arr.std()
        keep = arr[np.abs(arr - mean) <= 3 * std] if std > 0 else arr
        return float(keep.mean())

    # -- logging + output ---------------------------------------------------

    def prints(self, it: int) -> bool:
        """Whether :meth:`iteration_log` prints a line for iteration ``it``:
        rank 0, on the log interval."""
        interval = self.args.logging.log_interval
        return bool(self.rank == 0 and interval and it % interval == 0)

    def start_log_copies(self, it: int, metrics: Dict[str, Any]) -> None:
        """On a printing iteration, start the device-to-host copies of
        exactly the leaves the log line formats (:data:`LOG_LINE_KEYS`).
        Called right after the step is dispatched, the copies queue behind
        it on the device and land as it ends, so :meth:`iteration_log`
        formats host values where it used to pay one blocking round trip a
        leaf on an idle device. Off the interval: nothing."""
        if not self.prints(it):
            return
        line = {k: metrics[k] for k in LOG_LINE_KEYS if k in metrics}
        for leaf in jax.tree.leaves(line):
            # the host pipeline engine hands its grad-norm over as a float
            if isinstance(leaf, jax.Array):
                leaf.copy_to_host_async()

    def iteration_log(self, it: int, metrics: Dict[str, Any],
                      lr: Optional[float] = None) -> str:
        """One line per iteration (reference runtime_profiler.py:333-370).

        Returns EXACTLY the line that was printed, or "" on non-printing
        iterations (rank != 0 or off the log interval) — the return value
        is consistent for every caller, and off-interval iterations pay
        ZERO device-to-host traffic: no copy is started and no value is
        converted (the MoE balance tracker included), never half of it.

        On a printing iteration the values come from the host: the loop
        has called :meth:`start_log_copies` behind the step, so the
        ``float()`` / ``asarray()`` below find copies that have landed
        (a caller that did not pays the blocking read-backs here, with the
        same line), and ``lr`` is a Python float the loop evaluated off
        the accelerator.
        """
        if not self.prints(it):
            return ""
        bits = [f"iter {it}"]
        if "loss" in metrics:
            bits.append(f"loss {float(metrics['loss']):.4f}")
        if "grad_norm" in metrics:
            bits.append(f"grad-norm {float(metrics['grad_norm']):.3f}")
        if lr is not None:
            bits.append(f"lr {lr:.3e}")
        if self.time_samples:
            bits.append(f"iter-time {self.time_samples[-1]:.1f}ms")
        if "moe" in metrics:
            step_passes = None
            # per-layer balance tracker (reference moe_utils.py:608-644
            # track_moe_metrics log lines): aux/z-loss per MoE layer plus
            # the tokens-per-expert imbalance max/mean; the converted
            # scalars also land in the registry as moe/* gauges, with the
            # most loaded expert's rows (the longest grouped matmul)
            for name in sorted(metrics["moe"]):
                st = metrics["moe"][name]
                if "beta_over_one" in st:
                    # a Gated DeltaNet block's one count: the share of
                    # (position, head) whose delta step overshoots
                    over = 100.0 * float(st["beta_over_one"])
                    bits.append(f"gdn[{name}] beta>1 {over:.1f}%")
                    self.registry.gauge("gated_delta/beta_over_one_pct",
                                        layer=name).set(over)
                    continue
                tpe = np.asarray(st["tokens_per_expert"], dtype=float)
                if "rows_held" in st:
                    # a layer that holds a share of its experts: the routes
                    # that fell on them over all T*K, the rows its chunks
                    # handed to the grouped matmuls against the rows that
                    # belonged to a held expert, the counted passes behind
                    # the first chunk, the share of the microbatches that
                    # took none, and the balance over the HELD experts,
                    # whose rows are the matmuls' groups
                    all_routes = tpe.sum()
                    tpe = np.asarray(st["held_tokens_per_expert"],
                                     dtype=float)
                    if "rows_by_chip" in st:
                        # inside the expert exchange the layer's counts
                        # are MEANS over the chips, and a chip's rows are
                        # its experts' (``held_tokens_per_expert`` is every
                        # expert's, chip by chip): the line converts the
                        # one new leaf, the passes by chip, and takes the
                        # rest from what it converts anyway. A vector that
                        # lies a part a chip costs the host of a chip 0.1
                        # to 0.2 ms to read (PERF.md section 6, PR 54)
                        by_chip = np.asarray(st["passes_by_chip"],
                                             dtype=float)
                        chips = tpe.reshape(len(by_chip), -1).sum(axis=1)
                        held, passes = float(chips.mean()), float(
                            by_chip.mean())
                        computed = float(st["rows_computed"])
                    else:
                        held, computed, passes = (
                            float(st[k]) for k in (
                                "rows_held", "rows_computed",
                                "overflow_chunks"))
                    local = 100.0 * held / max(all_routes, 1e-9)
                    bits.append(f"moe[{name}] local {local:.2f}% rows "
                                f"{held:.0f}/{computed:.0f} "
                                f"+{passes:.0f} chunks")
                    self.registry.gauge("moe/local_routes_pct",
                                        layer=name).set(local)
                    self.registry.gauge("moe/rows_held", layer=name).set(held)
                    self.registry.gauge("moe/rows_computed",
                                        layer=name).set(computed)
                    self.registry.gauge("moe/overflow_chunks",
                                        layer=name).set(passes)
                    self.registry.gauge(
                        "moe/short_dispatch_pct", layer=name).set(
                            100.0 * float(st["short_dispatch"]))
                if "rows_by_chip" in st:
                    # a layer inside the expert exchange, chip by chip: the
                    # routes that fell on each chip's experts and the
                    # counted passes it took behind its first chunk, the
                    # fullest chip's routes over the mean and over the
                    # first chunk's rows (100 is the line behind which a
                    # pass is taken). The layer's ``rows_computed`` and
                    # ``overflow_chunks`` above are MEANS over the chips;
                    # the step waits for the chip that took the most
                    for r, (rows, took) in enumerate(zip(chips, by_chip)):
                        self.registry.gauge("moe/chip_rows", layer=name,
                                            chip=str(r)).set(float(rows))
                        self.registry.gauge("moe/chip_passes", layer=name,
                                            chip=str(r)).set(float(took))
                    chip_imb = float(chips.max() / max(chips.mean(), 1e-9))
                    bits.append(
                        f"moe[{name}] chips {chip_imb:.3f} passes "
                        + "/".join(f"{took:.0f}" for took in by_chip))
                    self.registry.gauge("moe/chip_imbalance",
                                        layer=name).set(chip_imb)
                    if self.pass_rows is not None or not passes:
                        first = computed - passes * (self.pass_rows or 0)
                        self.registry.gauge(
                            "moe/fullest_chip_pct", layer=name).set(
                                100.0 * float(chips.max()) / max(first, 1e-9))
                    step_passes = (step_passes or 0.0) + float(by_chip.max())
                imb = float(tpe.max() / max(tpe.mean(), 1e-9))
                aux = float(st["load_balance_loss"])
                z = float(st["z_loss"])
                bits.append(f"moe[{name}] aux {aux:.3e} "
                            f"z {z:.3e} imb {imb:.2f}")
                self.registry.gauge("moe/aux_loss", layer=name).set(aux)
                self.registry.gauge("moe/z_loss", layer=name).set(z)
                self.registry.gauge("moe/imbalance", layer=name).set(imb)
                self.registry.gauge("moe/rows_per_expert", layer=name,
                                    stat="max").set(float(tpe.max()))
            if step_passes is not None:
                # the passes the step waited for: every exchanged layer's
                # fullest chip's, added up
                self.registry.histogram("moe/step_passes").observe(
                    step_passes)
        line = " | ".join(bits)
        print(line, flush=True)
        return line

    def computation_profile_key(self, layertype: int, bsz: int,
                                seq: int) -> str:
        return f"layertype_{layertype}_bsz{bsz}_seq{seq}"

    def save_computation_profile(self, path: str, entries: Dict[str, float]
                                 ) -> None:
        """Merge per-run timing entries into computation_profiling_*.json."""
        import json, os

        existing = {}
        if os.path.exists(path):
            with open(path) as f:
                existing = json.load(f)
        existing.update(entries)
        write_json(existing, path)

    def save_memory_profile(self, path: str, entries: Dict[str, Any]) -> None:
        import json, os

        existing = {}
        if os.path.exists(path):
            with open(path) as f:
                existing = json.load(f)
        existing.update(entries)
        write_json(existing, path)
