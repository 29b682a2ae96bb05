"""Derived training telemetry: throughput, MFU, memory, plan comm volume.

:class:`TrainingTelemetry` is a hook of ``cli/train_dist.py``'s loop
(``hook(it, metrics)``) that turns raw step metrics into the numbers the
ROADMAP cares about:

* ``train/step_time_ms`` histogram — host wall-clock between hook calls.
  Under async dispatch the host runs ahead of the device until XLA's
  in-flight limit back-pressures it, so after a couple of warmup steps the
  host cadence equals device step time without ever calling
  ``block_until_ready``.
* ``train/tokens_per_sec`` gauge — windowed tokens/s.
* ``train/mfu`` gauge — model-FLOPs utilization: achieved model FLOP/s
  (tokens/s x analytic FLOPs/token from ``core/cost_model/cost.py``) over
  the device fleet's peak FLOP/s (:func:`peak_device_tflops`, overridable
  for hardware the table does not know).
* ``train/loss`` / ``train/grad_norm`` gauges — device scalars buffered
  un-synced and converted one flush LATE, so the hot loop never blocks on
  an in-flight value (the "no float() in the step loop" contract the CPU
  smoke test pins).
* ``device/mem_mb`` gauges — allocator stats at flush time (host-side API,
  no device sync; absent on backends without allocator stats).

:func:`plan_comm_volume` computes each layer's PREDICTED per-step
collective volume from the strategy plan (mirroring the message-size
arithmetic in ``core/cost_model/cost.py``), emitted once in the one-shot
``plan`` event so a run's observed step time can be audited against what
the search engine thought the plan would communicate ("Revisiting the
Time Cost Model of AllReduce": analytical comm models drift; keep the
receipts).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

from hetu_galvatron_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
)

MB = 1024 * 1024

# bf16 peak TFLOP/s per chip by device_kind substring (generation specs;
# matched case-insensitively against jax device_kind strings like
# "TPU v5 lite"). CPUs and unknown kinds resolve to None — MFU is then
# emitted only when the caller supplies peak_tflops_per_device.
_PEAK_TFLOPS = (
    ("v5 lite", 197.0), ("v5litepod", 197.0), ("v5e", 197.0),
    ("v6 lite", 918.0), ("v6e", 918.0),
    ("v5p", 459.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def peak_device_tflops(device_kind: str) -> Optional[float]:
    """Per-chip bf16 peak for a jax ``device_kind`` string, or None when
    unknown (CPU, new hardware)."""
    kind = (device_kind or "").lower()
    for sub, tf in _PEAK_TFLOPS:
        if sub in kind:
            return tf
    return None


class TrainingTelemetry:
    """Sync-free train-loop hook producing throughput/MFU/memory metrics.

    Call it as ``hook(it, metrics)`` once per step; call :meth:`close`
    (or use as a context manager) at loop exit so the tail of the run is
    flushed. ``metrics`` entries named in ``scalar_keys`` may be live
    device arrays — they are buffered and converted only at the NEXT
    flush boundary, by which point the device finished them long ago.
    """

    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        *,
        model=None,
        global_batch_size: int = 0,
        seq_length: int = 0,
        world_size: int = 1,
        peak_tflops_per_device: float = 0.0,
        flush_interval: int = 16,
        window: int = 32,
        scalar_keys: Sequence[str] = ("loss", "grad_norm"),
    ):
        self.registry = registry if registry is not None else get_registry()
        self.global_batch_size = int(global_batch_size)
        self.seq_length = int(seq_length)
        self.world_size = max(int(world_size), 1)
        self.flush_interval = max(int(flush_interval), 1)
        self.window = max(int(window), 2)
        self.scalar_keys = tuple(scalar_keys)
        self.flops_per_token = 0.0
        if model is not None:
            from hetu_galvatron_tpu.core.cost_model.cost import (
                model_flops_per_token,
            )

            self.flops_per_token = model_flops_per_token(model)
        self.peak_flops = 0.0
        if peak_tflops_per_device > 0:
            self.peak_flops = peak_tflops_per_device * 1e12 * self.world_size
        else:
            import jax

            tf = peak_device_tflops(jax.devices()[0].device_kind)
            if tf:
                self.peak_flops = tf * 1e12 * self.world_size
        self._last_t: Optional[float] = None
        self._times: List[float] = []  # (t, step) ring for the window
        self._steps_seen = 0
        self._pending: List[tuple] = []  # (it, {key: device scalar})
        self._closed = False

    # -- hook ---------------------------------------------------------------

    def __call__(self, it: int, metrics: Dict[str, Any]) -> None:
        now = time.perf_counter()
        self._closed = False  # re-armed: one instance may span many loops
        reg = self.registry
        if self._last_t is not None:
            reg.histogram("train/step_time_ms").observe(
                (now - self._last_t) * 1000.0)
        self._last_t = now
        self._times.append(now)
        if len(self._times) > self.window:
            self._times = self._times[-self.window:]
        self._steps_seen += 1
        reg.counter("train/steps").inc()
        tokens = self.global_batch_size * self.seq_length
        if tokens:
            reg.counter("train/tokens").inc(tokens)
        # buffer device scalars WITHOUT converting — float() here would
        # block async dispatch and serialize host prep with device compute
        pend = {k: metrics[k] for k in self.scalar_keys if k in metrics}
        if pend:
            self._pending.append((it, pend))
        if self._steps_seen % self.flush_interval == 0:
            self.flush(step=it)

    def resume_from(self, step: int, *, samples: Optional[int] = None
                    ) -> None:
        """Carry the telemetry step across a checkpoint resume: the
        cumulative ``train/steps`` / ``train/tokens`` counters restart at
        the checkpointed totals instead of zero, so a preempted-and-resumed
        run's metrics stream is continuous (throughput windows and step
        timing stay process-local — wall-clock did genuinely restart).
        ``samples`` overrides the consumed-sample count for runs whose
        batch size varied (a rampup): ``step * global_batch_size`` would
        overstate the tokens the original run actually trained on."""
        if step <= 0:
            return
        self._steps_seen = int(step)
        self.registry.counter("train/steps").inc(step)
        tokens = (samples * self.seq_length if samples is not None
                  else step * self.global_batch_size * self.seq_length)
        if tokens:
            self.registry.counter("train/tokens").inc(tokens)

    # -- flushing -----------------------------------------------------------

    def _drain_pending(self, final: bool) -> None:
        """Convert buffered device scalars to floats. All but the newest
        entry are at least one step old — the device already finished
        them, so float() returns without stalling; the newest is held
        back until the next flush (or converted at close)."""
        keep = 0 if final else 1
        while len(self._pending) > keep:
            it, vals = self._pending.pop(0)
            for k, v in vals.items():
                self.registry.gauge(f"train/{k}").set(float(v))

    def tokens_per_sec(self) -> float:
        if len(self._times) < 2:
            return 0.0
        span_s = self._times[-1] - self._times[0]
        if span_s <= 0:
            return 0.0
        return (len(self._times) - 1) * self.global_batch_size * \
            self.seq_length / span_s

    def flush(self, step: Optional[int] = None, final: bool = False) -> None:
        reg = self.registry
        self._drain_pending(final)
        tps = self.tokens_per_sec()
        reg.gauge("train/tokens_per_sec").set(tps)
        if self.flops_per_token:
            mflops = tps * self.flops_per_token
            reg.gauge("train/model_tflops").set(mflops / 1e12)
            if self.peak_flops:
                reg.gauge("train/mfu").set(mflops / self.peak_flops)
        self._memory_gauges()
        reg.flush(step)

    def _memory_gauges(self) -> None:
        # lazy import: profiler imports observability, not vice versa
        from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
            device_memory_mb,
        )

        stats = device_memory_mb()
        if stats:
            self.registry.gauge("device/mem_mb", stat="current").set(
                stats["current"])
            self.registry.gauge("device/mem_mb", stat="peak").set(
                stats["peak"])

    def close(self, step: Optional[int] = None) -> None:
        """Final flush (drains ALL buffered device scalars). Idempotent
        until the next ``__call__``, which re-arms the instance — one
        telemetry object may serve several consecutive loops."""
        if self._closed:
            return
        self._closed = True
        self.flush(step, final=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# ---------------------------------------------------------------------------
# predicted per-strategy comm volume (from the plan JSON)
# ---------------------------------------------------------------------------


def layer_param_mb(model) -> float:
    """Per-decoder-layer parameter megabytes at fp32 (the unit
    ``CostContext.parameter_size`` uses)."""
    h = model.hidden_size
    nd = model.num_attention_heads * model.head_dim
    kd = model.kv_heads * model.head_dim
    attn = h * nd + 2 * h * kd + nd * h
    gated = model.hidden_act in ("swiglu", "geglu")
    ffn = (3 if gated else 2) * h * model.ffn_dim
    norms = 2 * h
    return (attn + ffn + norms) * 4 / MB


def plan_comm_volume(
    layers: Sequence[Any],
    model,
    *,
    global_bsz: int,
    chunks: int,
    mixed_precision: bool = True,
) -> List[Dict[str, float]]:
    """Predicted per-step communication megabytes for each layer of a
    strategy plan (``utils.strategy.LayerStrategy`` list, e.g.
    ``hpc.layers``). Mirrors the message-size arithmetic of
    ``cost_model.cost.layer_time_cost`` — dp gradient sync, tp/sp
    activation collectives (x chunks microbatches), cp ring K/V exchange,
    pp activation p2p — so observed runs can be audited against the cost
    model's communication assumptions."""
    seq, h = model.seq_length, model.hidden_size
    param_mb = layer_param_mb(model)
    elem = 2 if mixed_precision else 4
    out = []
    for s in layers:
        dp, cp = s.dp_size, s.cp_size
        # LayerStrategy encodes Ulysses as sp=True with tp_size holding the
        # sequence-parallel degree (utils/strategy.py:53-72)
        ulysses = s.tp_size if s.sp else 1
        tp = 1 if s.sp else s.tp_size
        tp_sp = max(tp, ulysses)
        # ZeRO shard group: dp x sp x cp (SearchStrategy.sdp)
        sdp = max(dp * cp * ulysses, 1)
        lbsz = max(global_bsz // max(chunks, 1) // max(dp, 1), 1)
        # dp gradient sync: ring all-reduce moves 2(d-1)/d of the shard
        grad_mb = param_mb / tp * (0.5 if mixed_precision else 1.0)
        dp_mb = 2 * (sdp - 1) / sdp * grad_mb if sdp > 1 else 0.0
        # tp/sp activation collectives per microbatch (cost.py:147-161:
        # 4 all-to-alls for Ulysses, 6 allgather-equivalents for TP+SP)
        act_mb = lbsz * seq * h * elem / MB
        if tp_sp > 1:
            comm_num = 4 if ulysses > 1 else 6
            if s.checkpoint:
                comm_num = int(comm_num * 1.5)
            tp_mb = act_mb * comm_num * chunks
        else:
            tp_mb = 0.0
        # cp ring: K+V blocks each hop, fwd + bwd(K/V + dK/dV)
        if cp > 1:
            block_mb = lbsz * seq * h / cp * elem / MB
            cp_mb = block_mb * 2 * (cp - 1) * 3 * chunks
        else:
            cp_mb = 0.0
        # pp activation p2p (fwd activation + bwd cotangent)
        pp_mb = (2 * lbsz * seq * h * elem / MB * chunks
                 if s.pp_deg > 1 else 0.0)
        out.append({"dp_allreduce_mb": dp_mb, "tp_collective_mb": tp_mb,
                    "cp_ring_mb": cp_mb, "pp_p2p_mb": pp_mb,
                    "total_mb": dp_mb + tp_mb + cp_mb + pp_mb})
    return out


def _remat_rings(model) -> int:
    """Rings the per-layer remat recompute re-runs in the backward unit.
    The recompute is dead-code-eliminated down to what the backward reads:
    fc2's reduce-scatter ring feeds only the residual output, which no
    gradient needs, so pre-norm layers replay 3 of the 4 forward rings.
    A post-norm layer normalizes that sum, so its recompute keeps all 4."""
    return 4 if model.post_norm else 3


def plan_collective_counts(
    hpc,
    model,
    *,
    num_microbatches: Optional[int] = None,
    tp_overlap: bool = True,
) -> Dict[str, int]:
    """Predicted EXECUTED explicit-collective counts for the compiled
    single-program 1F1B step — the count-side companion of
    :func:`plan_comm_volume` (which predicts megabytes), consumed by the
    static jaxpr census (``analysis/census.py``).

    Only the EXPLICIT collectives are predicted: the shard_map kernels'
    ``lax.ppermute`` rings. GSPMD-inserted collectives (dp gradient
    all-reduce, ZeRO gathers) appear at partition time, not in the jaxpr.
    Counts are per one traced step program, INCLUDING the masked bubble
    ticks the lockstep schedule executes (T = m + 2(pp-1) ticks): volumes
    in :func:`plan_comm_volume` scale with the m real microbatches, so the
    count-derived tp volume equals the MB prediction times T/m.

    Arithmetic (mirrors ops/overlap.py + runtime/compiled_pipeline.py):
    per decoder-layer slot and tick, the forward unit runs 4 rings (qkv,
    out-proj, fc1 — the gated pair counts as ONE rotation — and fc2); the
    backward unit recomputes the stage forward from its stored input
    (``jax.vjp``) and runs the 4 transposed rings, so 8 rings, plus
    the forward recompute under per-layer remat (:func:`_remat_rings`: 3
    rings, 4 in post-norm layers). Each ring is ``tp - 1`` ppermute hops.
    The stage rotations add 2 ppermutes per tick (activations forward,
    cotangents backward).

    Raises ValueError for plan shapes the prediction does not model
    (non-uniform strategies, Ulysses/cp layers — the census still counts
    those programs, there is just no exact-count prediction to pin them
    to).
    """
    s = hpc.layers[0]
    if any(l != s for l in hpc.layers):
        raise ValueError("collective-count prediction needs a uniform "
                         "per-layer strategy (the compiled engine's gate)")
    if s.sp or s.cp_size > 1:
        # the cp-ring / ulysses-a2a kernel hops have no exact prediction
        raise ValueError("collective-count prediction models Megatron-TP "
                         "plans only (no Ulysses / cp ring layers)")
    m = max(num_microbatches if num_microbatches is not None
            else hpc.chunks, 1)
    pp = max(hpc.pp_deg, 1)
    T = m + 2 * (pp - 1)
    lps = hpc.pp_division[0] if hpc.pp_division else len(hpc.layers)
    out: Dict[str, int] = {}
    if pp > 1:
        out["ppermute_pp"] = 2 * T
    tp = s.tp_size
    if tp_overlap and tp > 1:
        rings_per_tick = 4 + 8 + (_remat_rings(model) if s.checkpoint else 0)
        out["ppermute_tp"] = T * lps * rings_per_tick * (tp - 1)
    return out


def plan_collective_bytes(
    hpc,
    model,
    *,
    num_microbatches: Optional[int] = None,
    tp_overlap: bool = True,
    elem_bytes: int = 4,
) -> Dict[str, float]:
    """Predicted per-device EXECUTED explicit-collective megabytes for the
    compiled single-program 1F1B step — the byte-side companion of
    :func:`plan_collective_counts` (counts) and :func:`plan_comm_volume`
    (per-microbatch message megabytes), consumed by the sharding-flow
    byte census (``analysis/sharding_flow.py``).

    Derivation (same message arithmetic as :func:`plan_comm_volume`'s
    ``act_mb = lbsz * seq * h * elem``, re-expressed in the executed
    schedule's units):

    * **tp rings** — each of :func:`plan_collective_counts`'s
      ``T * lps * rings_per_tick * (tp-1)`` ppermute hops carries one
      per-device sequence chunk ``act_mb / tp`` (``ops/overlap.py`` rings
      rotate ``[lbsz, seq/tp, hidden]`` blocks; every fwd/bwd/recompute
      ring's hop payload is that same chunk shape).
    * **pp rotations** — ``2 * T`` stage rotations, each moving one
      per-device slice of the stacked activation: ``act_mb / tp`` under
      Megatron-SP (the boundary activation is sequence-sharded over tp),
      the full ``act_mb`` at tp = 1.

    Counts include the masked bubble ticks (T = m + 2(pp-1)), exactly as
    the traced program executes them — so traced bytes == predicted bytes
    with no tolerance. ``elem_bytes`` must match the traced compute dtype
    (the census traces in f32 → 4; note a bf16 program would ALSO move
    f32 ring accumulators, which this arithmetic does not model — trace
    in f32 to cross-check).

    Raises ValueError for plan shapes the prediction does not model, the
    same gate as :func:`plan_collective_counts` (non-uniform strategies,
    Ulysses/cp layers).
    """
    s = hpc.layers[0]
    if any(l != s for l in hpc.layers):
        raise ValueError("collective-byte prediction needs a uniform "
                         "per-layer strategy (the compiled engine's gate)")
    if s.sp or s.cp_size > 1:
        raise ValueError("collective-byte prediction models Megatron-TP "
                         "plans only (no Ulysses / cp ring layers)")
    m = max(num_microbatches if num_microbatches is not None
            else hpc.chunks, 1)
    pp = max(hpc.pp_deg, 1)
    tp = max(s.tp_size, 1)
    T = m + 2 * (pp - 1)
    lps = hpc.pp_division[0] if hpc.pp_division else len(hpc.layers)
    lbsz = max(hpc.global_bsz // m // max(s.dp_size, 1), 1)
    act_mb = lbsz * model.seq_length * model.hidden_size * elem_bytes / MB
    out: Dict[str, float] = {}
    if pp > 1:
        out["ppermute_pp"] = 2 * T * act_mb / tp
    if tp_overlap and tp > 1:
        rings_per_tick = 4 + 8 + (_remat_rings(model) if s.checkpoint else 0)
        out["ppermute_tp"] = (T * lps * rings_per_tick * (tp - 1)
                              * act_mb / tp)
    return out


def plan_tp_overlap_hidden_frac(hpc, model, overlapped: Sequence[int],
                                mixed_precision: bool = True) -> float:
    """Predicted fraction of the plan's TP collective traffic hidden under
    compute by the decomposed overlap matmuls: the volume-weighted share
    (``plan_comm_volume``'s per-layer ``tp_collective_mb``) carried by the
    layers actually running overlapped (``overlapped`` = indices where
    ops/overlap.plan_overlap_reasons reported None). In the cost model's
    compute-bound regime that traffic is hidden up to the overlap-slowdown
    residue (cost_model.cost.tp_overlap_hidden_frac); this gauge reports
    the coverage term, which needs no hardware profile at runtime."""
    vols = plan_comm_volume(hpc.layers, model, global_bsz=hpc.global_bsz,
                            chunks=max(hpc.chunks, 1),
                            mixed_precision=mixed_precision)
    total = sum(v["tp_collective_mb"] for v in vols)
    if not total:
        return 0.0
    hidden = sum(vols[i]["tp_collective_mb"] for i in overlapped)
    return hidden / total


def emit_plan_telemetry(registry: MetricsRegistry, hpc, model,
                        mixed_precision: bool = True) -> None:
    """Emit the plan's predicted comm volume as ONE ``plan`` event at
    startup. The per-layer numbers are constants of the plan, so they ride
    the one-shot event's ``layers`` list instead of registered gauges —
    gauges re-snapshot into the sink on EVERY registry flush, which
    duplicated ~4*layers identical records per flush for the whole run."""
    vols = plan_comm_volume(hpc.layers, model, global_bsz=hpc.global_bsz,
                            chunks=max(hpc.chunks, 1),
                            mixed_precision=mixed_precision)
    total = sum(v["total_mb"] for v in vols)
    registry.event("plan", {
        "global_bsz": hpc.global_bsz, "chunks": hpc.chunks,
        "pp_deg": hpc.pp_deg, "predicted_comm_mb_per_step": total,
        "layers": [
            {"layer": i,
             **{coll: mb for coll, mb in v.items() if mb}}
            for i, v in enumerate(vols)],
    })
