"""Close the loop: device-time attribution, program cost accounting, and
the predicted-vs-actual plan audit.

Everything upstream of this module *predicts*: the search engine prices a
plan with an analytical cost model, ``plan_comm_volume`` predicts what the
plan should communicate, and ``profile_alpha_beta`` fits latency/bandwidth
pairs. Nothing checked those predictions against what the hardware actually
did — the exact drift failure mode "Revisiting the Time Cost Model of
AllReduce" (PAPERS.md) documents. This module is the feedback half:

* **Trace parsing** — :func:`load_trace` reads the Chrome-trace JSON that
  ``jax.profiler.stop_trace`` writes under ``<trace_dir>/plugins/profile/
  <run>/*.trace.json.gz`` (this jax pin emits it next to the xplane proto;
  stdlib gzip+json, no tensorflow needed). Torn/corrupt captures from
  crashed runs are skipped, not fatal.
* **Attribution** — :func:`attribute` classifies device-op events into
  compute vs collective categories by HLO op-name stem (``all-reduce``,
  ``all-gather``/``reduce-scatter``, ``all-to-all``,
  ``collective-permute``), reconstructs host ``span()`` paths by interval
  containment, attributes device time to annotations that propagated onto
  device tracks (TPU; the CPU thunk trace carries ``hlo_op`` args
  instead), and measures per-track idle time — the pipeline-bubble proxy.
* **Cost accounting** — :func:`jit_cost_summary` /
  :func:`maybe_record_jit_cost` wrap ``Lowered.cost_analysis()`` (no
  backend compile — see the function docstring) so the train-step, both
  pipeline engines, and the serving prefill/decode programs publish their
  XLA-counted flops/bytes as ``cost/*`` gauges.
* **Plan audit** — :func:`audit_plan` diffs the plan's predicted
  per-component communication (``plan_comm_volume`` message sizes priced
  through the fitted α-β pairs) against the measured attribution and emits
  ``audit/*`` gauges plus one ``plan_audit`` event;
  ``cli/summarize.py`` renders it as a calibration table. This is the
  data source the topology-aware-collectives roadmap item consumes.

Known attribution limits (documented, not hidden): collective→component
mapping is by op kind, so ZeRO-3 parameter all-gathers land in the ``tp``
bucket; the HOST pipeline engine moves stage activations with
``jax.device_put`` DMAs, which never appear as ``collective-permute`` HLOs
(the compiled engine's ``ppermute`` transfers do) — its ``pp`` component
therefore measures near zero on the host path and the bubble/idle metric
carries the schedule cost instead.
"""

from __future__ import annotations

import gc
import glob
import gzip
import json
import os
import re
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from hetu_galvatron_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
)

MB = 1024 * 1024

# ---------------------------------------------------------------------------
# trace loading (Chrome trace event format, jax.profiler output)
# ---------------------------------------------------------------------------


def latest_profile_dir(trace_dir: str) -> Optional[str]:
    """Newest ``plugins/profile/<run>`` directory under a TraceCapture
    trace_dir (run names are timestamps, so lexicographic max = newest);
    None when no capture ever flushed."""
    runs = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*")))
    runs = [r for r in runs if os.path.isdir(r)]
    return runs[-1] if runs else None


@dataclass
class TraceData:
    """Merged events + track names from one profile run directory."""

    events: List[dict]
    process_names: Dict[int, str] = field(default_factory=dict)
    thread_names: Dict[Tuple[int, int], str] = field(default_factory=dict)
    path: str = ""


def load_trace(trace_dir: str) -> TraceData:
    """Parse the newest capture under ``trace_dir``. Accepts either the
    TraceCapture root (``<dir>/plugins/profile/<run>/...``) or a run
    directory itself. Unreadable/torn files are skipped — a crashed run's
    half-written capture must not kill the post-mortem."""
    run = trace_dir
    if not glob.glob(os.path.join(run, "*.trace.json*")):
        found = latest_profile_dir(trace_dir)
        if found is None:
            raise FileNotFoundError(
                f"no trace capture under {trace_dir!r} (expected "
                "plugins/profile/<run>/*.trace.json.gz)")
        run = found
    events: List[dict] = []
    procs: Dict[int, str] = {}
    threads: Dict[Tuple[int, int], str] = {}
    for path in sorted(glob.glob(os.path.join(run, "*.trace.json.gz"))
                       + glob.glob(os.path.join(run, "*.trace.json"))):
        try:
            opener = gzip.open if path.endswith(".gz") else open
            with opener(path, "rt") as f:
                obj = json.load(f)
        except (OSError, EOFError, json.JSONDecodeError, UnicodeDecodeError):
            continue
        for e in obj.get("traceEvents", []) if isinstance(obj, dict) else []:
            if not isinstance(e, dict):
                continue
            ph = e.get("ph")
            if ph == "M":
                args = e.get("args") or {}
                if e.get("name") == "process_name":
                    procs[e.get("pid")] = str(args.get("name", ""))
                elif e.get("name") == "thread_name":
                    threads[(e.get("pid"), e.get("tid"))] = str(
                        args.get("name", ""))
            elif ph == "X" and isinstance(e.get("dur"), (int, float)):
                events.append(e)
    return TraceData(events, procs, threads, run)


# ---------------------------------------------------------------------------
# event classification
# ---------------------------------------------------------------------------

# HLO op-name stems -> collective category. Async pairs
# ("all-reduce-start"/"-done") both match their stem, so their durations
# sum into the same bucket.
_COLLECTIVE_STEMS: Tuple[Tuple[str, str], ...] = (
    ("all-reduce", "allreduce"),
    ("reduce-scatter", "reducescatter"),
    ("all-gather", "allgather"),
    ("all-to-all", "alltoall"),
    ("collective-permute", "permute"),
    ("collective-broadcast", "broadcast"),
    ("send", "p2p"),
    ("recv", "p2p"),
)

# span()-style annotation names: slash-separated identifier segments
# ("train/step", "pp/fwd_s0", "layer3/attn"). HLO instruction names
# ("fusion.12", "all-reduce.1") never contain '/'.
_ANNOTATION_RE = re.compile(r"^[\w.\-]+(/[\w.\-]+)+$")
_LAYER_RE = re.compile(r"(?:^|/)layer[_]?(\d+)(?:/|$)")

# permute-source markers: the kernels stamp their collective-permutes with
# jax.named_scope metadata (ops/overlap.py TP_RING_SCOPE, ring_attention's
# cp_ring, mesh.make_pp_rotation's pp_rotate) that shows up in the trace
# event name or its tf_op/long_name args. A marked permute is billed to its
# OWN component even when tp-ring, cp-ring and pp stage rotations share one
# compiled program — the plan-level "permute -> pp iff pipelined" heuristic
# only covers whatever remains unmarked.
_PERMUTE_MARKERS: Tuple[Tuple[str, str], ...] = (
    ("tp_ring", "permute_tp"),
    ("cp_ring", "permute_cp"),
    ("pp_rotate", "permute_pp"),
)
# device-propagated span() names whose covered permute time belongs to tp
# (the overlapped-TP step annotation, cli/train_dist.py)
_TP_SPAN = "tp/overlap_step"


def op_category(name: str) -> str:
    base = name.lower()
    for stem, cat in _COLLECTIVE_STEMS:
        if base.startswith(stem):
            return cat
    return "compute"


def _is_annotation(name: str) -> bool:
    return bool(_ANNOTATION_RE.match(name))


def _merged_busy_ms(intervals: List[Tuple[float, float]]) -> float:
    """Total covered µs->ms of possibly-overlapping (start, end) pairs."""
    if not intervals:
        return 0.0
    intervals.sort()
    busy = 0.0
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    busy += cur_e - cur_s
    return busy / 1000.0


@dataclass
class Attribution:
    """Measured device-time breakdown of one captured trace window.

    Per-device quantities divide the summed device-track time by the
    number of tracks, so they compare directly against the cost model's
    per-device per-step predictions once divided by ``steps``."""

    steps: int = 0
    tracks: int = 0
    wall_ms: float = 0.0              # first-to-last device op, one track's view
    device_busy_ms: float = 0.0       # summed over tracks
    per_device_busy_ms: float = 0.0
    bubble_ms: float = 0.0            # per-device idle inside the wall window
    bubble_frac: float = 0.0
    categories_ms: Dict[str, float] = field(default_factory=dict)  # per-device
    per_module_ms: Dict[str, float] = field(default_factory=dict)  # per-device
    host_span_ms: Dict[str, float] = field(default_factory=dict)   # host wall
    device_annotation_ms: Dict[str, float] = field(default_factory=dict)
    per_layer_ms: Dict[int, float] = field(default_factory=dict)

    @property
    def collective_ms(self) -> float:
        return sum(v for k, v in self.categories_ms.items()
                   if k != "compute")

    @property
    def compute_ms(self) -> float:
        return self.categories_ms.get("compute", 0.0)


# host-span names that mark one optimizer step, tried in order: the SPMD
# trainer loop, the compiled 1F1B engine, and the host pipeline engine
# (one "pp/update" per step).
STEP_SPANS = ("train/step", "pp/compiled_step", "pp/update")


def attribute(trace: TraceData,
              step_spans: Sequence[str] = STEP_SPANS) -> Attribution:
    """Attribute the captured window. Device-op events are those carrying
    ``hlo_op``/``hlo_module`` args (CPU thunk trace) or riding a
    ``/device:*`` process (TPU tracks); annotation events are ``span()``
    names, reconstructed into nesting paths per thread by interval
    containment."""
    dev_events: List[Tuple[int, int, float, float, str, str, str]] = []
    ann_events: List[Tuple[int, int, float, float, str]] = []
    for e in trace.events:
        name = str(e.get("name", ""))
        args = e.get("args") if isinstance(e.get("args"), dict) else {}
        pid, tid = e.get("pid"), e.get("tid")
        ts, dur = float(e.get("ts", 0.0)), float(e.get("dur", 0.0))
        on_device = trace.process_names.get(pid, "").startswith("/device")
        # marker hint: the HLO metadata path (named_scope) rides in the
        # event name on some backends and in tf_op/long_name args on others
        hint = " ".join((name, str(args.get("tf_op", "")),
                         str(args.get("long_name", ""))))
        if "hlo_op" in args or "hlo_module" in args:
            dev_events.append((pid, tid, ts, dur, name,
                               str(args.get("hlo_module", "")), hint))
        elif _is_annotation(name):
            ann_events.append((pid, tid, ts, dur, name))
        elif on_device and not name.startswith(("$", "Thread")) \
                and "::" not in name:
            dev_events.append((pid, tid, ts, dur, name, "", hint))

    attr = Attribution()
    if not dev_events and not ann_events:
        return attr

    # -- device tracks: busy/idle + category + module attribution --
    by_track: Dict[Tuple[int, int],
                   List[Tuple[float, float, str, str]]] = {}
    # unmarked collective-permutes per track: candidates for the
    # tp/overlap_step annotation-coverage rebilling below
    bare_permutes: Dict[Tuple[int, int], List[Tuple[float, float]]] = {}
    cats: Dict[str, float] = {}
    mods: Dict[str, float] = {}
    for pid, tid, ts, dur, name, mod, hint in dev_events:
        by_track.setdefault((pid, tid), []).append((ts, dur, name, mod))
        cat = op_category(name)
        if cat in ("permute", "p2p", "broadcast"):
            for marker, key in _PERMUTE_MARKERS:
                if marker in hint:
                    cat = key
                    break
            else:
                if cat == "permute":
                    bare_permutes.setdefault((pid, tid), []).append(
                        (ts, ts + dur))
        cats[cat] = cats.get(cat, 0.0) + dur / 1000.0
        if mod:
            mods[mod] = mods.get(mod, 0.0) + dur / 1000.0
    if by_track:
        w0 = min(ts for evs in by_track.values() for ts, _, _, _ in evs)
        w1 = max(ts + d for evs in by_track.values() for ts, d, _, _ in evs)
        attr.wall_ms = (w1 - w0) / 1000.0
        for evs in by_track.values():
            busy = _merged_busy_ms([(ts, ts + d) for ts, d, _, _ in evs])
            attr.device_busy_ms += busy
            attr.bubble_ms += max(attr.wall_ms - busy, 0.0)
        attr.tracks = len(by_track)
        attr.per_device_busy_ms = attr.device_busy_ms / attr.tracks
        attr.bubble_ms /= attr.tracks
        denom = attr.per_device_busy_ms + attr.bubble_ms
        attr.bubble_frac = attr.bubble_ms / denom if denom > 0 else 0.0
        attr.per_module_ms = {k: v / attr.tracks for k, v in mods.items()}

    # -- annotations: nesting paths (host spans) + device-track attribution
    ann_by_track: Dict[Tuple[int, int], List[Tuple[float, float, str]]] = {}
    for pid, tid, ts, dur, name in ann_events:
        ann_by_track.setdefault((pid, tid), []).append((ts, dur, name))
    # device-propagated tp/overlap_step windows per track: a bare
    # collective-permute inside one is a tp ring hop, not a stage transfer
    tp_windows: Dict[Tuple[int, int], List[Tuple[float, float]]] = {}
    # steps are counted PER TRACK and the max taken: on TPU the step
    # annotation propagates onto every device track too, so a global sum
    # would count (1 + num device tracks) per real step
    step_counts: Dict[str, Dict[Tuple[int, int], int]] = {}
    for (pid, tid), evs in ann_by_track.items():
        # containment stack: events sorted by (start, -dur) so parents
        # precede the children they cover
        evs.sort(key=lambda t: (t[0], -t[1]))
        stack: List[Tuple[float, str]] = []  # (end, path)
        on_device = trace.process_names.get(pid, "").startswith("/device")
        for ts, dur, name in evs:
            while stack and ts >= stack[-1][0] - 1e-9:
                stack.pop()
            path = (stack[-1][1] + "/" + name) if stack else name
            stack.append((ts + dur, path))
            attr.host_span_ms[path] = attr.host_span_ms.get(path, 0.0) \
                + dur / 1000.0
            if name in step_spans:
                per_track = step_counts.setdefault(name, {})
                per_track[(pid, tid)] = per_track.get((pid, tid), 0) + 1
            m = _LAYER_RE.search(name)
            if m is not None:
                attr.per_layer_ms[int(m.group(1))] = attr.per_layer_ms.get(
                    int(m.group(1)), 0.0) + dur / 1000.0
            if on_device and (pid, tid) in by_track:
                if name == _TP_SPAN:
                    tp_windows.setdefault((pid, tid), []).append(
                        (ts, ts + dur))
                # TPU device track: sum the device-op time the annotation
                # interval covers (the propagated-name attribution)
                covered = [(max(ts, ots), min(ts + dur, ots + od))
                           for ots, od, _, _ in by_track[(pid, tid)]
                           if ots < ts + dur and ots + od > ts]
                attr.device_annotation_ms[name] = \
                    attr.device_annotation_ms.get(name, 0.0) + \
                    _merged_busy_ms([c for c in covered if c[1] > c[0]])
    # rebill unmarked permute time covered by a tp/overlap_step window.
    # The span wraps the WHOLE train step (cli/train_dist.py), so this is
    # only sound when the tp ring hops are the sole collective-permutes in
    # the program — the HOST engine's case (its pp transfers are
    # device_puts, so the plan heuristic would mis-bill the rings to pp).
    # Under the COMPILED engine the pp stage rotations are in-program
    # ppermutes inside the same window: there the named_scope markers
    # above are the only sound disambiguator, and if they failed to
    # propagate, rebilling every bare permute to tp would mis-bill the
    # stage rotations — strictly worse than the plan heuristic. The
    # pp/compiled_step span (a TraceAnnotation, present even when HLO
    # metadata is stripped) is the evidence the compiled engine ran, and
    # it disables the window pass.
    compiled_pp_ran = any(name == "pp/compiled_step"
                          for _, _, _, _, name in ann_events)
    moved_us = 0.0
    for key, perms in ({} if compiled_pp_ran
                       else bare_permutes).items():
        wins = sorted(tp_windows.get(key) or [])
        if not wins:
            continue
        merged: List[Tuple[float, float]] = [wins[0]]
        for ws, we in wins[1:]:  # overlapping windows must not double-bill
            if ws <= merged[-1][1]:
                merged[-1] = (merged[-1][0], max(merged[-1][1], we))
            else:
                merged.append((ws, we))
        for ps, pe in perms:
            moved_us += sum(max(0.0, min(pe, we) - max(ps, ws))
                            for ws, we in merged)
    if moved_us:
        moved = moved_us / 1000.0
        cats["permute"] = max(cats.get("permute", 0.0) - moved, 0.0)
        cats["permute_tp"] = cats.get("permute_tp", 0.0) + moved
        if not cats["permute"]:
            cats.pop("permute", None)
    if attr.tracks:
        attr.categories_ms = {k: v / attr.tracks for k, v in cats.items()}
    for name in step_spans:  # first marker that fired wins
        if step_counts.get(name):
            attr.steps = max(step_counts[name].values())
            break
    return attr


# ---------------------------------------------------------------------------
# compiled-program cost accounting (Compiled.cost_analysis)
# ---------------------------------------------------------------------------


def jit_cost_summary(fn: Any, args: Sequence[Any] = (),
                     kwargs: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, float]:
    """XLA's own static accounting for one jitted program: flops and bytes
    accessed, read from the LOWERED module (``Lowered.cost_analysis()``).
    Deliberately NO backend compile: on this jax pin an AOT
    ``.lower().compile()`` does not populate the jit dispatch cache, so
    compiling here would double every instrumented program's compile time
    (minutes for the fused 1F1B program on TPU). ``args`` may be concrete
    arrays or ``ShapeDtypeStruct``s — lowering never executes and never
    consumes donated buffers. Returns {} when the backend cannot answer
    (and never raises: this is telemetry, not the product)."""
    try:
        ca = fn.lower(*args, **(kwargs or {})).cost_analysis()
        d = ca[0] if isinstance(ca, (list, tuple)) and ca else (ca or {})
        out: Dict[str, float] = {}
        if d.get("flops"):
            out["flops"] = float(d["flops"])
        if d.get("bytes accessed"):
            out["bytes_accessed"] = float(d["bytes accessed"])
        return out
    except Exception:  # noqa: BLE001 — observability must never break a run
        return {}


_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
COLLECTIVE_OPS = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
                  "collective-permute")

# ---------------------------------------------------------------------------
# the compiled step's HLO, read once
# ---------------------------------------------------------------------------

# The ``jax.named_scope`` names the step program carries (models/,
# runtime/trainer.py), a closed vocabulary: a TPU trace names its events by
# HLO instruction (``fusion.12``), and the scope an instruction came from
# is only in the optimized HLO's ``op_name``. A name is here because a
# per-layer metric or a line of ``tools/trace_by_scope.py``'s table reads it
# (PERF.md section 3), and tests/observability/test_step_map.py finds every
# literal of those files in it.
SCOPES: Tuple[str, ...] = (
    "embed", "norm", "attn/qkv_proj", "attn/qk_norm", "attn/rope",
    "attn/core", "attn/window_core", "attn/cross_core", "attn/diff",
    "attn/gate", "attn/out_proj",
    "attn/latent_proj", "hc/maps", "hc/mix",
    "mtp/embed_proj", "mtp/block", "mtp/head",
    # (a tower's own before the names they end in: of two names that end at
    # one place in a name stack the first listed is taken)
    "tower/patch_embed", "tower/attn_proj", "tower/attention", "tower/mlp",
    "tower/merge_project", "embed/place_images",
    "mlp", "head", "param_view",
    "grad/accumulate", "grad/clip", "optimizer/update",
    "moe/route", "moe/dispatch", "moe/experts", "moe/combine",
    "moe/exchange/gather", "moe/exchange/scatter",
    "mixer/short_conv/in_proj", "mixer/short_conv/gate_conv",
    "mixer/short_conv/out_proj",
    "mixer/mamba/in_proj", "mixer/mamba/conv", "mixer/mamba/ssd",
    "mixer/mamba/gated_norm", "mixer/mamba/out_proj",
    "mixer/kda/in_proj", "mixer/kda/conv", "mixer/kda/gates",
    "mixer/kda/scan", "mixer/kda/gated_norm", "mixer/kda/out_proj",
    "mixer/mamba1/in_proj", "mixer/mamba1/conv", "mixer/mamba1/x_proj",
    "mixer/mamba1/scan", "mixer/mamba1/gate", "mixer/mamba1/out_proj",
    "mixer/gmu/in_proj", "mixer/gmu/gate", "mixer/gmu/out_proj",
    "mixer/gdn/in_proj", "mixer/gdn/conv", "mixer/gdn/gates",
    "mixer/gdn/scan", "mixer/gdn/gated_norm", "mixer/gdn/out_proj")
PHASES = ("forward", "recompute", "backward", "update", "other")
# the scopes ``step_scopes()["scopes"]`` lists by instruction, by mixer kind
# (what the ``granite_*`` readers join a trace to, by PR 35's rule: an
# instruction by its own ``op_name``)
MIXER_SCOPES: Dict[str, Tuple[str, ...]] = {
    kind: tuple(s for s in SCOPES if s.startswith(f"mixer/{kind}/"))
    for kind in dict.fromkeys(s.split("/")[1] for s in SCOPES
                              if s.startswith("mixer/"))}
# the further prediction depth's three parts: what is under them by the
# deepest scope is the block's own (``attn/core``, ``moe/experts``, ``head``),
# so a reader that wants the depth's whole time takes these lists
MTP_SCOPES = tuple(s for s in SCOPES if s.startswith("mtp/"))
# what ``step_scopes()["scopes"]`` lists by instruction: every mixer's
# parts and the further depth's
OWN_SCOPES = MTP_SCOPES + tuple(
    s for scopes in MIXER_SCOPES.values() for s in scopes)
# the scope of the state-space scan, whose Mosaic calls the step report
# counts (``ssd/mosaic_calls``; ops/pallas/ssd.py traces under it)
SSD_SCOPE = "mixer/mamba/ssd"
# the scope of a mamba block's skip and gated norm, whose Mosaic calls the
# step report counts (``gated_norm/mosaic_calls``;
# ops/pallas/gated_norm.py's backward traces under the scope it is told)
GATED_NORM_SCOPE = "mixer/mamba/gated_norm"

# the scope of a kda block's recurrence, whose loops or kernels the step
# report counts (:func:`kda_loops`, :func:`kda_kernel_calls`;
# ops/pallas/kda.py traces under it), and the forward kernel's name there
KDA_SCAN_SCOPE = "mixer/kda/scan"
KDA_FWD_CALL = "kda_scan_fwd"
# the scope of a mamba1 block's selective scan, whose Mosaic calls the step
# report counts (``selective/mosaic_calls``;
# ops/pallas/selective_scan.py traces under it)
SELECTIVE_SCOPE = "mixer/mamba1/scan"
# the scope of a linear_attention block's recurrence, whose Mosaic calls the
# step report counts (``gdn/mosaic_calls``; ops/pallas/gdn.py traces under
# it)
GDN_SCAN_SCOPE = "mixer/gdn/scan"
# the forward kernels of the recurrent mixers' scans (ops/pallas/kda.py,
# ops/pallas/gdn.py, ops/pallas/ssd.py, ops/pallas/selective_scan.py),
# which :func:`scans_recomputed` counts
SCAN_FWD_CALLS = (KDA_FWD_CALL, "gdn_scan_fwd", "ssd_scan_fwd",
                  "selective_scan_fwd")
_OPERAND_SHAPES = "operand_layout_constraints="
_TRIP_COUNT = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_BODY = re.compile(r"body=%?([\w.\-]+)")
# an array as HLO prints it: its element type, that type's bits (``f32``,
# 32; none for pred) and its dimensions (none for a scalar)
_ARRAY = re.compile(r"\b(pred|[sufb]\w*?(\d+)\w*)\[([\d,]*)\]")


def _array_dims(text: str, at: int = 0) -> List[str]:
    """The printed dimensions (``"1,4096,50304"``) of the arrays of rank
    one and more in ``text`` from ``at`` on."""
    return [dims for _, _, dims in _ARRAY.findall(text, at) if dims]

# the scopes a causal depthwise convolution is entered under, one a mixer
# kind, and its kernels' names there (ops/pallas/conv.py), which the step
# report counts by phase (:func:`conv_kernel_calls`)
CONV_SCOPES = ("mixer/kda/conv", "mixer/mamba/conv", "mixer/mamba1/conv",
               "mixer/short_conv/gate_conv", "mixer/gdn/conv")
CONV_CALLS = ("causal_conv_fwd", "causal_conv_bwd")

# the scope of an expert block's grouped matmuls and the names of the
# program's own kernels there (ops/pallas/grouped_matmul.py traces under it),
# whose Mosaic calls the step report counts (``experts/mosaic_calls``:
# :func:`experts_kernel_calls`)
EXPERTS_SCOPE = "moe/experts"
EXPERTS_CALLS = ("grouped_matmul_fwd", "grouped_matmul_drows",
                 "grouped_matmul_dweights")

# the flash forward kernel's name (ops/pallas/flash_attention.py), which its
# instruction in the compiled step carries (``flash_attention_fwd.3``)
FLASH_FWD_CALL = "flash_attention_fwd"

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_APPLIES = re.compile(r"to_apply=%?([\w.\-]+)")
# ``jvp(mixer/mamba)/ssd`` and ``transpose(jvp(mixer/mamba))/ssd`` are both
# under ``mixer/mamba/ssd``: a transformation wraps the part of the name
# stack it was applied under
_TRANSFORM = re.compile(r"\w+\(|\)")
# ``jit(silu)`` is a function's name and no scope
_JIT = re.compile(r"jit\([^()]*\)")
_OPERAND = re.compile(r"%([\w.\-]+)")
# an ``op_name`` that is no name stack: libtpu gives the Mosaic calls it
# lowers ``lax.ragged_dot`` to these, and the program calls ``ragged_dot``
# for the experts' grouped matmuls alone (models/moe.py, under moe/experts)
KERNEL_SCOPES = {"ragged-dot-none": "moe/experts",
                 "ragged-dot-metadata": "moe/experts"}
# a pass of a step needs the ones before it
_PASS_ORDER = ("forward", "recompute", "backward")
# opcodes that, left in an OPTIMIZED HLO outside a fusion, are a pass over
# their operand that computes nothing: XLA has made every reshape and
# transpose that is one a ``bitcast`` by then
RELAYOUT_OPS = ("reshape", "copy", "transpose")
# the asynchronous pairs that move an array between memory spaces on one
# chip (XLA:TPU prints a prefetch into ``S(1)`` as one of these two, or, an
# attached chip's text for the slices, as an ``async-start`` / ``async-done``
# pair that calls a computation holding the ``slice``: found on the chip,
# PR 73); every other ``-start`` is half of a collective
PREFETCH_STARTS = ("copy-start", "slice-start")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_INDEX = re.compile(r"index=(\d+)")
_SPACE = re.compile(r"S\(\d+\)")
# how far the step's data is followed from an instruction that has no scope
# to one that has (a copy, its ``-start`` and ``-done``, a bitcast and a
# ``ConcatBitcast`` are four)
OWNER_HOPS = 8
# what packs values so that a user further on takes one of many: the walk
# to an owner does not pass through them
_PACKING = ("tuple", "while", "conditional", "call")
# what hands a value on unchanged: a transfer's producer and a custom
# call's operands are looked for behind these
_VIEWS = ("bitcast", "get-tuple-element")


def walk_hlo(hlo_text: str):
    """The one walk over an optimized HLO text that every reader of it
    shares (``step_hlo`` below, ``tools/aot_hlo_report.py``): yields
    ``(computation, name, opcode, op_name, calls, line, at)`` for each
    instruction in the text's order; ``op_name`` is "" and ``calls`` (the
    computation a fusion runs) None where the line has none;
    ``line[at:]`` begins with the first operand."""
    comp = ""
    for line in hlo_text.splitlines():
        if not line:
            continue
        if not line[0].isspace():
            m = _HEADER.match(line)
            if m:
                comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        at = line.find('op_name="')
        op_name = _OP_NAME.match(line, at).group(1) if at >= 0 else ""
        at = line.find("calls=")
        yield (comp, m.group(1), m.group(2), op_name,
               _CALLS.match(line, at).group(1) if at >= 0 else None, line,
               m.end())


def scope_and_phase(op_name: str) -> Tuple[Optional[str], str]:
    """The scope and the phase an ``op_name`` says.

    ``scope``: the name of ``SCOPES`` that ends deepest in the name stack,
    once the transformation wrappers (``jvp(``, ``transpose(``, ``)``) and
    the jitted functions' names (``jit(silu)``) are taken out; None where
    there is none.

    ``phase``, the first of these that holds:

    * ``recompute``: ``rematted_computation`` is in it
      (``jit(step)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/
      rematted_computation/bsh,hf->bsf/dot_general``: the forward made
      again inside the backward pass);
    * ``backward``: ``transpose(`` is
      (``.../closed_call/transpose(jvp(jvp()))/checkpoint/
      jit(flash_attention_bwd_hmajor)/flash_attention_bwd_dq/pallas_call``);
    * ``forward``: ``jvp(`` is (``.../closed_call/jvp(jit(silu))/div``);
    * ``update``: its scope lies under ``optimizer/``
      (``jit(step)/optimizer/update/mul``);
    * ``other``: the rest, what a step does once outside the three passes
      (``jit(step)/grad/clip/reduce_sum``, ``jit(step)/while/body/squeeze``).

    An ``op_name`` that is no name stack (none at all: a copy or a convert
    XLA made; or a kernel's own, ``KERNEL_SCOPES``) says ``other`` here, and
    :func:`step_hlo` asks the instruction's operands."""
    path = "/" + _TRANSFORM.sub("", _JIT.sub("", op_name)) + "/"
    scope, end = KERNEL_SCOPES.get(op_name), -1
    for s in SCOPES:
        at = path.rfind("/" + s + "/")
        if at >= 0 and at + len(s) > end:
            scope, end = s, at + len(s)
    if "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    elif scope is not None and scope.startswith("optimizer/"):
        phase = "update"
    else:
        phase = "other"
    return scope, phase


def _op_tail(op_name: str, parts: int = 3) -> str:
    return "/".join(op_name.split("/")[-parts:])


def result_type(line: str, opcode: str) -> str:
    """The result type an instruction's line prints before its opcode
    (``line`` and ``opcode`` as :func:`walk_hlo` yields them)."""
    return line.split(" = ", 1)[1].split(f" {opcode}(", 1)[0]


def result_bytes(shape: str) -> int:
    """Bytes of the arrays a printed result type names
    (``f32[1,4096,50304]{2,1,0:T(8,128)}``; a tuple's summed)."""
    total = 0
    for _, bits, dims in _ARRAY.findall(shape):
        n = int(bits or 8)      # (pred: a byte)
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n // 8
    return total


def step_hlo(hlo_text: str, own_scopes: Sequence[str] = OWN_SCOPES
             ) -> Dict[str, Any]:
    """Everything the program keeps of its compiled step's optimized HLO,
    from one walk over the text.

    ``mosaic_custom_calls`` and ``collectives``: see :func:`hlo_counts`.
    ``relayouts``: the ``reshape``, ``copy`` and ``transpose`` instructions
    (``RELAYOUT_OPS``) the optimized step still holds outside its fusions,
    each a pass over an array that computes nothing: ``{"bytes": their
    result bytes summed (the gauge ``step/relayout_bytes``; a loop body's
    counted once however often it runs), "count", "largest": {"bytes",
    "opcode", "shape", "op_name" (its tail, ``(none)`` where XLA gave it
    none)} or None}``. (Found in PR 68:
    ``reshape f32[206045184]`` and back, 18 ms a step of ``olmoe_c1_s4k``,
    around the scatter-add a gathered label's logit transposes to.)
    ``scopes``, ``instructions``, ``mosaic_calls``: see
    :func:`scope_instructions` (``own_scopes`` are its ``scopes``).
    ``flow``: ``{"prefetches", "prefetch_bytes", "unowned_instructions"}``,
    the counts of the map's ``transfers`` of kind ``prefetch``, their bytes
    and the instructions under no scope that are not in its ``owners`` (the
    gauges ``step/prefetches``, ``step/prefetch_bytes``,
    ``step/unowned_instructions``).

    ``map``: each instruction of a computation that is no fusion's and no
    reduction's (an event of a TPU trace is one of these, named by it),
    ``{"instructions": {name: (scope, phase, collective)}, "inferred":
    [names], "tails": {name: op_name tail}, "transfers", "calls", "owners",
    "relayouts"}``. The last four follow the step's data: the walk keeps
    each such instruction's operands and, from them, its users, in the order
    the program runs them; what goes into a ``while``'s tuple at a place,
    and what its body's root holds there, is used by the body's
    ``get-tuple-element`` of that place (a prefetch started in one trip of a
    loop over the layers feeds the next).

    * ``transfers``: one entry for each asynchronous pair, keyed by the
      starting instruction's name (what a trace's ``Async XLA Ops`` line
      calls it): ``{"done": its other half's name, "kind": ``prefetch`` for
      a ``copy-start`` / ``slice-start`` (how XLA:TPU prints a move between
      memory spaces on one chip), else the collective (``all-gather``),
      "bytes": of the destination (the ``-done``'s result), "space": the
      destination layout's memory space (``S(1)``) or None, "from": (name,
      scope, phase) of what made the moved array, "feeds": (name, scope,
      phase) of the first instruction with a scope that uses the result,
      reached through instructions that have none (a ``ConcatBitcast``, a
      bitcast, a further copy), or None}``.
    * ``calls``: for each ``custom-call``, ``{"target": its
      ``custom_call_target``, "transfers": the transfers whose results are
      among its operands}``: a ``ConcatBitcast`` of four prefetched slices
      names the four ``slice-start``s.
    * ``owners``: for each instruction whose scope is None, ``(scope,
      phase, "user" | "operand", hops)``: the nearest instruction with a
      scope forward through its users (of several as near, the earliest in
      program order), else backward through its operands (the latest),
      ``OWNER_HOPS`` at most and never through a ``tuple`` or a loop other
      than by the place; absent where there is none. A copy XLA made on the
      way into a matmul is that matmul's.
    * ``relayouts``: the names of the ``RELAYOUT_OPS`` instructions counted
      above.

    The triples, ``inferred`` and ``tails`` are what they were before the
    step's data was followed (PR 73); the phase rule below asks the same
    operands.

    * ``scope`` and ``phase`` by :func:`scope_and_phase` from the
      instruction's OWN ``op_name`` (a fusion carries its root's; every
      fusion that holds a matmul carries that matmul's). An instruction with
      no ``op_name`` that calls a fused computation takes the commonest
      scope and the commonest phase of the instructions inside it (where
      none of them has a name, of the reductions they apply: a fused
      reduce-scatter made of a shard_map's ``psum_scatter``), and is
      listed in ``inferred``; any other has no scope. Where the ``op_name``
      is no name stack (no ``/`` in it: none, or ``ragged-dot-none``) and
      nothing inside says a phase, the phase is the latest pass among the
      instruction's operands (forward, then recompute, then backward: a
      copy of what the backward pass made is the backward pass's), else
      ``other``. (Found on the chip, PR 37: the experts' grouped matmuls,
      35 ms a step of ``lfm2moe_c1_s8k``, carry ``op_name=
      "ragged-dot-none"``.)
    * ``collective``: None, or what the instruction moves between chips:
      the opcode for a collective under its own name (``all-gather``; an
      asynchronous one's halves ``all-gather.start`` / ``all-gather.done``);
      ``reduce-scatter.fused`` for a fusion that calls an
      ``all-reduce-scatter*`` computation (how XLA:TPU runs one);
      ``overlapped`` for a fusion that calls an ``async_collective_fusion*``
      (an all-gather riding a matmul: compute, with traffic behind it);
      ``<opcode>.start`` / ``<opcode>.done`` for the fusions around XLA:TPU's
      ``AsyncCollectiveStart`` / ``AsyncCollectiveDone`` custom calls
      (``async-collective-start.N`` / ``-done.N``), the opcode being the
      collective the fused computation holds beside the custom call.
    * ``tails``: for an instruction under no scope, the last parts of its
      ``op_name`` (its opcode where it has none): what
      ``tools/trace_by_scope.py`` prints beside the heaviest of them."""
    # The cyclic collector is held off: the walk makes a few hundred
    # thousand small tuples and lists and no cycle, and the collector's
    # passes over the trainer's whole heap, which those allocations set off,
    # cost more than the walk itself (0.25 s against 0.14 s for an 8 MB text
    # in a process that has imported the trainer).
    collecting = gc.isenabled()
    gc.disable()
    try:
        return _step_hlo(hlo_text, own_scopes)
    finally:
        if collecting:
            gc.enable()


def _step_hlo(hlo_text: str, own_scopes: Sequence[str]) -> Dict[str, Any]:
    """:func:`step_hlo`'s walk."""
    classify: Dict[str, Tuple[Optional[str], str]] = {"": (None, "other")}
    own: Dict[str, Tuple[str, ...]] = {"": ()}
    found: Dict[str, List[str]] = {s: [] for s in own_scopes}
    names, mosaic = set(), set()
    # per computation: its instructions, the collectives it holds by opcode,
    # what is inside it by (scope, phase), and the async half it is
    comps: Dict[str, Dict[str, Any]] = {}
    fused, applied = set(), set()
    for comp, name, opcode, op_name, calls, line, at in walk_hlo(hlo_text):
        c = comps.get(comp)
        if c is None:
            c = comps[comp] = {"rows": [], "held": {}, "inside": {},
                               "half": None, "applies": [], "relayouts": []}
        if op_name not in classify:
            classify[op_name] = scope_and_phase(op_name)
            path = _TRANSFORM.sub("", op_name) + "/"
            own[op_name] = tuple(s for s in own_scopes if s + "/" in path)
        is_mosaic = False
        if opcode == "custom-call":
            is_mosaic = _MOSAIC_CALL in line
            if 'custom_call_target="AsyncCollectiveStart"' in line:
                c["half"] = "start"
            elif 'custom_call_target="AsyncCollectiveDone"' in line:
                c["half"] = "done"
        elif opcode == "fusion":
            if calls:
                fused.add(calls)
        elif opcode in RELAYOUT_OPS:
            shape = result_type(line, opcode)
            c["relayouts"].append((result_bytes(shape), opcode, shape,
                                   _op_tail(op_name) or "(none)"))
        elif opcode != "call" and "to_apply=" in line:
            reduction = _APPLIES.search(line).group(1)
            applied.add(reduction)
            c["applies"].append(reduction)
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if base in COLLECTIVE_OPS:
            c["held"][base] = c["held"].get(base, 0) + 1
        if op_name:
            key = classify[op_name]
            c["inside"][key] = c["inside"].get(key, 0) + 1
        # (operands are read below, and only in the computations whose
        # instructions are a trace's events)
        c["rows"].append((name, opcode, op_name, calls, line, at))
        if is_mosaic:
            mosaic.add(name)
        if "fused_computation" not in comp:
            names.add(name)
            for s in own[op_name]:
                found[s].append(name)

    counts = dict.fromkeys(COLLECTIVE_OPS, 0)
    for comp, c in comps.items():
        if comp.startswith("async_collective_fusion") or c["half"] == "done":
            continue    # these two repeat what the starting fusion holds
        if comp.startswith("all-reduce-scatter"):
            counts["reduce-scatter"] += 1
            continue
        for op, n in c["held"].items():
            counts[op] += n

    def commonest(inside, part):
        tally: Dict[Any, int] = {}
        for key, n in inside.items():
            if key[part] is not None:
                tally[key[part]] = tally.get(key[part], 0) + n
        return max(tally, key=tally.get) if tally else None

    def collective_of(opcode, calls):
        for suffix in ("", "-start", "-done"):
            base = opcode[:-len(suffix)] if suffix else opcode
            if opcode.endswith(suffix) and base in COLLECTIVE_OPS:
                return base + suffix.replace("-", ".")
        if opcode != "fusion" or calls is None:
            return None
        if calls.startswith("all-reduce-scatter"):
            return "reduce-scatter.fused"
        if calls.startswith("async_collective_fusion"):
            return "overlapped"
        callee = comps.get(calls)
        if callee and callee["half"]:   # (each half holds the collective)
            return (next(iter(callee["held"]), "collective") + "."
                    + callee["half"])
        return None

    instructions: Dict[str, Tuple[Optional[str], str, Optional[str]]] = {}
    inferred: List[str] = []
    tails: Dict[str, str] = {}
    relayouts: List[Tuple[int, str, str, str]] = []
    # the step's data, followed: each event's operands and, from them, its
    # users, in the order the program runs them
    flow = _Flow()
    for comp, c in comps.items():
        if comp in fused or comp in applied:
            continue
        relayouts += c["relayouts"]
        for name, opcode, op_name, calls, line, at in c["rows"]:
            operands = flow.add(comp, name, opcode, line, at)
            scope, phase = classify[op_name]
            if not op_name and calls in comps:
                inside = comps[calls]["inside"]
                if not inside:
                    # a ``psum_scatter`` of a shard_map, fused: pad,
                    # all-reduce and slice carry no name, the reduction the
                    # all-reduce applies does
                    for reduction in comps[calls]["applies"]:
                        inside = {**inside, **comps[reduction]["inside"]}
                scope = commonest(inside, 0)
                phase = commonest(inside, 1) or "other"
                if scope is not None:
                    inferred.append(name)
            if phase == "other" and "/" not in op_name:
                # (an operand is earlier in the same computation)
                passes = [instructions[o][1] for o in operands
                          if o in instructions
                          and instructions[o][1] in _PASS_ORDER]
                if passes:
                    phase = max(passes, key=_PASS_ORDER.index)
            instructions[name] = (scope, phase, collective_of(opcode, calls))
            if scope is None:
                tails[name] = _op_tail(op_name) if op_name else opcode
    largest = max(relayouts, default=None)
    followed = flow.read(instructions, lambda comp: comps[comp]["held"])
    prefetches = [t["bytes"] for t in followed["transfers"].values()
                  if t["kind"] == "prefetch"]
    return {"mosaic_custom_calls": len(mosaic), "collectives": counts,
            "flow": {"prefetches": len(prefetches),
                     "prefetch_bytes": sum(prefetches),
                     "unowned_instructions": len(tails) - len(
                         followed["owners"])},
            "relayouts": {
                "bytes": sum(r[0] for r in relayouts),
                "count": len(relayouts),
                "largest": largest and dict(zip(
                    ("bytes", "opcode", "shape", "op_name"), largest))},
            "scopes": found, "instructions": frozenset(names),
            "mosaic_calls": frozenset(n for n in mosaic if n in names),
            "map": {"instructions": instructions, "inferred": inferred,
                    "tails": tails, **followed}}


class _Flow:
    """The def-use structure of the step's events (:func:`step_hlo`'s
    ``transfers``, ``calls``, ``owners`` and ``relayouts``): each
    instruction's operands and users, in program order."""

    def __init__(self):
        self.order: Dict[str, int] = {}
        self.opcode: Dict[str, str] = {}
        self.operands: Dict[str, List[str]] = {}
        self.users: Dict[str, List[str]] = {}
        # (read again for the halves of an asynchronous pair, the moved
        # array's type, and for the custom calls, their target)
        self.lines: Dict[str, str] = {}
        self.relayouts: List[str] = []
        # {(a tuple's instruction, a place in it): what takes that place}
        self.elements: Dict[Tuple[str, int], List[str]] = {}
        self.loops: List[Tuple[str, str]] = []
        self.carried = False
        # a computation's parameter and its root (its last instruction)
        self.params: Dict[str, str] = {}
        self.roots: Dict[str, str] = {}
        # the computation an instruction is in, and for one that a generic
        # ``async-start`` calls, that start
        self.comp: Dict[str, str] = {}
        self.wrapped: Dict[str, str] = {}

    def add(self, comp: str, name: str, opcode: str, line: str,
            at: int) -> List[str]:
        operands = _OPERAND.findall(line, at, line.find(")", at))
        self.roots[comp] = name
        self.comp[name] = comp
        self.order[name] = len(self.order)
        self.opcode[name] = opcode
        self.operands[name] = operands
        for o in operands:
            self.users.setdefault(o, []).append(name)
        self.lines[name] = line
        if opcode in RELAYOUT_OPS:
            self.relayouts.append(name)
        elif opcode == "get-tuple-element":
            index = int(_INDEX.search(line, at).group(1))
            self.elements.setdefault((operands[0], index), []).append(name)
        elif opcode == "while":
            self.loops.append((name, _BODY.search(line, at).group(1)))
        elif opcode == "parameter":
            self.params.setdefault(comp, name)
        elif opcode == "async-start":
            called = _CALLS.search(line, at)
            if called:
                self.wrapped[called.group(1)] = name
        return operands

    def _carry(self, name: str, body: str) -> None:
        """The edges a loop hides: what goes into ``name``'s tuple at a
        place, and what the ``body``'s root tuple holds there, is used by
        the body's ``get-tuple-element`` of that place (the next trip's) and
        by the loop's own after it."""
        entering = self._behind(self.operands[name][0])
        param, root = self.params.get(body), self.roots.get(body)
        if self.opcode.get(entering) != "tuple" or param is None \
                or self.opcode.get(root) != "tuple":
            return
        for at, (first, again) in enumerate(zip(
                self.operands[entering], self.operands[root])):
            inside = self.elements.get((param, at), [])
            for made, taken in ((first, inside),
                                (again, inside + self.elements.get(
                                    (name, at), []))):
                if taken:
                    self.users.setdefault(made, []).extend(taken)
                for n in taken:
                    self.operands[n].append(made)
            self.carried = self.carried or bool(inside)

    def _nearest(self, instructions, edges, names, latest=False):
        """{name: (hops, place, scoped instruction)}: along ``edges`` (users
        or operands) the nearest instruction that has a scope, through
        instructions that have none and pack nothing, ``OWNER_HOPS`` at
        most; of several as near the earliest in program order (the
        ``latest`` of operands). ``names`` run so that an instruction comes
        after what its edges lead to."""
        sign, found = (-1 if latest else 1), {}
        # (an edge a loop carries leads against the order: a second and a
        # third sweep take what the one before found across it)
        for _ in range(3 if self.carried else 1):
            moved = False
            for name in names:
                best = before = found.get(name)
                for n in edges.get(name, ()):
                    if n not in instructions:
                        continue
                    if instructions[n][0] is not None:
                        reached = (1, sign * self.order[n], n)
                    else:
                        via = found.get(n)
                        if via is None or via[0] >= OWNER_HOPS \
                                or self.opcode[n] in _PACKING:
                            continue
                        reached = (via[0] + 1, via[1], via[2])
                    if best is None or reached < best:
                        best = reached
                if best is not before:
                    found[name], moved = best, True
            if not moved:
                break
        return found

    def _behind(self, name: str) -> str:
        """``name``, or what it is a view of."""
        while self.opcode.get(name) in _VIEWS and self.operands[name]:
            name = self.operands[name][0]
        return name

    def _done_of(self, start: str, instructions) -> Optional[str]:
        """The ``-done`` half of ``start``: its user, or (XLA:TPU's three
        fusions) what follows the overlapped fusion that rides on it."""
        front = [start]
        for _ in range(6):
            ahead = []
            for n in front:
                for u in self.users.get(n, ()):
                    cls = instructions[u][2] or ""
                    if self.opcode[u].endswith("-done") \
                            or cls.endswith(".done"):
                        return u
                    if self.opcode[u] in _VIEWS or cls == "overlapped":
                        ahead.append(u)
            front = ahead
        return None

    def read(self, instructions, held) -> Dict[str, Any]:
        """``held(computation)``: the collectives it holds, by opcode."""
        for loop, body in self.loops:
            self._carry(loop, body)
        program = list(self.order)
        ahead = self._nearest(instructions, self.users, program[::-1])
        back = self._nearest(instructions, self.operands, program,
                             latest=True)
        place = lambda n: (n,) + instructions[n][:2]
        owners = {}
        for name, (scope, _, _) in instructions.items():
            if scope is not None:
                continue
            for via, found in (("user", ahead), ("operand", back)):
                if name in found:
                    hops, _, owner = found[name]
                    owners[name] = instructions[owner][:2] + (via, hops)
                    break
        starts = {start: comp for comp, start in self.wrapped.items()}
        for name, comp in self.comp.items():
            # (what an async-start wraps is the start's)
            if comp in self.wrapped and self.wrapped[comp] in owners \
                    and instructions[name][0] is None:
                owners[name] = owners[self.wrapped[comp]]
        transfers, made = {}, {}
        for name in program:
            opcode, cls = self.opcode[name], instructions[name][2] or ""
            if not (opcode.endswith("-start") or cls.endswith(".start")):
                continue
            done = self._done_of(name, instructions)
            moved = result_type(self.lines[done], self.opcode[done]) \
                if done else ""
            space = _SPACE.search(moved)
            feeds = ahead.get(done or name)
            source = self._behind(self.operands[name][0]) \
                if self.operands[name] else None
            moves = next(iter(held(starts[name])), None) \
                if name in starts else cls.rsplit(".", 1)[0] or opcode[:-6]
            transfers[name] = {
                "done": done,
                "kind": ("prefetch" if opcode in PREFETCH_STARTS
                         or moves is None else moves),
                "bytes": result_bytes(moved),
                "space": space and space.group(0),
                "from": place(source) if source in instructions else None,
                "feeds": place(feeds[2]) if feeds else None}
            if done:
                made[done] = name
        calls = {}
        for name, line in self.lines.items():
            if self.opcode[name] != "custom-call":
                continue
            target = _TARGET.search(line)
            behind = (self._behind(o) for o in self.operands[name])
            calls[name] = {
                "target": target.group(1) if target else "",
                "transfers": [made[o] for o in behind if o in made]}
        return {"transfers": transfers, "calls": calls, "owners": owners,
                "relayouts": self.relayouts}


def hlo_counts(hlo_text: str) -> Dict[str, Any]:
    """What an optimized HLO text holds, counted in one place (a view of
    :func:`step_hlo`'s walk): ``mosaic_custom_calls``, the Mosaic (Pallas
    TPU) kernels, and ``collectives``, the collectives by opcode (the gauges
    ``step/collectives{op=...}``): what GSPMD inserted, a loop body's
    counted once however often it runs. Each is counted as what it is: an
    async one once (a ``-start`` / ``-done`` pair, or XLA:TPU's three
    fusions), and a fused ``all-reduce-scatter``, which is how XLA:TPU runs
    a reduce-scatter, as that and not as the all-reduce it holds.
    ``benchmark/aot_check.py`` counts opcodes in the whole text, so its
    all-gather, all-reduce and collective-permute read higher on a TPU."""
    read = step_hlo(hlo_text, ())
    return {k: read[k] for k in ("mosaic_custom_calls", "collectives")}


def scope_instructions(hlo_text: str, scopes: Sequence[str]
                       ) -> Dict[str, Any]:
    """Which instructions of an optimized HLO text lie under each of
    ``scopes`` (``jax.named_scope`` paths), a view of :func:`step_hlo`'s
    walk: ``{"scopes": {scope: [names]}, "instructions": every name,
    "mosaic_calls": the names that are Mosaic (Pallas TPU) kernels}``. An
    instruction counts by its own ``op_name``, so a fusion by its root's;
    the instructions INSIDE a fused computation are no events of a trace
    and are left out. With these a reader lays device time over scopes: a
    trace event's name is an instruction's."""
    read = step_hlo(hlo_text, scopes)
    return {k: read[k] for k in ("scopes", "instructions", "mosaic_calls")}


def _loop_trips(line: str) -> int:
    """How often a ``while`` of an optimized HLO text runs: its
    ``known_trip_count`` where the backend wrote one (XLA:CPU), else the
    length a scan's stacked operands share: the commonest leading dimension
    of the arrays of four dimensions and more that the loop carries
    (XLA:TPU's text says no count; a carried state is one array among
    several stacked ones)."""
    known = _TRIP_COUNT.search(line)
    if known:
        return int(known.group(1))
    leads = [int(dims.split(",")[0])
             for dims in _array_dims(line[:line.find(" while(")])
             if dims.count(",") >= 3]
    return max(set(leads), key=leads.count) if leads else 0


def kda_loops(hlo_text: str) -> Dict[str, int]:
    """What a compiled step says of its kda blocks' recurrences
    (``modules.kda_chunked``): the loops of the forward pass under
    ``mixer/kda/scan``. A block's recurrence is a loop over its groups of
    chunks around a loop over a group's chunks (the inner one in the outer
    one's body; one loop where one group holds every chunk): ``blocks``
    counts the loops that lie in no other, and ``chunks`` is a sequence's
    chunks, outer trips times inner trips (:func:`_loop_trips`). Zeros for
    a step without such a block."""
    loops = []   # (the computation it is in, its body, its trips)
    for comp, _, opcode, op_name, _, line, _ in walk_hlo(hlo_text):
        if opcode == "while" and scope_and_phase(op_name) == (
                KDA_SCAN_SCOPE, "forward"):
            body = _BODY.search(line)
            if body:
                loops.append((comp, body.group(1), _loop_trips(line)))
    bodies = {body for _, body, _ in loops}
    outer = [(body, trips) for comp, body, trips in loops
             if comp not in bodies]
    if not outer:
        return {"blocks": 0, "chunks": 0}
    body, trips = outer[0]
    inner = [n for comp, _, n in loops if comp == body]
    return {"blocks": len(outer), "chunks": trips * (inner[0] if inner else 1)}


def kda_kernel_calls(hlo_text: str) -> Dict[str, int]:
    """What a compiled step says of its kda blocks' recurrences where they
    run in the kernels of ``ops/pallas/kda.py`` (the step then has no loop
    for :func:`kda_loops` to read): ``mosaic_calls``, the Mosaic calls
    under ``mixer/kda/scan`` in every phase (0 = the ``jax.numpy`` form
    ran); ``blocks``, the forward kernels of the forward pass among them;
    ``chunk``, the chunk length by such a call's own operands: the fifth of
    them is ``beta`` as columns, ``[B, chunks, packs of heads, chunk, heads
    a pack]``. Zeros for a step without the kernels."""
    out = {"mosaic_calls": 0, "blocks": 0, "chunk": 0}
    for _, name, opcode, op_name, _, line, _ in walk_hlo(hlo_text):
        if opcode != "custom-call" or _MOSAIC_CALL not in line:
            continue
        scope, phase = scope_and_phase(op_name)
        if scope != KDA_SCAN_SCOPE:
            continue
        out["mosaic_calls"] += 1
        if phase == "forward" and name.startswith(KDA_FWD_CALL):
            out["blocks"] += 1
            at = line.find(_OPERAND_SHAPES)
            shapes = _array_dims(line, at) if at >= 0 else ()
            if len(shapes) >= 5:
                out["chunk"] = int(shapes[4].split(",")[-2])
    return out


def _recomputed(found: Dict[str, Any], calls) -> int:
    phases = found["map"]["instructions"]
    return sum(name.startswith(calls) and phases[name][1] == "recompute"
               for name in found["mosaic_calls"])


def cores_recomputed(found: Dict[str, Any]) -> int:
    """The flash forward kernels of a step (``found``: :func:`step_hlo`'s
    answer) that its map puts in the ``recompute`` phase: attention cores
    that per-layer remat runs a second time. 0 where ``modules.remat`` keeps
    every core's output and row statistics (the gauge
    ``step/cores_recomputed``), and in a step without the kernels."""
    return _recomputed(found, FLASH_FWD_CALL)


def scans_recomputed(found: Dict[str, Any]) -> int:
    """The same of the recurrent mixers' scans: the Mosaic calls named
    ``kda_scan_fwd``, ``gdn_scan_fwd``, ``ssd_scan_fwd`` or
    ``selective_scan_fwd`` that the step's map puts in the ``recompute``
    phase. 0 where ``modules.remat`` keeps every scan's output and entering
    states (the gauge ``step/scans_recomputed``), and in a step
    without the kernels."""
    return _recomputed(found, SCAN_FWD_CALLS)


def conv_kernel_calls(found: Dict[str, Any]) -> Dict[str, int]:
    """The convolution's kernels of a step (``found``: :func:`step_hlo`'s
    answer), by the phase its map puts them in: the Mosaic calls named
    ``CONV_CALLS`` under one of ``CONV_SCOPES``. A block whose convolution
    runs in them counts once in each of ``forward``, ``recompute`` (the
    forward kernel again, under per-layer remat) and ``backward``; zeros
    where the ``jax.numpy`` form ran (the gauges
    ``conv/kernel_calls{phase=...}``)."""
    out = {"forward": 0, "recompute": 0, "backward": 0}
    placed = found["map"]["instructions"]
    for name in found["mosaic_calls"]:
        if name.startswith(CONV_CALLS):
            scope, phase = placed[name][:2]
            if scope in CONV_SCOPES and phase in out:
                out[phase] += 1
    return out


def experts_kernel_calls(found: Dict[str, Any]) -> int:
    """The program's own grouped-matmul kernels of a step (``found``:
    :func:`step_hlo`'s answer): the Mosaic calls named ``EXPERTS_CALLS``
    that the map puts under ``EXPERTS_SCOPE`` by their name stack, forward,
    recomputed and backward, a loop's body counted once. 0 where
    ``lax.ragged_dot`` ran (libtpu's own calls for it carry no name stack
    and none of these names; the gauge ``experts/mosaic_calls``)."""
    placed = found["map"]["instructions"]
    return sum(name.startswith(EXPERTS_CALLS)
               and placed[name][0] == EXPERTS_SCOPE
               for name in found["mosaic_calls"])


# what ``step_hlo`` found in the step program this process last reported
# (cli/train_dist.py), for a reader in the same process (the benchmark's
# per-layer readers run there); empty until a step reports
_STEP_SCOPES: Dict[str, Any] = {}
STEP_MAP_FILE = "step_map.json"


def record_step_scopes(found: Dict[str, Any]) -> None:
    _STEP_SCOPES.clear()
    _STEP_SCOPES.update(found)


def step_scopes() -> Dict[str, Any]:
    return dict(_STEP_SCOPES)


def chip_counts() -> Dict[str, Dict[str, List[float]]]:
    """What the log line last wrote chip by chip for the layers inside the
    expert exchange: ``{"rows": {layer: [by chip]}, "passes": {...}}`` from
    the gauges ``moe/chip_rows{layer,chip}`` and ``moe/chip_passes``, and
    ``"devices": {device id: chip}`` from ``ep/chip_of_device{device}``
    (a trace's planes are named by device id); empty where no layer ran
    inside one."""
    out: Dict[str, Dict[str, Dict[int, float]]] = {}
    devices: Dict[str, int] = {}
    for m in get_registry().metrics():
        kind = {"moe/chip_rows": "rows", "moe/chip_passes": "passes"}.get(
            m.name)
        if kind:
            out.setdefault(kind, {}).setdefault(m.labels["layer"], {})[
                int(m.labels["chip"])] = m.value
        elif m.name == "ep/chip_of_device":
            devices[m.labels["device"]] = int(m.value)
    found: Dict[str, Any] = {
        kind: {layer: [by[c] for c in sorted(by)]
               for layer, by in sorted(layers.items())}
        for kind, layers in out.items()}
    if found and devices:
        found["devices"] = devices
    return found


def write_step_map(trace_dir: str) -> Optional[str]:
    """Write the recorded step's ``map`` beside a trace
    (``<trace_dir>/step_map.json``), so that the trace is joined to scopes,
    phases and collective classes later without the process that made it
    (``tools/trace_by_scope.py``); with it, as ``chips``, the rows and
    passes the last logged step (a traced one: the window has just closed)
    handed each chip of an expert exchange (:func:`chip_counts`). Nothing
    is written, and None returned, where no step has reported or the
    directory cannot be written: a profiler window closes on crash paths
    too."""
    kept = _STEP_SCOPES.get("map")
    if not kept or not trace_dir:
        return None
    path = os.path.join(trace_dir, STEP_MAP_FILE)
    try:
        os.makedirs(trace_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"vocabulary": SCOPES, "phases": PHASES, **kept,
                       "chips": chip_counts()}, f)
    except OSError:
        return None
    return path


def mosaic_custom_calls(fn: Any, args: Sequence[Any]) -> int:
    """Mosaic (Pallas TPU) kernels in the COMPILED program of a jitted
    ``fn`` that was just called with ``args``-shaped inputs, counted in the
    optimized HLO. After the call this costs no compile: ``.lower()`` and
    ``.compile()`` return the lowering and executable the call cached.
    Unlike the cost telemetry above it raises on failure — callers use it
    to prove which attention core a step ran."""
    return hlo_counts(
        fn.lower(*args).compile().as_text())["mosaic_custom_calls"]


# one record per (registry, program): keyed on the live registry object so
# a reused id() after GC can never suppress a fresh registry's recording
_RECORDED: "weakref.WeakKeyDictionary[MetricsRegistry, set]" = \
    weakref.WeakKeyDictionary()


def maybe_record_jit_cost(program: str, fn: Any, args: Sequence[Any] = (),
                          kwargs: Optional[Dict[str, Any]] = None,
                          registry: Optional[MetricsRegistry] = None
                          ) -> Optional[Dict[str, float]]:
    """Record one program's cost analysis as ``cost/*`` gauges (labelled
    ``program=``) plus a one-shot ``program_cost`` event — once per
    (registry, program). With no explicit registry AND no sinks configured
    this is a no-op, so un-instrumented runs pay only a set lookup."""
    reg = registry if registry is not None else get_registry()
    if registry is None and not reg.sinks:
        # only the process-default registry is sink-gated: an explicitly
        # passed registry may be scraped sink-less (the Prometheus endpoint
        # reads gauges directly), so its caller opted into the lower() cost
        return None
    seen = _RECORDED.setdefault(reg, set())
    if program in seen:
        return None
    seen.add(program)
    out = jit_cost_summary(fn, args, kwargs)
    if not out:
        return None
    for k, v in out.items():
        reg.gauge(f"cost/{k}", program=program).set(v)
    reg.event("program_cost", {"program": program, **out})
    return out


# ---------------------------------------------------------------------------
# predicted communication (plan + fitted α-β pairs)
# ---------------------------------------------------------------------------


def _ab_for(alpha_beta: Dict[str, Tuple[float, float]], size: int,
            consec: bool) -> Optional[Tuple[float, float]]:
    return (alpha_beta.get(f"{size}_{1 if consec else 0}")
            or alpha_beta.get(f"{size}_1") or alpha_beta.get(f"{size}_0"))


def predicted_comm_per_step(
    hpc: Any,
    model: Any,
    *,
    alpha_beta: Optional[Dict[str, Tuple[float, float]]] = None,
    alpha_beta_algos: Optional[Dict[str, Dict[str, Tuple[float, float]]]]
    = None,
    mixed_precision: bool = True,
) -> Dict[str, Dict[str, float]]:
    """Per component (tp/dp/sp/cp/pp): the plan's predicted per-step MB
    (``plan_comm_volume``) and — for the allreduce-derived collectives,
    when fitted α-β pairs are available — the predicted per-device ms,
    priced exactly the way the cost model prices them: one Megatron-SP
    ag/rs-equivalent message costs ``0.5 * (α + size/β)``
    (``cost_model.cost._tp_message_ms``) and a dp ring all-reduce of a
    ``size``-MB gradient buffer costs ``α + size/β`` (the curve
    ``profile_alpha_beta`` fitted). sp/cp/pp volumes are reported MB-only:
    their collectives were not fitted on the allreduce curve, so a time
    prediction here would be invented, not measured.

    The measured side (``Attribution``) is a per-device-track average, and
    each device only runs the layers of its own pipeline stage — so the
    priced times sum over all layers and divide by ``pp_deg`` (the uniform
    per-device average; volumes stay whole-plan MB).

    ``alpha_beta_algos`` (``profiles.read_alpha_beta_algos``) adds the
    PER-ALGORITHM view of tp: its dict gains an ``algorithms`` map of
    candidate-curve predicted ms (``flat`` plus each fitted
    ``{ring|tree}_ici`` curve), an ``algorithm`` key naming the winner,
    and ``predicted_ms`` = the min — the choice the cost model priced
    (cost._tp_message_ms). ``audit_plan`` renders these as per-algorithm
    rows."""
    from hetu_galvatron_tpu.observability.telemetry import (
        layer_param_mb,
        plan_comm_volume,
    )

    chunks = max(hpc.chunks, 1)
    pp = max(getattr(hpc, "pp_deg", 1), 1)
    vols = plan_comm_volume(hpc.layers, model, global_bsz=hpc.global_bsz,
                            chunks=chunks, mixed_precision=mixed_precision)
    ab = alpha_beta or {}
    abalgos = alpha_beta_algos or {}
    param_mb = layer_param_mb(model)
    seq, h = model.seq_length, model.hidden_size
    elem = 2 if mixed_precision else 4
    out: Dict[str, Dict[str, float]] = {
        c: {"predicted_mb": 0.0} for c in ("tp", "dp", "sp", "cp", "pp")}
    for s, v in zip(hpc.layers, vols):
        ulysses = s.tp_size if s.sp else 1
        out["sp" if ulysses > 1 else "tp"]["predicted_mb"] += \
            v["tp_collective_mb"]
        out["dp"]["predicted_mb"] += v["dp_allreduce_mb"]
        out["cp"]["predicted_mb"] += v["cp_ring_mb"]
        out["pp"]["predicted_mb"] += v["pp_p2p_mb"]
        # α-β time predictions (allreduce-fitted collectives only)
        tp = 1 if s.sp else s.tp_size
        lbsz = max(hpc.global_bsz // chunks // max(s.dp_size, 1), 1)
        if tp > 1:
            # mirror cost._tp_message_ms EXACTLY: the search only ever
            # prices tp with the "{tp}_1" pair (tp groups are consecutive
            # by construction, level ici) and takes the MIN over the flat
            # pair and the per-algorithm ICI curves — auditing against any
            # other choice would measure drift vs a curve it never used
            act_mb = lbsz * seq * h * elem / MB
            n_msgs = 6 * chunks * (1.5 if s.checkpoint else 1.0)
            scale = n_msgs * 0.5 / pp
            cands: Dict[str, float] = {}
            pair = ab.get(f"{tp}_1")
            if pair is not None:
                cands["flat"] = (pair[0] + act_mb / pair[1]) * scale
            for alg_lvl, (alpha, beta) in (abalgos.get(f"{tp}_1") or
                                           {}).items():
                if alg_lvl.endswith("_ici"):
                    cands[alg_lvl] = (alpha + act_mb / beta) * scale
            if cands:
                # per-LAYER min summed — exactly the cost model's choice
                # (mixed curve coverage across layers stays correct: a
                # flat-only layer contributes its flat time, an
                # algo-covered layer its cheapest curve)
                out["tp"]["predicted_ms"] = out["tp"].get(
                    "predicted_ms", 0.0) + min(cands.values())
                if len(cands) > 1 or "flat" not in cands:
                    algs = out["tp"].setdefault("algorithms", {})
                    for k, v in cands.items():
                        algs[k] = algs.get(k, 0.0) + v
        sdp = max(s.dp_size * s.cp_size * ulysses, 1)
        if sdp > 1:
            # dc_key convention (cost.py): tp>1 groups leave dp strided
            pair = _ab_for(ab, sdp, tp == 1)
            grad_mb = param_mb / max(tp, 1) * \
                (0.5 if mixed_precision else 1.0)
            if pair is not None:
                out["dp"]["predicted_ms"] = out["dp"].get(
                    "predicted_ms", 0.0) + (pair[0] + grad_mb / pair[1]) / pp
    # tp's accumulated argmin is indicative (exact when curve coverage is
    # layer-uniform)
    tp_algs = out["tp"].get("algorithms")
    if tp_algs:
        out["tp"]["algorithm"] = min(tp_algs, key=tp_algs.get)
    return {c: d for c, d in out.items()
            if d["predicted_mb"] or d.get("predicted_ms")}


# ---------------------------------------------------------------------------
# the plan audit
# ---------------------------------------------------------------------------


def measured_components(attr: Attribution, hpc: Any) -> Dict[str, float]:
    """Map measured collective categories onto plan components using the
    plan as the disambiguator: ag/rs -> tp (Megatron-SP activations; ZeRO-3
    parameter gathers land here too — documented), a2a -> sp (Ulysses),
    allreduce -> dp when the plan has a dp/ZeRO shard group else tp (plain
    TP without SP all-reduces activations).

    Permutes are split by SOURCE first: ``attribute`` bills marked hops
    (named_scope metadata — ``tp_ring`` / ``cp_ring`` / ``pp_rotate`` —
    or, host-engine runs only, coverage by a device-propagated
    ``tp/overlap_step`` span) into ``permute_tp`` / ``permute_cp`` /
    ``permute_pp``, which map straight onto their components. Only the
    UNMARKED remainder falls back to the plan-level heuristic (pp when
    pipelined, else cp, else tp) — so a compiled program mixing tp-ring,
    cp-ring and stage-rotation permutes no longer mis-bills the ring hops
    as pipeline time."""
    cat = attr.categories_ms
    any_sdp = any(
        max(s.dp_size * s.cp_size * (s.tp_size if s.sp else 1), 1) > 1
        for s in hpc.layers)
    any_cp = any(s.cp_size > 1 for s in hpc.layers)
    permute_to = ("pp" if hpc.pp_deg > 1 else ("cp" if any_cp else "tp"))
    out: Dict[str, float] = {}

    def add(comp, ms):
        if ms:
            out[comp] = out.get(comp, 0.0) + ms

    add("tp", cat.get("allgather", 0.0) + cat.get("reducescatter", 0.0)
        + cat.get("permute_tp", 0.0))
    add("sp", cat.get("alltoall", 0.0))
    add("cp", cat.get("permute_cp", 0.0))
    add("pp", cat.get("permute_pp", 0.0))
    add("dp" if any_sdp else "tp", cat.get("allreduce", 0.0))
    add(permute_to, cat.get("permute", 0.0) + cat.get("p2p", 0.0)
        + cat.get("broadcast", 0.0))
    return out


def audit_plan(
    attr: Attribution,
    hpc: Any,
    model: Any,
    *,
    registry: Optional[MetricsRegistry] = None,
    alpha_beta: Optional[Dict[str, Tuple[float, float]]] = None,
    alpha_beta_algos: Optional[Dict[str, Dict[str, Tuple[float, float]]]]
    = None,
    mixed_precision: bool = True,
    predicted_layer_s: Optional[Sequence[float]] = None,
    steps: Optional[int] = None,
) -> Dict[str, Any]:
    """Diff the active plan's predictions against the measured attribution
    and emit the calibration data: per component, predicted MB + (α-β)
    predicted ms vs measured per-step per-device ms, the measured/predicted
    time ratio, and the α-β residual (measured − predicted, the number the
    topology-aware collective-selection work needs to know when the fitted
    curve has drifted). Also audits compute time against the cost model's
    per-layer predictions when given, and the pipeline bubble fraction
    against the 1F1B analytical ``2(pp−1)/(m+2(pp−1))``.

    With ``alpha_beta_algos``, per-ALGORITHM rows follow each priced
    component (``tp[ring_ici]``, ...): every candidate curve's predicted
    ms, the chosen one flagged.

    Emits ``audit/*`` gauges (labelled ``component=``) into ``registry``
    (the process default when omitted) plus one ``plan_audit`` event
    carrying the whole table for ``cli/summarize.py``; returns the table.
    """
    reg = registry if registry is not None else get_registry()
    n_steps = steps or attr.steps or 1
    measured = {c: ms / n_steps for c, ms in
                measured_components(attr, hpc).items()}
    predicted = predicted_comm_per_step(
        hpc, model, alpha_beta=alpha_beta,
        alpha_beta_algos=alpha_beta_algos,
        mixed_precision=mixed_precision)

    rows: List[Dict[str, Any]] = []
    for comp in ("tp", "dp", "sp", "cp", "pp"):
        m_ms = measured.get(comp)
        pred = predicted.get(comp, {})
        if m_ms is None and not pred:
            continue
        row: Dict[str, Any] = {"component": comp,
                               "measured_ms": round(m_ms or 0.0, 4),
                               "predicted_mb": round(
                                   pred.get("predicted_mb", 0.0), 3)}
        p_ms = pred.get("predicted_ms")
        if p_ms:
            row["predicted_ms"] = round(p_ms, 4)
            row["ratio"] = round((m_ms or 0.0) / p_ms, 4)
            row["residual_ms"] = round((m_ms or 0.0) - p_ms, 4)
        rows.append(row)
        # per-algorithm candidate rows (alpha_beta_algos present)
        chosen = pred.get("algorithm")
        for alg, alg_ms in sorted((pred.get("algorithms") or {}).items()):
            arow: Dict[str, Any] = {"component": f"{comp}[{alg}]",
                                    "predicted_ms": round(alg_ms, 4)}
            if alg == chosen:
                arow["chosen"] = True
            rows.append(arow)

    compute_row: Dict[str, Any] = {
        "component": "compute",
        "measured_ms": round(attr.compute_ms / n_steps, 4)}
    if predicted_layer_s:
        # predicted_layer_s is per-layer SECONDS for ONE microbatch (the
        # cost model prices at lbsz = gbsz/chunks/dp; the parameter name
        # carries the unit so callers cannot pass ms by mistake). One
        # optimizer step runs `chunks` microbatches, and the measured side
        # is a per-device average where each device executes only its own
        # stage's layers — scale by chunks/pp to the same normalization.
        p = (float(sum(predicted_layer_s)) * 1000.0
             * max(hpc.chunks, 1) / max(hpc.pp_deg, 1))
        compute_row["predicted_ms"] = round(p, 4)
        if p > 0:
            compute_row["ratio"] = round(
                attr.compute_ms / n_steps / p, 4)
            compute_row["residual_ms"] = round(
                attr.compute_ms / n_steps - p, 4)
    rows.append(compute_row)

    bubble_row: Dict[str, Any] = {"component": "bubble",
                                  "measured_frac": round(attr.bubble_frac, 4)}
    if hpc.pp_deg > 1:
        m = max(hpc.chunks, 1)
        bubble_row["predicted_frac"] = round(
            2 * (hpc.pp_deg - 1) / (m + 2 * (hpc.pp_deg - 1)), 4)
    rows.append(bubble_row)

    table = {
        "steps": n_steps,
        "tracks": attr.tracks,
        "step_device_ms": round(attr.per_device_busy_ms / n_steps, 4),
        "rows": rows,
    }
    for row in rows:
        comp = row["component"]
        for key, gauge in (("measured_ms", "audit/measured_ms"),
                           ("predicted_ms", "audit/predicted_ms"),
                           ("ratio", "audit/time_ratio"),
                           ("residual_ms", "audit/residual_ms"),
                           ("predicted_mb", "audit/predicted_mb"),
                           ("measured_frac", "audit/measured_frac"),
                           ("predicted_frac", "audit/predicted_frac")):
            if key in row:
                reg.gauge(gauge, component=comp).set(row[key])
    reg.gauge("audit/step_device_ms").set(table["step_device_ms"])
    reg.event("plan_audit", table)
    return table


def analyze_and_audit(
    trace_dir: str,
    hpc: Any,
    model: Any,
    *,
    registry: Optional[MetricsRegistry] = None,
    alpha_beta: Optional[Dict[str, Tuple[float, float]]] = None,
    alpha_beta_algos: Optional[Dict[str, Dict[str, Tuple[float, float]]]]
    = None,
    mixed_precision: bool = True,
    predicted_layer_s: Optional[Sequence[float]] = None,
    step_spans: Sequence[str] = STEP_SPANS,
) -> Optional[Dict[str, Any]]:
    """One-call closed loop for the launchers: parse the newest capture
    under ``trace_dir``, attribute it, audit it against the plan. Thread
    per-layer per-MICROBATCH compute predictions in SECONDS via
    ``predicted_layer_s`` to get a compute-row ratio (``audit_plan`` scales
    them by chunks/pp itself) — searched plans carry them as
    ``hpc.predicted_layer_compute_ms`` (``cost_model.layer_time_components``
    fct+bct, in MILLISECONDS — divide by 1e3 before passing, as
    ``cli/train_dist.py`` does); without it the compute row is
    measured-only. Returns the audit
    table, or None when no capture/attribution is available (never raises
    — this runs in crash-path ``finally`` blocks)."""
    try:
        attr = attribute(load_trace(trace_dir), step_spans=step_spans)
        if not attr.tracks and not attr.host_span_ms:
            return None
        return audit_plan(attr, hpc, model, registry=registry,
                          alpha_beta=alpha_beta,
                          alpha_beta_algos=alpha_beta_algos,
                          mixed_precision=mixed_precision,
                          predicted_layer_s=predicted_layer_s)
    except FileNotFoundError:
        return None
    except Exception:  # noqa: BLE001 — post-mortem helper, never fatal
        return None
