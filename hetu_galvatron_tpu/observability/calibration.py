"""Self-calibrating cost model: the refit half of the observability loop.

``trace_analysis.audit_plan`` diffs measured vs predicted collective time
per component every traced run — this module stops dropping those numbers
on the floor and closes the loop::

    profile ──> search ──> run ──> audit ──> refit ──> regret
      (prior)    (plan)   (trace)  (residuals) (posterior)  (alarm)

Three pieces, glued by :func:`run_calibration` (wired into the loop-exit
audit hook in ``cli/train_dist.py``):

1. **Persistent residual store** (:class:`ResidualStore`): every plan
   audit appends per-curve ``(message MB, measured ms)`` observations —
   derived from the audit table with exactly the message arithmetic
   ``predicted_comm_per_step`` prices with — to an append-only JSONL
   file keyed by a hardware fingerprint (device kind, world size, mesh
   shape). Appends are single-``os.write`` on an ``O_APPEND`` fd so
   concurrent supervisor restarts interleave whole lines; the reader
   skips torn or foreign lines with a warning, never a traceback (the
   PR 6 summarize contract).
2. **α-β re-fitter** (:func:`refit_profile`): robust regression
   (min-sample-gated, MAD outlier-rejecting, reusing
   ``hardware_profiler.fit_alpha_beta``'s degenerate-slope hardening)
   over the accumulated points per ``(group, algorithm, level)`` curve.
   Single-size point clouds — the common steady-production case — fall
   back to a *scale* calibration against the prior curve (α·r, β/r with
   r the median measured/predicted ratio), so one-shot profiling is the
   prior and production traces the posterior. The emitted JSON lives in
   the exact key namespace ``profiles.read_alpha_beta`` /
   ``read_alpha_beta_algos`` already parse, provenance-tagged under a
   ``calibration_meta`` key (source, per-curve point counts + method,
   fit window, fingerprint) that both parsers and the summarize router
   ignore — profiled and calibrated curves coexist, and the search
   engine consumes whichever file the operator points it at.
3. **Plan-regret drift sentinel** (:func:`evaluate_plan_regret`): the
   search engine embeds its top-k runner-up strategies (priced ms each)
   in the winning plan JSON; the audit hook re-prices incumbent +
   runner-ups under the calibrated curves
   (``cost_model.reprice_stored_plan_ms``) and publishes
   ``calibration/plan_regret_ms`` + ``calibration/drift_score`` gauges,
   raising one ``plan_regret`` event when a runner-up now beats the
   incumbent by more than ``observability.regret_threshold`` — "the
   plan went stale" becomes a measured, alarmable signal instead of a
   silent throughput loss.

Everything here is post-mortem/loop-exit machinery: :func:`run_calibration`
never raises (it runs in the same crash-path ``finally`` block as the
audit itself).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from hetu_galvatron_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
)

MB = 1024 * 1024

# file names under observability.calibration_dir
STORE_NAME = "residuals.jsonl"
PROFILE_NAME = "calibrated_profile.json"

# provenance key both α-β parsers and the summarize hardware router ignore
META_KEY = "calibration_meta"


# ---------------------------------------------------------------------------
# hardware fingerprint
# ---------------------------------------------------------------------------


def hardware_fingerprint(hpc: Any = None, *, world: Optional[int] = None,
                         device_kind: Optional[str] = None
                         ) -> Dict[str, Any]:
    """Identity of the hardware the residuals were measured on: device
    kind, world size, and the plan's mesh shape ``[pp, tp, dp]``. Points
    from a different fingerprint never pollute a fit — a v5e curve must
    not be refit from v4 residuals, nor an 8-chip curve from a 4-chip
    run."""
    if device_kind is None:
        try:
            import jax

            device_kind = jax.devices()[0].device_kind
        except Exception:  # noqa: BLE001 — post-mortem helper
            device_kind = "unknown"
    mesh: List[int] = []
    if hpc is not None:
        layers = getattr(hpc, "layers", None) or []
        s0 = layers[0] if layers else None
        mesh = [int(getattr(hpc, "pp_deg", 1) or 1),
                int(s0.tp_size) if s0 is not None else 1,
                int(s0.dp_size) if s0 is not None else 1]
        if world is None:
            world = getattr(hpc, "world_size", None)
    return {"device": str(device_kind), "world": int(world or 0),
            "mesh": mesh}


def fingerprint_key(fp: Dict[str, Any]) -> str:
    """Stable short form for logs and meta tags."""
    mesh = "x".join(str(int(m)) for m in fp.get("mesh", []) or [])
    dev = str(fp.get("device", "unknown")).replace(" ", "-")
    return f"{dev}_w{int(fp.get('world', 0))}_{mesh or 'nomesh'}"


def _fp_matches(a: Optional[Dict[str, Any]], b: Optional[Dict[str, Any]]
                ) -> bool:
    if not isinstance(a, dict) or not isinstance(b, dict):
        return False
    return (str(a.get("device")) == str(b.get("device"))
            and int(a.get("world", 0)) == int(b.get("world", 0))
            and list(a.get("mesh") or []) == list(b.get("mesh") or []))


# ---------------------------------------------------------------------------
# persistent residual store
# ---------------------------------------------------------------------------


class ResidualStore:
    """Append-only JSONL of per-curve residual observations, accumulated
    across runs and supervisor restarts.

    Writes go through one ``os.write`` on an ``O_APPEND`` descriptor per
    batch — concurrent multi-process appenders interleave whole batches,
    not bytes. Reads tolerate torn trailing lines, corrupt records, and
    foreign fingerprints: bad lines are counted in ``skipped`` and warned
    to stderr once per load, never raised."""

    def __init__(self, path: str):
        self.path = path
        self.skipped = 0

    def append(self, points: Sequence[Dict[str, Any]], *,
               fingerprint: Dict[str, Any],
               run_id: Optional[str] = None) -> int:
        """Append one audit's points (each tagged with the fingerprint and
        a wall timestamp); returns how many were written."""
        if not points:
            return 0
        now = time.time()
        lines = []
        for p in points:
            rec = dict(p)
            rec.setdefault("t", now)
            rec["fp"] = fingerprint
            if run_id is not None:
                rec["run"] = str(run_id)
            lines.append(json.dumps(rec, separators=(",", ":"),
                                    default=_jsonable))
        # leading newline: if the previous writer died mid-line, its torn
        # tail gets terminated here and only THAT line is lost — without
        # it the torn tail would concatenate onto (and swallow) this
        # batch's first record. Blank lines are skipped by load() without
        # counting as corruption.
        payload = ("\n" + "\n".join(lines) + "\n").encode("utf-8")
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        fd = os.open(self.path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                     0o644)
        try:
            os.write(fd, payload)
        finally:
            os.close(fd)
        return len(lines)

    def load(self, *, fingerprint: Optional[Dict[str, Any]] = None
             ) -> List[Dict[str, Any]]:
        """Every parseable point (optionally fingerprint-filtered).
        ``self.skipped`` counts dropped lines of the last load."""
        self.skipped = 0
        out: List[Dict[str, Any]] = []
        try:
            with open(self.path, encoding="utf-8", errors="replace") as f:
                raw = f.read()
        except OSError:
            return out
        for line in raw.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                self.skipped += 1
                continue
            if not isinstance(rec, dict):
                self.skipped += 1
                continue
            if fingerprint is not None and not _fp_matches(
                    rec.get("fp"), fingerprint):
                continue
            out.append(rec)
        if self.skipped:
            print(f"calibration: skipped {self.skipped} unparseable "
                  f"line(s) in {self.path} (torn/concurrent append)",
                  file=sys.stderr)
        return out


def _jsonable(x: Any) -> Any:
    try:
        return float(x)
    except (TypeError, ValueError):
        return str(x)


# ---------------------------------------------------------------------------
# residual extraction from one audit table
# ---------------------------------------------------------------------------


def calibration_points(table: Dict[str, Any], hpc: Any, model: Any, *,
                       mixed_precision: bool = True
                       ) -> List[Dict[str, Any]]:
    """Per-curve ``(message MB, measured per-message ms)`` observations
    from one ``audit_plan`` table, using exactly the message arithmetic
    ``predicted_comm_per_step`` prices with (so a refit curve predicts the
    same quantity the audit measures).

    tp: the component's measured ms is apportioned across (tp size,
    activation MB) groups by their bandwidth-dominated share and divided
    by the group's message count — one point per group on the
    ``"{tp}_1"`` curve, attributed to the algorithm the audit chose
    (``flat`` when no per-algorithm curves priced it). dp: same, per
    flat-ring gradient buffer on ``"{sdp}_{consec}"``."""
    from hetu_galvatron_tpu.observability.telemetry import layer_param_mb

    rows = [r for r in (table.get("rows") or []) if isinstance(r, dict)]
    by_comp = {str(r.get("component")): r for r in rows}
    chosen_tp_alg = "flat"
    for r in rows:
        c = str(r.get("component", ""))
        if c.startswith("tp[") and c.endswith("]") and r.get("chosen"):
            chosen_tp_alg = c[3:-1]
    points: List[Dict[str, Any]] = []
    layers = getattr(hpc, "layers", None) or []
    if not layers:
        return points
    chunks = max(int(getattr(hpc, "chunks", 1) or 1), 1)
    pp = max(int(getattr(hpc, "pp_deg", 1) or 1), 1)
    seq, h = model.seq_length, model.hidden_size
    elem = 2 if mixed_precision else 4
    param_mb = layer_param_mb(model)

    def _apportion(groups: Dict[Tuple, List[float]], measured: float,
                   alg: str, group_of) -> None:
        # share by w·mb (bandwidth-dominated proxy); exact in the common
        # single-group case where no apportioning happens at all
        shares = {k: g[1] * g[0] for k, g in groups.items()}
        tot = sum(shares.values())
        if tot <= 0:
            return
        for key, (mb, w) in groups.items():
            if w <= 0:
                continue
            ms = measured * shares[key] / tot / w
            if ms <= 0 or mb <= 0:
                continue
            points.append({"collective": "allreduce",
                           "group": group_of(key), "alg": alg,
                           "mb": round(mb, 9), "ms": round(ms, 9),
                           "w": round(w, 6)})

    # tp (Megatron-SP ag/rs-equivalent messages on the "{tp}_1" curve)
    tp_groups: Dict[Tuple, List[float]] = {}
    for s in layers:
        tp = 1 if s.sp else s.tp_size
        if tp <= 1:
            continue
        lbsz = max(hpc.global_bsz // chunks // max(s.dp_size, 1), 1)
        act_mb = lbsz * seq * h * elem / MB
        w = 6 * chunks * (1.5 if s.checkpoint else 1.0) * 0.5 / pp
        g = tp_groups.setdefault((tp, round(act_mb, 9)), [act_mb, 0.0])
        g[1] += w
    trow = by_comp.get("tp")
    if tp_groups and trow and trow.get("measured_ms"):
        _apportion(tp_groups, float(trow["measured_ms"]), chosen_tp_alg,
                   lambda key: f"{key[0]}_1")

    # dp (per-layer gradient rings)
    dp_groups: Dict[Tuple, List[float]] = {}
    for s in layers:
        tp = 1 if s.sp else s.tp_size
        sdp = max(s.dp_size * s.cp_size * (s.tp_size if s.sp else 1), 1)
        if sdp <= 1:
            continue
        grad_mb = param_mb / max(tp, 1) * \
            (0.5 if mixed_precision else 1.0)
        key = (sdp, 1 if tp == 1 else 0, round(grad_mb, 9))
        g = dp_groups.setdefault(key, [grad_mb, 0.0])
        g[1] += 1.0 / pp
    drow = by_comp.get("dp")
    if dp_groups and drow and drow.get("measured_ms"):
        _apportion(dp_groups, float(drow["measured_ms"]), "flat",
                   lambda key: f"{key[0]}_{key[1]}")
    return points


def drift_score(table: Dict[str, Any]) -> Optional[float]:
    """Aggregate model drift from one audit table:
    Σ|measured−predicted| / Σpredicted over the top-level components that
    carried a time prediction (0 = the curves still price reality)."""
    num = den = 0.0
    for r in table.get("rows") or []:
        if not isinstance(r, dict) or "[" in str(r.get("component", "")):
            continue
        p = r.get("predicted_ms")
        if not isinstance(p, (int, float)) or p <= 0:
            continue
        m = r.get("measured_ms")
        if not isinstance(m, (int, float)):
            continue
        num += abs(float(m) - float(p))
        den += float(p)
    return (num / den) if den > 0 else None


# ---------------------------------------------------------------------------
# α-β re-fitter
# ---------------------------------------------------------------------------


def _robust_fit(pts: List[Tuple[float, float, float]], *, outlier_k: float,
                min_rel_spread: float, label: str
                ) -> Tuple[Optional[Tuple[float, float]], int]:
    """Outlier-rejecting α-β regression over (mb, ms, weight) points.
    Returns ((α, β), points_used) or (None, n) when the sizes carry no
    spread (single size / zero variance) or the slope is degenerate —
    the caller then falls back to scale calibration."""
    from hetu_galvatron_tpu.core.profiler.hardware_profiler import (
        fit_alpha_beta,
    )

    xs = np.asarray([p[0] for p in pts], dtype=np.float64)
    ys = np.asarray([p[1] for p in pts], dtype=np.float64)
    lo, hi = float(xs.min()), float(xs.max())
    if hi <= 0 or (hi - lo) / hi < min_rel_spread:
        return None, len(pts)
    fit = fit_alpha_beta(xs, ys, label=label)
    if fit is None:
        return None, len(pts)
    alpha, beta = fit
    res = ys - (alpha + xs / beta)
    med = float(np.median(res))
    mad = float(np.median(np.abs(res - med)))
    if mad > 0:
        keep = np.abs(res - med) <= outlier_k * mad
        n_keep = int(keep.sum())
        if 2 <= n_keep < len(xs):
            xs2, ys2 = xs[keep], ys[keep]
            if float(xs2.max()) > float(xs2.min()):
                refit = fit_alpha_beta(xs2, ys2,
                                       label=f"{label} (outliers dropped)")
                if refit is not None:
                    return refit, n_keep
    return fit, len(pts)


def window_points(points: Sequence[Dict[str, Any]], *,
                  window_days: float = 0.0,
                  max_points_per_curve: int = 0,
                  now: Optional[float] = None) -> List[Dict[str, Any]]:
    """Decay + window the residual store before a re-fit.

    ``window_days > 0`` drops points whose wall timestamp (``t``, stamped
    by :meth:`ResidualStore.append`) is older than that many days —
    hardware or software changes age out of the posterior instead of
    anchoring it forever. Points carrying no timestamp are of unknown
    age and are dropped too under an active window (legacy pre-timestamp
    lines; keeping them would defeat the decay).

    ``max_points_per_curve > 0`` then keeps only that many NEWEST points
    per ``(group, alg)`` curve key, bounding both the fit cost and the
    influence of any one flood of appends. 0 disables either limit;
    the default is the historical keep-everything behaviour."""
    pts = [p for p in points if isinstance(p, dict)]
    if window_days > 0:
        cutoff = (now if now is not None else time.time()) \
            - window_days * 86400.0
        pts = [p for p in pts
               if isinstance(p.get("t"), (int, float))
               and float(p["t"]) >= cutoff]
    if max_points_per_curve > 0:
        by_curve: Dict[Tuple[str, str], List[Dict[str, Any]]] = {}
        for p in pts:
            key = (str(p.get("group", "")), str(p.get("alg") or "flat"))
            by_curve.setdefault(key, []).append(p)
        keep = set()
        for recs in by_curve.values():
            newest = sorted(
                recs,
                key=lambda p: float(p["t"]) if isinstance(
                    p.get("t"), (int, float)) else float("-inf"),
            )[-max_points_per_curve:]
            keep.update(id(p) for p in newest)
        pts = [p for p in pts if id(p) in keep]
    return pts


def refit_profile(points: Sequence[Dict[str, Any]], *,
                  prior: Optional[Dict[str, Any]] = None,
                  min_points: int = 4, min_rel_spread: float = 0.05,
                  outlier_k: float = 4.0
                  ) -> Tuple[Dict[str, float], Dict[str, Any]]:
    """Fit calibrated α-β pairs per (group, algorithm) curve from
    accumulated residual points. Returns ``(profile_keys, meta)`` where
    ``profile_keys`` uses the exact ``read_alpha_beta`` /
    ``read_alpha_beta_algos`` namespace and ``meta`` is the
    ``calibration_meta`` provenance payload (per-curve point counts, fit
    method, fit window).

    Per curve: with at least ``min_points`` size-diverse points, a robust
    regression; otherwise, when the prior profiled the curve, a scale
    calibration (median measured/predicted ratio applied as α·r, β/r —
    the posterior update a single-size production workload supports);
    otherwise the curve is skipped."""
    from hetu_galvatron_tpu.core.search_engine.profiles import (
        read_alpha_beta,
        read_alpha_beta_algos,
    )

    prior_cfg = prior or {}
    try:
        prior_flat = read_alpha_beta(prior_cfg)
        prior_algos = read_alpha_beta_algos(prior_cfg)
    except Exception:  # noqa: BLE001 — a corrupt prior degrades, not dies
        prior_flat, prior_algos = {}, {}

    curves: Dict[Tuple[str, str], List[Tuple[float, float, float]]] = {}
    t_vals: List[float] = []
    for p in points:
        if not isinstance(p, dict):
            continue
        mb, ms = p.get("mb"), p.get("ms")
        if not isinstance(mb, (int, float)) or not isinstance(
                ms, (int, float)) or mb <= 0 or ms <= 0:
            continue
        group = str(p.get("group", ""))
        parts = group.split("_")
        if len(parts) != 2 or not all(x.isdigit() for x in parts):
            continue
        alg = str(p.get("alg") or "flat")
        w = p.get("w", 1.0)
        w = float(w) if isinstance(w, (int, float)) and w > 0 else 1.0
        curves.setdefault((group, alg), []).append(
            (float(mb), float(ms), w))
        if isinstance(p.get("t"), (int, float)):
            t_vals.append(float(p["t"]))

    cfg: Dict[str, float] = {}
    meta_curves: Dict[str, Dict[str, Any]] = {}
    for (group, alg), pts in sorted(curves.items()):
        fitted = None
        method = None
        used = len(pts)
        if len(pts) >= max(min_points, 2):
            fitted, used = _robust_fit(
                pts, outlier_k=outlier_k, min_rel_spread=min_rel_spread,
                label=f"calibration {group}/{alg}")
            if fitted is not None:
                method = "regression"
        if fitted is None:
            pr = (prior_flat.get(group) if alg == "flat"
                  else (prior_algos.get(group) or {}).get(alg))
            if pr is not None:
                ratios = [ms / (pr[0] + mb / pr[1]) for mb, ms, _ in pts
                          if pr[0] + mb / pr[1] > 0]
                if ratios:
                    r = float(np.median(ratios))
                    r = min(max(r, 0.05), 20.0)
                    fitted = (pr[0] * r, pr[1] / r)
                    method = "scale"
                    used = len(ratios)
        if fitted is None:
            continue
        alpha, beta = max(float(fitted[0]), 0.0), float(fitted[1])
        if beta <= 0:
            continue
        n, c = group.split("_")
        if alg == "flat":
            stem = f"allreduce_size_{n}_consec_{c}"
        else:
            a, _, lvl = alg.rpartition("_")
            if not a or not lvl:
                continue
            stem = f"allreduce_size_{n}_consec_{c}_alg_{a}_lvl_{lvl}"
        cfg[f"{stem}_alpha_ms"] = round(alpha, 9)
        cfg[f"{stem}_beta_mb_per_ms"] = round(beta, 6)
        meta_curves[f"{group}/{alg}"] = {"points": int(used),
                                         "method": method}
    meta: Dict[str, Any] = {"source": "runtime-calibrated",
                            "curves": meta_curves,
                            "fitted_at": time.time()}
    if t_vals:
        meta["window"] = [min(t_vals), max(t_vals)]
    return cfg, meta


def write_calibrated_profile(path: str, cfg: Dict[str, Any]) -> str:
    """Atomic write (tmp + fsync + replace — the flight-dump discipline):
    a reader never sees a torn profile."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(cfg, f, indent=2, sort_keys=True, default=_jsonable)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def _ensure_bandwidth_keys(cfg: Dict[str, Any]) -> None:
    """Bare ``allreduce_size_{n}_consec_{c}`` bandwidth keys for every
    fitted flat curve that lacks one (summarize's group listing keys off
    them): β IS the fitted effective MB/ms."""
    for key in list(cfg):
        if (key.startswith("allreduce_size_")
                and key.endswith("_beta_mb_per_ms") and "_alg_" not in key):
            bare = key[:-len("_beta_mb_per_ms")]
            cfg.setdefault(bare, cfg[key])


# ---------------------------------------------------------------------------
# plan-regret drift sentinel
# ---------------------------------------------------------------------------


def plan_spec_from_hpc(hpc: Any) -> Dict[str, Any]:
    """The incumbent plan in the stored-strategy shape
    ``cost_model.reprice_stored_plan_ms`` prices (the same shape
    ``save_results`` embeds for each runner-up)."""
    layers = []
    for s in getattr(hpc, "layers", None) or []:
        layers.append({"tp": int(s.tp_size), "dp": int(s.dp_size),
                       "cp": int(s.cp_size), "sp": int(bool(s.sp)),
                       "ckpt": int(bool(s.checkpoint)),
                       "consec": int(bool(s.tp_consecutive))})
    return {"layers": layers, "pp": int(getattr(hpc, "pp_deg", 1) or 1),
            "bsz": int(getattr(hpc, "global_bsz", 1) or 1),
            "chunks": int(getattr(hpc, "chunks", 1) or 1)}


def evaluate_plan_regret(
    incumbent: Dict[str, Any],
    runner_ups: Sequence[Dict[str, Any]],
    *,
    seq_len: int,
    hidden_size: int,
    param_mb: float,
    mixed_precision: bool = True,
    prior: Tuple[Optional[Dict], Optional[Dict]] = (None, None),
    calibrated: Tuple[Optional[Dict], Optional[Dict]] = (None, None),
    threshold: float = 0.05,
) -> Dict[str, Any]:
    """Re-price the incumbent and its stored runner-ups under calibrated
    curves and measure the regret of keeping the incumbent.

    Each candidate's search-time total (``time_cost_ms``) is adjusted by
    the *differential* the calibration implies: ``adjusted = time_cost_ms
    − comm(prior curves) + comm(calibrated curves)`` — the compute and
    schedule terms the search priced are untouched, only the collective
    model moves. ``triggered`` when the best runner-up's adjusted total
    beats the incumbent's by more than ``threshold`` (a fraction of the
    incumbent's adjusted step time). Candidates the curves cannot price
    are skipped, never guessed."""
    from hetu_galvatron_tpu.core.cost_model.cost import (
        reprice_stored_plan_ms,
    )

    def adjusted(plan: Dict[str, Any]) -> Optional[float]:
        t = plan.get("time_cost_ms")
        if not isinstance(t, (int, float)) or t <= 0:
            return None
        kw = dict(seq_len=seq_len, hidden_size=hidden_size,
                  param_mb=param_mb, mixed_precision=mixed_precision)
        pri = reprice_stored_plan_ms(plan, alpha_beta=prior[0],
                                     alpha_beta_algos=prior[1], **kw)
        cal = reprice_stored_plan_ms(plan, alpha_beta=calibrated[0],
                                     alpha_beta_algos=calibrated[1], **kw)
        if pri is None or cal is None:
            return None
        return float(t) - pri + cal

    inc_ms = adjusted(incumbent)
    rows: List[Dict[str, Any]] = []
    for i, r in enumerate(runner_ups or []):
        if not isinstance(r, dict):
            continue
        a = adjusted(r)
        rows.append({"index": i,
                     "strategies": r.get("strategies"),
                     "time_cost_ms": r.get("time_cost_ms"),
                     "adjusted_ms": (round(a, 6) if a is not None
                                     else None)})
    priced = [r for r in rows if r["adjusted_ms"] is not None]
    out: Dict[str, Any] = {
        "incumbent_ms": round(inc_ms, 6) if inc_ms is not None else None,
        "runner_ups": rows,
        "regret_ms": 0.0,
        "regret_frac": 0.0,
        "threshold": float(threshold),
        "triggered": False,
        "best_runner_up": None,
    }
    if inc_ms is None or not priced:
        return out
    best = min(priced, key=lambda r: r["adjusted_ms"])
    regret = max(inc_ms - best["adjusted_ms"], 0.0)
    out["best_runner_up"] = best["index"]
    out["regret_ms"] = round(regret, 6)
    out["regret_frac"] = round(regret / inc_ms, 6) if inc_ms > 0 else 0.0
    out["triggered"] = bool(regret > 0 and inc_ms > 0
                            and regret / inc_ms > threshold)
    return out


# ---------------------------------------------------------------------------
# the glue: one audit -> append, refit, sentinel
# ---------------------------------------------------------------------------


def run_calibration(
    table: Dict[str, Any],
    hpc: Any,
    model: Any,
    *,
    calibration_dir: str,
    registry: Optional[MetricsRegistry] = None,
    prior_config: Optional[str] = None,
    world: Optional[int] = None,
    device_kind: Optional[str] = None,
    min_points: int = 4,
    window_days: float = 0.0,
    max_points_per_curve: int = 0,
    regret_threshold: float = 0.05,
    plan_path: Optional[str] = None,
    mixed_precision: bool = True,
    recorder: Any = None,
    run_id: Optional[str] = None,
) -> Dict[str, Any]:
    """The whole calibration cycle off one plan-audit table: append the
    run's residual points to the store, refit the α-β curves over the
    accumulated (fingerprint-matched) points, write the calibrated
    profile, score the drift, and run the plan-regret sentinel when the
    plan carries runner-ups. Publishes ``calibration/*`` gauges and at
    most one ``plan_regret`` event into ``registry``. Never raises — it
    runs in the loop-exit ``finally`` alongside the audit; failures land
    in the returned summary's ``error``."""
    reg = registry if registry is not None else get_registry()
    out: Dict[str, Any] = {"points_appended": 0, "points_total": 0,
                           "curves_fitted": 0, "profile_path": None,
                           "drift_score": None, "regret": None}
    try:
        from hetu_galvatron_tpu.core.search_engine.profiles import (
            merge_calibrated_profile,
            read_alpha_beta,
            read_alpha_beta_algos,
            read_json,
        )
        from hetu_galvatron_tpu.observability.telemetry import (
            layer_param_mb,
        )

        fp = hardware_fingerprint(hpc, world=world,
                                  device_kind=device_kind)
        store = ResidualStore(os.path.join(calibration_dir, STORE_NAME))
        pts = calibration_points(table, hpc, model,
                                 mixed_precision=mixed_precision)
        out["points_appended"] = store.append(pts, fingerprint=fp,
                                              run_id=run_id)
        all_pts = store.load(fingerprint=fp)
        loaded = len(all_pts)
        all_pts = window_points(all_pts, window_days=window_days,
                                max_points_per_curve=max_points_per_curve)
        out["points_total"] = len(all_pts)
        out["points_windowed_out"] = loaded - len(all_pts)

        prior_cfg: Optional[Dict[str, Any]] = None
        if prior_config:
            try:
                prior_cfg = (read_json(prior_config)
                             if isinstance(prior_config, str)
                             else dict(prior_config))
            except Exception:  # noqa: BLE001 — calibrate prior-free
                prior_cfg = None

        prof, meta = refit_profile(all_pts, prior=prior_cfg,
                                   min_points=min_points)
        out["curves_fitted"] = len(meta.get("curves", {}))
        full: Optional[Dict[str, Any]] = None
        if prof:
            meta["fingerprint"] = fp
            if isinstance(prior_config, str):
                meta["prior"] = prior_config
            calibrated = dict(prof)
            calibrated[META_KEY] = meta
            full = merge_calibrated_profile(prior_cfg or {}, calibrated)
            _ensure_bandwidth_keys(full)
            out["profile_path"] = write_calibrated_profile(
                os.path.join(calibration_dir, PROFILE_NAME), full)

        ds = drift_score(table)
        out["drift_score"] = ds
        reg.gauge("calibration/points_appended").set(
            out["points_appended"])
        reg.gauge("calibration/points_total").set(out["points_total"])
        reg.gauge("calibration/curves_fitted").set(out["curves_fitted"])
        if ds is not None:
            reg.gauge("calibration/drift_score").set(round(ds, 6))
        if recorder is not None and hasattr(recorder, "retain"):
            recorder.retain("plan_audit", {
                "steps": table.get("steps"),
                "step_device_ms": table.get("step_device_ms"),
                "components": len(table.get("rows") or []),
                "drift_score": ds,
            })

        # plan-regret sentinel: needs the plan's embedded runner-ups AND
        # calibrated curves to re-price them under
        if plan_path and full is not None:
            try:
                with open(plan_path) as f:
                    plan_cfg = json.load(f)
            except (OSError, json.JSONDecodeError):
                plan_cfg = None
            rups = (plan_cfg.get("runner_ups")
                    if isinstance(plan_cfg, dict) else None)
            if isinstance(rups, list) and rups:
                incumbent = plan_spec_from_hpc(hpc)
                incumbent["time_cost_ms"] = plan_cfg.get(
                    "predicted_time_cost_ms")
                res = evaluate_plan_regret(
                    incumbent, rups,
                    seq_len=model.seq_length,
                    hidden_size=model.hidden_size,
                    param_mb=layer_param_mb(model),
                    mixed_precision=mixed_precision,
                    prior=(read_alpha_beta(prior_cfg or {}),
                           read_alpha_beta_algos(prior_cfg or {})),
                    calibrated=(read_alpha_beta(full),
                                read_alpha_beta_algos(full)),
                    threshold=regret_threshold)
                out["regret"] = res
                reg.gauge("calibration/plan_regret_ms").set(
                    res["regret_ms"])
                if res["triggered"]:
                    reg.event("plan_regret", res)
                    if recorder is not None and hasattr(recorder,
                                                        "retain"):
                        recorder.retain("plan_regret", res)
    except Exception as e:  # noqa: BLE001 — loop-exit helper, never fatal
        out["error"] = f"{type(e).__name__}: {e}"
    return out
