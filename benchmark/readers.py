"""Per-layer metrics: one small reader each, declared in a file of its own.

``benchmark/layer_metrics/<name>.json`` holds ``{"what": ..., "reader":
{"kind": ..., ...}}``. The kinds below cover what spans, counters and the
trace offer today; a metric that needs more names a function in a Python
file beside its JSON (``{"kind": "python", "file": "x.py", "function":
"read"}``), which gets the same ``facts`` and returns a number or ``None``.
A ``roofline`` reader takes its kernel's operations and bytes from the
function ``cost`` names: of ``benchmark/flops.py``, or of the ``file`` beside
the JSON where the reader names one, so a new kernel brings its cost and
shares the roofline arithmetic. A reader that finds nothing to read returns
``None`` and the harness leaves that metric out of the line.

``facts`` holds: ``trace`` (``xplane.facts_of``; absent in an untraced
run), ``window``, ``compile``, ``memory``, ``sizes`` (``flops.Sizes``),
``sequences_per_step``, ``chips`` and ``peaks``.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from benchmark import flops, manifest, xplane


def _lookup(facts: Dict[str, Any], dotted: str) -> Any:
    cur: Any = facts
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def read_fact(facts, *, key: str, scale: float = 1.0, **_) -> Optional[float]:
    v = _lookup(facts, key)
    return None if v is None else float(v) * scale


def read_op_time(facts, *, pattern: str, report: str,
                 line: str = xplane.OPS_LINE, **_) -> Optional[float]:
    """Device time of the operations whose name matches ``pattern``, on the
    cell's first device, over the steady window. ``line`` is ``XLA Ops``
    (leaf operations, the default) or ``Async XLA Ops`` (from start to
    done). ``report`` is one of ``ms_per_step``, ``pct_of_busy`` and
    ``exposed_pct_of_step`` (the part during which no other operation runs,
    over the step's device time)."""
    trace = facts.get("trace")
    if not trace:
        return None
    r = trace["reduced"][0]
    ns = xplane.matching_ns(r, pattern, line)
    if report == "ms_per_step":
        return ns / r.periods / 1e6
    if report == "pct_of_busy":
        return 100.0 * ns / (r.busy_s * 1e9)
    if report == "exposed_pct_of_step":
        return 100.0 * xplane.exposed_ns(r, pattern, line) / (r.busy_s * 1e9)
    raise ValueError(f"op_time: unknown report {report!r}")


def read_roofline(facts, *, pattern: str, cost: str, _dir: str,
                  file: Optional[str] = None, **_) -> Optional[float]:
    """Least time by the roofline over measured kernel time, in percent.
    ``cost`` names the function that gives the kernel's operations and
    bytes per step, ``cost(sizes, sequences_per_step) -> {"flops":,
    "bytes":}``: of ``file`` beside the metric's JSON, or of
    ``benchmark/flops.py`` when the reader names no file."""
    trace = facts.get("trace")
    if not trace:
        return None
    r = trace["reduced"][0]
    measured_s = xplane.matching_ns(r, pattern) / r.periods / 1e9
    if measured_s <= 0:
        return None
    where = (manifest.load_python(os.path.join(_dir, file)) if file
             else flops)
    need = getattr(where, cost)(facts["sizes"], facts["sequences_per_step"])
    least = flops.roofline_least_s(need, facts["peaks"], facts["chips"])
    facts.setdefault("roofline_bounds", {})[cost] = least["bound"]
    return 100.0 * least["least_s"] / measured_s


def read_python(facts, *, file: str, function: str, _dir: str, **_):
    mod = manifest.load_python(os.path.join(_dir, file))
    return getattr(mod, function)(facts)


KINDS = {"fact": read_fact, "op_time": read_op_time,
         "roofline": read_roofline, "python": read_python}


def read_metric(name: str, facts: Dict[str, Any],
                root: str = manifest.ROOT) -> Optional[float]:
    path = manifest.layer_metric_path(root, name)
    reader = dict(manifest.read_json(path)["reader"])
    kind = reader.pop("kind")
    return KINDS[kind](facts, _dir=os.path.dirname(path), **reader)
