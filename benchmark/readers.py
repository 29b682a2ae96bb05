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

A metric is one entry whatever the cell: a later cell whose program enters
the same scope or writes the same gauge appends its name to the entry's
``workloads`` in ``BENCHMARK.json`` and brings no reader. What differs from
cell to cell is in ``facts``, the cell's configuration among it.

``facts`` holds: ``trace`` (``xplane.facts_of``; absent in an untraced
run), ``window``, ``compile``, ``memory``, ``sizes`` (``flops.Sizes``),
``sequences_per_step``, ``microbatches_per_step``, ``config`` (the cell's
configuration file as read), ``chips`` and ``peaks``.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import inspect

from benchmark import flops, manifest, xplane

# the join of a trace to the compiled step's map: the one every reader of a
# named scope shares
_step_map = manifest.load_python(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "layer_metrics",
    "step_map.py"))


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


def _scope_ms(facts, scopes):
    """Summed leaf time a step (ms) of the instructions whose deepest named
    scope is one of ``scopes``, and the first device's reduced trace:
    ``step_map.py``'s join. ``None`` where the join cannot be made or the
    compiled step holds no instruction under them."""
    ms = _step_map._ms_a_step(facts, _step_map.SCOPE, tuple(scopes))
    return None if ms is None else (ms, _step_map.joined(facts)[0])


def read_scope_time(facts, *, scopes, report: str, **_) -> Optional[float]:
    """Device time of what the program traced under the named ``scopes``
    (the vocabulary is ``trace_analysis.SCOPES``), whatever implements it:
    a kernel of libtpu's, one of the repository's own or ``jax.numpy``.
    ``report`` is ``ms_per_step`` or ``pct_of_busy``."""
    got = _scope_ms(facts, scopes)
    if got is None:
        return None
    ms, r = got
    if report == "ms_per_step":
        return ms
    if report == "pct_of_busy":
        return 100.0 * ms * r.periods * 1e6 / (r.busy_s * 1e9)
    raise ValueError(f"scope_time: unknown report {report!r}")


def cost_of(fn, facts) -> Optional[Dict[str, float]]:
    """``fn(sizes, sequences a step)``, handed beside them what it names
    among ``config`` (the cell's configuration file as read) and
    ``microbatches`` (a step's): so one cost function serves every cell
    that has the layer, and opens no file. ``None``, or neither operations
    nor bytes, says the cell has no such layer."""
    named = inspect.signature(fn).parameters
    offered = {"config": facts.get("config"),
               "microbatches": facts.get("microbatches_per_step")}
    need = fn(facts["sizes"], facts["sequences_per_step"],
              **{k: v for k, v in offered.items() if k in named})
    return need if need and (need["flops"] or need["bytes"]) else None


def roofline_pct(facts, measured_s, cost: str, where) -> Optional[float]:
    """Least time by the roofline of what ``where.<cost>`` counts, over
    ``measured_s`` a step, in percent; nothing where there is no time to
    divide by or nothing counted."""
    if measured_s is None or measured_s <= 0:
        return None
    need = cost_of(getattr(where, cost), facts)
    if need is None:
        return None
    least = flops.roofline_least_s(need, facts["peaks"], facts["chips"])
    facts.setdefault("roofline_bounds", {})[cost] = least["bound"]
    return 100.0 * least["least_s"] / measured_s


def read_roofline(facts, *, cost: str, _dir: str,
                  pattern: Optional[str] = None, scopes=None,
                  file: Optional[str] = None, **_) -> Optional[float]:
    """Least time by the roofline over measured time, in percent. The time
    is that of the operations whose name matches ``pattern``, or of what the
    program traced under the named ``scopes`` (one of the two). ``cost``
    names the function that gives the operations and bytes a step,
    ``cost(sizes, sequences_per_step[, config=, microbatches=]) ->
    {"flops":, "bytes":}``: of ``file`` beside the metric's JSON, or of
    ``benchmark/flops.py`` when the reader names no file."""
    if (pattern is None) == (scopes is None):
        raise ValueError("roofline: a reader names a pattern or scopes, "
                         "one of the two")
    trace = facts.get("trace")
    if not trace:
        return None
    if scopes is not None:
        got = _scope_ms(facts, scopes)
        measured_s = None if got is None else got[0] / 1e3
    else:
        r = trace["reduced"][0]
        measured_s = xplane.matching_ns(r, pattern) / r.periods / 1e9
    where = (manifest.load_python(os.path.join(_dir, file)) if file
             else flops)
    return roofline_pct(facts, measured_s, cost, where)


def read_gauge(facts, *, names, lowest: Optional[str] = None,
               **_) -> Optional[float]:
    """The value of the first of ``names`` that the program wrote into its
    registry as a gauge (looked up, never made by asking). Without
    ``lowest`` the gauge has no label; with it, of the gauges of that name
    that carry the label ``lowest`` and no other, the one whose label
    holds the lowest number (``layer1`` before ``layer2``, both before a
    label without a number such as ``mtp``: the first layer of the stack
    that wrote one)."""
    from hetu_galvatron_tpu.observability.registry import get_registry

    def number(m):
        digits = "".join(c for c in m.labels[lowest] if c.isdigit())
        return (not digits, int(digits or 0))

    written = [m for m in get_registry().metrics() if m.kind == "gauge"]
    for name in names:
        mine = [m for m in written if m.name == name
                and set(m.labels) == ({lowest} if lowest else set())]
        if mine:
            return float((min(mine, key=number) if lowest
                          else mine[0]).value)
    return None


def read_python(facts, *, file: str, function: str, _dir: str, **_):
    mod = manifest.load_python(os.path.join(_dir, file))
    return getattr(mod, function)(facts)


KINDS = {"fact": read_fact, "op_time": read_op_time,
         "scope_time": read_scope_time, "roofline": read_roofline,
         "gauge": read_gauge, "python": read_python}


def read_metric(name: str, facts: Dict[str, Any],
                root: str = manifest.ROOT) -> Optional[float]:
    path = manifest.layer_metric_path(root, name)
    reader = dict(manifest.read_json(path)["reader"])
    kind = reader.pop("kind")
    return KINDS[kind](facts, _dir=os.path.dirname(path), **reader)
