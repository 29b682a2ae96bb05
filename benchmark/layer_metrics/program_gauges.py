"""What the program wrote into its own registry: the one-shot set-up spans
and the compiled step's static memory.

The reader runs in the trainer's process, so ``get_registry()`` is the
registry ``cli/train_dist.py::train`` wrote to: ``span_ms{path=setup/...}``
and ``span_ms{path=train/dispatch}`` histograms (one observation per span,
in ms) and the gauges ``step/static_bytes{part=...}`` (XLA's
``memory_analysis()`` of the executable the loop runs, per device, in
bytes). A histogram or gauge that was never written gives ``None``; it is
looked up in ``metrics()`` and never created by asking.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

GiB = 1024.0 ** 3


def written(name: str, **labels: str) -> Optional[Any]:
    """The metric ``name{labels}`` of the program's registry, if the
    program wrote one."""
    from hetu_galvatron_tpu.observability.registry import get_registry

    for m in get_registry().metrics():
        if m.name == name and m.labels == labels:
            if m.kind == "histogram" and not m.count:
                return None
            return m
    return None


def _span_s(path: str, largest: bool = False) -> Optional[float]:
    h = written("span_ms", path=path)
    if h is None:
        return None
    return (h.snapshot()["max"] if largest else h.total) / 1e3


def setup_imports_s(facts):
    return _span_s("setup/imports")


def setup_init_s(facts):
    return _span_s("setup/init")


def setup_step_report_s(facts):
    return _span_s("setup/step_report")


def setup_first_dispatch_s(facts):
    """The largest ``train/dispatch``: the first iteration's, which traces,
    lowers and compiles the step or loads it from the cache."""
    return _span_s("train/dispatch", largest=True)


def _live_peak_bytes() -> Optional[float]:
    g = written("step/static_bytes", part="live_peak")
    return None if g is None else g.value


def static_hbm_GiB(facts):
    b = _live_peak_bytes()
    return None if b is None else b / GiB


def static_hbm_fill_pct(facts: Dict[str, Any]):
    b = _live_peak_bytes()
    per_device = (facts.get("memory") or {}).get("per_device") or []
    limit = per_device[0].get("bytes_limit") if per_device else None
    return None if b is None or not limit else 100.0 * b / limit
