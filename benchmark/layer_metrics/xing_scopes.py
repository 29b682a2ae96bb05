"""Device time of what ``xing4.0-29b-a4b-ep8`` adds to a step, by named
scope: the residual streams' maps and mixes, and the further prediction
depth. (Its latent projections are read by the shared entry
``latent_proj_ms``, which lists the cell.)

The first reads ``step_map.py``'s join (each traced instruction's deepest
scope, from the map the step report keeps: ``hc/maps``, ``hc/mix``). The further depth's block runs the same scopes as every other
block (``attn/core``, ``moe/experts``, ``hc/mix``, ``head``), so its time is
read from the lists the program keeps of every instruction UNDER
``mtp/embed_proj``, ``mtp/block`` and ``mtp/head``, whatever deeper scope it
lies in (``trace_analysis.step_scopes()["scopes"]``), the way
``granite_scopes.py`` reads a mamba block's parts.

Nothing is published (``None``, the line leaves the metric out) where there
is no trace, where the program kept no map or no such list (the parent
commit has neither scope), or where an operation traced inside a step is no
instruction of the step's HLO.
"""

import importlib
import os

from benchmark import manifest

_step_map = manifest.load_python(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "step_map.py"))

HC_SCOPES = ("hc/maps", "hc/mix")
MTP_SCOPES = ("mtp/embed_proj", "mtp/block", "mtp/head")


def hc_ms(facts):
    return _step_map._ms_a_step(facts, _step_map.SCOPE, HC_SCOPES)


def hc_time_share_pct(facts):
    ms, got = hc_ms(facts), _step_map.joined(facts)
    if ms is None or got is None:
        return None
    r = got[0]
    return 100.0 * ms * r.periods * 1e6 / (r.busy_s * 1e9)


def _kept_lists():
    try:
        mod = importlib.import_module(
            "hetu_galvatron_tpu.observability.trace_analysis")
    except ImportError:
        return None
    kept = getattr(mod, "step_scopes", None)
    return (kept() or {}).get("scopes") if callable(kept) else None


def mtp_ms(facts):
    """Summed leaf time a step of every instruction under one of the three
    ``mtp/*`` scopes; the join is ``step_map.py``'s, so a trace of another
    program than the map's gives nothing."""
    got, lists = _step_map.joined(facts), _kept_lists()
    if got is None or not lists or any(not lists.get(s) for s in MTP_SCOPES):
        return None
    r = got[0]
    wanted = {n for s in MTP_SCOPES for n in lists[s]}
    inside = lambda s, e: any(a <= s and e <= b for a, b in r.steps)
    return sum(e - s for n, s, e in r.leaves
               if n in wanted and inside(s, e)) / r.periods / 1e6
