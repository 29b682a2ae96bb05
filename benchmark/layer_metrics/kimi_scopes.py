"""Device time of a Kimi Delta Attention block's parts, by named scope.

All read ``step_map.py``'s join (each traced instruction's deepest scope,
from the map the step report keeps: ``mixer/kda/*``), so a metric reads the
same work whatever implements it: a scope holds what the program traced
under it, kernel or ``jax.numpy``. (The cell's ``kimi_latent_proj_ms`` and
``kimi_mlp_ms`` are ``xing_scopes.py``'s and ``step_map.py``'s readers of
``attn/latent_proj`` and ``mlp``, under this cell's names.)

Nothing is published (``None``, the line leaves the metric out) where there
is no trace, where the program kept no map or the map holds no instruction
under the metric's scopes (the parent commit has no ``mixer/kda`` scope),
or where an operation traced inside a step is no instruction of the step's
HLO.
"""

import os

from benchmark import manifest, readers

_HERE = os.path.dirname(os.path.abspath(__file__))
_step_map = manifest.load_python(os.path.join(_HERE, "step_map.py"))

KDA_SCOPES = tuple(f"mixer/kda/{part}" for part in (
    "in_proj", "conv", "gates", "scan", "gated_norm", "out_proj"))
# the part between the projections: the convolutions, the gates, the scan
KDA_CORE_SCOPES = ("mixer/kda/conv", "mixer/kda/gates", "mixer/kda/scan")
COST_FILE, COST = "kimi_kda_cost.py", "kimi_kda_step_cost"


def kda_ms(facts):
    return _step_map._ms_a_step(facts, _step_map.SCOPE, KDA_CORE_SCOPES)


def kda_mixer_ms(facts):
    return _step_map._ms_a_step(facts, _step_map.SCOPE, KDA_SCOPES)


def kda_time_share_pct(facts):
    ms, got = kda_ms(facts), _step_map.joined(facts)
    if ms is None or got is None:
        return None
    r = got[0]
    return 100.0 * ms * r.periods * 1e6 / (r.busy_s * 1e9)


def kda_roofline(facts):
    """Least time by the roofline (``kimi_kda_cost.py``) over the measured
    time of the convolutions, the gates and the scan, in percent."""
    ms = kda_ms(facts)
    if not ms:
        return None
    return readers.roofline_pct(
        facts, ms / 1e3, COST,
        manifest.load_python(os.path.join(_HERE, COST_FILE)))
