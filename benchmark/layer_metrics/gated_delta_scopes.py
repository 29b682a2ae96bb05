"""Device time of a Gated DeltaNet block's parts, by named scope.

All read ``step_map.py``'s join (each traced instruction's deepest scope,
from the map the step report keeps: ``mixer/gdn/*``), so a metric reads the
same work whatever implements it: a scope holds what the program traced
under it, kernel or ``jax.numpy``.

Nothing is published (``None``, the line leaves the metric out) where there
is no trace, where the program kept no map or the map holds no instruction
under the metric's scopes (a program without the ``mixer/gdn`` scopes, as
the parent commit of the PR that brought them), or where an operation
traced inside a step is no instruction of the step's HLO.
"""

import os

from benchmark import manifest, readers

_HERE = os.path.dirname(os.path.abspath(__file__))
_step_map = manifest.load_python(os.path.join(_HERE, "step_map.py"))

SCOPES = tuple(f"mixer/gdn/{part}" for part in (
    "in_proj", "conv", "gates", "scan", "gated_norm", "out_proj"))
# the part between the projections: the convolutions, the gates, the scan
CORE_SCOPES = ("mixer/gdn/conv", "mixer/gdn/gates", "mixer/gdn/scan")
COST_FILE, COST = "gated_delta_cost.py", "gated_delta_step_cost"


def gated_delta_ms(facts):
    return _step_map._ms_a_step(facts, _step_map.SCOPE, CORE_SCOPES)


def gated_delta_mixer_ms(facts):
    return _step_map._ms_a_step(facts, _step_map.SCOPE, SCOPES)


def gated_delta_roofline(facts):
    """Least time by the roofline (``gated_delta_cost.py``) over the
    measured time of the convolutions, the gates and the scan, in percent."""
    ms = gated_delta_ms(facts)
    if not ms:
        return None
    return readers.roofline_pct(
        facts, ms / 1e3, COST,
        manifest.load_python(os.path.join(_HERE, COST_FILE)))
