"""Device time of what ``laguna-s-2.1-ep32`` adds to a step, by named scope:
the gate a head (``attn/gate``), and the share of the causal triangle's
score tiles its windowed kernels visit (the gauge ``flash/band_tiles_pct``).
(The attention cores of its window and full blocks are read by the shared
entries ``window_core_ms``, ``window_roofline`` and ``full_core_ms``, which
list the cell.)

The times read ``step_map.py``'s join (each traced instruction's deepest
scope, from the map the step report keeps), so a metric reads the same work
whatever implements it: a scope holds what the program traced under it,
kernel or ``jax.numpy``.

Nothing is published (``None``, the line leaves the metric out) where there
is no trace, where the program kept no map or the map holds no instruction
under the metric's scope (the parent commit has neither ``attn/window_core``
nor ``attn/gate``), where the program wrote no such gauge, or where an
operation traced inside a step is no instruction of the step's HLO.
"""

import os

from benchmark import manifest

_HERE = os.path.dirname(os.path.abspath(__file__))
_step_map = manifest.load_python(os.path.join(_HERE, "step_map.py"))
_gauges = manifest.load_python(os.path.join(_HERE, "program_gauges.py"))

GATE_SCOPES = ("attn/gate",)
BAND_TILES_GAUGE = "flash/band_tiles_pct"


def gate_ms(facts):
    return _step_map._ms_a_step(facts, _step_map.SCOPE, GATE_SCOPES)


def band_tiles_pct(facts):
    g = _gauges.written(BAND_TILES_GAUGE)
    return None if g is None else g.value
