"""What the program's balance tracker wrote into its registry for the first
expert layer of ``lfm2-24b-a2b-ep8`` (block 1 of the stack as run; block 0
is the dense one), a layer that holds a share of its experts:
``moe/local_routes_pct{layer=layer1}`` (routes that fell on a held expert
over all ``T*K``) and ``moe/imbalance{layer=layer1}`` (taken over the held
experts), set by ``RuntimeProfiler.iteration_log`` on every logged
iteration. A program without the gauges (the parent commit has no layer
that holds a share) gives ``None`` and the line leaves the metric out.
Looked up with ``program_gauges.written``, which never creates what it asks
for."""

import os

from benchmark import manifest

_gauges = manifest.load_python(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "program_gauges.py"))

# the first block of the configuration as run that has experts
FIRST_EXPERT_LAYER = "layer1"
LOCAL_ROUTES_GAUGE = "moe/local_routes_pct"
IMBALANCE_GAUGE = "moe/imbalance"
# what the traced cell's readers look up beside these, for the seam test:
# the rows the grouped matmuls were handed against the rows that belonged
# to a held expert
ROWS_GAUGES = ("moe/rows_held", "moe/rows_computed")


def _value(name):
    g = _gauges.written(name, layer=FIRST_EXPERT_LAYER)
    return None if g is None else g.value


def moe_imbalance(facts):
    # only where the layer holds a share: the gauge of a layer that holds
    # every expert is ``moe_imbalance``'s
    if _value(LOCAL_ROUTES_GAUGE) is None:
        return None
    return _value(IMBALANCE_GAUGE)
