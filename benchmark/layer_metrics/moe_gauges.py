"""What the program's balance tracker wrote into its registry for the first
expert layer: ``moe/imbalance{layer=layer0}`` (the most loaded expert's rows
over the mean), set by ``RuntimeProfiler.log_line`` from the step's
``tokens_per_expert`` on every logged iteration. A program without the gauge
(no expert layer, or a tree that has no tracker) gives ``None``. Looked up
with ``program_gauges.written``, which never creates what it asks for."""

import os

from benchmark import manifest

_gauges = manifest.load_python(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "program_gauges.py"))


def moe_imbalance(facts):
    g = _gauges.written("moe/imbalance", layer="layer0")
    return None if g is None else g.value
