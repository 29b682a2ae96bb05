"""Device time of what ``mellum2-12b-a2.5b-p1`` adds to a step, by named
scope, and what its expert exchange wrote into the program's registry: the
two collectives around an expert layer (``moe/exchange/gather``,
``moe/exchange/scatter``), the attention cores of its window blocks
(``attn/window_core``) and of its full block (``attn/core``), and the first
expert block's balance over the chips of the ``ep`` group
(``moe/chip_imbalance{layer=layer0}``) and over its experts
(``moe/imbalance{layer=layer0}``).

The times read ``step_map.py``'s join (each traced instruction's deepest
scope, from the map the step report keeps) on the cell's FIRST device, as
the four-chip cell's other readers do, so a metric reads the same work
whatever implements it.

Nothing is published (``None``, the line leaves the metric out) where there
is no trace, where the program kept no map or the map holds no instruction
under the metric's scope (the parent commit has no ``moe/exchange/*``),
where the program wrote no such gauge, or where an operation traced inside
a step is no instruction of the step's HLO.
"""

import os

from benchmark import flops, manifest, xplane

_HERE = os.path.dirname(os.path.abspath(__file__))
_step_map = manifest.load_python(os.path.join(_HERE, "step_map.py"))
_gauges = manifest.load_python(os.path.join(_HERE, "program_gauges.py"))

EXCHANGE_SCOPES = ("moe/exchange/gather", "moe/exchange/scatter")
WINDOW_CORE_SCOPES = ("attn/window_core",)
FULL_CORE_SCOPES = ("attn/core",)
# every block has experts: the first expert layer is block 0
FIRST_EXPERT_LAYER = "layer0"
CHIP_IMBALANCE_GAUGE = "moe/chip_imbalance"
IMBALANCE_GAUGE = "moe/imbalance"
COST_FILE, COST = "mellum_window_cost.py", "mellum_window_step_cost"


def exchange_ms(facts):
    return _step_map._ms_a_step(facts, _step_map.SCOPE, EXCHANGE_SCOPES)


def exchange_exposed_pct(facts):
    """The part of ``exchange_ms`` during which no other leaf operation
    runs on that device, over the step's device time."""
    got = _step_map.joined(facts)
    if got is None or not any(c[_step_map.SCOPE] in EXCHANGE_SCOPES
                              for c in got[2].values()):
        return None
    r, leaves, _ = got
    mine = [(s, e) for c, s, e in leaves
            if c[_step_map.SCOPE] in EXCHANGE_SCOPES]
    others = [(s, e) for c, s, e in leaves
              if c[_step_map.SCOPE] not in EXCHANGE_SCOPES]
    covered = xplane.union_ns(mine)
    hidden = covered + xplane.union_ns(others) - xplane.union_ns(
        mine + others)
    return 100.0 * (covered - hidden) / (r.busy_s * 1e9)


def window_core_ms(facts):
    return _step_map._ms_a_step(facts, _step_map.SCOPE, WINDOW_CORE_SCOPES)


def full_core_ms(facts):
    return _step_map._ms_a_step(facts, _step_map.SCOPE, FULL_CORE_SCOPES)


def window_roofline(facts):
    """Least time by the roofline (``mellum_window_cost.py``) over the
    measured time under ``attn/window_core``, in percent."""
    ms = window_core_ms(facts)
    if not ms:
        return None
    cost = getattr(manifest.load_python(os.path.join(_HERE, COST_FILE)), COST)
    least = flops.roofline_least_s(
        cost(facts["sizes"], facts["sequences_per_step"]), facts["peaks"],
        facts["chips"])
    facts.setdefault("roofline_bounds", {})[COST] = least["bound"]
    return 100.0 * least["least_s"] / (ms / 1e3)


def _gauge(name):
    g = _gauges.written(name, layer=FIRST_EXPERT_LAYER)
    return None if g is None else g.value


def chip_imbalance(facts):
    return _gauge(CHIP_IMBALANCE_GAUGE)


def moe_imbalance(facts):
    # only where the layer ran inside the exchange: the gauge of a layer on
    # one chip is ``moe_imbalance``'s
    if _gauge(CHIP_IMBALANCE_GAUGE) is None:
        return None
    return _gauge(IMBALANCE_GAUGE)
