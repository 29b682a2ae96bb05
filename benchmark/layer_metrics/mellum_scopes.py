"""Device time of what ``mellum2-12b-a2.5b-p1`` adds to a step, by named
scope, and what its expert exchange wrote into the program's registry: the
two collectives around an expert layer (``moe/exchange/gather``,
``moe/exchange/scatter``) and the first expert block's balance over the
chips of the ``ep`` group (``moe/chip_imbalance{layer=layer0}``). (Its
window and full cores, its experts and the balance over its experts are
read by the shared entries ``window_core_ms``, ``window_roofline``,
``full_core_ms``, ``experts_*`` and ``moe_imbalance``, which list the
cell.)

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

from benchmark import manifest, xplane

_HERE = os.path.dirname(os.path.abspath(__file__))
_step_map = manifest.load_python(os.path.join(_HERE, "step_map.py"))
_gauges = manifest.load_python(os.path.join(_HERE, "program_gauges.py"))

EXCHANGE_SCOPES = ("moe/exchange/gather", "moe/exchange/scatter")
# every block has experts: the first expert layer is block 0
FIRST_EXPERT_LAYER = "layer0"
CHIP_IMBALANCE_GAUGE = "moe/chip_imbalance"


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


def _gauge(name):
    g = _gauges.written(name, layer=FIRST_EXPERT_LAYER)
    return None if g is None else g.value


def chip_imbalance(facts):
    return _gauge(CHIP_IMBALANCE_GAUGE)
