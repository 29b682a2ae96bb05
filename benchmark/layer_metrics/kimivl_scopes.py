"""Device time of what ``kimi-vl-a3b-ep8`` adds to a step, by named scope:
the tower of image patches in front of the decoder (``tower/patch_embed``,
``tower/attn_proj``, ``tower/attention``, ``tower/mlp``,
``tower/merge_project``) and the placing of the projector's rows in the
embedded sequence (``embed/place_images``); and the share of its attention
cores' work that the mask leaves (the gauges ``tower/pairs_masked`` and
``tower/pairs_tiled``).

The times read ``step_map.py``'s join (each traced instruction's deepest
scope, from the map the step report keeps), so a metric reads the same work
whatever implements it: a scope holds what the program traced under it,
kernel or ``jax.numpy``. ``tower/attention`` holds the attention cores'
calls and nothing else of a block (the flash kernels on a TPU, with
whatever relayout they make around themselves).

Nothing is published (``None``, the line leaves the metric out) where there
is no trace, where the program kept no map or the map holds no instruction
under the metric's scopes (the parent commit has no ``tower/*`` scope),
where the program wrote no such gauge, or where an operation traced inside
a step is no instruction of the step's HLO.
"""

import os

from benchmark import manifest, readers

_HERE = os.path.dirname(os.path.abspath(__file__))
_step_map = manifest.load_python(os.path.join(_HERE, "step_map.py"))
_gauges = manifest.load_python(os.path.join(_HERE, "program_gauges.py"))

CORE_SCOPES = ("tower/attention",)
MLP_SCOPES = ("tower/mlp",)
MERGE_SCOPES = ("tower/merge_project",)
PLACE_SCOPES = ("embed/place_images",)
TOWER_SCOPES = ("tower/patch_embed", "tower/attn_proj") + CORE_SCOPES \
    + MLP_SCOPES + MERGE_SCOPES
PAIRS_GAUGES = ("tower/pairs_masked", "tower/pairs_tiled")
COST_FILE, COST = "kimivl_tower_cost.py", "kimivl_tower_step_cost"


def _ms(facts, scopes):
    return _step_map._ms_a_step(facts, _step_map.SCOPE, scopes)


def tower_ms(facts):
    return _ms(facts, TOWER_SCOPES)


def tower_core_ms(facts):
    return _ms(facts, CORE_SCOPES)


def tower_mlp_ms(facts):
    return _ms(facts, MLP_SCOPES)


def merge_project_ms(facts):
    return _ms(facts, MERGE_SCOPES)


def place_images_ms(facts):
    return _ms(facts, PLACE_SCOPES)


def tower_share_pct(facts):
    ms, got = tower_ms(facts), _step_map.joined(facts)
    if ms is None or got is None:
        return None
    r = got[0]
    return 100.0 * ms * r.periods * 1e6 / (r.busy_s * 1e9)


def tower_pairs_pct(facts):
    """The (query, key) pairs the tower's mask leaves over the pairs its
    attention cores compute, in percent: facts of the run, not times."""
    masked, tiled = (_gauges.written(name) for name in PAIRS_GAUGES)
    if masked is None or tiled is None or not tiled.value:
        return None
    return 100.0 * masked.value / tiled.value


def tower_core_roofline(facts):
    """Least time by the roofline (``kimivl_tower_cost.py``) over the
    measured time under ``tower/attention``, in percent."""
    ms = tower_core_ms(facts)
    if not ms:
        return None
    return readers.roofline_pct(
        facts, ms / 1e3, COST,
        manifest.load_python(os.path.join(_HERE, COST_FILE)))
