"""Device time of a step by what the compiled step's own HLO says each
traced instruction is: its named scope, its phase and its collective class.

A TPU trace names its events by HLO instruction (``fusion.12``). The
program, where it reads its optimized HLO for the step report
(``cli/train_dist.py``, span ``setup/step_report``), keeps for EVERY
instruction of a computation that is no fusion's ``(scope, phase,
collective)`` (``observability/trace_analysis.py::step_hlo``; the rules are
in its docstrings and in PERF.md section 3), and a reader in the trainer's
process finds them as ``trace_analysis.step_scopes()["map"]``. These readers
lay the leaf operations that the cell's first device ran inside its traced
steps over that map.

Nothing is published (``None``, the line leaves the metric out) where there
is no trace, where the program kept no such map (the parent commit's
``step_scopes()`` has no ``map``, or there is no ``step_scopes``), where an
operation traced inside a step is no instruction of the map (the join would
be of two programs), or where the map holds no instruction under the
metric's scopes or classes (a cell whose model has no such part). A phase
is a share of a partition: with the join made, the five ``phase_*_ms``
always publish and add up to the summed leaf time of a step.
"""

import importlib

from benchmark import xplane

PHASES = ("forward", "recompute", "backward", "update", "other")
SCOPE, PHASE, COLLECTIVE = 0, 1, 2


def _step_map():
    try:
        mod = importlib.import_module(
            "hetu_galvatron_tpu.observability.trace_analysis")
    except ImportError:
        return None
    kept = getattr(mod, "step_scopes", None)
    found = kept().get("map") if callable(kept) else None
    return found["instructions"] if found else None


def joined(facts):
    """(the first device's reduced trace, its leaves inside a traced step as
    ``(class, start, end)``, the map), each leaf's class looked up by its
    name; ``None`` where the join cannot be made. Made once a run and kept
    in ``facts``: every metric of this file reads the same join."""
    if "step_map_join" not in facts:
        facts["step_map_join"] = _join(facts.get("trace"), _step_map())
    return facts["step_map_join"]


def _join(trace, classes):
    if not trace or not classes:
        return None
    r = trace["reduced"][0]
    inside = lambda s, e: any(a <= s and e <= b for a, b in r.steps)
    leaves = [(n, s, e) for n, s, e in r.leaves if inside(s, e)]
    if any(n not in classes for n, _, _ in leaves):
        return None
    return r, [(classes[n], s, e) for n, s, e in leaves], classes


def _ms_a_step(facts, part, values, partition=False):
    """Summed leaf time a step of the instructions whose ``part`` (scope,
    phase or collective class) is one of ``values``; nothing where the map
    holds no such instruction, unless the values are shares of a
    ``partition`` (a phase without an instruction is 0 ms)."""
    got = joined(facts)
    if got is None:
        return None
    r, leaves, classes = got
    if not partition and not any(c[part] in values
                                 for c in classes.values()):
        return None
    return sum(e - s for c, s, e in leaves
               if c[part] in values) / r.periods / 1e6


def _phase_ms(facts, phase):
    return _ms_a_step(facts, PHASE, (phase,), partition=True)


def phase_forward_ms(facts):
    return _phase_ms(facts, "forward")


def phase_recompute_ms(facts):
    return _phase_ms(facts, "recompute")


def phase_backward_ms(facts):
    return _phase_ms(facts, "backward")


def phase_update_ms(facts):
    return _phase_ms(facts, "update")


def phase_other_ms(facts):
    return _phase_ms(facts, "other")


def scope_unnamed_pct(facts):
    got = joined(facts)
    if got is None:
        return None
    total = sum(e - s for _, s, e in got[1])
    if total <= 0:
        return None
    return 100.0 * sum(e - s for c, s, e in got[1]
                       if c[SCOPE] is None) / total


def attn_proj_ms(facts):
    return _ms_a_step(facts, SCOPE, ("attn/qkv_proj", "attn/out_proj"))


def mlp_ms(facts):
    return _ms_a_step(facts, SCOPE, ("mlp",))


def head_ms(facts):
    return _ms_a_step(facts, SCOPE, ("head",))


def moe_route_ms(facts):
    return _ms_a_step(facts, SCOPE, ("moe/route",))


def moe_dispatch_ms(facts):
    return _ms_a_step(facts, SCOPE, ("moe/dispatch",))


def moe_combine_ms(facts):
    return _ms_a_step(facts, SCOPE, ("moe/combine",))


def short_conv_ms(facts):
    return _ms_a_step(facts, SCOPE, tuple(
        f"mixer/short_conv/{part}"
        for part in ("in_proj", "gate_conv", "out_proj")))


def _collective_classes(facts, overlapped):
    """The classes the map holds that are (not) ``overlapped``: an
    all-gather riding a matmul is compute with traffic behind it."""
    got = joined(facts)
    return tuple({c[COLLECTIVE] for c in (got[2] if got else {}).values()
                  if c[COLLECTIVE]
                  and (c[COLLECTIVE] == "overlapped") == overlapped})


def collective_all_ms(facts):
    """Every instruction that moves data between chips and does nothing
    else: the collectives under their own names, the fused
    reduce-scatters, and both halves of the asynchronous ones (the
    ``-done`` half is the wait)."""
    return _ms_a_step(facts, COLLECTIVE, _collective_classes(facts, False))


def collective_overlapped_ms(facts):
    return _ms_a_step(facts, COLLECTIVE, _collective_classes(facts, True))


def collective_all_exposed_pct(facts):
    """The part of ``collective_all_ms`` during which no other leaf
    operation runs on that device, over the step's device time."""
    got = joined(facts)
    wanted = _collective_classes(facts, False)
    if got is None or not wanted:
        return None
    r, leaves, _ = got
    mine = [(s, e) for c, s, e in leaves if c[COLLECTIVE] in wanted]
    others = [(s, e) for c, s, e in leaves if c[COLLECTIVE] not in wanted]
    covered = xplane.union_ns(mine)
    hidden = covered + xplane.union_ns(others) - xplane.union_ns(
        mine + others)
    return 100.0 * (covered - hidden) / (r.busy_s * 1e9)
