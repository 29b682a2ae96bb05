"""Per-algorithm / per-level collective pricing: the min-over-curves
choice must pick the right algorithm per message size, and EMPTY
per-algorithm data must leave every cost byte-identical (the golden search
regressions pin the full-plan version against the legacy fixtures)."""

import numpy as np
import pytest

from hetu_galvatron_tpu.core.cost_model.cost import (
    CostContext,
    _algo_min_ms,
    _tp_message_ms,
    layer_time_cost,
)
from hetu_galvatron_tpu.core.search_engine.strategies import SearchStrategy

pytestmark = [pytest.mark.search_engine]


def _latency_table(per_mb=0.01):
    table = {mb: per_mb * mb for mb in (1, 2, 4, 8, 16, 32, 64, 128)}
    table["popt"] = np.array([per_mb, 0.0])
    return table


def _ctx(**kw):
    base = dict(
        parameter_size=48.0, seq_length=128, hidden_size=256, layer_num=4,
        mixed_precision=True,
        forward_computation_time=0.05,
        comm_coe_dict={"8_1": 0.01, "8_0": 0.01, "4_1": 0.01, "4_0": 0.01,
                       "2_1": 0.01, "2_0": 0.01, "1": 0.0, "1_1": 0.0},
        dp_overlap_coe=1.1, bct_overlap_coe=1.1,
        allgather_latency={2: _latency_table(), 4: _latency_table(),
                           8: _latency_table()},
        all2all_latency={2: _latency_table(), 4: _latency_table(),
                         8: _latency_table()},
    )
    base.update(kw)
    return CostContext(**base)


def _cost(s, ctx, gbsz=64, chunks=1):
    return layer_time_cost(s, ctx, gbsz, chunks)[0]


TP2 = SearchStrategy(pp=1, tp=2, dp=4)
TP4 = SearchStrategy(pp=1, tp=4, dp=2)
DP8 = SearchStrategy(pp=1, tp=1, dp=8)


# ---------------------------------------------------------------------------
# min-over-algorithm-curves
# ---------------------------------------------------------------------------


def test_algo_min_picks_per_message_size():
    """Ring: low α, low β⁻¹ slope advantage at bulk; tree: high bandwidth
    cost but tiny α. The min must switch algorithms with the message
    size — the whole point of fitting per-algorithm curves."""
    algos = {"4_1": {"ring_ici": (1.0, 100.0),   # 1ms + size/100
                     "tree_ici": (0.05, 20.0)}}  # 0.05ms + size/20
    ctx = _ctx(alpha_beta_algos=algos)
    small = _algo_min_ms(ctx, 4, 1, "ici", 0.1)
    big = _algo_min_ms(ctx, 4, 1, "ici", 64.0)
    assert small == pytest.approx(0.05 + 0.1 / 20.0)   # tree wins small
    assert big == pytest.approx(1.0 + 64.0 / 100.0)    # ring wins big
    # level filter: no dcn curves fitted -> None
    assert _algo_min_ms(ctx, 4, 1, "dcn", 1.0) is None
    assert _algo_min_ms(ctx, 2, 1, "ici", 1.0) is None


def test_tp_message_prices_min_of_flat_and_algo_curves():
    ab = {"2_1": (1.0, 50.0)}
    algos = {"2_1": {"tree_ici": (0.1, 50.0)}}
    ctx = _ctx(tp_alpha_beta=ab, alpha_beta_algos=algos)
    # algo curve cheaper at every size here
    assert _tp_message_ms(TP2, ctx, 4.0) == pytest.approx(
        0.5 * (0.1 + 4.0 / 50.0))
    # without algo data, the flat pair prices it (legacy behavior)
    ctx2 = _ctx(tp_alpha_beta=ab)
    assert _tp_message_ms(TP2, ctx2, 4.0) == pytest.approx(
        0.5 * (1.0 + 4.0 / 50.0))


def test_empty_algo_data_costs_byte_identical():
    """The golden-cost discipline: alpha_beta_algos={} (the default)
    reproduces today's costs bit-for-bit."""
    for s in (TP2, TP4, DP8):
        assert _cost(s, _ctx()) == _cost(s, _ctx(alpha_beta_algos={}))


def test_algo_pairs_flip_the_chosen_plan():
    """PINNED plan flip: with slow flat measured tables, tp4 wins (cheap
    dp sync); fitted per-algorithm ICI curves that are much faster at
    size 2 than size 4 flip the winner to tp2 — the choice the
    single-curve model cannot express."""
    coe = {"8_1": 0.1, "8_0": 0.1, "4_1": 0.1, "4_0": 0.1,
           "2_1": 0.1, "2_0": 0.1, "1": 0.0, "1_1": 0.0}
    ctx = _ctx(comm_coe_dict=coe)
    assert _cost(TP4, ctx) < _cost(TP2, ctx)
    algos = {"2_1": {"ring_ici": (0.01, 500.0), "tree_ici": (0.005, 80.0)},
             "4_1": {"ring_ici": (2.0, 60.0), "tree_ici": (1.5, 30.0)}}
    ctx_a = _ctx(comm_coe_dict=coe, alpha_beta_algos=algos)
    assert _cost(TP2, ctx_a) < _cost(TP4, ctx_a)

