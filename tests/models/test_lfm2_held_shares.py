"""LFM2's expert layers where a chip holds a share of the experts
(``tests/models/test_lfm2.py`` has the model): the shares add up to the uncut
layer, and no route to a held expert is dropped. A file of its own so that no
file of the suite runs longer than a worker's fair share
(``tests/conftest.py``: a module's cases stay on one worker)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs, TrainArgs
from hetu_galvatron_tpu.models import moe
from hetu_galvatron_tpu.models.builder import causal_lm_loss
from hetu_galvatron_tpu.models.moe import apply_moe_mlp, init_moe_mlp
from hetu_galvatron_tpu.runtime.dataloader import make_batch

from test_lfm2 import REF_CFG, TINY, ZOO, _family, _seeded

pytestmark = [pytest.mark.model]


# ---------------------------------------------------------------------------
# (c), (d) the share ties to the model; no route to a held expert is dropped
# ---------------------------------------------------------------------------

LAYER = ModelArgs(**{**TINY, "moe_topk": 2})
# a layer that holds 2 of 8 experts, over 64 slots: the first chunk (5/4 of
# the expected 16, in row tiles) and one counted pass behind it (a quarter)
FIRST, CHUNK = 24, 8


def _layer_and_tokens(bias=None, seq=16):
    p, _ = init_moe_mlp(jax.random.key(5), LAYER)
    p["expert_bias"] = (0.2 * jax.random.normal(jax.random.key(6), (8,))
                        if bias is None else jnp.asarray(bias, jnp.float32))
    x = jax.random.normal(jax.random.key(8), (2, seq, 32), jnp.float32)
    return p, x


def _passes(rows_held, first=FIRST, chunk=CHUNK):
    """The counted passes behind the first chunk that ``rows_held`` ask
    for."""
    return -(-max(rows_held - first, 0) // chunk)


def _share(p, first, held=2):
    return {**p, "win": p["win"][first:first + held],
            "wout": p["wout"][first:first + held]}


def _whole_layer_by_the_reference(p, x, first=0, held=8):
    """The uncut reference's layer output: all 8 experts held, or what
    experts ``[first, first + held)`` of them add."""
    w = {"gate.weight": p["router"].T, "expert_bias": p["expert_bias"]}
    for e in range(8):
        gate, up = jnp.split(p["win"][e], 2, axis=1)
        w[f"experts.{e}.w1.weight"] = gate.T
        w[f"experts.{e}.w3.weight"] = up.T
        w[f"experts.{e}.w2.weight"] = p["wout"][e].T
    ref_cfg = {**REF_CFG, "num_experts_per_tok": 2, "num_experts": held,
               "first_expert_held": first}
    return _family().sparse_experts(x.reshape(-1, 32), w, "", ref_cfg
                                    ).reshape(x.shape)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """With 8 tiny experts in shares of 2, the four shares' layer outputs
    add up to what the uncut reference gives for the whole layer (there is
    no shared expert to count once), and their routes to all T*K."""
    p, x = _layer_and_tokens()
    total, rows = 0.0, 0.0
    for first in (0, 2, 4, 6):
        cfg = LAYER.model_copy(update=dict(moe_held_experts=2,
                                           moe_first_held_expert=first))
        y, _, stats = apply_moe_mlp(_share(p, first), x, cfg,
                                    compute_dtype=jnp.float32)
        total, rows = total + y, rows + float(stats["rows_held"])
        # 2 of 8 held of 64 slots: the first chunk's 24 rows and the
        # passes of 8 the count asks for
        passes = _passes(float(stats["rows_held"]))
        assert float(stats["overflow_chunks"]) == passes
        assert float(stats["short_dispatch"]) == (passes == 0)
        assert float(stats["rows_computed"]) == FIRST + passes * CHUNK
    assert rows == 2 * 16 * 2
    # tolerance: fp32, sums in another order
    np.testing.assert_allclose(total, _whole_layer_by_the_reference(p, x),
                               rtol=1e-5, atol=1e-6)


def test_a_token_whose_routes_fall_on_one_share_loses_none():
    """A bias that sends every token's two routes to experts 2 and 3: the
    share that holds them gives the whole layer's output, holds all T*K
    rows (a heavily skewed router drops no route), and the other shares add
    nothing."""
    bias = np.zeros(8)
    bias[2:4] = 10.0
    p, x = _layer_and_tokens(bias)
    whole = _whole_layer_by_the_reference(p, x)
    for first in (0, 2, 4, 6):
        cfg = LAYER.model_copy(update=dict(moe_held_experts=2,
                                           moe_first_held_expert=first))
        y, _, stats = apply_moe_mlp(_share(p, first), x, cfg,
                                    compute_dtype=jnp.float32)
        if first == 2:
            assert float(stats["rows_held"]) == 2 * 16 * 2
            assert float(stats["overflow_chunks"]) == 5
            assert float(stats["rows_computed"]) == FIRST + 5 * CHUNK
            np.testing.assert_allclose(y, whole, rtol=1e-5, atol=1e-6)
        else:
            assert float(stats["rows_held"]) == 0.0
            assert float(stats["overflow_chunks"]) == 0
            assert float(stats["rows_computed"]) == FIRST
            assert float(jnp.max(jnp.abs(y))) == 0.0


def _routed_by_table(p, x, chosen):
    """``p`` with a router under which token ``t`` scores high on exactly
    the experts ``chosen[t]`` (the 32 tokens of ``x`` span the 32-wide
    hidden space, so any table of logits has its router), and a zero
    bias."""
    logits = np.full((32, 8), -6.0, np.float32)
    for t, experts in enumerate(chosen):
        logits[t, list(experts)] = 6.0
    router = jnp.linalg.solve(x.reshape(32, 32), jnp.asarray(logits))
    return {**p, "router": router, "expert_bias": jnp.zeros(8)}


def _held_dispatch_case(case):
    """(p, x, rows that fall on experts 2 and 3) of one case of
    test_both_bodies_of_the_held_dispatch; the first chunk of a layer that
    holds 2 of 8 has 24 of the 64 slots, and a pass behind it 8."""
    if case == "balanced":
        return (*_layer_and_tokens(np.zeros(8)), None)
    if case.startswith("every_route_held"):
        # 64 slots: five passes that end on the last slot; 68 slots (34
        # tokens): six, the last of them started four rows early, where it
        # still fits, and those four rows the fifth's
        bias = np.zeros(8)
        bias[2:4] = 10.0
        seq = 17 if case.endswith("last_chunk_clamped") else 16
        return (*_layer_and_tokens(bias, seq), 4 * seq)
    p, x = _layer_and_tokens()
    if case == "a_chunk_boundary_inside_a_group":
        # expert 2 has 30 rows and expert 3 the next 10: the first chunk
        # ends inside the one's group, the first pass holds the rest of it
        # and two rows of the other's, the second pass the last eight
        chosen = [(2, 3)] * 10 + [(2, 4)] * 20 + [(4, 5)] * 2
        return _routed_by_table(p, x, chosen), x, 40
    # 12 tokens on the two held experts fill the first chunk to its last
    # row; one more route is one too many
    chosen = [(2, 3)] * 12 + [(4, 5)] * 20
    if case == "one_row_over_the_short_buffer":
        chosen[12] = (3, 4)
    return _routed_by_table(p, x, chosen), x, FIRST + (chosen[12] == (3, 4))


@pytest.mark.parametrize("case,passes", [
    ("balanced", 0), ("every_route_held", 5),
    ("every_route_held_last_chunk_clamped", 6),
    ("the_short_buffer_filled", 0),
    ("one_row_over_the_short_buffer", 1),
    ("a_chunk_boundary_inside_a_group", 2)])
def test_both_bodies_of_the_held_dispatch(case, passes, monkeypatch):
    """A layer that holds experts 2 and 3 of 8 computes the first chunk of
    its sorted slots and, where the counted routes pass it, as many further
    chunks as they ask for, to the row (every route held: all ``T*K``, none
    dropped and none counted twice); either way its output and its
    gradients to the rows, the expert weights and the router are the uncut
    reference's for those two experts, and the gradients of the layer
    compiled with the one body over every slot."""
    p, x, rows = _held_dispatch_case(case)
    slots = x.shape[0] * x.shape[1] * 2
    cfg = LAYER.model_copy(update=dict(moe_held_experts=2,
                                       moe_first_held_expert=2))
    cot = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)

    def program(x, win, wout, router):
        q = {**p, "router": router, "win": win[2:4], "wout": wout[2:4]}
        y, _, stats = apply_moe_mlp(q, x, cfg, compute_dtype=jnp.float32)
        return jnp.sum(y * cot), (y, stats)

    def reference(x, win, wout, router):
        q = {**p, "router": router, "win": win, "wout": wout}
        return jnp.sum(_whole_layer_by_the_reference(q, x, 2, 2) * cot)

    args = (x, p["win"], p["wout"], p["router"])
    grad = jax.grad(program, argnums=(0, 1, 2, 3), has_aux=True)
    got, (y, stats) = grad(*args)
    if rows is not None:
        assert float(stats["rows_held"]) == rows
    assert moe.short_rows(slots, 2, 8) == FIRST
    assert moe.overflow_rows(slots, 2, 8) == CHUNK
    assert float(stats["overflow_chunks"]) == passes
    assert float(stats["short_dispatch"]) == (passes == 0)
    assert float(stats["rows_computed"]) == FIRST + passes * CHUNK
    # tolerance: fp32, sums in another order
    np.testing.assert_allclose(y, _whole_layer_by_the_reference(p, x, 2, 2),
                               rtol=1e-5, atol=2e-6)
    want = jax.grad(reference, argnums=(0, 1, 2, 3))(*args)
    monkeypatch.setattr(moe, "short_rows",
                        lambda slots, held, experts, margin=1.25: slots)
    one_body, (_, stats) = grad(*args)
    assert float(stats["rows_computed"]) == slots
    assert float(stats["overflow_chunks"]) == 0.0
    assert float(stats["short_dispatch"]) == 0.0
    for name, g, w, o in zip(("x", "win", "wout", "router"), got, want,
                             one_body):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5 * scale,
                                   err_msg=name)
        np.testing.assert_allclose(g, o, rtol=1e-4, atol=1e-5 * scale,
                                   err_msg=name)


def _leaving_nan_in_rows_of_no_group(real):
    """``moe._grouped_matmul`` as the chip may run it: what it writes to a
    row that belongs to no group is unspecified, forward (the product) and
    backward (the gradient to the rows), so both hold NaN there."""
    @jax.custom_vjp
    def product_leaves_nan(out, tail):
        return jnp.where(tail, jnp.nan, out)

    product_leaves_nan.defvjp(
        lambda out, tail: (jnp.where(tail, jnp.nan, out), tail),
        lambda tail, g: (jnp.where(tail, 0.0, g).astype(g.dtype), None))

    @jax.custom_vjp
    def gradient_leaves_nan(rows, tail):
        return rows

    gradient_leaves_nan.defvjp(
        lambda rows, tail: (rows, tail),
        lambda tail, g: (jnp.where(tail, jnp.nan, g).astype(g.dtype), None))

    def grouped_matmul(rows, weights, group_sizes, out_dtype):
        tail = (jnp.arange(rows.shape[0])[:, None]
                >= jnp.sum(group_sizes))
        return product_leaves_nan(
            real(gradient_leaves_nan(rows, tail), weights, group_sizes,
                 out_dtype), tail)
    return grouped_matmul


@pytest.mark.parametrize("case", ["balanced", "the_short_buffer_filled",
                                  "one_row_over_the_short_buffer",
                                  "a_chunk_boundary_inside_a_group"])
def test_rows_of_no_group_reach_no_result_and_no_gradient(case, monkeypatch):
    """Whatever the grouped matmuls leave in the rows of a chunk of the
    sorted buffer that belong to no group (a share's tail: the routes to
    absent experts; in a counted pass too: seven of the eight rows of the
    pass that one row over the first chunk asks for), forward and
    backward, the layer's output and its gradients to the rows,
    the expert weights and the ROUTER are what they are with zeros there.
    On the chip such a row once held an inf, the transpose of ``ys * ws``
    handed the weights ``0 * inf``, and a step's gradient was NaN from the
    router back (PERF.md section 6, PR 40)."""
    p, x, _ = _held_dispatch_case(case)
    cfg = LAYER.model_copy(update=dict(moe_held_experts=2,
                                       moe_first_held_expert=2))
    cot = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)

    def program(x, win, wout, router):
        q = {**p, "router": router, "win": win[2:4], "wout": wout[2:4]}
        y, _, _ = apply_moe_mlp(q, x, cfg, compute_dtype=jnp.float32)
        return jnp.sum(y * cot), y

    args = (x, p["win"], p["wout"], p["router"])
    grad = jax.grad(program, argnums=(0, 1, 2, 3), has_aux=True)
    want, y_want = grad(*args)
    monkeypatch.setattr(moe, "_grouped_matmul",
                        _leaving_nan_in_rows_of_no_group(moe._grouped_matmul))
    got, y = grad(*args)
    np.testing.assert_array_equal(y, y_want)
    for name, g, w in zip(("x", "win", "wout", "router"), got, want):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("cell,slots,held,experts,first,chunk", [
    # T x K routes a microbatch (mellum2: of the ep group's four chips)
    ("mellum2_c4_ep4", 4 * 4096 * 8, 16, 64, 40960, 8192),
    ("lfm2moe_c1_s8k", 8192 * 4, 8, 64, 5120, 1024),
    ("xing4_c1_b1_s4k", 4096 * 4, 8, 64, 2560, 512),
    ("kimilin_c1_b1_s8k", 8192 * 8, 8, 256, 2560, 512),
    ("laguna_c1_b1", 8192 * 10, 8, 256, 3200, 640)])
def test_the_two_chunk_lengths_follow_the_shapes(cell, slots, held, experts,
                                                 first, chunk):
    """The first chunk is the expected share of the routes and a quarter
    over, a counted pass a quarter of it, in whole row tiles: what the five
    cells that hold a share compute on a balanced step, and by how much a
    pass extends it."""
    assert moe.short_rows(slots, held, experts) == first
    assert moe.overflow_rows(slots, held, experts) == chunk
    assert first % 8 == chunk % 8 == 0 and first < slots
    # a thin share of few slots still moves whole tiles, and no length
    # passes the slots there are
    assert (moe.short_rows(24, 1, 8), moe.overflow_rows(24, 1, 8)) == (8, 8)
    assert (moe.short_rows(4, 7, 8), moe.overflow_rows(4, 7, 8)) == (4, 4)


def _primitives(jaxpr, counts=None):
    """How often each primitive occurs in ``jaxpr``, the bodies of its
    calls, conditionals and loops included."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, counts)
    return counts


@pytest.mark.parametrize("layer,bodies", [
    ("a_quarter_held", 2), ("a_half_held", 2), ("seven_eighths_held", 1),
    ("every_expert_held", 1), ("olmoe", 1)])
def test_only_a_share_under_a_half_has_a_second_body(layer, bodies):
    """What keeps the cells without a held share where they are between
    chip runs: the layer's jaxpr holds the counted loop and two pairs of
    grouped matmuls (the first chunk's and a pass's) where the first chunk
    is shorter than ``T*K``; a layer whose first chunk reaches ``T*K`` (four
    fifths of the experts and more) and a layer that holds every expert
    (the same ``_held_dispatch``; the OLMoE preset at a tiny size) hold one
    pair and no loop, forward and backward. No layer holds a conditional."""
    from hetu_galvatron_tpu.core.arguments import args_from_cli

    if layer == "olmoe":
        cfg = args_from_cli([
            os.path.join(ZOO, "olmoe-1b-7b.yaml"), "model.hidden_size=32",
            "model.num_attention_heads=2", "model.num_key_value_heads=2",
            "model.ffn_hidden_size=32", "model.num_experts=4",
            "model.moe_topk=2"], mode="train_dist").model
    else:
        held = {"a_quarter_held": 2, "a_half_held": 4,
                "seven_eighths_held": 7, "every_expert_held": None}[layer]
        cfg = LAYER.model_copy(update=dict(moe_held_experts=held))
    assert [moe.short_rows(64, held, 8) for held in (2, 4, 7)] == [24, 40, 64]
    p = jax.eval_shape(lambda k: init_moe_mlp(k, cfg)[0], jax.random.key(0))
    x = jax.ShapeDtypeStruct((2, 16, 32), jnp.float32)

    def layer_sum(p, x):
        return jnp.sum(apply_moe_mlp(p, x, cfg, compute_dtype=jnp.float32)[0])
    forward = _primitives(jax.make_jaxpr(layer_sum)(p, x).jaxpr)
    assert forward.get("while", 0) == bodies - 1
    assert forward["ragged_dot_general"] == 2 * bodies
    both = _primitives(jax.make_jaxpr(jax.grad(layer_sum))(p, x).jaxpr)
    # one loop forward and one backward; a chunk's backward recomputes its
    # two grouped matmuls and transposes each twice
    assert both.get("while", 0) == 2 * (bodies - 1)
    assert forward.get("cond", 0) == both.get("cond", 0) == 0
    if bodies == 2:
        assert both["ragged_dot_general"] == 2 * (2 + 6)


@pytest.mark.parametrize("held,skewed,short_pct", [
    (4, True, 0.0),    # half the experts: a first chunk of 40 and three passes
    (2, True, 0.0), (2, False, 100.0)])
def test_a_skewed_router_drops_no_route_and_the_gauge_says_so(held, skewed,
                                                              short_pct):
    """Every token to two held experts, through the train step's metrics and
    ``RuntimeProfiler.iteration_log``: ``moe/rows_held`` is all T*K of the
    step's two microbatches, ``moe/local_routes_pct`` 100, the layer took
    every pass there is (``moe/short_dispatch_pct`` 0, ``moe/rows_computed``
    the chunks that cover all T*K, ``moe/overflow_chunks`` the two
    microbatches' passes) and the
    gradient to the rows outside every group is zero, not what the grouped
    matmuls left there. Under a zero bias a layer that holds 2 of 8 stops
    at the first chunk in both microbatches, and the line and the gauges
    say so."""
    from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
        RuntimeProfiler,
    )
    from hetu_galvatron_tpu.observability.registry import MetricsRegistry
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer
    from hetu_galvatron_tpu.runtime.trainer import make_train_step

    cfg = ModelArgs(**{**TINY, "moe_topk": 2, "moe_held_experts": held,
                       "moe_first_held_expert": 2})
    params = _seeded(cfg)
    bias = jnp.zeros(8).at[2:4].set(10.0 if skewed else 0.0)
    params = {**params, "layers": tuple(
        {**lp, "moe": {**lp["moe"], "expert_bias": bias}}
        if "moe" in lp else lp for lp in params["layers"])}
    batch = jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(5).randint(0, 64, (4, 17))))
    tx = make_optimizer(TrainArgs(lr=1e-3))
    step = make_train_step(
        lambda p, b: causal_lm_loss(p, b, cfg, compute_dtype=jnp.float32,
                                    with_moe_stats=True),
        tx, chunks=2, aux_stats=True)
    _, _, metrics = jax.jit(step)(params, tx.init(params), batch)
    assert np.isfinite(float(metrics["grad_norm"]))
    reg = MetricsRegistry()
    prof = RuntimeProfiler(CoreArgs(model=cfg.model_dump()), registry=reg)
    line = prof.iteration_log(0, metrics)
    gauges = {(m.name, m.labels.get("layer")): m.value
              for m in reg.metrics() if m.name.startswith("moe/")}
    assert gauges[("moe/short_dispatch_pct", "layer1")] == short_pct
    assert gauges[("moe/short_dispatch_pct", "layer4")] == short_pct
    if not skewed:
        # two microbatches of the first chunk's 24 rows
        rows = gauges[("moe/rows_held", "layer1")]
        assert 0 < rows <= 2 * FIRST
        assert gauges[("moe/rows_computed", "layer1")] == 2 * FIRST
        assert gauges[("moe/overflow_chunks", "layer1")] == 0
        assert f"moe[layer1] local {100 * rows / 128:.2f}% " \
               f"rows {rows:.0f}/48 +0 chunks" in line
        return
    first, chunk = moe.short_rows(64, held, 8), moe.overflow_rows(64, held, 8)
    passes = 2 * _passes(64, first, chunk)
    computed = 2 * first + passes * chunk
    assert computed == 4 * 16 * 2
    assert f"moe[layer1] local 100.00% rows 128/{computed} " \
           f"+{passes} chunks" in line
    assert gauges[("moe/rows_held", "layer1")] == 4 * 16 * 2
    assert gauges[("moe/rows_computed", "layer1")] == computed
    assert gauges[("moe/overflow_chunks", "layer4")] == passes
    assert gauges[("moe/local_routes_pct", "layer4")] == 100.0
    # two of the held experts take everything: max / mean = held / 2
    assert gauges[("moe/imbalance", "layer1")] == pytest.approx(held / 2,
                                                                abs=0.2)
