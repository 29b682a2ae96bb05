"""The expert exchange (``models/moe.py::make_expert_exchange``): the sorted
dispatchers across the chips of an ``ep`` group, on the 8-CPU mesh. The
exchanged layer against the layer that holds every expert on one device
(``_held_dispatch`` at a share of all), the shares
against the uncut reference's layer, a chip that receives every route, what
the compiled step moves, which plans take the exchange, and the expert layer
of the cells that have no ``ep`` axes as the program it was."""

import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from hetu_galvatron_tpu.analysis import eligibility
from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs
from hetu_galvatron_tpu.models import moe
from hetu_galvatron_tpu.models.builder import init_causal_lm
from hetu_galvatron_tpu.runtime.hybrid_config import (
    get_hybrid_parallel_config,
)
from hetu_galvatron_tpu.runtime.mesh import build_mesh
from hetu_galvatron_tpu.utils.strategy import LayerStrategy

pytestmark = [pytest.mark.model]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
H, F, E, K = 32, 24, 8, 2
LAYER = ModelArgs(
    model_type="moe", hidden_size=H, num_hidden_layers=2,
    num_attention_heads=2, vocab_size=64, max_position_embeddings=32,
    seq_length=16, hidden_act="swiglu", normalization="rmsnorm",
    position_embedding_type="rope", tie_word_embeddings=False,
    add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, ffn_hidden_size=F, num_experts=E,
    moe_topk=K, moe_aux_loss_coeff=0.0, moe_dispatcher="dropless",
    use_flash_attn=False)
DP = ("d0", "d1", "d2")


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _layer(router: str, ep: int):
    """The layer's weights and [8, 16, H] tokens. ``skewed``: positive
    tokens and a router whose columns are positive for the experts of the
    group's LAST chip and negative for the rest, so every token chooses
    there."""
    p, _ = moe.init_moe_mlp(jax.random.key(0), LAYER)
    p = {**p, "win": 8.0 * p["win"], "wout": 8.0 * p["wout"]}
    x = jax.random.normal(jax.random.key(1), (8, 16, H))
    if router == "skewed":
        last = (jnp.arange(E) >= E - E // ep)[None, :]
        x = jnp.abs(x)
        p["router"] = jnp.where(last, 1.0, -1.0) * jnp.abs(p["router"])
    return p, x


def _loss(p, x, exchange=None):
    y, _, stats = moe.apply_moe_mlp(p, x, LAYER, compute_dtype=jnp.float32,
                                    exchange=exchange)
    return jnp.sum(jnp.sin(y)), (y, stats)


def _exchanged(ep: int):
    """The jitted value-and-gradient of the layer inside the exchange over
    the first ``log2(ep)`` dp axes of the 8-device mesh."""
    mesh = build_mesh(8, 1)
    ep_axes = DP[:ep.bit_length() - 1]
    exchange = moe.make_expert_exchange(mesh, DP, ep_axes)
    shd = lambda spec: NamedSharding(mesh, spec)
    return jax.jit(
        jax.value_and_grad(lambda p, x: _loss(p, x, exchange),
                           argnums=(0, 1), has_aux=True),
        in_shardings=({"router": shd(P()), "win": shd(P(ep_axes)),
                       "wout": shd(P(ep_axes))}, shd(P(DP))))


@pytest.mark.usefixtures("highest")
@pytest.mark.parametrize("router", ["balanced", "skewed"])
@pytest.mark.parametrize("ep", [2, 4])
def test_the_exchanged_layer_is_the_dropless_layer_on_one_device(
        ep, router, cpu_devices):
    """Output and every gradient (router, both expert matrices, the tokens)
    of the layer inside the exchange against the layer that holds every
    expert on one device (``_held_dispatch`` at a share of all, which
    ``tests/models/test_moe.py`` holds to the plain layer and to the
    parent's ``_dropless_dispatch``); with ep < dp the expert-dp groups
    exchange nothing and their weight gradients add up."""
    p, x = _layer(router, ep)
    (want, (want_y, _)), want_g = jax.jit(jax.value_and_grad(
        _loss, argnums=(0, 1), has_aux=True))(p, x)
    (got, (got_y, stats)), got_g = _exchanged(ep)(p, x)
    # tolerance: fp32, four partial sums in another order
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-6)
    assert abs(float(got) - float(want)) < 1e-4
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(want_g),
            jax.tree_util.tree_leaves_with_path(got_g)):
        np.testing.assert_allclose(
            b, a, rtol=1e-4, atol=1e-5 * float(jnp.abs(a).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))
    # every route is some chip's: none dropped, none counted twice
    rows = np.asarray(stats["rows_by_chip"])
    assert rows.shape == (ep,) and rows.sum() == 8 * 16 * K
    np.testing.assert_array_equal(stats["held_tokens_per_expert"],
                                  stats["tokens_per_expert"])


@pytest.mark.usefixtures("highest")
@pytest.mark.parametrize("ep", [2, 4])
def test_a_chip_that_receives_every_route_drops_none(ep, cpu_devices):
    """Every token to the last chip's experts: that chip's count passes its
    first chunk and it takes every pass there is, to all ``T*K`` rows, while
    the others stop at the first chunk and take none, and the result is the
    one-device layer's, which the case above holds."""
    p, x = _layer("skewed", ep)
    (_, (y, stats)), _ = _exchanged(ep)(p, x)
    rows = np.asarray(stats["rows_by_chip"])
    slots = 16 * K * ep          # an expert-dp group's routes, 8 / ep groups
    assert rows.tolist() == [0.0] * (ep - 1) + [8 * 16 * K]
    first_len = moe.short_rows(slots, E // ep, E)
    chunk_len = moe.overflow_rows(slots, E // ep, E)
    assert (first_len, chunk_len) == (40, 8)
    passes = -(-(slots - first_len) // chunk_len)
    # the mean over the group's chips of what each one's expert-dp groups
    # add up to
    assert float(stats["short_dispatch"]) == (ep - 1) / ep
    assert float(stats["overflow_chunks"]) == passes * (8 // ep) / ep
    assert float(stats["rows_computed"]) == (
        ep * first_len + passes * chunk_len) * (8 // ep) / ep
    want = moe.apply_moe_mlp(p, x, LAYER, compute_dtype=jnp.float32)[0]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)


def _routes_with_one_chip_over_the_line(fullest: int):
    """[8 x 16, K] chosen experts for the 8-device mesh under ep = 4: the
    ep group of the even token blocks sends 44 of its 128 routes to chip
    ``fullest``'s two experts and 28 to each other chip's, the group of the
    odd blocks 32 to each. A chip's first chunk is 40 rows and a pass 8."""
    pairs = [(2 * c, 2 * c + 1) for c in range(4)]
    others = [c for c in range(4) if c != fullest]
    skewed = [pairs[fullest]] * 22 + [pairs[c] for c in others for _ in
                                      range(14)]
    even_group = [pairs[c] for c in range(4) for _ in range(16)]
    idx = np.zeros((8, 16, K), np.int32)
    for block in range(8):
        source = skewed if block % 2 == 0 else even_group
        at = (block // 2) * 16
        idx[block] = source[at:at + 16]
    return jnp.asarray(idx.reshape(8 * 16, K))


@pytest.mark.usefixtures("highest")
@pytest.mark.parametrize("fullest", [0, 1, 2, 3])
def test_the_passes_are_handed_out_chip_by_chip(fullest, cpu_devices):
    """One chip of four is four routes past its first chunk in one of its two
    expert-dp groups: ``passes_by_chip`` says which chip took the pass,
    its mean is the ``overflow_chunks`` the layer always gave, and the
    layer's output and every gradient are bit for bit those of the exchange
    that hands out the means alone (the parent's)."""
    ep = 4
    mesh = build_mesh(8, 1)
    exchange = moe.make_expert_exchange(mesh, DP, DP[:2])
    p, x = _layer("balanced", ep)
    xt = x.reshape(-1, H)
    idx = _routes_with_one_chip_over_the_line(fullest)
    w = jax.nn.softmax(jax.random.normal(jax.random.key(2), idx.shape))

    def means_alone(*a):
        y, stats = exchange(*a)
        return y, {k: v for k, v in stats.items() if k != "passes_by_chip"}

    def run(layer):
        def loss(p, xt, w):
            y, stats = layer(p, xt, idx, w, LAYER, jnp.float32)
            return jnp.sum(jnp.sin(y)), (y, stats)
        shd = lambda spec: NamedSharding(mesh, spec)
        return jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True),
            in_shardings=({"router": shd(P()), "win": shd(P(DP[:2])),
                           "wout": shd(P(DP[:2]))}, shd(P(DP)),
                          shd(P(DP))))(p, xt, w)

    (got, (y, stats)), grads = run(exchange)
    (was, (y_was, stats_was)), grads_was = run(means_alone)
    want = [44.0 + 32.0 if c == fullest else 28.0 + 32.0 for c in range(ep)]
    assert np.asarray(stats["rows_by_chip"]).tolist() == want
    passes = np.asarray(stats["passes_by_chip"])
    assert passes.tolist() == [float(c == fullest) for c in range(ep)]
    assert float(stats["overflow_chunks"]) == passes.mean() == 0.25
    assert float(stats["rows_computed"]) == 2 * 40 + 8 / ep
    assert "passes_by_chip" not in stats_was
    assert float(got) == float(was)
    np.testing.assert_array_equal(y, y_was)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(grads_was),
            jax.tree_util.tree_leaves_with_path(grads)):
        np.testing.assert_array_equal(b, a,
                                      err_msg=jax.tree_util.keystr(path))
    # and it is the layer: every route computed, none twice
    one = moe._held_dispatch(p, xt, idx, w, LAYER, jnp.float32)[0]
    np.testing.assert_allclose(y, one, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("ep", [2, 4, 8])
def test_the_devices_of_a_chip_are_where_axis_index_counts_them(
        ep, cpu_devices):
    """``devices_along(mesh, ep_axes)[r]`` are the ids of the devices on
    which ``axis_index(ep_axes)`` is ``r``: what lays a trace's planes
    (device ids) beside the log line's chips (places in the group), on a
    mesh that holds its devices in another order than their ids."""
    from hetu_galvatron_tpu.ops.pallas.common import on_shards
    from hetu_galvatron_tpu.runtime.mesh import devices_along

    order = [3, 1, 7, 5, 0, 2, 6, 4]
    mesh = build_mesh(8, 1, devices=[cpu_devices[i] for i in order])
    ep_axes = DP[:ep.bit_length() - 1]
    got = devices_along(mesh, ep_axes)
    assert len(got) == ep and sorted(d for ids in got for d in ids) == \
        sorted(d.id for d in cpu_devices[:8])
    where = on_shards(
        lambda x: x + jax.lax.axis_index(ep_axes), mesh, (P(DP),),
        P(DP))(jnp.zeros(8, jnp.int32))
    by_device = {s.device.id: int(s.data[0])
                 for s in where.addressable_shards}
    assert by_device == {d: r for r, ids in enumerate(got) for d in ids}


def _reference_weights(p):
    """The layer's weights under the names benchmark/reference/mellum.py
    reads."""
    w = {"gate.weight": p["router"].T}
    for e in range(E):
        gate, up = jnp.split(p["win"][e], 2, axis=1)
        w.update({f"experts.{e}.gate_proj.weight": gate.T,
                  f"experts.{e}.up_proj.weight": up.T,
                  f"experts.{e}.down_proj.weight": p["wout"][e].T})
    return w


@pytest.mark.usefixtures("highest")
@pytest.mark.parametrize("ep", [2, 4])
def test_the_shares_partial_results_add_up_to_the_uncut_reference(ep):
    """The one test that ties a share to the model, here with every share
    present: ``_held_dispatch`` told it is chip ``r`` of ``ep`` computes the
    group's tokens for its experts alone; the ``ep`` partial results (what
    the exchange reduce-scatters) add up to what the plain reference gives
    for the whole layer, and one share left out does not."""
    from benchmark import reference

    ref = reference.load_family("mellum", ROOT)
    ref_cfg = {"num_experts": E, "num_experts_per_tok": K,
               "norm_topk_prob": True}
    p, x = _layer("balanced", ep)
    flat = x.reshape(-1, H)
    idx, w, _, _ = moe.route_tokens(p, flat, LAYER, jnp.float32)
    held = E // ep
    partials, rows = [], 0.0
    for r in range(ep):
        share = {"win": p["win"][r * held:(r + 1) * held],
                 "wout": p["wout"][r * held:(r + 1) * held]}
        y, stats = moe._held_dispatch(share, flat, idx, w, LAYER,
                                      jnp.float32, ep, r)
        partials.append(y)
        rows += float(stats["rows_held"])
    assert rows == flat.shape[0] * K
    whole = ref.experts(flat, _reference_weights(p), "", ref_cfg)
    np.testing.assert_allclose(sum(partials), whole, rtol=1e-5, atol=2e-6)
    # the control the share cells could not have: one chip's experts absent
    absent = ref.experts(flat, _reference_weights(p), "", {
        **ref_cfg, "experts_left_out": tuple(range(held))})
    np.testing.assert_allclose(sum(partials[1:]), absent, rtol=1e-5,
                               atol=2e-6)
    assert float(jnp.abs(whole - absent).max()) > 1e-3


# ---------------------------------------------------------------------------
# what the compiled step moves
# ---------------------------------------------------------------------------

STACK = LAYER.model_copy(update=dict(num_hidden_layers=2, seq_length=16))


def _compiled_step(ep: int, devices):
    import optax

    from hetu_galvatron_tpu.parallel.spmd import make_spmd_train_step

    args = CoreArgs(model=STACK.model_dump())
    args.parallel.global_ep_deg = ep
    args.parallel.global_train_batch_size = 4
    args.parallel.global_checkpoint = 1
    hpc = get_hybrid_parallel_config(args, 4)
    mesh = build_mesh(4, 1, devices=devices[:4])
    box = {}

    def init(key):
        p, box["axes"] = init_causal_lm(key, STACK)
        return p

    params = jax.eval_shape(init, jax.random.key(0))
    tx = optax.sgd(1e-2)
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        STACK, hpc, mesh, box["axes"], tx, params,
        compute_dtype=jnp.float32, donate=False)
    shaped = lambda specs, tree: jax.tree.map(
        lambda s, a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
        specs, tree, is_leaf=lambda v: isinstance(v, P))
    batch = {k: jax.ShapeDtypeStruct((4, 16), dt, sharding=batch_shd)
             for k, dt in (("tokens", jnp.int32), ("labels", jnp.int32),
                           ("loss_mask", jnp.float32))}
    return step.lower(shaped(pspecs, params),
                      shaped(ospecs, jax.eval_shape(tx.init, params)),
                      batch).compile().as_text()


@pytest.mark.parametrize("ep", [2, 4])
def test_the_compiled_step_moves_tokens_and_no_expert_weight(ep, cpu_devices):
    """The compiled step of a two-block stack under ``ep``: an all-gather
    and a reduce-scatter under ``moe/exchange/*`` in every expert block
    (forward and backward instructions carry the scopes), and NO all-gather that makes a whole expert matrix out of its shards."""
    from hetu_galvatron_tpu.observability.trace_analysis import (
        walk_hlo,
        scope_and_phase,
    )

    hlo = _compiled_step(ep, cpu_devices)
    under = {"moe/exchange/gather": set(), "moe/exchange/scatter": set()}
    for comp, name, opcode, op_name, calls, line, end in walk_hlo(
            hlo):
        scope, phase = scope_and_phase(op_name)
        if scope in under:
            under[scope].add((opcode.split("-start")[0], phase))
        if opcode.startswith("all-gather"):
            # the whole [E, H, 2F] or [E, F, H] behind an all-gather's "="
            result = line.split("=", 1)[1].split(opcode)[0]
            assert f"[{E},{H},{2 * F}]" not in result, line
            assert f"[{E},{F},{H}]" not in result, line
    # the gather and its transpose, a reduce-scatter; the scatter and its
    # transpose, an all-gather (what the recomputed forward repeats XLA:CPU
    # may merge with the forward's: the chip's step keeps all three passes,
    # PERF.md section 5)
    assert {("all-gather", "forward"), ("reduce-scatter", "backward")} <= \
        under["moe/exchange/gather"], under
    assert {("reduce-scatter", "forward"), ("all-gather", "backward")} <= \
        under["moe/exchange/scatter"], under
    # in EVERY expert block: two blocks, so two gathers of tokens a pass
    gathers = [line for _, _, opcode, op_name, _, line, _
               in walk_hlo(hlo)
               if opcode.startswith("all-gather") and "-done" not in opcode
               and scope_and_phase(op_name) == ("moe/exchange/gather",
                                                "forward")]
    tokens = [g for g in gathers if f",{H}]" in g.split("=", 1)[1][:40]]
    assert len(tokens) == STACK.num_hidden_layers, gathers


# ---------------------------------------------------------------------------
# which plans take it
# ---------------------------------------------------------------------------


def _plan(**degrees):
    base = dict(pp_deg=1, tp_size=1, cp_size=1, dp_size=4, ep_size=4)
    return LayerStrategy(**{**base, **degrees})


@pytest.mark.parametrize("degrees, pp, named", [
    ({}, 1, None),
    ({"ep_size": 1}, 1, None),
    ({"tp_size": 2, "dp_size": 2, "ep_size": 2}, 1, "tp=2"),
    ({"cp_size": 2, "dp_size": 2, "ep_size": 2}, 1, "cp=2"),
    ({"tp_size": 2, "dp_size": 2, "ep_size": 2, "etp_size": 2}, 1, "etp=2"),
    ({"dp_size": 2, "ep_size": 2, "pp_deg": 2}, 2, "pp=2"),
])
def test_ep_plan_reason_names_each_plan_the_exchange_does_not_serve(
        degrees, pp, named):
    layers = [_plan(**degrees)] * 2
    reason = eligibility.ep_plan_reason(LAYER, layers, pp)
    assert eligibility.takes_exchange(LAYER, layers[0], pp) == (
        named is None and layers[0].ep_size > 1)
    if named is None:
        assert reason is None
    else:
        assert reason.startswith("block 0: ep=2") and named in reason
    # the capacity dispatcher's einsums are GSPMD's by design: never named
    capacity = LAYER.model_copy(update=dict(moe_dispatcher="capacity"))
    assert eligibility.ep_plan_reason(capacity, layers, pp) is None
    assert not eligibility.takes_exchange(capacity, layers[0], pp)
    # a model without experts has nothing to exchange
    dense = LAYER.model_copy(update=dict(num_experts=0))
    assert eligibility.ep_plan_reason(dense, layers, pp) is None


# ---------------------------------------------------------------------------
# which expert blocks get the grouped matmuls' kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("devices, degrees, kernels, gets", [
    # a mesh of one device: the block's rows and experts are whole on it
    (1, {}, True, True),
    # inside the exchange: a chip on its own experts, under a shard_map
    (8, {"global_ep_deg": 4}, True, True),
    # plain dp: GSPMD cuts the rows, and a Pallas call is none of its own
    (8, {}, True, False),
    # ep beside tp, and etp > 1: the exchange is not taken, the experts
    # are GSPMD's to cut
    (8, {"global_ep_deg": 2, "global_tp_deg": 2}, True, False),
    (8, {"global_ep_deg": 2, "global_tp_deg": 2, "global_etp_deg": 2},
     True, False),
    # the rule of the devices (None: every device a TPU; here none is)
    (1, {}, None, False),
    (8, {"global_ep_deg": 4}, None, False),
])
def test_who_knows_the_plan_hands_an_expert_block_the_kernels(
        devices, degrees, kernels, gets, cpu_devices):
    """``spmd.expert_kernel_overrides``: ``LayerOps.grouped`` for an expert
    block whose operands are local to the device that runs it, on a mesh of
    TPUs (here: where a test says so); the capacity dispatcher and a dense
    block never."""
    from hetu_galvatron_tpu.parallel import spmd

    args = CoreArgs(model=STACK.model_dump())
    for k, v in degrees.items():
        setattr(args.parallel, k, v)
    args.parallel.global_train_batch_size = 8
    hpc = get_hybrid_parallel_config(args, devices)
    mesh = build_mesh(devices, 1, devices=cpu_devices[:devices])
    _, axes = init_causal_lm_shapes(STACK)
    per_layer = spmd._lower_specs(hpc, mesh, axes)[1]
    got = spmd.expert_kernel_overrides(per_layer, mesh, STACK, hpc,
                                       kernels=kernels, interpret=True)
    assert {i: list(ops.given()) for i, ops in got.items()} == (
        {0: ["grouped"], 1: ["grouped"]} if gets else {})
    capacity = STACK.model_copy(update=dict(moe_dispatcher="capacity"))
    assert spmd.expert_kernel_overrides(per_layer, mesh, capacity, hpc,
                                        kernels=kernels) == {}
    # and the loss the plan builds hands them on only there: the kernels'
    # calls are in its program, or ragged_dot is
    if kernels is None:
        params = jax.eval_shape(
            lambda k: init_causal_lm(k, STACK)[0], jax.random.key(0))
        loss = spmd.build_spmd_loss_fn(STACK, hpc, mesh, axes,
                                       compute_dtype=jnp.float32)[0]
        batch = {k: jnp.zeros((8, 16), dt) for k, dt in (
            ("tokens", jnp.int32), ("labels", jnp.int32),
            ("loss_mask", jnp.float32))}
        text = str(jax.make_jaxpr(loss)(params, batch))
        assert "pallas_call" not in text and "ragged_dot" in text


def init_causal_lm_shapes(cfg):
    box = {}

    def init(key):
        p, box["axes"] = init_causal_lm(key, cfg)
        return p

    return jax.eval_shape(init, jax.random.key(0)), box["axes"]


WIDE = LAYER.model_copy(update=dict(hidden_size=128, ffn_hidden_size=128))


@pytest.mark.usefixtures("highest")
def test_the_exchanged_layer_with_the_kernels_is_the_layer_without(
        cpu_devices):
    """Inside the exchange over four chips, at widths of a lane tile and a
    first chunk of 1,280 rows a chip: the layer handed the kernels
    (interpret mode: each chip's own ``pallas_call`` under the
    ``shard_map``) against the same exchange on ``lax.ragged_dot``, output
    and every gradient; a counted pass of 256 rows stays ``ragged_dot``'s
    under the plan's kernels too (``grouped_matmul.PLAN_ROWS``)."""
    from hetu_galvatron_tpu.ops.pallas.grouped_matmul import (
        CALLS,
        make_grouped_matmul,
    )

    mesh = build_mesh(8, 1)
    ep_axes = DP[:2]
    exchange = moe.make_expert_exchange(mesh, DP, ep_axes)
    p, _ = moe.init_moe_mlp(jax.random.key(0), WIDE)
    p = {**p, "win": 4.0 * p["win"], "wout": 4.0 * p["wout"]}
    x = jax.random.normal(jax.random.key(1), (8, 512, 128))
    shd = lambda spec: NamedSharding(mesh, spec)

    def loss(p, x, grouped):
        y, _, _ = moe.apply_moe_mlp(p, x, WIDE, compute_dtype=jnp.float32,
                                    exchange=exchange, grouped=grouped)
        return jnp.sum(jnp.sin(y)), y

    def run(grouped):
        fn = jax.jit(
            jax.value_and_grad(lambda p, x: loss(p, x, grouped),
                               argnums=(0, 1), has_aux=True),
            in_shardings=({"router": shd(P()), "win": shd(P(ep_axes)),
                           "wout": shd(P(ep_axes))}, shd(P(DP))))
        return fn, fn(p, x)

    _, ((want, want_y), want_g) = run(None)
    fn, ((got, got_y), got_g) = run(make_grouped_matmul(mesh,
                                                        interpret=True))
    text = str(jax.make_jaxpr(fn)(p, x))
    assert all(f"name={name}" in text for name in CALLS)
    assert "ragged_dot_general[" in text      # the pass of 256 rows
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-6)
    assert abs(float(got) - float(want)) < 1e-3
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(want_g),
            jax.tree_util.tree_leaves_with_path(got_g)):
        np.testing.assert_allclose(
            b, a, rtol=1e-4, atol=1e-5 * float(jnp.abs(a).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


def test_an_ep_that_does_not_divide_the_experts_is_refused():
    odd = LAYER.model_copy(update=dict(num_experts=6))
    args = CoreArgs(model=odd.model_dump())
    args.parallel.global_ep_deg = 4
    args.parallel.global_train_batch_size = 4
    with pytest.raises(ValueError, match="ep=4 does not divide the 6"):
        get_hybrid_parallel_config(args, 4)
    with pytest.raises(ValueError, match="must divide"):
        moe.held_range(odd, 4, 0)
    args.model.num_experts = 8
    assert get_hybrid_parallel_config(args, 4).layers[0].ep_size == 4


# ---------------------------------------------------------------------------
# the cells without ep axes keep the program they had
# ---------------------------------------------------------------------------

# sha256 of str(jaxpr) of the first expert block's layer, value and
# gradient, at the cell's own sizes (traced on shapes alone). ``olmoe``'s
# (every expert held) is recorded from PR 56, which gave the full holder the
# held share's one body: one sort with its payloads, rows moved by the
# permutation and its inverse. ``laguna``'s (a held share) from PR 52, which
# gave the share its chunks, and PR 56 left it where it was
RECORDED = {
    "laguna_c1_b1":
        "d51ba2076e4f176e20963d36bc4e426883f317bf1dd699b14618ef1554823be9",
    "olmoe_c1_s4k":
        "cfe56334df25bea2f7aa050187f3598623885a5db84ea5fcd627601554119981",
}


@pytest.mark.parametrize("cell_name", sorted(RECORDED))
def test_a_cell_without_ep_axes_traces_to_the_jaxpr_it_traced_to(cell_name):
    from benchmark import manifest as mf

    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        resolve_model_config,
    )

    cell = mf.resolve_cell(mf.load_manifest(ROOT), cell_name, ROOT)
    cfg = resolve_model_config(args_from_cli(
        mf.train_argv(cell, 0, ROOT), mode="train_dist")).model
    first = [i for i, (_, ff) in enumerate(cfg.block_kinds())
             if ff == "experts"][0]
    layer = cfg.for_block(first)
    p = jax.eval_shape(lambda k: moe.init_moe_mlp(k, layer)[0],
                       jax.random.key(0))
    x = jax.ShapeDtypeStruct((1, cfg.seq_length, cfg.hidden_size),
                             jnp.bfloat16)

    def f(p, x):
        y, aux, _ = moe.apply_moe_mlp(p, x, layer)
        return jnp.sum(y.astype(jnp.float32)) + aux

    text = str(jax.make_jaxpr(jax.value_and_grad(f))(p, x))
    assert hashlib.sha256(text.encode()).hexdigest() == RECORDED[cell_name]
