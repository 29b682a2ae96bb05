"""Jaxpr collective census (Pass 2): hand-math, markers, injections.

The acceptance drill: on the searched tp2 x dp2 x pp2 plan the census of
the compiled 1F1B step must match the plan arithmetic EXACTLY —
T = m + 2(pp-1) ticks, 12 rings x (tp-1) hops per layer-slot-tick, 2 stage
rotations per tick — and every permute must carry its named_scope marker.
Injected regressions (an unmarked ppermute, a host callback) must each
fail the pass with a diagnostic naming the program.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from hetu_galvatron_tpu.analysis.census import (
    CensusResult,
    census_compiled_step,
    census_jaxpr,
    census_serving_programs,
    check_census,
)
from hetu_galvatron_tpu.core.args_schema import CoreArgs, ServingArgs
from hetu_galvatron_tpu.observability.telemetry import plan_collective_counts
from hetu_galvatron_tpu.runtime.hybrid_config import (
    get_hybrid_parallel_config,
)

pytestmark = [pytest.mark.staticcheck, pytest.mark.distributed]


def tiny_args(**parallel):
    return CoreArgs.model_validate({
        "model": {
            "hidden_size": 64, "num_hidden_layers": 4,
            "num_attention_heads": 4, "vocab_size": 256, "seq_length": 16,
            "max_position_embeddings": 32, "hidden_act": "swiglu",
            "normalization": "rmsnorm", "position_embedding_type": "rope",
            "tie_word_embeddings": False, "add_bias_linear": False,
            "add_qkv_bias": False, "make_vocab_size_divisible_by": 1,
            "ffn_hidden_size": 128,
        },
        "parallel": parallel,
    })


# ---------------------------------------------------------------------------
# census mechanics on synthetic jaxprs
# ---------------------------------------------------------------------------


def test_scan_multiplier_and_recursion():
    def body(c, _):
        return c + jax.lax.psum(c, "i"), None

    def fn(x):
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    mesh = Mesh(np.array(jax.devices()[:2]), ("i",))
    shmapped = shard_map(fn, mesh, in_specs=P("i"), out_specs=P("i"),
                         check_rep=False)
    c = census_jaxpr(jax.make_jaxpr(shmapped)(jnp.zeros(2)))
    assert c.counts == {"all_reduce": 5}


def test_unmarked_permute_is_flagged_and_marked_is_not():
    mesh = Mesh(np.array(jax.devices()[:2]), ("i",))
    perm = [(0, 1), (1, 0)]

    def unmarked(x):
        return jax.lax.ppermute(x, "i", perm)

    def marked(x):
        with jax.named_scope("tp_ring"):
            return jax.lax.ppermute(x, "i", perm)

    for fn, want_unmarked in ((unmarked, 1), (marked, 0)):
        sm = shard_map(fn, mesh, in_specs=P("i"), out_specs=P("i"),
                       check_rep=False)
        c = census_jaxpr(jax.make_jaxpr(sm)(jnp.zeros(2)))
        assert c.counts.get("ppermute") == 1
        assert c.permutes_by_marker.get("<unmarked>", 0) == want_unmarked
        problems = check_census(c, program="drill")
        if want_unmarked:
            assert problems and "drill" in problems[0] \
                and "named_scope" in problems[0]
        else:
            assert problems == []


def test_host_callback_is_flagged():
    def fn(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct((2,),
                                                              jnp.float32),
            x)

    c = census_jaxpr(jax.make_jaxpr(fn)(jnp.zeros(2)))
    assert c.callbacks
    problems = check_census(c, program="step")
    assert problems and "host callback" in problems[0]
    assert check_census(c, program="step", allow_callbacks=True) == []


def test_predicted_count_mismatch_is_reported():
    c = CensusResult(counts={"ppermute": 4},
                     permutes_by_marker={"pp_rotate": 4})
    problems = check_census(c, {"ppermute_pp": 8}, program="step")
    assert problems and "predicts 8" in problems[0]


def test_surplus_permute_in_unpredicted_category_is_caught():
    """Total-strict: a permute under a marker the plan never billed (here
    a cp ring appearing in a plan priced without cp) must fail even though
    its own key is absent from the prediction."""
    c = CensusResult(counts={"ppermute": 10},
                     permutes_by_marker={"pp_rotate": 8, "cp_ring": 2})
    problems = check_census(c, {"ppermute_pp": 8}, program="step")
    assert problems and "bills 8 collective-permutes in total" in \
        problems[0]


# ---------------------------------------------------------------------------
# the real programs
# ---------------------------------------------------------------------------


# NOTE the three real-program tests below trace the full compiled 1F1B /
# serving programs (~seconds each) and ride the slow tier: the tier-1
# budget is nearly saturated, and the SAME exact-count cross-check runs
# in tier-1 anyway inside tests/analysis/test_check_cli.py::
# test_check_all_is_green_at_head (cli.check.run_census fails on any
# census/prediction mismatch, unmarked permute, callback, or missing
# donation).
@pytest.mark.slow
def test_compiled_step_census_matches_hand_math():
    """tp2 x dp2 x pp2, chunks m=2 on the 8-device virtual mesh:
    T = m + 2(pp-1) = 4 ticks; per tick each of the lps=2 layer slots runs
    4 forward rings + (4 recompute + 4 backward) rings of (tp-1)=1
    ppermute hop each -> 4*2*12 = 96 tp-ring permutes; stage rotation =
    2 per tick -> 8 pp permutes. The census and the plan arithmetic
    (plan_collective_counts) must both land exactly there."""
    args = tiny_args(global_tp_deg=2, pp_deg=2, chunks=2, vocab_tp=2,
                     pipeline_type="pipedream_flush",
                     global_train_batch_size=4)
    hpc = get_hybrid_parallel_config(args, 8)
    predicted = plan_collective_counts(hpc, args.model, tp_overlap=True)
    assert predicted == {"ppermute_pp": 8, "ppermute_tp": 96}
    c = census_compiled_step(args.model, hpc, args.train, tp_overlap=True)
    assert c.permutes_by_marker.get("tp_ring") == 96
    assert c.permutes_by_marker.get("pp_rotate") == 8
    assert c.permutes_by_marker.get("<unmarked>", 0) == 0
    assert c.counts["ppermute"] == 104
    assert c.callbacks == []
    assert c.donated_args > 0  # the fused step donates (params, opt)
    assert check_census(c, predicted, program="compiled_step") == []


@pytest.mark.slow
def test_compiled_step_census_without_rings_has_only_rotations():
    args = tiny_args(global_tp_deg=2, pp_deg=2, chunks=2, vocab_tp=2,
                     pipeline_type="pipedream_flush",
                     global_train_batch_size=4)
    hpc = get_hybrid_parallel_config(args, 8)
    c = census_compiled_step(args.model, hpc, args.train, tp_overlap=False)
    assert c.permutes_by_marker == {"pp_rotate": 8}
    predicted = plan_collective_counts(hpc, args.model, tp_overlap=False)
    assert check_census(c, predicted, program="compiled_step") == []


def test_plan_collective_counts_rejects_unmodeled_shapes():
    args = tiny_args(global_tp_deg=1, global_cp_deg=2, pp_deg=1, chunks=1,
                     global_train_batch_size=8)
    hpc = get_hybrid_parallel_config(args, 8)
    with pytest.raises(ValueError):
        plan_collective_counts(hpc, args.model)
    # with the rings off too, and pipelined: a cp plan's hops have no
    # exact prediction
    with pytest.raises(ValueError):
        plan_collective_counts(hpc, args.model, tp_overlap=False)
    args_pp = tiny_args(global_tp_deg=1, global_cp_deg=2, pp_deg=2,
                        chunks=2, global_train_batch_size=8)
    hpc_pp = get_hybrid_parallel_config(args_pp, 8)
    with pytest.raises(ValueError):
        plan_collective_counts(hpc_pp, args_pp.model, tp_overlap=False)
    from hetu_galvatron_tpu.observability.telemetry import (
        plan_collective_bytes,
    )

    with pytest.raises(ValueError):
        plan_collective_bytes(hpc_pp, args_pp.model, tp_overlap=False)


@pytest.mark.slow
def test_serving_programs_have_no_callbacks_or_unmarked_permutes():
    args = tiny_args()
    serving = ServingArgs(max_batch_size=2, kv_block_size=8,
                          max_seq_len=32, num_kv_blocks=10)
    results = census_serving_programs(args.model, serving=serving)
    assert set(results) == {"prefill_8", "decode"}
    for name, c in results.items():
        assert check_census(c, program=name) == [], name
