"""Sharding-flow analysis (Pass 5): byte census mechanics, the exact
acceptance-plan cross-check, and the injected drills the acceptance
criteria name — an undonated-buffer step and a stray weight all-gather
must each fail the pass with a diagnostic naming the program and eqn.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_galvatron_tpu.analysis.sharding_flow import (
    check_donation,
    check_flow,
    donation_report,
    flow_compiled_step,
    flow_jaxpr,
    flow_serving_programs,
    hlo_collectives,
    reshard_findings,
)
from hetu_galvatron_tpu.core.args_schema import CoreArgs, ServingArgs
from hetu_galvatron_tpu.observability.telemetry import plan_collective_bytes
from hetu_galvatron_tpu.runtime.hybrid_config import (
    get_hybrid_parallel_config,
)

pytestmark = [pytest.mark.staticcheck, pytest.mark.distributed]

MB = 1024 * 1024


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


ACCEPTANCE = "hetu_galvatron_tpu/profiles/example_plans/" \
    "galvatron_config_acceptance_tp2dp2pp2.json"


def acceptance_setup():
    import os

    root = os.path.join(os.path.dirname(__file__), "..", "..")
    args = tiny_args(config_mode="json",
                     galvatron_config_path=os.path.join(root, ACCEPTANCE))
    return args, get_hybrid_parallel_config(args, 8)


# ---------------------------------------------------------------------------
# byte-walk mechanics on synthetic jaxprs
# ---------------------------------------------------------------------------


def test_scan_multiplies_bytes():
    def body(c, _):
        return c + jax.lax.psum(c, "i"), None

    def fn(x):
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    mesh = Mesh(np.array(jax.devices()[:2]), ("i",))
    sm = shard_map(fn, mesh, in_specs=P("i"), out_specs=P("i"),
                   check_rep=False)
    # local shard: 128 f32 elems = 512 B per psum, 5 scan trips
    flow = flow_jaxpr(jax.make_jaxpr(sm)(jnp.zeros(256, jnp.float32)))
    assert flow.mb_by_cat["all_reduce"] * MB == pytest.approx(5 * 512)


def test_permute_bytes_split_by_marker():
    mesh = Mesh(np.array(jax.devices()[:2]), ("i",))
    perm = [(0, 1), (1, 0)]

    def fn(x):
        with jax.named_scope("tp_ring"):
            y = jax.lax.ppermute(x, "i", perm)
        return y + jax.lax.ppermute(y, "i", perm)  # unmarked

    sm = shard_map(fn, mesh, in_specs=P("i"), out_specs=P("i"),
                   check_rep=False)
    flow = flow_jaxpr(jax.make_jaxpr(sm)(jnp.zeros(512, jnp.float32)))
    each = 256 * 4
    assert flow.permute_mb_by_marker["tp_ring"] * MB == pytest.approx(each)
    assert flow.permute_mb_by_marker["<unmarked>"] * MB == \
        pytest.approx(each)
    assert flow.mb_by_cat["ppermute"] * MB == pytest.approx(2 * each)


def test_byte_mismatch_is_reported():
    from hetu_galvatron_tpu.analysis.sharding_flow import FlowResult

    flow = FlowResult(mb_by_cat={"ppermute": 1.0},
                      permute_mb_by_marker={"pp_rotate": 1.0})
    problems = check_flow(flow, {"ppermute_pp": 2.0}, program="step")
    assert problems and "2.000000" in problems[0]
    assert check_flow(flow, {"ppermute_pp": 1.0}, program="step") == []


def test_surplus_bytes_under_unbilled_marker_are_caught():
    from hetu_galvatron_tpu.analysis.sharding_flow import FlowResult

    flow = FlowResult(
        mb_by_cat={"ppermute": 3.0},
        permute_mb_by_marker={"pp_rotate": 1.0, "cp_ring": 2.0})
    problems = check_flow(flow, {"ppermute_pp": 1.0}, program="step")
    assert problems and "in total" in problems[-1]


# ---------------------------------------------------------------------------
# the acceptance drill: exact bytes, zero reshards, donation clean
# ---------------------------------------------------------------------------


def test_acceptance_plan_bytes_match_plan_arithmetic_exactly():
    """tp2 x dp2 x pp2: the traced compiled step's per-marker megabytes
    equal telemetry.plan_collective_bytes with NO tolerance, there are
    zero reshard findings, and the donation audit passes. The numbers
    themselves are pinned by hand: T=4 ticks, 12 rings x (tp-1)=1 hop x
    2 layer slots on [1,8,64] f32 chunks; 2 rotations x 4 ticks on the
    same slice."""
    args, hpc = acceptance_setup()
    pf = flow_compiled_step(args.model, hpc, args.train, tp_overlap=True)
    predicted = plan_collective_bytes(hpc, args.model, tp_overlap=True)

    hop_b = 1 * 8 * 64 * 4
    assert predicted["ppermute_tp"] * MB == pytest.approx(
        4 * 2 * 12 * 1 * hop_b)
    assert predicted["ppermute_pp"] * MB == pytest.approx(2 * 4 * hop_b)

    assert check_flow(pf.flow, predicted, program="compiled_step") == []
    assert pf.flow.permute_mb_by_marker["tp_ring"] == \
        predicted["ppermute_tp"]
    assert pf.flow.permute_mb_by_marker["pp_rotate"] == \
        predicted["ppermute_pp"]
    assert pf.reshard_problems == []
    assert check_donation(pf.donation, program="compiled_step") == []
    assert pf.donation.donated_mb > pf.donation.undonated_mb


def test_remat_plan_bytes_match(tmp_path):
    """checkpointed pre-norm layers add a 3-ring forward recompute (the
    fc2 ring's output feeds no gradient and is dead code): 15 rings per
    layer slot per tick, still exact."""
    import json
    import os

    root = os.path.join(os.path.dirname(__file__), "..", "..")
    with open(os.path.join(root, ACCEPTANCE)) as f:
        plan = json.load(f)
    plan["checkpoint"] = "1,1,1,1"
    p = str(tmp_path / "ckpt_plan_flow.json")
    with open(p, "w") as f:
        json.dump(plan, f)
    args = tiny_args(config_mode="json", galvatron_config_path=p)
    hpc = get_hybrid_parallel_config(args, 8)
    pf = flow_compiled_step(args.model, hpc, args.train, tp_overlap=True)
    predicted = plan_collective_bytes(hpc, args.model, tp_overlap=True)
    assert predicted["ppermute_tp"] * MB == pytest.approx(
        4 * 2 * 15 * 1 * (8 * 64 * 4))
    assert check_flow(pf.flow, predicted, program="compiled_step") == []


def test_undonated_buffer_drill():
    """The injected regression the acceptance criteria name: the same
    step built with donate=False must FAIL the donation audit with a
    diagnostic naming the program and the largest undonated buffer."""
    args, hpc = acceptance_setup()
    pf = flow_compiled_step(args.model, hpc, args.train, tp_overlap=True,
                            donate=False)
    problems = check_donation(pf.donation, program="compiled_step")
    assert problems, "undonated step must fail the audit"
    assert "compiled_step" in problems[0]
    assert "undonated" in problems[0]
    # the report names concrete buffers with shapes and sizes
    assert pf.donation.largest_undonated
    assert pf.donation.largest_undonated[0][1] > 0


# ---------------------------------------------------------------------------
# reshard drills
# ---------------------------------------------------------------------------


def test_stray_weight_all_gather_drill():
    """An explicit all-gather materializing a >= 1 MB weight inside the
    step path is flagged, naming program + eqn + shape; a tiny gather
    stays under the threshold."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("i",))

    def gather_big(w):
        return jax.lax.all_gather(w, "i", tiled=True)

    big = shard_map(gather_big, mesh, in_specs=P("i", None),
                    out_specs=P(None, None), check_rep=False)
    j = jax.make_jaxpr(big)(jnp.zeros((1024, 512), jnp.float32))
    problems = reshard_findings(j, program="drill_step")
    assert problems, "weight-sized gather must be flagged"
    assert "drill_step" in problems[0] and "eqn" in problems[0]
    assert "1024,512" in problems[0].replace(" ", "") or \
        "1024" in problems[0]

    small = shard_map(gather_big, mesh, in_specs=P("i", None),
                      out_specs=P(None, None), check_rep=False)
    j2 = jax.make_jaxpr(small)(jnp.zeros((16, 16), jnp.float32))
    assert reshard_findings(j2, program="drill_step") == []


def test_double_reshard_drill():
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))

    def double(x):
        y = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("a", None)))
        return jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P(None, "b")))

    with mesh:
        j = jax.make_jaxpr(double)(jnp.zeros((8, 8), jnp.float32))
    problems = reshard_findings(j, program="drill")
    assert problems and "twice" in problems[0]

    def single(x):
        y = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("a", None)))
        return y * 2.0

    with mesh:
        j2 = jax.make_jaxpr(single)(jnp.zeros((8, 8), jnp.float32))
    assert reshard_findings(j2, program="drill") == []


# ---------------------------------------------------------------------------
# serving programs: clean flows, pools donated
# ---------------------------------------------------------------------------


def test_serving_programs_flow_clean():
    args = tiny_args()
    serving = ServingArgs(max_batch_size=2, kv_block_size=8,
                          max_seq_len=32, num_kv_blocks=10,
                          prefix_cache=True, spec_decode=True, spec_k=2)
    flows = flow_serving_programs(args.model, serving=serving)
    assert set(flows) >= {"decode", "prefill_8"}
    for name, pf in flows.items():
        assert pf.reshard_problems == [], name
        # pools are donated in every program family
        assert pf.donation.donated_mb > 0, name


# ---------------------------------------------------------------------------
# partition-time HLO walk
# ---------------------------------------------------------------------------


def test_hlo_walk_flags_partition_time_weight_gather():
    """GSPMD forced to re-materialize a sharded weight: the compiled-HLO
    walk reports the all-gather with its size and flags it above the
    weight threshold."""
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("d0",))
    w = jax.device_put(jnp.zeros((1024, 512), jnp.float32),
                       NamedSharding(mesh, P("d0", None)))
    f = jax.jit(lambda w: w + 1.0, out_shardings=NamedSharding(mesh, P()))
    txt = f.lower(w).compile().as_text()
    cats, findings = hlo_collectives(txt, weight_gather_mb=1.0)
    assert cats["all-gather"]["count"] >= 1
    assert cats["all-gather"]["mb"] >= 2.0
    assert findings and "all-gather" in findings[0]
    assert "1024,512" in findings[0]


def test_hlo_walk_measures_async_start_by_gathered_result():
    """Async collective pairs: the -start op's tuple result lists
    (operand shard, gathered result) — the walk must measure the
    GATHERED size, or a full-weight re-gather at high tp slips under the
    threshold by its shard size; -done halves add no bytes."""
    txt = (
        "  %ag = (f32[1024,128]{1,0}, f32[1024,1024]{1,0}) "
        "all-gather-start(f32[1024,128]{1,0} %p), dimensions={1}\n"
        "  %agd = f32[1024,1024]{1,0} all-gather-done((f32[1024,128]{1,0},"
        " f32[1024,1024]{1,0}) %ag)\n")
    cats, findings = hlo_collectives(txt, weight_gather_mb=2.0)
    assert cats["all-gather"]["count"] == 1
    assert cats["all-gather"]["mb"] == pytest.approx(4.0)
    assert findings and "1024,1024" in findings[0]


def test_hlo_walk_full_compiled_step():
    """The heavy leg (slow tier): compile the acceptance plan's fused
    step and walk its partitioned HLO — the GSPMD-inserted collectives
    are reported, and no full decoder weight is re-gathered (weights
    stay sharded end to end)."""
    import jax.numpy as jnp

    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.runtime.compiled_pipeline import (
        CompiledPipelineEngine,
    )

    args, hpc = acceptance_setup()
    eng = CompiledPipelineEngine(args.model, hpc, args.train,
                                 compute_dtype=jnp.float32,
                                 tp_overlap=True, donate=True)
    params, axes = init_causal_lm(jax.random.key(0), args.model)
    sp = eng.split_params(params, axes)
    so = eng.init_opt(sp, axes)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, args.model.padded_vocab_size,
                       (hpc.global_bsz, args.model.seq_length + 1))
    batch = {"tokens": toks[:, :-1].astype(np.int32),
             "labels": toks[:, 1:].astype(np.int32)}
    txt = eng.step_lowered(sp, so, batch).compile().as_text()
    # full (unsharded) decoder weight threshold: the largest leaf is the
    # stacked gated fc1 [pp, h, 2f] f32 = 2*64*256*4 B per stage pair —
    # use half of it so ANY full-weight gather trips
    weight_mb = (2 * 64 * 256 * 4) / MB / 2
    cats, findings = hlo_collectives(txt, weight_gather_mb=weight_mb)
    assert findings == [], findings
    # the partitioned program does contain GSPMD collectives (dp grad
    # all-reduce at minimum) — the walk sees what the jaxpr cannot
    assert any(k in cats for k in ("all-reduce", "collective-permute"))
