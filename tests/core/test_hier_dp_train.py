"""Hierarchical dp gradient-reduction drills on the virtual 8-device mesh.

The acceptance drill: a searched-format tp2 x dp4 plan trains 3 steps with
the hierarchical reduce-scatter/all-reduce/all-gather path vs the flat
GSPMD all-reduce — trajectories equal within a tight tolerance (the two
differ ONLY by cross-dp reduction reassociation: per-device contractions
are identical, the lane sums just associate host-first), zero steady-state
recompiles, and the traced step's explicit collective counts AND bytes
match ``plan_collective_counts/bytes`` exactly.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs, TrainArgs
from hetu_galvatron_tpu.models.builder import init_causal_lm
from hetu_galvatron_tpu.parallel.spmd import make_spmd_train_step, shard_params
from hetu_galvatron_tpu.runtime.dataloader import make_batch
from hetu_galvatron_tpu.runtime.hybrid_config import get_hybrid_parallel_config
from hetu_galvatron_tpu.runtime.mesh import build_mesh
from hetu_galvatron_tpu.runtime.optimizer import make_optimizer
from hetu_galvatron_tpu.utils.strategy import (
    EmbeddingLMHeadStrategy,
    LayerStrategy,
    strategy_list2config,
)

pytestmark = [pytest.mark.core, pytest.mark.distributed]

CFG = ModelArgs(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    vocab_size=128, max_position_embeddings=64, seq_length=16,
    hidden_act="swiglu", normalization="rmsnorm",
    position_embedding_type="rope", tie_word_embeddings=False,
    add_bias_linear=False, add_qkv_bias=False, use_flash_attn=False,
    make_vocab_size_divisible_by=1, ffn_hidden_size=128,
)
TRAIN = TrainArgs(lr=1e-2, clip_grad=1.0, weight_decay=0.01,
                  lr_decay_style="constant", lr_warmup_iters=0)


def _searched_plan_json(tmp_path, tp=2, dp=4, cp=1, dp_type="ddp", gbsz=8,
                        chunks=2, vtp=None):
    layers = [LayerStrategy(pp_deg=1, tp_size=tp, dp_size=dp, cp_size=cp,
                            dp_type=__import__(
                                "hetu_galvatron_tpu.utils.strategy",
                                fromlist=["DPType"]).DPType.from_name(
                                    dp_type))
              for _ in range(CFG.num_hidden_layers)]
    cfg = strategy_list2config(
        layers, global_bsz=gbsz, chunks=chunks,
        pipeline_type="pipedream_flush", default_dp_type=dp_type,
        vocab=EmbeddingLMHeadStrategy(vtp=tp if vtp is None else vtp),
        pp_division=[CFG.num_hidden_layers])
    path = tmp_path / "galvatron_config_hier.json"
    path.write_text(json.dumps(cfg))
    return str(path)


def _steps(tmp_path, cpu_devices, hier_dp, *, n=3, dp_type="ddp",
           chunks=2, dcn_slices=2, hier_bucket_mb=0.0, tp=2, dp=4, cp=1,
           vtp=None):
    a = CoreArgs(model=CFG.model_dump(), train=TRAIN.model_dump())
    a.parallel.config_mode = "json"
    a.parallel.galvatron_config_path = _searched_plan_json(
        tmp_path, tp=tp, dp=dp, cp=cp, dp_type=dp_type, chunks=chunks,
        vtp=vtp)
    hpc = get_hybrid_parallel_config(a, 8)
    mesh = build_mesh(8, 1, devices=cpu_devices[:8], dcn_slices=dcn_slices)
    tx = make_optimizer(TRAIN)
    params, axes = init_causal_lm(jax.random.key(0), CFG)
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        CFG, hpc, mesh, axes, tx, params, compute_dtype=jnp.float32,
        donate=False, hier_dp=hier_dp, dcn_slices=dcn_slices,
        hier_bucket_mb=hier_bucket_mb)
    sp = shard_params(params, pspecs, mesh)
    so = jax.jit(tx.init, out_shardings=jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))(sp)
    data = np.random.RandomState(0).randint(0, 128, (8, CFG.seq_length + 1))
    b = jax.device_put(jax.tree.map(jnp.asarray, make_batch(data)),
                       batch_shd)
    losses = []
    for _ in range(n):
        sp, so, m = step(sp, so, b)
        losses.append(float(m["loss"]))
    return step, sp, so, b, losses


@pytest.mark.parametrize("dp_type,chunks", [("ddp", 2), ("zero2", 2),
                                            ("zero3", 2)])
def test_hier_vs_flat_trajectory(tmp_path, cpu_devices, dp_type, chunks):
    """3-step trajectories equal within reassociation tolerance, params
    included, under ddp AND the ZeRO flavours.

    zero3 now runs at chunks=2: the flat path's microbatch-scan sharding
    bug (the chunk axis absorbing the outer dp mesh axis, which made the
    partitioner's ZeRO-3 gradient program numerically wrong) is FIXED by
    the scanned-microbatch pin in ``make_spmd_train_step``, so the flat
    side is a valid reference everywhere — see
    ``test_hier_zero3_matches_single_device_where_flat_drifts``."""
    _, sp0, _, _, l0 = _steps(tmp_path, cpu_devices, False, dp_type=dp_type,
                              chunks=chunks)
    _, sp1, _, _, l1 = _steps(tmp_path, cpu_devices, True, dp_type=dp_type,
                              chunks=chunks)
    np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-5)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(sp0),
            jax.tree_util.tree_leaves_with_path(sp1)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(pa))


@pytest.mark.parametrize("dp_type", ["ddp", "zero3"])
def test_hier_lane_keeps_a_tp_layers_interior_on_its_shards(
        tmp_path, cpu_devices, dp_type):
    """The lane loss gets ``spmd.interior_sharding``'s ``shard`` and views as
    the flat loss does (which is why the two trajectories above agree to
    reassociation): the lane step moves no activation between a layer's two
    projections. Before PR 28 it held 16 all-to-alls there (``split``, the
    gated product's ``mul``, their transposes); what is left is the lane
    batch's own slice, outside the vmapped loss."""
    from tools.aot_hlo_report import parse_hlo

    step, sp, so, b, _ = _steps(tmp_path, cpu_devices, True, n=1,
                                dp_type=dp_type)
    hlo = step.lower(sp, so, b).compile().as_text()
    moved = [ins["op_name"] for _, instrs in parse_hlo(hlo) for ins in instrs
             if ins["opcode"].startswith("all-to-all")]
    assert [name for name in moved if "vmap(" in name] == [], moved


@pytest.mark.parametrize("dp_type", ["ddp", "zero2", "zero3"])
def test_hier_bucketed_matches_monolithic_trajectory(tmp_path, cpu_devices,
                                                     dp_type):
    """THE bucketed acceptance drill: the software-pipelined schedule
    (hier_bucket_mb small enough for several buckets on the tiny payload)
    is BIT-consistent with the monolithic hier path on the tp2 x dp4 plan
    — every element rides the same rs->ar->ag association, a bucket is
    just a contiguous slice — under ddp and both ZeRO flavours."""
    _, sp0, _, _, l0 = _steps(tmp_path, cpu_devices, True, dp_type=dp_type)
    _, sp1, _, _, l1 = _steps(tmp_path, cpu_devices, True, dp_type=dp_type,
                              hier_bucket_mb=0.02)
    np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-5)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(sp0),
            jax.tree_util.tree_leaves_with_path(sp1)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=jax.tree_util.keystr(pa))


def test_hier_bucketed_zero_steady_state_recompiles(tmp_path, cpu_devices):
    step, sp, so, b, _ = _steps(tmp_path, cpu_devices, True,
                                hier_bucket_mb=0.02)
    n0 = step._cache_size()
    assert n0 == 1
    for _ in range(2):
        sp, so, _ = step(sp, so, b)
    assert step._cache_size() == n0


def test_hier_cp_plan_takes_hier_path(tmp_path, cpu_devices):
    """cp-bearing sdp plan (tp1 x cp2 x dp4) through the hierarchical
    path: eligibility no longer kicks it flat (the lane vmap covers the
    dp axes; the in-lane cp partial sums stay a GSPMD reduction and the
    ring kernel swaps for the GSPMD attention core), and the 3-step
    trajectory + params match the flat path within reassociation/
    association tolerance."""
    from hetu_galvatron_tpu.analysis.eligibility import plan_hier_dp_reason

    _, sp0, _, _, l0 = _steps(tmp_path, cpu_devices, False, tp=1, cp=2,
                              dp=4, vtp=1)
    _, sp1, _, _, l1 = _steps(tmp_path, cpu_devices, True, tp=1, cp=2,
                              dp=4, vtp=1)
    np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-5)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(sp0),
            jax.tree_util.tree_leaves_with_path(sp1)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(pa))


def test_hier_cp_plan_eligible_and_zigzag_not(tmp_path):
    """The plan-level predicate: cp/ulysses sdp groups are eligible;
    zigzag-cp keeps the shared reason (its pre-permuted data layout is
    only correct under the ring kernel)."""
    from hetu_galvatron_tpu.analysis.eligibility import (
        HIER_ZIGZAG_REASON,
        hier_dp_unsupported_reason,
    )

    assert hier_dp_unsupported_reason(dp=4, cp=2) is None
    assert hier_dp_unsupported_reason(dp=4, ulysses=True, tp=2) is None
    assert hier_dp_unsupported_reason(dp=4, cp=2, cp_zigzag=True) == \
        HIER_ZIGZAG_REASON


def test_hier_cp_census_counts_and_bytes_exact(tmp_path, cpu_devices):
    """The cp-bearing lane program's explicit collectives are EXACTLY the
    hier rs/ar/ag (the cp partial-sum reduction is partition-time GSPMD,
    invisible to the jaxpr; the ring kernel is swapped out), counts and
    padded bytes pinned to the plan arithmetic."""
    from hetu_galvatron_tpu.analysis.census import (
        census_spmd_step,
        check_census,
    )
    from hetu_galvatron_tpu.analysis.sharding_flow import (
        check_flow,
        flow_spmd_step,
    )
    from hetu_galvatron_tpu.observability.telemetry import (
        plan_collective_bytes,
        plan_collective_counts,
    )

    a = CoreArgs(model=CFG.model_dump(), train=TRAIN.model_dump())
    a.parallel.config_mode = "json"
    a.parallel.galvatron_config_path = _searched_plan_json(
        tmp_path, tp=1, cp=2, dp=4, vtp=1)
    hpc = get_hybrid_parallel_config(a, 8)
    mesh = build_mesh(8, 1, devices=cpu_devices[:8], dcn_slices=2)

    census = census_spmd_step(CFG, hpc, TRAIN, mesh, tp_overlap=False,
                              hier_dp=True, dcn_slices=2)
    pred = plan_collective_counts(hpc, CFG, tp_overlap=False, hier_dp=True)
    assert pred == {"reduce_scatter": 1, "all_reduce": 1, "all_gather": 1}
    assert check_census(census, pred, program="spmd_hier_cp") == []

    pf = flow_spmd_step(CFG, hpc, TRAIN, mesh, tp_overlap=False,
                        hier_dp=True, dcn_slices=2, gather_mb=1e-6)
    pred_mb = plan_collective_bytes(hpc, CFG, tp_overlap=False,
                                    hier_dp=True, hier_cross=2)
    assert check_flow(pf.flow, pred_mb, program="spmd_hier_cp") == []


@pytest.mark.parametrize("bucket_mb", [0.02, 0.01])
def test_hier_bucketed_census_counts_and_bytes_exact(tmp_path, cpu_devices,
                                                     bucket_mb):
    """Bucketed acceptance: the traced pipelined step contains EXACTLY
    3 x buckets collectives with exactly the per-bucket padded payload
    megabytes the shared hier_bucket_layout arithmetic promises — pinned
    at two different bucket counts (zero tolerance)."""
    from hetu_galvatron_tpu.analysis.census import (
        census_spmd_step,
        check_census,
    )
    from hetu_galvatron_tpu.analysis.sharding_flow import (
        check_flow,
        flow_spmd_step,
    )
    from hetu_galvatron_tpu.observability.telemetry import (
        plan_collective_bytes,
        plan_collective_counts,
    )

    a = CoreArgs(model=CFG.model_dump(), train=TRAIN.model_dump())
    a.parallel.config_mode = "json"
    a.parallel.galvatron_config_path = _searched_plan_json(tmp_path)
    hpc = get_hybrid_parallel_config(a, 8)
    mesh = build_mesh(8, 1, devices=cpu_devices[:8], dcn_slices=2)

    census = census_spmd_step(CFG, hpc, TRAIN, mesh, tp_overlap=False,
                              hier_dp=True, dcn_slices=2,
                              hier_bucket_mb=bucket_mb)
    pred = plan_collective_counts(hpc, CFG, tp_overlap=False, hier_dp=True,
                                  hier_bucket_mb=bucket_mb, hier_cross=2)
    n = pred["reduce_scatter"]
    assert n > 1 and pred == {"reduce_scatter": n, "all_reduce": n,
                              "all_gather": n}
    assert check_census(census, pred,
                        program=f"spmd_hier_b{bucket_mb}") == []

    pf = flow_spmd_step(CFG, hpc, TRAIN, mesh, tp_overlap=False,
                        hier_dp=True, dcn_slices=2,
                        hier_bucket_mb=bucket_mb, gather_mb=1e-6)
    pred_mb = plan_collective_bytes(hpc, CFG, tp_overlap=False,
                                    hier_dp=True, hier_cross=2,
                                    hier_bucket_mb=bucket_mb)
    assert check_flow(pf.flow, pred_mb,
                      program=f"spmd_hier_b{bucket_mb}") == []
    # the per-bucket gather-backs stay marker-exempt (the bucketed scopes
    # keep the hier_dp_ag prefix)
    assert all("hier_dp_ag" not in p for p in pf.reshard_problems)


def test_hier_zero3_matches_single_device_where_flat_drifts(
        tmp_path, cpu_devices):
    """embed-ZeRO-3 + vtp2 + chunks=2 vs an UNSHARDED single-device run:
    BOTH paths now match it tightly. The hier lane path always did (its
    lane_batch pins the per-lane layout); the FLAT path's scanned
    microbatches used to arrive batch-sharded over only the inner dp
    axes — the reshape absorbed the outer dp axis into the chunk dim —
    and the partitioner's ZeRO-3 gradient program for that layout was
    numerically WRONG (the ROADMAP BUG: wte rows off at grad magnitude).
    ``make_spmd_train_step`` now pins the scanned stack to the plan's
    batch sharding, so the per-microbatch embed-grad reduce-scatter
    materializes in the correct layout: the bug is FIXED on the GSPMD
    path, not masked by comparing hier-to-flat."""
    from hetu_galvatron_tpu.models.builder import causal_lm_loss
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer as _mo
    from hetu_galvatron_tpu.runtime.trainer import make_train_step

    _, sp1, _, _, l1 = _steps(tmp_path, cpu_devices, True, dp_type="zero3",
                              chunks=2)
    _, sp0, _, _, l0 = _steps(tmp_path, cpu_devices, False, dp_type="zero3",
                              chunks=2)
    # single-device reference with the same optimizer + chunking
    tx = _mo(TRAIN)
    params, _ = init_causal_lm(jax.random.key(0), CFG)
    loss_fn = lambda p, b: causal_lm_loss(p, b, CFG,
                                          compute_dtype=jnp.float32)
    step = jax.jit(make_train_step(loss_fn, tx, chunks=2))
    so = tx.init(params)
    data = np.random.RandomState(0).randint(0, 128, (8, CFG.seq_length + 1))
    b = jax.tree.map(jnp.asarray, make_batch(data))
    ref = []
    for _ in range(3):
        params, so, m = step(params, so, b)
        ref.append(float(m["loss"]))
    np.testing.assert_allclose(ref, l1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref, l0, rtol=1e-5, atol=1e-5)
    # the flat path's PARAMS (wte included) match the reference too —
    # the strong form of "bug fixed": ~40% of wte rows used to deviate at
    # GRAD magnitude (~6e-2); the tolerance here is 3 orders below that,
    # absorbing only the 3-step adam amplification of f32 reassociation
    for (pa, a), (_, r) in zip(
            jax.tree_util.tree_leaves_with_path(jax.device_get(sp0)),
            jax.tree_util.tree_leaves_with_path(params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=2e-4, atol=1e-4,
            err_msg=jax.tree_util.keystr(pa))


def test_hier_zero_steady_state_recompiles(tmp_path, cpu_devices):
    step, sp, so, b, _ = _steps(tmp_path, cpu_devices, True)
    n0 = step._cache_size()
    assert n0 == 1
    for _ in range(2):
        sp, so, _ = step(sp, so, b)
    assert step._cache_size() == n0


def test_hier_census_counts_and_bytes_exact(tmp_path, cpu_devices):
    """The traced hierarchical step contains EXACTLY the collectives the
    plan arithmetic promises — one reduce-scatter, one cross-slice
    all-reduce, one all-gather — and moves exactly the predicted padded
    payload megabytes (zero tolerance, the sharding-flow contract)."""
    from hetu_galvatron_tpu.analysis.census import (
        census_spmd_step,
        check_census,
    )
    from hetu_galvatron_tpu.analysis.sharding_flow import (
        check_flow,
        flow_spmd_step,
    )
    from hetu_galvatron_tpu.observability.telemetry import (
        plan_collective_bytes,
        plan_collective_counts,
    )

    a = CoreArgs(model=CFG.model_dump(), train=TRAIN.model_dump())
    a.parallel.config_mode = "json"
    a.parallel.galvatron_config_path = _searched_plan_json(tmp_path)
    hpc = get_hybrid_parallel_config(a, 8)
    mesh = build_mesh(8, 1, devices=cpu_devices[:8], dcn_slices=2)

    census = census_spmd_step(CFG, hpc, TRAIN, mesh, tp_overlap=False,
                              hier_dp=True, dcn_slices=2)
    pred_counts = plan_collective_counts(hpc, CFG, tp_overlap=False,
                                         hier_dp=True)
    assert pred_counts == {"reduce_scatter": 1, "all_reduce": 1,
                           "all_gather": 1}
    assert check_census(census, pred_counts, program="spmd_hier") == []

    pf = flow_spmd_step(CFG, hpc, TRAIN, mesh, tp_overlap=False,
                        hier_dp=True, dcn_slices=2, gather_mb=1e-6)
    pred_mb = plan_collective_bytes(hpc, CFG, tp_overlap=False,
                                    hier_dp=True, hier_cross=2)
    assert check_flow(pf.flow, pred_mb, program="spmd_hier") == []
    # the deliberate hier gather-back is marker-exempt from the reshard
    # lint even at a microscopic threshold
    assert all("hier_dp_ag" not in p for p in pf.reshard_problems)
    assert not any("all-gathers" in p and "materialized" in p
                   for p in pf.reshard_problems), pf.reshard_problems


def _pp2_plan(dp=2, tp=2, gbsz=8, chunks=4):
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        HybridParallelConfig,
    )
    from hetu_galvatron_tpu.utils.strategy import DPType

    layers = [LayerStrategy(pp_deg=2, tp_size=tp, dp_size=dp)
              for _ in range(CFG.num_hidden_layers)]
    return HybridParallelConfig(
        layers=layers, vocab=EmbeddingLMHeadStrategy(vtp=tp), pp_deg=2,
        pp_division=[1, 1], chunks=chunks, global_bsz=gbsz,
        pipeline_type="pipedream_flush", default_dp_type=DPType.DDP,
        world_size=8)


def _engine_steps(cpu_devices, engine_cls, hier_dp, *, n=3, dcn=4):
    hpc = _pp2_plan()
    eng = engine_cls(CFG, hpc, TRAIN, devices=cpu_devices[:8],
                     compute_dtype=jnp.float32, dcn_slices=dcn,
                     hier_dp=hier_dp,
                     **({"donate": False} if "Compiled" in engine_cls.__name__
                        else {}))
    params, axes = init_causal_lm(jax.random.key(0), CFG)
    sp = eng.split_params(params, axes)
    so = eng.init_opt(sp, axes)
    data = np.random.RandomState(0).randint(0, 128, (8, CFG.seq_length + 1))
    b = make_batch(data)
    losses = []
    for _ in range(n):
        sp, so, m = eng.train_step(sp, so, b)
        losses.append(float(m["loss"]))
    return eng, sp, losses


def test_hier_compiled_engine_parity(cpu_devices):
    """Compiled 1F1B: hier vs flat 3-step trajectories + merged params
    within reassociation tolerance, exactly one compile."""
    from hetu_galvatron_tpu.runtime.compiled_pipeline import (
        CompiledPipelineEngine,
    )

    e0, sp0, l0 = _engine_steps(cpu_devices, CompiledPipelineEngine, False)
    e1, sp1, l1 = _engine_steps(cpu_devices, CompiledPipelineEngine, True)
    assert e1.compile_count() == 1
    np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-5)
    m0, m1 = e0.merge_params(sp0), e1.merge_params(sp1)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(m0),
            jax.tree_util.tree_leaves_with_path(m1)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(pa))


def test_hier_host_engine_parity(cpu_devices):
    """Host 1F1B: hier vs flat 3-step trajectories + merged params."""
    from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine

    e0, sp0, l0 = _engine_steps(cpu_devices, PipelineEngine, False)
    e1, sp1, l1 = _engine_steps(cpu_devices, PipelineEngine, True)
    np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-5)
    m0, m1 = e0.merge_params(sp0), e1.merge_params(sp1)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(m0),
            jax.tree_util.tree_leaves_with_path(m1)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(pa))


def test_hier_compiled_census_counts_and_bytes(cpu_devices):
    """The compiled hier step contains 2T marked stage rotations plus
    exactly the three hier collectives, bytes exact."""
    from hetu_galvatron_tpu.analysis.census import census_jaxpr, check_census
    from hetu_galvatron_tpu.analysis.sharding_flow import (
        check_flow,
        flow_jaxpr,
    )
    from hetu_galvatron_tpu.observability.telemetry import (
        MB,
        plan_collective_bytes,
        plan_collective_counts,
    )
    from hetu_galvatron_tpu.runtime.compiled_pipeline import (
        CompiledPipelineEngine,
    )

    hpc = _pp2_plan()
    eng = CompiledPipelineEngine(CFG, hpc, TRAIN, devices=cpu_devices[:8],
                                 compute_dtype=jnp.float32, dcn_slices=4,
                                 hier_dp=True, donate=False)
    params, axes = init_causal_lm(jax.random.key(0), CFG)
    sp = eng.split_params(params, axes)
    so = eng.init_opt(sp, axes)
    data = np.random.RandomState(0).randint(0, 128, (8, CFG.seq_length + 1))
    jaxpr = eng.step_jaxpr(sp, so, make_batch(data))
    census = census_jaxpr(jaxpr)
    pred = plan_collective_counts(hpc, CFG, tp_overlap=False, hier_dp=True)
    assert check_census(census, pred, program="compiled_hier") == []

    shapes = [tuple(l.shape) for l in jax.tree_util.tree_leaves(sp)]
    local, padded = eng._hier.payload_elems(shapes)
    intra = eng._hier.intra
    pred_mb = plan_collective_bytes(hpc, CFG, tp_overlap=False)
    pred_mb["reduce_scatter"] = padded * 4 / MB
    pred_mb["all_reduce"] = padded // intra * 4 / MB
    pred_mb["all_gather"] = padded // intra * 4 / MB
    assert check_flow(flow_jaxpr(jaxpr), pred_mb,
                      program="compiled_hier") == []


def test_train_dist_cli_hier_dp(tmp_path, cpu_devices, capfd, caplog):
    """Launcher wiring end to end: parallel.hier_dp trains with the
    hierarchical path (the slice x host split logged), and an ineligible
    config logs the shared fallback reason and keeps training flat."""
    from hetu_galvatron_tpu.cli.train_dist import train
    from hetu_galvatron_tpu.core.arguments import args_from_cli

    base = [
        "model.hidden_size=64", "model.num_hidden_layers=2",
        "model.num_attention_heads=4", "model.vocab_size=128",
        "model.seq_length=16", "model.max_position_embeddings=64",
        "model.hidden_act=swiglu", "model.normalization=rmsnorm",
        "model.position_embedding_type=rope",
        "model.tie_word_embeddings=false", "model.add_bias_linear=false",
        "model.make_vocab_size_divisible_by=1",
        "model.ffn_hidden_size=128", "model.use_flash_attn=false",
        "parallel.global_tp_deg=2", "parallel.global_train_batch_size=8",
        "parallel.num_devices=8", "parallel.dcn_slices=2",
        "parallel.hier_dp=true", "train.train_iters=2",
    ]
    import logging

    with caplog.at_level(logging.INFO):
        out = train(args_from_cli(base, mode="train_dist"))
    assert len(out["losses"]) == 2 and all(np.isfinite(out["losses"]))
    cap = capfd.readouterr()
    logged = cap.out + cap.err + caplog.text
    assert "hierarchical gradient reduction on" in logged
    assert "2 slice x 2 host" in logged
    caplog.clear()

    # ineligible: tp_overlap rings cannot nest under the lane vmap —
    # the launcher logs the shared reason and falls back to flat
    with caplog.at_level(logging.INFO):
        out = train(args_from_cli(base + ["tp_overlap.enable=true"],
                                  mode="train_dist"))
    assert len(out["losses"]) == 2 and all(np.isfinite(out["losses"]))
    cap = capfd.readouterr()
    logged = cap.out + cap.err + caplog.text
    assert "falling back to the flat GSPMD gradient all-reduce" in logged
    assert "cannot nest" in logged
    caplog.clear()

    # bucketed: hier_bucket_mb pipelines the schedule — logged, trains
    with caplog.at_level(logging.INFO):
        out = train(args_from_cli(base + ["parallel.hier_bucket_mb=0.05"],
                                  mode="train_dist"))
    assert len(out["losses"]) == 2 and all(np.isfinite(out["losses"]))
    cap = capfd.readouterr()
    logged = cap.out + cap.err + caplog.text
    assert "0.05 MB buckets, pipelined" in logged


def test_train_dist_cli_hier_dp_cp_plan_no_fallback(tmp_path, cpu_devices,
                                                    capfd, caplog):
    """The cp-bearing sdp plan takes the hierarchical path end to end
    through the launcher: NO flat-fallback line, the slice x host split
    logged, finite losses (acceptance: cp plans stop paying flat
    per-microbatch all-reduces)."""
    import logging

    from hetu_galvatron_tpu.cli.train_dist import train
    from hetu_galvatron_tpu.core.arguments import args_from_cli

    base = [
        "model.hidden_size=64", "model.num_hidden_layers=2",
        "model.num_attention_heads=4", "model.vocab_size=128",
        "model.seq_length=16", "model.max_position_embeddings=64",
        "model.hidden_act=swiglu", "model.normalization=rmsnorm",
        "model.position_embedding_type=rope",
        "model.tie_word_embeddings=false", "model.add_bias_linear=false",
        "model.make_vocab_size_divisible_by=1",
        "model.ffn_hidden_size=128", "model.use_flash_attn=false",
        "parallel.global_cp_deg=2", "parallel.global_train_batch_size=8",
        "parallel.num_devices=8", "parallel.dcn_slices=2",
        "parallel.hier_dp=true", "train.train_iters=2",
    ]
    with caplog.at_level(logging.INFO):
        out = train(args_from_cli(base, mode="train_dist"))
    assert len(out["losses"]) == 2 and all(np.isfinite(out["losses"]))
    cap = capfd.readouterr()
    logged = cap.out + cap.err + caplog.text
    assert "hierarchical gradient reduction on" in logged
    assert "falling back to the flat GSPMD gradient" not in logged


def test_hier_ineligible_plans_raise_with_reason(tmp_path, cpu_devices):
    """tp_overlap rings cannot nest under the lane vmap; dropout diverges."""
    a = CoreArgs(model=CFG.model_dump(), train=TRAIN.model_dump())
    a.parallel.config_mode = "json"
    a.parallel.galvatron_config_path = _searched_plan_json(tmp_path)
    hpc = get_hybrid_parallel_config(a, 8)
    mesh = build_mesh(8, 1, devices=cpu_devices[:8])
    tx = make_optimizer(TRAIN)
    params, axes = init_causal_lm(jax.random.key(0), CFG)
    with pytest.raises(ValueError, match="cannot nest"):
        make_spmd_train_step(CFG, hpc, mesh, axes, tx, params,
                             compute_dtype=jnp.float32, hier_dp=True,
                             tp_overlap=True)
    drop = CFG.model_copy(update={"hidden_dropout": 0.1})
    with pytest.raises(ValueError, match="dropout"):
        make_spmd_train_step(drop, hpc, mesh, axes, tx, params,
                             compute_dtype=jnp.float32, hier_dp=True)
