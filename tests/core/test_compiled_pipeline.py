"""Compiled (single-program) 1F1B schedule vs the host-sequenced engine.

The acceptance drill: on the virtual 8-device mesh, a pp2 x dp2 x tp2 plan
with gradient accumulation, global-norm clipping and tied embeddings must
produce the SAME loss trajectory and post-step params as the host engine
over >= 3 steps, compile exactly once for a fixed shape, and perform zero
host->device transfers in steady state apart from the microbatch feed."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs, TrainArgs
from hetu_galvatron_tpu.runtime.compiled_pipeline import CompiledPipelineEngine
from hetu_galvatron_tpu.runtime.dataloader import make_batch
from hetu_galvatron_tpu.runtime.hybrid_config import get_hybrid_parallel_config
from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine

pytestmark = [pytest.mark.pipeline, pytest.mark.parallel,
              pytest.mark.distributed]

# small enough that the fused-program compile fits the tier-1 budget
CFG = ModelArgs(
    hidden_size=32, num_hidden_layers=4, num_attention_heads=2,
    vocab_size=64, max_position_embeddings=32, seq_length=8,
    hidden_act="swiglu", normalization="rmsnorm",
    position_embedding_type="rope", tie_word_embeddings=True,
    add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, ffn_hidden_size=64)

TRAIN = TrainArgs(lr=1e-2, clip_grad=1.0, weight_decay=0.01,
                  lr_decay_style="constant", lr_warmup_iters=0)


def _hpc(cfg=CFG, train=TRAIN, **pkw):
    args = CoreArgs(model=cfg.model_dump(), train=train.model_dump())
    defaults = dict(pp_deg=2, chunks=4, pipeline_type="pipedream_flush",
                    global_train_batch_size=16, global_tp_deg=2)
    for k, v in {**defaults, **pkw}.items():
        setattr(args.parallel, k, v)
    return args, get_hybrid_parallel_config(args, 8)


def _engines(cpu_devices, cfg=CFG, **pkw):
    from hetu_galvatron_tpu.models.builder import init_causal_lm

    args, hpc = _hpc(cfg=cfg, **pkw)
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    host = PipelineEngine(cfg, hpc, args.train, devices=cpu_devices,
                          compute_dtype=jnp.float32)
    comp = CompiledPipelineEngine(cfg, hpc, args.train, devices=cpu_devices,
                                  compute_dtype=jnp.float32)
    return host, comp, params, axes, hpc


# to what the engines' Adam first moments (their gradients, before the
# division by sqrt(nu)) are held equal; the most they differ by is 8e-7
_MOMENT_ATOL = 1e-6


def _adam(opt_state):
    """The ScaleByAdamState (mu, nu) of an engine's optimizer state."""
    return opt_state.inner_states["adam"].inner_state[0]


def _batch(bsz=16, seed=0, cfg=CFG):
    data = np.random.RandomState(seed).randint(
        0, cfg.padded_vocab_size, (bsz, cfg.seq_length + 1))
    return make_batch(data)


def test_compiled_matches_host_engine_three_steps(cpu_devices):
    """The acceptance drill: pp2 x dp2 x tp2 with chunks=4 grad accum,
    clipping and TIED embeddings — identical trajectory and params."""
    host, comp, params, axes, hpc = _engines(cpu_devices)
    hsp = host.split_params(params, axes)
    hso = host.init_opt(hsp, axes)
    csp = comp.split_params(params, axes)
    cso = comp.init_opt(csp, axes)
    for step in range(3):
        batch = _batch(seed=step)
        hsp, hso, hm = host.train_step(hsp, hso, batch)
        csp, cso, cm = comp.train_step(csp, cso, batch)
        assert abs(float(cm["loss"]) - hm["loss"]) < 2e-5, step
        assert abs(float(cm["grad_norm"]) - hm["grad_norm"]) < 1e-4, step
    # post-step params are step-for-step equal (fp32 ulp tolerance only);
    # the compiled tree keeps ONE wte — merge_params drops the host's
    # transposed tied copy too, so the structures line up exactly.
    # Adam divides by sqrt(nu), so the one or two elements of a leaf whose
    # gradient is itself no larger than what the engines' gradients differ
    # by move by a visible share of lr on a rounding (layers[1].attn.wqkv
    # [6, 35]: sqrt(nu) 1.6e-7, the leaf's least, 140 times under its
    # median). The gradients are therefore held equal BEFORE the division,
    # as Adam's first moments, on every element; and the parameters' own
    # tolerance holds wherever sqrt(nu) stands above the moments' atol
    hp, cp = host.merge_params(hsp), comp.merge_params(csp)
    hmu = host.merge_params([_adam(s).mu for s in hso])
    hnu = host.merge_params([_adam(s).nu for s in hso])
    cmu = comp.merge_params(_adam(cso).mu)
    leaves = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b), (_, ma), (_, mb), (_, nu) in zip(
            leaves(hp), leaves(cp), leaves(hmu), leaves(cmu), leaves(hnu)):
        key = jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(ma), np.asarray(mb), rtol=5e-4, atol=_MOMENT_ATOL,
            err_msg=f"first moment {key}")
        live = np.sqrt(np.asarray(nu)) >= _MOMENT_ATOL
        assert live.mean() > 0.998, (key, int((~live).sum()))
        np.testing.assert_allclose(
            np.asarray(a)[live], np.asarray(b)[live], rtol=5e-4, atol=5e-5,
            err_msg=f"param {key}")
    # held-out eval under the same plan agrees too
    ev = _batch(seed=99)
    assert abs(comp.eval_step(csp, ev)["loss"]
               - host.eval_step(hsp, ev)["loss"]) < 2e-5


def test_compiled_recompile_pinning_and_steady_state_transfers(cpu_devices):
    """Exactly ONE compilation of the fused step across a multi-step run,
    and zero host->device transfers in the steady loop beyond the
    microbatch feed (pinned with jax.transfer_guard)."""
    _, comp, params, axes, hpc = _engines(cpu_devices)
    csp = comp.split_params(params, axes)
    cso = comp.init_opt(csp, axes)
    batch = _batch()
    csp, cso, _ = comp.train_step(csp, cso, batch)  # the one compile
    assert comp.compile_count() == 1
    dev_batch = comp.put_batch(batch, hpc.chunks)  # the microbatch feed
    for _ in range(3):
        with jax.transfer_guard("disallow"):
            csp, cso, m = comp.train_step(csp, cso, dev_batch)
    jax.block_until_ready(m["loss"])
    assert comp.compile_count() == 1, "steady state recompiled"
    # the per-tick host spans of the host engine collapse into one
    # pp/compiled_step span; the schedule shape is exported as a gauge
    from hetu_galvatron_tpu.observability.registry import get_registry

    gauge = get_registry().gauge("pp/bubble_frac")
    assert gauge.value == pytest.approx(comp.bubble_frac(hpc.chunks))


def test_compiled_untied_and_uniform_dp(cpu_devices):
    """Untied head + pure-dp stages (tp=1): the head grads live only on the
    last lane and the trajectory still matches the host engine."""
    cfg = CFG.model_copy(update={"tie_word_embeddings": False})
    host, comp, params, axes, _ = _engines(cpu_devices, cfg=cfg,
                                           global_tp_deg=1, chunks=2)
    hsp, hso = host.split_params(params, axes), None
    hso = host.init_opt(hsp, axes)
    csp = comp.split_params(params, axes)
    cso = comp.init_opt(csp, axes)
    batch = _batch(cfg=cfg)
    hsp, hso, hm = host.train_step(hsp, hso, batch)
    csp, cso, cm = comp.train_step(csp, cso, batch)
    assert abs(float(cm["loss"]) - hm["loss"]) < 2e-5
    hp, cp = host.merge_params(hsp), comp.merge_params(csp)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(hp),
                                 jax.tree_util.tree_leaves_with_path(cp)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"param {jax.tree_util.keystr(path)}")


def test_compiled_dropout_replays_host_masks(cpu_devices):
    """With dropout on, the compiled schedule derives the same
    per-(microbatch, stage) keys as the host engine and produces the
    bit-identical loss — under the PARTITIONABLE threefry rng. (Under the
    default non-partitionable rng, mask bits depend on how XLA shards the
    program, so the host's per-submesh programs and the fused full-mesh
    program draw different — equally valid — masks.)"""
    cfg = CFG.model_copy(update={"hidden_dropout": 0.1,
                                 "attention_dropout": 0.1})
    host, comp, params, axes, _ = _engines(cpu_devices, cfg=cfg, chunks=2)
    hsp = host.split_params(params, axes)
    hso = host.init_opt(hsp, axes)
    csp = comp.split_params(params, axes)
    cso = comp.init_opt(csp, axes)
    batch = dict(_batch(cfg=cfg))
    batch["dropout_rng"] = jax.random.key(7)
    old = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        _, _, hm = host.train_step(hsp, hso, batch)
        _, _, cm = comp.train_step(csp, cso, batch)
        assert abs(float(cm["loss"]) - hm["loss"]) < 1e-6
    finally:
        jax.config.update("jax_threefry_partitionable", old)
    # and a missing key is refused exactly like the host engine
    with pytest.raises(ValueError, match="dropout_rng"):
        comp.train_step(csp, cso, _batch(cfg=cfg))


def test_unsupported_plans_report_reasons():
    """The launcher's fallback gate: every shape the compiled path cannot
    express names its reason (the host engine remains the general path)."""
    _, hpc = _hpc()
    assert CompiledPipelineEngine.unsupported_reason(CFG, hpc) is None

    _, gpipe = _hpc(pipeline_type="gpipe")
    assert "1F1B" in CompiledPipelineEngine.unsupported_reason(CFG, gpipe)

    _, vpp = _hpc(virtual_pp_deg=2, chunks=4)
    assert "virtual" in CompiledPipelineEngine.unsupported_reason(CFG, vpp)

    cfg5 = CFG.model_copy(update={"num_hidden_layers": 5})
    _, uneven = _hpc(cfg=cfg5)
    assert "heterogeneous" in CompiledPipelineEngine.unsupported_reason(
        cfg5, uneven)

    moe = CFG.model_copy(update={"num_experts": 4, "moe_topk": 2})
    _, mhpc = _hpc(cfg=moe)
    assert "MoE" in CompiledPipelineEngine.unsupported_reason(moe, mhpc)

    # cp / zigzag-cp plans are EXPRESSIBLE since the stage axis was
    # de-vmapped (the ring kernel runs inside as a stage-stacked shard_map)
    _, cp = _hpc(global_cp_deg=2, global_tp_deg=1)
    assert CompiledPipelineEngine.unsupported_reason(CFG, cp) is None
    _, zz = _hpc(global_cp_deg=2, global_tp_deg=1, cp_zigzag=True)
    assert CompiledPipelineEngine.unsupported_reason(CFG, zz) is None

    class _Packed:
        reset_position_ids = True
        reset_attention_mask = False

    _, ok = _hpc()
    assert "packed" in CompiledPipelineEngine.unsupported_reason(
        CFG, ok, data=_Packed())

    # constructing an engine for an unsupported plan raises loudly
    with pytest.raises(ValueError, match="unsupported"):
        CompiledPipelineEngine(CFG, gpipe, TRAIN)


def test_bubble_frac_formula():
    _, hpc = _hpc()
    eng = CompiledPipelineEngine.__new__(CompiledPipelineEngine)
    eng.hpc = hpc
    eng.pp = 2
    # lockstep 1F1B: 2(pp-1) idle tick-slots over m + 2(pp-1) ticks
    assert eng.bubble_frac(4) == pytest.approx(2 / 6)
    assert eng.bubble_frac(1) == pytest.approx(2 / 3)
    eng.pp = 4
    assert eng.bubble_frac(8) == pytest.approx(6 / 14)


def test_pp_rotation_is_collective_permute(cpu_devices):
    """mesh.make_pp_rotation: a [pp, ...]-stacked array rotates one stage
    forward/backward (lax.ppermute over the pp axis), identity on the
    intra-stage axes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hetu_galvatron_tpu.runtime.mesh import (
        build_mesh,
        make_pp_rotation,
        stacked_spec,
    )

    mesh = build_mesh(8, 2, devices=cpu_devices)
    spec = stacked_spec(P(("d0",), ("d1",), None))
    x = np.arange(2 * 4 * 4 * 3, dtype=np.float32).reshape(2, 4, 4, 3)
    xd = jax.device_put(x, NamedSharding(mesh, spec))
    fwd = jax.jit(make_pp_rotation(mesh, spec, +1))
    bwd = jax.jit(make_pp_rotation(mesh, spec, -1))
    np.testing.assert_array_equal(np.asarray(fwd(xd)), np.roll(x, 1, axis=0))
    np.testing.assert_array_equal(np.asarray(bwd(xd)), np.roll(x, -1, axis=0))
    # a rotation really lowers to a collective-permute, not a reshard
    txt = fwd.lower(xd).compile().as_text()
    assert "collective-permute" in txt, "rotation did not lower to ppermute"


def _searched_pp2_tp2_dp2_plan(tmp_path):
    """A pp2 x tp2 x dp2 plan in the searched-config interchange format
    (what search_engine.save_results writes): the unified-engine drill runs
    the plan the SEARCH would hand the launcher, not a hand-built hpc."""
    import json

    from hetu_galvatron_tpu.utils.strategy import (
        EmbeddingLMHeadStrategy,
        LayerStrategy,
        strategy_list2config,
    )

    layers = [LayerStrategy(pp_deg=2, tp_size=2, dp_size=2)
              for _ in range(CFG.num_hidden_layers)]
    cfg = strategy_list2config(
        layers, global_bsz=16, chunks=4, pipeline_type="pipedream_flush",
        default_dp_type="ddp", vocab=EmbeddingLMHeadStrategy(vtp=2),
        pp_division=[2, 2])
    path = tmp_path / "galvatron_config_unified_drill.json"
    path.write_text(json.dumps(cfg))
    return str(path)


def test_compiled_kernels_acceptance_drill(tmp_path, cpu_devices):
    """ROUND-12 ACCEPTANCE: a searched tp2 x dp2 x pp2 plan with the
    overlapped-TP ring matmuls AND the Pallas flash kernel (interpret mode
    on the CPU mesh) runs through the COMPILED engine — no host fallback —
    with the bit-identical 3-step trajectory and final params as the host
    engine running the same kernels, exactly one compile, and zero
    steady-state recompiles. This is the composition the de-vmapped stage
    axis exists for: shard_map kernels inside the fused 1F1B program."""
    from hetu_galvatron_tpu.models.builder import init_causal_lm

    args = CoreArgs(model=CFG.model_dump(), train=TRAIN.model_dump())
    args.parallel.config_mode = "json"
    args.parallel.galvatron_config_path = _searched_pp2_tp2_dp2_plan(
        tmp_path)
    hpc = get_hybrid_parallel_config(args, 8)
    # the searched plan is expressible — no fallback reason
    assert CompiledPipelineEngine.unsupported_reason(CFG, hpc) is None
    kern = dict(tp_overlap=True, use_flash=True, flash_interpret=True)
    host = PipelineEngine(CFG, hpc, args.train, devices=cpu_devices,
                          compute_dtype=jnp.float32, **kern)
    comp = CompiledPipelineEngine(CFG, hpc, args.train, devices=cpu_devices,
                                  compute_dtype=jnp.float32, **kern)
    # the rings really are live inside the compiled program
    assert comp.tp_overlap and comp.overlap_reason is None
    assert comp._ops.matmuls and comp._ops.sdpa is not None
    params, axes = init_causal_lm(jax.random.key(0), CFG)
    hsp = host.split_params(params, axes)
    hso = host.init_opt(hsp, axes)
    csp = comp.split_params(params, axes)
    cso = comp.init_opt(csp, axes)
    for step in range(3):
        batch = _batch(seed=step)
        hsp, hso, hm = host.train_step(hsp, hso, batch)
        csp, cso, cm = comp.train_step(csp, cso, batch)
        assert abs(float(cm["loss"]) - hm["loss"]) < 2e-5, step
        assert abs(float(cm["grad_norm"]) - hm["grad_norm"]) < 1e-4, step
    hp, cp = host.merge_params(hsp), comp.merge_params(csp)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(hp),
                                 jax.tree_util.tree_leaves_with_path(cp)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"param {jax.tree_util.keystr(path)}")
    # one program, zero steady-state recompiles, no steady host transfers
    assert comp.compile_count() == 1
    dev_batch = comp.put_batch(_batch(seed=9), hpc.chunks)
    with jax.transfer_guard("disallow"):
        csp, cso, m = comp.train_step(csp, cso, dev_batch)
    jax.block_until_ready(m["loss"])
    assert comp.compile_count() == 1, "steady state recompiled"


def test_compiled_cp_plan_matches_host(cpu_devices):
    """cp plans no longer fall back: a cp2 x dp2 x pp2 plan runs the ring
    attention kernel INSIDE the fused program (stage-stacked shard_map)
    with host-engine parity. vocab_cp=2 rides along — the round-11 guard
    rejected `vocab.vcp > 1` too, and the replicated-across-pp vocab rows
    must keep their cp sharding parity now that the guard is gone."""
    host, comp, params, axes, _ = _engines(
        cpu_devices, global_cp_deg=2, global_tp_deg=1, chunks=2,
        global_train_batch_size=8, vocab_cp=2)
    hsp = host.split_params(params, axes)
    hso = host.init_opt(hsp, axes)
    csp = comp.split_params(params, axes)
    cso = comp.init_opt(csp, axes)
    batch = _batch(bsz=8)
    hsp, hso, hm = host.train_step(hsp, hso, batch)
    csp, cso, cm = comp.train_step(csp, cso, batch)
    assert abs(float(cm["loss"]) - hm["loss"]) < 2e-5
    hp, cp = host.merge_params(hsp), comp.merge_params(csp)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(hp),
                                 jax.tree_util.tree_leaves_with_path(cp)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"param {jax.tree_util.keystr(path)}")


def test_compiled_zigzag_cp_plan_matches_host(cpu_devices):
    """Zigzag-cp composes with the compiled schedule too (the balanced
    causal layout's entry/exit permutes run inside the program)."""
    host, comp, params, axes, _ = _engines(
        cpu_devices, global_cp_deg=2, global_tp_deg=1, chunks=2,
        global_train_batch_size=8, cp_zigzag=True)
    hsp = host.split_params(params, axes)
    hso = host.init_opt(hsp, axes)
    csp = comp.split_params(params, axes)
    cso = comp.init_opt(csp, axes)
    batch = _batch(bsz=8)
    hsp, hso, hm = host.train_step(hsp, hso, batch)
    csp, cso, cm = comp.train_step(csp, cso, batch)
    assert abs(float(cm["loss"]) - hm["loss"]) < 2e-5


def test_compiled_ramp_caches_one_program_per_chunk_count(cpu_devices):
    """A batch-size ramp varies num_microbatches at a fixed micro shape:
    one fused program per distinct count, each compiled once."""
    _, comp, params, axes, _ = _engines(cpu_devices, chunks=2,
                                        global_train_batch_size=8)
    csp = comp.split_params(params, axes)
    cso = comp.init_opt(csp, axes)
    b1 = _batch(bsz=8)
    csp, cso, _ = comp.train_step(csp, cso, b1, num_microbatches=2)
    csp, cso, _ = comp.train_step(csp, cso, _batch(bsz=4),
                                  num_microbatches=1)
    csp, cso, _ = comp.train_step(csp, cso, b1, num_microbatches=2)
    assert sorted(comp._step_jits) == [1, 2]
    assert comp.compile_count() == 2
