"""Overlapped-TP A/B microbench: decomposed ring collective matmuls
(ops/overlap.py, ``tp_overlap.enable``) vs the GSPMD auto-partitioned
collectives, on the SAME tp x dp plan.

Two legs per tp degree (tp2 x dp4 and tp4 x dp2 on the 8-device mesh),
INTERLEAVED per iteration so transient machine load hits both alike,
summarized by medians:

* ``overlap_vs_gspmd`` — overlap-step wall / gspmd-step wall: per-leg
  median ratios plus the headline median of the POOLED per-iteration
  ratios across all tp legs. On the virtual CPU mesh every "device"
  shares the host, so no real transfer/compute overlap exists and the
  ratio only bounds the decomposition's bookkeeping overhead (chunked
  matmuls + ppermutes vs one gathered matmul); the on-chip ratio (--tpu)
  is where the ring hops hide under the MXU and the ratio must drop below
  1. The companion cost-model term (cost_model/cost.py tp_overlap
  discount) prices that hardware effect for the search.
* ``overlap_recompiles`` — jit-cache growth of the overlap step across the
  timed steady state, which must be 0 (the ring path must not retrace).
* ``--schedule-impl compiled`` (round 12) — the same rings-vs-GSPMD A/B
  measured INSIDE the compiled single-program 1F1B engine on pp2 x tp x dp
  plans (tp2 x dp2 and tp4 x dp1): the de-vmapped stage axis lets the ring
  kernels run as stage-stacked shard_maps in the fused program, and this
  leg prices exactly that composition. Default ``--schedule-impl spmd`` is
  the original pp=1 GSPMD-step A/B.

Prints one JSON line. Run (virtual CPU mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/tp_overlap_bench.py [--schedule-impl compiled]
On real chips: add ``--tpu``.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

_FLAG = "--xla_force_host_platform_device_count=8"
# The CPU pin must only fire on DIRECT invocation: importers (the tests)
# set their own platform env, and a leg that wants the real chip would
# otherwise be silently forced onto 8 virtual CPU devices by this
# module-level guard (its argv never carries --tpu)
if __name__ == "__main__" and "--tpu" not in sys.argv:
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        # APPEND to any pre-set flags: setdefault would silently leave one
        # virtual device while the bench builds an 8-device plan
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " " + _FLAG).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"


def _build_step(args, devices, tp_overlap):
    import jax
    import jax.numpy as jnp

    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.parallel.spmd import (
        make_spmd_train_step,
        shard_params,
    )
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.runtime.mesh import build_mesh
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer

    hpc = get_hybrid_parallel_config(args, 8)
    mesh = build_mesh(8, 1, devices=devices)
    tx = make_optimizer(args.train)
    params, axes = init_causal_lm(jax.random.key(0), args.model)
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        args.model, hpc, mesh, axes, tx, params, compute_dtype=jnp.bfloat16,
        donate=False, tp_overlap=tp_overlap)
    sp = shard_params(params, pspecs, mesh)
    so = jax.jit(tx.init, out_shardings=jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))(sp)
    return step, sp, so, batch_shd


def _build_compiled_step(args, devices, tp_overlap):
    """One CompiledPipelineEngine train-step closure for the compiled-mode
    A/B: the rings (or GSPMD collectives) run INSIDE the fused 1F1B
    program. Returns (step, recompile_probe) where step(batch) runs one
    full optimizer step."""
    import jax
    import jax.numpy as jnp

    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.runtime.compiled_pipeline import (
        CompiledPipelineEngine,
    )
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    hpc = get_hybrid_parallel_config(args, 8)
    eng = CompiledPipelineEngine(args.model, hpc, args.train,
                                 devices=devices,
                                 compute_dtype=jnp.bfloat16,
                                 tp_overlap=tp_overlap)
    if tp_overlap and not eng.tp_overlap:
        raise RuntimeError(f"overlap ineligible: {eng.overlap_reason}")
    params, axes = init_causal_lm(jax.random.key(0), args.model)
    state = {"sp": eng.split_params(params, axes)}
    state["so"] = eng.init_opt(state["sp"], axes)

    def step(batch):
        state["sp"], state["so"], m = eng.train_step(
            state["sp"], state["so"], batch)
        return m

    return step, eng.compile_count


def run(iters: int = 12, on_tpu: bool = False, tps=(2, 4),
        hidden: int = 256, seq: int = 256,
        schedule_impl: str = "spmd") -> dict:
    import jax
    if not on_tpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from hetu_galvatron_tpu.core.args_schema import CoreArgs
    from hetu_galvatron_tpu.runtime.dataloader import make_batch

    compiled = schedule_impl == "compiled"
    devices = jax.devices()[:8] if on_tpu else jax.devices("cpu")[:8]
    if len(devices) < 8:
        return {"metric": "tp_overlap_ab", "skipped":
                f"need 8 devices for the tp x dp plans, have {len(devices)}"}

    legs = {}
    pooled_ratios = []
    total_recompiles = 0
    for tp in tps:
        # shapes big enough that the per-chunk matmuls amortize dispatch
        # (at toy widths the ring's extra op count dominates on CPU and the
        # ratio says nothing about the decomposition itself)
        parallel = {"global_tp_deg": tp, "global_train_batch_size": 8}
        if compiled:
            # the fused 1F1B program hosts the rings as stage-stacked
            # shard_maps: pp2 with the remaining degree as dp
            parallel.update(pp_deg=2, chunks=2,
                            pipeline_type="pipedream_flush")
        args = CoreArgs.model_validate({
            "model": {
                "hidden_size": hidden, "num_hidden_layers": 2,
                "num_attention_heads": max(hidden // 32, 1),
                "vocab_size": 128,
                "seq_length": seq, "max_position_embeddings": seq,
                "hidden_act": "swiglu", "normalization": "rmsnorm",
                "position_embedding_type": "rope",
                "tie_word_embeddings": False, "add_bias_linear": False,
                "make_vocab_size_divisible_by": 1,
                "ffn_hidden_size": 4 * hidden,
                "use_flash_attn": False,
            },
            "parallel": parallel,
        })
        data = np.random.RandomState(0).randint(
            0, args.model.padded_vocab_size, (8, seq + 1))
        if compiled:
            host_batch = make_batch(data)
            g_run, g_probe = _build_compiled_step(args, devices, False)
            o_run, o_probe = _build_compiled_step(args, devices, True)
            g_step = lambda: g_run(host_batch)
            o_step = lambda: o_run(host_batch)
        else:
            batch = jax.tree.map(jnp.asarray, make_batch(data))
            g_fn, g_sp, g_so, g_shd = _build_step(args, devices, False)
            o_fn, o_sp, o_so, o_shd = _build_step(args, devices, True)
            gb = jax.device_put(batch, g_shd)
            ob = jax.device_put(batch, o_shd)

            def g_step(_s=[g_sp, g_so]):
                _s[0], _s[1], m = g_fn(_s[0], _s[1], gb)
                return m

            def o_step(_s=[o_sp, o_so]):
                _s[0], _s[1], m = o_fn(_s[0], _s[1], ob)
                return m

            g_probe = g_fn._cache_size
            o_probe = o_fn._cache_size
        # compile + warm both legs outside the timed window
        for _ in range(2):
            gm = g_step()
            om = o_step()
        if abs(float(gm["loss"]) - float(om["loss"])) > 1e-2:
            raise AssertionError(
                f"overlap leg diverged from gspmd: {float(om['loss'])} vs "
                f"{float(gm['loss'])}")
        n_compiles = o_probe()

        g_times, o_times = [], []
        for _ in range(iters):
            t0 = time.perf_counter()
            gm = g_step()
            jax.block_until_ready(gm["loss"])
            g_times.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            om = o_step()
            jax.block_until_ready(om["loss"])
            o_times.append(time.perf_counter() - t0)
        g_ms = float(np.median(g_times)) * 1e3
        o_ms = float(np.median(o_times)) * 1e3
        recompiles = o_probe() - n_compiles
        total_recompiles += recompiles
        pooled_ratios += [o / g for o, g in zip(o_times, g_times)]
        legs[f"tp{tp}"] = {
            "gspmd_step_ms": round(g_ms, 2),
            "overlap_step_ms": round(o_ms, 2),
            "overlap_vs_gspmd": round(o_ms / max(g_ms, 1e-9), 3),
            "overlap_recompiles": int(recompiles),
        }

    return {
        "metric": "tp_overlap_ab",
        "platform": "tpu" if on_tpu else "cpu",
        "schedule_impl": schedule_impl,
        "iters": iters,
        "legs": legs,
        # headline: median of the POOLED per-iteration interleaved ratios
        # across all tp legs (each iteration's pair ran back-to-back, so
        # transient load cancels inside a ratio)
        "overlap_vs_gspmd": round(float(np.median(pooled_ratios)), 3),
        "overlap_recompiles": int(total_recompiles),
        "note": ("interleaved per-iteration medians. CPU mesh: no real "
                 "overlap exists (devices share the host), so the ratio "
                 "bounds the ring decomposition's bookkeeping overhead; "
                 "the on-chip ratio (--tpu) is where the ppermute hops "
                 "hide under the MXU."),
    }


if __name__ == "__main__":
    impl = "spmd"
    if "--schedule-impl" in sys.argv:
        impl = sys.argv[sys.argv.index("--schedule-impl") + 1]
    if impl not in ("spmd", "compiled"):
        sys.exit(f"unknown --schedule-impl {impl!r} (spmd | compiled)")
    print(json.dumps(run(on_tpu="--tpu" in sys.argv, schedule_impl=impl)))
