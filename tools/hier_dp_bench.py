"""Hierarchical dp gradient-reduction A/B: the explicit lane-accumulated
reduce-scatter/all-reduce/all-gather path (ops/hier_reduce.py,
``parallel.hier_dp``) vs the flat GSPMD dp all-reduce, on the SAME plans.

Two legs on the 8-device mesh (dp8 pure-dp and tp2 x dp4), chunks=8 so the
structural difference shows: the flat path's GSPMD all-reduce runs INSIDE
the microbatch scan (once per microbatch), while the hierarchical path
accumulates per-lane grads reduction-free and pays the three-collective
schedule ONCE per step. Iterations are INTERLEAVED so transient machine
load hits both alike, summarized by medians:

* ``hier_dp_vs_flat`` — hier-step wall / flat-step wall per leg, plus the
  headline median of the POOLED per-iteration ratios. On the virtual CPU
  mesh the links are all the same host memory, so the per-LEVEL win (the
  cross-slice hop carrying only the 1/intra shard over DCN) does not
  show — what the CPU ratio measures is the once-per-step vs
  once-per-microbatch schedule difference plus the lane-vmap overhead;
  the cost model's per-level curves price the topology effect for the
  search (cost_model.cost.hier_dp_reduce_ms).
* ``hier_dp_recompiles`` — jit-cache growth of the hier step across the
  timed steady state; must be 0 (the lane path must not retrace).
* ``hier_dp_bucketed_vs_mono`` — the BUCKETED software-pipelined
  schedule (``parallel.hier_bucket_mb``, ops/hier_reduce.py wavefront
  emission) vs the monolithic three-collective program, hier-vs-hier on
  the pure-dp plan. On the CPU mesh there is no DCN/ICI split to
  overlap, so the ratio mostly prices the bucketing overhead (slice /
  concat / extra collective dispatch) — the gate pins it at <= ~1.0 so
  the bucketed program never costs more than it hides; the overlap WIN
  itself needs a real multi-slice fleet.

Prints one JSON line. Run (virtual CPU mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/hier_dp_bench.py
On a real slice: add ``--tpu``.
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
if __name__ == "__main__" and "--tpu" not in sys.argv:
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " " + _FLAG).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"


def _bench_args(tp: int, hidden: int, seq: int, chunks: int,
                dcn_slices: int):
    """The one bench model/plan config (every leg measures the SAME
    model): tiny untied swiglu/rmsnorm/rope stack; the batch keeps
    B/chunks >= dp so every microbatch still splits into the dp lanes."""
    from hetu_galvatron_tpu.core.args_schema import CoreArgs

    return CoreArgs.model_validate({
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
        "parallel": {"global_tp_deg": tp,
                     "global_train_batch_size": 8 * chunks,
                     "chunks": chunks,
                     "dcn_slices": dcn_slices},
    })


def _build_step(args, devices, hier_dp, dcn_slices, hier_bucket_mb=0.0):
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
    mesh = build_mesh(8, 1, devices=devices, dcn_slices=dcn_slices)
    tx = make_optimizer(args.train)
    params, axes = init_causal_lm(jax.random.key(0), args.model)
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        args.model, hpc, mesh, axes, tx, params,
        compute_dtype=jnp.bfloat16, donate=False, hier_dp=hier_dp,
        dcn_slices=dcn_slices, hier_bucket_mb=hier_bucket_mb)
    sp = shard_params(params, pspecs, mesh)
    so = jax.jit(tx.init, out_shardings=jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))(sp)
    return step, sp, so, batch_shd


def run(iters: int = 8, on_tpu: bool = False,
        plans=((1, 8), (2, 4)), hidden: int = 320, seq: int = 128,
        chunks: int = 8, dcn_slices: int = 2,
        bucket_mb: float = 8.0) -> dict:
    import jax
    if not on_tpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from hetu_galvatron_tpu.runtime.dataloader import make_batch

    devices = jax.devices()[:8] if on_tpu else jax.devices("cpu")[:8]
    if len(devices) < 8:
        return {"metric": "hier_dp_ab", "skipped":
                f"need 8 devices for the dp plans, have {len(devices)}"}

    legs = {}
    pooled = []
    total_recompiles = 0
    for tp, dp in plans:
        args = _bench_args(tp, hidden, seq, chunks, dcn_slices)
        data = np.random.RandomState(0).randint(
            0, args.model.padded_vocab_size,
            (args.parallel.global_train_batch_size, seq + 1))
        batch = jax.tree.map(jnp.asarray, make_batch(data))
        f_fn, f_sp, f_so, f_shd = _build_step(args, devices, False,
                                              dcn_slices)
        h_fn, h_sp, h_so, h_shd = _build_step(args, devices, True,
                                              dcn_slices)
        fb = jax.device_put(batch, f_shd)
        hb = jax.device_put(batch, h_shd)

        def f_step(_s=[f_sp, f_so]):
            _s[0], _s[1], m = f_fn(_s[0], _s[1], fb)
            return m

        def h_step(_s=[h_sp, h_so]):
            _s[0], _s[1], m = h_fn(_s[0], _s[1], hb)
            return m

        for _ in range(2):
            fm = f_step()
            hm = h_step()
        if abs(float(fm["loss"]) - float(hm["loss"])) > 1e-2:
            raise AssertionError(
                f"hier leg diverged from flat: {float(hm['loss'])} vs "
                f"{float(fm['loss'])}")
        n_compiles = h_fn._cache_size()

        f_times, h_times = [], []
        for _ in range(iters):
            t0 = time.perf_counter()
            fm = f_step()
            jax.block_until_ready(fm["loss"])
            f_times.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            hm = h_step()
            jax.block_until_ready(hm["loss"])
            h_times.append(time.perf_counter() - t0)
        f_ms = float(np.median(f_times)) * 1e3
        h_ms = float(np.median(h_times)) * 1e3
        recompiles = h_fn._cache_size() - n_compiles
        total_recompiles += recompiles
        pooled += [h / f for h, f in zip(h_times, f_times)]
        legs[f"tp{tp}dp{dp}"] = {
            "flat_step_ms": round(f_ms, 2),
            "hier_step_ms": round(h_ms, 2),
            "hier_dp_vs_flat": round(h_ms / max(f_ms, 1e-9), 3),
            "hier_dp_recompiles": int(recompiles),
        }

    # bucketed-vs-monolithic leg (hier vs hier, pure-dp plan — the
    # largest payload): the monolithic step is REBUILT and re-timed here
    # on purpose — interleaving mono/bucketed iterations back to back is
    # what keeps the ratio fair under machine-load drift (reusing the
    # earlier hier leg's times would pair measurements minutes apart)
    tp, dp = plans[0]
    args = _bench_args(tp, hidden, seq, chunks, dcn_slices)
    data = np.random.RandomState(0).randint(
        0, args.model.padded_vocab_size,
        (args.parallel.global_train_batch_size, seq + 1))
    batch = jax.tree.map(jnp.asarray, make_batch(data))
    m_fn, m_sp, m_so, m_shd = _build_step(args, devices, True, dcn_slices)
    b_fn, b_sp, b_so, b_shd = _build_step(args, devices, True, dcn_slices,
                                          hier_bucket_mb=bucket_mb)
    mb_ = jax.device_put(batch, m_shd)
    bb_ = jax.device_put(batch, b_shd)

    def m_step(_s=[m_sp, m_so]):
        _s[0], _s[1], m = m_fn(_s[0], _s[1], mb_)
        return m

    def b_step(_s=[b_sp, b_so]):
        _s[0], _s[1], m = b_fn(_s[0], _s[1], bb_)
        return m

    for _ in range(2):
        mm = m_step()
        bm = b_step()
    if abs(float(mm["loss"]) - float(bm["loss"])) > 1e-2:
        raise AssertionError(
            f"bucketed hier diverged from monolithic: {float(bm['loss'])} "
            f"vs {float(mm['loss'])}")
    n_compiles = b_fn._cache_size()
    m_times, b_times = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        mm = m_step()
        jax.block_until_ready(mm["loss"])
        m_times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        bm = b_step()
        jax.block_until_ready(bm["loss"])
        b_times.append(time.perf_counter() - t0)
    # ratio of medians (not median of ratios): the reduce is a small
    # slice of the step, so per-iteration pairing mostly pairs noise
    bucketed_ratio = round(float(np.median(b_times))
                           / max(float(np.median(m_times)), 1e-9), 3)
    bucket_recompiles = int(b_fn._cache_size() - n_compiles)
    total_recompiles += bucket_recompiles

    return {
        "metric": "hier_dp_ab",
        "platform": "tpu" if on_tpu else "cpu",
        "iters": iters,
        "chunks": chunks,
        "dcn_slices": dcn_slices,
        "legs": legs,
        "hier_dp_vs_flat": round(float(np.median(pooled)), 3),
        "hier_dp_recompiles": int(total_recompiles),
        "hier_bucket_mb": bucket_mb,
        "bucketed": {
            "mono_step_ms": round(float(np.median(m_times)) * 1e3, 2),
            "bucketed_step_ms": round(float(np.median(b_times)) * 1e3, 2),
            "hier_dp_bucketed_vs_mono": bucketed_ratio,
            "bucket_recompiles": bucket_recompiles,
        },
        "hier_dp_bucketed_vs_mono": bucketed_ratio,
    }


if __name__ == "__main__":
    print(json.dumps(run(on_tpu="--tpu" in sys.argv)))
