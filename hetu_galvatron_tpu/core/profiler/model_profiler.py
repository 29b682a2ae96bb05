"""Model profiler: per-layer time/memory isolation via difference-of-runs.

Capability parity with the reference model profiler
(core/profiler/model_profiler.py:15-1034): sweep (layernum_min, layernum_max)
x batch sizes x sequence lengths x tp degrees x checkpoint, take differences
between the max- and min-layer runs to isolate ONE decoder layer's
time/memory, attribute the residual to the embedding/LM-head ("other"), and
write ``computation_profiling_*.json`` / ``memory_profiling_*.json`` in the
exact schema the search engine parses (profiles.py).

TPU-native: the reference launches a torchrun subprocess per grid point
(model_profiler.py:231-343); here each point is an in-process jit of the real
model — timing from executed steps, memory from XLA's own compiled
``memory_analysis`` (per-device under GSPMD partitioning), so the sweep also
runs on the virtual CPU mesh in CI.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs
from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
    compiled_memory_mb,
)
from hetu_galvatron_tpu.core.search_engine.profiles import write_json
from hetu_galvatron_tpu.models.builder import (
    forward_causal_lm,
    init_causal_lm,
    param_count,
)

MB = 1024 * 1024


def _param_size_mb(params: Dict[str, Any]) -> float:
    return param_count(params) * 4 / MB  # fp32 master weights


class ModelProfiler:
    def __init__(self, args: CoreArgs, devices: Optional[Sequence] = None):
        self.args = args
        self.devices = list(devices if devices is not None else jax.devices())
        self.prof = args.model_profiler
        from hetu_galvatron_tpu.analysis.eligibility import mixed_stack_reason

        reason = mixed_stack_reason(
            args.model, "the model profiler (it times stacks of differing "
            "depth and prices one block by their difference)",
            feed_forward_may_differ=True)
        if reason is not None:
            raise NotImplementedError(reason)

    def _cfg(self, layernum: int, seq: int) -> ModelArgs:
        return self.args.model.model_copy(update={
            "num_hidden_layers": layernum,
            "seq_length": seq,
            "max_position_embeddings": max(
                seq, self.args.model.max_position_embeddings),
        })

    # -- computation --------------------------------------------------------

    def _forward_ms(self, cfg: ModelArgs, bsz: int,
                    warmup: int = 2, iters: Optional[int] = None) -> float:
        if iters is None:  # more reps on hardware: amortized-loop timing
            iters = 20 if self.devices[0].platform == "tpu" else 5
        params, _ = init_causal_lm(jax.random.key(0), cfg)
        tokens = jnp.zeros((bsz, cfg.seq_length), jnp.int32)
        if cfg.model_type == "t5":
            from hetu_galvatron_tpu.models.encdec import forward_encdec

            half = max(cfg.seq_length // 2, 1)
            enc = jnp.zeros((bsz, half), jnp.int32)
            dec = jnp.zeros((bsz, cfg.seq_length - half), jnp.int32)
            fwd = jax.jit(lambda p, t: forward_encdec(
                p, enc, dec, cfg, compute_dtype=jnp.bfloat16))
        else:
            fwd = jax.jit(lambda p, t: forward_causal_lm(
                p, t, cfg, compute_dtype=jnp.bfloat16))
        # Queue all iters back-to-back and divide: the device serializes
        # them, so total/iters is the per-step time with dispatch overhead
        # amortized instead of sampled.
        for _ in range(warmup):
            out = fwd(params, tokens)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fwd(params, tokens)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) * 1000.0 / iters

    def profile_computation(self) -> Dict[str, float]:
        """Per-layer + "other" forward ms per (bsz, seq) grid point
        (reference _launch_computation_profiling + process_profiled_data:
        per-layer = (run[max] - run[min]) / (max - min), residual = other)."""
        p = self.prof
        if p.profile_mode == "batch":
            bszs = list(range(p.profile_min_batch_size,
                              p.profile_max_batch_size + 1,
                              p.profile_batch_size_step))
            seqs = [p.profile_seq_length_list[0]]
        elif p.profile_mode == "sequence":
            bszs = [1]
            seqs = list(range(p.profile_min_seq_length,
                              p.profile_max_seq_length + 1,
                              p.profile_seq_length_step))
        else:
            bszs = [p.profile_batch_size]
            seqs = list(p.profile_seq_length_list)

        out: Dict[str, float] = {}
        n_min, n_max = p.layernum_min, p.layernum_max
        for seq in seqs:
            for bsz in bszs:
                t_min = self._forward_ms(self._cfg(n_min, seq), bsz)
                t_max = self._forward_ms(self._cfg(n_max, seq), bsz)
                per_layer = max((t_max - t_min) / (n_max - n_min), 0.0)
                other = max(t_min - n_min * per_layer, 0.0)
                out[f"layertype_0_bsz{bsz}_seq{seq}"] = per_layer
                out[f"layertype_other_bsz{bsz}_seq{seq}"] = other
        return out

    # -- memory -------------------------------------------------------------

    def _step_memory_mb(self, cfg: ModelArgs, bsz: int, tp: int,
                        checkpoint: bool) -> Dict[str, float]:
        """Compile a full train step under a tp x dp sharding and read XLA's
        per-device memory accounting."""
        from hetu_galvatron_tpu.parallel.spmd import make_spmd_train_step
        from hetu_galvatron_tpu.runtime.hybrid_config import (
            get_hybrid_parallel_config,
        )
        from hetu_galvatron_tpu.runtime.mesh import build_mesh
        from hetu_galvatron_tpu.runtime.optimizer import make_optimizer

        world = tp  # one tp group; dp handled analytically by the cost model
        devices = self.devices[:world]
        if len(devices) < world:
            raise ValueError(f"need {world} devices for tp={tp}")
        args = self.args.model_copy(deep=True)
        args.model = cfg
        args.parallel.global_tp_deg = tp
        args.parallel.pp_deg = 1
        args.parallel.global_checkpoint = int(checkpoint)
        args.parallel.global_train_batch_size = bsz
        hpc = get_hybrid_parallel_config(args, world)
        mesh = build_mesh(world, 1, devices=devices)
        params, axes = init_causal_lm(jax.random.key(0), cfg)
        tx = make_optimizer(self.args.train)
        # (the tables say what a layer's checkpoint bit costs: every block
        # whose bit is set recomputes, whatever the device has left)
        step, pspecs, _, batch_shd = make_spmd_train_step(
            cfg, hpc, mesh, axes, tx, params, donate=False,
            keep_blocks=False)
        tokens = jax.ShapeDtypeStruct((bsz, cfg.seq_length), jnp.int32)
        batch = {"tokens": tokens, "labels": tokens}
        if cfg.model_type == "t5":
            half = max(cfg.seq_length // 2, 1)
            batch = {
                "enc_tokens": jax.ShapeDtypeStruct((bsz, half), jnp.int32),
                "tokens": jax.ShapeDtypeStruct(
                    (bsz, cfg.seq_length - half), jnp.int32),
                "labels": jax.ShapeDtypeStruct(
                    (bsz, cfg.seq_length - half), jnp.int32),
            }
        pshape = jax.eval_shape(lambda: params)
        oshape = jax.eval_shape(tx.init, params)
        compiled = step.lower(pshape, oshape, batch).compile()
        return compiled_memory_mb(compiled)

    def profile_memory(self) -> Dict[str, Any]:
        """memory_profiling_*.json in search-engine schema: per-layer
        parameter_size + tp_activation_per_bsz_dict (per tp degree +
        checkpoint), and the pp-off/first/last "other" tables."""
        p = self.prof
        seq = p.profile_seq_length_list[0]
        bsz = p.profile_batch_size
        n_min, n_max = p.layernum_min, p.layernum_max
        sp_suffix = "_sp"  # GSPMD sequence sharding is always on with tp

        cfg_min, cfg_max = self._cfg(n_min, seq), self._cfg(n_max, seq)
        params_min, _ = init_causal_lm(jax.random.key(0), cfg_min)
        params_max, _ = init_causal_lm(jax.random.key(0), cfg_max)
        layer_param_mb = (_param_size_mb(params_max) -
                         _param_size_mb(params_min)) / (n_max - n_min)
        other_param_mb = _param_size_mb(params_min) - n_min * layer_param_mb

        tp_degs = []
        tp = 1
        while tp <= min(p.max_tp_deg, len(self.devices)):
            tp_degs.append(tp)
            tp *= 2

        act_per_bsz: Dict[Any, float] = {}
        other_act: Dict[Any, float] = {}
        for tp in tp_degs:
            m_min = self._step_memory_mb(cfg_min, bsz, tp, False)
            m_max = self._step_memory_mb(cfg_max, bsz, tp, False)
            per_layer = max(
                (m_max["temps"] - m_min["temps"]) / (n_max - n_min), 0.0)
            act_per_bsz[tp] = per_layer / bsz
            other_act[tp] = max(
                (m_min["temps"] - n_min * per_layer), 0.0) / bsz
        m_ck = self._step_memory_mb(cfg_max, bsz, 1, True)
        m_ck_min = self._step_memory_mb(cfg_min, bsz, 1, True)
        act_per_bsz["checkpoint"] = max(
            (m_ck["temps"] - m_ck_min["temps"]) / (n_max - n_min), 0.0) / bsz

        # other model states: embed/head params x4 (params+grads+adam) per tp
        other_states = {tp: 4 * other_param_mb / tp for tp in tp_degs}
        half = {tp: v / 2 for tp, v in other_states.items()}
        out = {
            f"layertype_0{sp_suffix}": {
                str(seq): {
                    "parameter_size": layer_param_mb,
                    "tp_activation_per_bsz_dict": act_per_bsz,
                }
            },
            f"other_memory_pp_off{sp_suffix}": {
                str(seq): {"model_states": other_states,
                           "activation": other_act}
            },
            f"other_memory_pp_on_first{sp_suffix}": {
                str(seq): {"model_states": half,
                           "activation": {k: v / 2
                                          for k, v in other_act.items()}}
            },
            f"other_memory_pp_on_last{sp_suffix}": {
                str(seq): {"model_states": half,
                           "activation": {k: v / 2
                                          for k, v in other_act.items()}}
            },
        }
        return out

    # -- entry --------------------------------------------------------------

    def run(self, output_dir: Optional[str] = None) -> Dict[str, str]:
        import os

        p = self.prof
        out_dir = output_dir or p.output_dir
        name = self.args.model.model_name.replace("/", "_")
        precision = p.mixed_precision
        paths = {}
        if p.profile_type == "computation":
            path = os.path.join(
                out_dir, f"computation_profiling_{precision}_{name}_all.json")
            write_json(self.profile_computation(), path)
            paths["computation"] = path
        else:
            path = os.path.join(
                out_dir, f"memory_profiling_{precision}_{name}_all.json")
            write_json(self.profile_memory(), path)
            paths["memory"] = path
        return paths
