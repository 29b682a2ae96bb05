"""Compiled pipeline schedule: the whole 1F1B step as ONE SPMD program.

The host engine (:mod:`runtime.pipeline`) sequences its schedule from the
host — one jitted call per (stage, microbatch) leg, ~375 us of dispatch each
(PERF.md round 5) and no *guaranteed* device overlap. This module is the
idiomatic XLA answer to VERDICT r4 weak #5: compile the ENTIRE 1F1B schedule
(warmup forwards, steady-state one-forward-one-backward, cooldown backwards,
gradient accumulation, tied-embedding grad exchange, global-norm clip and the
optimizer update) into a single GSPMD program over a full ``(pp, d0..dk)``
mesh, so XLA's latency-hiding scheduler overlaps the inter-stage transfers
with compute ("The Big Send-off", PAPERS.md).

Layout and mechanics:

* **One mesh, real pp axis** — ``build_mesh(world, pp)`` instead of the host
  engine's disjoint per-stage submeshes. Per-stage decoder weights are
  STACKED along a leading ``[pp, ...]`` axis sharded on the ``pp`` mesh axis
  (``mesh.stacked_spec``), so stage s's slice physically lives on mesh row s.
  The vocab layers (embed / prenorm / head) are replicated across ``pp``
  rows; replication + psum-through-autodiff is what fuses the tied-embedding
  grad exchange into the program (see below).
* **Lockstep tick scan** — a `lax.scan` over ``T = m + 2(pp-1)`` schedule
  ticks (m microbatches). At tick t, stage s runs the FORWARD of microbatch
  ``i = t - s`` (when ``0 <= i < m``) and the BACKWARD of microbatch
  ``j = t - 2(pp-1) + s``; both units execute as ONE stacked computation
  over the leading stage axis, which GSPMD partitions along ``pp`` — every
  mesh row computes only its own stage. Bubble ticks are masked by zeroing
  the backward cotangent seeds (zero cotangent in => exactly-zero grads out,
  by linearity of the vjp) and by `where`-gating the loss/grad accumulators.
* **De-vmapped stage axis (shard_map kernels inside)** — the per-stage
  layer computation is NOT a vmap over stage lanes (it was, through round
  11): stage-stacked weights enter ordinary traced einsums with an explicit
  leading ``p`` batch dim (``"pbsh,phf->pbsf"``), weight-free segments
  (norms, rope, residuals, the XLA attention core, per-lane dropout keys)
  ride plain `jax.vmap` over the lane axis, and the shard_map kernels —
  ``ops/overlap.py`` ring ag/rs matmuls (``tp_overlap=True``), the Pallas
  flash kernel, Ulysses a2a and cp/zigzag ring attention — are built with
  ``stage_axis="pp"``: ONE full-manual shard_map spanning the whole mesh
  whose specs carry the stage lane, exactly like the ``ppermute`` stage
  rotations always did. No nesting, no vmapped shard_map — the two flagship
  perf features (single-program 1F1B + overlapped/kernel collectives)
  compose in one donated jit. (Partial-auto shard_map — manual over ``pp``
  only — hard-crashes the XLA partitioner on this jax pin; the stacked
  full-manual form is the shape that works.)
* **collective-permute stage transfers** — activations rotate ``s -> s+1``
  and cotangents ``s -> s-1`` with `lax.ppermute` over the ``pp`` axis
  (``mesh.make_pp_rotation``), the compiled analogue of the reference's
  batched isend/irecv and of the host engine's `jax.device_put` hops.
* **1F1B memory bound** — the backward recomputes its stage forward from the
  stored stage INPUT (`jax.vjp`, per-stage remat — same policy as the host
  engine), so each stage keeps a circular buffer of ``2*pp - 1`` in-flight
  stage inputs: O(pp), independent of the microbatch count (GPipe would be
  O(m)). The depth-``2pp-1`` bound (vs the host schedule's ``pp``) is the
  price of the lockstep fwd+bwd tick; slot reuse is provably collision-free
  because a slot distance of a full buffer length can never separate two
  live microbatches of one stage.
* **Tied embeddings for free** — the last stage's logits use ``wte.T``
  directly (the table is replicated across ``pp``), so autodiff SUMS the
  embedding-lookup grad (stage 0's lane) and the head grad (last lane) into
  one ``wte`` cotangent — the host engine's explicit transpose-and-exchange
  becomes a psum the partitioner places.
* **Redundant vocab compute** — under the vmapped lockstep tick every mesh
  row also executes the (masked) head matmul in backward ticks; only the
  last row's result carries a non-zero cotangent. This trades ~one
  layer-equivalent of per-tick compute for a schedule with zero host
  dispatch; the embedding lookup itself is batched OUT of the vmap (its
  inputs are lane-invariant) and costs nothing extra.

Eligibility (everything else falls back to the host engine, which stays the
general path): causal-LM / bert families (no t5 pair carry), vpp=1, uniform
``pp_division`` and a uniform per-layer strategy (stacking needs one shard
layout), no MoE, no packed-document fields. Context parallelism (plain and
zigzag), Megatron-SP tp with the overlapped ring matmuls, Ulysses, and the
Pallas flash kernel all run INSIDE the program via the stage-stacked
shard_map wrappers.
"""

from __future__ import annotations

from dataclasses import replace
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from hetu_galvatron_tpu.core.args_schema import ModelArgs, TrainArgs
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.observability.registry import get_registry
from hetu_galvatron_tpu.observability.trace_analysis import (
    maybe_record_jit_cost,
)
from hetu_galvatron_tpu.observability.tracing import span
from hetu_galvatron_tpu.runtime.hybrid_config import HybridParallelConfig
from hetu_galvatron_tpu.runtime.mesh import (
    axes_size,
    build_mesh,
    flash_kernel_runs,
    lower_strategy,
    lower_vocab_strategy,
    make_pp_rotation,
    spec_tree,
    stacked_spec,
)
from hetu_galvatron_tpu.runtime.trainer import microbatch_weights

Params = Dict[str, Any]


def _stacked_decay_mask(params: Params) -> Params:
    """Weight-decay mask for the stacked layout: the plain rule is
    ``ndim >= 2`` (runtime/optimizer.py `_decay_mask`), but ``stages`` leaves
    carry a leading ``[pp]`` stage axis that must not promote a stacked bias
    into a decayed "matrix"."""
    return {
        k: jax.tree.map(
            lambda p, off=(1 if k == "stages" else 0): p.ndim - off >= 2, v)
        for k, v in params.items()
    }


def _compiled_optimizer(train: TrainArgs) -> optax.GradientTransformation:
    """Host-parity optimizer (pipeline._pipeline_optimizer: Adam + wd +
    schedule WITHOUT the global clip — the clip scale is applied explicitly
    so it is global across stages) with the stacking-aware decay mask."""
    from hetu_galvatron_tpu.runtime.optimizer import (
        make_lr_schedule,
        partition_expert_bias,
    )

    chain = [optax.scale_by_adam(b1=train.adam_beta1, b2=train.adam_beta2,
                                 eps=train.adam_eps)]
    if train.weight_decay:
        chain.append(optax.add_decayed_weights(train.weight_decay,
                                               mask=_stacked_decay_mask))
    chain.append(optax.scale_by_learning_rate(make_lr_schedule(train)))
    return partition_expert_bias(optax.chain(*chain))


class CompiledPipelineEngine:
    """Single-program 1F1B: same external contract as ``PipelineEngine``
    (split_params / init_opt / train_step / eval_step / merge_params), but
    params are one pp-stacked tree instead of a list of per-stage trees and
    the whole optimizer step is one donated jit call."""

    @staticmethod
    def unsupported_reason(cfg: ModelArgs, hpc: HybridParallelConfig,
                           data: Any = None) -> Optional[str]:
        """None when the compiled schedule can express this plan; otherwise
        a human-readable reason the launcher logs before falling back to the
        host engine. The predicate itself lives in
        ``analysis/eligibility.py`` — shared with the cost model's
        dispatch-waiver gate and the plan doctor, so the three can never
        drift. (cp / zigzag-cp plans are expressible since the stage axis
        was de-vmapped: the ring-attention kernel runs inside the program
        as a stage-stacked full-manual shard_map, like the overlapped-TP
        ring matmuls and the flash kernel.)"""
        from hetu_galvatron_tpu.analysis.eligibility import (
            compiled_unsupported_reason,
        )

        return compiled_unsupported_reason(cfg, hpc, data)

    def __init__(
        self,
        cfg: ModelArgs,
        hpc: HybridParallelConfig,
        train: TrainArgs,
        devices: Optional[List] = None,
        *,
        compute_dtype=jnp.bfloat16,
        dcn_slices: int = 1,
        donate: bool = True,
        tp_overlap: bool = False,
        use_flash: Optional[bool] = None,
        flash_interpret: bool = False,
    ):
        """``tp_overlap`` swaps the (uniform) layer's projection matmuls for
        the stage-stacked ring ag/rs kernels (ops/overlap.py) when the layer
        is eligible; ``self.overlap_reason`` carries the reason otherwise.
        ``use_flash`` mirrors the host engine's attention dispatch: None =
        the platform default (Pallas flash on TPU when cfg.use_flash_attn),
        an explicit bool forces it; ``flash_interpret`` runs the Pallas
        kernels in interpret mode (CPU parity drills)."""
        reason = self.unsupported_reason(cfg, hpc)
        if reason is not None:
            raise ValueError(f"compiled pipeline schedule unsupported: "
                             f"{reason}")
        self.cfg = cfg
        self.hpc = hpc
        self.train = train
        self.compute_dtype = compute_dtype
        self.donate = donate
        self.pp = hpc.pp_deg
        self.lps = hpc.pp_division[0]  # layers per stage (uniform)
        devices = list(devices if devices is not None else jax.devices())
        if len(devices) < hpc.world_size:
            raise ValueError(
                f"need {hpc.world_size} devices, have {len(devices)}")
        self.mesh = build_mesh(hpc.world_size, self.pp,
                               devices=devices[:hpc.world_size],
                               dcn_slices=dcn_slices)
        self.layer_sh = lower_strategy(hpc.layers[0], self.mesh)
        self.vocab_sh = lower_vocab_strategy(hpc.vocab, self.mesh,
                                             hpc.default_dp_type)
        self.tx = _compiled_optimizer(train)
        self._use_dropout = (cfg.hidden_dropout > 0.0
                             or cfg.attention_dropout > 0.0)
        self._use_flash = use_flash
        # what the (uniform) plan swaps in every decoder layer: the stage-
        # stacked attention core and, below, the overlapped projections
        self._ops = M.LayerOps(
            sdpa=self._build_attention_core(flash_interpret))
        # overlapped-TP ring matmuls inside the program (the same per-layer
        # eligibility the SPMD/host paths apply; the plan is uniform, so one
        # decision covers every decoder layer)
        self.tp_overlap = False
        self.overlap_reason: Optional[str] = None
        if tp_overlap:
            from hetu_galvatron_tpu.ops.overlap import (
                layer_overlap_reason,
                make_layer_matmuls,
            )

            tp_axes = self.layer_sh.weight_tp_axes
            reason = layer_overlap_reason(
                cfg, self.layer_sh, axes_size(self.mesh, tp_axes))
            if reason is None:
                self._ops = replace(self._ops, matmuls=make_layer_matmuls(
                    self.mesh, self.layer_sh.dp_axes, tp_axes,
                    stage_axis="pp"))
                self.tp_overlap = True
            else:
                self.overlap_reason = reason
        # jit caches keyed by microbatch count (a batch-size ramp compiles
        # one program per distinct count; a fixed plan compiles exactly once)
        self._step_jits: Dict[int, Any] = {}
        self._eval_jits: Dict[int, Any] = {}

    def _build_attention_core(self, flash_interpret: bool):
        """The stage-stacked attention core for the (uniform) layer
        strategy — mirrors ``parallel/spmd.attention_overrides``: cp layers
        get ring attention over their cp axes, Ulysses layers the
        head-scatter a2a sandwich, flash-eligible layers the Pallas kernel;
        None means the vmapped XLA core (GSPMD inserts the collectives).
        Every kernel is built with ``stage_axis='pp'`` so it runs on the
        ``[pp, ...]``-stacked activations as one full-manual shard_map."""
        sh = self.layer_sh
        cfg = self.cfg
        use_flash = self._use_flash
        if use_flash is None:
            use_flash = flash_kernel_runs(cfg.use_flash_attn,
                                          self.mesh.devices.flat)
        if sh.cp_axes:
            from hetu_galvatron_tpu.ops.ring_attention import make_ring_sdpa

            zig = bool(getattr(self.hpc, "cp_zigzag", False))
            return make_ring_sdpa(
                self.mesh, sh.cp_axes, dp_axes=sh.dp_axes,
                tp_axes=sh.tp_axes, use_flash=use_flash, zigzag=zig,
                data_zigzagged=zig, interpret=flash_interpret,
                stage_axis="pp")
        if sh.ulysses and sh.tp_axes:
            from hetu_galvatron_tpu.ops.ulysses import make_ulysses_sdpa

            local = None
            if use_flash:
                from hetu_galvatron_tpu.ops.pallas.flash_attention import (
                    flash_sdpa,
                )

                local = (partial(flash_sdpa, interpret=True)
                         if flash_interpret else flash_sdpa)
            return make_ulysses_sdpa(self.mesh, sh.tp_axes,
                                     dp_axes=sh.dp_axes, local_sdpa=local,
                                     stage_axis="pp")
        if use_flash:
            from hetu_galvatron_tpu.ops.pallas.flash_attention import (
                make_flash_sdpa,
            )

            return make_flash_sdpa(self.mesh, dp_axes=sh.dp_axes,
                                   tp_axes=sh.tp_axes,
                                   interpret=flash_interpret,
                                   stage_axis="pp")
        return None

    # ------------------------------------------------------------------
    # params / optimizer state (stacked layout)
    # ------------------------------------------------------------------

    def _slot_axes(self, axes: Params, j: int) -> Params:
        """Logical-axis tree for stage-layer slot j (identical across
        stages under the uniform-strategy gate)."""
        return axes["layers"][j]

    def stacked_param_specs(self, axes: Params, opt: bool = False) -> Params:
        """PartitionSpec tree mirroring the stacked params: ``stages`` slot
        leaves get P('pp', *layer_spec); vocab-row leaves (embed / prenorm /
        head) keep the vocab sharding and replicate across pp."""
        isP = lambda x: isinstance(x, P)
        out: Params = {"stages": tuple(
            jax.tree.map(stacked_spec,
                         spec_tree(self._slot_axes(axes, j), self.layer_sh,
                                   opt),
                         is_leaf=isP)
            for j in range(self.lps))}
        for k in ("embed", "prenorm", "head"):
            out[k] = spec_tree(axes[k], self.vocab_sh, opt)
        return out

    def _nshd(self, spec_tree_: Any) -> Any:
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            spec_tree_, is_leaf=lambda x: isinstance(x, P))

    def split_params(self, params: Params, axes: Params) -> Params:
        """Full (host/single-device) params tree -> the stacked layout:
        decoder layer ``s*lps + j`` becomes row s of ``stages[j]``; the
        vocab rows are placed replicated across pp. The tied head carries NO
        transposed copy — the program reads ``wte.T`` directly."""
        n = self.pp * self.lps
        if len(params["layers"]) != n:
            raise ValueError(f"params have {len(params['layers'])} layers, "
                             f"plan has {n}")
        stages = tuple(
            jax.tree.map(lambda *leaves: jnp.stack(leaves),
                         *[params["layers"][s * self.lps + j]
                           for s in range(self.pp)])
            for j in range(self.lps))
        sp: Params = {"stages": stages, "embed": params["embed"],
                      "prenorm": params["prenorm"], "head": params["head"]}
        # remember the embed's logical axes so the step program can state
        # the ZeRO-3 use-site gather explicitly (spmd
        # make_embed_use_constraint); without it the program is still
        # correct, just chattier to partition
        self._embed_axes = axes["embed"]
        specs = self.stacked_param_specs(axes)
        self._param_shardings = self._nshd(specs)
        # stage through a host copy: device_put of a fully-replicated leaf
        # can ALIAS the caller's buffer, and the donated step would then
        # delete the caller's params out from under it
        return jax.tree.map(
            lambda p, s: jax.device_put(np.asarray(p),
                                        NamedSharding(self.mesh, s)),
            sp, specs)

    def merge_params(self, sp: Params) -> Params:
        """Stacked layout -> the full host tree (tests / checkpointing),
        matching ``PipelineEngine.merge_params`` output structure."""
        stages = jax.device_get(sp["stages"])
        layers: List[Params] = []
        for s in range(self.pp):
            for j in range(self.lps):
                layers.append(jax.tree.map(lambda x: np.asarray(x)[s],
                                           stages[j]))
        return {"layers": tuple(layers),
                "embed": jax.device_get(sp["embed"]),
                "prenorm": jax.device_get(sp["prenorm"]),
                "head": jax.device_get(sp["head"])}

    def init_opt(self, sp: Params, axes: Params) -> Any:
        from hetu_galvatron_tpu.parallel.spmd import opt_state_specs

        opt_pspecs = self.stacked_param_specs(axes, opt=True)
        specs = opt_state_specs(self.tx, sp, opt_pspecs)
        self._opt_shardings = self._nshd(specs)
        init = jax.jit(self.tx.init, out_shardings=self._opt_shardings)
        return init(sp)

    # ------------------------------------------------------------------
    # stacked stage programs (explicit leading [pp] stage axis — NOT a
    # vmap, so the shard_map kernels run inside; weight-free segments ride
    # plain vmaps over the lane axis, which trace identically to the old
    # per-lane form)
    # ------------------------------------------------------------------

    def _lane_keys(self, step_rng, mbs):
        """[pp] per-(microbatch, stage) dropout keys — same derivation as
        the host engine's ``_mb_rng`` (and the old vmapped core), so a
        compiled run replays identical masks. None when dropout is off."""
        if step_rng is None or not self._use_dropout:
            return None
        lanes = jnp.arange(self.pp)
        return jax.vmap(lambda mb, lane: jax.random.fold_in(
            jax.random.fold_in(step_rng, mb), lane))(mbs, lanes)

    def _st_dropout(self, x, rate, rngs):
        """Per-lane inverted dropout on a ``[pp, ...]`` stacked value:
        vmapped over the lane keys, bit-identical to the host engine's
        per-stage masks under the partitionable threefry rng."""
        if rngs is None or rate <= 0.0:
            return x
        return jax.vmap(lambda xl, r: M.dropout(xl, rate, r))(x, rngs)

    def _st_norm(self, p, x):
        """Stacked per-layer norm: params carry the leading ``[pp]`` stage
        axis; per-lane apply_norm under vmap keeps the fp32 arithmetic
        bit-identical to the host engine's per-stage call."""
        if not p:
            return x
        return jax.vmap(lambda pl, xl: M.apply_norm(pl, xl, self.cfg))(p, x)

    def _stacked_attention(self, p, x, rope, attn_rngs, causal):
        """modules.apply_attention on a ``[pp, B, S, H]`` stacked stream
        with ``[pp, ...]`` stacked weights: the projections run as explicit
        leading-axis einsums — or the stage-stacked ring kernels when
        ``tp_overlap`` is on — and the attention core is the stage-stacked
        kernel from ``_build_attention_core`` (vmapped XLA core when None).
        Mirrors the module's dtype casts and dropout dispatch rules."""
        cfg = self.cfg
        cd = self.compute_dtype
        mm = self._ops.matmuls or {}
        pp_, B, S, _ = x.shape
        hd = cfg.head_dim
        nq, nkv = cfg.num_attention_heads, cfg.kv_heads
        w = p["wqkv"].astype(cd)
        if "qkv" in mm:
            qkv = mm["qkv"](x.astype(cd), w)
        else:
            qkv = jnp.einsum("pbsh,phf->pbsf", x.astype(cd), w,
                             preferred_element_type=jnp.float32)
        if "bqkv" in p:
            qkv = qkv + p["bqkv"][:, None, None, :]
        qkv = qkv.astype(cd)
        q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
        q = q.reshape(pp_, B, S, nq, hd)
        k = k.reshape(pp_, B, S, nkv, hd)
        v = v.reshape(pp_, B, S, nkv, hd)
        if rope is not None:
            cos, sin = rope
            q = M.apply_rope(q, cos, sin)
            k = M.apply_rope(k, cos, sin)
        core = self._ops.sdpa
        use_drop = attn_rngs is not None and cfg.attention_dropout > 0.0
        if use_drop:
            if core is None:
                out = jax.vmap(lambda qq, kk, vv, rr: M.xla_sdpa(
                    qq, kk, vv, causal=causal,
                    dropout_rate=cfg.attention_dropout,
                    dropout_rng=rr))(q, k, v, attn_rngs)
            elif getattr(core, "supports_dropout", False):
                out = core(q, k, v, causal=causal,
                           dropout_rate=cfg.attention_dropout,
                           dropout_rng=attn_rngs)
            else:
                # same refusal as modules.apply_attention: silently swapping
                # a ring/Ulysses kernel for the score-materializing XLA core
                # would be an OOM/perf cliff on the plans it exists for
                raise NotImplementedError(
                    "attention_dropout > 0 is only supported with the XLA "
                    "attention core and the Pallas flash kernel; the "
                    "installed ring/Ulysses kernel has no dropout variant. "
                    "Avoid cp/ulysses layers or set "
                    "model.attention_dropout=0; hidden_dropout works with "
                    "every kernel")
        elif core is None:
            out = jax.vmap(lambda qq, kk, vv: M.xla_sdpa(
                qq, kk, vv, causal=causal))(q, k, v)
        else:
            out = core(q, k, v, causal=causal)
        out = out.reshape(pp_, B, S, nq * hd)
        wo = p["wo"].astype(cd)
        if "out" in mm:
            y = mm["out"](out, wo)
        else:
            y = jnp.einsum("pbsf,pfh->pbsh", out, wo,
                           preferred_element_type=jnp.float32)
        if "bo" in p:
            y = y + p["bo"][:, None, None, :]
        return y.astype(cd)

    def _stacked_mlp(self, p, x):
        """modules.apply_mlp with stacked weights (gated/plain, bias adds,
        and the fc1_pair overlapped form all mirrored)."""
        cfg = self.cfg
        cd = self.compute_dtype
        mm = self._ops.matmuls or {}
        act = M._ACTS[cfg.hidden_act]
        win = p["win"].astype(cd)
        gated = cfg.hidden_act in ("swiglu", "geglu")
        if gated and "fc1_pair" in mm:
            F = p["wout"].shape[1]
            gate, up = mm["fc1_pair"](x.astype(cd), win[..., :F],
                                      win[..., F:])
            if "bin" in p:
                gate = gate + p["bin"][:, None, None, :F]
                up = up + p["bin"][:, None, None, F:]
            hproj = act(gate.astype(cd)) * up.astype(cd)
        else:
            if "fc1" in mm:
                hproj = mm["fc1"](x.astype(cd), win)
            else:
                hproj = jnp.einsum("pbsh,phf->pbsf", x.astype(cd), win,
                                   preferred_element_type=jnp.float32)
            if "bin" in p:
                hproj = hproj + p["bin"][:, None, None, :]
            hproj = hproj.astype(cd)
            if gated:
                gate, up = jnp.split(hproj, 2, axis=-1)
                hproj = act(gate) * up
            else:
                hproj = act(hproj)
        wout = p["wout"].astype(cd)
        if "fc2" in mm:
            y = mm["fc2"](hproj, wout)
        else:
            y = jnp.einsum("pbsf,pfh->pbsh", hproj, wout,
                           preferred_element_type=jnp.float32)
        if "bout" in p:
            y = y + p["bout"][:, None, None, :]
        return y.astype(cd)

    def _stacked_decoder_layer(self, p, x, rope, layer_keys, causal):
        """modules.apply_decoder_layer on the stacked stream: pre-norm or
        post-norm (bert) residual block with per-lane dropout keys split
        exactly like the module does."""
        cfg = self.cfg
        r_attn = r_res1 = r_res2 = None
        if layer_keys is not None:
            r3 = jax.vmap(lambda kk: jax.random.split(kk, 3))(layer_keys)
            r_attn, r_res1, r_res2 = r3[:, 0], r3[:, 1], r3[:, 2]
        drop = lambda y, rr: self._st_dropout(y, cfg.hidden_dropout, rr)
        if cfg.post_norm:
            x = self._st_norm(
                p["ln1"],
                x + drop(self._stacked_attention(p["attn"], x, rope, r_attn,
                                                 causal), r_res1))
            return self._st_norm(
                p["ln2"],
                x + drop(self._stacked_mlp(p["mlp"], x), r_res2))
        h = self._st_norm(p["ln1"], x)
        x = x + drop(self._stacked_attention(p["attn"], h, rope, r_attn,
                                             causal), r_res1)
        h = self._st_norm(p["ln2"], x)
        x = x + drop(self._stacked_mlp(p["mlp"], h), r_res2)
        return x

    def _stacked_layers(self, stages_w, x, lane_keys):
        """The Lps decoder-layer slots on the stacked stream (per-layer
        remat honored, same checkpoint policy as the host engine)."""
        cfg = self.cfg
        rope = None
        if cfg.position_embedding_type == "rope":
            cos, sin = M.rope_cos_sin(x.shape[2], cfg.head_dim,
                                      cfg.rope_theta,
                                      scaling=cfg.rope_scaling)
            rope = (cos, sin)
        causal = cfg.model_type != "bert"
        for j, lp in enumerate(stages_w):
            keys = None
            if lane_keys is not None:
                keys = jax.vmap(
                    lambda kk, _j=j: jax.random.fold_in(kk, _j))(lane_keys)
            fn = partial(self._stacked_decoder_layer, rope=rope,
                         layer_keys=keys, causal=causal)
            if self.layer_sh.checkpoint:
                fn = M.remat(fn, cfg)
            x = fn(lp, x)
        return x

    def _stacked_entry(self, embed_p, x_in, tokens, lane_keys):
        """Stage input: lane 0 embeds the tick's tokens, others take the
        rotated activation. The embedding is lane-invariant (computed once
        and broadcast) unless dropout is on, in which case each lane embeds
        with its own key — matching the old vmapped trace exactly."""
        cfg = self.cfg
        if lane_keys is None:
            emb = M.apply_embedding(
                embed_p, tokens, cfg,
                compute_dtype=self.compute_dtype)[None]
        else:
            ek = jax.vmap(lambda kk: jax.random.fold_in(
                kk, M.DROPOUT_STREAM_EMBED))(lane_keys)
            emb = jax.vmap(lambda kk: M.apply_embedding(
                embed_p, tokens, cfg, compute_dtype=self.compute_dtype,
                dropout_rng=kk))(ek)
        lane0 = (jnp.arange(self.pp) == 0)[:, None, None, None]
        return jnp.where(lane0, emb, x_in)

    def _stacked_fwd(self, stages_w, embed_p, x_in, tokens, mbs, step_rng):
        lane_keys = self._lane_keys(step_rng, mbs)
        x = self._stacked_entry(embed_p, x_in, tokens, lane_keys)
        return self._stacked_layers(stages_w, x, lane_keys)

    def _stacked_full(self, stages_w, shared, x_in, tokens, labels, mask,
                      mbs, step_rng):
        """Stage forward INCLUDING the head: returns (y_out, [pp] losses).
        Used by backward ticks (the vjp recomputes the stage from its
        stored input, per-stage remat) and by eval. Only the last lane's
        loss ever receives a non-zero cotangent / enters the loss
        accumulator; the vocab weights are replicated across pp, so the
        head segment is a plain per-lane vmap."""
        cfg = self.cfg
        lane_keys = self._lane_keys(step_rng, mbs)
        x = self._stacked_entry(shared["embed"], x_in, tokens, lane_keys)
        y = self._stacked_layers(stages_w, x, lane_keys)
        h = M.apply_norm(shared["prenorm"], y, cfg)
        wte = (shared["embed"]["wte"]
               if cfg.tie_word_embeddings else None)
        head = shared["head"]

        def lane_loss(hh):
            logits = M.apply_lm_head(head, hh, cfg, wte=wte,
                                     compute_dtype=self.compute_dtype)
            return M.cross_entropy_loss(logits, labels, mask)

        return y, jax.vmap(lane_loss)(h)

    # ------------------------------------------------------------------
    # the fused step
    # ------------------------------------------------------------------

    def _schedule_constants(self, m: int):
        pp = self.pp
        T = m + 2 * (pp - 1)
        D = 2 * pp - 1  # circular input-buffer depth (see module docstring)
        return T, D

    def bubble_frac(self, m: Optional[int] = None) -> float:
        """Idle fraction of the lockstep schedule: each lane does 2m work
        units over T = m + 2(pp-1) ticks of 2 slots each."""
        m = max(m if m is not None else self.hpc.chunks, 1)
        return (2.0 * (self.pp - 1)) / (m + 2 * (self.pp - 1))

    def _build_step(self, m: int, use_dropout: bool):
        cfg = self.cfg
        pp, lps = self.pp, self.lps
        T, D = self._schedule_constants(m)
        mesh = self.mesh
        act_sp = stacked_spec(self.layer_sh.act_spec())
        rot_fwd = make_pp_rotation(mesh, act_sp, +1)
        rot_bwd = make_pp_rotation(mesh, act_sp, -1)
        act_shd = NamedSharding(mesh, act_sp)
        lanes = np.arange(pp)
        clip = self.train.clip_grad
        tx = self.tx

        from hetu_galvatron_tpu.parallel.spmd import make_embed_use_constraint

        # forward-side hint only: under ZeRO-3 the gathered table must not
        # re-materialize per use site (parallel/spmd.py)
        axes_embed = getattr(self, "_embed_axes", None)
        constrain_embed = (
            make_embed_use_constraint(axes_embed, self.vocab_sh, mesh)
            if axes_embed is not None else (lambda e: e))

        # de-vmapped stage programs: ordinary traced code over the stacked
        # [pp, ...] stream — which is what lets the shard_map kernels
        # (ring matmuls / flash / ulysses / cp) run inside the scan
        vfwd = self._stacked_fwd
        vfull = self._stacked_full

        def step(sp, opt, batch, step_rng):
            tokens = batch["tokens"]            # [m, B, S] int32
            labels = batch["labels"]            # [m, B, S] int32
            mask = batch.get("loss_mask")       # [m, B, S] f32 or absent
            weights = microbatch_weights(mask, m)
            shared = {"embed": constrain_embed(sp["embed"]),
                      "prenorm": sp["prenorm"], "head": sp["head"]}
            stages_w = sp["stages"]
            b, s = tokens.shape[1], tokens.shape[2]
            zero_act = jnp.zeros((pp, b, s, cfg.hidden_size),
                                 self.compute_dtype)
            gacc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, p.dtype),
                {"stages": stages_w, **shared})
            buf0 = jnp.zeros((pp, D, b, s, cfg.hidden_size),
                             self.compute_dtype)
            lanes_a = jnp.asarray(lanes)

            def idx(arr, i):
                return jax.lax.dynamic_index_in_dim(
                    arr, jnp.clip(i, 0, m - 1), 0, keepdims=False)

            def tick(carry, t):
                fwd_x, bwd_dy, buf, gacc, loss_acc = carry
                # ---- forward unit: stage s runs microbatch i = t - s ----
                fi = t - lanes_a
                tok_f = idx(tokens, t)  # lane 0's fwd microbatch is t
                # store the PRE-apply stage inputs (the backward recomputes
                # from them); raw-fi slots make out-of-range writes land on
                # provably-dead slots (module docstring), so no gating read
                slot_f = jnp.mod(fi, D)
                buf = jax.vmap(
                    lambda bl, x, i: jax.lax.dynamic_update_index_in_dim(
                        bl, x, i, 0))(buf, fwd_x, slot_f)
                y = vfwd(stages_w, shared["embed"], fwd_x, tok_f,
                         jnp.clip(fi, 0, m - 1), step_rng)
                y = jax.lax.with_sharding_constraint(y, act_shd)
                # ---- backward unit: stage s runs mb j = t - 2(pp-1) + s ----
                bj = t - 2 * (pp - 1) + lanes_a
                bwd_valid = (bj >= 0) & (bj < m)
                slot_b = jnp.mod(bj, D)
                x_st = jax.vmap(
                    lambda bl, i: jax.lax.dynamic_index_in_dim(
                        bl, i, 0, keepdims=False))(buf, slot_b)
                tok_b = idx(tokens, bj[0])        # lane 0 re-embeds
                lbl_b = idx(labels, bj[pp - 1])   # last lane's CE target
                msk_b = idx(mask, bj[pp - 1]) if mask is not None else None
                w_b = idx(weights, bj[pp - 1])

                # bubble masking: zero cotangent seeds on invalid lanes
                # make EVERY grad they emit exactly zero (vjp linearity)
                dy_in = jnp.where(bwd_valid[:, None, None, None], bwd_dy,
                                  jnp.zeros_like(bwd_dy))
                (y_re, losses), vjp_fn = jax.vjp(
                    lambda ws, sh, xs: vfull(
                        ws, sh, xs, tok_b, lbl_b, msk_b,
                        jnp.clip(bj, 0, m - 1), step_rng),
                    stages_w, shared, x_st)
                dl_in = jnp.where(
                    (lanes_a == pp - 1) & bwd_valid,
                    w_b.astype(jnp.float32), 0.0)
                dws, dsh, dxs = vjp_fn((dy_in, dl_in))
                gacc = jax.tree.map(jnp.add, gacc,
                                    {"stages": dws, **dsh})
                loss_acc = loss_acc + jnp.where(
                    bwd_valid[pp - 1], w_b * losses[pp - 1], 0.0)
                # ---- rotate: activations s->s+1, cotangents s->s-1 ----
                fwd_x = rot_fwd(y)
                dxs = jax.lax.with_sharding_constraint(dxs, act_shd)
                bwd_dy = rot_bwd(dxs)
                return (fwd_x, bwd_dy, buf, gacc, loss_acc), None

            carry0 = (zero_act, zero_act, buf0, gacc0,
                      jnp.zeros((), jnp.float32))
            (_, _, _, grads, loss), _ = jax.lax.scan(
                tick, carry0, jnp.arange(T))

            # global grad-norm clip fused into the program (host engine:
            # _gnorm_jit/_clip_jit across submeshes). The single wte already
            # counts the tied grads once — no double-count correction.
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in jax.tree.leaves(grads))
            gnorm = jnp.sqrt(sq)
            scale = (jnp.minimum(1.0, clip / (gnorm + 1e-12))
                     if clip and clip > 0 else jnp.ones((), jnp.float32))
            grads = jax.tree.map(lambda g: g * scale, grads)
            updates, new_opt = tx.update(grads, opt, sp)
            new_sp = optax.apply_updates(sp, updates)
            return new_sp, new_opt, {"loss": loss, "grad_norm": gnorm}

        # out_shardings pin the step to a FIXED POINT of its own layouts:
        # without them the first call's propagated outputs differ from the
        # split_params placement and the second call would recompile
        out_shd = (getattr(self, "_param_shardings", None),
                   getattr(self, "_opt_shardings", None), None)
        jit_kw = dict(donate_argnums=(0, 1) if self.donate else ())
        if out_shd[0] is not None and out_shd[1] is not None:
            jit_kw["out_shardings"] = out_shd
        if not use_dropout:
            step_nr = lambda sp, opt, batch: step(sp, opt, batch, None)
            return jax.jit(step_nr, **jit_kw)
        return jax.jit(step, **jit_kw)

    def _build_eval(self, m: int):
        """Forward-only compiled schedule: T = m + pp - 1 ticks, loss
        accumulated from the last lane (dropout off — eval semantics)."""
        cfg = self.cfg
        pp = self.pp
        mesh = self.mesh
        act_sp = stacked_spec(self.layer_sh.act_spec())
        rot_fwd = make_pp_rotation(mesh, act_sp, +1)
        act_shd = NamedSharding(mesh, act_sp)
        lanes = np.arange(pp)

        def vfull(stages_w, shared, x_stack, tokens, labels, mask, mbs):
            return self._stacked_full(stages_w, shared, x_stack, tokens,
                                      labels, mask, mbs, None)

        def eval_step(sp, batch):
            tokens, labels = batch["tokens"], batch["labels"]
            mask = batch.get("loss_mask")
            weights = microbatch_weights(mask, m)
            shared = {"embed": sp["embed"], "prenorm": sp["prenorm"],
                      "head": sp["head"]}
            b, s = tokens.shape[1], tokens.shape[2]
            zero_act = jnp.zeros((pp, b, s, cfg.hidden_size),
                                 self.compute_dtype)
            lanes_a = jnp.asarray(lanes)

            def idx(arr, i):
                return jax.lax.dynamic_index_in_dim(
                    arr, jnp.clip(i, 0, m - 1), 0, keepdims=False)

            def tick(carry, t):
                fwd_x, loss_acc = carry
                fi = t - lanes_a
                li = t - (pp - 1)  # last lane's microbatch this tick
                y, losses = vfull(sp["stages"], shared, fwd_x, idx(tokens, t),
                                  idx(labels, li), idx(mask, li)
                                  if mask is not None else None,
                                  jnp.clip(fi, 0, m - 1))
                loss_acc = loss_acc + jnp.where(
                    (li >= 0) & (li < m), idx(weights, li) * losses[pp - 1],
                    0.0)
                y = jax.lax.with_sharding_constraint(y, act_shd)
                return (rot_fwd(y), loss_acc), None

            (_, loss), _ = jax.lax.scan(
                tick, (zero_act, jnp.zeros((), jnp.float32)),
                jnp.arange(m + pp - 1))
            return loss

        return jax.jit(eval_step)

    # ------------------------------------------------------------------
    # public step API (PipelineEngine-compatible)
    # ------------------------------------------------------------------

    def put_batch(self, batch: Dict[str, np.ndarray], m: int
                  ) -> Dict[str, jax.Array]:
        """Host batch -> stacked [m, B, S] device arrays under the plan's
        batch sharding. The ONLY per-step host->device transfer of the
        steady state (the schedule's indices, weights and schedule masks
        are all program constants)."""
        allowed = {"tokens", "labels", "loss_mask"}
        extra = set(batch) - allowed - {"dropout_rng"}
        if extra:
            raise NotImplementedError(
                f"the compiled pipeline schedule does not thread batch keys "
                f"{sorted(extra)}")
        b = batch["tokens"].shape[0]
        if b % m:
            raise ValueError(f"batch {b} not divisible by chunks {m}")
        spec = self.vocab_sh.batch_spec()
        shd = NamedSharding(self.mesh, P(None, *spec))
        out = {}
        for k in allowed & set(batch):
            v = np.asarray(batch[k])
            out[k] = jax.device_put(
                v.reshape((m, b // m) + v.shape[1:]), shd)
        return out

    def _resolve_m(self, num_microbatches: Optional[int]) -> int:
        return max(num_microbatches if num_microbatches is not None
                   else self.hpc.chunks, 1)

    def train_step(
        self,
        sp: Params,
        opt: Any,
        batch: Dict[str, np.ndarray],
        num_microbatches: Optional[int] = None,
    ) -> Tuple[Params, Any, Dict[str, Any]]:
        """One fused optimizer step. ``batch`` may be a raw host batch
        ([gbsz, ...] numpy) or the output of :meth:`put_batch` (stacked
        device arrays — zero transfers besides the feed). Metrics stay lazy
        device scalars (no host sync on the step path)."""
        m = self._resolve_m(num_microbatches)
        batch = dict(batch)
        step_rng = batch.pop("dropout_rng", None)
        if self._use_dropout and step_rng is None:
            raise ValueError(
                "cfg enables dropout but the batch has no 'dropout_rng' "
                "key; cli/train_dist.py adds it automatically — manual "
                "callers must pass one per step")
        # .ndim only — np.asarray on a staged device batch would pull the
        # whole token array back to the host every step
        if batch["tokens"].ndim == 2:
            batch = self.put_batch(batch, m)
        if m not in self._step_jits:
            self._step_jits[m] = self._build_step(m, self._use_dropout)
        fn = self._step_jits[m]
        # XLA-counted flops/bytes for the fused program (cost/* gauges;
        # no-op without a metrics sink). BEFORE the call: the step donates
        # (sp, opt, batch), and lowering only reads avals
        maybe_record_jit_cost(
            f"pp/compiled_step_m{m}", fn,
            (sp, opt, batch, step_rng) if self._use_dropout
            else (sp, opt, batch))
        with span("pp/compiled_step"):
            if self._use_dropout:
                out = fn(sp, opt, batch, step_rng)
            else:
                out = fn(sp, opt, batch)
        get_registry().gauge("pp/bubble_frac").set(self.bubble_frac(m))
        return out

    def eval_step(
        self,
        sp: Params,
        batch: Dict[str, np.ndarray],
        num_microbatches: Optional[int] = None,
    ) -> Dict[str, float]:
        """Held-out loss under the training plan (dropout off)."""
        m = self._resolve_m(num_microbatches)
        batch = dict(batch)
        batch.pop("dropout_rng", None)
        if batch["tokens"].ndim == 2:
            batch = self.put_batch(batch, m)
        if m not in self._eval_jits:
            self._eval_jits[m] = self._build_eval(m)
        return {"loss": float(self._eval_jits[m](sp, batch))}

    def _prep_trace(self, sp: Params, opt: Any,
                    batch: Dict[str, np.ndarray],
                    num_microbatches: Optional[int]):
        """Shared trace-entry prep for :meth:`step_jaxpr` /
        :meth:`step_lowered`: resolve the microbatch count, validate and
        pop the dropout rng, stage the batch, and fill the step-jit
        cache. Returns ``(fn, args)`` ready to trace or lower."""
        m = self._resolve_m(num_microbatches)
        batch = dict(batch)
        step_rng = batch.pop("dropout_rng", None)
        if self._use_dropout and step_rng is None:
            raise ValueError(
                "cfg enables dropout but the batch has no 'dropout_rng' "
                "key; cli/train_dist.py adds it automatically — manual "
                "callers must pass one per step")
        if batch["tokens"].ndim == 2:
            batch = self.put_batch(batch, m)
        if m not in self._step_jits:
            self._step_jits[m] = self._build_step(m, self._use_dropout)
        fn = self._step_jits[m]
        args = (sp, opt, batch, step_rng) if self._use_dropout \
            else (sp, opt, batch)
        return fn, args

    def step_jaxpr(self, sp: Params, opt: Any, batch: Dict[str, np.ndarray],
                   num_microbatches: Optional[int] = None):
        """ClosedJaxpr of the fused step program — the static-analysis hook
        (``analysis/census.py``). Tracing never executes and never consumes
        donated buffers, so this is safe before (or instead of) any real
        step; the traced fn is cached in the step-jit cache, so a later
        ``train_step`` at the same microbatch count reuses it."""
        fn, args = self._prep_trace(sp, opt, batch, num_microbatches)
        return jax.make_jaxpr(fn)(*args)

    def step_lowered(self, sp: Params, opt: Any,
                     batch: Dict[str, np.ndarray],
                     num_microbatches: Optional[int] = None):
        """``jax.stages.Lowered`` of the fused step — the partition-time
        static-analysis hook (``analysis/sharding_flow.py`` compiles it
        and scans the HLO for GSPMD-inserted collectives). Lowering reads
        avals only; nothing executes and no donated buffer is consumed.
        Compiling the returned object is the expensive part — callers on
        the fast path should stick to :meth:`step_jaxpr`."""
        fn, args = self._prep_trace(sp, opt, batch, num_microbatches)
        return fn.lower(*args)

    def compile_count(self) -> int:
        """Total compiled executables across the engine's jit caches — the
        recompile-pinning hook (serving engine convention): steady state
        must hold this constant."""
        return sum(f._cache_size()
                   for f in (*self._step_jits.values(),
                             *self._eval_jits.values()))
