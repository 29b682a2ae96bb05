"""Pipeline-parallel engine: GPipe and 1F1B schedules over stage submeshes.

Capability parity with the reference pipeline engine
(runtime/pipeline/pipeline.py:43 ``PipelineParallel``, :729-905 gpipe,
:386-712 pipedream-flush/1F1B, stage slicing :104-106, tied-embedding grad
all-reduce :708-710,1042), re-designed for the single-controller JAX runtime:

* Each pipeline stage is its OWN jitted GSPMD program over a **submesh** of
  the global device set (the stage's slice of chips, with the binary d-axes
  of runtime/mesh.py). Per-layer tp/dp/ZeRO/remat heterogeneity inside a
  stage reuses the exact same sharding lowering as the pp=1 path — and
  uneven ``pp_division`` is natural because stages are separate programs.
* Microbatch activations travel between submeshes with `jax.device_put`
  (ICI DMA on TPU) — the reference's batched NCCL isend/irecv
  (pipeline.py:1091-1140) becomes a sharding-to-sharding transfer.
* The host sequences the schedule; JAX async dispatch overlaps stages
  (stage s microbatch m and stage s+1 microbatch m-1 run concurrently on
  disjoint chips). GPipe = all-forward-then-all-backward; 1F1B = warmup of
  (P - s) forwards per stage then alternating 1F1B steady state, which
  bounds live activations per stage exactly like the reference.
* Backward recomputes the stage forward (per-stage remat) via `jax.vjp`,
  so stored state per in-flight microbatch is just the stage input.
* Tied embeddings: the last stage holds a transposed copy of wte; after
  each step both copies' grads are summed across the two stages (the
  reference's finalize_wte_grads over the embedding group) and both are
  updated with identical elementwise Adam math, keeping them in sync.
* Encoder-decoder (t5) pipelines: the combined enc+dec layer sequence is
  stage-sliced like the reference's any-arch PipeSequential
  (pipeline.py:1592). The inter-stage activation is a PAIR ``(a, b)``:
  ``a`` is the encoder stream (then the encoder memory once the stage
  holding the last encoder layer applies enc_norm) and ``b`` is the decoder
  stream. Stage 0 embeds BOTH token streams with the shared embedding, so
  the decoder stream rides through encoder stages as a passthrough — wte
  gradients from both streams accumulate on stage 0 with no extra tied-copy
  reconciliation; memory cotangents flow back through the same pair.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_galvatron_tpu.core.args_schema import ModelArgs, TrainArgs
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.runtime.hybrid_config import HybridParallelConfig
from hetu_galvatron_tpu.runtime.mesh import (
    LayerSharding,
    build_mesh,
    device_array,
    lower_strategy,
    lower_vocab_strategy,
    spec_tree as _spec_tree,
)
from hetu_galvatron_tpu.observability.registry import get_registry
from hetu_galvatron_tpu.observability.trace_analysis import (
    maybe_record_jit_cost,
    mosaic_custom_calls,
)
from hetu_galvatron_tpu.observability.tracing import span
from hetu_galvatron_tpu.runtime.optimizer import make_lr_schedule

Params = Dict[str, Any]


def _pipeline_optimizer(train: TrainArgs) -> optax.GradientTransformation:
    """Adam+wd+schedule WITHOUT the global-norm clip — pipeline clipping is
    global across stages, so the scale factor is applied explicitly by the
    engine (reference clip_grad_norm handles sharded params the same way,
    optimizer/utils.py:14)."""
    from hetu_galvatron_tpu.runtime.optimizer import (
        _decay_mask,
        partition_expert_bias,
    )

    chain = [optax.scale_by_adam(b1=train.adam_beta1, b2=train.adam_beta2,
                                 eps=train.adam_eps)]
    if train.weight_decay:
        chain.append(optax.add_decayed_weights(train.weight_decay,
                                               mask=_decay_mask))
    chain.append(optax.scale_by_learning_rate(make_lr_schedule(train)))
    return partition_expert_bias(optax.chain(*chain))


@dataclass
class _Stage:
    index: int
    mesh: Mesh
    layer_range: Tuple[int, int]  # [lo, hi) decoder-layer indices
    shardings: List[LayerSharding]  # per decoder layer in this stage
    vocab: Optional[LayerSharding]  # set on first/last stage
    has_embed: bool
    has_head: bool
    # encoder-decoder (t5) only:
    enc_layer_range: Tuple[int, int] = (0, 0)  # [lo, hi) encoder-layer idxs
    enc_shardings: List[LayerSharding] = None
    has_enc_norm: bool = False


class PipelineEngine:
    """Stage-sliced hybrid-parallel training with GPipe / 1F1B schedules."""

    def __init__(
        self,
        cfg: ModelArgs,
        hpc: HybridParallelConfig,
        train: TrainArgs,
        devices: Optional[List] = None,
        *,
        compute_dtype=jnp.bfloat16,
        dcn_slices: int = 1,
        tp_overlap: bool = False,
        use_flash: Optional[bool] = None,
        flash_interpret: bool = False,
    ):
        from hetu_galvatron_tpu.analysis.eligibility import (
            mixed_stack_reason,
            residual_streams_reason,
            tower_reason,
        )

        reason = mixed_stack_reason(
            cfg, "the host pipeline engine (its stage programs tell dense "
            "and expert blocks apart by their trees and attend in every "
            "block)", feed_forward_may_differ=True
        ) or residual_streams_reason(
            cfg, "the host pipeline engine") or tower_reason(
            cfg, "the host pipeline engine")
        if reason is not None:
            raise NotImplementedError(reason + "; run it at pp_deg=1")
        self.cfg = cfg
        self.hpc = hpc
        self.train = train
        self.compute_dtype = compute_dtype
        devices = list(devices if devices is not None else jax.devices())
        if len(devices) < hpc.world_size:
            raise ValueError(
                f"need {hpc.world_size} devices, have {len(devices)}")
        # overlapped-TP projection matmuls inside the stage programs
        # (ops/overlap.py); eligible layers only — same dispatch as the
        # SPMD path's tp_overlap_overrides, per stage submesh
        self.tp_overlap = tp_overlap
        # attention-impl override knobs for parity drills: use_flash=None
        # keeps the cfg/platform default; flash_interpret runs the Pallas
        # kernels in interpret mode (CPU meshes)
        self._use_flash = use_flash
        self._flash_interpret = flash_interpret
        self.pp = hpc.pp_deg
        if self.pp < 2:
            # pp=1 routes through the SPMD path (cli/train_dist.py). The
            # engine's stage-0 backward differentiates w.r.t. its input —
            # with a single fused embed+head stage that input is integer
            # tokens — and the tied-embedding grad reconciliation assumes
            # separate first/last stages (ADVICE r2: a pp=1 engine would
            # silently untie wte/whead).
            raise ValueError(
                "PipelineEngine needs pp_deg >= 2; use make_spmd_train_step "
                "for pp=1")
        self.is_t5 = cfg.model_type == "t5"
        # DCN-aware global arrangement BEFORE carving stage groups: with
        # dcn_slices > 1 the pp axis (and outer dp) land on slice
        # boundaries, so each stage's submesh stays ICI-local
        devices = list(device_array(
            hpc.world_size, self.pp, devices[:hpc.world_size],
            dcn_slices).flat)
        per_stage = hpc.world_size // self.pp
        self.tx = _pipeline_optimizer(train)
        self.stages: List[_Stage] = []
        n_enc = hpc.num_encoder_layers
        # interleaved virtual stages: pp_division has pp*vpp chunks; chunk c
        # runs on physical device group c % pp (Megatron round-robin), so
        # each group hosts vpp non-contiguous model chunks and the
        # warmup/cooldown bubble shrinks ~vpp-fold. vpp=1 degenerates to the
        # plain one-chunk-per-group layout.
        self.vpp = max(getattr(hpc, "vpp_deg", 1), 1)
        group_meshes = []
        for g in range(self.pp):
            sub = devices[g * per_stage:(g + 1) * per_stage]
            group_meshes.append(build_mesh(per_stage, 1, devices=sub))
        n_chunks = self.pp * self.vpp
        lo = 0
        for s in range(n_chunks):
            mesh = group_meshes[s % self.pp]
            hi = lo + hpc.pp_division[s]
            # combined-stack slicing: hpc.layers = enc layers then dec layers
            enc_lo, enc_hi = min(lo, n_enc), min(hi, n_enc)
            dec_lo, dec_hi = max(lo, n_enc) - n_enc, max(hi, n_enc) - n_enc
            enc_shardings = [lower_strategy(st, mesh)
                             for st in hpc.layers[enc_lo:enc_hi]]
            shardings = [lower_strategy(st, mesh)
                         for st in hpc.layers[n_enc + dec_lo:n_enc + dec_hi]]
            vocab = lower_vocab_strategy(hpc.vocab, mesh, hpc.default_dp_type)
            has_enc_norm = self.is_t5 and (
                enc_lo <= n_enc - 1 < enc_hi or (n_enc == 0 and s == 0))
            self.stages.append(_Stage(
                index=s, mesh=mesh, layer_range=(dec_lo, dec_hi),
                shardings=shardings, vocab=vocab, has_embed=(s == 0),
                has_head=(s == n_chunks - 1),
                enc_layer_range=(enc_lo, enc_hi),
                enc_shardings=enc_shardings, has_enc_norm=has_enc_norm))
            lo = hi
        # ALL stage/step jits are built lazily on first use (like the eval
        # jits always were): an eval-only engine never constructs backward
        # or update programs, an untied plan never constructs the tied-grad
        # transpose, and plans that never train build nothing at all.
        self._lazy_jits: Dict[str, Any] = {}
        self._eval_jits = None  # built on first eval_step (dropout off)
        # one-shot cost/* recording: resolved once per step (train_step),
        # not per microbatch — the schedule's inner loop is host dispatch
        # the devices wait on, so it must stay free of registry lookups
        # after the first recorded step
        self._jit_cost_done = False
        self._record_costs = False
        # Mosaic kernels summed over the stage BACKWARD programs (each
        # recomputes its forward, so they hold every kernel of the step);
        # counted by the first microbatch's backward, None before
        self.mosaic_custom_calls: Optional[int] = None

    def _jit(self, name: str, build) -> Any:
        """Construct-on-first-use cache for the engine's jitted helpers."""
        if name not in self._lazy_jits:
            self._lazy_jits[name] = build()
        return self._lazy_jits[name]

    @property
    def _fwd_jits(self) -> List[Optional[Callable]]:
        return self._jit("fwd", lambda: [self._make_fwd(st)
                                         for st in self.stages])

    @property
    def _bwd_jits(self) -> List[Callable]:
        return self._jit("bwd", lambda: [self._make_bwd(st)
                                         for st in self.stages])

    @property
    def _update_jits(self) -> List[Callable]:
        return self._jit("update", lambda: [self._make_update(st)
                                            for st in self.stages])

    @property
    def _transpose_jit(self) -> Callable:
        return self._jit("transpose", lambda: jax.jit(jnp.transpose))

    @property
    def _gnorm_jit(self) -> Callable:
        # expert_bias maintenance pseudo-grads stay out of the clip norm,
        # matching the SPMD path (clip_by_global_norm lives inside the
        # multi_transform adam branch, which never sees bias leaves)
        return self._jit("gnorm", lambda: jax.jit(
            lambda g: sum(
                jnp.sum(jnp.square(x.astype(jnp.float32)))
                for path, x in jax.tree_util.tree_leaves_with_path(g)
                if not path or "expert_bias" not in str(path[-1]))))

    @property
    def _clip_jit(self) -> Callable:
        clip = self.train.clip_grad
        return self._jit("clip", lambda: jax.jit(
            lambda sq: (jnp.sqrt(sq),
                        jnp.minimum(1.0, clip / (jnp.sqrt(sq) + 1e-12))
                        if clip and clip > 0 else jnp.ones((), jnp.float32))))

    # ------------------------------------------------------------------
    # params / optimizer state
    # ------------------------------------------------------------------

    def stage_param_axes(self, axes: Params, s: int) -> Params:
        st = self.stages[s]
        lo, hi = st.layer_range
        out: Params = {"layers": tuple(axes["layers"][lo:hi])}
        if self.is_t5:
            elo, ehi = st.enc_layer_range
            out["enc_layers"] = tuple(axes["enc_layers"][elo:ehi])
            if st.has_enc_norm:
                out["enc_norm"] = axes["enc_norm"]
        if st.has_embed:
            out["embed"] = axes["embed"]
        if st.has_head:
            out["prenorm"] = axes["prenorm"]
            if self.cfg.tie_word_embeddings:
                # tied copy replaces the wte reference; any extra head params
                # (bert's MLM transform wt/bt/ln/bias) ride along
                out["head"] = {**axes["head"], "whead": ("embed", "vocab")}
            else:
                out["head"] = axes["head"]
        return out

    def stage_param_specs(self, axes: Params, s: int, opt: bool = False
                          ) -> Params:
        st = self.stages[s]
        saxes = self.stage_param_axes(axes, s)
        out: Params = {"layers": tuple(
            _spec_tree(a, sh, opt)
            for a, sh in zip(saxes["layers"], st.shardings))}
        if "enc_layers" in saxes:
            out["enc_layers"] = tuple(
                _spec_tree(a, sh, opt)
                for a, sh in zip(saxes["enc_layers"], st.enc_shardings))
        for k in ("embed", "prenorm", "head", "enc_norm"):
            if k in saxes:
                out[k] = _spec_tree(saxes[k], st.vocab, opt)
        return out

    def split_params(self, params: Params, axes: Params) -> List[Params]:
        """Slice a full (host/single-device) params tree into per-stage
        sharded trees (reference stage slicing, pipeline.py:104-106)."""
        out = []
        for s, st in enumerate(self.stages):
            lo, hi = st.layer_range
            sp: Params = {"layers": tuple(params["layers"][lo:hi])}
            if self.is_t5:
                elo, ehi = st.enc_layer_range
                sp["enc_layers"] = tuple(params["enc_layers"][elo:ehi])
                if st.has_enc_norm:
                    sp["enc_norm"] = params["enc_norm"]
            if st.has_embed:
                sp["embed"] = params["embed"]
            if st.has_head:
                sp["prenorm"] = params["prenorm"]
                if self.cfg.tie_word_embeddings:
                    sp["head"] = {**params["head"],
                                  "whead": jnp.asarray(params["embed"]["wte"]).T}
                else:
                    sp["head"] = params["head"]
            specs = self.stage_param_specs(axes, s)
            out.append(jax.tree.map(
                lambda p, spec: jax.device_put(
                    p, NamedSharding(st.mesh, spec)), sp, specs))
        return out

    def merge_params(self, stage_params: List[Params]) -> Params:
        """Reassemble the full params tree (host) — for tests/checkpointing."""
        layers: List[Params] = []
        for sp in stage_params:
            layers.extend(jax.device_get(list(sp["layers"])))
        full: Params = {"layers": tuple(layers)}
        if self.is_t5:
            enc: List[Params] = []
            for sp in stage_params:
                enc.extend(jax.device_get(list(sp["enc_layers"])))
            full["enc_layers"] = tuple(enc)
            for sp, st in zip(stage_params, self.stages):
                if st.has_enc_norm:
                    full["enc_norm"] = jax.device_get(sp["enc_norm"])
        full["embed"] = jax.device_get(stage_params[0]["embed"])
        last = stage_params[-1]
        full["prenorm"] = jax.device_get(last["prenorm"])
        if self.cfg.tie_word_embeddings:
            full["head"] = jax.device_get(
                {k: v for k, v in last["head"].items() if k != "whead"})
        else:
            full["head"] = jax.device_get(last["head"])
        return full

    def init_opt(self, stage_params: List[Params], axes: Params
                 ) -> List[Any]:
        out = []
        for s, (sp, st) in enumerate(zip(stage_params, self.stages)):
            ospecs = self._opt_state_specs(sp, axes, s)
            init = jax.jit(self.tx.init, out_shardings=ospecs)
            out.append(init(sp))
        return out

    def _opt_state_specs(self, sp: Params, axes: Params, s: int):
        from hetu_galvatron_tpu.parallel.spmd import opt_state_specs

        opt_pspecs = self.stage_param_specs(axes, s, opt=True)
        specs = opt_state_specs(self.tx, sp, opt_pspecs)
        mesh = self.stages[s].mesh
        return jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), specs,
            is_leaf=lambda x: isinstance(x, P))

    # ------------------------------------------------------------------
    # stage programs
    # ------------------------------------------------------------------

    def _stage_apply(self, st: _Stage, sp: Params, x: jax.Array,
                     labels=None, loss_mask=None, dropout_rng=None,
                     position_ids=None, segment_ids=None):
        """Non-head stages return (x, stage_aux); the head stage returns
        ce_loss + its own aux (MoE auxiliary losses contribute per stage).
        ``dropout_rng`` is the per-(microbatch, stage) key; the schedule
        passes the SAME key to a microbatch's forward and backward so the
        backward's remat recomputation reuses the forward's masks.

        ``position_ids`` / ``segment_ids`` [B, S] are the packed-document
        fields (reset_position_ids / reset_attention_mask): the single
        controller places them on every stage's submesh directly, where the
        reference ships them through multi-tensor p2p transfers
        (pipeline.py:1140 _communicate)."""
        from hetu_galvatron_tpu.models.builder import make_block

        cfg = self.cfg

        def layer_rng(j):
            return M.fold_dropout_rng(dropout_rng, cfg, j)

        if st.has_embed:
            x = M.apply_embedding(sp["embed"], x, cfg,
                                  compute_dtype=self.compute_dtype,
                                  dropout_rng=layer_rng(M.DROPOUT_STREAM_EMBED),
                                  position_ids=position_ids)
        rope = None
        if cfg.position_embedding_type == "rope":
            cos, sin = M.rope_cos_sin(x.shape[1], cfg.head_dim,
                                      cfg.rope_theta,
                                      scaling=cfg.rope_scaling)
            if position_ids is not None:
                # packed samples: gather per-token rows -> [B, S, D/2]
                cos, sin = cos[position_ids], sin[position_ids]
            rope = (cos, sin)
        from hetu_galvatron_tpu.parallel.spmd import (
            attention_overrides,
            merge_ops,
        )

        overrides = attention_overrides(
            st.shardings, st.mesh,
            use_flash=(self._use_flash if self._use_flash is not None
                       else (None if cfg.use_flash_attn else False)),
            cp_zigzag=getattr(self.hpc, "cp_zigzag", False),
            flash_interpret=self._flash_interpret)
        if self.tp_overlap:
            from hetu_galvatron_tpu.parallel.spmd import tp_overlap_overrides

            # MoE detection must look at THIS stage's param slice — the
            # global moe_layer_freq alternation is invisible to stage-local
            # indices
            ov, _ = tp_overlap_overrides(
                st.shardings, st.mesh, cfg,
                is_moe_layer_fn=lambda _c, j: "moe" in sp["layers"][j])
            overrides = merge_ops(ov, overrides)
        seg_kw = ({"segment_ids": segment_ids}
                  if segment_ids is not None else {})
        aux_total = jnp.zeros((), jnp.float32)
        for j, lp in enumerate(sp["layers"]):
            sh = st.shardings[j]
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(st.mesh, sh.act_spec()))
            fn = make_block(
                cfg, ("full_attention", "experts" if "moe" in lp else "dense"),
                dict(rope=rope, compute_dtype=self.compute_dtype,
                     dropout_rng=layer_rng(j),
                     ops=overrides.get(j, M.LayerOps()), **seg_kw),
                sh.checkpoint)
            # per-layer router stats are an spmd-path feature; the stage
            # programs fold only the aux scalar into the loss
            x, aux, _, _ = fn(lp, x, {})
            aux_total = aux_total + aux
        if not st.has_head:
            # a stage may carry zero decoder layers (embed-only stage 0)
            sh = st.shardings[-1] if st.shardings else st.vocab
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(st.mesh, sh.act_spec())), aux_total
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(st.mesh, st.vocab.act_spec()))
        x = M.apply_norm(sp["prenorm"], x, cfg)
        # sp["head"] always carries whead on this stage (split_params puts
        # the transposed tied copy there), so apply_lm_head uses it directly
        logits = M.apply_lm_head(sp["head"], x, cfg,
                                 compute_dtype=self.compute_dtype)
        return M.cross_entropy_loss(logits, labels, loss_mask) + aux_total

    def _stage_apply_t5(self, st: _Stage, sp: Params, carry,
                        labels=None, loss_mask=None, dropout_rng=None):
        """Encoder-decoder stage program. ``carry`` is (enc_tokens,
        dec_tokens) on the embed stage, else the (a, b) activation pair —
        a = encoder stream / memory [B,S,H], b = decoder stream [B,T,H].
        Same contract as :meth:`_stage_apply`: non-head stages return
        (carry, aux); the head stage returns the CE loss."""
        from hetu_galvatron_tpu.models.encdec import apply_cross_decoder_layer
        from hetu_galvatron_tpu.parallel.spmd import attention_overrides

        cfg = self.cfg

        def layer_rng(j):
            return M.fold_dropout_rng(dropout_rng, cfg, j)

        if st.has_embed:
            enc_tok, dec_tok = carry
            a = M.apply_embedding(sp["embed"], enc_tok, cfg,
                                  compute_dtype=self.compute_dtype,
                                  dropout_rng=layer_rng(M.DROPOUT_STREAM_EMBED_ENC))
            b = M.apply_embedding(sp["embed"], dec_tok, cfg,
                                  compute_dtype=self.compute_dtype,
                                  dropout_rng=layer_rng(M.DROPOUT_STREAM_EMBED))
        else:
            a, b = carry
        rope_enc = rope_dec = None
        if cfg.position_embedding_type == "rope":
            rope_enc = M.rope_cos_sin(a.shape[1], cfg.head_dim, cfg.rope_theta,
                                      scaling=cfg.rope_scaling)
            rope_dec = M.rope_cos_sin(b.shape[1], cfg.head_dim, cfg.rope_theta,
                                      scaling=cfg.rope_scaling)
        use_flash = None if cfg.use_flash_attn else False
        enc_over = attention_overrides(st.enc_shardings, st.mesh,
                                       use_flash=use_flash)
        dec_over = attention_overrides(st.shardings, st.mesh,
                                       use_flash=use_flash, with_cross=True)
        for j, lp in enumerate(sp["enc_layers"]):
            sh = st.enc_shardings[j]
            a = jax.lax.with_sharding_constraint(
                a, NamedSharding(st.mesh, sh.act_spec()))
            kwargs = dict(rope=rope_enc, compute_dtype=self.compute_dtype,
                          causal=False, dropout_rng=layer_rng(M.DROPOUT_STREAM_ENC + j),
                          ops=enc_over.get(j, M.LayerOps()))
            fn = partial(M.apply_decoder_layer, cfg=cfg, **kwargs)
            if sh.checkpoint:
                fn = M.remat(fn, cfg)
            a = fn(lp, a)
        if st.has_enc_norm:
            a = M.apply_norm(sp["enc_norm"], a, cfg)
        for j, lp in enumerate(sp["layers"]):
            sh = st.shardings[j]
            b = jax.lax.with_sharding_constraint(
                b, NamedSharding(st.mesh, sh.act_spec()))
            kwargs = dict(rope=rope_dec, compute_dtype=self.compute_dtype,
                          dropout_rng=layer_rng(j),
                          ops=dec_over.get(j, M.LayerOps()))
            fn = partial(apply_cross_decoder_layer, cfg=cfg, **kwargs)
            if sh.checkpoint:
                fn = M.remat(fn, cfg)
            b = fn(lp, b, a)
        aux = jnp.zeros((), jnp.float32)  # t5 stacks carry no MoE aux
        if not st.has_head:
            spec_a, spec_b = self._carry_specs(st, out=True)
            return (jax.lax.with_sharding_constraint(
                        a, NamedSharding(st.mesh, spec_a)),
                    jax.lax.with_sharding_constraint(
                        b, NamedSharding(st.mesh, spec_b))), aux
        b = jax.lax.with_sharding_constraint(
            b, NamedSharding(st.mesh, st.vocab.act_spec()))
        b = M.apply_norm(sp["prenorm"], b, cfg)
        logits = M.apply_lm_head(sp["head"], b, cfg,
                                 compute_dtype=self.compute_dtype)
        return M.cross_entropy_loss(logits, labels, loss_mask) + aux

    def _carry_specs(self, st: _Stage, *, out: bool) -> Tuple[P, P]:
        """(spec_a, spec_b) for the t5 inter-stage activation pair. ``out``
        selects the stage's last-layer shardings (output constraint /
        cotangent placement), else its first-layer shardings (forward
        transfer into the stage). Zero-layer corners fall back to any valid
        rank-3 spec on the stage."""
        idx = -1 if out else 0
        sh_a = (st.enc_shardings[idx] if st.enc_shardings
                else (st.shardings[idx] if st.shardings else st.vocab))
        sh_b = st.shardings[idx] if st.shardings else sh_a
        return sh_a.act_spec(), sh_b.act_spec()

    def _apply_with_extras(self, st, sp, x, labels=None, loss_mask=None,
                           dropout_rng=None, pos=None, seg=None):
        """Route to the family apply; packed-doc extras are causal-LM only
        (the dataloader and _microbatches both gate t5)."""
        if self.is_t5:
            return self._stage_apply_t5(st, sp, x, labels, loss_mask,
                                        dropout_rng=dropout_rng)
        return self._stage_apply(st, sp, x, labels, loss_mask,
                                 dropout_rng=dropout_rng,
                                 position_ids=pos, segment_ids=seg)

    def _make_fwd(self, st: _Stage) -> Optional[Callable]:
        if st.has_head:
            return None  # head fwd is fused into its value_and_grad backward

        def f(sp, x, rng, pos, seg):
            y, _ = self._apply_with_extras(st, sp, x, dropout_rng=rng,
                                           pos=pos, seg=seg)
            return y
        return jax.jit(f)

    def _make_bwd(self, st: _Stage) -> Callable:
        """(dparams, dx) by recomputing the stage forward (per-stage remat).
        The head stage returns the (unweighted) loss alongside grads so the
        forward never runs separately just for the metric. ``rng`` is the
        same per-(microbatch, stage) key the forward ran with, so the remat
        recomputation reuses the identical dropout masks."""
        if st.has_head:
            def g(sp, x, labels, mask, seed, rng, pos, seg):
                def lf(sp_, x_):
                    return self._apply_with_extras(
                        st, sp_, x_, labels, mask, dropout_rng=rng,
                        pos=pos, seg=seg)
                loss, (dp, dx) = jax.value_and_grad(
                    lambda sp_, x_: lf(sp_, x_), argnums=(0, 1))(sp, x)
                dp = jax.tree.map(lambda t: seed * t, dp)
                # the activation cotangent keeps the activation's dtype:
                # the previous stage's vjp refuses an f32 dy for a bf16 y
                dx = jax.tree.map(lambda t: (seed * t).astype(t.dtype), dx)
                return dp, dx, loss
            return jax.jit(g)

        def g(sp, x, dy, seed, rng, pos, seg):
            # cotangents: dy for the activation, seed (the microbatch weight)
            # for this stage's MoE aux loss which enters the total directly
            (_, aux), vjp = jax.vjp(
                lambda sp_, x_: self._apply_with_extras(
                    st, sp_, x_, dropout_rng=rng, pos=pos, seg=seg), sp, x)
            dp, dx = vjp((dy, seed))
            return dp, dx, aux
        return jax.jit(g)

    def _make_eval(self, st: _Stage) -> Callable:
        """Forward-only stage program with eval semantics (no dropout): the
        head stage returns the held-out loss, others the activation."""
        if st.has_head:
            def f(sp, x, labels, mask, pos, seg):
                return self._apply_with_extras(st, sp, x, labels, mask,
                                               dropout_rng=None,
                                               pos=pos, seg=seg)
            return jax.jit(f)

        def f(sp, x, pos, seg):
            y, _ = self._apply_with_extras(st, sp, x, dropout_rng=None,
                                           pos=pos, seg=seg)
            return y
        return jax.jit(f)

    def eval_step(
        self,
        stage_params: List[Params],
        batch: Dict[str, np.ndarray],
        num_microbatches: Optional[int] = None,
    ) -> Dict[str, float]:
        """Held-out loss under the training plan: forward-only through the
        stage pipeline (reference evaluate() over the valid iterator,
        dataloader.py:462 split machinery). Dropout is off; no optimizer
        state is touched."""
        batch = dict(batch)
        batch.pop("dropout_rng", None)
        if self._eval_jits is None:
            self._eval_jits = [self._make_eval(st) for st in self.stages]
        mbs, weights = self._microbatches(batch, num_microbatches)
        losses = []
        n_stages = len(self.stages)
        for mb in mbs:
            x = self._put_stage0(mb)
            for s in range(n_stages):
                pos, seg = self._put_extras(mb, s)
                if s == n_stages - 1:
                    lbl, msk = self._put_last(mb)
                    losses.append(self._eval_jits[s](
                        stage_params[s], x, lbl, msk, pos, seg))
                else:
                    y = self._eval_jits[s](stage_params[s], x, pos, seg)
                    x = self._transfer(y, s + 1)
        loss = sum(float(w) * float(l) for w, l in zip(weights, losses))
        return {"loss": loss}

    def _make_update(self, st: _Stage) -> Callable:
        tx = self.tx

        def u(sp, opt, grads, scale):
            # expert_bias "gradients" ARE the maintenance update (SGD(1)
            # partition, runtime/optimizer.py) — the global clip must not
            # scale them, matching the SPMD path where clip_by_global_norm
            # lives inside the adam branch only
            grads = jax.tree_util.tree_map_with_path(
                lambda path, g: (g if "expert_bias" in str(path[-1])
                                 else g * scale), grads)
            updates, new_opt = tx.update(grads, opt, sp)
            return optax.apply_updates(sp, updates), new_opt
        return jax.jit(u)

    # ------------------------------------------------------------------
    # schedules
    # ------------------------------------------------------------------

    # batch keys the schedule knows how to place; anything else would be
    # silently dropped by _put_stage0/_put_last/_put_extras, so its presence
    # must be a loud error. position_ids/segment_ids (packed documents,
    # reset_position_ids/reset_attention_mask) are placed on EVERY stage's
    # submesh by the single controller — the reference ships them via
    # multi-tensor p2p instead (pipeline.py:1140 _communicate).
    _SHIPPED_KEYS = frozenset({"tokens", "labels", "loss_mask", "enc_tokens"})
    _EXTRA_KEYS = frozenset({"position_ids", "segment_ids"})

    def _microbatches(self, batch: Dict[str, np.ndarray],
                      num_microbatches: Optional[int] = None):
        shipped = self._SHIPPED_KEYS | (
            frozenset() if self.is_t5 else self._EXTRA_KEYS)
        extra = set(batch) - shipped
        if extra:
            raise NotImplementedError(
                f"the pipeline engine does not thread batch keys "
                f"{sorted(extra)} through its stage transfers")
        m = max(num_microbatches if num_microbatches is not None
                else self.hpc.chunks, 1)
        b = batch["tokens"].shape[0]
        if b % m:
            raise ValueError(f"batch {b} not divisible by chunks {m}")
        mbs = []
        for i in range(m):
            sl = slice(i * (b // m), (i + 1) * (b // m))
            mbs.append({k: np.asarray(v)[sl] for k, v in batch.items()})
        if "loss_mask" in batch:
            counts = np.array([mb["loss_mask"].sum() for mb in mbs],
                              dtype=np.float64)
        else:
            counts = np.ones(m)
        weights = counts / max(counts.sum(), 1.0)
        return mbs, weights

    def _put_stage0(self, mb):
        st = self.stages[0]
        shd = NamedSharding(st.mesh, st.vocab.batch_spec())
        if self.is_t5:
            return (jax.device_put(jnp.asarray(mb["enc_tokens"]), shd),
                    jax.device_put(jnp.asarray(mb["tokens"]), shd))
        return jax.device_put(jnp.asarray(mb["tokens"]), shd)

    def _put_last(self, mb):
        st = self.stages[-1]
        shd = NamedSharding(st.mesh, st.vocab.batch_spec())
        lbl = jax.device_put(jnp.asarray(mb["labels"]), shd)
        msk = (jax.device_put(jnp.asarray(mb["loss_mask"]), shd)
               if "loss_mask" in mb else None)
        return lbl, msk

    def _put_extras(self, mb, s: int):
        """Place packed-doc fields [B, S] on stage s's submesh (every stage
        needs segment_ids for attention masking and position_ids for rope;
        the controller holds the batch, so no inter-stage p2p is needed)."""
        st = self.stages[s]
        spec = (st.shardings[0].batch_spec() if st.shardings
                else st.vocab.batch_spec())
        shd = NamedSharding(st.mesh, spec)
        put = lambda k: (jax.device_put(jnp.asarray(mb[k]), shd)
                         if k in mb else None)
        return put("position_ids"), put("segment_ids")

    def _transfer(self, y, to_stage: int):
        """Move the inter-stage activation (array, or (a, b) pair for t5)
        onto the receiving submesh (ICI DMA on TPU)."""
        st = self.stages[to_stage]
        if self.is_t5:
            spec_a, spec_b = self._carry_specs(st, out=False)
            return jax.device_put(
                y, (NamedSharding(st.mesh, spec_a),
                    NamedSharding(st.mesh, spec_b)))
        spec = (st.shardings[0].act_spec() if st.shardings
                else st.vocab.act_spec())
        return jax.device_put(y, NamedSharding(st.mesh, spec))

    def _put_cotangent(self, dx, to_stage: int):
        """Place the activation cotangent onto the producing stage's submesh
        with that stage's OUTPUT specs."""
        st = self.stages[to_stage]
        if self.is_t5:
            spec_a, spec_b = self._carry_specs(st, out=True)
            return jax.device_put(
                dx, (NamedSharding(st.mesh, spec_a),
                     NamedSharding(st.mesh, spec_b)))
        spec = (st.shardings[-1].act_spec() if st.shardings
                else st.vocab.act_spec())
        return jax.device_put(dx, NamedSharding(st.mesh, spec))

    def _mb_rng(self, ctx, m: int, s: int):
        """Per-(microbatch, stage) dropout key — identical for the forward
        and the backward's remat recomputation of the same microbatch."""
        return jax.random.fold_in(jax.random.fold_in(ctx["rng"], m), s)

    def _fwd_microbatch(self, stage_params, mb, ctx, m):
        """Run one microbatch up to the head stage's input; the head's
        forward happens fused with its backward (value_and_grad), so the
        loss costs no extra pass."""
        x = self._put_stage0(mb)
        inputs = []
        extras = []
        n_stages = len(self.stages)
        for s in range(n_stages):
            inputs.append(x)
            extras.append(self._put_extras(mb, s))
            if s == n_stages - 1:
                lbl, msk = self._put_last(mb)
                ctx["labels"].append((lbl, msk))
                ctx["losses"].append(None)  # filled by the backward
            else:
                pos, seg = extras[s]
                rng = self._mb_rng(ctx, m, s)
                # per-stage XLA flops/bytes (cost/* gauges; the flag is
                # resolved once per step so steady state pays one bool)
                if self._record_costs:
                    maybe_record_jit_cost(f"pp/fwd_s{s}", self._fwd_jits[s],
                                          (stage_params[s], x, rng, pos, seg))
                # host span = dispatch cost; the TraceAnnotation inside
                # carries the stage name into captured XLA device traces
                with span(f"pp/fwd_s{s}"):
                    y = self._fwd_jits[s](stage_params[s], x, rng, pos, seg)
                    x = self._transfer(y, s + 1)
        ctx["inputs"].append(inputs)
        ctx["extras"].append(extras)

    def _bwd_microbatch(self, stage_params, m, w, ctx, grad_acc):
        """Backward for microbatch m seeded with its token weight."""
        inputs = ctx["inputs"][m]
        extras = ctx["extras"][m]
        lbl, msk = ctx["labels"][m]
        seed = jnp.asarray(w, jnp.float32)
        n_stages = len(self.stages)
        pos, seg = extras[-1]
        rng = self._mb_rng(ctx, m, n_stages - 1)
        if self._record_costs:
            maybe_record_jit_cost(
                f"pp/bwd_s{n_stages - 1}", self._bwd_jits[-1],
                (stage_params[-1], inputs[-1], lbl, msk, seed, rng, pos, seg))
        with span(f"pp/bwd_s{n_stages - 1}"):
            dp, dx, loss = self._bwd_jits[-1](
                stage_params[-1], inputs[-1], lbl, msk, seed, rng, pos, seg)
        count_kernels = self.mosaic_custom_calls is None
        if count_kernels:
            kernels = mosaic_custom_calls(
                self._bwd_jits[-1],
                (stage_params[-1], inputs[-1], lbl, msk, seed, rng, pos, seg))
        # keep loss/aux as lazy device scalars — any host sync here would
        # serialize the schedule; train_step folds them once at the end
        aux_parts = []
        grad_acc[-1] = _tree_add(grad_acc[-1], dp)
        for s in range(n_stages - 2, -1, -1):
            dy = self._put_cotangent(dx, s)
            pos, seg = extras[s]
            rng = self._mb_rng(ctx, m, s)
            if self._record_costs:
                maybe_record_jit_cost(
                    f"pp/bwd_s{s}", self._bwd_jits[s],
                    (stage_params[s], inputs[s], dy, seed, rng, pos, seg))
            with span(f"pp/bwd_s{s}"):
                dp, dx, aux = self._bwd_jits[s](
                    stage_params[s], inputs[s], dy, seed, rng, pos, seg)
            if count_kernels:
                kernels += mosaic_custom_calls(
                    self._bwd_jits[s],
                    (stage_params[s], inputs[s], dy, seed, rng, pos, seg))
            if self.cfg.num_experts:
                aux_parts.append(aux)
            grad_acc[s] = _tree_add(grad_acc[s], dp)
        if count_kernels:
            self.mosaic_custom_calls = kernels
        ctx["losses"][m] = loss
        ctx["aux"][m] = aux_parts
        # free stored activations for this microbatch (1F1B memory bound)
        ctx["inputs"][m] = None
        ctx["extras"][m] = None

    def train_step(
        self,
        stage_params: List[Params],
        stage_opts: List[Any],
        batch: Dict[str, np.ndarray],
        num_microbatches: Optional[int] = None,
    ) -> Tuple[List[Params], List[Any], Dict[str, float]]:
        """One optimizer step under the configured schedule.
        ``num_microbatches`` overrides the plan's chunk count (batch-size
        ramp at fixed micro size — the stage jits see the same shapes, so a
        ramp costs zero recompiles here)."""
        batch = dict(batch)
        # per-step dropout key (popped BEFORE microbatch slicing: it is
        # per-step data, not a [B, ...] array). With dropout rates at 0 the
        # key is dead code at trace time, so a constant placeholder is free —
        # but a dropout-ENABLED cfg must get a fresh key per step, else every
        # step reuses identical masks (matching parallel/spmd.py's refusal).
        step_rng = batch.pop("dropout_rng", None)
        if step_rng is None:
            if (self.cfg.hidden_dropout > 0.0
                    or self.cfg.attention_dropout > 0.0):
                raise ValueError(
                    "cfg enables dropout but the batch has no 'dropout_rng' "
                    "key; cli/train_dist.py adds it automatically — manual "
                    "callers must pass one per step")
            step_rng = jax.random.key(0)
        # resolve the one-shot cost/* recording ONCE per step: the inner
        # microbatch loops then pay a single attribute read, never a
        # registry lookup (a sink attached later still records on its
        # first step because the done flag only flips after a live one)
        self._record_costs = (not self._jit_cost_done
                              and bool(get_registry().sinks))
        mbs, weights = self._microbatches(batch, num_microbatches)
        mcount = len(mbs)
        ctx = {"inputs": [], "extras": [], "labels": [], "losses": [],
               "aux": [[] for _ in range(mcount)], "rng": step_rng}
        grad_acc: List[Any] = [None] * len(self.stages)

        if self.hpc.pipeline_type == "gpipe":
            # all forwards, then all backwards (pipeline.py:729-905)
            for m in range(mcount):
                self._fwd_microbatch(stage_params, mbs[m], ctx, m)
            for m in range(mcount):
                self._bwd_microbatch(stage_params, m, weights[m], ctx,
                                     grad_acc)
        else:
            # pipedream-flush / 1F1B (pipeline.py:386-712): warmup forwards,
            # then alternate 1 fwd / 1 bwd, then cooldown backwards. With a
            # single controller the warmup depth is the pipeline depth —
            # in chunks, so interleaved runs keep every group fed.
            warmup = min(len(self.stages), mcount)
            for m in range(warmup):
                self._fwd_microbatch(stage_params, mbs[m], ctx, m)
            next_fwd, next_bwd = warmup, 0
            while next_bwd < mcount:
                self._bwd_microbatch(stage_params, next_bwd,
                                     weights[next_bwd], ctx, grad_acc)
                next_bwd += 1
                if next_fwd < mcount:
                    self._fwd_microbatch(stage_params, mbs[next_fwd], ctx,
                                         next_fwd)
                    next_fwd += 1

        # tied-embedding grad sum across first/last stages (pipeline.py:1042);
        # transposes run jitted on the owning submesh and the sum crosses
        # stages as a device-to-device sharded transfer (ICI on TPU)
        if self.cfg.tie_word_embeddings:
            g_wte = grad_acc[0]["embed"]["wte"]
            g_head = grad_acc[-1]["head"]["whead"]
            g_head_t = jax.device_put(
                self._transpose_jit(g_head),
                NamedSharding(self.stages[0].mesh,
                              self.stages[0].vocab.param_spec(
                                  ("vocab", "embed"))))
            total = g_wte + g_head_t
            grad_acc[0]["embed"]["wte"] = total
            grad_acc[-1]["head"]["whead"] = jax.device_put(
                self._transpose_jit(total),
                NamedSharding(self.stages[-1].mesh,
                              self.stages[-1].vocab.param_spec(
                                  ("embed", "vocab"))))

        # global grad-norm clip across stages — kept ON DEVICE (ADVICE r2):
        # per-stage squared norms fold on stage 0's mesh as replicated
        # scalars, the clip scale is computed there and re-broadcast to each
        # submesh, so no host sync lands between backward and the updates
        rep0 = NamedSharding(self.stages[0].mesh, P())
        sq_parts = [self._gnorm_jit(g) for g in grad_acc]
        total_sq = sq_parts[0]
        for part in sq_parts[1:]:
            total_sq = total_sq + jax.device_put(part, rep0)
        # tied copies are double-counted: subtract one copy
        if self.cfg.tie_word_embeddings:
            total_sq = total_sq - jax.device_put(
                self._gnorm_jit(grad_acc[-1]["head"]["whead"]), rep0)
        gnorm_dev, scale_dev = self._clip_jit(total_sq)

        new_params, new_opts = [], []
        with span("pp/update"):
            for s in range(len(self.stages)):
                scale_s = (scale_dev if s == 0 else jax.device_put(
                    scale_dev, NamedSharding(self.stages[s].mesh, P())))
                p, o = self._update_jits[s](stage_params[s], stage_opts[s],
                                            grad_acc[s], scale_s)
                new_params.append(p)
                new_opts.append(o)
        # single host sync at the very end (all device work already queued)
        loss = sum(float(w) * (float(l) + sum(float(a) for a in aux))
                   for w, l, aux in zip(weights, ctx["losses"], ctx["aux"]))
        if self._record_costs:
            # every per-stage program this step touched is now recorded;
            # later steps skip the registry entirely
            self._jit_cost_done = True
            self._record_costs = False
        return new_params, new_opts, {"loss": loss,
                                      "grad_norm": float(gnorm_dev)}


def _tree_add(a, b):
    if a is None:
        return b
    return jax.tree.map(lambda x, y: x + y, a, b)
