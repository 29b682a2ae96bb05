"""Serving engine: jitted paged prefill/decode + continuous batching loop.

A small set of programs, compiled once each (prefill once per length
bucket), drives all traffic:

* **prefill** — one request's (right-padded, bucketed) prompt through the
  stack with the same attention math as offline ``models/generate.prefill``,
  k/v written straight into the request's pool blocks, first token sampled
  from the last real position's logits.
* **prefix prefill** (``serving.prefix_cache`` on) — the same, but only
  over the UNCACHED suffix of a prompt whose block-aligned prefix the
  radix cache (``prefix_cache.py``) already holds: queries are the suffix
  bucket, keys are the gathered full block table, and the cached-prefix
  FLOPs are simply never spent. A fully-cached prompt dispatches no
  prefill at all — its slot enters at ``pos = len-1`` and the next decode
  step produces the first token (bit-identical: the decode program's
  single-row math equals the prefill row's).
* **decode** — one token for every slot at a FIXED batch shape
  ``[max_batch_size]``: per-slot positions, per-slot block tables, per-slot
  sampling params. Retired slots alias the scratch block and their outputs
  are discarded, so admission/retirement never changes the compiled shape —
  steady state runs with zero recompiles (``compile_count()`` lets tests
  pin this).
* **verify** (``serving.spec_decode`` on) — the speculative window: every
  slot's ``[last_token, draft_1..draft_K]`` through the stack at one fixed
  ``[max_batch_size, K+1]`` shape (``kv_cache.paged_sdpa_window`` masks
  row j at position pos+j), returning the target model's choice after
  every drafted token. Greedy acceptance keeps the stream bit-identical
  to plain decode while emitting up to K+1 tokens per step
  (``spec_decode.py`` holds the draft providers + acceptance rule).

Plan-aware SPMD: given a mesh + :class:`HybridParallelConfig`, params are
sharded by the plan's PartitionSpecs (``parallel/spmd.py``) and the KV pool's
kv-head axis rides each layer's attention tp axes (``kv_cache.pool_pspecs``)
— the searched plan picks the decode-time sharding just as it picks the
train-time one. Without a mesh the same programs jit on one device.

Determinism contract: a request's token stream depends only on (params,
prompt, its own sampling seed/temperature) — greedy rows are argmax rows and
sampled rows fold the request seed with the emitted-token index — never on
which neighbors share the batch. The continuous-batching drill pins stream
equality against offline ``generate()``.

Host/device cadence: every step syncs the sampled tokens to the host (they
feed the streams and the retirement logic). Decode steps are latency-bound
anyway; the sync is the product, not overhead.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import ModelArgs, ServingArgs
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.observability.events import EventStream
from hetu_galvatron_tpu.observability.recorder import FlightRecorder
from hetu_galvatron_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
)
from hetu_galvatron_tpu.observability.trace_analysis import (
    maybe_record_jit_cost,
)
from hetu_galvatron_tpu.serving.kv_cache import (
    SCRATCH_BLOCK,
    PagedKVCache,
    copy_block,
    gather_pages,
    paged_sdpa,
    paged_sdpa_window,
    scatter_prefill,
    resolve_num_blocks,
    scatter_token,
    scatter_window,
)
from hetu_galvatron_tpu.serving.prefix_cache import PrefixCache
from hetu_galvatron_tpu.serving.scheduler import (
    Request,
    RequestHandle,
    Scheduler,
    Slot,
)
from hetu_galvatron_tpu.serving.spec_decode import accept_length, make_draft

Params = Dict[str, Any]


class WeightSwapError(ValueError):
    """``swap_weights`` rejected the new checkpoint: its tree structure,
    shapes, or dtypes differ from the serving model's — a hot swap may
    only replace VALUES (same architecture), never recompile programs
    mid-traffic."""


def _check_supported(cfg: ModelArgs, params: Params) -> None:
    from hetu_galvatron_tpu.analysis.eligibility import tower_reason

    if cfg.post_norm or cfg.model_type in ("bert", "t5"):
        raise NotImplementedError(
            "ServingEngine serves dense causal decoder families; bert/t5 "
            "have no paged decode path")
    reason = tower_reason(cfg, "ServingEngine")
    if reason is not None:
        raise NotImplementedError(reason)
    if any("moe" in lp for lp in params["layers"]):
        raise NotImplementedError("ServingEngine: dense layers only")
    from hetu_galvatron_tpu.analysis.eligibility import (
        mixed_stack_reason,
        own_multipliers_reason,
        residual_streams_reason,
    )

    reason = mixed_stack_reason(
        cfg, "ServingEngine (paged key-value blocks for every layer, no "
        "convolution state and no state-space state)"
    ) or own_multipliers_reason(
        cfg, "ServingEngine (its paged attention cores)"
    ) or residual_streams_reason(cfg, "ServingEngine")
    if reason is not None:
        raise NotImplementedError(reason)


def default_buckets(block_size: int, cap_tokens: int) -> List[int]:
    """Every prefill bucket ``bucket_length`` can produce: the power-of-two
    ladder plus the capped (possibly non-power-of-two) top bucket — warmup
    must cover the cap too or the first long prompt recompiles
    mid-serving."""
    out = []
    b = block_size
    while b < cap_tokens:
        out.append(b)
        b *= 2
    out.append(cap_tokens)
    return out


def _make_sampler(cfg: ModelArgs, top_k: Optional[int]):
    """[S, V] logits -> [S] tokens. Greedy rows (temp <= 0) take the
    argmax; sampling rows draw categorical from a per-request key
    (fold_in(seed, emitted-token index)) so a request's stream is
    batch-composition invariant. Vocab-padding columns are never produced
    (mirrors ``models/generate._sample_pick``)."""
    valid = jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size
    neg = jnp.float32(jnp.finfo(jnp.float32).min)

    def sample(logits, temps, seeds, gen_idx):
        logits = jnp.where(valid, logits.astype(jnp.float32), neg)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def one(row, t, s, g):
            key = jax.random.fold_in(jax.random.key(s), g)
            ll = row / jnp.maximum(t, jnp.float32(1e-6))
            if top_k:
                kth = jax.lax.top_k(ll, top_k)[0][-1]
                ll = jnp.where(ll < kth, neg, ll)
            return jax.random.categorical(key, ll).astype(jnp.int32)

        sampled = jax.vmap(one)(logits, temps.astype(jnp.float32),
                                seeds, gen_idx)
        return jnp.where(temps <= 0.0, greedy, sampled)

    return sample


class ServingEngine:
    """Continuous-batching inference over a loaded checkpoint + plan.

    ``params`` is the (host or sharded) params tree from
    ``models/builder.init_causal_lm`` / checkpoint restore; with
    ``mesh``/``hpc``/``axes_tree`` the engine places it under the plan's
    GSPMD shardings itself. ``submit()`` returns a
    :class:`~hetu_galvatron_tpu.serving.scheduler.RequestHandle` streaming
    tokens; drive the loop with :meth:`step` / :meth:`run_until_idle`, or
    :meth:`start` a background thread.
    """

    def __init__(
        self,
        params: Params,
        cfg: ModelArgs,
        serving: Optional[ServingArgs] = None,
        *,
        mesh=None,
        hpc=None,
        axes_tree: Optional[Params] = None,
        registry: Optional[MetricsRegistry] = None,
        compute_dtype=jnp.bfloat16,
        kv_dtype=None,
        draft_params: Optional[Params] = None,
        draft_cfg: Optional[ModelArgs] = None,
    ):
        serving = serving if serving is not None else ServingArgs()
        _check_supported(cfg, params)
        if mesh is not None and (hpc is None or axes_tree is None):
            raise ValueError("mesh serving needs hpc + axes_tree (the plan "
                             "and the params' logical axes)")
        self.cfg = cfg
        self.serving = serving
        self.mesh = mesh
        self.registry = registry if registry is not None else get_registry()
        self.compute_dtype = compute_dtype
        self.S = int(serving.max_batch_size)

        max_seq_len = serving.max_seq_len or cfg.max_position_embeddings
        # pool sizing is shared with the static memory doctor
        # (kv_cache.resolve_num_blocks), so `check --memory --serving`
        # predicts exactly the pool this engine allocates
        num_blocks = resolve_num_blocks(serving, cfg)

        layer_shards = None
        self._pspecs = None
        if mesh is not None:
            from hetu_galvatron_tpu.parallel.spmd import (
                layer_shardings,
                param_specs,
                shard_params,
            )

            if hpc.pp_deg != 1:
                raise ValueError("ServingEngine is the pp=1 decode path")
            per_layer_all, vocab_sh = layer_shardings(hpc, mesh)
            layer_shards = per_layer_all[hpc.num_encoder_layers:]
            self._pspecs = param_specs(axes_tree, layer_shards, vocab_sh)
            params = shard_params(params, self._pspecs, mesh)
        self.params = params

        self.kv = PagedKVCache(
            cfg, num_blocks=num_blocks, block_size=serving.kv_block_size,
            max_seq_len=max_seq_len,
            dtype=kv_dtype if kv_dtype is not None else compute_dtype,
            mesh=mesh, layer_shardings=layer_shards)
        from hetu_galvatron_tpu.core.cost_model.cost import (
            model_flops_per_token,
        )

        self.prefix: Optional[PrefixCache] = None
        if serving.prefix_cache:
            self.prefix = PrefixCache(
                self.kv.allocator, self.kv.block_size,
                max_blocks=serving.prefix_cache_max_blocks)
        # request-lifecycle tracing (observability/events.py): the sink
        # stream is gated on serving.trace_requests (zero JSONL growth by
        # default). The flight recorder taps the stream whenever its ring
        # can matter — tracing on, or a dump directory configured — so
        # crash dumps carry last-N-events context; with BOTH off, no tap
        # is attached and emit() is a single attribute check per event
        # (the default serving path pays nothing per token)
        self.events = EventStream(self.registry,
                                  enabled=serving.trace_requests)
        self.recorder = FlightRecorder(
            registry=self.registry, out_dir=serving.flight_dir,
            capacity=serving.flight_events)
        if serving.trace_requests or serving.flight_dir:
            self.recorder.attach(self.events)
        self.scheduler = Scheduler(
            self.kv, max_slots=self.S,
            max_position_embeddings=cfg.max_position_embeddings,
            prefill_flops_budget=serving.prefill_flops_budget_g * 1e9,
            # cost-model FLOPs are fwd+bwd (bwd counted 2x); prefill is
            # forward-only
            flops_per_token=model_flops_per_token(cfg) / 3.0,
            max_prefill_tokens=serving.max_prefill_tokens,
            prefix_cache=self.prefix, events=self.events)

        # rope/position tables cover every storable position
        self._table_len = self.kv.max_blocks_per_seq * self.kv.block_size
        self._rope = None
        if cfg.position_embedding_type == "rope":
            self._rope = M.rope_cos_sin(self._table_len, cfg.head_dim,
                                        cfg.rope_theta,
                                        scaling=cfg.rope_scaling)
        self._sample = _make_sampler(cfg, serving.top_k)
        self._decode_fn = self._build_decode()
        self._prefill_fns: Dict[int, Callable] = {}
        self._prefix_fns: Dict[int, Callable] = {}
        self._cow_fn: Optional[Callable] = None
        # speculative decoding: draft provider + the [S, K+1] verify
        # program (None when serving.spec_decode is off)
        if serving.spec_decode and serving.spec_k < 1:
            raise ValueError(f"serving.spec_k must be >= 1, "
                             f"got {serving.spec_k}")
        self._draft = make_draft(serving, draft_params=draft_params,
                                 draft_cfg=draft_cfg)
        self._verify_fn = (self._build_verify()
                           if self._draft is not None else None)
        self._drafted_total = 0
        self._accepted_total = 0

        # Prometheus /metrics endpoint (serving.metrics_port): off unless
        # asked for; port 0 binds ephemeral and .metrics_port reports it
        self.metrics_server = None
        self.metrics_port: Optional[int] = None
        if serving.metrics_port is not None:
            from hetu_galvatron_tpu.observability.prometheus import (
                MetricsHTTPServer,
            )

            self.metrics_server = MetricsHTTPServer(
                self.registry, port=int(serving.metrics_port),
                host=serving.metrics_host)
            self.metrics_port = self.metrics_server.start()

        # SLO attainment accounting (serving.slo_ttft_ms / slo_itl_ms):
        # plain host-side counts; flush() exports the attainment gauges
        self._ttft_n = self._ttft_ok = 0
        self._itl_n = self._itl_ok = 0

        self._lock = threading.RLock()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._steps = 0
        self._emitted_window: List[tuple] = []  # (t, cumulative tokens)
        self._emitted_total = 0
        self._closed = False
        self.error: Optional[BaseException] = None  # fatal thread error

    # -- jitted programs ----------------------------------------------------

    def _shd(self, spec):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, spec)

    def _pool_shardings(self):
        return [{"k": self._shd(s), "v": self._shd(s)}
                for s in self.kv.pspecs]

    def _jit(self, fn, n_extra: int):
        """jit with pools donated (arg 1); under a mesh, params/pools keep
        their plan shardings and every batch array is replicated. Both
        programs return (pools, tokens)."""
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=(1,))
        from jax.sharding import PartitionSpec as P

        rep = self._shd(P())
        nshd = jax.tree.map(self._shd, self._pspecs,
                            is_leaf=lambda x: isinstance(x, P))
        pools = self._pool_shardings()
        return jax.jit(
            fn,
            in_shardings=(nshd, pools) + (rep,) * n_extra,
            out_shardings=(pools, rep),
            donate_argnums=(1,),
        )

    def _layer_stack(self, params, pools, x, rope, sdpa_for):
        """Shared decoder-stack walk for prefill and decode: layer i runs
        with an sdpa closure that updates/reads pools[i]."""
        cfg = self.cfg
        new_pools = list(pools)
        for i, lp in enumerate(params["layers"]):
            cell: Dict[str, jax.Array] = {}
            sdpa = sdpa_for(i, new_pools, cell)
            x = M.apply_decoder_layer(lp, x, cfg, rope=rope,
                                      ops=M.LayerOps(sdpa=sdpa),
                                      compute_dtype=self.compute_dtype)
            new_pools[i] = {"k": cell["k"], "v": cell["v"]}
        x = M.apply_norm(params["prenorm"], x, cfg)
        logits = M.apply_lm_head(params["head"], x, cfg,
                                 wte=params["embed"]["wte"],
                                 compute_dtype=self.compute_dtype)
        return new_pools, logits

    def _build_prefill(self, bucket: int):
        """(params, pools, tokens [1, bucket], table [bucket//bs],
        true_len, temp, seed) -> (pools, first_token). Causal attention
        over the right-padded prompt — pad rows never influence rows
        < true_len — with k/v scattered into the slot's blocks."""
        cfg = self.cfg
        maxpos = cfg.max_position_embeddings

        def fn(params, pools, tokens, table, true_len, temp, seed):
            rope = None
            if self._rope is not None:
                rope = (self._rope[0][:bucket], self._rope[1][:bucket])
            pos_ids = None
            if "wpe" in params["embed"]:
                pos_ids = jnp.minimum(jnp.arange(bucket), maxpos - 1)[None]
            x = M.apply_embedding(params["embed"], tokens, cfg,
                                  compute_dtype=self.compute_dtype,
                                  position_ids=pos_ids)

            def sdpa_for(i, new_pools, cell):
                def sdpa(q, k, v, *, causal=True):
                    cell["k"] = scatter_prefill(new_pools[i]["k"], k[0],
                                                table)
                    cell["v"] = scatter_prefill(new_pools[i]["v"], v[0],
                                                table)
                    return M.xla_sdpa(q, k, v, causal=causal)

                return sdpa

            new_pools, logits = self._layer_stack(params, pools, x, rope,
                                                  sdpa_for)
            last = jax.lax.dynamic_slice_in_dim(
                logits[0], true_len - 1, 1, axis=0)  # [1, V]
            tok = self._sample(
                last, jnp.asarray([temp], jnp.float32),
                jnp.asarray([seed], jnp.int32),
                jnp.zeros((1,), jnp.int32))
            return new_pools, tok[0]

        return self._jit(fn, n_extra=5)

    def _build_decode(self):
        """(params, pools, tokens [S], pos [S], tables [S, MB], temps [S],
        seeds [S], gen_idx [S]) -> (pools, next_tokens [S]). One fixed
        shape for any mix of live/retired lanes."""
        from hetu_galvatron_tpu.models.generate import _embed_at

        cfg = self.cfg
        S = self.S
        bs = self.kv.block_size

        def fn(params, pools, tokens, pos, tables, temps, seeds, gen_idx):
            # per-lane positions: the offline decode-step embedding with a
            # zero shift vector (scheduler admission guarantees pos stays
            # inside max_position_embeddings; parked lanes sit at 0)
            x = _embed_at(params["embed"], tokens, pos, cfg,
                          self.compute_dtype, shift=jnp.zeros_like(pos))
            rope = None
            if self._rope is not None:
                rope = (self._rope[0][pos][:, None],
                        self._rope[1][pos][:, None])
            blks = tables[jnp.arange(S), pos // bs]
            offs = pos % bs

            def sdpa_for(i, new_pools, cell):
                def sdpa(q, k, v, *, causal=True):
                    pk = scatter_token(new_pools[i]["k"], k[:, 0], blks, offs)
                    pv = scatter_token(new_pools[i]["v"], v[:, 0], blks, offs)
                    cell["k"], cell["v"] = pk, pv
                    ck = gather_pages(pk, tables)
                    cv = gather_pages(pv, tables)
                    return paged_sdpa(q, ck, cv, pos)

                return sdpa

            new_pools, logits = self._layer_stack(params, pools, x, rope,
                                                  sdpa_for)
            toks = self._sample(logits[:, 0], temps, seeds, gen_idx)
            return new_pools, toks

        return self._jit(fn, n_extra=6)

    def _build_prefix_prefill(self, bucket: int):
        """(params, pools, tokens [1, bucket], full_table [MB], ctx,
        true_len, temp, seed) -> (pools, first_token). The shared-prefix
        suffix prefill: queries are the UNCACHED suffix tokens at absolute
        positions ctx..ctx+bucket-1; keys are the slot's whole assembled
        page table (the cached prefix + the suffix being written), masked
        per row — bit-identical to having prefilled the whole prompt
        (``paged_sdpa_window`` mirrors the decode/prefill arithmetic).
        Pad lanes past the per-sequence table capacity write to scratch
        (a pow-of-two bucket may overshoot the capacity a deep prefix
        leaves)."""
        cfg = self.cfg
        maxpos = cfg.max_position_embeddings
        bs = self.kv.block_size
        MB = self.kv.max_blocks_per_seq

        def fn(params, pools, tokens, table, ctx, true_len, temp, seed):
            rope = None
            if self._rope is not None:
                rope = (
                    jax.lax.dynamic_slice_in_dim(self._rope[0], ctx, bucket),
                    jax.lax.dynamic_slice_in_dim(self._rope[1], ctx, bucket))
            pos_ids = None
            if "wpe" in params["embed"]:
                pos_ids = jnp.minimum(ctx + jnp.arange(bucket),
                                      maxpos - 1)[None]
            x = M.apply_embedding(params["embed"], tokens, cfg,
                                  compute_dtype=self.compute_dtype,
                                  position_ids=pos_ids)
            idx = ctx // bs + jnp.arange(bucket // bs)
            sblocks = jnp.where(idx < MB, table[jnp.minimum(idx, MB - 1)],
                                SCRATCH_BLOCK)

            def sdpa_for(i, new_pools, cell):
                def sdpa(q, k, v, *, causal=True):
                    pk = scatter_prefill(new_pools[i]["k"], k[0], sblocks)
                    pv = scatter_prefill(new_pools[i]["v"], v[0], sblocks)
                    cell["k"], cell["v"] = pk, pv
                    ck = gather_pages(pk, table[None])
                    cv = gather_pages(pv, table[None])
                    return paged_sdpa_window(q, ck, cv, ctx)

                return sdpa

            new_pools, logits = self._layer_stack(params, pools, x, rope,
                                                  sdpa_for)
            last = jax.lax.dynamic_slice_in_dim(
                logits[0], true_len - 1, 1, axis=0)  # [1, V]
            tok = self._sample(
                last, jnp.asarray([temp], jnp.float32),
                jnp.asarray([seed], jnp.int32),
                jnp.zeros((1,), jnp.int32))
            return new_pools, tok[0]

        return self._jit(fn, n_extra=6)

    def _build_verify(self):
        """(params, pools, tokens [S, K+1], pos [S], tables [S, MB],
        temps [S], seeds [S], gen_idx [S], limit [S]) -> (pools,
        targets [S, K+1]). The speculative window: lane s's tokens are
        [last_token, draft_1..draft_K] at positions pos..pos+K; row j's
        target is what the model emits AFTER seeing the drafts before j —
        the same arithmetic as j+1 sequential decode steps. Writes past a
        lane's position budget (``limit``) land on the scratch block;
        rejected drafts leave garbage k/v beyond the accepted point that
        the position mask hides until a later step overwrites it (the
        standard retired-lane contract). The [S, K+1] embedding below
        mirrors ``models/generate._embed_at`` (same op order: wte gather,
        wpe add, embedding norm, gemma scale, cast)."""
        cfg = self.cfg
        S = self.S
        K1 = int(self.serving.spec_k) + 1
        bs = self.kv.block_size
        tl = self._table_len
        maxpos = cfg.max_position_embeddings

        def fn(params, pools, tokens, pos, tables, temps, seeds, gen_idx,
               limit):
            p_j = pos[:, None] + jnp.arange(K1)[None, :]  # [S, K1] abs pos
            pc = jnp.minimum(p_j, tl - 1)
            x = jnp.take(params["embed"]["wte"], tokens, axis=0)
            if "wpe" in params["embed"]:
                x = x + jnp.take(params["embed"]["wpe"],
                                 jnp.minimum(p_j, maxpos - 1), axis=0)
            if "ln" in params["embed"]:
                x = M.apply_norm(params["embed"]["ln"], x, cfg)
            if cfg.scale_embeddings:
                x = x * jnp.sqrt(
                    jnp.float32(cfg.hidden_size)).astype(x.dtype)
            x = x.astype(self.compute_dtype)
            rope = None
            if self._rope is not None:
                rope = (self._rope[0][pc], self._rope[1][pc])
            write_ok = p_j <= limit[:, None]
            blks = jnp.where(
                write_ok, tables[jnp.arange(S)[:, None], pc // bs],
                SCRATCH_BLOCK)
            offs = pc % bs

            def sdpa_for(i, new_pools, cell):
                def sdpa(q, k, v, *, causal=True):
                    pk = scatter_window(new_pools[i]["k"], k, blks, offs)
                    pv = scatter_window(new_pools[i]["v"], v, blks, offs)
                    cell["k"], cell["v"] = pk, pv
                    ck = gather_pages(pk, tables)
                    cv = gather_pages(pv, tables)
                    return paged_sdpa_window(q, ck, cv, pos)

                return sdpa

            new_pools, logits = self._layer_stack(params, pools, x, rope,
                                                  sdpa_for)
            outs = [self._sample(logits[:, j], temps, seeds, gen_idx + j)
                    for j in range(K1)]
            return new_pools, jnp.stack(outs, axis=1)

        return self._jit(fn, n_extra=7)

    def _build_cow(self):
        """(params, pools, src, dst) -> (pools, 0): duplicate one block in
        every layer's k/v pool — the copy-on-write a fully-cached prompt
        needs before its bootstrap decode step rewrites the last prompt
        position (which lives in a SHARED block)."""

        def fn(params, pools, src, dst):
            out = [{"k": copy_block(pl["k"], src, dst),
                    "v": copy_block(pl["v"], src, dst)} for pl in pools]
            return out, jnp.zeros((), jnp.int32)

        return self._jit(fn, n_extra=2)

    def compile_count(self) -> int:
        """Total compiled-program count across decode/verify/copy-block +
        prefill and prefix-prefill buckets (tests pin this flat across
        steady state)."""
        fns = ([self._decode_fn] + list(self._prefill_fns.values())
               + list(self._prefix_fns.values()))
        if self._verify_fn is not None:
            fns.append(self._verify_fn)
        if self._cow_fn is not None:
            fns.append(self._cow_fn)
        return sum(f._cache_size() for f in fns)

    def step_jaxprs(self, bucket: Optional[int] = None) -> Dict[str, Any]:
        """ClosedJaxprs of every program family in the token-latency path
        — decode, one prefill bucket, and (when enabled) the
        prefix-prefill bucket and the speculative verify window — the
        static-analysis hook (``analysis/census.py`` censuses them for
        host callbacks / unmarked collectives). Tracing only: nothing
        executes, the donated pools are untouched, and the traced programs
        land in the normal jit caches."""
        if bucket is None:
            bucket = default_buckets(self.kv.block_size, self._table_len)[0]
        prefill = self._prefill_for(bucket)
        table = np.zeros((bucket // self.kv.block_size,), np.int32)
        pre_args = (self.params, self.kv.pools,
                    jnp.zeros((1, bucket), jnp.int32), jnp.asarray(table),
                    1, 0.0, 0)
        state = self.scheduler.decode_state()
        dec_args = (self.params, self.kv.pools,
                    jnp.asarray(state["tokens"], jnp.int32),
                    jnp.asarray(state["pos"], jnp.int32),
                    jnp.asarray(state["tables"], jnp.int32),
                    jnp.asarray(state["temps"], jnp.float32),
                    jnp.asarray(state["seeds"], jnp.int32),
                    jnp.asarray(state["gen_idx"], jnp.int32))
        out = {f"prefill_{bucket}": jax.make_jaxpr(prefill)(*pre_args),
               "decode": jax.make_jaxpr(self._decode_fn)(*dec_args)}
        if self.prefix is not None:
            fnp = self._prefix_prefill_for(bucket)
            full = jnp.zeros((self.kv.max_blocks_per_seq,), jnp.int32)
            ppre_args = (self.params, self.kv.pools,
                         jnp.zeros((1, bucket), jnp.int32), full, 0, 1,
                         0.0, 0)
            out[f"prefix_prefill_{bucket}"] = \
                jax.make_jaxpr(fnp)(*ppre_args)
        if self._verify_fn is not None:
            K1 = int(self.serving.spec_k) + 1
            ver_args = (self.params, self.kv.pools,
                        jnp.zeros((self.S, K1), jnp.int32),
                        jnp.asarray(state["pos"], jnp.int32),
                        jnp.asarray(state["tables"], jnp.int32),
                        jnp.asarray(state["temps"], jnp.float32),
                        jnp.asarray(state["seeds"], jnp.int32),
                        jnp.asarray(state["gen_idx"], jnp.int32),
                        jnp.asarray(state["limit"], jnp.int32))
            out["verify"] = jax.make_jaxpr(self._verify_fn)(*ver_args)
        return out

    def warmup(self, buckets: Optional[List[int]] = None) -> None:
        """Pre-compile every program traffic can reach — the decode (or,
        under spec decode, verify) step, the given prefill buckets
        (defaults to every power-of-two bucket up to the pool's
        per-sequence capacity), their prefix-prefill twins, and the
        copy-on-write block duplicator — so steady state never compiles.
        Dummy runs write only the scratch block, so a warm engine is
        still empty."""
        if buckets is None:
            buckets = default_buckets(self.kv.block_size, self._table_len)
        for b in buckets:
            fn = self._prefill_for(b)
            table = np.zeros((b // self.kv.block_size,), np.int32)
            args = (self.params, self.kv.pools,
                    jnp.zeros((1, b), jnp.int32),
                    jnp.asarray(table), 1, 0.0, 0)
            # record the bucket's XLA flops/bytes here, off the request
            # path: the one-shot lower() is a full retrace, and TTFT must
            # never pay it (BEFORE the call — the program donates pools)
            maybe_record_jit_cost(f"serve/prefill_{b}", fn, args,
                                  registry=self.registry)
            new_pools, tok = fn(*args)
            self.kv.pools = new_pools
            jax.block_until_ready(tok)
            if self.prefix is not None:
                fnp = self._prefix_prefill_for(b)
                full = jnp.zeros((self.kv.max_blocks_per_seq,), jnp.int32)
                pargs = (self.params, self.kv.pools,
                         jnp.zeros((1, b), jnp.int32), full, 0, 1, 0.0, 0)
                maybe_record_jit_cost(f"serve/prefix_prefill_{b}", fnp,
                                      pargs, registry=self.registry)
                new_pools, tok = fnp(*pargs)
                self.kv.pools = new_pools
                jax.block_until_ready(tok)
        if self.prefix is not None:
            self._cow_copy(SCRATCH_BLOCK, SCRATCH_BLOCK)
        state = self.scheduler.decode_state()
        if self._draft is not None:
            # both step programs: verify drives greedy lanes; a step
            # whose live lanes are ALL sampled (which never speculate)
            # falls back to the cheaper plain decode
            drafted = [[0] * int(self.serving.spec_k)
                       for _ in range(self.S)]
            toks = self._run_decode(state, drafted=drafted)
            del toks
        toks = self._run_decode(state)
        del toks

    # -- zero-downtime weight swap ------------------------------------------

    def swap_weights(self, new_params: Params) -> float:
        """Hot-swap the serving checkpoint without dropping a request.

        Double-buffered: the new tree is validated (same structure,
        shapes, dtypes — :class:`WeightSwapError` otherwise), staged onto
        the devices under the engine's existing shardings, and fully
        materialized OFF the serving lock, so for a moment both
        checkpoints are resident (the HBM headroom a swap needs). Only
        the pointer flip and the prefix-cache invalidation hold the lock
        — the TTFT/ITL blip is bounded by one in-flight engine step plus
        that flip, and is reported as ``serve/swap_stall_ms``.

        Contract mid-swap: in-flight requests keep their KV (computed
        under the old weights) and finish decoding under the new ones —
        the standard mixed-context rollout semantics; nothing is dropped
        or recomputed. Requests admitted after the swap run entirely
        under the new checkpoint and bit-match a cold engine serving it:
        the radix prefix cache is invalidated at the flip (old-weight k/v
        must never splice into new-weight prefills), and the jitted
        programs are untouched — same shapes, same shardings, zero
        recompiles. Returns the lock-held stall in milliseconds."""
        def sig(t):
            return (tuple(t.shape), jnp.result_type(t))

        try:
            mismatch = jax.tree.map(
                lambda old, new: sig(old) != sig(new),
                self.params, new_params)
        except (ValueError, TypeError, KeyError) as e:
            raise WeightSwapError(
                f"new checkpoint's tree structure differs from the "
                f"serving model's: {e}") from e
        if any(jax.tree.leaves(mismatch)):
            raise WeightSwapError(
                "new checkpoint's shapes/dtypes differ from the serving "
                "model's — a hot swap may only replace values; start a "
                "new engine for a different architecture")
        # stage OFF-lock: place under the plan shardings (or on-device)
        # and block until materialized, so the lock-held flip is a
        # pointer move, never a transfer
        if self.mesh is not None:
            from hetu_galvatron_tpu.parallel.spmd import shard_params

            staged = shard_params(new_params, self._pspecs, self.mesh)
        else:
            staged = jax.tree.map(jnp.asarray, new_params)
        jax.block_until_ready(staged)
        t0 = time.perf_counter()
        with self._lock:
            self.params = staged
            dropped = 0
            if self.prefix is not None:
                dropped = self.prefix.invalidate()
            stall_ms = (time.perf_counter() - t0) * 1000.0
            self.registry.counter("serve/weight_swaps").inc()
            self.registry.histogram("serve/swap_stall_ms").observe(stall_ms)
            self.events.emit("weight_swap", stall_ms=stall_ms,
                             prefix_blocks_dropped=dropped)
        return stall_ms

    # -- the serving loop ---------------------------------------------------

    def submit(
        self,
        tokens: List[int],
        *,
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        eos_id: Optional[int] = "default",
        seed: int = 0,
        timeout_s: Optional[float] = None,
    ) -> RequestHandle:
        s = self.serving
        req = Request(
            tokens=[int(t) for t in tokens],
            max_new_tokens=int(max_new_tokens if max_new_tokens is not None
                               else s.max_new_tokens),
            temperature=float(temperature if temperature is not None
                              else s.temperature),
            eos_id=s.eos_id if eos_id == "default" else eos_id,
            seed=int(seed),
            timeout_s=float(timeout_s if timeout_s is not None
                            else s.request_timeout_s),
        )
        with self._lock:
            self.registry.counter("serve/requests_submitted").inc()
            if self.error is not None:
                # dead engine thread: resolve immediately rather than
                # queueing work nothing will ever step
                handle = RequestHandle(req)
                handle._finish("error", f"engine error: {self.error}")
                self.registry.counter("serve/requests_rejected").inc()
                self.events.emit("submit", req.rid,
                                 prompt_len=len(req.tokens),
                                 max_new=req.max_new_tokens)
                self.events.emit("retire", req.rid, status="error",
                                 reason="engine dead", generated=0)
                return handle
            handle = self.scheduler.submit(req)
            if handle.status == "rejected":
                self.registry.counter("serve/requests_rejected").inc()
            return handle

    def step(self) -> bool:
        """One engine iteration: sweep retirements, admit + prefill the
        uncached suffixes (fully-cached prompts dispatch NO prefill — the
        decode step below produces their first token), one decode/verify
        step. Returns whether any work happened."""
        with self._lock:
            did = self._sweep() > 0
            admitted = self.scheduler.admit()
            for slot, bucket in admitted:
                h = slot.handle
                if h.admitted_t is not None:
                    self.registry.histogram("serve/queue_wait_ms").observe(
                        (h.admitted_t - h.submitted_t) * 1000.0)
                if slot.cached_len:
                    self.registry.counter("serve/prefix_hits").inc()
                    self.registry.counter("serve/prefix_cached_tokens").inc(
                        slot.cached_len)
                if slot.cow is not None:
                    self._cow_copy(*slot.cow)
                    slot.cow = None
                if bucket:
                    self._prefill_slot(slot, bucket)
                self.scheduler.note_prefilled(slot)
                did = True
            if self.scheduler.slots:
                self._decode_active()
                did = True
            if did:
                # idle iterations advance nothing: a parked background
                # engine must not flush duplicate snapshots forever
                self._steps += 1
                self._telemetry_step()
        return did

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        for _ in range(max_steps):
            if not self.scheduler.has_work():
                break
            self.step()
        self.flush()

    def start(self) -> None:
        """Background serving thread (idle-spins gently when no work). A
        step that raises aborts every in-flight and queued request with
        status "error" — handles must never block forever on a dead
        engine thread."""
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    did = self.step()
                except Exception as e:  # noqa: BLE001 — must resolve handles
                    self._abort(e)
                    return
                if not did:
                    time.sleep(0.001)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="serving-engine")
        self._thread.start()

    def _abort(self, exc: BaseException) -> None:
        """Resolve every outstanding handle after a fatal engine error.
        Every retirement is attributed (``serve/errors`` labelled with the
        exception class, retire events per request) and the flight
        recorder dumps a postmortem — dump() never raises, so the real
        fault always reaches ``self.error`` / the caller untouched."""
        self.error = exc
        with self._lock:
            self.registry.counter("serve/engine_errors").inc()
            self.registry.counter("serve/errors",
                                  error=type(exc).__name__).inc()
            self.events.emit("engine_error", error=type(exc).__name__,
                             message=str(exc))
            for slot in list(self.scheduler.slots.values()):
                self.scheduler.retire(slot, "error", f"engine error: {exc}")
            for h in self.scheduler.waiting:
                h._finish("error", f"engine error: {exc}")
                self.events.emit("retire", h.request.rid, status="error",
                                 reason="engine error", generated=0,
                                 queued=True)
            self.scheduler.waiting = []
            self.recorder.dump("engine_error", exc=exc)

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join()
        self._thread = None
        self.flush()

    # -- internals ----------------------------------------------------------

    def _sweep(self) -> int:
        """Retire cancelled/expired work — active slots AND still-queued
        requests (both count toward the cancel/timeout metrics, so
        submitted == completed + rejected + cancelled + timeout)."""
        now = time.monotonic()
        sc, st = self.scheduler.sweep(now)
        wc, wt = self.scheduler.sweep_waiting(now)
        if sc + wc:
            self.registry.counter("serve/requests_cancelled").inc(sc + wc)
        if st + wt:
            self.registry.counter("serve/requests_timeout").inc(st + wt)
        return sc + st + wc + wt

    def _prefill_for(self, bucket: int) -> Callable:
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            fn = self._build_prefill(bucket)
            self._prefill_fns[bucket] = fn
        return fn

    def _prefix_prefill_for(self, bucket: int) -> Callable:
        fn = self._prefix_fns.get(bucket)
        if fn is None:
            fn = self._build_prefix_prefill(bucket)
            self._prefix_fns[bucket] = fn
        return fn

    def _cow_copy(self, src: int, dst: int) -> None:
        if self._cow_fn is None:
            self._cow_fn = self._build_cow()
        new_pools, _ = self._cow_fn(self.params, self.kv.pools, src, dst)
        self.kv.pools = new_pools

    def _prefill_slot(self, slot: Slot, bucket: int) -> None:
        t0 = time.perf_counter()
        req = slot.request
        prompt_len = len(req.tokens)
        cached = slot.cached_len
        suffix = req.tokens[cached:]
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(suffix)] = suffix
        if cached:
            fn = self._prefix_prefill_for(bucket)
            name = f"serve/prefix_prefill_{bucket}"
            full = jnp.asarray(self.scheduler.padded_table(slot.blocks),
                               jnp.int32)
            args = (self.params, self.kv.pools, jnp.asarray(padded),
                    full, cached, len(suffix),
                    float(req.temperature), int(req.seed))
        else:
            table = np.asarray(slot.blocks[: bucket // self.kv.block_size],
                               np.int32)
            fn = self._prefill_for(bucket)
            name = f"serve/prefill_{bucket}"
            args = (self.params, self.kv.pools, jnp.asarray(padded),
                    jnp.asarray(table), prompt_len,
                    float(req.temperature), int(req.seed))
        # fallback for buckets warmup() never covered — warmed buckets
        # were recorded there, so this early-outs to a set lookup and the
        # request path never pays the lower() retrace (BEFORE the call —
        # the program donates the pools)
        maybe_record_jit_cost(name, fn, args, registry=self.registry)
        new_pools, tok = fn(*args)
        self.kv.pools = new_pools
        tok = int(np.asarray(tok))
        # dispatch-to-sync host wall for this slot's prefill: the TTFT
        # component split in _emit (queue + prefill + decode == ttft)
        # reads it, so set it BEFORE the first-token emit below
        slot.prefill_ms = (time.perf_counter() - t0) * 1000.0
        self.registry.counter("serve/prefill_tokens").inc(len(suffix))
        self.events.emit("prefill", req.rid, bucket=bucket,
                         suffix=len(suffix), cached=cached,
                         ms=slot.prefill_ms)
        self._emit(slot, tok, first=True)

    def _run_decode(self, state, drafted=None) -> np.ndarray:
        if drafted is None:
            fn, name = self._decode_fn, "serve/decode"
            args = (self.params, self.kv.pools,
                    jnp.asarray(state["tokens"], jnp.int32),
                    jnp.asarray(state["pos"], jnp.int32),
                    jnp.asarray(state["tables"], jnp.int32),
                    jnp.asarray(state["temps"], jnp.float32),
                    jnp.asarray(state["seeds"], jnp.int32),
                    jnp.asarray(state["gen_idx"], jnp.int32))
        else:
            fn, name = self._verify_fn, "serve/verify"
            window = [[t] + list(d)
                      for t, d in zip(state["tokens"], drafted)]
            args = (self.params, self.kv.pools,
                    jnp.asarray(window, jnp.int32),
                    jnp.asarray(state["pos"], jnp.int32),
                    jnp.asarray(state["tables"], jnp.int32),
                    jnp.asarray(state["temps"], jnp.float32),
                    jnp.asarray(state["seeds"], jnp.int32),
                    jnp.asarray(state["gen_idx"], jnp.int32),
                    jnp.asarray(state["limit"], jnp.int32))
        maybe_record_jit_cost(name, fn, args, registry=self.registry)
        new_pools, toks = fn(*args)
        self.kv.pools = new_pools
        return np.asarray(toks)

    def _decode_active(self) -> None:
        if self._draft is not None and any(
                s.request.temperature <= 0.0
                for s in self.scheduler.slots.values()):
            # at least one greedy lane can profit from drafts; sampled
            # lanes ride along untouched (they never speculate)
            self._verify_active()
            return
        state = self.scheduler.decode_state()
        toks = self._run_decode(state)
        for slot in list(self.scheduler.slots.values()):
            slot.pos += 1
            self.events.emit("decode", slot.request.rid, pos=slot.pos, n=1)
            # a fully-cached prompt skipped prefill entirely: its FIRST
            # token comes from this decode step (TTFT records here)
            self._emit(slot, int(toks[slot.index]),
                       first=slot.generated == 0)
        self.registry.counter("serve/decode_tokens").inc(
            sum(state["active"]))

    def _verify_active(self) -> None:
        """One speculative step: draft K tokens per live lane (host-side),
        verify the whole window in one fixed-shape pass, emit the accepted
        prefix + the bonus token. Greedy lanes emit exactly the
        non-speculative stream; sampled lanes do not speculate (row 0's
        sample uses the same per-request fold_in key plain decode
        would)."""
        K = int(self.serving.spec_k)
        state = self.scheduler.decode_state()
        slots = list(self.scheduler.slots.values())
        drafted = [[0] * K for _ in range(self.S)]
        for slot in slots:
            if slot.request.temperature > 0.0:
                continue  # sampled lanes never speculate: don't pay the
                # O(context) draft scan or skew the accept-rate stats
            ctx = list(slot.request.tokens) + slot.handle.output
            prop = list(self._draft.propose(ctx, K))[:K]
            drafted[slot.index][: len(prop)] = prop
        out = self._run_decode(state, drafted=drafted)
        emitted = 0
        for slot in slots:
            req = slot.request
            row = out[slot.index].tolist()
            budget = req.max_new_tokens - slot.generated
            k_eff = (min(K, max(budget - 1, 0))
                     if req.temperature <= 0.0 else 0)
            a = accept_length(drafted[slot.index], row, k_eff)
            # accepted is the window outcome; the EMITTED count is bounded
            # by accepted+1 but can be cut short by mid-window EOS/length
            # retirement — retire.generated stays the authoritative total
            self.events.emit("verify", req.rid, drafted=k_eff, accepted=a)
            if req.temperature <= 0.0:
                self._drafted_total += K
                self._accepted_total += a
                self.registry.counter("serve/drafted_tokens").inc(K)
            if a:
                self.registry.counter("serve/spec_accepted_tokens").inc(a)
            for tok in row[: a + 1]:
                slot.pos += 1
                self._emit(slot, int(tok), first=slot.generated == 0)
                emitted += 1
                if slot.index not in self.scheduler.slots:
                    break  # retired (eos / length) mid-window
        self.registry.counter("serve/decode_tokens").inc(emitted)

    def _emit(self, slot: Slot, tok: int, first: bool = False) -> None:
        """Record one generated token: stream it, time it, retire on
        EOS / length budget."""
        req = slot.request
        now = time.monotonic()
        slot.generated += 1
        slot.last_token = tok
        h = slot.handle
        if first:
            ttft_ms = (now - h.submitted_t) * 1000.0
            self.registry.histogram("serve/ttft_ms").observe(ttft_ms)
            self._ttft_n += 1
            if ttft_ms <= self.serving.slo_ttft_ms:
                self._ttft_ok += 1
            # additive TTFT split: queue (submit -> slot granted) +
            # prefill (this slot's dispatch wall) + decode (residual —
            # fully-cached prompts bootstrap through the decode step, so
            # their whole post-admit latency lands here). Components sum
            # to the measured TTFT by construction.
            queue_ms = ((h.admitted_t - h.submitted_t) * 1000.0
                        if h.admitted_t is not None else 0.0)
            self.events.emit(
                "first_token", req.rid, ttft_ms=ttft_ms, queue_ms=queue_ms,
                prefill_ms=slot.prefill_ms,
                decode_ms=max(ttft_ms - queue_ms - slot.prefill_ms, 0.0))
        else:
            itl_ms = (now - slot.last_token_t) * 1000.0
            self.registry.histogram("serve/itl_ms").observe(itl_ms)
            self._itl_n += 1
            if itl_ms <= self.serving.slo_itl_ms:
                self._itl_ok += 1
        slot.last_token_t = now
        slot.handle._emit(tok)
        self._emitted_total += 1
        if req.eos_id is not None and tok == req.eos_id:
            self.scheduler.retire(slot, "done", "eos")
            self.registry.counter("serve/requests_completed").inc()
        elif slot.generated >= req.max_new_tokens:
            self.scheduler.retire(slot, "done", "length")
            self.registry.counter("serve/requests_completed").inc()

    # -- telemetry ----------------------------------------------------------

    def _telemetry_step(self) -> None:
        reg = self.registry
        reg.counter("serve/steps").inc()
        if self.metrics_server is not None:
            self.metrics_server.note_step()  # /healthz last-step age
        now = time.monotonic()
        self._emitted_window.append((now, self._emitted_total))
        if len(self._emitted_window) > 64:
            self._emitted_window = self._emitted_window[-64:]
        if self._steps % max(self.serving.flush_interval, 1) == 0:
            self.flush()

    def tokens_per_sec(self) -> float:
        w = self._emitted_window
        if len(w) < 2 or w[-1][0] <= w[0][0]:
            return 0.0
        return (w[-1][1] - w[0][1]) / (w[-1][0] - w[0][0])

    def spec_accept_rate(self) -> float:
        """Fraction of drafted tokens the verify pass accepted (0 when
        spec decode is off or nothing was drafted yet)."""
        if not self._drafted_total:
            return 0.0
        return self._accepted_total / self._drafted_total

    def defrag(self) -> None:
        """Compact live pool blocks to the low indices (pool-shrink /
        snapshot): delegates to the scheduler, which rewrites every
        referencing table — active sequences AND radix prefix nodes."""
        with self._lock:
            self.scheduler.defrag()

    def flush(self) -> None:
        reg = self.registry
        reg.gauge("serve/queue_depth").set(self.scheduler.queue_depth)
        reg.gauge("serve/active_requests").set(len(self.scheduler.slots))
        reg.gauge("serve/kv_occupancy").set(self.kv.occupancy)
        reg.gauge("serve/kv_blocks_used").set(self.kv.allocator.used)
        reg.gauge("serve/tokens_per_sec").set(self.tokens_per_sec())
        reg.gauge("serve/jit_programs").set(self.compile_count())
        if self.prefix is not None:
            reg.gauge("serve/prefix_hit_rate").set(self.prefix.hit_rate)
            reg.gauge("serve/prefix_cache_blocks").set(
                self.prefix.blocks_held)
        if self._draft is not None:
            reg.gauge("serve/spec_accept_rate").set(self.spec_accept_rate())
        # SLO attainment (serving.slo_ttft_ms / slo_itl_ms > 0): share of
        # observations inside the target, exported for the Prometheus
        # endpoint and the summarize SLO report
        if self.serving.slo_ttft_ms > 0:
            reg.gauge("serve/slo_ttft_ms").set(self.serving.slo_ttft_ms)
            reg.gauge("serve/slo_ttft_attainment").set(
                self._ttft_ok / self._ttft_n if self._ttft_n else 1.0)
        if self.serving.slo_itl_ms > 0:
            reg.gauge("serve/slo_itl_ms").set(self.serving.slo_itl_ms)
            reg.gauge("serve/slo_itl_attainment").set(
                self._itl_ok / self._itl_n if self._itl_n else 1.0)
        reg.flush(step=self._steps)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.stop()
        self.flush()
        if self.metrics_server is not None:
            self.metrics_server.stop()
            self.metrics_server = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
