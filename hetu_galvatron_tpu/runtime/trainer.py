"""Single-program training step: jitted train_step + microbatch accumulation.

Capability parity with the reference's no-pipeline execution path
(runtime/pipeline/pipeline.py:306-385 ``no_pipeline_forward_backward`` +
models/gpt/train_dist.py:21-74 train loop): build loss, grads, clip, Adam
update, loss scalar back — but as one jitted pure function over
(params, opt_state, batch) instead of a module graph walk.

Microbatching (the reference's ``chunks``) is a `lax.scan` over the leading
batch-chunk axis with gradient accumulation in fp32, which XLA pipelines
without host round-trips.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs
from hetu_galvatron_tpu.models.builder import causal_lm_loss
from hetu_galvatron_tpu.runtime.optimizer import global_grad_norm


def make_loss_fn(
    cfg: ModelArgs,
    *,
    compute_dtype=jnp.bfloat16,
    remat_flags=None,
    layer_overrides=None,
) -> Callable[[Any, Dict[str, jax.Array]], jax.Array]:
    def loss_fn(params, batch):
        return causal_lm_loss(
            params, batch, cfg,
            compute_dtype=compute_dtype,
            remat_flags=remat_flags,
            layer_overrides=layer_overrides,
        )
    return loss_fn


def microbatch_weights(loss_mask: Optional[jax.Array], chunks: int
                       ) -> jax.Array:
    """Per-microbatch token-share weights from a ``[chunks, ...]``-stacked
    loss mask: each microbatch's masked-mean loss is weighted by its share
    of valid tokens so gradient accumulation matches the unchunked step
    exactly even under non-uniform masks. ``None`` mask -> uniform
    ``1/chunks``. Shared by the scanned SPMD step and both pipeline
    engines (host and compiled)."""
    if loss_mask is None:
        return jnp.full((chunks,), 1.0 / chunks, jnp.float32)
    counts = jnp.sum(loss_mask.astype(jnp.float32),
                     axis=tuple(range(1, loss_mask.ndim)))
    return counts / jnp.maximum(jnp.sum(counts), 1.0)


def make_train_step(
    loss_fn: Callable[[Any, Dict[str, jax.Array]], jax.Array],
    tx: optax.GradientTransformation,
    *,
    chunks: int = 1,
    aux_stats: bool = False,
    constrain_microbatches: Optional[Callable[[Any], Any]] = None,
    param_view: Optional[Callable[[Any], Any]] = None,
) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics). ``chunks`` splits the global batch into microbatches scanned
    with fp32 grad accumulation (reference chunks semantics,
    hybrid_parallel_config.py:359).

    ``aux_stats=True`` means loss_fn returns (loss, stats_pytree); the
    stats land in metrics["moe"] — the reference's per-layer aux-losses
    tracker (moe_utils.py:547-644). Loss-like stats are token-weighted
    across microbatches (so a 0/1 flag such as "short_dispatch" reads as a
    share of the microbatches); "tokens_per_expert", "rows_*",
    "overflow_chunks" and "passes_by_chip" leaves are summed.

    ``constrain_microbatches`` is an optional hook applied to the
    ``[chunks, B/chunks, ...]``-stacked batch tree right after the
    reshape on the scanned path. The SPMD path pins the stack so
    the CHUNK axis is replicated and the sample axis keeps the plan's
    batch sharding: without the pin, the reshape naturally absorbs the
    outer dp mesh axis into the chunk dim, every scanned microbatch
    arrives sharded over only the INNER dp axes — and under ZeRO-3 the
    partitioner's gradient program for that layout is numerically WRONG
    (the ROADMAP embed-ZeRO-3 + vtp>1 + chunks>1 bug: wrong wte rows at
    grad magnitude — and in fact every dp-sharded grad leaf drifts).
    The pin makes each microbatch's embed-grad reduce-scatter
    materialize per microbatch in the plan's own layout.

    ``param_view`` (parallel/spmd.py::interior_sharding) maps the stored
    parameters to the tree ``loss_fn`` takes. It runs once a step, outside
    the microbatch scan: gradients accumulate in the view's layout and are
    pulled back through it once, before the norm and the update."""

    if aux_stats:
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    else:
        _plain = jax.value_and_grad(loss_fn)

        def grad_fn(p, b):
            l, g = _plain(p, b)
            return (l, {}), g

    def _reduce_stats(stacked, weights):
        def red(path, s):
            # counts add up over the microbatches; the rest are means
            if any(name in str(k) for k in path for name in (
                    "tokens_per_expert", "rows_", "overflow_chunks",
                    "passes_by_chip")):
                return jnp.sum(s, axis=0)
            w = weights.reshape((-1,) + (1,) * (s.ndim - 1))
            return jnp.sum(w * s, axis=0)
        return jax.tree_util.tree_map_with_path(red, stacked)

    def step(params, opt_state, batch):
        # a "dropout_rng" key rides in the batch dict (so every execution
        # path — single-device, SPMD, chunked — keeps one step signature);
        # it is per-step data, not a [B, ...] array, so the microbatch
        # reshape must not touch it
        batch = dict(batch)
        rng = batch.pop("dropout_rng", None)
        stored = params
        if param_view is not None:
            with jax.named_scope("param_view"):
                params, pull_back = jax.vjp(param_view, stored)
        if chunks <= 1:
            if rng is not None:
                batch["dropout_rng"] = rng
            (loss, stats), grads = grad_fn(params, batch)
        else:
            bsz = batch["tokens"].shape[0]
            if bsz % chunks:
                raise ValueError(
                    f"batch size {bsz} is not divisible by chunks={chunks}; "
                    f"adjust global_train_batch_size or chunks")
            mbs = jax.tree.map(
                lambda x: x.reshape((chunks, x.shape[0] // chunks) + x.shape[1:]),
                batch)
            if constrain_microbatches is not None:
                mbs = constrain_microbatches(mbs)
            if rng is not None:
                mbs["dropout_rng"] = jax.random.split(rng, chunks)
            # token-weighted accumulation: each microbatch's masked-mean loss
            # is weighted by its share of valid tokens so chunks>1 matches
            # chunks=1 exactly even under non-uniform loss masks
            weights = microbatch_weights(mbs.get("loss_mask"), chunks)

            def microbatch(acc, xs):
                mb, w = xs
                (l, st), g = grad_fn(params, mb)
                with jax.named_scope("grad/accumulate"):
                    acc = jax.tree.map(
                        lambda a, b: a + w * b.astype(jnp.float32), acc, g)
                return acc, (w * l, st)

            with jax.named_scope("grad/accumulate"):
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, (wlosses, stacked) = jax.lax.scan(
                microbatch, zeros, (mbs, weights))
            loss = jnp.sum(wlosses)
            stats = _reduce_stats(stacked, weights) if aux_stats else {}
        if param_view is not None:
            with jax.named_scope("param_view"):
                grads, = pull_back(grads)
        with jax.named_scope("grad/clip"):
            gnorm = global_grad_norm(grads)
        with jax.named_scope("optimizer/update"):
            updates, new_opt = tx.update(grads, opt_state, stored)
            new_params = optax.apply_updates(stored, updates)
        metrics = {"loss": loss, "grad_norm": gnorm}
        if aux_stats:
            metrics["moe"] = stats
        return new_params, new_opt, metrics

    return step


def make_telemetry(args: CoreArgs, *, registry: Any = None,
                   world_size: int = 1, global_batch_size: Optional[int] = None
                   ) -> Any:
    """Build a ``TrainingTelemetry`` hook (plus its JSONL/TensorBoard
    sinks) from ``args.observability``. When no ``registry`` is passed the
    process-wide default registry is (re)configured with the sinks, so
    library-level instrumentation (rerun counters, profiler histograms,
    spans) lands in the same file."""
    import os

    from hetu_galvatron_tpu.observability.registry import configure
    from hetu_galvatron_tpu.observability.telemetry import TrainingTelemetry

    obs = args.observability
    if registry is None:
        path = obs.metrics_path or os.path.join(
            args.logging.tensorboard_dir or ".", "metrics.jsonl")
        registry = configure(
            jsonl_path=path,
            tensorboard_dir=(args.logging.tensorboard_dir
                             if obs.tensorboard else None))
    return TrainingTelemetry(
        registry,
        model=args.model,
        global_batch_size=(global_batch_size
                           or args.parallel.global_train_batch_size),
        seq_length=args.model.seq_length,
        world_size=world_size,
        peak_tflops_per_device=obs.peak_tflops,
        flush_interval=obs.flush_interval,
    )
