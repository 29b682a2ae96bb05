"""Optimizer + LR schedule construction (optax-based).

Capability parity with the reference optimizer stack
(runtime/optimizer/utils.py:14-108 ``get_optimizer_and_param_scheduler`` /
``clip_grad_norm``, param_scheduler.py:102 ``OptimizerParamScheduler``):
AdamW with weight-decay masking (no decay on norms/biases), global grad-norm
clipping, and constant/linear/cosine/inverse-square-root/WSD schedules with
warmup.

TPU note: grad-norm clipping needs no TP-duplication bookkeeping here — under
GSPMD the gradient pytree is logically global (sharded, not replicated-with-
duplicates), so `optax.clip_by_global_norm`'s tree-wide L2 norm is already the
true global norm; XLA inserts the cross-device reductions.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from hetu_galvatron_tpu.core.args_schema import TrainArgs


def make_lr_schedule(train: TrainArgs) -> optax.Schedule:
    """Warmup + decay schedule matching the reference styles
    (param_scheduler.py: constant/linear/cosine/inverse-square-root/WSD)."""
    peak, floor = train.lr, train.min_lr
    warmup = max(train.lr_warmup_iters, 0)
    total = train.lr_decay_iters or train.train_iters
    decay_steps = max(total - warmup, 1)
    style = train.lr_decay_style

    if style == "constant":
        body = optax.constant_schedule(peak)
    elif style == "linear":
        body = optax.linear_schedule(peak, floor, decay_steps)
    elif style == "cosine":
        body = optax.cosine_decay_schedule(
            peak, decay_steps, alpha=floor / max(peak, 1e-12))
    elif style == "inverse-square-root":
        def body(step):  # lr = peak * sqrt(warmup+1) / sqrt(step+warmup+1)
            s = jnp.asarray(step, jnp.float32) + warmup + 1.0
            return jnp.maximum(peak * jnp.sqrt(warmup + 1.0) / jnp.sqrt(s), floor)
    elif style == "WSD":
        # warmup-stable-decay: hold peak, then linear-decay the last
        # lr_wsd_decay_iters steps
        wsd = max(train.lr_wsd_decay_iters, 1)
        stable = max(decay_steps - wsd, 0)
        body = optax.join_schedules(
            [optax.constant_schedule(peak),
             optax.linear_schedule(peak, floor, wsd)],
            [stable],
        )
    else:
        raise ValueError(f"unknown lr_decay_style {style}")

    if warmup == 0:
        return body
    return optax.join_schedules(
        [optax.linear_schedule(0.0, peak, warmup), body], [warmup]
    )


class HostSchedule:
    """The learning rate of an iteration as a Python float, for the log
    line: :func:`make_lr_schedule` (the ONE definition, which the optimizer
    runs inside the step program on its own count) evaluated for ``BLOCK``
    iterations in one program and one read-back, then looked up on the
    host, so that asking for it between two steps puts nothing on the
    accelerator. Called eagerly an iteration at a time the schedule is ten
    scalar programs and a read-back, 6 ms of host time a step with the
    device idle (PERF.md, PR 31). A block begins at the first iteration
    asked for outside the one held (the run's first, a resume's), and
    ``train.train_iters`` does not size it: a benchmark sets ten million."""

    BLOCK = 1024

    def __init__(self, train: TrainArgs):
        self._block = jax.jit(jax.vmap(make_lr_schedule(train)))
        self._first, self._values = 0, np.empty(0, np.float32)

    def __call__(self, it: int) -> float:
        if not 0 <= it - self._first < len(self._values):
            self._first = it
            self._values = np.asarray(self._block(
                np.arange(it, it + self.BLOCK, dtype=np.int32)))
        return float(self._values[it - self._first])


def _decay_mask(params: Any) -> Any:
    """True for params that get weight decay: 2D+ weights, not norms/biases
    (reference utils.py splits wd/no-wd groups the same way)."""
    return jax.tree.map(lambda p: p.ndim >= 2, params)


def make_optimizer(
    train: TrainArgs, params: Optional[Any] = None
) -> optax.GradientTransformation:
    """AdamW + global-norm clip + schedule; the returned transformation's
    state is a pytree that the mesh layer shards per DPType (ZeRO-1/2).

    MoE expert-bias buffers (param paths ending in ``expert_bias``) bypass
    the Adam chain and take plain SGD with lr=1: their "gradient" IS the
    negated maintenance update emitted by the router
    (models/moe.py route_tokens), so bias_new = bias + update — the
    reference's aux-loss-free buffer update (router.py:116)."""
    schedule = make_lr_schedule(train)
    chain = []
    if train.clip_grad and train.clip_grad > 0:
        chain.append(optax.clip_by_global_norm(train.clip_grad))
    chain.append(
        optax.scale_by_adam(
            b1=train.adam_beta1, b2=train.adam_beta2, eps=train.adam_eps
        )
    )
    if train.weight_decay:
        chain.append(
            optax.add_decayed_weights(train.weight_decay, mask=_decay_mask)
        )
    chain.append(optax.scale_by_learning_rate(schedule))
    return partition_expert_bias(optax.chain(*chain))


def partition_expert_bias(
    adam: optax.GradientTransformation,
) -> optax.GradientTransformation:
    """Route ``expert_bias`` leaves to SGD(lr=1), everything else to the
    given chain (see :func:`make_optimizer`)."""

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: ("bias_buffer"
                             if str(path[-1]).find("expert_bias") >= 0
                             else "adam"),
            params)

    return optax.multi_transform(
        {"adam": adam, "bias_buffer": optax.sgd(learning_rate=1.0)}, labels)


def global_grad_norm(grads: Any) -> jax.Array:
    """fp32 global L2 norm across the whole gradient pytree (reference
    get_grad_norm_fp32, clip_grads.py:66)."""
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree.leaves(grads)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))
