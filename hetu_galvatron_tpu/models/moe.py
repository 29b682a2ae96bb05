"""Mixture-of-Experts layer: routers + token dispatchers + grouped MLPs.

Capability parity with the reference MoE runtime (runtime/moe/router.py:98
``TopKRouter`` with aux/z-losses, sinkhorn load balancing and the
aux-loss-free expert-bias correction; token_dispatcher.py:116/287/942
allgather/alltoall/flex dispatchers; mlp.py:26 ``GroupedMLP``;
moe_utils.py:166 aux-loss scaling).

TPU-first: two dispatch formulations replace the reference's three torch
dispatchers —

* ``capacity`` (GShard one-hot einsums): dispatch/combine are dense einsums
  over a fixed per-expert capacity; sharding the ``expert`` axis over the ep
  mesh axes makes GSPMD insert the token all-to-alls the reference issues by
  hand. Over-capacity tokens are dropped (weights renormalized). This is the
  expert-parallel mode — every shape is static and ep/etp-shardable.
* ``dropless`` (sort + ``lax.ragged_dot``): token slots are sorted by expert
  and the expert MLPs run as grouped ragged matmuls — no token is ever
  dropped and no capacity buffer is materialized (the reference's alltoall
  dropless dispatcher, token_dispatcher.py:287). Static [T*K] shapes keep it
  jit-clean; HF Mixtral numerics reproduce exactly (see
  tests/models/test_moe.py Mixtral parity).

Across chips (``parallel.global_ep_deg``, the ``ep`` axes a plan carves from
dp): the ``capacity`` einsums are left to GSPMD, which turns their sharded
``expert`` axis into all-to-alls; the sorted dispatchers (``dropless`` and
the held share) run inside :func:`make_expert_exchange`'s ``shard_map``: a
chip's tokens, choices and weights are all-gathered over ``ep``, each chip
runs :func:`_held_dispatch` over the group's tokens for the experts it
holds, and the partial results are reduce-scattered back to the tokens'
owners. Tokens move, expert weights never do.

Routers: softmax top-k (optionally with the DeepSeek-style expert-bias
selection correction, reference router.py expert_bias) and sinkhorn load
balancing (selection via a no-grad sinkhorn normalization, weights via
sigmoid/softmax of the raw logits — reference sinkhorn_load_balancing).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.analysis.eligibility import moe_capacity_of
from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.models import modules as M

Params = Dict[str, Any]


def is_moe_layer(cfg: ModelArgs, layer_idx: int) -> bool:
    """Whether block ``layer_idx`` has experts, from the per-layer
    description (``ModelArgs.block_kinds``: leading dense blocks, then
    every moe_layer_freq-th; reference moe_layer_freq semantics)."""
    return cfg.block_kinds()[layer_idx][1] == "experts"


def moe_capacity(cfg: ModelArgs, tokens: int,
                 capacity_factor: Optional[float] = None) -> int:
    """Per-expert token capacity (reference capacity-factor dispatch)."""
    cf = capacity_factor if capacity_factor is not None \
        else cfg.moe_capacity_factor
    return moe_capacity_of(tokens, cfg.moe_topk, cfg.num_experts, cf)


def sinkhorn(logits: jax.Array, n_iters: int = 8) -> jax.Array:
    """Sinkhorn normalization of a [T, E] score matrix (reference
    moe_utils.sinkhorn, fixed iteration count for jit)."""
    cost = jnp.exp(logits.astype(jnp.float32))
    T, E = cost.shape
    d1 = jnp.ones((E,), jnp.float32)

    def body(_, d1):
        d0 = 1.0 / T / jnp.maximum((cost * d1[None, :]).sum(-1), 1e-9)
        return 1.0 / E / jnp.maximum((cost * d0[:, None]).sum(0), 1e-9)

    d1 = jax.lax.fori_loop(0, n_iters, body, d1)
    d0 = 1.0 / T / jnp.maximum((cost * d1[None, :]).sum(-1), 1e-9)
    return d0[:, None] * cost * d1[None, :]


def route_tokens(
    p: Params, xt: jax.Array, cfg: ModelArgs, compute_dtype=jnp.bfloat16
) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Router: [T, H] tokens -> (topk_idx [T,K] int, weights [T,K] fp32,
    aux_loss scalar, stats dict).

    ``stats`` carries the per-layer balance observables the reference logs
    through its aux-losses tracker (moe_utils.py:547-644
    save_to_aux_losses_tracker / reduce_aux_losses_tracker_across_ranks):
    the load-balance loss, the z-loss, and tokens_per_expert [E].

    topk: softmax probs; selection optionally corrected by a no-grad expert
    bias (p["expert_bias"], reference moe_router_enable_expert_bias — the
    bias steers WHICH experts are picked, never the combine weights);
    weights renormalized over the selected k (HF Mixtral convention)
    unless ``cfg.moe_norm_topk_prob`` is off (HF OLMoE).
    Score function and epsilon by family (``cfg.moe_score_function``):
    Mixtral: softmax, the k chosen divided by ``max(sum, 1e-9)``; OLMoE:
    softmax, not renormalised, no epsilon; LFM2 (and DeepSeek-V3's form):
    a sigmoid each at any k, the bias added to the SIGMOID for the choice
    only, the k chosen unbiased sigmoids divided by ``sum +
    cfg.moe_norm_topk_eps`` (LFM2's 1e-6, DeepSeek-V3's 1e-20) where
    ``moe_norm_topk_prob`` is on, then times
    ``cfg.moe_routed_scaling_factor``. The bias's maintenance is the same
    under both.
    sinkhorn: selection from a no-grad sinkhorn normalization; weights are
    sigmoid (k=1) / softmax (k>1) of the raw logits (reference
    sinkhorn_load_balancing; aux loss unsupported there)."""
    E, K = cfg.num_experts, cfg.moe_topk
    router_dtype = jnp.float32 if cfg.moe_router_dtype == "float32" \
        else compute_dtype
    logits = jnp.einsum("th,he->te", xt.astype(router_dtype),
                        p["router"].astype(router_dtype),
                        preferred_element_type=jnp.float32)

    if cfg.moe_router_type == "sinkhorn":
        if cfg.moe_aux_loss_coeff:
            raise ValueError(
                "sinkhorn routing does not support the aux loss "
                "(reference router.py:158); set moe_aux_loss_coeff=0")
        norm = jax.lax.stop_gradient(sinkhorn(logits))
        _, topk_idx = jax.lax.top_k(norm, K)
        scores = (jax.nn.sigmoid(logits) if K == 1
                  else jax.nn.softmax(logits, axis=-1))
        w = jnp.take_along_axis(scores, topk_idx, axis=-1)
        aux = jnp.zeros((), jnp.float32)
        zloss = jnp.zeros((), jnp.float32)
        if cfg.moe_z_loss_coeff:
            z = jax.scipy.special.logsumexp(logits, axis=-1)
            zloss = cfg.moe_z_loss_coeff * jnp.mean(jnp.square(z))
            aux = zloss
        counts = jnp.sum(jax.nn.one_hot(topk_idx, E, dtype=jnp.float32),
                         axis=(0, 1))
        stats = {"load_balance_loss": jnp.zeros((), jnp.float32),
                 "z_loss": zloss,
                 "tokens_per_expert": jax.lax.stop_gradient(counts)}
        return topk_idx, w.astype(jnp.float32), aux, stats

    sigmoid = cfg.moe_score_function == "sigmoid"
    if sigmoid and cfg.moe_aux_loss_coeff:
        raise ValueError(
            "sigmoid router scores are not a distribution over the experts, "
            "which the load-balancing term's P_e assumes; balance them by "
            "the selection bias (moe_router_enable_expert_bias) and set "
            "moe_aux_loss_coeff=0")
    probs = (jax.nn.sigmoid(logits) if sigmoid
             else jax.nn.softmax(logits, axis=-1))  # [T, E]
    select_scores = probs
    if "expert_bias" in p:
        select_scores = probs + jax.lax.stop_gradient(p["expert_bias"])
    _, topk_idx = jax.lax.top_k(select_scores, K)
    bias_term = None
    if "expert_bias" in p:
        # aux-loss-free maintenance, routed THROUGH the gradient: this term
        # has value 0 but d/d(expert_bias) = -update, and the optimizer
        # applies plain SGD(lr=1) to expert_bias paths
        # (runtime/optimizer.py partition), so bias_new = bias + update —
        # the reference's buffer update (router.py:116) without mutating
        # state inside a pure function. stop_gradient everywhere else keeps
        # the model's real gradients untouched.
        counts = jnp.sum(jax.nn.one_hot(topk_idx, E, dtype=jnp.float32),
                         axis=(0, 1))
        update = update_expert_bias(jnp.zeros((E,), jnp.float32), counts,
                                    cfg.moe_expert_bias_update_rate)
        term = jnp.sum(jax.lax.stop_gradient(-update) * p["expert_bias"])
        bias_term = term - jax.lax.stop_gradient(term)
    topk_probs = jnp.take_along_axis(probs, topk_idx, axis=-1)
    if sigmoid:
        if cfg.moe_norm_topk_prob:
            topk_probs = topk_probs / (
                jnp.sum(topk_probs, axis=-1, keepdims=True)
                + cfg.moe_norm_topk_eps)
        topk_probs = topk_probs * cfg.moe_routed_scaling_factor
    elif cfg.moe_norm_topk_prob:
        # renormalize over the selected k (HF Mixtral convention; the
        # reference's moe_router_topk_scaling path covers the same role).
        # Off (HF OLMoE, norm_topk_prob false) the raw softmax values
        # combine, and a token's weights sum to less than one
        topk_probs = topk_probs / jnp.maximum(
            jnp.sum(topk_probs, axis=-1, keepdims=True), 1e-9)

    # aux losses (reference router.py aux/z-loss; moe_utils.py:166 scaling)
    sel = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)  # [T, K, E]
    tokens_per_expert = jnp.sum(sel, axis=(0, 1))  # [E]
    frac_tokens = jnp.mean(jnp.sum(sel, axis=1), axis=0)  # f_e
    frac_probs = jnp.mean(probs, axis=0)  # P_e
    balance = cfg.moe_aux_loss_coeff * E * jnp.sum(frac_tokens * frac_probs)
    zloss = jnp.zeros((), jnp.float32)
    if cfg.moe_z_loss_coeff:
        z = jax.scipy.special.logsumexp(logits, axis=-1)
        zloss = cfg.moe_z_loss_coeff * jnp.mean(jnp.square(z))
    aux = balance + zloss
    if bias_term is not None:
        aux = aux + bias_term  # value 0; carries the bias-maintenance grad
    stats = {"load_balance_loss": jax.lax.stop_gradient(balance),
             "z_loss": jax.lax.stop_gradient(zloss),
             "tokens_per_expert": jax.lax.stop_gradient(tokens_per_expert)}
    return topk_idx, topk_probs.astype(jnp.float32), aux, stats


def update_expert_bias(expert_bias: jax.Array, tokens_per_expert: jax.Array,
                       update_rate: float = 1e-3) -> jax.Array:
    """Aux-loss-free balancing step (reference expert-bias maintenance):
    nudge under-loaded experts' selection bias up, over-loaded down. The
    trainer calls this outside the gradient path with the batch's per-expert
    token counts."""
    err = jnp.mean(tokens_per_expert) - tokens_per_expert
    return expert_bias + update_rate * jnp.sign(err)


def held_range(cfg: ModelArgs, ep: int = 1, index: Any = 0
               ) -> Tuple[int, Any]:
    """(how many experts this layer holds, the first one's index). Under an
    expert exchange over ``ep`` chips, what chip ``index`` of the group
    holds: a whole ``ep``-th of the layer's experts, so ``first`` is traced
    where ``index`` is (``lax.axis_index``)."""
    held, first = cfg.held_experts, cfg.moe_first_held_expert
    if not 0 <= first <= first + held <= cfg.num_experts:
        raise ValueError(
            f"experts [{first}, {first + held}) held of "
            f"{cfg.num_experts}: moe_first_held_expert + moe_held_experts "
            "must lie inside the router's width")
    if held < cfg.num_experts and cfg.moe_dispatcher != "dropless":
        raise NotImplementedError(
            "an expert layer that holds a share of its experts "
            "(moe_held_experts) runs the dropless dispatcher; the capacity "
            "dispatcher lays out every expert's buffer")
    if held % ep:
        raise ValueError(
            f"{held} experts held over ep={ep}: parallel.global_ep_deg must "
            "divide the experts a layer holds")
    return held // ep, first + index * (held // ep)


def init_moe_mlp(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Params]:
    h = cfg.hidden_size
    f = cfg.moe_ffn_hidden_size or cfg.ffn_dim
    e = cfg.num_experts
    # the experts this layer holds: the router is over all ``e``, the
    # weights are the held ones' alone
    held, _ = held_range(cfg)
    gated = M._is_gated(cfg.hidden_act)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    p: Params = {
        "router": M._normal(k1, (h, e), std),
        "win": M._normal(k2, (held, h, 2 * f if gated else f), std),
        "wout": M._normal(k3, (held, f, h),
                          std / math.sqrt(2 * cfg.num_hidden_layers)),
    }
    a: Params = {
        "router": ("embed", "expert_out"),
        "win": ("expert", "embed", "mlp"),
        "wout": ("expert", "mlp", "embed"),
    }
    if cfg.num_shared_experts:
        sp, sa = M.init_mlp(k4, cfg,
                            ffn_dim=f * cfg.num_shared_experts)
        p["shared"] = sp
        a["shared"] = sa
    if cfg.moe_router_enable_expert_bias:
        # selection-only bias, updated outside the gradient path via
        # update_expert_bias (reference expert_bias buffer, router.py:116)
        p["expert_bias"] = jnp.zeros((e,), jnp.float32)
        a["expert_bias"] = ("expert_out",)
    return p, a


def _expert_act(hproj: jax.Array, cfg: ModelArgs,
                compute_dtype=jnp.bfloat16) -> jax.Array:
    hproj = hproj.astype(compute_dtype)
    act = M._ACTS[cfg.hidden_act]
    if M._is_gated(cfg.hidden_act):
        gate, up = jnp.split(hproj, 2, axis=-1)
        return act(gate) * up
    return act(hproj)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(rows: jax.Array, weights: jax.Array,
                    group_sizes: jax.Array, out_dtype) -> jax.Array:
    """The expert layer's grouped matmul: sorted ``rows`` [M, K] through
    ``weights`` [G, K, N] by ``group_sizes`` [G]. ``lax.ragged_dot``
    accumulates in float32 and writes once, in ``out_dtype``: the dtype the
    product's first consumer reads. The compute dtype is the operands' own.

    The backward pass keeps both transposed products in the compute dtype:
    the cotangent is rounded to it going in (as a dense matmul's is at
    default precision, and as the kernel does with a float32 operand
    anyway), and the gradients to the rows and to the weights come out in
    it, which is what the cast behind the one and the transpose of
    ``weight_view`` behind the other round them to. Plain reverse mode of a
    product asked for in float32 makes both of them mixed bfloat16 x float32
    kernels that write float32 for the next instruction to round. At
    float32 both passes are plain reverse mode's, number for number."""
    return jax.lax.ragged_dot(rows, weights, group_sizes,
                              preferred_element_type=out_dtype)


def _grouped_matmul_fwd(rows, weights, group_sizes, out_dtype):
    return (_grouped_matmul(rows, weights, group_sizes, out_dtype),
            (rows, weights, group_sizes))


def _grouped_matmul_bwd(out_dtype, saved, g):
    rows, weights, group_sizes = saved
    # JAX's own transposes of the product in the compute dtype: the modes
    # and dimension numbers plain reverse mode would have emitted
    product = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes,
                                preferred_element_type=rows.dtype)
    g = g.astype(rows.dtype)
    d_weights, = jax.linear_transpose(lambda w: product(rows, w), weights)(g)
    d_rows, = jax.linear_transpose(lambda r: product(r, weights), rows)(g)
    return d_rows, d_weights, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _capacity_dispatch(
    p: Params, xt: jax.Array, topk_idx: jax.Array, w: jax.Array,
    cfg: ModelArgs, compute_dtype, capacity_factor: Optional[float],
) -> jax.Array:
    """GShard one-hot capacity dispatch: position of each (token, k) slot
    within its expert's capacity buffer; over-capacity slots drop (weights
    renormalized over the survivors)."""
    T, _ = xt.shape
    E, K = cfg.num_experts, cfg.moe_topk
    C = moe_capacity(cfg, T, capacity_factor)
    sel = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)  # [T, K, E]
    flat_sel = sel.reshape(T * K, E)
    pos = jnp.cumsum(flat_sel, axis=0) * flat_sel - 1.0  # [T*K, E]
    in_cap = (pos >= 0) & (pos < C)
    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * \
        in_cap[..., None]  # [T*K, E, C]
    dispatch = pos_oh.reshape(T, K, E, C).sum(axis=1)  # [T, E, C]
    # redistribute dropped slots' weight over the survivors, preserving the
    # token's total combine weight (for the renormalized topk router this is
    # the reference's renormalize-over-survivors; sinkhorn scales survive
    # unchanged when nothing drops)
    kept = (flat_sel * in_cap.astype(jnp.float32)).sum(-1).reshape(T, K)
    wk = w * kept
    wk = wk * (jnp.sum(w, axis=-1, keepdims=True)
               / jnp.maximum(jnp.sum(wk, axis=-1, keepdims=True), 1e-9))
    combine = jnp.einsum("tkec,tk->tec", pos_oh.reshape(T, K, E, C), wk)

    # expert compute: [E, C, H] -> [E, C, F] -> [E, C, H]
    xe = jnp.einsum("tec,th->ech", dispatch.astype(compute_dtype),
                    xt.astype(compute_dtype),
                    preferred_element_type=jnp.float32).astype(compute_dtype)
    hproj = jnp.einsum("ech,ehf->ecf", xe,
                       M.weight_view(p["win"], compute_dtype),
                       preferred_element_type=jnp.float32)
    hproj = _expert_act(hproj, cfg, compute_dtype)
    ye = jnp.einsum("ecf,efh->ech", hproj,
                    M.weight_view(p["wout"], compute_dtype),
                    preferred_element_type=jnp.float32)
    return jnp.einsum("tec,ech->th", combine.astype(compute_dtype),
                      ye.astype(compute_dtype),
                      preferred_element_type=jnp.float32)


def _dropless_dispatch(
    p: Params, xt: jax.Array, topk_idx: jax.Array, w: jax.Array,
    cfg: ModelArgs, compute_dtype,
) -> jax.Array:
    """Dropless grouped-matmul dispatch (reference alltoall dropless
    dispatcher, token_dispatcher.py:287, re-designed for XLA): the [T*K]
    token slots sort by expert id (stable, so intra-expert order is token
    order), the expert MLPs run as ``lax.ragged_dot`` grouped matmuls over
    the sorted buffer, and a scatter-add combines weighted outputs. Every
    shape is static; no token is dropped; renormalized top-k weights make
    HF Mixtral numerics exact."""
    T, H = xt.shape
    E, K = cfg.num_experts, cfg.moe_topk
    with jax.named_scope("moe/dispatch"):
        eid = topk_idx.reshape(T * K)
        order = jnp.argsort(eid, stable=True)
        tok = jnp.arange(T * K, dtype=jnp.int32) // K  # slot -> token
        tok_sorted = tok[order]
        xs = xt[tok_sorted].astype(compute_dtype)  # [T*K, H]
        group_sizes = jnp.bincount(eid, length=E).astype(jnp.int32)
    with jax.named_scope("moe/experts"):
        hproj = _grouped_matmul(xs, M.weight_view(p["win"], compute_dtype),
                                group_sizes, compute_dtype)
        hproj = _expert_act(hproj, cfg, compute_dtype)
        ys = _grouped_matmul(hproj, M.weight_view(p["wout"], compute_dtype),
                             group_sizes, jnp.float32)
    with jax.named_scope("moe/combine"):
        ws = w.reshape(T * K)[order]
        return jnp.zeros((T, H), jnp.float32).at[tok_sorted].add(
            ys * ws[:, None])


# the margin over the expected share of the routes that the short buffer of
# a layer that holds a share leaves, and the granularity of its row count
# (a sublane tile)
_SHORT_MARGIN, _ROW_TILE = 2, 8


def short_rows(slots: int, held: int, num_experts: int) -> int:
    """Rows of the short buffer of a layer that holds ``held`` of
    ``num_experts`` experts and has ``slots`` = T*K routes: the expected
    share of the routes times a margin of 2, rounded up to a row tile, and
    never above ``slots`` (where it reaches ``slots`` the layer has one
    body)."""
    expected = -(-slots * held // num_experts)
    return min(slots, -(-_SHORT_MARGIN * expected // _ROW_TILE) * _ROW_TILE)


def _sorted_rows_mlp(rows: int, cfg: ModelArgs, compute_dtype, xt, win, wout,
                     ws, tok_sorted, mine_sorted, group_sizes):
    """The expert MLPs over the first ``rows`` of the sorted slots: gather,
    grouped matmuls, activation, weighting, scatter-add. Rows of the prefix
    that belong to no group are zeroed going in and masked coming out, so
    that nothing the grouped matmuls leave there reaches the result or a
    gradient. The mask is on ``ys`` itself, BEFORE the weights: behind the
    product its transpose hands the weights ``0 * ys``, which is NaN where
    the chip left an inf or a NaN in such a row, and from there the router's
    gradient and every block before it (PERF.md section 6, PR 40)."""
    tok, mine = tok_sorted[:rows], mine_sorted[:rows, None]
    with jax.named_scope("moe/dispatch"):
        xs = jnp.where(mine, xt[tok].astype(compute_dtype), 0)
    with jax.named_scope("moe/experts"):
        hproj = _grouped_matmul(xs, M.weight_view(win, compute_dtype),
                                group_sizes, compute_dtype)
        hproj = _expert_act(hproj, cfg, compute_dtype)
        ys = _grouped_matmul(hproj, M.weight_view(wout, compute_dtype),
                             group_sizes, jnp.float32)
    with jax.named_scope("moe/combine"):
        return jnp.zeros(xt.shape, jnp.float32).at[tok].add(
            jnp.where(mine, ys, 0.0) * ws[:rows, None])


def _short_or_full(short_body, full_body):
    """``lax.cond`` between two bodies of one signature, with one backward
    pass of its own: the forward keeps the operands alone, and the backward
    is another ``cond`` whose branch recomputes and transposes its own body.
    Plain reverse mode would have the forward return the residuals of BOTH
    bodies, zero-filled for the one not taken: the full body's rows written
    as zeros on the short path, which is the traffic the short body is
    there to save."""
    @jax.custom_vjp
    def either(short, operands, slots):
        return jax.lax.cond(short, lambda ops: short_body(*ops, *slots),
                            lambda ops: full_body(*ops, *slots), operands)

    def forward(short, operands, slots):
        return either(short, operands, slots), (short, operands, slots)

    def backward(saved, g):
        short, operands, slots = saved

        def pulled_back(body):
            return lambda ops, g: jax.vjp(
                lambda *o: body(*o, *slots), *ops)[1](g)
        return None, jax.lax.cond(short, pulled_back(short_body),
                                  pulled_back(full_body), operands, g), None

    either.defvjp(forward, backward)
    return either


def _held_dispatch(
    p: Params, xt: jax.Array, topk_idx: jax.Array, w: jax.Array,
    cfg: ModelArgs, compute_dtype, ep: int = 1, index: Any = 0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The dropless dispatch of a layer that holds experts ``[first, first
    + held)`` of the router's ``E``: it computes exactly the routes that
    fall on them and leaves out what the absent experts would have added
    (under an expert exchange, ``ep`` and ``index``: what the other chips of
    the group add, :func:`held_range`).

    The static ``T*K`` slots sort by LOCAL expert id with every route to an
    absent expert keyed ``held``, so the held experts' routes come first, in
    groups, and the tail belongs to no group. A token can choose
    ``min(K, held)`` held experts, so no static bound under ``T*K`` rows is
    safe when ``held >= K``: no route to a held expert is ever dropped,
    under any imbalance. What the layer moves follows the COUNT instead:
    where the routes that fell on a held expert fit the first
    :func:`short_rows` sorted slots (decided on the device, a step and
    microbatch at a time), gather, grouped matmuls and scatter-add run over
    that prefix alone; where they do not, over all ``T*K``. A layer whose
    short buffer would be the whole one has the one body. Returns (y [T, H]
    float32, stats): ``rows_held`` routes that fell on a held expert,
    ``rows_computed`` rows the body TAKEN handed to the grouped matmuls,
    ``short_dispatch`` 1.0 where that was the short one,
    ``held_tokens_per_expert`` [held]."""
    T, _ = xt.shape
    K = cfg.moe_topk
    held, first = held_range(cfg, ep, index)
    short_len = short_rows(T * K, held, cfg.num_experts)
    with jax.named_scope("moe/dispatch"):
        local = topk_idx.reshape(T * K) - first
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)
        order = jnp.argsort(key, stable=True)
        tok_sorted = (jnp.arange(T * K, dtype=jnp.int32) // K)[order]
        mine_sorted = mine[order]
        group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(
            jnp.int32)
        rows_held = jnp.sum(group_sizes)
    with jax.named_scope("moe/combine"):
        ws = w.reshape(T * K)[order]
    operands = (xt, p["win"], p["wout"], ws)
    slots = (tok_sorted, mine_sorted, group_sizes)
    full_body = functools.partial(_sorted_rows_mlp, T * K, cfg, compute_dtype)
    if short_len < T * K:
        short = rows_held <= short_len
        y = _short_or_full(
            functools.partial(_sorted_rows_mlp, short_len, cfg,
                              compute_dtype),
            full_body)(short, operands, slots)
    else:
        short = jnp.zeros((), bool)
        y = full_body(*operands, *slots)
    stats = {
        "rows_held": rows_held.astype(jnp.float32),
        "rows_computed": jnp.where(short, short_len, T * K).astype(
            jnp.float32),
        "short_dispatch": short.astype(jnp.float32),
        "held_tokens_per_expert": group_sizes.astype(jnp.float32)}
    return y, jax.lax.stop_gradient(stats)


def make_expert_exchange(mesh, dp_axes: Tuple[str, ...],
                         ep_axes: Tuple[str, ...]):
    """The sorted dispatchers across the chips of an ``ep`` group: returns
    ``exchange(p, xt, topk_idx, w, cfg, compute_dtype) -> (y, stats)``, the
    signature of :func:`_held_dispatch`, for a plan that shards the batch
    over ``dp_axes`` and the experts over their leading ``ep_axes``
    (``runtime/mesh.py::lower_strategy``; the rest of dp is expert-dp, whose
    groups exchange nothing).

    Inside one ``shard_map``: the chip's own tokens, their chosen experts
    and weights are all-gathered over ``ep`` (scope ``moe/exchange/gather``);
    the chip runs :func:`_held_dispatch` over the group's tokens with ``held
    = E / ep`` and ``first = axis_index x held``: sort, the short or the
    full buffer by ITS OWN count (a ``lax.cond`` a chip: one chip may take
    the full body while the others take the short one), grouped matmuls,
    scatter-add; the float32 partial results are reduce-scattered back to
    the tokens' owners (``moe/exchange/scatter``), so the sum of the ``ep``
    partials is taken in float32, in the order the uncut layer's scatter-add
    would take it. The backward pass is the transpose JAX derives: the
    gather's is a reduce-scatter and the reverse.

    Why tokens by all-gather and not routes by all-to-all: at top-K over
    ``ep`` chips a token is wanted by ``1 - (1 - 1/ep)^K`` of the chips (90 %
    at 8 over 4), so an exchange by route moves ``K (ep - 1) / ep`` rows a
    token where the gather moves ``ep - 1``; an all-to-all pays where the
    experts a token are fewer than the chips.

    ``stats`` are the group's: ``rows_held`` / ``rows_computed`` /
    ``short_dispatch`` the mean over its chips, ``held_tokens_per_expert``
    every expert's rows, ``rows_by_chip`` [ep] the routes that fell on each
    chip's experts."""
    from jax.sharding import PartitionSpec as P

    from hetu_galvatron_tpu.ops.pallas.common import on_shards

    ep = math.prod(mesh.shape[a] for a in ep_axes)
    tokens, weights = P(dp_axes, None), P(ep_axes, None, None)

    def exchange(p, xt, topk_idx, w, cfg, compute_dtype):
        def on_chip(win, wout, xt, topk_idx, w):
            with jax.named_scope("moe/exchange/gather"):
                xt, topk_idx, w = (
                    jax.lax.all_gather(a, ep_axes, axis=0, tiled=True)
                    for a in (xt, topk_idx, w))
            y, stats = _held_dispatch(
                {"win": win, "wout": wout}, xt, topk_idx, w, cfg,
                compute_dtype, ep, jax.lax.axis_index(ep_axes))
            with jax.named_scope("moe/exchange/scatter"):
                y = jax.lax.psum_scatter(y, ep_axes, scatter_dimension=0,
                                         tiled=True)
            # a row a chip of the mesh, in the order of the dp axes
            return y, jax.tree.map(lambda v: v[None], stats)

        y, stats = on_shards(
            on_chip, mesh, (weights, weights, tokens, tokens, tokens),
            (tokens, P(dp_axes)))(
                p["win"], p["wout"], xt.astype(compute_dtype), topk_idx, w)
        # [dp, ...] -> [ep, edp, ...]: the expert-dp groups of a chip's
        # experts add up, the chips of a group are averaged
        by_chip = jax.tree.map(
            lambda v: v.reshape((ep, -1) + v.shape[1:]).sum(1), stats)
        return y, {
            "rows_held": by_chip["rows_held"].mean(),
            "rows_computed": by_chip["rows_computed"].mean(),
            "short_dispatch": stats["short_dispatch"].mean(),
            "held_tokens_per_expert":
                by_chip["held_tokens_per_expert"].reshape(-1),
            "rows_by_chip": by_chip["rows_held"]}

    return exchange


def exchange_bytes(tokens: int, hidden: int, topk: int, ep: int,
                   compute_bytes: int, passes: int) -> int:
    """What one chip sends around ONE exchanged expert layer a step, from
    the shapes: its ``tokens`` (rows, chosen indices, weights) to the ``ep -
    1`` others, ``(ep - 1) / ep`` of the group's float32 partial results,
    and as much again the other way round in each backward pass; ``passes``
    = 3 under per-layer remat (forward, recompute, backward)."""
    gather = (ep - 1) * tokens * (hidden * compute_bytes + 2 * 4 * topk)
    scatter = (ep - 1) * tokens * hidden * 4
    return passes * (gather + scatter)


def apply_moe_mlp(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    compute_dtype=jnp.bfloat16,
    capacity_factor: Optional[float] = None,
    exchange: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x [B,S,H] -> (y [B,S,H], aux_loss scalar, router stats dict).

    Router per ``cfg.moe_router_type`` (see :func:`route_tokens`), dispatch
    per ``cfg.moe_dispatcher``: "capacity" (GShard one-hot einsums; across
    chips GSPMD shards their expert axis over ``ep`` and inserts the
    all-to-alls) or "dropless" (ragged grouped matmuls, exact numerics;
    across chips the sorted dispatchers, this and the held share, run inside
    ``exchange``, what a plan with ``ep`` axes hands the block as
    ``LayerOps.exchange``: :func:`make_expert_exchange`). The router is
    replicated and routes the chip's own tokens either way.
    """
    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    with jax.named_scope("moe/route"):
        topk_idx, w, aux, stats = route_tokens(p, xt, cfg, compute_dtype)
    if exchange is not None:
        y, share_stats = exchange(p, xt, topk_idx, w, cfg, compute_dtype)
        stats = {**stats, **share_stats}
    elif held_range(cfg)[0] < cfg.num_experts:
        y, share_stats = _held_dispatch(p, xt, topk_idx, w, cfg,
                                        compute_dtype)
        stats = {**stats, **share_stats}
    elif cfg.moe_dispatcher == "dropless":
        y = _dropless_dispatch(p, xt, topk_idx, w, cfg, compute_dtype)
    else:
        y = _capacity_dispatch(p, xt, topk_idx, w, cfg, compute_dtype,
                               capacity_factor)
    if "shared" in p:
        y = y + M.apply_mlp(p["shared"], xt[None], cfg,
                            compute_dtype=compute_dtype)[0]
    return y.reshape(B, S, H).astype(compute_dtype), aux, stats


def init_moe_decoder_layer(key: jax.Array, cfg: ModelArgs,
                           mixer: str = "full_attention"
                           ) -> Tuple[Params, Params]:
    """modules.init_decoder_layer with the expert layer, under ``"moe"``,
    for the dense MLP."""
    return M.init_decoder_layer(key, cfg, mixer, ff=("moe", init_moe_mlp))


def apply_moe_decoder_layer(
    p: Params,
    x: jax.Array,
    cfg: ModelArgs,
    *,
    compute_dtype=jnp.bfloat16,
    **block: Any,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """modules.apply_decoder_layer (``block``: its keywords) with the expert
    layer of ``p["moe"]`` as the feed-forward branch; returns (x, aux_loss,
    router stats) — stats feed the per-layer balance tracker (reference
    moe_utils.py:547-644)."""
    routed: Dict[str, Any] = {}
    exchange = block.get("ops", M.LayerOps()).exchange

    def experts(h):
        y, routed["aux"], routed["stats"] = apply_moe_mlp(
            p["moe"], h, cfg, compute_dtype=compute_dtype, exchange=exchange)
        return y

    x = M.apply_decoder_layer(p, x, cfg, compute_dtype=compute_dtype,
                              feed_forward=experts, **block)
    return x, routed["aux"], routed["stats"]
