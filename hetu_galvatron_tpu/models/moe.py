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
* ``dropless`` (sort + grouped matmuls, :func:`_held_dispatch`): token
  slots are sorted by expert and the MLPs of the experts the layer holds, all
  or a share, run as grouped ragged matmuls — no token is ever dropped and no
  capacity buffer is materialized (the reference's alltoall dropless
  dispatcher, token_dispatcher.py:287). Static [T*K] shapes keep it
  jit-clean; HF Mixtral numerics reproduce exactly (see
  tests/models/test_moe.py Mixtral parity). The grouped matmuls
  (:func:`_grouped_matmul`) are ``lax.ragged_dot``, the XLA form: every CPU
  run, and on a TPU any plan that leaves a block's rows or experts to GSPMD
  to cut. Where a block's rows and weights are whole on the chip that runs
  it (a mesh of one TPU, or inside :func:`make_expert_exchange`) the plan
  hands the block ``LayerOps.grouped``, the Pallas kernels of
  ops/pallas/grouped_matmul.py, which run the forward product and both
  gradients at every shape that fits their tiles (widths of whole lane
  tiles) and leave the rest to ``lax.ragged_dot``.

Across chips (``parallel.global_ep_deg``, the ``ep`` axes a plan carves from
dp): the ``capacity`` einsums are left to GSPMD, which turns their sharded
``expert`` axis into all-to-alls; the sorted dispatcher runs inside
:func:`make_expert_exchange`'s ``shard_map``: a chip's tokens, choices and
weights are all-gathered over ``ep``, each chip runs :func:`_held_dispatch`
over the group's tokens for the experts it holds, and the partial results
are reduce-scattered back to the tokens' owners. Tokens move, expert weights
never do.

Routers: softmax top-k (optionally with the DeepSeek-style expert-bias
selection correction, reference router.py expert_bias) and sinkhorn load
balancing (selection via a no-grad sinkhorn normalization, weights via
sigmoid/softmax of the raw logits — reference sinkhorn_load_balancing).
"""

from __future__ import annotations

import functools
import math
from fractions import Fraction
from typing import Any, Dict, NamedTuple, Optional, Tuple

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(rows: jax.Array, weights: jax.Array,
                    group_sizes: jax.Array, out_dtype,
                    grouped=None) -> jax.Array:
    """The expert layer's grouped matmul: sorted ``rows`` [M, K] through
    ``weights`` [G, K, N] by ``group_sizes`` [G]. ``lax.ragged_dot``
    accumulates in float32 and writes once, in ``out_dtype``: the dtype the
    product's first consumer reads. The compute dtype is the operands' own.

    ``grouped`` (``LayerOps.grouped``: ops/pallas/grouped_matmul.py, where a
    plan hands it) runs each of the three products, this one and the
    backward pass's two, at the same dtypes with the same float32
    accumulation and one rounding, and writes zeros in the rows of no
    group; a product whose shapes fit it no tile (it answers None), and
    every product where it is None, is ``lax.ragged_dot``'s.

    The backward pass keeps both transposed products in the compute dtype:
    the cotangent is rounded to it going in (as a dense matmul's is at
    default precision, and as the kernel does with a float32 operand
    anyway), and the gradients to the rows and to the weights come out in
    it, which is what the cast behind the one and the transpose of
    ``weight_view`` behind the other round them to. Plain reverse mode of a
    product asked for in float32 makes both of them mixed bfloat16 x float32
    kernels that write float32 for the next instruction to round. At
    float32 both passes are plain reverse mode's, number for number."""
    out = grouped and grouped("fwd", rows, weights, group_sizes, out_dtype)
    if out is None:
        out = jax.lax.ragged_dot(rows, weights, group_sizes,
                                 preferred_element_type=out_dtype)
    return out


def _grouped_matmul_fwd(rows, weights, group_sizes, out_dtype, grouped):
    return (_GROUPED_MATMUL(rows, weights, group_sizes, out_dtype, grouped),
            (rows, weights, group_sizes))


def _grouped_matmul_bwd(out_dtype, grouped, saved, g):
    rows, weights, group_sizes = saved
    # JAX's own transposes of the product in the compute dtype: the modes
    # and dimension numbers plain reverse mode would have emitted
    product = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes,
                                preferred_element_type=rows.dtype)
    g = g.astype(rows.dtype)
    kernel = lambda mode, a, b: grouped and grouped(  # noqa: E731
        mode, a, b, group_sizes, rows.dtype)
    d_weights = kernel("dweights", rows, g)
    if d_weights is None:
        d_weights, = jax.linear_transpose(
            lambda w: product(rows, w), weights)(g)
    d_rows = kernel("drows", g, weights)
    if d_rows is None:
        d_rows, = jax.linear_transpose(lambda r: product(r, weights), rows)(g)
    return d_rows, d_weights, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
# the rule's forward calls the product it belongs to, whatever a test has put
# under the module's name
_GROUPED_MATMUL = _grouped_matmul


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


# a layer that holds a share computes the sorted slots in chunks: the first,
# which always runs, is the expected share of the routes times the model's
# ``moe_capacity_factor`` (5/4 unless a configuration states another: rows
# provisioned over the expected share, as for the capacity dispatcher, with
# nothing dropped behind them), and each counted pass behind it the expected
# share times _CHUNK_SHARE (as (numerator, denominator)), rounded up to a
# sublane tile of rows. On the
# chip a pass of 512 or of 1,024 rows costs 1.6 ms at 8 held experts (their
# weights read and their float32 gradients added once more) and one of 2,048
# rows 3.9 (the scatter-adds, by the row): PERF.md section 6, PR 52
_CHUNK_SHARE, _ROW_TILE = (1, 4), 8


def _share_rows(slots: int, held: int, num_experts: int,
                share: Tuple[int, int]) -> int:
    expected = -(-slots * held // num_experts)
    return min(slots, -(-share[0] * expected // (share[1] * _ROW_TILE))
               * _ROW_TILE)


def short_rows(slots: int, held: int, num_experts: int,
               margin: float = 1.25) -> int:
    """Rows of the first chunk of a layer that holds ``held`` of
    ``num_experts`` experts and has ``slots`` = T*K routes: the expected
    share of the routes times ``margin`` (``cfg.moe_capacity_factor``: a
    quarter over unless the model states another), rounded up to a row tile,
    and never above ``slots`` (where it reaches ``slots`` the layer has the
    one body and no loop)."""
    share = Fraction(margin).limit_denominator(64)
    return _share_rows(slots, held, num_experts,
                       (share.numerator, share.denominator))


def overflow_rows(slots: int, held: int, num_experts: int) -> int:
    """Rows of one counted pass over what the first chunk did not reach: a
    quarter of the expected share of the routes, rounded up to a row tile."""
    return _share_rows(slots, held, num_experts, _CHUNK_SHARE)


# the grouped matmuls' widths are padded with zeros to what libtpu's kernel
# runs fast at (my chip runs, PR 66, 6,144 rows over 8 groups, forward and
# both gradients of the two products): an expert width that is no whole
# number of lane tiles to a multiple of 256 (Nemotron-H's 1856: 23.5 ms as it
# is, 23.4 at 1920, 9.6 at 2048), a hidden width that is no multiple of 256 to
# a multiple of 512 (its 2688: 9.6 ms, 7.5 at 2816, 6.6 at 3072). Every
# activation maps 0 to 0, so the padding adds nothing to a result or a
# gradient; widths under a lane tile are a test's model and are left alone.
# (The rules leave every width the benchmark's other cells run as it is:
# whether 1408 and 896 columns gain as well is PERF.md section 7's.)
_LANES = 128


def _padded_width(n: int, unless: int, to: int) -> int:
    return n if n < _LANES or n % unless == 0 else -(-n // to) * to


def _whole_tiles(xs, win, wout):
    """``xs`` [R, H], ``win`` [held, H, F] (or gate | up side by side, 2 F)
    and ``wout`` [held, F, H] with ``F`` and ``H`` padded with
    zeros as the comment above says; as they are where nothing is padded."""
    held, F, H = wout.shape
    f, h = _padded_width(F, _LANES, 256) - F, _padded_width(H, 256, 512) - H
    if not (f or h):
        return xs, win, wout
    win = jnp.pad(win.reshape(held, H, -1, F),
                  ((0, 0), (0, h), (0, 0), (0, f))).reshape(held, H + h, -1)
    return (jnp.pad(xs, ((0, 0), (0, h))), win,
            jnp.pad(wout, ((0, 0), (0, f), (0, h))))


def _sorted_rows_mlp(cfg: ModelArgs, compute_dtype, rows, win, wout, w_rows,
                     mine, group_sizes, grouped=None):
    """The expert MLPs over one chunk of the sorted slots, gathered: ``rows``
    [R, H] through the grouped matmuls (``win`` / ``wout`` in the compute
    dtype) and the activation, times the routes' weights; [R, H] float32 for
    the combine. Rows of the chunk that belong to no group (``mine`` false;
    None where every row has one) are zeroed going in and masked coming out,
    so that nothing the grouped matmuls leave there reaches the result or a
    gradient. The mask is on ``ys``
    itself, BEFORE the weights: behind the product its transpose hands the
    weights ``0 * ys``, which is NaN where the chip left an inf or a NaN in
    such a row, and from there the router's gradient and every block before
    it (PERF.md section 6, PR 40)."""
    with jax.named_scope("moe/dispatch"):
        xs = rows.astype(compute_dtype)
        if mine is not None:
            xs = jnp.where(mine, xs, 0)
    with jax.named_scope("moe/experts"):
        hidden = xs.shape[1]
        xs, win, wout = _whole_tiles(xs, win, wout)
        # (without kernels the call is the one it was, argument for
        # argument: tests put their own product in its place)
        product = _grouped_matmul if grouped is None else (
            lambda *a: _grouped_matmul(*a, grouped))
        hproj = product(xs, win, group_sizes, compute_dtype)
        hproj = _expert_act(hproj, cfg, compute_dtype)
        ys = product(hproj, wout, group_sizes, jnp.float32)[:, :hidden]
    with jax.named_scope("moe/combine"):
        if mine is not None:
            ys = jnp.where(mine, ys, 0.0)
        return ys * w_rows[:, None]


def layer_body(slots: int, held: int, num_experts: int,
               margin: float = 1.25) -> str:
    """The body a sorted layer of ``slots`` routes compiles to: ``whole`` (no
    loop) or ``counted <first chunk's rows>/<slots> +<a pass's rows>``."""
    first = short_rows(slots, held, num_experts, margin)
    return "whole" if first >= slots else (
        f"counted {first}/{slots} +{overflow_rows(slots, held, num_experts)}")


class _Sorted(NamedTuple):
    """What the sort of a layer's ``T*K`` slots hands its chunks; nothing
    here is differentiated."""
    order: jax.Array    # [T*K] the slot each sorted slot came from
    ws: jax.Array       # [T*K] the routes' weights in sorted order
    ends: jax.Array     # [held] the sorted slot a held expert's group ends at
    passes: jax.Array   # [] the counted passes behind the first chunk
    # [T*K] the sorted slot of each slot, where the first chunk is them all
    inv: Optional[jax.Array]


def _counted_rows_mlp(cfg: ModelArgs, compute_dtype, first_len: int,
                      chunk_len: int, grouped=None):
    """``layer((xt, win, wout, w), slots: _Sorted) -> y [T, H] float32``:
    :func:`_sorted_rows_mlp` over the first ``first_len`` sorted slots, then
    ``slots.passes`` (counted on the device) times over the next
    ``chunk_len``, each scatter-added into the one result; ``w`` [T*K] are
    the routes' weights in slot order. A chunk's group sizes are the counted
    ones clipped to it, so a chunk that ends inside a group computes the
    part of the group it holds; the last pass starts where it still fits the
    slots, and the rows it shares with the pass before are masked out of it.
    Where the first chunk is every slot (``slots.inv``) there is the one
    body, no loop and no scatter-add: the sort permutes all ``T*K`` slots, so
    a token's ``K`` rows are gathered by its inverse and summed in float32,
    the result and the rows' cotangent alike.

    One backward pass of its own, which keeps the operands alone: the first
    chunk's pull-back, then the same counted loop, each pass recomputing its
    own chunk and adding to the cotangents of the rows, the expert weights
    and ``ws``, whose cotangent a sort by ``order`` takes back to ``w``'s
    slots. Plain reverse mode cannot transpose a loop whose trip count is
    traced, and would keep every pass's residuals if it could."""
    def chunk_of(xt, slots, lo, length):
        """(the tokens of the ``length`` sorted slots from ``lo``, their
        rows, the MLP over them as a function of what is differentiated)."""
        ends = slots.ends
        at = jnp.minimum(lo, slots.order.shape[0] - length)
        sorted_slot = at + jnp.arange(length)
        # every slot of a layer that holds every expert has a group: no mask
        mine = None if slots.inv is not None and len(ends) == cfg.num_experts \
            else ((sorted_slot >= lo) & (sorted_slot < ends[-1]))[:, None]
        starts = jnp.concatenate([jnp.zeros_like(ends[:1]), ends[:-1]])
        sizes = (jnp.clip(ends, at, at + length)
                 - jnp.clip(starts, at, at + length))
        with jax.named_scope("moe/dispatch"):
            tok = jax.lax.dynamic_slice(
                slots.order, (at,), (length,)) // cfg.moe_topk
            rows = xt[tok]

        def mlp(rows, win, wout, ws):
            return _sorted_rows_mlp(
                cfg, compute_dtype, rows, win, wout,
                jax.lax.dynamic_slice(ws, (at,), (length,)), mine, sizes,
                grouped)
        return tok, rows, mlp

    def to_tokens(acc, tok, rows, slots):
        """``acc`` (zeros where ``inv``) + the sorted ``rows`` by token."""
        if slots.inv is None:
            return acc.at[tok].add(rows)
        by_token = rows[slots.inv].reshape(len(acc), cfg.moe_topk, -1)
        return by_token.sum(1, dtype=jnp.float32).astype(acc.dtype)

    def add_chunk(y, lo, length, xt, win, wout, slots):
        tok, rows, mlp = chunk_of(xt, slots, lo, length)
        out = mlp(rows, win, wout, slots.ws)
        with jax.named_scope("moe/combine"):
            return to_tokens(y, tok, out, slots)

    def pull_chunk(cots, g, lo, length, xt, win, wout, slots):
        tok, rows, mlp = chunk_of(xt, slots, lo, length)
        pull = jax.vjp(mlp, rows, win, wout, slots.ws)[1]
        with jax.named_scope("moe/combine"):
            d_rows, *d_rest = pull(g[tok])
        with jax.named_scope("moe/dispatch"):
            d_xt = to_tokens(cots[0], tok, d_rows, slots)
        with jax.named_scope("moe/experts"):
            return (d_xt, *(c + d.astype(c.dtype)
                            for c, d in zip(cots[1:], d_rest)))

    def views(operands):
        xt, win, wout, _ = operands
        return (xt, M.weight_view(win, compute_dtype),
                M.weight_view(wout, compute_dtype))

    def counted(first, chunk, slots):
        """``chunk`` folded over the passes behind ``first``."""
        if slots.inv is not None:
            return first
        return jax.lax.fori_loop(
            0, slots.passes,
            lambda i, acc: chunk(acc, first_len + i * chunk_len, chunk_len),
            first)

    @jax.custom_vjp
    def layer(operands, slots):
        ops = views(operands)
        add = lambda y, lo, length: add_chunk(  # noqa: E731
            y, lo, length, *ops, slots)
        return counted(
            add(jnp.zeros(ops[0].shape, jnp.float32), 0, first_len), add,
            slots)

    def forward(operands, slots):
        return layer(operands, slots), (operands, slots)

    def backward(saved, g):
        operands, slots = saved
        ops = views(operands)
        pull = lambda cots, lo, length: pull_chunk(  # noqa: E731
            cots, g, lo, length, *ops, slots)
        zeros = tuple(jnp.zeros(a.shape, a.dtype) for a in operands)
        *d_ops, d_ws = counted(pull(zeros, 0, first_len), pull, slots)
        with jax.named_scope("moe/combine"):
            # sorted slot i came from slot order[i]: a sort by ``order``
            # is the scatter back, at a sort's price
            _, d_w = jax.lax.sort((slots.order, d_ws), num_keys=1)
        return (*d_ops, d_w), None

    layer.defvjp(forward, backward)
    return layer


def _held_dispatch(
    p: Params, xt: jax.Array, topk_idx: jax.Array, w: jax.Array,
    cfg: ModelArgs, compute_dtype, ep: int = 1, index: Any = 0,
    grouped: Optional[Any] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The dropless dispatcher, sorted: a layer that holds experts ``[first,
    first + held)`` of the router's ``E``, all or a share, computes exactly
    the routes that fall on them and leaves out what absent experts would
    have added (under an expert exchange, ``ep`` and ``index``: what the
    other chips of the group add, :func:`held_range`).

    The static ``T*K`` slots sort by LOCAL expert id with every route to an
    absent expert keyed ``held``, so the held experts' routes come first, in
    groups, and the tail belongs to no group. One stable sort carries the
    slots' indices and the routes' weights along, so nothing is gathered by
    a slot at a time, and the groups' ends are counted from the keys. A
    token can choose ``min(K, held)`` held experts, so no static bound under
    ``T*K`` rows is safe when ``held >= K``: no route to a held expert is
    ever dropped, under any imbalance. What the layer moves follows the
    COUNT instead, in chunks (:func:`_counted_rows_mlp`): gather, grouped
    matmuls and scatter-add run over the first :func:`short_rows` sorted
    slots, which hold every counted route of a balanced step, and then over
    as many further chunks of :func:`overflow_rows` as the count asks for
    (decided on the device, a step and microbatch at a time: none on a
    balanced step, ``T*K`` rows in all where every route fell here). A layer
    whose first chunk is all ``T*K`` slots (every expert held, or four
    fifths of them) has the one body and no loop, and moves its rows by the
    permutation and its inverse (a second sort, of ``(order, iota)``).
    Returns (y [T, H] float32, stats), none where every expert is held, else
    ``rows_held`` routes that fell on a held expert, ``overflow_chunks``
    passes taken behind the first chunk, ``rows_computed`` rows handed to the
    grouped matmuls (first chunk and passes), ``short_dispatch`` 1.0 where
    the first chunk was shorter than ``T*K`` and no pass was taken,
    ``held_tokens_per_expert`` [held]."""
    T, _ = xt.shape
    K = cfg.moe_topk
    held, first = held_range(cfg, ep, index)
    first_len = short_rows(T * K, held, cfg.num_experts,
                           cfg.moe_capacity_factor)
    chunk_len = overflow_rows(T * K, held, cfg.num_experts)
    w = w.reshape(T * K)
    with jax.named_scope("moe/dispatch"):
        key = topk_idx.reshape(T * K) - first
        if held < cfg.num_experts:
            key = jnp.where((key >= 0) & (key < held), key, held)
        key = key.astype(jnp.int32)
        slot = jnp.arange(T * K, dtype=jnp.int32)
        _, order, ws = jax.lax.sort((key, slot, jax.lax.stop_gradient(w)),
                                    num_keys=1, is_stable=True)
        inv = (jax.lax.sort((order, slot), num_keys=1)[1]
               if first_len >= T * K else None)
        ends = jnp.sum(key[:, None] <= jnp.arange(held, dtype=jnp.int32),
                       axis=0, dtype=jnp.int32)
        group_sizes = jnp.diff(ends, prepend=0)
        rows_held = ends[-1]
        passes = (jnp.maximum(rows_held - first_len, 0) + chunk_len - 1
                  ) // chunk_len
    y = _counted_rows_mlp(cfg, compute_dtype, first_len, chunk_len, grouped)(
        (xt, p["win"], p["wout"], w), _Sorted(order, ws, ends, passes, inv))
    if held == cfg.num_experts:
        return y, {}
    stats = {
        "rows_held": rows_held.astype(jnp.float32),
        "rows_computed": (first_len + passes * chunk_len).astype(
            jnp.float32),
        "overflow_chunks": passes.astype(jnp.float32),
        "short_dispatch": ((passes == 0) & (first_len < T * K)).astype(
            jnp.float32),
        "held_tokens_per_expert": group_sizes.astype(jnp.float32)}
    return y, jax.lax.stop_gradient(stats)


def make_expert_exchange(mesh, dp_axes: Tuple[str, ...],
                         ep_axes: Tuple[str, ...]):
    """The sorted dispatchers across the chips of an ``ep`` group: returns
    ``exchange(p, xt, topk_idx, w, cfg, compute_dtype, grouped=None) -> (y,
    stats)``, the
    signature of :func:`_held_dispatch`, for a plan that shards the batch
    over ``dp_axes`` and the experts over their leading ``ep_axes``
    (``runtime/mesh.py::lower_strategy``; the rest of dp is expert-dp, whose
    groups exchange nothing).

    Inside one ``shard_map``: the chip's own tokens, their chosen experts
    and weights are all-gathered over ``ep`` (scope ``moe/exchange/gather``);
    the chip runs :func:`_held_dispatch` over the group's tokens with ``held
    = E / ep`` and ``first = axis_index x held``: sort, the first chunk and
    as many further ones as ITS OWN count asks for (a loop a chip, with no
    collective inside: one chip may take a pass or several while the others
    take none), grouped matmuls, scatter-add; the float32 partial results
    are reduce-scattered back to the tokens' owners
    (``moe/exchange/scatter``), so the sum of the ``ep`` partials is taken in
    float32, in the order the uncut layer's scatter-add would take it. The backward pass is the transpose JAX derives: the
    gather's is a reduce-scatter and the reverse.

    Why tokens by all-gather and not routes by all-to-all: at top-K over
    ``ep`` chips a token is wanted by ``1 - (1 - 1/ep)^K`` of the chips (90 %
    at 8 over 4), so an exchange by route moves ``K (ep - 1) / ep`` rows a
    token where the gather moves ``ep - 1``; an all-to-all pays where the
    experts a token are fewer than the chips.

    ``stats`` are the group's: ``rows_held`` / ``rows_computed`` /
    ``overflow_chunks`` / ``short_dispatch`` the MEAN over its chips (0.25
    ``overflow_chunks`` over four chips is one chip that took one pass; the
    share of them that took no pass), ``held_tokens_per_expert`` every
    expert's rows, and by chip, [ep] each: ``rows_by_chip`` the routes that
    fell on a chip's experts, ``passes_by_chip`` the counted passes it took
    behind its first chunk. The step runs at the pace of the MAX over the
    chips, not of the mean: the others wait for the fullest at the
    reduce-scatter."""
    from jax.sharding import PartitionSpec as P

    from hetu_galvatron_tpu.ops.pallas.common import on_shards

    ep = math.prod(mesh.shape[a] for a in ep_axes)
    tokens, weights = P(dp_axes, None), P(ep_axes, None, None)

    def exchange(p, xt, topk_idx, w, cfg, compute_dtype, grouped=None):
        def on_chip(win, wout, xt, topk_idx, w):
            with jax.named_scope("moe/exchange/gather"):
                xt, topk_idx, w = (
                    jax.lax.all_gather(a, ep_axes, axis=0, tiled=True)
                    for a in (xt, topk_idx, w))
            y, stats = _held_dispatch(
                {"win": win, "wout": wout}, xt, topk_idx, w, cfg,
                compute_dtype, ep, jax.lax.axis_index(ep_axes), grouped)
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
            "overflow_chunks": by_chip["overflow_chunks"].mean(),
            "short_dispatch": stats["short_dispatch"].mean(),
            "held_tokens_per_expert":
                by_chip["held_tokens_per_expert"].reshape(-1),
            "rows_by_chip": by_chip["rows_held"],
            "passes_by_chip": by_chip["overflow_chunks"]}

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
    grouped: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x [B,S,H] -> (y [B,S,H], aux_loss scalar, router stats dict).

    Router per ``cfg.moe_router_type`` (see :func:`route_tokens`), dispatch
    per ``cfg.moe_dispatcher``: "capacity" (GShard one-hot einsums; across
    chips GSPMD shards their expert axis over ``ep`` and inserts the
    all-to-alls) or "dropless": the sorted dispatcher at whatever share of
    its experts the layer holds (:func:`_held_dispatch`: ragged grouped
    matmuls, exact numerics; across chips it runs inside ``exchange``, what
    a plan with ``ep`` axes hands the block as ``LayerOps.exchange``:
    :func:`make_expert_exchange`; ``grouped``, its ``LayerOps.grouped``, are
    the sorted dispatcher's grouped-matmul kernels, :func:`_grouped_matmul`).
    The router is replicated and routes the chip's own tokens either way."""
    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    with jax.named_scope("moe/route"):
        topk_idx, w, aux, stats = route_tokens(p, xt, cfg, compute_dtype)
    if exchange is not None or held_range(cfg)[0] < cfg.num_experts \
            or cfg.moe_dispatcher == "dropless":
        y, share_stats = (exchange or _held_dispatch)(
            p, xt, topk_idx, w, cfg, compute_dtype, grouped=grouped)
        stats = {**stats, **share_stats}
    else:
        y = _capacity_dispatch(p, xt, topk_idx, w, cfg, compute_dtype,
                               capacity_factor)
    if "shared" in p:
        y = y + M.apply_mlp(p["shared"], xt[None], cfg,
                            compute_dtype=compute_dtype)[0]
    return y.reshape(B, S, H).astype(compute_dtype), aux, stats


def init_moe_decoder_layer(key: jax.Array, cfg: ModelArgs,
                           mixer: Optional[str] = "full_attention"
                           ) -> Tuple[Params, Params]:
    """modules.init_decoder_layer with the expert layer, under ``"moe"``,
    for the dense MLP (``mixer`` None: a block of one branch, the experts
    alone)."""
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
    ops = block.get("ops", M.LayerOps())

    def experts(h):
        y, routed["aux"], routed["stats"] = apply_moe_mlp(
            p["moe"], h, cfg, compute_dtype=compute_dtype,
            exchange=ops.exchange, grouped=ops.grouped)
        return y

    x = M.apply_decoder_layer(p, x, cfg, compute_dtype=compute_dtype,
                              feed_forward=experts, **block)
    return x, routed["aux"], routed["stats"]
