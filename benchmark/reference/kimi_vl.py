"""Reference family ``kimi_vl``: Kimi-VL-A3B's two stacks, written from the
published ``config.json`` (moonshotai/Kimi-VL-A3B-Instruct, ``model_type``
``kimi_vl``), the Kimi-VL report (arXiv:2504.07491, section 2.1: MoonViT, a
native-resolution encoder initialised from SigLIP-SO400M, and an MLP
projector) and, for the decoder, DeepSeek-V2/V3 (arXiv:2405.04434,
arXiv:2412.19437; ``text_config``'s keys are ``DeepseekV3Config``'s). Fed
tensors under their public names: ``vision_tower.*``,
``multi_modal_projector.*`` and ``language_model.*``. What the catalog's row
does not hold (the tower's sizes and conventions) is the configuration
file's ``assumed`` list, one entry a choice.

One sequence holds images ``j`` with grids ``(h_j, w_j)`` in patches, packed
along one axis in image order, row-major inside an image, ``P = sum h_j w_j``.

* THE TOWER. ``x_p = W_pe patch_p + b_pe + E_j[r, c]``: the learned table
  ``[64, 64, C]`` as it is where the grid is the table's, else interpolated
  bicubically (``torch.nn.functional.interpolate(mode="bicubic",
  align_corners=False)``: cubic convolution with a = -0.75, half-pixel
  centres, border indices clamped, no antialiasing; ``bicubic_matrix``
  builds one axis as a matrix on the host). ``tower_layers`` pre-norm
  blocks: ``[q | k | v] = LN0(x) W_qkv + b``, heads of ``C / heads``; q and
  k rotated on two axes (a head's numbers are adjacent pairs (2i, 2i + 1);
  with ``f_m = theta^(-4 m / D)``, pair ``2 m`` turns by the patch's column
  times ``f_m`` and pair ``2 m + 1`` by its row times ``f_m``);
  ``softmax(q k^T / sqrt(D))`` over the patches of the SAME image, both
  ways, an image at a time; ``x += [heads] W_o + b_o``; ``x += W_1
  gelu_tanh(W_0 LN1(x) + b_0) + b_1``. Then ``LN_f``.
* THE MERGE AND THE PROJECTOR. In each image the patches ``(2a, 2b), (2a,
  2b + 1), (2a + 1, 2b), (2a + 1, 2b + 1)``, each through the projector's
  own LayerNorm, side by side; ``z = W_b gelu(W_a [.] + b_a) + b_b`` (the
  exact GELU); an image yields ``h w / 4`` rows, row-major over ``(a, b)``.
* THE DECODER. The embedding of ``tokens`` with the row at every position
  whose id is ``media_placeholder_token_id`` replaced by the next row of
  ``z``, in order; ordinary 1-D positions. Latent attention
  (``DeepseekV3Attention``, ``q_lora_rank`` null: one full-rank ``q_proj``):
  ``[q_nope | q_rope]`` a head; ``[c_kv | k_rope] = x W_kva`` (``k_rope``
  one for all heads); ``[k_nope | v] = RMSNorm(c_kv) W_kvb`` a head; RoPE at
  ``rope_theta`` on ``q_rope`` and ``k_rope``, the stored columns
  de-interleaved first; causal ``softmax(q k^T / sqrt(192)) v``; ``W_o``.
  Block 0 a SwiGLU of ``intermediate_size``; every other: ``s = sigmoid(x
  W_g)`` over all ``num_routed_experts``, the ``num_experts_per_tok`` largest
  of ``s + b`` chosen, weights the unbiased ``s`` of the chosen over their
  sum + 1e-20, times ``routed_scaling_factor``; ``y = sum_e w_e E_e(x) +
  S(x)``, the shared experts one SwiGLU of ``n_shared_experts x
  moe_intermediate_size``.
* THE LOSS: next-token cross-entropy summed over the positions
  ``batch["loss_mask"]`` marks (those whose LABEL is no placeholder).

DEPARTURES from the published model, each because the configuration's file
states it and the program under test runs the same: the share of the
experts (``[first_expert_held, + n_routed_experts)`` of
``num_routed_experts``; what the absent ones would add is left out), the
sliced vocabulary with row ``vocab_size - 1`` standing for the placeholder,
the depths (``num_hidden_layers``, ``tower_layers``), and in the FLOP count
the held experts at their EXPECTED share of the routes.

``control`` (tests and ``tools/kimivl_forward_check.py``; never the
comparison that decides ``correct``) breaks one thing by name, to show that
a comparison sees it: ``tower_block_fewer``, ``no_rotation``,
``across_images``, ``causal_tower``, ``no_interpolation``, ``merge_order``.
"""

from __future__ import annotations

import math
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops
from benchmark.reference.plain import (
    Weights,
    causal_attention,
    layer_norm,
    merge_heads,
    rms_norm,
    rotate_half,
    split_heads,
)

ROUTER_EPS = 1e-20
CONTROLS = ("tower_block_fewer", "no_rotation", "across_images",
            "causal_tower", "no_interpolation", "merge_order")
TOWER, PROJECTOR, LM = ("vision_tower.", "multi_modal_projector.",
                        "language_model.")
# queries a call of the tower's attention takes at once
QUERY_BLOCK = 1024


# ---------------------------------------------------------------------------
# the tower
# ---------------------------------------------------------------------------


def grids_of(cfg: Mapping) -> Tuple[Tuple[int, int], ...]:
    return tuple((int(h), int(w)) for h, w in cfg["image_grids"])


def bicubic_matrix(n_in: int, n_out: int) -> np.ndarray:
    """One axis of torch's bicubic ``interpolate`` (``align_corners=False``)
    as ``[n_out, n_in]``, output by output, with the four coefficients in
    the form ``get_cubic_upsample_coefficients`` has them."""
    A = -0.75
    m = np.zeros((n_out, n_in))
    for i in range(n_out):
        x = (i + 0.5) * n_in / n_out - 0.5
        x0 = math.floor(x)
        t = x - x0
        coeff = (
            ((A * (t + 1) - 5 * A) * (t + 1) + 8 * A) * (t + 1) - 4 * A,
            ((A + 2) * t - (A + 3)) * t * t + 1,
            ((A + 2) * (1 - t) - (A + 3)) * (1 - t) * (1 - t) + 1,
            ((A * (2 - t) - 5 * A) * (2 - t) + 8 * A) * (2 - t) - 4 * A)
        for k, c in enumerate(coeff):
            m[i, min(max(x0 - 1 + k, 0), n_in - 1)] += c
    return m


def position_rows(table, grids, interpolate: bool = True):
    """[P, C]: each patch's row of the position table."""
    H, W, _ = table.shape
    rows = []
    for h, w in grids:
        if (h, w) == (H, W):
            e = table
        elif interpolate:
            wr = jnp.asarray(bicubic_matrix(H, h), table.dtype)
            wc = jnp.asarray(bicubic_matrix(W, w), table.dtype)
            e = jnp.einsum("rh,hwc,sw->rsc", wr, table, wc)
        else:   # the control: the table read in place, wrapped
            e = table[jnp.arange(h) % H][:, jnp.arange(w) % W]
        rows.append(e.reshape(h * w, -1))
    return jnp.concatenate(rows)


def rotate_two_axes(x, h: int, w: int, theta: float):
    """x [B, heads, h w, D] of ONE image, its pairs side by side."""
    *lead, n, d = x.shape
    freq = theta ** (-4.0 * jnp.arange(d // 4, dtype=jnp.float32) / d)
    r, c = jnp.divmod(jnp.arange(n), w)
    ang = jnp.stack([c[:, None] * freq, r[:, None] * freq],
                    axis=-1).reshape(n, d // 2)
    pairs = x.reshape(*lead, n, d // 2, 2)
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(*lead, n, d)


def two_way_attention(q, k, v, causal: bool = False):
    """[B, heads, n, D] each: every query meets every key (``causal``, a
    control: those not after it), a block of queries at a time."""
    n = q.shape[2]
    out = []
    for lo in range(0, n, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, n)
        s = jnp.einsum("bhsd,bhtd->bhst", q[:, :, lo:hi], k) \
            / math.sqrt(q.shape[-1])
        if causal:
            s = jnp.where(jnp.arange(lo, hi)[:, None]
                          >= jnp.arange(n)[None, :], s, -jnp.inf)
        out.append(jnp.einsum("bhst,bhtd->bhsd",
                              jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, axis=2)


def linear(x, w: Weights, name: str):
    return x @ w[name + ".weight"].T + w[name + ".bias"]


def norm(x, w: Weights, name: str, eps: float):
    return layer_norm(x, w[name + ".weight"], w[name + ".bias"], eps)


def rotate_packed(x, grids, theta: float):
    """x [B, heads, P, D]: every image's patches by their own rows and
    columns."""
    out, start = [], 0
    for h, w in grids:
        out.append(rotate_two_axes(x[:, :, start:start + h * w], h, w, theta))
        start += h * w
    return jnp.concatenate(out, axis=2)


def tower_block(x, w: Weights, p: str, cfg: Mapping, grids, control):
    v = cfg["vision_config"]
    heads, eps = v["num_attention_heads"], v["layer_norm_eps"]
    q, k, val = (split_heads(a, heads) for a in jnp.split(
        linear(norm(x, w, p + "norm0", eps), w, p + "wqkv"), 3, axis=-1))
    if control != "no_rotation":
        q = rotate_packed(q, grids, v["rope_theta"])
        k = rotate_packed(k, grids, v["rope_theta"])
    # an image at a time (the control: all patches as one image)
    spans = [h * w_ for h, w_ in grids]
    if control == "across_images":
        spans = [sum(spans)]
    out, start = [], 0
    for n in spans:
        out.append(two_way_attention(
            *(a[:, :, start:start + n] for a in (q, k, val)),
            causal=control == "causal_tower"))
        start += n
    x = x + linear(merge_heads(jnp.concatenate(out, axis=2)), w, p + "wo")
    hidden = jax.nn.gelu(linear(norm(x, w, p + "norm1", eps), w,
                                p + "mlp.fc0"), approximate=True)
    return x + linear(hidden, w, p + "mlp.fc1")


def merged(y, grids, order: str = "published"):
    """[B, P, C] -> [B, P / 4, 4 C]: an image at a time, its 2 x 2 cells
    row-major, a cell's patches (2a, 2b), (2a, 2b + 1), (2a + 1, 2b),
    (2a + 1, 2b + 1) side by side (the control: columns before rows)."""
    B, _, C = y.shape
    out, start = [], 0
    for h, w in grids:
        cells = y[:, start:start + h * w].reshape(B, h // 2, 2, w // 2, 2, C)
        cells = cells.transpose((0, 1, 3, 2, 4, 5) if order == "published"
                                else (0, 1, 3, 4, 2, 5))
        out.append(cells.reshape(B, (h // 2) * (w // 2), 4 * C))
        start += h * w
    return jnp.concatenate(out, axis=1)


def image_rows(w: Weights, cfg: Mapping, patches, *,
               control: Optional[str] = None):
    """patches [B, P, 588] -> the projector's rows z [B, P / 4, hidden]."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"control {control!r}: one of {CONTROLS}")
    grids = grids_of(cfg)
    v = cfg["vision_config"]
    proj = w[TOWER + "patch_embed.proj.weight"]
    x = patches @ proj.reshape(proj.shape[0], -1).T \
        + w[TOWER + "patch_embed.proj.bias"]
    x = x + position_rows(w[TOWER + "patch_embed.pos_emb.weight"], grids,
                          interpolate=control != "no_interpolation")
    depth = cfg["tower_layers"] - (control == "tower_block_fewer")
    for i in range(depth):
        x = tower_block(x, w, TOWER + f"encoder.blocks.{i}.", cfg, grids,
                        control)
    eps = v["layer_norm_eps"]
    y = norm(norm(x, w, TOWER + "encoder.final_layernorm", eps), w,
             PROJECTOR + "pre_norm", eps)
    y = merged(y, grids, "columns" if control == "merge_order"
               else "published")
    return linear(jax.nn.gelu(linear(y, w, PROJECTOR + "linear_1"),
                              approximate=False), w, PROJECTOR + "linear_2")


# ---------------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------------


def rope_interleaved(x, theta: float):
    """x [B, heads, S, D] with its pairs side by side (2i, 2i + 1), as the
    public projections store them; positions 0..S-1."""
    *lead, S, d = x.shape
    x = jnp.swapaxes(x.reshape(*lead, S, d // 2, 2), -1, -2
                     ).reshape(*lead, S, d)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return (x * jnp.cos(ang).astype(x.dtype)
            + rotate_half(x) * jnp.sin(ang).astype(x.dtype))


def latent_attention(a, w: Weights, p: str, cfg: Mapping):
    nh = cfg["num_attention_heads"]
    dn = cfg["qk_nope_head_dim"]
    q = split_heads(a @ w[p + "q_proj.weight"].T, nh)       # [B, nh, S, 192]
    ckv, k_rope = jnp.split(a @ w[p + "kv_a_proj_with_mqa.weight"].T,
                            [cfg["kv_lora_rank"]], axis=-1)
    kv = split_heads(rms_norm(ckv, w[p + "kv_a_layernorm.weight"],
                              cfg["rms_norm_eps"])
                     @ w[p + "kv_b_proj.weight"].T, nh)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_rope = rope_interleaved(q[..., dn:], cfg["rope_theta"])
    k_rope = rope_interleaved(k_rope[:, None], cfg["rope_theta"])
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, q_rope.shape)],
                        axis=-1)
    return merge_heads(causal_attention(q, k, v)) @ w[p + "o_proj.weight"].T


def swiglu(x, w: Weights, p: str):
    return (jax.nn.silu(x @ w[p + "gate_proj.weight"].T)
            * (x @ w[p + "up_proj.weight"].T)) @ w[p + "down_proj.weight"].T


def held_experts(cfg: Mapping) -> range:
    first = cfg.get("first_expert_held", 0)
    return range(first, first + cfg["n_routed_experts"])


def routed_weights(x, w: Weights, p: str, cfg: Mapping):
    """[tokens, num_routed_experts]: a token's weight on each expert, zero
    off its chosen."""
    E, K = cfg["num_routed_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid((x @ w[p + "gate.weight"].T).astype(jnp.float32))
    _, top_i = jax.lax.top_k(s + w[p + "gate.e_score_correction_bias"], K)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + ROUTER_EPS)
    top_s = top_s * cfg["routed_scaling_factor"]
    return jnp.einsum("tk,tke->te", top_s,
                      jax.nn.one_hot(top_i, E, dtype=top_s.dtype))


def experts(x, w: Weights, p: str, cfg: Mapping,
            held: Optional[Sequence[int]] = None, shared: bool = True):
    """``x`` [tokens, hidden] -> what the ``held`` experts (default: this
    share's) and, with ``shared``, the shared experts add."""
    combine = routed_weights(x, w, p, cfg)
    out = swiglu(x, w, p + "shared_experts.") if shared \
        else jnp.zeros_like(x)
    for e in held_experts(cfg) if held is None else held:
        out = out + combine[:, e:e + 1].astype(x.dtype) * swiglu(
            x, w, p + f"experts.{e}.")
    return out


def block(x, w: Weights, i: int, cfg: Mapping):
    p, eps = LM + f"model.layers.{i}.", cfg["rms_norm_eps"]
    x = x + latent_attention(
        rms_norm(x, w[p + "input_layernorm.weight"], eps), w,
        p + "self_attn.", cfg)
    m = rms_norm(x, w[p + "post_attention_layernorm.weight"], eps)
    if i < cfg["first_k_dense_replace"]:
        return x + swiglu(m, w, p + "mlp.")
    return x + experts(m.reshape(-1, m.shape[-1]), w, p + "mlp.",
                       cfg).reshape(m.shape)


def place_images(x, tokens, z, cfg: Mapping):
    """The embedded sequence with the row at each placeholder replaced by
    the next row of ``z``, in order."""
    is_image = tokens == cfg["media_placeholder_token_id"]
    nth = jnp.clip(jnp.cumsum(is_image, axis=1) - 1, 0, z.shape[1] - 1)
    rows = jnp.take_along_axis(z, nth[..., None], axis=1)
    return jnp.where(is_image[..., None], rows, x)


def logits(w: Weights, cfg: Mapping, tokens, batch: Mapping, *,
           layers: Optional[int] = None, control: Optional[str] = None):
    x = w[LM + "model.embed_tokens.weight"][tokens]
    x = place_images(x, tokens, image_rows(w, cfg, batch["patches"].astype(
        x.dtype), control=control).astype(x.dtype), cfg)
    for i in range(cfg["num_hidden_layers"] if layers is None else layers):
        x = block(x, w, i, cfg)
    return rms_norm(x, w[LM + "model.norm.weight"], cfg["rms_norm_eps"]) \
        @ w[LM + "lm_head.weight"].T


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None, batch: Optional[Mapping] = None,
            control: Optional[str] = None):
    """The token NLL summed over the positions ``batch["loss_mask"]`` marks.
    A batch whose ``patch_grids`` are not the configuration's
    ``image_grids`` (every image of every row) gives ``nan``: the grids fix
    the shapes and are read from the file, so the batch is held to them."""
    if batch is None or "patches" not in batch:
        raise ValueError("kimi_vl: the batch holds no patches; the family "
                         "takes `batch` with patches, patch_grids, loss_mask")
    logp = jax.nn.log_softmax(
        logits(w, cfg, tokens, batch, layers=layers, control=control),
        axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    total = jnp.sum(nll * batch["loss_mask"].astype(nll.dtype))
    same = jnp.all(batch["patch_grids"]
                   == jnp.asarray(cfg["image_grids"], jnp.int32)[None])
    return jnp.where(same, total, jnp.nan)


# ---------------------------------------------------------------------------
# what attends, and the operations of a token
# ---------------------------------------------------------------------------


def attention_blocks(config: Mapping) -> List[Dict[str, int]]:
    """The tower's blocks, then the decoder's. A patch meets every patch of
    its own image and no other: ``pairs`` is the sum of the images' squares,
    ``positions`` their sum; heads of ``hidden_size / heads`` (72). The
    decoder's: q/k 192 wide and v 128, the causal span."""
    v = config["vision_config"]
    heads = v["num_attention_heads"]
    width = v["hidden_size"] // heads
    patches = config["image_patches"]
    tower = {"heads": heads, "kv_heads": heads, "qk_head_dim": width,
             "v_head_dim": width, "hidden": v["hidden_size"],
             "positions": sum(patches), "pairs": sum(n * n for n in patches)}
    latent = {"qk_head_dim": config["qk_nope_head_dim"]
              + config["qk_rope_head_dim"],
              "v_head_dim": config["v_head_dim"]}
    return ([dict(tower) for _ in range(config["tower_layers"])]
            + [dict(latent) for _ in range(config["num_hidden_layers"])])


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """A token of the step is a position of the decoder's sequence. The
    tower: a block's four projections and its core by its entry
    (``flops.attention_flops_per_token``: ``positions / seq`` a token), its
    two-matrix MLP, the patch map over the patches and the projector's two
    maps over the merged rows. The decoder: a block's latent projections AS
    THEY ARE (``q``, ``kv_a``, ``kv_b``, ``o``) and the causal core of its
    entry; the dense block's SwiGLU; an expert block's router over all
    routed experts, the held experts at ``num_experts_per_tok * held /
    routed`` routes a token and the shared experts; the head."""
    v = config["vision_config"]
    entries = sizes.attention_blocks()
    tower = [a for a in entries if a.positions]
    latent = [a for a in entries if not a.positions]
    C = v["hidden_size"]
    cell = v["merge_kernel_size"][0] * v["merge_kernel_size"][1]
    patches = sum(config["image_patches"])
    per_seq = (
        sum(flops.attention_flops_per_token(sizes, a) * sizes.seq
            + 2 * 2 * C * v["intermediate_size"] * a.positions
            for a in tower)
        + 2 * v["num_channels"] * v["patch_size"] ** 2 * C * patches
        + 2 * (cell * C) * (cell * C + sizes.hidden) * (patches // cell))
    H, nh = sizes.hidden, config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    rkv = config["kv_lora_rank"]
    proj = 2 * (H * nh * (dn + dr) + H * (rkv + dr)
                + rkv * nh * (dn + dv) + nh * dv * H)
    cores = sum(2 * (a.heads or nh) * (a.qk_head_dim + a.v_head_dim)
                * flops.causal_pairs(sizes.seq, a.window) / sizes.seq
                for a in latent)
    dense = 2 * 3 * H * config["intermediate_size"]
    routes = (config["num_experts_per_tok"] * config["n_routed_experts"]
              / config["num_routed_experts"])
    expert = 2 * 3 * H * config["moe_intermediate_size"]
    sparse = (2 * H * config["num_routed_experts"] + routes * expert
              + config["n_shared_experts"] * expert)
    L, n_dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    return (per_seq / sizes.seq + L * proj + cores + n_dense * dense
            + (L - n_dense) * sparse + flops.head_flops_per_token(sizes))
