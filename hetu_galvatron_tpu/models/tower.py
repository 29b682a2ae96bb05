"""A tower of image patches in front of the decoder (Kimi-VL's MoonViT,
arXiv:2504.07491 section 2.1): images at their native resolution, each a
grid of ``h x w`` patches, packed along one axis in image order (row-major
inside an image); a patch projection plus a learned 2-D position table
interpolated bicubically to the image's grid; pre-norm blocks whose heads
attend both ways inside an image and nowhere else, q and k rotated on two
axes; a final norm; the ``2 x 2`` merge and the projector, whose rows take
the embedding's place at the sequence's image positions.

The grids of a sequence are static (``ModelArgs.image_grids``, a traffic's
own list), so everything that depends on them is made on the host with
``numpy`` and is a constant of the step program: the two 1-D interpolation
matrices an image, the rotation's cos and sin, the image of each patch (the
attention core's ``segment_ids``), the merge's gather and the positions the
projector's rows go to (``place_images`` finds those from the ids). No
``[P, P]`` array exists: the core is the decoder's (``LayerOps.sdpa``: the
Pallas flash kernels on a TPU, ``causal=False`` with segments).
"""

from __future__ import annotations

import math
from functools import lru_cache
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.models import modules as M

Params = Dict[str, Any]
Grids = Tuple[Tuple[int, int], ...]


# ---------------------------------------------------------------------------
# what the grids fix, on the host
# ---------------------------------------------------------------------------


def grids_of(cfg: ModelArgs) -> Grids:
    return tuple((int(h), int(w)) for h, w in cfg.image_grids or ())


def _cubic(x: np.ndarray, a: float = -0.75) -> np.ndarray:
    """The cubic convolution kernel (Keys 1981) at ``a`` = -0.75, the value
    ``torch.nn.functional.interpolate(mode="bicubic")`` uses."""
    x = np.abs(x)
    return np.where(
        x <= 1.0, ((a + 2.0) * x - (a + 3.0)) * x * x + 1.0,
        np.where(x < 2.0, ((a * x - 5.0 * a) * x + 8.0 * a) * x - 4.0 * a,
                 0.0))


@lru_cache(maxsize=None)
def bicubic_weights(n_in: int, n_out: int) -> np.ndarray:
    """``[n_out, n_in]``: one axis of ``interpolate(mode="bicubic",
    align_corners=False)`` without antialiasing, as a matrix. Output ``i``
    lies at ``(i + 1/2) n_in / n_out - 1/2`` of the input (half-pixel
    centres), takes the four inputs around it by the cubic kernel, and an
    index past the border is the border's (clamped), so its weight is added
    there."""
    out = np.zeros((n_out, n_in), np.float64)
    src = (np.arange(n_out) + 0.5) * (n_in / n_out) - 0.5
    base = np.floor(src).astype(np.int64)
    for tap in (-1, 0, 1, 2):
        at = base + tap
        np.add.at(out, (np.arange(n_out), np.clip(at, 0, n_in - 1)),
                  _cubic(src - at))
    return out.astype(np.float32)


@lru_cache(maxsize=None)
def rotation_tables(grids: Grids, head_dim: int, theta: float
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """cos and signed sin ``[P, head_dim]`` of the two-axis rotation: a
    head's numbers are ``head_dim / 2`` adjacent pairs (2i, 2i + 1); with
    ``f_m = theta^(-4 m / head_dim)``, pair ``2 m`` turns by the patch's
    column times ``f_m`` and pair ``2 m + 1`` by its row times ``f_m``. The
    sin is negative at a pair's first number, so that ``x cos + swap(x)
    sin`` is the rotation (:func:`rotate_pairs`)."""
    freq = theta ** (-4.0 * np.arange(head_dim // 4) / head_dim)
    angles = []
    for h, w in grids:
        r, c = np.divmod(np.arange(h * w), w)
        both = np.stack([c[:, None] * freq, r[:, None] * freq], axis=-1)
        angles.append(both.reshape(h * w, head_dim // 2))
    ang = np.repeat(np.concatenate(angles), 2, axis=-1)
    sign = np.tile(np.array([-1.0, 1.0]), head_dim // 2)
    return (np.cos(ang).astype(np.float32),
            (np.sin(ang) * sign).astype(np.float32))


@lru_cache(maxsize=None)
def image_of_patch(grids: Grids) -> np.ndarray:
    """``[P]``: which image a patch belongs to."""
    return np.concatenate([np.full(h * w, j, np.int32)
                           for j, (h, w) in enumerate(grids)])


@lru_cache(maxsize=None)
def merge_order(grids: Grids, merge: Tuple[int, int]) -> np.ndarray:
    """``[P]``: the patches in the order the merge reads them: an image at
    a time, its ``(a, b)`` cells row-major, a cell's ``mh x mw`` patches
    row-major, so that ``x[:, order].reshape(B, P / (mh mw), mh mw C)`` is
    the merged rows."""
    mh, mw = merge
    out, start = [], 0
    for h, w in grids:
        idx = start + np.arange(h * w).reshape(h // mh, mh, w // mw, mw)
        out.append(idx.transpose(0, 2, 1, 3).reshape(-1))
        start += h * w
    return np.concatenate(out).astype(np.int32)


def pairs_masked(grids: Grids) -> int:
    """The (query, key) pairs one sequence's tower attention leaves, a
    block and head: a patch meets its own image both ways. (What its core
    computes for them is the core's to say: the flash kernels' tiles by the
    calls as built, ``flash_attention.TWO_WAY_CALLS``, and the chunk ranges
    ``image_of_patch``'s ids leave their loops; the XLA core makes the
    whole square of the packed patches.)"""
    return sum((h * w) ** 2 for h, w in grids)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def _norm(width: int) -> Tuple[Params, Params]:
    return ({"scale": jnp.ones((width,), jnp.float32),
             "bias": jnp.zeros((width,), jnp.float32)},
            {"scale": ("embed",), "bias": ("embed",)})


def _linear(key, n_in: int, n_out: int, std: float, names
            ) -> Tuple[Params, Params]:
    return ({"w": M._normal(key, (n_in, n_out), std),
             "b": jnp.zeros((n_out,), jnp.float32)},
            {"w": names, "b": (names[1],)})


def _centred(linear: Tuple[Params, Params]) -> Tuple[Params, Params]:
    """A map whose every output's incoming weights sum to zero."""
    p, a = linear
    return {**p, "w": p["w"] - jnp.mean(p["w"], axis=0, keepdims=True)}, a


def init_tower(key: jax.Array, cfg: ModelArgs) -> Tuple[Params, Params]:
    """(params, axes) of the tower and the projector. The plain draw:
    matrices N(0, 0.02), a block's two output maps over sqrt(2 x blocks) as
    the decoder's, biases 0, norm scales 1, the position table N(0, 0.02).

    A configuration may state three other initial values (``ModelArgs``;
    a benchmark cell's are in its configuration file's ``assumed`` with
    their reasons, none is this module's choice): ``tower_qkv_init_std``
    and ``tower_pos_emb_init_std`` for the fused q | k | v maps and the
    position table, and ``tower_centred_init``: the two maps that read a
    GELU (a block's ``fc1``, the projector's ``fc2``) CENTRED over their
    inputs, every output's incoming weights summing to zero, so that the
    GELU's positive mean, the same number in every channel and every row,
    maps to nothing where the plain draw turns it into one vector shared by
    every image row.

    No tower weight carries an axis tensor parallelism cuts
    (``eligibility.tower_plan_reason``)."""
    n, c, f = cfg.tower_layers, cfg.tower_hidden_size, cfg.tower_ffn_hidden_size
    merged = c * math.prod(cfg.tower_merge_kernel)
    keys = jax.random.split(key, n + 2)
    k_pe, k_pos = jax.random.split(keys[0])
    pe_p, pe_a = _linear(k_pe, cfg.tower_patch_dim, c, 0.02,
                         ("tower_in", "embed"))
    pe_p["pos_emb"] = M._normal(
        k_pos, (cfg.tower_pos_emb_height, cfg.tower_pos_emb_width, c),
        cfg.tower_pos_emb_init_std or 0.02)
    pe_a["pos_emb"] = ("tower_rows", "tower_cols", "embed")
    out_std = 0.02 / math.sqrt(2.0 * max(n, 1))
    reads_gelu = _centred if cfg.tower_centred_init else (lambda linear: linear)
    blocks = []
    for i in range(n):
        kq, ko, k0, k1 = jax.random.split(keys[1 + i], 4)
        parts = {
            "ln0": _norm(c), "ln1": _norm(c),
            "qkv": _linear(kq, c, 3 * c, cfg.tower_qkv_init_std or 0.02,
                           ("embed", "tower_qkv")),
            "out": _linear(ko, c, c, out_std, ("tower_heads", "embed")),
            "fc0": _linear(k0, c, f, 0.02, ("embed", "tower_mlp")),
            "fc1": reads_gelu(_linear(k1, f, c, out_std,
                                      ("tower_mlp", "embed"))),
        }
        blocks.append(({k: v[0] for k, v in parts.items()},
                       {k: v[1] for k, v in parts.items()}))
    ka, kb = jax.random.split(keys[n + 1])
    proj = {
        "pre_norm": _norm(c),
        "fc1": _linear(ka, merged, merged, 0.02, ("tower_merged", "embed")),
        "fc2": reads_gelu(_linear(kb, merged, cfg.hidden_size, 0.02,
                                  ("tower_merged", "embed"))),
    }
    fn_p, fn_a = _norm(c)
    return (
        {"patch_embed": pe_p, "blocks": tuple(b for b, _ in blocks),
         "final_norm": fn_p,
         "projector": {k: v[0] for k, v in proj.items()}},
        {"patch_embed": pe_a, "blocks": tuple(a for _, a in blocks),
         "final_norm": fn_a,
         "projector": {k: v[1] for k, v in proj.items()}})


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _layer_norm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    """LayerNorm with bias in float32 (under the caller's scope: the
    tower's norms are no ``norm`` of the decoder's)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(dtype)


def _dense(p: Params, x: jax.Array, dtype) -> jax.Array:
    y = jnp.einsum("bpi,io->bpo", x.astype(dtype),
                   M.weight_view(p["w"], dtype),
                   preferred_element_type=jnp.float32)
    return (y + p["b"]).astype(dtype)


def position_rows(table: jax.Array, grids: Grids) -> jax.Array:
    """``[P, C]`` float32: each patch's row of the learned ``[H, W, C]``
    table: the table as it is for an image of its own grid, else the table
    interpolated to the image's grid, rows then columns by
    :func:`bicubic_weights`."""
    H, W, C = table.shape
    table = table.astype(jnp.float32)
    rows = []
    for h, w in grids:
        if (h, w) == (H, W):
            e = table
        else:
            e = jnp.einsum(
                "rh,hwc,sw->rsc", jnp.asarray(bicubic_weights(H, h)), table,
                jnp.asarray(bicubic_weights(W, w)),
                precision=jax.lax.Precision.HIGHEST)
        rows.append(e.reshape(h * w, C))
    return jnp.concatenate(rows)


def rotate_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x ``[B, P, heads, D]`` with adjacent pairs (2i, 2i + 1) rotated by
    ``rotation_tables``' angles: ``x cos + swap(x) sin``, the swap of a
    pair's two numbers as a product with a permutation (exact in any dtype;
    nothing is moved between lanes by hand)."""
    D = x.shape[-1]
    swap = np.zeros((D, D), np.float32)
    swap[np.arange(D) ^ 1, np.arange(D)] = 1.0
    swapped = jnp.einsum("bpnd,de->bpne", x, jnp.asarray(swap, x.dtype))
    y = (x.astype(jnp.float32) * cos[None, :, None, :]
         + swapped.astype(jnp.float32) * sin[None, :, None, :])
    return y.astype(x.dtype)


def apply_tower_block(p: Params, x: jax.Array, cfg: ModelArgs, *,
                      rope, segments: jax.Array, sdpa, compute_dtype
                      ) -> jax.Array:
    B, P, C = x.shape
    n, d = cfg.tower_num_heads, cfg.tower_head_dim
    eps = cfg.tower_layernorm_epsilon
    with jax.named_scope("tower/attn_proj"):
        qkv = _dense(p["qkv"], _layer_norm(p["ln0"], x, eps), compute_dtype)
        q, k, v = (a.reshape(B, P, n, d) for a in jnp.split(qkv, 3, axis=-1))
        q, k = rotate_pairs(q, *rope), rotate_pairs(k, *rope)
    with jax.named_scope("tower/attention"):
        # the core is handed heads of the published width (72: no whole lane
        # tile, so the flash kernels run head-major between transposes);
        # padded to 128 lanes at the call they index the rows as they lie
        # and the step is 25 ms SLOWER (432.0 against 407.3 ms on the v5e,
        # the forward kernel 69.8 against 49.1: PERF.md section 6, PR 59).
        # The images' ids bound the kernels' loops: a tile pair in which no
        # patch meets one of its own image is neither copied nor computed
        o = sdpa(q, k, v, causal=False, segment_ids=segments)
    with jax.named_scope("tower/attn_proj"):
        x = x + _dense(p["out"], o.reshape(B, P, C), compute_dtype)
    with jax.named_scope("tower/mlp"):
        hmid = _dense(p["fc0"], _layer_norm(p["ln1"], x, eps), compute_dtype)
        hmid = jax.nn.gelu(hmid.astype(jnp.float32), approximate=True)
        return x + _dense(p["fc1"], hmid, compute_dtype)


def apply_tower(params: Params, patches: jax.Array, cfg: ModelArgs, *,
                compute_dtype=jnp.bfloat16,
                remat_flags: Optional[Sequence[bool]] = None,
                ops: Optional[M.LayerOps] = None) -> jax.Array:
    """patches ``[B, P, patch_dim]`` (the images of ``cfg.image_grids``
    packed in order) -> the projector's rows ``[B, P / merge, hidden_size]``,
    in the order of the sequence's image positions. ``remat_flags[i]``: a
    bool, or the step program's probe (:func:`modules.recomputed`)."""
    grids = grids_of(cfg)
    B, P, _ = patches.shape
    if P != sum(cfg.image_patches):
        raise ValueError(
            f"the batch holds {P} patches a sequence and model.image_grids "
            f"{cfg.image_grids} names {sum(cfg.image_patches)}")
    sdpa = (ops.sdpa if ops is not None and ops.sdpa is not None
            else M.xla_sdpa)
    if not (sdpa is M.xla_sdpa or getattr(sdpa, "supports_segments", False)):
        from hetu_galvatron_tpu.analysis.eligibility import TOWER_REASON

        raise NotImplementedError(TOWER_REASON)
    with jax.named_scope("tower/patch_embed"):
        x = _dense(params["patch_embed"], patches, compute_dtype)
        x = (x.astype(jnp.float32) + position_rows(
            params["patch_embed"]["pos_emb"], grids)).astype(compute_dtype)
    rope = tuple(jnp.asarray(t) for t in rotation_tables(
        grids, cfg.tower_head_dim, float(cfg.tower_rope_theta)))
    segments = jnp.broadcast_to(jnp.asarray(image_of_patch(grids)), (B, P))
    for i, bp in enumerate(params["blocks"]):
        fn = lambda p, h: apply_tower_block(
            p, h, cfg, rope=rope, segments=segments, sdpa=sdpa,
            compute_dtype=compute_dtype)
        x = M.recomputed(
            fn, cfg, remat_flags is not None and remat_flags[i])(bp, x)
    with jax.named_scope("tower/merge_project"):
        eps = cfg.tower_layernorm_epsilon
        pp = params["projector"]
        y = _layer_norm(pp["pre_norm"],
                        _layer_norm(params["final_norm"], x, eps), eps)
        cell = math.prod(cfg.tower_merge_kernel)
        order = merge_order(grids, tuple(cfg.tower_merge_kernel))
        y = y[:, jnp.asarray(order)].reshape(B, P // cell, cell * x.shape[-1])
        y = _dense(pp["fc1"], y, compute_dtype)
        y = jax.nn.gelu(y.astype(jnp.float32), approximate=False)
        return _dense(pp["fc2"], y, compute_dtype)


def place_images(x: jax.Array, tokens: jax.Array, z: jax.Array,
                 cfg: ModelArgs) -> jax.Array:
    """The embedded sequence ``[B, S, H]`` with the row at every position
    whose id is ``cfg.image_token_id`` replaced by the next row of ``z``
    ``[B, rows, H]``, in order. A gather by the running count of image
    positions and a select (its transpose a scatter-add into ``z``)."""
    with jax.named_scope("embed/place_images"):
        is_image = tokens == cfg.image_token_id
        nth = jnp.cumsum(is_image, axis=1) - 1
        rows = jnp.take_along_axis(
            z, jnp.clip(nth, 0, z.shape[1] - 1)[..., None], axis=1)
        return jnp.where(is_image[..., None], rows.astype(x.dtype), x)
