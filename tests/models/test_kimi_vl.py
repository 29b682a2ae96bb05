"""Kimi-VL-A3B (``kimi_vl``): a tower of image patches at native resolution
(a patch map with a bicubically interpolated 2-D position table, heads
rotated on two axes that attend both ways inside an image, a 2 x 2 merge and
a projector) in front of a decoder of latent attention and shared beside
sigmoid-routed experts on a held share: the program against the benchmark's
plain reference on loss and gradients with six controls that must fail, the
share against the uncut layer, the checkpoint names, the loader's fields,
the interpolation's weights by hand, the published preset, the step's names
and counters, the refusals, and a model without a tower as it was. CPU,
fp32 at ``highest``, tiny widths."""

import hashlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, DataArgs, ModelArgs
from hetu_galvatron_tpu.core.arguments import load_config
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.models import tower as T
from hetu_galvatron_tpu.models.builder import causal_lm_loss, init_causal_lm
from hetu_galvatron_tpu.models.moe import apply_moe_mlp, init_moe_mlp
from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params, params_to_hf
from hetu_galvatron_tpu.runtime.dataloader import (
    get_data_iterator,
    image_layout,
    image_text_batches,
    make_batch,
)

pytestmark = [pytest.mark.model]

ZOO = os.path.join(os.path.dirname(M.__file__), "configs")
# three images whose grids differ from each other and from the table's
# (4 x 4): one as the table is, one wider than tall, one taller than wide
GRIDS = [[4, 4], [2, 6], [6, 4]]
DECODER = dict(
    model_type="moe", hidden_size=32, num_hidden_layers=3,
    layer_types=["latent_attention"] * 3, num_dense_layers=1,
    num_attention_heads=2, num_key_value_heads=2, q_lora_rank=None,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    ffn_hidden_size=64, moe_ffn_hidden_size=16, vocab_size=64,
    max_position_embeddings=64, seq_length=48, hidden_act="swiglu",
    normalization="rmsnorm", layernorm_epsilon=1e-5,
    position_embedding_type="rope", rope_theta=800000.0,
    tie_word_embeddings=False, add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, num_experts=8, num_shared_experts=2,
    moe_topk=2, moe_score_function="sigmoid", moe_norm_topk_prob=True,
    moe_norm_topk_eps=1e-20, moe_routed_scaling_factor=2.446,
    moe_router_enable_expert_bias=True, moe_hf_layout="deepseek",
    moe_dispatcher="dropless", moe_aux_loss_coeff=0.0, use_flash_attn=False)
TOWER = dict(
    tower_layers=2, tower_hidden_size=24, tower_num_heads=2,
    tower_ffn_hidden_size=40, tower_patch_size=2, tower_pos_emb_height=4,
    tower_pos_emb_width=4, image_token_id=63, image_grids=GRIDS)
TINY = {**DECODER, **TOWER}
# the text between the images of one sequence of 48: 35 text positions
SPANS = [3, 9, 12, 11]

# the configuration's file as benchmark/reference/kimi_vl.py reads it
REF_CFG = {
    "hidden_size": 32, "num_hidden_layers": 3, "num_attention_heads": 2,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "rms_norm_eps": 1e-5,
    "rope_theta": 800000.0, "first_k_dense_replace": 1,
    "n_routed_experts": 8, "num_routed_experts": 8, "first_expert_held": 0,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.446, "n_shared_experts": 2,
    "media_placeholder_token_id": 63, "tower_layers": 2,
    "vision_config": {
        "hidden_size": 24, "num_attention_heads": 2, "intermediate_size": 40,
        "patch_size": 2, "num_channels": 3, "init_pos_emb_height": 4,
        "init_pos_emb_width": 4, "merge_kernel_size": [2, 2],
        "layer_norm_eps": 1e-5, "rope_theta": 10000.0},
    "image_patches": [16, 12, 24], "image_grids": GRIDS}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _family():
    from benchmark import reference

    return reference.load_family("kimi_vl")


def _seeded(cfg, key=1):
    """Seeded random weights drawn so that each equation matters: norm
    scales and every bias off their initial 1 and 0, a nonzero selection
    bias, matrices large enough that the scores and the gates range."""
    params, _ = init_causal_lm(jax.random.key(key), cfg)

    def shake(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.key(len(name) + 13 * sum(map(ord, name)))
        if "expert_bias" in name:
            return 0.2 * jax.random.normal(k, x.shape)
        if "scale" in name or "bias" in name or name.endswith("['b']"):
            return x + 0.3 * jax.random.normal(k, x.shape)
        return (8.0 if "tower" in name else 5.0) * x
    return jax.tree_util.tree_map_with_path(shake, params)


def _batch(cfg, seed=5, rows=2):
    return next(image_text_batches(cfg, rows, spans=SPANS, seed=seed))


# ---------------------------------------------------------------------------
# (a) the program against the plain reference, and the controls
# ---------------------------------------------------------------------------

CONTROLS = [None, "tower_block_fewer", "no_rotation", "across_images",
            "causal_tower", "no_interpolation", "merge_order"]
# the program in bfloat16 (what the cell computes in) against the float32
# reference: the loss alone, of order 4.3, within bf16's eight bits
BF16_LOSS = 2e-2


@pytest.fixture(scope="module")
def sides():
    """The program's loss and gradients under their public names, and what
    the reference needs to give its own."""
    with jax.default_matmul_precision("highest"):
        cfg = ModelArgs(**TINY)
        params, batch = _seeded(cfg), _batch(cfg)
        on_device = jax.tree.map(jnp.asarray, batch)
        loss, grads = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
            p, on_device, cfg, compute_dtype=jnp.float32)))(params)
        weights = {k: jnp.asarray(v)
                   for k, v in params_to_hf(params, cfg).items()}
        return (cfg, float(loss), params_to_hf(grads, cfg), weights,
                on_device)


@pytest.mark.parametrize("control", CONTROLS,
                         ids=[c or "as_published" for c in CONTROLS])
def test_program_matches_plain_reference(control, sides):
    """Loss and the gradient with respect to every tower, projector and
    decoder parameter agree with the reference as published, and under each
    control the comparison fails: the loss moves past its tolerance and a
    tower or projector gradient past its."""
    cfg, loss, grads, weights, batch = sides
    ref = _family()
    marked = float(batch["loss_mask"].sum())

    def mean(w):
        return ref.nll_sum(w, REF_CFG, batch["tokens"], batch["labels"],
                           batch=batch, control=control) / marked

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(mean))(weights)
    assert set(ref_grads) == set(grads)
    # tolerances: fp32 sums in another order, a loss of 4.3 and gradient
    # leaves from 1e-4 up
    worst = max(
        float(np.abs(np.asarray(ref_grads[k]) - grads[k]).max()
              / (np.abs(grads[k]).max() + 1e-12))
        for k in grads if k.startswith(("vision_tower", "multi_modal")))
    decoder = max(
        float(np.abs(np.asarray(ref_grads[k]) - grads[k]).max()
              / (np.abs(grads[k]).max() + 1e-12))
        for k in grads if k.startswith("language_model")
        # (the selection bias is kept outside the gradient: what the
        # program hands the optimizer there is its balance update)
        and not k.endswith("e_score_correction_bias"))
    if control is None:
        assert abs(float(ref_loss) - loss) < 5e-6
        assert worst < 2e-4 and decoder < 2e-4
    else:
        # a control moves the loss by 2e-4 to 6e-3 and some tower or
        # projector gradient by tenths of its size
        assert abs(float(ref_loss) - loss) > 1e-4
        assert worst > 5e-2


def test_the_position_table_is_read_as_it_is_at_its_own_grid():
    cfg = ModelArgs(**TINY)
    table = jax.random.normal(jax.random.key(2), (4, 4, 24))
    rows = T.position_rows(table, T.grids_of(cfg))
    np.testing.assert_array_equal(np.asarray(rows[:16]),
                                  np.asarray(table.reshape(16, 24)))
    ref = _family().position_rows(table, T.grids_of(cfg))
    np.testing.assert_allclose(np.asarray(rows), np.asarray(ref), atol=1e-6)


def test_bf16_compute_stays_near_the_fp32_reference(sides):
    cfg, loss, _, _, batch = sides
    got = jax.jit(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.bfloat16))(_seeded(cfg))
    assert abs(float(got) - loss) < 2e-2


def test_program_in_bf16_matches_plain_reference(sides):
    """``test_program_matches_plain_reference``'s ``as_published`` with the
    program in bfloat16, the reference as it is: the loss alone."""
    cfg, _, _, weights, batch = sides
    marked = float(batch["loss_mask"].sum())
    want = jax.jit(lambda w: _family().nll_sum(
        w, REF_CFG, batch["tokens"], batch["labels"],
        batch=batch))(weights) / marked
    got = jax.jit(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.bfloat16))(_seeded(cfg))
    assert abs(float(got) - float(want)) < BF16_LOSS, (
        float(got), float(want))


# ---------------------------------------------------------------------------
# (b) the interpolation's weights, by hand
# ---------------------------------------------------------------------------


def _keys(x, a=-0.75):
    """The cubic convolution kernel written out a case at a time."""
    x = abs(x)
    if x <= 1:
        return (a + 2) * x ** 3 - (a + 3) * x ** 2 + 1
    if x < 2:
        return a * x ** 3 - 5 * a * x ** 2 + 8 * a * x - 4 * a
    return 0.0


def test_bicubic_weights_against_hand_computed_values():
    """4 -> 3 and 4 -> 5, the two axes of a 4 x 4 table read at 3 x 5.
    Output 0 of 4 -> 3 lies at (0 + 1/2) 4/3 - 1/2 = 1/6 of the input: taps
    -1, 0, 1, 2 at distances 7/6, 1/6, 5/6, 11/6, the tap at -1 clamped onto
    input 0."""
    w3 = T.bicubic_weights(4, 3)
    k = [_keys(7 / 6), _keys(1 / 6), _keys(5 / 6), _keys(11 / 6)]
    np.testing.assert_allclose(w3[0], [k[0] + k[1], k[2], k[3], 0.0],
                               atol=1e-7)
    # (numbers, so that the formula above is held too: W(1/6) = 1.25/216 -
    # 2.25/36 + 1 = 0.943287, W(7/6) = -0.086806, W(5/6) = 0.160880,
    # W(11/6) = -0.017361)
    np.testing.assert_allclose(
        w3[0], [0.85648148, 0.16087963, -0.01736111, 0.0], atol=1e-7)
    # output 1 lies at 1.5: symmetric about the middle
    np.testing.assert_allclose(w3[1], [-0.09375, 0.59375, 0.59375, -0.09375],
                               atol=1e-7)
    np.testing.assert_allclose(w3[2], w3[0][::-1], atol=1e-7)
    # 4 -> 5: output 0 at 0.5 * 0.8 - 0.5 = -0.1: floor -1, t = 0.9; taps
    # -2, -1 and 0 fall on input 0 (clamped), tap 1 on input 1
    w5 = T.bicubic_weights(4, 5)
    np.testing.assert_allclose(
        w5[0], [_keys(1.9) + _keys(0.9) + _keys(0.1), _keys(1.1), 0.0, 0.0],
        atol=1e-7)
    np.testing.assert_allclose(w5[2], [-0.09375, 0.59375, 0.59375, -0.09375],
                               atol=1e-7)
    # every row sums to one (a constant table stays constant); the
    # reference's own matrix is the same numbers
    ref = _family()
    for n_out, w in ((3, w3), (5, w5)):
        np.testing.assert_allclose(w.sum(1), 1.0, atol=1e-6)
        np.testing.assert_allclose(w, ref.bicubic_matrix(4, n_out),
                                   atol=1e-6)
    # jax.image.resize's cubic kernel (a = -0.5) is another interpolation
    other = jax.image.resize(jnp.eye(4), (3, 4), "cubic")
    assert float(jnp.abs(other - w3).max()) > 1e-2


# ---------------------------------------------------------------------------
# (c) the share ties to the model
# ---------------------------------------------------------------------------

LAYER = ModelArgs(**{**DECODER, "num_experts": 16})


def test_the_eight_shares_and_the_shared_experts_once_are_the_uncut_layer():
    """16 tiny experts in eight shares of 2: the eight shares' layer outputs,
    with the shared experts (which every chip computes alike) counted once,
    add up to what the uncut reference gives for the whole layer, and their
    routes to all T*K."""
    ref = _family()
    p, _ = init_moe_mlp(jax.random.key(5), LAYER)
    p["expert_bias"] = 0.2 * jax.random.normal(jax.random.key(6), (16,))
    x = jax.random.normal(jax.random.key(8), (2, 16, 32), jnp.float32)
    w = {"gate.weight": p["router"].T,
         "gate.e_score_correction_bias": p["expert_bias"]}
    gate, up = jnp.split(p["shared"]["win"], 2, axis=1)
    assert gate.shape == (32, 2 * 16)   # two shared experts: one SwiGLU
    w.update({"shared_experts.gate_proj.weight": gate.T,
              "shared_experts.up_proj.weight": up.T,
              "shared_experts.down_proj.weight": p["shared"]["wout"].T})
    for e in range(16):
        gate, up = jnp.split(p["win"][e], 2, axis=1)
        w.update({f"experts.{e}.gate_proj.weight": gate.T,
                  f"experts.{e}.up_proj.weight": up.T,
                  f"experts.{e}.down_proj.weight": p["wout"][e].T})
    ref_cfg = {**REF_CFG, "num_routed_experts": 16, "n_routed_experts": 16}
    flat = x.reshape(-1, 32)
    whole = ref.experts(flat, w, "", ref_cfg).reshape(x.shape)
    shared = ref.swiglu(flat, w, "shared_experts.").reshape(x.shape)
    total, rows = 0.0, 0.0
    for first in range(0, 16, 2):
        cfg = LAYER.model_copy(update=dict(moe_held_experts=2,
                                           moe_first_held_expert=first))
        share = {**p, "win": p["win"][first:first + 2],
                 "wout": p["wout"][first:first + 2]}
        y, _, stats = apply_moe_mlp(share, x, cfg, compute_dtype=jnp.float32)
        total, rows = total + y, rows + float(stats["rows_held"])
    assert rows == 2 * 16 * 2
    # tolerance: fp32, sums in another order
    np.testing.assert_allclose(total - 7 * shared, whole, rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# (d) the checkpoint layout
# ---------------------------------------------------------------------------


def test_round_trip_through_the_public_names():
    cfg = ModelArgs(**TINY)
    params = _seeded(cfg)
    sd = params_to_hf(params, cfg)
    tower = {k for k in sd if not k.startswith("language_model.")}
    block = [f"vision_tower.encoder.blocks.{i}.{stem}.{leaf}"
             for i in range(2) for stem in ("norm0", "norm1", "wqkv", "wo",
                                            "mlp.fc0", "mlp.fc1")
             for leaf in ("weight", "bias")]
    assert tower == set(block) | {
        "vision_tower.patch_embed.proj.weight",
        "vision_tower.patch_embed.proj.bias",
        "vision_tower.patch_embed.pos_emb.weight",
        "vision_tower.encoder.final_layernorm.weight",
        "vision_tower.encoder.final_layernorm.bias",
        "multi_modal_projector.pre_norm.weight",
        "multi_modal_projector.pre_norm.bias",
        "multi_modal_projector.linear_1.weight",
        "multi_modal_projector.linear_1.bias",
        "multi_modal_projector.linear_2.weight",
        "multi_modal_projector.linear_2.bias"}
    assert sd["vision_tower.patch_embed.proj.weight"].shape == (24, 3, 2, 2)
    assert sd["vision_tower.encoder.blocks.0.wqkv.weight"].shape == (72, 24)
    assert sd["multi_modal_projector.linear_2.weight"].shape == (32, 96)
    assert "language_model.model.layers.1.mlp.experts.7.down_proj.weight" \
        in sd and "language_model.lm_head.weight" in sd
    # every trained parameter has a name: the counts agree
    assert sum(v.size for v in sd.values()) == sum(
        p.size for p in jax.tree.leaves(params))
    back = hf_to_params(sd, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_published_yaml_is_the_published_model():
    from benchmark import manifest
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        populate_model_args_from_hf,
    )

    cfg = load_config(os.path.join(ZOO, "kimi-vl-a3b.yaml")).model
    body = manifest.read_json(os.path.join(
        manifest.ROOT, "benchmark", "configs", "kimi-vl-a3b-ep8.json"))
    cut = body["reduced_from"]
    # the catalog's keys, as the configuration's file keeps them with the
    # cuts taken back, as KimiVLConfig nests them
    text = {**{k: v for k, v in body.items()
               if not isinstance(v, (dict, list))
               and k != "media_placeholder_token_id"},
            **{k: cut[k] for k in ("num_hidden_layers", "n_routed_experts",
                                   "vocab_size")}}
    published = {
        "model_type": "kimi_vl", "text_config": text,
        "vision_config": {**body["vision_config"],
                          "num_hidden_layers": cut["tower_layers"]},
        "media_placeholder_token_id": cut["media_placeholder_token_id"]}
    read = populate_model_args_from_hf(published).model_copy(update=dict(
        model_name=cfg.model_name, seq_length=cfg.seq_length))
    assert read.model_dump() == cfg.model_dump()
    assert cfg.vision_config == body["vision_config"]
    assert (cfg.tower_layers, cfg.tower_head_dim, cfg.tower_patch_dim,
            cfg.qk_head_dim, cfg.v_head_dim, cfg.num_shared_experts) == (
        27, 72, 588, 192, 128, 2)
    # the cell's share, as shapes alone: the count the file states
    run = cfg.model_copy(update=dict(
        num_hidden_layers=5, layer_types=["latent_attention"] * 5,
        moe_held_experts=8, vocab_size=20480, image_token_id=20479,
        tower_layers=12))
    shapes = jax.eval_shape(lambda k: init_causal_lm(k, run)[0],
                            jax.random.key(0))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    assert count(shapes) == 787_437_888
    assert count(shapes["tower"]["blocks"]) == 12 * 15_239_504
    assert f"{count(shapes):,} parameters" in body["deployment"]
    # a file that leaves out a size of the tower, or the id that marks an
    # image position, is refused: the adapter fills nothing in
    for group, key in (("vision_config", "intermediate_size"),
                       ("vision_config", "merge_kernel_size"),
                       (None, "media_placeholder_token_id"),
                       (None, "vision_config")):
        holed = {**published, "vision_config": dict(
            published["vision_config"])}
        (holed[group] if group else holed).pop(key)
        with pytest.raises(KeyError, match=key):
            populate_model_args_from_hf(holed)


def test_the_plain_draw_and_the_initial_values_a_configuration_states():
    """``init_tower`` draws every matrix and the table N(0, 0.02) and
    centres nothing; a configuration's three overrides change the leaves
    they name and no other."""
    plain = init_causal_lm(jax.random.key(3), ModelArgs(**TINY))[0]["tower"]
    stated = init_causal_lm(jax.random.key(3), ModelArgs(
        **TINY, tower_qkv_init_std=0.06, tower_pos_emb_init_std=0.5,
        tower_centred_init=True))[0]["tower"]
    flat = lambda t: {jax.tree_util.keystr(k): np.asarray(v) for k, v in
                      jax.tree_util.tree_leaves_with_path(t)}
    a, b = flat(plain), flat(stated)
    moved = {k for k in a if not np.array_equal(a[k], b[k])}
    assert moved == (
        {"['patch_embed']['pos_emb']", "['projector']['fc2']['w']"}
        | {f"['blocks'][{i}]['{m}']['w']" for i in range(2)
           for m in ("qkv", "fc1")})
    for i in range(2):
        np.testing.assert_allclose(
            b[f"['blocks'][{i}]['qkv']['w']"],
            3.0 * a[f"['blocks'][{i}]['qkv']['w']"], rtol=1e-6)
        centred = b[f"['blocks'][{i}]['fc1']['w']"]
        assert np.abs(centred.sum(0)).max() < 1e-6
        assert np.abs(a[f"['blocks'][{i}]['fc1']['w']"].sum(0)).max() > 1e-3
    np.testing.assert_allclose(b["['patch_embed']['pos_emb']"],
                               25.0 * a["['patch_embed']['pos_emb']"],
                               rtol=1e-6)
    assert np.abs(b["['projector']['fc2']['w']"].sum(0)).max() < 1e-6


def test_todays_trees_are_untouched_by_the_tower():
    """A model without a tower draws the leaves it drew: the tower takes a
    key folded from the model's, not a share of its split."""
    plain, _ = init_causal_lm(jax.random.key(3), ModelArgs(**DECODER))
    both, _ = init_causal_lm(jax.random.key(3), ModelArgs(**TINY))
    assert set(both) - set(plain) == {"tower"}
    for a, b in zip(jax.tree.leaves(plain),
                    jax.tree.leaves({k: both[k] for k in plain})):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _jaxpr_digest(cfg):
    params = jax.eval_shape(lambda k: init_causal_lm(k, cfg)[0],
                            jax.random.key(0))
    batch = jax.tree.map(jnp.asarray, make_batch(
        np.zeros((2, cfg.seq_length + 1), np.int32)))
    text = str(jax.make_jaxpr(jax.grad(lambda p, b: causal_lm_loss(
        p, b, cfg, compute_dtype=jnp.float32,
        remat_flags=[True] * cfg.num_hidden_layers)))(params, batch))
    # (a remat policy prints as a function at an address)
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    return hashlib.sha256(text.encode()).hexdigest()


DENSE = dict(
    model_type="llama", hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, vocab_size=64, make_vocab_size_divisible_by=1,
    max_position_embeddings=32, seq_length=16, hidden_act="swiglu",
    normalization="rmsnorm", position_embedding_type="rope",
    add_bias_linear=False)


@pytest.mark.parametrize("model,digest", [
    (DENSE,
     "208f0c51083b1f439dbcd501bbf93b9e3dc5ba61c11ad51c8daf7978861dd2fc"),
    (DECODER,
     "b802adfd365ae24e12eee12eb90e75a7bb73289c2b8690576ebdb9403fe0f0f6"),
], ids=["dense", "latent_experts"])
def test_a_model_without_a_tower_traces_to_the_program_it_was(model, digest):
    """The loss and its gradient of a model without ``tower_layers`` are the
    jaxpr PR 59's parent (b5ac02a) traced, digest for digest (recorded
    there with this function, under this file's ``highest`` matmul
    precision); re-record only when the decoder's program is MEANT to
    change. Re-recorded in PR 68, which meant to: the loss takes the label's
    logit by compare-and-sum and writes its backward out
    (``modules._token_nll``); with the gather put back in
    ``cross_entropy_loss`` both were PR 59's digests."""
    assert _jaxpr_digest(ModelArgs(**model)) == digest


# ---------------------------------------------------------------------------
# (e) the loader
# ---------------------------------------------------------------------------


def test_the_loader_hands_patches_beside_ids():
    cfg = ModelArgs(**TINY)
    args = CoreArgs(model=cfg.model_dump(),
                    data=DataArgs(image_text_spans=[3, 9, 12, 11]))
    args.parallel.global_train_batch_size = 4
    batch = next(get_data_iterator(args))
    assert {k: (v.shape, v.dtype.name) for k, v in batch.items()} == {
        "tokens": ((4, 48), "int32"), "labels": ((4, 48), "int32"),
        "loss_mask": ((4, 48), "float32"),
        "patches": ((4, 52, 12), "float32"),
        "patch_grids": ((4, 3, 2), "int32")}
    assert (batch["patch_grids"] == np.asarray(GRIDS)).all()
    # the placeholders of a row are the rows of z: a quarter of the patches
    assert ((batch["tokens"] == 63).sum(1) == 13).all()
    assert cfg.image_positions == 13 == sum(cfg.image_patches) // 4
    # text, image, text, image, text, image, text at the spans given
    row = batch["tokens"][0] == 63
    assert row.tolist() == ([False] * 3 + [True] * 4 + [False] * 9
                            + [True] * 3 + [False] * 12 + [True] * 6
                            + [False] * 11)
    # zeros exactly where the LABEL is the placeholder; text ids never are
    np.testing.assert_array_equal(batch["loss_mask"],
                                  (batch["labels"] != 63).astype(np.float32))
    assert (batch["labels"][:, :-1] == batch["tokens"][:, 1:]).all()
    assert batch["loss_mask"].sum() == 4 * (48 - 13)
    # pixel values N(0, 1)
    assert abs(float(batch["patches"].mean())) < 0.1
    assert abs(float(batch["patches"].std()) - 1.0) < 0.1
    # the same batch from the same seed, another from another, and the
    # second batch is no copy of the first
    again = get_data_iterator(args)
    first, second = next(again), next(again)
    for k in batch:
        np.testing.assert_array_equal(batch[k], first[k])
    assert not np.array_equal(first["patches"], second["patches"])
    args.train.seed += 1
    other = next(get_data_iterator(args))
    assert not np.array_equal(other["tokens"], batch["tokens"])
    assert not np.array_equal(other["patches"], batch["patches"])


def test_spans_that_do_not_fill_the_sequence_are_refused():
    cfg = ModelArgs(**TINY)
    assert image_layout(cfg, SPANS).sum() == 13
    assert image_layout(cfg, SPANS).size == 49
    # a traffic with images says where they lie: nothing is filled in
    with pytest.raises(ValueError, match="4 text spans that sum to 35"):
        image_layout(cfg, None)
    with pytest.raises(ValueError, match="4 text spans that sum to 35"):
        image_layout(cfg, [3, 9, 12, 10])
    with pytest.raises(ValueError, match="4 text spans"):
        image_layout(cfg, [3, 9, 23])
    with pytest.raises(ValueError, match="no whole number of 2 x 2 merges"):
        ModelArgs(**{**TINY, "image_grids": [[3, 4]]})
    with pytest.raises(ValueError, match="image positions in a sequence"):
        ModelArgs(**{**TINY, "seq_length": 12})
    with pytest.raises(ValueError, match="names the id that marks"):
        ModelArgs(**{**TINY, "image_token_id": 64})
    with pytest.raises(ValueError, match="has no tower"):
        ModelArgs(**{**DECODER, "image_grids": GRIDS})


def test_a_batch_of_other_grids_is_refused_at_trace_time():
    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    batch = jax.tree.map(jnp.asarray, _batch(cfg))
    batch["patches"] = batch["patches"][:, :-4]
    with pytest.raises(ValueError, match="48 patches a sequence"):
        causal_lm_loss(params, batch, cfg, compute_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# (f) the step's names, its counters, its FLOPs
# ---------------------------------------------------------------------------


def test_the_step_names_the_towers_parts():
    from hetu_galvatron_tpu.observability import trace_analysis

    cfg = ModelArgs(**TINY)
    params, batch = _seeded(cfg), jax.tree.map(jnp.asarray, _batch(cfg))
    hlo = jax.jit(jax.grad(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32, remat_flags=[True] * 3,
        tower_remat_flags=[True] * 2))).lower(params).compile().as_text()
    found = trace_analysis.step_hlo(hlo)
    by_scope = {}
    for scope, phase, _ in found["map"]["instructions"].values():
        by_scope.setdefault(scope, set()).add(phase)
    for scope in ("tower/patch_embed", "tower/attn_proj", "tower/attention",
                  "tower/mlp", "tower/merge_project", "embed/place_images"):
        assert scope in trace_analysis.SCOPES and scope in by_scope
    # remat covers the tower's blocks as the decoder's: their matmuls are
    # made again inside the backward pass
    assert "recompute" in by_scope["tower/mlp"]
    assert "recompute" in by_scope["tower/attn_proj"]
    assert "recompute" not in by_scope["tower/merge_project"]


def test_the_flop_counts_hold_the_tower():
    from hetu_galvatron_tpu.core.cost_model.cost import (
        model_flops_per_token,
        tower_flops_per_sequence,
    )

    cfg = ModelArgs(**TINY)
    text = ModelArgs(**DECODER)
    c, f, patches, pairs = 24, 40, 52, 16 * 16 + 12 * 12 + 24 * 24
    by_hand = (2 * (patches * 2 * c * (4 * c + 2 * f) + 4 * c * pairs)
               + patches * 2 * 12 * c + 13 * 2 * 96 * (96 + 32))
    assert tower_flops_per_sequence(cfg) == by_hand
    assert tower_flops_per_sequence(text) == 0.0
    assert model_flops_per_token(cfg) - model_flops_per_token(
        text) == pytest.approx(3 * by_hand / 48)
    assert T.pairs_masked(T.grids_of(cfg)) == pairs


def test_the_tiles_a_two_way_call_covers_are_read_off_the_call():
    """``tower/pairs_tiled`` is the flash kernels' own loop bounds over the
    calls as built, not the traffic's arithmetic: a call that is not causal
    records its lengths and tiles; without ids every tile of it is visited,
    with them the chunks of each q tile's ``segment_chunk_ranges``, which
    the kernels prefetch as their loops' bounds. A tiny tower on the flash
    path (interpret) is the XLA core's tower and leaves the launcher the
    calls to read; the XLA core's reading stays the square."""
    from functools import partial

    from hetu_galvatron_tpu.cli.train_dist import tower_pairs_tiled
    from hetu_galvatron_tpu.ops.pallas import flash_attention as F

    F.TWO_WAY_CALLS.clear()
    q = jnp.zeros((1, 1024, 2, 64), jnp.float32)
    seg = jnp.zeros((1, 1024), jnp.int32)
    jax.eval_shape(lambda a: F.flash_sdpa(
        a, a, a, causal=False, segment_ids=seg, interpret=True), q)
    jax.eval_shape(lambda a: F.flash_sdpa(a, a, a, interpret=True), q)
    assert F.TWO_WAY_CALLS == {(1024, 1024, 512, 512)}
    (call,) = F.TWO_WAY_CALLS
    assert F.two_way_tiles(*call) * call[2] * call[3] == 1024 * 1024
    # the cell's call and images: three squares on the diagonal, 8 x 8 +
    # 6 x 6 + 3 x 3 tiles less the one the second boundary puts in two
    cell = T.image_of_patch(((64, 64), (36, 80), (32, 38)))
    assert F.two_way_tiles(8192, 8192, 512, 512) == 256
    assert F.two_way_tiles(8192, 8192, 512, 512, cell) == 108
    F.TWO_WAY_CALLS.clear()

    # a tower of 1024 patches: the first image fills the first 512 x 512
    # tile's rows, the other two the second's
    cfg = ModelArgs(**{**TINY, "seq_length": 512,
                       "max_position_embeddings": 512,
                       "image_grids": [[32, 16], [16, 16], [16, 16]]})
    tower = _seeded(cfg)["tower"]
    patches = jax.random.normal(jax.random.key(3), (1, 1024, 12))
    sdpa = partial(F.flash_sdpa, interpret=True)
    sdpa.supports_segments = True
    flash = M.LayerOps(sdpa=sdpa)

    def rows(ops):
        return jax.value_and_grad(lambda t: jnp.sum(T.apply_tower(
            t, patches, cfg, compute_dtype=jnp.float32, ops=ops) ** 2))(tower)

    (got, got_grads), (want, want_grads) = rows(flash), rows(None)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-3 * float(
            jnp.abs(b).max()))
    assert F.TWO_WAY_CALLS == {(1024, 1024, 512, 512)}
    assert tower_pairs_tiled(cfg, True) == 2 * 512 * 512
    assert T.pairs_masked(T.grids_of(cfg)) == 512 ** 2 + 2 * 256 ** 2
    assert tower_pairs_tiled(cfg, False) == 1024 ** 2
    F.TWO_WAY_CALLS.clear()


# ---------------------------------------------------------------------------
# (g) what cannot run it says why
# ---------------------------------------------------------------------------


def _plan(**parallel):
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    args = CoreArgs(model=ModelArgs(**TINY).model_dump())
    for k, v in parallel.items():
        setattr(args.parallel, k, v)
    args.parallel.global_train_batch_size = 8
    return get_hybrid_parallel_config(args, 4)


@pytest.mark.parametrize("parallel,said", [
    (dict(global_tp_deg=2), "plan has tp=2"),
    (dict(global_cp_deg=2), "plan has cp=2"),
    (dict(pp_deg=2), r"the plan has pp=2 and the model a tower"),
], ids=["tp2", "cp2", "pp2"])
def test_a_plan_that_cuts_heads_sequence_or_depth_is_refused(parallel, said):
    with pytest.raises(ValueError, match=said):
        _plan(**parallel)
    assert _plan() is not None    # dp alone runs


def test_the_towers_own_reason_names_each_cut():
    from hetu_galvatron_tpu.analysis.eligibility import (
        TOWER_REASON,
        tower_plan_reason,
        tower_reason,
    )
    from hetu_galvatron_tpu.utils.strategy import LayerStrategy

    cfg, text = ModelArgs(**TINY), ModelArgs(**DECODER)
    whole = [LayerStrategy(pp_deg=1, tp_size=1, dp_size=4)] * 3
    cut = [LayerStrategy(pp_deg=1, tp_size=2, dp_size=2)] * 3
    ring = [LayerStrategy(pp_deg=1, tp_size=1, cp_size=2, dp_size=2)] * 3
    assert tower_plan_reason(cfg, whole) is None
    assert tower_plan_reason(text, cut, 2) is None
    assert "block 0's plan has tp=2" in tower_plan_reason(cfg, cut)
    assert "block 0's plan has cp=2" in tower_plan_reason(cfg, ring)
    assert "pp=2" in tower_plan_reason(cfg, whole, 2)
    for said in (tower_plan_reason(cfg, cut), tower_reason(cfg, "x")):
        assert TOWER_REASON in said
    assert tower_reason(text, "x") is None


def test_decoding_paths_and_the_pipeline_refuse_the_tower_by_name():
    from hetu_galvatron_tpu.models import generate as G
    from hetu_galvatron_tpu.serving import engine as E

    cfg = ModelArgs(**{**TINY, "num_experts": 0, "num_shared_experts": 0,
                       "layer_types": None})
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    for check, what in ((G._check_supported, r"generate\(\)"),
                        (E._check_supported, "ServingEngine")):
        with pytest.raises(NotImplementedError,
                           match=what + ".*tower_layers=2"):
            check(cfg, params)
    from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine

    with pytest.raises(NotImplementedError,
                       match="host pipeline engine.*tower_layers=2"):
        PipelineEngine(cfg, None, None)


def test_a_core_without_segments_is_refused():
    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    batch = jax.tree.map(jnp.asarray, _batch(cfg))
    blind = lambda q, k, v, **kw: M.xla_sdpa(q, k, v, causal=kw["causal"])
    with pytest.raises(NotImplementedError, match="tp=1, cp=1 and pp=1"):
        causal_lm_loss(params, batch, cfg, compute_dtype=jnp.float32,
                       tower_ops=M.LayerOps(sdpa=blind))
