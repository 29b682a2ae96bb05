"""A block recomputes its forward only where its values do not fit
(parallel/kept.py, parallel/spmd.py::KeptStep): the chooser as a pure
function, the byte count against a hand count, the plan's flags and program
where the device leaves nothing, gradients with mixed flags against
all-recomputed, the fall-back, and what the launcher says of the choice."""

import functools
import io
import logging
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs, TrainArgs
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.models.builder import causal_lm_loss, init_causal_lm
from hetu_galvatron_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
    set_registry,
)
from hetu_galvatron_tpu.ops.pallas import flash_attention
from hetu_galvatron_tpu.parallel import kept, spmd
from hetu_galvatron_tpu.runtime.dataloader import (
    image_text_batches,
    make_batch,
)
from hetu_galvatron_tpu.runtime.hybrid_config import (
    get_hybrid_parallel_config,
)
from hetu_galvatron_tpu.runtime.mesh import build_mesh
from hetu_galvatron_tpu.runtime.optimizer import make_optimizer

pytestmark = pytest.mark.model

ZOO = os.path.join(os.path.dirname(M.__file__), "configs")
GiB = 2 ** 30


# ---------------------------------------------------------------------------
# (a) the chooser
# ---------------------------------------------------------------------------

# two kinds: 100 bytes for 1000 operations (worth 10) and 50 for 100 (worth 2)
DEAR, CHEAP = (100, 1000), (50, 100)


@pytest.mark.parametrize("blocks,budget,keep", [
    ([DEAR, CHEAP, DEAR], 0, [False, False, False]),
    ([DEAR, CHEAP, DEAR], 250, [True, True, True]),
    ([DEAR, CHEAP, DEAR], 10 ** 9, [True, True, True]),
    # the kind of the greater worth first, whatever its place in the stack
    ([CHEAP, DEAR], 100, [False, True]),
    ([DEAR, CHEAP], 100, [True, False]),
    # of two of one worth the later block first
    ([DEAR, DEAR, DEAR], 100, [False, False, True]),
    ([DEAR, DEAR, DEAR], 200, [False, True, True]),
    # one that does not fit is passed over, a smaller one behind it is not
    ([DEAR, CHEAP, DEAR], 160, [False, True, True]),
    # a block that holds nothing more costs nothing
    ([(0, 10), DEAR], 0, [True, False]),
    ([], 100, []),
], ids=["nothing_left", "just_all", "room_for_all", "worth_not_place",
        "worth_not_place_reversed", "ties_take_the_later",
        "ties_take_the_two_later", "passes_over_what_does_not_fit",
        "nothing_to_hold_costs_nothing", "no_blocks"])
def test_the_chooser_takes_blocks_by_worth_within_the_budget(blocks, budget,
                                                              keep):
    assert kept.choose(blocks, budget) == keep


@pytest.mark.parametrize("budget", [0, 49, 50, 99, 149, 151, 249, 10 ** 6])
def test_the_kept_bytes_never_pass_the_budget(budget):
    blocks = [DEAR, CHEAP, (70, 300), DEAR, (30, 10)]
    keep = kept.choose(blocks, budget)
    assert sum(b[0] for b, k in zip(blocks, keep) if k) <= budget
    # and nothing that still fits is left out
    left = budget - sum(b[0] for b, k in zip(blocks, keep) if k)
    assert all(k or b[0] > left for b, k in zip(blocks, keep))


@pytest.mark.parametrize("case,peak", [
    # one microbatch, blocks that hold little: the first block's backward,
    # every gradient made and its working set, which counts what the block
    # itself holds already
    (dict(args=1000, accumulator=0, grads=300,
          blocks=[(10, 100, 40), (10, 100, 40)], outer=20),
     1000 + 300 + 40),
    # blocks that hold much: the loss's backward, before any gradient, the
    # logits' cotangent as large again as what the loss holds
    (dict(args=1000, accumulator=0, grads=300,
          blocks=[(200, 100, 10), (200, 100, 10)], outer=150),
     1000 + 400 + 2 * 150),
    # nothing but the update: every gradient
    (dict(args=1000, accumulator=0, grads=300, blocks=[], outer=20),
     1000 + 300),
    # several microbatches: the accumulator beside everything
    (dict(args=1000, accumulator=600, grads=300,
          blocks=[(10, 100, 40)], outer=20), 1000 + 600 + 300 + 40),
    # a working set smaller than what the block holds anyway (a block of
    # kernels' kept names): the held values stand for it
    (dict(args=1000, accumulator=0, grads=300,
          blocks=[(60, 100, 40), (60, 100, 40)], outer=20),
     1000 + 300 + 60),
    # one microbatch and no loop over gradients: each gradient as its
    # backward makes it, the blocks' at a half, the rest's at three quarters
    (dict(args=1000, accumulator=0, grads=300,
          blocks=[(10, 100, 40, 0.5), (10, 100, 40, 0.5)], outer=20,
          rest=(0.75, False)), 1000 + 75 + 50 + 50 + 40),
    # the same counts where microbatches accumulate: float32 throughout
    (dict(args=1000, accumulator=600, grads=300,
          blocks=[(10, 100, 40, 0.5), (10, 100, 40, 0.5)], outer=20,
          rest=(0.75, False)), 1000 + 600 + 300 + 40),
    # a block whose rule carries its cotangents through a loop: float32
    # for every gradient of the step, the other block's and the rest's too
    (dict(args=1000, accumulator=0, grads=300,
          blocks=[(10, 100, 40, 0.5), (10, 100, 40, 0.8, True)], outer=20,
          rest=(0.75, False)), 1000 + 300 + 40),
    # and where the rule stands outside the blocks (a further depth's layer)
    (dict(args=1000, accumulator=0, grads=300,
          blocks=[(10, 100, 40, 0.5), (10, 100, 40, 0.5)], outer=20,
          rest=(0.75, True)), 1000 + 300 + 40),
    # narrow gradients move the fullest moment to the last block's
    # backward: the blocks before it hold more than their gradients take
    (dict(args=1000, accumulator=0, grads=300,
          blocks=[(80, 100, 90, 0.5), (80, 100, 90, 0.5)], outer=20,
          rest=(0.5, False)), 1000 + 80 + 50 + 50 + 90),
], ids=["a_blocks_backward", "the_losss_backward", "the_update",
        "the_accumulator", "held_stands_for_the_working_set",
        "one_microbatch_narrow", "accumulating_float32",
        "an_expert_block_float32", "a_rule_outside_the_blocks",
        "the_last_blocks_backward"])
def test_the_plans_peak_is_the_fullest_moment(case, peak):
    assert kept.plan_peak(**case) == kept.CODE + peak


# ---------------------------------------------------------------------------
# (b) the byte count against a hand count
# ---------------------------------------------------------------------------

TINY_GPT = ModelArgs(
    model_type="gpt", hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, vocab_size=128, max_position_embeddings=32,
    seq_length=16, hidden_act="gelu", normalization="layernorm",
    position_embedding_type="learned", make_vocab_size_divisible_by=1,
)
ROWS, WIDE = 64, 256
X = 4 * ROWS * ROWS            # the input, float32 [64, 64]
B = 4 * ROWS * WIDE            # tanh's result, float32 [64, 256]


def _two_matmuls(name=None):
    """``exp(tanh(x w1) w2) w3``: the backward reads the input, tanh's
    result (by tanh's own rule and by the second matmul's) and exp's."""
    def block(p, x):
        b = jnp.tanh(x @ p["w1"])
        if name:
            b = checkpoint_name(b, name)
        return jnp.exp(b @ p["w2"]) @ p["w3"]

    p = {"w1": jnp.zeros((ROWS, WIDE)), "w2": jnp.zeros((WIDE, ROWS)),
         "w3": jnp.zeros((ROWS, 128))}
    return block, (p, jnp.zeros((ROWS, ROWS)))


@pytest.mark.parametrize("name,whole,again", [
    # plain: x, tanh's result and exp's; recomputed: the input alone
    (None, X + B + X, X),
    # a named value is held either way, and counted once where it is also
    # what the backward reads
    (flash_attention.KEPT[0], X + B + X, X + B),
], ids=["no_name", "a_kept_name"])
def test_the_byte_count_is_the_hand_count(name, whole, again):
    block, args = _two_matmuls(name)
    for fn, want in ((block, whole), (M.remat(block, TINY_GPT), again)):
        jaxpr, n_out, _ = kept.trace_vjp(fn, args)
        assert kept.residual_bytes(jaxpr, n_out, n_params=3) == want
    count, out = kept.count_block(block, TINY_GPT, args)
    assert count == kept.BlockCount(
        held_bytes=whole - again, input_bytes=again, whole_bytes=whole,
        forward_flops=2 * ROWS * (ROWS * WIDE + WIDE * ROWS + ROWS * 128))
    assert out.shape == (ROWS, 128)
    # the stream over four devices: a quarter each
    assert kept.count_block(block, TINY_GPT, args, shards=4)[0] == (
        kept.BlockCount(held_bytes=(whole - again) // 4,
                        forward_flops=count.forward_flops,
                        input_bytes=again // 4, whole_bytes=whole // 4))


def _dense_block(dtype):
    """Two projections whose weights are read through ``weight_view`` and
    a scale the block multiplies by as stored."""
    def block(p, x):
        h = jnp.tanh(x.astype(dtype) @ M.weight_view(p["w1"], dtype))
        return (h @ M.weight_view(p["w2"], dtype)) * p["scale"]

    p = {"scale": jnp.zeros((ROWS,)), "w1": jnp.zeros((ROWS, WIDE)),
         "w2": jnp.zeros((WIDE, ROWS))}
    return block, (p, jnp.zeros((ROWS, ROWS)))


def _expert_block(dtype):
    """A projection, then a router and experts behind the sorted
    dispatcher, whose rule reads the expert weights as stored
    (models/moe.py)."""
    from hetu_galvatron_tpu.models import moe

    cfg = TINY_GPT.model_copy(update=dict(
        num_experts=4, moe_topk=2, moe_ffn_hidden_size=32,
        moe_dispatcher="dropless", hidden_act="swiglu"))
    p = {"moe": moe.init_moe_mlp(jax.random.key(0), cfg)[0],
         "w": jnp.zeros((cfg.hidden_size, cfg.hidden_size))}

    def block(p, x):
        h = x.astype(dtype) @ M.weight_view(p["w"], dtype)
        return moe.apply_moe_mlp(p["moe"], h, cfg, compute_dtype=dtype)[0]

    return block, (p, jnp.zeros((2, 8, cfg.hidden_size)))


W = 4 * ROWS * WIDE            # a projection's weight, float32 [64, 256]


@pytest.mark.parametrize("make,dtype,made,stored,carries", [
    # both projections' gradients are made in bfloat16, the scale's as stored
    (_dense_block, jnp.bfloat16, W + 4 * ROWS, 2 * W + 4 * ROWS, False),
    # full precision: a cast to the stored dtype narrows nothing
    (_dense_block, jnp.float32, 2 * W + 4 * ROWS, 2 * W + 4 * ROWS, False),
    # the projection's gradient is made in bfloat16; the router's product
    # runs in float32 on the weight as stored, and the experts' weights are
    # the rule's operands
    (_expert_block, jnp.bfloat16, None, None, True),
], ids=["dense_bf16", "dense_f32", "experts_bf16"])
def test_a_gradient_is_counted_in_the_dtype_its_backward_makes_it(
        make, dtype, made, stored, carries):
    block, args = make(dtype)
    n_params = len(jax.tree.leaves(args[0]))
    jaxpr, _, _ = kept.trace_vjp(jax.jit(block), args)
    got = kept.made_bytes(jaxpr, n_params)
    if made is None:
        stored = 4 * sum(a.size for a in jax.tree.leaves(args[0]))
        made = stored - 2 * args[0]["w"].size
    assert got == (made, stored, carries)
    count, _ = kept.count_block(block, TINY_GPT, args)
    assert count.grad_share == made / stored
    assert count.carries == carries


def test_a_mamba1_blocks_count_reads_what_its_scans_kernels_keep():
    """A block that runs the selective scan in its kernels holds, recomputed
    or not, what the differentiated forward names (``selective_scan.KEPT``:
    the output and the entering states, float32): they are ``input_bytes``
    beside the block's input, where the ``jax.numpy`` form keeps the input
    alone; and what keeping the block whole adds is the less for them."""
    from hetu_galvatron_tpu.ops.pallas import selective_scan

    rows, seq = 2, 256
    cfg = ModelArgs(hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=2, vocab_size=64, seq_length=seq,
                    max_position_embeddings=seq, hidden_act="swiglu",
                    make_vocab_size_divisible_by=1)
    channels, state = cfg.mamba1_d_inner, cfg.mamba1_d_state
    assert selective_scan.tile_plan(channels, state, seq) == 128
    params, _ = M.init_decoder_layer(jax.random.key(0), cfg, mixer="mamba1")
    x = jnp.zeros((rows, seq, cfg.hidden_size), jnp.float32)

    def block(ops):
        return lambda p, h: M.apply_decoder_layer(
            p, h, cfg, mixer="mamba1", ops=ops, compute_dtype=jnp.float32)

    plain, _ = kept.count_block(block(M.LayerOps()), cfg, (params, x))
    kernels, _ = kept.count_block(block(M.LayerOps(
        selective=functools.partial(selective_scan.selective_scan,
                                    interpret=True))), cfg, (params, x))
    named = 4 * rows * (seq * channels
                        + seq // selective_scan.CHUNK * state * channels)
    assert plain.input_bytes == 4 * x.size
    assert kernels.input_bytes == plain.input_bytes + named
    assert kernels.forward_flops == plain.forward_flops
    assert 0 < kernels.held_bytes < plain.held_bytes


def test_a_chain_of_cheap_operations_is_held_once_at_its_cheapest():
    """The float32 copy of a bfloat16 input, its square and a jitted
    multiple of it cost the input, once; a transcendental of it costs
    itself; the block's own parameters cost nothing."""
    def block(p, x):
        xf = x.astype(jnp.float32)
        return jnp.sum(jnp.sin(xf * xf * p["w"]) * jax.jit(
            lambda a: 3.0 * a)(xf))

    args = ({"w": jnp.ones((8, 128))}, jnp.ones((8, 128), jnp.bfloat16))
    jaxpr, n_out, _ = kept.trace_vjp(block, args)
    # x itself; sin(...), which the product's rule reads, and the
    # cos(...) of sin's own
    assert kept.residual_bytes(jaxpr, n_out, n_params=1) == (
        2 * 8 * 128 + 2 * 4 * 8 * 128)


def test_matmul_operations_count_bodies_and_leave_kernels():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        return jax.lax.scan(body, x, None, length=5)[0] @ w

    jaxpr = jax.make_jaxpr(f)(jnp.zeros((4, 8)), jnp.zeros((8, 8))).jaxpr
    assert kept.matmul_flops(jaxpr) == 6 * 2 * 4 * 8 * 8


# ---------------------------------------------------------------------------
# (c) the step program: a tiny GPT-2 on one device and on a mesh
# ---------------------------------------------------------------------------

TRAIN = TrainArgs(lr=1e-2, clip_grad=1.0, weight_decay=0.01,
                  lr_decay_style="constant", lr_warmup_iters=0)


def _step(cfg, devices, limit, monkeypatch, batch, keep_blocks=True,
          **parallel):
    """``make_spmd_train_step`` for ``cfg`` with every block's bit set, on
    devices that say they hold ``limit`` bytes (None: they say nothing, as
    a CPU does); returns (step, placed parameters, optimizer state, batch)."""
    monkeypatch.setattr(kept, "bytes_limit", lambda devices: limit)
    args = CoreArgs(model=cfg.model_dump(), train=TRAIN.model_dump())
    args.parallel.global_checkpoint = 1
    args.parallel.global_train_batch_size = batch["tokens"].shape[0]
    for k, v in parallel.items():
        setattr(args.parallel, k, v)
    hpc = get_hybrid_parallel_config(args, len(devices))
    mesh = build_mesh(len(devices), 1, devices=devices)
    params, axes = init_causal_lm(jax.random.key(0), cfg)
    tx = make_optimizer(TRAIN)
    step, pspecs, ospecs, batch_shd = spmd.make_spmd_train_step(
        cfg, hpc, mesh, axes, tx, params, compute_dtype=jnp.float32,
        donate=False, keep_blocks=keep_blocks)
    named = lambda specs: jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    sp = spmd.shard_params(params, pspecs, mesh)
    so = jax.jit(tx.init, out_shardings=named(ospecs))(sp)
    return step, sp, so, jax.device_put(batch, batch_shd)


def _tokens(cfg, rows=8, seed=0):
    data = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (rows, cfg.seq_length + 1))
    return jax.tree.map(jnp.asarray, make_batch(data))


@pytest.mark.parametrize("limit,keep_blocks", [(None, True),
                                               (64 * GiB, False)],
                         ids=["no_limit_stated", "the_profilers_step"])
def test_the_jitted_step_of_before_where_nothing_is_to_choose(
        cpu_devices, monkeypatch, limit, keep_blocks):
    """Devices that state no limit (a CPU), and the caller that measures
    what the plan's bit costs, get the bare ``jax.jit`` of the plan's
    flags."""
    step, *_ = _step(TINY_GPT, cpu_devices, limit, monkeypatch,
                     _tokens(TINY_GPT), keep_blocks=keep_blocks)
    assert not isinstance(step, spmd.KeptStep)
    assert hasattr(step, "_cache_size")
    monkeypatch.undo()
    assert kept.bytes_limit(cpu_devices) is None   # what a CPU says


@pytest.mark.parametrize("limit", [1, 2 ** 20],
                         ids=["nothing_beside_the_arguments",
                              "nothing_beside_the_estimate"])
def test_no_room_is_the_plans_flags_and_the_program_of_before(
        cpu_devices, monkeypatch, limit):
    """Both ways to a budget of zero (the arguments alone pass the limit,
    no trace made; the estimate does): the flags are the plan's and the
    step lowers to the text it lowered to without the choice."""
    batch = _tokens(TINY_GPT)
    plain, sp, so, b = _step(TINY_GPT, cpu_devices, None, monkeypatch, batch)
    before = plain.lower(sp, so, b).as_text()
    if limit > 1:
        # a byte of room beside the arguments and the gradients, none
        # beside the estimate
        exact = kept.device_bytes((sp, so, b)) + kept.device_bytes(sp)
        limit = int(exact / kept.FILL)
        while int(limit * kept.FILL) <= exact:
            limit += 1
    step, sp, so, b = _step(TINY_GPT, cpu_devices, limit, monkeypatch, batch)
    assert isinstance(step, spmd.KeptStep)
    assert step.lower(sp, so, b).as_text() == before
    assert step.report["blocks_kept"] == {
        "decoder": 0, "tower": 0, "encoder": 0}
    assert step.report["blocks_recomputed"]["decoder"] == 2
    assert step.report["kept_bytes"] == step.report["budget_bytes"] == 0
    assert (step.report["count_s"] > 0) == (limit > 1)


def test_room_for_one_block_keeps_the_later_one(cpu_devices, monkeypatch):
    batch = _tokens(TINY_GPT)
    roomy, sp, so, b = _step(TINY_GPT, cpu_devices, 64 * GiB, monkeypatch,
                             batch)
    roomy.lower(sp, so, b)
    assert roomy.report["blocks_kept"]["decoder"] == 2
    one = roomy.report["kept_bytes"] // 2
    assert one > 0
    limit = int((roomy.report["estimate_bytes"] + 1.5 * one) / kept.FILL)
    step, sp, so, b = _step(TINY_GPT, cpu_devices, limit, monkeypatch, batch)
    step.lower(sp, so, b)
    assert step._flags["decoder"] == [True, False]
    assert step.report["blocks_kept"]["decoder"] == 1
    assert step.report["blocks_recomputed"]["decoder"] == 1
    assert step.report["kept_bytes"] == one <= step.report["budget_bytes"]
    # eight devices: the stream's bytes a device are an eighth of one's
    alone, sp, so, b = _step(TINY_GPT, cpu_devices[:1], 64 * GiB,
                             monkeypatch, batch)
    alone.lower(sp, so, b)
    assert abs(alone.report["kept_bytes"]
               - 8 * roomy.report["kept_bytes"]) < 64


def test_the_chosen_step_trains_as_the_plans_does(cpu_devices, monkeypatch):
    batch = _tokens(TINY_GPT)
    plain, sp, so, b = _step(TINY_GPT, cpu_devices, None, monkeypatch, batch)
    _, _, want = plain(sp, so, b)
    step, sp, so, b = _step(TINY_GPT, cpu_devices, 64 * GiB, monkeypatch,
                            batch)
    _, _, got = step(sp, so, b)
    assert step.report["blocks_kept"]["decoder"] == 2
    assert step.report["fallback"] == 0
    np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-6)
    np.testing.assert_allclose(got["grad_norm"], want["grad_norm"],
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# (d) gradients with mixed flags are the all-recomputed ones
# ---------------------------------------------------------------------------

GRANITE = dict(
    model_type="llama", hf_layout="granite", hidden_size=32,
    num_hidden_layers=4,
    layer_types=["mamba", "mamba", "full_attention", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, ffn_hidden_size=48,
    vocab_size=64, max_position_embeddings=64, seq_length=21,
    hidden_act="swiglu", normalization="rmsnorm", layernorm_epsilon=1e-5,
    position_embedding_type="nope", tie_word_embeddings=True,
    add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, use_flash_attn=False,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_chunk_size=8, attention_multiplier=0.2, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0)
# one dense block, then four with experts, a share of four of eight held
EXPERTS = dict(
    model_type="moe", hf_layout="lfm2", hidden_size=32, num_hidden_layers=5,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    num_dense_layers=1, conv_L_cache=3, num_attention_heads=4,
    num_key_value_heads=2, ffn_hidden_size=48, moe_ffn_hidden_size=24,
    vocab_size=64, max_position_embeddings=32, seq_length=16,
    hidden_act="swiglu", normalization="rmsnorm", layernorm_epsilon=1e-5,
    position_embedding_type="rope", rope_theta=1e6,
    tie_word_embeddings=True, add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, qk_norm=True, qk_norm_per_head=True,
    num_experts=8, moe_topk=4, moe_score_function="sigmoid",
    moe_norm_topk_prob=True, moe_router_enable_expert_bias=True,
    moe_hf_layout="lfm2", moe_dispatcher="dropless", moe_aux_loss_coeff=0.0,
    use_flash_attn=False, moe_held_experts=4, moe_first_held_expert=0)
TOWER = dict(
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
    moe_dispatcher="dropless", moe_aux_loss_coeff=0.0, use_flash_attn=False,
    tower_layers=2, tower_hidden_size=24, tower_num_heads=2,
    tower_ffn_hidden_size=40, tower_patch_size=2, tower_pos_emb_height=4,
    tower_pos_emb_width=4, image_token_id=63,
    image_grids=[[4, 4], [2, 6], [6, 4]])
SPANS = [3, 9, 12, 11]

MIXED = {
    "gpt2": (TINY_GPT.model_dump(), [False, True], None),
    "granite_hybrid": (GRANITE, [True, False, False, True], None),
    "held_share_of_experts": (EXPERTS, [True, False, True, False, True],
                              None),
    "tower_and_decoder": (TOWER, [False, True, False], [True, False]),
}


@pytest.mark.parametrize("model", sorted(MIXED))
def test_mixed_flags_give_the_all_recomputed_loss_and_gradients(model):
    fields, flags, tower_flags = MIXED[model]
    cfg = ModelArgs(**fields)
    params, _ = init_causal_lm(jax.random.key(1), cfg)
    if cfg.tower_layers:
        batch = jax.tree.map(jnp.asarray, next(image_text_batches(
            cfg, 2, spans=SPANS, seed=5)))
    else:
        batch = _tokens(cfg, rows=2, seed=3)

    def loss_and_grads(remat, tower):
        return jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.float32, remat_flags=remat,
            tower_remat_flags=tower)))(params)

    n = cfg.num_hidden_layers
    want, want_g = loss_and_grads([True] * n, [True] * cfg.tower_layers)
    got, got_g = loss_and_grads(flags, tower_flags)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))


def test_the_tower_and_the_decoder_draw_on_one_budget(cpu_devices,
                                                      monkeypatch):
    """Both stacks' blocks are counted in one trace and chosen from one
    list: with room for everything all five hold; with room for the
    tower's two blocks and a little, what is kept of both stacks together
    stays inside the one budget."""
    cfg = ModelArgs(**TOWER)
    batch = jax.tree.map(jnp.asarray, next(image_text_batches(
        cfg, 2, spans=SPANS, seed=5)))
    batch.pop("patch_grids")
    roomy, sp, so, b = _step(cfg, cpu_devices[:1], 64 * GiB, monkeypatch,
                             batch)
    roomy.lower(sp, so, b)
    assert roomy.report["blocks_kept"] == {
        "decoder": 3, "tower": 2, "encoder": 0}
    sizes = {(stack, i): held
             for stack, i, held, _, _ in roomy.report["counted"]["blocks"]}
    assert len(sizes) == 5 and all(sizes.values())
    room = sizes["tower", 0] + sizes["tower", 1] + min(
        sizes["decoder", i] for i in range(3))
    limit = int((roomy.report["estimate_bytes"] + room) / kept.FILL) + 1
    step, sp, so, b = _step(cfg, cpu_devices[:1], limit, monkeypatch, batch)
    step.lower(sp, so, b)
    r = step.report
    assert 0 < r["kept_bytes"] <= r["budget_bytes"] < sum(sizes.values())
    assert sum(r["blocks_kept"].values()) < 5
    assert r["kept_bytes"] == sum(
        sizes[stack, i] for stack in ("tower", "decoder")
        for i, flag in enumerate(step._flags[stack]) if not flag)
    assert (r["blocks_kept"]["tower"] + r["blocks_recomputed"]["tower"],
            r["blocks_kept"]["decoder"] + r["blocks_recomputed"]["decoder"]
            ) == (2, 3)


# ---------------------------------------------------------------------------
# (e) the fall-back, the gauges and the launcher's words
# ---------------------------------------------------------------------------

SIZE = ["model.hidden_size=32", "model.num_hidden_layers=2",
        "model.num_attention_heads=2", "model.vocab_size=64",
        "model.seq_length=16", "model.max_position_embeddings=16",
        "model.make_vocab_size_divisible_by=1",
        "parallel.global_train_batch_size=4", "parallel.chunks=1",
        "parallel.num_devices=1", "parallel.mixed_precision=fp32",
        "parallel.global_checkpoint=1", "data.dataset=random",
        "train.train_iters=2"]


def _launched(monkeypatch, limit):
    """``train_dist.main`` on the tiny GPT-2 with devices that say they
    hold ``limit`` bytes: (registry, ``train()``'s result, the log)."""
    from hetu_galvatron_tpu.cli import train_dist

    monkeypatch.setattr(kept, "bytes_limit", lambda devices: limit)
    before = get_registry()
    reg = set_registry(MetricsRegistry())
    out, said = {}, io.StringIO()
    heard = logging.StreamHandler(said)
    logging.getLogger("hetu_galvatron_tpu").addHandler(heard)
    try:
        assert train_dist.main(
            [os.path.join(ZOO, "gpt2-small.yaml")] + SIZE, result=out) == 0
    finally:
        logging.getLogger("hetu_galvatron_tpu").removeHandler(heard)
        set_registry(before)
    gauges = {(m.name, m.labels.get("stack")): m.value
              for m in reg.metrics() if m.name.startswith("step/")}
    return gauges, out, said.getvalue()


@pytest.mark.parametrize("limit,kept_blocks", [(None, 0), (64 * GiB, 2)],
                         ids=["no_limit_stated", "room_for_all"])
def test_the_gauges_and_the_step_report_say_what_was_chosen(
        monkeypatch, limit, kept_blocks):
    gauges, out, log = _launched(monkeypatch, limit)
    assert len(out["losses"]) == 2 and all(np.isfinite(out["losses"]))
    r = out["kept_blocks"]
    assert r["blocks_kept"] == {"decoder": kept_blocks, "tower": 0,
                                "encoder": 0}
    assert r["blocks_recomputed"]["decoder"] == 2 - kept_blocks
    assert (r["kept_bytes"] > 0) == bool(kept_blocks)
    for stack in ("decoder", "tower", "encoder"):
        assert gauges["step/blocks_kept", stack] == r["blocks_kept"][stack]
        assert gauges["step/blocks_recomputed", stack] == (
            r["blocks_recomputed"][stack])
    assert gauges["step/kept_bytes", None] == r["kept_bytes"]
    assert gauges["step/kept_budget_bytes", None] == r["budget_bytes"]
    assert gauges["step/kept_fallback", None] == 0
    (line,) = [line for line in log.splitlines() if "step report:" in line]
    assert (f", kept {kept_blocks} of 2 blocks, "
            f"{r['kept_bytes'] / GiB:.2f} of {r['budget_bytes'] / GiB:.2f} "
            f"GiB (counted in {r['count_s']:.2f} s, checked in "
            f"{r['check_s']:.2f} s), ") in line
    assert (r["count_s"] > 0) == (r["check_s"] > 0) == bool(kept_blocks)


@pytest.mark.parametrize("fault", ["raises", "over_the_fill"])
def test_a_step_that_does_not_fit_falls_back_to_the_plans_flags(
        monkeypatch, fault):
    """The chosen step's compile answers RESOURCE_EXHAUSTED once (or XLA's
    count of it passes the fill): the run trains with the plan's flags,
    says so once, and ``step/kept_fallback`` reads 1."""
    from hetu_galvatron_tpu.core.profiler import runtime_profiler

    real, calls = runtime_profiler.compiled_memory_bytes, []

    def faulty(compiled):
        # (the first look is the guard's; the step report's follows)
        calls.append(compiled)
        if len(calls) > 1:
            return real(compiled)
        if fault == "raises":
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
                "out of memory in memory space hbm.")
        return {**real(compiled), "live_peak": 64 * GiB}

    monkeypatch.setattr(runtime_profiler, "compiled_memory_bytes", faulty)
    gauges, out, log = _launched(monkeypatch, 64 * GiB)
    assert len(calls) == 2     # the guard looked once, at the first call
    assert len(out["losses"]) == 2 and all(np.isfinite(out["losses"]))
    r = out["kept_blocks"]
    assert r["fallback"] == 1 and gauges["step/kept_fallback", None] == 1
    assert r["blocks_kept"]["decoder"] == 0
    assert r["blocks_recomputed"]["decoder"] == 2
    assert gauges["step/blocks_recomputed", "decoder"] == 2
    assert gauges["step/kept_bytes", None] == 0
    assert ", kept 0 of 2 blocks, " in log
    assert log.count("building it with the plan's flags") == 1


@pytest.fixture
def cache_dir(tmp_path):
    """JAX's persistent compilation cache on (the tests' conftest turns it
    off) and kept in a directory of the test's own."""
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir")
    before = [getattr(jax.config, name) for name in names]
    for name, value in zip(names, (True, str(tmp_path))):
        jax.config.update(name, value)
    try:
        yield tmp_path
    finally:
        for name, value in zip(names, before):
            jax.config.update(name, value)


def test_every_run_of_a_job_counts_and_looks_afresh(cpu_devices, monkeypatch,
                                                    cache_dir):
    """Nothing of a choice outlives the step that made it: the same job's
    next step, beside the compiled programs the first one left, counts
    again, is held to the fill by XLA's count again, and chooses the same."""
    from hetu_galvatron_tpu.core.profiler import runtime_profiler

    real, looked = runtime_profiler.compiled_memory_bytes, []
    monkeypatch.setattr(runtime_profiler, "compiled_memory_bytes",
                        lambda compiled: looked.append(1) or real(compiled))
    batch = _tokens(TINY_GPT)
    steps = []
    for run in (1, 2):
        step, sp, so, b = _step(TINY_GPT, cpu_devices, 64 * GiB, monkeypatch,
                                batch)
        step(sp, so, b)
        step(sp, so, b)     # (a step looks once, before its first call)
        assert len(looked) == run
        assert step.report["count_s"] > 0 and step.report["check_s"] > 0
        steps.append(step)
    assert steps[0]._flags == steps[1]._flags == {
        "decoder": [False, False], "tower": [], "encoder": []}
    assert not [f for f in cache_dir.iterdir() if "kept" in f.name]


class _Described:
    """A device as ``jax.experimental.topologies`` describes one: its kind,
    and no allocator to ask."""

    def __init__(self, kind):
        self.device_kind = kind

    def memory_stats(self):
        raise jax.errors.JaxRuntimeError(
            "INVALID_ARGUMENT: MemoryStats is only supported for "
            "addressable PjRt devices.")


class _Attached(_Described):
    def __init__(self, kind, limit):
        self.device_kind, self._limit = kind, limit

    def memory_stats(self):
        return {"bytes_limit": self._limit, "bytes_in_use": 0}


@pytest.mark.parametrize("devices,limit", [
    ([_Described("TPU v5 lite")] * 4, kept.DESCRIBED_LIMIT["TPU v5 lite"]),
    ([_Described("TPU v9 heavy")], None),
    ([_Attached("TPU v5 lite", 7 * GiB), _Attached("TPU v5 lite", 6 * GiB)],
     6 * GiB),
    ([_Attached("TPU v5 lite", 7 * GiB), _Described("TPU v9 heavy")], None),
], ids=["described", "a_kind_unknown", "attached_the_least", "one_says_none"])
def test_the_limit_is_the_allocators_or_the_described_kinds(devices, limit):
    """An attached device's allocator states the limit; a described one's
    kind does (the tools that compile a cell's step with no chip compile
    the step the chip runs); devices of which one states none have none."""
    assert kept.bytes_limit(devices) == limit
