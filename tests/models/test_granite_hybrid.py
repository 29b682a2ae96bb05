"""Granite-4.0-H (ibm-granite/granite-4.0-h-micro, ``granitemoehybrid``):
Mamba-2 state-space blocks among GQA blocks without positions from one
per-layer description, the four Granite multipliers, the softmax scale as an
argument of the attention cores, its checkpoint names, and the program (the
recurrence in its chunked matmul form) against the benchmark's plain
reference (the recurrence one position at a time). CPU, fp32, tiny widths."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.analysis import eligibility
from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.core.arguments import args_from_cli, load_config
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.models.builder import (
    causal_lm_loss,
    forward_causal_lm,
    init_causal_lm,
)
from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params, params_to_hf
from hetu_galvatron_tpu.runtime.dataloader import make_batch
from hetu_galvatron_tpu.utils.hf_config_adapter import (
    populate_model_args_from_hf,
)
from tools.granite_forward_check import rounding_scan

pytestmark = [pytest.mark.model]

ZOO = os.path.join(os.path.dirname(M.__file__), "configs")
TYPES = ["mamba", "mamba", "full_attention", "mamba"]
PUBLISHED_TYPES = ["mamba", "mamba", "attention", "mamba"]
# a sequence of 21 and a chunk of 8: the chunk does not divide the sequence
TINY = dict(
    model_type="llama", hf_layout="granite", hidden_size=32,
    num_hidden_layers=4, layer_types=TYPES, num_attention_heads=4,
    num_key_value_heads=2, ffn_hidden_size=48, vocab_size=64,
    max_position_embeddings=64, seq_length=21, hidden_act="swiglu",
    normalization="rmsnorm", layernorm_epsilon=1e-5,
    position_embedding_type="nope", tie_word_embeddings=True,
    add_bias_linear=False, add_qkv_bias=False,
    make_vocab_size_divisible_by=1, use_flash_attn=False,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_chunk_size=8, attention_multiplier=0.2, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0)

# the configuration's file as benchmark/reference/granite_hybrid.py reads it
REF_CFG = {
    "hidden_size": 32, "num_hidden_layers": 4, "layer_types": PUBLISHED_TYPES,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 48, "rms_norm_eps": 1e-5,
    "position_embedding_type": "nope", "rope_theta": 10000,
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_chunk_size": 8,
    "mamba_conv_bias": True, "num_local_experts": 0,
    "attention_multiplier": 0.2, "embedding_multiplier": 12.0,
    "residual_multiplier": 0.22, "logits_scaling": 8.0}


def _family():
    from benchmark import reference

    return reference.load_family("granite_hybrid")


def _seeded(cfg, key=7):
    """Seeded random weights with norm scales that are not all ones, a conv
    bias that is not zero, a ``D`` that is not one and q and k of order
    one, so that a norm, a bias, the skip, a scale or a rotation shows."""
    params, _ = init_causal_lm(jax.random.key(key), cfg)

    def shake(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.key(len(name) + 13 * sum(map(ord, name)))
        if "norm" in name or "ln" in name or name.endswith("['D']"):
            return x + 0.3 * jax.random.normal(k, x.shape)
        if "conv_bias" in name:
            return 0.2 * jax.random.normal(k, x.shape)
        if "wqkv" in name:
            # scores of order one: at 0.02 the softmax is flat whatever
            # the scale and whatever rotates q and k
            return 25.0 * x
        # a state that outweighs the skip: x, B and C of order one, steps
        # near one and a slow decay, so that 21 positions add up
        if "mamba']['win" in name:
            return 8.0 * x
        if "dt_bias" in name:
            return 0.5 + 0.3 * jax.random.normal(k, x.shape)
        if "A_log" in name:
            return jnp.log(jax.random.uniform(k, x.shape, minval=0.05,
                                              maxval=0.3))
        return x
    return jax.tree_util.tree_map_with_path(shake, params)


def _batch(rows=2, seq=21, seed=3):
    return jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(seed).randint(0, 64, (rows, seq + 1))))


# ---------------------------------------------------------------------------
# (a) Hugging Face parity: GraniteMoeHybridForCausalLM's torch_forward path
# ---------------------------------------------------------------------------


def _hf_granite():
    torch = pytest.importorskip("torch")
    from transformers import (
        GraniteMoeHybridConfig,
        GraniteMoeHybridForCausalLM,
    )

    hf_cfg = GraniteMoeHybridConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        shared_intermediate_size=48, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        layer_types=PUBLISHED_TYPES, position_embedding_type="nope",
        attention_bias=False, tie_word_embeddings=True,
        num_local_experts=0, num_experts_per_tok=0,
        mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
        mamba_chunk_size=8, mamba_expand=2, mamba_n_groups=1,
        mamba_conv_bias=True, mamba_proj_bias=False,
        attention_multiplier=0.2, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0,
        attn_implementation="eager")
    torch.manual_seed(0)
    hf = GraniteMoeHybridForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for name, t in hf.named_parameters():
            # a fresh model's scales and D are all ones, its biases zero,
            # and its dt_bias 1 with A = 1..heads forgets within a token
            if "norm" in name or name.endswith("mamba.D"):
                t.add_(0.3 * torch.randn_like(t))
            if name.endswith("conv1d.bias"):
                t.copy_(0.2 * torch.randn_like(t))
            if name.endswith("dt_bias"):
                t.copy_(-2.0 + torch.randn_like(t))
            if name.endswith("A_log"):
                t.copy_(torch.log(0.5 + 2.0 * torch.rand_like(t)))
    return torch, hf


def test_granite_hf_logit_parity():
    """A random tiny ``GraniteMoeHybridForCausalLM`` (mamba and attention
    blocks, the shared MLP, no positions, the four multipliers) through the
    adapter and ``hf_to_params`` gives HF's logits on its ``torch_forward``
    path (no ``mamba_ssm``), at a sequence its chunk does not divide."""
    torch, hf = _hf_granite()
    cfg = populate_model_args_from_hf(hf.config).model_copy(update=dict(
        use_flash_attn=False, make_vocab_size_divisible_by=1))
    assert cfg.block_kinds() == tuple((m, "dense") for m in TYPES)
    assert (cfg.position_embedding_type, cfg.attention_multiplier,
            cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == ("nope", 0.2, 12.0, 0.22, 8.0)
    params = jax.tree.map(jnp.asarray, hf_to_params(hf.state_dict(), cfg))
    tokens = np.random.RandomState(1).randint(0, 64, (2, 21))
    with torch.no_grad():
        want = hf(torch.tensor(tokens)).logits.numpy()
    got = jax.jit(lambda p, t: forward_causal_lm(
        p, t, cfg, compute_dtype=jnp.float32))(params, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_granite_hf_roundtrip():
    """``params_to_hf(hf_to_params(sd))`` gives back every tensor of HF's
    state dict under its name (the tied head apart)."""
    _, hf = _hf_granite()
    cfg = populate_model_args_from_hf(hf.config).model_copy(update=dict(
        make_vocab_size_divisible_by=1))
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()
          if k != "lm_head.weight"}
    back = params_to_hf(hf_to_params(sd, cfg), cfg)
    assert sorted(back) == sorted(sd)
    for k in sd:
        np.testing.assert_array_equal(back[k], sd[k], err_msg=k)
    assert any(k.endswith("mamba.conv1d.weight") and v.shape == (96, 1, 4)
               for k, v in back.items())
    assert "model.layers.2.shared_mlp.input_linear.weight" in back


@pytest.mark.parametrize("key,value,said", [
    ("num_local_experts", 4, "num_local_experts=4"),
    ("position_embedding_type", "alibi", "position_embedding_type='alibi'"),
    ("layer_types", ["mamba", "window"], "['window']"),
    ("layer_types", None, "names no layer_types"),
])
def test_the_adapter_refuses_by_name(key, value, said):
    body = {"model_type": "granitemoehybrid", "hidden_size": 32,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "mamba_n_heads": 8, "mamba_d_head": 8,
            "layer_types": ["mamba", "attention"],
            "position_embedding_type": "nope", key: value}
    with pytest.raises(NotImplementedError) as err:
        populate_model_args_from_hf(body)
    assert said in str(err.value)


def test_the_published_yaml_is_the_published_model():
    """The adapter reads the YAML's model out of the catalog's
    ``config.json`` (the benchmark's configuration with its cut taken
    back), and the YAML's parameter count is the issue's arithmetic."""
    from benchmark import manifest

    cfg = load_config(os.path.join(ZOO, "granite-4.0-h-micro.yaml")).model
    body = manifest.read_json(os.path.join(
        manifest.ROOT, "benchmark", "configs", "granite-4.0-h-micro-p1.json"))
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert body["layer_types"] == period
    published = {**{k: v for k, v in body.items()
                    if not isinstance(v, (dict, list))},
                 **{k: v for k, v in body["reduced_from"].items()
                    if k != "layer_types"}, "layer_types": period * 4}
    published.pop("head_dim")   # not in config.json; 64 is the file's note
    read = populate_model_args_from_hf(published).model_copy(update=dict(
        model_name=cfg.model_name, seq_length=cfg.seq_length))
    assert read.model_dump() == cfg.model_dump()
    kinds = cfg.block_kinds()
    assert [i for i, (m, _) in enumerate(kinds)
            if m == "full_attention"] == [5, 15, 25, 35]
    assert {ff for _, ff in kinds} == {"dense"}
    cut = cfg.model_copy(update=dict(
        num_hidden_layers=10, layer_types=cfg.layer_types[:10],
        vocab_size=12544))
    tree = jax.eval_shape(lambda k: init_causal_lm(k, cut)[0],
                          jax.random.key(0))
    sizes = [sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
             for t in (tree["layers"][0], tree["layers"][5], tree)]
    assert sizes == [76_182_976, 60_821_504, 772_160_448]
    with pytest.raises(ValueError, match="names 40 blocks"):
        cfg.model_copy(update=dict(num_hidden_layers=10)).block_kinds()


# ---------------------------------------------------------------------------
# (b) the program against the benchmark's plain reference, and the controls
# ---------------------------------------------------------------------------

# the program in bfloat16 (what the cell computes in) against the float32
# reference: the loss alone, of order 4.2, within bf16's eight bits
BF16_LOSS = 2e-2
CONTROLS = ["as_published", "as_published_bf16", "state_carried_in_bf16", "decay_in_bf16",
            "d_skip_left_out", "gated_norm_left_out", "conv_bias_left_out",
            "embedding_multiplier_left_out", "residual_multiplier_left_out",
            "logits_scaling_left_out", "rope_left_on",
            "scale_one_over_sqrt_d"]


def _without(params, leaf, value):
    return {**params, "layers": tuple(
        {**lp, "mamba": {**lp["mamba"],
                         leaf: jnp.full_like(lp["mamba"][leaf], value)}}
        if "mamba" in lp else lp for lp in params["layers"])}


@pytest.mark.parametrize("case", CONTROLS)
def test_program_matches_plain_reference(case, monkeypatch):
    """Loss and gradients of the program (chunked recurrence) against
    ``benchmark/reference/granite_hybrid.py`` (sequential recurrence) on
    seeded random weights through the exporter; the program's gradient tree
    goes through the same exporter and meets ``jax.grad`` of the
    reference's ``nll_sum``. Each control breaks one equation on one side
    and FAILS the comparison."""
    ref = _family()
    cfg = ModelArgs(**TINY)
    params = _seeded(cfg)
    batch = _batch()
    weights = {k: jnp.asarray(v)
               for k, v in params_to_hf(params, cfg).items()}
    run_cfg, run_params, ref_cfg = cfg, params, dict(REF_CFG)
    if case == "state_carried_in_bf16":
        monkeypatch.setattr(ref, "selective_scan",
                            rounding_scan(round_state=True))
    if case == "decay_in_bf16":
        monkeypatch.setattr(ref, "selective_scan",
                            rounding_scan(round_decay=True))
    if case == "d_skip_left_out":
        run_params = _without(params, "D", 0.0)
    if case == "conv_bias_left_out":
        run_params = _without(params, "conv_bias", 0.0)
    if case == "gated_norm_left_out":
        plain_norm = ref.rms_norm   # the gated norm is the d_inner-wide one
        monkeypatch.setattr(ref, "rms_norm", lambda x, w, eps: (
            x * w if x.shape[-1] == cfg.mamba_d_inner
            else plain_norm(x, w, eps)))
    if case == "embedding_multiplier_left_out":
        run_cfg = cfg.model_copy(update=dict(embedding_multiplier=1.0))
    if case == "residual_multiplier_left_out":
        run_cfg = cfg.model_copy(update=dict(residual_multiplier=1.0))
    if case == "logits_scaling_left_out":
        run_cfg = cfg.model_copy(update=dict(logits_scaling=1.0))
    if case == "rope_left_on":
        run_cfg = cfg.model_copy(update=dict(position_embedding_type="rope"))
    if case == "scale_one_over_sqrt_d":
        run_cfg = cfg.model_copy(update=dict(attention_multiplier=None))

    def ref_loss(w):
        return ref.nll_sum(w, ref_cfg, batch["tokens"],
                           batch["labels"]) / batch["labels"].size
    # (one program a side: op by op they are some thousands of compiles)
    if case == "as_published_bf16":
        got = jax.jit(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.bfloat16))(params)
        assert abs(float(got) - float(jax.jit(ref_loss)(weights))) \
            < BF16_LOSS, float(got)
        return
    def run_loss(p):
        return causal_lm_loss(p, batch, run_cfg, compute_dtype=jnp.float32)
    loss_close = abs(float(jax.jit(run_loss)(run_params))
                     - float(jax.jit(ref_loss)(weights))) < 2e-5
    if case != "as_published" and not loss_close:
        return   # told by the loss: the gradients' programs are not built
    want, want_grads = jax.jit(jax.value_and_grad(ref_loss))(weights)
    got, got_grads = jax.jit(jax.value_and_grad(run_loss))(run_params)
    # tolerance: both sides are fp32 on the CPU and differ in operation
    # order (the recurrence as three chunked matmuls and a scan over chunks
    # against 21 steps; fused qkv and in_proj products). The loss is of
    # order 4.2, gradients up to 0.1
    got_grads = params_to_hf(got_grads, cfg)
    assert sorted(got_grads) == sorted(want_grads)
    apart = [k for k in want_grads if not np.allclose(
        got_grads[k], want_grads[k], rtol=3e-4, atol=3e-6)]
    if case != "as_published":
        # by the loss or, where the loss hardly sees it (a state or a
        # decay rounded to bf16 over 21 positions), by the gradients
        assert not loss_close or apart, (case, float(got), float(want))
        return
    assert loss_close, (float(got), float(want))
    assert not apart, apart


@pytest.mark.parametrize("seq,chunk,bytes_", [
    (37, 8, None),        # five chunks, the last one padded, one group
    (37, 8, 2 * 4 * 8 * 8 * 4),   # the same in groups of one chunk, mapped
    (24, 8, None),        # the chunk divides the sequence
    (5, 8, None),         # shorter than a chunk
])
def test_chunked_recurrence_is_the_sequential_one(seq, chunk, bytes_,
                                                  monkeypatch):
    """``modules.ssd_chunked`` against the reference's ``selective_scan``,
    values and gradients to all five inputs, where the chunk does not
    divide the sequence and where the chunks are taken a group at a time
    with each group's decay matrix made again in the backward pass."""
    ref = _family()
    if bytes_ is not None:
        monkeypatch.setattr(M, "SSD_DECAY_BYTES", bytes_)
        assert M.ssd_chunks_a_group(2, -(-seq // chunk), 4, chunk) == 1
    k = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(k[0], (2, seq, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, seq, 4)))
    A = -jnp.exp(jax.random.normal(k[2], (4,)))
    Bm = jax.random.normal(k[3], (2, seq, 16))
    Cm = jax.random.normal(k[4], (2, seq, 16))
    args = (x, dt, A, Bm, Cm)
    chunked = jax.jit(lambda *a: M.ssd_chunked(*a, chunk, jnp.float32))
    sequential = jax.jit(ref.selective_scan)
    np.testing.assert_allclose(chunked(*args), sequential(*args),
                               rtol=1e-4, atol=1e-4)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(chunked(*a))),
                           argnums=range(5)))(*args)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(sequential(*a))),
                            argnums=range(5)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3)


def test_the_decay_matrix_is_bounded_at_the_cell_sizes():
    """One 8192-token sequence of 64 heads at chunk 256: four chunks a
    group, 64 MiB of decay matrix at once where the whole is 512 MiB."""
    assert M.ssd_chunks_a_group(1, 32, 64, 256) == 4
    assert 4 * 64 * 256 * 256 * 4 == M.SSD_DECAY_BYTES
    assert M.ssd_chunks_a_group(2, 3, 4, 8) == 3    # tiny: all at once


# ---------------------------------------------------------------------------
# (c) the softmax scale is an argument of the cores
# ---------------------------------------------------------------------------


def _qkv(seq=128, d=64):
    k = jax.random.split(jax.random.key(2), 3)
    return (jax.random.normal(k[0], (1, seq, 4, d)),
            jax.random.normal(k[1], (1, seq, 2, d)),
            jax.random.normal(k[2], (1, seq, 2, d)))


def test_flash_kernels_take_the_scale():
    """Forward and both backward kernels (interpret mode) at a scale that
    is not 1/sqrt(D), against the XLA core at the same scale."""
    from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa

    q, k, v = _qkv()
    scale = 1.0 / 64

    def loss(core, **kw):
        return lambda q, k, v: jnp.sum(jnp.sin(core(q, k, v, causal=True,
                                                    scale=scale, **kw)))
    want = jax.value_and_grad(loss(M.xla_sdpa), argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(loss(flash_sdpa, interpret=True),
                             argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-5)
    # and it is not the default's result
    assert abs(float(got[0]) - float(jnp.sum(jnp.sin(
        M.xla_sdpa(q, k, v, causal=True))))) > 1e-2


@pytest.mark.parametrize("core", ["xla", "flash"])
def test_no_scale_is_the_float_it_was(core):
    """``scale=None`` and ``scale=1/sqrt(D)`` give the same bits: a model
    without ``attention_multiplier`` gets what it got."""
    import math

    from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa

    q, k, v = _qkv()
    fn = (M.xla_sdpa if core == "xla"
          else lambda *a, **kw: flash_sdpa(*a, interpret=True, **kw))
    plain = fn(q, k, v, causal=True)
    # the XLA core divides by sqrt(D) where it is given nothing, so the
    # stated scale is compared at a power of two, where both are exact
    stated = fn(q, k, v, causal=True, scale=1.0 / math.sqrt(64))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(stated))


def test_a_core_without_the_argument_is_refused():
    cfg = ModelArgs(**TINY)
    p, _ = M.init_attention(jax.random.key(0), cfg)
    x = jnp.ones((1, 8, 32))

    def ring_like(q, k, v, *, causal=True):
        return q
    with pytest.raises(NotImplementedError, match="attention_multiplier"):
        M.apply_attention(p, x, cfg, sdpa_fn=ring_like,
                          compute_dtype=jnp.float32)
    plain = cfg.model_copy(update=dict(attention_multiplier=None))
    assert M.apply_attention(p, x, plain, sdpa_fn=ring_like,
                             compute_dtype=jnp.float32).shape == (1, 8, 32)


# ---------------------------------------------------------------------------
# (d) what cannot take the new block says so
# ---------------------------------------------------------------------------


def _args(*overrides):
    return args_from_cli(
        [os.path.join(ZOO, "granite-4.0-h-micro.yaml"),
         "model.hidden_size=32", "model.num_hidden_layers=4",
         "model.layer_types=[mamba,mamba,full_attention,mamba]",
         "model.num_attention_heads=4", "model.num_key_value_heads=2",
         "model.ffn_hidden_size=48", "model.vocab_size=64",
         "model.seq_length=16", "model.max_position_embeddings=64",
         "model.make_vocab_size_divisible_by=1", "model.mamba_n_heads=8",
         "model.mamba_d_head=8", "model.mamba_d_state=16",
         "model.mamba_chunk_size=8", "parallel.mixed_precision=fp32",
         "parallel.global_train_batch_size=8", *overrides],
        mode="train_dist")


def _pp2():
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    args = _args("parallel.pp_deg=2", "parallel.chunks=2",
                 "parallel.pipeline_type=pipedream_flush")
    return args, get_hybrid_parallel_config(args, 2)


def _refuse_compiled_pipeline():
    args, hpc = _pp2()
    reason = eligibility.compiled_unsupported_reason(args.model, hpc)
    assert reason is not None
    raise NotImplementedError(reason)


def _refuse_host_pipeline():
    from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine

    args, hpc = _pp2()
    PipelineEngine(args.model, hpc, args.train, devices=jax.devices()[:2])


def _refuse_generate():
    from hetu_galvatron_tpu.models.generate import generate

    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    generate(params, jnp.zeros((1, 4), jnp.int32), cfg, max_new_tokens=2)


def _refuse_serving():
    from hetu_galvatron_tpu.serving import engine

    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    engine._check_supported(cfg, params)


def _refuse_search():
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        model_layer_configs,
    )

    model_layer_configs(ModelArgs(**TINY))


def _refuse_model_profiler():
    from hetu_galvatron_tpu.core.profiler.model_profiler import ModelProfiler

    ModelProfiler(_args())


@pytest.mark.parametrize("engine,said", [
    (_refuse_compiled_pipeline, "the compiled pipeline engine"),
    (_refuse_host_pipeline, "the host pipeline engine"),
    (_refuse_generate, "generate()"),
    (_refuse_serving, "ServingEngine"),
    (_refuse_search, "the model profiler and the search"),
    (_refuse_model_profiler, "the model profiler"),
])
def test_what_cannot_take_a_state_space_block_names_the_kinds(engine, said):
    with pytest.raises(NotImplementedError) as err:
        engine()
    assert said in str(err.value)
    assert "3 x mamba/dense, 1 x full_attention/dense" in str(err.value)


@pytest.mark.parametrize("override,said", [
    ("parallel.global_tp_deg=2", "tp=2"),
    ("parallel.global_cp_deg=2", "cp=2"),
])
def test_a_plan_that_cuts_a_mamba_block_is_refused_by_name(override, said):
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    with pytest.raises(ValueError) as err:
        get_hybrid_parallel_config(_args(override), 8)
    assert "block 0 is a mamba block" in str(err.value)
    assert said in str(err.value)
    # dp and ZeRO-3 are what it runs under
    hpc = get_hybrid_parallel_config(_args("parallel.sdp=1"), 8)
    assert hpc.layers[0].dp_size == 8


def test_tp_overlap_leaves_a_mamba_block_to_gspmd_with_a_reason():
    assert eligibility.MIXER_OVERLAP_REASON["mamba"] == \
        eligibility.MAMBA_REASON
    assert "state-space" in eligibility.MAMBA_REASON
    cfg = ModelArgs(**{**TINY, "layer_types": ["mamba", "full_attention"],
                       "num_hidden_layers": 2, "attention_multiplier": None})

    class Plan:   # two layers at tp2, as plan_overlap_reasons reads a plan
        class S:
            sp, cp_size, tp_size = False, 1, 2
        layers = [S, S]
    reasons = dict(eligibility.plan_overlap_reasons(cfg, Plan))
    assert reasons[0] == eligibility.MAMBA_REASON
    assert reasons[1] != eligibility.MAMBA_REASON


def test_a_mamba_block_refuses_packed_documents():
    cfg = ModelArgs(**TINY)
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    batch = {**_batch(), "segment_ids": jnp.zeros((2, 21), jnp.int32)}
    with pytest.raises(NotImplementedError) as err:
        causal_lm_loss(params, batch, cfg, compute_dtype=jnp.float32)
    assert "through a mamba block" in str(err.value)
    assert "carried state" in str(err.value)


def test_decoding_paths_refuse_a_stated_multiplier():
    """A stack of attention blocks alone that states a softmax scale or a
    multiplier: generate() and serving have their own cores and adds."""
    from hetu_galvatron_tpu.models.generate import generate

    cfg = ModelArgs(**{**TINY, "layer_types": None})
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    with pytest.raises(NotImplementedError) as err:
        generate(params, jnp.zeros((1, 4), jnp.int32), cfg, max_new_tokens=2)
    assert "attention_multiplier=0.2" in str(err.value)
    assert "logits_scaling=8.0" in str(err.value)
    plain = ModelArgs()
    assert eligibility.own_multipliers_reason(plain, "x") is None
    assert eligibility.mamba_plan_reason(plain, [object()] * 12) is None


def test_the_block_says_what_it_is_not_written_for():
    # (groups of B and C that do not divide the heads are refused where the
    # model is described; two groups over eight heads are run since PR 66)
    for key, value, said in (("mamba_n_groups", 3, "mamba_n_groups=3"),
                             ("mamba_proj_bias", True, "mamba_proj_bias"),
                             ("mamba_n_heads", 0, "mamba_n_heads > 0")):
        with pytest.raises((NotImplementedError, ValueError)) as err:
            M.init_mamba2(jax.random.key(0),
                          ModelArgs(**{**TINY, key: value}))
        assert said in str(err.value)
    M.init_mamba2(jax.random.key(0), ModelArgs(**{**TINY,
                                                  "mamba_n_groups": 2}))
    with pytest.raises(ValueError, match="full_attention | conv | mamba"):
        M.apply_mixer({}, jnp.ones((1, 2, 32)), ModelArgs(**TINY), "window")


# ---------------------------------------------------------------------------
# (e) every configuration of today builds the tree and the step-0 loss it
# built on the parent commit
# ---------------------------------------------------------------------------

# <yaml>: (leaves, parameters, step-0 loss) at the tiny size below, key 3,
# fp32, computed on the parent commit ca9ddbd by the same lines
PARENTS = json.load(open(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "parents_step0.json")))


def tiny_of(base):
    types = base.layer_types[1:6] if base.layer_types else None
    return base.model_copy(update=dict(
        hidden_size=32, num_hidden_layers=5 if types else 3,
        layer_types=types, num_attention_heads=4,
        num_key_value_heads=2 if base.num_key_value_heads else None,
        head_dim_override=None, ffn_hidden_size=48,
        moe_ffn_hidden_size=24 if base.moe_ffn_hidden_size else None,
        vocab_size=64, max_position_embeddings=32, seq_length=16,
        make_vocab_size_divisible_by=1, use_flash_attn=False,
        num_experts=8 if base.num_experts else 0,
        num_dense_layers=min(base.num_dense_layers, 1),
        num_encoder_layers=None))


@pytest.mark.parametrize("yaml", sorted(PARENTS))
def test_todays_configurations_build_what_they_built(yaml):
    """No new model field moves a model that does not set it: the
    parameter tree (leaves, sizes) and the step-0 loss of every YAML the
    parent commit has, at a tiny size, are the parent's."""
    cfg = tiny_of(load_config(os.path.join(ZOO, yaml)).model)
    assert (cfg.attention_multiplier, cfg.embedding_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling,
            cfg.mamba_n_heads) == (None, 1.0, 1.0, 1.0, 0)
    params, _ = init_causal_lm(jax.random.key(3), cfg)
    loss = causal_lm_loss(params, _batch(rows=2, seq=16), cfg,
                          compute_dtype=jnp.float32)
    leaves = jax.tree.leaves(params)
    want = PARENTS[yaml]
    assert [len(leaves), sum(x.size for x in leaves)] == want[:2]
    assert float(loss) == pytest.approx(want[2], abs=1e-6)


def test_the_cost_model_counts_a_mamba_block():
    from hetu_galvatron_tpu.core.cost_model.cost import model_flops_per_token

    cfg = load_config(os.path.join(ZOO, "granite-4.0-h-micro.yaml")).model
    cut = cfg.model_copy(update=dict(
        num_hidden_layers=10, layer_types=cfg.layer_types[:10],
        vocab_size=12544))
    mlp = 2 * 3 * 2048 * 8192
    mamba = 2 * 2048 * 8512 + 2 * 4096 * 2048 + 4 * 64 * 128 * 64
    # the cost model counts attention dense (no causal discount)
    attn = 2 * 2048 * (2048 + 2 * 512) + 2 * 2048 * 2048 + 4 * 8192 * 2048
    head = 2 * 2048 * 12544
    assert model_flops_per_token(cut) == 3.0 * (
        9 * mamba + attn + 10 * mlp + head)


@pytest.mark.parametrize("dtype,loss_band,grad_band", [
    ("float32", 2e-5, 1e-4), ("bfloat16", 2e-2, 5e-2)])
def test_a_block_through_the_convolutions_kernels_is_the_block(
        conv_kernels_are_the_block, dtype, loss_band, grad_band):
    """A mamba block whose x | B | C are whole lane tiles (2 heads of 64,
    a state of 64: 256 channels) over 300 positions: its convolution, bias
    and SiLU in the kernels of ``ops/pallas/conv.py`` (interpret mode)
    against the ``jax.numpy`` form, the scan in its ``jax.numpy`` form on
    both sides."""
    cfg = ModelArgs(**{**TINY, "hidden_size": 64, "mamba_n_heads": 2,
                       "mamba_d_head": 64, "mamba_d_state": 64,
                       "mamba_chunk_size": 64, "seq_length": 300,
                       "max_position_embeddings": 512})
    params, _ = M.init_mamba2(jax.random.key(5), cfg)
    assert "conv_bias" in params
    x = jax.random.normal(jax.random.key(6), (2, 300, 64))
    conv_kernels_are_the_block(
        lambda p, a, conv_fn: M.apply_mamba2(
            p, a, cfg, compute_dtype=jnp.dtype(dtype), conv_fn=conv_fn),
        params, x, dtype, loss_band, grad_band)
