"""Olmo-Hybrid-7B (``olmo_hybrid``): Gated DeltaNet blocks (a delta rule
with a decay a head, keys narrower than values, ``beta`` up to 2, chunked in
the program) three to one with Olmo 3's attention block without positions,
the norms of the one on its branches' inputs and of the other on their
outputs: the program against the benchmark's plain reference, which runs the
recurrence one position at a time, and against ``transformers``' torch code
for the recurrence and the attention block; the checkpoint names, the
published preset, the step's names and its gauge, and the refusals. CPU,
fp32 at ``highest``, tiny widths."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs
from hetu_galvatron_tpu.core.arguments import load_config
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

pytestmark = [pytest.mark.model]

ZOO = os.path.join(os.path.dirname(M.__file__), "configs")
KINDS = ["linear_attention"] * 3 + ["full_attention"]
# the configuration's file as benchmark/reference/olmo_hybrid.py reads it,
# at a size that keeps what is odd about the model: 3 heads, keys of 24
# under values of 48, four blocks in the published order; a sequence of 40
# in chunks of 32 (two sub-blocks of 16 a chunk, the last chunk padded)
REF_CFG = {
    "model_type": "olmo_hybrid", "hidden_size": 96, "num_hidden_layers": 4,
    "layer_types": KINDS, "num_attention_heads": 3, "num_key_value_heads": 3,
    "intermediate_size": 64, "vocab_size": 64, "hidden_act": "silu",
    "max_position_embeddings": 64, "attention_bias": False,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "linear_num_key_heads": 3, "linear_num_value_heads": 3,
    "linear_key_head_dim": 24, "linear_value_head_dim": 48,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


def _cfg(**update):
    return populate_model_args_from_hf(REF_CFG).model_copy(update={
        "seq_length": 40, "linear_chunk_size": 32,
        "make_vocab_size_divisible_by": 1, "use_flash_attn": False, **update})


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _family():
    from benchmark import reference

    return reference.load_family("olmo_hybrid")


def _seeded(cfg, key=7):
    """Seeded random weights drawn so that each equation matters: norm
    scales that are not all ones; in a linear block a decay near 0 in one
    head and near 1 in another, ``beta``'s and the gate's projections large
    enough that ``beta`` ranges over (0, 2) and the gate over its bend, q, k
    and v large enough that the state and the delta term are of order one
    and the output projection that the mixer weighs beside the stream;
    the attention block's q and k large enough that its scores are."""
    params, _ = init_causal_lm(jax.random.key(key), cfg)

    def shake(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.key(len(name) + 13 * sum(map(ord, name)))
        if "norm" in name or "ln" in name:
            return x + 0.3 * jax.random.normal(k, x.shape)
        if "A_log" in name:
            return jnp.log(jnp.asarray([16.0, 1.0, 0.05, 4.0])[:x.size])
        if "dt_bias" in name:
            return jnp.log(jnp.expm1(
                jnp.asarray([0.1, 0.03, 1e-3, 0.01])[:x.size]))
        if "wab" in name or "wg'" in name:
            return 30.0 * x
        if "wqkv" in name:
            return 10.0 * x
        if "['gdn']['wout']" in name:
            return 4.0 * x
        return x
    return jax.tree_util.tree_map_with_path(shake, params)


def _batch(seed=3, rows=2, seq=40):
    return jax.tree.map(jnp.asarray, make_batch(
        np.random.RandomState(seed).randint(0, 64, (rows, seq + 1))))


# ---------------------------------------------------------------------------
# (a) the program against the plain reference, and the controls
# ---------------------------------------------------------------------------

CONTROLS = ["as_published", "as_published_bf16", "beta_without_its_2",
            "decay_left_out", "l2_norm_left_out", "convolution_left_out",
            "silu_after_it_left_out", "output_gate_left_out",
            "gate_before_the_norm", "norm_placements_swapped",
            "qk_norm_left_out", "rotation_left_on"]
# the program in bfloat16 (what the cell computes in) against the float32
# reference: the loss alone, of order 4.2, within bf16's eight bits
BF16_LOSS = 2e-2


def _block_with_the_placements_swapped(ref):
    def block(x, w, i, cfg):
        p, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
        norm = lambda name, t: ref.rms_norm(t, w[p + name + ".weight"], eps)
        if cfg["layer_types"][i] == "linear_attention":
            h = x + norm("attention_layer_norm", ref.gated_delta_net(
                x, w, p + "linear_attn.", cfg))
            return h + norm("feedforward_layer_norm",
                            ref.swiglu(h, w, p + "mlp."))
        h = x + ref.attention(norm("post_attention_layernorm", x), w,
                              p + "self_attn.", cfg)
        return h + ref.swiglu(norm("post_feedforward_layernorm", h), w,
                              p + "mlp.")
    return block


@pytest.fixture(scope="module")
def program():
    """The program's side, made once: seeded weights, a batch, the weights
    under their public names, and its loss, gradients and logits."""
    with jax.default_matmul_precision("highest"):
        cfg = _cfg()
        params, batch = _seeded(cfg), _batch()
        loss, grads = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.float32)))(params)
        logits = jax.jit(lambda p: forward_causal_lm(
            p, batch["tokens"], cfg, compute_dtype=jnp.float32))(params)
        weights = {k: jnp.asarray(v)
                   for k, v in params_to_hf(params, cfg).items()}
        loss_bf16 = jax.jit(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.bfloat16))(params)
        return dict(cfg=cfg, batch=batch, weights=weights, loss=float(loss),
                    loss_bf16=float(loss_bf16),
                    grads=params_to_hf(grads, cfg), logits=logits)


@pytest.mark.parametrize("case", CONTROLS)
def test_program_matches_plain_reference(case, program, monkeypatch):
    """Logits, loss and every gradient leaf of the program against
    ``benchmark/reference/olmo_hybrid.py`` (the recurrence one position at
    a time) on seeded random weights through the exporter; the program's
    gradient tree goes through the same exporter and meets ``jax.grad`` of
    the reference's ``nll_sum``. Each control breaks one equation of the
    reference and FAILS the loss's tolerance."""
    ref = _family()
    batch, weights, ref_cfg = program["batch"], program["weights"], REF_CFG
    rule, conv = ref.delta_rule, ref.causal_conv
    if case == "beta_without_its_2":
        ref_cfg = {**REF_CFG, "linear_allow_neg_eigval": False}
    if case == "decay_left_out":
        monkeypatch.setattr(ref, "delta_rule", lambda q, k, v, g, b: rule(
            q, k, v, jnp.zeros_like(g), b))
    if case == "l2_norm_left_out":
        monkeypatch.setattr(ref, "unit", lambda x: x)
    if case == "convolution_left_out":
        monkeypatch.setattr(ref, "causal_conv", lambda u, kernel: u)
    if case == "silu_after_it_left_out":
        monkeypatch.setattr(ref, "short_conv", conv)
    if case == "output_gate_left_out":
        monkeypatch.setattr(ref, "gated_norm", lambda o, z, w, eps:
                            ref.rms_norm(o, w, eps))
    if case == "gate_before_the_norm":
        monkeypatch.setattr(ref, "gated_norm", lambda o, z, w, eps:
                            ref.rms_norm(o * jax.nn.silu(z), w, eps))
    if case == "norm_placements_swapped":
        monkeypatch.setattr(ref, "block",
                            _block_with_the_placements_swapped(ref))
    if case == "qk_norm_left_out":
        monkeypatch.setattr(ref, "qk_norm", lambda t, w, eps: t)
    if case == "rotation_left_on":
        from benchmark.reference.plain import rope

        attend = ref.causal_attention
        monkeypatch.setattr(ref, "causal_attention", lambda q, k, v: attend(
            rope(q, 10000.0), rope(k, 10000.0), v))

    def ref_loss(w):
        return ref.nll_sum(w, ref_cfg, batch["tokens"],
                           batch["labels"]) / batch["labels"].size
    # tolerance: both sides are fp32 at highest on the CPU and differ in
    # operation order only (chunks and a triangular inverse against one
    # position at a time; a fused qkv against three matmuls). The loss is
    # of order 4.2, gradients up to 0.3, logits of order one (the worst of
    # 5120 is 4e-5 off: weights ten and thirty times their draw through
    # four norms)
    if case == "as_published_bf16":
        want = float(jax.jit(ref_loss)(weights))
        assert abs(program["loss_bf16"] - want) < BF16_LOSS, (
            program["loss_bf16"], want)
        return
    if case != "as_published":
        want = float(jax.jit(ref_loss)(weights))
        assert abs(program["loss"] - want) > 2e-4, (case, want)
        return
    want, want_grads = jax.jit(jax.value_and_grad(ref_loss))(weights)
    assert abs(program["loss"] - float(want)) < 2e-5, float(want)
    np.testing.assert_allclose(
        program["logits"],
        jax.jit(lambda w: ref.logits(w, REF_CFG, batch["tokens"]))(weights),
        rtol=1e-4, atol=1e-4)
    got_grads = program["grads"]
    assert sorted(got_grads) == sorted(want_grads)
    for k in want_grads:
        scale = float(jnp.max(jnp.abs(want_grads[k])))
        np.testing.assert_allclose(
            got_grads[k], want_grads[k], rtol=5e-4,
            atol=1e-4 * scale + 1e-9, err_msg=k)


@pytest.fixture(scope="module")
def with_and_without_kernels():
    """The stack at shapes the gdn kernels' tiles fit (4 heads, keys of 32
    under values of 48, two chunks of 32), its three linear blocks handed
    the kernels in interpret mode and not: logits, loss and gradients of
    both, under per-layer remat as the cell runs, and how often the kernels
    were called."""
    from hetu_galvatron_tpu.ops.pallas import gdn

    with jax.default_matmul_precision("highest"):
        cfg = _cfg(seq_length=64, linear_num_key_heads=4,
                   linear_num_value_heads=4, linear_key_head_dim=32,
                   linear_value_head_dim=48)
        assert gdn.tile_plan(32, 4, 32, 48) is not None
        params, batch = _seeded(cfg), _batch(seq=64)
        called = []

        def kernels(*a):
            called.append(a[0].shape)
            return gdn.gdn_scan(*a, interpret=True)

        sides = {}
        for name, ops in (("kernels", {i: M.LayerOps(gdn=kernels)
                                       for i in range(3)}), ("plain", None)):
            loss, grads = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
                p, batch, cfg, compute_dtype=jnp.float32,
                remat_flags=[True] * 4, layer_overrides=ops)))(params)
            logits = jax.jit(lambda p: forward_causal_lm(
                p, batch["tokens"], cfg, compute_dtype=jnp.float32,
                layer_overrides=ops))(params)
            sides[name] = dict(loss=float(loss), grads=grads, logits=logits)
        return dict(sides, called=called)


@pytest.mark.parametrize("quantity", ["called", "logits", "loss",
                                      "gradients"])
def test_linear_blocks_handed_the_kernels_are_the_blocks_without_them(
        quantity, with_and_without_kernels):
    """``LayerOps.gdn`` swaps the recurrence's implementation and nothing
    else: within the limits the program is held to the plain reference by
    (both sides float32 at highest, differing in operation order alone)."""
    got, want = (with_and_without_kernels[s] for s in ("kernels", "plain"))
    if quantity == "called":
        # three blocks in the loss's trace and in the logits', no other
        assert len(with_and_without_kernels["called"]) >= 6
        assert set(with_and_without_kernels["called"]) == {(2, 64, 4, 32)}
    if quantity == "logits":
        np.testing.assert_allclose(got["logits"], want["logits"], rtol=1e-4,
                                   atol=1e-4)
    if quantity == "loss":
        assert abs(got["loss"] - want["loss"]) < 2e-5
    if quantity == "gradients":
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(got["grads"])[0],
                jax.tree.leaves(want["grads"])):
            scale = float(jnp.max(jnp.abs(b)))
            np.testing.assert_allclose(
                a, b, rtol=5e-4, atol=1e-4 * scale + 1e-9,
                err_msg=jax.tree_util.keystr(path))


# ---------------------------------------------------------------------------
# (b) transformers' torch code on this machine
# ---------------------------------------------------------------------------


def test_the_recurrence_is_transformers_recurrent_gated_delta_rule():
    """``gated_delta_chunked`` and the reference's ``delta_rule`` against
    ``torch_recurrent_gated_delta_rule`` (Qwen3-Next's, the layer these
    published keys are of), ``beta`` drawn in (0, 2), keys of 24 under
    values of 48, a length the chunk does not divide."""
    torch = pytest.importorskip("torch")
    from transformers.models.qwen3_next.modeling_qwen3_next import (
        torch_recurrent_gated_delta_rule,
    )

    rng = np.random.RandomState(0)
    B, S, H, dk, dv = 2, 45, 3, 24, 48
    q, k = (rng.randn(B, S, H, dk).astype(np.float32) for _ in range(2))
    v = rng.randn(B, S, H, dv).astype(np.float32)
    g = -np.exp(rng.uniform(-6, 0.5, (B, S, H))).astype(np.float32)
    beta = rng.uniform(0, 2, (B, S, H)).astype(np.float32)
    with torch.no_grad():
        want, _ = torch_recurrent_gated_delta_rule(
            *(torch.tensor(t) for t in (q, k, v, g, beta)), None, False,
            use_qk_l2norm_in_kernel=True)
    unit = lambda t: t / np.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    args = tuple(jnp.asarray(t) for t in (
        unit(q) * dk ** -0.5, unit(k), v, g, beta))
    for got in (jax.jit(lambda *a: M.gated_delta_chunked(
            *a, 16, jnp.float32))(*args),
                jax.jit(_family().delta_rule)(*args)):
        np.testing.assert_allclose(np.asarray(got), want.numpy(),
                                   rtol=1e-4, atol=2e-6)


def test_the_attention_block_is_olmo3_decoder_layer_without_rotation():
    """The program's ``full_attention`` block of this model (whole-width
    q/k norms, the two norms on the branches' outputs, no positions)
    against ``Olmo3DecoderLayer`` handed ``cos = 1, sin = 0``."""
    torch = pytest.importorskip("torch")
    from transformers.models.olmo3.configuration_olmo3 import Olmo3Config
    from transformers.models.olmo3.modeling_olmo3 import Olmo3DecoderLayer

    cfg = _cfg()
    torch.manual_seed(0)
    layer = Olmo3DecoderLayer(Olmo3Config(
        hidden_size=96, intermediate_size=64, num_attention_heads=3,
        num_key_value_heads=3, num_hidden_layers=1, rms_norm_eps=1e-6,
        vocab_size=64, layer_types=["full_attention"],
        attn_implementation="eager"), layer_idx=0).eval()
    with torch.no_grad():
        for name, t in layer.named_parameters():
            if "norm" in name:
                t.add_(0.3 * torch.randn_like(t))
            if "q_proj" in name or "k_proj" in name:
                t.mul_(8.0)
    S = 21
    x = np.random.RandomState(1).randn(2, S, 96).astype(np.float32)
    mask = torch.full((S, S), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        want = layer(torch.tensor(x), attention_mask=mask,
                     position_embeddings=(torch.ones(2, S, 32),
                                          torch.zeros(2, S, 32)))
    want = (want[0] if isinstance(want, tuple) else want).numpy()
    # the block's leaves through the importer's names (block 3 attends)
    sd = {f"model.layers.3.{k}": v.detach().numpy()
          for k, v in layer.state_dict().items()}
    like = params_to_hf(init_causal_lm(jax.random.key(0), cfg)[0], cfg)
    lp = jax.tree.map(jnp.asarray, hf_to_params({**like, **sd}, cfg)
                      )["layers"][3]
    got = M.apply_decoder_layer(lp, jnp.asarray(x), cfg.for_block(3),
                                compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# (c) names, the preset, the adapter
# ---------------------------------------------------------------------------


def test_round_trip_through_the_public_names():
    cfg = _cfg()
    params = _seeded(cfg)
    sd = params_to_hf(params, cfg)
    lin, att = "model.layers.0.", "model.layers.3."
    assert {k[len(lin):] for k in sd if k.startswith(lin)} == {
        "attention_layer_norm.weight", "feedforward_layer_norm.weight",
        *(f"linear_attn.{m}_proj.weight" for m in "qkvabgo"),
        *(f"linear_attn.{m}_conv1d.weight" for m in "qkv"),
        "linear_attn.A_log", "linear_attn.dt_bias",
        "linear_attn.o_norm.weight",
        *(f"mlp.{m}_proj.weight" for m in ("gate", "up", "down"))}
    assert {k[len(att):] for k in sd if k.startswith(att)} == {
        "post_attention_layernorm.weight",
        "post_feedforward_layernorm.weight",
        *(f"self_attn.{m}_proj.weight" for m in "qkvo"),
        "self_attn.q_norm.weight", "self_attn.k_norm.weight",
        *(f"mlp.{m}_proj.weight" for m in ("gate", "up", "down"))}
    assert sd[lin + "linear_attn.k_proj.weight"].shape == (72, 96)
    assert sd[lin + "linear_attn.v_conv1d.weight"].shape == (144, 1, 4)
    assert sd[lin + "linear_attn.b_proj.weight"].shape == (3, 96)
    assert sd[att + "self_attn.q_norm.weight"].shape == (96,)
    gp = params["layers"][0]["gdn"]
    np.testing.assert_array_equal(
        sd[lin + "linear_attn.k_conv1d.weight"][:, 0, :],
        np.asarray(gp["taps"])[72:144])
    np.testing.assert_array_equal(sd[lin + "linear_attn.b_proj.weight"].T,
                                  np.asarray(gp["wab"])[:, 3:])
    back = hf_to_params(sd, cfg)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


def test_the_published_yaml_is_the_published_model():
    """The adapter reads the YAML's model out of the catalog's
    ``config.json`` (the benchmark's configuration with its cut taken
    back), and the cell's share counts the parameters the file states."""
    from benchmark import manifest

    cfg = load_config(os.path.join(ZOO, "olmo-hybrid-7b.yaml")).model
    body = manifest.read_json(os.path.join(
        manifest.ROOT, "benchmark", "configs", "olmo-hybrid-7b-p1.json"))
    assert body["layer_types"] == KINDS
    published = {**{k: v for k, v in body.items()
                    if not isinstance(v, (dict, list)) and k != "head_dim"},
                 "rope_parameters": body["rope_parameters"],
                 "num_hidden_layers": 32, "vocab_size": 100352,
                 "layer_types": KINDS * 8}
    assert body["reduced_from"]["num_hidden_layers"] == 32
    read = populate_model_args_from_hf(published).model_copy(update=dict(
        model_name=cfg.model_name, seq_length=cfg.seq_length))
    assert read.model_dump() == cfg.model_dump()
    assert cfg.block_kinds() == tuple((m, "dense") for m in KINDS * 8)
    assert (cfg.linear_key_dim, cfg.linear_value_dim, cfg.head_dim,
            cfg.position_embedding_type, cfg.qk_norm) == (
        2880, 5760, 128, "nope", True)
    assert [cfg.for_block(i).norm_position for i in range(4)] == [
        "pre"] * 3 + ["branch"]
    cut = cfg.model_copy(update=dict(
        num_hidden_layers=4, layer_types=KINDS, vocab_size=12544))
    for attr, key in body["program"]["equals"].items():
        assert getattr(cut, attr) == body[key], attr
    shapes = jax.eval_shape(lambda k: init_causal_lm(k, cut)[0],
                            jax.random.key(0))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    assert count(shapes) == body["parameters"] == 928_862_196
    assert f"{count(shapes):,} parameters" in body["deployment"]
    assert (count(shapes["layers"][0]), count(shapes["layers"][3])) == (
        215_570_172, 185_809_920)


@pytest.mark.parametrize("key,value,error,said", [
    ("layer_types", ["linear_attention", "mamba"], NotImplementedError,
     "['mamba']"),
    ("layer_types", None, NotImplementedError, "layer_types=none"),
    ("hidden_act", "gelu", NotImplementedError, "hidden_act='gelu'"),
    ("sliding_window", 4096, NotImplementedError, "sliding window"),
    ("linear_num_value_heads", 6, ValueError,
     "a key head repeated for several value heads is not written"),
    ("linear_num_value_heads", 4, ValueError, "a multiple of the 3 key"),
], ids=["kind", "no_kinds", "act", "window", "repeat", "ragged"])
def test_the_adapter_refuses_by_name(key, value, error, said):
    with pytest.raises(error) as err:
        populate_model_args_from_hf({**REF_CFG, key: value})
    assert said in str(err.value)


def test_a_rotation_and_the_norms_placements_are_read_from_the_keys():
    read = populate_model_args_from_hf(
        {**REF_CFG, "rope_parameters": {"rope_theta": 5e5}})
    assert (read.position_embedding_type, read.rope_theta) == ("rope", 5e5)
    cfg = _cfg()
    assert cfg.norm_positions == {"linear_attention": "pre",
                                  "full_attention": "branch"}
    with pytest.raises(ValueError, match="names a kind of model.layer_types"):
        ModelArgs(**{**cfg.model_dump(),
                     "norm_positions": {"mamba": "branch"}})


# ---------------------------------------------------------------------------
# (d) the published paths of apply_block are the code they were
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["pre", "post"])
def test_a_block_of_another_family_is_the_block_it_was(family):
    """A pre-norm block and BERT's post-norm block, written out by hand
    from the pieces, are what ``apply_decoder_layer`` computes, bit for
    bit: the third placement changed neither."""
    cfg = ModelArgs(
        model_type="bert" if family == "post" else "llama", hidden_size=32,
        num_hidden_layers=1, num_attention_heads=4, ffn_hidden_size=48,
        vocab_size=64, seq_length=12, max_position_embeddings=16,
        normalization="rmsnorm" if family == "pre" else "layernorm",
        use_flash_attn=False)
    assert cfg.for_block(0) is cfg and not cfg.branch_norm
    p, _ = M.init_decoder_layer(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 12, 32))
    kw = dict(compute_dtype=jnp.float32, causal=family == "pre")
    mix = lambda h: M.apply_mixer(p, h, cfg, "full_attention", **kw)
    mlp = lambda h: M.apply_mlp(p["mlp"], h, cfg, compute_dtype=jnp.float32)
    norm = lambda name, h: M.block_norm(p[name], h, cfg)
    if family == "pre":
        h = x + mix(norm("ln1", x))
        want = h + mlp(norm("ln2", h))
    else:
        h = norm("ln1", x + mix(x))
        want = norm("ln2", h + mlp(h))
    got = M.apply_decoder_layer(p, x, cfg, compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_norm_on_the_branches_outputs_is_h_plus_norm_of_f():
    cfg = _cfg().for_block(3)
    assert cfg.branch_norm
    p = _seeded(_cfg())["layers"][3]
    x = jax.random.normal(jax.random.key(1), (2, 12, 96))
    h = x + M.block_norm(p["ln1"], M.apply_mixer(
        p, x, cfg, "full_attention", compute_dtype=jnp.float32), cfg)
    want = h + M.block_norm(p["ln2"], M.apply_mlp(
        p["mlp"], h, cfg, compute_dtype=jnp.float32), cfg)
    got = M.apply_decoder_layer(p, x, cfg, compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# (e) scopes, the gauge, the refusals
# ---------------------------------------------------------------------------


def test_the_step_names_the_new_parts_and_holds_no_wide_decay():
    """The compiled step's HLO carries the six ``mixer/gdn/*`` scopes, the
    attention block's, and no array whose last width is a key head's under
    ``mixer/gdn/scan`` beside q and k themselves: the decay is one number a
    head (chunk 8: ``[.., 8, 8]`` pair matrices, never ``[.., 8, 8, 24]``)."""
    from hetu_galvatron_tpu.observability import trace_analysis

    cfg = _cfg(linear_chunk_size=8)
    params, batch = _seeded(cfg), _batch()
    hlo = jax.jit(jax.grad(lambda p: causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32,
        remat_flags=[True] * 4))).lower(params).compile().as_text()
    found = trace_analysis.step_hlo(hlo)
    scopes = {c[0] for c in found["map"]["instructions"].values()}
    assert set(trace_analysis.MIXER_SCOPES["gdn"]) <= scopes
    assert len(trace_analysis.MIXER_SCOPES["gdn"]) == 6
    assert {"attn/qkv_proj", "attn/qk_norm", "attn/core", "attn/out_proj",
            "mlp", "head"} <= scopes
    assert "attn/rope" not in scopes     # a model without positions
    assert all(found["scopes"][s] for s in trace_analysis.MIXER_SCOPES["gdn"])
    assert "mixer/gdn/conv" in trace_analysis.CONV_SCOPES
    jaxpr = str(jax.make_jaxpr(lambda *a: M.gated_delta_chunked(
        *a, 8, jnp.float32))(
            *(jnp.zeros(s) for s in ((2, 40, 3, 24), (2, 40, 3, 24),
                                     (2, 40, 3, 48), (2, 40, 3),
                                     (2, 40, 3)))))
    assert "8,8,24]" not in jaxpr and "8,8]" in jaxpr


def test_a_logged_step_writes_the_share_of_overshooting_steps():
    """``gated_delta/beta_over_one_pct{layer}`` at a logged step, through
    the launcher's own step and log line: about half of (position, head) at
    the seed's weights, and none where ``beta`` keeps to (0, 1)."""
    from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
        RuntimeProfiler,
    )
    from hetu_galvatron_tpu.observability.registry import MetricsRegistry

    for neg, lo, hi in ((True, 30.0, 70.0), (False, 0.0, 0.0)):
        cfg = _cfg(linear_allow_neg_eigval=neg)
        params, batch = init_causal_lm(jax.random.key(0), cfg)[0], _batch()
        _, stats = jax.jit(lambda p: causal_lm_loss(
            p, batch, cfg, compute_dtype=jnp.float32, with_moe_stats=True,
            remat_flags=[True] * 4))(params)
        assert sorted(stats) == ["layer0", "layer1", "layer2"]
        reg = MetricsRegistry()
        prof = RuntimeProfiler(CoreArgs(model=cfg.model_dump()),
                               registry=reg)
        line = prof.iteration_log(0, {"loss": 1.0, "moe": stats})
        assert "gdn[layer0] beta>1" in line
        got = [m.value for m in reg.metrics()
               if m.name == "gated_delta/beta_over_one_pct"]
        assert len(got) == 3 and all(lo <= v <= hi for v in got), got
    assert M.writes_counts(cfg) and not M.writes_counts(
        ModelArgs(num_hidden_layers=2))


def _plan(**parallel):
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    args = CoreArgs(model=_cfg().model_dump())
    for k, v in parallel.items():
        setattr(args.parallel, k, v)
    args.parallel.global_train_batch_size = 8
    return get_hybrid_parallel_config(args, 4)


@pytest.mark.parametrize("parallel,said", [
    (dict(global_tp_deg=2), "linear_attention block and its plan has tp=2"),
    (dict(global_cp_deg=2), "linear_attention block and its plan has cp=2"),
], ids=["tp2", "cp2"])
def test_a_plan_that_cuts_heads_or_sequence_is_refused(parallel, said):
    with pytest.raises(ValueError, match=said):
        _plan(**parallel)
    assert _plan() is not None    # dp alone runs


def test_other_engines_refuse_the_stack_by_a_reason():
    from hetu_galvatron_tpu.analysis.eligibility import (
        GDN_REASON,
        MIXER_OVERLAP_REASON,
        mixed_stack_reason,
        window_plan_reason,
    )
    from hetu_galvatron_tpu.models.generate import generate
    from hetu_galvatron_tpu.runtime.pipeline import PipelineEngine

    cfg = _cfg()
    said = mixed_stack_reason(cfg, "generate()")
    assert "3 x linear_attention/dense" in said
    assert "1 x full_attention/dense" in said
    assert MIXER_OVERLAP_REASON["linear_attention"] is GDN_REASON
    params, _ = init_causal_lm(jax.random.key(0), cfg)
    with pytest.raises(NotImplementedError, match="linear_attention/dense"):
        generate(params, jnp.zeros((1, 4), jnp.int32), cfg, max_new_tokens=1)
    with pytest.raises(NotImplementedError, match="run it at pp_deg=1"):
        PipelineEngine(cfg, None, None, None)
    # a stack of attention blocks alone whose norms sit on the outputs is
    # named by the field, to the engines that build pre-norm blocks
    olmo3 = ModelArgs(num_hidden_layers=2, norm_position="branch")
    assert "norm_position=branch" in mixed_stack_reason(olmo3, "generate()")
    assert window_plan_reason(ModelArgs(num_hidden_layers=2), []) is None
    batch = _batch()
    with pytest.raises(NotImplementedError, match="carried state"):
        forward_causal_lm(params, batch["tokens"], cfg,
                          compute_dtype=jnp.float32,
                          segment_ids=jnp.zeros_like(batch["tokens"]))
    with pytest.raises(NotImplementedError, match="gdn_plan_reason"):
        M.apply_mixer(params["layers"][0], jnp.zeros((1, 40, 96)), cfg,
                      "linear_attention",
                      ops=M.LayerOps(shard=lambda a, axis: a))
    with pytest.raises(ValueError, match="linear_chunk_size=48|chunk"):
        M.init_gated_delta(jax.random.key(0), cfg.model_copy(
            update=dict(linear_chunk_size=48)))
