"""Pallas fused cross-entropy vs the XLA reference path (interpret mode on
CPU; chip_smoke.py exercises the Mosaic compile on hardware)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_galvatron_tpu.models.modules import cross_entropy_loss
from hetu_galvatron_tpu.ops.pallas.cross_entropy import (
    fit_vocab_block,
    fused_ce_nll,
)

pytestmark = pytest.mark.kernels


def _ref_nll(logits, labels, z_loss=0.0):
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    return nll + z_loss * jnp.square(lse) if z_loss else nll


def _data(B=2, S=64, V=512, seed=0):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(B, S, V) * 3, jnp.float32)
    labels = jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32)
    return logits, labels


@pytest.mark.parametrize("z_loss", [0.0, 1e-4])
def test_fused_nll_matches_reference(z_loss):
    logits, labels = _data()
    nll = fused_ce_nll(logits, labels, z_loss=z_loss, interpret=True)
    np.testing.assert_allclose(np.asarray(nll),
                               np.asarray(_ref_nll(logits, labels, z_loss)),
                               rtol=1e-5, atol=1e-5)


def test_fused_nll_bf16_multi_tile():
    # several vocab tiles + bf16 inputs (the production dtype)
    logits, labels = _data(B=1, S=128, V=1024)
    logits = logits.astype(jnp.bfloat16)
    nll = fused_ce_nll(logits, labels, interpret=True)
    np.testing.assert_allclose(np.asarray(nll),
                               np.asarray(_ref_nll(logits, labels)),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("z_loss", [0.0, 1e-4])
def test_fused_gradients_match(z_loss):
    logits, labels = _data(B=1, S=32, V=256)

    def loss_fused(x):
        return jnp.mean(fused_ce_nll(x, labels, z_loss=z_loss,
                                     interpret=True))

    def loss_ref(x):
        return jnp.mean(_ref_nll(x, labels, z_loss))

    g_fused = jax.grad(loss_fused)(logits)
    g_ref = jax.grad(loss_ref)(logits)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)


def test_untileable_vocab_returns_none():
    logits, labels = _data(V=500)  # 500 % 128 != 0
    assert fused_ce_nll(logits, labels, interpret=True) is None
    assert fit_vocab_block(500) == 0
    assert fit_vocab_block(50304) == 128
    assert fit_vocab_block(32000) == 256


def _mk_sharding(dp=(), tp=(), ulysses=False):
    from hetu_galvatron_tpu.runtime.mesh import LayerSharding

    return LayerSharding(dp_axes=tuple(dp), cp_axes=(), tp_axes=tuple(tp),
                         ulysses=ulysses)


@pytest.mark.distributed
def test_vocab_parallel_ce_matches_single_device(cpu_devices):
    """vtp4 x dp2: fused CE under shard_map (pmax/psum logsumexp merge) ==
    the plain XLA nll, values and gradients."""
    from jax.sharding import Mesh

    from hetu_galvatron_tpu.ops.pallas.cross_entropy import (
        make_vocab_parallel_ce,
    )

    mesh = Mesh(np.array(cpu_devices).reshape(2, 4), ("dp", "tp"))
    logits, labels = _data(B=2, S=64, V=512)
    nll_fn = make_vocab_parallel_ce(mesh, _mk_sharding(dp=("dp",),
                                                       tp=("tp",)),
                                    interpret=True)
    nll = nll_fn(logits, labels)
    np.testing.assert_allclose(np.asarray(nll),
                               np.asarray(_ref_nll(logits, labels)),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda x: jnp.mean(nll_fn(x, labels)))(logits)
    g_ref = jax.grad(lambda x: jnp.mean(_ref_nll(x, labels)))(logits)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.distributed
def test_vocab_parallel_ce_multi_axis_and_vsp(cpu_devices):
    from jax.sharding import Mesh

    from hetu_galvatron_tpu.ops.pallas.cross_entropy import (
        make_vocab_parallel_ce,
    )

    logits, labels = _data(B=2, S=64, V=1024)
    # vocab over two mesh axes: exercises the flattened axis-index offset
    mesh = Mesh(np.array(cpu_devices).reshape(2, 2, 2), ("dp", "t1", "t2"))
    nll_fn = make_vocab_parallel_ce(
        mesh, _mk_sharding(dp=("dp",), tp=("t1", "t2")), interpret=True)
    np.testing.assert_allclose(np.asarray(nll_fn(logits, labels)),
                               np.asarray(_ref_nll(logits, labels)),
                               rtol=1e-5, atol=1e-5)
    # vsp (ulysses): sequence sharded, head replicated — no collective leg
    mesh2 = Mesh(np.array(cpu_devices).reshape(2, 4), ("dp", "tp"))
    nll_fn2 = make_vocab_parallel_ce(
        mesh2, _mk_sharding(dp=("dp",), tp=("tp",), ulysses=True),
        interpret=True)
    np.testing.assert_allclose(np.asarray(nll_fn2(logits, labels)),
                               np.asarray(_ref_nll(logits, labels)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.distributed
def test_spmd_train_step_fused_ce_matches(cpu_devices):
    """End-to-end: the distributed train step with use_fused_ce=True (tp2-
    sharded 512-wide head, so the kernel really runs: V_local=256) produces
    the single-device reference loss."""
    from hetu_galvatron_tpu.core.args_schema import (
        CoreArgs,
        ModelArgs,
        TrainArgs,
    )
    from hetu_galvatron_tpu.models.builder import (
        causal_lm_loss,
        init_causal_lm,
    )
    from hetu_galvatron_tpu.parallel.spmd import (
        make_spmd_train_step,
        shard_params,
    )
    from hetu_galvatron_tpu.runtime.dataloader import make_batch
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.runtime.mesh import build_mesh
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer

    cfg = ModelArgs(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        vocab_size=512, max_position_embeddings=64, seq_length=16,
        hidden_act="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", tie_word_embeddings=False,
        add_bias_linear=False, add_qkv_bias=False,
        make_vocab_size_divisible_by=1, ffn_hidden_size=128,
        use_fused_ce=True)
    train = TrainArgs(lr=1e-2, lr_decay_style="constant", lr_warmup_iters=0)
    args = CoreArgs(model=cfg.model_dump(), train=train.model_dump())
    args.parallel.global_tp_deg = 2
    args.parallel.global_train_batch_size = 8

    params, axes = init_causal_lm(jax.random.key(0), cfg)
    data = np.random.RandomState(0).randint(0, 512, (8, cfg.seq_length + 1))
    batch = jax.tree.map(jnp.asarray, make_batch(data))
    ref = float(causal_lm_loss(params, batch, cfg,
                               compute_dtype=jnp.float32, fused_ce=False))

    hpc = get_hybrid_parallel_config(args, 8)
    mesh = build_mesh(8, hpc.pp_deg, devices=cpu_devices)
    tx = make_optimizer(train)
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        cfg, hpc, mesh, axes, tx, params,
        compute_dtype=jnp.float32, donate=False, kernel_interpret=True)
    sp = shard_params(params, pspecs, mesh)
    opt = jax.jit(tx.init, out_shardings=jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))(sp)
    _, _, metrics = step(sp, opt, jax.device_put(batch, batch_shd))
    assert abs(float(metrics["loss"]) - ref) < 2e-5


def test_cross_entropy_loss_fused_flag():
    """The public loss through the fused kernel (masked mean) == XLA path.
    ``interpret`` reaches the kernel only from here: the bare ``fused=True``
    asks for Mosaic, which a CPU refuses."""
    from functools import partial

    logits, labels = _data(B=2, S=64, V=512)
    mask = jnp.asarray(
        np.random.RandomState(1).rand(2, 64) > 0.3, jnp.float32)
    fused = partial(fused_ce_nll, interpret=True)
    a = cross_entropy_loss(logits, labels, mask)
    b = cross_entropy_loss(logits, labels, mask, fused=fused)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-5)
    with pytest.raises(ValueError, match="interpret mode"):
        cross_entropy_loss(logits, labels, mask, fused=True)
    ga = jax.grad(lambda x: cross_entropy_loss(x, labels, mask))(logits)
    gb = jax.grad(lambda x: cross_entropy_loss(x, labels, mask,
                                               fused=fused))(logits)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                               rtol=1e-5, atol=1e-6)
