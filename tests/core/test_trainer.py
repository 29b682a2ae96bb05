"""Trainer/optimizer/dataloader unit tests: schedules, decay masking,
microbatch-accumulation equivalence, and a short loss-goes-down run."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, ModelArgs, TrainArgs
from hetu_galvatron_tpu.models.builder import init_causal_lm
from hetu_galvatron_tpu.runtime.dataloader import (
    RandomTokenDataset,
    get_data_iterator,
    make_batch,
    synthetic_batches,
)
from hetu_galvatron_tpu.runtime.optimizer import (
    HostSchedule,
    global_grad_norm,
    make_lr_schedule,
    make_optimizer,
)
from hetu_galvatron_tpu.runtime.trainer import (
    make_loss_fn,
    make_train_step,
)

pytestmark = pytest.mark.utils

TINY = ModelArgs(
    hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
    vocab_size=64, max_position_embeddings=32, seq_length=8,
    make_vocab_size_divisible_by=1,
)


def test_lr_schedules():
    for style in ["constant", "linear", "cosine", "inverse-square-root", "WSD"]:
        t = TrainArgs(lr=1e-3, min_lr=1e-5, lr_decay_style=style,
                      lr_warmup_iters=10, train_iters=100,
                      lr_wsd_decay_iters=20)
        sched = make_lr_schedule(t)
        # warmup ramps from 0
        assert float(sched(0)) < 1e-4
        assert abs(float(sched(10)) - 1e-3) < 1e-4
        final = float(sched(99))
        if style != "constant":
            assert final < 1e-3 + 1e-9
        assert final >= 0.0


LR_STYLES = ["constant", "linear", "cosine", "inverse-square-root", "WSD"]


def _lr_args(style, warmup):
    return TrainArgs(lr=1e-3, min_lr=1e-5, lr_decay_style=style,
                     lr_warmup_iters=warmup, train_iters=100,
                     lr_wsd_decay_iters=20)


@pytest.mark.parametrize("warmup", [0, 10])
@pytest.mark.parametrize("style", LR_STYLES)
def test_printed_lr_is_the_optimizers_schedule(style, warmup):
    """The log line's learning rate (``HostSchedule``, what ``run_loop``
    calls) is ``make_lr_schedule``'s value: the first iteration, around
    the end of the warm-up, the last decay step and one past it."""
    t = _lr_args(style, warmup)
    schedule, printed = make_lr_schedule(t), HostSchedule(t)
    in_step = jax.jit(schedule)   # as the optimizer evaluates it
    for it in sorted({0, max(warmup - 1, 0), warmup, warmup + 1, 99, 100}):
        got = printed(it)
        assert isinstance(got, float)
        assert got == pytest.approx(float(in_step(it)), rel=1e-7, abs=0)
        # the eager evaluation rounds once more where XLA fuses a multiply
        # and an add: one f32 ulp at the peak, which is 2.5e-6 of a value
        # at the floor, where two numbers of the peak's size cancel
        assert abs(got - float(schedule(it))) \
            <= np.finfo(np.float32).eps * t.lr


@pytest.mark.parametrize("first", [0, 7, 1500])
def test_printed_lr_is_a_host_lookup_inside_a_block(first):
    """One program and one read-back a block of iterations, wherever the
    run starts (a resume); every other look-up touches no device at all."""
    t = _lr_args("cosine", 10)
    printed = HostSchedule(t)
    programs, block = [], printed._block
    printed._block = lambda its: programs.append(int(its[0])) or block(its)
    want = np.asarray(jax.jit(jax.vmap(make_lr_schedule(t)))(
        np.arange(first, first + HostSchedule.BLOCK + 1, dtype=np.int32)))
    assert printed(first) == float(want[0])
    with jax.transfer_guard("disallow"):
        got = [printed(it) for it in range(first, first + HostSchedule.BLOCK)]
    assert programs == [first]
    np.testing.assert_array_equal(np.float32(got), want[:-1])
    # past the block's end: the next block, once
    assert printed(first + HostSchedule.BLOCK) == float(want[-1])
    assert programs == [first, first + HostSchedule.BLOCK]


def test_optimizer_decay_mask_and_step():
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,)),
              "scale": jnp.ones((4,))}
    t = TrainArgs(lr=0.1, weight_decay=0.5, lr_warmup_iters=0,
                  lr_decay_style="constant", clip_grad=0.0)
    tx = make_optimizer(t)
    state = tx.init(params)
    zero_g = jax.tree.map(jnp.zeros_like, params)
    upd, _ = tx.update(zero_g, state, params)
    # zero grads: 2D weight decays, 1D bias/scale must not move
    assert float(jnp.abs(upd["w"]).sum()) > 0
    assert float(jnp.abs(upd["b"]).sum()) == 0
    assert float(jnp.abs(upd["scale"]).sum()) == 0


def test_global_grad_norm():
    g = {"a": jnp.full((2, 2), 3.0), "b": jnp.full((3,), 4.0)}
    expect = np.sqrt(4 * 9 + 3 * 16)
    assert abs(float(global_grad_norm(g)) - expect) < 1e-5


def test_dataset_deterministic_and_batch_shapes():
    ds1 = RandomTokenDataset(64, 8, size=16, seed=7)
    ds2 = RandomTokenDataset(64, 8, size=16, seed=7)
    np.testing.assert_array_equal(ds1[3], ds2[3])
    b = make_batch(np.stack([ds1[0], ds1[1]]))
    assert b["tokens"].shape == (2, 8)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    it = synthetic_batches(TINY, 4)
    first = next(it)
    assert first["tokens"].shape == (4, 8)
    assert first["tokens"].max() < TINY.padded_vocab_size


def _assert_chunked_matches(m1, p1, m4, p4, dtype, lr=1e-2):
    """Four microbatches against one batch. float32: element by element.
    bfloat16 (float32 parameters, Adam's first update lr * sign(g)): a
    microbatch of two rounds other sums than a batch of eight, and a
    gradient element that is zero to eight bits may turn its sign and land
    2 lr away: the loss within 1e-4 (measured 5e-7), under 1 % of the
    elements further than lr / 2 (measured 0.05 % and, under the uneven
    mask, 0.20 %), none further than 2 lr."""
    if dtype == jnp.float32:
        assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-5
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
        return
    dist = np.concatenate([np.abs(np.asarray(a) - np.asarray(b)).ravel()
                           for a, b in zip(jax.tree.leaves(p1),
                                           jax.tree.leaves(p4))])
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-4
    assert dist.max() <= 2 * lr + 1e-5
    assert (dist > lr / 2).mean() < 1e-2


dtype_axis = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                     ids=lambda d: jnp.dtype(d).name)


@dtype_axis
def test_microbatch_accumulation_matches_full_batch(dtype):
    params, _ = init_causal_lm(jax.random.key(0), TINY)
    loss_fn = make_loss_fn(TINY, compute_dtype=dtype)
    t = TrainArgs(lr=1e-2, clip_grad=0.0, weight_decay=0.0,
                  lr_decay_style="constant", lr_warmup_iters=0)
    tx = make_optimizer(t)
    step1 = jax.jit(make_train_step(loss_fn, tx, chunks=1))
    step4 = jax.jit(make_train_step(loss_fn, tx, chunks=4))
    batch = make_batch(
        np.random.RandomState(0).randint(0, 64, (8, 9)).astype(np.int32))
    batch = jax.tree.map(jnp.asarray, batch)
    opt = tx.init(params)
    p1, _, m1 = step1(params, opt, batch)
    p4, _, m4 = step4(params, opt, batch)
    _assert_chunked_matches(m1, p1, m4, p4, dtype)


def test_launcher_loss_decreases():
    """The one training loop there is (``cli/train_dist.py::run_loop``,
    through ``main``) learns: the synthetic dataset holds 1024 samples, so
    a batch of 1024 is the same batch every step and the model can
    memorize it (uniform random tokens are otherwise irreducible)."""
    import os

    from hetu_galvatron_tpu.cli import train_dist

    yaml = os.path.join(os.path.dirname(train_dist.__file__), "..", "models",
                        "configs", "gpt2-small.yaml")
    out = {}
    rc = train_dist.main(
        [yaml, "model.hidden_size=32", "model.num_hidden_layers=2",
         "model.num_attention_heads=2", "model.vocab_size=64",
         "model.seq_length=8", "model.max_position_embeddings=32",
         "model.make_vocab_size_divisible_by=1", "train.train_iters=25",
         "train.lr=1e-2", "train.lr_warmup_iters=0",
         "parallel.mixed_precision=fp32", "parallel.num_devices=1",
         "parallel.global_train_batch_size=1024", "data.dataset=random"],
        result=out)
    losses = out["losses"]
    assert rc == 0 and len(losses) == 25
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.1
    assert all(b < a for a, b in zip(losses, losses[1:]))


def test_get_data_iterator_random():
    args = CoreArgs(model=TINY.model_dump())
    b = next(get_data_iterator(args, global_batch_size=4))
    assert b["tokens"].shape == (4, TINY.seq_length)


@dtype_axis
def test_microbatch_nonuniform_loss_mask_matches(dtype):
    """chunks>1 must equal chunks=1 even when microbatches carry very
    different numbers of valid tokens (token-weighted accumulation)."""
    params, _ = init_causal_lm(jax.random.key(0), TINY)
    from hetu_galvatron_tpu.runtime.trainer import make_loss_fn
    loss_fn = make_loss_fn(TINY, compute_dtype=dtype)
    t = TrainArgs(lr=1e-2, clip_grad=0.0, weight_decay=0.0,
                  lr_decay_style="constant", lr_warmup_iters=0)
    tx = make_optimizer(t)
    step1 = jax.jit(make_train_step(loss_fn, tx, chunks=1))
    step4 = jax.jit(make_train_step(loss_fn, tx, chunks=4))
    batch = make_batch(
        np.random.RandomState(0).randint(0, 64, (8, 9)).astype(np.int32))
    mask = np.ones((8, 8), np.float32)
    mask[:2] = 0.0          # first microbatch fully masked
    mask[2, 4:] = 0.0       # second partially masked
    batch["loss_mask"] = mask
    batch = jax.tree.map(jnp.asarray, batch)
    opt = tx.init(params)
    p1, _, m1 = step1(params, opt, batch)
    p4, _, m4 = step4(params, opt, batch)
    _assert_chunked_matches(m1, p1, m4, p4, dtype)


from hetu_galvatron_tpu.utils.strategy import IGNORED_PLAN_KEYS  # noqa: E402

# the keywords that chose the hierarchical dp reduction, or its lane loss
GONE = set(IGNORED_PLAN_KEYS) | {"hier", "lane_dp"}
ONE_PATH = [
    ("hetu_galvatron_tpu.runtime.trainer", "make_train_step"),
    ("hetu_galvatron_tpu.parallel.spmd", "build_spmd_loss_fn"),
    ("hetu_galvatron_tpu.parallel.spmd", "make_spmd_train_step"),
    ("hetu_galvatron_tpu.runtime.pipeline", "PipelineEngine"),
    ("hetu_galvatron_tpu.runtime.compiled_pipeline",
     "CompiledPipelineEngine"),
]


@pytest.mark.parametrize("module,name", ONE_PATH,
                         ids=[n for _, n in ONE_PATH])
def test_a_step_maker_takes_no_second_gradient_reduction(module, name):
    """Gradients are reduced over dp by XLA's partitioner, once a
    microbatch: no step maker or engine has a keyword that would choose
    another reduction."""
    import importlib
    import inspect

    made = getattr(importlib.import_module(module), name)
    assert not GONE & set(inspect.signature(made).parameters)
