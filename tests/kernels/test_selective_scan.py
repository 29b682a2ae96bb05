"""Mamba-1's selective scan in its chunked ``jax.numpy`` form
(``modules.selective_scan``: the oracle the kernels of
``ops/pallas/selective_scan.py`` are tested against in
``test_selective_scan_kernel.py``)
against the recurrence one position at a time, and the names the step
carries for what phi4flash adds: the scopes in the compiled step's HLO, the
gauges and the cores ``train_dist`` logs for the cut model through its
normal entry point. CPU, fp32, tiny widths."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.observability import trace_analysis
from hetu_galvatron_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
    set_registry,
)

pytestmark = [pytest.mark.kernels]

ZOO = os.path.join(os.path.dirname(M.__file__), "configs")
B, S, C, N = 2, 21, 12, 4


def _inputs(seed=0, strongest=0.1, seq=S):
    k = jax.random.split(jax.random.key(seed), 5)
    return dict(
        u=jax.random.normal(k[0], (B, seq, C)),
        dt=jax.random.uniform(k[1], (B, seq, C), minval=1e-3,
                              maxval=strongest),
        A=-jnp.broadcast_to(jnp.arange(1.0, N + 1), (C, N))
        * jax.random.uniform(k[2], (C, 1), minval=0.5, maxval=1.5),
        Bm=jax.random.normal(k[3], (B, seq, N)),
        Cm=jax.random.normal(k[4], (B, seq, N)))


def _one_position_at_a_time(u, dt, A, Bm, Cm):
    """The benchmark's plain reference, which shares nothing with the
    chunked form."""
    from benchmark import reference

    return reference.load_family("phi4flash").selective_scan(
        u, dt, A, Bm, Cm)


# chunks that divide the sequence (7, 21, 1), that do not (5, 16), and one
# longer than it (64)
@pytest.mark.parametrize("chunk", [1, 5, 7, 16, 21, 64])
def test_the_chunked_scan_is_the_recurrence(chunk):
    x = _inputs()
    ours = M.selective_scan(**x, chunk=chunk)
    theirs = _one_position_at_a_time(**x)
    assert ours.shape == (B, S, C) and ours.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               atol=1e-6 * float(jnp.abs(theirs).max()))


@pytest.mark.parametrize("chunk", [5, 7])
def test_its_five_gradients_are_the_recurrences(chunk):
    x = _inputs(seed=1)
    weight = jax.random.normal(jax.random.key(9), (B, S, C))
    ours = jax.grad(lambda x: jnp.sum(
        weight * M.selective_scan(**x, chunk=chunk)))(x)
    theirs = jax.grad(lambda x: jnp.sum(
        weight * _one_position_at_a_time(**x)))(x)
    for name in x:
        assert float(jnp.abs(theirs[name]).max()) > 0, name
        np.testing.assert_allclose(
            np.asarray(ours[name]), np.asarray(theirs[name]),
            atol=2e-6 * float(jnp.abs(theirs[name]).max()), err_msg=name)


def test_a_decay_far_past_the_initialisations_neither_overflows_nor_drifts():
    """``dt A`` down to -160 a position (a fresh block draws -1.6 at the
    strongest): the state is multiplied by ``exp(dt A)`` itself, never by a
    cumulative decay's inverse."""
    x = _inputs(seed=2, strongest=100.0)
    ours = M.selective_scan(**x, chunk=8)
    assert bool(jnp.isfinite(ours).all())
    theirs = _one_position_at_a_time(**x)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               atol=1e-6 * float(jnp.abs(theirs).max()))


def _largest_value(jaxpr):
    most = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            most = max(most, int(np.prod(v.aval.shape)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            most = max(most, _largest_value(sub))
    return most


def test_neither_pass_holds_a_state_for_every_position():
    """Forward and backward hold a chunk's states [chunk, N, C] and the
    states that entered the chunks [S / chunk, N, C], never [S, N, C]."""
    x = _inputs(seq=64)
    grad = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(
        M.selective_scan(**x, chunk=8))))(x)
    assert _largest_value(grad.jaxpr) < B * 64 * C * N // 2
    # (a scan over positions that keeps its states for the backward pass
    # does hold them)
    kept = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(
        _one_position_at_a_time(**x))))(x)
    assert _largest_value(kept.jaxpr) >= B * 64 * C * N


def test_the_mixer_pads_nothing_into_its_memory():
    """A sequence the chunk does not divide: the memory a block leaves is
    the sequence's own positions."""
    from hetu_galvatron_tpu.core.args_schema import ModelArgs

    cfg = ModelArgs(hidden_size=8, num_hidden_layers=1, num_attention_heads=2)
    p, axes = M.init_mamba1(jax.random.key(0), cfg)
    assert jax.tree.structure(p) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    np.testing.assert_allclose(np.asarray(jnp.exp(p["A_log"])[3]),
                               np.arange(1.0, 17.0), rtol=1e-6)
    dt = jax.nn.softplus(p["dt_bias"])
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.001
    made = {}
    out = M.apply_mamba1(p, jnp.ones((1, 21, 8)), cfg,
                         compute_dtype=jnp.float32, made=made)
    assert out.shape == (1, 21, 8) and made["memory"].shape == (1, 21, 16)


# ---------------------------------------------------------------------------
# the names the step carries
# ---------------------------------------------------------------------------

NEW_SCOPES = (
    "mixer/mamba1/in_proj", "mixer/mamba1/conv", "mixer/mamba1/x_proj",
    "mixer/mamba1/scan", "mixer/mamba1/gate", "mixer/mamba1/out_proj",
    "mixer/gmu/in_proj", "mixer/gmu/gate", "mixer/gmu/out_proj",
    "attn/diff", "attn/cross_core")


@pytest.mark.parametrize("scope", NEW_SCOPES)
def test_every_new_scope_is_in_the_vocabulary(scope):
    assert scope in trace_analysis.SCOPES


def test_a_mixers_scopes_are_read_off_the_vocabulary():
    """One place a kind: ``MIXER_SCOPES`` and ``OWN_SCOPES`` follow from the
    ``mixer/<kind>/`` names of ``SCOPES``."""
    assert set(trace_analysis.MIXER_SCOPES) == {
        "short_conv", "mamba", "kda", "mamba1", "gmu", "gdn"}
    for kind, scopes in trace_analysis.MIXER_SCOPES.items():
        assert scopes == tuple(s for s in trace_analysis.SCOPES
                               if s.startswith(f"mixer/{kind}/"))
        assert set(scopes) <= set(trace_analysis.OWN_SCOPES)
    assert set(trace_analysis.MTP_SCOPES) <= set(trace_analysis.OWN_SCOPES)
    assert len(set(trace_analysis.OWN_SCOPES)) == len(
        trace_analysis.OWN_SCOPES)
    assert "mixer/mamba1/conv" in trace_analysis.CONV_SCOPES


TINY = [
    "model.hidden_size=32", "model.num_hidden_layers=6",
    "model.layer_types=[mamba1,sliding_attention,mamba1,full_attention,"
    "gmu,cross_attention]",
    "model.num_attention_heads=8", "model.num_key_value_heads=4",
    "model.ffn_hidden_size=48", "model.vocab_size=64", "model.seq_length=24",
    "model.max_position_embeddings=64",
    "model.make_vocab_size_divisible_by=1", "model.sliding_window=6",
    "model.mamba1_dt_rank=2",
    "data.dataset=random", "train.train_iters=2",
    "parallel.mixed_precision=fp32", "parallel.global_train_batch_size=8",
    "parallel.global_checkpoint=1"]


@pytest.fixture(scope="module")
def launched():
    """``train_dist.main`` on the zoo's YAML cut by overrides alone, on a
    registry of its own: the same entry point, loader, trainer, plan and
    step report as every other model."""
    from hetu_galvatron_tpu.cli import train_dist

    before = get_registry()
    reg = set_registry(MetricsRegistry())
    try:
        out = {}
        assert train_dist.main(
            [os.path.join(ZOO, "phi-4-mini-flash.yaml")] + TINY,
            result=out) == 0
        yield reg, out, trace_analysis.step_scopes()
    finally:
        set_registry(before)


def test_the_trainer_trains_the_cut_model(launched):
    _, out, _ = launched
    assert len(out["losses"]) == 2 and all(np.isfinite(out["losses"]))
    assert abs(out["losses"][0] - np.log(64)) < 0.5
    assert out["attention_cores"] == [
        "mamba1", "xla[w6]", "mamba1", "xla", "gmu", "xla"]
    assert out["blocks"] == {
        "mamba1/dense": 2, "sliding_attention/dense": 1,
        "full_attention/dense": 1, "gmu/dense": 1, "cross_attention/dense": 1}


@pytest.mark.parametrize("gauge,value", [
    ("mamba1/blocks", 2), ("gmu/blocks", 1), ("cross/blocks", 1),
    # a microbatch: 8 sequences of 24 positions, float32
    ("shared/memory_bytes", 8 * 24 * 64 * 4),
    ("shared/kv_bytes", 8 * 24 * 2 * 16 * 4),
])
def test_the_gauges_say_what_crosses_blocks(launched, gauge, value):
    reg, out, _ = launched
    assert [m.value for m in reg.metrics() if m.name == gauge] == [value]
    assert out["shared_values"][gauge] == value


def test_the_gauge_says_the_scans_kernels_did_not_engage(launched):
    """On a CPU (and at 64 channels) the ``jax.numpy`` form ran: no Mosaic
    call under ``mixer/mamba1/scan``, said by the gauge, ``train()``'s
    result and no scan made twice."""
    reg, out, _ = launched
    assert [m.value for m in reg.metrics()
            if m.name == "selective/mosaic_calls"] == [0]
    assert out["selective_mosaic_calls"] == 0
    assert out["scans_recomputed"] == 0


@pytest.mark.parametrize("scope", NEW_SCOPES + ("attn/window_core",
                                                "attn/core", "mlp"))
def test_every_new_scope_holds_instructions_of_the_step(launched, scope):
    _, _, kept = launched
    scopes = {c[0] for c in kept["map"]["instructions"].values()}
    assert scope in scopes
    if scope.startswith("mixer/"):
        assert kept["scopes"][scope]
