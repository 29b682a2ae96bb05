"""Which path of the chunked gated delta rule runs follows from shapes and
devices alone: the ``jax.numpy`` form where no tile fits, the kernels handed
down by who knows the devices, traced under the scan's scope, one trace for
blocks of one shape (``ops/pallas/kda.py`` in interpret mode on the CPU; the
kernels against the two references are in ``test_kda_kernel.py``). A file of
its own so that neither runs longer than a worker's fair share
(``tests/conftest.py``: a module's cases stay on one worker)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.ops.pallas import kda

# (``_highest``: the autouse fixture, matmuls at full precision here too)
from test_kda_kernel import CHUNK, DECAYS, _highest, _inputs  # noqa: F401

pytestmark = pytest.mark.kernels


def test_shapes_that_fit_no_tile_take_the_jax_numpy_form():
    """The tests' tiny model (2 heads of 8, chunk 8) with the kernels
    handed in: they are not called, nothing is raised, and the result is
    the plain one's bits."""
    def never(*a, **kw):
        raise AssertionError("the kernels were called")
    ks = jax.random.split(jax.random.key(1), 5)
    shape = (2, 21, 2, 8)
    args = (jax.random.normal(ks[0], shape), jax.random.normal(ks[1], shape),
            jax.random.normal(ks[2], shape),
            -jax.random.uniform(ks[3], shape),
            jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])))
    # (one program a side: they trace to one jaxpr)
    got, want = (jax.jit(lambda *a, fn=fn: M.kda_chunked(
        *a, 8, jnp.float32, scan_fn=fn))(*args) for fn in (never, None))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="fit no tile"):
        kda.kda_scan(*args, 8, interpret=True)


@pytest.mark.parametrize("forced", [None, True, False])
def test_who_knows_the_devices_hands_the_kernels_down(forced):
    """``attention_overrides`` gives a kda layer its ``kda`` kernels where
    every device of the mesh is a TPU (here: never, unless a test says so),
    and no other layer ever."""
    from hetu_galvatron_tpu.parallel.spmd import attention_overrides
    from hetu_galvatron_tpu.runtime.mesh import LayerSharding, build_mesh

    mesh = build_mesh(2, 1, devices=jax.devices()[:2])
    per_layer = [LayerSharding(dp_axes=("d0",), cp_axes=(), tp_axes=())] * 4
    got = attention_overrides(
        per_layer, mesh, use_flash=False, flash_interpret=True,
        mixers=["latent_attention", "kda", "mamba", "kda"],
        kernels=forced)
    assert {i: list(ops.given()) for i, ops in got.items()} == (
        {1: ["kda", "conv"], 2: ["ssd", "conv", "gated_norm"],
         3: ["kda", "conv"]}
        if forced else {})
    if forced:
        # and what it hands down is the scan, under shard_map over dp
        args = tuple(jnp.concatenate([t, t]) for t in _inputs(
            CHUNK, 2, DECAYS["strongest_init"]))
        np.testing.assert_allclose(
            np.asarray(got[1].kda(*args, CHUNK)),
            np.asarray(M.kda_chunked(*args, CHUNK, jnp.float32)),
            rtol=1e-4, atol=1e-5)


def test_forward_and_backward_are_traced_under_the_scans_scope():
    """What lays device time over ``mixer/kda/scan`` is the ``op_name`` of a
    compiled instruction (``trace_analysis.scope_instructions``). The
    forward is called under the block's scope; the backward rule of a
    ``custom_vjp`` is traced when the gradient is taken, outside every
    scope of the model, and opens the scope itself. Here as the step does
    it: the scope around the forward only, ``jax.grad`` around the whole."""
    from hetu_galvatron_tpu.observability import trace_analysis

    assert kda.SCOPE == trace_analysis.KDA_SCAN_SCOPE
    assert kda.SCOPE in trace_analysis.MIXER_SCOPES["kda"]

    def block(*a):
        with jax.named_scope("mixer/kda"):
            with jax.named_scope("scan"):
                return kda.kda_scan(*a, CHUNK, interpret=True)

    args = _inputs(CHUNK, 2, DECAYS["strongest_init"])
    text = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(block(*a))),
                            argnums=(0, 1, 2, 3, 4))).lower(
                                *args).compile().as_text()
    found = trace_analysis.scope_instructions(text, (kda.SCOPE,))
    listed = set(found["scopes"][kda.SCOPE])
    calls = {"kda_scan_fwd": [0, 0], "kda_scan_bwd": [0, 0]}
    for line in text.splitlines():
        inst = trace_analysis._INSTRUCTION.match(line)
        op = trace_analysis._OP_NAME.search(line)
        if not inst or not op or inst.group(1) not in found["instructions"]:
            continue
        for call, (inside, outside) in calls.items():
            if f"/{call}/" in op.group(1):
                calls[call] = [inside + (inst.group(1) in listed),
                               outside + (inst.group(1) not in listed)]
    # (interpret mode: a call is the instructions it was unrolled into)
    for call, (inside, outside) in calls.items():
        assert inside > 0 and outside == 0, (call, inside, outside)
    assert found["mosaic_calls"] == frozenset()   # none on a CPU
    # so the step report's reader finds no kernel, and no loop either
    assert trace_analysis.kda_kernel_calls(text) == {
        "mosaic_calls": 0, "blocks": 0, "chunk": 0}


def test_blocks_of_one_shape_share_one_trace_of_the_kernels(monkeypatch):
    """Tracing a kernel's body is most of what tracing a KDA block costs
    (and the step program traces every kind of block once more to count
    what it holds, ``parallel/kept.py``): a second scan of the same shapes,
    in another trace of the same kind, runs no kernel's Python again,
    forward or backward."""
    traced = {"fwd": 0, "bwd": 0}

    def counting(name, kernel):
        def body(*refs, **statics):
            traced[name] += 1
            return kernel(*refs, **statics)
        return body

    monkeypatch.setattr(kda, "_fwd_kernel", counting("fwd", kda._fwd_kernel))
    monkeypatch.setattr(kda, "_bwd_kernel", counting("bwd", kda._bwd_kernel))
    kda._scan_call.clear_cache()
    kda._scan_bwd_call.clear_cache()
    args = _inputs(CHUNK, 2, DECAYS["strongest_init"], seed=3)
    scan = lambda *a: kda.kda_scan(*a, CHUNK, interpret=True)
    trace = lambda block, scale: jax.make_jaxpr(jax.grad(
        lambda *a: scale * jnp.sum(block(*a)), argnums=(0, 1, 2, 3, 4)))(*args)
    try:
        trace(scan, 1.0)
        # (the forward that keeps its states, and the backward)
        assert traced == {"fwd": 1, "bwd": 1}
        trace(scan, 2.0)
        assert traced == {"fwd": 1, "bwd": 1}
        # a recomputed block's: the primal call, which keeps no states, and
        # the keeping forward as ``jax.checkpoint`` traces it, once each
        trace(jax.checkpoint(scan), 1.0)
        assert traced == {"fwd": 3, "bwd": 1}
        trace(jax.checkpoint(scan), 2.0)
        trace(scan, 3.0)
        assert traced == {"fwd": 3, "bwd": 1}
    finally:
        # (what was traced through the counting bodies is not left behind)
        kda._scan_call.clear_cache()
        kda._scan_bwd_call.clear_cache()
