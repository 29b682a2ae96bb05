"""Per-layer remat keeps a flash attention core's output and row statistics
(``modules.remat`` saves the two values ``flash_attention._flash_fwd``
names), so a block's recomputed forward holds no forward kernel: one
``flash_attention_fwd`` call a block in the gradient's jaxpr where plain
``jax.checkpoint`` with the same base policy has two, and the same numbers to
the last bit. Interpret-mode kernels on the CPU; the compile of the real
kernels for a described v5e is in ``test_flash_mosaic_compile.py``.

Bit-equality is asserted op by op (no outer ``jit``): each primitive then
runs as its own program on both sides, and what is compared is the
arithmetic, not which elementwise neighbours XLA:CPU chose to fuse into a
matmul in two differently shaped programs."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.ops.pallas.flash_attention import (
    flash_sdpa,
    make_flash_sdpa,
)

pytestmark = pytest.mark.kernels

B, S, H, N = 2, 128, 64, 2
CFG = ModelArgs(
    hidden_size=H, num_hidden_layers=2, num_attention_heads=N, vocab_size=64,
    max_position_embeddings=S, seq_length=S, hidden_act="swiglu",
    normalization="rmsnorm", position_embedding_type="rope",
    add_bias_linear=False, add_qkv_bias=False, make_vocab_size_divisible_by=1)
BASE_POLICIES = {
    "full": None,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch":
        jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}

# blocks in the stack, remat_policy, what the block is given besides
_CASES = {
    "block": (1, "full", ()),
    "stack": (2, "full", ()),
    "dots": (1, "dots", ()),
    "dots_no_batch": (1, "dots_no_batch", ()),
    "segments": (1, "full", ("segments",)),
    "dropout": (1, "full", ("dropout",)),
    "shard_map": (1, "full", ("shard_map",)),
    "shard_map_stack_dropout": (2, "full", ("shard_map", "dropout")),
}


def _flash_interpret(q, k, v, **kw):
    return flash_sdpa(q, k, v, interpret=True, **kw)


_flash_interpret.supports_segments = True
_flash_interpret.supports_dropout = True
_flash_interpret.supports_scale = True


def _core(extras, cpu_devices):
    """The block's attention core: the XLA one, the flash kernels, or the
    flash kernels under ``make_flash_sdpa``'s ``shard_map`` (dp2 x tp2)."""
    if "xla" in extras:
        return M.xla_sdpa
    if "shard_map" not in extras:
        return _flash_interpret
    from jax.sharding import Mesh

    mesh = Mesh(np.array(cpu_devices[:4]).reshape(2, 2), ("dp", "tp"))
    return make_flash_sdpa(mesh, dp_axes=("dp",), tp_axes=("tp",),
                           interpret=True)


def _stack(blocks, policy, extras, cpu_devices):
    """``(loss, params, x, cfg, block)``: ``loss(wrap)(params, x)`` runs
    ``blocks`` decoder blocks ``block(i)``, each wrapped by ``wrap``."""
    cfg = CFG.model_copy(update={
        "remat_policy": policy,
        "attention_dropout": 0.2 if "dropout" in extras else 0.0})
    kwargs = {"ops": M.LayerOps(sdpa=_core(extras, cpu_devices)),
              "compute_dtype": jnp.float32}
    if "segments" in extras:
        # a document boundary inside a score tile
        kwargs["segment_ids"] = jnp.asarray(
            np.repeat([[0, 1], [0, 2]], [40, S - 40], axis=1)
            .reshape(B, S).astype(np.int32))
    params = [M.init_decoder_layer(jax.random.key(i), cfg)[0]
              for i in range(blocks)]
    x = jax.random.normal(jax.random.key(9), (B, S, H), jnp.float32)

    def block(i):
        rng = (jax.random.fold_in(jax.random.key(3), i)
               if "dropout" in extras else None)
        return lambda p, h: M.apply_decoder_layer(p, h, cfg, dropout_rng=rng,
                                                  **kwargs)

    def loss(wrap):
        def fn(ps, h):
            for i, p in enumerate(ps):
                h = wrap(block(i))(p, h)
            return jnp.sum(h ** 2)
        return fn

    return loss, params, x, cfg, block


def _kernel_calls(jaxpr, kernel="flash_attention_fwd"):
    """``pallas_call`` equations named ``kernel`` in ``jaxpr`` and in every
    jaxpr its equations hold (checkpoint, shard_map, pjit, custom calls)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == kernel
            continue    # (the kernel's own body holds no call)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _kernel_calls(sub, kernel)
    return n


@pytest.mark.parametrize("case", sorted(_CASES))
def test_a_rematted_block_runs_its_attention_core_once(cpu_devices, case):
    blocks, policy, extras = _CASES[case]
    loss, params, x, cfg, _ = _stack(blocks, policy, extras, cpu_devices)
    kept = loss(lambda fn: M.remat(fn, cfg))
    plain = loss(lambda fn: jax.checkpoint(fn, policy=BASE_POLICIES[policy]))
    jaxprs = {name: jax.make_jaxpr(jax.grad(fn))(params, x).jaxpr
              for name, fn in (("kept", kept), ("plain", plain))}
    assert {name: _kernel_calls(j) for name, j in jaxprs.items()} == {
        "kept": blocks, "plain": 2 * blocks}
    for kernel in ("flash_attention_bwd_dq", "flash_attention_bwd_dkv"):
        assert _kernel_calls(jaxprs["kept"], kernel) == blocks
    ours = jax.value_and_grad(kept, argnums=(0, 1))(params, x)
    theirs = jax.value_and_grad(plain, argnums=(0, 1))(params, x)
    assert np.isfinite(float(ours[0]))
    paths = jax.tree_util.tree_flatten_with_path(ours)[0]
    for (path, a), b in zip(paths, jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{case}: {jax.tree_util.keystr(path)}")


def _kept_beside_the_arguments(capsys, fn, *args):
    """Shapes of what ``fn`` (a rematted function) saves for its backward
    pass that is no argument of it (``jax.ad_checkpoint``'s own listing)."""
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(fn, *args)
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines and all(re.match(r"\w+\[[\d,]*\] ", l) for l in lines), lines
    return sorted(l.split(" ", 1)[0] for l in lines
                  if " from the argument " not in l)


@pytest.mark.parametrize("core,policy", [
    ("flash", "full"), ("flash_shard_map", "full"), ("xla", "full"),
    ("xla", "dots_no_batch")])
def test_what_a_rematted_block_keeps(cpu_devices, capsys, core, policy):
    """``full`` keeps the block's input and, of a flash core, the output as
    [B, S, N * Dv] rows (the bytes of the input) and lse as [B, N, S] rows
    (never the [B, N, S, 1] column HBM pads 128 times); a block on the XLA
    core names nothing and keeps what the base policy keeps."""
    extras = {"flash": (), "flash_shard_map": ("shard_map",),
              "xla": ("xla",)}[core]
    _, params, x, cfg, block = _stack(1, policy, extras, cpu_devices)
    kept = _kept_beside_the_arguments(capsys, M.remat(block(0), cfg),
                                      params[0], x)
    if core == "xla":
        plain = _kept_beside_the_arguments(
            capsys, jax.checkpoint(block(0), policy=BASE_POLICIES[policy]),
            params[0], x)
        assert kept == plain and (policy != "full" or kept == [])
        return
    if core == "flash":
        assert kept == sorted([f"f32[{B},{S},{H}]", f"f32[{B},{N},{S}]"])
    # (a shard_map's residuals are its shards' laid side by side on a new
    # leading axis: the same numbers, and no trailing singleton either)
    shapes = [tuple(int(d) for d in re.findall(r"\d+", k.split("[")[1]))
              for k in kept]
    assert sorted(int(np.prod(sh)) for sh in shapes) == [B * N * S, B * S * H]
    assert all(len(sh) == 3 and sh[-1] > 1 for sh in shapes), shapes
