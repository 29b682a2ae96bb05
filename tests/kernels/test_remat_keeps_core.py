"""Per-layer remat keeps a flash attention core's output and row statistics
(``modules.remat`` saves the two values ``flash_attention._flash_fwd``
names), so a block's recomputed forward holds no forward kernel: one
``flash_attention_fwd`` call a block in the gradient's jaxpr where plain
``jax.checkpoint`` with the same base policy has two, and the same numbers to
the last bit. The same of a delta-rule block's ``kda_scan_fwd`` (a decay a
head: ``gdn_scan_fwd``), a Mamba-2
block's ``ssd_scan_fwd`` and a Mamba-1 block's ``selective_scan_fwd``, whose
differentiated forward names its output and the states that entered the
chunks (each kernel file's ``KEPT``).
Interpret-mode kernels on the CPU; the compile of the real kernels for a
described v5e is in ``test_flash_mosaic_compile.py``.

Here the kernels are counted in jaxprs, which costs no compile; that the
numbers are plain ``jax.checkpoint``'s to the last bit is in
``test_remat_keeps_numbers.py``."""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.models import modules as M
from hetu_galvatron_tpu.ops.pallas import gdn, kda, selective_scan, ssd
from hetu_galvatron_tpu.ops.pallas.flash_attention import (
    flash_sdpa,
    make_flash_sdpa,
)

pytestmark = pytest.mark.kernels

B, S, H, N = 2, 128, 64, 2
# the recurrent mixers at shapes their kernels' tiles fit (``tile_plan``),
# two chunks a sequence so that a state enters the second: 2 heads of 128
# at the cell's chunk of 64 (a decay a head: keys of 32 under values of 48),
# 8 heads of 16 with a state of 128 at a chunk of one lane tile over twice
# the positions, and 128 channels with a state of 16 over two of the
# selective scan's chunks
CFG = ModelArgs(
    hidden_size=H, num_hidden_layers=2, num_attention_heads=N, vocab_size=64,
    max_position_embeddings=2 * S, seq_length=S, hidden_act="swiglu",
    normalization="rmsnorm", position_embedding_type="rope",
    add_bias_linear=False, add_qkv_bias=False, make_vocab_size_divisible_by=1,
    kda_num_heads=2, kda_head_dim=128, kda_chunk_size=64,
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=32,
    linear_value_head_dim=48, linear_chunk_size=64,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=128,
    mamba_chunk_size=128)
# mixer kind -> (the field of ``LayerOps`` its kernels go in, the kernels,
# the same on a mesh, forward and backward kernel, positions, what the
# differentiated forward names and the shape of each)
SCANS = {
    "kda": ("kda", kda.kda_scan, kda.make_kda_scan, "kda_scan_fwd",
            "kda_scan_bwd", S, dict(zip(kda.KEPT, (
                (B, S, 2, 128), (B, S // 64, 2, 128, 128))))),
    # (the kernels' own output, head-major)
    "linear_attention": ("gdn", gdn.gdn_scan, gdn.make_gdn_scan,
                         "gdn_scan_fwd", "gdn_scan_bwd", S, dict(zip(
                             gdn.KEPT, ((B, 2, S, 48),
                                        (B, S // 64, 2, 32, 48))))),
    "mamba": ("ssd", ssd.ssd_scan, ssd.make_ssd_scan, "ssd_scan_fwd",
              "ssd_scan_bwd", 2 * S, dict(zip(ssd.KEPT, (
                  (B, 2 * S, 128), (B, 2 * S // 128, 128, 128))))),
    "mamba1": ("selective", selective_scan.selective_scan,
               selective_scan.make_selective_scan, "selective_scan_fwd",
               "selective_scan_bwd", 2 * S, dict(zip(selective_scan.KEPT, (
                   (B, 2 * S, 128),
                   (B, 2 * S // selective_scan.CHUNK, 16, 128))))),
}
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
    # heads of 64 and of 128: the kernels index the projections' rows, and
    # what remat keeps of the output is the kernel's own result (under
    # tp2 a shard holds one head of the two: half a pair)
    "rows_pairs": (1, "full", ("w64",)),
    "rows_pairs_dots_segments": (1, "dots", ("w64", "segments")),
    "rows_pairs_shard_map_stack_dropout": (2, "full", (
        "w64", "shard_map", "dropout")),
    "rows_w128_stack": (2, "full", ("w128",)),
    **{f"{kind}_{case}": (blocks, policy, (kind,) + extras)
       for kind in SCANS for case, (blocks, policy, extras) in {
           "block": (1, "full", ()), "stack": (2, "full", ()),
           "dots": (1, "dots", ()), "dots_no_batch": (1, "dots_no_batch", ()),
           "shard_map": (1, "full", ("shard_map",))}.items()},
}


def _flash_interpret(q, k, v, **kw):
    return flash_sdpa(q, k, v, interpret=True, **kw)


_flash_interpret.supports_segments = True
_flash_interpret.supports_dropout = True
_flash_interpret.supports_scale = True


def _kind(extras):
    """The blocks' mixer kind: a recurrent one where a case names it."""
    return next((k for k in SCANS if k in extras), "full_attention")


def _ops(extras, cpu_devices):
    """What the block is handed: the XLA attention core, the flash kernels,
    or the flash kernels under ``make_flash_sdpa``'s ``shard_map`` (dp2 x
    tp2); for a recurrent mixer its scan's kernels, alone or under
    ``on_shards`` over dp2, or nothing (``numpy``: the ``jax.numpy`` form)."""
    from jax.sharding import Mesh

    kind = _kind(extras)
    if kind in SCANS:
        field, scan, on_mesh = SCANS[kind][:3]
        if "numpy" in extras:
            return M.LayerOps()
        if "shard_map" not in extras:
            return M.LayerOps(**{field: functools.partial(scan,
                                                          interpret=True)})
        return M.LayerOps(**{field: on_mesh(
            Mesh(np.array(cpu_devices[:2]), ("dp",)), dp_axes=("dp",),
            interpret=True)})
    if "xla" in extras:
        return M.LayerOps(sdpa=M.xla_sdpa)
    if "shard_map" not in extras:
        return M.LayerOps(sdpa=_flash_interpret)
    mesh = Mesh(np.array(cpu_devices[:4]).reshape(2, 2), ("dp", "tp"))
    return M.LayerOps(sdpa=make_flash_sdpa(
        mesh, dp_axes=("dp",), tp_axes=("tp",), interpret=True))


def _stack(blocks, policy, extras, cpu_devices):
    """``(loss, params, x, cfg, block)``: ``loss(wrap)(params, x)`` runs
    ``blocks`` decoder blocks ``block(i)``, each wrapped by ``wrap``."""
    width = next((int(e[1:]) for e in extras if re.fullmatch(r"w\d+", e)),
                 H // N)
    cfg = CFG.model_copy(update={
        "hidden_size": N * width, "remat_policy": policy,
        "attention_dropout": 0.2 if "dropout" in extras else 0.0})
    kind = _kind(extras)
    seq = SCANS[kind][5] if kind in SCANS else S
    kwargs = {"ops": _ops(extras, cpu_devices), "mixer": kind,
              "compute_dtype": jnp.float32}
    if "segments" in extras:
        # a document boundary inside a score tile
        kwargs["segment_ids"] = jnp.asarray(
            np.repeat([[0, 1], [0, 2]], [40, S - 40], axis=1)
            .reshape(B, S).astype(np.int32))
    params = [M.init_decoder_layer(jax.random.key(i), cfg, mixer=kind)[0]
              for i in range(blocks)]
    x = jax.random.normal(jax.random.key(9), (B, seq, cfg.hidden_size),
                          jnp.float32)

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
    forward, *backward = (SCANS[_kind(extras)][3:5] if _kind(extras) in SCANS
                          else ("flash_attention_fwd",
                                "flash_attention_bwd_dq",
                                "flash_attention_bwd_dkv"))
    assert {name: _kernel_calls(j, forward)
            for name, j in jaxprs.items()} == {
        "kept": blocks, "plain": 2 * blocks}
    for kernel in backward:
        assert _kernel_calls(jaxprs["kept"], kernel) == blocks


def _transposes(jaxpr, found=None):
    """Operand shapes of the ``transpose`` equations of ``jaxpr`` and of
    every jaxpr its equations hold, a kernel's own body left out."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "transpose":
            found.append(tuple(eqn.invars[0].aval.shape))
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _transposes(sub, found)
    return found


@pytest.mark.parametrize("case,relayouts", [
    ("rows_pairs", 0), ("rows_w128_stack", 0),
    ("rows_pairs_shard_map_stack_dropout", 0), ("block", 12), ("stack", 24)])
def test_the_backward_holds_no_relayout_of_the_kept_rows(cpu_devices, case,
                                                         relayouts):
    """What remat keeps of a core on rows is the forward kernel's own
    output, and the backward kernels read it, the cotangent and q, k, v as
    they lie: the gradient of a rematted block holds no transpose of an
    array of four axes (heads beside positions), in the forward, the
    recomputed forward or the backward. At a head width the kernels do not
    index as rows it holds twelve a block: q, k, v in and the output back,
    q, k, v again in the recomputed forward, the kept rows and the
    cotangent in, dq, dk and dv back."""
    blocks, policy, extras = _CASES[case]
    loss, params, x, cfg, _ = _stack(blocks, policy, extras, cpu_devices)
    jaxpr = jax.make_jaxpr(jax.grad(loss(lambda fn: M.remat(fn, cfg))))(
        params, x).jaxpr
    assert _kernel_calls(jaxpr) == blocks
    moved = [sh for sh in _transposes(jaxpr) if len(sh) >= 4]
    assert len(moved) == relayouts, moved


def _kept_beside_the_arguments(capsys, fn, *args):
    """Shapes of what ``fn`` (a rematted function) saves for its backward
    pass that is no argument of it (``jax.ad_checkpoint``'s own listing)."""
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(fn, *args)
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines and all(re.match(r"\w+\[[\d,]*\] ", l) for l in lines), lines
    return sorted(l.split(" ", 1)[0] for l in lines
                  if " from the argument " not in l)


def _shapes(kept):
    """The shapes of ``_kept_beside_the_arguments``'s entries."""
    return [tuple(int(d) for d in re.findall(r"\d+", k.split("[")[1]))
            for k in kept]


def _named(jaxpr):
    """``{checkpoint_name: shape}`` of every value ``jaxpr`` names, in it and
    in every jaxpr its equations hold."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            found[eqn.params["name"]] = tuple(eqn.outvars[0].aval.shape)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found.update(_named(sub))
    return found


@pytest.mark.parametrize("core,policy", [
    ("flash", "full"), ("flash_shard_map", "full"), ("xla", "full"),
    ("flash_w64", "full"), ("flash_w128", "full"),
    ("xla", "dots_no_batch"),
    ("kda", "full"), ("kda_shard_map", "full"), ("kda_numpy", "full"),
    ("kda", "dots_no_batch"),
    ("linear_attention", "full"), ("linear_attention_shard_map", "full"),
    ("linear_attention_numpy", "full"),
    ("linear_attention", "dots_no_batch"),
    ("mamba", "full"), ("mamba_shard_map", "full"), ("mamba_numpy", "full"),
    ("mamba", "dots_no_batch"),
    ("mamba1", "full"), ("mamba1_shard_map", "full"),
    ("mamba1_numpy", "full"), ("mamba1", "dots_no_batch")])
def test_what_a_rematted_block_keeps(cpu_devices, capsys, core, policy):
    """``full`` keeps the block's input and, of a flash core, the output as
    [B, S, N * Dv] rows (the bytes of the input; at heads of 64 and of 128
    the kernel's own result) and lse as [B, N, S] rows
    (never the [B, N, S, 1] column HBM pads 128 times); of a recurrent
    mixer's scan kernels the output and the entering states, float32 as the
    kernel wrote them, by the names of the kernel file's ``KEPT``; a block on
    the XLA core or on a mixer's ``jax.numpy`` form names nothing and keeps
    what the base policy keeps."""
    kind = next((k for k in SCANS if core in (k, f"{k}_shard_map",
                                              f"{k}_numpy")),
                core.partition("_")[0])
    how = core[len(kind) + 1:]
    extras = {"flash": (), "xla": ("xla",)}.get(kind, (kind,)) + (
        (how,) if how else ())
    _, params, x, cfg, block = _stack(1, policy, extras, cpu_devices)
    kept = _kept_beside_the_arguments(capsys, M.remat(block(0), cfg),
                                      params[0], x)
    if kind == "xla" or how == "numpy":
        plain = _kept_beside_the_arguments(
            capsys, jax.checkpoint(block(0), policy=BASE_POLICIES[policy]),
            params[0], x)
        assert kept == plain and (kind != "xla" or policy != "full"
                                  or kept == [])
        assert not set(_named(jax.make_jaxpr(jax.grad(
            lambda p, h: jnp.sum(block(0)(p, h))))(params[0], x).jaxpr)) & {
                *kda.KEPT, *gdn.KEPT, *ssd.KEPT, *selective_scan.KEPT}
        return
    if kind in SCANS:
        # what the differentiated forward names, shard by shard under
        # ``shard_map`` (the batch over dp2) ...
        named = dict(SCANS[kind][6])
        traced = _named(jax.make_jaxpr(jax.grad(
            lambda p, h: jnp.sum(block(0)(p, h))))(params[0], x).jaxpr)
        if how == "shard_map":
            named = {n: (sh[0] // 2,) + sh[1:] for n, sh in named.items()}
        assert traced == named
        # ... and under ``full`` nothing else of the block is kept: the
        # bytes of the pair (a shard_map's residuals are its shards' laid
        # side by side on a new leading axis: the same numbers)
        plain = _kept_beside_the_arguments(
            capsys, jax.checkpoint(block(0), policy=BASE_POLICIES[policy]),
            params[0], x)
        sizes = sorted(int(np.prod(sh)) for sh in SCANS[kind][6].values())
        ours, theirs = ([int(np.prod(sh)) for sh in _shapes(k)]
                        for k in (kept, plain))
        assert all(k.startswith("f32[") for k in kept if k not in plain)
        assert sorted(ours) == sorted(theirs + sizes)
        assert policy != "full" or sorted(ours) == sizes
        return
    width = cfg.hidden_size
    if kind == "flash" and how != "shard_map":
        assert kept == sorted([f"f32[{B},{S},{width}]", f"f32[{B},{N},{S}]"])
    # (a shard_map's residuals are its shards' laid side by side on a new
    # leading axis: the same numbers, and no trailing singleton either)
    shapes = _shapes(kept)
    assert sorted(int(np.prod(sh)) for sh in shapes) == [B * N * S,
                                                        B * S * width]
    assert all(len(sh) == 3 and sh[-1] > 1 for sh in shapes), shapes
