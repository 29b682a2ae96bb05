"""Every cell of ``BENCHMARK.json`` as the program sees it, with no chip and
nothing compiled: the command line ``benchmark/run.py`` hands
``train_dist.main`` resolves to the configuration's own widths, its plan is
one the cell's chips can hold, the plan-lowered loss traces at those widths
(``jax.eval_shape``: shapes only), and the plan's explicit collectives are
what ``telemetry.plan_collective_counts`` says. Four cases a cell, so a
cell that a change to the program breaks fails here by its name, before a
chip is asked. Names come from ``benchmark/`` (imported, never copied), as
in ``test_benchmark_seam.py``."""

import functools

import pytest

import jax

from benchmark import manifest

pytestmark = pytest.mark.observability

MANIFEST = manifest.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
cell_axis = pytest.mark.parametrize("name", CELLS)


@functools.lru_cache(maxsize=None)
def _resolved(name):
    """(cell, args, hpc) of a cell, made once a process."""
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        resolve_model_config,
    )

    cell = manifest.resolve_cell(MANIFEST, name)
    args = resolve_model_config(args_from_cli(
        manifest.train_argv(cell, seed=0), mode="train_dist"))
    return cell, args, get_hybrid_parallel_config(args, cell.chips)


@cell_axis
def test_the_command_line_resolves_to_the_published_widths(name):
    cell, args, _ = _resolved(name)
    assert args.parallel.num_devices == cell.chips
    equals = cell.config["program"]["equals"]
    assert equals, "a configuration names the widths the program must run"
    for attr, key in equals.items():
        assert getattr(args.model, attr) == cell.config[key], (attr, key)
    # what the cells compute in
    assert args.parallel.mixed_precision == "bf16"


@cell_axis
def test_the_plan_is_one_the_cells_chips_hold(name):
    cell, args, hpc = _resolved(name)
    assert hpc.world_size == cell.chips and hpc.pp_deg == 1
    assert len(hpc.layers) == args.model.num_hidden_layers
    for s in hpc.layers:
        s.validate(cell.chips)
        assert s.tp_size * s.cp_size * s.dp_size == cell.chips
    assert hpc.global_bsz == args.parallel.global_train_batch_size
    assert hpc.global_bsz % max(hpc.chunks, 1) == 0
    assert not hpc.ignored_plan_keys


@cell_axis
def test_the_plans_loss_traces_at_the_published_widths(name, cpu_devices):
    """Shapes only: the parameters are ``eval_shape`` of the initialiser,
    the batch has the fields and shapes of the program's own first batch,
    and the loss is the one the step differentiates
    (``build_spmd_loss_fn`` on a mesh of the cell's chips)."""
    import jax.numpy as jnp

    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.models.modules import compute_dtype_of
    from hetu_galvatron_tpu.parallel.spmd import build_spmd_loss_fn
    from hetu_galvatron_tpu.runtime.dataloader import get_data_iterator
    from hetu_galvatron_tpu.runtime.mesh import build_mesh

    cell, args, hpc = _resolved(name)
    cfg, box = args.model, {}

    def init(key):
        p, box["axes"] = init_causal_lm(key, cfg)
        return p

    params = jax.eval_shape(init, jax.random.key(0))
    mesh = build_mesh(cell.chips, 1, devices=cpu_devices[:cell.chips])
    stats = bool(cfg.num_experts)
    loss_fn, pspecs, *_ = build_spmd_loss_fn(
        cfg, hpc, mesh, box["axes"],
        compute_dtype=compute_dtype_of(args.parallel.mixed_precision),
        with_moe_stats=stats)
    assert jax.tree.structure(pspecs, is_leaf=lambda x: isinstance(
        x, jax.sharding.PartitionSpec)) == jax.tree.structure(params)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in next(get_data_iterator(
                 args, global_batch_size=hpc.global_bsz, hpc=hpc)).items()}
    assert batch["tokens"].shape == (hpc.global_bsz, cfg.seq_length)
    out = jax.eval_shape(loss_fn, params, batch)
    loss = out[0] if stats else out
    assert loss.shape == () and loss.dtype == jnp.float32


@cell_axis
def test_the_plans_explicit_collectives_are_counted(name):
    """pp = 1 and no ring matmuls in any cell: the gradient reduction and
    the ZeRO gathers are the partitioner's, so the plan states no explicit
    collective; with the rings asked for, a tp > 1 plan states
    ``12 (tp - 1)`` hops a layer and microbatch (15 under remat)."""
    from hetu_galvatron_tpu.observability.telemetry import (
        plan_collective_counts,
    )

    _, args, hpc = _resolved(name)
    first = hpc.layers[0]
    if any(s != first for s in hpc.layers):
        with pytest.raises(ValueError, match="uniform"):
            plan_collective_counts(hpc, args.model, tp_overlap=False)
        return
    assert not args.tp_overlap.enable
    assert plan_collective_counts(hpc, args.model, tp_overlap=False) == {}
    rings = plan_collective_counts(hpc, args.model, tp_overlap=True)
    if first.tp_size == 1:
        assert rings == {}
    else:
        per_tick = 15 if first.checkpoint else 12
        assert rings == {"ppermute_tp": max(hpc.chunks, 1) * len(hpc.layers)
                         * per_tick * (first.tp_size - 1)}
