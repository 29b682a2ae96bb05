#!/usr/bin/env python3
"""Compile each cell's train step for a described TPU v5e, with no chip
attached, and say whether it fits.

    JAX_PLATFORMS=cpu python3 benchmark/aot_check.py [--workload NAME ...]

libtpu compiles ahead of time for ``v5e:2x2`` (four compile-only ``TPU v5
lite`` devices, ``jax.experimental.topologies``); a one-chip cell takes the
first of them. Nothing runs: this proves that the real Mosaic and XLA:TPU
compilers accept the step at its real size, counts the Mosaic custom calls
in the optimized HLO, and prints ``memory_analysis()`` per device. It is a
rehearsal that costs no chip time, never a measurement.

The step is built the way ``cli/train_dist.py::train`` builds it for pp=1
(``make_spmd_train_step`` on ``build_mesh``), from the cell's own command
line, because the launcher itself takes its devices from ``jax.devices()``.
Exit code 1 when a cell does not compile or does not fit ``HBM_BYTES``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import manifest as mf  # noqa: E402

HBM_BYTES = 16 * 1024 ** 3   # one v5e chip: 16 GiB as the allocator sees it
GiB = 1024.0 ** 3


def compile_cell(cell, topo_devices):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.models.modules import compute_dtype_of
    from hetu_galvatron_tpu.parallel.spmd import make_spmd_train_step
    from hetu_galvatron_tpu.runtime.dataloader import get_data_iterator
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.runtime.mesh import build_mesh
    from hetu_galvatron_tpu.runtime.optimizer import make_optimizer
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    args = resolve_model_config(
        args_from_cli(mf.train_argv(cell, seed=0), mode="train_dist"))
    cfg, world = args.model, cell.chips
    devices = list(topo_devices)[:world]
    hpc = get_hybrid_parallel_config(args, world)
    box = {}

    def init(key):
        p, box["axes"] = init_causal_lm(key, cfg)
        return p

    params = jax.eval_shape(init, jax.random.key(0))
    tx = make_optimizer(args.train)
    mesh = build_mesh(world, 1, devices=devices)
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        cfg, hpc, mesh, box["axes"], tx, params,
        compute_dtype=compute_dtype_of(args.parallel.mixed_precision))

    def shaped(specs, tree):
        return jax.tree.map(
            lambda s, a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            specs, tree, is_leaf=lambda x: isinstance(x, PartitionSpec))

    sp = shaped(pspecs, params)
    so = shaped(ospecs, jax.eval_shape(tx.init, params))
    # the shapes and dtypes of the program's own first batch, every field,
    # as the launcher hands it to the step (made on the host; nothing of it
    # reaches a device)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=batch_shd)
             for k, v in next(get_data_iterator(
                 args, global_batch_size=hpc.global_bsz, hpc=hpc)).items()}
    B, S = batch["tokens"].shape
    t0 = time.perf_counter()
    compiled = step.lower(sp, so, batch).compile()
    secs = time.perf_counter() - t0
    m = compiled.memory_analysis()
    hlo = compiled.as_text()
    n_params = sum(a.size for a in jax.tree.leaves(params))
    return {
        "cell": cell.name, "chips": world, "layers": cfg.num_hidden_layers,
        "parameters": int(n_params),
        "tokens_per_step": B * S,
        "compile_s": round(secs, 1),
        "mosaic_custom_calls": hlo.count(
            'custom_call_target="tpu_custom_call"'),
        "collectives_in_hlo": {k: hlo.count(f" {k}(") + hlo.count(f" {k}-start(")
                               for k in ("all-gather", "reduce-scatter",
                                         "all-reduce", "collective-permute",
                                         "all-to-all")},
        "per_device_GiB": {
            "arguments": m.argument_size_in_bytes / GiB,
            "outputs": m.output_size_in_bytes / GiB,
            "aliased": m.alias_size_in_bytes / GiB,
            "temporaries": m.temp_size_in_bytes / GiB,
            "generated_code": m.generated_code_size_in_bytes / GiB,
            # donated arguments are reused for the outputs
            "live_peak": (m.argument_size_in_bytes + m.output_size_in_bytes
                          - m.alias_size_in_bytes + m.temp_size_in_bytes
                          + m.generated_code_size_in_bytes) / GiB},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append",
                    help="a cell of BENCHMARK.json (default: every cell)")
    a = ap.parse_args()
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    manifest = mf.load_manifest()
    names = a.workload or [w["name"] for w in manifest["workloads"]]
    ok = True
    for name in names:
        cell = mf.resolve_cell(manifest, name)
        try:
            rep = compile_cell(cell, topo.devices)
        except Exception as e:  # noqa: BLE001 — the compiler's own message
            print(json.dumps({"cell": name, "fits": False,
                              "error": f"{type(e).__name__}: {e}"[:2000]}),
                  flush=True)
            ok = False
            continue
        rep["fits"] = rep["per_device_GiB"]["live_peak"] * GiB <= HBM_BYTES
        ok &= rep["fits"]
        print(json.dumps(rep), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
