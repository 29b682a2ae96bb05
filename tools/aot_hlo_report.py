#!/usr/bin/env python3
"""Where a cell's compiled step spends its cycles and what its collectives
are, read from the optimized HLO of a chipless compile.

    JAX_PLATFORMS=cpu python3 tools/aot_hlo_report.py --workload NAME \
        [--out DIR] [--top N]
    python3 tools/aot_hlo_report.py --hlo FILE        # a saved as_text()

The step is the one ``benchmark/aot_check.py`` compiles (the cell's own
command line, ``make_spmd_train_step`` on a described ``v5e:2x2``); nothing
runs. It prints the bytes of the ``reshape``, ``copy`` and ``transpose``
instructions left outside fusions (``step_hlo``'s ``relayouts``: the gauge
``step/relayout_bytes`` of a run), on the same line the prefetches XLA made
(asynchronous copies and slices), their bytes and the instructions under no
scope that no scoped one uses or feeds (``step_hlo``'s ``flow``: the gauges
``step/prefetches``, ``step/prefetch_bytes``, ``step/unowned_instructions``)
and, per computation of the optimized HLO (the entry, each while body, ...),

* XLA:TPU's own ``estimated_cycles`` summed by instruction stem
  (``fusion.123`` -> ``fusion``) and by the tail of ``op_name`` (the jax
  primitive and its last scopes): compute only, a collective carries none;
* every collective by opcode, result shape, ``replica_groups`` and
  ``op_name`` tail, with its count. A collective that XLA:TPU wrapped in a
  fusion (an async start / overlapped fusion / done triple, an
  ``all-reduce-scatter`` fusion) is counted once, in the computation that
  calls the fusion, as ``<opcode>@<the fused computation's stem>``;
* the instructions that are events of a trace by the collective class the
  program's own map gives them (``trace_analysis.step_hlo``: ``all-gather``,
  ``reduce-scatter.fused``, ``overlapped``, ``all-gather.start`` / ``.done``)
  and by phase and scope, in cycles: what ``tools/trace_by_scope.py`` lays
  a trace over.

Instruction names, opcodes, ``op_name``s and classes come from the program's
one walk over the text (``trace_analysis.walk_hlo`` / ``step_hlo``); this
file adds the shapes, cycles and replica groups of the lines it is handed.

Estimates are the compiler's, not a measurement: they say which instruction
a name in a device trace is and what it was lowered from, and they rank
compute. With ``--out`` the HLO text and the report (JSON) are written
there.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from hetu_galvatron_tpu.observability.trace_analysis import (  # noqa: E402
    COLLECTIVE_OPS,
    result_type,
    step_hlo,
    walk_hlo,
)

_CYCLES = re.compile(r'"estimated_cycles":"?(\d+)')
_GROUPS = re.compile(r"replica_groups=(\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?"
                     r"|\{[{}\d,]*\})")


def _tail(op_name: str, parts: int = 3) -> str:
    """The last ``parts`` scopes of an ``op_name`` (``jit(step)/.../mul``);
    an instruction's own name where XLA put that (``convert.63``), without
    its number."""
    op_name = re.sub(r"\.\d+$", "", op_name)
    return "/".join(op_name.split("/")[-parts:]) if op_name else "(none)"


def parse_hlo(text: str):
    """[(computation, [instruction dict, ...]), ...] in the text's order.
    An instruction: name, stem, opcode, shape (the result type as printed),
    op_name, cycles (int or None), replica_groups (str or None), calls (the
    fused computation a fusion runs, or None), done (the line is an async
    collective's done half)."""
    out, last = [], None
    for comp, name, opcode, op_name, calls, line, _ in walk_hlo(text):
        if comp != last:
            out.append((comp, []))
            last = comp
        shape = result_type(line, opcode)
        cyc = _CYCLES.search(line)
        grp = _GROUPS.search(line)
        out[-1][1].append({
            "calls": calls,
            "done": 'custom_call_target="AsyncCollectiveDone"' in line,
            "name": name, "stem": re.sub(r"[.\d]+$", "", name),
            "opcode": opcode,
            "shape": re.sub(r"\{[^{}]*\}", "", shape),  # layouts, tilings
            "op_name": op_name,
            "cycles": int(cyc.group(1)) if cyc else None,
            "replica_groups": grp.group(1) if grp else None})
    return out


def is_collective(opcode: str) -> bool:
    return opcode.removesuffix("-start") in COLLECTIVE_OPS


def _owners(comps):
    """{fused computation: (calling computation, calling instruction)}."""
    return {ins["calls"]: (comp, ins) for comp, instrs in comps
            for ins in instrs if ins["calls"]}


def report(text: str, top: int = 12):
    """The report as a dict: per computation that holds a collective or an
    estimated cycle, the cycle sums and the collectives."""
    parsed = parse_hlo(text)
    owners = _owners(parsed)
    found = step_hlo(text, ())
    classes = found["map"]["instructions"]
    cycles = {c: (collections.Counter(), collections.Counter())
              for c, _ in parsed}
    coll = {c: collections.Counter() for c, _ in parsed}
    for comp, instrs in parsed:
        by_stem, by_tail = cycles[comp]
        # the overlapped fusion and the done half of an async collective
        # repeat the instruction their start half holds
        repeat = (comp.startswith("async_collective_fusion")
                  or any(i["done"] for i in instrs))
        for ins in instrs:
            if ins["cycles"]:
                by_stem[ins["stem"]] += ins["cycles"]
                by_tail[_tail(ins["op_name"])] += ins["cycles"]
            if not is_collective(ins["opcode"]) or repeat:
                continue
            op, where, op_name = ins["opcode"].removesuffix("-start"), comp, \
                ins["op_name"]
            while where in owners:
                fused = re.sub(r"(\.clone|[.\d])+$", "", where)
                if fused != "fused_computation":
                    op = f"{op}@{fused}"
                where, via = owners[where]
                op_name = op_name or via["op_name"]
            coll[where][(op, ins["shape"], ins["replica_groups"] or "",
                         _tail(op_name))] += 1
    comps = []
    for comp, instrs in parsed:
        by_stem, by_tail = cycles[comp]
        if not by_stem and not coll[comp]:
            continue
        kinds = collections.Counter()
        for (op, _, _, _), n in coll[comp].items():
            kinds[op] += n
        # what the program's map says of this computation's instructions
        # (none for a fusion's or a reduction's computation)
        by_class, by_phase = collections.Counter(), collections.Counter()
        for ins in instrs:
            scope, phase, cls = classes.get(ins["name"], (None, None, None))
            if cls:
                by_class[f"{cls} {phase}"] += 1
            if phase and ins["cycles"]:
                by_phase[f"{phase} {scope or '(no scope)'}"] += ins["cycles"]
        comps.append({
            "computation": comp, "instructions": len(instrs),
            "estimated_cycles": sum(by_stem.values()),
            "cycles_by_stem": by_stem.most_common(top),
            "cycles_by_op_name": by_tail.most_common(top),
            "collective_counts": dict(kinds),
            "collective_classes": dict(sorted(by_class.items())),
            "cycles_by_phase_and_scope": by_phase.most_common(3 * top),
            "collectives": [
                {"op": op, "shape": shape, "replica_groups": grp,
                 "op_name": tail, "count": n}
                for (op, shape, grp, tail), n in sorted(
                    coll[comp].items(), key=lambda kv: (kv[0][0], -kv[1]))]})
    comps.sort(key=lambda c: -c["estimated_cycles"])
    return {"computations": comps, "relayouts": found["relayouts"],
            "flow": found["flow"]}


def print_report(rep, file=None):
    moved = rep["relayouts"]
    print("relayouts outside fusions (the gauge step/relayout_bytes): "
          f"{moved['bytes']} bytes in {moved['count']} instructions"
          + (", the largest {opcode} {shape} <- {op_name}".format(
              **moved["largest"]) if moved["largest"] else "")
          + "; step/prefetches {prefetches} of step/prefetch_bytes "
            "{prefetch_bytes}, step/unowned_instructions "
            "{unowned_instructions}".format(**rep["flow"]), file=file)
    for c in rep["computations"]:
        print(f"== {c['computation']}: {c['instructions']} instructions, "
              f"{c['estimated_cycles'] / 1e6:.1f} M estimated cycles, "
              f"collectives {c['collective_counts']}", file=file)
        if c["collective_classes"]:
            print(f"  the map's collective classes: "
                  f"{c['collective_classes']}", file=file)
        for title, rows in (("by stem", c["cycles_by_stem"]),
                            ("by op_name", c["cycles_by_op_name"]),
                            ("by the map's phase and scope",
                             c["cycles_by_phase_and_scope"])):
            if rows:
                print(f"  cycles {title}:", file=file)
            for key, cyc in rows:
                print(f"    {cyc / 1e6:10.2f} M  {key}", file=file)
        for row in c["collectives"]:
            shape = row["shape"]
            if len(shape) > 72:
                shape = shape[:69] + "..."
            print(f"  {row['count']:4d} x {row['op']} {shape} "
                  f"{row['replica_groups']}  <- {row['op_name']}", file=file)


def cell_step(name: str, keep_blocks: bool = True):
    """(the cell's train step, the shapes of its three arguments), built as
    ``benchmark/aot_check.py`` builds it: the cell's own command line,
    ``make_spmd_train_step`` on a described ``v5e:2x2``, the fields of the
    program's own first batch. Nothing is compiled yet."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmark import manifest as mf
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

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cell = mf.resolve_cell(mf.load_manifest(), name)
    args = resolve_model_config(
        args_from_cli(mf.train_argv(cell, seed=0), mode="train_dist"))
    cfg, world = args.model, cell.chips
    hpc = get_hybrid_parallel_config(args, world)
    box = {}

    def init(key):
        p, box["axes"] = init_causal_lm(key, cfg)
        return p

    params = jax.eval_shape(init, jax.random.key(0))
    tx = make_optimizer(args.train)
    mesh = build_mesh(world, 1, devices=list(topo.devices)[:world])
    step, pspecs, ospecs, batch_shd = make_spmd_train_step(
        cfg, hpc, mesh, box["axes"], tx, params,
        compute_dtype=compute_dtype_of(args.parallel.mixed_precision),
        keep_blocks=keep_blocks)

    def shaped(specs, tree):
        return jax.tree.map(
            lambda s, a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            specs, tree, is_leaf=lambda x: isinstance(x, PartitionSpec))

    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=batch_shd)
             for k, v in next(get_data_iterator(
                 args, global_batch_size=hpc.global_bsz, hpc=hpc)).items()}
    return step, (shaped(pspecs, params),
                  shaped(ospecs, jax.eval_shape(tx.init, params)), batch)


def compile_cell_hlo(name: str) -> str:
    """The optimized HLO of the cell's step."""
    step, shapes = cell_step(name)
    return step.lower(*shapes).compile().as_text()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--workload", help="a cell of BENCHMARK.json")
    src.add_argument("--hlo", help="a file holding compiled.as_text()")
    ap.add_argument("--out", help="directory for <name>.hlo.txt and "
                                  "<name>.report.json")
    ap.add_argument("--top", type=int, default=12,
                    help="rows of each cycle table (default 12)")
    a = ap.parse_args()
    if a.hlo:
        with open(a.hlo) as f:
            text = f.read()
        name = os.path.splitext(os.path.basename(a.hlo))[0]
    else:
        text, name = compile_cell_hlo(a.workload), a.workload
    rep = report(text, top=a.top)
    if a.out:
        os.makedirs(a.out, exist_ok=True)
        if not a.hlo:
            with open(os.path.join(a.out, f"{name}.hlo.txt"), "w") as f:
                f.write(text)
        with open(os.path.join(a.out, f"{name}.report.json"), "w") as f:
            json.dump(rep, f, indent=1)
    print_report(rep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
