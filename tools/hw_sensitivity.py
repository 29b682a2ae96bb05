"""Sensitivity sweep over the spec-derived TPU v5e hardware tables.

The v5e tables (profiles/tpu_v5e/hardware/*.json) are estimates from public
specs, not measurements (tools/make_v5e_hw_config.py). Before trusting a
searched plan "for v5e-8", this tool answers: *which of those invented
coefficients does the chosen plan actually depend on?* Each coefficient
family — allreduce bandwidth, p2p bandwidth, overlap coefficient, sp
collective latency — is scaled by 0.5x and 2x (bandwidths scale down when
times scale up and vice versa) while everything else stays at baseline; the
search engine (core/search_engine/engine.py) runs on each variant and the
chosen plan + throughput are recorded.

Output: ``hetu_galvatron_tpu/profiles/tpu_v5e/sensitivity.json`` and a
human-readable ``SENSITIVITY.md`` next to the tables. The committed JSON is
kept in sync by ``tests/search_engine/test_hw_sensitivity.py``, which
re-runs a subset of the sweep and compares.

Reference anchor: the measured-tables workflow this substitutes for is
``galvatron/profile_hardware/hardware_configs/*.json`` (the reference
measures on its 8xA100 node; nobody has measured the v5e tables on chips yet).

Run: ``python tools/hw_sensitivity.py`` (CPU-only; ~1-2 min).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
HW = os.path.join(REPO, "hetu_galvatron_tpu", "profiles", "tpu_v5e",
                  "hardware")
FIXTURES = os.path.join(REPO, "tests", "fixtures")

# coefficient family -> (filename, how a "2x better hardware" scale applies)
FAMILIES = {
    # bandwidths: scale values directly (2x = faster links)
    "allreduce_bandwidth": ("allreduce_bandwidth_1nodes_8gpus_per_node.json",
                            "bandwidth"),
    "p2p_bandwidth": ("p2p_bandwidth_1nodes_8gpus_per_node.json",
                      "bandwidth"),
    # times: scale values INVERSELY (2x hardware = half the time)
    "sp_time": ("sp_time_1nodes_8gpus_per_node.json", "time"),
    # dimensionless slowdown of overlapped compute (>= 1.0)
    "overlap_coe": ("overlap_coefficient.json", "overlap"),
}

FACTORS = (0.5, 2.0)


def _scaled_table(path: str, kind: str, factor: float) -> dict:
    with open(path) as f:
        table = json.load(f)
    out = {}
    for k, v in table.items():
        if not isinstance(v, (int, float)):
            out[k] = v
            continue
        if kind == "bandwidth":
            out[k] = v * factor
        elif kind == "time":
            out[k] = v / factor
        else:  # overlap: scale the slowdown margin above 1.0
            out[k] = 1.0 + (v - 1.0) * factor
    return out


def _run_search(tables: dict, out_dir: str):
    """One search-engine run over the given hardware table paths. Model
    time/memory profiles stay pinned to the repo fixtures (llama2-7b) —
    the sweep isolates the HARDWARE coefficients."""
    from hetu_galvatron_tpu.core.args_schema import SearchArgs
    from hetu_galvatron_tpu.core.search_engine.engine import SearchEngine

    sargs = SearchArgs(
        num_nodes=1, num_devices_per_node=8, memory_constraint=36,
        settle_bsz=64, settle_chunks=32, default_dp_type="zero2",
        pipeline_type="pipedream_flush", sequence_parallel=True,
        async_grad_reduce=False, time_profile_mode="sequence",
        memory_profile_mode="sequence", max_tp_deg=8, max_pp_deg=4,
        time_profiling_path=os.path.join(
            FIXTURES, "computation_profiling_bf16_llama2-7b_all.json"),
        memory_profiling_path=os.path.join(
            FIXTURES, "memory_profiling_bf16_llama2-7b_all.json"),
        allreduce_bandwidth_config_path=tables["allreduce_bandwidth"],
        p2p_bandwidth_config_path=tables["p2p_bandwidth"],
        overlap_coe_path=tables["overlap_coe"],
        sp_time_path=tables["sp_time"],
        output_config_path=out_dir)
    eng = SearchEngine(sargs)
    eng.set_model_info(
        [{"hidden_size": 4096, "seq_len": 8192, "layer_num": 28}],
        "llama2-7b")
    eng.initialize()
    throughput = eng.optimize()
    plan_file = [f for f in os.listdir(out_dir)
                 if f.startswith("galvatron_config_")][0]
    with open(os.path.join(out_dir, plan_file)) as f:
        plan = json.load(f)
    return throughput, plan


def plan_signature(plan: dict) -> str:
    """Compact strategy signature for flip detection: pp + the per-layer
    (tp, cp, sdp, ckpt) vectors collapsed to runs + vtp."""
    pp = plan.get("pp_deg")
    vtp = plan.get("vtp", plan.get("embed_sdp"))
    keys = ["tp_sizes_enc", "use_sp", "checkpoint", "fsdp_type"]
    parts = [f"pp{pp}", f"vtp{vtp}"]
    for k in keys:
        v = plan.get(k)
        if isinstance(v, str):
            toks = v.split(",")
            runs = []
            for t in toks:
                if runs and runs[-1][0] == t:
                    runs[-1][1] += 1
                else:
                    runs.append([t, 1])
            parts.append(k + "=" + ",".join(f"{t}x{n}" for t, n in runs))
    return " ".join(parts)


def run_sweep(factors=FACTORS, families=None) -> dict:
    baseline_paths = {name: os.path.join(HW, fn)
                      for name, (fn, _) in FAMILIES.items()}
    results = {"model": "llama2-7b fixtures over v5e-8 hw tables",
               "factors": list(factors), "runs": []}

    def one(label, tables):
        with tempfile.TemporaryDirectory() as out:
            thr, plan = _run_search(tables, out)
        sig = plan_signature(plan)
        results["runs"].append({"label": label, "throughput": round(thr, 4),
                                "signature": sig})
        print(f"  {label:34s} throughput {thr:8.4f}  {sig}",
              file=sys.stderr)
        return sig

    print("hw sensitivity sweep (baseline + ±2x per family):",
          file=sys.stderr)
    base_sig = one("baseline", baseline_paths)
    for name, (fn, kind) in (families or FAMILIES).items():
        for factor in factors:
            with tempfile.TemporaryDirectory() as tdir:
                scaled = _scaled_table(os.path.join(HW, fn), kind, factor)
                spath = os.path.join(tdir, fn)
                with open(spath, "w") as f:
                    json.dump(scaled, f)
                tables = dict(baseline_paths, **{name: spath})
                one(f"{name} x{factor}", tables)
    flips = [r["label"] for r in results["runs"]
             if r["signature"] != base_sig]
    results["baseline_signature"] = base_sig
    results["flipped"] = flips
    return results


def write_docs(results: dict) -> None:
    out_json = os.path.join(HW, os.pardir, "sensitivity.json")
    with open(out_json, "w") as f:
        json.dump(results, f, indent=2)
    lines = [
        "# Hardware-table sensitivity (spec-derived v5e-8 coefficients)",
        "",
        "The hardware tables in `hardware/` are estimates from public specs",
        "(`tools/make_v5e_hw_config.py`), not measurements. This sweep re-runs",
        "the search engine (llama2-7b profile fixtures, bsz 64, 36 GB HBM",
        "budget) with each coefficient family scaled to 0.5x and 2x of its",
        "estimated value, and records whether the chosen plan changes.",
        "",
        "Regenerate with `python tools/hw_sensitivity.py`;",
        "`tests/search_engine/test_hw_sensitivity.py` keeps this file in",
        "sync with the search engine.",
        "",
        f"Baseline plan: `{results['baseline_signature']}`",
        "",
        "| run | throughput | plan |",
        "|---|---|---|",
    ]
    base = results["baseline_signature"]
    for r in results["runs"]:
        mark = "**flips**" if r["signature"] != base else "same plan"
        lines.append(f"| {r['label']} | {r['throughput']} | {mark}: "
                     f"`{r['signature']}` |")
    lines += [
        "",
        "Reading: coefficient families whose ±2x runs keep the same plan do",
        "not gate the current searched plan, so their estimation error is",
        "harmless for plan CHOICE (throughput predictions still shift).",
        "Families listed under `flipped` in `sensitivity.json` are the ones",
        "worth measuring on real multi-chip hardware first",
        "(`cli/profiler mode=profile_hardware`).",
        "",
    ]
    out_md = os.path.join(HW, os.pardir, "SENSITIVITY.md")
    with open(out_md, "w") as f:
        f.write("\n".join(lines))
    print(f"wrote {os.path.normpath(out_json)} and "
          f"{os.path.normpath(out_md)}", file=sys.stderr)


if __name__ == "__main__":
    write_docs(run_sweep())
