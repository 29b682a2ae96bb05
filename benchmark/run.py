#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, on the TPU this process is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The run goes through the users' entry point
(``hetu_galvatron_tpu.cli.train_dist.main``) in this process; see
``benchmark/window.py`` for how the window is timed and ended, and
``benchmark/check.py`` for what ``correct`` means. The last line of stdout
is one JSON object with the keys ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device`` (and ``breakdown`` in a traced run); everything
else worth reading goes on earlier lines and into ``benchmark/out/``.

It refuses to run when ``jax.devices()[0].platform`` is not ``tpu`` or when
fewer chips are visible than the cell asks for: there is no CPU path and no
interpret path, and no CPU number is ever printed under a device metric's
name.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()   # set-up runs from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT_DIR = os.path.join(ROOT, "benchmark", "out")


def log(msg: str) -> None:
    print(msg, flush=True)


def measure(cell, *, seed: int, seconds: float, trace: int, chip: dict,
            root: str = ROOT, out_dir: str = OUT_DIR,
            expect_mosaic: bool = True,
            t_process_start: float = T_PROCESS_START):
    """Run one cell once and return ``(result line, report)``. ``chip`` is
    the device's row of ``peaks.py``. Platform-neutral so that
    ``benchmark/tests`` can drive it at a tiny size; ``main`` is what
    refuses anything but a TPU."""
    from benchmark import (
        check,
        flops,
        manifest,
        readers,
        reference,
        window,
        xplane,
    )

    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    argv = manifest.train_argv(cell, seed, root)
    args = resolve_model_config(args_from_cli(argv, mode="train_dist"))
    cfg = args.model
    for attr, key in cell.config["program"]["equals"].items():
        if getattr(cfg, attr) != cell.config[key]:
            raise SystemExit(
                f"{cell.config_name}: the program runs {attr}="
                f"{getattr(cfg, attr)!r}, the configuration's file says "
                f"{key}={cell.config[key]!r}")
    sizes = flops.Sizes.of(cfg)
    # the model's FLOPs are its family's; a family or an export that is not
    # there, a dense count for a program with experts, or more attending
    # blocks described than the program runs (in its decoder and in a stack
    # beside it whose depth the configuration's file states), stops the run
    # here
    family = reference.load_family(cell.config["reference"]["family"], root)
    if hasattr(family, "attention_blocks"):
        sizes = sizes.with_attention(
            family.attention_blocks(cell.config),
            beside=manifest.second_stack_depth(cell.config))
    train_flops = flops.train_from_forward(
        family.forward_flops_per_token(sizes, cell.config))
    sequences = args.parallel.global_train_batch_size
    # every position of the decoder's sequence is a token of the step, one
    # that holds an image's merged patches or that ``loss_mask`` leaves out
    # of the loss too
    tokens_per_step = sequences * cfg.seq_length

    os.makedirs(out_dir, exist_ok=True)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(out_dir, "trace", f"{cell.name}.seed{seed}")
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"cell {cell.name}: {cell.chips} chip(s), {tokens_per_step} tokens a "
        f"step, window {seconds:g} s, trace {trace}")
    log("train_dist.main(" + " ".join(argv[1:]) + " ...)")
    facts = window.run_window(argv, seconds=seconds, trace_dir=trace_dir)
    attempted, failed = window.attempted_failed(facts)
    if "window" not in facts:
        raise SystemExit(f"benchmark/run.py: {cell.name}: no measured step "
                         f"(rc {facts['rc']}, raised {facts['raised']})")

    # ---- facts every reader may use ------------------------------------
    win = facts["window"]
    win.update(window.steady_rate(facts["steps"]))
    mem = facts["memory"][:cell.chips]
    fullest = max(mem, key=lambda m: m["peak_bytes_in_use"] or 0)
    facts["memory"] = {
        "per_device": mem, "peak_bytes": fullest["peak_bytes_in_use"],
        "fill_pct": (100.0 * fullest["peak_bytes_in_use"]
                     / fullest["bytes_limit"]
                     if fullest["peak_bytes_in_use"] and fullest["bytes_limit"]
                     else None)}
    facts.update(sizes=sizes, sequences_per_step=sequences,
                 microbatches_per_step=max(args.parallel.chunks, 1),
                 config=cell.config, chips=cell.chips, peaks=chip)
    devices = facts.pop("devices")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": facts["memory"]["peak_bytes"]}
    setup_s = win["start"] - t_process_start
    # all the whole periods of the window over all of their time
    tokens_per_s = tokens_per_step / win["mean_period_s"]
    e2e = {"tokens_per_s": tokens_per_s,
           "mfu_pct": flops.mfu_pct(tokens_per_s, train_flops, cell.chips,
                                    chip["bf16_flops_per_s"]),
           "setup_s": setup_s}

    # ---- the trace -----------------------------------------------------
    result_extra = {}
    if trace:
        path = xplane.find_xplane(trace_dir)
        if path is None:
            raise SystemExit(f"no .xplane.pb under {trace_dir}")
        facts["trace"] = xplane.facts_of(path, cell.chips)
        device["busy_s"] = facts["trace"]["busy_s"]
        device["window_s"] = facts["trace"]["window_s"]
        result_extra["breakdown"] = facts["trace"]["breakdown"]
        with open(os.path.join(out_dir, f"{cell.name}.trace_dump.txt"),
                  "w") as f:
            f.write(xplane.dump(path))

    # ---- the reference, outside the window and outside setup_s -----------
    t_ref = time.perf_counter()
    ref = check.reference_loss(cell, argv, seed, root, out_dir)
    log(f"reference loss {ref['loss']:.6f} over {ref['tokens']} tokens "
        f"({'cached' if ref['cached'] else 'computed'} in "
        f"{time.perf_counter() - t_ref:.1f} s); step 0 loss "
        f"{facts['losses'][0] if facts['losses'] else None}")
    verdict = check.judge(facts, reference=ref["loss"],
                          tolerance=cell.config["reference"]["loss_tolerance"],
                          expect_mosaic=expect_mosaic,
                          expects=cell.config["program"].get("expects"))

    # ---- the metrics of this run ---------------------------------------
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = readers.read_metric(m["name"], facts, root)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    steps_ms = [1e3 * (e - s) for s, e in facts["steps"]]
    report = {
        "cell": cell.name, "seed": seed, "seconds": seconds,
        "trace": trace, "argv": facts["argv"][1:],
        "end_to_end": e2e, "window": win, "steps_ms": steps_ms,
        "tokens_per_s_at_median_period":
            tokens_per_step / win["median_period_s"],
        "losses": facts["losses"], "reference": ref,
        "checks": verdict["checks"],
        "setup_split_s": {
            "imports_and_arguments": facts["main_started"] - t_process_start,
            "first_step_incl_init_and_compile_or_cache_load":
                facts["goodput"].get("recompile"),
            "to_window_start": setup_s,
            "backend_compile": facts["compile"]["setup"]["backend_compile_s"]},
        "timeline_s_after_main": [[round(t, 2), m] for t, m in
                                  facts["timeline"] if t <= win["end"]
                                  - facts["main_started"]][:40],
        "compile": facts["compile"], "memory": facts["memory"],
        "attention_cores": facts["attention_cores"],
        "mosaic_custom_calls": facts["mosaic_custom_calls"],
        "roofline_bounds": facts.get("roofline_bounds"),
        "tokens_per_step": tokens_per_step,
        "train_flops_per_token": train_flops,
        "trace_facts": ({k: v for k, v in facts["trace"].items()
                         if k != "reduced"} if trace else None),
        "total_s": time.perf_counter() - t_process_start,
    }
    with open(os.path.join(out_dir, f"{cell.name}.seed{seed}."
                           f"trace{trace}.json"), "w") as f:
        json.dump(report, f, indent=1)
    log("report: " + json.dumps({k: report[k] for k in (
        "end_to_end", "checks", "setup_split_s", "timeline_s_after_main",
        "compile", "memory",
        "mosaic_custom_calls", "roofline_bounds", "total_s")}))
    log("steps_ms: " + " ".join(f"{x:.1f}" for x in steps_ms))
    return ({"correct": verdict["correct"], "attempted": attempted,
             "failed": failed, "metrics": metrics, "device": device,
             **result_extra}, report)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "hetu_galvatron_tpu")):
        print("benchmark/run.py: the program (hetu_galvatron_tpu/) is not "
              "beside benchmark/: this is a harness, it runs from the root "
              "of a checkout", file=sys.stderr)
        return 2
    from benchmark import manifest, peaks

    man = manifest.load_manifest(ROOT)
    problems = manifest.check_manifest(man, ROOT)
    if problems:
        print("BENCHMARK.json breaks the contract:\n  "
              + "\n  ".join(problems), file=sys.stderr)
        return 2
    cell = manifest.resolve_cell(man, a.workload, ROOT)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"benchmark/run.py: refusing to run {cell.name}: JAX shows "
              f"{len(devices)} x {devices[0].platform!r} "
              f"({devices[0].device_kind!r}), the cell needs {cell.chips} "
              "TPU chip(s)", file=sys.stderr)
        return 3
    chip = peaks.peaks_of(devices[0].device_kind)   # unknown kind: an error

    line, _ = measure(cell, seed=a.seed, seconds=a.seconds,
                      trace=a.trace, chip=chip)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
