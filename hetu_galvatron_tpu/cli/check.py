"""Static checks CLI: ``python -m hetu_galvatron_tpu.cli.check``.

Run the five-pass static analysis suite (``analysis/``) on CPU — no TPU,
no training step — BEFORE burning accelerator time:

* ``--plan plan.json [--model cfg.yaml] [--world N]`` — Pass 1, the plan
  doctor: per-layer engine/kernel report with actionable errors for
  malformed plans.
* ``--census`` — Pass 2: trace the compiled 1F1B step for the committed
  acceptance plan plus the serving prefill/decode programs, census their
  collectives, verify named_scope marker coverage and the exact-count
  cross-check against the plan arithmetic
  (``telemetry.plan_collective_counts``).
* ``--lint [--update-baseline | --prune-baseline]`` — Pass 3: the AST
  lint with the committed baseline (``analysis/lint_baseline.json``);
  the gate is zero NEW findings. ``--prune-baseline`` auto-removes STALE
  fingerprints only (no new finding is ever auto-accepted).
* ``--memory [--hbm-gb N]`` — Pass 4, the memory doctor: static
  per-device peak-HBM accounting for the committed example plans
  (model states / activations / compiled-engine stage buffer / vocab
  replication / serving KV pool), cross-checked per component against
  the search engine's memory cost model; ``--hbm-gb`` rejects plans
  whose predicted peak exceeds the budget — the SAME predicate the
  search engine prunes with (``search.hbm_budget_gb``).
* ``--flow`` — Pass 5, the sharding-flow analysis: the census extended
  from counts to BYTES (per-collective megabytes cross-checked exactly
  against ``telemetry.plan_collective_bytes``), plus reshard detection
  (stray all-gathers, double-resharded values) and the donation audit
  over the step + serving programs.
* ``--calibration`` — Pass 6: the calibration self-check — synthetic
  residual store -> α-β re-fit -> plan-regret sentinel round-trip with
  known ground truth.
* ``--all`` — every pass on the committed examples. This is the CI step
  (``__graft_entry__.dryrun_multichip`` runs it and tier-1 asserts it
  green). The partition-time HLO walk (``sharding_flow.hlo_collectives``)
  compiles programs and rides the slow test tier instead.

Exit code 0 = clean, 1 = findings/errors, 2 = usage.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys
from typing import List, Optional

EXAMPLE_PLAN_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "profiles", "example_plans")
ACCEPTANCE_PLAN = os.path.join(
    EXAMPLE_PLAN_DIR, "galvatron_config_acceptance_tp2dp2pp2.json")


def _force_cpu_devices(n: int = 8) -> None:
    """Static analysis must run on CPU with no accelerator: force the
    virtual host platform BEFORE jax initializes (a no-op when the test
    harness already did). APPEND to any pre-existing XLA_FLAGS — a host
    exporting e.g. --xla_dump_to must not silently lose the device-count
    flag."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " if flags else "") + \
            f"--xla_force_host_platform_device_count={n}"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def _example_model():
    """The tiny 4-layer model the committed example plans were written
    for (the dryrun/test shape: every kernel family exercisable on the
    8-device virtual mesh)."""
    from hetu_galvatron_tpu.core.args_schema import CoreArgs

    return CoreArgs.model_validate({
        "model": {
            "hidden_size": 64, "num_hidden_layers": 4,
            "num_attention_heads": 4, "vocab_size": 256,
            "seq_length": 16, "max_position_embeddings": 32,
            "hidden_act": "swiglu", "normalization": "rmsnorm",
            "position_embedding_type": "rope", "tie_word_embeddings": False,
            "add_bias_linear": False, "add_qkv_bias": False,
            "make_vocab_size_divisible_by": 1, "ffn_hidden_size": 128,
        },
    })


def _load_model(model_path: Optional[str]):
    """--model: a train_dist-style YAML, or None for the example model."""
    if model_path is None:
        return _example_model().model
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        resolve_model_config,
    )

    args = args_from_cli([model_path], mode="train_dist")
    return resolve_model_config(args).model


def run_doctor(plan: str, model_path: Optional[str], world: Optional[int],
               *, schedule_impl: str = "compiled",
               tp_overlap: bool = True) -> int:
    from hetu_galvatron_tpu.analysis.plan_doctor import diagnose_plan

    cfg = _load_model(model_path)
    report = diagnose_plan(plan, cfg, world, schedule_impl=schedule_impl,
                           tp_overlap=tp_overlap)
    report.render()
    return 0 if report.ok else 1


def run_census(verbose: bool = True) -> int:
    """Census smoke on the acceptance plan (compiled 1F1B step, exact
    count cross-check) + the serving prefill/decode programs."""
    _force_cpu_devices()
    from hetu_galvatron_tpu.analysis.census import (
        census_compiled_step,
        census_serving_programs,
        check_census,
    )
    from hetu_galvatron_tpu.observability.telemetry import (
        plan_collective_counts,
    )
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    args = _example_model()
    args.parallel.config_mode = "json"
    args.parallel.galvatron_config_path = ACCEPTANCE_PLAN
    hpc = get_hybrid_parallel_config(args, 8)
    problems: List[str] = []

    c = census_compiled_step(args.model, hpc, args.train, tp_overlap=True)
    predicted = plan_collective_counts(hpc, args.model, tp_overlap=True)
    if verbose:
        print(f"census: compiled 1F1B step "
              f"[{hpc.describe()}] -> {c.counts} "
              f"(markers {c.permutes_by_marker})")
        print(f"census: plan arithmetic predicts {predicted}")
    if not c.donated_args:
        problems.append("compiled step: no donated arguments — the fused "
                        "optimizer step must donate (params, opt) or live "
                        "memory doubles")
    problems += check_census(c, predicted, program="compiled_step")
    for n in c.notes:
        print(f"census note: {n}")

    # serving prefill + decode + prefix-prefill + speculative verify:
    # single-device tiny engine; the check is marker coverage + no host
    # callbacks in the token-latency path (prefix_cache/spec_decode on so
    # the new program families are censused too)
    serving = _census_serving_args()
    for name, sc in census_serving_programs(
            args.model, serving=serving).items():
        if verbose:
            print(f"census: serving {name} -> {sc.counts or '{}'}")
        problems += check_census(sc, program=f"serving {name}")

    for p in problems:
        print(f"CENSUS FAILURE: {p}")
    print(f"census: {'OK' if not problems else 'FAILED'}")
    return 0 if not problems else 1


def _census_serving_args():
    """The serving shape every serving-program pass censuses (prefix
    cache + spec decode on, so all program families are covered)."""
    from hetu_galvatron_tpu.core.args_schema import ServingArgs

    return ServingArgs(max_batch_size=2, kv_block_size=8,
                       max_seq_len=32, num_kv_blocks=10,
                       prefix_cache=True, spec_decode=True, spec_k=2)


def run_memory(hbm_gb: Optional[float] = None, verbose: bool = True,
               schedule_impl: str = "compiled") -> int:
    """Pass 4: the memory doctor over every committed example plan, plus
    a serving-mode row (KV pool + prefix budget) on the acceptance plan.
    ``--hbm-gb`` turns the accounting into a gate; ``--schedule-impl``
    picks the engine convention to account for (compiled — the
    conservative default the search's HBM gate also uses — adds the
    stage-input buffer and the vocab replication premium)."""
    from hetu_galvatron_tpu.analysis.memory_doctor import diagnose_memory

    model = _example_model().model
    rc = 0
    for plan in sorted(glob.glob(os.path.join(EXAMPLE_PLAN_DIR, "*.json"))):
        report = diagnose_memory(plan, model, 8, hbm_gb=hbm_gb,
                                 schedule_impl=schedule_impl)
        if verbose:
            report.render()
            print()
        rc |= 0 if report.ok else 1
    serving = _census_serving_args()
    report = diagnose_memory(ACCEPTANCE_PLAN, model, 8, hbm_gb=hbm_gb,
                             serving=serving,
                             schedule_impl=schedule_impl)
    if verbose:
        print("(serving mode: paged KV pool + prefix-cache budget)")
        report.render()
    rc |= 0 if report.ok else 1
    print(f"memory doctor: {'OK' if rc == 0 else 'FAILED'} (all plans)")
    return rc


def run_flow(verbose: bool = True) -> int:
    """Pass 5: the sharding-flow byte census on the acceptance plan's
    compiled step (exact cross-check against
    ``telemetry.plan_collective_bytes``, donation audit, reshard lint)
    plus the serving program families (reshard lint; their params stay
    undonated by design)."""
    _force_cpu_devices()
    from hetu_galvatron_tpu.analysis.sharding_flow import (
        check_donation,
        check_flow,
        flow_compiled_step,
        flow_serving_programs,
    )
    from hetu_galvatron_tpu.observability.telemetry import (
        plan_collective_bytes,
    )
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )

    args = _example_model()
    args.parallel.config_mode = "json"
    args.parallel.galvatron_config_path = ACCEPTANCE_PLAN
    hpc = get_hybrid_parallel_config(args, 8)
    problems: List[str] = []

    pf = flow_compiled_step(args.model, hpc, args.train, tp_overlap=True)
    predicted = plan_collective_bytes(hpc, args.model, tp_overlap=True)
    if verbose:
        cats = {k: round(v, 6) for k, v in pf.flow.mb_by_cat.items()}
        marks = {k: round(v, 6)
                 for k, v in pf.flow.permute_mb_by_marker.items()}
        pred = {k: round(v, 6) for k, v in predicted.items()}
        print(f"flow: compiled 1F1B step [{hpc.describe()}] moves "
              f"{cats} MB (markers {marks})")
        print(f"flow: plan arithmetic predicts {pred} MB")
        print(f"flow: donation — {pf.donation.donated_mb:.2f} MB donated, "
              f"{pf.donation.undonated_mb:.2f} MB undonated")
    problems += check_flow(pf.flow, predicted, program="compiled_step")
    problems += check_donation(pf.donation, program="compiled_step")
    problems += pf.reshard_problems
    for n in pf.flow.notes:
        print(f"flow note: {n}")

    for name, spf in flow_serving_programs(
            args.model, serving=_census_serving_args()).items():
        if verbose:
            scats = {k: round(v, 6)
                     for k, v in spf.flow.mb_by_cat.items()} or "{}"
            print(f"flow: serving {name} -> {scats} MB "
                  f"(donated {spf.donation.donated_mb:.2f} MB)")
        problems += spf.reshard_problems

    for p in problems:
        print(f"FLOW FAILURE: {p}")
    print(f"flow: {'OK' if not problems else 'FAILED'}")
    return 0 if not problems else 1


def run_lint(update_baseline: bool = False, prune_stale: bool = False,
             verbose: bool = True) -> int:
    from hetu_galvatron_tpu.analysis.lint import (
        lint_package,
        load_baseline,
        new_findings,
        prune_baseline,
        save_baseline,
        stale_baseline,
    )

    findings = lint_package()
    baseline = load_baseline()
    if prune_stale:
        removed = prune_baseline(findings)
        print(f"lint: pruned {len(removed)} stale baseline entr"
              f"{'y' if len(removed) == 1 else 'ies'}")
        for k in removed[:10]:
            print(f"  pruned: {k}")
        baseline = load_baseline()
        # fall through: the gate still runs, so a prune that leaves NEW
        # findings behind stays red (pruning never accepts new findings)
    if update_baseline:
        save_baseline(findings, keep=baseline)
        print(f"lint: baseline rewritten with {len(findings)} finding(s); "
              "fill in any 'TODO: justify or fix' entries")
        return 0
    new = new_findings(findings, baseline)
    stale = stale_baseline(findings, baseline)
    if verbose:
        print(f"lint: {len(findings)} finding(s), "
              f"{len(findings) - len(new)} baselined, {len(new)} new")
    for f in new:
        print(f"LINT: {f}")
    if stale:
        # stale entries FAIL the gate too (same contract as the tier-1
        # test): the baseline must only ever describe live findings
        print(f"lint: {len(stale)} baselined finding(s) no longer occur — "
              "prune them with --update-baseline:")
        for k in stale[:10]:
            print(f"  stale: {k}")
    if new:
        verdict = ("FAILED (new findings — fix them or baseline with a "
                   "justification via --update-baseline)")
    elif stale:
        verdict = "FAILED (stale baseline — prune with --update-baseline)"
    else:
        verdict = "OK"
    print(f"lint: {verdict}")
    return 0 if not new and not stale else 1


def run_calibration() -> int:
    """Pass 6 — calibration self-check (``observability/calibration.py``):
    a synthetic end-to-end exercise of the store -> re-fit -> regret loop
    with known ground truth. Appends two runs' worth of residual points
    drawn from a known α-β "truth" curve (plus a foreign-fingerprint
    batch that must be excluded), re-fits, and asserts the calibrated
    curve recovers the truth, the profile round-trips through BOTH α-β
    parsers with provenance intact, and the regret sentinel triggers on a
    seeded stale-plan case while staying quiet when calibrated == prior."""
    import tempfile

    from hetu_galvatron_tpu.core.search_engine.profiles import (
        read_alpha_beta,
        read_alpha_beta_algos,
        read_profile_provenance,
    )
    from hetu_galvatron_tpu.observability.calibration import (
        ResidualStore,
        evaluate_plan_regret,
        refit_profile,
        write_calibrated_profile,
    )

    print("== calibration self-check ==")
    failures: List[str] = []

    def check(ok: bool, what: str) -> None:
        print(f"  {'ok' if ok else 'FAIL'}: {what}")
        if not ok:
            failures.append(what)

    with tempfile.TemporaryDirectory() as td:
        store = ResidualStore(os.path.join(td, "residuals.jsonl"))
        fp = {"device": "synthetic", "world": 8, "mesh": [2, 2, 2]}
        alien = {"device": "synthetic", "world": 4, "mesh": [1, 2, 2]}
        a_true, b_true = 0.05, 250.0
        sizes = (1.0, 2.0, 4.0, 8.0, 16.0)

        def batch(scale):
            return [{"collective": "allreduce", "group": "4_1",
                     "alg": "flat", "mb": mb,
                     "ms": (a_true + mb / b_true) * scale, "w": 1.0}
                    for mb in sizes]

        store.append(batch(1.0), fingerprint=fp, run_id="run0")
        store.append(batch(1.02), fingerprint=fp, run_id="run1")
        # a foreign mesh's (wildly different) points must not pollute
        store.append([{"collective": "allreduce", "group": "4_1",
                       "alg": "flat", "mb": mb, "ms": 50.0, "w": 1.0}
                      for mb in sizes], fingerprint=alien, run_id="alien")
        pts = store.load(fingerprint=fp)
        check(len(pts) == 2 * len(sizes), "store round-trip keeps only "
              f"fingerprint-matched points ({len(pts)})")
        prof, meta = refit_profile(pts, min_points=4)
        pair = read_alpha_beta(prof).get("4_1")
        check(pair is not None, "re-fit emitted a flat 4_1 curve")
        if pair:
            a_fit, b_fit = pair
            check(abs(a_fit - a_true) < 0.02 * max(a_true, 1e-9) + 5e-3
                  and abs(b_fit - b_true) / b_true < 0.05,
                  f"fitted curve recovers truth (α {a_fit:.4f}~{a_true}, "
                  f"β {b_fit:.1f}~{b_true})")
        curve_meta = meta.get("curves", {}).get("4_1/flat", {})
        check(curve_meta.get("method") == "regression"
              and curve_meta.get("points", 0) >= 4,
              "provenance records method + point count")
        # file round-trip through both parsers, meta intact
        prof["calibration_meta"] = dict(meta, fingerprint=fp)
        prof["allreduce_size_4_consec_1_alg_ring_lvl_ici_alpha_ms"] = 0.04
        prof["allreduce_size_4_consec_1_alg_ring_lvl_ici_beta_mb_per_ms"] \
            = 260.0
        path = write_calibrated_profile(
            os.path.join(td, "calibrated_profile.json"), prof)
        check("4_1" in read_alpha_beta(path)
              and read_alpha_beta_algos(path)
              .get("4_1", {}).get("ring_ici") is not None,
              "profile file round-trips through both α-β parsers")
        check(read_profile_provenance(path)
              .get("source") == "runtime-calibrated",
              "provenance survives the file round-trip")

        # regret sentinel: calibration halves the comm-heavy runner-up's
        # collective cost, so it overtakes a compute-identical incumbent
        prior_ab = {"2_1": (0.1, 100.0), "4_0": (0.1, 100.0),
                    "4_1": (0.1, 100.0)}
        calib_ab = {"2_1": (0.05, 200.0), "4_0": (0.05, 200.0),
                    "4_1": (0.05, 200.0)}
        incumbent = {"time_cost_ms": 100.0, "pp": 1, "bsz": 8, "chunks": 2,
                     "layers": [{"tp": 1, "dp": 2}] * 2}
        hungry = {"time_cost_ms": 101.0, "pp": 1, "bsz": 8, "chunks": 2,
                  "layers": [{"tp": 4, "dp": 2}] * 2}
        # 64-MB tp activation messages make the runner-up comm-dominated:
        # calibration (halved α, doubled β) shrinks ITS priced comm far
        # more than the incumbent's small dp buffers, flipping the order
        kw = dict(seq_len=4096, hidden_size=4096, param_mb=8.0,
                  mixed_precision=True, threshold=0.001)
        res = evaluate_plan_regret(incumbent, [hungry],
                                   prior=(prior_ab, None),
                                   calibrated=(calib_ab, None), **kw)
        check(bool(res["triggered"]) and res["regret_ms"] > 0,
              f"seeded stale plan triggers regret "
              f"({res['regret_ms']:.3f} ms)")
        quiet = evaluate_plan_regret(incumbent, [hungry],
                                     prior=(prior_ab, None),
                                     calibrated=(prior_ab, None), **kw)
        check(not quiet["triggered"] and quiet["regret_ms"] == 0.0,
              "calibrated == prior stays quiet")

    print(f"calibration: {'OK' if not failures else 'FAILED'}")
    return 0 if not failures else 1


def run_all(hbm_gb: Optional[float] = None,
            schedule_impl: str = "compiled") -> int:
    """The CI gate: plan doctor over every committed example plan, the
    census smoke, the memory doctor with its cost-model cross-check, the
    sharding-flow byte census, and the lint baseline gate."""
    _force_cpu_devices()
    rc = 0
    for plan in sorted(glob.glob(os.path.join(EXAMPLE_PLAN_DIR, "*.json"))):
        rc |= run_doctor(plan, None, 8, schedule_impl=schedule_impl)
        print()
    rc |= run_census()
    print()
    rc |= run_memory(hbm_gb=hbm_gb, schedule_impl=schedule_impl)
    print()
    rc |= run_flow()
    print()
    rc |= run_lint()
    print()
    rc |= run_calibration()
    print()
    print(f"check --all: {'OK' if rc == 0 else 'FAILED'}")
    return rc


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m hetu_galvatron_tpu.cli.check",
        description="static analysis suite: plan doctor, jaxpr collective "
                    "census, AST lint")
    p.add_argument("--plan", help="plan JSON to diagnose (Pass 1)")
    p.add_argument("--model", help="train_dist-style YAML config for the "
                   "model the plan targets (default: the tiny example "
                   "model the committed plans were written for)")
    p.add_argument("--world", type=int, default=None,
                   help="world size to validate the plan against "
                   "(default: the smallest world the plan fits)")
    p.add_argument("--schedule-impl", choices=("compiled", "host"),
                   default="compiled", help="launcher schedule impl the "
                   "doctor should predict for (default compiled)")
    p.add_argument("--no-tp-overlap", action="store_true",
                   help="doctor: assume tp_overlap.enable is off")
    p.add_argument("--census", action="store_true",
                   help="run the jaxpr collective census (Pass 2)")
    p.add_argument("--lint", action="store_true",
                   help="run the AST lint against the baseline (Pass 3)")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the lint baseline from current findings, "
                   "preserving existing justifications")
    p.add_argument("--prune-baseline", action="store_true",
                   help="remove STALE lint-baseline fingerprints only "
                   "(never accepts new findings), then run the gate")
    p.add_argument("--memory", action="store_true",
                   help="run the memory doctor (Pass 4): static "
                   "per-device peak-HBM accounting + cost-model "
                   "cross-check on the committed example plans")
    p.add_argument("--hbm-gb", type=float, default=None,
                   help="per-device HBM budget in GB: the memory doctor "
                   "REJECTS plans whose predicted peak exceeds it (the "
                   "same predicate search.hbm_budget_gb prunes with)")
    p.add_argument("--flow", action="store_true",
                   help="run the sharding-flow analysis (Pass 5): "
                   "byte-level collective census with the exact "
                   "plan_collective_bytes cross-check, reshard "
                   "detection, and the donation audit")
    p.add_argument("--calibration", action="store_true",
                   help="run the calibration self-check (Pass 6): "
                   "synthetic residual store -> α-β re-fit -> plan-regret "
                   "sentinel round-trip with known ground truth")
    p.add_argument("--all", action="store_true",
                   help="every pass on the committed examples (the CI "
                   "step)")
    a = p.parse_args(argv)

    if a.all:
        return run_all(hbm_gb=a.hbm_gb, schedule_impl=a.schedule_impl)
    rc = None
    if a.plan:
        _force_cpu_devices()
        rc = run_doctor(a.plan, a.model, a.world,
                        schedule_impl=a.schedule_impl,
                        tp_overlap=not a.no_tp_overlap)
    if a.census:
        rc = (rc or 0) | run_census()
    if a.memory:
        rc = (rc or 0) | run_memory(hbm_gb=a.hbm_gb,
                                    schedule_impl=a.schedule_impl)
    if a.flow:
        rc = (rc or 0) | run_flow()
    if a.calibration:
        rc = (rc or 0) | run_calibration()
    if a.lint or a.update_baseline or a.prune_baseline:
        rc = (rc or 0) | run_lint(update_baseline=a.update_baseline,
                                  prune_stale=a.prune_baseline)
    if rc is None:
        p.print_help()
        return 2
    return rc


if __name__ == "__main__":
    sys.exit(main())
