"""The window logic and the result line at a tiny size on the CPU, through
the harness's own functions (the command itself refuses a CPU)."""

import json
import math
import os
import subprocess
import sys

import pytest

from benchmark import check, manifest, run, window
from benchmark.tests import tiny

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _measure(tmp_path, cell_name, **kw):
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    cell = manifest.resolve_cell(man, cell_name, root)
    return run.measure(cell, seed=7, seconds=0.5, trace=0,
                       chip=tiny.FAKE_CHIP, root=root,
                       out_dir=str(tmp_path / "out"), expect_mosaic=False,
                       **kw)


@pytest.mark.parametrize("cell_name,chips", [("tiny_gpt2_c1", 1),
                                             ("tiny_mistral_c4", 4)])
def test_cell_runs_through_train_dist_and_ends_its_own_window(
        tmp_path, cell_name, chips):
    line, report = _measure(tmp_path, cell_name)
    assert set(line) == RESULT_KEYS
    assert set(line["metrics"]) == {"tokens_per_s", "mfu_pct", "setup_s"}
    assert all(set(m) == {"value", "unit"} and m["value"] > 0
               for m in line["metrics"].values())
    assert line["failed"] == 0 and line["attempted"] == len(report["steps_ms"])
    assert line["attempted"] >= 5
    # the window closed at the first step that ended 0.5 s after its start
    w = report["window"]
    assert w["wall_s"] >= 0.5
    assert w["wall_s"] - report["steps_ms"][-1] / 1e3 < 0.5
    assert math.isclose(
        line["metrics"]["tokens_per_s"]["value"],
        report["tokens_per_step"] / w["mean_period_s"])
    # warm-up steps are not in the window; their losses are kept
    assert len(report["losses"]) == window.WARMUP_STEPS + line["attempted"]
    checks = report["checks"]
    assert checks["ended_by_harness"] and checks["losses_finite"]
    assert checks["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, checks
    assert line["device"]["count"] >= chips
    json.dumps(line)


def test_a_new_architecture_is_new_files_and_new_entries_only(tmp_path):
    """A family the harness has never seen (a sparse mixture of experts,
    which the program trains) is added to a copy of the benchmark by its
    reference file, a configuration, a cell and a metric, without editing a
    file that was there, the Python among it; the harness finds all of it by
    name, step 0 agrees with the new reference, and ``mfu_pct`` follows the
    family's own FLOP count."""
    import dataclasses

    from benchmark import flops, reference

    root = tiny.make_root(tmp_path)
    tiny.add_new_family(root)
    tiny.assert_nothing_that_was_there_is_edited(root)
    man = manifest.load_manifest(root)
    assert manifest.check_manifest(man, root) == []
    cell = manifest.resolve_cell(man, tiny.NEW_FAMILY_CELL[0], root)
    kw = dict(seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP, root=root,
              out_dir=str(tmp_path / "out"), expect_mosaic=False)
    line, report = run.measure(cell, **kw)
    checks = report["checks"]
    assert checks["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, checks
    assert not report["reference"]["cached"]

    # two experts of four and the router a token and layer, not one MLP
    sizes = flops.Sizes(layers=2, hidden=32, heads=4, kv_heads=2, head_dim=8,
                        ffn=64, ffn_matrices=3, vocab=64, seq=16, experts=4)
    family = reference.load_family("tiny_mixtral", root)
    forward = family.forward_flops_per_token(sizes, cell.config)
    dense = flops.forward_flops_per_token(
        dataclasses.replace(sizes, experts=0))
    assert forward - dense == 2 * (2 * 32 * 4 + 2 * 32 * 64 * 3)
    assert report["train_flops_per_token"] == 3 * forward
    m = line["metrics"]
    assert m["mfu_pct"]["value"] == pytest.approx(
        100 * m["tokens_per_s"]["value"] * 3 * forward
        / tiny.FAKE_CHIP["bf16_flops_per_s"], rel=1e-12)

    # the same program under a dense family is refused before the window
    path = os.path.join(root, "benchmark", "configs", "tiny-mixtral.json")
    with open(path, "w") as f:
        json.dump({**cell.config, "reference": {
            **cell.config["reference"], "family": "mistral"}}, f)
    cell = manifest.resolve_cell(man, tiny.NEW_FAMILY_CELL[0], root)
    with pytest.raises(ValueError, match="4 experts a layer"):
        run.measure(cell, **kw)
    # and a family whose file lacks an export, by the file's name
    with open(manifest.family_path(root, "half_written"), "w") as f:
        f.write("def nll_sum(w, cfg, tokens, labels, *, layers=None): ...\n")
    with pytest.raises(AttributeError,
                       match="half_written.py does not export "
                             "forward_flops_per_token"):
        reference.load_family("half_written", root)
    with pytest.raises(FileNotFoundError, match="never_written.py"):
        reference.load_family("never_written", root)


@pytest.mark.parametrize("cell_name", ["tiny_gpt2_c1", "tiny_mistral_c1"])
def test_the_moved_references_return_the_old_loss_bit_for_bit(
        tmp_path, cell_name):
    """``reference/gpt2.py`` and ``reference/mistral.py`` against
    ``decoder_lm.py`` as it was before the split (kept beside this file as
    the oracle), on the tiny cells' own weights and first batch: as they
    are, with a block fewer, and in bfloat16."""
    import jax.numpy as jnp

    from benchmark import reference
    from benchmark.tests import old_decoder_lm

    root = tiny.make_root(tmp_path)
    cell = manifest.resolve_cell(manifest.load_manifest(root), cell_name,
                                 root)
    weights, tokens, labels = check.first_batch_and_weights(
        manifest.train_argv(cell, 7, root))
    family = cell.config["reference"]["family"]
    for kw in ({}, {"layers": 1}, {"dtype": jnp.bfloat16}):
        assert reference.mean_loss(
            family, weights, cell.config, tokens, labels, **kw
        ) == old_decoder_lm.mean_loss(
            family, weights, cell.config, tokens, labels, **kw), kw


def test_reference_comparison_fails_for_a_dropped_block(tmp_path):
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    cell = manifest.resolve_cell(man, "tiny_gpt2_c1", root)
    argv = manifest.train_argv(cell, 7, root)
    full = check.reference_loss(cell, argv, 7, root, str(tmp_path))["loss"]
    short = check.reference_loss(cell, argv, 7, root, str(tmp_path),
                                 layers=1)["loss"]
    assert full != short
    facts = {"losses": [full], "window_losses": [], "rc": 18,
             "signalled": True, "raised": None, "attention_cores": ["xla"],
             "mosaic_custom_calls": 0}
    ok = check.judge(facts, reference=full, tolerance=1e-6,
                     expect_mosaic=False)["checks"]
    bad = check.judge(facts, reference=short,
                      tolerance=abs(full - short) / 2,
                      expect_mosaic=False)["checks"]
    assert ok["step0_matches_reference"]
    assert not bad["step0_matches_reference"]


def test_judge_names_each_failed_condition():
    good = {"losses": [5.0] * 8, "window_losses": [4.9] * 5, "rc": 18,
            "signalled": True, "raised": None,
            "attention_cores": ["flash"] * 2, "mosaic_custom_calls": 6,
            "compile": {"window": {"cache_writes": 0,
                                   "backend_compiles": 0}}}
    assert check.judge(good, reference=5.0, tolerance=0.01)["correct"]
    for change, failed in [
            ({"rc": 0}, "ended_by_harness"),
            ({"losses": [5.0, float("nan")] + [5.0] * 6}, "losses_finite"),
            ({"attention_cores": ["flash", "xla"]}, "flash_core_everywhere"),
            ({"mosaic_custom_calls": 5}, "flash_core_everywhere"),
            ({"compile": {"window": {"cache_writes": 1,
                                     "backend_compiles": 1}}},
             "no_compile_in_window"),
            ({"window_losses": [9.0] * 5}, "loss_not_rising")]:
        v = check.judge({**good, **change}, reference=5.0, tolerance=0.01)
        assert not v["correct"] and not v["checks"][failed], (change, v)
    assert not check.judge(good, reference=5.5,
                           tolerance=0.01)["checks"]["step0_matches_reference"]


def test_judge_takes_cores_and_mosaic_calls_from_the_configuration():
    """A hybrid stack: its configuration's file allows a second core beside
    flash and states the Mosaic calls a layer that its step has to hold."""
    hybrid = {"losses": [5.0] * 8, "window_losses": [4.9] * 5, "rc": 18,
              "signalled": True, "raised": None,
              "attention_cores": ["flash", "window"] * 2,
              "mosaic_custom_calls": 8,
              "compile": {"window": {"cache_writes": 0,
                                     "backend_compiles": 0}}}
    judge = lambda facts, expects: check.judge(
        facts, reference=5.0, tolerance=0.01,
        expects=expects)["checks"]["flash_core_everywhere"]
    assert not judge(hybrid, None)     # today's rule: flash alone, 3 a layer
    assert not judge(hybrid, {})
    two = {"attention_cores": ["flash", "window"],
           "mosaic_calls_per_layer": 2}
    assert judge(hybrid, two)
    assert not judge({**hybrid, "mosaic_custom_calls": 7}, two)
    assert not judge({**hybrid, "attention_cores": ["flash", "xla"] * 2}, two)
    assert not judge({**hybrid, "attention_cores": []}, two)
    assert not judge(hybrid, {"attention_cores": ["flash", "window"]})  # 3 x 4
    # one key of the two: the other keeps its default
    assert judge({**hybrid, "attention_cores": ["flash"] * 4},
                 {"mosaic_calls_per_layer": 2})


def test_window_signals_once_at_the_first_sample_past_its_length():
    stops = []
    w = window.Window(1.0, stop=lambda: stops.append(1))
    w.on_sample(10.4, 400.0)    # step [10.0, 10.4]
    w.on_sample(10.9, 400.0)
    assert not stops
    w.on_sample(11.3, 400.0)    # 1.3 s after the first start
    w.on_sample(11.7, 400.0)
    assert stops == [1] and w.signalled
    assert w.start == pytest.approx(10.0) and w.end == 11.7


def test_the_rate_is_over_every_period_of_the_window_a_stalled_one_too():
    steps = [(k * 0.23, k * 0.23 + 0.222) for k in range(44)]
    calm = window.steady_rate(steps)
    assert calm["mean_period_s"] == pytest.approx(0.23)
    assert calm["median_period_s"] == pytest.approx(0.23)
    assert calm["loop_overhead_ms"] == pytest.approx(8.0)
    assert calm["stall_pct"] == pytest.approx(0.0, abs=1e-9)
    # step 7 stalls for 1.1 s (seen on the chip in gpt2xl_c1_b4): the rate
    # holds it, the median period beside it does not, and stall_pct is the
    # difference
    late = [(s + (1.1 if k > 7 else 0), e + (1.1 if k >= 7 else 0))
            for k, (s, e) in enumerate(steps)]
    stalled = window.steady_rate(late)
    assert stalled["mean_period_s"] == pytest.approx((43 * 0.23 + 1.1) / 43)
    assert stalled["median_period_s"] == pytest.approx(0.23)
    assert stalled["stall_pct"] == pytest.approx(
        100 * 1.1 / (43 * 0.23 + 1.1))
    with pytest.raises(ValueError):
        window.steady_rate(steps[:1])


def test_steps_on_levels_give_a_rate_that_moves_with_their_shares():
    """``mellum2_c4_ep4``: a step takes 368.6, 381.3 or 394.9 ms by the
    passes its fullest chip counts. With 13 or 14 of 27 periods on the lowest
    level the median period is one level or the next (3.4 % apart), and the
    mean period moves by one step's 12.7 ms over 27."""
    def window_of(levels):
        t, steps = 0.0, []
        for ms in levels:
            steps.append((t, t + ms / 1e3 - 0.004))
            t += ms / 1e3
        return window.steady_rate(steps + [(t, t + 0.36)])

    a = window_of([368.6] * 14 + [381.3] * 9 + [394.9] * 4)
    b = window_of([368.6] * 13 + [381.3] * 10 + [394.9] * 4)
    assert a["median_period_s"] == pytest.approx(0.3686)
    assert b["median_period_s"] == pytest.approx(0.3813)
    assert b["mean_period_s"] - a["mean_period_s"] == pytest.approx(
        0.0127 / 27)
    assert a["mean_period_s"] == pytest.approx(
        (14 * 368.6 + 9 * 381.3 + 4 * 394.9) / 27e3)


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, "benchmark", "run.py"),
         "--workload", "gpt2xl_c1_b4", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        cwd=manifest.ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert "refusing to run" in p.stderr
    assert not p.stdout.strip().startswith("{")
