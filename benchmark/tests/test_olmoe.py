"""The ``olmoe`` family, its configuration, its cell and its per-layer
metrics: a tiny OLMoE through ``measure()`` on the CPU with both router
terms on, the family's FLOP count at the published widths, the manifest,
and the expert readers on a trace that has no expert in it."""

import gzip
import os
import shutil

import pytest

from benchmark import (
    check,
    flops,
    manifest,
    peaks,
    readers,
    reference,
    run,
    xplane,
)
from benchmark.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "olmoe_c1_s4k"

TINY_OLMOE = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 32, "intermediate_size": 16,
    "max_position_embeddings": 32, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 4, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "vocab_size": 64,
    "router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001,
    "qk_norm": True,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(tiny.YAMLS, "olmoe-1b-7b.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=2",
            "model.num_attention_heads=4", "model.num_key_value_heads=4",
            "model.ffn_hidden_size=16", "model.vocab_size=64",
            "model.make_vocab_size_divisible_by=1", "model.seq_length=16",
            "model.max_position_embeddings=32", "model.num_experts=8",
            "model.moe_topk=2"],
        "equals": {"hidden_size": "hidden_size",
                   "ffn_hidden_size": "intermediate_size",
                   "num_experts": "num_experts",
                   "moe_topk": "num_experts_per_tok",
                   "moe_norm_topk_prob": "norm_topk_prob",
                   "qk_norm": "qk_norm",
                   "moe_aux_loss_coeff": "router_aux_loss_coef",
                   "moe_z_loss_coeff": "router_z_loss_coef"}},
    "reference": {"family": "olmoe", "loss_tolerance": 0.02},
}
# one sequence a microbatch: the condition under which the program's router
# terms (a mean over a microbatch) are the reference's (a mean over a call)
ONE_SEQUENCE_A_MICROBATCH = tiny.COMMON + [
    "parallel.global_train_batch_size=4", "parallel.chunks=4"]


def _tiny_root(tmp_path, body=TINY_OLMOE):
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    tiny._add_config(root, man, "tiny-olmoe", body)
    tiny._write(manifest.traffic_path(root, "tiny_c1_s1"),
                {"overrides": ONE_SEQUENCE_A_MICROBATCH})
    tiny._add_cell(man, "tiny_olmoe_c1", "tiny-olmoe", "tiny_c1_s1", 1)
    tiny._write(os.path.join(root, "BENCHMARK.json"), man)
    tiny.assert_nothing_that_was_there_is_edited(root)
    assert manifest.check_manifest(man, root) == []
    return root, manifest.resolve_cell(man, "tiny_olmoe_c1", root)


def test_a_tiny_olmoe_runs_and_meets_its_reference_with_the_router_terms_on(
        tmp_path):
    root, cell = _tiny_root(tmp_path)
    line, report = run.measure(
        cell, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP, root=root,
        out_dir=str(tmp_path / "out"), expect_mosaic=False)
    checks = report["checks"]
    assert checks["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, checks
    # the terms are in the loss: 0.01 x 2 a block when balanced, and the z
    # term, over two blocks, lift it above ln(64) at random weights
    assert report["reference"]["loss"] > 4.19
    # and the comparison would have failed without them
    weights, tokens, labels = check.first_batch_and_weights(
        manifest.train_argv(cell, 7, root))
    bare = reference.mean_loss(
        "olmoe", weights, {**cell.config, "router_aux_loss_coef": 0.0,
                           "router_z_loss_coef": 0.0},
        tokens, labels, root=root)
    assert report["reference"]["loss"] - bare > 0.03
    # the tracker's gauges are what moe_imbalance reads
    assert readers.read_metric("moe_imbalance", {}, root) >= 1.0
    sizes = flops.Sizes(layers=2, hidden=32, heads=4, kv_heads=4, head_dim=8,
                        ffn=16, ffn_matrices=3, vocab=64, seq=16, experts=8)
    forward = reference.load_family("olmoe", root).forward_flops_per_token(
        sizes, cell.config)
    assert report["train_flops_per_token"] == 3 * forward


def _published_sizes():
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    args = resolve_model_config(args_from_cli(
        manifest.train_argv(cell, seed=0), mode="train_dist"))
    for attr, key in cell.config["program"]["equals"].items():
        assert getattr(args.model, attr) == cell.config[key], attr
    return cell, flops.Sizes.of(args.model)


def test_the_family_counts_eight_experts_and_the_router_at_published_widths():
    cell, sizes = _published_sizes()
    assert (sizes.layers, sizes.seq, sizes.experts) == (1, 4096, 64)
    forward = reference.load_family("olmoe").forward_flops_per_token(
        sizes, cell.config)
    attention = (2 * 2048 * (16 + 2 * 16) * 128 + 2 * 16 * 128 * 2048
                 + 2 * 2 * 16 * 128 * (4096 + 1) // 2)
    assert forward == (attention + 8 * 3 * 2 * 2048 * 1024 + 2 * 2048 * 64
                       + 2 * 2048 * 50304) == 357_306_368
    # the expert layer is 28 % of it and the head 58 %
    assert round(100 * (8 * 3 * 2 * 2048 * 1024 + 2 * 2048 * 64)
                 / forward) == 28
    assert round(100 * 2 * 2048 * 50304 / forward) == 58


def test_the_manifest_holds_five_cells_and_the_expert_metrics_are_the_cell_s():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    # the cell's own entries and the manifest's beginning, not its length:
    # a later PR adds cells after these
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4][:1] == [
        "mistral7b_c4_tp2dp2z3"]
    assert man["workloads"][4] == {
        "name": CELL, "config": "olmoe-1b-7b-d1", "traffic": "c1_s4k",
        "chips": 1, "why": man["workloads"][4]["why"]}
    # the cell's expert metrics are the shared entries' since PR 65: the
    # first cell each of them lists
    mine = [m for m in man["per_layer"]
            if (m.get("workloads") or [None])[0] == CELL]
    assert {m["name"] for m in mine} >= {
        "experts_ms", "experts_time_share_pct", "experts_roofline",
        "moe_imbalance"}
    assert all(m["moves"] == "tokens_per_s" and m["layer"] == "experts"
               for m in mine)
    cell = manifest.resolve_cell(man, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"flash_roofline", "static_hbm_GiB", "device_idle_pct"} < names
    other = manifest.resolve_cell(man, "mistral7b_c1_s4k")
    assert not {m["name"] for m in mine} & {m["name"]
                                            for m in other.per_layer}
    # the file is the catalog's config with the depth alone cut
    body = cell.config
    assert body["reduced_from"] == {"num_hidden_layers": 16}
    assert (body["num_experts"], body["num_experts_per_tok"],
            body["intermediate_size"], body["hidden_size"]) == (
                64, 8, 1024, 2048)


def test_the_experts_cost_is_three_passes_of_rows_through_three_matrices():
    cell, sizes = _published_sizes()
    need = tiny.cost_beside_the_metrics(
        "experts_cost.py", "experts_step_cost")(sizes, 4, cell.config, 4)
    rows = 4 * 4096 * 8
    assert need["flops"] == 3 * rows * 3 * 2 * 2048 * 1024
    assert need["bytes"] == 3 * (4 * 64 * 3 * 2048 * 1024 * 2
                                 + rows * (2048 + 2048 + 1024 + 2048) * 2)
    least = flops.roofline_least_s(need, peaks.peaks_of("TPU v5 lite"))
    assert least["bound"] == "compute"
    assert least["least_s"] == pytest.approx(0.02512, rel=1e-3)


def test_on_a_trace_without_experts_the_readers_say_so_and_do_not_raise(
        tmp_path):
    """``mistral7b_c1_s4k``'s recorded trace has no grouped matmul and no
    map of its step to join: the readers of the scope say nothing."""
    path = str(tmp_path / "t.xplane.pb")
    with gzip.open(os.path.join(
            HERE, "mistral7b_c1_s4k.seed1.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    _, sizes = _published_sizes()
    facts = {"trace": xplane.facts_of(path, chips=1), "sizes": sizes,
             "sequences_per_step": 4, "chips": 1,
             "peaks": peaks.peaks_of("TPU v5 lite")}
    assert readers.read_metric("experts_ms", facts) is None
    assert readers.read_metric("experts_time_share_pct", facts) is None
    assert readers.read_metric("experts_roofline", facts) is None
    assert readers.read_metric("experts_ms", {}) is None
