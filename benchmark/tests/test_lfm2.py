"""The ``lfm2_moe`` family, its configuration, its cell and its per-layer
metrics: a tiny LFM2 (a dense conv block, then an attention block and conv
blocks with a held share of sigmoid-routed experts) through ``measure()``
on the CPU, the family's FLOP count as an exact integer at the published
widths and both depths, ``attention_blocks``, the manifest, and that every
file the benchmark had is as it was."""

import os

import pytest

from benchmark import flops, manifest, peaks, readers, reference, run
from benchmark.tests import tiny

CELL = "lfm2moe_c1_s8k"
PARENT = "78a062adbc83b319cddbbf3beb5897ac9ddfd3fe"
TYPES = ["conv", "full_attention", "conv", "conv", "conv"]

TINY_LFM2 = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 32,
    "intermediate_size": 48, "layer_types": TYPES,
    "max_position_embeddings": 32, "model_type": "lfm2_moe",
    "moe_intermediate_size": 16, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_dense_layers": 1, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 5,
    "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 64,
    "num_routed_experts": 8, "first_expert_held": 2,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(tiny.YAMLS, "lfm2-24b-a2b.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=5",
            "model.layer_types=[" + ",".join(TYPES) + "]",
            "model.num_dense_layers=1", "model.num_attention_heads=4",
            "model.num_key_value_heads=2", "model.ffn_hidden_size=48",
            "model.moe_ffn_hidden_size=16", "model.vocab_size=64",
            "model.make_vocab_size_divisible_by=1", "model.seq_length=16",
            "model.max_position_embeddings=32", "model.num_experts=8",
            "model.moe_topk=2", "model.moe_held_experts=4",
            "model.moe_first_held_expert=2"],
        "equals": {"hidden_size": "hidden_size", "layer_types": "layer_types",
                   "num_dense_layers": "num_dense_layers",
                   "ffn_hidden_size": "intermediate_size",
                   "moe_ffn_hidden_size": "moe_intermediate_size",
                   "num_experts": "num_routed_experts",
                   "moe_held_experts": "num_experts",
                   "moe_first_held_expert": "first_expert_held",
                   "moe_topk": "num_experts_per_tok"},
        "expects": {"attention_cores": ["flash", "xla", "short_conv"],
                    "mosaic_calls_per_layer": 0}},
    "reference": {"family": "lfm2_moe", "depth_key": "num_hidden_layers",
                  "loss_tolerance": 0.02},
}


def _tiny_root(tmp_path):
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    tiny._add_config(root, man, "tiny-lfm2", TINY_LFM2)
    tiny._add_cell(man, "tiny_lfm2_c1", "tiny-lfm2", "tiny_c1", 1)
    tiny._write(os.path.join(root, "BENCHMARK.json"), man)
    tiny.assert_nothing_that_was_there_is_edited(root)
    assert manifest.check_manifest(man, root) == []
    return root, manifest.resolve_cell(man, "tiny_lfm2_c1", root)


def test_a_tiny_lfm2_runs_and_meets_its_reference(tmp_path):
    root, cell = _tiny_root(tmp_path)
    line, report = run.measure(
        cell, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP, root=root,
        out_dir=str(tmp_path / "out"), expect_mosaic=False)
    checks = report["checks"]
    assert checks["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, checks
    assert report["attention_cores"].count("short_conv") == 4
    # the tracker's gauges are what the cell's two counter metrics read
    share = readers.read_metric("local_routes_pct", {}, root)
    assert 30.0 < share < 70.0          # 4 of 8 held: half of the routes
    assert readers.read_metric("lfm2_moe_imbalance", {}, root) >= 1.0
    # one attending block of five, in the FLOPs the run was scored by
    sizes = flops.Sizes(layers=5, hidden=32, heads=4, kv_heads=2, head_dim=8,
                        ffn=48, ffn_matrices=3, vocab=64, seq=16, experts=8)
    family = reference.load_family("lfm2_moe", root)
    sizes = sizes.with_attention(family.attention_blocks(cell.config))
    assert report["train_flops_per_token"] == 3 * \
        family.forward_flops_per_token(sizes, cell.config)


def _published(depth):
    """The configuration's file at ``depth`` blocks (5 as committed; 9 is
    the stack the sizing rule turned down) and the program's sizes."""
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    args = resolve_model_config(args_from_cli(
        manifest.train_argv(cell, seed=0), mode="train_dist"))
    for attr, key in cell.config["program"]["equals"].items():
        assert getattr(args.model, attr) == cell.config[key], attr
    types = (["conv", "conv", "full_attention", "conv"] * 10)[1:1 + depth]
    config = {**cell.config, "num_hidden_layers": depth, "layer_types": types}
    sizes = flops.Sizes.of(args.model.model_copy(update=dict(
        num_hidden_layers=depth, layer_types=types)))
    return config, sizes


@pytest.mark.parametrize("depth,attending,forward", [
    (5, 1, 405_803_008), (9, 2, 599_793_664)])
def test_the_family_adds_its_blocks_up_by_kind(depth, attending, forward):
    config, sizes = _published(depth)
    family = reference.load_family("lfm2_moe")
    blocks = family.attention_blocks(config)
    assert blocks == [{}] * attending
    sizes = sizes.with_attention(blocks)
    assert (sizes.layers, sizes.seq, sizes.vocab) == (depth, 8192, 8192)
    attention = (2 * 2048 * (32 + 2 * 8) * 64 + 2 * 32 * 64 * 2048
                 + 2 * 2 * 32 * 64 * (8192 + 1) // 2)
    conv = 2 * (3 * 2048 * 2048 + 2048 * 2048)
    dense = 2 * 3 * 2048 * 11776
    # the held experts at their expected share of the routes: 4 x 8 / 64
    experts = 2 * 2048 * 64 + (4 * 8 * 2 * 3 * 2048 * 1536) // 64
    head = 2 * 2048 * 8192
    assert family.forward_flops_per_token(sizes, config) == (
        attending * attention + (depth - attending) * conv + dense
        + (depth - 1) * experts + head) == forward
    if depth == 5:   # conv 33 %, the dense MLP 36 %, attention 13 %
        assert round(100 * 4 * conv / forward) == 33
        assert round(100 * dense / forward) == 36
        assert round(100 * attention / forward) == 13


def test_the_manifest_holds_six_cells_and_the_new_metrics_are_the_cell_s():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    # the cell's own entries and the manifest's beginning, not its length:
    # a later PR adds cells after these
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4][:1] == [
        "mistral7b_c4_tp2dp2z3"]
    assert man["workloads"][5]["name"] == CELL
    # what the cell came with, under the names PR 65 left (the shared
    # entries list it), and what later PRs read of its trace beside them
    new = {"experts_ms", "experts_time_share_pct", "experts_roofline",
           "local_routes_pct", "lfm2_moe_imbalance"}
    assert new <= tiny.listed_for(man, CELL)
    assert all(m["moves"] == "tokens_per_s" and m["layer"] == "experts"
               for m in man["per_layer"] if m["name"] in new)
    cell = manifest.resolve_cell(man, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"flash_roofline", "static_hbm_GiB", "device_idle_pct"} < names
    # the first expert layer's balance is read behind a dense block here
    assert "moe_imbalance" not in names
    body = cell.config
    assert sorted(body["reduced_from"]) == sorted(
        man["configs"][4]["reduced"])
    assert (body["hidden_size"], body["intermediate_size"],
            body["moe_intermediate_size"], body["num_experts_per_tok"],
            body["num_routed_experts"], body["conv_L_cache"],
            body["head_dim"]) == (2048, 11776, 1536, 4, 64, 3, 64)


def test_the_experts_cost_is_the_expected_share_of_the_rows():
    config, sizes = _published(5)
    need = tiny.cost_beside_the_metrics(
        "experts_cost.py", "experts_step_cost")(sizes, 2, config, 2)
    rows = 8192 * 4 * 8 // 64          # 4096 a microbatch
    assert need["flops"] == 4 * 2 * 3 * rows * 3 * 2 * 2048 * 1536
    assert need["bytes"] == 4 * 2 * 3 * (
        8 * 3 * 2048 * 1536 * 2 + rows * (2048 + 3072 + 1536 + 2048) * 2)
    least = flops.roofline_least_s(need, peaks.peaks_of("TPU v5 lite"))
    assert least["bound"] == "compute"


def test_every_file_the_benchmark_had_is_as_it_was():
    """Against the parent commit, where git and the commit are at hand:
    every data file it has under ``benchmark/`` is here byte for byte (what
    this PR brings under ``benchmark/`` are new files; the harness's own
    Python is a ``benchmark`` PR's to change, ``tiny.DATA_DIRS``)."""
    tiny.data_files_as_they_were_at(PARENT, 60)
