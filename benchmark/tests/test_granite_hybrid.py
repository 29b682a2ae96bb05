"""The ``granite_hybrid`` family, its configuration, its cell and its
per-layer metrics: a tiny Granite hybrid (Mamba-2 blocks and an attention
block without positions, the four multipliers) through ``measure()`` on the
CPU against the sequential-recurrence reference, the family's FLOP count as
exact integers at the published widths, ``attention_blocks``, the cell's
own entries of the manifest (not its length), the catalog row, and that
every file the benchmark had is as it was."""

import json
import os

import pytest

from benchmark import flops, manifest, peaks, reference, run
from benchmark.tests import tiny

CELL, CONFIG = "granite4h_c1_b1", "granite-4.0-h-micro-p1"
PARENT = "ca9ddbd8560dafd5bf157b0f372fd52060ad40b9"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TYPES = ["mamba", "mamba", "attention", "mamba"]
AS_RUN = ["mamba", "mamba", "full_attention", "mamba"]

TINY_GRANITE = {
    "attention_multiplier": 0.2, "embedding_multiplier": 12,
    "hidden_size": 32, "layer_types": TYPES, "layer_types_as_run": AS_RUN,
    "logits_scaling": 8, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_n_heads": 8, "model_type": "granitemoehybrid",
    "num_attention_heads": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "shared_intermediate_size": 48, "tie_word_embeddings": True,
    "vocab_size": 64,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(tiny.YAMLS, "granite-4.0-h-micro.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=4",
            "model.layer_types=[" + ",".join(AS_RUN) + "]",
            "model.num_attention_heads=4", "model.num_key_value_heads=2",
            "model.ffn_hidden_size=48", "model.vocab_size=64",
            "model.make_vocab_size_divisible_by=1", "model.seq_length=20",
            "model.max_position_embeddings=32", "model.mamba_n_heads=8",
            "model.mamba_d_head=8", "model.mamba_d_state=16",
            "model.mamba_chunk_size=8", "model.attention_multiplier=0.2"],
        "equals": {"hidden_size": "hidden_size",
                   "layer_types": "layer_types_as_run",
                   "ffn_hidden_size": "shared_intermediate_size",
                   "attention_multiplier": "attention_multiplier",
                   "embedding_multiplier": "embedding_multiplier",
                   "residual_multiplier": "residual_multiplier",
                   "logits_scaling": "logits_scaling",
                   "mamba_n_heads": "mamba_n_heads",
                   "mamba_d_state": "mamba_d_state",
                   "mamba_chunk_size": "mamba_chunk_size",
                   "position_embedding_type": "position_embedding_type"},
        "expects": {"attention_cores": ["flash", "xla", "mamba2"],
                    "mosaic_calls_per_layer": 0}},
    "reference": {"family": "granite_hybrid",
                  "depth_key": "num_hidden_layers", "loss_tolerance": 0.02},
}


def _tiny_root(tmp_path):
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    tiny._add_config(root, man, "tiny-granite", TINY_GRANITE)
    tiny._add_cell(man, "tiny_granite_c1", "tiny-granite", "tiny_c1", 1)
    tiny._write(os.path.join(root, "BENCHMARK.json"), man)
    tiny.assert_nothing_that_was_there_is_edited(root)
    assert manifest.check_manifest(man, root) == []
    return root, manifest.resolve_cell(man, "tiny_granite_c1", root)


def test_a_tiny_granite_runs_and_meets_its_reference(tmp_path):
    """A sequence of 20 in chunks of 8: the program's chunked recurrence
    (bf16 operands) against the reference's one position at a time."""
    root, cell = _tiny_root(tmp_path)
    line, report = run.measure(
        cell, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP, root=root,
        out_dir=str(tmp_path / "out"), expect_mosaic=False)
    checks = report["checks"]
    assert checks["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, checks
    assert report["attention_cores"].count("mamba2") == 3
    family = reference.load_family("granite_hybrid", root)
    sizes = flops.Sizes(layers=4, hidden=32, heads=4, kv_heads=2, head_dim=8,
                        ffn=48, ffn_matrices=3, vocab=64, seq=20)
    sizes = sizes.with_attention(family.attention_blocks(cell.config))
    assert len(sizes.attention_blocks()) == 1
    assert report["train_flops_per_token"] == 3 * \
        family.forward_flops_per_token(sizes, cell.config)


def _published():
    """The cell, and the program's sizes from the cell's own command line
    after every ``program.equals`` pair was checked."""
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    args = resolve_model_config(args_from_cli(
        manifest.train_argv(cell, seed=0), mode="train_dist"))
    for attr, key in cell.config["program"]["equals"].items():
        assert getattr(args.model, attr) == cell.config[key], attr
    assert args.parallel.global_train_batch_size == 1
    assert args.parallel.chunks == 1
    return cell, flops.Sizes.of(args.model)


def test_the_family_adds_its_blocks_up_by_kind():
    cell, sizes = _published()
    family = reference.load_family("granite_hybrid")
    blocks = family.attention_blocks(cell.config)
    assert blocks == [{}]
    sizes = sizes.with_attention(blocks)
    assert (sizes.layers, sizes.seq, sizes.vocab) == (10, 8192, 12544)
    mlp = 2 * 3 * 2048 * 8192
    mamba = 2 * 2048 * (2 * 4096 + 2 * 128 + 64) + 2 * 4096 * 2048
    scan = 4 * 64 * 128 * 64
    attention = (2 * 2048 * (32 + 2 * 8) * 64 + 2 * 32 * 64 * 2048
                 + 2 * 2 * 32 * 64 * (8192 + 1) // 2)
    head = 2 * 2048 * 12544
    forward = family.forward_flops_per_token(sizes, cell.config)
    assert forward == (9 * (mamba + scan) + attention + 10 * mlp
                       + head) == 1_596_198_912
    # the issue's "about 4.8 GFLOP a token trained": the projections of
    # the nine mamba blocks 29 %, the ten MLPs 63 %, the recurrence 1.2 %
    assert round(3 * forward / 1e9, 1) == 4.8
    assert round(100 * 9 * mamba / forward) == 29
    assert round(100 * 10 * mlp / forward) == 63
    assert round(1000 * 9 * scan / forward) == 12


def test_the_cells_own_entries_of_the_manifest():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    (work,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "c1_b1_s8k", 1)
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4][:1] == [
        "mistral7b_c4_tp2dp2z3"]
    (entry,) = [c for c in man["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "vocab_size"]
    mine = [m for m in man["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "granite_ssd_ms", "granite_ssd_time_share_pct",
        "granite_ssd_roofline", "granite_mamba_ms"]
    assert all(m["moves"] == "tokens_per_s"
               and m["layer"] == "state-space blocks"
               and m["source"] == "device_trace" for m in mine)
    cell = manifest.resolve_cell(man, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"flash_roofline", "flash_fwd_ms", "static_hbm_GiB",
            "device_idle_pct", "gap_sync_ms"} < names
    assert not names & {"experts_ms", "lfm2_experts_ms", "collective_ms"}
    assert cell.traffic["overrides"] == [
        "data.dataset=random", "parallel.mixed_precision=bf16",
        "parallel.global_checkpoint=1",
        "parallel.global_train_batch_size=1", "parallel.chunks=1",
        "model.seq_length=8192"]
    body = cell.config
    assert sorted(body["reduced_from"]) == sorted(entry["reduced"])
    assert body["reduced_from"]["num_hidden_layers"] == 40
    assert body["reduced_from"]["vocab_size"] == 100352
    assert body["program"]["expects"]["attention_cores"] == ["flash",
                                                             "mamba2"]
    assert 0 < body["reference"]["loss_tolerance"] < 1e-3


def test_the_configuration_holds_every_number_of_the_catalog_row():
    """Every key of the catalog's ``config`` under the same key with the
    same value, but the three that ``reduced`` lists; ``layer_types`` is
    the published list's first ten."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "granite-4.0-h-micro"]
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    body, reduced = cell.config, set(cell.config["reduced_from"])
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert body[key] == value, key
    assert body["layer_types"] == row["config"]["layer_types"][:10]
    assert body["layer_types"].count("attention") == 1
    assert (body["num_hidden_layers"], body["vocab_size"]) == (10, 12544)
    assert body["vocab_size"] * 8 == row["config"]["vocab_size"]


def test_the_scan_cost_and_its_bound():
    cost = manifest.load_python(os.path.join(
        manifest.ROOT, "benchmark", "layer_metrics", "granite_ssd_cost.py"))
    _, sizes = _published()
    need = cost.granite_ssd_step_cost(sizes, 1)
    assert need["flops"] == 9 * 8192 * 3 * (
        2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 64 * 128))
    assert need["bytes"] == 9 * 8192 * 3 * (
        (3 * 4096 + 2 * 128) * 2 + 4 * 64)
    least = flops.roofline_least_s(need, peaks.peaks_of("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert 6e-3 < least["least_s"] < 8e-3


def test_every_file_the_benchmark_had_is_as_it_was():
    """Against the parent commit, where git and the commit are at hand:
    every data file it has under ``benchmark/`` is here byte for byte (what
    this PR brings under ``benchmark/`` are new files; the harness's own
    Python is a ``benchmark`` PR's to change, ``tiny.DATA_DIRS``)."""
    tiny.data_files_as_they_were_at(PARENT, 70)
