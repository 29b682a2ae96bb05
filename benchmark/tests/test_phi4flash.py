"""The ``phi4flash`` family, its configuration and its cell: a tiny stack of all five kinds (Mamba-1, window and full
differential attention, a gated memory unit, a cross-attention) through
``measure()`` on the CPU against the reference that scans one position at a
time and computes the two softmax maps apart, the family's FLOP count by
hand at the published widths, ``attention_blocks`` through ``Sizes``, the
cell's own entries of the manifest, the catalog row, and the cell's compile
for a described chip."""

import json
import os

import pytest

from benchmark import flops, manifest, reference, run
from benchmark.tests import tiny

CELL, CONFIG = "phi4flash_c1_b1", "phi-4-mini-flash-d6"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TYPES = ["mamba1", "sliding_attention", "mamba1", "full_attention", "gmu",
         "cross_attention"]

TINY_PHI = {
    "hidden_size": 32, "intermediate_size": 48, "layer_norm_eps": 1e-05,
    "model_type": "phi4flash", "num_attention_heads": 8,
    "num_hidden_layers": 6, "num_key_value_heads": 4, "sliding_window": 6,
    "tie_word_embeddings": True, "vocab_size": 64, "layer_types": TYPES,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 2, "differential_attention": True,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(tiny.YAMLS, "phi-4-mini-flash.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=6",
            "model.layer_types=[" + ",".join(TYPES) + "]",
            "model.num_attention_heads=8", "model.num_key_value_heads=4",
            "model.ffn_hidden_size=48", "model.vocab_size=64",
            "model.make_vocab_size_divisible_by=1", "model.seq_length=20",
            "model.max_position_embeddings=32", "model.sliding_window=6",
            "model.mamba1_dt_rank=2"],
        "equals": {"hidden_size": "hidden_size", "layer_types": "layer_types",
                   "ffn_hidden_size": "intermediate_size",
                   "sliding_window": "sliding_window",
                   "differential_attention": "differential_attention",
                   "mamba1_d_state": "mamba_d_state",
                   "mamba1_rank": "mamba_dt_rank"},
        "expects": {"attention_cores": ["flash", "xla", "xla[w6]", "mamba1",
                                        "gmu"],
                    "mosaic_calls_per_layer": 0}},
    "reference": {"family": "phi4flash", "depth_key": "num_hidden_layers",
                  "loss_tolerance": 0.02},
}


def _tiny_root(tmp_path):
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    tiny._add_config(root, man, "tiny-phi", TINY_PHI)
    tiny._add_cell(man, "tiny_phi_c1", "tiny-phi", "tiny_c1", 1)
    tiny._write(os.path.join(root, "BENCHMARK.json"), man)
    tiny.assert_nothing_that_was_there_is_edited(root)
    assert manifest.check_manifest(man, root) == []
    return root, manifest.resolve_cell(man, "tiny_phi_c1", root)


def test_a_tiny_stack_runs_and_meets_its_reference(tmp_path):
    """A sequence of 20 in chunks of 8 under a window of 6: the program
    (bf16 operands, the scan in chunks, one core call a block) against the
    reference, through the harness's own ``measure()``."""
    root, cell = _tiny_root(tmp_path)
    line, report = run.measure(
        cell, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP, root=root,
        out_dir=str(tmp_path / "out"), expect_mosaic=False)
    checks = report["checks"]
    assert checks["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, checks
    assert report["attention_cores"] == [
        "mamba1", "xla[w6]", "mamba1", "xla", "gmu", "xla"]
    family = reference.load_family("phi4flash", root)
    sizes = flops.Sizes(layers=6, hidden=32, heads=8, kv_heads=4, head_dim=4,
                        ffn=48, ffn_matrices=3, vocab=64, seq=20)
    sizes = sizes.with_attention(family.attention_blocks(cell.config))
    assert len(sizes.attention_blocks()) == 3
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
    family = reference.load_family("phi4flash")
    blocks = family.attention_blocks(cell.config)
    core = {"heads": 40, "kv_heads": 20, "qk_head_dim": 64,
            "v_head_dim": 128}
    assert blocks == [{**core, "window": 512}, core, core]
    sizes = sizes.with_attention(blocks)
    assert (sizes.layers, sizes.seq, sizes.vocab) == (6, 8192, 25008)
    mlp = 2 * 3 * 2560 * 10240
    mamba = 2 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
    gmu = 2 * 2 * 2560 * 5120
    maps = 2 * 2560 * (2560 + 2 * 1280) + 2 * 2560 * 2560
    cross = 2 * 2 * 2560 * 2560
    whole = 2 * 40 * (64 + 128) * (8192 + 1) // 2
    band = 2 * 40 * (64 + 128) * (512 * 513 // 2 + (8192 - 512) * 512) / 8192
    head = 2 * 2560 * 25008
    forward = family.forward_flops_per_token(sizes, cell.config)
    assert forward == (2 * mamba + gmu + 2 * maps + cross + 2 * whole + band
                       + 6 * mlp + head)
    # the issue's "about 1,527 M": the MLPs 62 %, the Mamba maps 11 %, the
    # three cores 9 %, the head 8 %
    assert round(forward / 1e6) == 1527
    assert round(100 * 6 * mlp / forward) == 62
    assert round(100 * 2 * mamba / forward) == 11
    assert round(100 * (2 * whole + band) / forward) == 9
    assert round(100 * head / forward) == 8
    # what flops.attention_flops_per_token would add for the same entries:
    # v and out at the core's doubled width, k and v maps for the cross block
    generic = sum(flops.attention_flops_per_token(sizes, e)
                  for e in sizes.attention_blocks())
    assert generic > 2 * maps + cross + 2 * whole + band


def test_flash_roofline_counts_the_pairs_value_as_the_kernels_read_it():
    """``flops.flash_step_cost`` over the three entries: v and o at the 128
    the kernels read and write (the pair's value handed to both of its key
    heads: twice the bytes the projection made, and what the core moves)."""
    cell, sizes = _published()
    family = reference.load_family("phi4flash")
    sizes = sizes.with_attention(family.attention_blocks(cell.config))
    cost = flops.flash_step_cost(sizes, 1)
    io = 8192 * (40 * 64 + 20 * 64 + 20 * 128 + 40 * 128)
    assert cost["bytes"] == 3 * (3 * io * 2 + 2 * 8192 * 40 * 4)
    pairs = 2 * 8192 * 8193 // 2 + 512 * 513 // 2 + (8192 - 512) * 512
    assert cost["flops"] == 2 * 40 * (4 * 64 + 3 * 128) * pairs


def test_the_cells_own_entries_of_the_manifest():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    (work,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "c1_b1_s8k", 1)
    (entry,) = [c for c in man["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # the manifest held the contract's 128 per-layer metrics when the cell
    # came, so it was appended to ``mlp_ms`` alone; PR 65 made room (one
    # entry a reader) and listed the readers of its blocks' scopes, its
    # fall-back gauge, and the cell in the shared cores' entries
    assert len(man["per_layer"]) <= 100
    assert tiny.listed_for(man, CELL) == {
        "mlp_ms", "selective_scan_ms", "selective_scan_time_share_pct",
        "selective_scan_roofline", "mamba1_mixer_ms", "gmu_ms",
        "attn_diff_ms", "cross_core_ms", "window_core_ms", "full_core_ms",
        "scan_mosaic_calls"}
    cell = manifest.resolve_cell(man, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"flash_roofline", "flash_fwd_ms", "static_hbm_GiB",
            "device_idle_pct", "scope_unnamed_pct", "attn_proj_ms",
            "head_ms", "phase_recompute_ms", "mlp_ms"} < names
    assert not names & {"experts_ms", "granite_ssd_ms", "collective_all_ms",
                        "window_roofline"}
    assert cell.traffic["overrides"] == [
        "data.dataset=random", "parallel.mixed_precision=bf16",
        "parallel.global_checkpoint=1",
        "parallel.global_train_batch_size=1", "parallel.chunks=1",
        "model.seq_length=8192"]
    body = cell.config
    assert sorted(body["reduced_from"]) == sorted(entry["reduced"])
    assert body["reduced_from"]["num_hidden_layers"] == 32
    assert body["reduced_from"]["vocab_size"] == 200064
    assert body["layer_types"] == TYPES
    assert body["program"]["expects"]["attention_cores"] == [
        "flash", "flash[w512]", "mamba1", "gmu"]
    assert 0 < body["reference"]["loss_tolerance"] < 1e-2
    # four-chip cells: two of thirteen, under the cap of 13 // 4
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 2
    assert len(man["workloads"]) == 13 and len(man["configs"]) == 12


def test_the_configuration_holds_every_number_of_the_catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Phi-4-mini-flash-reasoning"]
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    body, reduced = cell.config, set(cell.config["reduced_from"])
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert body[key] == value, key
    assert (body["num_hidden_layers"], body["vocab_size"]) == (6, 25008)
    assert body["vocab_size"] * 8 == row["config"]["vocab_size"]
    # the kinds as run are the adapter's published blocks 14 to 19
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        phi4flash_layer_types,
    )

    assert phi4flash_layer_types(32)[14:20] == body["layer_types"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_the_cell_compiles_for_a_described_chip_and_fits(topo):
    """``aot_check.py``'s own compile of the cell (about a minute and a
    half): the parameters the file states, the Mosaic calls of three flash
    cores and the convolution's kernels, and a live peak under 16 GiB."""
    from benchmark import aot_check

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    rep = aot_check.compile_cell(cell, topo.devices)
    assert rep["parameters"] == 697_299_072     # 80 padding rows among them
    assert rep["tokens_per_step"] == 8192
    assert rep["mosaic_custom_calls"] >= 2.5 * 6
    assert rep["per_device_GiB"]["live_peak"] * aot_check.GiB <= \
        aot_check.HBM_BYTES
