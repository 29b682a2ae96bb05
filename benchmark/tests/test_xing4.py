"""The ``xing4_0`` family, its configuration, its cell and its per-layer
metrics: a tiny model with every new part on (latent attention with YaRN,
four residual streams, a shared expert beside a held share of routed ones,
the multi-token block in the loss) through ``measure()`` on the CPU against
the plain reference, the family's FLOP count against a hand count at the
cut, ``attention_blocks``, the cell's own entries of the manifest, the
catalog row, the readers on a synthetic step map, and that every file the
benchmark had is as it was."""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import flops, manifest, readers, reference, run
from benchmark.tests import tiny

CELL, CONFIG = "xing4_c1_b1_s4k", "xing4.0-29b-a4b-ep8"
PARENT = "98a0ba2a3b5bdd9810e099af89ecc9a16f05536f"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 8,
        "type": "yarn"}
AS_RUN = ["latent_attention"] * 3

TINY_XING = {
    "first_k_dense_replace": 1, "hidden_size": 32, "intermediate_size": 48,
    "kv_lora_rank": 8, "model_type": "xing4_0", "moe_intermediate_size": 16,
    "n_routed_experts": 4, "num_routed_experts": 8, "first_expert_held": 2,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 3,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 12, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "rms_norm_eps": 1e-06, "rope_theta": 10000, "rope_scaling": YARN,
    "routed_scaling_factor": 2, "v_head_dim": 8, "vocab_size": 64,
    "layer_types_as_run": AS_RUN, "mtp_loss_lambda": 0.3,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(tiny.YAMLS, "xing4.0-29b-a4b.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=3",
            "model.layer_types=[" + ",".join(AS_RUN) + "]",
            "model.num_dense_layers=1", "model.num_attention_heads=4",
            "model.num_key_value_heads=4", "model.q_lora_rank=12",
            "model.kv_lora_rank=8", "model.qk_nope_head_dim=8",
            "model.qk_rope_head_dim=4", "model.v_head_dim=8",
            "model.ffn_hidden_size=48", "model.moe_ffn_hidden_size=16",
            "model.vocab_size=64", "model.make_vocab_size_divisible_by=1",
            "model.seq_length=16", "model.max_position_embeddings=64",
            "model.rope_scaling.original_max_position_embeddings=8",
            "model.num_experts=8", "model.moe_topk=2",
            "model.moe_held_experts=4", "model.moe_first_held_expert=2"],
        "equals": {"hidden_size": "hidden_size",
                   "layer_types": "layer_types_as_run",
                   "num_dense_layers": "first_k_dense_replace",
                   "q_lora_rank": "q_lora_rank",
                   "qk_rope_head_dim": "qk_rope_head_dim",
                   "v_head_dim": "v_head_dim", "rope_scaling": "rope_scaling",
                   "hc_mult": "hc_mult",
                   "num_nextn_predict_layers": "num_nextn_predict_layers",
                   "mtp_loss_coeff": "mtp_loss_lambda",
                   "num_experts": "num_routed_experts",
                   "moe_held_experts": "n_routed_experts",
                   "moe_first_held_expert": "first_expert_held",
                   "num_shared_experts": "n_shared_experts"},
        "expects": {"attention_cores": ["flash", "xla"],
                    "mosaic_calls_per_layer": 0}},
    "reference": {"family": "xing4_0", "depth_key": "num_hidden_layers",
                  "loss_tolerance": 0.02},
}


def _tiny_root(tmp_path):
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    tiny._add_config(root, man, "tiny-xing", TINY_XING)
    tiny._add_cell(man, "tiny_xing_c1", "tiny-xing", "tiny_c1_b2", 1)
    tiny._write(os.path.join(root, "BENCHMARK.json"), man)
    tiny.assert_nothing_that_was_there_is_edited(root)
    assert manifest.check_manifest(man, root) == []
    return root, manifest.resolve_cell(man, "tiny_xing_c1", root)


def test_a_tiny_xing_runs_and_meets_its_reference(tmp_path):
    """bf16 operands on the timed path against the float32 reference, the
    program's weights through its exporter under the public names."""
    root, cell = _tiny_root(tmp_path)
    line, report = run.measure(
        cell, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP, root=root,
        out_dir=str(tmp_path / "out"), expect_mosaic=False)
    checks = report["checks"]
    assert checks["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, checks
    assert len(report["attention_cores"]) == 3
    family = reference.load_family("xing4_0", root)
    sizes = flops.Sizes(layers=3, hidden=32, heads=4, kv_heads=4, head_dim=8,
                        ffn=48, ffn_matrices=3, vocab=64, seq=16, experts=8)
    sizes = sizes.with_attention(family.attention_blocks(cell.config))
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
    assert len(cell.config["program"]["equals"]) >= 39
    assert args.parallel.global_train_batch_size == 1
    assert args.parallel.chunks == 1
    return cell, flops.Sizes.of(args.model)


def test_the_family_adds_its_blocks_up_against_a_hand_count():
    cell, sizes = _published()
    family = reference.load_family("xing4_0")
    blocks = family.attention_blocks(cell.config)
    # five blocks of the stack at q/k 192 and v 128; the multi-token block's
    # core rides the last entry as a second block's worth of heads, because
    # Sizes.with_attention takes no more entries than num_hidden_layers
    assert blocks == [{"qk_head_dim": 192, "v_head_dim": 128}] * 4 + [
        {"qk_head_dim": 192, "v_head_dim": 128, "heads": 64, "kv_heads": 64}]
    sizes = sizes.with_attention(blocks)
    assert (sizes.layers, sizes.seq, sizes.vocab, sizes.hidden) == (
        5, 4096, 16384, 3584)
    H, S = 3584, 4096
    proj_weights = (H * 768 + 768 * 32 * 192 + H * (512 + 64)
                    + 512 * 32 * (128 + 128) + 32 * 128 * H)
    assert proj_weights == 28_409_856            # the issue's 28.41M a block
    proj = 2 * proj_weights
    core = 2 * 32 * (192 + 128) * (S + 1) // 2   # a block, a token
    maps = 2 * 2 * (4 * H) * (4 + 4 + 16)
    dense = 2 * 3 * H * 9216
    expert = 2 * 3 * H * 1024
    sparse = 2 * H * 64 + (4 * 8 / 64) * expert + expert
    head = 2 * H * 16384
    eh_proj = 2 * (2 * H) * H
    stack = 5 * (proj + maps) + 5 * core + dense + 4 * sparse + head
    further = eh_proj + proj + maps + sparse + head
    forward = family.forward_flops_per_token(sizes, cell.config)
    assert forward == pytest.approx(
        stack + core + (S - 1) / S * further, rel=1e-12)
    # the issue's shares: 1.25 GFLOPs a token forward, a dense block 298
    # MFLOPs, an expert block 134, each head 117, eh_proj 51, a core 42
    assert round(forward / 1e9, 2) == 1.25
    assert round((proj + maps + core + dense) / 1e6) == 298
    assert round((proj + maps + core + sparse) / 1e6) == 134
    assert (round(head / 1e6), round(eh_proj / 1e6), round(core / 1e6)) == (
        117, 51, 42)
    # the six cores are a fifth of the model's FLOPs; full-rank q, k and v
    # projections would have counted 147 MFLOPs a block where there are 57
    assert round(100 * 6 * core / forward) == 20
    assert round(proj / 1e6) == 57
    assert round((flops.attention_flops_per_token(
        sizes, flops.Attention(qk_head_dim=192, v_head_dim=128)) - core)
        / 1e6) == 147
    # the kernels' cost reads six blocks' worth of heads
    cost = flops.flash_step_cost(sizes, 1)
    assert cost["flops"] == 2 * 6 * 32 * (4 * 192 + 3 * 128) * S * (S + 1) / 2
    # ... and the doubled entry stands for two honest ones only while a
    # reader is linear in an entry's heads: six entries of the model's own
    # heads, which the harness would take of a stack of six blocks, cost the
    # same operations AND bytes. A reader that derives a group or a tile
    # from an entry has to fail here first
    six = dataclasses.replace(sizes, layers=6).with_attention(
        [{"qk_head_dim": 192, "v_head_dim": 128}] * 6)
    assert flops.flash_step_cost(six, 1) == cost
    assert family.forward_flops_per_token(six, cell.config) == forward


def test_the_experts_cost_counts_the_rows_held_against_a_hand_count():
    """``experts_roofline``'s operations and bytes here: four expert blocks
    of the stack over 4096 positions and the further depth's over 4095, a
    half of a route a position on the eight held of 64 (top-4)."""
    cell, sizes = _published()
    cost = tiny.cost_beside_the_metrics("experts_cost.py",
                                        "experts_step_cost")
    rows = (4 * 4096 + 4095) * 4 * 8 / 64
    assert rows == 10239.5
    matrices = 5 * 8 * 3 * 3584 * 1024 * 2          # bf16, every held expert
    row_bytes = rows * (3584 + 2 * 1024 + 1024 + 3584) * 2
    assert cost(sizes, 1, cell.config, 1) == {
        "flops": 3 * rows * 3 * 2 * 3584 * 1024,
        "bytes": 3 * (matrices + row_bytes)}
    two = cost(sizes, 2, cell.config, 2)
    assert two["flops"] == 2 * 3 * rows * 3 * 2 * 3584 * 1024
    assert two["bytes"] == 2 * 3 * (matrices + row_bytes)
    # the family's FLOP count takes the same share of the routes
    assert 3 * rows * 3 * 2 * 3584 * 1024 == pytest.approx(
        3 * (4 * 4096 + 4095) * (4 * 8 / 64) * 2 * 3 * 3584 * 1024)
    # which key is which is the configuration file's to say
    assert cell.config["reference"]["experts"] == {
        "held": "n_routed_experts", "routed": "num_routed_experts",
        "per_token": "num_experts_per_tok", "width": "moe_intermediate_size",
        "dense_blocks": "first_k_dense_replace",
        "further_depths": "num_nextn_predict_layers"}
    assert any(m["name"] == "local_routes_pct" and CELL in m["workloads"]
               and m["source"] == "program_counter"
               for m in manifest.load_manifest()["per_layer"])


def test_the_fall_back_describes_five_cores():
    cell, _ = _published()
    family = reference.load_family("xing4_0")
    cut = {**cell.config, "num_nextn_predict_layers": 0}
    assert family.attention_blocks(cut) == [
        {"qk_head_dim": 192, "v_head_dim": 128}] * 5


def test_the_cells_own_entries_of_the_manifest():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    (work,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "c1_b1_s4k", 1)
    assert man["workloads"][7]["name"] == CELL
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4][:1] == [
        "mistral7b_c4_tp2dp2z3"]
    (entry,) = [c for c in man["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "n_routed_experts", "vocab_size"]
    # the set it lists, the shared entries among it (PR 65): where an entry
    # stands in the list says nothing
    assert tiny.listed_for(man, CELL) == {
        "xing_hc_ms", "xing_hc_time_share_pct", "xing_mtp_ms",
        "xing_moe_imbalance", "latent_proj_ms", "mlp_ms", "experts_ms",
        "experts_time_share_pct", "experts_roofline", "local_routes_pct",
        "moe_route_ms", "moe_dispatch_ms", "moe_combine_ms"}
    assert all(m["moves"] == "tokens_per_s" for m in man["per_layer"]
               if CELL in m.get("workloads", ()))
    cell = manifest.resolve_cell(man, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"flash_roofline", "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms",
            "static_hbm_GiB", "device_idle_pct", "attn_proj_ms", "head_ms",
            "mlp_ms", "moe_route_ms", "moe_dispatch_ms",
            "moe_combine_ms", "scope_unnamed_pct"} < names
    assert not names & {"moe_imbalance", "collective_all_ms",
                        "granite_ssd_ms", "window_core_ms"}
    assert cell.traffic["overrides"] == [
        "data.dataset=random", "parallel.mixed_precision=bf16",
        "parallel.global_checkpoint=1",
        "parallel.global_train_batch_size=1", "parallel.chunks=1",
        "model.seq_length=4096", "train.lr_warmup_iters=2000"]
    body = cell.config
    assert body["reduced_from"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "vocab_size": 131072}
    assert body["program"]["expects"]["attention_cores"] == ["flash"]
    assert 0 < body["reference"]["loss_tolerance"] < 5e-3
    assert "913,473,668 parameters" in body["deployment"]


def test_the_configuration_holds_every_number_of_the_catalog_row():
    """Every key of the catalog's ``config`` under the same key with the
    same value, but the four that ``reduced`` lists; no width among them."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Xing4.0-29B-A4B"]
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    body, reduced = cell.config, set(cell.config["reduced_from"])
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert body[key] == value, key
        else:
            assert body["reduced_from"][key] == value, key
            assert not manifest.WIDTH_RE.search(key), key
    assert (body["num_hidden_layers"], body["first_k_dense_replace"],
            body["n_routed_experts"], body["vocab_size"]) == (5, 1, 8, 16384)
    assert body["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert body["num_routed_experts"] == row["config"]["n_routed_experts"]


def _facts(leaves, steps, busy_s):
    reduced = SimpleNamespace(leaves=leaves, steps=steps, periods=len(steps),
                              busy_s=busy_s)
    return {"trace": {"reduced": [reduced]}}


def test_the_readers_on_a_synthetic_step_map(monkeypatch):
    """Two traced steps of five instructions each laid over a map the
    program would have kept: the scope readers by each instruction's
    deepest scope, the further depth's by containment."""
    from hetu_galvatron_tpu.observability import trace_analysis

    scopes = manifest.load_python(os.path.join(
        manifest.ROOT, "benchmark", "layer_metrics", "xing_scopes.py"))
    instructions = {
        "fusion.1": ("attn/latent_proj", "forward", None),
        "fusion.2": ("hc/maps", "forward", None),
        "fusion.3": ("hc/mix", "backward", None),
        "fusion.4": ("hc/mix", "forward", None),      # the further depth's
        "flash_attention_fwd.6": ("attn/core", "forward", None),  # likewise
        "fusion.5": ("mtp/embed_proj", "forward", None)}
    kept = {"map": {"instructions": instructions, "inferred": [],
                    "tails": {}},
            "scopes": {"mtp/embed_proj": ["fusion.5"],
                       "mtp/block": ["fusion.4", "flash_attention_fwd.6"],
                       "mtp/head": ["fusion.9"]}}
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: kept)
    ms = 1_000_000
    step = lambda t0: [("fusion.1", t0, t0 + 3 * ms),
                       ("fusion.2", t0 + 3 * ms, t0 + 5 * ms),
                       ("fusion.3", t0 + 5 * ms, t0 + 9 * ms),
                       ("fusion.4", t0 + 9 * ms, t0 + 10 * ms),
                       ("flash_attention_fwd.6", t0 + 10 * ms, t0 + 12 * ms),
                       ("fusion.5", t0 + 12 * ms, t0 + 13 * ms)]
    facts = _facts(step(0) + step(20 * ms),
                   [(0, 13 * ms), (20 * ms, 33 * ms)], busy_s=0.026)
    assert readers.read_metric("latent_proj_ms", facts) == 3.0
    assert scopes.hc_ms(facts) == 2.0 + 4.0 + 1.0
    assert scopes.hc_time_share_pct(facts) == pytest.approx(100 * 7 / 13)
    assert scopes.mtp_ms(facts) == 1.0 + 2.0 + 1.0
    # a program that kept no such lists (the parent commit) publishes
    # nothing and does not raise; neither does a run without a trace
    monkeypatch.setattr(trace_analysis, "step_scopes",
                        lambda: {"map": kept["map"], "scopes": {}})
    facts.pop("step_map_join", None)
    assert scopes.mtp_ms(facts) is None
    assert scopes.hc_ms(facts) == 7.0
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: {})
    assert scopes.hc_ms(_facts([], [], 0.0)) is None
    assert scopes.mtp_ms({}) is None
    assert readers.read_metric("latent_proj_ms", {}) is None


def test_every_file_the_benchmark_had_is_as_it_was():
    """Against the parent commit, where git and the commit are at hand:
    every data file it has under ``benchmark/`` is here byte for byte (what
    this PR brings under ``benchmark/`` are new files; the harness's own
    Python is a ``benchmark`` PR's to change, ``tiny.DATA_DIRS``)."""
    tiny.data_files_as_they_were_at(PARENT, 80)
