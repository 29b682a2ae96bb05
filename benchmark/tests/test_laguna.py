"""The ``laguna`` family, its configuration, its cell and its per-layer
metrics: a tiny model with every new part on (window and full attention
blocks with query heads, a rotation and a gate a head of their own, a dense
block, a shared expert beside a held share of routed ones) through
``measure()`` on the CPU against the plain reference, the family's FLOP
count against a hand count at the cut, ``attention_blocks``, the cell's own
entries of the manifest, the catalog row, the readers on a synthetic step
map, the two cost functions against hand counts, and that every file the
benchmark had is as it was."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import flops, manifest, readers, reference, run
from benchmark.tests import tiny

CELL, CONFIG = "laguna_c1_b1", "laguna-s-2.1-ep32"
PARENT = "f5bc5946ce6afa5df0135b255e1b983b0440d9c1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
AS_RUN = ["full_attention", "sliding_attention", "sliding_attention",
          "sliding_attention", "full_attention"]
METRICS = os.path.join(manifest.ROOT, "benchmark", "layer_metrics")
ROPE = {
    "full_attention": {
        "rope_theta": 100.0, "rope_type": "yarn", "factor": 8,
        "original_max_position_embeddings": 8, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.2,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10.0,
                          "partial_rotary_factor": 1}}

TINY_LAGUNA = {
    "model_type": "laguna", "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 5, "layer_types": AS_RUN,
    "num_attention_heads": 4, "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "num_key_value_heads": 2, "head_dim": 8, "sliding_window": 4,
    "gating": "per-head", "rope_parameters": ROPE, "rms_norm_eps": 1e-06,
    "mlp_only_layers": [0], "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "num_experts": 4,
    "num_routed_experts": 8, "first_expert_held": 2,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5, "vocab_size": 64,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(tiny.YAMLS, "laguna-s-2.1.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=5",
            "model.layer_types=[" + ",".join(AS_RUN) + "]",
            "model.num_attention_heads=4",
            "model.num_attention_heads_per_layer=[4,6,6,6,4]",
            "model.num_key_value_heads=2", "model.head_dim_override=8",
            "model.sliding_window=4",
            "model.rope_parameters=" + json.dumps(ROPE).replace(" ", ""),
            "model.ffn_hidden_size=48", "model.moe_ffn_hidden_size=16",
            "model.vocab_size=64", "model.make_vocab_size_divisible_by=1",
            "model.seq_length=16", "model.max_position_embeddings=64",
            "model.num_experts=8", "model.moe_topk=3",
            "model.moe_held_experts=4", "model.moe_first_held_expert=2"],
        "equals": {"hidden_size": "hidden_size", "layer_types": "layer_types",
                   "num_attention_heads_per_layer":
                       "num_attention_heads_per_layer",
                   "sliding_window": "sliding_window", "gating": "gating",
                   "rope_parameters": "rope_parameters",
                   "num_experts": "num_routed_experts",
                   "moe_held_experts": "num_experts",
                   "moe_first_held_expert": "first_expert_held"},
        "expects": {"attention_cores": ["flash", "xla", "flash[w4]",
                                        "xla[w4]"],
                    "mosaic_calls_per_layer": 0}},
    "reference": {"family": "laguna", "depth_key": "num_hidden_layers",
                  "loss_tolerance": 0.02},
}


def _tiny_root(tmp_path):
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    tiny._add_config(root, man, "tiny-laguna", TINY_LAGUNA)
    tiny._add_cell(man, "tiny_laguna_c1", "tiny-laguna", "tiny_c1_b2", 1)
    tiny._write(os.path.join(root, "BENCHMARK.json"), man)
    tiny.assert_nothing_that_was_there_is_edited(root)
    assert manifest.check_manifest(man, root) == []
    return root, manifest.resolve_cell(man, "tiny_laguna_c1", root)


def test_a_tiny_laguna_runs_and_meets_its_reference(tmp_path):
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
    assert report["attention_cores"] == ["xla", "xla[w4]", "xla[w4]",
                                         "xla[w4]", "xla"]
    family = reference.load_family("laguna", root)
    sizes = flops.Sizes(layers=5, hidden=32, heads=4, kv_heads=2, head_dim=8,
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
    assert len(cell.config["program"]["equals"]) >= 30
    assert args.parallel.global_train_batch_size == 1
    assert args.parallel.chunks == 1
    assert args.train.lr_warmup_iters == 2000
    sizes = flops.Sizes.of(args.model)
    family = reference.load_family("laguna")
    return cell, sizes.with_attention(family.attention_blocks(cell.config))


def test_the_family_adds_its_blocks_up_against_a_hand_count():
    cell, sizes = _published()
    family = reference.load_family("laguna")
    assert family.attention_blocks(cell.config) == [
        {"heads": 48}, {"heads": 72, "window": 512},
        {"heads": 72, "window": 512}, {"heads": 72, "window": 512},
        {"heads": 48}]
    assert (sizes.layers, sizes.seq, sizes.vocab, sizes.hidden,
            sizes.kv_heads, sizes.head_dim) == (5, 8192, 12544, 3072, 8, 128)
    H, S = 3072, 8192
    full_weights = H * 6144 + 2 * H * 1024 + 6144 * H + H * 48
    window_weights = H * 9216 + 2 * H * 1024 + 9216 * H + H * 72
    assert (full_weights, window_weights) == (44_187_648, 63_135_744)
    full_core = 2 * 48 * 256 * (S + 1) / 2
    band = 512 * 513 // 2 + (S - 512) * 512
    assert band == flops.causal_pairs(S, 512) == 4_063_488
    window_core = 2 * 72 * 256 * band / S
    dense = 2 * 3 * H * 12288
    expert = 2 * 3 * H * 1024
    sparse = 2 * H * 256 + (10 * 8 / 256) * expert + expert
    head = 2 * H * 12544
    forward = family.forward_flops_per_token(sizes, cell.config)
    assert forward == pytest.approx(
        2 * (2 * full_weights + full_core)
        + 3 * (2 * window_weights + window_core) + dense + 4 * sparse + head,
        rel=1e-12)
    # the issue's figures: 1,220.7 MFLOPs a token forward at 8192:
    # projections and gates 555.6, the two full cores 201.4, the three
    # banded cores 54.9 (unbanded 453.0), block 0's MLP 226.5, routers,
    # shared and held experts 105.4, the head 77.1; 30.0 TFLOPs a step
    assert round(forward / 1e6, 1) == 1220.7
    assert round(2 * (2 * full_weights + 3 * window_weights) / 1e6, 1) \
        == 555.6
    assert round(2 * full_core / 1e6, 1) == 201.4
    assert round(3 * window_core / 1e6, 1) == 54.9
    assert round(3 * 2 * 72 * 256 * (S + 1) / 2 / 1e6, 1) == 453.0
    assert round(dense / 1e6, 1) == 226.5
    assert round(4 * sparse / 1e6, 1) == 105.4
    assert round(head / 1e6, 1) == 77.1
    assert round(3 * forward * S / 1e12, 1) == 30.0
    # the kernels' cost reads the five entries: the band in three of them
    assert flops.flash_step_cost(sizes, 1)["flops"] == (
        2 * 7 * 128 * (2 * 48 * S * (S + 1) / 2 + 3 * 72 * band))


def test_the_window_cost_counts_the_band_against_a_hand_count():
    """``window_roofline``'s operations and bytes here: three blocks of
    72 heads over the band of 512, seven matmuls, q, k, v, o three times."""
    _, sizes = _published()
    cost = manifest.load_python(os.path.join(
        METRICS, "window_cost.py")).window_step_cost
    band = 4_063_488
    io = 8192 * (72 * 128 + 2 * 8 * 128 + 72 * 128)
    got = cost(sizes, 1)
    assert got == {"flops": 3 * 2 * 72 * 7 * 128 * band,
                   "bytes": 3 * (3 * io * 2 + 2 * 8192 * 72 * 4)}
    assert cost(sizes, 2)["flops"] == 2 * got["flops"]
    # compute-bound on a v5e: 8.0 ms for the three blocks
    least = flops.roofline_least_s(
        got, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "compute"
    assert round(1e3 * least["least_s"], 1) == 8.0
    # a model without a window block costs nothing here
    from dataclasses import replace
    assert cost(replace(sizes, attention=None), 1) == {"flops": 0.0,
                                                       "bytes": 0}


def test_the_experts_cost_counts_the_rows_held_against_a_hand_count():
    """``experts_roofline``'s operations and bytes here: four expert
    blocks over 8192 positions, 10 x 8 / 256 of a route a position."""
    cell, sizes = _published()
    cost = tiny.cost_beside_the_metrics("experts_cost.py",
                                        "experts_step_cost")
    rows = 4 * 8192 * 10 * 8 / 256
    assert rows == 4 * 2560
    matrices = 4 * 8 * 3 * 3072 * 1024 * 2
    row_bytes = rows * (3072 + 2 * 1024 + 1024 + 3072) * 2
    assert cost(sizes, 1, cell.config, 1) == {
        "flops": 3 * rows * 3 * 2 * 3072 * 1024,
        "bytes": 3 * (matrices + row_bytes)}
    # which key is which is the configuration file's to say: this model
    # lists its dense blocks
    assert cell.config["reference"]["experts"] == {
        "held": "num_experts", "routed": "num_routed_experts",
        "per_token": "num_experts_per_tok", "width": "moe_intermediate_size",
        "dense_blocks": "mlp_only_layers"}


def test_the_cells_own_entries_of_the_manifest():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    (work,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "c1_b1_s8k_w2k", 1)
    assert man["workloads"][9]["name"] == CELL
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4][:1] == [
        "mistral7b_c4_tp2dp2z3"]
    (entry,) = [c for c in man["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "gating_types", "num_attention_heads_per_layer", "num_experts",
        "vocab_size"]
    assert not any(manifest.WIDTH_RE.search(k) for k in entry["reduced"])
    # the set it lists, the shared entries among it (PR 65): where an entry
    # stands in the list says nothing
    assert tiny.listed_for(man, CELL) == {
        "laguna_gate_ms", "laguna_band_tiles_pct", "laguna_moe_imbalance",
        "window_core_ms", "window_roofline", "full_core_ms", "mlp_ms",
        "experts_ms", "experts_time_share_pct", "experts_roofline",
        "moe_route_ms", "moe_dispatch_ms", "moe_combine_ms",
        "local_routes_pct"}
    mine = [m for m in man["per_layer"] if CELL in m.get("workloads", ())]
    assert all(m["moves"] == "tokens_per_s" for m in mine)
    assert {m["layer"] for m in mine} == {"kernels", "dense blocks",
                                          "experts"}
    cell = manifest.resolve_cell(man, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"flash_roofline", "flash_time_share_pct", "flash_fwd_ms",
            "flash_dq_ms", "flash_dkv_ms", "static_hbm_GiB",
            "device_idle_pct", "attn_proj_ms", "head_ms",
            "phase_recompute_ms", "scope_unnamed_pct",
            "gap_dispatch_ms"} < names
    # every metric that names no cells is the new cell's too
    assert {m["name"] for m in man["per_layer"]
            if "workloads" not in m} <= names
    assert not names & {"moe_imbalance", "kimi_kda_ms", "latent_proj_ms",
                        "granite_ssd_ms", "scan_mosaic_calls"}
    assert cell.traffic["overrides"] == [
        "data.dataset=random", "parallel.mixed_precision=bf16",
        "parallel.global_checkpoint=1",
        "parallel.global_train_batch_size=1", "parallel.chunks=1",
        "model.seq_length=8192", "train.lr_warmup_iters=2000"]
    body = cell.config
    assert {k: v for k, v in body["reduced_from"].items()
            if not isinstance(v, list)} == {
        "num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352}
    assert all(len(v) == 48 for v in body["reduced_from"].values()
               if isinstance(v, list))
    assert body["program"]["expects"] == {
        "attention_cores": ["flash", "flash[w512]"],
        "mosaic_calls_per_layer": 3}
    assert 0 < body["reference"]["loss_tolerance"] < 5e-3
    assert "811,017,216 parameters" in body["deployment"]
    assert len(body["assumed"]) >= 6


def test_the_configuration_holds_every_number_of_the_catalog_row():
    """Every key of the catalog's ``config`` under the same key with the
    same value, but the seven that ``reduced`` lists; no width among them,
    the four lists a block cut with the depth to their first five entries,
    and both rotations whole."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Laguna-S-2.1"]
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    body, reduced = cell.config, set(cell.config["reduced_from"])
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert body[key] == value, key
        else:
            assert body["reduced_from"][key] == value, key
            assert not manifest.WIDTH_RE.search(key), key
            if isinstance(value, list):
                assert body[key] == value[:5], key
    assert (body["num_hidden_layers"], body["num_experts"],
            body["vocab_size"]) == (5, 8, 12544)
    assert body["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert body["num_routed_experts"] == row["config"]["num_experts"]
    assert body["rope_parameters"] == row["config"]["rope_parameters"]
    assert body["num_dense_layers_as_run"] == len(body["mlp_only_layers"])
    assert body["num_shared_experts_as_run"] * body[
        "moe_intermediate_size"] == body["shared_expert_intermediate_size"]


def _facts(leaves, steps, busy_s):
    reduced = SimpleNamespace(leaves=leaves, steps=steps, periods=len(steps),
                              busy_s=busy_s)
    return {"trace": {"reduced": [reduced]}, "sequences_per_step": 1,
            "chips": 1, "peaks": {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9}}


def test_the_readers_on_a_synthetic_step_map(monkeypatch):
    """Two traced steps of five instructions each laid over a map the
    program would have kept: each reader by the instructions' deepest
    scope, whatever implements them; and the gauge, where the program wrote
    one."""
    from hetu_galvatron_tpu.observability import trace_analysis
    from hetu_galvatron_tpu.observability.registry import get_registry

    scopes = manifest.load_python(os.path.join(METRICS, "laguna_scopes.py"))
    mlp_ms, route_ms, window_core_ms, full_core_ms, window_roofline = (
        (lambda f, name=name: readers.read_metric(name, f))
        for name in ("mlp_ms", "moe_route_ms", "window_core_ms",
                     "full_core_ms", "window_roofline"))
    instructions = {
        "flash_attention_fwd.1": ("attn/window_core", "forward", None),
        "flash_attention_bwd_dq.2": ("attn/window_core", "backward", None),
        "flash_attention_fwd.3": ("attn/core", "forward", None),
        "fusion.4": ("attn/gate", "forward", None),
        "fusion.5": ("mlp", "forward", None),
        "fusion.6": ("moe/route", "forward", None)}
    kept = {"map": {"instructions": instructions, "inferred": [],
                    "tails": {}}}
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: kept)
    ms = 1_000_000
    step = lambda t0: [("flash_attention_fwd.1", t0, t0 + 4 * ms),
                       ("flash_attention_bwd_dq.2", t0 + 4 * ms, t0 + 16 * ms),
                       ("flash_attention_fwd.3", t0 + 16 * ms, t0 + 21 * ms),
                       ("fusion.4", t0 + 21 * ms, t0 + 22 * ms),
                       ("fusion.5", t0 + 22 * ms, t0 + 25 * ms),
                       ("fusion.6", t0 + 25 * ms, t0 + 27 * ms)]
    _, sizes = _published()
    facts = {**_facts(step(0) + step(30 * ms),
                      [(0, 27 * ms), (30 * ms, 57 * ms)], busy_s=0.054),
             "sizes": sizes}
    assert window_core_ms(facts) == 16.0
    assert full_core_ms(facts) == 5.0
    assert scopes.gate_ms(facts) == 1.0
    assert mlp_ms(facts) == 3.0 and route_ms(facts) == 2.0
    # 8.0 ms by the roofline over the 16 measured
    assert window_roofline(facts) == pytest.approx(
        100 * 7.984 / 16, rel=1e-3)
    assert facts["roofline_bounds"] == {"window_step_cost": "compute"}
    # the gauge: nothing where the program wrote none, its value where it did
    assert scopes.band_tiles_pct(facts) is None or isinstance(
        scopes.band_tiles_pct(facts), float)
    get_registry().gauge("flash/band_tiles_pct").set(22.8)
    assert scopes.band_tiles_pct(facts) == 22.8
    # a program whose map holds no such scope (the parent commit) publishes
    # nothing and does not raise; neither does a run without a trace
    plain = {"map": {"instructions": {
        n: ("attn/core", p, c) for n, (_, p, c) in instructions.items()},
        "inferred": [], "tails": {}}}
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: plain)
    facts.pop("step_map_join")
    assert window_core_ms(facts) is None
    assert window_roofline(facts) is None
    assert scopes.gate_ms(facts) is None
    assert full_core_ms(facts) == 27.0
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: {})
    assert window_core_ms(_facts([], [], 0.0)) is None
    assert scopes.gate_ms({}) is None and mlp_ms({}) is None


def test_every_file_the_benchmark_had_is_as_it_was():
    """Against the parent commit, where git and the commit are at hand:
    every data file it has under ``benchmark/`` is here byte for byte (what
    this PR brings under ``benchmark/`` are new files; the harness's own
    Python is a ``benchmark`` PR's to change, ``tiny.DATA_DIRS``), and
    ``BENCHMARK.json`` still begins with what it held."""
    tiny.assert_the_manifest_begins_with(
        tiny.data_files_as_they_were_at(PARENT, 100))
