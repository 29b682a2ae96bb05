"""The ``kimi_linear`` family, its configuration, its cell and its per-layer
metrics: a tiny model with every new part on (Kimi Delta Attention blocks
beside a latent block without positions and without a low-rank query, a
dense block, a shared expert beside a held share of routed ones) through
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

CELL, CONFIG = "kimilin_c1_b1_s8k", "kimi-linear-48b-a3b-ep32"
PARENT = "28ca5a791ea96bbcfc0178e9c084d5195b86ac32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
AS_RUN = ["kda", "kda", "kda", "latent_attention", "kda"]
METRICS = os.path.join(manifest.ROOT, "benchmark", "layer_metrics")

TINY_KIMI = {
    "first_k_dense_replace": 1, "hidden_size": 32, "intermediate_size": 48,
    "kv_lora_rank": 8, "model_type": "kimi_linear",
    "linear_attn_config": {"full_attn_layers": [4], "head_dim": 8,
                           "kda_layers": [1, 2, 3, 5], "num_heads": 2,
                           "short_conv_kernel_size": 4},
    "mla_use_nope": True, "moe_intermediate_size": 16,
    "moe_renormalize": True, "num_attention_heads": 4, "num_experts": 4,
    "num_routed_experts": 8, "first_expert_held": 2,
    "num_experts_per_token": 2, "num_hidden_layers": 5,
    "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 2.446, "v_head_dim": 8, "vocab_size": 64,
    "layer_types_as_run": AS_RUN, "kda_chunk_size": 16,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(tiny.YAMLS, "kimi-linear-48b-a3b.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=5",
            "model.layer_types=[" + ",".join(AS_RUN) + "]",
            "model.num_attention_heads=4", "model.num_key_value_heads=4",
            "model.head_dim_override=8",
            "model.kda_num_heads=2", "model.kda_head_dim=8",
            "model.kda_chunk_size=16", "model.kv_lora_rank=8",
            "model.qk_nope_head_dim=8", "model.qk_rope_head_dim=4",
            "model.v_head_dim=8", "model.ffn_hidden_size=48",
            "model.moe_ffn_hidden_size=16", "model.vocab_size=64",
            "model.make_vocab_size_divisible_by=1", "model.seq_length=40",
            "model.max_position_embeddings=64", "model.num_experts=8",
            "model.moe_topk=2", "model.moe_held_experts=4",
            "model.moe_first_held_expert=2"],
        "equals": {"hidden_size": "hidden_size",
                   "layer_types": "layer_types_as_run",
                   "num_dense_layers": "first_k_dense_replace",
                   "q_lora_rank": "q_lora_rank",
                   "kda_chunk_size": "kda_chunk_size",
                   "v_head_dim": "v_head_dim",
                   "num_experts": "num_routed_experts",
                   "moe_held_experts": "num_experts",
                   "moe_first_held_expert": "first_expert_held",
                   "num_shared_experts": "num_shared_experts"},
        "expects": {"attention_cores": ["flash", "xla", "kda"],
                    "mosaic_calls_per_layer": 0}},
    "reference": {"family": "kimi_linear", "depth_key": "num_hidden_layers",
                  "loss_tolerance": 0.02},
}


def _tiny_root(tmp_path):
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    tiny._add_config(root, man, "tiny-kimi", TINY_KIMI)
    tiny._add_cell(man, "tiny_kimi_c1", "tiny-kimi", "tiny_c1_b2", 1)
    tiny._write(os.path.join(root, "BENCHMARK.json"), man)
    tiny.assert_nothing_that_was_there_is_edited(root)
    assert manifest.check_manifest(man, root) == []
    return root, manifest.resolve_cell(man, "tiny_kimi_c1", root)


def test_a_tiny_kimi_runs_and_meets_its_reference(tmp_path):
    """bf16 operands on the timed path (the recurrence in chunks) against
    the float32 reference (one position at a time), the program's weights
    through its exporter under the public names."""
    root, cell = _tiny_root(tmp_path)
    line, report = run.measure(
        cell, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP, root=root,
        out_dir=str(tmp_path / "out"), expect_mosaic=False)
    checks = report["checks"]
    assert checks["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, checks
    assert report["attention_cores"] == ["kda", "kda", "kda", "xla", "kda"]
    family = reference.load_family("kimi_linear", root)
    sizes = flops.Sizes(layers=5, hidden=32, heads=4, kv_heads=4, head_dim=8,
                        ffn=48, ffn_matrices=3, vocab=64, seq=40, experts=8)
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
    assert len(cell.config["program"]["equals"]) >= 38
    assert args.parallel.global_train_batch_size == 1
    assert args.parallel.chunks == 1
    assert args.train.lr_warmup_iters == 2000
    return cell, flops.Sizes.of(args.model)


def test_the_family_adds_its_blocks_up_against_a_hand_count():
    cell, sizes = _published()
    family = reference.load_family("kimi_linear")
    blocks = family.attention_blocks(cell.config)
    # ONE entry, the latent block's, at q/k 192 and v 128; none for a KDA
    # block
    assert blocks == [{"qk_head_dim": 192, "v_head_dim": 128}]
    sizes = sizes.with_attention(blocks)
    assert (sizes.layers, sizes.seq, sizes.vocab, sizes.hidden) == (
        5, 8192, 20480, 2304)
    H, S = 2304, 8192
    kda_weights = (3 * H * 4096 + 2 * (H * 128 + 128 * 4096) + H * 32
                   + 4096 * H)
    assert kda_weights == 39_460_864          # the issue's nine matrices
    recurrence = 6 * 128 * 128 * 32
    kda = 2 * kda_weights + recurrence
    latent_weights = (H * 32 * 192 + H * (512 + 64) + 512 * 32 * 256
                      + 32 * 128 * H)
    assert latent_weights == 29_114_368
    core = 2 * 32 * (192 + 128) * (S + 1) // 2
    dense = 2 * 3 * H * 9216
    expert = 2 * 3 * H * 1024
    sparse = 2 * H * 256 + (8 * 8 / 256) * expert + expert
    head = 2 * H * 20480
    forward = family.forward_flops_per_token(sizes, cell.config)
    assert forward == pytest.approx(
        4 * kda + 2 * latent_weights + core + dense + 4 * sparse + head,
        rel=1e-12)
    # the issue's shares: 767.7 MFLOPs a token forward at 8192 (it says
    # 767.8: a rounding), a KDA block 82.1 of them (3.15 the recurrence),
    # the latent core 84
    assert forward == 767_666_176
    assert round(forward / 1e6, 1) == 767.7
    assert round(kda / 1e6, 1) == 82.1
    assert round(recurrence / 1e6, 2) == 3.15
    assert round(core / 1e6) == 84
    assert round(100 * 4 * kda / forward) == 43
    # full-rank k and v would have counted 94 MFLOPs of projections where
    # the latent block has 58
    assert round(2 * latent_weights / 1e6) == 58
    assert round((flops.attention_flops_per_token(
        sizes, flops.Attention(qk_head_dim=192, v_head_dim=128)) - core)
        / 1e6) == 94
    # the kernels' cost reads one block
    assert flops.flash_step_cost(sizes, 1)["flops"] == (
        2 * 32 * (4 * 192 + 3 * 128) * S * (S + 1) / 2)


def test_the_kda_cost_against_a_hand_count():
    """``kimi_kda_roofline``'s operations and bytes: four blocks of 32 heads
    of 128 at chunk 64 over 8192 positions, three passes."""
    _, sizes = _published()
    cost = manifest.load_python(os.path.join(METRICS, "kimi_kda_cost.py"))
    # a token and head: five products with a 64-wide tile and three with
    # the 128 x 128 state
    forward = 32 * (5 * 2 * 64 * 128 + 3 * 2 * 128 * 128)
    assert forward == 5_767_168
    one_pass = 4 * 4096 * 2 + 4096 * 4 + 32 * 4
    got = cost.kimi_kda_step_cost(sizes, 1)
    assert got == {"flops": 4 * 8192 * 3 * forward,
                   "bytes": 4 * 8192 * 3 * one_pass}
    assert cost.kimi_kda_step_cost(sizes, 2)["bytes"] == 2 * got["bytes"]
    # memory-bound on a v5e by a factor of two: 5.9 ms against 2.9 ms
    least = flops.roofline_least_s(
        got, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "memory"
    assert round(1e3 * least["least_s"], 1) == 5.9
    # the chunked form is 1.8 times the recurrence the model's count takes
    family = reference.load_family("kimi_linear")
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    assert forward / family.recurrence_flops_per_token(cell.config) == \
        pytest.approx(1.8333, rel=1e-4)
    # the chunk the cost reads is the one the program is held to
    assert cell.config["program"]["equals"]["kda_chunk_size"] == \
        "kda_chunk_size"


def test_the_experts_cost_counts_the_rows_held_against_a_hand_count():
    """``experts_roofline``'s operations and bytes here: four expert blocks
    over 8192 positions, a quarter of a route a position on the eight held
    of 256 (top-8)."""
    cell, sizes = _published()
    cost = tiny.cost_beside_the_metrics("experts_cost.py",
                                        "experts_step_cost")
    rows = 4 * 8192 * 8 * 8 / 256
    assert rows == 8192
    matrices = 4 * 8 * 3 * 2304 * 1024 * 2
    row_bytes = rows * (2304 + 2 * 1024 + 1024 + 2304) * 2
    assert cost(sizes, 1, cell.config, 1) == {
        "flops": 3 * rows * 3 * 2 * 2304 * 1024,
        "bytes": 3 * (matrices + row_bytes)}
    # which key is which is the configuration file's to say: this model
    # spells the experts a token its own way
    assert cell.config["reference"]["experts"] == {
        "held": "num_experts", "routed": "num_routed_experts",
        "per_token": "num_experts_per_token",
        "width": "moe_intermediate_size",
        "dense_blocks": "first_k_dense_replace"}


def test_the_cells_own_entries_of_the_manifest():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    (work,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "c1_b1_s8k_w2k", 1)
    assert man["workloads"][8]["name"] == CELL
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4][:1] == [
        "mistral7b_c4_tp2dp2z3"]
    (entry,) = [c for c in man["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "linear_attn_config",
                                "num_experts", "vocab_size"]
    # the set it lists, the shared entries among it (PR 65): where an entry
    # stands in the list says nothing
    assert tiny.listed_for(man, CELL) == {
        "kimi_kda_ms", "kimi_kda_time_share_pct", "kimi_kda_mixer_ms",
        "kimi_kda_roofline", "kimi_moe_imbalance", "latent_proj_ms",
        "mlp_ms", "experts_ms", "experts_time_share_pct", "experts_roofline",
        "local_routes_pct", "scan_mosaic_calls"}
    mine = [m for m in man["per_layer"] if CELL in m.get("workloads", ())]
    assert all(m["moves"] == "tokens_per_s" for m in mine)
    assert {m["layer"] for m in mine} == {"delta-rule blocks", "dense blocks",
                                          "experts", "kernels"}
    cell = manifest.resolve_cell(man, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"flash_roofline", "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms",
            "static_hbm_GiB", "device_idle_pct", "attn_proj_ms", "head_ms",
            "phase_recompute_ms", "scope_unnamed_pct",
            "gap_dispatch_ms"} < names
    assert not names & {"moe_imbalance", "xing_hc_ms", "window_core_ms",
                        "granite_ssd_ms", "moe_route_ms"}
    assert cell.traffic["overrides"] == [
        "data.dataset=random", "parallel.mixed_precision=bf16",
        "parallel.global_checkpoint=1",
        "parallel.global_train_batch_size=1", "parallel.chunks=1",
        "model.seq_length=8192", "train.lr_warmup_iters=2000"]
    body = cell.config
    assert body["reduced_from"]["linear_attn_config"]["kda_layers"][:5] == [
        1, 2, 3, 5, 6]
    assert {k: v for k, v in body["reduced_from"].items()
            if k != "linear_attn_config"} == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    assert body["program"]["expects"] == {
        "attention_cores": ["flash", "kda"], "mosaic_calls_per_layer": 0.6}
    assert 0 < body["reference"]["loss_tolerance"] < 5e-3
    assert "602,434,432 parameters" in body["deployment"]
    # linear_attn_config's widths at the top level, where program.equals
    # reads them, are the group's
    lin = body["linear_attn_config"]
    assert (body["kda_num_heads"], body["kda_head_dim"],
            body["kda_conv_kernel"]) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    assert [body["layer_types_as_run"][i - 1] for i in lin["kda_layers"]] \
        == ["kda"] * 4
    assert [body["layer_types_as_run"][i - 1]
            for i in lin["full_attn_layers"]] == ["latent_attention"]


def test_the_configuration_holds_every_number_of_the_catalog_row():
    """Every key of the catalog's ``config`` under the same key with the
    same value, but the four that ``reduced`` lists; no width among them,
    and inside the one nested group that changed only the two lists of
    block numbers did."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    body, reduced = cell.config, set(cell.config["reduced_from"])
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert body[key] == value, key
        else:
            assert body["reduced_from"][key] == value, key
            assert not manifest.WIDTH_RE.search(key), key
    assert (body["num_hidden_layers"], body["num_experts"],
            body["vocab_size"]) == (5, 8, 20480)
    assert body["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert body["num_routed_experts"] == row["config"]["num_experts"]
    was, now = row["config"]["linear_attn_config"], body["linear_attn_config"]
    assert {k for k in was if was[k] != now[k]} == {"kda_layers",
                                                    "full_attn_layers"}
    assert now["kda_layers"] == [i for i in was["kda_layers"] if i <= 5]
    assert now["full_attn_layers"] == [i for i in was["full_attn_layers"]
                                       if i <= 5]


def _facts(leaves, steps, busy_s):
    reduced = SimpleNamespace(leaves=leaves, steps=steps, periods=len(steps),
                              busy_s=busy_s)
    return {"trace": {"reduced": [reduced]}, "sequences_per_step": 1,
            "chips": 1, "peaks": {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9}}


def test_the_readers_on_a_synthetic_step_map(monkeypatch):
    """Two traced steps of six instructions each laid over a map the
    program would have kept: each reader by the instructions' deepest
    scope, whatever implements them."""
    from hetu_galvatron_tpu.observability import trace_analysis

    scopes = manifest.load_python(os.path.join(METRICS, "kimi_scopes.py"))
    latent_proj_ms, mlp_ms = (
        (lambda f, name=name: readers.read_metric(name, f))
        for name in ("latent_proj_ms", "mlp_ms"))
    instructions = {
        "fusion.1": ("mixer/kda/in_proj", "forward", None),
        "fusion.2": ("mixer/kda/conv", "forward", None),
        "fusion.3": ("mixer/kda/gates", "recompute", None),
        "while.4": ("mixer/kda/scan", "backward", None),
        "fusion.5": ("attn/latent_proj", "forward", None),
        "fusion.6": ("mlp", "forward", None)}
    kept = {"map": {"instructions": instructions, "inferred": [],
                    "tails": {}}}
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: kept)
    ms = 1_000_000
    step = lambda t0: [("fusion.1", t0, t0 + 3 * ms),
                       ("fusion.2", t0 + 3 * ms, t0 + 5 * ms),
                       ("fusion.3", t0 + 5 * ms, t0 + 6 * ms),
                       ("while.4", t0 + 6 * ms, t0 + 16 * ms),
                       ("fusion.5", t0 + 16 * ms, t0 + 18 * ms),
                       ("fusion.6", t0 + 18 * ms, t0 + 22 * ms)]
    _, sizes = _published()
    facts = {**_facts(step(0) + step(30 * ms),
                      [(0, 22 * ms), (30 * ms, 52 * ms)], busy_s=0.044),
             "sizes": sizes}
    assert scopes.kda_ms(facts) == 2.0 + 1.0 + 10.0
    assert scopes.kda_mixer_ms(facts) == 3.0 + 13.0
    assert scopes.kda_time_share_pct(facts) == pytest.approx(100 * 13 / 22)
    assert latent_proj_ms(facts) == 2.0
    assert mlp_ms(facts) == 4.0
    # 5.9 ms by the roofline over the 13 measured
    assert scopes.kda_roofline(facts) == pytest.approx(100 * 5.915 / 13,
                                                       rel=1e-3)
    assert facts["roofline_bounds"] == {"kimi_kda_step_cost": "memory"}
    # a program whose map holds no such scope (the parent commit) publishes
    # nothing and does not raise; neither does a run without a trace
    plain = {"map": {"instructions": {
        n: ("mlp", p, c) for n, (_, p, c) in instructions.items()},
        "inferred": [], "tails": {}}}
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: plain)
    facts.pop("step_map_join")
    assert scopes.kda_ms(facts) is None
    assert scopes.kda_roofline(facts) is None
    assert scopes.kda_time_share_pct(facts) is None
    assert latent_proj_ms(facts) is None
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: {})
    assert scopes.kda_mixer_ms(_facts([], [], 0.0)) is None
    assert scopes.kda_ms({}) is None and mlp_ms({}) is None


def test_every_file_the_benchmark_had_is_as_it_was():
    """Against the parent commit, where git and the commit are at hand:
    every data file it has under ``benchmark/`` is here byte for byte (what
    this PR brings under ``benchmark/`` are new files; the harness's own
    Python is a ``benchmark`` PR's to change, ``tiny.DATA_DIRS``), and
    ``BENCHMARK.json`` still begins with what it held."""
    tiny.assert_the_manifest_begins_with(
        tiny.data_files_as_they_were_at(PARENT, 100))
