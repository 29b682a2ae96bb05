"""The ``kimi_vl`` family, its configuration, its cell and its per-layer
metrics: a tiny model with every part on (a tower of image patches at three
grids in front of latent attention and shared beside routed experts) through
``measure()`` on the CPU against the plain reference fed the whole first
batch, the family's attending blocks and FLOP count against numbers written
out by hand for the cell's three grids, the two cost functions against hand
counts, the cell's own entries of the manifest, the catalog row, the readers
on a synthetic step map, the chipless compile of the cell, and that every
file the benchmark had is as it was."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import flops, manifest, readers, reference, run
from benchmark.tests import tiny

CELL, CONFIG, TRAFFIC = "kimivl_c1_b1_s4k", "kimi-vl-a3b-ep8", "c1_b1_s4k_img3"
PARENT = "b5ac02aff860aad4f28143446949ff39b6a523e8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = os.path.join(manifest.ROOT, "benchmark", "layer_metrics")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "media_placeholder_token_id", "tower_layers"]
# the tower's readers and the decoder's, the first nine by scope; the
# decoder's are shared entries that list the cell since PR 65
TOWER = [
    "kimivl_tower_ms", "kimivl_tower_share_pct", "kimivl_tower_core_ms",
    "kimivl_tower_core_roofline", "kimivl_tower_pairs_pct",
    "kimivl_tower_mlp_ms", "kimivl_merge_project_ms",
    "kimivl_place_images_ms"]
MINE = set(TOWER) | {
    "latent_proj_ms", "experts_ms", "experts_time_share_pct",
    "experts_roofline", "kimivl_moe_imbalance", "local_routes_pct", "mlp_ms",
    "moe_dispatch_ms", "moe_combine_ms"}
GRIDS = [[64, 64], [36, 80], [32, 38]]

TINY_GRIDS = [[4, 4], [2, 6], [6, 4]]
TINY_VISION = {
    "hidden_size": 24, "num_attention_heads": 2, "intermediate_size": 40,
    "patch_size": 2, "num_channels": 3, "init_pos_emb_height": 4,
    "init_pos_emb_width": 4, "merge_kernel_size": [2, 2],
    "layer_norm_eps": 1e-05, "rope_theta": 10000.0}
TINY_KIMI_VL = {
    "model_type": "kimi_vl", "hidden_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 2, "intermediate_size": 64,
    "moe_intermediate_size": 16, "q_lora_rank": None, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rms_norm_eps": 1e-05, "rope_theta": 800000, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "num_routed_experts": 8, "first_expert_held": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.446, "n_shared_experts": 2, "vocab_size": 64,
    "media_placeholder_token_id": 63, "tower_layers": 2,
    "vision_config": TINY_VISION, "image_patches": [16, 12, 24],
    "image_grids": TINY_GRIDS,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(tiny.YAMLS, "kimi-vl-a3b.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=3",
            "model.layer_types=[latent_attention,latent_attention,"
            "latent_attention]",
            "model.num_attention_heads=2", "model.num_key_value_heads=2",
            "model.kv_lora_rank=16", "model.qk_nope_head_dim=8",
            "model.qk_rope_head_dim=4", "model.v_head_dim=8",
            "model.ffn_hidden_size=64", "model.moe_ffn_hidden_size=16",
            "model.num_experts=8", "model.moe_held_experts=4",
            "model.moe_first_held_expert=2", "model.moe_topk=2",
            "model.vocab_size=64", "model.image_token_id=63",
            "model.make_vocab_size_divisible_by=1", "model.seq_length=48",
            "model.max_position_embeddings=64", "model.tower_layers=2",
            "model.tower_hidden_size=24", "model.tower_num_heads=2",
            "model.tower_ffn_hidden_size=40", "model.tower_patch_size=2",
            "model.tower_pos_emb_height=4", "model.tower_pos_emb_width=4"],
        "equals": {"hidden_size": "hidden_size",
                   "num_experts": "num_routed_experts",
                   "moe_held_experts": "n_routed_experts",
                   "moe_first_held_expert": "first_expert_held",
                   "tower_layers": "tower_layers",
                   "vision_config": "vision_config",
                   "image_patches": "image_patches",
                   "image_grids": "image_grids",
                   "image_token_id": "media_placeholder_token_id"},
        "expects": {"attention_cores": ["xla"],
                    "mosaic_calls_per_layer": 0}},
    "reference": {"family": "kimi_vl", "depth_key": "num_hidden_layers",
                  "second_stack_depth_key": "tower_layers",
                  "loss_tolerance": 0.02},
}
TINY_IMAGES = tiny.COMMON + [
    "parallel.global_train_batch_size=4", "parallel.chunks=2",
    "model.image_grids=[[4,4],[2,6],[6,4]]",
    "data.image_text_spans=[3,9,12,11]"]


def _tiny_root(tmp_path, traffic=TINY_IMAGES):
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    tiny._add_config(root, man, "tiny-kimi-vl", TINY_KIMI_VL)
    tiny._write(manifest.traffic_path(root, "tiny_c1_img3"),
                {"overrides": traffic})
    tiny._add_cell(man, "tiny_kimivl_c1", "tiny-kimi-vl", "tiny_c1_img3", 1)
    tiny._write(os.path.join(root, "BENCHMARK.json"), man)
    tiny.assert_nothing_that_was_there_is_edited(root)
    assert manifest.check_manifest(man, root) == []
    return root, manifest.resolve_cell(man, "tiny_kimivl_c1", root)


def test_a_tiny_kimi_vl_runs_and_meets_its_reference(tmp_path):
    """bf16 operands on the timed path, two microbatches of two sequences
    with three images each, a held share of the experts that does not start
    at expert 0, against the float32 reference fed the program's weights
    through its exporter and every field of its first batch; the loss over
    the marked positions."""
    root, cell = _tiny_root(tmp_path)
    line, report = run.measure(
        cell, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP, root=root,
        out_dir=str(tmp_path / "out"), expect_mosaic=False)
    checks = report["checks"]
    assert checks["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, checks
    # the tower's cores first, then the decoder's
    assert report["attention_cores"] == ["xla"] * 5
    # the mean is over the positions whose label is no placeholder
    assert report["reference"]["tokens"] == 4 * (48 - 13)
    assert report["tokens_per_step"] == 4 * 48
    family = reference.load_family("kimi_vl", root)
    sizes = flops.Sizes(layers=3, hidden=32, heads=2, kv_heads=2, head_dim=16,
                        ffn=64, ffn_matrices=3, vocab=64, seq=48, experts=8)
    sizes = sizes.with_attention(family.attention_blocks(cell.config),
                                 beside=2)
    assert report["train_flops_per_token"] == 3 * \
        family.forward_flops_per_token(sizes, cell.config)
    from hetu_galvatron_tpu.observability.registry import get_registry

    gauges = {(m.name, tuple(sorted(m.labels.items()))): m.value
              for m in get_registry().metrics() if m.kind == "gauge"}
    assert gauges[("tower/patches", ())] == 4 * 52
    assert gauges[("tower/image_positions", ())] == 4 * 13
    assert gauges[("tower/marked_positions", ())] == 4 * 35
    assert gauges[("tower/pairs_masked", ())] == 4 * (256 + 144 + 576)
    assert gauges[("tower/pairs_tiled", ())] == 4 * 52 * 52


def test_a_traffic_of_other_images_stops_the_run(tmp_path):
    """``image_patches`` of the configuration's file is tied to the
    program's: a traffic whose grids differ stops before anything runs."""
    other = [o.replace("[[4,4],[2,6],[6,4]]", "[[4,4],[2,6],[4,4]]")
             .replace("[3,9,12,11]", "[3,9,12,13]") for o in TINY_IMAGES]
    root, cell = _tiny_root(tmp_path, other)
    with pytest.raises(SystemExit, match="image_patches"):
        run.measure(cell, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP,
                    root=root, out_dir=str(tmp_path / "out"),
                    expect_mosaic=False)


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
    assert len(cell.config["program"]["equals"]) >= 36
    assert args.parallel.global_train_batch_size == 1
    assert args.parallel.chunks == 1
    assert args.train.lr_warmup_iters == 2000
    assert args.data.image_text_spans == [128, 512, 640, 768]
    family = reference.load_family("kimi_vl")
    sizes = flops.Sizes.of(args.model).with_attention(
        family.attention_blocks(cell.config),
        beside=manifest.second_stack_depth(cell.config))
    return cell, sizes


def test_the_family_describes_and_counts_both_stacks_by_hand():
    cell, sizes = _published()
    family = reference.load_family("kimi_vl")
    # the three grids: 896 x 896, 504 x 1120 and 448 x 532 pixels
    patches = [64 * 64, 36 * 80, 32 * 38]
    assert patches == [4096, 2880, 1216] == cell.config["image_patches"]
    assert cell.config["image_grids"] == GRIDS
    P, pairs = 8192, 4096 ** 2 + 2880 ** 2 + 1216 ** 2
    assert (sum(patches), pairs) == (P, 26_550_272)
    assert [n // 4 for n in patches] == [1024, 720, 304]
    blocks = family.attention_blocks(cell.config)
    assert blocks == [{"heads": 16, "kv_heads": 16, "qk_head_dim": 72,
                       "v_head_dim": 72, "hidden": 1152, "positions": P,
                       "pairs": pairs}] * 12 + [
        {"qk_head_dim": 192, "v_head_dim": 128}] * 5
    assert manifest.second_stack_depth(cell.config) == 12
    assert (sizes.layers, sizes.hidden, sizes.vocab, sizes.seq,
            sizes.heads) == (5, 2048, 20480, 4096, 16)
    S, H, C = 4096, 2048, 1152
    # the tower, forward, a sequence
    tower_linear = 12 * P * 2 * (4 * C * C + 2 * C * 4304) + P * 2 * 588 * C
    tower_cores = 12 * 2 * 16 * (72 + 72) * pairs
    projector = 2048 * 2 * 4608 * (4608 + H)
    assert round(tower_linear / 1e12, 2) == 3.00
    assert round(tower_cores / 1e12, 2) == 1.47
    assert round(projector / 1e12, 2) == 0.13
    tower = tower_linear + tower_cores + projector
    assert round(tower / 1e12, 2) == 4.60
    # the decoder, forward, a token
    proj = 2 * (H * 16 * 192 + H * (512 + 64) + 512 * 16 * (128 + 128)
                + 16 * 128 * H)
    assert proj / 2 == 13_762_560   # the block's attention matrices
    core = 2 * 16 * (192 + 128) * (S + 1) / 2
    dense = 2 * 3 * H * 11264
    expert = 2 * 3 * H * 1408
    sparse = 2 * H * 64 + (6 * 8 / 64) * expert + 2 * expert
    head = 2 * H * 20480
    decoder = 5 * (proj + core) + dense + 4 * sparse + head
    assert round(decoder * S / 1e12, 2) == 2.69
    assert round(5 * core * S / 1e12, 2) == 0.43
    assert round(head * S / 1e12, 2) == 0.34
    forward = family.forward_flops_per_token(sizes, cell.config)
    assert forward == pytest.approx(tower / S + decoder, rel=1e-12)
    # ISSUE 59's figures: the tower 63 % of about 21.9 TFLOP a step, 5.34
    # GFLOP a token of training
    assert round(tower / (tower + decoder * S), 2) == 0.63
    assert round(3 * forward * S / 1e12, 1) == 21.9
    assert round(3 * forward / 1e9, 2) == 5.34
    # a held expert's rows a step: 1/8 of ep8's
    assert S * 6 / 64 == 384


def test_the_cost_functions_against_hand_counts():
    _, sizes = _published()
    load = lambda f: manifest.load_python(os.path.join(METRICS, f))
    pairs, P = 26_550_272, 8192
    core = load("kimivl_tower_cost.py").kimivl_tower_step_cost(sizes, 1)
    # seven matmuls over three passes, all 72 wide; q, k, v, o three times
    # and two reads of the float32 row statistics, 12 blocks
    assert core["flops"] == 12 * 2 * 16 * (4 * 72 + 3 * 72) * pairs
    assert core["bytes"] == 12 * (3 * P * 4 * 16 * 72 * 2 + 2 * P * 16 * 4)
    both = flops.flash_step_cost(sizes, 1)
    decoder = 5 * 2 * 16 * (4 * 192 + 3 * 128) * flops.causal_pairs(4096)
    assert both["flops"] == core["flops"] + decoder
    cell, _ = _published()
    experts = tiny.cost_beside_the_metrics(
        "experts_cost.py", "experts_step_cost")(sizes, 1, cell.config, 1)
    rows = 4 * 4096 * 6 * 8 / 64
    assert rows == 4 * 3072
    assert experts["flops"] == 3 * rows * 3 * 2 * 2048 * 1408
    assert experts["bytes"] == 3 * (
        4 * 8 * 3 * 2048 * 1408 * 2
        + rows * (2048 + 2 * 1408 + 1408 + 2048) * 2)


def test_the_cells_own_entries_of_the_manifest():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    (work,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, TRAFFIC, 1)
    # the twelfth cell; two of them on four chips, as before
    assert man["workloads"][11]["name"] == CELL
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4][:2] == [
        "mistral7b_c4_tp2dp2z3", "mellum2_c4_ep4"]
    for said in ("63 %", "384 rows", "host share"):
        assert said in work["why"]
    (entry,) = [c for c in man["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == REDUCED
    assert not any(manifest.WIDTH_RE.search(k) for k in entry["reduced"])
    assert tiny.listed_for(man, CELL) == MINE
    mine = [m for m in man["per_layer"] if CELL in m.get("workloads", ())]
    assert {m["layer"] for m in mine} == {"image tower", "dense blocks",
                                          "experts"}
    cell = manifest.resolve_cell(man, CELL)
    names = {m["name"] for m in cell.per_layer}
    # every metric that names no cells is the new cell's too
    assert {m["name"] for m in man["per_layer"]
            if "workloads" not in m} <= names
    assert {"flash_roofline", "flash_time_share_pct"} <= names
    assert not names & {"moe_imbalance", "kimi_kda_ms", "moe_route_ms",
                        "window_core_ms"}
    assert cell.traffic["overrides"] == [
        "data.dataset=random", "parallel.mixed_precision=bf16",
        "parallel.global_checkpoint=1", "parallel.global_train_batch_size=1",
        "parallel.chunks=1", "model.seq_length=4096",
        "train.lr_warmup_iters=2000",
        "model.image_grids=[[64,64],[36,80],[32,38]]",
        "data.image_text_spans=[128,512,640,768]"]
    body = cell.config
    assert body["reduced_from"] == {
        "num_hidden_layers": 27, "n_routed_experts": 64,
        "vocab_size": 163840, "media_placeholder_token_id": 163605,
        "tower_layers": 27}
    assert body["reference"]["second_stack_depth_key"] == "tower_layers"
    assert body["program"]["expects"] == {
        "attention_cores": ["flash"], "mosaic_calls_per_layer": 3}
    assert 0 < body["reference"]["loss_tolerance"] < 5e-3
    for control in reference.load_family("kimi_vl").CONTROLS:
        assert control in body["reference"]["loss_tolerance_reason"]
    assert len(body["assumed"]) >= 10
    assert "787,437,888 parameters" in body["deployment"]


def test_the_configuration_holds_every_number_of_the_catalog_row():
    """Every key of the catalog's ``config`` under the same key with the
    same value, but the three of them that ``reduced`` lists (the depth, the
    experts held, the vocabulary's slice); no width among the five."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Kimi-VL-A3B-Instruct"]
    body = manifest.resolve_cell(manifest.load_manifest(), CELL).config
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert body["reduced_from"][key] == value, key
            assert not manifest.WIDTH_RE.search(key), key
        else:
            assert body[key] == value, key
    assert (body["num_hidden_layers"], body["n_routed_experts"],
            body["vocab_size"], body["tower_layers"]) == (5, 8, 20480, 12)
    # the floors: four blocks behind the dense one, 8 experts, an eighth
    assert body["vocab_size"] * 8 == row["config"]["vocab_size"]
    v = body["vision_config"]
    assert (v["hidden_size"] // v["num_attention_heads"],
            v["num_channels"] * v["patch_size"] ** 2) == (72, 588)


def _facts(leaves, steps, busy_s, sizes):
    reduced = SimpleNamespace(leaves=leaves, steps=steps, periods=len(steps),
                              busy_s=busy_s)
    return {"trace": {"reduced": [reduced]}, "sequences_per_step": 1,
            "chips": 1, "sizes": sizes,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_the_readers_on_a_synthetic_step_map(monkeypatch):
    """Two traced steps laid over a map the program would have kept: each
    reader by the instructions' deepest scope; the gauges where the program
    wrote them; and a program without the scopes (the parent) publishes
    nothing and does not raise."""
    from hetu_galvatron_tpu.observability import trace_analysis
    from hetu_galvatron_tpu.observability.registry import get_registry

    scopes = manifest.load_python(os.path.join(METRICS, "kimivl_scopes.py"))
    read = lambda name: (lambda f: readers.read_metric(name, f))
    instructions = {
        "fusion.1": ("tower/patch_embed", "forward", None),
        "fusion.2": ("tower/attn_proj", "forward", None),
        "flash_attention_fwd.3": ("tower/attention", "forward", None),
        "fusion.4": ("tower/mlp", "forward", None),
        "fusion.5": ("tower/merge_project", "forward", None),
        "fusion.6": ("embed/place_images", "forward", None),
        "fusion.7": ("attn/latent_proj", "forward", None),
        "flash_attention_fwd.8": ("attn/core", "forward", None)}
    kept = {"map": {"instructions": instructions, "inferred": [],
                    "tails": {}}}
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: kept)
    ms = 1_000_000
    ends = [1, 3, 13, 17, 18, 18.5, 20.5, 22.5]
    step = lambda t0: [
        (name, t0 + int(a * ms), t0 + int(b * ms))
        for name, a, b in zip(instructions, [0] + ends[:-1], ends)]
    _, sizes = _published()
    facts = _facts(step(0) + step(30 * ms),
                   [(0, 23 * ms), (30 * ms, 53 * ms)], 0.045, sizes)
    assert scopes.tower_ms(facts) == 18.0
    assert scopes.tower_share_pct(facts) == pytest.approx(100 * 36 / 45)
    assert scopes.tower_core_ms(facts) == 10.0
    assert read("kimivl_tower_mlp_ms")(facts) == 4.0
    assert read("kimivl_merge_project_ms")(facts) == 1.0
    assert read("kimivl_place_images_ms")(facts) == 0.5
    assert read("latent_proj_ms")(facts) == 2.0
    cost = manifest.load_python(os.path.join(
        METRICS, "kimivl_tower_cost.py")).kimivl_tower_step_cost(sizes, 1)
    assert read("kimivl_tower_core_roofline")(facts) == pytest.approx(
        100 * cost["flops"] / 197e12 / 10e-3)
    assert facts["roofline_bounds"] == {"kimivl_tower_step_cost": "compute"}
    # the gauges: nothing where the program wrote none
    get_registry().gauge("tower/pairs_masked").set(26_550_272)
    get_registry().gauge("tower/pairs_tiled").set(8192 ** 2)
    assert scopes.tower_pairs_pct(facts) == pytest.approx(39.563, abs=1e-3)
    # the parent: no tower scope in its map
    plain = {"map": {"instructions": {
        n: ("attn/core", p, None) for n, (_, p, _) in instructions.items()},
        "inferred": [], "tails": {}}}
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: plain)
    facts.pop("step_map_join")
    for name in TOWER[:4] + TOWER[5:] + ["latent_proj_ms"]:
        assert read(name)(facts) is None, name
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: {})
    assert scopes.tower_ms(_facts([], [], 0.0, sizes)) is None
    assert scopes.tower_share_pct({}) is None
    assert scopes.tower_core_roofline({}) is None


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu says
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_the_cell_compiles_for_a_described_chip_and_fits(topo):
    """``aot_check.py``'s own compile of the cell (about two minutes): the
    parameters the file states, the Mosaic calls of seventeen flash cores
    and more, and a live peak under the chip's 16 GiB."""
    from benchmark import aot_check

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    rep = aot_check.compile_cell(cell, topo.devices)
    assert rep["parameters"] == 787_437_888
    assert rep["tokens_per_step"] == 4096
    assert rep["mosaic_custom_calls"] >= 3 * 17
    assert rep["per_device_GiB"]["live_peak"] * aot_check.GiB <= \
        aot_check.HBM_BYTES


def test_every_file_the_benchmark_had_is_as_it_was():
    """Against the parent commit, where git and the commit are at hand:
    every data file and reference it has under ``benchmark/`` is here byte
    for byte (what this PR brings under ``benchmark/`` are new files), and
    ``BENCHMARK.json`` still begins with what it held."""
    was = tiny.data_files_as_they_were_at(PARENT, 150)
    tiny.assert_the_manifest_begins_with(was)
    now = manifest.load_manifest()
    assert now["configs"][len(was["configs"])]["name"] == CONFIG
    assert now["workloads"][len(was["workloads"])]["name"] == CELL
    assert MINE <= tiny.listed_for(now, CELL)
    # the eleventh configuration and the twelfth cell; PR 61 came after
    assert (len(was["configs"]), len(was["workloads"])) == (10, 11)
