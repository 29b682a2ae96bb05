"""The ``mellum`` family, its configuration, its four-chip cell and its
per-layer metrics: a tiny model with every part on (a period of window and
full attention blocks with a q/k norm a head and a rotation a kind, softmax
top-k experts in every block) through ``measure()`` on four CPU devices
under the cell's own plan (ep4 carved from dp4, the vocabulary in four
slices) against the plain reference, the family's FLOP count against the
issue's figures, the parameter counts, the cell's own entries of the
manifest, the catalog row, the readers on a synthetic step map, the two
cost functions against hand counts, and that every file the benchmark had is
as it was."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import flops, manifest, readers, reference, run
from benchmark.tests import tiny

CELL, CONFIG, TRAFFIC = "mellum2_c4_ep4", "mellum2-12b-a2.5b-p1", "c4_ep4_s4k"
PARENT = "845c4f65590dfff67a7edd29fc3a3caec8c78eb0"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
AS_RUN = ["sliding_attention", "sliding_attention", "sliding_attention",
          "full_attention"]
METRICS = os.path.join(manifest.ROOT, "benchmark", "layer_metrics")
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 100.0, "factor": 8,
        "original_max_position_embeddings": 8, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2},
    "sliding_attention": {"rope_type": "default", "rope_theta": 100.0}}
# what the cell came with, under the names PR 65 left: its own entries and
# the shared ones that list it (a set: where an entry stands says nothing)
MINE = {
    "mellum_exchange_ms", "mellum_exchange_exposed_pct",
    "collective_all_ms", "collective_all_exposed_pct",
    "experts_ms", "experts_roofline", "moe_route_ms",
    "moe_dispatch_ms", "moe_combine_ms",
    "mellum_chip_imbalance", "moe_imbalance",
    "window_core_ms", "window_roofline", "full_core_ms"}

TINY_MELLUM = {
    "model_type": "mellum", "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 4, "layer_types": AS_RUN,
    "mlp_layer_types": ["sparse"] * 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "sliding_window": 8,
    "rope_parameters": ROPE, "rms_norm_eps": 1e-06,
    "moe_intermediate_size": 16, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "vocab_size": 64,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(tiny.YAMLS, "mellum2-12b-a2.5b.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=4",
            "model.layer_types=[" + ",".join(AS_RUN) + "]",
            "model.num_attention_heads=4", "model.num_key_value_heads=2",
            "model.head_dim_override=8", "model.sliding_window=8",
            "model.rope_parameters=" + json.dumps(ROPE).replace(" ", ""),
            "model.ffn_hidden_size=48", "model.moe_ffn_hidden_size=16",
            "model.vocab_size=64", "model.make_vocab_size_divisible_by=1",
            "model.seq_length=32", "model.max_position_embeddings=64",
            "model.num_experts=8", "model.moe_topk=2"],
        "equals": {"hidden_size": "hidden_size", "layer_types": "layer_types",
                   "sliding_window": "sliding_window",
                   "rope_parameters": "rope_parameters",
                   "num_experts": "num_experts",
                   "moe_topk": "num_experts_per_tok",
                   "moe_norm_topk_prob": "norm_topk_prob"},
        "expects": {"attention_cores": ["flash", "xla", "flash[w8]",
                                        "xla[w8]"],
                    "mosaic_calls_per_layer": 0}},
    "reference": {"family": "mellum", "depth_key": "num_hidden_layers",
                  "loss_tolerance": 0.02},
}
# the cell's plan at a tiny batch: ep4 carved from dp4, the vocabulary in
# four slices, a sequence a device in one microbatch
TINY_EP4 = tiny.COMMON + [
    "parallel.global_ep_deg=4", "parallel.vocab_tp=4",
    "parallel.global_train_batch_size=4", "parallel.chunks=1"]


def _tiny_root(tmp_path):
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    tiny._add_config(root, man, "tiny-mellum", TINY_MELLUM)
    tiny._write(manifest.traffic_path(root, "tiny_c4_ep4"),
                {"overrides": TINY_EP4})
    # (a cell on four devices; the copy's cap is not what is tested here)
    tiny._add_cell(man, "tiny_mellum_c4", "tiny-mellum", "tiny_c4_ep4", 4)
    tiny._write(os.path.join(root, "BENCHMARK.json"), man)
    tiny.assert_nothing_that_was_there_is_edited(root)
    return root, manifest.resolve_cell(man, "tiny_mellum_c4", root)


def test_a_tiny_mellum_runs_under_the_cells_plan_and_meets_its_reference(
        tmp_path):
    """bf16 operands on the timed path, its expert layers inside the
    exchange over four devices, against the float32 reference on one, which
    holds every expert: the program's weights gathered through its exporter
    under the public names."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    root, cell = _tiny_root(tmp_path)
    line, report = run.measure(
        cell, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP, root=root,
        out_dir=str(tmp_path / "out"), expect_mosaic=False)
    checks = report["checks"]
    assert checks["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, checks
    assert report["attention_cores"] == ["xla[w8]"] * 3 + ["xla"]
    assert any("global_ep_deg=4" in a for a in report["argv"])
    from hetu_galvatron_tpu.observability.registry import get_registry

    gauges = {(m.name, tuple(sorted(m.labels.items()))): m.value
              for m in get_registry().metrics() if m.kind == "gauge"}
    assert gauges[("ep/axes", ())] == 4
    # what a chip sends a step, from the shapes: four blocks, three passes
    # (the tiny traffic remats), 32 tokens to three others
    assert gauges[("ep/exchange_bytes_per_step", ())] == 4 * 3 * 3 * 32 * (
        32 * 2 + 2 * 4 * 2 + 32 * 4)
    assert gauges[("moe/local_routes_pct", (("layer", "layer0"),))] == 25.0
    rows = [gauges[("moe/chip_rows", (("chip", str(r)),
                                      ("layer", "layer0")))]
            for r in range(4)]
    assert sum(rows) == 4 * 32 * 2
    assert gauges[("moe/chip_imbalance", (("layer", "layer0"),))] == \
        max(rows) / (sum(rows) / 4)
    family = reference.load_family("mellum", root)
    sizes = flops.Sizes(layers=4, hidden=32, heads=4, kv_heads=2, head_dim=8,
                        ffn=48, ffn_matrices=3, vocab=64, seq=32, experts=8)
    sizes = sizes.with_attention(family.attention_blocks(cell.config))
    assert report["train_flops_per_token"] == 3 * \
        family.forward_flops_per_token(sizes, cell.config)


def _published(seq=None):
    """The cell, and the program's sizes from the cell's own command line
    after every ``program.equals`` pair was checked."""
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    args = resolve_model_config(args_from_cli(
        manifest.train_argv(cell, seed=0), mode="train_dist"))
    for attr, key in cell.config["program"]["equals"].items():
        assert getattr(args.model, attr) == cell.config[key], attr
    assert len(cell.config["program"]["equals"]) >= 28
    assert args.parallel.global_train_batch_size == 4
    assert args.parallel.chunks == 1
    assert (args.parallel.global_ep_deg, args.parallel.vocab_tp) == (4, 4)
    assert args.train.lr_warmup_iters == 2000
    model = args.model if seq is None else args.model.model_copy(
        update={"seq_length": seq})
    sizes = flops.Sizes.of(model)
    family = reference.load_family("mellum")
    return cell, sizes.with_attention(family.attention_blocks(cell.config))


def test_the_family_adds_its_blocks_up_against_the_issues_figures():
    cell, sizes = _published(seq=8192)
    family = reference.load_family("mellum")
    assert family.attention_blocks(cell.config) == [
        {"window": 1024}, {"window": 1024}, {"window": 1024}, {}]
    assert (sizes.layers, sizes.vocab, sizes.hidden, sizes.heads,
            sizes.kv_heads, sizes.head_dim) == (4, 98304, 2304, 32, 4, 128)
    H, S = 2304, 8192
    weights = H * 4096 + 2 * H * 512 + 4096 * H
    assert weights == 21_233_664
    band = 1024 * 1025 // 2 + (S - 1024) * 1024
    assert band == flops.causal_pairs(S, 1024)
    window_core = 4 * 128 * 32 * band / S
    full_core = 4 * 128 * 32 * (S + 1) / 2
    expert = 3 * H * 896
    assert expert == 6_193_152
    sparse = 2 * H * 64 + 8 * 2 * expert
    head = 2 * H * 98304
    forward = family.forward_flops_per_token(sizes, cell.config)
    assert forward == pytest.approx(
        4 * (2 * weights + sparse) + 3 * window_core + full_core + head,
        rel=1e-12)
    # ISSUE 51's figures at 8192: projections 42.47 M a block, a window core
    # 15.73 M, the full core 67.12 M, router 0.29 M, experts 99.09 M, three
    # window blocks 157.58 M each and the full one 208.97 M, head 452.98 M:
    # 1,134.70 M forward, 3.40 GFLOP a token trained
    assert round(2 * weights / 1e6, 2) == 42.47
    assert round(window_core / 1e6, 2) == 15.73
    assert round(full_core / 1e6, 2) == 67.12
    assert round(2 * H * 64 / 1e6, 2) == 0.29
    assert round(8 * 2 * expert / 1e6, 2) == 99.09
    assert round((2 * weights + window_core + sparse) / 1e6, 2) == 157.58
    assert round((2 * weights + full_core + sparse) / 1e6, 2) == 208.97
    assert round(head / 1e6, 2) == 452.98
    assert round(forward / 1e6, 2) == 1134.70
    assert round(3 * forward / 1e9, 2) == 3.40
    # the cell as it runs, at 4096: 1,098.0 M forward, 54.0 TFLOP a step
    _, at_4k = _published()
    assert at_4k.seq == 4096
    forward_4k = family.forward_flops_per_token(at_4k, cell.config)
    assert round(forward_4k / 1e6, 1) == 1098.0
    assert round(3 * forward_4k * 4 * 4096 / 1e12, 1) == 54.0


def test_the_parameter_counts():
    """595,154,176 a chip, 2,123,977,984 over the host, 12.15 B at the
    published depth, from the configuration's numbers."""
    cell, _ = _published()
    c = cell.config
    H, D = c["hidden_size"], c["head_dim"]
    attention = (H * c["num_attention_heads"] * D
                 + 2 * H * c["num_key_value_heads"] * D
                 + c["num_attention_heads"] * D * H)
    expert = 3 * H * c["moe_intermediate_size"]
    norms, qk, router = 2 * H, 2 * D, H * c["num_experts"]
    assert (attention, expert, router) == (21_233_664, 6_193_152, 147_456)
    a_chip = 4 * (attention + norms + qk + router + 16 * expert) \
        + 2 * (c["vocab_size"] // 4) * H + H
    host = 4 * (attention + norms + qk + router + 64 * expert) \
        + 2 * c["vocab_size"] * H + H
    published = c["reduced_from"]["num_hidden_layers"] * (
        attention + norms + qk + router + 64 * expert) \
        + 2 * c["vocab_size"] * H + H
    assert (a_chip, host) == (595_154_176, 2_123_977_984)
    assert round(published / 1e9, 2) == 12.15
    assert "595,154,176 a chip" in c["deployment"]
    assert "2,123,977,984 parameters" in c["deployment"]


def test_the_window_cost_counts_the_band_against_a_hand_count():
    _, sizes = _published()
    cost = manifest.load_python(os.path.join(
        METRICS, "window_cost.py")).window_step_cost
    band = 1024 * 1025 // 2 + (4096 - 1024) * 1024
    io = 4096 * (32 * 128 + 2 * 4 * 128 + 32 * 128)
    got = cost(sizes, 1)
    assert got == {"flops": 3 * 2 * 32 * 7 * 128 * band,
                   "bytes": 3 * (3 * io * 2 + 2 * 4096 * 32 * 4)}
    assert cost(sizes, 4)["flops"] == 4 * got["flops"]
    from dataclasses import replace
    assert cost(replace(sizes, attention=None), 1) == {"flops": 0.0,
                                                       "bytes": 0}


def test_the_experts_cost_counts_the_groups_rows_against_a_hand_count():
    """``experts_roofline``'s operations and bytes here: four blocks over
    the group's 4 x 4096 positions at 8 routes each, 32768 rows a chip and
    block; every expert's matrices once a pass over the group."""
    cell, sizes = _published()
    cost = tiny.cost_beside_the_metrics("experts_cost.py",
                                        "experts_step_cost")
    rows = 4 * 4 * 4096 * 8
    assert rows / 4 / 4 == 32768 and rows / 4 / 64 == 2048
    matrices = 4 * 64 * 3 * 2304 * 896 * 2
    row_bytes = rows * (2304 + 2 * 896 + 896 + 2304) * 2
    # four sequences in ONE microbatch: every matrix once a pass
    got = cost(sizes, 4, cell.config, 1)
    assert got == {"flops": 3 * rows * 3 * 2 * 2304 * 896,
                   "bytes": 3 * (matrices + row_bytes)}
    assert cost(sizes, 4, cell.config, 4)["bytes"] == 3 * (
        4 * matrices + row_bytes)
    assert cell.config["reference"]["experts"] == {
        "held": "num_experts", "per_token": "num_experts_per_tok",
        "width": "moe_intermediate_size"}
    # over four chips at the peak: 24.7 ms a step, compute-bound
    least = flops.roofline_least_s(
        got, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, 4)
    assert least["bound"] == "compute"
    assert round(1e3 * least["least_s"], 1) == 24.7


def test_the_cells_own_entries_of_the_manifest():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    (work,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, TRAFFIC, 4)
    # the eleventh cell: the cap is two, and this cell takes the second
    assert man["workloads"][10]["name"] == CELL
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4][:2] == [
        "mistral7b_c4_tp2dp2z3", CELL]
    (entry,) = [c for c in man["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "mlp_layer_types"]
    assert not any(manifest.WIDTH_RE.search(k) for k in entry["reduced"])
    # the cell lists what it came with; later PRs read more of its trace
    assert MINE <= tiny.listed_for(man, CELL)
    mine = [m for m in man["per_layer"] if CELL in m.get("workloads", ())]
    assert all(m["moves"] == "tokens_per_s" for m in mine)
    assert {m["layer"] for m in mine} == {"collectives", "experts",
                                          "kernels", "step program"}
    cell = manifest.resolve_cell(man, CELL)
    names = {m["name"] for m in cell.per_layer}
    # every metric that names no cells is the new cell's too
    assert {m["name"] for m in man["per_layer"]
            if "workloads" not in m} <= names
    assert not names & {"laguna_gate_ms", "mlp_ms", "local_routes_pct",
                        "latent_proj_ms", "collective_overlapped_ms"}
    assert cell.traffic["overrides"] == [
        "data.dataset=random", "parallel.mixed_precision=bf16",
        "parallel.global_checkpoint=1", "parallel.global_ep_deg=4",
        "parallel.vocab_tp=4", "parallel.global_train_batch_size=4",
        "parallel.chunks=1", "model.seq_length=4096",
        "train.lr_warmup_iters=2000"]
    body = cell.config
    assert body["reduced_from"]["num_hidden_layers"] == 28
    assert all(len(body["reduced_from"][k]) == 28
               for k in ("layer_types", "mlp_layer_types"))
    assert body["program"]["expects"] == {
        "attention_cores": ["flash", "flash[w1024]"],
        "mosaic_calls_per_layer": 3}
    assert 0 < body["reference"]["loss_tolerance"] < 5e-3
    assert len(body["assumed"]) >= 6


def test_the_configuration_holds_every_number_of_the_catalog_row():
    """Every key of the catalog's ``config`` under the same key with the
    same value, but the three that ``reduced`` lists: the depth, and the two
    lists a block cut with it to the published blocks 0 to 3; no width among
    them, every expert and every row of the vocabulary as published."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Mellum2-12B-A2.5B-Instruct"]
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    body, reduced = cell.config, set(cell.config["reduced_from"])
    assert reduced == {"num_hidden_layers", "layer_types", "mlp_layer_types"}
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert body[key] == value, key
        else:
            assert body["reduced_from"][key] == value, key
            assert not manifest.WIDTH_RE.search(key), key
            if isinstance(value, list):
                assert body[key] == value[:4], key
    assert (body["num_hidden_layers"], body["num_experts"],
            body["vocab_size"]) == (4, 64, 98304)
    assert body["layer_types"] == AS_RUN


def _facts(leaves, steps, busy_s):
    reduced = SimpleNamespace(leaves=leaves, steps=steps, periods=len(steps),
                              busy_s=busy_s)
    return {"trace": {"reduced": [reduced]}, "sequences_per_step": 4,
            "chips": 4, "peaks": {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9}}


def test_the_readers_on_a_synthetic_step_map(monkeypatch):
    """Two traced steps laid over a map the program would have kept: each
    reader by the instructions' deepest scope; the exchange's exposed part
    is what no other leaf runs beside; the gauges where the program wrote
    them; and a program without the scopes (the parent) publishes nothing
    and does not raise."""
    from hetu_galvatron_tpu.observability import trace_analysis
    from hetu_galvatron_tpu.observability.registry import get_registry

    scopes = manifest.load_python(os.path.join(METRICS, "mellum_scopes.py"))
    read = lambda name: (lambda f: readers.read_metric(name, f))
    instructions = {
        "all-gather.1": ("moe/exchange/gather", "forward", "all-gather"),
        "fusion.2": ("moe/exchange/scatter", "forward",
                     "reduce-scatter.fused"),
        "fusion.3": ("moe/dispatch", "forward", None),
        "flash_attention_fwd.4": ("attn/window_core", "forward", None),
        "flash_attention_fwd.5": ("attn/core", "forward", None),
        "all-reduce.6": ("optimizer/update", "update", "all-reduce"),
        "fusion.7": ("moe/route", "forward", None),
        "fusion.8": ("moe/combine", "forward", None)}
    kept = {"map": {"instructions": instructions, "inferred": [],
                    "tails": {}}}
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: kept)
    ms = 1_000_000
    # the gather alone for 2 ms; the scatter for 4, the dispatch beside its
    # last 1; the cores; the all-reduce alone for 3; route and combine
    step = lambda t0: [("all-gather.1", t0, t0 + 2 * ms),
                       ("fusion.2", t0 + 2 * ms, t0 + 6 * ms),
                       ("fusion.3", t0 + 5 * ms, t0 + 8 * ms),
                       ("flash_attention_fwd.4", t0 + 8 * ms, t0 + 16 * ms),
                       ("flash_attention_fwd.5", t0 + 16 * ms, t0 + 21 * ms),
                       ("all-reduce.6", t0 + 21 * ms, t0 + 24 * ms),
                       ("fusion.7", t0 + 24 * ms, t0 + 25 * ms),
                       ("fusion.8", t0 + 25 * ms, t0 + 27 * ms)]
    _, sizes = _published()
    facts = {**_facts(step(0) + step(30 * ms),
                      [(0, 27 * ms), (30 * ms, 57 * ms)], busy_s=0.054),
             "sizes": sizes}
    assert scopes.exchange_ms(facts) == 6.0
    # 5 of the 6 ms a step with nothing beside them, over 27 ms busy a step
    assert scopes.exchange_exposed_pct(facts) == pytest.approx(
        100 * 2 * 5 / 54)
    assert read("collective_all_ms")(facts) == 9.0
    assert read("collective_all_exposed_pct")(facts) == \
        pytest.approx(100 * 2 * 8 / 54)
    assert read("window_core_ms")(facts) == 8.0
    assert read("full_core_ms")(facts) == 5.0
    assert read("moe_route_ms")(facts) == 1.0
    assert read("moe_dispatch_ms")(facts) == 3.0
    assert read("moe_combine_ms")(facts) == 2.0
    cost = manifest.load_python(os.path.join(
        METRICS, "window_cost.py")).window_step_cost(sizes, 4)
    least = cost["flops"] / (4 * 197e12)
    assert read("window_roofline")(facts) == pytest.approx(
        100 * least / 8e-3)
    assert facts["roofline_bounds"] == {"window_step_cost": "compute"}
    # the gauges: nothing where the program wrote none
    get_registry().gauge("moe/imbalance", layer="layer0").set(1.17)
    get_registry().gauge("moe/chip_imbalance", layer="layer0").set(1.02)
    assert scopes.chip_imbalance(facts) == 1.02
    assert read("moe_imbalance")(facts) == 1.17
    # the parent: no moe/exchange scope, no window scope in its map
    plain = {"map": {"instructions": {
        n: ("attn/core", p, None) for n, (_, p, _) in instructions.items()},
        "inferred": [], "tails": {}}}
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: plain)
    facts.pop("step_map_join")
    assert scopes.exchange_ms(facts) is None
    assert scopes.exchange_exposed_pct(facts) is None
    assert read("window_core_ms")(facts) is None
    assert read("window_roofline")(facts) is None
    assert read("collective_all_ms")(facts) is None
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: {})
    assert scopes.exchange_ms(_facts([], [], 0.0)) is None
    assert scopes.exchange_exposed_pct({}) is None
    assert read("full_core_ms")({}) is None


def test_every_file_the_benchmark_had_is_as_it_was():
    """Against the parent commit, where git and the commit are at hand:
    every data file it has under ``benchmark/`` is here byte for byte (what
    this PR brings under ``benchmark/`` are new files; the harness's own
    Python is a ``benchmark`` PR's to change, ``tiny.DATA_DIRS``), and
    ``BENCHMARK.json`` still begins with what it held."""
    was = tiny.data_files_as_they_were_at(PARENT, 100)
    tiny.assert_the_manifest_begins_with(was)
    now = manifest.load_manifest()
    # what came first after it is this PR's; later PRs add after these
    assert now["configs"][len(was["configs"])]["name"] == CONFIG
    assert now["workloads"][len(was["workloads"])]["name"] == CELL
    assert MINE <= tiny.listed_for(now, CELL)
