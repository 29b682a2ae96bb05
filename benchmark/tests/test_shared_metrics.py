"""One per-layer metric a reader, not one a configuration (PR 65): the
shared entries and the cells they list, the one cost function of the routed
experts held to what each deleted cost file returned, the readers by named
scope (``scope_time``, a ``roofline`` over ``scopes``) and by gauge on a map
and a registry made by hand, and what the list may not hold again."""

import functools
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import flops, manifest, readers, reference
from benchmark.tests import pr65

METRICS = os.path.join(manifest.ROOT, "benchmark", "layer_metrics")
MAN = manifest.load_manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the five copies of one gauge reader that stay: tier-1's
# tests/observability/test_benchmark_seam.py holds each by its name
# (``MOE_ONLY``), and a ``benchmark`` PR may not edit it (PERF.md section 7)
KEPT_COPIES = {f"{p}_moe_imbalance"
               for p in ("lfm2", "xing", "kimi", "laguna", "kimivl")}


def _reader(name):
    return manifest.read_json(manifest.layer_metric_path(
        manifest.ROOT, name))["reader"]


@functools.lru_cache(maxsize=None)
def _as_run(cell_name):
    """(cell, sizes, sequences a step, microbatches a step) as ``run.py``
    makes them from the cell's own command line."""
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        resolve_model_config,
    )

    cell = manifest.resolve_cell(MAN, cell_name)
    args = resolve_model_config(args_from_cli(
        manifest.train_argv(cell, seed=0), mode="train_dist"))
    sizes = flops.Sizes.of(args.model)
    family = reference.load_family(cell.config["reference"]["family"])
    if hasattr(family, "attention_blocks"):
        sizes = sizes.with_attention(
            family.attention_blocks(cell.config),
            beside=manifest.second_stack_depth(cell.config))
    return (cell, sizes, args.parallel.global_train_batch_size,
            args.parallel.chunks)


def test_the_list_has_room_and_no_two_entries_share_a_reader():
    assert manifest.check_manifest(MAN) == []
    assert len(MAN["per_layer"]) <= 100
    by_reader = {}
    for m in MAN["per_layer"]:
        by_reader.setdefault(json.dumps(_reader(m["name"]), sort_keys=True),
                             set()).add(m["name"])
    assert [names for names in by_reader.values() if len(names) > 1] == [
        KEPT_COPIES]
    # every file of a metric is an entry, and nothing PR 65 took away is back
    files = {f[:-5] for f in os.listdir(METRICS) if f.endswith(".json")}
    assert files == {m["name"] for m in MAN["per_layer"]}
    assert not files & set(pr65.RETIRED)
    assert {new for new in pr65.RETIRED.values() if new} <= files
    # the bounds are PR 58's, and nothing here moves them
    assert [(m["name"], m["bound"]) for m in MAN["end_to_end"]] == [
        ("tokens_per_s", 0.024), ("mfu_pct", 0.024), ("setup_s", 0.1)]
    assert (len(MAN["configs"]), len(MAN["workloads"])) == (12, 13)


@pytest.mark.parametrize("name", [m["name"] for m in MAN["per_layer"]])
def test_no_reader_names_what_libtpu_calls_the_grouped_matmul(name):
    assert "ragged-dot" not in json.dumps(_reader(name))


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_lists_this_set_of_metrics(cell):
    listed = {m["name"] for m in MAN["per_layer"]
              if cell in m.get("workloads", ())}
    assert listed == pr65.LISTED[cell]
    # whatever names no cells is every cell's
    resolved = {m["name"] for m in manifest.resolve_cell(MAN, cell).per_layer}
    assert resolved == listed | {m["name"] for m in MAN["per_layer"]
                                 if "workloads" not in m}


@pytest.mark.parametrize("name", ["experts_ms", "experts_time_share_pct",
                                  "experts_roofline"])
def test_an_expert_metric_lists_the_cells_whose_configuration_has_experts(
        name):
    (entry,) = [m for m in MAN["per_layer"] if m["name"] == name]
    have = [w["name"] for w in MAN["workloads"]
            if "experts" in manifest.resolve_cell(
                MAN, w["name"]).config["reference"]]
    assert entry["workloads"] == have and len(have) == 7
    assert _reader(name)["scopes"] == ["moe/experts"]
    assert (entry["layer"], entry["moves"]) == ("experts", "tokens_per_s")


@pytest.mark.parametrize("cost,cell", [
    (cost, cell) for cost, cells in pr65.COSTS.items() for cell in cells])
def test_the_shared_cost_returns_what_the_cells_own_file_returned(cost, cell):
    """To the last digit: the yardstick of each cell's roofline stays the
    one the ledger has."""
    gone, want_flops, want_bytes = pr65.COSTS[cost][cell]
    assert not os.path.exists(os.path.join(METRICS, gone)) or gone == (
        "experts_cost.py")
    file = {"experts_step_cost": "experts_cost.py",
            "window_step_cost": "window_cost.py"}[cost]
    fn = getattr(manifest.load_python(os.path.join(METRICS, file)), cost)
    run, sizes, sequences, microbatches = _as_run(cell)
    need = readers.cost_of(fn, {
        "sizes": sizes, "sequences_per_step": sequences,
        "microbatches_per_step": microbatches, "config": run.config})
    assert (need["flops"], need["bytes"]) == (want_flops, want_bytes)
    assert repr(float(need["flops"])) == repr(float(want_flops))
    assert repr(float(need["bytes"])) == repr(float(want_bytes))


@pytest.mark.parametrize("cell", [c for c in CELLS
                                  if c not in pr65.COSTS["experts_step_cost"]])
def test_a_configuration_without_experts_counts_none(cell):
    """The file says which key is which; a file that says nothing has no
    such layer, whatever keys of that name it carries
    (``granite-4.0-h-micro-p1`` states ``num_experts_per_tok: 0``)."""
    run, sizes, sequences, microbatches = _as_run(cell)
    fn = manifest.load_python(os.path.join(
        METRICS, "experts_cost.py")).experts_step_cost
    assert readers.cost_of(fn, {
        "sizes": sizes, "sequences_per_step": sequences,
        "microbatches_per_step": microbatches, "config": run.config}) is None


def test_the_scans_cost_is_bytes_alone_and_the_configurations():
    run, sizes, sequences, _ = _as_run("phi4flash_c1_b1")
    fn = manifest.load_python(os.path.join(
        METRICS, "selective_scan_cost.py")).selective_scan_step_cost
    need = readers.cost_of(fn, {"sizes": sizes, "config": run.config,
                                "sequences_per_step": sequences})
    assert need == {"flops": 0,
                    "bytes": 2 * 8192 * (36 * 5120 + 24 * 16)}
    least = flops.roofline_least_s(need, PEAKS)
    assert least["bound"] == "memory"
    assert round(1e3 * least["least_s"], 2) == 3.69
    # a stack without such a block counts none
    other, sizes, sequences, _ = _as_run("granite4h_c1_b1")
    assert readers.cost_of(fn, {"sizes": sizes, "config": other.config,
                                "sequences_per_step": sequences}) is None


def _facts(leaves, steps, busy_s, **more):
    reduced = SimpleNamespace(leaves=leaves, steps=steps, periods=len(steps),
                              busy_s=busy_s)
    return {"trace": {"reduced": [reduced]}, "sequences_per_step": 1,
            "microbatches_per_step": 1, "chips": 1, "peaks": PEAKS, **more}


PHI_SCOPES = {
    "selective_scan_fwd.1": "mixer/mamba1/scan",
    "causal_conv_fwd.2": "mixer/mamba1/conv",
    "fusion.3": "mixer/mamba1/in_proj", "fusion.4": "mixer/mamba1/x_proj",
    "fusion.5": "mixer/mamba1/gate", "fusion.6": "mixer/mamba1/out_proj",
    "fusion.7": "mixer/gmu/in_proj", "fusion.8": "mixer/gmu/gate",
    "fusion.9": "mixer/gmu/out_proj", "fusion.10": "attn/diff",
    "flash_attention_fwd.11": "attn/cross_core",
    "flash_attention_fwd.12": "attn/window_core",
    "flash_attention_fwd.13": "attn/core",
    "ragged-dot-none.14": "moe/experts", "fusion.15": "moe/experts",
    "fusion.16": "attn/latent_proj"}


def test_the_readers_by_scope_on_a_synthetic_step_map(monkeypatch):
    """Two traced steps, every instruction i ms long (i its number), laid
    over a map the program would have kept: a reader's value is the summed
    time of the instructions whose deepest scope it names, whatever
    implements them (libtpu's grouped matmul and a fusion beside it under
    ``moe/experts`` alike)."""
    from hetu_galvatron_tpu.observability import trace_analysis

    kept = {"map": {"instructions": {
        n: (s, "forward", None) for n, s in PHI_SCOPES.items()},
        "inferred": [], "tails": {}}}
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: kept)
    ms, number = 1_000_000, lambda n: int(n.rsplit(".", 1)[1])

    def step(t0):
        out, t = [], t0
        for n in PHI_SCOPES:
            out.append((n, t, t + number(n) * ms))
            t += number(n) * ms
        return out
    total = sum(number(n) for n in PHI_SCOPES)            # 136 ms a step
    run, sizes, _, _ = _as_run("phi4flash_c1_b1")
    facts = _facts(step(0) + step(200 * ms),
                   [(0, total * ms), (200 * ms, (200 + total) * ms)],
                   busy_s=2 * total / 1e3, sizes=sizes, config=run.config)
    read = lambda name: readers.read_metric(name, facts)
    assert read("selective_scan_ms") == 1 + 2
    assert read("selective_scan_time_share_pct") == pytest.approx(
        100 * 3 / total)
    assert read("mamba1_mixer_ms") == 1 + 2 + 3 + 4 + 5 + 6
    assert read("gmu_ms") == 7 + 8 + 9
    assert read("attn_diff_ms") == 10 and read("cross_core_ms") == 11
    assert read("window_core_ms") == 12 and read("full_core_ms") == 13
    assert read("experts_ms") == 14 + 15
    assert read("experts_time_share_pct") == pytest.approx(100 * 29 / total)
    assert read("latent_proj_ms") == 16
    # 3.69 ms of memory traffic by the count over the 3 measured: the map is
    # made by hand, and a share over 100 is what the driver would refuse
    assert read("selective_scan_roofline") == pytest.approx(
        100 * 3.6949 / 3, rel=1e-3)
    assert facts["roofline_bounds"] == {"selective_scan_step_cost": "memory"}
    # this configuration has no routed experts: time under the scope, and
    # nothing to hold it to, so nothing is said (never a 0)
    assert read("experts_roofline") is None
    olmoe, sizes, sequences, microbatches = _as_run("olmoe_c1_s4k")
    with_experts = {**facts, "sizes": sizes, "config": olmoe.config,
                    "sequences_per_step": sequences,
                    "microbatches_per_step": microbatches}
    assert readers.read_metric("experts_roofline", with_experts) == \
        pytest.approx(100 * 25.12 / 29, rel=1e-3)
    # a map without the scope, no map, no trace: nothing, and no raise
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: {"map": {
        "instructions": {n: ("mlp", "forward", None) for n in PHI_SCOPES},
        "inferred": [], "tails": {}}})
    facts.pop("step_map_join")
    for name in ("selective_scan_ms", "selective_scan_roofline", "gmu_ms",
                 "experts_ms", "experts_time_share_pct", "window_core_ms"):
        assert read(name) is None, name
    assert read("mlp_ms") == total
    monkeypatch.setattr(trace_analysis, "step_scopes", lambda: {})
    facts.pop("step_map_join")
    assert read("experts_ms") is None and read("mamba1_mixer_ms") is None
    assert readers.read_metric("experts_ms", {}) is None
    assert readers.read_metric("selective_scan_roofline", {}) is None


def test_a_roofline_reader_takes_a_pattern_or_scopes_and_not_both():
    facts = _facts([], [], 0.0)
    here = dict(cost="flash_step_cost", _dir=METRICS)
    with pytest.raises(ValueError, match="one of the two"):
        readers.read_roofline(facts, **here)
    with pytest.raises(ValueError, match="one of the two"):
        readers.read_roofline(facts, pattern="^x", scopes=["mlp"], **here)


@pytest.fixture
def registry():
    from hetu_galvatron_tpu.observability.registry import (
        MetricsRegistry,
        get_registry,
        set_registry,
    )

    before = get_registry()
    try:
        yield set_registry(MetricsRegistry())
    finally:
        set_registry(before)


@pytest.mark.parametrize("gauge", ["ssd/mosaic_calls", "kda/mosaic_calls",
                                   "selective/mosaic_calls"])
def test_scan_mosaic_calls_finds_each_of_its_gauges(registry, gauge):
    """Whichever the cell's program set: a model has one kind of recurrent
    mixer's gauge; 0 (the ``jax.numpy`` scan ran) is a reading, not
    nothing."""
    assert readers.read_metric("scan_mosaic_calls", {}) is None
    registry.gauge("kda/blocks").set(4)          # not asked for
    assert readers.read_metric("scan_mosaic_calls", {}) is None
    registry.gauge(gauge).set(0)
    assert readers.read_metric("scan_mosaic_calls", {}) == 0.0
    registry.gauge(gauge).set(4)
    assert readers.read_metric("scan_mosaic_calls", {}) == 4.0


def test_local_routes_reads_the_first_layer_of_the_stack_that_wrote_one(
        registry):
    read = lambda: readers.read_metric("local_routes_pct", {})
    assert read() is None
    registry.gauge("moe/imbalance", layer="layer1").set(1.2)  # another name
    assert read() is None
    # the further depth's layer carries no number and comes last
    registry.gauge("moe/local_routes_pct", layer="mtp").set(11.0)
    assert read() == 11.0
    registry.gauge("moe/local_routes_pct", layer="layer10").set(12.0)
    registry.gauge("moe/local_routes_pct", layer="layer2").set(12.6)
    assert read() == 12.6
    registry.gauge("moe/local_routes_pct", layer="layer1").set(12.44)
    assert read() == 12.44
    # a gauge of the name with another label set is not the layer's
    registry.gauge("moe/local_routes_pct", layer="layer0", chip="1").set(9.0)
    assert read() == 12.44
