"""The seam between the program and its benchmark is a set of names. The
readers under ``benchmark/layer_metrics/`` find the program by the spans,
gauges and the one histogram it writes; a run in which one is missing is
refused on the chip as malformed. Here every such name is looked up the way
the benchmark does it, through the readers' own code and tables (imported,
never copied), after ``train_dist.main`` ran on the CPU at a tiny size: the
dense preset, an expert model with the dropless path of ``olmoe_c1_s4k``, a
tiny LFM2 (conv and attention blocks, a held share of the experts) with the
path of ``lfm2moe_c1_s8k``, and a tiny Granite hybrid (a Mamba-2 block and
an attention block) with the path of ``granite4h_c1_b1``. One case a name,
so a renamed span fails by its name."""

import glob
import io
import json
import logging
import os

import pytest

from benchmark import manifest, window, xplane
from hetu_galvatron_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
    set_registry,
)

pytestmark = pytest.mark.observability

ZOO = os.path.join(manifest.ROOT, "hetu_galvatron_tpu", "models", "configs")
READERS = os.path.join(manifest.ROOT, "benchmark", "layer_metrics")
host_phases = manifest.load_python(os.path.join(READERS, "host_phases.py"))
# the files whose readers look into the program's registry, not the trace
GAUGE_FILES = ("program_gauges.py", "moe_gauges.py", "lfm2_gauges.py")
lfm2_gauges = manifest.load_python(os.path.join(READERS, "lfm2_gauges.py"))
granite_scopes = manifest.load_python(
    os.path.join(READERS, "granite_scopes.py"))

TRACED, MEASURED = 3, 2
ITERS = window.WARMUP_STEPS + TRACED + MEASURED
SIZE = ["model.hidden_size=32", "model.num_hidden_layers=2",
        "model.num_attention_heads=2", "model.vocab_size=64",
        "model.seq_length=16", "model.max_position_embeddings=16",
        "model.make_vocab_size_divisible_by=1",
        "parallel.global_train_batch_size=4", "parallel.chunks=2",
        "parallel.num_devices=1", "data.dataset=random"]
PRESETS = {
    "dense": ["gpt2-small.yaml"] + SIZE,
    "moe": ["olmoe-1b-7b.yaml"] + SIZE + [
        "model.num_key_value_heads=2", "model.ffn_hidden_size=32",
        "model.num_experts=4", "model.moe_topk=2"],
    "lfm2": ["lfm2-24b-a2b.yaml"] + SIZE + [
        "model.num_hidden_layers=3",
        "model.layer_types=[conv,full_attention,conv]",
        "model.num_dense_layers=1", "model.num_key_value_heads=2",
        "model.ffn_hidden_size=32", "model.moe_ffn_hidden_size=16",
        "model.num_experts=8", "model.moe_topk=2",
        # a quarter of the experts: at a half and more the layer that holds
        # a share has no short body to take
        "model.moe_held_experts=2", "model.moe_first_held_expert=0"],
    "granite": ["granite-4.0-h-micro.yaml"] + SIZE + [
        "model.layer_types=[mamba,full_attention]",
        "model.num_key_value_heads=2", "model.ffn_hidden_size=32",
        "model.mamba_n_heads=4", "model.mamba_d_head=16",
        "model.mamba_d_state=8", "model.mamba_chunk_size=8"],
}
# what a cell without an expert layer does not write, and is not asked for;
# and what only a layer that holds a share of its experts writes
MOE_ONLY = {"moe_imbalance": ("moe",),
            "lfm2_local_routes_pct": ("lfm2",),
            "lfm2_moe_imbalance": ("lfm2",)}
# the named scopes the HLO-metadata join will look for (PERF.md section 7)
SCOPES = ("mixer/short_conv/in_proj", "mixer/short_conv/gate_conv",
          "mixer/short_conv/out_proj", "attn/qk_norm", "moe/route",
          "moe/dispatch", "moe/experts", "moe/combine")


def _gauge_readers():
    """name -> reader function of every per-layer metric that is read from
    the program's registry, as ``benchmark/layer_metrics/<name>.json`` says."""
    modules, readers = {}, {}
    for path in sorted(glob.glob(os.path.join(READERS, "*.json"))):
        with open(path) as f:
            reader = json.load(f)["reader"]
        if reader.get("file") not in GAUGE_FILES:
            continue
        mod = modules.setdefault(reader["file"], manifest.load_python(
            os.path.join(READERS, reader["file"])))
        name = os.path.splitext(os.path.basename(path))[0]
        readers[name] = getattr(mod, reader["function"])
    return readers


GAUGE_READERS = _gauge_readers()


@pytest.fixture(scope="module", params=sorted(PRESETS))
def run(request, tmp_path_factory):
    """One traced tiny run with the overrides the harness gives a cell (but
    for the far-away ``train_iters``), its registry left in place as the
    process's own for the readers to find, and the parent's put back after."""
    from hetu_galvatron_tpu.cli import train_dist

    tdir = str(tmp_path_factory.mktemp(request.param) / "trace")
    harness = [w for w in window.harness_overrides(tdir)
               if not w.startswith(("train.train_iters=",
                                    "profile.trace_iters="))]
    yaml, *size = PRESETS[request.param]
    argv = ([os.path.join(ZOO, yaml)] + size + harness
            + [f"train.train_iters={ITERS}",
               f"profile.trace_iters={TRACED}"])
    before = get_registry()
    reg = set_registry(MetricsRegistry())
    try:
        out, said = {}, io.StringIO()
        # the launcher's own logger (runtime/initialize.py), which does
        # not propagate to the root where pytest listens
        heard = logging.StreamHandler(said)
        logging.getLogger("hetu_galvatron_tpu").addHandler(heard)
        try:
            assert train_dist.main(argv, result=out) == 0
        finally:
            logging.getLogger("hetu_galvatron_tpu").removeHandler(heard)
        assert len(out["losses"]) == ITERS
        yield {"preset": request.param, "registry": reg, "result": out,
               "log": said.getvalue(),
               "trace": xplane.find_xplane(tdir),
               # the CPU's allocator states no limit; a chip's does
               "facts": {"memory": {"per_device": [{"bytes_limit": 2 ** 34}]}}}
    finally:
        set_registry(before)


@pytest.mark.parametrize("path", sorted(host_phases.PART_OF))
def test_every_span_a_gap_is_cut_into_is_entered_each_iteration(run, path):
    (h,) = [m for m in run["registry"].metrics()
            if m.name == "span_ms" and m.labels == {"path": path}]
    assert h.count == ITERS


@pytest.mark.parametrize("name", sorted(GAUGE_READERS))
def test_registry_reader_finds_what_the_program_wrote(run, name):
    assert get_registry() is run["registry"]
    value = GAUGE_READERS[name](run["facts"])
    if name in MOE_ONLY and run["preset"] not in MOE_ONLY[name]:
        assert value is None     # looked up, never made by asking
    else:
        assert value is not None and value > 0


@pytest.mark.parametrize("name", (lfm2_gauges.LOCAL_ROUTES_GAUGE,
                                  lfm2_gauges.IMBALANCE_GAUGE)
                         + lfm2_gauges.ROWS_GAUGES
                         + ("moe/short_dispatch_pct",))
def test_a_layer_that_holds_a_share_writes_its_gauges(run, name):
    found = [m for m in run["registry"].metrics() if m.name == name
             and m.labels.get("layer") == lfm2_gauges.FIRST_EXPERT_LAYER]
    if run["preset"] == "lfm2":
        assert len(found) == 1 and found[0].value > 0
    elif name != lfm2_gauges.IMBALANCE_GAUGE:
        assert not found


def test_the_rows_gauges_show_the_body_taken(run):
    """A quarter of the experts held at random weights: both microbatches
    of the last logged step took the short body, 32 of their 64 slots."""
    from hetu_galvatron_tpu.models.moe import short_rows

    gauge = {m.name: m.value for m in run["registry"].metrics()
             if m.labels.get("layer") == lfm2_gauges.FIRST_EXPERT_LAYER}
    if run["preset"] != "lfm2":
        assert "moe/short_dispatch_pct" not in gauge
        return
    held, computed = (gauge[name] for name in lfm2_gauges.ROWS_GAUGES)
    assert gauge["moe/short_dispatch_pct"] == 100.0
    assert 0 < held <= computed == 2 * short_rows(2 * 16 * 2, 2, 8) == 64


@pytest.mark.parametrize("mixer,ff,blocks", [
    ("conv", "dense", 1), ("full_attention", "experts", 1),
    ("conv", "experts", 1)])
def test_the_step_says_how_many_blocks_of_each_kind_it_holds(run, mixer, ff,
                                                             blocks):
    found = [m.value for m in run["registry"].metrics()
             if m.name == "step/blocks"
             and m.labels == {"mixer": mixer, "ff": ff}]
    if run["preset"] == "lfm2":
        assert found == [blocks]
        assert run["result"]["blocks"][f"{mixer}/{ff}"] == blocks
        assert run["result"]["attention_cores"].count("short_conv") == 2
    else:
        assert sum(run["result"]["blocks"].values()) == 2


@pytest.mark.parametrize("scope", SCOPES)
def test_the_lowered_step_carries_the_scope_names(scope):
    """The HLO metadata of a tiny LFM2 loss carries every named scope a
    per-layer metric will join on."""
    import jax
    import jax.numpy as jnp

    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.models.builder import (
        causal_lm_loss,
        init_causal_lm,
    )

    yaml, *size = PRESETS["lfm2"]
    cfg = args_from_cli([os.path.join(ZOO, yaml)] + size,
                        mode="train_dist").model
    params = jax.eval_shape(lambda k: init_causal_lm(k, cfg)[0],
                            jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = jax.jit(lambda p, t: causal_lm_loss(
        p, {"tokens": t, "labels": t}, cfg)).lower(params, tokens).as_text(
            debug_info=True)
    assert scope in text


@pytest.mark.parametrize("name,value", [
    ("ssd/chunks", 2),                  # 16 positions in chunks of 8
    ("ssd/state_bytes", 4 * 64 * 8),    # 4 heads x 16 x 8 float32
])
def test_a_state_space_block_says_what_it_carries(run, name, value):
    found = {m.labels["layer"]: m.value for m in run["registry"].metrics()
             if m.name == name}
    assert found == ({"layer0": value} if run["preset"] == "granite" else {})


def test_a_mamba_block_reports_its_own_operator(run):
    blocks = [m.value for m in run["registry"].metrics()
              if m.name == "step/blocks"
              and m.labels == {"mixer": "mamba", "ff": "dense"}]
    if run["preset"] != "granite":
        assert not blocks and "mamba2" not in run["result"]["attention_cores"]
        return
    assert blocks == [1] and run["result"]["blocks"] == {
        "mamba/dense": 1, "full_attention/dense": 1}
    assert run["result"]["attention_cores"] == ["mamba2", "xla"]


@pytest.mark.parametrize("scope", granite_scopes.SCOPES)
def test_the_step_report_keeps_the_instructions_under_a_scope(run, scope):
    """The map the ``granite_*`` readers join a trace to: instruction names
    of the compiled step's HLO under each ``mixer/mamba/*`` scope, in
    ``train()``'s result and where a reader in this process finds them."""
    from hetu_galvatron_tpu.observability import trace_analysis

    kept = run["result"]["scope_instructions"]
    if run["preset"] != "granite":
        assert kept is None
        return
    assert kept[scope] and len(set(kept[scope])) == len(kept[scope])
    mine = trace_analysis.step_scopes()
    assert mine["scopes"][scope] == kept[scope]
    assert set(kept[scope]) <= mine["instructions"]
    # the scopes do not share an instruction
    others = {n for s in granite_scopes.SCOPES if s != scope
              for n in kept[s]}
    assert not others & set(kept[scope])


def test_the_step_report_says_whether_the_scan_kernels_engaged(run):
    """``ssd/mosaic_calls``: the Mosaic calls among the instructions under
    ``mixer/mamba/ssd``. A step compiled for a CPU holds none (the scan ran
    in its ``jax.numpy`` form), and the gauge, the result and the ``step
    report:`` line say 0; the block is ``mamba2`` on the ``attention
    cores:`` line whichever way its scan runs."""
    gauges = [m.value for m in run["registry"].metrics()
              if m.name == "ssd/mosaic_calls"]
    (report,) = [line for line in run["log"].splitlines()
                 if "step report:" in line]
    if run["preset"] != "granite":
        assert not gauges and run["result"]["ssd_mosaic_calls"] is None
        assert "under mixer/mamba/ssd" not in report.split("Mosaic")[1]
        return
    assert gauges == [0] and run["result"]["ssd_mosaic_calls"] == 0
    assert "0 Mosaic calls (0 under mixer/mamba/ssd)," in report
    assert "attention cores: 1 x mamba2, 1 x xla" in run["log"]


def test_a_mosaic_call_is_counted_under_its_scope():
    from hetu_galvatron_tpu.observability.trace_analysis import (
        SSD_SCOPE,
        scope_instructions,
    )

    hlo = """HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %inner.1 = f32[8]{0} exponential(%p), metadata={op_name="jit(step)/mixer/mamba/ssd/exp"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %ssd_scan_fwd.3 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(mixer/mamba)/ssd/ssd_scan_fwd/pallas_call"}
  %ssd_scan_bwd.4 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/checkpoint/mixer/mamba/ssd/ssd_scan_bwd/pallas_call"}
  %flash_attention_fwd.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/flash_attention_fwd/pallas_call"}
  ROOT %fusion.7 = f32[8]{0} fusion(%ssd_scan_fwd.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/mixer/mamba/ssd/exp"}
}
"""
    found = scope_instructions(hlo, (SSD_SCOPE,))
    assert found["scopes"][SSD_SCOPE] == [
        "ssd_scan_fwd.3", "ssd_scan_bwd.4", "fusion.7"]
    assert found["mosaic_calls"] == {
        "ssd_scan_fwd.3", "ssd_scan_bwd.4", "flash_attention_fwd.1"}
    assert sum(n in found["mosaic_calls"]
               for n in found["scopes"][SSD_SCOPE]) == 2


def _traced_by_hand(names, known_only=True):
    """A steady window of two periods in which every named instruction ran
    0.1 ms a step, one after the other: what ``xplane.reduce_device`` hands
    a reader, without a TPU."""
    ms, leaves, t = 1e6, [], 0.0
    steps = [(0.0, 50 * ms), (60 * ms, 110 * ms), (120 * ms, 170 * ms)]
    for start, _ in steps[:2]:
        t = start
        for n in list(names) + ([] if known_only else ["stranger.1"]):
            leaves.append((n, t, t + 0.1 * ms))
            t += 0.1 * ms
    return xplane.Reduced(0, steps, (0.0, 120 * ms), leaves,
                          [(n, e - s) for n, s, e in leaves], [])


def test_the_granite_readers_join_a_trace_to_the_map(run):
    from benchmark import flops, peaks

    if run["preset"] != "granite":
        return
    kept = run["result"]["scope_instructions"]
    every = [n for s in granite_scopes.SCOPES for n in kept[s]]
    ssd = [n for s in granite_scopes.SSD_SCOPES for n in kept[s]]
    facts = {"trace": {"reduced": [_traced_by_hand(every)]},
             "sizes": flops.Sizes(layers=10, hidden=2048, heads=32,
                                  kv_heads=8, head_dim=64, ffn=8192,
                                  ffn_matrices=3, vocab=12544, seq=8192),
             "sequences_per_step": 1, "chips": 1,
             "peaks": peaks.peaks_of("TPU v5 lite")}
    assert len(every) < 500    # they fit a step of the hand-made trace
    assert granite_scopes.mamba_ms(facts) == pytest.approx(0.1 * len(every))
    assert granite_scopes.ssd_ms(facts) == pytest.approx(0.1 * len(ssd))
    assert granite_scopes.ssd_time_share_pct(facts) == pytest.approx(
        100.0 * len(ssd) / len(every))
    assert 0 < granite_scopes.ssd_roofline(facts) < 100
    # no trace, or an operation inside a step that is no instruction of the
    # step's HLO: nothing is published
    assert granite_scopes.ssd_ms({}) is None
    stranger = {**facts, "trace": {"reduced": [
        _traced_by_hand(every, known_only=False)]}}
    assert granite_scopes.ssd_ms(stranger) is None


def test_without_a_map_the_granite_readers_publish_nothing(monkeypatch):
    """What the parent commit gives them: no ``step_scopes`` in the
    program, or an empty one."""
    from hetu_galvatron_tpu.observability import trace_analysis

    facts = {"trace": {"reduced": [_traced_by_hand(["fusion.1"])]}}
    monkeypatch.setattr(trace_analysis, "_STEP_SCOPES", {})
    assert granite_scopes.mamba_ms(facts) is None
    monkeypatch.delattr(trace_analysis, "step_scopes")
    assert granite_scopes.ssd_roofline(facts) is None


def test_iteration_spans_are_flat_siblings_on_the_dispatching_thread(run):
    # read_planes keeps the train/* TraceMes of the thread that dispatches
    spans = host_phases.read_planes(run["trace"]).spans
    steps = sorted({step for _, _, _, step in spans})
    assert steps == list(range(window.WARMUP_STEPS,
                               window.WARMUP_STEPS + TRACED))
    for it in steps:
        mine = [s for s in spans if s[3] == it]
        assert set(host_phases.PART_OF) <= {name for name, *_ in mine}
        assert all(a[2] <= b[1] for a, b in zip(mine, mine[1:])), mine


def test_iter_time_histogram_gets_one_sample_a_measured_step(run):
    (h,) = [m for m in run["registry"].metrics()
            if m.name == window.HISTOGRAM and not m.labels]
    assert h.count == MEASURED
