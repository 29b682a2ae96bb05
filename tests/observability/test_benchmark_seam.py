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

import contextlib
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
step_map = manifest.load_python(os.path.join(READERS, "step_map.py"))
chip_skew = manifest.load_python(os.path.join(READERS, "chip_skew.py"))

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
        # a quarter of the experts: at four fifths and more the first chunk
        # of a layer that holds a share is the whole buffer
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
            "lfm2_moe_imbalance": ("lfm2",),
            # the same readers under the newer cell's names: a held share's
            # gauges, which of these presets the lfm2 one alone writes
            "xing_moe_imbalance": ("lfm2",),
            "xing_local_routes_pct": ("lfm2",),
            "kimi_moe_imbalance": ("lfm2",),
            "kimi_local_routes_pct": ("lfm2",),
            "laguna_moe_imbalance": ("lfm2",),
            "laguna_local_routes_pct": ("lfm2",),
            "kimivl_moe_imbalance": ("lfm2",),
            "kimivl_local_routes_pct": ("lfm2",)}
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


@contextlib.contextmanager
def _launched(argv):
    """``train_dist.main(argv)`` with a registry of its own, left in place
    as the process's for the readers to find and the parent's put back
    after: the registry, ``train()``'s result and the launcher's log."""
    from hetu_galvatron_tpu.cli import train_dist

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
        yield {"registry": reg, "result": out, "log": said.getvalue()}
    finally:
        set_registry(before)


@pytest.fixture(scope="module", params=sorted(PRESETS))
def run(request, tmp_path_factory):
    """One traced tiny run with the overrides the harness gives a cell (but
    for the far-away ``train_iters``), its registry left in place as the
    process's own for the readers to find, and the parent's put back after."""
    tdir = str(tmp_path_factory.mktemp(request.param) / "trace")
    harness = [w for w in window.harness_overrides(tdir)
               if not w.startswith(("train.train_iters=",
                                    "profile.trace_iters="))]
    yaml, *size = PRESETS[request.param]
    argv = ([os.path.join(ZOO, yaml)] + size + harness
            + [f"train.train_iters={ITERS}",
               f"profile.trace_iters={TRACED}"])
    with _launched(argv) as ran:
        assert len(ran["result"]["losses"]) == ITERS
        yield {"preset": request.param, **ran,
               "trace": xplane.find_xplane(tdir), "trace_dir": tdir,
               # the CPU's allocator states no limit; a chip's does
               "facts": {"memory": {"per_device": [{"bytes_limit": 2 ** 34}]}}}


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
                         + ("moe/short_dispatch_pct", "moe/overflow_chunks"))
def test_a_layer_that_holds_a_share_writes_its_gauges(run, name):
    found = [m for m in run["registry"].metrics() if m.name == name
             and m.labels.get("layer") == lfm2_gauges.FIRST_EXPERT_LAYER]
    if run["preset"] == "lfm2":
        # a balanced step takes no pass behind the first chunk
        assert len(found) == 1 and (found[0].value > 0) == (
            name != "moe/overflow_chunks")
    elif name != lfm2_gauges.IMBALANCE_GAUGE:
        assert not found


def test_the_rows_gauges_show_the_body_taken(run):
    """A quarter of the experts held at random weights: both microbatches
    of the last logged step stopped at the first chunk, 24 of their 64
    slots, and took no counted pass behind it."""
    from hetu_galvatron_tpu.models.moe import short_rows

    gauge = {m.name: m.value for m in run["registry"].metrics()
             if m.labels.get("layer") == lfm2_gauges.FIRST_EXPERT_LAYER}
    if run["preset"] != "lfm2":
        assert "moe/short_dispatch_pct" not in gauge
        return
    held, computed = (gauge[name] for name in lfm2_gauges.ROWS_GAUGES)
    assert gauge["moe/short_dispatch_pct"] == 100.0
    assert gauge["moe/overflow_chunks"] == 0
    assert 0 < held <= computed == 2 * short_rows(2 * 16 * 2, 2, 8) == 48


def test_the_step_report_says_each_expert_layers_body(run):
    """``step report: ..., moe[layer0] whole`` and ``train()``'s
    ``expert_bodies``: a layer that holds every expert compiled to the one
    body (its first chunk is every route of a microbatch), one that holds a
    quarter to a first chunk of 24 of the microbatch's 64 routes and passes
    of 8; the gauge ``moe/whole_body_layers`` counts the first kind."""
    want = {"moe": {"layer0": "whole", "layer1": "whole"},
            "lfm2": {"layer1": "counted 24/64 +8",
                     "layer2": "counted 24/64 +8"}}.get(run["preset"], {})
    assert run["result"]["expert_bodies"] == want
    (line,) = [line for line in run["log"].splitlines()
               if "step report:" in line]
    assert ("moe[" in line) == bool(want)
    for name, body in want.items():
        assert f", moe[{name}] {body}," in line
    gauges = [m.value for m in run["registry"].metrics()
              if m.name == "moe/whole_body_layers"]
    assert gauges == ([sum(b == "whole" for b in want.values())]
                      if want else [])


def test_the_step_report_says_whether_the_experts_kernels_engaged(run):
    """``experts/mosaic_calls``: the program's own grouped-matmul kernels
    under ``moe/experts`` in the compiled step. A step compiled for a CPU
    holds none (``lax.ragged_dot`` ran), and the gauge, ``train()``'s
    ``experts_mosaic_calls`` and the ``step report:`` line say 0; a model
    without an expert block writes none of the three."""
    gauges = [m.value for m in run["registry"].metrics()
              if m.name == "experts/mosaic_calls"]
    (line,) = [line for line in run["log"].splitlines()
               if "step report:" in line]
    if run["result"]["expert_bodies"]:
        assert gauges == [0] and run["result"]["experts_mosaic_calls"] == 0
        assert ", experts/mosaic_calls 0" in line
    else:
        assert not gauges and run["result"]["experts_mosaic_calls"] is None
        assert "experts/mosaic_calls" not in line


# the expert preset across four devices, its experts inside the exchange:
# the plan of ``mellum2_c4_ep4`` (ep 4 carved from dp 4, a sequence a device
# in one microbatch), whose log line counts chip by chip
EXCHANGED = PRESETS["moe"] + [
    "model.num_experts=8", "parallel.num_devices=4", "parallel.chunks=1",
    "parallel.global_ep_deg=4", "train.train_iters=2"]


@pytest.fixture(scope="module")
def exchange_run():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    yaml, *size = EXCHANGED
    with _launched([os.path.join(ZOO, yaml)] + size) as ran:
        yield ran


@pytest.mark.parametrize("name", chip_skew.CHIP_GAUGES + (
    chip_skew.FULLEST_CHIP_GAUGE, chip_skew.STEP_PASSES_HISTOGRAM,
    "ep/first_chunk_rows", "ep/pass_rows"))
def test_a_layer_inside_the_exchange_counts_chip_by_chip(exchange_run, name):
    """What ``chip_skew.py``'s readers and ``tools/trace_by_scope.py``'s
    table look up (names imported, never copied): a gauge a layer and chip,
    one histogram observation a logged step, and the log line and the step
    report saying the same. Four devices of two experts each over 16 tokens
    at top-2: a chip's first chunk is 40 rows of 128 slots, a pass 8."""
    from hetu_galvatron_tpu.models.moe import overflow_rows, short_rows

    found = [m for m in exchange_run["registry"].metrics()
             if m.name == name]
    first, chunk = short_rows(128, 2, 8), overflow_rows(128, 2, 8)
    assert (first, chunk) == (40, 8)
    assert get_registry() is exchange_run["registry"]
    lines = [line for line in exchange_run["log"].splitlines()
             if "expert exchange:" in line or "step report:" in line]
    if name in chip_skew.CHIP_GAUGES:
        # one a layer and chip; every route is some chip's
        assert sorted((m.labels["layer"], m.labels["chip"])
                      for m in found) == [
            (f"layer{i}", str(c)) for i in range(2) for c in range(4)]
        if name == "moe/chip_rows":
            assert sum(m.value for m in found) == 2 * 4 * 16 * 2
        else:
            rows = {(m.labels["layer"], m.labels["chip"]): m.value
                    for m in exchange_run["registry"].metrics()
                    if m.name == "moe/chip_rows"}
            for m in found:
                took = -(-max(rows[(m.labels["layer"], m.labels["chip"])]
                              - first, 0) // chunk)
                assert m.value == took
    elif name == chip_skew.FULLEST_CHIP_GAUGE:
        assert sorted(m.labels["layer"] for m in found) == [
            "layer0", "layer1"]
        fullest = max(m.value for m in exchange_run["registry"].metrics()
                      if m.name == "moe/chip_rows")
        assert chip_skew.fullest_chip_pct({}) == 100.0 * fullest / first
    elif name == chip_skew.STEP_PASSES_HISTOGRAM:
        (h,) = found
        assert h.count == 2             # one a logged step
        assert chip_skew.step_passes({}) == h.total / 2
        last = sum(max(m.value for m in exchange_run["registry"].metrics()
                       if m.name == "moe/chip_passes"
                       and m.labels["layer"] == layer)
                   for layer in ("layer0", "layer1"))
        assert h.snapshot()["max"] >= last
    else:
        (g,) = found
        assert g.value == {"ep/first_chunk_rows": first,
                           "ep/pass_rows": chunk}[name]
        assert exchange_run["result"]["ep"][name[3:]] == g.value
        assert all(f"{name} {g.value:.0f}" in lines[0]
                   or f"{name[3:].replace('_', ' ')}" in line
                   for line in lines)
    # which device each chip of the group is, for the trace's planes
    chips = exchange_run["result"]["ep"]["chip_devices"]
    assert sorted(d for ids in chips for d in ids) == [0, 1, 2, 3]
    assert {int(m.labels["device"]): m.value
            for m in exchange_run["registry"].metrics()
            if m.name == "ep/chip_of_device"} == {
                d: r for r, ids in enumerate(chips) for d in ids}
    assert f"ep 4 first-chunk rows {first} pass rows {chunk}" in lines[1]
    # the body a chip's share compiled to, over its group's routes
    assert f", moe[layer0] counted {first}/128 +{chunk}," in lines[1]
    assert exchange_run["result"]["expert_bodies"] == {
        f"layer{i}": f"counted {first}/128 +{chunk}" for i in range(2)}
    assert f"ep/first_chunk_rows {first}, ep/pass_rows {chunk}" in lines[0]


def test_the_log_line_says_the_passes_by_chip():
    """``moe[layer0] chips 1.188 passes 0/1/0/0``, and the gauges and the
    one histogram observation of a step that the line is formatted from."""
    from hetu_galvatron_tpu.core.args_schema import CoreArgs
    from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
        RuntimeProfiler,
    )
    import numpy as np

    reg = MetricsRegistry()
    stats = {"tokens_per_expert": np.full(8, 32.0),
             # every expert's rows, chip by chip: chip 1's add up to 76
             "held_tokens_per_expert": np.array(
                 [30.0, 30.0, 40.0, 36.0, 30.0, 30.0, 30.0, 30.0]),
             "load_balance_loss": 1.0, "z_loss": 0.0,
             "rows_held": 64.0, "rows_computed": 42.0,
             "overflow_chunks": 0.25, "short_dispatch": 0.75,
             "rows_by_chip": np.array([60.0, 76.0, 60.0, 60.0]),
             "passes_by_chip": np.array([0.0, 1.0, 0.0, 0.0])}
    line = RuntimeProfiler(CoreArgs(), registry=reg, pass_rows=8
                           ).iteration_log(0, {"moe": {"layer0": stats,
                                                       "layer1": stats}})
    assert "moe[layer0] chips 1.188 passes 0/1/0/0" in line
    gauges = {(m.name, m.labels.get("layer")): m.value
              for m in reg.metrics() if m.kind == "gauge"}
    # the first chunk's rows a step: rows_computed less the passes' rows
    assert gauges[("moe/fullest_chip_pct", "layer0")] == 100.0 * 76 / 40
    assert "moe/short_dispatch" not in {name for name, _ in gauges}
    assert gauges[("moe/short_dispatch_pct", "layer0")] == 75.0
    (h,) = [m for m in reg.metrics()
            if m.name == chip_skew.STEP_PASSES_HISTOGRAM]
    # two layers whose fullest chip took one pass each
    assert (h.count, h.total) == (1, 2.0)
    # without the pass's rows and with a pass taken the first chunk's rows
    # are not known: the gauge is left as it was, never guessed
    RuntimeProfiler(CoreArgs(), registry=reg).iteration_log(
        0, {"moe": {"layer0": {**stats, "held_tokens_per_expert": np.array(
            [30.0, 30.0, 40.0, 40.0, 30.0, 30.0, 30.0, 30.0])}}})
    assert [m.value for m in reg.metrics()
            if m.name == "moe/fullest_chip_pct"
            and m.labels["layer"] == "layer0"] == [100.0 * 76 / 40]


@pytest.mark.parametrize("name", ["step_passes", "fullest_chip_pct"])
def test_without_an_exchange_the_chip_readers_publish_nothing(run, name):
    assert get_registry() is run["registry"]
    assert getattr(chip_skew, name)(run["facts"]) is None
    assert run["result"]["ep"] is None
    assert not [m for m in run["registry"].metrics()
                if m.name.startswith(("ep/", "moe/chip_"))]


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
    mine = trace_analysis.step_scopes()
    if run["preset"] != "granite":
        # every model keeps the map now; one without a state-space block
        # has nothing under these scopes, and the readers publish nothing
        assert kept[scope] == [] == mine["scopes"][scope]
        assert mine["map"]["instructions"] and mine["instructions"]
        return
    assert kept[scope] and len(set(kept[scope])) == len(kept[scope])
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


# a tiny Kimi Linear: a kda block, a latent one, dense feed-forwards; the
# cell ``kimilin_c1_b1_s8k``'s step report, whose ``kda`` entry the
# benchmark's ``program.equals.kda_chunk_size`` reads
KIMI = ["kimi-linear-48b-a3b.yaml"] + SIZE + [
    "model.layer_types=[kda,latent_attention]", "model.num_dense_layers=2",
    "model.num_key_value_heads=2", "model.head_dim_override=null",
    "model.kda_num_heads=2", "model.kda_head_dim=8",
    "model.kda_chunk_size=8", "model.kv_lora_rank=8",
    "model.qk_nope_head_dim=8", "model.qk_rope_head_dim=4",
    "model.v_head_dim=8", "model.ffn_hidden_size=32",
    "model.moe_ffn_hidden_size=16", "model.num_experts=4",
    "model.moe_topk=2", "train.train_iters=2"]


@pytest.fixture(scope="module")
def kimi_run():
    yaml, *size = KIMI
    with _launched([os.path.join(ZOO, yaml)] + size) as ran:
        yield ran


@pytest.mark.parametrize("part,value", [
    ("blocks", 1), ("chunk", 8), ("mosaic_calls", 0)])
def test_the_step_report_says_how_the_delta_rule_ran(kimi_run, part, value):
    """``kda/blocks``, ``kda/chunk`` and ``kda/mosaic_calls`` beside
    ``ssd/mosaic_calls``: the gauge, ``train()``'s result and the ``step
    report:`` line say the same. A step compiled for a CPU holds no kernel
    (``mosaic_calls`` 0: the recurrence ran in its ``jax.numpy`` form), and
    its blocks and chunk (16 positions in chunks of 8) are read from its
    loops; the block is ``kda`` on the ``attention cores:`` line whichever
    way its recurrence runs."""
    gauges = [m.value for m in kimi_run["registry"].metrics()
              if m.name == f"kda/{part}"]
    (report,) = [line for line in kimi_run["log"].splitlines()
                 if "step report:" in line]
    assert gauges == [value] and kimi_run["result"]["kda"][part] == value
    assert f"kda/{part} {value}" in report
    assert "attention cores: 1 x kda, 1 x xla" in kimi_run["log"]
    assert kimi_run["result"]["ssd_mosaic_calls"] is None


def test_a_model_without_a_kda_block_reports_none(run):
    assert run["result"]["kda"] is None
    assert not [m for m in run["registry"].metrics()
                if m.name.startswith("kda/")]


# a tiny Olmo Hybrid: a linear_attention block (Gated DeltaNet) and an
# attention block; the cell ``olmohybrid_c1_b1``'s step report
OLMO_HYBRID = ["olmo-hybrid-7b.yaml"] + SIZE + [
    "model.layer_types=[linear_attention,full_attention]",
    "model.num_key_value_heads=2", "model.ffn_hidden_size=32",
    "model.linear_num_key_heads=2", "model.linear_num_value_heads=2",
    "model.linear_key_head_dim=8", "model.linear_value_head_dim=16",
    "model.linear_chunk_size=8", "train.train_iters=2"]


@pytest.fixture(scope="module")
def olmo_hybrid_run():
    yaml, *size = OLMO_HYBRID
    with _launched([os.path.join(ZOO, yaml)] + size) as ran:
        yield ran


@pytest.mark.parametrize("said_by", ["gauge", "result", "report", "scans",
                                     "cores_line"])
def test_the_step_report_says_whether_the_gdn_kernels_engaged(
        olmo_hybrid_run, said_by):
    """``gdn/mosaic_calls``: the Mosaic calls among the instructions under
    ``mixer/gdn/scan``, beside ``ssd/mosaic_calls`` and
    ``selective/mosaic_calls``. A step compiled for a CPU holds none (the
    recurrence ran as ``modules.gated_delta_chunked``): the gauge,
    ``train()``'s result and the ``step report:`` line say 0, no scan is
    made twice, and the block is ``gdn`` on the ``attention cores:`` line
    whichever way its recurrence runs."""
    ran = olmo_hybrid_run
    (report,) = [line for line in ran["log"].splitlines()
                 if "step report:" in line]
    if said_by == "gauge":
        assert [m.value for m in ran["registry"].metrics()
                if m.name == "gdn/mosaic_calls"] == [0]
    if said_by == "result":
        assert ran["result"]["gdn_mosaic_calls"] == 0
        assert ran["result"]["ssd_mosaic_calls"] is None
    if said_by == "report":
        assert ", gdn/mosaic_calls 0" in report
    if said_by == "scans":
        assert ran["result"]["scans_recomputed"] == 0
        assert ", 0 scans recomputed, static live peak " in report
    if said_by == "cores_line":
        assert "attention cores: 1 x gdn, 1 x xla" in ran["log"]


def test_a_model_without_a_linear_block_reports_no_gdn_calls(run):
    assert run["result"]["gdn_mosaic_calls"] is None
    assert not [m for m in run["registry"].metrics()
                if m.name.startswith("gdn/")]


def test_the_step_report_counts_the_cores_remat_runs_again(run):
    """``step/cores_recomputed``: the flash forward kernels the compiled
    step holds in its recompute phase. The gauge, ``train()``'s result and
    the ``step report:`` line (its reader) say the same number in every
    preset: 0 here, where a step compiled for a CPU holds no kernel (what
    the count is on a step that holds them:
    ``test_step_map.py::test_cores_recomputed_*`` and the compile for a
    described v5e in ``tests/kernels/test_flash_mosaic_compile.py``)."""
    gauges = [m.value for m in run["registry"].metrics()
              if m.name == "step/cores_recomputed"]
    (report,) = [line for line in run["log"].splitlines()
                 if "step report:" in line]
    assert gauges == [0] and run["result"]["cores_recomputed"] == 0
    assert ", 0 cores recomputed, 0 scans recomputed, static live" in report


@pytest.mark.parametrize("path", ["row_layout", "transposed"])
def test_the_step_report_counts_the_flash_calls_by_layout(run, path):
    """``flash/row_layout_calls`` and ``flash/transposed_calls``: the
    distinct flash calls the step was built with whose kernels index the
    projections' own rows, and those that run on head-major copies between
    transposes. The gauge, ``train()``'s result and the ``step report:``
    line say the same; 0 and 0 here, where the XLA core attends (what
    decides the path of a call that is built, and that it is recorded once:
    ``tests/kernels/test_flash_attention.py::
    test_a_call_on_rows_holds_no_transpose_around_its_kernels``)."""
    gauges = [m.value for m in run["registry"].metrics()
              if m.name == f"flash/{path}_calls"]
    (report,) = [line for line in run["log"].splitlines()
                 if "step report:" in line]
    assert gauges == [0]
    assert run["result"]["flash_layout_calls"][path] == 0
    assert f"flash/{path}_calls 0" in report


def test_the_step_report_counts_the_scans_remat_runs_again(run):
    """``step/scans_recomputed`` beside it: the recurrent mixers' scan
    forward kernels the compiled step holds in its recompute phase, of the
    Granite preset's mamba block as of a preset without one. 0 on a step
    compiled for a CPU (what it is on a step that holds the kernels:
    ``test_step_map.py::test_scans_recomputed_*`` and the compile for a
    described v5e); the gauge and ``train()``'s result say the same."""
    gauges = [m.value for m in run["registry"].metrics()
              if m.name == "step/scans_recomputed"]
    assert gauges == [0] and run["result"]["scans_recomputed"] == 0
    if run["preset"] == "granite":
        assert run["result"]["ssd_mosaic_calls"] == 0


def test_the_kimi_preset_reports_its_scans_too(kimi_run):
    gauges = [m.value for m in kimi_run["registry"].metrics()
              if m.name == "step/scans_recomputed"]
    (report,) = [line for line in kimi_run["log"].splitlines()
                 if "step report:" in line]
    assert gauges == [0] and kimi_run["result"]["scans_recomputed"] == 0
    assert ", 0 scans recomputed, static live peak " in report


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


# a tiny mamba stack at TWO groups of B, C and the gated norm's channels
# under per-layer remat (``nemotronh_c1_s8k``'s mixer: eight there)
TWO_GROUPS = PRESETS["granite"] + ["model.mamba_n_groups=2",
                                   "parallel.global_checkpoint=1",
                                   "train.train_iters=2"]


@pytest.fixture(scope="module")
def two_group_run():
    yaml, *size = TWO_GROUPS
    with _launched([os.path.join(ZOO, yaml)] + size) as ran:
        yield ran


@pytest.mark.parametrize("said_by", ["phases", "relayouts", "gauge",
                                     "result", "report"])
def test_the_gated_norm_is_one_scope_in_every_phase(two_group_run, said_by):
    """``mixer/mamba/gated_norm`` holds instructions of the compiled step
    in the forward pass, the forward made again and the backward pass (a
    reader of the scope finds all three: ``mamba_gated_norm_ms``,
    ``granite_mamba_ms``), and no ``reshape`` or ``copy`` outside a fusion
    is the scope's, by its ``op_name`` or by the map's ``owners``.
    ``gated_norm/mosaic_calls`` counts the Mosaic calls under it beside
    ``ssd/mosaic_calls``, which counts ``mixer/mamba/ssd`` alone: 0 and 0
    on a step compiled for a CPU, where both ran in ``jax.numpy`` (what
    they are on a step that holds the kernels, and that the several groups
    of the ``jax.numpy`` form ARE relayouts on a TPU: the compile for a
    described v5e, ``tests/kernels/test_flash_mosaic_compile.py::
    test_a_mamba_blocks_gated_norm_is_one_pass_a_phase``)."""
    from hetu_galvatron_tpu.observability import trace_analysis

    ran = two_group_run
    scope = trace_analysis.GATED_NORM_SCOPE
    held = trace_analysis.step_scopes()["map"]
    (report,) = [line for line in ran["log"].splitlines()
                 if "step report:" in line]
    if said_by == "phases":
        assert {"forward", "recompute", "backward"} <= {
            phase for s, phase, _ in held["instructions"].values()
            if s == scope}
        assert ran["result"]["scope_instructions"][scope]
    if said_by == "relayouts":
        assert [n for n in held["relayouts"] if scope in (
            held["instructions"][n][0],
            held["owners"].get(n, (None,))[0])] == []
    if said_by == "gauge":
        assert {m.name: m.value for m in ran["registry"].metrics()
                if m.name in ("gated_norm/mosaic_calls",
                              "ssd/mosaic_calls")} == {
                    "gated_norm/mosaic_calls": 0, "ssd/mosaic_calls": 0}
    if said_by == "result":
        assert ran["result"]["gated_norm_mosaic_calls"] == 0
        assert ran["result"]["ssd_mosaic_calls"] == 0
    if said_by == "report":
        assert ("(0 under mixer/mamba/ssd), ssd/groups 2, "
                "gated_norm/mosaic_calls 0") in report


def test_a_model_without_a_mamba_block_reports_no_gated_norm_calls(
        olmo_hybrid_run):
    assert olmo_hybrid_run["result"]["gated_norm_mosaic_calls"] is None
    assert not [m for m in olmo_hybrid_run["registry"].metrics()
                if m.name.startswith("gated_norm/")]


def test_the_norms_calls_and_the_scans_are_counted_apart():
    """A step's lines in the cell's own form, shortened: three calls of
    the norm's kernels (forward, the forward made again, backward) and the
    scan's two are each their own scope's."""
    from hetu_galvatron_tpu.observability.trace_analysis import (
        GATED_NORM_SCOPE,
        SSD_SCOPE,
        step_hlo,
    )

    call = 'custom-call(%a), custom_call_target="tpu_custom_call"'
    remat = "jit(step)/transpose(jvp())/checkpoint"
    found = step_hlo(f"""ENTRY %main (a: f32[8]) -> f32[8] {{
  %ssd_scan_fwd.3 = f32[8]{{0}} {call}, metadata={{op_name="jit(step)/jvp(mixer/mamba)/ssd/ssd_scan_fwd/pallas_call"}}
  %gated_norm_fwd.1 = bf16[8]{{0}} {call}, metadata={{op_name="jit(step)/jvp(mixer/mamba)/gated_norm/gated_norm_fwd/pallas_call"}}
  %gated_norm_fwd.2 = bf16[8]{{0}} {call}, metadata={{op_name="{remat}/rematted_computation/mixer/mamba/gated_norm/gated_norm_fwd/pallas_call"}}
  %gated_norm_bwd.1 = f32[8]{{0}} {call}, metadata={{op_name="{remat}/mixer/mamba/gated_norm/gated_norm_bwd/pallas_call"}}
  %ssd_scan_bwd.4 = f32[8]{{0}} {call}, metadata={{op_name="{remat}/mixer/mamba/ssd/mixer/mamba/ssd/ssd_scan_bwd/pallas_call"}}
}}
""")
    under = lambda scope: sorted(  # noqa: E731
        n for n in found["scopes"][scope] if n in found["mosaic_calls"])
    assert under(SSD_SCOPE) == ["ssd_scan_bwd.4", "ssd_scan_fwd.3"]
    assert under(GATED_NORM_SCOPE) == [
        "gated_norm_bwd.1", "gated_norm_fwd.1", "gated_norm_fwd.2"]
    assert [found["map"]["instructions"][n][1]
            for n in under(GATED_NORM_SCOPE)] == [
                "backward", "forward", "recompute"]


def _traced_by_hand(names, known_only=True, each_ms=0.1):
    """A steady window of two periods in which every named instruction ran
    ``each_ms`` a step, one after the other: what ``xplane.reduce_device``
    hands a reader, without a TPU."""
    ms, leaves, t = 1e6, [], 0.0
    steps = [(0.0, 50 * ms), (60 * ms, 110 * ms), (120 * ms, 170 * ms)]
    for start, _ in steps[:2]:
        t = start
        for n in list(names) + ([] if known_only else ["stranger.1"]):
            leaves.append((n, t, t + each_ms * ms))
            t += each_ms * ms
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


# the scopes a preset's step has to carry (the vocabulary is
# trace_analysis.SCOPES), and the per-layer metrics that read them
# (grad/clip is not asked for: XLA merges the gradient norm under it with
# the one optax's clip computes inside optimizer/update, and keeps either)
EVERY_STEP = {"embed", "norm", "attn/qkv_proj", "attn/core", "attn/out_proj",
              "head", "param_view", "grad/accumulate", "optimizer/update"}
PRESET_SCOPES = {
    "dense": EVERY_STEP | {"mlp"},
    "moe": EVERY_STEP | {"attn/rope", "attn/qk_norm", "moe/route",
                         "moe/dispatch", "moe/experts", "moe/combine"},
    "lfm2": EVERY_STEP | {"mlp", "attn/rope", "attn/qk_norm", "moe/route",
                          "moe/dispatch", "moe/experts", "moe/combine",
                          "mixer/short_conv/in_proj",
                          "mixer/short_conv/gate_conv",
                          "mixer/short_conv/out_proj"},
    "granite": EVERY_STEP | {"mlp"} | set(granite_scopes.SCOPES),
}
SCOPE_READERS = {
    "attn_proj_ms": ("attn/qkv_proj", "attn/out_proj"), "mlp_ms": ("mlp",),
    "head_ms": ("head",), "moe_route_ms": ("moe/route",),
    "moe_dispatch_ms": ("moe/dispatch",), "moe_combine_ms": ("moe/combine",),
    "short_conv_ms": ("mixer/short_conv/in_proj",
                      "mixer/short_conv/gate_conv",
                      "mixer/short_conv/out_proj"),
}


def test_every_preset_keeps_a_map_of_its_step(run):
    """``step_scopes()["map"]``: every instruction of the compiled step
    that is an event of a trace, with exactly one phase and at most one
    scope of the vocabulary; counted in ``train()``'s result and in the
    ``step report:`` line, and written beside the trace when the profiler
    window closed."""
    from hetu_galvatron_tpu.observability import trace_analysis

    kept = trace_analysis.step_scopes()["map"]
    classes = kept["instructions"]
    assert run["result"]["step_map"] == {
        "instructions": len(classes), "inferred": len(kept["inferred"]),
        "unnamed": len(kept["tails"])}
    assert f"{len(classes)} instructions mapped" in run["log"]
    assert all(len(c) == 3 and c[1] in trace_analysis.PHASES
               and (c[0] is None or c[0] in trace_analysis.SCOPES)
               for c in classes.values())
    assert set(kept["inferred"]) <= set(classes)
    assert {n for n, c in classes.items() if c[0] is None} == set(
        kept["tails"])
    assert set(classes) <= trace_analysis.step_scopes()["instructions"]
    with open(os.path.join(run["trace_dir"],
                           trace_analysis.STEP_MAP_FILE)) as f:
        written = json.load(f)
    assert written["instructions"] == {n: list(c)
                                       for n, c in classes.items()}
    assert written["vocabulary"] == list(trace_analysis.SCOPES)


def test_the_map_names_the_step_by_scope_and_phase(run):
    from hetu_galvatron_tpu.observability import trace_analysis

    classes = trace_analysis.step_scopes()["map"]["instructions"].values()
    assert PRESET_SCOPES[run["preset"]] <= {c[0] for c in classes}
    # no layer of these presets is rematerialised; a mamba block's scan
    # makes its groups' decay matrices again in the backward pass
    assert {c[1] for c in classes} == {
        "forward", "backward", "update", "other"} | (
            {"recompute"} if run["preset"] == "granite" else set())
    assert {c[0] for c in classes if c[1] == "recompute"} <= {
        "mixer/mamba/ssd", None}
    # the update is what lies under optimizer/ and no transformation
    assert {c[0] for c in classes if c[1] == "update"} == {
        "optimizer/update"}
    assert not any(c[2] for c in classes)      # one device: no collective


def test_the_step_map_readers_join_a_trace_to_the_map(run):
    """Every traced instruction ran ``each`` ms a step: a reader's value is
    its instructions' count times that, the five phases add up to the
    summed leaf time of a step, and a metric whose scope the model lacks
    (a dense MLP in the expert preset) publishes nothing."""
    from hetu_galvatron_tpu.observability import trace_analysis

    classes = trace_analysis.step_scopes()["map"]["instructions"]
    each = 40.0 / len(classes)
    facts = {"trace": {"reduced": [_traced_by_hand(classes, each_ms=each)]}}
    phases = {p: getattr(step_map, f"phase_{p}_ms")(facts)
              for p in step_map.PHASES}
    for p, ms in phases.items():
        assert ms == pytest.approx(
            each * sum(c[1] == p for c in classes.values()))
    if run["preset"] != "granite":  # a share of a partition: 0, not None
        assert phases["recompute"] == 0.0
    assert sum(phases.values()) == pytest.approx(each * len(classes),
                                                 rel=1e-3)
    assert step_map.scope_unnamed_pct(facts) == pytest.approx(
        100.0 * sum(c[0] is None for c in classes.values()) / len(classes))
    for name, scopes in SCOPE_READERS.items():
        value = getattr(step_map, name)(facts)
        if set(scopes) <= PRESET_SCOPES[run["preset"]]:
            assert value == pytest.approx(
                each * sum(c[0] in scopes for c in classes.values()))
            assert value > 0
        else:
            assert value is None
    for name in ("collective_all_ms", "collective_all_exposed_pct",
                 "collective_overlapped_ms"):
        assert getattr(step_map, name)(facts) is None
    # no trace, or an operation inside a step that is no instruction of the
    # step's HLO: nothing is published
    stranger = {"trace": {"reduced": [_traced_by_hand(
        classes, known_only=False, each_ms=each)]}}
    for name in (*SCOPE_READERS, "phase_forward_ms", "scope_unnamed_pct"):
        assert getattr(step_map, name)({}) is None
        assert getattr(step_map, name)(stranger) is None


STEP_MAP_METRICS = tuple(f"phase_{p}_ms" for p in step_map.PHASES) + (
    "scope_unnamed_pct", *SCOPE_READERS, "collective_all_ms",
    "collective_all_exposed_pct", "collective_overlapped_ms")


@pytest.mark.parametrize("name", STEP_MAP_METRICS)
def test_a_step_map_metric_has_its_file_and_its_reader(name):
    with open(os.path.join(READERS, name + ".json")) as f:
        declared = json.load(f)
    assert declared["what"] and declared["reader"] == {
        "kind": "python", "file": "step_map.py", "function": name}
    assert callable(getattr(step_map, name))
    (entry,) = [m for m in manifest.load_manifest()["per_layer"]
                if m["name"] == name]
    assert (entry["source"], entry["moves"], entry["better"]) == (
        "device_trace", "tokens_per_s", "lower")


@pytest.mark.parametrize("name", STEP_MAP_METRICS)
def test_without_the_new_key_a_step_map_reader_publishes_nothing(
        monkeypatch, name):
    """What the parent commit gives them: a ``step_scopes()`` with the keys
    of before and no ``map``, an empty one, or none at all: ``None``, never
    a 0."""
    from hetu_galvatron_tpu.observability import trace_analysis

    facts = {"trace": {"reduced": [_traced_by_hand(["fusion.1"])]}}
    reader = getattr(step_map, name)
    monkeypatch.setattr(trace_analysis, "_STEP_SCOPES", {
        "scopes": {"mixer/mamba/ssd": ["fusion.1"]},
        "instructions": frozenset(["fusion.1"]),
        "mosaic_calls": frozenset()})
    assert reader(facts) is None
    monkeypatch.setattr(trace_analysis, "_STEP_SCOPES", {})
    assert reader(facts) is None
    monkeypatch.delattr(trace_analysis, "step_scopes")
    assert reader(facts) is None


def test_the_collective_readers_read_the_classes(monkeypatch):
    """Four chips by hand (``test_step_map.FOUR_CHIPS``'s classes): the
    start, the wait, a fused reduce-scatter and an all-to-all are
    ``collective_all_ms``; the all-gather riding a matmul is not; the
    exposed share is the part no other leaf covers."""
    from hetu_galvatron_tpu.observability import trace_analysis

    monkeypatch.setattr(trace_analysis, "_STEP_SCOPES", {"map": {
        "instructions": {
            "async-collective-start.1": ("mlp", "forward",
                                         "all-gather.start"),
            "fusion.12": ("mlp", "forward", "overlapped"),
            "async-collective-done.1": ("mlp", "forward", "all-gather.done"),
            "fusion.13": ("attn/qkv_proj", "backward",
                          "reduce-scatter.fused"),
            "all-to-all.2": (None, "forward", "all-to-all"),
            "fusion.14": ("mlp", "backward", None)},
        "inferred": [], "tails": {}}})
    ms = 1e6
    one = [("async-collective-start.1", 0.0, 0.1), ("fusion.12", 0.1, 1.1),
           ("async-collective-done.1", 1.1, 1.4), ("fusion.13", 1.4, 2.4),
           # this one runs beside the second half of the reduce-scatter
           ("fusion.14", 1.9, 2.9), ("all-to-all.2", 2.9, 3.0)]
    steps = [(0.0, 5 * ms), (10 * ms, 15 * ms), (20 * ms, 25 * ms)]
    leaves = [(n, (s + at) * ms, (e + at) * ms)
              for at in (0.0, 10.0) for n, s, e in one]
    r = xplane.Reduced(0, steps, (0.0, 20 * ms), leaves,
                       [(n, e - s) for n, s, e in leaves], [])
    facts = {"trace": {"reduced": [r]}}
    assert step_map.collective_all_ms(facts) == pytest.approx(1.5)
    assert step_map.collective_overlapped_ms(facts) == pytest.approx(1.0)
    # exposed: 0.1 + 0.3 + 0.5 of the reduce-scatter + 0.1, of 3.0 busy
    assert step_map.collective_all_exposed_pct(facts) == pytest.approx(
        100.0 * 1.0 / 3.0)
    assert step_map.mlp_ms(facts) == pytest.approx(2.4)


def _scope_instructions_of_pr35(hlo_text, scopes):
    """``trace_analysis.scope_instructions`` as PR 35 wrote it (its own walk
    over the computations), kept here as the oracle of the lists the
    ``granite_*`` metrics read."""
    import re

    computation = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*?^\}",
                             re.M | re.S)
    instruction = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
    found = {s: [] for s in scopes}
    for m in computation.finditer(hlo_text):
        if "fused_computation" in m.group(1):
            continue
        for line in m.group(0).splitlines():
            inst = instruction.match(line)
            op = re.search(r'op_name="([^"]*)"', line)
            if not inst or not op:
                continue
            path = re.sub(r"\w+\(|\)", "", op.group(1)) + "/"
            for s in scopes:
                if s + "/" in path:
                    found[s].append(inst.group(1))
    return found


def test_the_mamba_lists_are_what_scope_instructions_gave():
    """The five ``mixer/mamba/*`` lists of ``step_scopes()["scopes"]`` by
    the rule of before, on the compiled step of the tiny Granite hybrid: an
    instruction by its OWN ``op_name`` (no inference from what it fuses),
    whatever the vocabulary's deepest name says of it."""
    import jax
    import jax.numpy as jnp

    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.models.builder import (
        causal_lm_loss,
        init_causal_lm,
    )
    from hetu_galvatron_tpu.observability import trace_analysis

    yaml, *size = PRESETS["granite"]
    cfg = args_from_cli([os.path.join(ZOO, yaml)] + size,
                        mode="train_dist").model
    params = jax.eval_shape(lambda k: init_causal_lm(k, cfg)[0],
                            jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = jax.jit(jax.grad(lambda p, t: causal_lm_loss(
        p, {"tokens": t, "labels": t}, cfg))).lower(
            params, tokens).compile().as_text()
    expected = _scope_instructions_of_pr35(text, granite_scopes.SCOPES)
    assert all(expected[s] for s in granite_scopes.SCOPES)
    # (beside them the step keeps the further prediction depth's lists,
    # empty for a model without one)
    kept = trace_analysis.step_hlo(text)["scopes"]
    assert {s: kept[s] for s in expected} == expected
    assert all(kept[s] == [] for s in trace_analysis.MTP_SCOPES)
    assert trace_analysis.scope_instructions(
        text, granite_scopes.SCOPES)["scopes"] == expected
    assert tuple(expected) == trace_analysis.MIXER_SCOPES["mamba"]


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
