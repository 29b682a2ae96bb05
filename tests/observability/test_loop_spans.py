"""The launcher loop says what it is doing: flat sibling phase spans that
tile one iteration of ``run_loop``, one-shot set-up spans, and the compiled
step's static memory as gauges — all through ``tracing.span`` and the
process-wide registry, on the CPU at a tiny size through
``train_dist.main``."""

import os

import pytest

from hetu_galvatron_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
    set_registry,
)
from hetu_galvatron_tpu.observability.tracing import span

pytestmark = pytest.mark.observability

ZOO = os.path.join(os.path.dirname(__file__), "..", "..",
                   "hetu_galvatron_tpu", "models", "configs")
LOOP_SPANS = ["train/data", "train/h2d", "train/dispatch", "train/sync",
              "train/lr", "train/log", "train/check"]
SETUP_SPANS = ["setup/imports", "setup/runtime", "setup/init",
               "setup/resume", "setup/step_report"]
PARTS = ["arguments", "outputs", "aliased", "temporaries",
         "generated_code", "live_peak"]
ITERS, WARMUP, TRACED = 6, 2, 3
# large enough that the step, not the interpreter between two spans, is
# what an iteration's time is made of
TINY = [
    "model.hidden_size=64", "model.num_hidden_layers=2",
    "model.num_attention_heads=2", "model.vocab_size=256",
    "model.seq_length=64", "model.max_position_embeddings=64",
    "model.make_vocab_size_divisible_by=1", f"train.train_iters={ITERS}",
    "parallel.mixed_precision=fp32", "parallel.global_train_batch_size=8",
    "parallel.num_devices=1", "data.dataset=random"]


class _RecordingLowered:
    """A lowering, keeping the optimized HLO of the executable made of it."""

    def __init__(self, lowered, compiled_texts):
        self.lowered, self.compiled_texts = lowered, compiled_texts

    def compile(self):
        compiled = self.lowered.compile()
        self.compiled_texts.append(compiled.as_text())
        return compiled


class _RecordingStep:
    """The jitted step, keeping the text of every lowering made of it."""

    def __init__(self, fn, texts, compiled_texts):
        self.fn, self.texts, self.compiled_texts = fn, texts, compiled_texts

    def __call__(self, *args):
        return self.fn(*args)

    def lower(self, *args):
        lowered = self.fn.lower(*args)
        self.texts.append(lowered.as_text())
        return _RecordingLowered(lowered, self.compiled_texts)


def _run(extra, yaml="gpt2-small.yaml"):
    """One tiny run on a registry of its own; returns (registry, result,
    the step's lowered text); the result also holds the optimized HLO of
    the executable the step report read (``compiled_hlo``)."""
    from hetu_galvatron_tpu.cli import train_dist
    from hetu_galvatron_tpu.parallel import spmd

    texts, compiled_texts = [], []
    make = spmd.make_spmd_train_step

    def recording(*args, **kwargs):
        step, *rest = make(*args, **kwargs)
        return (_RecordingStep(step, texts, compiled_texts), *rest)

    before = get_registry()
    reg = set_registry(MetricsRegistry())
    spmd.make_spmd_train_step = recording
    try:
        out = {}
        rc = train_dist.main(
            [os.path.join(ZOO, yaml)] + TINY + extra,
            result=out)
    finally:
        spmd.make_spmd_train_step = make
        set_registry(before)
    assert rc == 0 and len(out["losses"]) == ITERS
    (text,) = texts   # the step report lowers the step once
    (out["compiled_hlo"],) = compiled_texts
    return reg, out, text


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tdir = str(tmp_path_factory.mktemp("loop_spans") / "trace")
    reg, out, hlo = _run(["profile.profile=1",
                          f"profile.profile_warmup={WARMUP}",
                          f"profile.trace_iters={TRACED}",
                          f"profile.trace_dir={tdir}"])
    return {"registry": reg, "result": out, "hlo": hlo, "trace_dir": tdir}


@pytest.fixture(scope="module")
def host_events(traced):
    """name -> [(start ns, end ns, step)] of the ``train/*`` and
    ``setup/*`` TraceMes on ``/host:CPU``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        traced["trace_dir"], "plugins", "profile", "*", "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("train/", "setup/")):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats).get("step")))
    return events


def _histogram(reg, path):
    found = [m for m in reg.metrics()
             if m.name == "span_ms" and m.labels == {"path": path}]
    assert len(found) == 1, (path, [m.labels for m in reg.metrics()
                                    if m.name == "span_ms"])
    return found[0]


# (a) the registry side: on every run, traced or not ------------------------

@pytest.mark.parametrize("path,count",
                         [(p, ITERS) for p in LOOP_SPANS]
                         + [(p, 1) for p in SETUP_SPANS])
def test_span_histogram_counts_every_entry(traced, path, count):
    h = _histogram(traced["registry"], path)
    assert h.count == count
    assert h.total > 0.0


@pytest.mark.parametrize("path", ["train/telemetry", "train/eval",
                                  "train/save"])
def test_spans_of_work_not_done_are_absent(traced, path):
    assert not [m for m in traced["registry"].metrics()
                if m.labels.get("path") == path]


# (b) the trace side: on the profiler's clock, with the iteration ----------

@pytest.mark.parametrize("name", LOOP_SPANS)
def test_phase_span_is_on_the_host_plane_with_its_step(host_events, name):
    steps = [step for _, _, step in host_events[name]]
    assert steps == list(range(WARMUP, WARMUP + TRACED))


def test_setup_spans_end_before_the_trace_window_opens(host_events):
    # all but the step report (made after the first step, long before the
    # window) are over before the loop begins; none is on the trace
    assert not [n for n in host_events if n.startswith("setup/")]


def _iteration(host_events, it):
    """``[(start, end, name)]`` of iteration ``it``'s spans, in time."""
    return sorted((s, e, name) for name, evs in host_events.items()
                  for s, e, step in evs if step == it)


@pytest.mark.parametrize("it", range(WARMUP, WARMUP + TRACED))
def test_phase_spans_tile_the_iteration(host_events, it):
    mine = _iteration(host_events, it)
    assert [name for _, _, name in mine] == LOOP_SPANS
    assert all(a[1] <= b[0] for a, b in zip(mine, mine[1:])), mine
    # between two spans lie the same few lines of Python in every traced
    # iteration, plus whatever a host with six busy workers takes from the
    # thread there: each gap is read in the iteration that had it shortest
    traced = [_iteration(host_events, i)
              for i in range(WARMUP, WARMUP + TRACED)]
    gaps = sum(min(spans[k + 1][0] - spans[k][1] for spans in traced)
               for k in range(len(LOOP_SPANS) - 1))
    assert gaps <= 0.05 * (mine[-1][1] - mine[0][0]), mine


# (c) the compiled step's static memory -------------------------------------

@pytest.mark.parametrize("part", PARTS)
def test_static_memory_gauge(traced, part):
    (g,) = [m for m in traced["registry"].metrics()
            if m.name == "step/static_bytes" and m.labels == {"part": part}]
    assert g.value == traced["result"]["static_memory"][part]
    assert g.value >= 0
    if part in ("arguments", "outputs", "temporaries", "live_peak"):
        assert g.value > 0


def test_static_live_peak_is_the_stated_sum(traced):
    m = traced["result"]["static_memory"]
    assert set(m) == set(PARTS)
    assert m["live_peak"] == (m["arguments"] + m["outputs"] - m["aliased"]
                              + m["temporaries"] + m["generated_code"])
    # donated parameters and optimizer state are reused for the outputs
    assert 0 < m["aliased"] <= m["outputs"]


# (c') and its collectives --------------------------------------------------

COLLECTIVES = ["all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute"]


@pytest.fixture(scope="module")
def tp2_run():
    """The same tiny model on a tp2 x dp2 ZeRO-3 mesh of four CPU devices."""
    reg, out, _ = _run(["parallel.num_devices=4", "parallel.global_tp_deg=2",
                        "parallel.default_dp_type=zero3"])
    return reg, out


@pytest.mark.parametrize("op", COLLECTIVES)
def test_collective_gauge_is_the_count_in_the_steps_hlo(tp2_run, op):
    reg, out = tp2_run
    (g,) = [m for m in reg.metrics()
            if m.name == "step/collectives" and m.labels == {"op": op}]
    hlo = out["compiled_hlo"]
    assert g.value == out["collectives"][op] \
        == hlo.count(f" {op}(") + hlo.count(f" {op}-start(")


def test_tp2_step_has_collectives_and_one_chip_step_has_none(tp2_run, traced):
    _, out = tp2_run
    assert set(out["collectives"]) == set(COLLECTIVES)
    assert out["collectives"]["all-gather"] > 0
    assert out["collectives"]["all-reduce"] > 0
    assert traced["result"]["collectives"] == dict.fromkeys(COLLECTIVES, 0)


@pytest.mark.parametrize("run", ["one_device", "tp2"])
def test_relayout_gauge_is_a_number_read_off_the_steps_hlo(
        request, traced, run):
    """``step/relayout_bytes``: the result bytes of the reshape, copy and
    transpose instructions the compiled step holds outside its fusions,
    with the largest one named in ``train()``'s result."""
    from hetu_galvatron_tpu.observability.trace_analysis import step_hlo

    reg, out = ((traced["registry"], traced["result"]) if run == "one_device"
                else request.getfixturevalue("tp2_run"))
    (g,) = [m for m in reg.metrics() if m.name == "step/relayout_bytes"]
    moved = out["relayouts"]
    assert isinstance(g.value, float) and g.value == moved["bytes"] >= 0
    assert moved == step_hlo(out["compiled_hlo"])["relayouts"]
    assert (moved["largest"] is None) == (moved["count"] == 0)
    if moved["largest"]:
        assert moved["largest"]["opcode"] in ("reshape", "copy", "transpose")
        assert 0 < moved["largest"]["bytes"] <= moved["bytes"]
        assert moved["largest"]["shape"] in out["compiled_hlo"]


@pytest.mark.parametrize("run", ["one_device", "tp2"])
def test_the_flow_gauges_are_counts_of_the_recorded_map(request, traced, run):
    """``step/prefetches``, ``step/prefetch_bytes``,
    ``step/unowned_instructions``: set beside ``step/relayout_bytes`` from
    the same walk, in ``train()``'s ``flow``, and counts of the map a reader
    finds in the process and ``step_map.json`` carries."""
    from hetu_galvatron_tpu.observability.trace_analysis import step_hlo

    reg, out = ((traced["registry"], traced["result"]) if run == "one_device"
                else request.getfixturevalue("tp2_run"))
    found = step_hlo(out["compiled_hlo"])
    assert out["flow"] == found["flow"]
    assert {m.name[len("step/"):]: m.value for m in reg.metrics()
            if m.name in ("step/prefetches", "step/prefetch_bytes",
                          "step/unowned_instructions")} == out["flow"]
    kept = found["map"]
    assert out["flow"]["unowned_instructions"] == sum(
        n not in kept["owners"] for n in kept["tails"])
    assert set(kept["relayouts"]) <= set(kept["instructions"])
    assert len(kept["relayouts"]) == out["relayouts"]["count"]
    assert all(t["done"] in kept["instructions"]
               for t in kept["transfers"].values())


# (d) spans change nothing on the device -------------------------------------

def test_lowered_step_is_the_same_with_and_without_a_trace_window(traced):
    reg, out, hlo = _run([])
    assert hlo == traced["hlo"]
    assert out["losses"] == traced["result"]["losses"]
    # no profiler: no sample, and the same spans all the same
    assert _histogram(reg, "train/sync").count == ITERS


# (e) span attributes go onto the TraceMe -----------------------------------

@pytest.mark.parametrize("attrs", [{"step": 3}, {"step": 4, "chunk": 1}, {}])
def test_span_attributes_reach_the_trace_annotation(tmp_path, attrs):
    import glob

    import jax
    from jax.profiler import ProfileData

    reg = MetricsRegistry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("probe/attrs", registry=reg, **attrs):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = [dict(e.stats) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "probe/attrs"]
    assert found == [attrs]
    # the registry path is the name alone
    assert _histogram(reg, "probe/attrs").count == 1


# the repair: a traced iteration blocks where a measured one does -----------

class _Loss:
    def __init__(self):
        self.blocked = 0

    def block_until_ready(self):
        self.blocked += 1
        return self


@pytest.mark.parametrize("profile,tracing,blocks,samples", [
    (1, True, 1, 0),     # traced: blocks on the loss, records no sample
    (1, False, 1, 1),    # measured: blocks and records
    (0, True, 0, 0),     # profiler off: the loop never blocks here
])
def test_time_end_blocks_in_traced_iterations_too(tmp_path, profile, tracing,
                                                  blocks, samples):
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
        RuntimeProfiler,
    )

    args = args_from_cli(
        [os.path.join(ZOO, "gpt2-small.yaml"), f"profile.profile={profile}",
         "profile.profile_warmup=0"], mode="train_dist")
    prof = RuntimeProfiler(args, registry=MetricsRegistry())
    prof._trace.step = lambda it: tracing   # no real capture
    loss = _Loss()
    prof.time_start(0)
    prof.time_end(0, sync=loss)
    assert loss.blocked == blocks
    assert len(prof.time_samples) == samples


# (f) nothing on the chip between two steps: the log line's values --------

MOE_TINY = ["model.num_key_value_heads=2", "model.ffn_hidden_size=32",
            "model.num_experts=4", "model.moe_topk=2"]


def _logged_run(extra, yaml="gpt2-small.yaml"):
    """A tiny run with every call of the loop into the log line recorded:
    ``lr`` (iterations whose learning rate was asked for), ``copies`` (how
    many host copies were started), ``logged`` ((iteration, the step's
    metrics, the line returned)), ``printed`` (the ``iter`` lines on
    stdout); and the run's registry."""
    import contextlib
    import io

    from jax._src.array import ArrayImpl

    from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
        RuntimeProfiler,
    )
    from hetu_galvatron_tpu.runtime.optimizer import HostSchedule

    seen = {"lr": [], "copies": 0, "logged": []}
    lookup, copy = HostSchedule.__call__, ArrayImpl.copy_to_host_async
    log = RuntimeProfiler.iteration_log

    def counting_lookup(self, it):
        seen["lr"].append(it)
        return lookup(self, it)

    def counting_copy(self):
        seen["copies"] += 1
        return copy(self)

    def recording_log(self, it, metrics, lr=None):
        line = log(self, it, metrics, lr=lr)
        seen["logged"].append((it, metrics, line))
        return line

    stdout = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, \
            contextlib.redirect_stdout(stdout):
        mp.setattr(HostSchedule, "__call__", counting_lookup)
        mp.setattr(ArrayImpl, "copy_to_host_async", counting_copy)
        mp.setattr(RuntimeProfiler, "iteration_log", recording_log)
        seen["registry"], _, _ = _run(extra, yaml)
    seen["printed"] = [ln for ln in stdout.getvalue().splitlines()
                       if ln.startswith("iter ")]
    return seen


def _parents_line(it, metrics, schedule):
    """The line as PR 30 printed it: every value read back from the device
    where it is formatted, the schedule evaluated eagerly."""
    import numpy as np

    bits = [f"iter {it}", f"loss {float(metrics['loss']):.4f}",
            f"grad-norm {float(metrics['grad_norm']):.3f}",
            f"lr {float(schedule(it)):.3e}"]
    for name in sorted(metrics.get("moe", {})):
        st = metrics["moe"][name]
        tpe = np.asarray(st["tokens_per_expert"], dtype=float)
        bits.append(f"moe[{name}] aux {float(st['load_balance_loss']):.3e} "
                    f"z {float(st['z_loss']):.3e} "
                    f"imb {float(tpe.max() / max(tpe.mean(), 1e-9)):.2f}")
    return " | ".join(bits)


@pytest.fixture(scope="module")
def every_other():
    return _logged_run(["logging.log_interval=2"])


@pytest.mark.parametrize("what", ["learning rate", "host copies", "lines"])
def test_off_the_log_interval_nothing_is_computed_or_copied(every_other,
                                                            what):
    seen, printing = every_other, list(range(0, ITERS, 2))
    if what == "learning rate":
        assert seen["lr"] == printing
    elif what == "host copies":
        # the loss and the gradient norm, on printing iterations alone
        assert seen["copies"] == 2 * len(printing)
    else:
        lines = [line for _, _, line in seen["logged"]]
        assert [it for it, line in enumerate(lines) if line] == printing
        assert seen["printed"] == [line for line in lines if line]


@pytest.mark.parametrize("preset", ["dense", "moe"])
def test_log_line_is_the_parents_to_the_character(preset):
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.runtime.optimizer import make_lr_schedule

    yaml, extra = {"dense": ("gpt2-small.yaml", []),
                   "moe": ("olmoe-1b-7b.yaml", MOE_TINY)}[preset]
    # a warm-up and a decay inside the run, so that the field moves
    extra = extra + ["train.lr_warmup_iters=2", "train.lr_decay_iters=5"]
    seen = _logged_run(extra, yaml)
    schedule = make_lr_schedule(args_from_cli(
        [os.path.join(ZOO, yaml)] + TINY + extra, mode="train_dist").train)
    assert seen["printed"] == [_parents_line(it, metrics, schedule)
                               for it, metrics, _ in seen["logged"]]
    assert len(seen["printed"]) == ITERS
    assert len({ln.split(" | ")[3] for ln in seen["printed"]}) > 2  # lr
    # each leaf the line formats was copied behind its step, once
    layers = sorted(seen["logged"][0][1].get("moe", {}))
    assert seen["copies"] == ITERS * (2 + 3 * len(layers))
    assert (preset == "moe") == bool(layers)
    reg = seen["registry"]
    for layer in layers:
        tpe = seen["logged"][-1][1]["moe"][layer]["tokens_per_expert"]
        assert reg.gauge("moe/rows_per_expert", layer=layer,
                         stat="max").value == float(max(tpe))
        for gauge in ("moe/aux_loss", "moe/z_loss", "moe/imbalance"):
            assert reg.gauge(gauge, layer=layer).value > 0
