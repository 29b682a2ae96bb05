"""Profiler tests on the virtual CPU mesh: schema compatibility with the
search engine is the contract (reference tests/profiler/*)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_galvatron_tpu.core.args_schema import CoreArgs, HardwareProfileArgs
from hetu_galvatron_tpu.core.profiler.hardware_profiler import HardwareProfiler
from hetu_galvatron_tpu.core.profiler.model_profiler import ModelProfiler
from hetu_galvatron_tpu.core.profiler.runtime_profiler import RuntimeProfiler
from hetu_galvatron_tpu.core.search_engine.profiles import (
    parse_memory_config,
    parse_time_config,
    read_allreduce_bandwidth,
    read_p2p_bandwidth,
    remap_collective_latency,
)

pytestmark = [pytest.mark.profiler, pytest.mark.distributed]

TINY = dict(hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
            vocab_size=64, max_position_embeddings=64, seq_length=16,
            make_vocab_size_divisible_by=1)


@pytest.fixture(scope="module")
def hw_args():
    return HardwareProfileArgs(num_nodes=1, num_devices_per_node=8,
                               start_mb=1, end_mb=8, scale=2,
                               warmup_iters=1, profile_iters=2)


def test_hardware_profiler_schemas(hw_args, cpu_devices, tmp_path):
    prof = HardwareProfiler(hw_args, devices=cpu_devices)
    ar = prof.profile_allreduce_bandwidth(message_mb=1)
    assert "allreduce_size_8_consec_1" in ar
    assert "allreduce_size_4_consec_0" in ar
    assert all(v > 0 for v in ar.values())
    # consumable by the search-engine reader
    bw, coe = read_allreduce_bandwidth(ar, 8)
    assert coe["8"] > 0 and coe["1"] == 0

    p2p = prof.profile_p2p_bandwidth(message_mb=1)
    assert set(p2p) == {"pp_size_2", "pp_size_4", "pp_size_8"}
    _, p2p_coe = read_p2p_bandwidth(p2p)
    assert p2p_coe[2] > 0

    ov = prof.profile_overlap_coefficient(message_mb=1)
    assert ov["overlap_coe"] >= 1.0


@pytest.mark.slow
def test_sp_time_profile_feeds_latency_tables(hw_args, cpu_devices):
    args = HardwareProfileArgs(num_nodes=1, num_devices_per_node=4,
                               start_mb=1, end_mb=128, scale=2,
                               warmup_iters=1, profile_iters=1)
    prof = HardwareProfiler(args, devices=cpu_devices[:4])
    sp = prof.profile_sp_time()
    # 8 sizes per group per op -> latency remap fits a line
    tables = remap_collective_latency(sp, "allgather")
    assert 4 in tables and "popt" in tables[4]
    a2a = remap_collective_latency(sp, "all2all")
    assert 2 in a2a
    # the new sub-MB points ride a 'sub_' prefix the legacy remap parsers
    # never see (their MB values would otherwise read as megabytes)
    assert "sub_allreduce_size_4_512KB_time" in sp
    assert all(mb in tables[4] or mb == "popt" for mb in tables[4])
    assert not any(isinstance(k, int) and k > 128 for k in tables[4])


def test_alpha_beta_fit_roundtrips_into_cost_model(cpu_devices, monkeypatch):
    """profile_alpha_beta fits (α ms, β MB/ms) per (size, consec) from the
    sub-MB + MB allreduce points; the pairs merge into the bandwidth JSON,
    profiles.read_alpha_beta parses them, and a legacy JSON yields {}. The
    collectives run, on a clock they are handed (α + MB / β of the message:
    a loaded host's own readings fit a slope of any sign), and the fit gives
    the pair back."""
    from hetu_galvatron_tpu.core.profiler import hardware_profiler
    from hetu_galvatron_tpu.core.search_engine.profiles import (
        read_alpha_beta,
    )

    alpha_ms, beta_mb_per_ms = 0.05, 2.0

    def handed(fn, arg, **_):
        jax.block_until_ready(fn(arg))
        return alpha_ms + arg.size * 4 / 2 ** 20 / beta_mb_per_ms

    monkeypatch.setattr(hardware_profiler, "_time_fn", handed)
    args = HardwareProfileArgs(num_nodes=1, num_devices_per_node=4,
                               start_mb=1, end_mb=4, scale=2,
                               warmup_iters=1, profile_iters=1)
    prof = HardwareProfiler(args, devices=cpu_devices[:4])
    sp = prof.profile_sp_time()
    ab = prof.profile_alpha_beta(sp)
    for size, consec in ((4, 1), (2, 1), (2, 0)):
        assert f"allreduce_size_{size}_consec_{consec}_alpha_ms" in ab
        assert ab[f"allreduce_size_{size}_consec_{consec}_alpha_ms"] == \
            pytest.approx(alpha_ms, rel=1e-3)
        assert ab[f"allreduce_size_{size}_consec_{consec}_beta_mb_per_ms"] \
            == pytest.approx(beta_mb_per_ms, rel=1e-3)
    # merged with the bandwidth keys, the reader recovers the pairs...
    bw = prof.profile_allreduce_bandwidth(message_mb=1)
    bw.update(ab)
    pairs = read_alpha_beta(bw)
    assert set(pairs) == {"4_1", "2_1", "2_0"}
    assert all(a >= 0 and b > 0 for a, b in pairs.values())
    # ...and the legacy reader still parses the merged JSON untouched
    bw2, coe = read_allreduce_bandwidth(bw, 4)
    assert coe["4"] > 0
    # legacy bandwidth-only JSON -> empty table (golden costs unchanged)
    assert read_alpha_beta(
        {"allreduce_size_4_consec_1": 100.0}) == {}


def test_runtime_profiler_timing_and_log():
    args = CoreArgs.model_validate({"profile": {"profile": 1,
                                                "profile_warmup": 0}})
    prof = RuntimeProfiler(args)
    for it in range(4):
        prof.time_start(it)
        x = jnp.ones((64, 64)) @ jnp.ones((64, 64))
        prof.time_end(it, sync=x)
        line = prof.iteration_log(it, {"loss": 1.0, "grad_norm": 0.5})
    assert prof.filtered_time_ms() > 0
    assert "loss 1.0000" in line


def test_iteration_log_consistent_and_sync_free(capsys):
    """ADVICE r5: the returned string equals the PRINTED line (MoE stats
    included) on printing iterations, and non-printing iterations return
    "" with ZERO device-to-host conversions — never a half-formatted
    line."""

    class NoSync:
        def __float__(self):
            raise AssertionError("device sync on a non-printing iteration")

    moe = {"layer1": {"load_balance_loss": 0.5, "z_loss": 0.25,
                      "tokens_per_expert": np.array([3.0, 1.0])}}
    args = CoreArgs.model_validate({"logging": {"log_interval": 2}})
    prof = RuntimeProfiler(args)
    # off-interval: no formatting at all -> NoSync never converted
    assert prof.iteration_log(
        1, {"loss": NoSync(), "grad_norm": NoSync(), "moe": moe}) == ""
    # non-zero rank: same
    prof_r1 = RuntimeProfiler(args, rank=1)
    assert prof_r1.iteration_log(
        0, {"loss": NoSync(), "grad_norm": NoSync()}) == ""
    capsys.readouterr()
    # printing iteration: the full line — MoE stats included — is BOTH
    # returned and printed
    line = prof.iteration_log(2, {"loss": 1.0, "grad_norm": 0.5,
                                  "moe": moe})
    printed = capsys.readouterr().out.strip()
    assert line == printed
    assert "moe[layer1]" in line and "imb 1.50" in line
    # ...and the converted stats land in the metrics registry
    assert prof.registry.gauge("moe/aux_loss", layer="layer1").value == 0.5
    assert prof.registry.gauge("moe/imbalance", layer="layer1").value == 1.5
    # the most loaded expert's rows: the longest of the grouped matmuls
    assert prof.registry.gauge("moe/rows_per_expert", layer="layer1",
                               stat="max").value == 3.0


def test_runtime_profiler_routes_registry(tmp_path):
    """Iteration timing flows through the observability registry."""
    from hetu_galvatron_tpu.observability.registry import MetricsRegistry

    reg = MetricsRegistry()
    args = CoreArgs.model_validate({"profile": {"profile": 1,
                                                "profile_warmup": 0}})
    prof = RuntimeProfiler(args, registry=reg)
    for it in range(3):
        prof.time_start(it)
        prof.time_end(it)
    h = reg.histogram("profiler/iter_time_ms")
    assert h.count == 3
    assert h.snapshot()["mean"] == pytest.approx(
        float(np.mean(prof.time_samples)), rel=1e-6)


def test_model_profiler_computation_schema(tmp_path):
    args = CoreArgs.model_validate({
        "model": TINY,
        "model_profiler": {"profile_type": "computation",
                           "profile_mode": "static",
                           "profile_batch_size": 2,
                           "profile_seq_length_list": [16],
                           "layernum_min": 1, "layernum_max": 2},
    })
    prof = ModelProfiler(args)
    entries = prof.profile_computation()
    assert "layertype_0_bsz2_seq16" in entries
    assert "layertype_other_bsz2_seq16" in entries
    times, others = parse_time_config(
        entries, mode="static", num_layertype=1, seqlen_list=[16])
    assert len(times) == 1 and len(others) == 1


@pytest.mark.slow
def test_model_profiler_memory_schema(cpu_devices):
    args = CoreArgs.model_validate({
        "model": TINY,
        "model_profiler": {"profile_type": "memory",
                           "profile_batch_size": 2,
                           "profile_seq_length_list": [16],
                           "layernum_min": 1, "layernum_max": 2,
                           "max_tp_deg": 2},
    })
    prof = ModelProfiler(args, devices=cpu_devices)
    mem = prof.profile_memory()
    assert "layertype_0_sp" in mem
    layer = mem["layertype_0_sp"]["16"]
    assert layer["parameter_size"] > 0
    assert 1 in layer["tp_activation_per_bsz_dict"]
    assert "checkpoint" in layer["tp_activation_per_bsz_dict"]
    # consumable by the search-engine reader
    params, acts, off, on = parse_memory_config(
        mem, mode="static", num_layertype=1, seqlen_list=[16],
        sequence_parallel=True)
    assert params[0] > 0 and 1 in acts[0]
    assert "model_states" in off and "first_stage" in on


def test_runtime_profiler_trace_capture(tmp_path):
    """profile.trace_dir captures an XLA trace window (the reference's
    torch.profiler counterpart); stop_trace is idempotent."""
    import glob
    import os

    from hetu_galvatron_tpu.core.args_schema import CoreArgs
    from hetu_galvatron_tpu.core.profiler.runtime_profiler import (
        RuntimeProfiler,
    )

    args = CoreArgs()
    args.profile.profile = 1
    args.profile.profile_warmup = 1
    args.profile.trace_dir = str(tmp_path / "trace")
    args.profile.trace_iters = 2
    prof = RuntimeProfiler(args)
    x = jnp.ones((8, 8))
    for it in range(5):
        prof.time_start(it)
        y = jax.jit(lambda a: a @ a)(x)
        prof.time_end(it, sync=y)
    prof.stop_trace()
    prof.stop_trace()  # idempotent
    files = glob.glob(str(tmp_path / "trace" / "**" / "*"), recursive=True)
    assert any(os.path.isfile(f) for f in files), "no trace files written"


def test_alpha_beta_degenerate_fit_falls_back(cpu_devices):
    """Satellite hardening: a noisy fit with a non-positive slope must NOT
    write a garbage β pair — the (size, consec) falls back to the legacy
    single-point bandwidth (absent keys), with a warning."""
    from hetu_galvatron_tpu.core.profiler.hardware_profiler import (
        fit_alpha_beta,
    )
    from hetu_galvatron_tpu.core.search_engine.profiles import (
        read_alpha_beta,
    )

    # flat and DECREASING synthetic point sets are both degenerate
    with pytest.warns(UserWarning, match="degenerate slope"):
        assert fit_alpha_beta([0.1, 0.5, 1, 2, 4], [1, 1, 1, 1, 1],
                              label="flat") is None
    with pytest.warns(UserWarning, match="degenerate slope"):
        assert fit_alpha_beta([0.1, 0.5, 1, 2, 4],
                              [5, 4, 3, 2, 1], label="neg") is None
    # a healthy set still fits (α clamped ≥ 0)
    pair = fit_alpha_beta([1, 2, 4], [0.9, 2.1, 3.9], label="ok")
    assert pair is not None and pair[0] >= 0 and pair[1] > 0

    # integration: synthetic sp_times whose size-4 curve is constant ->
    # profile_alpha_beta emits NO 4_1 pair, and the strided/other groups
    # it measures live are unaffected (world 2: no strided variant)
    args = HardwareProfileArgs(num_nodes=1, num_devices_per_node=4,
                               start_mb=1, end_mb=4, sub_mb_floor_kb=256,
                               warmup_iters=0, profile_iters=1)
    prof = HardwareProfiler(args, devices=cpu_devices[:4])
    sp = {}
    for size in (4, 2):
        for kb in (256, 512):
            sp[f"sub_allreduce_size_{size}_{kb}KB_time"] = (
                1.0 if size == 4 else kb / 1024.0)
        for mb in (1, 2, 4):
            sp[f"allreduce_size_{size}_{mb}MB_time"] = (
                1.0 if size == 4 else float(mb))
    with pytest.warns(UserWarning, match="allreduce_size_4_consec_1"):
        ab = prof.profile_alpha_beta(sp)
    assert "allreduce_size_4_consec_1_alpha_ms" not in ab
    assert "allreduce_size_4_consec_1_beta_mb_per_ms" not in ab
    assert "allreduce_size_2_consec_1_alpha_ms" in ab
    # the reader sees only the healthy pairs
    pairs = read_alpha_beta(ab)
    assert "4_1" not in pairs and "2_1" in pairs


def test_alpha_beta_algos_roundtrip(cpu_devices):
    """profile_alpha_beta_algos fits per-(algorithm, level) pairs from
    ring vs halving-doubling shaped schedules; read_alpha_beta_algos
    parses them; the FLAT reader and legacy parsers skip the namespaced
    keys untouched."""
    from hetu_galvatron_tpu.core.search_engine.profiles import (
        read_alpha_beta,
        read_alpha_beta_algos,
    )

    args = HardwareProfileArgs(num_nodes=1, num_devices_per_node=4,
                               start_mb=1, end_mb=4, sub_mb_floor_kb=256,
                               warmup_iters=0, profile_iters=1)
    prof = HardwareProfiler(args, devices=cpu_devices[:4])
    algos = prof.profile_alpha_beta_algos()
    # full-world group: ici only; sub-world: ici + the strided dcn proxy
    for key in ("allreduce_size_4_consec_1_alg_ring_lvl_ici_alpha_ms",
                "allreduce_size_4_consec_1_alg_tree_lvl_ici_alpha_ms",
                "allreduce_size_2_consec_0_alg_ring_lvl_dcn_alpha_ms"):
        # CPU timing noise may legitimately drop a degenerate fit; the
        # schema contract is that whatever IS emitted pairs α with β
        if key in algos:
            assert key.replace("_alpha_ms", "_beta_mb_per_ms") in algos
    table = read_alpha_beta_algos(algos)
    for group, curves in table.items():
        for alg_lvl, (a, b) in curves.items():
            assert a >= 0 and b > 0
            alg, lvl = alg_lvl.split("_")
            assert alg in ("ring", "tree") and lvl in ("ici", "dcn")
    # the namespaced keys are INVISIBLE to the flat reader: merging them
    # next to flat pairs does not corrupt the legacy table
    flat = {"allreduce_size_4_consec_1_alpha_ms": 0.5,
            "allreduce_size_4_consec_1_beta_mb_per_ms": 100.0}
    merged = {**flat, **algos}
    assert read_alpha_beta(merged) == read_alpha_beta(flat)
    assert read_alpha_beta_algos(flat) == {}
    # single-process fleet: the dcn level is the strided PROXY and the
    # fitted JSON says so in metadata (a proxy must never silently pass
    # as a fleet measurement); the metadata key is invisible to parsers
    assert algos.get("dcn_level_source") == "proxy-strided"
    assert read_alpha_beta_algos({**algos}) == table


def test_dcn_group_true_multihost_vs_proxy(cpu_devices, recwarn):
    """_dcn_group_devices: with devices spanning processes, the group is
    built round-robin across processes (every hop crosses the seam — a
    true DCN group, tagged 'multihost'); a single-process fleet keeps
    the strided proxy WITH a warning and the 'proxy-strided' tag."""
    from types import SimpleNamespace

    from hetu_galvatron_tpu.core.profiler.hardware_profiler import (
        _dcn_group_devices,
    )

    multi = [SimpleNamespace(id=i, process_index=i // 2) for i in range(8)]
    group, src = _dcn_group_devices(multi, 4, 8)
    assert src == "multihost"
    assert len(group) == 4
    # adjacent group members always sit in DIFFERENT processes
    procs = [d.process_index for d in group]
    assert all(a != b for a, b in zip(procs, procs[1:]))

    import warnings as _w

    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        group, src = _dcn_group_devices(list(cpu_devices[:8]), 4, 8)
    assert src == "proxy-strided"
    assert len(group) == 4
    assert any("strided intra-host PROXY" in str(w.message) for w in rec)
