"""The trace reducer and the trace readers on one recorded trace: the
``--trace 1`` run of ``mistral7b_c1_s4k`` on a TPU v5e (seed 1, PR 22),
five traced steps, kept gzipped beside this file. The numbers below are
that run's, so this pins the reduction: a change to ``xplane.py`` that
moves them is a change of the yardstick."""

import gzip
import os
import shutil

import pytest

from benchmark import flops, manifest, peaks, readers, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace") / "t.xplane.pb")
    with gzip.open(os.path.join(
            HERE, "mistral7b_c1_s4k.seed1.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xplane.facts_of(path, chips=1)


def test_steps_window_busy_and_gaps(trace):
    assert trace["step_module"].startswith("jit_step(")
    assert trace["periods"] == 4
    assert trace["window_s"] == pytest.approx(2.375506917, rel=1e-9)
    assert trace["busy_s"] == pytest.approx(2.354121122, rel=1e-9)
    assert trace["idle_pct"] == pytest.approx(0.9002623754515438)
    assert trace["step_device_ms"] == pytest.approx(588.5302805)
    assert trace["host_gap_ms"] == pytest.approx(4.95369)
    r = trace["reduced"][0]
    assert len(r.steps) == 5 and all(
        (e - s) / 1e6 == pytest.approx(588.9, abs=0.5) for s, e in r.steps)


def test_breakdown_names_the_kernels_and_classes_the_gaps(trace):
    b = trace["breakdown"]
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    ops = dict(map(tuple, b["device_ops"]))
    assert list(ops)[:2] == ["fusion", "select_add_fusion"]
    assert ops["flash_attention_hmajor"] == pytest.approx(0.279248221)
    assert ops["flash_attention_bwd_hmajor"] == pytest.approx(0.194638092)
    assert sum(ops.values()) <= trace["busy_s"]
    gaps = b["idle_gaps"]
    assert gaps[0][0] == "between_steps_total"
    assert gaps[0][1] == pytest.approx(0.019868366)
    assert all(name.split("_")[0] in ("between", "inside")
               for name, _ in gaps)


def _facts_of_the_run(trace):
    sizes = flops.Sizes(layers=2, hidden=4096, heads=32, kv_heads=8,
                        head_dim=128, ffn=14336, ffn_matrices=3,
                        vocab=32000, seq=4096)
    return {"trace": trace, "sizes": sizes, "sequences_per_step": 4,
            "chips": 1, "peaks": peaks.peaks_of("TPU v5 lite")}


def test_the_flash_readers_find_the_kernels_by_the_pattern_in_their_files(
        trace):
    facts = _facts_of_the_run(trace)
    share = readers.read_metric("flash_time_share_pct", facts)
    roof = readers.read_metric("flash_roofline", facts)
    assert share == pytest.approx(20.13007353663258)
    # least time 19.54 ms (compute-bound) over 118.47 ms measured
    assert roof == pytest.approx(100 * 0.01953923970209137 / 0.11847157825)
    assert facts["roofline_bounds"] == {"flash_step_cost": "compute"}
    assert readers.read_metric("device_idle_pct", facts) == pytest.approx(
        trace["idle_pct"])
    # a recorded trace has no map to join (the readers of a named scope
    # need the trainer's own process): nothing, and no raise
    assert readers.read_metric("collective_all_ms", facts) is None
    assert readers.read_metric("experts_ms", facts) is None


def test_a_kernel_s_roofline_cost_may_live_in_a_file_beside_its_metric(
        trace, tmp_path):
    """``reader.cost`` names a function of the ``file`` beside the metric's
    JSON: a new kernel brings its operations and bytes and shares the
    roofline arithmetic."""
    import json

    from benchmark.tests import tiny

    root = tiny.make_root(tmp_path)
    d = os.path.join(root, "benchmark", "layer_metrics")
    with open(os.path.join(d, "flash_again_roofline.json"), "w") as f:
        json.dump({"what": "the flash kernels, their cost in a file",
                   "reader": {"kind": "roofline",
                              "pattern": "^flash_attention",
                              "cost": "half_of_flash",
                              "file": "flash_again_cost.py"}}, f)
    with open(os.path.join(d, "flash_again_cost.py"), "w") as f:
        f.write("from benchmark import flops\n\n\n"
                "def half_of_flash(sizes, sequences):\n"
                "    c = flops.flash_step_cost(sizes, sequences)\n"
                "    return {k: v / 2 for k, v in c.items()}\n")
    facts = _facts_of_the_run(trace)
    assert readers.read_metric("flash_again_roofline", facts, root) == \
        pytest.approx(readers.read_metric("flash_roofline", facts, root) / 2)
    assert facts["roofline_bounds"] == {"half_of_flash": "compute",
                                        "flash_step_cost": "compute"}
    # nothing of that name ran: nothing to read
    tiny.add_new_family(root)
    assert readers.read_metric("router_roofline", facts, root) is None


def test_async_operations_are_kept_apart_from_the_core_s_operations(trace):
    r = trace["reduced"][0]
    assert len(r.in_flight) == 2600
    assert {xplane.stem(n) for n, _, _ in r.in_flight} >= {"slice-start"}
    in_flight = xplane.matching_ns(r, "^slice-start", xplane.ASYNC_LINE)
    # on XLA Ops a -start only issues the transfer: microseconds in all
    assert in_flight > 1000 * xplane.matching_ns(r, "^slice-start")
