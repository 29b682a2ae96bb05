"""The manifest check, and "a new cell is a new file"."""

import copy
import json
import os

import pytest

from benchmark import manifest
from benchmark.tests import tiny


def test_the_repository_manifest_holds_the_contract():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    assert man["run_seconds"] <= manifest.max_run_seconds() == 51
    assert man["paths"] == ["benchmark"]
    # the file is what is run: depth and every width as the program has them
    for c in man["configs"]:
        body = manifest.read_json(os.path.join(manifest.ROOT, c["file"]))
        assert body["source"] == c["source"]
        assert sorted(body["reduced_from"]) == sorted(c["reduced"])


def _break(man, how):
    man = copy.deepcopy(man)
    how(man)
    return manifest.check_manifest(man)


@pytest.mark.parametrize("how,says", [
    (lambda m: m["workloads"][0].update(name="has space"), "name"),
    (lambda m: m["workloads"][0].update(name="a/b"), "name"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per s"), "unit"),
    (lambda m: m["end_to_end"][0].update(unit="µs"), "unit"),
    (lambda m: m["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda m: m["end_to_end"][0].update(bound=0.001), "bound"),
    (lambda m: m["end_to_end"][0].update(source="program_counter"),
     "taken by the benchmark"),
    (lambda m: m["end_to_end"][0].update(why="x"), "keys"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda m: m["workloads"][1].update(chips=4), "25%"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: m["workloads"].pop(0) and m["workloads"].pop(0),
     "has no cell"),
    (lambda m: m["workloads"][1].update(
        config=m["workloads"][0]["config"],
        traffic=m["workloads"][0]["traffic"]), "appears twice"),
    (lambda m: m["configs"][0].update(reduced=["n_layer", "n_embd"]),
     "width"),
    (lambda m: m["configs"][0].update(reduced=[]), "reduced"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
    (lambda m: m.update(run_seconds=10.5), "run_seconds"),
    (lambda m: m.update(command=["python3", "chip_smoke.py"]),
     "outside paths"),
    (lambda m: m.update(command=["python3", "../x.py"]), "leaves the repo"),
    (lambda m: m.update(extra=1), "top-level"),
    (lambda m: m["end_to_end"].pop(2), "setup_s"),
    (lambda m: m["per_layer"].append(dict(m["per_layer"][0],
                                          name="no_such_reader")),
     "no reader file"),
])
def test_manifest_check_names_what_is_wrong(how, says):
    problems = _break(manifest.load_manifest(), how)
    assert any(says in p for p in problems), problems


def test_a_new_cell_is_new_files_and_new_entries_only(tmp_path):
    """The tiny cells are added to a copy of the benchmark without editing
    a file that was there, and the harness finds them by name."""
    root = tiny.make_root(tmp_path)
    man = manifest.load_manifest(root)
    assert manifest.check_manifest(man, root) == []
    for d in ("configs", "workloads", "layer_metrics"):
        for f in os.listdir(os.path.join(manifest.ROOT, "benchmark", d)):
            with open(os.path.join(manifest.ROOT, "benchmark", d, f)) as a, \
                    open(os.path.join(root, "benchmark", d, f)) as b:
                assert a.read() == b.read(), f
    cell = manifest.resolve_cell(man, "tiny_mistral_c4", root)
    assert cell.chips == 4 and cell.config["hidden_size"] == 32
    argv = manifest.train_argv(cell, seed=3, root=root)
    assert "parallel.num_devices=4" in argv and "train.seed=3" in argv
    assert "parallel.global_tp_deg=2" in argv
    # every old cell still resolves, untouched
    for w in manifest.load_manifest()["workloads"]:
        assert manifest.resolve_cell(man, w["name"], root).name == w["name"]


def test_a_new_per_layer_metric_is_a_new_file(tmp_path):
    from benchmark import readers

    root = tiny.make_root(tmp_path)
    d = os.path.join(root, "benchmark", "layer_metrics")
    with open(os.path.join(d, "steps_in_window.json"), "w") as f:
        json.dump({"what": "a counter", "reader": {
            "kind": "fact", "key": "window.steps"}}, f)
    with open(os.path.join(d, "twice_the_steps.json"), "w") as f:
        json.dump({"what": "a reader of its own", "reader": {
            "kind": "python", "file": "twice_the_steps.py",
            "function": "read"}}, f)
    with open(os.path.join(d, "twice_the_steps.py"), "w") as f:
        f.write("def read(facts):\n    return 2 * facts['window']['steps']\n")
    facts = {"window": {"steps": 21}}
    assert readers.read_metric("steps_in_window", facts, root) == 21.0
    assert readers.read_metric("twice_the_steps", facts, root) == 42
    # a reader that finds nothing to read returns nothing
    assert readers.read_metric("device_idle_pct", facts, root) is None
    assert readers.read_metric("flash_roofline", facts, root) is None


def test_peaks_raise_for_an_unknown_chip():
    from benchmark import peaks

    assert peaks.peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_of("TPU v9 imaginary")


def test_flops_count_attention_causal_and_no_recomputation():
    from benchmark import flops

    g = flops.Sizes(layers=16, hidden=1600, heads=25, kv_heads=25,
                    head_dim=64, ffn=6400, ffn_matrices=2, vocab=50257,
                    seq=1024)
    per_layer = (2 * 1600 * 4800 + 2 * 1600 * 1600 + 2 * 2 * 1600 * 6400
                 + 4 * 1600 * 512.5)
    assert flops.train_flops_per_token(g) == pytest.approx(
        3 * (16 * per_layer + 2 * 1600 * 50257))
    m = flops.Sizes(layers=8, hidden=4096, heads=32, kv_heads=8,
                    head_dim=128, ffn=14336, ffn_matrices=3, vocab=32000,
                    seq=4096)
    assert flops.train_flops_per_token(m) / 1e9 == pytest.approx(12.06, abs=0.01)
    # 100 % MFU at the rate that needs exactly the peak
    rate = 4 * 197e12 / flops.train_flops_per_token(m)
    assert flops.mfu_pct(rate, m, 4, 197e12) == pytest.approx(100.0)
    cost = flops.flash_step_cost(g, sequences=16)
    assert cost["flops"] == pytest.approx(
        16 * 7 * 2 * 25 * 64 * 16 * 1024 * 512.5)
    assert flops.roofline_least_s(cost, {"bf16_flops_per_s": 197e12,
                                         "hbm_bytes_per_s": 819e9}
                                  )["bound"] == "compute"
