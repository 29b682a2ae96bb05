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


def _one_four_chip_cell_too_many(man):
    """Whatever the count of cells: as many on four chips as a quarter of
    them, rounded down, and one more."""
    cells = man["workloads"]
    for w in cells[:len(cells) // 4 + 1]:
        w.update(chips=4)


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
    (_one_four_chip_cell_too_many, "25%"),
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
    tiny.assert_nothing_that_was_there_is_edited(root)
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


def _unknown_family(root):
    path = os.path.join(root, "benchmark", "configs", "tiny-mixtral.json")
    body = manifest.read_json(path)
    body["reference"]["family"] = "never_written"
    with open(path, "w") as f:
        json.dump(body, f)


def _roofline_cost_nowhere(root):
    path = manifest.layer_metric_path(root, "router_roofline")
    body = manifest.read_json(path)
    del body["reader"]["file"]
    with open(path, "w") as f:
        json.dump(body, f)


def _roofline_of_nothing(root):
    path = manifest.layer_metric_path(root, "router_roofline")
    body = manifest.read_json(path)
    body["reader"]["scopes"] = ["moe/route"]      # beside its pattern
    with open(path, "w") as f:
        json.dump(body, f)


@pytest.mark.parametrize("how,says", [
    (_roofline_of_nothing, "router_roofline: a roofline reader names a "
                           "pattern or scopes, one of the two"),
    (_unknown_family, "reference family 'never_written' has no file "
                      "benchmark/reference/never_written.py"),
    (lambda root: os.remove(os.path.join(
        root, "benchmark", "layer_metrics", "router_cost.py")),
     "router_roofline: its reader names 'router_cost.py'"),
    (_roofline_cost_nowhere, "cost 'router_step_cost' is no function of "
                             "benchmark/flops.py"),
])
def test_manifest_check_names_a_missing_family_or_cost_file(
        tmp_path, how, says):
    """What a new architecture brings is found by name, and what is not
    there is named before any chip time is spent."""
    root = tiny.make_root(tmp_path)
    tiny.add_new_family(root)
    assert manifest.check_manifest(manifest.load_manifest(root), root) == []
    how(root)
    problems = manifest.check_manifest(manifest.load_manifest(root), root)
    assert any(says in p for p in problems), problems


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
    assert flops.mfu_pct(rate, flops.train_flops_per_token(m), 4,
                         197e12) == pytest.approx(100.0)
    cost = flops.flash_step_cost(g, sequences=16)
    assert cost["flops"] == pytest.approx(
        16 * 7 * 2 * 25 * 64 * 16 * 1024 * 512.5)
    assert flops.roofline_least_s(cost, {"bf16_flops_per_s": 197e12,
                                         "hbm_bytes_per_s": 819e9}
                                  )["bound"] == "compute"


def _model_args(cell):
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    return resolve_model_config(args_from_cli(
        manifest.train_argv(cell, seed=0), mode="train_dist")).model


@pytest.mark.parametrize("config", ["gpt2-xl", "mistral-7b-d8",
                                    "mistral-7b-d2"])
def test_a_dense_family_s_flops_are_the_dense_count_exactly(config):
    """The count moved from ``run.py`` into the family's file and did not
    change: ``mfu_pct / tokens_per_s`` of every cell is the constant it was."""
    from benchmark import flops, reference

    man = manifest.load_manifest()
    name = next(w["name"] for w in man["workloads"] if w["config"] == config)
    cell = manifest.resolve_cell(man, name)
    sizes = flops.Sizes.of(_model_args(cell))
    family = reference.load_family(cell.config["reference"]["family"])
    assert flops.train_from_forward(family.forward_flops_per_token(
        sizes, cell.config)) == flops.train_flops_per_token(sizes)
    assert sizes.experts == 0


def test_the_dense_count_refuses_a_program_with_experts():
    import dataclasses

    from benchmark import flops

    m = flops.Sizes(layers=1, hidden=2048, heads=16, kv_heads=16,
                    head_dim=128, ffn=1024, ffn_matrices=3, vocab=50304,
                    seq=4096)
    flops.forward_flops_per_token(m)
    with pytest.raises(ValueError, match="64 experts a layer"):
        flops.forward_flops_per_token(dataclasses.replace(m, experts=64))


@pytest.mark.parametrize("config,key", [
    ({"reference": {}, "reduced_from": {"n_layer": 48}}, "n_layer"),
    ({"reference": {"depth_key": "num_hidden_layers"},
      "reduced_from": {"num_hidden_layers": 16, "num_experts": 64}},
     "num_hidden_layers"),
    ({"reference": {}, "reduced_from": {"num_hidden_layers": 16,
                                        "num_experts": 64}}, None),
    ({"reference": {}}, None),
])
def test_reference_variants_take_the_depth_key_from_the_configuration(
        config, key):
    from benchmark import reference_variants

    if key is None:
        with pytest.raises(SystemExit, match="reference.depth_key"):
            reference_variants.depth_key(config)
    else:
        assert reference_variants.depth_key(config) == key
