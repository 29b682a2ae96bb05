"""A temporary copy of the benchmark with tiny cells, and an architecture
the harness has never seen, added as NEW FILES and new entries only: what a
later PR does, and what the CPU tests run."""

import json
import os
import shutil

from benchmark import manifest
from benchmark.tests import pr65

YAMLS = os.path.join(manifest.ROOT, "hetu_galvatron_tpu", "models", "configs")

TINY_GPT2 = {
    "activation_function": "gelu_new", "layer_norm_epsilon": 1e-05,
    "model_type": "gpt2", "n_embd": 32, "n_head": 2, "n_layer": 2,
    "n_positions": 16, "vocab_size": 60,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(YAMLS, "gpt2-small.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=2",
            "model.num_attention_heads=2", "model.vocab_size=60",
            "model.make_vocab_size_divisible_by=8", "model.seq_length=8",
            "model.max_position_embeddings=16"],
        "equals": {"hidden_size": "n_embd", "num_hidden_layers": "n_layer",
                   "vocab_size": "vocab_size"}},
    "reference": {"family": "gpt2", "loss_tolerance": 0.02},
}

TINY_MISTRAL = {
    "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 64,
    "max_position_embeddings": 32, "model_type": "mistral",
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "vocab_size": 64,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(YAMLS, "mistral-7b.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=2",
            "model.num_attention_heads=4", "model.num_key_value_heads=2",
            "model.ffn_hidden_size=64", "model.vocab_size=64",
            "model.make_vocab_size_divisible_by=1", "model.seq_length=16",
            "model.max_position_embeddings=32"],
        "equals": {"hidden_size": "hidden_size",
                   "ffn_hidden_size": "intermediate_size"}},
    "reference": {"family": "mistral", "loss_tolerance": 0.02},
}

COMMON = ["data.dataset=random", "parallel.mixed_precision=bf16",
          "parallel.global_checkpoint=1"]
TINY_TRAFFIC = {
    "tiny_c1": COMMON + ["parallel.global_train_batch_size=4",
                         "parallel.chunks=2"],
    "tiny_c1_b2": COMMON + ["parallel.global_train_batch_size=2",
                            "parallel.chunks=1"],
    "tiny_c4": COMMON + ["parallel.global_tp_deg=2", "parallel.sdp=1",
                         "parallel.global_train_batch_size=8",
                         "parallel.chunks=2"],
}
TINY_CELLS = [   # two of eight cells on four chips: the 25 % the contract allows
    ("tiny_gpt2_c1", "tiny-gpt2", "tiny_c1", 1),
    ("tiny_gpt2_c1_b2", "tiny-gpt2", "tiny_c1_b2", 1),
    ("tiny_mistral_c1", "tiny-mistral", "tiny_c1", 1),
    ("tiny_mistral_c4", "tiny-mistral", "tiny_c4", 4),
]

# an architecture the harness does not know: a sparse mixture of experts the
# program already trains (mixtral-8x7b.yaml), its reference family, its FLOP
# count and a kernel's cost all in files of its own (tests/new_family/)
NEW_FAMILY_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "new_family")
TINY_MIXTRAL = {
    "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 64,
    "max_position_embeddings": 32, "model_type": "mixtral",
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 2,
    "num_local_experts": 4, "rms_norm_eps": 1e-05, "rope_theta": 1000000.0,
    "router_aux_loss_coef": 0.0, "tie_word_embeddings": False,
    "vocab_size": 64,
    "program": {
        "driver": "train_dist",
        "yaml": os.path.join(YAMLS, "mixtral-8x7b.yaml"),
        "overrides": [
            "model.hidden_size=32", "model.num_hidden_layers=2",
            "model.num_attention_heads=4", "model.num_key_value_heads=2",
            "model.ffn_hidden_size=64", "model.vocab_size=64",
            "model.make_vocab_size_divisible_by=1", "model.seq_length=16",
            "model.max_position_embeddings=32", "model.num_experts=4",
            "model.moe_topk=2",
            # the reported loss is then the token cross-entropy alone, and no
            # token is dropped at an expert's capacity
            "model.moe_aux_loss_coeff=0.0", "model.moe_dispatcher=dropless"],
        "equals": {"hidden_size": "hidden_size",
                   "ffn_hidden_size": "intermediate_size",
                   "num_experts": "num_local_experts",
                   "moe_topk": "num_experts_per_tok",
                   "moe_aux_loss_coeff": "router_aux_loss_coef"}},
    "reference": {"family": "tiny_mixtral", "loss_tolerance": 0.02},
}
NEW_FAMILY_CELL = ("tiny_mixtral_c1", "tiny-mixtral", "tiny_c1", 1)

# a stack whose blocks differ: the tiny Mistral program under a family that
# says which blocks attend and over what span (tests/new_family/
# tiny_hybrid.py). Both of its blocks attend, the second over a window of
# the whole sequence, which is the mask the program (it has no window) runs
TINY_HYBRID = {
    **TINY_MISTRAL, "layer_types": ["attention", "sliding_attention"],
    "sliding_window": 16,
    "reference": {"family": "tiny_hybrid", "loss_tolerance": 0.02},
}
HYBRID_CELL = ("tiny_hybrid_c1", "tiny-hybrid", "tiny_c1", 1)

# a batch that holds more than ids: documents packed into the tiny Mistral
# program's sequences from an indexed corpus that a test writes, under a
# family that takes the batch's other fields (tests/new_family/
# tiny_packed.py). The corpus's path is the test's, so the traffic file is
# written with it (``add_packed_family``)
PACKED_EOD = 63          # of the 64 ids; no other token of a document is it
PACKED_TRAFFIC = [o for o in COMMON if o != "data.dataset=random"] + [
    "data.dataset=indexed", "data.reset_position_ids=true",
    "data.reset_attention_mask=true", "data.eod_mask_loss=true",
    "parallel.global_train_batch_size=4", "parallel.chunks=2"]
PACKED_CELL = ("tiny_packed_c1", "tiny-packed", "tiny_c1_packed", 1)
# at this size attention moves the loss little, so the fixture states a
# tolerance of its own: the program's step 0 lay within 6.8e-5 of the family
# on seeds 7 to 12, and a family that leaves ``segment_ids`` unread 7.3e-4
# (seed 7) to 2.5e-3 (seeds 8, 9) from it
PACKED_TOLERANCE = 3e-4

# a stand-in row of peaks so the arithmetic runs; nothing is reported
FAKE_CHIP = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 1e9, "ici_bits_per_s": 1e9}


NOT_COPIED = ("out", "__pycache__")   # what a run and an import leave behind


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


def make_root(tmp_path) -> str:
    """Copy BENCHMARK.json and the whole of ``benchmark/``, then ADD the
    tiny configurations, traffic mixes and cells."""
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(*NOT_COPIED))
    man = manifest.load_manifest()
    for name, body in (("tiny-gpt2", TINY_GPT2),
                       ("tiny-mistral", TINY_MISTRAL)):
        _add_config(root, man, name, body)
    for name, over in TINY_TRAFFIC.items():
        _write(manifest.traffic_path(root, name), {"overrides": over})
    for cell in TINY_CELLS:
        _add_cell(man, *cell)
    _write(os.path.join(root, "BENCHMARK.json"), man)
    return root


def original_files():
    """Every file of the repository's ``benchmark/`` that ``make_root``
    copies, relative to it."""
    top = os.path.join(manifest.ROOT, "benchmark")
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs if x not in NOT_COPIED]
        for f in files:
            yield os.path.relpath(os.path.join(d, f), top)


def assert_nothing_that_was_there_is_edited(root: str) -> None:
    """Every file of the original ``benchmark/``, the Python among it, is
    in the copy byte for byte: whatever was added, was added."""
    for rel in original_files():
        with open(os.path.join(manifest.ROOT, "benchmark", rel), "rb") as a, \
                open(os.path.join(root, "benchmark", rel), "rb") as b:
            assert a.read() == b.read(), rel


# what a later PR may not edit, whatever its kind but ``benchmark``: the
# data, and each family's reference. The harness's own Python, its README and
# its tests are what a ``benchmark`` PR changes (PR 58 did), so a test that
# holds a commit's files against today's holds these alone
DATA_DIRS = ("configs", "workloads", "layer_metrics", "reference")
NOT_DATA = ("benchmark/reference/__init__.py",)


def data_files_as_they_were_at(commit: str, least: int):
    """Against ``commit``, where git and the commit are at hand (else the
    test is skipped): every data file it has under ``benchmark/`` is here
    byte for byte, of ``least`` files or more under ``benchmark/`` in all,
    but those a ``benchmark`` PR has since rewritten or taken away
    (``pr65.REWRITTEN``, ``pr65.RETIRED_FILES``: only such a PR may).
    Returns its ``BENCHMARK.json``."""
    import subprocess

    import pytest

    def git(*words):
        return subprocess.run(["git", *words], cwd=manifest.ROOT,
                              capture_output=True, check=True).stdout
    try:
        had = git("ls-tree", "-r", "--name-only", commit, "--",
                  "benchmark").decode().split()
        was = json.loads(git("show", f"{commit}:BENCHMARK.json"))
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git, or the commit is not in this checkout")
    assert len(had) > least
    for rel in had:
        if rel in pr65.REWRITTEN or rel in pr65.RETIRED_FILES:
            continue
        if rel.split("/")[1] in DATA_DIRS and rel not in NOT_DATA:
            with open(os.path.join(manifest.ROOT, rel), "rb") as f:
                assert f.read() == git("show", f"{commit}:{rel}"), rel
    return was


def listed_for(man, cell: str):
    """The per-layer metrics whose ``workloads`` name ``cell``: what a cell
    lists beside the metrics of every cell. A set: where an entry stands in
    the list says nothing, and a shared entry is several cells'."""
    return {m["name"] for m in man["per_layer"]
            if cell in m.get("workloads", ())}


def cost_beside_the_metrics(file: str, function: str):
    """A cost function of ``benchmark/layer_metrics/<file>``, called as the
    roofline reader calls it: ``cost(sizes, sequences, config=,
    microbatches=)``, of which the function is handed what it names."""
    from benchmark import readers

    fn = getattr(manifest.load_python(os.path.join(
        manifest.ROOT, "benchmark", "layer_metrics", file)), function)
    return lambda sizes, sequences, config=None, microbatches=None: \
        readers.cost_of(fn, {"sizes": sizes, "sequences_per_step": sequences,
                             "config": config,
                             "microbatches_per_step": microbatches})


def assert_the_manifest_begins_with(was) -> None:
    """``BENCHMARK.json`` still begins with what it held: the lists of
    configurations and cells with the entries they had, every other key as
    it was. Of the metrics, which only a ``benchmark`` PR may change: an
    end-to-end metric is as it was but for its bound (PR 58 widened two);
    a per-layer metric that PR 65 did not fold into a shared entry or
    retire (``pr65.RETIRED``) is as it was, but that its ``workloads`` may
    have grown (a later cell appends itself to a shared entry)."""
    now = manifest.load_manifest()
    for key, value in was.items():
        if key == "end_to_end":
            strip = lambda ms: [{k: v for k, v in m.items() if k != "bound"}
                                for m in ms]
            assert strip(now[key][:len(value)]) == strip(value), key
        elif key == "per_layer":
            listed = {m["name"]: m for m in now[key]}
            for m in value:
                if m["name"] in pr65.RETIRED:
                    assert m["name"] not in listed, m["name"]
                    continue
                got = dict(listed[m["name"]])
                assert ("workloads" in got) == ("workloads" in m), m["name"]
                assert set(got.pop("workloads", ())) >= set(
                    m.get("workloads", ())), m["name"]
                assert got == {k: v for k, v in m.items()
                               if k != "workloads"}, m["name"]
        elif isinstance(value, list) and value and isinstance(value[0], dict):
            assert now[key][:len(value)] == value, key
        else:
            assert now[key] == value, key


def _add_config(root, man, name, body):
    _write(os.path.join(root, "benchmark", "configs", name + ".json"), body)
    man["configs"].append({
        "name": name, "source": "benchmark/tests/tiny.py",
        "file": f"benchmark/configs/{name}.json", "reduced": [],
        "why": "CPU test fixture"})


def _add_cell(man, name, cfg, traffic, chips):
    man["workloads"].append({"name": name, "config": cfg, "traffic": traffic,
                             "chips": chips, "why": "CPU test fixture"})


def add_new_family(root: str) -> None:
    """What a later ``model_config`` PR does for a new architecture, in the
    copy ``make_root`` made: the family's file, a configuration that names
    it, a cell, and a roofline metric whose cost is in a file beside it.
    New files and new entries only."""
    shutil.copy(os.path.join(NEW_FAMILY_DIR, "tiny_mixtral.py"),
                manifest.family_path(root, "tiny_mixtral"))
    metrics = os.path.join(root, "benchmark", "layer_metrics")
    shutil.copy(os.path.join(NEW_FAMILY_DIR, "router_cost.py"), metrics)
    _write(os.path.join(metrics, "router_roofline.json"), {
        "what": "least time by the roofline for the routers' logits over "
                "their measured time",
        "reader": {"kind": "roofline", "pattern": "^router",
                   "cost": "router_step_cost", "file": "router_cost.py"}})
    man = manifest.load_manifest(root)
    _add_config(root, man, "tiny-mixtral", TINY_MIXTRAL)
    _add_cell(man, *NEW_FAMILY_CELL)
    man["per_layer"].append({
        "name": "router_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "mfu_pct",
        "workloads": [NEW_FAMILY_CELL[0]]})
    _write(os.path.join(root, "BENCHMARK.json"), man)


def add_hybrid_family(root: str, **config) -> None:
    """A family that exports ``attention_blocks``, its configuration (keys
    of ``config`` laid over ``TINY_HYBRID``) and a cell: new files and new
    entries only."""
    shutil.copy(os.path.join(NEW_FAMILY_DIR, "tiny_hybrid.py"),
                manifest.family_path(root, "tiny_hybrid"))
    man = manifest.load_manifest(root)
    _add_config(root, man, "tiny-hybrid", {**TINY_HYBRID, **config})
    _add_cell(man, *HYBRID_CELL)
    _write(os.path.join(root, "BENCHMARK.json"), man)


def write_packed_corpus(prefix: str, seed: int = 0, documents: int = 600
                        ) -> None:
    """An indexed corpus in the program's own format (its public writer and
    the ``.meta.json`` its preprocessor leaves beside it): documents of 2 to
    11 ids below ``PACKED_EOD``, each ended by it, so that a sequence of 16
    holds the ends of one to five."""
    import numpy as np

    from hetu_galvatron_tpu.data.indexed_dataset import write_indexed_dataset

    rng = np.random.default_rng(seed)
    write_indexed_dataset(prefix, [
        list(rng.integers(0, PACKED_EOD, rng.integers(2, 12))) + [PACKED_EOD]
        for _ in range(documents)])
    _write(prefix + ".meta.json", {"vocab_size": TINY_MISTRAL["vocab_size"],
                                   "eod_id": PACKED_EOD})


def add_packed_family(root: str, corpus: str, family: str = "tiny_packed",
                      source: str = "") -> None:
    """A family that takes the batch's other fields, its configuration, the
    traffic that packs documents from ``corpus`` and a cell: new files and
    new entries only. A control names a ``family`` that is there already,
    or gives the ``source`` of another."""
    path = manifest.family_path(root, family)
    if not os.path.isfile(path) and source:
        with open(path, "w") as f:
            f.write(source)
    elif not os.path.isfile(path):
        shutil.copy(os.path.join(NEW_FAMILY_DIR, "tiny_packed.py"), path)
    _write(manifest.traffic_path(root, PACKED_CELL[2]),
           {"overrides": PACKED_TRAFFIC + [f"data.data_path=[{corpus}]"]})
    man = manifest.load_manifest(root)
    _add_config(root, man, PACKED_CELL[1], {
        **TINY_MISTRAL,
        "reference": {"family": family,
                      "loss_tolerance": PACKED_TOLERANCE}})
    _add_cell(man, *PACKED_CELL)
    _write(os.path.join(root, "BENCHMARK.json"), man)
