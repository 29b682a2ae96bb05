"""A temporary copy of the benchmark with tiny cells, and an architecture
the harness has never seen, added as NEW FILES and new entries only: what a
later PR does, and what the CPU tests run."""

import json
import os
import shutil

from benchmark import manifest

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
