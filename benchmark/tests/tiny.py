"""A temporary copy of the benchmark with tiny cells added as NEW FILES and
new entries only: what a later PR does, and what the CPU tests run."""

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
# a stand-in row of peaks so the arithmetic runs; nothing is reported
FAKE_CHIP = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 1e9, "ici_bits_per_s": 1e9}


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


def make_root(tmp_path) -> str:
    """Copy BENCHMARK.json and the benchmark's data directories, then ADD
    the tiny configurations, traffic mixes and cells."""
    root = str(tmp_path / "root")
    os.makedirs(os.path.join(root, "benchmark"))
    for d in ("configs", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(manifest.ROOT, "benchmark", d),
                        os.path.join(root, "benchmark", d))
    man = manifest.load_manifest()
    for name, body in (("tiny-gpt2", TINY_GPT2),
                       ("tiny-mistral", TINY_MISTRAL)):
        _write(os.path.join(root, "benchmark", "configs", name + ".json"),
               body)
        man["configs"].append({
            "name": name, "source": "benchmark/tests/tiny.py",
            "file": f"benchmark/configs/{name}.json", "reduced": [],
            "why": "CPU test fixture"})
    for name, over in TINY_TRAFFIC.items():
        _write(manifest.traffic_path(root, name), {"overrides": over})
    for name, cfg, traffic, chips in TINY_CELLS:
        man["workloads"].append({"name": name, "config": cfg,
                                 "traffic": traffic, "chips": chips,
                                 "why": "CPU test fixture"})
    _write(os.path.join(root, "BENCHMARK.json"), man)
    return root
