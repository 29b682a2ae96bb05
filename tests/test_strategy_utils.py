"""Strategy spine tests (parity with reference tests for strategy_utils:
dataclass invariants + strategy list <-> JSON round trip)."""

import pytest

from hetu_galvatron_tpu.utils.strategy import (
    DPType,
    EmbeddingLMHeadStrategy,
    LayerStrategy,
    config2strategy,
    form_strategy,
    print_strategies,
    strategy_list2config,
)

pytestmark = pytest.mark.utils


def test_validate_world_size():
    s = LayerStrategy(pp_deg=2, tp_size=2, dp_size=2)
    s.validate(8)
    with pytest.raises(ValueError):
        s.validate(16)
    with pytest.raises(ValueError):
        LayerStrategy(tp_size=3, dp_size=1).validate(3)


def test_sp_cp_exclusive():
    with pytest.raises(ValueError):
        LayerStrategy(tp_size=2, cp_size=2, sp=True, dp_size=1).validate(4)


def test_round_trip():
    layers = [
        LayerStrategy(pp_deg=2, tp_size=2, dp_size=2, dp_type=DPType.ZERO3,
                      checkpoint=True),
        LayerStrategy(pp_deg=2, tp_size=4, dp_size=1, dp_type=DPType.ZERO2),
        LayerStrategy(pp_deg=2, tp_size=1, dp_size=2, cp_size=2, dp_type=DPType.ZERO2),
        LayerStrategy(pp_deg=2, tp_size=2, dp_size=2, sp=True, dp_type=DPType.ZERO2),
    ]
    vocab = EmbeddingLMHeadStrategy(vtp=2, vsp=True, embed_sdp=True)
    cfg = strategy_list2config(
        layers, global_bsz=16, chunks=4, default_dp_type="zero2", vocab=vocab
    )
    assert cfg["pp_deg"] == 2
    assert cfg["tp_sizes_enc"] == "2,4,1,2"
    assert cfg["dp_types_enc"] == "1,0,0,0"
    assert cfg["use_sp"] == "0,0,0,1"
    assert cfg["cp_sizes_enc"] == "1,1,2,1"
    assert cfg["checkpoint"] == "1,0,0,0"
    assert cfg["vtp"] == 2 and cfg["vsp"] == 1 and cfg["embed_sdp"] == 1

    back, vback, extras = config2strategy(cfg, world_size=8)
    assert [s.key() for s in back] == [s.key() for s in layers]
    assert vback == vocab
    assert extras["global_bsz"] == 16 and extras["chunks"] == 4
    assert extras["predicted_layer_compute_ms"] is None  # not embedded


def test_predicted_layer_compute_ms_roundtrip():
    """Searched plans embed the cost model's per-layer compute prediction;
    it survives the interchange round trip, a wrong-length vector raises at
    write time and is dropped (not mis-attributed) at read time."""
    layers = [LayerStrategy(pp_deg=1, tp_size=2, dp_size=2)
              for _ in range(3)]
    pred = [0.25, 0.5, 0.125]
    cfg = strategy_list2config(
        layers, global_bsz=8, chunks=1, predicted_layer_compute_ms=pred)
    assert cfg["predicted_layer_compute_ms"] == pred
    _, _, extras = config2strategy(cfg, world_size=4)
    assert extras["predicted_layer_compute_ms"] == pred

    with pytest.raises(ValueError, match="predicted_layer_compute_ms"):
        strategy_list2config(
            layers, global_bsz=8, chunks=1,
            predicted_layer_compute_ms=[1.0])

    cfg["predicted_layer_compute_ms"] = [1.0, 2.0]  # hand-edited plan drift
    _, _, extras = config2strategy(cfg, world_size=4)
    assert extras["predicted_layer_compute_ms"] is None


def test_reference_format_json_parses():
    # A reference-shaped config (BASELINE.md row: searched llama2-7b 8-dev plan)
    cfg = {
        "pp_deg": 1,
        "tp_sizes_enc": ",".join(["1"] * 32),
        "tp_consecutive_flags": ",".join(["1"] * 32),
        "dp_types_enc": ",".join(["1"] * 32),
        "use_sp": ",".join(["0"] * 32),
        "checkpoint": ",".join(["1"] * 20 + ["0"] * 12),
        "global_bsz": 16,
        "chunks": 1,
        "pp_division": "32",
        "pipeline_type": "pipedream_flush",
        "default_dp_type": "zero2",
        "vtp": 2,
        "vsp": 1,
        "embed_sdp": 1,
    }
    layers, vocab, extras = config2strategy(cfg, world_size=8)
    assert len(layers) == 32
    assert all(s.dp_type == DPType.ZERO3 for s in layers)  # dp_types_enc==1
    assert sum(s.checkpoint for s in layers) == 20
    assert layers[0].dp_size == 8
    assert vocab.vtp == 2 and vocab.vsp
    assert extras["pipeline_type"] == "pipedream_flush"


def test_pretty_print():
    s = LayerStrategy(pp_deg=2, tp_size=2, dp_size=2, dp_type=DPType.ZERO3,
                      checkpoint=True)
    assert "tp2" in form_strategy(s) and "ckpt" in form_strategy(s)
    txt = print_strategies([s, s, s.with_checkpoint(False)])
    assert "*2" in txt


def test_unrepresentable_dp_type_raises():
    # ZERO2 layer under default ddp cannot be carried by the 1-bit encoding
    layers = [LayerStrategy(tp_size=1, dp_size=8, dp_type=DPType.ZERO2)]
    with pytest.raises(ValueError, match="not representable"):
        strategy_list2config(layers, global_bsz=8, chunks=1, default_dp_type="ddp")


def test_default_pp_division_remainder():
    from hetu_galvatron_tpu.utils.strategy import default_pp_division

    assert default_pp_division(30, 4) == [7, 7, 7, 9]
    assert default_pp_division(32, 4) == [8, 8, 8, 8]
    assert default_pp_division(5, 1) == [5]
    layers = [LayerStrategy(pp_deg=4, tp_size=1, dp_size=2) for _ in range(30)]
    cfg = strategy_list2config(layers, global_bsz=8, chunks=1)
    assert sum(int(x) for x in cfg["pp_division"].split(",")) == 30


def test_tp_of_ep_key_roundtrip():
    layers = [LayerStrategy(tp_size=2, dp_size=4, ep_size=4, etp_size=2)]
    cfg = strategy_list2config(layers, global_bsz=8, chunks=1)
    assert "tp_of_ep_sizes_enc" in cfg and "etp_sizes_enc" not in cfg
    back, _, _ = config2strategy(cfg, world_size=8)
    assert back[0].etp_size == 2
    # legacy spelling still readable
    legacy = dict(cfg)
    legacy["etp_sizes_enc"] = legacy.pop("tp_of_ep_sizes_enc")
    back2, _, _ = config2strategy(legacy, world_size=8)
    assert back2[0].etp_size == 2


def test_config2strategy_validates_world_size():
    cfg = {
        "pp_deg": 1,
        "tp_sizes_enc": "16",  # tp 16 > world 8
        "global_bsz": 8,
        "chunks": 1,
    }
    with pytest.raises(ValueError):
        config2strategy(cfg, world_size=8)


# what a plan file written while the hierarchical dp reduction existed may
# still carry, one case a key
REMOVED_PLAN_KEYS = [("hier_dp", 1), ("hier_bucket_mb", 4.0),
                     ("dp_schedule", "ring"),
                     ("dp_schedule_rankings", {"ring": 0.5, "tree_hd": 0.7})]


@pytest.mark.parametrize("key,value", REMOVED_PLAN_KEYS,
                         ids=[k for k, _ in REMOVED_PLAN_KEYS])
def test_a_removed_plan_key_is_read_past_and_named_once(key, value, tmp_path,
                                                        capsys):
    """The plan loads as the plan without the key does, the key changes
    nothing of what is built from it, and the launcher trains it on the one
    gradient reduction there is, saying so in one line."""
    import json
    import os

    from hetu_galvatron_tpu.cli import train_dist
    from hetu_galvatron_tpu.core.args_schema import CoreArgs
    from hetu_galvatron_tpu.runtime.hybrid_config import (
        get_hybrid_parallel_config,
    )
    from hetu_galvatron_tpu.utils.strategy import IGNORED_PLAN_KEYS

    assert key in IGNORED_PLAN_KEYS
    plain = strategy_list2config(
        [LayerStrategy(pp_deg=1, tp_size=1, dp_size=1)] * 2,
        global_bsz=4, chunks=2)
    assert not set(IGNORED_PLAN_KEYS) & set(plain)   # nothing writes them
    paths = {}
    for name, cfg in (("plain", plain), ("old", {**plain, key: value})):
        paths[name] = str(tmp_path / f"{name}.json")
        with open(paths[name], "w") as f:
            json.dump(cfg, f)

    layers, vocab, extras = config2strategy({**plain, key: value},
                                            world_size=1)
    layers0, vocab0, extras0 = config2strategy(plain, world_size=1)
    assert (layers, vocab) == (layers0, vocab0)
    assert extras == {**extras0, "ignored_keys": (key,)}

    def hpc_of(path):
        args = CoreArgs()
        args.model.num_hidden_layers = 2
        args.parallel.config_mode = "json"
        args.parallel.galvatron_config_path = path
        return get_hybrid_parallel_config(args, 1)

    old, new = hpc_of(paths["old"]), hpc_of(paths["plain"])
    assert old.ignored_plan_keys == (key,) and not new.ignored_plan_keys
    old.ignored_plan_keys = ()
    assert old == new

    yaml = os.path.join(os.path.dirname(train_dist.__file__), "..", "models",
                        "configs", "gpt2-small.yaml")
    size = ["model.hidden_size=32", "model.num_hidden_layers=2",
            "model.num_attention_heads=2", "model.vocab_size=64",
            "model.seq_length=8", "model.max_position_embeddings=16",
            "model.make_vocab_size_divisible_by=1", "train.train_iters=2",
            "parallel.mixed_precision=fp32", "parallel.num_devices=1",
            "data.dataset=random", "parallel.config_mode=json"]
    losses = {}
    for name, path in paths.items():
        out = {}
        assert train_dist.main(
            [yaml] + size + [f"parallel.galvatron_config_path={path}"],
            result=out) == 0
        said = capsys.readouterr()
        losses[name] = (out["losses"], said.out + said.err)
    assert losses["old"][0] == losses["plain"][0]
    said = [line for line in losses["old"][1].splitlines()
            if "are ignored" in line]
    assert len(said) == 1 and f"plan keys {key} are ignored" in said[0]
    assert "flat reduction" in said[0]
    assert "are ignored" not in losses["plain"][1]
