"""Config spine tests (parity with reference tests/test_arguments.py:
YAML + override round-trips through the validated schema)."""

import pytest

from hetu_galvatron_tpu.core.arguments import load_config, parse_overrides, args_from_cli
from hetu_galvatron_tpu.utils.strategy import IGNORED_PLAN_KEYS

pytestmark = pytest.mark.utils


def test_defaults():
    args = load_config()
    assert args.model.hidden_size == 768
    assert args.parallel.mixed_precision == "bf16"
    assert args.mode == "train_dist"


def test_yaml_and_overrides(tmp_path):
    cfg = tmp_path / "m.yaml"
    cfg.write_text(
        "model:\n  hidden_size: 1024\n  num_hidden_layers: 4\n"
        "parallel:\n  global_tp_deg: 2\n"
    )
    args = load_config(str(cfg), ["model.hidden_size=2048", "++parallel.pp_deg=2"])
    assert args.model.hidden_size == 2048  # override wins over yaml
    assert args.model.num_hidden_layers == 4
    assert args.parallel.global_tp_deg == 2
    assert args.parallel.pp_deg == 2


def test_include_composition(tmp_path):
    (tmp_path / "base.yaml").write_text("model:\n  vocab_size: 32000\n  hidden_size: 64\n")
    child = tmp_path / "child.yaml"
    child.write_text("include: base.yaml\nmodel:\n  hidden_size: 128\n")
    args = load_config(str(child))
    assert args.model.vocab_size == 32000
    assert args.model.hidden_size == 128


def test_override_types():
    t = parse_overrides(["a.b=8", "a.c=true", "a.d=1e-4", "a.e=hello"])
    assert t == {"a": {"b": 8, "c": True, "d": 1e-4, "e": "hello"}}


def test_invalid_value_rejected():
    with pytest.raises(Exception):
        load_config({"parallel": {"mixed_precision": "fp64"}})


def test_cli_convention(tmp_path):
    cfg = tmp_path / "c.yaml"
    cfg.write_text("model:\n  hidden_size: 256\n")
    args = args_from_cli([str(cfg), "train.lr=0.5"], mode="train_dist")
    assert args.model.hidden_size == 256 and args.train.lr == 0.5


def test_derived_model_fields():
    args = load_config({"model": {"hidden_size": 512, "num_attention_heads": 8,
                                  "vocab_size": 50257}})
    assert args.model.head_dim == 64
    assert args.model.padded_vocab_size % 128 == 0
    assert args.model.kv_heads == 8



# the five options of the hierarchical dp reduction, by the names the plan
# loader still reads past: three of ``parallel``, the first two of ``search``
REMOVED_OPTIONS = ([("parallel", key, value) for key, value in zip(
    IGNORED_PLAN_KEYS, (True, 4.0, "ring"))] + [("search", key, value)
    for key, value in zip(IGNORED_PLAN_KEYS, (1, -1.0))])


@pytest.mark.parametrize("section,option,value", REMOVED_OPTIONS,
                         ids=[f"{s}.{o}" for s, o, _ in REMOVED_OPTIONS])
def test_a_removed_option_is_no_field_and_moves_nothing(section, option,
                                                        value):
    """The options of the hierarchical dp reduction are gone: a file that
    still sets one resolves to the configuration it is without the line
    (the schema reads past unknown keys), on the one reduction there is."""
    args = load_config({section: {option: value}})
    assert not hasattr(getattr(args, section), option)
    assert args == load_config()


def test_the_schema_holds_378_options():
    """What a reader of ``core/args_schema.py`` has to know: the annotated
    fields of its 18 classes (375 before the hierarchical dp reduction and
    its five options went; 370 until Olmo Hybrid's six published
    ``linear_*`` keys, ``linear_chunk_size`` and ``norm_positions``). A PR
    that adds an option says so here."""
    import ast
    import inspect

    from hetu_galvatron_tpu.core import args_schema

    classes = [n for n in ast.parse(inspect.getsource(args_schema)).body
               if isinstance(n, ast.ClassDef)
               and any(isinstance(x, ast.AnnAssign) for x in n.body)]
    assert len(classes) == 18
    assert sum(isinstance(x, ast.AnnAssign)
               for c in classes for x in c.body) == 378
