"""Attention that is not the decoder's causal span, counted as what it is:
a block of a second stack states its positions, the (query, key) pairs its
mask leaves and its hidden width (``flops.Attention``), the configuration's
file states that stack's depth, and ``flash_step_cost`` and
``attention_flops_per_token`` read them. Numbers written out by hand."""

import dataclasses

import pytest

from benchmark import flops, manifest, run
from benchmark.tests import tiny

# a decoder of Kimi-VL's widths over 4,096 positions, 500 of which hold the
# merged patches of three images of 32 x 32, 24 x 24 and 20 x 20 patches: the
# tower sees 4 x 500 = 2,000 positions of a sequence, and a patch meets its
# own image's patches, both ways
DECODER = flops.Sizes(layers=2, hidden=2048, heads=16, kv_heads=16,
                      head_dim=128, ffn=11264, ffn_matrices=3, vocab=20480,
                      seq=4096)
CONFIG = {
    "num_hidden_layers": 2, "tower_layers": 2,
    "image_patches": [1024, 576, 400],
    "vision_config": {"hidden_size": 1152, "num_attention_heads": 16,
                      "intermediate_size": 4304, "merge_kernel_size": [2, 2],
                      "num_hidden_layers": 27},
    "program": {"equals": {"tower_layers": "tower_layers"}},
    "reference": {"family": "tiny_tower",
                  "second_stack_depth_key": "tower_layers"},
}
PAIRS = 1024 * 1024 + 576 * 576 + 400 * 400       # 1,540,352
CAUSAL = 4096 * 4097 // 2                         # 8,390,656


def _family():
    return manifest.load_python(tiny.NEW_FAMILY_DIR + "/tiny_tower.py")


def _sizes():
    return DECODER.with_attention(
        _family().attention_blocks(CONFIG),
        beside=manifest.second_stack_depth(CONFIG))


def test_the_family_states_the_tower_from_the_configurations_own_keys():
    tower = {"heads": 16, "kv_heads": 16, "qk_head_dim": 72,
             "v_head_dim": 72, "hidden": 1152, "positions": 2000,
             "pairs": PAIRS}
    assert PAIRS == 1_540_352 and 2000 == 4 * 500
    assert _family().attention_blocks(CONFIG) == [tower, tower, {}, {}]
    assert _sizes().attention_blocks() == (
        flops.Attention(**tower),) * 2 + (flops.Attention(),) * 2


def test_a_block_of_the_tower_costs_what_its_positions_and_pairs_say():
    sizes = _sizes()
    tower, decoder = sizes.attention[0], sizes.attention[2]
    # q, k, v and out at 1152 x (16 x 72), 2,000 positions to 4,096 tokens;
    # q.k^T and p.v over the pairs its mask leaves, both ways
    projections = 2 * 1152 * 3 * 16 * 72 + 2 * 16 * 72 * 1152
    assert projections == 10_616_832
    assert flops.attention_flops_per_token(sizes, tower) == (
        projections * 2000 / 4096 + 2 * 16 * (72 + 72) * PAIRS / 4096
    ) == 5_184_000 + 1_732_896
    # the decoder's block beside it is the causal count it was
    assert flops.attention_flops_per_token(sizes, decoder) \
        == flops.attention_flops_per_token(DECODER) == (
            2 * 2048 * 3 * 16 * 128 + 2 * 16 * 128 * 2048
            + 2 * 16 * (128 + 128) * CAUSAL / 4096) == 50_335_744

    # the kernels, two sequences a step: seven matmuls over the pairs; every
    # operand once over the block's OWN positions (q, k, v, o three times in
    # bf16, the float32 row statistics written and read)
    one = lambda entry: flops.flash_step_cost(
        DECODER.with_attention([entry], beside=1), 2)
    assert one(dataclasses.asdict(tower)) == {
        "flops": 2 * 16 * 7 * 72 * 2 * PAIRS,
        "bytes": 2 * 2000 * 3 * 4 * 16 * 72 * 2 + 2 * 2 * 2000 * 16 * 4}
    assert one(dataclasses.asdict(tower)) == {
        "flops": 49_685_594_112, "bytes": 111_104_000}
    assert one({}) == {"flops": 2 * 16 * 7 * 128 * 2 * CAUSAL,
                       "bytes": 2 * 4096 * 3 * 4 * 16 * 128 * 2
                       + 2 * 2 * 4096 * 16 * 4}
    assert flops.flash_step_cost(sizes, 2) == {
        "flops": 2 * (49_685_594_112 + 481_153_777_664),
        "bytes": 2 * (111_104_000 + 403_701_760)}
    # a causal count of the decoder alone over both stacks' kernel time is
    # what flash_roofline would have read: 9.4 % short in operations
    assert flops.flash_step_cost(DECODER, 2)["flops"] \
        / flops.flash_step_cost(sizes, 2)["flops"] == pytest.approx(0.9064,
                                                                    abs=1e-4)
    # positions alone: causal over them, under the block's window
    assert flops.flash_step_cost(DECODER.with_attention(
        [{"positions": 2000, "window": 128}]), 2)["flops"] \
        == 2 * 16 * 7 * 128 * 2 * flops.causal_pairs(2000, 128)


def test_the_familys_forward_count_adds_the_two_stacks_up():
    sizes = _sizes()
    tower = sizes.attention[0]
    attention = 2 * flops.attention_flops_per_token(sizes, tower) \
        + 2 * 50_335_744
    assert _family().forward_flops_per_token(sizes, CONFIG) == (
        attention
        + 2 * (2 * 2 * 1152 * 4304) * 2000 / 4096       # the tower's MLPs
        + 2 * (4608 * 4608 + 4608 * 2048) * 500 / 4096  # the projector
        + 2 * (2 * 2048 * 11264 * 3) + 2 * 2048 * 20480)


@pytest.mark.parametrize("entry,says", [
    ({"position": 2000}, "position"),
    ({"pairs": -1}, "pairs"),
    ({"positions": 2000.0}, "positions"),
    ({"hidden": True}, "hidden"),
])
def test_an_entry_still_holds_known_keys_as_whole_numbers(entry, says):
    with pytest.raises(ValueError, match=f"this one holds.*{says}"):
        DECODER.with_attention([entry], beside=2)


def test_more_blocks_than_the_two_stacks_hold_are_refused():
    entries = _family().attention_blocks(CONFIG)
    assert len(DECODER.with_attention(entries, beside=2).attention) == 4
    with pytest.raises(ValueError, match="describes 4 blocks that attend "
                                         "and the program runs 2"):
        DECODER.with_attention(entries)
    with pytest.raises(ValueError, match="describes 5 blocks that attend "
                                         "and the program runs 4"):
        DECODER.with_attention(entries + [{}], beside=2)


@pytest.mark.parametrize("change,says", [
    ({"program": {"equals": {}}}, "a value of program.equals"),
    ({"tower_layers": -1}, "tower_layers=-1"),
    ({"tower_layers": 2.0}, "tower_layers=2.0"),
    ({"tower_layers": None}, "tower_layers=None"),
])
def test_the_second_stacks_depth_is_a_key_tied_to_the_program(change, says):
    assert manifest.second_stack_depth(CONFIG) == 2
    plain = {k: v for k, v in CONFIG.items() if k != "reference"}
    assert manifest.second_stack_depth(
        {**plain, "reference": {"family": "tiny_tower"}}) == 0
    with pytest.raises(ValueError, match=says):
        manifest.second_stack_depth({**CONFIG, **change})


def test_a_run_counts_the_second_stack_the_configuration_states(tmp_path):
    """Through ``run.measure``: the tiny hybrid family under a configuration
    that states a second stack of two blocks (tied to the one whole number
    the tiny program has that is 2) may describe four attending blocks and
    not five; a depth that is tied to nothing stops the manifest's check."""
    def cell(kinds, **reference):
        root = tiny.make_root(tmp_path / str(kinds))
        body = tiny.TINY_HYBRID
        tiny.add_hybrid_family(
            root, layer_types=["attention"] * kinds, beside_layers=2,
            program={**body["program"], "equals": {
                **body["program"]["equals"],
                "num_hidden_layers": "beside_layers"}},
            reference={**body["reference"],
                       "second_stack_depth_key": "beside_layers",
                       **reference})
        man = manifest.load_manifest(root)
        return root, man, manifest.resolve_cell(man, tiny.HYBRID_CELL[0],
                                                root)

    root, man, five = cell(5)
    assert manifest.check_manifest(man, root) == []
    with pytest.raises(ValueError, match="describes 5 blocks that attend "
                                         "and the program runs 4"):
        run.measure(five, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP,
                    root=root, out_dir=str(tmp_path / "out"),
                    expect_mosaic=False)
    root, man, untied = cell(4, second_stack_depth_key="sliding_window")
    assert any("second_stack_depth_key names 'sliding_window'" in p
               for p in manifest.check_manifest(man, root))
    with pytest.raises(ValueError, match="a value of program.equals"):
        run.measure(untied, seed=7, seconds=0.5, trace=0,
                    chip=tiny.FAKE_CHIP, root=root,
                    out_dir=str(tmp_path / "out"), expect_mosaic=False)
