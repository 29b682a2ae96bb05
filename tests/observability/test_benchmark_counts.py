"""The benchmark's counts, in tier-1: which blocks of a stack attend and over
what span, through ``flops.Sizes`` into the flash kernels' cost and a
family's FLOPs. The cases are ``benchmark/tests/test_attention_blocks.py``'s
arithmetic ones (that file runs in no driver run), imported and collected
here so that one text is run in both places; and the family of the stack
whose blocks differ, ``lfm2_moe``, and the family with a recurrence,
``granite_hybrid``, at their published widths."""

import pytest

from benchmark import flops, manifest, reference
from benchmark.tests.test_attention_blocks import (  # noqa: F401
    test_a_description_longer_than_the_stack_is_refused,
    test_a_window_of_w_keys_is_the_sum_of_min_i_plus_1_and_w,
    test_an_entry_holds_the_five_keys_as_whole_numbers,
    test_attention_in_query_blocks_and_over_a_window,
    test_one_attending_block_in_four_is_a_quarter_of_the_cost,
    test_q_k_width_192_and_v_width_128_split_the_seven_matmuls_4_to_3,
    test_the_cells_take_one_call_and_a_longer_sequence_takes_blocks,
    test_the_default_description_counts_what_the_parent_counted,
)

pytestmark = pytest.mark.observability

LFM2 = dict(layers=5, hidden=2048, heads=32, kv_heads=8, head_dim=64,
            ffn=11776, ffn_matrices=3, vocab=8192, seq=8192, experts=64)


def _cell():
    return manifest.resolve_cell(manifest.load_manifest(), "lfm2moe_c1_s8k")


def test_the_conv_blocks_of_lfm2_cost_the_flash_kernels_nothing():
    cell = _cell()
    family = reference.load_family("lfm2_moe")
    blocks = family.attention_blocks(cell.config)
    assert len(blocks) == cell.config["layer_types"].count("full_attention")
    sizes = flops.Sizes(**LFM2).with_attention(blocks)
    every = flops.flash_step_cost(flops.Sizes(**LFM2), 2)
    mine = flops.flash_step_cost(sizes, 2)
    assert mine["flops"] * 5 == every["flops"] * len(blocks)
    assert mine["bytes"] * 5 == every["bytes"] * len(blocks)


def test_lfm2_forward_flops_are_the_integer_the_issue_wrote_out():
    cell = _cell()
    family = reference.load_family("lfm2_moe")
    sizes = flops.Sizes(**LFM2).with_attention(
        family.attention_blocks(cell.config))
    assert family.forward_flops_per_token(sizes, cell.config) == 405_803_008
    # the dense count would refuse this program: it has experts
    with pytest.raises(ValueError, match="experts"):
        flops.forward_flops_per_token(sizes)


def test_the_manifest_keeps_the_contract():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    # a quarter of the cells, rounded down, may take four chips: the two
    # whose plans exist only across chips (tp2 x dp2 ZeRO-3; ep4 + vocab_tp4)
    four = [w["name"] for w in man["workloads"] if w["chips"] == 4]
    assert four == ["mistral7b_c4_tp2dp2z3", "mellum2_c4_ep4"]
    assert len(four) <= len(man["workloads"]) // 4
    assert len(man["workloads"]) >= 11


GRANITE = dict(layers=10, hidden=2048, heads=32, kv_heads=8, head_dim=64,
               ffn=8192, ffn_matrices=3, vocab=12544, seq=8192)


def _granite():
    cell = manifest.resolve_cell(manifest.load_manifest(), "granite4h_c1_b1")
    family = reference.load_family("granite_hybrid")
    return cell, family, flops.Sizes(**GRANITE).with_attention(
        family.attention_blocks(cell.config))


def test_one_block_of_granites_ten_attends():
    cell, family, sizes = _granite()
    assert family.attention_blocks(cell.config) == [{}]
    assert cell.config["layer_types"].index("attention") == 5
    every = flops.flash_step_cost(flops.Sizes(**GRANITE), 1)
    mine = flops.flash_step_cost(sizes, 1)
    assert mine["flops"] * 10 == every["flops"]
    assert mine["bytes"] * 10 == every["bytes"]


def test_granite_forward_flops_are_the_integers_the_issue_wrote_out():
    cell, family, sizes = _granite()
    cfg = cell.config
    assert family.mamba_matmul_flops_per_token(cfg) + 2 * 3 * 2048 * 8192 \
        == 2 * 76_152_832
    # the recurrence as the recurrence: update and read-out of the state
    assert family.recurrence_flops_per_token(cfg) == 2_097_152
    attending = 2 * 60_817_408 + 2 * 32 * (64 + 64) * (8192 + 1) / 2
    assert flops.attention_flops_per_token(sizes) + 2 * 3 * 2048 * 8192 \
        == attending
    total = family.forward_flops_per_token(sizes, cfg)
    assert total == (9 * (2 * 76_152_832 + 2_097_152) + attending
                     + 2 * 25_690_112) == 1_596_198_912
    # a chunk of another size is another implementation, not another model
    assert family.forward_flops_per_token(
        sizes, {**cfg, "mamba_chunk_size": 64}) == total


def test_granites_scan_cost_reads_the_configurations_own_file():
    import os

    cost = manifest.load_python(os.path.join(
        manifest.ROOT, "benchmark", "layer_metrics", "granite_ssd_cost.py"))
    _, _, sizes = _granite()
    need = cost.granite_ssd_step_cost(sizes, 1)
    per_token = 2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 64 * 128)
    assert need["flops"] == 9 * 8192 * 3 * per_token
    assert need["bytes"] == 9 * 8192 * 3 * ((3 * 4096 + 2 * 128) * 2 + 4 * 64)
    # memory-bound on a v5e by this count
    from benchmark import peaks

    chip = peaks.peaks_of("TPU v5 lite")
    assert flops.roofline_least_s(need, chip)["bound"] == "memory"
