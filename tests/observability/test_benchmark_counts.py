"""The benchmark's counts, in tier-1: which blocks of a stack attend and over
what span, through ``flops.Sizes`` into the flash kernels' cost and a
family's FLOPs. The cases are ``benchmark/tests/test_attention_blocks.py``'s
arithmetic ones (that file runs in no driver run), imported and collected
here so that one text is run in both places; and the family of the stack
whose blocks differ, ``lfm2_moe``, at its published widths."""

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


def test_the_manifest_with_six_cells_keeps_the_contract():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    assert [w["chips"] for w in man["workloads"]].count(4) == 1
    assert len(man["workloads"]) == 6
