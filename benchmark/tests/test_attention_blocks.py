"""Attention counted where a stack has it: the family's ``attention_blocks``
through ``flops.Sizes`` into ``flash_step_cost``, ``attention_flops_per_token``
and a run; and the plain reference's attention in blocks of queries and over
a window. Pure arithmetic first (numbers written out by hand, and the four
configurations' counts as the parent commit gave them), then the recorded
trace, then the tiny program."""

import dataclasses
import math
import os

import numpy as np
import pytest

from benchmark import flops, manifest, reference, run
from benchmark.tests import tiny
from benchmark.tests.test_recorded_trace import (  # noqa: F401
    _facts_of_the_run,
    trace,
)

# the four configuration files as their cells run them, and what the parent
# commit (171d894) returned for them: forward FLOPs a token by the
# configuration's family, one block's attention, and the flash kernels'
# operations and bytes a step at the sequences of the configuration's cell
CONFIGS = {
    "gpt2-xl": (dict(layers=16, hidden=1600, heads=25, kv_heads=25,
                     head_dim=64, ffn=6400, ffn_matrices=2, vocab=50257,
                     seq=1024), 16,
                1196342400.0, 23760000.0, 3009413120000.0, 10118758400),
    "mistral-7b-d8": (dict(layers=8, hidden=4096, heads=32, kv_heads=8,
                           head_dim=128, ffn=14336, ffn_matrices=3,
                           vocab=32000, seq=4096), 8,
                      4020305920.0, 117448704.0, 30793841770496.0,
                      16173236224),
    "mistral-7b-d2": (dict(layers=2, hidden=4096, heads=32, kv_heads=8,
                           head_dim=128, ffn=14336, ffn_matrices=3,
                           vocab=32000, seq=4096), 4,
                      1201684480.0, 117448704.0, 3849230221312.0, 2021654528),
    "olmoe-1b-7b-d1": (dict(layers=1, hidden=2048, heads=16, kv_heads=16,
                            head_dim=128, ffn=1024, ffn_matrices=3,
                            vocab=50304, seq=4096, experts=64), 4,
                       357306368.0, 50335744.0, 962307555328.0, 807403520),
}
MISTRAL = flops.Sizes(**CONFIGS["mistral-7b-d2"][0])
FULL = 4096 * 4097 // 2                       # 8,390,656 pairs a sequence
W1024 = 1024 * 1025 // 2 + 3072 * 1024        # 3,670,528: min(i + 1, 1024)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_the_default_description_counts_what_the_parent_counted(config):
    kw, sequences, forward, attention, flash_flops, flash_bytes = \
        CONFIGS[config]
    sizes = flops.Sizes(**kw)
    cfg = manifest.read_json(os.path.join(
        manifest.ROOT, "benchmark", "configs", config + ".json"))
    family = reference.load_family(cfg["reference"]["family"])
    assert not hasattr(family, "attention_blocks")
    assert family.forward_flops_per_token(sizes, cfg) == forward
    assert flops.attention_flops_per_token(sizes) == attention
    assert flops.flash_step_cost(sizes, sequences) == {
        "flops": flash_flops, "bytes": flash_bytes}
    # said out loud, the default is the same count
    said = sizes.with_attention([{}] * sizes.layers)
    assert said.attention_blocks() == sizes.attention_blocks()
    assert flops.flash_step_cost(said, sequences) == {
        "flops": flash_flops, "bytes": flash_bytes}


@pytest.mark.parametrize("window,pairs", [
    (0, FULL), (4096, FULL), (5000, FULL),     # no window, or none that bites
    (1, 4096),                                 # each query meets itself
    (2, 1 + 2 * 4095),
    (128, 128 * 129 // 2 + 3968 * 128),        # 516,160: 16.26 times fewer
    (1024, W1024),
])
def test_a_window_of_w_keys_is_the_sum_of_min_i_plus_1_and_w(window, pairs):
    assert flops.causal_pairs(4096, window) == pairs
    assert pairs == sum(min(i + 1, window or 4096) for i in range(4096))
    one = MISTRAL.with_attention([{"window": window}])
    assert flops.flash_step_cost(one, 4)["flops"] == \
        2 * 32 * 7 * 128 * 4 * pairs
    # the operands are read and written once whatever the window
    assert flops.flash_step_cost(one, 4)["bytes"] == 2021654528 // 2
    assert flops.attention_flops_per_token(MISTRAL, flops.Attention(
        window=window)) == (2 * 4096 * (32 + 2 * 8) * 128
                            + 2 * 32 * 128 * 4096
                            + 2 * 32 * (128 + 128) * (pairs / 4096))


def test_one_attending_block_in_four_is_a_quarter_of_the_cost():
    eight = dataclasses.replace(MISTRAL, layers=8)
    default = flops.flash_step_cost(eight, 4)
    assert default == {"flops": 4 * 3849230221312.0, "bytes": 4 * 2021654528}
    quarter = flops.flash_step_cost(eight.with_attention([{}, {}]), 4)
    assert quarter == {"flops": default["flops"] / 4,
                       "bytes": default["bytes"] // 4}
    # a stack that does not attend at all asks nothing of the kernels
    assert flops.flash_step_cost(eight.with_attention([]), 4) == {
        "flops": 0.0, "bytes": 0}


def test_q_k_width_192_and_v_width_128_split_the_seven_matmuls_4_to_3():
    """MiMo's and Kimi's heads: q.k^T, the scores again, dq and dk contract
    or produce 192, p.v, dp and dv 128."""
    entry = {"heads": 64, "kv_heads": 8, "qk_head_dim": 192,
             "v_head_dim": 128, "window": 128}
    s = MISTRAL.with_attention([entry])
    pairs = 128 * 129 // 2 + 3968 * 128
    cost = flops.flash_step_cost(s, 4)
    assert cost["flops"] == 2 * 64 * (4 * 192 + 3 * 128) * 4 * pairs
    tokens = 4 * 4096
    q, k, v, o = 64 * 192, 8 * 192, 8 * 128, 64 * 128
    # forward q, k, v, o; backward those again and do, dq, dk, dv: three of
    # each, in bf16, and the float32 row statistics written and read
    assert cost["bytes"] == (tokens * 3 * (q + k + v + o) * 2
                             + 2 * tokens * 64 * 4)
    per_token = flops.attention_flops_per_token(s, s.attention[0])
    projections = 2 * 4096 * (q + k + v) + 2 * o * 4096
    assert per_token == projections + 2 * 64 * (192 + 128) * (pairs / 4096)
    # the forward's two matmuls of the kernel's seven are the helper's span
    assert (per_token - projections) * tokens == pytest.approx(
        cost["flops"] * (192 + 128) / (4 * 192 + 3 * 128), rel=1e-12)


@pytest.mark.parametrize("entry,says", [
    ({"windows": 128}, "windows"),
    ({"window": -1}, "window"),
    ({"heads": 2.5}, "heads"),
    ({"window": True}, "window"),
])
def test_an_entry_holds_the_five_keys_as_whole_numbers(entry, says):
    with pytest.raises(ValueError, match=f"this one holds.*{says}"):
        MISTRAL.with_attention([entry])


def test_a_description_longer_than_the_stack_is_refused():
    with pytest.raises(ValueError, match="describes 3 blocks that attend "
                                         "and the program runs 2"):
        MISTRAL.with_attention([{}, {}, {}])


def test_flash_roofline_of_a_stack_of_which_one_block_in_four_attends(
        trace):  # noqa: F811
    """The recorded ``mistral7b_c1_s4k`` trace read as if its 118.47 ms of
    flash kernels a step were those of an eight-block stack of the fixture
    family, blocks 2 and 6 attending, block 6 over 1024 keys."""
    from benchmark import readers

    family = manifest.load_python(tiny.NEW_FAMILY_DIR + "/tiny_hybrid.py")
    blocks = family.attention_blocks({
        "layer_types": ["mlp", "mlp", "attention", "mlp",
                        "mlp", "mlp", "sliding_attention", "mlp"],
        "sliding_window": 1024})
    assert blocks == [{}, {"window": 1024}]
    facts = _facts_of_the_run(trace)
    eight = dataclasses.replace(facts["sizes"], layers=8)
    facts["sizes"] = eight
    default = readers.read_metric("flash_roofline", facts)
    # every block counted as full causal attention: 78.16 ms of MXU time
    assert default == pytest.approx(100 * 0.07815695880836548 / 0.11847157825)
    facts["sizes"] = eight.with_attention(blocks)
    described = readers.read_metric("flash_roofline", facts)
    # 2 x 32 x 7 x 128 x 4 sequences x (8,390,656 + 3,670,528) pairs
    assert flops.flash_step_cost(facts["sizes"], 4)["flops"] == 2766546141184
    assert described == pytest.approx(100 * 0.01404338142732995
                                      / 0.11847157825)
    assert described / default == pytest.approx((FULL + W1024) / (8 * FULL))
    facts["sizes"] = eight.with_attention([{}, {}])
    assert readers.read_metric("flash_roofline", facts) == pytest.approx(
        default / 4)


# the plain reference's attention ------------------------------------------

def _qkv(seed, B=2, H=3, S=16, D=8, Dv=8):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((B, H, S, D), np.float32),
            rng.standard_normal((B, H, S, D), np.float32),
            rng.standard_normal((B, H, S, Dv), np.float32))


def _by_hand(q, k, v, window):
    """Every query's softmax over the keys its mask leaves, written out."""
    B, H, S, D = q.shape
    out = np.zeros(v.shape, np.float64)
    for i in range(S):
        keys = [j for j in range(S) if j <= i and j > i - (window or S)]
        s = np.einsum("bhd,bhjd->bhj", q[:, :, i].astype(np.float64),
                      k[:, :, keys].astype(np.float64)) / math.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[:, :, i] = np.einsum("bhj,bhjd->bhd", p, v[:, :, keys])
    return out


@pytest.mark.parametrize("window", [None, 1, 5, 16, 40])
@pytest.mark.parametrize("block", [None, 4, 5, 16])
def test_attention_in_query_blocks_and_over_a_window(window, block):
    from benchmark.reference import plain

    q, k, v = _qkv(3, Dv=6)
    got = np.asarray(plain.causal_attention(q, k, v, window, block))
    assert got.shape == v.shape
    np.testing.assert_allclose(got, _by_hand(q, k, v, window),
                               rtol=2e-6, atol=2e-6)
    # blocked against the one call: float32 rounding
    np.testing.assert_allclose(
        got, np.asarray(plain.causal_attention(q, k, v)
                        if not window or window >= 16 else
                        plain.causal_attention(q, k, v, window, 16)),
        rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("batch,heads,seq,block", [
    (1, 25, 1024, 1024), (1, 32, 4096, 4096), (1, 16, 4096, 4096),
    (1, 32, 8192, 2048), (1, 32, 16384, 1024), (1, 64, 8192, 1024),
    (2, 32, 4096, 2048), (1, 40, 4096, 2048)])
def test_the_cells_take_one_call_and_a_longer_sequence_takes_blocks(
        batch, heads, seq, block):
    from benchmark.reference import plain

    assert plain.query_block(batch, heads, seq) == block
    assert batch * heads * block * seq <= plain.SCORE_ELEMENTS


# through a run ---------------------------------------------------------------

def _hybrid_cell(tmp_path, **config):
    root = tiny.make_root(tmp_path)
    tiny.add_hybrid_family(root, **config)
    tiny.assert_nothing_that_was_there_is_edited(root)
    man = manifest.load_manifest(root)
    assert manifest.check_manifest(man, root) == []
    return root, manifest.resolve_cell(man, tiny.HYBRID_CELL[0], root)


def test_a_family_that_says_which_blocks_attend_runs_by_files_alone(tmp_path):
    root, cell = _hybrid_cell(tmp_path)
    line, report = run.measure(
        cell, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP, root=root,
        out_dir=str(tmp_path / "out"), expect_mosaic=False)
    assert report["checks"]["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, report["checks"]
    # both blocks attend and the window is the whole sequence: the dense
    # count, made by the family from the description
    sizes = flops.Sizes(layers=2, hidden=32, heads=4, kv_heads=2, head_dim=8,
                        ffn=64, ffn_matrices=3, vocab=64, seq=16)
    assert report["train_flops_per_token"] == \
        flops.train_flops_per_token(sizes)
    # with the second block's window at 4 keys the count falls by that
    # block's span: (16 x 17 / 2 - (4 x 5 / 2 + 12 x 4)) pairs a sequence
    family = reference.load_family("tiny_hybrid", root)
    narrow = sizes.with_attention(family.attention_blocks(
        {**cell.config, "sliding_window": 4}))
    assert flops.forward_flops_per_token(sizes) \
        - family.forward_flops_per_token(narrow, cell.config) \
        == 2 * 4 * (8 + 8) * (136 - 58) / 16


def test_a_run_refuses_more_attending_blocks_than_the_program_has(tmp_path):
    root, cell = _hybrid_cell(tmp_path, layer_types=["attention"] * 3)
    with pytest.raises(ValueError, match="describes 3 blocks that attend "
                                         "and the program runs 2"):
        run.measure(cell, seed=7, seconds=0.5, trace=0, chip=tiny.FAKE_CHIP,
                    root=root, out_dir=str(tmp_path / "out"),
                    expect_mosaic=False)
