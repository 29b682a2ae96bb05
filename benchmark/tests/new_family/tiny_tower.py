"""Reference family ``tiny_tower``: a second stack in front of the decoder,
kept here as a test fixture of how its attention is DESCRIBED and COUNTED
(``test_second_stack.py``): what a later PR states for an encoder of image
patches whose blocks attend both ways inside an image, over positions of
their own, at a hidden width and heads of their own.

The configuration's own keys give everything: ``vision_config`` (the
published group: ``hidden_size``, ``num_attention_heads``,
``intermediate_size``, ``merge_kernel_size``), ``tower_layers`` (the tower's
depth as it is run, the key that ``reference.second_stack_depth_key`` names
and ``program.equals`` ties to the program) and ``image_patches`` (the
patches of each image of one sequence as the traffic packs them; after the
merge of ``merge_kernel_size`` they take a quarter as many positions of the
decoder's sequence).

The program has no tower yet (``ROADMAP.md`` reach item A12), so there is
nothing to compare a loss with and ``nll_sum`` says so; ``tiny_packed.py``
is the fixture of a family that takes a batch's other fields.
"""

from __future__ import annotations

from typing import Dict, List, Mapping

from benchmark import flops


def attention_blocks(config: Mapping) -> List[Dict[str, int]]:
    """The tower's blocks, then the decoder's. A patch meets every patch of
    its own image and no other: ``pairs`` is the sum of the images' squares,
    ``positions`` their sum."""
    v = config["vision_config"]
    heads = v["num_attention_heads"]
    width = v["hidden_size"] // heads
    patches = config["image_patches"]
    tower = {"heads": heads, "kv_heads": heads, "qk_head_dim": width,
             "v_head_dim": width, "hidden": v["hidden_size"],
             "positions": sum(patches), "pairs": sum(n * n for n in patches)}
    return ([dict(tower) for _ in range(config["tower_layers"])]
            + [{} for _ in range(config["num_hidden_layers"])])


def nll_sum(w, cfg, tokens, labels, *, layers=None, batch=None):
    raise NotImplementedError(
        "tiny_tower describes and counts a second stack; the program runs "
        "none yet, so there is no loss to compare")


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """A token of the step is a position of the decoder's sequence. Every
    attending block by its entry; the tower's two-matrix MLP at its own
    positions, ``positions / seq`` a token; the projector's two matrices
    over the merged patches at the decoder's image positions; the decoder's
    gated MLP a block and the head."""
    v = config["vision_config"]
    blocks = sizes.attention_blocks()
    attention = sum(flops.attention_flops_per_token(sizes, a) for a in blocks)
    tower = [a for a in blocks if a.positions]
    merged = v["hidden_size"] * v["merge_kernel_size"][0] \
        * v["merge_kernel_size"][1]
    images = sum(config["image_patches"]) // (merged // v["hidden_size"])
    return (attention
            + sum(2 * 2 * a.hidden * v["intermediate_size"]
                  * a.positions / sizes.seq for a in tower)
            + 2 * (merged * merged + merged * sizes.hidden)
            * images / sizes.seq
            + sizes.layers * 2 * sizes.hidden * sizes.ffn * sizes.ffn_matrices
            + flops.head_flops_per_token(sizes))
