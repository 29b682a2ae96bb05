"""Reference family ``gpt2``: a GPT-2 block stack, from Radford et al. 2019
and the ``gpt2`` model card, fed ``transformer.*`` tensors under their
public Hugging Face names.

Departures from the published model, each because the program under test
trains that way and the comparison is of the same mathematics: no dropout
(published 0.1). The token table may carry extra padding rows beyond
``vocab_size`` (``extra_vocab_rows``): the program pads 50257 to 50304,
draws random tokens over all of them and keeps them in its softmax, so the
reference must see the same rows.
"""

from __future__ import annotations

from typing import Mapping, Optional

import jax.numpy as jnp

from benchmark import flops
from benchmark.reference.plain import (
    Weights,
    causal_attention,
    gelu_new,
    layer_norm,
    merge_heads,
    split_heads,
    token_nll_sum,
)


def nll_sum(w: Weights, cfg: Mapping, tokens, labels, *,
            layers: Optional[int] = None):
    """Sum of token negative log-likelihoods. ``w`` holds
    ``transformer.*`` tensors; ``extra_vocab_rows`` (optional) are padding
    rows appended to ``transformer.wte.weight``."""
    wte = w["transformer.wte.weight"]
    if "extra_vocab_rows" in w:
        wte = jnp.concatenate([wte, w["extra_vocab_rows"]], axis=0)
    S = tokens.shape[1]
    eps, nh = cfg["layer_norm_epsilon"], cfg["n_head"]
    h = wte[tokens] + w["transformer.wpe.weight"][:S]
    for i in range(cfg["n_layer"] if layers is None else layers):
        p = f"transformer.h.{i}."
        a = layer_norm(h, w[p + "ln_1.weight"], w[p + "ln_1.bias"], eps)
        qkv = a @ w[p + "attn.c_attn.weight"] + w[p + "attn.c_attn.bias"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        a = merge_heads(causal_attention(
            split_heads(q, nh), split_heads(k, nh), split_heads(v, nh)))
        h = h + a @ w[p + "attn.c_proj.weight"] + w[p + "attn.c_proj.bias"]
        m = layer_norm(h, w[p + "ln_2.weight"], w[p + "ln_2.bias"], eps)
        m = gelu_new(m @ w[p + "mlp.c_fc.weight"] + w[p + "mlp.c_fc.bias"])
        h = h + m @ w[p + "mlp.c_proj.weight"] + w[p + "mlp.c_proj.bias"]
    h = layer_norm(h, w["transformer.ln_f.weight"],
                    w["transformer.ln_f.bias"], eps)
    return token_nll_sum(h @ wte.T, labels)   # tied output head


def forward_flops_per_token(sizes: flops.Sizes, config: Mapping) -> float:
    """qkv, out, one two-matrix MLP and causal attention a block, and the
    head: the dense count as it is."""
    return flops.forward_flops_per_token(sizes)
