#!/usr/bin/env python3
"""The program's forward pass against the plain OLMoE reference at the
published widths, token by token, with three controls that must fail.

    python3 tools/olmoe_forward_check.py [--seed N] [--workload olmoe_c1_s4k]

The benchmark's ``correct`` compares ONE scalar (the step-0 loss), which at
random weights sees the expert path only faintly. This looks closer, once,
outside the harness: the cell's own weights for one seed and its first
4096-token sequence go through ``forward_causal_lm`` (bfloat16, the flash
core, dropless experts: what the cell trains with) and through
``benchmark/reference/olmoe.py`` (float32 under
``jax.default_matmul_precision("highest")``), and the two sets of logits
``[4096, vocab]`` are compared. Then the program runs three more times with
one thing wrong each: the router's weights renormalised, the q/k norm left
out, seven experts a token for eight. Each has to lie further from the
reference than the tolerance the comparison holds itself to.

Prints one JSON object a line. Runs on whatever device JAX shows and takes
no timing; the numbers that PERF.md quotes are from a TPU v5e.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# The statistic: for every token the RMS over the vocabulary of (program -
# reference) logits over the RMS of the reference's logits, and of those
# 4096 numbers the MEDIAN. The median, because bfloat16 moves the router's
# input, so a token whose eighth and ninth expert are nearly tied picks the
# other one: a discrete change in a few per cent of the tokens that is as
# large, for them, as the top-7 control is for all. The 90th percentile and
# the RMS over everything are printed beside it. The limit is written with
# its reason in PERF.md section 6 (PR 27): some twice what the chip reads
# for the program as published, and under a third of the nearest control.
TOLERANCE = 0.02


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="olmoe_c1_s4k")
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import check, manifest, reference
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.models.builder import (
        forward_causal_lm,
        init_causal_lm,
    )
    from hetu_galvatron_tpu.models.modules import LayerOps
    from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa
    from hetu_galvatron_tpu.ops.pallas.grouped_matmul import grouped_matmul
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    cell = manifest.resolve_cell(manifest.load_manifest(), a.workload)
    argv = manifest.train_argv(cell, a.seed)
    cfg = resolve_model_config(args_from_cli(argv, mode="train_dist")).model
    weights, tokens, labels = check.first_batch_and_weights(argv)
    tokens, labels = jnp.asarray(tokens[:1]), jnp.asarray(labels[:1])
    dev = jax.devices()[0]
    print(json.dumps({"cell": cell.name, "seed": a.seed,
                      "tokens": int(tokens.size), "platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "tolerance_median_token_rel": TOLERANCE}), flush=True)

    family = reference.load_family(cell.config["reference"]["family"])

    @jax.jit
    def reference_logits(w, t):
        with jax.default_matmul_precision("highest"):
            h, _ = family.hidden_states(w, cell.config, t)
            return (h @ w["lm_head.weight"].T)[0]

    want = reference_logits({k: jnp.asarray(v, jnp.float32)
                             for k, v in weights.items()
                             if k != "extra_vocab_rows"}, tokens)
    want_nll = -jnp.take_along_axis(jax.nn.log_softmax(want, axis=-1),
                                    labels[0][:, None], axis=-1)[:, 0]
    scale = float(jnp.sqrt(jnp.mean(jnp.square(want))))
    del weights

    params = jax.jit(lambda k: init_causal_lm(k, cfg)[0])(
        jax.random.key(a.seed))
    # the kernels the cell's plan hands its blocks on one chip: the flash
    # core and the experts' grouped matmuls
    sdpa = ({i: LayerOps(sdpa=flash_sdpa, grouped=grouped_matmul)
             for i in range(cfg.num_hidden_layers)}
            if dev.platform == "tpu" else None)

    def program_logits(p, run_cfg):
        return jax.jit(lambda p, t: forward_causal_lm(
            p, t, run_cfg, compute_dtype=jnp.bfloat16,
            layer_overrides=sdpa)[0, :, :cfg.vocab_size])(p, tokens)

    def without_qk_norm(p):
        return {**p, "layers": tuple(
            {**lp, "attn": {k: v for k, v in lp["attn"].items()
                            if k not in ("q_norm", "k_norm")}}
            for lp in p["layers"])}

    runs = (
        ("as_published", params, cfg),
        ("combine_weights_renormalised", params,
         cfg.model_copy(update=dict(moe_norm_topk_prob=True))),
        ("qk_norm_left_out", without_qk_norm(params), cfg),
        ("top_7_for_top_8", params,
         cfg.model_copy(update=dict(moe_topk=cfg.moe_topk - 1))),
    )
    ok = True
    for name, p, run_cfg in runs:
        got = program_logits(p, run_cfg)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(got, axis=-1),
                                   labels[0][:, None], axis=-1)[:, 0]
        per_token = (jnp.sqrt(jnp.mean(jnp.square(got - want), axis=-1))
                     / jnp.sqrt(jnp.mean(jnp.square(want), axis=-1)))
        rel = float(jnp.median(per_token))
        inside = rel <= TOLERANCE
        ok &= inside == (name == "as_published")
        print(json.dumps({
            "run": name, "median_token_rel_logit_error": rel,
            "inside_tolerance": inside,
            "p90_token_rel_logit_error": float(jnp.percentile(per_token, 90)),
            "rel_rms_logit_error": float(jnp.sqrt(jnp.mean(jnp.square(
                got - want)))) / scale,
            "max_abs_logit_error": float(jnp.max(jnp.abs(got - want))),
            "reference_logit_rms": scale,
            "mean_abs_token_nll_error": float(jnp.mean(jnp.abs(
                nll - want_nll))),
            "max_abs_token_nll_error": float(jnp.max(jnp.abs(
                nll - want_nll))),
            "mean_nll_program": float(jnp.mean(nll)),
            "mean_nll_reference": float(jnp.mean(want_nll)),
            "argmax_agreement": float(np.mean(np.asarray(
                jnp.argmax(got, -1) == jnp.argmax(want, -1))))}), flush=True)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
