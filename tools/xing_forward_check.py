#!/usr/bin/env python3
"""The program's forward pass against the plain Xing4.0 reference at the
published widths, token by token, both prediction depths, with controls
that must fail.

    python3 tools/xing_forward_check.py [--seed N] [--workload xing4_c1_b1_s4k]

The benchmark's ``correct`` compares ONE scalar, the step-0 loss over 4096
random tokens, which a missing block moves by an amount that is zero-mean
over seeds, so no limit fails that control on every seed
(``reference.loss_tolerance_reason`` in the configuration's file). This
looks closer, once, outside the harness: the cell's own weights for one seed
and its first 4096-token sequence go through ``forward_causal_lm`` (bfloat16,
the flash core at q/k 192 and v 128, the held share of the experts: what the
cell trains with) and through ``benchmark/reference/xing4_0.py`` (float32
under ``jax.default_matmul_precision("highest")``), and the logits of the
main head and of the multi-token block, ``[4096, vocab]`` each, are compared.
Then the program runs again with one thing wrong each: the Sinkhorn passes
left out, YaRN's ``mscale_all_dim`` left out, the last block left out. Each
has to lie further from the reference than the tolerance, at one depth at
least. (The maps' token-dependent term is no control here: at its initial
gate of 0.01 it moves the logits by less than bfloat16 does; tier-1 holds
it on weights drawn for it, tests/models/test_xing4.py.)

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

# The statistic is tools/olmoe_forward_check.py's: for every token the RMS
# over the vocabulary of (program - reference) logits over the RMS of the
# reference's, and of those 4096 numbers the MEDIAN (a token whose fourth
# and fifth expert are nearly tied picks the other one under bfloat16). The
# limit is written with its readings in PERF.md section 6 (PR 40): some
# two and a half times what the program as published reads, and under a
# third of the nearest control at either depth.
TOLERANCE = 0.03


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="xing4_c1_b1_s4k")
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark import check, manifest, reference
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.models.builder import (
        forward_causal_lm,
        init_causal_lm,
    )
    from hetu_galvatron_tpu.models.modules import LayerOps
    from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa
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
    def reference_logits(w, t, nxt):
        # the multi-token block over every position (the loss leaves the
        # last one out; under the causal mask it reaches no other)
        with jax.default_matmul_precision("highest"):
            h = family.stack_output(w, cell.config, t)
            main = family.rms_norm(h, w["model.norm.weight"],
                                   cell.config["rms_norm_eps"]) \
                @ w["lm_head.weight"].T
            return main[0], family.mtp_logits(w, cell.config, h, nxt)[0]

    want = reference_logits({k: jnp.asarray(v, jnp.float32)
                             for k, v in weights.items()
                             if k != "extra_vocab_rows"}, tokens, labels)
    del weights

    params = jax.jit(lambda k: init_causal_lm(k, cfg)[0])(
        jax.random.key(a.seed))

    def program_logits(p, run_cfg):
        sdpa = ({i: LayerOps(sdpa=flash_sdpa)
                 for i in range(run_cfg.num_hidden_layers)}
                if dev.platform == "tpu" else None)

        def both(p, t, nxt):
            main, _, _, ahead = forward_causal_lm(
                p, t, run_cfg, compute_dtype=jnp.bfloat16,
                layer_overrides=sdpa, mtp_labels=nxt)
            return main[0, :, :cfg.vocab_size], ahead[0, :, :cfg.vocab_size]
        return jax.jit(both)(p, tokens, labels)

    scaling = dict(cfg.rope_scaling)
    scaling.pop("mscale_all_dim")
    fewer = cfg.num_hidden_layers - 1
    runs = (
        ("as_published", params, cfg),
        ("sinkhorn_left_out", params,
         cfg.model_copy(update=dict(hc_sinkhorn_iters=0))),
        ("mscale_all_dim_left_out", params,
         cfg.model_copy(update=dict(rope_scaling=scaling))),
        ("one_block_fewer", {**params, "layers": params["layers"][:fewer]},
         cfg.model_copy(update=dict(num_hidden_layers=fewer,
                                    layer_types=cfg.layer_types[:fewer]))),
    )
    ok = True
    for name, p, run_cfg in runs:
        line = {"run": name}
        inside = True
        for depth, got, ref in zip(("main", "multi_token"),
                                   program_logits(p, run_cfg), want):
            per_token = (jnp.sqrt(jnp.mean(jnp.square(got - ref), axis=-1))
                         / jnp.sqrt(jnp.mean(jnp.square(ref), axis=-1)))
            rel = float(jnp.median(per_token))
            inside &= rel <= TOLERANCE
            line[depth] = {
                "median_token_rel_logit_error": rel,
                "p90_token_rel_logit_error": float(
                    jnp.percentile(per_token, 90)),
                "max_abs_logit_error": float(jnp.max(jnp.abs(got - ref))),
                "reference_logit_rms": float(jnp.sqrt(jnp.mean(
                    jnp.square(ref)))),
                "argmax_agreement": float(jnp.mean(
                    jnp.argmax(got, -1) == jnp.argmax(ref, -1)))}
        line["inside_tolerance"] = bool(inside)
        ok &= inside == (name == "as_published")
        print(json.dumps(line), flush=True)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
