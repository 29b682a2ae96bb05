#!/usr/bin/env python3
"""The program's forward pass against the plain Granite-4.0-H reference at
the published widths, token by token, with controls that must fail.

    python3 tools/granite_forward_check.py [--seed N] [--workload granite4h_c1_b1]

The benchmark's ``correct`` compares ONE scalar (the step-0 loss), and this
model divides its logits by 8 and multiplies every branch by 0.22, so at
random weights the loss lies within a thousandth of ln(vocabulary) whatever
a block does. This looks closer, once, outside the harness: the cell's own
weights for one seed and its first 8192-token sequence go through
``forward_causal_lm`` (bfloat16, the recurrence in its chunked matmul form,
on a TPU through the kernels of ``ops/pallas/ssd.py``, the flash core at
the model's own softmax scale on the block that attends: what the cell
trains with) and through
``benchmark/reference/granite_hybrid.py`` (float32 under
``jax.default_matmul_precision("highest")``, the recurrence one position at
a time), and the two sets of logits ``[8192, vocab]`` are compared. Then one
thing is wrong at a time.

On the whole STACK, in the program: each of the three multipliers left out
(the embedding's 12, the branches' 0.22, the logits' 8).

On the MAMBA OPERATOR alone (block 0: the reference's normed embedding
through ``apply_mamba2`` and through the reference's ``mamba2``), because
one operator of ten blocks under a 0.22 is faint in the stack's logits: the
``D x`` skip left out and the convolution's taps reversed in the PROGRAM;
the state carried in bfloat16 and the decay rounded to bfloat16 in the
REFERENCE (printed and judged against the program as published: a program
that is right lies nearer the float32 recurrence than the rounded ones do).

On the ATTENTION OPERATOR alone (block 5, the reference's normed hidden
states after five blocks through ``apply_attention`` with the flash core
and through the reference's ``attention``): RoPE left on, and the softmax
at 1/sqrt(head_dim) instead of ``attention_multiplier``, in the PROGRAM.

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
# over the width of (program - reference) over the RMS of the reference's,
# and of those 8192 numbers the median. The limits are written with their
# reasons in PERF.md section 6 (PR 35): above what the chip reads for the
# program as published, with room, and under the nearest control, with room.
TOLERANCE = 0.02            # the stack's logits
MAMBA_TOLERANCE = 0.012     # the mamba operator's output
ATTENTION_TOLERANCE = 0.012  # the attention operator's output


def rounding_scan(round_state=False, round_decay=False):
    """The reference's recurrence (``granite_hybrid.selective_scan``) with
    the carried state or the decay rounded to bfloat16 at every position:
    what a program that kept either in bfloat16 would compute."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        # an explicit rounding: XLA may drop a convert to bfloat16 and back
        # (xla_allow_excess_precision)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def scan(x, dt, A, B, C):
        def step(state, at):
            x_t, dt_t, b_t, c_t = at
            decay = jnp.exp(dt_t * A)
            if round_decay:
                decay = rounded(decay)
            state = (decay[..., None, None] * state
                     + (dt_t[..., None] * x_t)[..., None]
                     * b_t[:, None, None, :])
            if round_state:
                state = rounded(state)
            return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

        zero = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], x.dtype)
        _, y = jax.lax.scan(step, zero, tuple(
            jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)))
        return jnp.moveaxis(y, 0, 1)
    return scan


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="granite4h_c1_b1")
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark import check, manifest, reference
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.models import modules as M
    from hetu_galvatron_tpu.models.builder import (
        forward_causal_lm,
        init_causal_lm,
    )
    from hetu_galvatron_tpu.ops.pallas.conv import causal_conv
    from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa
    from hetu_galvatron_tpu.ops.pallas.gated_norm import gated_norm
    from hetu_galvatron_tpu.ops.pallas.ssd import ssd_scan
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
                      "tolerance_median_token_rel": TOLERANCE,
                      "mamba_tolerance": MAMBA_TOLERANCE,
                      "attention_tolerance": ATTENTION_TOLERANCE}),
          flush=True)

    family = reference.load_family(cell.config["reference"]["family"])
    ref_cfg = cell.config
    w32 = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()
           if k != "extra_vocab_rows"}
    del weights
    kinds = ref_cfg["layer_types"]
    mamba_at, attending = kinds.index("mamba"), kinds.index("attention")

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    want = highest(lambda w, t: family.logits(w, ref_cfg, t)[0])(w32, tokens)

    def operator_inputs(w, t):
        return (family.hidden_states(w, ref_cfg, t, layers=mamba_at),
                family.hidden_states(w, ref_cfg, t, layers=attending))

    mamba_in, attention_in = highest(operator_inputs)(w32, tokens)

    def reference_mamba():
        return highest(lambda w, x: family.mamba2(
            x, w, f"model.layers.{mamba_at}.mamba.", ref_cfg))(w32, mamba_in)

    want_mamba = reference_mamba()
    published_scan = family.selective_scan

    rounded = {}
    for name, kw in (("state_carried_in_bf16",
                      dict(round_state=True)),
                     ("decay_in_bf16", dict(round_decay=True))):
        family.selective_scan = rounding_scan(**kw)
        rounded[name] = reference_mamba()
    family.selective_scan = published_scan
    want_attention = highest(lambda w, x: family.attention(
        x, w, f"model.layers.{attending}.self_attn.", ref_cfg))(
            w32, attention_in)
    del w32

    params = jax.jit(lambda k: init_causal_lm(k, cfg)[0])(
        jax.random.key(a.seed))
    on_tpu = dev.platform == "tpu"
    # on a TPU what the cell trains with: the flash core, and the scan's,
    # the convolution's and the gated norm's kernels in the mamba blocks
    mamba_ops = (M.LayerOps(ssd=ssd_scan, conv=causal_conv,
                            gated_norm=gated_norm) if on_tpu
                 else M.LayerOps())
    sdpa = ({i: mamba_ops if mixer == "mamba"
             else M.LayerOps(sdpa=flash_sdpa)
             for i, (mixer, _) in enumerate(cfg.block_kinds())}
            if on_tpu else None)

    def but(**update):
        return cfg.model_copy(update=update)

    def program_logits(run_cfg):
        return jax.jit(lambda p, t: forward_causal_lm(
            p, t, run_cfg, compute_dtype=jnp.bfloat16,
            layer_overrides=sdpa)[0, :, :cfg.vocab_size])(params, tokens)

    def mamba_operator(leaves):
        p = {**params["layers"][mamba_at]["mamba"], **leaves}
        return jax.jit(lambda p, x: M.apply_mamba2(
            p, x.astype(jnp.bfloat16), cfg, compute_dtype=jnp.bfloat16,
            ssd_fn=mamba_ops.ssd, conv_fn=mamba_ops.conv,
            norm_fn=mamba_ops.gated_norm))(p, mamba_in)

    def attention_operator(run_cfg):
        rope = None
        if run_cfg.position_embedding_type == "rope":
            rope = M.rope_cos_sin(tokens.shape[1], run_cfg.head_dim,
                                  run_cfg.rope_theta)
        kw = {"sdpa_fn": flash_sdpa} if on_tpu else {}
        return jax.jit(lambda p, x: M.apply_attention(
            p, x.astype(jnp.bfloat16), run_cfg, rope=rope,
            compute_dtype=jnp.bfloat16, **kw))(
                params["layers"][attending]["attn"], attention_in)

    def per_token_rel(got, ref):
        got = got.astype(jnp.float32).reshape(ref.shape)
        return (jnp.sqrt(jnp.mean(jnp.square(got - ref), axis=-1))
                / jnp.sqrt(jnp.mean(jnp.square(ref), axis=-1))).reshape(-1)

    ok = True

    def judge(name, got, ref, limit, published, extra=None):
        nonlocal ok
        rel = per_token_rel(got, ref)
        med = float(jnp.median(rel))
        inside = med <= limit
        ok &= inside == published
        print(json.dumps({
            "run": name, "median_token_rel_error": med,
            "inside_tolerance": inside, "tolerance": limit,
            "p10_token_rel_error": float(jnp.percentile(rel, 10)),
            "p90_token_rel_error": float(jnp.percentile(rel, 90)),
            "reference_rms": float(jnp.sqrt(jnp.mean(jnp.square(ref)))),
            **(extra or {})}), flush=True)

    mamba_p = params["layers"][mamba_at]["mamba"]
    got_mamba = mamba_operator({})
    judge("mamba_operator_as_published", got_mamba, want_mamba,
          MAMBA_TOLERANCE, True)
    judge("mamba_operator_d_skip_left_out",
          mamba_operator({"D": jnp.zeros_like(mamba_p["D"])}), want_mamba,
          MAMBA_TOLERANCE, False)
    judge("mamba_operator_taps_reversed",
          mamba_operator({"taps": mamba_p["taps"][:, ::-1]}), want_mamba,
          MAMBA_TOLERANCE, False)
    # the program as published against a reference that rounds: printed
    # beside the same program against the float32 recurrence (above); a
    # bfloat16 state or decay in the PROGRAM would read like these
    for name, ref in rounded.items():
        rel = per_token_rel(ref, want_mamba)
        print(json.dumps({
            "run": f"reference_mamba_operator_{name}_against_float32",
            "median_token_rel_error": float(jnp.median(rel)),
            "p90_token_rel_error": float(jnp.percentile(rel, 90))}),
            flush=True)

    judge("attention_operator_as_published", attention_operator(cfg),
          want_attention, ATTENTION_TOLERANCE, True)
    judge("attention_operator_rope_left_on",
          attention_operator(but(position_embedding_type="rope")),
          want_attention, ATTENTION_TOLERANCE, False)
    judge("attention_operator_scale_one_over_sqrt_d",
          attention_operator(but(attention_multiplier=None)),
          want_attention, ATTENTION_TOLERANCE, False)

    ref_nll = -jnp.take_along_axis(jax.nn.log_softmax(want, axis=-1),
                                   labels[0][:, None], axis=-1)[:, 0]
    for name, run_cfg in (
            ("as_published", cfg),
            ("embedding_multiplier_left_out", but(embedding_multiplier=1.0)),
            ("residual_multiplier_left_out", but(residual_multiplier=1.0)),
            ("logits_scaling_left_out", but(logits_scaling=1.0))):
        got = program_logits(run_cfg)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(
            got.astype(jnp.float32), axis=-1), labels[0][:, None],
            axis=-1)[:, 0]
        judge(name, got, want, TOLERANCE, name == "as_published", {
            "max_abs_logit_error": float(jnp.max(jnp.abs(got - want))),
            "mean_abs_token_nll_error": float(jnp.mean(jnp.abs(
                nll - ref_nll))),
            "mean_nll_program": float(jnp.mean(nll)),
            "mean_nll_reference": float(jnp.mean(ref_nll)),
            "argmax_agreement": float(jnp.mean(
                jnp.argmax(got, -1) == jnp.argmax(want, -1)))})
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
