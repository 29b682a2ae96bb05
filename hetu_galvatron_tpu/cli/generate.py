"""Text-generation launcher: prompt -> tokens -> (sharded) KV-cache decode
-> text.

The reference ships no inference entry point (its attention layer has only
inference-context stubs); this CLI completes the L7 surface over the
generation runtime (models/generate.py + parallel/spmd.py
make_spmd_generate)::

    python -m hetu_galvatron_tpu.cli.generate <model.yaml> \
        prompt="once upon a time" [max_new_tokens=64] [temperature=0.8] \
        [top_k=40] [tokenizer=byte|<hf-name-or-path>] \
        [ckpt=<framework ckpt root>] [hf_path=<hf checkpoint dir>] \
        [model.* / parallel.* overrides]

Weights come from a framework checkpoint (``ckpt=``), an HF checkpoint dir
(``hf_path=``), or random init (smoke/demo). With more than one visible
device the decode runs under the plan's GSPMD shardings (tp/dp) via
``make_spmd_generate``; single-device runs jit the plain generate().
"""

from __future__ import annotations

import os
import sys


def main(argv=None) -> int:
    argv = list(argv if argv is not None else sys.argv[1:])
    kv_keys = ("prompt", "max_new_tokens", "temperature", "top_k",
               "tokenizer", "ckpt", "hf_path", "seed")
    kv = {}
    passthrough = []
    for a in argv:
        k = a.split("=", 1)[0]
        if "=" in a and k in kv_keys:
            kv[k] = a.split("=", 1)[1]
        else:
            passthrough.append(a)
    if "prompt" not in kv:
        print("usage: generate <model.yaml> prompt=\"...\" [key=value ...]",
              file=sys.stderr)
        return 2

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hetu_galvatron_tpu.cli.compile_cache import configure_compile_cache
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.cli.preprocess_data import make_tokenizer
    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.models.generate import generate
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    configure_compile_cache()
    args = args_from_cli(passthrough, mode="train_dist")
    args = resolve_model_config(args)
    cfg = args.model

    tok = make_tokenizer(kv.get("tokenizer"))
    if tok.vocab_size > cfg.vocab_size:
        # padded rows hold untrained weights — matching against them would
        # silently embed real tokens into garbage
        raise ValueError(
            f"tokenizer vocab {tok.vocab_size} exceeds model vocab "
            f"{cfg.vocab_size}; pass a matching model config")
    ids = tok.encode(kv["prompt"])
    if not ids:
        raise ValueError("empty prompt after tokenization")
    prompt = jnp.asarray(np.asarray(ids, np.int32)[None, :])

    init_key, sample_key = jax.random.split(
        jax.random.key(int(kv.get("seed", 0))))

    # weights about to be replaced need only an ABSTRACT restore target;
    # the logical-axes tree is plain python data, captured while shaping
    # (eval_shape cannot return string leaves)
    box = {}

    def _shapes(k):
        p, box["axes"] = init_causal_lm(k, cfg)
        return p

    params_target = jax.eval_shape(_shapes, init_key)
    axes = box["axes"]
    if kv.get("ckpt"):
        from hetu_galvatron_tpu.runtime.checkpoint import (
            latest_checkpoint,
            load_checkpoint,
        )

        ckdir = kv["ckpt"]
        if not os.path.basename(ckdir).startswith("step_"):
            found = latest_checkpoint(ckdir)
            if found is None:
                raise FileNotFoundError(
                    f"no step_* checkpoint found under {ckdir}")
            ckdir = found
        params, _, step = load_checkpoint(ckdir, params_target)
        print(f"loaded {ckdir} (step {step})", file=sys.stderr)
    elif kv.get("hf_path"):
        from hetu_galvatron_tpu.cli.checkpoint_convert import (
            _load_hf_state_dict,
        )
        from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params

        params = hf_to_params(_load_hf_state_dict(kv["hf_path"]), cfg)
        print(f"loaded HF weights from {kv['hf_path']}", file=sys.stderr)
    else:
        print("warning: no ckpt/hf_path given; generating from RANDOM "
              "weights (smoke mode)", file=sys.stderr)
        params = init_causal_lm(init_key, cfg)[0]

    n_new = int(kv.get("max_new_tokens", 64))
    gen_kwargs = dict(
        temperature=float(kv.get("temperature", 0.0)),
        top_k=int(kv["top_k"]) if kv.get("top_k") else None,
        eos_id=getattr(tok, "eod_id", None),
    )
    key = sample_key

    # Single-prompt decode cannot shard the batch axis, so multi-device runs
    # use a pure-TP submesh: the largest power-of-2 tp <= world that divides
    # the (kv) head counts. Explicit DEGREE overrides win (other parallel.*
    # keys like mixed_precision must not force a dp-sharded plan onto a
    # batch of one).
    if cfg.model_type == "t5":
        # seq2seq: the prompt is the ENCODER source; decode starts from the
        # start token (HF T5 uses pad id 0). The CLI decodes single-device
        # (one prompt); make_spmd_generate also handles t5 for sharded
        # programmatic decoding.
        from hetu_galvatron_tpu.models.generate import generate_encdec

        out = jax.jit(lambda p, t, k: generate_encdec(
            p, t, cfg, n_new, key=k, **gen_kwargs))(params, prompt, key)
        new_ids = np.asarray(out)[0, 1:].tolist()  # strip the start token
        eod = getattr(tok, "eod_id", None)
        if eod is not None and eod in new_ids:
            new_ids = new_ids[:new_ids.index(eod)]
        print(tok.decode(new_ids))
        return 0

    world = len(jax.devices())
    degree_keys = ("parallel.global_tp_deg", "parallel.pp_deg",
                   "parallel.global_cp_deg", "parallel.global_ep_deg",
                   "parallel.vocab_tp", "parallel.vocab_sp",
                   "parallel.use_ulysses", "parallel.sdp")
    user_parallel = any(a.split("=", 1)[0] in degree_keys
                        for a in passthrough)
    tp = 1
    while (tp * 2 <= world and cfg.num_attention_heads % (tp * 2) == 0
           and cfg.kv_heads % (tp * 2) == 0):
        tp *= 2
    if world > 1 and (user_parallel or tp > 1):
        from hetu_galvatron_tpu.parallel.spmd import (
            make_spmd_generate,
            shard_params,
        )
        from hetu_galvatron_tpu.runtime.hybrid_config import (
            get_hybrid_parallel_config,
        )
        from hetu_galvatron_tpu.runtime.mesh import build_mesh

        if not user_parallel:
            args.parallel.global_tp_deg = tp
            if cfg.padded_vocab_size % tp == 0:
                args.parallel.vocab_tp = tp
            # gbsz only feeds plan validation (must divide by the vocab
            # layer's dp); the actual decode batch is the prompt's
            args.parallel.global_train_batch_size = tp
            sub_world = tp
        else:
            sub_world = world
        print(f"decoding on {sub_world} devices "
              f"(tp={args.parallel.global_tp_deg})", file=sys.stderr)
        hpc = get_hybrid_parallel_config(args, sub_world)
        dp = hpc.layers[0].dp_size
        if prompt.shape[0] % dp:
            raise ValueError(
                f"the plan data-parallelizes the batch {dp} ways but there "
                f"is {prompt.shape[0]} prompt; use tp-only degrees "
                f"(e.g. parallel.global_tp_deg={sub_world}) for "
                "single-prompt decoding")
        mesh = build_mesh(sub_world, 1, devices=jax.devices()[:sub_world])
        fn, pspecs, batch_shd = make_spmd_generate(
            cfg, hpc, mesh, axes, n_new, **gen_kwargs)
        sp = shard_params(params, pspecs, mesh)
        out = fn(sp, jax.device_put(prompt, batch_shd), key)
    else:
        out = jax.jit(lambda p, t, k: generate(
            p, t, cfg, n_new, key=k, **gen_kwargs))(params, prompt, key)

    new_ids = np.asarray(out)[0, prompt.shape[1]:].tolist()
    eod = getattr(tok, "eod_id", None)
    if eod is not None and eod in new_ids:
        new_ids = new_ids[:new_ids.index(eod)]
    print(kv["prompt"] + tok.decode(new_ids))
    return 0


if __name__ == "__main__":
    sys.exit(main())
