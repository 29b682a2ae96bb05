"""Serving launcher: request stream -> continuous-batching engine -> token
streams.

The offline ``cli/generate.py`` decodes ONE prompt per invocation; this
frontend drives the serving engine (``serving/engine.py``) with many
concurrent requests::

    python -m hetu_galvatron_tpu.cli.serve <model.yaml> \
        requests=<requests.jsonl> [tokenizer=byte|<hf-name-or-path>] \
        [ckpt=<framework ckpt root>] [hf_path=<hf checkpoint dir>] \
        [metrics=<metrics.jsonl>] [stream=1] [watch=<poll seconds>] \
        [serving.* / model.* / parallel.* overrides]

    # one-shot form (single request):
    python -m hetu_galvatron_tpu.cli.serve <model.yaml> prompt="..." \
        max_new_tokens=64

Each line of ``requests.jsonl`` is one request::

    {"prompt": "...", "max_new_tokens": 32, "temperature": 0.8,
     "seed": 7, "arrival_offset_s": 0.5}

``arrival_offset_s`` staggers submission relative to startup (a recorded
trace replays with its original arrival pattern). With ``stream=1`` every
token is printed as a JSONL event as its request's stream drains —
requests print in submission order (the engine generates them
concurrently; per-request TTFT in the metrics reflects actual production
time); ``stream=0`` prints one completion record per request. Serving metrics (TTFT / inter-token
latency percentiles, queue depth, KV occupancy, tokens/sec — see README
"Serving") land in ``metrics`` and render with ``cli/summarize.py``.

Shared-prefix traffic: ``serving.prefix_cache=1`` turns on the radix
prefix cache (requests whose prompts share cached block-aligned prefixes
skip that prefill entirely; ``serve/prefix_hit_rate`` lands in the
metrics). ``serving.spec_decode=1`` adds lossless speculative decoding
(``serving.spec_k`` drafted tokens per step via n-gram prompt-lookup,
verified in one batched pass; greedy output is bit-identical, and
``serve/spec_accept_rate`` reports how often drafts paid off).

A small DRAFT MODEL instead of the n-gram draft:
``serving.spec_draft=model`` with ``draft_model=<model.yaml>`` (the
draft architecture — its vocab must match the target's) and optionally
``draft_ckpt=<framework ckpt root>`` for the draft weights; without a
checkpoint the draft serves random weights (smoke mode, warned). The
engine already took ``draft_params``/``draft_cfg`` — this is the CLI
path to it.

Zero-downtime weight rolls: ``watch=<seconds>`` (with ``ckpt=<root>``)
polls the checkpoint root and hot-swaps every newly COMMITTED step into
the live engine via ``ServingEngine.swap_weights`` — no request is
dropped, the jitted programs never recompile, and the stall lands in the
``serve/swap_stall_ms`` histogram (``serve/weight_swaps`` counts rolls).
A training run writing checkpoints into the same root therefore serves
its own freshest weights continuously.

With more than one visible device the decode runs under the plan's GSPMD
shardings exactly like ``cli/generate.py`` (pure-TP submesh unless explicit
``parallel.*`` degrees are given); the KV pool's head axis follows the
plan's attention tp axes. The offline ``generate`` CLI remains supported
for single prompts.
"""

from __future__ import annotations

import json
import sys
import time


def _read_requests(kv):
    if kv.get("requests"):
        out = []
        with open(kv["requests"]) as f:
            for line in f:
                line = line.strip()
                if line:
                    out.append(json.loads(line))
        return out
    req = {"prompt": kv["prompt"]}
    for key in ("max_new_tokens", "temperature", "seed"):
        if key in kv:
            req[key] = float(kv[key]) if key == "temperature" else int(kv[key])
    return [req]


def _ckpt_params(ckdir: str, params_target):
    """Load a framework checkpoint (a step_* dir or a root holding them)
    into the given eval_shape target; returns (params, resolved dir,
    step). Shared by the target-model and draft-model load paths."""
    import os

    from hetu_galvatron_tpu.runtime.checkpoint import (
        latest_checkpoint,
        load_checkpoint,
    )

    if not os.path.basename(ckdir).startswith("step_"):
        found = latest_checkpoint(ckdir)
        if found is None:
            raise FileNotFoundError(
                f"no step_* checkpoint found under {ckdir}")
        ckdir = found
    params, _, step = load_checkpoint(ckdir, params_target)
    return params, ckdir, step


def _load_draft(kv, serving):
    """The draft-model checkpoint path (serving.spec_draft=model):
    resolve ``draft_model=<yaml>`` to a ModelArgs, load ``draft_ckpt``
    weights when given (random smoke weights otherwise), and return
    (draft_params, draft_cfg) for ``ServingEngine``. Returns (None, None)
    when the n-gram draft (or no spec decode) is configured."""
    if not (serving.spec_decode and serving.spec_draft == "model"):
        return None, None
    import jax

    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.utils.hf_config_adapter import (
        resolve_model_config,
    )

    if not kv.get("draft_model"):
        raise ValueError(
            "serving.spec_draft=model needs draft_model=<model.yaml> "
            "(the draft architecture); pass draft_ckpt=<ckpt root> for "
            "its weights")
    dargs = args_from_cli([kv["draft_model"]], mode="train_dist")
    draft_cfg = resolve_model_config(dargs).model
    key = jax.random.key(int(kv.get("seed", 0)) + 1)
    if kv.get("draft_ckpt"):
        target = jax.eval_shape(
            lambda k: init_causal_lm(k, draft_cfg)[0], key)
        draft_params, ckdir, step = _ckpt_params(kv["draft_ckpt"], target)
        print(f"loaded draft {ckdir} (step {step})", file=sys.stderr)
    else:
        print("warning: no draft_ckpt given; drafting with RANDOM "
              "weights (smoke mode — accept rate will be ~0)",
              file=sys.stderr)
        draft_params = init_causal_lm(key, draft_cfg)[0]
    return draft_params, draft_cfg


def main(argv=None) -> int:
    argv = list(argv if argv is not None else sys.argv[1:])
    kv_keys = ("prompt", "requests", "max_new_tokens", "temperature", "seed",
               "tokenizer", "ckpt", "hf_path", "metrics", "stream",
               "draft_model", "draft_ckpt", "watch")
    kv = {}
    passthrough = []
    for a in argv:
        k = a.split("=", 1)[0]
        if "=" in a and k in kv_keys:
            kv[k] = a.split("=", 1)[1]
        else:
            passthrough.append(a)
    if "prompt" not in kv and "requests" not in kv:
        print("usage: serve <model.yaml> requests=<jsonl> | prompt=\"...\" "
              "[key=value ...]", file=sys.stderr)
        return 2

    import jax

    from hetu_galvatron_tpu.cli.compile_cache import configure_compile_cache
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.cli.preprocess_data import make_tokenizer
    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    configure_compile_cache()
    args = args_from_cli(passthrough, mode="train_dist")
    args = resolve_model_config(args)
    cfg = args.model

    tok = make_tokenizer(kv.get("tokenizer"))
    if tok.vocab_size > cfg.vocab_size:
        raise ValueError(
            f"tokenizer vocab {tok.vocab_size} exceeds model vocab "
            f"{cfg.vocab_size}; pass a matching model config")

    watch_s = float(kv.get("watch", 0) or 0)
    if watch_s > 0 and not kv.get("ckpt"):
        print("watch=<seconds> needs ckpt=<checkpoint root> to poll",
              file=sys.stderr)
        return 2

    init_key = jax.random.key(int(kv.get("seed", 0)))
    box = {}

    def _shapes(k):
        p, box["axes"] = init_causal_lm(k, cfg)
        return p

    params_target = jax.eval_shape(_shapes, init_key)
    axes = box["axes"]
    served_step = -1
    if kv.get("ckpt"):
        params, ckdir, served_step = _ckpt_params(kv["ckpt"], params_target)
        print(f"loaded {ckdir} (step {served_step})", file=sys.stderr)
    elif kv.get("hf_path"):
        from hetu_galvatron_tpu.cli.checkpoint_convert import (
            _load_hf_state_dict,
        )
        from hetu_galvatron_tpu.runtime.checkpoint import hf_to_params

        params = hf_to_params(_load_hf_state_dict(kv["hf_path"]), cfg)
        print(f"loaded HF weights from {kv['hf_path']}", file=sys.stderr)
    else:
        print("warning: no ckpt/hf_path given; serving RANDOM weights "
              "(smoke mode)", file=sys.stderr)
        params = init_causal_lm(init_key, cfg)[0]

    # metrics registry: a dedicated JSONL stream for this serving run
    from hetu_galvatron_tpu.observability.registry import MetricsRegistry
    from hetu_galvatron_tpu.observability.sinks import JsonlSink

    metrics_path = kv.get("metrics") or args.serving.metrics_path or \
        "serve_metrics.jsonl"
    registry = MetricsRegistry([JsonlSink(metrics_path)])

    # plan-aware mesh (same pure-TP submesh heuristic as cli/generate.py)
    mesh = hpc = None
    world = len(jax.devices())
    degree_keys = ("parallel.global_tp_deg", "parallel.pp_deg",
                   "parallel.global_cp_deg", "parallel.vocab_tp")
    user_parallel = any(a.split("=", 1)[0] in degree_keys
                        for a in passthrough)
    tp = 1
    while (tp * 2 <= world and cfg.num_attention_heads % (tp * 2) == 0
           and cfg.kv_heads % (tp * 2) == 0):
        tp *= 2
    if world > 1 and (user_parallel or tp > 1):
        from hetu_galvatron_tpu.runtime.hybrid_config import (
            get_hybrid_parallel_config,
        )
        from hetu_galvatron_tpu.runtime.mesh import build_mesh

        if not user_parallel:
            args.parallel.global_tp_deg = tp
            if cfg.padded_vocab_size % tp == 0:
                args.parallel.vocab_tp = tp
            args.parallel.global_train_batch_size = tp
            sub_world = tp
        else:
            sub_world = world
        print(f"serving on {sub_world} devices "
              f"(tp={args.parallel.global_tp_deg})", file=sys.stderr)
        hpc = get_hybrid_parallel_config(args, sub_world)
        mesh = build_mesh(sub_world, 1, devices=jax.devices()[:sub_world])

    from hetu_galvatron_tpu.serving.engine import ServingEngine

    serving = args.serving
    if serving.eos_id is None:
        serving = serving.model_copy(
            update={"eos_id": getattr(tok, "eod_id", None)})
    stream = kv.get("stream", "1") not in ("0", "false", "False")
    draft_params, draft_cfg = _load_draft(kv, serving)
    engine = ServingEngine(params, cfg, serving, mesh=mesh, hpc=hpc,
                           axes_tree=axes if mesh is not None else None,
                           registry=registry,
                           draft_params=draft_params, draft_cfg=draft_cfg)
    if engine.metrics_port is not None:
        # serving.metrics_port: Prometheus text endpoint over the serve/*
        # registry (observability/prometheus.py); port 0 binds ephemeral,
        # serving.metrics_host widens the (loopback-default) bind
        print(f"metrics: http://{serving.metrics_host}:"
              f"{engine.metrics_port}/metrics", file=sys.stderr)

    if serving.prefix_cache or serving.spec_decode:
        print(f"serving features: prefix_cache={serving.prefix_cache} "
              f"spec_decode={serving.spec_decode}"
              + (f" (k={serving.spec_k}, draft={serving.spec_draft})"
                 if serving.spec_decode else ""), file=sys.stderr)
    if serving.trace_requests:
        # request-lifecycle tracing (observability/events.py): timelines
        # + TTFT breakdown render with `summarize <metrics> --timeline`
        print("request tracing: ON (per-request lifecycle events in the "
              "metrics stream)", file=sys.stderr)
    slo_parts = []
    if serving.slo_ttft_ms > 0:
        slo_parts.append(f"ttft<={serving.slo_ttft_ms}ms")
    if serving.slo_itl_ms > 0:
        slo_parts.append(f"itl<={serving.slo_itl_ms}ms")
    if slo_parts:
        # 0 means that SLO is off — never print an impossible 0ms target
        print(f"SLO targets: {' '.join(slo_parts)} (attainment gauges "
              "in serve/slo_*)", file=sys.stderr)
    if serving.flight_dir:
        print(f"flight recorder: dumps to {serving.flight_dir} on engine "
              "fault", file=sys.stderr)
    reqs = _read_requests(kv)
    # compile decode + every prefill bucket BEFORE traffic: TTFT must
    # measure serving latency, not jit compilation
    print("warmup: compiling decode + prefill buckets ...", file=sys.stderr)
    engine.warmup()
    engine.start()

    # watch mode: poll the checkpoint root and hot-swap every newly
    # committed step into the live engine (zero dropped requests, zero
    # recompiles; the stall rides serve/swap_stall_ms)
    watcher = None
    watch_stop = None
    if watch_s > 0:
        import os
        import threading

        from hetu_galvatron_tpu.runtime.checkpoint import latest_checkpoint

        watch_stop = threading.Event()

        def _watch(cur_step=served_step):
            # a step that keeps failing (wrong architecture, torn shards,
            # flaky mount) must not re-download the whole tree every poll
            # forever — but a TRANSIENT fault must not strand the watcher
            # on stale weights either: after 3 consecutive failures the
            # step backs off to one retry per ~30 polls (a newer commit
            # always tries immediately; success clears the slate)
            fails: dict = {}
            skip = 0
            bad_step = None
            while not watch_stop.wait(watch_s):
                step_n = None
                try:
                    found = latest_checkpoint(kv["ckpt"])
                    if not found:
                        continue
                    # advance by the DIRECTORY step (what latest_checkpoint
                    # orders by), never the loaded meta step — a dir whose
                    # name and meta disagree must not re-swap every poll
                    step_n = int(os.path.basename(found)[len("step_"):])
                    if step_n <= cur_step:
                        continue
                    if step_n == bad_step and skip > 0:
                        skip -= 1
                        continue
                    new_params, ckd, _ = _ckpt_params(found, params_target)
                    stall = engine.swap_weights(new_params)
                    print(f"weight swap: step {cur_step} -> {step_n} "
                          f"({ckd}, stall {stall:.1f} ms)",
                          file=sys.stderr)
                    cur_step = step_n
                    fails.pop(step_n, None)
                    bad_step = None
                except Exception as e:  # noqa: BLE001 — keep serving
                    print(f"warning: weight-swap watch failed: {e}",
                          file=sys.stderr)
                    if step_n is not None:
                        fails[step_n] = fails.get(step_n, 0) + 1
                        if fails[step_n] >= 3:
                            bad_step = step_n
                            skip = 30
                            print(f"warning: step {step_n} failed "
                                  f"{fails[step_n]} swap attempts; "
                                  "backing off (retry roughly every "
                                  "30 polls; a newer checkpoint swaps "
                                  "immediately)", file=sys.stderr)

        watcher = threading.Thread(target=_watch, daemon=True,
                                   name="ckpt-watch")
        watcher.start()
        print(f"watching {kv['ckpt']} every {watch_s:g}s for new "
              "committed checkpoints (hot swap)", file=sys.stderr)
    t0 = time.monotonic()
    handles = []
    try:
        for i, r in enumerate(reqs):
            at = float(r.get("arrival_offset_s", 0.0))
            wait = t0 + at - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            ids = tok.encode(r["prompt"])
            if not ids:
                print(json.dumps({"rid": i, "event": "rejected",
                                  "reason": "empty prompt"}))
                continue
            h = engine.submit(
                ids,
                max_new_tokens=r.get("max_new_tokens"),
                temperature=r.get("temperature"),
                seed=int(r.get("seed", 0)))
            handles.append((i, r, h))
            if h.status == "rejected":
                print(json.dumps({"rid": i, "event": "rejected",
                                  "reason": "capacity"}))

        for i, r, h in handles:
            if h.status == "rejected":
                continue
            if stream:
                for t in h.tokens():
                    print(json.dumps({"rid": i, "event": "token",
                                      "text": tok.decode([t])}), flush=True)
            out = h.result()
            eod = getattr(tok, "eod_id", None)
            if eod is not None and eod in out:
                out = out[: out.index(eod)]
            print(json.dumps({
                "rid": i, "event": "done", "status": h.status,
                "reason": h.finish_reason, "n_tokens": len(h.output),
                "ttft_ms": (None if h.ttft_s() is None
                            else round(h.ttft_s() * 1000.0, 3)),
                "text": tok.decode(out)}), flush=True)
    finally:
        if watch_stop is not None:
            watch_stop.set()
            watcher.join(timeout=5.0)
        engine.close()
        registry.close()
    print(f"metrics written to {metrics_path} "
          f"(render: python -m hetu_galvatron_tpu.cli.summarize "
          f"{metrics_path})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
