#!/usr/bin/env python
"""Closed-loop serving load generator: TTFT / inter-token / throughput.

Drives the in-process serving engine (``hetu_galvatron_tpu/serving/``) with
a fixed-concurrency closed loop — every completed request is immediately
replaced until the request budget is spent — the standard way to find a
serving stack's latency/throughput operating point (open-loop arrival
replays live in ``cli/serve.py`` via ``arrival_offset_s``).

CPU-runnable smoke mode::

    JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke

Real shapes::

    python tools/serve_bench.py --hidden 1024 --layers 8 --heads 16 \
        --kv-heads 4 --vocab 32000 --requests 256 --concurrency 32 \
        --max-batch 16 --max-new 64

Shared-prefix trace (``--shared-prefixes N``): requests open with one of N
generated system prompts (``--prefix-len`` tokens) plus a random suffix —
the production shape the radix prefix cache serves. With
``--prefix-cache`` the report splits TTFT percentiles by hit/miss and
carries ``prefix_hit_rate``; ``--spec-decode``/``--spec-k`` turn on
speculative decoding and report the accept rate.

Weights are random (the bench measures the serving machinery, not the
model); pass ``--json out.json`` for a machine-readable report and
``--metrics m.jsonl`` to keep the engine's own telemetry stream.

``run_prefix()`` / ``run_spec()`` are the importable A/B legs
``tools/bench_gate.py`` consumes (committed CPU baselines in
``tools/bench_baseline.json``): hit-vs-cold TTFT ratio and
spec-vs-plain tokens/sec ratio, both at zero steady-state recompiles.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def build_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + small load (CI / laptop)")
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="0 = MHA (== --heads)")
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--max-positions", type=int, default=1024)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--prompt-len", default="8:64",
                    help="min:max prompt length (uniform)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shared-prefixes", type=int, default=0,
                    help="N shared system prompts prepended to prompts "
                         "(0 = fully random trace)")
    ap.add_argument("--prefix-len", type=int, default=64,
                    help="shared system-prompt length in tokens")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the radix prefix cache")
    ap.add_argument("--spec-decode", action="store_true",
                    help="enable speculative decoding (n-gram draft)")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--json", default=None, help="write the report here")
    ap.add_argument("--metrics", default=None,
                    help="engine telemetry JSONL path")
    return ap.parse_args(argv)


def pct(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


def main(argv=None) -> int:
    ns = build_args(argv)
    if ns.smoke:
        ns.hidden, ns.layers, ns.heads, ns.vocab = 64, 2, 4, 256
        ns.max_positions = 128
        ns.requests = min(ns.requests, 24)
        ns.concurrency = min(ns.concurrency, 6)
        ns.max_batch = min(ns.max_batch, 4)
        ns.max_new = min(ns.max_new, 8)
        ns.prompt_len = "4:24"
        ns.block_size = 8
        ns.prefix_len = min(ns.prefix_len, 32)

    import jax
    import jax.numpy as jnp

    from hetu_galvatron_tpu.core.args_schema import ModelArgs, ServingArgs
    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.observability.registry import MetricsRegistry
    from hetu_galvatron_tpu.observability.sinks import JsonlSink
    from hetu_galvatron_tpu.serving.engine import ServingEngine

    lo, hi = (int(x) for x in ns.prompt_len.split(":"))
    base_len = ns.prefix_len if ns.shared_prefixes else 0
    max_total = min(ns.max_positions, base_len + hi + ns.max_new)
    cfg = ModelArgs(
        hidden_size=ns.hidden, num_hidden_layers=ns.layers,
        num_attention_heads=ns.heads,
        num_key_value_heads=ns.kv_heads or None,
        vocab_size=ns.vocab, max_position_embeddings=ns.max_positions,
        seq_length=max_total,
        hidden_act="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", tie_word_embeddings=False,
        add_bias_linear=False, add_qkv_bias=False,
        make_vocab_size_divisible_by=1)
    params, _ = init_causal_lm(jax.random.key(ns.seed), cfg)
    serving = ServingArgs(
        max_batch_size=ns.max_batch, kv_block_size=ns.block_size,
        max_seq_len=max_total,
        max_new_tokens=ns.max_new, temperature=ns.temperature,
        prefix_cache=ns.prefix_cache,
        spec_decode=ns.spec_decode, spec_k=ns.spec_k)
    registry = MetricsRegistry(
        [JsonlSink(ns.metrics)] if ns.metrics else [])
    # bf16 on accelerators, f32 on CPU (smoke numerics)
    dtype = (jnp.float32 if jax.devices()[0].platform == "cpu"
             else jnp.bfloat16)
    engine = ServingEngine(params, cfg, serving, registry=registry,
                           compute_dtype=dtype)
    # the shared-prefix trace: N fixed system prompts; each request opens
    # with one of them (uniform), then a random suffix
    sys_rng = np.random.RandomState(ns.seed + 100003)
    sys_prompts = [sys_rng.randint(0, cfg.vocab_size,
                                   (ns.prefix_len,)).tolist()
                   for _ in range(ns.shared_prefixes)]

    print(f"warmup: compiling decode + prefill buckets ...", file=sys.stderr)
    t0 = time.monotonic()
    engine.warmup()
    warm_s = time.monotonic() - t0
    compiles_warm = engine.compile_count()

    counter = {"left": ns.requests}
    lock = threading.Lock()
    ttfts, itls, lats, toks_out = [], [], [], [0]
    ttft_hit, ttft_miss = [], []
    not_done = {}  # status -> count: rejected/timeout/cancelled/error

    def worker(wid: int):
        # per-worker stream: RandomState is not thread-safe and a shared
        # one would make --seed runs depend on thread interleaving
        rng = np.random.RandomState(ns.seed + wid)
        while True:
            with lock:
                if counter["left"] <= 0:
                    return
                counter["left"] -= 1
            n = rng.randint(lo, hi + 1)
            prompt = rng.randint(0, cfg.vocab_size, (n,)).tolist()
            if sys_prompts:
                prompt = sys_prompts[rng.randint(len(sys_prompts))] + prompt
            t_sub = time.monotonic()
            h = engine.submit(prompt, seed=wid)
            prev = None
            for _ in h.tokens():
                now = time.monotonic()
                if prev is not None:
                    itls.append((now - prev) * 1000.0)
                prev = now
            if h.status != "done":
                # a benchmark must not silently shrink its own load:
                # non-completions are reported, not dropped
                with lock:
                    not_done[h.status] = not_done.get(h.status, 0) + 1
                continue
            ttfts.append(h.ttft_s() * 1000.0)
            (ttft_hit if h.cached_tokens else ttft_miss).append(
                h.ttft_s() * 1000.0)
            lats.append((h.finished_t - t_sub) * 1000.0)
            with lock:
                toks_out[0] += len(h.output)

    engine.start()
    t0 = time.monotonic()
    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(ns.concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    engine.close()
    registry.close()

    report = {
        "model": {"hidden": ns.hidden, "layers": ns.layers,
                  "heads": ns.heads, "vocab": ns.vocab},
        "load": {"requests": ns.requests, "concurrency": ns.concurrency,
                 "max_batch": ns.max_batch, "prompt_len": ns.prompt_len,
                 "max_new": ns.max_new},
        "warmup_s": round(warm_s, 3),
        "wall_s": round(wall, 3),
        "completed": len(lats),
        "not_completed": not_done,  # rejected/timeout/cancelled/error
        "tokens_out": toks_out[0],
        "tokens_per_sec": round(toks_out[0] / wall, 2) if wall else 0.0,
        "requests_per_sec": round(len(lats) / wall, 2) if wall else 0.0,
        "ttft_ms": {"p50": round(pct(ttfts, 50), 3),
                    "p90": round(pct(ttfts, 90), 3),
                    "p99": round(pct(ttfts, 99), 3)},
        "itl_ms": {"p50": round(pct(itls, 50), 3),
                   "p99": round(pct(itls, 99), 3)},
        "latency_ms": {"p50": round(pct(lats, 50), 3),
                       "p99": round(pct(lats, 99), 3)},
        "jit_programs_after_warmup": compiles_warm,
        "jit_programs_final": engine.compile_count(),
        "steady_state_recompiles":
            engine.compile_count() - compiles_warm,
    }
    if ns.prefix_cache:
        report["prefix_hit_rate"] = round(
            engine.prefix.hit_rate if engine.prefix else 0.0, 4)
        report["ttft_ms_hit"] = {"p50": round(pct(ttft_hit, 50), 3),
                                 "p90": round(pct(ttft_hit, 90), 3),
                                 "n": len(ttft_hit)}
        report["ttft_ms_miss"] = {"p50": round(pct(ttft_miss, 50), 3),
                                  "p90": round(pct(ttft_miss, 90), 3),
                                  "n": len(ttft_miss)}
    if ns.spec_decode:
        report["spec_accept_rate"] = round(engine.spec_accept_rate(), 4)
    print(json.dumps(report, indent=2))
    if ns.json:
        with open(ns.json, "w") as f:
            json.dump(report, f, indent=2)
    return 0


# ---------------------------------------------------------------------------
# importable A/B legs (bench.py / tools/bench_gate.py)
# ---------------------------------------------------------------------------


def _leg_engine(prefix_cache, spec_decode, *, seed=0, max_new=24,
                hidden=128, layers=2, max_pos=256, max_seq=192,
                warm_buckets=None):
    """One small single-device engine for the A/B legs (CPU-runnable; on
    TPU the same shapes measure the real dispatch path)."""
    import jax
    import jax.numpy as jnp

    from hetu_galvatron_tpu.core.args_schema import ModelArgs, ServingArgs
    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.serving.engine import ServingEngine

    cfg = ModelArgs(
        hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=4, vocab_size=512,
        max_position_embeddings=max_pos, seq_length=128,
        hidden_act="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", tie_word_embeddings=False,
        add_bias_linear=False, add_qkv_bias=False,
        make_vocab_size_divisible_by=1)
    params, _ = init_causal_lm(jax.random.key(seed), cfg)
    sv = ServingArgs(max_batch_size=4, kv_block_size=8,
                     max_seq_len=max_seq,
                     max_new_tokens=max_new, prefix_cache=prefix_cache,
                     spec_decode=spec_decode, spec_k=4)
    dtype = (jnp.float32 if jax.devices()[0].platform == "cpu"
             else jnp.bfloat16)
    eng = ServingEngine(params, cfg, sv, compute_dtype=dtype)
    eng.warmup(buckets=warm_buckets)
    return eng, cfg


def run_prefix(on_tpu: bool = False, reps: int = 12):
    """The ``serve_prefix`` bench leg: hit-vs-cold TTFT on a shared-prefix
    trace. Reports ``serve_prefix_ttft_ratio`` = median(hit TTFT) /
    median(cold TTFT) — below 1.0 means the radix cache really skips
    prefill work; regresses UP. The model/prefix are sized so a cold
    prefill is tens of ms on CPU (OS scheduling noise amortizes) and the
    pairs interleave so load spikes land on both sides."""
    import numpy as np

    # only the two buckets the leg exercises get warmed (cold prompts
    # bucket to 512, hit suffixes to 8) — warmup stays seconds, not the
    # full ladder
    eng, cfg = _leg_engine(True, False, max_new=2, hidden=256, layers=4,
                           max_pos=640, max_seq=520,
                           warm_buckets=[8, 512])
    rng = np.random.RandomState(0)
    cold_ms, hit_ms = [], []
    recompiles0 = eng.compile_count()
    try:
        for rep in range(reps):
            sys_p = rng.randint(0, cfg.vocab_size, (496,)).tolist()
            hc = eng.submit(sys_p + [1])
            eng.run_until_idle()
            if hc.status != "done":
                return {"skipped": f"cold request {hc.status}"}
            hh = eng.submit(sys_p + [2])
            eng.run_until_idle()
            if hh.status != "done" or not hh.cached_tokens:
                return {"skipped": "hit request missed the cache"}
            if rep == 0:
                continue  # first pair warms allocator paths; drop it
            cold_ms.append(hc.ttft_s() * 1000.0)
            hit_ms.append(hh.ttft_s() * 1000.0)
        ratio = float(np.median(hit_ms) / np.median(cold_ms))
        return {
            "serve_prefix_ttft_ratio": round(ratio, 4),
            "ttft_cold_ms": round(float(np.median(cold_ms)), 3),
            "ttft_hit_ms": round(float(np.median(hit_ms)), 3),
            "prefix_hit_rate": round(eng.prefix.hit_rate, 4),
            "serve_prefix_recompiles": eng.compile_count() - recompiles0,
            "platform": "tpu" if on_tpu else "cpu",
        }
    finally:
        eng.close()


def run_spec(on_tpu: bool = False, requests: int = 6, iters: int = 5):
    """The ``spec_decode`` bench leg: tokens/sec with speculative decoding
    vs plain decode on the same greedy workload (long continuations, so
    the n-gram draft has cycles to predict). Reports
    ``spec_decode_tokens_ratio`` = spec/plain — above 1.0 means accepted
    drafts outpace the wider verify program; regresses DOWN.

    A/B runs INTERLEAVE (plain, spec, plain, spec, ...) and the ratio is
    taken between per-iteration medians, so a load spike on a shared CPU
    host lands on both sides instead of poisoning one (the
    tp_overlap_bench recipe). Both sides emit the identical greedy
    streams, so the tokens/sec ratio reduces to a wall-time ratio."""
    import time as _time

    import numpy as np

    eng_plain, cfg = _leg_engine(False, False, max_new=64)
    eng_spec, _ = _leg_engine(False, True, max_new=64)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (12,)).tolist()
               for _ in range(requests)]
    recompiles0 = eng_spec.compile_count()
    walls = {False: [], True: []}
    toks = {False: 0, True: 0}
    try:
        for it in range(iters + 1):
            for spec, eng in ((False, eng_plain), (True, eng_spec)):
                t0 = _time.monotonic()
                handles = [eng.submit(p) for p in prompts]
                eng.run_until_idle()
                wall = _time.monotonic() - t0
                if not all(h.status == "done" for h in handles):
                    return {"skipped": "a bench request did not complete"}
                if it == 0:
                    continue  # warm allocator/telemetry paths; drop it
                walls[spec].append(wall)
                toks[spec] = sum(len(h.output) for h in handles)
        if toks[False] != toks[True]:
            return {"skipped": "spec stream diverged from plain (token "
                               "counts differ) — losslessness bug"}
        ratio = float(np.median(walls[False]) / np.median(walls[True]))
        return {
            "spec_decode_tokens_ratio": round(ratio, 4),
            "tokens_per_sec_plain": round(
                toks[False] / float(np.median(walls[False])), 2),
            "tokens_per_sec_spec": round(
                toks[True] / float(np.median(walls[True])), 2),
            "spec_accept_rate": round(eng_spec.spec_accept_rate(), 4),
            "spec_decode_recompiles":
                eng_spec.compile_count() - recompiles0,
            "platform": "tpu" if on_tpu else "cpu",
        }
    finally:
        eng_plain.close()
        eng_spec.close()


if __name__ == "__main__":
    sys.exit(main())
