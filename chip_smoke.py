#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the trainer still starts on a TPU.

    python chip_smoke.py

Drives the system's main path once, through the entry point users call
(``hetu_galvatron_tpu.cli.train_dist.main``), on ``gpt2-small.yaml`` exactly
as published (hidden 768, 12 layers, 12 heads of 64, vocab 50257 padded to
50304, sequence 1024; bf16, batch 8 x 1024, random seeded data, default
options), and checks what comes out. It refuses to run on anything but a
TPU: a CPU, an interpret-mode kernel or the XLA attention core is a failure
here, never a slower pass.

Legs, each in its OWN child process, one after another (a chip belongs to
one process; this parent never imports JAX, so it never holds one):

  kernels        Mosaic-compiles the Pallas kernels at the model's own shapes
                 and compares them with the XLA reference, forward and
                 backward: flash attention (MHA, GQA at head 64, and GQA
                 4:1 at head 128 and S=4096) and the fused cross-entropy.
  train1         the trainer on ONE chip, a few steps, run twice: the second
                 process must find the first one's programs in the
                 persistent compile cache (cli/compile_cache.py).
  train4_tp2dp2  (hosts showing >= 4 chips) the same model as tp2 x dp2,
                 ZeRO-3, on the pp=1 SPMD path; every chip must hold its
                 share and chip 0 must not carry the model.
  train4_pp2tp2  (hosts showing >= 4 chips) pp2 x tp2 on the host pipeline
                 engine (per-stage submeshes).

Every train leg checks: exit code 0, every loss finite, first loss within
3% of ln(padded vocab), every layer on the flash core, and the Mosaic
custom calls counted in the compiled step's HLO (three per layer). The
four-chip plans must also reproduce the one-chip run's losses.

The last line of stdout is one JSON object with these keys and no other:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
the device as JAX reports it. The line before it, ``report: {...}``, carries
the versions and every leg's figures. Any failed leg ends the run at once
with a non-zero exit code and neither line. Times printed here are smoke timings of one run each (compile and
first-step seconds); they are not benchmark metrics.

The leg bodies (:func:`leg_kernels`, :func:`leg_train`) are plain functions
so the test suite rehearses their control flow on the CPU mesh at a tiny
size (interpret-mode kernels passed explicitly); the script entry itself
only ever runs them on a TPU.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

REPO = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(REPO, "hetu_galvatron_tpu", "models", "configs",
                      "gpt2-small.yaml")
LEG_TIMEOUT_S = 420    # per child
TOTAL_BUDGET_S = 1100  # all children, inside the driver's 1200 s

# Problem sizes. FULL is the smoke model's own; tests pass a tiny dict of
# the same shape.
FULL: Dict[str, Any] = {
    "flash_mha": (8, 1024, 12, 12, 64),   # B, S, heads, kv heads, head dim
    "flash_gqa": (2, 1024, 12, 4, 64),
    # Mistral's attention (GQA 4:1, head 128, S=4096) on a quarter of its
    # heads: the dense f32 reference holds [heads, S, S] scores
    "flash_gqa_h128": (1, 4096, 8, 2, 128),
    "ce": (8 * 1024, 50304),              # tokens, padded vocab
    "model": [],                          # no width or depth override
    "iters": 5,
}

# plan overrides per train leg, on top of the model's defaults
PLANS: Dict[str, List[str]] = {
    "train1": ["parallel.num_devices=1"],
    "train4_tp2dp2": ["parallel.num_devices=4", "parallel.global_tp_deg=2",
                      "parallel.sdp=1"],
    "train4_pp2tp2": ["parallel.num_devices=4", "parallel.pp_deg=2",
                      "parallel.global_tp_deg=2"],
}

# parity tolerances, set from the dtype before any run: a result within
# TOL * max|reference| of the float32 reference. bfloat16 keeps 8
# significand bits (eps 2^-7); float32 results cross the chip's
# transcendental approximations, hence 1e-3 and not 1e-6.
TOL = {"bfloat16": 2 * 2.0 ** -7, "float32": 1e-3}
# a four-chip plan against the one-chip run, per step, on losses near 11:
# bf16 reduction order moves the 4th decimal, a wrong sharding the 1st
PLAN_LOSS_TOL = 0.01


class SmokeFailure(Exception):
    """A leg's check did not hold."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_report() -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them, plus the versions of
    the one installation there is."""
    from importlib import metadata

    import jax
    import jaxlib

    devs = jax.devices()
    return {
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": metadata.version("libtpu")},
    }


def require_tpu() -> Dict[str, Any]:
    rep = device_report()
    d = rep["device"]
    print(f"device: platform={d['platform']} kind={d['kind']!r} "
          f"count={d['count']}  jax={rep['versions']['jax']} "
          f"jaxlib={rep['versions']['jaxlib']} "
          f"libtpu={rep['versions']['libtpu']}", flush=True)
    if d["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: refusing to run: jax.devices()[0].platform is "
            f"{d['platform']!r} ({d['kind']!r} x {d['count']}), not 'tpu'")
    return rep


def device_memory() -> List[Dict[str, Any]]:
    """Per-device allocator statistics of THIS process (peak is the
    process's high-water mark, which is why each leg has its own)."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit")})
    return out


class CompileWatch:
    """Counts persistent-cache hits and writes and sums backend compile
    seconds, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.hits = self.writes = 0
        self.backend_compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def close(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_listener(self._event)
        mon.unregister_event_duration_listener(self._duration)

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1  # recorded when an entry is written

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += secs


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# ---------------------------------------------------------------------------
# leg: kernels
# ---------------------------------------------------------------------------


def _check_parity(name: str, got, ref, dtype_name: str) -> float:
    """max|got - ref| against TOL[dtype] * max|ref|; returns the error."""
    import jax.numpy as jnp

    ref = ref.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    tol = TOL[dtype_name] * scale
    print(f"  {name}: max|err| {err:.3e}  (tolerance {tol:.3e}, "
          f"max|ref| {scale:.3e})", flush=True)
    require(math.isfinite(err) and err <= tol,
            f"{name}: max|err| {err:.3e} exceeds {tol:.3e}")
    return err


def _flash_parity(name: str, shape: Sequence[int], interpret: bool
                  ) -> Dict[str, Any]:
    """Flash forward and backward against the XLA core on float32 copies of
    the same bfloat16 inputs, at highest matmul precision."""
    import jax
    import jax.numpy as jnp

    from hetu_galvatron_tpu.models.modules import xla_sdpa
    from hetu_galvatron_tpu.ops.pallas.flash_attention import flash_sdpa

    B, S, N, K, D = shape
    kq, kk, kv, kw = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(kq, (B, S, N, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, K, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, K, D), jnp.bfloat16)
    w = jax.random.normal(kw, (B, S, N, D), jnp.float32)  # cotangent

    def both(fn, *xs):
        f = jax.jit(jax.value_and_grad(
            lambda a, b, c: jnp.sum(fn(a, b, c).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))
        out = jax.jit(fn)(*xs)
        _, grads = f(*xs)
        return (out,) + tuple(grads)

    print(f"{name}: B{B} S{S} N{N} K{K} D{D} bf16 causal, "
          f"interpret={interpret}", flush=True)
    t0 = time.perf_counter()
    got = jax.block_until_ready(both(
        lambda a, b, c: flash_sdpa(a, b, c, causal=True,
                                   interpret=interpret), q, k, v))
    secs = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        ref = both(lambda a, b, c: xla_sdpa(a, b, c, causal=True),
                   *(x.astype(jnp.float32) for x in (q, k, v)))
    errs = {part: _check_parity(f"{name}.{part}", g, r, "bfloat16")
            for part, g, r in zip(("out", "dq", "dk", "dv"), got, ref)}
    return {"shape": list(shape), "compile_and_run_s": round(secs, 2),
            "max_err": errs}


def _ce_parity(shape: Sequence[int], interpret: bool) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from hetu_galvatron_tpu.ops.pallas.cross_entropy import fused_ce_nll

    T, V = shape
    kx, kl, kw = jax.random.split(jax.random.key(1), 3)
    # float32: what the LM head hands the loss
    logits = jax.random.normal(kx, (T, V), jnp.float32)
    labels = jax.random.randint(kl, (T,), 0, V, jnp.int32)
    w = jax.random.uniform(kw, (T,), jnp.float32, 0.5, 1.5)  # cotangent

    def ref_nll(x):
        lse = jax.scipy.special.logsumexp(x, axis=-1)
        return lse - jnp.take_along_axis(x, labels[:, None], axis=-1)[:, 0]

    def fused_nll(x):
        nll = fused_ce_nll(x, labels, interpret=interpret)
        require(nll is not None, f"fused CE cannot tile {T} x {V}")
        return nll

    def both(fn):
        nll = jax.jit(fn)(logits)
        g = jax.jit(jax.grad(lambda x: jnp.sum(fn(x) * w)))(logits)
        return nll, g

    print(f"fused_ce: {T} x {V} f32, interpret={interpret}", flush=True)
    t0 = time.perf_counter()
    got = jax.block_until_ready(both(fused_nll))
    secs = time.perf_counter() - t0
    ref = both(ref_nll)
    errs = {part: _check_parity(f"fused_ce.{part}", g, r, "float32")
            for part, g, r in zip(("nll", "dlogits"), got, ref)}
    return {"shape": list(shape), "compile_and_run_s": round(secs, 2),
            "max_err": errs}


def leg_kernels(sizes: Dict[str, Any], *, interpret: bool) -> Dict[str, Any]:
    """Compile the Pallas kernels (Mosaic unless ``interpret``) and compare
    them with the XLA reference, forward and backward."""
    return {
        **{name: _flash_parity(name, sizes[name], interpret)
           for name in ("flash_mha", "flash_gqa", "flash_gqa_h128")},
        "fused_ce": _ce_parity(sizes["ce"], interpret),
        "device_memory": device_memory(),
    }


# ---------------------------------------------------------------------------
# leg: train
# ---------------------------------------------------------------------------


def leg_train(name: str, sizes: Dict[str, Any], *, expect_mosaic: bool
              ) -> Dict[str, Any]:
    """One trainer run through ``train_dist.main`` under ``PLANS[name]``,
    and the checks on what it returned. ``expect_mosaic`` is True on a TPU
    (every layer on the flash core, Mosaic calls in the compiled step) and
    False only in the CPU rehearsal, where the trainer must say ``xla``."""
    from hetu_galvatron_tpu.cli import train_dist
    from hetu_galvatron_tpu.cli.compile_cache import configure_compile_cache
    from hetu_galvatron_tpu.core.arguments import args_from_cli

    argv = ([CONFIG, "data.dataset=random",
             f"train.train_iters={sizes['iters']}"]
            + list(sizes["model"]) + PLANS[name])
    cfg = args_from_cli(argv, mode="train_dist").model
    cache_dir = configure_compile_cache()
    entries_before = cache_entries(cache_dir)
    watch = CompileWatch()
    print(f"{name}: train_dist.main({' '.join(argv[1:])})", flush=True)
    out: Dict[str, Any] = {}
    t0 = time.perf_counter()
    try:
        rc = train_dist.main(argv, result=out)
    finally:
        watch.close()
    wall = time.perf_counter() - t0

    require(rc == 0, f"{name}: train_dist.main returned {rc}")
    losses = out["losses"]
    require(len(losses) == sizes["iters"],
            f"{name}: {len(losses)} losses for {sizes['iters']} iterations")
    require(all(math.isfinite(x) for x in losses),
            f"{name}: non-finite loss in {losses}")
    want = math.log(cfg.padded_vocab_size)
    require(abs(losses[0] - want) <= 0.03 * want,
            f"{name}: first loss {losses[0]:.4f} is not within 3% of "
            f"ln({cfg.padded_vocab_size}) = {want:.4f}")
    cores = out["attention_cores"]
    mosaic = out["mosaic_custom_calls"]
    if expect_mosaic:
        require(set(cores) == {"flash"},
                f"{name}: attention cores {cores}, wanted flash everywhere")
        # forward, dq and dk/dv kernels per layer, counted in the compiled
        # step's HLO (pp=1) or over the stage backward programs (pp>1)
        require(mosaic is not None and mosaic >= 3 * len(cores),
                f"{name}: compiled step holds {mosaic} Mosaic custom calls, "
                f"wanted >= {3 * len(cores)}")
    else:
        require(set(cores) == {"xla"} and not mosaic,
                f"{name}: rehearsal expected the xla core and no Mosaic "
                f"call, got {cores} / {mosaic}")
    mem = device_memory()
    report = {
        "argv": argv[1:],
        "losses": [round(x, 4) for x in losses],
        "attention_cores": sorted(set(cores)),
        "mosaic_custom_calls": mosaic,
        "device_memory": mem,
        "compile_cache": {"dir": cache_dir,
                          "entries_before": entries_before,
                          "entries_after": cache_entries(cache_dir),
                          "hits": watch.hits, "writes": watch.writes},
        "smoke_timings_s": {
            "backend_compile": round(watch.backend_compile_s, 2),
            "first_step_incl_compile": round(
                out["goodput"]["totals"].get("recompile", 0.0), 2),
            "leg_wall": round(wall, 2)},
    }
    print(f"  losses {report['losses']}", flush=True)
    print(f"  cores {report['attention_cores']}  mosaic custom calls "
          f"{mosaic}", flush=True)
    print(f"  compile cache {report['compile_cache']}", flush=True)
    print(f"  smoke timings (s) {report['smoke_timings_s']}", flush=True)
    for m in mem:
        print(f"  device {m['id']}: peak_bytes_in_use "
              f"{m['peak_bytes_in_use']}", flush=True)
    return report


def check_spread(name: str, mem: List[Dict[str, Any]], world: int) -> None:
    """Every chip of the plan holds its share; chip 0 does not carry the
    model (its peak stays within a quarter of the largest other peak)."""
    peaks = [m["peak_bytes_in_use"] for m in mem[:world]]
    require(all(peaks), f"{name}: a device reports no bytes in use: {peaks}")
    require(peaks[0] <= 1.25 * max(peaks[1:]),
            f"{name}: device 0 peaked at {peaks[0]} bytes against "
            f"{max(peaks[1:])} on the fullest other device")


# ---------------------------------------------------------------------------
# child and parent entries
# ---------------------------------------------------------------------------


def run_child(leg: str) -> None:
    """One leg, in this process, on a TPU or not at all."""
    rep = require_tpu()
    if leg == "kernels":
        rep.update(leg_kernels(FULL, interpret=False))
    else:
        rep.update(leg_train(leg, FULL, expect_mosaic=True))
        if leg.startswith("train4"):
            check_spread(leg, rep["device_memory"], 4)
    print(json.dumps({"leg": leg, "ok": True, **rep}), flush=True)


def spawn_leg(leg: str, deadline: float) -> Dict[str, Any]:
    """Run one leg as a child, echo its output, return its report. The
    child is killed at its time limit; nothing is left running."""
    print(f"=== leg {leg}", flush=True)
    t0 = time.monotonic()
    limit = min(LEG_TIMEOUT_S, deadline - t0)
    require(limit > 0, f"leg {leg}: the run's {TOTAL_BUDGET_S} s are spent")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--leg", leg],
        cwd=REPO, stdout=subprocess.PIPE, text=True)
    try:
        stdout, _ = proc.communicate(timeout=limit)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise SmokeFailure(f"leg {leg}: no result in {limit:.0f} s")
    lines = stdout.splitlines()
    ok = proc.returncode == 0
    for line in lines[:-1] if ok else lines:
        print(line, flush=True)
    require(ok, f"leg {leg}: exit code {proc.returncode}")
    report = json.loads(lines[-1])
    require(report.get("ok") is True and report.get("leg") == leg,
            f"leg {leg}: last line is not its report")
    print(f"=== leg {leg} ok in {time.monotonic() - t0:.0f} s", flush=True)
    return report


def run_parent() -> Dict[str, Any]:
    """Every leg in turn; returns the final report or raises
    :class:`SmokeFailure` at the first leg that fails."""
    require(os.path.isfile(CONFIG),
            f"{CONFIG} is missing: chip_smoke.py runs from the root of a "
            "checkout of the repository, not on its own")
    deadline = time.monotonic() + TOTAL_BUDGET_S
    legs: Dict[str, Any] = {}
    first = legs["kernels"] = spawn_leg("kernels", deadline)
    device = first["device"]
    cold = legs["train1"] = spawn_leg("train1", deadline)
    warm = legs["train1_cached"] = spawn_leg("train1", deadline)
    require(warm["compile_cache"]["hits"] > 0,
            "train1 run twice: the second process hit nothing in "
            f"{warm['compile_cache']['dir']}")
    print("compile cache: first run wrote "
          f"{cold['compile_cache']['writes']} entries (backend compile "
          f"{cold['smoke_timings_s']['backend_compile']} s), second run hit "
          f"{warm['compile_cache']['hits']} (backend compile "
          f"{warm['smoke_timings_s']['backend_compile']} s) — smoke timings",
          flush=True)
    if device["count"] >= 4:
        for leg in ("train4_tp2dp2", "train4_pp2tp2"):
            legs[leg] = spawn_leg(leg, deadline)
            # same seed, same data: the one-chip run is the reference
            worst = max(abs(a - b) for a, b in
                        zip(legs[leg]["losses"], cold["losses"]))
            require(worst <= PLAN_LOSS_TOL,
                    f"{leg}: losses {legs[leg]['losses']} stray {worst:.4f} "
                    f"from the one-chip run's {cold['losses']}")
    for name, r in legs.items():  # every child saw the same chips
        require(r["device"] == device, f"leg {name} saw {r['device']}")
    print("legs ran on devices: " + ", ".join(
        f"{n} x{4 if n.startswith('train4') else 1}" for n in legs),
        flush=True)
    shared = ("leg", "ok", "device", "versions")
    return {"ok": True, "device": device, "versions": first["versions"],
            "legs": {n: {k: v for k, v in r.items() if k not in shared}
                     for n, r in legs.items()}}


def result_line(report: Dict[str, Any]) -> str:
    """The driver's line: ``ok`` and the device, exactly those keys."""
    d = report["device"]
    return json.dumps({"ok": report["ok"], "device": {
        "platform": d["platform"], "kind": d["kind"], "count": d["count"]}})


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leg", choices=["kernels"] + list(PLANS),
                    help="run one leg in this process (what the parent "
                         "spawns); default: run every leg in children")
    args = ap.parse_args(argv)
    try:
        if args.leg is None:
            report = run_parent()
            print("report: " + json.dumps(report), flush=True)
            print(result_line(report), flush=True)
        else:
            run_child(args.leg)
        return 0
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
