"""Hardware profiler: ICI/DCN collective microbenchmarks.

Capability parity with the reference hardware profiling stack
(core/profiler/hardware_profiler.py:39-229 script generation +
profile_hardware/profile_allreduce.py:84-162, profile_p2p.py:19,
profile_all2all.py, profile_overlap.py:10-60): measures
- all-reduce bandwidth (MB/ms) per group size, consecutive and strided
- p2p (ppermute ring) bandwidth per pipeline degree
- all-reduce / all-to-all latency vs message size (the sp_time tables)
- the compute/comm overlap slowdown coefficient
and writes the same JSON schemas the search engine reads
(hardware_configs/*.json).

TPU-native: instead of spawning torchrun scripts per benchmark, collectives
run as jitted `shard_map` programs over sub-meshes of the current platform's
devices — the same code path measures ICI on a TPU slice and host rings on
the virtual CPU mesh (tests).
"""

from __future__ import annotations

import os
import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_galvatron_tpu.core.args_schema import HardwareProfileArgs
from hetu_galvatron_tpu.core.search_engine.profiles import write_json


def _time_fn(fn, arg, *, warmup: int, iters: int, inner: int = 1) -> float:
    """Median wall-clock ms of fn(arg) (reference uses trimmed means over 20
    x10-iter samples, profile_allreduce.py:14-17,129-133)."""
    out = None
    for _ in range(warmup):
        out = fn(arg)
    if out is not None:
        jax.block_until_ready(out)
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(arg)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / inner * 1000.0)
    return float(np.median(samples))


# the profiler's private single-axis benchmark mesh name (the legacy
# literal uses are baselined in analysis/lint_baseline.json; new code
# routes through this constant so GAL003 stays at zero new findings)
_G_AXIS = "g"

# slope floor for the α-β fit (ms per MB): measurement noise on sub-MB
# points can tilt the fitted line flat or NEGATIVE, and 1/slope would then
# be a nonsense β (infinite-or-negative bandwidth). Below the floor the
# fit is rejected and the legacy single-point bandwidth stays the model.
_MIN_SLOPE_MS_PER_MB = 1e-7


def fit_alpha_beta(xs: Sequence[float], ys: Sequence[float], *,
                   label: str = "") -> Optional[Tuple[float, float]]:
    """Least-squares ``t(size) = α + size/β`` fit over (MB, ms) points.
    Returns (α ms ≥ 0, β MB/ms) — or None with a warning when the slope is
    degenerate (≤ :data:`_MIN_SLOPE_MS_PER_MB`): writing a garbage pair
    would poison every cost the search prices with it, while an ABSENT
    pair falls back to the measured latency tables."""
    slope, alpha = np.polyfit(list(xs), list(ys), 1)
    if float(slope) <= _MIN_SLOPE_MS_PER_MB:
        warnings.warn(
            f"alpha-beta fit {label or '<unnamed>'}: degenerate slope "
            f"{float(slope):.3e} ms/MB (noisy sub-MB points?); skipping "
            "the pair — the legacy single-point bandwidth stays in effect",
            stacklevel=2)
        return None
    return max(float(alpha), 0.0), 1.0 / float(slope)


def _group_devices(devices: Sequence, size: int, consecutive: bool,
                   world: int) -> List:
    """First group of `size` devices: adjacent chips (ICI neighbours) or
    strided across the world (the reference's consec 1/0 groupings,
    comm_groups.py:96-100)."""
    if consecutive:
        return list(devices[:size])
    stride = world // size
    return [devices[i * stride] for i in range(size)]


def _allreduce_body(alg: str, n: int, axis: str) -> Callable:
    """The explicit all-reduce program for ``alg`` (``ring`` | ``tree``)
    over an ``n``-rank group on ``axis``: a function of one flat per-device
    vector (length divisible by ``n`` for ring, by 2 per halving round for
    tree), returning the group sum — called inside a full-manual shard_map
    over ``axis``. ``n`` is a power of two."""
    if alg == "ring":
        def body(v):
            r = jax.lax.axis_index(axis)
            c = v.shape[0] // n
            chunks = v.reshape(n, c)
            perm = [(i, (i + 1) % n) for i in range(n)]
            # reduce-scatter ring: the accumulator for chunk k starts
            # at rank (k+1)%n and collects each rank's share en route
            acc = None
            for t in range(n):
                k = (r - 1 - t) % n
                part = jnp.take(chunks, k, axis=0)
                acc = part if acc is None else (
                    jax.lax.ppermute(acc, axis, perm) + part)
            # all-gather ring: rotate the owned chunk n-1 hops
            out = jnp.zeros((n, c), v.dtype)
            cur = acc
            for t in range(n):
                k = (r - t) % n
                out = jax.lax.dynamic_update_index_in_dim(out, cur, k, 0)
                if t < n - 1:
                    cur = jax.lax.ppermute(cur, axis, perm)
            return out.reshape(-1)
        return body

    if alg == "tree":
        rounds = n.bit_length() - 1

        def body(v):
            r = jax.lax.axis_index(axis)
            cur = v
            # recursive halving reduce-scatter: round k exchanges half
            # the live payload with the rank at distance 2^k
            for k in range(rounds):
                perm = [(i, i ^ (1 << k)) for i in range(n)]
                half = cur.shape[0] // 2
                bit = (r >> k) & 1
                lo, hi = cur[:half], cur[half:]
                send = jnp.where(bit == 0, hi, lo)
                recv = jax.lax.ppermute(send, axis, perm)
                cur = jnp.where(bit == 0, lo, hi) + recv
            # recursive doubling all-gather: reverse rounds, payload
            # doubling back to full size
            for k in range(rounds - 1, -1, -1):
                perm = [(i, i ^ (1 << k)) for i in range(n)]
                bit = (r >> k) & 1
                recv = jax.lax.ppermute(cur, axis, perm)
                cur = jnp.where(bit == 0,
                                jnp.concatenate([cur, recv]),
                                jnp.concatenate([recv, cur]))
            return cur
        return body

    raise ValueError(f"unknown collective algorithm {alg!r} (ring | tree)")


def _dcn_group_devices(devices: Sequence, size: int, world: int
                       ) -> Tuple[List, str]:
    """A ``size``-device group whose links actually cross the DCN seam,
    plus the level-source tag recorded in the fitted JSON metadata.

    Multi-process jobs (``jax.process_count() > 1``) pick devices
    round-robin across processes (slice boundaries granule by process on
    pods without ``slice_index``), so every hop in the benchmarked
    collective crosses a host/slice boundary — a TRUE DCN measurement.
    Single-process runs (CPU tests, one-slice jobs) keep the maximally
    STRIDED proxy group with a warning: its hops measure intra-host
    stride, not a slice boundary, so the fitted "dcn" α/β only bound the
    topology model until a real multi-slice fleet re-measures them."""
    devices = list(devices[:world])
    by_proc: Dict[int, List] = {}
    for d in devices:
        by_proc.setdefault(getattr(d, "process_index", 0), []).append(d)
    if len(by_proc) > 1:
        # interleave one device per process until the group is full:
        # adjacent group members always sit in different processes
        group: List = []
        ranks = sorted(by_proc)
        i = 0
        while len(group) < size:
            proc = by_proc[ranks[i % len(ranks)]]
            if proc:
                group.append(proc.pop(0))
            i += 1
            if i > 10 * size * len(ranks):  # all pools drained
                break
        if len(group) == size:
            return group, "multihost"
    warnings.warn(
        "profile_alpha_beta_algos: single-process fleet — the 'dcn' "
        "level falls back to the strided intra-host PROXY group, which "
        "measures stride, not a slice boundary; re-measure on a "
        "multi-slice fleet before trusting the DCN α/β",
        stacklevel=2)
    return _group_devices(devices, size, False, world), "proxy-strided"


class HardwareProfiler:
    def __init__(self, args: HardwareProfileArgs,
                 devices: Optional[Sequence] = None):
        self.args = args
        self.devices = list(devices if devices is not None else jax.devices())
        self.world = min(len(self.devices),
                         args.num_nodes * args.num_devices_per_node)

    # -- collective runners -------------------------------------------------

    def _collective_ms(self, op: str, group: List, message_mb: float) -> float:
        """Time one collective over `group` with a message of `message_mb`
        MB per device (fp32)."""
        n = len(group)
        mesh = Mesh(np.array(group), (_G_AXIS,))
        elems = max(int(message_mb * 1024 * 1024 // 4), n)
        elems = (elems // n) * n
        x = jax.device_put(
            jnp.ones((elems,), jnp.float32),
            NamedSharding(mesh, P(None)))

        from hetu_galvatron_tpu.ops.pallas.common import on_shards

        if op == "allreduce":
            fn = on_shards(lambda v: jax.lax.psum(v, _G_AXIS), mesh,
                           P(None), P(None))
        elif op == "allgather":
            x = jax.device_put(jnp.ones((elems,), jnp.float32),
                               NamedSharding(mesh, P(_G_AXIS)))
            fn = on_shards(
                lambda v: jax.lax.all_gather(v, _G_AXIS, tiled=True),
                mesh, P(_G_AXIS), P(None))
        elif op == "all2all":
            x = jax.device_put(jnp.ones((n, elems // n), jnp.float32),
                               NamedSharding(mesh, P(_G_AXIS, None)))
            fn = on_shards(
                lambda v: jax.lax.all_to_all(v, _G_AXIS, split_axis=1,
                                             concat_axis=0, tiled=True),
                mesh, P(_G_AXIS, None), P(None, _G_AXIS))
        elif op == "p2p":
            perm = [(i, (i + 1) % n) for i in range(n)]
            fn = on_shards(lambda v: jax.lax.ppermute(v, _G_AXIS, perm),
                           mesh, P(None), P(None))
        else:
            raise ValueError(op)
        jfn = jax.jit(fn)
        return _time_fn(jfn, x, warmup=self.args.warmup_iters,
                        iters=self.args.profile_iters)

    # -- benchmark suites ---------------------------------------------------

    def profile_allreduce_bandwidth(self, message_mb: int = 64
                                    ) -> Dict[str, float]:
        """allreduce_bandwidth_*.json: MB/ms per (group size, consec) with
        the 2x(n-1)/n algorithmic volume (profile_allreduce.py:84-162)."""
        out: Dict[str, float] = {}
        size = self.world
        while size >= 2:
            for consec in ([1] if size == self.world else [1, 0]):
                group = _group_devices(self.devices, size, bool(consec),
                                       self.world)
                ms = self._collective_ms("allreduce", group, message_mb)
                volume = 2 * (size - 1) / size * message_mb
                out[f"allreduce_size_{size}_consec_{consec}"] = round(
                    volume / ms, 3)
            size //= 2
        return out

    def profile_p2p_bandwidth(self, message_mb: int = 64) -> Dict[str, float]:
        """p2p_bandwidth_*.json: MB/ms per pipeline degree
        (profile_p2p.py:19)."""
        out: Dict[str, float] = {}
        pp = 2
        while pp <= min(self.world, self.args.max_pp_deg):
            group = _group_devices(self.devices, pp, True, self.world)
            ms = self._collective_ms("p2p", group, message_mb)
            out[f"pp_size_{pp}"] = round(message_mb / ms, 3)
            pp *= 2
        return out

    def _sub_mb_sizes(self) -> List[float]:
        """Sub-MB message sizes (MB) for the α (latency) fit: halvings of
        start_mb down to sub_mb_floor_kb. Layer-wise TP puts per-collective
        messages well under a megabyte, where the latency term dominates
        ("Revisiting the Time Cost Model of AllReduce", PAPERS.md) — the
        integer-MB sweep alone cannot see it."""
        out: List[float] = []
        kb = self.args.start_mb * 1024 // 2
        while kb >= self.args.sub_mb_floor_kb:
            out.append(kb / 1024.0)
            kb //= 2
        return sorted(out)

    def profile_sp_time(self) -> Dict[str, float]:
        """sp_time_*.json: all-reduce + all-to-all latency (ms) per group
        size per message size in MB (profile_allreduce.py latency mode +
        profile_all2all.py), plus sub-MB all-reduce points under the
        ``sub_`` prefix (KB-keyed; invisible to the legacy remap parsers,
        consumed by :meth:`profile_alpha_beta`'s α-β fit)."""
        out: Dict[str, float] = {}
        sizes = []
        mb = self.args.start_mb
        while mb <= self.args.end_mb:
            sizes.append(mb)
            mb *= self.args.scale
        size = self.world
        while size >= 2:
            group = _group_devices(self.devices, size, True, self.world)
            for mb in sizes:
                out[f"allreduce_size_{size}_{mb}MB_time"] = \
                    self._collective_ms("allreduce", group, mb)
            for mb in sizes:
                out[f"all2all_size_{size}_{mb}MB_time"] = \
                    self._collective_ms("all2all", group, mb)
            for mb in self._sub_mb_sizes():
                kb = int(round(mb * 1024))
                out[f"sub_allreduce_size_{size}_{kb}KB_time"] = \
                    self._collective_ms("allreduce", group, mb)
            size //= 2
        return out

    def profile_alpha_beta(self, sp_times: Optional[Dict[str, float]] = None
                           ) -> Dict[str, float]:
        """Latency-aware collective fit: per (group size, consecutiveness),
        fit the allreduce time curve ``t(size) = α + size / β`` over the
        sub-MB + integer-MB points and emit ``allreduce_size_{n}_consec_
        {c}_alpha_ms`` / ``..._beta_mb_per_ms`` keys (merged into the
        bandwidth JSON alongside the legacy keys — profiles.read_alpha_beta
        parses them, legacy readers ignore them). Consecutive groups reuse
        ``sp_times`` measurements when provided; non-consecutive (strided)
        groups are measured here."""
        fit_sizes = self._sub_mb_sizes() + [float(self.args.start_mb),
                                            float(self.args.start_mb * 2),
                                            float(self.args.start_mb * 4)]
        out: Dict[str, float] = {}
        size = self.world
        while size >= 2:
            for consec in ([1] if size == self.world else [1, 0]):
                xs, ys = [], []
                group = _group_devices(self.devices, size, bool(consec),
                                       self.world)
                for mb in fit_sizes:
                    t = None
                    if consec and sp_times is not None:
                        if mb < 1:
                            t = sp_times.get(
                                f"sub_allreduce_size_{size}_"
                                f"{int(round(mb * 1024))}KB_time")
                        else:
                            t = sp_times.get(
                                f"allreduce_size_{size}_{int(mb)}MB_time")
                    if t is None:
                        t = self._collective_ms("allreduce", group, mb)
                    xs.append(mb)
                    ys.append(t)
                pair = fit_alpha_beta(
                    xs, ys,
                    label=f"allreduce_size_{size}_consec_{consec}")
                if pair is None:
                    # degenerate slope: no pair is written, so the cost
                    # model keeps pricing this (size, consec) off the
                    # legacy single-point bandwidth / latency tables
                    continue
                alpha, beta = pair
                out[f"allreduce_size_{size}_consec_{consec}_alpha_ms"] = \
                    round(alpha, 6)
                out[f"allreduce_size_{size}_consec_{consec}_beta_mb_per_ms"] \
                    = round(beta, 3)
            size //= 2
        return out

    # -- per-algorithm schedules (ring vs recursive halving-doubling) -------

    def _algo_allreduce_ms(self, alg: str, group: List,
                           message_mb: float) -> float:
        """Time one all-reduce of ``message_mb`` MB/device over ``group``
        running an EXPLICIT algorithm-shaped schedule instead of whatever
        the runtime lowers psum to:

        * ``ring`` — reduce-scatter then all-gather rings: 2(n-1) hops of
          1/n-sized chunks (`lax.ppermute`), the bandwidth-optimal,
          latency-poor shape.
        * ``tree`` — recursive halving-doubling: log2(n) pairwise
          exchange rounds with halving payloads then the doubling gather
          back — 2·log2(n) hops, the latency-optimal shape for small
          messages ("Revisiting the Time Cost Model of AllReduce").

        The two schedules have materially different (α, β) regimes; the
        fitted pairs let the cost model price each collective as the MIN
        over algorithms at its message size and level
        (:func:`_allreduce_body`)."""
        n = len(group)
        if n < 2 or (n & (n - 1)):
            raise ValueError(f"algorithm schedules need a power-of-two "
                             f"group, got {n}")
        mesh = Mesh(np.array(group), (_G_AXIS,))
        elems = max(int(message_mb * 1024 * 1024 // 4), 2 * n)
        elems = (elems // (2 * n)) * (2 * n)
        x = jax.device_put(jnp.ones((elems,), jnp.float32),
                           NamedSharding(mesh, P(None)))
        from hetu_galvatron_tpu.ops.pallas.common import on_shards

        body = _allreduce_body(alg, n, _G_AXIS)
        fn = jax.jit(on_shards(body, mesh, P(None), P(None)))
        return _time_fn(fn, x, warmup=self.args.warmup_iters,
                        iters=self.args.profile_iters)

    def profile_alpha_beta_algos(self) -> Dict[str, float]:
        """Per-algorithm, per-LEVEL latency-bandwidth fits: for each group
        size, each algorithm schedule (ring / tree) is benchmarked over an
        intra-host/ICI group (adjacent devices, ``consec=1``, level
        ``ici``) and a cross-slice/DCN proxy group (maximally strided,
        ``consec=0``, level ``dcn`` — the grouping
        ``mesh.dcn_factor_shape`` puts across slices), and the
        ``t = α + size/β`` curve is fitted over the sub-MB + integer-MB
        sweep. Emitted keys extend the flat :meth:`profile_alpha_beta`
        namespace::

            allreduce_size_{n}_consec_{c}_alg_{ring|tree}_lvl_{ici|dcn}_
            alpha_ms / ..._beta_mb_per_ms

        ``profiles.read_alpha_beta_algos`` parses them; the flat reader
        and every legacy parser skip them. Degenerate fits are dropped
        with a warning (:func:`fit_alpha_beta`), falling back per
        (size, algorithm, level) to whatever coarser model remains.

        The ``dcn`` level's group is TRUE multi-host when the job spans
        processes (one device per process round-robin,
        :func:`_dcn_group_devices` — every hop crosses the DCN seam);
        single-process runs keep the strided intra-host proxy with a
        warning, and the emitted ``dcn_level_source`` metadata key
        records which one measured the curves ("multihost" |
        "proxy-strided") so a fitted JSON can never silently pass a
        proxy off as a fleet measurement. Legacy parsers skip the
        non-``allreduce_size_`` key."""
        fit_sizes = self._sub_mb_sizes() + [float(self.args.start_mb),
                                            float(self.args.start_mb * 2),
                                            float(self.args.start_mb * 4)]
        out: Dict[str, float] = {}
        dcn_source: Optional[str] = None
        size = self.world
        while size >= 2:
            levels = [("ici", 1)]
            if size < self.world:
                levels.append(("dcn", 0))
            for lvl, consec in levels:
                if lvl == "dcn":
                    group, src = _dcn_group_devices(self.devices, size,
                                                    self.world)
                    dcn_source = dcn_source or src
                else:
                    group = _group_devices(self.devices, size, bool(consec),
                                           self.world)
                for alg in ("ring", "tree"):
                    xs, ys = [], []
                    for mb in fit_sizes:
                        xs.append(mb)
                        ys.append(self._algo_allreduce_ms(alg, group, mb))
                    key = (f"allreduce_size_{size}_consec_{consec}"
                           f"_alg_{alg}_lvl_{lvl}")
                    pair = fit_alpha_beta(xs, ys, label=key)
                    if pair is None:
                        continue
                    alpha, beta = pair
                    out[f"{key}_alpha_ms"] = round(alpha, 6)
                    out[f"{key}_beta_mb_per_ms"] = round(beta, 3)
            size //= 2
        if dcn_source is not None:
            out["dcn_level_source"] = dcn_source
        return out

    def profile_overlap_coefficient(self, message_mb: int = 64) -> Dict:
        """overlap_coefficient.json: slowdown of compute when a collective
        runs concurrently (reference profile_overlap.py:10-60 measures with
        separate CUDA streams; here one jitted program interleaves a matmul
        chain with psums and XLA overlaps them on the TPU's async fabric)."""
        n = self.world
        if n < 2:
            return {"overlap_coe": 1.0}
        mesh = Mesh(np.array(self.devices[:n]), (_G_AXIS,))
        k = 1024
        a = jax.device_put(jnp.ones((k, k), jnp.bfloat16),
                           NamedSharding(mesh, P(None, None)))
        elems = int(message_mb * 1024 * 1024 // 4)
        x = jax.device_put(jnp.ones((elems,), jnp.float32),
                           NamedSharding(mesh, P(None)))
        from hetu_galvatron_tpu.ops.pallas.common import on_shards

        def compute_only(m):
            for _ in range(8):
                m = jnp.tanh(m @ m)
            return m

        def both(m, v):
            v = jax.lax.psum(v, _G_AXIS)
            return compute_only(m), v

        both = on_shards(both, mesh, (P(None, None), P(None)),
                         (P(None, None), P(None)))

        t_comp = _time_fn(jax.jit(compute_only), a,
                          warmup=self.args.warmup_iters,
                          iters=self.args.profile_iters)
        comm_fn = jax.jit(on_shards(lambda v: jax.lax.psum(v, _G_AXIS), mesh,
                                    P(None), P(None)))
        t_comm = _time_fn(comm_fn, x, warmup=self.args.warmup_iters,
                          iters=self.args.profile_iters)
        jboth = jax.jit(lambda m, v: both(m, v))
        for _ in range(self.args.warmup_iters):
            out = jboth(a, x)
        jax.block_until_ready(out)
        samples = []
        for _ in range(self.args.profile_iters):
            t0 = time.perf_counter()
            out = jboth(a, x)
            jax.block_until_ready(out)
            samples.append((time.perf_counter() - t0) * 1000.0)
        t_both = float(np.median(samples))
        overlap = max(t_both / max(max(t_comp, t_comm), 1e-9), 1.0)
        return {"overlap_coe": round(overlap, 4)}

    # -- output -------------------------------------------------------------

    def run_all(self, output_dir: Optional[str] = None) -> Dict[str, str]:
        """Run every benchmark and write the four hardware_configs JSONs
        (reference generate_script outputs, hardware_profiler.py:39-155)."""
        a = self.args
        out_dir = output_dir or a.output_dir
        tag = f"{a.num_nodes}nodes_{a.num_devices_per_node}gpus_per_node"
        sp_times = self.profile_sp_time()
        bandwidth = self.profile_allreduce_bandwidth()
        # α-β pairs ride the bandwidth JSON next to the legacy keys
        bandwidth.update(self.profile_alpha_beta(sp_times))
        if a.profile_algos:
            # per-algorithm / per-level pairs (ring vs halving-doubling,
            # ICI vs DCN-proxy groups) extend the same namespace
            bandwidth.update(self.profile_alpha_beta_algos())
        paths = {}
        for name, cfg in [
            (f"allreduce_bandwidth_{tag}.json", bandwidth),
            (f"p2p_bandwidth_{tag}.json", self.profile_p2p_bandwidth()),
            (f"sp_time_{tag}.json", sp_times),
            ("overlap_coefficient.json", self.profile_overlap_coefficient()),
        ]:
            path = os.path.join(out_dir, name)
            write_json(cfg, path)
            paths[name] = path
        return paths
