"""Generate spec-derived TPU v5e hardware tables for the search engine.

The hardware profiler (core/profiler/hardware_profiler.py) measures these
tables on a live multi-chip mesh; until someone runs it on one (ROADMAP
speed item 3), this tool fills the gap with tables DERIVED FROM PUBLIC v5e
SPECS so the search engine can plan for a v5e pod slice instead of the
reference's A100/NCCL fixtures (tests/fixtures/*). Every value is estimated
from first principles and labeled as such in the JSON (`"source"` key);
whenever a real multi-chip mesh is available, run
``python -m hetu_galvatron_tpu.cli.profiler <cfg> mode=profile_hardware``
and the measured tables take the same schema and path layout.

Model (documented assumptions, not measurements):
- v5e ICI: 2D torus, per-link one-way bandwidth ~45 GB/s (= 45 MB/ms); each
  torus axis has two directed links per chip (one per direction).
- Ring all-reduce over one axis of n chips: each directed link carries
  (n-1)/n of the buffer, both directions used in parallel =>
  t = M * (n-1)/n / B_uni; effective "bandwidth" M/t = B_uni * n/(n-1).
- Consecutive vs non-consecutive groups: wormhole routing keeps per-link
  bandwidth flat within a slice; the non-consec value is derated 10% for
  the longer average path (the A100 fixture's consec/non-consec distinction
  is an NVLink-vs-PCIe artifact with no v5e equivalent).
- P2P (pipeline stage boundary, one neighbor): one directed link => 45 MB/ms
  regardless of pp degree (the reference's degradation with pp is an NVLink
  topology artifact).
- All-to-all over a bidirectional ring of n chips: per-chip shard M, average
  hop distance n/4, two directed links => t ~= M * n / (8 * B_uni).
- Overlap slowdown: TPUs run collectives on a dedicated async fabric, but
  HBM contention still slows concurrent compute; 1.1 is a conservative
  placeholder between "no slowdown" (1.0) and the A100-measured 1.1256.
"""

from __future__ import annotations

import json
import os
import sys

B_UNI = 45.0  # MB/ms one-way per ICI link (public v5e spec, ~45 GB/s)


def allreduce_bandwidth(n: int) -> float:
    return round(B_UNI * n / (n - 1), 3)


def allreduce_time_ms(mb: float, n: int) -> float:
    return mb * (n - 1) / n / B_UNI


def all2all_time_ms(mb: float, n: int) -> float:
    return mb * n / (8.0 * B_UNI)


def make_tables(world: int = 8):
    source = ("spec-derived estimate (tools/make_v5e_hw_config.py); "
              "not measured — single-chip environment")
    ar = {"source": source}
    n = world
    while n >= 2:
        ar[f"allreduce_size_{n}_consec_1"] = allreduce_bandwidth(n)
        ar[f"allreduce_size_{n}_consec_0"] = round(
            allreduce_bandwidth(n) * 0.9, 3)
        n //= 2
    p2p = {"source": source}
    pp = 2
    while pp <= world:
        p2p[f"pp_size_{pp}"] = B_UNI
        pp *= 2
    sp = {"source": source}
    size = 2
    while size <= world:
        mb = 1
        while mb <= 512:
            sp[f"allreduce_size_{size}_{mb}MB_time"] = round(
                allreduce_time_ms(mb, size), 4)
            sp[f"all2all_size_{size}_{mb}MB_time"] = round(
                all2all_time_ms(mb, size), 4)
            mb *= 2
        size *= 2
    overlap = {"overlap_coe": 1.1, "source": source}
    return ar, p2p, sp, overlap


def main(out_dir: str, world: int = 8) -> int:
    ar, p2p, sp, overlap = make_tables(world)
    os.makedirs(out_dir, exist_ok=True)
    tag = f"1nodes_{world}gpus_per_node"
    for name, payload in [
        (f"allreduce_bandwidth_{tag}.json", ar),
        (f"p2p_bandwidth_{tag}.json", p2p),
        (f"sp_time_{tag}.json", sp),
        ("overlap_coefficient.json", overlap),
    ]:
        path = os.path.join(out_dir, name)
        with open(path, "w") as f:
            json.dump(payload, f, indent=4)
        print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else (
        "hetu_galvatron_tpu/profiles/tpu_v5e/hardware")
    world = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    sys.exit(main(out, world))
