#!/usr/bin/env python3
"""The controls of ``mellum2-12b-a2.5b-p1`` that ``reference_variants.py``
cannot make: the reference with ONE CHIP'S EXPERTS LEFT OUT.

    python3 benchmark/mellum_controls.py --workload mellum2_c4_ep4 --seed <n> [--seed <m> ...]

For each seed prints the plain reference's step-0 loss for the cell as it
is, with one block fewer, computed in bfloat16, and with the sixteen experts
of each chip of the ep4 group left out of every block in turn (what a lost
exchange, a wrong ``first`` or a chip's zeroed partial result would train
on). The share cells could not have this control: there the absent experts
are absent on both sides. The distance of each from the first is what
``reference.loss_tolerance`` of the configuration's file is held against.
Runs on whatever device JAX shows (one chip holds the reference: it is not
sharded); no timing is taken.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--chips", type=int, default=4,
                    help="the ep degree whose shares are left out in turn")
    a = ap.parse_args()
    import jax.numpy as jnp

    from benchmark import check, manifest, reference
    from benchmark.reference_variants import depth_key

    cell = manifest.resolve_cell(manifest.load_manifest(), a.workload)
    family = cell.config["reference"]["family"]
    depth = cell.config[depth_key(cell.config)]
    held = cell.config["num_experts"] // a.chips
    for seed in a.seed:
        argv = manifest.train_argv(cell, seed)
        weights, tokens, labels = check.first_batch_and_weights(argv)
        out = {"cell": cell.name, "seed": seed}
        variants = [("as_published", cell.config, {}),
                    ("one_block_fewer", cell.config, {"layers": depth - 1}),
                    ("bfloat16", cell.config, {"dtype": jnp.bfloat16})]
        variants += [
            (f"chip_{r}_experts_left_out",
             {**cell.config,
              "experts_left_out": tuple(range(r * held, (r + 1) * held))}, {})
            for r in range(a.chips)]
        for name, config, kw in variants:
            out[name] = reference.mean_loss(family, weights, config, tokens,
                                            labels, **kw)
            print(json.dumps(out), flush=True)
        del weights
    return 0


if __name__ == "__main__":
    sys.exit(main())
