#!/usr/bin/env python3
"""Show, by hand, that the reference comparison fails when it should.

    python3 benchmark/reference_variants.py --workload <name> --seed <n>

Prints the plain reference's step-0 loss for the cell as it is, with one
block fewer, and computed in bfloat16 instead of float32. The distance of
the last two from the first is what ``reference.loss_tolerance`` in the
configuration's file has to stay under; the program's own step-0 loss (the
``reference loss ... step 0 loss ...`` line of a run) has to stay inside
it. Runs on whatever device JAX shows; no timing is taken.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def depth_key(config) -> str:
    """The key of the configuration's file that holds its depth:
    ``reference.depth_key``, or the only key of ``reduced_from``."""
    named = config["reference"].get("depth_key")
    if named:
        return named
    cut = list(config.get("reduced_from", {}))
    if len(cut) != 1:
        raise SystemExit(
            f"reduced_from has the keys {cut}: name the one that is the "
            "depth as reference.depth_key in the configuration's file")
    return cut[0]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()
    import jax.numpy as jnp

    from benchmark import check, manifest, reference

    cell = manifest.resolve_cell(manifest.load_manifest(), a.workload)
    argv = manifest.train_argv(cell, a.seed)
    weights, batch = check.first_batch(argv)
    tokens, labels = batch.pop("tokens"), batch.pop("labels")
    family = cell.config["reference"]["family"]
    depth = cell.config[depth_key(cell.config)]
    out = {"cell": cell.name, "seed": a.seed}
    for name, kw in (("as_published", {}),
                     ("one_block_fewer", {"layers": depth - 1}),
                     ("bfloat16", {"dtype": jnp.bfloat16})):
        out[name] = reference.mean_loss(family, weights, cell.config,
                                        tokens, labels, batch=batch, **kw)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
