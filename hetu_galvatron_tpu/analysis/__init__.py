"""Static analysis suite: plan doctor, collective census, AST lint,
memory doctor, sharding-flow analysis.

Five passes that run on CPU with no devices and no training step, so a
malformed, inexpressible, OOM-bound or byte-wasting plan is caught
BEFORE any TPU time is burned
(``python -m hetu_galvatron_tpu.cli.check``):

* :mod:`~hetu_galvatron_tpu.analysis.eligibility` — the ONE home of every
  plan-eligibility predicate (compiled-schedule expressibility, per-layer
  tp_overlap eligibility, plan-structure divisibility checks). The runtime
  engines, the launcher's fallback logging, and the cost model's
  expressibility gates all import from here, so they can never drift.
* :mod:`~hetu_galvatron_tpu.analysis.plan_doctor` — Pass 1: statically
  reports, per layer, which engine/kernels a plan will get and why, with
  actionable errors for malformed plan JSONs.
* :mod:`~hetu_galvatron_tpu.analysis.census` — Pass 2: trace the hot-path
  programs with ``jax.make_jaxpr`` and count their collectives (recursing
  into jit/shard_map/scan subjaxprs), verify trace-marker coverage, and
  cross-check against the plan's predicted collective counts.
* :mod:`~hetu_galvatron_tpu.analysis.lint` — Pass 3: stdlib-``ast`` lint
  passes (host sync in hot paths, jit-in-loop, mesh-axis canon, dynamic
  named_scope, bare except, env reads outside the schema) with a
  committed baseline so the CI gate is zero-NEW-findings.
* :mod:`~hetu_galvatron_tpu.analysis.memory_doctor` — Pass 4: static
  per-device peak-HBM accounting (model states / activations / compiled
  stage buffer / vocab replication / serving KV pool) cross-checked per
  component against the search engine's memory cost model, with an
  ``--hbm-gb`` budget gate the search engine prunes with too.
* :mod:`~hetu_galvatron_tpu.analysis.sharding_flow` — Pass 5: the census
  extended from counts to BYTES (exact cross-check against
  ``telemetry.plan_collective_bytes``), reshard detection and the
  donation audit, plus the slow-tier partition-time HLO collective walk.
"""

from hetu_galvatron_tpu.analysis.eligibility import (  # noqa: F401
    compiled_schedule_unsupported_reason,
    compiled_unsupported_reason,
    layer_overlap_reason,
    overlap_unsupported_reason,
    plan_overlap_reasons,
    plan_structure_reasons,
    search_compiled_expressible,
    search_tp_overlap_expressible,
)
