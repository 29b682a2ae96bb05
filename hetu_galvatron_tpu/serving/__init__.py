"""Inference serving subsystem: paged KV cache, continuous batching, engine.

The training stack stops at offline fixed-batch decode
(``models/generate.py``); this package adds the online-serving workload the
ROADMAP's "heavy traffic" north star implies:

* ``kv_cache.py`` — a paged KV cache: fixed-size blocks in a preallocated
  pool with a per-sequence block table (vLLM's PagedAttention layout,
  expressed as gather/scatter over jax arrays so the whole decode step
  stays one jitted program).
* ``scheduler.py`` — a continuous-batching scheduler: FIFO admission under
  a KV-block + prefill-FLOPs budget (``cost_model/cost.py`` accounting),
  per-sequence EOS/length/timeout retirement, slot recycling at a fixed
  jitted batch shape.
* ``prefix_cache.py`` — the shared-prefix radix cache: block-granular
  radix tree keyed on token ids over the same pool, refcount-shared
  blocks (a cached prompt prefix skips its prefill copy-free), LRU
  eviction over unpinned nodes.
* ``spec_decode.py`` — lossless speculative decoding: pluggable drafts
  (n-gram prompt-lookup, small draft model) verified in one batched
  fixed-shape pass; greedy streams stay bit-identical to plain decode.
* ``engine.py`` — the serving engine: jitted paged prefill/decode (+
  prefix-prefill and speculative-verify) programs (plan-aware GSPMD
  sharding when given a mesh + HybridParallelConfig), per-request token
  streams, cancellation, timeouts, and serving telemetry wired into
  ``observability/``.

Front end: ``cli/serve.py`` (file/stdin request streams).
"""

from hetu_galvatron_tpu.serving.engine import ServingEngine
from hetu_galvatron_tpu.serving.kv_cache import (
    BlockAccountingError,
    BlockAllocator,
    PagedKVCache,
)
from hetu_galvatron_tpu.serving.prefix_cache import PrefixCache
from hetu_galvatron_tpu.serving.scheduler import (
    Request,
    RequestHandle,
    Scheduler,
)
from hetu_galvatron_tpu.serving.spec_decode import (
    ModelDraft,
    NgramDraft,
)

__all__ = [
    "BlockAccountingError",
    "BlockAllocator",
    "ModelDraft",
    "NgramDraft",
    "PagedKVCache",
    "PrefixCache",
    "Request",
    "RequestHandle",
    "Scheduler",
    "ServingEngine",
]
