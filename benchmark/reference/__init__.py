"""Plain references, one file a family, found by name.

``reference.family`` of a configuration's file names
``benchmark/reference/<family>.py``. That file is written from the
architecture's published description in straightforward ``jax.numpy``
(the shared pieces are in ``plain.py``), is fed weights under their public
Hugging Face names, so it does not know the program's parameter tree, and
exports two functions, and a third where its blocks differ:

``nll_sum(w, cfg, tokens, labels, *, layers=None)``
    the sum of the token negative log-likelihoods of ``tokens`` ->
    ``labels`` ([B, S] each) under the weights ``w``; ``cfg`` is the
    configuration's file, ``layers`` runs only the first so many blocks.

``forward_flops_per_token(sizes, config)``
    the matmul operations one token's forward pass requires, from
    ``flops.Sizes`` and the configuration's file, by the rules of
    ``benchmark/flops.py`` (matmuls only, attention causal, no
    recomputation); the harness multiplies by three for training.

``attention_blocks(config)``, optional
    one entry for each block of the configuration AS IT IS RUN that
    attends, in order; a block that does not (a convolution, a scan) has
    none. An entry is a dict that may hold ``window`` (the keys a query
    meets at most, itself included; absent or 0 = the whole causal span)
    and, where they differ from the model's, ``heads``, ``kv_heads``,
    ``qk_head_dim`` and ``v_head_dim`` (``flops.Attention``). The harness
    puts them into ``flops.Sizes.attention``, which ``flash_step_cost`` and
    any cost function beside a metric sum over, and which the family's own
    ``forward_flops_per_token`` adds up with
    ``flops.attention_flops_per_token(sizes, entry)``. A family without the
    export attends in every block, over the whole causal span, at the
    model's sizes.

A new architecture adds its file here and edits nothing.

TOLERANCE. The program computes in bfloat16 with float32 accumulation,
norms, softmax and loss; ``mean_loss`` computes in float32 throughout. Each
configuration's file states ``reference.loss_tolerance``, the allowed
|program's step-0 loss - reference loss|, and PERF.md says how it was set
from chip runs (the bf16 error of the mean loss over a batch of thousands
of tokens against what a dropped block or a wrong precision moves it).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Mapping, Optional

import jax
import jax.numpy as jnp

from benchmark import manifest

EXPORTS = ("nll_sum", "forward_flops_per_token")


def load_family(family: str, root: str = manifest.ROOT) -> Any:
    """The module ``benchmark/reference/<family>.py`` under ``root``."""
    path = manifest.family_path(root, family)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"reference family {family!r}: no {path}")
    mod = manifest.load_python(path)
    missing = [n for n in EXPORTS if not callable(getattr(mod, n, None))]
    if missing:
        raise AttributeError(f"reference family {family!r}: {path} does "
                             f"not export {', '.join(missing)}")
    return mod


def mean_loss(family: str, weights: Mapping[str, Any], cfg: Mapping,
              tokens, labels, *, root: str = manifest.ROOT,
              rows_per_call: int = 1, dtype=jnp.float32,
              layers: Optional[int] = None) -> float:
    """Mean token cross-entropy of ``tokens`` -> ``labels`` ([B, S] each)
    by the family's ``nll_sum``, in float32 under
    ``jax.default_matmul_precision("highest")``, computed ``rows_per_call``
    sequences at a time so that the logits of a whole batch never have to
    exist. ``dtype`` and ``layers`` are there to show that the comparison
    fails when it should (a lower precision, a dropped block); a real check
    leaves them alone."""
    fn = load_family(family, root).nll_sum
    w: Dict[str, jax.Array] = {k: jnp.asarray(v, dtype)
                               for k, v in weights.items()}
    precision = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def nll_sum(w, t, l):
        with jax.default_matmul_precision(precision):
            return fn(w, cfg, t, l, layers=layers).astype(jnp.float32)

    total = 0.0
    for i in range(0, tokens.shape[0], rows_per_call):
        total += float(nll_sum(w, jnp.asarray(tokens[i:i + rows_per_call]),
                               jnp.asarray(labels[i:i + rows_per_call])))
    return total / labels.size
