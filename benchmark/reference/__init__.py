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

``nll_sum(w, cfg, tokens, labels, *, layers=None, batch=None)``
    a family whose batches hold more than ids names the keyword ``batch``
    and gets every other field of the program's first batch there, the same
    rows of each (``segment_ids``, ``position_ids``, an image's patches and
    grids, ``loss_mask``). It uses them as the published description says
    (masks across documents, restarts the positions, runs its tower) and
    sums the loss over the positions ``batch["loss_mask"]`` marks, weighted
    by it; ``mean_loss`` divides by the mask's sum. A batch that holds a
    field beyond ``tokens``, ``labels`` and a ``loss_mask`` of ones under a
    family that does not name the keyword stops the comparison
    (``beyond_ids``): nothing is dropped in silence.

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
    model's sizes. A block of a second stack (a tower in front of the
    decoder) is an entry too, with ``positions`` (of one sequence as that
    block sees them), ``pairs`` (the (query, key) pairs of one sequence that
    its mask leaves; absent or 0 = causal over its positions) and ``hidden``
    (the width its projections read and write), from the configuration's own
    keys; the configuration's file states that stack's depth under the key
    ``reference.second_stack_depth_key`` names, tied to the program through
    ``program.equals``, and the entries may then be that many more.

A new architecture adds its file here and edits nothing.

TOLERANCE. The program computes in bfloat16 with float32 accumulation,
norms, softmax and loss; ``mean_loss`` computes in float32 throughout. Each
configuration's file states ``reference.loss_tolerance``, the allowed
|program's step-0 loss - reference loss|, and PERF.md says how it was set
from chip runs (the bf16 error of the mean loss over a batch of thousands
of tokens against what a dropped block or a wrong precision moves it).
"""

from __future__ import annotations

import inspect
import os
from typing import Any, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

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


def beyond_ids(batch: Optional[Mapping[str, Any]]) -> Dict[str, Any]:
    """The fields of a first batch without its ``tokens`` and ``labels``
    that say something: all but a ``loss_mask`` of ones."""
    return {k: v for k, v in (batch or {}).items()
            if k != "loss_mask" or not np.all(np.asarray(v) == 1)}


def mean_loss(family: str, weights: Mapping[str, Any], cfg: Mapping,
              tokens, labels, *, batch: Optional[Mapping[str, Any]] = None,
              root: str = manifest.ROOT, rows_per_call: int = 1,
              dtype=jnp.float32, layers: Optional[int] = None) -> float:
    """Mean token cross-entropy of ``tokens`` -> ``labels`` ([B, S] each)
    by the family's ``nll_sum``, in float32 under
    ``jax.default_matmul_precision("highest")``, computed ``rows_per_call``
    sequences at a time so that the logits of a whole batch never have to
    exist. ``batch`` holds the other fields of the program's first batch,
    for a family whose ``nll_sum`` names that keyword (for any other an
    error, unless they say nothing: ``beyond_ids``): each whose leading
    length is the batch's rows is cut to the same rows, and the mean is
    over the positions ``loss_mask`` marks where that is not all ones.
    ``dtype`` and ``layers`` are there to show that the comparison fails
    when it should (a lower precision, a dropped block); a real check
    leaves them alone."""
    fn = load_family(family, root).nll_sum
    takes_batch = "batch" in inspect.signature(fn).parameters
    rest = dict(batch or {}) if takes_batch else {}
    unread = {} if takes_batch else beyond_ids(batch)
    if unread:
        raise ValueError(
            f"the program's first batch holds {sorted(unread)} "
            f"beside tokens and labels, and reference family {family!r} "
            "takes ids alone: its nll_sum has to name the keyword `batch` "
            "and use these fields (benchmark/reference/__init__.py), as "
            "the program trained on them")
    w: Dict[str, jax.Array] = {k: jnp.asarray(v, dtype)
                               for k, v in weights.items()}
    precision = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def nll_sum(w, t, l, rest):
        kw = {"batch": rest} if takes_batch else {}
        with jax.default_matmul_precision(precision):
            return fn(w, cfg, t, l, layers=layers, **kw).astype(jnp.float32)

    rows = tokens.shape[0]
    total = 0.0
    for i in range(0, rows, rows_per_call):
        cut = {k: jnp.asarray(v[i:i + rows_per_call]
                              if np.ndim(v) and np.shape(v)[0] == rows else v)
               for k, v in rest.items()}
        total += float(nll_sum(w, jnp.asarray(tokens[i:i + rows_per_call]),
                               jnp.asarray(labels[i:i + rows_per_call]), cut))
    return total / loss_positions(labels, batch)


def loss_positions(labels, batch: Optional[Mapping[str, Any]]):
    """What the summed loss is divided by: every position where the
    batch's ``loss_mask`` is all ones or not there, else the mask's sum."""
    mask = beyond_ids(batch).get("loss_mask")
    if mask is None:
        return labels.size
    return float(np.asarray(mask, np.float64).sum())
