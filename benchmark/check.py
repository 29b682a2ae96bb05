"""What decides ``correct``: the run's own facts and the plain reference.

``correct`` is true only when all of these hold:

1. the run ended the way the harness ended it (exit code 18 after the
   harness's own SIGTERM) and no step raised;
2. every loss is finite;
3. the loss of step 0 agrees with the configuration's reference family
   (``benchmark/reference/<family>.py``) on the same weights and the same
   first global batch, every field of it, over the positions its
   ``loss_mask`` marks, within the tolerance the configuration's file
   states;
4. every layer ran on an attention core the configuration's file allows
   (``program.expects.attention_cores``; the flash core alone where it says
   nothing) and the compiled step holds at least
   ``program.expects.mosaic_calls_per_layer`` Mosaic custom calls a layer
   (3 where it says nothing: forward, dq, dk/dv), so a run on the XLA core
   can never pass for a kernel run;
5. nothing compiled and no cache entry was written inside the window;
6. the mean loss of the last five measured steps (or as many as the window
   has) is not above that of as many first steps by more than ``LOSS_RISE_SLACK`` (on random tokens the
   loss only drifts towards ln of the vocabulary, and every step sees other
   sequences, so this catches an update that blows up, nothing finer).
"""

from __future__ import annotations

import hashlib
import json
import math
import os
from typing import Any, Dict, List, Mapping, Optional

from benchmark.window import EXIT_PREEMPTED

# sequences per reference call: bounds the logits and the attention scores
# that exist at once ([rows, heads, S, S] float32)
REFERENCE_ROWS_PER_CALL = 1
# nats. Batch-to-batch noise of the mean loss is about 0.01 at thousands of
# tokens a step (PR 21's smoke losses); an update that blows up moves it by
# tenths or makes it infinite
LOSS_RISE_SLACK = 0.05
# what ``program.expects`` of a configuration's file defaults to: every layer
# on the flash core, whose forward, dq and dk/dv kernels are three Mosaic
# calls a layer
DEFAULT_EXPECTS = {"attention_cores": ["flash"], "mosaic_calls_per_layer": 3}


def _code_hash(root: str) -> str:
    """Hash of every .py under the program and the reference: a cached
    reference result is good only for the code that produced it."""
    h = hashlib.sha256()
    for top in ("hetu_galvatron_tpu", os.path.join("benchmark", "reference")):
        for d, dirs, files in os.walk(os.path.join(root, top)):
            dirs.sort()
            for f in sorted(files):
                if f.endswith(".py"):
                    p = os.path.join(d, f)
                    h.update(os.path.relpath(p, root).encode())
                    with open(p, "rb") as fh:
                        h.update(fh.read())
    return h.hexdigest()[:16]


def first_batch(argv: List[str]):
    """The program's own weights for this seed under their public Hugging
    Face names (through its public exporter), and the first global batch of
    its dataset as ``get_data_iterator`` yields it, every field. Nothing
    else is taken from the program."""
    import jax
    import numpy as np

    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.models.builder import init_causal_lm
    from hetu_galvatron_tpu.runtime.checkpoint import params_to_hf
    from hetu_galvatron_tpu.runtime.dataloader import get_data_iterator
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    args = resolve_model_config(args_from_cli(argv, mode="train_dist"))
    cfg = args.model
    params = jax.jit(lambda k: init_causal_lm(k, cfg)[0])(
        jax.random.key(args.train.seed))
    weights = params_to_hf(params, cfg)
    if cfg.padded_vocab_size > cfg.vocab_size:
        # the exporter drops the vocabulary's padding rows; the program's
        # tokens and softmax include them, so the reference gets them too
        weights["extra_vocab_rows"] = np.asarray(
            params["embed"]["wte"][cfg.vocab_size:])
    del params
    return weights, dict(next(get_data_iterator(args)))


def first_batch_and_weights(argv: List[str]):
    """``first_batch`` for a comparison on ids alone: the weights, the
    tokens and the labels, and an error where the batch holds another field
    that says something."""
    from benchmark import reference

    weights, batch = first_batch(argv)
    tokens, labels = batch.pop("tokens"), batch.pop("labels")
    more = reference.beyond_ids(batch)
    if more:
        raise ValueError(f"the first batch holds {sorted(more)} beside "
                         "tokens and labels: take check.first_batch")
    return weights, tokens, labels


def reference_loss(cell, argv: List[str], seed: int, root: str,
                   out_dir: str, **variant) -> Dict[str, Any]:
    """The reference's step-0 loss for this cell and seed, and the positions
    it is the mean over, from the file kept under ``out_dir`` when the code
    has not changed since."""
    from benchmark import reference

    key = f"{cell.name}.seed{seed}.{_code_hash(root)}"
    path = os.path.join(out_dir, "reference", key + ".json")
    if not variant and os.path.isfile(path):
        with open(path) as f:
            return {**json.load(f), "cached": True}
    weights, batch = first_batch(argv)
    tokens, labels = batch.pop("tokens"), batch.pop("labels")
    loss = reference.mean_loss(
        cell.config["reference"]["family"], weights, cell.config,
        tokens, labels, batch=batch, root=root,
        rows_per_call=REFERENCE_ROWS_PER_CALL, **variant)
    res = {"loss": loss,
           "tokens": int(reference.loss_positions(labels, batch))}
    if not variant:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(res, f)
    return {**res, "cached": False}


def judge(facts: Dict[str, Any], *, reference: Optional[float],
          tolerance: float, expect_mosaic: bool = True,
          expects: Optional[Mapping[str, Any]] = None) -> Dict[str, Any]:
    """Each condition of ``correct`` by name, and their conjunction.
    ``expects`` is ``program.expects`` of the configuration's file: the
    attention cores its layers may run on, and the least Mosaic custom calls
    a layer, over the whole stack, that the compiled step has to hold."""
    expects = {**DEFAULT_EXPECTS, **(expects or {})}
    losses = facts["losses"]
    win = facts["window_losses"]
    cores = facts["attention_cores"] or []
    mosaic = facts["mosaic_custom_calls"]
    comp = facts.get("compile", {}).get("window", {})
    k = min(5, len(win))   # a short window has fewer than five steps
    checks = {
        "ended_by_harness": (facts["rc"] == EXIT_PREEMPTED
                             and facts["signalled"]
                             and not facts["raised"]),
        "losses_finite": bool(losses) and all(map(math.isfinite, losses)),
        "step0_matches_reference": (
            reference is not None and bool(losses)
            and abs(losses[0] - reference) <= tolerance),
        "flash_core_everywhere": (
            (bool(cores) and set(cores) <= set(expects["attention_cores"])
             and mosaic is not None
             and mosaic >= expects["mosaic_calls_per_layer"] * len(cores))
            if expect_mosaic else bool(cores)),
        "no_compile_in_window": (
            bool(comp) and comp["cache_writes"] == 0
            and comp["backend_compiles"] == 0),
        "loss_not_rising": (
            k > 0 and sum(win[-k:]) / k
            <= sum(losses[:k]) / k + LOSS_RISE_SLACK),
    }
    return {"checks": checks, "correct": all(checks.values())}
