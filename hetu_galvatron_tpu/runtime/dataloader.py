"""Synthetic + host-side data pipeline.

Capability parity with the reference dataloader layer (runtime/dataloader.py:
462-567 ``get_train_valid_test_data_iterators`` / ``get_batch`` / ``_loss_func``
and the random profiling dataset): deterministic synthetic token streams for
profiling/benchmarks and a batch iterator that yields numpy arrays ready for
``jax.device_put`` with a dp-sharded layout.

The mmap indexed dataset (+C++ index builder) lives in
``data/indexed_dataset.py`` and plugs into :func:`get_data_iterator` via
``data.dataset=indexed``; BERT-family models get masked-LM batches instead of
the causal shift.

TPU note: the reference broadcasts batches within TP groups and zigzag-slices
for CP on each rank (utils.py:194-295). Under GSPMD there is one logical batch:
`jax.make_array_from_process_local_data` (or device_put with a NamedSharding)
places the dp-shard on each chip; TP/CP slicing happens inside the jitted
program via shardings, not in the loader.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional

import numpy as np

from hetu_galvatron_tpu.core.args_schema import CoreArgs, DataArgs, ModelArgs


class RandomTokenDataset:
    """Deterministic random tokens (reference's random dataset used by
    profiling runs and correctness tests, dataloader.py:462-524)."""

    def __init__(self, vocab_size: int, seq_length: int, size: int = 1024,
                 seed: int = 1234):
        self.vocab_size = vocab_size
        self.seq_length = seq_length
        self.size = size
        rng = np.random.RandomState(seed)
        # +1 token so input/label shift stays inside the sample
        self._data = rng.randint(
            0, vocab_size, (size, seq_length + 1), dtype=np.int32)

    def __len__(self) -> int:
        return self.size

    def __getitem__(self, idx: int) -> np.ndarray:
        return self._data[idx % self.size]


def make_batch(samples: np.ndarray) -> Dict[str, np.ndarray]:
    """[B, S+1] tokens -> {tokens, labels, loss_mask} (the reference's
    get_batch shift, dataloader.py:525-557)."""
    return {
        "tokens": samples[:, :-1].astype(np.int32),
        "labels": samples[:, 1:].astype(np.int32),
        "loss_mask": np.ones_like(samples[:, 1:], dtype=np.float32),
    }


def make_mlm_batch(
    samples: np.ndarray,
    vocab_size: int,
    rng: np.random.RandomState,
    *,
    mask_prob: float = 0.15,
    mask_token: Optional[int] = None,
    eligible: Optional[np.ndarray] = None,
) -> Dict[str, np.ndarray]:
    """[B, S] tokens -> BERT-style masked-LM batch: 15% of positions are
    selected (80% -> [MASK], 10% -> random token, 10% -> unchanged); labels
    are the originals and loss_mask covers only the selected positions.

    ``rng`` must advance between calls (the caller owns it) so each batch
    masks different positions. ``mask_token`` defaults to the top id of the
    (padded) vocab — real tokenizers should pass their [MASK] id; the padded
    rows the vocab-size rounding adds are a safe default home for it.
    ``eligible`` restricts which positions may be selected at all (the
    loader threads eod_mask_loss through it, so eod tokens are never masked
    or predicted)."""
    tokens = samples.astype(np.int32).copy()
    labels = samples.astype(np.int32)
    mask_token = vocab_size - 1 if mask_token is None else mask_token
    selected = rng.rand(*tokens.shape) < mask_prob
    if eligible is not None:
        selected &= np.asarray(eligible) > 0
    action = rng.rand(*tokens.shape)
    tokens[selected & (action < 0.8)] = mask_token
    random_ids = rng.randint(0, vocab_size, tokens.shape)
    swap = selected & (action >= 0.8) & (action < 0.9)
    tokens[swap] = random_ids[swap]
    return {
        "tokens": tokens,
        "labels": labels,
        "loss_mask": selected.astype(np.float32),
    }


def synthetic_batches(
    model: ModelArgs,
    global_batch_size: int,
    *,
    size: int = 1024,
    seed: int = 1234,
) -> Iterator[Dict[str, np.ndarray]]:
    """Infinite iterator of global batches of synthetic data."""
    ds = RandomTokenDataset(model.padded_vocab_size, model.seq_length,
                            size=size, seed=seed)
    i = 0
    while True:
        idx = [(i * global_batch_size + j) % len(ds)
               for j in range(global_batch_size)]
        yield make_batch(np.stack([ds[j] for j in idx]))
        i += 1


def image_layout(model: ModelArgs, spans: Optional[List[int]]
                 ) -> np.ndarray:
    """``[S + 1]`` bool: which tokens of one sample (``seq_length`` and the
    one more the label shift reads) are image positions. Text span, image,
    text span, ..., image, text span: ``spans`` (``data.image_text_spans``)
    are the text spans' lengths, one more than the images, and sum, with
    the images' merged patches, to ``seq_length``; the token after the
    sequence is text."""
    mh, mw = model.tower_merge_kernel
    rows = [n // (mh * mw) for n in model.image_patches]
    text = model.seq_length - sum(rows)
    if (spans is None or len(spans) != len(rows) + 1 or sum(spans) != text
            or min(spans) < 0):
        raise ValueError(
            f"data.image_text_spans {spans}: {len(rows) + 1} text spans "
            f"that sum to {text} = model.seq_length {model.seq_length} less "
            f"the images' {sum(rows)} positions")
    parts = []
    for span, n in zip(spans, rows + [1]):
        parts += [np.zeros(span, bool), np.ones(n, bool)]
    layout = np.concatenate(parts)
    layout[-1] = False   # the last "image" is the one text token after S
    return layout


def image_text_batches(model: ModelArgs, global_batch_size: int, *,
                       spans: Optional[List[int]], seed: int = 1234
                       ) -> Iterator[Dict[str, np.ndarray]]:
    """Infinite iterator of global batches of a model with a tower: beside
    ``tokens`` and ``labels``, ``patches`` [B, P, patch_dim] (every image of
    ``model.image_grids`` packed in order, seeded N(0, 1) pixel values),
    ``patch_grids`` [B, images, 2] and a ``loss_mask`` with zeros where the
    LABEL is an image position (``model.image_token_id``; the projector's
    rows are no targets). Text ids are uniform over the other rows of the
    vocabulary. Every field leads with the batch's rows, so the microbatch
    split and the host-to-device copy take them as they take ``tokens``."""
    from hetu_galvatron_tpu.observability.tracing import span

    image = image_layout(model, spans)
    grids = np.asarray(model.image_grids, np.int32)
    marker, patches = model.image_token_id, sum(model.image_patches)
    rng = np.random.default_rng(seed)
    B = global_batch_size
    while True:
        with span("train/data/images"):
            pixels = rng.standard_normal(
                (B, patches, model.tower_patch_dim), dtype=np.float32)
        ids = rng.integers(0, model.vocab_size - 1, (B, image.size),
                           dtype=np.int32)
        ids += ids >= marker   # every row of the vocabulary but the marker
        sample = np.where(image[None, :], np.int32(marker), ids)
        batch = make_batch(sample)
        batch["loss_mask"] = (batch["labels"] != marker).astype(np.float32)
        batch["patches"] = pixels
        batch["patch_grids"] = np.broadcast_to(
            grids, (B,) + grids.shape).copy()
        yield batch


def one_ahead(it: Iterator) -> Iterator:
    """``it`` made one item ahead on a thread of its own: the next batch is
    drawn while the device runs the step (the loop waits for the step's
    loss before it asks for data, and ``numpy``'s generators release the
    interpreter). The items and their order are ``it``'s."""
    import queue
    import threading

    box: "queue.Queue" = queue.Queue(maxsize=1)

    def fill():
        try:
            for item in it:
                box.put((item, None))
        except BaseException as e:  # noqa: BLE001 — raised where it is read
            box.put((None, e))

    threading.Thread(target=fill, daemon=True).start()
    while True:
        item, err = box.get()
        if err is not None:
            raise err
        yield item


def skip_batches(it: Iterator, n: int) -> None:
    """Fast-forward ``n`` global batches — the full-state-resume replay of
    the data stream. Replaying (rather than seeking) keeps every stateful
    stage downstream of the raw reader — MLM masking RNG, packed-doc
    segmentation, zigzag permutation — in exactly the state the original
    run left it in. Rerun-machine wrappers are committed per batch so the
    replayed prefix does not pile up in the rewind cache."""
    advance = getattr(it, "advance", None)
    for _ in range(int(n)):
        next(it)
        if advance is not None:
            advance()


_SPLIT_INDEX = {"train": 0, "valid": 1, "test": 2}


def _zigzag_perm(seq: int, cp: int) -> np.ndarray:
    """Slot -> global-position permutation of the zigzag cp layout (rank r
    holds global half-blocks r and 2cp-1-r; ops/ring_attention.py
    zigzag_layout over arange)."""
    blocks = np.split(np.arange(seq), 2 * cp)
    order = []
    for r in range(cp):
        order.append(blocks[r])
        order.append(blocks[2 * cp - 1 - r])
    return np.concatenate(order)


def zigzag_cp_batches(it: Iterator[Dict[str, np.ndarray]], cp: int
                      ) -> Iterator[Dict[str, np.ndarray]]:
    """Apply the zigzag cp layout in the LOADER (reference get_batch zigzag
    slice, utils.py:295): every [B, S] field is permuted along the sequence
    and ``position_ids`` carry each slot's true global (or packed
    doc-relative) position so rope stays correct — ring layers then run
    ``data_zigzagged`` and skip the per-call layout reshard entirely."""
    perm = None
    for batch in it:
        S = batch["tokens"].shape[1]
        if S % (2 * cp):
            raise ValueError(
                f"cp_zigzag needs sequence {S} divisible by 2*cp = {2 * cp}")
        if perm is None or perm.size != S:
            perm = _zigzag_perm(S, cp)
        out = {}
        for k, v in batch.items():
            v = np.asarray(v)
            out[k] = (v[:, perm] if v.ndim >= 2 and v.shape[1] == S else v)
        if "position_ids" not in out:
            out["position_ids"] = np.broadcast_to(
                perm.astype(np.int32), batch["tokens"].shape).copy()
        yield out


def get_data_iterator(
    args: CoreArgs, *, global_batch_size: Optional[int] = None,
    split: str = "train", hpc=None,
) -> Iterator[Dict[str, np.ndarray]]:
    """One split's batch iterator (see
    :func:`get_train_valid_test_data_iterators` for the reference-shaped
    three-way entry point, runtime/dataloader.py:462). ``split`` selects
    the document range by the ``data.split`` ratios for indexed corpora;
    the synthetic dataset draws each split from a disjoint seed. Evaluation
    splits iterate in a stable (unshuffled) order."""
    gbs = global_batch_size or args.parallel.global_train_batch_size
    data: DataArgs = args.data
    meta: Dict = {}
    split_idx = _SPLIT_INDEX[split]
    if data.dataset == "random" and args.model.image_grids:
        # a model with a tower and a traffic that names images: patches
        # beside ids (a model with a tower and no images reads text alone)
        it = one_ahead(image_text_batches(
            args.model, gbs, spans=data.image_text_spans,
            seed=args.train.seed + 101 * split_idx))
    elif data.dataset == "random":
        it = synthetic_batches(args.model, gbs,
                               seed=args.train.seed + 101 * split_idx)
    elif data.dataset == "indexed":
        from hetu_galvatron_tpu.data.indexed_dataset import indexed_batches
        from hetu_galvatron_tpu.data.object_store import localize_prefix

        if not data.data_path:
            raise ValueError("data.dataset=indexed requires data.data_path")
        # s3:// prefixes download-once into the local cache (reference S3
        # indexed datasets, indexed_dataset.py:506); local paths unchanged
        data = data.model_copy(
            update={"data_path": [localize_prefix(p)
                                  for p in data.data_path]})
        meta = corpus_meta(data.data_path)
        if meta.get("vocab_size", 0) > args.model.padded_vocab_size:
            raise ValueError(
                f"corpus tokenizer vocab {meta['vocab_size']} exceeds model "
                f"padded vocab {args.model.padded_vocab_size}")
        it = indexed_batches(data.data_path, args.model.seq_length, gbs,
                             seed=args.train.seed, split=data.split,
                             split_index=split_idx,
                             shuffle=split == "train")
        if (data.eod_mask_loss and meta.get("eod_id") is not None
                and args.model.model_type != "bert"):
            # bert handles eod inside mlm_batches (the causal-shifted
            # loss_mask here would be off by one for MLM positions)
            it = eod_masked_batches(it, meta["eod_id"])
    else:
        raise ValueError(f"unknown dataset kind {data.dataset}")
    if data.reset_position_ids or data.reset_attention_mask:
        if args.model.model_type in ("bert", "t5"):
            raise NotImplementedError(
                "reset_position_ids/reset_attention_mask are causal-LM "
                "packing flags (bert/t5 batches have no packed documents)")
        if meta.get("eod_id") is None:
            raise ValueError(
                "reset_position_ids/reset_attention_mask need document "
                "boundaries: use data.dataset=indexed with an eod-emitting "
                "tokenizer (preprocess_data writes eod_id to the sidecar)")
        it = packed_doc_batches(
            it, meta["eod_id"],
            reset_position_ids=data.reset_position_ids,
            reset_attention_mask=data.reset_attention_mask)
    if args.model.model_type == "bert":
        # encoders train on the MLM objective, never the causal shift
        # (bidirectional attention would leak shifted labels)
        return mlm_batches(it, args.model, seed=args.train.seed,
                           mask_token=meta.get("mask_id"),
                           eod_id=(meta.get("eod_id")
                                   if data.eod_mask_loss else None))
    if args.model.model_type == "t5":
        return seq2seq_batches(it)
    if hpc is not None and getattr(hpc, "cp_zigzag", False):
        # plan validated by get_hybrid_parallel_config: uniform cp, causal
        it = zigzag_cp_batches(it, hpc.layers[0].cp_size)
    return it


def get_train_valid_test_data_iterators(
    args: CoreArgs, *, global_batch_size: Optional[int] = None, hpc=None,
):
    """(train, valid, test) iterators (reference
    get_train_valid_test_data_iterators, runtime/dataloader.py:462). The
    eval iterators are built lazily only when train.eval_interval and
    eval_iters are both set — an empty valid/test split must not fail a
    training-only run."""
    import sys

    train_it = get_data_iterator(args, global_batch_size=global_batch_size,
                                 split="train", hpc=hpc)
    valid_it = test_it = None
    if args.train.eval_interval and args.train.eval_iters:
        for name in ("valid", "test"):
            try:
                it = get_data_iterator(
                    args, global_batch_size=global_batch_size, split=name,
                    hpc=hpc)
            except ValueError as e:
                # an undersized split must degrade eval, not crash a run
                # after the training compute is spent (the small-corpus case
                # under the default 969/30/1 ratios)
                print(f"warning: {name} eval disabled: {e}",
                      file=sys.stderr)
                it = None
            if name == "valid":
                valid_it = it
            else:
                test_it = it
    return train_it, valid_it, test_it


def corpus_meta(paths) -> Dict:
    """Read the preprocess CLI's ``<prefix>.meta.json`` sidecar (tokenizer
    geometry: vocab_size / eod_id). Multiple blended corpora must agree."""
    import json
    import os

    paths = [paths] if isinstance(paths, str) else list(paths)
    metas = []
    for p in paths:
        mp = p + ".meta.json"
        if os.path.exists(mp):
            with open(mp) as f:
                metas.append(json.load(f))
    if not metas:
        return {}
    first = metas[0]
    for m in metas[1:]:
        if (m.get("vocab_size"), m.get("eod_id")) != (
                first.get("vocab_size"), first.get("eod_id")):
            raise ValueError(
                "blended corpora were tokenized with different tokenizers: "
                f"{metas}")
    return first


def eod_masked_batches(it: Iterator[Dict[str, np.ndarray]], eod_id: int
                       ) -> Iterator[Dict[str, np.ndarray]]:
    """Zero the loss where the INPUT token is end-of-document (reference
    eod_mask_loss, utils.py get_ltor_masks_and_position_ids): the eod
    position would otherwise be trained to predict the NEXT document's
    first token. Predicting eod itself (label == eod) stays in the loss —
    the model must learn to emit it."""
    for batch in it:
        batch = dict(batch)
        batch["loss_mask"] = (batch["loss_mask"]
                              * (batch["tokens"] != eod_id))
        yield batch


def packed_doc_fields(tokens: np.ndarray, eod_id: int, *,
                      reset_position_ids: bool, reset_attention_mask: bool
                      ) -> Dict[str, np.ndarray]:
    """Per-token position/segment ids for packed multi-document samples
    (reference reset_position_ids / reset_attention_mask, Megatron
    get_ltor_masks_and_position_ids): a document starts AFTER each eod
    token; positions restart at 0 there and segment ids increment so
    attention can be block-diagonalized per document."""
    doc_starts = np.zeros_like(tokens, dtype=np.int64)
    doc_starts[:, 1:] = (tokens[:, :-1] == eod_id)
    segments = np.cumsum(doc_starts, axis=1)
    out: Dict[str, np.ndarray] = {}
    if reset_attention_mask:
        out["segment_ids"] = segments.astype(np.int32)
    if reset_position_ids:
        pos = np.arange(tokens.shape[1], dtype=np.int64)[None, :]
        # position of each document's first token, broadcast along the doc
        starts = np.where(doc_starts.astype(bool), pos, 0)
        doc_start_pos = np.maximum.accumulate(starts, axis=1)
        out["position_ids"] = (pos - doc_start_pos).astype(np.int32)
    return out


def packed_doc_batches(it: Iterator[Dict[str, np.ndarray]], eod_id: int, *,
                       reset_position_ids: bool, reset_attention_mask: bool
                       ) -> Iterator[Dict[str, np.ndarray]]:
    for batch in it:
        batch = dict(batch)
        batch.update(packed_doc_fields(
            batch["tokens"], eod_id,
            reset_position_ids=reset_position_ids,
            reset_attention_mask=reset_attention_mask))
        yield batch


def seq2seq_batches(it: Iterator[Dict[str, np.ndarray]]
                    ) -> Iterator[Dict[str, np.ndarray]]:
    """Causal batches -> seq2seq: the first half of each sample becomes the
    encoder source, the second half the (shifted) decoder target."""
    for batch in it:
        tokens = batch["tokens"]
        half = tokens.shape[1] // 2
        # tokens/labels are already the one-step-shifted pair, so slicing
        # both at `half` keeps decoder input i aligned with label i+1
        yield {
            "enc_tokens": tokens[:, :half],
            "tokens": tokens[:, half:],
            "labels": batch["labels"][:, half:],
            "loss_mask": batch["loss_mask"][:, half:],
        }


def mlm_batches(it: Iterator[Dict[str, np.ndarray]], model: ModelArgs,
                seed: int, mask_token: Optional[int] = None,
                eod_id: Optional[int] = None
                ) -> Iterator[Dict[str, np.ndarray]]:
    """``eod_id`` excludes end-of-document tokens from MLM selection (the
    bert leg of data.eod_mask_loss — without this the flag would be a
    silent no-op for encoders)."""
    rng = np.random.RandomState(seed + 1)
    for batch in it:
        eligible = (batch["tokens"] != eod_id) if eod_id is not None else None
        yield make_mlm_batch(batch["tokens"], model.padded_vocab_size, rng,
                             mask_token=mask_token, eligible=eligible)
