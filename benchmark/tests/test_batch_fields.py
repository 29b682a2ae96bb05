"""A batch that holds more than token ids: the whole first batch reaches the
plain reference, the loss is over the positions the batch marks, and a
family that leaves a field unread is caught. The witness is the program as
it is: documents packed into one sequence from an indexed corpus
(``segment_ids``, ``position_ids`` and a ``loss_mask`` with zeros), which
it already trains on."""

import os

import numpy as np
import pytest

from benchmark import check, manifest, reference, run
from benchmark.tests import tiny


def _packed_cell(tmp_path, **kw):
    root = tiny.make_root(tmp_path)
    corpus = str(tmp_path / "corpus")
    tiny.write_packed_corpus(corpus)
    tiny.add_packed_family(root, corpus, **kw)
    tiny.assert_nothing_that_was_there_is_edited(root)
    man = manifest.load_manifest(root)
    assert manifest.check_manifest(man, root) == []
    return root, manifest.resolve_cell(man, tiny.PACKED_CELL[0], root)


def _measure(tmp_path, root, cell):
    return run.measure(cell, seed=7, seconds=0.5, trace=0,
                       chip=tiny.FAKE_CHIP, root=root,
                       out_dir=str(tmp_path / "out"), expect_mosaic=False)


def test_packed_documents_run_by_files_alone(tmp_path):
    root, cell = _packed_cell(tmp_path)
    _, batch = check.first_batch(manifest.train_argv(cell, 7, root))
    assert set(batch) == {"tokens", "labels", "loss_mask", "segment_ids",
                          "position_ids"}
    mask, labels = batch["loss_mask"], batch["labels"]
    # every sequence holds a document's end: its position is out of the loss,
    # the next one starts a segment at position 0
    ends = batch["tokens"] == tiny.PACKED_EOD
    assert ends.any(axis=1).all() and (mask == ~ends).all()
    assert (batch["segment_ids"].max(axis=1) >= 1).all()
    assert (batch["position_ids"][:, 1:][ends[:, :-1]] == 0).all()
    assert 0 < mask.sum() < labels.size

    line, report = _measure(tmp_path, root, cell)
    assert report["checks"]["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is True, report["checks"]
    assert report["reference"]["tokens"] == mask.sum() < labels.size
    # a position that is out of the loss is still a token of the step
    assert report["tokens_per_step"] == labels.size == 4 * 16


def test_a_family_that_takes_ids_alone_is_refused_a_batch_with_more(
        tmp_path):
    root, cell = _packed_cell(tmp_path, family="mistral")
    with pytest.raises(ValueError, match=(
            r"first batch holds \['loss_mask', 'position_ids', "
            r"'segment_ids'\] beside tokens and labels, and reference "
            r"family 'mistral' takes ids alone")):
        _measure(tmp_path, root, cell)
    with pytest.raises(ValueError, match="take check.first_batch"):
        check.first_batch_and_weights(manifest.train_argv(cell, 7, root))


def test_a_family_that_leaves_the_segments_unread_fails_the_comparison(
        tmp_path):
    with open(os.path.join(tiny.NEW_FAMILY_DIR, "tiny_packed.py")) as f:
        source = f.read()
    unread = source.replace("IGNORES: tuple = ()",
                            'IGNORES: tuple = ("segment_ids",)')
    assert unread != source
    root, cell = _packed_cell(tmp_path, family="tiny_packed_unmasked",
                              source=unread)
    line, report = _measure(tmp_path, root, cell)
    checks = report["checks"]
    assert not checks["step0_matches_reference"], (
        report["losses"][0], report["reference"])
    assert line["correct"] is False
    assert all(ok for name, ok in checks.items()
               if name != "step0_matches_reference"), checks


def test_the_mean_is_over_the_marked_positions_and_ones_change_nothing(
        tmp_path):
    """``mean_loss`` on the tiny Mistral cell's own batch: a ``loss_mask``
    of ones is the path of a batch without one, bit for bit; with zeros in
    it the family is handed it and the mean is over its sum."""
    root, cell = _packed_cell(tmp_path)
    weights, batch = check.first_batch(manifest.train_argv(
        manifest.resolve_cell(manifest.load_manifest(root),
                              "tiny_mistral_c1", root), 7, root))
    tokens, labels = batch.pop("tokens"), batch.pop("labels")
    assert set(batch) == {"loss_mask"} and (batch["loss_mask"] == 1).all()
    assert reference.beyond_ids(batch) == {}
    loss = lambda family, **kw: reference.mean_loss(
        family, weights, cell.config, tokens, labels, root=root, **kw)
    plain = loss("mistral")
    assert loss("mistral", batch=batch) == plain
    assert loss("tiny_packed", batch=batch) == pytest.approx(plain, rel=1e-6)
    half = np.ones_like(batch["loss_mask"])
    half[:, ::2] = 0
    assert reference.loss_positions(labels, {"loss_mask": half}) \
        == half.sum() == labels.size // 2
    with pytest.raises(ValueError, match=r"holds \['loss_mask'\] beside"):
        loss("mistral", batch={"loss_mask": half})
    masked = loss("tiny_packed", batch={"loss_mask": half})
    other = loss("tiny_packed", batch={"loss_mask": 1 - half})
    # the two halves' means average to the whole's
    assert (masked + other) / 2 == pytest.approx(plain, rel=1e-6)
    assert masked != pytest.approx(other, rel=1e-3)
    # a field whose leading length is not the batch's rows goes whole
    assert loss("tiny_packed", rows_per_call=2, batch={
        "loss_mask": half, "not_a_row_field": np.zeros(3)}) \
        == pytest.approx(masked, rel=1e-6)
