"""The comparison that decides ``correct`` fails a broken timed path.

Each test drives a whole tiny run through the harness (its look for a
chip skipped) with one fault planted in the program underneath, where
the answers are produced, or with the bfloat16 control in the program's
place, and sees ``correct`` come out false."""
import dataclasses
import json
import shutil
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.entries import sides_off
from bench.entries.corpus_job import CorpusJob
from bench.entries.screening import Screening
from bench.reference import EXTRA, VARIANT_EXACT, answers
from repro.core.eejoin import EEJoinOperator
from repro.serving.service import ExtractionService

CELLS = {"corpus-zipf16k.splits": CorpusJob,
         "screen-watchlist16k.steady": Screening}


def altered(m, rows):
    """Every seventh answer's score moved by 1e-3."""
    i = jnp.arange(m.score.shape[0])
    return dataclasses.replace(m, score=jnp.where(i % 7 == 0,
                                                  m.score + 1e-3, m.score))


def half_left_out(m, rows):
    """The answers of the second half of the batch's documents dropped."""
    keep = m.doc < (rows + 1) // 2
    return dataclasses.replace(
        m, doc=jnp.where(keep, m.doc, -1), pos=jnp.where(keep, m.pos, -1),
        length=jnp.where(keep, m.length, -1),
        entity=jnp.where(keep, m.entity, -1))


def fanned_out_wrong(m, rows):
    """Each batch row's answers handed to the next row's request."""
    return dataclasses.replace(
        m, doc=jnp.where(m.doc >= 0, (m.doc + 1) % rows, m.doc))


def run_faulty(root, name):
    cell = run.load_cell(root, name)
    return run.run_cell(cell, 4242, 1.0, False, jax.devices()[:1],
                        time.monotonic())


@pytest.mark.parametrize("fault", [altered, half_left_out])
def test_corpus_job_fault_is_not_correct(tiny_root, monkeypatch, fault):
    orig = EEJoinOperator.execute_corpus

    def broken(self, prepared, corpus, *a, **k):
        return fault(orig(self, prepared, corpus, *a, **k), corpus.rows)

    monkeypatch.setattr(EEJoinOperator, "execute_corpus", broken)
    out = run_faulty(tiny_root, "corpus-zipf16k.splits")
    assert out["correct"] is False
    c = out["compared"]
    assert any(c[k]["value"] > c[k]["limit"] for k in c)


@pytest.mark.parametrize("fault", [altered, half_left_out, fanned_out_wrong])
def test_screening_fault_is_not_correct(tiny_root, monkeypatch, fault):
    orig = ExtractionService._complete

    def broken(self, batch, matches, *a, **k):
        return orig(self, batch, fault(matches, batch.rows), *a, **k)

    monkeypatch.setattr(ExtractionService, "_complete", broken)
    out = run_faulty(tiny_root, "screen-watchlist16k.steady")
    assert out["correct"] is False
    c = out["compared"]
    assert any(c[k]["value"] > c[k]["limit"] for k in c)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_bf16_control_is_not_correct(tiny_root, monkeypatch, name):
    """The reference computed in bfloat16, its answers put in place of
    the program's for the sampled documents."""
    job_cls = CELLS[name]
    orig = job_cls.sample

    def control(self):
        orig(self)
        self.served = list(answers(self.reference("bfloat16"),
                                   self.check_docs).items())

    monkeypatch.setattr(job_cls, "sample", control)
    out = run_faulty(tiny_root, name)
    assert out["correct"] is False
    c = out["compared"]
    assert c["set_diff"]["value"] > 0 or c["score_gap"]["value"] > 1e-4


def test_a_plan_that_promises_other_semantics_is_not_correct(tiny_root,
                                                            tmp_path):
    """A configuration stating ``extra`` while the program plans the
    variant scheme (``variant_exact``): the run is not correct, whatever
    the answers."""
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    path = root / "bench" / "configs" / "corpus-zipf16k.json"
    cfg = json.loads(path.read_text())
    assert cfg["semantics"] == VARIANT_EXACT
    path.write_text(json.dumps({**cfg, "semantics": EXTRA}))
    out = run_faulty(root, "corpus-zipf16k.splits")
    assert out["correct"] is False
    assert out["compared"]["side_semantics_off"] == {"value": 1, "limit": 0}


def test_sides_off_counts_each_side_that_holds_entities():
    def plan(split, head, tail):
        return SimpleNamespace(split=split, head=SimpleNamespace(scheme=head),
                               tail=SimpleNamespace(scheme=tail))

    assert sides_off(plan(0, "lsh", "variant"), 10, VARIANT_EXACT) == 0
    assert sides_off(plan(4, "lsh", "variant"), 10, VARIANT_EXACT) == 1
    assert sides_off(plan(10, "prefix", "lsh"), 10, EXTRA) == 0
    assert sides_off(plan(4, "word", "prefix"), 10, VARIANT_EXACT) == 2
    assert sides_off(plan(4, "word", "lsh"), 10, EXTRA) == 1
