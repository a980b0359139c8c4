"""Entries: how a kind of deployment is set up, driven and checked.

A configuration names its entry (``"entry": "corpus_job"``). An entry
module has ``setup(ctx) -> job``, which plans, makes the traffic of
``ctx.seed`` and warms up. The job has ``plan_desc`` and ``plan_s``;
``make_traffic(seed, seconds, rate=None)`` (another seed's traffic, the
same shapes); ``window(seconds) -> Window``; ``sample()`` (keeps a
seed-drawn sample of the window's answers on the host); ``close()``
(frees the program's state); ``reference(precision)`` and
``check(ref=None) -> {number: value}`` (the sample against the plain
reference, made after ``close``).

The reference answers the semantics the configuration states, never
those of the plan the program chose; a plan side whose scheme promises
other semantics is counted, so the run is not correct.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import EXTRA, VARIANT_EXACT, Reference, check

#: the semantics each of the program's plan-side schemes promises
#: (``lsh`` promises a subset of the ``extra`` answers: none of these)
SCHEME_SEMANTICS = {"word": EXTRA, "prefix": EXTRA, "variant": VARIANT_EXACT}


@dataclasses.dataclass
class Window:
    """What one measured window did."""

    elapsed_s: float
    attempted: int
    failed: int
    metrics: dict  # end-to-end metric name -> value
    steady: dict  # work completed, generator lag: printed on an earlier line
    record: dict  # what the per-layer readers read


def program_dictionary(wl, cfg: dict):
    """The watch list as the program's ``Dictionary``, built by its own
    ``build_dictionary``; its entity ids must be the watch list's."""
    from repro.core.dictionary import build_dictionary

    d = build_dictionary([wl.entity(e) for e in range(wl.num_entities)],
                         cfg["vocab_size"], token_weight=wl.token_weight,
                         freq=wl.freq, max_len=wl.max_len)
    if not np.array_equal(d.tokens, wl.tokens):
        raise RuntimeError("the program's dictionary renumbered entities")
    return d


def reference(wl, cfg: dict, precision: str = "float32") -> Reference:
    """The plain reference for the watch list, under the semantics the
    configuration states."""
    return Reference([wl.entity(e) for e in range(wl.num_entities)],
                     wl.token_weight, cfg["gamma"], wl.max_len,
                     cfg["semantics"], precision)


def sides_off(plan, num_entities: int, semantics: str) -> int:
    """Plan sides, of those that hold entities, whose scheme promises
    other semantics than ``semantics``."""
    sides = ((0, plan.split, plan.head), (plan.split, num_entities, plan.tail))
    return sum(1 for a, b, side in sides
               if a < b and SCHEME_SEMANTICS.get(side.scheme) != semantics)


def check_job(job, ref: Reference | None) -> dict:
    """The numbers that decide ``correct`` for ``job``'s sample: the
    comparison with the reference, and the plan's sides that promise
    other semantics than the configuration states."""
    cfg = job.cfg
    return {**check(ref or reference(job.wl, cfg), job.served,
                    job.check_docs),
            "side_semantics_off": sides_off(job.plan, job.wl.num_entities,
                                            cfg["semantics"])}
