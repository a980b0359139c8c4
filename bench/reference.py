"""Plain reference of EE-Join's answers, and the comparison that decides
``correct``. It imports nothing of the program.

An entity ``e`` (a token set with weights ``w``) matches a document
window ``s`` (a contiguous run of at most ``L`` tokens, taken as a set)
under the weighted-Jaccard ``extra`` containment of the paper's
Definition 1 when ``w(e & s) >= gamma * w(e)``. Its score is
``w(e & s) / w(e)``. A *variant* of ``e`` (Definition 2) is a subset
``v`` of ``e`` with ``w(v) >= gamma * w(e)``; so ``s`` matches ``e``
exactly when ``e & s`` is a variant of ``e``. The configuration states
which of the two its deployment answers: ``extra``, or the narrower
``variant_exact``, where the window's token set must itself be a
variant (no extra words). A match that one side has and the other lacks
is counted either way.

Weights are summed in float64 (as near to the real sums of the float32
weights as matters); the threshold has the operator's absolute slack of
1e-6. ``precision="bfloat16"`` rounds every weight and every partial sum
to bfloat16: the control that the comparison has to fail.
"""
from __future__ import annotations

import itertools

import numpy as np

EXTRA, VARIANT_EXACT = "extra", "variant_exact"
SEMANTICS = (EXTRA, VARIANT_EXACT)
SLACK = 1e-6


def to_bf16(x) -> np.ndarray:
    """Round float32 values to bfloat16 (nearest, ties to even), as float32."""
    b = np.asarray(x, dtype=np.float32).copy().view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return b.view(np.float32)


class Reference:
    """All matches of a token list against a dictionary, by enumeration.

    ``semantics`` is the configuration's: ``extra`` or ``variant_exact``.
    """

    def __init__(self, entities: list[list[int]], token_weight: np.ndarray,
                 gamma: float, max_len: int, semantics: str,
                 precision: str = "float32"):
        if precision not in ("float32", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        if semantics not in SEMANTICS:
            raise ValueError(f"unknown semantics {semantics!r}")
        self.bf16 = precision == "bfloat16"
        tw = to_bf16(token_weight) if self.bf16 else token_weight
        self.w = [float(x) for x in np.asarray(tw, dtype=np.float32)]
        self.gamma, self.max_len = float(gamma), max_len
        self.extra = semantics == EXTRA
        self.ent = [frozenset(t) for t in entities]
        self.ent_w = [self._sum(sorted(e)) for e in self.ent]
        # variant set -> entities it is a variant of
        self.variants: dict[frozenset, list[int]] = {}
        for e, toks in enumerate(entities):
            thresh = self.gamma * self.ent_w[e] - SLACK
            for r in range(1, len(toks) + 1):
                for v in itertools.combinations(toks, r):
                    if self._sum(v) >= thresh:
                        self.variants.setdefault(frozenset(v), []).append(e)

    def _sum(self, toks) -> float:
        s = 0.0
        for t in toks:
            s += self.w[t]
            if self.bf16:
                s = float(to_bf16(s))
        return s

    def _score(self, inter, e: int) -> float:
        x = self._sum(sorted(inter)) / self.ent_w[e]
        return float(to_bf16(x)) if self.bf16 else x

    def window_matches(self, s: frozenset) -> dict[int, float]:
        """Entity -> score for one window's token set."""
        out: dict[int, float] = {}
        for e in self.variants.get(s, ()):  # s itself is a variant of e
            out[e] = self._score(s, e)
        if len(s) > 1 and self.extra:
            for r in range(1, len(s)):
                for v in itertools.combinations(sorted(s), r):
                    for e in self.variants.get(frozenset(v), ()):
                        if e not in out:
                            inter = self.ent[e] & s
                            if inter == frozenset(v):
                                out[e] = self._score(inter, e)
        return out

    def doc_matches(self, tokens) -> dict[tuple[int, int, int], float]:
        """``(pos, length, entity) -> score`` over one PAD-free token list."""
        toks = [int(t) for t in tokens]
        out = {}
        for p in range(len(toks)):
            for n in range(1, min(self.max_len, len(toks) - p) + 1):
                s = frozenset(toks[p:p + n])
                for e, score in self.window_matches(s).items():
                    out[(p, n, e)] = score
        return out


def answers(ref: Reference, docs) -> dict:
    """``ref``'s matches over ``docs``, ``[(key_prefix, tokens)]``, keyed
    ``(*key_prefix, pos, length, entity)``."""
    out = {}
    for prefix, toks in docs:
        for k, s in ref.doc_matches(toks).items():
            out[(*prefix, *k)] = s
    return out


def check(ref: Reference, served: list, docs) -> dict[str, float]:
    """Compare ``served`` answers over ``docs`` with the reference's."""
    return compare(served, answers(ref, docs))


def compare(served, want: dict) -> dict[str, float]:
    """The numbers that decide ``correct`` for one set of answers.

    ``served`` is a list of ``(key, score)`` pairs and ``want`` a dict of
    them. ``set_diff`` counts the matches that one side has and the
    other lacks, a match served twice counting once more;
    ``score_gap`` is the widest score difference over the matches both
    have (0 when they share none).
    """
    got = dict(served)
    extra = len(served) - len(got) + sum(1 for k in got if k not in want)
    missing = sum(1 for k in want if k not in got)
    gap = max((abs(s - want[k]) for k, s in served if k in want),
              default=0.0)
    return {"set_diff": extra + missing, "score_gap": gap,
            "matches": len(want)}
