"""The plain reference against the definitions, by brute force, and
the comparison it feeds."""
import numpy as np
import pytest

from bench import gen
from bench.reference import (
    EXTRA,
    VARIANT_EXACT,
    Reference,
    answers,
    check,
    compare,
    to_bf16,
)

GAMMA = 0.8


def brute(entities, w, doc, semantics, max_len):
    """Every (pos, len, entity) -> score, straight from Definitions 1-2."""
    w = np.asarray(w, dtype=np.float64)
    out = {}
    for p in range(len(doc)):
        for n in range(1, min(max_len, len(doc) - p) + 1):
            s = set(doc[p:p + n])
            for e, ent in enumerate(entities):
                we = w[list(ent)].sum()
                inter = w[list(set(ent) & s)].sum()
                if semantics == VARIANT_EXACT and not s <= set(ent):
                    continue
                if inter >= GAMMA * we - 1e-6:
                    out[(p, n, e)] = inter / we
    return out


@pytest.fixture(scope="module")
def small():
    cfg = {"dictionary_seed": 11, "vocab_size": 64, "num_entities": 40,
           "min_entity_len": 1, "max_entity_len": 4, "token_zipf_s": 1.1,
           "mention_zipf_s": 1.3}
    wl = gen.make_watchlist(cfg)
    docs = gen.make_docs(wl, gen.rng_for(5, gen.STREAM_DOCS), 6, 40, 4.0,
                         {"p_drop": 0.3, "p_insert": 0.3, "p_permute": 0.3})
    return wl, [wl.entity(e) for e in range(wl.num_entities)], docs


@pytest.mark.parametrize("semantics", [VARIANT_EXACT, EXTRA])
def test_reference_equals_the_definitions(small, semantics):
    wl, ents, docs = small
    ref = Reference(ents, wl.token_weight, GAMMA, wl.max_len, semantics)
    total = 0
    for doc in docs:
        want = brute(ents, wl.token_weight, doc.tolist(), semantics,
                     wl.max_len)
        got = ref.doc_matches(doc)
        assert got.keys() == want.keys()
        for k in want:
            assert got[k] == pytest.approx(want[k], abs=1e-12)
        total += len(want)
    assert total > 20  # the planted mentions do match


def test_extra_contains_variant_exact(small):
    wl, ents, docs = small
    narrow = Reference(ents, wl.token_weight, GAMMA, wl.max_len,
                       VARIANT_EXACT)
    wide = Reference(ents, wl.token_weight, GAMMA, wl.max_len, EXTRA)
    a, b = narrow.doc_matches(docs[0]), wide.doc_matches(docs[0])
    assert a.keys() <= b.keys() and len(b) > len(a)


def test_bf16_rounding_is_nearest_even():
    x = np.array([1.0, 1.0 + 2**-8, 1.0 + 3 * 2**-8, 3.14159], np.float32)
    assert to_bf16(x).tolist() == [1.0, 1.0, 1.0 + 2**-6, 3.140625]


def test_the_bf16_control_fails_the_comparison(small):
    wl, ents, docs = small
    ref = Reference(ents, wl.token_weight, GAMMA, wl.max_len, EXTRA)
    low = Reference(ents, wl.token_weight, GAMMA, wl.max_len, EXTRA,
                    precision="bfloat16")
    check_docs = [((i,), d) for i, d in enumerate(docs)]
    served = list(answers(low, check_docs).items())
    got = check(ref, served, check_docs)
    assert got["score_gap"] > 1e-4 or got["set_diff"] > 0
    exact = check(ref, list(answers(ref, check_docs).items()), check_docs)
    assert exact["set_diff"] == 0 and exact["score_gap"] == 0.0


def test_compare_counts_missing_extra_and_duplicate_matches():
    want = {(0, 1, 1, 5): 0.9, (0, 2, 2, 6): 1.0}
    served = [((0, 1, 1, 5), 0.9 + 1e-3), ((0, 1, 1, 5), 0.9),
              ((0, 3, 1, 7), 0.8)]
    got = compare(served, want)
    # one extra, one missing, one duplicate
    assert got["set_diff"] == 3
    assert got["score_gap"] == pytest.approx(1e-3)
    # a missing match counts whatever the plan that lost it
    assert compare([((0, 1, 1, 5), 0.9)], want)["set_diff"] == 1


def test_unknown_semantics_is_refused(small):
    wl, ents, _ = small
    with pytest.raises(ValueError, match="semantics"):
        Reference(ents, wl.token_weight, GAMMA, wl.max_len, "approx")
