"""The benchmark's generator: the dictionary from the configuration's
seed, documents and arrivals from ``--seed``, the same work for every
seed."""
import json

import numpy as np

from bench import gen
from bench.tests.tiny import BENCH, TINY, merge


def tiny_config(name="corpus-zipf16k"):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return merge(cfg, TINY[name])


NOISE = {"p_drop": 0.25, "p_insert": 0.15, "p_permute": 0.1}


def test_watchlist_depends_on_the_configuration_seed_only():
    cfg = tiny_config()
    a, b = gen.make_watchlist(cfg), gen.make_watchlist(cfg)
    assert np.array_equal(a.tokens, b.tokens)
    assert np.array_equal(a.token_weight, b.token_weight)
    other = gen.make_watchlist({**cfg, "dictionary_seed": 7})
    assert not np.array_equal(a.tokens, other.tokens)
    # entities are distinct sets of distinct tokens, in mention order
    sets = {frozenset(a.entity(e)) for e in range(a.num_entities)}
    assert len(sets) == a.num_entities
    assert all(len(set(a.entity(e))) == a.lengths[e]
               for e in range(a.num_entities))
    assert np.all(np.diff(a.freq) <= 0) and a.token_weight[0] == 0


def test_same_seed_gives_identical_traffic():
    wl = gen.make_watchlist(tiny_config())
    seed = 2**31 + 12345  # seeds may exceed 32 signed bits
    for _ in range(2):
        docs = [gen.make_docs(wl, gen.rng_for(seed, gen.STREAM_DOCS), 16, 64,
                              3.0, NOISE) for _ in range(2)]
        assert np.array_equal(docs[0], docs[1])
        lens = gen.transaction_lengths(gen.rng_for(seed, gen.STREAM_LENGTHS),
                                       50, 16, 128)
        txns = [gen.make_transactions(wl, gen.rng_for(seed, gen.STREAM_DOCS),
                                      lens, 0.1, NOISE)
                for _ in range(2)]
        assert all(np.array_equal(x, y) for x, y in zip(*txns))
        due = [gen.arrival_offsets(gen.rng_for(seed, gen.STREAM_ARRIVALS),
                                   50, {"arrivals": "poisson",
                                        "rate_per_s": 10.0})
               for _ in range(2)]
        assert np.array_equal(due[0], due[1])


def test_different_seeds_give_different_documents_over_one_dictionary():
    wl = gen.make_watchlist(tiny_config())
    a = gen.make_docs(wl, gen.rng_for(1, gen.STREAM_DOCS), 16, 64, 3.0, NOISE)
    b = gen.make_docs(wl, gen.rng_for(2, gen.STREAM_DOCS), 16, 64, 3.0, NOISE)
    assert a.shape == b.shape and not np.array_equal(a, b)
    assert a.min() >= 1 and a.max() < wl.token_weight.shape[0]


def test_open_loop_work_is_the_same_multiset_for_every_seed():
    wl = gen.make_watchlist(tiny_config())
    traffic = {"arrivals": "poisson", "rate_per_s": 100.0}
    lens, gaps = [], []
    for seed in (3, 4):
        t = gen.make_transactions(
            wl, gen.rng_for(seed, gen.STREAM_DOCS),
            gen.transaction_lengths(gen.rng_for(seed, gen.STREAM_LENGTHS),
                                    200, 16, 128), 0.1, NOISE)
        lens.append(sorted(len(x) for x in t))
        assert min(lens[-1]) >= 16 and max(lens[-1]) <= 128
        due = gen.arrival_offsets(gen.rng_for(seed, gen.STREAM_ARRIVALS),
                                  200, traffic)
        assert np.all(np.diff(due) > 0)
        gaps.append(np.sort(np.diff(np.concatenate([[0.0], due]))))
    assert lens[0] == lens[1]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=0, atol=1e-9)
    # the mean rate is the mix's rate
    assert abs(200 / due[-1] - 100.0) < 10.0


def test_a_mix_schedule_is_the_same_for_every_seed_and_only_tokens_differ():
    from bench.entries.screening import Screening

    job = Screening.__new__(Screening)  # make_traffic alone, no program
    job.wl = gen.make_watchlist(tiny_config("screen-watchlist16k"))
    job.traffic = {"arrivals": "poisson", "rate_per_s": 20.0,
                   "schedule_seed": 99}
    job.docs_cfg = tiny_config("screen-watchlist16k")["documents"]
    job.make_traffic(1, 5.0)
    due, txns = job.due, job.txns
    job.make_traffic(2, 5.0)
    assert np.array_equal(due, job.due)
    assert [len(t) for t in txns] == [len(t) for t in job.txns]
    assert any(not np.array_equal(a, b) for a, b in zip(txns, job.txns))
