"""Deployment data and traffic from seeds: the benchmark's own generator.

A vectorised copy of the semantics of ``repro.data.synth.make_corpus`` /
``drift_docs``: a Zipf(1.1) vocabulary with IDF-style token weights,
entities of distinct tokens drawn from it, Zipf(1.3) mention
frequencies, and background documents with planted noisy mentions
(a token dropped, two swapped, a junk token inserted). Draws use
inverse-CDF sampling in bulk, so a 4096 x 256-token split takes a
fraction of a second where ``make_corpus`` draws one row at a time.

The dictionary comes from the configuration's ``dictionary_seed``: every
run of a cell serves the same watch list and executes the same plan.
Documents come from ``--seed``. An open-loop mix draws its schedule,
the due times and the transaction lengths, from its own
``schedule_seed``: lengths and inter-arrival gaps are the mid-quantiles
of their distributions in an order drawn once, so every ``--seed``
offers the same work at the same times and changes only the tokens.
"""
from __future__ import annotations

import dataclasses

import numpy as np

PAD = 0

#: independent random streams under one seed
(STREAM_DICT, STREAM_STATS, STREAM_DOCS, STREAM_ARRIVALS, STREAM_CHECK,
 STREAM_LENGTHS) = range(6)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """One independent generator per (seed, stream); any whole ``seed``."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def zipf_probs(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), s)
    return p / p.sum()


def _cdf(p: np.ndarray) -> np.ndarray:
    c = np.cumsum(p)
    return c / c[-1]


def _draw(rng: np.random.Generator, cdf: np.ndarray, size) -> np.ndarray:
    """Indices ``0 .. len(cdf) - 1`` with probabilities given by ``cdf``."""
    i = np.searchsorted(cdf, rng.random(size), side="right")
    return np.minimum(i, len(cdf) - 1)


@dataclasses.dataclass
class WatchList:
    """A dictionary as plain arrays: entity ``i`` is ``tokens[i, :lengths[i]]``.

    Entities are in descending mention frequency (``freq``), the order
    the operator's plan search assumes, so entity ids mean the same to
    the program and to the reference.
    """

    tokens: np.ndarray  # [E, L] int32, PAD tails
    lengths: np.ndarray  # [E] int32
    freq: np.ndarray  # [E] float32, descending
    token_weight: np.ndarray  # [V] float32, w[PAD] = 0
    token_cdf: np.ndarray  # [V - 1] background token CDF (ids 1..V-1)
    mention_cdf: np.ndarray  # [E] mention CDF over entities

    @property
    def num_entities(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def max_len(self) -> int:
        return int(self.tokens.shape[1])

    def entity(self, e: int) -> list[int]:
        return self.tokens[e, : self.lengths[e]].tolist()


def make_watchlist(cfg: dict) -> WatchList:
    """The configuration's dictionary, from its ``dictionary_seed``."""
    rng = rng_for(cfg["dictionary_seed"], STREAM_DICT)
    V, E = cfg["vocab_size"], cfg["num_entities"]
    lo, hi = cfg["min_entity_len"], cfg["max_entity_len"]
    bg = zipf_probs(V - 1, cfg["token_zipf_s"])
    token_cdf = _cdf(bg)
    rows: list[list[int]] = []
    seen: set[frozenset] = set()
    while len(rows) < E:
        m = 2 * (E - len(rows)) + 64
        lens = rng.integers(lo, hi + 1, size=m)
        cand = _draw(rng, token_cdf, (m, hi)) + 1
        for n, row in zip(lens.tolist(), cand.tolist()):
            toks = row[:n]
            key = frozenset(toks)
            if len(key) < n or key in seen:
                continue  # tokens of an entity are distinct; entities too
            seen.add(key)
            rows.append(toks)
            if len(rows) == E:
                break
    tokens = np.zeros((E, hi), dtype=np.int32)
    lengths = np.zeros((E,), dtype=np.int32)
    for i, toks in enumerate(rows):
        tokens[i, : len(toks)] = toks
        lengths[i] = len(toks)
    tw = np.zeros((V,), dtype=np.float32)
    if cfg.get("weighted", True):
        tw[1:] = np.log1p(1.0 / (bg * V)).astype(np.float32) + np.float32(0.1)
    else:
        tw[1:] = 1.0
    mention = zipf_probs(E, cfg["mention_zipf_s"])
    return WatchList(tokens=tokens, lengths=lengths,
                     freq=mention.astype(np.float32), token_weight=tw,
                     token_cdf=token_cdf, mention_cdf=_cdf(mention))


def noisy_mention(wl: WatchList, e: int, u: np.ndarray, junk: int,
                  noise: dict) -> list[int]:
    """Entity ``e``'s tokens with drop / swap / insert noise.

    ``u`` holds 7 uniform numbers that decide the noise, ``junk`` the
    token an insertion adds (the order of ``make_corpus``'s draws).
    """
    toks = wl.entity(e)
    if len(toks) > 1 and u[0] < noise["p_drop"]:
        toks.pop(int(u[1] * len(toks)))
    if len(toks) > 1 and u[2] < noise["p_permute"]:
        i = int(u[3] * len(toks))
        j = (i + 1 + int(u[4] * (len(toks) - 1))) % len(toks)
        toks[i], toks[j] = toks[j], toks[i]
    if u[5] < noise["p_insert"]:
        toks.insert(int(u[6] * (len(toks) + 1)), junk)
    return toks


def make_docs(wl: WatchList, rng: np.random.Generator, num_docs: int,
              doc_len: int, mentions_per_doc: float, noise: dict
              ) -> np.ndarray:
    """[num_docs, doc_len] int32 background documents with
    ``mentions_per_doc * num_docs`` planted noisy mentions, Zipf over
    entities (a corpus split)."""
    docs = (_draw(rng, wl.token_cdf, (num_docs, doc_len)) + 1).astype(np.int32)
    n = int(round(mentions_per_doc * num_docs))
    ents = _draw(rng, wl.mention_cdf, n)
    u = rng.random((n, 9))
    junk = _draw(rng, wl.token_cdf, n) + 1
    for k in range(n):
        toks = noisy_mention(wl, int(ents[k]), u[k], int(junk[k]), noise)
        m = len(toks)
        if m > doc_len:
            continue
        d = int(u[k, 7] * num_docs)
        p = int(u[k, 8] * (doc_len - m + 1))
        docs[d, p:p + m] = toks
    return docs


def quantiles_in_order(rng: np.random.Generator, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles ``(i + 0.5) / n`` in a seed-drawn order."""
    return rng.permutation((np.arange(n) + 0.5) / n)


def transaction_lengths(rng: np.random.Generator, n: int, len_min: int,
                        len_max: int) -> np.ndarray:
    """``n`` log-uniform lengths in ``[len_min, len_max]``: the same
    multiset (the distribution's mid-quantiles) in an order from ``rng``."""
    q = quantiles_in_order(rng, n)
    lens = np.floor(np.exp(np.log(len_min)
                           + q * (np.log(len_max + 1) - np.log(len_min))))
    return np.clip(lens.astype(np.int64), len_min, len_max)


def make_transactions(wl: WatchList, rng: np.random.Generator,
                      lens: np.ndarray, mention_share: float, noise: dict
                      ) -> list[np.ndarray]:
    """PAD-free background transactions of lengths ``lens``, with one
    planted noisy mention, Zipf over entities, in ``mention_share`` of
    them (the same count for every seed)."""
    n = len(lens)
    flat = (_draw(rng, wl.token_cdf, int(lens.sum())) + 1).astype(np.int32)
    txns = np.split(flat, np.cumsum(lens)[:-1])
    k = int(round(mention_share * n))
    carriers = rng.permutation(n)[:k]
    ents = _draw(rng, wl.mention_cdf, k)
    u = rng.random((k, 9))
    junk = _draw(rng, wl.token_cdf, k) + 1
    for j, t in enumerate(carriers.tolist()):
        toks = noisy_mention(wl, int(ents[j]), u[j], int(junk[j]), noise)
        m = len(toks)
        if m > len(txns[t]):
            continue
        p = int(u[j, 8] * (len(txns[t]) - m + 1))
        txns[t][p:p + m] = toks
    return txns


def pad_rows(rows: list[np.ndarray], width: int) -> np.ndarray:
    out = np.full((len(rows), width), PAD, dtype=np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


def arrival_offsets(rng: np.random.Generator, n: int, traffic: dict
                    ) -> np.ndarray:
    """Due times (s from the window's start) of ``n`` Poisson arrivals at
    the mix's ``rate_per_s``: unit-rate exponential gaps at the ``n``
    mid-quantiles, in a seed-drawn order, over the rate."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    gaps = -np.log1p(-quantiles_in_order(rng, n))
    return np.cumsum(gaps) / float(traffic["rate_per_s"])
