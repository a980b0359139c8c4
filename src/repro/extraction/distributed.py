"""Distributed extraction: the MapReduce algorithms on a jax mesh (§3).

Mapping (see DESIGN.md §2):

* mappers            -> per-device bodies under ``shard_map`` over the
                        worker axes (documents sharded along them)
* broadcast of index -> replicated device arrays
* shuffle on sig key -> capacity-bounded ``jax.lax.all_to_all`` routed by
                        ``sig % n_workers`` (MoE-style dispatch: sort by
                        owner, scatter into per-destination slots, drop +
                        count overflow)
* reducers           -> the signature-table shard owned by each device,
                        probed after the exchange; verification runs
                        against a *replicated* dictionary (beyond-paper
                        tweak: the dictionary is orders of magnitude
                        smaller than the shuffled candidate stream, so we
                        replicate it instead of shuffling entity records
                        as Hadoop does)

Both algorithms return per-device ``Matches`` buffers (left sharded —
result sets stay distributed, as in MapReduce output files) plus a
``ShuffleDiag`` with measured bytes / skew / overflow so the benchmarks
can validate the cost model against reality.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import hashing
from repro.core.dictionary import PAD
from repro.core.signatures import EntitySignatures, num_window_signatures
from repro.extraction import engine, sharded
from repro.extraction.results import Matches, compact_matches, merge_matches
from repro.extraction.verify import dedup_hits, verify_pairs

_META_FIELDS = 3  # doc, pos, len


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ShuffleDiag:
    """Measured shuffle statistics (feed the cost-model validation)."""

    sent: jnp.ndarray  # [] records actually routed
    send_overflow: jnp.ndarray  # [] records dropped to capacity
    bytes_shuffled: jnp.ndarray  # [] payload bytes over the interconnect
    max_received: jnp.ndarray  # [] max per-device received records
    mean_received: jnp.ndarray  # [] mean per-device received records


def worker_index(axis_names: tuple[str, ...]) -> jnp.ndarray:
    """Flat worker id across (possibly several) mesh axes."""
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * axis_size(name) + jax.lax.axis_index(name)
    return idx


def num_workers(mesh: Mesh, axis_names: tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in axis_names]))


# --------------------------------------------------------------------------
# Index-on-Entities, distributed: replicate index, map-side everything
# --------------------------------------------------------------------------


def distributed_extract_index(
    mesh: Mesh,
    axis_names: tuple[str, ...],
    doc_tokens,  # [D, T] global, sharded over axis_names
    side,  # eejoin.PreparedSide with index_parts
    max_len: int,
):
    """Run the index algorithm; returns sharded Matches (doc ids global)."""
    n = num_workers(mesh, axis_names)
    D = doc_tokens.shape[0]
    assert D % n == 0, f"docs {D} must divide workers {n}"
    dl = D // n
    params = side.params

    def body(docs):
        docs = docs.reshape(dl, -1)
        if params.use_kernel:
            # per-device double-buffered tile stream (same lanes + merge
            # as the sharded driver; doc ids stay shard-local here)
            cands = sharded.stream_filter_compact(docs, max_len, side.flt, params)
        else:
            base, surv = engine.survival_mask(docs, max_len, side.flt, False)
            cands = engine.compact_candidates(base, surv, params.max_candidates)
        out = None
        for part in side.index_parts:
            m = engine.extract_index_part(cands, part, side.ddict, params)
            out = m if out is None else merge_matches(m, out, params.result_capacity)
        # globalise doc ids
        off = worker_index(axis_names) * dl
        doc = jnp.where(out.doc >= 0, out.doc + off, -1)
        return dataclasses.replace(out, doc=doc, count=jax.lax.psum(out.count, axis_names))

    spec = P(axis_names)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec,),
        out_specs=Matches(
            doc=spec, pos=spec, length=spec, entity=spec, score=spec, count=P()
        ),
        check_vma=False,
    )
    return jax.jit(fn)(doc_tokens)


# --------------------------------------------------------------------------
# ISHFilter & SSJoin, distributed: signature-routed all_to_all shuffle
# --------------------------------------------------------------------------


def build_sharded_sig_tables(
    esigs: EntitySignatures, n_workers_: int, entity_offset: int = 0
) -> tuple[engine.SigTable, float]:
    """Partition entity signatures by owner and build per-owner tables
    with a common static (n_buckets, cap); stacked along axis 0."""
    owner = (esigs.sig % np.uint32(n_workers_)).astype(np.int64)
    # common static geometry across all shards
    per_owner = np.bincount(owner, minlength=n_workers_)
    n_max = max(int(per_owner.max()) if per_owner.size else 1, 1)
    n_buckets = 1 << max(3, int(np.ceil(np.log2(n_max / 0.5 + 1))))
    cap = 4
    for w in range(n_workers_):
        sig = esigs.sig[owner == w]
        if len(sig):
            b = engine._bucket_of(sig.astype(np.uint32), n_buckets, xp=np)
            cap = max(cap, int(np.bincount(b.astype(np.int64), minlength=n_buckets).max()))

    k1s, k2s, ens, skews = [], [], [], []
    for w in range(n_workers_):
        keep = owner == w
        sub = EntitySignatures(sig=esigs.sig[keep], entity_id=esigs.entity_id[keep])
        t = _build_table_fixed(sub, n_buckets, cap, entity_offset)
        k1s.append(t[0])
        k2s.append(t[1])
        ens.append(t[2])
        skews.append(t[3])
    counts = np.array([int((owner == w).sum()) for w in range(n_workers_)])
    entity_skew = float(counts.max() / max(counts.mean(), 1e-9))
    stacked = engine.SigTable(
        keys1=jnp.asarray(np.stack(k1s)),
        keys2=jnp.asarray(np.stack(k2s)),
        ents=jnp.asarray(np.stack(ens)),
        n_buckets=n_buckets,
        bucket_cap=cap,
        entity_offset=entity_offset,
        nbytes=int(np.stack(k1s).nbytes * 2 + np.stack(ens).nbytes),
        skew=entity_skew,
    )
    return stacked, entity_skew


def _build_table_fixed(esigs: EntitySignatures, n_buckets: int, cap: int, entity_offset: int):
    sig = esigs.sig.astype(np.uint32)
    k2v = hashing.hash_u32(sig, seed=engine._SIGKEY_SEED, xp=np)
    bucket = engine._bucket_of(sig, n_buckets, xp=np).astype(np.int64)
    keys1 = np.zeros((n_buckets, cap), dtype=np.uint32)
    keys2 = np.zeros((n_buckets, cap), dtype=np.uint32)
    ents = np.full((n_buckets, cap), -1, dtype=np.int32)
    fill = np.zeros((n_buckets,), dtype=np.int64)
    if len(sig):
        # vectorised fill (see engine.build_sig_table): stable sort by
        # bucket, rank-in-bucket scatter, overflow checked in bulk.
        order = np.argsort(bucket, kind="stable")
        sb = bucket[order]
        rank = np.arange(len(sig)) - np.searchsorted(sb, sb)
        dropped = int((rank >= cap).sum())
        assert dropped == 0, "common table geometry must fit every shard"
        keys1[sb, rank] = sig[order]
        keys2[sb, rank] = k2v[order]
        ents[sb, rank] = esigs.entity_id[order]
        np.add.at(fill, sb, 1)
    return keys1, keys2, ents, float(fill.max() / max(fill.mean(), 1e-9))


def shuffle_capacity(
    max_candidates: int, sigs_per_window: int, n_workers_: int, factor: float = 2.0
) -> int:
    """Per-destination record capacity for the all_to_all dispatch."""
    per_dest = max_candidates * sigs_per_window / max(n_workers_, 1)
    return max(16, int(math.ceil(per_dest * factor)))


def distributed_extract_ssjoin(
    mesh: Mesh,
    axis_names: tuple[str, ...],
    doc_tokens,
    side,  # eejoin.PreparedSide with a *stacked* sig_table
    max_len: int,
    capacity_factor: float = 2.0,
):
    """ISHFilter & SSJoin with an explicit signature-keyed shuffle."""
    n = num_workers(mesh, axis_names)
    D = doc_tokens.shape[0]
    assert D % n == 0, f"docs {D} must divide workers {n}"
    dl = D // n
    params = side.params
    table = side.sig_table
    S = num_window_signatures(params.scheme, max_len, params.lsh)
    cap = shuffle_capacity(params.max_candidates, S, n, capacity_factor)
    rec_bytes = 4 * (max_len + _META_FIELDS + 1)  # tokens + meta + sig

    def body(docs, tk1, tk2, ten):
        docs = docs.reshape(dl, -1)
        local_table = engine.SigTable(
            keys1=tk1.reshape(table.n_buckets, table.bucket_cap),
            keys2=tk2.reshape(table.n_buckets, table.bucket_cap),
            ents=ten.reshape(table.n_buckets, table.bucket_cap),
            n_buckets=table.n_buckets,
            bucket_cap=table.bucket_cap,
            entity_offset=table.entity_offset,
        )
        if params.use_kernel:
            # fused megakernel tile stream; window sigs recomputed from
            # the gathered windows (bit-identical to the in-kernel path)
            cands = sharded.stream_filter_compact(docs, max_len, side.flt, params)
        else:
            base, surv = engine.survival_mask(docs, max_len, side.flt, False)
            cands = engine.compact_candidates(base, surv, params.max_candidates)
        toks, ok = cands["win_tokens"], cands["win_valid"]
        N = toks.shape[0]
        sigs, smask = engine.window_sigs_for(cands, params)
        smask = smask & ok[:, None]

        # ---- dispatch: route each (candidate, signature) to its owner
        flat_sig = sigs.reshape(-1)  # [N*S]
        flat_ok = smask.reshape(-1)
        owner = jnp.where(flat_ok, (flat_sig % jnp.uint32(n)).astype(jnp.int32), n)
        order = jnp.argsort(owner, stable=True)
        sowner = owner[order]
        counts = jnp.bincount(owner, length=n + 1)
        starts = jnp.cumsum(counts) - counts
        pos_in = jnp.arange(flat_sig.shape[0]) - starts[sowner]
        keep = (pos_in < cap) & (sowner < n)
        dst_w = jnp.where(keep, sowner, n - 1)
        dst_p = jnp.where(keep, pos_in, cap)  # cap -> dropped via mode="drop"

        cand_idx = order // S
        off = worker_index(axis_names) * dl
        meta_src = jnp.stack(
            [
                jnp.where(cands["doc"][cand_idx] >= 0, cands["doc"][cand_idx] + off, -1),
                cands["pos"][cand_idx],
                cands["length"][cand_idx],
            ],
            axis=-1,
        )  # [N*S, 3]
        send_tok = jnp.full((n, cap, max_len), PAD, dtype=jnp.int32)
        send_meta = jnp.full((n, cap, _META_FIELDS), -1, dtype=jnp.int32)
        send_sig = jnp.zeros((n, cap), dtype=jnp.uint32)
        send_tok = send_tok.at[dst_w, dst_p].set(toks[cand_idx], mode="drop")
        send_meta = send_meta.at[dst_w, dst_p].set(meta_src, mode="drop")
        send_sig = send_sig.at[dst_w, dst_p].set(flat_sig[order], mode="drop")

        sent = (keep & flat_ok[order]).sum()
        overflow = (flat_ok.sum() - sent).astype(jnp.int32)

        # ---- the shuffle
        a2a = partial(
            jax.lax.all_to_all, axis_name=axis_names, split_axis=0, concat_axis=0
        )
        recv_tok = a2a(send_tok)
        recv_meta = a2a(send_meta)
        recv_sig = a2a(send_sig)

        # ---- reduce side: probe own table shard, verify, emit
        r_tok = recv_tok.reshape(n * cap, max_len)
        r_meta = recv_meta.reshape(n * cap, _META_FIELDS)
        r_sig = recv_sig.reshape(n * cap)
        r_ok = r_meta[:, 0] >= 0
        ents = engine.probe_sig_table(local_table, r_sig[:, None], r_ok[:, None])
        gamma = 0.0 if params.scheme == "variant" else params.gamma
        hits, scores = verify_pairs(
            r_tok,
            ents,
            side.ddict.tokens,
            side.ddict.token_weight,
            gamma=gamma,
            sim_name=params.sim_name,
            use_kernel=params.use_kernel,
        )
        hits = dedup_hits(hits, ents)
        # NOTE: the same (window, entity) pair may also arrive via several
        # *distinct* signatures on different reducers; final results are
        # a distributed multiset, deduplicated at collection (as in
        # MapReduce, where reducers write independent output files).
        ent_global = jnp.where(ents >= 0, ents + table.entity_offset, -1)
        K = hits.shape[1]
        rep = lambda a: jnp.repeat(a, K)
        m = compact_matches(
            hits.reshape(-1),
            rep(r_meta[:, 0]),
            rep(r_meta[:, 1]),
            rep(r_meta[:, 2]),
            ent_global.reshape(-1),
            scores.reshape(-1),
            params.result_capacity,
        )
        m = dataclasses.replace(m, count=jax.lax.psum(m.count, axis_names))

        received = r_ok.sum().astype(jnp.float32)
        diag = ShuffleDiag(
            sent=jax.lax.psum(sent, axis_names),
            send_overflow=jax.lax.psum(overflow, axis_names),
            bytes_shuffled=jax.lax.psum(sent * rec_bytes, axis_names),
            max_received=jax.lax.pmax(received, axis_names),
            mean_received=jax.lax.pmean(received, axis_names),
        )
        return m, diag

    spec = P(axis_names)
    rep_spec = P()
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(
            Matches(doc=spec, pos=spec, length=spec, entity=spec, score=spec, count=rep_spec),
            ShuffleDiag(
                sent=rep_spec,
                send_overflow=rep_spec,
                bytes_shuffled=rep_spec,
                max_received=rep_spec,
                mean_received=rep_spec,
            ),
        ),
        check_vma=False,
    )
    # table shards travel as [n, ...] arrays sharded along the worker axes
    return jax.jit(fn)(doc_tokens, table.keys1, table.keys2, table.ents)


# --------------------------------------------------------------------------
# Distributed statistics gathering (the §"means to gather statistics" job)
# --------------------------------------------------------------------------


def distributed_token_histogram(
    mesh: Mesh, axis_names: tuple[str, ...], doc_tokens, vocab_size: int
):
    """Corpus token histogram as a shard_map + psum job."""

    def body(docs):
        h = jnp.zeros((vocab_size,), dtype=jnp.int32)
        h = h.at[docs.reshape(-1)].add(1)
        return jax.lax.psum(h, axis_names)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_names),),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)(doc_tokens)
