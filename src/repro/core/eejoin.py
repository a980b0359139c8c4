"""The EE-Join operator: statistics → cost-based plan choice → execution.

This is the paper's contribution as a composable module. Usage::

    op = EEJoinOperator(dictionary, EEJoinConfig(gamma=0.8))
    stats = op.gather_statistics(sample_docs, total_docs=len(corpus))
    plan = op.choose_plan(stats)
    prepared = op.prepare(plan)
    matches = op.execute(prepared, doc_tokens)          # single shard
    matches = op.execute_distributed(prepared, sharded) # shard_map (launch/)

The operator is deliberately split into prepare (host-side structure
builds, done once) and execute (pure jitted device function) so the same
prepared plan runs on a laptop shard or a 512-chip mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.cost_model import (
    ALGO_INDEX,
    ALGO_SSJOIN,
    ALL_OPTIONS,
    OBJ_JOB,
    CostParams,
)
from repro.core.dictionary import Dictionary
from repro.core.filter import build_ish_filter
from repro.core.plan import Plan, PlanSide
from repro.core.search import search_plan
from repro.core.signatures import LshParams, entity_signatures
from repro.core.stats import EEStats, gather_stats
from repro.extraction import engine
from repro.extraction.results import Matches, merge_matches
from repro.tracing import span


@dataclasses.dataclass(frozen=True)
class EEJoinConfig:
    gamma: float = 0.8
    sim_name: str = "extra"
    objective: str = OBJ_JOB
    use_filter: bool = True
    max_candidates: int = 8192
    result_capacity: int = 16384
    lsh: LshParams = LshParams()
    options: Sequence[tuple[str, str]] = ALL_OPTIONS
    use_kernel: bool = False
    filter_bits: int = 1 << 18
    # kernel-path lane compaction knobs, forwarded to every side's
    # ExtractParams (validated there): adaptive two-pass lane sizing,
    # its emit-width floor, and forced/suppressed in-kernel signatures.
    adaptive_lanes: bool = False
    lane_width: int | None = None
    kernel_sigs: bool | None = None
    # kernel-path streaming: per-shard launch mode for the streaming
    # drivers (None = auto: stream shards spanning >= 2 tiles through
    # the single-launch DMA megakernel; see ExtractParams.streamed) and
    # the device-resident byte budget ``execute_corpus`` sizes spill
    # shards against (None -> sharded.DEFAULT_DEVICE_BUDGET_BYTES).
    streamed: bool | None = None
    device_budget_bytes: int | None = None
    # continuous calibration (serving.replan): how many recent documents
    # a session's ObservedStats ring retains as the statistics sample an
    # online replan re-runs the §5 search over.
    observe_capacity: int = 128


@dataclasses.dataclass
class PreparedSide:
    """One executable side of a plan (device-resident structures)."""

    side: PlanSide
    params: engine.ExtractParams
    ddict: engine.DeviceDictionary
    flt: tuple | None  # (bits, num_bits, num_hashes)
    index_parts: list[engine.BuiltIndex] | None = None
    sig_table: engine.SigTable | None = None


@dataclasses.dataclass
class PreparedPlan:
    plan: Plan
    sides: list[PreparedSide]
    max_entity_len: int


#: candidates verified per step. The verify stage's [N, K] and
#: [N, K, L] gathers have small minor axes that the TPU pads to its
#: (8, 128) tile, so their working set is bounded by verifying larger
#: candidate buffers in chunks of this many rows.
VERIFY_ROWS = 1 << 17


def side_matches(cands: dict, side: PreparedSide, result_capacity: int) -> Matches:
    """Probe + verify one prepared side over compacted candidates.

    Module-level so state that outlives (or never had) an operator can
    execute it — the live-updates subsystem probes base and delta-
    segment ``PreparedSide``s of *pinned past epochs* through here
    (``updates.builders.epoch_side_matches``) while the session's
    operator has already moved on to a compacted base. Buffers of more
    than ``VERIFY_ROWS`` candidates are verified chunk by chunk (a
    ``lax.scan``) and merged in candidate order, so the result equals
    the one-shot verify and ``count`` stays the true match total.
    """
    n = cands["win_valid"].shape[0]
    if n <= VERIFY_ROWS:
        return _verify_side(cands, side, result_capacity)
    scalars = {k: cands[k] for k in ("n_survive", "overflow")}
    rows = {k: v for k, v in cands.items() if k not in scalars}

    def step(acc, i):
        # the last chunk slides back to stay in bounds; its rows that
        # an earlier chunk already verified are masked invalid
        start = jnp.minimum(i * VERIFY_ROWS, n - VERIFY_ROWS)
        chunk = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, start, VERIFY_ROWS),
            rows)
        fresh = start + jnp.arange(VERIFY_ROWS) >= i * VERIFY_ROWS
        chunk["win_valid"] = chunk["win_valid"] & fresh
        m = _verify_side({**chunk, **scalars}, side, result_capacity)
        return merge_matches(acc, m, result_capacity), None

    empty = Matches(
        doc=jnp.full((result_capacity,), -1, jnp.int32),
        pos=jnp.full((result_capacity,), -1, jnp.int32),
        length=jnp.full((result_capacity,), -1, jnp.int32),
        entity=jnp.full((result_capacity,), -1, jnp.int32),
        score=jnp.zeros((result_capacity,), jnp.float32),
        count=jnp.int32(0),
    )
    out, _ = jax.lax.scan(step, empty, jnp.arange(-(-n // VERIFY_ROWS)))
    return out


def _verify_side(cands: dict, side: PreparedSide,
                 result_capacity: int) -> Matches:
    if side.side.algo == ALGO_INDEX:
        m: Matches | None = None
        for part in side.index_parts:
            pm = engine.extract_index_part(cands, part, side.ddict, side.params)
            m = pm if m is None else merge_matches(m, pm, result_capacity)
        return m
    return engine.extract_ssjoin_local(
        cands, side.sig_table, side.ddict, side.params
    )


class EEJoinOperator:
    def __init__(self, dictionary: Dictionary, config: EEJoinConfig = EEJoinConfig()):
        self.dictionary = dictionary
        self.config = config

    # -- §"a means to gather data statistics" --------------------------------
    def gather_statistics(
        self, sample_docs: np.ndarray, total_docs: int, num_shuffle_buckets: int = 256
    ) -> EEStats:
        return gather_stats(
            self.dictionary,
            sample_docs,
            total_docs,
            self.config.gamma,
            lsh=self.config.lsh,
            num_shuffle_buckets=num_shuffle_buckets,
        )

    # -- §5 optimisation ------------------------------------------------------
    def choose_plan(self, stats: EEStats, cost_params: CostParams | None = None) -> Plan:
        return search_plan(
            stats,
            cost_params or CostParams(num_devices=1),
            self.config.objective,
            options=self.config.options,
        )

    # -- plan -> device structures -------------------------------------------
    def _prepare_side(
        self, side: PlanSide, a: int, b: int, hbm_budget: float
    ) -> PreparedSide | None:
        if a >= b:
            return None
        cfg = self.config
        sl = self.dictionary.slice(a, b)
        ddict = engine.DeviceDictionary.from_host(sl, entity_offset=a)
        flt = None
        if cfg.use_filter:
            f = build_ish_filter(sl, cfg.gamma, num_bits=cfg.filter_bits)
            flt = (jnp.asarray(f.bits), f.num_bits, f.num_hashes)
        params = engine.ExtractParams(
            gamma=cfg.gamma,
            scheme=side.scheme,
            sim_name=cfg.sim_name,
            use_filter=cfg.use_filter,
            max_candidates=cfg.max_candidates,
            result_capacity=cfg.result_capacity,
            lsh=cfg.lsh,
            use_kernel=cfg.use_kernel,
            adaptive_lanes=cfg.adaptive_lanes,
            lane_width=cfg.lane_width,
            kernel_sigs=cfg.kernel_sigs,
            streamed=cfg.streamed,
        )
        prepared = PreparedSide(side=side, params=params, ddict=ddict, flt=flt)
        if side.algo == ALGO_INDEX:
            prepared.index_parts = engine.build_index_partitions(
                sl, side.scheme, cfg.gamma, int(hbm_budget), entity_offset=a
            )
        elif side.algo == ALGO_SSJOIN:
            esig = entity_signatures(side.scheme, sl, cfg.gamma, cfg.lsh)
            prepared.sig_table = engine.build_sig_table(esig, entity_offset=a)
        else:
            raise ValueError(side.algo)
        return prepared

    def prepare(
        self, plan: Plan, cost_params: CostParams | None = None
    ) -> PreparedPlan:
        cp = cost_params or CostParams(num_devices=1)
        E = self.dictionary.num_entities
        sides = []
        head = self._prepare_side(plan.head, 0, plan.split, cp.hbm_budget_bytes)
        tail = self._prepare_side(plan.tail, plan.split, E, cp.hbm_budget_bytes)
        for s in (head, tail):
            if s is not None:
                sides.append(s)
        return PreparedPlan(plan=plan, sides=sides, max_entity_len=self.dictionary.max_len)

    # -- distributed preparation / execution ----------------------------------
    def prepare_distributed(
        self, plan: Plan, n_workers: int, cost_params: CostParams | None = None
    ) -> PreparedPlan:
        """Like prepare(), but SSJoin sides get owner-sharded signature
        tables (stacked [n_workers, ...]) for the all_to_all shuffle."""
        from repro.extraction.distributed import build_sharded_sig_tables

        prepared = self.prepare(plan, cost_params)
        for side in prepared.sides:
            if side.side.algo == ALGO_SSJOIN:
                a = side.ddict.entity_offset
                b = a + side.ddict.tokens.shape[0]
                esig = entity_signatures(
                    side.side.scheme,
                    self.dictionary.slice(a, b),
                    self.config.gamma,
                    self.config.lsh,
                )
                side.sig_table, _ = build_sharded_sig_tables(
                    esig, n_workers, entity_offset=a
                )
        return prepared

    def execute_distributed(
        self, prepared: PreparedPlan, doc_tokens, mesh, axis_names: tuple[str, ...]
    ):
        """Run every plan side on the mesh; returns (list[Matches], diags)."""
        from repro.extraction import distributed as D

        out, diags = [], []
        for side in prepared.sides:
            if side.side.algo == ALGO_INDEX:
                m = D.distributed_extract_index(
                    mesh, axis_names, doc_tokens, side, prepared.max_entity_len
                )
                diags.append(None)
            else:
                m, diag = D.distributed_extract_ssjoin(
                    mesh, axis_names, doc_tokens, side, prepared.max_entity_len
                )
                diags.append(diag)
            out.append(m)
        return out, diags

    # -- execution (single shard; distributed wrapper in extraction/) --------
    def side_matches(self, cands: dict, side: PreparedSide) -> Matches:
        """Probe + verify one prepared side over compacted candidates.

        Public because it is the verify-stage body of the serving
        pipeline (``repro.serving.service``): any candidate front end
        that produces the ``compact_candidates`` dict — single-call,
        sharded streaming, or a served micro-batch lane — feeds the
        same probe+verify join through here.
        """
        return side_matches(cands, side, self.config.result_capacity)

    def execute_epoch(self, state, doc_tokens) -> Matches:
        """Versioned execution against one live-updates epoch.

        ``state`` is an ``updates.builders.EpochState``: every plan
        side probes its base structures plus the open delta segments
        over one shared candidate pass, and tombstoned entities are
        masked after the merge. Epoch 0 of an unchanged dictionary is
        bit-identical to ``execute``.
        """
        from repro.updates.builders import execute_epoch as _exec

        return _exec(state, doc_tokens, self.config)

    def execute(self, prepared: PreparedPlan, doc_tokens) -> Matches:
        cfg = self.config
        out: Matches | None = None
        for side in prepared.sides:
            if cfg.use_kernel:
                # fused megakernel: one pass emits survival + (lsh) sigs
                cands = engine.fused_filter_compact(
                    doc_tokens, prepared.max_entity_len, side.flt, side.params
                )
            else:
                base, surv = engine.survival_mask(
                    doc_tokens, prepared.max_entity_len, side.flt, False
                )
                cands = engine.compact_candidates(
                    base, surv, side.params.max_candidates
                )
            m = self.side_matches(cands, side)
            out = m if out is None else merge_matches(out, m, cfg.result_capacity)
        assert out is not None, "empty plan"
        return out

    def execute_sharded(
        self,
        prepared: PreparedPlan,
        doc_tokens,
        mesh=None,
        axis_name: str = "workers",
        shard_docs: int | None = None,
        tile_docs: int | None = None,
        checkpoint_dir: str | None = None,
        stream_stats: dict | None = None,
    ) -> Matches:
        """Streaming execution: the sharded per-device ``fused_probe``
        driver feeds the candidate front end (documents split into
        shards, each device streaming its shard's tiles with the
        in-kernel compaction epilogue), then each plan side verifies
        over the merged global candidate buffer. Bit-identical to
        ``execute`` with ``use_kernel=True``; requires it (candidate
        streaming is a kernel-path feature). With ``mesh=None`` shards
        stream sequentially on the local device. ``checkpoint_dir``
        makes the candidate waves resumable (per-shard lane
        checkpoints, one subdirectory per plan side)."""
        from repro.extraction import sharded as S

        assert self.config.use_kernel, "execute_sharded requires use_kernel=True"
        cfg = self.config
        out: Matches | None = None
        for i, side in enumerate(prepared.sides):
            cands = S.sharded_filter_compact(
                doc_tokens,
                prepared.max_entity_len,
                side.flt,
                side.params,
                mesh=mesh,
                axis_name=axis_name,
                shard_docs=shard_docs,
                tile_docs=tile_docs,
                checkpoint_dir=None if checkpoint_dir is None
                else f"{checkpoint_dir}/side{i}",
                stream_stats=stream_stats,
            )
            m = self.side_matches(cands, side)
            out = m if out is None else merge_matches(out, m, cfg.result_capacity)
        assert out is not None, "empty plan"
        return out

    def execute_corpus(
        self,
        prepared: PreparedPlan,
        corpus,
        shard_docs: int | None = None,
        tile_docs: int | None = None,
        checkpoint_dir: str | None = None,
        stream_stats: dict | None = None,
        fail_after_shards: int | None = None,
    ) -> Matches:
        """Corpus-scale execution over a *file-backed* document set.

        ``corpus`` is a ``sharded.MemmapCorpus`` (or any host [D, T]
        int32 array): shards are file regions staged through one
        reusable host buffer and probed by the single-launch streamed
        megakernel — the corpus is never device-resident, so it may
        exceed the device budget (``config.device_budget_bytes`` sizes
        the shards). With ``checkpoint_dir`` the per-shard lanes are
        persisted (one subdirectory per plan side) and an interrupted
        run resumes to bit-identical merged matches. Verification runs
        over the merged candidate buffer exactly as in ``execute``.
        ``stream_stats`` accumulates ``spill_filter_compact``'s counters
        over every side (``windows`` and ``survivors`` among them).
        """
        from repro.extraction import sharded as S

        assert self.config.use_kernel, "execute_corpus requires use_kernel=True"
        cfg = self.config
        out: Matches | None = None
        with span("eejoin.execute_corpus"):
            for i, side in enumerate(prepared.sides):
                cands = S.spill_filter_compact(
                    corpus,
                    prepared.max_entity_len,
                    side.flt,
                    side.params,
                    device_budget_bytes=cfg.device_budget_bytes,
                    shard_docs=shard_docs,
                    tile_docs=tile_docs,
                    checkpoint_dir=None if checkpoint_dir is None
                    else f"{checkpoint_dir}/side{i}",
                    stream_stats=stream_stats,
                    fail_after_shards=fail_after_shards,
                )
                with span("eejoin.verify"):
                    m = self.side_matches(cands, side)
                if out is None:
                    out = m
                else:
                    with span("eejoin.matches.merge"):
                        out = merge_matches(out, m, cfg.result_capacity)
        assert out is not None, "empty plan"
        return out
