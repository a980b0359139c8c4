"""Fixed-capacity extraction result buffers (static shapes under jit)."""
from __future__ import annotations

import dataclasses
import os

import numpy as np

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Matches:
    """A batch of extraction matches, -1-padded to static capacity.

    doc/pos/length/entity: [R] int32 (-1 where empty); score: [R] f32;
    count: [] int32 true matches (may exceed R if the buffer overflowed —
    overflow is surfaced, never silent).
    """

    doc: jnp.ndarray
    pos: jnp.ndarray
    length: jnp.ndarray
    entity: jnp.ndarray
    score: jnp.ndarray
    count: jnp.ndarray

    def to_set(self) -> set[tuple[int, int, int, int]]:
        """Host-side dedup'd set of (doc, pos, len, entity)."""
        d = np.asarray(self.doc)
        keep = d >= 0
        return set(
            zip(
                np.asarray(self.doc)[keep].tolist(),
                np.asarray(self.pos)[keep].tolist(),
                np.asarray(self.length)[keep].tolist(),
                np.asarray(self.entity)[keep].tolist(),
            )
        )


def select_nonzero(mask, capacity: int):
    """First ``capacity`` flat indices of set bits in ``mask`` (-1 pad).

    Semantically ``jnp.nonzero(mask, size=capacity, fill_value=-1)``,
    but XLA lowers sized-nonzero through a full sort; this prefix-sum +
    ``searchsorted`` selection (the k-th survivor lives where the cumsum
    first reaches k) is ~5x faster on CPU and sort-free on TPU. Returns
    (idx [capacity] int32, ok [capacity] bool).
    """
    flat = mask.reshape(-1)
    c = jnp.cumsum(flat.astype(jnp.int32))
    idx = jnp.searchsorted(
        c, jnp.arange(1, capacity + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    ok = jnp.arange(capacity) < c[-1]
    return jnp.where(ok, idx, -1), ok


def tile_ranks(counts, capacity: int):
    """Global rank -> (tile, within-tile rank) map for per-tile lanes.

    ``counts`` [G] int32 are true per-tile survivor counts. Returns
    ``(g [capacity] int32, within [capacity] int32, ok [capacity] bool,
    total [] int32)``: the tile index and within-tile rank of each of
    the global first ``capacity`` survivors (tiles ordered by ascending
    index range). Shared by ``select_from_tiles`` (index lanes) and
    ``gather_from_tiles`` (payload lanes, e.g. the fused variant keys)
    so both gather the *same* survivors. O(G + capacity).
    """
    G = counts.shape[0]
    cum = jnp.cumsum(counts.astype(jnp.int32))
    total = cum[-1]
    j = jnp.arange(capacity, dtype=jnp.int32)
    ok = j < jnp.minimum(total, capacity)
    g = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    gs = jnp.minimum(g, G - 1)
    within = j - (cum[gs] - counts[gs])
    return gs, within, ok, total


def select_from_tiles(counts, cands, capacity: int):
    """Merge per-tile compacted candidate lanes into one global selection.

    ``counts`` [G] int32 are true per-tile survivor counts (may exceed
    the lane width); ``cands`` [G, C] int32 hold each tile's first C
    survivors as ascending flat indices (-1 pad), tiles ordered by
    ascending index range — the layout the ``fused_probe`` compaction
    epilogue emits. Returns (idx [capacity] int32 -1-padded, ok
    [capacity] bool, total [] int32), bit-identical to running
    ``select_nonzero`` over the full bitmap whenever ``C >= capacity``
    (any candidate inside the global first ``capacity`` has within-tile
    rank < capacity, so lane truncation can never hide it). Cost is
    O(G + capacity) — the [D, T] survival bitmap is never touched.

    A lane narrower than ``capacity`` must be *complete* — hold all of
    its tile's survivors (``max(counts) <= C``) — and the merge is then
    exact at any C. Lanes from the ``kernels.ops`` probe wrappers are:
    sized from a count pass (adaptive two-pass) or capped at the tile's
    window count (``fused_probe.lane_width_for``). Lanes read from
    outside (the wire, a checkpoint) go through ``check_lanes`` first.
    """
    G, C = cands.shape
    gs, within, ok, total = tile_ranks(counts, capacity)
    idx = cands[gs, jnp.clip(within, 0, C - 1)]
    return jnp.where(ok, idx, -1), ok, total


def check_lanes(counts, width: int, capacity: int) -> None:
    """Host check of the ``select_from_tiles`` invariant.

    Raises ``ValueError`` when a lane of ``width`` slots, narrower than
    the merge ``capacity``, drops survivors (some tile's count exceeds
    ``width``): the merge would re-read clipped slots. Reads ``counts``
    to the host only for narrow lanes.
    """
    if width >= capacity:
        return
    mx = int(np.max(np.asarray(counts), initial=0))
    if mx > width:
        raise ValueError(
            f"lane width {width} < merge capacity {capacity} holds only "
            f"{width} of a tile's {mx} survivors"
        )


def gather_from_tiles(counts, payload, capacity: int, fill=0):
    """Gather per-lane payload rows for the ``select_from_tiles`` merge.

    ``payload`` [G, C, ...] carries one record per lane slot (e.g. the
    fused variant key pairs [G, C, 2]); returns the [capacity, ...]
    records of the globally selected survivors, ``fill`` in padded
    slots. Must be driven by the same ``counts`` as the index-lane
    merge so both pick identical survivors.
    """
    G, C = payload.shape[:2]
    gs, within, ok, _ = tile_ranks(counts, capacity)
    out = payload[gs, jnp.clip(within, 0, C - 1)]
    mask = ok.reshape(ok.shape + (1,) * (out.ndim - 1))
    return jnp.where(mask, out, fill)


def save_lane_checkpoint(path: str, lane, count, keys=None) -> None:
    """Persist one shard's lane wire unit ``(lane, count[, keys])`` to disk.

    The lane triple is the complete ``select_from_tiles`` /
    ``gather_from_tiles`` input for that shard — persisting it per shard
    is exactly the resumable-merge state: a restarted corpus job reloads
    finished shards' lanes and re-runs only the missing probes, and the
    final merge is bit-identical because the merge never saw anything
    but these lanes in the first place. Written atomically (tmp file +
    ``os.replace``) so a kill mid-write leaves either the old file or
    none, never a torn one.
    """
    arrays = {
        "lane": np.asarray(lane, dtype=np.int32),
        "count": np.asarray(count, dtype=np.int32),
    }
    if keys is not None:
        arrays["keys"] = np.asarray(keys, dtype=np.uint32)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def load_lane_checkpoint(path: str):
    """Load a shard lane persisted by ``save_lane_checkpoint``.

    Returns ``(lane [1, NC] int32, count [1] int32, keys [1, NC, 2]
    uint32 | None)`` as device arrays, ready to concatenate into the
    ``select_from_tiles`` merge alongside freshly probed lanes.
    """
    with np.load(path) as z:
        lane = jnp.asarray(z["lane"])
        count = jnp.asarray(z["count"])
        keys = jnp.asarray(z["keys"]) if "keys" in z.files else None
    return lane, count, keys


def compact_matches(hit_mask, doc, pos, length, entity, score, capacity: int) -> Matches:
    """Compact flat hit arrays into a fixed-capacity Matches buffer.

    All inputs are flat [N]; ``hit_mask`` selects real matches.
    """
    idx, ok = select_nonzero(hit_mask, capacity)
    take = jnp.maximum(idx, 0)
    return Matches(
        doc=jnp.where(ok, doc[take], -1).astype(jnp.int32),
        pos=jnp.where(ok, pos[take], -1).astype(jnp.int32),
        length=jnp.where(ok, length[take], -1).astype(jnp.int32),
        entity=jnp.where(ok, entity[take], -1).astype(jnp.int32),
        score=jnp.where(ok, score[take], 0.0).astype(jnp.float32),
        count=hit_mask.sum().astype(jnp.int32),
    )


def filter_matches(m: Matches, entity_live, capacity: int) -> Matches:
    """Drop matches whose entity is tombstoned (live-updates emit mask).

    ``entity_live`` is a [total_entities] bool device mask (True =
    live). Tombstoned entities stay inside prepared filter/table/index
    structures — deletes are logical — so their matches are produced
    normally and masked here, after verification, before results leave
    the device. ``count`` becomes the number of *live* matches; like
    every fixed-capacity buffer, matches truncated by an upstream
    overflow are gone before masking (overflow stays surfaced via the
    producing buffer's count).
    """
    keep = (m.doc >= 0) & entity_live[jnp.maximum(m.entity, 0)]
    return compact_matches(
        keep, m.doc, m.pos, m.length, m.entity, m.score, capacity
    )


def merge_matches(a: Matches, b: Matches, capacity: int) -> Matches:
    """Merge two buffers into one of ``capacity`` (dedup NOT performed).

    ``count`` is the sum of both true counts, so an overflow in either
    input (or in the merge) stays surfaced.
    """
    doc = jnp.concatenate([a.doc, b.doc])
    hit = doc >= 0
    m = compact_matches(
        hit,
        doc,
        jnp.concatenate([a.pos, b.pos]),
        jnp.concatenate([a.length, b.length]),
        jnp.concatenate([a.entity, b.entity]),
        jnp.concatenate([a.score, b.score]),
        capacity,
    )
    return dataclasses.replace(m, count=(a.count + b.count).astype(jnp.int32))
