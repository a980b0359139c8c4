"""Replica server: snapshot bootstrap + delta replay + epoch-acked serving.

A replica is a verify/serving process that holds its own built
extraction state and keeps it bit-identical to the coordinator's by
construction:

* **bootstrap** — a session ships once as a *compacted base snapshot*
  (``snapshot_session``): the ``DictionaryVersion`` bytes plus the
  JSON-coded config / plan / cost params. The replica rebuilds the
  session locally (filters, signature tables, indexes) — structures
  are deterministic functions of (dictionary, config, plan), so
  rebuilding from the same bytes yields the same state without ever
  shipping device structures.
* **replication** — every subsequent change ships as the serialized
  ``DictionaryDelta`` (or replan) *with the maintenance action the
  coordinator actually took* (``force_action``). Replaying the same
  (delta, action) chain through the same ``apply_delta`` code path
  reproduces the same epoch numbers and the same global entity id
  space — compaction renumbers identically on every host.
* **epoch agreement** — each applied change is acked with the
  replica's resulting epoch; the coordinator routes a request admitted
  at epoch E only to replicas that acked >= E. The replica holds a
  retention pin on every epoch it has built and releases it on the
  coordinator's RELEASE frame (cluster-wide drain), so a request at a
  past epoch still finds its exact state.

Requests execute through the same ``updates.builders`` entry points as
single-host serving: ``execute_epoch`` for full documents (FT_REQUEST)
and the lane-verify path (FT_LANES) for the remote half of
``ExtractionService``'s probe→verify split.
"""
from __future__ import annotations

import dataclasses
import json
import socket

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.cost_model import CostParams
from repro.core.eejoin import EEJoinConfig, PreparedPlan
from repro.core.plan import Plan, PlanSide
from repro.core.signatures import LshParams
from repro.fabric.transport import SocketChannel, serve_frames
from repro.fabric.wire import (
    FT_ACK,
    FT_DELTA,
    FT_LANES,
    FT_MATCHES,
    FT_RELEASE,
    FT_REQUEST,
    FT_SHUTDOWN,
    FT_SNAPSHOT,
    FT_STATS,
    Frame,
    encode_frame,
    matches_to_wire,
)
from repro.serving.session import DictionarySession, SessionCache, pure_plan
from repro.updates.delta import (
    DictionaryDelta,
    DictionaryVersion,
    pack_arrays,
    unpack_arrays,
)

# ------------------------------------------------------------ JSON codecs
# Config / plan / cost-params travel as JSON inside payload headers.
# Reconstruction must restore *exact* types — ``dictionary_fingerprint``
# folds in ``repr(config)``, so a list where a tuple was, or a dict
# where an LshParams was, would silently give the replica a different
# session key than the coordinator's.


def config_to_json(cfg: EEJoinConfig) -> dict:
    d = dataclasses.asdict(cfg)
    d["lsh"] = {"bands": cfg.lsh.bands, "rows": cfg.lsh.rows}
    d["options"] = [list(o) for o in cfg.options]
    return d


def config_from_json(d: dict) -> EEJoinConfig:
    d = dict(d)
    d["lsh"] = LshParams(**d["lsh"])
    d["options"] = tuple(tuple(o) for o in d["options"])
    return EEJoinConfig(**d)


def plan_to_json(plan: Plan) -> dict:
    # only the executable identity of the plan travels: split + sides +
    # objective fully determine ``prepare``; cost predictions are local
    # diagnostics and are zeroed on the far side (pure_plan pattern)
    return {
        "split": int(plan.split),
        "head": [plan.head.algo, plan.head.scheme],
        "tail": [plan.tail.algo, plan.tail.scheme],
        "objective": plan.objective,
    }


def plan_from_json(d: dict) -> Plan:
    z = pure_plan("prefix")  # donor for zeroed cost fields
    return Plan(
        split=int(d["split"]),
        head=PlanSide(*d["head"]),
        tail=PlanSide(*d["tail"]),
        objective=d["objective"],
        predicted_cost=0.0,
        head_cost=z.head_cost,
        tail_cost=z.tail_cost,
        evaluations=0,
    )


def cost_params_to_json(cp: CostParams) -> dict:
    return dataclasses.asdict(cp)


def cost_params_from_json(d: dict) -> CostParams:
    return CostParams(**d)


# ------------------------------------------------------- snapshot payloads


def snapshot_session(sess: DictionarySession) -> bytes:
    """Bootstrap payload: compacted base version + config/plan/params.

    Requires the current epoch to be segment- and tombstone-free (a
    compacted base): open segments can't be reconstructed by a session
    build, only replayed — snapshot at session creation or right after
    a compaction, then ship the delta stream.
    """
    state = sess.current_state
    version = state.version
    if version.num_segments or bool(version.tombstones.any()):
        raise ValueError(
            f"snapshot_session: epoch {sess.epoch} has "
            f"{version.num_segments} open segment(s) and "
            f"{int(version.tombstones.sum())} tombstone(s); replicas "
            "bootstrap from a compacted base only — snapshot before "
            "applying deltas, or after a compact"
        )
    meta = {
        "kind": "session_snapshot",
        "session": sess.key,
        "epoch": int(sess.epoch),
        "config": config_to_json(sess.config),
        "plan": plan_to_json(state.plan),
        "cost_params": cost_params_to_json(
            sess.cost_params or CostParams(num_devices=1)
        ),
    }
    blob = version.to_bytes()
    return pack_arrays(meta, {
        "version": np.frombuffer(blob, dtype=np.uint8).copy()
    })


def encode_delta_ship(session_key: str, parent_epoch: int, action: str,
                      delta: DictionaryDelta,
                      sample_docs: np.ndarray | None = None) -> bytes:
    """One replicated update: the delta bytes + the forced action."""
    meta = {
        "kind": "delta_ship",
        "session": session_key,
        "parent_epoch": int(parent_epoch),
        "action": action,
    }
    arrays = {
        "delta": np.frombuffer(delta.to_bytes(), dtype=np.uint8).copy()
    }
    if sample_docs is not None:
        arrays["sample_docs"] = np.asarray(sample_docs, dtype=np.int32)
    return pack_arrays(meta, arrays)


def encode_replan_ship(session_key: str, parent_epoch: int, plan: Plan,
                       cost_params: CostParams) -> bytes:
    return pack_arrays({
        "kind": "replan_ship",
        "session": session_key,
        "parent_epoch": int(parent_epoch),
        "plan": plan_to_json(plan),
        "cost_params": cost_params_to_json(cost_params),
    }, {})


def encode_request(session_key: str, epoch: int,
                   docs: np.ndarray) -> bytes:
    return pack_arrays(
        {"kind": "extract_request", "session": session_key,
         "epoch": int(epoch)},
        {"docs": np.asarray(docs, dtype=np.int32)},
    )


def verify_lanes_on_state(state, config: EEJoinConfig, docs: np.ndarray,
                          lanes: list):
    """The verify stage over shipped lanes — remote half of
    ``ExtractionService._verify_batch``.

    ``lanes`` is the wire list: per plan side ``(count [1] i32,
    lane [1, NC] i32, keys [1, NC, 2] u32 | None)``. Returns
    ``(Matches, overflow)``; bit-identical to running the local verify
    stage because it is the same sequence of calls over the same
    (replicated) epoch state.
    """
    from repro.extraction import engine
    from repro.extraction.results import (
        check_lanes,
        filter_matches,
        gather_from_tiles,
        merge_matches,
        select_from_tiles,
    )
    from repro.updates.builders import epoch_side_matches

    if len(lanes) != len(state.sides):
        raise ValueError(
            f"lane frame has {len(lanes)} sides, epoch state has "
            f"{len(state.sides)} — plan mismatch between hosts"
        )
    docs_j = jnp.asarray(np.asarray(docs, dtype=np.int32))
    out = None
    overflow = 0
    for eside, (count, lane, keys) in zip(state.sides, lanes):
        count = jnp.asarray(count)
        lane = jnp.asarray(lane)
        NC = eside.params.max_candidates
        # lanes decoded from a frame come from outside this process
        check_lanes(count, lane.shape[1], NC)
        sel, ok, n = select_from_tiles(count, lane, NC)
        cands = engine.candidates_from_flat(
            docs_j, sel, ok, n, state.max_len, NC
        )
        if keys is not None:
            cands = engine.attach_variant_keys(
                cands, gather_from_tiles(count, jnp.asarray(keys), NC)
            )
        overflow += int(cands["overflow"])
        m = epoch_side_matches(cands, eside, config.result_capacity)
        out = m if out is None else merge_matches(
            out, m, config.result_capacity
        )
    if state.has_tombstones:
        out = filter_matches(out, state.live, config.result_capacity)
    return out, overflow


class ReplicaServer:
    """One replica's sessions + the frame handler driving them.

    ``device`` pins the replica: every frame is handled under
    ``jax.default_device(device)``, so its sessions' structures live
    and its requests run on that device (one chip per replica when
    several serve from one process). ``None`` keeps JAX's default.
    """

    def __init__(self, name: str, device=None):
        self.name = name
        self.device = device
        # build logic reuses SessionCache.get_or_create; lookup happens
        # on this dict under the *coordinator's* session key (which may
        # differ from the local fingerprint when the snapshot was taken
        # after a compaction changed the dictionary bytes)
        self._cache = SessionCache(max_sessions=64)
        self.sessions: dict[str, DictionarySession] = {}
        self.requests_served = 0
        self.lane_batches_served = 0
        self.deltas_applied = 0
        self.replans_applied = 0
        self.released_epochs = 0

    # ------------------------------------------------------------ handlers
    def _bootstrap(self, payload: bytes) -> tuple[int, bytes]:
        meta, arrays = unpack_arrays(payload)
        if meta.get("kind") != "session_snapshot":
            raise ValueError(f"SNAPSHOT payload kind {meta.get('kind')!r}")
        version = DictionaryVersion.from_bytes(arrays["version"].tobytes())
        if version.num_segments or bool(version.tombstones.any()):
            raise ValueError(
                "snapshot is not a compacted base (open segments or "
                "tombstones present)"
            )
        config = config_from_json(meta["config"])
        plan = plan_from_json(meta["plan"])
        cp = cost_params_from_json(meta["cost_params"])
        sess = self._cache.get_or_create(
            version.base, config, plan=plan, cost_params=cp
        )
        snap_epoch = int(meta["epoch"])
        if snap_epoch != sess.epoch:
            # snapshot taken at a compacted epoch > 0: adopt the
            # coordinator's numbering so the replayed delta chain and
            # the acks line up
            state = sess.epochs.pop(sess.epoch)
            state.epoch = snap_epoch
            state.version = dataclasses.replace(
                state.version, epoch=snap_epoch
            )
            sess.epochs[snap_epoch] = state
            sess.epoch = snap_epoch
        key = meta["session"]
        self.sessions[key] = sess
        # retention pin: the bootstrap epoch stays until RELEASEd
        sess.epochs[sess.epoch].pins += 1
        return self._ack(key, sess)

    def _ack(self, key: str, sess: DictionarySession) -> tuple[int, bytes]:
        return FT_ACK, json.dumps({
            "replica": self.name,
            "session": key,
            "epoch": int(sess.epoch),
        }).encode()

    def _session(self, key: str) -> DictionarySession:
        sess = self.sessions.get(key)
        if sess is None:
            raise KeyError(
                f"replica {self.name}: unknown session {key!r} "
                "(not bootstrapped)"
            )
        return sess

    def _apply_delta(self, payload: bytes) -> tuple[int, bytes]:
        meta, arrays = unpack_arrays(payload)
        kind = meta.get("kind")
        sess = self._session(meta["session"])
        parent = int(meta["parent_epoch"])
        if sess.epoch != parent:
            raise ValueError(
                f"replica {self.name}: delta parented at epoch {parent} "
                f"but session {meta['session']} is at {sess.epoch} — "
                "replication gap; re-bootstrap from a fresh snapshot"
            )
        if kind == "delta_ship":
            delta = DictionaryDelta.from_bytes(arrays["delta"].tobytes())
            sample = arrays.get("sample_docs")
            sess.apply_delta(
                delta,
                sample_docs=sample,
                force_action=meta["action"],
            )
            self.deltas_applied += 1
        elif kind == "replan_ship":
            sess.apply_replan(
                plan_from_json(meta["plan"]),
                cost_params_from_json(meta["cost_params"]),
                reason="replicated",
            )
            self.replans_applied += 1
        else:
            raise ValueError(f"DELTA payload kind {kind!r}")
        # retention pin on the new epoch until the coordinator RELEASEs
        # it (apply_delta/apply_replan already GC'd the parent only if
        # it was unpinned — it wasn't, it holds the previous retention
        # pin)
        sess.epochs[sess.epoch].pins += 1
        return self._ack(meta["session"], sess)

    def _state_for(self, sess: DictionarySession, epoch: int):
        if epoch > sess.epoch:
            raise ValueError(
                f"replica {self.name} lags: request at epoch {epoch}, "
                f"applied epoch {sess.epoch} — coordinator must not "
                "route ahead of the ack"
            )
        try:
            return sess.state_for(epoch)
        except KeyError:
            raise ValueError(
                f"replica {self.name}: epoch {epoch} already released"
            ) from None

    def _extract(self, payload: bytes) -> tuple[int, bytes]:
        from repro.updates.builders import execute_epoch

        meta, arrays = unpack_arrays(payload)
        if meta.get("kind") != "extract_request":
            raise ValueError(f"REQUEST payload kind {meta.get('kind')!r}")
        sess = self._session(meta["session"])
        epoch = int(meta["epoch"])
        state = self._state_for(sess, epoch)
        matches = execute_epoch(
            state, jnp.asarray(arrays["docs"]), sess.config
        )
        self.requests_served += 1
        return FT_MATCHES, matches_to_wire(
            matches, {"epoch": epoch, "replica": self.name}
        )

    def _verify_lanes(self, payload: bytes) -> tuple[int, bytes]:
        from repro.extraction.sharded import lanes_from_wire

        meta, docs, lanes = lanes_from_wire(payload)
        sess = self._session(meta["session"])
        epoch = int(meta["epoch"])
        state = self._state_for(sess, epoch)
        matches, overflow = verify_lanes_on_state(
            state, sess.config, docs, lanes
        )
        self.lane_batches_served += 1
        return FT_MATCHES, matches_to_wire(
            matches,
            {"epoch": epoch, "replica": self.name, "overflow": overflow},
        )

    def _release(self, payload: bytes) -> tuple[int, bytes]:
        meta = json.loads(payload.decode())
        sess = self._session(meta["session"])
        epoch = int(meta["epoch"])
        if epoch in sess.epochs:
            sess.unpin_epoch(epoch)
            self.released_epochs += 1
        return self._ack(meta["session"], sess)

    def stats(self) -> dict:
        return {
            "replica": self.name,
            "sessions": {
                k: int(s.epoch) for k, s in self.sessions.items()
            },
            "retained_epochs": {
                k: sorted(int(e) for e in s.epochs)
                for k, s in self.sessions.items()
            },
            "requests_served": self.requests_served,
            "lane_batches_served": self.lane_batches_served,
            "deltas_applied": self.deltas_applied,
            "replans_applied": self.replans_applied,
            "released_epochs": self.released_epochs,
            "devices": sorted(self._session_devices()),
        }

    def _session_devices(self) -> set[str]:
        """Devices holding this replica's session structures."""
        out: set[str] = set()
        for sess in self.sessions.values():
            for side in sess.prepared.sides:
                out |= {str(d) for d in side.ddict.tokens.devices()}
        return out

    def handle(self, frame: Frame):
        """``transport.serve_frames`` handler: dispatch one frame."""
        if self.device is None:
            return self._dispatch(frame)
        with jax.default_device(self.device):
            return self._dispatch(frame)

    def _dispatch(self, frame: Frame):
        if frame.ftype == FT_SNAPSHOT:
            return self._bootstrap(frame.payload)
        if frame.ftype == FT_DELTA:
            return self._apply_delta(frame.payload)
        if frame.ftype == FT_REQUEST:
            return self._extract(frame.payload)
        if frame.ftype == FT_LANES:
            return self._verify_lanes(frame.payload)
        if frame.ftype == FT_RELEASE:
            return self._release(frame.payload)
        if frame.ftype == FT_STATS:
            return FT_STATS, json.dumps(self.stats()).encode()
        if frame.ftype == FT_SHUTDOWN:
            return None  # ends the serve loop; peer sees the close
        raise ValueError(
            f"replica {self.name}: unexpected frame {frame.type_name}"
        )


def replica_main(host: str, port: int, name: str, device=None,
                 idle_timeout: float = 600.0) -> None:
    """Replica serve loop: connect back, announce, serve frames.

    Run on a thread by ``cluster.launch_local_cluster`` — replicas share
    the coordinator's process (a process that has touched JAX holds the
    chip, so it must not start children that need it), each pinned to
    its own ``device``. The hello frame carries the replica name so the
    accepting coordinator can map connections to ring members.
    ``idle_timeout`` bounds orphaned loops: no frame for that long and
    the thread exits.
    """
    sock = socket.create_connection((host, port))
    channel = SocketChannel(sock)
    channel.send(encode_frame(
        FT_ACK, 0, json.dumps({"replica": name}).encode()
    ))
    server = ReplicaServer(name, device)
    try:
        serve_frames(channel, server.handle, idle_timeout=idle_timeout)
    finally:
        channel.close()
