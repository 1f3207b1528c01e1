"""Geo-replication tier: region-aware slot map, quorum group-commit,
follower snapshot reads (the ROADMAP's "millions of users" shape —
traffic survives a region, reads scale on followers).

The reference's replication stops at active-passive log sinks
(`REPL_TYPE` `config.h:24-27`: a replica acks LOG_MSG bytes it never
reads back).  This tier grows three things on top of the existing
epoch-quantized machinery, all off one ``Config.geo`` gate (default off
= today's paths bit-identically):

**Region-aware slot map.**  The PR 4 membership map said ``slot ->
owner``; here every slot reads as ``slot -> (primary, replica set,
region)`` (`GeoMap`): the primary is the slot-map owner, its replicas
are the log followers backing it (placed in OTHER regions — replica k
of primary p homes in region ``(region(p) + 1 + k) % R``, so a region
loss never takes a primary together with all of its replicas), and the
region is the primary's.  Clients use the same map for nearest-primary
writes and nearest-follower reads.

**Quorum group-commit.**  The primary's group boundary already gates
held CL_RSPs on local flush + every replica's LOG_RSP.  In geo mode the
replica answers with LOG_ACK (acked epoch + its applied horizon) and
the gate becomes a QUORUM: a boundary is durable once ``geo_quorum`` of
``replica_cnt`` followers acked it (`quorum_ack`) — a slow or dead WAN
follower no longer blocks commit latency, exactly the epoch-boundary
cut that epoch-based geo-replication schemes exploit (PAPERS:
*Epoch-based Optimistic Concurrency Control in Geo-replicated
Databases*).

**Follower snapshot reads.**  A geo replica is no longer a blind sink:
`GeoFollower` replays the merged command stream (every primary logs the
IDENTICAL merged record, so one primary's stream is the whole cluster's
writes) group-by-group through the per-epoch jit with FULL slot
ownership over the elastic full-residency tables.  Between group
applies its tables are exactly the epoch-boundary state, so a
REGION_READ serves a consistent boundary snapshot without ever touching
(or blocking) the primaries' OLTP loop.  Each applied group also pushes
the written rows into a `storage.table.VersionRing` keyed by the
boundary id — the lockless read-set/version-check shape (PAPERS:
*Lockless Transaction Isolation in Hyperledger Fabric*): every
REGION_READ_RSP carries the per-row version stamp next to the value,
and clients verify ``version <= served boundary`` on every response.

**Failover.**  Region loss (``fault_kill`` under geo = the region's
server AND the replicas homed there) promotes through the PR 4
dead-peer reassignment path: every surviving server stalls at the same
first-missing epoch, installs the same reassignment map, and rebuilds
the lost slots by replaying its own log to that boundary — counted as
``promote_cnt`` and emitted as a ``promote`` replication span.  The
lost primary's followers (homed elsewhere) keep serving reads across
the takeover.

WAN profiles ride the native transport's per-link delay hook
(`dt_set_peer_delay_us`): ``geo_wan_us`` names region-pair one-way
delays and `apply_wan_profile` stamps them onto every link at node
start — asymmetric matrices model asymmetric routes.

Wire bodies (rtypes 18-20, outside ``FAULT_RTYPE_MASK`` like the
membership rtypes 15-17 — commit protocol / control plane, not
open-loop traffic):

* LOG_ACK          replica -> primary: (acked epoch, applied epoch).
* REGION_READ      client -> replica: (tag, key batch).
* REGION_READ_RSP  replica -> client: (tag, served boundary, values,
                   per-row version stamps).
"""

from __future__ import annotations

import json
import os
import struct
import time

import jax
import numpy as np

from deneva_tpu.config import Config
from deneva_tpu.engine.epoch import make_dist_step

# ---- region assignment -------------------------------------------------

def region_of(cfg: Config, tid: int) -> int:
    """Region of transport id ``tid`` (servers, clients, replicas).

    Servers and clients deal block-wise over ``geo_region_cnt``;
    replica k of primary p homes in region ``(region(p) + 1 + k) % R``
    so a primary's replicas always live in other regions (the placement
    that makes region loss survivable)."""
    r = max(1, cfg.geo_region_cnt)
    n_srv, n_cl = cfg.node_cnt, cfg.client_node_cnt
    if tid < n_srv:
        return tid * r // n_srv
    if tid < n_srv + n_cl:
        return (tid - n_srv) * r // max(1, n_cl)
    k, p = divmod(tid - n_srv - n_cl, n_srv)
    return (region_of(cfg, p) + 1 + k) % r


def replica_ids_of(cfg: Config, primary: int) -> list[int]:
    """Transport ids of the replicas backing ``primary`` (layout
    [servers | clients | replicas], replica r backs primary r % n_srv)."""
    base = cfg.node_cnt + cfg.client_node_cnt
    return [base + primary + k * cfg.node_cnt
            for k in range(cfg.replica_cnt)]


def link_cost(wan: dict, a_region: int, b_region: int) -> tuple[int, int]:
    """Sort key for "nearest": same-region first, then by the WAN
    profile's one-way delay (0 when unprofiled)."""
    return (0 if a_region == b_region else 1,
            wan.get((a_region, b_region), 0))


def server_tiers(cfg: Config, my_region: int) -> list[list[int]]:
    """Server ids grouped into ascending-cost tiers from ``my_region``
    (the client's nearest-primary write preference: all of tier 0, then
    tier 1 when tier 0 has no active server, ...)."""
    wan = cfg.geo_wan_spec()
    by_cost: dict[tuple[int, int], list[int]] = {}
    for s in range(cfg.node_cnt):
        by_cost.setdefault(
            link_cost(wan, my_region, region_of(cfg, s)), []).append(s)
    return [by_cost[c] for c in sorted(by_cost)]


def follower_order(cfg: Config, my_region: int) -> list[int]:
    """All follower (replica) transport ids, nearest-first from
    ``my_region`` (the client's snapshot-read target preference)."""
    wan = cfg.geo_wan_spec()
    base = cfg.node_cnt + cfg.client_node_cnt
    rids = range(base, base + cfg.replica_cnt * cfg.node_cnt)
    return sorted(rids, key=lambda rid: (*link_cost(
        wan, my_region, region_of(cfg, rid)), rid))


def apply_wan_profile(tp, cfg: Config, me: int) -> int:
    """Stamp the WAN latency profile onto every outbound link of ``tp``
    (per-link `dt_set_peer_delay_us`); returns the number of delayed
    links.  A node in region A sends to a node in region B with the
    profile's one-way A->B delay added — asymmetric entries model
    asymmetric routes."""
    wan = cfg.geo_wan_spec()
    if not wan:
        return 0
    mine = region_of(cfg, me)
    n = 0
    n_all = (cfg.node_cnt + cfg.client_node_cnt
             + cfg.replica_cnt * cfg.node_cnt)
    for peer in range(n_all):
        if peer == me:
            continue
        d = wan.get((mine, region_of(cfg, peer)), 0)
        if d:
            tp.set_peer_delay_us(peer, d)
            n += 1
    return n


# ---- region-aware slot map ---------------------------------------------

class GeoMap:
    """``slot -> (primary, replica set, region)`` view over a membership
    `SlotMap`: the geo extension of PR 4's ``slot -> owner``.  Pure
    derivation — the slot map stays the single routing authority, so a
    rebalance or a dead-peer reassignment updates the geo view for
    free."""

    def __init__(self, cfg: Config, smap):
        self.cfg = cfg
        self.smap = smap

    def primary_of(self, slot: int) -> int:
        return int(self.smap.owners[slot])

    def replicas_of(self, slot: int) -> tuple[int, ...]:
        return tuple(replica_ids_of(self.cfg, self.primary_of(slot)))

    def region_of_slot(self, slot: int) -> int:
        return region_of(self.cfg, self.primary_of(slot))

    def describe(self, slot: int) -> tuple[int, tuple[int, ...], int]:
        """The issue's triple: (primary, replica set, region)."""
        return (self.primary_of(slot), self.replicas_of(slot),
                self.region_of_slot(slot))


# ---- quorum group-commit -----------------------------------------------

def quorum_ack(acked: list[int], quorum: int) -> int:
    """Highest epoch acked by at least ``quorum`` of the replicas
    (0 = all of them, the pre-geo gate).  With q < n the q-th highest
    ack is the horizon — stragglers stop gating commit latency."""
    if not acked:
        return -1
    q = quorum if quorum else len(acked)
    return sorted(acked, reverse=True)[min(q, len(acked)) - 1]


def durable_quorum(acked: dict[int, int], alive, quorum: int,
                   flushed: int) -> int:
    """The primary's commit horizon: ``flushed`` capped by the quorum
    over the LIVE follower set.  A dead follower (region loss) leaves
    the ack set and ``quorum_ack``'s clamp shrinks the quorum to the
    survivors — durability margin degrades instead of commit wedging
    forever behind an ack that can never come (the whole point of the
    tier is that traffic SURVIVES a region).  With no follower left the
    gate falls back to local flush alone, exactly the replica_cnt=0
    contract."""
    live = [e for rid, e in acked.items() if alive(rid)]
    if not live:
        return flushed
    return min(flushed, quorum_ack(live, quorum))


# ---- wire codecs -------------------------------------------------------
# LOG_ACK body:          acked i64 | applied i64
# REGION_READ body:      tag i64 | n u32 | keys i32[n]
# REGION_READ_RSP body:  tag i64 | boundary i64 | n u32
#                        | values u32[n] | vers i32[n]
_ACK = struct.Struct("<qq")
_RR = struct.Struct("<qI")
_RRSP = struct.Struct("<qqI")


def encode_log_ack(acked: int, applied: int) -> bytes:
    return _ACK.pack(acked, applied)


def decode_log_ack(buf: bytes) -> tuple[int, int]:
    """-> (acked epoch, follower applied epoch)."""
    return _ACK.unpack_from(buf)


def region_read_parts(tag: int, keys: np.ndarray) -> list:
    """REGION_READ as sendv parts; concatenated == encode_region_read."""
    keys = np.ascontiguousarray(keys, np.int32)
    return [_RR.pack(tag, len(keys)), keys]


def encode_region_read(tag: int, keys: np.ndarray) -> bytes:
    return b"".join(bytes(p) for p in region_read_parts(tag, keys))


def decode_region_read(buf: bytes) -> tuple[int, np.ndarray]:
    tag, n = _RR.unpack_from(buf)
    return tag, np.frombuffer(buf, np.int32, count=n, offset=_RR.size)


def region_read_rsp_parts(tag: int, boundary: int, values: np.ndarray,
                          vers: np.ndarray) -> list:
    """REGION_READ_RSP as sendv parts (the follower's serve hot path);
    concatenated == encode_region_read_rsp of the same columns."""
    values = np.ascontiguousarray(values, np.uint32)
    vers = np.ascontiguousarray(vers, np.int32)
    return [_RRSP.pack(tag, boundary, len(values)), values, vers]


def encode_region_read_rsp(tag: int, boundary: int, values: np.ndarray,
                           vers: np.ndarray) -> bytes:
    return b"".join(bytes(p) for p in region_read_rsp_parts(
        tag, boundary, values, vers))


def decode_region_read_rsp(buf: bytes
                           ) -> tuple[int, int, np.ndarray, np.ndarray]:
    """-> (tag, served boundary epoch, values u32[n], row versions
    i32[n] — the boundary id of each row's newest overwrite, 0 = load
    base; consistency contract: vers <= boundary)."""
    tag, boundary, n = _RRSP.unpack_from(buf)
    off = _RRSP.size
    values = np.frombuffer(buf, np.uint32, count=n, offset=off)
    vers = np.frombuffer(buf, np.int32, count=n, offset=off + 4 * n)
    return tag, boundary, values, vers


def replication_line(node: int, role: str, region: int, **fields) -> str:
    """The per-node ``[replication]`` log line (parsed by
    `harness.parse.parse_replication`); float fields print with one
    decimal, everything else as ints."""
    body = " ".join(
        f"{k}={v:.1f}" if isinstance(v, float) else f"{k}={int(v)}"
        for k, v in fields.items())
    return (f"[replication] node={node} role={role} region={region}"
            + (f" {body}" if body else ""))


# ---- follower state machine --------------------------------------------

def follower_boot(cfg: Config, primary: int):
    """(fcfg, wl, step, db, cc_state, dev_stats) for a follower of
    ``primary``: the elastic full-residency tables with EVERY slot owned
    by the follower, so replaying the merged command stream materializes
    every partition's rows (deterministic execution makes followers
    free: the merged verdicts are identical everywhere, ownership only
    masks which rows a node bothers to write).  Shared by the live
    `GeoFollower` and the chaos harness's independent snapshot-replay
    check — both must build byte-identical state."""
    import jax.numpy as jnp

    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.runtime.membership import MEMBER_KEY, initial_map
    from deneva_tpu.workloads import get_workload

    fcfg = cfg.replace(node_id=primary, recover=False, fault_kill="")
    wl = get_workload(fcfg)
    be = get_backend(fcfg.cc_alg)
    step = make_dist_step(fcfg, wl, be)
    db = wl.load()
    db[MEMBER_KEY] = jnp.full((initial_map(fcfg).n_slots,), primary,
                              jnp.int32)
    dev_stats = init_device_stats(
        len(getattr(wl, "txn_type_names", ("txn",))))
    return fcfg, wl, step, db, be.init_state(fcfg), dev_stats


@jax.jit
def _ring_push(ring, slots, boundary, mask):
    """`GeoFollower._push_ring`'s push of one applied group, compiled."""
    return ring.push_rows(ring.rows(slots), slots,
                          jax.numpy.full(slots.shape, boundary), mask)


class GeoFollower:
    """Replaying state machine behind a geo replica.

    ``offer`` buffers framed log records as they arrive off the LOG_MSG
    stream; ``tick`` applies the next COMPLETE group of
    ``pipeline_epochs`` records through the per-epoch jit — group
    boundaries are the durability/determinism cutpoints everywhere else
    in this runtime, and applying whole groups atomically means the
    tables between ticks are exactly the boundary snapshot.  ``serve``
    answers a key batch from that snapshot plus each row's version
    stamp out of the `VersionRing` (pushed per applied group with the
    boundary id).  All of it runs on the replica process: the primaries'
    OLTP epoch loop is never consulted, let alone blocked."""

    def __init__(self, cfg: Config, me: int):
        from deneva_tpu.storage.table import VersionRing
        from deneva_tpu.workloads.ycsb import TABLE

        self.me = me
        primary = (me - cfg.node_cnt - cfg.client_node_cnt) % cfg.node_cnt
        self.primary = primary
        (self.cfg, self.wl, self.step, self.db, self.cc_state,
         self.dev_stats) = follower_boot(cfg, primary)
        self._table = TABLE
        self.C = max(1, cfg.pipeline_epochs)
        self.b = max(1, cfg.epoch_batch // cfg.node_cnt) * cfg.node_cnt
        # boundary-granularity version stamps: one ring row per table
        # row (+1 trash), entry = the boundary id whose group last
        # overwrote the row.  Serving always reads at the CURRENT
        # boundary, and the ring's FIFO always retains each row's newest
        # entry — so the stamp is exact at any depth.
        self._ring = VersionRing.create(self.wl.n_rows + 1,
                                        max(2, cfg.mvcc_his_len))
        self.applied = -1          # last applied epoch
        self.boundary = 0          # applied state == epochs < boundary
        self.last_seen = -1        # newest epoch offered off the stream
        self.pending: dict[int, tuple] = {}   # epoch -> (active, ts, blk)
        self.reads_served = 0
        self.rows_served = 0
        self.stale_max = 0
        self.apply_s = 0.0
        self.serve_s = 0.0
        self._f0_snap = None
        self._snapshot()
        self._warmup()

    def _warmup(self) -> None:
        """Compile the replay jit before the INIT_DONE barrier (the
        servers pre-compile the same way, so no node's first group
        stalls the lockstep)."""
        import jax
        import jax.numpy as jnp

        W = self.wl.n_req if hasattr(self.wl, "n_req") else 1
        query = self.wl.from_wire(np.zeros((self.b, W), np.int32),
                                  np.zeros((self.b, W), np.int8),
                                  np.zeros((self.b, 0), np.int32))
        out = self.step(self.db, self.cc_state, self.dev_stats,
                        jnp.int32(0), jnp.zeros(self.b, bool),
                        jnp.zeros(self.b, jnp.int32), query)
        jax.block_until_ready(out[2]["total_txn_commit_cnt"])

    def _snapshot(self) -> None:
        """Pin the boundary F0 column as numpy.  Functional updates
        rebind the column, so this reference stays a stable snapshot
        even if a later apply lands mid-serve."""
        self._f0_snap = np.asarray(self.db[self._table].columns["F0"])

    # -- log ingestion --------------------------------------------------
    def offer(self, framed: bytes) -> None:
        """Buffer every complete framed record in ``framed`` (one per
        LOG_MSG in steady state; REJOIN resends may batch several)."""
        from deneva_tpu.runtime import wire
        from deneva_tpu.runtime.logger import unpack_records

        for epoch, blob, bits in unpack_records(framed):
            if epoch <= self.applied or epoch in self.pending:
                continue              # duplicate (rejoin resend)
            _, blk, ts = wire.decode_epoch_blob(blob)
            active = np.unpackbits(bits)[: len(blk.keys)].astype(bool)
            self.pending[epoch] = (active, np.asarray(ts), blk)
            self.last_seen = max(self.last_seen, epoch)

    def _apply_epoch(self, epoch: int) -> np.ndarray:
        """Replay one buffered record; returns the committed-write row
        set (the ring push feed)."""
        import jax.numpy as jnp

        active, ts, blk = self.pending.pop(epoch)
        query = self.wl.from_wire(blk.keys, blk.types, blk.scalars)
        (self.db, self.cc_state, self.dev_stats, done, *_) = self.step(
            self.db, self.cc_state, self.dev_stats, jnp.int32(epoch),
            jnp.asarray(active), jnp.asarray(ts.astype(np.int32)), query)
        self.applied = epoch
        wrote = (blk.types == 2) & np.asarray(done)[:, None] \
            & active[:, None]
        return np.unique(blk.keys[wrote])

    def _push_ring(self, rows: np.ndarray, boundary: int) -> None:
        import jax.numpy as jnp

        n = len(rows)
        if not n:
            return
        # one compiled push a power of two of written rows, the padding
        # masked off: the ring's row write is a loop inside a conditional
        # (`VersionRing.push_rows`), which run op by op is traced and
        # compiled anew at every call
        cap = 1 << (n - 1).bit_length()
        slots = np.zeros(cap, np.int32)
        slots[:n] = rows
        self._ring = _ring_push(self._ring, jnp.asarray(slots),
                                jnp.int32(boundary),
                                jnp.asarray(np.arange(cap) < n))

    def tick(self) -> bool:
        """Apply the next group iff every one of its records arrived;
        returns True when a boundary advanced (the caller's timeline
        hook)."""
        lo = self.boundary
        if any(e not in self.pending for e in range(lo, lo + self.C)):
            return False
        t0 = time.monotonic()
        rows = [self._apply_epoch(e) for e in range(lo, lo + self.C)]
        self.boundary = lo + self.C
        self._push_ring(np.unique(np.concatenate(rows)), self.boundary)
        self._snapshot()
        self.apply_s += time.monotonic() - t0
        return True

    def catch_up(self) -> int:
        """Shutdown drain: apply every remaining contiguous record
        (partial tail group included — there is no later read to keep at
        a boundary) so the final state covers the whole received stream.
        Returns the last applied epoch."""
        rows = []
        while self.applied + 1 in self.pending:
            rows.append(self._apply_epoch(self.applied + 1))
        if rows:
            self.boundary = self.applied + 1
            self._push_ring(np.unique(np.concatenate(rows)),
                            self.boundary)
            self._snapshot()
        return self.applied

    # -- snapshot reads -------------------------------------------------
    def serve(self, keys: np.ndarray
              ) -> tuple[int, np.ndarray, np.ndarray]:
        """(boundary, values, version stamps) for a key batch at the
        last applied group boundary.  Values come off the pinned
        boundary snapshot; version stamps off the ring (`version_from`
        at ts=boundary — the newest overwrite boundary <= the served
        one, the per-row check a lockless reader validates against)."""
        import jax.numpy as jnp

        t0 = time.monotonic()
        k = np.clip(np.asarray(keys, np.int64), 0, self.wl.n_rows - 1)
        values = self._f0_snap[k].astype(np.uint32)
        vstar, _ = self._ring.version_from(
            self._ring.rows(jnp.asarray(k.astype(np.int32))),
            jnp.full(len(k), self.boundary, jnp.int32))
        self.reads_served += 1
        self.rows_served += len(k)
        self.stale_max = max(self.stale_max,
                             self.last_seen - (self.boundary - 1))
        self.serve_s += time.monotonic() - t0
        return self.boundary, values, np.asarray(vstar)

    # -- rejoin / verification ------------------------------------------
    def resync(self, log_path: str, resume: int) -> None:
        """A recovered primary truncated the stream to ``resume``: drop
        buffered records past it and, if the applied state already ran
        ahead of the truncation, rebuild from the (truncated) log file —
        replay is cheap and exact; guessing an inverse is neither."""
        for e in [e for e in self.pending if e >= resume]:
            del self.pending[e]
        if self.applied < resume:
            return
        from deneva_tpu.storage.table import VersionRing

        (_, self.wl, self.step, self.db, self.cc_state,
         self.dev_stats) = follower_boot(self.cfg, self.primary)
        self._ring = VersionRing.create(self.wl.n_rows + 1,
                                        max(2, self.cfg.mvcc_his_len))
        self.applied, self.boundary, self.pending = -1, 0, {}
        self.last_seen = -1
        if os.path.exists(log_path):
            with open(log_path, "rb") as f:
                self.offer(f.read())
        self._snapshot()

    def digest(self) -> str:
        from deneva_tpu.runtime.logger import state_digest
        return state_digest(self.db)

    def write_sidecar(self, path: str) -> None:
        """The chaos harness's verification anchor: an independent
        full-ownership replay of this replica's own log must reproduce
        ``state_digest`` bit for bit at ``applied_epoch``."""
        with open(path, "w") as f:
            json.dump({"node": self.me, "primary": self.primary,
                       "applied_epoch": self.applied,
                       "boundary": self.boundary,
                       "state_digest": self.digest(),
                       "reads_served": self.reads_served,
                       "rows_served": self.rows_served,
                       "stale_read_max_epochs": int(self.stale_max)}, f)
