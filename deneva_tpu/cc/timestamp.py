"""TIMESTAMP (basic T/O) and MVCC (reference `concurrency_control/row_ts.{h,cpp}`,
`row_mvcc.{h,cpp}`).

The reference tracks per-row ``wts``/``rts`` watermarks plus buffered
read/prewrite/write request lists (`row_ts.cpp:63-80`), and MVCC keeps
per-row version histories GC'd against the global min-ts
(`row_mvcc.cpp:303-321`, `system/manager.cpp:71-80`).

Batch mapping.  Cross-epoch watermarks live in per-*bucket* tables
``rts[K]/wts[K]`` (max-aggregated over the keys hashing there — an
over-approximation that can only add aborts, never hide one; the analogue
of the reference's hash-bucketed TimeTable for MAAT).  Within an epoch all
reads observe the epoch-start snapshot, so the only intra-epoch violation
is a *reader ordered after a committing writer* (ts_r > ts_w): the reader
should have seen the writer's value but read the snapshot.  Those RW pairs
are swept in timestamp order and the later reader **waits** — the batch
analogue of the reference parking the read on the row until the prewrite
drains (`row_ts.cpp:63-80` buffer_req / `row_mvcc.cpp:252-258`): the
reader defers with its timestamp intact, and next epoch the writer's value
is the committed snapshot, which the reader then reads — exactly the value
the reference's woken waiter gets.  Writer-after-read pairs serialize
reader-first for free; blind write-write pairs both commit with
last-writer-wins application — Thomas' write rule, exact because
``Verdict.order = ts``.

TIMESTAMP rules (abort conditions, cross-epoch):
* read k:  ``wts[k] > ts``  — value from my future already committed
  (`row_ts.cpp` aborts the same read; we cannot time-travel either).
* write k: ``rts[k] > ts`` or ``wts[k] > ts`` — a future read/write
  already committed against the old value.

MVCC (multi-version) differences:
* Read-only transactions *always commit*: they serialize at the snapshot
  point (reads of old versions never conflict) — the multi-version win,
  mirroring the reference's read-only fast path (`system/txn.cpp:498-530`)
  made unconditional.
* Pure reads of read-write txns serve **old versions**: a per-bucket ring
  of the last ``mvcc_his_len`` version-boundary timestamps (the
  HIS_RECYCLE_LEN-bounded write history, `row_mvcc.cpp:172-196,303-321`)
  decides whether the version a stale read needs is still retained — the
  read commits iff ``ts >= min(ring)`` (the oldest retained boundary;
  version at boundary w serves reads in [w, next boundary)).  Reads older
  than the retained history abort, exactly like the reference's recycled
  versions.  Version boundaries are recorded at epoch granularity (one
  boundary per bucket per epoch — within an epoch the table has a single
  committed state, so finer boundaries are unobservable).
* RMW accesses (read & write of one key) must read latest: ``wts[k] > ts``
  still aborts — serving an old version to a read-modify-write would
  corrupt the newer committed value.
* Old-version *payloads* are materialized per row: the workload's
  version-value ring (`storage.table.VersionRing`, wired in
  `workloads/ycsb.py`) records the bytes each committed write overwrote,
  and a committed stale read gathers the version current at its ts —
  matching `row_mvcc.cpp:172-196` value-for-value (oracle:
  `tests/test_cc.py::test_mvcc_serves_historical_bytes`).  The bucket
  boundary ring here makes the retention DECISION; its commit rule
  (``ts >= min(ring)``) guarantees the per-row ring still holds the
  needed version (at most H-1 boundaries, hence at most H-1 per-row
  overwrites, can exceed a servable ts).  TPC-C/PPS need NO value
  rings to be value-exact (round-4, oracle-proven): every gather their
  executors perform is (a) a load-immutable column (W_TAX / D_TAX /
  C_DISCOUNT; USES/SUPPLIES mappings), (b) an RMW read, which this
  module only permits at the latest version (``wts > ts`` aborts), or
  (c) a read-only txn's gather, whose serialization point IS the epoch
  snapshot it reads — so the live gather is the correct version in
  every committed case
  (`tests/test_tpcc.py::test_mvcc_reads_byte_match_serial_oracle`,
  `tests/test_pps.py::test_mvcc_getpart_reads_snapshot_values`).

Timestamps are epoch-fresh on restart exactly as the reference re-stamps
restarted txns (`system/worker_thread.cpp:492-508`); deferred (waiting)
txns keep their birth ts like the reference's parked requests.

Escrow (``order_free``) rules, gated by ``escrow_order_free`` AND
``escrow_sweep`` (``batch.order_free`` arrives pre-gated — None gives
bit-identical pre-escrow behavior).  An escrow WRITE is a commutative
delta: deltas reorder freely among themselves (their sum is
order-invariant — the escrow guarantee of O'Neil's escrow method /
DGCC's commutative decomposition, arXiv:1503.03642), so
* escrow writes skip the ``wts > ts`` check — an older delta landing
  after a newer delta is not a violation — but KEEP the ``rts > ts``
  check: a committed ORDERED read at higher ts already fixed the
  accumulator value it observed, and a delta slotting before it in ts
  order would invalidate that read;
* escrow writes still RECORD ``wts`` so later ordered readers at lower
  ts correctly abort (they missed a delta in their ts-past);
* escrow READS (declared immutable columns) check nothing and record no
  ``rts`` — a false rts from the accumulator's row bucket would
  re-floor the adds.  Intra-epoch reader-wait edges likewise come from
  the ORDERED read incidence (`overlap(ro, w)`).
Consequence stated honestly: escrow deltas serialize in COMMIT order,
not ts order (two deltas committed in different epochs apply in epoch
order however their ts compare).  Sums, D_NEXT_O_ID uniqueness/density
and every ordered read stay exact — the equivalence is modulo
commutativity, which is the escrow contract.  Workloads must not mix
ordered writes into order_free columns (none do; the executors apply
deltas unconditionally).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deneva_tpu.cc.base import AccessBatch, Incidence, Verdict
from deneva_tpu.ops import (bucket_hash, combine_key, earlier_edges,
                            greedy_first_fit)


def _wm_bucket(cfg, batch: AccessBatch) -> jax.Array:
    """Per-access bucket ids in the WATERMARK hash space.  Decoupled from
    the incidence bucket space: watermark tables are O(K) memory, so they
    run much wider (``watermark_buckets``) than the O(B*K) incidence
    matrices can afford — per-bucket max-aggregation stays a sound
    over-approximation of the reference's per-row ts state, with false
    sharing driven toward zero."""
    ident = combine_key(batch.table_ids, batch.keys)
    return bucket_hash(ident, cfg.watermark_buckets, family=0)


@dataclass
class TOState:
    """Per-bucket committed watermarks (family-0 hash space)."""

    rts: jax.Array   # int32[K] max committed read ts
    wts: jax.Array   # int32[K] max committed write ts


jax.tree_util.register_dataclass(TOState, data_fields=["rts", "wts"],
                                 meta_fields=[])


@dataclass
class MVCCState:
    """TOState plus the bounded version-boundary ring (write history)."""

    rts: jax.Array   # int32[K]
    wts: jax.Array   # int32[K]
    his: jax.Array   # int32[K, H] recent version-boundary ts (0 = the
    #                  load-time base version, retained until overwritten)
    pos: jax.Array   # int32[K] next ring slot per bucket
    lossy: jax.Array  # int32[K] greatest boundary of an epoch that wrote
    #                   the bucket at two timestamps (the earlier
    #                   writer's version is in no ring); 0 = none yet


jax.tree_util.register_dataclass(
    MVCCState, data_fields=["rts", "wts", "his", "pos", "lossy"],
    meta_fields=[])


def init_to_state(cfg) -> TOState:
    k = cfg.watermark_buckets
    return TOState(rts=jnp.zeros((k,), jnp.int32),
                   wts=jnp.zeros((k,), jnp.int32))


def init_mvcc_state(cfg) -> MVCCState:
    k, h = cfg.watermark_buckets, cfg.mvcc_his_len
    return MVCCState(rts=jnp.zeros((k,), jnp.int32),
                     wts=jnp.zeros((k,), jnp.int32),
                     his=jnp.zeros((k, h), jnp.int32),
                     pos=jnp.zeros((k,), jnp.int32),
                     lossy=jnp.zeros((k,), jnp.int32))


def _readonly(batch: AccessBatch) -> jax.Array:
    """bool[B]: read-only txns.  Prefers the GLOBAL ``ro_hint`` (set by
    the distributed VOTE prepare, whose valid mask covers only locally
    owned accesses) over the local derivation."""
    if batch.ro_hint is not None:
        return batch.ro_hint
    v = batch.valid & batch.active[:, None]
    return ~(v & batch.is_write).any(axis=1)


def _history_read_lanes(cfg, state: MVCCState, batch: AccessBatch
                        ) -> jax.Array:
    """bool[B, A]: MVCC's pure read lanes whose version is out of reach
    — older than the bucket's retained boundaries (version recycled,
    row_mvcc.cpp:303-321) or below an epoch that kept only the later of
    two writers' versions (`MVCCState.lossy`)."""
    wm = _wm_bucket(cfg, batch)
    ts = batch.ts[:, None]
    floor = jnp.take(jnp.maximum(state.his.min(axis=1), state.lossy), wm)
    return batch.valid & batch.active[:, None] & batch.is_read \
        & ~batch.is_write & (jnp.take(state.wts, wm) > ts) & (ts < floor)


def _stale_read_lanes(cfg, state, batch: AccessBatch,
                      mvcc: bool) -> jax.Array:
    """bool[B, A]: read lanes violating the cross-epoch ``wts`` watermark
    at the txn's CURRENT ts (the read half of ``_watermark_aborts``,
    exposed per access so the repair frontier can name exactly which
    reads went stale).  Escrow reads are exempt per the module
    docstring."""
    wm = _wm_bucket(cfg, batch)
    v = batch.valid & batch.active[:, None]
    wts_at = jnp.take(state.wts, wm)                   # [B, A]
    ts = batch.ts[:, None]
    if mvcc:
        # pure reads serve the retained version at their ts; only reads
        # whose version is out of reach or RMW reads (must read latest)
        # abort
        rmw = batch.is_read & batch.is_write
        read_bad = _history_read_lanes(cfg, state, batch) \
            | (v & rmw & (wts_at > ts))
    else:
        read_bad = v & batch.is_read & (wts_at > ts)
    if batch.order_free is not None:
        # escrow reads check nothing (declared-immutable columns)
        read_bad = read_bad & ~batch.order_free
    return read_bad


def _watermark_aborts(cfg, state, batch: AccessBatch,
                      mvcc: bool) -> jax.Array:
    """bool[B]: txn violates a cross-epoch watermark (escrow accesses
    follow the relaxed rules in the module docstring)."""
    wm = _wm_bucket(cfg, batch)
    v = batch.valid & batch.active[:, None]
    wts_at = jnp.take(state.wts, wm)                   # [B, A]
    rts_at = jnp.take(state.rts, wm)
    ts = batch.ts[:, None]
    read_bad = _stale_read_lanes(cfg, state, batch, mvcc)
    if batch.order_free is None:
        write_bad = v & batch.is_write & ((rts_at > ts) | (wts_at > ts))
    else:
        # escrow writes (deltas) check only rts — deltas commute with
        # prior deltas, never with a committed ordered read whose
        # ts-past they would rewrite
        write_bad = v & batch.is_write & jnp.where(
            batch.order_free, rts_at > ts, (rts_at > ts) | (wts_at > ts))
    bad = (read_bad | write_bad).any(axis=1)
    if mvcc:
        bad = bad & ~_readonly(batch)       # read-only: snapshot
    return bad


def _repair_frontier(cfg, state, batch: AccessBatch, inc: Incidence,
                     committed, losers, mvcc: bool):
    """T/O invalidation rule (transaction repair, engine/repair.py):
    the wts/rts watermark re-check.  A T/O loser is a watermark
    violator — its birth ts sits in the PAST of committed state (a
    value "from its future" was already on disk), which whole-txn retry
    fixes by restamping next epoch.  Repair restamps NOW: the frontier
    is the union of (a) this epoch's winner overwrites of the loser's
    ordered reads (the generic bucket frontier) and (b) the cross-epoch
    stale-read lanes that caused the abort (``wts_at > birth ts``, the
    per-access view of ``_watermark_aborts``).  The repair sub-round
    then re-runs this module's validate at a fresh ts above every stamp
    in the epoch — the same watermark check, which now passes exactly
    when the re-read serves the committed value, and the same
    later-reader-waits sweep restricted to the losers.  Repaired
    commits record watermarks at the fresh ts, so a second sub-round's
    reader of a first-sub-round write re-checks against it (and falls
    back to the retry queue if its own stamp is older — conservative,
    never a wrong commit)."""
    from deneva_tpu.cc.base import committed_write_frontier
    base = committed_write_frontier(cfg, batch, inc, committed, losers)
    stale = _stale_read_lanes(cfg, state, batch, mvcc) & losers[:, None]
    return base | stale


def repair_frontier_timestamp(cfg, state, batch, inc, committed, losers):
    return _repair_frontier(cfg, state, batch, inc, committed, losers,
                            mvcc=False)


def repair_frontier_mvcc(cfg, state, batch, inc, committed, losers):
    return _repair_frontier(cfg, state, batch, inc, committed, losers,
                            mvcc=True)


def _rw_later_reader_edges(cfg, batch: AccessBatch, inc: Incidence):
    """E[i,j]: ORDERED reader i (by ts) after writer j on a common key
    (ro aliases r when no escrow exemption applies: declared-immutable
    column reads never wait behind the row's delta writers)."""
    rw = inc.overlap("ro", "w")    # i reads ∩ j writes
    return earlier_edges(rw, batch.ts, batch.active)   # j earlier by ts


def _commit_watermarks(cfg, state, batch: AccessBatch,
                       commit: jax.Array):
    v = batch.valid & commit[:, None]
    ts = jnp.broadcast_to(batch.ts[:, None], batch.keys.shape)
    # escrow reads record no rts (immutable columns; a false rts from
    # the row's shared bucket would abort the row's own deltas); escrow
    # WRITES still record wts so stale ordered readers abort
    r_rec = v & batch.is_read if batch.order_free is None \
        else v & batch.is_read & ~batch.order_free
    r_ts = jnp.where(r_rec, ts, 0)
    w_ts = jnp.where(v & batch.is_write, ts, 0)
    flat = _wm_bucket(cfg, batch).reshape(-1)
    rts = state.rts.at[flat].max(r_ts.reshape(-1))
    if not isinstance(state, MVCCState):
        return TOState(rts=rts, wts=state.wts.at[flat].max(w_ts.reshape(-1)))
    # record this epoch's version boundary per written bucket: the ring
    # keeps the last H boundaries (bounded write history); epoch
    # granularity is exact because the table exposes one committed state
    # per epoch
    w_flat = w_ts.reshape(-1)
    epoch_w = jnp.zeros_like(state.wts).at[flat].max(w_flat)
    wrote = epoch_w > 0
    # (the bucket's watermark needs no scatter of its own: this epoch's
    # greatest write is in hand, dense)
    wts = jnp.maximum(state.wts, epoch_w)
    h = state.his.shape[1]
    slot = jnp.arange(h, dtype=jnp.int32)[None, :] == state.pos[:, None]
    his = jnp.where(wrote[:, None] & slot, epoch_w[:, None], state.his)
    pos = jnp.where(wrote, (state.pos + 1) % h, state.pos)
    # a bucket written at two timestamps this epoch (its least committed
    # write lies under its greatest): the table and the row's ring keep
    # the later writer's version only
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    epoch_lo = jnp.full_like(state.wts, big).at[flat].min(
        jnp.where(w_flat > 0, w_flat, big))
    lossy = jnp.where(epoch_lo < epoch_w,
                      jnp.maximum(state.lossy, epoch_w), state.lossy)
    return MVCCState(rts=rts, wts=wts, his=his, pos=pos, lossy=lossy)


def _validate_to(cfg, state, batch, inc, mvcc: bool, stats=None):
    wm_abort = _watermark_aborts(cfg, state, batch, mvcc)
    live = batch.active & ~wm_abort
    if mvcc:
        ro = _readonly(batch)
    else:
        ro = jnp.zeros(batch.active.shape, bool)
    # read-only MVCC txns leave the conflict graph entirely
    swept = live & ~ro
    e = _rw_later_reader_edges(cfg, batch, inc)
    e = e & swept[:, None] & swept[None, :]
    win, lose, und = greedy_first_fit(e, swept, rounds=cfg.sweep_rounds)
    commit = win | (live & ro)
    # MVCC read-only txns serialize AT the snapshot: order them before
    # every epoch writer (ts are >= 1), so duplicate-write resolution and
    # the serializability oracle see reader-first order.
    order = jnp.where(ro, 0, batch.ts)
    # a swept-out later reader WAITS (buffered read, row_ts.cpp:63-80):
    # defer with ts intact — next epoch the writer's value is committed
    # state and the read proceeds.  Only watermark violations abort.
    v = Verdict(commit=commit, abort=batch.active & wm_abort,
                defer=und | lose, order=order,
                level=jnp.zeros_like(batch.rank))
    if stats is not None:
        # what the backend decided (`workloads/base.MVCC_COUNTERS`; the
        # served MVCC program hands its stats in): transactions sent
        # back for a read whose version is out of reach, transactions
        # that wait behind a writer of their epoch, read-only commits
        out_of_reach = _history_read_lanes(cfg, state, batch).any(axis=1)
        for k, m in (("mvcc_history_aborts", wm_abort & out_of_reach),
                     ("mvcc_waits", v.defer), ("mvcc_ro_commits", live & ro)):
            stats[k] = stats[k] + m.sum(dtype=jnp.uint32)
    return v, _commit_watermarks(cfg, state, batch, commit)


def commit_to_state(cfg, state, batch: AccessBatch, inc, commit: jax.Array):
    """Post-decision watermark application for the distributed VOTE
    protocol: local validation's state output is discarded and the
    watermarks advance only for *globally* committed txns (the
    reference's row managers likewise update ts state on the 2PC commit
    path, not at prepare).  ``inc`` is unused (watermark buckets are
    self-hashed) and kept for the hook signature."""
    return _commit_watermarks(cfg, state, batch, commit)


def validate_timestamp(cfg, state, batch: AccessBatch, inc: Incidence):
    return _validate_to(cfg, state, batch, inc, mvcc=False)


def validate_mvcc(cfg, state, batch: AccessBatch, inc: Incidence,
                  stats=None):
    return _validate_to(cfg, state, batch, inc, mvcc=True, stats=stats)
