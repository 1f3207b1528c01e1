"""2PL variants: NO_WAIT and WAIT_DIE (reference `concurrency_control/row_lock.{h,cpp}`).

The reference keeps a per-row owners/waiters lock table under a pthread
mutex: NO_WAIT aborts any conflicting requester (`row_lock.cpp:86-90`);
WAIT_DIE lets a requester *older* than every conflicting owner wait on a
FIFO list, younger requesters die (`row_lock.cpp:91-151`), and release
promotes waiters via `txn_table.restart_txn` (`:317-357`).

Batch semantics: lock-acquisition order becomes ``rank`` (pool arrival
order).  A txn "reaches the lock table first" iff it wins the lex-first
maximal-independent-set sweep over the RW/WR/WW conflict matrix in rank
order — exactly the set of txns that would have acquired all their locks
had the epoch's requests arrived serially in rank order.

* NO_WAIT: sweep losers abort (with the engine's exponential backoff,
  `system/abort_queue.cpp:26-50`).  Sweep-round-cap leftovers defer —
  they were never refused a lock, merely unresolved this epoch.
* WAIT_DIE: a loser conflicting only with *younger* winners (all winner
  timestamps greater than its own) waits — deferred to the next epoch
  where its lower rank makes it the presumptive owner; otherwise it dies.
  Timestamps are assigned at first arrival and preserved across restarts
  (the reference preserves them the same way, `worker_thread.cpp:492-508`),
  which is what makes WAIT_DIE starvation-free.

Isolation levels (reference `config.h:102,337-340`) relax which lock
requests conflict, exactly mirroring the reference's per-level gating:

* SERIALIZABLE — long read + write locks: any pair sharing a key with at
  least one writer conflicts (RR excluded).
* READ_COMMITTED — read locks are released immediately after the read
  (`benchmarks/ycsb_txn.cpp:233`, cleanup skip `system/txn.cpp:720`):
  writers no longer block behind earlier readers, but a reader still
  contends at acquire time with an *earlier* writer holding the lock —
  directed reader←writer edges stay, reader→writer edges drop.
* READ_UNCOMMITTED — reads bypass the lock table entirely
  (`storage/row.cpp:208,359`): only WW conflicts remain.
* NOLOCK — CC bypassed (`storage/row.cpp:203,355`): everyone commits;
  the engine's last-writer-wins scatter resolves duplicate writes.

Each level's edge set is a subset of the previous, so throughput is
monotone in the isolation ladder — the shape `experiments.py`'s
isolation_levels sweep exists to show.

Escrow (``order_free``) exemption, gated by ``escrow_order_free`` AND
``escrow_sweep``: lock requests for commutative accumulator updates and
immutable-column reads need no lock at all — the reference analogue is
escrow locking (O'Neil) layered under 2PL, where increment locks are
mutually compatible.  Edges therefore come from the ORDERED incidence
views: a pair conflicts iff it overlaps and at least one side's access
is ordered — symmetrizing ``overlap(uo, w)`` (SERIALIZABLE),
``overlap(wo, w)`` (WW), and directing ``overlap(pro, w)`` (RC's
residual read locks) — so Payment add-add pairs on one warehouse row
all acquire their "increment locks" together, while an ordered read of
W_YTD still contends with every add.  With the gate off the views alias
r/w/pr and the edges are bit-identical to the pre-escrow derivation.
"""

from __future__ import annotations

import jax.numpy as jnp

from deneva_tpu.cc.base import (AccessBatch, Incidence, Verdict,
                                committed_write_frontier)
from deneva_tpu.cc.nocc import validate_nocc
from deneva_tpu.ops import earlier_edges, greedy_first_fit


def repair_frontier(cfg, state, batch: AccessBatch, inc: Incidence,
                    committed, losers):
    """2PL invalidation rule (transaction repair, engine/repair.py):
    lock-edge losers.  A NO_WAIT/WAIT_DIE loser was refused a lock some
    winner held; by the repair sub-round every winner has committed and
    "released", so the loser re-acquires against the epoch-end state.
    Its invalidated reads are the ones an earlier winner's WRITE lock
    covered — ordered reads overlapping committed writes — the same
    access set under every isolation level (READ_COMMITTED's early-
    released read locks and READ_UNCOMMITTED's lock-free reads change
    which REQUESTS conflict, not which read VALUES went stale; the
    generic frontier is the conservative superset for both).  Write-only
    lock losers (WW refusals) re-apply their blind writes with an empty
    frontier.  The sub-round's re-acquisition is this module's own edge
    derivation restricted to the losers (``validate_no_wait``/
    ``validate_wait_die`` on the loser-masked batch)."""
    return committed_write_frontier(cfg, batch, inc, committed, losers)


def _lock_edges(cfg, batch: AccessBatch, inc: Incidence):
    """Directed blocked-by edges E[i,j] ("earlier j blocks i") under the
    configured isolation level; None means no locking at all (NOLOCK).
    Ordered incidence views (uo/wo/pro — alias u/w/pr when no escrow
    exemption applies) keep escrow add-add pairs edge-free."""
    iso = cfg.isolation_level
    ov = inc.overlap
    if iso == "NOLOCK":
        return None
    if iso == "SERIALIZABLE":
        # symmetrized ordered-vs-write overlap: a pair conflicts iff at
        # least one side's ORDERED access meets the other's write
        uw = ov("uo", "w")
        return earlier_edges(uw | uw.T, batch.rank, batch.active)
    ww = ov("wo", "w")
    e = earlier_edges(ww | ww.T, batch.rank, batch.active)
    if iso == "READ_COMMITTED":
        # i's ordered pure read contends with an earlier writer j of the
        # same key; the reverse direction (writer behind reader) is gone —
        # the read lock is already released by the time the writer asks.
        e = e | earlier_edges(ov("pro", "w"), batch.rank, batch.active)
    return e


def _count_locks(stats, die, wait, leftover) -> None:
    """The sweep's three kinds of loser into the device counters, where
    the ``stats`` dict carries them (the served 2PL program's:
    `workloads/base.LOCK_COUNTERS`).  The host sees deaths as aborts and
    waits and leftovers as one defer, so the split is counted here."""
    if stats is None or "lock_die" not in stats:
        return
    for k, m in (("lock_die", die), ("lock_wait", wait),
                 ("lock_leftover", leftover)):
        stats[k] = stats[k] + m.sum(dtype=jnp.uint32)


def validate_no_wait(cfg, state, batch: AccessBatch, inc: Incidence,
                     stats=None):
    e = _lock_edges(cfg, batch, inc)
    if e is None:
        return validate_nocc(cfg, state, batch, inc)
    win, lose, und = greedy_first_fit(e, batch.active, rounds=cfg.sweep_rounds)
    _count_locks(stats, lose, jnp.zeros_like(lose), und)
    v = Verdict(commit=win, abort=lose, defer=und,
                order=batch.rank, level=jnp.zeros_like(batch.rank))
    return v, state


def validate_wait_die(cfg, state, batch: AccessBatch, inc: Incidence,
                      stats=None):
    e = _lock_edges(cfg, batch, inc)
    if e is None:
        return validate_nocc(cfg, state, batch, inc)
    win, lose, und = greedy_first_fit(e, batch.active, rounds=cfg.sweep_rounds)
    # min timestamp over the winning earlier neighbors that blocked me
    blockers = e & win[None, :]
    big = jnp.iinfo(jnp.int32).max
    min_owner_ts = jnp.where(blockers, batch.ts[None, :], big).min(axis=1)
    waits = lose & (batch.ts < min_owner_ts)   # older than every owner -> wait
    _count_locks(stats, lose & ~waits, waits, und)
    v = Verdict(commit=win, abort=lose & ~waits, defer=und | waits,
                order=batch.rank, level=jnp.zeros_like(batch.rank))
    return v, state
