"""OCC — Kung-Robinson backward validation (reference `concurrency_control/occ.{h,cpp}`).

The reference copies rows on access (`storage/row.cpp:283-290`) and runs
*central* validation under a global semaphore: a committing txn's read set
is checked against the write sets of txns that committed during its
execution window, and against concurrently-validating writers
(`occ.cpp:116-239`); committed write sets are appended to a history list
(`central_finish` `:248-294`).

Batch semantics collapse the execution window to the epoch: every txn read
the epoch-start snapshot, so validation against *prior* epochs passes
vacuously (their writes were all applied before the snapshot — the
reference prunes its history list with ``his_oldest_active_tn`` the same
way).  Within the epoch, serial validation in rank order admits txn i iff
no already-admitted j has ``W_j ∩ (R_i ∪ W_i) ≠ ∅`` — the Kung-Robinson
serial-equivalence test with j's writes "after" i's snapshot reads.  That
is the lex-first MIS sweep over the *directed* U-vs-W overlap.

Like the reference's central validation, the whole epoch validates in one
place — except "one place" is the chip, and the critical section is one
[B, B] conflict matrix instead of a semaphore: a pairwise compare of the
epoch's exact keys (`cc.base.Incidence.overlap`), then the sweep's matvec
fixpoint on the MXU.

Escrow (``order_free``) exemption, gated by ``escrow_order_free`` AND
``escrow_sweep``: a txn's escrow accesses leave its validated set —
``W_j ∩ (R_i ∪ W_i)`` is tested against the ORDERED union ``uo_i`` (the
coarse-granularity false-abort class of arXiv:1811.04967: commutative
deltas against one hot record are not read-write conflicts) — while j's
write set stays FULL, so an ordered read of an accumulator still
invalidates against every admitted add.  Add-add pairs carry no edge and
the executor accumulates all their deltas.  With the gate off ``uo``
aliases ``u`` and validation is bit-identical to Kung-Robinson.
"""

from __future__ import annotations

import jax.numpy as jnp

from deneva_tpu.cc.base import (AccessBatch, Incidence, Verdict,
                                committed_write_frontier)
from deneva_tpu.ops import earlier_edges, greedy_first_fit


def repair_frontier(cfg, state, batch: AccessBatch, inc: Incidence,
                    committed, losers):
    """OCC invalidation rule (transaction repair, engine/repair.py):
    read-set vs winner write-set.  A Kung-Robinson loser aborted because
    an admitted j's writes intersected its validated set; the READ half
    of that intersection is what made its execution stale — those reads
    observed the epoch-start snapshot where they should have seen j's
    value.  Re-executing them against the post-winner state moves the
    loser's serialization point after every winner, after which the
    repair sub-round re-runs this module's own serial-admission test
    restricted to the losers (``validate_occ`` on the loser-masked
    batch) — the same validation, one snapshot later.  Write-only
    intersections need no re-read (blind writes recompute); they show up
    as an EMPTY frontier and salvage in the first sub-round."""
    return committed_write_frontier(cfg, batch, inc, committed, losers)


def validate_occ(cfg, state, batch: AccessBatch, inc: Incidence):
    # directed: my ORDERED accesses vs their writes (their reads never
    # invalidate me; my escrow deltas commute with their writes' deltas
    # and an ordered write of theirs on the same key appears in their uo
    # for the mirrored pair, which earlier_edges then directs)
    uw = inc.overlap("uo", "w")
    e = earlier_edges(uw, batch.rank, batch.active)
    win, lose, und = greedy_first_fit(e, batch.active, rounds=cfg.sweep_rounds)
    v = Verdict(commit=win, abort=lose, defer=und,
                order=batch.rank, level=jnp.zeros_like(batch.rank))
    return v, state
