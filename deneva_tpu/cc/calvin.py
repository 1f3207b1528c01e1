"""CALVIN (deterministic) and TPU_BATCH (the headline backend).

Reference Calvin: a sequencer stamps each txn with ``(batch_id=epoch,
txn_id)`` and broadcasts per-epoch batches (`system/sequencer.cpp:184-326`);
a lock-scheduler thread acquires all locks in strict sequence order —
conflicts enqueue FIFO, never abort (`row_lock.cpp:152-170`) — and workers
execute when granted, forwarding dirty reads to remote peers (RFWD,
`system/txn.cpp:957-974`).  Determinism means zero aborts and no 2PC.

Batch mapping.  The engine's epoch *is* the sequencer batch and ``rank``
is the sequence number.  The per-row FIFO lock queues become wavefront
levels over the conflict matrix: a txn's level is its longest conflict
chain through earlier-ranked txns, and the engine executes levels as
chained sub-rounds — level-l reads see all writes of levels < l, which is
exactly the deterministic serial order Calvin's scheduler enforces (and
subsumes the RFWD dirty-read forwarding: the "forwarded" value is simply
present in table state by the reader's sub-round).  Txns whose chain
exceeds ``exec_subrounds`` defer whole to the next epoch where their
preserved rank keeps them at the head — deterministic order is preserved,
they just commit in a later batch (the reference's epochs likewise bound
batch extent in time, `config.h:348`).

On blind-write workloads (YCSB) both backends take the single-pass
forwarding executor instead of sub-rounds (`cc.__init__` registry,
``forward=True``): a reader of a key with an earlier in-batch writer
receives that writer's value arithmetically (ops/forward), which is the
*closed form* of RFWD — the reference's scheduler likewise executes a
hot-key chain serially WITHIN the batch and commits all of it, whatever
its depth.  This is what makes the deterministic backends flat under
skew (the paper's signature Calvin result); the sub-round level budget
applies only where writes depend on reads (TPC-C/PPS), and execution
runs only the levels that actually occur (`lax.while_loop`, not a fixed
unroll), so raising ``exec_subrounds`` costs nothing at low contention.

TPU_BATCH = the same deterministic executor, minus the fiction of a
separate sequencer node: ranks are pool arrival order, and the conflict
matrix is dual-hash exact.  It commits *everything* (cycle-free by
construction since edges follow rank), so throughput is bounded by chain
depth rather than abort rate — the design SURVEY §7 stage 8 targets.  The
two share an implementation; CALVIN additionally reports the deterministic
``order`` for cross-node replay (`deneva_tpu.runtime` ships per-epoch
verdicts instead of RFWD messages).
"""

from __future__ import annotations

import jax.numpy as jnp

from deneva_tpu.cc.base import AccessBatch, Incidence, Verdict
from deneva_tpu.ops import earlier_edges, wavefront_levels


def validate_calvin(cfg, state, batch: AccessBatch, inc: Incidence):
    # conflict iff the pair overlaps AND at least one side is an
    # ORDERED access: escrow/commutative (order_free) add-add pairs
    # carry no edge, while reads of the same accumulators still order
    # against every write (uo == u when nothing is exempt)
    uw = inc.overlap("uo", "w")
    c = uw | uw.T
    e = earlier_edges(c, batch.rank, batch.active)
    lv, overflow = wavefront_levels(e, max_level=cfg.exec_subrounds - 1)
    commit = batch.active & ~overflow
    v = Verdict(commit=commit, abort=jnp.zeros_like(batch.active),
                defer=batch.active & overflow,
                order=batch.rank, level=jnp.where(commit, lv, 0))
    return v, state


validate_tpu_batch = validate_calvin
