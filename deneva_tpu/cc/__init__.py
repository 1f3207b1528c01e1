"""Concurrency control as batched epoch validation (SURVEY §2.3).

One registry entry per reference algorithm (`config.h:101`, README:24-35),
each a pure ``validate(cfg, state, batch, incidence)`` function — runtime
dispatch replacing the reference's compile-time ``#if CC_ALG`` forest.

``CCBackend`` bundles the algorithm with its cross-epoch state handling
and declares whether the engine must run chained sub-rounds
(``n_levels > 1``: Calvin/TPU_BATCH) and whether incidence matrices are
needed at all (NOCC skips them).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

from deneva_tpu.config import CCAlg, Config
from deneva_tpu.cc.base import (AUDIT_KEY, AccessBatch,  # noqa: F401
                                Incidence, Recon, Verdict, audit_init,
                                audit_mutate_verdict, audit_observe,
                                build_conflict_incidence, build_incidence,
                                committed_write_frontier, conflict_density,
                                gate_order_free, stale_recon)
from deneva_tpu.cc import maat as _maat
from deneva_tpu.cc import occ as _occ
from deneva_tpu.cc import timestamp as _tsmod
from deneva_tpu.cc import twopl as _twopl
from deneva_tpu.cc.calvin import validate_calvin, validate_tpu_batch
from deneva_tpu.cc.dgcc import validate_dgcc
from deneva_tpu.cc.maat import validate_maat
from deneva_tpu.cc.nocc import validate_nocc
from deneva_tpu.cc.occ import validate_occ
from deneva_tpu.cc.timestamp import (commit_to_state, init_mvcc_state,
                                     init_to_state, validate_mvcc,
                                     validate_timestamp)
from deneva_tpu.cc.twopl import validate_no_wait, validate_wait_die


@dataclass(frozen=True)
class CCBackend:
    alg: CCAlg
    validate: Callable[..., tuple[Verdict, Any]]
    init_state: Callable[[Config], Any]
    needs_incidence: bool = True
    chained: bool = False      # engine executes commit levels as sub-rounds
    fresh_ts_on_restart: bool = True   # WAIT_DIE keeps its birth ts
    # the device counter whose presence in the epoch's ``stats`` means
    # "hand ``validate`` the stats dict": the backend counts what it
    # decides where a server asked for its counters
    # (`workloads/base.MVCC_COUNTERS` / `LOCK_COUNTERS`), and is called
    # without them everywhere else
    counts_in: str | None = None
    # single-pass forwarding executor (ops/forward): on blind-write
    # workloads the whole batch commits with reads forwarded in-batch —
    # no conflict matrix at all; chained path is the fallback otherwise
    forward: bool = False
    # the backend may EXCLUDE accesses the workload marks ``order_free``
    # from conflict detection (escrow/commutative semantics: scatter-add
    # deltas and immutable-column reads need no ordering; the executor
    # applies deltas order-exactly over every committed winner).  Opted
    # in per backend; the sweep backends' opt-in is additionally gated
    # by ``Config.escrow_sweep`` (cc.base.gate_order_free) so the
    # reference-faithful row-level-conflict baseline stays one flag away.
    exempt_order_free: bool = False
    # distributed VOTE protocol hook: apply cross-epoch state for the
    # GLOBALLY decided commit set (local validation's state output is
    # discarded at prepare time).  None = stateless backend.
    commit_state: Any = None
    # transaction repair hook (engine/repair.py, gated by Config.repair):
    # the backend's invalidated-read frontier rule
    # ``(cfg, cc_state, batch, inc, committed, losers) -> bool[B, A]`` —
    # which of a loser's reads saw a value the committed set overwrote
    # (OCC: read-set vs winner write-set; 2PL: lock-edge losers; T/O:
    # wts/rts watermark re-check; MAAT: range re-intersection).  The
    # repair sub-round re-validates losers through the backend's OWN
    # ``validate`` on the loser-masked batch, so the in-round conflict
    # semantics cannot diverge from the main round's.  None = not
    # repairable (chained backends never abort; NOCC never conflicts).
    repair_rule: Any = None


_NO_STATE = lambda cfg: ()  # noqa: E731

_REGISTRY: dict[CCAlg, CCBackend] = {
    CCAlg.NOCC: CCBackend(CCAlg.NOCC, validate_nocc, _NO_STATE,
                          needs_incidence=False),
    # the six sweep backends opt into the escrow exemption (gated by
    # escrow_order_free AND escrow_sweep): their edge derivations draw
    # from the ordered incidence views, so commutative hot-row updates
    # (TPC-C Payment's W_YTD/D_YTD, PPS PART_AMOUNT) commit many winners
    # per epoch instead of ~1 — the reference's per-row latch serializes
    # them within the window (row_lock.cpp:86-151) where epoch-snapshot
    # validation used to admit a single winner and abort-storm the rest
    CCAlg.NO_WAIT: CCBackend(CCAlg.NO_WAIT, validate_no_wait, _NO_STATE,
                             counts_in="lock_die", exempt_order_free=True,
                             repair_rule=_twopl.repair_frontier),
    CCAlg.WAIT_DIE: CCBackend(CCAlg.WAIT_DIE, validate_wait_die, _NO_STATE,
                              fresh_ts_on_restart=False,
                              counts_in="lock_die", exempt_order_free=True,
                              repair_rule=_twopl.repair_frontier),
    CCAlg.OCC: CCBackend(CCAlg.OCC, validate_occ, _NO_STATE,
                         exempt_order_free=True,
                         repair_rule=_occ.repair_frontier),
    CCAlg.TIMESTAMP: CCBackend(CCAlg.TIMESTAMP, validate_timestamp,
                               init_to_state, commit_state=commit_to_state,
                               exempt_order_free=True,
                               repair_rule=_tsmod.repair_frontier_timestamp),
    CCAlg.MVCC: CCBackend(CCAlg.MVCC, validate_mvcc, init_mvcc_state,
                          commit_state=commit_to_state,
                          counts_in="mvcc_waits", exempt_order_free=True,
                          repair_rule=_tsmod.repair_frontier_mvcc),
    CCAlg.MAAT: CCBackend(CCAlg.MAAT, validate_maat, _NO_STATE,
                          exempt_order_free=True,
                          repair_rule=_maat.repair_frontier),
    # forward=True: on blind-write workloads (YCSB) the forwarding
    # executor is the closed form of the reference Calvin's RFWD dirty-
    # read forwarding — the whole batch commits whatever the chain depth,
    # exactly like the reference's scheduler grinding a hot-key queue
    # serially WITHIN the batch (it never defers a chain to the next
    # epoch).  The chained sub-round path remains for non-blind
    # workloads (TPC-C/PPS), where its level budget models the lock
    # queues.  Round-2 weak #3 (CALVIN collapsing at high skew) was this
    # missing equivalence: the level budget denied what the reference
    # merely serializes.
    CCAlg.CALVIN: CCBackend(CCAlg.CALVIN, validate_calvin, _NO_STATE,
                            chained=True, forward=True,
                            exempt_order_free=True),
    CCAlg.TPU_BATCH: CCBackend(CCAlg.TPU_BATCH, validate_tpu_batch, _NO_STATE,
                               chained=True, forward=True,
                               exempt_order_free=True),
    # DGCC builds the exact-key dependency graph BEFORE commit
    # (cc/depgraph.py lane sort + segmented scans — no hashed-bucket
    # incidence at all, hence needs_incidence=False) and serializes
    # conflicting txns into chained waves; over-deep closures DEFER to
    # the retry queue, so aborts stay zero by construction.  forward
    # stays False on purpose: unlike CALVIN's blind-write forwarding
    # collapse, DGCC always executes its real wavefront — the [dgcc]
    # line's waves>1 is the anti-inert signal the smoke gate pins.
    CCAlg.DGCC: CCBackend(CCAlg.DGCC, validate_dgcc, _NO_STATE,
                          needs_incidence=False, chained=True,
                          exempt_order_free=True),
}


def get_backend(alg: CCAlg | str) -> CCBackend:
    return _REGISTRY[CCAlg(alg)]
