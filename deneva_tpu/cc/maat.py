"""MAAT — dynamic timestamp-range validation (reference
`concurrency_control/maat.{h,cpp}`, `row_maat.{h,cpp}`).

The reference gives every txn a mutable commit-timestamp range
``[lower, upper]`` in a hashed global TimeTable (`maat.cpp:192-323`), has
accesses soft-lock rows by recording uncommitted reader/writer sets
(`row_maat.cpp:54-164`), and at validation shrinks ranges per five
conflict cases so that conflicting txns order *dynamically* — a reader may
serialize before a later-arriving writer instead of aborting
(`maat.cpp:44-162`).  Aborts happen only when a range closes
(lower >= upper).

Batch mapping.  Under epoch-snapshot execution the range algebra
collapses to its essence: every intra-epoch read observed the snapshot,
so the *only* ordering constraint is **reader-before-writer** — if i read
a key j writes, i's commit ts must precede j's.  Those constraints form a
directed must-precede graph P (one MXU matmul).  P decomposes into:

* **Mutual pairs** (``P[i,j] & P[j,i]``): RMW-RMW on a shared key, or
  crossed read/write pairs across two keys.  Both directions required =
  both ranges cannot stay open: in the reference's serial validation the
  first validator commits and the later one's lower bound rises past its
  upper — it ABORTS (`maat.cpp:44-162`; RMW-RMW pairs close the same
  way: each is in the other's uncommitted reader AND writer sets).  The
  batch analogue is the lex-first MIS sweep: winners are the txns a
  serial validation pass would admit first, losers abort with the
  backoff the reference's restart path applies.  (Round-2 cliff fixed
  here: a hot-key RMW clique of m txns is m*(m-1)/2 mutual pairs; the
  old cycle peel removed ONE member per iteration with a fixed budget of
  4, so TPC-C's warehouse-row cliques aborted *wholesale* — winners
  included — and MAAT posted 0 txn/s at 4-16 warehouses.)  Sweep-budget
  leftovers (undecided) defer: a budget artifact, not a closed range.
* **Residual one-directional edges**: a consistent assignment of commit
  timestamps exists iff no directed cycle (length >= 3) remains — and
  with real-valued ranges ANY acyclic structure is feasible in serial
  validation (a range only closes when committed txns sandwich it, which
  needs a cycle), so every acyclic txn must COMMIT, however deep its
  chain (ADVICE r3 redesign: the old level-budget test aborted deep
  acyclic chain middles as false cycle members and deferred the rest).
  Shallow-acyclic epochs (the common case, gated by `ops.level_sweep`
  instability) commit everything with longest-path levels as the
  topological order (= the reference's ``find_bound`` picking the least
  timestamp above all lower bounds, `maat.cpp:176-190`).  Otherwise one
  full-graph transitive closure (log2(B) boolean matmul squarings on
  the MXU) answers both questions exactly: a node is on a cycle iff
  SELF-REACHABLE, and ancestor count is a strict topological key for
  everything else.  Cycles follow serial-validation semantics — the
  LATEST validators are the ones whose ranges close — via
  ``maat_peel_rounds`` bounded peel iterations that abort the
  locally-youngest members of the initially-proven cycle set that are
  still level-unstable (cheap sweeps between closures; see the in-code
  note for the precise approximation); survivors order dynamically and
  commit (a 3-cycle commits two, `maat.cpp:44-162`).

  Liveness: acyclic txns always commit; cycles lose their youngest
  members every peel round; peel leftovers past the budget defer, and
  the engine's defer budget (``defer_rounds_max``) force-restarts them
  — no livelock in any case.

Blind write-write pairs need no edge: any linear extension applies them
last-writer-wins in ``order``, and reader-before-writer edges already
force every epoch reader of that key before both writers.

Cross-epoch state is unnecessary: prior-epoch committers are wholly
before the snapshot (the TimeTable's GC'd steady state).  MAAT is thus
the most permissive sweep backend — pure readers and blind writers never
conflict regardless of rank, and only closed ranges (mutual pairs and
directed cycles) abort — matching its paper's claim of fewer aborts than
OCC/2PL at a (here vanished) validation-cost premium.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deneva_tpu.cc.base import (AccessBatch, Incidence, Verdict,
                                committed_write_frontier)
from deneva_tpu.ops import (earlier_edges, greedy_first_fit,
                            precedence_levels)


def must_precede(cfg, inc: Incidence, b: int):
    """P[i, j] = i must precede j (i read a key j writes; snapshot read),
    minus the RMW self-overlap diagonal.  The ONE edge derivation shared
    by validate_maat and the distributed verify round
    (engine/epoch.make_vote_steps.check): the verify round must check
    exactly the edge set the positions were negotiated for.

    Escrow (``order_free``) exemption, gated by ``escrow_order_free``
    AND ``escrow_sweep``: the reader side draws from the ORDERED read
    incidence (ro aliases r when off).  Escrow writes are commutative
    deltas — like blind writes they need no range constraint among
    themselves (any linear extension accumulates the same sum) — and
    escrow reads are declared-immutable columns, so a TPC-C Payment
    epoch contributes NO must-precede edges: the warehouse-row RMW
    clique that used to close m*(m-1)/2 ranges per epoch vanishes,
    while an ordered read of the accumulator still precedes every
    uncommitted delta writer exactly as before."""
    p = inc.overlap("ro", "w")
    return p & ~jnp.eye(b, dtype=bool)


def repair_frontier(cfg, state, batch: AccessBatch, inc: Incidence,
                    committed, losers):
    """MAAT invalidation rule (transaction repair, engine/repair.py):
    range re-intersection.  A MAAT loser's commit-timestamp range closed
    — a mutual must-precede pair or a peeled cycle pinned its lower
    bound at or above its upper.  Every closing constraint is a
    reader-before-writer edge ``P[i, j]`` (under epoch snapshots the
    ONLY constraint MAAT has), so the loser's range re-opens exactly by
    re-reading the keys on its P-edges into the committed set: the
    re-read inverts the edge (j's value is now i's input, so i orders
    AFTER j with an open upper bound) — in access space that is the
    ordered-read-vs-committed-write frontier.  The repair sub-round then
    re-runs this module's validate restricted to the losers: mutual
    pairs re-sweep, residual cycles re-peel — the range re-intersection
    one snapshot later, against ranges that all start open."""
    return committed_write_frontier(cfg, batch, inc, committed, losers)


def validate_maat(cfg, state, batch: AccessBatch, inc: Incidence):
    b = batch.active.shape[0]
    p = must_precede(cfg, inc, b)
    lane = jnp.arange(b, dtype=jnp.int32)

    # -- stage 1: mutual pairs -> lex-first MIS, losers' ranges close ---
    mutual = p & p.T
    e = earlier_edges(mutual, batch.rank, batch.active)
    win, lose, und = greedy_first_fit(e, batch.active,
                                      rounds=cfg.sweep_rounds)
    closed = lose & batch.active
    defer = und & batch.active

    # -- stage 2: peel true cycles (>= 3) from the residual digraph -----
    live0 = batch.active & ~closed & ~defer
    gt = (batch.rank[None, :] > batch.rank[:, None]) | (
        (batch.rank[None, :] == batch.rank[:, None])
        & (lane[None, :] > lane[:, None]))

    # cheap gate: any instability (cycle members always have lv >=
    # rounds; so do over-deep chains) routes to the closure branch.  The
    # common shallow-acyclic epoch keeps the level order and pays no
    # matmuls beyond the sweeps.
    lv_f, un_f0 = precedence_levels(p, live0, rounds=cfg.sweep_rounds)
    closure_rounds = max(1, (b - 1).bit_length())   # paths up to 2^k >= b

    def fast(_):
        zero = jnp.zeros_like(live0)
        return zero, zero, lv_f

    def closure(_):
        # Full-graph transitive closure by boolean matmul squaring on the
        # MXU (log2(B) squarings cover every simple path).  It answers
        # both open questions at once, exactly:
        # * cycles: a node is on a directed cycle iff self-reachable —
        #   never true for acyclic nodes, so deep chains are spared
        #   (ADVICE r3: the old both-directions-unstable test aborted
        #   them);
        # * order: ancestor COUNT is a strict topological key on the
        #   acyclic part (i -> j implies anc(j) >= anc(i)+1), so every
        #   acyclic txn commits regardless of chain depth — matching
        #   serial validation, where real-valued ranges make any DAG
        #   feasible (`maat.cpp:44-162` only closes a range against
        #   already-committed txns that sandwich it, which needs a
        #   cycle).
        # Serial-validation semantics on cycles: the LATEST validators
        # are the ones whose ranges close, so each peel round aborts the
        # locally-youngest proven cycle members, recomputes
        # reachability, and repeats — survivors order dynamically and
        # COMMIT (a 3-cycle commits two).  Fixed trip count (ADVICE r3:
        # the old fixpoint while_loop was a data-dependent latency
        # cliff); cycle leftovers past the budget defer, and the
        # engine's defer budget backstops their liveness.
        def square(_, r):
            f = r.astype(jnp.bfloat16)
            return r | (jnp.matmul(
                f, f, preferred_element_type=jnp.float32) > 0)

        def reach_of(live):
            sub = p & live[:, None] & live[None, :]
            return jax.lax.fori_loop(0, closure_rounds, square, sub)

        on_cycle0 = jnp.diagonal(reach_of(live0)) & live0
        sym = p | p.T

        # peel rounds are CHEAP (level sweeps, no matmuls — recomputing
        # the closure every round would cost 16x the matmuls): victims
        # are the locally-youngest members of the INITIAL proven cycle
        # set that are still unstable both ways after earlier removals.
        # Approximation, stated precisely: instability is a proxy for
        # "still on a cycle", so an ex-cycle node sitting in a residual
        # chain segment deeper than ~2*sweep_rounds from both ends can
        # still be peeled (conservative: extra abort, never a wrong
        # commit).  A PURE chain node is never on_cycle0, so the ADVICE
        # r3 class — acyclic txns aborted as cycle members — cannot
        # recur; only txns that started the epoch on a real cycle pay.
        def peel(_, aborted):
            live = live0 & ~aborted
            _, un_f = precedence_levels(p, live, rounds=cfg.sweep_rounds)
            _, un_r = precedence_levels(p.T, live,
                                        rounds=cfg.sweep_rounds)
            candr = un_f & un_r & on_cycle0
            nb = sym & candr[:, None] & candr[None, :]
            has_younger = (nb & gt).any(axis=1)
            return aborted | (candr & ~has_younger)

        aborted = jax.lax.fori_loop(0, cfg.maat_peel_rounds, peel,
                                    jnp.zeros_like(batch.active))
        # order + leftover pass on the survivor graph: committed txns
        # are never self-reachable here, so ancestor count is a STRICT
        # topological key for them; still-cyclic leftovers past the
        # peel budget defer (the engine's defer budget backstops them)
        live = live0 & ~aborted
        reach = reach_of(live)
        leftover = jnp.diagonal(reach) & live
        anc = jnp.sum(reach, axis=0, dtype=jnp.int32)
        return aborted, leftover, anc

    aborted, defer2, ordkey = jax.lax.cond(un_f0.any(), closure, fast,
                                           None)
    defer = defer | (defer2 & live0)
    commit = live0 & ~aborted & ~defer2
    order = ordkey * b + lane                 # topological extension of P
    v = Verdict(commit=commit, abort=(closed | aborted) & batch.active,
                defer=defer, order=order,
                level=jnp.zeros_like(batch.rank))
    return v, state
