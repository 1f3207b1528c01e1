"""Transaction repair engine: salvage aborts by re-executing only the
invalidated slice (PAPERS: *Transaction Repair: Full Serializability
Without Locks*; DGCC's dependency-graph batching, arXiv:1503.03642).

The retry queue treats every abort as total loss: the txn re-enters
admission, re-plans, re-reads everything and pays an exponential backoff
— even when only a fraction of its reads were invalidated by the epoch's
winners.  But every sweep backend already materializes the conflict
incidence the repair literature needs (`cc.base.build_incidence`), so
the invalidated-read frontier of each loser is one matvec away, and the
Calvin chained sub-round machinery (`cc/calvin.py`, `engine/epoch.
run_levels`) is the template for executing a second dependent wave
inside the same epoch.  Repair turns the losers of a sweep round into
that second wave:

1. **Frontier** — the backend's invalidation rule
   (``CCBackend.repair_rule``: OCC read-set vs winner write-set, 2PL
   lock-edge losers, T/O wts/rts watermark re-check, MAAT range
   re-intersection) names, per access, which of a loser's reads saw a
   value the committed set overwrote.  Losers with an EMPTY frontier
   lost on write-only conflicts (blind writes recompute — nothing to
   re-read) or on hash collisions; they salvage in the first sub-round.
2. **Mini-validation restricted to the repaired set** — the backend's
   OWN ``validate`` runs on the loser-masked batch (``active=losers``;
   fresh-ts backends restamp above every stamp in the epoch, WAIT_DIE
   keeps its birth ts exactly like its retry path).  Reusing the main
   round's edge derivation is what makes the sub-round sound per
   backend: T/O's later-reader-waits sweep, OCC's serial admission,
   MAAT's mutual-pair/cycle machinery all apply one snapshot later.
3. **Masked re-read + recomputed writes + scatter-apply** — the
   sub-round's winners re-execute through the workload's pure
   re-execution closure (``wl.re_execute``, keyed by txn slot: the
   query pytree row IS the captured plan).  Reads gather the
   post-winner state; lanes OUTSIDE the frontier re-read values nothing
   overwrote, so the full re-gather is bit-identical to a masked
   re-read of only the invalidated keys (the frontier is a bucket-space
   SUPERSET of the true overwrites — `cc.base.committed_write_frontier`).
4. **Chaining** — sub-round r+1's losers re-validate against a
   committed set that includes sub-round r's winners (state threading
   carries T/O watermarks across rounds).  After ``repair_rounds``
   passes the leftovers — cyclic re-invalidation: each pass's winners
   keep invalidating the rest — fall back to the retry queue exactly as
   before.

Serialization order: main-round winners in their verdict order, then
sub-round 1's winners, then sub-round 2's, each sub-round internally
ordered by its own verdict (executed as separate scatter waves, so the
physical apply order IS the serial order).  Each repaired txn re-read
every value it consumes at its new position, and each sub-round's
commit set is conflict-free under the backend's own rule — the chained
sub-round argument of `cc/calvin.py`, applied to salvage.  For the T/O
family the honest caveat mirrors escrow's: repaired txns serialize in
ROUND order at fresh stamps, so commit order — not birth-ts order — is
the serial order, and a cross-round intra-epoch conflict simply fails
the watermark re-check and retries (conservative, never a wrong
commit).

Default-off contract: with ``repair=false`` (default) no caller invokes
anything here and every code path, log byte, verdict plane and ack is
bit-identical to pre-repair — enforced by the graftlint gate family
(``repair`` in `runtime/gates.py`).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


def repair_ts(batch, ts_base=None):
    """Fresh per-lane serialization stamps for the repair sub-rounds:
    unique per lane, preserving lane order (the same relative order the
    retry path's restamp space ``next_seq - B + lane`` would assign).

    ``ts_base`` is the caller's monotone stamp authority when it has
    one: the in-process engine passes its pool's reserved restamp base
    (``next_seq - B`` — strictly above every committed watermark AND
    every stamp in the epoch, exactly like `engine.pool.TxnPool.update`
    restamps aborts).  Without it (the cluster epoch body, which is a
    pure function of its feed — no epoch counter by the replay-
    determinism contract), the fallback is ``max(active ts) + 1``:
    above every watermark whenever the epoch carries at least one fresh
    arrival (the server stamps fresh arrivals monotonically past all
    prior commits); an epoch of ONLY old parked retries can leave the
    fallback at or below a watermark, in which case the T/O re-check
    simply declines the salvage — conservative, never a wrong commit.

    A cross-EPOCH equality collision with a later stamp is benign: the
    T/O checks are strict (``>``), so an equal-ts reader/writer pair
    resolves as reads-committed-value / overwrites-after — consistent
    with the actual commit order — and intra-batch ties are broken by
    lane everywhere (`ops.earlier_edges`)."""
    lane = jnp.arange(batch.ts.shape[0], dtype=jnp.int32)
    if ts_base is None:
        ts_base = jnp.max(jnp.where(batch.active, batch.ts, 0)) + 1
    return ts_base + lane


def run_repair(cfg, wl, be, db, queries, batch, inc, verdict, cc_state,
               stats, exec_commit, forced=None, ts_base=None,
               rounds_cap=None):
    """Run ``cfg.repair_rounds`` fused repair sub-rounds over the epoch's
    losers, inside the SAME jitted epoch program as the main round.

    Inputs are the main round's artifacts: the planned ``batch``, its
    ``inc``idence views, the backend ``verdict`` (post defer-budget
    merge), the threaded ``cc_state`` and the executed commit mask
    ``exec_commit``.  Returns ``(db, cc_state, verdict', salvaged,
    rounds)`` where ``rounds`` is int32[B] naming each salvaged txn's
    sub-round (1-based; 0 = main-round/not salvaged — the audit
    plane's visibility level: a round-r salvage re-read state that
    includes every wave < r) and ``verdict'`` has the salvaged txns
    moved from ``abort`` to ``commit`` — so retry routing, ack planes
    and the abort counters downstream never see a salvaged txn as
    aborted
    (``rep_salvaged_cnt`` counts them instead; the satellite contract
    for `harness/parse.py` compatibility).  Device-counter contract:
    ``rep_salvaged_cnt + rep_fallback_cnt`` equals the repair-eligible
    losers of the epoch, and ``rep_frontier_cnt`` totals invalidated
    read lanes observed across sub-rounds.

    ``forced`` (the ycsb_abort_mode sentinel) txns are logical aborts —
    final answers, never salvaged.

    ``rounds_cap`` (the ctrl plane's repair-budget knob, int32 traced
    scalar): statically-unrolled rounds at index >= cap skip their
    whole body via ``lax.cond`` — real compute saved at low fallback
    rates, not just masked lanes.  None (default) compiles the exact
    pre-ctrl graph; cap == cfg.repair_rounds is value-identical to it
    (every cond takes the live branch)."""
    import jax

    losers = verdict.abort & batch.active
    if forced is not None:
        losers = losers & ~forced
    committed = exec_commit & batch.active
    salvaged = jnp.zeros_like(losers)
    rounds = jnp.zeros_like(batch.rank)
    fresh = repair_ts(batch, ts_base)
    frontier_cnt = stats["rep_frontier_cnt"]

    def one_round(rnd, carry):
        db, cc_state, committed, losers, salvaged, rounds, fcnt, \
            stats_r = carry
        frontier = be.repair_rule(cfg, cc_state, batch, inc, committed,
                                  losers)
        fcnt = fcnt + frontier.sum(dtype=jnp.uint32)
        rb = dataclasses.replace(batch, active=losers)
        if be.fresh_ts_on_restart:
            # restamp like the retry path would — but NOW, not an epoch
            # (plus backoff) later; WAIT_DIE keeps its birth ts (its
            # starvation-freedom) exactly as its retries do
            rb = dataclasses.replace(rb, ts=jnp.where(losers, fresh,
                                                      batch.ts))
        rv, cc_state = be.validate(cfg, cc_state, rb, inc)
        rep = rv.commit & losers
        # masked re-read + recomputed writes + scatter-apply: the
        # workload's pure re-execution closure against CURRENT state
        # (which includes every prior wave's writes — the chained
        # sub-round dataflow)
        stats_r = dict(stats_r)
        db = wl.re_execute(db, queries, rep, rv.order, stats_r)
        salvaged = salvaged | rep
        rounds = jnp.where(rep, jnp.int32(rnd + 1), rounds)
        committed = committed | rep
        # the sub-round's own aborts/defers (still-conflicting losers)
        # chain into the next pass; leftovers past the budget fall back
        losers = losers & ~rep
        return (db, cc_state, committed, losers, salvaged, rounds,
                fcnt, stats_r)

    carry = (db, cc_state, committed, losers, salvaged, rounds,
             frontier_cnt, stats)
    for rnd in range(cfg.repair_rounds):
        if rounds_cap is None:
            carry = one_round(rnd, carry)
        else:
            carry = jax.lax.cond(
                jnp.int32(rnd) < rounds_cap,
                lambda c, r=rnd: one_round(r, c), lambda c: c, carry)
    (db, cc_state, committed, losers, salvaged, rounds, frontier_cnt,
     stats_out) = carry
    # write back through the CALLER'S dict (run_repair's contract is
    # in-place stats mutation, like wl.execute's)
    for k, v in stats_out.items():
        stats[k] = v
    stats["rep_frontier_cnt"] = frontier_cnt
    stats["rep_salvaged_cnt"] = stats["rep_salvaged_cnt"] \
        + salvaged.sum(dtype=jnp.uint32)
    stats["rep_fallback_cnt"] = stats["rep_fallback_cnt"] \
        + losers.sum(dtype=jnp.uint32)
    verdict = dataclasses.replace(
        verdict, commit=verdict.commit | salvaged,
        abort=verdict.abort & ~salvaged)
    return db, cc_state, verdict, salvaged, rounds


def repair_line(node: int, fields: dict) -> str:
    """Per-node ``[repair]`` summary line (parsed by
    `harness.parse.parse_repair`; same fwd/bwd-compat contract as the
    ``[membership]``/``[replication]``/``[admission]`` families)."""
    from deneva_tpu.stats import tagged_line
    return tagged_line("repair", {"node": node, **fields})
