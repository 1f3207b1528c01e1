"""DGCC: dependency-graph wavefront execution backend (CC_ALG=DGCC).

PAPERS: *DGCC: A New Dependency Graph based Concurrency Control
Protocol* (arXiv:1503.03642) — separate dependency resolution from
execution: build the epoch's transaction dependency graph FIRST, then
execute along it, so conflicting transactions serialize instead of
aborting.  Every optimistic backend here detects conflicts after
execution and pays for them with aborts (OCC zipf-0.9 write-heavy:
0.842 abort rate even with repair, `results/repair`); DGCC runs the
audit plane's edge-derivation kernel (`cc/depgraph.py` — one exact-key
lane sort + segmented scans, zero bucket-collision false conflicts)
over the PLANNED access sets of all active txns and assigns each txn an
execution wave, the chained-level machinery CALVIN/TPU_BATCH already
execute through (`engine/epoch.run_levels`, the repair engine's
re-execution waves generalized): wave k re-reads only rows written by
waves < k.  Near-zero aborts by construction: the only non-commit
outcome is a DEFER of over-deep dependency closures to the next epoch's
retry queue — exactly repair's cyclic fallback, with no abort penalty.

Wave assignment (level relaxation, iterated segmented max over
predecessor levels):

* lanes: every ordered access doubles into a read lane (position
  ``2*r``) and/or a write lane (position ``2*r + 1``) where ``r`` is the
  txn's dense arrival rank — reads sit BELOW the same txn's writes, the
  executor's serial-in-rank gather-then-scatter semantics.
* per round, two exclusive segmented maxima over each key segment
  (`depgraph.seg_excl_max`) relax every txn's wave:
  -  wr/ww TRUE dependency: a READ lane must land strictly after every
     earlier writer of its key — ``lv >= max(earlier writer lv) + 1``;
  -  rw ANTI-dependency: a WRITE lane must not land before an earlier
     reader or writer of its key — ``lv >= max(earlier reader/writer
     lv)`` with NO increment: within one wave the executor gathers all
     reads before scattering writes, and same-wave duplicate writes
     resolve by the ``last_writer`` order tournament (the wavefront
     executor runs the tournament path, not the conflict-free
     ``level_exec`` fast path) — so a same-wave earlier-reader or
     earlier-writer is already serialized correctly.
* iterate to fixpoint (`lax.while_loop`), with candidates CLAMPED at
  the ``Config.dgcc_levels`` wave budget.  Each +1 hop needs its
  predecessor's updated value (~2 rounds per read-after-write
  alternation) but same-level propagation is instantaneous (the scans
  span whole key segments), so an un-clamped chain of true depth d
  converges in ~2d rounds — and the clamp makes saturation itself
  propagate segment-wide in O(1) rounds, bounding convergence at
  ~2*dgcc_levels however deep the hot-key chain really is (the
  ``2 * rounds + 4`` loop budget).  At the fixpoint, levels BELOW the
  clamp are exact longest-path waves and commit; saturated txns
  (``lv >= dgcc_levels`` — over-deep closures, and transitively
  everything downstream of one: a dependent of a saturated txn
  saturates too, so committed waves never read a hole) fall to the
  DEFER retry queue with ``abort`` kept zero.  A fixpoint miss inside
  even that budget (never observed; the anti-inert smoke scenario
  covers the deep-chain regime) defers the whole epoch — sound,
  non-localizable on device.

Escrow (``order_free``) lanes are exempt: commutative deltas carry no
ordering claim, contribute no lanes, and commit in wave 0 — the same
exemption the audit plane and `committed_write_frontier` apply.

The verdict is a pure replicated function of the merged batch (sort +
scans + scatter-max, no RNG, no cross-epoch state), so merged-mode
cluster nodes and mesh shards (dp>1) reproduce it bit-identically —
the cluster path ships the verdict exactly like CALVIN's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deneva_tpu.cc import depgraph
from deneva_tpu.cc.base import AccessBatch, Verdict
from deneva_tpu.ops import combine_key


def dgcc_levels(cfg, batch: AccessBatch):
    """Wave assignment: returns ``(lv, overflow, edge_cnt)`` — int32[B]
    exact wave per txn, bool[B] defer mask (over-deep closures plus, on
    a cut-short relaxation, every active txn), and the dependency-edge
    count of the epoch's nearest-predecessor graph (the [dgcc] line's
    density signal)."""
    b, a = batch.shape
    act = batch.valid & batch.active[:, None]
    if batch.order_free is not None:
        act = act & ~batch.order_free
    rm = act & batch.is_read
    wm = act & batch.is_write
    ident = combine_key(batch.table_ids, batch.keys)
    big = jnp.uint32(depgraph.LANE_PAD)

    # dense arrival positions over ACTIVE txns (stable iota tiebreak),
    # doubled so a txn's read lanes precede its own write lanes
    okey = jnp.where(batch.active, batch.rank, jnp.int32(2**31 - 1))
    perm = jnp.argsort(okey, stable=True)
    dpos = jnp.zeros((b,), jnp.int32).at[perm].set(
        jnp.arange(b, dtype=jnp.int32))
    rpos = dpos * 2
    wpos = dpos * 2 + 1

    tid = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None],
                           (b, a))
    keys2 = jnp.concatenate([jnp.where(rm, ident, big).reshape(-1),
                             jnp.where(wm, ident, big).reshape(-1)])
    pos2 = jnp.concatenate([
        jnp.broadcast_to(rpos[:, None], (b, a)).reshape(-1),
        jnp.broadcast_to(wpos[:, None], (b, a)).reshape(-1)])
    tid2 = jnp.concatenate([tid.reshape(-1), tid.reshape(-1)])
    sk, sp, sid = depgraph.lane_sort(keys2, pos2, tid2)
    sw = (sp & 1) == 1
    live = sk != big
    head, _tail = depgraph.segment_bounds(sk)

    # static edge census: lanes with a nearest preceding writer (wr/ww)
    # plus write lanes with a nearest preceding reader (rw).  Self-preds
    # (duplicate lanes of one txn) carry no ordering constraint.
    pw = depgraph.prev_writer(head, jnp.where(sw & live, sid,
                                              jnp.int32(-1)))
    pr = depgraph.prev_writer(head, jnp.where(~sw & live, sid,
                                              jnp.int32(-1)))
    dep = live & (((pw >= 0) & (pw != sid))
                  | (sw & (pr >= 0) & (pr != sid)))
    edge_cnt = dep.sum(dtype=jnp.int32)

    rounds = jnp.int32(max(1, cfg.dgcc_levels))

    def relax(lv):
        g = jnp.take(lv, sid)
        exw = depgraph.seg_excl_max(head, jnp.where(sw & live, g,
                                                    jnp.int32(-1)))
        exr = depgraph.seg_excl_max(head, jnp.where(~sw & live, g,
                                                    jnp.int32(-1)))
        # clamp at the wave budget: saturation then propagates like a
        # same-level hop (full-prefix max), so deep chains converge in
        # O(rounds) iterations instead of O(chain length) — and every
        # dependent of a saturated txn saturates with it
        cand = jnp.minimum(jnp.where(
            sw,
            jnp.maximum(jnp.maximum(exw, exr), 0),
            jnp.where(exw >= 0, exw + 1, 0)), rounds)
        return lv.at[sid].max(jnp.where(live, cand, 0))

    def cond(c):
        _lv, changed, i = c
        return changed & (i < 2 * rounds + 4)

    def body(c):
        lv, _changed, i = c
        lv2 = relax(lv)
        return lv2, (lv2 != lv).any(), i + 1

    lv0 = jnp.zeros((b,), jnp.int32)
    lv, changed, _i = jax.lax.while_loop(
        cond, body, (lv0, jnp.bool_(True), jnp.int32(0)))

    # at the fixpoint, sub-clamp levels are exact longest-path waves:
    # commit them; saturated txns are the over-deep closures (plus
    # everything downstream of one) — the cyclic-fallback DEFER.  A
    # fixpoint miss inside even the 2*rounds+4 budget cannot be
    # localized on device, so the whole epoch retries (never observed;
    # the anti-inert smoke scenario covers the deep-chain regime).
    deep = lv >= rounds
    overflow = batch.active & (deep | changed)
    return lv, overflow, edge_cnt


def validate_dgcc(cfg, state, batch: AccessBatch, inc=None, stats=None):
    """DGCC verdict: commit everything whose dependency closure fits the
    wave budget, DEFER the rest to the next epoch (abort stays zero —
    the near-zero-abort claim is by construction, pinned by the smoke
    gate's anti-inert scenario).  ``inc`` is unused: the lane graph is
    exact-key, so watermark coarsening and bucket incidence never
    inflate the wavefront.  ``stats``, when passed by the engine,
    accumulates the [dgcc] summary counters in place (the repair-engine
    stats contract)."""
    b, _a = batch.shape
    lv, overflow, edge_cnt = dgcc_levels(cfg, batch)
    commit = batch.active & ~overflow
    zeros = jnp.zeros((b,), bool)
    level = jnp.where(commit, lv, 0)
    if stats is not None:
        waves = (jnp.max(jnp.where(commit, level, -1))
                 + 1).astype(jnp.uint32)
        stats["dgcc_wave_cnt"] = stats["dgcc_wave_cnt"] + waves
        stats["dgcc_wave_max"] = jnp.maximum(stats["dgcc_wave_max"],
                                             waves)
        stats["dgcc_fallback_cnt"] = (
            stats["dgcc_fallback_cnt"]
            + overflow.sum(dtype=jnp.uint32))
        stats["dgcc_edge_cnt"] = (stats["dgcc_edge_cnt"]
                                  + edge_cnt.astype(jnp.uint32))
    return Verdict(commit=commit, abort=zeros,
                   defer=batch.active & overflow,
                   order=batch.rank, level=level), state
