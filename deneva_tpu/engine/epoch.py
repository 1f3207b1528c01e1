"""The epoch's device program: validate-and-execute, written once.

Everything here is traced into an XLA program and nothing of it knows a
host loop.  `epoch_core` is the middle of an epoch (a built
``AccessBatch`` -> verdict -> execution -> repair); its callers own what
enters and leaves an epoch: `engine/step.Engine` (the device-resident
transaction pool admits, selects and plans, and is updated from what
comes back) and `make_epoch_body` (the served path: the host's merged
batch in, the ``done / abort / defer / rep`` planes and the counters
out; `make_dist_step` and `make_dist_group` wrap it).  What the two need
differently reaches `epoch_core` as a VALUE from the caller that has it
(the pool's defer budget, its restamp space), never as a switch on who
calls.  `make_vote_steps` holds the batched 2PC jits, which validate and
execute in separate programs.

The phases of an epoch carry `jax.named_scope`s — metadata only, the
compiled program is the same — so a device trace can say which phase an
operation belongs to whatever the compiler numbers it
(`benchmark/phase_reduce.py`): `ep.plan` (the workload's plan, the
access batch, and on the forwarding path the plan sort of
`forward_verdict`, which validates nothing), `ep.validate` (incidence +
the backend's sweep: sweep backends only), `ep.read` / `ep.write`
(inside the workload's executor, where the gather and the scatter are),
`ep.levels`, `ep.repair`, `ep.stats` (counters), `ep.recon` (where a
plan marks reconnaissance: its mapping gather and the stale test); the
group program adds `ep.decode` and `grp.pack`.  Nested scopes read
innermost-first: a gather under `ep.levels/ep.read` is a read.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from deneva_tpu.cc import (AUDIT_KEY, AccessBatch, audit_mutate_verdict,
                           audit_observe, build_conflict_incidence,
                           conflict_density, gate_order_free, get_backend,
                           stale_recon)
from deneva_tpu.cc.depgraph import witness_count
from deneva_tpu.config import CCAlg, Config, Mode
from deneva_tpu.ops import (forward_verdict, forwarding_applies,
                            mc_defer_verdict)


def forced_sentinel_mask(batch):
    """YCSB_ABORT_MODE (reference `config.h:103`, `ycsb_txn.cpp:243-246`):
    a sentinel condition forces a logical abort, exercising the abort
    accounting deterministically.  Batch analogue: a txn whose RW-set
    touches key 0 logically aborts — ONCE: it releases its slot like a
    completed txn (a logical abort is a final answer, not a retry; an
    ever-firing sentinel would otherwise fill the pool with immortal
    txns).  Under the forwarding executor the forced txns are removed
    from the batch BEFORE dependency resolution, so no reader ever
    observes an aborted txn's write."""
    return ((batch.keys == 0) & batch.valid).any(axis=1) & batch.active


def access_batch(cfg, be, planned, *, ts, rank, active, **over):
    """The workload's plan as the CC layer's ``AccessBatch``
    (``order_free`` rides the batch pre-gated, so the incidence builder
    and the T/O watermark rules cannot disagree); ``over`` replaces or
    adds fields (the VOTE protocol's owner mask and read-only hint)."""
    fields = dict(
        table_ids=planned["table_ids"], keys=planned["keys"],
        is_read=planned["is_read"], is_write=planned["is_write"],
        valid=planned["valid"], ts=ts, rank=rank, active=active,
        order_free=gate_order_free(cfg, be, planned.get("order_free")),
        recon=planned.get("recon"))
    fields.update(over)
    return AccessBatch(**fields)


def plan_owner(cfg, planned, batch):
    """int32[B, A] owning partition of each access: the plan's map, or
    the ``key % part_cnt`` striping the VOTE protocol routes on."""
    return planned.get("owner",
                       batch.keys % jnp.int32(max(cfg.part_cnt, 1)))


def count_verdict(stats: dict, wl, queries, commit, abort, defer) -> None:
    """An epoch's decisions into the device counters, in all and per txn
    type (a dense compare-and-sum, the latency histogram's shape trick)."""
    stats["total_txn_commit_cnt"] += commit.sum(dtype=jnp.uint32)
    stats["total_txn_abort_cnt"] += abort.sum(dtype=jnp.uint32)
    stats["defer_cnt"] += defer.sum(dtype=jnp.uint32)
    tt = wl.txn_type_of(queries)
    n = stats["commit_by_type"].shape[0]
    onehot = tt[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :]
    stats["commit_by_type"] = stats["commit_by_type"] + \
        (onehot & commit[:, None]).sum(axis=0, dtype=jnp.uint32)
    stats["abort_by_type"] = stats["abort_by_type"] + \
        (onehot & abort[:, None]).sum(axis=0, dtype=jnp.uint32)


# the widths a near-empty level pass runs at
NARROW_WIDTHS = (32, 128)


def level_widths(b: int) -> tuple[int, ...]:
    """The static widths a level pass of a batch of ``b`` may run at,
    narrowest first: `NARROW_WIDTHS`, then ``b`` — a function of the
    batch alone, and one rung wherever the batch is no wider than the
    widest of them (the toy launches' whole batch).  Why 32 beside 128
    (`PERF.md` section 6, PR 39): a scatter into one of STOCK's 51 MB
    columns costs the chip ~0.09 us a lane up to the ~0.2 ms a sweep of
    the column takes, so TPC-C's passes 1-2 (~16 and 1 transactions x
    15 lines) pay 0.70 ms for the four at 128 transactions and 0.15 at
    32.  Not more rungs: each is an executor body to compile (TPC-C:
    ~2.5 s) and a conditional on a narrow pass's way (TPC-C: ~26 us,
    its carry is a hundred buffers)."""
    return NARROW_WIDTHS + (b,) if b > NARROW_WIDTHS[-1] else (b,)


def run_levels(cfg, wl, db, queries, exec_commit, verdict, stats,
               level_exec=True):
    """Chained sub-round execution to the DYNAMIC depth of this epoch:
    the wavefront executor — wave k re-reads only rows written by waves
    < k (each pass gathers from the db the previous passes scattered).

    Level-l txns read state that includes all writes of levels < l (the
    deterministic lock-queue order).  A `lax.while_loop` runs exactly
    ``max committed level + 1`` passes instead of unrolling the full
    ``exec_subrounds`` budget, and a near-empty pass costs its level's
    LIVE transactions, not the batch: once an epoch the committed lanes
    are ordered by (level, lane), stably, so level l is a contiguous
    run of that order; a pass whose run fits a narrow rung of the
    batch's `level_widths` gathers the queries and ``verdict.order`` at
    the run's first lanes and calls the executor at the narrowest such
    width, the lanes past the run masked (a masked lane writes what a
    masked lane of a whole-batch pass writes: zeros into a trash row,
    nothing into a ring, 0 into an add).  Lane order is kept inside a
    level, so ring appends land in the same slots and every rank by
    ``order`` is the same: the tables are those of the whole-batch
    pass, leaf for leaf.  A fuller level runs on the batch as it stands
    (no gather), and a batch of one rung traces to that pass alone.
    The executors' lane counters count lanes HANDED to a call, so they
    follow the width.

    ``level_exec=True`` (CALVIN/TPU_BATCH): each level's committed set
    is write-conflict-free by construction (`wavefront_levels` over the
    exact-key conflict matrix), so executors skip the ``last_writer``
    scatter-max tournament.  ``level_exec=False`` (DGCC): a wave may
    carry several writers of one key — rw anti-dependencies and blind
    ww chains serialize by the in-wave order tournament instead of
    extra waves, which is what keeps DGCC's wavefront shallow at
    write-heavy contention.

    Where ``stats`` carries them (the served chained path:
    `engine/step.init_device_stats(level_passes=True)`),
    ``level_pass_cnt`` counts the passes and ``narrow_pass_cnt`` those
    run under the batch's width.
    """
    b = exec_commit.shape[0]
    *narrow, full = level_widths(b)
    assert full == b and all(w < b for w in narrow), (narrow, full)
    lv_max = jnp.max(jnp.where(exec_commit, verdict.level, 0))

    def whole(lvl, db, stats):
        stats = dict(stats)
        db = wl.execute(db, queries, exec_commit & (verdict.level == lvl),
                        verdict.order, stats, level_exec=level_exec)
        return db, stats

    if narrow:
        # the committed lanes by (level, lane); what did not commit
        # sorts behind every level.  (`narrow[-1]` lanes of padding: a
        # `dynamic_slice` that does not fit is moved, not cut)
        lv = jnp.where(exec_commit, verdict.level, lv_max + 1)
        by_level = jnp.pad(jnp.argsort(lv, stable=True).astype(jnp.int32),
                           (0, narrow[-1]))

    def front(w, off, n, db, stats):
        stats = dict(stats)
        lanes = jax.lax.dynamic_slice_in_dim(by_level, off, w)
        rows = lambda a: jnp.take(a, lanes, axis=0, mode="clip")  # noqa: E731
        db = wl.execute(db, jax.tree.map(rows, queries),
                        jnp.arange(w, dtype=jnp.int32) < n,
                        rows(verdict.order), stats, level_exec=level_exec)
        if "narrow_pass_cnt" in stats:
            stats["narrow_pass_cnt"] = stats["narrow_pass_cnt"] + \
                jnp.uint32(1)
        return db, stats

    def body(carry):
        lvl, off, db, stats = carry
        if narrow:
            # (a compare-and-sum: `jnp.bincount` is a scatter-add on the
            # chip.)  Two-way `lax.cond`s, the whole-batch pass against
            # the narrow ones and those among themselves, narrowest
            # innermost: under a `lax.switch`, and in a nest that leans
            # the other way, the chip's compiler copies whole columns
            # (TPC-C's `OL_DIST_INFO`, 755 MB) inside a middle branch
            n = (lv == lvl).sum(dtype=jnp.int32)
            run = functools.partial(front, narrow[0], off, n)
            for under, w in zip(narrow, narrow[1:]):
                run = functools.partial(jax.lax.cond, n <= under, run,
                                        functools.partial(front, w, off, n))
            db, stats = jax.lax.cond(n <= narrow[-1], run,
                                     functools.partial(whole, lvl), db, stats)
            off = off + n
        else:
            db, stats = whole(lvl, db, stats)
        if "level_pass_cnt" in stats:
            stats["level_pass_cnt"] = stats["level_pass_cnt"] + \
                jnp.uint32(1)
        return lvl + 1, off, db, stats

    z = jnp.zeros((), jnp.int32)
    _, _, db, stats = jax.lax.while_loop(
        lambda carry: carry[0] <= lv_max, body, (z, z, db, stats))
    return db, stats


class CoreOut(NamedTuple):
    """What the middle of an epoch hands back to its caller."""
    db: Any
    cc_state: Any
    stats: dict
    verdict: Any            # final: budget merged, salvaged txns committed
    exec_commit: Any        # bool[B] executed against the tables
    release: Any            # bool[B] exec_commit + forced completions
    forced: Any             # bool[B] | None (ycsb_abort_mode)
    inc: Any                # the sweep's incidence, None where none is built
    rep: Any                # bool[B] | None: committed via repair
    srounds: Any            # int32[B] | None: each salvaged txn's sub-round


def _finalize(verdict, forced):
    """A forced txn completes-as-aborted only when the CC would not
    retry it anyway (CC aborts/defers follow their normal path);
    released slots are real commits + forced completions."""
    if forced is None:
        return None, verdict.commit, verdict.commit
    forced = forced & ~(verdict.abort | verdict.defer)
    return forced, verdict.commit & ~forced, verdict.commit | forced


def epoch_core(cfg: Config, wl, be, db, cc_state, stats, queries, batch, *,
               epoch=None, budget=None, ts_base=None) -> CoreOut:
    """The middle of an epoch, from a built ``AccessBatch``: forced
    sentinels -> verdict -> execution -> repair.

    ``epoch`` is an observation LABEL (the audit_mutate window key),
    never an input to a sound verdict.  ``budget`` (the in-process
    pool's defer budget; None where no pool counts defers) maps the
    backend's verdict to the one that is executed.  ``ts_base`` is the
    restamp space repaired txns draw fresh stamps from (the pool's
    reserved range; None: `engine/repair.repair_ts`'s default).

    ``cfg.mode``: NOCC validates nothing, SIMPLE / QRY_ONLY validate
    and ack without touching tables (reference SIMPLE_MODE /
    QRY_ONLY_MODE, `config.h:276-281`); forwarding, chained levels and
    repair belong to NORMAL.
    """
    normal = cfg.mode == Mode.NORMAL
    inc = rep = srounds = None
    with jax.named_scope("ep.plan"):
        forced = forced_sentinel_mask(batch) if cfg.ycsb_abort_mode else None

    if normal and forwarding_applies(be, wl):
        # single-pass forwarding executor (ops/forward): everything
        # commits in rank order; the sort IS the validation.  Forced
        # sentinel txns leave the batch before dependency resolution
        # so their (never-applied) writes are invisible to readers.
        fbatch = batch if forced is None else dataclasses.replace(
            batch, active=batch.active & ~forced)
        if cfg.device_parts > 1:
            # mesh-sharded: per-shard plans and the capacity-overflow
            # defers are decided inside wl.execute_mc (shard-local
            # O(N/D) + one all_gather), so the verdict is built AFTER
            # execution from the replicated defer mask
            db, mc_dfr = wl.execute_mc(db, fbatch, stats)
            verdict = mc_defer_verdict(fbatch, mc_dfr)
            forced, exec_commit, release = _finalize(verdict, forced)
        else:
            with jax.named_scope("ep.plan"):
                verdict, fwd = forward_verdict(fbatch)
            # (forward_verdict never aborts or defers: the forced rule
            # is applied anyway, for a forwarding backend that would)
            forced, exec_commit, release = _finalize(verdict, forced)
            # commit set baked into the plan (fbatch.active);
            # mask=None is asserted by the executor so the two
            # cannot diverge
            db = wl.execute(db, queries, None, verdict.order, stats,
                            fwd_rank=fwd)
        return CoreOut(db, cc_state, stats, verdict, exec_commit, release,
                       forced, None, None, None)

    if cfg.mode == Mode.NOCC:
        verdict, cc_state = get_backend("NOCC").validate(
            cfg, cc_state, batch, None)
    else:
        stale = None
        if be.chained and batch.recon is not None:
            # stale reconnaissance (`cc/base.stale_recon`): out of the
            # batch before any edge is drawn, deferred whole below
            with jax.named_scope("ep.recon"):
                stale = stale_recon(batch)
                batch = dataclasses.replace(batch,
                                            active=batch.active & ~stale)
        with jax.named_scope("ep.validate"):
            # (None for DGCC: an exact-key lane graph, cc/depgraph, no
            # hashed incidence — its verdict is a pure replicated
            # function of the merged batch like any other's)
            inc = build_conflict_incidence(cfg, be, batch, batch.order_free)
            # DGCC takes the stats dict (repair-engine contract): its
            # wave/fallback/edge counters come from inside the wave
            # assignment, where the lane graph is in hand
            # (and a backend that counts what it decides, where the
            # stats carry its counters: the served MVCC and 2PL programs',
            # `CCBackend.counts_in`)
            kw = {"stats": stats} if be.alg == CCAlg.DGCC \
                or stats.get(be.counts_in) is not None else {}
            verdict, cc_state = be.validate(cfg, cc_state, batch, inc, **kw)
        if stale is not None:
            verdict = dataclasses.replace(verdict,
                                          defer=verdict.defer | stale)
            if "recon_defer_cnt" in stats:
                # (the served PPS program counts them:
                # `engine/step.init_device_stats(recon_defers=True)`)
                stats["recon_defer_cnt"] = stats["recon_defer_cnt"] + \
                    stale.sum(dtype=jnp.uint32)
        if cfg.audit_mutate:
            # seeded edge-derivation fault (the audit plane's anti-inert
            # knob): flipped losers execute and ack like any commit — a
            # real isolation violation every server computes identically
            # (config-keyed) and replay reproduces (the epoch label
            # rides the log)
            verdict = audit_mutate_verdict(cfg, batch, inc, verdict, epoch)
    if budget is not None:
        verdict = budget(verdict)
    forced, exec_commit, release = _finalize(verdict, forced)

    chained = be.chained and normal
    if cfg.mode in (Mode.NORMAL, Mode.NOCC):
        if cfg.device_parts > 1:
            # generic partition-parallel execution (workloads/mc):
            # replicated verdict, owner-major sharded tables, the
            # workload's own execute body per chip under shard_map
            from deneva_tpu.workloads.mc import mc_execute
            db = mc_execute(cfg, wl, db, queries, exec_commit,
                            verdict.order, verdict.level, stats,
                            chained=chained,
                            level_exec=be.alg != CCAlg.DGCC,
                            n_levels=cfg.dgcc_levels
                            if be.alg == CCAlg.DGCC else None)
        elif chained:
            with jax.named_scope("ep.levels"):
                db, stats = run_levels(cfg, wl, db, queries, exec_commit,
                                       verdict, stats,
                                       level_exec=be.alg != CCAlg.DGCC)
        else:
            db = wl.execute(db, queries, exec_commit, verdict.order, stats)

    # transaction repair (engine/repair.py, default off): the losers of
    # the sweep re-execute as fused sub-rounds against the post-winner
    # state inside this same program; salvaged txns move abort -> commit
    # (and release like any commit) before any caller's counters see
    # them.  Part of the replicated deterministic verdict (config pins
    # merged mode; multi-chip is a config.validate error).
    if cfg.repair and normal and be.repair_rule is not None:
        from deneva_tpu.engine.repair import run_repair
        with jax.named_scope("ep.repair"):
            db, cc_state, verdict, rep, srounds = run_repair(
                cfg, wl, be, db, queries, batch, inc, verdict, cc_state,
                stats, exec_commit, forced, ts_base=ts_base)
        exec_commit = exec_commit | rep
        release = release | rep
    return CoreOut(db, cc_state, stats, verdict, exec_commit, release,
                   forced, inc, rep, srounds)


# ---- observations of an epoch: never an input to a verdict or a write --

def density_into(cfg, stats: dict, batch, owner, inc) -> None:
    """Metrics bus (``cfg.metrics``): accumulate the per-partition
    observed-conflict density (`cc.conflict_density`, off the incidence
    views the sweep already materialized; backends without one pay two
    bucket scatter-adds) into the device stats."""
    stats["conflict_density"] = stats["conflict_density"] + \
        conflict_density(cfg, batch, owner, inc).astype(jnp.uint32)


def observe_audit(cfg, db, stats: dict, batch, commit, verdict, srounds,
                  epoch, *, forwarding: bool, chained: bool, cadence=None):
    """Isolation audit (``cfg.audit``, `cc/base.audit_observe`): the
    dependency observations of the FINAL committed set ``commit``.
    Visibility: forwarding = serial-in-order; chained = levels; repair
    salvage waves = their sub-round; level-0 sweeps = epoch-start
    snapshot.  Updates the stamp tables in ``db`` and the ``audit_*``
    counters; returns ``(db, (edges, edge buckets, cnt, dropped, vdig,
    rdig))`` — the planes the served path exports and the in-process
    engine drops.  ``cadence``: the ctrl plane's knob as a traced
    operand (None: ``cfg.audit_cadence``)."""
    if forwarding:
        lvl = jnp.zeros_like(verdict.level)
    elif chained:
        lvl = verdict.level
    else:
        lvl = srounds if srounds is not None \
            else jnp.zeros_like(verdict.level)
    aud2, edges, ebkt, cnt, drop, vdig, rdig = audit_observe(
        cfg, batch, commit, verdict.order, lvl, forwarding, db[AUDIT_KEY],
        epoch, cadence=cadence)
    db = dict(db)
    db[AUDIT_KEY] = aud2
    stats["audit_edge_cnt"] += cnt.astype(jnp.uint32)
    stats["audit_drop_cnt"] += drop.astype(jnp.uint32)
    if not forwarding and not chained:
        # witness density (the controller's certificate-pressure
        # signal): a level-0 sweep backend claims a conflict-free
        # committed set, so any edge between two level-0 commits is a
        # claim violation (repair-salvaged endpoints sit at lvl >= 1);
        # chained waves and forwarded ranks carry legitimate edges
        stats["audit_wit_cnt"] += witness_count(edges, lvl).astype(
            jnp.uint32)
    return db, (edges, ebkt, cnt, drop, vdig, rdig)


# ---- the served path's programs -----------------------------------------

def _merged_batch(cfg: Config) -> int:
    # merged batch = equal slices per server; epoch_batch is the budget
    return max(1, cfg.epoch_batch // cfg.node_cnt) * cfg.node_cnt


def make_epoch_body(cfg: Config, wl, be):
    """Pure per-epoch validation+execution body shared by the per-epoch
    jit (replay path) and the pipelined multi-epoch dispatch group.

    Deterministic: every server runs this exact function on the identical
    merged batch, so verdicts agree without any vote exchange.
    Returns (body, b_merged) where body maps
    (db, cc_state, stats, active, ts, query, epoch=None) ->
    (db, cc_state, stats, done, restart_abort, defer, rep, dens, aud).
    ``rep`` marks txns that committed via transaction repair
    (engine/repair.py — a subset of ``done``; all-false when
    ``cfg.repair`` is off, and the group jit only packs its plane when
    armed, so the off-wire stays bit-identical).  ``dens`` is the
    per-partition observed-conflict density (int32[P], the metrics
    bus's per-epoch contention signal) when ``cfg.metrics`` is armed,
    else None — with metrics off the body computes nothing extra and
    the group jit's outputs are exactly the pre-bus ones.  ``aud`` is
    the isolation audit plane's per-epoch observation tuple
    (`observe_audit`) when ``cfg.audit`` is armed, else None; armed
    bodies take ``epoch`` — an observation LABEL (and the audit_mutate
    window key), never an input to any verdict, and the log replay path
    feeds the recorded epoch numbers back so replay reproduces the
    observations bit for bit.
    """
    b = _merged_batch(cfg)
    forwarding = forwarding_applies(be, wl) and cfg.mode == Mode.NORMAL

    def step(db, cc_state, stats, active, ts, query, epoch=None):
        dens = None
        aud_out = None
        with jax.named_scope("ep.plan"):
            rank = jnp.arange(b, dtype=jnp.int32)
            planned = wl.plan(db, query)
            batch = access_batch(cfg, be, planned, ts=ts, rank=rank,
                                 active=active)
        out = epoch_core(cfg, wl, be, db, cc_state, stats, query, batch,
                         epoch=epoch)
        db, cc_state, stats = out.db, out.cc_state, out.stats
        verdict, forced, rep = out.verdict, out.forced, out.rep
        if cfg.metrics:
            # an OBSERVATION of the batch, never an input to any
            # verdict, so replay determinism is untouched
            dens = conflict_density(cfg, batch,
                                    plan_owner(cfg, planned, batch), out.inc)
        # forced txns complete (acked + released by the caller via the
        # commit mask) but count as aborts, exactly like the engine
        with jax.named_scope("ep.stats"):
            commit = out.exec_commit & active
            done = commit if forced is None \
                else (commit | (forced & active))
            abort = verdict.abort & active
            if forced is not None:
                abort = abort | (forced & active)
            defer = verdict.defer & active
            stats = dict(stats)
            count_verdict(stats, wl, query, commit, abort, defer)
            rep = jnp.zeros_like(done) if rep is None else rep & active
        if cfg.audit:
            db, aud_out = observe_audit(
                cfg, db, stats, batch, commit, verdict, out.srounds, epoch,
                forwarding=forwarding, chained=be.chained)
        return (db, cc_state, stats, done, abort & ~done, defer, rep,
                dens, aud_out)

    return step, b


def make_dist_step(cfg: Config, wl, be):
    """Jitted single-epoch step (kept for the log-replay path, which
    re-executes the command stream one recorded epoch at a time)."""
    body, _ = make_epoch_body(cfg, wl, be)

    @jax.jit
    def step(db, cc_state, stats, epoch, active, ts, query):
        # determinism: verdicts depend only on the feed.  The audit
        # plane consumes the epoch as an observation LABEL (stamp-table
        # entries + the audit_mutate window key); replay feeds the
        # recorded epoch numbers back, so replayed observations are
        # bit-identical too.
        ep = epoch if cfg.audit else None
        return body(db, cc_state, stats, active, ts, query, epoch=ep)

    return step


def make_dist_group(cfg: Config, wl, be, width: int, n_scalars: int):
    """Jitted C-epoch dispatch group for the pipelined cluster loop.

    ``lax.scan`` threads (db, cc_state, stats) through ``pipeline_epochs``
    consecutive merged epochs in ONE device dispatch: the host pays its
    2-3 host<->device transfers (and their dispatch latency) per GROUP
    instead of per epoch.  Commit masks come back only for this
    node's slice of the merged batch (all a node ever consumes: CL_RSP +
    retry routing), cutting the down-transfer by node_cnt.  State buffers
    are donated so K in-flight groups do not multiply table memory.

    The feed is the RAW WIRE COLUMNS (keys/types/scalars), shipped as
    FLAT 1-D buffers and decoded on device by ``wl.from_wire_dev``: a
    [C, b, W] leaf with a small minor dimension (W ~ 10) gets its minor
    dim padded to the 128-lane tile in the device layout, so
    transferring it shaped costs ~13x the bytes.  Flat transfers
    relayout on chip at HBM speeds instead.
    """
    body, b = make_epoch_body(cfg, wl, be)
    C = max(1, cfg.pipeline_epochs)
    b_loc = b // cfg.node_cnt
    lo = cfg.node_id * b_loc
    # elastic + faults: verdict planes cover the FULL merged batch, not
    # just this node's slice — a survivor needs every slice's committed
    # tags for re-ack takeover after a dead peer's slots are reassigned
    # (the committed set must outlive its admitting server).  Off this
    # mode the shapes (and the d2h volume) are exactly the pre-elastic
    # ones.
    full_planes = cfg.elastic and cfg.faults_enabled
    mask_n = b if full_planes else b_loc
    sl = slice(0, b) if full_planes else slice(lo, lo + b_loc)
    pb = (mask_n + 7) // 8 * 8          # bit-pack padding

    # a 4th "repaired" verdict plane rides the d2h stack ONLY when the
    # repair subsystem is armed (rep_* accounting + the repair timeline
    # span at retirement); off, the stack shape and bytes are exactly
    # the pre-repair three planes
    n_planes = 4 if cfg.repair else 3

    def scan_body(carry, xs):
        db, cc_state, stats = carry
        if cfg.audit:
            # the audit plane labels each epoch's observations with its
            # number (stamp tables + the audit_mutate window key): the
            # host feeds the group's epoch indices as one extra int32[C]
            # scan input when — and only when — audit is armed
            active, ts, keys, types, scal, ep = xs
        else:
            active, ts, keys, types, scal = xs
            ep = None
        with jax.named_scope("ep.decode"):
            query = wl.from_wire_dev(keys, types, scal)
        db, cc_state, stats, done, abort, defer, rep, dens, aud = body(
            db, cc_state, stats, active, ts, query, epoch=ep)
        outs = (done[sl], abort[sl], defer[sl], rep[sl])
        if cfg.metrics:
            # per-epoch density plane rides the scan outputs ONLY when
            # the bus is armed — off, the d2h volume is exactly the
            # pre-bus verdict planes
            outs = outs + (dens,)
        if cfg.audit:
            # audit observation planes (edges/buckets/counts/digests)
            # ride the d2h stack only when armed — same off-contract as
            # the density plane
            outs = outs + aud
        return (db, cc_state, stats), outs

    def pack(m):
        # bool[C, b_loc] -> uint8[C, pb/8], little-endian bit order (the
        # host unpacks with np.unpackbits(bitorder="little")).  The
        # verdict planes cross d2h once per group and gate every ack:
        # as bits they are 8x fewer bytes than bools.
        w = jnp.pad(m, ((0, 0), (0, pb - mask_n))).reshape(m.shape[0], -1, 8)
        weights = jnp.left_shift(jnp.ones((8,), jnp.uint8),
                                 jnp.arange(8, dtype=jnp.uint8))
        return (w.astype(jnp.uint8) * weights).sum(-1).astype(jnp.uint8)

    # donation is claimed off the CPU backend only (the tests' backend
    # keeps every argument readable after the call).  Consequence for
    # host code on the chip: a donated array is DELETED at dispatch —
    # the caller adopts the returned state and never reads a feed
    # buffer again.  Besides the persistent state pytrees
    # (db/cc_state/stats), the per-group FEED buffers are donated too:
    # each is a fresh device_put the host never rereads, so XLA can
    # reuse their pages for the scan carries instead of allocating a
    # second copy per in-flight group — the "persistent donated epoch
    # buffers" half of the pod-scale path (the host side already
    # recycles the pinned staging buffers via _feed_acquire).
    donate = (0, 1, 2, 3, 4, 5, 6, 7) if jax.default_backend() != "cpu" \
        else ()

    @functools.partial(jax.jit, donate_argnums=donate)
    def group(db, cc_state, stats, active_f, ts_f, keys_f, types_f,
              scal_f, epochs_f=None):
        active = active_f.reshape(C, b)
        ts = ts_f.reshape(C, b)
        keys = keys_f.reshape(C, b, width)
        types = types_f.reshape(C, b, width)
        scal = scal_f.reshape(C, b, n_scalars)
        xs = (active, ts, keys, types, scal)
        if cfg.audit:
            xs = xs + (epochs_f,)
        (db, cc_state, stats), masks = jax.lax.scan(
            scan_body, (db, cc_state, stats), xs)
        with jax.named_scope("grp.pack"):
            planes = jnp.stack([pack(masks[i]) for i in range(n_planes)])
        out = (db, cc_state, stats, planes)
        if cfg.metrics:
            # int32[C, P] per-epoch density beside the packed planes
            # (the scan outputs carry the four mask planes at 0..3
            # whether or not repair packs its plane, so density sits at
            # the FIXED index 4)
            out = out + (masks[4],)
        if cfg.audit:
            # audit observation stack: ([C, E] edges, [C, E] buckets,
            # [C] cnt, [C] dropped, [C] vdig, [C] rdig)
            out = out + (masks[-6:],)
        return out

    return group


def make_vote_steps(cfg: Config, wl, be):
    """Batched 2PC (VOTE protocol) jits for non-deterministic backends.

    The reference coordinates a multi-partition txn with per-txn
    prepare/ack round trips (`system/txn.cpp:498-606`); here the whole
    epoch prepares at once:

    * ``vote(db, cc_state, query, active, ts)`` — each server validates
      ONLY the accesses it owns (the workload plan's ``owner`` map masks
      the rest invalid) against its LOCAL cross-epoch state, yielding its
      per-txn prepare votes.  Soundness: every conflicting access pair
      shares a key, the key's single owner sees both sides, and every
      backend's serialization order in vote mode is a *globally shared*
      total order (rank for locks/OCC, birth-ts for T/O) — so the union
      of locally-conflict-free commit sets is serializable in that order.
      (MAAT's locally-derived order is not shared — it negotiates
      positions through the vote payloads instead, below.)
    * ``apply(...)`` — after the vote exchange decides (commit = every
      owner voted yes, abort = any owner voted abort, else wait), execute
      the decided set locally and advance cross-epoch CC state for
      GLOBAL commits only (`CCBackend.commit_state` — the reference
      updates row ts-state on the 2PC commit path, not at prepare).

    MAAT (round-4): its dynamic serialization order is locally derived,
    so the vote additionally negotiates POSITIONS, the batch analogue of
    the reference's timestamp-range negotiation
    (`concurrency_control/maat.cpp:176-190` intersects `[lower,upper)`
    bounds shipped on RACK_PREP, `transport/message.cpp:1057-1137`):

    1. prepare: each owner's local validate yields per-txn lower-bound
       positions (``verdict.order // b`` — its local ancestor count),
       piggybacked on the VOTE message;
    2. intersect: every node takes the elementwise MAX of all bounds —
       the least position satisfying every owner's local constraints
       (the reference's range intersection, commit point = lower end);
    3. verify (``check``): each owner re-checks its local must-precede
       edges against the final positions; a violated edge — exactly the
       signature of a CROSS-NODE cycle such as distributed write skew,
       which no single owner can see — aborts its later-positioned
       endpoint, announced in a second VOTE round.  Survivors' edges all
       agree with one shared total order, so the union is serializable.
    """
    b = _merged_batch(cfg)
    me = cfg.node_id

    def local_batch(db, query, active, ts):
        rank = jnp.arange(b, dtype=jnp.int32)
        planned = wl.plan(db, query)
        # ro_hint: GLOBAL read-only classification from the unmasked plan
        # — without it a cross-partition rw-txn would look read-only to
        # the node owning only its reads and skip MVCC read validation.
        # order_free is per-access, so the owner mask composes: each
        # owner exempts exactly its owned escrow accesses (and advances
        # its LOCAL watermarks with the same rules at commit)
        return access_batch(
            cfg, be, planned, ts=ts, rank=rank, active=active,
            valid=planned["valid"] & (planned["owner"] == jnp.int32(me)),
            ro_hint=~(planned["valid"] & planned["is_write"]).any(axis=1))

    def global_order(batch):
        # must be identical on every node: locks/OCC serialize in merged
        # rank order; the T/O family in birth-ts order, with GLOBALLY
        # read-only MVCC txns at the snapshot point (batch.ro_hint comes
        # from the unmasked plan so every node agrees)
        if cfg.cc_alg == CCAlg.TIMESTAMP:
            return batch.ts
        if cfg.cc_alg == CCAlg.MVCC:
            return jnp.where(batch.ro_hint, 0, batch.ts)
        return batch.rank

    maat = cfg.cc_alg == CCAlg.MAAT

    @jax.jit
    def vote(db, cc_state, query, active, ts):
        batch = local_batch(db, query, active, ts)
        inc = build_conflict_incidence(cfg, be, batch, batch.order_free)
        verdict, _ = be.validate(cfg, cc_state, batch, inc)
        # MAAT lower bound = local serialization position (order packs
        # position * b + lane; undo the lane)
        lo = verdict.order // jnp.int32(b)
        return verdict.commit, verdict.abort, verdict.defer, lo

    @jax.jit
    def check(db, query, cand, ts, order):
        """MAAT verify round: my local must-precede edges AMONG THE
        GLOBAL COMMIT CANDIDATES (the AND of round-1 votes) vs the
        intersected positions; a violated edge aborts its
        later-positioned endpoint (the range that closed).  Candidates
        only: at node_cnt=1 each candidate's position is this node's own
        locally-consistent order, so no edge can violate and vote mode
        decides exactly like merged mode."""
        from deneva_tpu.cc.maat import must_precede
        batch = local_batch(db, query, cand, ts)
        inc = build_conflict_incidence(cfg, be, batch, batch.order_free)
        p = must_precede(cfg, inc, b)
        p = p & cand[:, None] & cand[None, :]
        # order values are distinct (lane tiebreak), so >= means >
        viol = p & (order[:, None] >= order[None, :])
        return viol.any(axis=1)

    @jax.jit
    def apply(db, cc_state, stats, query, active, ts, commit, abort,
              defer, order):
        batch = local_batch(db, query, active, ts)
        commit = commit & active
        abort = abort & active
        defer = defer & active
        if be.commit_state is not None:
            # watermark buckets are self-hashed from the batch (see
            # cc/timestamp._wm_bucket) — no incidence rebuild needed here
            cc_state = be.commit_state(cfg, cc_state, batch, None, commit)
        db = wl.execute(db, query, commit,
                        order if maat else global_order(batch), stats)
        stats = dict(stats)
        count_verdict(stats, wl, query, commit, abort, defer)
        return db, cc_state, stats

    return vote, check, apply
