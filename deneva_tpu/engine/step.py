"""The jitted epoch step + scan driver (reference call stack §3.B collapsed).

One epoch performs what the reference spreads over client threads, IO
threads, worker threads, the CC managers and 2PC:

    refill   — admit fresh queries        (client_thread + new_txn_queue)
    select   — oldest-B runnable txns     (work_queue dequeue loop)
    plan     — declare padded RW-sets     (ycsb/tpcc/pps txn state machines)
    validate — CC backend verdict         (concurrency_control/*)
    execute  — gather/compute/scatter     (row_t reads + return_row commits)
    update   — free/backoff/park slots    (txn_table + abort_queue)

Everything is one XLA program; `run_epochs` wraps it in `lax.scan` so a
benchmark window runs thousands of epochs without leaving the device.
2PC itself has no analogue: epoch-snapshot validation decides all
participants of a txn at once (the conflict matrix *is* the vote), which
is precisely why the TPU build can win — prepare/ack round-trips
(`system/txn.cpp:498-606`) become matmul cycles.

Chained backends (CALVIN/TPU_BATCH) execute ``exec_subrounds`` waves:
level-l txns read state that already includes writes of levels < l —
deterministic dataflow equal to serial execution in sequence order.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from deneva_tpu.cc import (AccessBatch, build_conflict_incidence,
                           conflict_density, gate_order_free, get_backend)
from deneva_tpu.config import CCAlg, Config, Mode
from deneva_tpu.engine.pool import PoolState, TxnPool
from deneva_tpu.ops import (forward_verdict, forwarding_applies,
                            mc_defer_verdict)

LAT_BUCKETS = 64
RETRY_BUCKETS = 8      # per-txn restart/wait counts at commit (clipped)


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


@dataclass
class EngineState:
    db: Any                 # dict[str, DeviceTable]
    cc_state: Any
    pool: PoolState
    rng: jax.Array
    epoch: jax.Array        # int32
    stats: dict             # str -> device scalar / latency histogram


jax.tree_util.register_dataclass(
    EngineState,
    data_fields=["db", "cc_state", "pool", "rng", "epoch", "stats"],
    meta_fields=[])


def init_device_stats(n_txn_types: int = 1, n_parts: int = 1) -> dict:
    z = lambda: jnp.zeros((), jnp.uint32)  # noqa: E731
    return {
        # per-partition observed-conflict density (cc/base.
        # conflict_density; the metrics bus's contention signal and the
        # contention-adaptive router's input).  Always present so the
        # stats pytree shape depends only on the config; stays zero
        # unless metrics is armed.
        "conflict_density": jnp.zeros((max(n_parts, 1),), jnp.uint32),
        "generated_cnt": z(), "admitted_cnt": z(),
        "total_txn_commit_cnt": z(), "total_txn_abort_cnt": z(),
        "unique_txn_abort_cnt": z(),
        "defer_cnt": z(), "write_cnt": z(), "read_checksum": z(),
        # lanes handed to YCSB's F0 scatter (ops/scatter.
        # scatter_winner_rows): against write_cnt and the epoch's lane
        # count it says how far the winner compaction engages
        "write_scatter_lanes": z(),
        # lanes handed to YCSB's F0 gather: under a forwarding plan with
        # full rows the unforwarded reads, in whole sixteenths of the
        # plan (ops/gather.checksum_needed_rows), else every lane
        "read_gather_lanes": z(),
        # commit latency in epochs, PER TXN TYPE (round-4: the
        # reference's per-txn StatsArr families, stats_array.cpp);
        # the driver calibrates buckets to wall seconds per chunk
        "latency_hist": jnp.zeros((n_txn_types, LAT_BUCKETS), jnp.uint32),
        # per-txn work decomposition at commit time (reference TxnStats,
        # system/txn.h:72-114): how many restarts (abort_cnt) and how
        # many waited epochs (defer_cnt) each committed txn paid
        "retry_hist": jnp.zeros((RETRY_BUCKETS,), jnp.uint32),
        "wait_hist": jnp.zeros((RETRY_BUCKETS,), jnp.uint32),
        # transaction repair (engine/repair.py, Config.repair): txns
        # salvaged by in-epoch re-execution (committed, NOT counted in
        # total_txn_abort_cnt), invalidated read lanes observed, and
        # losers that exhausted repair_rounds and fell back to the
        # retry queue.  Always present (pytree structure is config-
        # independent); stay zero unless repair is armed.
        "rep_salvaged_cnt": z(), "rep_frontier_cnt": z(),
        "rep_fallback_cnt": z(),
        # isolation audit plane (cc/base.audit_observe, Config.audit):
        # dependency edge-lanes observed among committed txns, export-
        # cap overflows, and CLAIM-VIOLATING edges (both endpoints at
        # level 0 of a zero-edge-claim backend — cc/depgraph.
        # witness_count, the controller's witness-density signal).
        # Always present (pytree structure is config-independent); stay
        # zero unless audit is armed.
        "audit_edge_cnt": z(), "audit_drop_cnt": z(),
        "audit_wit_cnt": z(),
        # DGCC wavefront backend (cc/dgcc.py, CC_ALG=DGCC): waves
        # executed (sum over epochs), deepest single-epoch wavefront,
        # over-deep closures deferred to the retry queue (the cyclic
        # fallback), and dependency edges in the pre-commit lane graph.
        # Always present; stay zero unless DGCC validates.
        "dgcc_wave_cnt": z(), "dgcc_wave_max": z(),
        "dgcc_fallback_cnt": z(), "dgcc_edge_cnt": z(),
        # per-txn-kind commit/abort breakdown (reference Stats_thd's
        # per-type counter families); names come from
        # Workload.txn_type_names at summary time
        "commit_by_type": jnp.zeros((n_txn_types,), jnp.uint32),
        "abort_by_type": jnp.zeros((n_txn_types,), jnp.uint32),
    }


def count_by_type(stats: dict, wl, queries, commit, abort) -> None:
    """Fold per-type commit/abort one-hots into the device stats (cheap
    dense compare-and-sum, same shape trick as the latency histogram)."""
    tt = wl.txn_type_of(queries)
    n = stats["commit_by_type"].shape[0]
    onehot = tt[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :]
    stats["commit_by_type"] = stats["commit_by_type"] + \
        (onehot & commit[:, None]).sum(axis=0, dtype=jnp.uint32)
    stats["abort_by_type"] = stats["abort_by_type"] + \
        (onehot & abort[:, None]).sum(axis=0, dtype=jnp.uint32)


def _run_levels(cfg, wl, db, queries, exec_commit, verdict, stats,
                level_exec=True):
    """Chained sub-round execution to the DYNAMIC depth of this epoch:
    the wavefront executor — wave k re-reads only rows written by waves
    < k (each pass gathers from the db the previous passes scattered).

    Level-l txns read state that includes all writes of levels < l (the
    deterministic lock-queue order).  A `lax.while_loop` runs exactly
    ``max committed level + 1`` passes instead of unrolling the full
    ``exec_subrounds`` budget — at low contention most epochs execute 1-2
    levels, so a generous budget (deep-chain admission) no longer costs
    idle full-batch passes on shallow epochs.

    ``level_exec=True`` (CALVIN/TPU_BATCH): each level's committed set
    is write-conflict-free by construction (true conflicts are a subset
    of the hashed over-approximation), so executors skip the
    ``last_writer`` scatter-max tournament.  ``level_exec=False``
    (DGCC): a wave may carry several writers of one key — rw anti-
    dependencies and blind ww chains serialize by the in-wave order
    tournament instead of extra waves, which is what keeps DGCC's
    wavefront shallow at write-heavy contention.
    """
    lv_max = jnp.max(jnp.where(exec_commit, verdict.level, 0))

    def cond(carry):
        lvl, _, _ = carry
        return lvl <= lv_max

    def body(carry):
        lvl, db, stats = carry
        m = exec_commit & (verdict.level == lvl)
        stats = dict(stats)
        db = wl.execute(db, queries, m, verdict.order, stats,
                        level_exec=level_exec)
        return lvl + 1, db, stats

    _, db, stats = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), db, stats))
    return db, stats


class Engine:
    """Binds (config, workload, cc backend) into jitted step/scan fns."""

    def __init__(self, cfg: Config, workload):
        self.cfg = cfg
        self.workload = workload
        self.backend = get_backend(cfg.cc_alg)
        cap = max(cfg.max_txn_in_flight, cfg.epoch_batch)
        self.pool = TxnPool(capacity=cap, batch=cfg.epoch_batch,
                            gen_chunk=cfg.epoch_batch,
                            backoff=cfg.backoff)

    # ------------------------------------------------------------------
    def init_state(self, seed: int | None = None) -> EngineState:
        cfg = self.cfg
        db = self.workload.load()
        empty_q = self.workload.generate(
            jax.random.PRNGKey(0), self.pool.p)
        pool = self.pool.create(jax.tree.map(jnp.zeros_like, empty_q))
        return EngineState(
            db=db, cc_state=self.backend.init_state(cfg), pool=pool,
            rng=jax.random.PRNGKey(cfg.seed if seed is None else seed),
            epoch=jnp.zeros((), jnp.int32),
            stats=init_device_stats(len(self.workload.txn_type_names),
                                    max(cfg.part_cnt, 1)))

    # ------------------------------------------------------------------
    def step(self, state: EngineState, knobs=None) -> EngineState:
        if knobs is not None:
            # contention-adaptive router (Config.ctrl, cc/router.py):
            # the controller's per-epoch knob pytree selects the CC
            # branch + incidence granularity per partition.  knobs=None
            # (the default, and the only path when ctrl is off) is this
            # exact pre-ctrl body, untouched.
            return self._routed_step(state, knobs)
        cfg, wl, be = self.cfg, self.workload, self.backend
        rng, gen_key = jax.random.split(state.rng)
        stats = dict(state.stats)

        # 1. admit fresh queries
        newq = wl.generate(gen_key, self.pool.g)
        pool, admitted = self.pool.refill(state.pool, newq, state.epoch)
        stats["generated_cnt"] += jnp.uint32(self.pool.g)
        stats["admitted_cnt"] += admitted.astype(jnp.uint32)

        # 2. select epoch batch (full-pool mode: identity, no gathers)
        slots, active, queries = self.pool.select(pool, state.epoch)
        sel = (lambda v: v) if self.pool.full_pool \
            else (lambda v: jnp.take(v, slots))

        # 3. plan RW-sets (order_free rides the batch pre-gated so the
        # incidence builder and the T/O watermark rules cannot disagree)
        planned = wl.plan(state.db, queries)
        batch = AccessBatch(
            table_ids=planned["table_ids"], keys=planned["keys"],
            is_read=planned["is_read"], is_write=planned["is_write"],
            valid=planned["valid"],
            ts=sel(pool.ts), rank=sel(pool.seq),
            active=active,
            order_free=gate_order_free(cfg, be,
                                       planned.get("order_free")))

        # 4. validate
        forwarding = forwarding_applies(be, wl) and cfg.mode == Mode.NORMAL
        fwd = None
        inc = None
        forced = forced_sentinel_mask(batch) if cfg.ycsb_abort_mode else None
        if cfg.mode == Mode.NOCC:
            nocc = get_backend("NOCC")
            verdict, cc_state = nocc.validate(cfg, state.cc_state, batch, None)
        elif forwarding:
            # single-pass forwarding executor (ops/forward): everything
            # commits in rank order; the sort IS the validation.  Forced
            # sentinel txns leave the batch before dependency resolution
            # so their (never-applied) writes are invisible to readers.
            fbatch = batch if forced is None else dataclasses.replace(
                batch, active=batch.active & ~forced)
            if cfg.device_parts > 1:
                # multi-chip: plans are built per-shard inside
                # wl.execute_mc, which also decides the capacity-
                # overflow defers shard-locally (O(N/D)) and returns the
                # replicated mask — the verdict is built after execution
                # (`mc_defer_verdict`; forwarding implies Mode.NORMAL,
                # so the execute below always runs)
                verdict = None
                mc_batch = fbatch
            else:
                verdict, fwd = forward_verdict(fbatch)
                mc_batch = None
            cc_state = state.cc_state
        else:
            inc = build_conflict_incidence(cfg, be, batch,
                                           batch.order_free)
            if be.alg == CCAlg.DGCC:
                # DGCC takes the stats dict (repair-engine contract):
                # its wave/fallback/edge counters come from inside the
                # wave assignment, where the lane graph is in hand
                verdict, cc_state = be.validate(cfg, state.cc_state,
                                                batch, inc, stats=stats)
            else:
                verdict, cc_state = be.validate(cfg, state.cc_state,
                                                batch, inc)
            if cfg.audit_mutate:
                # seeded edge-derivation fault (the audit plane's
                # anti-inert knob): flipped losers execute and ack like
                # any commit — a real violation the certifier must catch
                from deneva_tpu.cc import audit_mutate_verdict
                verdict = audit_mutate_verdict(cfg, batch, inc, verdict,
                                               state.epoch)
        if cfg.metrics:
            # metrics bus (runtime/metricsbus.py): accumulate the
            # per-partition observed-conflict density off the incidence
            # views (the sweep already materialized them; forwarding
            # backends pay two bucket scatter-adds).  Multi-chip is
            # pinned OUT by config.validate (sharded tables have no
            # single bucket space to fold) — a validated error, not a
            # silent skip, so an armed knob can never quietly no-op.
            owner = planned.get("owner",
                                batch.keys % jnp.int32(max(cfg.part_cnt,
                                                           1)))
            stats["conflict_density"] = stats["conflict_density"] + \
                conflict_density(cfg, batch, owner, inc).astype(jnp.uint32)
        # defer budget (defer_rounds_max, WAIT_DIE-style wait timeout): a
        # txn deferred past the budget force-restarts with fresh ts +
        # backoff — the liveness backstop for waits that never resolve
        # on their own (e.g. a MAAT cycle longer than 2^closure_rounds
        # evading conviction).  Deterministic backends are exempt: their
        # defers are part of the replicated decision and resolve by
        # construction (the committed prefix always advances).
        if not be.chained and cfg.defer_rounds_max > 0:
            stuck = verdict.defer & active \
                & (sel(pool.defer_cnt) >= jnp.int32(cfg.defer_rounds_max))
            verdict = dataclasses.replace(
                verdict, abort=verdict.abort | stuck,
                defer=verdict.defer & ~stuck)
        def finalize(verdict, forced):
            # a forced txn completes-as-aborted only when the CC would
            # not retry it anyway (CC aborts/defers follow their normal
            # path); released slots are real commits + forced completions
            if forced is None:
                return None, verdict.commit, verdict.commit
            forced = forced & ~(verdict.abort | verdict.defer)
            return (forced, verdict.commit & ~forced,
                    verdict.commit | forced)

        if verdict is not None:
            forced, exec_commit, release = finalize(verdict, forced)

        # 5. execute committed txns (the multi-chip forwarding path
        # produces its verdict here, from the capacity defer mask)
        db = state.db
        if cfg.mode in (Mode.NORMAL, Mode.NOCC):
            if forwarding:
                if cfg.device_parts > 1:
                    db, mc_dfr = wl.execute_mc(db, mc_batch, stats)
                    verdict = mc_defer_verdict(fbatch, mc_dfr)
                    forced, exec_commit, release = finalize(verdict,
                                                            forced)
                else:
                    # commit set baked into the plan (fbatch.active);
                    # mask=None is asserted by the executor so the two
                    # cannot diverge
                    db = wl.execute(db, queries, None, verdict.order,
                                    stats, fwd_rank=fwd)
            elif cfg.device_parts > 1:
                # generic partition-parallel execution (workloads/mc):
                # replicated verdict, owner-major sharded tables, the
                # workload's own execute body per chip under shard_map
                from deneva_tpu.workloads.mc import mc_execute
                db = mc_execute(cfg, wl, db, queries, exec_commit,
                                verdict.order, verdict.level, stats,
                                chained=be.chained and cfg.mode == Mode.NORMAL,
                                level_exec=be.alg != CCAlg.DGCC,
                                n_levels=cfg.dgcc_levels
                                if be.alg == CCAlg.DGCC else None)
            elif be.chained and cfg.mode == Mode.NORMAL:
                db, stats = _run_levels(cfg, wl, db, queries, exec_commit,
                                        verdict, stats,
                                        level_exec=be.alg != CCAlg.DGCC)
            else:
                db = wl.execute(db, queries, exec_commit, verdict.order,
                                stats)
        # Mode.SIMPLE / QRY_ONLY: ack without touching tables
        # (reference SIMPLE_MODE / QRY_ONLY_MODE, config.h:276-281)

        srounds = None
        # 5b. transaction repair (engine/repair.py, default off): the
        # losers of the sweep re-execute as chained sub-rounds against
        # the post-winner state inside this same jitted step; salvaged
        # txns move abort -> commit (and release their slot like any
        # commit) before the pool update and the counters below ever
        # see them.  Gated exactly like the validate path it extends:
        # sweep backend, NORMAL mode (multi-chip is a config.validate
        # error, never a silent skip here).
        if cfg.repair and cfg.mode == Mode.NORMAL and not forwarding \
                and be.repair_rule is not None:
            from deneva_tpu.engine.repair import run_repair
            # ts_base: the pool's reserved restamp space — the exact
            # stamp authority pool.update uses for abort restamps, so
            # repaired stamps sit strictly above every committed
            # watermark and every stamp in this epoch
            db, cc_state, verdict, salvaged, srounds = run_repair(
                cfg, wl, be, db, queries, batch, inc, verdict, cc_state,
                stats, exec_commit, forced,
                ts_base=pool.next_seq - jnp.int32(self.pool.b))
            exec_commit = exec_commit | salvaged
            release = release | salvaged

        # 5c. isolation audit (cc/base.audit_observe, default off): an
        # OBSERVATION of the final committed set — never an input to any
        # verdict or table write, so armed-vs-off row state is
        # bit-identical (tested).  The in-process engine keeps the stamp
        # tables + device counters; the sidecar export is the cluster
        # runtime's job (runtime/audit.py).
        # (multi-chip is a config.validate error, never a silent skip)
        if cfg.audit and cfg.mode == Mode.NORMAL:
            from deneva_tpu.cc import AUDIT_KEY, audit_observe
            order_vis = forwarding
            if forwarding:
                lvl = jnp.zeros_like(verdict.level)
            elif be.chained:
                lvl = verdict.level
            else:
                lvl = srounds if srounds is not None \
                    else jnp.zeros_like(verdict.level)
            aud2, _e, _bk, cnt, drop, _vd, _rd = audit_observe(
                cfg, batch, exec_commit & active, verdict.order, lvl,
                order_vis, db[AUDIT_KEY], state.epoch)
            db = dict(db)
            db[AUDIT_KEY] = aud2
            stats["audit_edge_cnt"] += cnt.astype(jnp.uint32)
            stats["audit_drop_cnt"] += drop.astype(jnp.uint32)
            if not forwarding and not be.chained:
                # witness density (the controller's certificate-pressure
                # signal): a level-0 sweep backend claims a conflict-
                # free committed set, so any edge between two level-0
                # commits is a claim violation — chained waves and
                # forwarded ranks carry legitimate edges and skip this
                from deneva_tpu.cc.depgraph import witness_count
                stats["audit_wit_cnt"] += witness_count(
                    _e, lvl).astype(jnp.uint32)

        # 6. update pool + counters (forced txns release like commits)
        pre_abort_cnt = sel(pool.abort_cnt)   # pre-update: 0 = never aborted
        pool = self.pool.update(pool, slots, active, release,
                                verdict.abort, state.epoch,
                                be.fresh_ts_on_restart)
        ncommit = (exec_commit & active).sum(dtype=jnp.uint32)
        stats["total_txn_commit_cnt"] += ncommit
        aborts = verdict.abort if forced is None else verdict.abort | forced
        stats["total_txn_abort_cnt"] += (aborts & active).sum(dtype=jnp.uint32)
        # exact unique-txn aborts (reference stats.h:60-61 counts each
        # txn's FIRST abort): the slot's abort_cnt — reset on admission,
        # bumped per abort — is zero exactly at a txn's first abort
        stats["unique_txn_abort_cnt"] += (
            aborts & active & (pre_abort_cnt == 0)).sum(dtype=jnp.uint32)
        count_by_type(stats, wl, queries, exec_commit & active,
                      aborts & active)
        stats["defer_cnt"] += (verdict.defer & active).sum(dtype=jnp.uint32)
        # histograms as one-hot reductions: a 64-bucket scatter-add over
        # the batch serializes on bucket contention on TPU (~4.5 ms at
        # 64k lanes on v5e); the dense compare-and-sum is ~free.
        # latency_hist is PER TYPE (static unrolled — n_types is 2-8):
        # the reference's per-txn-kind StatsArr latency families
        committed = exec_commit & active
        lat = jnp.clip(state.epoch - sel(pool.entry_epoch),
                       0, LAT_BUCKETS - 1)
        onehot = (lat[:, None] == jnp.arange(LAT_BUCKETS, dtype=jnp.int32)) \
            & committed[:, None]
        ttype = wl.txn_type_of(queries) if len(
            getattr(wl, "txn_type_names", ("txn",))) > 1 else None
        rows = []
        for t in range(stats["latency_hist"].shape[0]):
            m = onehot if ttype is None \
                else onehot & (ttype == t)[:, None]
            rows.append(m.sum(axis=0, dtype=jnp.uint32))
        stats["latency_hist"] = stats["latency_hist"] + jnp.stack(rows)
        # per-txn restart/wait decomposition at commit (TxnStats
        # analogue, system/txn.h:72-114): pre-update counters are the
        # txn's whole-life totals since its slot (re)admission
        rb = jnp.arange(RETRY_BUCKETS, dtype=jnp.int32)
        retries = jnp.clip(pre_abort_cnt, 0, RETRY_BUCKETS - 1)
        waits = jnp.clip(sel(pool.defer_cnt), 0, RETRY_BUCKETS - 1)
        stats["retry_hist"] = stats["retry_hist"] + (
            (retries[:, None] == rb) & committed[:, None]).sum(
            axis=0, dtype=jnp.uint32)
        stats["wait_hist"] = stats["wait_hist"] + (
            (waits[:, None] == rb) & committed[:, None]).sum(
            axis=0, dtype=jnp.uint32)

        return EngineState(db=db, cc_state=cc_state, pool=pool, rng=rng,
                           epoch=state.epoch + 1, stats=stats)

    # ------------------------------------------------------------------
    def _routed_step(self, state: EngineState, knobs) -> EngineState:
        """One epoch under the contention-adaptive router (PR 16
        tentpole; only reachable through ``step(state, knobs)`` with a
        non-None ``RouterKnobs``, which config.validate arms only under
        ``ctrl`` — metrics on, Mode.NORMAL, single device, candidate
        cc_alg, no forced-abort/audit-mutate/escrow special paths).

        Sections 1-3 (admit/select/plan) and section 6 (pool update +
        counters) are the static step's, shared OUTSIDE the routed
        switch.  Section 4-5 becomes a ``lax.switch`` over
        ``candidates(cfg)``: one branch per uniform candidate backend —
        each replicating the static step's exact
        validate/execute/repair/audit dataflow for that backend — plus
        a mixed-assignment branch (always last) that validates each
        backend's sub-batch against the shared (coarsened) incidence
        and defers the cross-group conflict surface symmetrically
        (`cc/router.cross_group_defer`).  Under ``ctrl_dgcc`` a fourth
        uniform branch runs the DGCC wavefront (index 3, the
        controller's HOT class), and the mixed branch moves to index 4;
        unarmed, the compiled 4-way program is bit-identical to the
        PR 16 plane.  With ``static_knobs(cfg)``
        every epoch takes the uniform branch of ``cfg.cc_alg`` with
        gshift=0 / cap=repair_rounds / cadence=cfg.audit_cadence, and
        the outputs are value-identical to the unrouted step (pinned by
        tests/test_ctrl.py).

        Branch contract: each returns ``(db, stats, exec_commit,
        release, abort, defer)`` with identical pytree structure (every
        stats key pre-exists in `init_device_stats`), so the switch is
        shape-stable and knob VALUES never recompile.
        """
        from deneva_tpu.cc import Verdict
        from deneva_tpu.cc.router import (candidates, coarsen_keys,
                                          cross_group_defer, txn_backend)
        cfg, wl = self.cfg, self.workload
        rng, gen_key = jax.random.split(state.rng)
        stats = dict(state.stats)

        # 1. admit fresh queries (identical to the static step)
        newq = wl.generate(gen_key, self.pool.g)
        pool, admitted = self.pool.refill(state.pool, newq, state.epoch)
        stats["generated_cnt"] += jnp.uint32(self.pool.g)
        stats["admitted_cnt"] += admitted.astype(jnp.uint32)

        # 2. select epoch batch
        slots, active, queries = self.pool.select(pool, state.epoch)
        sel = (lambda v: v) if self.pool.full_pool \
            else (lambda v: jnp.take(v, slots))

        # 3. plan RW-sets (exact keys; the router only ever coarsens
        # the conflict-derivation VIEW below)
        planned = wl.plan(state.db, queries)
        batch = AccessBatch(
            table_ids=planned["table_ids"], keys=planned["keys"],
            is_read=planned["is_read"], is_write=planned["is_write"],
            valid=planned["valid"],
            ts=sel(pool.ts), rank=sel(pool.seq),
            active=active,
            order_free=gate_order_free(cfg, self.backend,
                                       planned.get("order_free")))

        # router views: owner partitions anchor both the per-partition
        # knob lookups and the density fold (same fallback hash as the
        # static metrics block); cbatch carries the per-partition
        # coarsened conflict keys (gshift=0 -> bit-identical to batch)
        owner = planned.get("owner",
                            batch.keys % jnp.int32(max(cfg.part_cnt, 1)))
        cbatch = coarsen_keys(batch, owner, knobs.gshift)
        group = txn_backend(knobs, owner)
        # config-dependent candidate list: without ctrl_dgcc this is
        # exactly the 3-class tuple, so the compiled 4-way switch (and
        # every [ctrl] replay) is bit-identical to the pre-DGCC plane
        backends = [get_backend(a) for a in candidates(cfg)]

        def density_into(st, inc):
            st["conflict_density"] = st["conflict_density"] + \
                conflict_density(cfg, cbatch, owner, inc).astype(jnp.uint32)

        def audit_into(db, st, exec_commit, order, lvl, order_vis,
                       claim_zero=False):
            # static step's 5c with the cadence knob as a traced operand
            if not cfg.audit:
                return db, st
            from deneva_tpu.cc import AUDIT_KEY, audit_observe
            from deneva_tpu.cc.depgraph import witness_count
            aud2, _e, _bk, cnt, drop, _vd, _rd = audit_observe(
                cfg, batch, exec_commit & active, order, lvl, order_vis,
                db[AUDIT_KEY], state.epoch, cadence=knobs.audit_cadence)
            db = dict(db)
            db[AUDIT_KEY] = aud2
            st["audit_edge_cnt"] += cnt.astype(jnp.uint32)
            st["audit_drop_cnt"] += drop.astype(jnp.uint32)
            if claim_zero:
                # sweep branches claim a conflict-free level-0 commit
                # set: any level-0/level-0 edge is a claim witness
                # (repair-salvaged endpoints sit at lvl >= 1, excluded)
                st["audit_wit_cnt"] += witness_count(
                    _e, lvl).astype(jnp.uint32)
            return db, st

        def budget_merge(verdict, eligible=None):
            # static step's defer budget (liveness backstop); `eligible`
            # narrows it in the mixed branch
            if cfg.defer_rounds_max <= 0:
                return verdict
            stuck = verdict.defer & active \
                & (sel(pool.defer_cnt) >= jnp.int32(cfg.defer_rounds_max))
            if eligible is not None:
                stuck = stuck & eligible
            return dataclasses.replace(
                verdict, abort=verdict.abort | stuck,
                defer=verdict.defer & ~stuck)

        def sweep_branch(be_s):
            # uniform NO_WAIT / OCC epoch — the static step's sweep path
            # over the coarsened conflict view
            def body(_):
                st = dict(stats)
                inc = build_conflict_incidence(cfg, be_s, cbatch,
                                               cbatch.order_free)
                verdict, _cc = be_s.validate(cfg, state.cc_state, cbatch,
                                             inc)
                density_into(st, inc)
                verdict = budget_merge(verdict)
                exec_commit = verdict.commit
                db = wl.execute(state.db, queries, exec_commit,
                                verdict.order, st)
                srounds = None
                if cfg.repair and be_s.repair_rule is not None:
                    from deneva_tpu.engine.repair import run_repair
                    db, _cc, verdict, salvaged, srounds = run_repair(
                        cfg, wl, be_s, db, queries, cbatch, inc, verdict,
                        state.cc_state, st, exec_commit, None,
                        ts_base=pool.next_seq - jnp.int32(self.pool.b),
                        rounds_cap=knobs.repair_cap)
                    exec_commit = exec_commit | salvaged
                lvl = srounds if srounds is not None \
                    else jnp.zeros_like(verdict.level)
                db, st = audit_into(db, st, exec_commit, verdict.order,
                                    lvl, False, claim_zero=True)
                return (db, st, exec_commit, exec_commit, verdict.abort,
                        verdict.defer)
            return body

        def tb_branch():
            # uniform TPU_BATCH epoch: exactly the static step's path
            # for this backend — forwarding executor when the workload
            # is blind-write (density via the scatter-add path, inc
            # never built), chained level waves otherwise
            tb = backends[2]
            if forwarding_applies(tb, wl):
                def body(_):
                    st = dict(stats)
                    verdict, fwd = forward_verdict(batch)
                    density_into(st, None)
                    db = wl.execute(state.db, queries, None,
                                    verdict.order, st, fwd_rank=fwd)
                    db, st = audit_into(db, st, verdict.commit,
                                        verdict.order,
                                        jnp.zeros_like(verdict.level),
                                        True)
                    return (db, st, verdict.commit, verdict.commit,
                            verdict.abort, verdict.defer)
            else:
                def body(_):
                    st = dict(stats)
                    inc = build_conflict_incidence(cfg, tb, cbatch,
                                                   cbatch.order_free)
                    verdict, _cc = tb.validate(cfg, state.cc_state,
                                               cbatch, inc)
                    density_into(st, inc)
                    db, st = _run_levels(cfg, wl, state.db, queries,
                                         verdict.commit, verdict, st)
                    db, st = audit_into(db, st, verdict.commit,
                                        verdict.order, verdict.level,
                                        False)
                    return (db, st, verdict.commit, verdict.commit,
                            verdict.abort, verdict.defer)
            return body

        def dgcc_branch():
            # uniform DGCC epoch (the controller's HOT class under
            # ctrl_dgcc): the static step's wavefront path over the
            # coarsened conflict view — coarsening composes soundly
            # with the exact-key lane graph (merged keys only ADD
            # dependencies, deepening waves but never hiding one) while
            # execution/audit keep exact keys as everywhere.  No
            # incidence (density via the scatter-add path), no repair
            # (DGCC never aborts), no defer budget (chained exemption:
            # its defers are the bounded cyclic fallback).
            dg = backends[3]

            def body(_):
                st = dict(stats)
                verdict, _cc = dg.validate(cfg, state.cc_state, cbatch,
                                           None, stats=st)
                density_into(st, None)
                db, st = _run_levels(cfg, wl, state.db, queries,
                                     verdict.commit, verdict, st,
                                     level_exec=False)
                db, st = audit_into(db, st, verdict.commit,
                                    verdict.order, verdict.level, False)
                return (db, st, verdict.commit, verdict.commit,
                        verdict.abort, verdict.defer)
            return body

        def mixed_branch(_):
            # mixed assignment: one shared coarse incidence; each
            # backend validates its own sub-batch with the cross-group
            # conflict surface deferred symmetrically, so the merged
            # committed set needs no cross-group ordering.  Sweep
            # winners commit at level 0 beside TPU_BATCH's level-0 wave
            # (the union stays write-conflict-free: each group's wave
            # is conflict-free by its own verdict invariant and every
            # cross-group conflicting txn was deferred).  Repair is
            # skipped in mixed epochs (its frontier algebra is
            # per-backend; the next uniform epoch resumes it).
            st = dict(stats)
            inc = build_conflict_incidence(cfg, backends[0], cbatch,
                                           cbatch.order_free)
            crossdef = cross_group_defer(inc, cbatch, group,
                                         n_groups=len(backends))
            commit = jnp.zeros_like(active)
            abort = jnp.zeros_like(active)
            defer = crossdef
            level = jnp.zeros_like(batch.rank)
            for g, be_g in enumerate(backends):
                m = active & (group == g) & ~crossdef
                sb = dataclasses.replace(cbatch, active=m)
                if be_g.alg == CCAlg.DGCC:
                    # DGCC ignores the incidence (exact-key lane graph
                    # over its masked sub-batch) but keeps the [dgcc]
                    # counters flowing in mixed epochs too
                    v_g, _cc = be_g.validate(cfg, state.cc_state, sb,
                                             None, stats=st)
                else:
                    v_g, _cc = be_g.validate(cfg, state.cc_state, sb,
                                             inc)
                commit = commit | (v_g.commit & m)
                abort = abort | (v_g.abort & m)
                defer = defer | (v_g.defer & m)
                if be_g.chained:
                    level = jnp.where(m, v_g.level, level)
            density_into(st, inc)
            # budget covers sweep-group txns and cross-group defers;
            # chained groups' internal defers resolve by construction
            # (TPU_BATCH) or are the bounded cyclic fallback (DGCC) —
            # the static step's chained exemption, per group
            nonchained = functools.reduce(
                jnp.logical_or,
                [group == g for g, be_g in enumerate(backends)
                 if not be_g.chained])
            verdict = budget_merge(
                Verdict(commit=commit, abort=abort, defer=defer,
                        order=batch.rank, level=level),
                eligible=nonchained | crossdef)
            # the union executes through one level chain: sweep winners
            # at level 0 beside the chained groups' waves (cross-group
            # conflicts all deferred).  With DGCC armed the executor
            # takes the order-tournament path — for the conflict-free
            # non-DGCC waves it degenerates to the fast path's result,
            # so the static python flag keeps PR 16 programs untouched
            db, st = _run_levels(cfg, wl, state.db, queries,
                                 verdict.commit, verdict, st,
                                 level_exec=not cfg.ctrl_dgcc)
            db, st = audit_into(db, st, verdict.commit, verdict.order,
                                verdict.level, False)
            return (db, st, verdict.commit, verdict.commit,
                    verdict.abort, verdict.defer)

        # 4+5. routed validate/execute/repair/audit: uniform epochs take
        # their backend's exact static branch; disagreement routes to
        # the mixed branch (always last)
        branches = [sweep_branch(backends[0]), sweep_branch(backends[1]),
                    tb_branch()]
        if len(backends) > 3:
            branches.append(dgcc_branch())
        branches.append(mixed_branch)
        uniform = (knobs.assign == knobs.assign[0]).all()
        idx = jnp.where(uniform, knobs.assign[0],
                        jnp.int32(len(backends)))
        db, stats, exec_commit, release, aborts, defers = jax.lax.switch(
            idx, branches, None)

        # 6. update pool + counters (identical to the static step with
        # forced=None; every candidate restamps aborts with fresh ts)
        pre_abort_cnt = sel(pool.abort_cnt)
        pool = self.pool.update(pool, slots, active, release, aborts,
                                state.epoch, True)
        ncommit = (exec_commit & active).sum(dtype=jnp.uint32)
        stats["total_txn_commit_cnt"] += ncommit
        stats["total_txn_abort_cnt"] += (aborts & active).sum(
            dtype=jnp.uint32)
        stats["unique_txn_abort_cnt"] += (
            aborts & active & (pre_abort_cnt == 0)).sum(dtype=jnp.uint32)
        count_by_type(stats, wl, queries, exec_commit & active,
                      aborts & active)
        stats["defer_cnt"] += (defers & active).sum(dtype=jnp.uint32)
        committed = exec_commit & active
        lat = jnp.clip(state.epoch - sel(pool.entry_epoch),
                       0, LAT_BUCKETS - 1)
        onehot = (lat[:, None] == jnp.arange(LAT_BUCKETS, dtype=jnp.int32)) \
            & committed[:, None]
        ttype = wl.txn_type_of(queries) if len(
            getattr(wl, "txn_type_names", ("txn",))) > 1 else None
        rows = []
        for t in range(stats["latency_hist"].shape[0]):
            m = onehot if ttype is None \
                else onehot & (ttype == t)[:, None]
            rows.append(m.sum(axis=0, dtype=jnp.uint32))
        stats["latency_hist"] = stats["latency_hist"] + jnp.stack(rows)
        rb = jnp.arange(RETRY_BUCKETS, dtype=jnp.int32)
        retries = jnp.clip(pre_abort_cnt, 0, RETRY_BUCKETS - 1)
        waits = jnp.clip(sel(pool.defer_cnt), 0, RETRY_BUCKETS - 1)
        stats["retry_hist"] = stats["retry_hist"] + (
            (retries[:, None] == rb) & committed[:, None]).sum(
            axis=0, dtype=jnp.uint32)
        stats["wait_hist"] = stats["wait_hist"] + (
            (waits[:, None] == rb) & committed[:, None]).sum(
            axis=0, dtype=jnp.uint32)

        return EngineState(db=db, cc_state=state.cc_state, pool=pool,
                           rng=rng, epoch=state.epoch + 1, stats=stats)

    # ------------------------------------------------------------------
    @functools.cached_property
    def jit_step(self):
        return jax.jit(self.step, donate_argnums=0)

    @functools.cached_property
    def jit_run(self):
        """scan ``n`` epochs on device; n is static per compile."""

        @functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
        def run(state: EngineState, n: int) -> EngineState:
            return jax.lax.scan(lambda s, _: (self.step(s), None), state,
                                None, length=n)[0]
        return run

    @functools.cached_property
    def jit_run_ctrl(self):
        """Routed scan: ``n`` epochs under ONE knob decision (the
        controller decides at chunk boundaries; knobs are traced
        operands, so re-arming with new VALUES reuses the compile)."""

        @functools.partial(jax.jit, static_argnums=2, donate_argnums=0)
        def run(state: EngineState, knobs, n: int) -> EngineState:
            return jax.lax.scan(
                lambda s, _: (self.step(s, knobs), None), state,
                None, length=n)[0]
        return run
