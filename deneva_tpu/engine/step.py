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
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from deneva_tpu.cc import build_conflict_incidence, get_backend
from deneva_tpu.config import CCAlg, Config, Mode
from deneva_tpu.engine.epoch import (access_batch, count_verdict,
                                     density_into, epoch_core,
                                     observe_audit, plan_owner, run_levels)
from deneva_tpu.engine.pool import PoolState, TxnPool
from deneva_tpu.ops import forwarding_applies
from deneva_tpu.workloads.base import (APPEND_COUNTERS, EXEC_COUNTERS,
                                       LOCK_COUNTERS, MVCC_COUNTERS,
                                       ROW_GROUP_COUNTER)

LAT_BUCKETS = 64
RETRY_BUCKETS = 8      # per-txn restart/wait counts at commit (clipped)


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


def init_device_stats(n_txn_types: int = 1, n_parts: int = 1,
                      level_passes: bool = False,
                      append_lanes: bool = False,
                      recon_defers: bool = False,
                      mc_defer_passes: bool = False,
                      mvcc_counters: bool = False,
                      lock_counters: bool = False,
                      row_groups: bool = False) -> dict:
    """``level_passes``: add ``level_pass_cnt`` and ``narrow_pass_cnt``,
    which `engine/epoch.run_levels` counts where it finds them (its
    passes, and those run under the batch's width) — asked for by
    the server of a chained backend alone, so every other program's
    stats pytree (and with it its compiled text) is what it was.
    ``append_lanes``: likewise `workloads/base.APPEND_COUNTERS`, which
    `storage/table.DeviceTable.append` counts — asked for by the server
    of a workload with ring tables.  ``recon_defers``: likewise
    ``recon_defer_cnt``, the lanes `engine/epoch.epoch_core` defers on
    stale reconnaissance — asked for by the server of a chained backend
    whose workload marks reconnaissance (PPS).  ``mc_defer_passes``:
    likewise ``mc_defer_pass_cnt``, the shard-epochs in which
    `workloads/ycsb.YCSBWorkload.execute_mc` RAN its capacity-defer pass
    (a slice with an owner's block over `ops.mc_pair_cap`) — asked for
    by the server of a forwarding backend on a mesh.  ``mvcc_counters``:
    likewise `workloads/base.MVCC_COUNTERS`, what MVCC decides and the
    lanes its version ring's row write is handed — asked for by an MVCC
    server on one device.  ``lock_counters``: likewise
    `workloads/base.LOCK_COUNTERS`, the lock family's deaths, waits and
    sweep-budget leftovers — asked for by a NO_WAIT / WAIT_DIE server on
    one device.  ``row_groups``: likewise
    `workloads/base.ROW_GROUP_COUNTER`, the tile groups the row write's
    kernel writes back — asked for by the server of a workload that
    writes full rows through `ops.scatter.scatter_winner_rows` (YCSB
    under ``sim_full_row``)."""
    z = lambda: jnp.zeros((), jnp.uint32)  # noqa: E731
    return {
        **({"level_pass_cnt": z(), "narrow_pass_cnt": z()}
           if level_passes else {}),
        **({"recon_defer_cnt": z()} if recon_defers else {}),
        **({"mc_defer_pass_cnt": z()} if mc_defer_passes else {}),
        **({k: z() for k in APPEND_COUNTERS} if append_lanes else {}),
        **({k: z() for k in MVCC_COUNTERS} if mvcc_counters else {}),
        **({k: z() for k in LOCK_COUNTERS} if lock_counters else {}),
        **({ROW_GROUP_COUNTER: z()} if row_groups else {}),
        # per-partition observed-conflict density (cc/base.
        # conflict_density; the metrics bus's contention signal and the
        # contention-adaptive router's input).  Always present so the
        # stats pytree shape depends only on the config; stays zero
        # unless metrics is armed.
        "conflict_density": jnp.zeros((max(n_parts, 1),), jnp.uint32),
        "generated_cnt": z(), "admitted_cnt": z(),
        "total_txn_commit_cnt": z(), "total_txn_abort_cnt": z(),
        "unique_txn_abort_cnt": z(),
        "defer_cnt": z(),
        # the executors' counters (workloads/base.EXEC_COUNTERS)
        **{k: z() for k in EXEC_COUNTERS},
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


def _hist(values, n: int, mask):
    """uint32[n]: the ``mask`` lanes by clipped value.  A one-hot
    reduction: an n-bucket scatter-add over the batch serializes on
    bucket contention on TPU (~4.5 ms at 64k lanes on v5e); the dense
    compare-and-sum is ~free."""
    v = jnp.clip(values, 0, n - 1)
    return ((v[:, None] == jnp.arange(n, dtype=jnp.int32))
            & mask[:, None]).sum(axis=0, dtype=jnp.uint32)


class EpochIn(NamedTuple):
    """An opened epoch: what `Engine._open` hands the middle."""
    stats: dict
    queries: Any
    planned: dict
    batch: Any              # cc.AccessBatch
    active: jax.Array
    budget: Callable        # the pool's defer budget: verdict -> verdict
    ts_base: jax.Array      # the pool's restamp space (repair)
    rng: jax.Array          # the rest is the shell's own, for `_close`
    pool: PoolState
    slots: jax.Array
    sel: Callable           # pool column -> the selected batch's values


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
        """One epoch.  ``knobs`` (a ``RouterKnobs``; config.validate arms
        them only under ``ctrl``) selects the contention-adaptive
        router's middle; None — the default, and the only path when
        ctrl is off — runs `epoch_core`."""
        x = self._open(state)
        if knobs is not None:
            out = self._routed_middle(knobs, state, x)
        else:
            out = self._middle(state, x)
        return self._close(state, x, *out)

    def _open(self, state: EngineState) -> EpochIn:
        """Sections 1-3 of an epoch: what the pool does before the
        middle."""
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

        # 3. plan RW-sets
        with jax.named_scope("ep.plan"):
            planned = wl.plan(state.db, queries)
            batch = access_batch(cfg, be, planned, ts=sel(pool.ts),
                                 rank=sel(pool.seq), active=active)

        def budget(verdict, eligible=None):
            # defer budget (defer_rounds_max, WAIT_DIE-style wait
            # timeout): a txn deferred past the budget force-restarts
            # with fresh ts + backoff — the liveness backstop for waits
            # that never resolve on their own (e.g. a MAAT cycle longer
            # than 2^closure_rounds evading conviction).  ``eligible``
            # narrows it (the router's mixed branch).
            if cfg.defer_rounds_max <= 0:
                return verdict
            stuck = verdict.defer & active \
                & (sel(pool.defer_cnt) >= jnp.int32(cfg.defer_rounds_max))
            if eligible is not None:
                stuck = stuck & eligible
            return dataclasses.replace(
                verdict, abort=verdict.abort | stuck,
                defer=verdict.defer & ~stuck)

        # ts_base: the pool's reserved restamp space — the exact stamp
        # authority pool.update uses for abort restamps, so repaired
        # stamps sit strictly above every committed watermark and every
        # stamp in this epoch
        return EpochIn(
            stats=stats, queries=queries, planned=planned, batch=batch,
            active=active, budget=budget,
            ts_base=pool.next_seq - jnp.int32(self.pool.b),
            rng=rng, pool=pool, slots=slots, sel=sel)

    def _close(self, state: EngineState, x: EpochIn, db, cc_state, stats,
               exec_commit, release, abort, defer, forced) -> EngineState:
        """Section 6 of an epoch: the pool update and the counters, from
        what the middle decided."""
        wl, be = self.workload, self.backend
        pool, slots, sel, active, queries = \
            x.pool, x.slots, x.sel, x.active, x.queries
        # 6. update pool + counters (forced txns release like commits)
        with jax.named_scope("ep.stats"):
            pre_abort_cnt = sel(pool.abort_cnt)  # pre-update: 0 = never aborted
            pool = self.pool.update(pool, slots, active, release, abort,
                                    state.epoch, be.fresh_ts_on_restart)
            committed = exec_commit & active
            aborts = (abort if forced is None else abort | forced) & active
            count_verdict(stats, wl, queries, committed, aborts,
                          defer & active)
            # exact unique-txn aborts (reference stats.h:60-61 counts each
            # txn's FIRST abort): the slot's abort_cnt — reset on admission,
            # bumped per abort — is zero exactly at a txn's first abort
            stats["unique_txn_abort_cnt"] += (
                aborts & (pre_abort_cnt == 0)).sum(dtype=jnp.uint32)
            # latency_hist is PER TYPE (static unrolled — n_types is 2-8):
            # the reference's per-txn-kind StatsArr latency families
            lat = state.epoch - sel(pool.entry_epoch)
            ttype = wl.txn_type_of(queries) if len(
                getattr(wl, "txn_type_names", ("txn",))) > 1 else None
            stats["latency_hist"] = stats["latency_hist"] + jnp.stack([
                _hist(lat, LAT_BUCKETS, committed if ttype is None
                      else committed & (ttype == t))
                for t in range(stats["latency_hist"].shape[0])])
            # per-txn restart/wait decomposition at commit (TxnStats
            # analogue, system/txn.h:72-114): pre-update counters are the
            # txn's whole-life totals since its slot (re)admission
            stats["retry_hist"] = stats["retry_hist"] + _hist(
                pre_abort_cnt, RETRY_BUCKETS, committed)
            stats["wait_hist"] = stats["wait_hist"] + _hist(
                sel(pool.defer_cnt), RETRY_BUCKETS, committed)

        return EngineState(db=db, cc_state=cc_state, pool=pool, rng=x.rng,
                           epoch=state.epoch + 1, stats=stats)

    # ------------------------------------------------------------------
    def _middle(self, state: EngineState, x: EpochIn):
        """`epoch_core` and its observations.  The in-process engine
        keeps the audit stamp tables + device counters; the sidecar
        export is the cluster runtime's job (runtime/audit.py).  The
        defer budget exempts deterministic backends: their defers are
        part of the replicated decision and resolve by construction
        (the committed prefix always advances)."""
        cfg, wl, be = self.cfg, self.workload, self.backend
        out = epoch_core(cfg, wl, be, state.db, state.cc_state, x.stats,
                         x.queries, x.batch, epoch=state.epoch,
                         budget=None if be.chained else x.budget,
                         ts_base=x.ts_base)
        db, stats = out.db, out.stats
        if cfg.metrics:
            # (multi-chip is pinned OUT by config.validate: sharded
            # tables have no single bucket space to fold — a validated
            # error, so an armed knob can never quietly no-op)
            density_into(cfg, stats, x.batch,
                         plan_owner(cfg, x.planned, x.batch), out.inc)
        if cfg.audit and cfg.mode == Mode.NORMAL:
            db, _planes = observe_audit(
                cfg, db, stats, x.batch, out.exec_commit & x.active,
                out.verdict, out.srounds, state.epoch,
                forwarding=forwarding_applies(be, wl), chained=be.chained)
        return (db, out.cc_state, stats, out.exec_commit, out.release,
                out.verdict.abort, out.verdict.defer, out.forced)

    # ------------------------------------------------------------------
    def _routed_middle(self, knobs, state: EngineState, x: EpochIn):
        """The middle of an epoch under the contention-adaptive router
        (PR 16 tentpole; config.validate arms ``ctrl`` only with metrics
        on, Mode.NORMAL, single device, candidate cc_alg, no
        forced-abort/audit-mutate/escrow special paths).

        A ``lax.switch`` over ``candidates(cfg)``: one branch per
        uniform candidate backend plus a mixed-assignment branch (always
        last) that validates each backend's sub-batch against the shared
        (coarsened) incidence and defers the cross-group conflict
        surface symmetrically (`cc/router.cross_group_defer`).  The
        chained candidates' branches (TPU_BATCH, and DGCC — the
        controller's HOT class, index 3 under ``ctrl_dgcc``, the mixed
        branch then at 4) ARE `epoch_core`; the sweep branches validate
        on the coarsened view and cap repair by a knob, the mixed branch
        merges verdicts, and both stay written out.  Unarmed, the
        compiled 4-way program is the PR 16 plane's.  With
        ``static_knobs(cfg)`` every epoch takes the uniform branch of
        ``cfg.cc_alg`` with gshift=0 / cap=repair_rounds /
        cadence=cfg.audit_cadence, and the outputs are value-identical
        to the unrouted step (pinned by tests/test_ctrl.py).

        Branch contract: each returns ``(db, stats, exec_commit,
        release, abort, defer)`` with identical pytree structure (every
        stats key pre-exists in `init_device_stats`), so the switch is
        shape-stable and knob VALUES never recompile.
        """
        from deneva_tpu.cc import Verdict
        from deneva_tpu.cc.router import (candidates, coarsen_keys,
                                          cross_group_defer, txn_backend)
        cfg, wl = self.cfg, self.workload
        stats, queries, batch, active = x.stats, x.queries, x.batch, x.active

        # router views: owner partitions anchor both the per-partition
        # knob lookups and the density fold; cbatch carries the
        # per-partition coarsened conflict keys (gshift=0 ->
        # bit-identical to batch; the router only ever coarsens the
        # conflict-derivation VIEW, execution and audit keep exact keys)
        owner = plan_owner(cfg, x.planned, batch)
        cbatch = coarsen_keys(batch, owner, knobs.gshift)
        group = txn_backend(knobs, owner)
        # config-dependent candidate list: without ctrl_dgcc this is
        # exactly the 3-class tuple, so the compiled 4-way switch (and
        # every [ctrl] replay) is bit-identical to the pre-DGCC plane
        backends = [get_backend(a) for a in candidates(cfg)]

        def audit(db, st, exec_commit, verdict, srounds, **vis):
            # the cadence knob rides as a traced operand
            if not cfg.audit:
                return db
            return observe_audit(
                cfg, db, st, batch, exec_commit & active, verdict, srounds,
                state.epoch, cadence=knobs.audit_cadence, **vis)[0]

        def sweep_branch(be_s):
            # uniform NO_WAIT / OCC epoch — the static step's sweep path
            # over the coarsened conflict view
            def body(_):
                st = dict(stats)
                inc = build_conflict_incidence(cfg, be_s, cbatch,
                                               cbatch.order_free)
                verdict, _cc = be_s.validate(cfg, state.cc_state, cbatch,
                                             inc)
                density_into(cfg, st, cbatch, owner, inc)
                verdict = x.budget(verdict)
                exec_commit = verdict.commit
                db = wl.execute(state.db, queries, exec_commit,
                                verdict.order, st)
                srounds = None
                if cfg.repair and be_s.repair_rule is not None:
                    from deneva_tpu.engine.repair import run_repair
                    db, _cc, verdict, salvaged, srounds = run_repair(
                        cfg, wl, be_s, db, queries, cbatch, inc, verdict,
                        state.cc_state, st, exec_commit, None,
                        ts_base=x.ts_base, rounds_cap=knobs.repair_cap)
                    exec_commit = exec_commit | salvaged
                db = audit(db, st, exec_commit, verdict, srounds,
                           forwarding=False, chained=False)
                return (db, st, exec_commit, exec_commit, verdict.abort,
                        verdict.defer)
            return body

        def core_branch(be_c):
            # uniform TPU_BATCH / DGCC epoch: `epoch_core` for this
            # backend — the forwarding executor over exact keys when the
            # workload is blind-write, else the backend's waves over the
            # coarsened view (coarsening composes soundly with DGCC's
            # exact-key lane graph: merged keys only ADD dependencies,
            # deepening waves but never hiding one).  No repair, no
            # defer budget: chained backends never abort, and their
            # defers resolve by construction (TPU_BATCH) or are the
            # bounded cyclic fallback (DGCC).
            fwd = forwarding_applies(be_c, wl)

            def body(_):
                out = epoch_core(cfg, wl, be_c, state.db, state.cc_state,
                                 dict(stats), queries,
                                 batch if fwd else cbatch,
                                 epoch=state.epoch)
                st = out.stats
                density_into(cfg, st, cbatch, owner, out.inc)
                db = audit(out.db, st, out.exec_commit, out.verdict, None,
                           forwarding=fwd, chained=True)
                return (db, st, out.exec_commit, out.release,
                        out.verdict.abort, out.verdict.defer)
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
            density_into(cfg, st, cbatch, owner, inc)
            # budget covers sweep-group txns and cross-group defers;
            # chained groups' internal defers resolve by construction
            # (TPU_BATCH) or are the bounded cyclic fallback (DGCC) —
            # the static step's chained exemption, per group
            nonchained = functools.reduce(
                jnp.logical_or,
                [group == g for g, be_g in enumerate(backends)
                 if not be_g.chained])
            verdict = x.budget(
                Verdict(commit=commit, abort=abort, defer=defer,
                        order=batch.rank, level=level),
                eligible=nonchained | crossdef)
            # the union executes through one level chain: sweep winners
            # at level 0 beside the chained groups' waves (cross-group
            # conflicts all deferred).  With DGCC armed the executor
            # takes the order-tournament path — for the conflict-free
            # non-DGCC waves it degenerates to the fast path's result,
            # so the static python flag keeps PR 16 programs untouched
            db, st = run_levels(cfg, wl, state.db, queries,
                                verdict.commit, verdict, st,
                                level_exec=not cfg.ctrl_dgcc)
            db = audit(db, st, verdict.commit, verdict, None,
                       forwarding=False, chained=True)
            return (db, st, verdict.commit, verdict.commit,
                    verdict.abort, verdict.defer)

        # uniform epochs take their backend's branch; disagreement
        # routes to the mixed branch (always last)
        branches = [sweep_branch(backends[0]), sweep_branch(backends[1])]
        branches += [core_branch(be_c) for be_c in backends[2:]]
        branches.append(mixed_branch)
        uniform = (knobs.assign == knobs.assign[0]).all()
        idx = jnp.where(uniform, knobs.assign[0],
                        jnp.int32(len(backends)))
        db, stats, exec_commit, release, abort, defer = jax.lax.switch(
            idx, branches, None)
        # (forced=None; every candidate keeps no cross-epoch CC state
        # and restamps aborts with fresh ts, as cfg.cc_alg — one of
        # them — tells the shell)
        return (db, state.cc_state, stats, exec_commit, release, abort,
                defer, None)

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
