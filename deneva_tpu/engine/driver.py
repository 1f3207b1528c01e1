"""Run lifecycle: warmup / measure / summary (reference `system/sim_manager.*`).

The reference runs free threads against wall-clock timers (WARMUP_TIMER /
DONE_TIMER, `config.h:346-350`; `SimManager::timeout`).  Here the unit of
progress is a compiled chunk of epochs: the driver scans chunks until the
wall-clock window closes, then diffs device counters across the measured
window and emits the reference-compatible ``[summary]`` line
(`statistics/stats.cpp:1470`; parsed by `scripts/parse_results.py`).

Latency: the engine histograms commit latency in *epochs*; the driver
scales bucket centers by the measured seconds/epoch to report
``client_client_latency`` percentiles like `scripts/latency_stats.py:20`.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from deneva_tpu.config import Config
from deneva_tpu.engine.step import Engine, EngineState
from deneva_tpu.stats import Stats
from deneva_tpu.workloads import get_workload


def _counters(state: EngineState) -> dict:
    host = jax.device_get(state.stats)
    return {k: np.asarray(v) for k, v in host.items()}


def _sync(state: EngineState) -> tuple[int, int, np.ndarray, bool]:
    """Device->host fetch as the per-chunk pacing barrier.

    A wall-clock-bounded loop must see each chunk END before it reads
    the clock, or it enqueues an unbounded backlog of device work.
    `jax.block_until_ready` would pace as well; the fetch stays because
    the loop needs these values after every chunk anyway, and fetching
    them is itself the wait — it also surfaces an execution error at
    the call site.

    Returns (commit_cnt, next_seq, latency_hist, index_overflowed) from
    ONE transfer: the seq-wrap guard, the per-chunk latency snapshot
    (the wall-clock calibration data, ~512 B) AND the capacity-bounded-
    index overflow bit ride one fetch rather than each paying a host<->
    device round trip of its own per chunk."""
    ovf = [t.overflowed()
           for t in (state.db.values() if isinstance(state.db, dict) else ())
           if hasattr(t, "overflowed")]
    c, s, h, o = jax.device_get((state.stats["total_txn_commit_cnt"],
                                 state.pool.next_seq,
                                 state.stats["latency_hist"],
                                 ovf))
    return int(c), int(s), np.asarray(h), any(bool(np.asarray(x))
                                              for x in o)


def run_simulation(cfg: Config, chunk: int = 50,
                   quiet: bool = False) -> Stats:
    """Warmup for ``warmup_secs``, measure for ``done_secs``; returns Stats."""
    from deneva_tpu.runtime.jaxenv import place_compile_cache
    place_compile_cache()
    wl = get_workload(cfg)
    eng = Engine(cfg, wl)
    state = eng.init_state()
    if cfg.resume and cfg.checkpoint_path:
        from deneva_tpu.engine.checkpoint import load_state
        state = load_state(cfg.checkpoint_path, state)
    if cfg.device_parts > 1:
        # multi-chip: lay the state out over the partition mesh and run
        # under it (tables owner-major sharded, workloads/mc executor)
        from deneva_tpu.parallel import make_mesh, make_sharded_run
        place, run_n = make_sharded_run(eng, make_mesh(cfg.device_parts))
        state = place(state)
    else:
        run_n = eng.jit_run

    ctl = None
    if cfg.ctrl:
        # self-driving control plane (runtime/controller.py): the
        # routed scan replaces jit_run; each chunk boundary folds the
        # device counter deltas into one deterministic decision tick
        # and re-arms the knob pytree for the NEXT chunk (values only —
        # the compile is shared).  config.validate pins ctrl to the
        # single-device metrics-on shape, so this arm never races the
        # multi-chip placement above.
        from deneva_tpu.cc.router import knobs_from_decision, static_knobs
        from deneva_tpu.runtime.controller import (Controller, CtrlSignals,
                                                   ctrl_line)
        ctl = Controller(cfg)
        knobs = [static_knobs(cfg)]
        ctrl_prev = [None]          # baseline counter snapshot

        def run_n(state, n):
            return eng.jit_run_ctrl(state, knobs[0], n)

    ckpt_bound = cfg.checkpoint_every_epochs \
        if cfg.checkpoint_path and cfg.checkpoint_every_epochs else 0
    ckpt_due = [cfg.checkpoint_every_epochs]
    run_t0 = time.monotonic()
    prog_next = [run_t0 + cfg.prog_timer_secs]
    epochs_total = [0]      # cumulative across warmup+measure windows
    seq_per_chunk = [(eng.pool.g + eng.pool.b) * chunk]

    def prog_tick(state):
        # [prog] line every prog_timer_secs (reference PROG_TIMER,
        # system/thread.cpp:86-105)
        now = time.monotonic()
        if quiet or cfg.prog_timer_secs <= 0 or now < prog_next[0]:
            return
        prog_next[0] = now + cfg.prog_timer_secs
        from deneva_tpu.stats import make_prog_line
        print(make_prog_line(now - run_t0, _counters(state),
                             {"epoch_cnt": float(epochs_total[0])}),
              flush=True)

    def _guard_seq(head: int):
        # int32 seq/ts wrap guard (see pool.py docstring): next_seq
        # advances (G + B) per epoch; refuse to run another chunk that
        # could cross 2^31 (checked post-chunk with a 2-chunk margin;
        # `head < 0` catches a wrap that somehow slipped past).  The head
        # value rides _sync's transfer instead of paying a round trip of
        # its own per chunk.
        if head < 0 or head > 2**31 - 2 * seq_per_chunk[0]:
            raise RuntimeError(
                f"int32 txn-sequence space nearly exhausted (next_seq="
                f"{head}); shorten the run window or shrink epoch_batch "
                "(seq advances epoch_batch+gen_chunk per epoch)")

    # per-chunk latency calibration records (epochs, wall secs, hist
    # snapshot): the summary maps each chunk's epoch-valued buckets to
    # wall seconds with THAT chunk's measured pace — not one global mean
    # (round-3's mean-scaled buckets, VERDICT r3 next #6)
    chunk_log: list[tuple[int, float, np.ndarray]] = []
    last_t = [time.monotonic()]

    def _ctrl_tick(state):
        """One controller decision per chunk boundary: diff the device
        counters against the previous tick's snapshot, decide, re-arm.
        The first call only establishes the baseline (the pre-baseline
        chunks run on `static_knobs`, i.e. the unrouted values)."""
        # witness density = CLAIM-VIOLATING edge count (audit_wit_cnt,
        # cc/depgraph.witness_count), not the raw edge-lane volume —
        # chained/DGCC epochs legitimately emit edges, so the raw count
        # would spuriously pin audit_cadence to 1 under any contention
        dens, fb, sv, wit = jax.device_get(
            (state.stats["conflict_density"],
             state.stats["rep_fallback_cnt"],
             state.stats["rep_salvaged_cnt"],
             state.stats["audit_wit_cnt"]))
        now = time.monotonic()
        cur = (np.asarray(dens).astype(np.int64), int(fb), int(sv),
               int(wit), epochs_total[0], now)
        prev, ctrl_prev[0] = ctrl_prev[0], cur
        if prev is None:
            return
        sig = CtrlSignals(
            epoch=epochs_total[0], epochs=cur[4] - prev[4],
            dens=[int(x) for x in cur[0] - prev[0]],
            fallback=cur[1] - prev[1], salvaged=cur[2] - prev[2],
            witnesses=cur[3] - prev[3], breaches=0,
            gap_us=int((now - prev[5]) * 1e6))
        dec = ctl.decide(sig)
        knobs[0] = knobs_from_decision(cfg, dec.assign, dec.gshift,
                                       dec.repair_cap, dec.audit_cadence)
        if not quiet:
            print(ctrl_line(0, sig, dec), flush=True)

    def _after_chunk(state):
        """Shared per-chunk bookkeeping: pacing sync + wrap guard +
        overflow fail-fast + progress + checkpoint cadence."""
        _, head, hist, ovf = _sync(state)
        _guard_seq(head)
        _guard_overflow(ovf)
        if ctl is not None:
            _ctrl_tick(state)
        now = time.monotonic()
        chunk_log.append((chunk, now - last_t[0], hist))
        epochs_total[0] += chunk
        prog_tick(state)
        if ckpt_bound:
            ckpt_due[0] -= chunk
            if ckpt_due[0] <= 0:
                from deneva_tpu.engine.checkpoint import save_state
                save_state(cfg.checkpoint_path, state)
                ckpt_due[0] = ckpt_bound
        # reset AFTER the host-side bookkeeping (prog fetch, checkpoint
        # write) so its cost is charged to no chunk's latency pace
        last_t[0] = time.monotonic()

    def _retarget(state, epochs_per_sec: float, spread: int):
        """ONE resize rule for both calibrations: aim each device call at
        ``chunk_target_secs`` of work, capped by the 20k ceiling (one
        device call stays bounded) and the checkpoint interval;
        recompile only when the
        current chunk is off by more than ``spread``x."""
        nonlocal chunk
        target = max(1, min(int(epochs_per_sec * cfg.chunk_target_secs),
                            20_000))
        if ckpt_bound:
            target = min(target, ckpt_bound)
        if target > chunk * spread or target < chunk // spread \
                or (ckpt_bound and chunk > ckpt_bound):
            chunk = target
            seq_per_chunk[0] = (eng.pool.g + eng.pool.b) * chunk
            state = run_n(state, chunk)     # one compile at the new n
            _after_chunk(state)
        return state

    def _guard_overflow(ovf: bool):
        # fail-fast surfacing for capacity-bounded index structures
        # (DynamicSortedIndex contract): past overflow, probes may return
        # slots of ring-overwritten rows — refuse at the FIRST overflowed
        # chunk instead of burning the whole window (ADVICE r4); the bit
        # rides the existing pacing fetch so it costs no extra round trip
        if ovf:
            raise RuntimeError(
                "a capacity-bounded index overflowed during the run "
                "(stale lookups possible); raise its capacity "
                "(insert_table_cap) or shorten the run")

    # pre-flight wrap check (a resumed checkpoint may sit near int32 seq
    # exhaustion, e.g. after an epoch_batch change): refuse before the
    # first unguarded calibration chunk, not after
    _guard_seq(int(jax.device_get(state.pool.next_seq)))
    # compile once (excluded from both windows, like the reference's setup
    # barrier, system/thread.cpp:62-84)
    state = run_n(state, chunk)
    _guard_seq(_sync(state)[1])
    last_t[0] = time.monotonic()
    # adaptive chunking: size each device call to ~chunk_target_secs —
    # large enough that the per-call sync round trip stays in the noise,
    # small enough that the wall-clock windows end near their edge
    t1 = time.monotonic()
    state = run_n(state, chunk)
    _guard_seq(_sync(state)[1])
    last_t[0] = time.monotonic()
    per_chunk = max(last_t[0] - t1, 1e-4)
    state = _retarget(state, chunk / per_chunk, spread=2)

    def run_window(state, secs):
        t0 = time.monotonic()
        ep0 = epochs_total[0]
        while time.monotonic() - t0 < secs:
            state = run_n(state, chunk)
            _after_chunk(state)
        return state, epochs_total[0] - ep0, time.monotonic() - t0

    state, ep_w, el_w = run_window(state, cfg.warmup_secs)
    # re-calibrate against STEADY-STATE epoch time: early epochs can be
    # far cheaper than saturated ones (e.g. T/O at high contention — hot
    # retry keys serialize the watermark scatters), and an optimistic
    # chunk would run one multi-minute device call in the measure window
    if ep_w:
        state = _retarget(state, ep_w / max(el_w, 1e-4), spread=3)
    before = _counters(state)
    chunk_log.clear()                 # calibrate over the measure window
    last_t[0] = time.monotonic()
    t_start = time.monotonic()
    state, epochs, elapsed = run_window(state, cfg.done_secs)
    after = _counters(state)

    st = Stats()
    st._t_start = t_start
    st._t_end = t_start + elapsed
    st.set("total_runtime", elapsed)
    st.set("epoch_cnt", float(epochs))
    for k in ("generated_cnt", "admitted_cnt", "total_txn_commit_cnt",
              "total_txn_abort_cnt", "unique_txn_abort_cnt", "defer_cnt",
              "write_cnt"):
        st.set(k, float(after[k] - before[k]))
    if cfg.repair:
        # repair counters ([summary] satellite): salvaged txns committed
        # (NOT double-counted as aborts — total_txn_abort_cnt already
        # excludes them at the source, engine/repair.run_repair),
        # invalidated read lanes, and retry-queue fallbacks.  Emitted
        # only when armed so the default summary line is byte-identical.
        for k in ("rep_salvaged_cnt", "rep_frontier_cnt",
                  "rep_fallback_cnt"):
            st.set(k, float(after[k] - before[k]))
    if cfg.metrics:
        # metrics bus ([summary] satellite): cumulative per-partition
        # observed-conflict density over the measured window (the
        # per-epoch series is the cluster bus's job; in-process runs
        # get the window totals).  Emitted only when armed so the
        # default summary line is byte-identical.
        dens = (after["conflict_density"]
                - before["conflict_density"]).astype(np.float64)
        for i, d in enumerate(dens):
            st.set(f"mb_density_p{i}", float(d))
        st.set("mb_density_total", float(dens.sum()))
    if cfg.audit:
        # isolation audit ([summary] satellite): dependency edge lanes
        # observed among committed txns + export-cap overflows over the
        # measured window (the sidecar export is the cluster runtime's
        # job — in-process runs surface the device counters).  Emitted
        # only when armed so the default summary line is byte-identical.
        for k in ("audit_edge_cnt", "audit_drop_cnt", "audit_wit_cnt"):
            st.set(k, float(after[k] - before[k]))
    if cfg.ctrl:
        # control plane ([summary] satellite): decision ticks taken and
        # governor trips over the whole run (the per-tick record is the
        # [ctrl] line stream).  Emitted only when armed so the default
        # summary line is byte-identical.
        st.set("ctrl_decisions", float(ctl.seq))
        st.set("ctrl_trips", float(ctl.stale_trips))
    from deneva_tpu.config import CCAlg
    if cfg.cc_alg == CCAlg.DGCC or cfg.ctrl_dgcc:
        # DGCC wavefront ledger ([summary] satellite + the [dgcc] line,
        # parsed by harness.parse.parse_dgcc): waves executed over the
        # window, the deepest single-epoch wavefront of the WHOLE run
        # (a device-side running max — no windowed delta exists),
        # over-deep closures deferred (the cyclic fallback), and
        # pre-commit dependency edges.  Emitted only when DGCC can
        # validate so every other config's output is byte-identical.
        for k in ("dgcc_wave_cnt", "dgcc_fallback_cnt", "dgcc_edge_cnt"):
            st.set(k, float(after[k] - before[k]))
        st.set("dgcc_wave_max", float(after["dgcc_wave_max"]))
        if not quiet:
            from deneva_tpu.stats import tagged_line
            print(tagged_line("dgcc", {
                "node": 0,
                "waves": int(after["dgcc_wave_cnt"]
                             - before["dgcc_wave_cnt"]),
                "wave_max": int(after["dgcc_wave_max"]),
                "fallback": int(after["dgcc_fallback_cnt"]
                                - before["dgcc_fallback_cnt"]),
                "edges": int(after["dgcc_edge_cnt"]
                             - before["dgcc_edge_cnt"])}), flush=True)
    for i, nm in enumerate(getattr(wl, "txn_type_names", ())):
        for fam in ("commit", "abort"):
            key = f"{fam}_by_type"
            st.set(f"{nm}_{fam}_cnt", float(after[key][i] - before[key][i]))
    commits = after["total_txn_commit_cnt"] - before["total_txn_commit_cnt"]
    aborts = after["total_txn_abort_cnt"] - before["total_txn_abort_cnt"]
    # every committed txn contributes exactly one latency sample (its
    # commit-epoch minus entry-epoch, engine latency_hist), calibrated
    # to wall seconds with the PACE OF ITS OWN CHUNK (epoch timestamps
    # per chunk; the weighted StatsArr keeps the full multiset — no
    # cap, no synthesis).  Per-type families feed {type}_latency_*;
    # the combined series keeps the reference-compatible name.
    type_names = list(getattr(wl, "txn_type_names", ("txn",)))
    lb = after["latency_hist"].shape[-1]
    prev = before["latency_hist"].astype(np.float64)
    for n_ep, secs, snap in chunk_log:
        cur = snap.astype(np.float64)
        delta = cur - prev
        prev = cur
        spe = secs / max(n_ep, 1)
        centers = (np.arange(lb) + 0.5) * spe
        for i, nm in enumerate(type_names):
            row = delta[i] if delta.ndim == 2 else delta
            if row.sum() > 0:
                st.arr(f"{nm}_latency").extend_weighted(centers, row)
                st.arr("client_client_latency").extend_weighted(
                    centers, row)
    # per-txn restart/wait decomposition (TxnStats analogue): counts of
    # retries and waited epochs each committed txn paid
    for key, name in (("retry_hist", "txn_retries"),
                      ("wait_hist", "txn_waits")):
        d = (after[key] - before[key]).astype(np.float64)
        if d.sum() > 0:
            st.arr(name).extend_weighted(np.arange(len(d)), d)
    st.set("abort_rate", float(aborts) / max(float(commits + aborts), 1.0))
    # named backstop for the per-chunk _guard_overflow fail-fast (also
    # covers overflow in the final partial chunk): past overflow, probes
    # may return slots of ring-overwritten rows — refuse to report
    for name, t in (state.db.items() if isinstance(state.db, dict) else ()):
        if hasattr(t, "overflowed") and bool(
                np.asarray(jax.device_get(t.overflowed()))):
            raise RuntimeError(
                f"index {name!r} overflowed its capacity during the run "
                "(stale lookups possible); raise its capacity "
                "(insert_table_cap) or shorten the run")
    if cfg.checkpoint_path:
        from deneva_tpu.engine.checkpoint import save_state
        save_state(cfg.checkpoint_path, state)
    if not quiet:
        print(st.summary_line())
    return st
