"""Transaction repair engine (engine/repair.py, Config.repair).

Four claim families:

* **Serial-sum oracle with repair on** — per sweep backend, the TPC-C
  audit invariants (YTD conservation, balance conservation, dense
  per-district o_ids) hold with the escrow exemption OFF and repair ON:
  salvaged txns are serializable commits, and the commit count
  dominates the retry-only floor.
* **Repair-off / no-loser identity** — ``repair=false`` takes the
  pre-repair code paths (structural: the gate family lint enforces it;
  the run here pins behavior), and ``repair=true`` with ZERO losers is
  bit-identical to ``repair=false`` on every data row, cc_state leaf
  and stats counter (the repair no-op path really is a no-op; the
  padded trash slot absorbs the masked waves by design and is excluded
  like `logger.state_digest` excludes control-plane leaves).
* **Scripted frontier cases** — empty frontier (write-only loser
  salvages, zero invalidated lanes), full frontier (the loser's re-read
  observes the winner's value, checksum-exact), cyclic re-invalidation
  (an m-deep hot-key chain salvages exactly ``repair_rounds`` losers
  and the rest fall back to the retry queue), and the escrow contract
  (escrow reads never enter the frontier — repair of an escrow delta
  is a no-op).
* **Floor smoke** (slow) — YCSB zipf-0.9 write-heavy: OCC and MAAT
  commit >= 2x the retry-only run per epoch at the calibrated CPU
  operating point (epoch-rate-free formulation, like the escrow floor
  smoke; wall-clock curves live in results/repair with capture
  provenance).

Accounting contract (the parse-compat satellite): a salvaged txn is a
COMMIT — ``total_txn_abort_cnt`` counts only retry-queue fallbacks, so
``total_txn_abort_cnt == rep_fallback_cnt`` on any forced-free run.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deneva_tpu.cc import (AccessBatch, build_conflict_incidence,
                           committed_write_frontier, get_backend)
from deneva_tpu.config import CCAlg, Config, WorkloadKind
from deneva_tpu.engine import Engine
from deneva_tpu.engine.repair import repair_ts, run_repair
from deneva_tpu.engine.step import init_device_stats
from deneva_tpu.workloads import get_workload
from deneva_tpu.workloads.ycsb import YCSBQuery, _field_fingerprint

SWEEP_ALGS = ("NO_WAIT", "WAIT_DIE", "OCC", "TIMESTAMP", "MVCC", "MAAT")


def ycsb_cfg(**kw):
    base = dict(workload=WorkloadKind.YCSB, synth_table_size=1 << 12,
                req_per_query=4, max_accesses=4, epoch_batch=128,
                conflict_buckets=1024, max_txn_in_flight=512,
                zipf_theta=0.9, read_perc=0.1, write_perc=0.9,
                repair=True, warmup_secs=0.0, done_secs=0.2)
    base.update(kw)
    if "cc_alg" in base:
        base["cc_alg"] = CCAlg(base["cc_alg"])
    return Config(**base).validate()


def tpcc_cfg(**kw):
    base = dict(workload=WorkloadKind.TPCC, num_wh=2, cust_per_dist=120,
                max_items=4096, max_items_per_txn=5, max_accesses=8,
                epoch_batch=64, conflict_buckets=1024,
                max_txn_in_flight=256, insert_table_cap=1 << 14,
                repair=True, escrow_sweep=False,
                warmup_secs=0.0, done_secs=0.2)
    base.update(kw)
    if "cc_alg" in base:
        base["cc_alg"] = CCAlg(base["cc_alg"])
    return Config(**base).validate()


# ---- scripted rig: one epoch, hand-built plans, direct run_repair -----

B, R = 8, 2


def _rig(alg, scripts, rounds=2, cfg_kw=()):
    """scripts: per-txn [(key, 'r'|'w'), ...] (padded to R with reads of
    a per-lane cold key).  Returns (cfg, wl, be, db0, queries, batch,
    inc, verdict, cc_state, stats) after the MAIN round's validate +
    execute — run_repair's exact inputs in Engine.step."""
    cfg = ycsb_cfg(cc_alg=alg, synth_table_size=1024, req_per_query=R,
                   max_accesses=R, epoch_batch=B, zipf_theta=0.0,
                   **dict(cfg_kw))
    wl = get_workload(cfg)
    be = get_backend(cfg.cc_alg)
    db = wl.load()
    keys = np.zeros((B, R), np.int32)
    is_w = np.zeros((B, R), bool)
    for i in range(B):
        for s in range(R):
            # pad: read of a distinct cold key (600+lane*R+s)
            keys[i, s] = 600 + i * R + s
        for s, (key, mode) in enumerate(scripts[i] if i < len(scripts)
                                        else ()):
            keys[i, s] = key
            is_w[i, s] = mode == "w"
    active = np.zeros(B, bool)
    active[:len(scripts)] = True
    queries = YCSBQuery(keys=jnp.asarray(keys), is_write=jnp.asarray(is_w))
    planned = wl.plan(db, queries)
    batch = AccessBatch(
        table_ids=planned["table_ids"], keys=planned["keys"],
        is_read=planned["is_read"], is_write=planned["is_write"],
        valid=planned["valid"],
        ts=jnp.arange(1, B + 1, dtype=jnp.int32),
        rank=jnp.arange(B, dtype=jnp.int32),
        active=jnp.asarray(active))
    inc = build_conflict_incidence(cfg, be, batch, None)
    verdict, cc_state = be.validate(cfg, be.init_state(cfg), batch, inc)
    stats = init_device_stats()
    exec_commit = verdict.commit
    db = wl.execute(db, queries, exec_commit, verdict.order, stats)
    return cfg, wl, be, db, queries, batch, inc, verdict, cc_state, stats


def _repair(rig, rounds=2):
    cfg, wl, be, db, q, batch, inc, v, st, stats = rig
    cfg = cfg.replace(repair_rounds=rounds)
    db, st, v2, salvaged, _rounds = run_repair(cfg, wl, be, db, q,
                                               batch, inc, v, st,
                                               stats, v.commit)
    return db, v2, np.asarray(salvaged), stats


def _f0(db, key):
    from deneva_tpu.workloads.ycsb import TABLE
    return int(np.asarray(db[TABLE].columns["F0"])[key])


def test_empty_frontier_salvages_write_only_loser():
    """A write-write loser has nothing to re-read: empty frontier
    (rep_frontier_cnt == 0), salvaged in the first sub-round, and its
    blind write lands AFTER the winner's (final value = the loser's)."""
    rig = _rig("OCC", [[(5, "w")], [(5, "w")]])
    v0 = rig[7]
    assert np.asarray(v0.commit)[0] and np.asarray(v0.abort)[1]
    db, v, salvaged, stats = _repair(rig)
    assert salvaged[1] and np.asarray(v.commit)[1]
    assert not np.asarray(v.abort)[1]
    assert int(stats["rep_frontier_cnt"]) == 0
    assert int(stats["rep_salvaged_cnt"]) == 1
    assert int(stats["rep_fallback_cnt"]) == 0
    # the salvage wave applies after the winner: f(5, loser order)
    assert _f0(db, 5) == int(_field_fingerprint(5, np.asarray(v.order)[1]))


def test_full_frontier_reader_observes_winner_value():
    """A loser whose ONLY conflict is a stale read re-reads the winner's
    value in the sub-round: frontier names exactly that lane, and the
    read checksum contains f(key, winner order) — the value a serial
    schedule (winner, then loser) reads."""
    # lane0 writes key 5; lane1 reads key 5 (plus its cold pad read)
    rig = _rig("OCC", [[(5, "w")], [(5, "r")]])
    cfg, wl, be, db0, q, batch, inc, v0, st, stats = rig
    assert np.asarray(v0.commit)[0] and np.asarray(v0.abort)[1]
    pre_cks = int(stats["read_checksum"])
    db, v, salvaged, stats = _repair(rig)
    assert salvaged[1]
    assert int(stats["rep_frontier_cnt"]) == 1     # exactly the r5 lane
    # sub-round checksum delta = the re-read values: winner's f(5, ord0)
    # + the loser's two cold pads... lane1 pad read + re-read of 5
    w_ord = int(np.asarray(v0.order)[0])
    delta = (int(stats["read_checksum"]) - pre_cks) % (1 << 32)
    expect = (int(_field_fingerprint(5, w_ord))
              + int(_field_fingerprint(603, 0))) % (1 << 32)
    assert delta == expect, (delta, expect)


def test_cyclic_reinvalidation_falls_back():
    """An m-writer hot-key chain: the main round admits one, each repair
    sub-round admits exactly one more (each pass's winner re-invalidates
    the rest — the cyclic re-invalidation case), and past repair_rounds
    the leftovers fall back to the retry queue as aborts."""
    rig = _rig("OCC", [[(5, "w")], [(5, "w")], [(5, "w")], [(5, "w")]])
    v0 = rig[7]
    assert int(np.asarray(v0.commit).sum()) == 1
    db, v, salvaged, stats = _repair(rig, rounds=2)
    assert int(salvaged.sum()) == 2                # one per sub-round
    assert int(stats["rep_salvaged_cnt"]) == 2
    assert int(stats["rep_fallback_cnt"]) == 1     # lane3 -> retry queue
    assert np.asarray(v.abort)[3] and not np.asarray(v.commit)[3]
    # waves applied in order: final value is the LAST salvaged wave's
    assert _f0(db, 5) == int(_field_fingerprint(5, np.asarray(v.order)[2]))


def test_timestamp_watermark_loser_restamps_and_salvages():
    """A T/O watermark violator (read from its ts-future) is exactly
    what retry-with-fresh-ts fixes next epoch; repair restamps within
    the epoch.  Scripted: seed the watermark with a committed write at
    ts 10, then a reader stamped ts 2 (< 10) aborts the main round and
    salvages at a fresh stamp in the sub-round."""
    # epoch 1: lane0 writes key 5 at its ts; raises wts[bucket(5)]
    rig1 = _rig("TIMESTAMP", [[(5, "w")] for _ in range(8)])
    _, _, be, _, _, _, _, _, st1, _ = rig1
    # epoch 2 against st1: lane0 reads key 5 at ts 1 < recorded wts
    cfg, wl, _, db, q, batch, inc, _, _, _ = _rig("TIMESTAMP",
                                                  [[(5, "r")]])
    v, st2 = be.validate(cfg, st1, batch, inc)
    assert np.asarray(v.abort)[0], "stale reader must abort pre-repair"
    stats = init_device_stats()
    db = wl.execute(db, q, v.commit, v.order, stats)
    cfg = cfg.replace(repair_rounds=2)
    # ts_base: the engine passes its pool's reserved restamp base,
    # which is strictly above every committed watermark; the scripted
    # rig reuses low ts across "epochs", so supply the base explicitly
    # (20 > the epoch-1 writers' recorded wts)
    db, st3, v2, salvaged, _r = run_repair(cfg, wl, be, db, q, batch,
                                           inc, v, st2, stats,
                                           v.commit,
                                           ts_base=jnp.int32(20))
    assert np.asarray(salvaged)[0], "watermark loser must salvage"
    assert int(stats["rep_frontier_cnt"]) >= 1     # the stale-read lane
    assert not np.asarray(v2.abort)[0]
    # the fallback base rule (no authority supplied): fresh stamps sit
    # above every ACTIVE stamp in the epoch
    rts = np.asarray(repair_ts(batch))
    act = np.asarray(batch.active)
    assert rts.min() > int(np.asarray(batch.ts)[act].max())
    # and without a sufficient base the T/O re-check DECLINES the
    # salvage (conservative, never a wrong commit): stamp below the
    # watermark -> still aborted
    stats2 = init_device_stats()
    _, _, v3, salv2, _r2 = run_repair(cfg, wl, be, db, q, batch, inc,
                                      v, st2, stats2, v.commit,
                                      ts_base=jnp.int32(2))
    assert not np.asarray(salv2)[0]
    assert np.asarray(v3.abort)[0]


def test_escrow_reads_never_enter_frontier():
    """The escrow contract: order_free accesses are commutative deltas /
    immutable-column reads — repair of an escrow access is a no-op, so
    escrow READ lanes are excluded from the frontier even when their
    bucket was overwritten."""
    cfg = ycsb_cfg(cc_alg="OCC", synth_table_size=1024, req_per_query=R,
                   max_accesses=R, epoch_batch=B, zipf_theta=0.0)
    wl = get_workload(cfg)
    be = get_backend(cfg.cc_alg)
    db = wl.load()
    keys = np.array([[5, 600], [5, 601]] + [[602 + i, 610 + i]
                                            for i in range(B - 2)],
                    np.int32)
    is_w = np.zeros((B, R), bool)
    is_w[0, 0] = True                  # lane0 writes key 5
    q = YCSBQuery(keys=jnp.asarray(keys), is_write=jnp.asarray(is_w))
    planned = wl.plan(db, q)
    of = np.zeros((B, R), bool)
    of[1, 0] = True                    # lane1's read of key 5 is escrow
    batch = AccessBatch(
        table_ids=planned["table_ids"], keys=planned["keys"],
        is_read=planned["is_read"], is_write=planned["is_write"],
        valid=planned["valid"], ts=jnp.arange(1, B + 1, dtype=jnp.int32),
        rank=jnp.arange(B, dtype=jnp.int32),
        active=jnp.ones(B, bool), order_free=jnp.asarray(of))
    inc = build_conflict_incidence(cfg, be, batch, batch.order_free)
    committed = jnp.zeros(B, bool).at[0].set(True)
    losers = jnp.zeros(B, bool).at[1].set(True)
    fr = np.asarray(committed_write_frontier(cfg, batch, inc, committed,
                                             losers))
    assert not fr[1, 0], "escrow read must not enter the frontier"
    # the same lane WITHOUT the escrow mark is in the frontier
    plain = dataclasses.replace(batch, order_free=None)
    inc2 = build_conflict_incidence(cfg, be, plain, None)
    fr2 = np.asarray(committed_write_frontier(cfg, plain, inc2, committed,
                                              losers))
    assert fr2[1, 0]


# ---- engine-level: accounting + no-loser identity ---------------------

def test_salvaged_txns_are_commits_not_aborts():
    """The parse-compat satellite: total_txn_abort_cnt counts ONLY
    retry-queue fallbacks (== rep_fallback_cnt); salvaged txns ride the
    commit counter and rep_salvaged_cnt."""
    cfg = ycsb_cfg(cc_alg="OCC")
    eng = Engine(cfg, get_workload(cfg))
    st = jax.device_get(eng.jit_run(eng.init_state(0), 20)).stats
    assert int(st["rep_salvaged_cnt"]) > 0, "contention point inert"
    assert int(st["total_txn_abort_cnt"]) == int(st["rep_fallback_cnt"])
    off = cfg.replace(repair=False)
    eng2 = Engine(off, get_workload(off))
    so = jax.device_get(eng2.jit_run(eng2.init_state(0), 20)).stats
    assert int(st["total_txn_commit_cnt"]) > int(so["total_txn_commit_cnt"])


@pytest.mark.parametrize("alg", ["OCC", "TIMESTAMP", "MVCC"])
def test_repair_noop_when_no_losers_bit_identical(alg):
    """All-read workload: no conflicts, no losers — the armed repair
    machinery must be an exact no-op: every DATA row, cc_state leaf,
    pool leaf and stats counter bitwise equals the repair-off run (the
    padded trash slot, which absorbs every masked wave by design, is
    the only writable difference and is excluded exactly like
    state_digest excludes control-plane leaves)."""
    from deneva_tpu.workloads.ycsb import TABLE
    kw = dict(cc_alg=alg, read_perc=1.0, write_perc=0.0)
    on = ycsb_cfg(**kw)
    off = ycsb_cfg(repair=False, **kw)
    s_on = jax.device_get(Engine(on, get_workload(on)).jit_run(
        Engine(on, get_workload(on)).init_state(0), 10))
    s_off = jax.device_get(Engine(off, get_workload(off)).jit_run(
        Engine(off, get_workload(off)).init_state(0), 10))
    n = on.synth_table_size
    np.testing.assert_array_equal(
        np.asarray(s_on.db[TABLE].columns["F0"])[:n],
        np.asarray(s_off.db[TABLE].columns["F0"])[:n])
    for a, b in zip(jax.tree.leaves(s_on.cc_state),
                    jax.tree.leaves(s_off.cc_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_on.pool),
                    jax.tree.leaves(s_off.pool)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # (`write_scatter_lanes` and `read_gather_lanes` count device work:
    # the armed sub-rounds hand their scatters and gathers every lane
    # again, all of them masked)
    for k in set(s_on.stats) - {"write_scatter_lanes", "read_gather_lanes"}:
        np.testing.assert_array_equal(np.asarray(s_on.stats[k]),
                                      np.asarray(s_off.stats[k]), k)
    assert int(s_on.stats["rep_salvaged_cnt"]) == 0
    assert int(s_on.stats["rep_frontier_cnt"]) == 0


def test_repair_rounds_zero_salvages_nothing():
    """The ablation floor: repair armed with rounds=0 runs the pre-
    repair semantics (zero salvage, fallbacks == aborts == the
    repair-off aborts on the same stream)."""
    cfg = ycsb_cfg(cc_alg="OCC", repair_rounds=0)
    st = jax.device_get(Engine(cfg, get_workload(cfg)).jit_run(
        Engine(cfg, get_workload(cfg)).init_state(0), 10)).stats
    off = cfg.replace(repair=False)
    so = jax.device_get(Engine(off, get_workload(off)).jit_run(
        Engine(off, get_workload(off)).init_state(0), 10)).stats
    assert int(st["rep_salvaged_cnt"]) == 0
    assert int(st["total_txn_commit_cnt"]) == int(so["total_txn_commit_cnt"])
    assert int(st["total_txn_abort_cnt"]) == int(so["total_txn_abort_cnt"])


# ---- per-backend serial-sum oracle (TPC-C audit, escrow OFF) ----------

def _tpcc_oracle(alg, n=25):
    import sys
    import os
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_escrow import _audit
    cfg = tpcc_cfg(cc_alg=alg)
    eng = Engine(cfg, get_workload(cfg))
    s0 = eng.init_state(0)
    d0 = jax.device_get(s0.db)
    state = jax.device_get(eng.jit_run(s0, n))
    _audit(cfg, state, d0)
    off = cfg.replace(repair=False)
    eng2 = Engine(off, get_workload(off))
    so = jax.device_get(eng2.jit_run(eng2.init_state(0), n))
    on_c = int(state.stats["total_txn_commit_cnt"])
    off_c = int(so.stats["total_txn_commit_cnt"])
    assert int(state.stats["rep_salvaged_cnt"]) > 0, alg
    assert on_c > off_c, (alg, on_c, off_c)
    return on_c, off_c


def test_repair_oracle_occ():
    """Fast-tier representative: OCC's repaired commit set satisfies the
    TPC-C serial-sum audit (YTD/balance conservation + dense o_ids) on
    the re-floored hot rows (escrow off), and dominates retry-only."""
    on, off = _tpcc_oracle("OCC")
    assert on > 2 * off, (on, off)


@pytest.mark.slow
@pytest.mark.parametrize("alg", [a for a in SWEEP_ALGS if a != "OCC"])
def test_repair_oracle_all_backends(alg):
    _tpcc_oracle(alg)


# ---- the floor smoke (slow; acceptance pair, tools/smoke.sh repair) ---

@pytest.mark.slow
@pytest.mark.parametrize("alg", ["OCC", "MAAT"])
def test_ycsb_highwrite_repair_above_floor(alg):
    """YCSB zipf-0.9 write-heavy at the calibrated CPU point (16k rows,
    8 acc/txn, eb=512 — results/repair README): repair-on commits per
    epoch must clear the retry-only floor by >= 1.7x (measured 2.0x OCC
    / 2.4x MAAT; the margin absorbs seed variance).  Epoch-rate-free
    like the escrow floor smoke — wall-clock curves with capture
    provenance live in results/repair."""
    n = 40
    cfg = ycsb_cfg(cc_alg=alg, synth_table_size=1 << 14, req_per_query=8,
                   max_accesses=8, epoch_batch=512, conflict_buckets=2048,
                   max_txn_in_flight=2048)
    eng = Engine(cfg, get_workload(cfg))
    on = jax.device_get(eng.jit_run(eng.init_state(0), n)).stats
    off_cfg = cfg.replace(repair=False)
    eng2 = Engine(off_cfg, get_workload(off_cfg))
    off = jax.device_get(eng2.jit_run(eng2.init_state(0), n)).stats
    on_c = int(on["total_txn_commit_cnt"])
    off_c = int(off["total_txn_commit_cnt"])
    assert on_c >= 1.7 * max(off_c, 1), (alg, on_c, off_c)
    # and a strictly lower abort RATE (raw abort EVENTS can rise:
    # salvage frees slots faster, so more fresh txns enter the
    # contention — the rate is the per-attempt outcome that must drop)
    on_a, off_a = int(on["total_txn_abort_cnt"]), \
        int(off["total_txn_abort_cnt"])
    assert on_a / (on_a + on_c) < off_a / (off_a + off_c), \
        (alg, on_a, on_c, off_a, off_c)
