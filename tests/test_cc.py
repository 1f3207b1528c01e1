"""CC backends: scripted interleavings + serializability oracles.

Each txn script is a list of (key, mode) with mode 'r' | 'w' | 'rw'.
The oracle checks the *semantic* contract of a Verdict under epoch-snapshot
execution: committed reads must be correct in the claimed serialization
order (no committed writer of a key ordered before a committed
snapshot-reader of it, unless the backend chains levels and the reader's
level is above the writer's).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from deneva_tpu.config import Config, CCAlg
from deneva_tpu.cc import AccessBatch, build_incidence, get_backend


CFG = Config(epoch_batch=16, conflict_buckets=4096, max_accesses=4,
             req_per_query=4, synth_table_size=1024)


def make_batch(txns, ts=None, rank=None, a=4):
    # pad every batch to a fixed B so jit compiles once per algorithm
    b, bp = len(txns), CFG.epoch_batch
    assert b <= bp
    keys = np.zeros((bp, a), np.int32)
    is_r = np.zeros((bp, a), bool)
    is_w = np.zeros((bp, a), bool)
    valid = np.zeros((bp, a), bool)
    for i, script in enumerate(txns):
        assert len(script) <= a
        for s, (key, mode) in enumerate(script):
            keys[i, s] = key
            valid[i, s] = True
            is_r[i, s] = "r" in mode
            is_w[i, s] = "w" in mode
    ts = np.arange(1, b + 1, dtype=np.int32) if ts is None else np.asarray(ts, np.int32)
    rank = np.arange(b, dtype=np.int32) if rank is None else np.asarray(rank, np.int32)
    ts = np.concatenate([ts, np.full(bp - b, ts.max() + 1, np.int32)])
    rank = np.concatenate([rank, np.arange(bp - b, dtype=np.int32) + rank.max() + 1])
    active = np.zeros(bp, bool)
    active[:b] = True
    return AccessBatch(
        table_ids=jnp.zeros((bp, a), jnp.int32), keys=jnp.asarray(keys),
        is_read=jnp.asarray(is_r), is_write=jnp.asarray(is_w),
        valid=jnp.asarray(valid), ts=jnp.asarray(ts), rank=jnp.asarray(rank),
        active=jnp.asarray(active))


import functools
import jax


@functools.lru_cache(maxsize=32)
def _jitted_validate(alg, cfg):
    be = get_backend(alg)

    @jax.jit
    def go(state, batch):
        inc = build_incidence(batch, cfg.conflict_buckets, cfg.conflict_exact) \
            if be.needs_incidence else None
        return be.validate(cfg, state, batch, inc)
    return go


def run(alg, txns, cfg=CFG, state=None, **kw):
    be = get_backend(alg)
    batch = make_batch(txns, **kw)
    if state is None:
        state = be.init_state(cfg)
    verdict, state = _jitted_validate(alg, cfg)(state, batch)
    return verdict, state, batch


def check_verdict(verdict, batch, txns, chained=False):
    commit = np.asarray(verdict.commit)
    abort = np.asarray(verdict.abort)
    defer = np.asarray(verdict.defer)
    order = np.asarray(verdict.order)
    level = np.asarray(verdict.level)
    active = np.asarray(batch.active)
    # disjoint partition covering active
    assert not (commit & abort).any() and not (commit & defer).any() \
        and not (abort & defer).any()
    assert ((commit | abort | defer) == active).all()
    # serializability of the committed set
    reads = [set(k for k, m in s if "r" in m) for s in txns]
    writes = [set(k for k, m in s if "w" in m) for s in txns]
    b = len(txns)
    for i in range(b):
        for j in range(b):
            if i == j or not (commit[i] and commit[j]):
                continue
            if order[j] < order[i] and (writes[j] & reads[i]):
                # j's write ordered before i's snapshot read of same key
                if chained:
                    assert level[i] > level[j], (i, j)
                else:
                    raise AssertionError(f"stale read: writer {j} < reader {i}")
            if writes[i] & writes[j]:
                assert order[i] != order[j]
    return commit[:b], abort[:b], defer[:b]


# ---- NO_WAIT -----------------------------------------------------------

def test_no_wait_conflict_aborts_later():
    v, _, batch = run("NO_WAIT", [[(5, "w")], [(5, "r")], [(7, "r")]])
    c, a, d = check_verdict(v, batch, [[(5, "w")], [(5, "r")], [(7, "r")]])
    assert c[0] and a[1] and c[2]

def test_no_wait_read_read_no_conflict():
    v, _, b = run("NO_WAIT", [[(5, "r")], [(5, "r")]])
    c, a, d = check_verdict(v, b, [[(5, "r")], [(5, "r")]])
    assert c.all()

def test_no_wait_rank_decides():
    v, _, b = run("NO_WAIT", [[(5, "w")], [(5, "w")]], rank=[9, 2])
    c, a, d = check_verdict(v, b, [[(5, "w")], [(5, "w")]])
    assert a[0] and c[1]


# ---- WAIT_DIE ----------------------------------------------------------

def test_wait_die_older_waits_younger_dies():
    # txn0 owns (rank 0); txn1 older (smaller ts) -> waits; txn2 younger -> dies
    txns = [[(5, "w")], [(5, "w")], [(5, "w")]]
    v, _, b = run("WAIT_DIE", txns, ts=[50, 10, 90], rank=[0, 1, 2])
    c, a, d = check_verdict(v, b, txns)
    assert c[0] and d[1] and a[2]


# ---- OCC ---------------------------------------------------------------

def test_occ_reader_first_commits_both():
    txns = [[(5, "r")], [(5, "w")]]
    v, _, b = run("OCC", txns)
    c, a, d = check_verdict(v, b, txns)
    assert c.all()   # reader rank 0, writer rank 1: serial r->w valid

def test_occ_writer_first_aborts_reader():
    txns = [[(5, "w")], [(5, "r")]]
    v, _, b = run("OCC", txns)
    c, a, d = check_verdict(v, b, txns)
    assert c[0] and a[1]

def test_occ_blind_ww_conflicts():
    txns = [[(5, "w")], [(5, "w")]]
    v, _, b = run("OCC", txns)
    c, a, d = check_verdict(v, b, txns)
    assert c[0] and a[1]


def test_occ_verdict_invariants_on_hot_random_epochs():
    """Disjoint, covering, serializable (`check_verdict`) on epochs of
    14 txns over eight keys; OCC decides every txn within the epoch."""
    rng = np.random.default_rng(5)
    for trial in range(4):
        txns = [[(int(rng.integers(0, 8)), str(rng.choice(["r", "w", "rw"])))
                 for _ in range(rng.integers(1, 5))] for _ in range(14)]
        v, _, b = run("OCC", txns)
        c, a, d = check_verdict(v, b, txns)
        assert c.sum() >= 1 and not d.any()


@pytest.mark.parametrize("alg", ["OCC", "NO_WAIT", "CALVIN", "MAAT",
                                 "TIMESTAMP"])
def test_sweep_backends_conflict_on_keys_not_on_buckets(alg):
    """ONE bucket in each hash family: every access of the epoch shares
    it, and still txns with keys of their own do not conflict — the
    sweep backends' conflict matrix is `Incidence.overlap`'s compare of
    the exact keys, whatever `conflict_buckets` says."""
    cfg = CFG.replace(conflict_buckets=1, conflict_exact=True)
    txns = [[(k, "rw")] for k in range(6)] + [[(0, "r")]]
    v, _, b = run(alg, txns, cfg=cfg)
    c, a, d = check_verdict(v, b, txns, chained=alg == "CALVIN")
    assert c[:6].all()
    assert c[6] == (alg in ("CALVIN", "MAAT"))


# ---- TIMESTAMP ---------------------------------------------------------

def test_to_reader_after_writer_waits():
    # buffered read (row_ts.cpp:63-80): the later reader parks until the
    # writer's value is committed — defer, not abort
    txns = [[(5, "w")], [(5, "r")]]
    v, st, b = run("TIMESTAMP", txns, ts=[1, 2])
    c, a, d = check_verdict(v, b, txns)
    assert c[0] and d[1] and not a[1]
    # next epoch the parked reader finds the committed value (wts=1 < 2)
    v, st, b = run("TIMESTAMP", [[(5, "r")]], ts=[2], state=st)
    assert np.asarray(v.commit)[0]

def test_to_reader_before_writer_both_commit():
    txns = [[(5, "r")], [(5, "w")]]
    v, _, b = run("TIMESTAMP", txns, ts=[1, 2])
    c, a, d = check_verdict(v, b, txns)
    assert c.all()

def test_to_blind_ww_thomas_rule():
    txns = [[(5, "w")], [(5, "w")]]
    v, _, b = run("TIMESTAMP", txns, ts=[1, 2])
    c, a, d = check_verdict(v, b, txns)
    assert c.all()
    assert np.asarray(v.order)[1] > np.asarray(v.order)[0]

def test_to_watermarks_cross_epoch():
    be = get_backend("TIMESTAMP")
    st = be.init_state(CFG)
    # epoch 1: writer at ts 10 commits
    v, st, _ = run("TIMESTAMP", [[(5, "w")]], ts=[10], state=st)
    assert np.asarray(v.commit)[0]
    # epoch 2: stale reader ts 5 aborts; fresh reader ts 15 commits;
    # stale writer ts 7 aborts
    txns = [[(5, "r")], [(5, "r")], [(5, "w")]]
    v, st, b = run("TIMESTAMP", txns, ts=[5, 15, 7], state=st)
    c, a, d = check_verdict(v, b, txns)
    assert a[0] and c[1] and a[2]


# ---- MVCC --------------------------------------------------------------

def test_mvcc_readonly_always_commits():
    be = get_backend("MVCC")
    st = be.init_state(CFG)
    v, st, _ = run("MVCC", [[(5, "w")]], ts=[10], state=st)
    # stale read-only txn commits under MVCC (old version), aborts under T/O
    v, st, b = run("MVCC", [[(5, "r")]], ts=[5], state=st)
    assert np.asarray(v.commit)[0]

def test_mvcc_rw_txn_still_validates():
    be = get_backend("MVCC")
    st = be.init_state(CFG)
    v, st, _ = run("MVCC", [[(5, "w")]], ts=[10], state=st)
    # RMW with stale ts aborts: it must read latest AND its write hits
    # the wts watermark (row_mvcc.cpp P_REQ conflict)
    v, st, b = run("MVCC", [[(5, "rw")]], ts=[7], state=st)
    assert np.asarray(v.abort)[0]


def test_mvcc_version_ring_serves_stale_read():
    """The round-1 divergence, fixed: a read-WRITE txn whose pure read
    hits ``wts > ts`` commits when the needed version is retained in the
    bounded history ring (reference serves the old version,
    row_mvcc.cpp:264-270) — under TIMESTAMP the same txn aborts."""
    be = get_backend("MVCC")
    st = be.init_state(CFG)
    v, st, _ = run("MVCC", [[(5, "w")]], ts=[10], state=st)
    txns = [[(5, "r"), (6, "w")]]          # stale read + fresh blind write
    v, st, b = run("MVCC", txns, ts=[7], state=st)
    assert np.asarray(v.commit)[0]
    # same interleaving under single-version T/O: abort
    be_to = get_backend("TIMESTAMP")
    st2 = be_to.init_state(CFG)
    v2, st2, _ = run("TIMESTAMP", [[(5, "w")]], ts=[10], state=st2)
    v2, st2, _ = run("TIMESTAMP", txns, ts=[7], state=st2)
    assert np.asarray(v2.abort)[0]


def test_mvcc_recycled_version_aborts():
    """Reads older than the retained history abort, mirroring
    HIS_RECYCLE_LEN garbage collection (row_mvcc.cpp:303-321): after
    mvcc_his_len version boundaries, the oldest retained boundary rises
    above a sufficiently stale reader's ts."""
    be = get_backend("MVCC")
    st = be.init_state(CFG)
    for wts in (10, 20, 30, 40):           # mvcc_his_len = 4 boundaries
        v, st, _ = run("MVCC", [[(5, "w")]], ts=[wts], state=st)
        assert np.asarray(v.commit)[0]
    # ring now [10, 20, 30, 40]: ts 5 predates every retained version
    v, _, _ = run("MVCC", [[(5, "r"), (6, "w")]], ts=[5], state=st)
    assert np.asarray(v.abort)[0]
    # ts 15 is covered by the ts-10 version: served, commits
    v, _, _ = run("MVCC", [[(5, "r"), (6, "w")]], ts=[15], state=st)
    assert np.asarray(v.commit)[0]


def test_mvcc_serves_historical_bytes():
    """Multi-version value oracle (VERDICT round-2 #3): a committed stale
    read must return the HISTORICAL bytes of the version current at its
    timestamp — matching serial execution value-for-value
    (`row_mvcc.cpp:172-196`) — while read-only snapshot txns read the
    live epoch-start state."""
    from deneva_tpu.config import WorkloadKind
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.workloads import get_workload
    from deneva_tpu.workloads.ycsb import (VER_TABLE, YCSBQuery,
                                           _field_fingerprint)

    cfg = Config(workload=WorkloadKind.YCSB, cc_alg=CCAlg.MVCC,
                 synth_table_size=1024, req_per_query=2, max_accesses=2,
                 epoch_batch=4, conflict_buckets=512,
                 max_txn_in_flight=4)
    wl = get_workload(cfg)
    db = wl.load()
    assert VER_TABLE in db, "MVCC must allocate the version-value ring"
    be = get_backend(CCAlg.MVCC)
    st = be.init_state(cfg)
    stats = init_device_stats(len(wl.txn_type_names))

    def epoch(db, st, stats, keys, is_write, ts):
        n = len(keys)
        q = YCSBQuery(keys=jnp.asarray(keys, jnp.int32),
                      is_write=jnp.asarray(is_write))
        p = wl.plan(db, q)
        batch = AccessBatch(
            table_ids=p["table_ids"], keys=p["keys"], is_read=p["is_read"],
            is_write=p["is_write"], valid=p["valid"],
            ts=jnp.asarray(ts, jnp.int32),
            rank=jnp.arange(n, dtype=jnp.int32),
            active=jnp.ones(n, bool))
        inc = build_incidence(batch, cfg.conflict_buckets, cfg.conflict_exact)
        v, st = be.validate(cfg, st, batch, inc)
        db = wl.execute(db, q, v.commit & batch.active, v.order, stats)
        return db, st, v, stats

    def f(key, ver):
        return int(np.asarray(_field_fingerprint(np.int32(key),
                                                 np.int32(ver))))

    def cks(stats):
        return int(np.asarray(stats["read_checksum"]))

    # epoch 1: blind write of key 5 at ts 10 -> value f(5, 10)
    db, st, v, stats = epoch(db, st, stats, [[5, 5]], [[True, True]], [10])
    assert np.asarray(v.commit)[0]
    # epoch 2: overwrite key 5 at ts 20 -> value f(5, 20); ring now holds
    # (wts=10, old=f(5,0)) and (wts=20, old=f(5,10))
    db, st, v, stats = epoch(db, st, stats, [[5, 5]], [[True, True]], [20])
    assert np.asarray(v.commit)[0]
    c0 = cks(stats)
    # epoch 3: three committed readers of key 5 —
    #   rw txn at ts 5   -> the pre-10 base version      f(5, 0)
    #   rw txn at ts 15  -> the version written at ts 10 f(5, 10)
    #   read-only txn    -> the live snapshot            f(5, 20) twice
    db, st, v, stats = epoch(
        db, st, stats,
        [[5, 7], [5, 9], [5, 5]],
        [[False, True], [False, True], [False, False]],
        [5, 15, 30])
    assert np.asarray(v.commit)[:3].all()
    got = (cks(stats) - c0) & 0xFFFFFFFF
    want = (f(5, 0) + f(5, 10) + 2 * f(5, 20)) & 0xFFFFFFFF
    assert got == want, f"stale reads returned wrong bytes: {got} != {want}"


# ---- MAAT --------------------------------------------------------------

def test_maat_reader_writer_any_rank_commit():
    # writer arrives first by rank; MAAT dynamically orders reader before it
    txns = [[(5, "w")], [(5, "r")]]
    v, _, b = run("MAAT", txns)
    c, a, d = check_verdict(v, b, txns)
    assert c.all()
    assert np.asarray(v.order)[1] < np.asarray(v.order)[0]

def test_maat_write_skew_cycle_aborts():
    txns = [[(1, "r"), (2, "w")], [(2, "r"), (1, "w")]]
    v, _, b = run("MAAT", txns)
    c, a, d = check_verdict(v, b, txns)
    assert a.any() and not c.all()

def test_maat_blind_ww_both_commit():
    txns = [[(5, "w")], [(5, "w")], [(5, "r")]]
    v, _, b = run("MAAT", txns)
    c, a, d = check_verdict(v, b, txns)
    assert c.all()

def test_maat_hot_key_rmw_clique_commits_winner():
    # round-2 liveness cliff (VERDICT r3 next #3): m txns RMW one hot
    # key form m*(m-1)/2 mutual pairs; the old fixed-budget cycle peel
    # aborted such cliques WHOLESALE — winners included — and MAAT
    # posted 0 txn/s on TPC-C warehouse rows.  The mutual-pair MIS
    # sweep must admit exactly the lex-first winner.
    m = 12
    txns = [[(7, "rw")] for _ in range(m)]
    v, _, b = run("MAAT", txns)
    c, a, d = check_verdict(v, b, txns)
    assert c[0] and c.sum() == 1
    assert a.sum() == m - 1 and d.sum() == 0

def test_maat_deep_acyclic_chain_commits_wholesale():
    # ADVICE r3 (medium): deep ACYCLIC chain middles used to be
    # misclassified as cycle members and aborted.  Cycle detection is
    # now self-reachability (exact) and acyclic order is ancestor count,
    # so a chain of ANY depth commits WHOLE — matching serial
    # validation, where real-valued ranges make any DAG feasible.
    cfg = CFG.replace(sweep_rounds=4)
    n = 16
    txns = [[(0, "r")]] + [[(i, "r"), (i - 1, "w")] for i in range(1, n)]
    v, _, b = run("MAAT", txns, cfg=cfg)
    c, a, d = check_verdict(v, b, txns)
    assert a.sum() == 0 and d.sum() == 0
    assert c.all()

def test_maat_cycle_peels_youngest_rest_commit():
    # pure 3-cycle (write-skew triangle, no mutual pairs): serial
    # validation commits the two earlier validators with a dynamic order
    # and closes only the latest one's range — the peel must abort
    # exactly the lex-youngest member, THIS epoch, no defers.
    txns = [[(10, "r"), (11, "w")],
            [(11, "r"), (12, "w")],
            [(12, "r"), (10, "w")]]
    v, _, b = run("MAAT", txns)
    c, a, d = check_verdict(v, b, txns)
    assert a.sum() == 1 and a[2]
    assert c[0] and c[1] and d.sum() == 0


# ---- CALVIN / TPU_BATCH ------------------------------------------------

@pytest.mark.parametrize("alg", ["CALVIN", "TPU_BATCH"])
def test_calvin_never_aborts_levels_chain(alg):
    txns = [[(5, "w")], [(5, "rw")], [(5, "r")], [(9, "r")]]
    v, _, b = run(alg, txns)
    c, a, d = check_verdict(v, b, txns, chained=True)
    assert not a.any()
    assert c.all()
    lv = np.asarray(v.level)
    assert lv[0] == 0 and lv[1] == 1 and lv[2] == 2 and lv[3] == 0

@pytest.mark.parametrize("alg", ["CALVIN", "TPU_BATCH"])
def test_calvin_deep_chain_defers_deterministically(alg):
    txns = [[(5, "rw")] for _ in range(10)]   # chain depth 10 > exec_subrounds
    v, _, b = run(alg, txns)
    c, a, d = check_verdict(v, b, txns, chained=True)
    assert not a.any()
    s = CFG.exec_subrounds
    assert c[:s].all() and d[s:].all()


# ---- NOCC + randomized cross-algorithm oracle --------------------------

def test_nocc_commits_everything():
    txns = [[(5, "w")], [(5, "w")], [(5, "rw")]]
    v, _, b = run("NOCC", txns)
    assert np.asarray(v.commit)[:3].all()

@pytest.mark.parametrize("alg", ["NO_WAIT", "WAIT_DIE", "OCC", "TIMESTAMP",
                                 "MVCC", "MAAT", "CALVIN", "TPU_BATCH",
                                 "DGCC"])
def test_randomized_serializability(alg):
    rng = np.random.default_rng(42)
    be = get_backend(alg)
    st = be.init_state(CFG)
    ts_base = 1
    for trial in range(6):
        txns = []
        for _ in range(12):
            script = []
            for _ in range(rng.integers(1, 5)):
                key = int(rng.integers(0, 8))       # tiny keyspace: hot
                mode = rng.choice(["r", "w", "rw"])
                script.append((key, mode))
            txns.append(script)
        ts = ts_base + rng.permutation(12).astype(np.int32)
        ts_base += 12
        v, st, b = run(alg, txns, state=st, ts=ts)
        check_verdict(v, b, txns, chained=be.chained)
        assert np.asarray(v.commit).sum() >= 1


# ---- isolation levels (reference config.h:102,337-340) -----------------

def _iso_cfg(level):
    return CFG.replace(isolation_level=level)


def test_isolation_serializable_reader_blocks_writer():
    # earlier pure reader of key 5 blocks a later writer under long locks
    v, _, _ = run("NO_WAIT", [[(5, "r")], [(5, "w")]])
    assert bool(v.commit[0]) and bool(v.abort[1])


@pytest.mark.parametrize("level", ["READ_COMMITTED", "READ_UNCOMMITTED"])
def test_isolation_relaxed_reader_does_not_block_writer(level):
    v, _, _ = run("NO_WAIT", [[(5, "r")], [(5, "w")]],
                  cfg=_iso_cfg(level))
    assert bool(v.commit[0]) and bool(v.commit[1])


def test_isolation_read_committed_reader_behind_writer_conflicts():
    # writer earlier in rank still holds the lock when the reader asks
    v, _, _ = run("NO_WAIT", [[(5, "w")], [(5, "r")]],
                  cfg=_iso_cfg("READ_COMMITTED"))
    assert bool(v.commit[0]) and bool(v.abort[1])


def test_isolation_read_uncommitted_only_ww_conflicts():
    v, _, _ = run("NO_WAIT", [[(5, "w")], [(5, "r")], [(5, "w")]],
                  cfg=_iso_cfg("READ_UNCOMMITTED"))
    assert bool(v.commit[0])
    assert bool(v.commit[1])      # read bypasses the lock table
    assert bool(v.abort[2])       # WW still conflicts


def test_isolation_nolock_commits_everything():
    v, _, _ = run("NO_WAIT", [[(5, "w")], [(5, "w")], [(5, "rw")]],
                  cfg=_iso_cfg("NOLOCK"))
    assert bool(np.asarray(v.commit)[:3].all())


def test_isolation_wait_die_relaxed_wait_rule_still_applies():
    # two writers, older arrives later in rank: waits instead of dying
    v, _, _ = run("WAIT_DIE", [[(5, "w")], [(5, "w")]],
                  ts=[2, 1], cfg=_iso_cfg("READ_UNCOMMITTED"))
    assert bool(v.commit[0]) and bool(v.defer[1])


def test_isolation_monotone_commit_counts():
    # same contended batch; commits must not decrease as isolation relaxes
    txns = [[(k % 3, "w" if i % 2 else "r")] for i, k in enumerate(range(8))]
    counts = []
    for lvl in ["SERIALIZABLE", "READ_COMMITTED", "READ_UNCOMMITTED", "NOLOCK"]:
        v, _, _ = run("NO_WAIT", txns, cfg=_iso_cfg(lvl))
        counts.append(int(np.asarray(v.commit).sum()))
    assert counts == sorted(counts)


# ---- distributed VOTE prepare classification ---------------------------

def test_mvcc_ro_hint_overrides_local_view():
    """VOTE-mode soundness: a cross-partition rw-txn whose writes live on
    another node must NOT take the read-only fast path locally — the
    global ro_hint (from the unmasked plan) forces read validation, so a
    recycled-version read still aborts (the review-found hole)."""
    import dataclasses
    be = get_backend("MVCC")
    st = be.init_state(CFG)
    for wts in (10, 20, 30, 40):
        v, st, _ = run("MVCC", [[(5, "w")]], ts=[wts], state=st)
    # locally: only the read of key 5 is owned (the write of key 6 is
    # masked invalid, as the vote prepare does for remote accesses)
    batch = make_batch([[(5, "r")]], ts=[5])
    batch = dataclasses.replace(batch,
                                ro_hint=jnp.zeros(CFG.epoch_batch, bool))
    inc = build_incidence(batch, CFG.conflict_buckets, CFG.conflict_exact)
    v, _ = be.validate(CFG, st, batch, inc)
    assert np.asarray(v.abort)[0]          # recycled version -> abort
    # without the hint the same local view looks read-only and commits
    batch2 = make_batch([[(5, "r")]], ts=[5])
    v2, _ = be.validate(CFG, st, batch2, inc)
    assert np.asarray(v2.commit)[0]


def test_to_watermark_width_no_false_aborts():
    """Wide watermark tables (watermark_buckets >> incidence buckets):
    uncontended TIMESTAMP traffic must not abort on bucket false sharing
    — the round-2 fidelity fix (the reference tracks per-row ts state;
    8k shared buckets at 32k accesses/epoch aborted >50% at theta=0)."""
    import jax
    from deneva_tpu.config import Config
    from deneva_tpu.engine import Engine
    from deneva_tpu.workloads import get_workload

    cfg = Config(cc_alg="TIMESTAMP", epoch_batch=256, conflict_buckets=512,
                 max_accesses=4, req_per_query=4, synth_table_size=1 << 16,
                 zipf_theta=0.0, max_txn_in_flight=1024)
    eng = Engine(cfg, get_workload(cfg))
    stats = jax.device_get(eng.jit_run(eng.init_state(seed=1), 30).stats)
    commits = int(stats["total_txn_commit_cnt"])
    aborts = int(stats["total_txn_abort_cnt"])
    assert commits > 0
    # uniform keys on 64k rows, 1k accesses/epoch, 1M watermark buckets:
    # real ts conflicts are rare and false sharing rarer
    assert aborts / max(commits + aborts, 1) < 0.05


def test_mvcc_value_ring_boundary_depth():
    """Round-5 review regression: the ts-only VersionRing must retain the
    FULL mvcc_his_len entries.  A servable read may have his_len-1
    overwrites postdating its ts (the decision ring's commit rule allows
    exactly that many), and the reconstruction reads the newest entry
    <= ts — one MORE retained entry than the old displaced-bytes ring
    needed.  With his_len=4: overwrites at ts 10/20/30/40, reader at 15
    commits and must see f(5, 10), not the load base f(5, 0)."""
    from deneva_tpu.config import WorkloadKind
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.workloads import get_workload
    from deneva_tpu.workloads.ycsb import (VER_TABLE, YCSBQuery,
                                           _field_fingerprint)

    cfg = Config(workload=WorkloadKind.YCSB, cc_alg=CCAlg.MVCC,
                 synth_table_size=1024, req_per_query=2, max_accesses=2,
                 epoch_batch=2, conflict_buckets=512,
                 max_txn_in_flight=2)
    wl = get_workload(cfg)
    db = wl.load()
    be = get_backend(CCAlg.MVCC)
    st = be.init_state(cfg)
    stats = init_device_stats(len(wl.txn_type_names))

    def epoch(db, st, stats, keys, is_write, ts):
        n = len(keys)
        q = YCSBQuery(keys=jnp.asarray(keys, jnp.int32),
                      is_write=jnp.asarray(is_write))
        p = wl.plan(db, q)
        batch = AccessBatch(
            table_ids=p["table_ids"], keys=p["keys"], is_read=p["is_read"],
            is_write=p["is_write"], valid=p["valid"],
            ts=jnp.asarray(ts, jnp.int32),
            rank=jnp.arange(n, dtype=jnp.int32),
            active=jnp.ones(n, bool))
        inc = build_incidence(batch, cfg.conflict_buckets, cfg.conflict_exact)
        v, st = be.validate(cfg, st, batch, inc)
        db = wl.execute(db, q, v.commit & batch.active, v.order, stats)
        return db, st, v, stats

    for wts in (10, 20, 30, 40):          # his_len=4 overwrites of key 5
        db, st, v, stats = epoch(db, st, stats, [[5, 5]],
                                 [[True, True]], [wts])
        assert np.asarray(v.commit)[0]
    c0 = int(np.asarray(stats["read_checksum"]))
    # reader at ts 15: 3 = his_len-1 overwrites (20/30/40) postdate it;
    # the needed v*=10 entry must still be retained
    db, st, v, stats = epoch(db, st, stats, [[5, 7]],
                             [[False, True]], [15])
    assert np.asarray(v.commit)[0], "decision ring must serve ts 15"
    got = (int(np.asarray(stats["read_checksum"])) - c0) & 0xFFFFFFFF
    want = int(np.asarray(_field_fingerprint(np.int32(5),
                                             np.int32(10))))
    assert got == want, f"boundary-depth read got {got} != f(5,10)={want}"


def test_timestamp_staleness_abort_after_queueing_age():
    """The theta=0.7-cliff mechanism, scripted (BASELINE round-5 note): a
    txn stamped at admission but validated epochs later aborts iff some
    NEWER-ts txn committed its key meanwhile — the cross-epoch watermark
    staleness term that lock backends don't have.  Epoch 1: writer W2
    (ts 20) commits key 5.  Epoch 2: aged reader R (ts 15, stamped before
    W2 but queued behind it) must watermark-abort its read of key 5,
    while a fresh reader (ts 30) sails through; same for writers."""
    be = get_backend("TIMESTAMP")
    st = be.init_state(CFG)
    v, st, _ = run("TIMESTAMP", [[(5, "w")]], ts=[20], state=st)
    assert np.asarray(v.commit)[0]
    # aged reader (15 < 20) + fresh reader (30 > 20), one epoch later
    v, st, _ = run("TIMESTAMP", [[(5, "r")], [(5, "r")]],
                   ts=[15, 30], state=st)
    assert np.asarray(v.abort)[0], "aged reader must hit wts>ts"
    assert np.asarray(v.commit)[1], "fresh reader unaffected"
    # aged writer aborts on BOTH watermarks; fresh writer commits
    v, st, _ = run("TIMESTAMP", [[(5, "w")], [(5, "w")]],
                   ts=[18, 40], state=st)
    assert np.asarray(v.abort)[0], "aged writer must hit wts>ts"
    assert np.asarray(v.commit)[1]
