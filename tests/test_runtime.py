"""Distributed runtime integration tests (SURVEY §4.4: the reference's
de-facto integration test is N servers + M clients as processes on one box
over IPC; same rig here via `runtime.launch.run_cluster`).

Each test boots a real multi-process cluster: native transport mesh,
INIT_DONE barrier, client open loop with inflight throttle, per-epoch
EPOCH_BLOB exchange, deterministic merged validation, partitioned
execution, CL_RSP acks, SHUTDOWN protocol, per-node [summary] lines.
"""

import numpy as np
import pytest

from deneva_tpu.config import Config, CCAlg, WorkloadKind
from deneva_tpu.stats import parse_summary


def small_cfg(**kw):
    base = dict(
        workload=WorkloadKind.YCSB, cc_alg=CCAlg.CALVIN,
        epoch_batch=128, conflict_buckets=512, synth_table_size=4096,
        max_txn_in_flight=1024, req_per_query=4, max_accesses=4,
        zipf_theta=0.6, warmup_secs=0.5, done_secs=1.5)
    base.update(kw)
    return Config(**base)


def boot(cfg, **kw):
    from deneva_tpu.runtime.launch import run_cluster
    return run_cluster(cfg, platform="cpu", **kw)


@pytest.mark.slow
def test_cluster_2s1c_calvin_commits_and_agrees():
    cfg = small_cfg(node_cnt=2, client_node_cnt=1)
    out = boot(cfg)
    assert set(out) == {0, 1, 2}
    s0 = parse_summary(out[0][1])
    s1 = parse_summary(out[1][1])
    cl = parse_summary(out[2][1])
    # deterministic replicated validation: identical global commit counts
    assert s0["total_txn_commit_cnt"] == s1["total_txn_commit_cnt"] > 0
    assert s0["epoch_cnt"] == s1["epoch_cnt"]
    # Calvin never aborts (reference: deterministic locks queue, never refuse)
    assert s0["total_txn_abort_cnt"] == 0
    # client measured end-to-end latency for completed txns, with
    # per-txn-type percentile families (VERDICT r3 next #6)
    assert cl["txn_cnt"] > 0
    assert cl["client_client_latency_p50"] > 0
    assert cl["ycsb_rw_latency_p50"] > 0
    # server-side TxnStats decomposition: every committed txn reports
    # its restart/wait counts (CALVIN: zero retries by construction)
    assert s0["txn_retries_p99"] == 0 and "txn_waits_p99" in s0


@pytest.mark.slow
def test_cluster_no_wait_aborts_and_recovers():
    cfg = small_cfg(node_cnt=2, client_node_cnt=1, cc_alg=CCAlg.NO_WAIT,
                    zipf_theta=0.9, synth_table_size=1024)
    out = boot(cfg)
    s0 = parse_summary(out[0][1])
    s1 = parse_summary(out[1][1])
    assert s0["total_txn_commit_cnt"] == s1["total_txn_commit_cnt"] > 0
    # high contention: the abort/backoff/retry path must actually fire
    assert s0["total_txn_abort_cnt"] == s1["total_txn_abort_cnt"] > 0
    assert parse_summary(out[2][1])["txn_cnt"] > 0


@pytest.mark.slow
def test_cluster_3s2c_tpu_batch():
    cfg = small_cfg(node_cnt=3, client_node_cnt=2, cc_alg=CCAlg.TPU_BATCH,
                    synth_table_size=4098)
    out = boot(cfg)
    commits = [parse_summary(out[s][1])["total_txn_commit_cnt"]
               for s in range(3)]
    assert commits[0] == commits[1] == commits[2] > 0
    # both clients served
    assert parse_summary(out[3][1])["txn_cnt"] > 0
    assert parse_summary(out[4][1])["txn_cnt"] > 0


@pytest.mark.slow
def test_cluster_2s1c_tpcc_partitioned():
    """TPC-C over 2 partitioned server nodes (warehouse -> node, reference
    wh_to_part): commits agree, cross-warehouse payments/orders split
    across owners without 2PC."""
    cfg = Config(
        workload=WorkloadKind.TPCC, cc_alg=CCAlg.CALVIN,
        node_cnt=2, client_node_cnt=1,
        num_wh=4, cust_per_dist=64, max_items=128, max_items_per_txn=5,
        insert_table_cap=1 << 12,
        epoch_batch=64, conflict_buckets=512, max_accesses=8,
        max_txn_in_flight=512, warmup_secs=0.5, done_secs=1.5)
    out = boot(cfg)
    s0 = parse_summary(out[0][1])
    s1 = parse_summary(out[1][1])
    assert s0["total_txn_commit_cnt"] == s1["total_txn_commit_cnt"] > 0
    assert parse_summary(out[2][1])["txn_cnt"] > 0


@pytest.mark.slow
def test_cluster_2s1c_pps_partitioned():
    """PPS over 2 partitioned nodes: recon against the replicated
    USES/SUPPLIES maps stays local, commits agree across servers."""
    cfg = Config(
        workload=WorkloadKind.PPS, cc_alg=CCAlg.CALVIN,
        node_cnt=2, client_node_cnt=1,
        pps_parts_cnt=500, pps_products_cnt=100, pps_suppliers_cnt=100,
        pps_parts_per=4,
        epoch_batch=64, conflict_buckets=512, max_accesses=16,
        max_txn_in_flight=512, warmup_secs=0.5, done_secs=1.5)
    out = boot(cfg)
    s0 = parse_summary(out[0][1])
    s1 = parse_summary(out[1][1])
    assert s0["total_txn_commit_cnt"] == s1["total_txn_commit_cnt"] > 0
    assert parse_summary(out[2][1])["txn_cnt"] > 0


@pytest.mark.slow
def test_dead_peer_detected_fast():
    """Failure detection (SURVEY §5.3 — the reference has none and would
    hang): a server whose peer dies mid-run must raise naming the peer,
    long before the 60s blob timeout."""
    import threading
    import time as _time
    import jax
    jax.config.update("jax_platforms", "cpu")
    from deneva_tpu.runtime.native import ipc_endpoints
    from deneva_tpu.runtime.server import ServerNode

    cfg = small_cfg(node_cnt=2, client_node_cnt=0, done_secs=30.0,
                    synth_table_size=4096)
    eps = ipc_endpoints(2, "deadpeer")
    err: dict = {}

    def run_a():
        node = ServerNode(cfg.replace(node_id=0, part_cnt=2), eps, "cpu")
        t0 = _time.monotonic()
        try:
            node.run()
        except RuntimeError as e:
            err["msg"] = str(e)
            err["secs"] = _time.monotonic() - t0
        finally:
            node.close()

    def run_b():
        node = ServerNode(cfg.replace(node_id=1, part_cnt=2), eps, "cpu")
        node.barrier()          # join the mesh, then die without a word
        node.close()

    ta = threading.Thread(target=run_a)
    tb = threading.Thread(target=run_b)
    ta.start(); tb.start()
    tb.join(timeout=60)
    ta.join(timeout=60)
    assert "msg" in err, "server 0 never noticed the dead peer"
    assert "died" in err["msg"] and "[1]" in err["msg"]
    assert err["secs"] < 30, f"detection took {err['secs']:.1f}s"


def test_replica_barrier_timeout_and_clean_close(tmp_path):
    """ReplicaNode.barrier timeout path (previously untested): a peer
    that joins the mesh but never sends INIT_DONE must trip the bounded
    TimeoutError naming the replica, and close() afterwards must release
    the log file handle AND the transport in that order, idempotently —
    teardown after a failed barrier may not leak the open log or hang."""
    import os
    import threading

    from deneva_tpu.runtime.native import NativeTransport, ipc_endpoints
    from deneva_tpu.runtime.replica import ReplicaNode

    # layout [1 server | 0 clients | 1 replica]: replica is node 1
    cfg = small_cfg(node_cnt=1, client_node_cnt=0, replica_cnt=1,
                    node_id=1, logging=True,
                    log_dir=str(tmp_path)).validate()
    eps = ipc_endpoints(2, f"replbar_{os.getpid()}")
    peer_box: dict = {}

    def run_peer():
        # joins the mesh so both dt_starts complete, then stays silent
        tp = NativeTransport(0, eps, 2)
        tp.start()
        peer_box["tp"] = tp
        peer_box["ev"].wait(30)
        tp.close()

    peer_box["ev"] = threading.Event()
    t = threading.Thread(target=run_peer)
    t.start()
    node = ReplicaNode(cfg, eps)
    node.setup_wait_s = 0.8     # the barrier's wait; the dial has passed
    try:
        with pytest.raises(TimeoutError, match="replica 1"):
            node.barrier()
    finally:
        node.close()
        peer_box["ev"].set()
        t.join(timeout=30)
    # close ordering: the log handle is released (no dangling fsync
    # target) and a second close is a no-op, not a crash
    assert node._f.closed
    node.close()


@pytest.mark.slow
def test_client_load_rate_throttles():
    """LOAD_RATE mode (reference `config.h:21-22`, client_thread.cpp:35-41):
    a fixed txn/s budget must cap the send rate well below saturation."""
    cfg = small_cfg(node_cnt=1, client_node_cnt=1, load_rate=2000,
                    warmup_secs=0.3, done_secs=2.0)
    out = boot(cfg)
    cl = parse_summary(out[1][1])
    # ~2000 txn/s over the ~3s client lifetime, chunked sends => bound
    # generously above budget (one batch of slack) but far below the
    # saturated rate
    assert cl["sent_cnt"] <= 2000 * cl["total_runtime"]         + 2 * cfg.client_batch_size


@pytest.fixture(scope="module")
def admission_node():
    """One single-server node per backend, shared by the admission cases
    (construction loads the table; the cases only drive the queues)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from deneva_tpu.runtime.native import ipc_endpoints
    from deneva_tpu.runtime.server import ServerNode

    nodes: dict = {}

    def get(alg):
        if alg not in nodes:
            cfg = small_cfg(node_cnt=1, part_cnt=1, client_node_cnt=0,
                            cc_alg=alg)
            nodes[alg] = ServerNode(
                cfg, ipc_endpoints(1, f"adm_{alg.name}"), "cpu")
        node = nodes[alg]
        node.pending.clear()
        node.retry.items.clear()
        node._queue_txns = 0
        return node

    yield get
    for node in nodes.values():
        node.close()


def _four_txns(tag0=0):
    from deneva_tpu.runtime import wire
    return wire.QueryBlock(
        keys=np.arange(16, dtype=np.int32).reshape(4, 4) + 1,
        types=np.ones((4, 4), np.int8),
        scalars=np.zeros((4, 0), np.int32),
        tags=np.arange(4, dtype=np.int64) + tag0)


@pytest.mark.parametrize("alg, aborted, keeps", [
    (CCAlg.WAIT_DIE, True, True),     # fresh_ts_on_restart = False
    (CCAlg.OCC, True, False),         # an aborted restart is re-stamped
    (CCAlg.TIMESTAMP, False, True),   # a deferred waiter keeps its own
])
def test_admission_birth_ts_rule(admission_node, alg, aborted, keeps):
    """The stamping rule of the served admission path, driven directly:
    WAIT_DIE keeps a restart's birth timestamp (its starvation-freedom;
    reference worker_thread.cpp:492-508), a fresh-ts backend re-stamps
    an ABORTED restart, and a deferred (waiting) txn keeps its own like
    the in-process pool and the reference's parked requests."""
    node = admission_node(alg)
    birth = np.array([7, 9, 11, 13], np.int64)
    node.retry.push(_four_txns(), np.full(4, int(aborted), np.int32),
                    birth, epoch=0, aborted=np.full(4, aborted, bool))
    fs = node._feed_acquire()
    block, cnt, ts, dfc = node._contribution_into(5, fs, 0)
    assert len(block) == len(cnt) == len(ts) == len(dfc) == 4
    if keeps:
        assert (ts == birth).all()
    else:
        # epoch-anchored: (epoch + 1) * b_merged + position
        assert (ts == 6 * node.b_merged + np.arange(4)).all()
    assert fs["active"][0, :4].all() and not fs["active"][0, 4:].any()
    assert (fs["ts"][0, :4] == ts).all()


def test_admission_reused_feed_buffer_tail_reads_zero(admission_node):
    """Unfilled lanes of a REUSED feed buffer are zero and inactive, so
    every node builds the same feed (and log) bytes whatever the buffer
    held before: fill row 0 from the clients' queue, recycle the set,
    then admit four txns into it."""
    node = admission_node(CCAlg.OCC)
    b = node.b_loc
    rng = np.random.default_rng(0)
    from deneva_tpu.runtime import wire
    full = wire.QueryBlock(
        keys=rng.integers(1, 4096, (b, 4)).astype(np.int32),
        types=np.full((b, 4), 2, np.int8),
        scalars=np.zeros((b, 0), np.int32),
        tags=np.arange(b, dtype=np.int64) + 1)
    node.pending.append((1, full))
    node._queue_txns = b
    fs = node._feed_acquire()
    blk, _, ts, _ = node._contribution_into(0, fs, 0)
    assert len(blk) == b and (blk.tags >> 40 == 1).all()
    assert (ts == node.b_merged + np.arange(b)).all()
    assert fs["keys"][0].all() and fs["active"][0].all()
    node._feed_free.append(fs)
    again = node._feed_acquire()
    assert again is fs
    node.pending.append((1, _four_txns(tag0=1000)))
    node._queue_txns = 4
    blk, _, ts, _ = node._contribution_into(1, again, 0)
    assert len(blk) == 4
    for name in ("keys", "types", "scal", "tags", "ts"):
        assert not again[name][0, 4:].any(), name
    assert again["active"][0, :4].all()
    assert not again["active"][0, 4:].any()
    assert node._queue_txns == 0


@pytest.mark.parametrize("epoch, birth, what", [
    (2**31 // 128, 7, "horizon exceeded"),   # a stamp past 2^31
    (5, 0, "below 1"),                       # ts 0 is MVCC's sentinel
])
def test_admission_stamp_invariants_raise(admission_node, epoch, birth,
                                          what):
    """Both stamping invariants fire: the 2^31 birth-timestamp horizon,
    and ts >= 1 (a restart that kept a birth ts of 0)."""
    node = admission_node(CCAlg.WAIT_DIE)
    assert node.b_merged == 128
    node.retry.push(_four_txns(), np.ones(4, np.int32),
                    np.full(4, birth, np.int64), epoch=0,
                    aborted=np.ones(4, bool))
    with pytest.raises(RuntimeError, match=what):
        node._contribution_into(epoch, node._feed_acquire(), 0)


@pytest.mark.slow
def test_wait_die_preserves_birth_ts_across_restarts():
    """WAIT_DIE starvation-freedom: a restarted txn must keep its birth
    timestamp (reference preserves them, worker_thread.cpp:492-508);
    fresh-ts backends re-stamp ABORTED restarts only — deferred waiters
    keep their birth ts like the in-process pool and the reference's
    parked requests.  Driven directly through the server's admission
    path."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from deneva_tpu.runtime import wire
    from deneva_tpu.runtime.native import ipc_endpoints
    from deneva_tpu.runtime.server import ServerNode

    def probe(alg, aborted):
        cfg = small_cfg(node_cnt=1, part_cnt=1, client_node_cnt=0,
                        cc_alg=alg)
        node = ServerNode(cfg, ipc_endpoints(1, f"tspin_{alg}_{aborted}"),
                          "cpu")
        try:
            blk = wire.QueryBlock(
                keys=np.zeros((4, 4), np.int32),
                types=np.ones((4, 4), np.int8),
                scalars=np.zeros((4, 0), np.int32),
                tags=np.arange(4, dtype=np.int64))
            birth = np.array([7, 9, 11, 13], np.int64)
            node.retry.push(blk, np.full(4, int(aborted), np.int32), birth,
                            epoch=0, aborted=np.full(4, aborted, bool))
            _, _, ts, _ = node._contribution_into(
                5, node._feed_acquire(), 0)
            return birth, ts
        finally:
            node.close()

    birth, ts = probe(CCAlg.WAIT_DIE, aborted=True)  # fresh_ts=False
    assert (ts[:4] == birth).all(), "WAIT_DIE restart lost its birth ts"
    birth, ts = probe(CCAlg.OCC, aborted=True)       # fresh_ts=True
    assert not (ts[:4] == birth).any(), "OCC abort-restart kept a stale ts"
    birth, ts = probe(CCAlg.TIMESTAMP, aborted=False)  # deferred waiter
    assert (ts[:4] == birth).all(), \
        "a deferred (waiting) txn must keep its birth ts"


@pytest.mark.slow
def test_wait_die_cluster_commits_agree():
    """WAIT_DIE over the full cluster under heavy contention: the blob-
    carried timestamps keep every node's verdicts identical."""
    cfg = small_cfg(node_cnt=2, client_node_cnt=1, cc_alg=CCAlg.WAIT_DIE,
                    zipf_theta=0.95, synth_table_size=512)
    out = boot(cfg)
    s0 = parse_summary(out[0][1])
    s1 = parse_summary(out[1][1])
    assert s0["total_txn_commit_cnt"] == s1["total_txn_commit_cnt"] > 0
    # WAIT_DIE under contention must actually wait (defer) and/or die
    assert s0["defer_cnt"] + s0["total_txn_abort_cnt"] > 0


@pytest.mark.slow
def test_cluster_tcp_transport():
    """TCP transport mode (reference TPORT_TYPE TCP, config.h:335):
    same cluster protocol over loopback TCP sockets."""
    cfg = small_cfg(node_cnt=2, client_node_cnt=1, tport_type="tcp")
    out = boot(cfg)
    s0 = parse_summary(out[0][1])
    s1 = parse_summary(out[1][1])
    assert s0["total_txn_commit_cnt"] == s1["total_txn_commit_cnt"] > 0
    assert parse_summary(out[2][1])["txn_cnt"] > 0


@pytest.mark.slow
def test_cluster_abort_mode_forces_and_completes():
    """YCSB_ABORT_MODE in the distributed runtime: forced aborts are
    counted identically on every server, forced txns complete (client
    gets acked, no immortal retries) and commits keep flowing."""
    cfg = small_cfg(node_cnt=2, client_node_cnt=1, cc_alg=CCAlg.TPU_BATCH,
                    ycsb_abort_mode=True, zipf_theta=0.9,
                    synth_table_size=8192)
    out = boot(cfg)
    s0 = parse_summary(out[0][1])
    s1 = parse_summary(out[1][1])
    assert s0["total_txn_abort_cnt"] == s1["total_txn_abort_cnt"] > 0
    assert s0["total_txn_commit_cnt"] == s1["total_txn_commit_cnt"] > 0
    assert parse_summary(out[2][1])["txn_cnt"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("alg", [CCAlg.OCC, CCAlg.TIMESTAMP, CCAlg.MVCC])
def test_cluster_vote_protocol_agrees(alg):
    """Batched 2PC (VOTE): each server validates only its partition's
    accesses against local state and the epoch vote exchange decides —
    the coordination shape of the reference's RPREPARE/RACK_PREP
    (system/txn.cpp:498-606), batched.  Global decisions are the same
    AND/OR on every node, so commit counts must agree."""
    cfg = small_cfg(node_cnt=2, client_node_cnt=1, cc_alg=alg,
                    zipf_theta=0.8, synth_table_size=2048)
    assert cfg.dist_protocol == "auto"   # auto routes lock/ts/occ to VOTE
    out = boot(cfg)
    s0 = parse_summary(out[0][1])
    s1 = parse_summary(out[1][1])
    assert s0["total_txn_commit_cnt"] == s1["total_txn_commit_cnt"] > 0
    # partitioned validation under contention must exercise the abort path
    assert s0["total_txn_abort_cnt"] == s1["total_txn_abort_cnt"]
    assert parse_summary(out[2][1])["txn_cnt"] > 0


@pytest.mark.slow
def test_cluster_maat_vote_negotiates_positions():
    """Distributed MAAT (VERDICT r3 next #4): explicit --dist_protocol=
    vote routes MAAT through partition-local validation with per-txn
    position bounds piggybacked on the votes (the reference's
    `[lower,upper)` RACK_PREP range negotiation, maat.cpp:176-190) and a
    verify round that catches cross-node cycles.  Both servers must
    reach identical global decisions and commit under contention."""
    cfg = small_cfg(node_cnt=2, client_node_cnt=1, cc_alg=CCAlg.MAAT,
                    dist_protocol="vote", zipf_theta=0.8,
                    synth_table_size=2048)
    out = boot(cfg)
    s0 = parse_summary(out[0][1])
    s1 = parse_summary(out[1][1])
    assert s0["total_txn_commit_cnt"] == s1["total_txn_commit_cnt"] > 0
    assert s0["total_txn_abort_cnt"] == s1["total_txn_abort_cnt"]
    assert parse_summary(out[2][1])["txn_cnt"] > 0


def test_maat_vote_steps_single_node_equals_merged():
    """Unit-level equivalence (the VERDICT's bar): at node_cnt=1 the
    owner mask covers every access, so the vote path's local prepare IS
    merged validation, the intersected positions are the node's own
    locally-consistent order, and the verify round finds no violated
    edge — verdicts must match validate_maat exactly."""
    import jax.numpy as jnp
    from deneva_tpu.cc import AccessBatch, build_conflict_incidence, \
        get_backend
    from deneva_tpu.engine.epoch import make_vote_steps
    from deneva_tpu.workloads import get_workload

    cfg = small_cfg(node_cnt=1, cc_alg=CCAlg.MAAT, dist_protocol="vote",
                    zipf_theta=0.9, synth_table_size=256,
                    epoch_batch=32, req_per_query=4, max_accesses=4)
    wl = get_workload(cfg)
    be = get_backend(cfg.cc_alg)
    db = wl.load()
    import jax
    q = wl.generate(jax.random.PRNGKey(5), 32)
    active = jnp.ones(32, bool)
    ts = jnp.arange(1, 33, dtype=jnp.int32)
    vote, check, _apply = make_vote_steps(cfg, wl, be)
    vc, va, vd, lo = vote(db, be.init_state(cfg), q, active, ts)
    # merged-mode reference verdict on the identical batch
    p = wl.plan(db, q)
    batch = AccessBatch(
        table_ids=p["table_ids"], keys=p["keys"], is_read=p["is_read"],
        is_write=p["is_write"], valid=p["valid"], ts=ts,
        rank=jnp.arange(32, dtype=jnp.int32), active=active)
    inc = build_conflict_incidence(cfg, be, batch, p.get("order_free"))
    verdict, _ = be.validate(cfg, be.init_state(cfg), batch, inc)
    assert (np.asarray(vc) == np.asarray(verdict.commit)).all()
    assert (np.asarray(va) == np.asarray(verdict.abort)).all()
    assert (np.asarray(vd) == np.asarray(verdict.defer)).all()
    # the verify round must pass vacuously on the committed candidates
    order = np.asarray(lo).astype(np.int64) * 32 + np.arange(32)
    ab2 = check(db, q, vc, ts, jnp.asarray(order.astype(np.int32)))
    assert not np.asarray(ab2).any()


def test_maat_vote_detects_cross_node_write_skew():
    """The verify round is exactly the reference's range-intersection
    abort: a write-skew cycle split across two owners is invisible to
    both local validations, but the intersected positions cannot satisfy
    both nodes' edges — one txn's range closes (maat.cpp:176-190)."""
    import jax.numpy as jnp
    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine.epoch import make_vote_steps
    from deneva_tpu.workloads import get_workload
    from deneva_tpu.workloads.ycsb import YCSBQuery

    base = small_cfg(node_cnt=2, cc_alg=CCAlg.MAAT, dist_protocol="vote",
                     synth_table_size=256, epoch_batch=2,
                     req_per_query=2, max_accesses=2)
    be = get_backend(base.cc_alg)
    # txn0: r(k0) w(k1); txn1: r(k1) w(k0) — k0 owned by node0, k1 node1
    k0, k1 = 2, 3
    q = YCSBQuery(
        keys=jnp.asarray([[k0, k1], [k1, k0]], jnp.int32),
        is_write=jnp.asarray([[False, True], [False, True]]))
    active = jnp.ones(2, bool)
    ts = jnp.asarray([1, 2], jnp.int32)
    votes, checks = [], []
    for me in (0, 1):
        cfg = base.replace(node_id=me, part_cnt=2)
        wl = get_workload(cfg)
        db = wl.load()
        vote, check, _apply = make_vote_steps(cfg, wl, be)
        vc, va, vd, lo = vote(db, be.init_state(cfg), q, active, ts)
        votes.append((np.asarray(vc), np.asarray(va), np.asarray(lo)))
        checks.append((check, db, wl))
    # both local validations see only their half: everyone prepares yes
    for vc, va, _ in votes:
        assert vc.all() and not va.any()
    # server-side combine: AND votes, MAX bounds, verify, OR the aborts
    commit_g = votes[0][0] & votes[1][0]
    glo = np.maximum(votes[0][2], votes[1][2])
    order = glo.astype(np.int64) * 2 + np.arange(2)
    ab = np.zeros(2, bool)
    for check, db, _wl in checks:
        ab |= np.asarray(check(db, q, jnp.asarray(commit_g), ts,
                               jnp.asarray(order.astype(np.int32))))
    commit_g &= ~ab
    assert ab.sum() == 1 and commit_g.sum() == 1


# epochs a VOTE run of `_drive_overlap_run` lasts: TIMESTAMP's waiters
# restart free and its aborts back off 1, 2, 4, ... epochs, which drains
# the 256 txns inside it (NO_WAIT's synchronised restarts want ~1000)
_VOTE_EPOCHS = 96


def _drive_overlap_run(tmp_path, overlap: bool, vote: bool = False) -> dict:
    """One deterministic single-server cluster run (+ 1 replica, with the
    test posing as the client): every query batch is delivered BEFORE the
    INIT_DONE barrier (per-link FIFO puts them all in the server's
    pending queue ahead of epoch 0) and warmup/done are zero, so the
    measure/stop epochs pin to the 3C group boundary — admission, epochs
    and verdicts are a pure function of the config, which is what makes
    the overlap-on and overlap-off runs byte-comparable.

    ``vote`` runs the server under the VOTE protocol instead (C = K = 1,
    a synchronous host round trip an epoch): no log and no replica (a
    vote run's log is not its replay), and the stop epoch rides in with
    the batches, far enough out that every restart has its turn."""
    import os
    import threading
    import time as _time
    import uuid

    import jax
    jax.config.update("jax_platforms", "cpu")
    from deneva_tpu.runtime import wire
    from deneva_tpu.runtime.logger import state_digest
    from deneva_tpu.runtime.native import NativeTransport, ipc_endpoints
    from deneva_tpu.runtime.replica import ReplicaNode
    from deneva_tpu.runtime.server import ServerNode
    from deneva_tpu.workloads import get_workload

    log_dir = str(tmp_path / f"logs_overlap_{overlap}")
    cfg = small_cfg(node_cnt=1, client_node_cnt=1,
                    cc_alg=CCAlg.TIMESTAMP if vote else CCAlg.NO_WAIT,
                    zipf_theta=0.9, synth_table_size=512, epoch_batch=64,
                    pipeline_epochs=2, pipeline_groups=2, logging=not vote,
                    replica_cnt=0 if vote else 1, log_dir=log_dir,
                    dist_protocol="vote" if vote else "auto",
                    warmup_secs=0.0, done_secs=0.0,
                    host_overlap="on" if overlap else "off",
                    # arm the thread-ownership runtime asserts on BOTH
                    # sides: with overlap on, the wire/retire workers run
                    # for real against the guards (any worker-side
                    # mutation of dispatch-owned state raises), and the
                    # on==off byte-compare doubles as proof the guards
                    # themselves change nothing
                    owner_check=True)
    n_all = 2 if vote else 3
    eps = ipc_endpoints(n_all, uuid.uuid4().hex[:8])
    wl = get_workload(cfg)
    batches = []
    for s in range(4):          # 256 txns, distinct tag ranges
        q = wl.generate(jax.random.PRNGKey(100 + s), 64)
        k, t, sc = wl.to_wire(q)
        batches.append((np.arange(64, dtype=np.int64) + 64 * s, k, t, sc))

    out: dict = {}

    def run_server():
        node = ServerNode(cfg.replace(node_id=0, part_cnt=1), eps, "cpu")
        try:
            # the workers exist where asked for, never under VOTE
            assert node._overlap == (overlap and not vote)
            assert node.vote_mode == vote
            node.run()
            out["digest"] = state_digest(node.db)
            out["commits"] = int(jax.device_get(
                node.dev_stats["total_txn_commit_cnt"]))
        except Exception as e:      # surface instead of hanging the test
            out["err"] = repr(e)
        finally:
            node.close()

    def run_replica():
        node = ReplicaNode(cfg.replace(node_id=2, part_cnt=1), eps)
        try:
            node.run()
        finally:
            node.close()

    ts_srv = threading.Thread(target=run_server)
    ts_rep = threading.Thread(target=run_replica)
    ts_srv.start()
    if not vote:
        ts_rep.start()
    cl = NativeTransport(1, eps, n_all)
    cl.start()
    acked: list[int] = []
    try:
        for tags, k, t, sc in batches:
            cl.sendv(0, "CL_QRY_BATCH", wire.qry_block_parts(tags, k, t, sc))
        if vote:
            cl.send(0, "SHUTDOWN", wire.encode_shutdown(_VOTE_EPOCHS))
        cl.flush()

        def on_other(src, rtype, payload):
            if rtype == "CL_RSP":
                acked.extend(wire.decode_cl_rsp(payload).tolist())

        wire.run_barrier(cl, 1, n_all, on_other, "overlap-test client",
                         300.0)
        t0 = _time.monotonic()
        stopped = False
        while not stopped and _time.monotonic() - t0 < 300:
            m = cl.recv(50_000)
            if m is None:
                continue
            if m[1] == "CL_RSP":
                acked.extend(wire.decode_cl_rsp(m[2]).tolist())
            elif m[1] == "SHUTDOWN":
                stopped = True
        assert stopped, "server never announced SHUTDOWN"
    finally:
        ts_srv.join(timeout=300)
        if not vote:
            ts_rep.join(timeout=60)
        cl.close()
    assert "err" not in out, out["err"]
    out["acked"] = sorted(acked)
    out["sent"] = sorted(int(t) for b in batches for t in b[0])
    if vote:
        return out
    with open(os.path.join(log_dir, "node0.log.bin"), "rb") as f:
        out["log"] = f.read()
    with open(os.path.join(log_dir, "replica2.log.bin"), "rb") as f:
        out["rlog"] = f.read()
    return out


def test_host_overlap_bit_identical(tmp_path):
    """The property the host path rests on: host_overlap=off (the wire
    and retire bodies called inline on the dispatch thread) and =on
    (the same bodies on their worker threads) must produce bit-identical
    command logs, byte-identical replica logs, identical replayed-state
    digests and the same acked-tag multiset — the THREADING changes
    nothing — under a backend that aborts and retries (NO_WAIT at zipf
    0.9), so the retirement->admission feedback path is exercised, not
    just the happy path."""
    on = _drive_overlap_run(tmp_path, True)
    off = _drive_overlap_run(tmp_path, False)
    assert len(on["log"]) > 0
    assert on["log"] == off["log"]
    assert on["rlog"] == off["rlog"]
    # replica stream is a byte prefix of the primary's log by construction
    assert on["rlog"] == on["log"][:len(on["rlog"])] and len(on["rlog"])
    assert on["digest"] == off["digest"]
    assert on["commits"] == off["commits"] > 0
    assert on["acked"] == off["acked"] and len(on["acked"]) > 0


def test_vote_mode_served_loop_in_thread(tmp_path):
    """The VOTE protocol through the served loop, in tier 1: one server
    (so the vote exchange has no peer to wait for, and the loop, the
    admission, the feed and the retirement are what runs), asked for
    worker threads and given none — its epoch is a synchronous host
    round trip.  It commits, every sent tag is acked exactly once, and
    a second run of the same batches ends on the same table."""
    a = _drive_overlap_run(tmp_path, True, vote=True)
    b = _drive_overlap_run(tmp_path, True, vote=True)
    assert a["commits"] > 0
    assert a["acked"] == a["sent"]
    assert (a["digest"], a["commits"], a["acked"]) == \
        (b["digest"], b["commits"], b["acked"])


@pytest.mark.slow
def test_cluster_merged_protocol_still_available():
    """--dist_protocol=merged forces the round-1 replicated-validation
    mode for a non-deterministic backend (the semantics-only comparison
    point next to VOTE's distributed behavior)."""
    cfg = small_cfg(node_cnt=2, client_node_cnt=1, cc_alg=CCAlg.OCC,
                    dist_protocol="merged")
    out = boot(cfg)
    s0 = parse_summary(out[0][1])
    s1 = parse_summary(out[1][1])
    assert s0["total_txn_commit_cnt"] == s1["total_txn_commit_cnt"] > 0
