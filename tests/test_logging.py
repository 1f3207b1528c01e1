"""Logging / replication / replay (reference `system/logger.*` + SURVEY §5.4).

The reference's logger is write-only (no recovery path); here the command
log replays by deterministic re-execution, so the tests can assert the
strongest property available: replayed state == live state, bit for bit.
"""

import os

import numpy as np
import pytest

from deneva_tpu.config import Config, CCAlg, WorkloadKind
from deneva_tpu.runtime.logger import pack_record, unpack_records
from deneva_tpu.stats import parse_summary


def test_log_record_roundtrip_and_torn_tail():
    act = np.array([1, 0, 1, 1, 0, 0, 0, 1, 1], bool)
    rec = pack_record(7, b"payload-bytes", act)
    rec2 = pack_record(8, b"second", np.ones(4, bool))
    out = list(unpack_records(rec + rec2))
    assert [e for e, _, _ in out] == [7, 8]
    assert out[0][1] == b"payload-bytes"
    got = np.unpackbits(out[0][2])[: len(act)].astype(bool)
    assert (got == act).all()
    # torn tail (crash mid-write): parser stops cleanly at the last
    # complete record instead of raising
    torn = rec + rec2[: len(rec2) - 3]
    assert [e for e, _, _ in list(unpack_records(torn))] == [7]


def test_recovery_invariant_replay_equals_straight_run(tmp_path):
    """The failover contract, in-process and tier-1-fast: a command
    stream written through EpochLogger and replayed with replay_log /
    replay_into rebuilds db AND device stats bit-identical to a
    straight-through run of the same stream through the same per-epoch
    jit (deterministic replay = re-execution, runtime/logger.py)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.runtime import wire
    from deneva_tpu.runtime.logger import (EpochLogger, replay_into,
                                           replay_log, state_digest)
    from deneva_tpu.engine.epoch import make_dist_step
    from deneva_tpu.workloads import get_workload

    cfg = Config(
        workload=WorkloadKind.YCSB, cc_alg=CCAlg.CALVIN,
        epoch_batch=32, conflict_buckets=256, synth_table_size=1024,
        req_per_query=2, max_accesses=2, logging=True,
        log_dir=str(tmp_path))
    wl = get_workload(cfg)
    be = get_backend(cfg.cc_alg)
    step = make_dist_step(cfg, wl, be)
    n_types = len(getattr(wl, "txn_type_names", ("txn",)))

    # one command stream: 6 epochs of 32 txns with varying active masks
    rng = jax.random.PRNGKey(11)
    path = str(tmp_path / "inproc.log.bin")
    log = EpochLogger(path)
    db = wl.load()
    cc_state = be.init_state(cfg)
    stats = init_device_stats(n_types)
    for e in range(6):
        q = wl.generate(jax.random.fold_in(rng, e), 32)
        keys, types, scalars = wl.to_wire(q)
        block = wire.QueryBlock(keys, types, scalars,
                                tags=np.arange(32, dtype=np.int64))
        ts = np.arange(1, 33, dtype=np.int64) + e * 32
        active = np.ones(32, bool)
        active[e % 32] = False          # vary the logged active mask
        log.append(e, wire.encode_epoch_blob(e, block, ts), active)
        # straight-through execution of the same record
        db, cc_state, stats, *_ = step(
            db, cc_state, stats, jnp.int32(e), jnp.asarray(active),
            jnp.asarray(ts.astype(np.int32)),
            wl.from_wire(keys, types, scalars))
    jax.block_until_ready(stats["total_txn_commit_cnt"])
    assert log.wait_flushed(5, timeout=10.0)
    log.close()

    # full-state replay (db + cc_state + stats) must match bit for bit
    rdb, rcc, rstats, last = replay_into(
        path, cfg, wl, step, wl.load(), be.init_state(cfg),
        init_device_stats(n_types))
    assert last == 5
    assert state_digest(rdb) == state_digest(db)
    assert state_digest(rcc) == state_digest(cc_state)
    for k in stats:
        assert (np.asarray(rstats[k]) == np.asarray(stats[k])).all(), k
    # the public one-shot entry point agrees too
    assert state_digest(replay_log(path, cfg)) == state_digest(db)
    # a prefix replay stops exactly where asked (recovery's truncated-
    # boundary replay path)
    pdb, _, _, plast = replay_into(
        path, cfg, wl, step, wl.load(), be.init_state(cfg),
        init_device_stats(n_types), stop_epoch=3)
    assert plast == 2
    assert state_digest(pdb) != state_digest(db)


def _cfg(tmp, **kw):
    base = dict(
        workload=WorkloadKind.YCSB, cc_alg=CCAlg.CALVIN,
        epoch_batch=64, conflict_buckets=512, synth_table_size=2048,
        max_txn_in_flight=512, req_per_query=4, max_accesses=4,
        zipf_theta=0.6, warmup_secs=0.3, done_secs=1.0,
        logging=True, log_dir=str(tmp))
    base.update(kw)
    return Config(**base)


@pytest.mark.slow
def test_replay_matches_live_state(tmp_path):
    """Solo server, seeded admission queue; replaying the log must
    reproduce the live table state exactly."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from deneva_tpu.runtime import wire
    from deneva_tpu.runtime.logger import replay_log
    from deneva_tpu.runtime.native import ipc_endpoints
    from deneva_tpu.runtime.server import ServerNode

    cfg = _cfg(tmp_path, node_cnt=1, part_cnt=1, client_node_cnt=0)
    node = ServerNode(cfg, ipc_endpoints(1, "replaytest",
                                         str(tmp_path)), "cpu")
    # seed the admission queue directly (no client process needed)
    rng = jax.random.PRNGKey(3)
    for i in range(30):
        q = node.wl.generate(jax.random.fold_in(rng, i), 64)
        keys, types, scalars = node.wl.to_wire(q)
        blk = wire.QueryBlock(keys=keys, types=types, scalars=scalars,
                              tags=np.arange(64, dtype=np.int64) + i * 64)
        node.pending.append((0, blk))
    node.run()
    live_f0 = np.asarray(node.db["MAIN_TABLE"].columns["F0"])
    commits_live = float(
        jax.device_get(node.dev_stats["total_txn_commit_cnt"]))
    node.close()
    assert commits_live > 0

    db = replay_log(node.log_path, cfg)
    replay_f0 = np.asarray(db["MAIN_TABLE"].columns["F0"])
    assert (replay_f0 == live_f0).all(), "replayed state diverged from live"


@pytest.mark.slow
def test_cluster_with_replicas_logs_identical(tmp_path):
    """2 servers + 1 client + 1 replica each: group commit completes,
    and each replica's log is byte-identical to its primary's."""
    from deneva_tpu.runtime.launch import run_cluster

    cfg = _cfg(tmp_path, node_cnt=2, client_node_cnt=1, replica_cnt=1,
               epoch_batch=128, synth_table_size=4096)
    out = run_cluster(cfg, platform="cpu", run_id="replitest")
    log_dir = os.path.join(tmp_path, "replitest")  # per-run namespacing
    # servers 0,1; client 2; replicas 3,4
    assert set(out) == {0, 1, 2, 3, 4}
    s0 = parse_summary(out[0][1])
    assert s0["total_txn_commit_cnt"] > 0
    assert s0["log_records"] > 0
    # client got acks only for durable txns; it must have seen some
    assert parse_summary(out[2][1])["txn_cnt"] > 0
    for primary, replica in ((0, 3), (1, 4)):
        with open(os.path.join(log_dir, f"node{primary}.log.bin"),
                  "rb") as f:
            p = f.read()
        with open(os.path.join(log_dir, f"replica{replica}.log.bin"),
                  "rb") as f:
            r = f.read()
        assert len(p) > 0
        # the replica may trail by the final in-flight records; it must
        # hold a prefix — and a substantial one (group commit acked it)
        assert p.startswith(r) or r.startswith(p)
        assert min(len(p), len(r)) > 0.5 * max(len(p), len(r))
