"""Multi-process client fleet unit tests (pod-scale PR): disjoint
lane-tag / tenant ranges across generators, seeded determinism of the
merged arrival schedule, exactly-once credit accounting under
ADMIT_NACK with multiple generators — including through the real
ClientNode routing paths via the transport-free rig."""

import time as _time
from collections import deque

import numpy as np
import pytest

from deneva_tpu.config import CCAlg, Config, WorkloadKind
from deneva_tpu.runtime import wire
from deneva_tpu.runtime.admission import encode_admit_nack
from deneva_tpu.runtime.client import TAG_RING, ClientNode
from deneva_tpu.runtime.loadgen import (FLEET_LANE_BITS, BackoffLedger,
                                        FleetCredits, FleetGen, LoadFleet,
                                        fleet_gen_of, fleet_tag_range,
                                        fleet_tenant_range)
from deneva_tpu.stats import Stats

MS = 1_000


def _fleet_cfg(**kw) -> Config:
    base = dict(workload=WorkloadKind.YCSB, cc_alg=CCAlg.TPU_BATCH,
                epoch_batch=64, conflict_buckets=512,
                synth_table_size=512, req_per_query=4, max_accesses=4,
                arrival_process="poisson", arrival_rate=200_000.0,
                loadgen_procs=4, tenant_cnt=8)
    base.update(kw)
    cfg = Config(**base)
    cfg.validate()
    return cfg


# ---- range partitioning --------------------------------------------------

def test_fleet_tag_ranges_disjoint_and_owner_decodable():
    span = TAG_RING >> FLEET_LANE_BITS
    prev_hi = 0
    for g in range(64):
        lo, hi = fleet_tag_range(TAG_RING, g)
        assert lo == prev_hi and hi - lo == span
        prev_hi = hi
        tags = np.arange(lo, hi, 997, dtype=np.int64)
        assert (fleet_gen_of(TAG_RING, tags) == g).all()
    assert prev_hi == TAG_RING          # the lanes tile the whole ring
    # tenant / client-id high bits never perturb ownership decoding
    tags = np.arange(*fleet_tag_range(TAG_RING, 3), 1009, dtype=np.int64)
    wtags = tags | (np.int64(5) << 24) | (np.int64(1) << 40)
    assert (fleet_gen_of(TAG_RING, wtags) == 3).all()


def test_fleet_gen_emits_only_its_own_ranges():
    cfg = _fleet_cfg()
    for g in range(cfg.loadgen_procs):
        gen = FleetGen(cfg, node_id=1, gid=g, ring=TAG_RING)
        lo, hi = fleet_tag_range(TAG_RING, g)
        tlo, thi = fleet_tenant_range(cfg.tenant_cnt,
                                      cfg.loadgen_procs, g)
        seen = 0
        t = 0.0
        while seen < 3 * (hi - lo) // 2:     # force a sub-ring wrap
            t += 0.05
            blk = gen.take(t, 4096)
            if blk is None:
                continue
            tags, tenants = blk
            seen += len(tags)
            assert (tags >= lo).all() and (tags < hi).all()
            assert (tenants >= tlo).all() and (tenants < thi).all()
        assert seen > hi - lo               # the wrap actually happened


def test_fleet_tenant_ranges_partition_tenants():
    for tenant_cnt, procs in ((8, 4), (5, 5), (256, 64), (7, 3)):
        covered = []
        for g in range(procs):
            lo, hi = fleet_tenant_range(tenant_cnt, procs, g)
            assert hi > lo, "validate pins tenant_cnt >= loadgen_procs"
            covered.extend(range(lo, hi))
        assert covered == list(range(tenant_cnt))   # disjoint + total
    assert fleet_tenant_range(1, 4, 3) == (0, 1)    # tenants off


def test_fleet_config_validation():
    _fleet_cfg()                                    # sane base composes
    with pytest.raises(ValueError, match="arrival_process"):
        _fleet_cfg(arrival_process="", arrival_rate=0.0)
    with pytest.raises(ValueError, match="64"):
        _fleet_cfg(loadgen_procs=65, tenant_cnt=256)
    with pytest.raises(ValueError, match="tenant_cnt"):
        _fleet_cfg(loadgen_procs=8, tenant_cnt=4)


# ---- seeded determinism of the merged schedule ---------------------------

def test_fleet_merged_schedule_is_deterministic():
    cfg = _fleet_cfg()
    a = LoadFleet(cfg, node_id=1, ring=TAG_RING, chunk=256, start=False)
    b = LoadFleet(cfg, node_id=1, ring=TAG_RING, chunk=256, start=False)
    grid = [0.01, 0.1, 0.37, 0.8, 1.5]
    ta = [a.target(t) for t in grid]
    assert ta == [b.target(t) for t in grid]
    assert all(x <= y for x, y in zip(ta, ta[1:]))       # monotone
    # the merged target is the sum of the per-lane schedules, and the
    # lanes are seeded DIFFERENTLY (independent Poisson gap streams)
    gens = [FleetGen(cfg, 1, g, TAG_RING) for g in range(4)]
    assert a.target(2.0) == sum(g.sched.target(2.0) for g in gens)
    per_lane = [g.sched.target(2.0) for g in gens]
    assert len(set(per_lane)) > 1, "lanes must not share one gap stream"
    # a different seed reshuffles, the same seed reproduces
    c = LoadFleet(_fleet_cfg(seed=1234), 1, TAG_RING, 256, start=False)
    assert c.target(2.0) != a.target(2.0)


def test_fleet_gen_streams_reproduce():
    cfg = _fleet_cfg()
    for g in (0, 3):
        x = FleetGen(cfg, 1, g, TAG_RING)
        y = FleetGen(cfg, 1, g, TAG_RING)
        for t in (0.05, 0.2, 0.21, 0.9):
            bx, by = x.take(t, 300), y.take(t, 300)
            if bx is None:
                assert by is None
                continue
            assert np.array_equal(bx[0], by[0])
            assert np.array_equal(bx[1], by[1])


def test_fleet_worker_processes_match_inline_oracle():
    """Two REAL generator processes: everything each lane streams over
    the queue must equal, in order, what the inline FleetGen (same cfg,
    node, gid) emits — the per-lane stream is deterministic even though
    the cross-lane interleaving is wall-clock."""
    cfg = _fleet_cfg(loadgen_procs=2, tenant_cnt=4)
    fl = LoadFleet(cfg, node_id=1, ring=TAG_RING, chunk=256)
    fl.go()
    # What the comparison needs is the first `need` blocks of EACH lane,
    # however far apart the two spawned processes come up: on a loaded
    # box one lane streams for seconds before the other has imported
    # numpy, so a count over both lanes says nothing about the second.
    # Later blocks of a lane that has its share are drained and dropped
    # (the queue's backpressure must not park the lane that is ahead).
    need = 8
    got = {0: [], 1: []}
    ten = {0: [], 1: []}
    t0 = _time.monotonic()
    try:
        while min(len(got[0]), len(got[1])) < need:
            # a guard against a lane that died, not a pace: a worker
            # gives up on its own go signal after 300 s
            assert _time.monotonic() - t0 < 280, \
                f"blocks after 280 s: {len(got[0])} | {len(got[1])}"
            b = fl.take(256)
            if b is None:
                _time.sleep(0.005)
                continue
            tags, tc = b
            g = int(fleet_gen_of(TAG_RING, tags[:1])[0])
            assert (fleet_gen_of(TAG_RING, tags) == g).all(), \
                "a streamed block never mixes lanes"
            if len(got[g]) < need:
                got[g].append(tags)
                ten[g].append(tc)
    finally:
        fl.close()
    for g in (0, 1):
        ref = FleetGen(cfg, 1, g, TAG_RING)
        n = sum(map(len, got[g]))
        rt, rten = [], []
        t = 0.0
        while sum(map(len, rt)) < n:
            t += 0.01
            blk = ref.take(t, 256)
            if blk is not None:
                rt.append(blk[0])
                rten.append(blk[1])
        assert np.array_equal(np.concatenate(got[g]),
                              np.concatenate(rt)[:n])
        assert np.array_equal(np.concatenate(ten[g]),
                              np.concatenate(rten)[:n])


# ---- exactly-once credit accounting --------------------------------------

def test_fleet_credits_exactly_once():
    rng = np.random.default_rng(7)
    fc = FleetCredits(4, TAG_RING)
    span = TAG_RING >> FLEET_LANE_BITS
    outstanding: list[np.ndarray] = []
    acked = nacked = 0
    for round_ in range(50):
        g = int(rng.integers(4))
        # fresh slots per round: a charge collision would be a test
        # artifact, not a ledger property (double_charge must stay 0)
        tags = g * span + round_ * 64 + np.arange(64, dtype=np.int64)
        fc.charge(tags)
        outstanding.append(tags)
        if rng.random() < 0.5 and outstanding:
            victim = outstanding.pop(int(rng.integers(len(outstanding))))
            if rng.random() < 0.5:
                fc.nack(victim)
                nacked += len(victim)
                fc.nack(victim)        # duplicate NACK: counted, no-op
            else:
                fc.release(victim)
                acked += len(victim)
                fc.release(victim)     # duplicate ack: counted, no-op
    held = sum(map(len, outstanding))
    assert int(fc.outstanding().sum()) == held
    assert (fc.outstanding() >= 0).all()
    assert int(fc.acked.sum()) == acked
    assert int(fc.nacked.sum()) == nacked
    assert fc.double_release == acked + nacked    # one dup per release
    assert fc.double_charge == 0
    # NACK-released tags recharge cleanly (the backoff re-entry path)
    fc2 = FleetCredits(2, TAG_RING)
    tags = np.arange(64, dtype=np.int64)
    fc2.charge(tags)
    fc2.nack(tags)
    fc2.charge(tags)
    fc2.release(tags)
    assert fc2.double_charge == 0 and fc2.double_release == 0
    assert int(fc2.outstanding().sum()) == 0
    assert int(fc2.sent[0]) == 128    # two charges, both legitimate


# ---- through the real ClientNode routing (transport-free rig) ------------

class _FakeTp:
    def __init__(self):
        self.sent = []

    def sendv(self, dest, rtype, parts):
        self.sent.append((dest, rtype, b"".join(bytes(p) for p in parts)))


def _fleet_client(n_procs=2, n_srv=2, chunk=64):
    """ClientNode.__new__ rig (test_backoff.py's pattern) with the fleet
    credit ledger armed: _route / the sweeps exercise the REAL exactly-
    once filters feeding FleetCredits."""
    c = ClientNode.__new__(ClientNode)
    c.cfg = None
    c.n_srv = n_srv
    c._fault_mode = False
    c._adm = True
    c._elastic = False
    c._geo = False
    c._active = np.ones(n_srv, bool)
    c._rr = 0
    c._unacked = np.zeros(TAG_RING, bool)
    c._nacked = np.zeros(TAG_RING, bool)
    c._ledger = BackoffLedger(TAG_RING, 10 * MS, 500 * MS, seed=11)
    c._tag_srv = None
    c.tel = None
    c._resend_q = deque()
    c._resend_us = 100 * MS
    c._resend_cnt = 0
    c._dup_acks = 0
    c._nack_cnt = 0
    c._nack_resend_cnt = 0
    c._flash_end_us = None
    c.inflight = np.zeros(n_srv, np.int64)
    c.send_us = np.zeros(TAG_RING, np.int64)
    c.tag_type = np.zeros(TAG_RING, np.uint8)
    c.type_names = ["txn"]
    c.ring_tenants = None
    c._tenant_on = False
    c._fleet = None
    c._fleet_credits = FleetCredits(n_procs, TAG_RING)
    c.chunk = chunk
    c.ring = [wire.QueryBlock(
        keys=np.zeros((chunk, 2), np.int32),
        types=np.ones((chunk, 2), np.int8),
        scalars=np.zeros((chunk, 1), np.int32),
        tags=np.zeros(chunk, np.int64))]
    c.ring_types = [np.zeros(chunk, np.uint8)]
    c.ring_pos = 0
    c.stats = Stats()
    c.tp = _FakeTp()
    return c


def _send(c, srv, tags):
    """The hot loop's bookkeeping for a sent fleet batch."""
    c._unacked[tags % TAG_RING] = True
    c._nacked[tags % TAG_RING] = False
    c._ledger.reset(tags)
    c.inflight[srv] += len(tags)
    c._fleet_credits.charge(tags)


def test_fleet_credits_exactly_once_through_client_routing():
    """Multiple generators' tags through the REAL _route paths: dup
    NACKs, the NACK-then-late-CL_RSP race and backoff re-entry keep the
    per-lane ledger exactly once (double counters stay 0 — the client's
    freshness filters are the dedup point)."""
    span = TAG_RING >> FLEET_LANE_BITS
    c = _fleet_client(n_procs=2)
    fc = c._fleet_credits
    lat = c.stats.arr("client_client_latency")
    t0 = np.arange(10, dtype=np.int64)              # lane 0
    t1 = span + np.arange(10, dtype=np.int64)       # lane 1
    _send(c, 0, t0)
    _send(c, 1, t1)
    assert (fc.outstanding() == [10, 10]).all()
    # lane 1 takes a NACK for 4 tags, then the same NACK duplicated
    nack = encode_admit_nack(t1[:4], np.full(4, 20 * MS, np.uint32))
    c._route(1, "ADMIT_NACK", nack, lat)
    c._route(1, "ADMIT_NACK", nack, lat)
    assert (fc.outstanding() == [10, 6]).all()
    assert (fc.nacked == [0, 4]).all()
    # the late CL_RSP race: ALL lane-1 tags ack, the 4 NACKed ones must
    # not release twice (their credit is gone)
    c._route(1, "CL_RSP", wire.encode_cl_rsp(t1), lat)
    assert (fc.outstanding() == [10, 0]).all()
    assert (fc.acked == [0, 6]).all()
    # duplicate CL_RSP for lane 0: one release only
    c._route(0, "CL_RSP", wire.encode_cl_rsp(t0), lat)
    c._route(0, "CL_RSP", wire.encode_cl_rsp(t0), lat)
    assert (fc.outstanding() == [0, 0]).all()
    assert (fc.acked == [10, 6]).all()
    assert fc.double_charge == 0 and fc.double_release == 0


def test_fleet_backoff_reentry_recharges_the_owning_lane():
    span = TAG_RING >> FLEET_LANE_BITS
    c = _fleet_client(n_procs=2)
    fc = c._fleet_credits
    lat = c.stats.arr("client_client_latency")
    t1 = span + np.arange(6, dtype=np.int64)
    _send(c, 1, t1)
    c._route(1, "ADMIT_NACK",
             encode_admit_nack(t1, np.full(6, 15 * MS, np.uint32)), lat)
    assert int(fc.outstanding()[1]) == 0 and int(fc.nacked[1]) == 6
    now = _time.monotonic_ns() // 1000
    c._backoff_sweep(now_us=now + 10_000 * MS)
    assert c._nack_resend_cnt == 6
    assert int(fc.outstanding()[1]) == 6, "re-entry recharges lane 1"
    assert int(fc.sent[1]) == 12
    c._route(1, "CL_RSP", wire.encode_cl_rsp(t1), lat)
    assert int(fc.outstanding()[1]) == 0
    assert fc.double_charge == 0 and fc.double_release == 0
