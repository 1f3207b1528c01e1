"""Partition & gray-failure tolerance (runtime/faildet.py + the server
fencing integration): detector math, quorum decisions, the fence
envelope, route-level fencing behaviors on a loopback ServerNode, the
fencing-off wire pin (bytes verbatim, no detector, no envelope — the
default-off bit-identity contract), and the slow end-to-end
partition-split scenario."""

import json
import os

import numpy as np
import pytest

from deneva_tpu.config import CCAlg, Config, WorkloadKind
from deneva_tpu.runtime import faildet as FD
from deneva_tpu.runtime import wire

from tests.test_chaos import _solo_server


# ---- failure detector --------------------------------------------------

def _cfg(**kw):
    base = dict(fencing_phi=8.0, fencing_heartbeat_ms=100.0,
                fencing_suspect_s=2.0)
    base.update(kw)
    return Config(**base)


def test_detector_steady_traffic_stays_fresh():
    fd = FD.FailureDetector(_cfg(), [1, 2], now_s=0.0)
    t = 0.0
    for _ in range(50):
        t += 0.1
        fd.observe(1, t)
        fd.observe(2, t)
    assert fd.phi(1, t + 0.1) < 1.0
    assert not fd.suspected(1, t + 0.1)
    assert fd.suspect_cnt == 0 and fd.heal_cnt == 0


def test_detector_silence_suspects_then_heals():
    fd = FD.FailureDetector(_cfg(), [1], now_s=0.0)
    for i in range(10):
        fd.observe(1, 0.1 * (i + 1))
    t0 = 1.0
    # phi crosses 8.0 at ~1.84 s of silence (mean gap floored at the
    # 100 ms cadence); the fence additionally needs the 2 s floor
    assert not fd.suspected(1, t0 + 1.0)
    assert fd.suspected(1, t0 + 1.9)
    assert fd.suspect_cnt == 1
    assert not fd.fence_ready(1, t0 + 1.9)       # floor not yet cleared
    assert fd.fence_ready(1, t0 + 2.1)
    # latched until traffic resumes; the heal returns the silence gap
    gap = fd.observe(1, t0 + 2.5)
    assert gap == pytest.approx(2.5)
    assert fd.heal_cnt == 1 and not fd.suspected(1, t0 + 2.6)
    assert fd.phi_peak > 8.0


def test_detector_mean_floored_at_cadence():
    """Heavy epoch traffic (ms-scale gaps) must not shrink the expected
    gap so far that a sub-second stall reads as death."""
    fd = FD.FailureDetector(_cfg(), [1], now_s=0.0)
    t = 0.0
    for _ in range(200):
        t += 0.002
        fd.observe(1, t)
    assert not fd.suspected(1, t + 0.5)
    assert fd.suspect_cnt == 0


def test_detector_warming_half_threshold():
    fd = FD.FailureDetector(_cfg(), [1], now_s=0.0)
    assert not fd.warming(1, 0.5)
    assert fd.warming(1, 1.0)        # phi ~4.3 >= 8/2
    assert not fd.suspected(1, 1.0)  # but not yet suspected


def test_detector_observe_unknown_peer_is_noop():
    fd = FD.FailureDetector(_cfg(), [1], now_s=0.0)
    assert fd.observe(7, 1.0) is None


# ---- quorum decisions --------------------------------------------------

def test_majority_side_strict_and_tiebreak():
    # strict majority wins
    assert FD.majority_side([0, 1], [2])
    assert not FD.majority_side([2], [0, 1])
    # exact tie: the side holding the lowest live id proceeds — both
    # sides compute the same answer from their own view
    assert FD.majority_side([0, 3], [1, 2])
    assert not FD.majority_side([1, 2], [0, 3])


def test_majority_confirms():
    assert FD.majority_confirms(1, 1)          # solo cluster
    assert FD.majority_confirms(3, 2)
    assert not FD.majority_confirms(3, 1)
    assert FD.majority_confirms(2, 2)
    assert not FD.majority_confirms(2, 1)      # 2-node: both must see it


# ---- fence envelope ----------------------------------------------------

def test_fence_envelope_round_trip():
    body = b"\x01\x02payload"
    buf = FD.fence_wrap(body, 5)
    ver, off = FD.fence_peek(buf)
    assert ver == 5 and buf[off:] == body
    # the sendv part prepended on the zero-copy path is the same header
    assert FD.fence_parts(5) + body == buf
    with pytest.raises(ValueError):
        FD.fence_peek(b"\x00" * 16)            # wrong magic


# ---- config gating -----------------------------------------------------

def test_fencing_defaults_off_and_gated():
    cfg = Config()
    assert not cfg.fencing and not cfg.faults_enabled
    with pytest.raises(ValueError, match="fencing needs"):
        Config().replace(fencing=True)
    # the valid arming shape
    cfg = Config().replace(elastic=True, logging=True, fencing=True,
                           cc_alg=CCAlg.CALVIN,
                           workload=WorkloadKind.YCSB)
    assert cfg.fencing


def test_partition_and_stall_specs_validate():
    ok = Config(node_cnt=3).replace(
        fault_partition="2-0:2.5,2>1:3.0", logging=True)
    assert ok.fault_partition_spec() == [(2, 0, True, 2.5),
                                         (2, 1, False, 3.0)]
    assert ok.faults_enabled
    with pytest.raises(ValueError, match="fault_partition"):
        Config(node_cnt=3).replace(fault_partition="2-2:1.0")
    with pytest.raises(ValueError, match="fault_partition"):
        Config(node_cnt=3).replace(fault_partition="2-9:1.0")
    with pytest.raises(ValueError, match="flap"):
        Config().replace(fault_partition_flap_s=1.0)
    assert Config(node_cnt=3).replace(
        fault_peer_stall="1:4000:3.0").fault_peer_stall_spec() \
        == (1, 4000.0, 3.0)
    with pytest.raises(ValueError, match="fault_peer_stall"):
        Config(node_cnt=3).replace(fault_peer_stall="1:4000")
    with pytest.raises(ValueError, match="node 0"):
        Config(node_cnt=3).replace(
            elastic=True, logging=True, fencing=True,
            cc_alg=CCAlg.CALVIN, fault_peer_stall="0:4000:3.0")
    # fencing may not isolate the measure/stop coordinator into a
    # minority; cutting around node >= 1 (or leaving node 0 in the
    # majority component) is fine
    with pytest.raises(ValueError, match="node 0"):
        Config(node_cnt=3).replace(
            elastic=True, logging=True, fencing=True,
            cc_alg=CCAlg.CALVIN, fault_partition="0-1:3.0,0-2:3.0")
    ok = Config(node_cnt=3).replace(
        elastic=True, logging=True, fencing=True,
        cc_alg=CCAlg.CALVIN, fault_partition="2-0:3.0,2-1:3.0")
    assert ok.fencing


# ---- loopback ServerNode: fencing-off wire pin -------------------------

def _blob(epoch=7):
    blk = wire.QueryBlock(
        keys=np.arange(8, dtype=np.int32).reshape(4, 2),
        types=np.ones((4, 2), np.int8),
        scalars=np.zeros((4, 0), np.int32),
        tags=np.arange(4, dtype=np.int64))
    ts = np.arange(4, dtype=np.int64) + 100
    return blk, ts, wire.encode_epoch_blob(epoch, blk, ts)


def test_fencing_off_takes_pre_fencing_path_verbatim():
    """The house contract, executable: with fencing off a server builds
    NO detector, arms no partition surface, routes EPOCH_BLOB payloads
    unstripped, and its blob broadcast is byte-identical to the
    pre-fencing codec output — no envelope, no heartbeat, no new rtype
    ever touches the wire."""
    node = _solo_server("fence_off_pin")
    try:
        assert node._fencing is False
        assert node._fd is None and node._FD is None
        assert node._partitions is None and node._stall is None
        blk, ts, blob = _blob()
        node._route(0, "EPOCH_BLOB", blob)
        assert node.blob_buf[7][0] == blob     # the raw payload, kept
        # broadcast bytes == the pre-fencing codec, verbatim
        sent = []
        node.tp.sendv_many = \
            lambda dests, rt, parts: sent.append((list(dests), rt, parts))
        node.tp.send = lambda d, rt, pl=b"": sent.append(([d], rt, [pl]))
        node.n_srv = 2          # pretend a peer so the bcast emits
        node._bcast_views(7, blk, ts)
        (dests, rt, parts), = sent
        assert rt == "EPOCH_BLOB"
        assert b"".join(bytes(p) for p in parts) == blob
        assert not any(k in node.stats.counters
                       for k in ("fence_nack_cnt", "suspect_cnt"))
    finally:
        node.n_srv = 1
        node.close()


# ---- loopback ServerNode: fencing-on route behaviors -------------------

def _fencing_server(tag, tmp_path, **kw):
    base = dict(elastic=True, logging=True, fencing=True,
                log_dir=str(tmp_path), synth_table_size=1024)
    base.update(kw)
    return _solo_server(tag, **base)


def test_fence_nack_and_healed_out_self_halt(tmp_path, monkeypatch):
    """A FENCE_NACK carrying a newer map version (or a HEAL whose map
    no longer includes us) self-halts with the exit-18 sentinel; a nack
    echoing our own version (stale crossing) does not."""
    node = _fencing_server("fence_nack_halt", tmp_path)
    halts = []
    try:
        monkeypatch.setattr(
            node, "_self_fence",
            lambda reason, epoch: halts.append((reason, epoch)))
        node._route(5, "FENCE_NACK", FD.encode_fence_nack(0, 0, 7))
        assert halts == [] and node._fence_nack_rx == 1
        node._route(5, "FENCE_NACK", FD.encode_fence_nack(3, 0, 9))
        assert halts == [("fence_nack", 9)]
        # HEAL with a newer map that still includes us: no halt
        node._route(5, "HEAL", FD.encode_heal(11, 4, np.zeros(4, np.int32)))
        assert len(halts) == 1
        # HEAL with a newer map we were evicted from: healed out
        node._route(5, "HEAL", FD.encode_heal(12, 4, np.ones(4, np.int32)))
        assert halts[-1] == ("healed_out", 12)
    finally:
        node.close()


def test_stale_incarnation_blob_rejected_with_fence_nack(tmp_path):
    """An EPOCH_BLOB from a RETIRED peer's stale incarnation is dropped
    and FENCE_NACKed; a live (non-retired) peer briefly one map version
    behind is accepted — pipeline skew across a deterministic cutover
    is not split-brain."""
    from deneva_tpu.runtime.membership import SlotMap

    node = _fencing_server("fence_stale_blob", tmp_path)
    sent = []
    try:
        node.tp.send = lambda d, rt, pl=b"": sent.append((d, rt, pl))
        node.n_srv = 3                      # pretend peers 1, 2 exist
        node.smap = SlotMap(1, node.smap.owners)   # we are at v1
        node._reassigned.add(2)
        _blk, _ts, blob = _blob(epoch=9)
        # retired peer 2 at v0: rejected + nacked
        node._route(2, "EPOCH_BLOB", FD.fence_wrap(blob, 0))
        assert 9 not in node.blob_buf
        assert node._fence_nacks == 1
        d, rt, pl = sent[-1]
        assert (d, rt) == (2, "FENCE_NACK")
        assert FD.decode_fence_nack(pl)[0] == 1
        # live peer 1 at v0: accepted, envelope stripped, lease ledger
        # records the epoch
        node._route(1, "EPOCH_BLOB", FD.fence_wrap(blob, 0))
        assert node.blob_buf[9][1] == blob
        assert node._blob_seen_from[1] == 9
    finally:
        node.n_srv = 1
        node._reassigned.clear()
        node.close()


def test_ack_lease_needs_majority_blob_confirmation(tmp_path):
    """_fence_ack_ok: an epoch's acks release only once a majority of
    the live set (self included) confirmed its blob via heartbeats."""
    node = _fencing_server("fence_ack_lease", tmp_path)
    try:
        assert node._fence_ack_ok(12)          # solo: majority of 1
        node.n_srv = 3
        node._hb_peer_seen = {1: 5, 2: -1}
        assert node._fence_ack_ok(5)           # self + peer 1 = 2 of 3
        assert not node._fence_ack_ok(6)       # only self has seen 6
        node._reassigned.add(2)                # live set shrinks to 2
        assert node._fence_ack_ok(5)
        assert not node._fence_ack_ok(6)       # 2-node: both must see
        node._hb_peer_seen[1] = 6
        assert node._fence_ack_ok(6)
    finally:
        node.n_srv = 1
        node._reassigned.clear()
        node.close()


def test_self_fence_writes_sidecar_and_exits_18(tmp_path, monkeypatch):
    node = _fencing_server("fence_halt_sidecar", tmp_path)
    codes = []
    try:
        monkeypatch.setattr(os, "_exit", lambda c: codes.append(c))
        node._fence_last_ack = 41
        node._self_fence("minority", 48)
        assert codes == [FD.FENCED_EXIT] == [18]
        with open(os.path.join(str(tmp_path),
                               "node0.fenced.json")) as f:
            side = json.load(f)
        assert side["reason"] == "minority" and side["epoch"] == 48
        assert side["last_acked_epoch"] == 41
        assert side["map_version"] == 0
    finally:
        node.close()


def test_fenced_run_ends_on_its_sidecar(tmp_path):
    """A whole served run with fencing armed reaches its closing: the
    fencing counters in `[summary]` and `node<N>.fencing.json` — the
    table's digest under the FINAL map, which the chaos harness audits
    against an independent replay."""
    from deneva_tpu.runtime.logger import state_digest

    node = _fencing_server("fence_whole_run", tmp_path)
    try:
        st = node.run()
        assert st.counters["suspect_cnt"] == 0
        with open(os.path.join(str(tmp_path), "node0.fencing.json")) as f:
            side = json.load(f)
        assert side["state_digest"] == state_digest(node.db)
        assert side["epochs_run"] == st.counters["epoch_cnt"] > 0
        assert side["map_version"] == 0 and side["reassign_epoch"] == -1
    finally:
        node.close()


# ---- end-to-end scenario (the smoke gate runs all four) ----------------

@pytest.mark.slow
def test_partition_split_scenario():
    """Symmetric split: majority reassigns, minority self-fences with
    exit 18, single-writer + digest-vs-replay invariants green."""
    from deneva_tpu.harness.chaos import run_scenario

    rep = run_scenario("partition-split", quick=True, quiet=True)
    assert rep["fenced_node"] == 2
    assert rep["fence_reason"] == "minority"
    assert rep["fenced_last_ack"] < rep["reassign_epoch"]
    assert rep["digest_match"]
