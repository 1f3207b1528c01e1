"""Isolation audit plane (cc/base.audit_observe + runtime/audit.py +
harness/auditgraph.py): scripted edge-derivation semantics per
visibility mode, escrow/self-edge exclusions, export-cap accounting,
the seeded audit_mutate fault, graph certification + Adya
classification + witness forensics, cross-node divergence detection,
the default-off group-output arity, the observation-only contract
(armed == off row state, bit for bit), and the end-to-end
mutation-catch through the real cluster epoch body."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deneva_tpu.config import CCAlg, Config, WorkloadKind
from deneva_tpu.cc import (AUDIT_KEY, AccessBatch, audit_init,
                           audit_mutate_verdict, audit_observe)
from deneva_tpu.harness import auditgraph
from deneva_tpu.runtime import audit as AU

from tests.test_chaos import _solo_server


def _cfg(**kw):
    base = dict(audit=True, audit_cadence=1, audit_buckets=1024,
                audit_edges_max=64, cc_alg=CCAlg.OCC,
                dist_protocol="merged", epoch_batch=128,
                synth_table_size=1024)
    base.update(kw)
    return Config(**base).validate()


def _batch(scripts, B=8, A=2, order_free=None):
    """AccessBatch from per-txn [(key, 'r'|'w'|'rw'), ...] scripts;
    txns beyond the scripts are inactive."""
    keys = np.zeros((B, A), np.int32)
    is_r = np.zeros((B, A), bool)
    is_w = np.zeros((B, A), bool)
    valid = np.zeros((B, A), bool)
    for i, script in enumerate(scripts):
        for s, (key, mode) in enumerate(script):
            keys[i, s] = key
            is_r[i, s] = "r" in mode
            is_w[i, s] = "w" in mode
            valid[i, s] = True
    active = np.zeros(B, bool)
    active[:len(scripts)] = True
    return AccessBatch(
        table_ids=jnp.zeros((B, A), jnp.int32), keys=jnp.asarray(keys),
        is_read=jnp.asarray(is_r), is_write=jnp.asarray(is_w),
        valid=jnp.asarray(valid), ts=jnp.arange(B, dtype=jnp.int32),
        rank=jnp.arange(B, dtype=jnp.int32), active=jnp.asarray(active),
        order_free=None if order_free is None
        else jnp.asarray(order_free))


def _observe(cfg, batch, committed, lvl=None, order_vis=False,
             aud=None, epoch=0):
    b = batch.shape[0]
    committed = jnp.asarray(committed)
    lvl = jnp.zeros(b, jnp.int32) if lvl is None \
        else jnp.asarray(lvl, jnp.int32)
    aud = audit_init(cfg) if aud is None else aud
    out = audit_observe(cfg, batch, committed, batch.rank, lvl,
                        order_vis, aud, jnp.int32(epoch))
    aud2, edges, ebkt, cnt, drop, vdig, rdig = out
    es = sorted(AU.decode_edge(int(e))
                for e in np.asarray(edges)[:int(cnt)])
    return aud2, es, int(cnt), int(drop), int(vdig), int(rdig)


def _mask(B, committed_ids):
    m = np.zeros(B, bool)
    m[list(committed_ids)] = True
    return m


# ---- config gating -----------------------------------------------------

def test_config_gating():
    assert Config().audit is False
    with pytest.raises(ValueError):        # mutate needs audit
        Config(audit_mutate="occ-read-skip:4").validate()
    with pytest.raises(ValueError):        # mutate is OCC-scoped
        _cfg(cc_alg=CCAlg.CALVIN, dist_protocol="auto",
             audit_mutate="occ-read-skip:4")
    with pytest.raises(ValueError):        # malformed spec
        _cfg(audit_mutate="occ-read-skip")
    # MVCC version-select reads are MODELED since the depgraph refactor
    # (per-slot version rings in the stamp state): audit+MVCC validates
    _cfg(cc_alg=CCAlg.MVCC)
    with pytest.raises(ValueError):        # PPS not wired
        _cfg(workload=WorkloadKind.PPS, pps_parts_per=4, max_accesses=16)
    with pytest.raises(ValueError):        # rank packing bound
        _cfg(epoch_batch=32768)
    with pytest.raises(ValueError):        # vote body observes nothing
        _cfg(dist_protocol="vote")
    with pytest.raises(ValueError):
        _cfg(audit_cadence=0)
    spec = _cfg(audit_mutate="occ-read-skip:48:8").audit_mutate_spec()
    assert spec == ("occ-read-skip", 48, 8)
    assert _cfg().audit_mutate_spec() is None


# ---- scripted edge derivation ------------------------------------------

def test_snapshot_write_skew_two_rw_cycle():
    """Level-0 sweep visibility (reads observe the epoch-start
    snapshot): a committed write-skew pair yields exactly the two rw
    anti-dependency edges whose cycle IS the G2 anomaly."""
    cfg = _cfg()
    batch = _batch([[(10, "r"), (20, "w")], [(20, "r"), (10, "w")]])
    _, es, cnt, drop, _, _ = _observe(cfg, batch, _mask(8, [0, 1]))
    assert es == [(2, 0, 1), (2, 1, 0)] and drop == 0


def test_clean_committed_set_no_edges():
    cfg = _cfg()
    batch = _batch([[(10, "r"), (20, "w")], [(30, "r"), (40, "w")]])
    _, es, cnt, *_ = _observe(cfg, batch, _mask(8, [0, 1]))
    assert es == [] and cnt == 0


def test_uncommitted_txns_never_observed():
    """An aborted txn's accesses are not part of the history: the same
    write-skew pair with one side aborted emits only the surviving
    side's (acyclic) rw edge."""
    cfg = _cfg()
    batch = _batch([[(10, "r"), (20, "w")], [(20, "r"), (10, "w")]])
    _, es, *_ = _observe(cfg, batch, _mask(8, [0]))
    assert es == []


def test_forward_visibility_wr_rw_ww():
    """Forwarding (serial-in-order) visibility: T1's read of k observes
    T0's earlier write (wr), the next writer T2 takes T1's rw
    anti-dependency, and the writers chain ww."""
    cfg = _cfg()
    batch = _batch([[(5, "w")], [(5, "r")], [(5, "w")]])
    _, es, *_ = _observe(cfg, batch, _mask(8, [0, 1, 2]),
                         order_vis=True)
    assert es == [(0, 0, 2), (1, 0, 1), (2, 1, 2)]


def test_level_visibility_chained():
    """Chained visibility: a level-1 reader observes the level-0 write
    (wr); a level-0 reader of a level-1 writer's key observes the
    snapshot (rw toward the writer)."""
    cfg = _cfg()
    batch = _batch([[(5, "w"), (7, "r")], [(5, "r"), (7, "w")]])
    _, es, *_ = _observe(cfg, batch, _mask(8, [0, 1]),
                         lvl=[0, 1, 0, 0, 0, 0, 0, 0])
    assert es == [(1, 0, 1), (2, 0, 1)]


def test_escrow_lanes_excluded():
    """order_free (escrow) lanes carry no ordering claim: the same
    conflicting pair with the mask set emits nothing."""
    cfg = _cfg()
    of = np.zeros((8, 2), bool)
    of[0] = of[1] = True
    batch = _batch([[(10, "r"), (20, "w")], [(20, "r"), (10, "w")]],
                   order_free=of)
    _, es, *_ = _observe(cfg, batch, _mask(8, [0, 1]))
    assert es == []


def test_self_rmw_no_self_edges():
    cfg = _cfg()
    batch = _batch([[(5, "rw")]])
    _, es, *_ = _observe(cfg, batch, _mask(8, [0]), order_vis=True)
    assert es == []


def test_edge_cap_overflow_counted():
    """Past audit_edges_max the export truncates and COUNTS — the
    certificate degrades to incomplete, never silently."""
    cfg = _cfg(epoch_batch=64)
    scripts = [[(5, "r"), (5, "w")] for _ in range(40)]
    batch = _batch(scripts, B=64)
    _, es, cnt, drop, _, _ = _observe(cfg, batch, _mask(64, range(40)))
    assert cnt > cfg.audit_edges_max
    assert drop == cnt - cfg.audit_edges_max
    assert len(es) == cfg.audit_edges_max


def test_stamp_tables_and_digests():
    """Version stamps advance per epoch, digests are deterministic, and
    an epoch-start read's rdig depends on what the PREVIOUS epochs
    wrote (the cross-epoch fingerprint)."""
    cfg = _cfg()
    w = _batch([[(5, "w")]])
    r = _batch([[(5, "r")]])
    aud0 = audit_init(cfg)
    aud1, _, _, _, v1, _ = _observe(cfg, w, _mask(8, [0]), epoch=3)
    assert int(np.asarray(aud1["epoch"]).max()) == 3
    # identical inputs -> identical digests (what the cross-node
    # consensus check rests on)
    aud1b, _, _, _, v1b, _ = _observe(cfg, w, _mask(8, [0]), epoch=3)
    assert v1 == v1b
    _, _, _, _, _, r_fresh = _observe(cfg, r, _mask(8, [0]), aud=aud0)
    _, _, _, _, _, r_after = _observe(cfg, r, _mask(8, [0]), aud=aud1)
    assert r_fresh != r_after


def test_mvcc_version_ring_visibility():
    """MVCC per-read observed-version export (the depgraph refactor's
    headroom item): a read's observed stamp is SELECTED BY ITS OWN
    TIMESTAMP from the bucket's version-boundary ring, so a stale
    reader and a fresh reader digest DIFFERENT observations — under
    every other backend's last-writer stamp model they are identical,
    which is exactly the MVCC anomaly the audit plane used to miss."""
    import dataclasses
    from deneva_tpu.cc import depgraph

    # the in-ring select rule: newest boundary <= ts, -1 pre-horizon
    vts = jnp.asarray([[10, 20, 30, -1]], jnp.int32)
    for ts, want in ((15, 0), (25, 1), (99, 2), (5, -1)):
        sel = depgraph.version_select(vts, jnp.asarray([ts], jnp.int32))
        assert int(sel[0]) == want, (ts, want)

    # two writer epochs push boundaries ts=10 and ts=20 into the ring
    cfg = _cfg(cc_alg=CCAlg.MVCC)
    aud = audit_init(cfg)
    assert "vts" in aud            # rings exist only under MVCC
    w = _batch([[(5, "w")]])
    for e, wts in ((1, 10), (2, 20)):
        wb = dataclasses.replace(w, ts=jnp.full(8, wts, jnp.int32))
        aud, _, _, _, _, _ = _observe(cfg, wb, _mask(8, [0]), aud=aud,
                                      epoch=e)
    retained = set(np.asarray(aud["vts"]).ravel().tolist())
    assert {10, 20} <= retained    # both boundaries retained

    def rdig_at(a, ts):
        r = dataclasses.replace(_batch([[(5, "r")]]),
                                ts=jnp.full(8, ts, jnp.int32))
        return _observe(cfg, r, _mask(8, [0]), aud=a)[5]

    stale, fresh, horizon = rdig_at(aud, 12), rdig_at(aud, 25), \
        rdig_at(aud, 5)
    assert stale != fresh          # ts selects the version, not the
    assert horizon not in (stale, fresh)   # last writer; pre-horizon
    # reads observe epoch-start-of-history, distinct from both
    # control: the OCC stamp model cannot see the difference
    ocfg = _cfg()
    oaud = audit_init(ocfg)
    assert "vts" not in oaud
    for e, wts in ((1, 10), (2, 20)):
        wb = dataclasses.replace(w, ts=jnp.full(8, wts, jnp.int32))
        oaud, _, _, _, _, _ = _observe(ocfg, wb, _mask(8, [0]),
                                       aud=oaud, epoch=e)

    def ordig_at(ts):
        r = dataclasses.replace(_batch([[(5, "r")]]),
                                ts=jnp.full(8, ts, jnp.int32))
        return _observe(ocfg, r, _mask(8, [0]), aud=oaud)[5]

    assert ordig_at(12) == ordig_at(25)


# ---- the seeded mutation ----------------------------------------------

def test_mutate_flips_only_clean_losers_inside_window():
    from deneva_tpu.cc import build_conflict_incidence, get_backend

    cfg = _cfg(audit_mutate="occ-read-skip:7:2", epoch_batch=8,
               conflict_buckets=256)
    be = get_backend(cfg.cc_alg)
    # T0 wins writing 5; T1 reads 5 (clean writes) -> flippable;
    # T2 reads 5 AND writes 5 (dirty write) -> stays aborted
    batch = _batch([[(5, "w")], [(5, "r"), (9, "w")],
                    [(5, "r"), (5, "w")]])
    inc = build_conflict_incidence(cfg, be, batch, None)
    verdict, _ = be.validate(cfg, be.init_state(cfg), batch, inc)
    assert bool(np.asarray(verdict.commit)[0])
    assert bool(np.asarray(verdict.abort)[1])
    assert bool(np.asarray(verdict.abort)[2])
    out = audit_mutate_verdict(cfg, batch, inc, verdict, jnp.int32(7))
    assert bool(np.asarray(out.commit)[1])     # flipped
    assert not bool(np.asarray(out.abort)[1])
    assert bool(np.asarray(out.abort)[2])      # dirty write: untouched
    miss = audit_mutate_verdict(cfg, batch, inc, verdict, jnp.int32(9))
    np.testing.assert_array_equal(np.asarray(miss.commit),
                                  np.asarray(verdict.commit))


# ---- graph certification ----------------------------------------------

def test_classify_adya():
    assert auditgraph.classify([0, 0]) == "G0"
    assert auditgraph.classify([0, 1]) == "G1c"
    assert auditgraph.classify([1, 1, 2]) == "G-single"
    assert auditgraph.classify([2, 2]) == "G2-item"


def _pack(kind, src, dst):
    return (kind << 28) | (src << 14) | dst


def _emit(tmp_path, node, epoch, edges, tags, vdig=1, rdig=1,
          lo=0, b_loc=64, dropped=0):
    cfg = _cfg(telemetry_dir=str(tmp_path))
    ex = AU.AuditExporter(cfg, node, b_loc, lo, append=True)
    tag_col = np.zeros(b_loc, np.int64)
    for r, t in tags.items():
        tag_col[r - lo] = t
    ex.export(epoch, np.asarray(edges + [-1], np.int32),
              np.zeros(len(edges) + 1, np.int32),
              len(edges), dropped, vdig, rdig, commit=3, tags=tag_col)
    ex.close()


def test_certify_clean_and_violation(tmp_path):
    # epoch 0: a forward rw edge (legal); epoch 1: a 2-cycle
    _emit(tmp_path, 0, 0, [_pack(2, 1, 2)], {1: 101, 2: 102})
    cert = auditgraph.certify(str(tmp_path))
    assert cert["ok"] and cert["epochs"] == 1 and cert["complete"]
    _emit(tmp_path, 0, 1, [_pack(2, 3, 4), _pack(2, 4, 3)],
          {3: 103, 4: 104})
    cert = auditgraph.certify(str(tmp_path))
    assert not cert["ok"] and len(cert["cycles"]) == 1
    w = cert["cycles"][0]
    assert w["epoch"] == 1 and w["anomaly"] == "G2-item"
    assert {t["tag"] for t in w["txns"]} == {103, 104}
    assert all(t["node"] == 0 for t in w["txns"])
    text = auditgraph.render(cert)
    assert "VIOLATION" in text and "G2-item" in text
    # exit code contract: violation -> 1
    assert auditgraph.main([str(tmp_path)]) == 1


def test_certify_divergence_and_node_filter(tmp_path):
    """Two nodes exporting the SAME epoch must agree bit-for-bit; a
    vdig mismatch is the split-brain signature.  The node filter (the
    chaos oracle excludes fenced/killed nodes) silences it."""
    _emit(tmp_path, 0, 5, [_pack(2, 1, 2)], {1: 11}, vdig=7, lo=0)
    _emit(tmp_path, 1, 5, [_pack(2, 1, 2)], {2: 22}, vdig=8, lo=64)
    cert = auditgraph.certify(str(tmp_path))
    assert cert["divergences"] \
        and cert["divergences"][0]["epoch"] == 5 \
        and "vdig" in cert["divergences"][0]["fields"]
    assert "DIVERGENCE" in auditgraph.render(cert)
    # tag/owner union across the two slices
    assert auditgraph.main([str(tmp_path)]) == 1
    cert1 = auditgraph.certify(str(tmp_path), nodes=[0])
    assert not cert1["divergences"]


def test_certify_incomplete_on_dropped(tmp_path):
    """An epoch whose edge export overflowed the cap degrades the
    certificate to incomplete — reported, never silent."""
    _emit(tmp_path, 0, 2, [_pack(2, 1, 2)], {1: 11}, dropped=17)
    cert = auditgraph.certify(str(tmp_path))
    assert cert["ok"]                    # no cycle in what was seen
    assert not cert["complete"] and cert["dropped_epochs"] == 1
    assert "incomplete" in auditgraph.render(cert)


# ---- default-off contract on the real runtime --------------------------

def test_audit_off_group_outputs():
    """The group jit's output arity is exactly the pre-audit one with
    audit off (state + packed planes), no exporter exists, and the
    [summary] carries no audit_* counters — the d2h volume and the
    sidecar surface are part of the off-contract."""
    node = _solo_server("aud_off_arity")
    try:
        assert node.aud is None
        C, b = node.C, node.b_merged
        W, S = node._width, node._n_scalars
        warm = jax.device_put((
            np.zeros(C * b, bool), np.zeros(C * b, np.int32),
            np.zeros(C * b * W, np.int32), np.zeros(C * b * W, np.int8),
            np.zeros(C * b * S, np.int32)))
        out = node.group_step(node.db, node.cc_state, node.dev_stats,
                              *warm)
        assert len(out) == 4
        assert AUDIT_KEY not in node.db
    finally:
        node.close()


def test_audit_armed_group_outputs_and_export(tmp_path):
    """Armed: the group jit takes the epoch-label feed and returns the
    six-plane audit stack beside the verdict planes; the exporter
    writes a certifiable sidecar record."""
    node = _solo_server("aud_on_arity", audit=True, audit_cadence=1,
                        telemetry_dir=str(tmp_path))
    try:
        assert node.aud is not None and AUDIT_KEY in node.db
        C, b = node.C, node.b_merged
        W, S = node._width, node._n_scalars
        warm = jax.device_put((
            np.zeros(C * b, bool), np.zeros(C * b, np.int32),
            np.zeros(C * b * W, np.int32), np.zeros(C * b * W, np.int8),
            np.zeros(C * b * S, np.int32),
            np.full(C, -1, np.int32)))
        out = node.group_step(node.db, node.cc_state, node.dev_stats,
                              *warm)
        assert len(out) == 5 and len(out[4]) == 6
        edges = np.asarray(out[4][0])
        assert edges.shape == (C, node.cfg.audit_edges_max)
        node.aud.export(0, edges[0], np.asarray(out[4][1])[0], 0, 0,
                        1, 2, commit=0,
                        tags=np.zeros(node.b_loc, np.int64))
        node.aud.close()
        cert = auditgraph.certify(str(tmp_path))
        assert cert["ok"] and cert["epochs"] == 1
        fields = node.aud.fields()
        assert fields["epochs"] == 1
        line = AU.audit_line(0, fields)
        from deneva_tpu.harness.parse import parse_audit
        rows = parse_audit([line])
        assert rows and rows[0]["epochs"] == 1
    finally:
        node.close()


def test_audit_observation_only_row_state():
    """The armed engine's ROW state and verdict counters are
    bit-identical to the off run's — the audit plane observes, never
    decides (the wire-pin/digest half of the acceptance contract; the
    cluster wire bytes are untouched by construction since the audit
    adds no message and no codec)."""
    from deneva_tpu.engine.step import Engine
    from deneva_tpu.runtime.logger import state_digest
    from deneva_tpu.workloads import get_workload

    digests, commits, edge_cnts = [], [], []
    for armed in (False, True):
        cfg = Config(workload=WorkloadKind.YCSB, cc_alg=CCAlg.OCC,
                     audit=armed, audit_cadence=1, epoch_batch=32, conflict_buckets=256,
                     synth_table_size=256, req_per_query=2,
                     max_accesses=2, zipf_theta=0.9,
                     max_txn_in_flight=64)
        eng = Engine(cfg, get_workload(cfg))
        state = eng.init_state()
        for _ in range(6):
            state = eng.jit_step(state)
        digests.append(state_digest(state.db))
        commits.append(int(state.stats["total_txn_commit_cnt"]))
        edge_cnts.append(int(state.stats["audit_edge_cnt"]))
    assert digests[0] == digests[1]
    assert commits[0] == commits[1]
    assert edge_cnts[0] == 0           # off: counter never moves


def test_engine_forwarding_anti_inert():
    """The in-process CALVIN engine at zipf 0.9 produces real in-batch
    wr/rw dependencies — the armed counter must move (a zero here means
    the instrument is dead)."""
    from deneva_tpu.engine.step import Engine
    from deneva_tpu.workloads import get_workload

    cfg = Config(workload=WorkloadKind.YCSB, cc_alg=CCAlg.CALVIN,
                 audit=True, audit_cadence=1, epoch_batch=64, conflict_buckets=256,
                 synth_table_size=256, req_per_query=2, max_accesses=2,
                 zipf_theta=0.9, max_txn_in_flight=128)
    eng = Engine(cfg, get_workload(cfg))
    state = eng.init_state()
    for _ in range(4):
        state = eng.jit_step(state)
    assert int(state.stats["audit_edge_cnt"]) > 0


def test_checkpoint_roundtrip_with_audit(tmp_path):
    """Schema v8: the armed EngineState (audit stamp tables in db +
    the new counters) checkpoints and resumes bit-exactly."""
    from deneva_tpu.engine.checkpoint import load_state, save_state
    from deneva_tpu.engine.step import Engine
    from deneva_tpu.workloads import get_workload

    cfg = Config(workload=WorkloadKind.YCSB, cc_alg=CCAlg.OCC,
                 audit=True, audit_cadence=1, epoch_batch=32, conflict_buckets=256,
                 synth_table_size=256, req_per_query=2, max_accesses=2,
                 max_txn_in_flight=64)
    eng = Engine(cfg, get_workload(cfg))
    state = eng.init_state()
    state = eng.jit_step(state)
    path = str(tmp_path / "aud.npz")
    save_state(path, state)
    restored = load_state(path, eng.init_state())
    np.testing.assert_array_equal(
        np.asarray(state.db[AUDIT_KEY]["epoch"]),
        np.asarray(restored.db[AUDIT_KEY]["epoch"]))


def test_monitor_audit_panel(tmp_path):
    """tools/monitor.py surfaces the latest per-node audit verdict
    (clean / edges-observed / export-overflow) + Prometheus gauges."""
    import importlib
    monitor = importlib.import_module("tools.monitor")

    _emit(tmp_path, 0, 4, [], {})
    _emit(tmp_path, 1, 4, [_pack(2, 1, 2)], {1: 11}, lo=64)
    by_node = monitor.load_audit_dir(str(tmp_path))
    assert sorted(by_node) == [0, 1]
    text = monitor.render_audit(by_node)
    assert "clean" in text and "edges-observed" in text
    prom = monitor.prom_audit(by_node)
    assert 'deneva_audit_edges_total{node="1"} 1' in prom
    assert 'deneva_audit_epochs_total{node="0"} 1' in prom


# ---- end-to-end mutation catch through the cluster epoch body ----------

def test_mutation_caught_and_clean_run_certifies(tmp_path):
    """The anti-inert contract end to end through the REAL merged epoch
    body (make_dist_step): a clean contended OCC run certifies
    serializable; the same run with occ-read-skip seeded on epochs
    [2, 4) is rejected with rw-anomaly witnesses naming epochs inside
    exactly that window.

    The flipped losers only close an rw CYCLE when the drawn queries
    overlap reciprocally, which depends on the PRNG stream — so the test
    sweeps query seeds instead of pinning the one stream of one JAX
    default: every seed's clean run must certify, every seed's mutation
    must take effect (flipped losers commit inside the window and only
    there), every rejection must be witnessed inside the window, and at
    least one seed of the sweep must be rejected."""
    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.engine.epoch import make_dist_step
    from deneva_tpu.workloads import get_workload

    def run(mutate, d, seed):
        d.mkdir()
        cfg = Config(workload=WorkloadKind.YCSB, cc_alg=CCAlg.OCC,
                     dist_protocol="merged", audit=True,
                     audit_cadence=1, audit_mutate=mutate,
                     epoch_batch=128,
                     conflict_buckets=512, synth_table_size=1024,
                     req_per_query=4, max_accesses=4, zipf_theta=0.9,
                     telemetry_dir=str(d))
        wl = get_workload(cfg)
        be = get_backend(cfg.cc_alg)
        step = make_dist_step(cfg, wl, be)
        db, cc = wl.load(), be.init_state(cfg)
        stats = init_device_stats(len(wl.txn_type_names))
        ex = AU.AuditExporter(cfg, 0, 128, 0)
        rng = jax.random.PRNGKey(seed)
        commits = []
        for e in range(6):
            rng, k = jax.random.split(rng)
            q = wl.generate(k, 128)
            out = step(db, cc, stats, jnp.int32(e),
                       jnp.ones(128, bool),
                       jnp.arange(128, dtype=jnp.int32) + e * 128, q)
            db, cc, stats, done = out[:4]
            edges, ebkt, cnt, drop, vdig, rdig = \
                (np.asarray(x) for x in out[8])
            commits.append(int(np.asarray(done).sum()))
            ex.export(e, edges, ebkt, int(cnt), int(drop), int(vdig),
                      int(rdig), commit=commits[-1],
                      tags=np.arange(128, dtype=np.int64))
        ex.close()
        return auditgraph.certify(str(d)), commits

    rejected = None
    for seed in range(8):
        cert, clean = run("", tmp_path / f"clean{seed}", seed)
        assert cert["ok"] and cert["epochs"] == 6
        assert cert["edge_lanes"] > 0      # legal forward rw edges exist
        cert, mut = run("occ-read-skip:2:2", tmp_path / f"mut{seed}", seed)
        # the fault is live on every seed: stale-read losers commit in
        # epochs 2-3, and the epochs before the window are untouched
        assert mut[:2] == clean[:2]
        assert mut[2] > clean[2] and mut[3] > clean[3]
        if not cert["ok"]:
            rejected = cert
            break
    assert rejected is not None, "no seed's mutated run was rejected"
    cert = rejected
    eps = {w["epoch"] for w in cert["cycles"]}
    assert eps and all(2 <= e < 4 for e in eps)
    assert all(w["anomaly"] in ("G-single", "G2-item")
               for w in cert["cycles"])
    w = cert["cycles"][0]
    assert all(t["tag"] is not None and t["node"] == 0
               for t in w["txns"])
