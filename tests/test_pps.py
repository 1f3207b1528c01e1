"""PPS workload: loader, mix distribution, recon-path correctness
(planned part accesses must equal the snapshot USES mapping), and
PART_AMOUNT accounting across ORDERPRODUCT/UPDATEPART."""

import numpy as np
import jax
import pytest

from deneva_tpu.config import CCAlg, Config, WorkloadKind
from deneva_tpu.engine import Engine
from deneva_tpu.workloads import get_workload
from deneva_tpu.workloads.pps import (
    GETPARTBYPRODUCT, ORDERPRODUCT, TID, UPDATEPART, UPDATEPRODUCTPART)


def pps_cfg(**kw):
    base = dict(workload=WorkloadKind.PPS, pps_parts_cnt=500,
                pps_products_cnt=100, pps_suppliers_cnt=100, pps_parts_per=4,
                max_accesses=9, epoch_batch=64, conflict_buckets=1024,
                max_txn_in_flight=256, warmup_secs=0.0, done_secs=0.2)
    base.update(kw)
    if "cc_alg" in base:
        base["cc_alg"] = CCAlg(base["cc_alg"])
    return Config(**base)


def test_loader_and_mapping():
    cfg = pps_cfg()
    wl = get_workload(cfg)
    db = wl.load()
    assert set(db) == {"PARTS", "PRODUCTS", "SUPPLIERS", "USES", "SUPPLIES"}
    assert int(db["USES"].row_cnt) == 100 * 4
    pk = db["USES"].host_column("PART_KEY")
    assert pk.min() >= 0 and pk.max() < 500
    assert (db["PARTS"].host_column("PART_AMOUNT") == 10000).all()


def test_mix_distribution():
    cfg = pps_cfg(perc_getpartbyproduct=0.5, perc_orderproduct=0.25,
                  perc_updateproductpart=0.25, perc_updatepart=0.0)
    wl = get_workload(cfg)
    q = jax.device_get(wl.generate(jax.random.PRNGKey(1), 8192))
    frac = np.bincount(q.txn_type, minlength=8) / 8192
    assert abs(frac[GETPARTBYPRODUCT] - 0.5) < 0.05
    assert abs(frac[ORDERPRODUCT] - 0.25) < 0.04
    assert abs(frac[UPDATEPRODUCTPART] - 0.25) < 0.04
    assert frac[UPDATEPART] == 0


def test_recon_plan_matches_snapshot():
    """plan() must declare exactly the part rows the USES snapshot maps:
    the reference's sequencer recon-restart (system/sequencer.cpp:88-115)
    collapsed into one gather."""
    cfg = pps_cfg()
    wl = get_workload(cfg)
    db = wl.load()
    q = wl.generate(jax.random.PRNGKey(2), 64)
    p = jax.device_get(wl.plan(db, q))
    qh = jax.device_get(q)
    uses = db["USES"].host_column("PART_KEY")
    per = cfg.pps_parts_per
    for i in np.where(qh.txn_type == GETPARTBYPRODUCT)[0]:
        want = uses[qh.product_key[i] * per:(qh.product_key[i] + 1) * per]
        got = p["keys"][i, 1 + per:1 + 2 * per]
        np.testing.assert_array_equal(np.sort(got), np.sort(want))
        assert p["table_ids"][i, 1 + per] == TID["PARTS"]
        assert not p["is_write"][i, 1 + per:1 + 2 * per].any()
    for i in np.where(qh.txn_type == ORDERPRODUCT)[0]:
        assert p["is_write"][i, 1 + per:1 + 2 * per].all()


@pytest.mark.parametrize("alg", ["NOCC", "OCC", "TPU_BATCH", "CALVIN"])
def test_pps_runs_and_commits(alg):
    cfg = pps_cfg(cc_alg=alg)
    eng = Engine(cfg, get_workload(cfg))
    state = eng.init_state(0)
    state = eng.jit_run(state, 25)
    stats = jax.device_get(state.stats)
    assert int(stats["total_txn_commit_cnt"]) > 0


def _amount_delta(cfg, epochs=20):
    wl = get_workload(cfg)
    eng = Engine(cfg, wl)
    state = eng.init_state(3)
    a0 = wl.load()["PARTS"].host_column("PART_AMOUNT").astype(np.int64).sum()
    state = eng.jit_run(state, epochs)
    st = jax.device_get(state)
    a1 = np.asarray(st.db["PARTS"].columns["PART_AMOUNT"])[
        :cfg.pps_parts_cnt].astype(np.int64).sum()
    return a1 - a0, int(st.stats["total_txn_commit_cnt"])


def test_escrow_adds_do_not_chain():
    """UPDATEPART / ORDERPRODUCT part updates are order_free escrow
    adds: a pure-add mix must commit (nearly) everything per epoch no
    matter how hot the part rows — add-add pairs carry no conflict
    edges (build_incidence uo) — while the exact accounting above
    guarantees the adds still all land."""
    import jax
    from deneva_tpu.engine import Engine
    from deneva_tpu.workloads import get_workload

    cfg = pps_cfg(cc_alg="TPU_BATCH", pps_parts_cnt=50,
                  perc_getpartbyproduct=0.0, perc_orderproduct=0.5,
                  perc_updateproductpart=0.0, perc_updatepart=0.5)
    eng = Engine(cfg, get_workload(cfg))
    state = eng.jit_run(eng.init_state(1), 25)
    stats = jax.device_get(state.stats)
    commits = int(stats["total_txn_commit_cnt"])
    defers = int(stats["defer_cnt"])
    assert commits > 0
    # GETPART anchors (the remaining ordered reads in this mix) are a
    # small fraction; without the exemption this config defers ~90%
    assert defers < max(commits // 5, 10), (commits, defers)


@pytest.mark.slow
@pytest.mark.parametrize("alg", ["TPU_BATCH", "MVCC"])
def test_part_amount_accounting(alg):
    """Exact accounting per txn type (pure mixes so the audit is exact):
    UPDATEPART adds 100/commit; ORDERPRODUCT subtracts parts_per/commit.
    MVCC included: committed write VALUES must land exactly (the write
    half of MVCC value fidelity, VERDICT r3 next #7)."""
    delta, commits = _amount_delta(pps_cfg(
        cc_alg=alg, perc_getpartbyproduct=0.0, perc_orderproduct=0.0,
        perc_updateproductpart=0.0, perc_updatepart=1.0))
    assert commits > 0 and delta == 100 * commits

    delta, commits = _amount_delta(pps_cfg(
        cc_alg=alg, perc_getpartbyproduct=0.0, perc_orderproduct=1.0,
        perc_updateproductpart=0.0, perc_updatepart=0.0))
    assert commits > 0 and delta == -4 * commits


def test_mvcc_getpart_reads_snapshot_values():
    """MVCC value fidelity for PPS reads (VERDICT r3 next #7): a
    read-only GETPART serializes AT the epoch snapshot, so after
    committed UPDATEPART escrow adds its gathered PART_AMOUNT must be
    the post-update value byte-for-byte — reconstructed exactly by
    regenerating the epoch's query stream and reading the snapshot
    table on the host.  One stale or garbled gather breaks equality."""
    import dataclasses

    import jax

    # phase 1: pure-update MVCC run mutates PART_AMOUNT
    cfg_u = pps_cfg(cc_alg="MVCC", perc_getpartbyproduct=0.0,
                    perc_orderproduct=0.0, perc_updateproductpart=0.0,
                    perc_updatepart=1.0)
    eng_u = Engine(cfg_u, get_workload(cfg_u))
    s_u = eng_u.jit_run(eng_u.init_state(2), 10)
    amt = np.asarray(jax.device_get(
        s_u.db["PARTS"].columns["PART_AMOUNT"]))[:cfg_u.pps_parts_cnt]
    assert (amt != 10000).any(), "phase 1 must mutate the table"

    # phase 2: one full-pool pure-GETPART epoch against the mutated db
    cfg_r = pps_cfg(cc_alg="MVCC", epoch_batch=64, max_txn_in_flight=64,
                    perc_getparts=1.0, perc_getpartbyproduct=0.0,
                    perc_orderproduct=0.0, perc_updateproductpart=0.0,
                    perc_updatepart=0.0)
    wl_r = get_workload(cfg_r)
    eng_r = Engine(cfg_r, wl_r)
    s0 = eng_r.init_state(5)
    # regenerate the epoch's admissions exactly like Engine.step (the
    # rng split) BEFORE the step donates the state buffers
    gen_key = jax.random.split(s0.rng)[1]
    q = jax.device_get(wl_r.generate(gen_key, eng_r.pool.g))
    s0 = dataclasses.replace(s0, db=s_u.db)
    s1 = eng_r.jit_step(s0)
    got = int(jax.device_get(s1.stats["read_checksum"]))

    keys = np.asarray(q.part_key)
    ref = int(amt[keys].astype(np.int64).sum()) & 0xFFFFFFFF
    assert got == ref


# ---- the written mapping: stale reconnaissance (PR 38) -------------------

from deneva_tpu.workloads.pps import (  # noqa: E402
    GETPART, GETPARTBYSUPPLIER, PPSQuery)


def _served(cfg, recon_defers=True):
    """The served epoch body of ``cfg`` (`engine/epoch.make_epoch_body`),
    jitted, with a fresh database and the counters a served PPS program
    carries: (step, state dict, workload)."""
    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine.epoch import make_epoch_body
    from deneva_tpu.engine.step import init_device_stats
    wl = get_workload(cfg)
    be = get_backend(cfg.cc_alg)
    body, b = make_epoch_body(cfg, wl, be)
    assert b == cfg.epoch_batch
    st = dict(db=wl.load(), cc=be.init_state(cfg), stats=init_device_stats(
        len(wl.txn_type_names), level_passes=be.chained,
        recon_defers=recon_defers))
    return jax.jit(body), st, wl


def _epoch(step, st, lanes, b):
    """Run one epoch of ``lanes`` (rows of txn_type, part, product,
    supplier; rank = row) padded to ``b``: (done, abort, defer) of the
    live lanes."""
    n = len(lanes)
    pad = np.zeros((b, 4), np.int32)
    pad[:n] = lanes
    q = PPSQuery(*(jax.numpy.asarray(pad[:, i]) for i in range(4)))
    active = jax.numpy.arange(b) < n
    ts = jax.numpy.arange(1, b + 1, dtype=jax.numpy.int32)
    st["db"], st["cc"], st["stats"], done, abort, defer, *_ = step(
        st["db"], st["cc"], st["stats"], active, ts, q)
    return tuple(np.asarray(m)[:n] for m in (done, abort, defer))


def test_updateproductpart_rewrites_uses_and_a_later_walk_resolves_it():
    cfg = pps_cfg(cc_alg="TPU_BATCH")
    step, st, wl = _served(cfg)
    per = cfg.pps_parts_per
    assert "PRODUCT_PART" not in st["db"]["PRODUCTS"].columns
    old = st["db"]["USES"].host_column("PART_KEY")[3 * per:4 * per].copy()
    new = int((old[0] + 7) % cfg.pps_parts_cnt)
    done, _, defer = _epoch(step, st, [[UPDATEPRODUCTPART, new, 3, 0]], 64)
    assert done.all() and not defer.any()
    uses = st["db"]["USES"].host_column("PART_KEY")
    assert uses[3 * per] == new and (uses[3 * per + 1:4 * per]
                                     == old[1:]).all()
    # the next epoch's walk is planned from the written mapping, reads
    # the new part row, and its reads reach the checksum
    q = PPSQuery(*(jax.numpy.asarray([v]) for v in (GETPARTBYPRODUCT, 0, 3,
                                                    0)))
    p = jax.device_get(wl.plan(st["db"], q))
    assert list(p["keys"][0, 1 + per:1 + 2 * per]) == [new, *old[1:]]
    st["db"]["PARTS"] = st["db"]["PARTS"].scatter_add(
        jax.numpy.asarray([new]), {"PART_AMOUNT": jax.numpy.asarray([5])})
    before = int(st["stats"]["read_checksum"])
    done, _, _ = _epoch(step, st, [[GETPARTBYPRODUCT, 0, 3, 0]], 64)
    assert done.all()
    assert int(st["stats"]["read_checksum"]) - before == 10000 * per + 5


def test_the_walks_reads_cover_the_full_width_row():
    """With ``sim_full_row`` a walk's and a GETPART's read of a part row
    folds the row's hundred string bytes in beside PART_AMOUNT; an
    ORDERPRODUCT's ten decrements read nothing into the checksum."""
    from deneva_tpu.workloads.pps import FIELDS
    cfg = pps_cfg(cc_alg="TPU_BATCH", sim_full_row=True).validate()
    step, st, wl = _served(cfg)
    per = cfg.pps_parts_per
    parts = st["db"]["PARTS"]
    assert parts.columns[FIELDS].shape == (parts.columns["PART_KEY"].shape[0],
                                           100)
    rowsum = np.asarray(parts.columns[FIELDS]).sum(axis=1, dtype=np.int64)
    assert rowsum[:cfg.pps_parts_cnt].min() > 0 \
        and not rowsum[cfg.pps_parts_cnt:].any()
    uses = st["db"]["USES"].host_column("PART_KEY")
    sup = st["db"]["SUPPLIES"].host_column("PART_KEY")
    done, _, _ = _epoch(step, st, [[GETPARTBYPRODUCT, 0, 5, 0],
                                   [GETPARTBYSUPPLIER, 0, 0, 9],
                                   [GETPART, 17, 0, 0],
                                   [ORDERPRODUCT, 0, 60, 0]], 64)
    assert done.all()
    read = np.concatenate([uses[5 * per:6 * per], sup[9 * per:10 * per],
                           [17]])
    assert int(st["stats"]["read_checksum"]) == \
        (10000 * len(read) + rowsum[read].sum()) & 0xFFFFFFFF


_MIX = dict(perc_getparts=0.1, perc_getpartbyproduct=0.25,
            perc_getpartbysupplier=0.1, perc_orderproduct=0.25,
            perc_updateproductpart=0.2, perc_updatepart=0.1)


@pytest.mark.parametrize("alg", ["TPU_BATCH", "CALVIN", "OCC"])
def test_stale_rule_against_a_brute_force_serial_execution(alg):
    """Twelve served epochs at a toy size where most products are
    rewritten every epoch.  The lanes an epoch sends back come again at
    the head of the next.  Held: the tables and the read checksum equal
    the execution of each epoch's committed lanes ONE AFTER ANOTHER in
    rank order on numpy tables (every walk resolving its parts at its
    own place in that order); under the chained backends
    `recon_defer_cnt` equals the rule's count (walks with an earlier
    active UPDATEPRODUCTPART of their product in the epoch), each of
    those lanes is deferred and commits in the next epoch; under OCC
    the rule does nothing and no walk commits behind a committed writer
    of its product."""
    cfg = pps_cfg(cc_alg=alg, pps_parts_cnt=60, pps_products_cnt=20,
                  pps_suppliers_cnt=20, exec_subrounds=16, **_MIX)
    b, per = cfg.epoch_batch, cfg.pps_parts_per
    step, st, wl = _served(cfg)
    amount = st["db"]["PARTS"].host_column("PART_AMOUNT").astype(np.int64)
    amount = amount[:cfg.pps_parts_cnt].copy()
    uses = st["db"]["USES"].host_column("PART_KEY").copy()
    sup = st["db"]["SUPPLIES"].host_column("PART_KEY").copy()
    rng = np.random.default_rng(38)
    chained = alg != "OCC"
    checksum = rule_count = commits = 0
    again = np.zeros((0, 4), np.int32)
    was_stale = 0
    for _ in range(12):
        fresh = np.asarray(jax.device_get(wl.to_wire(wl.generate(
            jax.random.PRNGKey(int(rng.integers(1 << 30))),
            b - len(again)))[2]))
        lanes = np.concatenate([again, fresh])
        t, prod = lanes[:, 0], lanes[:, 2]
        walk = (t == GETPARTBYPRODUCT) | (t == ORDERPRODUCT)
        stale = np.array([walk[i] and any(
            t[j] == UPDATEPRODUCTPART and prod[j] == prod[i]
            for j in range(i)) for i in range(b)])
        done, abort, defer = _epoch(step, st, lanes, b)
        assert not (done & (abort | defer)).any()
        if chained:
            rule_count += stale.sum()
            assert (defer & stale).sum() == stale.sum() and not abort.any()
            # what waited last epoch stands at the head and commits now
            assert done[:was_stale].all()
            was_stale = int(stale.sum())
            assert was_stale > 0
        for i in np.flatnonzero(done):      # serially, in rank order
            kind, part, product, supplier = (int(x) for x in lanes[i])
            now = uses[product * per:(product + 1) * per]
            if kind == UPDATEPRODUCTPART:
                uses[product * per] = part
            elif kind == ORDERPRODUCT:
                np.subtract.at(amount, now, 1)
            elif kind == GETPARTBYPRODUCT:
                checksum += amount[now].sum()
            elif kind == GETPARTBYSUPPLIER:
                checksum += amount[sup[supplier * per:(supplier + 1) * per]
                                   ].sum()
            elif kind == GETPART:
                checksum += amount[part]
            elif kind == UPDATEPART:
                amount[part] += 100
            if not chained and kind in (GETPARTBYPRODUCT, ORDERPRODUCT):
                assert not any(done[j] and t[j] == UPDATEPRODUCTPART
                               and prod[j] == product for j in range(i))
        commits += int(done.sum())
        again = lanes[~done]
        if chained:                 # the stale ones lead, in their order
            again = np.concatenate([lanes[defer & stale],
                                    lanes[defer & ~stale]])
    stats = jax.device_get(st["stats"])
    assert commits == int(stats["total_txn_commit_cnt"]) > 300
    assert int(stats["recon_defer_cnt"]) == (rule_count if chained else 0)
    assert int(stats["read_checksum"]) == checksum & 0xFFFFFFFF
    np.testing.assert_array_equal(
        st["db"]["USES"].host_column("PART_KEY"), uses)
    np.testing.assert_array_equal(
        st["db"]["PARTS"].host_column("PART_AMOUNT")[:cfg.pps_parts_cnt],
        amount)
    if chained:
        assert int(stats["defer_cnt"]) >= rule_count > 20
        assert int(stats["level_pass_cnt"]) > 12


def test_a_plan_that_marks_no_recon_traces_no_stale_test(monkeypatch):
    """The rule is keyed on the plan's mark alone: a workload whose plan
    marks nothing (TPC-C; PPS with the mark taken off) never reaches
    `stale_recon` and traces to the same jaxpr as with the rule's code
    made unreachable; the marked PPS plan traces to a longer one."""
    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine import epoch
    from deneva_tpu.engine.step import init_device_stats

    def jaxpr_of(cfg, wl):
        be = get_backend(cfg.cc_alg)
        body, b = epoch.make_epoch_body(cfg, wl, be)
        q = wl.generate(jax.random.PRNGKey(0), b)
        return str(jax.make_jaxpr(body)(
            wl.load(), be.init_state(cfg),
            init_device_stats(len(wl.txn_type_names), level_passes=True),
            jax.numpy.ones(b, bool), jax.numpy.arange(1, b + 1), q))

    cfg = pps_cfg(cc_alg="TPU_BATCH")
    marked = get_workload(cfg)
    plain = get_workload(cfg)
    plan = plain.plan
    plain.plan = lambda db, q: {k: v for k, v in plan(db, q).items()
                                if k != "recon"}
    tpcc_cfg = Config(workload="TPCC", cc_alg="TPU_BATCH", epoch_batch=64,
                      num_wh=2, cust_per_dist=30, max_items=100,
                      max_accesses=18, insert_table_cap=1 << 10)
    before = [jaxpr_of(cfg, plain),
              jaxpr_of(tpcc_cfg, get_workload(tpcc_cfg))]
    with_mark = jaxpr_of(cfg, marked)

    def unreachable(batch):
        raise AssertionError("stale_recon reached")
    monkeypatch.setattr(epoch, "stale_recon", unreachable)
    assert [jaxpr_of(cfg, plain),
            jaxpr_of(tpcc_cfg, get_workload(tpcc_cfg))] == before
    with pytest.raises(AssertionError, match="stale_recon reached"):
        jaxpr_of(cfg, marked)
    assert len(with_mark) > len(before[0])
