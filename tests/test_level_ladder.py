"""`engine/epoch.run_levels`' ladder (PR 39): a level pass runs on the
narrowest static width that holds its level's live transactions, moved
to the front in lane order — and is the SAME execution as the whole-batch
pass: every table leaf, ring cursor and device counter equal, but for the
counters of lanes HANDED to a call and `narrow_pass_cnt`.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deneva_tpu.cc import Verdict, get_backend
from deneva_tpu.config import Config
from deneva_tpu.engine import epoch
from deneva_tpu.engine.step import init_device_stats
from deneva_tpu.workloads import get_workload

# what follows the width a pass is called at, and how often it was narrow
FOLLOW_WIDTH = {"write_scatter_lanes", "read_gather_lanes",
                "narrow_pass_cnt"}


def test_the_ladder_is_a_function_of_the_batch_alone():
    assert epoch.level_widths(64) == (64,)
    assert epoch.level_widths(128) == (128,)
    assert epoch.level_widths(129) == (32, 128, 129)
    assert epoch.level_widths(256) == (32, 128, 256)
    assert epoch.level_widths(300) == (32, 128, 300)
    assert epoch.level_widths(1024) == (32, 128, 1024)
    assert epoch.level_widths(16384) == (32, 128, 16384)
    # no Config field, flag or environment variable steers it
    assert not [f.name for f in dataclasses.fields(Config)
                if "ladder" in f.name or "level_width" in f.name]


# ---- hand-made level vectors on an executor that records its calls -------

class _Recorder:
    """An executor that appends, a call, its live lanes' (tag, order) to
    a log in lane order and folds them into a checksum weighted by the
    call's number: equal logs = the same lanes, in the same order, in
    the same pass, with the same ``order`` values."""

    def execute(self, db, q, mask, order, stats, fwd_rank=None,
                level_exec=False):
        n = mask.shape[0]
        k = mask.astype(jnp.int32)
        at = jnp.where(mask, db["cnt"] + jnp.cumsum(k) - k, db["log"].shape[0])
        log = db["log"].at[at].set(
            jnp.stack([q["tag"], order, jnp.full((n,), db["calls"])], 1),
            mode="drop")
        stats["write_scatter_lanes"] = stats["write_scatter_lanes"] + \
            jnp.uint32(n)
        # a masked lane's query must not reach the tables
        seen = jnp.where(mask, q["tag"], 0).sum()
        return dict(log=log, cnt=db["cnt"] + k.sum(), calls=db["calls"] + 1,
                    seen=db["seen"] + seen * (db["calls"] + 1))


def _levels_case(name, b):
    """(levels, commit) of a hand-made epoch of ``b`` lanes."""
    lane = np.arange(b)
    commit = np.ones(b, bool)
    if name.startswith("a_level_of_exactly_"):
        w = int(name.rsplit("_", 1)[1])
        lv = np.where(lane < b - w, 0, 1)
    elif name.startswith("a_level_of_one_more_than_"):
        w = int(name.rsplit("_", 1)[1])
        lv = np.where(lane < b - w - 1, 0, 1)
    elif name == "an_empty_level_between_two_full_ones":
        lv = np.where(lane % 2 == 0, 0, 2)
    elif name == "every_lane_at_level_0":
        lv = np.zeros(b, int)
    elif name == "interleaved_levels_and_lanes_that_did_not_commit":
        lv = (lane * 7) % 5
        commit = lane % 3 != 0
    elif name == "a_late_level_wider_than_the_ones_before_it":
        lv = np.where(lane < 8, 0, np.where(lane < 20, 1, 2))
    else:
        raise KeyError(name)
    return lv.astype(np.int32), commit


@pytest.mark.parametrize("b", [512, 300])
@pytest.mark.parametrize("case", [
    "a_level_of_exactly_32", "a_level_of_one_more_than_32",
    "a_level_of_exactly_128", "a_level_of_one_more_than_128",
    "an_empty_level_between_two_full_ones", "every_lane_at_level_0",
    "interleaved_levels_and_lanes_that_did_not_commit",
    "a_late_level_wider_than_the_ones_before_it"])
def test_hand_made_levels_execute_as_the_whole_batch_pass_does(
        case, b, monkeypatch):
    lv, commit = _levels_case(case, b)
    widths = epoch.level_widths(b)
    q = {"tag": jnp.arange(1, b + 1, dtype=jnp.int32) * 3}
    order = jnp.asarray((np.arange(b) * 5 + 2) % b, jnp.int32)
    verdict = Verdict(commit=jnp.asarray(commit),
                      abort=jnp.zeros(b, bool), defer=jnp.zeros(b, bool),
                      order=order, level=jnp.asarray(lv))

    def run():
        db = dict(log=jnp.zeros((b, 3), jnp.int32), cnt=jnp.int32(0),
                  calls=jnp.int32(0), seen=jnp.int32(0))
        stats = {k: jnp.uint32(0) for k in (
            "level_pass_cnt", "narrow_pass_cnt", "write_scatter_lanes")}
        return jax.device_get(jax.jit(
            lambda db, st: epoch.run_levels(
                None, _Recorder(), db, q, verdict.commit, verdict, st))(
                    db, stats))

    db, st = run()
    monkeypatch.setattr(epoch, "level_widths", lambda n: (n,))
    db1, st1 = run()
    for k in db:
        np.testing.assert_array_equal(db[k], db1[k], err_msg=k)
    # the whole-batch pass, spelled out: a level's committed lanes in
    # lane order, one call a level up to the deepest committed one
    deepest = int(lv[commit].max())
    want = [(3 * (i + 1), int(order[i]), l) for l in range(deepest + 1)
            for i in range(b) if commit[i] and lv[i] == l]
    assert [tuple(r) for r in db["log"][:len(want)]] == want
    assert int(db["cnt"]) == len(want) and int(db["calls"]) == deepest + 1
    assert int(st["level_pass_cnt"]) == int(st1["level_pass_cnt"]) \
        == deepest + 1
    # which passes ran narrow, and at which width: by the level's count
    sizes = [int((commit & (lv == l)).sum()) for l in range(deepest + 1)]
    at = [min(w for w in widths if n <= w) for n in sizes]
    assert int(st["narrow_pass_cnt"]) == sum(w < b for w in at)
    assert int(st["write_scatter_lanes"]) == sum(at)
    assert int(st1["narrow_pass_cnt"]) == 0 \
        and int(st1["write_scatter_lanes"]) == b * (deepest + 1)


# ---- the served epoch body on the three chained executors ----------------

def _tpcc(**over):
    return Config(**{**dict(
        workload="TPCC", cc_alg="TPU_BATCH", epoch_batch=512, num_wh=8,
        cust_per_dist=64, max_items=512, max_accesses=18,
        insert_table_cap=1 << 9, tpcc_full_schema=True, sim_full_row=True,
        perc_payment=0.5), **over}).validate()


def _pps(**over):
    return Config(**{**dict(
        workload="PPS", cc_alg="TPU_BATCH", epoch_batch=512,
        sim_full_row=True, pps_parts_cnt=2000, pps_products_cnt=200,
        pps_suppliers_cnt=200, max_accesses=21, exec_subrounds=8),
        **over}).validate()


def _served_epochs(cfg, epochs=6):
    """``epochs`` served epochs (`make_epoch_body`, the counters a served
    chained program carries) of generated traffic: (db, stats) on the
    host."""
    wl, be = get_workload(cfg), get_backend(cfg.cc_alg)
    body, b = epoch.make_epoch_body(cfg, wl, be)
    db, cc = wl.load(), be.init_state(cfg)
    stats = init_device_stats(
        len(wl.txn_type_names), level_passes=True,
        append_lanes=cfg.workload == "TPCC",
        recon_defers=cfg.workload == "PPS")
    step = jax.jit(body)
    for e in range(epochs):
        q = wl.generate(jax.random.PRNGKey(100 + e), b)
        ts = jnp.arange(1, b + 1, dtype=jnp.int32) + e * b
        db, cc, stats, *_ = step(db, cc, stats, jnp.ones(b, bool), ts, q)
    return jax.device_get((db, stats))


@pytest.mark.parametrize("make,over,epochs", [
    (_tpcc, dict(exec_subrounds=8), 10), (_pps, {}, 6),
    (_tpcc, dict(cc_alg="DGCC"), 6)],
    ids=["tpcc_full_schema_rings_that_wrap", "pps_exec_subrounds_8",
         "dgcc_level_exec_false"])
def test_served_epochs_equal_the_one_rung_epochs_leaf_for_leaf(
        make, over, epochs, monkeypatch):
    cfg = make(**over)
    db, st = _served_epochs(cfg, epochs)
    monkeypatch.setattr(epoch, "level_widths", lambda n: (n,))
    db1, st1 = _served_epochs(cfg, epochs)
    leaves = jax.tree_util.tree_leaves_with_path(db)
    assert len(leaves) == (92 if cfg.workload == "TPCC" else 16)
    for (path, x), y in zip(leaves, jax.tree.leaves(db1)):
        np.testing.assert_array_equal(
            x, y, err_msg=jax.tree_util.keystr(path))
    assert set(st) == set(st1)
    for k in set(st) - FOLLOW_WIDTH:
        np.testing.assert_array_equal(st[k], st1[k], err_msg=k)
    passes = int(st["level_pass_cnt"])
    assert passes > 6 and int(st["total_txn_commit_cnt"]) > 1000
    assert 0 < int(st["narrow_pass_cnt"]) <= passes
    assert int(st1["narrow_pass_cnt"]) == 0
    assert int(st["write_scatter_lanes"]) < int(st1["write_scatter_lanes"])
    if cfg.workload == "TPCC":
        # the rings wrapped (512 orders), in passes of either kind:
        # cursors run free, mod on use
        for ring in ("HISTORY", "ORDER", "NEW-ORDER", "ORDER-LINE"):
            assert int(db[ring].row_cnt) > db[ring].capacity, ring
        assert int(st["append_scatter_lanes"]) == 0
    else:
        assert int(st["read_gather_lanes"]) < int(st1["read_gather_lanes"])
        assert int(st["recon_defer_cnt"]) > 0


# ---- at epoch_batch 128: one executor body, as before --------------------

@pytest.mark.parametrize("make", [_tpcc, _pps], ids=["tpcc", "pps"])
def test_a_batch_of_128_traces_to_one_executor_body(make, monkeypatch):
    """Why every file under `tests/benchmark/` passes unedited: their toy
    launches run `epoch_batch` 128, where the ladder has one rung — the
    served epoch body calls the executor once (inside the loop), orders
    nothing, and `narrow_pass_cnt` stays 0.  At 256 it holds three."""
    def traced(b):
        cfg = make(epoch_batch=b)
        wl, be = get_workload(cfg), get_backend(cfg.cc_alg)
        calls = []
        execute = wl.execute

        def counting(db, q, mask, *a, **kw):
            calls.append(mask.shape[0])
            return execute(db, q, mask, *a, **kw)
        wl.execute = counting
        body, _ = epoch.make_epoch_body(cfg, wl, be)
        stats = init_device_stats(len(wl.txn_type_names), level_passes=True)
        jaxpr = jax.make_jaxpr(body)(
            wl.load(), be.init_state(cfg), stats, jnp.ones(b, bool),
            jnp.arange(1, b + 1, dtype=jnp.int32),
            wl.generate(jax.random.PRNGKey(0), b))
        return calls, str(jaxpr)

    calls, text = traced(128)
    assert calls == [128]
    calls, wider = traced(256)
    assert calls == [32, 128, 256] and "sort" in wider
    # the program of 128 lanes is the one-rung program, to the letter
    monkeypatch.setattr(epoch, "level_widths", lambda n: (n,))
    assert traced(128) == ([128], text)
    monkeypatch.undo()
    # and run: the counter is carried and stays 0
    _, st = _served_epochs(make(epoch_batch=128), epochs=2)
    assert int(st["level_pass_cnt"]) >= 2 and int(st["narrow_pass_cnt"]) == 0


# ---- served toy launches at epoch_batch 256 against the serial references -

def _bench_script(rel):
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    spec = importlib.util.spec_from_file_location(
        "ladder_" + os.path.basename(rel)[:-3], os.path.join(bench, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cell,ref,toy", [
    ("tpcc_fullschema_tpubatch.mixed", "references/tpcc_serial.py",
     dict(num_wh=4, cust_per_dist=64, max_items=128,
          insert_table_cap=1 << 14)),
    ("pps_fullrow_tpubatch.lookup_order_update", "references/pps_serial.py",
     dict(pps_parts_cnt=200, pps_products_cnt=40, pps_suppliers_cnt=40)),
], ids=["tpcc", "pps"])
def test_served_launch_of_256_lanes_is_what_the_serial_reference_computes(
        cell, ref, toy, tmp_path, monkeypatch):
    """The benchmark's toy cells at `epoch_batch` 256 (three rungs): the
    plain numpy references reproduce every leaf, the counts and PPS's
    read checksum of a launch most of whose passes ran 32 or 128 wide."""
    run, ref = _bench_script("run.py"), _bench_script(ref)
    b = 256
    c = run.load_cell(cell)
    c["config_file"]["fields"].update(
        epoch_batch=b, pipeline_epochs=4, max_txn_in_flight=8192,
        client_batch_size=b, **toy)
    c["traffic_file"].update(warmup_secs=0.5, ring_txns=1 << 13)
    monkeypatch.setattr(run, "SERVER_PLATFORM", "cpu")
    res, fields, log, _ = run.logged_launch(c, 3_000_000_019, str(tmp_path))
    info = res["server"]["info"]
    checks, notes = ref.verify(log, fields, info)
    assert [n for n, v, lim in checks if v > lim] == [], (checks, notes)
    sz = ref.Sizes(fields)
    tab, _ = ref.replay(log, sz)
    ours = ref.digests(ref.columns(sz, tab))
    assert ours == info["column_digests"]
    assert len(ours) == (92 if "tpcc" in cell else 16)
    assert notes["commits"] == info["run_commit_cnt"] > 1000
    s = res["server"]["summary"]
    assert 0 < s["narrow_pass_cnt"] <= s["level_pass_cnt"]
    assert s["level_pass_cnt"] > s["stage_epoch_cnt"] > 0
    # lanes HANDED to a call follow the width: a narrow pass hands its
    # executor 32 or 128 transactions, a whole one 256
    per_lane = 7 + 3 * 15 if "tpcc" in cell else 12
    narrow, whole = s["narrow_pass_cnt"], \
        s["level_pass_cnt"] - s["narrow_pass_cnt"]
    lanes = s["write_scatter_lane_cnt"]
    assert lanes % (32 * per_lane) == 0
    assert per_lane * (32 * narrow + b * whole) <= lanes \
        <= per_lane * (128 * narrow + b * whole) \
        < per_lane * b * s["level_pass_cnt"]
