"""Both sides of the owner exchange's gate (`YCSBWorkload.execute_mc`).

A shard counts the lanes of its slice per owner with compares; where
every real owner's count fits its `ops.mc_pair_cap` block the
capacity-defer pass is not run (its mask is all False there), where one
does not the pass runs as it always did.  On a CPU mesh of four, at
shapes whose blocks CAN overflow (256 txns x 10 accesses, factor 0.5:
blocks of 128 lanes in slices of 640), each case holds `execute_mc` to

* `ops.mc_plan_defer` of the replicated batch (the rule's spec): the
  same deferred set on both sides of the gate;
* the PARENT's exchange, kept verbatim below (`_parent_execute_mc`: the
  pass in every epoch, block starts from `jnp.bincount`): the table row
  for row, `read_checksum`, `write_cnt`, the lane counters;
* the count of shards whose slice overflows, which is what
  `mc_defer_pass_cnt` must read.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deneva_tpu.cc.base import AccessBatch
from deneva_tpu.config import Config
from deneva_tpu.engine.step import init_device_stats
from deneva_tpu.ops import (forward_plan_flat, mc_pair_cap, mc_plan_defer)
from deneva_tpu.parallel import AXIS, current_mesh, make_mesh
from deneva_tpu.parallel.mesh import use_mesh
from deneva_tpu.workloads import get_workload
from deneva_tpu.workloads.ycsb import TABLE, _forward_execute_f0

D, B, A = 4, 256, 10
BD = B // D                      # txns a slice
ROWS = 4096
CAP = 128                        # of a slice's 640 lanes


def _parent_execute_mc(wl, db, batch, stats):
    """`execute_mc` as PR 40 left it, sharded-plan mode (`pair_cap` > 0):
    the reference this PR's form must equal to the bit."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    d_parts = wl.cfg.device_parts
    mesh = current_mesh()
    tab = db[TABLE]
    valid = batch.valid & batch.active[:, None]
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    b, a = batch.keys.shape
    pair_cap = mc_pair_cap(b, a, d_parts, wl.cfg.mc_plan_capacity)
    bD = b // d_parts
    sl = bD * a

    def body(f0, keys, rank, ts, is_write, valid):
        me = jax.lax.axis_index(AXIS)
        k2 = jax.lax.dynamic_slice_in_dim(keys, me * bD, bD)
        r2 = jax.lax.dynamic_slice_in_dim(rank, me * bD, bD)
        t2 = jax.lax.dynamic_slice_in_dim(ts, me * bD, bD)
        w2 = jax.lax.dynamic_slice_in_dim(is_write & valid, me * bD, bD)
        v2 = jax.lax.dynamic_slice_in_dim(valid, me * bD, bD)
        ks = jnp.where(v2, k2, big).reshape(-1)
        rs = jnp.broadcast_to(r2[:, None], (bD, a)).reshape(-1)
        tss = jnp.broadcast_to(t2[:, None], (bD, a)).reshape(-1)
        ws = w2.reshape(-1)
        vs = v2.reshape(-1)
        lane = jnp.arange(sl, dtype=jnp.int32)
        owner = jnp.where(vs, ks % d_parts, d_parts)
        so, _, stx = jax.lax.sort((owner, tss, lane // a),
                                  num_keys=2, is_stable=True)
        head = jnp.concatenate([jnp.ones((1,), bool), so[1:] != so[:-1]])
        start = jax.lax.cummax(jnp.where(head, lane, 0))
        over = (lane - start >= pair_cap) & (so != d_parts)
        _, sov = jax.lax.sort((stx, over), num_keys=1, is_stable=True)
        dfr = sov.reshape(bD, a).any(axis=1)
        dfr_lane = jnp.broadcast_to(dfr[:, None], (bD, a)).reshape(-1)
        vs2 = vs & ~dfr_lane
        ks2 = jnp.where(vs2, ks, big)
        ws2 = ws & ~dfr_lane
        owner2 = jnp.where(vs2, ks2 % d_parts, d_parts)
        _, _, ck, cr, cw = jax.lax.sort(
            (owner2, tss, ks2, rs, ws2), num_keys=2, is_stable=True)
        cnt = jnp.bincount(owner2, length=d_parts + 1)
        starts = jnp.cumsum(cnt) - cnt
        blk = [jnp.stack([jax.lax.dynamic_slice_in_dim(
            x, starts[d], pair_cap) for d in range(d_parts)])
            for x in (ck, cr, cw)]
        bk, br, bw = [jax.lax.all_to_all(
            x, AXIS, split_axis=0, concat_axis=0) for x in blk]
        bk, br, bw = (bk.reshape(-1), br.reshape(-1), bw.reshape(-1))
        mine = (bk % d_parts == me) & (bk != big)
        bk = jnp.where(mine, bk, big)
        bw = bw & mine
        p = forward_plan_flat(bk, br, bw)
        trash = jnp.int32(f0.shape[0] - 1)
        slots = jnp.where(p.keys != big, p.keys // d_parts, trash)
        f0, cks, wcnt, lanes, rlanes, _ = _forward_execute_f0(
            f0, p, slots, trash, mono=True)
        return (f0, jax.lax.psum(cks, AXIS), jax.lax.psum(wcnt, AXIS),
                jax.lax.psum(lanes, AXIS), jax.lax.psum(rlanes, AXIS), dfr)

    f0, cks, wcnt, lanes, rlanes, dfr = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(), P(), P(), P(), P()),
        out_specs=(P(AXIS), P(), P(), P(), P(), P(AXIS)))(
            tab.columns["F0"], batch.keys, batch.rank, batch.ts,
            batch.is_write, valid)
    dfr = jax.lax.with_sharding_constraint(dfr, NamedSharding(mesh, P()))
    for k, v in dict(read_checksum=cks, write_cnt=wcnt,
                     write_scatter_lanes=lanes,
                     read_gather_lanes=rlanes).items():
        stats[k] = stats[k] + v
    db = dict(db)
    db[TABLE] = tab._replace(columns={**tab.columns, "F0": f0})
    return db, dfr


def _round_robin(n_valid):
    """Owners i % 4 down a slice's valid lanes, ``n_valid`` a txn: each
    owner gets BD * n_valid / 4 lanes of every slice."""
    owner = np.zeros((B, A), np.int64)
    valid = np.zeros((B, A), bool)
    valid[:, :n_valid] = True
    for s in range(D):
        rows = slice(s * BD, (s + 1) * BD)
        owner[rows, :n_valid] = (
            np.arange(BD * n_valid) % D).reshape(BD, n_valid)
    return owner, valid


def _case(name, rng):
    """(owner int[B, A], valid bool[B, A], active bool[B]) of a case."""
    active = np.ones(B, bool)
    if name == "ample":
        # 96 lanes an owner a slice, under the 128 of a block
        owner, valid = _round_robin(6)
        active[rng.choice(B, 9, replace=False)] = False
    elif name == "one_owner":
        # 640 lanes of every slice want owner 0's block
        owner, valid = np.zeros((B, A), np.int64), np.ones((B, A), bool)
        active[rng.choice(B, 5, replace=False)] = False
    elif name == "one_shard":
        # slice 2 alone overflows (its 640 lanes all owner 1's)
        owner, valid = _round_robin(6)
        owner[2 * BD:3 * BD], valid[2 * BD:3 * BD] = 1, True
    elif name == "all_invalid":
        owner, valid = _round_robin(6)
        valid[:] = False
    else:
        # owner 3 takes the first two lanes of every txn: 128 a slice,
        # a block exactly full; the four others go round
        owner, valid = _round_robin(6)
        owner[:, :2] = 3
        owner[:, 2:6] = np.arange(4 * B).reshape(B, 4) % 3
        if name == "one_past_cap":
            # ... and one lane more in slice 1: 129
            owner[BD + 17, 6], valid[BD + 17, 6] = 3, True
        else:
            assert name == "at_cap"
    return owner, valid, active


CASES = {"ample": 0, "one_owner": 4, "one_shard": 1, "all_invalid": 0,
         "at_cap": 0, "one_past_cap": 1}


@pytest.fixture(scope="module")
def rig():
    cfg = Config(cc_alg="TPU_BATCH", epoch_batch=B, conflict_buckets=1024,
                 max_accesses=A, req_per_query=A, synth_table_size=ROWS,
                 max_txn_in_flight=1024, sim_full_row=True, tup_size=8,
                 field_per_tuple=2, device_parts=D, mc_plan_capacity=0.5)
    assert mc_pair_cap(B, A, D, cfg.mc_plan_capacity) == CAP < BD * A
    wl = get_workload(cfg)
    mesh = make_mesh(D)

    def run(execute):
        def f(db, batch):
            stats = init_device_stats(2, mc_defer_passes=True)
            db, dfr = execute(db, batch, stats)
            return db[TABLE].columns["F0"], dfr, stats
        with use_mesh(mesh):
            return jax.jit(f)
    with use_mesh(mesh):
        db = wl.load()
    return (wl, mesh, db, run(wl.execute_mc),
            run(lambda *a: _parent_execute_mc(wl, *a)))


@pytest.mark.parametrize("name", list(CASES))
def test_the_gate_defers_what_the_rule_defers_and_runs_where_it_must(
        name, rig):
    wl, mesh, db, change, parent = rig
    rng = np.random.default_rng(sorted(CASES).index(name))
    owner, valid, active = _case(name, rng)
    keys = owner + D * rng.integers(0, ROWS // D, (B, A))
    batch = AccessBatch(
        table_ids=jnp.zeros((B, A), jnp.int32),
        keys=jnp.asarray(keys, jnp.int32),
        is_read=jnp.asarray(~(w := rng.random((B, A)) < 0.5)),
        is_write=jnp.asarray(w), valid=jnp.asarray(valid),
        # ages in no slot order: the blocks keep the OLDEST
        ts=jnp.asarray(rng.permutation(B), jnp.int32),
        rank=jnp.arange(B, dtype=jnp.int32), active=jnp.asarray(active))
    live = valid & active[:, None]
    # lanes of slice s for owner d, and the shards whose pass must run
    counts = np.array([[(live[s * BD:(s + 1) * BD]
                         & (owner[s * BD:(s + 1) * BD] == d)).sum()
                        for d in range(D)] for s in range(D)])
    assert int((counts > CAP).any(axis=1).sum()) == CASES[name]
    if name == "at_cap":
        assert (counts.max(axis=1) == CAP).all()
    if name == "one_past_cap":
        assert counts.max() == CAP + 1

    with use_mesh(mesh):
        f0, dfr, stats = change(db, batch)
        f0_p, dfr_p, stats_p = parent(db, batch)
    want = np.asarray(mc_plan_defer(batch.keys, batch.ts, jnp.asarray(live),
                                    D, CAP))
    np.testing.assert_array_equal(np.asarray(dfr), want)
    np.testing.assert_array_equal(np.asarray(dfr_p), want)
    assert want.any() == (CASES[name] > 0)
    if name == "one_past_cap":
        assert want.sum() == 1
    np.testing.assert_array_equal(np.asarray(f0), np.asarray(f0_p))
    for k in ("read_checksum", "write_cnt", "write_scatter_lanes",
              "read_gather_lanes"):
        assert int(stats[k]) == int(stats_p[k]), k
    assert (int(stats["write_cnt"]) > 0) == (name != "all_invalid")
    assert int(stats["mc_defer_pass_cnt"]) == CASES[name]
    assert int(stats_p["mc_defer_pass_cnt"]) == 0     # (never counted)
