"""Partition-parallel engine on the virtual 8-device CPU mesh.

The analogue of the reference's multi-node-on-one-box IPC rig
(SURVEY §4.4): the full sharded path — partitioned tables, sharded
conflict matmul with cross-device reduction — executes for real.
"""

import numpy as np
import jax
import pytest

from deneva_tpu.config import Config
from deneva_tpu.engine import Engine
from deneva_tpu.parallel import make_mesh, make_sharded_run, state_shardings
from deneva_tpu.workloads import get_workload


# counts of device work, not of the serial semantics: each chip is
# handed its own lanes (every lane of a fingerprint column, whole chunks
# covering its own winners or read heads of a full-row one), so the sum
# over chips is not the single device's
WORK_ONLY = {"write_scatter_lanes", "read_gather_lanes"}


def cfg_for(alg):
    return Config(cc_alg=alg, epoch_batch=64, conflict_buckets=1024,
                  max_accesses=4, req_per_query=4, synth_table_size=4096,
                  zipf_theta=0.6, max_txn_in_flight=256)


@pytest.mark.slow
@pytest.mark.parametrize("alg", ["OCC", "TPU_BATCH", "TIMESTAMP"])
def test_sharded_run_matches_single_device(alg):
    cfg = cfg_for(alg)
    eng = Engine(cfg, get_workload(cfg))

    s0 = eng.init_state(seed=3)
    ref = eng.jit_run(s0, 12)
    ref_stats = {k: np.asarray(v) for k, v in
                 jax.device_get(ref.stats).items()}

    mesh = make_mesh(8)
    place, run = make_sharded_run(eng, mesh)
    s1 = place(eng.init_state(seed=3))
    out = run(s1, 12)
    out_stats = {k: np.asarray(v) for k, v in
                 jax.device_get(out.stats).items()}

    for k in set(ref_stats) - WORK_ONLY:
        assert (ref_stats[k] == out_stats[k]).all(), k


@pytest.mark.slow
def test_partition_parallel_forwarding_matches_single_device():
    """device_parts=8: tables shard owner-major and each device plans +
    executes only its keyspace partition (ycsb.execute_mc under
    shard_map).  Serial semantics are device-count-invariant, so every
    counter — including the read checksum over forwarded values — must
    be bit-identical to the single-device run."""
    cfg = cfg_for("TPU_BATCH")
    eng = Engine(cfg, get_workload(cfg))
    ref = eng.jit_run(eng.init_state(seed=5), 12)
    ref_stats = {k: np.asarray(v) for k, v in
                 jax.device_get(ref.stats).items()}

    cfg8 = cfg.replace(device_parts=8)
    eng8 = Engine(cfg8, get_workload(cfg8))
    mesh = make_mesh(8)
    place, run = make_sharded_run(eng8, mesh)
    out = run(place(eng8.init_state(seed=5)), 12)
    out_stats = {k: np.asarray(v) for k, v in
                 jax.device_get(out.stats).items()}
    for k in set(ref_stats) - WORK_ONLY:
        assert (ref_stats[k] == out_stats[k]).all(), k


@pytest.mark.slow
def test_partition_parallel_full_pool_and_forced_aborts():
    """The multi-chip executor composes with full-pool epochs and the
    forced-abort sentinel (forced txns leave the batch before the
    per-shard plans are built, so no shard applies their writes)."""
    cfg = cfg_for("TPU_BATCH").replace(
        epoch_batch=256, max_txn_in_flight=256, zipf_theta=0.9,
        synth_table_size=4096, ycsb_abort_mode=True)
    ref = Engine(cfg, get_workload(cfg))
    r = ref.jit_run(ref.init_state(seed=2), 10)
    ref_stats = {k: np.asarray(v) for k, v in jax.device_get(r.stats).items()}

    cfg8 = cfg.replace(device_parts=8)
    eng8 = Engine(cfg8, get_workload(cfg8))
    assert eng8.pool.full_pool
    mesh = make_mesh(8)
    place, run = make_sharded_run(eng8, mesh)
    out = run(place(eng8.init_state(seed=2)), 10)
    out_stats = {k: np.asarray(v) for k, v in
                 jax.device_get(out.stats).items()}
    assert int(out_stats["total_txn_abort_cnt"]) > 0
    for k in set(ref_stats) - WORK_ONLY:
        assert (ref_stats[k] == out_stats[k]).all(), k


def _mc_bit_identity(cfg, seed=7, epochs=10):
    """stats of an 8-partition run must equal the single-device run
    bit-for-bit (serial semantics are device-count-invariant; the mc.py
    executor contract makes every counter exactly reconstructable)."""
    eng = Engine(cfg, get_workload(cfg))
    ref = jax.device_get(eng.jit_run(eng.init_state(seed=seed), epochs).stats)
    cfg8 = cfg.replace(device_parts=8)
    eng8 = Engine(cfg8, get_workload(cfg8))
    place, run = make_sharded_run(eng8, make_mesh(8))
    out = jax.device_get(run(place(eng8.init_state(seed=seed)), epochs).stats)
    for k in set(ref) - WORK_ONLY:
        assert (np.asarray(ref[k]) == np.asarray(out[k])).all(), k
    assert int(out["total_txn_commit_cnt"]) > 0
    return out


TPCC_MC = Config(workload="TPCC", cc_alg="TPU_BATCH", epoch_batch=64,
                 conflict_buckets=1024, num_wh=8, cust_per_dist=30,
                 max_items=100, max_accesses=18, max_txn_in_flight=256,
                 insert_table_cap=1 << 10)
PPS_MC = Config(workload="PPS", cc_alg="TPU_BATCH", epoch_batch=64,
                conflict_buckets=1024, pps_parts_cnt=400,
                pps_products_cnt=80, pps_suppliers_cnt=80, pps_parts_per=4,
                max_accesses=9, max_txn_in_flight=256)


@pytest.mark.parametrize("alg", ["TPU_BATCH", "NO_WAIT"])
def test_tpcc_partition_parallel_matches_single_device(alg):
    """TPC-C multi-chip (VERDICT round-1 #1): warehouses shard owner-major
    (the reference's wh_to_part node partition, `benchmarks/
    tpcc_helper.cpp`, across chips); remote-customer payments and
    remote-supply neworder stock rows split across their owners like the
    reference's remote hops (`tpcc_txn.cpp:332-368`)."""
    _mc_bit_identity(TPCC_MC.replace(cc_alg=alg))


@pytest.mark.parametrize("alg", ["TPU_BATCH", "MAAT"])
def test_pps_partition_parallel_matches_single_device(alg):
    """PPS multi-chip: anchor keys stripe across chips; the replicated
    USES/SUPPLIES mapping tables keep recon local (`pps_wl.cpp`)."""
    _mc_bit_identity(PPS_MC.replace(cc_alg=alg))


def test_ycsb_chained_calvin_partition_parallel():
    """CALVIN's chained wavefront execution runs partition-parallel: the
    replicated verdict plays the sequencer broadcast, each chip executes
    its partition's slice of every level."""
    out = _mc_bit_identity(cfg_for("CALVIN"))
    assert int(out["write_cnt"]) > 0


def test_mc_plan_defer_marks_overflow_txns():
    """Sharded-plan capacity (VERDICT r3 missing #2): txns whose owned
    lanes land past a chip's plan buffer defer — a replicated,
    deterministic decision (the MoE capacity pattern with deferral
    instead of dropping)."""
    import jax.numpy as jnp

    from deneva_tpu.ops import mc_plan_defer

    # 4 txns x 2 lanes, every key even -> all owned by chip 0 of D=2.
    # Flat lanes split into two source slices of 4: slice 0 = txns 0-1,
    # slice 1 = txns 2-3.  Priority is AGE (smallest ts first), not
    # slot order: in slice 0 the SECOND txn is older, so capacity 2
    # keeps it and defers the slot-earlier-but-younger first txn —
    # the starvation-freedom property (a deferred txn ages upward).
    keys = jnp.asarray([[0, 2], [4, 6], [8, 10], [12, 14]], jnp.int32)
    valid = jnp.ones((4, 2), bool)
    ts = jnp.asarray([9, 1, 2, 8], jnp.int32)
    dfr = np.asarray(mc_plan_defer(keys, ts, valid, 2, 2))
    assert list(dfr) == [True, False, False, True]
    # ample capacity: nobody defers
    assert not np.asarray(mc_plan_defer(keys, ts, valid, 2, 4)).any()


@pytest.mark.slow
def test_sharded_plan_path_bit_identical_to_single_device():
    """Bit-identity THROUGH the active sharded-plan path: these shapes
    give pair_cap = 512 < sl = 2048 (the all_to_all routing actually
    runs, unlike the small-shape tests whose mc_pair_cap falls back to
    the replicated plan), while moderate skew plus an ample capacity
    factor keeps defers at zero — so every counter, including the read
    checksum over forwarded values, must equal the single-device run."""
    from deneva_tpu.ops import mc_pair_cap

    cfg = cfg_for("TPU_BATCH").replace(
        epoch_batch=4096, max_txn_in_flight=4096, zipf_theta=0.6,
        synth_table_size=8192)
    assert 0 < mc_pair_cap(4096, 4, 8, cfg.mc_plan_capacity) < 4096 * 4 // 8
    eng = Engine(cfg, get_workload(cfg))
    ref = jax.device_get(eng.jit_run(eng.init_state(seed=6), 8).stats)
    cfg8 = cfg.replace(device_parts=8)
    eng8 = Engine(cfg8, get_workload(cfg8))
    place, run = make_sharded_run(eng8, make_mesh(8))
    out = jax.device_get(run(place(eng8.init_state(seed=6)), 8).stats)
    assert int(np.asarray(out["defer_cnt"])) == 0   # capacity ample
    for k in set(ref) - WORK_ONLY:
        assert (np.asarray(ref[k]) == np.asarray(out[k])).all(), k
    assert int(np.asarray(out["total_txn_commit_cnt"])) > 0


@pytest.mark.slow
def test_mc_plan_capacity_overflow_defers_and_recovers():
    """Engine-level: a deliberately tight plan capacity under hot skew
    forces overflow defers; conservation must hold (no drops) and the
    oldest-first retry keeps committing (liveness)."""
    cfg = cfg_for("TPU_BATCH").replace(
        epoch_batch=4096, max_txn_in_flight=4096, zipf_theta=0.9,
        synth_table_size=4096, device_parts=8, mc_plan_capacity=0.25)
    eng = Engine(cfg, get_workload(cfg))
    place, run = make_sharded_run(eng, make_mesh(8))
    out = run(place(eng.init_state(seed=4)), 8)
    stats = {k: np.asarray(v) for k, v in jax.device_get(out.stats).items()}
    inflight = int(np.asarray(jax.device_get(out.pool.occupied)).sum())
    assert int(stats["defer_cnt"]) > 0          # capacity actually bound
    assert int(stats["total_txn_commit_cnt"]) > 0
    assert int(stats["total_txn_commit_cnt"]) + inflight \
        == int(stats["admitted_cnt"])           # no drops
    assert int(stats["total_txn_abort_cnt"]) == 0


def test_state_shardings_partition_tables():
    cfg = cfg_for("TIMESTAMP")
    eng = Engine(cfg, get_workload(cfg))
    state = eng.init_state()
    mesh = make_mesh(8)
    sh = state_shardings(mesh, state)
    from deneva_tpu.parallel.mesh import AXIS
    f0 = sh.db["MAIN_TABLE"].columns["F0"]
    assert f0.spec == jax.sharding.PartitionSpec(AXIS)
    assert sh.cc_state.rts.spec == jax.sharding.PartitionSpec(AXIS)
    assert sh.pool.ts.spec == jax.sharding.PartitionSpec()
