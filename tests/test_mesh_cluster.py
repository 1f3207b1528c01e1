"""Mesh-sharded measured cluster path (pod-scale PR): `device_parts=8`
through the REAL server loop — transport, admission, epoch groups,
verdict planes, CL_RSP acks, command log, replica stream — must be
bit-identical to `device_parts=1` on the same config, per backend.

conftest.py forces an 8-way fake-device CPU mesh
(`--xla_force_host_platform_device_count=8`), so these run in tier-1.
The engine-level bit-identity of `workloads/mc.py` is test_parallel's
job; here the oracle is the full cluster surface: the bytes a client
or replica could observe, plus digest-vs-replay of the sharded state
through the same mesh-wrapped per-epoch jit recovery uses.
"""

import os
import threading
import time as _time
import uuid

import numpy as np
import pytest

from deneva_tpu.config import CCAlg, Config, WorkloadKind


def _mesh_cfg(log_dir: str, device_parts: int, **kw) -> Config:
    base = dict(
        workload=WorkloadKind.YCSB, cc_alg=CCAlg.TPU_BATCH,
        node_cnt=1, client_node_cnt=1, epoch_batch=64,
        conflict_buckets=512, synth_table_size=512, req_per_query=4,
        max_accesses=4, max_txn_in_flight=1024, zipf_theta=0.9,
        pipeline_epochs=2, pipeline_groups=2, logging=True,
        log_dir=log_dir, warmup_secs=0.0, done_secs=0.0,
        device_parts=device_parts, owner_check=True)
    base.update(kw)
    return Config(**base)


def _drive_mesh_run(tmp_path, device_parts: int, replica: bool = True,
                    **kw) -> dict:
    """One deterministic single-server cluster run with the test posing
    as the client (the `_drive_overlap_run` rig from test_runtime.py):
    all query batches are delivered BEFORE the INIT_DONE barrier and
    warmup/done are zero, so admission, epochs and verdicts are a pure
    function of the config — which is what makes the device_parts=1 and
    =8 runs byte-comparable."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from deneva_tpu.runtime import wire
    from deneva_tpu.runtime.logger import state_digest
    from deneva_tpu.runtime.native import NativeTransport, ipc_endpoints
    from deneva_tpu.runtime.replica import ReplicaNode
    from deneva_tpu.runtime.server import ServerNode
    from deneva_tpu.workloads import get_workload

    log_dir = str(tmp_path / f"logs_mesh_{device_parts}")
    n_nodes = 3 if replica else 2
    cfg = _mesh_cfg(log_dir, device_parts,
                    replica_cnt=1 if replica else 0, **kw)
    eps = ipc_endpoints(n_nodes, uuid.uuid4().hex[:8])
    wl = get_workload(cfg)
    batches = []
    for s in range(3):          # 192 txns, distinct tag ranges
        q = wl.generate(jax.random.PRNGKey(100 + s), 64)
        k, t, sc = wl.to_wire(q)
        batches.append((np.arange(64, dtype=np.int64) + 64 * s, k, t, sc))

    out: dict = {}

    def run_server():
        node = ServerNode(cfg.replace(node_id=0, part_cnt=1), eps, "cpu")
        try:
            assert (node.mesh is not None) == (device_parts > 1)
            node.run()
            out["digest"] = state_digest(node.db)
            out["commits"] = int(jax.device_get(
                node.dev_stats["total_txn_commit_cnt"]))
            out["aborts"] = int(jax.device_get(
                node.dev_stats["total_txn_abort_cnt"]))
            out["prefetch"] = (node._prefetch_hits, node._prefetch_polls)
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
    ts_srv.start()
    ts_rep = None
    if replica:
        ts_rep = threading.Thread(target=run_replica)
        ts_rep.start()
    cl = NativeTransport(1, eps, n_nodes)
    cl.start()
    acked: list[int] = []
    try:
        for tags, k, t, sc in batches:
            cl.sendv(0, "CL_QRY_BATCH", wire.qry_block_parts(tags, k, t, sc))
        cl.flush()

        def on_other(src, rtype, payload):
            if rtype == "CL_RSP":
                acked.extend(wire.decode_cl_rsp(payload).tolist())

        wire.run_barrier(cl, 1, n_nodes, on_other, "mesh-test client",
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
        if ts_rep is not None:
            ts_rep.join(timeout=60)
        cl.close()
    assert "err" not in out, out["err"]
    with open(os.path.join(log_dir, "node0.log.bin"), "rb") as f:
        out["log"] = f.read()
    if replica:
        with open(os.path.join(log_dir, "replica2.log.bin"), "rb") as f:
            out["rlog"] = f.read()
    out["acked"] = sorted(acked)
    out["cfg"] = cfg.replace(node_id=0, part_cnt=1)
    out["log_path"] = os.path.join(log_dir, "node0.log.bin")
    return out


def _replay_digest(run: dict) -> str:
    """Digest-vs-replay half of the oracle: re-execute the command log
    through the mesh-wrapped per-epoch jit (exactly what crash recovery
    does) into fresh sharded state and hash the result."""
    import jax

    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.parallel.mesh import (make_mesh, state_shardings,
                                          use_mesh)
    from deneva_tpu.runtime.logger import replay_into, state_digest
    from deneva_tpu.engine.epoch import make_dist_step
    from deneva_tpu.workloads import get_workload

    cfg = run["cfg"]
    wl = get_workload(cfg)
    be = get_backend(cfg.cc_alg)
    db = wl.load()
    cc = be.init_state(cfg)
    stats = init_device_stats(
        len(getattr(wl, "txn_type_names", ("txn",))))
    step = make_dist_step(cfg, wl, be)
    if cfg.device_parts > 1:
        mesh = make_mesh(cfg.device_parts)
        state = {"db": db, "cc_state": cc, "stats": stats}
        state = jax.device_put(state, state_shardings(mesh, state))
        db, cc, stats = state["db"], state["cc_state"], state["stats"]
        inner = step

        def step(*a, **kw):
            with use_mesh(mesh):
                return inner(*a, **kw)
    db, cc, stats, last = replay_into(run["log_path"], cfg, wl, step,
                                      db, cc, stats)
    assert last >= 0, "empty command log"
    return state_digest(db)


def test_mesh_cluster_ycsb_bit_identical(tmp_path):
    """YCSB/TPU_BATCH (the forwarding executor → `wl.execute_mc` owner
    exchange): device_parts=8 through the measured cluster path must
    reproduce device_parts=1's command log, replica stream, commit
    counters and acked-tag multiset byte for byte, and the sharded
    run's state must replay bit-identically from its own log."""
    m8 = _drive_mesh_run(tmp_path, 8)
    m1 = _drive_mesh_run(tmp_path, 1)
    assert len(m8["log"]) > 0
    assert m8["log"] == m1["log"]
    assert m8["rlog"] == m1["rlog"]
    assert m8["rlog"] == m8["log"][:len(m8["rlog"])] and len(m8["rlog"])
    assert m8["commits"] == m1["commits"] > 0
    assert m8["aborts"] == m1["aborts"]
    assert m8["acked"] == m1["acked"] and len(m8["acked"]) > 0
    # the sharded tables hold the rows in the owner-major mc layout, so
    # their digest is compared against an independent mesh REPLAY of the
    # same log (the recovery path), not against the =1 layout
    assert _replay_digest(m8) == m8["digest"]
    assert _replay_digest(m1) == m1["digest"]


def test_mesh_cluster_tpcc_bit_identical(tmp_path):
    """TPC-C/NO_WAIT (the generic sweep → `workloads.mc.mc_execute`
    shard_map path, with real aborts + retry feedback): same cluster
    bit-identity bar as YCSB, warehouses as the ownership anchor."""
    kw = dict(workload=WorkloadKind.TPCC, cc_alg=CCAlg.NO_WAIT,
              num_wh=8, cust_per_dist=30, max_items=100,
              max_accesses=18, insert_table_cap=1 << 10,
              synth_table_size=4096)
    m8 = _drive_mesh_run(tmp_path, 8, replica=False, **kw)
    m1 = _drive_mesh_run(tmp_path, 1, replica=False, **kw)
    assert len(m8["log"]) > 0
    assert m8["log"] == m1["log"]
    assert m8["commits"] == m1["commits"] > 0
    assert m8["aborts"] == m1["aborts"]
    assert m8["acked"] == m1["acked"] and len(m8["acked"]) > 0
    assert _replay_digest(m8) == m8["digest"]


def test_mesh_pins_are_validated_errors():
    """The former silent `device_parts == 1` skips are config errors
    now: arming an incompatible plane on a mesh config must raise a
    named ValueError, never quietly no-op (engine/step.py drops the
    inline guards in the same PR)."""
    ok = dict(workload=WorkloadKind.YCSB, cc_alg=CCAlg.TPU_BATCH,
              epoch_batch=64, conflict_buckets=512,
              synth_table_size=512, req_per_query=4, max_accesses=4)
    Config(**ok, device_parts=8).validate()     # sane base composes
    with pytest.raises(ValueError, match="metrics"):
        Config(**ok, device_parts=8, metrics=True).validate()
    with pytest.raises(ValueError, match="VOTE"):
        Config(**{**ok, "cc_alg": CCAlg.OCC}, device_parts=8,
               dist_protocol="vote").validate()
