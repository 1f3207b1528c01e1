"""The seam of PR 31: the epoch's device program lives in
`deneva_tpu/engine/epoch.py`, the in-process engine and the served path
both run its `epoch_core`, and no module below the host loop reaches up
for it."""

import ast
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deneva_tpu.config import Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "deneva_tpu")
SERVER = "deneva_tpu.runtime.server"


def _imports(path: str) -> set[str]:
    """Every module a file imports, at any depth of nesting, as dotted
    names (``from a.b import c`` gives both ``a.b`` and ``a.b.c``)."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module \
                and not node.level:
            out.add(node.module)
            out.update(f"{node.module}.{a.name}" for a in node.names)
    return out


def _files(rel: str) -> list[str]:
    path = os.path.join(PKG, rel)
    if path.endswith(".py"):
        return [path]
    return [os.path.join(d, f) for d, _, fs in os.walk(path)
            for f in fs if f.endswith(".py")]


@pytest.mark.parametrize("below", [
    "engine", "cc", "ops", "storage", "parallel", "workloads",
    "runtime/logger.py", "runtime/replication.py", "harness/chaos.py"])
def test_nothing_below_the_host_loop_imports_it(below):
    files = _files(below)
    assert files, below
    for path in files:
        up = {m for m in _imports(path)
              if m == SERVER or m.startswith(SERVER + ".")}
        assert not up, (os.path.relpath(path, ROOT), up)


def test_the_server_module_hands_on_the_engines_program():
    from deneva_tpu.engine import epoch
    from deneva_tpu.runtime import server
    assert server.make_dist_step is epoch.make_dist_step
    # host code only: the module defines no jitted program of its own
    with open(os.path.join(PKG, "runtime", "server.py")) as f:
        src = f.read()
    assert not re.search(r"jax\.jit|lax\.scan", src)


# ---- one middle: the same batch through both callers -------------------

YCSB = dict(workload="YCSB", synth_table_size=512, epoch_batch=128,
            max_txn_in_flight=128, conflict_buckets=512, req_per_query=4,
            max_accesses=4, zipf_theta=0.9)
TPCC = dict(workload="TPCC", num_wh=2, cust_per_dist=120, max_items=200,
            max_items_per_txn=5, max_accesses=8, epoch_batch=64,
            max_txn_in_flight=64, conflict_buckets=1024,
            insert_table_cap=1 << 14)
CASES = {
    "tpu_batch_forwarding": dict(YCSB, cc_alg="TPU_BATCH"),
    "occ": dict(YCSB, cc_alg="OCC"),
    "dgcc": dict(YCSB, cc_alg="DGCC"),
    "tpcc_chained": dict(TPCC, cc_alg="TPU_BATCH"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_and_served_step_decide_and_write_alike(case):
    """An epoch of the in-process engine (`Engine.step`: pool around
    `epoch_core`) against the served per-epoch program (`make_dist_step`)
    on the batch the engine's pool selected: the same commit / abort /
    defer masks and the same tables.  Through public names only, so it
    holds on the parent of PR 31 too (two copies that agreed); what it
    guards is the next fork.  The pool is as large as the batch, so lane
    i is slot i and the pool's sequence is the served path's rank."""
    from deneva_tpu.engine import Engine
    from deneva_tpu.engine.epoch import make_dist_step
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.runtime.logger import state_digest
    from deneva_tpu.workloads import get_workload

    cfg = Config.from_args(
        [f"--{k}={v}" for k, v in CASES[case].items()] + ["--node_cnt=1"]
        ).replace(node_id=0, part_cnt=1)
    wl = get_workload(cfg)
    eng = Engine(cfg, wl)
    be = eng.backend
    s0 = eng.init_state(seed=5)
    # YCSB writes f(key, rank): start the pool's sequence where the
    # served path's rank starts, so that the two write the same bytes
    s0 = dataclasses.replace(s0, pool=dataclasses.replace(
        s0.pool, next_seq=jnp.zeros((), jnp.int32)))

    # the batch the engine's first epoch runs, rebuilt from its state
    _, gen_key = jax.random.split(s0.rng)
    pool, _ = eng.pool.refill(s0.pool, wl.generate(gen_key, eng.pool.g),
                              s0.epoch)
    _, active, queries = eng.pool.select(pool, s0.epoch)
    assert eng.pool.full_pool and bool(np.asarray(active).all())

    db, _cc, st, done, abort, defer, *_ = make_dist_step(cfg, wl, be)(
        s0.db, s0.cc_state, init_device_stats(len(wl.txn_type_names)),
        s0.epoch, active, pool.ts, queries)
    s1 = eng.step(s0)

    left = np.asarray(s1.pool.occupied)
    aborted = np.asarray(s1.pool.abort_cnt) > 0
    assert (np.asarray(done) == ~left).all()
    assert (np.asarray(abort) == aborted).all()
    assert (np.asarray(defer) == (left & ~aborted)).all()
    assert int(st["total_txn_commit_cnt"]) == \
        int(s1.stats["total_txn_commit_cnt"]) == int((~left).sum()) > 0
    assert int(st["write_cnt"]) == int(s1.stats["write_cnt"])
    assert state_digest(db) == state_digest(s1.db)
    if case == "occ":
        # the case decides something: a loser exists
        assert aborted.any()
