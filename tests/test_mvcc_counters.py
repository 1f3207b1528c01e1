"""What served MVCC counts and names (PR 43), and what it must leave alone.

* the device counters of `workloads/base.MVCC_COUNTERS` — reads
  served a version other than the live one, transactions sent back for a
  read whose version is out of reach, waits, read-only commits, and
  (PR 45) the lanes handed to the version ring's row write — against
  a plain numpy restatement of the rule on seeded toys, verdict by
  verdict (the toys also hold the fault this PR mends: a reader that
  waited and came back between two writers of one epoch is sent back,
  never served the version under the lost one);
* `ep.version` is in the lowered MVCC epoch and in no TPU_BATCH or OCC
  one, and neither holds a counter of MVCC's;
* the accepted one-chip deployments' servers — TPU_BATCH, OCC, TPC-C, PPS
  at their configuration files' fields, toy-sized — carry the stats
  pytree and print the `[summary]` keys pinned here: what the parent's
  did (their lowered group programs were byte-equal to the parent's at
  these shapes when this file was written: PERF.md section 6, PR 43).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deneva_tpu.cc import get_backend
from deneva_tpu.cc.base import AccessBatch
from deneva_tpu.config import CCAlg, Config, WorkloadKind
from deneva_tpu.engine.step import init_device_stats
from deneva_tpu.ops import bucket_hash, combine_key
from deneva_tpu.ops.scatter import _CHUNKS, _ROWS_PER_LANE
from deneva_tpu.storage.table import padded_rows
from deneva_tpu.workloads import get_workload
from deneva_tpu.workloads.base import MVCC_COUNTERS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, N_KEYS, R, B = 3, 40, 3, 8


def _cfg(alg=CCAlg.MVCC, **kw):
    base = dict(workload=WorkloadKind.YCSB, cc_alg=alg, synth_table_size=256,
                req_per_query=R, max_accesses=R, epoch_batch=B,
                conflict_buckets=256, max_txn_in_flight=B, mvcc_his_len=H)
    base.update(kw)
    return Config(**base)


# ---- the rule, in numpy ---------------------------------------------------

class Model:
    """`cc/timestamp.validate_mvcc` + the ring half of
    `YCSBWorkload.execute`, per KEY (the toys' keys share no watermark
    bucket), one transaction after another."""

    def __init__(self):
        z = lambda: np.zeros(N_KEYS, np.int64)  # noqa: E731
        self.rts, self.wts, self.lossy = z(), z(), z()
        self.his = np.zeros((N_KEYS, H), np.int64)      # bucket boundaries
        self.pos = np.zeros(N_KEYS, np.int64)
        self.ring = np.zeros((N_KEYS, H), np.int64)     # the row's ring
        self.counts = dict.fromkeys(MVCC_COUNTERS, 0)

    def epoch(self, ts, keys, is_write):
        n = len(ts)
        ro = ~is_write.any(axis=1)
        floor = np.maximum(self.his.min(axis=1), self.lossy)
        out_of_reach = np.zeros(n, bool)
        bad = np.zeros(n, bool)
        for i in range(n):
            for k, w in zip(keys[i], is_write[i]):
                if w:
                    bad[i] |= self.rts[k] > ts[i] or self.wts[k] > ts[i]
                elif self.wts[k] > ts[i] and ts[i] < floor[k]:
                    out_of_reach[i] = bad[i] = True
        abort = bad & ~ro
        live = ~abort
        # a reader behind an earlier-stamped committing writer of a key
        # it reads waits: in timestamp order, first fit
        win = np.zeros(n, bool)
        for i in np.argsort(ts):
            if not live[i] or ro[i]:
                continue
            blocked = any(
                win[j] and ts[j] < ts[i] and any(
                    (not wi) and wj and ki == kj
                    for ki, wi in zip(keys[i], is_write[i])
                    for kj, wj in zip(keys[j], is_write[j]))
                for j in range(n))
            win[i] = not blocked
        commit = win | (live & ro)
        defer = live & ~ro & ~win
        c = self.counts
        c["mvcc_history_aborts"] += int((abort & out_of_reach).sum())
        c["mvcc_waits"] += int(defer.sum())
        c["mvcc_ro_commits"] += int(ro.sum())
        # reads: an old version where the row's ring holds a newer entry
        for i in np.flatnonzero(commit & ~ro):
            for k, w in zip(keys[i], is_write[i]):
                if not w and (self.ring[k] > ts[i]).any():
                    c["mvcc_old_version_reads"] += 1
        # writes: watermarks, one boundary and one ring entry a key
        top = np.zeros(N_KEYS, np.int64)
        for i in np.flatnonzero(commit):
            for k, w in zip(keys[i], is_write[i]):
                if w:
                    self.wts[k] = max(self.wts[k], ts[i])
                    top[k] = max(top[k], ts[i])
                else:
                    self.rts[k] = max(self.rts[k], ts[i])
        for i in np.flatnonzero(commit):
            for k, w in zip(keys[i], is_write[i]):
                if w and ts[i] < top[k]:
                    self.lossy[k] = max(self.lossy[k], top[k])
        for k in np.flatnonzero(top):
            self.his[k, self.pos[k]] = top[k]
            self.pos[k] = (self.pos[k] + 1) % H
            self.ring[k, int(np.argmin(self.ring[k]))] = top[k]
        c["ring_push_lanes"] += _row_write_lanes(
            np.count_nonzero(top), keys.size, padded_rows(256))
        return commit, abort, defer


def _row_write_lanes(winners: int, n: int, rows: int) -> int:
    """Lanes `ops.scatter.scatter_winner_rows` is handed for ``winners``
    rows of an epoch of ``n`` lanes on a column of ``rows``: whole
    chunks of n / 64 while that beats one pass, else all n."""
    chunk = -(-n // _CHUNKS)
    lanes = -(-winners // chunk) * chunk
    return lanes if lanes * _ROWS_PER_LANE < rows + 6 * n else n


class Device:
    """The program's own validate + execute, as `engine/epoch.epoch_core`
    calls them for a served MVCC node."""

    def __init__(self, cfg):
        self.cfg, self.wl = cfg, get_workload(cfg)
        self.be = get_backend(CCAlg.MVCC)
        self.db, self.st = self.wl.load(), self.be.init_state(cfg)
        self.stats = init_device_stats(len(self.wl.txn_type_names),
                                       mvcc_counters=True)

    def epoch(self, ts, keys, is_write):
        self.db, self.st, self.stats, v = self._step(
            self.db, self.st, self.stats, jnp.asarray(ts, jnp.int32),
            jnp.asarray(keys, jnp.int32), jnp.asarray(is_write))
        return tuple(np.asarray(x) for x in v)

    @property
    def _step(self):
        if not hasattr(self, "_jit"):
            self._jit = jax.jit(self._epoch)
        return self._jit

    def _epoch(self, db, st, stats, ts, keys, is_write):
        from deneva_tpu.cc.base import build_incidence
        from deneva_tpu.workloads.ycsb import YCSBQuery
        n = ts.shape[0]
        q = YCSBQuery(keys=keys, is_write=is_write)
        p = self.wl.plan(db, q)
        batch = AccessBatch(
            table_ids=p["table_ids"], keys=p["keys"], is_read=p["is_read"],
            is_write=p["is_write"], valid=p["valid"], ts=ts,
            rank=jnp.arange(n, dtype=jnp.int32), active=jnp.ones(n, bool))
        inc = build_incidence(batch, self.cfg.conflict_buckets,
                              self.cfg.conflict_exact)
        stats = dict(stats)
        v, st = self.be.validate(self.cfg, st, batch, inc, stats=stats)
        db = self.wl.execute(db, q, v.commit, v.order, stats)
        return db, st, stats, (v.commit, v.abort, v.defer)


@pytest.fixture(scope="module")
def toy_keys():
    """N_KEYS keys of the toy table that share no watermark bucket (the
    model keeps its state per key)."""
    cfg = _cfg()
    cand = np.arange(200, dtype=np.int32)
    b = np.asarray(bucket_hash(combine_key(0, jnp.asarray(cand)),
                               cfg.watermark_buckets, family=0))
    _, first = np.unique(b, return_index=True)
    keys = cand[np.sort(first)][:N_KEYS]
    assert len(keys) == N_KEYS
    return keys


@pytest.mark.parametrize("seed", [1, 2, 3, 6])
def test_the_mvcc_counters_equal_a_numpy_count(seed, toy_keys):
    """Sixty epochs of eight transactions over forty hot keys: a waiting
    transaction comes back with its timestamp two epochs later (so it
    meets writers of later timestamps: old versions, lost versions, a
    history three deep), an aborted one with a fresh one."""
    rng = np.random.default_rng(seed)
    dev, model = Device(_cfg()), Model()
    waiting: list[tuple[int, int, np.ndarray, np.ndarray]] = []
    for e in range(60):
        back = [w for w in waiting if w[0] <= e][:B // 2]
        waiting = [w for w in waiting if w not in back]
        n_new = B - len(back)
        ki = rng.integers(0, N_KEYS, (n_new, R))
        wr = (rng.random((n_new, R)) < 0.5) & (rng.random((n_new, 1)) < 0.6)
        ts = np.array([w[1] for w in back]
                      + list((e + 1) * B + np.arange(n_new)), np.int64)
        kidx = np.concatenate([np.stack([w[2] for w in back])
                               if back else np.zeros((0, R), np.int64), ki])
        is_w = np.concatenate([np.stack([w[3] for w in back])
                               if back else np.zeros((0, R), bool), wr])
        got = dev.epoch(ts, toy_keys[kidx], is_w)
        want = model.epoch(ts, kidx, is_w)
        for g, w, what in zip(got, want, ("commit", "abort", "defer")):
            assert (g == w).all(), (e, what, g, w)
        for i in np.flatnonzero(want[2]):
            waiting.append((e + 2, int(ts[i]), kidx[i], is_w[i]))
    counts = {k: int(np.asarray(dev.stats[k])) for k in MVCC_COUNTERS}
    assert counts == model.counts
    assert all(v > 0 for v in counts.values()), counts


def test_a_reader_between_two_writers_of_one_epoch_is_sent_back(toy_keys):
    """The history PR 43's reference found in a served toy launch: W5 and
    W7 write one key in one epoch while R6 waits; the row keeps 7's
    version only, so R6 — owed 5's — must not be served the load's."""
    dev = Device(_cfg())
    k, other = toy_keys[0], toy_keys[1:]
    wr = [True] * R
    c, a, d = dev.epoch([5, 6, 7], [[k] * R, [k, other[0], other[1]],
                                    [k] * R], [wr, [False, True, True], wr])
    assert c.tolist() == [True, False, True] and d.tolist() == [
        False, True, False]
    c, a, d = dev.epoch([6], [[k, other[0], other[1]]],
                        [[False, True, True]])
    assert a.tolist() == [True] and not c.any()
    assert int(np.asarray(dev.stats["mvcc_history_aborts"])) == 1
    assert int(np.asarray(dev.stats["mvcc_old_version_reads"])) == 0
    # ... while a waiter behind ONE writer is served that writer's version
    # after a later epoch overwrote it (an old version, counted)
    k2 = toy_keys[10]
    dev.epoch([20, 21], [[k2] * R, [k2, other[2], other[3]]],
              [wr, [False, True, True]])
    dev.epoch([30], [[k2] * R], [wr])
    c, a, d = dev.epoch([21], [[k2, other[2], other[3]]],
                        [[False, True, True]])
    assert c.tolist() == [True]
    assert int(np.asarray(dev.stats["mvcc_old_version_reads"])) == 1


# ---- the scope and the counters, by program ---------------------------------

def _lowered_epoch(alg, **kw) -> str:
    """The served per-epoch program of a toy YCSB node, lowered, with its
    op names."""
    from deneva_tpu.engine.epoch import make_dist_step
    cfg = _cfg(alg, sim_full_row=True, **kw)
    wl, be = get_workload(cfg), get_backend(alg)
    stats = init_device_stats(len(wl.txn_type_names),
                              mvcc_counters=alg == CCAlg.MVCC)
    step = make_dist_step(cfg, wl, be)
    q = wl.generate(jax.random.PRNGKey(0), B)
    low = step.lower(wl.load(), be.init_state(cfg), stats, jnp.int32(0),
                     jnp.ones(B, bool), jnp.arange(1, B + 1, dtype=jnp.int32),
                     q)
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("alg,has", [(CCAlg.MVCC, True), (CCAlg.OCC, False),
                                     (CCAlg.TPU_BATCH, False)],
                         ids=["mvcc", "occ", "tpu_batch"])
def test_ep_version_names_the_ring_in_the_mvcc_epoch_alone(alg, has):
    txt = _lowered_epoch(alg)
    assert ("ep.version" in txt) == has
    assert "ep.read" in txt and "ep.write" in txt
    if has:
        # the ring's gather, its select and its push carry the scope
        lines = [ln for ln in txt.splitlines() if "ep.version" in ln]
        assert len(lines) > 10


# ---- the accepted deployments' servers, pinned --------------------------------

_STATS = ["abort_by_type", "admitted_cnt", "audit_drop_cnt", "audit_edge_cnt",
          "audit_wit_cnt", "commit_by_type", "conflict_density", "defer_cnt",
          "dgcc_edge_cnt", "dgcc_fallback_cnt", "dgcc_wave_cnt",
          "dgcc_wave_max", "generated_cnt", "latency_hist", "read_checksum",
          "read_gather_lanes", "rep_fallback_cnt", "rep_frontier_cnt",
          "rep_salvaged_cnt", "retry_hist", "total_txn_abort_cnt",
          "total_txn_commit_cnt", "unique_txn_abort_cnt", "wait_hist",
          "write_cnt", "write_scatter_lanes"]
_STAGES = ("admit", "collect", "dispatch", "drain", "feed", "other", "retire",
           "retire_wait")
_SUMMARY = ["abort_rate", "defer_cnt", "epoch_cnt", "net_batches_sent",
            "net_bytes_rcvd", "net_bytes_sent", "net_msg_rcvd",
            "net_msg_sent", "net_recv_queue_depth", "net_send_queue_depth",
            "pipeline_time_mean", "process_cpu_time", "queue_txn_mean",
            "read_gather_lane_cnt", "stage_epoch_cnt", "stage_wall_time",
            "total_runtime", "total_txn_abort_cnt", "total_txn_commit_cnt",
            "tput", "txn_cnt", "unique_txn_abort_cnt", "worker_idle_time",
            "worker_process_time", "write_cnt", "write_scatter_lane_cnt"] \
    + [f"stage_{s}_{k}" for s in _STAGES for k in ("time", "cpu_time")]


def _types(prefix, names):
    return [f"{prefix}_{n}_{fam}_cnt" for n in names
            for fam in ("abort", "commit")]


_YCSB_TOY = dict(synth_table_size=1024, conflict_buckets=256)
_LEVELS = ["level_pass_cnt", "narrow_pass_cnt"]
# configuration -> (toy sizes, stats leaves beyond _STATS, [summary] keys
# beyond _SUMMARY)
# (PR 48: a server that writes full rows through the row write's kernel
# counts the tile groups it writes back — YCSB's, no other's)
_GROUPS = (["write_row_groups"], ["write_row_group_cnt"])
PINNED = {
    "ycsb-fullrow-tpubatch": (_YCSB_TOY, _GROUPS[0],
                              _GROUPS[1] + _types("ycsb", ("ro", "rw"))),
    "ycsb-fullrow-occ": (_YCSB_TOY, _GROUPS[0],
                         _GROUPS[1] + _types("ycsb", ("ro", "rw"))),
    "tpcc-fullschema-tpubatch": (
        dict(num_wh=2),
        _LEVELS + ["append_scatter_lanes", "append_window_lanes"],
        _LEVELS + ["append_scatter_lane_cnt", "append_window_lane_cnt"]
        + _types("tpcc", ("new_order", "payment"))),
    "pps-fullrow-tpubatch": (
        dict(pps_parts_cnt=200, pps_products_cnt=40, pps_suppliers_cnt=40),
        _LEVELS + ["recon_defer_cnt"],
        _LEVELS + ["recon_defer_cnt", "pps_lookup_commit_cnt",
                   "pps_order_commit_cnt", "pps_update_commit_cnt"]
        + _types("pps", ("getpart", "getpartbyproduct", "getpartbysupplier",
                         "getproduct", "getsupplier", "orderproduct",
                         "updatepart", "updateproductpart"))),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_an_accepted_deployments_server_is_what_the_parents_was(name):
    """The stats pytree a served node of each accepted one-chip
    configuration carries, and the keys its `[summary]` prints: no
    counter of MVCC's, nothing gone."""
    from deneva_tpu.runtime.native import ipc_endpoints
    from deneva_tpu.runtime.server import ServerNode
    from deneva_tpu.stats import parse_summary
    toy, more_stats, more_summary = PINNED[name]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        fields = json.load(f)["fields"]
    fields.update(toy, epoch_batch=64, pipeline_epochs=2,
                  max_txn_in_flight=512, client_batch_size=64,
                  client_node_cnt=0, part_cnt=1, warmup_secs=0.1,
                  done_secs=0.3)
    cfg = Config.from_args([f"--{k}={v}" for k, v in fields.items()])
    node = ServerNode(cfg, ipc_endpoints(1, f"pin{os.getpid()}{name[:6]}"),
                      "cpu")
    try:
        leaves = sorted(node.dev_stats)
        node.run()
        summary = sorted(parse_summary(node.stats.summary_line()))
    finally:
        node.close()
    assert leaves == sorted(_STATS + more_stats)
    assert summary == sorted(_SUMMARY + more_summary)
    assert not [k for k in leaves + summary if "mvcc" in k]
