"""Server node of the distributed runtime (reference `rundb`, SURVEY §3.A-C).

One process per server node.  The reference coordinates multi-partition
transactions with 2PC (RQRY/RPREPARE/RFIN/ACK round trips,
`system/txn.cpp:498-606`); here distribution is Calvin-shaped end to end
(`system/sequencer.cpp`, `system/calvin_thread.cpp`), because determinism
is what lets a batch engine skip the vote:

* every global epoch, each server contributes an equal, deterministic
  slice of transactions (its local admission queue — the per-node
  sequencer batch, `sequencer.cpp:207-220`);
* contributions are broadcast as EPOCH_BLOBs; exactly one blob per
  (server, epoch) doubles as the RDONE barrier
  (`system/work_queue.cpp:126-143`);
* every server materializes the *identical* merged batch (concat by node
  id; rank = position, ts = epoch * B + rank) and runs the *identical*
  pure validation function on it — so all nodes reach the same verdicts
  with zero further communication.  The conflict matrix is the vote;
* execution is local: the strided partition index maps remote keys to the
  trash slot, so each node's gathers/scatters touch only the keyspace it
  owns (reference `GET_NODE_ID` hash partitioning, `system/global.h:294`).
  Per-row RMW semantics (all three benchmarks) need no cross-node reads —
  the reference's RFWD forwarding phase (`system/txn.cpp:957-974`) has no
  work to do in this execution model;
* the home server (the one the client sent the txn to) answers CL_RSP
  after the epoch that commits it, and re-enqueues aborted txns with the
  exponential backoff of `system/abort_queue.cpp:26-50`.

The engine state (tables, CC watermarks, stats) lives on this process's
JAX device; the epoch step is one jitted program per node, identical on
every node modulo the partition index baked into its workload.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np

from deneva_tpu.config import CCAlg, Config
from deneva_tpu.engine.epoch import (make_dist_group, make_dist_step,
                                     make_vote_steps)
from deneva_tpu.runtime import replication as georepl
from deneva_tpu.runtime import wire
from deneva_tpu.runtime.telemetry import (ST_ADMIT, ST_BATCH, ST_HOLD,
                                          ST_RELEASE, ST_VERDICT, V_ABORT,
                                          V_COMMIT, V_DEFER, V_SALVAGE,
                                          telemetry_line)
from deneva_tpu.runtime.native import NativeTransport
from deneva_tpu.runtime.stages import StageClock, span as stage_span
from deneva_tpu.stats import Stats
from deneva_tpu.workloads.base import (APPEND_COUNTERS, EXEC_COUNTERS,
                                       LOCK_COUNTERS, MVCC_COUNTERS,
                                       ROW_GROUP_COUNTER)

_TAG_MASK = np.int64((1 << 40) - 1)


class _RetryQueue:
    """Aborted-txn restart queue with exponential backoff
    (`system/abort_queue.cpp:26-50`); deferred txns re-enter with zero
    penalty (waiter-list analogue).  ``aborted`` records whether the LAST
    verdict was an abort (vs a defer): fresh-ts backends re-stamp only
    aborted restarts — deferred (waiting) txns keep their birth ts like
    the reference's parked requests and the in-process pool."""

    def __init__(self, backoff: bool, cap: int = 64):
        self.items: list[tuple[int, wire.QueryBlock, np.ndarray,
                               np.ndarray, np.ndarray, np.ndarray]] = []
        self.backoff = backoff
        self.cap = cap

    def push(self, block: wire.QueryBlock, abort_cnt: np.ndarray,
             ts: np.ndarray, epoch: int,
             aborted: np.ndarray | None = None,
             defer_cnt: np.ndarray | None = None) -> None:
        if not len(block):
            return
        if aborted is None:
            aborted = abort_cnt > 0
        if defer_cnt is None:
            defer_cnt = np.zeros(len(block), np.int32)
        # clamp the exponent, not the power: 2**(cnt-1) overflows int32
        # past cnt=32 and would turn the penalty negative
        exp = np.minimum(np.maximum(abort_cnt - 1, 0),
                         int(np.log2(self.cap)))
        pen = np.minimum(2 ** exp, self.cap) \
            if self.backoff else np.ones_like(abort_cnt)
        ready = epoch + 1 + np.where(aborted, pen, 0)
        for r in np.unique(ready):
            m = ready == r
            idx = np.where(m)[0]
            self.items.append((int(r), block.take(idx), abort_cnt[m],
                               ts[idx], aborted[m], defer_cnt[m]))

    def pop_ready(self, epoch: int, limit: int):
        take_b, take_c, take_t, take_a, take_d, rest = [], [], [], [], [], []
        n = 0
        self.items.sort(key=lambda it: it[0])
        for r, blk, cnt, ts, ab, dc in self.items:
            if r <= epoch and n < limit:
                room = limit - n
                if len(blk) <= room:
                    take_b.append(blk)
                    take_c.append(cnt)
                    take_t.append(ts)
                    take_a.append(ab)
                    take_d.append(dc)
                    n += len(blk)
                else:
                    take_b.append(blk.slice(0, room))
                    take_c.append(cnt[:room])
                    take_t.append(ts[:room])
                    take_a.append(ab[:room])
                    take_d.append(dc[:room])
                    rest.append((r, blk.slice(room, len(blk)), cnt[room:],
                                 ts[room:], ab[room:], dc[room:]))
                    n = limit
            else:
                rest.append((r, blk, cnt, ts, ab, dc))
        self.items = rest
        return take_b, take_c, take_t, take_a, take_d


class ServerNode:
    """One server process: transport + admission + epoch loop + stats."""

    def __init__(self, cfg: Config, endpoints: str, platform: str,
                 setup_wait_s: float = wire.SETUP_WAIT_S):
        import jax
        from deneva_tpu.runtime.jaxenv import compile_ledger, init_jax
        # what this process's JAX runs on (raises unless it is the
        # platform asked for) + set-up costs; the launcher carries the
        # dict back to its caller beside the [summary] line
        self.info: dict = init_jax(platform)
        self.setup_wait_s = setup_wait_s
        self._compiles = compile_ledger()
        from deneva_tpu.cc import get_backend
        from deneva_tpu.engine.step import init_device_stats
        from deneva_tpu.workloads import get_workload

        self.cfg = cfg
        self.me = cfg.node_id
        self.n_srv = cfg.node_cnt
        self.n_cl = cfg.client_node_cnt
        self.n_repl = cfg.replica_cnt * cfg.node_cnt
        self.b_loc = max(1, cfg.epoch_batch // self.n_srv)
        self.b_merged = self.b_loc * self.n_srv
        self.wl = get_workload(cfg)
        self.be = get_backend(cfg.cc_alg)
        from deneva_tpu.ops import forwarding_applies
        deterministic = self.be.chained or forwarding_applies(self.be,
                                                              self.wl)
        self.vote_mode = cfg.dist_protocol == "vote" or (
            cfg.dist_protocol == "auto" and self.n_srv > 1
            and not deterministic and cfg.cc_alg != CCAlg.MAAT
            and not cfg.ycsb_abort_mode)
        # cluster analogue of the engine's defer budget (engine/step.py):
        # a txn deferred past defer_rounds_max force-restarts as an abort
        # at retirement.  Node-local retry policy like abort backoff —
        # it never enters the replicated verdict computation.
        # Deterministic backends are exempt (their defers resolve by
        # construction).
        self.defer_budget = 0 if deterministic else cfg.defer_rounds_max
        # pipeline shape: C epochs per device dispatch, K groups in
        # flight.  The VOTE protocol needs a host round trip (prepare ->
        # vote exchange -> decide) inside every epoch, so it cannot fuse
        # or run ahead — it keeps the synchronous shape.
        self.C = 1 if self.vote_mode else max(1, cfg.pipeline_epochs)
        self.K = 1 if self.vote_mode else max(1, cfg.pipeline_groups)
        # wire shape of one query (width, scalar count) from a sample
        _k, _t, _s = self.wl.to_wire(self.wl.generate(_key0(), 1))
        self._width = _k.shape[1]
        self._n_scalars = _s.shape[1]
        if self.vote_mode:
            self.vote_step, self.check_step, self.apply_step = \
                make_vote_steps(cfg, self.wl, self.be)
            self.maat_vote = cfg.cc_alg == CCAlg.MAAT
        else:
            self.group_step = make_dist_group(cfg, self.wl, self.be,
                                              self._width,
                                              self._n_scalars)
        t_load = time.monotonic()
        self.db = self.wl.load()
        self.cc_state = self.be.init_state(cfg)
        # `run_levels`' loop runs where a chained backend executes its
        # levels in the group program of one device: there its passes
        # are counted (`level_pass_cnt`, `narrow_pass_cnt`)
        self._counts_levels = (
            self.be.chained and not forwarding_applies(self.be, self.wl)
            and not self.vote_mode and cfg.device_parts == 1)
        # a workload with ring tables appends to them on one device:
        # there `DeviceTable.append` counts how its lanes were written
        # (`workloads/base.APPEND_COUNTERS`)
        # ... and where its workload marks reconnaissance (PPS), the
        # lanes deferred on a stale one (`cc/base.stale_recon`)
        counts_recon = self._counts_levels \
            and getattr(self.wl, "recon", None) is not None
        # what the lock family decides, in the group program of one
        # device (`workloads/base.LOCK_COUNTERS`: deaths, waits, sweep
        # leftovers) and, on the host, the waiters the defer budget
        # sends back as aborts (`lock_forced_restart_cnt`)
        self._counts_locks = (
            cfg.cc_alg in (CCAlg.NO_WAIT, CCAlg.WAIT_DIE)
            and not self.vote_mode and cfg.device_parts == 1)
        self._lock_forced = 0
        self.dev_stats = init_device_stats(
            len(getattr(self.wl, "txn_type_names", ("txn",))),
            level_passes=self._counts_levels,
            append_lanes=cfg.device_parts == 1 and any(
                getattr(t, "ring", False) for t in self.db.values()),
            recon_defers=counts_recon,
            # the owner exchange of a mesh (`wl.execute_mc`): the
            # shard-epochs that ran its capacity-defer pass
            mc_defer_passes=(cfg.device_parts > 1 and not self.vote_mode
                             and forwarding_applies(self.be, self.wl)),
            # what MVCC decides (`workloads/base.MVCC_COUNTERS`), in the
            # group program of one device
            mvcc_counters=(cfg.cc_alg == CCAlg.MVCC and not self.vote_mode
                           and cfg.device_parts == 1),
            lock_counters=self._counts_locks,
            # the tile groups the row write's kernel writes back
            # (`workloads/base.ROW_GROUP_COUNTER`)
            row_groups=getattr(self.wl, "writes_row_groups", False))
        jax.block_until_ready(self.db)
        self.info["load_s"] = round(time.monotonic() - t_load, 3)

        # ---- mesh-sharded measured path (device_parts > 1): the SAME
        # merged-mode epoch program, called under a use_mesh context so
        # the epoch body traces through workloads/mc (owner-major
        # sharded tables + the all_to_all owner exchange) and the CC
        # incidence builds shard their bucket dim.  config.validate pins
        # the planes whose fold needs a single device (metrics → ctrl,
        # repair, audit, the vote protocol), so the group jit's shapes —
        # and therefore verdict planes, logs, digests and acks — are
        # exactly the single-device ones (tests/test_mesh_cluster.py
        # holds them bit-identical). ----
        self.mesh = None
        self._mesh_mod = None
        self._feed_sharding = None
        if cfg.device_parts > 1:
            from deneva_tpu.parallel import mesh as _mesh
            self._mesh_mod = _mesh
            # the configuration's mesh: the one a workload's sharded
            # loader built its table over (`YCSBWorkload._load_mc`), so
            # the placement below finds those columns where they belong
            self.mesh = _mesh.make_mesh(cfg.device_parts)
            if not self.vote_mode:
                _inner_group = self.group_step

                def _mesh_group(*a, _g=_inner_group, **kw):
                    # use_mesh matters at TRACE time; jit traces lazily
                    # at the first call (and again per shape), so every
                    # call runs under the context — cached executions
                    # just pay a dict write
                    with _mesh.use_mesh(self.mesh):
                        return _g(*a, **kw)
                self.group_step = _mesh_group
            # engine-state layout over the mesh, derived ONCE here:
            # tables + per-bucket CC watermarks shard dim 0 (keyspace
            # slices per chip), stats replicate
            _state = {"db": self.db, "cc_state": self.cc_state,
                      "stats": self.dev_stats}
            _state = jax.device_put(
                _state, _mesh.state_shardings(self.mesh, _state))
            self.db = _state["db"]
            self.cc_state = _state["cc_state"]
            self.dev_stats = _state["stats"]
            # feed buffers (and the warm call) replicate: device_put
            # needs the explicit placement or the sharded state and the
            # default-device feed would sit on incompatible device sets
            self._feed_sharding = _mesh.NamedSharding(self.mesh,
                                                      _mesh.P())

        # ---- elastic membership (slot-map routing + live rebalance;
        # runtime/membership.py — all off on a default config) ----------
        self._elastic = cfg.elastic
        self.smap = None
        self._full_planes = cfg.elastic and cfg.faults_enabled
        self._plane_lo = self.me * self.b_loc if self._full_planes else 0
        self._plane_n = self.b_merged if self._full_planes else self.b_loc

        # ---- transaction repair (engine/repair.py — off on a default
        # config: three verdict planes, no rep accounting, no [repair]
        # line).  Armed, the group jit returns a 4th "repaired" plane
        # (salvaged txns, a subset of done) for host-side accounting +
        # the "repair" timeline span; config pins merged mode, so the
        # vote path never sees it. ----
        self._repair = cfg.repair
        self._rep_salvaged = 0          # rep-plane bits retired (host)
        self._rep_meas = 0
        self._rep_span = 0.0            # retire-side accounting seconds
        if self._elastic:
            from deneva_tpu.runtime import membership as _M
            self._M = _M
            self.smap = _M.initial_map(cfg)
            self._mig_pending: dict | None = None
            self._mig_rows: dict[int, dict[int, bytes]] = {}
            self._contrib_gone: dict[int, int] = {}   # node -> 1st dead epoch
            self._reassigned: set[int] = set()
            self._plan_sent = False
            self._rebalance_cnt = 0
            self._rows_in = 0
            self._rows_out = 0
            self._cutover_stall_ms = 0.0
            self._redirects = 0
            # full-plane committed ids held until their epoch is durable
            # (re-ack takeover authority; same gate as held CL_RSPs)
            self._held_commit: deque[tuple[int, np.ndarray]] = deque()

        # ---- geo-replication tier (quorum group-commit + region roles;
        # runtime/replication.py — all off on a default config) ----------
        self._geo = cfg.geo
        self._geo_region = georepl.region_of(cfg, self.me) if self._geo \
            else 0
        self.repl_applied: dict[int, int] = {}
        self._promote_cnt = 0
        self._quorum_hold_t: dict[int, float] = {}
        self._quorum_stall_s = 0.0
        self._quorum_release_cnt = 0
        self._geo_spans = {"quorum": 0.0, "promote": 0.0}

        # ---- partition & gray-failure tolerance (fencing layer;
        # runtime/faildet.py — all off on a default config: no
        # heartbeat is ever sent, no frame grows a fence envelope, and
        # every wire/log byte is bit-identical to pre-fencing) ----
        self._fencing = cfg.fencing
        self._fd = None                 # detector; built AFTER the
        #                                 barrier (jit compile time must
        #                                 not read as peer silence)
        self._FD = None
        if self._fencing:
            from deneva_tpu.runtime import faildet as _FD
            self._FD = _FD
            self._hb_next_s = 0.0
            self._epoch_cur = 0
            # per-peer: highest epoch whose EPOCH_BLOB we received from
            # them (our lease grant, shipped in heartbeats) and the
            # highest of OUR epochs they confirmed (their grant to us —
            # the ack-lease quorum input)
            self._blob_seen_from = {p: -1 for p in range(self.n_srv)
                                    if p != self.me}
            self._hb_peer_seen = {p: -1 for p in range(self.n_srv)
                                  if p != self.me}
            self._fence_nacks = 0       # FENCE_NACKs sent
            self._fence_nack_rx = 0     # FENCE_NACKs received
            self._fence_last_ack = -1   # highest epoch whose CL_RSPs
            #                             released (single-writer oracle)
            self._fence_reassign_epoch = -1
            self._fence_spans = {"suspect": 0.0, "heal": 0.0,
                                 "fence": 0.0}
        # partition/stall fault surface (native per-link blackholes +
        # gray-slow stalls; armed by cfg.fault_partition /
        # cfg.fault_peer_stall alone — they model the network, with or
        # without the fencing layer watching it)
        self._partitions = None
        self._part_links: list[tuple[int, float]] = []
        self._part_on: list[bool] = []
        self._stall = None
        self._stall_on = False
        self._t_run0 = 0.0
        if cfg.fault_partition:
            self._partitions = cfg.fault_partition_spec()
            # my TX-side links: each sender silences its own outbound at
            # its own loop positions, so the first silenced epoch is
            # group-aligned and identical on every receiver
            starts: dict[int, float] = {}
            for a, b, bidir, start in self._partitions:
                if a == self.me:
                    starts[b] = min(starts.get(b, start), start)
                elif bidir and b == self.me:
                    starts[a] = min(starts.get(a, start), start)
            self._part_links = sorted(starts.items())
            self._part_on = [False] * len(self._part_links)
        if cfg.fault_peer_stall:
            spec = cfg.fault_peer_stall_spec()
            if spec is not None and spec[0] == self.me:
                self._stall = spec

        # ---- overload tier: per-tenant admission control ahead of
        # epoch-batch formation (runtime/admission.py — off on a default
        # config: no controller exists and _route admits every decoded
        # CL_QRY_BATCH exactly as before) ----
        self.adm = None
        if cfg.admission:
            from deneva_tpu.runtime.admission import AdmissionController
            self.adm = AdmissionController(cfg,
                                           time.monotonic_ns() // 1000)

        # ---- transaction flight recorder (runtime/telemetry.py — off
        # on a default config: no recorder, no sidecar, no [telemetry]
        # line, no metrics stream; every wire/log byte bit-identical).
        # Recovery appends to the pre-crash sidecars like the command
        # log: events intact to the kill boundary survive the restart.
        self.tel = None
        self._metrics = None
        if cfg.telemetry:
            from deneva_tpu.runtime import telemetry as _T
            self.tel = _T.FlightRecorder(cfg, self.me, "node",
                                         append=cfg.recover)
            self._metrics = _T.MetricsStream(
                os.path.join(_T.telemetry_dir(cfg),
                             f"metrics_node{self.me}.jsonl"),
                self.me, append=cfg.recover)

        # ---- live metrics bus (runtime/metricsbus.py — off on a
        # default config: no frame, no rtype 25 on the wire, no
        # aggregator, no [crit]/[watch] line; every broadcast byte
        # bit-identical).  The boot aggregator is server 0; the role
        # follows the lowest-id LIVE server (a later receiver builds
        # its aggregator lazily at the first frame addressed to it).
        # Recovery appends to the pre-crash bus stream like the command
        # log, so a killed aggregator resumes its series. ----
        self.mbus = None
        self.magg = None
        if cfg.metrics:
            from deneva_tpu.runtime import metricsbus as _MB
            self._MB = _MB
            self.mbus = _MB.BusSender(cfg, self.me, _MB.ROLE_SERVER)
            if self.me == 0:
                self.magg = _MB.Aggregator(cfg, self.me,
                                           append=cfg.recover)

        # ---- isolation audit plane (runtime/audit.py — off on a
        # default config: no exporter, no audit_*.jsonl sidecar, no
        # [audit] line, and the group jit's outputs are exactly the
        # pre-audit ones).  Recovery appends to the pre-crash sidecar
        # like the command log. ----
        self.aud = None
        if cfg.audit:
            from deneva_tpu.runtime import audit as _AUD
            self._AUD = _AUD
            self.aud = _AUD.AuditExporter(cfg, self.me, self.b_loc,
                                          self.me * self.b_loc,
                                          append=cfg.recover)

        # ---- self-driving control plane (runtime/controller.py — off
        # on a default config: no controller object, no [ctrl] line, no
        # quota actuation; config.validate pins ctrl to metrics-on, so
        # the density plane below always feeds it).  Cluster actuation
        # is the admission quota scale; the backend/granularity knobs
        # are the in-process engine's (engine/driver.py).  Signals are
        # this node's OWN retired-group deltas — a dead aggregator /
        # partitioned peer stalls group progress, which the governor
        # reads as staleness (epochs=0 or gap > ctrl_stale_s) and
        # reverts to static until the heal streak clears. ----
        self.ctl = None
        if cfg.ctrl:
            from deneva_tpu.runtime.controller import Controller
            self.ctl = Controller(cfg)
            # accumulators between boundary ticks: [epochs, dens[P],
            # salvaged, witnesses], last-tick wall ns and breach base
            self._ctrl_ep = 0
            self._ctrl_dens = np.zeros(max(cfg.part_cnt, 1), np.int64)
            self._ctrl_sv = 0
            # witness DENSITY baseline: the device audit_wit_cnt counter
            # holds claim-violating edges only (cc/depgraph.
            # witness_count) — chained/DGCC epochs legitimately emit
            # edges, so feeding the raw edge volume would pin
            # audit_cadence to 1 under any contention.  Delta'd against
            # this baseline at each boundary tick.
            self._ctrl_wit0 = 0
            self._ctrl_t = time.monotonic()
            self._ctrl_breach0 = 0
            self._ctrl_span = 0.0
            self._ctrl_primed = False
            # decision-record sidecar (the [ctrl] lines, one per tick):
            # the chaos oracle replays these through replay_decisions,
            # so they must survive the process like the audit sidecars
            # do — recovery appends to the pre-crash file
            os.makedirs(cfg.log_dir, exist_ok=True)
            self._ctrl_log = open(
                os.path.join(cfg.log_dir, f"ctrl_node{self.me}.log"),
                "a" if cfg.recover else "w")

        # ---- chaos / failover gates (all off on a default config) ------
        # _failover: peers tolerate a dead server and wait for its
        # recovered incarnation instead of raising; acks gate on whole-
        # group durability so recovery's truncate-to-boundary never
        # drops an acked txn.  _dedup_on: idempotent admission (client
        # resend + transport dup protection).
        self._failover = cfg.faults_enabled and cfg.logging
        self._dedup_on = cfg.faults_enabled
        kill = cfg.fault_kill_spec()
        self._kill_at = (kill[1] if kill is not None and kill[0] == self.me
                         and not cfg.recover else None)
        self._in_system: set[int] = set()
        self._committed_set: set[int] = set()
        self._committed_recent: deque[int] = deque()
        self._committed_cap = 1 << 20
        self._dup_admits = 0
        self._reacks = 0
        self._rejoin_pending: set[int] = set()
        # retained recent own-contribution blobs (bytes), resent verbatim
        # when a crashed peer rejoins and asks for epochs it missed
        self._sent_blobs: deque[tuple[int, bytes]] = deque(
            maxlen=max(64, 6 * self.C * self.K))
        # guards REJOIN's snapshot iteration against the wire worker's
        # concurrent appends (deque append is atomic; iteration during a
        # mutation is not)
        self._sent_lock = threading.Lock()
        self._resume_epoch = 0
        if cfg.recover:
            self._recover_state()

        self.tp = NativeTransport(self.me, endpoints,
                                  self.n_srv + self.n_cl + self.n_repl,
                                  msg_size_max=cfg.msg_size_max,
                                  send_threads=cfg.send_thread_cnt,
                                  recv_threads=cfg.rem_thread_cnt,
                                  rejoin=cfg.recover)
        self.tp.start(int(setup_wait_s * 1000))
        if self._geo and cfg.geo_wan_us:
            # WAN latency profile: per-link delays from the region
            # distance matrix (the geo tier's network model)
            georepl.apply_wan_profile(self.tp, cfg, self.me)
        if (cfg.fault_drop_prob or cfg.fault_dup_prob
                or cfg.fault_delay_jitter_us):
            self.tp.set_fault(cfg.fault_drop_prob, cfg.fault_dup_prob,
                              cfg.fault_delay_jitter_us,
                              seed=cfg.fault_seed + 7919 * cfg.node_id)
        # host_overlap decides ONE thing: which thread runs the loop's pure
        # bodies.  With workers, ONE ordered wire worker carries each
        # epoch's blob broadcast, the group's flush and its log records (a
        # single thread consuming in program order is what preserves
        # per-link FIFO) and ONE retire worker turns each dispatched
        # group's verdict planes into ack payloads, so retirement K groups
        # later collects a finished result.  Without them the dispatch
        # thread calls the same bodies at the same loop positions.  Every
        # state mutation (retry queue, dedup sets, held acks) is the
        # dispatch thread's either way, so both give identical verdict
        # planes and log bytes (tested).  A VOTE epoch is a synchronous
        # host round trip with nothing to overlap: inline.
        ov = cfg.host_overlap
        if ov == "auto":
            # worker threads only overlap DEVICE time if a spare cycle
            # exists: on the single-box launcher rig, more processes
            # than cores+1 means they would steal dispatch cycles
            # instead (measured: +5-10% at <=3 procs on 2 cores, -29%
            # at 5 — BASELINE round-7)
            procs = (self.n_srv + self.n_cl + self.n_repl)
            ov = "on" if (os.cpu_count() or 1) + 1 >= procs else "off"
        self._overlap = ov == "on" and not self.vote_mode
        self.wire_pool = None
        self.retire_pool = None
        if self._overlap:
            self.wire_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix=f"srv{self.me}-wire")
            self.retire_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix=f"srv{self.me}-retire")
        # reusable flat feed-buffer sets (zero-copy assembly): recycled
        # through a free list once their group retires AND its wire
        # sends drained — device_put may alias host memory on CPU
        # backends, and retirement (mask fetch) proves the group's
        # computation consumed its inputs
        self._feed_free: list[dict] = []
        # d2h overlap accounting: how many groups' verdict prefetches
        # were already finished when their retirement turn came, and the
        # wait the misses cost (the "mesh" trace track's ledger)
        self._prefetch_polls = 0
        self._prefetch_hits = 0
        self._prefetch_wait_s = 0.0
        if cfg.net_delay_us:
            self.tp.set_delay_us(int(cfg.net_delay_us))
        # durability (reference LOGGING + replication, SURVEY §5.4):
        # per-epoch command-log records; CL_RSPs gate on flush + replica ack
        self.logger = None
        self.log_path = None
        # my replicas: layout [servers | clients | replicas], replica r
        # backs primary r % n_srv — so mine sit every n_srv slots
        self.repl_ids = [self.n_srv + self.n_cl + self.me + k * self.n_srv
                         for k in range(cfg.replica_cnt)]
        self.repl_acked = {r: -1 for r in self.repl_ids}
        self.repl_applied.update({r: -1 for r in self.repl_ids})
        self._held_rsp: deque[tuple[int, int, np.ndarray]] = deque()
        if cfg.logging:
            from deneva_tpu.runtime.logger import EpochLogger
            self.log_path = os.path.join(cfg.log_dir,
                                         f"node{self.me}.log.bin")
            # recovery appends after the replayed prefix (truncated to
            # the resume boundary by _recover_state) instead of
            # truncating the whole file
            self.logger = EpochLogger(
                self.log_path, append=cfg.recover,
                flushed_epoch=self._resume_epoch - 1)
        # new_txn_queue: FIFO of (src client id, query block)
        self.pending: deque[tuple[int, wire.QueryBlock]] = deque()
        self.retry = _RetryQueue(cfg.backoff)
        # transactions waiting in `pending` + the retry queue: a running
        # count kept at append/pop (the stage clock samples it at every
        # group boundary), never a walk of the queues
        self._queue_txns = 0
        self.blob_buf: dict[int, dict] = {}
        self.vote_buf: dict[int, dict] = {}
        self.vote2_buf: dict[int, dict] = {}
        self._uniq_aborts = 0
        self.stop_epoch: int | None = None
        self.measure_epoch: int | None = None
        self.stats = Stats()
        # per-committed-txn restart/wait histograms (TxnStats analogue,
        # system/txn.h:72-114), accumulated host-side at retirement
        self._retry_hist = np.zeros(8, np.int64)
        self._wait_hist = np.zeros(8, np.int64)

    def _mesh_wrap(self, fn):
        """Run ``fn`` under this node's ``use_mesh`` context (identity
        when no mesh is armed): the context is read at jit TRACE time,
        so the per-epoch replay jits pick the same mesh-sharded code
        paths as the dispatch group."""
        if self.mesh is None:
            return fn
        _mesh = self._mesh_mod

        def wrapped(*a, **kw):
            with _mesh.use_mesh(self.mesh):
                return fn(*a, **kw)
        return wrapped

    # -- crash recovery (SURVEY §5.4: the reference logs and never
    # reads back; here deterministic replay IS the failover path) -------
    def _recover_state(self) -> None:
        """Rebuild partition state by replaying the local command log
        through the per-epoch jit, truncated to the last complete group
        boundary (a torn tail group is discarded — acks gate on whole-
        group durability in fault mode, so nothing acked is lost).
        Leaves ``self.db/cc_state/dev_stats`` at the boundary and writes
        a sidecar JSON the chaos harness uses for its bit-for-bit check.
        """
        import json

        from deneva_tpu.runtime.logger import (
            iter_record_spans, replay_into, state_digest,
            truncate_log_to_epoch)

        cfg = self.cfg
        path = os.path.join(cfg.log_dir, f"node{self.me}.log.bin")
        if not os.path.exists(path):
            raise RuntimeError(
                f"server {self.me}: recovery needs a command log at "
                f"{path}")
        with open(path, "rb") as f:
            buf = f.read()
        last = -1
        for e, _lo, _hi in iter_record_spans(buf):
            last = max(last, e)
        boundary = (last + 1) // self.C * self.C
        truncate_log_to_epoch(path, boundary)
        # per-epoch jit: the replay path this function exists for
        # (under the node's mesh context, so a sharded run replays
        # through the same mesh-sharded program it logged)
        step = self._mesh_wrap(make_dist_step(cfg, self.wl, self.be))
        sl = slice(self.me * self.b_loc, (self.me + 1) * self.b_loc)
        committed: list[np.ndarray] = []

        def seed_committed(epoch, block, active, done):
            del epoch
            # my slice's done txns were (or will be, via re-ack) acked:
            # they must never be admitted again
            mine = done[sl] & active[sl]
            if mine.any():
                committed.append(block.tags[sl][mine])

        self.db, self.cc_state, self.dev_stats, replayed = replay_into(
            path, cfg, self.wl, step, self.db, self.cc_state,
            self.dev_stats, stop_epoch=boundary,
            on_epoch=seed_committed if self._dedup_on else None)
        for tags in committed:
            for t in tags:
                p = int(t)
                if p not in self._committed_set:
                    self._committed_set.add(p)
                    self._committed_recent.append(p)
        self._resume_epoch = boundary
        meta = {"node": self.me, "resume_epoch": boundary,
                "log_last_epoch": last, "replayed_through": replayed,
                "state_digest": state_digest(self.db),
                "committed_tags": len(self._committed_set)}
        with open(os.path.join(cfg.log_dir,
                               f"node{self.me}.recovery.json"), "w") as f:
            json.dump(meta, f)
        print(f"[recovery] node={self.me} resume_epoch={boundary} "
              f"replayed_through={replayed} "
              f"digest={meta['state_digest'][:16]}", flush=True)

    def _announce_rejoin(self) -> None:
        """Tell every server and replica we are back and where we
        resume; then close the replica log gap (records the replica
        acked before the crash may trail our truncated prefix — re-ship
        (acked, resume) so its file stays a byte prefix of ours)."""
        from deneva_tpu.runtime.logger import iter_record_spans

        msg = wire.encode_shutdown(self._resume_epoch)
        for p in range(self.n_srv):
            if p != self.me:
                self.tp.send(p, "REJOIN", msg)
        # mutate in place: rebinding would shed the owner_check guard
        # installed over this set at run() entry
        self._rejoin_pending.clear()
        self._rejoin_pending.update(self.repl_ids)
        for r in self.repl_ids:
            self.tp.send(r, "REJOIN", msg)
        self.tp.flush()
        if not self.repl_ids:
            return
        t0 = time.monotonic()
        # cfg.failover_timeout_s, not a hidden 30 s wall: slow CI boxes
        # raise the whole failover-wait family with one knob
        while self._rejoin_pending \
                and time.monotonic() - t0 < self.cfg.failover_timeout_s:
            self._drain(timeout_us=20_000)
        if self._rejoin_pending:
            raise RuntimeError(
                f"server {self.me}: replicas {sorted(self._rejoin_pending)}"
                " never answered the rejoin handshake within "
                f"failover_timeout_s={self.cfg.failover_timeout_s:g}")
        with open(self.log_path, "rb") as f:
            buf = f.read()
        for r in self.repl_ids:
            acked = self.repl_acked[r]
            for e, lo, hi in iter_record_spans(buf):
                if acked < e < self._resume_epoch:
                    self._fenced_send(r, "LOG_MSG", buf[lo:hi])
        self.tp.flush()

    # -- message routing (reference InputThread::server_recv_loop) ------
    def _route(self, src: int, rtype: str, payload: bytes) -> None:
        if self._fd is not None and src < self.n_srv and src != self.me:
            # ANY frame from a server peer is a heartbeat observation
            # (the epoch exchange piggybacks); a suspected→fresh
            # transition is a partition HEAL — catch the peer up
            gap = self._fd.observe(src, time.monotonic())
            if gap is not None and src not in self._reassigned:
                self._heal_peer(src, gap)
        if rtype == "CL_QRY_BATCH":
            if (self._elastic and self._dedup_on
                    and len(self.smap.slots_of(self.me)) == 0):
                # drained/spare node in fault mode: redirect-NACK — the
                # client's resend sweep retargets the unacked tags onto
                # an owner (exactly-once holds: nothing was admitted).
                # Without the fault machinery there is no resend path,
                # so a slotless node ADMITS instead (admission is
                # ownership-independent in the merged-deterministic
                # model; execution stays slot-map-local) — no txn is
                # ever dropped on the floor.
                self._redirects += 1
                self.tp.send(src, "MAP_UPDATE", self._M.encode_map_msg(
                    self.smap, -1, self._M.REASON_INSTALL, self.me))
                return
            blk = wire.decode_qry_block(payload)
            # stamp the source client into the tag's high bits? no — tags
            # are opaque to servers; remember src alongside
            if self._dedup_on:
                blk = self._admit_dedup(src, blk)
                if blk is None:
                    return
            if self.adm is not None:
                # admission control AFTER dedup: committed resends were
                # already re-acked and in-flight dups dropped above, so
                # only genuinely fresh queries meter against quotas
                blk = self._admission_gate(src, blk)
                if blk is None:
                    return
            if self.tel is not None:
                # flight recorder: the "admission pop" lifecycle hop —
                # the sampled tags (same lane predicate the client used)
                # entered this server's pending queue.  Keyed on the
                # packed id the contribution path stamps.
                self.tel.record(
                    (np.int64(src) << 40) | (blk.tags & _TAG_MASK),
                    ST_ADMIT)
            self.pending.append((src, blk))
            self._queue_txns += len(blk)
        elif rtype == "EPOCH_BLOB":
            if self._fencing:
                # fence envelope: the sender's map_version precedes the
                # blob.  Reject a RETIRED peer's stale incarnation with
                # FENCE_NACK (a live survivor briefly one deterministic
                # reassignment behind is NOT stale — pipeline skew);
                # versions ahead of ours buffer as usual (we will apply
                # the same cutover at the same boundary).
                ver, off = self._FD.fence_peek(payload)
                if ver < self.smap.version and src in self._reassigned:
                    self._fence_nacks += 1
                    self._fence_spans["fence"] += 1e-3
                    self.tp.send(src, "FENCE_NACK",
                                 self._FD.encode_fence_nack(
                                     self.smap.version, ver,
                                     self._epoch_cur))
                    return
                payload = payload[off:]
                if src < self.n_srv:
                    e0 = wire.peek_blob_epoch(payload)
                    if e0 > self._blob_seen_from.get(src, -1):
                        self._blob_seen_from[src] = e0
            # keep the raw payload: collect decodes it STRAIGHT into the
            # stacked feed slice (decode_epoch_blob_into), no arrays
            # allocated here and copied again there
            epoch = wire.peek_blob_epoch(payload)
            self.blob_buf.setdefault(epoch, {})[src] = payload
        elif rtype == "VOTE":
            epoch, c, a, bnd = wire.decode_vote(payload)
            self.vote_buf.setdefault(epoch, {})[src] = (c, a, bnd)
        elif rtype == "VOTE2":
            epoch, _, a, _b = wire.decode_vote(payload)
            self.vote2_buf.setdefault(epoch, {})[src] = a
        elif rtype == "SHUTDOWN":
            self.stop_epoch = wire.decode_shutdown(payload)
        elif rtype == "MEASURE":
            self.measure_epoch = wire.decode_shutdown(payload)
        elif rtype == "LOG_RSP":
            # this replica acked everything up to this epoch (FIFO link)
            e = wire.decode_shutdown(payload)
            self.repl_acked[src] = max(self.repl_acked.get(src, -1), e)
            self._rejoin_pending.discard(src)
        elif rtype == "LOG_ACK":
            # geo quorum ack: durability watermark + the follower's
            # applied horizon (replica-lag visibility for the summary)
            e, applied = georepl.decode_log_ack(payload)
            self.repl_acked[src] = max(self.repl_acked.get(src, -1), e)
            self.repl_applied[src] = max(self.repl_applied.get(src, -1),
                                         applied)
            self._rejoin_pending.discard(src)
        elif rtype == "REJOIN":
            # a crashed peer server recovered and resumes at this epoch
            # boundary: resend our retained contribution blobs it missed
            # while its link was down (idempotent — blob_buf keys on
            # (epoch, src) and the bytes are verbatim), drop any stale
            # buffered blobs of its dead incarnation past the boundary,
            # and (coordinator only) re-announce the measure/stop epochs
            # its restart lost
            e = wire.decode_shutdown(payload)
            if not self._fencing:
                # crash-recovery rejoin only: with fencing armed a
                # server REJOIN is a partition HEAL from a live peer
                # that never died (fenced nodes exit 18 and stay down)
                # — its buffered blobs are valid and must survive
                for ep, blobs in self.blob_buf.items():
                    if ep >= e:
                        blobs.pop(src, None)
            with self._sent_lock:
                retained = list(self._sent_blobs)
            for ep, blob in retained:
                if ep >= e:
                    # fencing: re-wrapped at the CURRENT map version (a
                    # retained blob predating a reassignment must not
                    # read as a stale incarnation's frame)
                    self._fenced_send(src, "EPOCH_BLOB", blob)
            # ANY surviving peer echoes the coordinator's announcements
            # (identical values everywhere, so duplicates are no-ops):
            # a restarted node — including a restarted coordinator —
            # re-learns the window instead of inventing a later one
            if self.measure_epoch is not None:
                self.tp.send(src, "MEASURE",
                             wire.encode_shutdown(self.measure_epoch))
            if self.stop_epoch is not None:
                self.tp.send(src, "SHUTDOWN",
                             wire.encode_shutdown(self.stop_epoch))
            self.tp.flush()
        elif rtype == "MIGRATE_BEGIN":
            # controller-announced rebalance: install at the cutover
            # group boundary (applied by _elastic_tick, never mid-group)
            smap, cutover, reason, subject = self._M.decode_map_msg(payload)
            if smap.version > self.smap.version:
                self._mig_pending = dict(map=smap, cutover=cutover,
                                         reason=reason, subject=subject)
        elif rtype == "MIGRATE_ROWS":
            v = self._M.peek_rows_version(payload)
            self._mig_rows.setdefault(v, {})[src] = payload
        elif rtype == "MAP_UPDATE":
            pass  # client-facing; a server learns maps via MIGRATE_BEGIN
        elif rtype == "HEARTBEAT":
            # liveness + ack-lease grant: the sender's map version and
            # the highest of OUR epochs whose blob it has received
            ver, seen, _ep = self._FD.decode_heartbeat(payload)
            if src < self.n_srv:
                if seen > self._hb_peer_seen.get(src, -1):
                    self._hb_peer_seen[src] = seen
                if ver < self.smap.version and src in self._reassigned:
                    # a retired incarnation is still beating: fence it
                    self._fence_nacks += 1
                    self._fence_spans["fence"] += 1e-3
                    self.tp.send(src, "FENCE_NACK",
                                 self._FD.encode_fence_nack(
                                     self.smap.version, ver,
                                     self._epoch_cur))
        elif rtype == "FENCE_NACK":
            # a peer running a NEWER map incarnation rejected our frame:
            # we were fenced out while partitioned — self-halt rather
            # than serve split-brain writes.  (A nack echoing our own
            # version is a stale crossing; ignore.)
            their_ver, _stale, ep = self._FD.decode_fence_nack(payload)
            self._fence_nack_rx += 1
            if their_ver > self.smap.version and self._mig_pending is None:
                self._self_fence("fence_nack", ep)
        elif rtype == "HEAL":
            # post-partition map catch-up: if the healed majority's map
            # no longer includes us, we were fenced out; otherwise both
            # sides already agree (the REJOIN resend covers the blobs)
            ep, ver, owners = self._FD.decode_heal(payload)
            if ver > self.smap.version and self._mig_pending is None \
                    and self.me not in owners:
                self._self_fence("healed_out", ep)
        elif rtype == "METRICS":
            # metrics bus frame: the sender believes we are the lowest
            # live server — aggregate (building the aggregator lazily
            # covers the role handoff after the boot aggregator retires)
            if self.mbus is not None:
                if self.magg is None:
                    self.magg = self._MB.Aggregator(self.cfg, self.me,
                                                    append=self.cfg.recover)
                self.magg.feed(self._MB.frame_record(payload))
        elif rtype == "INIT_DONE":
            pass  # late barrier duplicate; the barrier itself already ran

    def _drain(self, timeout_us: int = 0, max_msgs: int = 4096) -> None:
        # bounded per call: an open-loop flood (the overload tier's
        # flash crowd) can sustain a non-empty recv queue indefinitely,
        # and an unbounded drain would receive-livelock the epoch loop.
        # 4096 is far above any per-epoch message count on the normal
        # paths (every caller loops, so nothing is lost — later
        # messages just wait for the next call).
        for _ in range(max_msgs):
            m = self.tp.recv(timeout_us)
            if m is None:
                return
            self._route(*m)
            timeout_us = 0

    # -- barrier (reference INIT_DONE, system/sim_manager.cpp:95-100) ----
    def barrier(self) -> None:
        wire.run_barrier(self.tp, self.me,
                         self.n_srv + self.n_cl + self.n_repl,
                         self._route, f"server {self.me}",
                         self.setup_wait_s)

    # -- idempotent admission (fault mode): message loss degrades
    # throughput instead of correctness --------------------------------
    def _admit_dedup(self, src: int,
                     blk: wire.QueryBlock) -> wire.QueryBlock | None:
        """Filter a CL_QRY_BATCH against the in-system and recently-
        committed id sets (keyed on the same packed client<<40|tag id
        the admission path stamps).  Already-committed tags are re-acked
        immediately — a resend after a lost CL_RSP must converge, not
        re-execute; in-flight duplicates are dropped.  Returns the block
        of genuinely fresh txns (None if empty)."""
        packed = (np.int64(src) << 40) | (blk.tags & _TAG_MASK)
        fresh = np.ones(len(blk), bool)
        reack: list[int] = []
        for i, pid in enumerate(packed):
            p = int(pid)
            if p in self._committed_set:
                fresh[i] = False
                reack.append(int(blk.tags[i]))
            elif p in self._in_system:
                fresh[i] = False
                self._dup_admits += 1
            else:
                self._in_system.add(p)
        if reack:
            self._reacks += len(reack)
            self.tp.send(src, "CL_RSP",
                         wire.encode_cl_rsp(np.asarray(reack, np.int64)))
        if fresh.all():
            return blk
        if not fresh.any():
            return None
        return blk.take(np.where(fresh)[0])

    def _admission_gate(self, src: int,
                        blk: wire.QueryBlock) -> wire.QueryBlock | None:
        """Per-tenant admission (overload tier): token-bucket quotas +
        bounded queue + SLO shed decide per row; shed rows are answered
        with ADMIT_NACK (tags + retry-after hints) instead of being held
        forever.  Returns the admitted block (None if everything shed)."""
        from deneva_tpu.runtime.admission import admit_nack_parts

        reason, retry = self.adm.admit(blk.tags,
                                       time.monotonic_ns() // 1000)
        ok = reason == 0
        if ok.all():
            return blk
        nk = np.where(~ok)[0]
        if self.mbus is not None:
            # bus frame field: admission NACKs since the last frame
            self.mbus.shed += len(nk)
        # clip before the uint32 narrowing: a tiny quota against a big
        # deficit can push the refill hint past 2^32 us
        self.tp.sendv(src, "ADMIT_NACK",
                      admit_nack_parts(blk.tags[nk],
                                       retry[nk].clip(max=0xFFFFFFFF)
                                       .astype(np.uint32)))
        if not ok.any():
            return None
        return blk.take(np.where(ok)[0])

    def _retire_dedup(self, done_tags: np.ndarray) -> None:
        """Move committed packed ids from in-system to the bounded
        recently-committed ring (admission dedup's re-ack source)."""
        for t in done_tags:
            p = int(t)
            self._in_system.discard(p)
            if p not in self._committed_set:
                self._committed_set.add(p)
                self._committed_recent.append(p)
        while len(self._committed_recent) > self._committed_cap:
            self._committed_set.discard(self._committed_recent.popleft())

    # -- partition & gray-failure tolerance (fencing layer) --------------
    def _fault_net_tick(self) -> None:
        """Apply/lift this node's share of the armed partition/stall
        faults by wall clock.  TX-side only: each sender blackholes its
        own outbound at its own loop positions (group boundaries and
        blob-wait polls), so the first silenced epoch is group-aligned
        and identical on every receiver — which is what lets every
        survivor derive the same reassignment with no negotiation."""
        t = time.monotonic() - self._t_run0
        if self._partitions is not None:
            flap = self.cfg.fault_partition_flap_s
            for i, (peer, start) in enumerate(self._part_links):
                if t < start:
                    want = False
                elif flap > 0:
                    want = int((t - start) // flap) % 2 == 0
                else:
                    want = True
                if want != self._part_on[i]:
                    self._part_on[i] = want
                    self.tp.set_partition(
                        peer, self.tp.PART_TX if want
                        else self.tp.PART_NONE)
        if self._stall is not None and not self._stall_on:
            _node, ms, start = self._stall
            if t >= start:
                # gray-slow: EVERY outbound link stalls (a slow process
                # is slow to everyone); sockets stay open, peer_alive
                # stays true — only the suspicion score sees it
                self._stall_on = True
                for p in range(self.n_srv + self.n_cl + self.n_repl):
                    if p != self.me:
                        self.tp.set_peer_stall_us(p, int(ms * 1000))

    def _fenced_send(self, dest: int, rtype: str, payload) -> None:
        """Single-payload send that grows the 12-byte fence envelope
        (sender's map version) when fencing is armed — THE one place
        the wrap-or-not decision lives for EPOCH_BLOB/LOG_MSG bodies
        (the zero-copy parts broadcast prepends ``fence_parts`` to its
        parts list instead).  ``payload`` may be bytes or a C-contiguous
        array (``sendv`` frames either)."""
        if self._fencing:
            self.tp.sendv(dest, rtype,
                          [self._FD.fence_parts(self.smap.version),
                           payload])
        else:
            self.tp.send(dest, rtype, payload)

    def _maybe_heartbeat(self, now_s: float) -> None:
        """Standalone HEARTBEAT on its cadence to every live server
        peer.  The payload is per-link: our map version plus the
        highest epoch whose blob we received from THAT peer (our
        ack-lease grant to it)."""
        if now_s < self._hb_next_s:
            return
        self._hb_next_s = now_s + self.cfg.fencing_heartbeat_ms / 1e3
        for p in range(self.n_srv):
            if p != self.me and p not in self._reassigned:
                self.tp.send(p, "HEARTBEAT", self._FD.encode_heartbeat(
                    self.smap.version, self._blob_seen_from.get(p, -1),
                    self._epoch_cur))

    def _heal_peer(self, p: int, gap_s: float) -> None:
        """Suspected→fresh transition: partition heal.  Catch-up rides
        the existing REJOIN path — the peer resends its retained blobs
        from our first-missing epoch (and re-echoes measure/stop) — and
        a HEAL frame carries our map so a behind peer learns it was (or
        was not) fenced out.  Never a dual-map merge."""
        self._fence_spans["heal"] += gap_s * 1e3
        self.tp.send(p, "REJOIN", wire.encode_shutdown(
            self._blob_seen_from.get(p, -1) + 1))
        self.tp.send(p, "HEAL", self._FD.encode_heal(
            self._epoch_cur, self.smap.version, self.smap.owners))
        self.tp.flush()

    def _fence_ack_ok(self, epoch: int) -> bool:
        """The epoch-boundary ack lease: an epoch's CL_RSPs (and its
        committed-id re-ack authority) may release only once a MAJORITY
        of the live server set — self included — has confirmed
        receiving that epoch's blob (heartbeat ``blob_seen``).  A
        partitioned primary's acks for epochs the surviving side never
        saw are thereby causally impossible, not merely unlikely."""
        if not self._fencing:
            return True
        alive = [p for p in range(self.n_srv)
                 if p not in self._reassigned]
        have = 1 + sum(1 for p in alive if p != self.me
                       and self._hb_peer_seen.get(p, -1) >= epoch)
        return self._FD.majority_confirms(len(alive), have)

    def _fence_fields(self, self_halt: int, reason: str = "",
                      epoch: int = -1) -> dict:
        d = {"phi_peak": (self._fd.phi_peak if self._fd else 0.0),
             "suspect_cnt": (self._fd.suspect_cnt if self._fd else 0),
             "fence_nack_cnt": self._fence_nacks,
             "fence_nack_rx": self._fence_nack_rx,
             "self_halt": self_halt,
             "heal_cnt": (self._fd.heal_cnt if self._fd else 0),
             "reassign_epoch": self._fence_reassign_epoch,
             "last_acked_epoch": self._fence_last_ack}
        if reason:
            d["reason"] = reason
        if epoch >= 0:
            d["epoch"] = epoch
        return d

    def _self_fence(self, reason: str, epoch: int) -> None:
        """Fenced out (newer map incarnation exists, or we are the
        minority side of a partition): emit the [fencing] line and the
        sidecar the harness audits, drain the log, and self-halt with
        the exit-18 sentinel — the launcher retires it as a scenario
        outcome; serving even one more write would be split-brain."""
        import json

        print(self._FD.fencing_line(
            self.me, self._fence_fields(1, reason, epoch)), flush=True)
        if self.logger is not None and epoch > 0:
            self.logger.wait_flushed(epoch - 1, timeout=5.0)
        with open(os.path.join(self.cfg.log_dir,
                               f"node{self.me}.fenced.json"), "w") as f:
            json.dump({"node": self.me, "reason": reason,
                       "epoch": int(epoch),
                       "map_version": int(self.smap.version),
                       "last_acked_epoch": int(self._fence_last_ack)}, f)
        if self.tel is not None:
            # the fenced node's lifecycle events stay auditable
            self.tel.flush()
            self._metrics.close()
        if self.magg is not None:
            self.magg.close()
        self.tp.flush()
        os._exit(self._FD.FENCED_EXIT)

    # -- the host path of an epoch group, on ONE reusable set of flat feed
    # buffers.  `_bcast_views`, `_log_group_views` and `_prefetch_retire`
    # are PURE given their inputs, so `host_overlap` may hand them to a
    # worker thread; the rest is the dispatch thread's, at its loop
    # position. ----------------------------------------------------------
    def _feed_acquire(self) -> dict:
        """One reusable flat feed-buffer set [C, b, ...].  Only the
        active plane is re-zeroed here: every other lane is covered by
        exactly one per-server slice region, which its filler either
        overwrites or tail-zeroes (_contribution_into/_collect_into) —
        so unfilled lanes of a reused buffer are zero, and every node
        builds the same feed and log bytes, without a full-buffer memset
        per group."""
        if self._feed_free:
            fs = self._feed_free.pop()
            fs["active"].fill(False)
            return fs
        C, b = self.C, self.b_merged
        return {
            "keys": np.zeros((C, b, self._width), np.int32),
            "types": np.zeros((C, b, self._width), np.int8),
            "scal": np.zeros((C, b, self._n_scalars), np.int32),
            "tags": np.zeros((C, b), np.int64),
            "ts": np.zeros((C, b), np.int64),
            "ts32": np.zeros((C, b), np.int32),
            "active": np.zeros((C, b), bool),
        }

    @staticmethod
    def _zero_lanes(fs: dict, i: int, lo: int, hi: int) -> None:
        """Unfilled lanes of a reused buffer read zero (and inactive), so
        every node builds the same feed and log bytes."""
        for k in ("keys", "types", "scal", "tags", "ts"):
            fs[k][i, lo:hi] = 0

    # admission (client_thread + new_txn_queue + abort_queue)
    def _contribution_into(self, epoch: int, fs: dict, i: int
                           ) -> tuple[wire.QueryBlock, np.ndarray,
                                      np.ndarray, np.ndarray]:
        """Up to b_loc txns — ready retries first, then fresh arrivals —
        written STRAIGHT into this node's slice of feed row ``i`` (no
        ``QueryBlock.concat``, no second fill pass).

        Fresh arrivals get the home client's transport id packed into the
        tag high bits (client << 40 | tag) and an epoch-anchored birth
        timestamp ``(epoch+1)*b_merged + me*b_loc + position``: unique
        across nodes AND monotone with epochs, so a (re)stamped txn always
        exceeds every watermark the T/O family persisted in earlier epochs
        — per-node counters would let a slow node starve behind a fast
        node's watermarks.  Retried blocks keep their packed tags, and
        keep their birth ts unless the backend wants restarts re-stamped
        (CCBackend.fresh_ts_on_restart — WAIT_DIE preserves age, which is
        its starvation-freedom) — and even then only entries whose last
        verdict was an ABORT: deferred (waiting) txns keep their birth ts
        like the in-process pool and the reference's parked requests.
        Returns (view block, abort_cnt, birth-ts view, defer_cnt)."""
        lo = self.me * self.b_loc
        keys_r, types_r = fs["keys"][i], fs["types"][i]
        scal_r, tags_r, ts_r = fs["scal"][i], fs["tags"][i], fs["ts"][i]
        blocks, counts, tss, abms, dfcs = self.retry.pop_ready(
            epoch, self.b_loc)
        if self.be.fresh_ts_on_restart:
            # mark aborted retries for re-stamping (-1 = stamp me below)
            tss = [np.where(ab, np.int64(-1), ts)
                   for ts, ab in zip(tss, abms)]
        n = 0
        for blk, ts in zip(blocks, tss):
            m = len(blk)
            o = lo + n
            keys_r[o:o + m] = blk.keys
            types_r[o:o + m] = blk.types
            scal_r[o:o + m] = blk.scalars
            tags_r[o:o + m] = blk.tags
            ts_r[o:o + m] = ts
            n += m
        n_retry = n
        while self.pending and n < self.b_loc:
            src, blk = self.pending[0]
            room = self.b_loc - n
            if len(blk) <= room:
                self.pending.popleft()
                use = blk
            else:
                self.pending[0] = (src, blk.slice(room, len(blk)))
                use = blk.slice(0, room)
            m = len(use)
            o = lo + n
            keys_r[o:o + m] = use.keys
            types_r[o:o + m] = use.types
            scal_r[o:o + m] = use.scalars
            tags_r[o:o + m] = (np.int64(src) << 40) | (use.tags & _TAG_MASK)
            ts_r[o:o + m] = -1                        # -1 = stamp me
            counts.append(np.zeros(m, np.int32))
            dfcs.append(np.zeros(m, np.int32))
            n += m
        self._queue_txns -= n
        if self.adm is not None and n > n_retry:
            # admission-queue delay ledger: these fresh rows just left
            # the bounded queue for epoch formation
            self.adm.on_pop(n - n_retry, time.monotonic_ns() // 1000)
        # the unfilled tail of my slice (reused buffer)
        self._zero_lanes(fs, i, lo + n, lo + self.b_loc)
        sl = slice(lo, lo + n)
        base = np.int64(epoch + 1) * self.b_merged + lo
        stamped = base + np.arange(n, dtype=np.int64)
        if n and stamped[-1] >= 2**31:
            raise RuntimeError(
                "birth-timestamp horizon exceeded (2^31; ~2^31/epoch_batch "
                "epochs); restart the run — the reference's 64-bit ts has "
                "the same finite-horizon caveat at larger scale")
        # fresh arrivals and (for fresh-ts backends) aborted restarts
        # carry the -1 sentinel; deferred waiters keep their birth ts
        np.copyto(ts_r[sl], stamped, where=ts_r[sl] < 0)
        if n and ts_r[sl].min() < 1:
            # ts==0 is reserved as the MVCC read-only serialization
            # sentinel (cc/timestamp.py order, ycsb.py ver_ts): a real
            # txn stamped 0 would be misrouted to the live snapshot
            raise RuntimeError(
                f"birth timestamp below 1 (min={ts_r[sl].min()}): the "
                "ts>=1 stamping invariant is broken")
        fs["active"][i, sl] = True
        block = wire.QueryBlock(keys_r[sl], types_r[sl], scal_r[sl],
                                tags_r[sl])
        cnt = np.concatenate(counts) if counts else np.zeros(0, np.int32)
        dfc = np.concatenate(dfcs) if dfcs else np.zeros(0, np.int32)
        return block, cnt, ts_r[sl], dfc

    def _wire(self, fn, *args) -> Future:
        """Run a wire body — on the wire worker or, without one, here
        and now: its future either way (read at the group's retirement)."""
        if self.wire_pool is not None:
            return self.wire_pool.submit(fn, *args)
        done: Future = Future()
        done.set_result(fn(*args))
        return done

    def _bcast_views(self, e: int, block: wire.QueryBlock,
                     birth_ts: np.ndarray) -> None:
        """Wire body: broadcast this node's contribution as
        scatter-gather parts (``dt_sendv``) — zero Python-side payload
        copies; the native layer frames header + ts + columns in one
        pass.  Failover mode materializes the bytes instead: the
        retained blob must survive feed-buffer recycling for verbatim
        REJOIN resends."""
        if self._failover:
            blob = wire.encode_epoch_blob(e, block, birth_ts)
            with self._sent_lock:
                # retained RAW: a REJOIN resend re-wraps with the then-
                # current version (a retained pre-reassignment stamp
                # must not read as a stale incarnation)
                self._sent_blobs.append((e, blob))
            for p in range(self.n_srv):
                if p != self.me:
                    self._fenced_send(p, "EPOCH_BLOB", blob)
            return
        parts = wire.epoch_blob_parts(e, birth_ts, block.tags, block.keys,
                                      block.types, block.scalars)
        if self._fencing:
            parts = [self._FD.fence_parts(self.smap.version)] + parts
        self.tp.sendv_many([p for p in range(self.n_srv) if p != self.me],
                           "EPOCH_BLOB", parts)

    def _collect_into(self, eps, fs: dict) -> float:
        """RDONE barrier + zero-copy merge: each peer's raw EPOCH_BLOB
        payload decodes STRAIGHT into its slice of the stacked feed row
        (``decode_epoch_blob_into``).  Returns seconds spent decoding
        (the caller's idle ledger carves it back out)."""
        decode_s = 0.0
        for i, (e, _blk, _cnt, _ts, _dfc) in enumerate(eps):
            self._wait_blobs(e)
            t0 = time.monotonic()
            if self._elastic and self._contrib_gone:
                # a retired contributor's slice reads zero and inactive
                # on every node (reused buffer hygiene AND cross-node
                # feed determinism)
                for p, ge in self._contrib_gone.items():
                    if ge <= e:
                        o = p * self.b_loc
                        self._zero_lanes(fs, i, o, o + self.b_loc)
            for s, payload in self.blob_buf.pop(e, {}).items():
                o = s * self.b_loc
                hi = o + self.b_loc
                _ep, m = wire.decode_epoch_blob_into(
                    payload, fs["tags"][i, o:hi], fs["ts"][i, o:hi],
                    fs["keys"][i, o:hi], fs["types"][i, o:hi],
                    fs["scal"][i, o:hi])
                fs["active"][i, o:o + m] = True
                if m < self.b_loc:      # reused buffer: a short one's tail
                    self._zero_lanes(fs, i, o + m, hi)
            decode_s += time.monotonic() - t0
        return decode_s

    def _log_group_views(self, fs: dict, eps) -> None:
        """Wire body: one-pass framed record per epoch straight from the
        merged feed row (``pack_record_views``), appended locally and
        shipped to my replicas — identical bytes by construction (one
        packing, two destinations), and the bytes of
        ``logger.pack_record(wire.encode_epoch_blob(...))``, which the
        replica's packer and the replay read (fuzz-tested)."""
        from deneva_tpu.runtime.logger import pack_record_views
        for i, (e, _blk, _cnt, _ts, _dfc) in enumerate(eps):
            framed = pack_record_views(e, fs["ts"][i], fs["tags"][i],
                                       fs["keys"][i], fs["types"][i],
                                       fs["scal"][i], fs["active"][i])
            self.logger.append(e, b"", fs["active"][i], framed=framed)
            for r in self.repl_ids:
                # fence envelope rides the durability stream too: the
                # replica strips it before appending, so its log stays
                # a byte prefix of ours
                self._fenced_send(r, "LOG_MSG", framed)

    def _prefetch_retire(self, group: dict):
        """Retire body: wait out the verdict d2h copy, unpack the planes
        and precompute the PURE per-epoch retirement pieces (committed
        tags, per-client ack splits, histogram increments); `_retire` is
        left with state mutation and sends.  Returns ``(done, abort,
        defer, rep, acks)`` and the seconds the split took: on the
        dispatch thread they are work inside its wait for the device."""
        import jax

        with stage_span("prefetch", group["eps"][0][0]):
            masks = jax.device_get(group["masks"])
            if group["packed"]:
                # uint8 bit-planes [3 (+1 repaired), C, pb/8]; the d2h
                # copy was started asynchronously at dispatch
                planes = np.unpackbits(np.asarray(masks), axis=-1,
                                       bitorder="little")
                masks = planes[:, :, :self._plane_n].astype(bool)
        t0 = time.monotonic()
        done, abort, defer = masks[0], masks[1], masks[2]
        # a VOTE epoch's three host masks are this node's slice already,
        # and no repaired plane rides with them
        rep = masks[3] if self._repair and group["packed"] else None
        lo = self._plane_lo if group["packed"] else 0
        acks = []
        for i, (_e, block, abort_cnt, _ts, dfc) in enumerate(group["eps"]):
            n = len(block)
            my_commit = done[i, lo:lo + n]
            if not my_commit.any():
                acks.append(None)
                continue
            # tag high bits carry the home client's transport id
            tags = block.tags[my_commit]
            clients = tags >> 40
            rsp = [(int(c), tags[clients == c] & _TAG_MASK)
                   for c in np.unique(clients)]
            # TxnStats analogue: whole-life restart/wait counts of each
            # committed txn (clipped to the 8-bucket family)
            retry_inc = np.bincount(np.minimum(abort_cnt[my_commit], 7),
                                    minlength=8)
            wait_inc = np.bincount(np.minimum(dfc[:n][my_commit], 7),
                                   minlength=8)
            acks.append((tags, rsp, retry_inc, wait_inc))
        return done, abort, defer, rep, acks, time.monotonic() - t0

    def _durable_through(self) -> int:
        """Highest epoch that is on disk locally AND acked by every one of
        my replicas (the reference's `log_flushed && repl_finished` commit
        gate, `system/txn.cpp:436`).  Geo mode relaxes "every" to a
        QUORUM of ``geo_quorum`` LOG_ACKs over the LIVE follower set
        (replication.durable_quorum): a slow WAN follower stops gating
        commit latency, and a DEAD one (region loss) leaves the quorum
        instead of freezing the horizon — held acks must keep releasing
        across the promotion."""
        e = self.logger.flushed_epoch
        if self._geo and self.repl_ids:
            return georepl.durable_quorum(
                {r: self.repl_acked[r] for r in self.repl_ids},
                self.tp.peer_alive, self.cfg.geo_quorum, e)
        for r in self.repl_ids:
            e = min(e, self.repl_acked[r])
        return e

    def _durable_ack_epoch(self) -> int:
        """Durability horizon for releasing held CL_RSPs.  In failover
        mode it rounds DOWN to a group boundary: recovery truncates the
        log to the last complete group, so an ack must never ride a
        partially-durable group a crash could tear away."""
        e = self._durable_through()
        if self._failover:
            e = (e + 1) // self.C * self.C - 1
        return e

    def _flush_held_rsp(self, wait_epoch: int | None = None) -> None:
        """Release group-committed responses whose epoch is durable.
        With ``wait_epoch`` set, block (bounded) until that epoch is
        durable — used at shutdown so no committed txn loses its ack."""
        if self.logger is None:
            return
        held_any = bool(self._held_rsp) or (self._full_planes
                                            and bool(self._held_commit))
        if wait_epoch is not None and held_any:
            # the bounded wait exists only to release held items; with
            # nothing held (e.g. a geo server whose region admits no
            # clients) it would just burn the 10 s budget
            t0 = time.monotonic()
            while (self._durable_ack_epoch() < wait_epoch
                   or (self._fencing
                       and not self._fence_ack_ok(wait_epoch))) \
                    and time.monotonic() - t0 < 10.0:
                self.logger.wait_flushed(wait_epoch, timeout=0.05)
                if self._fencing:
                    # the lease needs live heartbeat confirmations of
                    # the final epochs' blobs — keep beating + draining
                    # through the shutdown flush
                    self._maybe_heartbeat(time.monotonic())
                    self._drain(timeout_us=10_000)
                elif self.n_repl:
                    self._drain(timeout_us=10_000)
        durable = self._durable_ack_epoch()
        if self.mbus is not None:
            # bus quorum ledger: hold -> release lag of every epoch
            # whose acks just went durable (the generic twin of the geo
            # quorum ledger below — armed by metrics alone)
            self.mbus.release_through(durable, time.monotonic())
        if self._geo and self._quorum_hold_t:
            # quorum wait ledger: hold -> release lag of each retiring
            # epoch.  Epochs wait overlapped (the pipeline holds whole
            # groups), so the [replication]/[summary] quorum_stall_ms is
            # the MEAN per-epoch lag at the quorum gate, not a sum; the
            # timeline span carries the max released this pass (the
            # visible stall width).
            now = time.monotonic()
            released = [e for e in self._quorum_hold_t if e <= durable]
            if released:
                lags = [now - self._quorum_hold_t.pop(e)
                        for e in released]
                self._quorum_stall_s += sum(lags)
                self._quorum_release_cnt += len(lags)
                self._geo_spans["quorum"] += max(lags) * 1e3
        if self._full_planes:
            while self._held_commit and self._held_commit[0][0] <= durable:
                if self._fencing \
                        and not self._fence_ack_ok(self._held_commit[0][0]):
                    break   # re-ack authority waits for the same lease
                _, ids = self._held_commit.popleft()
                self._retire_dedup(ids)
        while self._held_rsp and self._held_rsp[0][1] <= durable:
            if self._fencing:
                # epoch-boundary ack lease: durable is not enough — a
                # majority must have CONFIRMED this epoch's blob, or a
                # partitioned primary could ack writes the surviving
                # side never saw (the split-brain this layer closes)
                e = self._held_rsp[0][1]
                if not self._fence_ack_ok(e):
                    break
                if e > self._fence_last_ack:
                    self._fence_last_ack = e
            c, e_rel, tags = self._held_rsp.popleft()
            if self._dedup_on:
                # the ack is now safe to (re-)issue: only here do the
                # packed ids gain re-ack authority in the committed set
                self._retire_dedup((np.int64(c) << 40) | tags)
            if self.tel is not None:
                # quorum hold -> release hop: the epoch went durable
                # (and, under fencing, its ack lease confirmed) — the
                # CL_RSP leaves right below
                self.tel.record((np.int64(c) << 40) | tags, ST_RELEASE,
                                epoch=e_rel)
            # scatter-send parts: identical wire bytes, no encode copy
            self.tp.sendv(c, "CL_RSP", wire.cl_rsp_parts(tags))

    # -- batched 2PC round (VOTE protocol; see make_vote_steps) ----------
    def _vote_epoch(self, epoch: int, query, active_np, active_j, ts_j, tl
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Local prepare -> vote exchange -> global decision -> apply.
        The vote exchange is the epoch-batched analogue of the
        reference's per-txn RPREPARE/RACK_PREP round trip — one extra
        network round per epoch, amortized over the whole batch."""
        import jax.numpy as jnp

        vc, va, vd, lo = self.vote_step(self.db, self.cc_state, query,
                                        active_j, ts_j)
        vc, va, vd = np.asarray(vc), np.asarray(va), np.asarray(vd)
        if tl:
            tl.mark("prepare")
        msg = wire.encode_vote(epoch, vc, va,
                               np.asarray(lo) if self.maat_vote else None)
        for p in range(self.n_srv):
            if p != self.me:
                self.tp.send(p, "VOTE", msg)
        self.tp.flush()
        self._wait_votes(self.vote_buf, epoch, "votes")
        if tl:
            tl.mark("votes")
        commit_g, abort_g = vc.copy(), va.copy()
        glo = np.asarray(lo).copy()
        for c, a, bnd in self.vote_buf.pop(epoch, {}).values():
            commit_g &= c
            abort_g |= a
            if bnd is not None:
                # range intersection (maat.cpp:176-190): the least
                # position satisfying every owner's local constraints
                glo = np.maximum(glo, bnd)
        order_j = jnp.zeros(len(vc), jnp.int32)
        if self.maat_vote:
            # verify round: every owner re-checks its local edges
            # against the intersected positions — a violation is a
            # cross-node cycle (e.g. distributed write skew); its
            # later-positioned endpoint's range closes -> abort
            b = len(vc)
            order_np = glo.astype(np.int64) * b + np.arange(b)
            order_j = jnp.asarray(order_np.astype(np.int32))
            cand_np = commit_g & active_np & ~abort_g
            ab2 = np.asarray(self.check_step(self.db, query,
                                             jnp.asarray(cand_np),
                                             ts_j, order_j))
            msg2 = wire.encode_vote(epoch, np.zeros_like(ab2), ab2)
            for p in range(self.n_srv):
                if p != self.me:
                    self.tp.send(p, "VOTE2", msg2)
            self.tp.flush()
            self._wait_votes(self.vote2_buf, epoch, "order checks")
            abort_g |= ab2
            for a2 in self.vote2_buf.pop(epoch, {}).values():
                abort_g |= a2
        commit_g &= active_np & ~abort_g      # any-abort wins
        abort_g &= active_np
        defer_g = active_np & ~commit_g & ~abort_g   # someone waits
        self.db, self.cc_state, self.dev_stats = self.apply_step(
            self.db, self.cc_state, self.dev_stats, query, active_j, ts_j,
            jnp.asarray(commit_g), jnp.asarray(abort_g),
            jnp.asarray(defer_g), order_j)
        return commit_g, abort_g, defer_g

    def _wait_votes(self, buf: dict, epoch: int, what: str) -> None:
        """Collect one message per peer server into ``buf[epoch]`` with
        dead-peer detection; the wait is carved out of process time."""
        t0 = time.monotonic()
        timeout = (self.cfg.fault_recovery_timeout_s if self._failover
                   else 60.0)
        while len(buf.get(epoch, {})) < self.n_srv - 1:
            self._drain(timeout_us=5_000)
            have = buf.get(epoch, {})
            if len(have) >= self.n_srv - 1:
                break
            dead = [p for p in range(self.n_srv)
                    if p != self.me and p not in have
                    and not self.tp.peer_alive(p)]
            if dead:
                self._drain(timeout_us=50_000)
                have = buf.get(epoch, {})
                dead = [p for p in dead if p not in have]
            if dead and len(have) < self.n_srv - 1 and not self._failover:
                raise RuntimeError(
                    f"server {self.me}: peer server(s) {dead} died "
                    f"waiting for epoch {epoch} {what}")
            if time.monotonic() - t0 > timeout:
                raise TimeoutError(
                    f"server {self.me}: epoch {epoch} {what} wait: have "
                    f"{sorted(have)}")
        # the caller's dispatch stage covers this whole round: charge the
        # network wait to the peer wait, so the stages partition wall time
        self.clk.shift("dispatch", "collect", time.monotonic() - t0)

    # -- blob barrier ----------------------------------------------------
    def _exp_peers(self, epoch: int) -> list[int]:
        """Peer servers expected to contribute to ``epoch``: everyone,
        minus peers whose contribution is retired from a reassignment
        cutover on (their merged-batch slice stays inactive)."""
        if not self._elastic:
            return [p for p in range(self.n_srv) if p != self.me]
        return [p for p in range(self.n_srv) if p != self.me
                and self._contrib_gone.get(p, 1 << 62) > epoch]

    def _wait_blobs(self, epoch: int) -> None:
        """Block until every expected peer's contribution for ``epoch``
        arrived (the RDONE analogue), with dead-peer detection (SURVEY
        §5.3: the reference has none — it would hang on its 1s recv
        timeouts).  In failover mode a dead peer is NOT fatal: the
        supervisor restarts it in recovery mode, it replays its log,
        rejoins the mesh and re-broadcasts — we keep waiting up to the
        recovery timeout.  In ELASTIC failover mode the dead peer is
        instead retired in place: every survivor deterministically
        reassigns its slots (plan_reassign) at this stalled boundary,
        rebuilds the acquired rows by replaying its own command log, and
        the barrier proceeds without it."""
        t0 = time.monotonic()
        timeout = (self.cfg.fault_recovery_timeout_s if self._failover
                   else 60.0)
        while True:
            if self._partitions is not None or self._stall is not None:
                # a symmetric partition stalls BOTH sides right here, so
                # wall-clock fault changes (flap lift/re-apply) must
                # tick inside the wait, not only at loop tops
                self._fault_net_tick()
            if self._fencing:
                self._maybe_heartbeat(time.monotonic())
            have = self.blob_buf.get(epoch, {})
            missing = [p for p in self._exp_peers(epoch) if p not in have]
            if not missing:
                return
            self._drain(timeout_us=5_000)
            have = self.blob_buf.get(epoch, {})
            missing = [p for p in self._exp_peers(epoch) if p not in have]
            if not missing:
                return
            # check liveness only AFTER draining: a peer may have
            # flushed this epoch's blob (now in our recv queue) and
            # then exited — that epoch is completable, not failed
            dead = [p for p in missing if not self.tp.peer_alive(p)]
            if dead:
                # the dead flag is set by the receiver thread, which
                # may have delivered the final blob between our drain
                # and this check — drain once more and re-verify
                # before declaring failure
                self._drain(timeout_us=50_000)
                have = self.blob_buf.get(epoch, {})
                dead = [p for p in dead if p not in have]
            if self._fencing and self._failover:
                # partition & gray-failure handling: socket death stays
                # the fast path, suspicion (phi threshold + wall-clock
                # silence floor) catches peers whose sockets never
                # closed.  Only the side holding a MAJORITY of the live
                # set may retire peers (ties resolve to the side with
                # the lowest live id); the minority self-fences instead
                # of installing a second map — split-brain-free by
                # construction.
                now = time.monotonic()
                susp = sorted(set(dead)
                              | {p for p in missing
                                 if self._fd.fence_ready(p, now)})
                # cohort settling: suspicions mature one peer at a time
                # (per-peer last-frame clocks skew by up to a heartbeat
                # interval), and acting on the first while a second is
                # mid-window would mis-read a 1-vs-2 partition as 2-vs-1
                # — a minority node would reassign a majority peer
                # before discovering it is the minority.  Hold until
                # every missing peer is either demonstrably fresh
                # (below the half-threshold warning) or fence-ready;
                # silence only ever promotes, so the hold is bounded by
                # the suspect floor.
                pending = [p for p in missing if p not in susp
                           and self._fd.warming(p, now)]
                if susp and not pending:
                    alive = [p for p in range(self.n_srv)
                             if p not in self._reassigned]
                    mine = [p for p in alive if p not in susp]
                    if not self._FD.majority_side(mine, susp):
                        self._self_fence("minority", epoch)
                    if self._fence_reassign_epoch < 0:
                        self._fence_reassign_epoch = epoch
                    for p in susp:
                        self._fence_spans["suspect"] += \
                            self._fd.elapsed(p, now) * 1e3
                        # targeted fence: reachable-but-partitioned
                        # peers (one-way links, gray-slow) halt on this
                        # instead of waiting to observe the new map
                        self._fence_nacks += 1
                        self.tp.send(p, "FENCE_NACK",
                                     self._FD.encode_fence_nack(
                                         self.smap.version + 1,
                                         self.smap.version, epoch))
                        self._elastic_reassign(p, epoch)
                    self.tp.flush()
                    continue
            elif dead and self._elastic and self._failover:
                # failover-with-reassignment: the kill path flushes its
                # transport at the boundary, so every survivor stalls at
                # the SAME first-missing epoch and derives the same new
                # map — no negotiation round needed
                for p in dead:
                    self._elastic_reassign(p, epoch)
                continue
            if dead and not self._failover:
                raise RuntimeError(
                    f"server {self.me}: peer server(s) {dead} died "
                    f"waiting for epoch {epoch} blobs")
            if time.monotonic() - t0 > timeout:
                raise TimeoutError(
                    f"server {self.me}: epoch {epoch} blob wait: have "
                    f"{sorted(have)}")

    # -- elastic membership: live rebalance protocol ---------------------
    # All of it runs at GROUP BOUNDARIES only (the durability +
    # determinism cutpoint the ack gating and the overlap pipeline
    # already quantize on): a cutover is one atomic map-version bump,
    # identical on every node at the identical epoch, so the merged
    # verdict stream never observes a half-installed map.
    def _elastic_tick(self, epoch0: int) -> bool:
        """Top-of-loop membership work: (controller) announce a planned
        rebalance; (everyone) apply a pending cutover when its boundary
        arrives.  Returns True when a cutover was applied this tick (the
        caller carves a ``membership`` span out of the timeline)."""
        cfg = self.cfg
        plan = cfg.elastic_plan_spec()
        if (self.me == 0 and plan is not None and not self._plan_sent
                and epoch0 >= plan[2]):
            kind, node, _ = plan
            M = self._M
            new_map = (M.plan_grow if kind == "grow"
                       else M.plan_drain)(self.smap, node)
            # cutover 3 groups out — the measure-epoch margin: peers
            # dispatch at most ~1 group ahead (their group g needs our
            # g blobs) and per-link FIFO lands this announcement before
            # the boundary group's blobs
            cutover = (epoch0 // self.C + 3) * self.C
            reason = M.REASON_GROW if kind == "grow" else M.REASON_DRAIN
            msg = M.encode_map_msg(new_map, cutover, reason, node)
            for p in range(self.n_srv):
                if p != self.me:
                    self.tp.send(p, "MIGRATE_BEGIN", msg)
            self.tp.flush()
            self._plan_sent = True
            self._mig_pending = dict(map=new_map, cutover=cutover,
                                     reason=reason, subject=node)
        mp = self._mig_pending
        if mp is not None and epoch0 >= mp["cutover"]:
            if epoch0 > mp["cutover"]:
                raise RuntimeError(
                    f"server {self.me}: missed rebalance cutover "
                    f"{mp['cutover']} (at epoch {epoch0}): announcement "
                    "margin violated")
            self._apply_cutover(mp)
            self._mig_pending = None
            return True
        return False

    def _apply_cutover(self, mp: dict) -> None:
        """Planned grow/drain cutover at its group boundary: donors
        snapshot + stream the moving slots' rows, recipients install
        them, and everyone bumps the map version — the committed state
        through ``cutover - 1`` is exactly what the pipelined loop has
        already dispatched, so the snapshot is the handoff point."""
        t0 = time.monotonic()
        M = self._M
        new_map = mp["map"]
        mv = M.moves(self.smap, new_map)
        rows_out = rows_in = 0
        for (d, r), slots in mv.items():
            if d == self.me:
                rows_out += self._send_rows(r, new_map.version, slots)
        if rows_out:
            self.tp.flush()
        donors = sorted({d for (d, r) in mv if r == self.me})
        for d in donors:
            rows_in += self._install_rows(
                self._wait_rows(new_map.version, d))
        self._install_map(new_map, mp["cutover"], mp["reason"],
                          mp["subject"], rows_in, rows_out,
                          (time.monotonic() - t0) * 1e3)

    def _send_rows(self, recipient: int, version: int,
                   slots: np.ndarray) -> int:
        """Donor half: gather the moving slots' rows from the device
        tables and stream them to the recipient."""
        import jax
        import jax.numpy as jnp

        M = self._M
        keys = M.keys_of_slots(slots, self.wl.n_rows, self.smap.n_slots)
        kj = jnp.asarray(keys)
        # sorted: the MIGRATE_ROWS byte stream must not depend on the
        # db/columns dict INSERTION history (a rebuilt-by-replay node's
        # tables must snapshot byte-identically to a boot-built one's)
        gathered = {f"{name}/{cn}": jnp.take(v, kj, axis=0)
                    for name, tab in sorted(self.db.items())
                    if not name.startswith("__")
                    for cn, v in sorted(tab.columns.items())}
        # ONE batched d2h fetch: per-column device_get would serialize a
        # host<->device round trip per column straight into the cutover
        # stall every node pays
        cols = {k: np.asarray(v)
                for k, v in zip(gathered, jax.device_get(
                    list(gathered.values())))}
        self.tp.send(recipient, "MIGRATE_ROWS",
                     M.encode_migrate_rows(version, keys, cols))
        return len(keys)

    def _wait_rows(self, version: int, donor: int) -> bytes:
        """Recipient half: block (bounded) for one donor's row stream."""
        t0 = time.monotonic()
        while True:
            buf = self._mig_rows.get(version, {})
            if donor in buf:
                return buf.pop(donor)
            self._drain(timeout_us=10_000)
            if time.monotonic() - t0 > self.cfg.failover_timeout_s:
                raise TimeoutError(
                    f"server {self.me}: MIGRATE_ROWS v{version} from "
                    f"donor {donor} never arrived within "
                    f"failover_timeout_s={self.cfg.failover_timeout_s:g}")

    def _scatter_rows(self, kj, get_col) -> None:
        """Scatter per-column values into the local full-residency
        tables at row indices ``kj`` (``get_col(name, cn, col)`` supplies
        the replacement rows; ``__``-prefixed control-plane leaves are
        skipped)."""
        newdb = dict(self.db)
        for name, tab in self.db.items():
            if name.startswith("__"):
                continue
            tc = dict(tab.columns)
            for cn in tc:
                tc[cn] = tc[cn].at[kj].set(get_col(name, cn, tc[cn]))
            newdb[name] = tab._replace(columns=tc)
        self.db = newdb

    def _install_rows(self, payload: bytes) -> int:
        """Scatter a donor's row stream into the local tables (elastic
        tables are full-residency, so local slot == key)."""
        import jax.numpy as jnp

        _v, keys, cols = self._M.decode_migrate_rows(payload)
        self._scatter_rows(
            jnp.asarray(keys),
            lambda name, cn, col: jnp.asarray(cols[f"{name}/{cn}"],
                                              col.dtype))
        return len(keys)

    def _elastic_reassign(self, dead: int, epoch: int) -> None:
        """Failover-with-reassignment: retire a dead peer in place.  The
        plan is a deterministic pure function of (map, dead) and every
        survivor stalls at the same first-missing epoch, so all
        survivors install the identical new map at the identical
        boundary with no negotiation.  Acquired rows are rebuilt by
        deterministic replay of THIS node's own command log — the
        merged command stream is identical on every node, so replaying
        it under the acquired-slot ownership mask reproduces the dead
        node's rows bit for bit."""
        if dead in self._reassigned:
            return
        t0 = time.monotonic()
        M = self._M
        self._reassigned.add(dead)
        new_map = M.plan_reassign(self.smap, dead)
        acquired = np.concatenate(
            [s for (d, r), s in M.moves(self.smap, new_map).items()
             if r == self.me] or [np.zeros(0, np.int32)])
        rows_in = 0
        if len(acquired) and epoch > 0:
            rows_in = self._adopt_by_replay(acquired, epoch)
        self._contrib_gone[dead] = epoch
        # drop any buffered blobs of the dead incarnation at/past the
        # boundary (there should be none — it died at its boundary)
        for ep, blobs in self.blob_buf.items():
            if ep >= epoch:
                blobs.pop(dead, None)
        stall_ms = (time.monotonic() - t0) * 1e3
        if self._geo:
            # geo failover: this takeover IS the promotion — a surviving
            # replica-holder of the lost region's slots replayed itself
            # up to the quorum-durable boundary and now answers for them
            self._promote_cnt += 1
            self._geo_spans["promote"] += stall_ms
        self._install_map(new_map, epoch, M.REASON_REASSIGN, dead,
                          rows_in, 0, stall_ms)

    def _adopt_by_replay(self, acquired: np.ndarray, stop_epoch: int
                         ) -> int:
        """Rebuild the acquired slots' rows by replaying the local
        command log through ``stop_epoch`` with ownership restricted to
        exactly those slots, then merge the rows into the live tables.
        This is PR 1's recovery replay pointed at a different owner
        mask — catch-up without the dead process."""
        import jax.numpy as jnp

        from deneva_tpu.engine.step import init_device_stats
        from deneva_tpu.runtime.logger import replay_into

        M = self._M
        if self.logger is None:
            raise RuntimeError(
                f"server {self.me}: slot reassignment needs --logging "
                "(acquired rows are rebuilt by log replay)")
        # records for every epoch < stop_epoch were appended at their
        # group's dispatch; drain in-flight wire submissions (they may
        # ride the wire worker) before waiting out the flush
        for g in getattr(self, "_inflight", ()):
            for f in g.get("wire_futs", ()):
                f.result()
        self.logger.wait_flushed(stop_epoch - 1,
                                 timeout=self.cfg.failover_timeout_s)
        step = self._mesh_wrap(make_dist_step(self.cfg, self.wl,
                                              self.be))
        db0 = self.wl.load()
        owners = np.full(self.smap.n_slots, -1, np.int32)
        owners[acquired] = self.me
        db0[M.MEMBER_KEY] = jnp.asarray(owners)
        stats0 = init_device_stats(
            len(getattr(self.wl, "txn_type_names", ("txn",))))
        db0, _, _, last = replay_into(
            self.log_path, self.cfg, self.wl, step, db0,
            self.be.init_state(self.cfg), stats0, stop_epoch=stop_epoch)
        if last != stop_epoch - 1:
            raise RuntimeError(
                f"server {self.me}: reassignment replay ended at epoch "
                f"{last}, needed {stop_epoch - 1}")
        keys = M.keys_of_slots(acquired, self.wl.n_rows,
                               self.smap.n_slots)
        kj = jnp.asarray(keys)
        self._scatter_rows(
            kj, lambda name, cn, col: jnp.take(db0[name].columns[cn],
                                               kj, axis=0))
        return len(keys)

    def _install_map(self, new_map, epoch: int, reason: int, subject: int,
                     rows_in: int, rows_out: int, stall_ms: float) -> None:
        """The atomic cutover: swap the host map AND the device-resident
        owner array (a data update between group dispatches — no
        re-jit), bump the counters, emit the [membership] line, and (the
        lowest live server) announce the map to every client."""
        import jax.numpy as jnp

        M = self._M
        mv_total = int((self.smap.owners != new_map.owners).sum())
        self.smap = new_map
        db = dict(self.db)
        db[M.MEMBER_KEY] = jnp.asarray(new_map.owners)
        self.db = db
        self._rebalance_cnt += 1
        self._rows_in += rows_in
        self._rows_out += rows_out
        self._cutover_stall_ms += stall_ms
        print(M.membership_line(self.me, new_map, epoch, reason, subject,
                                mv_total, rows_in, rows_out, stall_ms),
              flush=True)
        alive = [p for p in range(self.n_srv) if p not in self._reassigned]
        if self.me == min(alive):
            msg = M.encode_map_msg(new_map, epoch, reason, subject)
            for c in range(self.n_cl):
                self.tp.send(self.n_srv + c, "MAP_UPDATE", msg)
            self.tp.flush()

    # -- flight recorder: verdict-plane hop ------------------------------
    def _tel_verdicts(self, epoch: int, block: wire.QueryBlock,
                      commit: np.ndarray, ab: np.ndarray, df: np.ndarray,
                      rep_row: np.ndarray | None, abort_cnt: np.ndarray,
                      t_us: int) -> None:
        """One ST_VERDICT event per sampled txn that got a verdict this
        epoch — verdict code says which plane (commit / salvage / abort
        / defer; aux carries the txn's restart count so the waterfall
        can split first-try from retried commits) — plus the ST_HOLD
        quorum-gate event for committed tags whose CL_RSP is held for
        group-commit durability (released in ``_flush_held_rsp``)."""
        tags = block.tags
        sampled = self.tel.mask(tags)
        m = sampled & (commit | ab | df)
        if m.any():
            v = np.zeros(len(tags), np.uint8)
            v[commit] = V_COMMIT
            if rep_row is not None:
                v[commit & rep_row] = V_SALVAGE
            v[ab] = V_ABORT
            v[df] = V_DEFER
            self.tel.record(tags[m], ST_VERDICT, epoch=epoch,
                            verdict=v[m],
                            aux=abort_cnt[m].astype(np.int32),
                            t_us=t_us)
        if self.logger is not None:
            held = sampled & commit
            if held.any():
                self.tel.record(tags[held], ST_HOLD, epoch=epoch,
                                t_us=t_us)

    # -- metrics bus: frame emission + aggregator targeting --------------
    def _mb_agg(self) -> int:
        """The aggregator's node id: the lowest-id LIVE server (elastic
        retirement hands the role down; a killed-and-recovering
        aggregator keeps it — frames sent into its death window are
        lost, which the bus's lossy-telemetry contract permits)."""
        if self._elastic and self._reassigned:
            return min(p for p in range(self.n_srv)
                       if p not in self._reassigned)
        return 0

    def _mb_emit(self, epoch: int, dens_row, commit: int, ab: int,
                 df: int, salv: int) -> None:
        """Ship one per-epoch frame (or feed it straight into the local
        aggregator when this node holds the role)."""
        counters = dict(
            commit=commit, abort=ab, defer=df, salvage=salv,
            pending=len(self.pending), retry_depth=len(self.retry.items),
            held_rsp=len(self._held_rsp),
            adm_depth=self.adm.depth if self.adm is not None else 0)
        if self.ctl is not None:
            # controller state rides the frame (the monitor panel's
            # input).  gov encodes 0=off / 1=static / 2=armed: the
            # schema zero-fills unset fields, so a ctrl-off frame reads
            # gov=0 and the monitor panel stays hidden
            counters["ctrl_gov"] = 2 if self.ctl.gov == "armed" else 1
            counters["ctrl_qidx"] = self.ctl.quota_idx
            counters["ctrl_trips"] = self.ctl.stale_trips
        parts, rec = self.mbus.frame(epoch, counters, dens_row)
        agg = self._mb_agg()
        if agg == self.me:
            if self.magg is None:
                self.magg = self._MB.Aggregator(self.cfg, self.me,
                                                append=self.cfg.recover)
            self.magg.feed(rec)
        else:
            self.tp.sendv(agg, "METRICS", parts)

    # -- control plane: boundary tick -------------------------------------
    def _wit_counter(self) -> int:
        """Cumulative witness density off the device (audit_wit_cnt —
        claim-violating edges only; one scalar fetch per boundary tick,
        riding the same cadence as the breach/salvage folds)."""
        if not self.cfg.audit:
            return 0
        import jax
        return int(jax.device_get(self.dev_stats["audit_wit_cnt"]))

    def _ctrl_tick(self, group_end: int, tl) -> None:
        """One controller decision per group boundary: fold the retire
        loop's accumulated signals into a `CtrlSignals`, decide, actuate
        the admission quota scale, and emit the ``[ctrl]`` record (the
        replay contract's whole input).  A stalled pipeline (dead
        aggregator node, partition, fenced peer — nothing retired, or
        the boundary gap blew past ``ctrl_stale_s``) reads as unhealthy
        and the governor reverts to the static config until the heal
        streak clears."""
        from deneva_tpu.runtime.controller import (CtrlSignals, ctrl_line,
                                                   quota_scale)
        t0 = time.monotonic()
        if not self._ctrl_primed:
            # baseline tick: the first group boundary lands right after
            # jit compile — a multi-second gap that says nothing about
            # signal health.  Stamp the clock/accumulator baseline and
            # decide nothing (the driver's _ctrl_tick does the same).
            self._ctrl_primed = True
            self._ctrl_t = t0
            self._ctrl_ep = 0
            self._ctrl_dens[:] = 0
            self._ctrl_sv = 0
            self._ctrl_wit0 = self._wit_counter()
            if self.adm is not None:
                self._ctrl_breach0 = self.adm.breach_groups
            return
        gap_us = int((t0 - self._ctrl_t) * 1e6)
        self._ctrl_t = t0
        breaches = 0
        if self.adm is not None:
            b = self.adm.breach_groups
            breaches = b - self._ctrl_breach0
            self._ctrl_breach0 = b
        wit_now = self._wit_counter()
        sig = CtrlSignals(
            epoch=int(group_end), epochs=self._ctrl_ep,
            dens=[int(x) for x in self._ctrl_dens],
            fallback=0, salvaged=self._ctrl_sv,
            witnesses=wit_now - self._ctrl_wit0, breaches=breaches,
            gap_us=gap_us)
        self._ctrl_ep = 0
        self._ctrl_dens[:] = 0
        self._ctrl_sv = 0
        self._ctrl_wit0 = wit_now
        dec = self.ctl.decide(sig)
        if self.adm is not None:
            self.adm.set_scale(quota_scale(dec.quota_idx))
        line = ctrl_line(self.me, sig, dec)
        print(line, flush=True)
        self._ctrl_log.write(line + "\n")
        self._ctrl_log.flush()
        if tl:
            # decision-tick latency ledger on the declared "ctrl" track
            tl.spans.append(("ctrl", time.monotonic() - t0))

    # -- verdict retirement (the back half of an epoch) ------------------
    def _retire(self, group: dict) -> None:
        """Fetch a dispatched group's commit masks (ONE host<->device
        transfer for all its epochs) and finish its host-side epoch work:
        CL_RSP acks, retry/backoff routing, exact unique-abort counts."""
        import jax

        # blocked on the device's verdicts (the retire worker's future,
        # or the same body called here): the host waiting for the device
        epoch0 = group["eps"][0][0]
        t_wait = self.clk.enter("retire_wait", epoch0)
        fut = group["prefetch"]
        if fut is None:
            done, abort, defer, rep, acks, split_s = \
                self._prefetch_retire(group)
        else:
            # the retire worker waited the d2h, unpacked the planes and
            # split the ack payloads while later groups were dispatching.
            # A future that is done BEFORE we ask proves the d2h + unpack
            # genuinely overlapped device execution of the later groups
            # (the [mesh] line's prefetch_overlap ratio); one that is not
            # makes this .result() the wait the prefetch was to hide.
            self._prefetch_polls += 1
            if fut.done():
                self._prefetch_hits += 1
            done, abort, defer, rep, acks, _ = fut.result()
        # the work after the wait: CL_RSP sends, retry routing
        t_retire = self.clk.enter("retire", epoch0)
        if fut is None:
            self.clk.shift("retire_wait", "retire", split_s)
        else:
            # the mesh track's wait ledger: this group's `retire_wait`
            self._prefetch_wait_s += t_retire - t_wait
        dens = None
        if self.mbus is not None and group.get("dens_dev") is not None:
            # per-epoch density plane [C, P]: same d2h cadence as the
            # verdict planes (the async copy started at dispatch)
            dens = np.asarray(jax.device_get(group["dens_dev"]))
        auda = None
        if self.aud is not None and group.get("aud_dev") is not None:
            # audit observation stack: same d2h cadence as the planes
            auda = [np.asarray(jax.device_get(a))
                    for a in group["aud_dev"]]
        lo = self._plane_lo if group["packed"] else 0
        for i, (epoch, block, abort_cnt, birth_ts, dfc) in enumerate(
                group["eps"]):
            n = len(block)
            my_commit = done[i, lo:lo + n]
            # flight recorder: stamp the verdict time BEFORE any of this
            # epoch's CL_RSPs leave — on a same-box mesh the client's
            # first-ack record would otherwise beat a post-send verdict
            # record by microseconds and read as an ordering inversion
            tel_t = time.monotonic_ns() // 1000 \
                if self.tel is not None else 0
            if rep is not None:
                # repaired-plane accounting (host cross-check of the
                # device rep_salvaged_cnt; surfaces as the [repair]
                # line's plane_cnt and the "repair" timeline span)
                t_r = time.monotonic()
                self._rep_salvaged += int(rep[i, lo:lo + n].sum())
                self._rep_span += time.monotonic() - t_r
            if self._full_planes and group["packed"]:
                # re-ack takeover authority: every PEER slice's committed
                # packed ids survive their admitting server (held to the
                # same durability gate as the CL_RSPs they answer).  The
                # own slice is excluded — the normal retire/held-rsp path
                # already moves those ids, and doubling them would run a
                # redundant O(b_loc) dedup pass per epoch
                at = group["all_tags"][i]
                full = done[i, :self.b_merged] & (at != 0)
                full[self._plane_lo:self._plane_lo + self.b_loc] = False
                ids = at[full]
                if len(ids):
                    if self.logger is None:
                        self._retire_dedup(ids)
                    else:
                        if self._geo:
                            self._quorum_hold_t.setdefault(
                                epoch, time.monotonic())
                        self._held_commit.append((epoch, ids))
            if acks[i] is not None:
                tags, rsp_split, retry_inc, wait_inc = acks[i]
                self._retry_hist += retry_inc
                self._wait_hist += wait_inc
                if self._dedup_on and self.logger is None:
                    # without logging the ack goes out right below; with
                    # logging the committed-set entry (and its re-ack
                    # authority) must wait for the SAME durability gate
                    # the held ack waits for — _flush_held_rsp moves the
                    # ids at release time, or a resend could extract an
                    # early re-ack for a txn a crash then truncates away
                    self._retire_dedup(tags)
                for c, masked in rsp_split:
                    if self.logger is None:
                        self.tp.sendv(c, "CL_RSP",
                                      wire.cl_rsp_parts(masked))
                    else:
                        # group commit: hold until epoch is durable
                        if self._geo:
                            self._quorum_hold_t.setdefault(
                                epoch, time.monotonic())
                        self._held_rsp.append((c, epoch, masked))
            ab = abort[i, lo:lo + n]
            df = defer[i, lo:lo + n]
            if self.defer_budget:
                # defer budget (engine/step.py analogue): past the
                # budget a wait force-restarts as an abort.  Host-side
                # conversion, so the DEVICE abort counter does not see
                # these — [summary] totals can differ from an in-process
                # run by the (rare) conversion count.
                stuck = df & (dfc[:n] >= self.defer_budget)
                ab = ab | stuck
                df = df & ~stuck
                if self._counts_locks:
                    # a waiter past its budget restarts as an abort (with
                    # its timestamp): no death of the rule, so it is
                    # counted apart from the device's `lock_die`
                    self._lock_forced += int(stuck.sum())
            # exact unique-txn aborts (stats.h:60-61): first abort of a
            # txn is the one whose retry counter is still zero
            self._uniq_aborts += int((ab & (abort_cnt == 0)).sum())
            if self.tel is not None:
                self._tel_verdicts(epoch, block, my_commit, ab, df,
                                   rep[i, lo:lo + n]
                                   if rep is not None else None,
                                   abort_cnt, tel_t)
            if self._metrics is not None:
                # per-epoch structured counter stream — the [summary]
                # aggregates as a time series, host-side numbers only
                self._metrics.emit(
                    epoch, commit=int(my_commit.sum()),
                    abort=int(ab.sum()), defer=int(df.sum()),
                    salvaged=int((rep[i, lo:lo + n] & my_commit).sum())
                    if rep is not None else 0,
                    retry_depth=len(self.retry.items),
                    pending=len(self.pending),
                    held_rsp=len(self._held_rsp),
                    adm_depth=self.adm.depth
                    if self.adm is not None else 0)
            if self.mbus is not None:
                # metrics bus: quorum-hold ledger + the per-epoch frame
                # (the aggregator's cluster view; density row from the
                # group's device plane when the merged path produced one)
                if self.logger is not None and my_commit.any():
                    self.mbus.hold(epoch, time.monotonic())
                if self.mbus.due(epoch):
                    self._mb_emit(
                        epoch, dens[i] if dens is not None else None,
                        int(my_commit.sum()), int(ab.sum()),
                        int(df.sum()),
                        int((rep[i, lo:lo + n] & my_commit).sum())
                        if rep is not None else 0)
            if self.aud is not None and auda is not None \
                    and self.aud.due(epoch):
                # isolation audit sidecar: this epoch's dependency
                # observations + digests, tags joined for the edge
                # endpoints this node admitted
                self.aud.export(
                    epoch, auda[0][i], auda[1][i], int(auda[2][i]),
                    int(auda[3][i]), int(auda[4][i]), int(auda[5][i]),
                    commit=int(my_commit.sum()), tags=block.tags)
            if self.ctl is not None:
                # control-plane signal accumulation (consumed at the
                # group-boundary tick, _ctrl_tick): per-epoch density
                # row, salvage plane, audit witness count
                self._ctrl_ep += 1
                if dens is not None:
                    self._ctrl_dens += dens[i].astype(np.int64)
                if rep is not None:
                    self._ctrl_sv += int((rep[i, lo:lo + n]
                                          & my_commit).sum())
            restart = ab | df
            if restart.any():
                idx = np.where(restart)[0]
                self._queue_txns += len(idx)
                # aborts bump the backoff counter; defers restart free
                # (with their wait budget spent recorded)
                self.retry.push(block.take(idx), abort_cnt[idx] + ab[idx],
                                birth_ts[idx], epoch, aborted=ab[idx],
                                defer_cnt=np.where(
                                    ab, 0, dfc[:n] + df)[idx])
        self._flush_held_rsp()
        # surface wire-worker errors and recycle the feed buffer set —
        # the mask fetch above proved the device consumed its inputs, and
        # the drained wire futures prove the blob/log sends no longer
        # reference the rows
        for f in group["wire_futs"]:
            f.result()
        if group["feed"] is not None:
            self._feed_free.append(group["feed"])
        self.clk.enter("other")
        self.clk.retired(group["t_dispatch"])

    # -- the pipelined epoch-group loop ----------------------------------
    def _warm(self) -> None:
        """Before the loop: compile every shape it will use, then meet
        the peers (the INIT_DONE barrier, or a recovered node's rejoin)."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        if cfg.owner_check:
            # debug mode: stamp this (dispatch) thread as owner of the
            # mutable host collections and assert every mutation comes
            # from it (runtime/ownercheck.py; the static half is
            # tools/graftlint's ownership checker)
            from deneva_tpu.runtime import ownercheck
            ownercheck.install(self)
        b, C = self.b_merged, self.C
        W, S = self._width, self._n_scalars
        # compile before the barrier so no node's first epoch stalls the
        # lockstep (reference: setup/warmup barriers, system/thread.cpp:62-84).
        # Peers sit in their INIT_DONE wait meanwhile: that wait lasts
        # setup_wait_s, which under the launcher is the launcher's own
        # limit, so a cold compile of any length cannot expire it.
        t_warm = time.monotonic()
        if self.vote_mode:
            warm_q = self.wl.from_wire(
                np.zeros((b, W), np.int32), np.zeros((b, W), np.int8),
                np.zeros((b, S), np.int32))
            wa, wt = jnp.zeros(b, bool), jnp.zeros(b, jnp.int32)
            vc, va, vd, _lo = self.vote_step(self.db, self.cc_state,
                                             warm_q, wa, wt)
            if self.maat_vote:
                self.check_step(self.db, warm_q, wa, wt,
                                jnp.zeros(b, jnp.int32))
            out = self.apply_step(self.db, self.cc_state, self.dev_stats,
                                  warm_q, wa, wt, vc & False, va & False,
                                  vd & False, jnp.zeros(b, jnp.int32))
            jax.block_until_ready(out[2]["total_txn_commit_cnt"])
        else:
            # mesh runs place the (replicated) feed explicitly so it
            # shares a device set with the sharded state
            fsh = self._feed_sharding
            warm = jax.device_put((
                np.zeros(C * b, bool), np.zeros(C * b, np.int32),
                np.zeros(C * b * W, np.int32), np.zeros(C * b * W, np.int8),
                np.zeros(C * b * S, np.int32)), fsh)
            if self.aud is not None:
                # audit epoch labels: -1 on the warm call (no epoch;
                # nothing commits, so no stamp ever records it)
                warm = warm + (jax.device_put(np.full(C, -1, np.int32),
                                              fsh),)
            out = self.group_step(self.db, self.cc_state, self.dev_stats,
                                  *warm)
            # group_step donates its state args: adopt the outputs
            self.db, self.cc_state, self.dev_stats = out[:3]
            jax.block_until_ready(out[3])
        self.info["warm_s"] = round(time.monotonic() - t_warm, 3)
        if cfg.recover:
            # the mesh is mid-run: no INIT_DONE barrier — announce the
            # rejoin instead (peers resend the blobs we missed, replicas
            # resync their log tail)
            self._announce_rejoin()
        else:
            self.barrier()
        if self._fencing:
            # the detector baselines NOW, not at __init__: jit compile
            # + barrier time must not read as peer silence
            self._fd = self._FD.FailureDetector(
                cfg, [p for p in range(self.n_srv) if p != self.me],
                time.monotonic())
        self._t_run0 = time.monotonic()

    def run(self, progress=None) -> Stats:
        """The served loop: a pass per epoch GROUP through the stage
        clock's stages — drain, admit, collect, feed, dispatch, then
        retire_wait + retire of the group K back, other.

        C = ``pipeline_epochs`` merged epochs form ONE device dispatch
        (`make_dist_group`), K = ``pipeline_groups`` dispatches stay in
        flight, and a group's commit-mask fetch happens only after the
        NEXT group is dispatched — so admission, blob exchange and codec
        work for epochs e+C.. overlap the device execution of epochs
        e..e+C-1, where a synchronous loop pays 2-4 host<->device round
        trips an epoch, each far longer than the device step.  This is
        the reference's sequencer-thread vs worker-thread decoupling
        (`system/calvin_thread.cpp:102-170`) rebuilt on async dispatch.
        Retries re-enter up to K*C epochs later than synchronously —
        the same kind of delay the reference's abort queue imposes.
        """
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        C, K = self.C, self.K
        self._warm()
        if self.mbus is not None:
            # re-anchor the critical-path ledger NOW: jit compile +
            # barrier time is setup, not epoch wall
            self.mbus.crit.reset()
        t_start = time.monotonic()
        prog_next = t_start + cfg.prog_timer_secs
        warm_edge = t_start + cfg.warmup_secs
        measured = None     # counter snapshot at measure start
        epoch0 = self._resume_epoch   # 0, or the recovery group boundary
        tl = _Timeline() if cfg.debug_timeline else None
        # the ONE stage clock of this loop (runtime/stages.py): always-on
        # seconds per stage (the reference Stats_thd worker time
        # breakdowns, `statistics/stats.h:116` worker_idle_time etc., are
        # read from it), the `[timeline]` and `[crit]` ledgers where
        # armed, and `srv.<stage>` spans in a live profiler trace
        self.clk = clk = StageClock(
            tl, self.mbus.crit if self.mbus is not None else None)
        self._stage_meas = None     # its snapshot at measure start
        inflight: deque[dict] = deque()
        self._inflight = inflight   # reassignment replay drains wire futs
        while True:
            # ---- stage: drain (inbound decode) --------------------------
            clk.begin_pass(epoch0, C, self._queue_txns)
            if self._kill_at is not None and epoch0 >= self._kill_at:
                # injected crash (fault_kill "node:epoch"): die at this
                # group boundary with no teardown or farewell — but let
                # the async log writer drain first, so the crash model
                # is "process lost at an epoch boundary, log intact to
                # that boundary" (torn tails are exercised separately:
                # recovery truncates them, tests/test_chaos.py).
                if self.logger is not None and epoch0 > 0:
                    # the log records may ride the wire worker: drain
                    # the in-flight groups' submissions so the appends
                    # exist before waiting on the flush
                    for g in inflight:
                        for f in g.get("wire_futs", ()):
                            f.result()
                    self.logger.wait_flushed(epoch0 - 1, timeout=10.0)
                if self.tel is not None:
                    # crash-model parity with the command log: lifecycle
                    # events intact to the kill boundary survive in the
                    # sidecar (the restarted incarnation appends)
                    self.tel.flush()
                    self._metrics.close()
                if self.magg is not None:
                    # bus stream intact to the kill boundary; the
                    # recovered aggregator appends (its series resumes)
                    self.magg.close()
                if self.aud is not None:
                    # audit sidecar intact to the kill boundary, like
                    # the command log; the recovered incarnation appends
                    self.aud.close()
                if self._elastic:
                    # reassignment (instead of restart) needs every
                    # survivor to stall at the SAME first-missing epoch:
                    # drain the queued boundary sends so the departure
                    # is clean at this group boundary
                    self.tp.flush()
                os._exit(17)
            if self._partitions is not None or self._stall is not None:
                self._fault_net_tick()
            if self._fencing:
                self._epoch_cur = epoch0
                self._maybe_heartbeat(time.monotonic())
            self._drain()
            now = time.monotonic()
            # epoch-aligned measurement window: server 0 announces a
            # GROUP-BOUNDARY start epoch so every node snapshots the same
            # prefix of epochs.  Margin of 3 groups: peers dispatch at
            # most ~1 group ahead (their group g needs our g blobs), and
            # per-link FIFO delivers this announcement before the blobs
            # we send for the boundary group.
            if self.me == 0 and self.measure_epoch is None \
                    and now >= warm_edge:
                self.measure_epoch = (epoch0 // C + 3) * C
                ms = wire.encode_shutdown(self.measure_epoch)
                for p in range(self.n_srv):
                    if p != self.me:
                        self.tp.send(p, "MEASURE", ms)
            if self.me == 0 and self.stop_epoch is None \
                    and self.measure_epoch is not None \
                    and now >= warm_edge + cfg.done_secs:
                self.stop_epoch = (epoch0 // C + 3) * C
                sd = wire.encode_shutdown(self.stop_epoch)
                for p in range(self.n_srv):
                    if p != self.me:
                        self.tp.send(p, "SHUTDOWN", sd)
                self.tp.flush()
            # elastic membership: announce planned rebalances
            # (controller) and apply pending cutovers at their boundary
            if self._elastic and self._elastic_tick(epoch0) and tl:
                tl.mark("membership")
            # ---- stage: admit — assemble + broadcast contributions for
            # the group (its inner drains are part of it) ---------------
            clk.enter("admit")
            eps: list[tuple[int, wire.QueryBlock, np.ndarray, np.ndarray,
                            np.ndarray]] = []
            # admission writes straight into the reusable flat feed
            # buffers; each blob goes out (on the ordered wire worker,
            # where there is one) while the NEXT epoch's admission and,
            # below, the device group proceed
            fs = self._feed_acquire()
            wire_futs: list = []
            for i in range(C):
                e = epoch0 + i
                if i:
                    with stage_span("drain", epoch0):
                        self._drain()
                block, abort_cnt, birth_ts, dfc = \
                    self._contribution_into(e, fs, i)
                if self.tel is not None:
                    # epoch-batch assignment hop (retries re-record at
                    # their re-entry epoch — the span tree keeps the
                    # committing pass's batch)
                    self.tel.record(block.tags, ST_BATCH, epoch=e)
                if self.n_srv > 1:
                    wire_futs.append(self._wire(self._bcast_views, e, block,
                                                birth_ts))
                eps.append((e, block, abort_cnt, birth_ts, dfc))
            if self.n_srv > 1:
                # peers block on these blobs: push them onto the wire
                # behind the group's last bcast (FIFO worker)
                wire_futs.append(self._wire(self.tp.flush))
            # ---- stage: collect every peer's contributions (on the
            # `[crit]` ledger everything since the last pass closed —
            # inbound drain, heartbeats, contribution assembly, admission,
            # blob broadcast staging — is its admit stage, and the
            # blob-collect wait its wire stage: peer skew + network
            # transit show up exactly here) ------------------------------
            clk.enter("collect")
            decode_s = self._collect_into(eps, fs)
            # ---- stage: feed — the stacked device feed [C, b] is `fs`,
            # submit the log records ------------------------------------
            clk.enter("feed")
            # decode work is feed building, not network wait
            clk.shift("collect", "feed", decode_s)
            if self.logger is not None:
                # command log: the MERGED epoch block + active mask is
                # the log record — deterministic replay = re-execution
                # of the full command stream; ship the same record to
                # my replica (LOG_MSG, SURVEY §5.4).  Logged at
                # dispatch: verdicts are a pure function of the record.
                wire_futs.append(self._wire(self._log_group_views, fs, eps))
            # ---- dispatch (async for merged mode; the masks are fetched
            # at retirement, K groups later) ----------------------------
            # stage: dispatch (`device_put` + the group call + the d2h
            # starts; `[crit]` charges feed + dispatch to its device
            # stage: a recompile spike is the jit watchdog's signature)
            t_dispatch = clk.enter("dispatch")
            # the device's int32 timestamps, in the preallocated shadow
            np.copyto(fs["ts32"], fs["ts"], casting="unsafe")
            if self.vote_mode:
                # C == K == 1: the vote exchange is a host round trip
                # inside the epoch, so this path stays synchronous
                query = self.wl.from_wire(fs["keys"][0], fs["types"][0],
                                          fs["scal"][0])
                active_np = fs["active"][0]
                commit, abort, defer = self._vote_epoch(
                    eps[0][0], query, active_np, jnp.asarray(active_np),
                    jnp.asarray(fs["ts32"][0]), tl)
                lo = self.me * self.b_loc
                mine = slice(lo, lo + self.b_loc)
                masks = (commit[None, mine], abort[None, mine],
                         defer[None, mine])
                packed = False
                dens_dev = None     # vote mode: no merged density plane
                aud_dev = None      # ... and no audit plane (config
                #                     pins audit to merged/deterministic)
            else:
                # FLAT explicit async device_put: the raw wire columns
                # decode on device (wl.from_wire_dev inside the group
                # jit).  Shipping [C, b, W] leaves shaped pays the
                # 128-lane minor-dim layout padding on the way up
                # (~13x the bytes), and shipping numpy straight into the
                # jit call makes the transfer part of the dispatch
                # instead of an async copy that overlaps the feed build
                # of the next group.
                feed = jax.device_put(tuple(
                    fs[k].reshape(-1) for k in (
                        "active", "ts32", "keys", "types", "scal")),
                    self._feed_sharding)
                if self.aud is not None:
                    # audit epoch labels for this group's scan slices
                    feed = feed + (jax.device_put(np.arange(
                        epoch0, epoch0 + C, dtype=np.int32),
                        self._feed_sharding),)
                out = self.group_step(self.db, self.cc_state,
                                      self.dev_stats, *feed)
                self.db, self.cc_state, self.dev_stats = out[:3]
                masks = out[3]
                nxt_out = 4
                if self.mbus is not None:
                    # the bus-armed group jit returns the density plane
                    # beside the packed verdict planes
                    dens_dev = out[nxt_out]
                    nxt_out += 1
                    if hasattr(dens_dev, "copy_to_host_async"):
                        dens_dev.copy_to_host_async()
                else:
                    dens_dev = None
                aud_dev = None
                if self.aud is not None:
                    # audit observation stack (edges/buckets/counts/
                    # digests): start its d2h copies with the planes'
                    aud_dev = out[nxt_out]
                    for arr in aud_dev:
                        if hasattr(arr, "copy_to_host_async"):
                            arr.copy_to_host_async()
                packed = True
                # start the verdict d2h now; retirement K groups later
                # finds the copy already landed instead of paying the
                # d2h round trip synchronously
                if hasattr(masks, "copy_to_host_async"):
                    masks.copy_to_host_async()
            clk.enter("other")
            # the feed set recycles once a mask fetch has proved that the
            # device consumed it; a VOTE epoch's masks are the host's and
            # prove nothing, so its set is not reused
            group = {"eps": eps, "masks": masks, "packed": packed,
                     "feed": fs if packed else None,
                     "wire_futs": wire_futs, "prefetch": None,
                     "dens_dev": dens_dev, "aud_dev": aud_dev,
                     "t_dispatch": t_dispatch}
            if self._full_planes and packed:
                # full-plane retirement needs every slice's packed tags
                # (copied: the feed buffers recycle under the group)
                group["all_tags"] = fs["tags"].copy()
            if self.retire_pool is not None:
                # hand the verdict-plane fetch to the retire worker now:
                # by the time this group's turn to retire comes (K groups
                # later) the planes and ack splits are already unpacked
                group["prefetch"] = self.retire_pool.submit(
                    self._prefetch_retire, group)
            inflight.append(group)
            group_end = epoch0 + C
            # ---- measured-window snapshot at the announced boundary ----
            if measured is None and self.measure_epoch is not None \
                    and group_end >= self.measure_epoch:
                # drain the pipeline first so host-side counters (unique
                # aborts) cover exactly the same epoch prefix as the
                # device counters
                while inflight:
                    self._retire(inflight.popleft())
                measured = {k: np.asarray(v) for k, v in
                            jax.device_get(self.dev_stats).items()}
                self._stage_meas = clk.snapshot()
                self._t_meas = time.monotonic()
                self._compiles_meas = self._compiles.snapshot()[0]
                self._uniq_meas = self._uniq_aborts
                self._lock_forced_meas = self._lock_forced
                self._retry_meas = self._retry_hist.copy()
                self._wait_meas = self._wait_hist.copy()
                self._rep_meas = self._rep_salvaged
            # ---- retire the oldest group once K are in flight ----------
            while len(inflight) > K - 1:
                self._retire(inflight.popleft())
            now = time.monotonic()
            if progress and group_end % 50 < C:
                progress(self, group_end)
            if cfg.prog_timer_secs > 0 and now >= prog_next:
                # [prog] tick (reference PROG_TIMER, system/thread.cpp:86-105);
                # device_get only on the tick, never in the steady loop
                prog_next = now + cfg.prog_timer_secs
                from deneva_tpu.stats import make_prog_line
                c = {k: float(np.asarray(v))
                     for k, v in jax.device_get(self.dev_stats).items()
                     if k in ("total_txn_commit_cnt", "total_txn_abort_cnt")}
                print(f"node {self.me} " + make_prog_line(
                    now - t_start, c, {"epoch_cnt": float(group_end)}),
                    flush=True)
            if self.tel is not None and self.tel.should_flush:
                # half-full ring flush at the group boundary: drops only
                # ever count when a single group outruns half the ring
                self.tel.flush()
            if self.adm is not None:
                # per-group SLO tick: quantile the group's queue-delay
                # samples, re-arm/clear the shed-over-quota state, and
                # surface the max delay as an "admission"-track span
                adm_ms = self.adm.on_group()
                if tl and adm_ms > 0:
                    tl.spans.append(("adm_wait", adm_ms / 1e3))
            if self.ctl is not None:
                # control-plane boundary tick AFTER the SLO tick, so the
                # breach delta it consumes includes this very group
                self._ctrl_tick(group_end, tl)
            if tl:
                if self._repair and self._rep_span:
                    # retire-side salvage accounting (the repair compute
                    # itself is fused into the device step — the
                    # dispatch span carries it); lays out on the node's
                    # main track like adm_wait
                    tl.spans.append(("repair", self._rep_span))
                    self._rep_span = 0.0
                if self.mesh is not None and self._prefetch_wait_s:
                    # mesh prefetch-wait ledger: the serial remainder of
                    # the verdict-plane d2h the prefetch failed to hide
                    # behind device execution — lays out on the declared
                    # "mesh" track (harness/timeline.py tid 8); 0 on a
                    # fully overlapped run emits nothing
                    tl.spans.append(("mesh_prefetch",
                                     self._prefetch_wait_s))
                    self._prefetch_wait_s = 0.0
                if self.aud is not None and self.aud.span_s:
                    # audit export accounting (sidecar write + tag
                    # join): lays out on the declared "audit" track
                    # (harness/timeline.py tid 6) like the other
                    # latency ledgers
                    tl.spans.append(("audit", self.aud.span_s))
                    self.aud.span_s = 0.0
                if self._fencing:
                    # fencing spans (suspicion windows, heal gaps, fence
                    # rejections): latency ledgers like the geo spans —
                    # the chrome-trace export lays them on a separate
                    # per-node "fencing" track (harness/timeline.py)
                    for name in ("suspect", "heal", "fence"):
                        ms = self._fence_spans[name]
                        if ms:
                            self._fence_spans[name] = 0.0
                            tl.spans.append((name, ms / 1e3))
                if self._geo:
                    # replication spans (quorum wait, failover promote):
                    # latency ledgers, not thread-time slices — the
                    # chrome-trace export lays them on a separate
                    # per-node "replication" track (harness/timeline.py)
                    for name in ("quorum", "promote"):
                        ms = self._geo_spans[name]
                        if ms:
                            self._geo_spans[name] = 0.0
                            # _Timeline.spans holds SECONDS (emit scales
                            # by 1e3); the geo ledgers are ms
                            tl.spans.append((name, ms / 1e3))
                tl.emit(self.me, group_end)
            if self.mbus is not None:
                # close the critical-path pass; at the emit cadence the
                # ledger prints the [crit] attribution line and hands
                # back the gating stage for the critpath trace track
                gated = self.mbus.crit.end_pass(group_end)
                if gated is not None and tl:
                    tl.spans.append(("crit_" + gated[0], gated[1]))
                if self.magg is not None:
                    # aggregator heartbeat: the cluster-silence watchdog
                    # + a stream flush so the live TUI tails fresh lines
                    self.magg.tick(time.monotonic())
            if self.stop_epoch is not None and group_end >= self.stop_epoch:
                while inflight:
                    self._retire(inflight.popleft())
                break
            epoch0 += C
        clk.end()
        return self._summarise(measured, epoch0)

    def _summarise(self, measured: dict | None, epoch0: int) -> Stats:
        """After the loop (its last group began at ``epoch0``): release
        what is held, tell clients and replicas, and reduce the window's
        counters (from the ``measured`` snapshot on) to `[summary]`, the
        `[device]` record and each armed plane's tagged line."""
        import jax

        cfg, C, clk = self.cfg, self.C, self.clk
        # the stage clock's WINDOW values and the window's wall on
        # readings of its own (an empty window when the measurement
        # never began, like the counters below)
        stage_whole = clk.since(None)
        if self._stage_meas is None:
            self._stage_meas = clk.snapshot()
        stage_keys = clk.since(self._stage_meas)
        stage_keys["stage_wall_time"] = \
            time.monotonic() - self._t_meas if measured is not None else 0.0
        # every interval the clock closed, reduced once: the window's
        # pass walls and the run's longest intervals with their CPU, on
        # the `[device]` line (`tools/stage_record.py` prints it)
        self.info["stage_record"] = clk.record(self._stage_meas)
        epochs_run = epoch0 + C
        # final: release remaining group-committed acks, notify clients
        # and my replica, emit summary
        self._flush_held_rsp(wait_epoch=epochs_run - 1)
        for c in range(self.n_cl):
            self.tp.send(self.n_srv + c, "SHUTDOWN",
                         wire.encode_shutdown(epochs_run))
        for r in self.repl_ids:
            self.tp.send(r, "SHUTDOWN", wire.encode_shutdown(epochs_run))
        if self._elastic and self._reassigned:
            # takeover duty: a reassigned (dead, never-restarted) node
            # cannot release its own replicas — the lowest survivor does
            alive = [p for p in range(self.n_srv)
                     if p not in self._reassigned]
            if self.me == min(alive):
                for d in sorted(self._reassigned):
                    for k in range(self.cfg.replica_cnt):
                        rid = self.n_srv + self.n_cl + d + k * self.n_srv
                        self.tp.send(rid, "SHUTDOWN",
                                     wire.encode_shutdown(epochs_run))
        self.tp.flush()
        if self.logger is not None:
            self.stats.set("log_records", float(self.logger.records))
            self.stats.set("log_bytes", float(self.logger.bytes))
            self.logger.close()
        end = time.monotonic()
        final = {k: np.asarray(v) for k, v in
                 jax.device_get(self.dev_stats).items()}
        n_comp, comp_s, hits = self._compiles.snapshot()
        if measured is None:
            measured, self._t_meas = final, end
            self._compiles_meas = n_comp
        # closing record for the launcher's caller: compile requests of
        # the whole process (trace + lower + XLA seconds, cache hits
        # among them), compiles INSIDE the measured window (must be 0 —
        # every shape was warmed before the barrier), and the whole-run
        # commit count (client acks cover warm-up too, so acks <= this)
        self.info.update(
            compile_cnt=n_comp, compile_s=round(comp_s, 3),
            cache_hits=hits,
            window_compile_cnt=n_comp - self._compiles_meas,
            run_commit_cnt=int(final["total_txn_commit_cnt"]),
            run_abort_cnt=int(final["total_txn_abort_cnt"]))
        from deneva_tpu.runtime.logger import state_digest, state_digests
        if cfg.logging:
            # the table as the device holds it after the last logged
            # epoch: a replay of the command log on any backend must
            # hash to the same digest (runtime/logger.replay_log); the
            # leaves' own digests say WHICH table and column differs
            self.info["state_digest"], self.info["column_digests"] = \
                state_digests(self.db)
            # what the committed reads returned and how many lanes
            # waited, whole run: a reference that replays the log holds
            # both (a read-only transaction changes no digest)
            self.info["read_checksum"] = int(final["read_checksum"])
            self.info["run_defer_cnt"] = int(final["defer_cnt"])
            # ... and, of an MVCC server, how often the mechanism ran
            for k in MVCC_COUNTERS:
                if k in final:
                    self.info[f"run_{k[:-1]}_cnt"] = int(final[k])
            # ... and, of a 2PL server, its deaths, waits and leftovers
            # and the waiters the host's defer budget sent back
            if self._counts_locks:
                for k in LOCK_COUNTERS:
                    self.info[f"run_{k}_cnt"] = int(final[k])
                self.info["run_lock_forced_restart_cnt"] = self._lock_forced
        st = self.stats
        st.set("total_runtime", end - self._t_meas)
        st.set("epoch_cnt", float(epochs_run))
        for k in ("total_txn_commit_cnt", "total_txn_abort_cnt",
                  "defer_cnt", "write_cnt"):
            st.set(k, float(final[k] - measured[k]))
        # the executors' lane counters: `<x>_lanes` -> `<x>_lane_cnt`
        # (the append counters where this server's stats carry them)
        for dev in EXEC_COUNTERS + tuple(
                k for k in APPEND_COUNTERS if k in final):
            if dev.endswith("_lanes"):
                st.set(dev[:-1] + "_cnt", float(final[dev] - measured[dev]))
        for k in ("level_pass_cnt", "narrow_pass_cnt", "recon_defer_cnt",
                  "mc_defer_pass_cnt"):
            if k in final:      # (where this server's stats carry them)
                st.set(k, float(final[k] - measured[k]))
        if ROW_GROUP_COUNTER in final:      # `<x>s` -> `<x>_cnt`, likewise
            st.set(ROW_GROUP_COUNTER[:-1] + "_cnt", float(
                final[ROW_GROUP_COUNTER] - measured[ROW_GROUP_COUNTER]))
        for k in MVCC_COUNTERS:     # `<x>s` -> `<x>_cnt`, likewise
            if k in final:
                st.set(k[:-1] + "_cnt", float(final[k] - measured[k]))
        if self._counts_locks:      # a 2PL server's, `<x>` -> `<x>_cnt`
            for k in LOCK_COUNTERS:
                st.set(k + "_cnt", float(final[k] - measured[k]))
            st.set("lock_forced_restart_cnt", float(
                self._lock_forced - getattr(self, "_lock_forced_meas", 0)))
        by_type = final["commit_by_type"] - measured["commit_by_type"]
        for i, nm in enumerate(getattr(self.wl, "txn_type_names", ())):
            for fam in ("commit", "abort"):
                key = f"{fam}_by_type"
                st.set(f"{nm}_{fam}_cnt",
                       float(final[key][i] - measured[key][i]))
        # a workload's own sums over its types (PPS: look-ups, orders,
        # updates — what its roofline counts bytes for)
        for nm, types in getattr(self.wl, "commit_groups", {}).items():
            st.set(f"{nm}_commit_cnt", float(by_type[list(types)].sum()))
        # exact first-abort count, tracked host-side in the retry path
        st.set("unique_txn_abort_cnt",
               float(self._uniq_aborts - getattr(self, "_uniq_meas", 0)))
        commits = final["total_txn_commit_cnt"] - measured["total_txn_commit_cnt"]
        aborts = final["total_txn_abort_cnt"] - measured["total_txn_abort_cnt"]
        st.set("abort_rate",
               float(aborts) / max(float(commits + aborts), 1.0))
        for name, hist, base in (
                ("txn_retries", self._retry_hist,
                 getattr(self, "_retry_meas", np.zeros(8, np.int64))),
                ("txn_waits", self._wait_hist,
                 getattr(self, "_wait_meas", np.zeros(8, np.int64)))):
            d = (hist - base).astype(np.float64)
            if d.sum() > 0:
                st.arr(name).extend_weighted(np.arange(len(d)), d)
        # the reference's two worker times, whole run, off the stage
        # clock: idle = blocked on the peers' blobs and votes; process =
        # feed build, dispatch, and — as it always did — the blocked wait
        # for the device's verdicts (`stage_retire_wait_time` beside it
        # says how much of "process" is that wait)
        st.set("worker_idle_time", stage_whole["stage_collect_time"])
        st.set("worker_process_time", sum(
            stage_whole[f"stage_{s}_time"]
            for s in ("feed", "dispatch", "retire_wait")))
        for k, v in stage_keys.items():
            st.set(k, v)
        chaos = cfg.faults_enabled
        if chaos:
            st.set("dup_admit_cnt", float(self._dup_admits))
            st.set("reack_cnt", float(self._reacks))
            st.set("recovered", 1.0 if cfg.recover else 0.0)
        if self._geo:
            # geo-replication counters + the [replication] summary line
            # (parsed by harness.parse.parse_replication)
            acked = [self.repl_acked[r] for r in self.repl_ids]
            applied = [self.repl_applied[r] for r in self.repl_ids]
            stall_ms = (self._quorum_stall_s
                        / max(self._quorum_release_cnt, 1)) * 1e3
            st.set("quorum_stall_ms", stall_ms)
            st.set("promote_cnt", float(self._promote_cnt))
            st.set("geo_region", float(self._geo_region))
            st.set("quorum_acked_epoch",
                   float(georepl.quorum_ack(acked, cfg.geo_quorum)))
            print(georepl.replication_line(
                self.me, "primary", self._geo_region,
                quorum=cfg.geo_quorum or cfg.replica_cnt,
                quorum_acked=georepl.quorum_ack(acked, cfg.geo_quorum),
                repl_applied_min=min(applied, default=-1),
                quorum_stall_ms=stall_ms,
                promote_cnt=self._promote_cnt), flush=True)
        if self._repair:
            # repair counters ([summary] satellite) + the [repair] line
            # (parsed by harness.parse.parse_repair).  Salvaged txns are
            # commits — total_txn_abort_cnt already excludes them at the
            # source (engine/repair.run_repair) — so abort parsing keeps
            # its pre-repair semantics; plane_cnt is the host-side
            # cross-check counted off the 4th verdict plane.
            from deneva_tpu.engine.repair import repair_line
            rep_fields = {}
            for k in ("rep_salvaged_cnt", "rep_frontier_cnt",
                      "rep_fallback_cnt"):
                v = float(final[k] - measured[k])
                st.set(k, v)
                rep_fields[k[4:-4]] = int(v)
            print(repair_line(self.me, dict(
                **rep_fields, rounds=cfg.repair_rounds,
                plane_cnt=self._rep_salvaged - self._rep_meas)),
                flush=True)
        if cfg.cc_alg == CCAlg.DGCC:
            # DGCC wavefront ledger ([summary] satellite + the [dgcc]
            # line, parsed by harness.parse.parse_dgcc) — same fields
            # as the in-process driver's; wave_max is the run-wide
            # device running max.  Emitted only under DGCC so every
            # other config's output is byte-identical.
            from deneva_tpu.stats import tagged_line
            for k in ("dgcc_wave_cnt", "dgcc_fallback_cnt",
                      "dgcc_edge_cnt"):
                st.set(k, float(final[k] - measured[k]))
            st.set("dgcc_wave_max", float(final["dgcc_wave_max"]))
            print(tagged_line("dgcc", {
                "node": self.me,
                "waves": int(final["dgcc_wave_cnt"]
                             - measured["dgcc_wave_cnt"]),
                "wave_max": int(final["dgcc_wave_max"]),
                "fallback": int(final["dgcc_fallback_cnt"]
                                - measured["dgcc_fallback_cnt"]),
                "edges": int(final["dgcc_edge_cnt"]
                             - measured["dgcc_edge_cnt"])}), flush=True)
        if self.adm is not None:
            # admission counters ([summary]) + per-tenant [admission]
            # lines (parsed by harness.parse.parse_admission)
            self.adm.summary_into(st)
            for line in self.adm.admission_lines(self.me):
                print(line, flush=True)
        if self.ctl is not None:
            # control-plane counters ([summary] satellite; the per-tick
            # record is the [ctrl] line stream parsed by
            # harness.parse.parse_ctrl).  Emitted only when armed so
            # the default summary line is byte-identical.
            st.set("ctrl_decisions", float(self.ctl.seq))
            st.set("ctrl_trips", float(self.ctl.stale_trips))
            st.set("ctrl_qidx", float(self.ctl.quota_idx))
        if self.tel is not None:
            # flight-recorder counters ([summary]) + the [telemetry]
            # line (parsed by harness.parse.parse_telemetry); the final
            # flush closes the sidecar the txntrace merger joins
            self.tel.flush()
            self._metrics.close()
            self.tel.summary_into(st)
            st.set("metrics_lines", float(self._metrics.lines))
            print(telemetry_line(self.me, self.tel.fields()), flush=True)
        if self.mbus is not None:
            # metrics bus counters ([summary] satellite): frames sent,
            # [crit] windows, per-partition density totals; the
            # aggregator adds its receive/watch accounting and closes
            # the metrics_bus_*.jsonl stream the TUI tails
            self.mbus.summary_into(st)
            if self.magg is not None:
                self.magg.summary_into(st)
                self.magg.close()
        if self.aud is not None:
            # isolation audit counters ([summary] satellite: the
            # anti-inert audit_edges_exported the bench gate reads) +
            # the [audit] line (parsed by harness.parse.parse_audit);
            # the device edge counters diff over the measured window
            # like every other device stat
            for k in ("audit_edge_cnt", "audit_drop_cnt",
                      "audit_wit_cnt"):
                st.set(k, float(final[k] - measured[k]))
            self.aud.summary_into(st)
            print(self._AUD.audit_line(self.me, self.aud.fields()),
                  flush=True)
            self.aud.close()
        if self._fencing:
            # fencing counters ([summary]) + the [fencing] line (parsed
            # by harness.parse.parse_fencing) + the sidecar the chaos
            # harness audits (digest-vs-independent-replay under the
            # FINAL map, single-writer last-acked-epoch bound)
            import json

            print(self._FD.fencing_line(self.me, self._fence_fields(0)),
                  flush=True)
            st.set("fence_nack_cnt", float(self._fence_nacks))
            st.set("fence_nack_rx_cnt", float(self._fence_nack_rx))
            st.set("suspect_cnt", float(self._fd.suspect_cnt))
            st.set("heal_cnt", float(self._fd.heal_cnt))
            st.set("phi_peak", self._fd.phi_peak)
            st.set("fence_reassign_epoch",
                   float(self._fence_reassign_epoch))
            with open(os.path.join(cfg.log_dir,
                                   f"node{self.me}.fencing.json"),
                      "w") as f:
                json.dump({
                    "node": self.me, "epochs_run": int(epochs_run),
                    "map_version": int(self.smap.version),
                    "owners": [int(x) for x in self.smap.owners],
                    "reassign_epoch": int(self._fence_reassign_epoch),
                    "state_digest": state_digest(self.db),
                    "last_acked_epoch": int(self._fence_last_ack)}, f)
        if self._elastic:
            # membership counters ([summary] satellite): how much the
            # control plane moved and what the cutovers cost
            st.set("map_version", float(self.smap.version))
            st.set("owned_slots", float(len(self.smap.slots_of(self.me))))
            st.set("rebalance_cnt", float(self._rebalance_cnt))
            st.set("rows_migrated", float(self._rows_in + self._rows_out))
            st.set("rows_migrated_in", float(self._rows_in))
            st.set("rows_migrated_out", float(self._rows_out))
            st.set("cutover_stall_ms", self._cutover_stall_ms)
            st.set("redirect_nack_cnt", float(self._redirects))
        if self.mesh is not None:
            # mesh counters ([summary] satellite) + the [mesh] line
            # (parsed by harness.parse.parse_mesh): shard count, the
            # bytes the owner exchange moves between chips an epoch
            # (static, from the block shapes `execute_mc` cuts; 0 where
            # the generic `mc_execute` runs, which exchanges no lanes),
            # and how often the verdict-plane prefetch was
            # already finished at its retirement turn (prefetch_overlap
            # = d2h+unpack genuinely hidden behind device execution).
            # Emitted only when a mesh is armed, so the single-device
            # summary stays byte-identical.
            from deneva_tpu.parallel.mesh import (a2a_bytes_per_epoch,
                                                  mesh_line)
            ratio = self._prefetch_hits / max(self._prefetch_polls, 1)
            from deneva_tpu.ops import forwarding_applies
            a2a = a2a_bytes_per_epoch(cfg, self.b_merged, self._width) \
                if forwarding_applies(self.be, self.wl) else 0
            st.set("mesh_shards", float(cfg.device_parts))
            st.set("mesh_a2a_bytes", float(a2a))
            st.set("mesh_prefetch_overlap", ratio)
            print(mesh_line(self.me, {
                "shards": cfg.device_parts, "a2a_bytes": a2a,
                "prefetch_overlap": f"{ratio:.4f}",
                "groups": self._prefetch_polls}), flush=True)
        for k, v in self.tp.stats().items():
            if not chaos and k in ("msg_dropped", "msg_dup", "reconnects",
                                   "msg_blackholed"):
                continue   # keep the default-config summary line as-is
            st.set(f"net_{k}", float(v))
        return st

    def close(self) -> None:
        if self.wire_pool is not None:
            # wait: an in-flight wire body still holds self.tp; destroying
            # the native transport under it would be a use-after-free
            self.wire_pool.shutdown(wait=True)
        if self.retire_pool is not None:
            self.retire_pool.shutdown(wait=True)
        if self.magg is not None:
            # idempotent: the summary path already closed it on the
            # normal exit; this covers error unwinds
            self.magg.close()
        if self.aud is not None:
            # same idempotent-close posture as the aggregator stream
            self.aud.close()
        if self.ctl is not None:
            self._ctrl_log.close()
        self.tp.close()


class _Timeline:
    """Per-epoch phase timing (reference DEBUG_TIMELINE, SURVEY §5.1)."""

    def __init__(self):
        self.t = time.monotonic()
        self.spans: list[tuple[str, float]] = []

    def mark(self, name: str, now: float | None = None) -> None:
        if now is None:
            now = time.monotonic()
        self.spans.append((name, now - self.t))
        self.t = now

    def emit(self, node: int, epoch: int) -> None:
        body = " ".join(f"{n}={dt * 1e3:.1f}ms" for n, dt in self.spans)
        print(f"[timeline] node={node} epoch={epoch} {body}", flush=True)
        self.spans.clear()


@functools.lru_cache(maxsize=1)
def _key0():
    import jax
    return jax.random.PRNGKey(0)
