"""Client node (reference `runcl`: `client/` + `system/client_thread.cpp`).

Pre-generates a ring of queries per server (reference
`client_query_queue`, `client/client_query.cpp:112-121`), then drives an
open loop: send CL_QRY_BATCH blocks round-robin across servers while the
per-server inflight count stays under the throttle
(`client/client_txn.cpp:25-46`, `g_inflight_max`), decrement on CL_RSP and
record end-to-end latency (`system/io_thread.cpp:85-132`).  Two load modes
as in the reference (`config.h:21-22`): LOAD_MAX (saturate) and LOAD_RATE
(fixed txn/s budget per tick).

Latency tags: each txn carries a 40-bit tag = (batch_seq << 16 | lane);
the client remembers send times per tag in a ring and matches CL_RSP tags
back to compute client_client_latency percentiles (the reference's
client-side `StatsArr`, `scripts/latency_stats.py:20`).
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from deneva_tpu.config import Config
from deneva_tpu.runtime import wire
from deneva_tpu.runtime.native import NativeTransport
from deneva_tpu.runtime.telemetry import (ST_ACK, ST_BACKOFF, ST_RESEND,
                                          ST_SEND, V_SHED, telemetry_line)
from deneva_tpu.stats import Stats

TAG_RING = 1 << 22            # outstanding-tag ring per client: must
#                               exceed the per-client inflight cap or tag
#                               reuse corrupts latency matching (the
#                               pipelined server holds pipeline_epochs *
#                               pipeline_groups * epoch_batch txns open)


class ClientNode:
    def __init__(self, cfg: Config, endpoints: str, platform: str,
                 setup_wait_s: float = wire.SETUP_WAIT_S):
        import jax
        from deneva_tpu.runtime.jaxenv import init_jax
        self.info = init_jax(platform)
        from deneva_tpu.workloads import get_workload

        self.cfg = cfg
        self.setup_wait_s = setup_wait_s
        self.me = cfg.node_id                   # transport id (>= node_cnt)
        self.n_srv = cfg.node_cnt
        self.n_all = (self.n_srv + cfg.client_node_cnt
                      + cfg.replica_cnt * cfg.node_cnt)
        self.wl = get_workload(cfg)
        self.tp = NativeTransport(self.me, endpoints, self.n_all,
                                  msg_size_max=cfg.msg_size_max,
                                  send_threads=cfg.send_thread_cnt,
                                  recv_threads=cfg.rem_thread_cnt)
        self.tp.start(int(setup_wait_s * 1000))
        if cfg.net_delay_us:
            self.tp.set_delay_us(int(cfg.net_delay_us))
        # ---- fault mode (chaos harness): the open loop must DEGRADE
        # under message loss, not wedge.  A lost CL_QRY_BATCH or CL_RSP
        # is repaired by resending the still-unacked tags after
        # fault_resend_us (the server's idempotent admission dedups and
        # re-acks); duplicate acks are filtered against the unacked
        # bitmap so the inflight throttle never drifts.  All of it is
        # gated off on a default config. ----
        self._fault_mode = cfg.faults_enabled
        if (cfg.fault_drop_prob or cfg.fault_dup_prob
                or cfg.fault_delay_jitter_us):
            self.tp.set_fault(cfg.fault_drop_prob, cfg.fault_dup_prob,
                              cfg.fault_delay_jitter_us,
                              seed=cfg.fault_seed + 7919 * cfg.node_id)
        # ---- overload tier (runtime/loadgen.py + runtime/admission.py):
        # open-loop arrival schedule, per-query tenant ids in tag bits
        # 24..31, and the ADMIT_NACK backoff ledger.  All gated off on a
        # default config: no arrival process, tenant_cnt=1 writes no tag
        # bits, admission=false means no NACK ever arrives. ----
        self._adm = cfg.admission
        self._arrival = None
        self._fleet = None
        self._fleet_credits = None
        self._flash_end_us: float | None = None
        if cfg.loadgen_procs > 1:
            # pod-scale fleet: N generator processes pace disjoint
            # lane-tag sub-rings and tenant sub-ranges; the coordinator
            # (this node) keeps mirror schedules for the merged target.
            # LoadFleet speaks the ArrivalSchedule interface, so every
            # arrival-gated path below is shared verbatim.
            from deneva_tpu.runtime.loadgen import FleetCredits, LoadFleet
            self._fleet = LoadFleet(cfg, cfg.node_id, TAG_RING,
                                    cfg.client_batch_size)
            self._fleet_credits = FleetCredits(cfg.loadgen_procs, TAG_RING)
            self._arrival = self._fleet
        elif cfg.arrival_process:
            from deneva_tpu.runtime.loadgen import ArrivalSchedule
            self._arrival = ArrivalSchedule(cfg, cfg.node_id)
        self._ledger = None
        self._nacked = None
        if self._adm:
            from deneva_tpu.runtime.loadgen import BackoffLedger
            self._ledger = BackoffLedger(
                TAG_RING, cfg.nack_backoff_base_us,
                cfg.nack_backoff_max_us,
                cfg.seed + 104729 * cfg.node_id)
            self._nacked = np.zeros(TAG_RING, bool)
            # sweep at half the base backoff, floored at 10 ms: the
            # sweep coalesces everything ready, so a coarse cadence
            # costs at most one tick of extra delay and keeps re-entry
            # traffic in few large batches
            self._bo_sweep_us = max(int(cfg.nack_backoff_base_us) // 2,
                                    10_000)
            self._bo_next_us = 0
        self._nack_cnt = 0
        self._nack_resend_cnt = 0
        self._post_flash_acks = 0
        self._backlog_max = 0
        # the unacked bitmap serves BOTH repair paths: fault-mode resend
        # (loss) and admission backoff (NACK) key their freshness and
        # exactly-once filters on it
        self._unacked = (np.zeros(TAG_RING, bool)
                         if (self._fault_mode or self._adm) else None)
        self._resend_q: deque[tuple[int, int, wire.QueryBlock]] = deque()
        self._resend_us = int(cfg.fault_resend_us)
        # resend sweeps amortize across ticks: walking the queue every
        # loop iteration is per-tick overhead for a timeout-granularity
        # job — sweeping at resend_us/8 cadence delays a repair by at
        # most 12.5% of the timeout and frees the hot loop
        self._sweep_every_us = max(self._resend_us // 8, 1_000)
        self._sweep_next_us = 0
        self._resend_cnt = 0
        self._dup_acks = 0
        # ---- elastic membership (runtime/membership.py): target only
        # servers that own slots; MAP_UPDATE (install broadcast or a
        # drained server's redirect NACK) refreshes the active set and
        # the resend sweep retargets unacked tags onto an owner.  With
        # elastic off (default) every server is active and no code path
        # below changes. ----
        self._elastic = cfg.elastic
        self._map_version = 0
        self._redirect_resends = 0
        if self._elastic:
            from deneva_tpu.runtime.membership import initial_map
            self._active = np.zeros(self.n_srv, bool)
            self._active[[n for n in initial_map(cfg).active_nodes()
                          if n < self.n_srv]] = True
        else:
            self._active = np.ones(self.n_srv, bool)
        self._rr = 0   # rotating retarget cursor
        # ---- geo tier (runtime/replication.py): nearest-primary write
        # targeting, follower snapshot reads against the nearest live
        # replica, WAN profile on every outbound link.  With geo off
        # (default) no code path below changes. ----
        self._geo = cfg.geo
        if self._geo:
            from deneva_tpu.runtime import replication as georepl
            self._georepl = georepl
            self._region = georepl.region_of(cfg, self.me)
            self._srv_tiers = georepl.server_tiers(cfg, self._region)
            self._follower_order = georepl.follower_order(cfg,
                                                          self._region)
            self._geo_rr = 0
            self._read_batch = min(256, cfg.client_batch_size)
            self._fr_ring_pos = 0
            self._fr_seq = 0
            self._fr_out: dict[int, tuple[int, int, int]] = {}
            # outstanding reads: seq -> (sent us, follower id, rows)
            self._fr_rows = 0          # snapshot rows answered
            self._fr_sent_rows = 0     # rate-target ledger (lost rows
            #                            re-credited so reads re-issue)
            self._fr_tx_rows = 0       # rows actually transmitted
            self._fr_lost = 0          # rows written off as lost
            self._fr_boundary: dict[int, int] = {}   # rid -> last epoch
            self._fr_mono_viol = 0     # served boundary regressed
            self._fr_ver_viol = 0      # row version stamp > boundary
            if cfg.geo_wan_us:
                georepl.apply_wan_profile(self.tp, cfg, self.me)
        # ---- transaction flight recorder (runtime/telemetry.py — off
        # on a default config: no recorder, no sidecar, no [telemetry]
        # line; the send path is untouched byte for byte).  The client
        # records the SAME deterministically sampled txns every server
        # picks (lane % telemetry_sample), keyed by the packed
        # ``me << 40 | tag`` id the servers stamp at admission. ----
        self.tel = None
        if cfg.telemetry:
            from deneva_tpu.runtime.telemetry import FlightRecorder
            self.tel = FlightRecorder(cfg, self.me, "client")
        # ---- live metrics bus (runtime/metricsbus.py — off on a
        # default config: no frame is ever built and the send path is
        # untouched byte for byte).  The client ships wall-cadence
        # frames (no epochs to key on): ack/resend/backoff rates + the
        # open-loop backlog, to the lowest-id active server. ----
        self.mbus = None
        if cfg.metrics:
            from deneva_tpu.runtime import metricsbus as _MB
            self._MB = _MB
            self.mbus = _MB.BusSender(cfg, self.me, _MB.ROLE_CLIENT)
            self._mb_last = {"acked": 0, "resend": 0, "backoff": 0}
        # elastic + fault mode: remember which server each tag's inflight
        # credit is CHARGED to.  After a retarget, the first ack may come
        # from a different server than the charge (the drained-but-alive
        # original releasing a held CL_RSP, or the retarget target
        # re-acking) — decrementing by ack SOURCE would leak credit on
        # one server and drive another negative; decrementing the charged
        # server is exact either way.
        self._tag_srv = (np.zeros(TAG_RING, np.int16)
                         if (cfg.elastic and self._fault_mode) else None)
        self.inflight = np.zeros(self.n_srv, np.int64)
        self.chunk = cfg.client_batch_size
        # reference: inflight cap is per server pair (client_txn.cpp:25);
        # sends SLICE down to the remaining budget (never the reverse —
        # flooring the cap up to the batch size would let a big batch
        # override max_txn_in_flight), floored at one minimal send
        self.cap = max(64,
                       cfg.max_txn_in_flight // max(cfg.client_node_cnt, 1))
        # tag-ring soundness (ADVICE r3): a tag may be reissued only
        # after its txn left the system.  Tags come from ONE ring shared
        # across all servers while ``cap`` bounds inflight PER server, so
        # the bound is cap * n_srv total outstanding; the servers' whole
        # pipeline window must fit a ring lap too
        total_cap = self.cap * self.n_srv
        # epoch_batch is already the CLUSTER-wide merged batch (servers
        # split it b_loc = epoch_batch/n_srv), so no n_srv factor here
        window = (cfg.pipeline_epochs * cfg.pipeline_groups
                  * cfg.epoch_batch)
        if total_cap >= TAG_RING or window >= TAG_RING:
            raise ValueError(
                f"client tag ring ({TAG_RING}) must exceed both the "
                f"total outstanding cap ({total_cap} = per-server cap * "
                f"{self.n_srv} servers) and the servers' pipeline window "
                f"({window}); shrink max_txn_in_flight or the pipeline "
                "depth")
        if self._fleet is not None:
            # fleet mode shrinks the reuse horizon: tags cycle within
            # one generator's sub-ring, so the whole outstanding window
            # must fit a single lane's span
            from deneva_tpu.runtime.loadgen import FLEET_LANE_BITS
            span = TAG_RING >> FLEET_LANE_BITS
            if total_cap >= span or window >= span:
                raise ValueError(
                    f"fleet lane sub-ring ({span}) must exceed the "
                    f"total outstanding cap ({total_cap}) and the "
                    f"pipeline window ({window}): tags reuse within one "
                    "generator's range — shrink max_txn_in_flight or "
                    "the pipeline depth")
        self.send_us = np.zeros(TAG_RING, np.int64)   # tag -> send time
        self.next_tag = 0
        self.stats = Stats()
        self.stop = False

        # pre-generate a query ring (client_query.cpp pre-generation):
        # enough blocks that wraparound reuse is harmless (fresh zipf draws
        # per block; the reference wraps the same way)
        rng = jax.random.PRNGKey(cfg.seed + 7919 * cfg.node_id)
        n_pregen = 64
        self.ring: list[wire.QueryBlock] = []
        self.ring_types: list[np.ndarray] = []
        for i in range(n_pregen):
            q = self.wl.generate(jax.random.fold_in(rng, i), self.chunk)
            keys, types, scalars = self.wl.to_wire(q)
            self.ring.append(wire.QueryBlock(
                keys=keys, types=types, scalars=scalars,
                tags=np.zeros(self.chunk, np.int64)))
            self.ring_types.append(
                np.asarray(self.wl.txn_type_of(q), np.uint8))
        self.ring_pos = 0
        # mid-run contention shift (Config.zipf_shift, the ctrl chaos
        # scenario's load-shift half): a SECOND seeded ring drawn at the
        # shifted theta, swapped in wholesale AT_S seconds after run
        # start — tags, tenants, pacing and every repair path are ring-
        # agnostic, so only the key skew of freshly issued queries
        # changes.  Empty spec (default) builds nothing.
        self._shift = None
        if cfg.zipf_shift:
            from deneva_tpu.workloads import get_workload as _gw
            theta2, at_s = cfg.zipf_shift_spec()
            wl2 = _gw(cfg.replace(zipf_theta=theta2))
            rng2 = jax.random.PRNGKey(cfg.seed + 7919 * cfg.node_id + 1)
            ring2: list[wire.QueryBlock] = []
            types2: list[np.ndarray] = []
            for i in range(n_pregen):
                q = wl2.generate(jax.random.fold_in(rng2, i), self.chunk)
                keys, types, scalars = wl2.to_wire(q)
                ring2.append(wire.QueryBlock(
                    keys=keys, types=types, scalars=scalars,
                    tags=np.zeros(self.chunk, np.int64)))
                types2.append(np.asarray(wl2.txn_type_of(q), np.uint8))
            self._shift = (float(at_s), ring2, types2)
        # per-txn-type latency families (reference per-kind StatsArr,
        # VERDICT r3 next #6): remember each tag's txn type so CL_RSP
        # latency samples can feed {type}_latency percentiles
        self.type_names = list(getattr(self.wl, "txn_type_names",
                                       ("txn",)))
        self.tag_type = np.zeros(TAG_RING, np.uint8)
        # per-query tenant ids (overload tier): seeded per-ring-block
        # columns from the configured weights; each tag remembers its
        # tenant so acks feed tenant{t}_latency percentiles and the
        # fairness counters.  tenant_cnt=1 (default) builds none of it.
        self.ring_tenants: list[np.ndarray] | None = None
        self._tenant_on = cfg.tenant_cnt > 1
        if self._tenant_on:
            self.tag_tenant = np.zeros(TAG_RING, np.uint8)
            self._tenant_sent = np.zeros(cfg.tenant_cnt, np.int64)
            if self._fleet is None:
                # fleet mode draws tenant columns in the generator
                # processes (disjoint sub-ranges); single-process mode
                # keeps the seeded per-ring-block columns
                from deneva_tpu.runtime.loadgen import tenant_column
                w = np.asarray(cfg.tenant_weights_spec())
                trng = np.random.default_rng(
                    (cfg.seed + 15485863 * cfg.node_id) & 0x7FFFFFFF)
                self.ring_tenants = [tenant_column(trng, w, self.chunk)
                                     for _ in range(n_pregen)]

    # ------------------------------------------------------------------
    def _route(self, src: int, rtype: str, payload: bytes,
               lat_arr) -> None:
        if rtype == "CL_RSP":
            tags = wire.decode_cl_rsp(payload)
            now = time.monotonic_ns() // 1000
            if self._unacked is not None:
                # exactly-once accounting under dup/replay: accept each
                # tag's FIRST ack only — a duplicated CL_RSP or a
                # re-ack answering our own resend must not double-count
                # txn_cnt or drive the inflight throttle negative
                fresh = self._unacked[tags % TAG_RING]
                if not fresh.all():
                    self._dup_acks += int((~fresh).sum())
                    tags = tags[fresh]
                    if not len(tags):
                        return
                self._unacked[tags % TAG_RING] = False
            # inflight credit: a tag whose NACK already released its
            # credit (the NACK-then-late-CL_RSP race: a duplicate of the
            # query was NACKed while the original went on to commit)
            # must not release it twice — the ack retires the tag but
            # only non-NACKed tags still hold a charge
            rel = tags
            if self._nacked is not None:
                nk = self._nacked[tags % TAG_RING]
                if nk.any():
                    self._nacked[tags % TAG_RING] = False
                    rel = tags[~nk]
                self._ledger.reset(tags)
            if (self._flash_end_us is not None
                    and now >= self._flash_end_us):
                # post-burst recovery ledger: acks landing after the
                # flash window prove goodput came back
                self._post_flash_acks += len(tags)
            if self._tag_srv is not None:
                # release each tag's credit from the server it is
                # charged to (may differ from the answering server
                # after a retarget)
                self.inflight -= np.bincount(
                    self._tag_srv[rel % TAG_RING], minlength=self.n_srv
                )[: self.n_srv]
            else:
                self.inflight[src] -= len(rel)   # src is a server id
            slot = tags % TAG_RING
            vals = (now - self.send_us[slot]) / 1e6     # seconds
            # append each sample ONCE, into its type family — the
            # combined client_client_latency series is merged from the
            # families at summary time.  (Appending into both here
            # doubled the per-response host cost and halved measured
            # cluster throughput on a 1-core box where the client is
            # the binding resource.)
            if len(self.type_names) == 1:
                lat_arr.extend(vals)
            else:
                tt = self.tag_type[slot]
                for t in np.unique(tt):
                    m = tt == t
                    self.stats.arr(
                        f"{self.type_names[t]}_latency").extend(vals[m])
            if self._fleet_credits is not None:
                # fleet accounting: only non-NACKed tags still hold a
                # credit (same rule as the inflight release above)
                self._fleet_credits.release(rel)
            if self._tenant_on:
                # per-tenant latency families (overload tier): the
                # aggressor/fairness invariants compare these — samples
                # go ONLY into tenant arrays here, the combined series
                # is already fed by the type families above
                tn = self.tag_tenant[slot]
                for t in np.unique(tn):
                    m = tn == t
                    self.stats.arr(f"tenant{t}_latency").extend(vals[m])
            if self.tel is not None:
                # first-ack lifecycle hop (post-freshness: dup acks
                # never record)
                self.tel.record((np.int64(self.me) << 40) | tags,
                                ST_ACK, t_us=now)
            self.stats.incr("txn_cnt", len(tags))
        elif rtype == "ADMIT_NACK":
            from deneva_tpu.runtime.admission import decode_admit_nack
            tags, retry = decode_admit_nack(payload)
            slot = tags % TAG_RING
            # freshness: only outstanding, not-already-NACKed tags carry
            # a charge to release (a duplicated NACK, or one racing the
            # ack of an admitted copy, must be a no-op)
            fresh = self._unacked[slot] & ~self._nacked[slot]
            if not fresh.all():
                tags, retry, slot = tags[fresh], retry[fresh], slot[fresh]
            if not len(tags):
                return
            self._nacked[slot] = True
            self._nack_cnt += len(tags)
            now_us = time.monotonic_ns() // 1000
            if self._tag_srv is not None:
                self.inflight -= np.bincount(
                    self._tag_srv[slot], minlength=self.n_srv
                )[: self.n_srv]
            else:
                self.inflight[src] -= len(tags)
            if self._fleet_credits is not None:
                # the NACK releases the lane's credit exactly once
                # (the backoff re-entry recharges it)
                self._fleet_credits.nack(tags)
            if self.tel is not None:
                # shed lifecycle hop (aux = the server's retry-after
                # hint; the waterfall's "shed" verdict class keys on it)
                self.tel.record(
                    (np.int64(self.me) << 40) | tags, ST_BACKOFF,
                    verdict=V_SHED,
                    aux=retry.clip(max=0x7FFFFFFF).astype(np.int32),
                    t_us=now_us)
            # re-entry rides the backoff ledger (exponential + jitter,
            # floored at the server's per-tag retry-after hints)
            self._ledger.nack(src, tags, retry, now_us)
        elif rtype == "REGION_READ_RSP":
            tag, boundary, vals, vers = \
                self._georepl.decode_region_read_rsp(payload)
            ent = self._fr_out.pop(tag, None)
            if ent is not None:
                now = time.monotonic_ns() // 1000
                self.stats.arr("follower_read_latency").extend(
                    [(now - ent[0]) / 1e6])
                self._fr_rows += len(vals)
            # lockless version check (the read-set/version-check shape):
            # no served row may carry a version stamp newer than the
            # snapshot boundary it was served at, and one follower's
            # served boundary must never regress
            if len(vers) and int(vers.max()) > boundary:
                self._fr_ver_viol += 1
            if boundary < self._fr_boundary.get(src, -1):
                self._fr_mono_viol += 1
            else:
                self._fr_boundary[src] = boundary
        elif rtype == "MAP_UPDATE":
            from deneva_tpu.runtime.membership import decode_map_msg
            smap, _cut, _reason, _subject = decode_map_msg(payload)
            if smap.version > self._map_version:
                self._map_version = smap.version
                act = np.zeros(self.n_srv, bool)
                act[[n for n in smap.active_nodes()
                     if n < self.n_srv]] = True
                self._active = act
        elif rtype == "SHUTDOWN":
            self.stop = True

    def _drain(self, lat_arr, timeout_us: int = 0,
               max_msgs: int = 4096) -> None:
        # bounded like the server's _drain: under an overload NACK storm
        # the recv queue may never go dry, and the send/sweep half of
        # the loop must keep running (the hot loop re-calls every tick)
        for _ in range(max_msgs):
            m = self.tp.recv(timeout_us)
            if m is None:
                return
            self._route(*m, lat_arr)
            timeout_us = 0

    def barrier(self) -> None:
        lat = self.stats.arr("client_client_latency")
        wire.run_barrier(self.tp, self.me, self.n_all,
                         lambda s, r, p: self._route(s, r, p, lat),
                         f"client {self.me}", self.setup_wait_s)

    def _resend_sweep(self) -> None:
        """Repair message loss: batches older than fault_resend_us with
        tags still unacked are re-sent (same tags — the server's
        idempotent admission drops in-flight dups and re-acks committed
        ones); fully-acked batches just retire from the queue.  Latency
        keeps measuring from the FIRST send (send_us is not reset), so
        a repaired loss shows up as tail latency, not a clean sample."""
        now = time.monotonic_ns() // 1000
        while self._resend_q and now - self._resend_q[0][0] >= self._resend_us:
            _, srv, blk = self._resend_q.popleft()
            alive = self._unacked[blk.tags % TAG_RING]
            if self._nacked is not None:
                # NACKed tags are the backoff ledger's to re-enter (it
                # re-appends them here once resent); sweeping them too
                # would re-offer a query the server just shed
                alive = alive & ~self._nacked[blk.tags % TAG_RING]
            if not alive.any():
                continue
            sub = blk if alive.all() else blk.take(np.where(alive)[0])
            if self._elastic and not self._active[srv]:
                # the original target was drained, reassigned, or died:
                # retarget the unacked tags onto an owner (the server's
                # idempotent admission dedups / re-acks as usual — the
                # committed set outlives its admitting server)
                act = np.where(self._active)[0]
                if len(act):
                    old = srv
                    srv = int(act[self._rr % len(act)])
                    self._rr += 1
                    self._redirect_resends += len(sub)
                    self.inflight[old] -= len(sub)
                    self.inflight[srv] += len(sub)
                    self._tag_srv[sub.tags % TAG_RING] = srv
            self.tp.sendv(srv, "CL_QRY_BATCH",
                          wire.qry_block_parts(sub.tags, sub.keys,
                                               sub.types, sub.scalars))
            if self.tel is not None:
                # loss-repair resend hop (latency still measures from
                # the FIRST send; this marks the tail's cause)
                self.tel.record((np.int64(self.me) << 40) | sub.tags,
                                ST_RESEND, t_us=now)
            self._resend_cnt += len(sub)
            self._resend_q.append((now, srv, sub))

    def _backoff_sweep(self, now_us: int) -> None:
        """Re-enter NACKed tags whose backoff expired: fresh rows from
        the pre-generated ring under the SAME tags (the tag, not the row
        values, is the txn's identity — a NACKed query was never
        admitted anywhere), re-charging the inflight credit the NACK
        released.  Everything ready this sweep COALESCES into chunk-
        sized batches per server: ledger entries fragment as batches
        re-NACK (each cycle splits on the spread of fresh retry hints),
        and sending them one entry at a time degenerated into a tiny-
        message storm that crawled the 2-core cluster's epoch loop.  In
        fault mode the resent batches join the resend queue so a lost
        re-entry is repaired like any other loss."""
        ready = self._ledger.pop_ready(now_us)
        if not ready:
            return
        by_srv: dict[int, list] = {}
        for srv, tags in ready:
            if self._elastic and not self._active[srv]:
                # original target drained or died: re-enter via an owner
                act = np.where(self._active)[0]
                if not len(act):
                    # nobody to target — push back, try next sweep
                    self._ledger.nack(srv, tags,
                                      np.full(len(tags), 50_000,
                                              np.uint32), now_us)
                    continue
                srv = int(act[self._rr % len(act)])
                self._rr += 1
            by_srv.setdefault(srv, []).append(tags)
        for srv, tag_lists in by_srv.items():
            tags = np.concatenate(tag_lists)
            slot = tags % TAG_RING
            live = self._unacked[slot] & self._nacked[slot]
            if not live.all():
                tags, slot = tags[live], slot[live]
            for lo in range(0, len(tags), self.chunk):
                part = tags[lo:lo + self.chunk]
                pslot = slot[lo:lo + self.chunk]
                n = len(part)
                blk = self.ring[self.ring_pos]
                # the replacement rows carry the fresh block's txn types:
                # re-stamp the tag->type map or the ack's latency sample
                # lands in the ORIGINAL rows' type family
                self.tag_type[pslot] = self.ring_types[self.ring_pos][:n]
                self.ring_pos = (self.ring_pos + 1) % len(self.ring)
                self._nacked[pslot] = False
                self.inflight[srv] += n
                if self._fleet_credits is not None:
                    self._fleet_credits.charge(part)   # re-entry recharge
                if self._tag_srv is not None:
                    self._tag_srv[pslot] = srv
                self.tp.sendv(srv, "CL_QRY_BATCH",
                              wire.qry_block_parts(part, blk.keys[:n],
                                                   blk.types[:n],
                                                   blk.scalars[:n]))
                if self._fault_mode:
                    self._resend_q.append((now_us, srv, wire.QueryBlock(
                        blk.keys[:n], blk.types[:n], blk.scalars[:n],
                        part)))
                if self.tel is not None:
                    # backoff re-entry hop: the shed tag re-offers
                    self.tel.record((np.int64(self.me) << 40) | part,
                                    ST_RESEND, t_us=now_us)
                self._nack_resend_cnt += n

    def _mb_frame(self, backlog) -> None:
        """Ship one client metrics frame (wall-cadence) to the lowest-id
        active server — the aggregator's home.  Counters are deltas
        since the last SENT frame (a tick with no active target keeps
        its deltas for the next frame — the series may gap in transit,
        never at the source); backlog is the open-loop arrival debt."""
        act = np.where(self._active)[0]
        if not len(act):
            return
        last = self._mb_last
        acked = int(self.stats.counters.get("txn_cnt", 0))
        counters = dict(
            commit=acked - last["acked"],
            resend=self._resend_cnt - last["resend"],
            backoff=self._nack_resend_cnt - last["backoff"],
            backlog=int(backlog) if backlog is not None else 0,
            pending=len(self._resend_q))
        last.update(acked=acked, resend=self._resend_cnt,
                    backoff=self._nack_resend_cnt)
        parts, _rec = self.mbus.frame(-1, counters)
        self.tp.sendv(int(act[0]), "METRICS", parts)

    # -- geo tier: nearest-primary writes + follower snapshot reads -----
    def _geo_write_targets(self) -> list[int]:
        """Servers of the nearest tier (by region, then WAN delay) that
        still has an active member, rotated for in-tier fairness; [] if
        every server is inactive."""
        for tier in self._srv_tiers:
            live = [s for s in tier if self._active[s]]
            if live:
                self._geo_rr += 1
                r = self._geo_rr % len(live)
                return live[r:] + live[:r]
        return []

    def _nearest_follower(self) -> int | None:
        """First live replica in nearest-first order (None when the
        whole follower fleet is gone)."""
        for rid in self._follower_order:
            if self.tp.peer_alive(rid):
                return rid
        return None

    def _issue_follower_reads(self, sent_total: int, now_us: int) -> None:
        """Keep snapshot-read traffic at ``geo_read_perc`` of total load
        (reads / (reads + writes)), at most 4 outstanding batches;
        outstanding batches older than 16x the resend timeout are
        written off as lost (a killed follower must not wedge the read
        loop — REGION_READ has no resend story by design, it is
        re-issued from this ledger against the next-nearest follower).
        16x = 4 s at the default resend timeout: past the worst
        serve+apply head-of-line lag measured on the contended 2-core
        box (~1.3 s), still well inside the region-loss scenario window
        so re-targeting off a dead follower stays live.  Written-off
        rows are re-credited to the rate target, so replacement batches
        go out (to whichever follower is nearest NOW) and the achieved
        read fraction recovers after a failover instead of permanently
        undershooting by the lost traffic."""
        for seq in [s for s, (t, _r, _n) in self._fr_out.items()
                    if now_us - t > 16 * self._resend_us]:
            rows = self._fr_out.pop(seq)[2]
            self._fr_sent_rows -= rows
            self._fr_lost += rows
        p = self.cfg.geo_read_perc
        target = p / (1.0 - p) * max(sent_total, 1)
        while (self._fr_sent_rows < target and len(self._fr_out) < 4):
            rid = self._nearest_follower()
            if rid is None:
                return
            blk = self.ring[self._fr_ring_pos]
            self._fr_ring_pos = (self._fr_ring_pos + 1) % len(self.ring)
            keys = np.ascontiguousarray(
                blk.keys.reshape(-1)[: self._read_batch], np.int32)
            seq = self._fr_seq
            self._fr_seq += 1
            self.tp.sendv(rid, "REGION_READ",
                          self._georepl.region_read_parts(seq, keys))
            self._fr_out[seq] = (now_us, rid, len(keys))
            self._fr_sent_rows += len(keys)
            self._fr_tx_rows += len(keys)

    # ------------------------------------------------------------------
    def run(self) -> Stats:
        cfg = self.cfg
        self.barrier()
        lat = self.stats.arr("client_client_latency")
        srv = 0
        # LOAD_RATE budget (reference client_thread.cpp:35-41,70-91)
        rate = cfg.load_rate / max(cfg.client_node_cnt, 1)
        t_start = time.monotonic()
        if self._fleet is not None:
            self._fleet.go()     # start every generator lane's clock
        if self._arrival is not None:
            fe = self._arrival.flash_end()
            if fe is not None:
                self._flash_end_us = (t_start + fe) * 1e6
        sent_total = 0
        iota = np.arange(self.chunk, dtype=np.int64)   # reusable tag base
        while not self.stop:
            if self._shift is not None \
                    and time.monotonic() - t_start >= self._shift[0]:
                # contention shift: swap the whole pre-generated ring;
                # in-flight tags, backoff ledgers and resend queues keep
                # their original rows (a tag's identity is the tag)
                _, self.ring, self.ring_types = self._shift
                self._shift = None
                print(f"[client] node={self.me} zipf_shift engaged",
                      flush=True)
            progressed = False
            # open-loop arrivals: the seeded schedule, not acks, drives
            # the send budget — a stalled server grows the backlog
            # (visible as backlog_max) instead of throttling the load
            backlog = None
            if self._arrival is not None:
                backlog = self._arrival.target(
                    time.monotonic() - t_start) - sent_total
                if backlog > self._backlog_max:
                    self._backlog_max = backlog
            # vectorized admission: per-server send budgets for this
            # whole tick in one pass (the per-send path below touches
            # no Python-level min/int bookkeeping)
            budgets = np.minimum(self.chunk,
                                 self.cap - self.inflight).astype(np.int64)
            if self._geo:
                # nearest-primary writes: the closest region tier that
                # still has an active server takes this tick's sends
                # (rotated for fairness inside the tier); farther tiers
                # only see traffic once every nearer one is drained or
                # dead
                cand = self._geo_write_targets()
            else:
                cand = [(srv + 1 + i) % self.n_srv
                        for i in range(self.n_srv)]
            for c in cand:
                srv = c
                if not self._active[srv]:       # slotless under the map
                    continue
                n = int(budgets[srv])
                if n < 64:                      # not worth a message yet
                    continue
                if backlog is not None:
                    if backlog < 64:            # schedule has no arrivals
                        break                   # worth a message yet
                    n = min(n, backlog)
                elif rate:
                    budget = int(rate * (time.monotonic() - t_start)) \
                        - sent_total
                    if budget <= 0:
                        break
                    n = min(n, budget)
                tcol = None
                if self._fleet is not None:
                    # fleet mode: tags + tenant columns stream from the
                    # generator processes (disjoint lane sub-rings);
                    # nothing buffered means nothing is due yet
                    fb = self._fleet.take(n)
                    if fb is None:
                        break
                    tags, tcol = fb
                    n = len(tags)
                blk = self.ring[self.ring_pos]
                blk_types = self.ring_types[self.ring_pos]
                self.ring_pos = (self.ring_pos + 1) % len(self.ring)
                now = time.monotonic_ns() // 1000
                if self._fleet is None:
                    tags = (iota[:n] + self.next_tag) % TAG_RING
                    self.next_tag = int(tags[-1]) + 1
                self.send_us[tags] = now
                self.tag_type[tags] = blk_types[:n]
                wtags = tags
                if self._tenant_on:
                    # tenant ids ride tag bits 24..31; the lane (low
                    # bits) keeps indexing every per-tag ring below
                    from deneva_tpu.runtime.loadgen import pack_tenant
                    if tcol is None:
                        tcol = self.ring_tenants[
                            (self.ring_pos - 1) % len(self.ring)][:n]
                    wtags = pack_tenant(tags, tcol)
                    self.tag_tenant[tags] = tcol
                    self._tenant_sent += np.bincount(
                        tcol, minlength=len(self._tenant_sent))
                # scatter-send straight from the pre-generated ring
                # columns (row slices stay C-contiguous): the per-send
                # codec pass — the client's dominant per-message cost —
                # is gone; the native layer frames header+tags+columns
                self.tp.sendv(srv, "CL_QRY_BATCH",
                              wire.qry_block_parts(wtags, blk.keys[:n],
                                                   blk.types[:n],
                                                   blk.scalars[:n]))
                if self.tel is not None:
                    # first-send lifecycle hop: the sampled subset here
                    # is exactly what every server will sample (same
                    # lane predicate), keyed by the packed id admission
                    # stamps
                    self.tel.record((np.int64(self.me) << 40) | wtags,
                                    ST_SEND, t_us=now)
                if self._unacked is not None:
                    self._unacked[tags] = True
                    if self._nacked is not None:
                        # reissued lane hygiene: stale NACK state from a
                        # previous ring lap must not leak into this tag
                        self._nacked[tags] = False
                        self._ledger.reset(tags)
                    if self._tag_srv is not None:
                        self._tag_srv[tags] = srv
                    if self._fault_mode:
                        self._resend_q.append((now, srv, wire.QueryBlock(
                            blk.keys[:n], blk.types[:n], blk.scalars[:n],
                            wtags)))
                self.inflight[srv] += n
                if self._fleet_credits is not None:
                    self._fleet_credits.charge(tags)
                sent_total += n
                if backlog is not None:
                    backlog -= n
                progressed = True
            if self._geo and self.cfg.geo_read_perc > 0:
                self._issue_follower_reads(sent_total,
                                           time.monotonic_ns() // 1000)
            if self._fault_mode:
                now_us = time.monotonic_ns() // 1000
                if now_us >= self._sweep_next_us:
                    self._resend_sweep()
                    self._sweep_next_us = now_us + self._sweep_every_us
            if self._ledger is not None:
                now_us = time.monotonic_ns() // 1000
                if now_us >= self._bo_next_us:
                    self._backoff_sweep(now_us)
                    self._bo_next_us = now_us + self._bo_sweep_us
            if self.tel is not None and self.tel.should_flush:
                # half-full ring flush (the server does this at group
                # boundaries): a saturated multi-second run otherwise
                # fills the ring and silently drops the tail's acks
                self.tel.flush()
            if self.mbus is not None \
                    and self.mbus.client_due(time.monotonic_ns() // 1000):
                # metrics bus: wall-cadence client frame (ack/resend/
                # backoff rates + the open-loop backlog)
                self._mb_frame(backlog)
            self._drain(lat, timeout_us=0 if progressed else 2_000)
        # drain trailing responses so server-side commits are counted
        t_end = time.monotonic() + 0.3
        while time.monotonic() < t_end:
            self._drain(lat, timeout_us=20_000)
        st = self.stats
        if len(self.type_names) > 1:
            # merge the per-type families into the combined series (one
            # cheap pass at the end, not one per response)
            combined = st.arr("client_client_latency")
            for nm in self.type_names:
                a = st.arrays.get(f"{nm}_latency")
                if a is not None:
                    combined.merge_from(a)
        st.set("total_runtime", time.monotonic() - t_start)
        st.set("sent_cnt", float(sent_total))
        if self._fault_mode:
            st.set("resend_cnt", float(self._resend_cnt))
            st.set("dup_ack_cnt", float(self._dup_acks))
            st.set("unacked_cnt", float(int(self._unacked.sum())))
        if self._adm:
            st.set("nack_cnt", float(self._nack_cnt))
            st.set("nack_resend_cnt", float(self._nack_resend_cnt))
            st.set("backoff_pending_cnt", float(len(self._ledger)))
        if self._arrival is not None:
            st.set("arrival_target_cnt", float(
                self._arrival.target(time.monotonic() - t_start)))
            st.set("backlog_max", float(self._backlog_max))
            if self._flash_end_us is not None:
                st.set("post_flash_ack_cnt", float(self._post_flash_acks))
        if self._fleet_credits is not None:
            # per-lane ledger + the exactly-once invariant counters
            # (double_* must be 0 — the freshness filters upstream are
            # the only legal dedup point)
            fc = self._fleet_credits
            for g in range(fc.n):
                st.set(f"fleetg{g}_sent_cnt", float(fc.sent[g]))
                st.set(f"fleetg{g}_acked_cnt", float(fc.acked[g]))
                st.set(f"fleetg{g}_nacked_cnt", float(fc.nacked[g]))
            st.set("fleet_procs", float(fc.n))
            st.set("fleet_outstanding_cnt", float(fc.outstanding().sum()))
            st.set("fleet_double_release_cnt",
                   float(fc.double_charge + fc.double_release))
        if self._tenant_on:
            for t in range(len(self._tenant_sent)):
                st.set(f"tenant{t}_sent_cnt",
                       float(self._tenant_sent[t]))
                a = st.arrays.get(f"tenant{t}_latency")
                st.set(f"tenant{t}_acked_cnt",
                       float(len(a)) if a is not None else 0.0)
        if self.tel is not None:
            # flight-recorder flush + counters + the [telemetry] line
            # (same emission contract as the servers')
            self.tel.flush()
            self.tel.summary_into(st)
            print(telemetry_line(self.me, self.tel.fields()), flush=True)
        if self.mbus is not None:
            # metrics bus counters (frames shipped; no density or crit
            # windows on a client)
            self.mbus.summary_into(st)
        if self._elastic:
            st.set("map_version", float(self._map_version))
            st.set("redirect_resend_cnt", float(self._redirect_resends))
        if self._geo:
            st.set("geo_region", float(self._region))
            st.set("follower_read_cnt", float(self._fr_rows))
            st.set("follower_read_sent", float(self._fr_tx_rows))
            st.set("follower_read_lost", float(self._fr_lost))
            st.set("follower_read_mono_viol", float(self._fr_mono_viol))
            st.set("follower_read_ver_viol", float(self._fr_ver_viol))
        for k, v in self.tp.stats().items():
            if not self._fault_mode and k in ("msg_dropped", "msg_dup",
                                              "reconnects",
                                              "msg_blackholed"):
                continue   # keep the default-config summary line as-is
            st.set(f"net_{k}", float(v))
        return st

    def close(self) -> None:
        if self._fleet is not None:
            self._fleet.close()
        self.tp.close()
