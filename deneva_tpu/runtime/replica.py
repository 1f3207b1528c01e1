"""Replica node (reference active-passive replication, SURVEY §5.4:
`REPLICA_CNT`/`REPL_TYPE` `config.h:24-27`, replica id range ISREPLICA
`system/global.h:301`, LOG_MSG/LOG_MSG_RSP flow
`system/worker_thread.cpp:527-541`).

A replica is a log sink: it receives its primary's framed epoch records
(LOG_MSG payload = the exact bytes the primary fsyncs), appends them to
its own log file, and acks the epoch (LOG_RSP).  The primary's group
commit waits for both its local flush and this ack.  Unlike the reference
(which never reads records back), a replica's log replays with
`runtime.logger.replay_log` to rebuild the primary's partition state —
that is the failover story: promote by replay.

Geo mode (`Config.geo`, runtime/replication.py) turns the sink into a
FOLLOWER: the durability ack becomes LOG_ACK (acked + applied horizon,
feeding the primary's quorum group-commit), a `GeoFollower` replays the
merged command stream group-by-group into full-residency tables, and
REGION_READ snapshot reads are served off the last applied group
boundary with per-row version stamps — read traffic scales on replicas
and never touches the OLTP epoch loop.  Region loss semantics: under
geo, ``fault_kill "n:e"`` also kills every replica homed in n's REGION
at its first record >= e (exit 17, the planned-kill sentinel), so a
region takes its whole process set down together.
"""

from __future__ import annotations

import os
import struct
import time

from deneva_tpu.config import Config
from deneva_tpu.runtime import replication as georepl
from deneva_tpu.runtime import wire
from deneva_tpu.runtime.native import NativeTransport
from deneva_tpu.runtime.telemetry import ST_APPLY, telemetry_line
from deneva_tpu.stats import Stats

_EPOCH_HDR = struct.Struct("<Iq")   # magic, epoch (prefix of logger._FRAME)


class ReplicaNode:
    def __init__(self, cfg: Config, endpoints: str, platform: str = "cpu",
                 setup_wait_s: float = wire.SETUP_WAIT_S):
        self.cfg = cfg
        self.setup_wait_s = setup_wait_s
        # a plain log sink never touches JAX; a geo follower replays
        # through the per-epoch jit and picks its device like any node
        self.info: dict = {}
        if cfg.geo:
            from deneva_tpu.runtime.jaxenv import init_jax
            self.info = init_jax(platform)
        self.me = cfg.node_id
        self.n_srv = cfg.node_cnt
        self.n_cl = cfg.client_node_cnt
        n_repl = cfg.replica_cnt * cfg.node_cnt
        self.n_all = self.n_srv + self.n_cl + n_repl
        self._geo = cfg.geo
        self.follower = None
        self._kill_at = None
        self.region = 0
        # fencing (runtime/faildet.py): LOG_MSG arrives wrapped in a
        # fence envelope carrying the primary's map version; the replica
        # strips it before appending (its log must stay a byte prefix
        # of the primary's) and rejects a REGRESSED version with
        # FENCE_NACK — a fenced-out primary must not extend the
        # durability stream its successor already owns
        self._fencing = cfg.fencing
        self._fence_ver = -1
        self._fence_nacks = 0
        if self._geo:
            self.region = georepl.region_of(cfg, self.me)
            kill = cfg.fault_kill_spec()
            if kill is not None \
                    and georepl.region_of(cfg, kill[0]) == self.region:
                # region loss: every replica homed in the killed
                # server's region dies at its own first record >= epoch
                self._kill_at = kill[1]
            # boot the replay state machine (and compile its jit) BEFORE
            # the transport barrier, like the servers pre-compile
            self.follower = georepl.GeoFollower(cfg, self.me)
        self.tp = NativeTransport(self.me, endpoints, self.n_all,
                                  msg_size_max=cfg.msg_size_max,
                                  send_threads=cfg.send_thread_cnt,
                                  recv_threads=cfg.rem_thread_cnt)
        self.tp.start(int(setup_wait_s * 1000))
        if cfg.net_delay_us:
            self.tp.set_delay_us(int(cfg.net_delay_us))
        if self._geo and cfg.geo_wan_us:
            georepl.apply_wan_profile(self.tp, cfg, self.me)
        # flight recorder (runtime/telemetry.py — off by default): the
        # replica's per-epoch durability apply is an epoch-scoped event
        # (tag = -1) the txntrace merger joins to sampled txns by epoch
        self.tel = None
        if cfg.telemetry:
            from deneva_tpu.runtime.telemetry import FlightRecorder
            self.tel = FlightRecorder(cfg, self.me, "replica")
        self.log_path = os.path.join(cfg.log_dir,
                                     f"replica{self.me}.log.bin")
        os.makedirs(cfg.log_dir, exist_ok=True)
        self._f = open(self.log_path, "wb")
        self.stats = Stats()
        self.stop = False
        self._tl_last = 0.0
        self._tl_serve_last = 0.0

    def barrier(self) -> None:
        wire.run_barrier(self.tp, self.me, self.n_all, self._handle,
                         f"replica {self.me}", self.setup_wait_s)

    def _handle(self, src: int, rtype: str, payload: bytes) -> None:
        if rtype == "LOG_MSG":
            if self._fencing:
                from deneva_tpu.runtime import faildet
                ver, off = faildet.fence_peek(payload)
                if ver < self._fence_ver:
                    self._fence_nacks += 1
                    self.tp.send(src, "FENCE_NACK",
                                 faildet.encode_fence_nack(
                                     self._fence_ver, ver, -1))
                    return
                self._fence_ver = ver
                payload = payload[off:]
            _, epoch = _EPOCH_HDR.unpack_from(payload)
            if self._kill_at is not None and epoch >= self._kill_at:
                # region loss: die BEFORE appending the boundary record,
                # so the log stays clean to the previous boundary (the
                # same crash model as the server's fault_kill)
                if self.tel is not None:
                    self.tel.flush()   # events intact to the boundary
                os._exit(17)
            self._f.write(payload)
            self._f.flush()
            os.fsync(self._f.fileno())
            if self._geo:
                # quorum ack: durability watermark + the follower's
                # applied horizon (the primary's replica-lag ledger)
                self.follower.offer(payload)
                self.tp.send(src, "LOG_ACK", georepl.encode_log_ack(
                    epoch, self.follower.applied))
            else:
                self.tp.send(src, "LOG_RSP", wire.encode_shutdown(epoch))
            self.stats.incr("log_records")
            self.stats.incr("log_bytes", len(payload))
            if self.tel is not None:
                # replica-apply lifecycle hop: this epoch's record is
                # durable here (the ack above is what the primary's
                # quorum gate counts)
                self.tel.record_event(ST_APPLY, int(epoch))
                if self.tel.should_flush:
                    self.tel.flush()
        elif rtype == "REGION_READ":
            # follower snapshot read: serve the last applied group
            # boundary (consistent by construction — groups apply
            # atomically) with per-row version stamps off the ring
            tag, keys = georepl.decode_region_read(payload)
            boundary, values, vers = self.follower.serve(keys)
            self.tp.sendv(src, "REGION_READ_RSP",
                          georepl.region_read_rsp_parts(
                              tag, boundary, values, vers))
        elif rtype == "REJOIN":
            # crash-recovery: the restarted primary resumes at this epoch
            # boundary — drop any records past it (they were truncated
            # from the primary's log too, so the byte-prefix invariant
            # holds) and tell the primary what we last kept so it can
            # re-ship the gap from its own log
            from deneva_tpu.runtime.logger import truncate_log_to_epoch
            resume = wire.decode_shutdown(payload)
            self._f.flush()
            os.fsync(self._f.fileno())
            last = truncate_log_to_epoch(self.log_path, resume)
            self._f.seek(0, os.SEEK_END)
            if self._geo:
                self.follower.resync(self.log_path, resume)
            self.tp.send(src, "LOG_RSP", wire.encode_shutdown(last))
            self.stats.incr("rejoin_cnt")
        elif rtype == "SHUTDOWN":
            self.stop = True

    def _geo_emit(self) -> None:
        """Replication timeline spans after a group apply (under
        --debug_timeline).  Both ledgers are cumulative, so each line
        carries the DELTA since the previous emission — the trace
        export treats every value as an independent span duration."""
        if self.cfg.debug_timeline:
            f = self.follower
            apply_ms = (f.apply_s - self._tl_last) * 1e3
            self._tl_last = f.apply_s
            serve_ms = (f.serve_s - self._tl_serve_last) * 1e3
            self._tl_serve_last = f.serve_s
            print(f"[timeline] node={self.me} epoch={f.boundary} "
                  f"apply={apply_ms:.1f}ms "
                  f"follower_read={serve_ms:.1f}ms", flush=True)

    def run(self) -> Stats:
        self.barrier()
        t0 = time.monotonic()
        while not self.stop:
            # drain-first: acks and read serves must never queue behind
            # a group apply (a tick costs a group's worth of jit steps —
            # ack latency is the primary's quorum gate, so it stays
            # fsync-bound); the follower applies only on an empty queue,
            # one group per pass, and re-drains between groups
            m = self.tp.recv(0)
            if m:
                self._handle(*m)
                continue
            if self._geo and self.follower.tick():
                self._geo_emit()
                continue
            m = self.tp.recv(20_000)
            if m:
                self._handle(*m)
        if self._geo:
            # catch-up: apply every record the stream delivered (the
            # replica-lag scenario's convergence half), then leave the
            # verification sidecar + the [replication] summary line
            f = self.follower
            f.catch_up()
            f.write_sidecar(os.path.join(
                self.cfg.log_dir, f"replica{self.me}.follower.json"))
            print(georepl.replication_line(
                self.me, "follower", self.region, primary=f.primary,
                applied_epoch=f.applied,
                follower_read_cnt=f.rows_served,
                stale_read_max_epochs=f.stale_max,
                follower_read_ms=f.serve_s * 1e3,
                apply_ms=f.apply_s * 1e3), flush=True)
            self.stats.set("applied_epoch", float(f.applied))
            self.stats.set("follower_read_cnt", float(f.rows_served))
            self.stats.set("stale_read_max_epochs", float(f.stale_max))
            self.stats.set("geo_region", float(self.region))
        if self._fencing:
            self.stats.set("fence_nack_cnt", float(self._fence_nacks))
        if self.tel is not None:
            self.tel.flush()
            self.tel.summary_into(self.stats)
            print(telemetry_line(self.me, self.tel.fields()), flush=True)
        self._f.close()
        self.stats.set("total_runtime", time.monotonic() - t0)
        return self.stats

    def close(self) -> None:
        # idempotent, and safe after a failed barrier: release the log
        # file handle first, then the transport (teardown never leaves
        # an fsync racing a closed mesh)
        if not self._f.closed:
            self._f.close()
        self.tp.close()
