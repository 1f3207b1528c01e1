"""Live cluster observability plane: the metrics bus.

PR 13's flight recorder answers "where did this txn's latency go" —
post-hoc, from sidecars joined after the run ends.  This module is the
LIVE half: every node samples a per-epoch metrics frame (host counters
+ the per-partition conflict density the incidence matmuls already
compute for free, ``cc/base.conflict_density``) and ships it as a
METRICS message (rtype 25, outside ``FAULT_RTYPE_MASK``) to an
aggregator on the lowest-id live server.  The aggregator maintains
rolling cluster state and serves it two ways:

* ``metrics_bus_node*.jsonl`` — one JSON line per received frame,
  written through the SAME schema module as the flight recorder's
  per-epoch stream (runtime/metricschema.py), tailed live by
  ``tools/monitor.py`` (per-node TUI + ``--prom`` Prometheus text
  exposition dump);
* two analysis layers on the stream: per-group **critical-path
  attribution** (which stage — admit, wire, device, retire, quorum
  hold — gated the epoch boundary; ``[crit]`` tagged lines + a
  ``critpath`` Chrome-trace track in the declared registry) and
  **anomaly watchdogs** (epoch-stall, straggler-node transit skew vs
  the cluster median, jit-recompile spike detector) that emit
  structured ``[watch]`` events — into the stream AND the log — instead
  of burying gray failures in raw logs.

Contention-adaptive routing input: the per-epoch, per-partition
density series in the frames is exactly the observed-conflict signal
the ROADMAP's CC-router item needs (PAPERS: *DGCC* builds its protocol
on this dependency-graph signal; *Timestamp Granularity in OCC* argues
protocol/granularity choice should follow observed contention).

Loss model: frames are telemetry, lossy BY DESIGN — a frame sent to a
dead aggregator is a gap in a chart, never a correctness event.  The
rtype therefore sits outside the fault mask with the other gated
control-plane messages, and the aggregator role follows the lowest-id
LIVE server (a killed aggregator resumes its stream on recovery with
``append=True``; an elastically retired one hands the role to the next
lowest id, which lazily starts aggregating at its first received
frame).

With ``metrics=false`` (default) nothing here is constructed: no
frame, no rtype 25 on the wire, no ``[crit]``/``[watch]`` line, no
``metrics_bus_*.jsonl`` — every broadcast byte is bit-identical to the
pre-bus codecs (wire pin test in tests/test_metricsbus.py; gate
registry runtime/gates.py).
"""

from __future__ import annotations

import struct

import numpy as np

from deneva_tpu.runtime.metricschema import (MetricsStream, now_us,
                                             stream_dir)
from deneva_tpu.stats import tagged_line

MB_VERSION = 1
ROLE_SERVER, ROLE_CLIENT = 0, 1
ROLE_NAMES = ("server", "client")

# One frame = header + float32 counter vector + int32 density vector.
# Field NAMES are positional against this tuple (version-stamped in the
# header): decoders of a newer frame keep the prefix they know.
#
#   commit/abort/defer/salvage  this node's slice of the epoch's verdicts
#   shed                        admission NACKs sent since the last frame
#   pending/retry_depth         admission + retry queue depths
#   held_rsp                    CL_RSPs held at the group-commit gate
#   adm_depth                   bounded admission-queue depth
#   quorum_ms                   mean hold->release lag of acks released
#                               since the last frame (group-commit gate)
#   resend/backoff              client loss-repair + NACK re-entry counts
#   backlog                     client open-loop arrival backlog
#   admit/wire/device/retire/other_ms + wall_ms
#                               the LAST critical-path window's stage
#                               decomposition (CritLedger; sums to
#                               wall_ms by construction)
#   ctrl_gov/ctrl_qidx/ctrl_trips
#                               feedback-controller governor state
#                               (1=armed), admission quota-scale rung,
#                               cumulative stale trips (ctrl=true only;
#                               appended at the tail so older decoders
#                               keep their known prefix)
FRAME_FIELDS = (
    "commit", "abort", "defer", "salvage", "shed",
    "pending", "retry_depth", "held_rsp", "adm_depth", "quorum_ms",
    "resend", "backoff", "backlog",
    "admit_ms", "wire_ms", "device_ms", "retire_ms", "other_ms",
    "wall_ms",
    "ctrl_gov", "ctrl_qidx", "ctrl_trips",
)

_FHDR = struct.Struct("<hBBqqHH")   # node, role, version, epoch, t_us,
#                                     n_fields, n_density


def encode_metrics_frame(node: int, role: int, epoch: int, t_us: int,
                         fields: np.ndarray,
                         density: np.ndarray) -> bytes:
    """One METRICS frame.  ``fields`` is float32[F] positional against
    FRAME_FIELDS; ``density`` int32[P] per-partition conflict density
    (empty where the sender has none — clients, vote-mode servers)."""
    fields = np.ascontiguousarray(fields, np.float32)
    density = np.ascontiguousarray(density, np.int32)
    return (_FHDR.pack(node, role, MB_VERSION, epoch, t_us,
                       len(fields), len(density))
            + fields.tobytes() + density.tobytes())


def metrics_frame_parts(node: int, role: int, epoch: int, t_us: int,
                        fields: np.ndarray, density: np.ndarray) -> list:
    """METRICS as sendv parts; concatenated == encode_metrics_frame of
    the same columns (zero-copy contract, fuzzed in the registry
    round-trip test)."""
    fields = np.ascontiguousarray(fields, np.float32)
    density = np.ascontiguousarray(density, np.int32)
    return [_FHDR.pack(node, role, MB_VERSION, epoch, t_us,
                       len(fields), len(density)),
            fields, density]


def decode_metrics_frame(buf: bytes
                         ) -> tuple[int, int, int, int, np.ndarray,
                                    np.ndarray]:
    """(node, role, epoch, t_us, fields f32[F], density i32[P])."""
    node, role, _ver, epoch, t_us, nf, nd = _FHDR.unpack_from(buf)
    fields = np.frombuffer(buf, np.float32, count=nf,
                           offset=_FHDR.size)
    density = np.frombuffer(buf, np.int32, count=nd,
                            offset=_FHDR.size + 4 * nf)
    return node, role, epoch, t_us, fields, density


def named_record(node: int, role: int, epoch: int, t_us: int,
                 fields: np.ndarray, density: np.ndarray) -> dict:
    """Positional frame columns -> the JSONL record shape the
    aggregator streams.  THE one builder (the wire decode path and the
    local-feed path both call it, so the two record shapes cannot
    drift): unknown tail positions of a NEWER sender are dropped,
    missing ones of an older sender read 0 — the same ignore-unknown
    compat posture as the tagged-line parsers."""
    rec = {"node": node, "role": ROLE_NAMES[role]
           if role < len(ROLE_NAMES) else str(role),
           "epoch": epoch, "frame_t_us": t_us}
    for i, name in enumerate(FRAME_FIELDS):
        rec[name] = float(fields[i]) if i < len(fields) else 0.0
    if len(density):
        rec["density"] = [int(x) for x in density]
    return rec


def frame_record(buf: bytes) -> dict:
    """Decode a frame payload into its JSONL record."""
    return named_record(*decode_metrics_frame(buf))


def pack_fields(d: dict) -> np.ndarray:
    """dict -> positional float32 vector (unknown keys are a bug: the
    field list is the wire contract)."""
    out = np.zeros(len(FRAME_FIELDS), np.float32)
    for k, v in d.items():
        out[FRAME_FIELDS.index(k)] = v
    return out


def bus_path(cfg, node: int) -> str:
    import os
    return os.path.join(stream_dir(cfg), f"metrics_bus_node{node}.jsonl")


def crit_line(node: int, fields: dict) -> str:
    """``[crit]`` critical-path attribution line (parsed by
    ``harness.parse.parse_metrics`` under the standard ignore-unknown-
    tags forward/backward-compat contract)."""
    return tagged_line("crit", {"node": node, **fields})


def watch_line(node: int, fields: dict) -> str:
    """``[watch]`` anomaly watchdog event line (same parse contract)."""
    return tagged_line("watch", {"node": node, **fields})


# ---- critical-path attribution ----------------------------------------

# emit cadence for [crit] lines: accumulate stage time across dispatch
# passes and attribute once per window, so a fast chip (ms-scale groups)
# does not print thousands of lines per second
CRIT_EMIT_S = 1.0

CRIT_STAGES = ("admit", "wire", "device", "retire", "other")


class CritLedger:
    """Wall-time decomposition of the server's dispatch loop.

    The loop marks stage boundaries (``lap``) each pass: admit
    (contribution assembly + admission), wire (the blob-collect wait),
    device (feed build + dispatch), retire (verdict retirement).
    Everything unmarked lands in ``other`` at window close, so the
    stages SUM TO THE MEASURED WALL TIME by construction (the
    acceptance's 5% bound is measurement noise, not bookkeeping slack).
    ``quorum_ms`` rides beside the wall stages as a latency LEDGER (the
    mean hold->release lag of acks released in the window — overlapped
    time, never part of the wall sum) and competes for the ``gate``
    attribution: a group whose acks waited out durability longer than
    any loop stage ran is quorum-gated.
    """

    def __init__(self, node: int):
        import time
        self._time = time.monotonic
        self.node = node
        t = self._time()
        self._t_mark = t            # last lap boundary
        self._t_win = t             # window start
        self._next_emit = t + CRIT_EMIT_S
        self.stage_s = {s: 0.0 for s in CRIT_STAGES}
        self.quorum_s = 0.0
        self.quorum_n = 0
        self.last: dict[str, float] = {s + "_ms": 0.0
                                       for s in CRIT_STAGES}
        self.last["wall_ms"] = 0.0
        self.last["quorum_ms"] = 0.0
        self.crit_cnt = 0

    def reset(self) -> None:
        """Re-anchor both clocks (run start: compile/barrier time is
        setup, not epoch wall) and drop any accumulated stage time."""
        t = self._time()
        self._t_mark = t
        self._t_win = t
        self._next_emit = t + CRIT_EMIT_S
        self.stage_s = {s: 0.0 for s in CRIT_STAGES}
        self.quorum_s, self.quorum_n = 0.0, 0

    def lap(self, stage: str, now: float | None = None) -> None:
        if now is None:
            now = self._time()
        self.stage_s[stage] += now - self._t_mark
        self._t_mark = now

    def quorum(self, lag_s: float) -> None:
        self.quorum_s += lag_s
        self.quorum_n += 1

    def end_pass(self, epoch: int) -> tuple[str, float] | None:
        """Close a dispatch pass; at the emit cadence, attribute the
        window: print the [crit] line, remember the decomposition for
        the next frames, return (gate_stage, gate_seconds) so the
        caller can lay the critpath Chrome-trace span.  Returns None
        between emits."""
        now = self._time()
        self.stage_s["other"] += now - self._t_mark
        self._t_mark = now
        if now < self._next_emit:
            return None
        self._next_emit = now + CRIT_EMIT_S
        wall = now - self._t_win
        self._t_win = now
        q_ms = (self.quorum_s / self.quorum_n * 1e3) if self.quorum_n \
            else 0.0
        fields: dict[str, float] = {"epoch": epoch}
        gate, gate_s = "other", -1.0
        for s in CRIT_STAGES:
            v = self.stage_s[s]
            fields[s + "_ms"] = round(v * 1e3, 3)
            if v > gate_s:
                gate, gate_s = s, v
        if q_ms / 1e3 > gate_s:
            gate, gate_s = "quorum", q_ms / 1e3
        fields["quorum_ms"] = round(q_ms, 3)
        fields["wall_ms"] = round(wall * 1e3, 3)
        fields["gate"] = gate
        self.last = {k: v for k, v in fields.items()
                     if k.endswith("_ms")}
        print(crit_line(self.node, fields), flush=True)
        self.crit_cnt += 1
        self.stage_s = {s: 0.0 for s in CRIT_STAGES}
        self.quorum_s, self.quorum_n = 0.0, 0
        return gate, gate_s


# ---- sender ------------------------------------------------------------

CLIENT_FRAME_US = 250_000       # client frame cadence (no epochs to key on)


class BusSender:
    """Per-node frame assembly + summary accounting (servers key frames
    on the epoch cadence, clients on wall time).  Owned by the node's
    dispatch thread like every host counter."""

    def __init__(self, cfg, node: int, role: int):
        self.cfg = cfg
        self.node = node
        self.role = role
        self.cadence = max(1, cfg.metrics_cadence)
        self.frames_sent = 0
        self.crit = CritLedger(node)
        self.density_sum = np.zeros(max(cfg.part_cnt, 1), np.int64)
        self.shed = 0               # admission NACKs since last frame
        self._hold_t: dict[int, float] = {}   # epoch -> hold start
        self._next_client_us = 0

    # group-commit hold->release lag (the generic twin of the geo
    # quorum ledger: armed by metrics alone, geo or not)
    def hold(self, epoch: int, now_s: float) -> None:
        self._hold_t.setdefault(epoch, now_s)

    def release_through(self, epoch: int, now_s: float) -> None:
        for e in [e for e in self._hold_t if e <= epoch]:
            self.crit.quorum(now_s - self._hold_t.pop(e))

    def due(self, epoch: int) -> bool:
        return epoch % self.cadence == 0

    def client_due(self, t_us: int) -> bool:
        if t_us < self._next_client_us:
            return False
        self._next_client_us = t_us + CLIENT_FRAME_US
        return True

    def frame(self, epoch: int, counters: dict,
              density: np.ndarray | None = None
              ) -> tuple[list, dict]:
        """Build one frame: (sendv parts, decoded record).  The record
        is what a local aggregator feeds directly — same bytes, no
        decode round-trip."""
        fields = dict(counters)
        fields["shed"] = self.shed
        self.shed = 0
        fields.update(self.crit.last)
        t_us = now_us()
        if density is None:
            density = np.zeros(0, np.int32)
        else:
            density = np.ascontiguousarray(density, np.int32)
            self.density_sum[:len(density)] += density
        vec = pack_fields(fields)
        parts = metrics_frame_parts(self.node, self.role, epoch, t_us,
                                    vec, density)
        rec = named_record(self.node, self.role, epoch, t_us, vec,
                           density)
        self.frames_sent += 1
        return parts, rec

    def summary_into(self, st) -> None:
        st.set("mb_frames_sent", float(self.frames_sent))
        if self.role == ROLE_SERVER:
            st.set("mb_crit_cnt", float(self.crit.crit_cnt))
            for i, d in enumerate(self.density_sum):
                st.set(f"mb_density_p{i}", float(d))


# ---- aggregator + watchdogs --------------------------------------------

# watchdog thresholds (module constants, not config: observability
# heuristics, tuned against the chaos scenarios — the config surface
# stays the one `metrics` flag + the cadence knob)
WATCH_STRAGGLER_FLOOR_US = 250_000   # min transit lag to call straggler
WATCH_STRAGGLER_FACTOR = 8.0         # ... and vs the cluster median
WATCH_STALL_S = 3.0                  # cluster-wide frame silence
WATCH_JIT_FLOOR_MS = 50.0            # min device-stage spike
WATCH_JIT_FACTOR = 10.0              # ... vs the node's rolling median
WATCH_MIN_FRAMES = 3                 # frames before a node is judged
WATCH_EMIT_EVERY_S = 1.0             # per-(kind, subject) rate limit
_HIST = 32                           # rolling window per node


class Aggregator:
    """Rolling cluster state + watchdogs on the lowest-id live server.

    ``feed`` takes one decoded frame record: append it to the
    ``metrics_bus_node*.jsonl`` stream (the flight-recorder schema
    module), update the per-node rolling windows, and run the
    frame-triggered watchdogs.  ``tick`` runs the silence watchdog from
    the owner's loop.  Watch events are emitted twice on purpose: a
    ``[watch]`` tagged line (greppable, parse_metrics) and a structured
    record in the stream (kind="watch" — what the chaos oracle and the
    TUI read)."""

    def __init__(self, cfg, node: int, append: bool = False):
        from collections import deque
        self.cfg = cfg
        self.node = node
        self.stream = MetricsStream(bus_path(cfg, node), node,
                                    append=append)
        self.frames_rx = 0
        self.watch_cnt = 0
        self._deque = deque
        # node -> rolling ledgers
        self._lag_us: dict[int, object] = {}
        self._dev_ms: dict[int, object] = {}
        self._epoch: dict[int, int] = {}
        self._last_rx_s: float | None = None
        self._stalled = False
        self._mute_until: dict[tuple[str, int], float] = {}

    # -- feeding ---------------------------------------------------------
    def feed(self, rec: dict, now_s: float | None = None) -> None:
        import time
        now_s = time.monotonic() if now_s is None else now_s
        node = int(rec.get("node", -1))
        lag_us = now_s * 1e6 - float(rec.get("frame_t_us", 0))
        self.stream.emit(int(rec.get("epoch", -1)), node=node,
                         **{k: v for k, v in rec.items()
                            if k not in ("node", "epoch")})
        self.frames_rx += 1
        self._last_rx_s = now_s
        if self._stalled:
            self._stalled = False
        if rec.get("role") == "server":
            # straggler judgment covers the CLUSTER MEMBERS: a client
            # is a load generator whose sparse wall-cadence frames can
            # arrive in stale bursts after an aggregator failover (they
            # queue toward the dead socket), which is not a gray-slow
            # server
            self._lag_us.setdefault(node, self._deque(maxlen=_HIST)) \
                .append(lag_us)
            if float(rec.get("device_ms", 0.0)) > 0.0:
                # frames before the first crit window carry zero stage
                # ms; a zero median would read the first real window
                # as a recompile spike
                self._dev_ms.setdefault(node, self._deque(maxlen=_HIST)) \
                    .append(float(rec.get("device_ms", 0.0)))
            self._epoch[node] = max(self._epoch.get(node, -1),
                                    int(rec.get("epoch", -1)))
            self._watch_straggler(node, now_s)
            self._watch_jit(node, rec, now_s)

    def tick(self, now_s: float) -> None:
        """Cluster-wide silence watchdog (called from the owner's
        loop) + a stream flush so the live TUI tails fresh lines."""
        self.stream.flush()
        if self._last_rx_s is None or self._stalled:
            return
        idle = now_s - self._last_rx_s
        if idle > WATCH_STALL_S:
            self._stalled = True
            self._emit(now_s, "epoch_stall", -1,
                       idle_s=round(idle, 2),
                       epoch=max(self._epoch.values(), default=-1))

    # -- watchdogs -------------------------------------------------------
    def _emit(self, now_s: float, kind: str, subject: int,
              **fields) -> None:
        key = (kind, subject)
        if now_s < self._mute_until.get(key, 0.0):
            return
        self._mute_until[key] = now_s + WATCH_EMIT_EVERY_S
        self.watch_cnt += 1
        rec = {"kind": kind, "subject": subject, **fields}
        print(watch_line(self.node, rec), flush=True)
        rec.pop("epoch", None)   # the stream record carries it already
        self.stream.emit(int(fields.get("epoch", -1)), node=self.node,
                         **rec)

    def _watch_straggler(self, node: int, now_s: float) -> None:
        """Gray-slow skew: a node whose frame TRANSIT lag (arrival time
        minus the frame's own CLOCK_MONOTONIC stamp — shared on a
        single box) sits far above the cluster median.  Socket-level
        death never trips this; a stalled-but-alive link is exactly
        what it sees.  The subject's statistic is the window MINIMUM:
        a stalled outbound link delays EVERY frame, while a healthy
        node whose queued frames flush in a stale burst after an
        aggregator failover still has fresh low-lag frames in its
        window — the min rejects the burst, the median would not."""
        mine = self._lag_us.get(node)
        if mine is None or len(mine) < WATCH_MIN_FRAMES:
            return
        others = [float(np.median(v)) for n, v in self._lag_us.items()
                  if n != node and len(v) >= WATCH_MIN_FRAMES]
        if not others:
            return
        lag_mine = float(np.min(mine))
        med_rest = float(np.median(np.asarray(others)))
        if lag_mine > max(WATCH_STRAGGLER_FLOOR_US,
                          WATCH_STRAGGLER_FACTOR * med_rest):
            self._emit(now_s, "straggler", node,
                       lag_ms=round(lag_mine / 1e3, 1),
                       cluster_ms=round(med_rest / 1e3, 1),
                       epoch=self._epoch.get(node, -1))

    def _watch_jit(self, node: int, rec: dict, now_s: float) -> None:
        """Recompile detector: a one-off device-stage spike far above
        the node's own rolling median after warmup — the signature of a
        mid-run re-jit (shape change, cache eviction)."""
        cur = float(rec.get("device_ms", 0.0))
        hist = self._dev_ms.get(node)
        if cur <= 0.0 or hist is None \
                or len(hist) < WATCH_MIN_FRAMES + 1:
            return
        med = float(np.median(np.asarray(hist)[:-1]))
        if cur > max(WATCH_JIT_FLOOR_MS, WATCH_JIT_FACTOR * max(med, 1e-3)):
            self._emit(now_s, "jit_recompile", node,
                       device_ms=round(cur, 1),
                       median_ms=round(med, 1),
                       epoch=int(rec.get("epoch", -1)))

    # -- reporting -------------------------------------------------------
    def summary_into(self, st) -> None:
        st.set("mb_frames_rx", float(self.frames_rx))
        st.set("mb_watch_cnt", float(self.watch_cnt))
        st.set("mb_bus_lines", float(self.stream.lines))

    def close(self) -> None:
        self.stream.close()
