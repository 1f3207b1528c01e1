"""ctypes bindings for the native host runtime (``native/`` C++ library).

pybind11 is not in the image, so the boundary is a plain C API
(`native/include/deneva_host.h`) loaded with ctypes; numpy arrays cross
zero-copy via ``ndarray.ctypes``.  ``make`` decides whether the library
is stale (the reference rebuilds per config via
`scripts/run_experiments.py:83-96`; config is runtime state here).  This
module imports only numpy and ctypes, so a launcher's parent can build
the library before any node exists without touching JAX.
"""

from __future__ import annotations

import ctypes as C
import fcntl
import os
import shutil
import struct
import subprocess
import tempfile
import threading
import zlib

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE = os.path.join(_ROOT, "native")
_LIB = os.path.join(_NATIVE, "build", "libdeneva_host.so")

_lock = threading.Lock()
_lib: C.CDLL | None = None

RTYPE = {
    "INIT_DONE": 1, "CL_QRY_BATCH": 2, "CL_RSP": 3, "RDONE": 4,
    "EPOCH_BLOB": 5, "LOG_MSG": 6, "LOG_RSP": 7, "PING": 8, "PONG": 9,
    "SHUTDOWN": 10, "MEASURE": 11, "VOTE": 12, "VOTE2": 13, "REJOIN": 14,
    # elastic membership (runtime/membership.py): rebalance announcement,
    # row migration stream, and the client-facing map install / redirect
    # NACK.  Deliberately OUTSIDE FAULT_RTYPE_MASK: the migration stream
    # is control plane, like the epoch exchange — its fault mode is
    # process death, not silent loss.
    "MIGRATE_BEGIN": 15, "MIGRATE_ROWS": 16, "MAP_UPDATE": 17,
    # geo-replication tier (runtime/replication.py): quorum durability
    # ack (replica -> primary, replaces LOG_RSP in geo mode and adds the
    # follower's applied horizon), and the follower snapshot-read pair
    # (client <-> replica).  Deliberately OUTSIDE FAULT_RTYPE_MASK like
    # rtypes 15-17: the quorum ack is the commit protocol itself, and
    # follower reads are best-effort control-plane traffic the client
    # re-issues from its own outstanding ledger — neither has the
    # resend+idempotent-admission story the fault mask encodes.
    "LOG_ACK": 18, "REGION_READ": 19, "REGION_READ_RSP": 20,
    # overload tier (runtime/admission.py): per-tenant admission NACK
    # (server -> client, tags + retry-after hints).  Deliberately
    # OUTSIDE FAULT_RTYPE_MASK: a lost NACK self-heals through the
    # client's resend sweep (the unacked query is re-offered and
    # re-NACKed or admitted), so it needs no loss story of its own —
    # and faulting it would only re-test the CL_QRY_BATCH path.
    "ADMIT_NACK": 21,
    # partition & gray-failure tolerance (runtime/faildet.py): per-link
    # liveness + ack-lease grants, stale-incarnation rejection, and
    # post-partition map catch-up.  Deliberately OUTSIDE FAULT_RTYPE_MASK
    # like every control-plane rtype since 15: a heartbeat is re-sent on
    # its cadence, a FENCE_NACK is re-triggered by the next stale frame,
    # and HEAL rides the heal transition — their fault mode is the
    # partition itself, never silent single-frame loss.
    "HEARTBEAT": 22, "FENCE_NACK": 23, "HEAL": 24,
    # live metrics bus (runtime/metricsbus.py): per-epoch metrics frame,
    # node -> lowest-id live server (the aggregator).  Deliberately
    # OUTSIDE FAULT_RTYPE_MASK like every gated rtype since 15 — frames
    # are telemetry, lossy BY DESIGN: a dropped frame is a gap in a
    # chart, never a correctness event, and the next cadence tick
    # supersedes it.
    "METRICS": 25,
}
RTYPE_NAME = {v: k for k, v in RTYPE.items()}

STAT_NAMES = ("msg_sent", "msg_rcvd", "bytes_sent", "bytes_rcvd",
              "batches_sent", "send_queue_depth", "recv_queue_depth",
              "msg_dropped", "msg_dup", "reconnects", "msg_blackholed")

# Fault-eligible message classes (chaos harness): only the client<->server
# open-loop traffic may be dropped/duplicated/jittered — it has an
# end-to-end retry story (client resend + server idempotent admission).
# The server<->server epoch exchange and log shipping are the commit
# protocol itself; their fault mode is process death + recovery, not
# silent message loss (dropping an EPOCH_BLOB models a dead link, which
# IS the dead-peer/kill scenario).
FAULT_RTYPE_MASK = (1 << RTYPE["CL_QRY_BATCH"]) | (1 << RTYPE["CL_RSP"])


def ensure_built(force: bool = False) -> str:
    """Build ``libdeneva_host.so`` if ``make`` finds it missing or stale;
    return its path.  ``native/build/`` is not part of a checkout, so the
    first process of a clean tree builds it — under a FILE lock, because
    nodes are processes: a second caller waits and then finds the work
    done, and the Makefile moves the finished library into place, so
    nobody ever loads a half-written one."""
    for tool in ("make", os.environ.get("CXX", "g++")):
        if shutil.which(tool) is None:
            if os.path.exists(_LIB) and not force:
                return _LIB     # prebuilt library, no toolchain: use it
            raise RuntimeError(
                f"native build needs {tool!r} on PATH and it is missing "
                f"(building {_LIB})")
    os.makedirs(os.path.dirname(_LIB), exist_ok=True)
    with open(os.path.join(os.path.dirname(_LIB), ".lock"), "w") as lk:
        fcntl.flock(lk, fcntl.LOCK_EX)
        cmd = ["make", "-C", _NATIVE] + (["-B"] if force else [])
        proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"native build failed:\n{proc.stderr}")
    return _LIB


def _load() -> C.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            try:
                lib = C.CDLL(ensure_built())
            except OSError:
                # stale artifact from another arch/toolchain: rebuild
                lib = C.CDLL(ensure_built(force=True))
            lib.dt_create.restype = C.c_void_p
            lib.dt_create.argtypes = [C.c_uint32, C.c_char_p, C.c_uint32,
                                      C.c_uint32, C.c_uint32]
            lib.dt_start.restype = C.c_int
            lib.dt_start.argtypes = [C.c_void_p, C.c_int]
            lib.dt_set_io_threads.restype = C.c_int
            lib.dt_set_io_threads.argtypes = [C.c_void_p, C.c_uint32,
                                              C.c_uint32]
            lib.dt_send.restype = C.c_int
            lib.dt_send.argtypes = [C.c_void_p, C.c_uint32, C.c_uint16,
                                    C.c_void_p, C.c_uint32]
            lib.dt_sendv.restype = C.c_int
            lib.dt_sendv.argtypes = [C.c_void_p, C.c_uint32, C.c_uint16,
                                     C.c_void_p, C.c_uint32]
            lib.dt_recv.restype = C.c_long
            lib.dt_recv.argtypes = [C.c_void_p, C.c_void_p, C.c_uint32,
                                    C.POINTER(C.c_uint32),
                                    C.POINTER(C.c_uint16), C.c_long,
                                    C.POINTER(C.c_uint32)]
            lib.dt_flush.argtypes = [C.c_void_p]
            lib.dt_set_delay_us.argtypes = [C.c_void_p, C.c_uint64]
            lib.dt_set_peer_delay_us.restype = C.c_int
            lib.dt_set_peer_delay_us.argtypes = [C.c_void_p, C.c_uint32,
                                                 C.c_uint64]
            lib.dt_set_partition.restype = C.c_int
            lib.dt_set_partition.argtypes = [C.c_void_p, C.c_uint32,
                                             C.c_uint32]
            lib.dt_set_peer_stall_us.restype = C.c_int
            lib.dt_set_peer_stall_us.argtypes = [C.c_void_p, C.c_uint32,
                                                 C.c_uint64]
            lib.dt_set_fault.restype = C.c_int
            lib.dt_set_fault.argtypes = [C.c_void_p, C.c_uint32,
                                         C.c_uint32, C.c_uint64,
                                         C.c_uint64, C.c_uint32]
            lib.dt_set_rejoin.restype = C.c_int
            lib.dt_set_rejoin.argtypes = [C.c_void_p, C.c_int]
            lib.dt_stats.argtypes = [C.c_void_p, C.POINTER(C.c_uint64)]
            lib.dt_peer_alive.restype = C.c_int
            lib.dt_peer_alive.argtypes = [C.c_void_p, C.c_uint32]
            lib.dt_ping.restype = C.c_long
            lib.dt_ping.argtypes = [C.c_void_p, C.c_uint32, C.c_uint32,
                                    C.c_uint32]
            lib.dt_destroy.argtypes = [C.c_void_p]
            lib.dt_qrybatch_encode.restype = C.c_long
            lib.dt_qrybatch_encode.argtypes = [
                C.c_uint32, C.c_uint32, C.c_uint32, C.c_void_p, C.c_void_p,
                C.c_void_p, C.c_void_p, C.c_void_p, C.c_size_t]
            lib.dt_qrybatch_decode.restype = C.c_long
            lib.dt_qrybatch_decode.argtypes = [
                C.c_void_p, C.c_size_t, C.POINTER(C.c_uint32),
                C.POINTER(C.c_uint32), C.POINTER(C.c_uint32), C.c_void_p,
                C.c_void_p, C.c_void_p, C.c_void_p, C.c_size_t]
            _lib = lib
    return _lib


def ipc_endpoints(n_nodes: int, run_id: str, base_dir: str = "/tmp") -> str:
    """Endpoint table for same-host IPC runs (`ifconfig.txt` +
    `ipc://node_N.ipc`, `transport/transport.cpp:132-133`): one socket a
    node in ``base_dir``, or in the temp dir where ``base_dir`` is too
    deep for a socket's address."""
    paths = [f"{base_dir}/dt_{run_id}_n{i}.sock" for i in range(n_nodes)]
    if len(paths[-1]) > 100:
        # a deep run directory (pytest's ``tmp_path`` under a long
        # TMPDIR, a run id that holds a pid of six digits): the sockets
        # move up into the temp dir, named after the run directory too —
        # whether a launch starts must not hang on a path's last byte
        tag = f"{run_id}_{zlib.crc32(base_dir.encode()):08x}"
        paths = [f"{tempfile.gettempdir()}/dt_{tag}_n{i}.sock"
                 for i in range(n_nodes)]
    if len(paths[-1]) > 100:
        # sockaddr_un.sun_path holds 108 bytes; a longer path would be
        # cut to the same prefix for every node
        raise ValueError(f"IPC socket path too long ({len(paths[-1])} > "
                         f"100): {paths[-1]}")
    return "".join(f"{i} ipc {p}\n" for i, p in enumerate(paths))


def tcp_endpoints(n_nodes: int, base_port: int = 17000,
                  host: str = "127.0.0.1") -> str:
    return "".join(f"{i} tcp {host}:{base_port + i}\n"
                   for i in range(n_nodes))


# dt_iov mirrored as a numpy record: building ONE structured array and
# passing its base pointer costs ~1 us per sendv, where per-part ctypes
# objects measured ~10 us each — at cluster blob sizes the wrapper
# overhead would have eaten the copy savings
_IOV_DT = np.dtype([("base", np.uint64), ("len", np.uint64)])


def _iov_parts(parts) -> tuple[list, np.ndarray]:
    """(live refs, iov record array) for ``dt_sendv``.

    Accepts ``bytes``/``bytearray`` and numpy arrays (contiguified if
    needed); the native side copies every segment into its frame before
    returning, so the memory only has to stay alive for the call — the
    refs list pins it that long."""
    refs = []
    bases = []
    lens = []
    for p in parts:
        if isinstance(p, (bytes, bytearray)):
            p = np.frombuffer(p, np.uint8)
        elif not (isinstance(p, np.ndarray) and p.flags["C_CONTIGUOUS"]):
            p = np.ascontiguousarray(p)
        refs.append(p)
        bases.append(p.__array_interface__["data"][0])
        lens.append(p.nbytes)
    iov = np.empty(len(refs), _IOV_DT)
    iov["base"] = bases
    iov["len"] = lens
    return refs, iov


class NativeTransport:
    """One node's handle on the mesh (reference `Transport`,
    `transport/transport.cpp:171`)."""

    def __init__(self, node_id: int, endpoints: str, n_nodes: int,
                 msg_size_max: int = 4096, flush_timeout_us: int = 200,
                 send_threads: int = 1, recv_threads: int = 1,
                 rejoin: bool = False):
        self._lib = _load()
        self._h = self._lib.dt_create(node_id, endpoints.encode(), n_nodes,
                                      msg_size_max, flush_timeout_us)
        if not self._h:
            raise RuntimeError("dt_create failed (bad endpoint table?)")
        if send_threads > 1 or recv_threads > 1:
            # reference SEND_THREAD_CNT / REM_THREAD_CNT axes
            if self._lib.dt_set_io_threads(self._h, send_threads,
                                           recv_threads) != 0:
                raise RuntimeError("dt_set_io_threads must precede start")
        if rejoin:
            # crash-recovery restart: dt_start dials every live peer
            # instead of the bind/connect split (they accept mid-run)
            if self._lib.dt_set_rejoin(self._h, 1) != 0:
                raise RuntimeError("dt_set_rejoin must precede start")
        self.node_id = node_id
        self.n_nodes = n_nodes
        self._recv_buf = np.empty(1 << 20, np.uint8)

    def start(self, timeout_ms: int = 120000) -> None:
        # a chip-backed peer loads (and jit-compiles the loader of) its
        # table BEFORE starting its transport, and CPU peers must keep
        # dialing until it shows up: under the launcher the limit is the
        # launcher's own (wire.SETUP_WAIT_S explains)
        if self._lib.dt_start(self._h, timeout_ms) != 0:
            raise RuntimeError(
                f"node {self.node_id}: a peer did not connect within "
                f"{timeout_ms / 1000:.0f} s (transport mesh setup)")

    def send(self, dest: int, rtype: int | str, payload: bytes | np.ndarray
             = b"") -> None:
        if isinstance(rtype, str):
            rtype = RTYPE[rtype]
        if isinstance(payload, bytes):
            rc = self._lib.dt_send(self._h, dest, rtype, payload,
                                   len(payload))
        else:
            # zero-copy: the native side frames from the array's memory
            # before returning (no .tobytes() round trip)
            a = payload if payload.flags["C_CONTIGUOUS"] \
                else np.ascontiguousarray(payload)
            rc = self._lib.dt_send(
                self._h, dest, rtype,
                C.c_void_p(a.__array_interface__["data"][0]), a.nbytes)
            del a
        if rc != 0:
            raise RuntimeError(f"send to {dest} failed")

    def sendv(self, dest: int, rtype: int | str, parts) -> None:
        """Scatter-send: the message body is the concatenation of
        ``parts`` (bytes / numpy arrays), framed once in the native
        layer — the Python side never builds the contiguous payload
        (`dt_sendv`, the writev-shaped fast path)."""
        self.sendv_many((dest,), rtype, parts)

    def sendv_many(self, dests, rtype: int | str, parts) -> None:
        """``sendv`` to several destinations: the iov table is built
        once and reused per dest (the server's blob broadcast — N-1
        peers, identical body)."""
        if isinstance(rtype, str):
            rtype = RTYPE[rtype]
        refs, iov = _iov_parts(parts)
        pv = C.c_void_p(iov.__array_interface__["data"][0])
        n = len(refs)
        for d in dests:
            if self._lib.dt_sendv(self._h, d, rtype, pv, n) != 0:
                raise RuntimeError(f"sendv to {d} failed")
        del refs

    def recv(self, timeout_us: int = -1) -> tuple[int, str, bytes] | None:
        """(src, rtype_name, payload) or None on timeout."""
        src = C.c_uint32()
        rt = C.c_uint16()
        need = C.c_uint32()
        while True:
            n = self._lib.dt_recv(
                self._h, self._recv_buf.ctypes.data_as(C.c_void_p),
                len(self._recv_buf), C.byref(src), C.byref(rt), timeout_us,
                C.byref(need))
            if n == -1:
                return None
            if n == -2:
                self._recv_buf = np.empty(int(need.value) * 2, np.uint8)
                continue
            return (src.value, RTYPE_NAME.get(rt.value, str(rt.value)),
                    self._recv_buf[:n].tobytes())

    def flush(self) -> None:
        """Block until everything sent so far is on the wire (bounded 1s)."""
        self._lib.dt_flush(self._h)

    def set_delay_us(self, us: int) -> None:
        self._lib.dt_set_delay_us(self._h, us)

    def set_peer_delay_us(self, peer: int, us: int) -> None:
        """Per-link extra send delay (geo WAN profiles; adds on top of
        the global delay — `runtime/replication.py` drives it from the
        region distance matrix)."""
        if self._lib.dt_set_peer_delay_us(self._h, peer, int(us)) != 0:
            raise RuntimeError(f"set_peer_delay_us({peer}) failed")

    # partition blackhole directions (native dt_part_mode)
    PART_NONE = 0
    PART_TX = 1
    PART_RX = 2

    def set_partition(self, peer: int, mode: int) -> None:
        """Per-link partition blackhole (chaos partition scenarios):
        PART_TX discards frames we send to ``peer``, PART_RX frames
        arriving from it — every rtype, but the sockets stay open so
        ``peer_alive`` keeps reporting True (the gray failure only the
        fencing layer's suspicion score can see).  0 heals the link."""
        if self._lib.dt_set_partition(self._h, peer, int(mode)) != 0:
            raise RuntimeError(f"set_partition({peer}) failed")

    def set_peer_stall_us(self, peer: int, us: int) -> None:
        """Gray-slow peer: extra per-link outbound stall, additive with
        the global/WAN delays (a fault knob, kept separate from the geo
        topology profile so scenarios compose)."""
        if self._lib.dt_set_peer_stall_us(self._h, peer, int(us)) != 0:
            raise RuntimeError(f"set_peer_stall_us({peer}) failed")

    def set_fault(self, drop_prob: float = 0.0, dup_prob: float = 0.0,
                  jitter_us: float = 0.0, seed: int = 0,
                  rtype_mask: int = FAULT_RTYPE_MASK) -> None:
        """Seeded drop/dup/jitter injection on the fault-eligible message
        classes (chaos harness; all-zero disables)."""
        self._lib.dt_set_fault(
            self._h, int(drop_prob * 1_000_000), int(dup_prob * 1_000_000),
            int(jitter_us), seed & (2**64 - 1), rtype_mask)

    def peer_alive(self, peer: int) -> bool:
        """Link-level failure detection (the reference has none: its
        heartbeat body is commented out, `system/thread.cpp:28-41`)."""
        return bool(self._lib.dt_peer_alive(self._h, peer))

    def stats(self) -> dict[str, int]:
        out = (C.c_uint64 * len(STAT_NAMES))()
        self._lib.dt_stats(self._h, out)
        return dict(zip(STAT_NAMES, [int(v) for v in out]))

    def ping(self, peer: int, rounds: int = 10) -> float:
        """Mean round-trip in microseconds (NETWORK_TEST)."""
        ns = self._lib.dt_ping(self._h, peer, rounds, 8)
        if ns < 0:
            raise RuntimeError(f"ping {peer} failed")
        return ns / 1000.0

    def close(self) -> None:
        if self._h:
            self._lib.dt_destroy(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ---- columnar query batches -------------------------------------------

def encode_qrybatch(startts: np.ndarray, keys: np.ndarray,
                    types: np.ndarray, scalars: np.ndarray | None = None
                    ) -> bytes:
    """CL_QRY batch -> wire bytes (columnar; server feeds these straight
    into the device pool refill)."""
    lib = _load()
    n, width = keys.shape
    startts = np.ascontiguousarray(startts, np.int64)
    keys = np.ascontiguousarray(keys, np.int32)
    types = np.ascontiguousarray(types, np.int8)
    if scalars is None:
        scalars = np.zeros((n, 0), np.int32)
    scalars = np.ascontiguousarray(scalars, np.int32)
    n_scalars = scalars.shape[1] if scalars.ndim == 2 else 0
    need = lib.dt_qrybatch_encode(n, width, n_scalars, None, None, None,
                                  None, None, 0)
    out = np.empty(need, np.uint8)
    rc = lib.dt_qrybatch_encode(
        n, width, n_scalars,
        startts.ctypes.data_as(C.c_void_p), keys.ctypes.data_as(C.c_void_p),
        types.ctypes.data_as(C.c_void_p),
        scalars.ctypes.data_as(C.c_void_p),
        out.ctypes.data_as(C.c_void_p), need)
    if rc < 0:
        raise RuntimeError("qrybatch encode failed")
    return out.tobytes()


def decode_qrybatch(buf: bytes) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                         np.ndarray]:
    """Wire bytes -> (startts[n], keys[n,w], types[n,w], scalars[n,s])."""
    lib = _load()
    n = C.c_uint32()
    w = C.c_uint32()
    s = C.c_uint32()
    rc = lib.dt_qrybatch_decode(buf, len(buf), C.byref(n), C.byref(w),
                                C.byref(s), None, None, None, None, 0)
    if rc < 0:
        raise RuntimeError("qrybatch decode failed (truncated)")
    N, W, S = int(n.value), int(w.value), int(s.value)
    startts = np.empty(N, np.int64)
    keys = np.empty((N, W), np.int32)
    types = np.empty((N, W), np.int8)
    scalars = np.empty((N, S), np.int32)
    rc = lib.dt_qrybatch_decode(
        buf, len(buf), C.byref(n), C.byref(w), C.byref(s),
        startts.ctypes.data_as(C.c_void_p), keys.ctypes.data_as(C.c_void_p),
        types.ctypes.data_as(C.c_void_p),
        scalars.ctypes.data_as(C.c_void_p), N * W)
    if rc < 0:
        raise RuntimeError("qrybatch decode failed")
    return startts, keys, types, scalars


_QB_HDR = struct.Struct("<III")


def decode_qrybatch_into(buf: bytes, offset: int, startts: np.ndarray,
                         keys: np.ndarray, types: np.ndarray,
                         scalars: np.ndarray) -> int:
    """Decode wire bytes (starting at ``offset`` into ``buf``) DIRECTLY
    into caller-provided C-contiguous row views — the zero-copy feed
    assembly path: a peer's contribution lands straight in the stacked
    device-feed slice instead of round-tripping through fresh arrays
    plus a copy.  The views' leading dimension is the capacity; rows
    past the decoded count are left untouched.  Returns n decoded.

    The header is parsed here (the shape checks below MUST precede the
    native write — the C side only caps the keys array), so the decode
    is a single native call."""
    lib = _load()
    if len(buf) - offset < _QB_HDR.size:
        raise RuntimeError("qrybatch decode failed (truncated)")
    N, W, S = _QB_HDR.unpack_from(buf, offset)
    need = 12 + N * 8 + N * W * 4 + N * W + N * S * 4
    if len(buf) - offset < need:
        raise RuntimeError("qrybatch decode failed (truncated)")
    for arr, want_minor, name in ((startts, 1, "startts"), (keys, W, "keys"),
                                  (types, W, "types"),
                                  (scalars, S, "scalars")):
        minor = arr.shape[1] if arr.ndim == 2 else 1
        if not arr.flags.c_contiguous or len(arr) < N \
                or (want_minor and minor != want_minor):
            raise ValueError(
                f"decode_into target {name}: need C-contiguous "
                f"[>= {N}, {want_minor}], got {arr.shape}")
    base = C.cast(C.c_char_p(buf), C.c_void_p).value or 0
    ai = startts.__array_interface__["data"][0]
    rc = lib.dt_qrybatch_decode(
        C.c_void_p(base + offset), len(buf) - offset, None, None, None,
        C.c_void_p(ai),
        C.c_void_p(keys.__array_interface__["data"][0]),
        C.c_void_p(types.__array_interface__["data"][0]),
        C.c_void_p(scalars.__array_interface__["data"][0]) if S else None,
        N * W)
    if rc < 0:
        raise RuntimeError("qrybatch decode failed")
    return N
